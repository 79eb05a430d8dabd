"""Share, in %, of the subjects' counted memory accesses (loads and stores
of whole elements, ``f_mem_*_load`` and ``f_mem_*_store``) that fall on
features no rung prices (``Prediction.unmodeled``), from the last window
profile's prices.  Moves ``profile_s``: a rung for them needs battery
kernels that exercise them."""
import re

_ACCESS = re.compile(r"^f_mem_.*_(load|store)$")


def read(ctx):
    priced = unpriced = 0.0
    for p in getattr(ctx, "preds", []):
        priced += sum(v for f, v in p.features.items() if _ACCESS.match(f))
        unpriced += sum(v for f, v in p.unmodeled.items()
                        if _ACCESS.match(f))
    total = priced + unpriced
    return 100.0 * unpriced / total if total > 0 else None
