"""``python -m repro.tune`` — predictor-guided autotuning entry point.

Thin shim over :mod:`repro.tuning.cli`; see that module (or ``--help``)
for the flag reference.  The search library itself is
:mod:`repro.tuning`.
"""
import sys
from typing import List, Optional

from repro import compile_cache
from repro.tuning.cli import main as _cli_main


def main(argv: Optional[List[str]] = None) -> int:
    compile_cache.enable()
    return _cli_main(argv)


if __name__ == "__main__":
    sys.exit(main())
