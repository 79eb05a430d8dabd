"""Peaks by ``device_kind`` and the least work each battery kernel needs.

A kernel's roofline time is the larger of its operations over the peak
rate and its bytes over the peak bandwidth.  Operations and bytes are
what the algorithm needs from its shapes (each operand read once, each
result written once), not what the compiler emitted.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Mapping, Optional, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"

_BYTES = {"float32": 4, "bfloat16": 2}


def peaks(kind: str, path: Path = PEAKS) -> Dict[str, float]:
    """The peak table row of one ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)} (bench/peaks.json)")
    return table[kind]


def work(name: str, tags: Mapping[str, object]
         ) -> Optional[Tuple[float, float]]:
    """(operations, bytes) one call of a battery kernel needs, from its
    tags; None for a kernel this table does not know."""
    dt = _BYTES.get(str(tags.get("dtype", "float32")))
    if dt is None:
        return None
    if name.startswith("matmul_sq_"):
        n = int(tags["n"])
        return 2.0 * n ** 3, 3.0 * n * n * dt
    if name.startswith("stream_contig_"):
        n, a = int(tags["nelements"]), int(tags["n_arrays"])
        if a == 1:
            return 0.0, 0.0            # returns its input: nothing to do
        return float((a - 1) * n), float((a + 1) * n * dt)
    if name.startswith("empty_"):
        return 0.0, 0.0                # the identity
    return None


def seconds(flops: float, nbytes: float, peak: Mapping[str, float]
            ) -> Tuple[float, str]:
    """Roofline seconds and which bound sets them."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
