"""``python -m repro.calibrate`` — machine-calibration entry point.

Thin shim over :mod:`repro.profiles.cli`; see that module (or ``--help``)
for the flag reference.  Not to be confused with :mod:`repro.core.calibrate`
(the Levenberg-Marquardt fitting engine), which this CLI drives.
"""
import sys
from typing import List, Optional

from repro import compile_cache
from repro.profiles.cli import main as _cli_main


def main(argv: Optional[List[str]] = None) -> int:
    compile_cache.enable()
    return _cli_main(argv)


if __name__ == "__main__":
    sys.exit(main())
