"""``python -m repro.serve`` — alias for the serving-daemon CLI.

The implementation lives in :mod:`repro.serving.cli`; this module only
provides the memorable entry point.
"""
import sys
from typing import List, Optional

from repro import compile_cache
from repro.serving.cli import main as _cli_main


def main(argv: Optional[List[str]] = None) -> int:
    compile_cache.enable()
    return _cli_main(argv)


if __name__ == "__main__":
    sys.exit(main())
