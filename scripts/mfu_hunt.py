#!/usr/bin/env python
"""On-chip MFU hunt — thin wrapper over the compute tuner's probes.

The dependent-chain MXU peak probe and the flash tile/layout/backward
sweep moved in-library (`kungfu_tpu/tuner/measure.py`, PR 10).  This
script keeps the historical CLI and the `HUNT:` JSON-line contract:

    python scripts/mfu_hunt.py [peak|flash|all]   (default all)

Unknown probe names exit nonzero so an unattended queue retries/surfaces
the typo instead of recording a silent no-op success.
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv) -> int:
    which = argv[1] if len(argv) > 1 else "all"
    if which not in ("peak", "flash", "all"):
        print(f"# mfu_hunt: unknown probe {which!r} "
              "(expected peak|flash|all)", file=sys.stderr)
        return 2
    from kungfu_tpu.tuner.__main__ import main as tuner_main

    return tuner_main(["--probe", which])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
