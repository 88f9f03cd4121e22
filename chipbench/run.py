#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (build, weights from the seed, warm-up, compile) is timed as
``setup_s``; then the cell's traffic runs for ``--seconds``; then what the
window produced is checked against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number compared beside its
limit, also printed as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench.lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
