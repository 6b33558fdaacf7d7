"""The benchmark's own CPU tests: ``pytest bench``.

They import the harness's modules as ``run.py`` does, with ``bench/`` and
the program's ``src/`` on the path.
"""

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
