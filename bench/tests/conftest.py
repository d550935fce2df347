"""Tests of the benchmark itself (not of the program). Run them with

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

from the repository root; the repository's own ``pytest.ini`` collects
only ``tests/``.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
