"""The benchmark's own checks, run on the CPU:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``. They are not part of
the repository's tier-1 suite (``pytest.ini`` collects ``tests/``)."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
