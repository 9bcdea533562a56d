"""The benchmark's own tests (outside the repo's tier-1 ``testpaths``).

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""

import os
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# CPU compilations stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-cache-"))

TINY = BENCH / "tests" / "data" / "tiny"
