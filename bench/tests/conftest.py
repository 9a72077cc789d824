"""The benchmark's own tests, on the CPU with Pallas interpreted:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiled programs of the tests stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
