"""The benchmark's own tests (outside tier-1's ``tests/``): run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# small CPU datasets must not pay 32768-row-padded wave matmuls
os.environ.setdefault("LGBM_TPU_CHUNK", "8192")
