"""CPU, tiny sizes. Run with ``python -m pytest benchmarks/tests``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

# float32 products at full precision, as the references compute them
jax.config.update("jax_default_matmul_precision", "highest")
