"""Test configuration: the CPU backend with 8 virtual devices, so the
multi-device sharding tests run anywhere. Both come from the environment
(``JAX_PLATFORMS``, ``XLA_FLAGS``), set here before jax is imported, so the
child processes tests start inherit them.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# The suite is compile-bound (hundreds of distinct jit programs): a
# persistent compilation cache makes repeat runs hit compiled artifacts
# instead of XLA. Opt out with JAX_TEST_NO_COMPILE_CACHE=1.
if not os.environ.get("JAX_TEST_NO_COMPILE_CACHE"):
    from perceiver_io_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache(default_dir=os.path.join(os.path.dirname(__file__), ".jax_cache"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from perceiver_io_tpu import _startup  # noqa: E402

# The first Tracer of a process that has a sink writes the start-up record into its stream (obs/startup.py): in a
# worker that would be whichever test comes first, so a test that counts a stream's rows would depend on the order.
# Here the record counts as taken; tests/test_obs_startup.py hands it out again where it tests the hand-over.
_startup.RECORD.handed = True


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()
