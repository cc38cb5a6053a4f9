"""Test harness: force an 8-device CPU JAX backend.

SURVEY.md §4 takeaway (c): all collective/parallel tests run on virtual CPU
devices — real multi-device SPMD semantics without TPU hardware. The
8-device CPU backend is selected before anything initializes a backend.
"""
import os

# Persistent XLA compilation cache: repeated suite runs skip recompiles.
# The caller's JAX_COMPILATION_CACHE_DIR if set, else the checkout's
# .jax_cache — jax reads the variable when it is imported.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"))

import jax  # noqa: E402
from jax._src import xla_bridge as _xb  # noqa: E402

if not _xb.backends_are_initialized():
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
elif jax.default_backend() != "cpu":
    raise RuntimeError(
        "JAX backend initialized before conftest; run pytest with "
        "JAX_PLATFORMS=cpu")

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from tier-1 "
                   "(-m 'not slow'); covered by dedicated gates")
