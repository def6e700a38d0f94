"""Test harness config.

Pin an 8-virtual-device CPU platform before any jax *backend* initializes,
so topology-masked collectives and the tpu backend's mesh sharding run
without an accelerator (SURVEY.md §4 test plan item (c)).  The chip is
reached only through ``chip_smoke.py`` (README "Install / run").
"""

import os

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# The suite counts compiles and reads compiled text; it never wants a
# persistent-cache hit, and it must not litter the checkout's cache
# (factories.apply_compilation_cache).
jax.config.update("jax_enable_compilation_cache", False)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

assert jax.default_backend() == "cpu", (
    "a non-CPU jax backend initialized before tests/conftest.py could pin "
    "the platform — the suite runs on the CPU"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
