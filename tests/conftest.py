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

# ``tests/benchmark/test_bench_added.py`` was PR 30's dry run of the PR that
# adds the benchmark's first token-model configuration: it lays
# ``reference/rule_fedavg.py`` and ``roofline/fedavg.py`` into a copy of
# ``benchmark/`` (asserting they are not there yet) and counts exactly one
# configuration and one cell in the tree it copies.  PR 34 is that PR: the
# files now exist and the benchmark has two of each, so the dry run cannot
# pass on any tree that holds the configuration it rehearsed.  A PR may not
# edit or delete a benchmark file, so it stays as it is, uncollected; what
# it covered is held on the real files by
# ``tests/benchmark/test_bench_moonlight.py`` and ``test_bench_files.py``
# (CHANGES.md, PR 34: a ``benchmark`` PR should delete or rewrite it).
collect_ignore = ["benchmark/test_bench_added.py"]


@pytest.fixture
def rng():
    return np.random.default_rng(0)
