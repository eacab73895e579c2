"""Test configuration: fake 8-device CPU mesh.

The reference's only "distributed without a cluster" mechanism was Spark
``local[N]`` (SURVEY.md §4). The TPU analogue is XLA's forced host platform
device count: 8 fake CPU devices give every trainer's collective path a real
mesh in CI, no TPU required. Must be set before JAX is imported.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("KERAS_BACKEND", "jax")

# The suite runs on the CPU backend whatever the outer environment selects:
# the driver and CI set JAX_PLATFORMS=cpu, and a bare `pytest` on a machine
# with a chip must neither take the chip nor compile the suite for it.
import jax

jax.config.update("jax_platforms", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# NO persistent compile cache for the test suite. tests/test_tpu_compile.py
# compiles for a DESCRIBED v5e that is not attached: such an executable is
# written to the cache but cannot be read back without the chip, so every
# later run would warn and compile again. And a compile the suite asserts
# on (a kernel the chip's compiler must accept) has to happen in THIS run,
# not be answered from an earlier tree's cache. Cold compiles fit the
# tier-1 budget.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
