"""Test configuration: run JAX on CPU with an 8-device virtual mesh.

≙ SURVEY.md §4.7: instead of the reference's multiprocessing cluster hacks,
multi-chip semantics are tested on one host via XLA's forced host platform
device count — real SPMD partitioning, no hardware needed.
"""

import os

# Force CPU regardless of the session's JAX_PLATFORMS (e.g. a live TPU):
# tests need determinism, fp32 matmuls, and the 8-device virtual mesh.
os.environ["JAX_PLATFORMS"] = "cpu"

# Static program verification (analysis/verifier.py) is opt-in at large
# (PT_VERIFY=1) but DEFAULT-ON under test: every program a test compiles
# is verified first, so an IR defect fails as a named diagnostic here
# instead of a cryptic trace error on hardware.
os.environ.setdefault("PT_VERIFY", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# also through the config, in case something imported jax before this file
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + a fresh global scope."""
    import paddle_tpu as pt
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.core import scope as scope_mod

    prev_main = prog_mod.switch_main_program(pt.Program())
    prev_startup = prog_mod.switch_startup_program(pt.Program())
    prev_stack = scope_mod._scope_stack[:]
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    prog_mod.reset_unique_names()
    yield
    prog_mod.switch_main_program(prev_main)
    prog_mod.switch_startup_program(prev_startup)
    scope_mod._scope_stack[:] = prev_stack


@pytest.fixture
def rng():
    return np.random.RandomState(1234)
