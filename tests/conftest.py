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


@pytest.fixture
def flash_in_strips(monkeypatch):
    """`flash_in_strips(sq, sk, block, window, strips, dtype)`: the three
    flash kernels, interpreted, with the plan's `strips` held at a count
    (`_strips`, the one place the rule lives, replaced for the test):
    forward, dq, dk and dv against `mha_reference` and `jax.grad` of it on
    the same inputs in float32. Returns the call's plan."""
    import jax.numpy as jnp
    from paddle_tpu.kernels import flash_attention as fa

    def check(sq, sk, block, window, strips, dtype):
        monkeypatch.setattr(fa, "_strips", lambda *seen: strips)
        fa._flash_fwd.clear_cache()     # the jitted wrappers keep traces
        fa._flash_bwd_pallas.clear_cache()
        dtype, rng = jnp.dtype(dtype), np.random.RandomState(sq + strips)
        q = jnp.asarray(rng.randn(1, sq, 1, 32), dtype)
        k = jnp.asarray(rng.randn(1, sk, 1, 32), dtype)
        v = jnp.asarray(rng.randn(1, sk, 1, 32), dtype)
        w = jnp.asarray(rng.randn(1, sq, 1, 32), jnp.float32)
        flash = lambda *a: fa.flash_attention(
            *a, causal=True, block_q=block, block_k=block, interpret=True,
            window=window)
        ref = lambda *a: fa.mha_reference(*a, causal=True, window=window)
        loss = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w)
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        got = (flash(q, k, v),) + jax.grad(loss(flash), (0, 1, 2))(q, k, v)
        want = (ref(*f32),) + jax.grad(loss(ref), (0, 1, 2))(*f32)
        fa._flash_fwd.clear_cache()
        fa._flash_bwd_pallas.clear_cache()
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            b = np.asarray(b, np.float32)
            off = np.asarray(a, np.float32) - b
            rms = np.sqrt(np.mean(b * b))
            if dtype == jnp.bfloat16:   # (`test_flash_kernels_block_bodies`)
                assert np.sqrt(np.mean(off * off)) / rms <= 8e-3, name
                assert np.max(np.abs(off)) / rms <= 0.15, name
            else:
                assert np.max(np.abs(off)) / rms <= 5e-5, name
        return fa.flash_block_plan(sq, sk, block, block, True, dtype, window)

    return check


@pytest.fixture
def watch_steps():
    """`watch_steps(model)` patches a method onto that one DecodeModel as
    `decode_step` (three positional arguments), the way the benchmark's
    `ProgramSpans` does, and returns the list it fills, a pair a step:
    the tokens the device chose, and the host's np.argmax over the
    step's logits as `np.asarray` of the result gives them."""

    def watch(model):
        seen = []
        inner = model.decode_step

        def traced_step(token_ids, context_lens, block_tables):
            result = inner(token_ids, context_lens, block_tables)
            seen.append((result.tokens,
                         np.argmax(np.asarray(result), axis=-1)))
            return result

        model.decode_step = traced_step
        return seen

    return watch


@pytest.fixture
def served_and_watched(watch_steps):
    """`served_and_watched(bundle_dir, vocab, slots)` serves a few
    requests from the bundle unwatched (serving itself moves 4 bytes a
    slot a step and never asks for the logits), then a few watched
    (every step's ids from the device are the host's np.argmax over
    `np.asarray` of the same step's result). Returns the model."""
    from paddle_tpu.serving import ServingEngine

    def run(bundle_dir, vocab, slots):
        engine = ServingEngine()
        engine.load_decode_model("lm", bundle_dir, warmup=False,
                                 max_new_tokens=8)
        dec = engine.decode_engine("lm")
        rng = np.random.RandomState(12)

        def generate(shapes):
            handles = [engine.generate(
                "lm", [int(t) for t in rng.randint(0, vocab, n)],
                max_new_tokens=m) for n, m in shapes]
            for h in handles:
                h.result(timeout=300)
            return dec.metrics_snapshot()

        try:
            quiet = generate(((3, 5), (9, 7)))
            assert quiet["logits_fetches"] == 0
            assert quiet["step_host_bytes"] \
                == 4 * slots * quiet["decode_steps"] > 0
            seen = watch_steps(dec.model)
            snap = generate(((5, 6), (2, 4), (7, 8)))
        finally:
            engine.shutdown(drain=False)
        assert len(seen) == snap["decode_steps"] - quiet["decode_steps"] > 0
        assert snap["logits_fetches"] == len(seen)   # the watcher's asking
        for tokens, host_argmax in seen:
            assert tokens.dtype == np.int32 and tokens.shape == (slots,)
            assert np.array_equal(tokens, host_argmax)
        return dec.model

    return run
