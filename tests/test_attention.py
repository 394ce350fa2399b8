"""Attention subsystem: flash kernel, ring/Ulysses SP, transformer LM.

The reference has no attention op; these tests cover the TPU-native
extension (SURVEY.md §5 long-context plan): kernel numerics vs the XLA
reference, sequence parallelism vs single-device attention on the 8-device
virtual mesh, and end-to-end transformer training.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                mha_reference)
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.ring import ring_attention, ulysses_attention
from paddle_tpu.parallel.parallel_executor import ParallelExecutor


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(rng, causal):
    b, s, h, d = 2, 128, 2, 32
    q, k, v = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
               for _ in range(3)]
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_flash_kernel_grads(rng):
    b, s, h, d = 1, 64, 2, 16
    q, k, v = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
               for _ in range(3)]

    def f(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True,
                                        interpret=True, block_q=32,
                                        block_k=32) ** 2)

    def r(q, k, v):
        return jnp.mean(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [False, True])
def test_sequence_parallel_matches_full(rng, mode, causal):
    mesh = make_mesh({"sp": 8})
    b, s, h, d = 2, 64, 8, 16
    q, k, v = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
               for _ in range(3)]
    spec = P(None, "sp", None, None)
    inner = ring_attention if mode == "ring" else ulysses_attention
    from paddle_tpu.core.compat import shard_map
    f = jax.jit(shard_map(
        lambda q, k, v: inner(q, k, v, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_sdpa_op_single_chip(rng):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [16, 4, 8])
        k = layers.data("k", [16, 4, 8])
        v = layers.data("v", [16, 4, 8])
        out = layers.fused_attention(q, k, v, causal=True)
    exe = pt.Executor()
    exe.run(startup)
    qs, ks, vs = [rng.randn(2, 16, 4, 8).astype(np.float32)
                  for _ in range(3)]
    (res,) = exe.run(main, feed={"q": qs, "k": ks, "v": vs},
                     fetch_list=[out])
    ref = mha_reference(jnp.asarray(qs), jnp.asarray(ks), jnp.asarray(vs),
                        causal=True)
    np.testing.assert_allclose(res, np.asarray(ref), atol=1e-5, rtol=1e-5)


def _train_transformer(mesh, sp_mode, tp_shard, steps=4, seed=7):
    from paddle_tpu.models.transformer import transformer_lm_loss
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 1
    with pt.program_guard(main, startup):
        avg, _ = transformer_lm_loss(vocab_size=64, seq_len=32, n_layers=2,
                                     d_model=32, n_heads=4, d_ff=64,
                                     sp_mode=sp_mode, tp_shard=tp_shard)
        opt = pt.optimizer.AdamOptimizer(learning_rate=1e-3)
        opt.minimize(avg)

    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        rs = np.random.RandomState(seed)
        losses = []
        if mesh is None:
            runner = lambda feed: exe.run(main, feed=feed, fetch_list=[avg])
        else:
            pe = ParallelExecutor(loss_name=avg.name, main_program=main,
                                  mesh=mesh, scope=scope)
            runner = lambda feed: pe.run([avg], feed=feed)
        for i in range(steps):
            ids = rs.randint(0, 64, (8, 32)).astype(np.int64)
            tgt = np.roll(ids, -1, axis=1).reshape(8, 32, 1)
            (l,) = runner({"src_ids": ids, "tgt_ids": tgt})
            losses.append(float(np.asarray(l).ravel()[0]))
    return losses


def test_transformer_lm_trains_single_chip():
    losses = _train_transformer(None, "none", False, steps=6)
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("sp_mode", ["ring", "ulysses"])
def test_transformer_sp_matches_single(sp_mode):
    single = _train_transformer(None, "none", False)
    mesh = make_mesh({"dp": 2, "sp": 4})
    par = _train_transformer(mesh, sp_mode, False)
    np.testing.assert_allclose(single, par, atol=1e-3, rtol=1e-3)


def test_transformer_tp_sp_mesh():
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    par = _train_transformer(mesh, "ring", True)
    single = _train_transformer(None, "none", False)
    np.testing.assert_allclose(single, par, atol=1e-3, rtol=1e-3)


def test_flash_kernel_cross_length_causal(rng):
    """Bottom-right-aligned causal mask when sq != sk (decode-style)."""
    b, h, d = 1, 2, 16
    q = jnp.asarray(rng.randn(b, 32, h, d).astype(np.float32))
    k, v = [jnp.asarray(rng.randn(b, 96, h, d).astype(np.float32))
            for _ in range(2)]
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)

    def f(q, k, v):
        return jnp.mean(flash_attention(q, k, v, causal=True,
                                        interpret=True, block_q=32,
                                        block_k=32) ** 2)

    def r(q, k, v):
        return jnp.mean(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-3)


def test_sp_precondition_error():
    """Requested sp that cannot shard must error, not silently fall back."""
    mesh = make_mesh({"sp": 8})
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [12, 4, 8])   # seq 12 % 8 != 0
        k = layers.data("k", [12, 4, 8])
        v = layers.data("v", [12, 4, 8])
        out = layers.fused_attention(q, k, v, causal=True, sp_mode="ring")
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        pe = ParallelExecutor(main_program=main, mesh=mesh, scope=scope)
        feed = {n: np.zeros((2, 12, 4, 8), np.float32) for n in "qkv"}
        with pytest.raises(ValueError, match="not divisible by sp"):
            pe.run([out], feed=feed)


def test_block_defaults_divide_sequence_dims(rng):
    """The dispatch's seq-adaptive block defaults must always divide the
    sequence dims (the kernel has no ragged-block masking): seq lengths
    that are multiples of 128 but not of 512/1024 fall back to a dividing
    block, and cross-attention picks bq/bk from their own dims."""
    import importlib
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

    calls = []
    orig = fa.flash_attention

    def spy(q, k, v, **kw):
        calls.append((kw["block_q"], kw["block_k"]))
        return orig(q, k, v, **dict(kw, interpret=True))

    # force the TPU dispatch path; restore everything afterwards
    old_ok = fa._tpu_ok
    fa._tpu_ok = lambda q, k, causal=False: (
        q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0)
    fa.flash_attention, orig_fn = spy, fa.flash_attention
    try:
        for sq, sk in [(640, 640), (1024, 640), (8192, 8192), (1024, 1024)]:
            q = jnp.asarray(rng.randn(1, sq, 1, 8).astype(np.float32))
            k = jnp.asarray(rng.randn(1, sk, 1, 8).astype(np.float32))
            if sq > 2048:  # keep the 8k case cheap: check choice only
                assert fa._default_block(sq, sq, sk) == 1024
                continue
            out = fa.dot_product_attention(q, k, k)
            ref = fa.mha_reference(q, k, k)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-3, rtol=2e-3)
            bq, bk = calls[-1]
            assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
            assert not np.isnan(np.asarray(out)).any()
    finally:
        fa._tpu_ok = old_ok
        fa.flash_attention = orig_fn


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(64, 64), (128, 64)])
def test_pallas_backward_matches_reference_grads(rng, causal, blocks):
    """The Pallas dq / dkv kernels (interpret mode) against autodiff
    through mha_reference — all three input grads, both maskings."""
    import importlib
    fa_mod = importlib.import_module("paddle_tpu.kernels.flash_attention")
    if not fa_mod._HAS_PLTPU:
        pytest.skip("pallas TPU backend unavailable: the dispatch would "
                    "silently test the XLA fallback instead of the kernels")
    b, s, h, d = 1, 128, 2, 16
    q, k, v = [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
               for _ in range(3)]

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True,
                                block_q=blocks[0], block_k=blocks[1])
                ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-3, rtol=5e-3)


def test_the_sparse_walk_sweep_rehearses(tmp_path, monkeypatch, capsys):
    """`tools/sparse_walk_sweep.py --rehearse`: the sweep that fixes the
    sparse kernel's `kappa`, interpreted at a tiny size: both walks and
    their two stubs traced, the mask equal to its positions, a table at
    the end; no time under a device's name."""
    import importlib.util
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "sparse_walk_sweep.py")
    spec = importlib.util.spec_from_file_location("sparse_walk_sweep", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "sweep.jsonl"
    assert tool.main(["--rehearse", "--out", str(out)]) == 0
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    by = {}
    for line in lines:
        by.setdefault(line["what"], []).append(line)
    assert by["mask_against_positions"][0]["positions_off"] == 0
    assert max(by["max_abs_error_against_float64"][0][w]
               for w in ("rows", "pages", "both")) <= 2e-5
    assert [w["context"] for w in by["walks"]] == [24, 96]
    assert all(w["unit"] == "interpreted_s" for w in by["walks"])
    assert {"rows_without_arithmetic", "pages_without_arithmetic",
            "rows_without_copies", "pages_without_copies"} <= set(
                by["walks"][-1])
    assert set(by["cell_call"][0]) >= {"rows", "pages", "rows_empty",
                                       "pages_empty", "both"}
    assert "page walk" in capsys.readouterr().out
