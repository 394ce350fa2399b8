"""Mellum 2's block THROUGH THE TRAINER (a 3 : 1 period of window and full
grouped-query layers whose rotary tables differ by layer kind, plain RoPE
and YaRN, and one chip's share of a softmax-routed top-k expert layer in
every layer) against the plain reference `benchmark/reference_mellum2.py`,
loaded by path (it lives once and imports nothing of `paddle_tpu`): loss,
logits and EVERY gradient against `jax.grad` of the reference, the
windowed flash backward against the masked dense form, the shares'
gradients against the uncut layer's, the YaRN table against its closed
form, the `pt_train_moe_*` counts, and one prefill-then-decode pass of a
bundle that carries the rotary fields.

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. Interpret-mode Pallas only at a few blocks.
"""

import hashlib
import importlib
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.core.registry import ExecContext, require_op
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.models import transformer as tfm
from paddle_tpu.obs.metrics import TrainMetrics, render_prometheus
from paddle_tpu.ops.attention_ops import rope_rotate, rope_table
from paddle_tpu.serving.decode import DecodeModel

from references import by_path

moe_ops = importlib.import_module("paddle_tpu.ops.moe_ops")
ref = by_path("reference_mellum2")

V, L, DM, NH, NKV, HD, FF, E, TOP_K = 61, 4, 32, 4, 2, 16, 24, 8, 3
FIRST, HELD = 2, 4                       # experts 2..5 of 8
WINDOW, SEQ, BATCH = 5, 12, 2            # a window shorter than the sequence
EPS, THETA = 1e-6, 500000.0
#: (factor, original context, beta_fast, beta_slow, attention factor) at
#: which a head of 16 has its ramp INSIDE its eight pairs (low 2, high 6)
YARN = (16.0, 64.0, 4.0, 0.25, 1.2772588722239782)
PATTERN = ("window", "window", "window", "full")
KINDS = tuple("sliding_attention" if k == "window" else "full_attention"
              for k in PATTERN)


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, bias=False, attention="gqa",
                n_kv_heads=NKV, head_dim=HD, ffn="moe_gated",
                num_experts=E, experts_per_tok=TOP_K, router="softmax",
                norm_topk=True, experts_first=FIRST, experts_held=HELD,
                window=WINDOW, layer_pattern=PATTERN,
                full_rope_theta=THETA, full_rope_scaling=YARN)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(NH, NKV, HD, WINDOW, KINDS, TOP_K, FIRST, EPS, THETA, THETA,
               YARN)

LAYER_NAME = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
              "q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w", "router": "moe{i}_router_w",
              "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
              "down": "moe{i}_down_w"}
MODEL_NAME = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale",
              "head": "lm_head_w"}


def reference_weights(get, n_layers=L):
    out = {key: get(name) for key, name in MODEL_NAME.items()}
    out["layers"] = [{key: get(name.format(i=i))
                      for key, name in LAYER_NAME.items()}
                     for i in range(n_layers)]
    return out


def randomise(scope, seed):
    """Seeded weights with gains away from 1, a router spread wide enough
    that top-k choices are not near ties, and heads sharp enough that
    which rows are read decides the output."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif "router" in name:
            new = rng.randn(*v.shape)
        elif name.endswith(("_q_w", "_k_w")):
            new = rng.randn(*v.shape) * (1.5 / np.sqrt(v.shape[-2]))
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def _batch(seed=5):
    draw = np.random.RandomState(seed).randint(0, V, (BATCH, SEQ + 1))
    return draw[:, :-1], draw[:, 1:]


# ---------------------------------------------------------------------------
# (a) the period through the trainer: loss, logits, every gradient
# ---------------------------------------------------------------------------

def _trained(block):
    """(loss, logits, {parameter: gradient}, the step's expert counts, the
    reference's weights) of one step of the training program."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    load = []
    with pt.program_guard(main, startup):
        avg, logits = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=SEQ, n_layers=L, d_model=DM, n_heads=NH,
            d_ff=FF, max_len=SEQ, block=block, collect_moe_load=load)
        grads = pt.backward.append_backward(avg)
    ids, tgt = _batch()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 6)
        weights = reference_weights(
            lambda n: np.asarray(scope.find_var(n)))
        by_name = {p.name: g for p, g in grads}
        got = exe.run(main, feed={"src_ids": ids, "tgt_ids": tgt[..., None]},
                      fetch_list=[avg, logits, load[0]]
                      + list(by_name.values()))
    return (float(np.ravel(got[0])[0]), np.asarray(got[1]),
            dict(zip(by_name, got[3:])), np.asarray(got[2]), weights)


@pytest.fixture(scope="module")
def trained():
    return _trained(block_of())


def test_training_step_matches_reference_loss_logits_and_gradients(trained):
    got_loss, got_logits, got_grads, load, weights = trained
    ids, tgt = _batch()
    want_loss, want = jax.value_and_grad(
        lambda w: ref.mean_loss(w, ids, tgt, HP))(weights)
    # float32 sums in another order (measured 2e-7 relative)
    assert abs(got_loss - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    for b in range(BATCH):
        want_logits = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got_logits[b] - want_logits)) \
            <= 2e-5 * np.std(want_logits)

    def check(program_name, want_grad):
        g = np.asarray(got_grads[program_name])
        w = np.asarray(want_grad)
        # per parameter, against the gradient's own largest entry: 2e-5
        # is ten times what float32 accumulation through four layers and
        # a softmax gives; a band one row off, the plain table on the
        # full layer or a pair dropped in the backward is of order 1e-2
        # and more (the faults below)
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-9, \
            program_name

    for key, name in MODEL_NAME.items():
        check(name, want[key])
    for i in range(L):
        for key, name in LAYER_NAME.items():
            check(name.format(i=i), want["layers"][i][key])
    assert len(got_grads) == len(MODEL_NAME) + L * len(LAYER_NAME)
    # the counts the step fetched beside its loss, against the
    # reference's own count of its routes
    _, counts, _, _ = ref.loss_and_counts(weights, ids, tgt, HP, HELD)
    assert tuple(int(v) for v in load) == counts
    assert counts[0] == BATCH * SEQ * TOP_K * L


@pytest.mark.parametrize("fault", [dict(window_off=1), dict(window_off=-1),
                                   dict(plain_full=True), dict(drop=True)],
                         ids=str)
def test_the_gradients_see_what_a_loss_near_its_start_cannot(fault, trained):
    """Each fault of the reference moves some gradient by far more than
    the comparison's 2e-5: the check above would catch the program doing
    the same."""
    _, _, got_grads, _, weights = trained
    ids, tgt = _batch()
    wrong = jax.grad(lambda w: ref.mean_loss(
        w, ids, tgt, HP._replace(**fault)))(weights)
    worst = max(
        float(np.max(np.abs(np.asarray(got_grads[name.format(i=i)])
                            - np.asarray(wrong["layers"][i][key])))
              / np.max(np.abs(np.asarray(wrong["layers"][i][key]))))
        for i in range(L) for key, name in LAYER_NAME.items())
    assert worst >= 1e-3, worst


def test_the_trainer_trains_the_period_on_run_loop_under_amp():
    """The normal path: transformer_lm_loss -> Adam -> run_loop, bf16 AMP;
    the loss falls, and the expert counts come back with the losses."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    load = []
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=SEQ, n_layers=L, d_model=DM, n_heads=NH,
            d_ff=FF, max_len=SEQ, block=block_of(), collect_moe_load=load)
        pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(avg)
    main.amp_dtype = "bfloat16"
    ids, tgt = _batch()
    steps = 6
    feed = {"src_ids": np.stack([ids] * steps),
            "tgt_ids": np.stack([tgt[..., None]] * steps)}
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        losses, counts = exe.run_loop(main, feed=feed,
                                      fetch_list=[avg, load[0]],
                                      n_steps=steps, per_step_feeds=True)
    losses = np.ravel(losses)
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    counts = np.asarray(counts).reshape(steps, 4)
    assert np.all(counts[:, 0] == BATCH * SEQ * TOP_K * L)
    assert np.all(counts[:, 1] <= counts[:, 0])
    metrics = TrainMetrics("mellum")
    metrics.observe_moe(counts)
    snap = metrics.snapshot()
    assert snap["moe_steps"] == steps
    assert snap["moe_routed_pairs"] == int(counts[:, 0].sum())
    assert snap["moe_held_pairs"] == int(counts[:, 1].sum())
    text = render_prometheus({"train": {"mellum": snap}})
    for key in ("moe_steps", "moe_routed_pairs", "moe_held_pairs",
                "moe_held_touched", "moe_largest_rows"):
        assert f'pt_train_{key}_total{{trainer="mellum"}}' in text
    bare = render_prometheus({"train": {"t": TrainMetrics("t").snapshot()}})
    assert "pt_train_moe" not in bare


# ---------------------------------------------------------------------------
# (b) the windowed flash backward against the masked dense form
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,block,window,heads,kv_heads", [
    (512, 512, 128, 100, 1, 1),      # a window under a block
    (512, 512, 128, 128, 8, 1),      # at a block, groups of 8
    (512, 512, 128, 200, 2, 2),      # over a block
    (256, 512, 128, 128, 1, 1),      # a chunk of query rows (q_off 256)
    (1024, 1024, 256, 256, 1, 1),    # diagonal and edge blocks in strips
])
def test_windowed_flash_backward_matches_the_masked_dense_form(
        sq, sk, block, window, heads, kv_heads):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, sq, heads, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, sk, kv_heads, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, sk, kv_heads, 128), jnp.float32)
    w = jnp.asarray(rng.randn(1, sq, heads, 128), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(w * fa.flash_attention(
        *a, causal=True, block_q=block, block_k=block, interpret=True,
        window=window)), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * fa.mha_reference(
        *a, causal=True, window=window)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        # float32 sums in another order (measured 4e-6 at most)
        assert np.max(np.abs(np.asarray(g - r))) <= 2e-5
    plan = fa.flash_block_plan(sq, sk, block, block, True, jnp.float32,
                               window)
    assert plan.skipped + plan.diagonal + plan.full + plan.edge \
        == plan.n_q * plan.n_k
    # dk/dv's walk is dq's transposed: column ik runs `_first_q` ..
    # `_last_q`, exactly the q-blocks whose row of the grid runs ik
    for ik in range(plan.n_k):
        runs = [iq for iq in range(plan.n_q)
                if int(fa._first_k(iq, plan)) <= ik
                <= int(fa._last_k(iq, plan))
                and fa._block_runs(fa._ahead(iq, ik, block, block,
                                             plan.q_off), block)
                and fa._block_in_window(fa._ahead(iq, ik, block, block,
                                                  plan.q_off), block,
                                        window)]
        if runs:
            assert (int(fa._first_q(ik, plan)), int(fa._last_q(ik, plan))) \
                == (runs[0], runs[-1])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("strips", [1, 2, 4, 8])
def test_a_window_of_one_block_runs_in_strips(flash_in_strips, strips,
                                              dtype):
    """The cell's band, a window of exactly one block: a row's band is
    the edge block (strip i against the keys from its own first one on,
    under the window's mask alone) and the diagonal block (against the
    keys up to its own last one); the grid's axes are the band's two
    blocks and nothing behind it."""
    block = 128 * strips
    plan = flash_in_strips(3 * block, 3 * block, block, block, strips,
                           dtype)
    assert (plan.strips, plan.edge_strips) == (strips, strips)
    assert (plan.diagonal, plan.edge, plan.full, plan.behind) == (3, 2, 0, 1)
    assert (plan.band_k, plan.band_q) == (2, 2)
    assert plan.blocks_run == 5 * (strips + 1) / (2 * strips)
    assert round(plan.blocks_inside, 2) == 2.5


def test_a_windowed_calls_grid_is_its_bands():
    """At the cell's call (32 heads of 8,192 rows, blocks of 1,024,
    window 1,024) the forward's and dq's k axis is the band's two blocks,
    dk/dv's q axis likewise: 16 steps a head for the matrix's 64, one of
    them empty (the first row's band is one block); and the plan counts
    what the kernels run (15 crossed blocks in strips) beside what lies
    inside the band."""
    of = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kw = dict(scale=0.125, causal=True, block_q=1024, block_k=1024,
              window=1024)

    def grids(jaxpr):
        return [tuple(e.params["grid_mapping"].grid)
                for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                if e.primitive.name == "pallas_call"]

    qkv = [of(32, 8192, 128)] * 3
    assert grids(jax.make_jaxpr(lambda *a: fa._flash_fwd(*a, **kw))(
        *qkv)) == [(32, 8, 2)]
    assert grids(jax.make_jaxpr(lambda *a: fa._flash_bwd_pallas(*a, **kw))(
        *qkv, of(32, 8192, 128),
        jax.ShapeDtypeStruct((32, 8192, 1), jnp.float32),
        of(32, 8192, 128))) == [(32, 8, 2), (32, 8, 2)]
    del kw["window"]
    assert grids(jax.make_jaxpr(lambda *a: fa._flash_fwd(*a, **kw))(
        *qkv)) == [(32, 8, 8)]
    # the forward in halves, the backward in strips of a lane tile's rows
    # (`_strips`): 11.25 and 8.4 blocks' products for the parent's 13
    for backward, strips, run in ((False, 2, 11.25), (True, 8, 8.4375)):
        plan = fa.flash_block_plan(8192, 8192, 1024, 1024, True,
                                   jnp.bfloat16, 1024, backward=backward)
        assert (plan.band_k, plan.band_q, plan.diagonal, plan.edge) \
            == (2, 2, 8, 7)
        assert (plan.strips, plan.edge_strips) == (strips, strips)
        assert plan.blocks_run == run
        assert round(plan.blocks_inside, 2) == 7.5
    # the walk a step stands for: from the row's first block, and past a
    # short row's band the block already resident
    assert [int(fa._needed_k(plan, iq, step)) for iq in (0, 1, 7)
            for step in (0, 1)] == [0, 0, 0, 1, 6, 7]


def _traced_backward(kernels, call, **kw):
    """The text of the backward wrapper's jaxpr at one call's shapes (the
    kernels' bodies, grids and blocks) and of every operand's
    `index_map`."""
    bh, sq, sk, d, dtype = call
    of = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    args = [of(bh, sq, d), of(bh, sk, d), of(bh, sk, d), of(bh, sq, d),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32), of(bh, sq, d)]
    jaxpr = jax.make_jaxpr(lambda *a: kernels._flash_bwd_pallas(
        *a, scale=0.125, causal=True, block_q=kernels._default_block(sq),
        block_k=kernels._default_block(sk), **kw))(*args)
    text = [str(jaxpr)]
    for eqn in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns:
        if eqn.primitive.name == "pallas_call":
            text += [str(m.index_map_jaxpr)
                     for m in eqn.params["grid_mapping"].block_mappings]
    return "\n".join(text)


#: sha256 of `_traced_backward` of the kernel file as it was before the
#: backward took a window (commit 4a877cd): what a call without one must
#: still trace, to the letter. The train cell's call first: PR 64 read
#: it anew, on purpose (a bfloat16 backward at blocks of 1,024 cuts its
#: diagonal blocks in eight strips where it cut halves: `_strips`, read
#: on the chip); the float32 calls trace what they traced.
_BACKWARD_AS_IT_WAS = {
    (64, 2048, 2048, 128, "bfloat16"): "4b702eb65734cbfb",
    (16, 1024, 1024, 128, "float32"): "e14a9839d863f8cd",
    (8, 512, 1024, 128, "float32"): "b96c8ce581894d2a",
}


@pytest.mark.parametrize("call", sorted(_BACKWARD_AS_IT_WAS), ids=str)
def test_without_a_window_the_traced_backward_is_what_it_was(call):
    text = _traced_backward(fa, call)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _BACKWARD_AS_IT_WAS[call]
    # and a window changes it: the band is in the plan
    assert _traced_backward(fa, call, window=256) != text


def test_the_trace_tells_a_windowed_call_from_a_full_one():
    assert fa._scope_of(None) == "scaled_dot_product_attention"
    assert fa._scope_of(None, True) \
        == "transpose_scaled_dot_product_attention"
    names = {fa._scope_of(w, t) for w in (None, 1024) for t in (0, 1)}
    assert len(names) == 4
    assert not any("scaled_dot_product_attention" in fa._scope_of(1024, t)
                   for t in (0, 1))


# ---------------------------------------------------------------------------
# (c) the shares: gradients add up, no pair dropped in either direction
# ---------------------------------------------------------------------------

def _share_inputs(n=24, d=16, h=24, seed=3):
    rng = np.random.RandomState(seed)
    xt = jnp.asarray(rng.randn(n, d), jnp.float32)
    experts = jnp.asarray(np.stack(
        [rng.permutation(E)[:TOP_K] for _ in range(n)]), jnp.int32)
    gates = jnp.asarray(rng.rand(n, TOP_K), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(E, d, h) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(E, h, d) * 0.3, jnp.float32)
    w = jnp.asarray(rng.randn(n, d), jnp.float32)
    return xt, experts, gates, wg, wu, wd, w


def test_the_shares_gradients_add_up_to_the_uncut_layers():
    """Four shares of two experts each: their gradients of x and of the
    gates add up to the uncut layer's, and each share's weight gradients
    are the uncut layer's rows of its own experts."""
    xt, experts, gates, wg, wu, wd, w = _share_inputs()

    def whole(xt, gates, wg, wu, wd):
        return jnp.sum(w * moe_ops._experts_sorted(xt, experts, gates, wg,
                                                   wu, wd))

    want = jax.grad(whole, (0, 1, 2, 3, 4))(xt, gates, wg, wu, wd)
    dx, dg = 0.0, 0.0
    for first in range(0, E, 2):
        mine = slice(first, first + 2)

        def share(xt, gates, wg, wu, wd):
            return jnp.sum(w * moe_ops._experts_held_trained(
                xt, experts, gates, wg, wu, wd, first, E)[0])

        got = jax.grad(share, (0, 1, 2, 3, 4))(xt, gates, wg[mine],
                                               wu[mine], wd[mine])
        dx, dg = dx + got[0], dg + got[1]
        for g, r in zip(got[2:], want[2:]):
            assert np.max(np.abs(np.asarray(g - r[mine]))) <= 1e-5
    assert np.max(np.abs(np.asarray(dx - want[0]))) <= 1e-5
    assert np.max(np.abs(np.asarray(dg - want[1]))) <= 1e-5


@pytest.mark.parametrize("routing", ["every", "none", "mixed"])
def test_no_pair_is_dropped_in_either_direction(routing, monkeypatch):
    """Every token on held experts (the bound that cannot bind: n x
    min(k, held) rows, several waves of the backward), none on them, and
    a mix: forward and every gradient against a dense form that loops
    over the pairs."""
    monkeypatch.setattr(moe_ops, "_HELD_WAVE_ROWS", 8)
    xt, experts, gates, wg, wu, wd, w = _share_inputs()
    first, count = 2, 4
    rng = np.random.RandomState(1)
    n = xt.shape[0]
    if routing == "every":
        experts = jnp.asarray(np.stack(
            [first + rng.permutation(count)[:TOP_K] for _ in range(n)]),
            jnp.int32)
    elif routing == "none":
        others = [e for e in range(E) if not first <= e < first + count]
        experts = jnp.asarray(np.stack(
            [rng.permutation(others)[:TOP_K] for _ in range(n)]), jnp.int32)
    mine = slice(first, first + count)

    def dense(xt, gates, wg, wu, wd):
        out = 0.0
        for j in range(TOP_K):
            e = experts[:, j] - first
            ok = (e >= 0) & (e < count)
            e = jnp.clip(e, 0, count - 1)
            hid = jax.nn.silu(jnp.einsum("nd,ndh->nh", xt, wg[e])) \
                * jnp.einsum("nd,ndh->nh", xt, wu[e])
            out = out + jnp.where(
                ok[:, None], jnp.einsum("nh,nhd->nd", hid, wd[e])
                * gates[:, j:j + 1], 0.0)
        return jnp.sum(w * out)

    def held(xt, gates, wg, wu, wd):
        return jnp.sum(w * moe_ops._experts_held_trained(
            xt, experts, gates, wg, wu, wd, first, E)[0])

    args = (xt, gates, wg[mine], wu[mine], wd[mine])
    got = jax.jit(jax.value_and_grad(held, (0, 1, 2, 3, 4)))(*args)
    want = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(*args)
    assert abs(float(got[0] - want[0])) <= 1e-4
    for g, r in zip(got[1], want[1]):
        assert np.max(np.abs(np.asarray(g - r))) <= 2e-5
    rows = moe_ops._held_grad_rows(n, TOP_K, count, E)
    assert rows < n * TOP_K       # several waves, as many as hold a pair
    # and the plain form (a server's) gives the same sums
    plain, walked = moe_ops._experts_held(xt, experts, gates, wg[mine],
                                          wu[mine], wd[mine], first)
    assert abs(float(jnp.sum(w * plain) - got[0])) <= 1e-4
    assert int(jnp.sum(walked)) == int(jnp.sum(
        (experts >= first) & (experts < first + count)))
    if routing == "none":
        assert float(got[0]) == 0.0
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in got[1])


def test_rows_of_no_group_never_reach_a_gradient(monkeypatch):
    """What a grouped matmul writes in the rows behind its groups is
    unspecified: zeros on the CPU, whatever was there on the chip, in the
    product and in its transposes alike. With a grouped matmul that
    writes 1e30 there, forward and backward, the trained share's sums and
    gradients are what they are with one that writes zeros (PR 62's first
    chip run: dx of real tokens came back 1e4 times the reference's)."""
    def dead(rows, sizes):
        return (jnp.arange(rows.shape[0]) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(rows, w, sizes):
        return jnp.where(dead(rows, sizes), 1e30,
                         jax.lax.ragged_dot(rows, w, sizes))

    def fwd(rows, w, sizes):
        return poisoned(rows, w, sizes), (rows, w, sizes)

    def bwd(saved, g):
        rows, w, sizes = saved
        d_rows, d_w = jax.vjp(lambda r, m: jax.lax.ragged_dot(r, m, sizes),
                              rows, w)[1](g)
        return jnp.where(dead(rows, sizes), 1e30, d_rows), d_w, None

    poisoned.defvjp(fwd, bwd)
    xt, experts, gates, wg, wu, wd, w = _share_inputs()
    first, count = 2, 4
    mine = slice(first, first + count)

    def held(xt, gates, wg, wu, wd):
        return jnp.sum(w * moe_ops._experts_held_trained(
            xt, experts, gates, wg, wu, wd, first, E)[0])

    args = (xt, gates, wg[mine], wu[mine], wd[mine])
    want = jax.value_and_grad(held, (0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(moe_ops.expert_matmul, "expert_matmul", poisoned)
    got = jax.value_and_grad(held, (0, 1, 2, 3, 4))(*args)
    assert abs(float(got[0] - want[0])) <= 1e-4
    for g, r in zip(got[1], want[1]):
        assert np.max(np.abs(np.asarray(g - r))) <= 2e-5


def test_the_op_counts_what_a_training_step_asks_of_its_experts():
    rng = np.random.RandomState(0)
    n, d, h = 16, 8, 8
    ins = {"X": [jnp.asarray(rng.randn(n, d), jnp.float32)],
           "RouterW": [jnp.asarray(rng.randn(d, E), jnp.float32)],
           "WGate": [jnp.asarray(rng.randn(HELD, d, h), jnp.float32)],
           "WUp": [jnp.asarray(rng.randn(HELD, d, h), jnp.float32)],
           "WDown": [jnp.asarray(rng.randn(HELD, h, d), jnp.float32)]}
    ctx = ExecContext(jax.random.PRNGKey(0))      # not for test: trained
    out = require_op("moe_gated_ffn").compute(
        ctx, ins, {"top_k": TOP_K, "first_expert": FIRST})
    chosen = np.asarray(out["Experts"][0]).reshape(-1)
    mine = chosen[(chosen >= FIRST) & (chosen < FIRST + HELD)]
    rows = np.bincount(mine - FIRST, minlength=HELD)
    assert [int(v) for v in out["Load"][0]] == [
        n * TOP_K, len(mine), int(np.sum(rows > 0)), int(rows.max())]
    served = require_op("moe_gated_ffn").compute(
        ExecContext(jax.random.PRNGKey(0), is_test=True), ins,
        {"top_k": TOP_K, "first_expert": FIRST})
    assert "Load" not in served
    assert np.max(np.abs(np.asarray(served["Out"][0] - out["Out"][0]))) \
        <= 1e-5


def test_a_wave_that_does_not_run_shows_in_the_steps_counts(monkeypatch):
    """`Load`'s pairs on held experts are summed inside the share's walk
    (the rows each expert's products took in the waves that ran), not
    read off the router's histogram: with the walk's last wave left out,
    the count falls under what the router sent here, and with every wave
    run it is exactly that."""
    rng = np.random.RandomState(0)
    n, d, h = 16, 8, 8
    router = rng.randn(d, E)
    router[:, FIRST:FIRST + HELD] += 1.0    # a routing skewed onto the share
    ins = {"X": [jnp.asarray(np.abs(rng.randn(n, d)), jnp.float32)],
           "RouterW": [jnp.asarray(router, jnp.float32)],
           "WGate": [jnp.asarray(rng.randn(HELD, d, h), jnp.float32)],
           "WUp": [jnp.asarray(rng.randn(HELD, d, h), jnp.float32)],
           "WDown": [jnp.asarray(rng.randn(HELD, h, d), jnp.float32)]}
    attrs = {"top_k": TOP_K, "first_expert": FIRST}
    monkeypatch.setattr(moe_ops, "_HELD_WAVE_ROWS", 4)

    def step():
        out = require_op("moe_gated_ffn").compute(
            ExecContext(jax.random.PRNGKey(0)), ins, attrs)
        chosen = np.asarray(out["Experts"][0]).reshape(-1)
        sent = int(np.sum((chosen >= FIRST) & (chosen < FIRST + HELD)))
        return [int(v) for v in out["Load"][0]], sent

    load, sent = step()
    rows = moe_ops._held_grad_rows(n, TOP_K, HELD, E)
    assert sent > rows                  # several waves hold a pair
    assert load[1] == sent
    fori_loop = jax.lax.fori_loop
    monkeypatch.setattr(
        jax.lax, "fori_loop",
        lambda lo, hi, body, init: fori_loop(lo, hi - 1, body, init))
    load, sent = step()
    assert load[1] == (-(-sent // rows) - 1) * rows < sent


# ---------------------------------------------------------------------------
# (d) the YaRN table against the closed form
# ---------------------------------------------------------------------------

PUBLISHED_YARN = (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782)


def test_the_yarn_table_is_the_closed_form_at_the_published_keys():
    d, theta = 128, 500000.0
    f = lambda n: d * math.log(8192 / (2 * math.pi * n)) \
        / (2 * math.log(theta))
    assert (math.floor(f(32)), math.ceil(f(1))) == (18, 35)
    assert ref.yarn_ends(d, theta, PUBLISHED_YARN) == (18, 35)
    plain = theta ** (-np.arange(0, d, 2) / d)
    got, factor = rope_table(d, theta, PUBLISHED_YARN)
    got = np.asarray(got, np.float64)
    assert factor == 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)  # untouched
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)
    ramp = np.clip((np.arange(64) - 18) / 17.0, 0, 1)
    np.testing.assert_allclose(
        got, (1 - ramp) * plain + ramp * plain / 16, rtol=2e-6)
    want, c = ref.rope_table(d, theta, PUBLISHED_YARN)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-6)
    assert c == factor
    # the plain table is what it was, to the bit, and carries no factor
    w, one = rope_table(d, theta)
    assert one == 1.0 and np.array_equal(
        np.asarray(w), np.asarray(theta ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)))


def test_rotation_under_yarn_scales_cos_and_sin_alike():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 6, 2, 16), jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)
    got = np.asarray(rope_rotate(x, pos, THETA, False, YARN))
    w, c = ref.rope_table(16, THETA, YARN)
    ang = np.arange(6)[:, None] * np.asarray(w)[None]
    a, b = np.asarray(x)[0, :, :, :8], np.asarray(x)[0, :, :, 8:]
    cos, sin = c * np.cos(ang)[:, None], c * np.sin(ang)[:, None]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    assert np.max(np.abs(got[0] - want)) <= 1e-5
    # a rotation at position 0 is the factor alone
    assert np.allclose(got[0, 0], c * np.asarray(x)[0, 0], atol=1e-6)


@pytest.mark.parametrize("bad", [
    dict(full_rope_scaling=(16.0, 64.0, 4.0)),
    dict(full_rope_scaling=(16.0, 64.0, 4.0, 0.25, 0.0)),
    dict(full_rope_theta=-1.0),
    dict(full_positions="none"),
    dict(attention="mha", n_kv_heads=0, head_dim=0, window=0,
         layer_pattern=()),
])
def test_block_spec_refuses_rotary_fields_it_cannot_build(bad):
    with pytest.raises(ValueError):
        block_of(**bad)


def test_a_layer_kind_says_its_rotary_table():
    block = block_of(full_rope_theta=1e6)
    assert [(block.layer(i).rope_theta, block.layer(i).rope_scaling)
            for i in range(4)] == [(THETA, ())] * 3 + [(1e6, YARN)]
    said = block.to_dict()
    assert said["full_rope_scaling"] == list(YARN)
    assert tfm.BlockSpec.of(said) == block
    # a block without them records what it did before there were the fields
    plain = block_of(full_rope_theta=0.0, full_rope_scaling=())
    assert not {"full_rope_theta", "full_rope_scaling"} & set(
        plain.to_dict())
    assert plain.layer(3).rope_theta == THETA


# ---------------------------------------------------------------------------
# serving takes the fields: a prefill, then decode steps through the pools
# ---------------------------------------------------------------------------

def test_a_bundle_with_the_rotary_fields_prefills_then_decodes(tmp_path):
    """`export_decode_model` records `full_rope_*` in the bundle's block,
    and the buckets and the step rotate through the one `rope_table`:
    logits after a prefill and after each teacher-forced step through
    the window and the full pools against the reference's full forward."""
    maxc, p_len, steps = 32, 9, 5
    block = block_of(window=8)     # a cache's window is whole blocks
    hp = HP._replace(window=8)
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [maxc], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=L, d_model=DM, n_heads=NH,
                           d_ff=FF, max_len=maxc, block=block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, 7)
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
        bundle = str(tmp_path / "m")
        pio.export_decode_model(
            bundle, dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH,
                         d_ff=FF, max_context=maxc, block=block),
            scope=scope, length_buckets=(16, 32), slots=2, block_size=4,
            pool_blocks=24)
    model = DecodeModel(bundle, warmup=False)
    with open(os.path.join(bundle, "serving.json")) as f:
        said = json.load(f)["decode"]["model_cfg"]["block"]
    assert said["full_rope_scaling"] == list(YARN)
    ids = np.random.RandomState(2).randint(0, V, p_len + steps)
    want = np.asarray(ref.logits(weights, ids, hp))
    bs = model.block_size
    blocks = list(range(1, 1 + math.ceil((p_len + steps) / bs)))
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence(blocks[:math.ceil(p_len / bs)], kv)
    rows = [np.asarray(last)]
    tokens = np.zeros(model.slots, np.int64)
    lens = np.zeros(model.slots, np.int32)
    tables = np.zeros((model.slots, model.max_blocks_per_seq), np.int32)
    tables[0, :len(blocks)] = blocks
    for j in range(steps):
        tokens[0] = ids[p_len + j]
        lens[0] = p_len + j + 1
        rows.append(np.asarray(model.decode_step(tokens, lens, tables))[0])
    got = np.stack(rows)
    assert np.max(np.abs(got - want[p_len - 1:])) <= 5e-5 * np.std(want)
