"""Kanana-2's block (`deepseek_v3`: latent attention with interleaved
partial RoPE, a leading dense gated layer, sigmoid-routed experts with a
selection bias, renormalised and scaled gates, a shared expert) through
the three builders of `models/transformer.py`, against the plain
reference `benchmark/reference_kanana.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. Each is written beside its check with what it is far inside of.
The absorbed decode against the reference's expanded full forward
(`test_prefill_then_paged_decode_matches_reference`) is the proof that
the two groupings of latent attention are one function.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.core.registry import require_op
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.metrics import render_prometheus

from references import by_path

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa

ref = by_path("reference_kanana")
HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, FF, E, TOP_K = 97, 3, 64, 4, 16, 8, 2
RANK, NOPE, ROPE, VDIM = 32, 16, 8, 16
DENSE_W, SHARED_W, SCALE = 48, 32, 2.448
MAXC, BLOCK, POOL, SLOTS = 48, 4, 40, 4
BUCKETS = (8, 16, 32)
EPS, THETA = 1e-6, 1000000.0
ROW = 128            # the pool's row: RANK + ROPE = 40 in one lane tile


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, bias=False, ffn="moe_gated",
                num_experts=E, experts_per_tok=TOP_K, attention="latent",
                kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
                qk_rope_head_dim=ROPE, v_head_dim=VDIM,
                rope_interleave=True, router="sigmoid_bias",
                norm_topk=True, routed_scale=SCALE,
                shared_width=SHARED_W, dense_layers=1,
                dense_width=DENSE_W)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(NH, TOP_K, RANK, NOPE, ROPE, VDIM, EPS, THETA, SCALE)

PROGRAM_NAME = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale",
                "head": "lm_head_w"}
ATTN_NAME = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
             "q": "attn{i}_q_w", "kva": "attn{i}_kva_w",
             "kv_norm": "attn{i}_kvnorm_scale", "kvb": "attn{i}_kvb_w",
             "out": "attn{i}_out_w"}
DENSE_NAME = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
              "down": "ffn{i}_down_w"}
MOE_NAME = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w", "shared_gate": "moe{i}_shared_gate_w",
            "shared_up": "moe{i}_shared_up_w",
            "shared_down": "moe{i}_shared_down_w"}


def layer_names(i, dense_layers=1):
    return dict(ATTN_NAME, **(DENSE_NAME if i < dense_layers else MOE_NAME))


def reference_weights(get, n_layers=L):
    """The program's weights, by the names the builders give them, in
    the shape the reference documents. `get(name)` -> array."""
    return dict({key: get(name) for key, name in PROGRAM_NAME.items()},
                layers=[{key: get(name.format(i=i))
                         for key, name in layer_names(i).items()}
                        for i in range(n_layers)])


def randomise(scope, seed):
    """Seeded weights with gains away from 1, a router spread wide
    enough that top-k choices are not near ties, and a selection bias
    large enough to change choices."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("router_bias"):
            new = 0.3 * rng.randn(*v.shape)
        elif "router" in name:
            new = rng.randn(*v.shape)
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def forward_program(seq_len, block=None, **kw):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block or block_of(), **kw)
    chosen = [op.output("Experts")[0] for op in main.global_block.ops
              if op.type == "moe_gated_ffn"]
    return main, startup, logits, chosen


# ---------------------------------------------------------------------------
# forward: logits and the chosen experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [24, 3])
def test_forward_matches_reference(seq_len):
    main, startup, logits, chosen = forward_program(seq_len)
    assert len(chosen) == L - 1           # layer 0 is dense
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 3)
        ids = np.random.RandomState(4).randint(0, V, (2, seq_len))
        got = exe.run(main, feed={"src_ids": ids},
                      fetch_list=[logits] + chosen)
        weights = reference_weights(scope.find_var)
    for b in range(2):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        # 2e-5 of the logits' spread: two float32 evaluation orders of
        # the same sums (measured 4e-6 at most). Any one part of the
        # block changed (the scale, the bias, the shared expert, the
        # angles, the latent's norm) moves logits by over 2e-2 of it
        # (the next test).
        assert np.max(np.abs(got[0][b] - want)) <= 2e-5 * np.std(want)
        want_sets = np.asarray(ref.chosen_experts(weights, ids[b], HP))
        for j in range(L - 1):
            assert np.array_equal(np.sort(got[1 + j][b], -1),
                                  np.sort(want_sets[j], -1))


def test_the_parts_of_the_block_each_count():
    """What the tolerance above is far inside of, part by part: the
    reference with one part of the published block changed is a
    thousand times the tolerance and more away from the program."""
    seq_len = 16
    main, startup, logits, _ = forward_program(seq_len)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 3)
        ids = np.random.RandomState(4).randint(0, V, (1, seq_len))
        got, = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
    plain = np.asarray(ref.logits(weights, ids[0], HP))
    assert np.max(np.abs(got[0] - plain)) <= 2e-5 * np.std(plain)

    def off_by(hp=HP, **layer_changes):
        w = dict(weights, layers=[
            dict(lay, **{k: f(lay[k]) for k, f in layer_changes.items()
                         if k in lay}) for lay in weights["layers"]])
        other = np.asarray(ref.logits(w, ids[0], hp))
        return np.max(np.abs(got[0] - other)) / np.std(plain)

    assert off_by(HP._replace(routed_scale=1.0)) > 2e-2       # the scale
    assert off_by(router_bias=np.zeros_like) > 2e-2   # the bias chooses
    assert off_by(shared_down=np.zeros_like) > 2e-2   # the shared expert
    assert off_by(HP._replace(theta=10000.0)) > 2e-2          # the angles
    assert off_by(kv_norm=np.ones_like) > 2e-2        # the latent's norm


def test_interleaved_rope_is_the_published_rotation():
    """The published code de-interleaves q_rope and k_rope and rotates
    halves; the program and the reference rotate the pairs (2i, 2i+1)
    in place. The same rotation up to one permutation of the rope
    dimensions, common to q and k: every q . k agrees."""
    from paddle_tpu.ops.attention_ops import rope_rotate
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 9, 3, ROPE), jnp.float32)
    k = jnp.asarray(rng.randn(1, 9, 1, ROPE), jnp.float32)
    pos = jnp.arange(9, dtype=jnp.int32)

    def published(t):       # view(.., d/2, 2).transpose -> rotate_half
        t = jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)
        return rope_rotate(t, pos, THETA, interleave=False)

    ours = jnp.einsum("bqhd,bkd->bhqk", rope_rotate(q, pos, THETA, True),
                      rope_rotate(k, pos, THETA, True)[:, :, 0])
    theirs = jnp.einsum("bqhd,bkd->bhqk", published(q), published(k)[:, :, 0])
    assert np.max(np.abs(np.asarray(ours - theirs))) <= 1e-5
    assert np.max(np.abs(np.asarray(
        ref._rope(q[0], THETA) - rope_rotate(q, pos, THETA, True)[0]))) \
        <= 1e-6


# ---------------------------------------------------------------------------
# the router's rule, the shared expert, the dense layer
# ---------------------------------------------------------------------------

def _op_inputs(rng, n, d=16, h=8, e=E, hs=12, bias=0.0):
    f32 = jnp.float32
    return {"X": [jnp.asarray(rng.randn(n, d), f32)],
            "RouterW": [jnp.asarray(rng.randn(d, e), f32)],
            "RouterBias": [jnp.asarray(bias * rng.randn(e), f32)],
            "WGate": [jnp.asarray(rng.randn(e, d, h) * .3, f32)],
            "WUp": [jnp.asarray(rng.randn(e, d, h) * .3, f32)],
            "WDown": [jnp.asarray(rng.randn(e, h, d) * .3, f32)],
            "SharedGate": [jnp.asarray(rng.randn(d, hs) * .3, f32)],
            "SharedUp": [jnp.asarray(rng.randn(d, hs) * .3, f32)],
            "SharedDown": [jnp.asarray(rng.randn(hs, d) * .3, f32)]}


def _op_reference(ins, top_k, scale=SCALE):
    layer = {"router": ins["RouterW"][0], "router_bias": ins["RouterBias"][0],
             "gate": ins["WGate"][0], "up": ins["WUp"][0],
             "down": ins["WDown"][0], "shared_gate": ins["SharedGate"][0],
             "shared_up": ins["SharedUp"][0],
             "shared_down": ins["SharedDown"][0]}
    hp = HP._replace(top_k=top_k, routed_scale=scale)
    with jax.default_matmul_precision("highest"):
        chosen, w, _ = ref._route(ins["X"][0], layer, hp)
        return np.asarray(chosen), np.asarray(w), \
            np.asarray(ref._experts(ins["X"][0], layer, w))


ATTRS = {"router": "sigmoid_bias", "norm_topk": True, "routed_scale": SCALE}


@pytest.mark.parametrize("n,top_k,bias", [(1, 2, 0.0), (9, 2, 0.5),
                                          (64, 6, 0.5), (300, 2, 0.5)])
def test_expert_op_matches_reference(n, top_k, bias):
    ins = _op_inputs(np.random.RandomState(n), n, bias=bias)
    op = require_op("moe_gated_ffn").compute
    got = op(None, ins, dict(ATTRS, top_k=top_k))
    chosen, _, want = _op_reference(ins, top_k)
    assert np.array_equal(np.sort(np.asarray(got["Experts"][0]), -1),
                          np.sort(chosen, -1))
    assert np.max(np.abs(np.asarray(got["Out"][0]) - want)) \
        <= 2e-5 * np.std(want)


def test_the_bias_chooses_and_never_weighs():
    """A nonzero bias moves the choice (a sixth of the rows here) and
    leaves every weight what the sigmoid alone makes it: the chosen
    weights sum to the scale, and a row whose choice did not move keeps
    its output bit for bit."""
    ins = _op_inputs(np.random.RandomState(1), 200, bias=0.0)
    biased = dict(ins, RouterBias=[jnp.asarray(
        0.3 * np.random.RandomState(2).randn(E), jnp.float32)])
    op = require_op("moe_gated_ffn").compute
    plain = op(None, ins, dict(ATTRS, top_k=2))
    moved = op(None, biased, dict(ATTRS, top_k=2))
    same = np.all(np.sort(np.asarray(plain["Experts"][0]), -1)
                  == np.sort(np.asarray(moved["Experts"][0]), -1), axis=-1)
    assert 0.05 < 1 - same.mean() < 0.95
    assert np.array_equal(np.asarray(plain["Out"][0])[same],
                          np.asarray(moved["Out"][0])[same])
    _, w, want = _op_reference(biased, 2)
    assert np.allclose(w.sum(-1), SCALE, rtol=1e-6)
    assert np.max(np.abs(np.asarray(moved["Out"][0]) - want)) \
        <= 2e-5 * np.std(want)


def test_ties_go_to_the_lower_index():
    """Two experts with one router column, the others biased away: equal
    s + b, the lower index is chosen, in the program and in the
    reference."""
    ins = _op_inputs(np.random.RandomState(3), 12, bias=0.0)
    rw = np.asarray(ins["RouterW"][0]).copy()
    rw[:, 5] = rw[:, 2]
    bias = np.full(E, -10.0, np.float32)
    bias[[2, 5]] = 0.0                         # 2 and 5 lead, equal
    ins["RouterW"] = [jnp.asarray(rw)]
    ins["RouterBias"] = [jnp.asarray(bias)]
    got = require_op("moe_gated_ffn").compute(None, ins, dict(ATTRS, top_k=1))
    assert np.all(np.asarray(got["Experts"][0]) == 2)
    assert np.all(_op_reference(ins, 1)[0] == 2)


@pytest.mark.parametrize("bad", [
    dict(attrs=dict(router="softmax")),           # a bias without its rule
    dict(attrs=dict(router="group_limited")),
    dict(drop=("SharedUp",)),                     # two of three matrices
    dict(attrs=dict(top_k=E + 1))])
def test_the_expert_op_refuses_what_it_cannot_do(bad):
    ins = _op_inputs(np.random.RandomState(0), 4)
    for key in bad.get("drop", ()):
        ins.pop(key)
    with pytest.raises(ValueError):
        require_op("moe_gated_ffn").compute(
            None, ins, dict(dict(ATTRS, top_k=2), **bad.get("attrs", {})))


def test_the_dense_layer_and_the_shared_expert_have_their_weights():
    main, _, _, _ = forward_program(8)
    names = {v.name for v in main.list_vars() if v.persistable}
    want = set(PROGRAM_NAME.values())
    for i in range(L):
        want |= {n.format(i=i) for n in layer_names(i).values()}
    assert names == want
    shapes = {v.name: tuple(v.shape) for v in main.list_vars()
              if v.persistable}
    assert shapes["ffn0_gate_w"] == (DM, DENSE_W)
    assert shapes["moe1_shared_down_w"] == (SHARED_W, DM)
    assert shapes["attn0_q_w"] == (DM, NH * (NOPE + ROPE))
    assert shapes["attn0_kva_w"] == (DM, RANK + ROPE)
    assert shapes["attn0_kvb_w"] == (RANK, NH * (NOPE + VDIM))
    assert shapes["attn0_out_w"] == (NH * VDIM, DM)


# ---------------------------------------------------------------------------
# the kernels: the latent paged kernel, the flash forward with a V width
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lens,width", [
    ([0, 5, 72, 37], 9),       # an empty slot, ragged, one at the table
    ([8, 8, 8, 8], 9),         # one page each
    ([1, 0, 0, 200], 25),      # a many-block sequence beside a 1-token one
    ([0, 0, 0, 0], 4)])        # nothing live
def test_latent_kernel_matches_the_gather_reference(lens, width):
    """Interpret mode (f32 operands): P pages a block from the table's
    width, so [1, 0, 0, 200] walks 25 pages in 2 blocks of 16 (bs 8 ->
    lane-tile pages of 16) and [0, 5, 72, 37] in blocks of 9."""
    rng = np.random.RandomState(sum(lens))
    s_n, h, w, vw, nb, bs = 4, 4, 256, 128, 60, 8
    q = jnp.asarray(rng.randn(s_n, h, w), jnp.float32)
    pool = jnp.asarray(rng.randn(nb, bs, w), jnp.float32)
    tables = jnp.asarray(rng.randint(1, nb, (s_n, width)), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = pa.paged_latent_attention_reference(
        q, pool, tables, lens, value_width=vw, scale=0.07)
    got = pa.paged_latent_decode_attention(
        q, pool, tables, lens, value_width=vw, scale=0.07, interpret=True)
    # float32 sums in blocks against one softmax over the row
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) <= 2e-6
    assert not np.asarray(got)[np.asarray(lens) == 0].any()


def test_latent_block_pages_are_whole_lane_tiles():
    # the cell's page: 16 tokens x 640 floats, 40,960 B, a 640-wide table
    assert pa.paged_latent_block_pages(16, 640, jnp.float32, 640) == 24
    assert pa.paged_latent_block_pages(16, 640, jnp.float32, 5) == 5
    assert pa.paged_latent_block_pages(4, 128, jnp.float32, 12) == 12


def test_flash_forward_takes_a_v_width_of_its_own():
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(1, 256, 2, 24), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    want = fa.mha_reference(q, k, v, causal=True)
    assert got.shape == (1, 256, 2, 16)
    assert np.max(np.abs(np.asarray(got - want))) <= 2e-6

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
    g = jax.grad(loss(lambda *a: fa.flash_attention(
        *a, causal=True, interpret=True)), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda *a: fa.mha_reference(
        *a, causal=True)), (0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):     # the XLA scan: the Pallas backward
        assert np.max(np.abs(np.asarray(a - b))) \
            <= 1e-4 * np.max(np.abs(np.asarray(b)))   # is one width's


# ---------------------------------------------------------------------------
# training: loss and gradients
# ---------------------------------------------------------------------------

def test_training_step_matches_reference_gradients():
    seq_len, batch = 12, 3
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=seq_len, n_layers=L, d_model=DM,
            n_heads=NH, d_ff=FF, max_len=seq_len, block=block_of())
        grads = pt.backward.append_backward(avg)
    rng = np.random.RandomState(5)
    draw = rng.randint(0, V, (batch, seq_len + 1))
    ids, tgt = draw[:, :-1], draw[:, 1:]
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 6)
        weights = reference_weights(
            lambda n: np.asarray(scope.find_var(n)))
        by_name = {p.name: g for p, g in grads}
        got = exe.run(main, feed={"src_ids": ids,
                                  "tgt_ids": tgt[..., None]},
                      fetch_list=[avg] + list(by_name.values()))
    got_loss = float(np.ravel(got[0])[0])
    got_grads = dict(zip(by_name, got[1:]))

    def mean_loss(w):
        return sum(ref.nll_sum(w, jnp.asarray(ids[b]), jnp.asarray(tgt[b]),
                               HP)
                   for b in range(batch)) / (batch * seq_len)

    want_loss, want = jax.value_and_grad(mean_loss)(weights)
    # float32 sums in another order (measured 1e-7 relative)
    assert abs(got_loss - float(want_loss)) <= 1e-5 * abs(float(want_loss))

    checked = 0
    for key, name in PROGRAM_NAME.items():
        checked += _check_grad(got_grads, name, want[key])
    for i in range(L):
        for key, name in layer_names(i).items():
            if key == "router_bias":
                # it chooses and never weighs: no gradient reaches it,
                # in the reference's or the program's backward
                assert not np.asarray(want["layers"][i][key]).any()
                name = name.format(i=i)
                assert name not in got_grads \
                    or not np.asarray(got_grads[name]).any()
                continue
            checked += _check_grad(got_grads, name.format(i=i),
                                   want["layers"][i][key])
    assert checked == len(PROGRAM_NAME) + L * len(ATTN_NAME) \
        + len(DENSE_NAME) + (L - 1) * (len(MOE_NAME) - 1)


def _check_grad(got_grads, name, want_grad):
    g, w = np.asarray(got_grads[name]), np.asarray(want_grad)
    # per parameter, against the gradient's own largest entry: 2e-5 is
    # ten times what float32 accumulation through three layers and a
    # softmax gives (measured 3e-6 at most); a weight that skipped the
    # router's gradient, or the latent's norm left out of the
    # backward, is of order 1
    assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-9, name
    return 1


# ---------------------------------------------------------------------------
# serving: export -> load -> prefill -> paged decode through the latent
# cache, against the reference's full forward
# ---------------------------------------------------------------------------

def export_cfg(block):
    return dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
                max_context=MAXC, block=block)


@pytest.fixture(scope="module")
def kanana_bundle(tmp_path_factory):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [MAXC], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=L, d_model=DM, n_heads=NH,
                           d_ff=FF, max_len=MAXC, block=block_of())
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, 7)
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
        d = str(tmp_path_factory.mktemp("kanana") / "m")
        pio.export_decode_model(
            d, export_cfg(block_of()), scope=scope,
            length_buckets=BUCKETS, slots=SLOTS, block_size=BLOCK,
            pool_blocks=POOL)
    return d, weights


def _ref_logits(weights, ids):
    return np.asarray(ref.logits(weights, ids, HP))


def _step_feeds(model):
    return (np.zeros(model.slots, np.int64),
            np.zeros(model.slots, np.int32),
            np.zeros((model.slots, model.max_blocks_per_seq), np.int32))


def test_serving_json_records_the_block_and_the_cache(kanana_bundle):
    with open(os.path.join(kanana_bundle[0], "serving.json")) as f:
        meta = json.load(f)
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    assert dec["cache"] == {"kind": "latent", "rows": [[ROW]],
                            "row_floats": RANK + ROPE,
                            "bytes_per_token": 4 * L * ROW}
    pools = [m for m in dec["feeds"] if m["name"].startswith("latent_cache")]
    assert [m["shape"] for m in pools] == [[POOL, BLOCK, ROW]] * L
    assert dec["prefill_roles"]["kv"] == [[f"latent_{i}"] for i in range(L)]
    # one head row, whatever the bucket; the routes of the expert layers
    by_name = {m["name"]: m for m in meta["buckets"][-1]["fetches"]}
    assert by_name["logits"]["shape"] == [1, 1, V]
    assert by_name["latent_0"]["shape"] == [1, BUCKETS[-1], RANK + ROPE]
    assert by_name["moe_routes"]["shape"] == [1, L - 1, BUCKETS[-1], TOP_K]
    assert dec["fetches"][-1]["shape"] == [L - 1, SLOTS, TOP_K]


def test_prefill_then_paged_decode_matches_reference(kanana_bundle):
    """A 6-token prompt, then 9 teacher-forced steps: positions 6..14
    cross the block boundaries at 8 and 12 (blocks of 4). Each step is
    the ABSORBED grouping (q' = q_nope Wk^T against the cached latent,
    the values up-projected after the softmax), the reference the
    expanded one over the whole sequence: their agreement at every step
    is the proof that the two are one function. A busy neighbour slot at
    another position rides along."""
    d, weights = kanana_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    ids = rng.randint(0, V, 15)
    other = rng.randint(0, V, 30)
    p_len, o_len = 6, 21
    want = _ref_logits(weights, ids)
    want_other = _ref_logits(weights, other)

    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    assert [a.shape for a in kv.arrays] == [(1, 8, RANK + ROPE)] * L
    model.seed_sequence([1, 2], kv)
    last_o, kv_o = model.prefill([int(t) for t in other[:o_len]])
    model.seed_sequence([11, 12, 13, 14, 15, 16], kv_o)
    tol = 2e-5 * np.std(want)   # float32 order; a wrong position or a
    # stale cache row moves a row by 0.1 of the spread and more
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    assert np.max(np.abs(np.asarray(last_o) - want_other[o_len - 1])) <= tol
    # the seeded pool: the row's columns past rank + rope hold zeros
    pool = np.asarray(model._pools[0])
    assert pool.shape == (POOL, BLOCK, ROW)
    assert pool[1:3].reshape(-1, ROW)[:p_len, :RANK + ROPE].any(axis=1).all()
    assert not pool[..., RANK + ROPE:].any()
    assert not pool[2, p_len - BLOCK:].any()        # the bucket's padding

    tokens, lens, tables = _step_feeds(model)
    tables[0, :4] = [1, 2, 3, 4]
    tables[2, :8] = [11, 12, 13, 14, 15, 16, 17, 18]
    for j in range(len(ids) - p_len):
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        tokens[2], lens[2] = other[o_len + j], o_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[0] - want[p_len + j])) <= tol, j
        assert np.max(np.abs(rows[2] - want_other[o_len + j])) <= tol, j


def test_a_wrong_position_fails_the_check(kanana_bundle):
    """What the tolerance is far inside of: the same step with the
    slot's context one token short (RoPE one position early, the newest
    latent row unread) misses by over a thousand times it."""
    d, weights = kanana_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(8).randint(0, V, 15)
    want = _ref_logits(weights, ids)
    _, kv = model.prefill([int(t) for t in ids[:6]])
    model.seed_sequence([1, 2], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[0, :4] = [1, 2, 3, 4]
    tokens[0], lens[0] = ids[6], 6       # should be 7
    rows = np.asarray(model.decode_step(tokens, lens, tables))
    assert np.max(np.abs(rows[0] - want[6])) > 2e-2 * np.std(want)


def test_the_server_reports_the_experts_it_chose(kanana_bundle):
    """`DecodeModel.last_routes` after a prefill and after a step: the
    reference's own choice in its order, for the layers that have
    experts (the dense layer 0 has no row), so the reference forced
    onto them gives its plain logits and no shortfall."""
    d, weights = kanana_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(8).randint(0, V, 9)
    p_len = 6
    want = np.asarray(ref.chosen_experts(weights, ids, HP))
    assert want.shape == (L - 1, len(ids), TOP_K)
    _, kv = model.prefill([int(t) for t in ids[:p_len]])
    got = [np.asarray(model.last_routes)[:, :p_len]]
    assert model.last_routes.shape == (L - 1, BUCKETS[0], TOP_K)
    model.seed_sequence([1, 2], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[1, :4] = [1, 2, 3, 4]
    for j in range(len(ids) - p_len):
        tokens[1], lens[1] = ids[p_len + j], p_len + j + 1
        model.decode_step(tokens, lens, tables)
        got.append(np.asarray(model.last_routes)[:, 1:2])
    got = np.concatenate(got, axis=1)
    assert np.array_equal(got, want)
    logits, shortfall = ref.logits_on_routes(weights, ids, HP, got)
    assert not np.asarray(shortfall).any()
    assert np.array_equal(np.asarray(logits), _ref_logits(weights, ids))


def test_forced_routes_tell_a_near_tie_from_a_fault(kanana_bundle):
    """`test_olmoe.py`'s twin on s + b: an expert swapped for the
    reference's next one (what a near tie does) has a shortfall at that
    layer and token alone, moves that token's logits and leaves every
    earlier token's as they were; swapped for the expert with the
    smallest s + b (what a fault does) the shortfall is far larger."""
    _, weights = kanana_bundle
    ids = np.random.RandomState(8).randint(0, V, 9)
    own = np.asarray(ref.chosen_experts(weights, ids, HP))
    plain = _ref_logits(weights, ids)
    lay, tok = 0, 5      # the first expert layer: hangs on no route
    ranked = np.asarray(ref.chosen_experts(
        weights, ids, HP._replace(top_k=E)))[lay, tok]
    assert np.array_equal(ranked[:TOP_K], own[lay, tok])
    seen = []
    for other in (ranked[TOP_K], ranked[-1]):
        routes = own.copy()
        routes[lay, tok, 0] = other     # in place of the first choice
        logits, shortfall = ref.logits_on_routes(weights, ids, HP, routes)
        logits, shortfall = np.asarray(logits), np.array(shortfall)
        seen.append(shortfall[lay, tok])
        shortfall[lay, tok] = 0
        assert not shortfall.any()
        assert np.array_equal(logits[:tok], plain[:tok])
        assert np.max(np.abs(logits[tok] - plain[tok])) \
            > 1e-3 * np.std(plain)
    assert 0 < seen[0] < seen[1] and seen[1] > 0.5


def test_batch_invariance(kanana_bundle):
    """A prompt's last-position logits alone in the smallest bucket and
    padded to a larger one, and its decode row alone and beside three
    busy slots: what another order of float32 sums gives (5e-6 of the
    logits' spread) and no more. The latent kernel walks each slot's
    own pages and the experts are dropless, so neither the bucket's
    padding nor a neighbour can reach a row."""
    d, weights = kanana_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(9)
    ids = [int(t) for t in rng.randint(0, V, 8)]
    want = _ref_logits(weights, np.asarray(ids))
    tol = 5e-6 * np.std(want)

    alone, kv = model.prefill(ids[:7])              # bucket 8: 1 pad row
    assert kv.bound == 8
    calls = model._admit_fns[32]                    # 25 pad rows
    padded = np.zeros(calls.ids_shape, calls.ids_dtype)
    padded[0, :7] = ids[:7]
    in_32, *_ = calls.prefill(calls.weights, padded, np.int32(7))
    assert np.max(np.abs(np.asarray(alone) - want[6])) <= tol
    assert np.max(np.abs(np.asarray(in_32) - np.asarray(alone))) <= tol

    def row_with(neighbours):
        model.reset_pools()
        model.seed_sequence([1, 2], kv)
        tokens, lens, tables = _step_feeds(model)
        tables[0, :2] = [1, 2]
        tokens[0], lens[0] = ids[7], 8
        for slot, blocks in neighbours:
            n_tok = int(rng.randint(3, 9))
            _, nkv = model.prefill(
                [int(t) for t in rng.randint(0, V, n_tok)])
            model.seed_sequence(blocks, nkv)
            tables[slot, :3] = blocks
            tokens[slot] = int(rng.randint(0, V))
            lens[slot] = n_tok + 1
        return np.asarray(model.decode_step(tokens, lens, tables))[0]

    solo = row_with([])
    busy = row_with([(1, [5, 6, 7]), (2, [8, 9, 10]), (3, [11, 12, 13])])
    assert np.max(np.abs(solo - want[7])) <= tol
    assert np.max(np.abs(busy - solo)) <= tol


def test_through_the_engine_with_its_counters_and_gauges(kanana_bundle):
    """The normal path end to end: `ServingEngine.load_decode_model`,
    the scheduler and its block accounting, the donated pools; greedy
    tokens equal a teacher-forced argmax of the reference; the routing
    counters count the expert layers only; `describe()` and the scrape
    say what the cache is."""
    d, weights = kanana_bundle
    engine = ServingEngine()
    engine.load_decode_model("lm", d, warmup=False, max_new_tokens=6)
    try:
        prompt = [int(t) for t in np.random.RandomState(11).randint(0, V, 5)]
        tokens = engine.generate("lm", prompt).result(timeout=300)["tokens"]
        dec = engine.decode_engine("lm")
        seq = prompt + tokens
        want = _ref_logits(weights, np.asarray(seq))
        for j, tok in enumerate(tokens):
            row = want[len(prompt) - 1 + j]
            assert row[tok] >= np.max(row) - 1e-4 * np.std(want)
        snap = dec.metrics_snapshot()
        steps = snap["decode_steps"]
        assert snap["moe_layer_steps"] == (L - 1) * steps
        assert snap["moe_assignments"] == TOP_K * (L - 1) * steps
        assert snap["cache_bytes_per_token"] == 4 * L * ROW
        assert snap["paged_walked_pages"] >= snap["paged_live_pages"] > 0
        assert snap["step_aliased_bytes"] == L * POOL * BLOCK * ROW * 4
        desc = dec.describe()
        assert desc["cache"]["kind"] == "latent"
        assert desc["cache"]["bytes_per_token"] == 4 * L * ROW
        assert desc["paged_kernel"]["pages_per_block"] == MAXC // BLOCK
        # three matrices an expert, each product on XLA's grouped matmul
        assert {tag: plan["form"]
                for tag, plan in desc["expert_kernel"].items()} == {
            "gate": "ragged_dot", "up": "ragged_dot", "down": "ragged_dot"}
        text = render_prometheus(engine.metrics.snapshot())
        assert 'pt_decode_cache_bytes_per_token{model="lm"} %d' \
            % (4 * L * ROW) in text
    finally:
        engine.shutdown(drain=False)


def test_device_tokens_equal_host_argmax_latent(kanana_bundle,
                                                served_and_watched):
    """This bundle's step returns its ids before the pools and the
    routing counters and routes behind them."""
    model = served_and_watched(kanana_bundle[0], V, SLOTS)
    assert model.last_routes is not None


def test_a_kv_bundle_declares_its_cache_too(tmp_path):
    """The GPT-2 block's bundle says `kv`: two pools a layer of
    [H, d_key] rows, 8 x d_model bytes a token and layer."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [16], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=1, d_model=32, n_heads=2,
                           d_ff=64, max_len=16, pos_table_len=16)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        pio.export_decode_model(
            str(tmp_path), dict(vocab_size=V, n_layers=1, d_model=32,
                                n_heads=2, d_ff=64, max_context=16),
            scope=scope, length_buckets=(8,), slots=2, block_size=4,
            pool_blocks=8)
    model = DecodeModel(str(tmp_path), warmup=False)
    assert model.describe()["cache"] == {
        "kind": "kv", "rows": [[2, 16], [2, 16]], "row_floats": 64,
        "bytes_per_token": 256}
    assert [p.shape for p in model._pools] == [(8, 4, 2, 16)] * 2


# ---------------------------------------------------------------------------
# the head's one row, for every block
# ---------------------------------------------------------------------------

def _olmoe_block():
    return tfm.BlockSpec(norm="rms_norm", positions="rope", qk_norm=True,
                         bias=False, ffn="moe_gated", num_experts=E,
                         experts_per_tok=2)


@pytest.mark.parametrize("block", [None, _olmoe_block(), block_of()],
                         ids=["gpt2", "olmoe", "kanana"])
def test_the_one_row_head_is_the_full_heads_row(block):
    """`head_rows` picks positions BEFORE the final norm and the head:
    the same numbers as the full [S, vocab] logits at those rows, bit
    for bit on the CPU (a row's norm and matmul see no other row)."""
    seq_len = 12
    pt.core.program.reset_unique_names()
    full, startup, logits, _ = forward_program(seq_len, block=block or
                                               tfm.GPT2_BLOCK)
    one, _ = pt.Program(), None
    with pt.program_guard(one, pt.Program()):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        rows = pt.layers.data("rows", [2], dtype="int32")
        picked = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block, head_rows=rows)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 12)
        ids = np.random.RandomState(13).randint(0, V, (2, seq_len))
        at = np.asarray([[0, 7], [11, 3]], np.int32)
        want, = exe.run(full, feed={"src_ids": ids}, fetch_list=[logits])
        got, = exe.run(one, feed={"src_ids": ids, "rows": at},
                       fetch_list=[picked])
    assert got.shape == (2, 2, V)
    for b in range(2):
        assert np.max(np.abs(got[b] - want[b][at[b]])) \
            <= 1e-6 * np.std(want)


# ---------------------------------------------------------------------------
# BlockSpec: the round trip and what it refuses
# ---------------------------------------------------------------------------

def test_block_spec_round_trips_through_its_dict():
    blk = block_of()
    assert tfm.BlockSpec.of(json.loads(json.dumps(blk.to_dict()))) == blk
    first, second = blk.layer(0, FF), blk.layer(1, FF)
    assert (first.ffn, first.ffn_width) == ("gated", DENSE_W)
    assert (second.ffn, second.ffn_width) == ("moe_gated", FF)
    assert blk.cache_pools(NH, DM)["pools"] == [("latent_cache", [ROW])]
    assert tfm.GPT2_BLOCK.cache_pools(NH, DM)["kind"] == "kv"
    # the published widths: 576 floats of a token in 640
    wide = block_of(kv_lora_rank=512, qk_rope_head_dim=64)
    assert wide.cache_pools(32, 2048) == {
        "kind": "latent", "row_floats": 576,
        "pools": [("latent_cache", [640])]}


@pytest.mark.parametrize("bad", [
    dict(attention="sliding"), dict(router="group_limited"),
    dict(kv_lora_rank=0), dict(qk_rope_head_dim=7),
    dict(positions="learned"), dict(bias=True), dict(qk_norm=True),
    dict(dense_layers=1, dense_width=0), dict(shared_width=-1),
    dict(attention="mha", rope_interleave=False, qk_nope_head_dim=0,
         qk_rope_head_dim=0, v_head_dim=0),       # a rank without latent
    dict(ffn="gated"),                            # a router without experts
    dict(attention="mha", kv_lora_rank=0, qk_nope_head_dim=0,
         qk_rope_head_dim=0, v_head_dim=0)])      # interleave without it
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        block_of(**bad)


def test_a_dense_gated_block_is_a_block():
    """`ffn="gated"` alone: every layer the dense gated SiLU FFN."""
    blk = tfm.BlockSpec(norm="rms_norm", positions="rope", bias=False,
                        ffn="gated")
    main, _, _, chosen = forward_program(8, block=blk)
    names = {v.name for v in main.list_vars() if v.persistable}
    assert not chosen
    assert {f"ffn{i}_{t}_w" for i in range(L)
            for t in ("gate", "up", "down")} <= names
