"""LFM2-MoE's block (three gated short convolutions to one grouped-query
attention layer, heads 64 wide, two leading dense FFNs and experts chosen
by sigmoid plus a bias behind them, a tied head) through the three
builders of `models/transformer.py` and the decode engine, against the
plain reference `benchmark/reference_lfm2.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

A conv layer keeps no cache: all it remembers of a sequence is the two
rows before its next token, a STATE a slot, which an admission writes and
every step moves a row on. Most of this file is about that state being
the right one whatever the slot went through before.

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.decode.engine import (DecodeEngine,
                                              SequenceStateUnsupported)
from paddle_tpu.serving.metrics import render_prometheus

from references import by_path

from paddle_tpu.kernels import paged_attention as pa
attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
moe_ops = importlib.import_module("paddle_tpu.ops.moe_ops")

ref = by_path("reference_lfm2")
HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, NKV, HD, FF, DFF, E, TOP_K = 97, 6, 64, 4, 2, 64, 16, 48, 8, 2
DENSE, TAPS = 2, 3
MAXC, BLOCK, POOL, SLOTS = 48, 8, 40, 3
BUCKETS = (8, 16, 32)
EPS, THETA = 1e-5, 1e6
PATTERN = ("conv", "conv", "full", "conv")
KINDS = tuple("conv" if k == "conv" else "full_attention"
              for k in (PATTERN * 2)[:L])
STATE_LAYERS = KINDS.count("conv")
STATE_ROW_BYTES = 4 * STATE_LAYERS * (TAPS - 1) * DM     # a slot's


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, qk_norm=True, bias=False,
                attention="gqa", n_kv_heads=NKV, head_dim=HD,
                ffn="moe_gated", num_experts=E, experts_per_tok=TOP_K,
                router="sigmoid_bias", norm_topk=True, norm_topk_eps=1e-6,
                dense_layers=DENSE, dense_width=DFF, tied_head=True,
                layer_pattern=PATTERN, conv_taps=TAPS)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(NH, NKV, HD, KINDS, DENSE, TOP_K, EPS, THETA)

_NORMS = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale"}
_CONV = {"in": "conv{i}_in_w", "taps": "conv{i}_conv_w",
         "out": "conv{i}_out_w"}
_ATTENTION = {"q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w", "q_norm": "attn{i}_qnorm_scale",
              "k_norm": "attn{i}_knorm_scale"}
_DENSE = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
          "down": "ffn{i}_down_w"}
_EXPERTS = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w"}


def reference_weights(get):
    layers = []
    for i, kind in enumerate(KINDS):
        names = dict(_NORMS, **(_CONV if kind == "conv" else _ATTENTION),
                     **(_DENSE if i < DENSE else _EXPERTS))
        layers.append({k: get(n.format(i=i)) for k, n in names.items()})
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": layers}


def randomise(scope, seed):
    """Seeded weights with gains away from 1, a router spread wide
    enough that top-k choices are not near ties, a selection bias large
    enough to change them, and taps of the size of the rows they weigh."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("_router_bias"):
            new = 0.3 * rng.randn(*v.shape)
        elif "router" in name:
            new = rng.randn(*v.shape)
        elif name.endswith("_conv_w"):
            new = rng.randn(*v.shape) * 0.6
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def run_forward(seq_len, block, seed=3):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, seed)
        ids = np.random.RandomState(4).randint(0, V, (2, seq_len))
        got = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])[0]
        weights = reference_weights(
            lambda n: np.asarray(scope.find_var(n)))
    return ids, got, weights


# ---------------------------------------------------------------------------
# forward, and what each part is worth
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [24, 3, 2])
def test_forward_matches_reference(seq_len):
    """24 tokens; 3: every tap reads one row; 2: the row before the
    sequence's first is zeros (a prompt of ONE token goes through a
    prefill bucket below)."""
    ids, got, weights = run_forward(seq_len, block_of())
    assert got.shape == (2, seq_len, V)
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


def test_the_parts_of_the_block_each_count():
    """What the tolerance above is far inside of: the reference made
    wrong in one part, or started from another state than the prompt's
    own, moves the logits by a sizeable share of their spread."""
    ids, _, weights = run_forward(24, block_of())
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(state=None, **wrong):
        return float(np.max(np.abs(np.asarray(ref.logits(
            weights, ids[0], HP._replace(**wrong), state=state)) - want))
            / np.std(want))

    assert off_by() == 0.0
    for wrong in (dict(taps="reversed"), dict(taps="dropped"),
                  dict(gates="swapped"), dict(gates="no_c"),
                  dict(select="unbiased"), dict(weigh="biased"),
                  dict(qk_norm="after"), dict(pairing="strided"),
                  dict(theta=10000.0), dict(eps=1e-2)):
        assert off_by(**wrong) > 0.02, wrong
    # the state a sequence leaves is rows n - 2, n - 1 of u: given back
    # to the rows behind them it changes nothing; another sequence's, or
    # the one a padded bucket's end leaves, does
    own = ref.conv_state(weights, ids[0], HP, 17)
    assert own.shape == (STATE_LAYERS, TAPS - 1, DM)
    assert off_by(state=(17, own)) <= 1e-6
    padded = np.concatenate([ids[0][:17], np.zeros(15, ids.dtype)])
    for other in (ref.conv_state(weights, ids[1], HP, 9),
                  ref.conv_state(weights, padded, HP), 0 * own):
        assert off_by(state=(17, other)) > 0.02
    # shorter than the taps: zeros before the first row
    one = ref.conv_state(weights, ids[0], HP, 1)
    assert not np.asarray(one[:, 0]).any() and np.asarray(one[:, 1]).any()


def test_short_conv_gradients_match_the_reference():
    """The trainer trains a conv layer as it is: the op's gradients, for
    its input and its three weights, are jax.grad's of the plain
    reference's convolution."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 11, DM), jnp.float32)
    w = {"in": jnp.asarray(rng.randn(DM, 3 * DM) / 8, jnp.float32),
         "taps": jnp.asarray(rng.randn(TAPS, DM) * 0.6, jnp.float32),
         "out": jnp.asarray(rng.randn(DM, DM) / 8, jnp.float32)}
    probe = jnp.asarray(rng.randn(2, 11, DM), jnp.float32)

    def program(x, w):
        out = attn_ops.short_conv(None, {
            "X": [x], "WIn": [w["in"]], "Taps": [w["taps"]],
            "WOut": [w["out"]]}, {})["Out"][0]
        return jnp.sum(out * probe)

    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            return sum(jnp.sum(ref._conv(x[b], w, HP)[0] * probe[b])
                       for b in range(2))

    got = jax.grad(program, argnums=(0, 1))(x, w)
    want = jax.grad(reference, argnums=(0, 1))(x, w)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.max(np.abs(np.asarray(g - r))) <= 1e-4 * np.max(np.abs(r))


def test_the_block_trains():
    """`transformer_lm_loss` takes the block as it is (a conv layer is
    not refused as a window is), and the loss falls."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=8, n_layers=4, d_model=DM, n_heads=NH,
            d_ff=FF, block=block_of())
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(0, V, (2, 8)),
                "tgt_ids": rng.randint(0, V, (2, 8, 1))}
        taps = np.asarray(scope.find_var("conv0_conv_w")).copy()
        first = float(np.ravel(exe.run(main, feed=feed,
                                       fetch_list=[loss])[0])[0])
        for _ in range(5):
            last = float(np.ravel(exe.run(main, feed=feed,
                                          fetch_list=[loss])[0])[0])
        assert np.abs(np.asarray(scope.find_var("conv0_conv_w"))
                      - taps).max() > 0          # the taps learn
    assert np.isfinite(first) and last < first


def test_the_renormalisations_epsilon_is_an_attribute():
    """Kanana's outputs stay bit for bit (no attribute: 1e-20); LFM2's
    1e-6 moves a weight in its sixth digit."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 9, DM), jnp.float32)
    ins = {"X": [x], "RouterW": [jnp.asarray(rng.randn(DM, E), jnp.float32)],
           "RouterBias": [jnp.asarray(0.3 * rng.randn(E), jnp.float32)],
           "WGate": [jnp.asarray(rng.randn(E, DM, FF) / 8, jnp.float32)],
           "WUp": [jnp.asarray(rng.randn(E, DM, FF) / 8, jnp.float32)],
           "WDown": [jnp.asarray(rng.randn(E, FF, DM) / 4, jnp.float32)]}
    attrs = dict(top_k=TOP_K, router="sigmoid_bias", norm_topk=True)
    was = np.asarray(moe_ops.moe_gated_ffn(None, ins, attrs)["Out"][0])
    same = np.asarray(moe_ops.moe_gated_ffn(
        None, ins, dict(attrs, norm_topk_eps=1e-20))["Out"][0])
    assert np.array_equal(was, same)
    now = np.asarray(moe_ops.moe_gated_ffn(
        None, ins, dict(attrs, norm_topk_eps=1e-6))["Out"][0])
    assert 0 < np.max(np.abs(now - was)) <= 1e-5 * np.max(np.abs(was))
    # the layer leaves the attribute out unless told
    main = pt.Program()
    with pt.program_guard(main, pt.Program()):
        h = pt.layers.data("h", [4, DM], dtype="float32")
        pt.layers.moe_gated_ffn(h, E, FF, TOP_K, norm_topk=True, name="a")
        pt.layers.moe_gated_ffn(h, E, FF, TOP_K, norm_topk=True, name="b",
                                norm_topk_eps=1e-6)
    ops = [op for op in main.global_block.ops if op.type == "moe_gated_ffn"]
    assert "norm_topk_eps" not in ops[0].attrs
    assert ops[1].attrs["norm_topk_eps"] == 1e-6


# ---------------------------------------------------------------------------
# heads 64 wide in the grouped decode kernel, interpreted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("heads,kv_heads", [(32, 8), (8, 2), (2, 2)])
def test_paged_kernel_at_heads_under_a_lane_tile(window, heads, kv_heads):
    """Pools that store two 64-wide heads to a lane tile ([.., H_kv / 2,
    128]) through `_paged_group_kernel`, against the gather form on the
    same pools and on the heads unpacked: lengths that end mid-page, on
    a page's edge, a length of 1, an empty slot."""
    rng = np.random.RandomState(0)
    d, bs, nb, mb = 64, 16, 40, 6
    row = tfm.packed_kv_row(kv_heads, d)
    assert row == [kv_heads // 2, 128]
    q = jnp.asarray(rng.randn(5, heads, d), jnp.float32)
    kp = jnp.asarray(rng.randn(nb, bs, *row), jnp.float32)
    vp = jnp.asarray(rng.randn(nb, bs, *row), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:5 * mb]
                         .reshape(5, mb), jnp.int32)
    lens = jnp.asarray([1, 0, 37, 96, 16], jnp.int32)
    got = pa.paged_decode_attention(q, kp, vp, tables, lens,
                                    interpret=True, window=window)
    want = pa.paged_attention_reference(q, kp, vp, tables, lens,
                                        window=window)
    assert np.max(np.abs(np.asarray(got - want))) <= 2e-6
    assert not np.asarray(got[1]).any()              # the empty slot
    plain = pa.paged_attention_reference(
        q, kp.reshape(nb, bs, kv_heads, d), vp.reshape(nb, bs, kv_heads, d),
        tables, lens, window=window)
    assert np.array_equal(np.asarray(want), np.asarray(plain))
    # written out for the slot of 37 rows
    s, n = 2, 37
    lo = 0 if window is None else n - window
    flat = np.asarray(tables[s])
    k = np.asarray(kp)[flat].reshape(mb * bs, kv_heads, d)[lo:n]
    v = np.asarray(vp)[flat].reshape(mb * bs, kv_heads, d)[lo:n]
    group = heads // kv_heads
    for h in (0, heads - 1):
        sc = k[:, h // group] @ np.asarray(q[s, h]) / 8.0
        p = np.exp(sc - sc.max())
        direct = (p / p.sum()) @ v[:, h // group]
        assert np.max(np.abs(np.asarray(got[s, h]) - direct)) <= 2e-5
    # a new row lands where the unpacked pool would hold it
    new = jnp.asarray(rng.randn(5, kv_heads, d), jnp.float32)
    k2, _ = pa.paged_kv_update(kp, vp, new, new, tables, lens)
    k3, _ = pa.paged_kv_update(kp.reshape(nb, bs, kv_heads, d),
                               vp.reshape(nb, bs, kv_heads, d), new, new,
                               tables, lens)
    assert np.array_equal(np.asarray(k2).reshape(k3.shape), np.asarray(k3))


def test_heads_of_a_whole_lane_tile_are_stored_as_they_were():
    assert tfm.packed_kv_row(8, 128) == [8, 128]
    assert tfm.packed_kv_row(8, 256) == [8, 256]
    assert tfm.packed_kv_row(2, 16) == [2, 16]       # not whole tiles
    assert tfm.packed_kv_row(3, 64) == [3, 64]
    assert tfm.packed_kv_row(8, 64) == [4, 128]
    assert tfm.packed_kv_row(8, 32) == [2, 128]


# ---------------------------------------------------------------------------
# the block's description
# ---------------------------------------------------------------------------

def test_block_spec_says_what_each_layer_is():
    block = block_of()
    kinds = [block.layer(i, FF) for i in range(L)]
    assert [k.mixer for k in kinds] == [
        "short_conv", "short_conv", "attention", "short_conv",
        "short_conv", "short_conv"]
    assert block.cache_kinds(L) == ["state", "state", "full", "state",
                                    "state", "state"]
    assert [k.positions for k in kinds] == ["none", "none", "rope", "none",
                                            "none", "none"]
    assert [(k.ffn, k.ffn_width) for k in kinds] == \
        [("gated", DFF)] * 2 + [("moe_gated", FF)] * 4
    assert block.cache_pools(NH, DM, 0) == {
        "kind": "state", "row_floats": 0, "pools": [],
        "state": [("conv_state", [TAPS - 1, DM])]}
    assert block.cache_pools(NH, DM, 2) == block.cache_pools(NH, DM) == {
        "kind": "kv", "row_floats": 2 * NKV * HD,
        "pools": [("k_cache", [1, 128]), ("v_cache", [1, 128])]}
    assert tfm.BlockSpec.of(block.to_dict()) == block
    assert json.loads(json.dumps(block.to_dict()))["conv_taps"] == TAPS


@pytest.mark.parametrize("bad", [
    dict(conv_taps=0), dict(conv_taps=1), dict(conv_taps=-3),
    dict(layer_pattern=("full",)),              # taps without a conv layer
    dict(layer_pattern=("conv", "attention")),
    dict(attention="mha", n_kv_heads=0, head_dim=0, qk_norm=False),
    dict(norm_topk=False), dict(norm_topk_eps=-1.0),
    dict(index_heads=2, index_head_dim=16, index_topk=4)])
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        block_of(**bad)


_BEFORE = {
    "gpt2": (tfm.GPT2_BLOCK, {
        "kind": "kv", "row_floats": 4096,
        "pools": [("k_cache", [16, 128]), ("v_cache", [16, 128])]}),
    "olmoe": (tfm.BlockSpec(
        norm="rms_norm", positions="rope", qk_norm=True, bias=False,
        ffn="moe_gated", num_experts=64, experts_per_tok=8), {
        "kind": "kv", "row_floats": 4096,
        "pools": [("k_cache", [16, 128]), ("v_cache", [16, 128])]}),
    "kanana": (tfm.BlockSpec(
        norm="rms_norm", norm_eps=1e-6, positions="rope", bias=False,
        attention="latent", kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True,
        ffn="moe_gated", num_experts=128, experts_per_tok=6,
        router="sigmoid_bias", norm_topk=True, routed_scale=2.5,
        shared_width=768, dense_layers=1, dense_width=6144), {
        "kind": "latent", "row_floats": 576,
        "pools": [("latent_cache", [640])]}),
    "keye": (tfm.BlockSpec(
        norm="rms_norm", norm_eps=1e-6, positions="rope", qk_norm=True,
        bias=False, attention="gqa", n_kv_heads=4, head_dim=128,
        index_heads=16, index_head_dim=128, index_topk=2048,
        ffn="moe_gated", num_experts=128, experts_per_tok=8,
        norm_topk=True), {
        "kind": "kv_index", "row_floats": 1152,
        "pools": [("k_cache", [4, 128]), ("v_cache", [4, 128]),
                  ("index_cache", [128])]}),
    "cmda": (tfm.BlockSpec(
        norm="layer_norm_gain", positions="rope", rope_theta=50000.0,
        rope_interleave=True, bias=False, attention="gqa", n_kv_heads=8,
        head_dim=128, ffn="moe_gated", num_experts=128, experts_per_tok=8,
        router="sigmoid", norm_topk=True, shared_width=16384,
        shared_scale=0.25, experts_first=0, experts_held=8, parallel=True,
        tied_head=True, window=4096,
        layer_pattern=("window", "window", "window", "full"),
        full_positions="none"), {
        "kind": "kv", "row_floats": 2048,
        "pools": [("k_cache", [8, 128]), ("v_cache", [8, 128])]}),
}


@pytest.mark.parametrize("family", sorted(_BEFORE))
def test_the_five_bundles_that_were_there_record_what_they_did(family):
    """Their `serving.json` stays byte for byte: the block's dict has no
    key of this PR's, every layer's cache is declared as the one
    declaration was, no layer is a state, and the step's feeds are a
    layer's pools in the order they were."""
    block, cache = _BEFORE[family]
    said = block.to_dict()
    assert "conv_taps" not in said and "norm_topk_eps" not in said
    assert tfm.BlockSpec.of(said) == block
    assert block.cache_pools(16, 2048) == cache
    assert all(block.cache_pools(16, 2048, i) == cache for i in range(4))
    assert set(block.cache_kinds(8)) <= {"full", "window"}
    assert all(block.layer(i).mixer == "attention" for i in range(8))
    blocks_of = {"full": 11, "window": 7}
    for i in range(4):
        assert tfm.cache_feeds(block, i, 16, 2048, 3, 16, blocks_of) == [
            (stem, [blocks_of[block.layer(i).cache], 16] + row)
            for stem, row in cache["pools"]]


# ---------------------------------------------------------------------------
# the bundle: prefill through every bucket, then decode through the state
# ---------------------------------------------------------------------------

def export_cfg(block):
    return dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
                max_context=MAXC, block=block)


def _export(tmp, block, seed=3, pool_blocks=POOL):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [MAXC], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=L, d_model=DM, n_heads=NH,
                           d_ff=FF, max_len=MAXC, block=block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, seed)
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
        pio.export_decode_model(
            tmp, export_cfg(block), scope=scope, length_buckets=BUCKETS,
            slots=SLOTS, block_size=BLOCK, pool_blocks=pool_blocks)
    return tmp, weights


@pytest.fixture(scope="module")
def lfm2_bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("lfm2") / "m"), block_of())


def test_serving_json_declares_a_state_beside_the_pools(lfm2_bundle):
    with open(os.path.join(lfm2_bundle[0], "serving.json")) as f:
        meta = json.load(f)
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    row = 4 * 2 * NKV * HD
    assert dec["cache"] == {
        "kind": "kv", "rows": [[1, 128], [1, 128]],
        "row_floats": 2 * NKV * HD, "bytes_per_token": row,
        "layer_kinds": ["state", "state", "full", "state", "state",
                        "state"],
        "kinds": {"full": {"layers": 1, "pool_blocks": POOL,
                           "blocks_per_seq": MAXC // BLOCK,
                           "bytes_per_token": row},
                  "state": {"layers": STATE_LAYERS,
                            "rows": [[TAPS - 1, DM]],
                            "bytes_per_slot": STATE_ROW_BYTES}}}
    feeds = [(m["name"], m["shape"]) for m in dec["feeds"]]
    state = [SLOTS, TAPS - 1, DM]
    assert feeds == [
        ("token_ids", [SLOTS]), ("context_lens", [SLOTS]),
        ("block_tables", [SLOTS, MAXC // BLOCK]),
        ("conv_state_0", state), ("conv_state_1", state),
        ("k_cache_2", [POOL, BLOCK, 1, 128]),
        ("v_cache_2", [POOL, BLOCK, 1, 128]),
        ("conv_state_3", state), ("conv_state_4", state),
        ("conv_state_5", state), ("moe_stats", [3])]
    assert [m["name"] for m in dec["fetches"]][:8] == [
        "logits", "conv_state_out_0", "conv_state_out_1", "k_cache_out_2",
        "v_cache_out_2", "conv_state_out_3", "conv_state_out_4",
        "conv_state_out_5"]
    assert dec["prefill_roles"]["kv"] == [
        ["conv_state_0"], ["conv_state_1"], ["k_2", "v_2"],
        ["conv_state_3"], ["conv_state_4"], ["conv_state_5"]]
    by_name = {m["name"]: m["shape"] for m in meta["fetches"]}
    assert by_name["conv_state_0"] == [1, TAPS - 1, DM]
    assert by_name["k_2"] == [1, BUCKETS[-1], NKV, HD]
    assert by_name["logits"] == [1, 1, V]
    weights = set(dec["weights"])
    assert "lm_head_w" not in weights and "attn0_q_w" not in weights
    assert {"conv0_in_w", "conv0_conv_w", "conv0_out_w", "attn2_q_w",
            "ffn1_gate_w", "moe2_router_bias"} <= weights
    model = DecodeModel(lfm2_bundle[0], warmup=False)
    desc = model.describe()
    assert desc["cache"] == dec["cache"]
    assert model.state_layers == STATE_LAYERS
    assert model.state_bytes == SLOTS * STATE_ROW_BYTES


def test_describe_says_how_a_block_is_scored(lfm2_bundle):
    """Two 64-wide K/V heads to a lane tile: a product of a block scores
    the heads that read a TILE against that tile's rows."""
    model = DecodeModel(lfm2_bundle[0], warmup=False)
    tiles = NKV * HD // 128
    pages = pa.paged_sparse_block_pages(BLOCK, tiles, 128, np.float32,
                                        MAXC // BLOCK)
    kernel = model.describe()["paged_kernel"]
    assert kernel["heads_per_product"] == NH // tiles
    assert kernel["score_columns_per_block"] == pages * BLOCK


@pytest.mark.parametrize("p_len", [1, 2, 7, 13, 30])
def test_prefill_then_decode_matches_reference(lfm2_bundle, p_len):
    """Prompts shorter than the taps (1, 2), inside a bucket (7, 13: the
    state is what row n - 1 leaves, not what the padding leaves) and near
    a bucket's end, each into slot 1, then teacher-forced steps; a busy
    neighbour rides along in slot 2 and slot 0 stays empty."""
    d, weights = lfm2_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    total = min(p_len + 10, MAXC)
    ids = rng.randint(0, V, total)
    other, o_len = rng.randint(0, V, 40), 21
    want = np.asarray(ref.logits(weights, ids, HP))
    want_other = np.asarray(ref.logits(weights, other, HP))
    tol = 2e-5 * np.std(want)
    blocks, blocks_o = list(range(1, 7)), list(range(20, 26))

    def admit(tokens, blocks, slot):
        last, kv = model.prefill([int(t) for t in tokens])
        model.seed_sequence(blocks[:-(-len(tokens) // BLOCK)], kv, slot=slot)
        return np.asarray(last)

    assert np.max(np.abs(admit(ids[:p_len], blocks, 1)
                         - want[p_len - 1])) <= tol
    assert np.max(np.abs(admit(other[:o_len], blocks_o, 2)
                         - want_other[o_len - 1])) <= tol
    state = np.asarray(ref.conv_state(weights, ids, HP, p_len))
    at = [i for i, t in enumerate(model._pool_table) if t == 2]
    assert len(at) == STATE_LAYERS
    for layer, i in enumerate(at):
        held = np.asarray(model._pools[i])
        assert np.max(np.abs(held[1] - state[layer])) <= 1e-5
        assert not held[0].any()                     # nobody's slot
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    tables[1, :6], tables[2, :6] = blocks, blocks_o
    for j in range(total - p_len):
        tokens[1], lens[1] = ids[p_len + j], p_len + j + 1
        tokens[2], lens[2] = other[o_len + j], o_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[1] - want[p_len + j])) <= tol, j
        assert np.max(np.abs(rows[2] - want_other[o_len + j])) <= tol, j
    assert not np.asarray(model._pools[at[0]])[0].any()   # still nobody's
    # the state is updated in place: pools and states, every byte
    assert model.step_aliased_bytes == sum(
        4 * int(np.prod(s)) for s in model._pool_shapes) \
        > model.state_bytes > 0
    # a slot out of range is refused before anything is written
    _, kv = model.prefill([1, 2, 3])
    with pytest.raises(ValueError, match="slot"):
        model.seed_sequence([1], kv, slot=SLOTS)


def test_the_server_reports_its_routes(lfm2_bundle):
    """`last_routes` are the layers WITH experts, in order, and the
    reference's own choices (the bias chooses): forcing them changes
    nothing and shows no shortfall."""
    d, weights = lfm2_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(9).randint(0, V, 14)
    last, kv = model.prefill([int(t) for t in ids[:13]])
    routes = np.asarray(model.last_routes)[:, :13]
    assert routes.shape == (L - DENSE, 13, TOP_K)
    own = np.asarray(ref.chosen_experts(weights, ids[:13], HP))
    assert np.array_equal(routes, own)
    plain = np.asarray(ref.chosen_experts(
        weights, ids[:13], HP._replace(select="unbiased")))
    assert not np.array_equal(np.sort(plain, -1), np.sort(own, -1))
    forced, shortfall = ref.logits_on_routes(weights, ids[:13], HP, routes,
                                             rows=[12])
    assert not np.asarray(shortfall).any()
    assert np.max(np.abs(np.asarray(forced)[0] - np.asarray(last))) \
        <= 2e-5 * np.std(np.asarray(forced))


# ---------------------------------------------------------------------------
# the engine: the state through everything a slot goes through
# ---------------------------------------------------------------------------

def _greedy(weights, prompt, out):
    want = np.asarray(ref.logits(weights, np.asarray(prompt + out), HP))
    return list(np.argmax(want[len(prompt) - 1:-1], -1))


def _poison(dec):
    """Every pool and every state full of what no sequence wrote."""
    dec.model._pools = [jnp.full_like(p, 1e4).at[0].set(0.0)
                        if t != 2 else jnp.full_like(p, 1e4)
                        for p, t in zip(dec.model._pools,
                                        dec.model._pool_table)]


def _served(dec, weights, prompts, max_new, **kw):
    handles = [dec.generate(p, max_new_tokens=max_new, **kw)
               for p in prompts]
    results = [h.result(timeout=300) for h in handles]
    for prompt, r in zip(prompts, results):
        assert r["tokens"] == _greedy(weights, prompt, r["tokens"])
    return results


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, V, n).tolist() for n in lengths]


def _case_a_slot_reused_by_a_shorter_prompt(d, weights, tmp):
    dec = DecodeEngine(d, max_new_tokens=10, warmup=False)
    _poison(dec)
    for n in (29, 3, 17, 2):        # one at a time: slot 0 every time
        _served(dec, weights, _prompts(n, [n]), 10)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 4
    return dec


def _case_a_prompt_of_one_token(d, weights, tmp):
    dec = DecodeEngine(d, max_new_tokens=12, warmup=False)
    _poison(dec)
    _served(dec, weights, _prompts(21, [1, 1, 2, 1]), 12)
    return dec


def _case_a_preemption_and_resume(d, weights, tmp):
    """A pool too small for three sequences: one is preempted and
    resumes by a prefill of prompt + generated, which rebuilds its
    state in whatever slot it then gets."""
    d, weights = _export(str(tmp / "m"), block_of())
    dec = DecodeEngine(d, pool_blocks=9, max_new_tokens=14, warmup=False)
    _poison(dec)
    results = _served(dec, weights, _prompts(11, [14, 9, 15]), 14)
    assert sum(r["evictions"] for r in results) > 0
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    assert snap["state_seeds"] == snap["prefills"] > 3
    return dec


def _case_an_eviction_by_priority(d, weights, tmp):
    d, weights = _export(str(tmp / "m"), block_of())
    dec = DecodeEngine(d, pool_blocks=9, max_new_tokens=12, warmup=False)
    _poison(dec)
    prompts = _prompts(5, [7, 8, 7])
    handles = [dec.generate(p, max_new_tokens=12, priority=pr)
               for p, pr in zip(prompts, (1, 0, 0))]
    for p, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, p, out)
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    return dec


def _case_a_dispatch_ahead_drain(d, weights, tmp):
    """Three times the slots: most steps are dispatched before the
    tokens of the step before them were read, and every admission
    drains first; a freed slot's next owner starts from ITS state."""
    dec = DecodeEngine(d, max_new_tokens=13, warmup=False)
    _poison(dec)
    lengths = [5, 13, 9, 2, 30, 7, 1, 21, 11]
    handles = [dec.generate(p, max_new_tokens=m) for p, m in zip(
        _prompts(13, lengths), [4, 1, 4, 3, 13, 2, 8, 5, 4])]
    for p, h in zip(_prompts(13, lengths), handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, p, out)
    snap = dec.metrics_snapshot()
    assert snap["steps_ahead"] > 0 and snap["drains"]["admission"] > 0
    return dec


def _case_an_eos_overrun_then_an_admission_into_the_slot(d, weights, tmp):
    """The EOS is step N's token and step N+1 was dispatched with the
    sequence in it: that step moved the slot's state a row on for
    nobody. The sequence admitted into the slot next starts from its
    own state all the same."""
    dec = DecodeEngine(d, max_new_tokens=10, warmup=False)
    prompt, eos, want = None, None, None
    for cand in _prompts(401, [5, 6, 7, 8, 9, 6, 7, 8]):
        out = dec.generate(cand, max_new_tokens=10).result(
            timeout=300)["tokens"]
        for k in range(2, 8):
            if out[k] not in out[:k]:
                prompt, eos, want = cand, out[k], out[:k + 1]
                break
        if prompt:
            break
    assert prompt is not None
    dec.metrics.reset()
    _poison(dec)
    r = dec.generate(prompt, max_new_tokens=10, eos_id=eos).result(
        timeout=300)
    assert r["tokens"] == want and r["finish_reason"] == "eos"
    assert dec.metrics_snapshot()["overrun_tokens"] == 1
    _served(dec, weights, _prompts(419, [6]), 9)     # the same slot
    assert dec.metrics_snapshot()["overrun_tokens"] == 1
    return dec


def _case_permute_blocks(d, weights, tmp):
    """A defrag moves blocks and leaves every state where it is."""
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(17).randint(0, V, 20)
    want = np.asarray(ref.logits(weights, ids, HP))
    _, kv = model.prefill([int(t) for t in ids[:13]])
    model.seed_sequence([30, 31], kv, slot=2)
    before = [np.asarray(p) for p, t in zip(model._pools, model._pool_table)
              if t == 2]
    model.permute_blocks({30: 1, 31: 2})
    after = [np.asarray(p) for p, t in zip(model._pools, model._pool_table)
             if t == 2]
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    tables[2, :3] = [1, 2, 3]
    for j in range(5):
        tokens[2], lens[2] = ids[13 + j], 14 + j
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[2] - want[13 + j])) \
            <= 2e-5 * np.std(want)
    model.copy_block(1, 9)        # a state has no block to copy either
    return None


_CASES = {name[len("_case_"):]: fn for name, fn in sorted(globals().items())
          if name.startswith("_case_")}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_state_is_the_sequences_own(lfm2_bundle, tmp_path, case):
    """Every output is the reference's greedy continuation (the
    reference has no state: it convolves the whole sequence), whatever
    the slot held before; every block comes back; a state needs nothing
    freed."""
    d, weights = lfm2_bundle
    dec = _CASES[case](d, weights, tmp_path)
    if dec is None:
        return
    snap = dec.metrics_snapshot()
    assert dec.pool.blocks_in_use == 0
    # every live slot of every step, the over-run row's too: its state
    # moved a row on for nobody
    assert snap["state_slot_steps"] == STATE_LAYERS * (
        snap["slots_used_sum"] + snap["overrun_tokens"])
    assert snap["state_seed_bytes"] == STATE_ROW_BYTES * snap["state_seeds"]
    assert snap["state_bytes"] == SLOTS * STATE_ROW_BYTES
    dec.shutdown()


def test_through_the_engine_with_its_counters(lfm2_bundle):
    d, weights = lfm2_bundle
    engine = ServingEngine()
    engine.load_decode_model("lfm2", d, warmup=False, max_new_tokens=16)
    dec = engine.decode_engine("lfm2")
    prompts = _prompts(10, [5, 13, 30, 8, 21])
    handles = [engine.generate("lfm2", p, max_new_tokens=16)
               for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, prompt, out)
    snap = dec.metrics_snapshot()
    steps = snap["decode_steps"]
    assert snap["state_slot_steps"] == STATE_LAYERS * snap["slots_used_sum"]
    assert snap["state_seeds"] == snap["prefills"] == 5
    assert snap["state_seed_bytes"] == 5 * STATE_ROW_BYTES
    assert snap["moe_layer_steps"] == (L - DENSE) * steps
    assert snap["moe_assignments"] \
        == TOP_K * (L - DENSE) * snap["slots_used_sum"]
    assert dec.pool.blocks_in_use == 0
    pools = sum(4 * int(np.prod(s)) for s in dec.model._pool_shapes)
    assert snap["step_aliased_bytes"] == pools
    assert snap["cache_bytes_per_token"] == 4 * 2 * NKV * HD    # ONE layer
    text = render_prometheus(engine.metrics.snapshot())
    for line in ('pt_decode_state_slot_steps_total{model="lfm2"} %d'
                 % snap["state_slot_steps"],
                 'pt_decode_state_seeds_total{model="lfm2"} 5',
                 'pt_decode_state_seed_bytes_total{model="lfm2"} %d'
                 % (5 * STATE_ROW_BYTES),
                 'pt_decode_state_bytes{model="lfm2"} %d'
                 % (SLOTS * STATE_ROW_BYTES),
                 'pt_decode_step_aliased_bytes{model="lfm2"} %d' % pools):
        assert line in text, line
    desc = dec.describe()
    assert desc["refuses"] == ["kv_share", "speculation"]
    assert desc["cache"]["kinds"]["state"] == {
        "layers": STATE_LAYERS, "rows": [[TAPS - 1, DM]],
        "bytes_per_slot": STATE_ROW_BYTES}
    engine.shutdown()


def test_a_bundle_without_state_counts_none(tmp_path):
    """No `state_*` in the snapshot or the scrape of a model whose every
    layer is attention, and nothing refused."""
    d, _ = _export(str(tmp_path / "m"),
                   block_of(layer_pattern=(), conv_taps=0))
    dec = DecodeEngine(d, max_new_tokens=4, warmup=False)
    dec.generate([1, 2, 3], max_new_tokens=4).result(timeout=300)
    snap = dec.metrics_snapshot()
    assert not [k for k in snap if k.startswith("state_")]
    assert "pt_decode_state" not in render_prometheus(
        {"decode": {"m": snap}})
    assert dec.describe()["refuses"] == []
    assert dec.model.state_layers == 0 and dec.model.state_bytes == 0
    dec.shutdown()


def test_prefix_sharing_and_speculation_are_refused_at_load(lfm2_bundle):
    d, _ = lfm2_bundle
    model = DecodeModel(d, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="shared prefix"):
        DecodeEngine(model=model, kv_share=True, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="speculation"):
        DecodeEngine(model=model, drafter="ngram", spec_k=2, warmup=False)
    assert issubclass(SequenceStateUnsupported, ValueError)


def test_the_mixer_is_named_in_the_compiled_step(lfm2_bundle):
    """What a profile shows: the conv layers' operations under the scope
    `short_conv`, in the step and in a prefill bucket."""
    d, _ = lfm2_bundle
    model = DecodeModel(d, warmup=False)
    model.decode_step(np.zeros(SLOTS, np.int64), np.zeros(SLOTS, np.int32),
                      np.zeros((SLOTS, MAXC // BLOCK), np.int32)).tokens
    assert "short_conv" in model._step.as_text()
    calls = model._admit_fns[BUCKETS[0]]
    text = calls.prefill.lower(
        calls.weights, np.zeros(calls.ids_shape, calls.ids_dtype),
        np.int32(3)).compile().as_text()
    assert "short_conv" in text
