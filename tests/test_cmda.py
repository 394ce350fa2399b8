"""Command A+'s block (a 3 : 1 pattern of window and full attention layers
with two kinds of cache, a parallel attention + expert block over ONE
bias-free LayerNorm, interleaved rotary positions on window layers and
none on full ones, a tied head, and one chip's share of a sigmoid-routed
expert layer beside averaged shared experts) through the three builders
of `models/transformer.py` and the decode engine, against the plain
reference `benchmark/reference_cmda.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. Two periods of the pattern (8 layers), a window of 8 rows over
blocks of 4, 16 experts of which this program holds 4.
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
                                              WindowCacheUnsupported)
from paddle_tpu.serving.decode.kv_cache import window_blocks
from paddle_tpu.serving.metrics import render_prometheus

from references import by_path

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
moe_ops = importlib.import_module("paddle_tpu.ops.moe_ops")

ref = by_path("reference_cmda")
HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, NKV, HD, FF, E, TOP_K = 97, 8, 64, 8, 2, 16, 16, 16, 4
FIRST, HELD, SHARED = 4, 4, 2          # experts 4..7 of 16; two shared
WINDOW, MAXC, BLOCK, POOL, SLOTS = 8, 48, 4, 40, 3
BUCKETS = (8, 16, 32)
EPS, THETA = 1e-5, 50000.0
PATTERN = ("window", "window", "window", "full")
KINDS = tuple("sliding_attention" if k == "window" else "full_attention"
              for k in PATTERN) * (L // len(PATTERN))
WBLOCKS = WINDOW // BLOCK + 1          # blocks a slot holds of a window


def block_of(**changes):
    spec = dict(norm="layer_norm_gain", norm_eps=EPS, positions="rope",
                rope_theta=THETA, rope_interleave=True, bias=False,
                attention="gqa", n_kv_heads=NKV, head_dim=HD,
                ffn="moe_gated", num_experts=E, experts_per_tok=TOP_K,
                router="sigmoid", norm_topk=True,
                shared_width=SHARED * FF, shared_scale=1.0 / SHARED,
                experts_first=FIRST, experts_held=HELD, parallel=True,
                tied_head=True, window=WINDOW, layer_pattern=PATTERN,
                full_positions="none")
    spec.update(changes)
    return tfm.BlockSpec(**spec)


def whole_block(**changes):
    """Every expert held: the uncut layer."""
    return block_of(experts_first=0, experts_held=0, **changes)


HP = ref.Hyper(NH, NKV, HD, WINDOW, KINDS, TOP_K, SHARED, FIRST, EPS, THETA)

LAYER_NAME = {"ln": "ln1_{i}_scale", "q": "attn{i}_q_w", "k": "attn{i}_k_w",
              "v": "attn{i}_v_w", "out": "attn{i}_out_w",
              "router": "moe{i}_router_w", "gate": "moe{i}_gate_w",
              "up": "moe{i}_up_w", "down": "moe{i}_down_w",
              "shared_gate": "moe{i}_shared_gate_w",
              "shared_up": "moe{i}_shared_up_w",
              "shared_down": "moe{i}_shared_down_w"}


def reference_weights(get, n_layers=L):
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": [{key: get(name.format(i=i))
                        for key, name in LAYER_NAME.items()}
                       for i in range(n_layers)]}


def randomise(scope, seed):
    """Seeded weights with gains away from 1 and a router spread wide
    enough that top-k choices are not near ties."""
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
            # sharp heads: which rows are read decides the output
            new = rng.randn(*v.shape) * (1.5 / np.sqrt(v.shape[-2]))
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def forward_program(seq_len, block, **kw):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block, **kw)
    return main, startup, logits


def run_forward(seq_len, block, seed=3):
    main, startup, logits = forward_program(seq_len, block)
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
# forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [24, 8, 3])
def test_forward_matches_reference(seq_len):
    """24 tokens: every row past the 8th reads its window alone on six
    of the eight layers; 8 and 3: no window prunes."""
    ids, got, weights = run_forward(seq_len, block_of())
    assert got.shape == (2, seq_len, V)
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


def test_the_parts_of_the_block_each_count():
    """What the tolerance above is far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread."""
    ids, _, weights = run_forward(24, block_of())
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(**wrong):
        return float(np.max(np.abs(np.asarray(ref.logits(
            weights, ids[0], HP._replace(**wrong))) - want)) / np.std(want))

    assert off_by() == 0.0
    for wrong in (dict(window_off=1), dict(window_off=-1),
                  dict(rotate="all"), dict(rotate="none"), dict(page=BLOCK),
                  dict(shared="sum"), dict(pairing="strided"),
                  dict(drop=True), dict(first=0), dict(theta=10000.0),
                  dict(kinds=("full_attention",) * L)):
        assert off_by(**wrong) > 0.02, wrong


def test_the_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's share test: the layer cut into shares
    of its experts (here 4 shares of 4), each share's routed part plus
    the shared experts counted ONCE and the attention, is the uncut
    reference's layer; and the program's share is the reference's."""
    rng = np.random.RandomState(5)
    x = rng.randn(20, DM).astype(np.float32)
    layer = {"ln": 1 + 0.2 * rng.randn(DM),
             "q": rng.randn(DM, NH * HD) / 8, "k": rng.randn(DM, NKV * HD) / 8,
             "v": rng.randn(DM, NKV * HD) / 8,
             "out": rng.randn(NH * HD, DM) / 11, "router": rng.randn(DM, E),
             "gate": rng.randn(E, DM, FF) / 8, "up": rng.randn(E, DM, FF) / 8,
             "down": rng.randn(E, FF, DM) / 4,
             "shared_gate": rng.randn(DM, SHARED * FF) / 8,
             "shared_up": rng.randn(DM, SHARED * FF) / 8,
             "shared_down": rng.randn(SHARED * FF, DM) / 4}
    layer = {k: jnp.asarray(v, jnp.float32) for k, v in layer.items()}
    with jax.default_matmul_precision("highest"):
        a, whole, shared, chosen, _ = ref.layer_parts(
            x, layer, HP._replace(first=0), KINDS[0])
        parts = []
        for first in range(0, E, HELD):
            cut = dict(layer, **{k: layer[k][first:first + HELD]
                                 for k in ("gate", "up", "down")})
            a_s, routed, shared_s, chosen_s, _ = ref.layer_parts(
                x, cut, HP._replace(first=first), KINDS[0])
            assert np.array_equal(chosen_s, chosen)   # the router is whole
            assert np.array_equal(a_s, a) and np.array_equal(shared_s,
                                                             shared)
            parts.append(np.asarray(routed))
    whole = np.asarray(whole)
    assert np.max(np.abs(sum(parts) - whole)) <= 1e-5 * np.std(whole)
    assert all(np.abs(p).max() > 0 for p in parts)

    # the op, told which experts it holds, gives that share and no more
    h = ref._ln(jnp.asarray(x), layer["ln"], EPS)
    for first in (0, 4, 12):
        ins = {"X": [h[None]], "RouterW": [layer["router"]],
               "WGate": [layer["gate"][first:first + HELD]],
               "WUp": [layer["up"][first:first + HELD]],
               "WDown": [layer["down"][first:first + HELD]]}
        outs = moe_ops.moe_gated_ffn(None, ins, dict(
            top_k=TOP_K, router="sigmoid", norm_topk=True,
            first_expert=first))
        got = np.asarray(outs["Out"][0][0])
        assert np.max(np.abs(got - parts[first // HELD])) \
            <= 2e-5 * np.std(whole)
        assert np.array_equal(np.sort(outs["Experts"][0][0], -1),
                              np.sort(chosen, -1))
        pairs = int(np.sum((chosen >= first) & (chosen < first + HELD)))
        touched = len(set(np.asarray(chosen)[(chosen >= first)
                                             & (chosen < first + HELD)]))
        assert list(outs["Stats"][0]) == [20 * TOP_K, touched, 1, pairs]


def test_with_every_expert_held_the_op_is_what_it_was():
    """Bit for bit: `first_expert` absent, the sorted dispatch over all
    experts, three counters; and a share of ALL experts, which the waves
    compute, agrees with it to the order of the sums."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 9, DM), jnp.float32)
    ins = {"X": [x], "RouterW": [jnp.asarray(rng.randn(DM, E), jnp.float32)],
           "WGate": [jnp.asarray(rng.randn(E, DM, FF) / 8, jnp.float32)],
           "WUp": [jnp.asarray(rng.randn(E, DM, FF) / 8, jnp.float32)],
           "WDown": [jnp.asarray(rng.randn(E, FF, DM) / 4, jnp.float32)]}
    attrs = dict(top_k=TOP_K, router="softmax")
    was = moe_ops.moe_gated_ffn(None, ins, attrs)
    xt = x.reshape(-1, DM)
    logits = jnp.dot(xt, ins["RouterW"][0],
                     precision=jax.lax.Precision.HIGHEST)
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), TOP_K)
    direct = moe_ops._experts_sorted(xt, experts, gates, ins["WGate"][0],
                                     ins["WUp"][0], ins["WDown"][0])
    assert np.array_equal(np.asarray(was["Out"][0]).reshape(-1, DM),
                          np.asarray(direct))
    assert was["Stats"][0].shape == (3,)
    share, sizes = moe_ops._experts_held(
        xt, experts, gates, ins["WGate"][0], ins["WUp"][0],
        ins["WDown"][0], 0)
    assert int(sizes.sum()) == 18 * TOP_K
    assert np.max(np.abs(np.asarray(share) - np.asarray(direct))) <= 1e-5
    with pytest.raises(ValueError):     # a share has to say which
        moe_ops.moe_gated_ffn(None, dict(ins, WGate=[ins["WGate"][0][:4]]),
                              attrs)


def test_the_held_pairs_go_through_in_waves(monkeypatch):
    """A prompt's pairs on the held experts take as many passes of
    `_HELD_WAVE_ROWS` rows as there are pairs: the same sums, whatever
    the wave."""
    rng = np.random.RandomState(7)
    n = 40
    xt = jnp.asarray(rng.randn(n, DM), jnp.float32)
    experts = jnp.asarray(np.stack([rng.permutation(E)[:TOP_K]
                                    for _ in range(n)]), jnp.int32)
    gates = jnp.asarray(rng.rand(n, TOP_K), jnp.float32)
    w = [jnp.asarray(rng.randn(HELD, *s) / 8, jnp.float32)
         for s in ((DM, FF), (DM, FF), (FF, DM))]
    one, sizes = moe_ops._experts_held(xt, experts, gates, *w, FIRST)
    held = (np.asarray(experts) >= FIRST) & (np.asarray(experts)
                                             < FIRST + HELD)
    assert int(sizes.sum()) == int(held.sum()) > 16
    monkeypatch.setattr(moe_ops, "_HELD_WAVE_ROWS", 16)
    waves, _ = jax.jit(lambda *a: moe_ops._experts_held(*a, FIRST))(
        xt, experts, gates, *w)
    assert np.max(np.abs(np.asarray(waves) - np.asarray(one))) <= 1e-5
    # written out for one token
    t = int(np.argmax(held.sum(1)))
    direct = sum(
        float(gates[t, j]) * (jax.nn.silu(xt[t] @ w[0][e - FIRST])
                              * (xt[t] @ w[1][e - FIRST])) @ w[2][e - FIRST]
        for j, e in enumerate(np.asarray(experts[t])) if held[t, j])
    assert np.max(np.abs(np.asarray(one[t]) - np.asarray(direct))) <= 1e-4


# ---------------------------------------------------------------------------
# the kernels, interpreted, against the masked dense forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,sk,block,window,heads,kv_heads", [
    (512, 512, 128, 200, 4, 2),     # the edge inside a block
    (512, 512, 128, 128, 2, 2),     # on a block boundary
    (512, 512, 128, 129, 4, 1),     # one row past it
    (256, 512, 128, 256, 2, 1),     # a chunk of query rows: keys before it
    (512, 512, 256, 256, 2, 2),     # a window of one block: in strips
    (384, 640, 128, 300, 4, 2),
])
def test_windowed_flash_forward_matches_the_masked_dense_form(
        sq, sk, block, window, heads, kv_heads):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, sq, heads, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, sk, kv_heads, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, sk, kv_heads, 128), jnp.float32)
    got = fa.flash_attention(q, k, v, causal=True, block_q=block,
                             block_k=block, interpret=True, window=window)
    want = fa.mha_reference(q, k, v, causal=True, window=window)
    assert np.max(np.abs(np.asarray(got - want))) <= 2e-6
    # the mask, written out: row t of the last sq positions reads s with
    # s <= t and t - s < window
    t = np.arange(sk - sq, sk)[:, None]
    s = np.arange(sk)[None]
    seen = (s <= t) & (t - s < window)
    sc = np.einsum("qhd,khd->hqk", np.asarray(q[0]), np.repeat(
        np.asarray(k[0]), heads // kv_heads, 1)) / np.sqrt(128.0)
    sc = np.where(seen[None], sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    direct = np.einsum("hqk,khd->qhd", p, np.repeat(
        np.asarray(v[0]), heads // kv_heads, 1))
    assert np.max(np.abs(np.asarray(got[0]) - direct)) <= 2e-5
    plan = fa.flash_block_plan(sq, sk, block, block, True, jnp.float32,
                               window)
    oldest = (plan.n_q - 1) * block + plan.q_off    # the last rows' `ahead`
    assert (plan.behind > 0) == (oldest >= window + block - 1)
    assert plan.skipped + plan.diagonal + plan.full + plan.edge \
        == plan.n_q * plan.n_k


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("strips", [1, 2, 4, 8])
@pytest.mark.parametrize("band", ["four_blocks_cross_length",
                                  "under_a_block"])
def test_a_prefill_chunks_band_runs_in_strips(flash_in_strips, band, strips,
                                              dtype):
    """A chunk of query rows against the keys up to its end, as Command
    A+'s window layers prefill (a window of four blocks: the band is the
    edge block, three whole ones and the diagonal's: five steps where the
    keys have six blocks); and a window narrower than a block, where a
    strip would be crossed twice: the whole block with both masks."""
    block = 128 * strips
    if band == "under_a_block":
        plan = flash_in_strips(2 * block, 3 * block, block, block // 2,
                               strips, dtype)
        assert (plan.strips, plan.edge_strips) == (1, 1)
        assert (plan.band_k, plan.band_q, plan.blocks_run) == (2, 2, 4.0)
        return
    plan = flash_in_strips(block, 6 * block, block, 4 * block, strips,
                           dtype)
    assert (plan.strips, plan.edge_strips) == (strips, strips)
    assert (plan.band_k, plan.band_q, plan.behind) == (5, 1, 1)
    assert (plan.edge, plan.full, plan.diagonal) == (1, 3, 1)
    assert plan.blocks_run == 3 + 2 * (strips + 1) / (2 * strips)
    assert round(plan.blocks_inside, 2) == 4.0


#: every forward call the seven cells' prefills and the trainer make:
#: (batch-heads, K/V batch-heads, sq, sk, d, dv, dtype, window), the
#: blocks `_default_block`'s. Command A+'s are its chunks of 1,024 query
#: rows against the keys up to their end (from the window's first block
#: on a window layer); Keye's buckets make none without a selection
_CELL_CALLS = (
    [(64, 64, 2048, 2048, 128, 128, "bfloat16", None)]            # train
    + [(16, 16, s, s, 128, 128, "float32", None)
       for s in (128, 256, 512, 1024)]                  # Cerebras, OLMoE
    + [(32, 32, s, s, 192, 128, "float32", None)
       for s in (2048, 4096, 6144)]                                # Kanana
    + [(128, 8, 1024, sk, 128, 128, "float32", None)
       for sk in (1024, 2048, 3072, 4096, 5120, 6144)]       # Command A+
    + [(128, 8, 1024, sk, 128, 128, "float32", 4096)
       for sk in (1024, 2048, 3072, 4096, 5120)]
    + [(32, 32, s, s, 64, 64, "float32", None)
       for s in (2048, 4096, 6144)])                                 # LFM2

#: every (sq, sk, block_q, block_k, dtype) of them that is square (and
#: two smaller ones the tests' own bundles use)
_CELL_SHAPES = sorted({(sq, sk, fa._default_block(sq), fa._default_block(sk),
                        dtype)
                       for _, _, sq, sk, _, _, dtype, window in _CELL_CALLS
                       if sq == sk and window is None}
                      | {(64, 64, 64, 64, "float32")})


@pytest.mark.parametrize("shape", _CELL_SHAPES, ids=str)
def test_without_a_window_the_plan_is_what_it_was(shape):
    """The plan of every shape the cells that were there use, computed as
    PR 36's `flash_block_plan` computed it."""
    sq, sk, bq, bk, dtype = shape
    plan = fa.flash_block_plan(sq, sk, bq, bk, True, dtype)
    n_q, n_k = sq // bq, sk // bk
    skipped = sum(ik > iq for iq in range(n_q) for ik in range(n_k))
    full = n_q * n_k - skipped - n_q
    assert plan[:7] + plan[8:16] == (
        bq, bk, n_q, n_k, 0, True, jnp.dtype(dtype),
        None, 0, 0, skipped, n_q, full, n_k, n_q)
    # the forward keeps PR 36's halves (the sweep read no narrower strip
    # faster: PERF.md section 6, PR 64), and the products they leave
    assert plan.strips == 1 + (n_k > 1 and bq % 256 == 0)
    assert plan.blocks_run == full + n_q * (plan.strips + 1) / (
        2 * plan.strips)
    assert fa.flash_block_plan(sq, sk, bq, bk, False, dtype)[8:] \
        == (None, 0, 0, 0, 0, n_q * n_k, n_k, n_q, n_q * n_k, n_q * n_k)


def _traced_forward(kernels, call, selected=False):
    """The text of the forward wrapper's jaxpr at one call's shapes (the
    kernel's body, grid and blocks) and of every operand's `index_map`."""
    bh, bh_kv, sq, sk, d, dv, dtype, window = call
    of = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    args = [of(bh, sq, d), of(bh_kv, sk, d), of(bh_kv, sk, dv)]
    if selected:
        args.append(jax.ShapeDtypeStruct((1, sq, sk), jnp.int8))
    jaxpr = jax.make_jaxpr(lambda *a: kernels._flash_fwd(
        *a, scale=0.125, causal=True, block_q=kernels._default_block(sq),
        block_k=kernels._default_block(sk), window=window))(*args)
    text = [str(jaxpr)]
    for eqn in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns:
        if eqn.primitive.name == "pallas_call":
            text += [str(m.index_map_jaxpr)
                     for m in eqn.params["grid_mapping"].block_mappings]
    return "\n".join(text)


#: sha256 of `_traced_forward` at `_CELL_CALLS`, of the kernel file as it
#: was before the forward took a selection (commit e127f6c): what a call
#: without one must still trace, to the letter. A PR that changes the
#: forward for every caller prints them anew (`_traced_forward` over its
#: own file) and says so. PR 64 read the five WINDOWED calls anew, on
#: purpose: their grid's k axis is the band (`band_k` steps from
#: `_first_k`) and the edge block runs in halves; the seventeen calls
#: without a window trace what they traced.
_FORWARD_AS_IT_WAS = {
    (64, 64, 2048, 2048, 128, 128, 'bfloat16', None): "1c7aff3a86d1344a",
    (16, 16, 128, 128, 128, 128, 'float32', None): "65d71c0f697e9387",
    (16, 16, 256, 256, 128, 128, 'float32', None): "d88eb33cb032e883",
    (16, 16, 512, 512, 128, 128, 'float32', None): "ec897b0b6a2a596a",
    (16, 16, 1024, 1024, 128, 128, 'float32', None): "dc78b7dc05a827a1",
    (32, 32, 2048, 2048, 192, 128, 'float32', None): "9070eb0228604165",
    (32, 32, 4096, 4096, 192, 128, 'float32', None): "44fb66352606ef6a",
    (32, 32, 6144, 6144, 192, 128, 'float32', None): "9e9b44126271df8e",
    (128, 8, 1024, 1024, 128, 128, 'float32', None): "579642b230590c14",
    (128, 8, 1024, 2048, 128, 128, 'float32', None): "86e240288049b149",
    (128, 8, 1024, 3072, 128, 128, 'float32', None): "b609bfdb57918b0f",
    (128, 8, 1024, 4096, 128, 128, 'float32', None): "13e69879969f11bf",
    (128, 8, 1024, 5120, 128, 128, 'float32', None): "569efa6c50236a92",
    (128, 8, 1024, 6144, 128, 128, 'float32', None): "1ca0bff8e014971c",
    (128, 8, 1024, 1024, 128, 128, 'float32', 4096): "14be3ce3976d3e4a",
    (128, 8, 1024, 2048, 128, 128, 'float32', 4096): "348ea9264afee1bd",
    (128, 8, 1024, 3072, 128, 128, 'float32', 4096): "18fac2122206146e",
    (128, 8, 1024, 4096, 128, 128, 'float32', 4096): "88104a586a1be2ca",
    (128, 8, 1024, 5120, 128, 128, 'float32', 4096): "c658f371047e2032",
    (32, 32, 2048, 2048, 64, 64, 'float32', None): "b89656f5bc977e8c",
    (32, 32, 4096, 4096, 64, 64, 'float32', None): "87ab376024ca4c5e",
    (32, 32, 6144, 6144, 64, 64, 'float32', None): "f7696ae98408285a",
}


@pytest.mark.parametrize("call", _CELL_CALLS, ids=str)
def test_without_a_selection_the_traced_forward_is_what_it_was(call):
    """At every call the seven cells make, the forward without a
    selection traces the kernel it traced before it could take one: the
    same body, grid, blocks, scratch and `index_map`s. With one, another
    kernel (one operand more), and the plan is still the same plan."""
    import hashlib
    text = _traced_forward(fa, call)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _FORWARD_AS_IT_WAS[call]
    if call[-1] is None and call[2] == call[3]:
        assert _traced_forward(fa, call, selected=True) != text


def _pools(rng, heads, d=128, n_blocks=40, bs=8):
    return (rng.randn(n_blocks, bs, heads, d).astype(np.float32),
            rng.randn(n_blocks, bs, heads, d).astype(np.float32))


@pytest.mark.parametrize("window", [None, 16, 17, 24, 5])
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (32, 2), (4, 4)])
def test_windowed_paged_walk_matches_the_masked_dense_form(window, heads,
                                                            kv_heads):
    """Window edges inside a page, on a page boundary and one row past
    it; contexts under, at and over the window; an empty slot; entries
    behind the window NULL, as the scheduler leaves them."""
    rng = np.random.RandomState(1)
    k_pool, v_pool = _pools(rng, kv_heads)
    q = rng.randn(4, heads, 128).astype(np.float32)
    for lens in ([90, 0, 33, 8], [96, 1, 16, 17], [17, 40, 7, 24]):
        lens = np.asarray(lens, np.int32)
        tables = np.zeros((4, 12), np.int32)
        free = list(rng.permutation(np.arange(1, 40)))
        for s, n in enumerate(lens):
            first = 0 if window is None else max(n - window, 0) // 8
            for j in range(first, -(-n // 8)):
                tables[s, j] = free.pop()
        got = np.asarray(pa.paged_decode_attention(
            q, k_pool, v_pool, tables, lens, interpret=True, window=window))
        want = np.asarray(pa.paged_attention_reference(
            q, k_pool, v_pool, tables, lens, window=window))
        assert np.max(np.abs(got - want)) <= 2e-6
        # written out for slot 0
        n = int(lens[0])
        lo = 0 if window is None else max(n - window, 0)
        group = heads // kv_heads
        rows_k = np.stack([k_pool[tables[0, p // 8], p % 8]
                           for p in range(lo, n)])
        rows_v = np.stack([v_pool[tables[0, p // 8], p % 8]
                           for p in range(lo, n)])
        sc = np.einsum("hd,khd->hk", q[0], np.repeat(rows_k, group, 1)) \
            / np.sqrt(128.0)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        direct = np.einsum("hk,khd->hd", p, np.repeat(rows_v, group, 1))
        assert np.max(np.abs(got[0] - direct)) <= 2e-5
        assert not got[lens == 0].any()


def test_grouped_flash_gradients_match_the_dense_form():
    """K and V of fewer heads than q through the kernels' backward: a
    group's dk and dv are the sums over its query heads."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 256, 4, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 128), jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 128), jnp.float32)
    w = jnp.asarray(rng.randn(1, 256, 4, 128), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(w * fa.flash_attention(
        *a, causal=True, block_q=128, block_k=128, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * fa.mha_reference(
        *a, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert np.max(np.abs(np.asarray(g - r))) <= 2e-4
    # and under a window band (PR 62: dq walks the forward's band, dk/dv
    # its transpose; until then a typed refusal)
    got = jax.grad(lambda *a: jnp.sum(w * fa.flash_attention(
        *a, causal=True, block_q=128, block_k=128, interpret=True,
        window=100)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * fa.mha_reference(
        *a, causal=True, window=100)), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        assert np.max(np.abs(np.asarray(g - r))) <= 2e-4


def test_window_blocks_are_the_ones_the_window_reaches():
    for length in range(0, 60):
        first, count = window_blocks(length, WINDOW, BLOCK)
        held = sorted({p // BLOCK for p in range(max(length - WINDOW, 0),
                                                 length)})
        assert list(range(first, first + count)) == held
        assert count <= WBLOCKS


# ---------------------------------------------------------------------------
# BlockSpec
# ---------------------------------------------------------------------------

def test_block_spec_says_what_each_layer_is():
    blk = block_of()
    assert tfm.BlockSpec.of(json.loads(json.dumps(blk.to_dict()))) == blk
    assert blk.cache_kinds(L) == list(PATTERN) * 2
    first, fourth = blk.layer(0, FF), blk.layer(3, FF)
    assert (first.window, first.positions, first.cache) \
        == (WINDOW, "rope", "window")
    assert (fourth.window, fourth.positions, fourth.cache) \
        == (0, "none", "full")
    assert (first.ffn, first.ffn_width) == ("moe_gated", FF)
    assert blk.held_experts == HELD and whole_block().held_experts == E
    # the keys a block without the pattern records are the ones it had:
    # a field at its default is not said, the base ones apart, and none
    # of this model's fields is in an older block's record
    mine = {"parallel", "tied_head", "window", "layer_pattern",
            "full_positions", "shared_scale", "experts_first",
            "experts_held"}
    assert not mine & set(tfm.GPT2_BLOCK.to_dict())
    assert mine <= set(blk.to_dict())


@pytest.mark.parametrize("bad", [
    dict(window=0), dict(layer_pattern=()), dict(layer_pattern=("ring",)),
    dict(attention="mha", n_kv_heads=0, head_dim=0, rope_interleave=False),
    dict(full_positions="learned"), dict(experts_held=E + 1),
    dict(experts_first=14), dict(experts_held=0),
    dict(experts_first=0, experts_held=E), dict(router="softmax_bias"),
    dict(ffn="gated", num_experts=0, experts_per_tok=0, router="softmax",
         norm_topk=False, shared_width=0),
    dict(norm="layer_norm_nobias")])
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        block_of(**bad)


@pytest.mark.parametrize("windowed", [True, False],
                         ids=["window_and_full", "every_layer_full"])
def test_the_trainer_trains_a_window(windowed):
    """The flash backward has a window band since PR 62 (until then a
    typed refusal that said so, as for an indexer): the block trains as
    it is, and with every layer full."""
    block = block_of() if windowed else block_of(window=0,
                                                 layer_pattern=())
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        loss, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=8, n_layers=4, d_model=DM, n_heads=NH,
            d_ff=FF, block=block)
        pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(0, V, (2, 8)),
                "tgt_ids": rng.randint(0, V, (2, 8, 1))}
        first = float(np.ravel(exe.run(main, feed=feed,
                                       fetch_list=[loss])[0])[0])
        for _ in range(5):
            last = float(np.ravel(exe.run(main, feed=feed,
                                          fetch_list=[loss])[0])[0])
    assert np.isfinite(first) and last < first


# ---------------------------------------------------------------------------
# the bundle: prefill through every bucket, then decode through the pools
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
def cmda_bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("cmda") / "m"), block_of())


def test_describe_says_how_a_block_is_scored(cmda_bundle):
    """Groups of query heads share K/V heads: a block of the paged
    kernel is a product a K/V head, its score columns the block's
    rows once."""
    model = DecodeModel(cmda_bundle[0], warmup=False)
    pages = pa.paged_sparse_block_pages(BLOCK, NKV, HD, np.float32,
                                        MAXC // BLOCK)
    assert model.describe()["paged_kernel"] == {
        "pages_per_block": pages,
        "max_blocks_per_call": SLOTS * -(-(MAXC // BLOCK) // pages),
        "heads_per_product": NH // NKV,
        "score_columns_per_block": pages * BLOCK}
    assert model.describe()["sparse_kernel"] is None


def test_serving_json_declares_two_kinds_of_cache(cmda_bundle):
    with open(os.path.join(cmda_bundle[0], "serving.json")) as f:
        meta = json.load(f)
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    row = 4 * 2 * NKV * HD
    wpool = SLOTS * WBLOCKS + 1
    assert dec["cache"] == {
        "kind": "kv", "rows": [[NKV, HD], [NKV, HD]],
        "row_floats": 2 * NKV * HD, "bytes_per_token": row * L,
        "layer_kinds": list(PATTERN) * 2, "window": WINDOW,
        "kinds": {"full": {"layers": 2, "pool_blocks": POOL,
                           "blocks_per_seq": MAXC // BLOCK,
                           "bytes_per_token": 2 * row},
                  "window": {"layers": 6, "pool_blocks": wpool,
                             "blocks_per_seq": WBLOCKS,
                             "bytes_per_token": 6 * row}}}
    names = [m["name"] for m in dec["feeds"]]
    assert names[:4] == ["token_ids", "context_lens", "block_tables",
                         "window_tables"]
    assert names[-1] == "moe_stats" and dec["feeds"][-1]["shape"] == [4]
    assert dec["moe_stats"]["fields"] == [
        "assignments", "experts_touched", "layer_steps", "held_pairs"]
    shapes = [m["shape"][0] for m in dec["feeds"][4:4 + 2 * L]]
    assert shapes == [wpool] * 6 + [POOL] * 2 + [wpool] * 6 + [POOL] * 2
    # no head of its own, no second norm, the held experts' weights only
    weights = set(dec["weights"])
    assert "lm_head_w" not in weights and "ln2_0_scale" not in weights
    assert "ln1_0_bias" not in weights and "tok_emb" in weights
    model = DecodeModel(cmda_bundle[0], warmup=False)
    assert model.weights["moe0_gate_w"].shape == (HELD, DM, FF)
    assert model.weights["moe0_router_w"].shape == (DM, E)
    desc = model.describe()
    assert desc["cache"] == dec["cache"]
    assert (model.window, model.window_blocks_per_seq,
            model.window_pool_blocks) == (WINDOW, WBLOCKS, wpool)


@pytest.mark.parametrize("p_len", [6, 8, 13, 30])
def test_prefill_then_paged_decode_matches_reference(cmda_bundle, p_len):
    """Prompts under the window (6: the context crosses it while
    decoding), at it (8), past it (13) and near four windows long (30,
    the 32 bucket), each seeded with its last window alone, then
    teacher-forced steps with the blocks behind the window released as
    the scheduler releases them. A busy neighbour rides along."""
    d, weights = cmda_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    total = min(p_len + 12, MAXC)
    ids = rng.randint(0, V, total)
    other = rng.randint(0, V, 40)
    o_len = 21
    want = np.asarray(ref.logits(weights, ids, HP))
    want_other = np.asarray(ref.logits(weights, other, HP))
    tol = 2e-5 * np.std(want)

    def admit(tokens, blocks, wfree):
        n = len(tokens)
        last, kv = model.prefill([int(t) for t in tokens])
        first, count = window_blocks(n, WINDOW, BLOCK)
        held = [wfree.pop() for _ in range(count)]
        model.seed_sequence(blocks[:-(-n // BLOCK)], kv, window_ids=held)
        return np.asarray(last), first, held

    wfree = list(range(SLOTS * WBLOCKS, 0, -1))
    blocks, blocks_o = list(range(1, 13)), list(range(20, 32))
    last, wstart, held = admit(ids[:p_len], blocks, wfree)
    last_o, wstart_o, held_o = admit(other[:o_len], blocks_o, wfree)
    assert np.max(np.abs(last - want[p_len - 1])) <= tol
    assert np.max(np.abs(last_o - want_other[o_len - 1])) <= tol

    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    tables[0, :12], tables[2, :12] = blocks, blocks_o
    state = {0: [wstart, held], 2: [wstart_o, held_o]}
    for j in range(total - p_len):
        wtables = np.zeros_like(tables)
        for slot, length in ((0, p_len + j + 1), (2, o_len + j + 1)):
            start, mine = state[slot]
            first, count = window_blocks(length, WINDOW, BLOCK)
            while start < first:        # released BEFORE the new block
                wfree.append(mine.pop(0))
                start += 1
            while start + len(mine) < first + count:
                mine.append(wfree.pop())
            assert len(mine) <= WBLOCKS
            state[slot][0] = start
            wtables[slot, start:start + len(mine)] = mine
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        tokens[2], lens[2] = other[o_len + j], o_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables, wtables))
        assert np.max(np.abs(rows[0] - want[p_len + j])) <= tol, j
        assert np.max(np.abs(rows[2] - want_other[o_len + j])) <= tol, j
    # a table with every block still in it reads the same: nothing
    # behind the window is ever read (one table for both kinds)
    model.reset_pools()
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence(blocks[:-(-p_len // BLOCK)], kv)
    tokens[:], lens[:], tables[:] = 0, 0, 0
    tables[0, :9] = blocks[:9]       # ids the window pool has too
    tokens[0], lens[0] = ids[p_len], p_len + 1
    rows = np.asarray(model.decode_step(tokens, lens, tables))
    assert np.max(np.abs(rows[0] - want[p_len])) <= tol
    # the context one row short: its newest row unread, one position early
    lens[0] = p_len
    short = np.asarray(model.decode_step(tokens, lens, tables))[0]
    assert np.max(np.abs(short - want[p_len])) > 1000 * tol


def test_the_server_reports_its_routes(cmda_bundle):
    """`last_routes` after a prefill and a step are the reference's own
    choices over ALL the router's experts, so forcing them changes
    nothing and shows no shortfall."""
    d, weights = cmda_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(9).randint(0, V, 14)
    last, kv = model.prefill([int(t) for t in ids[:13]])
    routes = np.asarray(model.last_routes)[:, :13]
    assert routes.shape == (L, 13, TOP_K)
    own = np.asarray(ref.chosen_experts(weights, ids[:13], HP))
    assert np.array_equal(routes, own)
    assert routes.max() >= FIRST + HELD and routes.min() < FIRST
    forced, shortfall = ref.logits_on_routes(weights, ids[:13], HP, routes,
                                             rows=[12])
    assert not np.asarray(shortfall).any()
    assert np.max(np.abs(np.asarray(forced)[0] - np.asarray(last))) \
        <= 2e-5 * np.std(np.asarray(forced))


# ---------------------------------------------------------------------------
# the engine: two pools, release, eviction and resume
# ---------------------------------------------------------------------------

def _watch_window(dec):
    """Record, every step, each window layer's blocks a slot and check
    what a window layer may read: never a row behind the window, never a
    block another live sequence holds."""
    sched = dec.scheduler
    seen = {"most": 0, "steps": 0}
    step = dec.model.decode_step

    def watched(tokens, lens, tables, wtables):
        live = [s for s in range(len(lens)) if lens[s]]
        held = []
        for s in live:
            first, count = window_blocks(int(lens[s]), WINDOW, BLOCK)
            mine = wtables[s][wtables[s] > 0]
            assert list(np.nonzero(wtables[s])[0]) \
                == list(range(first, first + count))
            seen["most"] = max(seen["most"], len(mine))
            held += list(mine)
        assert len(held) == len(set(held))          # nobody shares one
        assert sched.window_pool.blocks_in_use == len(held)
        seen["steps"] += 1
        return step(tokens, lens, tables, wtables)

    dec.model.decode_step = watched
    return seen


def test_through_the_engine_with_its_counters(cmda_bundle):
    d, weights = cmda_bundle
    engine = ServingEngine()
    engine.load_decode_model("cmda", d, warmup=False, max_new_tokens=16)
    dec = engine.decode_engine("cmda")
    seen = _watch_window(dec)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, V, n).tolist() for n in (5, 13, 30, 8, 21)]
    handles = [engine.generate("cmda", p, max_new_tokens=16)
               for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        ids = np.asarray(prompt + out)
        want = np.asarray(ref.logits(weights, ids, HP))
        greedy = np.argmax(want[len(prompt) - 1:-1], axis=-1)
        assert list(greedy) == out
    assert seen["steps"] > 0 and seen["most"] == WBLOCKS
    snap = dec.metrics_snapshot()
    steps = snap["decode_steps"]
    assert snap["window_rows_live"] > snap["window_rows_read"] > 0
    assert snap["window_rows_read"] % 6 == 0        # six window layers
    assert snap["window_blocks_released"] > 0
    assert snap["window_pool_blocks_in_use"] == 0 \
        or dec.scheduler.window_pool.blocks_in_use == 0
    assert dec.scheduler.window_pool.blocks_in_use == 0
    assert dec.pool.blocks_in_use == 0
    assert snap["moe_layer_steps"] == L * steps
    assert snap["moe_assignments"] == TOP_K * L * snap["slots_used_sum"]
    assert 0 < snap["moe_held_pairs"] < snap["moe_assignments"]
    assert snap["moe_experts_touched"] <= HELD * L * steps
    text = render_prometheus(engine.metrics.snapshot())
    for name in ("pt_decode_window_rows_read_total",
                 "pt_decode_window_rows_live_total",
                 "pt_decode_window_blocks_released_total",
                 "pt_decode_window_pool_blocks_in_use",
                 "pt_decode_moe_held_pairs_total"):
        assert name in text
    desc = dec.describe()
    assert desc["refuses"] == ["kv_share", "speculation"]
    assert desc["cache"]["kinds"]["window"]["blocks_per_seq"] == WBLOCKS
    engine.shutdown()


def test_eviction_and_resume_keep_the_window_invariants(tmp_path):
    """A full pool too small for three sequences: the youngest is
    preempted, its window blocks freed with its others, and resumes by a
    prefill of prompt + generated, seeded with ITS last window; every
    output is still the reference's greedy continuation, and no window
    layer ever held more than its bound or a block of a neighbour's."""
    d, weights = _export(str(tmp_path / "m"), block_of())
    dec = DecodeEngine(d, pool_blocks=16, max_new_tokens=14, warmup=False)
    seen = _watch_window(dec)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, V, n).tolist() for n in (14, 9, 15)]
    handles = [dec.generate(p, max_new_tokens=14) for p in prompts]
    results = [h.result(timeout=300) for h in handles]
    assert sum(r["evictions"] for r in results) > 0
    for prompt, r in zip(prompts, results):
        ids = np.asarray(prompt + r["tokens"])
        want = np.asarray(ref.logits(weights, ids, HP))
        assert list(np.argmax(want[len(prompt) - 1:-1], -1)) == r["tokens"]
    assert seen["most"] == WBLOCKS
    assert dec.scheduler.window_pool.blocks_in_use == 0
    assert dec.pool.blocks_in_use == 0
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    dec.shutdown()


def test_a_window_block_released_with_a_step_in_flight(cmda_bundle):
    """Dispatch ahead: the host's window bookkeeping runs one step ahead
    of the tokens it has emitted. The table of step N+1 is built for the
    length that step runs at (`cached_len + 1` rows cached, its new row
    behind them), so the block that falls behind ITS window is released
    while step N, dispatched and not yet collected, still names it: it
    is in N's table for the slot, not in N+1's, and whoever is handed it
    writes it in a dispatch the device runs after N. The window pool
    never holds more than every slot's bound, and every output is the
    reference's greedy continuation."""
    from paddle_tpu.serving.decode import PREVIOUS_TOKEN
    d, weights = cmda_bundle
    dec = DecodeEngine(d, max_new_tokens=24, warmup=False)
    sched, step = dec.scheduler, dec.model.decode_step
    log = []

    def watched(tokens, lens, tables, wtables):
        # `_flight` is still the step dispatched before this one, if
        # its tokens have not been read
        log.append((sched._flight is not None, tokens.copy(), lens.copy(),
                    wtables.copy(), sched.window_pool.blocks_in_use,
                    dec.metrics.window_pool_blocks_in_use))
        return step(tokens, lens, tables, wtables)

    dec.model.decode_step = watched
    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, V, n).tolist() for n in (5, 13, 9)]
    handles = [dec.generate(p, max_new_tokens=24) for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        want = np.asarray(ref.logits(weights, np.asarray(prompt + out), HP))
        assert list(np.argmax(want[len(prompt) - 1:-1], -1)) == out
    snap = dec.metrics_snapshot()
    dec.shutdown()
    released = handed_on = 0
    for (_, _, lens_n, wt_n, _, _), (ahead, toks, lens, wt, in_use, gauge) \
            in zip(log, log[1:]):
        assert in_use <= SLOTS * WBLOCKS and gauge <= SLOTS * WBLOCKS
        if not ahead:
            continue
        for s in range(SLOTS):
            if not lens[s]:
                continue
            # the same sequence one row on, fed from the device
            assert lens[s] == lens_n[s] + 1 and toks[s] == PREVIOUS_TOKEN
            first, count = window_blocks(int(lens[s]), WINDOW, BLOCK)
            assert list(np.nonzero(wt[s])[0]) \
                == list(range(first, first + count))
            first_n, _ = window_blocks(int(lens_n[s]), WINDOW, BLOCK)
            for entry in range(first_n, first):
                block = wt_n[s, entry]       # N still reads it
                assert block > 0 and block not in wt[s]
                released += 1
                handed_on += block in wt    # another slot's new row
    assert released > 0
    assert snap["steps_ahead"] > 0 and snap["overrun_tokens"] == 0
    assert snap["window_blocks_released"] >= released
    assert sched.window_pool.blocks_in_use == 0
    assert dec.pool.blocks_in_use == 0


def test_prefix_sharing_and_speculation_are_refused_at_load(cmda_bundle):
    d, _ = cmda_bundle
    model = DecodeModel(d, warmup=False)
    with pytest.raises(WindowCacheUnsupported, match="not shareable"):
        DecodeEngine(model=model, kv_share=True, warmup=False)
    with pytest.raises(WindowCacheUnsupported, match="speculation"):
        DecodeEngine(model=model, drafter="ngram", spec_k=2, warmup=False)


def test_stale_rows_never_leak_into_a_window(cmda_bundle):
    """Churn: sequences come and go through the window pool, every pool
    poisoned first; a sequence's logits are those of a clean engine, so
    no window layer read a stale row or one behind its window."""
    d, weights = cmda_bundle
    dec = DecodeEngine(d, max_new_tokens=12, warmup=False)
    dec.model._pools = [jnp.full_like(p, 1e4).at[0].set(0.0)
                        for p in dec.model._pools]
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, V, n).tolist()
               for n in (7, 18, 9, 26, 12, 5, 31)]
    handles = [dec.generate(p, max_new_tokens=12) for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        want = np.asarray(ref.logits(weights, np.asarray(prompt + out), HP))
        assert list(np.argmax(want[len(prompt) - 1:-1], -1)) == out
    dec.shutdown()


def test_the_query_rows_go_through_in_chunks(monkeypatch):
    """A bucket whose q projection is over `_Q_CHUNK_BYTES` projects,
    attends and projects back its query rows a chunk at a time: the same
    logits."""
    attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
    seq = 256
    assert attn_ops._query_chunk(6144, 128 * 128 * 4) == 1024
    assert attn_ops._query_chunk(3072, 128 * 128 * 4) == 1024
    assert attn_ops._query_chunk(2048, 32 * 128 * 4) == 2048
    ids, whole, weights = None, None, None
    for limit in (None, 128 * NH * HD * 4):
        if limit:
            monkeypatch.setattr(attn_ops, "_Q_CHUNK_BYTES", limit)
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            src = pt.layers.data("src_ids", [seq], dtype="int64")
            logits = tfm.transformer_lm(
                src, V, n_layers=4, d_model=DM, n_heads=NH, d_ff=FF,
                max_len=seq, block=block_of(window=100))
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(startup)
            randomise(scope, 3)
            ids = np.random.RandomState(4).randint(0, V, (1, seq))
            got = exe.run(main, feed={"src_ids": ids},
                          fetch_list=[logits])[0]
        if whole is None:
            whole = got
    assert np.max(np.abs(got - whole)) <= 2e-5 * np.std(whole)
