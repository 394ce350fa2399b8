"""Phi-4-mini-flash-reasoning's decoder-hybrid-decoder (selective scans
and differential attention over a window in a first decoder, ONE full
layer whose pool the cross layers of a second decoder read, gated memory
units between them) through the three builders of `models/transformer.py`
and the decode engine, against the plain reference
`benchmark/reference_phi4flash.py`, loaded by path: the reference lives
ONCE (ROADMAP D19) and imports nothing of `paddle_tpu`.

A sequence's memory is of three kinds here: blocks of the full pool
(written by one layer, read by four), blocks of the window pools, and a
scan's state a slot. Most of this file is about all three being the
sequence's own whatever the slot and the blocks went through before.

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and no more.
"""

import importlib
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from references import by_path
from paddle_tpu import io as pio
from paddle_tpu.models import transformer as tfm
from paddle_tpu.obs import trace as obs_trace
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.decode.engine import (DecodeEngine,
                                              WindowCacheUnsupported)
from paddle_tpu.serving.metrics import render_prometheus

from paddle_tpu.kernels import paged_attention as pa
attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
HERE = os.path.dirname(os.path.abspath(__file__))


ref = by_path("reference_phi4flash")

V, DM, NH, NKV, HD, FF = 97, 32, 8, 4, 8, 48
DI, DS, RANK, TAPS, WINDOW = 64, 4, 2, 4, 8
PATTERN = ("mamba", "window", "mamba", "window", "memory", "full",
           "gmu", "cross", "gmu", "cross")
IDS = (0, 1, 2, 3, 16, 17, 18, 19, 20, 21)     # the published indices
L = len(PATTERN)
MAXC, BLOCK, POOL, SLOTS = 64, 8, 40, 3
BUCKETS = (8, 16, 32)
STATE_LAYERS, WINDOW_LAYERS, READERS = 3, 2, 2
STATE_ROW_BYTES = 4 * STATE_LAYERS * DI * (DS + TAPS - 1)     # a slot's
ROW = NKV * HD                                 # a K (or V) row's floats


def block_of(**changes):
    spec = dict(positions="none", bias=False, attn_bias=True,
                attention="gqa", differential=True, n_kv_heads=NKV,
                head_dim=HD, ffn="gated", tied_head=True, window=WINDOW,
                layer_pattern=PATTERN, layer_ids=IDS, conv_taps=TAPS,
                ssm_inner=DI, ssm_state=DS, ssm_dt_rank=RANK)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(NH, NKV, HD, PATTERN, IDS, WINDOW, DS, RANK)

_SCAN = dict(conv_w="conv_w", conv_b="conv_b", x="x_w", dt_w="dt_w",
             dt_b="dt_b", a_log="a_log", d_skip="d_skip", out="out_w",
             **{"in": "in_w"})
_CROSS = dict(q="q_w", q_b="q_b", out="out_w", out_b="out_b", lq1="lq1",
              lk1="lk1", lq2="lq2", lk2="lk2", subnorm="subnorm_scale")
_SELF = dict(_CROSS, k="k_w", k_b="k_b", v="v_w", v_b="v_b")


def reference_weights(get):
    layers = []
    for i, kind in enumerate(PATTERN):
        w = {"ln1": (get(f"ln1_{i}_scale"), get(f"ln1_{i}_bias")),
             "ln2": (get(f"ln2_{i}_scale"), get(f"ln2_{i}_bias")),
             "gate": get(f"ffn{i}_gate_w"), "up": get(f"ffn{i}_up_w"),
             "down": get(f"ffn{i}_down_w")}
        if kind in ("mamba", "memory"):
            w.update({k: get(f"mamba{i}_{n}") for k, n in _SCAN.items()})
        elif kind == "gmu":
            w.update({"in": get(f"gmu{i}_in_w"),
                      "out": get(f"gmu{i}_out_w")})
        else:
            names = _CROSS if kind == "cross" else _SELF
            w.update({k: get(f"attn{i}_{n}") for k, n in names.items()})
        layers.append(w)
    return {"tok_emb": get("tok_emb"),
            "ln_f": (get("ln_f_scale"), get("ln_f_bias")), "layers": layers}


def randomise(scope, seed):
    """Seeded weights with gains away from 1, lambdas large enough that
    the second softmax counts, taps of the size of the rows they weigh;
    the scans' A_log, step bias and D_skip as the layer draws them."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32 \
                or name.endswith(("a_log", "dt_b", "d_skip")):
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith(("lq1", "lk1", "lq2", "lk2")):
            new = 0.3 * rng.randn(*v.shape)
        elif name.endswith("_conv_w"):
            new = rng.randn(*v.shape) * 0.5
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.3)
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

@pytest.fixture(scope="module")
def forward():
    return run_forward(24, block_of())


@pytest.mark.parametrize("seq_len", [24, 3])
def test_forward_matches_reference(seq_len, forward):
    """24 tokens: three windows deep; 3: the rows before the sequence's
    first are zeros in every convolution."""
    ids, got, weights = forward if seq_len == 24 \
        else run_forward(seq_len, block_of())
    assert got.shape == (2, seq_len, V)
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


FAULTS = [dict(window=WINDOW - 1), dict(window=WINDOW + 1),
          dict(lam="dropped"), dict(lam="cut_index"),
          dict(subnorm="none"), dict(subnorm="unscaled"),
          dict(memory="after"), dict(cross="windowed"),
          dict(dt_bias="after"), dict(dtype="bfloat16"), dict(eps=1e-2)]


@pytest.mark.parametrize("wrong", FAULTS, ids=lambda w: "-".join(
    f"{k}_{v}" for k, v in w.items()))
def test_the_parts_of_the_block_each_count(forward, wrong):
    """What the tolerance above is far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread (the faults `benchmark/tools/phi4flash_check_readings.py`
    shows the cell's limits fail)."""
    ids, got, weights = forward
    off = np.asarray(ref.logits(weights, ids[0], HP._replace(**wrong)))
    # (a step bias behind the softplus makes steps negative: the state
    # grows without bound and reads as not a number, which is no pass)
    assert not np.max(np.abs(off - got[0])) / np.std(got[0]) <= 0.02, wrong


def test_a_state_that_is_not_the_prompts_own_shows(forward):
    """The state a sequence leaves, given back to the rows behind it,
    changes nothing; another sequence's (the slot's former owner), or
    the one a padded bucket's end leaves (padding rows that moved it),
    does."""
    ids, _, weights = forward
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(state):
        return float(np.max(np.abs(np.asarray(ref.logits(
            weights, ids[0], HP, state=state)) - want)) / np.std(want))

    own = ref.states(weights, ids[0][:17], HP)
    assert len(own) == STATE_LAYERS and own[0][0].shape == (DI, DS) \
        and own[0][1].shape == (TAPS - 1, DI)
    assert off_by((17, own)) <= 1e-5
    padded = np.concatenate([ids[0][:17], np.zeros(15, ids.dtype)])
    for other in (ref.states(weights, ids[1][:9], HP),
                  ref.states(weights, padded, HP)):
        assert off_by((17, other)) > 0.02


# ---------------------------------------------------------------------------
# the scan op: chunks, padding rows, gradients
# ---------------------------------------------------------------------------

def _scan_weights(rng):
    w = {"in": rng.randn(DM, 2 * DI) / 6, "conv_w": rng.randn(TAPS, DI) / 2,
         "conv_b": rng.randn(DI) / 4, "x": rng.randn(DI, RANK + 2 * DS) / 8,
         "dt_w": rng.randn(RANK, DI) / 1.5,
         "dt_b": np.log(np.expm1(np.exp(rng.uniform(
             np.log(1e-3), np.log(1e-1), DI)))),
         "a_log": np.tile(np.log(np.arange(1, DS + 1)), (DI, 1)),
         "d_skip": np.ones(DI), "out": rng.randn(DI, DM) / 8}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _scan_op(x, w, **more):
    ins = {"X": [x], "WIn": [w["in"]], "ConvW": [w["conv_w"]],
           "ConvB": [w["conv_b"]], "WX": [w["x"]], "WDt": [w["dt_w"]],
           "BDt": [w["dt_b"]], "ALog": [w["a_log"]],
           "DSkip": [w["d_skip"]], "WOut": [w["out"]]}
    ins.update({k: [v] for k, v in more.items()})
    return attn_ops.selective_scan(None, ins,
                                   {"d_state": DS, "dt_rank": RANK})


@pytest.mark.parametrize("seq,n", [(192, 192), (192, 130), (72, 65),
                                   (16, 1), (5, 5)])
def test_selective_scan_matches_the_token_by_token_reference(seq, n):
    """Three chunks of 64, chunks of 8, a sequence shorter than the
    taps; `n` the true length: the rows behind it are padding, which
    moves neither state, and the state returned is the one a decode step
    at position n reads."""
    assert attn_ops._SCAN_CHUNK == 64
    rng = np.random.RandomState(seq + n)
    w = _scan_weights(rng)
    x = jnp.asarray(rng.randn(2, seq, DM), jnp.float32)
    lens = jnp.asarray([n, max(n - 3, 1)], jnp.int32)
    got = _scan_op(x, w, NTokens=lens)
    for b in range(2):
        m = int(lens[b])
        out, memory, (state, rows) = ref._mamba(x[b, :m], w, HP)
        assert np.allclose(got["Out"][0][b, :m], out, atol=2e-5)
        assert np.allclose(got["Memory"][0][b, :m], memory, atol=2e-5)
        assert np.allclose(got["SsmStateOut"][0][b], state.T, atol=2e-5)
        assert np.allclose(got["ConvStateOut"][0][b], rows, atol=1e-6)
        # and a step from that state is the next row of the whole scan
        nxt = jnp.asarray(rng.randn(1, 1, DM), jnp.float32)
        step = _scan_op(nxt, w, SsmState=got["SsmStateOut"][0][b:b + 1],
                        ConvState=got["ConvStateOut"][0][b:b + 1],
                        ContextLens=jnp.asarray([m + 1], jnp.int32))
        whole, _, (state, rows) = ref._mamba(
            jnp.concatenate([x[b, :m], nxt[0]]), w, HP)
        assert np.allclose(step["Out"][0][0, 0], whole[-1], atol=2e-5)
        assert np.allclose(step["SsmStateOut"][0][0], state.T, atol=2e-5)
        assert np.allclose(step["ConvStateOut"][0][0], rows, atol=1e-6)
    # an empty slot keeps its state as it was
    idle = _scan_op(nxt, w, SsmState=got["SsmStateOut"][0][:1],
                    ConvState=got["ConvStateOut"][0][:1],
                    ContextLens=jnp.asarray([0], jnp.int32))
    assert np.array_equal(idle["SsmStateOut"][0], got["SsmStateOut"][0][:1])
    assert np.array_equal(idle["ConvStateOut"][0],
                          got["ConvStateOut"][0][:1])


def test_selective_scan_gradients_match_the_reference():
    """The op is differentiable as it is (the trainer is not asked to
    run the model): its gradients, for its input and its weights, are
    jax.grad's of the plain reference's scan, across a chunk boundary."""
    rng = np.random.RandomState(5)
    w = _scan_weights(rng)
    x = jnp.asarray(rng.randn(1, 80, DM), jnp.float32)
    probe = jnp.asarray(rng.randn(1, 80, DM), jnp.float32)

    def program(x, w):
        return jnp.sum(_scan_op(x, w)["Out"][0] * probe)

    def reference(x, w):
        return jnp.sum(ref._mamba(x[0], w, HP)[0] * probe[0])

    gx, gw = jax.grad(program, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(reference, argnums=(0, 1))(x, w)
    assert np.allclose(gx, rx, atol=1e-4 * float(jnp.max(jnp.abs(rx))))
    for key in w:
        scale = float(jnp.max(jnp.abs(rw[key]))) or 1.0
        assert np.allclose(gw[key], rw[key], atol=2e-4 * scale), key


# ---------------------------------------------------------------------------
# differential attention: whole sequences, and the paged kernel
# ---------------------------------------------------------------------------

def _attention_weights(rng, cross=False):
    w = {"q": rng.randn(DM, NH * HD) / 3, "q_b": rng.randn(NH * HD) / 4,
         "out": rng.randn(NH * HD, DM) / 8, "out_b": rng.randn(DM) / 4,
         "subnorm": 1 + 0.2 * rng.randn(2 * HD),
         **{k: 0.3 * rng.randn(HD) for k in ("lq1", "lk1", "lq2", "lk2")}}
    if not cross:
        w.update(k=rng.randn(DM, NKV * HD) / 3, k_b=rng.randn(NKV * HD) / 4,
                 v=rng.randn(DM, NKV * HD) / 4, v_b=rng.randn(NKV * HD) / 4)
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _diff_ins(x, w, **more):
    ins = {"X": [x], "Wq": [w["q"]], "Bq": [w["q_b"]], "Wo": [w["out"]],
           "Bo": [w["out_b"]], "SubNorm": [w["subnorm"]],
           "LamQ1": [w["lq1"]], "LamK1": [w["lk1"]], "LamQ2": [w["lq2"]],
           "LamK2": [w["lk2"]]}
    if "k" in w:
        ins.update(Wk=[w["k"]], Bk=[w["k_b"]], Wv=[w["v"]], Bv=[w["v_b"]])
    ins.update({k: [v] for k, v in more.items()})
    return ins


def _diff_attrs(layer_id, window=0):
    attrs = {"num_heads": NH, "num_kv_heads": NKV, "head_dim": HD,
             "lambda_init": 0.8 - 0.6 * np.exp(-0.3 * layer_id),
             "epsilon": 1e-5}
    if window:
        attrs["window"] = window
    return attrs


@pytest.mark.parametrize("kind", ["full", "window", "cross", "one_row"])
def test_differential_attention_matches_the_dense_form(kind):
    """Full, windowed and cross layers over whole sequences, and the
    prefill's form of the full layer: K and V of the whole sequence,
    ONE row asking (what `transformer_lm` builds for a head row)."""
    rng = np.random.RandomState(len(kind))
    x = jnp.asarray(rng.randn(1, 24, DM), jnp.float32)
    w = _attention_weights(rng)
    layer_id = 17
    want, (k, v) = ref._attention(
        x[0], w, HP, "window" if kind == "window" else "full", layer_id, 5)
    if kind == "cross":
        cross = _attention_weights(rng, cross=True)
        want, _ = ref._attention(x[0], cross, HP, "cross", 19, 7, kv=(k, v))
        got = attn_ops.diff_attention(None, _diff_ins(
            x, cross, KIn=attn_ops.diff_row(k)[None],
            VIn=attn_ops.diff_row(v)[None]), _diff_attrs(19))
        assert "K" not in got
    elif kind == "one_row":
        at = jnp.asarray([[20]], jnp.int32)
        got = attn_ops.diff_attention(None, _diff_ins(
            x[:, 20:21], w, XKV=x, QRows=at), _diff_attrs(layer_id))
        want = want[20:21]
        assert got["K"][0].shape == (1, 24, NKV // 2, 2 * HD)
    else:
        got = attn_ops.diff_attention(None, _diff_ins(x, w), _diff_attrs(
            layer_id, WINDOW if kind == "window" else 0))
        # a cache's rows: K head g of the first set beside the second's
        assert np.allclose(got["K"][0][0, :, 1, :HD], k[:, 1], atol=1e-6)
        assert np.allclose(got["K"][0][0, :, 1, HD:], k[:, 3], atol=1e-6)
        assert np.allclose(got["V"][0][0, :, 0, HD:], v[:, 2], atol=1e-6)
    assert np.allclose(got["Out"][0][0], want, atol=3e-5)


def _paged_case(lens, seed=0, heads=8, pairs=2, block=8, table=6):
    rng = np.random.RandomState(seed)
    n_blocks = 1 + len(lens) * table
    q = jnp.asarray(rng.randn(len(lens), heads, 64), jnp.float32)
    pools = [jnp.asarray(rng.randn(n_blocks, block, pairs * 128),
                         jnp.float32) for _ in range(2)]
    tables = jnp.asarray(rng.permutation(np.arange(1, n_blocks)).reshape(
        len(lens), table), jnp.int32)
    return q, pools, tables, jnp.asarray(lens, jnp.int32)


def _dense_diff(q, pools, tables, lens, lam, window):
    """float64 on the host: both softmaxes over a slot's live rows."""
    slots, heads, d = q.shape
    pairs = pools[0].shape[-1] // (2 * d)
    per = heads // (2 * pairs)
    out = np.zeros((slots, heads // 2, 2 * d))
    for s in range(slots):
        n = int(lens[s])
        if not n:
            continue
        lo = max(0, n - window) if window else 0
        k, v = (np.concatenate([np.asarray(p[b], np.float64)
                                for b in np.asarray(tables[s])])[lo:n]
                .reshape(n - lo, pairs, 2, d) for p in pools)
        for h in range(heads // 2):
            a = []
            for st in range(2):
                sc = k[:, h // per, st] @ np.asarray(
                    q[s, st * heads // 2 + h], np.float64) / np.sqrt(d)
                p = np.exp(sc - sc.max())
                a.append((p / p.sum()) @ v[:, h // per].reshape(n - lo, -1))
            out[s, h] = a[0] - lam * a[1]
    return out


@pytest.mark.parametrize("lens,window", [
    ([41, 0, 17], None),            # a partial last page, an empty slot
    ([48, 8, 1], None),             # whole pages, one row
    ([41, 30, 5], 16),              # the window's edge inside a page
    ([48, 16, 9], 16),              # on a page's edge; shorter than it
    ([33, 24, 47], 24)])
def test_the_paged_diff_kernel_matches_its_reference(lens, window):
    """The Pallas kernel, interpreted, at heads of 64 (a tile is a lane
    tile) against float64 on the host and against the gather form, over
    page and window edges: each K and V row once, two softmaxes a head
    pair, the difference written at a slot's end."""
    q, pools, tables, n = _paged_case(lens, seed=len(lens) + (window or 0))
    lam = 0.37
    want = _dense_diff(q, pools, tables, n, lam, window)
    gather = pa.paged_diff_attention_reference(
        q, *pools, tables, n, lam, scale=0.125, window=window)
    kernel = pa.paged_diff_attention(q, *pools, tables, n,
                                     jnp.float32(lam), interpret=True,
                                     window=window)
    assert kernel.shape == (len(lens), 4, 128)
    assert np.max(np.abs(np.asarray(gather) - want)) <= 2e-6
    assert np.max(np.abs(np.asarray(kernel) - want)) <= 2e-6


def test_the_diff_kernel_leaves_its_plan_in_the_trace_ring():
    """Each trace of the wrapper leaves `kernel/paged_plan`, as the flash
    wrappers leave `kernel/flash_plan`; the plan is `paged_decode_plan`'s
    for the bundle's declared row."""
    q, pools, tables, n = _paged_case([9, 30], seed=7, table=5)
    obs_trace.reset()
    pa._paged_diff_attention_pallas.clear_cache()   # a record a trace
    for _ in range(2):
        pa.paged_diff_attention(q, *pools, tables, n, jnp.float32(0.2),
                                interpret=True, window=16)
    plans = [e for e in obs_trace.events()
             if (e["cat"], e["name"]) == ("kernel", "paged_plan")]
    assert len(plans) == 1
    attrs = plans[0]["args"]
    plan = pa.paged_decode_plan("kv_diff", [[256], [256]], 8, 8,
                                jnp.float32, 5, 16)
    assert plan.kernel == attrs["kernel"] == "diff"
    assert attrs["pages_per_block"] == plan.pages_per_block == 5
    assert attrs["heads_per_product"] == plan.heads_per_product == 4
    assert (attrs["window"], attrs["tiles"], attrs["slots"]) == (16, 2, 2)


# ---------------------------------------------------------------------------
# the bundle: prefill through every bucket, then decode through the three
# kinds of memory
# ---------------------------------------------------------------------------

def export_cfg(block):
    return dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
                max_context=MAXC, block=block)


def _export(tmp, block, seed=3, pool_blocks=POOL):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [16], dtype="int64")
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
def bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("phi4flash") / "m"),
                   block_of())


def test_serving_json_declares_three_kinds_and_the_pools_readers(bundle):
    with open(os.path.join(bundle[0], "serving.json")) as f:
        dec = json.load(f)["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    row = 4 * 2 * ROW
    per_seq = WINDOW // BLOCK + 1
    assert dec["cache"] == {
        "kind": "kv_diff", "rows": [[ROW], [ROW]], "row_floats": 2 * ROW,
        "bytes_per_token": row * (1 + WINDOW_LAYERS),
        "layer_kinds": ["state", "window", "state", "window", "state",
                        "full", "none", "shared", "none", "shared"],
        "window": WINDOW,
        "kinds": {"full": {"layers": 1, "pool_blocks": POOL,
                           "blocks_per_seq": MAXC // BLOCK,
                           "bytes_per_token": row},
                  "window": {"layers": WINDOW_LAYERS,
                             "pool_blocks": SLOTS * per_seq + 1,
                             "blocks_per_seq": per_seq,
                             "bytes_per_token": row * WINDOW_LAYERS},
                  "state": {"layers": STATE_LAYERS,
                            "rows": [[DS, DI], [TAPS - 1, DI]],
                            "bytes_per_slot": STATE_ROW_BYTES}},
        "shared": {"source": 5, "readers": [7, 9]}}
    feeds = [(m["name"], m["shape"]) for m in dec["feeds"]]
    scan = lambda i: [(f"ssm_state_{i}", [SLOTS, DS, DI]),
                      (f"conv_state_{i}", [SLOTS, TAPS - 1, DI])]
    held = lambda i, n: [(f"k_cache_{i}", [n, BLOCK, ROW]),
                         (f"v_cache_{i}", [n, BLOCK, ROW])]
    assert feeds == [
        ("token_ids", [SLOTS]), ("context_lens", [SLOTS]),
        ("block_tables", [SLOTS, MAXC // BLOCK]),
        ("window_tables", [SLOTS, MAXC // BLOCK]),
        *scan(0), *held(1, SLOTS * per_seq + 1), *scan(2),
        *held(3, SLOTS * per_seq + 1), *scan(4), *held(5, POOL)]
    # a gmu layer and a cross layer have no role in a prefill's fetches
    assert [len(p) for p in dec["prefill_roles"]["kv"]] \
        == [2, 2, 2, 2, 2, 2, 0, 0, 0, 0]


class _Blocks:
    """A slot's window blocks as the scheduler holds them."""

    def __init__(self, model):
        self.model, self.first, self.held = model, 0, []
        self.free = list(range(model.window_blocks_per_seq + 1, 0, -1))

    def admit(self, length):
        self.free += self.held[::-1]
        self.first, count = self.model.window_span(length)
        self.held = [self.free.pop() for _ in range(count)]
        return self.held

    def table(self, length, row):
        first, count = self.model.window_span(length)
        while self.first < first:
            self.free.append(self.held.pop(0))
            self.first += 1
        while self.first + len(self.held) < first + count:
            self.held.append(self.free.pop())
        row[:] = 0
        row[self.first:self.first + len(self.held)] = self.held


@pytest.mark.parametrize("p_len,former", [(21, 0), (32, 5), (9, 13),
                                          (1, 7)])
def test_prefill_then_decode_through_the_three_kinds_of_memory(
        bundle, p_len, former):
    """Logits after the prefill (the second decoder on ONE row) and
    after each teacher-forced step, through the full pool and its three
    readers, the window pools and the states, against the reference's
    full forward; `former`: the slot, its blocks and its window blocks
    held a shorter (or longer) sequence's rows before."""
    d, weights = bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(p_len).randint(0, V, p_len + 14)
    total, slot = len(ids), 1
    want = np.asarray(ref.logits(weights, ids, HP))
    tol = 2e-5 * np.std(want)
    blocks = list(range(3, 3 + -(-total // BLOCK)))
    held = _Blocks(model)
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    wtables = np.zeros_like(tables)
    tables[slot, :len(blocks)] = blocks
    if former:
        other = ids[::-1][:former]
        _, kv = model.prefill([int(t) for t in other])
        model.seed_sequence(blocks[:-(-former // BLOCK)], kv,
                            window_ids=held.admit(former), slot=slot)
        tokens[slot], lens[slot] = other[0], former + 1
        held.table(former + 1, wtables[slot])
        model.decode_step(tokens, lens, tables, wtables).tokens
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    model.seed_sequence(blocks[:-(-p_len // BLOCK)], kv,
                        window_ids=held.admit(p_len), slot=slot)
    for j in range(total - p_len):
        tokens[slot], lens[slot] = ids[p_len + j], p_len + j + 1
        held.table(p_len + j + 1, wtables[slot])
        rows = np.asarray(model.decode_step(tokens, lens, tables, wtables))
        assert np.max(np.abs(rows[slot] - want[p_len + j])) <= tol, j
    # pools and states are all updated in place, every byte
    assert model.step_aliased_bytes == sum(
        4 * int(np.prod(s)) for s in model._pool_shapes) \
        > model.state_bytes == SLOTS * STATE_ROW_BYTES
    assert (model.state_layers, model.pool_readers, model.full_layers,
            model.window_layers) == (STATE_LAYERS, READERS, 1,
                                     WINDOW_LAYERS)


def test_a_cross_layer_on_a_window_layers_table_shows(bundle):
    """The fault the cell's check has to see: the step's cross layers
    given the window layers' table read other blocks of the full pool
    than the sequence's."""
    d, weights = bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(2).randint(0, V, 30)
    want = np.asarray(ref.logits(weights, ids, HP))
    _, kv = model.prefill([int(t) for t in ids[:29]])
    held = _Blocks(model)
    model.seed_sequence([5, 6, 7, 8], kv, window_ids=held.admit(29), slot=0)
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    wtables = np.zeros_like(tables)
    tokens[0], lens[0], tables[0, :4] = ids[29], 30, [5, 6, 7, 8]
    held.table(30, wtables[0])
    wrong = np.asarray(model.decode_step(tokens, lens, wtables, wtables))
    assert np.max(np.abs(wrong[0] - want[29])) > 0.02 * np.std(want)


# ---------------------------------------------------------------------------
# the engine: the three kinds through everything a slot goes through
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
    dec = DecodeEngine(d, max_new_tokens=12, warmup=False)
    _poison(dec)
    for n in (29, 3, 17, 1):        # one at a time: slot 0 every time
        _served(dec, weights, _prompts(n, [n]), 12)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 4
    return dec


def _case_a_preemption_and_resume(d, weights, tmp):
    """A pool too small for three sequences: one is preempted and
    resumes by a prefill of prompt + generated, which rebuilds its
    state and its window in whatever slot it then gets."""
    d, weights = _export(str(tmp / "m"), block_of(), pool_blocks=9)
    dec = DecodeEngine(d, max_new_tokens=14, warmup=False)
    _poison(dec)
    results = _served(dec, weights, _prompts(11, [14, 9, 15]), 14)
    assert sum(r["evictions"] for r in results) > 0
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    assert snap["state_seeds"] == snap["prefills"] > 3
    return dec


def _case_an_eviction_by_priority(d, weights, tmp):
    d, weights = _export(str(tmp / "m"), block_of(), pool_blocks=9)
    dec = DecodeEngine(d, max_new_tokens=12, warmup=False)
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
    """Three times the slots: a freed slot's next owner starts from ITS
    state, its window blocks and its blocks of the shared pool."""
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


_CASES = {name[len("_case_"):]: fn for name, fn in sorted(globals().items())
          if name.startswith("_case_")}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_memory_is_the_sequences_own(bundle, tmp_path, case):
    """Every output is the reference's greedy continuation (the
    reference has no cache and no state), whatever the slot and the
    blocks held before; every block of both kinds comes back; the
    counters count what the step read."""
    d, weights = bundle
    dec = _CASES[case](d, weights, tmp_path)
    snap = dec.metrics_snapshot()
    assert dec.pool.blocks_in_use == 0
    assert dec.window_pool.blocks_in_use == 0
    live = snap["slots_used_sum"] + snap["overrun_tokens"]
    assert snap["state_slot_steps"] == STATE_LAYERS * live
    assert snap["state_seed_bytes"] == STATE_ROW_BYTES * snap["state_seeds"]
    assert snap["state_bytes"] == SLOTS * STATE_ROW_BYTES
    # the full pool's rows: once by its writer, once by each reader
    assert snap["pool_rows_read_readers"] \
        == READERS * snap["pool_rows_read_writer"] > 0
    assert snap["window_rows_live"] \
        == WINDOW_LAYERS * snap["pool_rows_read_writer"]
    assert 0 < snap["window_rows_read"] <= snap["window_rows_live"]
    dec.shutdown()


def test_through_the_engine_with_its_counters(bundle):
    d, weights = bundle
    engine = ServingEngine()
    engine.load_decode_model("phi", d, warmup=False, max_new_tokens=16)
    dec = engine.decode_engine("phi")
    prompts = _prompts(10, [5, 13, 30, 8, 21])
    handles = [engine.generate("phi", p, max_new_tokens=16)
               for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, prompt, out)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 5
    pools = sum(4 * int(np.prod(s)) for s in dec.model._pool_shapes)
    assert snap["step_aliased_bytes"] == pools
    assert snap["cache_bytes_per_token"] \
        == 4 * 2 * ROW * (1 + WINDOW_LAYERS)
    text = render_prometheus(engine.metrics.snapshot())
    for line in ('pt_decode_pool_rows_read_writer_total{model="phi"} %d'
                 % snap["pool_rows_read_writer"],
                 'pt_decode_pool_rows_read_readers_total{model="phi"} %d'
                 % snap["pool_rows_read_readers"],
                 'pt_decode_state_slot_steps_total{model="phi"} %d'
                 % snap["state_slot_steps"],
                 'pt_decode_window_rows_read_total{model="phi"} %d'
                 % snap["window_rows_read"],
                 'pt_decode_state_bytes{model="phi"} %d'
                 % (SLOTS * STATE_ROW_BYTES)):
        assert line in text, line
    desc = dec.describe()
    assert desc["refuses"] == ["kv_share", "speculation"]
    assert desc["cache"]["shared"] == {"source": 5, "readers": [7, 9]}
    assert desc["paged_kernel"]["heads_per_product"] == NH
    engine.shutdown()


def test_prefix_sharing_and_speculation_are_refused_at_load(bundle):
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    with pytest.raises(WindowCacheUnsupported, match="kv_share"):
        DecodeEngine(model=model, kv_share=True, warmup=False)
    with pytest.raises(WindowCacheUnsupported, match="speculation"):
        DecodeEngine(model=model, drafter="ngram", spec_k=2, warmup=False)


def test_the_mixers_are_named_in_the_compiled_programs(bundle):
    """What a profile shows: the scans under `selective_scan`, in the
    step and in a prefill bucket; and a bucket runs its second decoder
    on one row (no [bound, d_ff] product of a gmu or cross layer)."""
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    model.decode_step(np.zeros(SLOTS, np.int64), np.zeros(SLOTS, np.int32),
                      np.zeros((SLOTS, MAXC // BLOCK), np.int32)).tokens
    assert "selective_scan" in model._step.as_text()
    calls = model._admit_fns[BUCKETS[-1]]
    text = calls.prefill.lower(
        calls.weights, np.zeros(calls.ids_shape, calls.ids_dtype),
        np.int32(3)).compile().as_text()
    assert "selective_scan" in text
    # the FFN's products over the whole bucket: the five layers before
    # the full layer alone (gate and up each); from that layer's query on
    # one row goes through, its own FFN included
    wide = [line for line in text.splitlines()
            if re.search(r"= f32\[(1,)?%d,%d\]\S* dot\("
                         % (BUCKETS[-1], FF), line)]
    assert len(wide) == 2 * 5, len(wide)


@pytest.mark.parametrize("wrong,match", [
    (dict(differential=False), "rotary"),
    (dict(layer_pattern=("mamba", "gmu")), "memory"),
    (dict(layer_pattern=("mamba", "cross")), "full"),
    (dict(ssm_inner=0), "ssm_inner"),
    (dict(conv_taps=0), "conv_taps"),
    (dict(n_kv_heads=3), "even"),
    (dict(attention="mha", n_kv_heads=0, head_dim=0), "gqa"),
    (dict(dense_precision="bfloat16"), "dense_precision")])
def test_what_the_block_cannot_be_is_refused(wrong, match):
    with pytest.raises(ValueError, match=match):
        block_of(**wrong)


def test_lambda_init_follows_the_published_index():
    block = block_of()
    assert block.layer(5).published == 17 and block.layer(7).kv_source == 5
    assert abs(block.lambda_init(5)
               - (0.8 - 0.6 * np.exp(-0.3 * 17))) < 1e-12
    assert block.lambda_init(5) != block_of(layer_ids=()).lambda_init(5)
    assert block.cache_kinds(L) == ["state", "window", "state", "window",
                                    "state", "full", "none", "shared",
                                    "none", "shared"]


@pytest.mark.parametrize("precision", ["", "high"])
def test_dense_precision_reaches_every_product_fc_builds(precision):
    """`dense_precision` is the `precision` of the FFNs', the gated
    memory units' and the head's products, in the whole-sequence program
    and in the step's; at its default the ops carry no such attribute
    (the other blocks' programs are what they were) and `to_dict` leaves
    the field out. On the CPU a float32 product is float32 either way:
    the logits are the same to the last bit."""
    block = block_of(dense_precision=precision)
    assert ("dense_precision" in block.to_dict()) == bool(precision)
    assert tfm.BlockSpec.of(block.to_dict()) == block
    main = pt.Program()
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [8], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=L, d_model=DM, n_heads=NH,
                           d_ff=FF, max_len=MAXC, block=block)
    step = pt.Program()
    with pt.program_guard(step, pt.Program()):
        tfm.transformer_decode_step(
            V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_context=MAXC, slots=SLOTS, block_size=BLOCK,
            pool_blocks=POOL, max_blocks_per_seq=MAXC // BLOCK, block=block,
            window_pool_blocks=SLOTS * (WINDOW // BLOCK + 1) + 1)
    gmus = PATTERN.count("gmu")
    for program in (main, step):
        dense = [op for op in program.global_block.ops
                 if op.type in ("mul", "matmul")]
        assert len(dense) == 3 * L + 2 * gmus + 1
        assert [op.attrs.get("precision") for op in dense] \
            == [precision or None] * len(dense)
    plain = run_forward(8, block_of())[1]
    assert np.array_equal(run_forward(8, block)[1], plain)


def test_the_state_layers_projections_take_three_passes():
    """What the device's one-pass default cost this block most: the
    selective scan's four projections and the differential attention's
    are traced at `Precision.HIGH` whatever the block says (the scan sums
    their rounding over a prompt, the sub-norm divides by a difference's
    size); no product of either op is left at the default."""
    def dots(fn, *args):
        text = str(jax.make_jaxpr(fn)(*args))
        found = re.findall(r"precision=(.*)", text)
        assert len(found) == text.count("dot_general[")
        return found

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 8, DM), jnp.float32)
    scan = {"X": [x], "WIn": [jnp.zeros((DM, 2 * DI))],
            "ConvW": [jnp.zeros((TAPS, DI))], "ConvB": [jnp.zeros(DI)],
            "WX": [jnp.zeros((DI, RANK + 2 * DS))],
            "WDt": [jnp.zeros((RANK, DI))], "BDt": [jnp.zeros(DI)],
            "ALog": [jnp.zeros((DI, DS))], "DSkip": [jnp.ones(DI)],
            "WOut": [jnp.zeros((DI, DM))]}
    found = dots(lambda: attn_ops.selective_scan(
        None, scan, {"d_state": DS, "dt_rank": RANK}))
    assert len(found) == 4 and all("HIGH" in p and "HIGHEST" not in p
                                   for p in found), found
    attn = {"X": [x], "Wq": [jnp.zeros((DM, NH * HD))],
            "Bq": [jnp.zeros(NH * HD)], "Wk": [jnp.zeros((DM, NKV * HD))],
            "Bk": [jnp.zeros(NKV * HD)], "Wv": [jnp.zeros((DM, NKV * HD))],
            "Bv": [jnp.zeros(NKV * HD)], "Wo": [jnp.zeros((NH * HD, DM))],
            "Bo": [jnp.zeros(DM)], "SubNorm": [jnp.ones(2 * HD)],
            **{k: [jnp.zeros(HD)] for k in ("LamQ1", "LamK1", "LamQ2",
                                            "LamK2")},
            "QRows": [jnp.asarray([[7]], jnp.int32)]}
    attn["XKV"], attn["X"] = attn["X"], [x[:, 7:]]
    found = dots(lambda: attn_ops.diff_attention(None, attn, dict(
        num_heads=NH, num_kv_heads=NKV, head_dim=HD, lambda_init=0.5,
        epsilon=1e-5)))
    # q, k, v, the scores, the values' product, the output projection
    assert len(found) == 6 and all("HIGH" in p and "HIGHEST" not in p
                                   for p in found), found
