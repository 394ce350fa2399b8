"""Granite 4.0-H's block (every layer a mixer AND a dense gated FFN, each
under a norm and a residual of its own with a residual multiplier; nine
Mamba-2 mixers at ONE group to one attention layer without positions
whose softmax scale is a constant of the file; a tied head behind a
logit divisor) through the builders of `models/transformer.py` and the
decode engine, in float32 and from a bundle whose matrices are bfloat16,
against the plain reference `benchmark/reference_granite4.py`, loaded by
path: the reference lives ONCE (ROADMAP D19) and imports nothing of
`paddle_tpu`.

Small sizes, seeded random weights, the CPU: f32 is f32 here and a
bfloat16 matrix is multiplied as the float32 values it holds, so the
tolerances are what a changed order of float32 sums gives and no more.
"""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from references import by_path
from paddle_tpu import io as pio
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import ssd_update
from paddle_tpu.models import transformer as tfm
from paddle_tpu.obs import trace
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.decode.engine import (DecodeEngine,
                                              SequenceStateUnsupported)
from paddle_tpu.serving.metrics import render_prometheus

attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
HERE = os.path.dirname(os.path.abspath(__file__))


ref = by_path("reference_granite4")

V, DM, NH, NKV, HD, FF = 97, 32, 4, 2, 8, 48
H, P, G, N, TAPS, CHUNK = 4, 8, 1, 128, 4, 8
DI, WIDTH = H * P, H * P + 2 * G * N
PATTERN = ("mamba2_ffn", "full", "mamba2_ffn")
L = len(PATTERN)
MAXC, BLOCK, POOL, SLOTS = 64, 8, 40, 3
BUCKETS = (8, 32)
STATE_LAYERS, FULL_LAYERS = 2, 1
STATE_LAYER_BYTES = 4 * (H * P * N + (TAPS - 1) * WIDTH)     # a slot's
STATE_ROW_BYTES = STATE_LAYERS * STATE_LAYER_BYTES
EMBED, RESIDUAL, LOGIT_DIV, ATTN_SCALE = 12.0, 0.22, 8.0, 1.0 / 16


def block_of(**changes):
    spec = dict(norm="rms_norm", positions="none", bias=False,
                attention="gqa", n_kv_heads=NKV, head_dim=HD, ffn="gated",
                tied_head=True, layer_pattern=PATTERN, conv_taps=TAPS,
                ssm_inner=DI, ssm_state=N, ssm_heads=H, ssm_groups=G,
                ssm_chunk=CHUNK, embed_scale=EMBED,
                residual_scale=RESIDUAL, logit_scale=1.0 / LOGIT_DIV,
                attn_scale=ATTN_SCALE)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(tuple("mamba" if k == "mamba2_ffn" else "attention"
                     for k in PATTERN), NH, NKV, HD, H, P, G, N, EMBED,
               RESIDUAL, LOGIT_DIV, ATTN_SCALE)

_FFN = dict(gate="gate_w", up="up_w", down="down_w")
_MAMBA = dict(conv_w="conv_w", conv_b="conv_b", dt_b="dt_b", a_log="a_log",
              d_skip="d_skip", norm="norm_scale", out="out_w",
              **{"in": "in_w"})
_ATTN = dict(q="q_w", k="k_w", v="v_w", out="out_w")


def reference_weights(get):
    layers = []
    for i, kind in enumerate(PATTERN):
        stem, names = ("mamba", _MAMBA) if kind == "mamba2_ffn" \
            else ("attn", _ATTN)
        w = {k: get(f"{stem}{i}_{n}") for k, n in names.items()}
        w.update({k: get(f"ffn{i}_{n}") for k, n in _FFN.items()})
        w.update(ln1=get(f"ln1_{i}_scale"), ln2=get(f"ln2_{i}_scale"))
        layers.append(w)
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": layers}


def randomise(scope, seed):
    """Seeded weights with gains away from 1, taps of the size of the
    rows they weigh and a convolution bias that is not zero, wide q and
    k projections; the scans' A_log and step bias as the layer draws
    them, D_skip moved off 1."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32 or name.endswith(("a_log", "dt_b")):
            continue
        if name.endswith(("_scale", "d_skip")):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("_conv_w"):
            new = rng.randn(*v.shape) * 0.5
        elif name.endswith("_conv_b"):
            new = rng.uniform(-0.5, 0.5, v.shape)
        elif name == "tok_emb":
            new = rng.randn(*v.shape) * 0.1
        elif name.endswith(("_q_w", "_k_w")):
            # scores of a deviation near 2 at the configuration's scale:
            # a softmax that is not flat, so a rotation or another scale
            # shows (the cell's `qk_gain`)
            new = rng.randn(*v.shape) * 4.0 / np.sqrt(v.shape[-2])
        else:
            new = rng.randn(*v.shape) * 0.7 / np.sqrt(v.shape[-2])
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def _round_in_scope(scope, weight_dtype):
    """Every matrix of `scope` replaced by its rounding (what the cell's
    start-up program does where it draws each: the export then finds
    nothing left to round)."""
    for name in list(scope.local_var_names() if weight_dtype else ()):
        value = scope.find_var(name)
        if pio.is_weight_matrix(name, np.shape(value)):
            scope.set_var(name, jnp.asarray(value).astype(weight_dtype))


def _built(seq_len, block):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block)
    return main, startup, logits


def run_forward(seq_len, block, weight_dtype="", seed=3):
    main, startup, logits = _built(seq_len, block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, seed)
        _round_in_scope(scope, weight_dtype)
        ids = np.random.RandomState(4).randint(0, V, (2, seq_len))
        got = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])[0]
        weights = reference_weights(scope.find_var)
    return ids, got, weights


# ---------------------------------------------------------------------------
# forward, and what each part is worth
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def forward():
    return run_forward(24, block_of())


@pytest.mark.parametrize("seq_len,weight_dtype", [
    (24, ""), (3, ""), (24, "bfloat16")])
def test_forward_matches_reference(seq_len, weight_dtype, forward):
    """24 tokens: three chunks of the SSD form; 3: the rows before the
    sequence's first are zeros in every convolution; bfloat16: the
    matrices rounded in the scope, the stream still float32, against the
    reference reading the SAME rounded matrices cast up."""
    ids, got, weights = forward if (seq_len, weight_dtype) == (24, "") \
        else run_forward(seq_len, block_of(), weight_dtype)
    assert got.shape == (2, seq_len, V) and got.dtype == np.float32
    if weight_dtype:
        assert weights["tok_emb"].dtype == jnp.bfloat16 \
            == weights["layers"][0]["in"].dtype \
            == weights["layers"][1]["q"].dtype \
            == weights["layers"][0]["down"].dtype
        assert weights["layers"][0]["conv_w"].dtype == jnp.float32 \
            == weights["layers"][0]["norm"].dtype == weights["ln_f"].dtype
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 1e-5 * np.std(want)


FAULTS = [dict(softmax="rsqrt"), dict(residual="mixer_unscaled"),
          dict(residual="ffn_unscaled"), dict(embedding="unscaled"),
          dict(gate="after"), dict(norm="groups_512"), dict(skip="dropped"),
          dict(conv="no_bias"), dict(dt_bias="after"), dict(rotary="half"),
          dict(halves="swapped"), dict(dtype="bfloat16")]


@pytest.mark.parametrize("wrong", FAULTS, ids=lambda w: "-".join(
    f"{k}_{v}" for k, v in w.items()))
def test_the_parts_of_the_block_each_count(forward, wrong):
    """What the tolerance above is far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread (the faults `benchmark/tools/granite4_check_readings.py`
    shows the cell's limits fail). The gated norm's run of 512 needs an
    inner width that holds two of them: a wider reference of its own."""
    ids, got, weights = forward
    hp = HP
    if wrong == dict(norm="groups_512"):
        rng = np.random.RandomState(0)
        hp = ref.Hyper(("mamba",), 1, 1, 8, 16, 64, 1, 128)
        di, width = 1024, 1024 + 256
        weights = {
            "tok_emb": rng.randn(V, DM).astype(np.float32) * 0.1,
            "ln_f": np.ones(DM, np.float32),
            "layers": [{
                "ln1": np.ones(DM, np.float32),
                "ln2": np.ones(DM, np.float32),
                "in": rng.randn(DM, 2 * di + 256 + 16).astype(np.float32)
                * 0.2,
                "conv_w": rng.randn(4, width).astype(np.float32) * 0.5,
                "conv_b": np.zeros(width, np.float32),
                "dt_b": np.zeros(16, np.float32),
                "a_log": np.zeros(16, np.float32),
                "d_skip": np.ones(16, np.float32),
                "norm": np.ones(di, np.float32),
                "out": rng.randn(di, DM).astype(np.float32) * 0.05,
                "gate": rng.randn(DM, FF).astype(np.float32) * 0.1,
                "up": rng.randn(DM, FF).astype(np.float32) * 0.1,
                "down": rng.randn(FF, DM).astype(np.float32) * 0.1}]}
        got = [np.asarray(ref.logits(weights, ids[0], hp))]
    want = np.asarray(ref.logits(weights, ids[0],
                                 hp._replace(**wrong)))
    apart = np.max(np.abs(got[0] - want)) / np.std(got[0])
    assert not apart <= (0.01 if "dtype" in wrong else 0.02), apart


def test_a_state_that_is_not_the_prompts_own_shows(forward):
    """The three faults of the state that the cell's check must fail,
    as the readings tool makes them: the decode rows start from another
    sequence's state, from another's convolution rows, or from the
    prompt's own rows a row early."""
    ids, _, weights = forward
    seq, n = ids[0], 16
    want = np.asarray(ref.logits(weights, seq, HP))
    own = ref.states(weights, seq[:n], HP)
    again = np.asarray(ref.logits(weights, seq, HP, state=(n, own)))
    assert np.max(np.abs(again - want)) <= 1e-5 * np.std(want)
    other = ref.states(weights, seq[::-1][:5], HP)
    early = ref.states(weights, seq[:n - 1], HP)
    for state in ([(o[0], s[1]) for o, s in zip(other, own)],
                  [(s[0], o[1]) for o, s in zip(other, own)],
                  [(s[0], e[1]) for e, s in zip(early, own)]):
        wrong = np.asarray(ref.logits(weights, seq, HP, state=(n, state)))
        assert np.max(np.abs(wrong[:n] - want[:n])) <= 1e-5 * np.std(want)
        assert np.max(np.abs(wrong[n:] - want[n:])) > 0.02 * np.std(want)


# ---------------------------------------------------------------------------
# one group of heads through the state update's kernel, and the softmax
# scale through the three forms of grouped-query attention
# ---------------------------------------------------------------------------

def test_one_group_through_the_state_update_kernel():
    """`ssm_groups` 1 is a size: all 64 heads read the same B and C row
    (`rep` 64) through the kernel as it is, and tracing it leaves the
    third caller's plan."""
    rng = np.random.RandomState(7)
    slots, heads, p, n = 2, 64, 8, 128
    state = rng.randn(slots, heads, p, n).astype(np.float32)
    x = rng.randn(slots, heads, p).astype(np.float32)
    dt = np.abs(rng.randn(slots, heads)).astype(np.float32) * 0.1
    a = -np.abs(rng.randn(heads)).astype(np.float32)
    b = rng.randn(slots, 1, n).astype(np.float32)
    c = rng.randn(slots, 1, n).astype(np.float32)
    live = np.array([True, False])
    before = len([e for e in trace.events() if e.get("name") == "ssd_plan"])
    y, moved = ssd_update.ssd_decode_update(
        *(jnp.asarray(t) for t in (state, x, dt, a, b, c, live)),
        interpret=True)
    y_ref, moved_ref = ssd_update.ssd_update_reference(
        *(jnp.asarray(t) for t in (state, x, dt, a, b, c, live)))
    np.testing.assert_allclose(np.asarray(moved), np.asarray(moved_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(moved)[1], state[1])
    plans = [e["args"] for e in trace.events()
             if e.get("name") == "ssd_plan"][before:]
    assert plans and (plans[-1]["groups"], plans[-1]["rep"],
                      plans[-1]["heads"]) == (1, 64, 64)
    plan = ssd_update.ssd_update_plan(64, 1, 64, 128)
    assert (plan.rep, plan.state_vregs, plan.mxu_products) == (64, 512, 64)


def _attention_weights(rng, d, heads, kv, hd):
    return {"Wq": rng.randn(d, heads * hd).astype(np.float32) * 0.3,
            "Wk": rng.randn(d, kv * hd).astype(np.float32) * 0.3,
            "Wv": rng.randn(d, kv * hd).astype(np.float32) * 0.3,
            "Wo": rng.randn(heads * hd, d).astype(np.float32) * 0.3}


def _dense_attention(x, w, heads, kv, hd, scale):
    seq = x.shape[0]
    q = (x @ w["Wq"]).reshape(seq, kv, heads // kv, hd)
    k = (x @ w["Wk"]).reshape(seq, kv, hd)
    v = (x @ w["Wv"]).reshape(seq, kv, hd)
    s = np.einsum("qngd,knd->ngqk", q, k) * scale
    s = np.where(np.tril(np.ones((seq, seq), bool))[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("ngqk,knd->qngd", p, v).reshape(seq, heads * hd) \
        @ w["Wo"]


@pytest.mark.parametrize("form", ["whole", "chunked"])
def test_the_softmax_scale_is_the_configurations(form, monkeypatch):
    """`attn_scale` through the op's whole-sequence form and through its
    query-row chunks, against dense attention at that scale; at the
    default the same op reads 1 / sqrt(head_dim)."""
    rng = np.random.RandomState(5)
    d, heads, kv, hd, seq = 16, 4, 2, 8, 256
    w = _attention_weights(rng, d, heads, kv, hd)
    x = rng.randn(1, seq, d).astype(np.float32)
    if form == "chunked":
        monkeypatch.setattr(attn_ops, "_Q_CHUNK_BYTES",
                            128 * heads * hd * 4)
    attrs = dict(num_heads=heads, num_kv_heads=kv, head_dim=hd,
                 index_heads=0, index_head_dim=0, index_topk=0,
                 rope_theta=1e4, epsilon=1e-5, rotary="none")
    ins = {"X": [jnp.asarray(x)], **{k: [jnp.asarray(v)]
                                     for k, v in w.items()}}
    for scale, said in ((0.03, dict(scale=0.03)), (hd ** -0.5, {})):
        got = np.asarray(attn_ops.grouped_attention(
            None, ins, dict(attrs, **said))["Out"][0])[0]
        want = _dense_attention(x[0], w, heads, kv, hd, scale)
        assert np.max(np.abs(got - want)) <= 2e-5 * np.std(want)
    other = _dense_attention(x[0], w, heads, kv, hd, hd ** -0.5)
    assert np.max(np.abs(got - other)) <= 2e-5 * np.std(other)


def test_the_softmax_scale_through_the_kernels():
    """The flash forward and the grouped paged kernel (two K/V heads of
    64 to a lane tile, as the cell's pools hold them) at a scale that is
    not 1 / sqrt(D), in interpret mode, against their references at the
    same scale and apart from the default's."""
    rng = np.random.RandomState(9)
    scale = 1.0 / 64
    q = jnp.asarray(rng.randn(1, 128, 4, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    got = fa.flash_attention(q, k, v, causal=True, scale=scale,
                             interpret=True)
    want = fa.mha_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                            None, causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    default = fa.mha_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                               None, causal=True)
    assert np.max(np.abs(np.asarray(got) - np.asarray(default))) > 0.05

    slots, heads, kv, hd, bs, blocks = 3, 8, 2, 64, 8, 12
    row = tfm.packed_kv_row(kv, hd)
    assert row == [1, 128]
    k_pool = jnp.asarray(rng.randn(blocks, bs, *row).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(blocks, bs, *row).astype(np.float32))
    qs = jnp.asarray(rng.randn(slots, heads, hd).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2, 3, 0], [4, 0, 0, 0],
                                   [5, 6, 7, 8]], np.int32))
    lens = jnp.asarray(np.array([19, 0, 32], np.int32))
    got = pa.paged_decode_attention(qs, k_pool, v_pool, tables, lens,
                                    scale=scale, interpret=True)
    want = pa.paged_attention_reference(qs, k_pool, v_pool, tables, lens,
                                        scale=scale)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=2e-5,
                               atol=2e-5)
    default = pa.paged_attention_reference(qs, k_pool, v_pool, tables, lens)
    assert np.max(np.abs(np.asarray(got)[live]
                         - np.asarray(default)[live])) > 0.05


# ---------------------------------------------------------------------------
# the bundle: float32 as every other, and with bfloat16 matrices
# ---------------------------------------------------------------------------

def export_cfg(block):
    return dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
                max_context=MAXC, block=block)


def _export(tmp, block, weight_dtype="bfloat16", seed=3, pool_blocks=POOL,
            round_scope=False, buckets=BUCKETS):
    """(the bundle's directory, the reference's weights as the bundle
    stores them, the scope's float32 matrices)."""
    _, startup, _ = _built(16, block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, seed)
        exact = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
        if round_scope:
            _round_in_scope(scope, weight_dtype)
        pio.export_decode_model(
            tmp, export_cfg(block), scope=scope, length_buckets=buckets,
            slots=SLOTS, block_size=BLOCK, pool_blocks=pool_blocks,
            weight_dtype=weight_dtype)
    rounded = jax.tree_util.tree_map(
        lambda w: w if w.ndim < 2 or w.shape[0] == TAPS
        else np.asarray(jnp.asarray(w).astype(weight_dtype or "float32")),
        exact)
    return tmp, rounded, exact


@pytest.fixture(scope="module")
def bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("granite4") / "m"),
                   block_of())


@pytest.fixture(scope="module")
def served(bundle):
    """The module's bundle loaded ONCE: its buckets and its step compile
    for every case that drives the model by hand (each leaves the pools
    as it found them: `reset_pools`)."""
    return DecodeModel(bundle[0], warmup=False)


def _meta(d):
    with open(os.path.join(d, "serving.json")) as f:
        return json.load(f)


def test_serving_json_declares_the_weights_and_the_states(bundle):
    meta = _meta(bundle[0])
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    assert dec["model_cfg"]["block"]["attn_scale"] == ATTN_SCALE
    matrices = [n for n in dec["weights"]
                if pio.is_weight_matrix(n, (2, 2))]
    assert sorted(meta["weights"]["stored"]["bfloat16_as_uint16"]) \
        == sorted(matrices)
    assert "tok_emb" in matrices and "mamba0_conv_w" not in matrices \
        and "lm_head_w" not in dec["weights"]
    with np.load(os.path.join(bundle[0], "weights.npz")) as f:
        on_disk = {n: f[n] for n in f.files}
    assert {str(on_disk[n].dtype) for n in matrices} == {"uint16"}
    assert {str(v.dtype) for n, v in on_disk.items()
            if n not in matrices} == {"float32"}
    assert meta["weights"]["dtype"] == "bfloat16"
    assert meta["weights"]["bytes"] == sum(v.nbytes
                                           for v in on_disk.values())
    row = 4 * 2 * NKV * HD
    assert dec["cache"]["layer_kinds"] == ["state", "full", "state"]
    assert dec["cache"]["kinds"] == {
        "full": {"layers": 1, "pool_blocks": POOL,
                 "blocks_per_seq": MAXC // BLOCK, "bytes_per_token": row},
        "state": {"layers": STATE_LAYERS,
                  "rows": [[H, P, N], [TAPS - 1, WIDTH]],
                  "bytes_per_slot": STATE_ROW_BYTES}}
    feeds = [(m["name"], m["shape"], m["dtype"]) for m in dec["feeds"]]
    scan = lambda i: [(f"ssm_state_{i}", [SLOTS, H, P, N], "float32"),
                      (f"conv_state_{i}", [SLOTS, TAPS - 1, WIDTH],
                       "float32")]
    assert feeds[3:] == [
        *scan(0),
        ("k_cache_1", [POOL, BLOCK, NKV, HD], "float32"),
        ("v_cache_1", [POOL, BLOCK, NKV, HD], "float32"), *scan(2)]
    assert dec["fetches"][0] == {"name": "logits", "shape": [SLOTS, V],
                                 "dtype": "float32"}


def _fingerprint(w):
    flat = jnp.asarray(w).reshape(-1).astype(jnp.float32)
    ramp = (jnp.arange(flat.shape[0]) % 251).astype(jnp.float32)
    return np.asarray(jnp.stack([jnp.sum(flat), jnp.sum(flat * flat),
                                 jnp.sum(flat * ramp)]))


@pytest.mark.parametrize("round_scope", [False, True],
                         ids=["rounded_by_the_export", "in_the_scope"])
def test_a_bfloat16_bundle_round_trips_bit_for_bit(bundle, served,
                                                   tmp_path, round_scope):
    """The `.npy` pieces hold the bfloat16 BITS (numpy has no bfloat16
    of its own) and come back on the device as the rounding of the
    scope's float32 matrices, whoever rounded them; the small parameters
    come back as the float32 they were; fingerprints taken of the
    rounded values come back bit for bit."""
    d, rounded, exact = _export(
        str(tmp_path / "m"), block_of(), round_scope=True,
        buckets=(8,)) if round_scope else bundle
    model = DecodeModel(d, warmup=False) if round_scope else served
    back = reference_weights(model.weights.__getitem__)
    same = jax.tree_util.tree_map(
        lambda a, b: a.dtype == b.dtype and np.array_equal(
            np.asarray(a).view(np.uint8), np.asarray(b).view(np.uint8)),
        back, rounded)
    assert all(jax.tree_util.tree_leaves(same))
    assert back["tok_emb"].dtype == jnp.bfloat16 \
        and back["layers"][0]["conv_w"].dtype == jnp.float32
    assert np.array_equal(
        np.stack([_fingerprint(w)
                  for w in jax.tree_util.tree_leaves(back)]),
        np.stack([_fingerprint(w)
                  for w in jax.tree_util.tree_leaves(rounded)]))
    # and they ARE a rounding: not the float32 values, near them
    assert not np.array_equal(np.asarray(back["tok_emb"], np.float32),
                              exact["tok_emb"])
    np.testing.assert_allclose(np.asarray(back["tok_emb"], np.float32),
                               exact["tok_emb"], rtol=2 ** -8)
    assert model.weight_dtype == "bfloat16"
    assert model.weight_bytes == _meta(d)["weights"]["bytes"]


def test_a_bundle_without_the_record_loads_as_float32(tmp_path):
    """The default export stores float32 as every bundle before did; and
    a serving.json from before the `weights` record loads its pieces as
    they are."""
    d, _, exact = _export(str(tmp_path / "m"), block_of(), weight_dtype="",
                          buckets=(8,))
    meta = _meta(d)
    assert meta["weights"]["dtype"] == "float32" \
        and meta["weights"]["stored"] == {"bfloat16_as_uint16": []}
    del meta["weights"]
    with open(os.path.join(d, "serving.json"), "w") as f:
        json.dump(meta, f)
    model = DecodeModel(d, warmup=False)
    assert model.weight_dtype == "float32"
    assert {str(w.dtype) for w in model.weights.values()} == {"float32"}
    assert np.array_equal(np.asarray(model.weights["tok_emb"]),
                          exact["tok_emb"])
    assert model.weight_bytes == 4 * sum(
        int(w.size) for w in model.weights.values())


def test_what_the_export_cannot_round_is_refused(tmp_path):
    with pytest.raises(ValueError, match="weight_dtype"):
        pio.export_decode_model(
            str(tmp_path / "a"), export_cfg(block_of()), scope=pt.Scope(),
            weight_dtype="float16")
    experts = tfm.BlockSpec(
        norm="rms_norm", positions="rope", bias=False, ffn="moe_gated",
        num_experts=4, experts_per_tok=2)
    with pytest.raises(NotImplementedError, match="moe_gated"):
        pio.export_decode_model(
            str(tmp_path / "b"), dict(export_cfg(experts)),
            scope=pt.Scope(), weight_dtype="bfloat16")


@pytest.mark.parametrize("p_len,former,steps", [
    (21, 0, 14), (32, 5, 14), (1, 7, 14), (9, 13, MAXC - 9)])
def test_prefill_then_decode_through_the_served_bundle(bundle, served,
                                                       p_len, former,
                                                       steps):
    """Logits after the prefill and after each teacher-forced step,
    through the two state layers' states (a bucket's end is not the prompt's:
    21 of 32, 9 of 32, 1 of 8) and the one full layer's pool, from the
    bfloat16 bundle, against the reference's full forward on the same
    rounded matrices; `former`: the slot and its blocks held another
    sequence's rows before; the last case runs to the context's last
    row."""
    _, weights, _ = bundle
    model = served
    model.reset_pools()
    ids = np.random.RandomState(p_len).randint(0, V, p_len + steps)
    total, slot = len(ids), 1
    want = np.asarray(ref.logits(weights, ids, HP))
    tol = 1e-5 * np.std(want)
    blocks = list(range(3, 3 + -(-total // BLOCK)))
    tokens = np.zeros(SLOTS, np.int64)
    lens = np.zeros(SLOTS, np.int32)
    tables = np.zeros((SLOTS, MAXC // BLOCK), np.int32)
    tables[slot, :len(blocks)] = blocks
    if former:
        other = ids[::-1][:former]
        _, kv = model.prefill([int(t) for t in other])
        model.seed_sequence(blocks[:-(-former // BLOCK)], kv, slot=slot)
        tokens[slot], lens[slot] = other[0], former + 1
        model.decode_step(tokens, lens, tables).tokens
    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    model.seed_sequence(blocks[:-(-p_len // BLOCK)], kv, slot=slot)
    for j in range(total - p_len):
        tokens[slot], lens[slot] = ids[p_len + j], p_len + j + 1
        rows = model.decode_step(tokens, lens, tables)
        assert np.asarray(rows).dtype == np.float32
        assert np.max(np.abs(np.asarray(rows)[slot]
                             - want[p_len + j])) <= tol, j
    # the four states and the two pools are all updated in place
    assert model.step_aliased_bytes == sum(
        4 * int(np.prod(s)) for s in model._pool_shapes) \
        > model.state_bytes == SLOTS * STATE_ROW_BYTES
    assert (model.state_layers, model.full_layers) == (STATE_LAYERS,
                                                       FULL_LAYERS)
    assert len(model._pool_table) == 2 * STATE_LAYERS + 2 * FULL_LAYERS


# ---------------------------------------------------------------------------
# the engine: the states through everything a slot goes through
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
    for n in (29, 3, 1):        # one at a time: slot 0 every time
        _served(dec, weights, _prompts(n, [n]), 12)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 3
    return dec


def _case_a_preemption_and_resume(d, weights, tmp):
    """A pool too small for three sequences: one is preempted and
    resumes by a prefill of prompt + generated, which rebuilds its
    states in whatever slot it then gets."""
    d, weights, _ = _export(str(tmp / "m"), block_of(), pool_blocks=9)
    dec = DecodeEngine(d, max_new_tokens=14, warmup=False)
    _poison(dec)
    results = _served(dec, weights, _prompts(11, [14, 9, 15]), 14)
    assert sum(r["evictions"] for r in results) > 0
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    assert snap["state_seeds"] == snap["prefills"] > 3
    return dec


_CASES = {name[len("_case_"):]: fn for name, fn in sorted(globals().items())
          if name.startswith("_case_")}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_state_is_the_sequences_own(bundle, tmp_path, case):
    """Every output is the reference's greedy continuation (the
    reference has no cache and no state), whatever the slot and the
    blocks held before; every block comes back; the state counters
    count every state layer of every live slot."""
    d, weights, _ = bundle
    dec = _CASES[case](d, weights, tmp_path)
    snap = dec.metrics_snapshot()
    assert dec.pool.blocks_in_use == 0
    live = snap["slots_used_sum"] + snap["overrun_tokens"]
    assert snap["state_slot_steps"] == STATE_LAYERS * live
    assert snap["state_seed_bytes"] == STATE_ROW_BYTES * snap["state_seeds"]
    assert snap["state_bytes"] == SLOTS * STATE_ROW_BYTES
    assert "moe_layer_steps" not in snap
    dec.shutdown()


def test_through_the_engine_with_its_counters(bundle):
    d, weights, _ = bundle
    engine = ServingEngine()
    engine.load_decode_model("granite", d, warmup=False, max_new_tokens=16)
    dec = engine.decode_engine("granite")
    prompts = _prompts(10, [5, 13, 30, 8])
    handles = [engine.generate("granite", p, max_new_tokens=16)
               for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, prompt, out)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 4
    assert snap["weight_dtype"] == "bfloat16" \
        == dec.describe()["weight_dtype"]
    assert snap["weight_bytes"] == dec.describe()["weight_bytes"] \
        == _meta(d)["weights"]["bytes"]
    text = render_prometheus(engine.metrics.snapshot())
    for line in ('pt_decode_weight_bytes{model="granite",dtype="bfloat16"}'
                 ' %d' % snap["weight_bytes"],
                 'pt_decode_state_slot_steps_total{model="granite"} %d'
                 % snap["state_slot_steps"],
                 'pt_decode_state_seeds_total{model="granite"} 4',
                 'pt_decode_state_bytes{model="granite"} %d'
                 % (SLOTS * STATE_ROW_BYTES)):
        assert line in text, line
    assert dec.describe()["refuses"] == ["kv_share", "speculation"]
    assert dec.describe()["expert_kernel"] is None
    with pytest.raises(SequenceStateUnsupported, match="kv_share"):
        DecodeEngine(model=dec.model, kv_share=True, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="speculation"):
        DecodeEngine(model=dec.model, drafter="ngram", spec_k=2,
                     warmup=False)
    engine.shutdown()


def test_the_mixer_and_the_ffn_are_named_in_the_compiled_programs(served):
    """What a profile shows: a mixed layer's mixer under `mamba2` and
    its FFN under `gated_ffn`, in the step and in a prefill bucket; and
    no matrix is converted to float32 ahead of its product (the
    compiled step holds no float32 array of a matrix's shape)."""
    model = served
    model.decode_step(np.zeros(SLOTS, np.int64), np.zeros(SLOTS, np.int32),
                      np.zeros((SLOTS, MAXC // BLOCK), np.int32)).tokens
    text = model._step.as_text()
    assert "mamba2" in text and "gated_ffn" in text
    calls = model._admit_fns[BUCKETS[-1]]
    text = calls.prefill.lower(
        calls.weights, np.zeros(calls.ids_shape, calls.ids_dtype),
        np.int32(3)).compile().as_text()
    assert "mamba2" in text and "gated_ffn" in text


# ---------------------------------------------------------------------------
# what the block can and cannot be
# ---------------------------------------------------------------------------

def test_a_mixed_layer_is_a_mixer_and_an_ffn():
    block = block_of()
    kinds = [block.layer(i, FF) for i in range(L)]
    assert [(k.mixer, k.ffn, k.ffn_width, k.cache, k.positions)
            for k in kinds] == [
        ("mamba2", "gated", FF, "state", "none"),
        ("attention", "gated", FF, "full", "none"),
        ("mamba2", "gated", FF, "state", "none")]
    # "mamba2" and "attn" keep their one meaning: the part alone
    alone = block_of(layer_pattern=("mamba2", "attn", "ffn"))
    assert [(k.mixer, k.ffn) for k in (alone.layer(i, FF)
                                       for i in range(3))] == [
        ("mamba2", "none"), ("attention", "none"), ("none", "gated")]
    said = block.to_dict()
    assert said["attn_scale"] == ATTN_SCALE and tfm.BlockSpec.of(said) \
        == block
    # at its default the field is not said: every other bundle's record
    # is what it was
    assert "attn_scale" not in block_of(attn_scale=0.0).to_dict()
    assert "attn_scale" not in tfm.GPT2_BLOCK.to_dict()
    pools = block.cache_pools(NH, DM, 0)
    assert pools["state"] == [("ssm_state", [H, P, N]),
                              ("conv_state", [TAPS - 1, WIDTH])]


@pytest.mark.parametrize("wrong,match", [
    (dict(ssm_groups=3), "ssm_groups"),
    (dict(ssm_heads=0), "ssm_heads"),
    (dict(conv_taps=0), "conv_taps"),
    (dict(attn_scale=-1.0), "attn_scale"),
    (dict(layer_pattern=("full",), conv_taps=0, ssm_inner=0, ssm_state=0,
          ssm_heads=0, ssm_groups=0, ssm_chunk=0), "carry the order"),
    (dict(ffn="moe_gated", num_experts=4, experts_per_tok=2,
          tied_head=False), "DENSE"),
    (dict(layer_pattern=("mamba2_ffn", "mamba"), ssm_dt_rank=2),
     "beside no 'mamba'"),
    (dict(differential=True), "attn_scale")])
def test_what_the_block_cannot_be_is_refused(wrong, match):
    with pytest.raises(ValueError, match=match):
        block_of(**wrong)


def test_attn_scale_belongs_to_grouped_query_attention():
    with pytest.raises(ValueError, match="attn_scale"):
        tfm.BlockSpec(attn_scale=0.1)


def test_the_trainer_refuses_the_block_typed():
    with pytest.raises(NotImplementedError, match="mamba2_ffn"):
        with pt.program_guard(pt.Program(), pt.Program()):
            tfm.transformer_lm_loss(
                vocab_size=V, seq_len=16, n_layers=L, d_model=DM,
                n_heads=NH, d_ff=FF, max_len=MAXC, block=block_of())
