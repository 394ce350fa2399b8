"""Nemotron-H's block (Mamba-2 layers whose state is a matrix a head,
attention without positions, two-matrix relu2 experts behind a
sigmoid-plus-bias router, every layer ONE of the three under one norm and
one residual) through the builders of `models/transformer.py` and the
decode engine, against the plain reference
`benchmark/reference_nemotron3.py`, loaded by path: the reference lives
ONCE (ROADMAP D19) and imports nothing of `paddle_tpu`.

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and no more.
"""

import functools
import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from references import by_path
from paddle_tpu import io as pio
from paddle_tpu.kernels import ssd_update
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.decode.engine import (DecodeEngine,
                                              SequenceStateUnsupported)
from paddle_tpu.serving.metrics import render_prometheus

attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
moe_ops = importlib.import_module("paddle_tpu.ops.moe_ops")
HERE = os.path.dirname(os.path.abspath(__file__))


ref = by_path("reference_nemotron3")

V, DM, NH, NKV, HD, FF, SFF = 97, 32, 4, 2, 8, 24, 40
H, P, G, N, TAPS, CHUNK = 4, 8, 2, 128, 4, 8
DI, WIDTH = H * P, H * P + 2 * G * N
E, K = 8, 3
PATTERN = ("mamba2", "ffn", "mamba2", "ffn", "mamba2", "attn", "ffn")
L = len(PATTERN)
MAXC, BLOCK, POOL, SLOTS = 64, 8, 40, 3
BUCKETS = (8, 16, 32)
STATE_LAYERS, FULL_LAYERS, EXPERT_LAYERS = 3, 1, 3
STATE_LAYER_BYTES = 4 * (H * P * N + (TAPS - 1) * WIDTH)     # a slot's
STATE_ROW_BYTES = STATE_LAYERS * STATE_LAYER_BYTES
_KIND = {"mamba2": "mamba", "ffn": "experts", "attn": "attention"}


def block_of(**changes):
    spec = dict(norm="rms_norm", positions="none", bias=False,
                attention="gqa", n_kv_heads=NKV, head_dim=HD,
                ffn="moe_gated", num_experts=E, experts_per_tok=K,
                router="sigmoid_bias", norm_topk=True, routed_scale=2.5,
                shared_width=SFF, expert_form="relu2",
                layer_pattern=PATTERN, conv_taps=TAPS, ssm_inner=DI,
                ssm_state=N, ssm_heads=H, ssm_groups=G, ssm_chunk=CHUNK)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


def hyper(first=0, **changes):
    return ref.Hyper(tuple(_KIND[k] for k in PATTERN), NH, NKV, HD, H, P,
                     G, N, K, first, 2.5)._replace(**changes)


HP = hyper()

_MAMBA = dict(conv_w="conv_w", conv_b="conv_b", dt_b="dt_b", a_log="a_log",
              d_skip="d_skip", norm="norm_scale", out="out_w",
              **{"in": "in_w"})
_ATTN = dict(q="q_w", k="k_w", v="v_w", out="out_w")
_EXPERTS = dict(router="router_w", router_bias="router_bias", up="up_w",
                down="down_w", shared_up="shared_up_w",
                shared_down="shared_down_w")
_STEMS = {"mamba2": ("mamba", _MAMBA), "attn": ("attn", _ATTN),
          "ffn": ("moe", _EXPERTS)}


def reference_weights(get):
    layers = []
    for i, kind in enumerate(PATTERN):
        stem, names = _STEMS[kind]
        w = {k: get(f"{stem}{i}_{n}") for k, n in names.items()}
        w["ln"] = get(f"ln1_{i}_scale")
        layers.append(w)
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def randomise(scope, seed):
    """Seeded weights with gains away from 1, a selection bias that
    chooses, taps of the size of the rows they weigh; the scans' A_log,
    step bias and D_skip as the layer draws them, D_skip moved off 1."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32 or name.endswith(("a_log", "dt_b")):
            continue
        if name.endswith(("_scale", "d_skip")):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("_conv_w"):
            new = rng.randn(*v.shape) * 0.5
        elif name.endswith("router_bias"):
            new = 0.1 * rng.randn(*v.shape)
        else:
            new = rng.randn(*v.shape) * (0.7 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.3)
        # an expert's width is stored in whole tiles, zeros behind. The
        # routed experts' down matrix stores the MODEL width in whole
        # tiles too ([E, 256, 512] here): the columns behind DM keep
        # their random values, ON PURPOSE: they are no part of the model,
        # and every case below that meets the reference holds with them
        # that nothing reads them
        width = SFF if "shared" in name else FF
        if name.endswith("up_w") and "moe" in name:
            new[..., width:] = 0.0
        elif name.endswith("down_w") and "moe" in name:
            new[..., width:, :] = 0.0
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
    """24 tokens: three chunks of the SSD form; 3: the rows before the
    sequence's first are zeros in every convolution."""
    ids, got, weights = forward if seq_len == 24 \
        else run_forward(seq_len, block_of())
    assert got.shape == (2, seq_len, V)
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


FAULTS = [dict(gate="after"), dict(norm="whole"), dict(pairing="strided"),
          dict(dt_bias="after"), dict(skip="dropped"),
          dict(conv="no_bias"), dict(conv="no_silu"), dict(act="relu"),
          dict(act="gated_silu"), dict(weigh="biased"),
          dict(routed_scale=1.0), dict(norm_topk=False),
          dict(shared="dropped"), dict(rotary="half"),
          dict(dtype="bfloat16")]


@pytest.mark.parametrize("wrong", FAULTS, ids=lambda w: "-".join(
    f"{k}_{v}" for k, v in w.items()))
def test_the_parts_of_the_block_each_count(forward, wrong):
    """What the tolerance above is far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread (the faults `benchmark/tools/nemotron3_check_readings.py`
    shows the cell's limits fail)."""
    ids, got, weights = forward
    if "conv" in wrong:     # the layer starts its bias at zero
        weights = dict(weights, layers=[
            dict(w, conv_b=np.full_like(w["conv_b"], 0.3))
            if "conv_b" in w else w for w in weights["layers"]])
        got = [np.asarray(ref.logits(weights, ids[0], HP))]
    off = np.asarray(ref.logits(weights, ids[0], HP._replace(**wrong)))
    assert not np.max(np.abs(off - got[0])) / np.std(got[0]) <= 0.02, wrong


def test_a_state_that_is_not_the_prompts_own_shows(forward):
    """The state a sequence leaves, given back to the rows behind it,
    changes nothing; another sequence's (the slot's former owner), the
    one a padded bucket's end leaves, or the sequence's own a row
    behind, does."""
    ids, _, weights = forward
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(state):
        return float(np.max(np.abs(np.asarray(ref.logits(
            weights, ids[0], HP, state=state)) - want)) / np.std(want))

    own = ref.states(weights, ids[0][:17], HP)
    assert len(own) == STATE_LAYERS and own[0][0].shape == (H, P, N) \
        and own[0][1].shape == (TAPS - 1, WIDTH)
    assert off_by((17, own)) <= 1e-5
    padded = np.concatenate([ids[0][:17], np.zeros(15, ids.dtype)])
    for other in (ref.states(weights, ids[1][:9], HP),
                  ref.states(weights, padded, HP),
                  ref.states(weights, ids[0][:16], HP)):
        assert off_by((17, other)) > 0.02


# ---------------------------------------------------------------------------
# the mixer op: the SSD chunks against the token-by-token recurrence
# ---------------------------------------------------------------------------

def _mixer_weights(rng):
    w = {"in": rng.randn(DM, DI + WIDTH + H) / 6,
         "conv_w": rng.randn(TAPS, WIDTH) / 2,
         "conv_b": rng.randn(WIDTH) / 4,
         "dt_b": np.log(np.expm1(np.exp(rng.uniform(
             np.log(1e-3), np.log(1e-1), H)))),
         "a_log": np.log(rng.uniform(1, 16, H)),
         "d_skip": 1 + 0.2 * rng.randn(H), "norm": 1 + 0.2 * rng.randn(DI),
         "out": rng.randn(DI, DM) / 8}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _mixer_op(x, w, chunk=128, **more):
    ins = {"X": [x], "WIn": [w["in"]], "ConvW": [w["conv_w"]],
           "ConvB": [w["conv_b"]], "BDt": [w["dt_b"]], "ALog": [w["a_log"]],
           "DSkip": [w["d_skip"]], "NormW": [w["norm"]], "WOut": [w["out"]]}
    ins.update({k: [v] for k, v in more.items()})
    return attn_ops.mamba2_mixer(None, ins, {
        "heads": H, "groups": G, "d_state": N, "chunk": chunk,
        "epsilon": 1e-5})


@pytest.mark.parametrize("seq,n", [(256, 256), (256, 130), (72, 65),
                                   (16, 1), (5, 5)])
def test_the_ssd_chunks_match_the_token_by_token_recurrence(seq, n):
    """Two chunks of 128, chunks of 8, a sequence shorter than
    the taps; `n` the true length, a multiple of the chunk or not, or 1:
    the rows behind it are padding, which moves neither state, and the
    state returned is the one a decode step at position n reads."""
    rng = np.random.RandomState(seq + n)
    w = _mixer_weights(rng)
    x = jnp.asarray(rng.randn(2, seq, DM), jnp.float32)
    lens = jnp.asarray([n, max(n - 3, 1)], jnp.int32)
    got = _mixer_op(x, w, NTokens=lens)
    for b in range(2):
        m = int(lens[b])
        out, (state, rows) = ref._mamba(x[b, :m], w, HP)
        assert np.allclose(got["Out"][0][b, :m], out, atol=3e-5)
        assert np.allclose(got["SsmStateOut"][0][b], state, atol=3e-5)
        assert np.allclose(got["ConvStateOut"][0][b], rows, atol=1e-6)
        # and a step from that state is the next row of the whole scan
        nxt = jnp.asarray(rng.randn(1, 1, DM), jnp.float32)
        step = _mixer_op(nxt, w, SsmState=got["SsmStateOut"][0][b:b + 1],
                         ConvState=got["ConvStateOut"][0][b:b + 1],
                         ContextLens=jnp.asarray([m + 1], jnp.int32))
        whole, (state, rows) = ref._mamba(
            jnp.concatenate([x[b, :m], nxt[0]]), w, HP)
        assert np.allclose(step["Out"][0][0, 0], whole[-1], atol=3e-5)
        assert np.allclose(step["SsmStateOut"][0][0], state, atol=3e-5)
        assert np.allclose(step["ConvStateOut"][0][0], rows, atol=1e-6)
    # an empty slot keeps its state as it was
    idle = _mixer_op(nxt, w, SsmState=got["SsmStateOut"][0][:1],
                     ConvState=got["ConvStateOut"][0][:1],
                     ContextLens=jnp.asarray([0], jnp.int32))
    assert np.array_equal(idle["SsmStateOut"][0], got["SsmStateOut"][0][:1])
    assert np.array_equal(idle["ConvStateOut"][0],
                          got["ConvStateOut"][0][:1])


# ---------------------------------------------------------------------------
# the decode kernel: interpreted, against its jnp reference
# ---------------------------------------------------------------------------

def _update_case(lens, seed, heads=8, p=16, groups=2, n=128, dt_ones=False,
                 exact=False):
    """`exact`: the state and B hold signed powers of two, so both
    products of `decay * S + (dt x) * B` are exact and the sum rounds
    once however a compiler contracts it (XLA's CPU backend fuses one
    product or the other into the add, differently from kernel to
    kernel; the chip's vector unit has no fused multiply-add)."""
    rng = np.random.RandomState(seed)
    slots = len(lens)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    two = lambda *shape: jnp.asarray(
        rng.choice([-1.0, 1.0], shape) * 2.0 ** rng.randint(-3, 4, shape),
        jnp.float32)
    state, b = (two, two) if exact else (f, f)
    dt = jnp.ones((slots, heads), jnp.float32) if dt_ones else jnp.asarray(
        rng.uniform(1e-3, 0.5, (slots, heads)), jnp.float32)
    return (state(slots, heads, p, n), f(slots, heads, p), dt,
            -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32),
            b(slots, groups, n), f(slots, groups, n),
            jnp.asarray(lens, jnp.int32) > 0)


#: the kernel's callers in miniature: the suite's first form (4 heads a
#: group in 2 groups), Nemotron's (8 heads share one B and C row, P under
#: a lane tile's 128), MiniCPM-SALA's (a group a head, P 128, dt = 1)
_UPDATE_FORMS = {
    "rep4_groups2": dict(),
    "nemotron_rep8": dict(heads=8, p=16, groups=1),
    "sala_rep1": dict(heads=4, p=128, groups=4, dt_ones=True),
}


@pytest.mark.parametrize("form", list(_UPDATE_FORMS))
@pytest.mark.parametrize("lens", [[5, 0, 17, 1], [0, 0, 3, 0], [2, 9, 4, 4],
                                  [0, 0, 0, 0], [7, 0, 0, 0]],
                         ids=lambda l: "-".join(map(str, l)))
def test_the_state_update_kernel_matches_its_reference(lens, form):
    """The Pallas kernel, interpreted: a live slot's state moved a row
    on and read, a row block at a time, where the reference does it
    whole; a slot that is not live is untouched, bit for bit, wherever
    it lies among the live ones (and where none is live at all). Where
    the reference's operations are the kernel's (`decay * S + (dt x) *
    B`, on operands whose products are exact: `_update_case`) the state
    is the reference's bit for bit; y is a float32 sum in another
    order."""
    shape = _UPDATE_FORMS[form]
    args = _update_case(lens, seed=sum(lens), **shape)
    y, moved = ssd_update.ssd_decode_update(*args, interpret=True)
    want_y, want = ssd_update.ssd_update_reference(*args)
    state, live = np.asarray(args[0]), np.asarray(args[-1])
    assert np.allclose(y, want_y, atol=1e-4)
    assert np.allclose(moved, want, atol=1e-5)
    assert np.array_equal(np.asarray(moved)[~live], state[~live])
    assert np.array_equal(np.asarray(y)[~live], np.zeros_like(y)[~live])
    args = _update_case(lens, seed=sum(lens) + 1, exact=True, **shape)
    y, moved = ssd_update.ssd_decode_update(*args, interpret=True)
    want_y, want = ssd_update.ssd_update_reference(*args)
    assert np.array_equal(moved, want)
    assert np.allclose(y, want_y, atol=1e-4)


def test_the_state_update_is_float64s_recurrence():
    state, x, dt, a, b, c, live = _update_case([3, 1], seed=2)
    y, moved = ssd_update.ssd_update_reference(state, x, dt, a, b, c, live)
    s, xs, d, bs, cs = (np.asarray(t, np.float64)
                        for t in (state, x, dt, b, c))
    for slot in range(2):
        for h in range(8):
            g = h // 4
            new = np.exp(d[slot, h] * float(a[h])) * s[slot, h] \
                + d[slot, h] * np.outer(xs[slot, h], bs[slot, g])
            assert np.allclose(moved[slot, h], new, atol=1e-5)
            assert np.allclose(y[slot, h], new @ cs[slot, g], atol=1e-4)


def test_the_lane_sum_splits_a_float32_exactly():
    """`_bf16_parts` / `_lane_sums`: three bfloat16 parts hold a float32
    whole (8 + 8 + 8 bits of mantissa, over 60 binary exponents), so the
    MXU's sum is a float32 sum of the float32 products: against float64
    it is no further off than the reference's own `jnp.sum`."""
    rng = np.random.RandomState(5)
    t = jnp.asarray(rng.randn(16, 256) * 2.0 ** rng.randint(-30, 30,
                                                            (16, 256)),
                    jnp.float32)
    parts = ssd_update._bf16_parts(t)
    assert all(part.dtype == jnp.bfloat16 for part in parts)
    t1, t2, t3 = (np.asarray(part, np.float32) for part in parts)
    assert np.array_equal(t1 + t2 + t3, t)
    t = jnp.asarray(rng.randn(16, 256), jnp.float32)
    sums = ssd_update._lane_sums(t, jnp.ones((768, 128), jnp.bfloat16))
    want = np.asarray(t, np.float64).sum(-1)
    assert np.array_equal(sums, np.broadcast_to(sums[:, :1], sums.shape))
    mine = np.max(np.abs(np.asarray(sums[:, 0], np.float64) - want))
    theirs = np.max(np.abs(np.asarray(jnp.sum(t, -1), np.float64) - want))
    assert mine <= max(2 * theirs, 1e-5), (mine, theirs)


@pytest.mark.parametrize("shape,counts", [
    ((64, 8, 64, 128), dict(rep=8, state_vregs=512, mxu_products=64,
                            vmem_bytes=16_924_672)),
    ((32, 32, 128, 128), dict(rep=1, state_vregs=512, mxu_products=32,
                              vmem_bytes=17_104_896)),
], ids=["nemotron3", "sala"])
def test_the_state_update_plan_at_the_cells_shapes(shape, counts):
    """`ssd_update_plan`: one arm, the counts from the shapes: a slot is
    512 state vregs at both callers, one lane broadcast each (`dt x`), the
    sum the MXU's (a product a head), the decay a scalar; the VMEM asked
    for is the blocks twice over (8.5 MB) and the margin's 8 MB, not 48."""
    plan = ssd_update.ssd_update_plan(*shape)
    assert (plan.decay, plan.reduction) == ("smem_scalar", "mxu_split3")
    assert plan.lane_broadcasts == plan.state_vregs
    assert {k: getattr(plan, k) for k in counts} == counts


def test_tracing_the_state_update_leaves_its_plan():
    """`kernel/ssd_plan`, once a wrapper traced: a record with no
    duration whose attrs are the plan of the call's shapes."""
    from paddle_tpu.obs import trace
    ssd_update._ssd_update_pallas.clear_cache()
    args = _update_case([3, 0, 1], seed=4, heads=8, p=16, groups=4)
    before = len([e for e in trace.events() if e.get("name") == "ssd_plan"])
    text = str(jax.make_jaxpr(functools.partial(
        ssd_update.ssd_decode_update, interpret=True))(*args))
    assert text.count("pallas_call") == 1
    records = [e for e in trace.events()
               if e.get("name") == "ssd_plan"][before:]
    assert len(records) == 1
    assert records[0]["cat"] == "kernel" and not records[0].get("dur")
    assert records[0]["args"] == ssd_update.ssd_update_plan(
        8, 4, 16, 128)._asdict()
    assert (records[0]["args"]["rep"], records[0]["args"]["reduction"]) \
        == (2, "mxu_split3")


def test_the_ssd_update_sweep_rehearses(tmp_path, capsys):
    """`tools/ssd_update_sweep.py --rehearse`: the call at the two cells'
    forms in miniature, whole and with either half of a slot's block
    stubbed, this tree's kernel beside another copy of the file (here:
    the same file, so the states are bit-equal); no time under a device's
    name."""
    spec = importlib.util.spec_from_file_location(
        "ssd_update_sweep", os.path.join(HERE, "..", "tools",
                                         "ssd_update_sweep.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "sweep.jsonl"
    assert tool.main(["--rehearse", "--kernels", os.path.abspath(
        ssd_update.__file__), "--out", str(out)]) == 0
    lines = [json.loads(line) for line in out.read_text().splitlines()]
    calls = [l for l in lines if l["what"] == "layer_call"]
    assert [(c["cell"], c["form"]) for c in calls] == [
        (cell, form) for cell in ("nemotron3", "sala")
        for form in ("other", "tree")]
    assert all(c["unit"] == "interpreted_s" for c in calls)
    assert all({"whole", "copies_alone", "arithmetic_alone",
                "whole_a_slot", "slot_hbm_us"} <= set(c) for c in calls)
    assert [c["live_slots"] for c in calls] == [3, 3, 3, 3]
    assert [(l["cell"], l["rep"]) for l in lines if l["what"] == "plan"] \
        == [("nemotron3", 8), ("sala", 1)]
    equal = [l for l in lines if l["what"] == "states_bit_equal"]
    assert len(equal) == 2 and all(l["equal"] for l in equal)
    errors = [l for l in lines if l["what"] == "against_reference"]
    assert len(errors) == 4
    assert max(l["state_max_abs"] for l in errors) <= 1e-5
    assert max(l["y_max_abs"] for l in errors) <= 1e-4
    assert "copies/slot" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the two-matrix experts: sorted, held (in waves), shared
# ---------------------------------------------------------------------------

def _expert_weights(rng, d=16, f=12, fs=20, e=8):
    w = {"router": rng.randn(d, e), "router_bias": 0.1 * rng.randn(e),
         "up": rng.randn(e, d, f) / 4, "down": rng.randn(e, f, d) / 4,
         "shared_up": rng.randn(d, fs) / 4,
         "shared_down": rng.randn(fs, d) / 4}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _moe_op(x, w, first=None, held=None, shared=True):
    sl = slice(None) if first is None else slice(first, first + held)
    ins = {"X": [x], "RouterW": [w["router"]],
           "RouterBias": [w["router_bias"]], "WUp": [w["up"][sl]],
           "WDown": [w["down"][sl]]}
    if shared:
        ins.update(SharedUp=[w["shared_up"]], SharedDown=[w["shared_down"]])
    attrs = {"top_k": 3, "router": "sigmoid_bias", "norm_topk": True,
             "routed_scale": 2.5, "expert_form": "relu2"}
    if first is not None:
        attrs["first_expert"] = first
    return moe_ops.moe_gated_ffn(None, ins, attrs)


_EHP = ref.Hyper((), 1, 1, 1, 1, 1, 1, 1, 3, 0, 2.5)


@pytest.mark.parametrize("rows,wave", [(40, 2048), (40, 16)])
def test_two_matrix_experts_sorted_and_held(rows, wave, monkeypatch):
    """relu(x W_up)^2 W_down through `_experts_sorted` (every expert
    held) and `_experts_held` (a share, one pass and in waves of 16
    rows), and the shared expert of the same form, against the
    reference's loop over the experts."""
    monkeypatch.setattr(moe_ops, "_HELD_WAVE_ROWS", wave)
    rng = np.random.RandomState(rows + wave)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.randn(rows, 16), jnp.float32)
    routed, shared = ref.experts_layer(w, x, _EHP)
    got = _moe_op(x, w)
    assert np.allclose(got["Out"][0], routed + shared, atol=2e-5)
    chosen = np.asarray(ref._route(x, w, _EHP)[0])
    assert np.array_equal(got["Experts"][0], chosen)
    held = dict(w, up=w["up"][2:5], down=w["down"][2:5])
    routed, _ = ref.experts_layer(held, x, _EHP._replace(experts_first=2))
    part = _moe_op(x, w, first=2, held=3, shared=False)
    assert np.allclose(part["Out"][0], routed, atol=2e-5)
    assert int(part["Stats"][0][3]) == int(np.sum((chosen >= 2)
                                                  & (chosen < 5)))


def test_the_shares_of_one_layer_add_up_to_the_uncut_layer():
    """The share tied to the model: four chips' parts of one E layer's
    result (experts 0-1, 2-3, 4-5, 6-7 of 8 here; 0-31 .. 96-127 of 128
    in the cell), the shared expert counted once, add up to the uncut
    reference's layer."""
    rng = np.random.RandomState(7)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.randn(24, 16), jnp.float32)
    routed, shared = ref.experts_layer(w, x, _EHP)
    whole = np.asarray(routed + shared)
    parts = [np.asarray(_moe_op(x, w, first=first, held=2,
                                shared=first == 0)["Out"][0])
             for first in (0, 2, 4, 6)]
    assert np.allclose(sum(parts), whole, atol=3e-5)
    assert min(np.max(np.abs(p)) for p in parts) > 0.05     # each counts
    # and the reference's own shares do
    own = [np.asarray(ref.experts_layer(
        dict(w, up=w["up"][f:f + 2], down=w["down"][f:f + 2]), x,
        _EHP._replace(experts_first=f))[0]) for f in (0, 2, 4, 6)]
    assert np.allclose(sum(own), routed, atol=3e-5)


def test_the_expert_forms_are_told_apart():
    rng = np.random.RandomState(1)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.randn(4, 16), jnp.float32)
    ins = {"X": [x], "RouterW": [w["router"]], "WUp": [w["up"]],
           "WDown": [w["down"]]}
    with pytest.raises(ValueError, match="3 matrices"):
        moe_ops.moe_gated_ffn(None, ins, {"top_k": 3})
    with pytest.raises(ValueError, match="2 matrices"):
        moe_ops.moe_gated_ffn(None, dict(ins, WGate=[w["up"]]),
                              {"top_k": 3, "expert_form": "relu2"})
    with pytest.raises(ValueError, match="unknown expert form"):
        moe_ops.moe_gated_ffn(None, ins, {"top_k": 3,
                                          "expert_form": "relu"})


# ---------------------------------------------------------------------------
# the bundle: prefill through a bucket, then decode through the state
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
    return _export(str(tmp_path_factory.mktemp("nemotron3") / "m"),
                   block_of())


def test_serving_json_declares_a_state_of_rank_three(bundle):
    with open(os.path.join(bundle[0], "serving.json")) as f:
        dec = json.load(f)["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    row = 4 * 2 * NKV * HD
    assert dec["cache"] == {
        "kind": "kv", "rows": [[NKV, HD], [NKV, HD]],
        "row_floats": 2 * NKV * HD, "bytes_per_token": row * FULL_LAYERS,
        "layer_kinds": ["state", "none", "state", "none", "state", "full",
                        "none"],
        "kinds": {"full": {"layers": 1, "pool_blocks": POOL,
                           "blocks_per_seq": MAXC // BLOCK,
                           "bytes_per_token": row},
                  "state": {"layers": STATE_LAYERS,
                            "rows": [[H, P, N], [TAPS - 1, WIDTH]],
                            "bytes_per_slot": STATE_ROW_BYTES}}}
    feeds = [(m["name"], m["shape"]) for m in dec["feeds"]]
    scan = lambda i: [(f"ssm_state_{i}", [SLOTS, H, P, N]),
                      (f"conv_state_{i}", [SLOTS, TAPS - 1, WIDTH])]
    assert feeds == [
        ("token_ids", [SLOTS]), ("context_lens", [SLOTS]),
        ("block_tables", [SLOTS, MAXC // BLOCK]),
        *scan(0), *scan(2), *scan(4),
        ("k_cache_5", [POOL, BLOCK, NKV, HD]),
        ("v_cache_5", [POOL, BLOCK, NKV, HD]), ("moe_stats", [3])]
    # an E layer has no role in a prefill's fetches
    assert [len(p) for p in dec["prefill_roles"]["kv"]] \
        == [2, 0, 2, 0, 2, 2, 0]
    # no gate matrix anywhere in the bundle
    assert not [n for n in dec["weights"] if "gate" in n]


@pytest.mark.parametrize("p_len,former", [(21, 0), (32, 5), (9, 13),
                                          (1, 7)])
def test_prefill_then_decode_through_the_served_bundle(bundle, p_len,
                                                       former):
    """Logits after the prefill and after each teacher-forced step,
    through the states (a bucket's end is not the prompt's: 21 of 32,
    9 of 16, 1 of 8) and the one full layer's pool, against the
    reference's full forward; `former`: the slot and its blocks held
    another sequence's rows before."""
    d, weights = bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(p_len).randint(0, V, p_len + 14)
    total, slot = len(ids), 1
    want = np.asarray(ref.logits(weights, ids, HP))
    tol = 2e-5 * np.std(want)
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
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[slot] - want[p_len + j])) <= tol, j
    # pools and states are all updated in place, every byte
    assert model.step_aliased_bytes == sum(
        4 * int(np.prod(s)) for s in model._pool_shapes) \
        > model.state_bytes == SLOTS * STATE_ROW_BYTES
    assert (model.state_layers, model.full_layers) == (STATE_LAYERS,
                                                       FULL_LAYERS)


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
    state in whatever slot it then gets."""
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
    state and its blocks."""
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
def test_the_state_is_the_sequences_own(bundle, tmp_path, case):
    """Every output is the reference's greedy continuation (the
    reference has no cache and no state), whatever the slot and the
    blocks held before; every block comes back; the counters count what
    the step moved."""
    d, weights = bundle
    dec = _CASES[case](d, weights, tmp_path)
    snap = dec.metrics_snapshot()
    assert dec.pool.blocks_in_use == 0
    live = snap["slots_used_sum"] + snap["overrun_tokens"]
    assert snap["state_slot_steps"] == STATE_LAYERS * live
    assert snap["state_seed_bytes"] == STATE_ROW_BYTES * snap["state_seeds"]
    assert snap["state_bytes"] == SLOTS * STATE_ROW_BYTES
    assert snap["moe_layer_steps"] == EXPERT_LAYERS * snap["decode_steps"]
    dec.shutdown()


def test_through_the_engine_with_its_counters(bundle):
    d, weights = bundle
    engine = ServingEngine()
    engine.load_decode_model("nemo", d, warmup=False, max_new_tokens=16)
    dec = engine.decode_engine("nemo")
    prompts = _prompts(10, [5, 13, 30, 8, 21])
    handles = [engine.generate("nemo", p, max_new_tokens=16)
               for p in prompts]
    for prompt, h in zip(prompts, handles):
        out = h.result(timeout=300)["tokens"]
        assert out == _greedy(weights, prompt, out)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 5
    pools = sum(4 * int(np.prod(s)) for s in dec.model._pool_shapes)
    assert snap["step_aliased_bytes"] == pools
    assert snap["cache_bytes_per_token"] == 4 * 2 * NKV * HD
    assert snap["moe_assignments"] == K * EXPERT_LAYERS \
        * snap["slots_used_sum"]
    text = render_prometheus(engine.metrics.snapshot())
    for line in ('pt_decode_state_slot_steps_total{model="nemo"} %d'
                 % snap["state_slot_steps"],
                 'pt_decode_state_seeds_total{model="nemo"} 5',
                 'pt_decode_state_seed_bytes_total{model="nemo"} %d'
                 % (5 * STATE_ROW_BYTES),
                 'pt_decode_state_bytes{model="nemo"} %d'
                 % (SLOTS * STATE_ROW_BYTES)):
        assert line in text, line
    assert dec.describe()["refuses"] == ["kv_share", "speculation"]
    # the step's grouped products, each with its plan: two matrices an
    # expert, at the step's slots x top-k rows (XLA's kernel at these toy
    # widths; the cell's up product is the repo's: tests/test_expert_matmul)
    plans = dec.describe()["expert_kernel"]
    assert sorted(plans) == ["down", "up"]
    assert {p["form"] for p in plans.values()} == {"ragged_dot"}
    assert (plans["up"]["rows"], plans["up"]["k"], plans["up"]["groups"]) \
        == (SLOTS * K, DM, E)
    engine.shutdown()


def test_prefix_sharing_and_speculation_are_refused_at_load(bundle):
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="kv_share"):
        DecodeEngine(model=model, kv_share=True, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="speculation"):
        DecodeEngine(model=model, drafter="ngram", spec_k=2, warmup=False)


def test_the_mixer_is_named_in_the_compiled_programs(bundle):
    """What a profile shows: the Mamba-2 layers under `mamba2`, in the
    step and in a prefill bucket."""
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    model.decode_step(np.zeros(SLOTS, np.int64), np.zeros(SLOTS, np.int32),
                      np.zeros((SLOTS, MAXC // BLOCK), np.int32)).tokens
    assert "mamba2" in model._step.as_text()
    calls = model._admit_fns[BUCKETS[-1]]
    text = calls.prefill.lower(
        calls.weights, np.zeros(calls.ids_shape, calls.ids_dtype),
        np.int32(3)).compile().as_text()
    assert "mamba2" in text


# ---------------------------------------------------------------------------
# what the block cannot be
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wrong,match", [
    (dict(ssm_heads=0), "ssm_heads"),
    (dict(ssm_groups=3), "ssm_groups"),
    (dict(ssm_dt_rank=2), "ssm_dt_rank"),
    (dict(conv_taps=0), "conv_taps"),
    (dict(expert_form="relu"), "expert_form"),
    (dict(layer_pattern=("mamba2", "mamba", "ffn"), ssm_dt_rank=2),
     "beside no 'mamba'"),
    (dict(layer_pattern=("attn", "ffn"), conv_taps=0, ssm_inner=0,
          ssm_state=0, ssm_heads=0, ssm_groups=0, ssm_chunk=0),
     "carry the order"),
    (dict(positions="learned"), "rotary positions")])
def test_what_the_block_cannot_be_is_refused(wrong, match):
    with pytest.raises(ValueError, match=match):
        block_of(**wrong)


def test_a_layer_is_one_part_alone():
    block = block_of()
    kinds = [block.layer(i, FF) for i in range(L)]
    assert [(k.mixer, k.ffn, k.cache) for k in kinds] == [
        ("mamba2", "none", "state"), ("none", "moe_gated", "none")] * 2 + [
        ("mamba2", "none", "state"), ("attention", "none", "full"),
        ("none", "moe_gated", "none")]
    assert kinds[5].positions == "none" and kinds[1].ffn_width == FF
    said = block.to_dict()
    assert said["expert_form"] == "relu2" and said["ssm_heads"] == H
    assert tfm.BlockSpec.of(said) == block
    # the blocks that were there say nothing of this one's fields: a
    # field at its default is not said, the base ones apart
    assert not {"ssm_heads", "ssm_groups", "ssm_chunk", "expert_form"} \
        & set(tfm.GPT2_BLOCK.to_dict())


def _expert_layers(dm, rows=(4,), **two):
    """A two-matrix layer `two` and a gated one `three` over x [-1, *rows,
    dm], started: (main, scope, the two layers' outputs)."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = pt.layers.data("x", list(rows) + [dm], dtype="float32")
        outs = [pt.layers.moe_gated_ffn(x, E, FF, K, shared_width=SFF,
                                        name="two", form="relu2", **two)[0],
                pt.layers.moe_gated_ffn(x, E, FF, K, shared_width=SFF,
                                        name="three")[0]]
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
    return main, scope, outs


@pytest.mark.parametrize("dm,stored", [(DM, 512), (512, 512), (520, 1024)])
def test_an_experts_width_is_stored_in_whole_tiles(dm, stored):
    """The two-matrix form's up and down matrices are [.., d, 256] and
    [.., 256, d'] here, the shared expert's [d, 128] and [128, d] (a width
    of 24, of 40: one tile each; d' the model width in whole tiles of 512
    columns: 32 -> 512, 512 as it is, 520 -> 1,024), drawn over their own
    widths as the unpadded matrix would be and ZERO behind them on every
    padded axis, so relu(0)^2 keeps the tile's rest out of the result and
    `_expert_rows` cuts zeros off; the gated form is stored as wide as it
    is."""
    _, scope, _ = _expert_layers(dm, held=(2, 3))
    got = {n: np.asarray(scope.find_var(n))
           for n in scope.local_var_names()}
    assert not [n for n in got if "@unpadded" in n]
    for name, shape, drawn in (
            ("two_up_w", (3, dm, 256), (3, dm, FF)),
            ("two_down_w", (3, 256, stored), (3, FF, dm)),
            ("two_shared_up_w", (dm, 128), (dm, SFF)),
            ("two_shared_down_w", (128, dm), (SFF, dm))):
        w = got[name]
        assert w.shape == shape, name
        inside = tuple(slice(0, n) for n in drawn)
        assert np.count_nonzero(w) == np.count_nonzero(w[inside]) \
            > 0.99 * np.prod(drawn), name
        assert np.all(np.std(w[inside], axis=-1) > 0) \
            and np.all(np.std(w[inside], axis=-2) > 0), name
        # Xavier's limit over the TRUE fans
        fans = drawn[-2] + drawn[-1]
        assert 0.9 * np.sqrt(6.0 / fans) < np.max(np.abs(w)) \
            <= np.sqrt(6.0 / fans), name
    for name, shape in (("three_gate_w", (E, dm, FF)),
                        ("three_up_w", (E, dm, FF)),
                        ("three_down_w", (E, FF, dm)),
                        ("three_shared_gate_w", (dm, SFF)),
                        ("three_shared_down_w", (SFF, dm))):
        assert got[name].shape == shape and np.all(got[name] != 0), name


@pytest.mark.parametrize("held", [None, (2, 3)], ids=["whole", "held"])
@pytest.mark.parametrize("shape", [(SLOTS, 1), (1, 16)],
                         ids=["step", "prefill"])
def test_nothing_reads_the_columns_behind_the_model_width(shape, held):
    """The layer's output is DM wide whatever its down matrix is stored
    at, and NONZERO values written into the stored columns behind DM
    change no bit of it: a decode step's [slots, 1, DM] and a prompt's [1,
    16, DM], every expert held and a share of them; the down matrix cut to
    DM by hand gives the same layer."""
    main, scope, (out, _) = _expert_layers(DM, shape[1:], held=held)
    assert tuple(out.shape)[1:] == shape[1:] + (DM,)
    rng = np.random.RandomState(11)
    x = rng.randn(*shape, DM).astype(np.float32)
    with pt.scope_guard(scope):
        for name in ("two_up_w", "two_down_w", "two_router_w"):
            w = np.asarray(scope.find_var(name))
            scope.set_var(name, jnp.asarray(
                np.where(w != 0, rng.randn(*w.shape) / 3, 0), jnp.float32))
        exe = pt.Executor()
        run = lambda: exe.run(main, feed={"x": x}, fetch_list=[out])[0]
        clean = run()
        down = np.asarray(scope.find_var("two_down_w"))
        assert down.shape[-1] == 512 and not np.any(down[..., DM:])
        wide = down.copy()
        wide[..., DM:] = 5.0 * rng.randn(*wide[..., DM:].shape)
        scope.set_var("two_down_w", jnp.asarray(wide))
        dirty = run()
        # the program's own op by hand, its down matrix cut to DM
        op = [o for o in main.global_block.ops
              if o.type == "moe_gated_ffn"][0]
        ins = {k: [jnp.asarray(x) if k == "X" else scope.find_var(n)
                   for n in names] for k, names in op.inputs.items()}
    assert clean.shape == x.shape and np.std(clean) > 0.01
    assert np.array_equal(clean, dirty)
    ins["WDown"] = [ins["WDown"][0][..., :DM]]
    cut = moe_ops.moe_gated_ffn(None, ins, op.attrs)["Out"][0]
    assert np.allclose(clean, cut, atol=2e-5)


#: sha256 of the gated op's jaxpr (`_traced_gated`), every expert held
#: and a share, as the tree before the two-matrix form's down matrix was
#: stored wider traced it (commit 9689a06): what three matrices stored as
#: wide as they are must still trace, to the letter.
_GATED_AS_IT_WAS = {None: "22aa615cc905a4d0", 2: "970ef61e9347e720"}


def _traced_gated(first, rows=40, d=16, f=12, fs=20, e=8):
    held = e if first is None else 3
    shapes = {"X": (rows, d), "RouterW": (d, e), "WGate": (held, d, f),
              "WUp": (held, d, f), "WDown": (held, f, d),
              "SharedGate": (d, fs), "SharedUp": (d, fs),
              "SharedDown": (fs, d)}
    attrs = {"top_k": 3}
    if first is not None:
        attrs["first_expert"] = first
    names = sorted(shapes)

    def fn(*args):
        out = moe_ops.moe_gated_ffn(
            None, {k: [a] for k, a in zip(names, args)}, attrs)
        return out["Out"][0], out["Stats"][0], out["Experts"][0]

    return str(jax.make_jaxpr(fn)(*[
        jax.ShapeDtypeStruct(shapes[k], jnp.float32) for k in names]))


@pytest.mark.parametrize("first", [None, 2], ids=["whole", "held"])
def test_the_gated_form_traces_what_it_traced(first):
    """Where the down matrix is as wide as the rows the cut is the
    identity and leaves NO operation behind: the five gated cells' op
    traces the same jaxpr as before."""
    import hashlib
    text = _traced_gated(first)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _GATED_AS_IT_WAS[first]


def test_the_trainer_refuses_the_block_typed():
    """Serving only: `transformer_lm_loss` says so and builds nothing."""
    with pytest.raises(NotImplementedError, match="served, not trained"):
        with pt.program_guard(pt.Program(), pt.Program()):
            tfm.transformer_lm_loss(
                vocab_size=V, seq_len=16, n_layers=L, d_model=DM,
                n_heads=NH, d_ff=FF, max_len=MAXC, block=block_of())
