"""MiniCPM-SALA's block (block-sparse attention over pooled keys beside
linear-attention layers whose state is a matrix a head, muP scalings)
through the builders of `models/transformer.py` and the decode engine,
against the plain reference `benchmark/reference_minicpm_sala.py`, loaded
by path: the reference lives ONCE (ROADMAP D19) and imports nothing of
`paddle_tpu`.

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and no more.
"""

import importlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from references import by_path
from paddle_tpu import io as pio
from paddle_tpu.kernels import block_sparse_attention as bsa
from paddle_tpu.kernels import ssd_update
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.decode.engine import (DecodeEngine,
                                              SequenceStateUnsupported)

attn_ops = importlib.import_module("paddle_tpu.ops.attention_ops")
bs_ops = importlib.import_module("paddle_tpu.ops.block_sparse_ops")
HERE = os.path.dirname(os.path.abspath(__file__))


ref = by_path("reference_minicpm_sala")

V, DM, NH, NKV, HD, FF = 97, 32, 4, 2, 8, 48
PATTERN = ("blocksparse", "linear", "linear", "linear")
L, DEPTH = len(PATTERN), 32
KERNEL, STRIDE, BLOCK, TOPK, WINDOW, INIT, DENSE = 4, 2, 4, 5, 8, 1, 24
MAXC, POOL, SLOTS = 64, 40, 3
BUCKETS = (16, 32, 48)
POOLED = (MAXC - KERNEL) // STRIDE + 1
WIDTH = max(TOPK, -(-(DENSE - 1) // BLOCK))
STATE_BYTES = 4 * (3 * NH * HD * HD + POOLED * NKV * HD)     # a slot's


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=1e-6, positions="none",
                bias=False, attention="gqa", qk_norm=True, n_kv_heads=NKV,
                head_dim=HD, ffn="gated", layer_pattern=PATTERN,
                layer_ids=(0, 1, 2, 3), attn_gate=True,
                sparse_kernel=KERNEL, sparse_stride=STRIDE,
                sparse_block=BLOCK, sparse_topk=TOPK, sparse_window=WINDOW,
                sparse_init=INIT, sparse_dense_len=DENSE,
                linear_positions="rope", decay_layers=DEPTH,
                embed_scale=12.0, residual_scale=1.4 / DEPTH ** 0.5,
                logit_scale=8.0 / DM, ssm_chunk=8)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


HP = ref.Hyper(("sparse", "linear", "linear", "linear"), (0, 1, 2, 3),
               DEPTH, NH, NKV, HD, DM, KERNEL, STRIDE, BLOCK, TOPK, WINDOW,
               INIT, DENSE, dim_model_base=8)

_MIXER = dict(q="q_w", k="k_w", v="v_w", gate="gate_w", out="out_w",
              qnorm="qnorm_scale", knorm="knorm_scale")


def reference_weights(get):
    layers = []
    for i, kind in enumerate(PATTERN):
        w = {k: get(f"attn{i}_{n}") for k, n in _MIXER.items()}
        if kind == "linear":
            w["onorm"] = get(f"attn{i}_onorm_scale")
        w.update(kind=kind, ln1=get(f"ln1_{i}_scale"),
                 ln2=get(f"ln2_{i}_scale"),
                 **{f"ffn_{t}": get(f"ffn{i}_{t}_w")
                    for t in ("gate", "up", "down")})
        layers.append(w)
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def randomise(scope, seed):
    """Seeded weights with gains away from 1 and q, k projections wide
    enough that the pooled scores choose (a flat softmax ties)."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        else:
            new = rng.randn(*v.shape) * (0.7 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.3)
        if name.endswith(("_q_w", "_k_w")):
            new = new * 2.0
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def run_forward(seq_len, block, seed=3):
    main, startup = pt.Program(), pt.Program()
    sels = []
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block, collect_selected=sels)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, seed)
        ids = np.random.RandomState(4).randint(0, V, (2, seq_len))
        got = exe.run(main, feed={"src_ids": ids},
                      fetch_list=[logits] + sels)
        weights = reference_weights(
            lambda n: np.asarray(scope.find_var(n)))
    return ids, got[0], got[1:], weights


def _unpack_blocks(packed, n_blocks):
    """[.., G * words] int32 -> bool [.., G, n_blocks]."""
    words = -(-n_blocks // 32)
    packed = np.asarray(packed)
    packed = packed.reshape(packed.shape[:-1] + (NKV, words))
    return attn_ops.unpack_mask(packed, n_blocks)


# ---------------------------------------------------------------------------
# forward, and what each part is worth
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def forward():
    return run_forward(40, block_of())


@pytest.mark.parametrize("seq_len", [40, 32, 16, 6])
def test_forward_matches_reference(seq_len, forward):
    """40 and 32 rows: over `dense_len`, every row past the 20th prunes;
    16 and 6: dense (6: under a kernel's rows too)."""
    ids, got, sels, weights = forward if seq_len == 40 \
        else run_forward(seq_len, block_of())
    assert got.shape == (2, seq_len, V)
    for b in range(ids.shape[0]):
        want, chosen = ref.logits_and_choices(weights, ids[b], HP)
        want = np.asarray(want)
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)
        mine = _unpack_blocks(sels[0][b], -(-seq_len // BLOCK))
        assert np.array_equal(mine, np.asarray(chosen[0]))
        if seq_len >= DENSE:    # the last row reads TOPK of its blocks
            assert mine[-1].sum(-1).tolist() == [TOPK] * NKV
            assert mine[-1].sum() < NKV * -(-seq_len // BLOCK)


def test_logits_agree_under_forced_choices(forward):
    """The reference on the PROGRAM's blocks: the same logits, and no
    shortfall where the choices are the reference's own."""
    ids, got, sels, weights = forward
    mine = _unpack_blocks(sels[0][0], 10)[None]
    want, short = ref.logits_on_choices(weights, ids[0], HP, mine)
    assert np.max(np.abs(got[0] - np.asarray(want))) \
        <= 2e-5 * np.std(np.asarray(want))
    assert float(np.max(short)) == 0.0
    # a program that chose another free block is told how far it lies
    other = mine.copy()
    row = other[0, -1, 0]
    free = np.flatnonzero(row[INIT:10 - WINDOW // BLOCK]) + INIT
    unread = [b for b in range(INIT, 10 - WINDOW // BLOCK) if not row[b]]
    row[free[0]], row[unread[0]] = False, True
    _, short = ref.logits_on_choices(weights, ids[0], HP, other)
    assert float(np.asarray(short)[0, -1, 0]) > 0.0


FAULTS = [dict(decay_index="held", layer_ids=(4, 9, 16, 17)),
          dict(linear_gate=False), dict(sparse_gate=False), dict(init=0),
          dict(window=WINDOW - BLOCK), dict(group_sum="one"),
          dict(sparse_rotary=True), dict(scale_emb=1.0),
          dict(scale_depth=32 ** 0.5), dict(dim_model_base=DM),
          dict(dtype="bfloat16")]


@pytest.mark.parametrize("wrong", FAULTS, ids=lambda w: "-".join(
    f"{k}_{v}" for k, v in w.items() if k != "layer_ids"))
def test_the_parts_of_the_block_each_count(wrong):
    """What the tolerance above is far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread (the faults `benchmark/tools/minicpm_sala_check_readings.py`
    shows the cell's limits fail). The decay's fault needs layers whose
    published index is not their index in the cut."""
    ids_of = wrong.pop("layer_ids", None) if "layer_ids" in wrong else None
    wrong = dict(wrong)
    hp = HP if ids_of is None else HP._replace(layer_ids=ids_of)
    block = block_of() if ids_of is None else block_of(layer_ids=ids_of)
    ids, got, _, weights = run_forward(40, block)
    right = np.asarray(ref.logits(weights, ids[0], hp))
    assert np.max(np.abs(got[0] - right)) <= 2e-5 * np.std(right)
    off = np.asarray(ref.logits(weights, ids[0], hp._replace(**wrong)))
    assert np.max(np.abs(off - right)) > 2e-3 * np.std(right)


# ---------------------------------------------------------------------------
# the linear layer's three forms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [1, 9, 31])
def test_the_linear_recurrence_three_ways(layer):
    """The reference's O(n^2) sum, `_ssd_chunks` (in three chunks, and
    in two calls with the state handed on) and `ssd_decode_update` a row
    at a time, with the published-index decay."""
    rng = np.random.RandomState(layer)
    seq, heads, d = 24, 4, 8
    q, k, v = (jnp.asarray(rng.randn(1, seq, heads, d), jnp.float32)
               for _ in range(3))
    a = bs_ops.linear_decay_log(heads, layer, DEPTH)
    hp = HP._replace(layer_ids=(layer,) * 4)
    assert np.allclose(np.exp(a), ref.decay(hp, 0), rtol=1e-6)
    gap = np.arange(seq)[:, None] - np.arange(seq)[None]
    weight = np.where(gap >= 0, np.exp(np.asarray(a)[:, None, None]
                                       * np.maximum(gap, 0)), 0.0)
    want = np.einsum("hts,shd->thd", np.einsum(
        "thd,shd->hts", q[0], k[0]) * weight, v[0])
    dt = jnp.ones((1, seq, heads), jnp.float32)
    y, last = attn_ops._ssd_chunks(dt, v, k, q, a, 8)
    assert np.allclose(y[0], want, atol=2e-5)
    y1, mid = attn_ops._ssd_chunks(dt[:, :16], v[:, :16], k[:, :16],
                                   q[:, :16], a, 8)
    y2, end = attn_ops._ssd_chunks(dt[:, 16:], v[:, 16:], k[:, 16:],
                                   q[:, 16:], a, 8, mid)
    assert np.allclose(np.concatenate([y1, y2], 1)[0], want, atol=2e-5)
    assert np.allclose(end, last, atol=2e-5)
    state = jnp.zeros((1, heads, d, d), jnp.float32)
    for t in range(seq):
        out, state = ssd_update.ssd_decode_update(
            state, v[:, t], dt[:, t], a, k[:, t], q[:, t],
            jnp.ones((1,), bool))
        assert np.allclose(out[0], want[t], atol=2e-5), t
    assert np.allclose(state, last, atol=2e-5)


# ---------------------------------------------------------------------------
# the block-sparse kernel, and the pieces of the selection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_heads,width", [(2, 8), (1, 5)])
def test_block_sparse_kernel_matches_its_reference(kv_heads, width):
    """The Pallas kernel (interpret mode) at 16 query heads a K/V head
    over pages of 64 rows of 128: chosen pages in any order of the pool,
    the last one partly filled, an empty slot."""
    rng = np.random.RandomState(width)
    slots, per, d, bs, nb = 3, 16, 128, 64, 12
    q = jnp.asarray(rng.randn(slots, kv_heads * per, d), jnp.float32)
    k_pool = jnp.asarray(rng.randn(nb, bs, kv_heads * d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, bs, kv_heads * d), jnp.float32)
    pages = jnp.asarray(rng.randint(1, nb, (slots, kv_heads, width)),
                        jnp.int32)
    rows = jnp.asarray([[width * bs - 11] * kv_heads,
                        [0] * kv_heads,
                        [bs + 1] + [3 * bs] * (kv_heads - 1)], jnp.int32)
    want = bsa.block_sparse_attention_reference(q, k_pool, v_pool, pages,
                                                rows)
    got = bsa.block_sparse_paged_attention(q, k_pool, v_pool, pages, rows,
                                           interpret=True)
    assert np.allclose(got, want, atol=2e-4)
    assert np.all(np.asarray(got)[1] == 0.0)
    # the reference against plain softmax attention over the named rows
    s, g = 0, kv_heads - 1
    n = int(rows[s, g])
    kk = np.concatenate([np.asarray(k_pool)[p, :, g * d:(g + 1) * d]
                         for p in np.asarray(pages)[s, g]])[:n]
    vv = np.concatenate([np.asarray(v_pool)[p, :, g * d:(g + 1) * d]
                         for p in np.asarray(pages)[s, g]])[:n]
    qq = np.asarray(q)[s, g * per:(g + 1) * per]
    sc = qq @ kk.T / np.sqrt(d)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    assert np.allclose(np.asarray(want)[s, g * per:(g + 1) * per],
                       (p / p.sum(-1, keepdims=True)) @ vv, atol=2e-4)


def test_compact_blocks_keeps_the_order():
    mask = np.zeros((2, 3, 11), bool)
    mask[0, 0, [0, 4, 9]] = True
    mask[1, 2, [10]] = True
    blocks, count = bs_ops.compact_blocks(jnp.asarray(mask), 4)
    assert np.asarray(blocks)[0, 0].tolist() == [0, 4, 9, -1]
    assert np.asarray(blocks)[1, 2].tolist() == [10, -1, -1, -1]
    assert np.asarray(count).tolist() == [[3, 0, 0], [0, 0, 1]]


def test_block_scores_and_choice_match_the_reference():
    rng = np.random.RandomState(0)
    seq = 40
    q = jnp.asarray(rng.randn(seq, NH, HD) * 2, jnp.float32)
    k = jnp.asarray(rng.randn(seq, NKV, HD), jnp.float32)
    pooled = ref.pooled_keys(k, HP)
    assert np.allclose(bs_ops._pool_keys(k[None], KERNEL, STRIDE)[0],
                       pooled, atol=1e-6)
    rows = jnp.arange(seq)
    want = ref.block_scores(q, pooled, rows, HP, 10)
    sizes = (KERNEL, STRIDE, BLOCK, TOPK, WINDOW // BLOCK, INIT, DENSE)
    seen = (jnp.arange(pooled.shape[0]) * STRIDE + KERNEL)[None] \
        <= rows[:, None] + 1
    got = bs_ops._block_scores(
        q.reshape(1, seq, NKV, NH // NKV, HD), pooled[None], seen[None],
        sizes, 10)[0]
    assert np.allclose(got, want, atol=1e-6)
    calls = jnp.full((seq,), seq)
    mine = bs_ops._choose_blocks(got, (rows // BLOCK)[None],
                                 jnp.zeros((1, 1), bool), sizes)
    assert np.array_equal(mine, ref.choose(want, rows, calls, HP))


# ---------------------------------------------------------------------------
# the bundle: prefill through a bucket, then decode through the caches
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
    return _export(str(tmp_path_factory.mktemp("sala") / "m"), block_of())


def test_serving_json_declares_pools_a_state_and_pooled_keys(bundle):
    with open(os.path.join(bundle[0], "serving.json")) as f:
        dec = json.load(f)["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    row = 4 * 2 * NKV * HD
    assert dec["cache"] == {
        "kind": "kv_blocks", "rows": [[NKV * HD], [NKV * HD]],
        "row_floats": 2 * NKV * HD, "bytes_per_token": row,
        "layer_kinds": ["full", "state", "state", "state"],
        "kinds": {"full": {"layers": 1, "pool_blocks": POOL,
                           "blocks_per_seq": MAXC // BLOCK,
                           "bytes_per_token": row},
                  "state": {"layers": 3, "rows": [[NH, HD, HD]],
                            "bytes_per_slot": 4 * 3 * NH * HD * HD}},
        "pooled": {"layers": 1, "rows": [[POOLED, NKV * HD]],
                   "bytes_per_slot": 4 * POOLED * NKV * HD}}
    feeds = [(m["name"], m["shape"]) for m in dec["feeds"]]
    assert feeds == [
        ("token_ids", [SLOTS]), ("context_lens", [SLOTS]),
        ("block_tables", [SLOTS, MAXC // BLOCK]),
        ("k_cache_0", [POOL, BLOCK, NKV * HD]),
        ("v_cache_0", [POOL, BLOCK, NKV * HD]),
        ("pooled_keys_0", [SLOTS, POOLED, NKV * HD]),
        *[(f"ssm_state_{i}", [SLOTS, NH, HD, HD]) for i in (1, 2, 3)]]
    assert dec["selections"]["blocks"] == dict(
        block_of().sparse_sizes, layers=[0])
    assert dec["selections"]["prefill"] == ["selected_0"]
    assert dec["fetches"][-1] == {"name": "selected_out", "dtype": "int32",
                                  "shape": [1, SLOTS, NKV, WIDTH]}


@pytest.mark.parametrize("p_len,former,steps", [
    (17, 0, 14),     # a prompt under dense_len that decodes past it
    (30, 5, 12),     # a prompt over it, the slot used before
    (40, 13, 10),    # the largest bucket, not at its end
    (3, 7, 8)])      # shorter than a kernel: the steps pool the first
def test_prefill_then_decode_through_the_served_bundle(bundle, p_len,
                                                       former, steps):
    """Logits after the prefill and after each teacher-forced step,
    through the pools, the pooled keys and the states, against the
    reference's full forward (its rows' calls as the program made them:
    the prompt one call, then a call a step); the step's chosen blocks
    are the reference's own."""
    d, weights = bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(p_len).randint(0, V, p_len + steps)
    total, slot = len(ids), 1
    want, chosen = ref.logits_and_choices(weights, ids, HP,
                                          prompt_len=p_len)
    want, chosen = np.asarray(want), np.asarray(chosen)
    tol = 2e-5 * np.std(want)
    blocks = list(range(3, 3 + -(-total // BLOCK)))[::-1]   # any order
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
    nb = -(-p_len // BLOCK)
    mine = _unpack_blocks(np.asarray(model.last_selections[0]),
                          -(-kv.bound // BLOCK))[:p_len, :, :nb]
    assert np.array_equal(mine, chosen[0, :p_len, :, :nb])
    model.seed_sequence(blocks[:-(-p_len // BLOCK)], kv, slot=slot)
    pruned = 0
    for j in range(total - p_len):
        tokens[slot], lens[slot] = ids[p_len + j], p_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[slot] - want[p_len + j])) <= tol, j
        picked = np.asarray(model.last_selections)[0, slot]    # [G, W]
        for g in range(NKV):
            own = np.flatnonzero(chosen[0, p_len + j, g])
            assert picked[g][picked[g] >= 0].tolist() == own.tolist()
            pruned += len(own) < -(-(p_len + j + 1) // BLOCK)
    assert (pruned > 0) == (total > DENSE)
    # pools, pooled keys and states are all updated in place, every byte
    assert model.step_aliased_bytes == sum(
        4 * int(np.prod(s)) for s in model._pool_shapes) \
        > model.state_bytes == SLOTS * STATE_BYTES
    assert (model.state_layers, model.full_layers) == (3, 1)


# ---------------------------------------------------------------------------
# the engine: pooled keys and states through everything a slot goes through
# ---------------------------------------------------------------------------

def _greedy(weights, prompt, out):
    ids = np.asarray(prompt + out)
    want = np.asarray(ref.logits(weights, ids, HP, prompt_len=len(prompt)))
    return list(np.argmax(want[len(prompt) - 1:-1], -1))


def _poison(dec):
    """Every pool, pooled key and state full of what no sequence wrote."""
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
    for n in (40, 3, 26, 1):        # one at a time: slot 0 every time
        _served(dec, weights, _prompts(n, [n]), 12)
    snap = dec.metrics_snapshot()
    assert snap["state_seeds"] == snap["prefills"] == 4
    return dec


def _case_a_preemption_and_resume(d, weights, tmp):
    """A pool too small for three sequences: one is preempted and
    resumes by a prefill of prompt + generated (ONE call of that length:
    what the greedy reference below is told too, through the tokens it
    is given), which rebuilds its pooled keys and its states in whatever
    slot it then gets."""
    d, weights = _export(str(tmp / "m"), block_of(), pool_blocks=17)
    dec = DecodeEngine(d, max_new_tokens=8, warmup=False)
    _poison(dec)
    prompts = _prompts(11, [26, 25, 27])
    handles = [dec.generate(p, max_new_tokens=8) for p in prompts]
    results = [h.result(timeout=300) for h in handles]
    assert sum(r["evictions"] for r in results) > 0
    for prompt, r in zip(prompts, results):
        # every call was over dense_len, so a resume's one long call
        # chooses as the calls it replaces did
        assert r["tokens"] == _greedy(weights, prompt, r["tokens"])
    snap = dec.metrics_snapshot()
    assert snap["evictions"] > 0 and snap["resumes"] > 0
    assert snap["state_seeds"] == snap["prefills"] > 3
    return dec


def _case_a_dispatch_ahead_drain(d, weights, tmp):
    """Three times the slots: a freed slot's next owner starts from ITS
    pooled keys, its states and its blocks."""
    dec = DecodeEngine(d, max_new_tokens=13, warmup=False)
    _poison(dec)
    lengths = [5, 33, 9, 2, 40, 27, 1, 21, 30]
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
def test_the_pooled_keys_and_the_state_are_the_sequences_own(
        bundle, tmp_path, case):
    """Every output is the reference's greedy continuation (the
    reference has no cache, no pooled-key pool and no state), whatever
    the slot and the blocks held before; every block comes back; the
    counters count what the step read."""
    d, weights = bundle
    dec = _CASES[case](d, weights, tmp_path)
    snap = dec.metrics_snapshot()
    assert dec.pool.blocks_in_use == 0
    live = snap["slots_used_sum"] + snap["overrun_tokens"]
    assert snap["state_slot_steps"] == 3 * live
    assert snap["state_seed_bytes"] == STATE_BYTES * snap["state_seeds"]
    assert snap["state_bytes"] == SLOTS * STATE_BYTES
    assert 0 < snap["sparse_selected_rows"] < snap["sparse_live_rows"]
    assert snap["block_chosen_blocks"] > 0
    assert snap["block_pooled_rows"] > 0
    # (the preempted prompts are all over `dense_len`)
    assert (snap["block_dense_slot_steps"] > 0) \
        == (case != "a_preemption_and_resume")
    dec.shutdown()


def test_the_counters_follow_the_rule_the_op_applies(bundle):
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    seen = {}
    model.count_sparse_rows = lambda live, read, *_: seen.update(
        live=live, read=read)
    model.count_block_choices = lambda blocks, pooled, dense: seen.update(
        blocks=blocks, pooled=pooled, dense=dense)
    lens = np.asarray([0, 23, 41], np.int64)
    model._count_blocks(lens)
    # 23: dense, its 6 blocks whole; 41: TOPK blocks, the last of 1 row
    assert seen == dict(live=64, read=23 + (TOPK - 1) * BLOCK + 1,
                        blocks=6 + TOPK,
                        pooled=(41 - KERNEL) // STRIDE + 1, dense=1)


def test_prefix_sharing_and_speculation_are_refused_at_load(bundle):
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="kv_share"):
        DecodeEngine(model=model, kv_share=True, warmup=False)
    with pytest.raises(SequenceStateUnsupported, match="speculation"):
        DecodeEngine(model=model, drafter="ngram", spec_k=2, warmup=False)
    dec = DecodeEngine(model=model, warmup=False)
    said = dec.describe()
    assert said["refuses"] == ["kv_share", "speculation"]
    assert said["block_sparse_kernel"]["heads_per_product"] == NH // NKV
    assert said["block_sparse_kernel"]["selected_pages"] == WIDTH
    dec.shutdown()


def test_the_mixers_are_named_in_the_compiled_programs(bundle):
    """What a profile tells apart: the pooling, the block scores, the
    choice and the linear layers, in the step."""
    d, _ = bundle
    model = DecodeModel(d, warmup=False)
    model.decode_step(np.zeros(SLOTS, np.int64), np.zeros(SLOTS, np.int32),
                      np.zeros((SLOTS, MAXC // BLOCK), np.int32)).tokens
    text = model._step.as_text()
    for scope in ("block_pool_keys", "block_scores", "block_select",
                  "linear_attention"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# what the block cannot be, and what the others say
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wrong,match", [
    (dict(sparse_kernel=0), "sparse_kernel"),
    (dict(sparse_kernel=5), "whole sparse_strides"),
    (dict(sparse_window=6), "whole blocks"),
    (dict(sparse_topk=2), "hold the first and the local"),
    (dict(decay_layers=0), "decay_layers"),
    (dict(linear_positions="learned"), "linear_positions"),
    (dict(qk_norm=False), "q/k-norm"),
    (dict(residual_scale=0.0), "positive"),
    (dict(layer_pattern=("full",), sparse_dense_len=0, decay_layers=0,
          linear_positions="", ssm_chunk=0, attn_gate=False,
          positions="rope"), "sparse_\\* sizes come with"),
    (dict(layer_pattern=("blocksparse",), decay_layers=0,
          linear_positions="", ssm_chunk=0), "carry the order"),
    (dict(positions="learned"), "rotary positions")])
def test_what_the_block_cannot_be_is_refused(wrong, match):
    with pytest.raises(ValueError, match=match):
        block_of(**wrong)


def test_training_is_refused_typed():
    with pytest.raises(NotImplementedError, match="served, not trained"):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            tfm.transformer_lm_loss(
                vocab_size=V, seq_len=16, n_layers=L, d_model=DM,
                n_heads=NH, d_ff=FF, max_len=MAXC, block=block_of())


def test_the_page_has_to_be_the_selections_block(tmp_path):
    with pytest.raises(ValueError, match="not the selection's block"):
        pio.export_decode_model(
            str(tmp_path / "m"), export_cfg(block_of()), scope=pt.Scope(),
            length_buckets=BUCKETS, slots=SLOTS, block_size=2 * BLOCK,
            pool_blocks=POOL)


def test_a_layer_has_positions_of_its_kind():
    block = block_of()
    kinds = [block.layer(i, FF) for i in range(L)]
    assert [(k.mixer, k.positions, k.cache, k.ffn) for k in kinds] == [
        ("blocksparse", "none", "full", "gated")] + [
        ("linear", "rope", "state", "gated")] * 3
    said = block.to_dict()
    assert said["sparse_topk"] == TOPK and said["embed_scale"] == 12.0
    assert tfm.BlockSpec.of(said) == block
    assert block.sparse_sizes["window"] == WINDOW // BLOCK
    # the blocks that were there say nothing of this one's fields: a
    # field at its default is not said, the base ones apart
    assert not _MINE & set(tfm.GPT2_BLOCK.to_dict())


#: the fields that came with this model's layers
_MINE = {"attn_gate", "sparse_kernel", "sparse_stride", "sparse_block",
         "sparse_topk", "sparse_window", "sparse_init", "sparse_dense_len",
         "linear_positions", "decay_layers", "embed_scale",
         "residual_scale", "logit_scale", "row_chunk"}


_CONFIGS = sorted(
    f for f in os.listdir(os.path.join(HERE, "..", "benchmark", "configs"))
    if f.endswith("-serve.json") and "minicpm" not in f
    and "cerebras" not in f
    # added since: it shares the three scalings (tests/test_granite4.py
    # holds ALL the configurations that were there to what they built)
    and "granite" not in f
    # and this one takes `row_chunk` for its dense layer's 12 k rows
    # (tests/test_built_programs.py holds all that were there)
    and "glm-5" not in f)


@pytest.mark.parametrize("name", _CONFIGS)
def test_the_other_bundles_blocks_say_what_they_said(name):
    """The serve configurations that were there: their `BlockSpec`, as
    the mapping of each builds it, records none of this PR's fields."""
    sys.path.insert(0, os.path.join(HERE, "..", "benchmark"))
    try:
        with open(os.path.join(HERE, "..", "benchmark", "configs",
                               name)) as f:
            config = json.load(f)
        mapping = importlib.import_module(
            "kinds." + config["harness"]["mapping"])
        said = tfm.BlockSpec.of(mapping.sizes(config)["block"]).to_dict()
    finally:
        sys.path.pop(0)
    assert not _MINE & set(said)
    assert tfm.BlockSpec.of(said).to_dict() == said


def test_the_ffn_takes_a_long_buckets_rows_in_chunks():
    """`row_chunk`: the same weights, the same result, the rows a chunk
    at a time."""
    ids, got, _, weights = run_forward(40, block_of(row_chunk=8))
    want = np.asarray(ref.logits(weights, ids[0], HP))
    assert np.max(np.abs(got[0] - want)) <= 2e-5 * np.std(want)
