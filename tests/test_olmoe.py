"""OLMoE's block (RMSNorm, RoPE, q/k-norm, dropless top-k gated experts,
no bias) through the three builders of `models/transformer.py`, against
the plain reference `benchmark/reference_olmoe.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. Each is written beside its check with what it is far inside of.
"""

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
from paddle_tpu.serving.metrics import MOE_COUNTERS, render_prometheus

from references import by_path

ref = by_path("reference_olmoe")

HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, FF, E = 97, 2, 64, 4, 32, 8
MAXC, BLOCK, POOL, SLOTS = 48, 4, 40, 4
BUCKETS = (8, 16, 32)
EPS, THETA = 1e-5, 10000.0


def block_of(top_k):
    return tfm.BlockSpec(norm="rms_norm", norm_eps=EPS, positions="rope",
                         rope_theta=THETA, qk_norm=True, bias=False,
                         ffn="moe_gated", num_experts=E,
                         experts_per_tok=top_k)


def hyper(top_k, norm_topk_prob=False):
    return ref.Hyper(NH, top_k, EPS, THETA, norm_topk_prob)


PROGRAM_NAME = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale",
                "head": "lm_head_w"}
LAYER_NAME = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
              "q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w", "q_norm": "attn{i}_qnorm_scale",
              "k_norm": "attn{i}_knorm_scale", "router": "moe{i}_router_w",
              "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
              "down": "moe{i}_down_w"}


def reference_weights(get, n_layers=L):
    """The program's weights, by the names the builders give them, in
    the shape the reference documents. `get(name)` -> array."""
    return dict({key: get(name) for key, name in PROGRAM_NAME.items()},
                layers=[{key: get(name.format(i=i))
                         for key, name in LAYER_NAME.items()}
                        for i in range(n_layers)])


def randomise(scope, seed):
    """Seeded weights with gains away from 1 and a router spread wide
    enough that top-k choices are not near ties: a test of the chosen
    SET must not hang on the last bit of a softmax."""
    rng = np.random.RandomState(seed)
    for name in list(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif "router" in name:
            new = rng.randn(*v.shape)
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def forward_program(top_k, seq_len):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block_of(top_k))
    chosen = [op.output("Experts")[0] for op in main.global_block.ops
              if op.type == "moe_gated_ffn"]
    return main, startup, logits, chosen


# ---------------------------------------------------------------------------
# forward: logits and the chosen experts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("top_k,seq_len", [(2, 24), (8, 24), (2, 3)])
def test_forward_matches_reference(top_k, seq_len):
    """Top-8 of 8 makes every gate count; 3 tokens at top-2 leave most
    experts without a row."""
    main, startup, logits, chosen = forward_program(top_k, seq_len)
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
        want = np.asarray(ref.logits(weights, ids[b], hyper(top_k)))
        # 2e-5 of the logits' spread: two float32 evaluation orders of
        # the same sums (measured 3.5e-6 at most). A router whose
        # weights were rounded to bf16 moves logits by 4e-2 of it, a
        # renormalised top-k by 0.22 (next test), the weakest single
        # dropped pair by 3.9e-4 (test_batch_invariance).
        assert np.max(np.abs(got[0][b] - want)) <= 2e-5 * np.std(want)
        want_sets = np.asarray(ref.chosen_experts(weights, ids[b],
                                                  hyper(top_k)))
        for layer in range(L):
            assert np.array_equal(np.sort(got[1 + layer][b], -1),
                                  np.sort(want_sets[layer], -1))


def test_renormalised_gates_are_another_model():
    """What the tolerance above is far inside of: the reference told to
    renormalise the chosen gates (`norm_topk_prob`: Mixtral's rule, not
    OLMoE's, and the program has no such switch) is 0.22 of the spread
    away from the program on the same weights, ten thousand times it."""
    seq_len = 24
    main, startup, logits, _ = forward_program(2, seq_len)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 3)
        ids = np.random.RandomState(4).randint(0, V, (1, seq_len))
        got, = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])
        weights = reference_weights(scope.find_var)
    plain = np.asarray(ref.logits(weights, ids[0], hyper(2)))
    renorm = np.asarray(ref.logits(weights, ids[0], hyper(2, True)))
    assert np.max(np.abs(got[0] - renorm)) > 2e-2 * np.std(renorm)
    assert np.max(np.abs(got[0] - plain)) <= 2e-5 * np.std(plain)


# ---------------------------------------------------------------------------
# the expert op alone: both forms, the work bound, the counters
# ---------------------------------------------------------------------------

def _op_inputs(rng, n, d=16, h=8, e=E):
    f32 = jnp.float32
    return {"X": [jnp.asarray(rng.randn(n, d), f32)],
            "RouterW": [jnp.asarray(rng.randn(d, e), f32)],
            "WGate": [jnp.asarray(rng.randn(e, d, h) * .3, f32)],
            "WUp": [jnp.asarray(rng.randn(e, d, h) * .3, f32)],
            "WDown": [jnp.asarray(rng.randn(e, h, d) * .3, f32)]}


def _op_reference(ins, top_k):
    layer = {"router": ins["RouterW"][0], "gate": ins["WGate"][0],
             "up": ins["WUp"][0], "down": ins["WDown"][0]}
    with jax.default_matmul_precision("highest"):
        _, w, _ = ref._route(ins["X"][0], layer, ref.Hyper(1, top_k))
        return np.asarray(ref._experts(ins["X"][0], layer, w))


@pytest.mark.parametrize("n,top_k", [(1, 2), (4, 2), (9, 2), (64, 2),
                                     (2, 8), (16, 8)])
def test_expert_op_matches_reference(n, top_k):
    """From one row (most experts get none) to every expert busy."""
    ins = _op_inputs(np.random.RandomState(n), n)
    out = require_op("moe_gated_ffn").compute(None, ins, {"top_k": top_k})
    want = _op_reference(ins, top_k)
    # float32 sums in another order: 1e-6 of the outputs' spread
    assert np.max(np.abs(np.asarray(out["Out"][0]) - want)) \
        <= 5e-6 * np.std(want)
    assert [int(v) for v in out["Stats"][0]][::2] == [n * top_k, 1]


def _dot_flops(jaxpr):
    """FLOPs of every matmul in a jaxpr from its static shapes: 2 per
    multiply-add of dot_general and ragged_dot (rows x contracted x
    columns, whatever the groups)."""
    total = 0
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _dot_flops(sub)
        name = eqn.primitive.name
        if name in ("ragged_dot", "ragged_dot_general"):
            (m, k), (_, _, n) = (v.aval.shape for v in eqn.invars[:2])
            total += 2 * m * k * n
        elif name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            contracted = int(np.prod([lhs[i] for i in lc]))
            batch = int(np.prod([lhs[i] for i in lb]))
            lfree = int(np.prod(lhs)) // (contracted * batch)
            rfree = int(np.prod(rhs)) // (contracted * batch)
            total += 2 * batch * lfree * rfree * contracted
    return total


def test_work_bound_at_published_widths():
    """N = 1,024 rows, 64 experts, top-8, widths 2048 / 1024: the op's
    matmuls execute at most 2 x the 2*N*k*3*D*H FLOPs the algorithm
    needs (capacity C = N one-hot dispatch would be 8 x). Counted from
    the traced op's static shapes, nothing is compiled or run;
    `tests/test_chip_compile.py` reads the same from the TPU compiler's
    own cost analysis."""
    n, e, k, d, h = 1024, 64, 8, 2048, 1024
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    ins = {"X": [sds((n, d), f32)], "RouterW": [sds((d, e), f32)],
           "WGate": [sds((e, d, h), f32)], "WUp": [sds((e, d, h), f32)],
           "WDown": [sds((e, h, d), f32)]}
    op = require_op("moe_gated_ffn")
    jaxpr = jax.make_jaxpr(
        lambda i: op.compute(None, i, {"top_k": k})["Out"][0])(ins)
    needed = 2 * n * k * 3 * d * h
    flops = _dot_flops(jaxpr.jaxpr)
    assert needed <= flops <= 2 * needed, (flops, needed)


def test_inactive_rows_add_nothing_to_the_counters():
    rng = np.random.RandomState(0)
    ins = _op_inputs(rng, 4)
    op = require_op("moe_gated_ffn")
    none = op.compute(None, dict(ins, Active=[jnp.zeros(4, jnp.int32)]),
                      {"top_k": 2})
    assert [int(v) for v in none["Stats"][0]] == [0, 0, 0]
    some = op.compute(None, dict(ins, Active=[jnp.asarray([0, 7, 0, 3])]),
                      {"top_k": 2})
    chosen = np.asarray(some["Experts"][0])
    assert [int(v) for v in some["Stats"][0]] == [
        4, len(set(chosen[1]) | set(chosen[3])), 1]


# ---------------------------------------------------------------------------
# training: loss and every gradient against jax.grad of the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("top_k", [2, 8])
def test_training_step_matches_reference_gradients(top_k):
    seq_len, batch = 12, 3
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=seq_len, n_layers=L, d_model=DM,
            n_heads=NH, d_ff=FF, max_len=seq_len, block=block_of(top_k))
        grads = pt.backward.append_backward(avg)
    rng = np.random.RandomState(5)
    draw = rng.randint(0, V, (batch, seq_len + 1))
    ids, tgt = draw[:, :-1], draw[:, 1:]
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 6)
        # host copies: the executor may donate the scope's arrays
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
                               hyper(top_k))
                   for b in range(batch)) / (batch * seq_len)

    want_loss, want = jax.value_and_grad(mean_loss)(weights)
    # float32 sums in another order (measured 1e-7 relative)
    assert abs(got_loss - float(want_loss)) <= 1e-5 * abs(float(want_loss))

    def check(program_name, want_grad):
        g = np.asarray(got_grads[program_name])
        w = np.asarray(want_grad)
        # per parameter, against the gradient's own largest entry: 2e-5
        # is ten times what float32 accumulation through two layers and
        # a softmax gives (measured 2e-6 at most); a gate that skipped
        # the router's gradient, or a pair dropped in the backward, is
        # of order 1
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-9, \
            program_name

    for key, name in PROGRAM_NAME.items():
        check(name, want[key])
    for i in range(L):
        for key, name in LAYER_NAME.items():
            check(name.format(i=i), want["layers"][i][key])
    assert len(got_grads) == len(PROGRAM_NAME) + L * len(LAYER_NAME)


# ---------------------------------------------------------------------------
# serving: export -> load -> prefill -> paged decode, against the reference
# ---------------------------------------------------------------------------

TOP_K = 2


@pytest.fixture(scope="module")
def olmoe_bundle(tmp_path_factory):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [MAXC], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=L, d_model=DM, n_heads=NH,
                           d_ff=FF, max_len=MAXC, block=block_of(TOP_K))
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, 7)
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var))
        d = str(tmp_path_factory.mktemp("olmoe") / "m")
        pio.export_decode_model(
            d, dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH,
                    d_ff=FF, max_context=MAXC, block=block_of(TOP_K)),
            scope=scope, length_buckets=BUCKETS, slots=SLOTS,
            block_size=BLOCK, pool_blocks=POOL)
    return d, weights


def _ref_logits(weights, ids):
    return np.asarray(ref.logits(weights, ids, hyper(TOP_K)))


def _step_feeds(model):
    return (np.zeros(model.slots, np.int64),
            np.zeros(model.slots, np.int32),
            np.zeros((model.slots, model.max_blocks_per_seq), np.int32))


def test_serving_json_records_the_block(olmoe_bundle):
    import json
    with open(os.path.join(olmoe_bundle[0], "serving.json")) as f:
        dec = json.load(f)["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of(TOP_K)
    assert dec["moe_stats"]["fetch"] == dec["fetches"][-2]["name"]
    assert dec["moe_routes"]["fetch"] == dec["fetches"][-1]["name"]
    assert dec["fetches"][-1]["shape"] == [L, SLOTS, TOP_K]
    assert dec["feeds"][-1]["name"] == dec["moe_stats"]["feed"]
    assert not any("pos_emb" in n or n.endswith("_b")
                   for n in dec["weights"])


def test_prefill_then_paged_decode_matches_reference(olmoe_bundle):
    """A 6-token prompt, then 9 teacher-forced steps: positions 6..14
    cross the block boundaries at 8 and 12 (blocks of 4). Each step's
    row depends on RoPE at the slot's own position and on K stored
    rotated; a busy neighbour slot at another position rides along."""
    d, weights = olmoe_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    ids = rng.randint(0, V, 15)
    other = rng.randint(0, V, 30)
    p_len, o_len = 6, 21
    want = _ref_logits(weights, ids)
    want_other = _ref_logits(weights, other)

    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    model.seed_sequence([1, 2], kv)
    last_o, kv_o = model.prefill([int(t) for t in other[:o_len]])
    model.seed_sequence([11, 12, 13, 14, 15, 16], kv_o)
    tol = 2e-5 * np.std(want)   # float32 order; a wrong position or a
    # stale cache row moves a row by 0.1 of the spread and more
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    assert np.max(np.abs(np.asarray(last_o) - want_other[o_len - 1])) <= tol

    tokens, lens, tables = _step_feeds(model)
    tables[0, :4] = [1, 2, 3, 4]
    tables[2, :8] = [11, 12, 13, 14, 15, 16, 17, 18]
    for j in range(len(ids) - p_len):
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        tokens[2], lens[2] = other[o_len + j], o_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[0] - want[p_len + j])) <= tol, j
        assert np.max(np.abs(rows[2] - want_other[o_len + j])) <= tol, j


def test_a_wrong_position_fails_the_check(olmoe_bundle):
    """What the tolerance is far inside of: the same step with the
    slot's context one token short (RoPE one position early, the newest
    cache row unread) misses by over a thousand times it."""
    d, weights = olmoe_bundle
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


def test_the_server_reports_the_experts_it_chose(olmoe_bundle):
    """`DecodeModel.last_routes` after a prefill and after a step, beside
    a busy neighbour: the reference's own choice, expert for expert and
    in its order (float32 on the CPU leaves no near tie open), so the
    reference forced onto them gives its plain logits and no
    shortfall."""
    d, weights = olmoe_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(8).randint(0, V, 9)
    p_len = 6
    want = np.asarray(ref.chosen_experts(weights, ids, hyper(TOP_K)))
    _, kv = model.prefill([int(t) for t in ids[:p_len]])
    got = [np.asarray(model.last_routes)[:, :p_len]]
    assert model.last_routes.shape == (L, BUCKETS[0], TOP_K)
    model.seed_sequence([1, 2], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[1, :4] = [1, 2, 3, 4]
    tokens[2], lens[2], tables[2, 0] = 3, 1, 9     # the neighbour
    for j in range(len(ids) - p_len):
        tokens[1], lens[1] = ids[p_len + j], p_len + j + 1
        model.decode_step(tokens, lens, tables)
        assert model.last_routes.shape == (L, SLOTS, TOP_K)
        got.append(np.asarray(model.last_routes)[:, 1:2])
    got = np.concatenate(got, axis=1)
    assert np.array_equal(got, want)
    logits, shortfall = ref.logits_on_routes(weights, ids, hyper(TOP_K),
                                             got)
    assert np.array_equal(np.asarray(shortfall), np.zeros((L, len(ids))))
    assert np.array_equal(np.asarray(logits), _ref_logits(weights, ids))


def test_forced_routes_tell_a_near_tie_from_a_fault(olmoe_bundle):
    """The reference forced onto another choice at ONE layer and token:
    an expert swapped for the reference's next one (what a near tie
    does) has a shortfall there alone, over 0, moves
    that token's logits and leaves every earlier token's as they were;
    swapped for the expert with the smallest gate (what a fault does)
    the shortfall is nearly 1."""
    _, weights = olmoe_bundle
    hp = hyper(TOP_K)
    ids = np.random.RandomState(8).randint(0, V, 9)
    own = np.asarray(ref.chosen_experts(weights, ids, hp))
    plain = _ref_logits(weights, ids)
    lay, tok = 0, 5      # the first layer: its ranking of all E experts
    ranked = np.asarray(ref.chosen_experts(      # hangs on no route
        weights, ids, hp._replace(top_k=E)))[lay, tok]
    assert np.array_equal(ranked[:TOP_K], own[lay, tok])
    seen = []
    for other in (ranked[TOP_K], ranked[-1]):
        routes = own.copy()
        routes[lay, tok, 0] = other     # in place of the largest gate
        logits, shortfall = ref.logits_on_routes(weights, ids, hp, routes)
        logits, shortfall = np.asarray(logits), np.array(shortfall)
        seen.append(shortfall[lay, tok])
        shortfall[lay, tok] = 0
        assert not shortfall.any()
        assert np.array_equal(logits[:tok], plain[:tok])
        assert np.max(np.abs(logits[tok] - plain[tok])) \
            > 1e-3 * np.std(plain)
    assert 0 < seen[0] < seen[1] <= 1 and seen[1] > 0.5


def test_batch_invariance(olmoe_bundle):
    """A prompt's last-position logits alone in the smallest bucket,
    padded to a larger one, and its decode row beside other busy slots:
    equal to what another order of float32 sums gives (5e-6 of the
    logits' spread; measured 0 beside neighbours, 8e-7 padded, 1.5e-6
    against the reference), and far inside what ONE dropped (token,
    expert) pair gives: 3.9e-4 for the weakest pair there is, the last
    layer's second choice of the last token (the last check). This is
    the property capacity routing breaks: pad rows and neighbours
    compete for an expert's rows there."""
    d, weights = olmoe_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(9)
    ids = [int(t) for t in rng.randint(0, V, 8)]
    want = _ref_logits(weights, np.asarray(ids))
    tol = 5e-6 * np.std(want)

    alone, kv = model.prefill(ids[:7])              # bucket 8: 1 pad row
    assert kv.bound == 8
    # the same 7 tokens inside the 32 bucket: 25 pad rows
    calls = model._admit_fns[32]
    padded = np.zeros(calls.ids_shape, calls.ids_dtype)
    padded[0, :7] = ids[:7]
    in_32, *_ = calls.prefill(calls.weights, padded, np.int32(7))
    assert np.max(np.abs(np.asarray(alone) - want[6])) <= tol
    assert np.max(np.abs(np.asarray(in_32) - np.asarray(alone))) <= tol

    # the decode row of token 7, alone and beside three busy slots
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

    # one dropped pair: the last layer's weakest chosen expert of the
    # last token, taken out of the reference
    hp = hyper(TOP_K)
    with jax.default_matmul_precision("highest"):
        w32 = jax.tree_util.tree_map(jnp.asarray, weights)
        x = w32["tok_emb"][jnp.asarray(ids)]
        for i, layer in enumerate(w32["layers"]):
            x = x + ref._attention(ref._rms(x, layer["ln1"], EPS), layer,
                                   hp)
            n2 = ref._rms(x, layer["ln2"], EPS)
            chosen, w, _ = ref._route(n2, layer, hp)
            if i == L - 1:
                w = w.at[7, chosen[7, -1]].set(0.0)
            x = x + ref._experts(n2, layer, w)
        dropped = np.asarray(ref._rms(x, w32["ln_f"], EPS) @ w32["head"])
    assert np.max(np.abs(dropped[7] - want[7])) > 50 * tol


def test_moe_counters_through_the_engine(olmoe_bundle):
    """Over a run of the engine: pairs routed = top_k x layers x the
    sum of busy slots over its steps; an idle step adds nothing to any
    of the three; the Prometheus names are there. And the dense bundle
    of `test_decode.py`'s shape exports no such output."""
    d, _ = olmoe_bundle
    engine = ServingEngine()
    engine.load_decode_model("olmoe", d, warmup=True, max_new_tokens=8)
    try:
        dec = engine.decode_engine("olmoe")
        before = dec.metrics_snapshot()
        assert [before[k] for k in MOE_COUNTERS] == [0, 0, 0]  # warm-up
        rng = np.random.RandomState(10)
        handles = [engine.generate(
            "olmoe", [int(t) for t in rng.randint(0, V, n)],
            max_new_tokens=m) for n, m in ((3, 5), (9, 7), (5, 2))]
        for h in handles:
            h.result(timeout=120)
        snap = dec.metrics_snapshot()
        used = dec.metrics.slots_used_sum
        assert snap["decode_steps"] > 0 and used > 0
        assert snap["moe_assignments"] == TOP_K * L * used
        assert snap["moe_layer_steps"] == L * snap["decode_steps"]
        assert (snap["moe_layer_steps"] <= snap["moe_experts_touched"]
                <= min(snap["moe_assignments"],
                       E * snap["moe_layer_steps"]))
        # an all-idle step, as the warm-up makes: nothing moves
        totals = dec.scheduler.while_idle(lambda: (
            dec.model.decode_step(*_step_feeds(dec.model)),
            dec.metrics.moe_probe())[1])
        assert [int(v) for v in np.asarray(totals[1])] == [
            snap[k] for k in MOE_COUNTERS]
        text = render_prometheus(engine.metrics.snapshot())
        for key in MOE_COUNTERS:
            assert f'pt_decode_{key}_total{{model="olmoe"}}' in text
    finally:
        engine.shutdown()


def test_device_tokens_equal_host_argmax_moe(olmoe_bundle,
                                             served_and_watched):
    """This bundle's step returns its ids before the pools and the
    routing counters and routes behind them."""
    model = served_and_watched(olmoe_bundle[0], V, SLOTS)
    assert model.last_routes is not None


def test_counters_are_not_donated_with_the_pools(olmoe_bundle):
    """The step donates the pools and nothing else: the reference
    `DecodeMetrics.on_step` keeps to the device's routing counters
    between snapshots, and the routes of the step before, are still
    readable after later steps have run; a pool held across a step is
    not."""
    from paddle_tpu.serving.metrics import DecodeMetrics
    model = DecodeModel(olmoe_bundle[0], warmup=False)
    metrics = DecodeMetrics("m")
    metrics.moe_probe = model.moe_counters
    _, kv = model.prefill([1, 2, 3])
    model.seed_sequence([1], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[0, :2] = [1, 2]
    tokens[0], lens[0] = 5, 4
    model.decode_step(tokens, lens, tables)
    metrics.on_step(1, model.slots, 0.0, 1)     # holds the counters
    held, routes, pool = metrics._moe_ref[1], model.last_routes, \
        model._pools[0]
    for j in range(2):
        tokens[0], lens[0] = 6 + j, 5 + j
        model.decode_step(tokens, lens, tables)
    assert pool.is_deleted()
    assert not held.is_deleted() and not routes.is_deleted()
    assert [int(v) for v in np.asarray(held)] == [TOP_K * L, held[1], L]
    assert np.asarray(routes).shape == (L, model.slots, TOP_K)
    snap = metrics.snapshot()     # fetches the held reference
    assert (snap["moe_assignments"], snap["moe_layer_steps"]) \
        == (TOP_K * L, L)
    assert model.step_aliased_bytes == sum(p.nbytes for p in model._pools)


def test_counters_fold_before_int32_wraps(olmoe_bundle):
    model = DecodeModel(olmoe_bundle[0], warmup=False)
    model._moe_fold_every = 2
    _, kv = model.prefill([1, 2, 3])
    model.seed_sequence([1], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[0, :2] = [1, 2]
    for j in range(3):
        tokens[0], lens[0] = 5, 4 + j
        model.decode_step(tokens, lens, tables)
    base, device = model.moe_counters()
    assert [int(v) for v in base] == [2 * TOP_K * L, base[1], 2 * L]
    assert [int(v) for v in np.asarray(device)][::2] == [TOP_K * L, L]


def test_dense_bundle_has_no_counters(tmp_path):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tfm.transformer_lm_loss(vocab_size=V, seq_len=16, n_layers=1,
                                d_model=16, n_heads=2, d_ff=32, max_len=16)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = pio.export_decode_model(
            str(tmp_path / "dense"),
            dict(vocab_size=V, n_layers=1, d_model=16, n_heads=2, d_ff=32,
                 max_context=16),
            scope=scope, length_buckets=(8,), slots=2, block_size=4,
            pool_blocks=8)
    import json
    with open(os.path.join(d, "serving.json")) as f:
        dec = json.load(f)["decode"]
    assert "moe_stats" not in dec and "moe_routes" not in dec
    assert [f["name"] for f in dec["fetches"]] == [
        "logits", "k_cache_out_0", "v_cache_out_0"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == tfm.GPT2_BLOCK
    model = DecodeModel(d, warmup=False)
    assert model.moe_counters() is None
    model.prefill([1, 2, 3])
    assert model.last_routes is None
    from paddle_tpu.serving.metrics import DecodeMetrics
    assert not set(MOE_COUNTERS) & set(DecodeMetrics("m").snapshot())


# ---------------------------------------------------------------------------
# the block description and the reference's two copies
# ---------------------------------------------------------------------------

def test_gpt2_block_keeps_its_parameter_names():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tfm.transformer_lm_loss(vocab_size=V, seq_len=8, n_layers=1,
                                d_model=16, n_heads=2, d_ff=32, max_len=8)
    names = {v.name for v in main.list_vars() if v.persistable}
    stems = [f"attn0_{t}" for t in "qkv"] + ["attn0_out", "ffn0_in",
                                             "ffn0_out", "lm_head"]
    assert names == ({"tok_emb", "pos_emb"}
                     | {f"{s}_{x}" for s in stems for x in "wb"}
                     | {f"{n}_{x}" for n in ("ln1_0", "ln2_0", "ln_f")
                        for x in ("scale", "bias")})


@pytest.mark.parametrize("bad", [dict(norm="batch"), dict(positions="alibi"),
                                 dict(ffn="swiglu"),
                                 dict(ffn="moe_gated", num_experts=4,
                                      experts_per_tok=5)])
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        tfm.BlockSpec(**bad)


def test_block_spec_round_trips_through_its_dict():
    blk = block_of(2)
    assert tfm.BlockSpec.of(blk.to_dict()) == blk
    assert tfm.BlockSpec.of(None) is tfm.GPT2_BLOCK
