"""GLM-5's block (latent attention with a query low-rank, a sparse-attention
indexer that reads that low-rank and chooses rows of the LATENT cache, an
index-key pool beside the latent one, sigmoid-routed experts with a
selection bias, a routed scale and a shared expert, of which the program
holds a share) through the builders of `models/transformer.py`, against
the plain reference `benchmark/reference_glm5.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. `index_topk` is 8, so a 24-token forward and a decode past position
8 both PRUNE.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import io as pio
from paddle_tpu.models import transformer as tfm
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.decode import DecodeModel
from paddle_tpu.serving.metrics import render_prometheus

from references import by_path

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.ops import attention_ops

ref = by_path("reference_glm5")
kanana = by_path("reference_kanana")
HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, FF, E, TOP_K = 97, 3, 64, 4, 16, 8, 3
QR, RANK, NOPE, ROPE, VD = 24, 16, 8, 8, 12
IH, ID, TOPK = 4, 16, 8          # the indexer: heads, width, rows kept
DENSE_W, FIRST, HELD = 48, 2, 4  # one leading dense layer; experts 2..5
MAXC, BLOCK, POOL, SLOTS = 48, 4, 40, 4
BUCKETS = (8, 16, 32)
EPS, THETA, SCALE = 1e-5, 1000000.0, 2.5
ROW, IROW = 128, 128     # the pools' rows: 24 and 16 floats in a lane tile


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, bias=False, attention="latent",
                kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
                qk_rope_head_dim=ROPE, v_head_dim=VD, rope_interleave=True,
                q_lora_rank=QR, index_heads=IH, index_head_dim=ID,
                index_topk=TOPK, index_rope_dim=ROPE,
                index_rope_interleave=True, ffn="moe_gated", num_experts=E,
                experts_per_tok=TOP_K, router="sigmoid_bias",
                norm_topk=True, routed_scale=SCALE, shared_width=FF,
                dense_layers=1, dense_width=DENSE_W, experts_first=FIRST,
                experts_held=HELD)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


def unindexed(**changes):
    return block_of(index_heads=0, index_head_dim=0, index_topk=0,
                    index_rope_dim=0, index_rope_interleave=False,
                    **changes)


HP = ref.Hyper(NH, TOP_K, QR, RANK, NOPE, ROPE, VD, IH, ID, TOPK, FIRST,
               EPS, THETA, SCALE)

PROGRAM_NAME = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale",
                "head": "lm_head_w"}
sys.path.insert(0, os.path.join(HERE, "..", "benchmark"))
from kinds import _model_glm5 as mapping    # noqa: E402
sys.path.pop(0)


def reference_weights(get, n_layers=L):
    return mapping.reference_weights(get, n_layers)


def randomise(scope, seed):
    """Seeded weights with gains away from 1, the LayerNorm's bias away
    from 0, a router spread wide enough that top-k choices are not near
    ties, and a selection bias large enough to change choices."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("iknorm_bias"):
            new = 0.2 * rng.randn(*v.shape)
        elif name.endswith("router_bias"):
            new = 0.3 * rng.randn(*v.shape)
        elif "router" in name:
            new = rng.randn(*v.shape)
        else:
            new = rng.randn(*v.shape) * (0.5 / np.sqrt(v.shape[-2])
                                         if v.ndim > 1 else 0.5)
        scope.set_var(name, jnp.asarray(new, jnp.float32))


def forward_program(seq_len, block=None, n_layers=L, **kw):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [seq_len], dtype="int64")
        logits = tfm.transformer_lm(
            src, V, n_layers=n_layers, d_model=DM, n_heads=NH, d_ff=FF,
            max_len=MAXC, block=block or block_of(), **kw)
    return main, startup, logits


def run_forward(seq_len, block, seed=3, change=None, weights_of=None):
    main, startup, logits = forward_program(seq_len, block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, seed)
        if change:
            change(scope)
        ids = np.random.RandomState(4).randint(0, V, (2, seq_len))
        got = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])[0]
        weights = (weights_of or reference_weights)(
            lambda n: None if scope.find_var(n) is None
            else np.asarray(scope.find_var(n)))
    return ids, got, weights


def close(got, want, tol=2e-5):
    return np.max(np.abs(got - want)) <= tol * np.std(want)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [24, 8, 3])
def test_forward_matches_reference(seq_len):
    """24 tokens: rows 8.. keep 8 of up to 24 positions; 8 and 3: every
    row keeps all it may read, the path that never selects."""
    ids, got, weights = run_forward(seq_len, block_of())
    for b in range(ids.shape[0]):
        assert close(got[b], np.asarray(ref.logits(weights, ids[b], HP)))


@pytest.mark.parametrize("seq_len,block,group_bytes", [
    (24, 8, 1 << 30), (40, 8, 40 * 2 * 16 * 4), (32, 16, 32 * 16 * 4)])
def test_forward_in_tiles_matches_reference(monkeypatch, seq_len, block,
                                            group_bytes):
    """The op's on-chip form, held to here with the kernel interpreted:
    the loop chooses, each chunk's selection leaves it as a mask of a
    byte a (row, key), and the heads go a GROUP at a time (all four, two,
    one) through the flash forward over that selection's tiles, each
    group's q projected and its K and V expanded inside the loop."""
    calls = []

    def tiled(q, k, v, **kw):
        assert kw.pop("block") is None      # heads of 16: the default tile
        calls.append((q.shape, k.shape, v.shape, kw["selected"].shape,
                      str(kw["selected"].dtype)))
        return fa.flash_attention(q, k, v, block_q=block, block_k=block,
                                  interpret=True, **kw)

    monkeypatch.setattr(fa, "attention_form",
                        lambda *shape: "flash_selected")
    monkeypatch.setattr(fa, "dot_product_attention", tiled)
    monkeypatch.setattr(attention_ops, "_Q_CHUNK_BYTES", group_bytes)
    ids, got, weights = run_forward(seq_len, block_of())
    group = max(g for g in (1, 2, 4)
                if seq_len * g * (NOPE + ROPE) * 4 <= group_bytes)
    assert calls == [((2, seq_len, group, NOPE + ROPE),
                      (2, seq_len, group, NOPE + ROPE),
                      (2, seq_len, group, VD),
                      (2, seq_len, seq_len), "int8")] * L
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert close(got[b], want, 2e-4)
    wrong = np.asarray(ref.logits(weights, ids[0],
                                  HP._replace(select="newest")))
    assert np.max(np.abs(got[0] - wrong)) > 0.05 * np.std(wrong)


def test_the_parts_each_count():
    """What the tolerances above are far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread."""
    ids, got, weights = run_forward(24, block_of())
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(hp=HP, **layer_changes):
        w = dict(weights, layers=[dict(lay, **{
            k: v for k, v in layer_changes.items() if k in lay})
            for lay in weights["layers"]])
        return float(np.max(np.abs(
            np.asarray(ref.logits(w, ids[0], hp)) - want)) / np.std(want))

    assert off_by() == 0.0
    assert off_by(HP._replace(select="all")) > 0.05
    assert off_by(HP._replace(select="newest")) > 0.05
    assert off_by(HP._replace(index_from="h")) > 0.02
    assert off_by(HP._replace(index_turn=ID)) > 0.02
    assert off_by(HP._replace(q_norm=False)) > 0.05
    assert off_by(HP._replace(routed_scale=1.0)) > 0.05
    assert off_by(HP._replace(first=FIRST + 1)) > 0.05
    assert off_by(HP._replace(theta=10000.0)) > 0.02
    assert off_by(ik_bias=np.zeros(ID, np.float32)) > 0.005
    assert off_by(router_bias=np.zeros(E, np.float32)) > 0.05


@pytest.mark.parametrize("turned,interleave,hp", [
    (ID, True, HP._replace(index_turn=ID)),   # the whole width, in pairs
    (ROPE, True, HP)])
def test_the_indexers_rotated_width(turned, interleave, hp):
    """`index_rope_dim`: the leading part of qI and kI that rotates, in
    pairs (2i, 2i + 1); the reference rotated over another width is
    another model."""
    block = block_of(index_rope_dim=turned, index_rope_interleave=interleave)
    ids, got, weights = run_forward(24, block)
    assert close(got[0], np.asarray(ref.logits(weights, ids[0], hp)))
    other = HP if hp is not HP else HP._replace(index_turn=ID)
    wrong = np.asarray(ref.logits(weights, ids[0], other))
    assert np.max(np.abs(got[0] - wrong)) > 0.02 * np.std(wrong)


def _weights_without(get, drop, **more):
    """The reference's dict from a program that lacks the roles `drop`
    of `_model_glm5._ATTENTION`, with `more` set into every layer."""
    names = {k: n for k, n in mapping._ATTENTION.items() if k not in drop}
    layers = []
    for i in range(L):
        ffn = mapping._EXPERTS if get(f"moe{i}_router_w") is not None \
            else mapping._DENSE
        layers.append(dict({k: get(n.format(i=i))
                            for k, n in dict(names, **ffn).items()},
                           **more))
    return dict({k: get(n) for k, n in PROGRAM_NAME.items()}, layers=layers)


_INDEX_ROLES = ("iq", "ik", "iw", "ik_norm", "ik_bias")


def test_a_query_low_rank_without_an_indexer_reads_every_row():
    """`q_lora_rank` on, the indexer off: the reference with its
    selection ignored (its indexer's weights anything)."""
    main, _, _ = forward_program(24, unindexed())
    names = {v.name for v in main.list_vars() if v.persistable}
    assert "attn0_qa_w" in names and "attn0_qnorm_scale" in names
    assert "attn0_q_w" not in names and "attn0_iq_w" not in names
    any_indexer = dict(iq=np.zeros((QR, IH * ID), np.float32),
                       ik=np.zeros((DM, ID), np.float32),
                       iw=np.zeros((DM, IH), np.float32),
                       ik_norm=np.ones(ID, np.float32),
                       ik_bias=np.zeros(ID, np.float32))
    ids, got, weights = run_forward(
        24, unindexed(), weights_of=lambda get: _weights_without(
            get, _INDEX_ROLES, **any_indexer))
    want = np.asarray(ref.logits(weights, ids[0],
                                 HP._replace(select="all")))
    assert close(got[0], want)
    pruned = np.asarray(ref.logits(weights, ids[0], HP))
    assert not close(got[0], pruned, 1e-3)


def test_without_a_query_low_rank_it_is_kananas_block():
    """`q_lora_rank` 0: one full-rank `q_w`, no indexer, every expert
    held: `reference_kanana.py`'s model, as before the field was."""
    block = unindexed(q_lora_rank=0, experts_first=0, experts_held=0)
    def weights_of(get):
        weights = _weights_without(get,
                                   _INDEX_ROLES + ("qa", "q_norm", "qb"))
        for i, layer in enumerate(weights["layers"]):
            layer["q"] = get(f"attn{i}_q_w")
        return weights

    ids, got, weights = run_forward(24, block, weights_of=weights_of)
    hp = kanana.Hyper(NH, TOP_K, RANK, NOPE, ROPE, VD, EPS, THETA, SCALE)
    assert close(got[0], np.asarray(kanana.logits(weights, ids[0], hp)))


def test_equal_scores_keep_the_lower_position():
    """An indexer whose scores are all equal (w = 0) keeps the OLDEST
    topk positions, in the program and in the reference alike."""
    def zero_weights(scope):
        for i in range(L):
            scope.set_var(f"attn{i}_iw_w", jnp.zeros((DM, IH), jnp.float32))

    ids, got, weights = run_forward(20, block_of(), change=zero_weights)
    assert close(got[0], np.asarray(ref.logits(weights, ids[0], HP)))
    _, masks = ref.choices(weights, ids[0], HP)
    assert np.array_equal(np.nonzero(np.asarray(masks)[0, 19])[0],
                          np.arange(TOPK))


# ---------------------------------------------------------------------------
# the share of the experts under this router
# ---------------------------------------------------------------------------

def _uncut(weights, rng):
    """`weights` with every layer's held experts set into ALL E (the
    others drawn): what the shares of a layer must add up to."""
    out = dict(weights, layers=[])
    for layer in weights["layers"]:
        layer = dict(layer)
        if "router" in layer:
            for key in ("gate", "up", "down"):
                held = layer[key]
                whole = (rng.randn(E, *held.shape[1:]) * 0.1
                         ).astype(np.float32)
                whole[FIRST:FIRST + HELD] = held
                layer[key] = whole
        out["layers"].append(layer)
    return out


def test_the_shares_add_up():
    """The parts every share of a layer gives (experts 0-3 and 4-7 here,
    the shared expert counted ONCE) equal the uncut layer: the gates are
    made over all 8 chosen of ALL experts, held or not, before a share
    is cut. In the reference, and in the program's op against it."""
    from paddle_tpu.ops.moe_ops import moe_gated_ffn
    rng = np.random.RandomState(5)
    h2 = rng.randn(40, DM).astype(np.float32)
    layer = {"router": rng.randn(DM, E).astype(np.float32),
             "router_bias": (0.3 * rng.randn(E)).astype(np.float32),
             "shared_gate": (0.1 * rng.randn(DM, FF)).astype(np.float32),
             "shared_up": (0.1 * rng.randn(DM, FF)).astype(np.float32),
             "shared_down": (0.1 * rng.randn(FF, DM)).astype(np.float32)}
    whole = {k: (0.1 * rng.randn(E, *s)).astype(np.float32)
             for k, s in (("gate", (DM, FF)), ("up", (DM, FF)),
                          ("down", (FF, DM)))}
    uncut, chosen = ref.layer_ffn(dict(layer, **whole), h2,
                                  HP._replace(held_all=True))
    # the bias changes choices: it is not the unbiased top-k
    plain = np.argsort(-(h2 @ layer["router"]), axis=-1,
                       kind="stable")[:, :TOP_K]
    assert (np.sort(np.asarray(chosen), -1) != np.sort(plain, -1)).any()
    shared_alone = np.asarray(ref._gated(
        jnp.asarray(h2), layer["shared_gate"], layer["shared_up"],
        layer["shared_down"]))
    parts = []
    for first in (0, 4):
        cut = {k: v[first:first + 4] for k, v in whole.items()}
        part, _ = ref.layer_ffn(dict(layer, **cut), h2,
                                HP._replace(first=first), shared=False)
        parts.append(np.asarray(part))
        # the program's op on the same share, the shared expert added
        ins = {"X": [jnp.asarray(h2[None])],
               "RouterW": [jnp.asarray(layer["router"])],
               "RouterBias": [jnp.asarray(layer["router_bias"])],
               "WGate": [jnp.asarray(cut["gate"])],
               "WUp": [jnp.asarray(cut["up"])],
               "WDown": [jnp.asarray(cut["down"])],
               "SharedGate": [jnp.asarray(layer["shared_gate"])],
               "SharedUp": [jnp.asarray(layer["shared_up"])],
               "SharedDown": [jnp.asarray(layer["shared_down"])]}
        attrs = {"top_k": TOP_K, "router": "sigmoid_bias",
                 "norm_topk": True, "routed_scale": SCALE,
                 "first_expert": first}
        got = np.asarray(moe_gated_ffn(None, ins, attrs)["Out"][0])[0]
        assert close(got, parts[-1] + shared_alone, 1e-5), first
    assert close(parts[0] + parts[1] + shared_alone, np.asarray(uncut),
                 1e-5)
    # a share alone is not the layer
    assert not close(parts[0] + shared_alone, np.asarray(uncut), 1e-2)


def test_the_held_pairs_are_counted_and_none_is_dropped():
    """The program's forward over a share whose router sends MOST pairs
    elsewhere, and one that sends every pair to held experts: both equal
    the reference (no pair on a held expert is dropped, whatever the
    routing)."""
    for bias in (-3.0, 3.0):
        def lean(scope, bias=bias):
            for i in range(1, L):
                b = np.zeros(E, np.float32)
                b[FIRST:FIRST + HELD] = bias
                scope.set_var(f"moe{i}_router_bias", jnp.asarray(b))

        ids, got, weights = run_forward(24, block_of(), change=lean)
        assert close(got[0], np.asarray(ref.logits(weights, ids[0], HP)))
        routes, _ = ref.choices(weights, ids[0], HP)
        held = (np.asarray(routes) >= FIRST) \
            & (np.asarray(routes) < FIRST + HELD)
        assert held.mean() == (1.0 if bias > 0 else held.mean()) \
            and (bias > 0 or held.mean() < 0.5)


# ---------------------------------------------------------------------------
# the kernel over selected latent rows, interpreted, against its reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("counts", [
    [5, 128, 0, 150],       # under, at and over a block of the kernel
    [160, 160, 160, 160],   # every slot at topk
    [0, 0, 1, 0]])
def test_sparse_latent_kernel_matches_the_gather_reference(counts):
    """Counts under, at and over `topk` and a block of the walk, empty
    slots, rows from pages in no order and across their edges; 8 heads
    at once against rows of two lane tiles whose value is the first."""
    rng = np.random.RandomState(2)
    pool = rng.randn(24, 8, 1, 256).astype(np.float32)
    counts = np.asarray(counts, np.int32)
    rows = np.stack([rng.permutation(np.arange(8, 24 * 8))[:160]
                     for _ in counts]).astype(np.int32)
    q = rng.randn(len(counts), 8, 256).astype(np.float32)
    kw = dict(value_width=128, scale=0.07)
    want = np.asarray(pa.paged_sparse_latent_attention_reference(
        q, pool, rows, counts, **kw))
    got = np.asarray(pa.paged_sparse_latent_attention(
        q, pool, rows, counts, interpret=True, **kw))
    assert got.shape == (len(counts), 8, 128)
    assert np.max(np.abs(got - want)) <= 2e-5
    assert not got[counts == 0].any()
    # against the definition, written out for one slot and head
    s, h = int(np.argmax(counts)), 7
    got_rows = pool.reshape(-1, 256)[rows[s, :counts[s]]]
    sc = (got_rows @ q[s, h]) * 0.07
    p = np.exp(sc - sc.max())
    assert np.max(np.abs(got[s, h] - (p / p.sum()) @ got_rows[:, :128])) \
        <= 2e-5
    # the selection against the whole context through the latent kernel:
    # a slot whose rows are all of its pages reads what that kernel reads
    lens = np.asarray([21, 0, 40, 8], np.int32)
    tables = np.zeros((4, 6), np.int32)
    tables[0, :3], tables[2, :5], tables[3, :1] = [3, 9, 4], \
        [7, 1, 12, 5, 20], [2]
    scores = np.where(np.arange(48)[None] < lens[:, None],
                      rng.randn(4, 48).astype(np.float32), -np.inf)
    _, all_rows, all_counts, _ = pa.sparse_select(
        scores, tables, lens, topk=48, block_size=8)
    q4 = q[:4] if len(q) >= 4 else np.tile(q, (4, 1, 1))[:4]
    whole = np.asarray(pa.paged_latent_attention_reference(
        q4, pool[:, :, 0], tables, lens, **kw))
    picked = np.asarray(pa.paged_sparse_latent_attention(
        q4, pool, all_rows, all_counts, interpret=True, **kw))
    assert np.max(np.abs(picked - whole)) <= 2e-5


def test_the_plan_names_the_kernel():
    plan = pa.paged_decode_plan("latent_index", [[1, 640], [128]], 64, 16,
                                np.float32, 896)
    assert plan.kernel == "index_sparse_latent"
    assert plan.sparse == {"walk": "rows", "chunk_rows": 128}
    # the index pool's walk: 16 rows of 128 floats a page
    assert plan.pages_per_block == pa.paged_latent_block_pages(
        16, 128, np.float32, 896)


# ---------------------------------------------------------------------------
# serving: export -> load -> prefill -> paged decode through both pools
# ---------------------------------------------------------------------------

def _export(tmp, block, seed=7, n_layers=L):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = pt.layers.data("src_ids", [MAXC], dtype="int64")
        tfm.transformer_lm(src, V, n_layers=n_layers, d_model=DM,
                           n_heads=NH, d_ff=FF, max_len=MAXC, block=block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        randomise(scope, seed)
        weights = jax.tree_util.tree_map(
            np.asarray, reference_weights(scope.find_var, n_layers))
        pio.export_decode_model(
            tmp, dict(vocab_size=V, n_layers=n_layers, d_model=DM,
                      n_heads=NH, d_ff=FF, max_context=MAXC, block=block),
            scope=scope, length_buckets=BUCKETS, slots=SLOTS,
            block_size=BLOCK, pool_blocks=POOL)
    return tmp, weights


@pytest.fixture(scope="module")
def glm5_bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("glm5") / "m"), block_of())


def _step_feeds(model):
    return (np.zeros(model.slots, np.int64),
            np.zeros(model.slots, np.int32),
            np.zeros((model.slots, model.max_blocks_per_seq), np.int32))


def test_serving_json_records_the_block_and_two_pools(glm5_bundle):
    with open(os.path.join(glm5_bundle[0], "serving.json")) as f:
        meta = json.load(f)
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    per_token = 4 * L * (ROW + IROW)
    assert dec["cache"] == {
        "kind": "latent_index", "rows": [[1, ROW], [IROW]],
        "row_floats": RANK + ROPE + ID, "bytes_per_token": per_token}
    pools = [m["name"] for m in dec["feeds"][3:3 + 2 * L]]
    assert pools == [f"{stem}_{i}" for i in range(L)
                     for stem in ("latent_cache", "index_cache")]
    assert [m["shape"] for m in dec["feeds"][3:5]] == [
        [POOL, BLOCK, 1, ROW], [POOL, BLOCK, IROW]]
    assert dec["prefill_roles"]["kv"] == [
        [f"latent_{i}", f"index_{i}"] for i in range(L)]
    assert dec["selections"] == {
        "fetch": "selected_out", "topk": TOPK,
        "prefill": [f"selected_{i}" for i in range(L)]}
    assert dec["moe_stats"]["fields"][-1] == "held_pairs"
    by_name = {m["name"]: m for m in meta["buckets"][-1]["fetches"]}
    assert by_name["latent_0"]["shape"] == [1, BUCKETS[-1], RANK + ROPE]
    assert by_name["index_0"]["shape"] == [1, BUCKETS[-1], ID]
    assert by_name["selected_2"]["shape"] == [1, BUCKETS[-1], 1]
    assert dec["fetches"][-1] == {"name": "selected_out",
                                  "shape": [L, SLOTS, TOPK],
                                  "dtype": "int32"}
    model = DecodeModel(glm5_bundle[0], warmup=False)
    desc = model.describe()
    assert desc["cache"] == dec["cache"]
    assert desc["sparse_kernel"] == {"walk": "rows", "chunk_rows": 128}
    assert [p.shape for p in model._pools[:2]] == [
        (POOL, BLOCK, 1, ROW), (POOL, BLOCK, IROW)]
    assert model.index_topk == TOPK


def test_prefill_then_paged_decode_matches_reference(glm5_bundle):
    """A 6-token prompt, then 9 teacher-forced steps: contexts 7..15
    pass the 8 rows kept at the second step, so from there every step
    PRUNES, and cross the block boundaries at 8 and 12. A busy neighbour
    whose 21-token prompt was already pruned in its prefill rides
    along at contexts 22..30."""
    d, weights = glm5_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    ids = rng.randint(0, V, 15)
    other = rng.randint(0, V, 30)
    p_len, o_len = 6, 21
    want = np.asarray(ref.logits(weights, ids, HP))
    want_other = np.asarray(ref.logits(weights, other, HP))

    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    assert [a.shape for a in kv.arrays] == [
        (1, 8, RANK + ROPE), (1, 8, ID)] * L
    model.seed_sequence([1, 2], kv)
    last_o, kv_o = model.prefill([int(t) for t in other[:o_len]])
    model.seed_sequence([11, 12, 13, 14, 15, 16], kv_o)
    tol = 2e-5 * np.std(want)
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    assert np.max(np.abs(np.asarray(last_o) - want_other[o_len - 1])) <= tol
    # the seeded pools: the rows' columns past their floats hold zeros
    latent, index = (np.asarray(p) for p in model._pools[:2])
    assert latent.shape == (POOL, BLOCK, 1, ROW)
    assert latent[1:3].reshape(-1, ROW)[:p_len, :RANK + ROPE].any(1).all()
    assert not latent[..., RANK + ROPE:].any() and not index[..., ID:].any()
    assert index[1:3].reshape(-1, IROW)[:p_len, :ID].any(axis=1).all()

    tokens, lens, tables = _step_feeds(model)
    tables[0, :4] = [1, 2, 3, 4]
    tables[2, :8] = [11, 12, 13, 14, 15, 16, 17, 18]
    for j in range(len(ids) - p_len):
        tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
        tokens[2], lens[2] = other[o_len + j], o_len + j + 1
        rows = np.asarray(model.decode_step(tokens, lens, tables))
        assert np.max(np.abs(rows[0] - want[p_len + j])) <= tol, j
        assert np.max(np.abs(rows[2] - want_other[o_len + j])) <= tol, j
    # one token short at a step that prunes
    tokens[0], lens[0] = ids[14], 14
    short = np.asarray(model.decode_step(tokens, lens, tables))[0]
    assert np.max(np.abs(short - want[14])) > 1000 * tol


def test_the_server_reports_its_routes_and_selections(glm5_bundle):
    """`DecodeModel.last_routes` (the expert layers') and
    `last_selections` after a prefill and after a step are the
    reference's own choices, so the reference forced onto them gives its
    plain logits and no shortfall; another selection shows as one."""
    from paddle_tpu.ops.attention_ops import unpack_mask
    d, weights = glm5_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(8).randint(0, V, 24)
    p_len = 20
    want_routes, want_masks = (np.asarray(a) for a in ref.choices(
        weights, ids, HP))
    assert want_routes.shape == (L - 1, len(ids), TOP_K)
    assert want_masks.shape == (L, len(ids), len(ids))
    _, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    masks = np.zeros((L, len(ids), len(ids)), bool)
    masks[:, :p_len, :p_len] = unpack_mask(
        np.stack([np.asarray(a) for a in model.last_selections]),
        BUCKETS[-1])[:, :p_len, :p_len]
    model.seed_sequence([1, 2, 3, 4, 5], kv)
    tokens, lens, tables = _step_feeds(model)
    tables[1, :6] = [1, 2, 3, 4, 5, 6]
    for j in range(len(ids) - p_len):
        tokens[1], lens[1] = ids[p_len + j], p_len + j + 1
        model.decode_step(tokens, lens, tables)
        routes.append(np.asarray(model.last_routes)[:, 1:2])
        got = np.asarray(model.last_selections)
        assert got.shape == (L, SLOTS, TOPK)
        assert np.all(got[:, [0, 2, 3]] == -1)      # the idle slots
        for layer in range(L):
            masks[layer, p_len + j, got[layer, 1]] = True
    routes = np.concatenate(routes, axis=1)
    assert np.array_equal(np.sort(routes, -1), np.sort(want_routes, -1))
    assert np.array_equal(masks, want_masks)
    plain = np.asarray(ref.logits(weights, ids, HP))
    logits, tie, sel_tie = ref.logits_on(weights, ids, HP, routes, masks)
    assert not np.asarray(tie).any() and not np.asarray(sel_tie).any()
    assert close(np.asarray(logits), plain, 1e-6)
    t = np.arange(len(ids))
    newest = np.broadcast_to(
        (t[None] <= t[:, None]) & (t[None] > t[:, None] - TOPK),
        masks.shape)
    logits, _, sel_tie = ref.logits_on(weights, ids, HP, routes, newest)
    assert np.asarray(sel_tie).max() > 0.3
    assert np.max(np.abs(np.asarray(logits) - plain)) > 0.01 * np.std(plain)


def test_through_the_engine_with_its_counters(glm5_bundle):
    """The normal path end to end: `ServingEngine.load_decode_model`,
    the scheduler and its block accounting, the donated pools; greedy
    tokens equal a teacher-forced argmax of the reference; the row
    counters count every step's live and selected rows (none by a page
    walk: this kernel walks rows alone) and the held pairs."""
    d, weights = glm5_bundle
    engine = ServingEngine()
    engine.load_decode_model("lm", d, warmup=False, max_new_tokens=20)
    try:
        prompt = [int(t) for t in np.random.RandomState(11).randint(0, V, 5)]
        tokens = engine.generate("lm", prompt).result(timeout=300)["tokens"]
        dec = engine.decode_engine("lm")
        seq = prompt + tokens
        want = np.asarray(ref.logits(weights, np.asarray(seq), HP))
        for j, tok in enumerate(tokens):
            row = want[len(prompt) - 1 + j]
            assert row[tok] >= np.max(row) - 1e-4 * np.std(want)
        snap = dec.metrics_snapshot()
        contexts = range(len(prompt) + 1, len(prompt) + len(tokens))
        assert snap["decode_steps"] == len(contexts)
        assert snap["sparse_live_rows"] == sum(contexts)
        assert snap["sparse_selected_rows"] == sum(
            min(n, TOPK) for n in contexts)
        assert snap["sparse_page_walk_slots"] == 0
        assert snap["sparse_walked_pages"] == 0
        routes, _ = ref.choices(weights, np.asarray(seq[:-1]), HP)
        stepped = np.asarray(routes)[:, len(prompt):]
        assert snap["moe_assignments"] == stepped.size
        assert snap["moe_held_pairs"] == int(np.sum(
            (stepped >= FIRST) & (stepped < FIRST + HELD)))
        per_token = 4 * L * (ROW + IROW)
        assert snap["cache_bytes_per_token"] == per_token
        desc = dec.describe()
        assert desc["cache"]["kind"] == "latent_index"
        text = render_prometheus(engine.metrics.snapshot())
        assert 'pt_decode_sparse_selected_rows_total{model="lm"}' in text
        assert 'pt_decode_moe_held_pairs_total{model="lm"}' in text
    finally:
        engine.shutdown(drain=False)


# ---------------------------------------------------------------------------
# BlockSpec, and the mapping's refusals
# ---------------------------------------------------------------------------

def test_block_spec_round_trips_and_declares_its_pools():
    blk = block_of()
    assert tfm.BlockSpec.of(json.loads(json.dumps(blk.to_dict()))) == blk
    assert blk.cache_pools(NH, DM) == {
        "kind": "latent_index", "row_floats": RANK + ROPE + ID,
        "pools": [("latent_cache", [1, ROW]), ("index_cache", [IROW])]}
    assert blk.choosing_layers(L) == list(range(L))
    assert unindexed().cache_pools(NH, DM) == {
        "kind": "latent", "row_floats": RANK + ROPE,
        "pools": [("latent_cache", [ROW])]}
    assert unindexed().choosing_layers(L) == []
    # the published widths: 576 latent floats in 640 and an index key of
    # 128, whole: 3,072 B a token and layer
    wide = block_of(kv_lora_rank=512, qk_nope_head_dim=192,
                    qk_rope_head_dim=64, v_head_dim=256, q_lora_rank=2048,
                    index_heads=32, index_head_dim=128, index_topk=2048,
                    index_rope_dim=64)
    assert [row for _, row in wide.cache_pools(64, 6144)["pools"]] \
        == [[1, 640], [128]]
    # a block without the new fields says what it said before them
    said = unindexed(q_lora_rank=0).to_dict()
    assert not {"q_lora_rank", "index_rope_dim",
                "index_rope_interleave"} & set(said)


@pytest.mark.parametrize("bad", [
    dict(q_lora_rank=0),                    # an indexer without a low-rank
    dict(q_lora_rank=-1), dict(index_rope_dim=7), dict(index_rope_dim=18),
    dict(index_topk=0), dict(index_head_dim=7), dict(index_heads=0),
    dict(attention="mha"),
    dict(attention="gqa", n_kv_heads=2, head_dim=16, kv_lora_rank=0,
         qk_nope_head_dim=0, qk_rope_head_dim=0, v_head_dim=0)])
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        block_of(**bad)


def test_the_trainer_refuses_an_indexer():
    with pt.program_guard(pt.Program(), pt.Program()):
        with pytest.raises(NotImplementedError, match="indexer"):
            tfm.transformer_lm_loss(
                vocab_size=V, seq_len=12, n_layers=L, d_model=DM,
                n_heads=NH, d_ff=FF, max_len=12, block=block_of())


def _config():
    with open(os.path.join(HERE, "..", "benchmark", "configs",
                           "glm-5-serve.json")) as f:
        return json.load(f)


def test_the_configuration_maps_onto_the_block():
    cfg = _config()
    sz = mapping.sizes(cfg)
    block = tfm.BlockSpec.of(sz["block"])
    assert (sz["n_layers"], sz["d_model"], sz["n_heads"], sz["d_ff"],
            sz["vocab"]) == (5, 6144, 64, 2048, 19360)
    assert (block.q_lora_rank, block.kv_lora_rank, block.index_heads,
            block.index_head_dim, block.index_topk, block.index_rope_dim,
            block.index_rope_interleave) == (2048, 512, 32, 128, 2048, 64,
                                             True)
    assert (block.num_experts, block.experts_first, block.experts_held,
            block.dense_layers, block.dense_width) == (256, 0, 8, 1, 12288)
    hp = ref.Hyper.of(cfg)
    assert (hp.n_head, hp.top_k, hp.q_rank, hp.index_topk, hp.first,
            hp.routed_scale, hp.held_all) == (64, 8, 2048, 2048, 0, 2.5,
                                              False)


@pytest.mark.parametrize("change,says", [
    (dict(n_group=8, topk_group=4), "group-limited"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type"),
    (dict(q_lora_rank=None), "indexer without"),
    (dict(num_nextn_predict_layers=1), "multi-token"),
    (dict(reduced=["num_hidden_layers"]), "multi-token")])
def test_the_mapping_refuses_what_is_not_built(change, says):
    cfg = dict(_config(), **change)
    with pytest.raises(ValueError, match=says):
        mapping.sizes(cfg)
