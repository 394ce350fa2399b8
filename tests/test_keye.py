"""Keye-VL-2.0's language block (grouped-query attention with per-head
q/k-norm and a head width of its own, DeepSeek Sparse Attention's indexer
choosing the rows a query reads, an index-key pool beside K and V,
renormalised softmax top-k experts) through the three builders of
`models/transformer.py`, against the plain reference
`benchmark/reference_keye.py`, loaded by path (it lives
once and imports nothing of `paddle_tpu`).

Small sizes, seeded random weights, the CPU: f32 is f32 here, so the
tolerances are what a changed order of float32 sums gives and nothing
more. `index_topk` is 8, so a 24-token forward and a decode past
position 8 both PRUNE: a selection that was ignored, or that kept other
rows, misses these tolerances by orders of magnitude
(`test_the_parts_of_the_selection_each_count`).
"""

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
from paddle_tpu.serving.metrics import render_prometheus

from references import by_path

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import paged_attention as pa

ref = by_path("reference_keye")
HERE = os.path.dirname(os.path.abspath(__file__))

V, L, DM, NH, NKV, HD, FF, E, TOP_K = 97, 2, 64, 8, 2, 16, 16, 8, 2
IH, ID, TOPK = 4, 8, 8           # the indexer: heads, width, rows kept
MAXC, BLOCK, POOL, SLOTS = 48, 4, 40, 4
BUCKETS = (8, 16, 32)
EPS, THETA = 1e-6, 10000000.0
ROW = 128            # the index pool's row: ID = 8 in one lane tile


def block_of(**changes):
    spec = dict(norm="rms_norm", norm_eps=EPS, positions="rope",
                rope_theta=THETA, bias=False, qk_norm=True,
                ffn="moe_gated", num_experts=E, experts_per_tok=TOP_K,
                norm_topk=True, attention="gqa", n_kv_heads=NKV,
                head_dim=HD, index_heads=IH, index_head_dim=ID,
                index_topk=TOPK)
    spec.update(changes)
    return tfm.BlockSpec(**spec)


def plain_gqa(**changes):
    return block_of(index_heads=0, index_head_dim=0, index_topk=0,
                    **changes)


HP = ref.Hyper(NH, NKV, HD, TOP_K, IH, ID, TOPK, EPS, THETA)
HP_PLAIN = HP._replace(index_heads=0, index_dim=0, index_topk=0)

PROGRAM_NAME = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale",
                "head": "lm_head_w"}
GQA_NAME = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
            "q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
            "out": "attn{i}_out_w", "q_norm": "attn{i}_qnorm_scale",
            "k_norm": "attn{i}_knorm_scale", "router": "moe{i}_router_w",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w"}
INDEX_NAME = {"iq": "attn{i}_iq_w", "ik": "attn{i}_ik_w",
              "iw": "attn{i}_iw_w", "ik_norm": "attn{i}_iknorm_scale",
              "ik_bias": "attn{i}_iknorm_bias"}


def reference_weights(get, indexed=True):
    """The program's weights, by the names the builders give them, in
    the shape the reference documents. `get(name)` -> array."""
    names = dict(GQA_NAME, **(INDEX_NAME if indexed else {}))
    return dict({key: get(name) for key, name in PROGRAM_NAME.items()},
                layers=[{key: get(name.format(i=i))
                         for key, name in names.items()}
                        for i in range(L)])


def randomise(scope, seed):
    """Seeded weights with gains away from 1, the LayerNorm's bias away
    from 0, and a router spread wide enough that top-k choices are not
    near ties."""
    rng = np.random.RandomState(seed)
    for name in sorted(scope.local_var_names()):
        v = np.asarray(scope.find_var(name))
        if v.dtype != np.float32:
            continue
        if name.endswith("_scale"):
            new = 1.0 + 0.2 * rng.randn(*v.shape)
        elif name.endswith("iknorm_bias"):
            new = 0.2 * rng.randn(*v.shape)
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
            lambda n: np.asarray(scope.find_var(n)),
            indexed=block.index_topk > 0)
    return ids, got, weights


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq_len", [24, 8, 3])
def test_forward_matches_reference(seq_len):
    """24 tokens: rows 8.. keep 8 of up to 24 positions (three chunks of
    query rows); 8 and 3: every row keeps all it may read, the path
    that never selects."""
    ids, got, weights = run_forward(seq_len, block_of())
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        # float32 order (measured 3e-6 of the spread)
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


@pytest.mark.parametrize("seq_len,block", [(24, 8), (40, 8), (32, 16)])
def test_forward_in_tiles_matches_reference(monkeypatch, seq_len, block):
    """The op's on-chip form, held to here with the kernel interpreted:
    the loop chooses, each chunk's selection leaves it as a mask of a
    byte a (row, key), ONE call of the flash forward a layer attends
    (K and V unrepeated, `block`-wide tiles, so three to five blocks a
    side and every block above the diagonal skipped) against the plain
    reference, to the scores' split into bfloat16 halves."""
    calls = []

    def tiled(q, k, v, **kw):
        calls.append((q.shape, k.shape, kw["selected"].shape,
                      str(kw["selected"].dtype)))
        return fa.flash_attention(q, k, v, block_q=block, block_k=block,
                                  interpret=True, **kw)

    monkeypatch.setattr(fa, "attention_form",
                        lambda *shape: "flash_selected")
    monkeypatch.setattr(fa, "dot_product_attention", tiled)
    ids, got, weights = run_forward(seq_len, block_of())
    assert calls == [((2, seq_len, NH, HD), (2, seq_len, NKV, HD),
                      (2, seq_len, seq_len), "int8")] * L
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP))
        assert np.max(np.abs(got[b] - want)) <= 2e-4 * np.std(want)
    # the selection still counts
    wrong = np.asarray(ref.logits(weights, ids[0],
                                  HP._replace(select="newest")))
    assert np.max(np.abs(got[0] - wrong)) > 0.05 * np.std(wrong)


def test_plain_gqa_matches_reference():
    ids, got, weights = run_forward(24, plain_gqa())
    for b in range(ids.shape[0]):
        want = np.asarray(ref.logits(weights, ids[b], HP_PLAIN))
        assert np.max(np.abs(got[b] - want)) <= 2e-5 * np.std(want)


def test_the_parts_of_the_selection_each_count():
    """What the tolerances above are far inside of: the reference made
    wrong in one part moves the logits by a sizeable share of their
    spread, so a program that ignored the selection, kept the newest
    rows, paired the heads with the wrong group or left a norm out
    could not pass."""
    ids, got, weights = run_forward(24, block_of())
    want = np.asarray(ref.logits(weights, ids[0], HP))

    def off_by(hp=HP, **layer_changes):
        w = dict(weights, layers=[dict(lay, **layer_changes)
                                  for lay in weights["layers"]])
        return float(np.max(np.abs(
            np.asarray(ref.logits(w, ids[0], hp)) - want)) / np.std(want))

    assert off_by() == 0.0
    assert off_by(HP._replace(select="all")) > 0.05
    assert off_by(HP._replace(select="newest")) > 0.05
    assert off_by(HP._replace(pairing="strided")) > 0.05
    assert off_by(HP._replace(theta=10000.0)) > 0.05
    assert off_by(q_norm=np.ones(HD, np.float32)) > 0.05
    assert off_by(ik_bias=np.zeros(ID, np.float32)) > 0.01
    assert off_by(ik_norm=np.ones(ID, np.float32)) > 0.01


def test_equal_scores_keep_the_lower_position():
    """An indexer whose scores are all equal (w = 0) keeps the OLDEST
    topk positions, in the program and in the reference alike."""
    block = block_of()
    main, startup, logits = forward_program(20, block)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        randomise(scope, 3)
        for i in range(L):
            scope.set_var(f"attn{i}_iw_w", jnp.zeros((DM, IH), jnp.float32))
        ids = np.random.RandomState(4).randint(0, V, (1, 20))
        got = exe.run(main, feed={"src_ids": ids}, fetch_list=[logits])[0]
        weights = reference_weights(lambda n: np.asarray(scope.find_var(n)))
    want = np.asarray(ref.logits(weights, ids[0], HP))
    assert np.max(np.abs(got[0] - want)) <= 2e-5 * np.std(want)
    _, masks = ref.choices(weights, ids[0], HP)
    assert np.array_equal(np.nonzero(np.asarray(masks)[0, 19])[0],
                          np.arange(TOPK))


# ---------------------------------------------------------------------------
# the kernels, interpreted, against their gather references
# ---------------------------------------------------------------------------

def _pools(rng, heads, d, n_blocks=24, bs=8):
    return (rng.randn(n_blocks, bs, heads, d).astype(np.float32),
            rng.randn(n_blocks, bs, heads, d).astype(np.float32))


#: contexts under, at and over the rows kept (16), an empty slot, and
#: tables of blocks that are neither in order nor adjacent
_LENS = [5, 16, 0, 37]


def _tables(rng, lens, bs=8, width=6, n_blocks=24):
    tables = np.zeros((len(lens), width), np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    for s, n in enumerate(lens):
        for j in range(-(-n // bs)):
            tables[s, j] = free.pop()
    return tables


def test_index_scores_kernel_matches_the_gather_reference():
    rng = np.random.RandomState(0)
    lens = np.asarray(_LENS, np.int32)
    tables = _tables(rng, lens)
    pool = np.zeros((24, 8, 128), np.float32)
    pool[..., :ID] = rng.randn(24, 8, ID)
    q = np.zeros((len(lens), IH, 128), np.float32)
    q[..., :ID] = rng.randn(len(lens), IH, ID)
    w = rng.randn(len(lens), IH).astype(np.float32)
    want = np.asarray(pa.paged_index_scores_reference(q, w, pool, tables,
                                                      lens))
    got = np.asarray(pa.paged_index_scores(q, w, pool, tables, lens,
                                           interpret=True))
    assert got.shape == want.shape == (len(lens), 48)
    live = np.arange(48)[None] < lens[:, None]
    assert np.all(np.isneginf(got[~live])) and np.all(np.isneginf(want[~live]))
    assert np.max(np.abs(got[live] - want[live])) <= 1e-5
    # against the definition, written out for one slot
    s = 3
    rows = pool[tables[s]].reshape(-1, 128)[:lens[s]]
    direct = np.sum(w[s][:, None] * np.maximum(q[s] @ rows.T, 0.0), axis=0)
    assert np.max(np.abs(got[s, :lens[s]] - direct)) <= 1e-5


def _positions_as_mask(pos, counts, width):
    """The set `sparse_select`'s positions name, a slot a row."""
    want = np.zeros((len(counts), width), bool)
    for s, n in enumerate(counts):
        want[s, pos[s, :n]] = True
    return want


def test_sparse_select_keeps_the_top_rows_lower_position_first():
    lens = np.asarray(_LENS, np.int32)
    rng = np.random.RandomState(1)
    tables = _tables(rng, lens)
    scores = rng.randn(len(lens), 48).astype(np.float32)
    scores[3, [4, 9, 30]] = 7.0                # three equal maxima
    scores = np.where(np.arange(48)[None] < lens[:, None], scores, -np.inf)
    pos, rows, counts, selected = (np.asarray(a) for a in pa.sparse_select(
        scores, tables, lens, topk=16, block_size=8))
    assert list(counts) == [5, 16, 0, 16]
    assert list(pos[3, :3]) == [4, 9, 30]
    for s, n in enumerate(lens):
        want = np.argsort(-scores[s], kind="stable")[:counts[s]]
        assert list(pos[s, :counts[s]]) == list(want)
        assert np.all(pos[s, counts[s]:] == -1)
        assert list(rows[s, :counts[s]]) == [
            tables[s, p // 8] * 8 + p % 8 for p in want]
    assert selected.dtype == bool and selected.shape == scores.shape
    assert np.array_equal(selected, _positions_as_mask(pos, counts, 48))
    # a table narrower than topk
    pos, rows, counts, selected = (np.asarray(a) for a in pa.sparse_select(
        scores[:, :8], tables[:, :1], np.minimum(lens, 8), topk=16,
        block_size=8))
    assert pos.shape == rows.shape == (4, 16)
    assert list(counts) == [5, 8, 0, 8]
    assert np.array_equal(selected, _positions_as_mask(pos, counts, 8))


@pytest.mark.parametrize("scores_of", [
    # every score the same: the oldest rows, wherever the count ends
    lambda rng, n: np.zeros(n, np.float32),
    # few distinct values, so the k-th place is always inside a tie
    lambda rng, n: np.round(rng.randn(n), 0).astype(np.float32),
    # a weighted sum of relus is 0.0 or -0.0 where every relu is shut:
    # `top_k` tells them apart, the set must not
    lambda rng, n: rng.choice(np.asarray(
        [-0.0, 0.0, -0.0, 0.0, 1.0, -1.0, 2.0], np.float32), n),
], ids=["all_equal", "ties_at_the_kth", "both_zeros"])
def test_the_selection_mask_is_the_positions_set_ties_included(scores_of):
    """The page walk reads the selection as a mask over positions: it
    names exactly the rows `positions` names, whatever ties the k-th
    place falls into."""
    rng = np.random.RandomState(7)
    lens = np.asarray([48, 33, 17, 16, 15, 1, 0, 40], np.int32)
    tables = _tables(rng, lens, n_blocks=40)
    scores = np.stack([scores_of(rng, 48) for _ in lens])
    scores = np.where(np.arange(48)[None] < lens[:, None], scores, -np.inf)
    pos, _, counts, selected = (np.asarray(a) for a in pa.sparse_select(
        scores, tables, lens, topk=16, block_size=8))
    assert list(counts) == list(np.minimum(lens, 16))
    assert np.array_equal(selected, _positions_as_mask(pos, counts, 48))
    assert list(selected.sum(axis=1)) == list(counts)


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4)])
def test_sparse_attention_kernel_matches_the_gather_reference(heads,
                                                               kv_heads):
    """The ROW walk. Counts under, at and over a chunk of the kernel, an
    empty slot, rows from blocks in no order; groups of 4 query heads a
    K/V row, and no groups."""
    rng = np.random.RandomState(2)
    k_pool, v_pool = _pools(rng, kv_heads, 128)
    counts = np.asarray([5, 128, 0, 150], np.int32)
    rows = np.stack([rng.permutation(np.arange(8, 24 * 8))[:160]
                     for _ in counts]).astype(np.int32)
    q = rng.randn(len(counts), heads, 128).astype(np.float32)
    want = np.asarray(pa.paged_sparse_attention_reference(
        q, k_pool, v_pool, rows, counts))
    got = np.asarray(pa.paged_sparse_attention(q, k_pool, v_pool, rows,
                                               counts, interpret=True))
    assert np.max(np.abs(got - want)) <= 2e-5
    assert not got[2].any()
    # against the definition, written out for one slot and head
    s, h = 3, heads - 1
    g = h // (heads // kv_heads)
    k = k_pool.reshape(-1, kv_heads, 128)[rows[s, :150], g]
    v = v_pool.reshape(-1, kv_heads, 128)[rows[s, :150], g]
    sc = (k @ q[s, h]) / np.sqrt(128.0)
    p = np.exp(sc - sc.max())
    assert np.max(np.abs(got[s, h] - (p / p.sum()) @ v)) <= 2e-5


#: the two walks of the sparse kernel, a case a batch of lengths under a
#: top-16 of 8-row pages (kappa 4.2: a slot of 5 to 24 rows takes the PAGE
#: walk, a shorter or a longer one its selected ROWS): (lengths, the walk
#: each slot takes, scores all equal?)
_WALKS = {
    # 21 = two pages and five rows of a third
    "partial_last_page": ([21, 8, 53], "ppr", False),
    "empty_slots": ([0, 24, 0, 0], "-p--", False),
    # every live row is selected: the mask is the length's
    "under_topk": ([5, 16, 9, 3], "pppr", False),
    # the k-th place inside a run of equal scores
    "ties_at_the_kth": ([20, 56, 24], "prp", True),
    # slots of each walk in one call, and neither
    "both_walks": ([5, 96, 0, 21, 81, 24, 1, 90], "pr-prprr", False),
    "rows_only": ([96, 88], "rr", False),
}


@pytest.mark.parametrize("case,heads,kv_heads", [
    (case, 8, 2) for case in sorted(_WALKS)] + [("both_walks", 4, 4)])
def test_sparse_attention_walks_match_the_gather_reference(case, heads,
                                                           kv_heads):
    """`paged_sparse_attention` given the block table, the lengths and
    the selection as a mask: each slot by the walk the rule gives it,
    the outputs merged; and the PAGE walk alone over every slot,
    whatever the rule says. One softmax over one set of rows: both equal
    the gather reference over `rows`."""
    lens, took, tied = _WALKS[case]
    lens = np.asarray(lens, np.int32)
    rng = np.random.RandomState(len(case))
    k_pool, v_pool = _pools(rng, kv_heads, 128, n_blocks=64)
    tables = _tables(rng, lens, width=12, n_blocks=64)
    scores = rng.randn(len(lens), 96).astype(np.float32)
    if tied:
        scores = np.round(scores, 0)
    scores = np.where(np.arange(96)[None] < lens[:, None], scores, -np.inf)
    pos, rows, counts, selected = pa.sparse_select(
        scores, tables, lens, topk=16, block_size=8)
    assert np.array_equal(np.asarray(selected), _positions_as_mask(
        np.asarray(pos), np.asarray(counts), 96))
    by_pages = np.asarray(pa.sparse_walks_pages(lens, topk=16,
                                                block_size=8))
    assert "".join("-" if n == 0 else "pr"[not p]
                   for n, p in zip(lens, by_pages)) == took
    q = rng.randn(len(lens), heads, 128).astype(np.float32)
    want = np.asarray(pa.paged_sparse_attention_reference(
        q, k_pool, v_pool, rows, counts))
    got = np.asarray(pa.paged_sparse_attention(
        q, k_pool, v_pool, rows, counts, pages=(tables, lens, selected),
        interpret=True))
    assert np.max(np.abs(got - want)) <= 2e-5
    pages_alone = np.asarray(pa._paged_sparse_attention_pallas(
        q, k_pool, v_pool, tables, lens, selected, scale=128 ** -0.5,
        interpret=True))
    assert np.max(np.abs(pages_alone - want)) <= 2e-5
    assert not got[lens == 0].any() and not pages_alone[lens == 0].any()


def test_sparse_page_walk_over_several_blocks(monkeypatch):
    """A slot whose live pages take several compute blocks, the last
    one partial, beside one that fits the first: the tile budget is cut
    to four pages a block for it."""
    monkeypatch.setattr(pa, "_PAGED_TILE_BYTES", 4 * 4 * 8 * 2 * 128 * 4)
    assert pa.paged_sparse_block_pages(8, 2, 128, np.float32, 13) == 4
    lens = np.asarray([100, 0, 30, 57], np.int32)      # 13, 0, 4, 8 pages
    rng = np.random.RandomState(9)
    k_pool, v_pool = _pools(rng, 2, 128, n_blocks=40)
    tables = _tables(rng, lens, width=13, n_blocks=40)
    scores = np.where(np.arange(104)[None] < lens[:, None],
                      rng.randn(4, 104).astype(np.float32), -np.inf)
    _, rows, counts, selected = pa.sparse_select(
        scores, tables, lens, topk=40, block_size=8)
    q = rng.randn(4, 8, 128).astype(np.float32)
    want = np.asarray(pa.paged_sparse_attention_reference(
        q, k_pool, v_pool, rows, counts))
    got = np.asarray(pa._paged_sparse_attention_pallas(
        q, k_pool, v_pool, tables, lens, selected, scale=128 ** -0.5,
        interpret=True))
    assert np.max(np.abs(got - want)) <= 2e-5


@pytest.mark.parametrize("length,pages", [
    (0, False), (1, False), (3, False), (5, True), (16, True),
    (2048, True), (2049, True), (5000, True), (7680, True),
    # the crossover of a top-2,048 over 16-row pages
    (7792, "at"), (7793, False), (20480, False), (32768, False)])
def test_the_walk_rule_at_the_crossover(length, pages):
    """`sparse_walks_pages`: pages x kappa <= min(length, topk), from the
    lengths alone, on the host's arrays and on traced ones alike. Every
    slot of the Keye cell (at most 7,680 rows of a top-2,048) walks its
    pages; the crossover is where kappa says, and a slot of fewer rows
    than a page costs row copies takes them one by one."""
    kappa = pa.sparse_kernel_walks(16, 4, 128, np.float32, 480)["kappa"]
    if pages == "at":
        pages = 487 * kappa <= 2048
    lens = np.asarray([length, 0, length], np.int32)
    host = pa.sparse_walks_pages(lens, topk=2048, block_size=16)
    assert isinstance(host, np.ndarray) and host.dtype == bool
    assert list(host) == [pages, False, pages]
    traced = jax.jit(lambda n: pa.sparse_walks_pages(
        n, topk=2048, block_size=16))(lens)
    assert list(np.asarray(traced)) == list(host)
    assert host[0] == (length > 0 and -(-length // 16) * kappa
                       <= min(length, 2048))


def test_kappa_leaves_the_keye_cell_its_page_walk():
    """The cell's longest slots (7,680 rows: 480 pages against a
    top-2,048) go over to the row walk at a kappa above 2,048 / 480 =
    4.267, 1.6% over the 4.2 in the code (measured 4.17 at one shape).
    A re-measurement that moves kappa past it moves the cell's walk:
    this case then fails, so that it is seen and said."""
    kappa = pa.sparse_kernel_walks(16, 4, 128, np.float32, 480)["kappa"]
    assert kappa == pa._SPARSE_PAGE_ROW_COPIES
    assert 480 * kappa <= 2048, "the Keye cell's longest slots flip walks"
    lens = np.asarray([3072, 7680], np.int32)     # the cell's range
    assert pa.sparse_walks_pages(lens, topk=2048, block_size=16).all()


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (16, 4)])
def test_paged_kernel_with_groups_matches_the_gather_reference(heads,
                                                               kv_heads):
    """The paged decode kernel with a group of query heads a pool row."""
    rng = np.random.RandomState(3)
    k_pool, v_pool = _pools(rng, kv_heads, 128)
    lens = np.asarray(_LENS, np.int32)
    tables = _tables(rng, lens)
    q = rng.randn(len(lens), heads, 128).astype(np.float32)
    want = np.asarray(pa.paged_attention_reference(q, k_pool, v_pool,
                                                   tables, lens))
    got = np.asarray(pa.paged_decode_attention(q, k_pool, v_pool, tables,
                                               lens, interpret=True))
    assert np.max(np.abs(got - want)) <= 2e-5
    # the reference itself, against heads repeated by hand
    group = heads // kv_heads
    wide = np.asarray(pa.paged_attention_reference(
        q, np.repeat(k_pool, group, 2), np.repeat(v_pool, group, 2),
        tables, lens))
    assert np.array_equal(want, wide)


# ---------------------------------------------------------------------------
# training: plain GQA trains, an indexer is refused
# ---------------------------------------------------------------------------

def test_plain_gqa_training_step_matches_reference_gradients():
    seq_len, batch = 12, 3
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=seq_len, n_layers=L, d_model=DM,
            n_heads=NH, d_ff=FF, max_len=seq_len, block=plain_gqa())
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
            lambda n: np.asarray(scope.find_var(n)), indexed=False)
        by_name = {p.name: g for p, g in grads}
        got = exe.run(main, feed={"src_ids": ids,
                                  "tgt_ids": tgt[..., None]},
                      fetch_list=[avg] + list(by_name.values()))
    got_loss = float(np.ravel(got[0])[0])
    got_grads = dict(zip(by_name, got[1:]))

    def mean_loss(w):
        return sum(ref.nll_sum(w, jnp.asarray(ids[b]), jnp.asarray(tgt[b]),
                               HP_PLAIN)
                   for b in range(batch)) / (batch * seq_len)

    want_loss, want = jax.value_and_grad(mean_loss)(weights)
    assert abs(got_loss - float(want_loss)) <= 1e-5 * abs(float(want_loss))

    def check(name, want_grad):
        g, w = np.asarray(got_grads[name]), np.asarray(want_grad)
        # per parameter, against the gradient's own largest entry: ten
        # times what float32 accumulation gives; a head paired with the
        # wrong group, or a norm left out of the backward, is of order 1
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-9, \
            name

    for key, name in PROGRAM_NAME.items():
        check(name, want[key])
    for i in range(L):
        for key, name in GQA_NAME.items():
            check(name.format(i=i), want["layers"][i][key])


def test_the_trainer_refuses_an_indexer():
    with pt.program_guard(pt.Program(), pt.Program()):
        with pytest.raises(NotImplementedError, match="indexer"):
            tfm.transformer_lm_loss(
                vocab_size=V, seq_len=12, n_layers=L, d_model=DM,
                n_heads=NH, d_ff=FF, max_len=12, block=block_of())


# ---------------------------------------------------------------------------
# serving: export -> load -> prefill -> paged decode through the three
# pools, against the reference's full forward
# ---------------------------------------------------------------------------

def export_cfg(block):
    return dict(vocab_size=V, n_layers=L, d_model=DM, n_heads=NH, d_ff=FF,
                max_context=MAXC, block=block)


def _export(tmp, block, seed=7, copy_from=None, block_size=BLOCK,
            pool_blocks=POOL):
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
            np.asarray, reference_weights(scope.find_var,
                                          indexed=block.index_topk > 0))
        pio.export_decode_model(
            tmp, export_cfg(block), scope=scope, length_buckets=BUCKETS,
            slots=SLOTS, block_size=block_size, pool_blocks=pool_blocks)
    return tmp, weights


@pytest.fixture(scope="module")
def keye_bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("keye") / "m"), block_of())


#: the bundle whose steps take BOTH walks of the sparse kernel under
#: the engine: a top-12 of 8-row pages, so that at kappa 4.2 a context
#: of 5 to 16 rows (one page, two) goes by its pages and one of 17 or
#: more (three pages cost 12.6 row copies, the selection is 12 rows)
#: by its selected rows. (`keye_bundle`'s pages of 4 rows hold fewer
#: rows than a page costs row copies: no step of it walks a page.)
WALK_BLOCK, WALK_TOPK = 8, 12


@pytest.fixture(scope="module")
def keye_walks_bundle(tmp_path_factory):
    return _export(str(tmp_path_factory.mktemp("keye_walks") / "m"),
                   block_of(index_topk=WALK_TOPK), block_size=WALK_BLOCK,
                   pool_blocks=POOL * BLOCK // WALK_BLOCK)


def _step_feeds(model):
    return (np.zeros(model.slots, np.int64),
            np.zeros(model.slots, np.int32),
            np.zeros((model.slots, model.max_blocks_per_seq), np.int32))


def test_serving_json_records_the_block_and_three_pools(keye_bundle):
    with open(os.path.join(keye_bundle[0], "serving.json")) as f:
        meta = json.load(f)
    dec = meta["decode"]
    assert tfm.BlockSpec.of(dec["model_cfg"]["block"]) == block_of()
    assert dec["head_dim"] == HD
    per_token = 4 * L * (2 * NKV * HD + ROW)
    assert dec["cache"] == {
        "kind": "kv_index", "rows": [[NKV, HD], [NKV, HD], [ROW]],
        "row_floats": 2 * NKV * HD + ID, "bytes_per_token": per_token}
    pools = [m["name"] for m in dec["feeds"][3:3 + 3 * L]]
    assert pools == [f"{stem}_{i}" for i in range(L)
                     for stem in ("k_cache", "v_cache", "index_cache")]
    assert [m["shape"] for m in dec["feeds"][3:6]] == [
        [POOL, BLOCK, NKV, HD], [POOL, BLOCK, NKV, HD], [POOL, BLOCK, ROW]]
    assert dec["prefill_roles"]["kv"] == [
        [f"k_{i}", f"v_{i}", f"index_{i}"] for i in range(L)]
    assert dec["selections"] == {
        "fetch": "selected_out", "topk": TOPK,
        "prefill": [f"selected_{i}" for i in range(L)]}
    by_name = {m["name"]: m for m in meta["buckets"][-1]["fetches"]}
    assert by_name["logits"]["shape"] == [1, 1, V]
    assert by_name["index_0"]["shape"] == [1, BUCKETS[-1], ID]
    assert [by_name[f"selected_{i}"]["shape"] for i in range(L)] \
        == [[1, BUCKETS[-1], 1]] * L
    assert dec["fetches"][-1] == {"name": "selected_out",
                                  "shape": [L, SLOTS, TOPK],
                                  "dtype": "int32"}
    # every bucket is over topk, and was traced off the chip
    assert dec["prefill_attention"] == {
        str(b): "masked_dense" for b in BUCKETS}
    model = DecodeModel(keye_bundle[0], warmup=False)
    desc = model.describe()
    assert desc["cache"] == dec["cache"]
    assert desc["prefill_attention"] == dict.fromkeys(BUCKETS,
                                                      "masked_dense")
    assert [p.shape for p in model._pools[:3]] == [
        (POOL, BLOCK, NKV, HD), (POOL, BLOCK, NKV, HD), (POOL, BLOCK, ROW)]
    assert model.index_topk == TOPK


def test_a_bundle_from_before_the_record_describes_no_form(keye_bundle,
                                                           tmp_path):
    import shutil
    old = str(tmp_path / "old")
    shutil.copytree(keye_bundle[0], old)
    with open(os.path.join(old, "serving.json")) as f:
        meta = json.load(f)
    del meta["decode"]["prefill_attention"]
    with open(os.path.join(old, "serving.json"), "w") as f:
        json.dump(meta, f)
    assert DecodeModel(old, warmup=False).describe()[
        "prefill_attention"] is None


def test_prefill_then_paged_decode_matches_reference(keye_bundle):
    """A 6-token prompt, then 9 teacher-forced steps: contexts 7..15
    pass the 8 rows kept at the second step, so from there every step
    PRUNES (8 of 9..15), and cross the block boundaries at 8 and 12. A
    busy neighbour whose 21-token prompt was already pruned in its
    prefill rides along at contexts 22..30."""
    d, weights = keye_bundle
    model = DecodeModel(d, warmup=False)
    rng = np.random.RandomState(8)
    ids = rng.randint(0, V, 15)
    other = rng.randint(0, V, 30)
    p_len, o_len = 6, 21
    want = np.asarray(ref.logits(weights, ids, HP))
    want_other = np.asarray(ref.logits(weights, other, HP))

    last, kv = model.prefill([int(t) for t in ids[:p_len]])
    assert [a.shape for a in kv.arrays] == [
        (1, 8, NKV, HD), (1, 8, NKV, HD), (1, 8, ID)] * L
    model.seed_sequence([1, 2], kv)
    last_o, kv_o = model.prefill([int(t) for t in other[:o_len]])
    model.seed_sequence([11, 12, 13, 14, 15, 16], kv_o)
    tol = 2e-5 * np.std(want)   # float32 order; the selection ignored
    # moves a row by 0.05 of the spread and more
    assert np.max(np.abs(np.asarray(last) - want[p_len - 1])) <= tol
    assert np.max(np.abs(np.asarray(last_o) - want_other[o_len - 1])) <= tol
    # the seeded index pool: the row's columns past ID hold zeros
    pool = np.asarray(model._pools[2])
    assert pool.shape == (POOL, BLOCK, ROW)
    assert pool[1:3].reshape(-1, ROW)[:p_len, :ID].any(axis=1).all()
    assert not pool[..., ID:].any()
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
    # one token short at a step that prunes: its newest row unread, RoPE
    # one position early: over a thousand times the tolerance
    tokens[0], lens[0] = ids[14], 14
    short = np.asarray(model.decode_step(tokens, lens, tables))[0]
    assert np.max(np.abs(short - want[14])) > 1000 * tol


def test_the_server_reports_its_routes_and_selections(keye_bundle):
    """`DecodeModel.last_routes` and `last_selections` after a prefill
    (every row's positions, one bit each) and after a step (a slot's
    positions) are the reference's own choices, so the reference forced
    onto them gives its plain logits and no shortfall; a selection that
    is another than its own shows as one."""
    from paddle_tpu.ops.attention_ops import pack_mask, unpack_mask
    d, weights = keye_bundle
    model = DecodeModel(d, warmup=False)
    ids = np.random.RandomState(8).randint(0, V, 24)
    p_len = 20                  # the prefill's rows 8.. prune
    want_routes, want_masks = (np.asarray(a) for a in ref.choices(
        weights, ids, HP))
    assert want_masks.shape == (L, len(ids), len(ids))
    assert list(want_masks[0].sum(-1)) == [min(t + 1, TOPK)
                                           for t in range(len(ids))]
    _, kv = model.prefill([int(t) for t in ids[:p_len]])
    routes = [np.asarray(model.last_routes)[:, :p_len]]
    assert [a.shape for a in model.last_selections] \
        == [(BUCKETS[-1], 1)] * L
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
    assert np.array_equal(routes, want_routes)
    assert np.array_equal(masks, want_masks)
    plain = np.asarray(ref.logits(weights, ids, HP))
    logits, tie, sel_tie = ref.logits_on(weights, ids, HP, routes, masks)
    assert not np.asarray(tie).any() and not np.asarray(sel_tie).any()
    assert np.array_equal(np.asarray(logits), plain)
    rows = list(range(p_len - 1, len(ids)))
    only = np.asarray(ref.logits_on(weights, ids, HP, routes, masks,
                                    rows=rows)[0])
    assert np.max(np.abs(only - plain[rows])) <= 1e-6 * np.std(plain)
    # the newest 8 rows in place of the top 8: the logits move and the
    # shortfall says why; a row with a position too many reads all it may
    t = np.arange(len(ids))
    newest = np.broadcast_to(
        (t[None] <= t[:, None]) & (t[None] > t[:, None] - TOPK),
        masks.shape)
    logits, _, sel_tie = ref.logits_on(weights, ids, HP, routes, newest)
    assert np.asarray(sel_tie).max() > 0.3
    assert np.max(np.abs(np.asarray(logits) - plain)) > 0.01 * np.std(plain)
    more = masks.copy()
    more[0, 20] = t <= 20
    assert np.asarray(ref.logits_on(weights, ids, HP, routes,
                                    more)[2])[0, 20] == 1e9
    # the packing itself: bit s % 32 of word s // 32
    some = np.random.RandomState(0).rand(3, 70) < 0.3
    packed = np.asarray(pack_mask(jnp.asarray(some)))
    assert packed.shape == (3, 3) and packed.dtype == np.int32
    assert np.array_equal(unpack_mask(packed, 70), some)
    assert (int(packed[0, 1]) >> 5) & 1 == int(some[0, 37])


def test_an_indexer_that_keeps_every_row_is_plain_gqa(tmp_path):
    """`index_topk` at or over the context: the indexed model IS plain
    grouped-query attention on the same weights, to rounding: in the
    prefill (which then never selects) and in the decode step, which
    scores, selects and gathers all the same and reads every live row
    through the sparse kernel's path."""
    every = block_of(index_topk=MAXC)
    d_idx, weights = _export(str(tmp_path / "indexed"), every)
    d_gqa, plain_weights = _export(str(tmp_path / "plain"), plain_gqa())
    for key in GQA_NAME:       # the same seed draws the same weights in
        # name order only where the names are the same: copy them over
        for i in range(L):
            assert plain_weights["layers"][i][key].shape \
                == weights["layers"][i][key].shape
    with np.load(os.path.join(d_idx, "weights.npz")) as f:
        shared = {n: f[n] for n in f.files}
    with np.load(os.path.join(d_gqa, "weights.npz")) as f:
        assert set(f.files) < set(shared)
        names = list(f.files)
    np.savez(os.path.join(d_gqa, "weights.npz"),
             **{n: shared[n] for n in names})
    indexed = DecodeModel(d_idx, warmup=False)
    plain = DecodeModel(d_gqa, warmup=False)
    assert plain.index_topk == 0 and plain.last_selections is None
    assert plain.cache["kind"] == "kv" and len(plain.cache["rows"]) == 2
    ids = np.random.RandomState(9).randint(0, V, 26)
    p_len = 18
    rows = []
    for model in (indexed, plain):
        last, kv = model.prefill([int(t) for t in ids[:p_len]])
        model.seed_sequence([1, 2, 3, 4, 5], kv)
        tokens, lens, tables = _step_feeds(model)
        tables[0, :7] = [1, 2, 3, 4, 5, 6, 7]
        out = [np.asarray(last)]
        for j in range(len(ids) - p_len):
            tokens[0], lens[0] = ids[p_len + j], p_len + j + 1
            out.append(np.asarray(model.decode_step(tokens, lens,
                                                    tables))[0])
        rows.append(np.stack(out))
    want = np.asarray(ref.logits(
        reference_weights(lambda n: shared[n], indexed=False), ids,
        HP_PLAIN))[p_len - 1:]
    assert np.max(np.abs(rows[0] - rows[1])) <= 2e-5 * np.std(want)
    assert np.max(np.abs(rows[1] - want)) <= 2e-5 * np.std(want)


def test_through_the_engine_with_its_counters(keye_walks_bundle):
    """The normal path end to end: `ServingEngine.load_decode_model`,
    the scheduler and its block accounting, the donated pools; greedy
    tokens equal a teacher-forced argmax of the reference; the two row
    counters count every step's live and selected rows; `describe()`
    and the scrape say what the cache is."""
    d, weights = keye_walks_bundle
    hp = HP._replace(index_topk=WALK_TOPK)
    engine = ServingEngine()
    engine.load_decode_model("lm", d, warmup=False, max_new_tokens=20)
    try:
        prompt = [int(t) for t in np.random.RandomState(11).randint(0, V, 5)]
        tokens = engine.generate("lm", prompt).result(timeout=300)["tokens"]
        dec = engine.decode_engine("lm")
        seq = prompt + tokens
        want = np.asarray(ref.logits(weights, np.asarray(seq), hp))
        for j, tok in enumerate(tokens):
            row = want[len(prompt) - 1 + j]
            assert row[tok] >= np.max(row) - 1e-4 * np.std(want)
        snap = dec.metrics_snapshot()
        # the first token is the prefill's; steps at contexts 6..24
        contexts = range(len(prompt) + 1, len(prompt) + len(tokens))
        assert snap["decode_steps"] == len(contexts)
        assert snap["sparse_live_rows"] == sum(contexts)
        assert snap["sparse_selected_rows"] == sum(
            min(n, WALK_TOPK) for n in contexts)
        # how the kernel reached them: the shorter contexts by their
        # pages, whole (at kappa 4.2: up to two pages of 8 against a
        # top-12), the steps past that by their selected rows: a mixed
        # window
        kappa = pa.sparse_kernel_walks(WALK_BLOCK, NKV, HD, np.float32,
                                       MAXC // WALK_BLOCK)["kappa"]
        by_pages = [n for n in contexts
                    if -(-n // WALK_BLOCK) * kappa <= min(n, WALK_TOPK)]
        assert 0 < len(by_pages) < len(contexts) == snap["slots_used_sum"]
        assert snap["sparse_page_walk_slots"] == len(by_pages)
        assert snap["sparse_walked_pages"] == sum(
            -(-n // WALK_BLOCK) for n in by_pages)
        assert snap["sparse_walked_pages"] > len(by_pages)  # two-page steps
        per_token = 4 * L * (2 * NKV * HD + ROW)
        assert snap["cache_bytes_per_token"] == per_token
        assert snap["step_aliased_bytes"] == POOL * BLOCK * per_token
        desc = dec.describe()
        assert desc["cache"]["kind"] == "kv_index"
        assert len(desc["cache"]["rows"]) == 3
        assert desc["sparse_kernel"] == {
            "kappa": kappa, "pages_per_block": MAXC // WALK_BLOCK,
            "chunk_rows": 128, "heads_per_product": NH // NKV,
            "score_columns_per_block": MAXC}
        text = render_prometheus(engine.metrics.snapshot())
        assert 'pt_decode_sparse_page_walk_slots_total{model="lm"} %d' \
            % len(by_pages) in text
        assert 'pt_decode_sparse_walked_pages_total{model="lm"}' in text
        assert 'pt_decode_sparse_live_rows_total{model="lm"} %d' \
            % sum(contexts) in text
        assert 'pt_decode_sparse_selected_rows_total{model="lm"}' in text
    finally:
        engine.shutdown(drain=False)


def test_device_tokens_equal_host_argmax_sparse(keye_bundle,
                                                served_and_watched):
    """This bundle's step returns its ids before the three pools a layer
    and the routing counters, routes and selections behind them."""
    model = served_and_watched(keye_bundle[0], V, SLOTS)
    assert model.last_routes is not None
    assert model.last_selections is not None


# ---------------------------------------------------------------------------
# BlockSpec
# ---------------------------------------------------------------------------

def test_block_spec_round_trips_and_declares_its_pools():
    blk = block_of()
    assert tfm.BlockSpec.of(json.loads(json.dumps(blk.to_dict()))) == blk
    assert blk.cache_pools(NH, DM) == {
        "kind": "kv_index", "row_floats": 2 * NKV * HD + ID,
        "pools": [("k_cache", [NKV, HD]), ("v_cache", [NKV, HD]),
                  ("index_cache", [ROW])]}
    assert plain_gqa().cache_pools(NH, DM) == {
        "kind": "kv", "row_floats": 2 * NKV * HD,
        "pools": [("k_cache", [NKV, HD]), ("v_cache", [NKV, HD])]}
    # the published widths: 4 K/V heads of 128 and 64 of index key in
    # 128: 4,608 B a token and layer as stored, 4,352 B of content
    wide = block_of(n_kv_heads=4, head_dim=128, index_heads=16,
                    index_head_dim=64, index_topk=2048)
    pools = wide.cache_pools(32, 2048)
    assert pools["row_floats"] == 1088
    assert [row for _, row in pools["pools"]] == [[4, 128], [4, 128], [128]]
    # a head width that is not d_model / n_heads, per-head K and V
    mha = tfm.BlockSpec(head_dim=24)
    assert mha.cache_pools(4, 64) == {
        "kind": "kv", "row_floats": 2 * 4 * 24,
        "pools": [("k_cache", [4, 24]), ("v_cache", [4, 24])]}


#: what `BlockSpec.to_dict` gave before this kind of attention existed:
#: the bundles of the kinds that were there record this and nothing else
_KEYS_BEFORE = [
    "norm", "norm_eps", "positions", "rope_theta", "qk_norm", "bias", "ffn",
    "num_experts", "experts_per_tok", "attention", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_interleave",
    "router", "norm_topk", "routed_scale", "shared_width", "dense_layers",
    "dense_width"]


@pytest.mark.parametrize("block,cache", [
    (tfm.GPT2_BLOCK,                       # Cerebras-GPT: 16 heads of 128
     {"kind": "kv", "row_floats": 4096,
      "pools": [("k_cache", [16, 128]), ("v_cache", [16, 128])]}),
    (tfm.BlockSpec(norm="rms_norm", positions="rope", qk_norm=True,
                   bias=False, ffn="moe_gated", num_experts=64,
                   experts_per_tok=8),     # OLMoE
     {"kind": "kv", "row_floats": 4096,
      "pools": [("k_cache", [16, 128]), ("v_cache", [16, 128])]}),
    (tfm.BlockSpec(norm="rms_norm", positions="rope", bias=False,
                   attention="latent", kv_lora_rank=512,
                   qk_nope_head_dim=128, qk_rope_head_dim=64,
                   v_head_dim=128, rope_interleave=True, ffn="moe_gated",
                   num_experts=128, experts_per_tok=6,
                   router="sigmoid_bias", norm_topk=True),   # Kanana
     {"kind": "latent", "row_floats": 576,
      "pools": [("latent_cache", [640])]})],
    ids=["gpt2", "olmoe", "kanana"])
def test_the_bundles_that_were_there_record_what_they_did(block, cache):
    """`serving.json` of the three cells that were there stays byte for
    byte: the block's dict has the keys it had, in their order, and the
    cache is declared as it was."""
    assert list(block.to_dict()) == _KEYS_BEFORE
    assert tfm.BlockSpec.of(block.to_dict()) == block
    assert block.cache_pools(16, 2048) == cache


@pytest.mark.parametrize("bad", [
    dict(n_kv_heads=0), dict(head_dim=0), dict(head_dim=15),
    dict(index_topk=0), dict(index_head_dim=7), dict(index_heads=0),
    dict(positions="learned"), dict(bias=True),
    dict(attention="mha"),                  # K/V groups without gqa
    dict(attention="mha", n_kv_heads=0)])   # an indexer without gqa
def test_block_spec_refuses_what_it_does_not_know(bad):
    with pytest.raises(ValueError):
        block_of(**bad)
