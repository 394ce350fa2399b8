"""Paged decode attention: the Pallas TPU kernels a decode step reads a
paged cache with, their XLA references and the pool writers.

One query token a slot against that slot's rows of a preallocated pool,
reached through a block table. Six kernels over one walk of the live
pages (`_paged_walk`): a head at a time (`_paged_kernel`), a group of
query heads a K/V head (`_paged_group_kernel`, windows and packed heads
too, and the two subtracted softmaxes of differential attention, "diff"),
one latent row a token (`_paged_latent_kernel`), the indexer's
scores (`_paged_index_kernel`) and the attention over the rows it
selected, of K and V (`_paged_sparse_kernel`) or of a latent pool, all
heads a row (`_paged_sparse_latent_kernel`). Which of them a bundle's step runs,
and at what block, is `paged_decode_plan`: the wrappers run by it and
`serving.decode.engine.DecodeModel.describe()` prints it, as
`flash_attention.flash_block_plan` is for the training kernels.

The arrow points one way: this module takes `DEFAULT_MASK_VALUE` and the
guarded `pltpu` import from `flash_attention.py`, which imports nothing
from here. Layout: pools [NB, BS, ...row], block_tables [S, MB] int32,
context_lens [S] int32, the newly written token counted.

The compile cache: an edit here (a blank line on top is enough) makes the
programs that hold one of these kernels, a bundle's decode step, compile
anew once, and no program of the trainer. Measured on the chip (PERF.md
section 6, PR 46): `cgpt1p3b_serve_rollout`'s `setup_s` 41.5 s warm, 54.5 s
after a blank line on top of this file, 64.8 s after one on top of
`flash_attention.py` (its prefill buckets); the train cell's 33.5 s warm
and 32.8 s after the line on top of this file.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..obs import trace as obs_trace
from .flash_attention import _HAS_PLTPU, DEFAULT_MASK_VALUE, pltpu


# ---------------------------------------------------------------------------
# The plan: which kernel a decode step's attention runs, and at what block
# ---------------------------------------------------------------------------

class PagedPlan(NamedTuple):
    """What a decode step's attention does at a bundle's shapes, static.
    `kernel`: "per_head" (`_paged_kernel`), "grouped"
    (`_paged_group_kernel`), "diff" (the same with two softmaxes a head
    pair subtracted at its end), "latent" (`_paged_latent_kernel`) or
    "index_sparse" (`_paged_index_kernel`, then `_paged_sparse_kernel`
    over what `sparse_select` kept) or "index_sparse_latent" (the same
    over latent rows: `_paged_sparse_latent_kernel`). `pages_per_block`: P of the kernel
    that walks every live page of a slot (of an indexer layer, the one
    over the index keys). `heads_per_product`, `score_columns_per_block`:
    a block of the grouped kernel (`group_block_shape`; None for the
    others). `sparse`: of an indexer layer whose K/V rows were given, the
    attention kernel's two walks (`sparse_kernel_walks`) and a block of
    its page walk."""
    kernel: str
    pages_per_block: int
    heads_per_product: Optional[int] = None
    score_columns_per_block: Optional[int] = None
    sparse: Optional[dict] = None


def paged_decode_plan(kind, rows, n_heads, block_size, dtype, table_width,
                      window=None):
    """The plan of a layer's paged attention, from shapes alone. `kind`
    and `rows` as a bundle's cache declares them
    (`models.transformer.BlockSpec.cache_pools`): "kv" with the K (and V)
    row [H_kv, D] (packed heads: [tiles, 128]), "kv_diff" with the row
    [H_kv D] of differential attention (tiles of 128 lanes side by side,
    K/V head g of either set in tile g: a tile is read by the 2 H / H_kv
    query heads of both sets that pair on it), "latent" with the one row
    [W], "kv_index" with the K and V rows and the index key's [W] last (a
    caller that holds the index pool alone gives that row alone, and gets
    no `sparse`), "latent_index" with the latent row and the index key's. `window`: the rows a window layer reads back (None: it
    is none). The `*_block_pages` functions are its parts."""
    if kind in ("latent", "kv_index", "latent_index"):
        # the pool a step walks page by page is the one of one row a
        # token: the latent rows, or the index keys
        pages = paged_latent_block_pages(block_size, rows[-1][0], dtype,
                                         table_width)
        if kind == "latent":
            return PagedPlan("latent", pages)
        if kind == "latent_index":
            # the selected latent rows come one copy a row
            return PagedPlan("index_sparse_latent", pages, sparse={
                "walk": "rows", "chunk_rows": _SPARSE_CHUNK_ROWS})
        sparse = None
        if len(rows) > 1:
            kv_heads, head_dim = rows[0]
            sparse = sparse_kernel_walks(block_size, kv_heads, head_dim,
                                         dtype, table_width)
            # a block of the page walk, as the grouped kernel's is
            sparse.update(group_block_shape(
                n_heads, kv_heads, sparse["pages_per_block"], block_size))
        return PagedPlan("index_sparse", pages, sparse=sparse)
    if kind == "kv_diff":
        # the row's tiles (a row under a lane tile, off the chip: one)
        kv_heads = max(1, rows[0][0] // 128)
        head_dim = rows[0][0] // kv_heads
        pages = paged_sparse_block_pages(block_size, kv_heads, head_dim,
                                         dtype, table_width)
        return PagedPlan("diff", pages, **group_block_shape(
            n_heads, kv_heads, pages, block_size))
    kv_heads, head_dim = rows[0]
    if window is not None or kv_heads != n_heads:
        # shared K/V heads, or a window: the MXU form, a block in whole
        # lane tiles of score columns
        pages = paged_sparse_block_pages(block_size, kv_heads, head_dim,
                                         dtype, table_width)
        return PagedPlan("grouped", pages, **group_block_shape(
            n_heads, kv_heads, pages, block_size))
    return PagedPlan("per_head", paged_block_pages(
        block_size, kv_heads, head_dim, dtype, table_width))


# ---------------------------------------------------------------------------
# Paged decode attention (the ragged-paged shape of this kernel family)
#
# Autoregressive serving keeps each sequence's K/V in fixed-size BLOCKS of a
# preallocated pool ([num_blocks, block_size, H, D]); a per-sequence block
# table maps logical positions to pool blocks, so sequences of ragged
# lengths share one pool with no per-sequence reallocation (the "Ragged
# Paged Attention" kernel shape, PAPERS.md). One decode step scores ONE new
# query token per sequence against that sequence's pages.
#
# Two paths, same contract as the training kernels (`flash_attention.py`):
#   * Pallas TPU kernel — one invocation, no grid over the table. The pools
#     stay in HBM (`ANY`); the block table and the context lengths ride in
#     scalar-prefetch refs. The kernel walks the sequences and, of each,
#     only its LIVE pages, in COMPUTE BLOCKS of P consecutive table entries
#     (P x block_size tokens): a block's K and V pages come in by one
#     `make_async_copy` a live page into one of two VMEM tiles, and the
#     next block's copies (the same sequence's, or the first block of the
#     next live sequence) are in flight while this one is scored. A block
#     is waited for by its BYTES, once a pool (`_paged_walk`): the core
#     issues its DMA operations from the instruction stream that drives
#     its arithmetic, so every start and wait is time the block's bytes
#     do not hide, and the smaller a page the more of them a block has. A
#     table entry past ceil(len/bs) costs nothing: no grid step, no DMA.
#     The online-softmax state lives in registers and is updated once a
#     block; the one masked tail is the sequence's last block.
#     P = `paged_block_pages`: what the double-buffered K and V tiles of
#     the pool's page fit of a fixed VMEM budget, never more than the
#     table's width. From shapes and dtype alone: no knob.
#   * gather-based XLA reference — k_pool[block_tables] + masked softmax;
#     the CPU/tier-1 path and the numerics oracle.
#
# Layout: q [S, H, D] (one token per slot), pools [NB, BS, H, D],
# block_tables [S, MB] int32, context_lens [S] int32 — the span INCLUDING
# the newly written token. Block id 0 is reserved as the null block:
# inactive slots (context_len 0) point every table entry at it and produce
# zero output rather than NaN.
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pool, v_pool, block_tables, context_lens,
                              *, scale: Optional[float] = None,
                              window: Optional[int] = None):
    """Gather-based XLA paged attention (CPU path + oracle). The pools
    may hold fewer heads than q has: query head j reads K/V head
    j // (H / H_kv). `window`: positions len - window .. len - 1 alone
    (whatever the table's older entries name is gathered and masked).
    Pools whose rows hold several heads to a lane tile (`_unpacked`) are
    read as the heads they hold."""
    s_n, h, d = q.shape
    k_pool, v_pool = _unpacked(k_pool, d), _unpacked(v_pool, d)
    bs, hk = k_pool.shape[1], k_pool.shape[2]
    mb = block_tables.shape[1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    flat = block_tables.reshape(-1).astype(jnp.int32)
    k = jnp.take(k_pool, flat, axis=0).reshape(s_n, mb * bs, hk, d)
    v = jnp.take(v_pool, flat, axis=0).reshape(s_n, mb * bs, hk, d)
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, :]
    lens = context_lens.astype(jnp.int32)[:, None, None]
    mask = kpos < lens
    if window is not None:
        mask = mask & (kpos >= lens - window)
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    # all-masked rows (context_len 0: the null slot) divide by 1 -> zeros;
    # any live row has l >= exp(0) = 1 at its own max
    p = p / jnp.maximum(l, 1.0)
    out = jnp.einsum("shk,skhd->shd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _unpacked(pool, head_dim):
    """A K or V pool as [NB, BS, H_kv, D]: what it is, unless its rows
    are stored `128 / D` heads to a lane tile ([NB, BS, H_kv D / 128,
    128], `models.transformer.packed_kv_row`: row-major, so head j of a
    token is lanes (j % pack) D .. of tile j // pack)."""
    if pool.shape[-1] == head_dim:
        return pool
    return pool.reshape(pool.shape[:2] + (-1, head_dim))


#: VMEM the paged kernel gives its K and V tiles, both double-buffered.
#: Measured on the v5e at the serve cells' page (f32, 16 x 16 x 128, 128
#: KB), a layer call: 2 / 4 / 8 MiB (4 / 8 / 16 pages a block) 134.7 /
#: 137.6 / 143.8 us at ragged contexts, 145 / 135 us (2 / 4 MiB) at 21
#: pages a slot, the same from 32 pages a slot up, and the Cerebras cell
#: the same within 1% at 2 and 4 MiB (PERF.md section 6, PR 30): a block
#: has to be long enough for its DMA to hide its arithmetic and the
#: scalar work of issuing it, and past that only lengthens the tail.
_PAGED_TILE_BYTES = 4 << 20


def paged_block_pages(block_size, heads, head_dim, dtype, table_width):
    """P, the pages of one compute block of the paged kernel: as many as
    the budget holds of K and V tiles, twice each, and at most the table's
    width; 1 where a single page is already over it."""
    page = block_size * heads * head_dim * jnp.dtype(dtype).itemsize
    return int(max(1, min(_PAGED_TILE_BYTES // (4 * page), table_width)))


def _walk_compiler_params():
    """Mosaic's options for a kernel over `_paged_walk`: no bounds check
    on a dynamic access. Mosaic guards every `make_async_copy(...)
    .start()` with two checks, the source inside its HBM array and the
    destination inside its VMEM array: a dozen scalar bundles of the
    seventeen a start is scheduled in, each waiting on the one before
    (compiled for a described v5e at Nemotron's shape: 10,456 bundles a
    call with them, 7,370 without), and the core issues them from the
    instruction stream that drives its arithmetic: a block of 64 small
    pages was bound by them, not by its bytes (PERF.md section 6, PR 59:
    3.93 us a block with the checks, 2.46-2.51 without, 2.56 of bytes
    were it full). What takes their place:

    - a copy's SOURCE is the pool's page (or row) a table entry names,
      and the table is the caller's: every wrapper hands the kernel its
      ids through `_pool_ids`, clipped into the pool outside the kernel
      (one elementwise XLA operation on the table a call, nothing a
      page). A stale or foreign id reads a page of ITS OWN pool,
      never another array;
    - a copy's DESTINATION is `buf.at[slot, j]`, slot 0 or 1 and j under
      the tile's pages by construction;
    - the option also lifts the check of every other dynamic index in
      these kernels, none of which a caller's value reaches: the table
      read `bt_ref[s, entry]` (`n_pages` holds it inside the table's
      width whatever a length says), `len_ref[s]`, `next_ref[s]`,
      `q_ref[s]`, `o_ref[s]` and the like with s a loop's counter under
      `s_n`, the tiles `buf.at[slot]`, and the sparse kernel's
      `sel_buf[s % 2, b]` with b under the slot's blocks."""
    return pltpu.CompilerParams(disable_bounds_checks=True)


def _pool_ids(ids, entries):
    """A table's ids as a kernel over `_paged_walk` takes them: int32
    and inside the pool's `entries` (pages, or rows for a table of row
    ids). The kernels run without Mosaic's bounds checks
    (`_walk_compiler_params`); this is what bounds a bad id's read to
    its own pool."""
    return jnp.clip(ids.astype(jnp.int32), 0, entries - 1)


def _paged_walk(bt_ref, len_ref, pools, bufs, sem, next_ref, *,
                block_size, block_pages, begin, block_fn, finish,
                source=None, first_page=None):
    """The walk the paged kernels share: every sequence, its live
    compute blocks only, the next block's page copies in flight while
    this one is scored. `pools` are the HBM pools and `bufs` their
    double-buffered VMEM tiles [2, block_pages, ...page]; `sem` is
    [len(pools), 2]: one DMA semaphore a pool and tile, which every
    page copy of a block signals. A block's copies are STARTED one a
    live page and pool (a page is wherever the table says) and WAITED
    for once a pool, by the bytes they bring (`wait_block`). What is
    computed on a block is the caller's:
    `begin(s)` -> (what the sequence's blocks share, the softmax state
    before its first block); `block_fn(shared, b, slot, ctx, state)` ->
    the state after block b, whose pages are in tile `slot`;
    `finish(s, state)` writes the sequence's output. `source(pool, id)`
    is what a table entry names in a pool, the page `pool.at[id]` unless
    said (a kernel that gathers single rows walks a table of row ids
    with `block_size` 1). `first_page(s)`: the table entry a sequence's
    walk starts at (a window layer's: the page of the oldest row the
    window reaches; block b of the walk is then the P entries from
    `first_page(s) + b P`), entry 0 unless said."""
    s_n = len_ref.shape[0]
    if source is None:
        def source(pool, page):
            return pool.at[page]

    def n_pages(s):
        # never past the table: a page id read beyond it would address
        # the pool with whatever SMEM holds there
        return jnp.minimum((len_ref[s] + block_size - 1) // block_size,
                           bt_ref.shape[1])

    def walked(s):
        """Pages of sequence s the walk covers, and the first of them."""
        if first_page is None:
            return n_pages(s), 0
        return n_pages(s) - first_page(s), first_page(s)

    def live_pages(s, b):
        """The live pages of block b of sequence s, at most a block's,
        and the table entry of the first."""
        pages, first = walked(s)
        return (jnp.clip(pages - b * block_pages, 0, block_pages),
                first + b * block_pages)

    def start_block(s, b, slot):
        """Start the copies of block b of sequence s into tile `slot`:
        one a pool and live page, wherever the table says it is, none
        for a page past the sequence's last."""
        live, entry = live_pages(s, b)
        for j in range(block_pages):
            @pl.when(j < live)
            def _():
                page = bt_ref[s, entry + j]
                for which, (pool, buf) in enumerate(zip(pools, bufs)):
                    pltpu.make_async_copy(
                        source(pool, page), buf.at[slot, j],
                        sem.at[which, slot]).start()

    def wait_block(s, b, slot):
        """Wait for what `start_block(s, b, slot)` started, by its
        BYTES: a DMA semaphore counts bytes, and a pool's and tile's one
        semaphore collects every page of the block, so a wait names no
        page. Its descriptor's only meaning is its size: one wait a pool
        of 2^k pages' bytes for every set bit of the block's live pages
        (a full block of 2^k pages: ONE wait a pool), at most log2 P + 1
        predicates where a wait a page asked P. The bytes waited for are
        the bytes started, exactly: more would wait for ever, fewer
        would score a tile before it has come."""
        live, _ = live_pages(s, b)
        for k in reversed(range(block_pages.bit_length())):
            n = 1 << k

            @pl.when(live & n != 0)
            def _():
                for which, buf in enumerate(bufs):
                    arrived = buf.at[slot, pl.ds(0, n)]
                    pltpu.make_async_copy(arrived, arrived,
                                          sem.at[which, slot]).wait()

    # the live sequence after each one (s_n: none), so that a sequence's
    # last block can start the first block of the next
    later = jnp.int32(s_n)
    for i in reversed(range(s_n)):
        next_ref[i] = later
        later = jnp.where(len_ref[i] > 0, jnp.int32(i), later)
    first = later

    # a partial block leaves the tile's other pages as they were: they are
    # masked out of the scores, and their value rows meet a probability of
    # 0, which only a finite row keeps at 0
    for buf in bufs:
        buf[...] = jnp.zeros_like(buf)

    @pl.when(first < s_n)
    def _():
        start_block(jnp.minimum(first, s_n - 1), 0, 0)

    def sequence(s, slot):
        ctx = len_ref[s]
        n_blocks = (walked(s)[0] + block_pages - 1) // block_pages
        shared, state0 = begin(s)

        def block(b, state):
            *inner, slot = state
            more = b + 1 < n_blocks
            ahead_s = jnp.where(more, s, next_ref[s])
            ahead_b = jnp.where(more, b + 1, 0)

            @pl.when(ahead_s < s_n)
            def _():
                start_block(jnp.minimum(ahead_s, s_n - 1), ahead_b,
                            1 - slot)

            wait_block(s, b, slot)
            return (*block_fn(shared, b, slot, ctx, tuple(inner)),
                    1 - slot)

        *final, slot = jax.lax.fori_loop(0, n_blocks, block,
                                         (*state0, slot))
        finish(s, tuple(final))
        return slot

    jax.lax.fori_loop(0, s_n, sequence, jnp.int32(0))


def _paged_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, next_ref, *, scale, block_size,
                  block_pages):
    """The whole call of per-head K and V pools, q_ref [S, H, D]: every
    head scores its own K rows."""
    h, d = q_ref.shape[-2:]
    tokens = block_pages * block_size

    def begin(s):
        return q_ref[s].astype(jnp.float32), (          # [H, D]
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, d), jnp.float32))

    def block_fn(q, b, slot, ctx, state):
        m_prev, l_prev, acc = state
        # One query row per head against a block is a batched
        # mat-vec: Mosaic has no dot for an operand that is batch x
        # contracting and nothing else, and decode is bound by the
        # page read, not the arithmetic, so both products run on the
        # VPU in the pool's own [tokens, H, D] layout, in f32. The
        # scores stay [tokens, H, 1]: Mosaic also compiles them as
        # [tokens, H], lanes dense, and that form measured 3% slower
        # at the cells' shapes (the relayouts cost more than the
        # thinner softmax saves; PERF.md section 6, PR 30).
        k = k_buf[slot].astype(jnp.float32).reshape(tokens, h, d)
        sc = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        kpos = b * tokens + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 0)
        sc = jnp.where(kpos < ctx, sc, DEFAULT_MASK_VALUE)
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=0))      # [H, 1]
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(sc - m_next[None])                  # [tokens, H, 1]
        v = v_buf[slot].astype(jnp.float32).reshape(tokens, h, d)
        return (m_next, l_prev * alpha + jnp.sum(p, axis=0),
                acc * alpha + jnp.sum(p * v, axis=0))

    def finish(s, state):
        _, l, acc = state
        # an inactive slot walks no block: acc and l are 0, the row zeros
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    _paged_walk(bt_ref, len_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem,
                next_ref, block_size=block_size, block_pages=block_pages,
                begin=begin, block_fn=block_fn, finish=finish)


def _paged_group_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                        k_buf, v_buf, sem, next_ref, *, scale, block_size,
                        block_pages, window, mxu_dtype, lam_ref=None):
    """The whole call of K and V pools that GROUPS of query heads share
    (q_ref [S, H, D], pools [.., H_kv, D], query head j reading K/V head
    j // (H / H_kv)), over every live row or, with `window`, over a
    slot's newest `window` rows alone: the walk then starts at the page
    of the oldest of them, and that page's older rows are masked. Sixteen
    heads a K/V row are too many for the vector unit's mat-vecs (the
    kernel above): a block is `_sparse_block`'s one MXU product a K/V
    head, that head's H / H_kv query heads against its rows of the block
    alone, and ONE softmax update over the [H, rows] scores; what is
    masked is a ROW (past the slot's length, behind the window).

    Heads narrower than a lane tile, several side by side in each of a
    row's H_kv tiles (`_unpacked`, K/V head j in tile j // pack): nothing
    is cut out of a tile. q_ref arrives [S, H, 128] with each head's D
    numbers in ITS K/V head's lanes and zeros in the others, so a tile's
    product scores each of its H / H_kv query heads against its own K/V
    head alone; a "group" is then a TILE and the heads that read it, and
    the output is [S, H, 128], every head's row accumulated over whole
    tiles: the caller keeps the lanes of the head's own K/V head.

    Differential attention (`lam_ref`, `_paged_diff_kernel`): a row of
    the pools is its tiles side by side ([.., tiles x 128], `_lane_rows`),
    the rows of q_ref the four of a head pair a tile; what is written is
    `_diff_rows` of the heads' outputs, [S, H / 2, 128]."""
    s_n, h, d = q_ref.shape
    lanes = {} if lam_ref is None else dict(
        groups=k_buf.shape[-1] // d, rows_of=_lane_rows)
    tokens = block_pages * block_size
    at = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)

    def first_page(s):
        return jnp.maximum(len_ref[s] - window, 0) // block_size

    def begin(s):
        base = 0 if window is None else first_page(s) * block_size
        return (q_ref[s].astype(jnp.float32), base), (      # [H, D]
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, d), jnp.float32))

    def block_fn(shared, b, slot, ctx, state):
        q, base = shared
        pos = base + b * tokens + at                        # [1, rows]
        live = pos < ctx
        if window is not None:
            live = live & (pos >= ctx - window)
        return _sparse_block(q, k_buf.at[slot], v_buf.at[slot], live,
                             state, scale=scale, mxu_dtype=mxu_dtype,
                             **lanes)

    def finish(s, state):
        _, l, acc = state
        out = acc / jnp.where(l == 0.0, 1.0, l)
        if lam_ref is not None:
            out = _diff_rows(out, lam_ref[0], lanes["groups"])
        o_ref[s] = out.astype(o_ref.dtype)

    _paged_walk(bt_ref, len_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem,
                next_ref, block_size=block_size, block_pages=block_pages,
                begin=begin, block_fn=block_fn, finish=finish,
                first_page=None if window is None else first_page)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _paged_attention_pallas(q, k_pool, v_pool, block_tables, context_lens,
                            *, scale, interpret=False, window=None):
    # Jitted so that a model's layers, which all call it at one shape,
    # share one trace and one lowering of the kernel (24 lowerings added
    # 13 s to the Cerebras bundle's export; PERF.md section 6, PR 30).
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_attention_reference")
    s_n, h, d = q.shape
    bs, hk = k_pool.shape[1], k_pool.shape[2]
    pack = k_pool.shape[3] // d     # heads to a lane tile of the pool
    if pack > 1:
        # each head's numbers into the lanes of its K/V head, zeros in
        # the tile's other lanes (`_paged_group_kernel`)
        lanes = (jnp.arange(h) // (h // (hk * pack)) % pack)[:, None] \
            == jnp.arange(pack)[None]                       # [H, pack]
        q = (q[:, :, None, :] * lanes[None, :, :, None].astype(q.dtype)
             ).reshape(s_n, h, pack * d)
        d = pack * d
    plan = paged_decode_plan("kv", [(hk, d)], h, bs, k_pool.dtype,
                             block_tables.shape[1], window)
    block_pages = plan.pages_per_block
    if plan.kernel == "grouped":
        kernel = functools.partial(
            _paged_group_kernel, scale=scale, block_size=bs,
            block_pages=block_pages, window=window,
            mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    else:
        kernel = functools.partial(_paged_kernel, scale=scale,
                                   block_size=bs, block_pages=block_pages)
    whole = pl.BlockSpec(q.shape, lambda i, bt, ln: (0,) * q.ndim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole,
                  # the pools stay where they are: the kernel copies the
                  # live pages itself, by the scalar-prefetched table
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, hk, d), k_pool.dtype),
            pltpu.VMEM((2, block_pages, bs, hk, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # K / V x tile
            pltpu.SMEM((s_n,), jnp.int32),          # the next live sequence
        ],
    )
    # the scope is the kernel's name in a device trace: the program op's
    # own, which `paged_decode_roofline` reads by; a window layer's call
    # has a name of its own, so a trace tells the two kinds of layer apart
    with jax.named_scope("paged_attention" if window is None
                         else "paged_window_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(block_tables, k_pool.shape[0]),
          context_lens.astype(jnp.int32), q, k_pool, v_pool)
    if pack > 1:    # of a head's whole tile, its own K/V head's lanes
        out = jnp.sum(out.reshape(s_n, h, pack, d // pack)
                      * lanes[None, :, :, None].astype(out.dtype), axis=2)
    return out


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           *, scale: Optional[float] = None,
                           interpret: bool = False,
                           window: Optional[int] = None):
    """Public paged-decode entry: Pallas on TPU-friendly shapes (the
    pools' lane dim a multiple of 128, sublane of 8), gather-based XLA
    elsewhere. `window`: a slot reads its newest `window` rows alone
    (positions len - window .. len - 1), and no table entry behind them.
    Heads narrower than a lane tile take the kernel where the pools hold
    them packed into whole tiles (`_unpacked`; K/V heads that groups
    share: the grouped kernel)."""
    d = q.shape[-1]
    bs = k_pool.shape[1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and k_pool.shape[-1] % 128 == 0 \
            and bs % 8 == 0:
        return _paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                       context_lens, scale=scale,
                                       interpret=interpret, window=window)
    return paged_attention_reference(q, k_pool, v_pool, block_tables,
                                     context_lens, scale=scale,
                                     window=window)


# ---------------------------------------------------------------------------
# Paged differential attention (`ops.attention_ops`, the text above
# `diff_attention`): the pools' row is [H_kv D], H_kv / 2 tiles of 2 D
# lanes side by side, tile g holding K (or V) head g of the first set
# beside head g of the second (ten tiles as a second-minor axis would be
# padded to sixteen in the device's memory; side by side a tile is a
# slice at whole lane tiles, `_lane_rows`). A tile is
# read by the heads of BOTH sets that pair on it: q arrives [S, H, 2 D] in
# tile order (`_diff_lanes`: a tile's first-set heads with their numbers
# in the first D lanes and zeros in the others, then its second-set heads
# in the last D lanes), so one product a tile scores every one of them
# against its own set's K head alone, and one product by the whole V tile
# gives each its [v1 | v2] output: the walk, the block and the softmax are
# `_paged_group_kernel`'s, and every K and V row is read ONCE a call. At a
# sequence's end the second set's outputs are subtracted from the first's
# (`_diff_rows`); the sub-norm follows in XLA.
# ---------------------------------------------------------------------------

def _diff_lanes(q, pairs):
    """q [S, H, D] (the first set's H / 2 heads, then the second's) ->
    [S, H, 2 D] in tile order: of tile g the first set's heads in the
    first D lanes, then the second set's in the last D."""
    s_n, h, d = q.shape
    per = h // (2 * pairs)
    sets = jnp.swapaxes(q.reshape(s_n, 2, pairs, per, d), 1, 2)
    lanes = jnp.eye(2, dtype=q.dtype)[None, None, :, None, :, None]
    return (sets[:, :, :, :, None, :] * lanes).reshape(s_n, h, 2 * d)


def _diff_rows(out, lam, pairs):
    """The heads' outputs in tile order [H, 2 D] -> A1 - lam A2 in the
    heads' own order [H / 2, 2 D], as ONE small product with a matrix of
    ones and -lam (a gather of rows two at a time is no Mosaic
    operation; float32 whole: the difference of two near-equal rows)."""
    h = out.shape[0]
    per = h // (2 * pairs)
    row = jax.lax.broadcasted_iota(jnp.int32, (h // 2, h), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (h // 2, h), 1)
    first = row // per * (2 * per) + row % per
    pick = jnp.where(col == first, 1.0,
                     jnp.where(col == first + per, -lam, 0.0))
    return jax.lax.dot_general(
        pick.astype(jnp.float32), out.astype(jnp.float32),
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def paged_diff_attention_reference(q, k_pool, v_pool, block_tables,
                                   context_lens, lam, *, scale: float,
                                   window: Optional[int] = None):
    """Gather-based XLA form (CPU path + oracle): [S, H / 2, 2 D]."""
    s_n, h, d = q.shape
    pairs = k_pool.shape[2] // (2 * d)
    tiled = k_pool.shape[:2] + (pairs, 2 * d)
    out = paged_attention_reference(
        _diff_lanes(q, pairs), k_pool.reshape(tiled), v_pool.reshape(tiled),
        block_tables, context_lens, scale=scale,
        window=window).astype(jnp.float32)
    out = out.reshape(s_n, pairs, 2, h // (2 * pairs), 2 * d)
    return (out[:, :, 0] - lam * out[:, :, 1]).reshape(s_n, h // 2, 2 * d)


def _paged_diff_kernel(bt_ref, len_ref, q_ref, lam_ref, *refs, **static):
    """`_paged_group_kernel` with `lam` (SMEM, [1]) among its inputs."""
    _paged_group_kernel(bt_ref, len_ref, q_ref, *refs, lam_ref=lam_ref,
                        **static)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def _paged_diff_attention_pallas(q, lam, k_pool, v_pool, block_tables,
                                 context_lens, *, scale, interpret=False,
                                 window=None):
    # jitted for the reason `_paged_attention_pallas` is
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_diff_attention_reference")
    s_n, h, d = q.shape             # in tile order, d the tile's lanes
    bs, row = k_pool.shape[1], k_pool.shape[2]
    pairs = row // d
    plan = paged_decode_plan("kv_diff", [(row,)], h, bs, k_pool.dtype,
                             block_tables.shape[1], window)
    # one record in the trace ring each time the wrapper is traced, as
    # the flash wrappers leave `kernel/flash_plan`
    obs_trace.phase("kernel", "paged_plan", 0.0, attrs=dict(
        plan._asdict(), slots=s_n, heads=h, tiles=pairs, tile_lanes=d,
        block_size=bs, table_width=int(block_tables.shape[1]),
        window=window, dtype=jnp.dtype(k_pool.dtype).name))
    block_pages = plan.pages_per_block
    kernel = functools.partial(
        _paged_diff_kernel, scale=scale, block_size=bs,
        block_pages=block_pages, window=window,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    whole = pl.BlockSpec(q.shape, lambda i, bt, ln: (0, 0, 0))
    out_shape = (s_n, h // 2, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole,
                  pl.BlockSpec(memory_space=pltpu.SMEM),     # lam
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(out_shape, lambda i, bt, ln: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, row), k_pool.dtype),
            pltpu.VMEM((2, block_pages, bs, row), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # K / V x tile
            pltpu.SMEM((s_n,), jnp.int32),          # the next live sequence
        ],
    )
    # the kernel's names in a device trace, which `paged_diff_roofline`
    # and `paged_diff_window_roofline` read by
    with jax.named_scope("paged_diff_attention" if window is None
                         else "paged_diff_window_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(block_tables, k_pool.shape[0]),
          context_lens.astype(jnp.int32), q,
          jnp.reshape(lam, (1,)).astype(jnp.float32), k_pool, v_pool)


def paged_diff_attention(q, k_pool, v_pool, block_tables, context_lens,
                         lam, *, scale: Optional[float] = None,
                         interpret: bool = False,
                         window: Optional[int] = None):
    """Differential attention of one query token a slot, A1 - lam A2
    [S, H / 2, 2 D] float32 (the text above): q [S, H, D], pools [NB, BS,
    H_kv D], `lam` a scalar. Pallas on a TPU where a tile (2 D) is a lane
    tile, gather-based XLA elsewhere. `window`: a slot reads its newest
    `window` rows alone."""
    d = q.shape[-1]
    bs, pairs = k_pool.shape[1], k_pool.shape[2] // (2 * d)
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and 2 * d == 128 and bs % 8 == 0:
        return _paged_diff_attention_pallas(
            _diff_lanes(q, pairs), lam, k_pool, v_pool, block_tables,
            context_lens, scale=scale, interpret=interpret, window=window)
    return paged_diff_attention_reference(
        q, k_pool, v_pool, block_tables, context_lens, lam, scale=scale,
        window=window)


def _new_row_index(block_size, block_tables, context_lens):
    """(block, offset) of each sequence's newest row: position
    context_len-1, block block_tables[s, pos // bs], offset pos % bs;
    inactive slots (context_len 0) land in null block 0."""
    lens = jnp.asarray(context_lens).astype(jnp.int32)
    pos = jnp.maximum(lens - 1, 0)
    blk = jnp.take_along_axis(block_tables.astype(jnp.int32),
                              (pos // block_size)[:, None], axis=1)[:, 0]
    return jnp.where(lens > 0, blk, 0), pos % block_size


def paged_kv_update(k_pool, v_pool, k_new, v_new, block_tables,
                    context_lens):
    """Write one new K/V row per sequence into its page
    (`_new_row_index`). Inactive slots write harmlessly into null block
    0. Returns the updated (k_pool, v_pool)."""
    k_pool = jnp.asarray(k_pool)
    v_pool = jnp.asarray(v_pool)
    blk, off = _new_row_index(k_pool.shape[1], block_tables, context_lens)
    # a row as the pool stores it (heads packed into lane tiles or not)
    row = (k_new.shape[0],) + k_pool.shape[2:]
    k_pool = k_pool.at[blk, off].set(k_new.astype(k_pool.dtype).reshape(row))
    v_pool = v_pool.at[blk, off].set(v_new.astype(v_pool.dtype).reshape(row))
    return k_pool, v_pool


# ---------------------------------------------------------------------------
# Paged decode over a LATENT pool (multi-head latent attention, absorbed)
#
# A latent cache holds ONE row a token and layer, [c | k_rope], shared by
# every head: the query of head h has been multiplied through that head's
# key up-projection already (`q' = q_nope Wk_h^T`), so its score against a
# token is `([q'_h | q_rope_h] . row) * scale` and its value is `P_h c`,
# the row's first `value_width` columns; the head's value up-projection
# comes after the kernel. All H heads read the same rows, so a block's
# scores are one real [H, W] x [W, tokens] product and its values one
# [H, tokens] x [tokens, value_width]: both on the MXU, where the per-head
# kernel above has nothing but mat-vecs. The walk over the live pages is
# that kernel's (`_paged_walk`).
#
# Layout: q [S, H, W], pool [NB, BS, W], out [S, H, value_width]. W is the
# pool's row as the bundle declares it: a multiple of the 128 lanes (576 of
# latent and rotary key are stored in 640; the padding columns are zeros
# in q and pool alike and are counted as the cache's bytes).
# ---------------------------------------------------------------------------

def paged_latent_attention_reference(q, pool, block_tables, context_lens,
                                     *, value_width: int, scale: float):
    """Gather-based XLA form (CPU path + oracle)."""
    s_n = q.shape[0]
    bs, w = pool.shape[1], pool.shape[2]
    mb = block_tables.shape[1]
    rows = jnp.take(pool, block_tables.reshape(-1).astype(jnp.int32),
                    axis=0).reshape(s_n, mb * bs, w).astype(jnp.float32)
    s = jnp.einsum("shw,skw->shk", q.astype(jnp.float32), rows,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, :]
    mask = kpos < context_lens.astype(jnp.int32)[:, None, None]
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1.0)
    out = jnp.einsum("shk,skv->shv", p, rows[..., :value_width])
    return out.astype(q.dtype)


def _whole_lane_tiles(pages, page_columns):
    """`pages` rounded down to whole 128-lane tiles of score columns, a
    page `page_columns` of them, where a block is that long."""
    lane_pages = max(1, 128 // page_columns)
    return pages - pages % lane_pages if pages >= lane_pages else pages


def paged_latent_block_pages(block_size, row_width, dtype, table_width):
    """P of the latent kernel: `paged_block_pages` of the page's bytes
    (one pool, so half the tile budget is used), rounded down to whole
    lane tiles of tokens where a block is that long: the scores are
    [H, P x block_size] with the tokens on the lanes."""
    return _whole_lane_tiles(
        paged_block_pages(block_size, 1, row_width, dtype, table_width),
        block_size)


def _paged_latent_kernel(bt_ref, len_ref, q_ref, pool_hbm, o_ref, buf, sem,
                         next_ref, *, scale, block_size, block_pages,
                         value_width, mxu_dtype):
    _, h, w = q_ref.shape
    tokens = block_pages * block_size

    def begin(s):
        return q_ref[s].astype(mxu_dtype), (                 # [H, W]
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, value_width), jnp.float32))

    def block_fn(q, b, slot, ctx, state):
        m_prev, l_prev, acc = state
        rows = buf[slot].reshape(tokens, w).astype(mxu_dtype)
        sc = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [H, tokens]
        kpos = b * tokens + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(kpos < ctx, sc, DEFAULT_MASK_VALUE)
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(sc - m_next)                            # [H, tokens]
        return (m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + jax.lax.dot_general(
                    p.astype(mxu_dtype), rows[:, :value_width],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    def finish(s, state):
        _, l, acc = state
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    _paged_walk(bt_ref, len_ref, (pool_hbm,), (buf,), sem, next_ref,
                block_size=block_size, block_pages=block_pages,
                begin=begin, block_fn=block_fn, finish=finish)


@functools.partial(jax.jit,
                   static_argnames=("value_width", "scale", "interpret"))
def _paged_latent_attention_pallas(q, pool, block_tables, context_lens, *,
                                   value_width, scale, interpret=False):
    # jitted for the reason `_paged_attention_pallas` is: one trace and
    # one lowering for all of a model's layers
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_latent_attention_reference")
    s_n, h, w = q.shape
    bs = pool.shape[1]
    block_pages = paged_decode_plan(
        "latent", [(w,)], h, bs, pool.dtype,
        block_tables.shape[1]).pages_per_block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec((s_n, h, w), lambda i, bt, ln: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((s_n, h, value_width),
                               lambda i, bt, ln: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, w), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),        # the pool x tile
            pltpu.SMEM((s_n,), jnp.int32),          # the next live sequence
        ],
    )
    # On the chip the two products take their operands in bfloat16, f32
    # accumulated: what an f32 matmul at XLA's default precision does
    # with every other weight of the step. Interpreted (the CPU's tests)
    # f32 stays f32.
    kernel = functools.partial(
        _paged_latent_kernel, scale=scale, block_size=bs,
        block_pages=block_pages, value_width=value_width,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    # the scope is the kernel's name in a device trace, which
    # `paged_latent_roofline` reads by
    with jax.named_scope("paged_latent_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, h, value_width), q.dtype),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(block_tables, pool.shape[0]),
          context_lens.astype(jnp.int32), q, pool)


def paged_latent_decode_attention(q, pool, block_tables, context_lens, *,
                                  value_width: int, scale: float,
                                  interpret: bool = False):
    """Public latent paged-decode entry: Pallas on a TPU where the row
    and the value are whole lane tiles, gather-based XLA elsewhere."""
    w, bs = q.shape[-1], pool.shape[1]
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and w % 128 == 0 \
            and value_width % 128 == 0 and bs % 8 == 0:
        return _paged_latent_attention_pallas(
            q, pool, block_tables, context_lens, value_width=value_width,
            scale=scale, interpret=interpret)
    return paged_latent_attention_reference(
        q, pool, block_tables, context_lens, value_width=value_width,
        scale=scale)


def paged_row_update(pool, row_new, block_tables, context_lens):
    """`paged_kv_update` for a pool of one row a token ([NB, BS, W]). A
    pool whose row is [1, W] (a row a copy: the text above
    `paged_sparse_latent_attention`) takes its rows one
    `dynamic_update_slice` a slot, in place: XLA's scatter turns such a
    pool into the [NB, BS, W] tiling and back, two copies of the WHOLE
    pool a layer and step (7 ms of a 29 ms step on the chip, PR 65)."""
    pool = jnp.asarray(pool)
    blk, off = _new_row_index(pool.shape[1], block_tables, context_lens)
    row_new = row_new.astype(pool.dtype)
    if pool.ndim == 3:
        return pool.at[blk, off].set(row_new)
    zeros = (0,) * (pool.ndim - 2)
    for s in range(row_new.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, row_new[s][None, None], (blk[s], off[s]) + zeros)
    return pool


# ---------------------------------------------------------------------------
# Sparse paged decode (DeepSeek Sparse Attention's indexer over a paged
# cache): a layer keeps, beside K and V, one INDEX KEY a token; a decode
# step scores every live token of a slot with the indexer,
#
#   I[slot, s] = sum_j w[slot, j] * relu(qI[slot, j] . kI[s]),
#
# keeps the `topk` highest (all of them while the slot holds no more; of
# equal scores the lower position), and runs the attention's softmax over
# those rows alone. Three device parts, each under a scope of its name:
# `paged_index_scores` (a Pallas kernel over `_paged_walk`: the index
# pool's live pages, one [heads, W] x [W, tokens] product a block),
# `sparse_select` (XLA: `top_k`, then positions to pool rows through the
# block table, and the same set as a mask over positions) and
# `paged_sparse_attention` (a Pallas kernel over `_paged_walk` that reaches
# a slot's selected rows of K and V one of two ways, chosen a slot from the
# step's lengths, `sparse_walks_pages`: the slot's live pages copied whole
# with the selection as a mask where the selection is dense in the slot,
# one 32 KB copy a page and pool; the selected rows one 2 KB copy each, by
# the scalar-prefetched row ids, where it is sparse. The scalar core starts
# a copy in about 10 ns whatever its size (`_walk_compiler_params`), so a
# row copy moves 200 GB/s and a page copy is bound by the HBM).
#
# Layout: qI [S, Hi, W], w [S, Hi], index pool [NB, BS, W] (W the pool's
# row: the index key's width in whole 128-lane tiles, zeros past it in qI
# and pool alike); q [S, H, D], K and V pools [NB, BS, H_kv, D], query
# head j reading K/V head j // (H / H_kv).
# ---------------------------------------------------------------------------

def paged_index_scores_reference(q_index, weights, pool, block_tables,
                                 context_lens):
    """Gather-based XLA form (CPU path + oracle): [S, MB * BS] float32,
    -inf at and past each slot's length."""
    s_n = q_index.shape[0]
    bs, w = pool.shape[1], pool.shape[2]
    mb = block_tables.shape[1]
    rows = jnp.take(pool, block_tables.reshape(-1).astype(jnp.int32),
                    axis=0).reshape(s_n, mb * bs, w).astype(jnp.float32)
    dots = jnp.einsum("shw,skw->shk", q_index.astype(jnp.float32), rows,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(weights.astype(jnp.float32)[..., None]
                     * jnp.maximum(dots, 0.0), axis=1)
    kpos = jnp.arange(mb * bs, dtype=jnp.int32)[None]
    return jnp.where(kpos < context_lens.astype(jnp.int32)[:, None],
                     scores, -jnp.inf)


def _paged_index_kernel(bt_ref, len_ref, q_ref, w_ref, pool_hbm, o_ref, buf,
                        sem, next_ref, *, block_size, block_pages):
    # float32 operands, whole: an index score decides whether a row is
    # read at all (`ops/attention_ops.py` `_CHOOSING`), and the product
    # is 16 heads of 128 columns a block, nothing beside the page copies
    tokens = block_pages * block_size
    # what no live block covers reads as "not there"
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, o_ref.dtype)

    def begin(s):
        return (s, q_ref[s].astype(jnp.float32),             # [Hi, W]
                w_ref[s].astype(jnp.float32)), ()            # [Hi, 1]

    def block_fn(shared, b, slot, ctx, state):
        s, q, w = shared
        rows = buf[slot].reshape(tokens, buf.shape[-1]).astype(jnp.float32)
        dots = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)             # [Hi, tokens]
        score = jnp.sum(w * jnp.maximum(dots, 0.0), axis=0, keepdims=True)
        kpos = b * tokens + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 1)
        o_ref[s, b] = jnp.where(kpos < ctx, score, -jnp.inf)
        return ()

    _paged_walk(bt_ref, len_ref, (pool_hbm,), (buf,), sem, next_ref,
                block_size=block_size, block_pages=block_pages,
                begin=begin, block_fn=block_fn,
                finish=lambda s, state: None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_index_scores_pallas(q_index, weights, pool, block_tables,
                               context_lens, *, interpret=False):
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_index_scores_reference")
    s_n, hi, w = q_index.shape
    bs = pool.shape[1]
    mb = block_tables.shape[1]
    block_pages = paged_decode_plan(
        "kv_index", [(w,)], hi, bs, pool.dtype, mb).pages_per_block
    tokens = block_pages * bs
    n_blocks = -(-mb // block_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec((s_n, hi, w), lambda i, bt, ln: (0, 0, 0)),
                  pl.BlockSpec((s_n, hi, 1), lambda i, bt, ln: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        # a slot's and block's scores are one row of lanes: the two
        # leading axes are addressed by number, never sliced
        out_specs=pl.BlockSpec((s_n, n_blocks, 1, tokens),
                               lambda i, bt, ln: (0, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, w), pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((s_n,), jnp.int32),
        ],
    )
    kernel = functools.partial(_paged_index_kernel, block_size=bs,
                               block_pages=block_pages)
    with jax.named_scope("paged_index_scores"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, n_blocks, 1, tokens),
                                           jnp.float32),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(block_tables, pool.shape[0]),
          context_lens.astype(jnp.int32), q_index, weights[..., None], pool)
    return out.reshape(s_n, n_blocks * tokens)[:, :mb * bs]


def paged_index_scores(q_index, weights, pool, block_tables, context_lens,
                       *, interpret: bool = False):
    """The indexer's scores of every slot's live tokens, [S, MB * BS]
    float32 with -inf at and past each slot's length: Pallas on a TPU
    where the pool's row is whole lane tiles, gather-based XLA
    elsewhere."""
    w, bs = q_index.shape[-1], pool.shape[1]
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and w % 128 == 0 and bs % 8 == 0:
        return _paged_index_scores_pallas(q_index, weights, pool,
                                          block_tables, context_lens,
                                          interpret=interpret)
    return paged_index_scores_reference(q_index, weights, pool,
                                        block_tables, context_lens)


def sparse_select(scores, block_tables, context_lens, *, topk: int,
                  block_size: int):
    """The rows a sparse decode step attends to. scores [S, T] (-inf
    where there is no token). Returns (positions [S, topk] int32, the
    `topk` highest-scored of each slot, of equal scores the lower
    position first, -1 behind the slot's count; their rows in a pool
    seen as [NB * BS, ...], int32; counts [S] = min(length, topk);
    selected [S, T] bool, the same set of positions as a mask)."""
    with jax.named_scope("sparse_select"):
        lens = context_lens.astype(jnp.int32)
        width = scores.shape[1]
        # one zero: `top_k` puts 0.0 before -0.0 (a weighted sum of
        # relus is either), and the mask below compares them equal
        scores = jnp.where(scores == 0.0, 0.0, scores)
        top, pos = jax.lax.top_k(scores, min(topk, width))
        counts = jnp.minimum(lens, topk)
        # The set again, as a mask, from its last member: the scores
        # come out in descending order and of equal ones the lower
        # position first, so a position is in the set where it scores
        # over the last member, or the same from no later a position.
        # No scatter and no second sort.
        last = jnp.clip(counts - 1, 0, top.shape[1] - 1)[:, None]
        kth = jnp.take_along_axis(top, last, axis=1)
        kth_pos = jnp.take_along_axis(pos, last, axis=1).astype(jnp.int32)
        at = jnp.arange(width, dtype=jnp.int32)[None]
        selected = ((scores > kth) | ((scores == kth) & (at <= kth_pos))) \
            & (at < lens[:, None])
        # a table narrower than topk: the columns behind it are never live
        pos = jnp.pad(pos.astype(jnp.int32),
                      ((0, 0), (0, max(topk - width, 0))))
        live = jnp.arange(topk, dtype=jnp.int32)[None] < counts[:, None]
        blocks = jnp.take_along_axis(block_tables.astype(jnp.int32),
                                     pos // block_size, axis=1)
        rows = jnp.where(live, blocks * block_size + pos % block_size, 0)
        return jnp.where(live, pos, -1), rows, counts, selected


def paged_sparse_attention_reference(q, k_pool, v_pool, rows, counts, *,
                                     scale: Optional[float] = None):
    """Gather-based XLA form (CPU path + oracle): softmax over the first
    counts[s] of rows[s] alone."""
    s_n, h, d = q.shape
    nb, bs, hk, _ = k_pool.shape
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    flat = rows.astype(jnp.int32)
    k = jnp.take(k_pool.reshape(nb * bs, hk, d), flat, axis=0)
    v = jnp.take(v_pool.reshape(nb * bs, hk, d), flat, axis=0)
    k = jnp.repeat(k, h // hk, axis=2).astype(jnp.float32)  # [S, K, H, D]
    v = jnp.repeat(v, h // hk, axis=2).astype(jnp.float32)
    s = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32), k,
                   preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None]
            < counts.astype(jnp.int32)[:, None, None])
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1.0)
    return jnp.einsum("shk,skhd->shd", p, v).astype(q.dtype)


#: selected rows a compute block of the sparse kernel's ROW walk copies
#: and scores
_SPARSE_CHUNK_ROWS = 128

#: kappa, the row copies a whole page costs the sparse kernel: a slot's
#: live pages are read whole, the selection a mask, where they number
#: `kappa` times fewer than its selected rows (`sparse_walks_pages`).
#: Measured on the v5e at the Keye cell's shape (16 slots, 32 heads over
#: 4 of 128, f32 pages of 16 rows, top-2,048; `tools/sparse_walk_sweep.py`;
#: PERF.md section 6, PR 59): a page 0.0867-0.0872 us of a call (64 KB
#: of K and V: its bytes at 92% of the HBM's rate; a block of 32 pages
#: takes 2.75 us where its arithmetic alone takes 1.3), a row
#: 0.0206-0.0211 us (two starts of 2 KB, no bounds check, and a wait a
#: pool for 2^k rows' bytes, not a row): 4.13-4.21 over three sweeps.
#: The walks cross at 7.8 k rows a slot. THE KEYE CELL'S longest slots
#: (7,680 rows: 480 pages against its top-2,048) go over to their rows
#: at a kappa above 2,048 / 480 = 4.267, 1.6% over this one: a
#: re-measurement that passes it moves the cell's walk
#: (`tests/test_keye.py::test_kappa_leaves_the_keye_cell_its_page_walk`
#: then fails, so that it is seen). It was 1.58 (a row 0.0550 us, four
#: DMA operations each behind its bounds checks, PR 44) and 2.1 before
#: (a page 0.118 us, PR 34, while a block scored every head against
#: every K/V head's rows and masked).
_SPARSE_PAGE_ROW_COPIES = 4.2


def sparse_walks_pages(context_lens, *, topk: int, block_size: int):
    """Which slots the sparse kernel serves by its PAGE walk, [S] bool,
    from the step's lengths alone (a numpy array on the host, or a
    traced one): those whose live pages, at `kappa` row copies a page,
    cost no more than their min(length, topk) selected rows one by one.
    A slot that holds no more than topk rows does from a few rows up; an
    empty slot takes neither walk."""
    cost = -(-context_lens // block_size) * _SPARSE_PAGE_ROW_COPIES
    return (context_lens > 0) & (cost <= context_lens) & (cost <= topk)


def paged_sparse_block_pages(block_size, kv_heads, head_dim, dtype,
                             table_width):
    """P of the sparse kernel's page walk and of the paged kernel of
    shared K/V heads: `paged_block_pages`, in whole lane tiles of score
    columns (a block's rows, `block_size` a page) where a block is that
    long."""
    return _whole_lane_tiles(
        paged_block_pages(block_size, kv_heads, head_dim, dtype,
                          table_width), block_size)


def group_block_shape(n_heads, kv_heads, pages, block_size):
    """What `describe()` says of a compute block of the kernels of shared
    K/V heads (`_sparse_block`): the query heads one product scores (H /
    H_kv, `kv_heads` the K/V heads or packed tiles a pool's row holds)
    and the score columns of a block (its rows, once: P x block_size)."""
    return {"heads_per_product": n_heads // kv_heads,
            "score_columns_per_block": pages * block_size}


def sparse_kernel_walks(block_size, kv_heads, head_dim, dtype, table_width):
    """What `describe()` says of the sparse kernel at a bundle's shapes:
    `kappa` of the rule that chooses a slot's walk, P of the page walk
    and the rows of a block of the row walk."""
    return {"kappa": _SPARSE_PAGE_ROW_COPIES,
            "pages_per_block": paged_sparse_block_pages(
                block_size, kv_heads, head_dim, dtype, table_width),
            "chunk_rows": _SPARSE_CHUNK_ROWS}


def _indexed_rows(tile, g):
    """K/V head `g`'s rows of a VMEM tile [.., H_kv, D] by an index on
    the K/V head's axis: Mosaic reads every row's sublane and packs
    them (on the v5e the whole call is then slower than one masked
    product over all heads, `tools/paged_group_sweep.py --reads`)."""
    return tile[..., g, :].reshape(-1, tile.shape[-1])


def _group_rows(tile, g):
    """K/V head `g`'s rows of a VMEM tile [.., H_kv, D] whose leading
    axes are the block's rows, as [rows, D]: a strided read of the tile
    seen as [rows x H_kv, D] (every H_kv-th sublane from the g-th on:
    at the rate of a dense read on the v5e, `tools/paged_group_sweep.py`).
    No copy of the tile is cut or turned for it, and the page copies
    fill it as the pool stores it. Mosaic's strided load is of 32-bit
    rows: a narrower tile takes `_indexed_rows`."""
    if jnp.dtype(tile.dtype).itemsize != 4:
        return _indexed_rows(tile, g)
    *lead, hk, d = tile.shape
    rows = math.prod(lead)
    return tile.reshape(rows * hk, d)[pl.ds(g, rows, stride=hk), :]


def _lane_rows(tile, g, lanes=128):
    """Tile g of a VMEM tile whose rows are several lane tiles side by
    side ([.., tiles x lanes], a differential layer's K or V row), as
    [rows, lanes]: a slice at whole lane tiles, no copy."""
    rows = math.prod(tile.shape[:-1])
    return tile.reshape(rows, tile.shape[-1])[:, g * lanes:(g + 1) * lanes]


def _sparse_block(q, k_tile, v_tile, admitted, state, *, scale, mxu_dtype,
                  groups=None, rows_of=None):
    """A compute block of the kernels of shared K/V heads, any walk's:
    `k_tile` and `v_tile` are the block's VMEM tiles [.., H_kv, D], q is
    [H, D], K/V head g read by the H / H_kv query heads from g H / H_kv
    on. One product a K/V head scores that head's group against that
    head's rows alone (`_group_rows`), the groups' scores laid one under
    the other as ONE [H, rows] array: one online-softmax update, no score
    of a head against another group's rows is computed or masked.
    `admitted` [1, rows] says which ROWS count (every head reads the same
    rows of its own K/V head); a row not admitted has probability 0. The
    values the same way, a product a K/V head. `groups`, `rows_of`:
    where a tile's K/V heads are not its last axis but one, how many
    there are and how head g's rows are read (`_lane_rows`)."""
    m_prev, l_prev, acc = state
    if rows_of is None:
        groups, rows_of = k_tile.shape[-2], _group_rows
    per = q.shape[0] // groups
    heads = [slice(g * per, (g + 1) * per) for g in range(groups)]
    # the scores whole in float32: their error enters the softmax
    # multiplied by their own size (`ops/attention_ops.py` `_CHOOSING`);
    # the values below in `mxu_dtype`
    sc = jnp.concatenate([jax.lax.dot_general(
        q[mine], rows_of(k_tile, g).astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
        for g, mine in enumerate(heads)], axis=0) * scale   # [H, rows]
    sc = jnp.where(admitted, sc, DEFAULT_MASK_VALUE)
    m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.where(admitted, jnp.exp(sc - m_next), 0.0)      # [H, rows]
    pv = jnp.concatenate([jax.lax.dot_general(
        p[mine].astype(mxu_dtype), rows_of(v_tile, g).astype(mxu_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for g, mine in enumerate(heads)], axis=0)           # [H, D]
    return (m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
            acc * alpha + pv)


def _paged_sparse_kernel(tab_ref, len_ref, q_ref, *refs, scale, block_size,
                         by_pages, mxu_dtype):
    """The sparse attention of every slot, its rows reached one of two
    ways. The ROW walk (`by_pages` False): `tab_ref` [S, topk] holds
    the selected rows' ids and `len_ref` their counts; each row of K
    and of V is one copy from its pool, `_SPARSE_CHUNK_ROWS` of them a
    block. The PAGE walk: `tab_ref` is the block table and `len_ref`
    the lengths; a slot's live pages are copied whole, P a block, and
    `sel_hbm` [S, blocks, 1, rows] says which of a block's ROWS are
    selected (1) and which not (0): one value a row, whatever K/V heads
    it holds. A slot's part of it is copied while the slot before is
    walked. The arithmetic of a block is the same, `_sparse_block`: a
    product a K/V head over that head's rows alone. A slot whose
    `len_ref` is 0 walks no block and writes zeros: the slots of the
    other walk."""
    if by_pages:
        sel_hbm, *refs, sel_buf, sel_sem = refs
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, next_ref = refs
    s_n, h, d = q_ref.shape
    tokens = math.prod(k_buf.shape[1:-2])    # rows of a block
    at = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)

    def selection(s, act):
        """`act` the copy of slot s's selection, if it walks a block."""
        @pl.when(len_ref[s] > 0)
        def _():
            getattr(pltpu.make_async_copy(
                sel_hbm.at[s], sel_buf.at[s % 2], sel_sem.at[s % 2]), act)()

    def begin(s):
        if by_pages:    # every slot begins, in order: s + 1 is the next
            @pl.when(s == 0)
            def _():
                selection(s, "start")

            @pl.when(s + 1 < s_n)
            def _():
                selection(jnp.minimum(s + 1, s_n - 1), "start")

            selection(s, "wait")
        return (s, q_ref[s].astype(jnp.float32)), (          # [H, D]
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, d), jnp.float32))

    def block_fn(shared, b, slot, n, state):
        s, q = shared
        # the block's rows before the slot's count (of selected rows, or
        # of live ones), and of a page walk's the selected
        admitted = b * tokens + at < n                       # [1, rows]
        if by_pages:
            admitted = admitted & (sel_buf[s % 2, b] != 0)
        return _sparse_block(q, k_buf.at[slot], v_buf.at[slot], admitted,
                             state, scale=scale, mxu_dtype=mxu_dtype)

    def finish(s, state):
        _, l, acc = state
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    if by_pages:
        walk = dict(block_size=block_size, block_pages=k_buf.shape[1])
    else:
        walk = dict(block_size=1, block_pages=tokens,
                    source=lambda pool, row: pool.at[row // block_size,
                                                     row % block_size])
    _paged_walk(tab_ref, len_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem,
                next_ref, begin=begin, block_fn=block_fn, finish=finish,
                **walk)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_sparse_attention_pallas(q, k_pool, v_pool, table, lens,
                                   selected=None, *, scale,
                                   interpret=False):
    """One walk of the sparse kernel over all slots: the row walk of
    `table` = row ids and `lens` = counts, or with `selected` [S, T]
    the page walk of `table` = block table and `lens` = lengths."""
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_sparse_attention_reference")
    s_n, h, d = q.shape
    bs, hk = k_pool.shape[1], k_pool.shape[2]
    by_pages = selected is not None
    whole = pl.BlockSpec((s_n, h, d), lambda i, tb, ln: (0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, in_specs, scratch = [q], [whole], []
    if by_pages:
        pages = paged_sparse_block_pages(bs, hk, d, k_pool.dtype,
                                         table.shape[1])
        tile = (pages, bs, hk, d)
        n_blocks = -(-table.shape[1] // pages)
        rows = pages * bs
        # one value a row; a slot's and block's rows one row of lanes,
        # the leading axes addressed by number
        operands.append(jnp.pad(selected.astype(jnp.int32), ((0, 0), (
            0, n_blocks * rows - selected.shape[1]))
        ).reshape(s_n, n_blocks, 1, rows))
        in_specs.append(hbm)
        scratch = [pltpu.VMEM((2, n_blocks, 1, rows), jnp.int32),
                   pltpu.SemaphoreType.DMA((2,))]     # slot parity
    else:
        tile = (min(_SPARSE_CHUNK_ROWS, table.shape[1]), hk, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=in_specs + [hbm, hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2,) + tile, k_pool.dtype),
            pltpu.VMEM((2,) + tile, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # K / V x tile
            pltpu.SMEM((s_n,), jnp.int32),          # the next live slot
        ] + scratch,
    )
    kernel = functools.partial(
        _paged_sparse_kernel, scale=scale, block_size=bs, by_pages=by_pages,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    # both walks under the one name `paged_sparse_roofline` reads by
    with jax.named_scope("paged_sparse_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, h, d), q.dtype),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(table, k_pool.shape[0] * (1 if by_pages else bs)),
          lens.astype(jnp.int32), *operands, k_pool, v_pool)


def paged_sparse_attention(q, k_pool, v_pool, rows, counts, *,
                           pages=None, scale: Optional[float] = None,
                           interpret: bool = False):
    """Attention of one query a slot over `counts[s]` selected rows of
    the paged pools, `rows[s]` (ids into a pool seen as [NB * BS, H_kv,
    D]): Pallas on TPU-friendly shapes, gather-based XLA elsewhere.

    `pages` = (block_tables, context_lens, selected [S, T] bool), the
    same selection as `sparse_select` gives it beside `rows`, lets the
    kernel reach a slot's rows the cheaper way: its live pages whole
    with the selection as a mask where `sparse_walks_pages` says so
    (the selection is dense in the slot), the selected rows one by one
    otherwise. One softmax over one set of rows either way; the two
    walks are two calls over disjoint slots."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if not ((interpret or tpu) and _HAS_PLTPU and d % 128 == 0):
        return paged_sparse_attention_reference(q, k_pool, v_pool, rows,
                                                counts, scale=scale)
    call = functools.partial(_paged_sparse_attention_pallas, q, k_pool,
                             v_pool, scale=scale, interpret=interpret)
    if pages is None:
        return call(rows, counts)
    tables, lens, selected = pages
    lens = lens.astype(jnp.int32)
    by_pages = sparse_walks_pages(lens, topk=rows.shape[1],
                                  block_size=k_pool.shape[1])
    return jnp.where(
        by_pages[:, None, None],
        call(tables, jnp.where(by_pages, lens, 0), selected),
        call(rows, jnp.where(by_pages, 0, counts)))



# ---------------------------------------------------------------------------
# Sparse paged decode over a LATENT cache (DeepSeek sparse attention as
# DeepSeek-V3.2 and GLM-5 apply it): the step's indexer scores the index
# pool (`paged_index_scores`), `sparse_select` keeps the `topk` rows, and
# the absorbed attention of `_paged_latent_kernel` runs over those rows of
# the latent pool alone: ONE copy a selected row (2,560 B as stored at a
# rank of 512 and a rotary key of 64) serves every head, where the K/V
# form copies a row a pool for a group of heads.
#
# Layout: q [S, H, W] (a head's absorbed query [q_nope Wk^T | q_rope],
# zeros past rank + rope), pool [NB, BS, 1, W], rows [S, topk] int32 (ids
# into the pool seen as [NB * BS, W]), counts [S]. The pool's row is [1, W]
# and not [W]: the device tiles an array's last TWO dimensions, so a pool
# [NB, BS, W] lies in tiles of 8 rows x 128 lanes, a row's W floats are W /
# 128 pieces 4 KB apart, and Mosaic copies no single row of it ("slice ...
# must be aligned to tiling (8)"); under a row of [1, W] the tiles are 1 x
# 128, a row is W contiguous floats and one copy, and no byte is padding
# (held by `tests/test_chip_compile_glm5.py`). `paged_latent_decode_attention`,
# which copies pages whole, keeps [NB, BS, W].
#
# The ROW walk only, through `_paged_walk` over a table of row ids (block
# size 1): a slot whose selection is dense in its live rows (a context
# under about three times topk) would be served cheaper by its pages whole
# with the selection as a mask, as `_paged_sparse_kernel` does. Not built:
# where this kernel runs (contexts of 6-14 k against a topk of 2,048) the
# selection is a seventh to a third of the live rows, and the page walk's
# crossing point (`_SPARSE_PAGE_ROW_COPIES`) would have to be measured
# anew for one pool of 40 KB pages.
# ---------------------------------------------------------------------------

def paged_sparse_latent_attention_reference(q, pool, rows, counts, *,
                                            value_width: int, scale: float):
    """Gather-based XLA form (CPU path + oracle): softmax over the first
    counts[s] of rows[s] alone."""
    got = jnp.take(pool.reshape(-1, pool.shape[-1]),
                   rows.astype(jnp.int32),
                   axis=0).astype(jnp.float32)              # [S, K, W]
    s = jnp.einsum("shw,skw->shk", q.astype(jnp.float32), got,
                   preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None]
            < counts.astype(jnp.int32)[:, None, None])
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1.0)
    return jnp.einsum("shk,skv->shv", p,
                      got[..., :value_width]).astype(q.dtype)


def _paged_sparse_latent_kernel(row_ref, cnt_ref, q_ref, pool_hbm, o_ref,
                                buf, sem, next_ref, *, scale, block_size,
                                value_width, mxu_dtype):
    """Every slot's absorbed attention over its selected latent rows:
    `row_ref` [S, topk] holds their ids and `cnt_ref` their counts; a row
    is one copy from the pool, `buf.shape[1]` of them a block, scored
    against all H heads at once."""
    _, h, w = q_ref.shape
    tokens = buf.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)

    def begin(s):
        return q_ref[s].astype(jnp.float32), (               # [H, W]
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, value_width), jnp.float32))

    def block_fn(q, b, slot, n, state):
        m_prev, l_prev, acc = state
        rows = buf[slot].reshape(tokens, w)                  # [tokens, W]
        admitted = b * tokens + at < n                       # [1, tokens]
        # the scores whole in float32, as `_sparse_block`'s: their error
        # enters the softmax multiplied by their own size; the values in
        # `mxu_dtype`
        sc = jax.lax.dot_general(
            q, rows.astype(jnp.float32), (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) * scale      # [H, tokens]
        sc = jnp.where(admitted, sc, DEFAULT_MASK_VALUE)
        m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.where(admitted, jnp.exp(sc - m_next), 0.0)
        return (m_next, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + jax.lax.dot_general(
                    p.astype(mxu_dtype),
                    rows[:, :value_width].astype(mxu_dtype),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))

    def finish(s, state):
        _, l, acc = state
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    _paged_walk(row_ref, cnt_ref, (pool_hbm,), (buf,), sem, next_ref,
                block_size=1, block_pages=tokens, begin=begin,
                block_fn=block_fn, finish=finish,
                source=lambda pool, row: pool.at[row // block_size,
                                                 row % block_size])


@functools.partial(jax.jit,
                   static_argnames=("value_width", "scale", "interpret"))
def _paged_sparse_latent_attention_pallas(q, pool, rows, counts, *,
                                          value_width, scale,
                                          interpret=False):
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "paged_sparse_latent_attention_reference")
    s_n, h, w = q.shape
    nb, bs = pool.shape[:2]
    tile = min(_SPARSE_CHUNK_ROWS, rows.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[pl.BlockSpec((s_n, h, w), lambda i, tb, ln: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((s_n, h, value_width),
                               lambda i, tb, ln: (0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile) + pool.shape[2:], pool.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),        # the pool x tile
            pltpu.SMEM((s_n,), jnp.int32),          # the next live slot
        ],
    )
    kernel = functools.partial(
        _paged_sparse_latent_kernel, scale=scale, block_size=bs,
        value_width=value_width,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    # the scope is the kernel's name in a device trace, which
    # `paged_sparse_latent_roofline` reads by
    with jax.named_scope("paged_sparse_latent_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, h, value_width), q.dtype),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(_pool_ids(rows, nb * bs), counts.astype(jnp.int32), q, pool)


def paged_sparse_latent_attention(q, pool, rows, counts, *,
                                  value_width: int, scale: float,
                                  interpret: bool = False):
    """Absorbed latent attention of one query a slot, all heads, over
    `counts[s]` selected rows of the latent pool, `rows[s]` (ids into the
    pool seen as [NB * BS, W], what `sparse_select` returns): Pallas on a
    TPU where the row and the value are whole lane tiles, gather-based
    XLA elsewhere. Returns [S, H, value_width]: P applied to the rows'
    leading `value_width` columns, which the caller unfolds through the
    value half of Wkvb."""
    w = q.shape[-1]
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and w % 128 == 0 \
            and value_width % 128 == 0:
        return _paged_sparse_latent_attention_pallas(
            q, pool, rows, counts, value_width=value_width, scale=scale,
            interpret=interpret)
    return paged_sparse_latent_attention_reference(
        q, pool, rows, counts, value_width=value_width, scale=scale)
