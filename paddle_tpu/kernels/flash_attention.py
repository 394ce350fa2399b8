"""Flash attention: Pallas TPU kernel + XLA reference path.

The reference framework (2018 snapshot) has no attention op at all —
attention is composed from matmul/softmax layers (e.g. the dot-product
attention in python/paddle/fluid/nets.py and the seq2seq attention in
tests/book machine_translation). On TPU the composed form materializes the
[seq, seq] score matrix in HBM; this kernel keeps the score tiles in VMEM
with the online-softmax recurrence, which is what makes long-context
training feasible (HBM traffic O(S·d) instead of O(S²)).

Layout convention: q, k, v are [batch, seq, heads, head_dim] ("BSHD").

Forward is a Pallas kernel (grid over batch*heads × q-blocks × k-blocks,
f32 accumulators in VMEM scratch). Backward is a custom VJP recomputing
attention blockwise from the saved logsumexp — flash-attention-2 style —
with two Pallas kernels on TPU (dq over k-blocks; dk/dv over q-blocks;
score/probability tiles never leave VMEM — shipping the backward to
Pallas took the 8k-token config from 275 to 179 ms/step) and an XLA
chunked-scan fallback elsewhere (also the numerics oracle).

This module is the flash family alone: the decode kernels over a paged
cache are `paged_attention.py`, which takes `DEFAULT_MASK_VALUE` and the
guarded `pltpu` import from here; nothing here imports from there.

The compile cache: this file's line numbers are in the serialized module
of every program that holds one of its kernels, so ANY edit here (a blank
line on top is enough) makes those programs compile anew once, and no
other. Measured on the chip (PERF.md section 6, PR 46): the train cell's
`setup_s` 33.5 s warm, 32.8 s after a blank line on top of
`paged_attention.py`, 80.8 s after one on top of this file (the `run_loop`
program compiled again; what holds no flash kernel stayed cached), 31.7 s
the run after. The ledger's `first_setup_s` does not show it: the
driver's first run on a lease is cold whatever a PR edited.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..obs import trace as obs_trace

try:  # TPU backend of pallas; absent on some CPU-only wheels
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PLTPU = True
except Exception:  # pragma: no cover
    pltpu = None
    _HAS_PLTPU = False

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Reference (XLA) implementation — also the CPU path and the numerics oracle
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, bias=None, *, causal: bool = False,
                  scale: Optional[float] = None,
                  window: Optional[int] = None):
    """Plain attention. q,k,v: [B, S, H, D] (k/v may have S_kv != S_q,
    and fewer heads: query head j then reads K/V head j // (H / H_kv)).
    `window` (with `causal`): a row reads the keys at most window - 1
    positions before its own and no older one."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if k.shape[2] != q.shape[2]:
        k = jnp.repeat(k, q.shape[2] // k.shape[2], axis=2)
        v = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias.astype(s.dtype)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        seen = ki <= qi
        if window is not None:
            seen = seen & (ki > qi - window)
        s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas forward and backward kernels
#
# One grid step is one [block_q, block_k] block of the score matrix, and
# its body does what that block needs and nothing else. What that is is
# static, decided in ONE place, `flash_block_plan`, from what the wrappers
# can see (the shapes, the blocks, the inputs' dtype):
#   * the MXU's operands stay in the dtype they came in: bfloat16 inputs
#     are multiplied as bfloat16 with float32 accumulation (a bf16 x bf16
#     product is exact in float32), P and dS are cast to it for their
#     products as `mha_reference` casts P; anything else is multiplied in
#     float32 as before. Accumulators, the softmax state, `lse` and
#     `delta` are float32 either way;
#   * a block the causal diagonal crosses builds the mask, a block wholly
#     under it runs the same arithmetic without (two bodies under
#     `pl.when`), a block wholly above it runs nothing AND copies nothing:
#     the `index_map`s name the block already resident for it;
#   * a block a boundary crosses corner to corner (square blocks; the
#     causal diagonal of self-attention, a window's edge where the window
#     is whole blocks) runs in `strips` of its rows, each against the keys
#     it can see: (strips + 1) / (2 strips) of the block's products, cut
#     inside the grid step (`_strips` has the rule and where it was read);
#   * with a WINDOW (row t reads keys s with t - s < window; the
#     forward, and dq and dk/dv the same walk and its transpose) the band
#     has a second edge, and the grid's reduction axis IS the band:
#     `band_k` steps from a row's first block (`_first_k`; dk/dv's
#     `band_q` from `_first_q`), so that no step stands for a block
#     behind the window or above the diagonal but a short row's last
#     ones, which name the block already resident and run nothing; the
#     block the window's edge crosses masks it.
#     Without a window none of this is traced: the grid, the bodies and
#     the `index_map`s are what they were.
#   * with a SELECTION (the forward alone: `selected` int8 [B, Sq, Sk],
#     row t reads key s where it is not 0, and the selection holds s <= t)
#     a grid step takes its [block_q, block_k] tile of it beside K and V
#     (the same `index_map`, blind to the head: a skipped block copies
#     none) and the score tile is masked by it in VMEM: one body for
#     every block that runs, no iota mask and no halves on the diagonal;
#     float32 scores are three bfloat16 passes (`_scores_of_choice`),
#     P V as ever. Without one none of this is traced.
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))      # a b^T
_NN = (((1,), (0,)), ((), ()))      # a b


def _mxu(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _ahead(iq, ik, block_q, block_k, q_off):
    """Where the causal diagonal crosses block (iq, ik): key j of the
    block is visible to row i of it where j - i <= this (bottom-right
    alignment: row i sits at position i + q_off, as in `mha_reference`).
    Python integers or traced ones."""
    return iq * block_q + q_off - ik * block_k


def _block_runs(ahead, block_q):
    """Some key of the block is visible to some row of it."""
    return ahead > -block_q


def _block_crosses(ahead, block_k):
    """Some key of the block is hidden from some row of it: the diagonal
    crosses the block, and only then is a mask needed."""
    return ahead < block_k - 1


def _block_in_window(ahead, block_k, window):
    """Some key of the block is no older than some row's window (key j
    is inside row i's where j - i > ahead - window)."""
    return ahead < window + block_k - 1


def _block_on_edge(ahead, block_q, window):
    """Some key of the block is older than some row's window: the
    window's edge crosses the block."""
    return ahead >= window - block_q + 1


class FlashPlan(NamedTuple):
    """The flash kernels' static choices for one call."""
    block_q: int
    block_k: int
    n_q: int
    n_k: int
    q_off: int              # sk - sq: where the causal diagonal starts
    causal: bool
    operand_dtype: Any      # what the MXU products take
    strips: int             # row strips of a block crossed corner to
    #                         corner (1: the block whole, with its mask)
    window: Optional[int]   # rows a row reads back; None
    behind: int             # of `skipped`, blocks wholly behind the window
    edge: int               # blocks the window's edge crosses
    skipped: int            # blocks (a batch-head) that run nothing
    diagonal: int           # ... that build the causal mask alone
    full: int               # ... that run without one
    band_k: int             # steps of the forward's and dq's k axis: n_k,
    #                         with a window the most blocks a row's band has
    band_q: int             # ... of dk/dv's q axis
    blocks_run: float       # blocks' worth of products a kernel runs a
    #                         batch-head, and how many of them lie inside
    blocks_inside: float    # the mask: what the band leaves to compute

    @property
    def edge_strips(self):
        """Row strips of an edge block: the window's edge runs corner
        to corner as well (`ahead == window` on every edge block)."""
        return self.strips if self.window is not None \
            and self.window % self.block_k == 0 else 1


def _strips(block, n_k, low, backward):
    """How many row strips a square block of `block` rows runs in where a
    boundary crosses it corner to corner (`low`: bfloat16 operands;
    `backward`: dq and dk/dv, else the forward). Read on the chip
    (`tools/flash_block_sweep.py --strips 1,2,4,8`, PERF.md section 6,
    PR 64; us a call at blocks of 1,024, strips 1 / 2 / 4 / 8):
      32 x 8,192 x 128 bf16, window 1,024   fwd    2,057 / 1,877 / 1,889 / 2,145
        (Mellum 2's window layers)          dq     2,630 / 2,032 / 1,808 / 1,766
                                            dk/dv  3,040 / 2,340 / 2,105 / 1,955
      64 x 2,048 x 128 bf16 (the Cerebras   fwd      826 /   713 /   766 /   865
        train cell)                         dq     1,088 /   934 /   858 /   868
                                            dk/dv  1,207 / 1,025 /   954 /   920
      32 x 6,144 x 192/128 f32 (Kanana)     fwd    4,224 / 3,944 / 3,987 / 4,239
    The forward's strips each run the online softmax's chain (scores,
    max, exp, sum, P V) behind the one before: halves, as since PR 36,
    and narrower ones give the saved products back. The backward has no
    such chain and gains down to the lane tile's 128 rows."""
    # not a block that is its row's only one, where the strips cost more
    # than the products they save (halves 75.7 against 60.3 us at 16 x
    # 1,024 x 128 float32: PERF.md section 6, PR 36); whole lane tiles
    if n_k == 1 or block % 256:
        return 1
    # (what the sweep did not read keeps halves: other blocks, and a
    # float32 backward, which no cell runs on the chip)
    return 8 if backward and low and block == 1024 else 2


def _inside(ahead, block_q, block_k, window):
    """The share of block's (row, key) pairs inside the mask."""
    row = np.arange(block_q)
    first = 0 if window is None else np.maximum(row + ahead - window + 1, 0)
    last = np.minimum(row + ahead, block_k - 1)
    return float(np.maximum(last - first + 1, 0).sum()) / (block_q * block_k)


def flash_block_plan(sq, sk, block_q, block_k, causal, dtype,
                     window=None, selected=False,
                     backward=False) -> FlashPlan:
    """What the forward (`backward`: dq and dk/dv) does at these shapes,
    blocks and input dtype (`selected`: over a selection, one body a
    block); the wrappers derive their grids, `index_map`s and bodies from
    it and leave it in the trace ring (`kernel/flash_plan`)."""
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    q_off = sk - sq
    low = jnp.dtype(dtype) == jnp.bfloat16
    skipped = diagonal = behind = edge = 0
    if window is not None and not (causal and window >= 1):
        raise ValueError("a window is a causal one of at least one row")
    # square blocks that the diagonal crosses corner to corner
    strips = 1
    if causal and not selected and block_q == block_k \
            and q_off % block_q == 0 \
            and (window is None or window >= block_q):
        # (a window narrower than a block would cross a strip)
        strips = _strips(block_q, n_k, low, backward)
    in_row, in_column = [0] * n_q, [0] * n_k    # blocks that run
    inside = 0.0
    for iq in range(n_q if causal else 0):
        for ik in range(n_k):
            ahead = _ahead(iq, ik, block_q, block_k, q_off)
            if not _block_runs(ahead, block_q):
                skipped += 1
                continue
            if window is not None \
                    and not _block_in_window(ahead, block_k, window):
                skipped += 1
                behind += 1
                continue
            in_row[iq] += 1
            in_column[ik] += 1
            if window is not None and _block_on_edge(ahead, block_q, window):
                edge += 1
            elif _block_crosses(ahead, block_k):
                diagonal += 1
            else:
                continue
            inside += _inside(ahead, block_q, block_k, window)
    full = n_q * n_k - skipped - diagonal - edge
    banded = window is not None
    plan = FlashPlan(block_q, block_k, n_q, n_k, q_off, bool(causal),
                     jnp.dtype(jnp.bfloat16 if low else jnp.float32),
                     strips, window, behind, edge, skipped, diagonal, full,
                     max(in_row + [1]) if banded else n_k,
                     max(in_column + [1]) if banded else n_q, 0.0,
                     full + inside)
    in_strips = lambda n: (n + 1) / (2 * n)
    return plan._replace(blocks_run=full + diagonal * in_strips(plan.strips)
                         + edge * in_strips(plan.edge_strips))


def _note_plan(plan, kernels, sq, sk, **more):
    """One record in the trace ring each time a wrapper is traced
    (`selected: True` on a forward that takes a selection)."""
    obs_trace.phase("kernel", "flash_plan", 0.0, attrs=dict(
        plan._asdict(), operand_dtype=plan.operand_dtype.name,
        kernels=kernels, sq=sq, sk=sk, **more))


def _last_k(iq, plan):
    """The last k-block row `iq` of the grid runs: a skipped step names
    it, the block already resident, and Pallas issues no copy."""
    return jnp.clip((iq * plan.block_q + plan.block_q - 1 + plan.q_off)
                    // plan.block_k, 0, plan.n_k - 1)


def _first_k(iq, plan):
    """The first k-block row `iq` runs under the plan's window: the one
    that holds the oldest key its first row reads."""
    return jnp.clip((iq * plan.block_q + plan.q_off - plan.window + 1)
                    // plan.block_k, 0, plan.n_k - 1)


def _first_q(ik, plan):
    """The first q-block column `ik` of the dk/dv grid runs."""
    return jnp.clip((ik * plan.block_k - plan.q_off) // plan.block_q,
                    0, plan.n_q - 1)


def _last_q(ik, plan):
    """The last q-block column `ik` runs under the plan's window: the one
    that holds the last row its newest key is inside the window of."""
    return jnp.clip((ik * plan.block_k + plan.block_k - 1 + plan.window - 1
                     - plan.q_off) // plan.block_q, 0, plan.n_q - 1)


def _step_k(plan, iq, step):
    """The k-block step `step` of row `iq`'s k axis stands for: with a
    window the axis is the band, counted from the row's first block."""
    return step if plan.window is None else _first_k(iq, plan) + step


def _step_q(plan, ik, step):
    """The q-block step `step` of column `ik`'s q axis (dk/dv's) stands
    for."""
    return step if plan.window is None else _first_q(ik, plan) + step


def _for_block(plan, iq, ik, body):
    """`body(rows, keys, ahead)` over what this grid step's block needs
    (`body(rows, keys, ahead, behind)` where the plan's window's edge
    crosses it: key - row > `behind` as well; nothing where the block is
    wholly older than the window):
    nothing where it lies wholly above the causal diagonal; the whole
    block without a mask (`ahead` None) where it lies wholly under; with
    the mask (key - row <= `ahead`, both counted inside the tile) where
    the diagonal crosses it, and there in the plan's `strips`: strip i of
    the rows against the keys up to its own last one, so that what lies
    above the diagonal is computed a strip deep and no deeper. An edge
    block in strips is the mirror: strip i against the keys from its own
    first one on, under the window's mask alone (the diagonal is a
    window away)."""
    rows, keys = slice(0, plan.block_q), slice(0, plan.block_k)
    if not plan.causal:
        return body(rows, keys, None)
    ahead = _ahead(iq, ik, plan.block_q, plan.block_k, plan.q_off)
    runs = _block_runs(ahead, plan.block_q)
    crosses = _block_crosses(ahead, plan.block_k)
    strip = plan.block_q // plan.strips

    def diagonal():
        if plan.strips > 1:     # corner to corner: `ahead` is 0
            for at in range(0, plan.block_q, strip):
                body(slice(at, at + strip), slice(0, at + strip), at)
        else:
            body(rows, keys, ahead)

    def edge():
        if plan.edge_strips > 1:    # corner to corner: `ahead` is `window`
            for at in range(0, plan.block_q, strip):
                body(slice(at, at + strip), slice(at, plan.block_k), None, 0)
        else:
            body(rows, keys, ahead, ahead - plan.window)

    if plan.window is not None:
        # (the band's axis steps past the matrix where a row's or a
        # column's band is cut short by it)
        runs = jnp.logical_and(runs, jnp.logical_and(
            _block_in_window(ahead, plan.block_k, plan.window),
            jnp.logical_and(iq < plan.n_q, ik < plan.n_k)))
        on_edge = _block_on_edge(ahead, plan.block_q, plan.window)
        pl.when(jnp.logical_and(runs, on_edge))(edge)
        runs = jnp.logical_and(runs, jnp.logical_not(on_edge))
    pl.when(jnp.logical_and(runs, crosses))(diagonal)
    pl.when(jnp.logical_and(runs, jnp.logical_not(crosses)))(
        lambda: body(rows, keys, None))


def _hide_future(s, ahead, keys_on=1):
    """The causal mask over a score tile the diagonal crosses; the
    tile's keys lie along axis `keys_on`, its rows along the other."""
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, keys_on)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - keys_on)
    return jnp.where(key - row <= ahead, s, DEFAULT_MASK_VALUE)


def _hide_past(s, behind, keys_on=1):
    """The window's mask over a score tile its edge crosses: the keys
    older than a row's window (the tile's keys along axis `keys_on`)."""
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, keys_on)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - keys_on)
    return jnp.where(key - row > behind, s, DEFAULT_MASK_VALUE)


def _scores(q, k, ahead, scale, transposed=False):
    """A tile's scores, float32: Q K^T [rows, keys] (K Q^T [keys, rows]
    `transposed`), scaled, and masked where the tile needs it."""
    s = (_mxu(k, q, _NT) if transposed else _mxu(q, k, _NT)) * scale
    return s if ahead is None else _hide_future(s, ahead, 1 - transposed)


def _split(x):
    """float32 -> (high, low) bfloat16 halves: high + low is x to sixteen
    bits of mantissa."""
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)


def _scores_of_choice(q, k, scale):
    """Q K^T [rows, keys] float32 for a softmax over a SELECTION, where a
    score's error decides what a row reads next to nothing else: float32
    operands in the three bfloat16 passes of `Precision.HIGH` (high x
    high + low x high + high x low; Mosaic lowers no such precision, so
    the halves are split here and laid along the contraction: ONE product
    three heads wide, summed in the MXU's float32), bfloat16 ones in
    their one exact pass."""
    if q.dtype == jnp.bfloat16:
        return _mxu(q, k, _NT) * scale
    (qh, ql), (kh, kl) = _split(q), _split(k)
    return _mxu(jnp.concatenate([qh, ql, qh], axis=1),
                jnp.concatenate([kh, kh, kl], axis=1), _NT) * scale


def _online_softmax(s, m_ref, l_ref, rows):
    """One step of the online-softmax recurrence over the score tile of
    the block's `rows`: (P, the factor the old accumulator shrinks by)."""
    m_prev = m_ref[rows]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])   # [rows, 1]
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)                                     # [rows, keys]
    l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1)[:, None]
    m_ref[rows] = m_next
    return p, alpha


def _probabilities(s, lse):
    """P recomputed from the saved logsumexp (the backward kernels)."""
    return jnp.exp(s - lse)


def _score_grads(p, dp, delta):
    """dS of the softmax (before `scale`): P (dP - rowsum(dO O))."""
    return p * (dp - delta)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, plan):
    """One (batch*head, q-block, k-block) grid step.

    q_ref: [block_q, d]; k_ref/v_ref: [block_k, d]; with a selection its
    tile [block_q, block_k] int8 next; then o_ref, lse_ref and the
    accumulators, which live in VMEM scratch across the k grid dimension
    (the innermost, sequential one).
    """
    *sel, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    iq, step = pl.program_id(1), pl.program_id(2)
    ik = _step_k(plan, iq, step)
    mxu = plan.operand_dtype

    @pl.when(step == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(rows, keys, ahead, behind=None):
        q, k = q_ref[0, rows].astype(mxu), k_ref[0, keys].astype(mxu)
        if sel:
            # A row with no key in a block leaves the mask's value in
            # its state; the first block with a key of its own
            # multiplies what that gathered by exp(mask - score) = 0,
            # and every row selects a key
            s = jnp.where(sel[0][0, rows, keys] != 0,
                          _scores_of_choice(q, k, scale), DEFAULT_MASK_VALUE)
        else:
            s = _scores(q, k, ahead, scale)
        if behind is not None:
            s = _hide_past(s, behind)
        p, alpha = _online_softmax(s, m_ref, l_ref, rows)
        acc_ref[rows] = acc_ref[rows] * alpha + _mxu(
            p.astype(mxu), v_ref[0, keys].astype(mxu), _NN)

    if sel:
        # the selection is every block's one mask (it holds s <= t): ONE
        # body for each block that runs, the diagonal's neither masked
        # again nor in strips (halves are 5% of the kernel's time and
        # two bodies more of its code: PERF.md section 6, PR 45)
        ahead = _ahead(iq, ik, plan.block_q, plan.block_k, plan.q_off)
        pl.when(_block_runs(ahead, plan.block_q))(lambda: body(
            slice(0, plan.block_q), slice(0, plan.block_k), None))
    else:
        _for_block(plan, iq, ik, body)

    @pl.when(step == plan.band_k - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _needed_k(plan, iq, step):
    """The k-block step `step` of row `iq`'s k axis names: its own where
    it runs, else the one already resident (the row's last), so that a
    skipped step copies nothing."""
    ik = _step_k(plan, iq, step)
    return jnp.minimum(ik, _last_k(iq, plan)) if plan.causal else ik


def _q_major_specs(plan, group=1):
    """(a q-block's spec of width `w`, a k-block's) on a grid (batch-head,
    q-block, k-block): the k-block a skipped step names is `_needed_k`'s.
    `group` query heads read one K/V head: batch-head b reads K/V
    batch-head b // group, and nothing is repeated in HBM."""
    def q_spec(w):
        return pl.BlockSpec((1, plan.block_q, w),
                            lambda b, iq, ik: (b, iq, 0))

    def k_spec(w):
        if group == 1:
            return pl.BlockSpec((1, plan.block_k, w), lambda b, iq, ik: (
                b, _needed_k(plan, iq, ik), 0))
        return pl.BlockSpec((1, plan.block_k, w), lambda b, iq, ik: (
            b // group, _needed_k(plan, iq, ik), 0))
    return q_spec, k_spec


def _selection_spec(plan, heads):
    """A selection's tile [block_q, block_k] on that grid, the same for
    the `heads` batch-heads of a sequence; a skipped step names the tile
    already resident, as it does K's block."""
    return pl.BlockSpec((1, plan.block_q, plan.block_k), lambda b, iq, ik: (
        b // heads, iq, _needed_k(plan, iq, ik)))


# Both wrappers are jitted so that a model's layers, which all call them
# at one shape, share one trace and one lowering of each kernel (the three
# bodies of a kernel traced a layer cost the train cell 5 s of set-up:
# PERF.md section 6, PR 36). An XLA operation is named by the innermost
# scope, and under a jit that is no longer the program op's: the scopes
# below give the kernels the names a device trace knows them by, which
# `flash_fwd_roofline` and `flash_bwd_roofline` read.
_KERNEL_STATICS = ("scale", "causal", "block_q", "block_k", "interpret")


def _scope_of(window, transpose=False):
    """The name a device trace knows a call's kernels by: a windowed
    call's apart from a full one's, forward and backward."""
    name = "scaled_dot_product_attention" if window is None \
        else "windowed_dot_product_attention"
    return "transpose_" + name if transpose else name


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS + ("window",))
def _flash_fwd(q3, k3, v3, selected=None, *, scale, causal, block_q,
               block_k, interpret=False, window=None):
    """q3: [BH, S, D]; k3: [BH_kv, Sk, D]; v3: [BH_kv, Sk, Dv] (Dv may
    differ from D: a latent-attention head scores on 192 and carries
    128; BH_kv may divide BH: groups of query heads over one K/V head,
    batch-head b reading K/V batch-head b // (BH / BH_kv)); `selected`
    int8 [B, Sq, Sk] (B divides BH: a sequence's heads share its
    selection), causal and without a window
    -> (o [BH, Sq, Dv], lse [BH, Sq, 1])."""
    bh, sq, d = q3.shape
    sk, dv = k3.shape[1], v3.shape[2]
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use the "
                           "mha_reference path")
    plan = flash_block_plan(sq, sk, block_q, block_k, causal,
                            jnp.result_type(q3, k3, v3), window,
                            selected is not None)
    q_spec, k_spec = _q_major_specs(plan, bh // k3.shape[0])
    in_specs, operands = [q_spec(d), k_spec(d), k_spec(dv)], (q3, k3, v3)
    if selected is None:
        _note_plan(plan, "fwd", sq, sk)
    else:
        _note_plan(plan, "fwd", sq, sk, selected=True)
        in_specs.append(_selection_spec(plan, bh // selected.shape[0]))
        operands += (selected,)
    scratch = [
        pltpu.VMEM((plan.block_q, dv), jnp.float32),  # acc
        pltpu.VMEM((plan.block_q, 1), jnp.float32),   # m
        pltpu.VMEM((plan.block_q, 1), jnp.float32),   # l
    ]
    with jax.named_scope(_scope_of(window)):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_q, plan.band_k),
            in_specs=in_specs,
            out_specs=[q_spec(dv), q_spec(1)],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, dv), q3.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)
    return o, lse


# Backward (flash-attention-2 split): one kernel accumulates dq over
# k-blocks, one accumulates dk/dv over q-blocks. Score/probability tiles
# live in VMEM only — the XLA fallback below materializes [bq, Sk]-sized
# p/ds chunks in HBM, which at 8k tokens is the dominant backward traffic.

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, plan):
    iq, step = pl.program_id(1), pl.program_id(2)
    ik = _step_k(plan, iq, step)
    mxu = plan.operand_dtype

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(rows, keys, ahead, behind=None):
        k = k_ref[0, keys].astype(mxu)
        s = _scores(q_ref[0, rows].astype(mxu), k, ahead, scale)
        if behind is not None:
            s = _hide_past(s, behind)
        p = _probabilities(s, lse_ref[0, rows])
        dp = _mxu(do_ref[0, rows].astype(mxu), v_ref[0, keys].astype(mxu),
                  _NT)
        ds = _score_grads(p, dp, delta_ref[0, rows]) * scale
        acc_ref[rows] = acc_ref[rows] + _mxu(ds.astype(mxu), k, _NN)

    _for_block(plan, iq, ik, body)

    @pl.when(step == plan.band_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, plan):
    """dk and dv of one k-block over the q-blocks, every tile TRANSPOSED
    ([block_k, block_q]: S^T = K Q^T, dP^T = V dO^T), so that dv += P^T dO
    and dk += dS^T Q are plain products and no tile is turned; `lse` and
    `delta` come as rows [1, block_q]."""
    ik, step = pl.program_id(1), pl.program_id(2)
    iq = _step_q(plan, ik, step)
    mxu = plan.operand_dtype

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(rows, keys, ahead, behind=None):
        q = q_ref[0, rows].astype(mxu)
        do = do_ref[0, rows].astype(mxu)
        s = _scores(q, k_ref[0, keys].astype(mxu), ahead, scale,
                    transposed=True)
        if behind is not None:
            s = _hide_past(s, behind, keys_on=0)
        p = _probabilities(s, lse_ref[0, :, rows])
        dv_acc[keys] = dv_acc[keys] + _mxu(p.astype(mxu), do, _NN)
        dp = _mxu(v_ref[0, keys].astype(mxu), do, _NT)
        ds = _score_grads(p, dp, delta_ref[0, :, rows]) * scale
        dk_acc[keys] = dk_acc[keys] + _mxu(ds.astype(mxu), q, _NN)

    _for_block(plan, iq, ik, body)

    @pl.when(step == plan.band_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS + ("window",))
def _flash_bwd_pallas(q3, k3, v3, o3, lse, do3, *, scale, causal, block_q,
                      block_k, interpret=False, window=None):
    """[BH, S, D] backward via the two Pallas kernels above; with a
    `window` dq walks the forward's band (`_first_k` .. `_last_k`) and
    dk/dv its transpose (`_first_q` .. `_last_q`)."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    plan = flash_block_plan(sq, sk, block_q, block_k, causal,
                            jnp.result_type(q3, k3, v3, do3), window,
                            backward=True)
    _note_plan(plan, "dq+dkv", sq, sk)
    # delta = rowsum(do * o): one cheap fused elementwise pass in XLA
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BH, Sq, 1]

    q_spec, k_spec = _q_major_specs(plan)

    # dk/dv's grid is (batch-head, k-block, q-block): the q-block a
    # skipped step names is the column's first (under a window the axis
    # starts there, and a step past the band names the column's last)
    def needed(ik, step):
        if plan.window is not None:
            return jnp.minimum(_step_q(plan, ik, step), _last_q(ik, plan))
        return jnp.maximum(step, _first_q(ik, plan)) if plan.causal \
            else step

    kv_block = pl.BlockSpec((1, plan.block_k, d),
                            lambda b, ik, iq: (b, ik, 0))
    q_block = pl.BlockSpec((1, plan.block_q, d),
                           lambda b, ik, iq: (b, needed(ik, iq), 0))
    # `lse` and `delta` as rows [BH, 1, Sq]: a q-block's is one
    # contiguous copy, and broadcasts down a transposed tile's keys
    q_row = pl.BlockSpec((1, 1, plan.block_q),
                         lambda b, ik, iq: (b, 0, needed(ik, iq)))

    with jax.named_scope(_scope_of(window, transpose=True)):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_q, plan.band_k),
            in_specs=[q_spec(d), k_spec(d), k_spec(d), q_spec(d), q_spec(1),
                      q_spec(1)],
            out_specs=q_spec(d),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            scratch_shapes=[pltpu.VMEM((plan.block_q, d), jnp.float32)],
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_k, plan.band_q),
            in_specs=[kv_block, kv_block, q_block, q_block, q_row, q_row],
            out_specs=[kv_block, kv_block],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk, d), k3.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v3.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((plan.block_k, d), jnp.float32),
                            pltpu.VMEM((plan.block_k, d), jnp.float32)],
            interpret=interpret,
        )(k3, v3, q3, do3, lse.reshape(bh, 1, sq), delta.reshape(bh, 1, sq))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom VJP: forward saves lse; backward recomputes p blockwise in XLA
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return o


def _bshd_to_3d(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _3d_to_bshd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret,
                    window=None, selected=None):
    b, sq, h, d = q.shape
    o3, lse = _flash_fwd(_bshd_to_3d(q), _bshd_to_3d(k), _bshd_to_3d(v),
                         selected, scale=scale, causal=causal,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, window=window)
    o = _3d_to_bshd(o3, b, h)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, do,
                    window=None):
    """Backward dispatch: Pallas kernels on TPU (score/probability tiles
    never leave VMEM), XLA chunked scan elsewhere (the numerics oracle).

      p = exp(s - lse);  ds = p * (dp - delta);  delta = rowsum(do * o)
    """
    q, k, v, o, lse = res
    group = q.shape[2] // k.shape[2]
    if group > 1:
        # K/V heads that groups share: the backward kernels take one K/V
        # head a query head, so K and V are repeated for them (here
        # alone) and a group's dk and dv summed
        dq, dk, dv = _flash_bwd_rule(
            scale, causal, block_q, block_k, interpret,
            (q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
             o, lse), do, window)
        fold = lambda g: g.reshape(g.shape[:2] + (k.shape[2], group,
                                                  g.shape[-1])).sum(3)
        return dq, fold(dk).astype(k.dtype), fold(dv).astype(v.dtype)
    # the two backward kernels are written for one head width: a V width
    # of its own (latent attention) takes the XLA scan below
    if _HAS_PLTPU and v.shape[-1] == q.shape[-1] \
            and (interpret or jax.default_backend() == "tpu"):
        b, h = q.shape[0], q.shape[2]
        # the backward kernels hold more VMEM per tile (s, p, dp, ds) than
        # the forward and still take the forward's blocks
        # (`tools/flash_block_sweep.py`: dq best and dk/dv level with 512
        # at the forward's 1,024)
        dq3, dk3, dv3 = _flash_bwd_pallas(
            _bshd_to_3d(q), _bshd_to_3d(k), _bshd_to_3d(v), _bshd_to_3d(o),
            lse, _bshd_to_3d(do), scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window)
        return (_3d_to_bshd(dq3, b, h), _3d_to_bshd(dk3, b, h),
                _3d_to_bshd(dv3, b, h))
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    ki = jnp.arange(sk)[None, :]

    bq = min(block_q, sq)
    n_q = (sq + bq - 1) // bq
    pad = n_q * bq - sq
    if pad:
        padded = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    else:
        padded = lambda x: x
    # [b, n_q, bq, ...] blocks, scan over n_q
    def blocks(x):
        x = padded(x)
        return x.reshape(b, n_q, bq, *x.shape[2:]).transpose(1, 0, 2, *range(3, x.ndim + 1))

    q_b, o_b, do_b = blocks(q), blocks(o), blocks(do.astype(jnp.float32))
    # lse: [b*h, sq, 1] -> [b, sq, h] so it blocks like the others
    lse_bsh = lse.reshape(b, h, sq).transpose(0, 2, 1)
    lse_b = blocks(lse_bsh)                                # [n_q, b, bq, h]

    def step(carry, xs):
        dk_acc, dv_acc = carry
        i, qc, oc, doc, lsec = xs
        qc = qc.astype(jnp.float32)                        # [b, bq, h, d]
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kf,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * bq + jnp.arange(bq)[:, None] + (sk - sq)
        if causal:
            s = jnp.where(ki <= qpos, s, DEFAULT_MASK_VALUE)
        if window is not None:
            s = jnp.where(ki > qpos - window, s, DEFAULT_MASK_VALUE)
        if pad:
            s = jnp.where((qpos - (sk - sq)) < sq, s, DEFAULT_MASK_VALUE)
        p = jnp.exp(s - lsec.transpose(0, 2, 1)[:, :, :, None])
        dv_acc = dv_acc + jnp.einsum("bhqk,bqhd->bkhd", p, doc)
        dp = jnp.einsum("bqhd,bkhd->bhqk", doc, vf)
        delta = jnp.sum(doc * oc.astype(jnp.float32), axis=-1)  # [b,bq,h]
        ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
        dk_acc = dk_acc + jnp.einsum("bhqk,bqhd->bkhd", ds, qc) * scale
        dq_c = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
        return (dk_acc, dv_acc), dq_c

    init = (jnp.zeros((b, sk, h, d), jnp.float32),
            jnp.zeros((b, sk, h, v.shape[-1]), jnp.float32))
    (dk, dv), dq_blocks = jax.lax.scan(
        step, init, (jnp.arange(n_q), q_b, o_b, do_b, lse_b))
    dq = dq_blocks.transpose(1, 0, 2, 3, 4).reshape(b, n_q * bq, h, d)[:, :sq]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_window(q, k, v, scale, block_q, block_k, interpret, window):
    """Causal attention inside a window band, forward and backward."""
    return _flash_fwd_rule(q, k, v, scale, True, block_q, block_k,
                           interpret, window)[0]


_flash_window.defvjp(
    lambda q, k, v, scale, block_q, block_k, interpret, window:
    _flash_fwd_rule(q, k, v, scale, True, block_q, block_k, interpret,
                    window),
    lambda scale, block_q, block_k, interpret, window, res, do:
    _flash_bwd_rule(scale, True, block_q, block_k, interpret, res, do,
                    window))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_selected(q, k, v, selected, scale, block_q, block_k, interpret):
    """The forward over a selection; it has no backward."""
    return _flash_fwd_rule(q, k, v, scale, True, block_q, block_k,
                           interpret, selected=selected)[0]


def _no_selected_bwd(scale, block_q, block_k, interpret, res, do):
    raise NotImplementedError(
        "the flash backward takes no selection: dq and dk/dv walk the "
        "whole causal triangle (models.transformer.transformer_lm_loss "
        "refuses a block with an indexer)")


_flash_selected.defvjp(
    lambda q, k, v, selected, *static: (
        _flash_selected(q, k, v, selected, *static), None),
    _no_selected_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False,
                    window: Optional[int] = None, selected=None):
    """Flash attention on [B, S, H, D] inputs (Pallas kernel). k and v
    may hold fewer heads (query head j reads K/V head j // (H / H_kv));
    `window`: a row reads back that many rows, itself counted;
    `selected` [B, Sq, Sk] (int8 as the kernel takes it; causal, no
    window): row t's softmax is over the keys s where it is not 0, of
    which every row has one, none ahead of it: the forward's alone."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if selected is not None:
        if not causal or window is not None:
            raise ValueError("a selection is a causal one, and has no "
                             "window")
        return _flash_selected(q, k, v, selected.astype(jnp.int8),
                               float(scale), int(block_q), int(block_k),
                               bool(interpret))
    if window is not None:
        if not causal:
            raise ValueError("a window is a causal one")
        return _flash_window(q, k, v, float(scale), int(block_q),
                             int(block_k), bool(interpret), int(window))
    return _flash(q, k, v, float(scale), bool(causal), int(block_q),
                  int(block_k), bool(interpret))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

def _tpu_takes(sq, sk, d, causal: bool = False):
    """Whether the flash kernels run attention of `sq` rows over `sk`
    keys at heads of `d` here."""
    if not _HAS_PLTPU or jax.default_backend() != "tpu":
        return False
    # MXU-friendly: lane dim multiple of 128 after padding is handled by
    # mosaic, but tiny/ragged heads are faster on the XLA path.
    # causal sq > sk is excluded: rows whose causal window precedes all keys
    # have no visible key, and the kernel's l==0 guard zeroes them while
    # mha_reference softmaxes the finite DEFAULT_MASK_VALUE — keep both
    # entry points on the (well-defined) reference semantics for that case.
    if causal and sq > sk:
        return False
    return sq >= 128 and sk >= 128 and sq % 128 == 0 and sk % 128 == 0 \
        and d % 8 == 0


def _tpu_ok(q, k, causal: bool = False):
    return _tpu_takes(q.shape[1], k.shape[1], q.shape[-1], causal)


def _default_block(s):
    """The largest block up to 1,024 that divides `s` (the kernels have
    no ragged-block masking): at every shape `tools/flash_block_sweep.py`
    times, the larger block won."""
    for b in (1024, 512, 256):
        if s % b == 0:
            return b
    return 128


def attention_form(sq, sk, head_dim, selected=False):
    """Which form `dot_product_attention` gives causal attention of `sq`
    rows over `sk` keys here: "flash" (the Pallas forward), "flash_selected"
    (the same over a selection's tiles) or "masked_dense" (XLA's products
    over whole score rows: off the chip, and at shapes `_tpu_takes`
    refuses)."""
    if not _tpu_takes(sq, sk, head_dim, True):
        return "masked_dense"
    return "flash_selected" if selected else "flash"


def dot_product_attention(q, k, v, bias=None, *, causal: bool = False,
                          scale: Optional[float] = None,
                          window: Optional[int] = None, selected=None,
                          block: Optional[int] = None):
    """Public entry: picks the Pallas kernel on TPU, XLA reference else.
    `window` (causal only): row t reads the keys s with t - s < window.
    `selected` [B, Sq, Sk] (causal only): row t reads the keys s where
    it is not 0 (`flash_attention`); off the chip a bias.
    k and v may hold fewer heads than q. `block`: the widest tile the
    caller's shape allows where that is under `_default_block`'s (float32
    heads of 256 over a selection: a 1,024 x 1,024 tile's scores, mask and
    bfloat16 halves are 65 MB of the 64 MB of scoped VMEM).

    bias (additive mask) forces the reference path — the kernel handles the
    causal structure itself and arbitrary bias tiles would defeat the
    block-skip.
    """
    if bias is None and _tpu_ok(q, k, causal):
        # `_default_block`: the largest block up to 1,024 that divides.
        # `tools/flash_block_sweep.py` on the v5e, kernel-alone device us a
        # call at square blocks of 256 / 512 / 1,024 (PERF.md section 6,
        # PR 36):
        #   64 x 2,048 x 128 bf16 (the train cell)  fwd    2,664 / 1,267 /   717
        #                                           dq     2,084 / 1,167 /   933
        #                                           dk/dv  2,104 / 1,136 / 1,016
        #   32 x 6,144 x 192/128 f32 (Kanana)       fwd   12,450 / 6,285 / 3,942
        #   16 x 1,024 x 128 f32 (other buckets)    fwd      187 /   102 /    56
        # and every rectangle of them lost to 1,024 x 1,024: a grid step
        # costs about a microsecond of fill, drain and softmax state
        # whatever its area, so fewer, larger steps win.
        # The kernel has no ragged-block masking, so a block is only
        # eligible when it DIVIDES its seq dim (128 always does: _tpu_ok
        # guarantees seq % 128 == 0); bq and bk follow their own dims so
        # cross-attention picks safely too.
        widest = block or 1024
        return flash_attention(
            q, k, v, causal=causal, scale=scale,
            block_q=min(_default_block(q.shape[1]), widest),
            block_k=min(_default_block(k.shape[1]), widest),
            window=window, selected=selected)
    if selected is not None:
        hidden = jnp.where(selected != 0, 0.0, DEFAULT_MASK_VALUE)[:, None]
        bias = hidden if bias is None else bias + hidden
    return mha_reference(q, k, v, bias, causal=causal, scale=scale,
                         window=window)
