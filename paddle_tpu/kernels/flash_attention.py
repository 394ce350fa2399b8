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

The compile cache: this file's line numbers are in every kernel's
serialized module, so ANY edit here makes every program that holds one of
its kernels (all five benchmark cells) compile anew once. That is the
ledger's `first_setup_s`, not `setup_s`, which is read warm (ROADMAP D16).
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
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
#   * a diagonal block of self-attention (square, the diagonal corner to
#     corner) runs in two halves of its rows, each against the keys it
#     can see: three quarters of the block's products;
#   * with a WINDOW (the forward alone: row t reads keys s with
#     t - s < window) the band has a second edge: a block wholly older
#     than every row's window is skipped like one above the diagonal
#     (nothing runs, nothing is copied: the `index_map` names the row's
#     first block that runs), the block that edge crosses masks it.
#     Without a window none of this is traced: the plan, the bodies and
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
    in_halves: bool         # a diagonal block skips its upper quarter
    window: Optional[int]   # rows a row reads back (forward only); None
    behind: int             # of `skipped`, steps wholly behind the window
    edge: int               # steps the window's edge crosses (both masks)
    skipped: int            # grid steps (a batch-head) that run nothing
    diagonal: int           # ... that build the causal mask alone
    full: int               # ... that run without one


def flash_block_plan(sq, sk, block_q, block_k, causal, dtype,
                     window=None) -> FlashPlan:
    """What the three kernels do at these shapes, blocks and input dtype;
    the wrappers derive their grids, `index_map`s and bodies from it and
    leave it in the trace ring (`kernel/flash_plan`)."""
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    q_off = sk - sq
    low = jnp.dtype(dtype) == jnp.bfloat16
    skipped = diagonal = behind = edge = 0
    if window is not None and not (causal and window >= 1):
        raise ValueError("a window is a causal one of at least one row")
    if causal:
        for iq in range(n_q):
            for ik in range(n_k):
                ahead = _ahead(iq, ik, block_q, block_k, q_off)
                if window is not None and _block_runs(ahead, block_q):
                    if not _block_in_window(ahead, block_k, window):
                        skipped += 1
                        behind += 1
                        continue
                    if _block_on_edge(ahead, block_q, window):
                        edge += 1
                        continue
                skipped += not _block_runs(ahead, block_q)
                diagonal += bool(_block_runs(ahead, block_q)
                                 and _block_crosses(ahead, block_k))
    # square blocks that the diagonal crosses corner to corner, in halves
    # of whole lane tiles; not a block that is its row's only one, where
    # the halves cost more than the quarter they save (75.7 against 60.3
    # us at 16 x 1,024 x 128 float32: PERF.md section 6, PR 36)
    in_halves = bool(causal and block_q == block_k and n_k > 1
                     and q_off % block_q == 0 and block_q % 256 == 0
                     # (a window narrower than a block would cross a half)
                     and (window is None or window >= block_q))
    return FlashPlan(block_q, block_k, n_q, n_k, q_off, bool(causal),
                     jnp.dtype(jnp.bfloat16 if low else jnp.float32),
                     in_halves, window, behind, edge, skipped, diagonal,
                     n_q * n_k - skipped - diagonal - edge)


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


def _for_block(plan, iq, ik, body):
    """`body(rows, keys, ahead)` over what this grid step's block needs
    (`body(rows, keys, ahead, behind)` where the plan's window's edge
    crosses it: key - row > `behind` as well; nothing where the block is
    wholly older than the window):
    nothing where it lies wholly above the causal diagonal; the whole
    block without a mask (`ahead` None) where it lies wholly under; with
    the mask (key - row <= `ahead`, both counted inside the tile) where
    the diagonal crosses it, and there `in_halves` where the plan says
    so: the upper rows against the first half of the keys, the lower
    rows against all, so that the quarter above the diagonal is not
    computed at all."""
    rows, keys = slice(0, plan.block_q), slice(0, plan.block_k)
    if not plan.causal:
        return body(rows, keys, None)
    ahead = _ahead(iq, ik, plan.block_q, plan.block_k, plan.q_off)
    runs = _block_runs(ahead, plan.block_q)
    crosses = _block_crosses(ahead, plan.block_k)
    half = plan.block_q // 2

    def diagonal():
        if plan.in_halves:      # corner to corner: `ahead` is 0
            body(slice(0, half), slice(0, half), 0)
            body(slice(half, plan.block_q), keys, half)
        else:
            body(rows, keys, ahead)

    if plan.window is not None:
        runs = jnp.logical_and(
            runs, _block_in_window(ahead, plan.block_k, plan.window))
        on_edge = _block_on_edge(ahead, plan.block_q, plan.window)
        pl.when(jnp.logical_and(runs, on_edge))(
            lambda: body(rows, keys, ahead, ahead - plan.window))
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


def _hide_past(s, behind):
    """The window's mask over a score tile [rows, keys] its edge
    crosses: the keys older than a row's window."""
    key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
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
    iq, ik = pl.program_id(1), pl.program_id(2)
    mxu = plan.operand_dtype

    @pl.when(ik == 0)
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
        # again nor in halves (halves are 5% of the kernel's time and
        # two bodies more of its code: PERF.md section 6, PR 45)
        ahead = _ahead(iq, ik, plan.block_q, plan.block_k, plan.q_off)
        pl.when(_block_runs(ahead, plan.block_q))(lambda: body(
            slice(0, plan.block_q), slice(0, plan.block_k), None))
    else:
        _for_block(plan, iq, ik, body)

    @pl.when(ik == plan.n_k - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:] + jnp.log(l_safe)).astype(lse_ref.dtype)


def _needed_k(plan, iq, ik):
    """The k-block grid step (iq, ik) names: its own where it runs, else
    the one already resident (the row's last; under a window, of those
    before the band, the row's first), so that a skipped step copies
    nothing."""
    if plan.window is not None:
        return jnp.clip(ik, _first_k(iq, plan), _last_k(iq, plan))
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
                            jnp.result_type(q3, k3, v3), window)
    q_spec, k_spec = _q_major_specs(plan, bh // k3.shape[0])
    in_specs, operands = [q_spec(d), k_spec(d), k_spec(dv)], (q3, k3, v3)
    if selected is None:
        _note_plan(plan, "fwd", sq, sk)
    else:
        plan = plan._replace(in_halves=False)   # (`_fwd_kernel`)
        _note_plan(plan, "fwd", sq, sk, selected=True)
        in_specs.append(_selection_spec(plan, bh // selected.shape[0]))
        operands += (selected,)
    scratch = [
        pltpu.VMEM((plan.block_q, dv), jnp.float32),  # acc
        pltpu.VMEM((plan.block_q, 1), jnp.float32),   # m
        pltpu.VMEM((plan.block_q, 1), jnp.float32),   # l
    ]
    with jax.named_scope("scaled_dot_product_attention"):
        o, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_q, plan.n_k),
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
    iq, ik = pl.program_id(1), pl.program_id(2)
    mxu = plan.operand_dtype

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(rows, keys, ahead):
        k = k_ref[0, keys].astype(mxu)
        p = _probabilities(
            _scores(q_ref[0, rows].astype(mxu), k, ahead, scale),
            lse_ref[0, rows])
        dp = _mxu(do_ref[0, rows].astype(mxu), v_ref[0, keys].astype(mxu),
                  _NT)
        ds = _score_grads(p, dp, delta_ref[0, rows]) * scale
        acc_ref[rows] = acc_ref[rows] + _mxu(ds.astype(mxu), k, _NN)

    _for_block(plan, iq, ik, body)

    @pl.when(ik == plan.n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, plan):
    """dk and dv of one k-block over the q-blocks, every tile TRANSPOSED
    ([block_k, block_q]: S^T = K Q^T, dP^T = V dO^T), so that dv += P^T dO
    and dk += dS^T Q are plain products and no tile is turned; `lse` and
    `delta` come as rows [1, block_q]."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    mxu = plan.operand_dtype

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(rows, keys, ahead):
        q = q_ref[0, rows].astype(mxu)
        do = do_ref[0, rows].astype(mxu)
        p = _probabilities(
            _scores(q, k_ref[0, keys].astype(mxu), ahead, scale,
                    transposed=True), lse_ref[0, :, rows])
        dv_acc[keys] = dv_acc[keys] + _mxu(p.astype(mxu), do, _NN)
        dp = _mxu(v_ref[0, keys].astype(mxu), do, _NT)
        ds = _score_grads(p, dp, delta_ref[0, :, rows]) * scale
        dk_acc[keys] = dk_acc[keys] + _mxu(ds.astype(mxu), q, _NN)

    _for_block(plan, iq, ik, body)

    @pl.when(iq == plan.n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_KERNEL_STATICS)
def _flash_bwd_pallas(q3, k3, v3, o3, lse, do3, *, scale, causal, block_q,
                      block_k, interpret=False):
    """[BH, S, D] backward via the two Pallas kernels above."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    plan = flash_block_plan(sq, sk, block_q, block_k, causal,
                            jnp.result_type(q3, k3, v3, do3))
    _note_plan(plan, "dq+dkv", sq, sk)
    # delta = rowsum(do * o): one cheap fused elementwise pass in XLA
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BH, Sq, 1]

    q_spec, k_spec = _q_major_specs(plan)

    # dk/dv's grid is (batch-head, k-block, q-block): the q-block a
    # skipped step names is the column's first
    def needed(ik, iq):
        return jnp.maximum(iq, _first_q(ik, plan)) if plan.causal else iq

    kv_block = pl.BlockSpec((1, plan.block_k, d),
                            lambda b, ik, iq: (b, ik, 0))
    q_block = pl.BlockSpec((1, plan.block_q, d),
                           lambda b, ik, iq: (b, needed(ik, iq), 0))
    # `lse` and `delta` as rows [BH, 1, Sq]: a q-block's is one
    # contiguous copy, and broadcasts down a transposed tile's keys
    q_row = pl.BlockSpec((1, 1, plan.block_q),
                         lambda b, ik, iq: (b, 0, needed(ik, iq)))

    with jax.named_scope("transpose_scaled_dot_product_attention"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_q, plan.n_k),
            in_specs=[q_spec(d), k_spec(d), k_spec(d), q_spec(d), q_spec(1),
                      q_spec(1)],
            out_specs=q_spec(d),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            scratch_shapes=[pltpu.VMEM((plan.block_q, d), jnp.float32)],
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, plan=plan),
            grid=(bh, plan.n_k, plan.n_q),
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


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, do):
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
             o, lse), do)
        fold = lambda g: g.reshape(g.shape[:2] + (k.shape[2], group,
                                                  g.shape[-1])).sum(3)
        return dq, fold(dk).astype(k.dtype), fold(dv).astype(v.dtype)
    # the two backward kernels are written for one head width: a V width
    # of its own (latent attention) takes the XLA scan below
    if _HAS_PLTPU and v.shape[-1] == q.shape[-1] \
            and (interpret or jax.default_backend() == "tpu"):
        import os
        b, h = q.shape[0], q.shape[2]
        # the backward kernels hold more VMEM per tile (s, p, dp, ds) than
        # the forward, so their blocks are tunable independently; defaults
        # follow the forward's (`tools/flash_block_sweep.py`: dq best and
        # dk/dv level with 512 at the forward's 1,024)
        bwd_bq = int(os.environ.get("FLASH_BWD_BLOCK_Q", 0)) or block_q
        bwd_bk = int(os.environ.get("FLASH_BWD_BLOCK_K", 0)) or block_k
        if q.shape[1] % min(bwd_bq, q.shape[1]) or \
                k.shape[1] % min(bwd_bk, k.shape[1]):
            bwd_bq, bwd_bk = block_q, block_k  # env must divide; else fwd's
        dq3, dk3, dv3 = _flash_bwd_pallas(
            _bshd_to_3d(q), _bshd_to_3d(k), _bshd_to_3d(v), _bshd_to_3d(o),
            lse, _bshd_to_3d(do), scale=scale, causal=causal,
            block_q=bwd_bq, block_k=bwd_bk, interpret=interpret)
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
    """The forward with a window band; it has no backward."""
    return _flash_fwd_rule(q, k, v, scale, True, block_q, block_k,
                           interpret, window)[0]


def _no_window_bwd(scale, block_q, block_k, interpret, window, res, do):
    raise NotImplementedError(
        "the flash backward has no window band: dq and dk/dv walk the "
        "whole causal triangle (models.transformer.transformer_lm_loss "
        "refuses a windowed block)")


_flash_window.defvjp(
    lambda q, k, v, *static: (_flash_window(q, k, v, *static), None),
    _no_window_bwd)


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
    which every row has one, none ahead of it. Both the forward's
    alone."""
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
# Paged decode attention (the ragged-paged shape of this kernel family)
#
# Autoregressive serving keeps each sequence's K/V in fixed-size BLOCKS of a
# preallocated pool ([num_blocks, block_size, H, D]); a per-sequence block
# table maps logical positions to pool blocks, so sequences of ragged
# lengths share one pool with no per-sequence reallocation (the "Ragged
# Paged Attention" kernel shape, PAPERS.md). One decode step scores ONE new
# query token per sequence against that sequence's pages.
#
# Two paths, same contract as the training kernel above:
#   * Pallas TPU kernel — one invocation, no grid over the table. The pools
#     stay in HBM (`ANY`); the block table and the context lengths ride in
#     scalar-prefetch refs. The kernel walks the sequences and, of each,
#     only its LIVE pages, in COMPUTE BLOCKS of P consecutive table entries
#     (P x block_size tokens): a block's K and V pages come in by one
#     `make_async_copy` a live page into one of two VMEM tiles, and the
#     next block's copies (the same sequence's, or the first block of the
#     next live sequence) are in flight while this one is scored. A table
#     entry past ceil(len/bs) costs nothing: no grid step, no DMA. The
#     online-softmax state lives in registers and is updated once a block;
#     the one masked tail is the sequence's last block.
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


def _paged_walk(bt_ref, len_ref, pools, bufs, sem, next_ref, *,
                block_size, block_pages, begin, block_fn, finish,
                source=None, first_page=None):
    """The walk the paged kernels share: every sequence, its live
    compute blocks only, the next block's page copies in flight while
    this one is scored. `pools` are the HBM pools and `bufs` their
    double-buffered VMEM tiles [2, block_pages, ...page]; `sem` is
    [len(pools), 2]. What is computed on a block is the caller's:
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

    def each_live_page(s, b, slot, act):
        """`act` ("start" or "wait") the copies of block b of sequence s
        into tile `slot`: one copy a pool and live page, none for a
        page past the sequence's last. A wait names the same copies as
        its start."""
        pages, first = walked(s)
        live = pages - b * block_pages
        for j in range(block_pages):
            @pl.when(j < live)
            def _():
                page = bt_ref[s, first + b * block_pages + j]
                for which, (pool, buf) in enumerate(zip(pools, bufs)):
                    getattr(pltpu.make_async_copy(
                        source(pool, page), buf.at[slot, j],
                        sem.at[which, slot]), act)()

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
        each_live_page(jnp.minimum(first, s_n - 1), 0, 0, "start")

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
                each_live_page(jnp.minimum(ahead_s, s_n - 1), ahead_b,
                               1 - slot, "start")

            each_live_page(s, b, slot, "wait")
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
                        block_pages, window, mxu_dtype):
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
    tiles: the caller keeps the lanes of the head's own K/V head."""
    s_n, h, d = q_ref.shape
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
                             state, scale=scale, mxu_dtype=mxu_dtype)

    def finish(s, state):
        _, l, acc = state
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

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
    if hk != h or window is not None:
        # shared K/V heads, or a window: the MXU form, a block in whole
        # lane tiles of score columns
        block_pages = paged_sparse_block_pages(bs, hk, d, k_pool.dtype,
                                               block_tables.shape[1])
        kernel = functools.partial(
            _paged_group_kernel, scale=scale, block_size=bs,
            block_pages=block_pages, window=window,
            mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    else:
        block_pages = paged_block_pages(bs, hk, d, k_pool.dtype,
                                        block_tables.shape[1])
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
        )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
          q, k_pool, v_pool)
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
    block_pages = paged_latent_block_pages(bs, w, pool.dtype,
                                           block_tables.shape[1])
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
        )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
          q, pool)


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
    """`paged_kv_update` for a pool of one row a token ([NB, BS, W])."""
    pool = jnp.asarray(pool)
    blk, off = _new_row_index(pool.shape[1], block_tables, context_lens)
    return pool.at[blk, off].set(row_new.astype(pool.dtype))


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
# the scalar-prefetched row ids, where it is sparse. The scalar core issues
# a copy in about 13 ns whatever its size, so a row copy moves 150 GB/s and
# a page copy is bound by the HBM).
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
    block_pages = paged_latent_block_pages(bs, w, pool.dtype, mb)
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
        )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
          q_index, weights[..., None], pool)
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
#: PERF.md section 6, PR 44): a page 0.0869 us of a call (a block of 32:
#: 64 copies issued and waited on, and four six-pass products of 8 heads
#: over the block's 512 rows, 1.4 us alone, 2.9 us together where the
#: HBM needs 2.6), a row 0.0550 us (4 scalar DMA operations of 13 ns):
#: 1.58. The walks cross at 20.5 k rows a slot. It was 2.1 (a page 0.118
#: us, PR 34) while a block scored every head against every K/V head's
#: rows and masked.
_SPARSE_PAGE_ROW_COPIES = 1.6


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


def _sparse_block(q, k_tile, v_tile, admitted, state, *, scale, mxu_dtype):
    """A compute block of the kernels of shared K/V heads, any walk's:
    `k_tile` and `v_tile` are the block's VMEM tiles [.., H_kv, D], q is
    [H, D], K/V head g read by the H / H_kv query heads from g H / H_kv
    on. One product a K/V head scores that head's group against that
    head's rows alone (`_group_rows`), the groups' scores laid one under
    the other as ONE [H, rows] array: one online-softmax update, no score
    of a head against another group's rows is computed or masked.
    `admitted` [1, rows] says which ROWS count (every head reads the same
    rows of its own K/V head); a row not admitted has probability 0. The
    values the same way, a product a K/V head."""
    m_prev, l_prev, acc = state
    groups = k_tile.shape[-2]
    per = q.shape[0] // groups
    heads = [slice(g * per, (g + 1) * per) for g in range(groups)]
    # the scores whole in float32: their error enters the softmax
    # multiplied by their own size (`ops/attention_ops.py` `_CHOOSING`);
    # the values below in `mxu_dtype`
    sc = jnp.concatenate([jax.lax.dot_general(
        q[mine], _group_rows(k_tile, g).astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
        for g, mine in enumerate(heads)], axis=0) * scale   # [H, rows]
    sc = jnp.where(admitted, sc, DEFAULT_MASK_VALUE)
    m_next = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.where(admitted, jnp.exp(sc - m_next), 0.0)      # [H, rows]
    pv = jnp.concatenate([jax.lax.dot_general(
        p[mine].astype(mxu_dtype), _group_rows(v_tile, g).astype(mxu_dtype),
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
        )(table.astype(jnp.int32), lens.astype(jnp.int32), *operands,
          k_pool, v_pool)


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
                          window: Optional[int] = None, selected=None):
    """Public entry: picks the Pallas kernel on TPU, XLA reference else.
    `window` (causal only): row t reads the keys s with t - s < window.
    `selected` [B, Sq, Sk] (causal only): row t reads the keys s where
    it is not 0 (`flash_attention`); off the chip a bias.
    k and v may hold fewer heads than q.

    bias (additive mask) forces the reference path — the kernel handles the
    causal structure itself and arbitrary bias tiles would defeat the
    block-skip.
    """
    if bias is None and _tpu_ok(q, k, causal):
        import os
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
        sq, sk = q.shape[1], k.shape[1]
        bq = int(os.environ.get("FLASH_BLOCK_Q", 0)) or \
            _default_block(sq)
        bk = int(os.environ.get("FLASH_BLOCK_K", 0)) or \
            _default_block(sk)
        if sq % bq or sk % bk:
            raise ValueError(
                f"flash block sizes must divide the sequence dims: "
                f"block_q={bq} vs sq={sq}, block_k={bk} vs sk={sk} "
                "(FLASH_BLOCK_Q/FLASH_BLOCK_K override)")
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=bq, block_k=bk, window=window,
                               selected=selected)
    if selected is not None:
        hidden = jnp.where(selected != 0, 0.0, DEFAULT_MASK_VALUE)[:, None]
        bias = hidden if bias is None else bias + hidden
    return mha_reference(q, k, v, bias, causal=causal, scale=scale,
                         window=window)
