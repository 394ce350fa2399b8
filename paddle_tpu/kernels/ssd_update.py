"""The decode step's Mamba-2 state update: one token a slot moves that
slot's state, a matrix a HEAD, a row on and reads it.

    S'[h] = exp(dt[h] A[h]) S[h] + (dt[h] x[h]) (x) B[g(h)]      [P, N]
    y[h]  = S'[h] C[g(h)]                                        [P]

with H heads of P channels, G groups of heads that share B and C (head h
reads group h // (H / G)) and N state columns, on the lanes. The state is
ALL the call moves: 2 x 4 H P N bytes a live slot (2.1 MB read and as
much written at 64 heads of [64, 128]); x, B, C and dt are a few KB.

Two paths, as the paged kernels have (`paged_attention.py`):
  * `_ssd_update_pallas`: ONE Pallas call over the live slots. The state
    array is the call's input AND its output (`input_output_aliases`): a
    live slot's [H, P, N] comes into VMEM as one block, is updated head
    by head and goes back to where it came from; a slot that is not live
    is never read or written, so the call's bytes follow the live slots.
    The grid walks the live slots' ids (scalar-prefetched, the dead
    steps behind them naming the last live slot again: the same block
    index, so no copy either way).
  * `ssd_update_reference`: the same in `jax.numpy`, the CPU path and the
    numerics oracle.

What the body does to a head's [P, N] (P / 8 vregs a lane tile; 512 state
vregs a slot at both callers' shapes), and what it costs in cross-lane
operations, which are what the body costs on a v5e (`ssd_update_plan` has the
counts; PERF.md section 6, PR 57 the measurements):
  * the decay `exp(dt[h] A[h])` is ONE number a (slot, head): it is
    scalar-prefetched `[S, H]` beside the ids and multiplies the head's
    vregs as a scalar operand (until PR 57 it came as a `[P, H]` array of
    columns and cost a lane broadcast a state vreg);
  * `dt x` comes as columns `[P, H]` (p on the sublanes, as the state has
    it) and is spread over the lanes, ONE lane broadcast a state vreg:
    the only cross-lane operation left; `decay * S + (dt x) * B` is
    float32 on the vector unit, the same operations in the same order
    since PR 51, so the state is the same bits;
  * `y[p] = sum_n S'[p, n] C[n]`: the products are float32 on the vector
    unit; the SUM over the lanes runs on the MXU, which is idle here:
    `T = S' * C` is split exactly into three bfloat16 parts (`T = t1 +
    t2 + t3`, 8 + 8 + 8 bits of its mantissa), laid side by side and
    multiplied by ones, `[P, 3 N] x [3 N, 128]`, accumulated in float32:
    a float32 sum of the same float32 products in another order, and no
    lane reduction (the cross-lane unit's reduction was 7-13 cycles a
    state vreg of the 19 a slot's copies allow, and the body with it
    longer than the copies; a shared roll-and-add tree cost three times
    the whole call: a lane rotation is no cheaper). Every
    lane of the product holds the row's sum, so y is gathered with one
    select a vreg into a `[P, H]` tile (head h in lane h) that is stored
    once a slot, not a one-lane store a vreg.
B and C rows are read where a head needs them: holding a group's rows
across its heads moved nothing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..obs import trace as obs_trace
from .flash_attention import _HAS_PLTPU, pltpu

#: VMEM the call asks for beyond what its blocks take (`_vmem_bytes`): the
#: compiler's own temporaries (a head's products in three bfloat16 parts,
#: the ones, what it spills). The whole is kept near the need, as
#: `expert_matmul.py`'s is: what a call inside a step reserves XLA cannot
#: keep the step's prefetched operands in (PERF.md section 7, PR 51)
_VMEM_MARGIN = 8 << 20


class SsdUpdatePlan(NamedTuple):
    """What one call does at its shapes, static: `rep` heads share one B
    and one C row; a slot's state is `state_vregs` vregs and its block
    costs `lane_broadcasts` cross-lane operations (`dt x`, one a state
    vreg; the decay none: `decay`) and `mxu_products` products `[P, 3 N]
    x [3 N, 128]` (`reduction` names the form of y's sum: the one arm,
    both callers' shapes read their copies alone with it); `vmem_bytes`
    is what the call asks for."""
    heads: int
    groups: int
    p: int
    n: int
    rep: int
    state_vregs: int
    decay: str
    reduction: str
    lane_broadcasts: int
    mxu_products: int
    vmem_bytes: int


def _vmem_bytes(heads, groups, p, n):
    """The scoped VMEM a call needs: a slot's state in and out, the `dt
    x` columns, the B and C rows and the y tile, each twice (the
    pipeline's buffers; a block's last two dimensions in whole (8, 128)
    tiles), and the margin: 4 x 2.1 MB + 0.15 MB + 8 MB = 16.9 MB at 64
    heads of [64, 128] in 8 groups, 17.1 MB at 32 heads of [128, 128] in
    32 (48 MB until PR 57, whatever the shapes)."""
    tile = lambda rows, cols: 4 * -(-rows // 8) * 8 * -(-cols // 128) * 128
    blocks = 2 * heads * tile(p, n) + 2 * tile(p, heads) \
        + 2 * tile(groups, n)
    return 2 * blocks + _VMEM_MARGIN


def ssd_update_plan(heads, groups, p, n):
    """The static plan of `_ssd_update_pallas` at a caller's shapes (the
    class's text). One arm: what differs between the callers (8 heads a
    group of [64, 128] at Nemotron, 1 of [128, 128] at MiniCPM-SALA) is
    counts, not form."""
    vregs = heads * (p // 8) * (n // 128)
    return SsdUpdatePlan(
        heads=heads, groups=groups, p=p, n=n, rep=heads // groups,
        state_vregs=vregs, decay="smem_scalar", reduction="mxu_split3",
        lane_broadcasts=vregs, mxu_products=heads,
        vmem_bytes=_vmem_bytes(heads, groups, p, n))


def ssd_update_reference(state, x, dt, a, b, c, live):
    """state [S, H, P, N]; x [S, H, P]; dt [S, H] (the step, after its
    softplus); a [H] (negative); b, c [S, G, N]; live [S] bool ->
    (y [S, H, P], zeros for a slot that is not live; the state a row on,
    a slot that is not live as it was)."""
    rep = x.shape[1] // b.shape[1]
    bh = jnp.repeat(b, rep, axis=1)[:, :, None, :]          # [S, H, 1, N]
    ch = jnp.repeat(c, rep, axis=1)[:, :, None, :]
    moved = jnp.exp(dt * a)[:, :, None, None] * state \
        + (dt[:, :, None] * x)[..., None] * bh
    y = jnp.sum(moved * ch, axis=-1)
    on = live[:, None, None]
    return (jnp.where(on, y, 0.0).astype(x.dtype),
            jnp.where(on[..., None], moved, state).astype(state.dtype))


def _bf16_parts(t):
    """A float32 array as three bfloat16 ones that sum to it exactly:
    each takes the 8 leading bits of what the parts before left of the
    24-bit mantissa."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    t1 = t.astype(bf16)
    r1 = t - t1.astype(f32)
    t2 = r1.astype(bf16)
    return t1, t2, (r1 - t2.astype(f32)).astype(bf16)


def _lane_sums(t, ones):
    """[P, N] float32 -> [P, 128], every lane the row's sum: `t`'s three
    bfloat16 parts side by side against ones `[3 N, 128]` on the MXU,
    accumulated in float32."""
    return jnp.dot(jnp.concatenate(_bf16_parts(t), axis=1), ones,
                   preferred_element_type=jnp.float32)


def _ssd_update_kernel(ids_ref, n_ref, decay_ref, cols_ref, bc_ref, s_ref,
                       o_ref, y_ref, *, heads, groups):
    """One live slot: decay [S, H] in SMEM (the slot's row read by its
    id), cols [1, P, H] (`dt x`, a head down its column), bc [1, 2, G, N]
    (B, C), the state [1, H, P, N] in `s_ref` and out `o_ref` (the same
    array), y [1, P, H] out."""
    i = pl.program_id(0)
    p, n = s_ref.shape[2], s_ref.shape[3]

    @pl.when(i < n_ref[0])
    def _():
        slot = ids_ref[i]
        rep = heads // groups
        lane = jax.lax.broadcasted_iota(jnp.int32, (p, 128), 1)
        ones = jnp.ones((3 * n, 128), jnp.bfloat16)
        y = jnp.zeros((p, 128), jnp.float32)
        for h in range(heads):
            g = h // rep
            s = decay_ref[slot, h] * s_ref[0, h] \
                + cols_ref[0, :, h:h + 1] * bc_ref[0, 0, g:g + 1, :]
            o_ref[0, h] = s
            sums = _lane_sums(s * bc_ref[0, 1, g:g + 1, :], ones)
            y = jnp.where(lane == h % 128, sums, y)
            if h % 128 == 127 or h == heads - 1:
                first = h - h % 128
                y_ref[0, :, first:h + 1] = y[:, :h + 1 - first]

    @pl.when((n_ref[0] == 0) & (i == 0))
    def _():
        # no live slot at all: the one block the walk names goes back as
        # it came (an output block is always written)
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_update_pallas(state, x, dt, a, b, c, live, *, interpret=False):
    # jitted so that a model's layers, which all call it at one shape,
    # share one trace and one lowering of the kernel
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "ssd_update_reference")
    slots, heads, p, n = state.shape
    groups = b.shape[1]
    plan = ssd_update_plan(heads, groups, p, n)
    obs_trace.phase("kernel", "ssd_plan", 0.0, attrs=plan._asdict())
    f32 = jnp.float32
    # the live slots' ids first, in order; behind them the last live one
    # again (slot 0 where none is live)
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    ids = jnp.where(jnp.arange(slots, dtype=jnp.int32) < n_live, order, last)
    decay = jnp.exp(dt.astype(f32) * a.astype(f32))         # [S, H]
    cols = jnp.swapaxes(dt.astype(f32)[:, :, None] * x.astype(f32), 1, 2)
    bc = jnp.stack([b, c], axis=1).astype(f32)              # [S, 2, G, N]

    def slot3(i, ids, n, decay):
        return (ids[i], 0, 0)

    def slot4(i, ids, n, decay):
        return (ids[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots,),
        in_specs=[pl.BlockSpec((1, p, heads), slot3),
                  pl.BlockSpec((1, 2, groups, n), slot4),
                  pl.BlockSpec((1, heads, p, n), slot4)],
        out_specs=[pl.BlockSpec((1, heads, p, n), slot4),
                   pl.BlockSpec((1, p, heads), slot3)],
    )
    # the scope is the kernel's name in a device trace, which
    # `ssd_update_roofline` reads by
    with jax.named_scope("ssd_decode_update"):
        moved, y = pl.pallas_call(
            functools.partial(_ssd_update_kernel, heads=heads,
                              groups=groups),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                       jax.ShapeDtypeStruct((slots, p, heads), f32)],
            # the state (input 5, the three prefetched scalars counted)
            # is output 0: updated where it lies
            input_output_aliases={5: 0},
            compiler_params=None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=plan.vmem_bytes),
            interpret=interpret,
        )(ids, n_live[None], decay, cols, bc, state)
    # a slot the walk never reached holds whatever its y block held
    y = jnp.where(live[:, None, None], jnp.swapaxes(y, 1, 2), 0.0)
    return y.astype(x.dtype), moved


def ssd_decode_update(state, x, dt, a, b, c, live, *,
                      interpret: bool = False):
    """Public entry of the state update (the module's text): (y [S, H,
    P], the state a row on). Pallas on a TPU at lane-whole shapes (N a
    multiple of 128, P of 8, float32 state), the `jax.numpy` reference
    elsewhere."""
    _, _, p, n = state.shape
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and n % 128 == 0 and p % 8 == 0 \
            and state.dtype == jnp.float32:
        return _ssd_update_pallas(state, x, dt, a, b, c, live,
                                  interpret=interpret)
    return ssd_update_reference(state, x, dt, a, b, c, live)
