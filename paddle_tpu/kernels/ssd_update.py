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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _HAS_PLTPU, pltpu

#: the scoped VMEM the call asks for: a slot's state in and out, each
#: double-buffered (4 x 2.1 MB at 64 heads of [64, 128]), and room for
#: the columns and the compiler's temporaries
_VMEM_LIMIT = 48 << 20


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


def _ssd_update_kernel(ids_ref, n_ref, cols_ref, bc_ref, s_ref, o_ref,
                       y_ref, *, heads, groups):
    """One live slot: cols [1, 2, P, H] (a head's decay down its column,
    and dt x), bc [1, 2, G, N] (B, C), the state [1, H, P, N] in `s_ref`
    and out `o_ref` (the same array), y [1, P, H] out."""
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _():
        rep = heads // groups
        for h in range(heads):
            g = h // rep
            s = cols_ref[0, 0, :, h:h + 1] * s_ref[0, h] \
                + cols_ref[0, 1, :, h:h + 1] * bc_ref[0, 0, g:g + 1, :]
            o_ref[0, h] = s
            y_ref[0, :, h:h + 1] = jnp.sum(
                s * bc_ref[0, 1, g:g + 1, :], axis=-1, keepdims=True)

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
    f32 = jnp.float32
    # the live slots' ids first, in order; behind them the last live one
    # again (slot 0 where none is live)
    n_live = jnp.sum(live, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    ids = jnp.where(jnp.arange(slots, dtype=jnp.int32) < n_live, order, last)
    decay = jnp.exp(dt.astype(f32) * a.astype(f32))         # [S, H]
    cols = jnp.stack([
        jnp.broadcast_to(decay[:, None, :], (slots, p, heads)),
        jnp.swapaxes(dt.astype(f32)[:, :, None] * x.astype(f32), 1, 2)],
        axis=1)                                             # [S, 2, P, H]
    bc = jnp.stack([b, c], axis=1).astype(f32)              # [S, 2, G, N]

    def slot4(i, ids, n):
        return (ids[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[pl.BlockSpec((1, 2, p, heads), slot4),
                  pl.BlockSpec((1, 2, groups, n), slot4),
                  pl.BlockSpec((1, heads, p, n), slot4)],
        out_specs=[pl.BlockSpec((1, heads, p, n), slot4),
                   pl.BlockSpec((1, p, heads),
                                lambda i, ids, n: (ids[i], 0, 0))],
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
            # the state (input 4, the two prefetched scalars counted) is
            # output 0: updated where it lies
            input_output_aliases={4: 0},
            compiler_params=None if interpret else pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(ids, n_live[None], cols, bc, state)
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
