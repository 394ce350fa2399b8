"""The experts' grouped matmul where XLA's own reads too small a tile.

    out[r] = rows[r] . W[g(r)]        rows [m, k] sorted by group,
                                      sizes [groups], W [groups, k, n]

`jax.lax.ragged_dot` on a TPU is XLA's Mosaic grouped matmul, whose weight
tile is `[tk, tn]` with each the widest of 512 / 256 / 128 that divides k
and n: a grid step of it costs 0.2-0.33 us beside its copy, so what sets
its rate is a tile's BYTES (256 KB tiles read 49% of the HBM's rate, 512 KB
72%, 1 MB 88%: PERF.md section 6, PR 53). A product whose k is 2,688 =
21 x 128 is held to `[128, 512]` tiles, and no stored layout moves that.

Kanana's and Keye's experts are 768 wide: `[512, 256]` tiles, 512 KB.
At XLA's widest tile, `[512, 512]` float32 = 1 MB, the bytes no longer
tell the shapes apart; the ROWS do, which XLA's kernel walks in tiles of
its own and this module's keeps resident (one product alone on a v5e,
XLA's ms -> this module's: 128 / 96 rows 0.647 -> 0.645 and 0.546 ->
0.553, nothing; 256 rows 1.111 -> 1.062; 768 rows 1.133 -> 1.079; 2,048
rows 1.556 -> 1.087: PERF.md section 6, PR 55 and PR 61).

Two paths, chosen by ONE static plan from shapes alone
(`expert_matmul_plan`, as `flash_block_plan` and `paged_decode_plan` are):
  * `ragged_dot`: XLA's kernel, where its tile is 1 MB and the rows are
    under 256, where the rows are over 2,048, and where the other kernel
    would ask for more VMEM than `_VMEM_BYTES_MAX`.
  * `_expert_matmul_pallas` (`expert_grouped_matmul` in a device trace),
    where XLA's tile is 512 KB or less or the rows are 256 or more:
    the weights stay where they are stored, an operand of the kernel and
    nothing else, and come into VMEM in tiles of megabytes, each ONCE a
    product: all m rows are one row tile (a decode step's pairs: 768, a
    few a group), resident with the output's column tile, and the grid
    walks (column tile, live group, k tile). A step multiplies the
    group's rows, `_ROW_CHUNK` at a time from the 8-row boundary under
    its first, by the tile (operands in bfloat16, one pass, float32
    accumulation: what XLA's kernel does on float32 operands at the
    default precision) and adds the group's own rows of the product into
    the output tile. Groups of no rows are never walked (the steps behind
    the live groups name the last live tile again: no copy); rows behind
    `sum(sizes)` belong to no group and are written zeros.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..obs import trace as obs_trace
from .flash_attention import _HAS_PLTPU, pltpu

#: XLA's weight tile at or under which a product goes through the kernel
#: of this module whatever its rows: 256 KB tiles read half the HBM's
#: rate, 512 KB tiles (Kanana's and Keye's `[512, 256]`) 73-86% where
#: this module's read 89-91% (`tools/expert_matmul_sweep.py`; the
#: module's text)
_XLA_TILE_BYTES_MAX = 512 << 10

#: rows from which a product goes through the kernel of this module at
#: ANY tile of XLA's (its widest, 1 MB, included): XLA's kernel walks the
#: rows in tiles of its own (LFM2's step, 256 rows, 4% slower than this
#: module's; Nemotron's down product 5% at 768 rows, 30% at 2,048),
#: under it the two read the same (OLMoE's 128, Command A+'s 96)
_BIG_TILE_ROWS_MIN = 256

#: bytes of one weight tile of the kernel at most: two of them are in
#: flight (the pipeline's double buffer) beside the resident rows. On a
#: v5e every tile from 1.8 to 11 MB read the same 90-92% of the HBM's
#: rate (PERF.md section 6, PR 55), so the least VMEM of them
_TILE_BYTES_MAX = 4 << 20

#: VMEM the call asks for beyond what its blocks take (`_vmem_bytes`):
#: the compiler's own temporaries (a chunk's rows in bfloat16, its
#: product). The whole is kept near the need: what a call reserves XLA
#: cannot keep the step's prefetched weights in while it runs (asked 100
#: MB for its 32, the Nemotron step was 1.4 ms longer: the down products
#: and the fusions behind each call waited for their operands; PERF.md
#: section 6, PR 55)
_VMEM_MARGIN = 8 << 20

#: rows of a group one product of the kernel takes: a decode step's
#: groups (6 rows, from an 8-row boundary) are one product each (16, 32
#: and 64 read the same on the chip, at 768 and at 2,048 rows: PR 55)
_ROW_CHUNK = 32

#: rows (all one row tile, resident in VMEM with a column tile of the
#: output) past which the plan keeps XLA's kernel
_ROWS_MAX = 2048


class ExpertMatmulPlan(NamedTuple):
    """What one grouped product does at its shapes, static. `form`:
    "ragged_dot" (XLA's kernel; `tm`, `tk`, `tn` its own tile) or
    "pallas" (this module's; `tm` all the rows, `tk` x `tn` the weight
    tile a grid step copies). `xla_tile_bytes`: XLA's weight tile, what
    the rule reads."""
    rows: int
    k: int
    n: int
    groups: int
    form: str
    tm: int
    tk: int
    tn: int
    xla_tile_bytes: int


def _xla_tile(dim):
    """XLA's grouped matmul's tile of a dimension: the widest of 512 /
    256 / 128 that divides it (`ragged_dot_tiling` in a compiled step;
    `tests/test_chip_compile.py`), the dimension itself where none does."""
    return next((t for t in (512, 256, 128) if dim % t == 0), dim)


def _kernel_tile(k, n, itemsize, most=_TILE_BYTES_MAX):
    """The kernel's weight tile: n in the wider of 1,024 / 512 columns
    that divides it (whole where neither does and it is whole lane
    tiles: 768), k in the fewest equal parts of whole lane tiles that
    keep a tile under `most` bytes (2,688 x 2,048 float32: `[896,
    1,024]`, 3.7 MB). None: no such tile."""
    for tn in (1024, 512, n):
        if n % tn or tn % 128:
            continue
        for parts in range(1, k // 128 + 1):
            tk = k // parts
            if k % parts == 0 and tk % 128 == 0 \
                    and tk * tn * itemsize <= most:
                return tk, tn
    return None


def _vmem_bytes(m, k, tk, tn, itemsize, operand_itemsize):
    """The scoped VMEM a call needs: the rows and the output's column
    tile resident and the weight tile in flight, each twice (the
    pipeline's buffers), the tile's copy in the operands' dtype, and the
    margin (40 MB at a decode step's 768 rows of 2,688, 78 MB at a
    prefill wave's 2,048)."""
    return 2 * 4 * m * (k + tn) + 2 * tk * tn * itemsize \
        + tk * tn * operand_itemsize + _VMEM_MARGIN


#: scoped VMEM a call asks for at most (78 MB of a v5e's 128): what the
#: widest call measured asks, a prefill wave's `_ROWS_MAX` rows of 2,688
#: at `[896, 1,024]` float32 tiles (PR 55). A product that needs more
#: (a held wave's 2,048 rows of 4,096: 102 MB) stays XLA's
_VMEM_BYTES_MAX = _vmem_bytes(_ROWS_MAX, 2688, 896, 1024, 4, 2)


def _operand_dtype(dtype):
    """What the kernel multiplies: float32 rounded to bfloat16 (XLA's own
    default on float32 operands), any other dtype as it is."""
    return jnp.bfloat16 if dtype == jnp.float32 else dtype


def expert_matmul_plan(rows, k, n, groups, dtype) -> ExpertMatmulPlan:
    """The plan of one grouped product `[rows, k] x [groups, k, n]`, from
    its shapes alone: XLA's weight tile by XLA's own rule times the item
    size; this module's kernel where that tile is 512 KB or less, or the
    rows are 256 or more (and one row tile of whole sublanes, the widths
    whole lane tiles, the call's VMEM at most `_VMEM_BYTES_MAX`),
    `ragged_dot` elsewhere."""
    dtype = jnp.dtype(dtype)
    xk, xn = _xla_tile(k), _xla_tile(n)
    xla_bytes = xk * xn * dtype.itemsize
    tile = None
    if (xla_bytes <= _XLA_TILE_BYTES_MAX or rows >= _BIG_TILE_ROWS_MIN) \
            and rows % 8 == 0 and rows <= _ROWS_MAX and k % 128 == 0:
        tile = _kernel_tile(k, n, dtype.itemsize)
    if tile is not None and _vmem_bytes(
            rows, k, *tile, dtype.itemsize,
            jnp.dtype(_operand_dtype(dtype)).itemsize) > _VMEM_BYTES_MAX:
        tile = None
    if tile is None:
        return ExpertMatmulPlan(rows, k, n, groups, "ragged_dot",
                                min(rows, 256), xk, xn, xla_bytes)
    return ExpertMatmulPlan(rows, k, n, groups, "pallas", rows, *tile,
                            xla_bytes)


def _expert_matmul_kernel(gids_ref, ends_ref, live_ref, x_ref, w_ref, o_ref,
                          wb_ref, *, m, tk, chunk):
    """One (column tile j, live group i, k tile) step: `x_ref` all the
    rows [m, k], `w_ref` the group's weight tile [tk, tn], `o_ref` the
    output's column tile [m, tn] (resident while j stands), `wb_ref` the
    weight tile in the operands' dtype."""
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (kk == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < live_ref[0])
    def _():
        g = gids_ref[i]
        hi = ends_ref[g]
        lo = jnp.where(g == 0, 0, ends_ref[jnp.maximum(g - 1, 0)])
        wb_ref[...] = w_ref[...].astype(wb_ref.dtype)
        first = lo // 8 * 8
        at_k = pl.multiple_of(kk * tk, 128)

        def product(c, carry):
            start = first + c * chunk
            # a chunk that would run past the last row starts earlier
            # (still on a sublane boundary) and leaves the rows the
            # chunk before added out
            at = pl.multiple_of(jnp.minimum(start, m - chunk), 8)
            x = x_ref[pl.ds(at, chunk), pl.ds(at_k, tk)]
            part = jnp.dot(x.astype(wb_ref.dtype), wb_ref[...],
                           preferred_element_type=jnp.float32)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            mine = (row >= jnp.maximum(lo, start)) & (row < hi)
            old = o_ref[pl.ds(at, chunk), :]
            o_ref[pl.ds(at, chunk), :] = jnp.where(mine, old + part, old)
            return carry

        jax.lax.fori_loop(0, (hi - first + chunk - 1) // chunk, product, 0)


@functools.partial(jax.jit, static_argnames=("tk", "tn", "interpret"))
def _expert_matmul_pallas(rows, w, sizes, *, tk, tn, interpret=False):
    # jitted so that a model's layers, which all call it at one shape,
    # share one trace and one lowering of the kernel
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "jax.lax.ragged_dot")
    m, k = rows.shape
    groups, _, n = w.shape
    chunk = min(_ROW_CHUNK, m)
    operand = _operand_dtype(rows.dtype)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    # the groups that have rows first, in order; behind them the last of
    # them again (group 0 where none has a row: its one tile a column
    # tile is copied, and nothing is multiplied)
    n_live = jnp.sum(sizes > 0, dtype=jnp.int32)
    order = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    gids = jnp.where(jnp.arange(groups, dtype=jnp.int32) < n_live, order,
                     last)
    k_tiles = k // tk

    def weight_tile(j, i, kk, gids, ends, live):
        # a step behind the live groups names the tile the last live
        # step left resident: no copy
        return gids[i], jnp.where(i < live[0], kk, k_tiles - 1), j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, groups, k_tiles),
        in_specs=[pl.BlockSpec((m, k), lambda j, i, kk, *_: (0, 0)),
                  pl.BlockSpec((None, tk, tn), weight_tile)],
        out_specs=pl.BlockSpec((m, tn), lambda j, i, kk, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((tk, tn), operand)],
    )
    out = pl.pallas_call(
        functools.partial(_expert_matmul_kernel, m=m, tk=tk, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_vmem_bytes(
                m, k, tk, tn, w.dtype.itemsize,
                jnp.dtype(operand).itemsize)),
        interpret=interpret,
        # the kernel's name in a device trace, which the expert
        # rooflines read by (`benchmark/README.md`, "Per-layer names")
        name="expert_grouped_matmul",
    )(gids, ends, n_live[None], rows, w)
    return out.astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _expert_matmul_own(rows, w, sizes, tk, tn, interpret):
    """The kernel under a derivative: a program that trains experts at
    rows the plan gives the kernel differentiates `ragged_dot` (XLA's
    transposes of the same product), the forward stays the kernel's."""
    return _expert_matmul_pallas(rows, w, sizes, tk=tk, tn=tn,
                                 interpret=interpret)


def _expert_matmul_own_fwd(rows, w, sizes, tk, tn, interpret):
    return _expert_matmul_pallas(rows, w, sizes, tk=tk, tn=tn,
                                 interpret=interpret), (rows, w, sizes)


def _expert_matmul_own_bwd(tk, tn, interpret, saved, g):
    rows, w, sizes = saved
    _, transposes = jax.vjp(lambda x, m: jax.lax.ragged_dot(
        x, m.astype(x.dtype), sizes), rows, w)
    return (*transposes(g), None)


_expert_matmul_own.defvjp(_expert_matmul_own_fwd, _expert_matmul_own_bwd)


def expert_matmul(rows, w, sizes, *, interpret: bool = False):
    """Public entry of the grouped product (the module's text): rows [m,
    k] sorted by group, `sizes` [groups] rows each, w [groups, k, n] as
    stored -> [m, n] in the rows' dtype. By the plan, this module's
    kernel on a TPU (or interpreted), `jax.lax.ragged_dot` elsewhere; the
    plan is left in the trace ring (`kernel/expert_matmul_plan`) each
    time the call is traced."""
    m, k = rows.shape
    groups, _, n = w.shape
    plan = expert_matmul_plan(m, k, n, groups, w.dtype)
    obs_trace.phase("kernel", "expert_matmul_plan", 0.0,
                    attrs=plan._asdict())
    if plan.form == "pallas" and _HAS_PLTPU and (
            interpret or jax.default_backend() == "tpu"):
        return _expert_matmul_own(rows, w, sizes, plan.tk, plan.tn,
                                  interpret)
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)
