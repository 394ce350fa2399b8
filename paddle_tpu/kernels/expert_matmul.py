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

Three paths, chosen by ONE static plan from shapes alone
(`expert_matmul_plan`, as `flash_block_plan` and `paged_decode_plan` are):
  * `ragged_dot`: XLA's kernel, where its tile is 1 MB and the rows are
    under 256, and where either kernel of this module would ask for more
    VMEM than `_VMEM_BYTES_MAX`.
  * `_expert_matmul_pallas` (`expert_grouped_matmul` in a device trace),
    up to `_ROWS_MAX` rows where XLA's tile is 512 KB or less or the rows
    are 256 or more:
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
  * `_expert_matmul_tiled` (the same name in a trace), over `_ROWS_MAX`
    rows (a bucket's thousands of pairs; a trained share's wave of
    18,432, where the product is bound by the MXU and XLA's kernel ran at
    a fifth of it: PERF.md section 6, PR 63): the rows in tiles of 512 to
    2,048 (`_tiled_rows`), a group's matrix whole (no k loop, no column
    tile). The grid walks VISITS: a visit is one (row tile, group) pair of a
    table built from `sizes` outside the kernel (`_visit_table`: a tile
    once for each group with rows in it, `m / tm + groups - 1` visits at
    most, which is the grid), the matrix is not copied again while the
    visits stay in one group nor the output tile written back while they
    stay in one tile, and a visit multiplies only the chunks of
    `_TILED_CHUNK` rows the group owns in the tile, masked at its two
    ends: a tile two groups share costs its rows once and a chunk. Tiles
    behind `sum(sizes)` are visited once with no rows: written zeros.
    Under a derivative its backward is the module's own
    (`_expert_matmul_tiled_own`): dx is the same kernel contracting the
    matrices' SECOND axis (`expert_grouped_matmul_dx`; nothing is
    transposed in HBM), dW a kernel of its own
    (`expert_grouped_matmul_dw`: a group's [k, n] float32 resident while
    its row tiles stream, x^T g over the chunks the group owns; groups of
    no rows written zeros). The resident form's backward stays
    `ragged_dot`'s (`_expert_matmul_own`).
Left: a k loop and column tiles (a matrix that does not fit VMEM whole
beside its row tiles stays XLA's over
`_ROWS_MAX` rows: Command A+'s 64 MB, Nemotron's 22 and 25 MB, which no
cell asks for: both hold their share by waves of 2,048 rows).
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
    "ragged_dot" (XLA's kernel; `tm`, `tk`, `tn` its own tile), "pallas"
    (this module's with the rows resident; `tm` all the rows, `tk` x `tn`
    the weight tile a grid step copies) or "tiled" (this module's over
    more rows than VMEM holds; `tm` the row tile, `tk` all of k, `tn` the
    output's column tile). `xla_tile_bytes`: XLA's weight tile, what the
    rule reads up to `_ROWS_MAX` rows."""
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


#: rows of a row tile of the tiled form: the widest that divides a
#: product's rows and keeps its call inside `_VMEM_BYTES_MAX`. On a v5e
#: the serve buckets' products (128 groups of some 300 rows: the groups
#: set the visits) read 5-12% faster at 2,048 than at 512, the trained
#: wave's (16 groups of a thousand) the same at both
#: (`tools/expert_matmul_sweep.py`; PERF.md section 6, PR 63)
_TILED_ROW_TILES = (2048, 1024, 512)

#: rows one multiplication of a visit takes: 128 read 3-7% faster than
#: 256 and 8-20% faster than 512 at every shape timed (a group's two
#: ends cost a chunk each at most; the same sweep)
_TILED_CHUNK = 128


def _tiled_vmem_bytes(tm, k, n, chunk, itemsize, w_itemsize):
    """The scoped VMEM a row-tiled product [tm, k] x [k, n] needs: the
    row tile, the matrix and the output tile, each twice (the pipeline's
    buffers), the matrix's copy in bfloat16 where it is stored in
    float32, a chunk's product in float32 and its rows, and the margin."""
    return 2 * tm * (k + n) * itemsize + 2 * k * n * w_itemsize \
        + (k * n * 2 if w_itemsize == 4 else 0) \
        + chunk * (4 * n + 2 * k) + _VMEM_MARGIN


def _dw_vmem_bytes(tm, k, n, chunk, itemsize):
    """The scoped VMEM the dW kernel needs: the group's [k, n] float32
    and the two row tiles, each twice, a chunk's product (as large as the
    output block) and its masked rows, and the margin."""
    return 2 * 4 * k * n + 2 * tm * (k + n) * itemsize + 4 * k * n \
        + chunk * 2 * (k + n) + _VMEM_MARGIN


def _tiled_rows(product, rows, k, n, itemsize):
    """The row tile of the row-tiled form of rows [rows, k] x [groups, k,
    n] ("product"), of its transpose in the rows ("dx": [rows, n] through
    the matrices' second axis) or in the matrices ("dw": [groups, k, n]
    float32): whole lane tiles in both widths, a group's matrix whole (no
    k loop, no column tile), the widest of `_TILED_ROW_TILES` that divides
    the rows and keeps the call's VMEM at most `_VMEM_BYTES_MAX`, at the
    matrices' item size (the rows of a call are bfloat16 where the
    matrices are, float32 otherwise). None: no such tile (the product
    stays XLA's)."""
    if k % 128 or n % 128:
        return None
    for tm in _TILED_ROW_TILES:
        need = _dw_vmem_bytes(tm, k, n, _TILED_CHUNK, itemsize) \
            if product == "dw" else _tiled_vmem_bytes(
                tm, k, n, _TILED_CHUNK, itemsize, itemsize)
        if rows % tm == 0 and need <= _VMEM_BYTES_MAX:
            return tm
    return None


def expert_matmul_plan(rows, k, n, groups, dtype) -> ExpertMatmulPlan:
    """The plan of one grouped product `[rows, k] x [groups, k, n]`, from
    its shapes alone: XLA's weight tile by XLA's own rule times the item
    size. Up to `_ROWS_MAX` rows this module's resident kernel where that
    tile is 512 KB or less, or the rows are 256 or more (and one row tile
    of whole sublanes, the widths whole lane tiles, the call's VMEM at
    most `_VMEM_BYTES_MAX`); over them its row-tiled form where
    `_tiled_rows` has a row tile for it; `ragged_dot` elsewhere."""
    dtype = jnp.dtype(dtype)
    xk, xn = _xla_tile(k), _xla_tile(n)
    xla_bytes = xk * xn * dtype.itemsize
    if rows > _ROWS_MAX:
        tm = _tiled_rows("product", rows, k, n, dtype.itemsize)
        if tm is not None:
            return ExpertMatmulPlan(rows, k, n, groups, "tiled", tm, k, n,
                                    xla_bytes)
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


def _ragged_dot_transposes(rows, w, sizes, g):
    """(dx, dW) of the cotangent g through XLA's own transposes."""
    _, transposes = jax.vjp(lambda x, m: jax.lax.ragged_dot(
        x, m.astype(x.dtype), sizes), rows, w)
    return transposes(g)


def _expert_matmul_own_bwd(tk, tn, interpret, saved, g):
    return (*_ragged_dot_transposes(*saved, g), None)


_expert_matmul_own.defvjp(_expert_matmul_own_fwd, _expert_matmul_own_bwd)


# ---------------------------------------------------------------------------
# the row-tiled form: more rows than VMEM holds, and its two transposes
# ---------------------------------------------------------------------------

def _visit_table(sizes, m, tm, every):
    """The (row tile, group) pairs a row-tiled call walks, `m // tm +
    groups - 1` of them (static: a group's rows are one run, so every
    group after the first adds at most one visit to the tiles'), as five
    int32 tables [visits]: the row tile, the group, the group's first and
    last-plus-one row INSIDE the tile, and 1 where the visit is the first
    of its output block. `every`:
      "tile"   (the product, dx: the output is a row tile) every row tile
               is visited, once for each group with rows in it, in order;
               a tile behind `sum(sizes)` once with no rows (it is written
               zeros); a group of no rows never.
      "group"  (dW: the output is a group's matrix) every group is
               visited, once for each tile its rows lie in; a group of no
               rows once with no rows (zeros); a tile behind the groups
               never.
    The visits left over repeat the last one with no rows: the same
    blocks, no copy, nothing multiplied."""
    groups = sizes.shape[0]
    tiles = m // tm
    visits = tiles + groups - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    span = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm,
                     1 if every == "group" else 0)
    behind = jnp.cumsum(span)
    walked = behind[-1]
    v = jnp.arange(visits, dtype=jnp.int32)
    # a visit past the walked ones repeats the last of them (no copy)
    at = jnp.minimum(v, jnp.maximum(walked - 1, 0))
    g = jnp.minimum(jnp.searchsorted(behind, at, side="right"),
                    groups - 1).astype(jnp.int32)
    tile = starts[g] // tm + at - (behind[g] - span[g])
    if every == "tile":
        # behind the walked visits the tiles that hold no group's row,
        # then the last tile again
        tile = jnp.where(v < walked, tile,
                         (ends[-1] + tm - 1) // tm + v - walked)
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    real = v < walked
    lo = jnp.where(real, jnp.clip(starts[g] - tile * tm, 0, tm), 0)
    hi = jnp.where(real, jnp.clip(ends[g] - tile * tm, 0, tm), 0)
    block = tile if every == "tile" else g
    first = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (block[1:] != block[:-1]).astype(jnp.int32)])
    return tile, g, lo, hi, first


def _sublanes(dtype):
    """Rows of one tile of a dtype (8 of 32 bits, 16 of 16): what a
    dynamic row offset into a block is a multiple of."""
    return 32 // jnp.dtype(dtype).itemsize


def _owned_chunks(lo, hi, tm, chunk, align, body):
    """`body(at, mine)` over the chunks of `chunk` rows that cover rows
    lo .. hi - 1 of a tile of `tm`, from the `align`-row boundary under
    lo: `at` the chunk's first row, `mine` [chunk, 1] the rows of it that
    are the group's and that no chunk before covered (a chunk that would
    run past the tile starts earlier instead)."""
    first = lo // align * align

    def one(c, carry):
        start = first + c * chunk
        at = pl.multiple_of(jnp.minimum(start, tm - chunk), align)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        body(at, (row >= jnp.maximum(lo, start)) & (row < hi))
        return carry

    jax.lax.fori_loop(0, jnp.where(hi > lo, (hi - first + chunk - 1)
                                   // chunk, 0), one, 0)


def _tiled_kernel(tile_ref, gid_ref, lo_ref, hi_ref, first_ref, x_ref, w_ref,
                  o_ref, *scratch, tm, chunk, transposed):
    """One visit: `x_ref` its row tile [tm, k], `w_ref` its group's
    matrix ([k, n]; transposed [n, k]: the product contracts the matrix's
    SECOND axis, which is dx), `o_ref` the row tile's output [tm, n],
    resident while the visits stay in the tile; `scratch`: the matrix in
    the operands' dtype, where it is stored in another."""
    v = pl.program_id(0)

    @pl.when(first_ref[v] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    if scratch:
        wb_ref, = scratch

        @pl.when((v == 0) | (gid_ref[v] != gid_ref[jnp.maximum(v - 1, 0)]))
        def _():
            wb_ref[...] = w_ref[...].astype(wb_ref.dtype)
    else:
        wb_ref = w_ref
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    def product(at, mine):
        x = x_ref[pl.ds(at, chunk), :].astype(wb_ref.dtype)
        part = jax.lax.dot_general(x, wb_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        old = o_ref[pl.ds(at, chunk), :]
        o_ref[pl.ds(at, chunk), :] = jnp.where(
            mine, part.astype(o_ref.dtype), old)

    _owned_chunks(lo_ref[v], hi_ref[v], tm, chunk,
                  _sublanes(x_ref.dtype), product)


@functools.partial(jax.jit, static_argnames=(
    "tm", "chunk", "transposed", "interpret"))
def _expert_matmul_tiled(rows, w, sizes, *, tm, chunk=_TILED_CHUNK,
                         transposed=False, interpret=False):
    """rows [m, k] x w[g] ([groups, k, n]; transposed: [groups, n, k],
    contracted over its second axis) -> [m, n] in the rows' dtype, the
    rows in tiles of `tm`."""
    # jitted so that a model's layers share one lowering of the kernel
    m, k = rows.shape
    n = w.shape[1] if transposed else w.shape[2]
    operand = _operand_dtype(rows.dtype)
    table = _visit_table(sizes, m, tm, "tile")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=table[0].shape,
        in_specs=[pl.BlockSpec((tm, k), lambda v, tile, *_: (tile[v], 0)),
                  pl.BlockSpec((None,) + w.shape[1:],
                               lambda v, tile, gid, *_: (gid[v], 0, 0))],
        out_specs=pl.BlockSpec((tm, n), lambda v, tile, *_: (tile[v], 0)),
        scratch_shapes=[pltpu.VMEM(w.shape[1:], operand)]
        if w.dtype != operand else [],
    )
    return pl.pallas_call(
        functools.partial(_tiled_kernel, tm=tm, chunk=chunk,
                          transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_tiled_vmem_bytes(
                tm, k, n, chunk, rows.dtype.itemsize, w.dtype.itemsize)),
        interpret=interpret,
        # both names hold the family's: the expert rooflines read by it
        name="expert_grouped_matmul_dx" if transposed
        else "expert_grouped_matmul",
    )(*table, rows, w)


def _dw_kernel(tile_ref, gid_ref, lo_ref, hi_ref, first_ref, x_ref, g_ref,
               o_ref, *, tm, chunk):
    """One visit of the grouped product's other transpose: `x_ref` [tm,
    k] and `g_ref` [tm, n] its row tile of the rows and of the cotangent,
    `o_ref` its group's [k, n] float32, resident while the visits stay in
    the group: x^T g over the group's own rows of the tile is added to
    it. Rows that are not the group's are zeroed in BOTH operands (what
    lies behind the groups may be anything, NaN included)."""
    v = pl.program_id(0)

    @pl.when(first_ref[v] == 1)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    operand = _operand_dtype(x_ref.dtype)

    def product(at, mine):
        x = jnp.where(mine, x_ref[pl.ds(at, chunk), :], 0).astype(operand)
        g = jnp.where(mine, g_ref[pl.ds(at, chunk), :], 0).astype(operand)
        o_ref[...] += jax.lax.dot_general(
            x, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _owned_chunks(lo_ref[v], hi_ref[v], tm, chunk,
                  _sublanes(x_ref.dtype), product)


@functools.partial(jax.jit, static_argnames=("tm", "chunk", "interpret"))
def _expert_matmul_dw(rows, g, sizes, *, tm, chunk=_TILED_CHUNK,
                      interpret=False):
    """dW[i] = rows[group i]^T . g[group i]: rows [m, k], g [m, n] ->
    [groups, k, n] float32; a group of no rows is written zeros."""
    m, k = rows.shape
    n = g.shape[1]
    table = _visit_table(sizes, m, tm, "group")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=table[0].shape,
        in_specs=[pl.BlockSpec((tm, k), lambda v, tile, *_: (tile[v], 0)),
                  pl.BlockSpec((tm, n), lambda v, tile, *_: (tile[v], 0))],
        out_specs=pl.BlockSpec((None, k, n),
                               lambda v, tile, gid, *_: (gid[v], 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), jnp.float32),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_dw_vmem_bytes(tm, k, n, chunk,
                                            rows.dtype.itemsize)),
        interpret=interpret,
        name="expert_grouped_matmul_dw",
    )(*table, rows, g)


def _tiled_call(product, a, b, sizes, interpret):
    """One row-tiled call at `_tiled_rows`' row tile ("product": rows,
    matrices; "dx": cotangent, matrices; "dw": rows, cotangent), None
    where it has none for the shapes; the transposes leave their own
    record in the trace ring (the product's is `expert_matmul`'s)."""
    m = a.shape[0]
    groups, k, n = (sizes.shape[0], a.shape[1], b.shape[1]) \
        if product == "dw" else b.shape
    tm = _tiled_rows(product, m, k, n, b.dtype.itemsize)
    if product != "product":
        obs_trace.phase("kernel", "expert_matmul_plan", 0.0, attrs=dict(
            product=product, rows=m, k=k, n=n, groups=groups,
            form="tiled" if tm else "ragged_dot", tm=tm or 0,
            chunk=_TILED_CHUNK if tm else 0,
            visits=m // tm + groups - 1 if tm else 0))
    if tm is None:
        return None
    if product == "dw":
        return _expert_matmul_dw(a, b, sizes, tm=tm, interpret=interpret)
    return _expert_matmul_tiled(a, b, sizes, tm=tm,
                                transposed=product == "dx",
                                interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _expert_matmul_tiled_own(rows, w, sizes, interpret):
    """The row-tiled form under a derivative: the backward is the
    module's own two transposes (`expert_grouped_matmul_dx`, `_dw`), each
    where `_tiled_rows` has a row tile for it and `ragged_dot`'s
    elsewhere."""
    return _tiled_call("product", rows, w, sizes, interpret)


def _expert_matmul_tiled_own_fwd(rows, w, sizes, interpret):
    return _tiled_call("product", rows, w, sizes, interpret), (rows, w, sizes)


def _expert_matmul_tiled_own_bwd(interpret, saved, g):
    rows, w, sizes = saved
    dx = _tiled_call("dx", g, w, sizes, interpret)
    dw = _tiled_call("dw", rows, g, sizes, interpret)
    if dx is None or dw is None:
        xla = _ragged_dot_transposes(rows, w, sizes, g)
        dx = xla[0] if dx is None else dx
        dw = xla[1] if dw is None else dw
    return dx, dw.astype(w.dtype), None


_expert_matmul_tiled_own.defvjp(_expert_matmul_tiled_own_fwd,
                                _expert_matmul_tiled_own_bwd)


def expert_matmul(rows, w, sizes, *, interpret: bool = False):
    """Public entry of the grouped product (the module's text): rows [m,
    k] sorted by group, `sizes` [groups] rows each, w [groups, k, n] as
    stored -> [m, n] in the rows' dtype. By the plan, one of this module's
    two kernels on a TPU (or interpreted), `jax.lax.ragged_dot` elsewhere;
    the plan is left in the trace ring (`kernel/expert_matmul_plan`) each
    time the call is traced, a tiled one with its chunk's rows and its
    static count of visits."""
    m, k = rows.shape
    groups, _, n = w.shape
    plan = expert_matmul_plan(m, k, n, groups, w.dtype)
    attrs = plan._asdict()
    if plan.form == "tiled":
        attrs.update(product="product", chunk=_TILED_CHUNK,
                     visits=m // plan.tm + groups - 1)
    obs_trace.phase("kernel", "expert_matmul_plan", 0.0, attrs=attrs)
    if plan.form != "ragged_dot" and _HAS_PLTPU and (
            interpret or jax.default_backend() == "tpu"):
        if plan.form == "tiled":
            return _expert_matmul_tiled_own(rows, w, sizes, interpret)
        return _expert_matmul_own(rows, w, sizes, plan.tk, plan.tn,
                                  interpret)
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes)
