"""Paged decode attention over SELECTED BLOCKS of a K/V head.

One query token a slot; of each K/V head the step has chosen a list of
whole blocks of the slot's rows (`ops.block_sparse_ops`: the first, the
local ones and the highest-scored of the rest), one list a K/V head,
shared by the query heads that read that head. A block is a PAGE of the
paged pools, so a selected block of one K/V head is one copy: the pools
hold a token's K (or V) heads side by side in the row's lanes ([NB, BS,
H_kv D]), and the copy takes the page's rows at that head's lanes alone.

    pages [S, H_kv, W] int32    the selected blocks' pool pages, the
                                sequence's order kept (so the last is the
                                block the query sits in, partly filled)
    rows  [S, H_kv]    int32    the rows those blocks hold: the softmax
                                is over the first `rows` of the selected
                                blocks laid end to end; 0: nothing read

Two paths, as `paged_attention.py` has:
  * the Pallas kernel: `_paged_walk` over S x H_kv "sequences" (a slot's
    K/V head each, its table the selected pages, its length `rows`), a
    block of the walk P pages of ONE head, scored by `_sparse_block` as
    one product of the head's H / H_kv query heads against its rows.
    Nothing of a page that was not selected is read, and nothing of the
    other K/V heads of one that was.
  * `block_sparse_attention_reference`: gathers, in `jax.numpy`; the CPU
    path and the numerics oracle.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _HAS_PLTPU, DEFAULT_MASK_VALUE, pltpu
from .paged_attention import (_lane_rows, _paged_walk, _pool_ids,
                              _sparse_block, _walk_compiler_params,
                              _whole_lane_tiles, paged_block_pages)


def block_sparse_block_pages(block_size, head_dim, dtype, table_width):
    """P, the selected pages of one compute block of the walk: what the
    double-buffered K and V tiles of ONE head's page fit of the paged
    kernels' budget (`paged_block_pages`), in whole lane tiles of score
    columns, never more than the selection's width."""
    return _whole_lane_tiles(
        paged_block_pages(block_size, 1, head_dim, dtype, table_width),
        block_size)


def block_sparse_plan(n_heads, kv_heads, head_dim, block_size, dtype,
                      table_width):
    """What `describe()` says of the kernel at a bundle's shapes: the
    selected pages a compute block copies, the query heads one product
    scores and the block's score columns, and the most pages a slot's
    K/V head walks (the selection's width)."""
    pages = block_sparse_block_pages(block_size, head_dim, dtype,
                                     table_width)
    return {"kernel": "block_sparse", "pages_per_block": pages,
            "heads_per_product": n_heads // kv_heads,
            "score_columns_per_block": pages * block_size,
            "selected_pages": table_width}


def block_sparse_attention_reference(q, k_pool, v_pool, pages, rows, *,
                                     scale: Optional[float] = None):
    """Gather-based XLA form (CPU path + oracle). q [S, H, D]; pools
    [NB, BS, H_kv D]; pages [S, H_kv, W]; rows [S, H_kv]."""
    s_n, h, d = q.shape
    bs = k_pool.shape[1]
    hk, w = pages.shape[1:]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale

    def head_rows(pool):
        # [S, H_kv, W, BS, H_kv, D] -> each K/V head's own lanes
        got = jnp.take(pool, pages.reshape(-1).astype(jnp.int32), axis=0)
        got = got.reshape(s_n, hk, w * bs, hk, d).astype(jnp.float32)
        own = jnp.arange(hk)
        return got[:, own, :, own]                      # [H_kv, S, rows, D]

    k, v = head_rows(k_pool), head_rows(v_pool)
    qg = jnp.moveaxis(q.reshape(s_n, hk, h // hk, d), 1, 0)
    s = jnp.einsum("gsid,gskd->gsik", qg.astype(jnp.float32), k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    mask = (jnp.arange(w * bs, dtype=jnp.int32)[None, None, None]
            < jnp.moveaxis(rows.astype(jnp.int32), 1, 0)[..., None, None])
    s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1.0)
    out = jnp.einsum("gsik,gskd->gsid", p, v,
                     precision=jax.lax.Precision.HIGHEST)
    return jnp.moveaxis(out, 0, 1).reshape(s_n, h, d).astype(q.dtype)


def _block_sparse_kernel(tab_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, next_ref, *, scale, block_size,
                         kv_heads, mxu_dtype):
    """The whole call. "Sequence" i of the walk is K/V head i % H_kv of
    slot i // H_kv: q_ref [S H_kv, H / H_kv, D] its query heads, tab_ref
    [S H_kv, W] its selected pages as `page * H_kv + head` (what
    `source` below takes apart: the page, and the head's lanes of its
    rows), len_ref its rows."""
    _, per, d = q_ref.shape
    tokens = k_buf.shape[1] * block_size
    at = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)

    def source(pool, entry):
        lanes = pl.multiple_of((entry % kv_heads) * d, 128)
        return pool.at[entry // kv_heads, :, pl.ds(lanes, d)]

    def begin(i):
        return q_ref[i].astype(jnp.float32), (              # [per, D]
            jnp.full((per, 1), -jnp.inf, jnp.float32),
            jnp.zeros((per, 1), jnp.float32),
            jnp.zeros((per, d), jnp.float32))

    def block_fn(q, b, slot, n, state):
        return _sparse_block(q, k_buf.at[slot], v_buf.at[slot],
                             b * tokens + at < n, state, scale=scale,
                             mxu_dtype=mxu_dtype, groups=1,
                             rows_of=_lane_rows)

    def finish(i, state):
        _, l, acc = state
        o_ref[i] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    _paged_walk(tab_ref, len_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem,
                next_ref, block_size=block_size,
                block_pages=k_buf.shape[1], begin=begin, block_fn=block_fn,
                finish=finish, source=source)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _block_sparse_attention_pallas(q, k_pool, v_pool, pages, rows, *,
                                   scale, interpret=False):
    # jitted so that a model's layers, which all call it at one shape,
    # share one trace and one lowering of the kernel
    if not _HAS_PLTPU:
        raise RuntimeError("pallas TPU backend unavailable; use "
                           "block_sparse_attention_reference")
    s_n, h, d = q.shape
    bs = k_pool.shape[1]
    hk, w = pages.shape[1:]
    per = h // hk
    block_pages = block_sparse_block_pages(bs, d, k_pool.dtype, w)
    table = (_pool_ids(pages, k_pool.shape[0]) * hk
             + jnp.arange(hk, dtype=jnp.int32)[None, :, None]
             ).reshape(s_n * hk, w)
    whole = pl.BlockSpec((s_n * hk, per, d), lambda i, tb, ln: (0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole, hbm, hbm],
        out_specs=whole,
        scratch_shapes=[
            pltpu.VMEM((2, block_pages, bs, d), k_pool.dtype),
            pltpu.VMEM((2, block_pages, bs, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),        # K / V x tile
            pltpu.SMEM((s_n * hk,), jnp.int32),     # the next live one
        ],
    )
    kernel = functools.partial(
        _block_sparse_kernel, scale=scale, block_size=bs, kv_heads=hk,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16)
    # the scope is the kernel's name in a device trace, which
    # `paged_block_sparse_roofline` reads by
    with jax.named_scope("paged_block_sparse_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n * hk, per, d), q.dtype),
            interpret=interpret,
            compiler_params=_walk_compiler_params(),
        )(table, rows.astype(jnp.int32).reshape(s_n * hk),
          q.reshape(s_n * hk, per, d), k_pool, v_pool)
    return out.reshape(s_n, h, d)


def block_sparse_paged_attention(q, k_pool, v_pool, pages, rows, *,
                                 scale: Optional[float] = None,
                                 interpret: bool = False):
    """Public entry (the module's text): out [S, H, D]. Pallas on a TPU
    at lane-whole shapes (D a multiple of 128, the page's rows of 8),
    the gather reference elsewhere."""
    d = q.shape[-1]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    tpu = _HAS_PLTPU and jax.default_backend() == "tpu"
    if (interpret or tpu) and _HAS_PLTPU and d % 128 == 0 \
            and k_pool.shape[1] % 8 == 0:
        return _block_sparse_attention_pallas(
            q, k_pool, v_pool, pages, rows, scale=scale,
            interpret=interpret)
    return block_sparse_attention_reference(q, k_pool, v_pool, pages, rows,
                                            scale=scale)
