"""GLM-5's kernels, decode step and prefill buckets compiled for a described
v5e: `tests/test_chip_compile.py`'s cases for `glm-5-serve`, in a file of
their own because that file is the tier-1 run's longest (one xdist worker
holds a file: 1,174 s of a 1,195 s run with these in it, PR 65). The
topology is described inside that module's fixtures, which this one
takes by name; nothing here describes a device at import."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from test_chip_compile import (CUSTOM_CALL, MEMORY_RULE,  # noqa: F401
                               _compile_engine_step, _compile_program, _on,
                               as_tpu, one_chip, topo)

# ---------------------------------------------------------------------------
# GLM-5 at its published widths, as `glm-5-serve` serves it: one dense and
# four expert layers, 8 of 256 experts held, an eighth of the vocabulary,
# 12 slots, 10,753 blocks of 16 tokens in two pools a layer (a latent row
# of 576 floats in [1, 640], an index key of 128), an 896-entry table. The
# configuration's memory rule is held here: the step and each of the three
# buckets beside the pools, at or under 15.0 GiB by the compiler's count.
# 16 slots (14,337 blocks) were asked for: the step and the two shorter
# buckets pass there, the 12,288 bucket reads 16,148,352,000 B with the
# pools, 42 MB over (PR 65).
# ---------------------------------------------------------------------------

GLM5 = dict(vocab=19360, d_model=6144, n_heads=64, d_ff=2048, layers=5,
            max_context=14336, slots=12, block_size=16, pool_blocks=10753,
            latent_row=640, index_row=128, index_heads=32, topk=2048)


def _glm5_block():
    from paddle_tpu.models.transformer import BlockSpec
    return BlockSpec(
        norm="rms_norm", norm_eps=1e-5, positions="rope", rope_theta=1e6,
        bias=False, attention="latent", kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        rope_interleave=True, q_lora_rank=2048, index_heads=32,
        index_head_dim=128, index_topk=2048, index_rope_dim=64,
        index_rope_interleave=True, ffn="moe_gated", num_experts=256,
        experts_per_tok=8, router="sigmoid_bias", norm_topk=True,
        routed_scale=2.5, shared_width=2048, dense_layers=1,
        dense_width=12288, experts_first=0, experts_held=8, row_chunk=2048)


def _glm5_pool_bytes():
    g = GLM5
    return g["layers"] * g["pool_blocks"] * g["block_size"] * 4 * (
        g["latent_row"] + g["index_row"])


def test_sparse_latent_kernels_compile_at_the_cells_shape(one_chip, as_tpu):
    """The step's two kernels at the cell's shapes: the indexer's (32
    heads of 128 over an 896-entry table: a shape it had not run) and
    the attention over 2,048 selected latent rows for 64 heads at once."""
    from paddle_tpu.kernels.paged_attention import (
        paged_index_scores, paged_sparse_latent_attention)
    g = GLM5
    table = g["max_context"] // g["block_size"]
    slots = jax.ShapeDtypeStruct((g["slots"],), jnp.int32)
    tables = jax.ShapeDtypeStruct((g["slots"], table), jnp.int32)
    index_pool = jax.ShapeDtypeStruct(
        (g["pool_blocks"], g["block_size"], g["index_row"]), jnp.float32)
    latent_pool = jax.ShapeDtypeStruct(
        (g["pool_blocks"], g["block_size"], 1, g["latent_row"]),
        jnp.float32)
    cases = [
        (paged_index_scores,
         (jax.ShapeDtypeStruct((g["slots"], g["index_heads"],
                                g["index_row"]), jnp.float32),
          jax.ShapeDtypeStruct((g["slots"], g["index_heads"]), jnp.float32),
          index_pool, tables, slots), {}),
        (paged_sparse_latent_attention,
         (jax.ShapeDtypeStruct((g["slots"], g["n_heads"], g["latent_row"]),
                               jnp.float32), latent_pool,
          jax.ShapeDtypeStruct((g["slots"], g["topk"]), jnp.int32), slots),
         dict(value_width=512, scale=1 / 16))]
    for fn, args, kw in cases:
        compiled = jax.jit(functools.partial(fn, **kw)).lower(
            *_on(one_chip, args)).compile()
        assert compiled.as_text().count(CUSTOM_CALL) == 1, fn.__name__
        mem = compiled.memory_analysis()
        pool_bytes = int(np.prod(
            (args[2] if fn is paged_index_scores else args[1]).shape)) * 4
        assert pool_bytes <= mem.argument_size_in_bytes \
            < pool_bytes + 8e6, fn.__name__


def test_glm5_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    g = GLM5
    compiled, shapes, n_pools = _compile_engine_step(one_chip, g,
                                                     _glm5_block())
    text = compiled.as_text()
    # a layer: the indexer's kernel and the sparse latent attention's
    for name in ("paged_index_scores", "paged_sparse_latent_attention"):
        assert len([line for line in text.splitlines()
                    if CUSTOM_CALL in line and name in line]) \
            == g["layers"], name
    assert n_pools == 2 * g["layers"]
    assert [s[2:] for s in shapes[:2]] == [(1, 640), (128,)]
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [
        (4,), (g["layers"] - 1, g["slots"], 8),
        (g["layers"], g["slots"], g["topk"])]
    mem = compiled.memory_analysis()
    pool_bytes = _glm5_pool_bytes()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 10.80e9 + pool_bytes < held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [6144, 8192, 12288])
def test_glm5_buckets_are_inside_the_memory_rule(one_chip, as_tpu, bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone, both pools' rows and every row's
    selection out, one bit a position), beside the pools that stay
    resident while it runs. Every bucket is longer than the 2,048 rows
    kept, so every one selects: the heads a group at a time through the
    flash forward over the selection's tiles, no [T, 64, 256] array and
    no [heads, T, T] scores whole."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    g = GLM5
    main, rows, routes, picked = pt.Program(), [], [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, g["vocab"], n_layers=g["layers"], d_model=g["d_model"],
            n_heads=g["n_heads"], d_ff=g["d_ff"],
            max_len=g["max_context"], collect_kv=rows,
            collect_routes=routes, collect_selected=picked,
            block=_glm5_block(), head_rows=last)
        chosen = pt.layers.stack(routes, axis=1)
    assert [len(r) for r in rows] == [2] * g["layers"]
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name] + [v.name for v in picked]
    compiled = _compile_program(one_chip, main, ["src_ids", "last"],
                                targets, [(1, bound), (1, 1)],
                                [jnp.int32, jnp.int32])
    text = compiled.as_text()
    flash = [line for line in text.splitlines() if CUSTOM_CALL in line
             and "scaled_dot_product_attention" in line]
    assert len(flash) == g["layers"], len(flash)    # one a layer's loop
    assert all("s8[1,%d,%d]" % (bound, bound) in line for line in flash)
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _glm5_pool_bytes() <= MEMORY_RULE, (
        held, mem.temp_size_in_bytes, bound)
    assert [tuple(o.shape) for o in compiled.out_info[-g["layers"]:]] \
        == [(1, bound, bound // 32)] * g["layers"]
    for heads in (g["n_heads"], g["index_heads"]):
        assert "f32[1,%d,%d,%d]" % (heads, bound, bound) not in text
        assert "f32[%d,%d,%d]" % (heads, bound, bound) not in text
    # no K, V or q of all 64 heads whole
    for width in (256, 448):
        assert "f32[1,%d,64,%d]" % (bound, width) not in text
