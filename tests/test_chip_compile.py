"""Compile-for-the-chip tests: the main path's Pallas kernels at the real
widths, compiled for a DESCRIBED v5e:2x2 (nothing attached, nothing runs).

The installed TPU compiler refuses here what it would refuse on the chip
(a dot Mosaic cannot lower, a kernel GSPMD cannot partition), so these
guard every later PR at no chip time. Shapes are chip_smoke.py's: the
transformer cell's attention (b8 s1024 h8 d256 bf16, blocks 512) and the
decode step's paged pool (8 slots, f32, head dims 128/256, blocks 16/32),
and the paged kernel at the two serve cells' own shapes (16 slots, 16 heads
of 128, 16-token blocks, tables 128 and 256 entries wide).

The topology is described inside the module-scoped fixture below and
nowhere else: only one process may load the TPU library, every xdist
worker imports this file, and a module that touches the library while it
is imported makes the workers collect different tests. Keep these tests
in this one file, compile in the test's own process, and leave the
persistent compile cache off around them (a described-chip entry cannot
be read back without a chip).
"""

import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from paddle_tpu.core.registry import ExecContext, require_op
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_attention import dot_product_attention
from paddle_tpu.kernels.paged_attention import (_paged_attention_pallas,
                                                paged_attention_reference,
                                                paged_block_pages,
                                                paged_decode_attention)

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

# the transformer cell's attention: batch 8, seq 1024, 8 heads of 256
B, S, H, D = 8, 1024, 8, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as jcc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jcc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    jcc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """The kernel gates ask jax.default_backend(), which still says cpu
    while compiling for a described chip: steer it here, in the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _qkv(sharding, dtype=jnp.bfloat16):
    return [jax.ShapeDtypeStruct((B, S, H, D), dtype, sharding=sharding)
            for _ in range(3)]


def test_flash_forward_compiles(one_chip, as_tpu):
    fwd = jax.jit(lambda q, k, v: dot_product_attention(q, k, v,
                                                        causal=True))
    text = fwd.lower(*_qkv(one_chip)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1, "flash forward is not the kernel"


def test_flash_backward_compiles(one_chip, as_tpu):
    def loss(q, k, v):
        out = dot_product_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(*_qkv(one_chip)).compile().as_text()
    # forward + the dq kernel + the dk/dv kernel
    assert text.count(CUSTOM_CALL) == 3, text.count(CUSTOM_CALL)


@pytest.mark.parametrize("block", [256, 512, 1024])
def test_flash_kernels_compile_at_the_train_cells_shape(one_chip, block):
    """The three flash kernels at `cgpt1p3b_train_seq2k`'s attention (64
    batch-heads of 2,048 x 128, bfloat16, causal) at every block
    `tools/flash_block_sweep.py` may choose: Mosaic takes the bfloat16
    operands, the two bodies and the clamped `index_map`s, inside the
    scoped VMEM limit (it refuses a kernel over it)."""
    def sds(width, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((64, 2048, width), dtype,
                                    sharding=one_chip)

    kw = dict(scale=128 ** -0.5, causal=True, block_q=block, block_k=block)
    fwd = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, **kw))
    text = fwd.lower(sds(128), sds(128), sds(128)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1
    bwd = jax.jit(lambda *a: fa._flash_bwd_pallas(*a, **kw))
    text = bwd.lower(sds(128), sds(128), sds(128), sds(128),
                     sds(1, jnp.float32), sds(128)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 2      # dq; dk and dv


# `cgpt1p3b_train_seq2k`: benchmark/configs/cerebras-gpt-1.3b-train-1chip
# .json under traffic/lm_stream_8k_seq2k.json (9 of 24 layers, 4 x 2,048
# tokens a step, run_loop calls of 8 steps, Adam over bfloat16 AMP)
TRAIN_CELL = dict(vocab_size=50257, seq_len=2048, n_layers=9, d_model=2048,
                  n_heads=16, d_ff=8192, max_len=2048)
TRAIN_CELL_BATCH, TRAIN_CELL_STEPS = 4, 8
# one v5e's `memory_stats()["bytes_limit"]` (my chip run, PR 50): 15.75 GiB
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.mark.parametrize("body", ["default_body", "two_steps"])
def test_train_cells_loop_recomputes_nothing(one_chip, as_tpu, body):
    """The train cell's `run_loop` executable, from shapes alone, with
    the body `Executor.run_loop` builds when no `unroll` is given (on a
    v5e `loop_body_steps` grants no second step: the estimate's state +
    twice its temporaries is 19.6 GiB): the compiler's own
    rematerialisation pass clones NOTHING (it runs only where a program
    would not fit otherwise, and what it chose to recompute under the
    two-step body was the most expensive product in the model) and the
    program holds at most 13.8 GiB (13.62 at PR 50). The two-step body is
    compiled beside it and DOES recompute the head's `[4, 2,048, 50,257]`
    logits: the day the compiler stops doing so, this case says it, and a
    second step in the body can be weighed again
    (`tools/loop_unroll_sweep.py` on the chip)."""
    import inspect
    import paddle_tpu as pt
    from paddle_tpu.core import lowering
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.analysis.memory import loop_body_steps
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(**TRAIN_CELL)
        pt.optimizer.AdamOptimizer(learning_rate=3e-4).minimize(avg)
    main.amp_dtype = "bfloat16"
    unroll = 2
    if body == "default_body":
        unroll = inspect.signature(
            pt.Executor.run_loop).parameters["unroll"].default
        if unroll is None:      # the program's size decides, as on a miss
            unroll = loop_body_steps(main, batch=TRAIN_CELL_BATCH,
                                     bytes_limit=V5E_BYTES_LIMIT)
    rows = (TRAIN_CELL_STEPS, TRAIN_CELL_BATCH, TRAIN_CELL["seq_len"])
    got = lowering.loop_compile_figures(
        main, {"src_ids": jax.ShapeDtypeStruct(rows, jnp.int32),
               "tgt_ids": jax.ShapeDtypeStruct(rows + (1,), jnp.int32)},
        [avg.name], n_steps=TRAIN_CELL_STEPS, per_step_feeds=True,
        unroll=unroll, sharding=one_chip)
    held = (got["temp_bytes"] + got["argument_bytes"]) / 2 ** 30
    logits = "bf16[%d,%d,%d]" % (TRAIN_CELL_BATCH, TRAIN_CELL["seq_len"],
                                 TRAIN_CELL["vocab_size"])
    if body == "default_body":
        assert got["remat_instructions"] == 0, got["remat"]
        assert held <= 13.8, held
    else:
        assert any(shape.startswith(logits) and "dot_general" in op_name
                   for _, shape, op_name in got["remat"]), got["remat"]
        assert got["remat_cycles"] > 0
        assert held > 13.8, held


@pytest.mark.parametrize("slots,heads,head_dim,block,pool_blocks,table", [
    (8, 8, 256, 16, 64, 64),
    (8, 16, 128, 32, 64, 32),
    # the two serve cells' own shapes: Cerebras (2,048-token table) and
    # OLMoE (4,096)
    pytest.param(16, 16, 128, 16, 640, 128, id="cerebras_cell"),
    pytest.param(16, 16, 128, 16, 1600, 256, id="olmoe_cell")])
def test_paged_decode_compiles(one_chip, as_tpu, slots, heads, head_dim,
                               block, pool_blocks, table):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((pool_blocks, block, heads, head_dim), jnp.float32)
    text = jax.jit(paged_decode_attention).lower(
        sds((slots, heads, head_dim), jnp.float32), pool, pool,
        sds((slots, table), jnp.int32),
        sds((slots,), jnp.int32)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1, "paged decode is not the kernel"


def test_attention_op_under_mesh_compiles(topo, as_tpu):
    """dp2 x tp2: GSPMD cannot partition a Mosaic kernel, so the op must
    enter it through shard_map — batch on dp, heads on tp, and no gather
    of q/k/v back to one device."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    sharded = NamedSharding(mesh, PartitionSpec("dp", None, "tp", None))
    op = require_op("scaled_dot_product_attention")

    def attn(q, k, v):
        ctx = ExecContext(jax.random.PRNGKey(0), mesh=mesh)
        out = op.compute(ctx, {"Q": [q], "K": [k], "V": [v]},
                         {"causal": True})
        return out["Out"][0]

    compiled = jax.jit(attn, out_shardings=sharded).lower(
        *_qkv(sharded)).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 1, "no Pallas kernel under the mesh"
    assert "all-gather" not in text, "q/k/v were gathered"


def _ragged(block):
    # ragged lengths, a partial last page, and an inactive slot
    return [[1, 2, 5, 0], [4, 0, 0, 0], [0, 0, 0, 0]], [2 * block + 3, 5, 0]


# (heads, head_dim, block, pool dtype, tables, lengths). At (8, 256, 16)
# and (16, 128, 32) in f32 a compute block is 8 / 4 pages; in bf16, 16.
PARITY_CASES = [
    pytest.param(8, 256, 16, "float32", *_ragged(16), id="8-256-16"),
    pytest.param(16, 128, 32, "float32", *_ragged(32), id="16-128-32"),
    # a table 10 entries wide that 8 pages a block do not divide; slot 0
    # ends inside the second block, slot 1 exactly on the first block's
    # boundary (8 pages x 16 tokens), slot 2 holds a single token
    pytest.param(8, 256, 16, "float32",
                 [[3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
                  [13, 14, 15, 16, 17, 18, 19, 20, 0, 0],
                  [2] + [0] * 9],
                 [9 * 16 + 7, 8 * 16, 1], id="table_not_a_multiple"),
    pytest.param(16, 128, 32, "float32", [[0] * 4] * 3, [0, 0, 0],
                 id="all_inactive"),
    # prefix sharing: blocks 1 and 2 are in both sequences' tables
    pytest.param(8, 256, 16, "float32",
                 [[1, 2, 5, 0], [1, 2, 6, 7], [0, 0, 0, 0]],
                 [2 * 16 + 3, 3 * 16 + 9, 0], id="shared_blocks"),
    pytest.param(16, 128, 16, "bfloat16",
                 [[1, 2, 5, 0], [4, 0, 0, 0], [0, 0, 0, 0]],
                 [2 * 16 + 3, 5, 0], id="bf16_pools"),
]


@pytest.mark.parametrize("heads,head_dim,block,dtype,tables,lengths",
                         PARITY_CASES)
def test_paged_kernel_interpret_parity(heads, head_dim, block, dtype,
                                       tables, lengths):
    """The kernel against the gather oracle, interpret mode."""
    rng = np.random.RandomState(2)
    slots, pool_blocks = len(lengths), 1 + int(np.max(tables))
    kp = jnp.asarray(rng.randn(pool_blocks, block, heads,
                               head_dim).astype(np.float32)).astype(dtype)
    vp = jnp.asarray(rng.randn(pool_blocks, block, heads,
                               head_dim).astype(np.float32)).astype(dtype)
    bt = jnp.asarray(np.array(tables, np.int32))
    lens = jnp.asarray(np.array(lengths, np.int32))
    q = jnp.asarray(rng.randn(slots, heads, head_dim).astype(np.float32))
    ref = np.asarray(paged_attention_reference(q, kp, vp, bt, lens))
    out = np.asarray(_paged_attention_pallas(
        q, kp, vp, bt, lens, scale=1.0 / np.sqrt(head_dim),
        interpret=True))
    np.testing.assert_allclose(out, ref, atol=1e-5)
    idle = np.array(lengths) == 0
    assert np.all(out[idle] == 0), "inactive slot must yield zeros"


def test_paged_block_pages_follows_the_shapes():
    """P comes from the page's bytes, a fixed VMEM budget and the table's
    width, and from nothing else."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert paged_block_pages(16, 16, 128, f32, 128) == 8    # both cells
    assert paged_block_pages(16, 16, 128, f32, 256) == 8
    assert paged_block_pages(16, 16, 128, bf16, 128) == 16
    assert paged_block_pages(32, 16, 128, f32, 32) == 4
    assert paged_block_pages(16, 8, 256, f32, 3) == 3       # a narrow table
    assert paged_block_pages(128, 32, 256, f32, 64) == 1    # a page too big


# ---------------------------------------------------------------------------
# OLMoE-1B-7B at its published widths, as the benchmark's cell serves it:
# 5 layers, 16 slots, 16-token blocks, 1,600 pool blocks, a 4,096-token
# table. Whole artifacts, as `io.export_decode_model` traces them, so a
# form the TPU compiler refuses (a `ragged_dot` it cannot lower, a kernel
# it cannot tile) fails here and not on the chip.
# ---------------------------------------------------------------------------

OLMOE = dict(vocab=50304, d_model=2048, n_heads=16, d_ff=1024, layers=5,
             max_context=4096, slots=16, block_size=16, pool_blocks=1600,
             experts=64, top_k=8)


def _olmoe_block():
    from paddle_tpu.models.transformer import BlockSpec
    return BlockSpec(norm="rms_norm", positions="rope", qk_norm=True,
                     bias=False, ffn="moe_gated",
                     num_experts=OLMOE["experts"],
                     experts_per_tok=OLMOE["top_k"])


def _program_fn(program, feed_names, targets, weight_dtype=jnp.float32):
    """A program's pruned step as the export traces it, weights first:
    (serve(state, *feeds), the state's shapes by name); `weight_dtype`:
    what the bundle stores its matrices in (`io.is_weight_matrix`)."""
    from paddle_tpu import io as pio
    from paddle_tpu.core import lowering
    pruned = program.clone(for_test=True).prune(targets=targets,
                                                feeds=feed_names)
    state = {v.name: jax.ShapeDtypeStruct(
        tuple(v.shape), weight_dtype if pio.is_weight_matrix(
            v.name, v.shape) else jnp.float32)
        for v in pruned.list_vars() if v.persistable}
    step, _ = lowering.build_step_fn(pruned, list(feed_names),
                                     list(targets), [], is_test=True)

    def serve(state, *feeds):
        fetches, _ = step(state, dict(zip(feed_names, feeds)),
                          jax.random.PRNGKey(0))
        return fetches

    return serve, state


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_program(sharding, program, feed_names, targets, shapes, dtypes,
                     weight_dtype=jnp.float32):
    """Compile a program's pruned step with its weights as arguments, as
    the export does, from shapes alone."""
    serve, state = _program_fn(program, feed_names, targets, weight_dtype)
    feeds = [jax.ShapeDtypeStruct(tuple(s), d)
             for s, d in zip(shapes, dtypes)]
    return jax.jit(serve).lower(*_on(sharding, (state, *feeds))).compile()


def test_olmoe_prefill_1024_compiles(one_chip, as_tpu):
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    o = OLMOE
    main, kvs, routes = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [1024], dtype="int64")
        logits = tfm.transformer_lm(
            src, o["vocab"], n_layers=o["layers"], d_model=o["d_model"],
            n_heads=o["n_heads"], d_ff=o["d_ff"], max_len=o["max_context"],
            collect_kv=kvs, collect_routes=routes, block=_olmoe_block())
        chosen = pt.layers.stack(routes, axis=1)
    targets = [logits.name] + [n for k, v in kvs for n in (k.name, v.name)] \
        + [chosen.name]
    compiled = _compile_program(one_chip, main, ["src_ids"], targets,
                                [(1, 1024)], [jnp.int32])
    # flash attention and the three grouped matmuls of every layer are
    # kernels, not expansions
    assert compiled.as_text().count(CUSTOM_CALL) >= 4 * o["layers"]
    # the work bound, from the compiler's own count: the whole prefill
    # (experts, attention, head) within 2 x what the algorithm needs,
    # where one-hot dispatch at C = N would put the experts alone at 8 x
    n, d, h = 1024, o["d_model"], o["d_ff"]
    experts = o["layers"] * 2 * n * o["top_k"] * 3 * d * h
    rest = o["layers"] * (2 * n * 4 * d * d + 2 * n * n * d) \
        + 2 * n * d * o["vocab"]
    flops = compiled.cost_analysis()["flops"]
    # since PR 63 the bucket's 8,192 pairs go through the repo's row-tiled
    # grouped matmul, three Mosaic calls a layer whose operations the
    # compiler does not count: what it counts is the rest, and the
    # experts' work is bounded by the kernel's own walk (a group's rows
    # once and a chunk of 128 at each of its ends: at most 8,192 + 64 x
    # 256 rows for the algorithm's 8,192, where one-hot dispatch at C = N
    # would put the experts at 8 x)
    text = compiled.as_text()
    assert len(re.findall(r"%expert_grouped_matmul[.\d]* = ", text)) \
        == 3 * o["layers"] and "ragged-dot" not in text
    assert flops <= 1.1 * rest, (flops, rest)
    assert (n * o["top_k"] + 2 * 128 * 64) * 3 * 2 * d * h * o["layers"] \
        <= 3 * experts
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


# Cerebras-GPT-1.3B as `cerebras-gpt-1.3b-serve` serves it: all 24 layers of
# the GPT-2 block, 16 slots, 640 pool blocks of 16 tokens
CEREBRAS = dict(vocab=50257, d_model=2048, n_heads=16, d_ff=8192, layers=24,
                max_context=2048, slots=16, block_size=16, pool_blocks=640)


def _compile_engine_step(sharding, o, block, weight_dtype=jnp.float32):
    """The decode step as `DecodeModel` runs it: the step program
    exported for the TPU as `io.export_decode_model` traces it, the
    artifact deserialized, and the engine's own `jit_step` over its
    call, pools donated. Returns (compiled, the pools' shape, how
    many). Held here for every bundle: what the step hands the host is
    one int32 a slot, ahead of the logits it was chosen from."""
    import paddle_tpu as pt
    from paddle_tpu.core.compat import jax_export
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving.decode.engine import jit_step
    max_blocks = o["max_context"] // o["block_size"]
    main, stats, routes, picked = pt.Program(), [], [], []
    extra = {} if block is None else dict(
        block=block, moe_stats_out=stats, moe_routes_out=routes,
        selected_out=picked)
    spec = tfm.BlockSpec.of(block)
    window_blocks = 0
    if spec.window:     # the window layers' pool, as the export sizes it
        window_blocks = o["slots"] * (
            spec.window // o["block_size"] + 1) + 1
        extra["window_pool_blocks"] = window_blocks
    with pt.program_guard(main, pt.Program()):
        logits, pools, feed_names = tfm.transformer_decode_step(
            o["vocab"], n_layers=o["layers"], d_model=o["d_model"],
            n_heads=o["n_heads"], d_ff=o["d_ff"],
            max_context=o["max_context"], slots=o["slots"],
            block_size=o["block_size"], pool_blocks=o["pool_blocks"],
            max_blocks_per_seq=max_blocks, **extra)
    targets = [logits.name] + [v.name for outs in pools for v in outs]
    behind = []
    if stats:    # the routing counters in and out, the routes out
        targets += [stats[0].name, routes[0].name]
        behind = [jax.ShapeDtypeStruct((4 if spec.experts_held else 3,),
                                       jnp.int32)]
    if picked:   # the selected positions of a sparse-attention layer
        targets.append(picked[0].name)
    blocks_of = {"full": o["pool_blocks"], "window": window_blocks}
    shapes = [tuple(shape) for i in range(o["layers"])
              for _, shape in tfm.cache_feeds(
                  spec, i, o["n_heads"], o["d_model"], o["slots"],
                  o["block_size"], blocks_of, o["max_context"])]
    # the one shape of a bundle whose pools are all alike, else all of
    # them in the step's order
    pool = shapes[0] if len(set(shapes)) == 1 else shapes
    n_pools = len(shapes)
    serve, state = _program_fn(main, feed_names, targets, weight_dtype)
    feeds = [jax.ShapeDtypeStruct(shape, jnp.int32) for shape in (
        (o["slots"],), (o["slots"],), (o["slots"], max_blocks))]
    tables = [feeds[2]] * (2 if spec.window else 1)    # one a kind
    pools_in = [jax.ShapeDtypeStruct(shape, jnp.float32)
                for shape in shapes]
    exported = jax_export().export(jax.jit(serve), platforms=["tpu"])(
        state, *feeds[:2], *tables, *pools_in, *behind)
    call = jax_export().deserialize(bytearray(exported.serialize())).call
    # the ids of the step before (what a `PREVIOUS_TOKEN` slot reads)
    # sit between the pools and what the artifact takes behind them
    placed = _on(sharding, (state, *feeds[:2],
                            tuple(tables) if spec.window else tables[0],
                            pools_in, feeds[0], *behind))
    compiled = jit_step(call, True, n_pools).lower(*placed).compile()
    ids, head = compiled.out_info[:2]
    assert (ids.shape, ids.dtype) == ((o["slots"],), jnp.int32)
    assert (head.shape, head.dtype) == ((o["slots"], o["vocab"]),
                                        jnp.float32)
    assert [tuple(p.shape) for p in compiled.out_info[2]] == shapes
    return compiled, pool, n_pools


@pytest.mark.parametrize("name,held_under", [
    pytest.param("olmoe", 11.5e9, id="olmoe"),
    pytest.param("cerebras", 9.9e9, id="cerebras")])
def test_engine_decode_step_updates_pools_in_place(one_chip, as_tpu, name,
                                                   held_under):
    o, block = (OLMOE, _olmoe_block()) if name == "olmoe" \
        else (CEREBRAS, None)
    compiled, pool, n_pools = _compile_engine_step(one_chip, o, block)
    text = compiled.as_text()
    # a layer: the paged decode kernel, and the three grouped matmuls of
    # a layer of experts
    assert text.count(CUSTOM_CALL) >= (4 if block else 1) * o["layers"]
    # every pool is returned in the buffer it came in: the donation is
    # answered with aliases, and no copy of a pool's shape is left
    mem = compiled.memory_analysis()
    pool_bytes = n_pools * 4 * int(np.prod(pool))
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    copies = re.findall(
        r"= %s\S* copy\(.*" % re.escape(
            "f32[%s]" % ",".join(map(str, pool))), text)
    assert not copies, copies[:2]
    # the step holds the weights and ONE copy of the pools (the pools it
    # returns are the pools it was given), and leaves room for a prefill
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert pool_bytes < held < held_under, held


# ---------------------------------------------------------------------------
# Kanana-2-30B-A3B at its published widths, as `kanana-2-30b-a3b-serve`
# serves it: the dense layer and four expert layers, every expert, the
# whole vocabulary, 16 slots, 10,241 latent blocks of 16 tokens, a
# 10,240-token table. The configuration's memory rule is held here: the
# step and the longest bucket, each at or under 15.0 GiB by the
# compiler's own count.
# ---------------------------------------------------------------------------

KANANA = dict(vocab=128256, d_model=2048, n_heads=32, d_ff=768, layers=5,
              max_context=10240, slots=16, block_size=16, pool_blocks=10241,
              longest_bucket=6144)
MEMORY_RULE = 15.0 * 2 ** 30


def _kanana_block():
    from paddle_tpu.models.transformer import BlockSpec
    return BlockSpec(
        norm="rms_norm", norm_eps=1e-6, positions="rope", rope_theta=1e6,
        bias=False, attention="latent", kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_interleave=True, ffn="moe_gated", num_experts=128,
        experts_per_tok=6, router="sigmoid_bias", norm_topk=True,
        routed_scale=2.448, shared_width=1536, dense_layers=1,
        dense_width=6144)


def test_chip_smoke_kernels_compile(one_chip, as_tpu, monkeypatch):
    """`chip_smoke.py`'s kernel rows (the flash pair, the paged kernel,
    the `paged_latent_decode` row, the sparse layer's two and its
    attention by both walks) compile for the chip as it builds them, the
    kernels each says: `phase_kernels` raises otherwise."""
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import chip_smoke
    seen = []
    monkeypatch.setattr(chip_smoke, "log",
                        lambda phase, **kw: seen.append(kw))
    chip_smoke.phase_kernels(_DescribedJax(one_chip), chip_smoke.FULL,
                             on_tpu=True)
    assert [row["kernel"] for row in seen] == [
        "flash_fwd", "flash_fwd_bwd", "paged_decode", "paged_latent_decode",
        "paged_index_scores", "paged_sparse_attention",
        "paged_sparse_walks"]


class _DescribedJax:
    """`jax` with `ShapeDtypeStruct` placed on the described chip, for
    code that builds its own shapes."""

    def __init__(self, sharding):
        self._sharding = sharding

    def ShapeDtypeStruct(self, shape, dtype):   # noqa: N802
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._sharding)

    def __getattr__(self, name):
        return getattr(jax, name)


def test_latent_kernel_compiles_at_the_cells_shape(one_chip, as_tpu):
    from paddle_tpu.kernels.paged_attention import (
        paged_latent_block_pages, paged_latent_decode_attention)
    k = KANANA
    table = k["max_context"] // k["block_size"]
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((k["slots"], k["n_heads"], 640), jnp.float32),
        jax.ShapeDtypeStruct((k["pool_blocks"], k["block_size"], 640),
                             jnp.float32),
        jax.ShapeDtypeStruct((k["slots"], table), jnp.int32),
        jax.ShapeDtypeStruct((k["slots"],), jnp.int32)))
    compiled = jax.jit(lambda *a: paged_latent_decode_attention(
        *a, value_width=512, scale=192 ** -0.5)).lower(*args).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1
    # the pool is an argument as it lies in HBM: 640 floats a row, no
    # padding the declaration does not count
    mem = compiled.memory_analysis()
    pool_bytes = k["pool_blocks"] * k["block_size"] * 640 * 4
    assert pool_bytes <= mem.argument_size_in_bytes < pool_bytes + 4e6
    # 24 pages a block: the check's 1,028 tokens (65 pages) walk three
    assert paged_latent_block_pages(16, 640, jnp.float32, table) == 24


def test_kanana_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    k = KANANA
    compiled, pool, n_pools = _compile_engine_step(one_chip, k,
                                                   _kanana_block())
    text = compiled.as_text()
    # a layer: the latent paged kernel; an expert layer: three grouped
    # matmuls
    assert text.count(CUSTOM_CALL) >= k["layers"] + 3 * (k["layers"] - 1)
    mem = compiled.memory_analysis()
    pool_bytes = n_pools * 4 * int(np.prod(pool))
    assert n_pools == k["layers"] and pool[-1] == 640
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 12.6e9 + pool_bytes < held <= MEMORY_RULE, held


def test_kanana_longest_bucket_is_inside_the_memory_rule(one_chip, as_tpu):
    """The 6,144-token prefill as the export traces it (the head for
    the prompt's last row alone, the latent rows out), beside the pools
    that stay resident while it runs."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    k, bound = KANANA, KANANA["longest_bucket"]
    main, rows, routes = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, k["vocab"], n_layers=k["layers"], d_model=k["d_model"],
            n_heads=k["n_heads"], d_ff=k["d_ff"],
            max_len=k["max_context"], collect_kv=rows,
            collect_routes=routes, block=_kanana_block(), head_rows=last)
        chosen = pt.layers.stack(routes, axis=1)
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name]
    compiled = _compile_program(one_chip, main, ["src_ids", "last"],
                                targets, [(1, bound), (1, 1)],
                                [jnp.int32, jnp.int32])
    # flash attention with a V width of its own and the grouped matmuls
    assert compiled.as_text().count(CUSTOM_CALL) \
        >= k["layers"] + 3 * (k["layers"] - 1)
    mem = compiled.memory_analysis()
    pools = k["layers"] * k["pool_blocks"] * k["block_size"] * 640 * 4
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + pools <= MEMORY_RULE, (held, pools)
    # one head row: no [bound, vocab] logits anywhere in the program
    assert "f32[1,%d,%d]" % (bound, k["vocab"]) not in compiled.as_text()


# ---------------------------------------------------------------------------
# Keye-VL-2.0-30B-A3B's language model at its published widths, as
# `keye-vl-2.0-30b-a3b-serve` serves it: four layers, every expert, the
# whole vocabulary, 16 slots, 7,681 blocks of 16 tokens in three pools a
# layer (K and V of 4 heads of 128, an index key of 64 in 128), a
# 7,680-token table. The configuration's memory rule is held here: the
# step and every bucket beside the pools, each at or under 15.0 GiB by
# the compiler's own count.
# ---------------------------------------------------------------------------

KEYE = dict(vocab=151936, d_model=2048, n_heads=32, d_ff=768, layers=4,
            max_context=7680, slots=16, block_size=16, pool_blocks=7681,
            kv_heads=4, head_dim=128, index_heads=16, index_row=128,
            topk=2048)


def _keye_block():
    from paddle_tpu.models.transformer import BlockSpec
    k = KEYE
    return BlockSpec(
        norm="rms_norm", norm_eps=1e-6, positions="rope", rope_theta=1e7,
        bias=False, qk_norm=True, attention="gqa", n_kv_heads=k["kv_heads"],
        head_dim=k["head_dim"], index_heads=k["index_heads"],
        index_head_dim=64, index_topk=k["topk"], ffn="moe_gated",
        num_experts=128, experts_per_tok=8, norm_topk=True)


def _keye_pool_bytes():
    k = KEYE
    return k["layers"] * k["pool_blocks"] * k["block_size"] * 4 * (
        2 * k["kv_heads"] * k["head_dim"] + k["index_row"])


def test_sparse_kernels_compile_at_the_cells_shape(one_chip, as_tpu):
    from paddle_tpu.kernels.paged_attention import (
        paged_decode_attention, paged_index_scores,
        paged_latent_block_pages, paged_sparse_attention)
    k = KEYE
    table = k["max_context"] // k["block_size"]
    slots = jax.ShapeDtypeStruct((k["slots"],), jnp.int32)
    tables = jax.ShapeDtypeStruct((k["slots"], table), jnp.int32)
    index_pool = jax.ShapeDtypeStruct(
        (k["pool_blocks"], k["block_size"], k["index_row"]), jnp.float32)
    kv_pool = jax.ShapeDtypeStruct(
        (k["pool_blocks"], k["block_size"], k["kv_heads"], k["head_dim"]),
        jnp.float32)
    q = jax.ShapeDtypeStruct((k["slots"], k["n_heads"], k["head_dim"]),
                             jnp.float32)
    cases = [
        (paged_index_scores,
         (jax.ShapeDtypeStruct((k["slots"], k["index_heads"],
                                k["index_row"]), jnp.float32),
          jax.ShapeDtypeStruct((k["slots"], k["index_heads"]), jnp.float32),
          index_pool, tables, slots), 1),
        (paged_sparse_attention,
         (q, kv_pool, kv_pool,
          jax.ShapeDtypeStruct((k["slots"], k["topk"]), jnp.int32), slots),
         2),
        # plain grouped-query decode: 8 query heads a pool row
        (paged_decode_attention, (q, kv_pool, kv_pool, tables, slots), 2)]
    for fn, args, n_pools in cases:
        compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
        assert compiled.as_text().count(CUSTOM_CALL) == 1, fn.__name__
        # the pools are arguments as they lie in HBM: a row of 4 heads
        # is stored in 4 x 128 floats, no padding the declaration does
        # not count
        mem = compiled.memory_analysis()
        pool_bytes = n_pools * int(np.prod(args[2].shape)) * 4
        assert pool_bytes <= mem.argument_size_in_bytes \
            < pool_bytes + 4e6, fn.__name__
    # 128 pages a block: the indexer walks a 6 k context in three
    assert paged_latent_block_pages(16, 128, jnp.float32, table) == 128


@pytest.mark.parametrize("table", [480, 2048])
def test_sparse_page_walk_compiles_at_the_cells_shape(one_chip, as_tpu,
                                                      table):
    """`paged_sparse_attention` as the step calls it, with the block
    table, the lengths and the selection as a mask: the page walk and
    the row walk, two kernels under the one name the roofline metric
    reads, each inside the scoped VMEM limit (the compiler refuses one
    that is not). At the cell's table (480 pages: every slot under 7,680
    rows) and at the long-context twin's (2,048 pages, 32 k rows a slot:
    a slot's selection is streamed, not held whole)."""
    from paddle_tpu.kernels.paged_attention import (
        paged_sparse_attention, paged_sparse_block_pages, sparse_select)
    k = KEYE
    slots = jax.ShapeDtypeStruct((k["slots"],), jnp.int32)
    tables = jax.ShapeDtypeStruct((k["slots"], table), jnp.int32)
    scores = jax.ShapeDtypeStruct((k["slots"], table * k["block_size"]),
                                  jnp.float32)
    kv_pool = jax.ShapeDtypeStruct(
        (k["pool_blocks"], k["block_size"], k["kv_heads"], k["head_dim"]),
        jnp.float32)
    q = jax.ShapeDtypeStruct((k["slots"], k["n_heads"], k["head_dim"]),
                             jnp.float32)

    def layer(q, k_pool, v_pool, scores, tables, lens):
        _, rows, counts, selected = sparse_select(
            scores, tables, lens, topk=k["topk"],
            block_size=k["block_size"])
        return paged_sparse_attention(q, k_pool, v_pool, rows, counts,
                                      pages=(tables, lens, selected))

    text = jax.jit(layer).lower(*_on(one_chip, (
        q, kv_pool, kv_pool, scores, tables, slots))).compile().as_text()
    calls = [line for line in text.splitlines() if CUSTOM_CALL in line]
    assert len(calls) == 2
    assert all(re.search(r"%paged_sparse_attention[.\d]* = ", line)
               for line in calls), calls
    # the selection adds no sort to the top_k's own
    assert len(re.findall(r" sort\(", text)) == 1
    # 32 pages a block: 2,048 score columns, 4 MiB of K and V tiles
    assert paged_sparse_block_pages(16, 4, 128, jnp.float32, table) == 32


def test_keye_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    k = KEYE
    compiled, shapes, n_pools = _compile_engine_step(one_chip, k,
                                                     _keye_block())
    # a layer: the indexer's kernel, the sparse attention's two walks,
    # and the three grouped matmuls of the experts
    assert compiled.as_text().count(CUSTOM_CALL) >= 6 * k["layers"]
    assert n_pools == 3 * k["layers"]
    assert [s[2:] for s in shapes[:3]] == [(4, 128), (4, 128), (128,)]
    # behind the pools: the routing counters, the routes, the selections
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [
        (3,), (k["layers"], k["slots"], 8),
        (k["layers"], k["slots"], k["topk"])]
    mem = compiled.memory_analysis()
    pool_bytes = _keye_pool_bytes()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 12.49e9 + pool_bytes < held <= MEMORY_RULE, held
    # `sparse_select` keeps its `top_k`: its ordered positions are the
    # op's output, so a layer sorts its slots' [16, 7,680] scores (the
    # prefill's count search is not the step's)
    scores = "f32[%d,%d]" % (k["slots"], k["max_context"])
    sorts = [line for line in compiled.as_text().splitlines()
             if re.search(r" sort\(", line) and scores in line]
    assert len(sorts) == k["layers"], sorts


@pytest.mark.parametrize("bound", [3072, 4096, 6144])
def test_selected_forward_compiles_at_the_cells_buckets(one_chip, as_tpu,
                                                        bound):
    """The flash forward over a selection as a Keye bucket calls it: 32
    query heads over 4 K/V heads of 128 unrepeated, float32, the mask a
    byte a (row, key), 1,024-wide blocks (a score tile, its mask tile
    and the bfloat16 halves of q and k inside the scoped VMEM limit:
    the compiler refuses a kernel that is not). One kernel, and nothing
    of [heads, rows, keys] beside it."""
    from paddle_tpu.kernels.flash_attention import (attention_form,
                                                    flash_block_plan)
    k = KEYE
    assert attention_form(bound, bound, k["head_dim"], True) \
        == "flash_selected"
    q = jax.ShapeDtypeStruct((1, bound, k["n_heads"], k["head_dim"]),
                             jnp.float32)
    kv = jax.ShapeDtypeStruct((1, bound, k["kv_heads"], k["head_dim"]),
                              jnp.float32)
    chosen = jax.ShapeDtypeStruct((1, bound, bound), jnp.int8)
    compiled = jax.jit(lambda q, k, v, chosen: dot_product_attention(
        q, k, v, causal=True, selected=chosen)).lower(
            *_on(one_chip, (q, kv, kv, chosen))).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 1
    assert "f32[%d,%d,%d]" % (k["n_heads"], bound, bound) not in text
    # q and the output in the kernel's layout, and little else
    mem = compiled.memory_analysis()
    row_bytes = 4 * k["n_heads"] * k["head_dim"]
    assert mem.temp_size_in_bytes <= 2.5 * bound * row_bytes, mem
    plan = flash_block_plan(bound, bound, 1024, 1024, True, jnp.float32)
    side = bound // 1024
    assert (plan.skipped, plan.diagonal, plan.full) == (
        side * (side - 1) // 2, side, side * (side - 1) // 2)


@pytest.mark.parametrize("bound", [3072, 4096, 6144])
def test_keye_buckets_are_inside_the_memory_rule(one_chip, as_tpu, bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone, the three pools' rows and every
    row's selection out, one bit a position), beside the pools that stay
    resident while it runs. Every bucket is longer than the 2,048 rows
    kept, so every one selects."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    k = KEYE
    main, rows, routes, picked = pt.Program(), [], [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, k["vocab"], n_layers=k["layers"], d_model=k["d_model"],
            n_heads=k["n_heads"], d_ff=k["d_ff"],
            max_len=k["max_context"], collect_kv=rows,
            collect_routes=routes, collect_selected=picked,
            block=_keye_block(), head_rows=last)
        chosen = pt.layers.stack(routes, axis=1)
    assert [len(r) for r in rows] == [3] * k["layers"]
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name] + [v.name for v in picked]
    compiled = _compile_program(one_chip, main, ["src_ids", "last"],
                                targets, [(1, bound), (1, 1)],
                                [jnp.int32, jnp.int32])
    text = compiled.as_text()
    # a layer: the experts' three grouped matmuls, and ONE call of the
    # flash forward over the selection's tiles
    assert text.count(CUSTOM_CALL) >= 4 * k["layers"]
    flash = [line for line in text.splitlines() if CUSTOM_CALL in line
             and "scaled_dot_product_attention" in line]
    assert len(flash) == k["layers"], flash
    assert all("s8[1,%d,%d]" % (bound, bound) in line for line in flash)
    # what chooses sorts nothing: the k-th index score of a chunk's
    # [1, 512, T] rows is a count search (a `while` of compare-and-counts
    # over the scores' unsigned image), not `top_k`'s sort of them; the
    # sorts left are the router's and the expert dispatch's
    sorts = [line for line in text.splitlines() if re.search(r" sort\(",
                                                             line)]
    assert sorts and not [line for line in sorts
                          if "512,%d]" % bound in line], sorts
    assert "u32[1,512,%d]" % bound in text
    # held beside the pools, as the compiler counts (the parent's, with
    # the sort's two [512, T] outputs, in brackets): 13,132,067,840
    # (13,134,385,152) at 3,072, 13,347,770,368 (13,342,463,488) at
    # 4,096, 13,775,928,832 (13,776,159,232) at 6,144: 14.34 / 14.54 /
    # 14.94 GiB with the pools
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _keye_pool_bytes() <= MEMORY_RULE, (held, bound)
    # no chunk's scores of every head in HBM (the masked dense form's
    # f32[1,4,8,512,T], 402.7 MB at 6,144), in any layout
    chunk = 512
    for shape in ((1, k["kv_heads"], k["n_heads"] // k["kv_heads"], chunk,
                   bound), (1, k["n_heads"], chunk, bound),
                  (k["n_heads"], chunk, bound)):
        assert "f32[%s]" % ",".join(map(str, shape)) not in text, shape
    # one head row, a bit a selected position; no [heads, bound, bound]
    # scores, of the attention or of the indexer, anywhere
    assert "f32[1,%d,%d]" % (bound, k["vocab"]) not in text
    assert [tuple(o.shape) for o in compiled.out_info[-k["layers"]:]] \
        == [(1, bound, bound // 32)] * k["layers"]
    for heads in (k["n_heads"], k["index_heads"]):
        assert "f32[1,%d,%d,%d]" % (heads, bound, bound) not in text
        assert "f32[%d,%d,%d]" % (heads, bound, bound) not in text


# -- Command A+ (command-a-plus-05-2026): window and full layers ------------

CMDA = dict(vocab=32768, d_model=4096, n_heads=128, d_ff=4096, layers=4,
            max_context=10240, slots=12, block_size=16, pool_blocks=7681,
            kv_heads=8, head_dim=128, window=4096)


def _cmda_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = CMDA
    return BlockSpec(
        norm="layer_norm_gain", norm_eps=1e-5, positions="rope",
        rope_theta=50000.0, rope_interleave=True, bias=False,
        attention="gqa", n_kv_heads=c["kv_heads"], head_dim=c["head_dim"],
        ffn="moe_gated", num_experts=128, experts_per_tok=8,
        router="sigmoid", norm_topk=True, shared_width=4 * 4096,
        shared_scale=0.25, experts_first=0, experts_held=8, parallel=True,
        tied_head=True, window=c["window"],
        layer_pattern=("window", "window", "window", "full"),
        full_positions="none")


def _cmda_pool_bytes():
    c = CMDA
    row = c["block_size"] * 4 * 2 * c["kv_heads"] * c["head_dim"]
    window_blocks = c["slots"] * (c["window"] // c["block_size"] + 1) + 1
    return row * (c["pool_blocks"] + 3 * window_blocks)


@pytest.mark.parametrize("window", [None, 4096])
def test_grouped_paged_kernel_compiles_at_the_cells_shape(one_chip, as_tpu,
                                                          window):
    """The decode kernel of 16 query heads a K/V head at the cell's
    shapes: the full layer's call over its pool, and the window layer's,
    under its own name, over the bounded pool."""
    from paddle_tpu.kernels.paged_attention import paged_sparse_block_pages
    c = CMDA
    table = c["max_context"] // c["block_size"]
    n_blocks = c["pool_blocks"] if window is None else \
        c["slots"] * (window // c["block_size"] + 1) + 1
    pool = jax.ShapeDtypeStruct(
        (n_blocks, c["block_size"], c["kv_heads"], c["head_dim"]),
        jnp.float32)
    args = (jax.ShapeDtypeStruct((c["slots"], c["n_heads"], c["head_dim"]),
                                 jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((c["slots"], table), jnp.int32),
            jax.ShapeDtypeStruct((c["slots"],), jnp.int32))
    compiled = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window)).lower(*_on(one_chip, args)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if CUSTOM_CALL in line]
    name = "paged_attention" if window is None else "paged_window_attention"
    assert len(calls) == 1 and re.search(r"%" + name + r"[.\d]* = ",
                                         calls[0]), calls
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool.shape)) * 4
    assert pool_bytes <= mem.argument_size_in_bytes < pool_bytes + 4e6
    # 16 pages a block: 2,048 score columns, 4 MiB of K and V tiles
    assert paged_sparse_block_pages(16, 8, 128, jnp.float32, table) == 16


@pytest.mark.parametrize("window", [None, 4096])
def test_grouped_paged_kernel_compiles_for_bf16_pools(one_chip, as_tpu,
                                                      window):
    """Pools of 16-bit rows (no cell has them yet): Mosaic's strided
    load is of 32-bit rows, so a group's rows are read by the K/V head's
    index there (`_group_rows`), and the kernel still compiles."""
    c = CMDA
    table = c["max_context"] // c["block_size"]
    pool = jax.ShapeDtypeStruct(
        (c["pool_blocks"], c["block_size"], c["kv_heads"], c["head_dim"]),
        jnp.bfloat16)
    args = (jax.ShapeDtypeStruct((c["slots"], c["n_heads"], c["head_dim"]),
                                 jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((c["slots"], table), jnp.int32),
            jax.ShapeDtypeStruct((c["slots"],), jnp.int32))
    compiled = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window)).lower(*_on(one_chip, args)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("rows,keys", [(2048, 2048), (2048, 4096),
                                       (2048, 6144), (1024, 3072)])
def test_windowed_flash_forward_compiles_at_the_cells_shapes(one_chip,
                                                             as_tpu, rows,
                                                             keys):
    """A chunk of a bucket's query rows against the keys up to its last
    row: 128 query heads over 8 K/V heads never repeated, with the
    window's band and without."""
    shape = lambda n, h: jax.ShapeDtypeStruct((1, n, h, 128), jnp.float32)
    for window in (4096, None):
        compiled = jax.jit(lambda q, k, v: dot_product_attention(
            q, k, v, causal=True, window=window)).lower(*_on(one_chip, (
                shape(rows, 128), shape(keys, 8), shape(keys, 8)))).compile()
        assert compiled.as_text().count(CUSTOM_CALL) == 1
        # K and V as they came: no 128-head copy of them
        assert compiled.memory_analysis().temp_size_in_bytes \
            < 2 * rows * 128 * 128 * 4 + 64e6


def test_cmda_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = CMDA
    compiled, shapes, n_pools = _compile_engine_step(one_chip, c,
                                                     _cmda_block())
    text = compiled.as_text()
    # a layer: its attention kernel and the three grouped matmuls of the
    # held experts; three layers under the window kernel's name
    assert text.count(CUSTOM_CALL) >= 4 * c["layers"]
    assert len(re.findall(r"%paged_window_attention[.\d]* = ", text)) == 3
    # no weight is copied inside the step: the q projection's product is
    # kept from the reshape into heads (`_columns_dot`), else the
    # compiler transposes the whole of Wq every step, 268 MB a layer
    assert not re.search(r" = f32\[(16384,4096|128,128,4096)\]\S* copy\(",
                         text)
    assert n_pools == 2 * c["layers"]
    window_blocks = c["slots"] * (c["window"] // c["block_size"] + 1) + 1
    assert [s[0] for s in shapes] == [window_blocks] * 6 \
        + [c["pool_blocks"]] * 2
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [
        (4,), (c["layers"], c["slots"], 8)]
    mem = compiled.memory_analysis()
    pool_bytes = _cmda_pool_bytes()
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 12.4e9 + pool_bytes < held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [3072, 4096, 6144])
def test_cmda_buckets_are_inside_the_memory_rule(one_chip, as_tpu, bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone, K and V of the 8 shared heads out),
    beside the pools that stay resident while it runs: the q projection
    (16,384 wide) and the shared experts' gate and up never whole."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = CMDA
    main, rows, routes = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, collect_routes=routes, block=_cmda_block(),
            head_rows=last)
        chosen = pt.layers.stack(routes, axis=1)
    assert [len(r) for r in rows] == [2] * c["layers"]
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name]
    compiled = _compile_program(one_chip, main, ["src_ids", "last"],
                                targets, [(1, bound), (1, 1)],
                                [jnp.int32, jnp.int32])
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) >= 4 * c["layers"]
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _cmda_pool_bytes() <= MEMORY_RULE, (held, bound)
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text
    assert "f32[1,%d,16384]" % bound not in text
    if bound != c["d_model"]:       # (a weight's own shape at 4,096)
        assert "f32[%d,16384]" % bound not in text


# ---------------------------------------------------------------------------
# LFM2-24B-A2B at its published widths, as `lfm2-24b-a2b-serve` serves it:
# layers 0-5 (conv, conv, attention, conv, conv, conv), both dense layers,
# every expert, the whole vocabulary, 64 slots; ONE layer holds K/V (8
# heads of 64, two to a lane tile of the pool), five hold two rows a slot.
# ---------------------------------------------------------------------------

LFM2 = dict(vocab=65536, d_model=2048, n_heads=32, kv_heads=8, head_dim=64,
            d_ff=1536, layers=6, max_context=10240, slots=64, block_size=16,
            pool_blocks=40961)


def _lfm2_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = LFM2
    return BlockSpec(
        norm="rms_norm", norm_eps=1e-5, positions="rope",
        rope_theta=1000000.0, qk_norm=True, bias=False, attention="gqa",
        n_kv_heads=c["kv_heads"], head_dim=c["head_dim"], ffn="moe_gated",
        num_experts=64, experts_per_tok=4, router="sigmoid_bias",
        norm_topk=True, norm_topk_eps=1e-6, dense_layers=2,
        dense_width=11776, tied_head=True,
        layer_pattern=("conv", "conv", "full", "conv"), conv_taps=3)


def _lfm2_pool_bytes():
    c = LFM2
    kv = c["pool_blocks"] * c["block_size"] * 4 * 2 * c["kv_heads"] \
        * c["head_dim"]
    return kv + 5 * c["slots"] * 2 * c["d_model"] * 4


def test_packed_grouped_kernel_compiles_at_the_cells_shape(one_chip, as_tpu):
    """The decode kernel at heads 64 wide, 4 query heads a K/V head, two
    K/V heads to a lane tile of the pool: one Pallas call under the
    scope `paged_attention` (not the gather form), and the pools take
    their own bytes in the device's memory, not twice them."""
    from paddle_tpu.kernels.paged_attention import paged_sparse_block_pages
    from paddle_tpu.models.transformer import packed_kv_row
    c = LFM2
    table = c["max_context"] // c["block_size"]
    row = packed_kv_row(c["kv_heads"], c["head_dim"])
    assert row == [4, 128]
    pool = jax.ShapeDtypeStruct(
        (c["pool_blocks"], c["block_size"], *row), jnp.float32)
    args = (jax.ShapeDtypeStruct((c["slots"], c["n_heads"], c["head_dim"]),
                                 jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((c["slots"], table), jnp.int32),
            jax.ShapeDtypeStruct((c["slots"],), jnp.int32))
    compiled = jax.jit(paged_decode_attention).lower(
        *_on(one_chip, args)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if CUSTOM_CALL in line]
    assert len(calls) == 1 and re.search(r"%paged_attention[.\d]* = ",
                                         calls[0]), calls
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool.shape)) * 4
    assert pool_bytes == 2 * c["pool_blocks"] * c["block_size"] * 2048
    assert pool_bytes <= mem.argument_size_in_bytes < pool_bytes + 4e6
    # 32 pages a block: 2,048 score columns, 4 MiB of K and V tiles
    assert paged_sparse_block_pages(16, 4, 128, jnp.float32, table) == 32


@pytest.mark.parametrize("rows,keys", [(2048, 2048), (2048, 4096),
                                       (2048, 6144)])
def test_flash_forward_compiles_at_heads_of_64(one_chip, as_tpu, rows, keys):
    """A chunk of a bucket's query rows against the keys up to its last
    row: 32 query heads of 64 over 8 K/V heads never repeated."""
    shape = lambda n, h: jax.ShapeDtypeStruct((1, n, h, 64), jnp.float32)
    compiled = jax.jit(lambda q, k, v: dot_product_attention(
        q, k, v, causal=True)).lower(*_on(one_chip, (
            shape(rows, 32), shape(keys, 8), shape(keys, 8)))).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


def test_lfm2_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = LFM2
    compiled, shapes, n_pools = _compile_engine_step(one_chip, c,
                                                     _lfm2_block())
    text = compiled.as_text()
    # the attention layer's kernel and the three grouped matmuls of each
    # of the four layers of experts
    assert text.count(CUSTOM_CALL) >= 1 + 3 * 4
    assert len(re.findall(r"%paged_attention[.\d]* = ", text)) == 1
    assert "short_conv" in text
    assert n_pools == 5 + 2
    state = (c["slots"], 2, c["d_model"])
    kv = (c["pool_blocks"], c["block_size"], 4, 128)
    assert shapes == [state, state, kv, kv, state, state, state]
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [(3,), (4, c["slots"], 4)]
    mem = compiled.memory_analysis()
    pool_bytes = _lfm2_pool_bytes()
    # the K/V pools AND the five states are returned where they came
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 11.1e9 + pool_bytes < held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [2048, 4096, 6144])
def test_lfm2_buckets_are_inside_the_memory_rule(one_chip, as_tpu, bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone; K and V of the one attention layer
    and the state of each conv layer at the prompt's true length out),
    beside the pools that stay resident while it runs."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = LFM2
    main, rows, routes = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        n_tokens = pt.layers.data("n_tokens", [], dtype="int32")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, collect_routes=routes, block=_lfm2_block(),
            head_rows=last, n_tokens=n_tokens)
        chosen = pt.layers.stack(routes, axis=1)
    assert [len(r) for r in rows] == [1, 1, 2, 1, 1, 1]
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name]
    compiled = _compile_program(
        one_chip, main, ["src_ids", "n_tokens", "last"], targets,
        [(1, bound), (1,), (1, 1)], [jnp.int32, jnp.int32, jnp.int32])
    text = compiled.as_text()
    # flash attention once, the grouped matmuls of four layers
    assert text.count(CUSTOM_CALL) >= 1 + 3 * 4
    assert "short_conv" in text
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _lfm2_pool_bytes() <= MEMORY_RULE, (held, bound)
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text


# ---------------------------------------------------------------------------
# Phi-4-mini-flash-reasoning at its published widths, as
# `phi-4-mini-flash-reasoning-serve` serves it: 16 of the 32 layers (0-7,
# 16-17, 18-23), the whole vocabulary, 64 slots; ONE layer holds a growing
# K/V (and three more read it), four hold a window of 512 rows, five hold a
# scan's state a slot.
# ---------------------------------------------------------------------------

PHI4FLASH = dict(vocab=200064, d_model=2560, n_heads=40, kv_heads=20,
                 head_dim=64, d_ff=10240, layers=16, max_context=6144,
                 slots=64, block_size=16, pool_blocks=24577, window=512,
                 d_inner=5120, d_state=16, dt_rank=160, taps=4)
PHI4FLASH_PATTERN = ("mamba", "window") * 4 + ("memory", "full") \
    + ("gmu", "cross") * 3
PHI4FLASH_IDS = tuple(range(8)) + tuple(range(16, 24))


def _phi4flash_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = PHI4FLASH
    return BlockSpec(
        positions="none", bias=False, attn_bias=True, attention="gqa",
        differential=True, n_kv_heads=c["kv_heads"], head_dim=c["head_dim"],
        ffn="gated", tied_head=True, window=c["window"],
        layer_pattern=PHI4FLASH_PATTERN, layer_ids=PHI4FLASH_IDS,
        conv_taps=c["taps"], ssm_inner=c["d_inner"], ssm_state=c["d_state"],
        ssm_dt_rank=c["dt_rank"], dense_precision="high")


def _phi4flash_pool_bytes():
    c = PHI4FLASH
    row = 4 * 2 * c["kv_heads"] * c["head_dim"]
    window_blocks = c["slots"] * (c["window"] // c["block_size"] + 1) + 1
    state = 4 * c["slots"] * c["d_inner"] * (c["d_state"] + c["taps"] - 1)
    return (c["pool_blocks"] + 4 * window_blocks) * c["block_size"] * row \
        + 5 * state


@pytest.mark.parametrize("window", [None, 512])
def test_diff_paged_kernel_compiles_at_the_cells_shape(one_chip, as_tpu,
                                                       window):
    """The differential decode kernel at 40 query heads of 64 over ten
    tiles of paired K/V heads, over the full pool and over a window
    layer's: ONE Pallas call under its own scope, and the pools take
    their own bytes in the device's memory (ten tiles side by side in a
    row's lanes, not a second-minor axis padded to sixteen)."""
    from paddle_tpu.kernels.paged_attention import (paged_decode_plan,
                                                    paged_diff_attention)
    c = PHI4FLASH
    table = c["max_context"] // c["block_size"]
    blocks = c["pool_blocks"] if window is None else \
        c["slots"] * (window // c["block_size"] + 1) + 1
    pool = jax.ShapeDtypeStruct((blocks, c["block_size"], 1280),
                                jnp.float32)
    args = (jax.ShapeDtypeStruct((c["slots"], c["n_heads"], c["head_dim"]),
                                 jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((c["slots"], table), jnp.int32),
            jax.ShapeDtypeStruct((c["slots"],), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32))
    compiled = jax.jit(lambda *a: paged_diff_attention(
        *a, window=window)).lower(*_on(one_chip, args)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if CUSTOM_CALL in line]
    name = "paged_diff_attention" if window is None \
        else "paged_diff_window_attention"
    assert len(calls) == 1 and re.search(r"%%%s[.\d]* = " % name,
                                         calls[0]), calls
    mem = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(pool.shape)) * 4
    assert pool_bytes <= mem.argument_size_in_bytes < pool_bytes + 4e6
    assert mem.temp_size_in_bytes < 4e6, mem
    plan = paged_decode_plan("kv_diff", [[1280], [1280]], c["n_heads"], 16,
                             jnp.float32, table, window)
    assert plan == ("diff", 8, 4, 128, None)


def test_phi4flash_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = PHI4FLASH
    compiled, shapes, n_pools = _compile_engine_step(one_chip, c,
                                                     _phi4flash_block())
    text = compiled.as_text()
    # four window layers, the full layer and its three readers
    assert len(re.findall(r"%paged_diff_attention[.\d]* = ", text)) == 4
    assert len(re.findall(r"%paged_diff_window_attention[.\d]* = ",
                          text)) == 4
    assert "selective_scan" in text
    assert n_pools == 5 * 2 + 5 * 2
    scan = [(c["slots"], c["d_state"], c["d_inner"]),
            (c["slots"], c["taps"] - 1, c["d_inner"])]
    held_window = (c["slots"] * 33 + 1, c["block_size"], 1280)
    full = (c["pool_blocks"], c["block_size"], 1280)
    assert shapes == (scan + [held_window] * 2) * 4 + scan + [full] * 2
    mem = compiled.memory_analysis()
    pool_bytes = _phi4flash_pool_bytes()
    # the pools AND the states are returned where they came
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 8.7e9 + pool_bytes < held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [256, 1024])
def test_phi4flash_buckets_are_inside_the_memory_rule(one_chip, as_tpu,
                                                      bound):
    """A prefill bucket of the cell as the export traces it: the first
    decoder and the full layer's K/V over the whole bucket, the second
    decoder and the head on the prompt's last row alone; every scan's
    state at the prompt's true length, the windows' and the full layer's
    K/V out, beside the pools that stay resident while it runs."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = PHI4FLASH
    main, rows = pt.Program(), []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        n_tokens = pt.layers.data("n_tokens", [], dtype="int32")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, block=_phi4flash_block(), head_rows=last,
            n_tokens=n_tokens)
    assert [len(r) for r in rows] == [2] * 10
    targets = [logits.name] + [v.name for r in rows for v in r]
    compiled = _compile_program(
        one_chip, main, ["src_ids", "n_tokens", "last"], targets,
        [(1, bound), (1,), (1, 1)], [jnp.int32, jnp.int32, jnp.int32])
    text = compiled.as_text()
    # the windowed flash forward of the four window layers; the full
    # layer's one row and the cross layers' are dense products
    assert text.count(CUSTOM_CALL) == 4
    assert "selective_scan" in text
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _phi4flash_pool_bytes() <= MEMORY_RULE, (held, bound)
    # the second decoder ran on one row: no FFN of a gmu or cross layer
    # over the bucket, and no logits over it
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text


# ---------------------------------------------------------------------------
# NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, as
# `nemotron-3-nano-30b-a3b-serve` serves it: 14 of the 52 layers
# (MEMEM*EMEMEM*E), 32 of 128 experts held, a quarter of the vocabulary,
# 128 slots; six layers hold a Mamba-2 state a slot (a matrix a head,
# 2.1 MB, and three rows of 6,144), two a growing K/V of 2 heads of 128.
# ---------------------------------------------------------------------------

NEMOTRON3 = dict(vocab=32768, d_model=2688, n_heads=32, kv_heads=2,
                 head_dim=128, d_ff=1856, shared=3712, experts=128,
                 held=32, top_k=6, layers=14, max_context=5120, slots=128,
                 block_size=16, pool_blocks=40961, ssm_heads=64,
                 ssm_head_dim=64, groups=8, d_state=128, taps=4)
NEMOTRON3_PATTERN = tuple({"M": "mamba2", "E": "ffn", "*": "attn"}[c]
                          for c in "MEMEM*EMEMEM*E")


def _nemotron3_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = NEMOTRON3
    return BlockSpec(
        norm="rms_norm", positions="none", bias=False, attention="gqa",
        n_kv_heads=c["kv_heads"], head_dim=c["head_dim"], ffn="moe_gated",
        num_experts=c["experts"], experts_per_tok=c["top_k"],
        router="sigmoid_bias", norm_topk=True, routed_scale=2.5,
        shared_width=c["shared"], expert_form="relu2", experts_first=0,
        experts_held=c["held"], layer_pattern=NEMOTRON3_PATTERN,
        conv_taps=c["taps"], ssm_inner=c["ssm_heads"] * c["ssm_head_dim"],
        ssm_state=c["d_state"], ssm_heads=c["ssm_heads"],
        ssm_groups=c["groups"], ssm_chunk=128)


def _nemotron3_state_shapes():
    c = NEMOTRON3
    width = c["ssm_heads"] * c["ssm_head_dim"] + 2 * c["groups"] \
        * c["d_state"]
    return [(c["slots"], c["ssm_heads"], c["ssm_head_dim"], c["d_state"]),
            (c["slots"], c["taps"] - 1, width)]


def _nemotron3_pool_bytes():
    c = NEMOTRON3
    states = 6 * sum(4 * int(np.prod(s)) for s in _nemotron3_state_shapes())
    row = 4 * 2 * c["kv_heads"] * c["head_dim"]
    return states + 2 * c["pool_blocks"] * c["block_size"] * row


#: the state update's two callers: (slots, heads, P, N, groups)
SSD_UPDATE_SHAPES = {
    "nemotron3": (NEMOTRON3["slots"], NEMOTRON3["ssm_heads"],
                  NEMOTRON3["ssm_head_dim"], NEMOTRON3["d_state"],
                  NEMOTRON3["groups"]),
    # MiniCPM-SALA's lightning layers: a head a group, dt = 1
    "sala": (64, 32, 128, 128, 32),
}


@pytest.mark.parametrize("cell", list(SSD_UPDATE_SHAPES))
def test_ssd_update_kernel_compiles_at_the_cells_shape(one_chip, as_tpu,
                                                       cell):
    """The state update at 128 slots of 64 heads of [64, 128] and at 64
    slots of 32 heads of [128, 128]: ONE Pallas call under its own scope,
    the state returned where it came (no second 1.07 / 0.5 GB array), a
    slot's 2 MB block in and out inside the VMEM it asks for (the plan's
    17 MB); tracing it leaves ONE `kernel/ssd_plan` record, the plan of
    the cell's shapes (8 heads a B and C row, or 1; the sum on the MXU)."""
    from paddle_tpu.kernels import ssd_update
    from paddle_tpu.obs import trace
    s, h, p, n, g = SSD_UPDATE_SHAPES[cell]
    ssd_update._ssd_update_pallas.clear_cache()
    before = len([e for e in trace.events() if e.get("name") == "ssd_plan"])
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((s, h, p, n), f32),
            jax.ShapeDtypeStruct((s, h, p), f32),
            jax.ShapeDtypeStruct((s, h), f32),
            jax.ShapeDtypeStruct((h,), f32),
            jax.ShapeDtypeStruct((s, g, n), f32),
            jax.ShapeDtypeStruct((s, g, n), f32),
            jax.ShapeDtypeStruct((s,), jnp.bool_))
    compiled = jax.jit(ssd_update.ssd_decode_update,
                       donate_argnums=0).lower(*_on(one_chip, args)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if CUSTOM_CALL in line]
    assert len(calls) == 1 and re.search(r"%ssd_decode_update[.\d]* = ",
                                         calls[0]), calls
    plan = ssd_update.ssd_update_plan(h, g, p, n)
    records = [e for e in trace.events()
               if e.get("name") == "ssd_plan"][before:]
    assert [r["args"] for r in records] == [plan._asdict()]
    assert (plan.rep, plan.state_vregs, plan.reduction) == (
        {"nemotron3": 8, "sala": 1}[cell], 512, "mxu_split3")
    asked = plan.vmem_bytes
    assert 16 << 20 < asked < 18 << 20
    assert '"size":"%d"' % asked in calls[0]
    mem = compiled.memory_analysis()
    state_bytes = 4 * s * h * p * n
    assert mem.alias_size_in_bytes >= state_bytes, mem
    assert mem.temp_size_in_bytes < 16e6, mem


#: (rows, k, n, groups) -> the plan's weight tile: each grouped product
#: the repo's kernel runs in a cell (`tests/test_expert_matmul.py` holds
#: the rule; here the chip's compiler takes each call)
_EXPERT_PRODUCTS = {
    "nemotron3_up_step": ((768, 2688, 2048, 32), (896, 1024)),
    "nemotron3_up_wave": ((2048, 2688, 2048, 32), (896, 1024)),
    "nemotron3_down_step": ((768, 2048, 3072, 32), (1024, 1024)),
    "nemotron3_down_wave": ((2048, 2048, 3072, 32), (1024, 1024)),
    "keye_up_step": ((128, 2048, 768, 128), (1024, 768)),
    "keye_down_step": ((128, 768, 2048, 128), (768, 1024)),
    "kanana_up_step": ((96, 2048, 768, 128), (1024, 768)),
    "kanana_down_step": ((96, 768, 2048, 128), (768, 1024)),
    "lfm2_up_step": ((256, 2048, 1536, 64), (2048, 512)),
    "lfm2_down_step": ((256, 1536, 2048, 64), (768, 1024)),
    "olmoe_up_short_bucket": ((2048, 2048, 1024, 64), (1024, 1024)),
    "olmoe_down_short_bucket": ((2048, 1024, 2048, 64), (1024, 1024)),
}


@pytest.mark.parametrize("product", sorted(_EXPERT_PRODUCTS))
def test_expert_matmul_kernel_compiles_at_the_cells_shape(one_chip, as_tpu,
                                                          product):
    """Each grouped product the plan gives the repo's kernel, at a decode
    step's rows (Nemotron's 768 pairs over 32 held experts, Keye's 128
    and Kanana's 96 over 128, LFM2's 256 over 64), at a prefill wave's
    2,048 and at OLMoE's shortest bucket's 2,048: ONE Mosaic call under
    its own name by the plan (weight tiles of 3-4 MB where XLA's are 256
    KB to 1 MB), no `ragged_dot`, the rows and a column tile of the
    output resident in the VMEM the call asks for, and nothing of the
    matrices' 0.4-0.8 GB copied beside it."""
    from paddle_tpu.kernels import expert_matmul as em
    (rows, k, n, groups), tile = _EXPERT_PRODUCTS[product]
    plan = em.expert_matmul_plan(rows, k, n, groups, jnp.float32)
    assert (plan.form, plan.tm, plan.tk, plan.tn) == ("pallas", rows) + tile
    args = (jax.ShapeDtypeStruct((rows, k), jnp.float32),
            jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
            jax.ShapeDtypeStruct((groups,), jnp.int32))
    compiled = jax.jit(em.expert_matmul).lower(
        *_on(one_chip, args)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if CUSTOM_CALL in line]
    assert len(calls) == 1 and re.search(
        r"%expert_grouped_matmul[.\d]* = ", calls[0]), calls
    assert "ragged_dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6
    # the call asks for the VMEM its blocks take and a margin, not for
    # all there is: what it reserves XLA cannot prefetch the step's other
    # weights into (100 MB asked for 32 cost the cell's step 1.4 ms:
    # PERF.md section 6, PR 55); a decode step's call under 48 MB, and
    # none over what the plan admits (Nemotron's up wave, under 80 MB)
    asked = em._vmem_bytes(rows, k, plan.tk, plan.tn, 4, 2)
    assert '"size":"%d"' % asked in calls[0]
    assert asked <= em._VMEM_BYTES_MAX < 80 << 20
    assert asked <= (48 << 20 if rows <= 768 else 80 << 20)


#: (rows, k, n, groups), the operands' dtype: the row-tiled form's
#: callers. The Mellum cell's trained wave (`_held_grad_rows(8192, 8, 16,
#: 64)` rows of its gate / up and of its down product, bfloat16 under AMP)
#: and one serve bucket (Kanana's 6,144 tokens x top-6, float32 matrices)
_TILED_PRODUCTS = {
    "mellum2_up_wave": ((18432, 2304, 896, 16), jnp.bfloat16),
    "mellum2_down_wave": ((18432, 896, 2304, 16), jnp.bfloat16),
    "kanana_up_bucket": ((36864, 2048, 768, 128), jnp.float32),
}


@pytest.mark.parametrize("product", sorted(_TILED_PRODUCTS))
def test_tiled_expert_matmul_and_its_transposes_compile_at_the_cells_shape(
        one_chip, as_tpu, product):
    """Over 2,048 rows the plan gives the row-tiled form: the product is
    ONE Mosaic call named `expert_grouped_matmul`, and under a derivative
    the backward two more under names that hold it (`_dx`: the same
    kernel through the matrices' second axis, nothing transposed in HBM;
    `_dw`: a group's float32 matrix resident), no `ragged_dot` anywhere;
    each call asks for the scoped VMEM its blocks take (`_tiled_vmem_bytes`
    / `_dw_vmem_bytes`), under the bound the plan states, and no
    temporary beside the operands but dW's float32 before its cast."""
    from paddle_tpu.kernels import expert_matmul as em
    (rows, k, n, groups), dtype = _TILED_PRODUCTS[product]
    item = jnp.dtype(dtype).itemsize
    plan = em.expert_matmul_plan(rows, k, n, groups, dtype)
    tm, chunk = 2048, em._TILED_CHUNK
    assert (plan.form, plan.tm, plan.tk, plan.tn) == ("tiled", tm, k, n)
    args = (jax.ShapeDtypeStruct((rows, k), dtype),
            jax.ShapeDtypeStruct((groups, k, n), dtype),
            jax.ShapeDtypeStruct((groups,), jnp.int32))

    def calls_of(fn):
        compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
        text = compiled.as_text()
        assert "ragged_dot" not in text and "ragged-dot" not in text
        return [line for line in text.splitlines()
                if CUSTOM_CALL in line], compiled.memory_analysis()

    calls, mem = calls_of(em.expert_matmul)
    assert len(calls) == 1 and re.search(
        r"%expert_grouped_matmul[.\d]* = ", calls[0]), calls
    asked = em._tiled_vmem_bytes(tm, k, n, chunk, item, item)
    assert '"size":"%d"' % asked in calls[0]
    assert asked <= em._VMEM_BYTES_MAX < 80 << 20
    assert mem.temp_size_in_bytes < 4e6, mem     # the visit table

    def loss(x, w, s):
        return jnp.sum(em.expert_matmul(x, w, s).astype(jnp.float32))

    calls, mem = calls_of(jax.grad(loss, (0, 1)))
    names = sorted(re.search(r"%(expert_grouped_matmul\w*?)[.\d]* = ",
                             line).group(1) for line in calls)
    assert names == ["expert_grouped_matmul_dw", "expert_grouped_matmul_dx"]
    sizes = {em._tiled_vmem_bytes(tm, n, k, chunk, item, item),
             em._dw_vmem_bytes(tm, k, n, chunk, item)}
    assert max(sizes) <= em._VMEM_BYTES_MAX
    assert {int(re.search(r'"size":"(\d+)"', line).group(1))
            for line in calls} == sizes


@functools.cache
def _nemotron3_step(one_chip):
    """The cell's decode step compiled ONCE for the cases that read it
    (15 s): whichever runs first compiles, under its own `as_tpu`."""
    return _compile_engine_step(one_chip, dict(NEMOTRON3),
                                _nemotron3_block())


def test_nemotron3_grouped_products_read_whole_tiles(one_chip, as_tpu):
    """All twelve grouped products of the step run in the repo's own
    kernel, `expert_grouped_matmul` (`kernels/expert_matmul.py`), one
    Mosaic call a product. The six UP products' k is the model width,
    2,688 = 21 x 128, which holds XLA's grouped matmul (tiles "m,k,n",
    the widest of 512 / 256 / 128 that divides a dimension) to `[128,
    512]` weight tiles, 256 KB a grid step at 49% of the bytes' rate
    (PERF.md section 6, PR 55). The six DOWN products write the model
    width as STORED (3,072, which gave XLA's kernel `[512, 512]` tiles:
    PR 53) and are the repo's by their 768 rows (PR 61: XLA's kernel
    walks the rows in tiles of its own). Either way an expert matrix is a
    parameter and an operand of its grouped matmul and nothing else (no
    copy, slice, cast or transpose of 0.7-0.8 GB a step), and the cut
    back to 2,688 stays on the down product."""
    c = NEMOTRON3
    compiled, _, _ = _nemotron3_step(one_chip)
    text = compiled.as_text()
    assert "ragged_dot_tiling" not in text
    stored = 3072
    up = "[%d,%d,2048]" % (c["held"], c["d_model"])
    down = "[%d,2048,%d]" % (c["held"], stored)
    lines = [l for l in text.splitlines() if up in l or down in l]
    params = [l for l in lines if re.search(r" parameter\(\d+\)", l)]
    own = [l for l in lines
           if re.search(r"%expert_grouped_matmul[.\d]* = ", l)]
    assert len(params) == len(own) == 12
    # each grouped product reads a parameter, as it is stored
    for tag, width in (("up", 2048), ("down", stored)):
        calls = [l for l in own if re.search(
            r"%%weights__moe\d+_%s_w__[.\d]*\), custom_call_target" % tag,
            l)]
        assert len(calls) == 6, (tag, [l[:300] for l in own[:2]])
        for call in calls:
            assert CUSTOM_CALL in call
            assert "f32[768,%d]" % width in call.split(" custom-call(")[0]
    other = [l for l in lines if l not in params + own
             and not l.startswith(("HloModule", "ENTRY"))]
    assert not other, [l[:200] for l in other[:3]]
    # and a down product leaves the step cut to the model width
    assert "f32[768,%d]" % stored in text
    assert not re.search(r"f32\[%d,%d\]" % (c["slots"], stored), text)
    # 6 x 32 x (2,688 x 2,048 + 2,048 x 3,072) floats of experts
    experts = 6 * c["held"] * 4 * (c["d_model"] * 2048 + 2048 * stored)
    mem = compiled.memory_analysis()
    assert experts == 9_059_696_640 \
        and mem.argument_size_in_bytes > experts + _nemotron3_pool_bytes()


def test_nemotron3_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = dict(NEMOTRON3)
    compiled, shapes, n_pools = _nemotron3_step(one_chip)
    text = compiled.as_text()
    # the state update is ONE Pallas call a Mamba-2 layer, the grouped
    # kernel one an attention layer (16 query heads a K/V head)
    assert len(re.findall(r"%ssd_decode_update[.\d]* = ", text)) == 6
    assert len(re.findall(r"%paged_attention[.\d]* = ", text)) == 2
    assert "mamba2" in text
    assert n_pools == 6 * 2 + 2 * 2
    state = _nemotron3_state_shapes()
    kv = (c["pool_blocks"], c["block_size"], c["kv_heads"], c["head_dim"])
    assert shapes == (state * 3 + [kv] * 2) * 2
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [(4,), (6, c["slots"], 6)]
    mem = compiled.memory_analysis()
    pool_bytes = _nemotron3_pool_bytes()
    # the K/V pools AND the states, the 1.07 GB matrices of each Mamba-2
    # layer among them, are returned where they came
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 11.4 GB of weights since the experts' down matrices store the model
    # width in whole tiles (15.78 GB held of the rule's 16.11: PR 53)
    assert 11.3e9 + pool_bytes < held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [512, 1024])
def test_nemotron3_buckets_are_inside_the_memory_rule(one_chip, as_tpu,
                                                      bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone; every Mamba-2 layer's state at the
    prompt's true length and the attention layers' K and V out; the
    chunked scan never holds a state a ROW), beside the pools and states
    that stay resident while it runs."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = NEMOTRON3
    main, rows, routes = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        n_tokens = pt.layers.data("n_tokens", [], dtype="int32")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, collect_routes=routes,
            block=_nemotron3_block(), head_rows=last, n_tokens=n_tokens)
        chosen = pt.layers.stack(routes, axis=1)
    assert [len(r) for r in rows] == [2] * 8 and len(routes) == 6
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [chosen.name]
    compiled = _compile_program(
        one_chip, main, ["src_ids", "n_tokens", "last"], targets,
        [(1, bound), (1,), (1, 1)], [jnp.int32, jnp.int32, jnp.int32])
    text = compiled.as_text()
    # flash attention twice, the two grouped matmuls of six expert layers
    assert text.count(CUSTOM_CALL) >= 2 + 2 * 6
    assert "mamba2" in text
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _nemotron3_pool_bytes() <= MEMORY_RULE, (held, bound)
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text
    # no state a row: nothing of [bound, 64, 64, 128]
    assert "f32[1,%d,64,64,128]" % bound not in text


# ---------------------------------------------------------------------------
# MiniCPM-SALA at its published widths, as `minicpm-sala-serve` serves it:
# published layers 0-3 (block-sparse attention over pooled keys, then three
# linear-attention layers), the whole vocabulary, 64 slots at prompts of
# 12-32 k: one layer's K/V pool with every slot at `max_context`, the
# slots' pooled keys, three [32, 128, 128] states a slot.
# ---------------------------------------------------------------------------

SALA = dict(vocab=73448, d_model=4096, n_heads=32, kv_heads=2, head_dim=128,
            d_ff=16384, layers=4, max_context=36864, slots=64,
            block_size=64, pool_blocks=36865)
SALA_BUCKETS = (12288, 16384, 24576, 32768)


def _sala_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = SALA
    return BlockSpec(
        norm="rms_norm", norm_eps=1e-6, positions="none", bias=False,
        attention="gqa", qk_norm=True, n_kv_heads=c["kv_heads"],
        head_dim=c["head_dim"], ffn="gated",
        layer_pattern=("blocksparse", "linear", "linear", "linear"),
        layer_ids=(0, 1, 2, 3), attn_gate=True, sparse_kernel=32,
        sparse_stride=16, sparse_block=64, sparse_topk=64,
        sparse_window=2048, sparse_init=1, sparse_dense_len=8192,
        linear_positions="rope", decay_layers=32, embed_scale=12.0,
        residual_scale=1.4 / 32 ** 0.5, logit_scale=1 / 16, ssm_chunk=128,
        row_chunk=2048)


def _sala_pool_shapes():
    c = SALA
    row = c["kv_heads"] * c["head_dim"]
    pooled = (c["max_context"] - 32) // 16 + 1
    kv = (c["pool_blocks"], c["block_size"], row)
    return [kv, kv, (c["slots"], pooled, row)] \
        + [(c["slots"], c["n_heads"], c["head_dim"], c["head_dim"])] * 3


def _sala_pool_bytes():
    return sum(4 * int(np.prod(s)) for s in _sala_pool_shapes())


def test_block_sparse_kernel_compiles_at_the_cells_shape(one_chip, as_tpu):
    """The decode kernel over chosen blocks at 64 slots of 32 heads over
    2 K/V heads of 128, pages of 64 rows, 128 table entries a K/V head:
    ONE Mosaic call under its own name, a compute block 32 pages of ONE
    head's lanes (4 MB of VMEM tiles), nothing of the pools copied."""
    from paddle_tpu.kernels import block_sparse_attention as bsa
    c = SALA
    plan = bsa.block_sparse_plan(c["n_heads"], c["kv_heads"], c["head_dim"],
                                 c["block_size"], jnp.float32, 128)
    assert plan == {"kernel": "block_sparse", "pages_per_block": 32,
                    "heads_per_product": 16,
                    "score_columns_per_block": 2048, "selected_pages": 128}
    pool = jax.ShapeDtypeStruct(_sala_pool_shapes()[0], jnp.float32)
    args = (jax.ShapeDtypeStruct((c["slots"], c["n_heads"], c["head_dim"]),
                                 jnp.float32), pool, pool,
            jax.ShapeDtypeStruct((c["slots"], c["kv_heads"], 128),
                                 jnp.int32),
            jax.ShapeDtypeStruct((c["slots"], c["kv_heads"]), jnp.int32))
    compiled = jax.jit(bsa.block_sparse_paged_attention).lower(
        *_on(one_chip, args)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if CUSTOM_CALL in line]
    assert len(calls) == 1 and re.search(
        r"%paged_block_sparse_attention[.\d]* = ", calls[0]), calls
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_sala_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = dict(SALA)
    compiled, shapes, n_pools = _compile_engine_step(one_chip, c,
                                                     _sala_block())
    text = compiled.as_text()
    # the block-sparse kernel once (the one sparse layer), the state
    # update once a linear layer: the Nemotron cell's kernel
    assert len(re.findall(r"%paged_block_sparse_attention[.\d]* = ",
                          text)) == 1
    assert len(re.findall(r"%ssd_decode_update[.\d]* = ", text)) == 3
    assert "%paged_attention" not in text
    for scope in ("block_pool_keys", "block_scores", "block_select",
                  "linear_attention"):
        assert scope in text, scope
    assert n_pools == 6 and shapes == [tuple(s) for s in
                                       _sala_pool_shapes()]
    behind = compiled.out_info[3]
    assert [tuple(b.shape) for b in behind] == [
        (1, c["slots"], c["kv_heads"], 128)]
    mem = compiled.memory_analysis()
    pool_bytes = _sala_pool_bytes()
    # the K/V pool, the pooled keys and the states come back where they
    # came: 5.4 GB
    assert mem.alias_size_in_bytes >= pool_bytes > 5.3e9, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # 6.84 GB of weights beside them: over 60% of the chip
    assert 6.8e9 + pool_bytes < held <= MEMORY_RULE, held
    assert 6.84e9 + pool_bytes > 0.6 * 17.18e9


@pytest.mark.parametrize("bound", SALA_BUCKETS)
def test_sala_buckets_are_inside_the_memory_rule(one_chip, as_tpu, bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone; the sparse layer's K, V, pooled keys
    and every row's chosen blocks, the linear layers' states at the
    prompt's true length out), beside the pools that stay resident while
    it runs: the row-wise parts go a chunk of rows at a time INSIDE the
    artifact, so no [bound, 16,384] product and no stacked copy of the
    stream is ever whole."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = SALA
    main, rows, sels = pt.Program(), [], []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        n_tokens = pt.layers.data("n_tokens", [], dtype="int32")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, collect_selected=sels, block=_sala_block(),
            head_rows=last, n_tokens=n_tokens)
    assert [len(r) for r in rows] == [3, 1, 1, 1] and len(sels) == 1
    targets = [logits.name] + [v.name for r in rows for v in r] \
        + [v.name for v in sels]
    compiled = _compile_program(
        one_chip, main, ["src_ids", "n_tokens", "last"], targets,
        [(1, bound), (1,), (1, 1)], [jnp.int32, jnp.int32, jnp.int32])
    text = compiled.as_text()
    # the flash forward over the choice's tiles, a call a query chunk
    assert text.count(CUSTOM_CALL) == bound // 2048
    assert "linear_attention" in text and "block_select" in text
    mem = compiled.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _sala_pool_bytes() <= MEMORY_RULE, (held, bound)
    assert mem.temp_size_in_bytes < 2.6e9, mem
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text
    assert "f32[%d,%d]" % (bound, c["d_ff"]) not in text
    # a chosen block is a bit: [bound, 2 x bound / 64 / 32] words
    assert "s32[1,%d,%d]" % (bound, 2 * bound // 2048) in text


# ---------------------------------------------------------------------------
# granite-4.0-h-micro at its published widths, as `granite-4.0-h-micro-serve`
# serves it: ALL 40 layers (36 Mamba-2 mixers at one group, 4 attention
# layers of 32 / 8 heads of 64 without positions, a dense gated FFN of 8,192
# in every one), the whole vocabulary, the matrices BFLOAT16 (6.38 GB), 48
# slots: 72 state arrays and 8 pools through the step.
# ---------------------------------------------------------------------------

GRANITE4 = dict(vocab=100352, d_model=2048, n_heads=32, kv_heads=8,
                head_dim=64, d_ff=8192, layers=40, max_context=5120,
                slots=48, block_size=16, pool_blocks=15361, ssm_heads=64,
                ssm_head_dim=64, groups=1, d_state=128, taps=4)
GRANITE4_PATTERN = tuple("full" if i % 10 == 5 else "mamba2_ffn"
                         for i in range(40))
GRANITE4_WEIGHT_BYTES = 6_384_999_424


def _granite4_block():
    from paddle_tpu.models.transformer import BlockSpec
    c = GRANITE4
    return BlockSpec(
        norm="rms_norm", positions="none", bias=False, attention="gqa",
        n_kv_heads=c["kv_heads"], head_dim=c["head_dim"], ffn="gated",
        tied_head=True, layer_pattern=GRANITE4_PATTERN,
        conv_taps=c["taps"], ssm_inner=c["ssm_heads"] * c["ssm_head_dim"],
        ssm_state=c["d_state"], ssm_heads=c["ssm_heads"],
        ssm_groups=c["groups"], ssm_chunk=256, embed_scale=12.0,
        residual_scale=0.22, logit_scale=0.125, attn_scale=0.015625)


def _granite4_pool_bytes():
    c = GRANITE4
    width = c["ssm_heads"] * c["ssm_head_dim"] + 2 * c["groups"] \
        * c["d_state"]
    state = 4 * c["slots"] * (c["ssm_heads"] * c["ssm_head_dim"]
                              * c["d_state"] + (c["taps"] - 1) * width)
    row = 4 * 2 * c["kv_heads"] * c["head_dim"]
    return 36 * state + 4 * c["pool_blocks"] * c["block_size"] * row


def _no_float32_copy_of_a_matrix(text, c):
    """No BUFFER of a matrix's shape in float32 in a compiled program:
    each product reads the bfloat16 matrix where it lies. An instruction
    inside a fused computation (a `convert` beside the product it feeds)
    allocates nothing; one of any other computation (the entry, a loop's
    body) is a buffer."""
    wide = c["ssm_heads"] * c["ssm_head_dim"]
    shapes = set()
    for rows, cols in ((c["d_model"], 2 * wide + 2 * c["d_state"]
                        + c["ssm_heads"]), (wide, c["d_model"]),
                       (c["d_model"], c["d_ff"]), (c["d_ff"], c["d_model"]),
                       (c["vocab"], c["d_model"]),
                       (c["d_model"], c["kv_heads"] * c["head_dim"])):
        shapes |= {"f32[%d,%d]" % (rows, cols), "f32[%d,%d]" % (cols, rows)}
    fused = set(re.findall(r"fusion\(.*calls=(%[\w.\-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            inside = head.group(1)
        elif inside not in fused:
            made = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (f32\[[\d,]+\])",
                            line)
            assert not (made and made.group(1) in shapes), line[:300]


def test_ssd_update_kernel_compiles_at_one_group(one_chip, as_tpu):
    """The state update's third caller: 64 heads read ONE B and C row
    (`rep` 64), 48 slots; one Pallas call, the state aliased."""
    from paddle_tpu.kernels import ssd_update
    c = GRANITE4
    slots, heads, p, n = (c["slots"], c["ssm_heads"], c["ssm_head_dim"],
                          c["d_state"])
    f32 = jnp.float32
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((slots, heads, p, n), f32),
        jax.ShapeDtypeStruct((slots, heads, p), f32),
        jax.ShapeDtypeStruct((slots, heads), f32),
        jax.ShapeDtypeStruct((heads,), f32),
        jax.ShapeDtypeStruct((slots, 1, n), f32),
        jax.ShapeDtypeStruct((slots, 1, n), f32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    compiled = jax.jit(ssd_update.ssd_decode_update,
                       donate_argnums=0).lower(*args).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 4 * slots * heads * p * n
    plan = ssd_update.ssd_update_plan(heads, 1, p, n)
    assert (plan.rep, plan.groups, plan.state_vregs) == (64, 1, 512)


def test_granite4_decode_step_is_inside_the_memory_rule(one_chip, as_tpu):
    c = dict(GRANITE4)
    compiled, shapes, n_pools = _compile_engine_step(
        one_chip, c, _granite4_block(), weight_dtype=jnp.bfloat16)
    text = compiled.as_text()
    # the state update is ONE Pallas call a Mamba-2 layer, the grouped
    # kernel one an attention layer (4 query heads a K/V head, two K/V
    # heads of 64 to a lane tile)
    assert len(re.findall(r"%ssd_decode_update[.\d]* = ", text)) == 36
    assert len(re.findall(r"%paged_attention[.\d]* = ", text)) == 4
    assert "mamba2" in text and "gated_ffn" in text
    assert n_pools == 36 * 2 + 4 * 2
    kv = (c["pool_blocks"], c["block_size"], 4, 128)
    assert shapes.count(kv) == 8 and len(shapes) == 80
    mem = compiled.memory_analysis()
    pool_bytes = _granite4_pool_bytes()
    assert pool_bytes == 3_714_121_728 + 4_026_793_984
    # the 72 states and the 8 pools are returned where they came
    assert mem.alias_size_in_bytes >= pool_bytes, mem
    # the matrices arrive bfloat16 (6.38 GB, not 12.77) and stay so
    assert GRANITE4_WEIGHT_BYTES + pool_bytes \
        <= mem.argument_size_in_bytes \
        < GRANITE4_WEIGHT_BYTES + pool_bytes + 2 ** 20, mem
    _no_float32_copy_of_a_matrix(text, c)
    assert mem.temp_size_in_bytes < 0.5e9, mem
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert held <= MEMORY_RULE, held


@pytest.mark.parametrize("bound", [512, 1024])
def test_granite4_buckets_are_inside_the_memory_rule(one_chip, as_tpu,
                                                     bound):
    """Each prefill bucket of the cell as the export traces it (the head
    for the prompt's last row alone; every Mamba-2 layer's state at the
    prompt's true length and the attention layers' K and V out), on the
    bfloat16 matrices, beside the pools and states that stay resident
    while it runs."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    c = GRANITE4
    main, rows = pt.Program(), []
    with pt.program_guard(main, pt.Program()):
        src = pt.layers.data("src_ids", [bound], dtype="int64")
        n_tokens = pt.layers.data("n_tokens", [], dtype="int32")
        last = pt.layers.data("last", [1], dtype="int32")
        logits = tfm.transformer_lm(
            src, c["vocab"], n_layers=c["layers"], d_model=c["d_model"],
            n_heads=c["n_heads"], d_ff=c["d_ff"], max_len=c["max_context"],
            collect_kv=rows, block=_granite4_block(), head_rows=last,
            n_tokens=n_tokens)
    assert [len(r) for r in rows] == [2] * 40
    targets = [logits.name] + [v.name for r in rows for v in r]
    compiled = _compile_program(
        one_chip, main, ["src_ids", "n_tokens", "last"], targets,
        [(1, bound), (1,), (1, 1)], [jnp.int32, jnp.int32, jnp.int32],
        weight_dtype=jnp.bfloat16)
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) >= 4     # the flash forward a layer
    assert "mamba2" in text and "gated_ffn" in text
    mem = compiled.memory_analysis()
    _no_float32_copy_of_a_matrix(text, c)
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held + _granite4_pool_bytes() <= MEMORY_RULE, (held, bound)
    assert "f32[1,%d,%d]" % (bound, c["vocab"]) not in text
    # no state a row: nothing of [bound, 64, 64, 128]
    assert "f32[1,%d,64,64,128]" % bound not in text


def test_the_bundles_that_were_there_record_what_they_did():
    """The seven older bundles' `serving.json` stays byte for byte: no
    block that was there says a word of this model's fields, every layer
    of theirs has a mixer AND a feed-forward part, and no op of theirs
    carries `expert_form`."""
    import paddle_tpu as pt
    from paddle_tpu.models import transformer as tfm
    # a field at its default is not said, the base ones apart: none of
    # the fields that came with Nemotron's and MiniCPM-SALA's layers
    mine = {"ssm_heads", "ssm_groups", "ssm_chunk", "expert_form",
            "attn_gate", "sparse_kernel", "sparse_stride", "sparse_block",
            "sparse_topk", "sparse_window", "sparse_init",
            "sparse_dense_len", "linear_positions", "decay_layers",
            "embed_scale", "residual_scale", "logit_scale", "row_chunk"}
    for block in (None, _olmoe_block(), _kanana_block(), _keye_block(),
                  _cmda_block(), _lfm2_block(), _phi4flash_block()):
        spec = tfm.BlockSpec.of(block)
        said = spec.to_dict()
        assert not mine & set(said), said
        assert tfm.BlockSpec.of(said) == spec
        kinds = [spec.layer(i, 64) for i in range(8)]
        assert all(k.ffn != "none" and k.mixer != "none" for k in kinds)
    main = pt.Program()
    with pt.program_guard(main, pt.Program()):
        x = pt.layers.data("x", [4, 16], dtype="float32")
        pt.layers.moe_gated_ffn(x, 4, 8, 2, shared_width=8, name="m")
    op = [o for o in main.global_block.ops if o.type == "moe_gated_ffn"][0]
    assert "expert_form" not in op.attrs
    assert sorted(op.inputs) == ["RouterW", "SharedDown", "SharedGate",
                                 "SharedUp", "WDown", "WGate", "WUp", "X"]


# `mellum2_12b_train_seq8k`: benchmark/configs/mellum2-12b-a2.5b-train-1chip
# .json under traffic/lm_stream_8k_seq8k.json (one period of window, window,
# window, full; 1 x 8,192 tokens a step, run_loop calls of 8 steps, Adam
# over bfloat16 AMP)
MELLUM2_SEQ, MELLUM2_STEPS, MELLUM2_WINDOW = 8192, 8, 1024


def test_windowed_flash_backward_compiles_at_the_mellum2_cells_shape(
        one_chip, as_tpu):
    """The window layers' three kernels at the cell's attention (32 query
    heads over 4 K/V heads of 128, 8,192 rows, window 1,024, bfloat16,
    blocks of 1,024): the forward with K and V as they came, dq on the
    forward's band and dk/dv on its transpose (K and V repeated to the
    query heads for them, as the unwindowed backward has it), inside the
    scoped VMEM limit; and the plan walks the band: of 36 blocks under
    the diagonal 21 lie wholly behind the window, and are no grid step."""
    def sds(heads, width=128, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((heads, MELLUM2_SEQ, width), dtype,
                                    sharding=one_chip)

    block = fa._default_block(MELLUM2_SEQ)
    kw = dict(scale=128 ** -0.5, causal=True, block_q=block, block_k=block,
              window=MELLUM2_WINDOW)
    fwd = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, **kw))
    text = fwd.lower(sds(32), sds(4), sds(4)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1
    bwd = jax.jit(lambda *a: fa._flash_bwd_pallas(*a, **kw))
    text = bwd.lower(sds(32), sds(32), sds(32), sds(32),
                     sds(32, 1, jnp.float32), sds(32)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 2      # dq; dk and dv
    plan = fa.flash_block_plan(MELLUM2_SEQ, MELLUM2_SEQ, block, block, True,
                               jnp.bfloat16, MELLUM2_WINDOW)
    assert (plan.n_q, plan.behind, plan.edge, plan.diagonal, plan.full) \
        == (8, 21, 7, 8, 0)
    # what was compiled above: the forward's crossed blocks in halves,
    # the backward's in strips of a lane tile's rows (the least Mosaic
    # slices without a relayout), both on the band's two steps a row
    bwd_plan = fa.flash_block_plan(MELLUM2_SEQ, MELLUM2_SEQ, block, block,
                                   True, jnp.bfloat16, MELLUM2_WINDOW,
                                   backward=True)
    assert [(p.strips, p.edge_strips, p.band_k, p.band_q, p.blocks_run)
            for p in (plan, bwd_plan)] == [(2, 2, 2, 2, 11.25),
                                           (8, 8, 2, 2, 8.4375)]
    # through the op's own path under a derivative: three kernels, and
    # the trace tells them from a full layer's
    def loss(q, k, v):
        return jnp.sum(dot_product_attention(
            q, k, v, causal=True, window=MELLUM2_WINDOW).astype(jnp.float32))

    shape = lambda h: jax.ShapeDtypeStruct((1, MELLUM2_SEQ, h, 128),
                                           jnp.bfloat16, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(32), shape(4), shape(4)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 3
    assert "windowed_dot_product_attention" in text
    assert "transpose_windowed_dot_product_attention" in text


def test_mellum2_train_loop_is_inside_the_memory_rule(one_chip, as_tpu):
    """The cell's `run_loop` executable from shapes alone, as the kind
    builds it (the mapping's trainer, `remat` as the configuration's file
    says, the loss and the experts' counts fetched together): the
    compiler rematerialises nothing and the program holds no more than
    the file records, 12.04 GiB of a v5e's 15.75 (state 7.14 GB of
    arguments: f32 masters and Adam's two moments of 595.2 M
    parameters; 11.93 GiB with the share's products in the repo's own
    kernels)."""
    import json
    import sys
    import paddle_tpu as pt
    from paddle_tpu.core import lowering
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    bench = os.path.join(root, "benchmark")
    sys.path.append(bench)
    try:
        from kinds import _model_mellum2 as mapping
    finally:
        sys.path.remove(bench)
    with open(os.path.join(
            bench, "configs", "mellum2-12b-a2.5b-train-1chip.json")) as f:
        cfg = json.load(f)
    sz = mapping.sizes(cfg)
    main, _, avg, load = mapping.build_trainer(pt, sz, MELLUM2_SEQ, 0,
                                               cfg["train"])
    rows = (MELLUM2_STEPS, 1, MELLUM2_SEQ)
    got = lowering.loop_compile_figures(
        main, {"src_ids": jax.ShapeDtypeStruct(rows, jnp.int32),
               "tgt_ids": jax.ShapeDtypeStruct(rows + (1,), jnp.int32)},
        [avg.name, load.name], n_steps=MELLUM2_STEPS, per_step_feeds=True,
        unroll=1, sharding=one_chip)
    assert got["remat_instructions"] == 0, got["remat"]
    params = 595_153_152
    assert got["argument_bytes"] >= 12 * params      # masters and moments
    held = (got["temp_bytes"] + got["argument_bytes"]) / 2 ** 30
    assert 9.5e9 / 2 ** 30 <= held <= 12.5, held
    assert got["temp_bytes"] + got["argument_bytes"] <= V5E_BYTES_LIMIT
    # the file's record is PR 62's, with the share's products in XLA's
    # grouped matmul: the arguments to the byte; the temporaries never
    # over it, and since PR 63 114 MB under (the repo's kernels keep
    # their tiles in VMEM and ask for no HBM temporary; a benchmark
    # file is not this PR's to edit)
    said = cfg["train"]["remat_why"]
    assert f"{got['argument_bytes']:,}" in said
    recorded = int(re.search(r"reads ([\d,]+) bytes of temporaries",
                             said).group(1).replace(",", ""))
    assert recorded - 0.2e9 <= got["temp_bytes"] <= recorded, got
