"""Program -> pure JAX step function.

This replaces the reference's entire runtime execution stack — the op-by-op
interpreting Executor (paddle/fluid/framework/executor.cc:322-345), the
per-step InferShape + kernel dispatch (operator.cc:605-699), and the
threaded SSA-graph scheduler (details/threaded_ssa_graph_executor.cc:38-124)
— with ONE function: trace every op of a block through its registered JAX
compute fn, producing a single XLA computation that the compiler schedules,
fuses, and (under a sharded jit) partitions. The op graph's parallelism is
discovered by XLA, not by a host thread pool.

Semantics of the produced function:

    step(state, feed, rng) -> (fetch_tuple, new_state)

* `state`  — dict of persistable vars (params, optimizer accumulators).
* `feed`   — dict of per-step inputs.
* `rng`    — JAX PRNG key threaded to random ops (deterministic per op index,
             so retracing cannot skew the stream).
* ops execute in program order by rebinding names in an environment dict —
  SSA by construction, matching details/ssa_graph.h's var-versioning without
  building it explicitly.
* an `autodiff` pseudo-op (backward.py) makes the prefix of the block run
  inside jax.value_and_grad; gradients bind to the declared `@GRAD` names
  and downstream (optimizer) ops consume them like any other var.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

from .program import Block, OpDesc, Program
from .registry import ExecContext, require_op

AUTODIFF_OP = "autodiff"


def _apply_var_marks(block: Block, name: str, val, ctx):
    """Post-op output adjustments driven by VarDesc marks: stop_gradient,
    and — under a mesh — activation sharding constraints.

    A sharding annotation on a NON-persistable intermediate is a layout
    constraint on the activation (the transpiler's sp pass uses this to
    pin the residual stream seq-sharded). Feeds/params get their layout
    from jit in_shardings, but GSPMD will not reliably propagate a feed
    sharding through embedding/reshape chains on its own — measured on
    the virtual mesh: without constraints the sp transformer all-gathers
    every [B, S, D] activation (tests/test_collectives_emitted.py)."""
    try:
        var = block.var(name)
    except KeyError:
        return val
    if var.stop_gradient and jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
        val = jax.lax.stop_gradient(val)
    mesh = getattr(ctx, "mesh", None)
    if var.sharding and not var.persistable and mesh is not None:
        from ..parallel.mesh import spec_for
        from jax.sharding import NamedSharding
        spec = spec_for(var.sharding, mesh)
        if tuple(spec):
            shape = jnp.shape(val)
            sizes_ok = True
            for i, axes in enumerate(tuple(spec)):
                if i >= len(shape):
                    # recorded VarDesc rank exceeds the runtime rank: the
                    # spec cannot apply at all — drop the constraint
                    sizes_ok = False
                    break
                if axes is None:
                    continue
                ax = axes if isinstance(axes, tuple) else (axes,)
                size = int(np.prod([mesh.shape[a] for a in ax]))
                if size == 0 or shape[i] % size:
                    sizes_ok = False
            if sizes_ok:
                val = jax.lax.with_sharding_constraint(
                    val, NamedSharding(mesh, spec))
    return val


def run_op(op: OpDesc, env: Dict[str, object], ctx: ExecContext, block: Block):
    """Execute one op by tracing its compute fn; rebind outputs in env."""
    impl = require_op(op.type)
    # control-flow ops (dynamic_rnn/while/cond) lower sub-blocks themselves:
    # they need the program and the enclosing environment (for captured vars
    # like parameters — ≙ the reference's parent-scope lookup, scope.h:62).
    ctx.program = block.program
    ctx.env = env
    ctx.block_idx = block.idx
    ins = {slot: [env[n] for n in names] for slot, names in op.inputs.items()}
    if not impl.supports_sparse:
        # ops without a SelectedRows kernel get sparse inputs densified
        # (≙ the reference's data transform between mismatched kernels)
        from .selected_rows import maybe_dense
        ins = {slot: [maybe_dense(v) for v in vals]
               for slot, vals in ins.items()}
    # named_scope tags every primitive this op traces with the PROGRAM
    # op's type+index, so a device profile (and an XLA dump) attributes
    # hot HLO back to program IR ops — the device-side complement of the
    # executor's host-phase timing. Trace-time-only; HLO opcodes are
    # untouched (the collective-counting tests key on opcodes).
    with jax.named_scope(f"{op.type}.{getattr(ctx, 'op_index', 0)}"):
        outs = impl.compute(ctx, ins, op.attrs)
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if len(vals) != len(names):
            raise RuntimeError(
                f"op {op.type}: slot {slot} produced {len(vals)} values for "
                f"{len(names)} names {names}")
        for n, v in zip(names, vals):
            env[n] = _apply_var_marks(block, n, v, ctx)


def _run_remat_segment(ops, start: int, stop: int, range_stop: int, env,
                       ctx, block, live_out):
    """Trace ops[start:stop] under jax.checkpoint: their intermediate
    activations are rematerialized in the backward pass instead of saved
    (≙ memory_optimization_transpiler.py's liveness-based var reuse,
    re-read as XLA-native rematerialization).

    Only values read AFTER the segment (by ops[stop:range_stop] or the
    caller's live_out set) escape as checkpoint outputs — everything
    returned from a checkpointed fn is a saved primal, so emitting every
    intermediate would defeat the remat entirely.
    """
    seg = ops[start:stop]
    read: List[str] = []
    defined: set = set()
    for op in seg:
        for n in op.input_names():
            if n in env and n not in defined and n not in read:
                read.append(n)
        defined.update(op.output_names())

    if live_out is None:
        # caller gave no liveness info (sub-block interpreters read
        # arbitrary names from env afterwards): every output escapes —
        # correctness over memory savings
        written = []
        for op in seg:
            for n in op.output_names():
                if n not in written:
                    written.append(n)
    else:
        later_reads = set(live_out)
        for op in ops[stop:range_stop]:
            later_reads.update(op.input_names())
        written = []
        for op in seg:
            for n in op.output_names():
                if n in later_reads and n not in written:
                    written.append(n)
        if not written:  # keep the segment observable
            written = list(seg[-1].output_names())

    def seg_fn(vals):
        e = dict(env)
        e.update(zip(read, vals))
        for k, op in enumerate(seg):
            ctx.op_index = start + k
            run_op(op, e, ctx, block)
        return tuple(e[n] for n in written)

    # remat_policy (remat_scope(tag, policy=...)): "save_attn" keeps the
    # flash-attention outputs (tagged via checkpoint_name in
    # ops/attention_ops.py) as saved primals so the backward recomputes
    # only the cheap elementwise/matmul parts; "dots" = checkpoint_dots.
    pol_name = seg[0].attrs.get("remat_policy")
    policy = None
    if pol_name == "save_attn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "flash_attn_out")
    elif pol_name == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    elif pol_name is not None:
        raise ValueError(f"unknown remat_policy {pol_name!r} "
                         "(save_attn | dots)")
    ckpt = (jax.checkpoint if policy is None
            else functools.partial(jax.checkpoint, policy=policy))
    outs = ckpt(seg_fn)(tuple(env[n] for n in read))
    env.update(zip(written, outs))


def iter_op_runs(ops: Sequence[OpDesc], start: int, stop: int):
    """Yield the maximal runs ``(i, j, tag)`` of ops[start:stop] sharing
    one ``remat_scope`` tag — untagged ops are unit runs, tagged ops
    coalesce into one run per contiguous tag span. This is THE run
    segmentation of the lowering: run_op_range executes exactly these
    runs (tagged ones under jax.checkpoint), the static memory estimator
    (analysis/memory.py) prices residuals at these boundaries, and the
    per-op profiler (obs/opprof.py) compiles and times these same
    segments — one definition, so measured attribution, memory liveness,
    and the traced program can never segment differently."""
    i = start
    while i < stop:
        tag = ops[i].attrs.get("remat_scope")
        j = i + 1
        if tag is not None:
            while j < stop and ops[j].attrs.get("remat_scope") == tag:
                j += 1
        yield i, j, tag
        i = j


def run_op_range(ops: Sequence[OpDesc], start: int, stop: int,
                 env: Dict[str, object], ctx: ExecContext, block: Block,
                 live_out=None):
    """live_out: names the CALLER reads from env after this range — used
    to bound what escapes a remat segment. None = everything may escape
    (safe default for sub-block interpreters)."""
    for i, j, tag in iter_op_runs(ops, start, stop):
        if tag is None:
            ctx.op_index = i
            run_op(ops[i], env, ctx, block)
        else:
            _run_remat_segment(ops, i, j, stop, env, ctx, block, live_out)
    return env


def post_forward_reads(block: Block) -> set:
    """Names the post-autodiff suffix (optimizer ops) reads, plus the
    loss — the values that must survive the forward pass. ONE shared
    definition for the traced lowering (run_block_with_autodiff seeds
    needed_after from it) and the static memory estimator
    (analysis/memory.py), so the liveness the estimator prices is the
    liveness the lowering actually keeps. Empty set when the block has
    no autodiff marker (inference programs)."""
    ops = block.ops
    bwd_idx = next((i for i, o in enumerate(ops)
                    if o.type == AUTODIFF_OP), None)
    if bwd_idx is None:
        return set()
    needed = {ops[bwd_idx].attrs["loss"]}
    for op in ops[bwd_idx + 1:]:
        needed.update(op.input_names())
    return needed


def _float_like(v):
    return jnp.issubdtype(jnp.result_type(v), jnp.floating)


def run_block_with_autodiff(block: Block, env: Dict[str, object], ctx: ExecContext):
    """Execute a block that may contain one autodiff pseudo-op.

    The prefix [0, bwd) is the forward program; it runs inside
    jax.value_and_grad w.r.t. the declared parameters so that XLA compiles
    forward+backward as one fused computation. ≙ the structural effect of
    backward.append_backward (python/paddle/fluid/backward.py:434) without
    materializing per-op grad ops.
    """
    ops = block.ops
    bwd_idx = next((i for i, o in enumerate(ops) if o.type == AUTODIFF_OP), None)
    if bwd_idx is None:
        return run_op_range(ops, 0, len(ops), env, ctx, block,
                            live_out=getattr(ctx, "live_out", None))

    bop = ops[bwd_idx]
    loss_name = bop.attrs["loss"]
    param_names = list(bop.attrs["params"])
    grad_names = list(bop.attrs["grad_names"])
    grad_of = dict(zip(param_names, grad_names))
    loss_scale = float(bop.attrs.get("loss_scale", 1.0))
    amp = getattr(ctx, "amp_dtype", None)

    # --- sparse embedding grads (≙ SelectedRows, selected_rows.h:30) ------
    # lookup_table(is_sparse=True) params are differentiated through a
    # per-op zero surrogate added to the gathered rows instead of through
    # the full table, so the cotangent is [n_ids, D] — never [vocab, D].
    # Restricted to block-0 lookups (embeddings inside control-flow
    # sub-blocks fall back to dense grads).
    sparse_ops = [
        (i, op.inputs["W"][0], op.inputs["Ids"][0])
        for i, op in enumerate(ops[:bwd_idx])
        if op.type == "lookup_table" and op.attrs.get("is_sparse")
        and op.inputs["W"][0] in grad_of
    ]
    # a table consumed by anything OTHER than its sparse lookups (tied
    # softmax projection, a second dense lookup) must take the dense path —
    # the surrogate only captures cotangents at the sparse lookup sites
    sparse_op_idx = {i for i, _, _ in sparse_ops}
    for j, op in enumerate(ops[:bwd_idx]):
        if j in sparse_op_idx:
            continue
        used = set(op.input_names())
        sparse_ops = [(i, w, ids) for i, w, ids in sparse_ops
                      if w not in used]
        sparse_op_idx = {i for i, _, _ in sparse_ops}
    sparse_param_names = {w for _, w, _ in sparse_ops}
    dense_param_vals = {p: env[p] for p in param_names
                        if p not in sparse_param_names}

    surrogates = {}
    if sparse_ops:
        # abstract pre-pass: learn each lookup's post-squeeze ids shape
        # without running any real compute (≙ compile-time InferShape)
        def probe(e_in):
            pctx = ExecContext(ctx._rng_key, is_test=ctx.is_test,
                               mesh=ctx.mesh)
            pctx.amp_dtype = amp
            pctx.sparse_probe = {}
            run_op_range(ops, 0, bwd_idx, dict(e_in), pctx, block)
            return {i: jnp.zeros(v.shape, jnp.int32)
                    for i, v in pctx.sparse_probe.items()}
        id_shapes = jax.eval_shape(probe, env)
        for i, w_name, _ in sparse_ops:
            wv = env[w_name]
            sdt = jnp.result_type(wv)
            if amp is not None and sdt == jnp.float32:
                sdt = jnp.dtype(amp)  # match the amp-cast table's output
            surrogates[i] = jnp.zeros(
                tuple(id_shapes[i].shape) + (wv.shape[-1],), sdt)

    # names still needed once the forward finishes: the loss, whatever the
    # optimizer suffix reads (post_forward_reads — shared with the static
    # memory estimator), the step's fetches/state, and sparse ids.
    # Anything else may die inside the forward — which is what lets remat
    # segments actually discard activations (their residuals must not be
    # aux outputs of the differentiated function).
    needed_after = post_forward_reads(block) | {loss_name} \
        | set(getattr(ctx, "live_out", ()) or ())
    needed_after.update(ids_name for _, _, ids_name in sparse_ops)

    def fwd(diff):
        pvals, zvals = diff
        e = dict(env)
        if amp is not None:
            # mixed precision: compute path sees low-precision params, but
            # grads flow to the f32 masters (the cast is differentiated, so
            # value_and_grad returns f32 grads for the optimizer ops)
            adt = jnp.dtype(amp)
            e.update({p: (v.astype(adt)
                          if jnp.result_type(v) == jnp.float32 else v)
                      for p, v in pvals.items()})
            # sparse tables live outside pvals (grads come via surrogates),
            # but their compute-dtype cast must match the dense params
            for sp in sparse_param_names:
                if jnp.result_type(e[sp]) == jnp.float32:
                    e[sp] = e[sp].astype(adt)
        else:
            e.update(pvals)
        ctx.sparse_surrogates = zvals
        try:
            e = run_op_range(ops, 0, bwd_idx, e, ctx, block,
                             live_out=needed_after)
        finally:
            ctx.sparse_surrogates = None
        loss = jnp.sum(e[loss_name].astype(jnp.float32))
        return loss * loss_scale, {k: v for k, v in e.items()
                                   if k in needed_after}

    orig_params = {p: env[p] for p in param_names}
    (_, env2), (grads, gz) = jax.value_and_grad(fwd, has_aux=True)(
        (dense_param_vals, surrogates))
    env = dict(env)
    env.update(env2)
    # the post-forward env holds the amp-cast param values; the optimizer
    # suffix must update the f32 MASTERS, not a bf16-quantized copy (the
    # whole point of master weights: small updates still accumulate)
    env.update(orig_params)
    for p, g in grad_of.items():
        if p not in sparse_param_names:
            env[g] = grads[p]

    if sparse_ops:
        from .selected_rows import (rowsparse_from_ids, merge_rowsparse,
                                    squeeze_trailing_ids)
        built: Dict[str, object] = {}
        for i, w_name, ids_name in sparse_ops:
            ids = squeeze_trailing_ids(env[ids_name])
            height = int(env[w_name].shape[0])
            rs = rowsparse_from_ids(ids, gz[i], height)
            built[w_name] = (rs if w_name not in built
                             else merge_rowsparse(built[w_name], rs))
        for w_name, rs in built.items():
            env[grad_of[w_name]] = rs

    # guard fault injection (resilience/guard.py): a traced int32 code
    # (0 none, 1 nan_loss, 2 nan_grad) poisons the bound loss/grads
    # in-graph via SELECT — never arithmetic, so a code of 0 is bit-exact
    # (adding 0.0 would already flip -0.0 to +0.0). The downstream
    # step_health op and optimizer suffix then see exactly what a real
    # anomalous batch would have produced.
    fault = getattr(ctx, "guard_fault", None)
    if fault is not None:
        from .selected_rows import RowSparseGrad

        def _poison(v, code):
            if isinstance(v, RowSparseGrad):
                return v._replace(values=_poison(v.values, code))
            bad = jnp.full(jnp.shape(v), jnp.nan, jnp.result_type(v))
            return jnp.where(fault == code, bad, v)

        env[loss_name] = _poison(env[loss_name], 1)
        for g_name in grad_of.values():
            if g_name in env:
                env[g_name] = _poison(env[g_name], 2)

    return run_op_range(ops, bwd_idx + 1, len(ops), env, ctx, block)


def build_step_fn(program: Program, feed_names: Sequence[str],
                  fetch_names: Sequence[str], state_in_names: Sequence[str],
                  is_test: bool = False, mesh=None, guard: bool = False):
    """Build the pure step function for block 0 of `program`.

    Returns (step, state_out_names): state_out_names is the set of
    persistable vars the step returns as new state (inputs carried through +
    any persistable var an op writes — e.g. param updates, accumulators).

    guard=True (resilience/guard.py; program must carry a `step_health`
    op) makes the update GUARDED: every state output becomes
    ``where(healthy, updated, old)``, so an anomalous step leaves all
    persistable state bit-identical — the skip is inside the compiled
    step, donation-safe, and valid under any GSPMD update sharding. The
    reserved ``__guard_fault__`` feed threads the deterministic fault
    code to the in-graph poisoning above.
    """
    block = program.global_block
    ops = block.ops
    state_in = list(state_in_names)

    persist_written = []
    seen = set(state_in)
    for op in ops:
        for n in op.output_names():
            if n in seen:
                continue
            try:
                v = block.var(n)
            except KeyError:
                continue
            if v.persistable:
                persist_written.append(n)
                seen.add(n)
    state_out_names = state_in + persist_written

    def step(state: Dict[str, object], feed: Dict[str, object], rng):
        ctx = ExecContext(rng, is_test=is_test, mesh=mesh)
        ctx.amp_dtype = program.amp_dtype
        ctx.live_out = set(fetch_names) | set(state_out_names)
        if guard:
            from ..resilience.guard import FAULT_FEED
            ctx.guard_fault = feed.get(FAULT_FEED)
        env: Dict[str, object] = {}
        env.update(state)
        env.update(feed)
        if program.amp_dtype is not None:
            # AMP entry casts: float32 feeds run in the compute dtype, so the
            # whole activation path is low-precision; params are cast inside
            # the differentiated forward (run_block_with_autodiff) so their
            # f32 masters keep receiving f32 grads. Wire-codec scale
            # companions (data/codec.py) are exempt: the feed_dequant op
            # consumes them at f32 and lands the decoded batch directly at
            # the compute dtype — truncating the scales would double-quantize.
            from .types import CODEC_SCALE_SUFFIX
            adt = jnp.dtype(program.amp_dtype)
            for k in feed:
                if k.endswith(CODEC_SCALE_SUFFIX):
                    continue
                if jnp.result_type(env[k]) == jnp.float32:
                    env[k] = env[k].astype(adt)
        env = run_block_with_autodiff(block, env, ctx)
        fetches = tuple(env[n] for n in fetch_names)
        new_state = {n: env[n] for n in state_out_names if n in env}
        if guard:
            from ..resilience.guard import HEALTH_VAR
            healthy = env[HEALTH_VAR]
            # guarded update: an unhealthy step keeps EVERY pre-step
            # state value (params, accumulators, bn stats). SELECT reads
            # the donated input before any aliasing write, so donation
            # stays on. Vars the scope did not hold yet (no old value)
            # keep the computed one.
            new_state = {n: (jnp.where(healthy, v, state[n])
                             if n in state else v)
                         for n, v in new_state.items()}
        return fetches, new_state

    return step, state_out_names


def build_loop_fn(program: Program, feed_names: Sequence[str],
                  fetch_names: Sequence[str], state_in_names: Sequence[str],
                  n_steps: int, is_test: bool = False, mesh=None,
                  per_step_feeds: bool = False, unroll: int = 1,
                  guard: bool = False):
    """Build a function running `n_steps` training steps in ONE dispatch.

    The reference amortizes host work with scope reuse
    (scope_buffered_ssa_graph_executor.cc, num_iteration_per_drop_scope);
    on TPU the equivalent lever is a device-side training loop: lax.scan
    over the step function, so host→device dispatch (and any control-plane
    latency) is paid once per n_steps instead of per step.

    feed values: per_step_feeds=False → one feed dict reused every step
    (fake-data benching, ≙ fluid_benchmark.py --use_fake_data);
    per_step_feeds=True → each feed array carries a leading [n_steps] axis.

    unroll: how many steps the scan's body holds. One, unless the caller
    has measured a second to be free: a second step's buffers are live
    while the first's last ones still are, and where that crosses the
    device's memory the compiler recomputes activations to fit (at the
    1.3B train cell the head's logits product, every step:
    `loop_compile_figures` counts it; PERF.md section 6, PR 50). What the
    body is goes to the trace ring each time a loop is built, as
    `program/loop_plan` (a record with no duration, as a kernel's plan).

    Returns (loop, state_out_names); loop(state, feed, rng) ->
    (stacked_fetches, new_state) with each fetch stacked to [n_steps, ...].
    """
    from ..obs import trace as obs_trace
    obs_trace.phase("program", "loop_plan", 0.0, attrs=dict(
        n_steps=int(n_steps), unroll=int(unroll),
        per_step_feeds=bool(per_step_feeds)))
    step, state_out_names = build_step_fn(program, feed_names, fetch_names,
                                          state_in_names, is_test=is_test,
                                          mesh=mesh, guard=guard)

    def loop(state: Dict[str, object], feed: Dict[str, object], rng):
        feed = {k: jnp.asarray(v) for k, v in feed.items()}

        def one(carry, i):
            f = ({k: v[i] for k, v in feed.items()} if per_step_feeds
                 else feed)
            fetches, st = step(carry, f, jax.random.fold_in(rng, i))
            return st, fetches

        # scan carries must be structurally identical: seed state vars that
        # the step writes but the scope didn't hold yet (zeros are safe —
        # a read-before-write of such a var would fail in build_step_fn too)
        out_shapes = jax.eval_shape(lambda s: one(s, jnp.int32(0))[0], state)
        full = dict(state)
        for k, sh in out_shapes.items():
            if k not in full:
                full[k] = jnp.zeros(sh.shape, sh.dtype)
        new_state, stacked = jax.lax.scan(one, full, jnp.arange(n_steps),
                                          unroll=unroll)
        return stacked, new_state

    return loop, state_out_names


#: an instruction XLA's rematerialisation pass cloned: `fusion.12.remat`,
#: a second clone of one `.remat2`
_REMAT_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+\.remat\d*) = (\S+)(.*)$", re.M)
_ESTIMATED_CYCLES = re.compile(r'"estimated_cycles":"?(\d+)')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def loop_compile_figures(program: Program, feeds: Dict[str, object],
                         fetch_names: Sequence[str], n_steps: int,
                         per_step_feeds: bool = False, unroll: int = 1,
                         sharding=None) -> Dict[str, object]:
    """What the compiler makes of `run_loop`'s executable, from shapes
    alone: nothing runs and no start-up program is needed.

    The loop is built as `Executor.run_loop` builds it (state donated)
    over `ShapeDtypeStruct`s: the state is the program's persistable
    variables that its ops read, `feeds` are {name: ShapeDtypeStruct} as
    the loop takes them (a leading [n_steps] axis under per_step_feeds;
    integer feeds int32, as `_prep_feed` leaves them). `sharding` places
    every argument, e.g. on one device of a described topology (the
    caller steers `jax.default_backend` for the kernel gates).

    Returns `temp_bytes` / `argument_bytes` (`memory_analysis()`),
    `remat_instructions`: how many instructions of the optimized module
    the compiler's own rematerialisation pass cloned (0 the healthy
    reading: it runs only where the program would not fit otherwise),
    `remat_cycles`: the sum of their `estimated_cycles` (a Pallas call and
    a scatter carry none), and `remat`: (name, result shape, op_name) of
    each. The sweep tool and the tier-1 guard read these; the executor's
    hot path does not.
    """
    block = program.global_block
    read = {n for b in program.blocks for op in b.ops
            for n in op.input_names()}

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=sharding)

    state = {v.name: sds(v.shape, v.dtype) for v in block.vars.values()
             if v.persistable and v.name in read}
    feeds = {k: sds(v.shape, v.dtype) for k, v in feeds.items()}
    loop, _ = build_loop_fn(program, list(feeds), list(fetch_names),
                            sorted(state), n_steps=n_steps,
                            per_step_feeds=per_step_feeds, unroll=unroll)
    key = sds((2,), jnp.uint32)
    compiled = jax.jit(loop, donate_argnums=(0,)).lower(
        state, feeds, key).compile()
    mem = compiled.memory_analysis()
    remat = []
    cycles = 0
    for name, shape, rest in _REMAT_INSTRUCTION.findall(compiled.as_text()):
        op_name = _OP_NAME.search(rest)
        est = _ESTIMATED_CYCLES.search(rest)
        cycles += int(est.group(1)) if est else 0
        remat.append((name, shape, op_name.group(1) if op_name else ""))
    return dict(temp_bytes=int(mem.temp_size_in_bytes),
                argument_bytes=int(mem.argument_size_in_bytes),
                remat_instructions=len(remat), remat_cycles=cycles,
                remat=remat)
