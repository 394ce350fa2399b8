"""ParallelExecutor: SPMD data-parallel (and mesh-parallel) training.

≙ reference ParallelExecutor (paddle/fluid/framework/parallel_executor.cc:54,
python/paddle/fluid/parallel_executor.py:29) + the SSA-graph machinery in
framework/details/. The reference replicates the program per GPU, inserts
NCCL allreduce op-handles per gradient, and drives the DAG with a host
thread pool. Here the SAME lowered step function is jit-compiled over a
jax.sharding.Mesh: feeds are batch-sharded (≙ SplitLoDTensor feed split,
parallel_executor.cc:216), parameters replicated (or sharded per
BuildStrategy), and XLA GSPMD inserts the gradient all-reduces that
AllReduceOpHandle (details/all_reduce_op_handle.cc:42) hand-codes — riding
ICI instead of NCCL rings.

BuildStrategy parity (details/build_strategy.h:24-33):
  * ReduceStrategy.AllReduce — params+optimizer state replicated, grad psum.
  * ReduceStrategy.Reduce    — optimizer state sharded over dp (the modern
    ZeRO-1 reading of the reference's reduce+broadcast round-robin placement,
    multi_devices_graph_builder.cc:234-259).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.program import (Program, VarDesc, default_main_program,
                            iter_optimizer_state_inputs)
from ..core.scope import Scope, global_scope
from ..core.executor import Executor, TimedExecutorMixin, _Compiled
from ..core.async_fetch import LazyFetch
from ..core import lowering
from .mesh import default_mesh, spec_for, DP


class ReduceStrategy:
    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """≙ details/build_strategy.h. gradient_scale_ and debug fields kept."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.debug_graphviz_path = ""


class ExecutionStrategy:
    """≙ details/execution_strategy.h — scheduling knobs. XLA owns
    scheduling, so these are accepted and recorded only."""

    def __init__(self):
        self.num_threads = 0
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 100


class ParallelExecutor(TimedExecutorMixin):
    def __init__(self, use_cuda: bool = False, loss_name: Optional[str] = None,
                 main_program: Optional[Program] = None,
                 share_vars_from: Optional["ParallelExecutor"] = None,
                 exec_strategy: Optional[ExecutionStrategy] = None,
                 build_strategy: Optional[BuildStrategy] = None,
                 num_trainers: int = 1, trainer_id: int = 0,
                 scope: Optional[Scope] = None, mesh: Optional[Mesh] = None,
                 plan=None):
        """plan: a PlacementPlan (analysis/planner.py) — artifact object,
        plan/artifact dict, or a saved-artifact path. Applies the plan's
        per-var specs + sp rewrite to `main_program` in place, builds the
        mesh from the plan's axes when `mesh` is not given, and switches
        to ReduceStrategy.Reduce when the plan says ZeRO — so the
        planner-chosen placement executes with zero per-model code."""
        self._program = main_program if main_program is not None else default_main_program()
        self._scope = scope or global_scope()
        self._build_strategy = build_strategy or BuildStrategy()
        if plan is not None:
            from ..analysis.planner import apply_plan, resolve_plan
            from .mesh import mesh_from_plan
            plan = resolve_plan(plan)
            apply_plan(self._program, plan)
            if mesh is None:
                mesh = mesh_from_plan(plan)
            if plan.get("zero"):
                # copy before flipping: a caller-supplied BuildStrategy
                # must not leak Reduce into executors built without a plan
                import copy
                self._build_strategy = copy.copy(self._build_strategy)
                self._build_strategy.reduce_strategy = ReduceStrategy.Reduce
        self._mesh = mesh or default_mesh()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._loss_name = loss_name
        self._cache: Dict[tuple, _Compiled] = {}
        self._run_counter = 0
        self._init_timing()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope

    # -- sharding decisions -------------------------------------------------
    def _divisible(self, spec: PartitionSpec, value) -> PartitionSpec:
        """Drop spec axes a dim cannot be evenly split over (GSPMD rejects
        explicit non-divisible shardings); e.g. a vocab of 50 over 8 devices
        falls back to replication rather than erroring. ≙ the reference's
        block-size rounding in slice_variable (distribute_transpiler.py:74),
        which also degrades placement instead of failing."""
        shape = jnp.shape(value)
        dims = []
        for i, axes in enumerate(tuple(spec)):
            if axes is None or i >= len(shape):
                dims.append(axes)
                continue
            ax_tuple = axes if isinstance(axes, tuple) else (axes,)
            size = int(np.prod([self._mesh.shape[a] for a in ax_tuple]))
            dims.append(axes if size and shape[i] % size == 0 else None)
        while dims and dims[-1] is None:
            dims.pop()
        return PartitionSpec(*dims)

    def _optimizer_state_names(self) -> dict:
        """Map accumulator var name -> its parameter name (velocity,
        moments, …). ≙ identifying the per-param state the reference's
        kReduce mode places on the grad's reduce device
        (multi_devices_graph_builder.cc:234-259). Cached per program
        CONTENT (fingerprint), so mutating the program between runs —
        which the compile cache supports — refreshes the set."""
        fp = self._program.fingerprint()
        if getattr(self, "_acc_cache_for", None) != fp:
            self._acc_cache = {acc: p for p, acc in
                               iter_optimizer_state_inputs(
                                   self._program.global_block)}
            self._acc_cache_for = fp
        return self._acc_cache

    def _state_spec(self, var: VarDesc, value) -> PartitionSpec:
        if var is not None and var.sharding:
            return self._divisible(spec_for(var.sharding, self._mesh), value)
        if var is not None and not var.is_parameter:
            # an accumulator with no sharding of its own follows its
            # parameter (same shape ⇒ same layout): a sharded param (moe
            # 'ep' experts, tp row/col shards) with replicated moments
            # would force GSPMD to all-gather every grad at the optimizer
            # update — measured on the moe leg: 8 expert-weight-shaped
            # all-gathers per step before this rule, 0 after
            p_name = self._optimizer_state_names().get(var.name)
            if p_name is not None:
                try:
                    p = self._program.global_block.var(p_name)
                except KeyError:
                    p = None
                if (p is not None and p.sharding
                        and tuple(p.shape) == tuple(var.shape)):
                    return self._divisible(spec_for(p.sharding, self._mesh),
                                           value)
        if (self._build_strategy.reduce_strategy == ReduceStrategy.Reduce
                and var is not None and not var.is_parameter
                and var.name in self._optimizer_state_names()):
            # ZeRO-1: shard the accumulator on its first dp-divisible axis.
            # GSPMD then computes the optimizer update dp-sharded (grads
            # arrive reduce-scattered) and all-gathers the updated param —
            # exactly the reduce-then-broadcast dataflow of the reference's
            # kReduce mode, derived instead of hand-built.
            shape = jnp.shape(value)
            dp_size = self._mesh.shape.get(DP, 1)
            if dp_size > 1:
                for i, s in enumerate(shape):
                    if s % dp_size == 0 and s >= dp_size:
                        return PartitionSpec(*([None] * i + [DP]))
        return PartitionSpec()

    def _feed_spec(self, var: Optional[VarDesc], value,
                   step_axis: bool = False) -> PartitionSpec:
        """step_axis: the array carries a leading [n_steps] window axis
        (run_loop per_step_feeds) — replicated; the batch axis moves to
        dim 1 and the var's own spec shifts right by one."""
        if var is not None and var.sharding:
            spec = spec_for(var.sharding, self._mesh)
            if step_axis:
                spec = PartitionSpec(None, *tuple(spec))
            # _divisible guard like _state_spec: an epoch-tail fragment
            # batch (3 rows on dp=2) must degrade to replication on the
            # offending axis, not crash jit in_shardings
            return self._divisible(spec, value)
        shape = jnp.shape(value)
        bdim = 1 if step_axis else 0
        dp_size = self._mesh.shape.get(DP, 1)
        if (len(shape) > bdim and dp_size > 1
                and shape[bdim] % dp_size == 0):
            # batch split ≙ SplitLoDTensor
            return PartitionSpec(*([None] * bdim), DP)
        return PartitionSpec()

    # -- compile ------------------------------------------------------------
    def _get_compiled(self, fetch_list: Sequence, feed: dict,
                      loop: Optional[tuple] = None, guard: bool = False):
        """Build (or fetch from cache) the jitted sharded step for this
        (program, feed-shapes, fetches) signature. Returns
        (compiled, state, feed_arrays, was_cached). `loop` = (n_steps,
        per_step_feeds, unroll) compiles a device-side lax.scan over the
        SAME sharded step — the multi-device fast path (run_loop).

        guard=True: guarded update + the step-health fetch, same contract
        as Executor (resilience/guard.py). The health scalar and the
        fault-code feed are replicated; the guarded select runs INSIDE
        the partitioned step, so it stays valid under whatever update
        sharding GSPMD picks (ZeRO-1 sharded accumulators included)."""
        program = self._program
        block = program.global_block
        with self._timings.span("host_prep"):
            exe_helper = Executor()
            per_step = bool(loop and loop[1])
            fetch_names = [exe_helper._fetch_name(f) for f in fetch_list]
            feed_arrays = exe_helper._prep_feed(program, feed,
                                                per_step=per_step)
            if guard:
                from ..resilience import guard as guard_mod
                guard_mod.assert_instrumented(program)
                fetch_names = fetch_names + [guard_mod.HEALTH_VAR]
                feed_arrays[guard_mod.FAULT_FEED] = guard_mod.fault_feed(
                    loop[0] if per_step else None)
                guard_key = ("guard", guard_mod.max_gnorm())
            else:
                guard_key = ()
            state = exe_helper._state_for(program, self._scope)

        feed_sig = tuple(sorted((k, v.shape, str(v.dtype))
                                for k, v in feed_arrays.items()))
        state_sig = tuple(sorted((k, jnp.shape(v), str(jnp.result_type(v)))
                                 for k, v in state.items()))
        key = (program.fingerprint(), feed_sig, tuple(fetch_names), state_sig,
               id(self._mesh), self._build_strategy.reduce_strategy, loop,
               guard_key)

        compiled = self._cache.get(key)
        was_cached = compiled is not None
        if compiled is None:
            from ..analysis import verify_enabled, verify_program
            if verify_enabled():
                # the mesh is known here, so the shard divisibility checks
                # AND the collective audit run concrete (the single-chip
                # Executor can only check axis names against the alphabet)
                verify_program(program, feeds=list(feed_arrays),
                               fetches=fetch_names,
                               mesh=self._mesh).raise_if_errors()
            # memory-budget pre-compile gate (analysis/memory.py). The
            # mesh is known, so the estimate prices the PER-DEVICE batch
            # (feeds' batch-dim shard factor divides it); params and
            # optimizer state stay whole-program — replicated under pure
            # dp, an upper bound under tp/ZeRO — conservative-safe.
            from ..analysis.memory import enforce_budget
            from ..core.executor import _autotune_batch_hint
            bh = _autotune_batch_hint(program, feed_arrays,
                                      1 if per_step else 0)
            enforce_budget(program, batch=bh, mesh=self._mesh)
            # drift monitor (obs/drift.py): whole-program roofline
            # prediction recorded at compile time, same contract as the
            # single-chip Executor — measured sharded steps fold into
            # the same pt_model_* entry
            if fetch_names:
                from ..obs import drift as obs_drift
                obs_drift.observe_prediction(program, batch=bh,
                                             timer=self._timings)
            if loop is None:
                step, state_out = lowering.build_step_fn(
                    program, list(feed_arrays), fetch_names, sorted(state),
                    mesh=self._mesh, guard=guard)
            else:
                n_steps, per_step_feeds, unroll = loop
                if unroll is None:
                    from ..analysis.memory import loop_body_steps
                    unroll = loop_body_steps(program, bh, mesh=self._mesh)
                step, state_out = lowering.build_loop_fn(
                    program, list(feed_arrays), fetch_names, sorted(state),
                    n_steps=n_steps, mesh=self._mesh,
                    per_step_feeds=per_step_feeds, unroll=unroll,
                    guard=guard)

            def var_of(name):
                try:
                    return block.var(name)
                except KeyError:
                    return None

            mesh = self._mesh

            def feed_sharding(n, v):
                spec = self._feed_spec(var_of(n), v, step_axis=per_step)
                return NamedSharding(mesh, spec)

            state_shardings = {
                n: NamedSharding(mesh, self._state_spec(var_of(n), v))
                for n, v in state.items()}
            feed_shardings = {n: feed_sharding(n, v)
                              for n, v in feed_arrays.items()}
            rng_sharding = NamedSharding(mesh, PartitionSpec())
            out_state_shardings = {
                n: state_shardings.get(n, NamedSharding(mesh, self._state_spec(var_of(n), state.get(n))))
                for n in state_out}
            fetch_shardings = tuple(NamedSharding(mesh, PartitionSpec())
                                    for _ in fetch_names)
            fn = jax.jit(step,
                         in_shardings=(state_shardings, feed_shardings,
                                       rng_sharding),
                         out_shardings=(fetch_shardings, out_state_shardings),
                         donate_argnums=(0,))
            compiled = _Compiled(fn, sorted(state), state_out, fetch_names)
            self._cache[key] = compiled
        return compiled, state, feed_arrays, was_cached

    def compiled_hlo(self, fetch_list: Sequence,
                     feed: Optional[dict] = None) -> str:
        """Post-GSPMD optimized HLO of the sharded step, for inspection.

        On a rig with no multi-chip hardware this is the load-bearing
        evidence of WHAT the parallelism axes actually emit — tests count
        collective instructions (all-reduce / reduce-scatter /
        collective-permute / all-to-all) instead of assuming GSPMD chose
        the intended program (tests/test_collectives_emitted.py)."""
        compiled, state, feed_arrays, _ = self._get_compiled(fetch_list,
                                                             feed or {})
        rng = jax.random.PRNGKey(0)
        with self._mesh:
            return compiled.fn.lower(state, feed_arrays,
                                     rng).compile().as_text()

    # -- run ----------------------------------------------------------------
    def run_loop(self, fetch_list: Sequence, feed: Optional[dict] = None,
                 n_steps: int = 1, per_step_feeds: bool = False,
                 unroll: Optional[int] = None, return_numpy: bool = True,
                 lazy: bool = False, guard: bool = False):
        """Run `n_steps` SHARDED training steps in one device dispatch:
        lax.scan over the same GSPMD-partitioned step `run` executes.

        This is the multi-device reading of the reference's hot loop —
        ParallelExecutor::Run drives the whole multi-GPU step graph per
        call (parallel_executor.cc:193, threaded_ssa_graph_executor.cc) —
        composed with the device-side loop that is this runtime's fast
        path (host dispatch costs 150-250 ms on the benched fabric;
        docs/design_decisions.md). Feeds follow Executor.run_loop
        semantics: same dict every step, or a leading [n_steps] axis with
        per_step_feeds=True (the batch axis then dp-shards at dim 1).
        `unroll`, the steps the scan's body holds, is chosen as
        Executor.run_loop chooses it where not given: one step unless
        the estimate of a device's share (the per-device batch; state
        whole, an upper bound under tp) leaves room for two.
        Fetches come back stacked [n_steps, ...]."""
        feed = feed or {}
        compiled, state, feed_arrays, was_cached = self._get_compiled(
            fetch_list, feed, loop=(n_steps, per_step_feeds, unroll),
            guard=guard)
        return self._execute(compiled, state, feed_arrays, return_numpy,
                             was_cached, lazy=lazy, n_steps=n_steps)

    def run(self, fetch_list: Sequence, feed: Optional[dict] = None,
            feed_dict: Optional[dict] = None, return_numpy: bool = True,
            lazy: bool = False, guard: bool = False):
        """lazy=True: LazyFetch handles, same contract as Executor.run —
        the sharded step is enqueued and the host moves on. guard=True:
        guarded update + step-health fetch (resilience/guard.py)."""
        feed = feed if feed is not None else (feed_dict or {})
        compiled, state, feed_arrays, was_cached = self._get_compiled(
            fetch_list, feed, guard=guard)
        return self._execute(compiled, state, feed_arrays, return_numpy,
                             was_cached, lazy=lazy)

    def _execute(self, compiled, state, feed_arrays, return_numpy,
                 was_cached=True, lazy=False, n_steps=1):
        program = self._program
        seed = program.random_seed if program.random_seed is not None else 0
        self._run_counter += 1
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), self._run_counter)
        # measured-step recorder (obs/drift.py): settle-to-settle gaps,
        # cached runs only — see Executor._run_impl for the rationale
        settle = None
        if was_cached and compiled.fetch_names:
            from ..obs import drift as obs_drift
            settle = obs_drift.step_recorder(program.fingerprint(),
                                             n_steps)
        with self._dispatching(was_cached), self._mesh:
            fetches, new_state = compiled.fn(state, feed_arrays, rng)
        for name, val in new_state.items():
            self._scope.set_var(name, val)
        if lazy:
            from ..obs import trace as obs_trace
            span_ctx = obs_trace.current_attrs()
            return [LazyFetch(f, self._timings,
                              provenance=dict(span_ctx, fetch=n),
                              on_settle=settle)
                    for n, f in zip(compiled.fetch_names, fetches)]
        if return_numpy:
            with self._timings.span("device"):
                jax.block_until_ready(fetches)
            if settle is not None:
                settle()
            with self._timings.span("fetch"):
                # host-sync: ok — the sync return contract (return_numpy)
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    @property
    def device_count(self) -> int:
        return int(np.prod(list(self._mesh.shape.values())))

    def bcast_params(self):
        """≙ ParallelExecutor::BCastParamsToGPUs (parallel_executor.cc:134).
        Under GSPMD replication is a sharding property, so this is a no-op
        kept for API parity."""
        return None
