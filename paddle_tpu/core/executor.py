"""Executor: compile-and-run programs against a Scope.

≙ reference Executor (paddle/fluid/framework/executor.h:39, executor.cc:127)
and its Python wrapper (python/paddle/fluid/executor.py:183). The reference
interprets programs op-by-op per step; here `run` lowers the program ONCE per
(program, feed-signature, fetch-list) to a jitted XLA executable
(core/lowering.py) and replays it — the compile cache plays the role of the
reference's program cache (executor.py:165) and `Executor::Prepare`
(executor.cc:296).

Feed/fetch: the reference injects feed/fetch ops that move data through
holder variables (executor.cc:230-294). Under a functional runtime the feed
dict simply becomes jit arguments and fetches become return values — no ops.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .program import Program, VarDesc, default_main_program
from .scope import Scope, global_scope
from .types import device_dtype, np_dtype
from .async_fetch import LazyFetch, PhaseTimer
from . import lowering


class Place:
    """Device identity (≙ platform/place.h:25-57). On the JAX runtime the
    actual placement is owned by XLA; Place survives as an API-parity tag."""

    def __init__(self, kind: str = "tpu", index: int = 0):
        self.kind, self.index = kind, index

    def __repr__(self):
        return f"{self.kind.upper()}Place({self.index})"


def CPUPlace():
    return Place("cpu")


def TPUPlace(index: int = 0):
    return Place("tpu", index)


class _Compiled:
    __slots__ = ("fn", "state_in", "state_out", "fetch_names")

    def __init__(self, fn, state_in, state_out, fetch_names):
        self.fn = fn
        self.state_in = state_in
        self.state_out = state_out
        self.fetch_names = fetch_names


def _autotune_batch_hint(program: Program, feed_arrays: Dict[str, object],
                         bdim: int) -> int:
    """Batch-size hint for the gconv autotune pre-pass.

    The leading dim of an arbitrary feed is NOT necessarily a batch axis:
    a host-table rows feed is [capacity, dim], and dict order could hand
    its capacity to the tuner as the batch, caching measurements under
    the wrong n (ADVICE r5). Registered rows feeds are skipped outright;
    feeds bound to program data vars whose declared leading dim is the
    symbolic batch (-1, layers.data's append_batch_size) win immediately;
    anything else (static-shape data vars, unknown names) is only the
    first-seen fallback."""
    from .. import host_table as _ht
    rows_names = {t.rows_name for t in _ht.registered_tables().values()}
    fallback = None
    for name, v in feed_arrays.items():
        if name in rows_names:
            continue  # [capacity, dim] rows block: never a batch axis
        shp = jnp.shape(v)
        if len(shp) <= bdim:
            continue
        try:
            var = program.global_block.var(name)
        except KeyError:
            var = None
        if var is not None and getattr(var, "is_data", False):
            dims = tuple(var.shape or ())
            if dims and int(dims[0]) == -1:
                return int(shp[bdim])
        if fallback is None:
            fallback = int(shp[bdim])
    return fallback if fallback is not None else 8


class TimedExecutorMixin:
    """Shared per-phase timing + compile accounting for Executor and
    ParallelExecutor — one implementation so the charge policy (cold
    dispatches go to compile_s, never the dispatch phase) cannot drift
    between the single-chip and sharded paths."""

    def _init_timing(self):
        #: per-phase wall-time attribution (async_fetch.PhaseTimer);
        #: read/reset via step_timings()
        self._timings = PhaseTimer()
        #: cumulative seconds spent inside first-call (compiling)
        #: dispatches — kept OUT of the dispatch phase so a one-off 43 s
        #: compile cannot masquerade as per-step host overhead
        self.compile_s = 0.0
        #: compile events since construction — the pt_train_* family's
        #: compile counter (obs/metrics.py TrainMetrics) reads it
        self.compile_count = 0

    @contextmanager
    def _dispatching(self, was_cached: bool):
        """Around the jitted call. A cached one is an OPEN `dispatch`
        span, so that the stall sentinel sees one that hangs
        (obs/trace.py); a cold one compiles, is no dispatch, and is
        charged to compile_s."""
        if was_cached:
            with self._timings.span("dispatch"):
                yield
        else:
            t0 = time.perf_counter()
            yield
            seconds = time.perf_counter() - t0
            self.compile_s += seconds
            self.compile_count += 1
            from ..obs import trace as obs_trace
            if obs_trace.enabled():
                obs_trace.complete("compile", seconds, cat="exec")
        self._timings.count_run()

    def step_timings(self, reset: bool = False) -> dict:
        """Per-phase accounted seconds since the last reset (host_prep /
        dispatch / device / fetch + host_overhead_pct). `compile_s` rides
        along so callers see amortized vs per-step cost separately, and
        `phase_overruns` / `last_overrun`: the spans the stall sentinel
        found open far beyond their phase's usual length, and what the
        newest one's thread was doing (docs/observability.md)."""
        out = self._timings.overrun_snapshot()   # before a reset
        out.update(self._timings.snapshot(reset=reset))
        out["compile_s"] = round(self.compile_s, 3)
        if reset:
            self.compile_s = 0.0
        return out


class Executor(TimedExecutorMixin):
    def __init__(self, place: Optional[Place] = None):
        self.place = place or Place("tpu")
        self._cache: Dict[tuple, _Compiled] = {}
        self._run_counter = 0
        self._init_timing()

    # -- helpers ------------------------------------------------------------
    @staticmethod
    def _fetch_name(f) -> str:
        return f.name if isinstance(f, VarDesc) else str(f)

    def _prep_feed(self, program: Program, feed: Dict[str, object],
                   per_step: bool = False):
        """per_step: arrays carry a leading [n_steps] axis (run_loop's
        per_step_feeds mode); ragged list/LoDTensor feeds are not supported
        there — feed padded arrays (+ explicit lengths if not full)."""
        out = {}
        for name, val in feed.items():
            try:
                var = program.global_block.var(name)
            except KeyError:
                var = None

            # ragged feeds: LoDTensor / list of sequences -> padded + lengths
            # (≙ DataFeeder LoD handling, data_feeder.py:73)
            seq_len_name = getattr(var, "seq_len_var", None) if var else None
            from ..lod import LoDTensor, pad_sequences
            if isinstance(val, (LoDTensor, list, tuple)) and per_step:
                raise ValueError(
                    f"per-step feed {name!r}: ragged LoDTensor/list feeds "
                    "are not supported with per_step_feeds=True; pass a "
                    "padded [n_steps, B, T, ...] array (+ explicit "
                    f"{seq_len_name!r} lengths if sequences are not full)")
            if isinstance(val, LoDTensor):
                if val.lod_level > 1:
                    raise NotImplementedError(
                        f"feed {name!r}: nested (level-{val.lod_level}) "
                        "LoDTensor feeds are not supported by the executor "
                        "— call to_padded() yourself and feed the dense "
                        "array plus per-level length arrays explicitly")
                padded, lens = val.to_padded()
                val = padded
                if seq_len_name:
                    out[seq_len_name] = jnp.asarray(lens)
            elif seq_len_name and isinstance(val, (list, tuple)):
                dt = np_dtype(device_dtype(var.dtype)) if var else None
                padded, lens = pad_sequences(val, dtype=dt)
                val = padded
                out[seq_len_name] = jnp.asarray(lens)
            elif seq_len_name and seq_len_name not in feed:
                # shape-only inspection: never np.asarray a device array
                arr0 = val if hasattr(val, "shape") \
                    else np.asarray(val)  # host-sync: ok — host list feed
                # full-length sequences: [B, T, ...] -> lens [B]=T; with a
                # leading step axis, [N, B, T, ...] -> lens [N, B]=T
                if per_step:
                    out[seq_len_name] = jnp.full(arr0.shape[:2], arr0.shape[2],
                                                 np.int32)
                else:
                    out[seq_len_name] = jnp.full((arr0.shape[0],),
                                                 arr0.shape[1], np.int32)

            # on-wire feed codec (data/codec.py apply_wire_codec): the
            # var's recorded dtype IS the wire dtype and the dequant is
            # traced into the step. A raw float feed is host-encoded HERE
            # — before device_put — so the bytes that cross the pipe are
            # the compact ones; an already-encoded feed (the pipeline's
            # encode stage) falls through to the normal dtype check.
            wire = getattr(var, "wire_codec", None) if var is not None \
                else None
            if wire:
                from ..data import codec as _codec
                from .types import CODEC_SCALE_SUFFIX
                want_wire = np_dtype(device_dtype(var.dtype))
                if not isinstance(val, jax.Array):
                    # never a device value: guarded by the jax.Array check
                    arr = np.asarray(val)  # host-sync: ok — host feed
                    if arr.dtype != want_wire:
                        # any not-yet-encoded host batch is encoded here:
                        # f32/f64 directly, integer pixel batches (uint8
                        # images that used to cast to the f32 var dtype)
                        # via f32 — a bare astype to int8 would wrap
                        # 128..255 into garbage
                        if not np.issubdtype(arr.dtype, np.floating):
                            arr = arr.astype(np.float32)
                        payload, scale = _codec.encode_array(arr, wire)
                        out[name] = jnp.asarray(payload)
                        sname = name + CODEC_SCALE_SUFFIX
                        if scale is not None and sname not in feed:
                            out[sname] = jnp.asarray(scale)
                        continue
                elif (val.dtype != jnp.dtype(want_wire)
                        and str(var.dtype) not in ("bfloat16", "float16")):
                    # a raw batch already uploaded (f32, uint8 pixels…):
                    # the wire saving is forfeit and an astype to int8
                    # would be garbage — refuse loudly instead of
                    # corrupting the feed (bf16 wire vars are exempt:
                    # the widening astype is lossless there)
                    raise ValueError(
                        f"feed {name!r} declares wire codec {wire!r} but "
                        f"arrived as an already-uploaded {val.dtype} "
                        "array — encode on the host (data/codec.py, or "
                        "feed numpy and the executor encodes for you)")
            if isinstance(val, jax.Array):
                # already on device (double-buffer prefetch, reader/prefetch
                # .py) — never round-trip through host numpy
                want = (np_dtype(device_dtype(var.dtype))
                        if var is not None else None)
                out[name] = (val if want is None
                             or val.dtype == jnp.dtype(want)
                             else val.astype(want))
                continue
            arr = np.asarray(val)  # host-sync: ok — host feed conversion
            if var is not None:
                want = np_dtype(device_dtype(var.dtype))
                if arr.dtype != want:
                    arr = arr.astype(want)
            out[name] = jnp.asarray(arr)
        return out

    def _state_for(self, program: Program, scope: Scope) -> Dict[str, object]:
        """Persistable vars the program reads that already exist in the scope."""
        state = {}
        block = program.global_block
        # scan every block: control-flow sub-blocks (dynamic_rnn etc.) may be
        # the only readers of a parameter (≙ parent-scope lookup, scope.h:62)
        read = {n for b in program.blocks for op in b.ops
                for n in op.input_names()}
        for name in sorted(read):
            try:
                var = block.var(name)
            except KeyError:
                continue
            if var.persistable and scope.has_var(name):
                v = scope.find_var(name)
                if v is not None:
                    state[name] = v
        return state

    # -- main entry ---------------------------------------------------------
    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  build, key_extra, per_step_feed_prep=False, lazy=False,
                  guard=False, guard_steps=None, n_steps=1):
        """Shared body of run/run_loop: prep feeds/state, hit the jit cache
        (≙ the reference's program cache, executor.py:165), execute, write
        new state back to the scope.

        lazy=True returns LazyFetch handles instead of materialized
        arrays: the call returns as soon as XLA has ENQUEUED the step, so
        the caller can prep + dispatch step N+1 while N executes; a
        handle blocks only when read (async_fetch.py).

        guard=True (resilience/guard.py): the step-health scalar is
        appended as the LAST fetch, the per-dispatch fault code rides the
        reserved feed, and the compiled state output is the guarded
        select. Exactly ONE numeric instrumentation applies per compile:
        the guard wins over FLAGS.check_nan_inf (checkify), and the
        cache key records which (plus the traced-in gnorm ceiling)."""
        # an open span, as every phase of the step: the stall sentinel
        # sees a feed preparation that hangs (obs/trace.py)
        with self._timings.span("host_prep"):
            if program is None:
                program = default_main_program()
            feed = feed or {}
            fetch_list = fetch_list or []
            scope = scope or global_scope()

            from ..flags import FLAGS
            fetch_names = [self._fetch_name(f) for f in fetch_list]
            feed_arrays = self._prep_feed(program, feed,
                                          per_step=per_step_feed_prep)
            # conv-epilogue fusion pre-pass (analysis/fuse.py): rewrite
            # conv2d→batch_norm→relu/add chains into fused_conv2d on a
            # CLONE before the jit cache fingerprints the program, so
            # fused and unfused compiles key separately and PT_FUSE=0
            # returns the caller's object bit-for-bit. Memoized per
            # (fingerprint, fetch set) — steady-state cost is one dict
            # hit.
            from ..analysis import fuse as conv_fuse
            program = conv_fuse.maybe_fuse(program, protect=fetch_names)
            if guard:
                from ..resilience import guard as guard_mod
                guard_mod.assert_instrumented(program)
                fetch_names = fetch_names + [guard_mod.HEALTH_VAR]
                feed_arrays[guard_mod.FAULT_FEED] = guard_mod.fault_feed(
                    guard_steps)
                if FLAGS.check_nan_inf:
                    guard_mod.warn_checkify_conflict()
                numeric_mode = ("guard", guard_mod.max_gnorm())
            elif FLAGS.check_nan_inf:
                numeric_mode = ("checkify",)
            else:
                numeric_mode = ()
            state = self._state_for(program, scope)

            feed_sig = tuple(sorted((k, v.shape, str(v.dtype))
                                    for k, v in feed_arrays.items()))
            state_sig = tuple(sorted(
                (k, jnp.shape(v), str(jnp.result_type(v)))
                for k, v in state.items()))
            fingerprint = program.fingerprint()
            key = (fingerprint, key_extra, feed_sig,
                   tuple(fetch_names), state_sig, numeric_mode)
        compiled = self._cache.get(key)
        was_cached = compiled is not None
        if compiled is None:
            # static verification pre-pass (analysis/verifier.py): once per
            # compile, never per step — the same amortization as the jit
            # cache itself. Errors abort before tracing; warnings are
            # available via verify_program directly / the CLI.
            from ..analysis import verify_enabled, verify_program
            if verify_enabled():
                verify_program(program, feeds=list(feed_arrays),
                               fetches=fetch_names).raise_if_errors()
            # per_step_feeds arrays carry a leading [n_steps] axis: the
            # batch lives at dim 1 there (dim 0 otherwise)
            bdim = 1 if per_step_feed_prep else 0
            bh = _autotune_batch_hint(program, feed_arrays, bdim)
            # memory-budget gate (analysis/memory.py): under
            # PT_MEM_BUDGET_GB the static peak-HBM estimate is checked
            # BEFORE tracing — a breach raises the typed
            # MemoryBudgetError with the per-category breakdown instead
            # of compiling for minutes and dying RESOURCE_EXHAUSTED.
            # Compile-miss only, pure host IR walk: a passing budget adds
            # zero device syncs to the hot path.
            from ..analysis.memory import enforce_budget
            enforce_budget(program, batch=bh)
            # drift monitor (obs/drift.py): record the roofline
            # predict_step for this program at the SAME amortization
            # point as the verifier/budget gates — compile-miss only, a
            # pure host IR walk; measured steps fold into its EWMA below
            # so pt_model_drift_ratio tracks prediction honesty live.
            # Fetch-less runs (startup programs) carry no step to drift.
            if fetch_names:
                from ..obs import drift as obs_drift
                obs_drift.observe_prediction(program, batch=bh,
                                             timer=self._timings)
            # grouped-conv autotune pre-pass (utils/gconv_autotune.py):
            # the formulation choice inside the trace is cache-lookup
            # only, so any un-tuned shape must be measured BEFORE tracing
            from ..utils import gconv_autotune
            gconv_autotune.tune_program(program, bh)
            # fused-conv epilogue autotune (kernels/fused_conv.py): same
            # contract — the Pallas-vs-XLA epilogue choice inside the
            # trace is cache-lookup only, so measure un-tuned shapes here
            from ..kernels import fused_conv
            fused_conv.tune_program(program, bh)
            raw, state_out, donate = build(program, list(feed_arrays),
                                           fetch_names, sorted(state), bh)
            if FLAGS.check_nan_inf and not guard:
                # ≙ FLAGS_check_nan_inf (operator.cc:590): every float
                # primitive of the compiled step is instrumented; a nan/inf
                # raises host-side naming the generating primitive. The
                # checkified step is what gets jitted (one compiled
                # artifact, no per-call transform), and donation is OFF so
                # a throw cannot strand the scope on deleted buffers.
                from jax.experimental import checkify

                checked = jax.jit(checkify.checkify(
                    raw, errors=checkify.float_checks))

                def fn(state, feed, rng, _checked=checked):
                    err, out = _checked(state, feed, rng)
                    err.throw()
                    return out
            else:
                fn = jax.jit(raw, donate_argnums=donate)
            compiled = _Compiled(fn, sorted(state), state_out, fetch_names)
            self._cache[key] = compiled

        seed = program.random_seed if program.random_seed is not None else 0
        self._run_counter += 1
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), self._run_counter)

        # measured-step recorder (obs/drift.py): settle-to-settle gaps
        # over the steps between fold into the program's EWMA — the
        # steady-state per-step time, immune to how late a lazy handle
        # materializes. Cached runs only; the compile miss above reset
        # the baseline so compile seconds never fold in.
        settle = None
        if was_cached and fetch_names:
            from ..obs import drift as obs_drift
            settle = obs_drift.step_recorder(fingerprint, n_steps)

        # jit compiles on FIRST call: a cold dispatch is charged to
        # compile_s, never to the per-step dispatch phase
        with self._dispatching(was_cached):
            fetches, new_state = compiled.fn(state, feed_arrays, rng)
        # device-resident write-back: new_state values are jax.Arrays
        # (possibly still executing) — the scope never forces them to host
        for name, val in new_state.items():
            scope.set_var(name, val)

        if lazy:
            # fetch-name provenance rides every handle: a deferred device
            # error (or a watchdog dump) names WHAT was in flight; the
            # Trainer annotates epoch/step on top. With tracing armed
            # the active span's context (the trainer step span carries
            # epoch=/step=) is captured here instead — the span IS the
            # provenance plumbing then (resilience/watchdog.py dumps it).
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

    def run(self, program: Optional[Program] = None, feed: Optional[dict] = None,
            fetch_list: Optional[Sequence] = None, scope: Optional[Scope] = None,
            return_numpy: bool = True, donate_state: bool = True,
            lazy: bool = False, guard: bool = False):
        """lazy=True: return LazyFetch handles (async_fetch.py) — the call
        returns once the step is enqueued and a handle blocks only when
        read, so back-to-back run() calls overlap step N+1's host prep +
        dispatch with step N's device execution.

        guard=True: guarded update + step-health flag appended as the
        LAST fetch (resilience/guard.py; the program must carry the
        `step_health` op — optimizer.minimize appends it under
        PT_GUARD, or guard.instrument(program) on demand)."""
        def build(program, feed_names, fetch_names, state_names, _batch):
            step, state_out = lowering.build_step_fn(
                program, feed_names, fetch_names, state_names, guard=guard)
            return step, state_out, (0,) if donate_state else ()

        return self._run_impl(program, feed, fetch_list, scope, return_numpy,
                              build, key_extra=("step", donate_state),
                              lazy=lazy, guard=guard)

    def run_loop(self, program: Optional[Program] = None,
                 feed: Optional[dict] = None,
                 fetch_list: Optional[Sequence] = None, n_steps: int = 1,
                 scope: Optional[Scope] = None, per_step_feeds: bool = False,
                 return_numpy: bool = True,
                 unroll: Optional[int] = None,
                 lazy: bool = False, guard: bool = False):
        """Run `n_steps` training steps in ONE device dispatch (lax.scan).

        The reference pays host dispatch per step (executor.cc:322 interprets
        ops every Run); on TPU — especially through a high-latency control
        plane — the idiomatic fix is a device-side loop so dispatch cost is
        paid once per n_steps. ≙ the intent of scope reuse in
        scope_buffered_ssa_graph_executor.cc, realized as lax.scan.

        feed: with per_step_feeds=False the same feed dict is reused every
        step (fake-data benching, ≙ fluid_benchmark.py --use_fake_data);
        with True every feed array carries a leading [n_steps] axis and step
        i consumes slice i (one upload for the whole window).

        unroll: how many steps the scan's body holds; no semantic change.
        Not given, the program's own size decides it on each compile miss
        (analysis/memory.py `loop_body_steps`): two steps where the static
        estimate puts state + twice a step's temporaries under the
        device's memory, else one, and one where the backend gives no
        limit (the CPU). Measured on one v5e (tools/loop_unroll_sweep.py;
        PERF.md section 6, PR 50): a scan iteration costs tens of
        microseconds, not the 2 ms an early remote control plane showed: a
        second step in the body saves 23 us a step of 0.38 ms at the
        2-layer transformer_lm and 2 us of 9 at an MLP; at the 1.3B train
        cell (14.5 GiB of 15.75 with two steps live) the compiler made
        room by recomputing the head's logits product every step, 265.9
        ms a step against 251.5 with one. The body built is left in the
        trace ring as `program/loop_plan`.

        Returns the fetches, each stacked to [n_steps, ...].
        """
        def build(program, feed_names, fetch_names, state_names, batch):
            from ..analysis.memory import loop_body_steps
            loop, state_out = lowering.build_loop_fn(
                program, feed_names, fetch_names, state_names,
                n_steps=n_steps, per_step_feeds=per_step_feeds,
                unroll=(loop_body_steps(program, batch) if unroll is None
                        else unroll), guard=guard)
            return loop, state_out, (0,)

        # per-step feeds get a PER-STEP fault code ([n_steps] int32: the
        # chaos plan addresses individual steps inside a window); a
        # shared-feed loop draws one code for the whole window
        return self._run_impl(
            program, feed, fetch_list, scope, return_numpy, build,
            key_extra=("loop", n_steps, per_step_feeds, unroll),
            per_step_feed_prep=per_step_feeds, lazy=lazy, guard=guard,
            guard_steps=n_steps if per_step_feeds else None,
            n_steps=n_steps)

    def close(self):
        self._cache.clear()
