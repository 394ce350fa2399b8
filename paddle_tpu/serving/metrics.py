"""Serving metrics: per-model QPS, batch-fill ratio, queue depth, and
phase-split latency percentiles.

The request phases mirror the training hot path's PhaseTimer
attribution (core/async_fetch.py) translated to the serving request
lifecycle:

    queue    submit -> the dispatcher picks the request's batch
    pad      gathering + zero-padding the batch into its bucket shape
    device   the compiled bucket executable, until its outputs are ready
    fetch    the outputs to host numpy
    scatter  splitting per-request rows back out of the batch outputs

pad/device/fetch/scatter are per-BATCH costs; every request in the batch is
charged the same share (the phases answer "where does a request's wall
time go", not "what does a request marginally cost"). Percentiles come
from a bounded ring of recent samples (default 2048) — a serving process
must not grow memory with request count, and "recent p99" is the number
an operator actually wants.

Snapshots are plain dicts (json-able) so tests assert on them and the
benchmark reads its counters from them at a window's two ends.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, Optional

import numpy as np

from ..core.async_fetch import PhaseTimer
from ..obs.metrics import REGISTRY, percentiles
from ..obs.metrics import render_prometheus  # noqa: F401 — re-export:
# the ONE exposition renderer now lives on the unified metrics plane
# (obs/metrics.py); existing importers keep working unchanged.

__all__ = ["ServingPhaseTimer", "DecodePhaseTimer", "ModelMetrics",
           "DecodeMetrics", "ServingMetrics", "PHASES", "DECODE_PHASES",
           "render_prometheus"]

PHASES = ("queue", "pad", "device", "fetch", "scatter")

#: the decode engine's phases (docs/observability.md has the table:
#: where each starts and ends, and the benchmark metric that reads it)
DECODE_PHASES = ("step_prep", "step_dispatch", "step_wait", "step_fetch",
                 "step_emit", "prefill_pad", "prefill_device", "seed_kv",
                 "prefill_fetch", "admit", "sched_idle", "device_idle")

#: per-phase ring size for percentile estimation
RESERVOIR = 2048


class ServingPhaseTimer(PhaseTimer):
    """PhaseTimer (same span()/add() surface as the executor's) over the
    serving request phases. snapshot() is re-derived here: the training
    timer's host_overhead_pct reads training-phase keys that do not
    exist on this axis. Emitted trace spans land under the "serve"
    category (one timing source, three views — see PhaseTimer)."""

    PHASES = PHASES
    WAITS_FOR_WORK = ("queue",)
    trace_cat = "serve"

    def snapshot(self, reset: bool = False) -> dict:
        with self._lock:
            out = {f"{p}_s": round(self._s[p], 6) for p in self.PHASES}
            out["batches"] = self._runs
            if reset:
                self._s = {p: 0.0 for p in self.PHASES}
                self._runs = 0
        return out


class DecodePhaseTimer(PhaseTimer):
    """The decode engine's phase clocks, owned by `DecodeMetrics`: the
    scheduler times its own phases on it (an admission's one wait,
    `prefill_fetch`, among them), `DecodeModel` the step's, the
    prefill's dispatch and the seeding's. `admit` contains the prefill
    and seeding phases; every other phase the host is in is disjoint
    from the rest. `device_idle` is not the host's phase but the
    device's (`DecodeModel._launched`: from the return of a wait on the
    newest dispatch to the return of the next call that dispatches), so
    it overlaps the host's phases by design, all but `step_wait`, and
    is left out of every sum of them."""

    PHASES = DECODE_PHASES
    WAITS_FOR_WORK = ("sched_idle",)
    WAITS_ON_DEVICE = ("step_wait", "prefill_fetch")
    trace_cat = "decode"

    def snapshot(self) -> dict:
        with self._lock:
            return {f"{p}_s": round(self._s[p], 6) for p in self.PHASES}


#: p50/p95/p99 by nearest-rank, in ms — shared with the train-plane
#: family (obs/metrics.py owns the one implementation now)
_percentiles = percentiles


class ModelMetrics:
    """One model's counters + phase timer + latency reservoirs.
    Thread-safe: submitters, the dispatcher, and HTTP scrapes all touch
    it concurrently."""

    def __init__(self, name: str,
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self._clock = clock
        self._lock = threading.Lock()
        self.timer = ServingPhaseTimer()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._t0 = self._clock()
            self.received = 0
            self.completed = 0
            self.failed = 0
            self.shed_overload = 0
            self.shed_deadline = 0
            self.batches = 0
            self.batch_slots_used = 0
            self.batch_slots_total = 0
            self.queue_depth = 0
            self.reloads = 0
            self._lat: Dict[str, deque] = {
                p: deque(maxlen=RESERVOIR) for p in PHASES}
            self._lat["total"] = deque(maxlen=RESERVOIR)
        self.timer.reset()

    # -- recording ----------------------------------------------------------
    def on_received(self, queue_depth: int) -> None:
        with self._lock:
            self.received += 1
            self.queue_depth = queue_depth

    def on_shed(self, kind: str) -> None:
        with self._lock:
            if kind == "overload":
                self.shed_overload += 1
            else:
                self.shed_deadline += 1

    def on_batch(self, used: int, capacity: int) -> None:
        with self._lock:
            self.batches += 1
            self.batch_slots_used += used
            self.batch_slots_total += capacity

    def on_done(self, ok: bool, queue_depth: int,
                phase_s: Optional[Dict[str, float]] = None,
                total_s: Optional[float] = None) -> None:
        with self._lock:
            if ok:
                self.completed += 1
            else:
                self.failed += 1
            self.queue_depth = queue_depth
            if phase_s:
                for p, s in phase_s.items():
                    if p in self._lat:
                        self._lat[p].append(s)
            if total_s is not None:
                self._lat["total"].append(total_s)

    def on_reload(self) -> None:
        with self._lock:
            self.reloads += 1

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._lock:
            elapsed = max(self._clock() - self._t0, 1e-9)
            fill = (self.batch_slots_used / self.batch_slots_total
                    if self.batch_slots_total else None)
            out = {
                "model": self.name,
                "received": self.received,
                "completed": self.completed,
                "failed": self.failed,
                "shed_overload": self.shed_overload,
                "shed_deadline": self.shed_deadline,
                "queue_depth": self.queue_depth,
                "reloads": self.reloads,
                "batches": self.batches,
                "batch_fill_ratio": round(fill, 4) if fill is not None
                else None,
                "qps": round(self.completed / elapsed, 2),
                "window_s": round(elapsed, 3),
                "latency": {k: _percentiles(list(v))
                            for k, v in self._lat.items()},
            }
        out["phases"] = self.timer.snapshot()
        return out


class DecodeMetrics:
    """One decode engine's counters: sequences, tokens, continuous-batch
    slot occupancy, and KV-pool pressure. The decode axis is different
    enough from the request/batch axis that it gets its own type —
    tokens/s and slot occupancy are THE numbers for a generation engine,
    where QPS and batch fill are the numbers for a one-shot one."""

    def __init__(self, name: str,
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self._clock = clock
        self._lock = threading.Lock()
        self.timer = DecodePhaseTimer()
        #: a model with experts: `DecodeModel.moe_counters`, which gives
        #: (host totals, the device's counters since) without waiting
        #: for anything. DecodeEngine sets it; a dense model leaves None
        self.moe_probe: Optional[Callable[[], tuple]] = None
        #: bytes the compiled decode step updates in place (the pools'
        #: bytes while their donation holds, 0 if it was answered with
        #: copies, None before the step is compiled): a property of the
        #: loaded model, so `reset` leaves it. DecodeEngine points it at
        #: `DecodeModel.step_aliased_bytes`
        self.step_aliased_probe: Callable[[], Optional[int]] = lambda: None
        #: bytes one cached token takes over all layers, as the loaded
        #: bundle's pools store it (`DecodeModel.cache`); None for a
        #: model that does not say
        self.cache_bytes_per_token: Optional[int] = None
        #: rows a query keeps, for a model with a sparse-attention
        #: indexer (DecodeEngine sets it); 0: a step reads every live
        #: row and the `sparse_*` counters are not in the snapshot
        self.index_topk = 0
        #: a model whose attention reads blocks chosen on pooled keys
        self.block_sparse = False
        #: rows a window layer reads back, for a model with window
        #: layers (DecodeEngine sets it); 0: the `window_*` counters
        #: are not in the snapshot
        self.window = 0
        #: bytes the state layers' arrays hold over all slots, for a
        #: model with state layers (DecodeEngine sets it); 0: the
        #: `state_*` counters are not in the snapshot
        self.state_bytes = 0
        #: layers that read a pool they do not own (DecodeEngine sets
        #: it); 0: the `pool_rows_*` counters are not in the snapshot
        self.pool_readers = 0
        #: bytes the bundle's weights hold on the device and the dtype
        #: its matrices are served in (DecodeEngine sets them); None for
        #: a model that does not say
        self.weight_bytes: Optional[int] = None
        self.weight_dtype: Optional[str] = None
        self._moe_ref: Optional[tuple] = None
        self._moe_zero = np.int64(0)    # broadcasts over the counters
        self.reset()

    def reset(self) -> None:
        self.timer.reset()   # as ModelMetrics.reset resets its timer
        if self.moe_probe is not None:
            self._moe_zero = _moe_totals(self.moe_probe())
        with self._lock:
            self._moe_ref = None
            self._t0 = self._clock()
            self.received = 0
            self.completed = 0
            self.failed = 0
            self.shed_overload = 0
            self.shed_deadline = 0
            self.admitted = 0
            self.queue_wait_s = 0.0
            self.evictions = 0
            self.resumes = 0
            self.prefills = 0
            self.prefill_tokens = 0
            self.prefill_host_bytes = 0
            self.step_host_bytes = 0
            self.logits_fetches = 0
            self.steps = 0
            # dispatch ahead (decode/scheduler.py): steps dispatched
            # while the step before was still uncollected, tokens
            # computed for a sequence that had already ended, and the
            # collects with nothing queued behind them, by reason
            self.steps_ahead = 0
            self.overrun_tokens = 0
            self.drains: Dict[str, int] = {}
            self.paged_live_pages = 0
            self.paged_walked_pages = 0
            self.sparse_live_rows = 0
            self.sparse_selected_rows = 0
            self.sparse_page_walk_slots = 0
            self.sparse_walked_pages = 0
            self.block_chosen_blocks = 0
            self.block_pooled_rows = 0
            self.block_dense_slot_steps = 0
            self.window_rows_read = 0
            self.window_rows_live = 0
            self.window_blocks_released = 0
            self.window_pool_blocks_in_use = 0
            self.state_slot_steps = 0
            self.state_seeds = 0
            self.state_seed_bytes = 0
            self.pool_rows_read_writer = 0
            self.pool_rows_read_readers = 0
            self.tokens_out = 0
            self.slots_used_sum = 0
            self.slots_capacity_sum = 0
            self.prefill_s = 0.0
            self.decode_s = 0.0
            self.active = 0
            self.waiting = 0
            self.kv_blocks_in_use = 0
            self.kv_blocks_capacity = 0
            self.kv_high_water = 0
            # KV economics (decode/prefix.py + decode/spec.py)
            self.kv_shared_hits = 0
            self.kv_shared_tokens = 0
            self.kv_cow_copies = 0
            self.kv_blocks_shared = 0
            self.kv_blocks_indexed = 0
            self.spec_steps = 0
            self.spec_drafted = 0
            self.spec_accepted = 0
            self.spec_fallbacks = 0

    # -- recording ----------------------------------------------------------
    def on_received(self) -> None:
        with self._lock:
            self.received += 1

    def on_finished(self, ok: bool) -> None:
        with self._lock:
            if ok:
                self.completed += 1
            else:
                self.failed += 1

    def on_shed(self, kind: str) -> None:
        with self._lock:
            if kind == "overload":
                self.shed_overload += 1
            else:
                self.shed_deadline += 1

    def on_admitted(self, queue_wait_s: float) -> None:
        """A request's FIRST admission (a resume after eviction is not
        one): submit -> the scheduler starts its prefill."""
        with self._lock:
            self.admitted += 1
            self.queue_wait_s += queue_wait_s

    def on_evicted(self) -> None:
        with self._lock:
            self.evictions += 1

    def on_resumed(self) -> None:
        with self._lock:
            self.resumes += 1

    def on_prefill(self, tokens: int, seconds: float) -> None:
        with self._lock:
            self.prefills += 1
            self.prefill_tokens += tokens
            self.prefill_s += seconds

    def on_prefill_host_bytes(self, nbytes: int) -> None:
        """Bytes an admission moved between host and device memory, in
        either direction, reported from where they moved: the padded
        ids and the block-id vector (each with its length scalar) going
        in, the last position's logits row coming out."""
        with self._lock:
            self.prefill_host_bytes += int(nbytes)

    def on_step_host_bytes(self, nbytes: int, logits: bool) -> None:
        """Bytes a decode step's results moved to the host, reported from
        where they moved: the chosen ids, 4 a slot, every step; and the
        step's logits each time somebody asked its result for them
        (`logits`), which serving never does."""
        with self._lock:
            self.step_host_bytes += int(nbytes)
            if logits:
                self.logits_fetches += 1

    def on_step(self, used: int, capacity: int, seconds: float,
                tokens: int, moe_ref: Optional[tuple] = None,
                ahead: bool = False, overrun: int = 0) -> None:
        """One step emitted. `seconds`: what it added to the loop's
        time, emission to emission with the admissions between taken
        out (`decode_s + prefill_s` is the loop's busy time). `moe_ref`:
        `moe_probe()` as read right after THIS step's dispatch (a later
        step may have been dispatched since); `ahead`: it was dispatched
        while the step before it was uncollected (over `decode_steps`:
        the share of steps whose host work ran beside the device's);
        `overrun`: its slots whose sequence had already ended when its
        tokens were read."""
        with self._lock:
            self.steps += 1
            self.slots_used_sum += used
            self.slots_capacity_sum += capacity
            self.decode_s += seconds
            self.tokens_out += tokens
            self.steps_ahead += ahead
            self.overrun_tokens += overrun
            if self.moe_probe is not None:
                # a reference to the device's counters as of this step,
                # taken with the step's other counts so a snapshot's
                # `moe_*` and `slots_used_sum` describe the same steps;
                # nothing is fetched until someone asks
                self._moe_ref = (self.moe_probe() if moe_ref is None
                                 else moe_ref)

    def on_drain(self, reason: str) -> None:
        """A step collected with nothing queued behind it
        (`decode.scheduler.DRAIN_REASONS`): the next dispatch finds the
        device idle."""
        with self._lock:
            self.drains[reason] = self.drains.get(reason, 0) + 1

    def on_paged_pages(self, live: int, walked: int) -> None:
        """A step's work for the paged kernel, a layer. `live`: sum over
        the step's slots of ceil(context / block_size), the pages it has
        to read; `walked`: the pages its compute blocks cover (P x the
        blocks it walks, `describe()["paged_kernel"]`). Walked over live
        is the share of the kernel's arithmetic spent on masked pages;
        no page outside `live` is copied."""
        with self._lock:
            self.paged_live_pages += live
            self.paged_walked_pages += walked

    def on_sparse_rows(self, live: int, selected: int,
                       page_walk_slots: int = 0,
                       walked_pages: int = 0) -> None:
        """A step of a model with a sparse-attention indexer, a layer:
        the cache rows live in the step's slots (what its indexer
        scores) and the rows of them its attention read, min(length,
        index_topk) a slot; then how the kernel reached them: the slots
        whose live pages it copied whole with the selection as a mask
        (`kernels.paged_attention.sparse_walks_pages`; over the steps'
        live slots, `slots_used_sum`: the share of slot-steps on the
        page walk) and the pages that was; every other live slot's
        selected rows were copied one by one."""
        with self._lock:
            self.sparse_live_rows += live
            self.sparse_selected_rows += selected
            self.sparse_page_walk_slots += page_walk_slots
            self.sparse_walked_pages += walked_pages

    def on_block_choices(self, blocks: int, pooled: int,
                         dense_slots: int) -> None:
        """A step of a model whose attention reads whole blocks chosen
        on pooled keys, a layer and K/V head: the blocks its live slots
        read, the pooled keys their choice was scored on, and the slots
        that were under `dense_len` and read every row they hold (the
        rows read and the rows live go through `on_sparse_rows`)."""
        with self._lock:
            self.block_chosen_blocks += blocks
            self.block_pooled_rows += pooled
            self.block_dense_slot_steps += dense_slots

    def on_window_rows(self, read: int, live: int) -> None:
        """A step of a model with window layers, summed over its slots
        and its window layers: the rows their attention read, min(length,
        window) a slot and layer, and the rows the contexts hold, which
        a full layer in their place would have read."""
        with self._lock:
            self.window_rows_read += read
            self.window_rows_live += live

    def on_state_rows(self, slot_steps: int, seeded_bytes: int) -> None:
        """A model with state layers. A step: its live slots times the
        state layers, each of which moved its slot's state a row on
        (`seeded_bytes` 0). An admission: the bytes of state it wrote
        into the sequence's slot, over all state layers (`slot_steps`
        0)."""
        with self._lock:
            self.state_slot_steps += slot_steps
            if seeded_bytes:
                self.state_seeds += 1
                self.state_seed_bytes += seeded_bytes

    def on_pool_rows(self, writer: int, readers: int) -> None:
        """A step of a model some of whose layers read a pool they do
        not own: the rows of the full layers' pools that the layers that
        write them read (every live row of every slot, a full layer),
        and the rows the other readers read of the same pools (the same
        rows again, a reader)."""
        with self._lock:
            self.pool_rows_read_writer += writer
            self.pool_rows_read_readers += readers

    def on_window_blocks(self, released: int, in_use: int) -> None:
        """The window layers' pool after a step's growth: blocks that
        fell wholly behind their sequence's window and went back to the
        free list (a sequence that ends frees its blocks like any other
        and is not counted), and the blocks now held."""
        with self._lock:
            self.window_blocks_released += released
            self.window_pool_blocks_in_use = in_use

    def on_prefix_hit(self, tokens: int, blocks: int) -> None:
        with self._lock:
            self.kv_shared_hits += 1
            self.kv_shared_tokens += tokens

    def on_cow(self) -> None:
        with self._lock:
            self.kv_cow_copies += 1

    def on_spec(self, drafted: int, accepted: int) -> None:
        with self._lock:
            self.spec_steps += 1
            self.spec_drafted += drafted
            self.spec_accepted += accepted

    def on_spec_fallback(self) -> None:
        with self._lock:
            self.spec_fallbacks += 1

    def set_gauges(self, *, active: int, waiting: int, blocks_in_use: int,
                   blocks_capacity: int, high_water: int,
                   blocks_shared: int = 0,
                   blocks_indexed: int = 0) -> None:
        with self._lock:
            self.active = active
            self.waiting = waiting
            self.kv_blocks_in_use = blocks_in_use
            self.kv_blocks_capacity = blocks_capacity
            self.kv_high_water = high_water
            self.kv_blocks_shared = blocks_shared
            self.kv_blocks_indexed = blocks_indexed

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> dict:
        phases = self.timer.snapshot()
        overruns = self.timer.overrun_snapshot()
        with self._lock:
            elapsed = max(self._clock() - self._t0, 1e-9)
            occ = (self.slots_used_sum / self.slots_capacity_sum
                   if self.slots_capacity_sum else None)
            moe_ref = self._moe_ref
            out = {
                "model": self.name,
                "received": self.received,
                "completed": self.completed,
                "failed": self.failed,
                "shed_overload": self.shed_overload,
                "shed_deadline": self.shed_deadline,
                "admitted": self.admitted,
                "queue_wait_s": round(self.queue_wait_s, 6),
                "evictions": self.evictions,
                "resumes": self.resumes,
                "prefills": self.prefills,
                "prefill_tokens": self.prefill_tokens,
                "prefill_host_bytes": self.prefill_host_bytes,
                "step_host_bytes": self.step_host_bytes,
                "logits_fetches": self.logits_fetches,
                "step_aliased_bytes": self.step_aliased_probe(),
                "cache_bytes_per_token": self.cache_bytes_per_token,
                "weight_bytes": self.weight_bytes,
                "weight_dtype": self.weight_dtype,
                "decode_steps": self.steps,
                "steps_ahead": self.steps_ahead,
                "overrun_tokens": self.overrun_tokens,
                "drains": dict(self.drains),
                "paged_live_pages": self.paged_live_pages,
                "paged_walked_pages": self.paged_walked_pages,
                "tokens_out": self.tokens_out,
                "tokens_per_sec": round(self.tokens_out / elapsed, 2),
                "slot_occupancy": round(occ, 4) if occ is not None
                else None,
                "slots_used_sum": self.slots_used_sum,
                "slots_capacity_sum": self.slots_capacity_sum,
                "active": self.active,
                "waiting": self.waiting,
                "kv_blocks_in_use": self.kv_blocks_in_use,
                "kv_blocks_capacity": self.kv_blocks_capacity,
                "kv_high_water": self.kv_high_water,
                "kv_shared_hits": self.kv_shared_hits,
                "kv_shared_tokens": self.kv_shared_tokens,
                "kv_cow_copies": self.kv_cow_copies,
                "kv_blocks_shared": self.kv_blocks_shared,
                "kv_blocks_indexed": self.kv_blocks_indexed,
                "spec_steps": self.spec_steps,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "spec_fallbacks": self.spec_fallbacks,
                "spec_acceptance_rate": (
                    round(self.spec_accepted / self.spec_drafted, 4)
                    if self.spec_drafted else None),
                "prefill_s": round(self.prefill_s, 6),
                "decode_s": round(self.decode_s, 6),
                "window_s": round(elapsed, 3),
                "phases": phases,
                # spans the stall sentinel found open far beyond their
                # phase's usual length, and the newest one's record
                **overruns,
            }
        if self.block_sparse:
            out["sparse_live_rows"] = self.sparse_live_rows
            out["sparse_selected_rows"] = self.sparse_selected_rows
            out["block_chosen_blocks"] = self.block_chosen_blocks
            out["block_pooled_rows"] = self.block_pooled_rows
            out["block_dense_slot_steps"] = self.block_dense_slot_steps
        if self.index_topk:
            out["sparse_live_rows"] = self.sparse_live_rows
            out["sparse_selected_rows"] = self.sparse_selected_rows
            out["sparse_page_walk_slots"] = self.sparse_page_walk_slots
            out["sparse_walked_pages"] = self.sparse_walked_pages
        if self.window:
            out["window_rows_read"] = self.window_rows_read
            out["window_rows_live"] = self.window_rows_live
            out["window_blocks_released"] = self.window_blocks_released
            out["window_pool_blocks_in_use"] = \
                self.window_pool_blocks_in_use
        if self.state_bytes:
            out["state_slot_steps"] = self.state_slot_steps
            out["state_seeds"] = self.state_seeds
            out["state_seed_bytes"] = self.state_seed_bytes
            out["state_bytes"] = self.state_bytes
        if self.pool_readers:
            out["pool_rows_read_writer"] = self.pool_rows_read_writer
            out["pool_rows_read_readers"] = self.pool_rows_read_readers
        if self.moe_probe is not None:
            # the one place the device's counters come to the host
            done = (_moe_totals(moe_ref) - self._moe_zero
                    if moe_ref is not None
                    else np.zeros_like(self.moe_probe()[0]))
            for key, value in zip(MOE_COUNTERS + MOE_SHARE_COUNTERS, done):
                out[key] = int(value)
        return out


#: the routing counters of a model with experts, in the order the decode
#: step's `moe_stats` holds them (io.export_decode_model)
MOE_COUNTERS = ("moe_assignments", "moe_experts_touched", "moe_layer_steps")
#: and behind them where the program holds a share of the experts: the
#: pairs that fell on the experts it holds
MOE_SHARE_COUNTERS = ("moe_held_pairs",)


def _moe_totals(ref: tuple) -> np.ndarray:
    base, device = ref
    return base + np.asarray(device, np.int64)


class ServingMetrics:
    """The engine-wide registry: one ModelMetrics per model NAME (metrics
    deliberately survive hot reloads — a reload is an event on the
    model's timeline, not a new timeline). Decode engines report through
    the same registry under their own axis (`decode(name)`), so ONE
    snapshot — and one Prometheus scrape — covers both serving planes.

    Multi-engine processes (the fleet tier, serving/fleet/): `replica`
    namespaces this engine's series — every model/decode snapshot
    carries a `replica` key the Prometheus renderer turns into a
    `replica="<id>"` label, so two replicas serving the SAME model name
    scrape as distinct series instead of duplicates (validate_exposition
    rejects the duplicate). The pre-fleet single-engine assumption —
    one engine per process, model name alone identifies a series — is
    exactly what this parameter retires."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 replica: Optional[str] = None):
        self._clock = clock
        self.replica = replica
        self._lock = threading.Lock()
        self._models: Dict[str, ModelMetrics] = {}
        self._decode: Dict[str, DecodeMetrics] = {}

    def model(self, name: str) -> ModelMetrics:
        with self._lock:
            m = self._models.get(name)
            if m is None:
                m = self._models[name] = ModelMetrics(name,
                                                      clock=self._clock)
            return m

    def decode(self, name: str) -> DecodeMetrics:
        with self._lock:
            m = self._decode.get(name)
            if m is None:
                m = self._decode[name] = DecodeMetrics(name,
                                                       clock=self._clock)
            return m

    def snapshot(self, merge_registry: bool = True) -> dict:
        with self._lock:
            models = list(self._models.values())
            decode = list(self._decode.values())
        out = {"models": {m.name: m.snapshot() for m in models}}
        if decode:
            out["decode"] = {m.name: m.snapshot() for m in decode}
        if self.replica is not None:
            for sec in ("models", "decode"):
                for snap in out.get(sec, {}).values():
                    snap["replica"] = self.replica
        # every other plane reports through the same snapshot (and so
        # the same Prometheus scrape) via the unified MetricsRegistry
        # (obs/metrics.py): live input pipelines (pt_data_*), the
        # training loop (pt_train_*), and the predicted-vs-measured
        # drift monitor (pt_model_*) all ride along — one scrape, one
        # observability plane. A fleet router merging N replica
        # snapshots passes merge_registry=False per replica and merges
        # the registry sections ONCE — the one-engine-per-process
        # assumption the fleet satellite fix retires.
        if merge_registry:
            for section, snaps in REGISTRY.snapshot().items():
                if snaps:
                    out.setdefault(section, snaps)
        return out


# The Prometheus text renderer lived here until the obs consolidation
# (obs/metrics.py render_prometheus is the ONE renderer for every
# family — pt_serve_*/pt_decode_*/pt_data_*/pt_train_*/pt_model_*);
# it is re-exported above so importers keep working.
