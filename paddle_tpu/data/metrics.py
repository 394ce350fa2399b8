"""Per-stage pipeline metrics: where does a delivered batch's wall time go?

The data plane's analogue of the executor's PhaseTimer (core/async_fetch)
and the serving plane's ModelMetrics: each pipeline stage records busy
seconds + item counts into one `PipelineMetrics`, and `snapshot()` turns
them into occupancy fractions over the measurement window — the number
that attributes residual input-boundness (BENCH r05: 245 img/s real-data
vs 2637 fake, with the gap unattributed until now).

Stages and their meaning:

    decode      seconds worker threads spent inside the decode fn,
                summed across workers. occupancy = busy / (window x
                workers): 1.0 means every worker decoded flat-out — add
                workers or move work on-device.
    encode      seconds spent host-encoding batches for the wire
                (data/codec.py int8/bf16 policies). The stage also feeds
                the wire accounting below: raw vs on-wire bytes and
                their ratio, exported as pt_data_wire_bytes /
                pt_data_codec_ratio.
    queue_wait  seconds the pipeline's CONSUMER blocked waiting for the
                next decoded batch. occupancy ~1.0 = input-bound (the
                device idles on data); ~0.0 = the pipeline outruns its
                consumer.
    upload      seconds the device_put stage spent staging batches
                (reader/prefetch.py's upload worker). High occupancy =
                host->device transfer bound (the thin-pipe reading).
    augment     seconds dispatching the device-side augmentation (the
                traced call only — execution overlaps the device step).

Snapshots are plain json-able dicts; a process-wide registry lets the
serving HTTP front end render every live pipeline as the `pt_data_*`
Prometheus family beside `pt_serve_*`/`pt_decode_*` (one scrape, one
observability plane — serving/metrics.py render_prometheus).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict

from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY

__all__ = ["PipelineMetrics", "STAGES", "register", "unregister",
           "registry_snapshots"]

#: the stage axis, in pipeline order
STAGES = ("decode", "encode", "queue_wait", "upload", "augment")


class _Stage:
    __slots__ = ("busy_s", "items")

    def __init__(self):
        self.busy_s = 0.0
        self.items = 0


class PipelineMetrics:
    """One pipeline's stage accounting. Thread-safe: decode workers, the
    upload worker, and the consumer all record concurrently; HTTP scrapes
    read while they do."""

    def __init__(self, name: str = "pipeline",
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self._clock = clock
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._t0 = self._clock()
            self._stages: Dict[str, _Stage] = {s: _Stage() for s in STAGES}
            self.batches = 0
            self.samples = 0
            self.workers = 1
            self.raw_bytes = 0
            self.wire_bytes = 0

    def set_workers(self, n: int) -> None:
        """Decode fan-out width — the denominator of decode occupancy."""
        with self._lock:
            self.workers = max(int(n), 1)

    def add(self, stage: str, seconds: float, items: int = 1,
            **attrs) -> None:
        with self._lock:
            st = self._stages[stage]
            st.busy_s += seconds
            st.items += items
        # one timing source, two views: the same interval lands on the
        # structured trace (obs/trace.py) when PT_TRACE is armed —
        # pipeline stages join the executor/trainer/serving timeline.
        # `attrs` (e.g. cursor=) ride the span only; the cumulative
        # stage accounting stays unchanged.
        if obs_trace.enabled():
            obs_trace.complete(stage, seconds, cat="data",
                               pipeline=self.name, items=items, **attrs)

    def span(self, stage: str, items: int = 1, **attrs):
        """Context manager: time a block into `stage`. Extra attrs (the
        batch cursor) ride the emitted trace span."""
        return _Span(self, stage, items, attrs)

    def on_delivered(self, samples: int = 0) -> None:
        """One batch handed to the consumer (the pipeline's output unit)."""
        with self._lock:
            self.batches += 1
            self.samples += int(samples)

    def add_wire(self, raw_bytes: int, wire_bytes: int) -> None:
        """One encoded batch: bytes it would have cost raw vs the bytes
        that actually cross the host->device pipe (the encode stage's
        wire accounting — codec_ratio = raw / wire)."""
        with self._lock:
            self.raw_bytes += int(raw_bytes)
            self.wire_bytes += int(wire_bytes)

    # -- reading ------------------------------------------------------------
    def snapshot(self, reset: bool = False) -> dict:
        with self._lock:
            window = max(self._clock() - self._t0, 1e-9)
            stages = {}
            for name, st in self._stages.items():
                denom = window * (self.workers if name == "decode" else 1)
                stages[name] = {
                    "busy_s": round(st.busy_s, 6),
                    "items": st.items,
                    "occupancy": round(min(st.busy_s / denom, 1.0), 4),
                }
            out = {
                "name": self.name,
                "window_s": round(window, 3),
                "batches": self.batches,
                "samples": self.samples,
                "workers": self.workers,
                "batches_per_sec": round(self.batches / window, 2),
                "samples_per_sec": round(self.samples / window, 1),
                "raw_bytes": self.raw_bytes,
                "wire_bytes": self.wire_bytes,
                "codec_ratio": (round(self.raw_bytes / self.wire_bytes, 3)
                                if self.wire_bytes else None),
                "stages": stages,
            }
            if reset:
                self._t0 = self._clock()
                self._stages = {s: _Stage() for s in STAGES}
                self.batches = 0
                self.samples = 0
                self.raw_bytes = 0
                self.wire_bytes = 0
        return out


class _Span:
    __slots__ = ("_m", "_stage", "_items", "_attrs", "_t0")

    def __init__(self, metrics: PipelineMetrics, stage: str, items: int,
                 attrs: dict = None):
        self._m = metrics
        self._stage = stage
        self._items = items
        self._attrs = attrs or {}

    def __enter__(self):
        self._t0 = self._m._clock()
        return self

    def __exit__(self, *exc):
        self._m.add(self._stage, self._m._clock() - self._t0, self._items,
                    **self._attrs)
        return False


# ---------------------------------------------------------------------------
# Process-wide registry: live pipelines register their metrics so ONE
# scrape of the serving HTTP front end covers the data plane too.
# Since the unified metrics plane (obs/metrics.py), these are thin
# wrappers over the shared MetricsRegistry's "data" section — same
# weakref semantics (an abandoned pipeline must not be pinned in memory,
# or keep reporting, just because it once registered), one registry for
# every plane.
# ---------------------------------------------------------------------------

def register(metrics: PipelineMetrics) -> None:
    """Expose a pipeline's metrics on the process-wide scrape. Re-using a
    name replaces the previous registrant (a rebuilt pipeline is the same
    timeline to an operator, like a reloaded serving model)."""
    REGISTRY.register("data", metrics.name, metrics)


def unregister(name: str) -> None:
    REGISTRY.unregister("data", name)


def registry_snapshots() -> Dict[str, dict]:
    live = REGISTRY.providers("data")
    return {name: m.snapshot() for name, m in sorted(live.items())}
