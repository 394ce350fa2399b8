"""Lazy fetch handles + per-phase step timing for the async hot path.

The reference pays a host round-trip per step by construction: Executor::Run
materializes every fetch into a LoDTensor the Python side reads
(executor.cc:230-294). Under the functional runtime the device work is
dispatched asynchronously by JAX — the ONLY thing that forces the host to
wait is converting a fetch to numpy. So the async hot path is not a new
scheduler; it is *not converting*: `Executor.run(..., lazy=True)` returns
`LazyFetch` handles and the host is immediately free to prep and dispatch
step N+1 while N executes (state donation is already in place, so the
param buffers alias forward). The handle blocks only when something
actually reads it — numpy coercion, float(), .numpy().

Per-phase timing (`PhaseTimer`) attributes wall time to:

  host_prep   feed conversion, scope scan, cache key     (host, per run)
  dispatch    the jitted call itself — returns when XLA   (host, per run)
              has *enqueued* the computation
  device      block_until_ready wait                      (device execute)
  fetch       device->host materialization (np.asarray)   (transfer+convert)

so an MFU gap is attributable by measurement: `host_overhead_pct` is the
share of accounted time the host spent NOT waiting on the device.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import jax
import numpy as np

from ..obs import trace as obs_trace

__all__ = ["LazyFetch", "PhaseTimer", "materialize"]


class PhaseTimer:
    """Per-phase wall-time accumulator (thread-safe: LazyFetch handles may
    be read from any thread, e.g. a metrics logger).

    ONE timing source feeding three views. Every `add()` (which both
    direct calls and the span() context manager funnel through) lands
    the same interval in the cumulative phase accounting AND as a phase
    record in the structured trace's ring (obs/trace.py `phase()`:
    always, one tuple; with PT_TRACE armed it carries ids and
    attributes like any span). A `span()` is, while open, also a
    `jax.profiler.TraceAnnotation` named `program/<trace_cat>/<phase>`:
    the same interval on the profiler's clock, where a device idle gap
    can be put down to it (a no-op when no profiler session is open).
    `trace_cat` names the plane (subclasses override: the serving timer
    emits under "serve", the decode engine's under "decode").

    What a `span()` has OPEN is visible from outside: `_open` holds
    `(phase, t0)` by thread, written in plain dict stores, and `_usual`
    a phase's running length. The stall sentinel (obs/trace.py) reads
    both from its own thread, and a span open far beyond its phase's
    usual length leaves a `stall` record that says what its thread was
    doing; `overruns` counts them and `last_overrun` is the newest.
    `WAITS_FOR_WORK` names the phases that are never judged (an idle
    scheduler is not stalled), `WAITS_ON_DEVICE` those whose record is
    marked `waits_on: "device"`."""

    PHASES = ("host_prep", "dispatch", "device", "fetch")
    WAITS_FOR_WORK: tuple = ()
    WAITS_ON_DEVICE: tuple = ("device",)
    trace_cat = "exec"

    def __init__(self):
        self._lock = threading.Lock()
        self._span_names = {p: f"program/{self.trace_cat}/{p}"
                            for p in self.PHASES}
        #: a phase's usual length: its newest peak among the lengths
        #: that were no overrun, sinking a sixteenth of the way to each
        #: shorter one (an admission's length goes with its prompt's,
        #: and the longest recent one is what the next is held to).
        #: Learned over the warm-up; `reset()` keeps it
        self._usual: Dict[str, float] = {}
        self._open: Dict[int, Optional[tuple]] = {}
        self._overrun: Dict[tuple, object] = {}   # the sentinel's flags
        self.last_overrun: Optional[dict] = None
        self.reset()
        obs_trace.watch(self)

    def reset(self):
        with self._lock:
            self._s: Dict[str, float] = {p: 0.0 for p in self.PHASES}
            self._runs = 0
            self.overruns = 0

    def add(self, phase: str, seconds: float,
            t_end: Optional[float] = None, open_span=None):
        with self._lock:
            self._s[phase] += seconds
            usual = self._usual.get(phase)
            if usual is None or (usual < seconds
                                 <= obs_trace.overrun_after(usual)):
                self._usual[phase] = seconds
            else:
                self._usual[phase] = usual + (seconds - usual) / 16
        obs_trace.phase(self.trace_cat, phase, seconds, t_end, open_span)

    def _on_overrun(self, record: dict):
        with self._lock:
            self.overruns += 1
            self.last_overrun = record

    def overrun_snapshot(self) -> dict:
        """What `step_timings()` / `metrics_snapshot()` / `describe()`
        carry: the count since `reset()` and the newest `stall` record
        (its attributes beside `phase`, `seconds`, `t_end`)."""
        with self._lock:
            return {"phase_overruns": self.overruns,
                    "last_overrun": self.last_overrun}

    def count_run(self):
        with self._lock:
            self._runs += 1

    class _Span:
        __slots__ = ("_timer", "_phase", "_t0", "_annotation", "_traced",
                     "_ident", "_outer")

        def __init__(self, timer, phase, traced):
            self._timer, self._phase, self._traced = timer, phase, traced

        def annotate(self, **attrs):
            """Attributes for the PT_TRACE view; dropped when it is
            off."""
            if self._traced is not None:
                self._traced.annotate(**attrs)
            return self

        def kept(self) -> bool:
            """Will `annotate` keep what it is given? Ask before
            building attributes that cost something to build."""
            return self._traced is not None

        def cancel(self):
            """Leave no record: the interval turned out not to be this
            phase (an admission attempt that found no capacity)."""
            self._phase = None

        def __enter__(self):
            if self._traced is not None:
                self._traced.__enter__()
            self._annotation = obs_trace.annotation(
                self._timer._span_names[self._phase])
            self._annotation.__enter__()
            self._ident = ident = threading.get_ident()
            opened = self._timer._open
            self._outer = opened.get(ident)
            self._t0 = t0 = time.perf_counter()
            opened[ident] = (self._phase, t0)
            return self

        def __exit__(self, *exc):
            t1 = time.perf_counter()
            self._annotation.__exit__(*exc)
            timer = self._timer
            # the span around this one is the open one again; only then
            # the look at the flags (the sentinel flags, then looks here)
            timer._open[self._ident] = self._outer
            if timer._overrun or t1 - self._t0 > obs_trace.OVERRUN_FLOOR_S:
                obs_trace._span_closed(timer, self._ident, self._phase,
                                       self._t0, t1)
            if self._phase is not None:
                self._timer.add(self._phase, t1 - self._t0, t1,
                                self._traced)
            elif self._traced is not None:
                self._traced.pop()
            return False

    def span(self, phase: str, parent: Optional[dict] = None,
             **attrs) -> "_Span":
        """Time `phase` around a `with` block. `parent` (a
        `trace.current_context()` dict) and `attrs` shape the PT_TRACE
        view only, as for `trace.span()`."""
        traced = (obs_trace.Span(phase, self.trace_cat, attrs, parent)
                  if obs_trace.enabled() else None)
        return self._Span(self, phase, traced)

    def snapshot(self, reset: bool = False) -> dict:
        """Accounted seconds per phase + derived host_overhead_pct.

        host_overhead_pct = host-side share of ACCOUNTED time (prep +
        dispatch + fetch vs device wait). With lazy fetches the phases
        overlap device execution, so this is an attribution of where the
        host spent its time, not a wall-clock decomposition — exactly
        what "is the remaining MFU gap host or device" needs."""
        with self._lock:
            out = {f"{p}_s": round(self._s[p], 6) for p in self.PHASES}
            out["runs"] = self._runs
            host = (self._s["host_prep"] + self._s["dispatch"]
                    + self._s["fetch"])
            total = host + self._s["device"]
            out["host_overhead_pct"] = (round(host / total * 100.0, 2)
                                        if total > 0 else None)
            if reset:
                self._s = {p: 0.0 for p in self.PHASES}
                self._runs = 0
                self.overruns = 0
        return out


def _attach_deferred_context(e: BaseException, prov: dict) -> None:
    """Attach (epoch, step, fetch name) provenance to an error raised at
    lazy materialization: the device computed it steps ago, and without
    this the traceback points at an unrelated log line. add_note on
    3.11+, args rewrite otherwise — the original exception TYPE is kept
    either way (callers match on it)."""
    if not prov:
        return
    note = ("deferred from device execution; in-flight fetch: "
            + ", ".join(f"{k}={v!r}" for k, v in sorted(prov.items())))
    add_note = getattr(e, "add_note", None)
    if callable(add_note):
        add_note(note)
    elif e.args and isinstance(e.args[0], str):
        e.args = (f"{e.args[0]}\n{note}",) + e.args[1:]
    else:
        e.args = e.args + (note,)


class LazyFetch:
    """Deferred fetch: wraps one fetch var's device value.

    Reading it (np.asarray / float() / .numpy() / indexing) blocks until
    the device value is ready and converts it to numpy ONCE (cached);
    `.value()` hands back the raw device array without any sync. The
    block is charged to the owning executor's device/fetch phases.

    `provenance` carries (fetch name from the executor; epoch/step via
    `annotate`) — a device error deferred to materialization re-raises
    with that context attached, and the step watchdog
    (resilience/watchdog.py, PT_STEP_DEADLINE_S) includes it in the
    hang dump."""

    __slots__ = ("_val", "_timer", "_np", "_prov", "_settle")

    def __init__(self, value, timer: Optional[PhaseTimer] = None,
                 provenance: Optional[dict] = None, on_settle=None):
        self._val = value
        self._timer = timer
        self._np = None
        self._prov = dict(provenance) if provenance else {}
        #: called once when the device value settles — the drift
        #: monitor's measured-step hook (obs/drift.py step_recorder);
        #: the recorder itself dedups across a run's several handles
        self._settle = on_settle

    def annotate(self, **context) -> "LazyFetch":
        """Merge provenance context (e.g. epoch=, step=); returns self."""
        self._prov.update(context)
        return self

    @property
    def provenance(self) -> dict:
        return dict(self._prov)

    # -- non-blocking surface ----------------------------------------------
    def value(self):
        """The underlying device value; never blocks."""
        return self._val

    @property
    def shape(self):
        return tuple(np.shape(self._val))

    @property
    def dtype(self):
        return np.dtype(jax.numpy.result_type(self._val))

    @property
    def ndim(self):
        return len(self.shape)

    def is_ready(self) -> bool:
        """True when the device computation has finished (never blocks)."""
        if self._np is not None:
            return True
        ready = getattr(self._val, "is_ready", None)
        return bool(ready()) if callable(ready) else True

    # -- blocking reads -----------------------------------------------------
    def numpy(self) -> np.ndarray:
        """Materialize to numpy (cached). THE synchronization point —
        which also makes it the step watchdog's boundary (an armed
        PT_STEP_DEADLINE_S turns a hung device step into StepHungError
        here) and where deferred device errors surface (re-raised with
        provenance attached)."""
        if self._np is None:
            from ..resilience import watchdog as _watchdog
            try:
                if self._timer is not None:
                    with self._timer.span("device"):
                        _watchdog.wait_until_ready(
                            self._val, provenance=self._prov,
                            timer=self._timer)
                    if self._settle is not None:
                        self._settle()
                    with self._timer.span("fetch"):
                        self._np = np.asarray(self._val)  # host-sync: ok — this IS the read
                else:
                    _watchdog.wait_until_ready(self._val,
                                               provenance=self._prov)
                    if self._settle is not None:
                        self._settle()
                    self._np = np.asarray(self._val)  # host-sync: ok — this IS the read
            except _watchdog.StepHungError:
                raise  # dump already carries the provenance
            except Exception as e:
                _attach_deferred_context(e, self._prov)
                raise
        return self._np

    def block_until_ready(self) -> "LazyFetch":
        self.numpy()
        return self

    def __array__(self, dtype=None, copy=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        return float(np.ravel(self.numpy())[0])  # host-sync: ok — explicit read

    def __int__(self):
        # host-sync: ok — explicit read
        return int(np.ravel(self.numpy())[0])

    def __bool__(self):
        return bool(self.numpy())

    def __getitem__(self, idx):
        return self.numpy()[idx]

    def __len__(self):
        return len(self.numpy())

    def __iter__(self):
        return iter(self.numpy())

    def __format__(self, spec):
        # host-sync: ok — explicit read
        return format(float(self) if spec and spec[-1] in "eEfFgGn%"
                      else self.numpy(), spec)

    def __repr__(self):
        if self._np is None and not self.is_ready():
            return (f"LazyFetch(shape={self.shape}, dtype={self.dtype}, "
                    "pending)")
        return f"LazyFetch({self.numpy()!r})"


def materialize(obj):
    """Recursively turn LazyFetch handles in lists/tuples/dicts into numpy
    arrays (anything else passes through unchanged)."""
    if isinstance(obj, LazyFetch):
        return obj.numpy()
    if isinstance(obj, (list, tuple)):
        return type(obj)(materialize(o) for o in obj)
    if isinstance(obj, dict):
        return {k: materialize(v) for k, v in obj.items()}
    return obj
