"""Structured tracing: the span core of the unified observability plane.

Every plane of the runtime — executor phases, trainer step/epoch/
checkpoint events, data-pipeline stages, the serving request lifecycle —
times itself already; what was missing is ONE causal timeline they all
land on. A `span` is a named, timed interval with attributes; finished
spans become Chrome-trace events (the JSON the Perfetto / chrome://
tracing UIs load natively, written by tools/trace_dump.py) in a bounded
process-wide ring buffer, so "why was this step/request slow" is
answerable from one artifact instead of four metric snapshots.

Design constraints, in order:

  1. near-zero cost off. Tracing is armed by ``PT_TRACE`` (read per
     call — one dict lookup — so it can be toggled at runtime); when
     off, ``span()`` returns a shared no-op and ``emit`` paths return
     before building anything. The documented budget is <= 1% on the
     disabled path (tests pin a per-call bound). The one thing that
     is recorded WITHOUT being asked is a finished `PhaseTimer` phase
     (`phase()`): one tuple appended to the ring, rendered to a
     Chrome event only when the ring is read — so the last phases of
     a process nobody armed are in `tools/trace_dump.py`'s file and
     the postmortem bundle, and a reader can sum them
     (`phase_records()`).
  2. bounded memory. Events land in a ring (``PT_TRACE_BUF`` events,
     default 65536, re-read whenever the ring is recreated) — a long
     run_loop keeps the NEWEST window, it never grows.
  3. thread-correct. The active-span stack is thread-local: spans
     opened on a serving dispatcher thread or a map_batches worker can
     never parent under another thread's trainer step. Cross-thread
     causality is EXPLICIT: capture `current_context()` where the work
     is submitted and pass it as ``parent=`` (or enter
     ``use_context()``) where it runs — the serving batcher does
     exactly this to thread a request id from HTTP ingress through the
     dispatcher.

Clocks are monotonic (`time.perf_counter`), with one process-wide
origin, so events from every thread and plane share one timeline.

``PT_TRACE_DIR`` additionally arms `device_profile()` — a
`jax.profiler.trace` session writing device-side op attribution (the
per-op `jax.named_scope`s from core/lowering.py) next to the host-side
spans; the Trainer enters it around the training loop.
"""

from __future__ import annotations

import atexit
import gc
import itertools
import os
import sys
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

__all__ = ["span", "instant", "complete", "phase", "phase_records",
           "attr_records", "annotation", "enabled", "current_context",
           "use_context", "watch", "overrun_after", "sentinel_since",
           "stall_counters",
           "active_stack", "events", "drain", "reset", "new_id",
           "device_profile", "postmortem_dump", "ENABLE_ENV", "BUF_ENV",
           "DIR_ENV", "DEFAULT_BUF"]

ENABLE_ENV = "PT_TRACE"
BUF_ENV = "PT_TRACE_BUF"
DIR_ENV = "PT_TRACE_DIR"
#: room for the phase records of a whole measured window, which a reader
#: sums (`phase_records()`): the decode engine leaves six a step, 25,000
#: in a 55 s window at a step of 13 ms, and this is over twice that (it
#: holds such a window down to a step of about 5 ms)
DEFAULT_BUF = 65536

#: values of PT_TRACE that mean "off" (mirrors flags._Flags bool parse)
_OFF = ("", "0", "false", "no", "off")

#: one timeline origin for every thread and plane
_T0 = time.perf_counter()

_ids = itertools.count(1)          # span/trace ids (next() is atomic)
_ring_lock = threading.Lock()
_ring: Optional[deque] = None      # created lazily; maxlen from env


class _TLS(threading.local):
    def __init__(self):
        self.stack: List["Span"] = []     # open spans, innermost last
        self.ctx: Optional[dict] = None   # inherited cross-thread context


_tls = _TLS()


def enabled() -> bool:
    """Is tracing armed? One env-dict lookup — cheap enough to call on
    every would-be span, and toggleable at runtime (tests, bench A/B)."""
    return os.environ.get(ENABLE_ENV, "0").strip().lower() not in _OFF


def new_id() -> int:
    """A fresh process-unique id (request ids, trace ids)."""
    return next(_ids)


def _now_us() -> float:
    return (time.perf_counter() - _T0) * 1e6


def _buf_size() -> int:
    raw = os.environ.get(BUF_ENV, "").strip()
    if not raw:
        return DEFAULT_BUF
    try:
        n = int(raw)
    except ValueError:
        return DEFAULT_BUF
    return n if n > 0 else DEFAULT_BUF


def _append(entry) -> None:
    """One ring for everything: a rendered event (dict) or a phase
    record (tuple, see `phase()`)."""
    global _ring
    with _ring_lock:
        if _ring is None:
            _ring = deque(maxlen=_buf_size())
        _ring.append(entry)


def _args_with_ids(attrs: Optional[dict], trace_id: Optional[int],
                   span_id: Optional[int],
                   parent_id: Optional[int]) -> dict:
    args: Dict[str, object] = dict(attrs) if attrs else {}
    if trace_id is not None:
        args["trace_id"] = trace_id
    if span_id is not None:
        args["span_id"] = span_id
    if parent_id is not None:
        args["parent_id"] = parent_id
    return args


def _event(name: str, cat: str, ph: str, ts_us: float, dur_us: float,
           args: dict, tid: Optional[int] = None) -> dict:
    ev = {"name": name, "cat": cat, "ph": ph,
          "ts": round(ts_us, 1), "pid": os.getpid(),
          "tid": threading.get_ident() if tid is None else tid,
          "args": args}
    if ph == "X":
        ev["dur"] = round(dur_us, 1)
    else:
        ev["s"] = "t"   # instant scope: thread
    return ev


def _render(entry) -> dict:
    if isinstance(entry, dict):
        return entry
    cat, name, t_end, seconds, tid, args = entry
    return _event(name, cat, "X", (t_end - seconds - _T0) * 1e6,
                  seconds * 1e6, args or {}, tid)


class _Noop:
    """Shared no-op span for the disabled path: supports the context
    protocol and the Span surface, allocates nothing per use."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def annotate(self, **attrs):
        return self


NOOP = _Noop()


class Span:
    """One open interval on this thread's stack. Entering pushes it
    (children parent under it); exiting pops and emits the Chrome-trace
    "X" event. Create via `span()`."""

    __slots__ = ("name", "cat", "attrs", "trace_id", "span_id",
                 "parent_id", "_t0")

    def __init__(self, name: str, cat: str, attrs: dict,
                 parent: Optional[dict]):
        self.name = name
        self.cat = cat
        self.attrs = attrs
        top = _tls.stack[-1] if _tls.stack else None
        if top is not None:
            self.trace_id, self.parent_id = top.trace_id, top.span_id
        else:
            ctx = parent if parent is not None else _tls.ctx
            if ctx:
                self.trace_id = ctx.get("trace_id")
                self.parent_id = ctx.get("span_id")
            else:
                self.trace_id, self.parent_id = new_id(), None
        self.span_id = new_id()

    def annotate(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        self._t0 = _now_us()
        _tls.stack.append(self)
        return self

    def pop(self) -> dict:
        """Leave the stack without emitting; returns the event args
        (attributes + ids). `__exit__` emits with them; a PhaseTimer
        span hands them to `phase()`, which records the interval."""
        # defensive pop: a mis-nested exit must not corrupt the stack
        if _tls.stack and _tls.stack[-1] is self:
            _tls.stack.pop()
        elif self in _tls.stack:
            _tls.stack.remove(self)
        return _args_with_ids(self.attrs, self.trace_id, self.span_id,
                              self.parent_id)

    def __exit__(self, *exc):
        args = self.pop()
        t1 = _now_us()
        _append(_event(self.name, self.cat, "X", self._t0,
                       t1 - self._t0, args))
        return False


def span(name: str, cat: str = "app", parent: Optional[dict] = None,
         **attrs):
    """Open a span: ``with trace.span("step", cat="train", epoch=e):``.
    Returns the shared no-op when tracing is off. `parent` (a
    `current_context()` dict) overrides the thread's inherited context
    when this thread's stack is empty — explicit cross-thread
    causality."""
    if not enabled():
        return NOOP
    return Span(name, cat, dict(attrs), parent)


def instant(name: str, cat: str = "app", parent: Optional[dict] = None,
            **attrs) -> None:
    """A zero-duration marker (guard anomaly, eviction, epoch edge)."""
    if not enabled():
        return
    _append(_event(name, cat, "i", _now_us(), 0.0,
                   _child_ids(attrs, parent)))


def complete(name: str, dur_s: float, cat: str = "app",
             parent: Optional[dict] = None, end_ts: Optional[float] = None,
             **attrs) -> None:
    """Emit an already-measured interval ending now (or at `end_ts`, a
    `time.perf_counter()` reading) — the hook the existing timers use:
    PhaseTimer.add / PipelineMetrics.add know a duration, not a span
    object. Parented like span(): this thread's stack, else `parent`,
    else the inherited context."""
    if not enabled():
        return
    end_us = (_now_us() if end_ts is None
              else (end_ts - _T0) * 1e6)
    _append(_event(name, cat, "X", end_us - dur_s * 1e6, dur_s * 1e6,
                   _child_ids(attrs, parent)))


def phase(cat: str, name: str, seconds: float,
          t_end: Optional[float] = None,
          open_span: Optional[Span] = None,
          attrs: Optional[dict] = None) -> None:
    """Record one finished `PhaseTimer` phase — ALWAYS, whatever
    PT_TRACE says: `(cat, name, t_end, seconds, tid, args)` appended
    under the ring's lock and rendered to a Chrome "X" event only when
    `events()` / `drain()` read the ring. `t_end` is a
    `time.perf_counter()` reading (now, when not given). With PT_TRACE
    on the record also carries ids and attributes: those of
    `open_span` (the Span a `PhaseTimer.span()` pushed on this
    thread's stack, popped here), else a fresh child of this thread's
    innermost open span, exactly as `complete()` parents. `attrs` are
    the record's own, kept whatever PT_TRACE says (a kernel's static
    plan: a record with no duration, read from `events()`)."""
    if t_end is None:
        t_end = time.perf_counter()
    if open_span is not None:
        args = open_span.pop()
    elif enabled():
        args = _child_ids(attrs, None)
    else:
        args = attrs
    _append((cat, name, t_end, seconds, threading.get_ident(), args))


def phase_records() -> List[tuple]:
    """The ring's phase records, oldest first, as `(cat, name, t_end,
    seconds)` with `t_end` on `time.perf_counter()` — what a reader
    needs to sum the phases that ended inside a window of its own. The
    ring keeps the newest `PT_TRACE_BUF` entries of every kind: a
    reader whose window starts before the oldest record here cannot
    know what was dropped, and must say so instead of summing."""
    return [e[:4] for e in _phase_entries()]


def attr_records() -> List[tuple]:
    """The ring's phase records that carry attributes, oldest first, as
    `(cat, name, t_end, seconds, attrs)`: what a `stall`, a `host/gc`
    or a `kernel/flash_plan` record says beside its length."""
    return [e[:4] + (e[5],) for e in _phase_entries() if e[5]]


def _phase_entries() -> List[tuple]:
    _flush_collections()
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
    return [e for e in entries if isinstance(e, tuple)]


def _child_ids(attrs: Optional[dict], parent: Optional[dict]) -> dict:
    ctx = _context_or(parent)
    return _args_with_ids(attrs, ctx.get("trace_id") if ctx else None,
                          new_id(), ctx.get("span_id") if ctx else None)


def _context_or(parent: Optional[dict]) -> Optional[dict]:
    if _tls.stack:
        top = _tls.stack[-1]
        return {"trace_id": top.trace_id, "span_id": top.span_id}
    if parent is not None:
        return parent
    return _tls.ctx


def current_context() -> Optional[dict]:
    """{"trace_id", "span_id"} of the innermost open span on THIS
    thread (or the inherited context), or None. Capture it where work
    is submitted; pass it as `parent=` / `use_context()` where the work
    runs on another thread."""
    return _context_or(None)


def current_attrs() -> dict:
    """Provenance view of the innermost open span: its ids plus its
    attributes (a trainer step span carries epoch=/step=). Empty when
    tracing is off or no span is open — callers layer their own
    plumbing only in that case (the LazyFetch provenance contract)."""
    if not _tls.stack:
        return {}
    top = _tls.stack[-1]
    out = dict(top.attrs)
    out["span"] = f"{top.cat}:{top.name}#{top.span_id}"
    out["trace_id"] = top.trace_id
    return out


@contextmanager
def use_context(ctx: Optional[dict]):
    """Adopt a captured context as this thread's root parent (worker
    threads executing submitted work)."""
    prev = _tls.ctx
    _tls.ctx = ctx
    try:
        yield
    finally:
        _tls.ctx = prev


def active_stack() -> List[dict]:
    """This thread's open spans, outermost first — what the step
    watchdog attaches to a StepHungError dump (which phase/stage/
    request was in flight when the step hung)."""
    return [{"name": s.name, "cat": s.cat, "span_id": s.span_id,
             "trace_id": s.trace_id, "attrs": dict(s.attrs)}
            for s in _tls.stack]


_annotation_factory = None     # jax.profiler.TraceAnnotation, on first use


def annotation(name: str):
    """The profiler's view of a program span: a
    `jax.profiler.TraceAnnotation` context manager, so the span lands
    on the device trace's own timeline and an idle gap of the chip can
    be put down to it. A no-op inside the profiler's C++ when no
    session is open. jax is imported on first use, once (tests
    substitute `_annotation_factory`)."""
    global _annotation_factory
    if _annotation_factory is None:
        import jax
        _annotation_factory = jax.profiler.TraceAnnotation
    return _annotation_factory(name)


# ---------------------------------------------------------------------------
# the stall sentinel
# ---------------------------------------------------------------------------
#
# A `PhaseTimer` says how long a phase took, not what its thread was
# doing inside ONE span that took a hundred times its usual length. The
# sentinel is the process's one daemon thread that looks at the timers'
# OPEN spans from outside (a timer keeps `(phase, t0)` by thread in
# plain dict writes: nothing on the hot path waits for this thread). It
# wakes every `SENTINEL_PERIOD_S` and reads the CPU clock of each thread
# that has a span open, so that a span it later finds overrun has a
# baseline from before it opened (the thread's /proc files are opened
# only once its span is flagged). A span is OVERRUN when it has been
# open longer than `overrun_after(usual)`, `usual` being its phase's
# own running length (`PhaseTimer.add`); there is no knob. Phases that
# wait for work are never judged; phases that wait on the device are,
# and say so. From then on the span's thread is sampled every period
# until the span closes, and ONE record goes to the ring:
# `("stall", "<cat>/<phase>", t_end, seconds)` with the attributes of
# `_finish`, whatever PT_TRACE says. docs/observability.md has the
# table.
#
# Collections are spans of the same plane: one `gc.callbacks` hook,
# installed with the sentinel, holds `program/host/gc` open on the
# profiler's clock from a collection's start to its stop and leaves a
# ring record `("host", "gc", t_end, seconds)` where it took
# `GC_RECORD_S` or more.

SENTINEL_PERIOD_S = 0.025
OVERRUN_FLOOR_S = 0.1
OVERRUN_MULTIPLE = 2.0
GC_RECORD_S = 1e-3
GC_SPAN = "program/host/gc"
STACK_FRAMES = 8

_sentinel_lock = threading.Lock()      # start / stop
_sentinel: Optional[threading.Thread] = None
_sentinel_halt: Optional[threading.Event] = None
_sentinel_off = False                  # `_sentinel_stop()` is sticky
_due = 0.0                             # when the sentinel wakes next
_since = 0.0                           # when it started
_tick_errors = 0
_watched: "weakref.WeakSet" = weakref.WeakSet()
_stalls: List["_Stall"] = []           # flagged, still open
_tracks: Dict[int, list] = {}          # ident -> [t0, baseline, newest]
_clocks: Dict[int, Optional[int]] = {}     # ident -> its CPU clock's id
_overruns: Dict[str, list] = {}        # "<cat>/<phase>" -> [n, seconds]
_gc_counts: Dict[int, list] = {}       # generation -> [n, seconds]
_gc_open: Optional[tuple] = None       # (annotation, t0) under way
_gc_pending: deque = deque()           # records the hook could not append
_gc_recent: deque = deque(maxlen=64)   # (t_start, t_stop) of the long ones


def overrun_after(usual: float) -> float:
    """THE overrun rule: seconds a span of a phase whose usual length
    is `usual` may stay open before it is judged overrun."""
    return max(OVERRUN_FLOOR_S, OVERRUN_MULTIPLE * usual)


def watch(timer) -> None:
    """Register a `PhaseTimer` (weakly) and start the sentinel with the
    first one. The timer brings `_open`, `_overrun`, `_usual`,
    `trace_cat`, `WAITS_FOR_WORK`, `WAITS_ON_DEVICE` and
    `_on_overrun(record)`."""
    _watched.add(timer)
    if _sentinel is None or not _sentinel.is_alive():
        _sentinel_start(wanted=False)


def _sentinel_start(wanted: bool = True) -> None:
    """Start the thread and install the collection hook, once a
    process. `wanted=True` (tests) undoes a `_sentinel_stop()`."""
    global _sentinel, _sentinel_halt, _sentinel_off
    global _due, _since, _tick_errors
    with _sentinel_lock:
        if wanted:
            _sentinel_off = False
        if _sentinel_off or (_sentinel is not None
                             and _sentinel.is_alive()):
            return
        annotation(GC_SPAN)     # jax is imported here, not in the hook
        if _on_collection not in gc.callbacks:
            gc.callbacks.append(_on_collection)
        if _sentinel_halt is None:     # the first start: out at exit,
            atexit.register(_sentinel_stop)    # before jax goes
        _sentinel_halt = threading.Event()
        _tick_errors = 0
        _since = _due = time.perf_counter()
        _sentinel = threading.Thread(
            target=_sentinel_loop, args=(_sentinel_halt,),
            name="pt-stall-sentinel", daemon=True)
        _sentinel.start()


def _sentinel_stop() -> None:
    """Test hook: stop the thread and take the collection hook out,
    and keep both out whatever timer registers later (a run that
    measures what the sentinel costs). `_sentinel_start()` undoes it."""
    global _sentinel, _sentinel_off
    with _sentinel_lock:
        _sentinel_off = True
        thread, _sentinel = _sentinel, None
        if _sentinel_halt is not None:
            _sentinel_halt.set()
        if _on_collection in gc.callbacks:
            gc.callbacks.remove(_on_collection)
    if thread is not None and thread is not threading.current_thread():
        thread.join(1.0)


def _sentinel_loop(halt: threading.Event) -> None:
    global _due, _tick_errors
    while not halt.wait(max(_due - time.perf_counter(), 0.0)):
        now = time.perf_counter()
        late = now - _due      # how long the interpreter kept it out
        try:
            _tick(now, late)
        except Exception:   # noqa: BLE001 — observability must not die
            _tick_errors += 1
        _due = time.perf_counter() + SENTINEL_PERIOD_S


def sentinel_since() -> Optional[float]:
    """Since when (on `time.perf_counter()`) the sentinel has watched
    the timers' open spans; `None` while it does not run. A reader that
    finds no `stall` record in a window knows from it whether anybody
    looked."""
    thread = _sentinel
    if thread is None or not thread.is_alive():
        return None
    return _since


def _tick(now: float, late: float) -> None:
    _flush_collections()
    frames = None
    for stall in list(_stalls):
        if stall.timer._overrun.get((stall.ident, stall.t0)) is not stall:
            _stalls.remove(stall)      # its own thread closed it
            continue
        if frames is None:
            frames = sys._current_frames()
        stall.observe(frames, late)
    if len(_tracks) > 64:              # threads come and go
        _tracks.clear()
    for timer in list(_watched):
        spans = timer._open
        if not spans:
            continue
        try:
            spans = list(spans.items())
        except RuntimeError:    # a thread's first span, this instant
            continue
        for ident, span in spans:
            if span is None:
                continue
            name, t0 = span
            sample = _sample(ident)
            track = _tracks.get(ident)
            if track is not None and track[0] == t0:
                track[2] = sample
            else:       # first sight of this span
                _tracks[ident] = track = [
                    t0, _baseline(ident, t0) or sample, sample]
            if name in timer.WAITS_FOR_WORK \
                    or (ident, t0) in timer._overrun:
                continue
            usual = timer._usual.get(name)
            if usual is None or now - t0 <= overrun_after(usual):
                continue
            stall = _Stall(timer, ident, name, t0, usual, track[1])
            # the flag first, then a second look: an exit that did not
            # see the flag has put another entry in `_open` by now, and
            # judges the span itself (`_span_closed`)
            timer._overrun[ident, t0] = stall
            if frames is None:
                frames = sys._current_frames()
            stall.observe(frames, late)
            if timer._open.get(ident) is span:
                _stalls.append(stall)
            elif timer._overrun.pop((ident, t0), None) is stall:
                stall.close()   # else its own thread has it, and closes


class _Stall:
    """One overrun span under observation. From the flag on it holds
    its thread's two /proc files (Linux's; `None` each where the kernel
    or a sandbox does not give them): whoever takes the stall out of
    the timer's `_overrun` calls `close()`, once."""

    __slots__ = ("timer", "ident", "phase", "t0", "usual", "base",
                 "stack_first", "stack_last", "others", "late", "fds",
                 "proc")

    def __init__(self, timer, ident, phase_, t0, usual, base,
                 flagged: bool = True):
        self.timer, self.ident, self.phase = timer, ident, phase_
        self.t0, self.usual, self.base = t0, usual, base
        self.stack_first = self.stack_last = self.others = None
        self.late = 0.0
        self.fds = _open_proc(ident) if flagged else (None, None)
        self.proc = _read_proc(self.fds)

    def observe(self, frames: dict, late: float,
                closing: bool = False) -> None:
        """One look at the thread. `closing`: the look its own thread
        takes when the span closes unwatched, so the stack is where the
        span closed and no `stack_first` exists."""
        self.stack_last = _stack(frames.get(self.ident))
        if self.others is None:
            if not closing:
                self.stack_first = self.stack_last
            names = {t.ident: t.name for t in threading.enumerate()}
            watcher = _sentinel.ident if _sentinel is not None else None
            self.others = [
                f"{names.get(i, i)}: {_stack(f, 1)[0]}"
                for i, f in frames.items()
                if i != self.ident and i != watcher][:16]
        self.late = max(self.late, late)

    def close(self) -> tuple:
        """The /proc files' last reading, and the files closed."""
        last = _read_proc(self.fds)
        for fd in self.fds:
            if fd is not None:
                os.close(fd)
        self.fds = (None, None)
        return last


def _span_closed(timer, ident: int, name: Optional[str], t0: float,
                 t1: float) -> None:
    """`PhaseTimer._Span.__exit__`, on the span's own thread, for a
    span the sentinel has flagged or one that was open longer than the
    rule's floor. A flagged span's record is written here, with the
    span's own end (a cancelled span, `name` None, leaves none, as it
    leaves no phase record). One that overran with nobody watching (the
    interpreter was held from its opening to its close: a collection,
    a C call that keeps it; the sentinel wakes after the span has
    closed) is judged here by the same rule, and its record says what
    can still be known: the collections inside it, how late the
    sentinel is, the thread's CPU clock since the sentinel's last look
    at it, where the span closed and what the other threads are in."""
    stall = timer._overrun.pop((ident, t0), None)
    if name is None:
        if stall is not None:
            stall.close()
        return
    if stall is None:
        usual = timer._usual.get(name)
        if usual is None or t1 - t0 <= overrun_after(usual) \
                or name in timer.WAITS_FOR_WORK or _sentinel is None:
            return
        stall = _Stall(timer, ident, name, t0, usual,
                       _baseline(ident, t0), flagged=False)
        frames = sys._current_frames()
        frames[ident] = sys._getframe(2)    # the code around the span
        stall.observe(frames, max(t1 - _due, 0.0), closing=True)
    _finish(stall, t1, _sample(ident))


def _baseline(ident: int, t0: float) -> Optional[tuple]:
    """The sample a span's CPU clock is read against: the sentinel's
    first of that span, or its newest of the thread from shortly BEFORE
    the span opened (the loop nearly always has some span open, and a
    span whose whole length the interpreter was held for is first seen
    when it is over); `None` where it has neither."""
    track = _tracks.get(ident)
    if track is None:
        return None
    if track[0] == t0:
        return track[1]
    if 0.0 <= t0 - track[2][0] <= 2 * SENTINEL_PERIOD_S:
        return track[2]
    return None


def _finish(stall: _Stall, t_end: float, last: tuple) -> None:
    base, timer = stall.base, stall.timer
    proc, proc_base = stall.close(), stall.proc

    def since(after, before, i):
        if before is None or after[i] is None or before[i] is None:
            return None
        return round(after[i] - before[i], 6)

    name = f"{timer.trace_cat}/{stall.phase}"
    seconds = t_end - stall.t0
    attrs = {
        "usual_s": round(stall.usual, 6),
        # the CPU clock runs from the baseline sample to the last one
        "sampled_s": since(last, base, 0), "cpu_s": since(last, base, 1),
        # the /proc files from the flag to the close
        "run_delay_s": since(proc, proc_base, 0),
        "major_faults": since(proc, proc_base, 1),
        "gc_s": round(_collecting(stall.t0, t_end), 6),
        # the longest the sentinel itself was kept from waking: the
        # interpreter was not handed over (a collection, a C call
        # that holds it) or the whole process did not run
        "late_s": round(stall.late, 6),
        "waits_on": ("device" if stall.phase in timer.WAITS_ON_DEVICE
                     else None),
        "stack_first": stall.stack_first, "stack_last": stall.stack_last,
        "others": stall.others}
    phase("stall", name, seconds, t_end, attrs=attrs)
    with _ring_lock:
        count = _overruns.setdefault(name, [0, 0.0])
        count[0] += 1
        count[1] += seconds
    timer._on_overrun(dict(attrs, phase=name, seconds=round(seconds, 6),
                           t_end=t_end))


def _stack(frame, limit: int = STACK_FRAMES) -> List[str]:
    """Innermost first, `dir/file.py:line function`."""
    out = []
    while frame is not None and len(out) < limit:
        code = frame.f_code
        path = "/".join(code.co_filename.rsplit("/", 2)[-2:])
        out.append(f"{path}:{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return out or ["(no frame)"]


def _sample(ident: int) -> tuple:
    """`(perf_counter, CPU seconds)` of the thread `ident`, read from
    any thread; the second `None` where the platform has no clock of a
    thread's or the thread is not one `threading` knows."""
    try:
        clock = _clocks[ident]
    except KeyError:
        if len(_clocks) >= 64:      # threads come and go
            _clocks.clear()
        clock = None
        if any(t.ident == ident for t in threading.enumerate()):
            try:
                clock = time.pthread_getcpuclockid(ident)
            except (AttributeError, OSError):
                pass
        _clocks[ident] = clock
    cpu = None
    if clock is not None:
        try:
            cpu = time.clock_gettime(clock)
        except OSError:             # the thread behind the ident went
            _clocks.pop(ident, None)
    return time.perf_counter(), cpu


def _open_proc(ident: int) -> tuple:
    """The thread's `schedstat` and `stat` under /proc/self/task, each
    an fd or `None`."""
    thread = next((t for t in threading.enumerate()
                   if t.ident == ident), None)
    fds = []
    for name in ("schedstat", "stat"):
        try:
            fds.append(os.open(
                f"/proc/self/task/{thread.native_id}/{name}", os.O_RDONLY))
        except (OSError, AttributeError):
            fds.append(None)
    return tuple(fds)


def _read_proc(fds: tuple) -> tuple:
    """`(run-queue delay seconds, major page faults)`, each `None`
    where its file is not there."""
    sched_fd, stat_fd = fds
    delay = faults = None
    try:
        if sched_fd is not None:
            delay = int(os.pread(sched_fd, 128, 0).split()[1]) / 1e9
        if stat_fd is not None:
            # the fields behind "(comm)": state is the third of /proc's
            # numbering, majflt the twelfth
            faults = int(os.pread(stat_fd, 1024, 0)
                         .rpartition(b")")[2].split()[9])
    except (OSError, IndexError, ValueError):
        pass
    return delay, faults


def _on_collection(when: str, info: dict) -> None:
    """The `gc.callbacks` hook. A collection runs with the interpreter
    held and never inside another, so one module slot is enough. The
    ring's lock may be held by the very thread that collects (an
    allocation inside `events()`): the record waits in `_gc_pending`
    for the sentinel's next wake or the ring's next reader."""
    global _gc_open
    if when == "start":
        span = annotation(GC_SPAN)
        span.__enter__()
        _gc_open = (span, time.perf_counter())
        return
    t1 = time.perf_counter()
    if _gc_open is None:       # installed while this one ran
        return
    (span, t0), _gc_open = _gc_open, None
    span.__exit__(None, None, None)
    generation = info["generation"]
    count = _gc_counts.get(generation)
    if count is None:
        count = _gc_counts[generation] = [0, 0.0]
    count[0] += 1
    count[1] += t1 - t0
    if t1 - t0 >= GC_RECORD_S:
        _gc_pending.append(("host", "gc", t1, t1 - t0,
                            threading.get_ident(),
                            {"generation": generation,
                             "collected": info["collected"]}))


def _flush_collections() -> None:
    while _gc_pending:
        try:
            entry = _gc_pending.popleft()
        except IndexError:     # another reader took it
            return
        _gc_recent.append((entry[2] - entry[3], entry[2]))
        _append(entry)


def _collecting(t0: float, t1: float) -> float:
    """Seconds of `[t0, t1]` inside collections of `GC_RECORD_S` or
    more (the newest 64 of them)."""
    _flush_collections()
    return sum(max(0.0, min(t1, stop) - max(t0, start))
               for start, stop in list(_gc_recent))


def stall_counters() -> dict:
    """What `obs.metrics` renders: overruns by `<cat>/<phase>` and
    collections by generation, each `(count, seconds)`; and, in the
    snapshot alone, since when the sentinel watches and how many of its
    wakes raised."""
    with _ring_lock:
        overruns = {k: tuple(v) for k, v in _overruns.items()}
    return {"overruns": overruns,
            "collections": {g: tuple(v)
                            for g, v in sorted(_gc_counts.items())},
            "watching_since": sentinel_since(),
            "tick_errors": _tick_errors}


def events() -> List[dict]:
    """Snapshot of the ring buffer (oldest first), non-destructive."""
    _flush_collections()
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
    return [_render(e) for e in entries]


def drain() -> List[dict]:
    """Pop every buffered event (tools/trace_dump.py's source)."""
    global _ring
    _flush_collections()
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
        _ring = None
    return [_render(e) for e in entries]


def reset(buf: Optional[int] = None) -> None:
    """Clear the buffer; the next event re-reads PT_TRACE_BUF (or uses
    `buf`) for the ring size."""
    global _ring
    _gc_pending.clear()
    with _ring_lock:
        _ring = deque(maxlen=int(buf)) if buf else None


def postmortem_dump(tag: str, error: Optional[str] = None) -> Optional[str]:
    """Crash-forensics mini-bundle: when PT_TRACE_DIR is set, write the
    trace ring (non-destructive snapshot), this thread's active span
    stack, and the merged metrics snapshot as ONE JSON file beside the
    jax.profiler dir — the Trainer calls this when it escalates
    StepAnomalyError / StepHungError, so the evidence of the dying run
    (which step, which spans were open, what every gauge last read)
    survives the process. Returns the path, or None when unarmed; never
    raises — forensics must not mask the original error."""
    out_dir = os.environ.get(DIR_ENV, "").strip()
    if not out_dir:
        return None
    try:
        import json
        from .metrics import global_snapshot
        doc = {"reason": str(tag), "error": error, "pid": os.getpid(),
               "unix_time": time.time(),
               "active_spans": active_stack(),
               "trace_events": events(),
               "metrics": global_snapshot()}
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"pt_postmortem_{os.getpid()}_{tag}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        os.replace(tmp, path)
        return path
    except Exception:   # noqa: BLE001 — never mask the escalating error
        return None


@contextmanager
def device_profile():
    """jax.profiler.trace session under PT_TRACE_DIR (and PT_TRACE on):
    device-side op attribution written beside the host-side spans. A
    no-op when unarmed; profiler failures never break the caller (the
    Trainer wraps its whole loop in this)."""
    log_dir = os.environ.get(DIR_ENV, "").strip()
    if not log_dir or not enabled():
        yield
        return
    try:
        import jax
        prof = jax.profiler.trace(log_dir)
        prof.__enter__()
    except Exception:   # noqa: BLE001 — observability must not kill runs
        yield
        return
    try:
        yield
    finally:
        try:
            prof.__exit__(None, None, None)
        except Exception:   # noqa: BLE001
            pass
