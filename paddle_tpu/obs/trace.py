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

import itertools
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional

__all__ = ["span", "instant", "complete", "phase", "phase_records",
           "annotation", "enabled", "current_context", "use_context",
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
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
    return [e[:4] for e in entries if isinstance(e, tuple)]


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


def events() -> List[dict]:
    """Snapshot of the ring buffer (oldest first), non-destructive."""
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
    return [_render(e) for e in entries]


def drain() -> List[dict]:
    """Pop every buffered event (tools/trace_dump.py's source)."""
    global _ring
    with _ring_lock:
        entries = list(_ring) if _ring is not None else []
        _ring = None
    return [_render(e) for e in entries]


def reset(buf: Optional[int] = None) -> None:
    """Clear the buffer; the next event re-reads PT_TRACE_BUF (or uses
    `buf`) for the ring size."""
    global _ring
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
