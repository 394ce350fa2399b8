"""Traffic kind `open_loop`: requests sent on a schedule, whatever the
server does. Each is due at a fixed time (the gaps are a fixed set, in
seeded order), is timed from that due time, and is read by one consumer
thread that stamps each token as it arrives, as a streaming client
would: the handle carries no timestamps of its own.

The schedule starts `lead_in_s` before the window so that the window
opens on a system in its steady state. Requests due inside the window
count; one with no first token `grace_s` after the window is failed.
A gap between two tokens counts when its later token arrived inside the
window, whichever request it belongs to.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import common
import workload
from kinds import _model, _serve


class _Client:
    """One request's streaming reader."""

    def __init__(self, handle, due: float):
        self.due = due
        self.sent = time.perf_counter()
        self.stamps: List[float] = []
        self.error = None
        self._handle = handle
        self._thread = threading.Thread(target=self._read, daemon=True)
        self._thread.start()

    def _read(self):
        try:
            for _ in self._handle.stream():
                self.stamps.append(time.perf_counter())
        except Exception as e:   # noqa: BLE001 (typed serving errors)
            self.error = e

    def join(self, timeout):
        self._thread.join(timeout)
        return not self._thread.is_alive()


def drive(engine, dec, cell, traffic: Dict, seed: int, seconds: float,
          compiles, tracer=None, spans=None, drain: bool = False) -> Dict:
    """One open-loop run on a warm, idle engine. Returns observations.
    `drain` waits for every request to finish (a sweep's next rate must
    start idle); otherwise the caller shuts the engine down."""
    lead_in, grace = float(traffic["lead_in_s"]), float(traffic["grace_s"])
    rate = float(traffic["rate_per_s"])
    vocab = _model.sizes(cell.config)["vocab"]
    n = int((lead_in + seconds) * rate * 1.5) + 16
    requests = workload.request_groups(traffic, seed, n, vocab)

    # the schedule: every request at its due time, and the window's
    # opening and closing among them. A traced run keeps the schedule
    # going for `trace_seconds` past the close and profiles that tail:
    # the profiler's start and stop hold the interpreter for seconds,
    # which inside the window would be read as the server's
    tracing = tracer is not None and tracer.enabled
    tail = float(traffic["trace_seconds"]) if tracing else 0.0
    events, due = [], time.perf_counter() + 0.05
    t_open = due + lead_in
    t_close = t_open + seconds
    for req in requests:
        due += req["gap_s"]
        if due >= t_close + tail:
            break
        events.append((due, req))
    events += [(t_open, "open"), (t_close, "close")]
    events.sort(key=lambda e: e[0])

    clients: List[_Client] = []
    refused = []
    trace_thread = None
    for when, req in events:
        time.sleep(max(0.0, when - time.perf_counter()))
        if req == "open":
            common.note(event="window_open")
            compiles_before = compiles.count
            before = _serve.counters(dec)
        elif req == "close":
            common.note(event="window_close")
            after = _serve.counters(dec)
            compiles_in_window = compiles.count - compiles_before
            if tracing:
                # a thread of its own: the sender keeps its schedule
                trace_thread = threading.Thread(
                    target=_serve.trace_for, daemon=True,
                    args=(tracer, spans, tail))
                trace_thread.start()
        else:
            try:
                handle = engine.generate(_serve.MODEL_NAME, req["prompt"],
                                         max_new_tokens=req["max_new"])
            except Exception as e:   # noqa: BLE001 (refused at admission)
                refused.append((when, e))
                continue
            clients.append(_Client(handle, when))
    if trace_thread is not None:
        trace_thread.join(tail + 120)

    in_window = [c for c in clients if t_open <= c.due < t_close]
    deadline = t_close + grace
    while time.perf_counter() < deadline and \
            any(not c.stamps and c.error is None for c in in_window):
        time.sleep(0.01)
    if drain:
        for c in clients:
            c.join(600)

    served = [c for c in in_window if c.stamps]
    n_refused = sum(1 for d, _ in refused if t_open <= d < t_close)
    gaps = [(b - a) * 1e3 for c in clients
            for a, b in zip(c.stamps, c.stamps[1:]) if t_open <= b < t_close]
    counts = _serve.window_counts(before, after)
    obs = dict(counts, window_s=seconds, t_open=t_open,
               compiles_in_window=compiles_in_window,
               ttft_ms=[(c.stamps[0] - c.due) * 1e3 for c in served],
               gaps_ms=gaps,
               gen_late_ms=[(c.sent - c.due) * 1e3 for c in in_window],
               offered=len(in_window) + n_refused,
               unserved=len(in_window) - len(served) + n_refused)
    if spans is not None and not counts["evictions"] and \
            len(spans.prefill_starts) >= len(served):
        # prefills run in submission order while nothing is evicted, so
        # the k-th prefill of the run is the k-th request sent
        obs["queue_wait_ms"] = [
            (start - c.due) * 1e3
            for start, c in zip(spans.prefill_starts, clients)
            if t_open <= c.due < t_close]
    obs["_clients"] = clients
    return obs


def run(cell, args, device, t_start):
    tr = cell.traffic
    traced = bool(args.trace)
    engine, dec, obs, correct = _serve.bring_up(cell, args, device)
    try:
        spans = _serve.ProgramSpans(dec.model) if traced else None
        tracer = common.Tracer(traced, cell.name, bool(args.rehearse))
        compiles = common.CompileCounter()
        gc.collect()
        gc.freeze()
        got = drive(engine, dec, cell, tr, args.seed, args.seconds,
                    compiles, tracer=tracer, spans=spans)
    finally:
        engine.shutdown(drain=False)
    clients = got.pop("_clients")
    for c in clients:
        c.join(30)
    # the window opens lead_in_s after the schedule starts: set-up runs
    # from process start (t_start, same clock) to that opening
    obs["setup_s"] = got.pop("t_open") - t_start
    obs.update(got,
               kernel=_serve.kernel_shape(cell, spans))
    common.note(window={k: v for k, v in got.items()
                        if not isinstance(v, list)},
                requests_sent=len(clients), gaps=len(got["gaps_ms"]))
    failed = got["unserved"] + got["failed"]
    return dict(obs=obs, correct=bool(correct), attempted=got["offered"],
                failed=failed, reduced=tracer.reduce())
