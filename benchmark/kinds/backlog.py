"""Traffic kind `backlog`: offline generation. The whole backlog is
submitted in one piece before the window opens, so that during the
window no client thread runs: the scheduler's sequence of admissions
and steps is the same in every run of a seed, and only its speed varies.

The window opens when the engine has made `lead_in_steps` decode steps
(a state, not a time: the same point of the same sequence every run) and
closes `--seconds` later. Tokens are counted when emitted
(`DecodeMetrics.tokens_out`, read at the window's two ends), never when
a request completes.

Observations: the counters of `_serve.COUNTERS` over the window,
`window_s`, `compiles_in_window`, the set-up clocks, `kernel` (a traced
run), and `model`, `block_size`: the configuration's sizes for the
reader that prices the whole step (`readers/step_mfu.py`).
"""

from __future__ import annotations

import gc
import time

import common
import workload
from kinds import _model, _serve


def _whole(handle, request) -> bool:
    """A request that finished gave exactly the tokens it was asked for
    (random weights, no EOS); one cut off by the shutdown says nothing."""
    if not handle.done():
        return True
    try:
        return len(handle.result()["tokens"]) == request["max_new"]
    except Exception:   # noqa: BLE001 (failed by the shutdown)
        return True


def run(cell, args, device, t_start):
    cfg, tr = cell.config, cell.traffic
    traced = bool(args.trace)
    engine, dec, obs, correct = _serve.bring_up(cell, args, device)
    try:
        spans = _serve.ProgramSpans(dec.model) if traced else None
        tracer = common.Tracer(traced, cell.name, bool(args.rehearse))
        slots = int(cfg["serving"]["slots"])
        requests = workload.request_groups(
            tr, args.seed, int(tr["requests"]),
            _model.sizes(cfg)["vocab"])
        workload.stagger_first(requests, slots)
        compiles = common.CompileCounter()
        gc.collect()
        gc.freeze()

        # one piece: the scheduler sees the whole backlog at once, in
        # submission order
        handles = dec.scheduler.while_idle(lambda: [
            engine.generate(_serve.MODEL_NAME, r["prompt"],
                            max_new_tokens=r["max_new"])
            for r in requests])
        base = _serve.counters(dec)
        lead_in = int(tr["lead_in_steps"])
        deadline = time.perf_counter() + 300
        while _serve.counters(dec)["decode_steps"] - base["decode_steps"] \
                < lead_in:
            if time.perf_counter() > deadline:
                raise SystemExit("benchmark: the lead-in never ended")
            time.sleep(0.002)

        # -- the measured window ----------------------------------------------
        compiles_before = compiles.count
        before = _serve.counters(dec)
        t_open = time.perf_counter()
        obs["setup_s"] = t_open - t_start
        time.sleep(max(0.0, t_open + args.seconds - time.perf_counter()))
        window_s = time.perf_counter() - t_open
        after = _serve.counters(dec)
        compiles_in_window = compiles.count - compiles_before
        gauges = dec.metrics_snapshot()
        waiting, active = gauges["waiting"], gauges["active"]
        # a traced run profiles the seconds after the window has closed,
        # on the same backlog: the profiler's start and stop cost the
        # host seconds that would otherwise be read as the engine's
        _serve.trace_for(tracer, spans, float(tr["trace_seconds"]))
    finally:
        engine.shutdown(drain=False)

    counts = _serve.window_counts(before, after)
    whole = all(_whole(h, r) for h, r in zip(handles, requests))
    failed = (counts["failed"] + counts["shed_overload"]
              + counts["shed_deadline"])
    if waiting == 0:
        raise SystemExit("benchmark: the backlog ran dry inside the "
                         "window; the traffic file needs more requests")
    obs.update(counts, window_s=window_s,
               compiles_in_window=compiles_in_window,
               kernel=_serve.kernel_shape(cell, spans),
               model=_model.sizes(cfg),
               block_size=int(cfg["serving"]["block_size"]))
    common.note(window=dict(
        counts, seconds=window_s, active_at_close=active,
        waiting_at_close=waiting,
        slot_occupancy=(counts["slots_used_sum"]
                        / max(counts["slots_capacity_sum"], 1))))
    return dict(obs=obs, correct=bool(correct and whole and not failed),
                attempted=counts["completed"] + active, failed=failed,
                reduced=tracer.reduce())
