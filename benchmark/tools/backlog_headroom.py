#!/usr/bin/env python3
"""The builder's count of how long a backlog keeps the slots full: the
cell's own request sequence (`workload.request_groups` + `stagger_first`)
replayed through a slot model of the decode scheduler's pass (admit into
every free slot, one step over the running slots), with a step and an
admission of fixed length. No chip, no program: numpy and this
directory's generator. It prints how many requests wait when the window
closes and when the traced seconds after it (+ 2 s of profiler) end, and,
by bisection on the step, the tokens/s from which each reaches 0: the
cell's dry points. The rule (PERF.md section 7 (8)): a backlog cell keeps
at least twice its ledger rate of headroom through both. Never a cell's
command.

    python3 benchmark/tools/backlog_headroom.py --workload <backlog cell> \
        --step-ms 11.9 --admit-ms 12.4
    python3 benchmark/tools/backlog_headroom.py \
        --traffic benchmark/traffic/rollout_backlog.json --slots 16 ...

The model keeps what sets the count and nothing else: a request of
`max_new` tokens holds its slot for `max_new - 1` steps (its first token
comes from the admission and `tokens_out` counts the steps' tokens);
the window opens after `lead_in_steps` steps; an admission costs
`--admit-ms` whatever the prompt's length (the cell's mean).
"""

import argparse
import json
import os
import sys
from collections import deque

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE]

import common  # noqa: E402  (the manifest's reader; it imports no JAX)
import workload  # noqa: E402

PROFILER_S = 2.0    # what a trace's start and stop hold the host, at most


def outputs_of(traffic, slots):
    """`max_new` of each request, in submission order, as a run of any
    seed offers them (the seed draws token ids only)."""
    requests = workload.request_groups(traffic, 0, int(traffic["requests"]),
                                       2)
    workload.stagger_first(requests, slots)
    return [r["max_new"] for r in requests]


def replay(outputs, slots, lead_in_steps, step_ms, admit_ms, marks_s):
    """Waiting requests at each of `marks_s` (seconds after the window
    opens, rising) and the step tokens counted up to the first mark.
    Returns (waiting at each mark, tokens/s over the first mark)."""
    waiting = deque(outputs)
    running = []            # steps each running request still takes
    t = steps = tokens = 0
    t_open = tokens_open = None
    at = []
    counted = None          # tokens when the first mark came
    while len(at) < len(marks_s):
        while waiting and len(running) < slots:
            left = waiting.popleft() - 1
            t += admit_ms
            if left > 0:
                running.append(left)
        if not running:
            break           # the backlog is spent: none waits at any later mark
        t += step_ms
        steps += 1
        tokens += len(running)
        running = [r - 1 for r in running if r > 1]
        if t_open is None and steps >= lead_in_steps:
            t_open, tokens_open = t, tokens
        while t_open is not None and len(at) < len(marks_s) \
                and t >= t_open + marks_s[len(at)] * 1e3:
            if not at:
                counted = tokens
            at.append(len(waiting))
    if counted is None:
        counted = tokens
    at += [0] * (len(marks_s) - len(at))
    return at, (counted - (tokens_open or 0)) / marks_s[0]


def dry_point(outputs, slots, lead_in_steps, admit_ms, marks_s, which):
    """The tokens/s at the longest step that leaves no request waiting
    at mark `which`: bisection on the step, the admission held."""
    lo, hi = 0.05, 200.0    # ms a step: dry at lo, not dry at hi
    args = (outputs, slots, lead_in_steps)
    if replay(*args, lo, admit_ms, marks_s)[0][which] > 0:
        return None         # never dry: admissions alone fill the time
    if replay(*args, hi, admit_ms, marks_s)[0][which] == 0:
        return 0.0
    while hi - lo > 1e-3:
        mid = (lo + hi) / 2
        if replay(*args, mid, admit_ms, marks_s)[0][which] == 0:
            lo = mid
        else:
            hi = mid
    return replay(*args, lo, admit_ms, marks_s)[1]


def headroom(traffic, slots, step_ms, admit_ms, seconds):
    """All the tool prints, as a dict (the test's entry too)."""
    outputs = outputs_of(traffic, slots)
    marks = [float(seconds),
             float(seconds) + float(traffic["trace_seconds"]) + PROFILER_S]
    lead_in = int(traffic["lead_in_steps"])
    at, rate = replay(outputs, slots, lead_in, step_ms, admit_ms, marks)
    return {
        "requests": len(outputs), "slots": slots,
        "step_ms": step_ms, "admit_ms": admit_ms,
        "seconds": marks[0], "traced_until_s": marks[1],
        "tokens_per_s": rate,
        "waiting_at_close": at[0], "waiting_after_trace": at[1],
        "dry_at_close_tokens_per_s": dry_point(
            outputs, slots, lead_in, admit_ms, marks, 0),
        "dry_under_trace_tokens_per_s": dry_point(
            outputs, slots, lead_in, admit_ms, marks, 1)}


def cell_files(manifest_path, name):
    """(traffic, slots, run_seconds) of a cell, as the harness reads the
    manifest and the two data files it names."""
    cell = common.Cell(manifest_path, name)
    return (cell.traffic, int(cell.config["serving"]["slots"]),
            cell.run_seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a backlog cell of the manifest")
    ap.add_argument("--manifest",
                    default=os.path.join(common.ROOT, "BENCHMARK.json"))
    ap.add_argument("--traffic", help="a traffic file, in a cell's place")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--admit-ms", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: the manifest's run_seconds)")
    args = ap.parse_args(argv)

    if args.workload:
        traffic, slots, seconds = cell_files(args.manifest, args.workload)
    elif args.traffic:
        traffic, slots = common.load_json(args.traffic), 16
        seconds = common.load_json(args.manifest)["run_seconds"]
    else:
        ap.error("give --workload or --traffic")
    if not str(traffic["kind"]).startswith("backlog"):
        ap.error(f"kind {traffic['kind']!r} is no backlog")
    got = headroom(traffic, args.slots or slots, args.step_ms,
                   args.admit_ms, args.seconds or seconds)
    print(json.dumps(dict(got, workload=args.workload,
                          traffic=args.traffic)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
