#!/usr/bin/env python3
"""Check of the trace reduction, kept with it: `trace_reduce.reduce` on
`fixtures/trace_small.json` (a cut of a trace recorded on the chip) and
on a few hand-made events whose answer can be worked out on paper.

    python3 benchmark/check_trace_reduce.py

Exits non-zero on the first number that is off. Not collected by the
repo's tests: it belongs to the benchmark.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402


def close(got, want, what, rel=1e-9):
    if abs(got - want) > rel * max(abs(want), 1e-12):
        raise SystemExit(f"check_trace_reduce: {what}: got {got!r}, "
                         f"want {want!r}")


def recorded():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as f:
        trace = json.load(f)
    red = trace_reduce.reduce(trace, "bench/traced_window")
    close(red["window_s"], 0.36, "window")
    close(red["busy_s"], 0.045399441, "device busy seconds")
    close(1 - red["busy_s"] / red["window_s"], 0.873890441667,
          "idle share", rel=1e-9)
    close(red["op_seconds"]["paged_attention"], 0.007672719,
          "paged_attention kernel seconds")
    if red["op_calls"]["paged_attention"] != 24:
        raise SystemExit("check_trace_reduce: paged_attention calls: "
                         f"{red['op_calls']['paged_attention']}, want 24")
    gaps = dict(red["idle_gaps"])
    close(gaps["program/seed_kv"], 0.199404552,
          "idle seconds under program/seed_kv")
    if red["idle_gaps"][0][0] != "program/seed_kv":
        raise SystemExit("check_trace_reduce: the longest idle gaps "
                         "should sit under program/seed_kv")


def by_hand():
    """One device, 1 ms window. Ops (us): a while container 0-1000;
    fusion.1 100-300; all-reduce.2 250-450 (50 us under the fusion, 150
    exposed); fusion.3.remat 600-700. Busy = 100-450 + 600-700 = 450 us.
    Gaps: 0-100 and 450-600 under span A (covers 0-600), 700-1000 under
    no span."""
    us = 1000
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while.7 = (s32[]) while(...)", 0, 1000 * us],
            ["%fusion.1 = f32[8] fusion(...)", 100 * us, 200 * us],
            ["%all-reduce.2 = f32[8] all-reduce(...)", 250 * us, 200 * us],
            ["%fusion.3.remat = f32[8] fusion(...)", 600 * us, 100 * us],
        ]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench/traced_window", 0, 1000 * us],
            ["program/A", 0, 600 * us],
            ["not/ours", 0, 1000 * us],
        ]}]}]}
    red = trace_reduce.reduce(trace, "bench/traced_window")
    close(red["busy_s"], 450e-6, "by hand: busy")
    close(red["collective_s"], 200e-6, "by hand: collective")
    close(red["collective_exposed_s"], 150e-6, "by hand: exposed")
    close(red["op_seconds"]["fusion"], 200e-6, "by hand: fusion")
    close(red["op_seconds"]["fusion.remat"], 100e-6, "by hand: remat")
    if "while" in red["op_seconds"]:
        raise SystemExit("check_trace_reduce: the while container "
                         "was counted")
    gaps = dict(red["idle_gaps"])
    close(gaps["program/A"], 250e-6, "by hand: gaps under A")
    close(gaps["(no span)"], 300e-6, "by hand: gaps under no span")


if __name__ == "__main__":
    recorded()
    by_hand()
    print("check_trace_reduce: ok")
