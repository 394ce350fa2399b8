#!/usr/bin/env python
"""Write the in-process structured trace as Chrome-trace-event JSON.

The obs/trace.py ring buffer holds the newest PT_TRACE_BUF spans from
every plane (executor phases, trainer events, data-pipeline stages, the
serving request lifecycle), and — whether or not PT_TRACE was ever set —
the last PhaseTimer phases of the executor, the serving planes and the
decode engine, plus every XLA compile. This tool serializes them in the Chrome
Trace Event format — load the file at https://ui.perfetto.dev (or
chrome://tracing) and the whole process reads as one timeline: pid/tid
lanes, nested spans, and trace/span/parent ids in each event's args.

Library use (the usual path — dump at the end of a run):

    from tools.trace_dump import dump
    path = dump("run_trace.json")            # drains the ring buffer

or, with ``PT_TRACE_DIR`` set, ``dump()`` writes
``<PT_TRACE_DIR>/pt_trace_<pid>.json`` next to the jax.profiler
device-side trace.

CLI:

    python tools/trace_dump.py --out trace.json [--demo]

--demo arms PT_TRACE, runs a tiny 3-step training program, and dumps
the resulting spans — a self-contained way to produce a loadable file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))


def dump(path: str = None, events=None, drain: bool = True) -> str:
    """Write a Perfetto-loadable Chrome-trace JSON file and return its
    path. `events` defaults to the live ring buffer (drained, so a
    periodic dumper emits disjoint windows; drain=False snapshots)."""
    from paddle_tpu.obs import trace
    if events is None:
        events = trace.drain() if drain else trace.events()
    if path is None:
        out_dir = os.environ.get(trace.DIR_ENV, "").strip() or "."
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"pt_trace_{os.getpid()}.json")
    doc = {"traceEvents": list(events), "displayTimeUnit": "ms"}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


def _demo_events() -> None:
    """Arm tracing and run a 3-step training program so the dump has a
    real multi-plane timeline in it."""
    os.environ["PT_TRACE"] = "1"
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        out = layers.fc(input=x, size=1, act=None)
        loss = layers.reduce_mean(layers.square(out - y))
        pt.optimizer.SGDOptimizer(learning_rate=0.01).minimize(loss)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"x": rng.rand(8, 4).astype("float32"),
                "y": rng.rand(8, 1).astype("float32")}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="output path (default: PT_TRACE_DIR/"
                         "pt_trace_<pid>.json, else ./)")
    ap.add_argument("--demo", action="store_true",
                    help="arm PT_TRACE and run a tiny 3-step training "
                         "program first, so the dump is non-empty")
    args = ap.parse_args(argv)
    if args.demo:
        _demo_events()
    path = dump(args.out)
    with open(path) as f:
        n = len(json.load(f)["traceEvents"])
    print(f"trace_dump: wrote {n} events to {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
