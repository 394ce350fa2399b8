#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is comes from data: its entry in BENCHMARK.json names a
configuration (`configs/`), a traffic mix (`traffic/`, whose `kind`
names the module under `kinds/` that drives it) and, through the
metrics' `workloads` lists, the metrics it reports (`end_to_end/`,
`layer_metrics/`, each naming its reader under `readers/`). This file
holds no name of a cell, configuration, traffic mix or metric.

The last line of standard output is the contract's JSON object. With
`--trace 0` its metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from the program's counters and
host clocks over the same window and from a profiler trace of the few
seconds after it closes (the profiler's start and stop stall the host,
which inside the window would be read as the system's).

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, the run ends non-zero and prints no result. `--rehearse
<manifest>` is the builder's walk through the same code off the chip,
with a manifest and tiny data files of its own (kept under
/root/scratch): it takes any backend and prints no metric at all.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="MANIFEST", default=None,
                    help="walk the code off the chip; prints no metric")
    args = ap.parse_args(argv)

    manifest = args.rehearse or os.path.join(ROOT, "BENCHMARK.json")
    cell = common.Cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)

    device = common.require_chips(cell.chips, bool(args.rehearse))
    from paddle_tpu.core.compile_cache import enable_compile_cache
    common.note(cell=cell.name, seed=args.seed, seconds=args.seconds,
                trace=args.trace, device=device,
                compile_cache=enable_compile_cache())

    kind = importlib.import_module("kinds." + cell.traffic["kind"])
    out = kind.run(cell, args, device, T_START)

    ctx = dict(obs=out["obs"], reduced=out["reduced"], device=device,
               cell=cell)
    specs = cell.per_layer if args.trace else cell.end_to_end
    metrics = common.read_metrics(specs, ctx)
    if args.rehearse:
        print({"rehearsal": True, "correct": out["correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               "readers_with_a_value": sorted(metrics),
               "readers_without": sorted(set(specs) - set(metrics))})
        return 0
    missing = set(cell.end_to_end) - set(metrics)
    if not args.trace and missing:
        raise SystemExit(f"benchmark: no value for {sorted(missing)}")
    print(common.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], metrics=metrics, device=device,
        chips=cell.chips, reduced=out["reduced"]))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        # A run that fails on the chip must end at once: after a
        # RESOURCE_EXHAUSTED on four chips the TPU runtime's own shutdown
        # waited for ever, and the process held its machine until it was
        # killed half an hour later (PR 24). No result was printed; leave
        # without the interpreter's teardown.
        import traceback
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(1)
