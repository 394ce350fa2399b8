#!/usr/bin/env python3
"""The builder's sweep for an open-loop cell's rate: one bring-up, then
one open-loop run at each rate on the same warm engine, drained between
rates. Prints, per rate, what shows whether the backlog grows: time to
first token over the first and the second half of the window, and what
was still queued at the close. The highest rate at which the second
half is no worse than the first is the knee; the traffic file then
carries 0.7 of it as a number. Not part of any cell's command.

    python3 benchmark/tools/sweep_rate.py --workload <open-loop cell> \
        --rates 0.4,0.6,0.8,1.0 --seconds 40
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", default=None)
    args = ap.parse_args(argv)
    args.trace = 0

    cell = common.Cell(args.rehearse or os.path.join(
        common.ROOT, "BENCHMARK.json"), args.workload)
    device = common.require_chips(cell.chips, bool(args.rehearse))
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    from kinds import _serve, open_loop
    engine, dec, _, correct = _serve.bring_up(cell, args, device)
    compiles = common.CompileCounter()
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_per_s=rate)
            got = open_loop.drive(engine, dec, cell, traffic, args.seed,
                                  args.seconds, compiles, drain=True)
            clients = got.pop("_clients")
            t_open = got["t_open"]
            mid = t_open + args.seconds / 2

            def ttft(lo, hi):
                v = [(c.stamps[0] - c.due) * 1e3 for c in clients
                     if lo <= c.due < hi and c.stamps]
                return statistics.median(v) if v else None

            gaps = sorted(got["gaps_ms"])
            common.note(
                rate_per_s=rate, offered=got["offered"],
                unserved=got["unserved"],
                ttft_p50_first_half_ms=ttft(t_open, mid),
                ttft_p50_second_half_ms=ttft(mid, t_open + args.seconds),
                ttft_max_ms=max(got["ttft_ms"], default=None),
                itl_mean_ms=sum(gaps) / max(len(gaps), 1),
                itl_p99_ms=gaps[int(0.99 * len(gaps))] if gaps else None,
                prefill_s=got["prefill_s"], decode_s=got["decode_s"],
                busy_share=(got["prefill_s"] + got["decode_s"])
                / args.seconds,
                correct=correct, device=device["kind"])
    finally:
        engine.shutdown(drain=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
