"""How many steps `run_loop`'s scan body should hold: both bodies on the
chip, at a program that fills the device and at one whose step is short.

`Executor.run_loop` runs a window of steps as ONE `lax.scan`; `unroll` is
how many steps its body holds. A second step in the body saves one scan
iteration's sequencing in two, and its buffers are live while the first
step's last ones still are: where that crosses the device's memory the
compiler's own rematerialisation pass recomputes activations to fit. This
tool puts both on one table, a row a body:

  ms a step            host clock around whole `run_loop` calls that end in
                       the fetched losses, median and least of `--calls`
                       calls in each of `--rounds` rounds (1, 2, 1, 2 ...)
  temp / argument GiB  `memory_analysis()` of the loop's executable
  remat instructions   instructions of the optimized module whose name ends
                       `.remat` (the compiler cloned them to recompute),
                       and the sum of their `estimated_cycles`

the last three from `lowering.loop_compile_figures`, which compiles the loop
again from shapes alone (the executor's own call stays a plain `jax.jit`).
Before a program's rows, the body `run_loop` builds there when no `unroll`
is given (`analysis.memory.loop_body_steps`: the estimate against this
device's memory).

Programs (`--program`):
  cell   the train cell's (`cgpt1p3b_train_seq2k`: `transformer_lm_loss` at
         9 layers of 2,048, vocabulary 50,257, 4 x 2,048 tokens a step,
         Adam, bfloat16 AMP; 8 steps a call)
  small  the 2-layer `transformer_lm` default (d 128, 4 heads, vocabulary
         1,000, 8 x 128 tokens a step, Adam, bfloat16 AMP; 256 steps a call):
         a step well under a millisecond, where a cost of each scan
         iteration would show if there is one
  mlp    the two `fc` layers of `tests/test_loop_amp.py` (SGD; 2,048 steps)

    python tools/loop_unroll_sweep.py --program cell     # on the chip
    python tools/loop_unroll_sweep.py --program small
    JAX_PLATFORMS=cpu python tools/loop_unroll_sweep.py --rehearse

Prints one JSON line a row and a table at the end; `--out` also writes the
lines to a file. `--rehearse` runs the same code at a tiny size on whatever
backend there is and prints no time under a device's name.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis.memory import loop_body_steps
from paddle_tpu.core import lowering
from paddle_tpu.models import transformer as tfm

GIB = 2 ** 30
#: the v5e's clock, for the compiler's cycles in milliseconds
CLOCK_HZ = 1.5e9

PROGRAMS = {
    "cell": dict(kind="lm", vocab=50257, seq_len=2048, batch=4, n_layers=9,
                 d_model=2048, n_heads=16, d_ff=8192, n_steps=8),
    "small": dict(kind="lm", vocab=1000, seq_len=128, batch=8, n_layers=2,
                  d_model=128, n_heads=4, d_ff=512, n_steps=256),
    "mlp": dict(kind="mlp", in_dim=4, hidden=8, batch=8, n_steps=2048),
}
_TINY_LM = dict(kind="lm", vocab=64, seq_len=16, batch=2, n_layers=1,
                d_model=32, n_heads=2, d_ff=64, n_steps=4)
TINY = {"cell": _TINY_LM, "small": _TINY_LM,
        "mlp": dict(kind="mlp", in_dim=4, hidden=8, batch=8, n_steps=4)}


def build(shape, seed):
    """(main, startup, loss, feeds of one call with a leading [n_steps])."""
    rng = np.random.RandomState(seed % (2 ** 31 - 1))
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % (2 ** 31 - 1)
    n, b = shape["n_steps"], shape["batch"]
    with pt.program_guard(main, startup):
        if shape["kind"] == "lm":
            s = shape["seq_len"]
            loss, _ = tfm.transformer_lm_loss(
                vocab_size=shape["vocab"], seq_len=s,
                n_layers=shape["n_layers"], d_model=shape["d_model"],
                n_heads=shape["n_heads"], d_ff=shape["d_ff"], max_len=s)
            pt.optimizer.AdamOptimizer(learning_rate=3e-4).minimize(loss)
            ids = rng.randint(0, shape["vocab"], (n, b, s + 1))
            feed = {"src_ids": ids[..., :-1].astype("int64"),
                    "tgt_ids": ids[..., 1:, None].astype("int64")}
        else:
            x = layers.data("x", [shape["in_dim"]], dtype="float32")
            y = layers.data("y", [1], dtype="float32")
            h = layers.fc(input=x, size=shape["hidden"], act="relu")
            loss = layers.mean(layers.square_error_cost(
                input=layers.fc(input=h, size=1), label=y))
            pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
            xs = rng.rand(n, b, shape["in_dim"]).astype("float32")
            feed = {"x": xs, "y": xs.sum(axis=2, keepdims=True) * 0.5}
    if shape["kind"] == "lm":
        main.amp_dtype = "bfloat16"
    return main, startup, loss, feed


def sweep(name, shape, unrolls, calls, rounds, seed, unit, emit):
    main, startup, loss, feed = build(shape, seed)
    n_steps = shape["n_steps"]
    scope, exe = pt.Scope(), pt.Executor()

    def call(unroll):
        with pt.scope_guard(scope):
            return exe.run_loop(main, feed=feed, fetch_list=[loss],
                                n_steps=n_steps, per_step_feeds=True,
                                unroll=unroll)[0]

    with pt.scope_guard(scope):
        exe.run(startup)
        shapes = exe._prep_feed(main, feed, per_step=True)
    emit(what="default_body", program=name,
         unroll=loop_body_steps(main, batch=shape["batch"]))
    rows = {}
    for unroll in unrolls:
        figures = lowering.loop_compile_figures(
            main, shapes, [loss.name], n_steps=n_steps, per_step_feeds=True,
            unroll=unroll)
        t0 = time.perf_counter()
        call(unroll)                         # compiles
        call(unroll)          # the state comes back laid out as it was left
        rows[unroll] = dict(figures, times=[], compile_and_two_calls_s=
                            time.perf_counter() - t0)
    for _ in range(rounds):
        for unroll in unrolls:
            for _ in range(calls):
                t0 = time.perf_counter()
                losses = call(unroll)        # numpy: the fetch is the wait
                rows[unroll]["times"].append(time.perf_counter() - t0)
            assert np.all(np.isfinite(losses)), (name, unroll)
    out = []
    for unroll in unrolls:
        row = rows[unroll]
        per_step = [1e3 * t / n_steps for t in row.pop("times")]
        remat = row.pop("remat")
        out.append(emit(
            what="body", program=name, unroll=unroll, n_steps=n_steps,
            unit=unit, median=statistics.median(per_step),
            least=min(per_step), calls=len(per_step),
            temp_gib=row["temp_bytes"] / GIB,
            argument_gib=row["argument_bytes"] / GIB,
            remat_instructions=row["remat_instructions"],
            remat_estimated_ms_per_body=1e3 * row["remat_cycles"] / CLOCK_HZ,
            remat=[f"{n} {s.split('{')[0]} {op.split('/', 3)[-1]}"
                   for n, s, op in remat],
            compile_and_two_calls_s=row["compile_and_two_calls_s"]))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--program", default="cell,small",
                    help="comma list of " + ", ".join(PROGRAMS))
    ap.add_argument("--unrolls", default="1,2")
    ap.add_argument("--calls", type=int, default=5,
                    help="timed run_loop calls a body a round")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--layers", type=int, default=None,
                    help="another depth for the `cell` program")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print(f"no chip here ({device.platform}): a time from this backend "
              "is no device time; --rehearse runs the tool at a tiny size",
              file=sys.stderr)
        return 2
    out = open(args.out, "w") if args.out else None

    def emit(**fields):
        line = json.dumps(fields)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        return fields

    limit = (device.memory_stats() or {}).get("bytes_limit")
    emit(what="device", platform=device.platform, kind=device.device_kind,
         bytes_limit=limit, rehearsal=bool(args.rehearse))
    unit = (f"{device.platform}_ms_per_step_rehearsal" if args.rehearse
            else "ms_per_step")
    calls, rounds = (1, 1) if args.rehearse else (args.calls, args.rounds)
    rows = []
    for name in args.program.split(","):
        shape = dict((TINY if args.rehearse else PROGRAMS)[name])
        if args.layers and name == "cell":
            shape["n_layers"] = args.layers
        rows += sweep(name, shape, [int(u) for u in args.unrolls.split(",")],
                      calls, rounds, args.seed, unit, emit)
    print(f"\n{'program':8} {'unroll':>6} {unit + ' median':>24} "
          f"{'least':>9} {'temp GiB':>9} {'args GiB':>9} {'.remat':>6} "
          f"{'their est. ms a body':>21}")
    for r in rows:
        print(f"{r['program']:8} {r['unroll']:>6} {r['median']:>24.4f} "
              f"{r['least']:>9.4f} {r['temp_gib']:>9.3f} "
              f"{r['argument_gib']:>9.3f} {r['remat_instructions']:>6} "
              f"{r['remat_estimated_ms_per_body']:>21.2f}")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
