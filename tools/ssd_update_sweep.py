"""The decode step's state update alone on the chip: one layer call of
`kernels/ssd_update.py`'s Pallas kernel at the two cells' shapes, and
what bounds a live slot's block.

    nemotron3   128 slots, 64 heads of [64, 128] in 8 groups (8 heads
                share a B and a C row), the step from a softplus
    sala        64 slots, 32 heads of [128, 128] in 32 groups, dt = 1
                (MiniCPM-SALA's lightning layers)

Each call is timed whole, with its arithmetic stubbed (the body is
`o_ref[...] = s_ref[...]`: the copies alone) and with its copies stubbed
(every grid step names slot 0, so no block moves between steps: the
arithmetic alone), on the device's own queue, the state carried from
call to call as the step carries it, and divided by the live slots: a
slot's period beside its 2 x 4 H P N bytes at the HBM's rate.
`--kernels FILE[,FILE]` times other copies of `kernels/ssd_update.py`
beside this tree's (the parent's) and says whether the states they write
are bit-equal to this tree's.

    python tools/ssd_update_sweep.py --kernels _checkout/parent/paddle_tpu/kernels/ssd_update.py
    JAX_PLATFORMS=cpu python tools/ssd_update_sweep.py --rehearse

Prints one JSON line a reading and a table at the end; `--out` also
writes the lines to a file. `--rehearse` runs the same code interpreted at
a tiny size and prints no time under a device's name.
"""

import argparse
import contextlib
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from flash_block_sweep import load_kernels
from paged_group_sweep import HBM_BYTES_PER_S
from sparse_walk_sweep import emit

from paddle_tpu.kernels import ssd_update as su

CELLS = {
    "nemotron3": dict(slots=128, heads=64, p=64, n=128, groups=8,
                      dt_ones=False),
    "sala": dict(slots=64, heads=32, p=128, n=128, groups=32, dt_ones=True),
}
TINY = {
    "nemotron3": dict(slots=4, heads=8, p=16, n=128, groups=1,
                      dt_ones=False, dead=(1,)),
    "sala": dict(slots=3, heads=4, p=128, n=128, groups=4, dt_ones=True),
}


def make_case(shape, seed):
    """A cell's call: (state, x, dt, a, b, c, live), every slot live but
    a tiny shape's `dead` ones."""
    s, h, p, n, g = (shape[k] for k in ("slots", "heads", "p", "n",
                                        "groups"))
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    dt = jnp.ones((s, h), f32) if shape["dt_ones"] else \
        jax.random.uniform(keys[2], (s, h), f32, 1e-3, 0.5)
    live = np.ones(s, bool)
    live[list(shape.get("dead", ()))] = False
    return (jax.random.normal(keys[0], (s, h, p, n), f32),
            jax.random.normal(keys[1], (s, h, p), f32), dt,
            -jax.random.uniform(keys[3], (h,), f32, 0.05, 4.0),
            jax.random.normal(keys[4], (s, g, n), f32),
            jax.random.normal(keys[5], (s, g, n), f32), jnp.asarray(live))


def layer_call(kernels, interpret):
    """One layer call as the step makes it, the Pallas path whatever the
    backend."""
    return lambda *case: kernels._ssd_update_pallas(*case,
                                                    interpret=interpret)


def _copy_body(*refs, **_):
    """The kernel's body with its arithmetic stubbed: a live slot's block
    goes back as it came (the refs end in the state in, the state out and
    y, in the parent's kernel and in this tree's)."""
    s_ref, o_ref, y_ref = refs[-3:]
    o_ref[...] = s_ref[...]
    y_ref[...] = jnp.zeros_like(y_ref)


@contextlib.contextmanager
def stubbed(what, kernels):
    """The kernel of `kernels` (a copy of `kernels/ssd_update.py`) traced
    `whole`, with the `copies` alone (`_copy_body` in the body's place) or
    with the `arithmetic` alone (the call's first two operands, the
    prefetched ids and live count, replaced by slot 0 at every step and
    all steps live); the jitted wrapper's cache cleared on both sides."""
    pallas = kernels.pl
    was_call, was_body = pallas.pallas_call, kernels._ssd_update_kernel

    def one_slot(*a, **kw):
        call = was_call(*a, **kw)
        return lambda ids, n, *rest: call(
            jnp.zeros_like(ids), jnp.full_like(n, ids.shape[0]), *rest)

    kernels._ssd_update_pallas.clear_cache()
    if what == "copies":
        kernels._ssd_update_kernel = _copy_body
    elif what == "arithmetic":
        pallas.pallas_call = one_slot
    try:
        yield
    finally:
        pallas.pallas_call, kernels._ssd_update_kernel = was_call, was_body
        kernels._ssd_update_pallas.clear_cache()


def seconds_a_call(fn, case, calls):
    """Device seconds of one call of `fn`: a loop of calls on the
    device's queue, each on the state the call before wrote and an x that
    call's y moved, at `calls` and at a quarter of it; the difference
    over the difference (so the loop's one copy of the state into its
    carry is not in it)."""
    @jax.jit
    def loop(n, state, x, rest):
        def body(_, carry):
            state, x = carry
            y, state = fn(state, x, *rest)
            return state, x + 1e-6 * y
        return jax.lax.fori_loop(0, n, body, (state, x))

    def run(n):
        jax.block_until_ready(loop(n, case[0], case[1], case[2:]))
        t0 = time.perf_counter()
        jax.block_until_ready(loop(n, case[0], case[1], case[2:]))
        return time.perf_counter() - t0

    few = max(calls // 4, 1)
    return (run(calls) - run(few)) / max(calls - few, 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--kernels", default="",
                    help="other copies of kernels/ssd_update.py to time "
                         "beside this tree's, comma-separated")
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--seed", type=int, default=57)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        raise SystemExit("the sweep times a TPU; --rehearse runs it here "
                         "interpreted, for its control flow alone")
    shapes = TINY if args.rehearse else CELLS
    if args.rehearse:
        args.calls = 2
    others = [f for f in args.kernels.split(",") if f]
    forms = {("other" if len(others) == 1 else f"other{i}"): load_kernels(f)
             for i, f in enumerate(others)}
    forms["tree"] = su
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_us"
    per = 1.0 if args.rehearse else 1e6
    emit(out, what="sweep", device=jax.devices()[0].device_kind,
         platform=platform, calls=args.calls, others=others or None)
    table = []
    for n, name in enumerate(c for c in args.cells.split(",") if c):
        shape = shapes[name]
        case = make_case(shape, args.seed + n)
        live = int(np.sum(np.asarray(case[-1])))
        slot_bytes = 2 * 4 * shape["heads"] * shape["p"] * shape["n"]
        want_y, want = jax.jit(su.ssd_update_reference)(*case)
        emit(out, what="plan", cell=name, **su.ssd_update_plan(
            shape["heads"], shape["groups"], shape["p"],
            shape["n"])._asdict())
        states = {}
        for form, kernels in forms.items():
            fn = layer_call(kernels, args.rehearse)
            y, states[form] = jax.jit(fn)(*case)
            emit(out, what="against_reference", cell=name, form=form,
                 state_max_abs=float(jnp.max(jnp.abs(states[form] - want))),
                 y_max_abs=float(jnp.max(jnp.abs(y - want_y))))
            line = dict(what="layer_call", cell=name, form=form, unit=unit,
                        live_slots=live,
                        slot_hbm_us=1e6 * slot_bytes / HBM_BYTES_PER_S)
            for what in ("whole", "copies", "arithmetic"):
                with stubbed(what, kernels):
                    key = what if what == "whole" else f"{what}_alone"
                    line[key] = per * seconds_a_call(fn, case, args.calls)
            for key in ("whole", "copies_alone", "arithmetic_alone"):
                line[f"{key}_a_slot"] = line[key] / live
            emit(out, **line)
            table.append(line)
        for form in forms:
            if form != "tree":
                emit(out, what="states_bit_equal", cell=name, form=form,
                     to="tree", equal=bool(jnp.array_equal(
                         states[form], states["tree"])))
        del case, states
    print(f"{'cell':>10} {'form':>7} {'live':>5} {'whole':>10} "
          f"{'copies':>10} {'arith':>10} {'whole/slot':>10} "
          f"{'copies/slot':>11} {'arith/slot':>10} {'HBM/slot':>8}  "
          f"({unit})")
    for r in table:
        print(f"{r['cell']:>10} {r['form']:>7} {r['live_slots']:>5} "
              f"{r['whole']:>10.4g} {r['copies_alone']:>10.4g} "
              f"{r['arithmetic_alone']:>10.4g} {r['whole_a_slot']:>10.4g} "
              f"{r['copies_alone_a_slot']:>11.4g} "
              f"{r['arithmetic_alone_a_slot']:>10.4g} "
              f"{r['slot_hbm_us']:>8.3g}")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
