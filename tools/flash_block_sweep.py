"""The flash kernels alone on the chip: the sweep that sets
`kernels/flash_attention.py` `_default_block`, and what bounds a block.

At the train cell's attention (`cgpt1p3b_train_seq2k`: 64 batch-heads of
2,048 x 128, bfloat16, causal: forward, dq and dk/dv) and at Kanana's
longest prefill bucket (32 heads of 6,144, scores on 192 and values of
128, float32, causal: the forward alone, its backward is XLA's) it times
each kernel by itself over square blocks of 256 / 512 / 1,024 (`--blocks`;
`1024x512` is block_q x block_k; `--shapes prefill1k`: the other two serve
cells' longest bucket, 16 heads of 1,024 x 128, float32). A call is
timed on the device's own queue: one jitted loop of `--calls` calls, each
fed a few rows of the call before, at two loop lengths, so that the
dispatch and the loop's fixed cost cancel.

Then, at `--stub-blocks`, each kernel again with parts of its block body
stubbed, so that the next writer knows what bounds it:
  no_mask         the causal mask built nowhere (the diagonal's blocks run
                  the full blocks' body; the skip stays)
  products_alone  no softmax arithmetic: the MXU products, the casts that
                  feed them and the accumulators
  copies_alone    no block body at all: the grid's steps and their copies

`--kernels FILE` times another copy of the kernel file beside this tree's
(the parent commit's, say), stubs where it has the functions they replace.

    python tools/flash_block_sweep.py              # on the chip
    JAX_PLATFORMS=cpu python tools/flash_block_sweep.py --rehearse

Prints one JSON line a reading, the plan (`flash_block_plan`) of every
case, and a table at the end; `--out` also writes the lines to a file.
`--rehearse` runs the same code interpreted at a tiny size and prints no
time under a device's name.
"""

import argparse
import contextlib
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as here

SHAPES = {
    "train": dict(bh=64, s=2048, d=128, dv=128, dtype="bfloat16",
                  kernels=("fwd", "dq", "dkv")),
    "kanana": dict(bh=32, s=6144, d=192, dv=128, dtype="float32",
                   kernels=("fwd",)),
    # the Cerebras and OLMoE serve cells' longest prefill bucket
    "prefill1k": dict(bh=16, s=1024, d=128, dv=128, dtype="float32",
                      kernels=("fwd",)),
}
TINY = {
    "train": dict(bh=2, s=256, d=32, dv=32, dtype="bfloat16",
                  kernels=("fwd", "dq", "dkv")),
    "kanana": dict(bh=1, s=256, d=48, dv=32, dtype="float32",
                   kernels=("fwd",)),
    "prefill1k": dict(bh=1, s=128, d=32, dv=32, dtype="float32",
                      kernels=("fwd",)),
}
#: the v5e's bf16 peak (Google Cloud, "TPU v5e"), for the MXU's share
PEAK_FLOPS = 197e12
#: batch-heads `check` holds to the reference (its scores are S x S each)
CHECKED = 4
#: products a block runs: S and P V; S, dP and dS K; S, P^T dO, dP, dS^T Q
PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def emit(out, **fields):
    line = json.dumps(fields)
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.flush()


def load_kernels(path):
    """Another copy of the kernel file, as a sibling module of this
    tree's (its relative imports resolve here)."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.kernels._flash_under_sweep", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_case(shape, seed):
    dtype = jnp.dtype(shape["dtype"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    bh, s, d, dv = shape["bh"], shape["s"], shape["d"], shape["dv"]
    return dict(
        q=jax.random.normal(keys[0], (bh, s, d), jnp.float32).astype(dtype),
        k=jax.random.normal(keys[1], (bh, s, d), jnp.float32).astype(dtype),
        v=jax.random.normal(keys[2], (bh, s, dv), jnp.float32).astype(dtype),
        do=jax.random.normal(keys[3], (bh, s, dv),
                             jnp.float32).astype(dtype))


def parse_blocks(text):
    """"512,1024x512" -> [(512, 512), (1024, 512)]: block_q x block_k."""
    pairs = [b.split("x") for b in text.split(",") if b]
    return [(int(p[0]), int(p[-1])) for p in pairs]


def label(block):
    return str(block[0]) if block[0] == block[1] else "%dx%d" % block


def kernel_calls(fa, block, interpret):
    """name -> (function of the case's arrays giving the kernel's
    outputs, a tuple, and the array a loop feeds them back into). dq
    and dk/dv are the one backward call with the other kernel's outputs
    unused: XLA drops a kernel nobody reads."""
    kw = dict(causal=True, block_q=block[0], block_k=block[1],
              interpret=interpret)

    def scale(c):
        return c["q"].shape[-1] ** -0.5

    def fwd(c):
        return fa._flash_fwd(c["q"], c["k"], c["v"], scale=scale(c), **kw)

    def bwd(c):
        return fa._flash_bwd_pallas(c["q"], c["k"], c["v"], c["o"],
                                    c["lse"], c["do"], scale=scale(c), **kw)

    return {"fwd": (lambda c: fwd(c)[:1], "q"),
            "dq": (lambda c: bwd(c)[:1], "do"),
            "dkv": (lambda c: bwd(c)[1:], "do")}


def seconds_a_call(fn, feeds, case, calls):
    """Device seconds of one call of `fn`: a loop of calls on the
    device's queue, eight rows of each call's result added into the
    next call's `feeds` (in place: the loop carries the array), at
    `calls` and at a quarter of it; the difference over the difference."""
    @jax.jit
    def loop(n, case):
        def step(_, x):
            for got in fn(dict(case, **{feeds: x})):
                w = min(got.shape[-1], x.shape[-1])
                x = x.at[0, :8, :w].add(
                    (1e-6 * got[0, :8, :w]).astype(x.dtype))
            return x
        return jax.lax.fori_loop(0, n, step, case[feeds])

    def run(n):
        loop(n, case).block_until_ready()        # compiled and warm
        best = float("inf")
        for _ in range(3):      # the least of three: a machine moment
            t0 = time.perf_counter()        # only ever adds
            loop(n, case).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    few = max(calls // 4, 1)
    return (run(calls) - run(few)) / max(calls - few, 1)


@contextlib.contextmanager
def stubbed(fa, what):
    """The kernels traced with a part of the block body replaced; False
    where this copy of the file has no such function."""
    new = {
        "no_mask": {"_hide_future": lambda s, *where, **kw: s},
        "products_alone": {
            "_online_softmax": lambda s, *state: (s, 1.0),
            "_probabilities": lambda s, lse: s,
            "_score_grads": lambda p, dp, delta: dp},
        "copies_alone": {"_for_block": lambda *block: None},
    }[what]
    if not all(hasattr(fa, name) for name in new):
        yield False
        return

    def forget():       # the jitted wrappers keep their traces
        for wrapper in (fa._flash_fwd, fa._flash_bwd_pallas):
            getattr(wrapper, "clear_cache", lambda: None)()

    was = {name: getattr(fa, name) for name in new}
    forget()
    for name, fn in new.items():
        setattr(fa, name, fn)
    try:
        yield True
    finally:
        for name, fn in was.items():
            setattr(fa, name, fn)
        forget()


def check(fa, case, block, interpret, out, **tag):
    """Forward and the three gradients of the first batch-heads against
    `mha_reference` on the same inputs in float32 at the highest
    precision: the error's root mean square, and its largest, over the
    reference's root mean square.
    Returns the whole case's (o, lse), the backward kernels' inputs."""
    f32 = {n: a[:CHECKED].astype(jnp.float32)[None].transpose(0, 2, 1, 3)
           for n, a in case.items()}                    # [1, S, BH, D]

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return here.mha_reference(q, k, v, causal=True)

    backward = case["v"].shape[-1] == case["q"].shape[-1]
    if backward:
        want_o, vjp = jax.vjp(ref, f32["q"], f32["k"], f32["v"])
        want = dict(zip(("dq", "dk", "dv"), vjp(f32["do"])), o=want_o)
    else:
        want = dict(o=ref(f32["q"], f32["k"], f32["v"]))
    kw = dict(causal=True, block_q=block[0], block_k=block[1],
              interpret=interpret, scale=case["q"].shape[-1] ** -0.5)
    o, lse = fa._flash_fwd(case["q"], case["k"], case["v"], **kw)
    got = dict(o=o)
    if backward:
        got.update(zip(("dq", "dk", "dv"), fa._flash_bwd_pallas(
            *(a[:CHECKED] for a in (case["q"], case["k"], case["v"], o, lse,
                                    case["do"])), **kw)))
    err = {}
    for name, a in got.items():
        w = want[name][0].transpose(1, 0, 2)
        off = a[:CHECKED].astype(jnp.float32) - w
        rms = jnp.sqrt(jnp.mean(w * w))
        err[name] = float(jnp.sqrt(jnp.mean(off * off)) / rms)
        err[name + "_max"] = float(jnp.max(jnp.abs(off)) / rms)
    emit(out, what="error_over_reference_rms", block=label(block), **tag,
         **err)
    return o, lse


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="train,kanana")
    ap.add_argument("--blocks", default="256,512,1024")
    ap.add_argument("--stub-blocks", default="512")
    ap.add_argument("--kernels", default="",
                    help="another copy of kernels/flash_attention.py to "
                         "time beside this tree's")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        raise SystemExit("the sweep times a TPU; --rehearse runs it here "
                         "interpreted, for its control flow alone")
    shapes = TINY if args.rehearse else SHAPES
    blocks = parse_blocks(args.blocks)
    stubs = parse_blocks(args.stub_blocks)
    if args.rehearse:
        blocks, stubs, args.calls = [(64, 64), (128, 64)], [(64, 64)], 2
    files = {"tree": here}
    if args.kernels:
        files["other"] = load_kernels(args.kernels)
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_us"
    per = 1.0 if args.rehearse else 1e6
    emit(out, what="sweep", device=jax.devices()[0].device_kind,
         platform=platform, calls=args.calls,
         blocks=[label(b) for b in blocks],
         other=args.kernels or None)

    table = []
    for n, name in enumerate(s for s in args.shapes.split(",") if s):
        shape = shapes[name]
        case = make_case(shape, args.seed + n)
        for block in blocks:
            if shape["s"] % block[0] or shape["s"] % block[1]:
                continue
            plan = here.flash_block_plan(shape["s"], shape["s"], *block,
                                         True, shape["dtype"])
            # blocks' worth of products a call runs (a diagonal block in
            # halves runs three quarters of one)
            executed = shape["bh"] * (
                plan.full + plan.diagonal * (0.75 if plan.in_halves else 1))
            emit(out, what="plan", shape=name, **dict(
                plan._asdict(), operand_dtype=plan.operand_dtype.name))
            for tag, fa in files.items():
                o, lse = check(fa, case, block, args.rehearse, out,
                               shape=name, kernels=tag)
                full = dict(case, o=o, lse=lse)
                calls = kernel_calls(fa, block, args.rehearse)
                line = dict(what="kernels", shape=name, block=label(block),
                            kernels=tag, unit=unit)
                for kernel in shape["kernels"]:
                    fn, feeds = calls[kernel]
                    line[kernel] = per * seconds_a_call(
                        fn, feeds, full, args.calls)
                    if not args.rehearse:
                        # the products a block runs, over the time it took
                        flop = PRODUCTS[kernel] * 2 * block[0] * block[1] * (
                            shape["d"] if kernel != "fwd"
                            else (shape["d"] + shape["dv"]) / 2)
                        line[f"{kernel}_us_a_block"] = (
                            line[kernel] / executed)
                        line[f"{kernel}_mxu_share"] = (
                            flop * executed / PEAK_FLOPS
                            / (line[kernel] * 1e-6))
                    if block not in stubs:
                        continue
                    for what in ("no_mask", "products_alone",
                                 "copies_alone"):
                        with stubbed(fa, what) as there:
                            if there:
                                line[f"{kernel}_{what}"] = (
                                    per * seconds_a_call(fn, feeds, full,
                                                         args.calls))
                emit(out, **line)
                table.append(line)
        del case

    kinds = ("fwd", "dq", "dkv")
    print(f"{'shape':>8} {'block':>9} {'kernels':>7} "
          + " ".join(f"{k:>10}" for k in kinds) + f"  ({unit} a call)")
    for r in table:
        print(f"{r['shape']:>8} {r['block']:>9} {r['kernels']:>7} "
              + " ".join(f"{r[k]:>10.4g}" if k in r else f"{'-':>10}"
                         for k in kinds))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
