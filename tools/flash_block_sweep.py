"""The flash kernels alone on the chip: the sweep that sets
`kernels/flash_attention.py` `_default_block`, and what bounds a block.

At the train cell's attention (`cgpt1p3b_train_seq2k`: 64 batch-heads of
2,048 x 128, bfloat16, causal: forward, dq and dk/dv) and at Kanana's
longest prefill bucket (32 heads of 6,144, scores on 192 and values of
128, float32, causal: the forward alone, its backward is XLA's) it times
each kernel by itself over square blocks of 256 / 512 / 1,024 (`--blocks`;
`1024x512` is block_q x block_k; `--shapes prefill1k`: the other two serve
cells' longest bucket, 16 heads of 1,024 x 128, float32;
`mellum_window`: the Mellum 2 train cell's window layers, 32 heads of
8,192 x 128, bfloat16, window 1,024, forward, dq and dk/dv, and
`mellum_full` its full layer; `cmda_window`: a Command A+ prefill chunk
on a window layer, 1,024 rows of 128 heads over 5,120 keys of 8, float32,
window 4,096). `--strips 1,2,4` times each block again with the plan's
`strips` held at each count (`_strips` replaced: the rule that function
holds is read from this axis), where the block's strips are whole lane
tiles. A call is
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
                  (at `mellum_window` on the parent's file: what 49 empty
                  steps a head cost; on this tree's, the band's one)

`--kernels FILE` times another copy of the kernel file beside this tree's
(the parent commit's, say), stubs where it has the functions they replace.

    python tools/flash_block_sweep.py              # on the chip
    JAX_PLATFORMS=cpu python tools/flash_block_sweep.py --rehearse
    JAX_PLATFORMS=cpu python tools/flash_block_sweep.py --rehearse \
        --shapes mellum_window --blocks 256 --stub-blocks 256 --strips 1

Prints one JSON line a reading, the plan (`flash_block_plan`) of every
case, and a table at the end; `--out` also writes the lines to a file.
`--rehearse` runs the same code interpreted at a tiny size and prints no
time under a device's name.
"""

import argparse
import contextlib
import importlib.util
import itertools
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
    # `mellum2_12b_train_seq8k`: three window layers to one full layer
    "mellum_window": dict(bh=32, s=8192, d=128, dv=128, dtype="bfloat16",
                          window=1024, kernels=("fwd", "dq", "dkv")),
    "mellum_full": dict(bh=32, s=8192, d=128, dv=128, dtype="bfloat16",
                        kernels=("fwd", "dq", "dkv")),
    # Command A+'s last chunk of a 5,120-row prompt on a window layer
    "cmda_window": dict(bh=128, kv=8, sq=1024, s=5120, d=128, dv=128,
                        dtype="float32", window=4096, kernels=("fwd",)),
}
TINY = {
    "train": dict(bh=2, s=256, d=32, dv=32, dtype="bfloat16",
                  kernels=("fwd", "dq", "dkv")),
    "kanana": dict(bh=1, s=256, d=48, dv=32, dtype="float32",
                   kernels=("fwd",)),
    "prefill1k": dict(bh=1, s=128, d=32, dv=32, dtype="float32",
                      kernels=("fwd",)),
    "mellum_window": dict(bh=2, s=768, d=32, dv=32, dtype="bfloat16",
                          window=256, kernels=("fwd", "dq", "dkv")),
    "mellum_full": dict(bh=2, s=512, d=32, dv=32, dtype="bfloat16",
                        kernels=("fwd", "dq", "dkv")),
    "cmda_window": dict(bh=4, kv=2, sq=256, s=768, d=32, dv=32,
                        dtype="float32", window=512, kernels=("fwd",)),
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
    sq, kv = shape.get("sq", s), shape.get("kv", bh)
    return dict(
        q=jax.random.normal(keys[0], (bh, sq, d), jnp.float32).astype(dtype),
        k=jax.random.normal(keys[1], (kv, s, d), jnp.float32).astype(dtype),
        v=jax.random.normal(keys[2], (kv, s, dv), jnp.float32).astype(dtype),
        do=jax.random.normal(keys[3], (bh, sq, dv),
                             jnp.float32).astype(dtype))


def parse_blocks(text):
    """"512,1024x512" -> [(512, 512), (1024, 512)]: block_q x block_k."""
    pairs = [b.split("x") for b in text.split(",") if b]
    return [(int(p[0]), int(p[-1])) for p in pairs]


def label(block):
    return str(block[0]) if block[0] == block[1] else "%dx%d" % block


def call_options(block, interpret, window):
    kw = dict(causal=True, block_q=block[0], block_k=block[1],
              interpret=interpret)
    return dict(kw, window=window) if window else kw


def kernel_calls(fa, block, interpret, window=None):
    """name -> (function of the case's arrays giving the kernel's
    outputs, a tuple, and the array a loop feeds them back into). dq
    and dk/dv are the one backward call with the other kernel's outputs
    unused: XLA drops a kernel nobody reads."""
    kw = call_options(block, interpret, window)

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


def units(rehearse):
    """(what a reading is called, readings a second)."""
    return ("interpreted_s", 1.0) if rehearse else ("device_us", 1e6)


def held_strips(count):
    """`_strips` with the sweep's count in the rule's place: `count`
    strips where they are whole lane tiles, else the block whole."""
    return lambda block, *seen: count if block % (128 * count) == 0 else 1


@contextlib.contextmanager
def replaced(fa, new):
    """The kernels traced with these functions of their file replaced;
    False where this copy of the file lacks one of them."""
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


def stubbed(fa, what):
    """The kernels traced with a part of the block body replaced."""
    return replaced(fa, {
        "no_mask": {"_hide_future": lambda s, *where, **kw: s,
                    "_hide_past": lambda s, *where, **kw: s},
        "products_alone": {
            "_online_softmax": lambda s, *state: (s, 1.0),
            "_probabilities": lambda s, lse: s,
            "_score_grads": lambda p, dp, delta: dp},
        "copies_alone": {"_for_block": lambda *block: None},
    }[what])


def check(fa, case, block, interpret, out, window=None, **tag):
    """Forward and the three gradients of the first batch-heads against
    `mha_reference` on the same inputs in float32 at the highest
    precision: the error's root mean square, and its largest, over the
    reference's root mean square.
    Returns the whole case's (o, lse), the backward kernels' inputs."""
    # (the first batch-heads, and the K/V heads they read)
    group = case["q"].shape[0] // case["k"].shape[0]
    heads, kv_heads = CHECKED, max(CHECKED // group, 1)
    f32 = {n: a[:kv_heads if n in "kv" else heads].astype(
        jnp.float32)[None].transpose(0, 2, 1, 3)
        for n, a in case.items()}                       # [1, S, BH, D]

    def ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return here.mha_reference(q, k, v, causal=True, window=window)

    backward = case["v"].shape[-1] == case["q"].shape[-1] and group == 1
    if backward:
        want_o, vjp = jax.vjp(ref, f32["q"], f32["k"], f32["v"])
        want = dict(zip(("dq", "dk", "dv"), vjp(f32["do"])), o=want_o)
    else:
        want = dict(o=ref(f32["q"], f32["k"], f32["v"]))
    kw = dict(call_options(block, interpret, window),
              scale=case["q"].shape[-1] ** -0.5)
    o, lse = fa._flash_fwd(case["q"], case["k"], case["v"], **kw)
    got = dict(o=o)
    if backward:
        got.update(zip(("dq", "dk", "dv"), fa._flash_bwd_pallas(
            *(a[:heads] for a in (case["q"], case["k"], case["v"], o, lse,
                                  case["do"])), **kw)))
    err = {}
    for name, a in got.items():
        w = want[name][0].transpose(1, 0, 2)
        off = a[:heads].astype(jnp.float32) - w
        rms = jnp.sqrt(jnp.mean(w * w))
        err[name] = float(jnp.sqrt(jnp.mean(off * off)) / rms)
        err[name + "_max"] = float(jnp.max(jnp.abs(off)) / rms)
    emit(out, what="error_over_reference_rms", block=label(block), **tag,
         **err)
    return o, lse


def blocks_run(plan):
    """Blocks' worth of products a batch-head's kernel runs, by this
    copy's plan (a file from before the plan counted them: a diagonal
    block in halves runs three quarters of one)."""
    if hasattr(plan, "blocks_run"):
        return plan.blocks_run
    return plan.full + plan.edge + plan.diagonal * (
        0.75 if plan.in_halves else 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="train,kanana")
    ap.add_argument("--blocks", default="",
                    help="256,512,1024 (64,128x64 under --rehearse)")
    ap.add_argument("--strips", default="",
                    help="counts to hold the plan's `strips` at, each "
                         "block again; empty: the plan's own choice alone")
    ap.add_argument("--stub-blocks", default="",
                    help="512 (64 under --rehearse)")
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
    blocks = parse_blocks(args.blocks or (
        "64,128x64" if args.rehearse else "256,512,1024"))
    stubs = parse_blocks(args.stub_blocks or (
        "64" if args.rehearse else "512"))
    strips = [None] + [int(n) for n in args.strips.split(",") if n]
    if args.rehearse:
        args.calls = 2
    files = {"tree": here}
    if args.kernels:
        files["other"] = load_kernels(args.kernels)
    out = open(args.out, "w") if args.out else None
    emit(out, what="sweep", device=jax.devices()[0].device_kind,
         platform=platform, calls=args.calls,
         blocks=[label(b) for b in blocks], strips=strips[1:],
         other=args.kernels or None)

    table = []
    for n, name in enumerate(s for s in args.shapes.split(",") if s):
        shape = shapes[name]
        case = make_case(shape, args.seed + n)
        for block in blocks:
            if shape.get("sq", shape["s"]) % block[0] \
                    or shape["s"] % block[1]:
                continue
            for (tag, fa), held in itertools.product(files.items(), strips):
                with replaced(fa, {} if held is None else {
                        "_strips": held_strips(held)}) as there:
                    if there:
                        time_block(fa, tag, name, shape, case, block, held,
                                   block in stubs and held is None, args,
                                   out, table)
        del case

    kinds = ("fwd", "dq", "dkv")
    print(f"{'shape':>13} {'block':>9} {'strips':>6} {'kernels':>7} "
          + " ".join(f"{k:>10}" for k in kinds)
          + f"  ({units(args.rehearse)[0]} a call)")
    for r in table:
        print(f"{r['shape']:>13} {r['block']:>9} {r['strips']:>6} "
              f"{r['kernels']:>7} "
              + " ".join(f"{r[k]:>10.4g}" if k in r else f"{'-':>10}"
                         for k in kinds))
    if out:
        out.close()
    return 0


def time_block(fa, tag, name, shape, case, block, held, stubs, args, out,
               table):
    """One copy of the kernels at one shape, block and strip count: its
    plans (the forward's; dq's and dk/dv's), its error against the
    reference, each kernel's time and, with `stubs`, the time of each
    stub."""
    sq, window = shape.get("sq", shape["s"]), shape.get("window")
    unit, per = units(args.rehearse)
    seen = (sq, shape["s"], *block, True, shape["dtype"]) + (
        (window,) if window else ())
    plans = {"fwd": fa.flash_block_plan(*seen)}
    if len(shape["kernels"]) > 1:
        try:    # (a file from before the backward had a plan of its own)
            plans["dq"] = fa.flash_block_plan(*seen, backward=True)
        except TypeError:
            plans["dq"] = plans["fwd"]
        plans["dkv"] = plans["dq"]
    which = dict(shape=name, kernels=tag,
                 strips="own" if held is None else held)
    for of in ("fwd", "dq"):
        if of in plans:
            emit(out, what="plan", of=of, held=held, shape=name,
                 kernels=tag, **dict(
                     plans[of]._asdict(),
                     operand_dtype=plans[of].operand_dtype.name))
    o, lse = check(fa, case, block, args.rehearse, out, window, **which)
    full = dict(case, o=o, lse=lse)
    calls = kernel_calls(fa, block, args.rehearse, window)
    line = dict(what="kernels", block=label(block), unit=unit, **which)
    for kernel in shape["kernels"]:
        fn, feeds = calls[kernel]
        line[kernel] = per * seconds_a_call(fn, feeds, full, args.calls)
        if not args.rehearse:
            # the products a block runs, over the time it took
            flop = PRODUCTS[kernel] * 2 * block[0] * block[1] * (
                shape["d"] if kernel != "fwd"
                else (shape["d"] + shape["dv"]) / 2)
            executed = shape["bh"] * blocks_run(plans[kernel])
            line[f"{kernel}_us_a_block"] = line[kernel] / executed
            line[f"{kernel}_mxu_share"] = (
                flop * executed / PEAK_FLOPS / (line[kernel] * 1e-6))
        for what in ("no_mask", "products_alone", "copies_alone") \
                if stubs else ():
            with stubbed(fa, what) as there:
                if there:
                    line[f"{kernel}_{what}"] = per * seconds_a_call(
                        fn, feeds, full, args.calls)
    emit(out, **line)
    table.append(line)


if __name__ == "__main__":
    sys.exit(main())
