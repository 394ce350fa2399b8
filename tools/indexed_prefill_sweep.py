"""The indexed prefill's three parts apart on the chip: what an admission
of a model with a sparse-attention indexer (Keye) pays a layer, and for
which part.

At the cell's shape (one sequence, 32 query heads over 4 K/V heads of 128,
16 index heads of 64, top-2,048; float32) and T = 3,072 / 4,096 / 6,144 it
times, each by itself and a LAYER at a time (all the chunks of 512 query
rows `ops.attention_ops._indexed_causal_attention` walks):

    index        the indexer's product: relu(q_i k_i^T) weighted over the
                 index heads, [512, T] scores a chunk (XLA, three passes),
                 of the chunks the function searches (`ops._select_plan`)
    select       what chooses, as the function does: the causal mask for
                 the chunks with nothing to choose, `_selected_mask` of
                 the others' scores (the k-th value by a count search,
                 two compares, a cumsum)
    select_all   `_selected_mask` over EVERY chunk: the search alone
    select_sort  the form it replaced, kept here: `top_k`'s sort of every
                 chunk for its last value (PR 45's `select`)
    attend_dense the OLD attention: a chunk's [32, 512, T] scores, masked,
                 softmaxed and multiplied by V, in XLA inside the loop
    attend_tiles the NEW attention: ONE call of the flash forward over the
                 selection as a mask of one byte a (row, key), a tile a
                 block, at `--blocks` (square, or block_q x block_k); at
                 the first of them also with the scores in one bfloat16
                 pass, which no cell may run
    whole_dense, whole_tiles
                 `_indexed_causal_attention` itself in either form, every
                 row's selection returned as bits, as the export traces it

A part is timed on the device's own queue: one jitted loop of `--calls`
calls, each fed a number of the call before, at two loop lengths, so that
the dispatch and the loop's fixed cost cancel. Every output is summed
whole into that number, so that XLA can narrow no part to the rows a
caller reads: a pass over the output more than the program pays (0.12 ms
for 6,144 rows of 32 x 128), the same for both attentions.

    python tools/indexed_prefill_sweep.py              # on the chip
    JAX_PLATFORMS=cpu python tools/indexed_prefill_sweep.py --rehearse

Prints one JSON line a reading and a table at the end; `--out` also
writes the lines to a file. `--rehearse` runs the same code interpreted at
a tiny size and prints no time under a device's name.
"""

import argparse
import contextlib
import importlib
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from flash_block_sweep import emit, label, parse_blocks

from paddle_tpu.kernels import flash_attention as fa
ops = importlib.import_module("paddle_tpu.ops.attention_ops")

CELL = dict(heads=32, kv_heads=4, head_dim=128, index_heads=16,
            index_dim=64, topk=2048, lengths=(3072, 4096, 6144))
TINY = dict(heads=4, kv_heads=2, head_dim=128, index_heads=4, index_dim=64,
            topk=300, lengths=(768,))


def make_case(shape, t, seed):
    """One layer's operands at T rows, and what the parts hand on: the
    loop's scores [chunks, 1, chunk, T] and its selection [1, T, T]."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, *dims: jax.random.normal(key, dims, jnp.float32)
    case = dict(
        q=normal(keys[0], 1, t, shape["heads"], shape["head_dim"]),
        k=normal(keys[1], 1, t, shape["kv_heads"], shape["head_dim"]),
        v=normal(keys[2], 1, t, shape["kv_heads"], shape["head_dim"]),
        qi=normal(keys[3], 1, t, shape["index_heads"], shape["index_dim"]),
        ki=normal(keys[4], 1, t, shape["index_dim"]),
        w=normal(keys[5], 1, t, shape["index_heads"]))
    case["scores"] = jax.jit(index_scores)(case)[0]
    case["selected"] = jax.jit(
        lambda c: select(c, shape["topk"])[0])(case)
    return case


def sorted_mask(scores, topk):
    """`ops._selected_mask` as it was until PR 49: the k-th value from
    `top_k`'s sort (the new one's oracle, and the parent's reading on
    any later tree)."""
    kth = jax.lax.top_k(scores, topk)[0][..., -1:]
    above = scores > kth
    equal = scores == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def unsearched(t, topk):
    """The chunks at a sequence's head that the function does not
    search."""
    return ops._select_plan(t, starts(t)[1], topk)["chunks_unsearched"]


def starts(t):
    """(every chunk's first row, the rows a chunk), as the loop walks."""
    chunk = math.gcd(t, ops._INDEX_Q_CHUNK)
    return jnp.arange(t // chunk, dtype=jnp.int32) * chunk, chunk


def chunks(x, t):
    """[1, T, ...] -> [chunks, 1, chunk, ...]."""
    chunk = starts(t)[1]
    return jnp.moveaxis(x.reshape((1, t // chunk, chunk) + x.shape[2:]),
                        1, 0)


def whole(x):
    """`chunks`' inverse."""
    return jnp.moveaxis(x, 0, 1).reshape((1, -1) + x.shape[3:])


def visible(start, chunk, t):
    rows = start + jnp.arange(chunk, dtype=jnp.int32)
    return jnp.arange(t, dtype=jnp.int32)[None] <= rows[:, None]


def index_scores(c, first=0):
    """The loop's first part alone, as `_indexed_causal_attention` writes
    it, from chunk `first` on: [chunks, 1, chunk, T] float32, -inf where
    a row may not read."""
    t = c["ki"].shape[1]
    at, chunk = starts(t)

    def one(_, xs):
        start, qic, wc = xs
        dots = jnp.einsum("bqhd,bkd->bhqk", qic, c["ki"],
                          precision=ops._CHOOSING,
                          preferred_element_type=jnp.float32)
        score = jnp.einsum("bqh,bhqk->bqk", wc, jnp.maximum(dots, 0.0),
                           precision=ops._CHOOSING)
        return None, jnp.where(visible(start, chunk, t)[None], score,
                               -jnp.inf)

    return (jax.lax.scan(one, None, (at[first:], chunks(c["qi"], t)[first:],
                                     chunks(c["w"], t)[first:]))[1],)


def select(c, topk, mask_of=None, first=None):
    """The second: the scores given, the selection [1, T, T] int8: the
    causal mask for the `first` chunks (the function's own count unless
    given), `mask_of` (`ops._selected_mask` unless given) of the others'
    scores."""
    t = c["scores"].shape[-1]
    at, chunk = starts(t)
    mask_of = mask_of or ops._selected_mask
    first = unsearched(t, topk) if first is None else first

    def one(_, xs):
        start, score = xs
        mask = mask_of(score, topk) & visible(start, chunk, t)[None]
        return None, mask.astype(jnp.int8)

    head = jax.vmap(lambda start: visible(start, chunk, t)[None])(
        at[:first]).astype(jnp.int8)
    rest = jax.lax.scan(one, None, (at[first:], c["scores"][first:]))[1]
    return (whole(jnp.concatenate([head, rest])),)


def attend_dense(c):
    """The third as it was: the selection given, a chunk's scores whole."""
    b, t, heads, hd = c["q"].shape
    kv = c["k"].shape[2]
    scale = hd ** -0.5
    qg = c["q"].reshape(b, t, kv, heads // kv, hd)

    def one(_, xs):
        qc, mask = xs
        s = jnp.einsum("bqgid,bkgd->bgiqk", qc, c["k"],
                       precision=ops._CHOOSING,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where((mask != 0)[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return None, jnp.einsum("bgiqk,bkgd->bqgid", p, c["v"])

    outs = jax.lax.scan(one, None, (chunks(qg, t),
                                    chunks(c["selected"], t)))[1]
    return (whole(outs).reshape(b, t, heads, hd),)


def attend_tiles(c, block, interpret):
    """The third as it is: one call of the flash forward."""
    return (fa.flash_attention(
        c["q"], c["k"], c["v"], causal=True, block_q=block[0],
        block_k=block[1], interpret=interpret, selected=c["selected"]),)


def whole_layer(c, topk):
    return ops._indexed_causal_attention(
        c["q"], c["k"], c["v"], (c["qi"], c["ki"], c["w"]), topk,
        c["q"].shape[-1] ** -0.5, True)


@contextlib.contextmanager
def form(name, interpret):
    """`_indexed_causal_attention` held to one form of its attention,
    whatever the device (the kernel interpreted off the chip)."""
    was = fa.attention_form, fa.dot_product_attention
    fa.attention_form = lambda *shape: name
    if interpret:
        fa.dot_product_attention = lambda q, k, v, **kw: fa.flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True, **kw)
    try:
        yield
    finally:
        fa.attention_form, fa.dot_product_attention = was


@contextlib.contextmanager
def one_pass():
    """What bounds a block of the new attention: the forward traced with
    its scores in ONE bfloat16 pass (the precision no cell may run)."""
    was = fa._scores_of_choice
    fa._scores_of_choice = lambda q, k, scale: fa._mxu(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), fa._NT) * scale
    fa._flash_fwd.clear_cache()
    try:
        yield
    finally:
        fa._scores_of_choice = was
        fa._flash_fwd.clear_cache()


def seconds_a_call(fn, feeds, case, calls, repeats=3):
    """Device seconds of one call of `fn` (the case -> a tuple of
    arrays): a loop of calls on the device's queue, every output summed
    into one number of the next call's `feeds`, at `calls` and at a
    quarter of it; the difference over the difference."""
    first = (0,) * case[feeds].ndim

    @jax.jit
    def loop(n, case):
        def step(_, x):
            for got in fn(dict(case, **{feeds: x})):
                total = jnp.sum(jnp.where(jnp.isfinite(got), got, 0)
                                if got.dtype == jnp.float32 else got,
                                dtype=jnp.float32)
                x = x.at[first].add((1e-9 * total).astype(x.dtype))
            return x
        return jax.lax.fori_loop(0, n, step, case[feeds])

    def run(n):
        loop(n, case).block_until_ready()        # compiled and warm
        best = float("inf")
        for _ in range(repeats):    # the least: a machine moment only adds
            t0 = time.perf_counter()
            loop(n, case).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    few = max(calls // 4, 1)
    return (run(calls) - run(few)) / max(calls - few, 1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--lengths", default="")
    ap.add_argument("--blocks", default="1024,512,1024x512")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--seed", type=int, default=45)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        raise SystemExit("the sweep times a TPU; --rehearse runs it here "
                         "interpreted, for its control flow alone")
    shape = TINY if args.rehearse else CELL
    blocks = parse_blocks(args.blocks)
    repeats = 1 if args.rehearse else 3
    if args.rehearse:
        blocks, args.calls = [(256, 256), (128, 64)], 2
    lengths = [int(n) for n in args.lengths.split(",") if n] \
        or list(shape["lengths"])
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_us"
    per = 1.0 if args.rehearse else 1e6
    emit(out, what="sweep", device=jax.devices()[0].device_kind,
         platform=platform, calls=args.calls, shape=shape,
         blocks=[label(b) for b in blocks])
    topk = shape["topk"]
    table = []
    for n, t in enumerate(lengths):
        case = make_case(shape, t, args.seed + n)
        # both forms of the whole layer on this device: the same
        # selection bit for bit, the outputs apart by their precision
        with form("masked_dense", args.rehearse):
            dense, bits = jax.jit(lambda c: whole_layer(c, topk))(case)
        with form("flash_selected", args.rehearse):
            tiles, bits_tiles = jax.jit(lambda c: whole_layer(c, topk))(case)
        off = tiles - dense
        by_sort = jax.jit(lambda c: select(c, topk, sorted_mask, 0)[0])(case)
        emit(out, what="tiles_against_dense", rows=t,
             # both forms' bits, and on the same scores the search's
             # selection against the sort's
             selection_equal=bool(
                 jnp.array_equal(bits, bits_tiles)
                 and jnp.array_equal(case["selected"], by_sort)),
             # (the function scores for itself: a last bit of a score
             # fused otherwise would show here and is no fault)
             whole_equals_sort=bool(
                 jnp.array_equal(bits, ops.pack_mask(by_sort != 0))),
             selected_a_row=float(jnp.mean(jnp.sum(
                 case["selected"] != 0, axis=-1))),
             rms=float(jnp.sqrt(jnp.mean(off * off)
                                / jnp.mean(dense * dense))),
             max_abs=float(jnp.max(jnp.abs(off))))
        del dense, tiles, bits, bits_tiles, off, by_sort
        line = dict(what="layer", rows=t, unit=unit)
        timed = {
            "index": (lambda c: index_scores(c, unsearched(t, topk)), "qi"),
            "select": (lambda c: select(c, topk), "scores"),
            "select_all": (lambda c: select(c, topk, first=0), "scores"),
            "select_sort": (lambda c: select(c, topk, sorted_mask, 0),
                            "scores"),
            "attend_dense": (attend_dense, "q"),
        }
        for name, (fn, feeds) in timed.items():
            line[name] = per * seconds_a_call(fn, feeds, case, args.calls,
                                               repeats)
        for block in blocks:
            if t % block[0] or t % block[1]:
                continue
            tiles_fn = lambda c: attend_tiles(c, block, args.rehearse)
            line[f"attend_tiles_{label(block)}"] = per * seconds_a_call(
                tiles_fn, "q", case, args.calls, repeats)
            if block == blocks[0]:
                with one_pass():
                    line[f"attend_tiles_{label(block)}_one_pass"] = \
                        per * seconds_a_call(tiles_fn, "q", case, args.calls,
                                             repeats)
        for name in ("masked_dense", "flash_selected"):
            with form(name, args.rehearse):
                key = "whole_dense" if name == "masked_dense" \
                    else "whole_tiles"
                line[key] = per * seconds_a_call(
                    lambda c: whole_layer(c, topk), "q", case, args.calls,
                    repeats)
        emit(out, **line)
        table.append(line)
        del case
    names = [k for k in table[0] if k not in ("what", "rows", "unit")]
    print(f"({unit} a layer)")
    print(f"{'part':>36} " + " ".join(f"{r['rows']:>10}" for r in table))
    for name in names:
        print(f"{name:>36} " + " ".join(
            f"{r[name]:>10.4g}" if name in r else f"{'-':>10}"
            for r in table))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
