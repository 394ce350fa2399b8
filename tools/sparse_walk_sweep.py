"""The sparse attention kernel's two walks beside each other on the chip:
the sweep that fixes `kappa` (`kernels/paged_attention.py`
`_SPARSE_PAGE_ROW_COPIES`, the row copies a whole page costs), and what
bounds each walk.

For each context length (rows a slot, every slot alike, top-2,048 of them
selected by random scores) it times one layer call of the ROW walk (the
selected rows one 2 KB copy each) and of the PAGE walk (the slot's live
pages whole, the selection a mask) at the Keye cell's shape: 16 slots, 32
query heads over 4 K/V heads of 128, f32 pools of 16-token pages. A call
is timed on the device's own queue: one jitted loop of `--calls` calls,
each query taken from the call before, at two loop lengths, so that the
dispatch and the loop's fixed cost cancel.

Then, at `--stub-contexts`, each walk with its arithmetic stubbed (the
copies alone) and with its copies stubbed (the arithmetic alone, over
whatever the tiles hold), so that the next writer knows which of the two
bounds it; an empty call of each walk (every slot the other walk's: what
the two-call form costs a step); the cell's own ragged lengths under its
480-page table through `paged_sparse_attention` as the step calls it; and
the selection's mask against a scatter of its positions on the chip, ties
and both zeros at the k-th place included.

    python tools/sparse_walk_sweep.py              # on the chip
    JAX_PLATFORMS=cpu python tools/sparse_walk_sweep.py --rehearse

Prints one JSON line a reading and a table at the end; `--out` also
writes the lines to a file. `--rehearse` runs the same code interpreted at
a tiny size and prints no time under a device's name.
"""

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import paged_attention as pa

CELL = dict(slots=16, heads=32, kv_heads=4, head_dim=128, block=16,
            topk=2048, table=480, lens=(3072, 7680))
TINY = dict(slots=4, heads=8, kv_heads=2, head_dim=128, block=8, topk=32,
            table=12, lens=(40, 96))


def emit(out, **fields):
    line = json.dumps(fields)
    print(line, flush=True)
    if out:
        out.write(line + "\n")
        out.flush()


def make_case(shape, lens, table_width, seed):
    """Pools, a block table of pages in no order, random index scores
    and what `sparse_select` makes of them."""
    s_n, bs = shape["slots"], shape["block"]
    lens = np.asarray(lens, np.int32)
    rng = np.random.RandomState(seed)
    n_blocks = int((-(-lens // bs)).sum()) + 1
    tables = np.zeros((s_n, table_width), np.int32)
    free = rng.permutation(np.arange(1, n_blocks)).astype(np.int32)
    at = 0
    for s, n in enumerate(-(-lens // bs)):
        tables[s, :n] = free[at:at + n]
        at += n
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = (n_blocks, bs, shape["kv_heads"], shape["head_dim"])
    k_pool = jax.random.normal(keys[0], pool, jnp.float32)
    v_pool = jax.random.normal(keys[1], pool, jnp.float32)
    q = jax.random.normal(keys[2], (s_n, shape["heads"], shape["head_dim"]),
                          jnp.float32)
    scores = jax.random.normal(keys[3], (s_n, table_width * bs), jnp.float32)
    scores = jnp.where(jnp.arange(table_width * bs)[None] < lens[:, None],
                       scores, -jnp.inf)
    lens, tables = jnp.asarray(lens), jnp.asarray(tables)
    positions, rows, counts, selected = jax.jit(
        pa.sparse_select, static_argnames=("topk", "block_size"))(
            scores, tables, lens, topk=shape["topk"], block_size=bs)
    return dict(q=q, k_pool=k_pool, v_pool=v_pool, tables=tables, lens=lens,
                rows=rows, counts=counts, selected=selected,
                positions=positions, scores=scores)


def walks(interpret):
    """The calls to time: name -> function of (q, the case's arrays)."""
    call = pa._paged_sparse_attention_pallas

    def kw(c):
        return dict(scale=c["q"].shape[-1] ** -0.5, interpret=interpret)

    return {
        "rows": lambda q, c: call(q, c["k_pool"], c["v_pool"], c["rows"],
                                  c["counts"], **kw(c)),
        "pages": lambda q, c: call(q, c["k_pool"], c["v_pool"], c["tables"],
                                   c["lens"], c["selected"], **kw(c)),
        "rows_empty": lambda q, c: call(
            q, c["k_pool"], c["v_pool"], c["rows"],
            jnp.zeros_like(c["counts"]), **kw(c)),
        "pages_empty": lambda q, c: call(
            q, c["k_pool"], c["v_pool"], c["tables"],
            jnp.zeros_like(c["lens"]), c["selected"], **kw(c)),
        "both": lambda q, c: pa.paged_sparse_attention(
            q, c["k_pool"], c["v_pool"], c["rows"], c["counts"],
            pages=(c["tables"], c["lens"], c["selected"]),
            interpret=interpret),
    }


def seconds_a_call(fn, case, calls):
    """Device seconds of one call of `fn`: a loop of calls on the
    device's queue, each query from the call before, at `calls` and at a
    quarter of it; the difference over the difference. The case's
    arrays are arguments: a pool is gigabytes, no constant."""
    @jax.jit
    def loop(n, case):
        q = case["q"]
        return jax.lax.fori_loop(
            0, n, lambda _, x: q + 1e-6 * fn(x, case).astype(q.dtype), q)

    def run(n):
        loop(n, case).block_until_ready()        # compiled and warm
        t0 = time.perf_counter()
        loop(n, case).block_until_ready()
        return time.perf_counter() - t0

    few = max(calls // 4, 1)
    return (run(calls) - run(few)) / max(calls - few, 1)


@contextlib.contextmanager
def swapped(owner, name, new, kernels=pa):
    """`owner.name` replaced by `new` while the paged kernels of
    `kernels` (a copy of `kernels/paged_attention.py`) are traced, their
    jitted wrappers' caches cleared on both sides of it."""
    def clear():
        kernels._paged_attention_pallas.clear_cache()
        kernels._paged_sparse_attention_pallas.clear_cache()

    clear()
    was = getattr(owner, name)
    setattr(owner, name, new)
    try:
        yield
    finally:
        setattr(owner, name, was)
        clear()


def stubbed(what, kernels=pa):
    """The kernels of shared K/V heads traced without their `arithmetic`
    (a block leaves the softmax state as it was) or without their
    `copies` (no copy is started or waited on)."""
    if what == "arithmetic":    # (q, K tile, V tile, admitted, state)
        return swapped(kernels, "_sparse_block", lambda *a, **kw: a[4],
                       kernels)

    class NoCopy:
        def start(self): pass
        def wait(self): pass
    return swapped(kernels.pltpu, "make_async_copy",
                   lambda *a, **kw: NoCopy(), kernels)


def check_mask(shape, out):
    """`sparse_select`'s mask against a scatter of its positions, where
    the scores tie at the k-th place and hold both zeros."""
    s_n, bs, topk = shape["slots"], shape["block"], shape["topk"]
    width = shape["table"] * bs
    rng = np.random.RandomState(5)
    lens = rng.randint(1, width + 1, s_n).astype(np.int32)
    lens[0], lens[1] = width, min(topk, width)
    scores = np.round(rng.randn(s_n, width), 1).astype(np.float32)
    scores[scores == 0.0] = 0.0
    scores[:, ::7] *= -1.0                       # -0.0 beside 0.0
    scores = np.where(np.arange(width)[None] < lens[:, None], scores,
                      -np.inf)
    tables = np.tile(np.arange(1, shape["table"] + 1, dtype=np.int32),
                     (s_n, 1))
    positions, _, counts, selected = (np.asarray(a) for a in jax.jit(
        pa.sparse_select, static_argnames=("topk", "block_size"))(
            scores, tables, lens, topk=topk, block_size=bs))
    wrong, shown = 0, []
    for s in range(s_n):
        want = np.zeros(width, bool)
        want[positions[s, :counts[s]]] = True
        off = np.nonzero(want != selected[s])[0]
        wrong += len(off)
        shown += [dict(slot=s, position=int(p), score=float(scores[s, p]),
                       negative_zero=bool(np.signbit(scores[s, p])
                                          and scores[s, p] == 0),
                       in_positions=bool(want[p]),
                       last_score=float(scores[s, positions[s, counts[s] - 1]]))
                  for p in off[:3]]
    emit(out, what="mask_against_positions", slots=s_n, width=width,
         positions_off=wrong, examples=shown[:8])
    return wrong


def check_outputs(case, fns, out):
    """Both walks against the definition in float64 on the host."""
    k = np.asarray(case["k_pool"], np.float64)
    v = np.asarray(case["v_pool"], np.float64)
    k = k.reshape(-1, *k.shape[2:])
    v = v.reshape(-1, *v.shape[2:])
    q = np.asarray(case["q"], np.float64)
    rows, counts = np.asarray(case["rows"]), np.asarray(case["counts"])
    group = q.shape[1] // k.shape[1]
    want = np.zeros(q.shape)
    for s in range(q.shape[0]):
        if not counts[s]:
            continue
        for h in range(q.shape[1]):
            ks = k[rows[s, :counts[s]], h // group]
            sc = ks @ q[s, h] * q.shape[-1] ** -0.5
            p = np.exp(sc - sc.max())
            want[s, h] = (p / p.sum()) @ v[rows[s, :counts[s]], h // group]
    err = {name: float(np.max(np.abs(np.asarray(
        jax.jit(fns[name])(case["q"], case), np.float64) - want)))
           for name in ("rows", "pages", "both")}
    emit(out, what="max_abs_error_against_float64", **err)
    return err


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", default="2048,4096,8192,16384,32768")
    ap.add_argument("--stub-contexts", default="4096,32768")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=34)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        raise SystemExit("the sweep times a TPU; --rehearse runs it here "
                         "interpreted, for its control flow alone")
    shape = TINY if args.rehearse else CELL
    contexts = [int(c) for c in args.contexts.split(",") if c]
    stubs = [int(c) for c in args.stub_contexts.split(",") if c]
    if args.rehearse:
        contexts, stubs, args.calls = [24, 96], [96], 2
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_us"
    per = 1.0 if args.rehearse else 1e6
    s_n, bs, topk = shape["slots"], shape["block"], shape["topk"]
    emit(out, what="sweep", device=jax.devices()[0].device_kind,
         platform=platform, shape=shape, calls=args.calls,
         kappa=pa._SPARSE_PAGE_ROW_COPIES,
         pages_per_block=pa.paged_sparse_block_pages(
             bs, shape["kv_heads"], shape["head_dim"], jnp.float32,
             shape["table"]),
         chunk_rows=pa._SPARSE_CHUNK_ROWS)

    bad = check_mask(shape, out)
    fns = walks(args.rehearse)
    table = []
    for n, ctx in enumerate(contexts):
        case = make_case(shape, [ctx] * s_n, -(-ctx // bs), args.seed + n)
        if n == 0:
            check_outputs(case, fns, out)
        read = {name: per * seconds_a_call(fns[name], case, args.calls)
                for name in ("rows", "pages")}
        pages, sel = -(-ctx // bs), min(ctx, topk)
        line = dict(what="walks", context=ctx, pages_a_slot=pages,
                    selected_a_slot=sel, unit=unit, **read,
                    rule_takes=("pages" if bool(pa.sparse_walks_pages(
                        np.asarray([ctx]), topk=topk, block_size=bs)[0])
                        else "rows"))
        if ctx in stubs:
            for what in ("arithmetic", "copies"):
                with stubbed(what):
                    for name in ("rows", "pages"):
                        line[f"{name}_without_{what}"] = per * seconds_a_call(
                            fns[name], case, args.calls)
        emit(out, **line)
        table.append(line)
        del case

    # the cell's own call: ragged lengths under its table, both walks as
    # the step calls them, and what a walk with no slot costs
    rng = np.random.RandomState(args.seed)
    lens = rng.randint(shape["lens"][0], shape["lens"][1] + 1, s_n)
    case = make_case(shape, lens, shape["table"], args.seed + 100)
    emit(out, what="cell_call", lens=[int(x) for x in lens], unit=unit,
         **{name: per * seconds_a_call(fn, case, args.calls)
            for name, fn in fns.items()})

    # kappa: a page's cost over a row's, from the two ends of the sweep
    # (the fixed part of a call falls out of a difference)
    if len(table) >= 2:
        lo, hi = table[0], table[-1]
        page = (hi["pages"] - lo["pages"]) / (
            s_n * (hi["pages_a_slot"] - lo["pages_a_slot"]))
        row = hi["rows"] / (s_n * hi["selected_a_slot"])
        emit(out, what="kappa", unit=unit, a_page=page, a_row=row,
             page_over_row=page / row, in_the_code=pa._SPARSE_PAGE_ROW_COPIES)
    print(f"{'context':>8} {'pages':>6} {'rows walk':>12} {'page walk':>12} "
          f"{'cheaper':>8} {'rule':>6}  ({unit})")
    for r in table:
        print(f"{r['context']:>8} {r['pages_a_slot']:>6} {r['rows']:>12.4g} "
              f"{r['pages']:>12.4g} "
              f"{'pages' if r['pages'] < r['rows'] else 'rows':>8} "
              f"{r['rule_takes']:>6}")
    if out:
        out.close()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
