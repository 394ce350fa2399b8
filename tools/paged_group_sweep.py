"""The decode kernels of shared K/V heads alone on the chip: one layer
call of `_paged_group_kernel` (full and windowed, plain and packed) and of
the sparse kernel's page walk at five cells' shapes, and what bounds a
block of each.

    cmda_full    12 slots, 128 query heads over 8 K/V heads of 128, f32
                 pages of 16 rows, ragged lengths 3-10 k (Command A+'s
                 full layer)
    cmda_window  the same under a window of 4,096 rows (its three window
                 layers)
    lfm2         64 slots, 32 query heads over 8 K/V heads of 64 stored
                 two to a lane tile, lengths 3-10 k (LFM2's one attention
                 layer)
    keye         16 slots, 32 query heads over 4 K/V heads of 128, top-2,048
                 of 3-7.7 k rows by the page walk (Keye's sparse layer)
    nemotron3    128 slots, 32 query heads over 2 K/V heads of 128, lengths
                 128-3,200 under a table of 320 (Nemotron's attention
                 layers: a K page is 16 KB, 64 of them a block)
    granite4     48 slots, 32 query heads over 8 K/V heads of 64 stored two
                 to a lane tile, the same lengths and table (Granite's)

A block holds 2 MiB of K and V pages whatever the shape, so its pages are
the more the smaller a page is, and with them the scalar work of issuing
its copies (`_paged_walk`: two starts a live page, and a wait a pool for
every set bit of a block's live pages): 16 pages a block at Command A+'s
shape, 32 at LFM2's, Keye's and Granite's, 64 at Nemotron's.

Each call is timed whole, with its arithmetic stubbed (`_sparse_block`
leaves the softmax state as it was: the copies alone) and with its copies
stubbed (the arithmetic alone, over whatever the tiles hold), on the
device's own queue (`sparse_walk_sweep.seconds_a_call`), and divided by
the compute blocks the call walks: a block's period beside its K and V
bytes at the HBM's rate. `--kernels FILE` times another copy of
`kernels/paged_attention.py` beside this tree's (or of a tree from before
PR 46 its `kernels/flash_attention.py`, which held these kernels). `--reads`
also times this tree's block with its group read (`_group_rows`, a strided
read of the tile) replaced by an indexed read of the K/V head's axis
(`_indexed_rows`), the form the strided read was chosen over.

    python tools/paged_group_sweep.py --kernels _checkout/parent/paddle_tpu/kernels/paged_attention.py
    JAX_PLATFORMS=cpu python tools/paged_group_sweep.py --rehearse

Prints one JSON line a reading and a table at the end; `--out` also
writes the lines to a file. `--rehearse` runs the same code interpreted at
a tiny size and prints no time under a device's name.
"""

import argparse
import contextlib
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import sparse_walk_sweep as sws
from flash_block_sweep import load_kernels

from paddle_tpu.kernels import paged_attention as pa

#: bytes a second of the v5e's HBM (`benchmark/peaks.json`)
HBM_BYTES_PER_S = 819e9

CELLS = {
    "cmda_full": dict(slots=12, heads=128, kv_heads=8, head_dim=128,
                      block=16, table=640, lens=(3072, 10240), window=None),
    "cmda_window": dict(slots=12, heads=128, kv_heads=8, head_dim=128,
                        block=16, table=640, lens=(3072, 10240),
                        window=4096),
    "lfm2": dict(slots=64, heads=32, kv_heads=8, head_dim=64, block=16,
                 table=640, lens=(3072, 10240), window=None),
    "keye": dict(slots=16, heads=32, kv_heads=4, head_dim=128, block=16,
                 table=480, lens=(3072, 7680), window=None, topk=2048),
    "nemotron3": dict(slots=128, heads=32, kv_heads=2, head_dim=128,
                      block=16, table=320, lens=(128, 3200), window=None),
    "granite4": dict(slots=48, heads=32, kv_heads=8, head_dim=64, block=16,
                     table=320, lens=(128, 3200), window=None),
}
TINY = {
    "cmda_full": dict(slots=3, heads=16, kv_heads=2, head_dim=128, block=8,
                      table=12, lens=(20, 96), window=None),
    "cmda_window": dict(slots=3, heads=16, kv_heads=2, head_dim=128,
                        block=8, table=12, lens=(20, 96), window=28),
    "lfm2": dict(slots=3, heads=8, kv_heads=4, head_dim=64, block=8,
                 table=12, lens=(20, 96), window=None),
    "keye": dict(slots=3, heads=8, kv_heads=2, head_dim=128, block=8,
                 table=12, lens=(40, 96), window=None, topk=32),
    "nemotron3": dict(slots=3, heads=8, kv_heads=2, head_dim=128, block=8,
                      table=12, lens=(4, 96), window=None),
    "granite4": dict(slots=3, heads=8, kv_heads=4, head_dim=64, block=8,
                     table=12, lens=(4, 96), window=None),
}


def make_case(shape, seed):
    """A cell's call: ragged lengths under its table. The sparse cell's
    is `sparse_walk_sweep.make_case`; a packed pool holds `128 / D` heads
    to a lane tile."""
    lens = np.random.RandomState(seed).randint(
        shape["lens"][0], shape["lens"][1] + 1, shape["slots"])
    if "topk" in shape:
        return sws.make_case(shape, lens, shape["table"], seed)
    d = shape["head_dim"]
    pack = max(128 // d, 1)
    stored = dict(shape, kv_heads=shape["kv_heads"] // pack,
                  head_dim=d * pack)
    case = sws.make_case(dict(stored, topk=1), lens, shape["table"], seed)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (shape["slots"], shape["heads"], d), jnp.float32)
    return dict(q=q, k_pool=case["k_pool"], v_pool=case["v_pool"],
                tables=case["tables"], lens=case["lens"])


def layer_call(kernels, shape, interpret):
    """One layer call as the step makes it: (q, the case's arrays) -> out."""
    if "topk" in shape:
        return lambda q, c: kernels._paged_sparse_attention_pallas(
            q, c["k_pool"], c["v_pool"], c["tables"], c["lens"],
            c["selected"], scale=shape["head_dim"] ** -0.5,
            interpret=interpret)
    return lambda q, c: kernels._paged_attention_pallas(
        q, c["k_pool"], c["v_pool"], c["tables"], c["lens"],
        scale=shape["head_dim"] ** -0.5, interpret=interpret,
        window=shape["window"])


def blocks_walked(kernels, shape, lens):
    """Compute blocks one call walks, and the pages a block holds."""
    bs = shape["block"]
    pack = max(128 // shape["head_dim"], 1)
    pages = kernels.paged_sparse_block_pages(
        bs, shape["kv_heads"] // pack, shape["head_dim"] * pack,
        jnp.float32, shape["table"])
    lens = np.asarray(lens)
    live = -(-lens // bs)
    if shape["window"] is not None:
        live = live - np.maximum(lens - shape["window"], 0) // bs
    return int((-(-live // pages)).sum()), pages


def traced(kernels, what):
    """The kernels of `kernels` traced `whole`, without their
    `arithmetic` or their `copies` (`sparse_walk_sweep.stubbed`), or
    with the group read `indexed` (`_indexed_rows` in `_group_rows`'
    place)."""
    if what == "whole":
        return contextlib.nullcontext()
    if what == "indexed":
        return sws.swapped(kernels, "_group_rows", kernels._indexed_rows,
                           kernels)
    return sws.stubbed(what, kernels)


def check_output(shape, case, fn, out, form):
    """The call's first two slots against the gather-based reference
    (which repeats the K/V heads: 4 GB a slot of 10 k rows at 128 query
    heads; float32 on this device: on the chip that reference multiplies
    in bf16 passes)."""
    got = jax.jit(fn)(case["q"], case)[:2]
    if "topk" in shape:
        want = pa.paged_sparse_attention_reference(
            case["q"][:2], case["k_pool"], case["v_pool"], case["rows"][:2],
            case["counts"][:2])
    else:
        want = pa.paged_attention_reference(
            case["q"][:2], case["k_pool"], case["v_pool"],
            case["tables"][:2], case["lens"][:2], window=shape["window"])
    sws.emit(out, what="max_abs_error_against_reference", form=form,
             error=float(jnp.max(jnp.abs(got - want))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--kernels", default="",
                    help="another copy of kernels/paged_attention.py to "
                         "time beside this tree's")
    ap.add_argument("--reads", action="store_true",
                    help="also time this tree's block with an indexed "
                         "read of a group's rows")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=44)
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
    forms = {"tree": pa}
    if args.kernels:
        forms = {"other": load_kernels(args.kernels), "tree": pa}
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_us"
    per = 1.0 if args.rehearse else 1e6
    sws.emit(out, what="sweep", device=jax.devices()[0].device_kind,
             platform=platform, calls=args.calls,
             other=args.kernels or None)
    table = []
    for n, name in enumerate(c for c in args.cells.split(",") if c):
        shape = shapes[name]
        case = make_case(shape, args.seed + n)
        for form, kernels in forms.items():
            fn = layer_call(kernels, shape, args.rehearse)
            blocks, pages = blocks_walked(kernels, shape, case["lens"])
            block_bytes = 2 * 4 * pages * shape["block"] \
                * shape["kv_heads"] * shape["head_dim"]
            line = dict(what="layer_call", cell=name, form=form, unit=unit,
                        blocks=blocks, pages_per_block=pages,
                        block_hbm_us=1e6 * block_bytes / HBM_BYTES_PER_S)
            for what in ("whole", "arithmetic", "copies") + (
                    ("indexed",) if args.reads and form == "tree" else ()):
                with traced(kernels, what):
                    if what == "whole":
                        check_output(shape, case, fn, out, f"{name}/{form}")
                    key = what if what in ("whole", "indexed") \
                        else f"without_{what}"
                    line[key] = per * sws.seconds_a_call(fn, case,
                                                         args.calls)
            line["block_period"] = line["whole"] / blocks
            sws.emit(out, **line)
            table.append(line)
        del case
    print(f"{'cell':>12} {'form':>6} {'blocks':>7} {'whole':>10} "
          f"{'no arith':>10} {'no copies':>10} {'a block':>9} "
          f"{'HBM us':>7}  ({unit})")
    for r in table:
        print(f"{r['cell']:>12} {r['form']:>6} {r['blocks']:>7} "
              f"{r['whole']:>10.4g} {r['without_arithmetic']:>10.4g} "
              f"{r['without_copies']:>10.4g} {r['block_period']:>9.4g} "
              f"{r['block_hbm_us']:>7.3g}")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
