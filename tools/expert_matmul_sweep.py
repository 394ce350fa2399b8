"""One expert layer's grouped products alone on the chip, at each MoE
cell's shapes: XLA's kernel (`jax.lax.ragged_dot`) beside the repo's
(`kernels/expert_matmul.py`) at the plan's weight tile and at others.

    olmoe      16 slots x top-8 over 64 experts of 2,048 x 1,024
    kanana     16 x top-6 over 128 of 2,048 x 768
    keye       16 x top-8 over 128 of 2,048 x 768
    cmda       12 x top-8 of 128, experts 0-7 held, 4,096 x 4,096
    lfm2       64 x top-4 over 64 of 2,048 x 1,536
    nemotron3  128 x top-6 of 128, experts 0-31 held, 2,688 x 2,048 up
               (1,856 stored in whole tiles) and 2,048 x 3,072 down
    mellum2    a TRAINED share: 8,192 tokens x top-8 of 64, experts 0-15
               held, 2,304 x 896, bfloat16; one wave of
               `_held_grad_rows` = 18,432 rows, forward, dx and dW

Of each cell the UP product (a gated expert's gate product is the same
shape) and the DOWN product, at the rows of a decode step (slots x top-k
pairs; the pairs on experts that are not held lie behind the groups) and
of a prefill wave (`ops.moe_ops._HELD_WAVE_ROWS` rows of a held share, a
bucket's pairs of a whole layer), and of the cell's shortest bucket where
its pairs are rows the repo's kernel takes (OLMoE's 256 tokens x top-8 =
2,048). A product is timed on the device's own queue: a loop of calls,
each fed a number of the call before, at `--calls` and at a quarter of
it, the difference over the difference. The repo's
kernel holds all its rows in VMEM up to its plan's row bound, and is
timed there at the plan's weight tile and at three others (`tiles_of`: a
float32 tile of at most 4, 2 and 1 MB; `--tiles` names them instead);
over that bound its row-tiled form is timed, at the plan's row tile and
chunk and at the others of `--row-tiles` (`512x256`: tiles of 512 rows
multiplied in chunks of 256), and with `--megablox` the installed JAX's
own example kernels (`gmm`, `tgmm`) beside it, as a yardstick. A trained
cell's products are timed with their two transposes (`/dx`, `/dw`).

    python tools/expert_matmul_sweep.py --out chiprun_out/expert_sweep.jsonl
    JAX_PLATFORMS=cpu python tools/expert_matmul_sweep.py --rehearse

Prints one JSON line a reading and a table at the end (ms a product, GB/s
of the touched groups' weight bytes as stored, the share of the HBM's
819 GB/s, the live rows' operations as a share of the MXUs' 197 TFLOP/s;
`plan`: what `expert_matmul_plan` answers at the shape, `*` on
the row it runs, and `SLOWER n%` there where the other kernel (XLA's, or
the repo's at `_kernel_tile`'s tile) read over 1% under it). `--rehearse`
runs the same code interpreted at a tiny size and prints no time under a
device's name.
"""

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import sparse_walk_sweep as sws

from paddle_tpu.kernels import expert_matmul as em
from paddle_tpu.ops.moe_ops import _HELD_WAVE_ROWS, _held_grad_rows

#: bytes a second of the v5e's HBM and bfloat16 operations a second of
#: its MXUs (`benchmark/peaks.json`)
HBM_BYTES_PER_S = 819e9
MXU_FLOPS_PER_S = 197e12

#: d the model width, h the expert width as stored, d_out the model width
#: the down matrix stores, e the router's experts, held of them here
CELLS = {
    "olmoe": dict(slots=16, top_k=8, e=64, held=64, d=2048, h=1024,
                  d_out=2048, bucket=1024, short=256),
    "kanana": dict(slots=16, top_k=6, e=128, held=128, d=2048, h=768,
                   d_out=2048, bucket=6144),
    "keye": dict(slots=16, top_k=8, e=128, held=128, d=2048, h=768,
                 d_out=2048, bucket=6144),
    "cmda": dict(slots=12, top_k=8, e=128, held=8, d=4096, h=4096,
                 d_out=4096, bucket=6144),
    "lfm2": dict(slots=64, top_k=4, e=64, held=64, d=2048, h=1536,
                 d_out=2048, bucket=6144),
    "nemotron3": dict(slots=128, top_k=6, e=128, held=32, d=2688, h=2048,
                      d_out=3072, bucket=1024),
    "mellum2": dict(top_k=8, e=64, held=16, d=2304, h=896, d_out=2304,
                    train=8192, dtype="bfloat16"),
}
TINY = {
    name: dict(slots=4, top_k=2, e=8, held=4 if c["held"] < c["e"] else 8,
               d=128 if c["d"] % 512 else 512, h=512, d_out=512, bucket=24,
               **({"short": 12} if "short" in c else {}))
    for name, c in CELLS.items() if "train" not in c}
# 4,096 rows: over the resident kernel's bound, as the cell's are
TINY["mellum2"] = dict(top_k=2, e=8, held=4, d=256, h=128, d_out=256,
                       train=2048, dtype="bfloat16")


def routed_sizes(tokens, shape, rows, seed):
    """Rows a held expert receives when `tokens` rows choose `top_k` of
    `e` experts each at random, the first `rows` of them: what
    `_experts_held`'s first wave (or `_experts_sorted`) hands a product."""
    rng = np.random.RandomState(seed)
    picks = np.argsort(rng.rand(tokens, shape["e"]), axis=1)[
        :, :shape["top_k"]].reshape(-1)
    sizes = np.bincount(picks[picks < shape["held"]],
                        minlength=shape["held"])
    return np.diff(np.minimum(np.cumsum(sizes), rows), prepend=0)


def products(shape, wave_rows):
    """(name, rows, k, n, tokens) of the cell's products: up and down, a
    decode step's, a prefill wave's and (a cell that says one) its
    shortest bucket's; of a trained share the one wave of a step, each
    product with its transposes (`/dx`: the cotangent [rows, n] through
    the matrices transposed, `/dw`: the matrices' gradient)."""
    part = shape["held"] < shape["e"]
    if "train" in shape:
        tokens = shape["train"]
        rows = _held_grad_rows(tokens, shape["top_k"], shape["held"],
                               shape["e"])
        phases = [(f"train{t}", rows, tokens) for t in ("", "/dx", "/dw")]
    else:
        step = shape["slots"] * min(shape["top_k"], shape["held"])
        wave = min(shape["bucket"] * min(shape["top_k"], shape["held"]),
                   wave_rows) if part else shape["bucket"] * shape["top_k"]
        phases = [("step", step, shape["slots"]),
                  ("wave", wave, shape["bucket"])]
        if "short" in shape:
            phases.append(("short", shape["short"] * shape["top_k"],
                           shape["short"]))
    for phase, rows, tokens in phases:
        yield f"up/{phase}", rows, shape["d"], shape["h"], tokens
        yield f"down/{phase}", rows, shape["h"], shape["d_out"], tokens


def operands(product, rows, k, n, groups, dtype, key):
    """(a, b) of a product's call `fn(a, b, sizes)`: the rows [rows, k]
    and the matrices [groups, k, n]; of `/dx` the cotangent [rows, n] and
    the matrices; of `/dw` the rows and the cotangent."""
    x = jax.random.normal(key, (rows, k), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n),
                           jnp.float32) * k ** -0.5).astype(dtype)
    dy = jax.random.normal(jax.random.fold_in(key, 2), (rows, n),
                           jnp.float32).astype(dtype)
    return {"dx": (dy, w), "dw": (x, dy)}.get(product.split("/")[-1], (x, w))


def xla_form(product, precision=None):
    """XLA's own kernel for a product: `ragged_dot`, or its transpose in
    the rows (`/dx`) or in the matrices (`/dw`), the cotangent's rows
    behind the groups zeroed first (what `ragged_dot`'s transposes make of
    rows in no group is unspecified on the chip: PERF.md section 6, PR
    62)."""
    def dot(x, w, s):
        return jax.lax.ragged_dot(x, w, s, precision=precision)

    def live(dy, s):
        return jnp.where((jnp.arange(dy.shape[0]) < jnp.sum(s))[:, None],
                         dy, 0)

    def dx(dy, w, s):
        rows = jax.ShapeDtypeStruct((dy.shape[0], w.shape[1]), dy.dtype)
        return jax.linear_transpose(lambda x: dot(x, w, s), rows)(
            live(dy, s))[0]

    def dw(x, dy, s):
        mats = jax.ShapeDtypeStruct(
            (s.shape[0], x.shape[1], dy.shape[1]), x.dtype)
        return jax.linear_transpose(lambda w: dot(x, w, s), mats)(
            live(dy, s))[0]

    return {"dx": dx, "dw": dw}.get(product.split("/")[-1], dot)


def seconds_a_call(fn, x, w, sizes, calls):
    """Device seconds of one `fn(x, w, sizes)`: `sws.seconds_a_call`'s
    difference of two loops, the next call's rows one number of this
    call's result away (an update in place: no pass over the rows)."""
    @jax.jit
    def loop(n, x, w, sizes):
        def body(_, x):
            return x.at[0, 0].add((1e-9 * fn(x, w, sizes).ravel()[0])
                                  .astype(x.dtype))
        return jax.lax.fori_loop(0, n, body, x)

    def run(n):
        loop(n, x, w, sizes).block_until_ready()     # compiled and warm
        t0 = time.perf_counter()
        loop(n, x, w, sizes).block_until_ready()
        return time.perf_counter() - t0

    few = max(calls // 4, 1)
    return (run(calls) - run(few)) / max(calls - few, 1)


def tiles_of(k, n):
    """Three weight tiles of the repo's kernel for a product: the plan's
    own rule (`expert_matmul._kernel_tile`) at a float32 tile of at most
    4, 2 and 1 MB."""
    tiles = []
    for most in (4 << 20, 2 << 20, 1 << 20):
        tile = em._kernel_tile(k, n, 4, most)
        if tile and tile not in tiles:
            tiles.append(tile)
    return tiles


#: (row tile, chunk) of the row-tiled form timed beside the plan's own
ROW_TILES = ((512, 128), (512, 256), (512, 512), (1024, 128), (1024, 256),
             (2048, 128), (2048, 256))


def tiled_forms(product, plan, itemsize, row_tiles, interpret):
    """{label: fn}: the row-tiled form of the product (or of its
    transpose) at the module's own row tile and chunk first
    (`_tiled_rows`), then at the asked ones that divide the rows."""
    kind = product.split("/")[-1]
    own = em._tiled_rows(kind if kind in ("dx", "dw") else "product",
                         plan.rows, plan.k, plan.n, itemsize)
    if own is None:
        return {}
    own = (own, em._TILED_CHUNK)
    forms = {}
    for tm, chunk in [own] + [t for t in row_tiles
                              if plan.rows % t[0] == 0 and t != own]:
        if kind == "dw":
            fn = functools.partial(em._expert_matmul_dw, tm=tm, chunk=chunk,
                                   interpret=interpret)
        else:
            fn = functools.partial(em._expert_matmul_tiled, tm=tm,
                                   chunk=chunk, transposed=kind == "dx",
                                   interpret=interpret)
        forms[f"tiled {tm}/{chunk}"] = fn
    return forms


def megablox_forms(product, plan, interpret):
    """{label: fn}: the installed JAX's example grouped matmuls at two
    tilings, where they import: a yardstick, nothing the repo runs."""
    try:
        import importlib
        mb = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm")
    except ImportError:
        return {}
    kind = product.split("/")[-1]
    forms = {}
    for tiling in ((512, 1024, 1024), (512, 512, 512)) if not interpret \
            else ((32, 128, 128),):
        label = "megablox %dx%dx%d" % tiling
        if kind == "dw":
            forms[label] = lambda x, dy, s, t=tiling: mb.tgmm(
                x.swapaxes(0, 1), dy, s, jnp.float32, t,
                interpret=interpret)
        else:
            forms[label] = lambda x, w, s, t=tiling: mb.gmm(
                x, w, s, x.dtype, t, transpose_rhs=kind == "dx",
                interpret=interpret)
    return forms


def forms_of(product, plan, itemsize, tiles, row_tiles, megablox,
             interpret):
    """{label: fn(a, b, sizes)}: XLA's kernel, then the repo's: up to
    `_ROWS_MAX` rows the resident form at the plan's tile and at the
    asked tiles that divide the product (`tiles_of` where none is asked),
    over them the row-tiled form (`tiled_forms`)."""
    forms = {"xla": xla_form(product)}
    if plan.rows > em._ROWS_MAX:
        forms.update(tiled_forms(product, plan, itemsize, row_tiles,
                                 interpret))
        if megablox:
            forms.update(megablox_forms(product, plan, interpret))
        return forms
    if plan.rows % 8 or plan.k % 128:
        return forms
    fit = [(tk, tn) for tk, tn in tiles or tiles_of(plan.k, plan.n)
           if plan.k % tk == 0 and plan.n % tn == 0]
    if plan.form == "pallas":
        fit = [(plan.tk, plan.tn)] + [t for t in fit
                                      if t != (plan.tk, plan.tn)]
    for tk, tn in fit:
        forms[f"pallas {tk}x{tn}"] = functools.partial(
            em._expert_matmul_pallas, tk=tk, tn=tn, interpret=interpret)
    return forms


def verdict(row, table):
    """`  *` on the row of the kernel the plan runs at the row's shape,
    and how far the OTHER kernel read under it where that is over 1%
    (XLA's against the repo's at the first tile timed, which is
    `_kernel_tile`'s or `_tiled_rows`'): what the plan's rule is held
    to."""
    same = [r for r in table if (r["cell"], r["product"])
            == (row["cell"], row["product"])]
    own = next((r for r in same if r["form"] != "xla"), None)
    xla = next(r for r in same if r["form"] == "xla")
    chosen, other = (xla, own) if row["plan"] == "ragged_dot" \
        else (own, xla)
    if row is not chosen:
        return ""
    if other is None or chosen["a_call"] <= 1.01 * other["a_call"]:
        return "  *"
    return "  * SLOWER %.1f%%" % (
        100 * (chosen["a_call"] / other["a_call"] - 1))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--products", default="up/step,down/step,up/wave,"
                    "down/wave,up/short,down/short,up/train,down/train,"
                    "up/train/dx,down/train/dx,up/train/dw,down/train/dw")
    ap.add_argument("--tiles", default="",
                    help="weight tiles tk x tn of the repo's kernel to "
                         "time beside the plan's, as 896x2048,384x1024 "
                         "(default: three a product, `tiles_of`)")
    ap.add_argument("--row-tiles", default="",
                    help="row tile / chunk of the row-tiled form to time "
                         "beside the module's own, as 512x128,1024x256 "
                         "(default: `ROW_TILES`; `none`: the own alone)")
    ap.add_argument("--megablox", action="store_true",
                    help="time the installed JAX's example grouped "
                         "matmuls beside the row-tiled form")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    platform = jax.devices()[0].platform
    if not args.rehearse and platform != "tpu":
        raise SystemExit("the sweep times a TPU; --rehearse runs it here "
                         "interpreted, for its control flow alone")
    shapes = TINY if args.rehearse else CELLS
    tiles = [tuple(int(v) for v in t.split("x"))
             for t in args.tiles.split(",") if t]
    row_tiles = [tuple(int(v) for v in t.split("x"))
                 for t in args.row_tiles.split(",") if t and t != "none"] \
        or (() if args.row_tiles == "none" else ROW_TILES)
    if args.rehearse:
        args.calls = 2
        row_tiles = ((1024, 128),)
    wave_rows = 32 if args.rehearse else _HELD_WAVE_ROWS
    out = open(args.out, "w") if args.out else None
    unit = "interpreted_s" if args.rehearse else "device_ms"
    per = 1.0 if args.rehearse else 1e3
    sws.emit(out, what="sweep", device=jax.devices()[0].device_kind,
             platform=platform, calls=args.calls)
    table = []
    for c, name in enumerate(n for n in args.cells.split(",") if n):
        shape = shapes[name]
        for p, (product, rows, k, n, tokens) in enumerate(
                products(shape, wave_rows)):
            if product not in args.products.split(","):
                continue
            seed = args.seed + 16 * c + p
            sizes = routed_sizes(tokens, shape, rows, seed)
            dtype = jnp.dtype(shape.get("dtype", "float32"))
            groups = shape["held"]
            a, b = operands(product, rows, k, n, groups, dtype,
                            jax.random.PRNGKey(seed))
            sz = jnp.asarray(sizes, jnp.int32)
            plan = em.expert_matmul_plan(rows, k, n, groups, dtype)
            touched = int((sizes > 0).sum())
            weight_bytes = dtype.itemsize * touched * k * n
            live = int(sizes.sum())
            # the groups' rows of a product or of dx, all of a dW
            mine = slice(None) if product.endswith("/dw") else slice(live)
            exact = jax.jit(xla_form(product, jax.lax.Precision.HIGHEST))(
                a.astype(jnp.float32), b.astype(jnp.float32), sz)
            for label, fn in forms_of(product, plan, dtype.itemsize, tiles,
                                      row_tiles, args.megablox,
                                      args.rehearse).items():
                try:
                    got = jax.jit(fn)(a, b, sz)
                except Exception as e:      # a tile the VMEM cannot hold
                    sws.emit(out, what="refused", cell=name, form=label,
                             product=product, error=str(e)[:300])
                    continue
                line = dict(
                    what="product", cell=name, product=product, rows=rows,
                    live_rows=live, k=k, n=n, groups=groups,
                    touched=touched, plan=plan.form, form=label, unit=unit,
                    xla_tile_bytes=plan.xla_tile_bytes,
                    # against the product at the highest precision
                    max_abs_error=float(jnp.max(jnp.abs(
                        got[mine].astype(jnp.float32) - exact[mine])))
                    if live else 0.0,
                    finite_behind=bool(jnp.all(jnp.isfinite(got))))
                line["a_call"] = per * seconds_a_call(fn, a, b, sz,
                                                      args.calls)
                if not args.rehearse:
                    line["weights_gb_per_s"] = weight_bytes / (
                        line["a_call"] / per) / 1e9
                    line["hbm_share"] = 100 * weight_bytes / (
                        line["a_call"] / per) / HBM_BYTES_PER_S
                    # the live rows' operations against the MXU's peak
                    line["mxu_share"] = 100 * 2 * live * k * n / (
                        line["a_call"] / per) / MXU_FLOPS_PER_S
                sws.emit(out, **line)
                table.append(line)
            del a, b, exact
    print(f"{'cell':>10} {'product':>13} {'rows':>6} {'k':>5} {'n':>5} "
          f"{'touched':>7} {'plan':>10} {'form':>22} {'a call':>9} "
          f"{'GB/s':>6} {'%HBM':>5} {'%MXU':>5}  ({unit})")
    for r in table:
        print(f"{r['cell']:>10} {r['product']:>13} {r['rows']:>6} "
              f"{r['k']:>5} {r['n']:>5} {r['touched']:>7} {r['plan']:>10} "
              f"{r['form']:>22} {r['a_call']:>9.4g} "
              f"{r.get('weights_gb_per_s', 0):>6.0f} "
              f"{r.get('hbm_share', 0):>5.1f} "
              f"{r.get('mxu_share', 0):>5.1f}{verdict(r, table)}")
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
