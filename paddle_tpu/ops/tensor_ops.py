"""Tensor creation/manipulation ops.

≙ reference paddle/fluid/operators/{reshape_op, transpose_op, concat_op,
split_op, slice_op, gather_op, scatter_op, pad_op, expand_op, one_hot_op,
cast_op, fill_constant_op, uniform_random_op, gaussian_random_op, assign_op,
lookup_table_op, shape_op, ...}.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op, same_shape
from ..core.types import device_dtype, np_dtype


def _dev_dtype(dtype: str):
    return np_dtype(device_dtype(dtype))


# -- creation ---------------------------------------------------------------

def _fill_infer(op, block):
    out = block.var(op.output("Out")[0])
    out.shape = tuple(op.attrs["shape"])
    out.dtype = op.attrs.get("dtype", "float32")


@register_op("fill_constant", infer_shape=_fill_infer)
def fill_constant(ctx, ins, attrs):
    return {"Out": [jnp.full(tuple(attrs["shape"]), attrs.get("value", 0.0),
                             _dev_dtype(attrs.get("dtype", "float32")))]}


def _fill_bsl_infer(op, block):
    x = block.var(op.input("Input")[0])
    out = block.var(op.output("Out")[0])
    shape = list(op.attrs["shape"])
    in_idx = op.attrs.get("input_dim_idx", 0)
    out_idx = op.attrs.get("output_dim_idx", 0)
    if x.shape:
        shape[out_idx] = x.shape[in_idx]
    out.shape = tuple(shape)
    out.dtype = op.attrs.get("dtype", "float32")


@register_op("fill_constant_batch_size_like", infer_shape=_fill_bsl_infer)
def fill_constant_batch_size_like(ctx, ins, attrs):
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    return {"Out": [jnp.full(tuple(shape), attrs.get("value", 0.0),
                             _dev_dtype(attrs.get("dtype", "float32")))]}


@register_op("uniform_random_batch_size_like", infer_shape=_fill_bsl_infer)
def uniform_random_batch_size_like(ctx, ins, attrs):
    """uniform_random_batch_size_like_op.cc: runtime batch dim from
    Input (build-time -1 resolves here, like fill_constant_batch_size_like)."""
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    key = (jax.random.PRNGKey(attrs["seed"]) if attrs.get("seed", 0)
           else ctx.next_rng_key())
    return {"Out": [jax.random.uniform(
        key, tuple(shape), _dev_dtype(attrs.get("dtype", "float32")),
        attrs.get("min", -1.0), attrs.get("max", 1.0))]}


@register_op("gaussian_random_batch_size_like", infer_shape=_fill_bsl_infer)
def gaussian_random_batch_size_like(ctx, ins, attrs):
    """gaussian_random_batch_size_like_op.cc."""
    x = ins["Input"][0]
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = x.shape[attrs.get("input_dim_idx", 0)]
    key = (jax.random.PRNGKey(attrs["seed"]) if attrs.get("seed", 0)
           else ctx.next_rng_key())
    dt = _dev_dtype(attrs.get("dtype", "float32"))
    out = jax.random.normal(key, tuple(shape), dt)
    return {"Out": [out * attrs.get("std", 1.0) + attrs.get("mean", 0.0)]}


@register_op("fill_zeros_like", infer_shape=same_shape())
def fill_zeros_like(ctx, ins, attrs):
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register_op("uniform_random", infer_shape=_fill_infer)
def uniform_random(ctx, ins, attrs):
    key = (jax.random.PRNGKey(attrs["seed"]) if attrs.get("seed", 0)
           else ctx.next_rng_key())
    return {"Out": [jax.random.uniform(
        key, tuple(attrs["shape"]), _dev_dtype(attrs.get("dtype", "float32")),
        attrs.get("min", -1.0), attrs.get("max", 1.0))]}


@register_op("gaussian_random", infer_shape=_fill_infer)
def gaussian_random(ctx, ins, attrs):
    key = (jax.random.PRNGKey(attrs["seed"]) if attrs.get("seed", 0)
           else ctx.next_rng_key())
    dt = _dev_dtype(attrs.get("dtype", "float32"))
    out = jax.random.normal(key, tuple(attrs["shape"]), dt)
    return {"Out": [out * attrs.get("std", 1.0) + attrs.get("mean", 0.0)]}


@register_op("truncated_gaussian_random", infer_shape=_fill_infer)
def truncated_gaussian_random(ctx, ins, attrs):
    key = (jax.random.PRNGKey(attrs["seed"]) if attrs.get("seed", 0)
           else ctx.next_rng_key())
    dt = _dev_dtype(attrs.get("dtype", "float32"))
    out = jax.random.truncated_normal(key, -2.0, 2.0, tuple(attrs["shape"]), dt)
    return {"Out": [out * attrs.get("std", 1.0) + attrs.get("mean", 0.0)]}


@register_op("assign", infer_shape=same_shape())
def assign(ctx, ins, attrs):
    return {"Out": [ins["X"][0]]}


@register_op("assign_value", infer_shape=_fill_infer)
def assign_value(ctx, ins, attrs):
    vals = np.array(attrs["values"], dtype=_dev_dtype(attrs.get("dtype", "float32")))
    return {"Out": [jnp.asarray(vals).reshape(tuple(attrs["shape"]))]}


@register_op("shape")
def shape_op(ctx, ins, attrs):
    return {"Out": [jnp.asarray(jnp.shape(ins["Input"][0]), jnp.int32)]}


# -- dtype / layout ---------------------------------------------------------

def _cast_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape = x.shape
    out.dtype = op.attrs["out_dtype"]


@register_op("cast", infer_shape=_cast_infer)
def cast(ctx, ins, attrs):
    return {"Out": [ins["X"][0].astype(_dev_dtype(attrs["out_dtype"]))]}


# -- shape manipulation -----------------------------------------------------

def _reshape_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    shape = list(op.attrs["shape"])
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    known = int(np.prod([s for s in shape if s != -1]))
    total = int(np.prod(x.shape)) if x.shape and all(d >= 0 for d in x.shape) else None
    if -1 in shape and total is not None:
        shape[shape.index(-1)] = total // known
    out.shape = tuple(shape)
    out.dtype = x.dtype


@register_op("reshape", infer_shape=_reshape_infer)
def reshape(ctx, ins, attrs):
    x = ins["X"][0]
    shape = list(attrs["shape"])
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    return {"Out": [jnp.reshape(x, tuple(shape))]}


def _transpose_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    perm = op.attrs["axis"]
    out.shape = tuple(x.shape[p] for p in perm) if x.shape else ()
    out.dtype = x.dtype


@register_op("transpose", infer_shape=_transpose_infer)
def transpose(ctx, ins, attrs):
    return {"Out": [jnp.transpose(ins["X"][0], attrs["axis"])]}


def _concat_infer(op, block):
    xs = [block.var(n) for n in op.input("X")]
    out = block.var(op.output("Out")[0])
    axis = op.attrs.get("axis", 0)
    shape = list(xs[0].shape)
    if shape:
        shape[axis] = sum(v.shape[axis] for v in xs)
    out.shape = tuple(shape)
    out.dtype = xs[0].dtype


@register_op("concat", infer_shape=_concat_infer)
def concat(ctx, ins, attrs):
    return {"Out": [jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))]}


def _split_infer(op, block):
    x = block.var(op.input("X")[0])
    axis = op.attrs.get("axis", 0)
    sections = op.attrs.get("sections") or []
    num = op.attrs.get("num", 0)
    outs = [block.var(n) for n in op.output("Out")]
    if not sections and num:
        sections = [x.shape[axis] // num] * num
    for v, s in zip(outs, sections):
        shape = list(x.shape)
        shape[axis] = s
        v.shape, v.dtype = tuple(shape), x.dtype


@register_op("split", infer_shape=_split_infer)
def split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    sections = attrs.get("sections") or []
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        return {"Out": list(jnp.split(x, idx, axis=axis))}
    return {"Out": list(jnp.split(x, attrs["num"], axis=axis))}


def _stack_infer(op, block):
    xs = [block.var(n) for n in op.input("X")]
    out = block.var(op.output("Y")[0])
    axis = op.attrs.get("axis", 0)
    shape = list(xs[0].shape)
    shape.insert(axis if axis >= 0 else axis + len(shape) + 1, len(xs))
    out.shape, out.dtype = tuple(shape), xs[0].dtype


@register_op("stack", infer_shape=_stack_infer)
def stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    n = x.shape[axis]
    return {"Y": [jnp.squeeze(s, axis) for s in jnp.split(x, n, axis)]}


def _squeeze_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    axes = op.attrs.get("axes", [])
    if axes:
        out.shape = tuple(s for i, s in enumerate(x.shape)
                          if i not in [a % len(x.shape) for a in axes])
    else:
        out.shape = tuple(s for s in x.shape if s != 1)
    out.dtype = x.dtype


@register_op("squeeze", infer_shape=_squeeze_infer)
def squeeze(ctx, ins, attrs):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    if not axes:
        return {"Out": [jnp.squeeze(x)]}
    return {"Out": [jnp.squeeze(x, axis=tuple(a % x.ndim for a in axes))]}


def _unsqueeze_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    shape = list(x.shape)
    for a in sorted(op.attrs["axes"]):
        shape.insert(a, 1)
    out.shape, out.dtype = tuple(shape), x.dtype


@register_op("unsqueeze", infer_shape=_unsqueeze_infer)
def unsqueeze(ctx, ins, attrs):
    x = ins["X"][0]
    for a in sorted(attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": [x]}


def _flatten_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    ax = op.attrs.get("axis", 1)
    lead = int(np.prod(x.shape[:ax])) if x.shape else 1
    out.shape = (lead, int(np.prod(x.shape[ax:])))
    out.dtype = x.dtype


@register_op("flatten", infer_shape=_flatten_infer)
def flatten(ctx, ins, attrs):
    x = ins["X"][0]
    ax = attrs.get("axis", 1)
    return {"Out": [jnp.reshape(x, (int(np.prod(x.shape[:ax]) or 1), -1))]}


def _expand_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    times = op.attrs["expand_times"]
    if x.shape and len(x.shape) == len(times):
        out.shape = tuple(d * t if d != -1 else -1
                          for d, t in zip(x.shape, times))
    out.dtype = x.dtype


@register_op("expand", infer_shape=_expand_infer)
def expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register_op("reverse", infer_shape=same_shape())
def reverse(ctx, ins, attrs):
    return {"Out": [jnp.flip(ins["X"][0], axis=tuple(attrs["axis"]))]}


def _pad_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    p = op.attrs["paddings"]
    out.shape = tuple(s + p[2 * i] + p[2 * i + 1] for i, s in enumerate(x.shape))
    out.dtype = x.dtype


@register_op("pad", infer_shape=_pad_infer)
def pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": [jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))]}


@register_op("crop")
def crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs.get("offsets")
    shape = attrs.get("shape")
    return {"Out": [jax.lax.dynamic_slice(x, offsets, shape)]}


def _slice_infer(op, block):
    x = block.var(op.input("Input")[0])
    out = block.var(op.output("Out")[0])
    shape = list(x.shape)
    for ax, st, en in zip(op.attrs["axes"], op.attrs["starts"], op.attrs["ends"]):
        size = x.shape[ax]
        st2 = max(st + size, 0) if st < 0 else min(st, size)
        en2 = max(en + size, 0) if en < 0 else min(en, size)
        shape[ax] = max(en2 - st2, 0)
    out.shape, out.dtype = tuple(shape), x.dtype


@register_op("slice", infer_shape=_slice_infer)
def slice_op(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(attrs["axes"], attrs["starts"], attrs["ends"]):
        idx[ax] = slice(st, en)
    return {"Out": [x[tuple(idx)]]}


# -- gather/scatter/indexing ------------------------------------------------

def _gather_infer(op, block):
    x = block.var(op.input("X")[0])
    idx = block.var(op.input("Index")[0])
    out = block.var(op.output("Out")[0])
    out.shape = tuple(idx.shape[:1]) + tuple(x.shape[1:])
    out.dtype = x.dtype


@register_op("gather", infer_shape=_gather_infer)
def gather(ctx, ins, attrs):
    idx = ins["Index"][0].astype(jnp.int32).reshape(-1)
    return {"Out": [jnp.take(ins["X"][0], idx, axis=0)]}


@register_op("scatter", infer_shape=same_shape())
def scatter(ctx, ins, attrs):
    x, idx, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    idx = idx.astype(jnp.int32).reshape(-1)
    if attrs.get("overwrite", True):
        return {"Out": [x.at[idx].set(upd)]}
    return {"Out": [x.at[idx].add(upd)]}


def _onehot_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    shape = list(x.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    out.shape = tuple(shape) + (op.attrs["depth"],)
    out.dtype = "float32"


@register_op("one_hot", infer_shape=_onehot_infer)
def one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    if x.shape and x.shape[-1] == 1:
        x = x.reshape(x.shape[:-1])
    return {"Out": [jax.nn.one_hot(x.astype(jnp.int32), attrs["depth"])]}


def _lookup_infer(op, block):
    ids = block.var(op.input("Ids")[0])
    w = block.var(op.input("W")[0])
    out = block.var(op.output("Out")[0])
    shape = list(ids.shape)
    if shape and shape[-1] == 1:
        shape = shape[:-1]
    out.shape = tuple(shape) + (w.shape[1],)
    out.dtype = w.dtype


@register_op("lookup_table", infer_shape=_lookup_infer)
def lookup_table(ctx, ins, attrs):
    """lookup_table_op.cc: embedding gather. padding_idx rows read as zero.

    is_sparse=True grads: the autodiff (core/lowering.py) differentiates
    through a zero surrogate added to the gathered rows instead of through
    the table, yielding a RowSparseGrad (≙ SelectedRows grad,
    lookup_table_op.cc's sparse path) whose size is O(n_ids), not O(vocab).
    is_distributed=True is handled at layer level: the table is annotated
    vocab-sharded over the mesh so GSPMD partitions the gather
    (≙ distributed lookup table, distribute_transpiler.py:120-180)."""
    from ..core.selected_rows import squeeze_trailing_ids
    ids, w = ins["Ids"][0], ins["W"][0]
    ids = squeeze_trailing_ids(ids)

    block0 = getattr(ctx, "block_idx", 0) == 0
    probe = getattr(ctx, "sparse_probe", None)
    if probe is not None and attrs.get("is_sparse") and block0:
        probe[ctx.op_index] = ids
    sur = getattr(ctx, "sparse_surrogates", None)
    if (sur is not None and block0 and ctx.op_index in sur
            and attrs.get("is_sparse")):
        out = jnp.take(jax.lax.stop_gradient(w), ids, axis=0) \
            + sur[ctx.op_index]
    else:
        out = jnp.take(w, ids, axis=0)
    if out.dtype == jnp.bfloat16 and getattr(ctx, "amp_dtype", None) is None:
        # a table STORED in bfloat16 under a float32 program (a served
        # bundle's `weight_dtype`): the gathered rows are the residual
        # stream, which stays float32; under AMP the rows run low
        out = out.astype(jnp.float32)
    pidx = attrs.get("padding_idx", -1)
    if pidx is not None and pidx >= 0:
        out = jnp.where((ids == pidx)[..., None], 0.0, out)
    return {"Out": [out]}


@register_op("multiplex")
def multiplex(ctx, ins, attrs):
    ids = ins["Ids"][0].astype(jnp.int32).reshape(-1)
    stacked = jnp.stack(ins["X"], axis=0)
    rows = jnp.arange(stacked.shape[1])
    return {"Out": [stacked[ids, rows]]}


@register_op("where_op", infer_shape=same_shape())
def where_op(ctx, ins, attrs):
    return {"Out": [jnp.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])]}


@register_op("arange", infer_shape=None)
def arange(ctx, ins, attrs):
    return {"Out": [jnp.arange(attrs["start"], attrs["end"], attrs.get("step", 1),
                               dtype=_dev_dtype(attrs.get("dtype", "int32")))]}


@register_op("linspace")
def linspace(ctx, ins, attrs):
    return {"Out": [jnp.linspace(attrs["start"], attrs["stop"], attrs["num"],
                                 dtype=_dev_dtype(attrs.get("dtype", "float32")))]}


@register_op("bilinear_interp")
def bilinear_interp(ctx, ins, attrs):
    """bilinear_interp_op.cc: NCHW resize via jax.image (`method` attr
    also admits "nearest" for layers.image_resize(resample="NEAREST"))."""
    x = ins["X"][0]
    oh = attrs.get("out_h")
    ow = attrs.get("out_w")
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow),
                           method=attrs.get("method", "bilinear"))
    return {"Out": [out]}


@register_op("random_crop")
def random_crop(ctx, ins, attrs):
    x = ins["X"][0]
    shape = attrs["shape"]
    key = ctx.next_rng_key()
    ndim = x.ndim
    crop_dims = len(shape)
    starts = []
    for i, target in enumerate(shape):
        dim = ndim - crop_dims + i
        limit = x.shape[dim] - target
        k = jax.random.fold_in(key, i)
        starts.append(jax.random.randint(k, (), 0, max(limit, 0) + 1))
    full_starts = [jnp.zeros((), jnp.int32)] * (ndim - crop_dims) + starts
    sizes = list(x.shape[:ndim - crop_dims]) + list(shape)
    return {"Out": [jax.lax.dynamic_slice(x, full_starts, sizes)]}


def _pad_constant_like_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, block.var(op.input("Y")[0]).dtype


@register_op("pad_constant_like", infer_shape=_pad_constant_like_infer)
def pad_constant_like(ctx, ins, attrs):
    """pad_constant_like_op.cc: pad Y up to X's (larger) shape with
    pad_value; a shape-driven variant of pad used by seq2seq decoders."""
    x, y = ins["X"][0], ins["Y"][0]
    pads = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return {"Out": [jnp.pad(y, pads,
                            constant_values=attrs.get("pad_value", 0.0))]}


@register_op("split_ids")
def split_ids(ctx, ins, attrs):
    """split_ids_op.cc: route each id to shard id%N (the distributed
    lookup-table dispatcher, distribute_transpiler.py:120-180). The
    reference emits N variable-length LoD outputs; the dense redesign
    keeps each output the full id shape with non-owned slots masked to
    -1 — shard k's lookup gathers only rows it owns, matching the
    vocab-sharded embedding design (docs/distributed_embedding.md)."""
    ids = ins["Ids"][0]
    n = int(attrs["num_shards"])
    outs = [jnp.where(ids % n == k, ids, -1) for k in range(n)]
    return {"Out": outs}


@register_op("merge_ids")
def merge_ids(ctx, ins, attrs):
    """merge_ids_op: inverse of split_ids — merge per-shard embedding rows
    back into the original id order. Ids is the original [N] id tensor;
    Rows is the per-shard stack [num_shards, N, D] where shard k filled
    only the slots it owns (others zero); output [N, D] sums the slots."""
    if len(ins["Rows"]) > 1:
        rows = jnp.stack(ins["Rows"], axis=0)      # N separate [N,D] shards
    elif ins["Rows"][0].ndim == 3:
        rows = ins["Rows"][0]                      # already-stacked [S, N, D]
    else:
        return {"Out": [ins["Rows"][0]]}           # single shard owns all ids
    return {"Out": [rows.sum(axis=0)]}
