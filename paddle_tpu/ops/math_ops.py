"""Dense math ops: elementwise w/ axis broadcast, matmul/mul, reductions.

≙ reference paddle/fluid/operators/elementwise_*_op.* (broadcast rules in
elementwise_op_function.h), matmul_op/mul_op (cuBLAS via operators/math/blas.h),
reduce_*_op, cumsum, arg_max/min, top_k_op.cu, sum_op, scale_op, clip ops.
Every CUDA kernel becomes a jax.numpy/lax expression lowered by XLA onto the
MXU/VPU; no per-dtype kernel registrations are needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op, same_shape


# ---------------------------------------------------------------------------
# Elementwise binary with reference broadcast semantics
# (elementwise_op_function.h: Y's shape must be a contiguous subsequence of
# X's shape beginning at `axis`; axis=-1 means trailing-aligned)
# ---------------------------------------------------------------------------

def harmonize(x, y):
    """Mixed-precision rule: the Y (weight/bias) side follows X's float dtype.

    This is the in-op reading of the reference's fp16 transpiler
    (paddle/contrib/float16/float16_transpiler.py): activations may run in
    bfloat16 while master params stay float32; casts are inserted where the
    dtypes meet, and autodiff casts gradients back to the param dtype.
    """
    xt, yt = jnp.result_type(x), jnp.result_type(y)
    if xt != yt and jnp.issubdtype(xt, jnp.floating) and jnp.issubdtype(yt, jnp.floating):
        y = y.astype(xt)
    return y


def broadcast_y_to_x(x, y, axis: int):
    y = harmonize(x, y)
    xnd, ynd = jnp.ndim(x), jnp.ndim(y)
    if ynd == 0 or xnd == ynd:
        return y
    if axis == -1:
        axis = xnd - ynd
    new_shape = list(jnp.shape(y)) + [1] * (xnd - axis - ynd)
    return jnp.reshape(y, [1] * axis + new_shape)


def _ew_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype


def _register_elementwise(name, fn):
    def compute(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        yb = broadcast_y_to_x(x, y, attrs.get("axis", -1))
        return {"Out": [fn(x, yb)]}
    register_op(name, infer_shape=_ew_infer)(compute)


_register_elementwise("elementwise_add", jnp.add)
_register_elementwise("elementwise_sub", jnp.subtract)
_register_elementwise("elementwise_mul", jnp.multiply)
_register_elementwise("elementwise_div", jnp.divide)
_register_elementwise("elementwise_max", jnp.maximum)
_register_elementwise("elementwise_min", jnp.minimum)
_register_elementwise("elementwise_pow", jnp.power)
_register_elementwise("elementwise_mod", jnp.mod)
_register_elementwise("elementwise_floordiv", jnp.floor_divide)


@register_op("scale", infer_shape=same_shape())
def scale(ctx, ins, attrs):
    """scale_op.cc: Out = scale * (X + bias_after_scale ? 0 : bias) ..."""
    x = ins["X"][0]
    s = attrs.get("scale", 1.0)
    b = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return {"Out": [x * s + b]}
    return {"Out": [(x + b) * s]}


def _sum_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype


@register_op("sum", infer_shape=_sum_infer)
def sum_op(ctx, ins, attrs):
    """sum_op.cc: add N tensors (grad-accumulation workhorse)."""
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


@register_op("sign", infer_shape=same_shape())
def sign(ctx, ins, attrs):
    return {"Out": [jnp.sign(ins["X"][0])]}


@register_op("clip", infer_shape=same_shape())
def clip(ctx, ins, attrs):
    return {"Out": [jnp.clip(ins["X"][0], attrs["min"], attrs["max"])]}


@register_op("clip_by_norm", infer_shape=same_shape())
def clip_by_norm(ctx, ins, attrs):
    """clip_by_norm_op.cc: Out = X * max_norm / max(norm(X), max_norm)."""
    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": [x * (max_norm / jnp.maximum(norm, max_norm))]}


# ---------------------------------------------------------------------------
# matmul / mul
# ---------------------------------------------------------------------------

def _matmul_infer(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.input("Y")[0])
    out = block.var(op.output("Out")[0])
    xs, ys = list(x.shape), list(y.shape)
    if op.attrs.get("transpose_X"):
        xs[-2:] = xs[:-3:-1] if len(xs) >= 2 else xs
    if op.attrs.get("transpose_Y") and len(ys) >= 2:
        ys[-2], ys[-1] = ys[-1], ys[-2]
    if len(xs) >= 2 and len(ys) >= 2:
        batch = xs[:-2] or ys[:-2]
        out.shape = tuple(batch) + (xs[-2], ys[-1])
    out.dtype = x.dtype


@register_op("matmul", infer_shape=_matmul_infer)
def matmul(ctx, ins, attrs):
    """matmul_op.cc with transpose_X/transpose_Y and batched broadcasting.

    The contraction maps straight onto the MXU; alpha folds into the result.
    `precision` ("high" | "highest"; absent: the backend's default): as
    `mul`'s.
    """
    x, y = ins["X"][0], ins["Y"][0]
    y = harmonize(x, y)
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim >= 2 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim >= 2 else y
    out = jnp.matmul(x, y, precision=attrs.get("precision") or None)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


def _mul_infer(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.input("Y")[0])
    out = block.var(op.output("Out")[0])
    xn = op.attrs.get("x_num_col_dims", 1)
    yn = op.attrs.get("y_num_col_dims", 1)
    out.shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    out.dtype = x.dtype


@register_op("mul", infer_shape=_mul_infer)
def mul(ctx, ins, attrs):
    """mul_op.cc: flatten X to 2-D at x_num_col_dims, Y at y_num_col_dims,
    GEMM, then restore leading dims. This is the core of layers.fc.

    When Y is consumed whole (yn == 1, the fc/matmul-weight case) the
    flatten-GEMM-restore collapses to one dot_general contracting X's
    trailing dims — bit-identical results, but WITHOUT the B*S reshape:
    a reshape that merges a (dp, sp)-sharded batch/seq pair forces GSPMD
    to all-gather the full sequence on every matmul (measured on the
    virtual mesh: one [B, S, D] gather per mul before this, none after —
    tests/test_collectives_emitted.py).

    `precision` ("high" | "highest"; absent: the backend's default, on a
    TPU ONE pass over float32 operands rounded to bfloat16): how many
    bfloat16 passes a float32 product takes there, three or six."""
    x, y = ins["X"][0], ins["Y"][0]
    y = harmonize(x, y)
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    precision = attrs.get("precision") or None
    xshape, yshape = x.shape, y.shape
    if yn == 1 and len(xshape) - xn == 1 and xshape[-1] == yshape[0]:
        out = jax.lax.dot_general(
            x, y, (((len(xshape) - 1,), (0,)), ((), ())),
            precision=precision)
        return {"Out": [out]}
    # explicit sizes, no -1: jax.export's shape checks reject inferred dims
    x2 = jnp.reshape(x, (int(np.prod(xshape[:xn]) or 1),
                         int(np.prod(xshape[xn:]) or 1)))
    y2 = jnp.reshape(y, (int(np.prod(yshape[:yn]) or 1),
                         int(np.prod(yshape[yn:]) or 1)))
    out = jnp.matmul(x2, y2, precision=precision)
    return {"Out": [jnp.reshape(out, tuple(xshape[:xn]) + tuple(yshape[yn:]))]}


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------

def _reduce_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    dims = op.attrs.get("dim", [0])
    keep = op.attrs.get("keep_dim", False)
    if op.attrs.get("reduce_all", False):
        out.shape = (1,) if keep else ()
    else:
        dims = [d % len(x.shape) for d in dims] if x.shape else []
        if keep:
            out.shape = tuple(1 if i in dims else s for i, s in enumerate(x.shape))
        else:
            out.shape = tuple(s for i, s in enumerate(x.shape) if i not in dims)
    out.dtype = x.dtype


def _register_reduce(name, fn):
    def compute(ctx, ins, attrs):
        x = ins["X"][0]
        if attrs.get("reduce_all", False):
            axis = None
        else:
            axis = tuple(d % x.ndim for d in attrs.get("dim", [0]))
        return {"Out": [fn(x, axis=axis, keepdims=attrs.get("keep_dim", False))]}
    register_op(name, infer_shape=_reduce_infer)(compute)


_register_reduce("reduce_sum", jnp.sum)
_register_reduce("reduce_mean", jnp.mean)
_register_reduce("reduce_max", jnp.max)
_register_reduce("reduce_min", jnp.min)
_register_reduce("reduce_prod", jnp.prod)


def _mean_infer(op, block):
    out = block.var(op.output("Out")[0])
    out.shape = (1,)
    out.dtype = block.var(op.input("X")[0]).dtype


@register_op("mean", infer_shape=_mean_infer)
def mean(ctx, ins, attrs):
    """mean_op.cc: all-reduce mean to a [1] tensor (the canonical loss head)."""
    return {"Out": [jnp.mean(ins["X"][0]).reshape((1,))]}


@register_op("cumsum", infer_shape=same_shape())
def cumsum(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    if attrs.get("flatten", False):
        x, axis = x.ravel(), 0
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("exclusive", False):
        out = out - x
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    return {"Out": [out]}


def _arg_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    axis = op.attrs.get("axis", -1) % max(len(x.shape), 1)
    out.shape = tuple(s for i, s in enumerate(x.shape) if i != axis)
    out.dtype = "int64"


@register_op("arg_max", infer_shape=_arg_infer)
def arg_max(ctx, ins, attrs):
    return {"Out": [jnp.argmax(ins["X"][0], axis=attrs.get("axis", -1))]}


@register_op("arg_min", infer_shape=_arg_infer)
def arg_min(ctx, ins, attrs):
    return {"Out": [jnp.argmin(ins["X"][0], axis=attrs.get("axis", -1))]}


def _topk_infer(op, block):
    x = block.var(op.input("X")[0])
    k = op.attrs["k"]
    shape = tuple(x.shape[:-1]) + (k,)
    out = block.var(op.output("Out")[0])
    idx = block.var(op.output("Indices")[0])
    out.shape, out.dtype = shape, x.dtype
    idx.shape, idx.dtype = shape, "int64"


@register_op("top_k", infer_shape=_topk_infer)
def top_k(ctx, ins, attrs):
    """top_k_op.cu's heap kernel ≙ lax.top_k (XLA sort-based, MXU-free)."""
    vals, idx = jax.lax.top_k(ins["X"][0], attrs["k"])
    return {"Out": [vals], "Indices": [idx]}


@register_op("accuracy")
def accuracy(ctx, ins, attrs):
    """accuracy_op.cu: fraction of rows whose top-k indices contain the label."""
    idx = ins["Indices"][0]
    label = ins["Label"][0].reshape((-1, 1))
    correct = jnp.any(idx == label, axis=1)
    total = correct.shape[0]
    num_correct = jnp.sum(correct.astype(jnp.int32))
    acc = num_correct.astype(jnp.float32) / float(total)
    return {"Accuracy": [acc.reshape((1,))],
            "Correct": [num_correct.reshape((1,))],
            "Total": [jnp.full((1,), total, jnp.int32)]}


@register_op("iou_similarity")
def iou_similarity(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    area = lambda b: jnp.maximum(b[..., 2] - b[..., 0], 0) * jnp.maximum(b[..., 3] - b[..., 1], 0)
    xi = x[:, None, :]
    yi = y[None, :, :]
    lt = jnp.maximum(xi[..., :2], yi[..., :2])
    rb = jnp.minimum(xi[..., 2:], yi[..., 2:])
    wh = jnp.maximum(rb - lt, 0)
    inter = wh[..., 0] * wh[..., 1]
    union = area(xi) + area(yi) - inter
    return {"Out": [inter / jnp.maximum(union, 1e-10)]}
