"""NN ops: activations, softmax, conv/pool/norm, dropout, losses.

≙ reference paddle/fluid/operators/{activation_op.cc, softmax_op, conv_op.cc,
conv_cudnn_op.cu.cc, pool_op, batch_norm_op, layer_norm_op, dropout_op,
cross_entropy_op, softmax_with_cross_entropy_op.cu, ...}. The cuDNN-special
kernels (conv/pool/BN) map to XLA's native convolution/reduce-window HLOs,
which XLA tiles onto the MXU — no library dispatch attr (`use_cudnn`) is
needed; it is accepted and ignored for API parity.
"""

from __future__ import annotations

import functools
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op, same_shape

# ---------------------------------------------------------------------------
# Activations (activation_op.cc registers ~20 via functor templates; here a
# table of jnp lambdas serves the same role)
# ---------------------------------------------------------------------------

_ACTIVATIONS = {
    "relu": lambda x, a: jnp.maximum(x, 0),
    "sigmoid": lambda x, a: jax.nn.sigmoid(x),
    "logsigmoid": lambda x, a: jax.nn.log_sigmoid(x),
    "tanh": lambda x, a: jnp.tanh(x),
    "tanh_shrink": lambda x, a: x - jnp.tanh(x),
    "exp": lambda x, a: jnp.exp(x),
    "log": lambda x, a: jnp.log(x),
    "sqrt": lambda x, a: jnp.sqrt(x),
    "rsqrt": lambda x, a: jax.lax.rsqrt(x),
    "abs": lambda x, a: jnp.abs(x),
    "ceil": lambda x, a: jnp.ceil(x),
    "floor": lambda x, a: jnp.floor(x),
    "round": lambda x, a: jnp.round(x),
    "reciprocal": lambda x, a: 1.0 / x,
    "square": lambda x, a: jnp.square(x),
    "softplus": lambda x, a: jax.nn.softplus(x),
    "softsign": lambda x, a: x / (1 + jnp.abs(x)),
    "sin": lambda x, a: jnp.sin(x),
    "cos": lambda x, a: jnp.cos(x),
    "relu6": lambda x, a: jnp.clip(x, 0, a.get("threshold", 6.0)),
    "leaky_relu": lambda x, a: jnp.where(x >= 0, x, a.get("alpha", 0.02) * x),
    "elu": lambda x, a: jnp.where(x >= 0, x, a.get("alpha", 1.0) * (jnp.exp(x) - 1)),
    "brelu": lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)),
    "soft_relu": lambda x, a: jnp.log1p(jnp.exp(jnp.clip(
        x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))),
    "hard_sigmoid": lambda x, a: jnp.clip(
        a.get("slope", 0.2) * x + a.get("offset", 0.5), 0, 1),
    "thresholded_relu": lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0.0),
    "hard_shrink": lambda x, a: jnp.where(jnp.abs(x) > a.get("threshold", 0.5), x, 0.0),
    "softshrink": lambda x, a: jnp.sign(x) * jnp.maximum(
        jnp.abs(x) - a.get("lambda", 0.5), 0.0),
    "swish": lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x),
    "gelu": lambda x, a: jax.nn.gelu(x, approximate=a.get("approximate", False)),
    "pow": lambda x, a: jnp.power(x, a.get("factor", 1.0)),
    "stanh": lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(a.get("scale_a", 0.67) * x),
}


def _make_activation(name, fn):
    def compute(ctx, ins, attrs):
        return {"Out": [fn(ins["X"][0], attrs)]}
    register_op(name, infer_shape=same_shape())(compute)


for _n, _f in _ACTIVATIONS.items():
    _make_activation(_n, _f)


@register_op("prelu", infer_shape=same_shape())
def prelu(ctx, ins, attrs):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "element":
        alpha = alpha.reshape((1,) + x.shape[1:])
    return {"Out": [jnp.where(x >= 0, x, alpha * x)]}


@register_op("softmax", infer_shape=same_shape())
def softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1))]}


@register_op("log_softmax", infer_shape=same_shape())
def log_softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1))]}


def _maxout_infer(op, block):
    x = block.var(op.input("X")[0])
    g = op.attrs["groups"]
    out = block.var(op.output("Out")[0])
    out.shape = (x.shape[0], x.shape[1] // g) + tuple(x.shape[2:])
    out.dtype = x.dtype


@register_op("maxout", infer_shape=_maxout_infer)
def maxout(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs["groups"]
    n, c = x.shape[0], x.shape[1]
    return {"Out": [jnp.max(x.reshape((n, c // g, g) + x.shape[2:]), axis=2)]}


@register_op("dropout", infer_shape=same_shape())
def dropout(ctx, ins, attrs):
    """dropout_op.cc (upscale-in-train OFF in this reference era: outputs are
    scaled by (1-p) at test time? No — reference uses 'downgrade_in_infer':
    train: mask only; infer: scale by (1-p))."""
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    if attrs.get("is_test", False):
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    key = ctx.next_rng_key()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    return {"Out": [x * mask], "Mask": [mask]}


# ---------------------------------------------------------------------------
# Convolution / pooling  (NCHW layout, matching the reference's default)
# ---------------------------------------------------------------------------

def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _conv_out_dim(size, k, pad, stride, dil=1):
    return (size + 2 * pad - (dil * (k - 1) + 1)) // stride + 1


def _conv2d_infer(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    out = block.var(op.output("Output")[0])
    s, p, d = (_pair(op.attrs.get(k, v)) for k, v in
               (("strides", 1), ("paddings", 0), ("dilations", 1)))
    n, _, h, wd = x.shape
    oc, _, kh, kw = w.shape
    out.shape = (n, oc, _conv_out_dim(h, kh, p[0], s[0], d[0]),
                 _conv_out_dim(wd, kw, p[1], s[1], d[1]))
    out.dtype = x.dtype


def _harmonize_w(x, w):
    from .math_ops import harmonize
    return harmonize(x, w)


def _dense_expand_grouped(w, groups):
    """[C_out, Cg, kh, kw] grouped filter -> [C_out, C_in, kh, kw] dense
    with zeros off the block diagonal, via a constant one-hot placement
    einsum (AD routes dW straight back to the grouped filter and the
    zeros contribute nothing)."""
    c_out, cg = int(w.shape[0]), int(w.shape[1])
    c_in = cg * groups
    out_per_group = c_out // groups
    place = np.zeros((c_out, cg, c_in), np.float32)
    for o in range(c_out):
        base = (o // out_per_group) * cg
        place[o, np.arange(cg), base + np.arange(cg)] = 1
    return jnp.einsum("ocyx,oci->oiyx", w,
                      jnp.asarray(place, w.dtype))


def _gconv_prefers_dense(x, w, groups, stride=(1, 1), padding=None,
                         dilation=(1, 1)) -> bool:
    """Formulation choice for grouped convs: XLA's native grouped lowering
    vs a dense conv over block-diagonal-expanded weights (the dense detour
    pays Cg->C_in flops inflation but keeps the MXU's lanes full where
    tiny groups would idle them).

    Decided by MEASUREMENT, not a rule (VERDICT r4 next #4): the executor
    pre-tunes every grouped conv shape before first compile
    (utils/gconv_autotune.py — per-shape fwd+bwd shootout memoized on
    disk, keyed by device kind); here at trace time the cache can only be
    read. An untuned shape (CPU tests, PT_GCONV_TUNE=0) takes the native
    path. PT_GCONV_DENSE=always|never remains the override."""
    cg = int(w.shape[1])
    # malformed configs (c_out not divisible by groups, mismatched c_in)
    # must keep the native path so XLA raises its loud shape error
    # instead of a silently wrong block placement
    if int(w.shape[0]) % groups or int(x.shape[1]) != cg * groups:
        return False
    mode = os.environ.get("PT_GCONV_DENSE", "auto")
    if mode in ("0", "never"):
        return False
    if mode in ("1", "always"):
        return True
    from ..utils import gconv_autotune as _gt
    key = _gt.shape_key(int(x.shape[0]), int(x.shape[1]),
                        int(x.shape[2]), int(x.shape[3]),
                        int(w.shape[0]), int(groups),
                        (int(stride[0]), int(stride[1])),
                        str(x.dtype), int(w.shape[2]),
                        padding=padding, dilation=dilation)
    hit = _gt.lookup(key)
    return bool(hit) if hit is not None else False


def _gconv_dense_layout(x, w, groups, stride=(1, 1), padding=None,
                        dilation=(1, 1)) -> str:
    """Weight layout for the DENSE grouped-conv formulation: 'oihw'
    (operand as stored) or 'hwio' (pre-transposed before the conv — the
    layout hint changes which tiling XLA's layout assignment hands the
    MXU; measured as a second autotuned dimension of the same gconv
    shootout). PT_GCONV_LAYOUT=oihw|hwio pins it; untuned shapes keep
    the stored layout."""
    mode = os.environ.get("PT_GCONV_LAYOUT", "auto")
    if mode in ("oihw", "hwio"):
        return mode
    from ..utils import gconv_autotune as _gt
    key = _gt.shape_key(int(x.shape[0]), int(x.shape[1]),
                        int(x.shape[2]), int(x.shape[3]),
                        int(w.shape[0]), int(groups),
                        (int(stride[0]), int(stride[1])),
                        str(x.dtype), int(w.shape[2]),
                        padding=padding, dilation=dilation)
    return _gt.lookup_layout(key) or "oihw"


def _conv2d(x, w, attrs, feature_group_count=None):
    w = _harmonize_w(x, w)
    s = _pair(attrs.get("strides", 1))
    p = _pair(attrs.get("paddings", 0))
    d = _pair(attrs.get("dilations", 1))
    groups = feature_group_count or attrs.get("groups", 1) or 1
    dn = ("NCHW", "OIHW", "NCHW")
    if groups > 1 and groups < x.shape[1] \
            and _gconv_prefers_dense(x, w, groups, stride=s, padding=p,
                                     dilation=d):
        layout = _gconv_dense_layout(x, w, groups, stride=s, padding=p,
                                     dilation=d)
        w = _dense_expand_grouped(w, groups)
        if layout == "hwio":
            w = jnp.transpose(w, (2, 3, 1, 0))
            dn = ("NCHW", "HWIO", "NCHW")
        groups = 1
    # NOTE: no preferred_element_type upcast — the MXU accumulates bf16
    # operands in fp32 internally, and jax 0.9's conv transpose rule cannot
    # transpose a dtype-upcasting conv.
    return jax.lax.conv_general_dilated(
        x, w, window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d, dimension_numbers=dn,
        feature_group_count=groups)


@register_op("conv2d", infer_shape=_conv2d_infer)
def conv2d(ctx, ins, attrs):
    """conv_op.cc / conv_cudnn_op.cu.cc → XLA conv_general_dilated (MXU)."""
    return {"Output": [_conv2d(ins["Input"][0], ins["Filter"][0], attrs)]}


@register_op("depthwise_conv2d", infer_shape=_conv2d_infer)
def depthwise_conv2d(ctx, ins, attrs):
    """operators/math/depthwise_conv.cu → grouped XLA conv."""
    x, w = ins["Input"][0], ins["Filter"][0]
    return {"Output": [_conv2d(x, w, attrs, feature_group_count=x.shape[1])]}


def _conv2d_transpose_infer(op, block):
    x = block.var(op.input("Input")[0])
    w = block.var(op.input("Filter")[0])
    out = block.var(op.output("Output")[0])
    s, p, d = (_pair(op.attrs.get(k, v)) for k, v in
               (("strides", 1), ("paddings", 0), ("dilations", 1)))
    n, _, h, wd = x.shape
    _, oc, kh, kw = w.shape
    oh = (h - 1) * s[0] - 2 * p[0] + d[0] * (kh - 1) + 1
    ow = (wd - 1) * s[1] - 2 * p[1] + d[1] * (kw - 1) + 1
    out.shape = (n, oc * (op.attrs.get("groups", 1) or 1), oh, ow)
    out.dtype = x.dtype


@register_op("conv2d_transpose", infer_shape=_conv2d_transpose_infer)
def conv2d_transpose(ctx, ins, attrs):
    """conv_transpose_op.cc → gradient-style dilated conv (IOHW filter).
    Grouped transpose runs per-group channel blocks (the flipped-kernel
    trick cannot express groups via feature_group_count)."""
    x, w = ins["Input"][0], ins["Filter"][0]
    w = _harmonize_w(x, w)
    s = _pair(attrs.get("strides", 1))
    p = _pair(attrs.get("paddings", 0))
    d = _pair(attrs.get("dilations", 1))
    kh, kw = w.shape[2], w.shape[3]
    pad_h = d[0] * (kh - 1) - p[0]
    pad_w = d[1] * (kw - 1) - p[1]
    g = attrs.get("groups", 1) or 1

    def one(xg, wg):
        return jax.lax.conv_general_dilated(
            xg, jnp.flip(wg, (2, 3)), window_strides=(1, 1),
            padding=[(pad_h, pad_h), (pad_w, pad_w)], lhs_dilation=s,
            rhs_dilation=d, dimension_numbers=("NCHW", "IOHW", "NCHW"))

    if g == 1:
        return {"Output": [one(x, w)]}
    cin = x.shape[1] // g
    outs = [one(x[:, i * cin:(i + 1) * cin], w[i * cin:(i + 1) * cin])
            for i in range(g)]
    return {"Output": [jnp.concatenate(outs, axis=1)]}


def _pool2d_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    if op.attrs.get("global_pooling", False):
        out.shape = tuple(x.shape[:2]) + (1, 1)
    else:
        k = _pair(op.attrs["ksize"])
        s = _pair(op.attrs.get("strides", 1))
        p = _pair(op.attrs.get("paddings", 0))
        n, c, h, w = x.shape
        if op.attrs.get("ceil_mode", False):
            oh = -(-(h + 2 * p[0] - k[0]) // s[0]) + 1
            ow = -(-(w + 2 * p[1] - k[1]) // s[1]) + 1
        else:
            oh = (h + 2 * p[0] - k[0]) // s[0] + 1
            ow = (w + 2 * p[1] - k[1]) // s[1] + 1
        out.shape = (n, c, oh, ow)
    out.dtype = x.dtype


@register_op("pool2d", infer_shape=_pool2d_infer)
def pool2d(ctx, ins, attrs):
    """pool_op.cc → XLA reduce_window (max) / avg via sum+count."""
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        if ptype == "max":
            return {"Out": [jnp.max(x, axis=(2, 3), keepdims=True)]}
        return {"Out": [jnp.mean(x, axis=(2, 3), keepdims=True)]}
    k = _pair(attrs["ksize"])
    s = _pair(attrs.get("strides", 1))
    p = _pair(attrs.get("paddings", 0))
    dims = (1, 1) + k
    strides = (1, 1) + s
    pads = ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, pads)
    else:
        ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads)
        if attrs.get("exclusive", True):
            ones = jnp.ones(x.shape[2:], x.dtype)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, k, s,
                                        ((p[0], p[0]), (p[1], p[1])))
            out = ssum / cnt
        else:
            out = ssum / (k[0] * k[1])
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def _bn_infer(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.output("Y")[0])
    y.shape, y.dtype = x.shape, x.dtype
    c = x.shape[1] if len(x.shape) > 1 else x.shape[0]
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if op.output(slot):
            v = block.var(op.output(slot)[0])
            v.shape, v.dtype = (c,), "float32"


def _bn_apply(x, mean, inv, scale, bias):
    """The normalize-scale-shift pass, kept byte-identical between forward
    and the backward's recompute (the ReLU mask must see the same y)."""
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    return (x - mean.reshape(bshape).astype(x.dtype)) * \
        (inv * scale).reshape(bshape).astype(x.dtype) + \
        bias.reshape(bshape).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bn_train(x, scale, bias, mean_in, var_in, eps, momentum, relu):
    """Training-mode batch norm with a memory-lean hand-written VJP.

    JAX's default AD of the naive formulation keeps the FLOAT32 cast of
    the whole activation (and the normalized x-hat) alive from forward to
    backward — for ResNet-50 bs128 that is gigabytes of extra HBM traffic
    per step (the round-3 control measured 44 GB moved vs a ~15 GB
    analytic floor). This VJP saves only the bf16 conv output plus two
    per-channel vectors and recomputes x-hat (elementwise, fuses into the
    backward reduces). `relu` additionally folds the activation into the
    same op (≙ the reference batch_norm op's fuse_with_relu attr,
    batch_norm_op.cc); the mask is recomputed from the residuals, never
    stored."""
    out, _ = _bn_train_fwd(x, scale, bias, mean_in, var_in, eps, momentum,
                           relu)
    return out


def _bn_train_stats(x, eps):
    axes = tuple(i for i in range(x.ndim) if i != 1)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    return mean, var, inv


def _bn_train_fwd(x, scale, bias, mean_in, var_in, eps, momentum, relu):
    mean, var, inv = _bn_train_stats(x, eps)
    new_mean = momentum * mean_in + (1 - momentum) * mean
    new_var = momentum * var_in + (1 - momentum) * var
    y = _bn_apply(x, mean, inv, scale, bias)
    if relu:
        y = jnp.maximum(y, 0)
    out = (y, new_mean, new_var, mean, var)
    return out, (x, scale, bias, mean, inv)


def _bn_train_bwd(eps, momentum, relu, res, cts):
    x, scale, bias, mean, inv = res
    gy, g_new_mean, g_new_var, g_saved_mean, g_saved_var = cts
    axes = tuple(i for i in range(x.ndim) if i != 1)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    m = 1
    for i in axes:
        m *= x.shape[i]
    if relu:
        y = _bn_apply(x, mean, inv, scale, bias)
        gy = jnp.where(y > 0, gy, jnp.zeros_like(gy))
    gyf = gy.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xhat = (xf - mean.reshape(bshape)) * inv.reshape(bshape)
    dbeta = jnp.sum(gyf, axis=axes)
    dgamma = jnp.sum(gyf * xhat, axis=axes)
    sf = scale.astype(jnp.float32)
    dx = (sf * inv).reshape(bshape) * (
        gyf - (dbeta / m).reshape(bshape) - xhat * (dgamma / m).reshape(bshape))
    # direct cotangents on the emitted batch statistics (zero in normal
    # training — MeanOut/SavedMean feed state, not the loss — but custom_vjp
    # must be exact for any caller): d mean/dx = 1/m, d var/dx = 2(x-mu)/m
    g_mean_tot = (1 - momentum) * g_new_mean + g_saved_mean
    g_var_tot = (1 - momentum) * g_new_var + g_saved_var
    dx = dx + (g_mean_tot / m).reshape(bshape) \
        + (xf - mean.reshape(bshape)) * (2.0 * g_var_tot / m).reshape(bshape)
    return (dx.astype(x.dtype), dgamma.astype(scale.dtype),
            dbeta.astype(bias.dtype), momentum * g_new_mean,
            momentum * g_new_var)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register_op("batch_norm", infer_shape=_bn_infer)
def batch_norm(ctx, ins, attrs):
    """batch_norm_op.cc/.cu. NCHW; running stats are persistable state vars
    threaded functionally (MeanOut/VarianceOut rebind the same names, exactly
    like the reference's in-place variable reuse). Training mode routes
    through the memory-lean custom-VJP kernel (see _bn_train; disable with
    PT_BN_PLAIN_VJP=1 for A/B measurement); fuse_with_relu folds the
    activation in (≙ the reference attr of the same name)."""
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    relu = bool(attrs.get("fuse_with_relu", False))
    axes = tuple(i for i in range(x.ndim) if i != 1)
    bshape = (1, -1) + (1,) * (x.ndim - 2)

    if is_test or attrs.get("use_global_stats", False):
        inv = jax.lax.rsqrt(var_in + eps)
        y = _bn_apply(x, mean_in, inv, scale, bias)
        if relu:
            y = jnp.maximum(y, 0)
        return {"Y": [y], "MeanOut": [mean_in], "VarianceOut": [var_in],
                "SavedMean": [mean_in], "SavedVariance": [var_in]}
    if os.environ.get("PT_BN_PLAIN_VJP"):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=axes)
        var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
        new_mean = momentum * mean_in + (1 - momentum) * mean
        new_var = momentum * var_in + (1 - momentum) * var
        inv = jax.lax.rsqrt(var + eps)
        y = _bn_apply(x, mean, inv, scale, bias)
        if relu:
            y = jnp.maximum(y, 0)
        return {"Y": [y], "MeanOut": [new_mean], "VarianceOut": [new_var],
                "SavedMean": [mean], "SavedVariance": [var]}
    y, new_mean, new_var, mean, var = _bn_train(
        x, scale, bias, mean_in, var_in, eps, momentum, relu)
    return {"Y": [y], "MeanOut": [new_mean], "VarianceOut": [new_var],
            "SavedMean": [mean], "SavedVariance": [var]}


def _ln_infer(op, block):
    x = block.var(op.input("X")[0])
    y = block.var(op.output("Y")[0])
    y.shape, y.dtype = x.shape, x.dtype
    ba = op.attrs.get("begin_norm_axis", 1)
    rows = int(np.prod(x.shape[:ba])) if x.shape else 1
    for slot in ("Mean", "Variance"):
        if op.output(slot):
            v = block.var(op.output(slot)[0])
            v.shape, v.dtype = (rows,), "float32"


@register_op("layer_norm", infer_shape=_ln_infer)
def layer_norm(ctx, ins, attrs):
    """layer_norm_op.cc: normalize over dims >= begin_norm_axis."""
    x = ins["X"][0]
    ba = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(ba, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape((1,) * ba + x.shape[ba:])
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape((1,) * ba + x.shape[ba:])
    return {"Y": [y], "Mean": [mean.reshape(-1)], "Variance": [var.reshape(-1)]}


@register_op("rms_norm", infer_shape=same_shape("X", "Y"))
def rms_norm(ctx, ins, attrs):
    """Root-mean-square norm over dims >= begin_norm_axis (Zhang &
    Sennrich 2019, as Llama/OLMo use it): x / sqrt(mean(x^2) + eps) * g.
    No mean is subtracted and there is no bias. The statistics are
    float32 whatever the input's dtype."""
    x = ins["X"][0]
    ba = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(ba, x.ndim))
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    y = (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape((1,) * ba + x.shape[ba:])
    return {"Y": [y]}


@register_op("l2_normalize", infer_shape=same_shape())
def l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    out = x / jnp.maximum(norm, eps)
    return {"Out": [out], "Norm": [norm]}


@register_op("lrn", infer_shape=same_shape())
def lrn(ctx, ins, attrs):
    """lrn_op.cc: local response normalization across channels (AlexNet)."""
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    win = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * win
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def _xent_infer(op, block):
    in_slot = "X" if op.type == "cross_entropy" else "Logits"
    x = block.var(op.input(in_slot)[0])
    out = block.var(op.output("Y" if op.type == "cross_entropy" else "Loss")[0])
    out.shape = tuple(x.shape[:-1]) + (1,)
    out.dtype = x.dtype


@register_op("cross_entropy", infer_shape=_xent_infer)
def cross_entropy(ctx, ins, attrs):
    """cross_entropy_op.cc: takes probabilities (post-softmax). Hard labels
    (int index, soft_label=False) or soft distributions."""
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1:] == (1,) else label
        p = jnp.take_along_axis(x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(p, eps))
    return {"Y": [loss]}


@jax.custom_vjp
def _softmax_xent_hard(logits, lbl):
    """Numerically-stable hard-label softmax cross-entropy with a
    memory-lean hand-written VJP.

    Default AD of log_softmax keeps an f32 copy of the FULL logits (and
    builds dlogits through a scatter-add into another full f32 array) —
    at 32k tokens x 32k vocab that is 2 x 3.9 GB of HLO temps, the
    allocations that OOM'd the long_context_32k config on a 16 GB chip.
    This VJP saves only the bf16 logits (alive anyway as the projection
    output) + the [*, 1] logsumexp, and computes
    dlogits = (softmax - onehot) * g with the onehot expressed as an
    iota==label compare (fuses; no scatter, no f32 temp)."""
    loss, _ = _softmax_xent_hard_fwd(logits, lbl)
    return loss


def _softmax_xent_hard_fwd(logits, lbl):
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(lf, lbl[..., None].astype(jnp.int32),
                                 axis=-1)
    return lse - picked, (logits, lbl, lse)


def _softmax_xent_hard_bwd(res, g):
    logits, lbl, lse = res
    lf = logits.astype(jnp.float32)
    p = jnp.exp(lf - lse)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
              == lbl[..., None].astype(jnp.int32))
    dl = (p - onehot.astype(jnp.float32)) * g
    return (dl.astype(logits.dtype),
            np.zeros(lbl.shape, jax.dtypes.float0))


_softmax_xent_hard.defvjp(_softmax_xent_hard_fwd, _softmax_xent_hard_bwd)


@register_op("softmax_with_cross_entropy", infer_shape=_xent_infer)
def softmax_with_cross_entropy(ctx, ins, attrs):
    """softmax_with_cross_entropy_op.cu: numerically-stable fused version.
    Hard labels route through the memory-lean custom VJP (see
    _softmax_xent_hard; PT_XENT_PLAIN=1 restores default AD for A/B)."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
        return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}
    lbl = label.reshape(label.shape[:-1]) if label.shape[-1:] == (1,) \
        else label
    if os.environ.get("PT_XENT_PLAIN"):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32),
                                    axis=-1)
        return {"Loss": [loss], "Softmax": [jnp.exp(logp)]}
    loss = _softmax_xent_hard(logits, lbl)
    # the Softmax side-output is DCE'd when unused; stop_gradient keeps it
    # off the AD path so consuming it costs fwd memory only
    soft = jax.lax.stop_gradient(
        jax.nn.softmax(logits.astype(jnp.float32), axis=-1))
    return {"Loss": [loss], "Softmax": [soft]}


@register_op("sigmoid_cross_entropy_with_logits", infer_shape=same_shape())
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": [loss]}


@register_op("square_error_cost", infer_shape=same_shape())
def square_error_cost(ctx, ins, attrs):
    """squared_l2_distance flavor used by fit_a_line: (X - Y)^2."""
    return {"Out": [jnp.square(ins["X"][0] - ins["Y"][0])]}


@register_op("smooth_l1_loss")
def smooth_l1_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    sigma2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight"):
        diff = diff * ins["InsideWeight"][0]
    abs_diff = jnp.abs(diff)
    val = jnp.where(abs_diff < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(diff),
                    abs_diff - 0.5 / sigma2)
    if ins.get("OutsideWeight"):
        val = val * ins["OutsideWeight"][0]
    out = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [diff]}


@register_op("huber_loss")
def huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    out = jnp.where(ar <= delta, 0.5 * jnp.square(r), delta * (ar - 0.5 * delta))
    return {"Out": [out], "Residual": [r]}


@register_op("hinge_loss", infer_shape=same_shape("Logits"))
def hinge_loss(ctx, ins, attrs):
    logits, labels = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": [jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0)]}


@register_op("log_loss", infer_shape=same_shape("Predicted", "Loss"))
def log_loss(ctx, ins, attrs):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1 - label) * jnp.log(1 - p + eps)
    return {"Loss": [loss]}


@register_op("rank_loss")
def rank_loss(ctx, ins, attrs):
    label, left, right = ins["Label"][0], ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": [jnp.log1p(jnp.exp(d)) - label * d]}


@register_op("squared_l2_norm")
def squared_l2_norm(ctx, ins, attrs):
    return {"Out": [jnp.sum(jnp.square(ins["X"][0])).reshape((1,))]}


@register_op("squared_l2_distance")
def squared_l2_distance(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sub = x - y
    out = 0.5 * jnp.sum(jnp.square(sub).reshape(sub.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "sub_result": [sub]}


@register_op("mse_loss", infer_shape=same_shape("X", "Out"))
def mse_loss(ctx, ins, attrs):
    return {"Out": [jnp.square(ins["X"][0] - ins["Label"][0])]}


@register_op("label_smooth", infer_shape=same_shape())
def label_smooth(ctx, ins, attrs):
    """label_smooth_op.cc: (1-eps)*label + eps/K."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.1)
    k = x.shape[-1]
    if ins.get("PriorDist"):
        return {"Out": [(1 - eps) * x + eps * ins["PriorDist"][0]]}
    return {"Out": [(1 - eps) * x + eps / k]}


@register_op("auc")
def auc(ctx, ins, attrs):
    """auc_op.cc: trapezoidal AUC over a uniform threshold grid (per batch)."""
    pred = ins["Predict"][0]
    label = ins["Label"][0].reshape(-1)
    n_th = attrs.get("num_thresholds", 200)
    pos_score = pred[:, 1] if pred.ndim == 2 and pred.shape[1] >= 2 else pred.reshape(-1)
    th = jnp.linspace(0.0, 1.0, n_th)
    is_pos = (label > 0)[None, :]
    above = pos_score[None, :] >= th[:, None]
    tp = jnp.sum(above & is_pos, axis=1).astype(jnp.float32)
    fp = jnp.sum(above & ~is_pos, axis=1).astype(jnp.float32)
    P = jnp.maximum(jnp.sum(is_pos), 1).astype(jnp.float32)
    N = jnp.maximum(jnp.sum(~is_pos), 1).astype(jnp.float32)
    tpr = tp / P
    fpr = fp / N
    auc_val = -jnp.trapezoid(tpr, fpr)
    return {"AUC": [auc_val.reshape((1,))]}
