"""Fused-block ops: the tuned-kernel tier above the generic op library.

≙ the reference's cuDNN tier (conv_cudnn_op.cu.cc — algorithm selection and
workspace tuning sitting above the im2col/math path) and its fusion passes
(fuse_elewise_add_act etc.): on TPU the equivalent lever is cross-op fusion
that XLA cannot perform because convolutions are HLO materialization
boundaries. See kernels/fused_block.py for the kernel design.

The `fused_bottleneck` op is semantically a conv1x1+BN+relu, conv3x3+BN+relu,
conv1x1+BN, +residual, relu chain (a stride-1 ResNet "rest" bottleneck) with
all three BNs in training mode.  On a single TPU device it lowers to the
Pallas chain; anywhere else (CPU tests, sharded meshes where GSPMD must
partition the program) it lowers to the same composition the individual ops
would have produced, so semantics — including running-stat updates and the
memory-lean BN VJP — are identical everywhere.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .nn_ops import _bn_train, _conv2d, _conv2d_infer


def _fused_block_enabled(ctx) -> bool:
    mode = os.environ.get("PT_FUSED_BLOCK", "auto")
    if mode in ("0", "never"):
        return False
    if ctx is not None and getattr(ctx, "mesh", None) is not None:
        # GSPMD cannot partition an opaque Pallas call; sharded programs
        # take the composition path (same math, partitionable HLO)
        return False
    if mode in ("1", "always"):
        try:
            return jax.default_backend() == "tpu"
        except Exception:  # pragma: no cover - backend probing never fatal
            return False
    # auto currently lowers to the composition: the round-5 A/B measured
    # the Pallas chain at 60.8 ms/batch vs 50.9 for XLA's op-by-op on the
    # full ResNet-50 step (P1 at 2.3x its traffic floor, 9-roll tap cost
    # in K2/B2, lane padding on the 14²/28² stages). Flip to the kernel
    # path per-shape once it wins its A/B — PT_FUSED_BLOCK=always forces
    # it for measurement.
    return False


def _conv(h, w, pad):
    return jax.lax.conv_general_dilated(
        h, w, (1, 1), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _compose_block(x, w1, w2, w3, bn_params, eps, momentum):
    """The unfused reference composition (identical math to emitting the
    individual conv2d/batch_norm/elementwise_add ops, memory-lean BN VJP
    included) — the fallback and the semantic definition of the op."""
    conv = _conv
    (g1, b1, m1, v1), (g2, b2, m2, v2), (g3, b3, m3, v3) = bn_params
    a1 = conv(x, w1, 0)
    h1, nm1, nv1, sm1, sv1 = _bn_train(a1, g1, b1, m1, v1, eps, momentum,
                                       True)
    a2 = conv(h1, w2, 1)
    h2, nm2, nv2, sm2, sv2 = _bn_train(a2, g2, b2, m2, v2, eps, momentum,
                                       True)
    a3 = conv(h2, w3, 0)
    h3, nm3, nv3, sm3, sv3 = _bn_train(a3, g3, b3, m3, v3, eps, momentum,
                                       False)
    out = jnp.maximum(h3 + x, 0)
    return out, (nm1, nv1, sm1, sv1, nm2, nv2, sm2, sv2, nm3, nv3, sm3, sv3)


def _fused_conv2d_infer(op, block):
    _conv2d_infer(op, block)              # same Input/Filter/Output slots
    out = block.var(op.output("Output")[0])
    c = out.shape[1]
    for slot in ("MeanOut", "VarianceOut", "SavedMean", "SavedVariance"):
        if op.output(slot):
            v = block.var(op.output(slot)[0])
            v.shape, v.dtype = (c,), "float32"


@register_op("fused_conv2d", infer_shape=_fused_conv2d_infer)
def fused_conv2d(ctx, ins, attrs):
    """conv2d + batch_norm (+ elementwise_add) (+ relu) as ONE op — what
    analysis/fuse.py rewrites eligible chains into.  The conv itself is
    the same lowering as the standalone conv2d op (ops/nn_ops._conv2d,
    gconv formulation/layout machinery included); the difference is the
    EPILOGUE:

    * inference (is_test / use_global_stats): the BN is folded into the
      conv weights and bias (w' = w·γ·rsqrt(v+eps) per output channel,
      b' = β − m·γ·rsqrt(v+eps)) — the add/activation ride the same
      expression, stats pass through untouched;
    * training: batch stats + normalize + scale/shift (+add) (+relu) as
      a conv epilogue — the memory-lean _bn_train custom VJP (identical
      math and residuals to the unfused batch_norm op) or, when the
      measured per-shape gate says so, the Pallas epilogue kernels in
      kernels/fused_conv.py (same quintuple contract, own custom VJP).

    Running-stat rebinding (MeanOut/VarianceOut keep the BN's var names)
    and saved-stat outputs are exactly the unfused batch_norm's, so the
    fusion pass never changes state threading."""
    x, w = ins["Input"][0], ins["Filter"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    addend = ins["Addend"][0] if ins.get("Addend") else None
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    relu = attrs.get("act", "") == "relu"

    if attrs.get("is_test", False) or attrs.get("use_global_stats", False):
        inv = jax.lax.rsqrt(var_in + eps)
        s = (scale * inv).astype(jnp.float32)
        wf = (w.astype(jnp.float32) * s.reshape(-1, 1, 1, 1)).astype(w.dtype)
        bias_f = (bias - mean_in * scale * inv).reshape(1, -1, 1, 1) \
            .astype(x.dtype)
        y = _conv2d(x, wf, attrs) + bias_f
        if addend is not None:
            y = y + addend
        if relu:
            y = jnp.maximum(y, 0)
        return {"Output": [y], "MeanOut": [mean_in],
                "VarianceOut": [var_in], "SavedMean": [mean_in],
                "SavedVariance": [var_in]}

    a = _conv2d(x, w, attrs)
    from ..kernels import fused_conv as _fc
    n, c, hh, ww = a.shape
    if _fc.epilogue_enabled(ctx, int(n), int(c), int(hh), int(ww),
                            str(a.dtype), relu=relu,
                            with_add=addend is not None):
        y, nm, nv, sm, sv = _fc.fused_conv_epilogue(
            a, scale, bias, mean_in, var_in, addend, eps, momentum, relu)
    elif addend is None:
        y, nm, nv, sm, sv = _bn_train(a, scale, bias, mean_in, var_in,
                                      eps, momentum, relu)
    else:
        y, nm, nv, sm, sv = _bn_train(a, scale, bias, mean_in, var_in,
                                      eps, momentum, False)
        y = y + addend
        if relu:
            y = jnp.maximum(y, 0)
    return {"Output": [y], "MeanOut": [nm], "VarianceOut": [nv],
            "SavedMean": [sm], "SavedVariance": [sv]}


def _fused_bottleneck_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    w2 = block.var(op.input("W2")[0])
    c = w2.shape[0]
    cin = x.shape[1]
    for slot in ("MeanOut1", "VarOut1", "SavedMean1", "SavedVar1",
                 "MeanOut2", "VarOut2", "SavedMean2", "SavedVar2"):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = (c,), "float32"
    for slot in ("MeanOut3", "VarOut3", "SavedMean3", "SavedVar3"):
        v = block.var(op.output(slot)[0])
        v.shape, v.dtype = (cin,), "float32"


@register_op("fused_bottleneck", infer_shape=_fused_bottleneck_infer)
def fused_bottleneck(ctx, ins, attrs):
    x = ins["X"][0]
    w1, w2, w3 = ins["W1"][0], ins["W2"][0], ins["W3"][0]
    bn_params = []
    for k in ("1", "2", "3"):
        bn_params.append((ins["Scale" + k][0], ins["Bias" + k][0],
                          ins["Mean" + k][0], ins["Variance" + k][0]))
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    n, cin, hh, ww = x.shape
    c = w1.shape[0]
    from .math_ops import harmonize
    w1 = harmonize(x, w1)
    w2 = harmonize(x, w2)
    w3 = harmonize(x, w3)

    if attrs.get("is_test", False):
        # inference: running stats, no stat updates (≙ batch_norm is_test),
        # kept in the same op so train/infer graphs share parameter names —
        # and the BN is FOLDED INTO THE CONV WEIGHTS (w' = w·γ/σ per output
        # channel, + bias), i.e. the op internalizes InferenceTranspiler's
        # conv→BN fold for the blocks its pattern-matcher no longer sees
        def conv_bn_folded(h, w, pad, g, b, m, v, act):
            inv = jax.lax.rsqrt(v + eps)
            s = (g * inv).astype(jnp.float32)
            wf = (w.astype(jnp.float32) * s.reshape(-1, 1, 1, 1)
                  ).astype(w.dtype)
            bias = (b - m * g * inv).reshape(1, -1, 1, 1).astype(h.dtype)
            y = _conv(h, wf, pad) + bias
            return jnp.maximum(y, 0) if act else y

        (g1, b1, m1, v1), (g2, b2, m2, v2), (g3, b3, m3, v3) = bn_params
        h1 = conv_bn_folded(x, w1, 0, g1, b1, m1, v1, True)
        h2 = conv_bn_folded(h1, w2, 1, g2, b2, m2, v2, True)
        h3 = conv_bn_folded(h2, w3, 0, g3, b3, m3, v3, False)
        out = jnp.maximum(h3 + x, 0)
        return {"Out": [out],
                "MeanOut1": [m1], "VarOut1": [v1],
                "SavedMean1": [m1], "SavedVar1": [v1],
                "MeanOut2": [m2], "VarOut2": [v2],
                "SavedMean2": [m2], "SavedVar2": [v2],
                "MeanOut3": [m3], "VarOut3": [v3],
                "SavedMean3": [m3], "SavedVar3": [v3]}

    min_s = int(os.environ.get("PT_FUSED_BLOCK_MIN_S", 196))
    use_pallas = (_fused_block_enabled(ctx) and hh == ww and n >= 8
                  and hh * ww >= min_s and cin % 128 == 0 and c % 64 == 0)
    if not use_pallas:
        out, st = _compose_block(x, w1, w2, w3, bn_params, eps, momentum)
        (nm1, nv1, sm1, sv1, nm2, nv2, sm2, sv2, nm3, nv3, sm3,
         sv3) = st
    else:
        from ..kernels.fused_block import fused_bottleneck_rest
        xr = x.reshape(n, cin, hh * ww)
        taps = jnp.transpose(w2, (2, 3, 0, 1)).reshape(9, c, c)
        (g1, b1, m1i, v1i), (g2, b2, m2i, v2i), (g3, b3, m3i,
                                                 v3i) = bn_params
        outs = fused_bottleneck_rest(
            xr, w1.reshape(c, cin), taps, w3.reshape(cin, c),
            g1.astype(jnp.float32), b1.astype(jnp.float32),
            g2.astype(jnp.float32), b2.astype(jnp.float32),
            g3.astype(jnp.float32), b3.astype(jnp.float32), hh, eps)
        out = outs[0].reshape(n, cin, hh, ww)
        sm1, sv1, sm2, sv2, sm3, sv3 = outs[1:]
        nm1 = momentum * m1i + (1 - momentum) * sm1
        nv1 = momentum * v1i + (1 - momentum) * sv1
        nm2 = momentum * m2i + (1 - momentum) * sm2
        nv2 = momentum * v2i + (1 - momentum) * sv2
        nm3 = momentum * m3i + (1 - momentum) * sm3
        nv3 = momentum * v3i + (1 - momentum) * sv3
    return {"Out": [out],
            "MeanOut1": [nm1], "VarOut1": [nv1],
            "SavedMean1": [sm1], "SavedVar1": [sv1],
            "MeanOut2": [nm2], "VarOut2": [nv2],
            "SavedMean2": [sm2], "SavedVar2": [sv2],
            "MeanOut3": [nm3], "VarOut3": [nv3],
            "SavedMean3": [sm3], "SavedVar3": [sv3]}
