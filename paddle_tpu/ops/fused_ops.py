"""Fused ops: the tuned-kernel tier above the generic op library.

≙ the reference's cuDNN tier (conv_cudnn_op.cu.cc — algorithm selection and
workspace tuning sitting above the im2col/math path) and its fusion passes
(fuse_elewise_add_act etc.): on TPU the equivalent lever is cross-op fusion
that XLA cannot perform because convolutions are HLO materialization
boundaries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .nn_ops import _bn_train, _conv2d, _conv2d_infer


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

