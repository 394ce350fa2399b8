"""Fused conv-epilogue kernels (Pallas, TPU) + their measured gate.

The conv-epilogue fusion pass (analysis/fuse.py) rewrites
conv2d → batch_norm → relu/add chains into single `fused_conv2d` ops
(ops/fused_ops.py).  The conv itself stays an XLA HLO — the MXU conv is
the one thing XLA already schedules well — but everything AFTER it is an
HBM round-trip XLA cannot fuse across the conv's materialization
boundary: the unfused chain writes the conv output, re-reads it for the
BN stats pass, re-reads it again for normalize(+add)+relu and writes the
final activation.  This module provides the epilogue as two Pallas
passes over the conv output laid out [N, C, S] per-image (the layout
every ResNet stage shares):

  stats  one read of `a`, accumulating per-channel Σ / Σ² across the
         batch grid (the BN batch-stats pass riding a single sweep);
  apply  one read of `a` (+ the residual addend when the pass absorbed
         an elementwise_add), one write of the output, with the BN
         folded to a per-channel affine and the ReLU applied in the
         epilogue — the eliminated intermediate round-trips are exactly
         the bytes analysis/cost.py's fused_conv2d entry drops.

Backward is a memory-lean custom VJP in the _bn_train mold
(ops/nn_ops.py): residuals are the raw conv output plus per-channel
vectors, x-hat and the ReLU mask are recomputed, stat cotangents are
exact, and the addend's cotangent is the masked upstream gradient.

Whether the Pallas epilogue beats XLA's own fusion of the lax
composition is a MEASURED per-shape choice through the shared autotune
harness (utils/kernel_autotune.py, PT_FUSE_CACHE /
~/.cache/paddle_tpu/fused_conv_autotune.json): `tune_program` runs as an
executor pre-pass next to the gconv shootout, `lookup` steers the
trace-time gate.  PT_FUSE_EPILOGUE=always|never overrides; untuned
shapes (CPU tests) take the lax composition, which is also the semantic
definition of the op.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import kernel_autotune

# Set True to run the kernels through the Pallas interpreter (CPU tests /
# numerics debugging); the TPU path never flips this.
INTERPRET = False

_CACHE = kernel_autotune.AutotuneCache(
    "fused_conv", "PT_FUSE_CACHE",
    decision_field="prefers_pallas",
    ms_fields=("xla_ms", "pallas_ms"))

#: the decision recorded when measurement fails: XLA lax composition
_FALLBACK = {"prefers_pallas": False}


# ---------------------------------------------------------------------------
# Pallas epilogue kernels
# ---------------------------------------------------------------------------

def _stats_kernel(a_ref, stats_ref):
    i = pl.program_id(0)
    af = a_ref[0].astype(jnp.float32)               # [C, S]
    st = jnp.concatenate([jnp.sum(af, axis=1, keepdims=True),
                          jnp.sum(af * af, axis=1, keepdims=True)], axis=1)

    @pl.when(i == 0)
    def _():
        stats_ref[:] = st

    @pl.when(i > 0)
    def _():
        stats_ref[:] = stats_ref[:] + st


def channel_stats(a):
    """a: [N, C, S] raw conv output -> (Σ [C], Σ² [C]) in f32 — the BN
    batch-stats pass as one sweep over the tensor."""
    n, c, s = a.shape
    stats = pl.pallas_call(
        _stats_kernel,
        interpret=INTERPRET,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, c, s), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((c, 2), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((c, 2), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * c * s,
            bytes_accessed=a.size * a.dtype.itemsize,
            transcendentals=0,
        ),
    )(a)
    return stats[:, 0], stats[:, 1]


def _apply_kernel(relu, a_ref, aff_ref, out_ref):
    af = a_ref[0].astype(jnp.float32)
    y = af * aff_ref[:, 0:1] + aff_ref[:, 1:2]
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[0] = y.astype(out_ref.dtype)


def _apply_add_kernel(relu, a_ref, add_ref, aff_ref, out_ref):
    af = a_ref[0].astype(jnp.float32)
    y = af * aff_ref[:, 0:1] + aff_ref[:, 1:2] \
        + add_ref[0].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    out_ref[0] = y.astype(out_ref.dtype)


def apply_epilogue(a, scale_c, shift_c, addend=None, relu=True):
    """a: [N, C, S]; scale_c/shift_c: [C] f32 (the BN folded to an
    affine: scale_c = γ·rsqrt(v+eps), shift_c = β − m·scale_c); addend:
    optional [N, C, S] residual absorbed by the pass.  One read of each
    input, one write of the output — no intermediate ever leaves VMEM."""
    n, c, s = a.shape
    aff = jnp.stack([scale_c, shift_c], axis=1)     # [C, 2]
    img = pl.BlockSpec((1, c, s), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    vec = pl.BlockSpec((c, 2), lambda i: (0, 0), memory_space=pltpu.VMEM)
    reads = a.size + 2 * c + (addend.size if addend is not None else 0)
    cost = pl.CostEstimate(
        flops=(3 if addend is not None else 2) * n * c * s,
        bytes_accessed=(reads + a.size) * a.dtype.itemsize,
        transcendentals=0,
    )
    if addend is None:
        return pl.pallas_call(
            functools.partial(_apply_kernel, relu),
            interpret=INTERPRET,
            grid=(n,),
            in_specs=[img, vec],
            out_specs=pl.BlockSpec((1, c, s), lambda i: (i, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((n, c, s), a.dtype),
            cost_estimate=cost,
        )(a, aff)
    return pl.pallas_call(
        functools.partial(_apply_add_kernel, relu),
        interpret=INTERPRET,
        grid=(n,),
        in_specs=[img, img, vec],
        out_specs=pl.BlockSpec((1, c, s), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, c, s), a.dtype),
        cost_estimate=cost,
    )(a, addend, aff)


# ---------------------------------------------------------------------------
# The differentiable epilogue (custom VJP, _bn_train's discipline + addend)
# ---------------------------------------------------------------------------

def _epilogue_fwd_impl(a, scale, bias, mean_in, var_in, addend, eps,
                       momentum, relu):
    n, c, h, w = a.shape
    a3 = a.reshape(n, c, h * w)
    ssum, ssq = channel_stats(a3)
    m_count = a3.shape[0] * a3.shape[2]
    mean = ssum / m_count
    var = jnp.maximum(ssq / m_count - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    sf = scale.astype(jnp.float32)
    scale_c = sf * inv
    shift_c = bias.astype(jnp.float32) - mean * scale_c
    add3 = addend.reshape(n, c, h * w) if addend is not None else None
    y = apply_epilogue(a3, scale_c, shift_c, add3, relu).reshape(a.shape)
    new_mean = momentum * mean_in + (1 - momentum) * mean
    new_var = momentum * var_in + (1 - momentum) * var
    out = (y, new_mean, new_var, mean, var)
    return out, (a, scale, bias, mean, inv, addend)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def fused_conv_epilogue(a, scale, bias, mean_in, var_in, addend, eps,
                        momentum, relu):
    """Pallas-backed BN(+add)(+relu) epilogue over a raw conv output
    `a` [N, C, H, W].  Returns (y, new_mean, new_var, saved_mean,
    saved_var) — the same quintuple as ops.nn_ops._bn_train, so the op
    layer's running-stat rebinding is backend-agnostic."""
    out, _ = _epilogue_fwd_impl(a, scale, bias, mean_in, var_in, addend,
                                eps, momentum, relu)
    return out


def _epilogue_fwd(a, scale, bias, mean_in, var_in, addend, eps, momentum,
                  relu):
    return _epilogue_fwd_impl(a, scale, bias, mean_in, var_in, addend,
                              eps, momentum, relu)


def _epilogue_bwd(eps, momentum, relu, res, cts):
    a, scale, bias, mean, inv, addend = res
    gy, g_new_mean, g_new_var, g_saved_mean, g_saved_var = cts
    axes = (0, 2, 3)
    bshape = (1, -1, 1, 1)
    m = a.shape[0] * a.shape[2] * a.shape[3]
    af = a.astype(jnp.float32)
    xhat = (af - mean.reshape(bshape)) * inv.reshape(bshape)
    if relu:
        # recompute the pre-relu value (never stored) for the mask
        sf32 = scale.astype(jnp.float32)
        pre = xhat * sf32.reshape(bshape) \
            + bias.astype(jnp.float32).reshape(bshape)
        if addend is not None:
            pre = pre + addend.astype(jnp.float32)
        gy = jnp.where(pre > 0, gy, jnp.zeros_like(gy))
    g_add = gy.astype(addend.dtype) if addend is not None else None
    gyf = gy.astype(jnp.float32)
    dbeta = jnp.sum(gyf, axis=axes)
    dgamma = jnp.sum(gyf * xhat, axis=axes)
    sf = scale.astype(jnp.float32)
    da = (sf * inv).reshape(bshape) * (
        gyf - (dbeta / m).reshape(bshape)
        - xhat * (dgamma / m).reshape(bshape))
    # stat cotangents, exactly as _bn_train_bwd derives them
    g_mean_tot = (1 - momentum) * g_new_mean + g_saved_mean
    g_var_tot = (1 - momentum) * g_new_var + g_saved_var
    da = da + (g_mean_tot / m).reshape(bshape) \
        + (af - mean.reshape(bshape)) * (2.0 * g_var_tot / m).reshape(bshape)
    return (da.astype(a.dtype), dgamma.astype(scale.dtype),
            dbeta.astype(bias.dtype), momentum * g_new_mean,
            momentum * g_new_var, g_add)


fused_conv_epilogue.defvjp(_epilogue_fwd, _epilogue_bwd)


# ---------------------------------------------------------------------------
# The measured gate (shared autotune harness)
# ---------------------------------------------------------------------------

def shape_key(n, c, h, w, dtype, relu=True, with_add=False) -> str:
    """Cache key of the EPILOGUE shape (the conv in front is keyed by the
    gconv/XLA machinery; the epilogue's regime is its output tensor)."""
    kind = kernel_autotune.device_kind()
    tail = ("a" if with_add else "") + ("r" if relu else "")
    return f"{kind}|ep|n{n}c{c}h{h}w{w}{tail or '-'}|{dtype}|nchw"


def lookup(key: str):
    ent = _CACHE.get(key)
    return None if ent is None else bool(ent["prefers_pallas"])


def epilogue_enabled(ctx, n, c, h, w, dtype, relu=True,
                     with_add=False) -> bool:
    """Trace-time gate for the Pallas epilogue: measured per shape
    (PT_FUSE_EPILOGUE=always|never overrides; sharded meshes always take
    the partitionable lax composition; untuned shapes too)."""
    mode = os.environ.get("PT_FUSE_EPILOGUE", "auto")
    if mode in ("0", "never"):
        return False
    if ctx is not None and getattr(ctx, "mesh", None) is not None:
        # GSPMD cannot partition an opaque Pallas call
        return False
    try:
        on_tpu = jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - backend probing never fatal
        on_tpu = False
    if not on_tpu and not INTERPRET:
        return False
    if mode in ("1", "always"):
        return True
    hit = lookup(shape_key(n, c, h, w, dtype, relu, with_add))
    return bool(hit) if hit is not None else False


def _reference_epilogue(a, scale, bias, mean_in, var_in, addend, eps,
                        momentum, relu):
    """The lax composition the measurement races the kernels against —
    the exact code path ops/fused_ops.py runs when the gate is off."""
    from ..ops.nn_ops import _bn_train
    if addend is None:
        return _bn_train(a, scale, bias, mean_in, var_in, eps, momentum,
                         relu)
    y, nm, nv, sm, sv = _bn_train(a, scale, bias, mean_in, var_in, eps,
                                  momentum, False)
    y = y + addend
    if relu:
        y = jnp.maximum(y, 0)
    return y, nm, nv, sm, sv


def measure(n, c, h, w, dtype, relu=True, with_add=False) -> dict:
    """Time the XLA lax composition vs the Pallas epilogue, fwd+bwd, on
    dummy data — same chained-slope instrument as the gconv shootout."""
    key_rng = jax.random.PRNGKey(0)
    dt = jnp.dtype(dtype)
    a0 = jax.random.normal(key_rng, (n, c, h, w), dt)
    add0 = a0 * 0.5 if with_add else None
    g = jnp.ones((c,), jnp.float32)
    b = jnp.zeros((c,), jnp.float32)
    rm = jnp.zeros((c,), jnp.float32)
    rv = jnp.ones((c,), jnp.float32)

    def make_step(fn):
        def step(carry):
            ac = carry

            def loss(av):
                if with_add:
                    outs = fn(av, g, b, rm, rv, add0, 1e-5, 0.9, relu)
                else:
                    outs = fn(av, g, b, rm, rv, None, 1e-5, 0.9, relu)
                y = outs[0]
                return jnp.sum(y.astype(jnp.float32) * 1e-6), y

            (_, y), da = jax.value_and_grad(loss, has_aux=True)(ac)
            ac = ac * 0.999 + y * 1e-3 + da * 1e-3
            return ac
        return step

    elems = n * c * h * w
    iters = max(8, min(96, int(2e9 / max(elems, 1))))
    from ..utils.chain_timer import time_step
    t_xla = time_step(make_step(_reference_epilogue), a0, iters)
    t_pallas = time_step(make_step(fused_conv_epilogue), a0, iters)
    return {"xla_ms": round(t_xla * 1e3, 4),
            "pallas_ms": round(t_pallas * 1e3, 4),
            "prefers_pallas": bool(t_pallas < t_xla)}


def ensure_tuned(n, c, h, w, dtype, relu=True, with_add=False) -> None:
    enabled = os.environ.get("PT_FUSE_TUNE", "1") not in ("0", "never")
    key = shape_key(n, c, h, w, dtype, relu, with_add)
    _CACHE.ensure(
        key, lambda: measure(n, c, h, w, dtype, relu, with_add),
        fallback=dict(_FALLBACK), enabled=enabled)


def tune_program(program, batch_hint: int) -> None:
    """Executor pre-pass (rides next to gconv_autotune.tune_program):
    make sure every fused_conv2d epilogue shape in `program` has a cache
    entry before the program traces."""
    try:
        platform = jax.default_backend()
    except Exception:  # pragma: no cover
        return
    if platform != "tpu":
        return
    if os.environ.get("PT_FUSE_EPILOGUE", "auto") in ("0", "never"):
        return
    for block in program.blocks:
        for op in block.ops:
            if op.type != "fused_conv2d":
                continue
            if (op.attrs or {}).get("is_test", False):
                continue            # inference folds BN into the conv
            try:
                ov = block.var(op.output("Output")[0])
            except KeyError:
                continue
            shape = tuple(ov.shape)
            if len(shape) != 4 or any(int(d) <= 0 for d in shape[1:]):
                continue
            n = shape[0] if shape[0] and shape[0] > 0 else batch_hint
            dt = str(ov.dtype)
            amp = getattr(program, "amp_dtype", None)
            if amp and dt == "float32":
                dt = str(amp)
            ensure_tuned(int(n), int(shape[1]), int(shape[2]),
                         int(shape[3]), dt,
                         relu=(op.attrs or {}).get("act", "") == "relu",
                         with_add=bool(op.input("Addend")))
