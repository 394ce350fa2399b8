"""Grouped-convolution autotune cache (VERDICT r4 next #4).

≙ the reference's cuDNN algorithm search (conv_cudnn_op.cu.cc:
CUDNN_CONVOLUTION_FWD_PREFER_FASTEST + workspace probing, cached per
shape in the op's scope) — rebuilt for the XLA world, where the choice is
not between library algorithms but between FORMULATIONS the compiler
then owns: XLA's native grouped conv vs a dense conv over a
block-diagonal-expanded filter (ops/nn_ops._dense_expand_grouped), the
dense side itself measured in two weight layouts (OIHW as stored vs a
pre-transposed HWIO operand — the layout hint changes which tiling XLA
assigns the MXU for the se_resnext grouped tail).

Rounds 3-4 picked by a static rule (groups small AND output-spatial
large, boundary measured once on one chip).  Here the rule is replaced by
MEASUREMENT: before a program first compiles, the executor walks its
grouped convs and, for any (shape, stride, dtype) not in the on-disk
cache, times the formulations fwd+bwd on dummy data — the chained
fori_loop slope method (a single dispatched loop whose iterations form a
data chain; two window lengths difference out the fixed dispatch cost),
because this fabric dedupes identical dispatches and bare wall-clock
lies.  Winners persist in PT_GCONV_CACHE (default
~/.cache/paddle_tpu/gconv_autotune.json) keyed by device kind, so the
cost is one-time per shape per chip generation.

The cache machinery itself (schema-versioned file envelope, load-time
floor filtering, crash-safe merge-save, the retry-then-invalid-then-
error measurement discipline) lives in utils/kernel_autotune.py, shared
with every other measured kernel choice; this module owns only the
gconv key schema and the shootout itself.

PT_GCONV_DENSE=always|never still overrides everything (escape hatch);
PT_GCONV_LAYOUT=oihw|hwio pins the dense weight layout;
PT_GCONV_TUNE=0 disables measurement (falls back to native grouped).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

from . import kernel_autotune

#: every entry records the namespace decision (prefers_dense) even on
#: error/invalid; these three candidates are the measured fields
_CACHE = kernel_autotune.AutotuneCache(
    "gconv", "PT_GCONV_CACHE",
    decision_field="prefers_dense",
    ms_fields=("native_ms", "dense_ms", "dense_hwio_ms"))

#: the decision recorded when measurement fails: native formulation,
#: stored weight layout
_FALLBACK = {"prefers_dense": False, "layout": "oihw"}


def _cache_path() -> str:
    return _CACHE.path()


def _load() -> Dict[str, dict]:
    return _CACHE.load()


def _save() -> None:
    _CACHE.save()


def _norm_pair(v, default) -> Tuple[int, int]:
    if v is None:
        v = default
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1] if len(v) > 1 else v[0]))
    return (int(v), int(v))


def shape_key(n, cin, h, w, cout, groups, stride, dtype, k=3,
              padding=None, dilation=(1, 1)) -> str:
    """Cache key. Audited so every attribute that can flip the winner is
    keyed: padding=None means the historical SAME default (k//2); convs
    with identical shapes but different padding/dilation measure in
    different regimes and must not share an entry (ADVICE r5); the
    trailing data-layout token names the activation layout the shootout
    ran in (NCHW is the only one the framework emits today — keyed so a
    future NHWC plane can never alias onto these winners). Key-schema
    changes ride kernel_autotune.SCHEMA_VERSION: bumping it retires
    every entry measured under the old key semantics at load."""
    kind = kernel_autotune.device_kind()
    ph, pw = _norm_pair(padding, int(k) // 2)
    dh, dw = _norm_pair(dilation, 1)
    return (f"{kind}|n{n}c{cin}h{h}w{w}->o{cout}g{groups}k{k}"
            f"s{stride[0]}x{stride[1]}p{ph}x{pw}d{dh}x{dw}|{dtype}|nchw")


def lookup(key: str) -> Optional[bool]:
    ent = _load().get(key)
    return None if ent is None else bool(ent["prefers_dense"])


def lookup_layout(key: str) -> Optional[str]:
    """The dense formulation's measured weight layout for `key`:
    'oihw' (as stored) or 'hwio' (pre-transposed operand). None when
    untuned; entries predating the layout dimension read as 'oihw'."""
    ent = _load().get(key)
    if ent is None:
        return None
    return str(ent.get("layout", "oihw"))


def measure(n, cin, h, w, cout, groups, stride, dtype, k=3,
            padding=None, dilation=(1, 1)) -> dict:
    """Time native-grouped vs dense-expanded conv (the dense side in both
    OIHW-as-stored and pre-transposed-HWIO weight layouts), fwd+bwd, on
    dummy data.  Runs OUTSIDE any trace (executor pre-pass).
    padding/dilation are the op's ACTUAL attrs (padding=None keeps the
    historical SAME default) — measuring a different regime than the
    trace runs was the ADVICE-r5 aliasing bug."""
    import jax
    import jax.numpy as jnp

    from ..ops.nn_ops import _dense_expand_grouped

    kh = kw = int(k)
    ph, pw = _norm_pair(padding, kh // 2)
    dh, dw = _norm_pair(dilation, 1)
    key_rng = jax.random.PRNGKey(0)
    x = jax.random.normal(key_rng, (n, cin, h, w), jnp.dtype(dtype))
    wg = (jax.random.normal(key_rng, (cout, cin // groups, kh, kw))
          * 0.1).astype(jnp.dtype(dtype))

    def conv(x, wv, g, dn=("NCHW", "OIHW", "NCHW")):
        return jax.lax.conv_general_dilated(
            x, wv, stride, [(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw),
            dimension_numbers=dn,
            feature_group_count=g)

    def make_step(formulation):
        def step(c):
            xc, wc = c
            def loss(wv):
                if formulation == "native":
                    y = conv(xc, wv, groups)
                else:
                    wd = _dense_expand_grouped(wv, groups)
                    if formulation == "dense_hwio":
                        # the transpose is traced INSIDE the step, as
                        # ops/nn_ops._conv2d traces it inside the jit:
                        # the point is the operand-layout hint it hands
                        # XLA's layout assignment, not the copy itself
                        y = conv(xc, jnp.transpose(wd, (2, 3, 1, 0)), 1,
                                 dn=("NCHW", "HWIO", "NCHW"))
                    else:
                        y = conv(xc, wd, 1)
                return jnp.sum(y.astype(jnp.float32) * 1e-6), y
            (_, y), dw = jax.value_and_grad(loss, has_aux=True)(wc)
            # chain the BIG activation through a scalar consuming ALL of
            # y: weight-only chains under-measured the dense side by
            # 100x+ (two broken tuning passes — the activation chain
            # reproduces the honest numbers), and the scalar broadcast is
            # shape-agnostic across strides. 0.999-decay bounds values.
            xc = xc * 0.999 + jnp.mean(y).astype(xc.dtype) * 1e-3
            wc = wc * 0.999 + dw * 1e-2
            return (xc, wc)
        return step

    flops = 2 * 3 * n * (h // stride[0]) * (w // stride[1]) \
        * cout * (cin // groups) * kh * kw
    iters = max(8, min(96, int(2.5e11 / max(flops, 1))))
    from .chain_timer import time_step
    t_native = time_step(make_step("native"), (x, wg), iters)
    t_dense = time_step(make_step("dense"), (x, wg), iters)
    t_hwio = time_step(make_step("dense_hwio"), (x, wg), iters)
    t_best_dense = min(t_dense, t_hwio)
    ent = {"native_ms": round(t_native * 1e3, 4),
           "dense_ms": round(t_dense * 1e3, 4),
           "dense_hwio_ms": round(t_hwio * 1e3, 4),
           "prefers_dense": bool(t_best_dense < t_native),
           "layout": "hwio" if t_hwio < t_dense else "oihw"}
    # predicted-vs-measured join (obs/opprof.py discipline applied to
    # the autotune harness): every cache entry carries the cost model's
    # roofline for this conv shape plus each candidate FORMULATION's
    # measured/predicted ratio — a delta far above the fleet norm names
    # the shape the conv-family MFU push should attack first. Advisory
    # only: the formulation choice stays purely measured.
    try:
        from ..analysis.cost import predict_grouped_conv_ms
        pred = predict_grouped_conv_ms(n, cin, h, w, cout, groups, stride,
                                       k=int(k), dtype=str(dtype))
        if pred > 0:
            ent["predicted_ms"] = round(pred, 6)
            ent["native_delta"] = round(t_native * 1e3 / pred, 3)
            ent["dense_delta"] = round(t_dense * 1e3 / pred, 3)
            ent["hwio_delta"] = round(t_hwio * 1e3 / pred, 3)
    except Exception:   # noqa: BLE001 — prediction must never break tuning
        pass
    return ent


def ensure_tuned(n, cin, h, w, cout, groups, stride, dtype, k=3,
                 padding=None, dilation=(1, 1)) -> None:
    enabled = os.environ.get("PT_GCONV_TUNE", "1") not in ("0", "never")
    key = shape_key(n, cin, h, w, cout, groups, stride, dtype, k,
                    padding, dilation)
    _CACHE.ensure(
        key,
        lambda: measure(n, cin, h, w, cout, groups, stride, dtype, k,
                        padding, dilation),
        fallback=dict(_FALLBACK), enabled=enabled)


def tune_program(program, batch_hint: int) -> None:
    """Executor pre-pass: make sure every grouped conv2d in `program` has
    a cache entry before the program traces (the trace-time decision in
    ops/nn_ops can only LOOK UP, never measure)."""
    import jax
    try:
        platform = jax.default_backend()
    except Exception:  # pragma: no cover
        return
    if platform != "tpu":
        return
    for block in program.blocks:
        for op in block.ops:
            if op.type != "conv2d":
                continue
            g = (op.attrs or {}).get("groups", 1) or 1
            if g <= 1:
                continue
            try:
                xv = block.var(op.input("Input")[0])
                wv = block.var(op.input("Filter")[0])
            except KeyError:
                continue
            if g >= xv.shape[1]:       # depthwise keeps the native path
                continue
            s = (op.attrs or {}).get("strides", (1, 1))
            s = tuple(s) if isinstance(s, (list, tuple)) else (s, s)
            pad = _norm_pair((op.attrs or {}).get("paddings", 0), 0)
            dil = _norm_pair((op.attrs or {}).get("dilations", 1), 1)
            n = xv.shape[0] if xv.shape[0] and xv.shape[0] > 0 \
                else batch_hint
            if any(int(d) <= 0 for d in tuple(xv.shape[1:])):
                continue
            # COMPUTE dtype, not VarDesc dtype: under amp_dtype the traced
            # arrays (and the trace-time lookup key) are the amp dtype —
            # a f32-keyed entry would never be read, and f32 dummies
            # would measure the wrong regime
            dt = str(xv.dtype)
            amp = getattr(program, "amp_dtype", None)
            if amp and dt == "float32":
                dt = str(amp)
            ensure_tuned(int(n), int(xv.shape[1]), int(xv.shape[2]),
                         int(xv.shape[3]), int(wv.shape[0]), int(g),
                         (int(s[0]), int(s[1])), dt, int(wv.shape[2]),
                         padding=pad, dilation=dil)
