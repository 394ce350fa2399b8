"""Grad-parity pins for the default-on custom VJPs (ADVICE r4 #1).

_bn_train and _softmax_xent_hard replace JAX AD for every model; the
PT_BN_PLAIN_VJP / PT_XENT_PLAIN env flags exist for timing A/B but until
round 5 nothing pinned the custom gradients against the plain-AD
formulations. These tests differentiate BOTH formulations with NONZERO
cotangents on every output (incl. MeanOut/VarianceOut/SavedMean/
SavedVariance, which are zero in normal training) and in both
fuse_with_relu modes, so a future edit to either path fails loudly."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import nn_ops


def _bn_plain(x, scale, bias, mean_in, var_in, eps, momentum, relu):
    """The PT_BN_PLAIN_VJP formulation (nn_ops.batch_norm:457-468),
    lifted so JAX default AD differentiates it."""
    axes = tuple(i for i in range(x.ndim) if i != 1)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(mean)
    new_mean = momentum * mean_in + (1 - momentum) * mean
    new_var = momentum * var_in + (1 - momentum) * var
    inv = jax.lax.rsqrt(var + eps)
    y = nn_ops._bn_apply(x, mean, inv, scale, bias)
    if relu:
        y = jnp.maximum(y, 0)
    return y, new_mean, new_var, mean, var


@pytest.mark.parametrize("relu", [False, True])
def test_bn_train_vjp_matches_plain_ad(relu):
    rng = np.random.RandomState(0)
    n, c, h, w = 4, 6, 5, 3
    x = jnp.asarray(rng.randn(n, c, h, w).astype(np.float32))
    scale = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5)
    bias = jnp.asarray(rng.randn(c).astype(np.float32))
    mean_in = jnp.asarray(rng.randn(c).astype(np.float32))
    var_in = jnp.asarray(rng.rand(c).astype(np.float32) + 0.5)
    eps, momentum = 1e-5, 0.9
    # fixed nonzero cotangents for EVERY output, so the state outputs'
    # backward rules are exercised, not just Y's
    cts = (jnp.asarray(rng.randn(n, c, h, w).astype(np.float32)),
           jnp.asarray(rng.randn(c).astype(np.float32)),
           jnp.asarray(rng.randn(c).astype(np.float32)),
           jnp.asarray(rng.randn(c).astype(np.float32)),
           jnp.asarray(rng.randn(c).astype(np.float32)))

    def objective(fn):
        def f(x, scale, bias, mean_in, var_in):
            outs = fn(x, scale, bias, mean_in, var_in, eps, momentum, relu)
            return sum(jnp.vdot(o, ct) for o, ct in zip(outs, cts))
        return f

    grads_custom = jax.grad(objective(nn_ops._bn_train),
                            argnums=(0, 1, 2, 3, 4))(
        x, scale, bias, mean_in, var_in)
    grads_plain = jax.grad(objective(_bn_plain), argnums=(0, 1, 2, 3, 4))(
        x, scale, bias, mean_in, var_in)
    for gc, gp, name in zip(grads_custom, grads_plain,
                            ("x", "scale", "bias", "mean_in", "var_in")):
        np.testing.assert_allclose(np.asarray(gc), np.asarray(gp),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"d{name} (relu={relu})")


def test_bn_train_forward_matches_plain():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 3, 4, 4).astype(np.float32))
    scale = jnp.ones(3)
    bias = jnp.zeros(3)
    mean_in = jnp.zeros(3)
    var_in = jnp.ones(3)
    a = nn_ops._bn_train(x, scale, bias, mean_in, var_in, 1e-5, 0.9, True)
    b = _bn_plain(x, scale, bias, mean_in, var_in, 1e-5, 0.9, True)
    for ya, yb in zip(a, b):
        np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                   rtol=1e-5, atol=1e-6)


def _xent_plain(logits, lbl):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, lbl[..., None].astype(jnp.int32),
                                axis=-1)


@pytest.mark.parametrize("shape,vocab", [((8,), 17), ((4, 6), 31)])
def test_softmax_xent_vjp_matches_plain_ad(shape, vocab):
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(*shape, vocab).astype(np.float32) * 3)
    lbl = jnp.asarray(rng.randint(0, vocab, shape).astype(np.int64))
    ct = jnp.asarray(rng.randn(*shape, 1).astype(np.float32))

    def objective(fn):
        return lambda lg: jnp.vdot(fn(lg, lbl), ct)

    g_custom = jax.grad(objective(nn_ops._softmax_xent_hard))(logits)
    g_plain = jax.grad(objective(_xent_plain))(logits)
    np.testing.assert_allclose(np.asarray(g_custom), np.asarray(g_plain),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(nn_ops._softmax_xent_hard(logits, lbl)),
        np.asarray(_xent_plain(logits, lbl)), rtol=1e-5, atol=1e-6)


def _rand_qkv(rng, b, s, h, d, dtype=np.float32):
    return [jnp.asarray(rng.randn(b, s, h, d).astype(dtype) * 0.5)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bwd_batch_gt1_matches_reference(causal):
    """VERDICT r5 Weak #1/#2: the flash backward was grad-tested at
    batch=1 only, and the one FAILED_LEARNING config (transformer) is the
    only batch>1 flash config. Pin all three input grads at batch 3 /
    heads 2 with nonzero cotangents against autodiff through
    mha_reference."""
    from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                    mha_reference)
    rng = np.random.RandomState(7)
    b, s, h, d = 3, 64, 2, 16
    q, k, v = _rand_qkv(rng, b, s, h, d)
    ct = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))

    def obj(fn):
        return lambda q, k, v: jnp.vdot(fn(q, k, v), ct)

    g_flash = jax.grad(obj(functools.partial(
        flash_attention, causal=causal, interpret=True,
        block_q=32, block_k=32)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(obj(functools.partial(
        mha_reference, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"d{name} (causal={causal}, "
                                           f"batch>1)")


@pytest.mark.parametrize("sq", [64, 48])  # 48: block padding path
def test_flash_attention_bwd_non_interpret_xla_fallback(sq):
    """The non-interpret backward (the XLA chunked-scan branch of
    _flash_bwd_rule — what every non-TPU backend runs, and the numerics
    oracle for the Pallas kernels) at batch>1, exercised directly: the
    residuals come from the interpret-mode forward, the backward runs
    with interpret=False so dispatch takes the scan path."""
    from paddle_tpu.kernels import flash_attention as fa
    rng = np.random.RandomState(8)
    b, h, d = 2, 2, 16
    q, k, v = _rand_qkv(rng, b, sq, h, d)
    do = jnp.asarray(rng.randn(b, sq, h, d).astype(np.float32))
    scale = 1.0 / np.sqrt(d)

    # forward blocks of 16 divide both sq values (the Pallas forward
    # needs block-divisible sequences); the backward runs with block 32,
    # so sq=48 exercises the fallback's q-block PADDING path
    _, res = fa._flash_fwd_rule(q, k, v, scale, True, 16, 16,
                                interpret=True)
    dq, dk, dv = fa._flash_bwd_rule(scale, True, 32, 32, False, res, do)

    g_ref = jax.grad(
        lambda q, k, v: jnp.vdot(
            fa.mha_reference(q, k, v, causal=True, scale=scale), do),
        argnums=(0, 1, 2))(q, k, v)
    for g, gr, name in zip((dq, dk, dv), g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=f"d{name} (sq={sq}, "
                                           "non-interpret fallback)")


def test_softmax_xent_bf16_logits_grad_dtype():
    """The bf16 path (amp) must return bf16 dlogits with f32 accuracy of
    the same order as casting the plain-AD result."""
    import ml_dtypes
    rng = np.random.RandomState(3)
    logits = jnp.asarray(rng.randn(4, 9).astype(ml_dtypes.bfloat16))
    lbl = jnp.asarray(rng.randint(0, 9, (4,)).astype(np.int64))

    def f(lg):
        return jnp.sum(nn_ops._softmax_xent_hard(lg, lbl))

    g = jax.grad(f)(logits)
    assert g.dtype == logits.dtype
    g_plain = jax.grad(
        lambda lg: jnp.sum(_xent_plain(lg, lbl)))(
        logits.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_plain), atol=0.02)
