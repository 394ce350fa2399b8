"""Shared on-chip timing harness for the profiling tools.

Every timing ends in an actual value fetch (written for a remote
control plane where `block_until_ready` alone did not synchronize and a
fetch cost ~1 s regardless of payload). So a measurement runs the same jitted
grad-step scan at TWO lengths, times each INCLUDING the scalar fetch,
and differences out the fixed dispatch+fetch cost:

    ms/step = (T(steps) - T(base)) / (steps - base)

min over `windows` repetitions is the least-contended estimate (same
policy as bench.py).
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp


def time_grad_steps(fn, args, steps=100, base=10, windows=3, lr=1e-6):
    """ms per train step of `fn(args) -> scalar-able value`, fwd+bwd.

    Each scan iteration takes value_and_grad of sum(fn(carry)) and folds
    the grads back into the carry so the loop has a data dependency XLA
    cannot hoist."""
    def make(n):
        @jax.jit
        def loop(a):
            def one(c, _):
                loss, g = jax.value_and_grad(
                    lambda c: jnp.sum(fn(c).astype(jnp.float32)))(c)
                c2 = jax.tree.map(
                    lambda p, gg: p - lr * gg.astype(p.dtype), c, g)
                return c2, loss
            _, losses = jax.lax.scan(one, a, None, length=n)
            return losses[-1]
        return loop

    big, small = make(steps), make(base)
    float(np.asarray(big(args)))    # compile + warm
    float(np.asarray(small(args)))
    best = float("inf")
    for _ in range(windows):
        t0 = time.time()
        float(np.asarray(small(args)))
        t_small = time.time() - t0
        t0 = time.time()
        float(np.asarray(big(args)))
        t_big = time.time() - t0
        best = min(best, (t_big - t_small) / (steps - base))
    return max(best, 0.0) * 1000.0
