"""Honest on-chip micro-timing (ONE shared implementation).

Three rules, each discovered by a wrong number on an earlier remote
set-up and kept because they cost nothing on a local chip:
  1. repeated identical dispatches may be deduped by a remote control
     plane — seed a carry leaf per repetition;
  2. end every timing in a value fetch, not only `block_until_ready` —
     fetch a scalar probe built from EVERY carry leaf (probing one leaf lets XLA dead-code-
     eliminate the whole loop when that leaf is carried unchanged);
  3. a single (n, 2n) window pair is at the mercy of ±30 ms contention
     noise on the fixed dispatch cost — difference well-separated
     windows and keep the marginal work ≳150 ms.

Also: chains must CHANGE float values (a `w + tiny` nudge that rounds
away is a fixed point, and weight-only chains under-measured a conv
backward by 100x) — chain through the big tensors, with decay to keep
values bounded.

Callers: utils/gconv_autotune.py, kernels/fused_conv.py.
"""

from __future__ import annotations

import time


def time_step(step, carry, iters: int, reps: int = 3,
              window_mult: int = 3) -> float:
    """Per-iteration seconds of `carry = step(carry)` on the default
    device.  `step` must chain its big tensors (see module docstring)."""
    import jax
    import jax.numpy as jnp

    def probe(c):
        return sum(leaf.reshape(-1)[0].astype(jnp.float32)
                   for leaf in jax.tree_util.tree_leaves(c))

    def seeded(c, s):
        leaves, treedef = jax.tree_util.tree_flatten(c)
        leaves = [(l.astype(jnp.float32) + s).astype(l.dtype)
                  for l in leaves]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def run(n):
        f = jax.jit(lambda c, s: probe(jax.lax.fori_loop(
            0, n, lambda i, c: step(c), seeded(c, s))))
        ts = []
        for r in range(reps + 1):
            t0 = time.perf_counter()
            float(f(carry, jnp.float32(r * 1e-3)))
            ts.append(time.perf_counter() - t0)
        return min(ts[1:])   # rep 0 pays compile

    t1 = run(iters)
    t2 = run(window_mult * iters)
    return max(t2 - t1, 1e-9) / ((window_mult - 1) * iters)
