"""Plain reference of the GPT-2 block: float32 `jax.numpy`, no kernels,
no cache, no batching tricks. Independent of `paddle_tpu`: it imports
nothing from the program, and takes the weights as a plain dict.

Follows the published description of GPT-2 / Cerebras-GPT (arXiv
2304.03208, table 1): learned positions, pre-LayerNorm blocks
`x + MHA(LN(x))`, `x + FFN(LN(x))` with erf-GELU, a final LayerNorm and
a linear head. Departures, both shared with the program under test and
listed under `assumed` in every configuration file: the head has its
own weight and bias (GPT-2 ties it to the token embedding), and GELU is
the exact erf form (GPT-2's original code used the tanh approximation).

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "pos_emb": [P, d], "ln_f": (scale, bias),
"head": (w [d, V], b [V]), "layers": [ {"ln1": (s, b), "ln2": (s, b),
"q": (w, b), "k": (w, b), "v": (w, b), "out": (w, b), "ffn_in": (w, b),
"ffn_out": (w, b)} ... ]}` with every matrix `[in, out]`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * scale + bias


def _linear(x, wb):
    w, b = wb
    return x @ w + b


def _attention(x, layer, n_head):
    seq, d_model = x.shape
    d_head = d_model // n_head

    def heads(t):
        return t.reshape(seq, n_head, d_head).transpose(1, 0, 2)

    q, k, v = (heads(_linear(x, layer[n])) for n in ("q", "k", "v"))
    scores = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(
        jnp.float32(d_head))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v)
    return _linear(ctx.transpose(1, 0, 2).reshape(seq, d_model),
                   layer["out"])


def _forward_one(weights, ids, n_head):
    """ids [S] -> logits [S, V], float32."""
    x = weights["tok_emb"][ids] + weights["pos_emb"][:ids.shape[0]]
    for layer in weights["layers"]:
        x = x + _attention(_layer_norm(x, *layer["ln1"]), layer, n_head)
        h = jax.nn.gelu(_linear(_layer_norm(x, *layer["ln2"]),
                                layer["ffn_in"]), approximate=False)
        x = x + _linear(h, layer["ffn_out"])
    return _linear(_layer_norm(x, *weights["ln_f"]), weights["head"])


@functools.partial(jax.jit, static_argnames=("n_head",))
def _logits_jit(weights, ids, n_head):
    with jax.default_matmul_precision("highest"):
        weights = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), weights)
        return _forward_one(weights, ids, n_head)


@functools.partial(jax.jit, static_argnames=("n_head",))
def _nll_jit(weights, ids, targets, n_head):
    with jax.default_matmul_precision("highest"):
        weights = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), weights)
        logp = jax.nn.log_softmax(_forward_one(weights, ids, n_head),
                                  axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                            axis=-1))


def logits(weights, ids, n_head):
    """Full causal forward of one sequence: ids [S] -> logits [S, V]."""
    return _logits_jit(weights, jnp.asarray(ids, jnp.int32), n_head)


def mean_loss(weights, ids, targets, n_head):
    """Mean next-token cross entropy over a batch, one sequence at a time
    so the [S, V] logits of only one sequence live at once.
    ids, targets: [B, S] integers."""
    total = 0.0
    for row, tgt in zip(ids, targets):
        total += float(_nll_jit(weights, jnp.asarray(row, jnp.int32),
                                jnp.asarray(tgt, jnp.int32), n_head))
    return total / (len(ids) * len(ids[0]))
