"""Plain reference of the OLMoE block: float32 `jax.numpy`, a Python
loop over the experts, no kernels, no cache, no batching. Independent of
`paddle_tpu`: it imports nothing from the program, and takes the
weights as a plain dict.

Follows the published model (arXiv:2409.02060; Hugging Face
`modeling_olmoe`, config `allenai/OLMoE-1B-7B-0125-Instruct`):

    h   = x + Wo . Attn(RoPE(split(RMS_q(Wq . n1))),
                        RoPE(split(RMS_k(Wk . n1))), split(Wv . n1))
                                                       n1 = RMS_1(x)
    out = h + sum_{e in top_k(p)} p_e . Wd_e(silu(Wg_e . n2) * Wu_e . n2)
                           n2 = RMS_2(h), p = softmax(Wr . n2) over all E
    logits = W_head . RMS_f(x_L)

RMS(x) = x / sqrt(mean(x^2) + eps) * g; RMS_q and RMS_k act on the
whole projection before it is split into heads; RoPE is the rotate-half
form over the whole head; attention is causal with scale
1/sqrt(head_dim); p is renormalised over the chosen experts only when
`norm_topk_prob` says so (OLMoE's does not); ties in the top-k go to the
lower expert index; no bias anywhere, no positional table, an untied
head.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[{"ln1": g, "ln2": g, "q", "k", "v", "out": [d, d], "q_norm", "k_norm":
g [d], "router": [d, E], "gate", "up": [E, d, h], "down": [E, h, d]}
...]}` with every matrix `[in, out]`.

Hyper-parameters: `Hyper(n_head, top_k, eps, theta, norm_topk_prob)`,
by the published keys `num_attention_heads`, `num_experts_per_tok`,
`rms_norm_eps`, `rope_theta`, `norm_topk_prob` (`Hyper.of(config)`).

Forced routes (`logits_on_routes`). Where a token's k-th and (k+1)-th
gates lie closer than the rounding of a lower matmul precision, a
program that is right chooses the other expert, and its logits then
differ from this reference's by a whole expert's output: a comparison
of logits alone cannot tell that from a fault. So the reference can be
told the experts the program chose, [L, S, k]: it then computes the
same equations with those experts and ITS OWN gates for them, and
reports for every layer and token how far the program's choice is from
its own, the shortfall 1 - (smallest of its gates for the forced
experts) / (its own k-th gate): 0 where the two sets are equal, a few
hundredths at a near tie, towards 1 for an expert the reference would
never choose.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    n_head: int
    top_k: int
    eps: float = 1e-5
    theta: float = 10000.0
    norm_topk_prob: bool = False

    @classmethod
    def of(cls, config) -> "Hyper":
        return cls(int(config["num_attention_heads"]),
                   int(config["num_experts_per_tok"]),
                   float(config["rms_norm_eps"]),
                   float(config["rope_theta"]),
                   bool(config["norm_topk_prob"]))


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _rope(t, theta):
    """t [S, H, D] at positions 0..S-1."""
    seq, _, d = t.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]   # [S, 1, D]
    rot = jnp.concatenate([-t[..., d // 2:], t[..., :d // 2]], axis=-1)
    return t * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(x, layer, hp):
    seq, d_model = x.shape
    d_head = d_model // hp.n_head

    def heads(t):
        return t.reshape(seq, hp.n_head, d_head)

    q = _rope(heads(_rms(x @ layer["q"], layer["q_norm"], hp.eps)),
              hp.theta)
    k = _rope(heads(_rms(x @ layer["k"], layer["k_norm"], hp.eps)),
              hp.theta)
    v = heads(x @ layer["v"])
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.float32(d_head))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return ctx.reshape(seq, d_model) @ layer["out"]


def _route(x, layer, hp, forced=None):
    """x [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert: p on the chosen, 0
    elsewhere; the shortfall [S] of `forced` [S, k], which then takes
    the place of the reference's own choice)."""
    p = jax.nn.softmax(x @ layer["router"], axis=-1)
    rows = jnp.arange(p.shape[0])[:, None]
    own = jnp.argsort(-p, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(p[rows, chosen], axis=-1) \
        / p[rows, own][:, -1]
    mask = jnp.zeros(p.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, p, 0.0)
    if hp.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w, shortfall


def _experts(x, layer, w):
    out = jnp.zeros_like(x)
    for e in range(layer["router"].shape[-1]):
        h = jax.nn.silu(x @ layer["gate"][e]) * (x @ layer["up"][e])
        out = out + w[:, e:e + 1] * (h @ layer["down"][e])
    return out


def _forward_one(weights, ids, hp, forced=None):
    """ids [S] -> (logits [S, V] float32, chosen experts [L, S, k],
    shortfall [L, S] of `forced` [L, S, k])."""
    x = weights["tok_emb"][ids]
    routes, shortfalls = [], []
    for i, layer in enumerate(weights["layers"]):
        x = x + _attention(_rms(x, layer["ln1"], hp.eps), layer, hp)
        n2 = _rms(x, layer["ln2"], hp.eps)
        chosen, w, shortfall = _route(
            n2, layer, hp, None if forced is None else forced[i])
        routes.append(chosen)
        shortfalls.append(shortfall)
        x = x + _experts(n2, layer, w)
    return _rms(x, weights["ln_f"], hp.eps) @ weights["head"], \
        jnp.stack(routes), jnp.stack(shortfalls)


def _f32(weights):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), weights)


@functools.partial(jax.jit, static_argnames=("hp",))
def _forward_jit(weights, ids, hp, forced=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(_f32(weights), ids, hp, forced)


def nll_sum(weights, ids, targets, hp):
    """Summed next-token cross entropy of one sequence; differentiable in
    `weights` (the trainer's gradients are checked against its grad)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            _forward_one(_f32(weights), ids, hp)[0], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                            axis=-1))


def logits(weights, ids, hp):
    """Full causal forward of one sequence: ids [S] -> logits [S, V]."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[0]


def chosen_experts(weights, ids, hp):
    """The experts every token chose in every layer: [L, S, k], each
    row sorted by gate, highest first."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[1]


def logits_on_routes(weights, ids, hp, routes):
    """The full causal forward with every token's experts forced to
    `routes` [L, S, k] (what a program chose): (logits [S, V], shortfall
    [L, S]), as the module's text says."""
    logits, _, shortfall = _forward_jit(
        weights, jnp.asarray(ids, jnp.int32), hp,
        jnp.asarray(routes, jnp.int32))
    return logits, shortfall
