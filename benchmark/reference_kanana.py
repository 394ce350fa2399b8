"""Plain reference of the Kanana-2-30B-A3B block (`model_type:
deepseek_v3`): float32 `jax.numpy`, a loop over the experts (every expert on every
row), no kernels, no cache, no batching. Independent of `paddle_tpu`: it imports
nothing from the program, and takes the weights as a plain dict.

Follows the published model (Hugging Face `modeling_deepseek_v3`, config
`kakaocorp/kanana-2-30b-a3b-instruct-2601`), x [S, d]:

    h   = x + Wo . Attn(n1)                             n1 = RMS_1(x)
    out = h + FFN_l(n2)                                 n2 = RMS_2(h)
    logits = W_head . RMS_f(x_L)

Attention, H heads, `q_lora_rank` null:
    q = n1 Wq -> [S, H, nope + rope] = q_nope | q_rope
    n1 Wkva  -> [S, rank + rope]    = c | k_rope (one for all heads)
    c = RMS_kv(c);  c Wkvb -> [S, H, nope + v] = k_nope | v
    RoPE(theta) on q_rope and k_rope only
    scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope),
    causal softmax, o = P v, heads concatenated, then Wo.
FFN of layer l < `first_k_dense_replace`: (silu(n2 Wg) * (n2 Wu)) Wd.
FFN of the other layers (`n_group` = `topk_group` = 1):
    s = sigmoid(n2 Wr) [S, E];  chosen = top k of s + b  (b the
    `e_score_correction_bias`: it chooses and never weighs)
    w_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor
    out = sum_e w_e . Wd_e(silu(Wg_e n2) * Wu_e n2) + shared(n2),
    `shared` one gated-SiLU expert of width n_shared_experts x the
    routed experts' width. Ties in the top-k go to the lower index.

RMS(x) = x / sqrt(mean(x^2) + eps) * g. No bias anywhere but b, no
positional table, an untied head.

Departures from the published `transformers` code, each the same
function: (1) RoPE is applied in the interleaved form the config asks
for (`rope_interleave: true`): dimensions (2i, 2i+1) are a pair, angle
pos x theta^(-2i/rope). The published code first de-interleaves q_rope
and k_rope and then rotates halves: the same rotation up to one fixed
permutation of the rope dimensions common to q and k, which their dot
product does not see. (2) `norm_topk_prob` false and `n_group` > 1 are
not written: this configuration has neither. (3) `rope_scaling` is null,
so there is no scale correction.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, each layer `{"ln1", "ln2": g [d],
"q": [d, H (nope + rope)], "kva": [d, rank + rope], "kv_norm": g [rank],
"kvb": [rank, H (nope + v)], "out": [H v, d]}` and either the dense FFN
`{"gate", "up": [d, F], "down": [F, d]}` or the experts `{"router":
[d, E], "router_bias": [E], "gate", "up": [E, d, h], "down": [E, h, d],
"shared_gate", "shared_up": [d, hs], "shared_down": [hs, d]}`: a layer
with a "router" has experts.

Hyper-parameters: `Hyper.of(config)`, by the published keys.

Forced routes (`logits_on_routes`). Where a token's k-th and (k+1)-th
choosing scores lie closer than the rounding of a lower matmul
precision, a program that is right chooses the other expert, and its
logits then differ from this reference's by a whole expert's output. So
the reference can be told the experts the program chose, [Le, S, k]
(Le: the layers with experts, in order): it then computes the same
equations with those experts and ITS OWN weights for them, and reports
for every such layer and token how far the program's choice is from
its own, on the score that chooses: the shortfall 1 - (smallest s + b
of the forced experts) / (its own k-th s + b): 0 where the two sets are
equal, a few hundredths at a near tie, large for an expert the
reference would never choose.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    n_head: int
    top_k: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    eps: float = 1e-6
    theta: float = 1000000.0
    routed_scale: float = 1.0
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: what a check's limits
    #: must fail (the nearest precision below the configuration's)

    @classmethod
    def of(cls, config) -> "Hyper":
        if not config["norm_topk_prob"] or config["n_group"] != 1 \
                or config["topk_group"] != 1 \
                or config.get("q_lora_rank") is not None \
                or config.get("rope_scaling") is not None \
                or not config["rope_interleave"] \
                or config["scoring_func"] != "sigmoid":
            raise ValueError("this reference writes sigmoid scores "
                             "renormalised over one group, a full-rank "
                             "query and plain interleaved RoPE only")
        return cls(int(config["num_attention_heads"]),
                   int(config["num_experts_per_tok"]),
                   int(config["kv_lora_rank"]),
                   int(config["qk_nope_head_dim"]),
                   int(config["qk_rope_head_dim"]),
                   int(config["v_head_dim"]),
                   float(config["rms_norm_eps"]),
                   float(config["rope_theta"]),
                   float(config["routed_scaling_factor"]))


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used, so the
    bfloat16 form never holds a second copy of the model."""
    return x @ w.astype(x.dtype)


def _rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)).astype(x.dtype)


def _rope(t, theta):
    """t [S, H, D] at positions 0..S-1, pairs (2i, 2i+1)."""
    seq, _, d = t.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]                   # [S, 1, D/2]
    tf = t.astype(jnp.float32)
    a, b = tf[..., 0::2], tf[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      b * jnp.cos(ang) + a * jnp.sin(ang)],
                     axis=-1).reshape(t.shape).astype(t.dtype)


def _attention(x, layer, hp):
    seq = x.shape[0]
    q = _mm(x, layer["q"]).reshape(seq, hp.n_head, hp.nope + hp.rope)
    q_nope, q_rope = q[..., :hp.nope], _rope(q[..., hp.nope:], hp.theta)
    kva = _mm(x, layer["kva"])
    c = _rms(kva[:, :hp.rank], layer["kv_norm"], hp.eps)
    k_rope = _rope(kva[:, None, hp.rank:], hp.theta)       # [S, 1, rope]
    kv = _mm(c, layer["kvb"]).reshape(seq, hp.n_head, hp.nope + hp.v_dim)
    k_nope, v = kv[..., :hp.nope], kv[..., hp.nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0])
              ).astype(jnp.float32) / jnp.sqrt(
                  jnp.float32(hp.nope + hp.rope))
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("hqk,khd->qhd", p, v)
    return _mm(ctx.reshape(seq, hp.n_head * hp.v_dim), layer["out"])


def _gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _route(x, layer, hp, forced=None):
    """x [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert, 0 off the chosen; the
    shortfall [S] of `forced` [S, k], which then takes the place of the
    reference's own choice)."""
    s = jax.nn.sigmoid(_mm(x, layer["router"]).astype(jnp.float32))
    by = s + layer["router_bias"].astype(jnp.float32)
    rows = jnp.arange(s.shape[0])[:, None]
    own = jnp.argsort(-by, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(by[rows, chosen], axis=-1) \
        / by[rows, own][:, -1]
    mask = jnp.zeros(s.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * hp.routed_scale
    return chosen, w, shortfall


def _experts(x, layer, w):
    """Every expert on every row, weighed by w [S, E] (0 off a row's
    chosen): a loop over the experts, written as a scan so that 128 of
    them compile as one body."""
    def one(acc, expert):
        gate, up, down, col = expert
        return acc + col[:, None].astype(jnp.float32) * _gated(
            x, gate, up, down).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"], w.T))
    out = out + _gated(x, layer["shared_gate"], layer["shared_up"],
                       layer["shared_down"]).astype(jnp.float32)
    return out.astype(x.dtype)


def _head(x, weights, hp, block=16384):
    """The head in column blocks: [S, 128 k] at the highest precision
    must fit beside a chip's worth of weights."""
    n = _rms(x, weights["ln_f"], hp.eps)
    vocab = weights["head"].shape[1]
    return jnp.concatenate(
        [_mm(n, weights["head"][:, i:i + block]).astype(jnp.float32)
         for i in range(0, vocab, block)], axis=-1)


def _forward_one(weights, ids, hp, forced=None):
    """ids [S] -> (logits [S, V] float32, chosen experts [Le, S, k],
    shortfall [Le, S] of `forced` [Le, S, k])."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, shortfalls = [], []
    for layer in weights["layers"]:
        x = x + _attention(_rms(x, layer["ln1"], hp.eps), layer, hp)
        n2 = _rms(x, layer["ln2"], hp.eps)
        if "router" not in layer:
            x = x + _gated(n2, layer["gate"], layer["up"], layer["down"])
            continue
        chosen, w, shortfall = _route(
            n2, layer, hp, None if forced is None else forced[len(routes)])
        routes.append(chosen)
        shortfalls.append(shortfall)
        x = x + _experts(n2, layer, w.astype(x.dtype))
    return _head(x, weights, hp), jnp.stack(routes), jnp.stack(shortfalls)


@functools.partial(jax.jit, static_argnames=("hp",))
def _forward_jit(weights, ids, hp, forced=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(weights, ids, hp, forced)


def nll_sum(weights, ids, targets, hp):
    """Summed next-token cross entropy of one sequence; differentiable in
    `weights` (the trainer's gradients are checked against its grad)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            _forward_one(weights, ids, hp)[0], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                            axis=-1))


def logits(weights, ids, hp):
    """Full causal forward of one sequence: ids [S] -> logits [S, V]."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[0]


def chosen_experts(weights, ids, hp):
    """The experts every token chose in every layer that has them:
    [Le, S, k], each row sorted by s + b, highest first."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[1]


def logits_on_routes(weights, ids, hp, routes):
    """The full causal forward with every token's experts forced to
    `routes` [Le, S, k] (what a program chose): (logits [S, V],
    shortfall [Le, S]), as the module's text says."""
    logits, _, shortfall = _forward_jit(
        weights, jnp.asarray(ids, jnp.int32), hp,
        jnp.asarray(routes, jnp.int32))
    return logits, shortfall
