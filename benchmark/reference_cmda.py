"""Plain reference of the Command A+ block (`model_type: cohere2_moe`,
`CohereLabs/command-a-plus-05-2026`): float32 `jax.numpy`, a loop over the
experts, no kernels, no cache, no batching. Independent of `paddle_tpu`: it
imports nothing from the program, and takes the weights as a plain dict.

One layer, x [S, d], kind `layer_types[l]` (`use_parallel_block`: ONE norm
a layer, attention and experts both read it, both are added to x):

    h  = (x - mean(x)) / sqrt(var(x) + eps) * g_l            (no bias)
    q  = h Wq -> [S, H, D]   k = h Wk, v = h Wv -> [S, H_kv, D]   (no bias,
                                                             no q/k-norm)
    sliding_attention: q and k rotated by the token's position over all D,
                       pairs (2i, 2i + 1) ("rope_gptj"), angle
                       pos x theta^(-2i / D); row t reads s with s <= t and
                       t - s < window
    full_attention:    no rotation at all; row t reads every s <= t
    query head j reads K/V head j // (H / H_kv); scores q.k / sqrt(D),
    softmax in float32; a = concat(heads) Wo
    s  = sigmoid(h Wr) in R^E (float32); T = the k largest of s (of equal
         scores the lower index); w_e = s_e / sum_{e' in T} s_e'
    routed = sum_{e in T, e held here} w_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    shared = 1/n sum_{j < n} (silu(h Sg_j) * (h Su_j)) Sd_j
    x' = x + a + routed + shared
    logits = logit_scale * LN_f(x_L) E^T,   E the embedding (tied)

A SHARE of the experts: the router has all E columns, the weights given
hold experts `first .. first + count - 1` alone (`Hyper.first`; count is
the weights' own leading axis) and `routed` sums over those of a token's
k that are held. The sixteen shares' routed parts and the shared experts
counted once add up to the uncut layer (`layer_parts`).

What is read into the published config, each an inference the
configuration file lists under `assumed`: the four shared experts'
outputs are averaged and added to the routed sum; `intermediate_size` is
the width of one routed and of one shared expert; the window counts the
token itself; full layers carry no positions; no selection bias, no
routed scale.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "layers": [...]}`, every
matrix `[in, out]`, each layer `{"ln": g [d], "q": [d, H D], "k", "v":
[d, H_kv D], "out": [H D, d], "router": [d, E], "gate", "up": [C, d, f],
"down": [C, f, d], "shared_gate", "shared_up": [d, n f], "shared_down":
[n f, d]}`: the n shared experts side by side, expert j the j-th f
columns of gate and up and the j-th f rows of down.

Forced routes (`logits_on_routes`): where a token's k-th and (k+1)-th
scores lie closer than the rounding of a lower matmul precision, a
program that is right chooses the other expert, and its logits then
differ by a whole expert's output. So the reference can be told the
experts the program chose, [L, S, k]: it computes the same equations
with those experts and ITS OWN weights for them, and reports for every
layer and token the shortfall 1 - (smallest s of the forced experts) /
(its own k-th s): 0 where the sets are equal, a few hundredths at a near
tie, large for an expert the reference would never choose.

`Hyper`'s last fields are not the model's: each makes the reference
WRONG in one part, for the tool that shows a check's limits fail it
(`benchmark/tools/cmda_check_readings.py`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    n_head: int
    n_kv: int
    head_dim: int
    window: int
    kinds: Tuple[str, ...]    #: a layer's kind, "sliding_attention" |
    #: "full_attention", layer l taking entry l
    top_k: int
    n_shared: int
    first: int = 0            #: the first expert the weights hold
    eps: float = 1e-5
    theta: float = 50000.0
    logit_scale: float = 1.0
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: what a check's limits
    #: must fail (the nearest precision below the configuration's)
    # -- faults, one at a time ------------------------------------------
    window_off: int = 0       #: the window this many rows long or short
    rotate: str = "window"    #: "all": full layers rotated too | "none"
    page: int = 0             #: > 0: the oldest page of a window read
    #: whole (rows behind the window in it unmasked)
    shared: str = "average"   #: "sum"
    pairing: str = "blocked"  #: "strided": head j reads K/V head j % H_kv
    drop: bool = False        #: a token's weakest held pair left out

    @classmethod
    def of(cls, config) -> "Hyper":
        if config["expert_selection_fn"] != "sigmoid" \
                or not config["norm_topk_prob"] \
                or config["position_embedding_type"] != "rope_gptj" \
                or config["rotary_pct"] != 1 \
                or config["first_k_dense_replace"] \
                or config["shared_expert_combination_strategy"] \
                != "average" \
                or not (config["use_parallel_block"]
                        and config["tie_word_embeddings"]
                        and config["use_gated_activation"]) \
                or config["use_qk_norm"] or config["attention_bias"] \
                or config["hidden_act"] != "silu":
            raise ValueError(
                "this reference writes the parallel block with a tied "
                "head, interleaved rotary positions over the whole head, "
                "gated SiLU experts under a sigmoid router renormalised "
                "over the chosen, shared experts averaged, no leading "
                "dense layer, no bias and no q/k-norm only")
        layers = int(config["num_hidden_layers"])
        held = config.get("published", {}).get("held_experts", {})
        return cls(int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]),
                   int(config["head_dim"]), int(config["sliding_window"]),
                   tuple(config["layer_types"][:layers]),
                   int(config["num_experts_per_tok"]),
                   int(config["num_shared_experts"]),
                   int(held.get("first", 0)),
                   float(config["layer_norm_eps"]),
                   float(config["rope_theta"]),
                   float(config["logit_scale"]))


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used, so the
    bfloat16 form never holds a second copy of the model."""
    return x @ w.astype(x.dtype)


def _ln(x, gain, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) / jnp.sqrt(var + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


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


#: query rows of attention at a time: [H, rows, S] scores, so that 6,144
#: rows at the published widths fit beside the weights and the pools
#: (128 x 64 x 6,148 float32: 201 MB)
_ROW_BLOCK = 64


def _attention(h, layer, hp, kind):
    seq = h.shape[0]
    group = hp.n_head // hp.n_kv
    q = _mm(h, layer["q"]).reshape(seq, hp.n_head, hp.head_dim)
    k = _mm(h, layer["k"]).reshape(seq, hp.n_kv, hp.head_dim)
    v = _mm(h, layer["v"]).reshape(seq, hp.n_kv, hp.head_dim)
    local = kind == "sliding_attention"
    if hp.rotate == "all" or (local and hp.rotate == "window"):
        q, k = _rope(q, hp.theta), _rope(k, hp.theta)
    if hp.pairing == "strided":    # the fault: head j reads j % H_kv
        q = q.reshape(seq, group, hp.n_kv, hp.head_dim).transpose(
            0, 2, 1, 3)
    else:
        q = q.reshape(seq, hp.n_kv, group, hp.head_dim)
    cols = jnp.arange(seq)[None, :]
    blocks = -(-seq // _ROW_BLOCK)
    q = jnp.pad(q, ((0, blocks * _ROW_BLOCK - seq),) + ((0, 0),) * 3)

    def block(args):
        qb, start = args                                   # [R, Hkv, G, D]
        rows = start + jnp.arange(_ROW_BLOCK)[:, None]
        seen = cols <= rows
        if local:
            oldest = rows - (hp.window + hp.window_off) + 1
            if hp.page:
                oldest = oldest // hp.page * hp.page
            seen = seen & (cols >= oldest)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k).astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hp.head_dim))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        return jnp.einsum("ngqk,knd->qngd", p, v)

    ctx = jax.lax.map(block, (
        q.reshape((blocks, _ROW_BLOCK) + q.shape[1:]),
        jnp.arange(blocks) * _ROW_BLOCK)).reshape(
            (blocks * _ROW_BLOCK,) + q.shape[1:])[:seq]
    if hp.pairing == "strided":
        ctx = ctx.transpose(0, 2, 1, 3)
    return _mm(ctx.reshape(seq, hp.n_head * hp.head_dim), layer["out"])


def _gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _route(h, layer, hp, forced=None):
    """h [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert, 0 off the chosen; the
    shortfall [S] of `forced` [S, k], which then takes the place of the
    reference's own choice)."""
    s = jax.nn.sigmoid(_mm(h, layer["router"]).astype(jnp.float32))
    rows = jnp.arange(s.shape[0])[:, None]
    own = jnp.argsort(-s, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(s[rows, chosen], axis=-1) / s[rows, own][:, -1]
    mask = jnp.zeros(s.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, s, 0.0)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True), shortfall


def _routed(h, layer, w, hp):
    """The held experts on every row, weighed by their columns of w
    [S, E] (0 off a row's chosen): a loop over the held experts, written
    as a scan so that they compile as one body. float32 [S, d]."""
    count = layer["gate"].shape[0]
    mine = w[:, hp.first:hp.first + count]
    if hp.drop:     # the fault: each token's weakest held pair left out
        weakest = jnp.min(jnp.where(mine > 0, mine, jnp.inf), axis=-1,
                          keepdims=True)
        mine = jnp.where(mine == weakest, 0.0, mine)

    def one(acc, expert):
        gate, up, down, col = expert
        return acc + col[:, None].astype(jnp.float32) * _gated(
            h, gate, up, down).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"],
         mine.T.astype(h.dtype)))
    return out


def _shared(h, layer, hp):
    """The n shared experts, one at a time, averaged. float32 [S, d]."""
    f = layer["shared_gate"].shape[1] // hp.n_shared
    out = jnp.zeros(h.shape, jnp.float32)
    for j in range(hp.n_shared):
        at = slice(j * f, (j + 1) * f)
        out = out + _gated(h, layer["shared_gate"][:, at],
                           layer["shared_up"][:, at],
                           layer["shared_down"][at]).astype(jnp.float32)
    return out if hp.shared == "sum" else out / hp.n_shared


def layer_parts(x, layer, hp, kind, forced=None):
    """One layer's three additions to x [S, d], each float32 [S, d]:
    (attention, this share's routed experts, the shared experts), and
    the experts chosen [S, k] with `forced`'s shortfall [S]."""
    h = _ln(x, layer["ln"], hp.eps)
    chosen, w, shortfall = _route(h, layer, hp, forced)
    return (_attention(h, layer, hp, kind).astype(jnp.float32),
            _routed(h, layer, w, hp), _shared(h, layer, hp),
            chosen, shortfall)


def _forward_one(weights, ids, hp, forced=None, rows=None):
    """ids [S] -> (logits [S, V] float32, or of positions `rows` alone;
    chosen experts [L, S, k]; shortfall [L, S] of `forced` [L, S, k])."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, shortfalls = [], []
    for layer, kind in zip(weights["layers"], hp.kinds):
        a, routed, shared, chosen, shortfall = layer_parts(
            x, layer, hp, kind,
            None if forced is None else forced[len(routes)])
        routes.append(chosen)
        shortfalls.append(shortfall)
        x = (x.astype(jnp.float32) + a + routed + shared).astype(x.dtype)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    n = _ln(x, weights["ln_f"], hp.eps)
    logits = hp.logit_scale * _mm(n, weights["tok_emb"].T).astype(
        jnp.float32)
    return logits, jnp.stack(routes), jnp.stack(shortfalls)


@functools.partial(jax.jit, static_argnames=("hp", "rows"))
def _forward_jit(weights, ids, hp, forced=None, rows=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(weights, ids, hp, forced, rows)


def _rows(rows):
    return None if rows is None else tuple(int(r) for r in rows)


def logits(weights, ids, hp, rows=None):
    """Full causal forward of one sequence: ids [S] -> logits [S, V], or
    of the positions `rows` alone [R, V]."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp,
                        rows=_rows(rows))[0]


def logits_and_choices(weights, ids, hp, rows=None):
    """`logits` and `chosen_experts` of one forward."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp,
                        rows=_rows(rows))[:2]


def chosen_experts(weights, ids, hp):
    """The experts every token chose in every layer: [L, S, k], each row
    sorted by s, highest first."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[1]


def logits_on_routes(weights, ids, hp, routes, rows=None):
    """The full causal forward with every token's experts forced to
    `routes` [L, S, k] (what a program chose): (logits [S, V] or [R, V],
    shortfall [L, S]), as the module's text says."""
    logits, _, shortfall = _forward_jit(
        weights, jnp.asarray(ids, jnp.int32), hp,
        jnp.asarray(routes, jnp.int32), _rows(rows))
    return logits, shortfall
