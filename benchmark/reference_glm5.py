"""Plain reference of GLM-5's block (`model_type: glm_moe_dsa`, Hugging
Face `zai-org/GLM-5`): float32 `jax.numpy`, a loop over the held experts
(every one on every row), no kernels, no cache, no batching; the selection
in blocks of query rows and the attention a few heads at a time at the
published widths (no [64, S, S] array is ever whole: the check runs beside
a loaded server). Independent of `paddle_tpu`: it imports nothing from the
program, and takes the weights as a plain dict.

x [S, d]; every norm is an RMS norm, eps 1e-5, no bias anywhere:

    x   = x + Wo . Attn(h)                              h  = RMS_1(x)
    x   = x + FFN(h2)                                   h2 = RMS_2(x)
    logits = W_head . RMS_f(x_L)                        (an untied head)

Latent attention (MLA) with a query low-rank, H = 64 heads:
    cq = RMS(h Wqa; gq) [q_rank 2,048];  q = cq Wqb -> [S, H, nope 192 +
    rope 64] = q_nope | q_rope
    [ckv | kr] = h Wkva [rank 512 + rope 64];  c = RMS(ckv; gkv)
    q_rope of every head and the ONE kr rotated at the token's position,
    pairs (2i, 2i + 1), theta 1e6, plain table
    [k_nope_j | v_j] = c Wkvb a head: 192 + 256
The indexer (DeepSeek sparse attention, after DeepSeek-V3.2-Exp's
published inference code), Hi = 32 heads of Di = 128, one key head:
    qI = cq WIq -> [S, Hi, Di]     (from the query's LOW-RANK, not from h)
    kI = LayerNorm(h WIk; gain, bias, 1e-6) -> [S, Di]
    the FIRST 64 (`qk_rope_head_dim`) of the 128 of qI and kI rotated,
    pairs (2i, 2i + 1), theta 1e6; the other 64 not
    w = h WIw [Hi] * Hi^-1/2 Di^-1/2
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the `index_topk` (2,048) positions s <= t of largest I[t, s]:
    all of them while t < index_topk; of equal scores the lower position
Head j over s in S_t: (q_nope_j . k_nope_j[s] + q_rope_j . kr[s]) /
sqrt(256), softmax in float32 over S_t alone, o_j = sum P v_j[s];
x <- x + concat_j(o_j) Wo.
FFN. A leading dense layer (no "router" among its weights): gated SiLU,
(silu(h2 Wg) * (h2 Wu)) Wd. An expert layer: s = sigmoid(h2 Wr) over ALL
E = 256 experts in float32; the 8 largest of s + b (b the selection bias:
it chooses and never weighs; of equal scores the lower index); gates
routed_scale (2.5) * s_e / sum of the chosen s; THIS CHIP'S PART:
    sum over the chosen e with first <= e < first + held of
    gate_e expert_e(h2)   +   shared(h2)
(`Hyper.first`, and `held` the experts the weights hold). What the other
experts would add is left out; the shared expert is what every chip
computes alike. `Hyper.held_all` True takes `gate` / `up` / `down` as ALL E
experts (a small model's uncut layer: what the shares must add up to).

Departures from the published description, each also in the
configuration's `assumed`: (1) the Hadamard rotation the source applies to
qI and kI before it quantises them to 8 bits is orthogonal on both sides
of a dot product and changes no score in float32: not built. (2)
Multi-token prediction (`num_nextn_predict_layers`) changes no logit of
the model and is left out. (3) The indexer's details (LayerNorm with a
bias at 1e-6, the partial rotation, the weights' scale) are the published
DeepSeek-V3.2-Exp code's, whose key names `index_n_heads`,
`index_head_dim`, `index_topk` the configuration's are.

On a TPU a float32 matmul runs in reduced precision unless asked, so every
entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, each layer `{"ln1", "ln2": g [d],
"qa": [d, q_rank], "q_norm": g [q_rank], "qb": [q_rank, H (nope + rope)],
"kva": [d, rank + rope], "kv_norm": g [rank], "kvb": [rank, H (nope + v)],
"out": [H v, d], "iq": [q_rank, Hi Di], "ik": [d, Di], "iw": [d, Hi],
"ik_norm", "ik_bias": [Di]}` and either `{"gate", "up": [d, w], "down":
[w, d]}` (dense) or `{"router": [d, E], "router_bias": [E], "gate", "up":
[held, d, h], "down": [held, h, d], "shared_gate", "shared_up": [d, h],
"shared_down": [h, d]}`.

Forced choices (`logits_on`), as `reference_keye.py` has them and for its
reasons: the experts of every expert layer and token, [Le, S, k], and what
every row's attention read, bool [L, S, S]. Shortfalls: for the experts
1 - (smallest s + b of the forced experts) / (its own k-th s + b); for a
row's positions (its own topk-th I[t, .] - the smallest forced I[t, .]) /
(the standard deviation of I[t, s <= t]), and 1e9 where the forced
positions are not min(t + 1, topk) positions s <= t.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: query rows a block of the selection holds, and heads a block of the
#: attention
Q_BLOCK = 256
HEAD_BLOCK = 4


class Hyper(NamedTuple):
    n_head: int
    top_k: int              #: experts a token
    q_rank: int
    rank: int
    nope: int
    rope: int
    v_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    first: int = 0          #: the first expert the weights hold
    eps: float = 1e-5
    theta: float = 1000000.0
    routed_scale: float = 2.5
    held_all: bool = False  #: the weights hold every expert
    dtype: str = "float32"  #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: the nearest precision
    #: below the configuration's, which a check's limits must fail
    # The next four make the reference WRONG on purpose, for a check's
    # readings (what its limits must fail); the model is the defaults.
    select: str = "topk"    #: "all": the selection ignored | "newest": the
    #: newest index_topk rows in place of the top index_topk
    index_from: str = "cq"  #: "h": qI projected from the first q_rank
    #: columns of h in place of the query's low-rank
    index_turn: int = 0     #: the indexer's rotated width (0: `rope`);
    #: `index_dim`: rotated over all of it
    q_norm: bool = True     #: False: the query's low-rank norm dropped

    @classmethod
    def of(cls, config) -> "Hyper":
        rope = config.get("rope_parameters") or {}
        if not config["norm_topk_prob"] or config["n_group"] != 1 \
                or config["topk_group"] != 1 \
                or rope.get("rope_type", "default") != "default" \
                or not config["rope_interleave"] \
                or not config["indexer_rope_interleave"] \
                or config["scoring_func"] != "sigmoid" \
                or config["topk_method"] != "noaux_tc" \
                or config["attention_bias"] \
                or config["tie_word_embeddings"] \
                or config["hidden_act"] != "silu" \
                or config["n_shared_experts"] != 1:
            raise ValueError("this reference writes sigmoid scores with a "
                             "selection bias renormalised over one group, "
                             "one shared expert, an untied head, no bias "
                             "and plain interleaved RoPE only")
        held = (config.get("published") or {}).get("held_experts") or {}
        return cls(int(config["num_attention_heads"]),
                   int(config["num_experts_per_tok"]),
                   int(config["q_lora_rank"]),
                   int(config["kv_lora_rank"]),
                   int(config["qk_nope_head_dim"]),
                   int(config["qk_rope_head_dim"]),
                   int(config["v_head_dim"]),
                   int(config["index_n_heads"]),
                   int(config["index_head_dim"]),
                   int(config["index_topk"]),
                   int(held.get("first", 0)),
                   float(config["rms_norm_eps"]),
                   float(rope.get("rope_theta", 1000000.0)),
                   float(config["routed_scaling_factor"]),
                   held_all=not held)


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used, so the
    bfloat16 form never holds a second copy of the model."""
    return x @ w.astype(x.dtype)


def _rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)).astype(x.dtype)


def _layer_norm(x, gain, bias, eps):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    return (xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope(t, theta, width=None):
    """t [S, H, D] at positions 0..S-1: the first `width` of D (all of
    it unless said) rotated in pairs (2i, 2i + 1), the rest as it is."""
    seq, _, d = t.shape
    width = d if width is None else width
    inv_freq = 1.0 / theta ** (jnp.arange(0, width, 2, dtype=jnp.float32)
                               / width)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]                   # [S, 1, W/2]
    tf = t.astype(jnp.float32)
    a, b = tf[..., 0:width:2], tf[..., 1:width:2]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        b * jnp.cos(ang) + a * jnp.sin(ang)],
                       axis=-1).reshape(tf.shape[:-1] + (width,))
    return jnp.concatenate([turned, tf[..., width:]],
                           axis=-1).astype(t.dtype)


def _index_scores(qi, ki, w):
    """qI [Q, Hi, Di], kI [S, Di], w [Q, Hi] -> I [Q, S] float32."""
    dots = jnp.einsum("qhd,kd->qhk", qi, ki).astype(jnp.float32)
    return jnp.einsum("qh,qhk->qk", w.astype(jnp.float32),
                      jnp.maximum(dots, 0.0))


def _top_mask(scores, allowed, topk):
    """bool [Q, S]: of each row's `allowed` positions the topk of largest
    score, of equal scores the lower position."""
    order = jnp.argsort(jnp.where(allowed, -scores, jnp.inf), axis=-1,
                        stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return allowed & (rank < topk)


def _selection(x, cq, layer, hp, forced=None):
    """What every row reads, bool [S, S], and the shortfall [S] of
    `forced` [S, S] bool, which then takes the place of the reference's
    own selection (zeros without one)."""
    seq = x.shape[0]
    turn = hp.index_turn or hp.rope
    q_from = x[:, :hp.q_rank] if hp.index_from == "h" else cq
    qi = _rope(_mm(q_from, layer["iq"]).reshape(seq, hp.index_heads,
                                                hp.index_dim),
               hp.theta, turn)
    ki = _rope(_layer_norm(_mm(x, layer["ik"]), layer["ik_norm"],
                           layer["ik_bias"], 1e-6)[:, None],
               hp.theta, turn)[:, 0]
    w = _mm(x, layer["iw"]) * (hp.index_heads * hp.index_dim) ** -0.5
    kpos = jnp.arange(seq)
    n_blocks = -(-seq // Q_BLOCK)
    pad = n_blocks * Q_BLOCK - seq

    def blocks(t):             # [S, ...] -> [n_blocks, Q_BLOCK, ...]
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((n_blocks, Q_BLOCK) + t.shape[1:])

    xs = [jnp.arange(n_blocks) * Q_BLOCK, blocks(qi), blocks(w)]
    if forced is not None:
        xs.append(blocks(forced))

    def one(_, block):
        start = block[0]
        rows = start + jnp.arange(Q_BLOCK)
        causal = kpos[None] <= rows[:, None]               # [Q, S]
        scores = _index_scores(block[1], ki, block[2])
        top = _top_mask(scores, causal, hp.index_topk)
        mask = {"topk": top, "all": causal,
                "newest": causal & (kpos[None] > rows[:, None]
                                    - hp.index_topk)}[hp.select]
        if forced is None:
            return None, (mask, jnp.zeros((Q_BLOCK,), jnp.float32))
        # a padding row past the sequence reads as any other row
        given = jnp.where((rows < seq)[:, None], block[3], causal)
        count = jnp.minimum(rows + 1, hp.index_topk)
        sound = (jnp.sum(given & causal, axis=1) == count) \
            & ~jnp.any(given & ~causal, axis=1)
        kth = jnp.min(jnp.where(top, scores, jnp.inf), axis=1)
        weakest = jnp.min(jnp.where(given, scores, jnp.inf), axis=1)
        n = jnp.sum(causal, axis=1)
        mean = jnp.sum(jnp.where(causal, scores, 0.0), axis=1) / n
        std = jnp.sqrt(jnp.sum(jnp.where(
            causal, jnp.square(scores - mean[:, None]), 0.0), axis=1) / n)
        shortfall = jnp.where(
            sound, jnp.maximum(kth - weakest, 0.0)
            / jnp.maximum(std, 1e-30), 1e9)
        return None, (jnp.where(sound[:, None], given, causal), shortfall)

    _, (masks, shortfall) = jax.lax.scan(one, None, tuple(xs))
    return masks.reshape(n_blocks * Q_BLOCK, seq)[:seq], \
        shortfall.reshape(-1)[:seq]


def _attention(x, layer, hp, forced=None):
    """x [S, d] (normed) -> (out [S, d]; what every row read, bool
    [S, S]; the shortfall [S] of `forced`)."""
    seq = x.shape[0]
    cq = _mm(x, layer["qa"])
    if hp.q_norm:
        cq = _rms(cq, layer["q_norm"], hp.eps)
    mask, shortfall = _selection(x, cq, layer, hp, forced)
    kva = _mm(x, layer["kva"])
    c = _rms(kva[:, :hp.rank], layer["kv_norm"], hp.eps)
    k_rope = _rope(kva[:, None, hp.rank:], hp.theta)[:, 0]   # [S, rope]
    groups = hp.n_head // HEAD_BLOCK if hp.n_head % HEAD_BLOCK == 0 else 1
    per = hp.n_head // groups
    qb = layer["qb"].reshape(hp.q_rank, groups, per * (hp.nope + hp.rope))
    kvb = layer["kvb"].reshape(hp.rank, groups, per * (hp.nope + hp.v_dim))
    out_w = layer["out"].reshape(groups, per * hp.v_dim, -1)

    def heads(acc, ws):
        wq, wkv, wo = ws
        q = _mm(cq, wq).reshape(seq, per, hp.nope + hp.rope)
        q_nope, q_rope = q[..., :hp.nope], _rope(q[..., hp.nope:], hp.theta)
        kv = _mm(c, wkv).reshape(seq, per, hp.nope + hp.v_dim)
        s = (jnp.einsum("qhd,khd->hqk", q_nope, kv[..., :hp.nope])
             + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)
             ).astype(jnp.float32) / jnp.sqrt(
                 jnp.float32(hp.nope + hp.rope))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf),
                           axis=-1).astype(x.dtype)
        ctx = jnp.einsum("hqk,khd->qhd", p, kv[..., hp.nope:])
        return acc + _mm(ctx.reshape(seq, per * hp.v_dim),
                         wo).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        heads, jnp.zeros((seq, out_w.shape[-1]), jnp.float32),
        (jnp.moveaxis(qb, 1, 0), jnp.moveaxis(kvb, 1, 0), out_w))
    return out.astype(x.dtype), mask, shortfall


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


def _experts(x, layer, w, hp, shared=True):
    """The held experts on every row, weighed by their columns of w
    [S, E] (0 off a row's chosen), and the shared expert: a loop over
    the experts, written as a scan."""
    held = layer["gate"].shape[0]
    first = 0 if hp.held_all else hp.first
    cols = jax.lax.dynamic_slice_in_dim(w, first, held, axis=1)

    def one(acc, expert):
        gate, up, down, col = expert
        return acc + col[:, None].astype(jnp.float32) * _gated(
            x, gate, up, down).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"], cols.T))
    if shared:
        out = out + _gated(x, layer["shared_gate"], layer["shared_up"],
                           layer["shared_down"]).astype(jnp.float32)
    return out.astype(x.dtype)


def _head(x, weights, hp, block=16384):
    """The head in column blocks, for the rows it is asked of."""
    n = _rms(x, weights["ln_f"], hp.eps)
    vocab = weights["head"].shape[1]
    return jnp.concatenate(
        [_mm(n, weights["head"][:, i:i + block]).astype(jnp.float32)
         for i in range(0, vocab, block)], axis=-1)


def _forward_one(weights, ids, hp, rows=None, forced_routes=None,
                 forced_masks=None):
    """ids [S] -> (logits [S, V] float32, or of `rows` alone; chosen
    experts [Le, S, k]; their shortfall [Le, S]; what every row's
    attention read, bool [L, S, S]; its shortfall [L, S])."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, shortfalls, masks, sel_shortfalls = [], [], [], []
    for n, layer in enumerate(weights["layers"]):
        att, mask, sel_short = _attention(
            _rms(x, layer["ln1"], hp.eps), layer, hp,
            None if forced_masks is None else forced_masks[n])
        masks.append(mask)
        sel_shortfalls.append(sel_short)
        x = x + att
        h2 = _rms(x, layer["ln2"], hp.eps)
        if "router" not in layer:
            x = x + _gated(h2, layer["gate"], layer["up"], layer["down"])
            continue
        chosen, w, shortfall = _route(
            h2, layer, hp,
            None if forced_routes is None else forced_routes[len(routes)])
        routes.append(chosen)
        shortfalls.append(shortfall)
        x = x + _experts(h2, layer, w.astype(x.dtype), hp)
    return (_head(x if rows is None else x[rows], weights, hp),
            jnp.stack(routes), jnp.stack(shortfalls), jnp.stack(masks),
            jnp.stack(sel_shortfalls))


@functools.partial(jax.jit, static_argnames=("hp",))
def _forward_jit(weights, ids, hp, rows=None, forced_routes=None,
                 forced_masks=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(weights, ids, hp, rows, forced_routes,
                            forced_masks)


def _ints(x):
    return None if x is None else jnp.asarray(x, jnp.int32)


def logits(weights, ids, hp, rows=None):
    """Full causal forward of one sequence: ids [S] -> logits [S, V], or
    [len(rows), V] for the positions `rows` alone."""
    return _forward_jit(weights, _ints(ids), hp, _ints(rows))[0]


def choices(weights, ids, hp):
    """The reference's own choices: the experts of every expert layer and
    token [Le, S, k], each row sorted by s + b, highest first, and what
    every row's attention read, bool [L, S, S]."""
    out = _forward_jit(weights, _ints(ids), hp, jnp.zeros((1,), jnp.int32))
    return out[1], out[3]


def logits_on(weights, ids, hp, routes, masks=None, rows=None):
    """The full causal forward with every token's experts forced to
    `routes` [Le, S, k] and what every row's attention read to `masks`
    [L, S, S] bool (what a program chose): (logits [S, V], or of `rows`
    alone; the experts' shortfall [Le, S]; the selections' [L, S]), as
    the module's text says."""
    out = _forward_jit(weights, _ints(ids), hp, _ints(rows), _ints(routes),
                       None if masks is None else jnp.asarray(masks, bool))
    return out[0], out[2], out[4]


def layer_ffn(weights_layer, h2, hp, shared=True):
    """One expert layer's FFN on h2 [S, d] by itself, (the sum, the
    chosen experts): what `tests/test_glm5.py` adds the shares up with
    (each share with `shared` False, the shared expert counted once)."""
    with jax.default_matmul_precision("highest"):
        chosen, w, _ = _route(h2, weights_layer, hp)
        return _experts(h2, weights_layer, w, hp, shared), chosen
