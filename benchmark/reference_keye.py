"""Plain reference of the Keye-VL-2.0-30B-A3B language model's block
(`model_type: KeyeVL2`; its keys are `Qwen3MoeConfig`'s plus `sa_config`):
float32 `jax.numpy`, a loop over the experts (every expert on every row), no
kernels, no cache, no batching, computed in blocks of query rows at the
published widths (no [heads, S, S] array is ever whole). Independent of
`paddle_tpu`: it imports nothing from the program, and takes the weights as a
plain dict. Text only: the vision tower is left out, and for text the three
position streams of M-RoPE (`mrope_section` [16, 24, 24] of the 64 frequency
pairs reading the temporal / height / width position) are all the token's
index, so M-RoPE is RoPE.

x [S, d]; `h` is the block's RMS-normed input, eps 1e-6, no bias anywhere:

    x   = x + Wo . Attn(h)                              h  = RMS_1(x)
    x   = x + MoE(n2)                                   n2 = RMS_2(x)
    logits = W_head . RMS_f(x_L)                        (an untied head)

Attention, H = 32 query heads over H_kv = 4 K/V heads of D = 128:
    q = Wq h -> [S, H, D];  k = Wk h, v = Wv h -> [S, H_kv, D]
    q, k: RMS norm over the D of each head (one gain [D] for q, one for k)
    RoPE, rotate-half over all D, theta 1e7, on q and k
    query head j reads K/V head j // (H / H_kv); scale D^-1/2
    the indexer (DeepSeek Sparse Attention's), Hi = 16 heads of Di = 64:
      qI_t = WIq h_t -> [Hi, Di];  kI_s = LayerNorm(WIk h_s) -> [Di] (one
      key head; gain and bias), both rotated (rotate-half over all Di, the
      model's theta);  w_t = WIw h_t -> [Hi]
      I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])
      S_t = the `topk` (2,048) positions s <= t of largest I[t, s]: all of
      them while t < topk; of equal scores the lower position
    the softmax of row t runs over s in S_t only; o = W_o concat(heads).
FFN of every layer: p = softmax(Wr n2) over E = 128 router logits, the top
8, gates renormalised over the chosen (`norm_topk_prob`), gated-SiLU
experts (silu(Wg n2) * Wu n2) Wd of width 768, no shared expert. Ties in
the top-k go to the lower index.

RMS(x) = x / sqrt(mean(x^2) + eps) * g; LayerNorm(x) = (x - mean) /
sqrt(var + eps) * g + b.

Assumptions, each also in the configuration's `assumed` (the catalog gives
the widths and names the mechanism, not these): (1) per-head q/k norm: the
config's keys are Qwen3-MoE's, which has it without a key. (2) The
indexer's LayerNorm on kI, its rotation (all Di dimensions, rotate-half, the
model's theta), that it reads the same `h` as the attention and projects qI
straight from it, after DeepSeek-V3.2-Exp's published indexer, which the
catalog's `described_as` names. (3) No scale on w: a positive scalar changes
no selection. (4) `sa_config.q_chunk_size` / `kv_chunk_size` are the source's
tiling; they change no result and the blocks here are this file's own.

On a TPU a float32 matmul runs in reduced precision unless asked, so every
entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, each layer `{"ln1", "ln2": g [d], "q":
[d, H D], "k", "v": [d, H_kv D], "out": [H D, d], "q_norm", "k_norm": g [D],
"iq": [d, Hi Di], "ik": [d, Di], "iw": [d, Hi], "ik_norm", "ik_bias": [Di],
"router": [d, E], "gate", "up": [E, d, h], "down": [E, h, d]}`. A layer
without "iq" has no indexer (plain grouped-query attention).

Hyper-parameters: `Hyper.of(config)`, by the published keys.

Forced choices (`logits_on`). Where two choosing scores lie closer than the
rounding of a lower matmul precision, a program that is right chooses the
other expert, or the other cache row, and its logits then differ from this
reference's by more than rounding: an expert's output is several per cent of
the stream, and a row that is read or not can carry a tenth of a head's
weight. So the reference can be told what the program chose and computes the
same equations on THAT, with its own weights: the experts of every layer and
token, [L, S, k], and what every row's attention read, bool [L, S, S]. It
reports how far each choice is from its own: for the experts 1 - (smallest p
of the forced experts) / (its own k-th p); for a row's positions (its own
topk-th I[t, .] - the smallest forced I[t, .]) / (the standard deviation of
I[t, s <= t]), and a large number (1e9) where the forced positions are not
min(t + 1, topk) positions s <= t (that row then reads all it may). 0 where
the sets are equal, a few hundredths at a near tie.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: query rows a block of the attention holds
Q_BLOCK = 256


class Hyper(NamedTuple):
    n_head: int
    n_kv_head: int
    head_dim: int
    top_k: int              #: experts a token
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0     #: rows a query keeps; 0: no indexer
    eps: float = 1e-6
    theta: float = 10000000.0
    dtype: str = "float32"  #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: the nearest precision
    #: below the configuration's, which a check's limits must fail
    # The next two make the reference WRONG on purpose, for a check's
    # readings (what its limits must fail); the model is the defaults.
    select: str = "topk"    #: "all": the selection ignored | "newest": the
    #: newest index_topk rows in place of the top index_topk
    pairing: str = "blocked"  #: "strided": query head j reads K/V head
    #: j % H_kv, the wrong group

    @classmethod
    def of(cls, config) -> "Hyper":
        sa = config.get("sa_config") or {}
        if not config["norm_topk_prob"] or config["attention_bias"] \
                or config["tie_word_embeddings"] \
                or config["hidden_act"] != "silu" \
                or config.get("mlp_only_layers") \
                or config.get("decoder_sparse_step", 1) != 1 \
                or config.get("use_sliding_window") \
                or sa.get("indexer_num_kv_heads", 1) != 1 \
                or (config.get("rope_scaling") or {}).get(
                    "rope_type", "default") != "default":
            raise ValueError("this reference writes renormalised softmax "
                             "top-k experts in every layer, an untied "
                             "head, no bias, plain (M-)RoPE and an "
                             "indexer of one key head only")
        return cls(int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]),
                   int(config["head_dim"]),
                   int(config["num_experts_per_tok"]),
                   int(sa.get("indexer_num_heads", 0)),
                   int(sa.get("indexer_head_dim", 0)),
                   int(sa.get("topk", 0)),
                   float(config["rms_norm_eps"]),
                   float(config["rope_theta"]))


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


def _rope(t, theta):
    """t [S, H, D] at positions 0..S-1, rotate-half over all D."""
    seq, _, d = t.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]                   # [S, 1, D/2]
    tf = t.astype(jnp.float32)
    a, b = tf[..., :d // 2], tf[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)],
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


def _attention(x, layer, hp, forced=None):
    """x [S, d] (normed) -> (out [S, d]; what every row read, bool [S, S];
    the shortfall [S] of `forced` [S, S] bool, which then takes the place
    of the reference's own selection). The last two None without an
    indexer."""
    seq = x.shape[0]
    group = hp.n_head // hp.n_kv_head
    q = _mm(x, layer["q"]).reshape(seq, hp.n_head, hp.head_dim)
    k = _mm(x, layer["k"]).reshape(seq, hp.n_kv_head, hp.head_dim)
    v = _mm(x, layer["v"]).reshape(seq, hp.n_kv_head, hp.head_dim)
    q = _rope(_rms(q, layer["q_norm"], hp.eps), hp.theta)
    k = _rope(_rms(k, layer["k_norm"], hp.eps), hp.theta)
    # the K/V head of every query head
    kv_of = (jnp.arange(hp.n_head) % hp.n_kv_head
             if hp.pairing == "strided"
             else jnp.arange(hp.n_head) // group)
    k_full, v_full = k[:, kv_of], v[:, kv_of]              # [S, H, D]
    indexed = "iq" in layer and hp.index_topk > 0
    kpos = jnp.arange(seq)
    n_blocks = -(-seq // Q_BLOCK)
    pad = n_blocks * Q_BLOCK - seq

    def blocks(t):             # [S, ...] -> [n_blocks, Q_BLOCK, ...]
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape((n_blocks, Q_BLOCK) + t.shape[1:])

    xs = [jnp.arange(n_blocks) * Q_BLOCK, blocks(q)]
    if indexed:
        qi = _rope(_mm(x, layer["iq"]).reshape(seq, hp.index_heads,
                                               hp.index_dim), hp.theta)
        ki = _rope(_layer_norm(_mm(x, layer["ik"]), layer["ik_norm"],
                               layer["ik_bias"], hp.eps)[:, None],
                   hp.theta)[:, 0]
        xs += [blocks(qi), blocks(_mm(x, layer["iw"]))]
        if forced is not None:
            xs.append(blocks(forced))

    def one(_, block):
        start, qb = block[:2]
        rows = start + jnp.arange(Q_BLOCK)
        causal = kpos[None] <= rows[:, None]               # [Q, S]
        mask, shortfall = causal, jnp.zeros((Q_BLOCK,), jnp.float32)
        if indexed:
            scores = _index_scores(block[2], ki, block[3])
            top = _top_mask(scores, causal, hp.index_topk)
            mask = {"topk": top, "all": causal,
                    "newest": causal & (kpos[None] > rows[:, None]
                                        - hp.index_topk)}[hp.select]
        if indexed and forced is not None:
            # a padding row past the sequence reads as any other row
            given = jnp.where((rows < seq)[:, None], block[4], causal)
            count = jnp.minimum(rows + 1, hp.index_topk)
            sound = (jnp.sum(given & causal, axis=1) == count) \
                & ~jnp.any(given & ~causal, axis=1)
            kth = jnp.min(jnp.where(top, scores, jnp.inf), axis=1)
            weakest = jnp.min(jnp.where(given, scores, jnp.inf), axis=1)
            n = jnp.sum(causal, axis=1)
            mean = jnp.sum(jnp.where(causal, scores, 0.0), axis=1) / n
            std = jnp.sqrt(jnp.sum(jnp.where(
                causal, jnp.square(scores - mean[:, None]), 0.0), axis=1)
                / n)
            shortfall = jnp.where(
                sound, jnp.maximum(kth - weakest, 0.0)
                / jnp.maximum(std, 1e-30), 1e9)
            mask = jnp.where(sound[:, None], given, causal)
        s = jnp.einsum("qhd,khd->hqk", qb, k_full).astype(jnp.float32) \
            / jnp.sqrt(jnp.float32(hp.head_dim))
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return None, (jnp.einsum("hqk,khd->qhd", p, v_full), mask,
                      shortfall)

    _, (ctx, masks, shortfall) = jax.lax.scan(one, None, tuple(xs))
    ctx = ctx.reshape(n_blocks * Q_BLOCK, hp.n_head * hp.head_dim)[:seq]
    out = _mm(ctx, layer["out"])
    if not indexed:
        return out, None, None
    return out, masks.reshape(n_blocks * Q_BLOCK, seq)[:seq], \
        shortfall.reshape(-1)[:seq]


def _route(x, layer, hp, forced=None):
    """x [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert, renormalised over the
    chosen, 0 elsewhere; the shortfall [S] of `forced` [S, k], which then
    takes the place of the reference's own choice)."""
    p = jax.nn.softmax(_mm(x, layer["router"]).astype(jnp.float32), axis=-1)
    rows = jnp.arange(p.shape[0])[:, None]
    own = jnp.argsort(-p, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(p[rows, chosen], axis=-1) \
        / p[rows, own][:, -1]
    mask = jnp.zeros(p.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, p, 0.0)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True), shortfall


def _experts(x, layer, w):
    """Every expert on every row, weighed by w [S, E] (0 off a row's
    chosen): a loop over the experts, written as a scan so that 128 of
    them compile as one body."""
    def one(acc, expert):
        gate, up, down, col = expert
        h = jax.nn.silu(_mm(x, gate)) * _mm(x, up)
        return acc + col[:, None].astype(jnp.float32) * _mm(
            h, down).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(x.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"], w.T))
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
    experts [L, S, k]; their shortfall [L, S]; what every row's attention
    read, bool [L, S, S]; its shortfall [L, S]: the last two None
    without an indexer)."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, shortfalls, masks, sel_shortfalls = [], [], [], []
    for n, layer in enumerate(weights["layers"]):
        att, mask, sel_short = _attention(
            _rms(x, layer["ln1"], hp.eps), layer, hp,
            None if forced_masks is None else forced_masks[n])
        x = x + att
        n2 = _rms(x, layer["ln2"], hp.eps)
        chosen, w, shortfall = _route(
            n2, layer, hp,
            None if forced_routes is None else forced_routes[n])
        x = x + _experts(n2, layer, w.astype(x.dtype))
        routes.append(chosen)
        shortfalls.append(shortfall)
        if mask is not None:
            masks.append(mask)
            sel_shortfalls.append(sel_short)
    return (_head(x if rows is None else x[rows], weights, hp),
            jnp.stack(routes), jnp.stack(shortfalls),
            jnp.stack(masks) if masks else None,
            jnp.stack(sel_shortfalls) if masks else None)


@functools.partial(jax.jit, static_argnames=("hp",))
def _forward_jit(weights, ids, hp, rows=None, forced_routes=None,
                 forced_masks=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(weights, ids, hp, rows, forced_routes,
                            forced_masks)


def _ints(x):
    return None if x is None else jnp.asarray(x, jnp.int32)


def nll_sum(weights, ids, targets, hp):
    """Summed next-token cross entropy of one sequence; differentiable in
    `weights` (a trainer's gradients are checked against its grad)."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(
            _forward_one(weights, ids, hp)[0], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None],
                                            axis=-1))


def logits(weights, ids, hp, rows=None):
    """Full causal forward of one sequence: ids [S] -> logits [S, V], or
    [len(rows), V] for the positions `rows` alone."""
    return _forward_jit(weights, _ints(ids), hp, _ints(rows))[0]


def choices(weights, ids, hp):
    """The reference's own choices: the experts of every layer and token
    [L, S, k], each row sorted by p, highest first, and what every row's
    attention read, bool [L, S, S] (None without an indexer)."""
    out = _forward_jit(weights, _ints(ids), hp, jnp.zeros((1,), jnp.int32))
    return out[1], out[3]


def logits_on(weights, ids, hp, routes, masks=None, rows=None):
    """The full causal forward with every token's experts forced to
    `routes` [L, S, k] and what every row's attention read to `masks`
    [L, S, S] bool (what a program chose): (logits [S, V], or of `rows`
    alone; the experts' shortfall [L, S]; the selections' shortfall
    [L, S] or None), as the module's text says."""
    out = _forward_jit(weights, _ints(ids), hp, _ints(rows), _ints(routes),
                       None if masks is None else jnp.asarray(masks, bool))
    return out[0], out[2], out[4]
