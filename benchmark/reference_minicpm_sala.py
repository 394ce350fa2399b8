"""Plain reference of the MiniCPM-SALA block (`model_type: minicpm_sala`,
`openbmb/MiniCPM-SALA`): float32 `jax.numpy`, the linear layers' masked
O(n^2) sum, dense masked attention over the chosen blocks, no kernel, no
cache, no chunked recurrence, no state handed in, no batching.
Independent of `paddle_tpu`: it imports nothing from the program, and
takes the weights as a plain dict. Rows are taken `_ROWS` at a time so
that 12 k rows at the published widths fit one chip beside the weights
(each block of rows against ALL the sequence's keys, the later ones
masked: one shape a layer, so one compilation); that tiles the arithmetic
and changes no sum's terms.

With L the PUBLISHED depth (32, in a cut too), r = scale_depth / sqrt(L):

    h = scale_emb * E[token]
    every layer:  h += r * Mixer(N(h));  h += r * W_down(silu(W_gate u) *
                  W_up u), u = N(h)       N: RMSNorm, eps 1e-6, no bias
    logits = W_head(N(h)) / (hidden_size / dim_model_base)     untied

`lightning-attn` (u [S, d] the normed input; H heads of D, head h):
    q = N_q(u W_q), k = N_k(u W_k)     RMS over the head's D, ONE gain [D]
    v = u W_v                          for q and one for k; no activation
    q, k rotated (halves (i, i + D/2), theta 10,000)
    o_t = sum_{s <= t} a_h^(t - s) (q_t . k_s / sqrt(D)) v_s
    y = W_o( RMSNorm(o) * sigmoid(u W_g) )     the norm over the H D joined
                                               columns, one gain [H D]
  ASSUMED (the catalog row gives no slopes): a_h = exp(-s_h (1 - l / (L -
  1) + 1e-5)), s_h = 2^(-8 (h + 1) / H), l the layer's PUBLISHED index:
  Lightning Attention's slope rule (arXiv:2401.04658) as MiniMax-01
  applies it (arXiv:2501.08313). ASSUMED: no activation on q, k, v (the
  q/k-norm stands where MiniMax's SiLU is); the output norm's span.

`minicpm4` (H query heads over H_kv K/V heads of D, 16 a group; NO
rotation; sizes kernel 32, stride 16, block 64, top-k 64, window 2,048,
one initial block, dense below 8,192: ASSUMED, MiniCPM4-8B's
`sparse_config`, arXiv:2506.07900): q = N_q(u W_q), k = N_k(u W_k), v =
u W_v. For a query at row t of a sequence whose length AT THAT CALL is n
(a prefill's prompt length; a decode step's context, t + 1):
    n < dense_len: causal softmax attention. Otherwise, K/V head g:
    c_j = mean(k_g[stride j : stride j + kernel])    every kernel wholly
                                                     at or before t
    p_hj = softmax_j(q_h . c_j / sqrt(D));  P_j = sum of p_hj over the
    group's heads;  B_b = max of P_j over the kernels that overlap rows
    [block b, block b + block)
    chosen: block 0 (.. init - 1), the window / block blocks that end at
    the query's own, and the highest B_b of the rest until top-k blocks
    in all (ties to the lower index); attention is the causal softmax of
    q_h . k_i / sqrt(D) over the chosen blocks' rows, ONE choice a K/V
    head shared by its heads.
    y = W_o( o * sigmoid(u W_g) )
  DEPARTURE: the released kernel approximates the softmax's normaliser
  from a second, coarser pooling; this reference (and the program)
  normalise exactly over the kernels. ASSUMED: dense or sparse by the
  call's length. `mup_denominator` is read by nothing here (ASSUMED).

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, every layer `"kind"` ("linear" |
"sparse"), `"ln1", "ln2": g [d]`, `"q", "gate": [d, H D]`, `"k", "v": [d,
H_kv D]` (a linear layer: H_kv = H), `"out": [H D, d]`, `"qnorm",
"knorm": [D]`, a linear layer `"onorm": [H D]`, and `"ffn_gate",
"ffn_up": [d, f]`, `"ffn_down": [f, d]`.

Choices: `logits_and_choices` returns beside the logits what every row
of every sparse layer chose, bool [L_s, S, H_kv, NB]. `logits_on_choices`
computes the same equations on the blocks a PROGRAM chose (with random
weights near ties flip on rounding) and reports, a layer, row and K/V
head, the shortfall 1 - (the program's weakest freely chosen B_b) / (the
reference's own weakest freely chosen B_b), 0 where the sets are equal.

`Hyper`'s last fields are not the model's: each makes the reference
WRONG in one part, for the tool that shows a check's limits fail it.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

#: rows a layer's parts are computed for at a time
_ROWS = 256


class Hyper(NamedTuple):
    kinds: tuple              #: "sparse" | "linear", a held layer
    layer_ids: tuple          #: each held layer's PUBLISHED index
    depth: int                #: L, the published depth
    heads: int
    kv_heads: int
    head_dim: int
    hidden: int
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    window: int = 2048        #: rows
    init: int = 1             #: blocks
    dense_len: int = 8192
    rope_theta: float = 10000.0
    eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # -- faults ---------------------------------------------------------
    decay_index: str = "published"  #: "held": the layer's index in the cut
    linear_gate: bool = True
    sparse_gate: bool = True
    group_sum: str = "all"          #: "one": the group's first head alone
    sparse_rotary: bool = False     #: q and k of a sparse layer rotated
    dtype: str = "float32"          #: "bfloat16": every weight, the
    #: residual stream and every intermediate (softmaxes in float32)

    @classmethod
    def of(cls, config) -> "Hyper":
        """From a configuration file's keys (the published ones and
        `assumed.sparse_config`)."""
        kinds = tuple("sparse" if m == "minicpm4" else "linear"
                      for m in config["mixer_types"][
                          :int(config["num_hidden_layers"])])
        sparse = config["assumed"]["sparse_config"]
        return cls(
            kinds, tuple(range(len(kinds))),
            int(config["published"]["num_hidden_layers"]),
            int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]),
            int(config["hidden_size"]), int(sparse["kernel_size"]),
            int(sparse["kernel_stride"]), int(sparse["block_size"]),
            int(sparse["topk"]), int(sparse["window_size"]),
            int(sparse["init_blocks"]), int(sparse["dense_len"]),
            float(config["rope_theta"]), float(config["rms_norm_eps"]),
            float(config["scale_emb"]), float(config["scale_depth"]),
            int(config["dim_model_base"]))


def _mm(x, w):
    return x @ w.astype(x.dtype)


def _rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                               + eps) * gain.astype(jnp.float32)
            ).astype(x.dtype)


def _rope(t, theta):
    """t [S, H, D] at positions 0..S-1, halves."""
    seq, _, d = t.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    tf = t.astype(jnp.float32)
    a, b = tf[..., :d // 2], tf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(t.dtype)


def _blocks_of_rows(n):
    return [(lo, min(lo + _ROWS, n)) for lo in range(0, n, _ROWS)]


def _project(u, w, hp, kv_heads, rotate):
    """u [S, d] -> q [S, H, D], k, v [S, H_kv, D], the gate [S, H D]."""
    seq = u.shape[0]
    q = _rms(_mm(u, w["q"]).reshape(seq, hp.heads, hp.head_dim),
             w["qnorm"], hp.eps)
    k = _rms(_mm(u, w["k"]).reshape(seq, kv_heads, hp.head_dim),
             w["knorm"], hp.eps)
    v = _mm(u, w["v"]).reshape(seq, kv_heads, hp.head_dim)
    if rotate:
        q, k = _rope(q, hp.rope_theta), _rope(k, hp.rope_theta)
    gate = jax.nn.sigmoid(_mm(u, w["gate"]).astype(jnp.float32))
    return q, k, v, gate


def decay(hp: Hyper, held: int):
    """a_h of layer `held` (its index among the held ones), [H]."""
    index = hp.layer_ids[held] if hp.decay_index == "published" else held
    h = jnp.arange(1, hp.heads + 1, dtype=jnp.float32)
    slope = jnp.exp2(-8.0 * h / hp.heads)
    return jnp.exp(-slope * (1.0 - index / (hp.depth - 1) + 1e-5))


@functools.partial(jax.jit, static_argnames=("hd",))
def _linear_rows(q, k, v, log_a, rows, *, hd):
    """Rows `rows` [R] (q [R, H, D]) of the masked O(n^2) sum over all
    S keys: [R, H D]."""
    gap = (rows[:, None] - jnp.arange(k.shape[0])[None]).astype(jnp.float32)
    seen = gap >= 0
    weight = jnp.where(seen[None], jnp.exp(
        log_a[:, None, None] * jnp.where(seen, gap, 0.0)[None]), 0.0)
    s = jnp.einsum("thd,shd->hts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    o = jnp.einsum("hts,shd->thd", (s * weight).astype(v.dtype), v)
    return o.reshape(q.shape[0], -1)


def _linear(u, w, hp, held):
    seq = u.shape[0]
    q, k, v, gate = _project(u, w, hp, hp.heads, True)
    log_a = jnp.log(decay(hp, held))                        # [H]
    o = jnp.concatenate([
        _linear_rows(q[lo:hi], k, v, log_a, jnp.arange(lo, hi),
                     hd=hp.head_dim)
        for lo, hi in _blocks_of_rows(seq)])
    o = _rms(o, w["onorm"], hp.eps)
    if hp.linear_gate:
        o = (o.astype(jnp.float32) * gate).astype(u.dtype)
    return _mm(o, w["out"])


def pooled_keys(k, hp: Hyper):
    """k [S, H_kv, D] -> c [NP, H_kv, D] float32: every kernel wholly
    inside the S rows."""
    n = max((k.shape[0] - hp.kernel) // hp.stride + 1, 0)
    at = (jnp.arange(n)[:, None] * hp.stride
          + jnp.arange(hp.kernel)[None])                    # [NP, kernel]
    return jnp.mean(k.astype(jnp.float32)[at], axis=1)


def block_scores(q, pooled, rows, hp: Hyper, n_blocks):
    """q [R, H, D] of the rows `rows` [R] -> B [H_kv, R, n_blocks]."""
    r = q.shape[0]
    per = hp.heads // hp.kv_heads
    qg = q.astype(jnp.float32).reshape(r, hp.kv_heads, per, hp.head_dim)
    if hp.group_sum == "one":
        qg = qg[:, :, :1]
    s = jnp.einsum("rgid,jgd->girj", qg, pooled) / math.sqrt(hp.head_dim)
    j_end = jnp.arange(pooled.shape[0]) * hp.stride + hp.kernel
    seen = j_end[None] <= rows[:, None] + 1                 # [R, NP]
    s = jnp.where(seen[None, None], s, -jnp.inf)
    top = jnp.max(s, -1, keepdims=True)
    e = jnp.where(seen[None, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)), 0.0)
    p = e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30)
    summed = jnp.sum(p, axis=1)                             # [G, R, NP]
    # the kernels that overlap block b: stride j < block (b + 1) and
    # stride j + kernel > block b
    j_lo = jnp.arange(pooled.shape[0]) * hp.stride
    b_lo = jnp.arange(n_blocks) * hp.block
    overlap = (j_lo[None] < b_lo[:, None] + hp.block) \
        & (j_lo[None] + hp.kernel > b_lo[:, None])          # [NB, NP]
    return jnp.max(jnp.where(overlap[None, None], summed[:, :, None], 0.0),
                   axis=-1)


def forced_blocks(rows, hp: Hyper, n_blocks):
    """bool [R, NB]: the initial blocks and the local ones of each row."""
    own = rows[:, None] // hp.block
    at = jnp.arange(n_blocks)[None]
    return ((at < hp.init) | (at > own - hp.window // hp.block)) \
        & (at <= own)


def choose(scores, rows, calls, hp: Hyper):
    """scores [G, R, NB] -> bool [G, R, NB]: the chosen blocks of each
    row, whose call's length is `calls` [R]."""
    n_blocks = scores.shape[-1]
    own = rows[:, None] // hp.block
    live = jnp.arange(n_blocks)[None] <= own                # [R, NB]
    forced = forced_blocks(rows, hp, n_blocks)
    free = jnp.where((live & ~forced)[None], scores, -jnp.inf)
    room = hp.topk - jnp.sum(forced, -1)                    # [R]
    # rank by score, of equal scores the lower index first
    order = jnp.argsort(-free, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    picked = (rank < room[None, :, None]) & (live & ~forced)[None]
    chosen = picked | forced[None]
    return jnp.where((calls < hp.dense_len)[None, :, None], live[None],
                     chosen)


def _shortfall(scores, mine, theirs, rows, hp):
    """[G, R]: 1 - (weakest freely chosen score of `theirs`) / (of
    `mine`), 0 where the sets are equal or nothing was free."""
    forced = forced_blocks(rows, hp, scores.shape[-1])[None]

    def weakest(chosen):
        return jnp.min(jnp.where(chosen & ~forced, scores, jnp.inf), -1)

    own, got = weakest(mine), weakest(theirs)
    same = jnp.all(mine == theirs, axis=-1)
    ok = jnp.isfinite(own) & jnp.isfinite(got) & ~same
    return jnp.where(ok, jnp.maximum(
        1.0 - got / jnp.maximum(own, 1e-30), 0.0), 0.0)


@functools.partial(jax.jit, static_argnames=("hp", "n_blocks"))
def _sparse_rows(q, k, v, pooled, rows, calls, forced, *, hp, n_blocks):
    """Rows `rows` [R] of a sparse layer over all S keys: (o [R, H D],
    what they chose [R, G, NB], the shortfall of `forced` [R, G] (zeros
    without one))."""
    per = hp.heads // hp.kv_heads
    if pooled.shape[0]:
        scores = block_scores(q, pooled, rows, hp, n_blocks)
    else:
        scores = jnp.zeros((hp.kv_heads, q.shape[0], n_blocks))
    mine = choose(scores, rows, calls, hp)                  # [G, R, NB]
    chosen, short = mine, jnp.zeros(mine.shape[:2]).T
    if forced is not None:
        chosen = jnp.moveaxis(forced, 1, 0)
        short = _shortfall(scores, mine, chosen, rows, hp).T
    keys = jnp.arange(k.shape[0])
    mask = jnp.repeat(chosen, hp.block, axis=-1)[..., :k.shape[0]] \
        & (keys[None] <= rows[:, None])[None]               # [G, R, S]
    qg = q.astype(jnp.float32).reshape(
        q.shape[0], hp.kv_heads, per, hp.head_dim)
    s = jnp.einsum("rgid,sgd->girs", qg, k.astype(jnp.float32)) \
        / math.sqrt(hp.head_dim)
    s = jnp.where(mask[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("girs,sgd->rgid", p, v)
    return o.reshape(q.shape[0], -1), jnp.moveaxis(mine, 0, 1), short


def _sparse(u, w, hp, prompt_len, forced=None):
    """-> (the mixer's output [S, d], chosen [S, G, NB] bool, the
    shortfall of `forced` [S, G])."""
    seq = u.shape[0]
    q, k, v, gate = _project(u, w, hp, hp.kv_heads, hp.sparse_rotary)
    pooled = pooled_keys(k, hp)
    n_blocks = -(-seq // hp.block)
    outs, picked, short = [], [], []
    for lo, hi in _blocks_of_rows(seq):
        rows = jnp.arange(lo, hi)
        o, mine, tie = _sparse_rows(
            q[lo:hi], k, v, pooled, rows, jnp.maximum(rows + 1, prompt_len),
            None if forced is None else jnp.asarray(forced[lo:hi]),
            hp=hp, n_blocks=n_blocks)
        outs.append(o)
        picked.append(mine)
        short.append(tie)
    o = jnp.concatenate(outs)
    if hp.sparse_gate:
        o = (o.astype(jnp.float32) * gate).astype(u.dtype)
    return (_mm(o, w["out"]), jnp.concatenate(picked),
            jnp.concatenate(short) if forced is not None else None)


def _ffn(u, w):
    outs = []
    for lo, hi in _blocks_of_rows(u.shape[0]):
        g = _mm(u[lo:hi], w["ffn_gate"])
        outs.append(_mm(jax.nn.silu(g) * _mm(u[lo:hi], w["ffn_up"]),
                        w["ffn_down"]))
    return jnp.concatenate(outs)


def _forward(weights, ids, hp, prompt_len, forced):
    dt = jnp.dtype(hp.dtype)
    r = hp.scale_depth / math.sqrt(hp.depth)
    x = (hp.scale_emb * jnp.asarray(weights["tok_emb"])[jnp.asarray(ids)]
         .astype(jnp.float32)).astype(dt)
    chosen, short = [], []
    at = 0
    for held, (kind, w) in enumerate(zip(hp.kinds, weights["layers"])):
        u = _rms(x, w["ln1"], hp.eps)
        if kind == "linear":
            f = _linear(u, w, hp, held)
        else:
            f, mine, tie = _sparse(
                u, w, hp, prompt_len,
                None if forced is None else forced[at])
            chosen.append(mine)
            short.append(tie)
            at += 1
        x = (x.astype(jnp.float32) + r * f.astype(jnp.float32)).astype(dt)
        f = _ffn(_rms(x, w["ln2"], hp.eps), w)
        x = (x.astype(jnp.float32) + r * f.astype(jnp.float32)).astype(dt)
    return x, chosen, short


def _logits(weights, ids, hp, rows, prompt_len, forced):
    ids = jnp.asarray(ids)
    prompt_len = ids.shape[0] if prompt_len is None else int(prompt_len)
    with jax.default_matmul_precision("highest"):
        x, chosen, short = _forward(weights, ids, hp, prompt_len, forced)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        out = _mm(_rms(x, weights["ln_f"], hp.eps), weights["head"]) \
            .astype(jnp.float32) / (hp.hidden / hp.dim_model_base)
    return out, chosen, short


def logits(weights, ids, hp: Hyper, rows=None,
           prompt_len: Optional[int] = None):
    """Logits [S, V] (or of `rows`) of the sequence `ids`, whose first
    `prompt_len` rows (all, unless said) were ONE call and every later
    row a call of its own: row t's call has length max(prompt_len, t +
    1), which is what decides dense or sparse."""
    return _logits(weights, ids, hp, rows, prompt_len, None)[0]


def logits_and_choices(weights, ids, hp: Hyper, rows=None,
                       prompt_len: Optional[int] = None):
    """`logits`, and what every row of every sparse layer chose, bool
    [L_s, S, H_kv, NB]."""
    out, chosen, _ = _logits(weights, ids, hp, rows, prompt_len, None)
    return out, jnp.stack(chosen)


def logits_on_choices(weights, ids, hp: Hyper, choices, rows=None,
                      prompt_len: Optional[int] = None):
    """The same equations on the blocks a program chose (`choices` bool
    [L_s, S, H_kv, NB]): (logits, the shortfall [L_s, S, H_kv])."""
    out, _, short = _logits(weights, ids, hp, rows, prompt_len, choices)
    return out, jnp.stack(short)
