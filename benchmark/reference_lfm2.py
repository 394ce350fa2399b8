"""Plain reference of the LFM2-MoE block (`model_type: lfm2_moe`,
`LiquidAI/LFM2-24B-A2B`): float32 `jax.numpy`, a loop over the experts,
no kernels, no cache, no state, no batching. Independent of `paddle_tpu`:
it imports nothing from the program, and takes the weights as a plain
dict.

One layer, x [S, d], kind `layer_types[l]`, N an RMSNorm with a gain:

    h = N_op(x)
    conv:            (B, C, z) = split_3(h W_in)          each [S, d], no bias
                     u   = B * z
                     c_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t   (w [3, d]: one
                           tap set a channel; u_{-1} = u_{-2} = 0: causal,
                           depthwise)
                     m   = (C * c) W_out
    full_attention:  q = h Wq -> [S, H, D]   k = h Wk, v = h Wv -> [S, H_kv, D]
                     q, k: RMSNorm over each head's D (one gain for q, one
                     for k), THEN rotated by the token's position, halves
                     (i, i + D/2), angle pos x theta^(-2i / D)
                     query head j reads K/V head j // (H / H_kv); scores
                     q.k / sqrt(D), causal, softmax in float32
                     m = concat(heads) Wo
    y = x + m
    g = N_ffn(y)
    l < num_dense_layers:  f = (silu(g Wg) * (g Wu)) Wd
    else:            s = sigmoid(g Wr) in R^E (float32); T = the k largest
                     of s + b (of equal scores the lower index)
                     w_e = s_e / (sum_{e' in T} s_e' + 1e-6)   (the UNBIASED
                     s; routed_scaling_factor multiplies it)
                     f = sum_{e in T} w_e (silu(g Wg_e) * (g Wu_e)) Wd_e
    x' = y + f
    logits = N_f(x_L) E^T,   E the embedding (tied)

The convolution runs over the whole sequence: what a server keeps of a
sequence (the two rows of u before its next token) does not exist here.
What is read into the published config, each an inference the
configuration file lists under `assumed`: the tied head, the final norm
before it, the in-projection's thirds in the order (B, C, x), the
q/k-norm before the rotation.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.
The dense FFN and the convolution's projections take their rows
`_ROW_BLOCK_WIDE` at a time and attention its query rows `_ROW_BLOCK` at
a time, so that 6,148 tokens at the published widths fit beside a
server's weights and pools.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "layers": [...]}`, every
matrix `[in, out]`. Every layer has `"ln1", "ln2": g [d]` and its FFN:
dense `"gate", "up": [d, f], "down": [f, d]`, or experts `"router": [d,
E], "router_bias": [E], "gate", "up": [E, d, f], "down": [E, f, d]`. A
conv layer has `"in": [d, 3 d], "taps": [3, d], "out": [d, d]`; an
attention layer `"q": [d, H D], "k", "v": [d, H_kv D], "out": [H D, d],
"q_norm", "k_norm": g [D]`.

Forced routes (`logits_on_routes`): where a token's k-th and (k+1)-th
choosing scores lie closer than the rounding of a lower matmul
precision, a program that is right chooses the other expert, and its
logits then differ by a whole expert's output. So the reference can be
told the experts the program chose, [L_moe, S, k] (the layers with
experts, in order): it computes the same equations with those experts
and ITS OWN weights for them, and reports for every such layer and token
the shortfall 1 - (smallest s + b of the forced experts) / (its own k-th
s + b): 0 where the sets are equal, a few hundredths at a near tie,
large for an expert the reference would never choose.

`Hyper`'s last fields and the `state` argument are not the model's: each
makes the reference WRONG in one part, for the tool that shows a check's
limits fail it (`benchmark/tools/lfm2_check_readings.py`). `state` = (n,
rows [conv layers, 2, d]) makes the rows from position n on read `rows`
where they would read u_{n-2}, u_{n-1}: a server whose decode steps
start from another state than the prompt's own (`conv_state` gives the
state any sequence leaves).
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
    kinds: Tuple[str, ...]    #: a layer's kind, "conv" | "full_attention",
    #: layer l taking entry l
    dense_layers: int
    top_k: int
    eps: float = 1e-5
    theta: float = 1000000.0
    routed_scale: float = 1.0
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: what a check's limits
    #: must fail (the nearest precision below the configuration's)
    # -- faults, one at a time ------------------------------------------
    taps: str = "causal"      #: "reversed": w_0 weighs the token itself |
    #: "dropped": the oldest tap left out
    gates: str = "both"       #: "swapped": B and C exchanged | "no_c":
    #: the output gate left out
    select: str = "biased"    #: "unbiased": the top of s, no bias
    weigh: str = "unbiased"   #: "biased": the weights from s + b
    qk_norm: str = "before"   #: "after": the rotation first
    pairing: str = "blocked"  #: "strided": head j reads K/V head j % H_kv

    @classmethod
    def of(cls, config) -> "Hyper":
        if config["model_type"] != "lfm2_moe" or config["conv_bias"] \
                or int(config["conv_L_cache"]) != 3 \
                or not config["norm_topk_prob"] \
                or not config["use_expert_bias"] \
                or config["rope_parameters"]["rope_type"] != "default":
            raise ValueError(
                "this reference writes gated short convolutions of three "
                "taps without a bias beside grouped-query attention with "
                "default rotary positions, experts chosen by sigmoid plus "
                "a bias and renormalised over the chosen only")
        layers = int(config["num_hidden_layers"])
        return cls(int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]),
                   int(config["hidden_size"])
                   // int(config["num_attention_heads"]),
                   tuple(config["layer_types"][:layers]),
                   int(config["num_dense_layers"]),
                   int(config["num_experts_per_tok"]),
                   float(config["norm_eps"]),
                   float(config["rope_parameters"]["rope_theta"]),
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
    """t [S, H, D] at positions 0..S-1, halves (i, i + D/2)."""
    seq, _, d = t.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]                   # [S, 1, D/2]
    tf = t.astype(jnp.float32)
    a, b = tf[..., :d // 2], tf[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)],
                           axis=-1).astype(t.dtype)


#: query rows of attention at a time: [H, rows, S] scores (32 x 64 x 6,148
#: float32: 50 MB)
_ROW_BLOCK = 64
#: rows of a wide projection at a time (the dense FFN's [rows, 11,776]
#: three times over: 145 MB)
_ROW_BLOCK_WIDE = 1024


def _by_rows(fn, x, block=_ROW_BLOCK_WIDE):
    """fn on x [S, ...] row block by row block (a row's result depends
    on its own row alone)."""
    seq = x.shape[0]
    if seq <= block:
        return fn(x)
    blocks = -(-seq // block)
    padded = jnp.pad(x, ((0, blocks * block - seq),)
                     + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, padded.reshape((blocks, block) + x.shape[1:]))
    return out.reshape((blocks * block,) + out.shape[2:])[:seq]


def _conv(h, layer, hp, state=None):
    """(m [S, d], u [S, d]); `state` as the module's text says."""
    taps = layer["taps"].astype(h.dtype)
    if hp.taps == "reversed":
        taps = taps[::-1]
    elif hp.taps == "dropped":
        taps = taps.at[0].set(0)

    def gated(rows):
        b, c, z = jnp.split(_mm(rows, layer["in"]), 3, axis=-1)
        if hp.gates == "swapped":
            b, c = c, b
        return jnp.stack([b * z, c], axis=1)

    both = _by_rows(gated, h)                              # [S, 2, d]
    u, c = both[:, 0], both[:, 1]

    def causal(u):
        back = jnp.pad(u, ((2, 0), (0, 0)))
        return taps[0] * back[:-2] + taps[1] * back[1:-1] + taps[2] * u

    conv = causal(u)
    if state is not None:
        n, rows = state
        other = causal(u.at[n - 2:n].set(rows.astype(u.dtype)))
        conv = jnp.where(jnp.arange(u.shape[0])[:, None] >= n, other, conv)
    if hp.gates != "no_c":
        conv = c * conv
    return _by_rows(lambda rows: _mm(rows, layer["out"]), conv), u


def _attention(h, layer, hp):
    seq = h.shape[0]
    group = hp.n_head // hp.n_kv
    q = _mm(h, layer["q"]).reshape(seq, hp.n_head, hp.head_dim)
    k = _mm(h, layer["k"]).reshape(seq, hp.n_kv, hp.head_dim)
    v = _mm(h, layer["v"]).reshape(seq, hp.n_kv, hp.head_dim)
    if hp.qk_norm == "after":     # the fault: the rotation first
        q = _rms(_rope(q, hp.theta), layer["q_norm"], hp.eps)
        k = _rms(_rope(k, hp.theta), layer["k_norm"], hp.eps)
    else:
        q = _rope(_rms(q, layer["q_norm"], hp.eps), hp.theta)
        k = _rope(_rms(k, layer["k_norm"], hp.eps), hp.theta)
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
        scores = jnp.einsum("qngd,knd->ngqk", qb, k).astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hp.head_dim))
        scores = jnp.where((cols <= rows)[None, None], scores, -jnp.inf)
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


def _route(g, layer, hp, forced=None):
    """g [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert, 0 off the chosen; the
    shortfall [S] of `forced` [S, k], which then takes the place of the
    reference's own choice)."""
    s = jax.nn.sigmoid(_mm(g, layer["router"]).astype(jnp.float32))
    biased = s + layer["router_bias"].astype(jnp.float32)
    by = s if hp.select == "unbiased" else biased
    rows = jnp.arange(s.shape[0])[:, None]
    own = jnp.argsort(-by, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(by[rows, chosen], axis=-1) \
        / by[rows, own][:, -1]
    mask = jnp.zeros(s.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, biased if hp.weigh == "biased" else s, 0.0)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * hp.routed_scale
    return chosen, w, shortfall


def _experts(g, layer, w):
    """Every expert on every row, weighed by w [S, E] (0 off a row's
    chosen): a loop over the experts, written as a scan so that they
    compile as one body. float32 [S, d]."""
    def one(acc, expert):
        gate, up, down, col = expert
        return acc + col[:, None].astype(jnp.float32) * _gated(
            g, gate, up, down).astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(g.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"], w.T.astype(g.dtype)))
    return out


def _forward_one(weights, ids, hp, forced=None, rows=None, state=None):
    """ids [S] -> (logits [S, V] float32, or of positions `rows` alone;
    chosen experts [L_moe, S, k]; shortfall [L_moe, S] of `forced`
    [L_moe, S, k]; u of every conv layer [L_conv, S, d])."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, shortfalls, us = [], [], []
    for l, (layer, kind) in enumerate(zip(weights["layers"], hp.kinds)):
        h = _rms(x, layer["ln1"], hp.eps)
        if kind == "conv":
            m, u = _conv(h, layer, hp, None if state is None else
                         (state[0], state[1][len(us)]))
            us.append(u)
        else:
            m = _attention(h, layer, hp)
        y = (x.astype(jnp.float32) + m.astype(jnp.float32)).astype(x.dtype)
        g = _rms(y, layer["ln2"], hp.eps)
        if l < hp.dense_layers:
            f = _by_rows(lambda r: _gated(r, layer["gate"], layer["up"],
                                          layer["down"]), g)
        else:
            chosen, w, shortfall = _route(
                g, layer, hp,
                None if forced is None else forced[len(routes)])
            routes.append(chosen)
            shortfalls.append(shortfall)
            f = _experts(g, layer, w)
        x = (y.astype(jnp.float32) + f.astype(jnp.float32)).astype(x.dtype)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    n = _rms(x, weights["ln_f"], hp.eps)
    logits = _mm(n, weights["tok_emb"].T).astype(jnp.float32)
    return logits, jnp.stack(routes), jnp.stack(shortfalls), jnp.stack(us)


@functools.partial(jax.jit, static_argnames=("hp", "rows", "state_at"))
def _forward_jit(weights, ids, hp, forced=None, rows=None, state_at=None,
                 state_rows=None):
    with jax.default_matmul_precision("highest"):
        return _forward_one(
            weights, ids, hp, forced, rows,
            None if state_at is None else (state_at, state_rows))


def _rows(rows):
    return None if rows is None else tuple(int(r) for r in rows)


def _state(state):
    if state is None:
        return {}
    return dict(state_at=int(state[0]), state_rows=jnp.asarray(state[1]))


def logits(weights, ids, hp, rows=None, state=None):
    """Full causal forward of one sequence: ids [S] -> logits [S, V], or
    of the positions `rows` alone [R, V]."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp,
                        rows=_rows(rows), **_state(state))[0]


def logits_and_choices(weights, ids, hp, rows=None, state=None):
    """`logits` and `chosen_experts` of one forward."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp,
                        rows=_rows(rows), **_state(state))[:2]


def chosen_experts(weights, ids, hp):
    """The experts every token chose in every layer with experts:
    [L_moe, S, k], each row sorted by s + b, highest first."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[1]


def conv_state(weights, ids, hp, n=None):
    """What a server keeps of ids[:n] (n: all of them) for its next
    token, a conv layer: rows n - 2, n - 1 of u, [L_conv, 2, d] (zeros
    before the sequence's first row)."""
    n = len(ids) if n is None else int(n)
    u = _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp, rows=(0,))[3]
    return jnp.pad(u, ((0, 0), (2, 0), (0, 0)))[:, n:n + 2]


def logits_on_routes(weights, ids, hp, routes, rows=None):
    """The full causal forward with every token's experts forced to
    `routes` [L_moe, S, k] (what a program chose): (logits [S, V] or [R,
    V], shortfall [L_moe, S]), as the module's text says."""
    logits, _, shortfall, _ = _forward_jit(
        weights, jnp.asarray(ids, jnp.int32), hp,
        jnp.asarray(routes, jnp.int32), _rows(rows))
    return logits, shortfall
