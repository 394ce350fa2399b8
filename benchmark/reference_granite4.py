"""Plain reference of the Granite 4.0-H block (`model_type:
granitemoehybrid`, `ibm-granite/granite-4.0-h-micro`): float32
`jax.numpy`, the recurrence a token at a time over the whole sequence,
dense masked attention, no kernels, no chunks, no cache, no state handed
in, no batching. Independent of `paddle_tpu`: it imports nothing from the
program, and takes the weights as a plain dict.

Every layer is a MIXER and a dense gated FFN, each under an RMSNorm and a
residual of its own, each branch times `residual_multiplier` r; the
mixer's kind is the layer's entry of `layer_types`; u = N1(x) [S, d]; no
bias but the convolution's:

    x0 = embedding_multiplier * E[ids]
    x  = x + r * mixer(N1(x))
    x  = x + r * (silu(a) * b) W_out          [a | b] = N2(x) W_in
    logits = N_f(x_L) E^T / logits_scaling    (the head is the table)

    mamba      [z | xBC | dt] = u W_in    d_i | d_i + 2 G N | H   (d_i = H P)
       xBC_t = silu(b_c + sum_j w_j xBC_{t-3+j})     depthwise, causal, 4 taps
       [x | B | C] = xBC                  d_i | G N | G N
       head h: channels P h .. P h + P - 1 of x, group g(h) = h // (H / G)
       D_t[h] = softplus(dt_t[h] + b_dt[h])
       A[h]   = -exp(A_log[h])
       S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)]  [P, N]
       y_t[h] = S_t[h] C_t[g(h)] + Dskip[h] x_t[h]
       v_t = y_t * silu(z_t)              the gate BEFORE the norm
       n_t = v_t / sqrt(mean over each group's d_i / G channels of v_t^2
             + eps) * w                   (G = 1: over all d_i)
       mixer = n_t W_out
    attention  q = u Wq -> [S, H_a, D]   k = u Wk, v = u Wv -> [S, H_kv, D]
       NO rotation, no position table (`position_embedding_type: nope`);
       query head j reads K/V head j // (H_a / H_kv); scores q.k *
       attention_multiplier (a constant of the file, NOT 1 / sqrt(D)),
       causal, softmax in float32; mixer = concat(heads) Wo

Departures from the published description, each a matter of storage and
none of arithmetic: the checkpoint's `shared_mlp.input_linear` is ONE
matrix [d, 2 f] whose first half is the gated one; here it arrives as its
two halves, `"gate"` and `"up"` [d, f] each (`[a | b] = [u gate | u up]`),
as the program holds them. Matrices are `[in, out]` (the checkpoint's
`nn.Linear` stores `[out, in]`). The matrices may arrive in bfloat16 (a
served bundle's `weight_dtype`): each is cast up where it is used, so the
reference reads the SAME rounded values the program does and computes
with them in float32.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "layers": [...]}`, every
layer with `"ln1", "ln2": g [d]`, `"gate", "up": [d, f]`, `"down": [f,
d]` and, by its kind: mamba `"in": [d, 2 d_i + 2 G N + H]`, `"conv_w":
[4, d_i + 2 G N]` (tap j weighs the row 3 - j before the token),
`"conv_b"`, `"dt_b", "a_log", "d_skip": [H]`, `"norm": [d_i]`, `"out":
[d_i, d]`; attention `"q": [d, H_a D]`, `"k", "v": [d, H_kv D]`, `"out":
[H_a D, d]`.

`Hyper`'s last fields and the `state` argument are not the model's: each
makes the reference WRONG in one part, for the tool that shows a check's
limits fail it (`benchmark/tools/granite4_check_readings.py`). `state` =
(n, [(S [H, P, N], rows [3, d_i + 2 G N]) a mamba layer]) makes the rows
from position n on start from that state where they would start from the
sequence's own (`states` gives the state any sequence leaves).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    kinds: Tuple[str, ...]    #: a layer's mixer, "mamba" | "attention"
    n_head: int               #: attention: query heads,
    n_kv: int                 #: K/V heads,
    head_dim: int             #: and their width
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    d_state: int
    embed_scale: float = 1.0      #: `embedding_multiplier`
    residual_scale: float = 1.0   #: `residual_multiplier`
    logit_div: float = 1.0        #: `logits_scaling` (the logits are
    #: DIVIDED by it)
    attn_scale: float = 0.0       #: `attention_multiplier`
    eps: float = 1e-5
    dtype: str = "float32"    #: "bfloat16": the residual stream, the scan,
    #: the states and every intermediate in bfloat16: what a check's
    #: limits must fail (the nearest precision below the configuration's)
    # -- faults, one at a time ------------------------------------------
    softmax: str = "config"   #: "rsqrt": scores times 1 / sqrt(D)
    residual: str = "both"    #: "mixer_unscaled" | "ffn_unscaled": that
    #: branch added without the multiplier
    embedding: str = "scaled"     #: "unscaled": no embedding multiplier
    gate: str = "before"      #: "after": the gate behind the norm
    norm: str = "config"      #: "groups_512": the gated norm over runs of
    #: 512 channels (another model's group size)
    dt_bias: str = "before"   #: "after": b_dt added behind the softplus
    skip: str = "kept"        #: "dropped": no Dskip x
    conv: str = "whole"       #: "no_bias": no convolution bias
    rotary: str = "none"      #: "half": q and k rotated by position
    halves: str = "gate_up"   #: "swapped": silu(b) * a

    @classmethod
    def of(cls, config) -> "Hyper":
        if config["model_type"] != "granitemoehybrid" \
                or int(config["num_local_experts"]) \
                or config["position_embedding_type"] != "nope" \
                or config["hidden_act"] != "silu" \
                or config["normalization_function"] != "rmsnorm" \
                or config.get("attention_bias") \
                or config.get("mamba_proj_bias") \
                or not config.get("mamba_conv_bias", True) \
                or not config.get("tie_word_embeddings") \
                or int(config["mamba_d_conv"]) != 4:
            raise ValueError(
                "this reference writes Granite 4.0-H without experts: "
                "Mamba-2 layers with a biased convolution of four taps, "
                "attention without positions or bias, a dense gated-SiLU "
                "FFN in every layer, a tied head")
        kinds = tuple(config["layer_types"][:int(
            config["num_hidden_layers"])])
        if set(kinds) - {"mamba", "attention"}:
            raise ValueError(f"unknown layer types in {kinds}")
        heads = int(config["num_attention_heads"])
        return cls(
            kinds, heads, int(config["num_key_value_heads"]),
            int(config["hidden_size"]) // heads,
            int(config["mamba_n_heads"]), int(config["mamba_d_head"]),
            int(config["mamba_n_groups"]), int(config["mamba_d_state"]),
            float(config["embedding_multiplier"]),
            float(config["residual_multiplier"]),
            float(config["logits_scaling"]),
            float(config["attention_multiplier"]),
            float(config["rms_norm_eps"]))


def _mm(x, w):
    """x @ w in x's dtype: a matrix is cast where it is used, so neither
    form ever holds a second copy of the model."""
    return x @ w.astype(x.dtype)


def _rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _scan(dt, x, b, c, a, s0):
    """The recurrence, a token at a time: dt [S, H]; x [S, H, P]; b, c
    [S, H, N] (each head's own group's); a [H]; s0 [H, P, N] -> (S_t C_t
    [S, H, P], the last state)."""
    def step(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        s = s.astype(s0.dtype)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1).astype(x.dtype)

    last, ys = jax.lax.scan(step, s0, (dt, x, b, c))
    return ys, last


def _mamba(u, w, hp, start=None):
    """(mixer [S, d], (the last state [H, P, N], the last three rows of
    xBC)); `start` = (n, (S, rows)): the rows from n on start from that
    state."""
    heads, p, groups, n_state = (hp.ssm_heads, hp.ssm_head_dim,
                                 hp.ssm_groups, hp.d_state)
    di, gn = heads * p, groups * n_state
    proj = _mm(u, w["in"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * gn], \
        proj[:, 2 * di + 2 * gn:]
    taps = w["conv_w"].astype(u.dtype)
    n_taps = taps.shape[0]
    a = -jnp.exp(w["a_log"].astype(jnp.float32)).astype(u.dtype)
    if hp.dt_bias == "after":
        dt = jax.nn.softplus(dt) + w["dt_b"].astype(u.dtype)
    else:
        dt = jax.nn.softplus(dt + w["dt_b"].astype(u.dtype))
    head_group = jnp.arange(heads) // (heads // groups)

    def run(xbc, dt, before, s0):
        """xbc [S', .] after the rows `before` [3, .], from state s0."""
        seq = xbc.shape[0]
        back = jnp.concatenate([before.astype(xbc.dtype), xbc], axis=0)
        conv = sum(taps[j] * back[j:j + seq] for j in range(n_taps))
        if hp.conv != "no_bias":
            conv = conv + w["conv_b"].astype(u.dtype)
        conv = _silu(conv)
        x = conv[:, :di].reshape(seq, heads, p)
        b = conv[:, di:di + gn].reshape(seq, groups, n_state)
        c = conv[:, di + gn:].reshape(seq, groups, n_state)
        ys, last = _scan(dt, x, b[:, head_group], c[:, head_group], a,
                         s0.astype(u.dtype))
        if hp.skip != "dropped":
            ys = ys + w["d_skip"].astype(u.dtype)[:, None] * x
        return ys.reshape(seq, di), last, back[-(n_taps - 1):]

    zeros = (jnp.zeros((heads, p, n_state), u.dtype),
             jnp.zeros((n_taps - 1, di + 2 * gn), u.dtype))
    if start is None:
        y, last, rows = run(xbc, dt, zeros[1], zeros[0])
    else:
        n, (s_n, rows_n) = start
        head, _, _ = run(xbc[:n], dt[:n], zeros[1], zeros[0])
        tail, last, rows = run(xbc[n:], dt[n:], rows_n, s_n)
        y = jnp.concatenate([head, tail], axis=0)

    def normed(v):
        run_of = min(512, di // groups) if hp.norm == "groups_512" \
            else di // groups
        vf = v.astype(jnp.float32).reshape(v.shape[0], di // run_of, run_of)
        vf = vf / jnp.sqrt(jnp.mean(jnp.square(vf), axis=-1, keepdims=True)
                           + hp.eps)
        return (vf.reshape(v.shape)
                * w["norm"].astype(jnp.float32)).astype(v.dtype)

    v = normed(y) * _silu(z) if hp.gate == "after" else normed(y * _silu(z))
    return _mm(v, w["out"]), (last, rows)


def _rope(t):
    """t [S, H, D] at positions 0..S-1, halves (i, i + D/2), theta 1e4:
    the fault `rotary` applies it."""
    seq, _, d = t.shape
    inv_freq = 1.0 / 10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * inv_freq[None])[:, None, :]
    tf = t.astype(jnp.float32)
    a, b = tf[..., :d // 2], tf[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)],
                           axis=-1).astype(t.dtype)


#: query rows an attention layer takes at a time
_ROW_BLOCK = 256


def _attention(u, w, hp):
    seq = u.shape[0]
    group = hp.n_head // hp.n_kv
    q = _mm(u, w["q"]).reshape(seq, hp.n_head, hp.head_dim)
    k = _mm(u, w["k"]).reshape(seq, hp.n_kv, hp.head_dim)
    v = _mm(u, w["v"]).reshape(seq, hp.n_kv, hp.head_dim)
    if hp.rotary == "half":
        q, k = _rope(q), _rope(k)
    scale = 1.0 / float(hp.head_dim) ** 0.5 \
        if hp.softmax == "rsqrt" or not hp.attn_scale else hp.attn_scale
    q = q.reshape(seq, hp.n_kv, group, hp.head_dim)
    cols = jnp.arange(seq)[None, :]
    blocks = []
    for lo in range(0, seq, _ROW_BLOCK):
        qb = q[lo:lo + _ROW_BLOCK]
        rows = lo + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qngd,knd->ngqk", qb, k).astype(
            jnp.float32) * scale
        scores = jnp.where((cols <= rows)[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
        blocks.append(jnp.einsum("ngqk,knd->qngd", p, v))
    ctx = jnp.concatenate(blocks, axis=0)
    return _mm(ctx.reshape(seq, hp.n_head * hp.head_dim), w["out"])


def _ffn(g, w, hp):
    a, b = _mm(g, w["gate"]), _mm(g, w["up"])
    if hp.halves == "swapped":
        a, b = b, a
    return _mm(_silu(a) * b, w["down"])


def _add(x, f, by):
    return (x.astype(jnp.float32)
            + by * f.astype(jnp.float32)).astype(x.dtype)


def _forward(weights, ids, hp, state=None):
    """ids [S] -> (the final hidden rows [S, d], before N_f; every mamba
    layer's (last state, last rows))."""
    by = hp.embed_scale if hp.embedding == "scaled" else 1.0
    x = (by * jnp.take(weights["tok_emb"], ids, axis=0).astype(
        jnp.float32)).astype(jnp.dtype(hp.dtype))
    left = []
    r = hp.residual_scale
    for kind, w in zip(hp.kinds, weights["layers"]):
        u = _rms(x, w["ln1"], hp.eps)
        if kind == "mamba":
            start = None if state is None else (state[0],
                                                state[1][len(left)])
            f, after = _mamba(u, w, hp, start)
            left.append(after)
        else:
            f = _attention(u, w, hp)
        x = _add(x, f, 1.0 if hp.residual == "mixer_unscaled" else r)
        f = _ffn(_rms(x, w["ln2"], hp.eps), w, hp)
        x = _add(x, f, 1.0 if hp.residual == "ffn_unscaled" else r)
    return x, left


@functools.partial(jax.jit, static_argnames=("hp", "state_at"))
def _logits(weights, ids, rows, hp, state_at, state):
    with jax.default_matmul_precision("highest"):
        x, _ = _forward(weights, ids, hp,
                        None if state is None else (state_at, state))
        x = _rms(x[rows], weights["ln_f"], hp.eps)
        return (_mm(x, weights["tok_emb"].T).astype(jnp.float32)
                / hp.logit_div)


def logits(weights, ids, hp: Hyper, rows=None, state=None):
    """The logits [R, V] of positions `rows` (all of them unless given)
    of the sequence `ids` [S]."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)
    at, given = (None, None) if state is None \
        else (int(state[0]), list(state[1]))
    return _logits(weights, ids, rows, hp, at, given)


@functools.partial(jax.jit, static_argnames=("hp",))
def _states(weights, ids, hp):
    with jax.default_matmul_precision("highest"):
        return _forward(weights, ids, hp)[1]


def states(weights, ids, hp: Hyper):
    """What the sequence `ids` leaves in every mamba layer: [(S [H, P,
    N], the last three rows of xBC [3, d_i + 2 G N])]."""
    return _states(weights, jnp.asarray(ids, jnp.int32), hp)
