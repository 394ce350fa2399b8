"""Plain reference of Phi-4-mini-flash-reasoning's decoder-hybrid-decoder
(`model_type: phi4flash`, `microsoft/Phi-4-mini-flash-reasoning`; SambaY,
arXiv:2507.06607, with differential attention, arXiv:2410.05258): float32
`jax.numpy`, a token-by-token scan, dense masked attention, no kernels, no
cache, no state handed in, no batching. Independent of `paddle_tpu`: it
imports nothing from the program, and takes the weights as a plain dict.

One layer, x [S, d], of kind `kinds[l]`, LN a LayerNorm with gain and bias:

    h = x + mix(LN1(x));   y = h + (silu(LN2(h) Wg) * (LN2(h) Wu)) Wd
    logits = LN_f(x_L) E^T,   E the embedding (tied). No positions anywhere.

    mamba / memory:  [x | z] = u W_in                            (no bias)
                     x_t = silu(b_c + sum_{j<4} w_j * x_{t-3+j})  (depthwise,
                           causal, zeros before the start)
                     [dt | B | C] = x W_x                (rank, N, N)
                     D_t = softplus(dt W_dt + b_dt)      [d_inner]
                     A = -exp(A_log)                     [d_inner, N]
                     S_t = exp(D_t A) * S_{t-1} + (D_t x_t) B_t^T
                     y_t = S_t C_t + D_skip * x_t
                     mix = (y_t * silu(z_t)) W_out;  a "memory" layer also
                     keeps m_t = y_t for the later layers of the same token
    gmu:             mix = (silu(u W_in) * m_t) W_out    (no bias, no state)
    window / full / cross (differential attention):
                     q = u Wq + bq -> [S, H, D]; k, v = u Wk + bk, u Wv + bv
                     -> [S, H_kv, D]; a "cross" layer has q alone and reads
                     the k, v of the nearest earlier "full" layer.
                     Query heads fall in two sets of H / 2 (the halves), K
                     and V heads in two sets of H_kv / 2; with g = h // (H /
                     H_kv) for h < H / 2:
                     A1[h] = softmax(q1[h] k1[g]^T / sqrt(D)) [v1[g] | v2[g]]
                     A2[h] = softmax(q2[h] k2[g]^T / sqrt(D)) [v1[g] | v2[g]]
                     causal; a "window" layer over the `window` rows that end
                     at the token, the token counted.
                     lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
                     lam_init = 0.8 - 0.6 exp(-0.3 i), i the layer's
                     PUBLISHED index (`layer_ids[l]`)
                     O[h] = RMSNorm_2D(A1[h] - lam A2[h]) * g * (1 - lam_init)
                     mix = concat_h O[h] Wo + bo

Departures from the issue's text, both of form alone: the FFN's `W1` is its
halves `gate` and `up` ([g | u] = LN2(h) [gate | up]) and `W_qkv` its three
parts, so that no second copy of a weight is made beside a server's.

What is read into the published config, each an inference the configuration
file lists under `assumed`: the `mamba_ssm` defaults (d_inner 2 d, N 16, 4
taps, rank d / 16), which heads pair (the halves), that the window counts
the token itself.

On a TPU a float32 matmul runs in reduced precision unless asked, so every
entry point runs under `jax.default_matmul_precision("highest")`. Attention
takes its query rows `_ROW_BLOCK` at a time.

Weights: `{"tok_emb": [V, d], "ln_f": (g, b), "layers": [...]}`, every
matrix `[in, out]`. Every layer has `"ln1", "ln2": (g, b)`, `"gate", "up":
[d, f]`, `"down": [f, d]`. A mamba layer: `"in": [d, 2 di]`, `"conv_w": [4,
di]`, `"conv_b"`, `"x": [di, rank + 2 N]`, `"dt_w": [rank, di]`, `"dt_b"`,
`"a_log": [di, N]`, `"d_skip"`, `"out": [di, d]`. A gmu layer: `"in": [d,
di]`, `"out": [di, d]`. An attention layer: `"q": [d, H D]`, `"q_b"`,
`"out": [H D, d]`, `"out_b"`, `"lq1", "lk1", "lq2", "lk2": [D]`,
`"subnorm": [2 D]`, and unless it is a cross layer `"k", "v": [d, H_kv D]`,
`"k_b", "v_b"`.

`Hyper`'s last fields and the `state` argument are not the model's: each
makes the reference WRONG in one part, for the tool that shows a check's
limits fail it (`benchmark/tools/phi4flash_check_readings.py`). `state` =
(n, [(S [di, N], rows [3, di]) a mamba layer]) makes the rows from position
n on start from that state where they would start from the sequence's own:
a server whose decode steps start from another state than the prompt's
(`states` gives the state any sequence leaves).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    n_head: int
    n_kv: int
    head_dim: int
    kinds: Tuple[str, ...]      #: a layer's kind, "mamba" | "memory" |
    #: "window" | "full" | "gmu" | "cross", layer l taking entry l
    layer_ids: Tuple[int, ...]  #: a layer's index in the published model
    window: int
    d_state: int
    dt_rank: int
    eps: float = 1e-5
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: what a check's limits
    #: must fail (the nearest precision below the configuration's)
    # -- faults, one at a time ------------------------------------------
    lam: str = "published"    #: "dropped": no second softmax subtracted |
    #: "cut_index": lam_init from the layer's index in the cut
    subnorm: str = "scaled"   #: "none": no sub-norm | "unscaled": its
    #: (1 - lam_init) left out
    memory: str = "before"    #: "after": m taken after the gate
    cross: str = "full"       #: "windowed": a cross layer reads a window
    #: layer's rows, the newest `window` alone
    dt_bias: str = "before"   #: "after": b_dt added after the softplus

    @classmethod
    def of(cls, config) -> "Hyper":
        if config["model_type"] != "phi4flash" \
                or config["hidden_act"] != "silu" \
                or config["mlp_bias"] or config["lm_head_bias"] \
                or not config["tie_word_embeddings"] \
                or int(config["mb_per_layer"]) != 2:
            raise ValueError(
                "this reference writes the SambaY decoder-hybrid-decoder: "
                "selective scans and differential attention in turn, a "
                "gated SiLU FFN without a bias, a tied head")
        kept = config["layers_held"]
        return cls(int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]),
                   int(config["hidden_size"])
                   // int(config["num_attention_heads"]),
                   tuple(layer_kinds(config)), tuple(int(i) for i in kept),
                   int(config["sliding_window"]),
                   int(config["assumed_sizes"]["mamba_d_state"]),
                   int(config["assumed_sizes"]["mamba_dt_rank"]),
                   float(config["layer_norm_eps"]))


def layer_kinds(config):
    """The kind of every layer the configuration holds, by its published
    index: of the first decoder (the first half and one layer) even
    indices scan and odd ones attend over a window; its last scan hands
    its memory on and the layer after it attends over everything; of the
    second decoder even indices are gated memory units and odd ones
    cross layers."""
    half = int(config["published"]["num_hidden_layers"]) // 2
    kinds = []
    for i in config["layers_held"]:
        i = int(i)
        if i < half:
            kinds.append("window" if i % 2 else "mamba")
        elif i in (half, half + 1):
            kinds.append("full" if i % 2 else "memory")
        else:
            kinds.append("cross" if i % 2 else "gmu")
    return kinds


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used, so the
    bfloat16 form never holds a second copy of the model."""
    return x @ w.astype(x.dtype)


def _ln(x, gb, eps):
    g, b = gb
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _scan(dt, xc, b, c, a, s0):
    """The recurrence, a token at a time: dt, xc [S, di]; b, c [S, N]; a
    [di, N]; s0 [di, N] -> (S_t C_t [S, di], the last state)."""
    def step(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s.astype(s0.dtype), (s @ c_t).astype(xc.dtype)

    last, ys = jax.lax.scan(step, s0, (dt, xc, b, c))
    return ys, last


def _mamba(u, w, hp, start=None):
    """(mix, m, (the last state, the last three rows of x)); `start` =
    (n, (S, rows)): the rows from n on start from that state."""
    n_state, rank = hp.d_state, hp.dt_rank
    xz = _mm(u, w["in"])
    di = xz.shape[-1] // 2
    xs, z = xz[:, :di], xz[:, di:]
    taps = w["conv_w"].astype(u.dtype)
    n_taps = taps.shape[0]
    a = -jnp.exp(w["a_log"].astype(jnp.float32)).astype(u.dtype)

    def run(xs, before, s0):
        """xs [S', di] after the rows `before` [3, di], from state s0."""
        seq = xs.shape[0]
        back = jnp.concatenate([before.astype(xs.dtype), xs], axis=0)
        xc = _silu(w["conv_b"].astype(u.dtype) + sum(
            taps[j] * back[j:j + seq] for j in range(n_taps)))
        proj = _mm(xc, w["x"])
        dt = _mm(proj[:, :rank], w["dt_w"])
        if hp.dt_bias == "after":
            dt = jax.nn.softplus(dt) + w["dt_b"].astype(u.dtype)
        else:
            dt = jax.nn.softplus(dt + w["dt_b"].astype(u.dtype))
        ys, last = _scan(dt, xc, proj[:, rank:rank + n_state],
                         proj[:, rank + n_state:], a, s0.astype(u.dtype))
        y = ys + w["d_skip"].astype(u.dtype) * xc
        return y, last, back[-(n_taps - 1):]

    zeros = (jnp.zeros((di, n_state), u.dtype),
             jnp.zeros((n_taps - 1, di), u.dtype))
    if start is None:
        y, last, rows = run(xs, zeros[1], zeros[0])
    else:
        n, (s_n, rows_n) = start
        head, _, _ = run(xs[:n], zeros[1], zeros[0])
        tail, last, rows = run(xs[n:], rows_n, s_n)
        y = jnp.concatenate([head, tail], axis=0)
    gated = y * _silu(z)
    return _mm(gated, w["out"]), (gated if hp.memory == "after" else y), \
        (last, rows)


#: query rows an attention layer takes at a time
_ROW_BLOCK = 256


def _attention(u, w, hp, kind, layer_id, cut_index, kv=None):
    """(mix, (k, v)) of a differential layer; `kv`: another layer's, for
    a cross layer."""
    seq = u.shape[0]
    heads, n_kv, hd = hp.n_head, hp.n_kv, hp.head_dim
    q = (_mm(u, w["q"]) + w["q_b"].astype(u.dtype)).reshape(seq, heads, hd)
    if kv is None:
        k = (_mm(u, w["k"]) + w["k_b"].astype(u.dtype)).reshape(
            seq, n_kv, hd)
        v = (_mm(u, w["v"]) + w["v_b"].astype(u.dtype)).reshape(
            seq, n_kv, hd)
    else:
        k, v = kv
    half, half_kv = heads // 2, n_kv // 2
    group = heads // n_kv
    window = hp.window if kind == "window" or (
        kind == "cross" and hp.cross == "windowed") else 0
    both = jnp.concatenate([v[:, :half_kv], v[:, half_kv:]], axis=-1)
    kpos = jnp.arange(seq)[None, :]

    def attend(qs, ks, rows):
        """qs [R, H / 2, D] of one set at positions `rows`, ks [S, H_kv
        / 2, D] of the same set -> [R, H / 2, 2 D]."""
        seen = kpos <= rows[:, None]
        if window:
            seen = seen & (kpos > rows[:, None] - window)
        outs = []
        for h in range(half):
            g = h // group
            sc = (qs[:, h] @ ks[:, g].T).astype(jnp.float32) \
                / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            outs.append(p.astype(u.dtype) @ both[:, g])
        return jnp.stack(outs, axis=1)

    index = cut_index if hp.lam == "cut_index" else layer_id
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    f32 = jnp.float32
    lam = jnp.exp(jnp.sum(w["lq1"].astype(f32) * w["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(w["lq2"].astype(f32) * w["lk2"].astype(f32))) \
        + lam_init
    blocks = []
    for lo in range(0, seq, _ROW_BLOCK):
        rows = jnp.arange(lo, min(lo + _ROW_BLOCK, seq))
        a1 = attend(q[rows, :half], k[:, :half_kv], rows)
        if hp.lam == "dropped":
            diff = a1
        else:
            a2 = attend(q[rows, half:], k[:, half_kv:], rows)
            diff = a1 - lam.astype(u.dtype) * a2
        if hp.subnorm != "none":
            df = diff.astype(f32)
            diff = (df * jax.lax.rsqrt(
                jnp.mean(jnp.square(df), axis=-1, keepdims=True) + hp.eps)
                * w["subnorm"].astype(f32)).astype(u.dtype)
            if hp.subnorm == "scaled":
                diff = diff * (1.0 - lam_init)
        blocks.append(diff.reshape(len(rows), heads * hd))
    mixed = jnp.concatenate(blocks, axis=0)
    return _mm(mixed, w["out"]) + w["out_b"].astype(u.dtype), (k, v)


def _forward(weights, ids, hp, state=None):
    """The final hidden rows [S, d] (before LN_f) and every mamba
    layer's (last state, last rows)."""
    dtype = jnp.dtype(hp.dtype)
    x = jnp.take(weights["tok_emb"], ids, axis=0).astype(dtype)
    memory, pool, left, scans = None, None, [], 0
    for at, (kind, w) in enumerate(zip(hp.kinds, weights["layers"])):
        u = _ln(x, w["ln1"], hp.eps)
        if kind in ("mamba", "memory"):
            start = None if state is None else (state[0], state[1][scans])
            mix, m, after = _mamba(u, w, hp, start)
            left.append(after)
            scans += 1
            if kind == "memory":
                memory = m
        elif kind == "gmu":
            mix = _mm(_silu(_mm(u, w["in"])) * memory, w["out"])
        else:
            mix, kv = _attention(u, w, hp, kind, hp.layer_ids[at], at,
                                 kv=pool if kind == "cross" else None)
            if kind == "full":
                pool = kv
        h = x + mix
        g = _ln(h, w["ln2"], hp.eps)
        x = h + _mm(_silu(_mm(g, w["gate"])) * _mm(g, w["up"]), w["down"])
    return x, left


@functools.partial(jax.jit, static_argnames=("hp", "state_at"))
def _logits(weights, ids, rows, hp, state_at, state):
    with jax.default_matmul_precision("highest"):
        x, _ = _forward(weights, ids, hp,
                        None if state is None else (state_at, state))
        x = _ln(x[rows], weights["ln_f"], hp.eps)
        return (x @ weights["tok_emb"].astype(x.dtype).T).astype(
            jnp.float32)


def logits(weights, ids, hp: Hyper, rows=None, state=None):
    """The logits [R, V] of positions `rows` (all of them unless given)
    of the sequence `ids` [S]."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)
    if state is None:
        return _logits(weights, ids, rows, hp, None, None)
    return _logits(weights, ids, rows, hp, int(state[0]), list(state[1]))


@functools.partial(jax.jit, static_argnames=("hp",))
def _states(weights, ids, hp):
    with jax.default_matmul_precision("highest"):
        return _forward(weights, ids, hp)[1]


def states(weights, ids, hp: Hyper):
    """What the sequence `ids` leaves in every mamba layer: [(S [di, N],
    the last three rows of x [3, di])]."""
    return _states(weights, jnp.asarray(ids, jnp.int32), hp)
