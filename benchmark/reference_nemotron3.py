"""Plain reference of the Nemotron-H block (`model_type: nemotron_h`,
`nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`): float32 `jax.numpy`, the
recurrence a token at a time, dense masked attention, a loop over the
experts, no kernels, no cache, no state handed in, no batching.
Independent of `paddle_tpu`: it imports nothing from the program, and
takes the weights as a plain dict.

Every layer is ONE part under one RMSNorm and one residual, x' = x +
f(N(x)), its kind the layer's letter of `hybrid_override_pattern`; u =
N(x) [S, d]; no bias but the convolution's:

    M  [z | xBC | dt] = u W_in            d_i | d_i + 2 G N | H   (d_i = H P)
       xBC_t = silu(b_c + sum_j w_j xBC_{t-3+j})     depthwise, causal, 4 taps
       [x | B | C] = xBC                  d_i | G N | G N
       head h: channels P h .. P h + P - 1 of x, group g(h) = h // (H / G)
       D_t[h] = softplus(dt_t[h] + b_dt[h])
       A[h]   = -exp(A_log[h])
       S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)]  [P, N]
       y_t[h] = S_t[h] C_t[g(h)] + Dskip[h] x_t[h]
       v_t = y_t * silu(z_t)              the gate BEFORE the norm
       n_t = v_t / sqrt(mean over each group's d_i / G channels of v_t^2
             + eps) * w
       f = n_t W_out
    *  q = u Wq -> [S, H_a, D]   k = u Wk, v = u Wv -> [S, H_kv, D]
       NO rotation, no position table; query head j reads K/V head
       j // (H_a / H_kv); scores q.k / sqrt(D), causal, softmax in float32
       f = concat(heads) Wo
    E  s = sigmoid(u Wr) in R^E (float32); T = the k largest of s + b (of
       equal scores the lower index)
       w_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' + 1e-20)
       f = sum_{e in T} w_e relu(u Wup_e)^2 Wdown_e + relu(u Sup)^2 Sdown
    logits = N_f(x_L) W_head

A share of the experts: the weights hold experts `Hyper.experts_first`
.. `experts_first + held - 1` ([held, ...]); the router keeps all E outputs
and its k a token, and only the chosen experts that are held are summed:
one chip's part of an expert-parallel layer, the shared expert counted
with it. With every expert held that is the whole layer.

On a TPU a float32 matmul runs in reduced precision unless asked, so
every entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, every layer with `"ln": g [d]` and, by
its kind: M `"in": [d, 2 d_i + 2 G N + H]`, `"conv_w": [4, d_i + 2 G N]`
(tap j weighs the row 3 - j before the token), `"conv_b"`, `"dt_b",
"a_log", "d_skip": [H]`, `"norm": [d_i]`, `"out": [d_i, d]`; * `"q": [d,
H_a D]`, `"k", "v": [d, H_kv D]`, `"out": [H_a D, d]`; E `"router": [d,
E]`, `"router_bias": [E]`, `"up": [held, d, f]`, `"down": [held, f, d]`,
`"shared_up": [d, f_s]`, `"shared_down": [f_s, d]`. An expert's matrices
may be STORED wider than published, in whole tiles with zeros behind the
published width: `up` [held, d, f'], `down` [held, f', d'] (`_expert`).

Forced routes (`logits_on_routes`): as `reference_lfm2.py`'s: the
reference computes the same equations on the experts a program chose
([L_E, S, k]) with ITS OWN weights for them, and reports the shortfall 1
- (smallest s + b of the forced experts) / (its own k-th s + b).

`Hyper`'s last fields and the `state` argument are not the model's: each
makes the reference WRONG in one part, for the tool that shows a check's
limits fail it (`benchmark/tools/nemotron3_check_readings.py`). `state` =
(n, [(S [H, P, N], rows [3, d_i + 2 G N]) an M layer]) makes the rows
from position n on start from that state where they would start from the
sequence's own (`states` gives the state any sequence leaves).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

_KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


class Hyper(NamedTuple):
    kinds: Tuple[str, ...]    #: a layer's kind, "mamba" | "experts" |
    #: "attention", layer l taking entry l
    n_head: int               #: attention: query heads,
    n_kv: int                 #: K/V heads,
    head_dim: int             #: and their width
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    d_state: int
    top_k: int
    experts_first: int = 0    #: the first expert the weights hold (they
    #: hold as many as their leading dimension says)
    routed_scale: float = 1.0
    eps: float = 1e-5
    norm_topk: bool = True
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16: what a check's limits
    #: must fail (the nearest precision below the configuration's)
    # -- faults, one at a time ------------------------------------------
    gate: str = "before"      #: "after": the gate behind the norm
    norm: str = "groups"      #: "whole": one norm over all d_i channels
    pairing: str = "blocked"  #: "strided": head h reads group h % G
    dt_bias: str = "before"   #: "after": b_dt added behind the softplus
    skip: str = "kept"        #: "dropped": no Dskip x
    conv: str = "whole"       #: "no_bias" | "no_silu"
    act: str = "relu2"        #: "relu": not squared | "gated_silu": silu(a)
    #: * a of the same two matrices (a = u W_up: no third matrix exists)
    weigh: str = "unbiased"   #: "biased": the weights from s + b
    shared: str = "kept"      #: "dropped": no shared expert
    rotary: str = "none"      #: "half": q and k rotated by position

    @classmethod
    def of(cls, config) -> "Hyper":
        if config["model_type"] != "nemotron_h" \
                or config["mlp_hidden_act"] != "relu2" \
                or int(config["n_group"]) != 1 \
                or int(config["topk_group"]) != 1 \
                or config.get("mamba_proj_bias") \
                or config.get("attention_bias") or config.get("mlp_bias") \
                or not config.get("use_conv_bias", True) \
                or int(config["conv_kernel"]) != 4:
            raise ValueError(
                "this reference writes Nemotron-H: Mamba-2 layers with a "
                "biased convolution of four taps, attention without "
                "positions, two-matrix relu2 experts chosen by sigmoid "
                "plus a bias with no group limit, no other bias")
        layers = int(config["num_hidden_layers"])
        return cls(
            tuple(_KINDS[c]
                  for c in config["hybrid_override_pattern"][:layers]),
            int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]),
            int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
            int(config["n_groups"]), int(config["ssm_state_size"]),
            int(config["num_experts_per_tok"]),
            int(config.get("experts_first", 0)),
            float(config["routed_scaling_factor"]),
            float(config["layer_norm_epsilon"]),
            bool(config["norm_topk_prob"]))


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used, so the
    bfloat16 form never holds a second copy of the model."""
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
    """(f [S, d], (the last state [H, P, N], the last three rows of xBC));
    `start` = (n, (S, rows)): the rows from n on start from that
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
    head_group = (jnp.arange(heads) % groups if hp.pairing == "strided"
                  else jnp.arange(heads) // (heads // groups))

    def run(xbc, dt, before, s0):
        """xbc [S', .] after the rows `before` [3, .], from state s0."""
        seq = xbc.shape[0]
        back = jnp.concatenate([before.astype(xbc.dtype), xbc], axis=0)
        conv = sum(taps[j] * back[j:j + seq] for j in range(n_taps))
        if hp.conv != "no_bias":
            conv = conv + w["conv_b"].astype(u.dtype)
        if hp.conv != "no_silu":
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
        shape = v.shape if hp.norm == "whole" \
            else (v.shape[0], groups, di // groups)
        vf = v.astype(jnp.float32).reshape(shape)
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
    q = q.reshape(seq, hp.n_kv, group, hp.head_dim)
    cols = jnp.arange(seq)[None, :]
    blocks = []
    for lo in range(0, seq, _ROW_BLOCK):
        qb = q[lo:lo + _ROW_BLOCK]
        rows = lo + jnp.arange(qb.shape[0])[:, None]
        scores = jnp.einsum("qngd,knd->ngqk", qb, k).astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hp.head_dim))
        scores = jnp.where((cols <= rows)[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1).astype(u.dtype)
        blocks.append(jnp.einsum("ngqk,knd->qngd", p, v))
    ctx = jnp.concatenate(blocks, axis=0)
    return _mm(ctx.reshape(seq, hp.n_head * hp.head_dim), w["out"])


def _expert(g, up, down, hp):
    """One expert on the rows g [S, d], at its PUBLISHED widths whatever
    its matrices are stored at: `up` [d, f'] and `down` [f', d'] may be
    stored in whole tiles, f' >= f and d' >= d with zeros behind the
    published width. The hidden padding passes through the activation
    as zeros (relu(0)^2 = 0) and adds nothing to any sum; the down
    product is cut to the model width it was given. A NONZERO value in
    the hidden padding (a column of `up` with its row of `down`) does
    change the result: the reference does not hide a program that
    computes with its padding. Columns of `down` past d are no part of
    the model: whoever computes them drops them."""
    a = _mm(g, up)
    h = (jnp.maximum(a, 0) if hp.act == "relu" else
         _silu(a) * a if hp.act == "gated_silu" else
         jnp.square(jnp.maximum(a, 0)))
    return _mm(h, down)[:, :g.shape[-1]]


def _route(g, w, hp, forced=None):
    """g [S, d] -> (chosen experts [S, k], lower index first among
    equals; the [S, E] weight of every expert, 0 off the chosen; the
    shortfall [S] of `forced` [S, k], which then takes the place of the
    reference's own choice)."""
    s = jax.nn.sigmoid(_mm(g, w["router"]).astype(jnp.float32))
    biased = s + w["router_bias"].astype(jnp.float32)
    rows = jnp.arange(s.shape[0])[:, None]
    own = jnp.argsort(-biased, axis=-1, stable=True)[:, :hp.top_k]
    chosen = own if forced is None else forced
    shortfall = 1.0 - jnp.min(biased[rows, chosen], axis=-1) \
        / biased[rows, own][:, -1]
    mask = jnp.zeros(s.shape, bool).at[rows, chosen].set(True)
    wts = jnp.where(mask, biased if hp.weigh == "biased" else s, 0.0)
    if hp.norm_topk:
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
    return chosen, wts * hp.routed_scale, shortfall


def _experts(g, w, wts, hp):
    """Every HELD expert on every row, weighed by its column of wts [S,
    E] (0 off a row's chosen): a loop over the experts, written as a
    scan so that they compile as one body. float32 [S, d]."""
    held = w["up"].shape[0]
    cols = jax.lax.dynamic_slice_in_dim(wts, hp.experts_first, held, axis=1)

    def one(acc, expert):
        up, down, col = expert
        return acc + col[:, None].astype(jnp.float32) * _expert(
            g, up, down, hp).astype(jnp.float32), None

    out, _ = jax.lax.scan(one, jnp.zeros(g.shape, jnp.float32),
                          (w["up"], w["down"], cols.T.astype(g.dtype)))
    return out


def _forward(weights, ids, hp, forced=None, state=None):
    """ids [S] -> (the final hidden rows [S, d], before N_f; chosen
    experts [L_E, S, k]; shortfall [L_E, S] of `forced`; every M layer's
    (last state, last rows))."""
    x = jnp.take(weights["tok_emb"], ids, axis=0).astype(
        jnp.dtype(hp.dtype))
    routes, shortfalls, left = [], [], []
    for kind, w in zip(hp.kinds, weights["layers"]):
        u = _rms(x, w["ln"], hp.eps)
        if kind == "mamba":
            start = None if state is None else (state[0],
                                                state[1][len(left)])
            f, after = _mamba(u, w, hp, start)
            left.append(after)
        elif kind == "attention":
            f = _attention(u, w, hp)
        else:
            chosen, wts, shortfall = _route(
                u, w, hp, None if forced is None else forced[len(routes)])
            routes.append(chosen)
            shortfalls.append(shortfall)
            f = _experts(u, w, wts, hp)
            if hp.shared != "dropped":
                f = f + _expert(u, w["shared_up"], w["shared_down"],
                                hp).astype(jnp.float32)
        x = (x.astype(jnp.float32) + f.astype(jnp.float32)).astype(x.dtype)
    none = jnp.zeros((0, ids.shape[0]), jnp.int32)
    return (x, jnp.stack(routes) if routes else none,
            jnp.stack(shortfalls) if routes else none, left)


@functools.partial(jax.jit, static_argnames=("hp", "state_at"))
def _logits(weights, ids, rows, hp, forced, state_at, state):
    with jax.default_matmul_precision("highest"):
        x, routes, shortfall, _ = _forward(
            weights, ids, hp, forced,
            None if state is None else (state_at, state))
        x = _rms(x[rows], weights["ln_f"], hp.eps)
        return _mm(x, weights["head"]).astype(jnp.float32), routes, \
            shortfall


def _rows(ids, rows):
    return jnp.arange(ids.shape[0]) if rows is None \
        else jnp.asarray(rows, jnp.int32)


def _state(state):
    return (None, None) if state is None \
        else (int(state[0]), list(state[1]))


def logits(weights, ids, hp: Hyper, rows=None, state=None):
    """The logits [R, V] of positions `rows` (all of them unless given)
    of the sequence `ids` [S]."""
    ids = jnp.asarray(ids, jnp.int32)
    return _logits(weights, ids, _rows(ids, rows), hp, None,
                   *_state(state))[0]


def logits_and_choices(weights, ids, hp: Hyper, rows=None, state=None):
    """`logits` and `chosen_experts` of one forward."""
    ids = jnp.asarray(ids, jnp.int32)
    return _logits(weights, ids, _rows(ids, rows), hp, None,
                   *_state(state))[:2]


def chosen_experts(weights, ids, hp: Hyper):
    """The experts every token chose in every E layer: [L_E, S, k], each
    row sorted by s + b, highest first."""
    ids = jnp.asarray(ids, jnp.int32)
    return _logits(weights, ids, _rows(ids, (0,)), hp, None, None, None)[1]


def logits_on_routes(weights, ids, hp: Hyper, routes, rows=None,
                     state=None):
    """The forward with every token's experts forced to `routes` [L_E,
    S, k] (what a program chose): (logits [R, V], shortfall [L_E, S]),
    as the module's text says."""
    ids = jnp.asarray(ids, jnp.int32)
    out, _, shortfall = _logits(weights, ids, _rows(ids, rows), hp,
                                jnp.asarray(routes, jnp.int32),
                                *_state(state))
    return out, shortfall


@functools.partial(jax.jit, static_argnames=("hp",))
def _states(weights, ids, hp, forced):
    with jax.default_matmul_precision("highest"):
        return _forward(weights, ids, hp, forced)[3]


def states(weights, ids, hp: Hyper, routes=None):
    """What the sequence `ids` leaves in every M layer: [(S [H, P, N],
    the last three rows of xBC [3, d_i + 2 G N])]."""
    return _states(weights, jnp.asarray(ids, jnp.int32), hp,
                   None if routes is None
                   else jnp.asarray(routes, jnp.int32))


def experts_layer(w, u, hp: Hyper):
    """One E layer's f(u) alone, u [S, d] the normed input, with the
    reference's own routes: the routed part of the experts `hp` says the
    weights hold, and the shared expert's, apart (float32 [S, d] each):
    what the test of the shares adds up."""
    with jax.default_matmul_precision("highest"):
        u = jnp.asarray(u, jnp.float32)
        _, wts, _ = _route(u, w, hp)
        return _experts(u, w, wts, hp), _expert(
            u, w["shared_up"], w["shared_down"], hp).astype(jnp.float32)
