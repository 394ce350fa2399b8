"""Plain reference of the Mellum 2 block (`model_type: mellum`,
`JetBrains/Mellum2-12B-A2.5B-Instruct`) and of its training loss: float32
`jax.numpy`, a loop over the experts, no kernels, no AMP, no batching.
Independent of `paddle_tpu`: it imports nothing from the program, and takes
the weights as a plain dict. `jax.grad` of `mean_loss` is the reference of
every gradient.

With x_0 = E[ids] (E the held rows of the table), layer l of kind
`layer_types[l]`, x [S, d]:

    h  = x / sqrt(mean(x^2) + eps) * g1_l                       (RMSNorm)
    q  = h Wq -> [S, H, D]   k = h Wk, v = h Wv -> [S, H_kv, D]   (no bias,
                                                             NO q/k-norm)
    q, k <- x cos + rotate_half(x) sin, pairs (i, i + D/2),
            cos, sin = c cos(p w), c sin(p w), p the position from 0:
      sliding_attention: w_i = theta^(-2i/D), c = 1
      full_attention:    YaRN as `transformers` computes it: with
            f(n) = D ln(original / (2 pi n)) / (2 ln theta),
            low = floor(f(beta_fast)), high = ceil(f(beta_slow)),
            ramp_i = clip((i - low) / (high - low), 0, 1),
            w_i = (1 - ramp_i) theta^(-2i/D) + ramp_i theta^(-2i/D) / factor
            and c = attention_factor on cos and sin both (the scores carry
            c^2). At the published keys low = 18, high = 35: pairs 0-17
            turn as they did, pairs 35-63 sixteen times slower. The table
            does not depend on the sequence's length.
    scores q k^T / sqrt(D), query head j against K/V head j // (H / H_kv);
    row t reads s <= t, a sliding layer also s > t - window (`window` rows,
    itself counted); softmax in float32;  x <- x + concat(heads) Wo
    h  = RMSNorm(x; g2_l)
    p  = softmax_f32(h Wr) over all E;  T = the k largest (of equal
         scores the lower index);  w_e = p_e / sum_{e' in T} p_e'
    x <- x + sum_{e in T, first <= e < first + count} w_e
                 (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = RMSNorm(x_L; gf) Wh        over the held rows of the head
    loss   = mean_t (logsumexp(logits_t) - logits_t[target_t])

A SHARE of the experts: the router has all E columns, the weights given
hold experts `first .. first + count - 1` alone and the sum runs over those
of a token's k that are held. What the other experts would add is left out
and the partial sum goes on: the four shares' parts add up to the uncut
layer's (`layer_parts`), and so do their gradients.

Departures from the published description, each listed under `assumed` in
the configuration's file: no q/k-norm (the published keys name none), no
auxiliary router loss and no z-loss (the keys carry no coefficient), no
MTP head (`described_as` names one, `config` has no key for one).
`intermediate_size` is read by no layer (every `mlp_layer_types` entry is
"sparse").

On a TPU a float32 matmul runs in reduced precision unless asked, so every
entry point runs under `jax.default_matmul_precision("highest")`.

Weights: `{"tok_emb": [V, d], "ln_f": g [d], "head": [d, V], "layers":
[...]}`, every matrix `[in, out]`, each layer `{"ln1", "ln2": g [d], "q":
[d, H D], "k", "v": [d, H_kv D], "out": [H D, d], "router": [d, E], "gate",
"up": [C, d, f], "down": [C, f, d]}`.

`Hyper`'s last fields are not the model's: each makes the reference WRONG
in one part, for the tool that shows what a check can and cannot see
(`benchmark/tools/mellum2_check_readings.py`).
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
    window: int
    kinds: Tuple[str, ...]    #: "sliding_attention" | "full_attention",
    #: layer l taking entry l
    top_k: int
    first: int = 0            #: the first expert the weights hold
    eps: float = 1e-6
    theta: float = 500000.0           #: the sliding layers' base
    full_theta: float = 500000.0      #: the full layers'
    yarn: Tuple[float, ...] = ()      #: the full layers' (factor, original
    #: context, beta_fast, beta_slow, attention factor); (): plain
    dtype: str = "float32"    #: "bfloat16": every weight, the residual
    #: stream and every intermediate in bfloat16 (the nearest precision
    #: below the configuration's)
    # -- faults, one at a time ------------------------------------------
    window_off: int = 0       #: the window this many rows long or short
    plain_full: bool = False  #: the full layers on the plain table
    drop: bool = False        #: a token's weakest held pair left out

    @classmethod
    def of(cls, config) -> "Hyper":
        rope = config["rope_parameters"]
        full, sliding = rope["full_attention"], rope["sliding_attention"]
        if config["model_type"] != "mellum" or config["attention_bias"] \
                or config["hidden_act"] != "silu" \
                or not config["norm_topk_prob"] \
                or config["tie_word_embeddings"] \
                or not config["use_sliding_window"] \
                or full["rope_type"] != "yarn" \
                or sliding["rope_type"] != "default" \
                or set(config["mlp_layer_types"]) != {"sparse"}:
            raise ValueError(
                "this reference writes the sequential block with an untied "
                "head, RMSNorm, gated SiLU experts in every layer under a "
                "softmax router renormalised over the chosen, plain RoPE "
                "on the sliding layers and YaRN on the full ones, no bias")
        layers = int(config["num_hidden_layers"])
        held = config.get("published", {}).get("held_experts", {})
        return cls(int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]),
                   int(config["head_dim"]), int(config["sliding_window"]),
                   tuple(config["layer_types"][:layers]),
                   int(config["num_experts_per_tok"]),
                   int(held.get("first", 0)),
                   float(config["rms_norm_eps"]),
                   float(sliding["rope_theta"]), float(full["rope_theta"]),
                   yarn_of(full))


def yarn_of(params) -> Tuple[float, ...]:
    """A `rope_parameters` entry's YaRN five."""
    return (float(params["factor"]),
            float(params["original_max_position_embeddings"]),
            float(params["beta_fast"]), float(params["beta_slow"]),
            float(params["attention_factor"]))


def _mm(x, w):
    """x @ w in x's dtype: a weight is cast where it is used."""
    return x @ w.astype(x.dtype)


def _rms(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf / jnp.sqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)).astype(x.dtype)


def yarn_ends(d, theta, yarn):
    """(low, high): the pairs between which YaRN's ramp runs."""
    _, original, fast, slow, _ = yarn

    def pair_of(turns):
        return d * math.log(original / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    return (max(math.floor(pair_of(fast)), 0),
            min(math.ceil(pair_of(slow)), d - 1))


def rope_table(d, theta, yarn=()):
    """(w [D/2] float32, c): a head's frequencies and what cos and sin are
    multiplied by (the module's text)."""
    plain = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not yarn:
        return plain, 1.0
    low, high = yarn_ends(d, theta, yarn)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / yarn[0], yarn[4]


def _rope(t, theta, yarn):
    """t [S, H, D] at positions 0..S-1, pairs (i, i + D/2)."""
    seq, _, d = t.shape
    w, c = rope_table(d, theta, yarn)
    ang = (jnp.arange(seq, dtype=jnp.float32)[:, None]
           * w[None])[:, None, :]                          # [S, 1, D/2]
    cos, sin = c * jnp.cos(ang), c * jnp.sin(ang)
    tf = t.astype(jnp.float32)
    a, b = tf[..., :d // 2], tf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(t.dtype)


#: a hidden key's score: exp(_HIDDEN - any score) is 0 exactly
_HIDDEN = -1e30

#: query rows of attention at a time: [H, rows, S] scores, so that 8,192
#: rows at the published widths fit beside the state (32 x 64 x 8,192
#: float32: 67 MB)
_ROW_BLOCK = 64


def _attention(h, layer, hp, kind):
    seq = h.shape[0]
    group = hp.n_head // hp.n_kv
    q = _mm(h, layer["q"]).reshape(seq, hp.n_head, hp.head_dim)
    k = _mm(h, layer["k"]).reshape(seq, hp.n_kv, hp.head_dim)
    v = _mm(h, layer["v"]).reshape(seq, hp.n_kv, hp.head_dim)
    local = kind == "sliding_attention"
    theta, yarn = (hp.theta, ()) if local else (
        hp.full_theta, () if hp.plain_full else hp.yarn)
    q, k = _rope(q, theta, yarn), _rope(k, theta, yarn)
    q = q.reshape(seq, hp.n_kv, group, hp.head_dim)
    cols = jnp.arange(seq)[None, :]
    blocks = -(-seq // _ROW_BLOCK)
    q = jnp.pad(q, ((0, blocks * _ROW_BLOCK - seq),) + ((0, 0),) * 3)

    def block(args):
        qb, start = args                                   # [R, Hkv, G, D]
        rows = start + jnp.arange(_ROW_BLOCK)[:, None]
        seen = cols <= rows
        if local:
            seen = seen & (cols > rows - (hp.window + hp.window_off))
        scores = jnp.einsum("qngd,knd->ngqk", qb, k).astype(
            jnp.float32) / jnp.sqrt(jnp.float32(hp.head_dim))
        # (finite: a padded row behind the last window reads no key at
        # all, and the gradient of a softmax over -inf alone is NaN)
        scores = jnp.where(seen[None, None], scores, _HIDDEN)
        p = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        return jnp.einsum("ngqk,knd->qngd", p, v)

    # (a block's scores are computed again under a derivative, not kept:
    # 8,192 rows' worth of them are 8.6 GB a layer at the published widths)
    ctx = jax.lax.map(jax.checkpoint(block), (
        q.reshape((blocks, _ROW_BLOCK) + q.shape[1:]),
        jnp.arange(blocks) * _ROW_BLOCK)).reshape(
            (blocks * _ROW_BLOCK,) + q.shape[1:])[:seq]
    return _mm(ctx.reshape(seq, hp.n_head * hp.head_dim), layer["out"])


#: how close (relative) a row's k-th and (k+1)-th gates lie for the row to
#: count as a near tie: a router that reads bfloat16 activations (eight
#: bits of mantissa) may take the other expert there
NEAR_TIE = 2.0 ** -7


def _route(h, layer, hp, forced=None):
    """h [S, d] -> (chosen experts [S, k], lower index first among
    equals, or `forced` [S, k] in their place; the [S, E] weight of every
    expert, 0 off the chosen; which rows' k-th and (k+1)-th gates are a
    near tie [S])."""
    p = jax.nn.softmax(_mm(h, layer["router"]).astype(jnp.float32),
                       axis=-1)
    rows = jnp.arange(p.shape[0])[:, None]
    order = jnp.argsort(-p, axis=-1, stable=True)
    chosen = order[:, :hp.top_k] if forced is None else forced
    edge = p[rows, order[:, hp.top_k - 1:hp.top_k + 1]]     # [S, 2]
    tie = (edge[:, 0] - edge[:, 1]) < NEAR_TIE * edge[:, 0]
    mask = jnp.zeros(p.shape, bool).at[rows, chosen].set(True)
    w = jnp.where(mask, p, 0.0)
    return chosen, w / jnp.sum(w, axis=-1, keepdims=True), tie


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
        y = _mm(jax.nn.silu(_mm(h, gate)) * _mm(h, up), down)
        return acc + col[:, None].astype(jnp.float32) \
            * y.astype(jnp.float32), None

    out, _ = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (layer["gate"], layer["up"], layer["down"],
         mine.T.astype(h.dtype)))
    return out


def layer_parts(x, layer, hp, kind, forced=None):
    """One layer's two additions to x [S, d], each float32 [S, d]:
    (attention, this share's routed experts of x + attention), the
    experts chosen [S, k] and the rows at a near tie [S]."""
    a = _attention(_rms(x, layer["ln1"], hp.eps), layer, hp,
                   kind).astype(jnp.float32)
    x = (x.astype(jnp.float32) + a).astype(x.dtype)
    h = _rms(x, layer["ln2"], hp.eps)
    chosen, w, tie = _route(h, layer, hp, forced)
    return a, _routed(h, layer, w, hp), chosen, tie


def _forward_one(weights, ids, hp, forced=None):
    """ids [S] -> (logits [S, V] float32, chosen experts [L, S, k], rows
    at a near tie [L, S]); `forced` [L, S, k]: every token's experts, in
    the place of the reference's own choice."""
    x = weights["tok_emb"][ids].astype(jnp.dtype(hp.dtype))
    routes, ties = [], []
    for layer, kind in zip(weights["layers"], hp.kinds):
        # (a layer's activations are computed again under a derivative)
        a, routed, chosen, tie = jax.checkpoint(
            layer_parts, static_argnums=(2, 3))(
                x, layer, hp, kind,
                None if forced is None else forced[len(routes)])
        routes.append(chosen)
        ties.append(tie)
        x = (x.astype(jnp.float32) + a + routed).astype(x.dtype)
    logits = _mm(_rms(x, weights["ln_f"], hp.eps),
                 weights["head"]).astype(jnp.float32)
    return logits, jnp.stack(routes), jnp.stack(ties)


def _loss_one(weights, ids, targets, hp, forced=None):
    logits, routes, ties = _forward_one(weights, ids, hp, forced)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return (jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked), routes,
            ties)


@functools.partial(jax.jit, static_argnames=("hp",))
def _forward_jit(weights, ids, hp):
    with jax.default_matmul_precision("highest"):
        return _forward_one(weights, ids, hp)


@functools.partial(jax.jit, static_argnames=("hp",))
def _loss_jit(weights, ids, targets, hp):
    with jax.default_matmul_precision("highest"):
        return _loss_one(weights, ids, targets, hp)


def logits(weights, ids, hp):
    """Full causal forward of one sequence: ids [S] -> logits [S, V]."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[0]


def chosen_experts(weights, ids, hp):
    """The experts every token chose in every layer: [L, S, k], each row
    sorted by p, highest first."""
    return _forward_jit(weights, jnp.asarray(ids, jnp.int32), hp)[1]


def mean_loss(weights, src, tgt, hp, routes=None):
    """The mean next-token cross-entropy of src [B, S] against tgt
    [B, S], as the trainer's program computes it (every sequence the same
    length, so the mean of the sequences' means). Differentiable:
    `jax.grad(mean_loss)` is the reference of every gradient. `routes`
    [B, L, S, k]: the experts a program chose, forced in the place of the
    reference's own (where a row's k-th and (k+1)-th gates lie closer
    than a lower precision's rounding a program that is right takes the
    other expert, and that row's gradients are then another expert's: the
    weights of the forced experts stay the reference's own)."""
    src, tgt = jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32)
    forced = [None] * src.shape[0] if routes is None \
        else jnp.asarray(routes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        return sum(_loss_one(weights, s, t, hp, f)[0]
                   for s, t, f in zip(src, tgt, forced)) / src.shape[0]


@functools.partial(jax.jit, static_argnames=("hp", "leaves"))
def _gradients_jit(weights, src, tgt, hp, leaves):
    def loss_of(picked):
        layers = [dict(layer) for layer in weights["layers"]]
        for (i, key), leaf in zip(leaves, picked):
            layers[i][key] = leaf
        w = dict(weights, layers=layers)
        return sum(_loss_one(w, s, t, hp)[0]
                   for s, t in zip(src, tgt)) / src.shape[0]

    with jax.default_matmul_precision("highest"):
        return jax.grad(loss_of)([weights["layers"][i][key]
                                  for i, key in leaves])


def gradients(weights, src, tgt, hp, leaves):
    """`jax.grad(mean_loss)` at each of `leaves` ((layer, key), ...) on
    the reference's own routes, as device arrays. Only these gradients
    are formed, and the layers' and the attention blocks' activations are
    computed again, so a step of 8,192 rows at the published widths fits
    beside the trainer's state."""
    return _gradients_jit(
        weights, jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32),
        hp, tuple((int(i), str(key)) for i, key in leaves))


def loss_and_counts(weights, src, tgt, hp, count):
    """(the mean loss of src, tgt [B, S]; what the reference counts of
    its own routes over the step's layers, as the program's
    `pt_train_moe_*` count theirs: routed pairs, pairs on the `count`
    held experts, held experts that received any summed over the layers,
    the largest held expert's rows summed over the layers; the chosen
    experts [B, L, S, k]; the share of (layer, row)s at a near tie,
    `NEAR_TIE`)."""
    src, tgt = jnp.asarray(src, jnp.int32), jnp.asarray(tgt, jnp.int32)
    losses, routes, ties = zip(*(_loss_jit(weights, s, t, hp)
                                 for s, t in zip(src, tgt)))
    routes = jnp.stack(routes)                          # [B, L, S, k]
    local = jnp.moveaxis(routes, 1, 0).reshape(routes.shape[1], -1) \
        - hp.first                                      # [L, B S k]
    held = (local >= 0) & (local < count)
    hits = jnp.sum(jax.nn.one_hot(jnp.where(held, local, count), count + 1,
                                  dtype=jnp.int32), axis=1)[:, :count]
    counts = (int(local.size), int(jnp.sum(hits)),
              int(jnp.sum(hits > 0)), int(jnp.sum(jnp.max(hits, axis=1))))
    return (float(sum(losses) / len(losses)), counts, routes,
            float(jnp.mean(jnp.stack(ties))))
