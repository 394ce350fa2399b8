"""Mixture-of-Experts FFN with expert parallelism.

ADDITIVE capability (SURVEY §2.4 last row: the reference has no expert
parallelism; designed TPU-first). The classic dense/static MoE
formulation (Mesh-TensorFlow / Switch Transformer): top-k gating, a
FIXED per-expert capacity C, and one-hot dispatch/combine einsums — no
dynamic shapes anywhere, so XLA compiles it like any other op. The
stacked expert weights [E, ...] are sharded over the 'ep' mesh axis
(annotated by the layer); GSPMD turns the dispatch einsum into the
all-to-all that routes tokens to their expert's devices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..core.registry import register_op
from ..kernels import expert_matmul


def _moe_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    aux = block.var(op.output("AuxLoss")[0])
    aux.shape, aux.dtype = (), "float32"


def _moe_tokens(xt, gate_w, top_k, cap_f, act, expert_fn, stat_mean):
    """Shared MoE math over a flat token block xt [n, D].

    `expert_fn(expert_in [E, C, D]) -> expert_out [E, C, D]` runs the
    expert FFNs — locally for the dense path, via all-to-all dispatch for
    the expert-parallel path. `stat_mean(sum_vec, n)` turns local sums
    into global means for the aux loss (psum over the token-sharding axes
    when inside shard_map)."""
    n, _ = xt.shape
    e = gate_w.shape[-1]
    c = max(int(math.ceil(top_k * n / e * cap_f)), 1)

    logits = (xt @ gate_w.astype(xt.dtype)).astype(jnp.float32)   # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)

    combine = jnp.zeros((n, e, c), jnp.float32)
    # iterative top-k assignment (k is 1 or 2: unrolled python loop)
    masked = probs
    counts = jnp.zeros((e,), jnp.int32)
    for _ in range(top_k):
        choice = jnp.argmax(masked, axis=-1)                # [n]
        gate = jnp.take_along_axis(masked, choice[:, None], 1)[:, 0]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.int32)  # [n, E]
        # position of each token within its chosen expert (cumsum order)
        pos = (jnp.cumsum(onehot, axis=0) - 1) + counts[None, :]  # [n, E]
        pos_tok = jnp.sum(pos * onehot, axis=1)             # [n]
        keep = pos_tok < c
        slot = jax.nn.one_hot(pos_tok, c, dtype=jnp.float32)     # [n, C]
        contrib = (gate * keep)[:, None, None] \
            * onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
        combine = combine + contrib
        counts = counts + jnp.sum(onehot, axis=0)
        masked = masked * (1.0 - onehot.astype(jnp.float32))

    if top_k > 1:
        # GShard-style: top-k gates renormalized over the kept set (their
        # RELATIVE weights stay differentiable w.r.t. the router)
        denom = jnp.maximum(jnp.sum(combine, axis=(1, 2), keepdims=True),
                            1e-9)
        combine = combine / denom
    # top_k == 1 keeps the RAW gate probability (Switch Transformer:
    # out = p_i * expert_i(x)) — normalizing would make the weight
    # identically 1 and cut the router off from the task gradient
    dispatch = (combine > 0).astype(xt.dtype)               # [n, E, C]

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xt)     # [E, C, D]
    expert_out = expert_fn(expert_in)                       # [E, C, D]
    out = jnp.einsum("nec,ecd->nd", combine.astype(xt.dtype), expert_out)

    # dropped tokens (no kept slot) pass through unchanged
    routed = jnp.sum(combine, axis=(1, 2)) > 0              # [n]
    out = jnp.where(routed[:, None], out, xt)

    # load-balancing aux loss: E * sum_e (fraction routed_e * mean prob_e)
    top1 = jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32)
    f_e = stat_mean(jnp.sum(top1, axis=0), n)
    p_e = stat_mean(jnp.sum(probs, axis=0), n)
    aux = e * jnp.sum(f_e * p_e)
    return out, aux


def _expert_ffn(expert_in, w1, b1, w2, b2, act):
    h = jnp.einsum("ecd,edh->ech", expert_in,
                   w1.astype(expert_in.dtype)) \
        + b1[:, None, :].astype(expert_in.dtype)
    h = jnp.maximum(h, 0) if act == "relu" else jax.nn.gelu(h)
    return jnp.einsum("ech,ehd->ecd", h, w2.astype(expert_in.dtype)) \
        + b2[:, None, :].astype(expert_in.dtype)


@register_op("moe_ffn", infer_shape=_moe_infer)
def moe_ffn(ctx, ins, attrs):
    """X [..., D]; GateW [D, E]; W1 [E, D, H]; B1 [E, H]; W2 [E, H, D];
    B2 [E, D] -> Out [..., D], AuxLoss [] (load-balancing, Switch
    Transformer eq. 4: E * sum_e f_e * p_e).

    top_k=1 (switch) or 2; capacity_factor bounds per-expert tokens at
    C = ceil(top_k * N / E * capacity_factor); overflow tokens pass
    through unchanged for their dropped slot (residual-friendly).

    On a mesh with an `ep` axis (experts divisible by it, tokens
    divisible by the token-sharding axes) the op enters shard_map:
    tokens shard over (dp, ep), expert weights over ep, and the
    dispatch/combine run as the canonical all-to-all PAIR over ICI —
    [E, C_loc, D] -> [E/ep, ep*C_loc, D] and back — rather than
    trusting GSPMD to reverse-engineer the routing from one-hot einsums
    (measured on the 8-device virtual mesh: the einsum formulation
    all-gathers; tests/test_collectives_emitted.py pins the a2a pair).
    Per-shard capacity (C computed from the LOCAL token count) is the
    GShard/Switch formulation; with ample capacity_factor it matches the
    dense path bit-for-bit (tested)."""
    x = ins["X"][0]
    gate_w = ins["GateW"][0]
    w1, b1 = ins["W1"][0], ins["B1"][0]
    w2, b2 = ins["W2"][0], ins["B2"][0]
    top_k = int(attrs.get("top_k", 1))
    cap_f = float(attrs.get("capacity_factor", 1.25))
    act = attrs.get("act", "relu")

    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)                                   # [N, D]
    n = xt.shape[0]
    e = gate_w.shape[-1]

    from ..parallel.mesh import DP, EP
    mesh = getattr(ctx, "mesh", None) if ctx is not None else None
    ep = mesh.shape.get(EP, 1) if mesh is not None else 1
    tok_axes = tuple(a for a in (DP, EP)
                     if mesh is not None and mesh.shape.get(a, 1) > 1)
    tok_shards = int(np.prod([mesh.shape[a] for a in tok_axes])) \
        if tok_axes else 1
    use_ep = (ep > 1 and e % ep == 0 and n % max(tok_shards, 1) == 0
              and n >= tok_shards)

    if not use_ep:
        out, aux = _moe_tokens(
            xt, gate_w, top_k, cap_f, act,
            expert_fn=lambda ein: _expert_ffn(ein, w1, b1, w2, b2, act),
            stat_mean=lambda s, cnt: s / cnt)
        return {"Out": [out.reshape(lead + (d,))], "AuxLoss": [aux]}

    def local(xt_l, gate_w_l, w1_l, b1_l, w2_l, b2_l):
        def expert_fn(expert_in):
            # dispatch: each source shard's per-expert slices route to the
            # expert's owner — the canonical a2a pair over the ep axis
            routed = jax.lax.all_to_all(expert_in, EP, split_axis=0,
                                        concat_axis=1, tiled=True)
            eout = _expert_ffn(routed, w1_l, b1_l, w2_l, b2_l, act)
            return jax.lax.all_to_all(eout, EP, split_axis=1,
                                      concat_axis=0, tiled=True)

        def stat_mean(s, cnt):
            return jax.lax.psum(s, tok_axes) / (cnt * tok_shards)

        return _moe_tokens(xt_l, gate_w_l, top_k, cap_f, act, expert_fn,
                           stat_mean)

    tok_spec = PartitionSpec(tok_axes if len(tok_axes) > 1
                             else tok_axes[0], None)
    from ..core.compat import shard_map
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, PartitionSpec(),
                  PartitionSpec(EP, None, None), PartitionSpec(EP, None),
                  PartitionSpec(EP, None, None), PartitionSpec(EP, None)),
        out_specs=(tok_spec, PartitionSpec()), check_vma=False)
    out, aux = fn(xt, gate_w, w1, b1, w2, b2)
    return {"Out": [out.reshape(lead + (d,))], "AuxLoss": [aux]}


# ---------------------------------------------------------------------------
# Dropless top-k gated experts (the sparse block of OLMoE, Mixtral, Qwen-MoE)
# ---------------------------------------------------------------------------
# Beside `moe_ffn`, not inside it: that op is capacity routing (a fixed C
# rows per expert, overflow passed through, top-k gates renormalised,
# biased relu/gelu experts, an aux loss, an all-to-all pair under `ep`).
# A server cannot use capacity routing: a prompt padded to its bucket and
# a decode slot beside fifteen others would see their tokens dropped by
# what the OTHER rows chose. Here every (token, expert) pair is computed.

def _gated_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    if op.output("Stats"):
        st = block.var(op.output("Stats")[0])
        st.shape = (4 if "first_expert" in op.attrs else 3,)
        st.dtype = "int32"
    if op.output("Load"):
        ld = block.var(op.output("Load")[0])
        ld.shape, ld.dtype = (4,), "int32"
    if op.output("Experts"):
        ex = block.var(op.output("Experts")[0])
        ex.shape = tuple(x.shape[:-1]) + (int(op.attrs["top_k"]),)
        ex.dtype = "int32"


def _expert_rows(xs, sizes, wg, wu, wd):
    """Rows sorted by expert through their experts, `sizes` rows each:
    gated SiLU of three matrices, or (`wg` None) the two-matrix form
    relu(x W_up)^2 W_down. A `wd` STORED wider than the rows (the model
    width in whole tiles of the grouped matmul, `layers.moe_gated_ffn`)
    gives its product cut to the rows' own width: a static slice of the
    result, the identity where the widths agree. Each product runs where
    its static plan says (`kernels.expert_matmul.expert_matmul_plan`,
    from its shapes alone): the repo's own grouped matmul where XLA's
    weight tile is 512 KB or less (experts 768 wide; the two-matrix
    experts' up product, k = 2,688 = 21 x 128) or the rows are 256 to
    2,048 (a decode step of 64 slots and more, a held share's prefill
    wave), its row-tiled form over 2,048 rows (a bucket's thousands of
    pairs; a trained share's wave, with transposes of its own), XLA's
    elsewhere (a step's 96-128 rows on 1 MB tiles)."""
    def dot(rows, w):
        return expert_matmul.expert_matmul(rows, w, sizes)

    h = jnp.square(jax.nn.relu(dot(xs, wu))) if wg is None \
        else jax.nn.silu(dot(xs, wg)) * dot(xs, wu)
    return dot(h, wd)[:, :xs.shape[-1]]


def _experts_sorted(xt, experts, gates, wg, wu, wd):
    """Rows sorted by expert into `jax.lax.ragged_dot`: k*n rows of
    matmul and no more, and only the touched experts' weights are read.
    XLA's TPU compiler lowers it to its grouped-matmul kernel and counts
    exactly 2*k*n*D*H operations a weight. One form for every size: at
    the published widths on a v5e it beat a batched matmul over all 64
    experts at a decode step's 16 rows (1.98 against 2.25 ms a layer,
    both at 87% of the memory bandwidth, this one reading the 88% of
    experts that were touched) and is the only one inside the work bound
    from 256 rows up; between 64 and 255 rows the batched form was a
    quarter faster, where no configuration is served yet (PERF.md, PR
    27)."""
    n, k = experts.shape
    e = wu.shape[0]
    flat = experts.reshape(-1)                              # [n*k]
    order = jnp.argsort(flat, stable=True)                  # by expert
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1)
    xs = jnp.take(xt, order // k, axis=0)                   # [n*k, D]
    ys = _expert_rows(xs, sizes, wg, wu, wd)                # [n*k, D]
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = jnp.take(ys, back, axis=0).reshape(n, k, -1)
    # the k terms of a row are summed in its own top-k order, in f32 on
    # the vector unit: a row's sum never depends on the other rows
    return jnp.sum(y.astype(jnp.float32) * gates[:, :, None], axis=1)


#: rows of expert matmul one pass of `_experts_held` takes: a prompt's
#: pairs on the held experts go through in waves of this many (34 MB of
#: f32 rows at a width of 4,096), as many waves as there are pairs, so
#: the cost follows the pairs that fell here and no bound on them is
#: needed; a decode step's few pairs are one pass with no loop
_HELD_WAVE_ROWS = 2048


def _experts_held(xt, experts, gates, wg, wu, wd, first):
    """`_experts_sorted` for a program that holds experts `first ..
    first + count - 1` alone (wg, wu, wd are [count, ...]): the (token,
    expert) pairs that fall on them, sorted by expert, are the rows of
    the `ragged_dot`s, and no other pair is computed, gathered or stood
    in for: what the other chips of the layer hold is theirs to add.
    Returns (the held experts' part of every row's sum [n, D] float32,
    pairs a held expert received [count] int32)."""
    n, k = experts.shape
    count = wu.shape[0]
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)       # held pairs first, by expert
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    ends = jnp.cumsum(sizes)
    total = ends[-1]
    most = n * min(k, count)                    # a token's experts differ
    rows = min(most, _HELD_WAVE_ROWS)
    waves = -(-most // rows)
    order = jnp.pad(order, (0, max(waves * rows - n * k, 0)))
    flat_gates = gates.reshape(-1)

    def wave(j, out):
        lo = j * rows
        pairs = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        mine = jnp.clip(ends, lo, lo + rows) \
            - jnp.clip(ends - sizes, lo, lo + rows)
        xs = jnp.take(xt, pairs // k, axis=0)               # [rows, D]
        ys = _expert_rows(xs, mine, wg, wu, wd)
        live = (lo + jnp.arange(rows, dtype=jnp.int32) < total)[:, None]
        term = jnp.where(live, ys.astype(jnp.float32)
                         * jnp.take(flat_gates, pairs)[:, None], 0.0)
        # a token's pairs are added in its experts' order, whatever the
        # other rows chose: the sort is stable
        return out.at[pairs // k].add(term)

    out = jnp.zeros((n, xt.shape[1]), jnp.float32)
    if waves == 1:
        return wave(0, out), sizes
    return jax.lax.fori_loop(0, -(-total // rows), wave, out), sizes


# A share under a derivative. `_experts_held` above stays as it is for the
# programs that are run for test (a server's buckets and its step: their
# jaxpr is what it was, `tests/test_nemotron3.py`); a program that may be
# differentiated takes `_experts_held_trained`, the same sums with a
# backward of their own.

def _held_pairs(experts, first, count):
    """(every pair's index with the pairs on experts `first .. first +
    count - 1` first, by expert; pairs each of them received [count])."""
    local = experts.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)       # held pairs first, by expert
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    return order, sizes


def _held_rows(xs, gates, mine, live, wg, wu, wd):
    """A wave's rows through their experts (`_expert_rows`' products),
    times their gates, float32; zeros where a row is no pair of a held
    expert. Every such row is zeroed where it enters and behind EVERY
    grouped product, not at the end alone: what a grouped matmul writes
    in the rows that belong to no group is unspecified (zeros on the
    CPU, whatever was there on the chip), and under a derivative each
    `where` here is what keeps that out of the transposes: without them
    the rows behind the pairs came back as dx of real tokens (PERF.md
    section 6, PR 62: right on the CPU, gradients 1e4 times the
    reference's on the chip)."""
    def dot(rows, w):
        return jnp.where(live, expert_matmul.expert_matmul(rows, w, mine),
                         0.0)

    xs = jnp.where(live, xs, 0.0)
    h = jnp.square(jax.nn.relu(dot(xs, wu))) if wg is None \
        else jax.nn.silu(dot(xs, wg)) * dot(xs, wu)
    ys = dot(h, wd)[:, :xs.shape[-1]]
    return ys.astype(jnp.float32) * gates[:, None]


def _held_grad_rows(n, k, count, of):
    """Rows a wave of a TRAINED share takes, forward and backward: what
    an even routing sends here (n k count / of) in whole `_HELD_WAVE_ROWS`
    and one more, so that a step whose routing is near even is ONE wave
    (a wave of the backward adds its three weight gradients into the
    layer's, 400 MB read and written at 16 experts of 2,304 x 896: eight
    waves of 2,048 would move that eight times) and any other routing
    takes as many as it has pairs, as `_experts_held`'s does; never more
    than n min(k, count): a token's experts differ."""
    share = -(-n * k * count // of)
    return min(n * min(k, count),
               (-(-share // _HELD_WAVE_ROWS) + 1) * _HELD_WAVE_ROWS)


def _held_walk(xt, order, sizes, gates, rows, wave, carry):
    """`wave(carry, pairs, tokens, xs, gates, mine, live)` over the
    waves of `rows` sorted pairs (`order`, `sizes`: `_held_pairs`) that
    hold a pair of a held expert: one call where a wave is all there can
    be, else a `fori_loop` over a TRACED count of them (which reverse
    mode never sees: `_held_sum`'s rules call this, and nothing
    differentiates them), so the cost follows the pairs that fell here."""
    n, k = gates.shape
    ends = jnp.cumsum(sizes)
    waves = -(-n * min(k, sizes.shape[0]) // rows)
    order = jnp.pad(order, (0, max(waves * rows - n * k, 0)))
    flat_gates = gates.reshape(-1)

    def one(j, carry):
        lo = j * rows
        pairs = jax.lax.dynamic_slice_in_dim(order, lo, rows)
        mine = jnp.clip(ends, lo, lo + rows) \
            - jnp.clip(ends - sizes, lo, lo + rows)
        live = (lo + jnp.arange(rows, dtype=jnp.int32) < ends[-1])[:, None]
        tokens = pairs // k
        return wave(carry, pairs, tokens, jnp.take(xt, tokens, axis=0),
                    jnp.take(flat_gates, pairs), mine, live)

    if waves == 1:
        return one(0, carry)
    return jax.lax.fori_loop(0, -(-ends[-1] // rows), one, carry)


def _spread(weights):
    """(wg or None, wu, wd) of the two or three matrices given."""
    return (None,) * (3 - len(weights)) + tuple(weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _held_sum(xt, order, sizes, gates, weights, rows):
    """`_experts_held`'s sum for a program that is differentiated: the
    same pairs through the same experts, in waves of `rows`, with a
    backward of its own (`_held_sum_bwd`). Returns (the sum [n, D]
    float32, the rows every held expert's products took over the waves
    that RAN [count] int32: `sizes` again where no wave was lost)."""
    def wave(carry, pairs, tokens, xs, g, mine, live):
        out, walked = carry
        # a token's pairs are added in its experts' order, whatever the
        # other rows chose: the sort is stable
        return (out.at[tokens].add(
            _held_rows(xs, g, mine, live, *_spread(weights))),
            walked + mine)

    return _held_walk(xt, order, sizes, gates, rows, wave,
                      (jnp.zeros(xt.shape, jnp.float32),
                       jnp.zeros(sizes.shape, jnp.int32)))


def _held_sum_fwd(xt, order, sizes, gates, weights, rows):
    # no activation of any pair is kept: the residuals are the inputs
    return (_held_sum(xt, order, sizes, gates, weights, rows),
            (xt, order, sizes, gates, weights))


def _held_sum_bwd(rows, saved, cotangents):
    """The waves again, each with its own transposes: a wave's rows are
    gathered and put through their experts anew, and `jax.vjp` of
    `_held_rows` gives the wave's dx, dgates and the weights' gradients,
    which are added up (float32). No pair is dropped in either
    direction: the backward walks exactly the forward's waves."""
    xt, order, sizes, gates, weights = saved
    dout, _ = cotangents                # the walked rows are a count

    def wave(carry, pairs, tokens, xs, g, mine, live):
        dx, dg, dws = carry
        _, transposes = jax.vjp(
            lambda xs, g, *ws: _held_rows(xs, g, mine, live, *_spread(ws)),
            xs, g, *weights)
        dxs, dgs, *dw = transposes(jnp.take(dout, tokens, axis=0))
        return (dx.at[tokens].add(dxs.astype(jnp.float32)),
                dg.at[pairs].add(dgs),
                tuple(a + b.astype(jnp.float32) for a, b in zip(dws, dw)))

    dx, dg, dws = _held_walk(
        xt, order, sizes, gates, rows, wave,
        (jnp.zeros(xt.shape, jnp.float32),
         jnp.zeros((gates.size,), gates.dtype),
         tuple(jnp.zeros(w.shape, jnp.float32) for w in weights)))
    return (dx.astype(xt.dtype), None, None, dg.reshape(gates.shape),
            tuple(d.astype(w.dtype) for d, w in zip(dws, weights)))


_held_sum.defvjp(_held_sum_fwd, _held_sum_bwd)


def _experts_held_trained(xt, experts, gates, wg, wu, wd, first, of):
    """`_experts_held` for a program that may be differentiated (the
    router has `of` experts): the same result from `_held_sum`, whose
    waves' count reverse mode never sees; dropless for any routing up to
    every token choosing min(k, count) held experts. The second result
    is counted INSIDE the walk: the rows each held expert's products
    took in the waves that ran, not the router's histogram of them."""
    n, k = experts.shape
    order, sizes = _held_pairs(experts, first, wu.shape[0])
    weights = tuple(w for w in (wg, wu, wd) if w is not None)
    return _held_sum(xt, order, sizes, gates, weights,
                     _held_grad_rows(n, k, wu.shape[0], of))


#: bytes of the shared expert's gate (or up) activation past which a
#: prompt's rows go through it a chunk at a time: four shared experts of
#: 4,096 side by side are 64 KB a row, 403 MB at 6,144 rows, twice
_SHARED_CHUNK_BYTES = 64 << 20


def _shared_expert(xt, sg, su, sd):
    """(silu(x Sg) * (x Su)) Sd, or (`sg` None) relu(x Su)^2 Sd, on xt
    [n, D], float32 [n, D]; the rows a chunk at a time where the activations would be over
    `_SHARED_CHUNK_BYTES`. Unrolled, not a scan: out of a loop the
    compiler hoists the weights' bfloat16 copies and keeps all three
    (400 MB at a width of 16,384, more than the chunks save)."""
    def gated(x):
        if sg is None:
            return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, su))),
                           sd).astype(jnp.float32)
        return jnp.dot(jax.nn.silu(jnp.dot(x, sg)) * jnp.dot(x, su),
                       sd).astype(jnp.float32)

    n, row = xt.shape[0], su.shape[1] * xt.dtype.itemsize
    rows = next((r for r in (2048, 1024, 512, 256, 128)
                 if n % r == 0 and r * row <= _SHARED_CHUNK_BYTES), n)
    if n * row <= _SHARED_CHUNK_BYTES or rows == n:
        return gated(xt)
    return jnp.concatenate([gated(xt[i:i + rows])
                            for i in range(0, n, rows)], axis=0)


@register_op("moe_gated_ffn", infer_shape=_gated_infer)
def moe_gated_ffn(ctx, ins, attrs):
    """Dropless top-k mixture of gated-SiLU experts, no bias:

        p   = softmax_f32(x . RouterW)            over all E experts
        out = sum_{e in topk(p)} p_e * (silu(x . WGate_e) * (x . WUp_e)) . WDown_e

    X [..., D]; RouterW [D, E]; WGate, WUp [E, D, H]; WDown [E, H, D]
    -> Out [..., D]. attrs: top_k, and the router's rule:

      router       "softmax" (above; OLMoE, Mixtral) | "sigmoid_bias"
                   (DeepSeek-V3's `noaux_tc` with one group: s =
                   sigmoid_f32(x . RouterW); the k experts are the top
                   of s + RouterBias [E]; the weights are s, never
                   s + bias: the bias chooses and does not weigh) |
                   "sigmoid" (the same with no bias at all: the top of s)
      norm_topk    the chosen weights divided by their sum (+
                   `norm_topk_eps`: 1e-20 unless the attr says, LFM2's
                   1e-6)
      routed_scale and then multiplied by this

    SharedGate, SharedUp [D, Hs], SharedDown [Hs, D], all three or none:
    one more gated-SiLU expert that every row takes, added times
    `shared_scale` (1: unweighted).

    `expert_form` "relu2" (the attr present; Nemotron-H's): every expert,
    routed and shared, is TWO matrices, relu(x . WUp_e)^2 . WDown_e, and
    the op takes no WGate and no SharedGate. Without the attr the op is
    what it was, bit for bit.

    WDown may be STORED [E, H, D'] with D' > D (the model width in whole
    tiles of the grouped matmul, zeros behind D: `layers.moe_gated_ffn`):
    its product is cut to D, read from the shapes alone, and Out is
    [..., D] as ever.

    `first_expert` (the attr present): WGate, WUp, WDown hold experts
    `first_expert .. first_expert + count - 1` of the router's E alone,
    one chip's share of an expert-parallel layer. The router, its top-k
    and the weights are over all E as ever; only the pairs that fall on
    held experts are computed (`_experts_held`), and Out is this share's
    part of the layer: the shares' routed parts and the shared expert
    counted once add up to the whole. Without the attr the op is what it
    was, bit for bit.
    A bias without the sigmoid rule, or a rule it does not know, is
    refused; group-limited routing (`n_group` > 1) is not built.

    The router's matmul and softmax run in float32 at the highest
    precision: D x E is nothing beside the experts, and a router that
    rounds differently from the reference chooses other experts at near
    ties. Ties go to the lower expert index (`jax.lax.top_k`).

    Optional Active [...] (any integer/boolean: nonzero = a live row) and
    output Stats [3] int32: routed (token, expert) pairs among live
    rows, experts that received at least one of them, and 1 if any row
    was live; with `first_expert` [4]: the experts counted are the held
    ones, and the fourth is the live pairs that fell on them. The decode step sums these over its layers (`Active` is
    `context_lens`). Output Experts [..., top_k] int32: each row's chosen
    experts, highest choosing score first. Output Load [4] int32, what a
    TRAINING step counts of its experts (every row live): routed pairs,
    pairs on the experts held here (all of them without `first_expert`),
    held experts that received any, and the rows of the held expert that
    received most (the straggler a grouped matmul waits for); a program
    run for test has none. Nothing else asks for any of them and XLA
    drops what is not fetched."""
    x = ins["X"][0]
    router_w = ins["RouterW"][0]
    trained = ctx is not None and not getattr(ctx, "is_test", False)
    form = attrs.get("expert_form", "gated")
    if form not in ("gated", "relu2"):
        raise ValueError(f"unknown expert form {form!r}")
    mats = 3 if form == "gated" else 2
    if bool(ins.get("WGate")) != (form == "gated"):
        raise ValueError(f"{form} experts take {mats} matrices each")
    wg = ins["WGate"][0] if ins.get("WGate") else None
    wu, wd = ins["WUp"][0], ins["WDown"][0]
    k = int(attrs["top_k"])
    rule = attrs.get("router", "softmax")
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    n, e = xt.shape[0], router_w.shape[-1]
    if not 1 <= k <= e:
        raise ValueError(f"top_k {k} outside 1..{e} experts")
    if rule not in ("softmax", "sigmoid_bias", "sigmoid"):
        raise ValueError(f"unknown router rule {rule!r}")
    if ins.get("RouterBias") and rule != "sigmoid_bias":
        raise ValueError("a selection bias belongs to the sigmoid_bias "
                         f"router, not to {rule!r}")
    shared = [ins[key][0] for key in ("SharedGate", "SharedUp",
                                      "SharedDown") if ins.get(key)]
    if len(shared) not in (0, mats) or (
            shared and bool(ins.get("SharedGate")) != (form == "gated")):
        raise ValueError(f"the shared expert takes its {mats} matrices "
                         "or none")

    logits = jnp.dot(xt.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if rule == "softmax":
        gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    else:
        scores = jax.nn.sigmoid(logits)
        by = scores + ins["RouterBias"][0].astype(jnp.float32) \
            if ins.get("RouterBias") else scores
        _, experts = jax.lax.top_k(by, k)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    if attrs.get("norm_topk", False):
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                         + float(attrs.get("norm_topk_eps", 1e-20)))
    scale = float(attrs.get("routed_scale", 1.0))
    if scale != 1.0:
        gates = gates * scale

    first = attrs.get("first_expert")
    if first is None:
        held = {w.shape[0] for w in (wg, wu, wd) if w is not None}
        if held != {e}:
            raise ValueError(f"{sorted(held)} experts' weights for a router "
                             f"of {e}: a share says which (first_expert)")
        out = _experts_sorted(xt, experts, gates, wg, wu, wd)
    else:
        # a program run for test (a server's buckets and its step are)
        # is never differentiated and keeps the form it had
        out, walked = (_experts_held_trained(xt, experts, gates, wg, wu, wd,
                                             int(first), e) if trained else
                       _experts_held(xt, experts, gates, wg, wu, wd,
                                     int(first)))
    if shared:
        mats_s = [w.astype(xt.dtype) for w in shared]
        part = _shared_expert(xt, *(mats_s if wg is not None
                                    else [None] + mats_s))
        share = float(attrs.get("shared_scale", 1.0))
        out = out + (part if share == 1.0 else part * share)
    out = out.astype(x.dtype)

    live = (ins["Active"][0].reshape(-1) != 0) if ins.get("Active") \
        else jnp.ones((n,), bool)
    hits = jnp.zeros((e,), jnp.int32).at[experts.reshape(-1)].add(
        jnp.repeat(live.astype(jnp.int32), k))
    fields = [k * jnp.sum(live, dtype=jnp.int32),
              jnp.sum(hits > 0, dtype=jnp.int32),
              jnp.any(live).astype(jnp.int32)]
    if first is not None:
        mine = jax.lax.dynamic_slice_in_dim(hits, int(first), wu.shape[0])
        fields[1] = jnp.sum(mine > 0, dtype=jnp.int32)
        fields.append(jnp.sum(mine, dtype=jnp.int32))
    stats = jnp.stack(fields)
    outs = {}
    if trained:
        # a share's are the rows its waves' products took, summed inside
        # the walk (`_held_sum`), not the router's count of them: a wave
        # of the forward that did not run shows here (the backward's
        # waves are held by what the step's update moved)
        mine = hits if first is None else walked
        outs["Load"] = [jnp.stack([
            fields[0], jnp.sum(mine, dtype=jnp.int32),
            jnp.sum(mine > 0, dtype=jnp.int32),
            jnp.max(mine).astype(jnp.int32)])]
    return {"Out": [out.reshape(lead + (d,))], "Stats": [stats], **outs,
            "Experts": [experts.astype(jnp.int32).reshape(lead + (k,))]}
