"""Two mixers of long-context hybrids and the row-chunked FFN beside them.

LINEAR ATTENTION (Lightning Attention, arXiv:2401.04658, one constant
decay a head). u [.., d] is the block's normed input; H heads of D:

    q = N_q(u W_q), k = N_k(u W_k)    RMS over each head's D, one gain
    v = u W_v                         for q and one for k; q and k then
                                      rotated (halves) where the layer
                                      carries positions
    S_t = a_h S_{t-1} + k_t^T v_t     a [D, D] matrix a head
    o_t = (q_t / sqrt(D)) S_t
    y   = (RMSNorm(o) * sigmoid(u W_g)) W_o     the norm over the H D
                                                joined columns

with a_h = exp(-s_h (1 - l / (L - 1) + 1e-5)), s_h = 2^(-8 (h + 1) / H),
l the layer's PUBLISHED index of L. This is the SSD recurrence of
`attention_ops._ssd_chunks` / `kernels.ssd_update.ssd_decode_update` with
a step of 1, A = log a_h, x = v, B = k, C = q / sqrt(D), H groups and no
skip: both run it as they are. A prompt's rows are taken `_ROW_CHUNK` at
a time (projections, norms and the gate of a 32 k bucket would be half
a gigabyte each, whole), the state carried from chunk to chunk.

BLOCK-SPARSE ATTENTION over pooled keys (InfLLM-V2 as MiniCPM4 ships it,
arXiv:2506.07900, with an exact normaliser). H query heads over H_kv K/V
heads of D, per-head q/k-norm, an output gate; sizes `kernel`, `stride`,
`block`, `topk` blocks, `window` blocks, `init` blocks and `dense_len`.
For a query at row t of a sequence whose length AT THAT CALL is n:

    n < dense_len: causal softmax attention over every row. Otherwise,
    a K/V head g:
    c_j   = mean(k_g[stride j : stride j + kernel])     kernels wholly at
                                                        or before t
    p_hj  = softmax_j(q_h . c_j / sqrt(D))
    P_j   = sum of p_hj over the heads h that read g
    B_b   = max of P_j over the kernels that overlap rows
            [block b, block (b + 1))
    chosen: the first `init` blocks, the `window` blocks that end at the
            query's own, then the highest B_b (ties to the lower b)
            until `topk` blocks in all
    attention: causal softmax over the rows of the chosen blocks, ONE
            choice a K/V head for all the heads that read it

and y = (o * sigmoid(u W_g)) W_o. What a cache holds: K and V a token
([H_kv D], the heads side by side in the row's lanes) and, a SEQUENCE,
the pooled keys c_j ([max_pooled, H_kv D]; a row arrives once in `stride`
tokens: a prefill returns every whole kernel of its prompt, a step
writes the one its token completes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .attention_ops import (_CHOOSING, _columns_dot, _rms_over_last,
                            _selected_mask, _ssd_chunks, pack_mask,
                            rope_rotate)

#: rows of a prompt the linear mixer and the row-chunked FFN take at a
#: time (a tiling: it changes no result)
_ROW_CHUNK = 2048

#: query rows a block-sparse prefill scores, selects and attends at a
#: time (one call of the flash forward each, over the keys up to its
#: last row), and the rows of them whose pooled scores are whole at once
_SPARSE_Q_CHUNK = 2048
_SPARSE_SCORE_ROWS = 256


def _row_chunk(seq, rows):
    """`rows` where it divides `seq` and is under it, else the whole."""
    return rows if seq > rows and seq % rows == 0 else seq


# ---------------------------------------------------------------------------
# the row-chunked gated FFN
# ---------------------------------------------------------------------------

def _ffn_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype


@register_op("gated_ffn_rows", infer_shape=_ffn_infer)
def gated_ffn_rows(ctx, ins, attrs):
    """(silu(x Wg) * (x Wu)) Wd on X [B, S, d], the rows `rows` at a
    time inside the one program: the two [S, width] products of a long
    bucket are never whole (2.1 GB each at 32 k rows of 16,384). attrs:
    rows, precision, scope (a `jax.named_scope` about all of it)."""
    x = ins["X"][0]
    wg, wu, wd = (ins[k][0].astype(x.dtype)
                  for k in ("WGate", "WUp", "WDown"))
    precision = jax.lax.Precision.HIGH \
        if attrs.get("precision") == "high" else None

    def ffn(rows):
        gate = jax.nn.silu(jnp.dot(rows, wg, precision=precision))
        return jnp.dot(gate * jnp.dot(rows, wu, precision=precision), wd,
                       precision=precision)

    seq = x.shape[1]
    rows = _row_chunk(seq, int(attrs.get("rows") or _ROW_CHUNK))
    if attrs.get("scope"):      # the whole of it under one name
        with jax.named_scope(str(attrs["scope"])):
            return {"Out": [ffn(x) if rows == seq else _in_place_rows(
                x, rows, lambda _, r, c: (c, ffn(r)), None)[1]]}
    if rows == seq:
        return {"Out": [ffn(x)]}
    with jax.named_scope("ffn_rows"):
        return {"Out": [_in_place_rows(x, rows, lambda _, r, c: (c, ffn(r)),
                                       None)[1]]}


def _in_place_rows(x, rows, fn, carry):
    """x [B, S, d] with each chunk of `rows` rows replaced by `fn(start,
    the chunk, carry) -> (carry, a chunk of the same shape)`, in order,
    in ONE buffer that starts as x: no stacked output beside the input
    (a zeroed [S, d] array a loop, which the compiler allocates ahead of
    all of them: 0.5 GB each at 32 k rows of 4,096). Returns (the last
    carry, the rows)."""
    def body(i, both):
        carry, buf = both
        start = i * rows
        chunk = jax.lax.dynamic_slice_in_dim(buf, start, rows, axis=1)
        carry, out = fn(start, chunk, carry)
        return carry, jax.lax.dynamic_update_slice_in_dim(
            buf, out.astype(buf.dtype), start, axis=1)

    return jax.lax.fori_loop(0, x.shape[1] // rows, body, (carry, x))


# ---------------------------------------------------------------------------
# linear attention
# ---------------------------------------------------------------------------

def linear_decay_log(heads, layer, n_layers):
    """log a_h of the text above, [H] float32 (negative)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    slope = jnp.exp2(-8.0 * h / heads)
    return -slope * (1.0 - layer / max(n_layers - 1, 1) + 1e-5)


def _linear_qkvg(x, ins, positions, attrs):
    """x [B, S, d] -> q (scaled), k, v [B, S, H, D] float32 and the gate
    [B, S, H D]."""
    heads, hd = int(attrs["heads"]), int(attrs["head_dim"])
    eps = float(attrs["epsilon"])

    def proj(name, precision=_CHOOSING):
        return _columns_dot(x, ins[name][0].astype(x.dtype), precision)

    def split(t):
        return t.reshape(x.shape[:2] + (heads, hd))

    q = _rms_over_last(split(proj("Wq")), ins["QNorm"][0], eps)
    k = _rms_over_last(split(proj("Wk")), ins["KNorm"][0], eps)
    if attrs.get("rotary", "half") != "none":
        theta = float(attrs["rope_theta"])
        q = rope_rotate(q, positions, theta)
        k = rope_rotate(k, positions, theta)
    f32 = jnp.float32
    gate = jax.nn.sigmoid(proj("Wg", None).astype(f32)) \
        if ins.get("Wg") else None
    return (q.astype(f32) * (1.0 / math.sqrt(hd)), k.astype(f32),
            split(proj("Wv", None)).astype(f32), gate)


def _linear_out(o, gate, x, ins, attrs):
    """o [B, S, H, D] -> (RMSNorm(o) * gate) W_o."""
    joined = o.reshape(o.shape[:2] + (-1,))
    normed = _rms_over_last(joined, ins["ONorm"][0], float(attrs["epsilon"]))
    if gate is not None:
        normed = normed * gate
    return _columns_dot(normed.astype(x.dtype),
                        ins["Wo"][0].astype(x.dtype), _CHOOSING)


def _linear_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    if op.output("StateOut"):
        heads, hd = int(op.attrs["heads"]), int(op.attrs["head_dim"])
        var = block.var(op.output("StateOut")[0])
        var.shape, var.dtype = (x.shape[0], heads, hd, hd), x.dtype


@register_op("linear_attention", infer_shape=_linear_infer)
def linear_attention(ctx, ins, attrs):
    """The text above. X [B, S, d]; Wq, Wk, Wv, Wg [d, H D]; Wo [H D,
    d]; QNorm, KNorm [D]; ONorm [H D] -> Out [B, S, d]. attrs: heads,
    head_dim, epsilon, rope_theta, rotary ("half" | "none"), layer,
    n_layers (the decay's), chunk (rows of `_ssd_chunks`).

    Whole sequences (no State) at positions 0..S-1 from a zero state;
    with NTokens [B] int (each row's true length n) also StateOut [B, H,
    D, D] (value columns down, key columns on the lanes): S after row
    n - 1, whatever padding follows it.

    One new token a slot (X [slots, 1, d]) with State [slots, H, D, D],
    Positions [slots, 1] and ContextLens [slots] -> Out and StateOut, by
    ONE call of `kernels.ssd_update.ssd_decode_update`. A slot of length
    0 keeps its state."""
    from ..kernels.ssd_update import ssd_decode_update

    x = ins["X"][0]
    heads = int(attrs["heads"])
    a = linear_decay_log(heads, float(attrs["layer"]),
                         int(attrs["n_layers"]))
    outs = {}
    with jax.named_scope("linear_attention"):
        if ins.get("State"):
            q, k, v, gate = _linear_qkvg(x, ins, ins["Positions"][0], attrs)
            live = ins["ContextLens"][0] > 0
            y, moved = ssd_decode_update(
                ins["State"][0], v[:, 0], jnp.ones(x.shape[:1] + (heads,),
                                                   jnp.float32),
                a, k[:, 0], q[:, 0], live)
            outs["StateOut"] = [moved]
            outs["Out"] = [_linear_out(y[:, None], gate, x, ins, attrs)]
            return outs
        b, seq, d = x.shape
        n = ins["NTokens"][0].astype(jnp.int32) if ins.get("NTokens") \
            else jnp.full((b,), seq, jnp.int32)
        rows = _row_chunk(seq, _ROW_CHUNK)

        def part(start, xr, state):
            at = start + jnp.arange(rows, dtype=jnp.int32)
            q, k, v, gate = _linear_qkvg(xr, ins, at, attrs)
            dt = (at[None] < n[:, None]).astype(jnp.float32)
            dt = jnp.broadcast_to(dt[..., None], (b, rows, heads))
            y, state = _ssd_chunks(dt, v, k, q, a, int(attrs["chunk"]),
                                   state)
            return state, _linear_out(y, gate, xr, ins, attrs)

        zero = jnp.zeros((b, heads) + (int(attrs["head_dim"]),) * 2,
                         jnp.float32)
        last, out = _in_place_rows(x, rows, part, zero)
        outs["Out"] = [out]
        if ins.get("NTokens"):
            outs["StateOut"] = [last.astype(x.dtype)]
    return outs


# ---------------------------------------------------------------------------
# block-sparse attention
# ---------------------------------------------------------------------------

def _sizes(attrs):
    return tuple(int(attrs[k]) for k in (
        "kernel", "stride", "block", "topk", "window", "init", "dense_len"))


def pooled_rows(length, kernel, stride):
    """Kernels wholly inside `length` rows."""
    return max((length - kernel) // stride + 1, 0)


def chosen_counts(lens, sizes, block_size):
    """What a step's block-sparse layer reads, from its lengths alone (a
    numpy array [slots]; the rule `block_sparse_decode_attention` applies
    to them), a layer and K/V head: (rows read, pooled keys scored,
    blocks chosen, dense slots). A slot under `dense_len` reads every
    row it holds, any other the rows of its `topk` chosen blocks (all
    full but the query's own) and scores every kernel wholly inside its
    context."""
    import numpy as np
    lens = np.asarray(lens, np.int64)
    live = lens > 0
    dense = live & (lens < int(sizes["dense_len"]))
    blocks = -(-lens // block_size)
    chosen = np.where(dense, blocks, np.minimum(blocks, int(sizes["topk"])))
    read = np.where(live, (chosen - 1) * block_size + lens
                    - (blocks - 1) * block_size, 0)
    pooled = np.where(live & ~dense, np.maximum(
        (lens - int(sizes["kernel"])) // int(sizes["stride"]) + 1, 0), 0)
    return (int(read.sum()), int(pooled.sum()), int(chosen[live].sum()),
            int(dense.sum()))


def selection_width(topk, block, dense_len):
    """The blocks a decode step's table holds a K/V head: `topk`, or the
    most a slot that still attends densely has."""
    return max(topk, -(-(dense_len - 1) // block)) if dense_len else topk


def _sparse_q(x, ins, attrs):
    """x [B, S, d] -> q [B, S, H, D], normed (no positions)."""
    heads, hd = int(attrs["num_heads"]), int(attrs["head_dim"])
    q = _columns_dot(x, ins["Wq"][0].astype(x.dtype),
                     _CHOOSING).reshape(x.shape[:2] + (heads, hd))
    return _rms_over_last(q, ins["QNorm"][0], float(attrs["epsilon"]))


def _sparse_kv(x, ins, attrs):
    """x [B, S, d] -> k (normed), v [B, S, H_kv, D]."""
    kv, hd = int(attrs["num_kv_heads"]), int(attrs["head_dim"])
    k = _columns_dot(x, ins["Wk"][0].astype(x.dtype),
                     _CHOOSING).reshape(x.shape[:2] + (kv, hd))
    v = _columns_dot(x, ins["Wv"][0].astype(x.dtype),
                     None).reshape(x.shape[:2] + (kv, hd))
    return _rms_over_last(k, ins["KNorm"][0], float(attrs["epsilon"])), v


def _gated_out(o, x, ins):
    """o [B, S, H D] -> (o * sigmoid(x W_g)) W_o (no W_g: o W_o)."""
    if ins.get("Wg"):
        gate = jax.nn.sigmoid(_columns_dot(
            x, ins["Wg"][0].astype(x.dtype), None).astype(jnp.float32))
        o = (o.astype(jnp.float32) * gate).astype(x.dtype)
    return jnp.dot(o, ins["Wo"][0].astype(x.dtype))


def _pool_keys(k, kernel, stride):
    """k [B, T, G, D] (T whole strides) -> [B, T / stride - kernel /
    stride + 1, G, D] float32: c_j of the text above, every kernel wholly
    inside the T rows, as the mean of its strides' means."""
    b, t, g, d = k.shape
    per = kernel // stride
    means = jnp.mean(k.astype(jnp.float32).reshape(
        b, t // stride, stride, g, d), axis=2)
    n = t // stride - per + 1
    return sum(means[:, i:i + n] for i in range(per)) / per


def _block_scores(q, pooled, seen, sizes, n_blocks):
    """q [.., R, G, per, D] against pooled [.., NP, G, D] with `seen`
    [.., R, NP] bool (kernel j wholly at or before the row) -> B [.., G,
    R, n_blocks] float32 of the text above (0 where no kernel of a
    block is seen)."""
    kernel, stride, block = sizes[:3]
    d = q.shape[-1]
    s = jnp.einsum("...rgid,...jgd->...girj", q.astype(jnp.float32),
                   pooled.astype(jnp.float32), precision=_CHOOSING,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    ok = seen[..., None, None, :, :]
    s = jnp.where(ok, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(ok, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    summed = jnp.sum(p, axis=-3)                        # [.., G, R, NP]
    # block b's kernels: j from ratio b - (per - 1) to ratio b + ratio - 1
    ratio, per = block // stride, kernel // stride
    need = ratio * n_blocks + per - 1
    have = summed.shape[-1]
    lead = [(0, 0)] * (summed.ndim - 1)
    padded = jnp.pad(summed, lead + [(per - 1, max(
        need - have - (per - 1), 0))])[..., :need]
    return functools.reduce(jnp.maximum, [
        padded[..., o:o + ratio * n_blocks:ratio]
        for o in range(ratio + per - 1)])


def _choose_blocks(scores, own, dense, sizes):
    """scores [.., NB] (B_b), `own` [..] or [.., 1]-broadcastable int32
    (the block the query sits in), `dense` bool broadcastable (the call
    attends densely) -> bool [.., NB], the chosen blocks."""
    topk, window, init = sizes[3:6]
    nb = scores.shape[-1]
    at = jnp.arange(nb, dtype=jnp.int32)
    own = own[..., None]
    live = at <= own
    if nb <= topk:
        return jnp.broadcast_to(live, scores.shape)
    forced = (at < init) | (at > own - window)
    ranked = jnp.where(forced, jnp.inf, scores)
    chosen = _selected_mask(jnp.where(live, ranked, -jnp.inf), topk) & live
    return jnp.where(dense[..., None], live, chosen)


def _sparse_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    kv = int(op.attrs["num_kv_heads"]) * int(op.attrs["head_dim"])
    for role in ("K", "V"):
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = tuple(x.shape[:-1]) + (kv,), x.dtype
    if op.output("Pooled"):
        var = block.var(op.output("Pooled")[0])
        var.shape = (x.shape[0], int(op.attrs["max_pooled"]), kv)
        var.dtype = x.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        nb = -(-int(x.shape[1]) // int(op.attrs["block"]))
        var.shape = tuple(x.shape[:-1]) + (
            int(op.attrs["num_kv_heads"]) * -(-nb // 32),)
        var.dtype = "int32"


@register_op("block_sparse_attention", infer_shape=_sparse_infer)
def block_sparse_attention(ctx, ins, attrs):
    """The text above over whole sequences at rows 0..S-1. X [B, S, d];
    Wq, Wg [d, H D]; Wk, Wv [d, H_kv D]; Wo [H D, d]; QNorm, KNorm [D];
    NTokens [B] int (each row's true length n: what decides dense or
    sparse; absent: S) -> Out [B, S, d]; K, V [B, S, H_kv D] (a cache's
    rows); with `max_pooled` > 0 Pooled [B, max_pooled, H_kv D] (the
    kernels wholly inside the first n rows, zeros behind them); and where
    the op has the output, Selected [B, S, H_kv ceil(NB / 32)] int32:
    the blocks every row read, a K/V head, one bit a block (`pack_mask`).

    A bucket under `dense_len` is plain causal attention. Past it the
    query rows go `_SPARSE_Q_CHUNK` at a time: scores on the pooled
    keys, the choice (`_selected_mask`: no sort), and the flash forward
    over the choice's tiles (`dot_product_attention(selected=)`, a K/V
    head a batch row: its heads share its choice), over the keys up to
    the chunk's last row."""
    from ..kernels.flash_attention import dot_product_attention

    x = ins["X"][0]
    b, seq, _ = x.shape
    heads, kv_heads = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    hd, per = int(attrs["head_dim"]), heads // kv_heads
    sizes = _sizes(attrs)
    kernel, stride, block, topk, _, _, dense_len = sizes
    n = ins["NTokens"][0].astype(jnp.int32) if ins.get("NTokens") \
        else jnp.full((b,), seq, jnp.int32)
    k, v = _sparse_kv(x, ins, attrs)
    outs = {"K": [k.reshape(b, seq, kv_heads * hd)],
            "V": [v.reshape(b, seq, kv_heads * hd)]}
    want = bool(attrs.get("return_selected", False))
    n_blocks = -(-seq // block)
    sparse = bool(dense_len) and seq >= dense_len and n_blocks > topk \
        and seq % block == 0
    pooled = _pool_keys(k, kernel, stride) if (
        seq % stride == 0 and seq >= kernel) else None
    max_pooled = int(attrs.get("max_pooled", 0))
    if max_pooled:
        rows = jnp.zeros((b, max_pooled, kv_heads * hd), jnp.float32)
        if pooled is not None:
            got = min(pooled.shape[1], max_pooled)
            whole = (jnp.arange(got, dtype=jnp.int32)[None] * stride
                     + kernel <= n[:, None])
            rows = rows.at[:, :got].set(jnp.where(
                whole[..., None], pooled[:, :got].reshape(b, -1,
                                                          kv_heads * hd),
                0.0))
        outs["Pooled"] = [rows.astype(x.dtype)]
    if not sparse:
        q = _sparse_q(x, ins, attrs)
        o = dot_product_attention(q, k, v, causal=True)
        outs["Out"] = [_gated_out(o.reshape(b, seq, heads * hd), x, ins)]
        if want:
            own = jnp.arange(seq, dtype=jnp.int32) // block
            mask = jnp.arange(n_blocks, dtype=jnp.int32)[None] <= own[:, None]
            outs["Selected"] = [jnp.broadcast_to(jnp.tile(
                pack_mask(mask), (1, kv_heads))[None],
                (b, seq, kv_heads * -(-n_blocks // 32)))]
        return outs

    dense = n < dense_len                                   # [B]
    chunk = _row_chunk(seq, _SPARSE_Q_CHUNK)
    sub = math.gcd(chunk, _SPARSE_SCORE_ROWS)
    kg = jnp.moveaxis(k, 2, 1).reshape(b * kv_heads, seq, 1, hd)
    vg = jnp.moveaxis(v, 2, 1).reshape(b * kv_heads, seq, 1, hd)
    parts, chosen_parts = [], []
    for start in range(0, seq, chunk):
        end = start + chunk
        nb = end // block
        xq = x[:, start:end]
        q = _sparse_q(xq, ins, attrs)                   # [B, C, H, D]
        qg = q.reshape(b, chunk, kv_heads, per, hd)
        pooled_c = pooled[:, :pooled_rows(end, kernel, stride)]
        j_end = jnp.arange(pooled_c.shape[1], dtype=jnp.int32) * stride \
            + kernel

        def choose(xs, pooled_c=pooled_c, j_end=j_end, nb=nb):
            at, qs = xs                     # [sub], [B, sub, G, per, D]
            seen = jnp.broadcast_to((j_end[None] <= at[:, None] + 1)[None],
                                    (b, sub, j_end.shape[0]))
            scores = _block_scores(qs, pooled_c, seen, sizes, nb)
            return _choose_blocks(scores, (at // block)[None, None],
                                  dense[:, None, None], sizes)

        with jax.named_scope("block_select"):
            rows = start + jnp.arange(chunk, dtype=jnp.int32)
            chosen = jax.lax.map(choose, (
                rows.reshape(chunk // sub, sub),
                jnp.moveaxis(qg.reshape(b, chunk // sub, sub, kv_heads,
                                        per, hd), 1, 0)))
            # [chunks, B, G, sub, NB] -> [B, G, C, NB]
            chosen = jnp.moveaxis(chosen, 0, 2).reshape(
                b, kv_heads, chunk, nb)
            keys = jnp.arange(end, dtype=jnp.int32)
            mask = (jnp.repeat(chosen, block, axis=-1)[..., :end]
                    & (keys[None] <= rows[:, None])).reshape(
                        b * kv_heads, chunk, end).astype(jnp.int8)
        o = dot_product_attention(
            jnp.moveaxis(qg, 2, 1).reshape(b * kv_heads, chunk, per, hd),
            kg[:, :end], vg[:, :end], causal=True, selected=mask)
        o = jnp.moveaxis(o.reshape(b, kv_heads, chunk, per, hd), 1, 2)
        parts.append(_gated_out(o.reshape(b, chunk, heads * hd), xq, ins))
        if want:
            words = -(-n_blocks // 32)
            packed = pack_mask(jnp.pad(chosen, [(0, 0)] * 3
                                       + [(0, n_blocks - nb)]))
            chosen_parts.append(jnp.moveaxis(packed, 1, 2).reshape(
                b, chunk, kv_heads * words))
    outs["Out"] = [jnp.concatenate(parts, axis=1)]
    if want:
        outs["Selected"] = [jnp.concatenate(chosen_parts, axis=1)]
    return outs


def _sparse_decode_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    for pool_in, pool_out in (("KPool", "KOut"), ("VPool", "VOut"),
                              ("Pooled", "PooledOut")):
        src = block.var(op.input(pool_in)[0])
        dst = block.var(op.output(pool_out)[0])
        dst.shape, dst.dtype = src.shape, src.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        var.shape = (x.shape[0], int(op.attrs["num_kv_heads"]),
                     selection_width(int(op.attrs["topk"]),
                                     int(op.attrs["block"]),
                                     int(op.attrs["dense_len"])))
        var.dtype = "int32"


def compact_blocks(mask, width):
    """mask [.., NB] bool -> (int32 [.., width]: the set blocks' indices
    in rising order, -1 behind their count; the count [..]). No sort and
    no scatter: entry w is the block whose rank among the set ones is w
    (one compare-and-sum over [width, NB])."""
    nb = mask.shape[-1]
    rank = jnp.cumsum(mask, axis=-1, dtype=jnp.int32) - 1
    at = jnp.arange(nb, dtype=jnp.int32)
    hit = mask[..., None, :] & (
        rank[..., None, :] == jnp.arange(width, dtype=jnp.int32)[:, None])
    count = jnp.sum(mask, axis=-1, dtype=jnp.int32)
    blocks = jnp.sum(jnp.where(hit, at, 0), axis=-1, dtype=jnp.int32)
    live = jnp.arange(width, dtype=jnp.int32) < count[..., None]
    return jnp.where(live, blocks, -1), count


@register_op("block_sparse_decode_attention",
             infer_shape=_sparse_decode_infer)
def block_sparse_decode_attention(ctx, ins, attrs):
    """One new token a slot: X [S, 1, d], the weights of
    `block_sparse_attention`, KPool and VPool [NB, BS, H_kv D] (BS the
    selection's `block`: a page is a block), Pooled [S, max_pooled, H_kv
    D], BlockTables [S, MB], ContextLens [S] (the span INCLUDING the new
    token) -> Out [S, 1, d]; KOut, VOut (each slot's new row written);
    PooledOut (the kernel the new token completes written, its mean
    gathered from the K pool); Selected [S, H_kv, W] int32: the blocks
    each K/V head of each slot read, rising, -1 behind their count (W:
    `selection_width`). A slot under `dense_len` reads every block it
    holds. The attention is `kernels.block_sparse_attention
    .block_sparse_paged_attention` over the chosen blocks' pages."""
    from ..kernels.block_sparse_attention import block_sparse_paged_attention
    from ..kernels.paged_attention import paged_kv_update

    x = ins["X"][0]
    tables = ins["BlockTables"][0].astype(jnp.int32)
    lens = ins["ContextLens"][0].astype(jnp.int32)
    heads, kv_heads = int(attrs["num_heads"]), int(attrs["num_kv_heads"])
    hd, per = int(attrs["head_dim"]), heads // kv_heads
    sizes = _sizes(attrs)
    kernel, stride, block, topk, _, _, dense_len = sizes
    slots, mb = tables.shape
    q = _sparse_q(x, ins, attrs)[:, 0]                      # [S, H, D]
    k, v = _sparse_kv(x, ins, attrs)
    k_pool, v_pool = paged_kv_update(
        ins["KPool"][0], ins["VPool"][0], k[:, 0], v[:, 0], tables, lens)
    bs = k_pool.shape[1]
    if bs != block:
        raise ValueError(f"the pools' page ({bs} rows) is not the "
                         f"selection's block ({block})")
    pooled = ins["Pooled"][0]
    max_pooled = pooled.shape[1]
    with jax.named_scope("block_pool_keys"):
        # the newest whole kernel: rows stride j .. stride j + kernel - 1,
        # j = (n - kernel) // stride; written again, the same, until the
        # next one is whole
        j = jnp.clip((lens - kernel) // stride, 0, max_pooled - 1)
        at = j[:, None] * stride + jnp.arange(kernel, dtype=jnp.int32)[None]
        page = jnp.take_along_axis(tables, at // bs, axis=1)
        got = k_pool.reshape((-1,) + k_pool.shape[2:])[page * bs + at % bs]
        mean = jnp.mean(got.astype(jnp.float32), axis=1)    # [S, G D]
        slot = jnp.arange(slots, dtype=jnp.int32)
        pooled = pooled.at[slot, j].set(jnp.where(
            (lens >= kernel)[:, None], mean.astype(pooled.dtype),
            pooled[slot, j]))
    with jax.named_scope("block_scores"):
        j_end = jnp.arange(max_pooled, dtype=jnp.int32) * stride + kernel
        seen = (j_end[None] <= lens[:, None])[:, None]      # [S, 1, NP]
        scores = _block_scores(
            q.reshape(slots, 1, kv_heads, per, hd),
            pooled.reshape(slots, max_pooled, kv_heads, hd), seen, sizes,
            mb)[:, :, 0]                                    # [S, G, MB]
    with jax.named_scope("block_select"):
        own = (jnp.maximum(lens, 1) - 1) // bs
        chosen = _choose_blocks(
            scores, own[:, None], (lens < dense_len)[:, None], sizes) \
            & (lens > 0)[:, None, None]
        width = selection_width(topk, block, dense_len)
        blocks, count = compact_blocks(chosen, width)       # [S, G, W]
        pages = jnp.take_along_axis(
            tables, jnp.maximum(blocks, 0).reshape(slots, -1),
            axis=1).reshape(blocks.shape)
        pages = jnp.where(blocks >= 0, pages, 0)
        # every chosen block but the last (the query's own) is full
        rows = jnp.where(count > 0, (count - 1) * bs
                         + (lens - own * bs)[:, None], 0)
    o = block_sparse_paged_attention(q, k_pool, v_pool, pages, rows)
    out = _gated_out(o.reshape(slots, 1, heads * hd), x, ins)
    return {"Out": [out], "KOut": [k_pool], "VOut": [v_pool],
            "PooledOut": [pooled], "Selected": [blocks]}
