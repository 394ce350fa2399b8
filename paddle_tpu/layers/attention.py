"""Attention layers (TPU-native extension; no 2018 reference equivalent).

The reference composes attention from mul/softmax ops (nets.py:75 here keeps
that form for parity). These layers instead emit the fused
`scaled_dot_product_attention` op so the lowering can use the flash-attention
Pallas kernel and, on an `sp` mesh axis, ring/Ulysses sequence parallelism
(ops/attention_ops.py, parallel/ring.py).
"""

from __future__ import annotations

from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["fused_attention", "multi_head_attention", "paged_kv_write",
           "paged_attention", "rotary_embedding", "latent_attention",
           "grouped_attention", "short_conv", "selective_scan",
           "mamba2_mixer",
           "diff_attention", "linear_attention", "block_sparse_attention",
           "gated_ffn_rows"]


def fused_attention(q, k, v, bias=None, causal=False, scale=0.0,
                    sp_mode="none", name=None):
    """Fused attention on [B, S, H, D] vars. Returns [B, S, H, D]."""
    helper = LayerHelper("fused_attention", input=q, name=name)
    out = helper.create_tmp_variable(q.dtype)
    ins = {"Q": q, "K": k, "V": v}
    if bias is not None:
        ins["BiasMask"] = bias
    helper.append_op("scaled_dot_product_attention", ins, {"Out": out},
                     {"causal": bool(causal), "scale": float(scale),
                      "sp_mode": sp_mode})
    return out


def rotary_embedding(x, positions=None, theta=10000.0, name=None):
    """Rotate-half RoPE over the whole head of x [B, S, H, D]
    (ops/attention_ops.py rotary_embedding). positions: an int var [S]
    or [B, S]; None means 0..S-1 (a prefill, a trainer)."""
    helper = LayerHelper("rotary_embedding", name=name)
    if positions is None:
        positions = helper.create_tmp_variable("int32", stop_gradient=True)
        positions.shape = (int(x.shape[1]),)
        helper.append_op("arange", {}, {"Out": positions},
                         {"start": 0, "end": int(x.shape[1]), "step": 1,
                          "dtype": "int32"})
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op("rotary_embedding", {"X": x, "Positions": positions},
                     {"Out": out}, {"theta": float(theta)})
    return out


def paged_kv_write(k_pool, v_pool, k, v, block_tables, context_lens,
                   name=None):
    """Write each slot's new K/V row ([S, 1, H, D]) into its page of the
    paged pool ([NB, BS, H, D]). Returns the updated (k_pool, v_pool)
    vars — the decode program fetches these as the next step's feeds."""
    helper = LayerHelper("paged_kv_write", name=name)
    k_out = helper.create_tmp_variable(k_pool.dtype)
    v_out = helper.create_tmp_variable(v_pool.dtype)
    helper.append_op("paged_kv_write",
                     {"KPool": k_pool, "VPool": v_pool, "K": k, "V": v,
                      "BlockTables": block_tables,
                      "ContextLens": context_lens},
                     {"KOut": k_out, "VOut": v_out}, {})
    return k_out, v_out


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale=0.0, name=None):
    """One decode token per slot (q [S, 1, H, D]) attends through its
    block table into the paged KV pool. Returns [S, 1, H, D]."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_tmp_variable(q.dtype)
    helper.append_op("paged_attention",
                     {"Q": q, "KPool": k_pool, "VPool": v_pool,
                      "BlockTables": block_tables,
                      "ContextLens": context_lens},
                     {"Out": out}, {"scale": float(scale)})
    return out


def qk_normed(q, k, eps, name):
    """q/k-norm: each of the two projections RMS-normalised over its
    whole width, before the heads are split. eps None: as they came.
    One place for the training, prefill and decode builders, so the
    gains' names cannot drift apart."""
    if eps is None:
        return q, k
    from . import nn as L
    stem = name or "attn"
    return tuple(
        L.rms_norm(t, begin_norm_axis=2, epsilon=eps,
                   param_attr=ParamAttr(name=f"{stem}_{tag}norm_scale"),
                   name=f"{stem}_{tag}norm")
        for t, tag in ((q, "q"), (k, "k")))


def _indexer_weights(matrix, vector, q_width, d, heads, width):
    """The indexer's five weights, under the roles the ops read them by
    (`ops.attention_ops._indexer`), for either attention that selects:
    `{name}_iq_w` [q_width, Hi Di] (`q_width`: the width of what qI is
    projected from), `{name}_ik_w` [d, Di], `{name}_iw_w` [d, Hi],
    `{name}_iknorm_scale`, `{name}_iknorm_bias` [Di]."""
    return {"WIq": matrix("iq", q_width, heads * width),
            "WIk": matrix("ik", d, width),
            "WIw": matrix("iw", d, heads),
            "IKNormScale": vector("iknorm_scale", width, 1.0),
            "IKNormBias": vector("iknorm_bias", width, 0.0)}


def latent_attention(x, *, num_heads, kv_lora_rank, qk_nope_head_dim,
                     qk_rope_head_dim, v_head_dim, rope_theta,
                     rope_interleave=True, epsilon=1e-6, name=None,
                     latent_out=None, pool=None, block_tables=None,
                     context_lens=None, positions=None, q_lora_rank=0,
                     index_heads=0, index_head_dim=0, index_topk=0,
                     index_rope_dim=0, index_rope_interleave=False,
                     index_pool=None, selected_out=None):
    """Multi-head latent attention (ops/attention_ops.py, the text above
    `latent_attention`) on x [B, S, d_model], causal, no bias. One place
    for the training, prefill and decode builders, so the weights' names
    cannot drift apart: `{name}_q_w` [d, H (nope + rope)], `{name}_kva_w`
    [d, rank + rope], `{name}_kvnorm_scale` [rank], `{name}_kvb_w`
    [rank, H (nope + v)], `{name}_out_w` [H v, d]. With `q_lora_rank`
    the query is `{name}_qa_w` [d, q_rank], `{name}_qnorm_scale`
    [q_rank], `{name}_qb_w` [q_rank, H (nope + rope)] in `{name}_q_w`'s
    place. With `index_topk` an indexer that reads the query's low-rank
    (`_indexer_weights` with q_width = q_rank; its LayerNorm's epsilon
    1e-6 and its head weights times (Hi Di)^-1/2, the published
    DeepSeek-V3.2 form), the first `index_rope_dim` of its width rotated
    (0: all of it).

    Without a pool: whole sequences at positions 0..S-1, expanded; each
    token's cache rows ([B, S, rank + rope], and [B, S, Di] with an
    indexer) are appended to `latent_out` (a list) when given; with an
    indexer and a list `selected_out`, every row's selection as bits
    ([B, S, ceil(S / 32)] int32). Returns out.

    With `pool` [NB, BS, W] (and `index_pool` [NB, BS, Wi]): one new
    token a slot (x [slots, 1, d]) at `positions` [slots, 1], absorbed,
    through `block_tables` and `context_lens`; each slot's selected
    positions are appended to `selected_out`. Returns (out, the pools
    with the new rows written, a tuple)."""
    from ..initializer import ConstantInitializer, XavierInitializer
    helper = LayerHelper("latent_attention", name=name)
    stem = helper.name
    d = int(x.shape[-1])
    q_w = num_heads * (qk_nope_head_dim + qk_rope_head_dim)
    kva_w = kv_lora_rank + qk_rope_head_dim
    kvb_w = num_heads * (qk_nope_head_dim + v_head_dim)
    indexed = index_topk > 0

    def matrix(tag, rows, cols):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), [rows, cols], "float32",
            default_initializer=XavierInitializer())

    def vector(tag, width, value):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}"), [width], "float32",
            default_initializer=ConstantInitializer(value))

    ins = {"X": x}
    if q_lora_rank:
        ins.update(Wqa=matrix("qa", d, q_lora_rank),
                   QNorm=vector("qnorm_scale", q_lora_rank, 1.0),
                   Wqb=matrix("qb", q_lora_rank, q_w))
    else:
        ins["Wq"] = matrix("q", d, q_w)
    ins.update(Wkva=matrix("kva", d, kva_w),
               KvNorm=vector("kvnorm_scale", kv_lora_rank, 1.0),
               Wkvb=matrix("kvb", kv_lora_rank, kvb_w),
               Wo=matrix("out", num_heads * v_head_dim, d))
    attrs = {"num_heads": int(num_heads), "kv_lora_rank": int(kv_lora_rank),
             "qk_nope_head_dim": int(qk_nope_head_dim),
             "qk_rope_head_dim": int(qk_rope_head_dim),
             "v_head_dim": int(v_head_dim), "rope_theta": float(rope_theta),
             "rope_interleave": bool(rope_interleave),
             "epsilon": float(epsilon)}
    if indexed:     # a program without one records what it did before
        ins.update(_indexer_weights(matrix, vector, q_lora_rank,
                                    d, index_heads, index_head_dim))
        attrs.update(
            index_heads=int(index_heads), index_topk=int(index_topk),
            index_head_dim=int(index_head_dim), index_epsilon=1e-6,
            index_rope_dim=int(index_rope_dim),
            index_rope_interleave=bool(index_rope_interleave),
            index_weight_scale=float(index_heads * index_head_dim) ** -0.5)
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}

    def more(role, dtype=None):
        outs[role] = helper.create_tmp_variable(
            dtype or x.dtype, stop_gradient=dtype is not None)
        return outs[role]

    if pool is None:
        rows = [more("Latent")] + ([more("IndexK")] if indexed else [])
        if indexed and selected_out is not None:
            attrs["return_selected"] = True
            selected_out.append(more("Selected", "int32"))
        helper.append_op("latent_attention", ins, outs, attrs)
        if latent_out is not None:
            latent_out.extend(rows)
        return out
    pool_outs = [helper.create_tmp_variable(pool.dtype)]
    outs["PoolOut"] = pool_outs[0]
    ins.update(Pool=pool, BlockTables=block_tables,
               ContextLens=context_lens, Positions=positions)
    if indexed:
        ins["IndexPool"] = index_pool
        pool_outs.append(more("IndexOut"))
        selected = more("Selected", "int32")
        if selected_out is not None:
            selected_out.append(selected)
    helper.append_op("latent_decode_attention", ins, outs, attrs)
    return out, tuple(pool_outs)


def grouped_attention(x, *, num_heads, num_kv_heads, head_dim, rope_theta,
                      qk_norm=True, index_heads=0, index_head_dim=0,
                      index_topk=0, epsilon=1e-6, name=None, cache_out=None,
                      selected_out=None, pools=None,
                      block_tables=None, context_lens=None, positions=None,
                      window=0, rotary="half", scale=0.0, rope_scaling=()):
    """Grouped-query attention, with a sparse-attention indexer where
    `index_topk` > 0 (ops/attention_ops.py, the text above
    `grouped_attention`) on x [B, S, d_model], causal, no bias. `rotary`:
    "half" (pairs (i, i + D/2)) | "interleave" (pairs (2i, 2i + 1)) |
    "none" (no positions at all); `window` > 0: row t reads the rows s
    with t - s < window and no others; `scale`: what the scores are
    multiplied by (0: 1 / sqrt(head_dim)); `rope_scaling`: the rotary
    table's YaRN parameters (`ops.attention_ops.rope_table`; (): plain).
    One place for the training, prefill and decode builders, so the
    weights' names cannot drift apart: `{name}_q_w` [d, H D],
    `{name}_k_w`, `{name}_v_w` [d, H_kv D], `{name}_out_w` [H D, d],
    `{name}_qnorm_scale`, `{name}_knorm_scale` [D] (with `qk_norm`), and
    the indexer's `{name}_iq_w` [d, Hi Di], `{name}_ik_w` [d, Di],
    `{name}_iw_w` [d, Hi], `{name}_iknorm_scale`, `{name}_iknorm_bias`
    [Di].

    Without pools: whole sequences at positions 0..S-1; what a cache
    holds of each token (K, V, and the index key with an indexer) is
    appended to `cache_out` (a list) as one tuple when given; with an
    indexer and a list `selected_out`, the positions every row attended
    to are appended to it, one bit a position ([B, S, ceil(S / 32)]
    int32, `ops.attention_ops.pack_mask`). Returns out.

    With `pools` (K, V[, index]): one new token a slot (x [slots, 1, d])
    at `positions` [slots, 1] through `block_tables` and `context_lens`;
    each slot's selected positions are appended to `selected_out` where
    there is an indexer. Returns (out, the pools with the new rows
    written)."""
    from ..initializer import ConstantInitializer, XavierInitializer
    helper = LayerHelper("grouped_attention", name=name)
    stem = helper.name
    d = int(x.shape[-1])
    indexed = index_topk > 0

    def matrix(tag, rows, cols):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), [rows, cols], "float32",
            default_initializer=XavierInitializer())

    def vector(tag, width, value):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}"), [width], "float32",
            default_initializer=ConstantInitializer(value))

    ins = {"X": x, "Wq": matrix("q", d, num_heads * head_dim),
           "Wk": matrix("k", d, num_kv_heads * head_dim),
           "Wv": matrix("v", d, num_kv_heads * head_dim),
           "Wo": matrix("out", num_heads * head_dim, d)}
    if qk_norm:
        ins.update(QNorm=vector("qnorm_scale", head_dim, 1.0),
                   KNorm=vector("knorm_scale", head_dim, 1.0))
    if indexed:
        ins.update(_indexer_weights(matrix, vector, d, d, index_heads,
                                    index_head_dim))
    attrs = {"num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
             "head_dim": int(head_dim), "index_heads": int(index_heads),
             "index_head_dim": int(index_head_dim),
             "index_topk": int(index_topk), "rope_theta": float(rope_theta),
             "epsilon": float(epsilon)}
    if window:      # a program without either records what it did before
        attrs["window"] = int(window)
    if rotary != "half":
        attrs["rotary"] = str(rotary)
    if scale:
        attrs["scale"] = float(scale)
    if rope_scaling:
        attrs["rope_scaling"] = [float(v) for v in rope_scaling]
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}

    def more(role, dtype=None):
        outs[role] = helper.create_tmp_variable(
            dtype or x.dtype, stop_gradient=dtype is not None)
        return outs[role]

    if pools is None:
        rows = [more("K"), more("V")] + ([more("IndexK")] if indexed else [])
        if indexed and selected_out is not None:
            attrs["return_selected"] = True
            selected_out.append(more("Selected", "int32"))
        helper.append_op("grouped_attention", ins, outs, attrs)
        if cache_out is not None:
            cache_out.append(tuple(rows))
        return out
    ins.update(KPool=pools[0], VPool=pools[1], BlockTables=block_tables,
               ContextLens=context_lens)
    if positions is not None:   # a block without positions has none
        ins["Positions"] = positions
    pool_outs = [more("KOut"), more("VOut")]
    if indexed:
        ins["IndexPool"] = pools[2]
        pool_outs.append(more("IndexOut"))
        selected = more("Selected", "int32")
        if selected_out is not None:
            selected_out.append(selected)
    helper.append_op("grouped_decode_attention", ins, outs, attrs)
    return out, tuple(pool_outs)


def short_conv(x, *, taps, name=None, n_tokens=None, state_out=None,
               state=None, context_lens=None):
    """A gated short convolution on x [B, S, d_model] (ops/
    attention_ops.py, the text above `short_conv`): LFM2's mixer in the
    attention's place, causal, depthwise, `taps` long, no bias. One
    place for the three builders: `{name}_in_w` [d, 3 d] (the thirds
    B, C, x in that order), `{name}_conv_w` [taps, d] (tap j weighs the
    row taps - 1 - j before the token), `{name}_out_w` [d, d].

    Without `state`: whole sequences; with `n_tokens` ([B] int, each
    row's true length) and a list `state_out`, what a sequence of that
    length leaves behind ([B, taps - 1, d]) is appended to it as one
    tuple, where an attention layer appends its K and V. Returns out.

    With `state` [slots, taps - 1, d] and `context_lens`: one new token
    a slot. Returns (out, the state a row on)."""
    from ..initializer import NormalInitializer, XavierInitializer
    helper = LayerHelper("short_conv", name=name)
    stem = helper.name
    d = int(x.shape[-1])

    def matrix(tag, rows, cols):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), [rows, cols], "float32",
            default_initializer=XavierInitializer())

    ins = {"X": x, "WIn": matrix("in", d, 3 * d),
           "Taps": helper.create_parameter(
               ParamAttr(name=f"{stem}_conv_w"), [int(taps), d], "float32",
               default_initializer=NormalInitializer(
                   scale=float(taps) ** -0.5)),
           "WOut": matrix("out", d, d)}
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}
    if state is not None:
        ins.update(State=state, ContextLens=context_lens)
        outs["StateOut"] = helper.create_tmp_variable(state.dtype)
        helper.append_op("short_conv", ins, outs, {})
        return out, outs["StateOut"]
    if n_tokens is not None and state_out is not None:
        ins["NTokens"] = n_tokens
        outs["StateOut"] = helper.create_tmp_variable(x.dtype)
        state_out.append((outs["StateOut"],))
    helper.append_op("short_conv", ins, outs, {})
    return out


def selective_scan(x, *, d_inner, d_state, dt_rank, taps, name=None,
                   n_tokens=None, state_out=None, state=None,
                   context_lens=None, memory_out=None):
    """Mamba-1's selective scan on x [B, S, d_model] (ops/
    attention_ops.py, the text above `selective_scan`) in the
    attention's place. One place for the three builders: `{name}_in_w`
    [d, 2 d_inner] (x, then the gate z), `{name}_conv_w` [taps, d_inner]
    (tap j weighs the row taps - 1 - j before the token), `{name}_conv_b`,
    `{name}_x_w` [d_inner, dt_rank + 2 d_state] (dt, B, C),
    `{name}_dt_w` [dt_rank, d_inner], `{name}_dt_b`, `{name}_a_log`
    [d_inner, d_state] (starts at log(1 .. d_state) a channel),
    `{name}_d_skip` (starts at 1), `{name}_out_w` [d_inner, d]. `dt_b`
    starts at the inverse softplus of steps spread geometrically over
    [1e-3, 1e-1], one a channel (the published initialisation draws them
    log-uniform there; a bias at 0 starts every step at 0.69 and the
    state forgets in a row).

    A list `memory_out` receives the scan's output before its gate
    ([B, S, d_inner]), what a later layer gates by.

    Without `state`: whole sequences; with `n_tokens` ([B] int, each
    row's true length) and a list `state_out`, what a sequence of that
    length leaves behind ((S [B, d_state, d_inner], the convolution's
    rows [B, taps - 1, d_inner])) is appended to it as one tuple, where
    an attention layer appends its K and V. Returns out.

    With `state` (the two arrays, a slot each) and `context_lens`: one
    new token a slot. Returns (out, the states a row on)."""
    import numpy as np
    from ..initializer import (ConstantInitializer, NormalInitializer,
                               NumpyArrayInitializer, XavierInitializer)
    helper = LayerHelper("selective_scan", name=name)
    stem = helper.name
    d = int(x.shape[-1])
    di, ds, rank, taps = (int(d_inner), int(d_state), int(dt_rank),
                          int(taps))

    def param(tag, shape, init):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}"), list(shape), "float32",
            default_initializer=init)

    steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), di))
    ins = {"X": x,
           "WIn": param("in_w", (d, 2 * di), XavierInitializer()),
           "ConvW": param("conv_w", (taps, di),
                          NormalInitializer(scale=float(taps) ** -0.5)),
           "ConvB": param("conv_b", (di,), ConstantInitializer(0.0)),
           "WX": param("x_w", (di, rank + 2 * ds), XavierInitializer()),
           "WDt": param("dt_w", (rank, di),
                        NormalInitializer(scale=float(rank) ** -0.5)),
           "BDt": param("dt_b", (di,), NumpyArrayInitializer(
               np.log(np.expm1(steps)).astype("float32"))),
           "ALog": param("a_log", (di, ds), NumpyArrayInitializer(
               np.tile(np.log(np.arange(1, ds + 1, dtype="float32")),
                       (di, 1)))),
           "DSkip": param("d_skip", (di,), ConstantInitializer(1.0)),
           "WOut": param("out_w", (di, d), XavierInitializer())}
    attrs = {"d_state": ds, "dt_rank": rank}
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}
    if memory_out is not None:
        outs["Memory"] = helper.create_tmp_variable(x.dtype)
        memory_out.append(outs["Memory"])
    if state is not None:
        ins.update(SsmState=state[0], ConvState=state[1],
                   ContextLens=context_lens)
        outs["SsmStateOut"] = helper.create_tmp_variable(state[0].dtype)
        outs["ConvStateOut"] = helper.create_tmp_variable(state[1].dtype)
        helper.append_op("selective_scan", ins, outs, attrs)
        return out, (outs["SsmStateOut"], outs["ConvStateOut"])
    if n_tokens is not None and state_out is not None:
        ins["NTokens"] = n_tokens
        outs["SsmStateOut"] = helper.create_tmp_variable(x.dtype)
        outs["ConvStateOut"] = helper.create_tmp_variable(x.dtype)
        state_out.append((outs["SsmStateOut"], outs["ConvStateOut"]))
    helper.append_op("selective_scan", ins, outs, attrs)
    return out


def mamba2_mixer(x, *, d_inner, d_state, heads, groups, taps, chunk=128,
                 epsilon=1e-5, name=None, n_tokens=None, state_out=None,
                 state=None, context_lens=None):
    """A Mamba-2 mixer on x [B, S, d_model] (ops/attention_ops.py, the
    text above `mamba2_mixer`): the whole layer, with nothing beside it
    under its norm. One place for the builders: `{name}_in_w` [d, 2
    d_inner + 2 groups d_state + heads] (the gate z, then x, B, C, then
    the heads' steps), `{name}_conv_w` [taps, d_inner + 2 groups d_state]
    (tap j weighs the row taps - 1 - j before the token), `{name}_conv_b`,
    `{name}_dt_b`, `{name}_a_log`, `{name}_d_skip` [heads],
    `{name}_norm_scale` [d_inner], `{name}_out_w` [d_inner, d]. As
    `mamba_ssm` starts them: `a_log` at log of 1 .. 16 spread over the
    heads, `d_skip` at 1, `dt_b` at the inverse softplus of steps spread
    geometrically over [1e-3, 1e-1].

    Without `state`: whole sequences; with `n_tokens` ([B] int, each
    row's true length) and a list `state_out`, what a sequence of that
    length leaves behind ((S [B, heads, d_inner / heads, d_state], the
    convolution's rows [B, taps - 1, d_inner + 2 groups d_state])) is
    appended to it as one tuple. Returns out.

    With `state` (the two arrays, a slot each) and `context_lens`: one
    new token a slot. Returns (out, the states a row on)."""
    import numpy as np
    from ..initializer import (ConstantInitializer, NormalInitializer,
                               NumpyArrayInitializer, XavierInitializer)
    helper = LayerHelper("mamba2_mixer", name=name)
    stem = helper.name
    d = int(x.shape[-1])
    di, ds, heads, groups, taps = (int(d_inner), int(d_state), int(heads),
                                   int(groups), int(taps))
    width = di + 2 * groups * ds

    def param(tag, shape, init):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}"), list(shape), "float32",
            default_initializer=init)

    def vector(tag, values):
        return param(tag, (heads,), NumpyArrayInitializer(
            np.asarray(values, "float32")))

    steps = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), heads))
    ins = {"X": x,
           "WIn": param("in_w", (d, di + width + heads),
                        XavierInitializer()),
           "ConvW": param("conv_w", (taps, width),
                          NormalInitializer(scale=float(taps) ** -0.5)),
           "ConvB": param("conv_b", (width,), ConstantInitializer(0.0)),
           "BDt": vector("dt_b", np.log(np.expm1(steps))),
           "ALog": vector("a_log", np.log(np.linspace(1.0, 16.0, heads))),
           "DSkip": vector("d_skip", np.ones(heads)),
           "NormW": param("norm_scale", (di,), ConstantInitializer(1.0)),
           "WOut": param("out_w", (di, d), XavierInitializer())}
    attrs = {"heads": heads, "groups": groups, "d_state": ds,
             "chunk": int(chunk), "epsilon": float(epsilon)}
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}
    if state is not None:
        ins.update(SsmState=state[0], ConvState=state[1],
                   ContextLens=context_lens)
        outs["SsmStateOut"] = helper.create_tmp_variable(state[0].dtype)
        outs["ConvStateOut"] = helper.create_tmp_variable(state[1].dtype)
        helper.append_op("mamba2_mixer", ins, outs, attrs)
        return out, (outs["SsmStateOut"], outs["ConvStateOut"])
    if n_tokens is not None and state_out is not None:
        ins["NTokens"] = n_tokens
        outs["SsmStateOut"] = helper.create_tmp_variable(x.dtype)
        outs["ConvStateOut"] = helper.create_tmp_variable(x.dtype)
        state_out.append((outs["SsmStateOut"], outs["ConvStateOut"]))
    helper.append_op("mamba2_mixer", ins, outs, attrs)
    return out


def diff_attention(x, *, num_heads, num_kv_heads, head_dim, lambda_init,
                   epsilon=1e-5, window=0, name=None, cache_out=None,
                   kv_from=None, q_rows=None, kv=None, pools=None,
                   block_tables=None, context_lens=None):
    """Differential attention (ops/attention_ops.py, the text above
    `diff_attention`) on x [B, S, d_model], causal, no positions, a bias
    on every projection. One place for the three builders: `{name}_q_w`
    [d, H D], `{name}_k_w`, `{name}_v_w` [d, H_kv D], `{name}_out_w`
    [H D, d], their `_b`, `{name}_lq1`, `_lk1`, `_lq2`, `_lk2` [D]
    (normal at 0.1) and `{name}_subnorm_scale` [2 D]. A CROSS layer
    (`kv` or `pools` another layer's, see below) has the q and out
    projections, the lambdas and the sub-norm alone.

    Without pools: whole sequences. A self layer appends what a cache
    holds of each token (K, V: [B, S, H_kv / 2, 2 D]) to `cache_out` (a
    list) as one tuple when given; `kv_from` [B, S', d] with `q_rows`
    [B, S] int: x is a few rows of that sequence, at those positions,
    and K and V are projected from all of it. A cross layer: `kv` = the
    (K, V) another layer appended. Returns out.

    With `pools` (K, V), `block_tables` and `context_lens`: one new
    token a slot (x [slots, 1, d]). A self layer writes its row and
    returns (out, the pools with the new rows written); a cross layer
    (`kv` True) reads the pools as they are and returns (out, ())."""
    from ..initializer import (ConstantInitializer, NormalInitializer,
                               XavierInitializer)
    helper = LayerHelper("diff_attention", name=name)
    stem = helper.name
    d = int(x.shape[-1])
    cross = kv is not None

    def matrix(tag, rows, cols):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), [rows, cols], "float32",
            default_initializer=XavierInitializer())

    def vector(tag, width, init):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}"), [width], "float32",
            default_initializer=init)

    zero = ConstantInitializer(0.0)
    ins = {"X": x, "Wq": matrix("q", d, num_heads * head_dim),
           "Bq": vector("q_b", num_heads * head_dim, zero)}
    if not cross:
        ins.update(Wk=matrix("k", d, num_kv_heads * head_dim),
                   Bk=vector("k_b", num_kv_heads * head_dim, zero),
                   Wv=matrix("v", d, num_kv_heads * head_dim),
                   Bv=vector("v_b", num_kv_heads * head_dim, zero))
    ins.update(Wo=matrix("out", num_heads * head_dim, d),
               Bo=vector("out_b", d, zero),
               SubNorm=vector("subnorm_scale", 2 * head_dim,
                              ConstantInitializer(1.0)),
               **{role: vector(tag, head_dim, NormalInitializer(scale=0.1))
                  for role, tag in (("LamQ1", "lq1"), ("LamK1", "lk1"),
                                    ("LamQ2", "lq2"), ("LamK2", "lk2"))})
    attrs = {"num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
             "head_dim": int(head_dim), "lambda_init": float(lambda_init),
             "epsilon": float(epsilon)}
    if window:
        attrs["window"] = int(window)
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}
    if pools is not None:
        ins.update(KPool=pools[0], VPool=pools[1], BlockTables=block_tables,
                   ContextLens=context_lens)
        written = ()
        if not cross:
            written = (helper.create_tmp_variable(pools[0].dtype),
                       helper.create_tmp_variable(pools[1].dtype))
            outs.update(KOut=written[0], VOut=written[1])
        helper.append_op("diff_decode_attention", ins, outs, attrs)
        return out, written
    if cross:
        ins.update(KIn=kv[0], VIn=kv[1])
    else:
        outs.update(K=helper.create_tmp_variable(x.dtype),
                    V=helper.create_tmp_variable(x.dtype))
        if cache_out is not None:
            cache_out.append((outs["K"], outs["V"]))
        if kv_from is not None:
            ins["XKV"] = kv_from
    if q_rows is not None:
        ins["QRows"] = q_rows
    helper.append_op("diff_attention", ins, outs, attrs)
    return out


def multi_head_attention(queries, keys=None, values=None, *, num_heads,
                         d_key=None, d_value=None, d_model=None,
                         causal=False, sp_mode="none", dropout_rate=0.0,
                         param_attr=None, bias_attr=None, tp_shard=False,
                         kv_out=None, qk_norm_eps=None, rope_theta=None,
                         name=None):
    """Full MHA block on [B, S, d_model] vars: QKV projections → fused
    attention → output projection. Self-attention when keys/values omitted.

    tp_shard: mark projection weights Megatron-style (column-parallel QKV,
    row-parallel output) for the `tp` mesh axis.

    kv_out: optional list — the per-head K and V vars ([B, S, H, d_key])
    are appended as a (k, v) pair, so a prefill export can fetch them for
    the paged decode cache (serving/decode).

    bias_attr=False leaves the four projections without a bias.
    qk_norm_eps: RMS-normalise the whole q and k projections (gains
    `{name}_qnorm_scale`, `{name}_knorm_scale`) before the head split,
    as OLMo-2/OLMoE do. rope_theta: rotate q and k by their position
    0..S-1 after the split; the K in kv_out is the rotated one, so a
    paged cache seeded from it needs no position of its own.
    """
    from . import nn as L
    from .nn import dropout as drop_layer

    keys = queries if keys is None else keys
    values = keys if values is None else values
    dm = int(queries.shape[-1]) if d_model is None else int(d_model)
    d_key = dm // num_heads if d_key is None else d_key
    d_value = d_key if d_value is None else d_value

    from ..layer_helper import capture_new_params
    new_weights = []  # (param, is_row_parallel) created by each projection

    def proj(x, width, tag, row_parallel=False):
        import copy
        # explicit param names when the layer is named, so a separately
        # built program (inference/decode) shares weights through the scope.
        # Each projection gets its OWN ParamAttr copy: create_parameter
        # fills attr.name in place when it is None (layer_helper.py), and a
        # shared object would silently alias Q/K/V/out onto one parameter.
        # A user-supplied explicit name is suffixed per projection for the
        # same reason — four projections cannot share one weight.
        pa = copy.copy(param_attr) if param_attr is not None else None
        ba = copy.copy(bias_attr) if bias_attr else None
        if pa is not None and pa.name is not None:
            pa.name = f"{pa.name}.{tag}"
        if ba is not None and ba.name is not None:
            ba.name = f"{ba.name}.{tag}"
        if bias_attr is False:
            ba = False
        if name is not None:
            pa = pa if pa is not None else ParamAttr(name=f"{name}_{tag}_w")
            if ba is None:
                ba = ParamAttr(name=f"{name}_{tag}_b")
        out, created = capture_new_params(lambda: L.fc(
            x, size=width, num_flatten_dims=2, param_attr=pa, bias_attr=ba,
            name=None if name is None else f"{name}_{tag}"))
        new_weights.extend((v, row_parallel) for v in created
                           if len(v.shape) == 2)
        return out

    q = proj(queries, num_heads * d_key, "q")
    k = proj(keys, num_heads * d_key, "k")
    v = proj(values, num_heads * d_value, "v")
    q, k = qk_normed(q, k, qk_norm_eps, name)

    qr = L.reshape(q, [0, 0, num_heads, d_key])
    kr = L.reshape(k, [0, 0, num_heads, d_key])
    vr = L.reshape(v, [0, 0, num_heads, d_value])
    if rope_theta is not None:
        qr = rotary_embedding(qr, theta=rope_theta)
        kr = rotary_embedding(kr, theta=rope_theta)
    if kv_out is not None:
        kv_out.append((kr, vr))

    ctx = fused_attention(qr, kr, vr, causal=causal, sp_mode=sp_mode,
                          name=name)
    merged = L.reshape(ctx, [0, 0, num_heads * d_value])
    if dropout_rate:
        merged = drop_layer(merged, dropout_prob=dropout_rate)
    out = proj(merged, dm, "out", row_parallel=True)

    if tp_shard:
        # Megatron layout: QKV weights column-parallel (heads split over tp),
        # output weight row-parallel (tp contributions psum'd by GSPMD)
        from ..parallel.mesh import TP
        for var, row_parallel in new_weights:
            var.sharding = (TP, None) if row_parallel else (None, TP)
    return out


def gated_ffn_rows(x, width, *, stem, rows=0, precision="", scope=""):
    """A gated-SiLU FFN on x [B, S, d] with the rows of a long bucket
    taken a chunk at a time inside the program (ops/block_sparse_ops.py
    `gated_ffn_rows`); the weights are `layers.fc`'s of the same names,
    `{stem}_gate_w`, `{stem}_up_w` [d, width], `{stem}_down_w`, no bias.
    `scope`: a `jax.named_scope` about the whole of it, its name in a
    device trace."""
    from ..initializer import XavierInitializer
    helper = LayerHelper("gated_ffn_rows", name=stem)
    d = int(x.shape[-1])

    def matrix(tag, shape):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), list(shape), "float32",
            default_initializer=XavierInitializer())

    out = helper.create_tmp_variable(x.dtype)
    attrs = {"rows": int(rows)}
    if precision:
        attrs["precision"] = str(precision)
    if scope:
        attrs["scope"] = str(scope)
    helper.append_op("gated_ffn_rows", {
        "X": x, "WGate": matrix("gate", (d, width)),
        "WUp": matrix("up", (d, width)),
        "WDown": matrix("down", (width, d))}, {"Out": out}, attrs)
    return out


def _mixer_weights(helper, d, wide, narrow, head_dim, out_norm, gate):
    """The projections, q/k gains (and the output norm's) the two gated
    mixers below share the names of: `{name}_q_w`, `{name}_gate_w` [d,
    wide], `{name}_k_w`, `{name}_v_w` [d, narrow], `{name}_out_w` [wide,
    d], `{name}_qnorm_scale`, `{name}_knorm_scale` [head_dim],
    `{name}_onorm_scale` [wide]."""
    from ..initializer import ConstantInitializer, XavierInitializer
    stem = helper.name

    def matrix(tag, rows, cols):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_w"), [rows, cols], "float32",
            default_initializer=XavierInitializer())

    def gain(tag, width):
        return helper.create_parameter(
            ParamAttr(name=f"{stem}_{tag}_scale"), [width], "float32",
            default_initializer=ConstantInitializer(1.0))

    ins = {"Wq": matrix("q", d, wide), "Wk": matrix("k", d, narrow),
           "Wv": matrix("v", d, narrow), "Wo": matrix("out", wide, d),
           "QNorm": gain("qnorm", head_dim), "KNorm": gain("knorm", head_dim)}
    if gate:
        ins["Wg"] = matrix("gate", d, wide)
    if out_norm:
        ins["ONorm"] = gain("onorm", wide)
    return ins


def linear_attention(x, *, heads, head_dim, layer, n_layers, rope_theta,
                     rotary="half", chunk=128, epsilon=1e-6, name=None,
                     n_tokens=None, state_out=None, state=None,
                     context_lens=None, positions=None, gate=True):
    """Linear attention with a constant decay a head on x [B, S, d]
    (ops/block_sparse_ops.py, the text on top): per-head q/k-norm,
    rotary positions unless `rotary` is "none", an output norm and,
    with `gate`, a sigmoid gate. `layer` of `n_layers`: the PUBLISHED index the decay
    is computed from.

    Without `state`: whole sequences; with `n_tokens` ([B] int) and a
    list `state_out`, the state a sequence of that length leaves ([B,
    heads, head_dim, head_dim]) is appended to it as a one-tuple.
    Returns out. With `state` (a one-tuple), `context_lens` and
    `positions` [slots, 1]: one new token a slot. Returns (out, (the
    state a row on,))."""
    helper = LayerHelper("linear_attention", name=name)
    wide = int(heads) * int(head_dim)
    ins = {"X": x, **_mixer_weights(helper, int(x.shape[-1]), wide, wide,
                                    int(head_dim), True, gate)}
    attrs = {"heads": int(heads), "head_dim": int(head_dim),
             "layer": int(layer), "n_layers": int(n_layers),
             "rope_theta": float(rope_theta), "rotary": str(rotary),
             "chunk": int(chunk), "epsilon": float(epsilon)}
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}
    if state is not None:
        ins.update(State=state[0], ContextLens=context_lens,
                   Positions=positions)
        outs["StateOut"] = helper.create_tmp_variable(state[0].dtype)
        helper.append_op("linear_attention", ins, outs, attrs)
        return out, (outs["StateOut"],)
    if n_tokens is not None:
        ins["NTokens"] = n_tokens
        if state_out is not None:
            outs["StateOut"] = helper.create_tmp_variable(x.dtype)
            state_out.append((outs["StateOut"],))
    helper.append_op("linear_attention", ins, outs, attrs)
    return out


def block_sparse_attention(x, *, num_heads, num_kv_heads, head_dim, sizes,
                           epsilon=1e-6, name=None, n_tokens=None,
                           max_pooled=0, cache_out=None, selected_out=None,
                           pools=None, block_tables=None,
                           context_lens=None, gate=True):
    """Grouped-query attention over SELECTED BLOCKS scored on pooled keys
    (ops/block_sparse_ops.py, the text on top) on x [B, S, d]: per-head
    q/k-norm, no positions, with `gate` a sigmoid output gate. `sizes`: a dict of
    kernel, stride, block, topk, window, init (the last three in
    blocks) and dense_len.

    Without `pools`: whole sequences, dense or sparse by `n_tokens` ([B]
    int: each row's length; absent: S); what a cache holds of them (K, V
    [B, S, H_kv D] and, with `max_pooled`, the pooled keys [B,
    max_pooled, H_kv D]) is appended to `cache_out` as one tuple, every
    row's chosen blocks to `selected_out` ([B, S, H_kv ceil(NB / 32)]
    int32, one bit a block). Returns out.

    With `pools` (K, V, the slots' pooled keys): one new token a slot
    through `block_tables` and `context_lens`; each slot's chosen blocks
    ([slots, H_kv, W] int32) are appended to `selected_out`. Returns
    (out, the three with the step's rows written)."""
    helper = LayerHelper("block_sparse_attention", name=name)
    ins = {"X": x, **_mixer_weights(
        helper, int(x.shape[-1]), int(num_heads) * int(head_dim),
        int(num_kv_heads) * int(head_dim), int(head_dim), False, gate)}
    attrs = {"num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
             "head_dim": int(head_dim), "epsilon": float(epsilon),
             **{k: int(v) for k, v in sizes.items()}}
    out = helper.create_tmp_variable(x.dtype)
    outs = {"Out": out}

    def more(role, dtype=None):
        outs[role] = helper.create_tmp_variable(
            dtype or x.dtype, stop_gradient=dtype is not None)
        return outs[role]

    if pools is None:
        if n_tokens is not None:
            ins["NTokens"] = n_tokens
        rows = [more("K"), more("V")]
        if max_pooled:
            attrs["max_pooled"] = int(max_pooled)
            rows.append(more("Pooled"))
        if selected_out is not None:
            attrs["return_selected"] = True
            selected_out.append(more("Selected", "int32"))
        helper.append_op("block_sparse_attention", ins, outs, attrs)
        if cache_out is not None:
            cache_out.append(tuple(rows))
        return out
    ins.update(KPool=pools[0], VPool=pools[1], Pooled=pools[2],
               BlockTables=block_tables, ContextLens=context_lens)
    pool_outs = (more("KOut"), more("VOut"), more("PooledOut"))
    selected = more("Selected", "int32")
    if selected_out is not None:
        selected_out.append(selected)
    helper.append_op("block_sparse_decode_attention", ins, outs, attrs)
    return out, pool_outs
