"""Attention ops.

The reference has no attention op (2018): attention is composed from
mul/softmax ops (python/paddle/fluid/nets.py scaled_dot_product_attention,
tests/book machine_translation attention decoder). Here attention is a
first-class op so the TPU lowering can pick the right kernel:

* no sp axis — flash-attention Pallas kernel on TPU (under a dp/tp mesh
  entered via shard_map, batch on dp and heads on tp), XLA reference
  path elsewhere (kernels/flash_attention.py);
* mesh with an `sp` axis — ring attention (ppermute ring over ICI) or
  Ulysses all-to-all sequence parallelism (parallel/ring.py), entered via
  shard_map *inside* the jitted program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.compat import shard_map
from ..core.registry import register_op, same_shape
from ..obs import trace as obs_trace


def _sdpa_infer(op, block):
    q = block.var(op.input("Q")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = q.shape, q.dtype


@register_op("scaled_dot_product_attention", infer_shape=_sdpa_infer)
def scaled_dot_product_attention(ctx, ins, attrs):
    """Q,K,V: [B, S, H, D]. Optional BiasMask input: additive [.., Sq, Sk].

    attrs:
      causal:  bool
      scale:   float; 0.0 means 1/sqrt(D)
      sp_mode: "none" | "ring" | "ulysses" — how to use a mesh `sp` axis
    """
    from ..kernels.flash_attention import _tpu_ok, dot_product_attention
    from ..parallel.ring import ring_attention, ulysses_attention
    from ..parallel.mesh import DP, SP, TP

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    bias = ins["BiasMask"][0] if ins.get("BiasMask") else None
    causal = bool(attrs.get("causal", False))
    scale = attrs.get("scale", 0.0) or None
    sp_mode = attrs.get("sp_mode", "none")

    mesh = ctx.mesh
    sp = mesh.shape.get(SP, 1) if mesh is not None else 1
    tp = mesh.shape.get(TP, 1) if mesh is not None else 1
    dp = mesh.shape.get(DP, 1) if mesh is not None else 1
    # batch on dp, heads on tp (each head independent); a dim that does
    # not divide its axis stays replicated
    bdim = DP if (dp > 1 and q.shape[0] % dp == 0) else None
    hdim = TP if (tp > 1 and q.shape[2] % tp == 0) else None
    heads_local = q.shape[2] // (tp if hdim else 1)
    use_sp = sp_mode in ("ring", "ulysses") and sp > 1
    if use_sp:
        # sp was explicitly requested for a multi-chip sp mesh — falling
        # back to full attention would silently reintroduce the O(S²)
        # per-device profile sp exists to avoid, so unmet preconditions
        # are errors (shapes are static: this fires at trace time).
        problems = []
        if bias is not None:
            problems.append("explicit bias/mask is unsupported under sp")
        if q.shape[1] != k.shape[1]:
            problems.append(f"sq={q.shape[1]} != sk={k.shape[1]}")
        if q.shape[1] % sp:
            problems.append(f"seq {q.shape[1]} not divisible by sp={sp}")
        if sp_mode == "ulysses" and heads_local % sp:
            problems.append(f"{heads_local} local heads not divisible by "
                            f"sp={sp} (ulysses shards heads)")
        if problems:
            raise ValueError(
                f"scaled_dot_product_attention(sp_mode={sp_mode!r}) cannot "
                f"shard over sp={sp}: " + "; ".join(problems))
    if not use_sp:
        if (bias is None and mesh is not None and mesh.size > 1
                and _tpu_ok(q, k, causal)):
            # GSPMD cannot partition a Mosaic kernel: enter it through
            # shard_map (heads that do not divide tp stay replicated,
            # never the O(S²) reference)
            spec = PartitionSpec(bdim, None, hdim, None)
            out = shard_map(
                lambda q, k, v: dot_product_attention(
                    q, k, v, causal=causal, scale=scale),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)(q, k, v)
        else:
            out = dot_product_attention(q, k, v, bias, causal=causal,
                                        scale=scale)
        # name the output so remat_scope(policy="save_attn") can keep it
        # as a saved primal (the expensive flash forward is then NOT
        # recomputed in the backward; the saved value is O(S·D))
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "flash_attn_out")
        return {"Out": [out]}

    # sequence on sp, beside batch on dp and heads on tp
    spec = PartitionSpec(bdim, SP, hdim, None)
    inner = ring_attention if sp_mode == "ring" else ulysses_attention

    def local(q, k, v):
        return inner(q, k, v, axis_name=SP, causal=causal, scale=scale)

    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    # same tag as the single-chip path so remat_scope(policy="save_attn")
    # keeps the (ring/ulysses) attention output instead of silently
    # degrading to full recompute under sp
    from jax.ad_checkpoint import checkpoint_name
    return {"Out": [checkpoint_name(fn(q, k, v), "flash_attn_out")]}


# ---------------------------------------------------------------------------
# Paged decode ops (serving/decode): one token per sequence slot against a
# block-paged KV pool. Inference-only — no grad rule needed; the decode
# program is built is_test and never differentiated.
# ---------------------------------------------------------------------------

def rope_table(d, theta, scaling=()):
    """The rotary table of a head of `d`: (the D/2 frequencies, float32;
    what cos and sin are multiplied by). Plain: theta^(-2i/D) and 1.
    `scaling` = (factor, original context, beta_fast, beta_slow,
    attention factor): YaRN as `transformers` computes it: with f(n) = D
    ln(original / (2 pi n)) / (2 ln theta), low = floor(f(beta_fast)),
    high = ceil(f(beta_slow)) and ramp_i = clip((i - low) / (high - low),
    0, 1), pair i turns at (1 - ramp_i) + ramp_i / factor of its plain
    frequency (the fast pairs as they are, the slow ones `factor` times
    slower), and cos and sin carry the attention factor. Neither depends
    on the sequence's length."""
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not scaling:
        return inv_freq, 1.0
    factor, original, fast, slow, attention = (float(v) for v in scaling)

    def pair_of(turns):
        return d * math.log(original / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low if high > low else 0.001), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + inv_freq / factor * ramp, attention


def rope_rotate(x, positions, theta, interleave=False, scaling=()):
    """x [B, S, H, D] rotated by `positions` ([S], shared by every row,
    or [B, S], each row's own), angles in float32 whatever x's dtype.
    `interleave`: dimensions (2i, 2i+1) are a pair (the published
    DeepSeek / GPT-J form); otherwise (i, i + D/2) are (rotate-half).
    `scaling`: the table's (`rope_table`: the ONE place that builds it)."""
    pos = positions.astype(jnp.float32)
    d = x.shape[-1]
    inv_freq, factor = rope_table(d, theta, scaling)
    ang = (pos[..., None] * inv_freq)[..., None, :]     # [(B,) S, 1, D/2]
    if pos.ndim == 1:
        ang = ang[None]                                 # [1, S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    if interleave:
        a, b = xf[..., 0::2], xf[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                        axis=-1).reshape(xf.shape)
    else:
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                              axis=-1)
    return out.astype(x.dtype)


@register_op("rotary_embedding", infer_shape=same_shape("X", "Out"))
def rotary_embedding(ctx, ins, attrs):
    """Rotary position embedding (Su et al. 2021) in the rotate-half
    form over the whole head, as GPT-NeoX/Llama/OLMo apply it:

        out = x * cos(p * f) + rotate_half(x) * sin(p * f),
        f_i = theta ** (-2i / D), the D/2 frequencies repeated twice,
        rotate_half([a, b]) = [-b, a].

    X: [B, S, H, D]. Positions: [S] (shared by every row: a prefill's
    or a trainer's iota) or [B, S] (a decode step's per-slot position,
    S = 1). Angles are float32 whatever X's dtype."""
    return {"Out": [rope_rotate(ins["X"][0], ins["Positions"][0],
                                float(attrs.get("theta", 10000.0)))]}


# ---------------------------------------------------------------------------
# Latent attention (DeepSeek-V2/V3's MLA): keys and values of all heads are
# up-projections of one low-rank latent a token, and one rotary key is
# shared by every head.
#
#   q = x Wq -> [.., H, nope + rope] = q_nope | q_rope;  or, with a query
#   low-rank (`Wqa`): cq = RMSNorm(x Wqa) * gq -> [.., q_rank], q = cq Wqb
#   x Wkva  -> [.., rank + rope]    = c | k_rope;   c = RMSNorm(c) * g
#   c Wkvb  -> [.., H, nope + v]    = k_nope | v
#   RoPE on q_rope and k_rope alone
#   scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)
#   With an indexer (index_topk > 0; DeepSeek-V3.2's sparse attention
#   over a latent cache, `_indexer` below): qI is projected from cq, the
#   query's low-rank, kI and w from x; only the first `index_rope_dim` of
#   qI's and kI's width rotate; row t's softmax runs over its selected
#   positions S_t alone.
#
# Two ops, one set of weights and one `_latent_project`: `latent_attention`
# builds K and V from the latent and runs ordinary causal attention with a
# V width of its own (a prefill, a trainer); `latent_decode_attention` is
# the same function regrouped for one new token a slot against a paged
# cache of latent rows [c | k_rope rotated]: with Wkvb split by head into
# Wk_h and Wv_h, q'_h = q_nope_h Wk_h^T scores straight against c, and
# o_h = (P_h c) Wv_h: the cache is read once for all heads and never
# expanded. With an indexer a second pool holds an index key a token, and
# the step reads the rows `sparse_select` kept of the latent pool
# (`kernels.paged_attention.paged_sparse_latent_attention`).
# ---------------------------------------------------------------------------

def _latent_dims(attrs):
    return (int(attrs["num_heads"]), int(attrs["kv_lora_rank"]),
            int(attrs["qk_nope_head_dim"]), int(attrs["qk_rope_head_dim"]),
            int(attrs["v_head_dim"]))


def _latent_q(source, w, positions, attrs, precision=None):
    """source [B, S, .] @ w [., h (nope + rope)] -> (q_nope [B, S, h,
    nope], q_rope [B, S, h, rope] rotated), h the heads `w` holds (all of
    them, or a prefill's group)."""
    _, _, nope, rope, _ = _latent_dims(attrs)
    q = _columns_dot(source, w, precision) if precision is not None \
        else jnp.dot(source, w.astype(source.dtype))
    q = q.reshape(source.shape[:2] + (-1, nope + rope))
    return q[..., :nope], rope_rotate(
        q[..., nope:], positions, float(attrs["rope_theta"]),
        bool(attrs["rope_interleave"]))


def _latent_project(x, ins, positions, attrs, with_q=True):
    """x [B, S, d] -> q_nope [B, S, H, nope], q_rope [B, S, H, rope]
    rotated (None unless `with_q`), the latent row [B, S, rank + rope] =
    normed c | rotated k_rope: what a cache holds of a token, and the
    query's normed low-rank cq [B, S, q_rank] (None without `Wqa`: a
    full-rank Wq). The low-rank's two products run at `_CHOOSING`: the
    indexer reads cq, and what a row reads is decided there."""
    heads, rank, nope, rope, _ = _latent_dims(attrs)
    theta = float(attrs["rope_theta"])
    inter = bool(attrs["rope_interleave"])
    eps = float(attrs["epsilon"])
    cq = q_nope = q_rope = None
    if ins.get("Wqa"):
        cq = _rms_over_last(_columns_dot(x, ins["Wqa"][0], _CHOOSING),
                            ins["QNorm"][0], eps)
        if with_q:
            q_nope, q_rope = _latent_q(cq, ins["Wqb"][0], positions, attrs,
                                       _CHOOSING)
    elif with_q:
        q_nope, q_rope = _latent_q(x, ins["Wq"][0], positions, attrs)
    kva = jnp.dot(x, ins["Wkva"][0].astype(x.dtype))
    cf = kva[..., :rank].astype(jnp.float32)
    c = (cf * jax.lax.rsqrt(jnp.mean(jnp.square(cf), axis=-1,
                                     keepdims=True) + eps)
         * ins["KvNorm"][0].astype(jnp.float32)).astype(x.dtype)
    k_rope = rope_rotate(kva[..., None, rank:], positions, theta,
                         inter)[..., 0, :]
    return q_nope, q_rope, jnp.concatenate([c, k_rope], axis=-1), cq


def _latent_index(x, cq, ins, positions, attrs):
    """The latent block's indexer (`_indexer`), reading the query's
    low-rank; None without one."""
    if not int(attrs.get("index_topk", 0)):
        return None
    return _indexer(x, cq, ins, positions, attrs)


def _latent_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    rows = {"Latent": int(op.attrs["kv_lora_rank"])
            + int(op.attrs["qk_rope_head_dim"]),
            "IndexK": int(op.attrs.get("index_head_dim", 0))}
    for role, width in rows.items():
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = tuple(x.shape[:-1]) + (width,), x.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        var.shape = tuple(x.shape[:-1]) + (-(-int(x.shape[1]) // 32),)
        var.dtype = "int32"


#: bytes of a chunk's index products ([Hi, rows, T] float32) past which a
#: latent prefill's selection takes fewer query rows a chunk than
#: `_INDEX_Q_CHUNK`: 32 heads over 12,288 keys are 805 MB at 512 rows
_INDEX_DOTS_BYTES = 288 << 20


def _index_chunk(t, index_heads):
    """Query rows a chunk of a latent prefill's selection: the largest of
    512 .. 128 whose index products stay under `_INDEX_DOTS_BYTES` (at
    12,288 keys 128 rows; 64 put the bucket's temporaries 240 MB
    HIGHER by the compiler's count, PR 65)."""
    for rows in (_INDEX_Q_CHUNK, 256, 128):
        if index_heads * rows * t * 4 <= _INDEX_DOTS_BYTES:
            return rows
    return 128


def _latent_selected_attention(x, ins, latent, cq, q, index, attrs,
                               want_mask):
    """`_attend_selected` for latent attention: out [B, T, d] (after Wo)
    and the packed selections. Off the chip K and V are expanded whole
    and a chunk's masked scores are dense (the oracle of the form below;
    `q` = (q_nope, q_rope) whole). On the chip (`q` None) the selection
    leaves its loop as one byte a (row, key), and the heads go a GROUP at
    a time through the flash forward over that selection's tiles: a
    group's q is projected, its K and V expanded from the latent rows,
    attended and projected back through its rows of Wo inside one loop,
    so that no [T, H, nope + rope] array is ever whole (64 heads of 256
    over 12,288 rows are 0.8 GB each for q, K and V). Masked dense tiles,
    expanded: a block the selection leaves empty is still multiplied."""
    from ..kernels.flash_attention import (attention_form,
                                           dot_product_attention)
    heads, rank, nope, rope, vdim = _latent_dims(attrs)
    b, t, d = x.shape
    topk = int(attrs["index_topk"])
    scale = 1.0 / float(nope + rope) ** 0.5
    wkvb = ins["Wkvb"][0].astype(x.dtype).reshape(rank, heads, nope + vdim)
    wo = ins["Wo"][0].astype(x.dtype)
    c, k_rope = latent[..., :rank], latent[..., rank:]
    in_tiles = q is None

    def expanded(w):       # the latent rows through `w` [rank, h, n + v]
        kv = jnp.einsum("btr,rhn->bthn", c, w)
        return (jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope[:, :, None], kv.shape[:3] + (rope,))], axis=-1),
            kv[..., nope:])

    if not in_tiles:
        k, v = expanded(wkvb)

    def dense(qc, mask):
        s = jnp.einsum("bqhd,bkhd->bhqk", jnp.concatenate(qc, axis=-1), k,
                       precision=_CHOOSING,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", p, v)

    # heads a group: its q under `_Q_CHUNK_BYTES`
    group = heads
    while group > 1 and (t * group * (nope + rope) * x.dtype.itemsize
                         > _Q_CHUNK_BYTES or heads % group):
        group -= 1
    q_from, wq, precision = (cq, ins["Wqb"][0], _CHOOSING) \
        if cq is not None else (x, ins["Wq"][0], None)
    positions = jnp.arange(t, dtype=jnp.int32)

    def tiles(selected):
        def one(g, out):
            first = g * group
            qn, qr = _latent_q(q_from, jax.lax.dynamic_slice_in_dim(
                wq, first * (nope + rope), group * (nope + rope), axis=1),
                positions, attrs, precision)
            kg, vg = expanded(jax.lax.dynamic_slice_in_dim(
                wkvb, first, group, axis=1))
            o = dot_product_attention(
                jnp.concatenate([qn, qr], axis=-1), kg, vg, causal=True,
                scale=scale, selected=selected,
                block=512 if nope + rope > 128 else None)
            return out + jnp.dot(
                o.reshape(b, t, group * vdim),
                jax.lax.dynamic_slice_in_dim(wo, first * vdim,
                                             group * vdim, axis=0))

        return jax.lax.fori_loop(0, heads // group, one,
                                 jnp.zeros((b, t, d), x.dtype))

    out, packed = _attend_selected(
        q, index, topk, want_mask, in_tiles=in_tiles, dense=dense,
        tiles=tiles, chunk=_index_chunk(t, int(attrs["index_heads"])))
    if not in_tiles:
        out = jnp.dot(out.reshape(b, t, heads * vdim), wo)
    return out, packed


@register_op("latent_attention", infer_shape=_latent_infer)
def latent_attention(ctx, ins, attrs):
    """Causal latent attention over whole sequences, expanded: X
    [B, S, d]; Wq [d, H (nope + rope)] (or Wqa [d, q_rank], QNorm
    [q_rank], Wqb [q_rank, H (nope + rope)]: a query low-rank); Wkva
    [d, rank + rope]; KvNorm [rank]; Wkvb [rank, H (nope + v)]; Wo
    [H v, d]; with an indexer WIq [q_rank, Hi Di], WIk [d, Di], WIw
    [d, Hi], IKNormScale, IKNormBias [Di] -> Out [B, S, d], Latent
    [B, S, rank + rope], each token's cache row, IndexK [B, S, Di] and,
    where the op has the output, Selected [B, S, ceil(S / 32)] int32
    (`pack_mask`). Positions are 0..S-1. Without an indexer, and with one
    while S <= index_topk, the attention itself is
    `dot_product_attention` (the flash forward kernel on a TPU, with a V
    width of its own); past that the selection prunes
    (`_latent_selected_attention`)."""
    from ..kernels.flash_attention import (attention_form,
                                           dot_product_attention)

    if ctx is not None and getattr(ctx, "mesh", None) is not None \
            and ctx.mesh.size > 1:
        raise NotImplementedError("latent attention on a mesh of several "
                                  "chips is not built")
    x = ins["X"][0]
    heads, rank, nope, rope, vdim = _latent_dims(attrs)
    seq = x.shape[1]
    topk = int(attrs.get("index_topk", 0))
    positions = jnp.arange(seq, dtype=jnp.int32)
    prunes = topk > 0 and seq > topk
    in_tiles = prunes and attention_form(
        seq, seq, nope + rope, True) == "flash_selected"
    q_nope, q_rope, latent, cq = _latent_project(
        x, ins, positions, attrs, with_q=not in_tiles)
    index = _latent_index(x, cq, ins, positions, attrs)
    want_mask = bool(attrs.get("return_selected", False))
    outs = {"Latent": [latent]}
    if index is not None:
        outs["IndexK"] = [index[1]]
    if prunes:
        out, selected = _latent_selected_attention(
            x, ins, latent, cq, None if in_tiles else (q_nope, q_rope),
            index, attrs, want_mask)
        outs["Out"] = [out]
        if selected is not None:
            outs["Selected"] = [selected]
        return outs
    kv = jnp.dot(latent[..., :rank], ins["Wkvb"][0].astype(x.dtype)
                 ).reshape(x.shape[:2] + (heads, nope + vdim))
    k_rope = jnp.broadcast_to(latent[..., None, rank:],
                              x.shape[:2] + (heads, rope))
    out = dot_product_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1),
        jnp.concatenate([kv[..., :nope], k_rope], axis=-1),
        kv[..., nope:], causal=True)
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_attn_out")
    merged = out.reshape(x.shape[:2] + (heads * vdim,))
    outs["Out"] = [jnp.dot(merged, ins["Wo"][0].astype(x.dtype))]
    if index is not None and want_mask:
        outs["Selected"] = [_causal_selection(x.shape[0], seq)]
    return outs


def _latent_decode_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    for pool_in, pool_out in (("Pool", "PoolOut"),
                              ("IndexPool", "IndexOut")):
        if op.output(pool_out):
            src = block.var(op.input(pool_in)[0])
            dst = block.var(op.output(pool_out)[0])
            dst.shape, dst.dtype = src.shape, src.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        var.shape = (x.shape[0], int(op.attrs["index_topk"]))
        var.dtype = "int32"


@register_op("latent_decode_attention", infer_shape=_latent_decode_infer)
def latent_decode_attention(ctx, ins, attrs):
    """One new token a slot, absorbed: X [S, 1, d], the weights of
    `latent_attention`, Pool [NB, BS, W] (W >= rank + rope: columns
    past them are padding and hold zeros; [NB, BS, 1, W] with an
    indexer), Positions [S, 1],
    BlockTables, ContextLens (the span INCLUDING the new token) ->
    Out [S, 1, d], PoolOut (the pool with each slot's new row written).
    With an indexer also IndexPool [NB, BS, Wi] (Wi >= Di: zeros past
    it) -> IndexOut, and Selected [S, index_topk] int32: the positions
    each slot attended to, highest indexer score first, -1 behind
    min(length, index_topk); the step then reads those rows of the
    latent pool alone. Pallas kernels on a TPU, the gather references
    elsewhere (kernels/paged_attention.py)."""
    from ..kernels import paged_attention as pa

    x, pool = ins["X"][0], ins["Pool"][0]
    tables, lens = ins["BlockTables"][0], ins["ContextLens"][0]
    heads, rank, nope, rope, vdim = _latent_dims(attrs)
    q_nope, q_rope, row, cq = _latent_project(
        x, ins, ins["Positions"][0], attrs)
    index = _latent_index(x, cq, ins, ins["Positions"][0], attrs)
    q_nope, q_rope, row = q_nope[:, 0], q_rope[:, 0], row[:, 0]
    pad = pool.shape[-1] - (rank + rope)
    pool = pa.paged_row_update(
        pool, jnp.pad(row, ((0, 0), (0, pad))).reshape(
            row.shape[:1] + pool.shape[2:]), tables, lens)
    wkvb = ins["Wkvb"][0].astype(x.dtype).reshape(rank, heads,
                                                  nope + vdim)
    with jax.named_scope("latent_absorb"):
        q_lat = jnp.einsum("shn,rhn->shr", q_nope, wkvb[..., :nope])
    q_full = jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                     ((0, 0), (0, 0), (0, pad)))
    scale = 1.0 / float(nope + rope) ** 0.5
    outs = {}
    if index is None:
        u = pa.paged_latent_decode_attention(
            q_full, pool, tables, lens, value_width=rank, scale=scale)
    else:
        qi, ki, w = index
        ipool = ins["IndexPool"][0]
        wide = (0, ipool.shape[-1] - ki.shape[-1])  # the row's lane tiles
        ipool = pa.paged_row_update(
            ipool, jnp.pad(ki[:, 0], ((0, 0), wide)), tables, lens)
        scores = pa.paged_index_scores(
            jnp.pad(qi[:, 0], ((0, 0), (0, 0), wide)), w[:, 0], ipool,
            tables, lens)
        positions, rows, counts, _ = pa.sparse_select(
            scores, tables, lens, topk=int(attrs["index_topk"]),
            block_size=ipool.shape[1])
        u = pa.paged_sparse_latent_attention(
            q_full, pool, rows, counts, value_width=rank, scale=scale)
        outs.update(IndexOut=[ipool], Selected=[positions])
    with jax.named_scope("latent_absorb"):
        o = jnp.einsum("shr,rhv->shv", u, wkvb[..., nope:])
    out = jnp.dot(o.reshape(o.shape[0], heads * vdim),
                  ins["Wo"][0].astype(x.dtype))
    outs.update(Out=[out[:, None]], PoolOut=[pool])
    return outs


# ---------------------------------------------------------------------------
# Grouped-query attention, with or without a sparse-attention indexer
# (Qwen3's attention; DeepSeek-V3.2's indexer as Keye-VL-2.0 applies it).
# x [.., d] is the block's normed input, no bias anywhere:
#
#   q = x Wq -> [.., H, D];  k = x Wk, v = x Wv -> [.., H_kv, D]
#   q, k: RMS norm over each head's D (one gain [D] for q, one for k;
#   or none), then RoPE over all D (`rotary`: "half", pairs (i, i + D/2);
#   "interleave", pairs (2i, 2i + 1); "none", no positions at all); query
#   head j reads K/V head j // (H / H_kv); scale D^-1/2; row t reads
#   every s <= t, or with `window` W only those with t - s < W;
#   out = concat(heads) Wo.
#   The indexer (index_topk > 0): qI = x WIq -> [.., Hi, Di] and
#   kI = LayerNorm(x WIk) -> [.., Di], both rotated the same way;
#   w = x WIw -> [.., Hi];
#     I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]),
#   S_t = the index_topk positions s <= t of largest I[t, s] (all of them
#   while t < index_topk; of equal scores the lower position), and the
#   softmax of row t runs over S_t alone.
#
# Two ops, one set of weights and one `_grouped_project`:
# `grouped_attention` over whole sequences (a prefill, a trainer) and
# `grouped_decode_attention`, one new token a slot against paged pools of
# K, V and, with an indexer, index keys.
# ---------------------------------------------------------------------------

#: The precision of the products that CHOOSE, or whose error a softmax
#: multiplies: the q and k projections, the attention's scores, and all
#: of the indexer. A head's score error enters its softmax multiplied by
#: the score's own size, and an index score decides whether a row is read
#: at all, so operands rounded to bfloat16 (what an f32 matmul is at the
#: TPU's default precision) cost these more than any other product of the
#: layer; three bfloat16 passes put them beside the router, which
#: `moe_gated_ffn` runs at the highest precision for the same reason.
#: Values, the output projection and the experts keep the default.
_CHOOSING = jax.lax.Precision.HIGH


def _grouped_dims(attrs):
    return (int(attrs["num_heads"]), int(attrs["num_kv_heads"]),
            int(attrs["head_dim"]), int(attrs["index_heads"]),
            int(attrs["index_head_dim"]), int(attrs["index_topk"]))


def _score_scale(attrs):
    """What the scores are multiplied by: the configuration's constant
    (`scale`) where it has one, else None, the kernels' own
    1 / sqrt(head_dim)."""
    return float(attrs["scale"]) if attrs.get("scale") else None


def _rope_scaling(attrs):
    """The layer's YaRN parameters (`rope_table`); (): plain RoPE."""
    return tuple(attrs.get("rope_scaling", ()))


def _rms_over_last(x, gain, eps):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                                        keepdims=True) + eps)
            * gain.astype(jnp.float32)).astype(x.dtype)


#: columns of a wide weight a multi-pass product takes at a time
_PASS_COLUMNS = 512


def _columns_dot(x, w, precision):
    """x [B, S, d] @ w [d, n]. At more than one pass a wide weight goes
    `_PASS_COLUMNS` columns at a time: its bfloat16 pieces depend on
    nothing but the weight, so the compiler makes them of the whole of it
    ahead of time and keeps them (75 MB over a 6,144 bucket's peak); of a
    slice chosen inside a loop it cannot. A decode step's product (one
    row a slot) is kept from the reshape into heads that follows it: else
    the compiler carries the heads' layout back into the WEIGHT, which is
    an argument of the step, and copies all of it transposed every step
    (268 MB a layer at 128 heads of 128: 2 GB and 3.4 ms a step of the
    four-layer cell on the chip, PERF.md section 6 PR 40). A weight
    STORED narrower than x (a bundle's bfloat16 matrices under float32
    rows) is cast where it is multiplied, a slice at a time inside the
    loop: cast ahead of the loop the whole of it would be a float32 copy
    in memory."""
    n = w.shape[1]
    if x.shape[1] == 1:
        return jax.lax.optimization_barrier(
            jnp.dot(x, w.astype(x.dtype), precision=precision))
    if precision is None or n <= _PASS_COLUMNS or n % _PASS_COLUMNS:
        return jnp.dot(x, w.astype(x.dtype), precision=precision)

    def block(i, out):
        cols = jax.lax.dynamic_slice_in_dim(w, i * _PASS_COLUMNS,
                                            _PASS_COLUMNS, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.dot(x, cols.astype(x.dtype), precision=precision),
            i * _PASS_COLUMNS, axis=2)

    return jax.lax.fori_loop(0, n // _PASS_COLUMNS, block,
                             jnp.zeros(x.shape[:2] + (n,), x.dtype))


def _grouped_q(x, ins, positions, attrs):
    """x [B, S, d] -> q [B, S, H, D], normed and rotated."""
    heads, _, hd, _, _, _ = _grouped_dims(attrs)
    q = _columns_dot(x, ins["Wq"][0],
                     _CHOOSING).reshape(x.shape[:2] + (heads, hd))
    if ins.get("QNorm"):
        q = _rms_over_last(q, ins["QNorm"][0], float(attrs["epsilon"]))
    rotary = attrs.get("rotary", "half")
    if rotary == "none":
        return q
    return rope_rotate(q, positions, float(attrs["rope_theta"]),
                       rotary == "interleave", _rope_scaling(attrs))


def _grouped_project(x, ins, positions, attrs, with_q=True):
    """x [B, S, d] -> q [B, S, H, D] (None unless `with_q`) and k, v
    [B, S, H_kv, D], normed and rotated as the text above says, and the
    indexer's (qI [B, S, Hi, Di], kI [B, S, Di], w [B, S, Hi]), or None
    without one."""
    heads, kv_heads, hd, ih, idim, topk = _grouped_dims(attrs)
    theta, eps = float(attrs["rope_theta"]), float(attrs["epsilon"])

    def proj(name, *shape, precision=_CHOOSING):
        return _columns_dot(x, ins[name][0],
                            precision).reshape(x.shape[:2] + shape)

    q = _grouped_q(x, ins, positions, attrs) if with_q else None
    k = proj("Wk", kv_heads, hd)
    if ins.get("KNorm"):
        k = _rms_over_last(k, ins["KNorm"][0], eps)
    rotary = attrs.get("rotary", "half")
    if rotary != "none":
        k = rope_rotate(k, positions, theta, rotary == "interleave",
                        _rope_scaling(attrs))
    v = proj("Wv", kv_heads, hd, precision=None)
    if not topk:
        return q, k, v, None
    return q, k, v, _indexer(x, x, ins, positions, attrs)


def _indexer(x, q_from, ins, positions, attrs):
    """The indexer's three projections, ONE piece for both attentions
    that select: (qI [B, S, Hi, Di], kI [B, S, Di], w [B, S, Hi]), all at
    `_CHOOSING`. kI = LayerNorm(x WIk) with a gain and a bias and w =
    x WIw read the block's normed input; qI is projected from `q_from`,
    that same input (grouped-query attention: Keye's) or the query's
    low-rank (latent attention: DeepSeek-V3.2's, GLM-5's). Both are
    rotated at the block's theta: over their whole width, rotate-half,
    or where the attributes say so over the first `index_rope_dim`
    alone (`index_rope_interleave`: pairs (2i, 2i + 1)), the rest as it
    is. `index_epsilon`: the LayerNorm's (the block's `epsilon` unless
    said); `index_weight_scale`: what w is multiplied by (a positive
    constant changes no selection; a published description states
    one)."""
    ih, idim = int(attrs["index_heads"]), int(attrs["index_head_dim"])
    theta = float(attrs["rope_theta"])
    eps = float(attrs.get("index_epsilon", attrs["epsilon"]))
    turned = int(attrs.get("index_rope_dim", 0)) or idim
    inter = bool(attrs.get("index_rope_interleave", False))

    def proj(source, name, *shape):
        return _columns_dot(source, ins[name][0],
                            _CHOOSING).reshape(source.shape[:2] + shape)

    def rotated(heads):            # [B, S, h, Di]
        if turned == idim:
            return rope_rotate(heads, positions, theta, inter)
        return jnp.concatenate([
            rope_rotate(heads[..., :turned], positions, theta, inter),
            heads[..., turned:]], axis=-1)

    ki = proj(x, "WIk", idim).astype(jnp.float32)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    ki = ((ki - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True) + eps)
        * ins["IKNormScale"][0].astype(jnp.float32)
        + ins["IKNormBias"][0].astype(jnp.float32)).astype(x.dtype)
    ki = rotated(ki[..., None, :])[..., 0, :]
    qi = rotated(proj(q_from, "WIq", ih, idim))
    w = proj(x, "WIw", ih)
    if attrs.get("index_weight_scale"):
        w = w * float(attrs["index_weight_scale"])
    return qi, ki, w


#: bytes of a bucket's q projection past which a prefill projects,
#: attends and projects back its query rows a chunk at a time (K and V,
#: of the few heads groups share, stay whole): 128 query heads of 128
#: are 64 KB a row, 403 MB at 6,144 rows, beside as much again for the
#: heads' transposes and for the output
_Q_CHUNK_BYTES = 64 << 20


def _query_chunk(seq, row_bytes):
    """Query rows a chunk: all of them while the projection is under
    `_Q_CHUNK_BYTES`, else the largest of 2,048 .. 128 that divides the
    sequence and keeps a chunk under it."""
    if seq * row_bytes <= _Q_CHUNK_BYTES:
        return seq
    for rows in (2048, 1024, 512, 256, 128):
        if seq % rows == 0 and rows * row_bytes <= _Q_CHUNK_BYTES:
            return rows
    return seq


def _chunked_causal_attention(x, ins, k, v, attrs, rows):
    """out [B, S, d] of causal (windowed) grouped attention with the
    query rows taken `rows` at a time: a chunk's q is projected, attends
    the keys up to its last row (from the first block of 1,024 its
    window reaches) with K and V as they are, H_kv heads, and is
    projected back through Wo. A Python loop: each chunk has its own key
    range, so its own shapes."""
    from ..kernels.flash_attention import dot_product_attention
    heads, _, hd, _, _, _ = _grouped_dims(attrs)
    window = int(attrs.get("window", 0)) or None
    wo = ins["Wo"][0].astype(x.dtype)
    outs = []
    for start in range(0, x.shape[1], rows):
        end = start + rows
        q = _grouped_q(x[:, start:end], ins,
                       jnp.arange(start, end, dtype=jnp.int32), attrs)
        lo = 0 if window is None else \
            max(0, (start - window + 1) // 1024 * 1024)
        o = dot_product_attention(q, k[:, lo:end], v[:, lo:end],
                                  causal=True, window=window,
                                  scale=_score_scale(attrs))
        outs.append(jnp.dot(o.reshape(o.shape[:2] + (heads * hd,)), wo))
    return jnp.concatenate(outs, axis=1)


#: query rows the indexed prefill scores, selects and attends at a time:
#: `sa_config.q_chunk_size` of the source; a tiling, it changes no result
_INDEX_Q_CHUNK = 512


#: compare-and-count passes `_kth_largest` makes over a row: one a bit
_SEARCH_PASSES = 32


def _kth_largest(scores, k):
    """float32 [.., T] (never NaN) -> [.., 1]: each row's `k`-th largest
    value, what `top_k(scores, k)[0][..., -1:]` is, with no sort. The
    scores are mapped to their order-preserving unsigned image (a
    negative float's bits flipped whole, the sign bit set on the others:
    integer order is float order, -inf the least; 0.0 and -0.0, equal as
    floats, made one zero first) and the largest v with count(image >=
    v) >= k is built bit by bit from the top: `_SEARCH_PASSES`
    compare-and-counts over a row, then mapped back."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.uint32)
    top = jnp.uint32(1 << 31)
    image = jnp.where(bits >= top, ~bits, bits | top)

    def bit(i, v):
        trial = v | (top >> i.astype(jnp.uint32))
        enough = jnp.sum(image >= trial, axis=-1, keepdims=True,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, trial, v)

    v = jax.lax.fori_loop(0, _SEARCH_PASSES, bit,
                          jnp.zeros(scores.shape[:-1] + (1,), jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(v >= top, v ^ top, ~v), jnp.float32)


def _selected_mask(scores, topk):
    """scores [.., T] (-inf where a position may not be read) -> bool
    [.., T]: the `topk` highest of each row, of equal scores the lower
    position first (what `top_k`'s indices are, as a mask and with no
    scatter and no sort: everything above the topk-th value, and of its
    equals the first few)."""
    kth = _kth_largest(scores, topk)
    above = scores > kth
    equal = scores == kth
    room = topk - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= room))


def _causal_selection(batch, seq):
    """What `Selected` says where no row prunes: every row's causal
    positions, packed ([batch, seq, ceil(seq / 32)] int32)."""
    return jnp.broadcast_to(
        pack_mask(jnp.tril(jnp.ones((seq, seq), bool)))[None],
        (batch, seq, -(-seq // 32)))


def pack_mask(mask):
    """bool [.., T] -> int32 [.., ceil(T / 32)]: position s is bit s % 32
    of word s // 32 (`unpack_mask` is its inverse, on the host)."""
    t = mask.shape[-1]
    words = -(-t // 32)
    bits = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, words * 32 - t)])
    bits = bits.reshape(mask.shape[:-1] + (words, 32)).astype(jnp.uint32)
    packed = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def unpack_mask(packed, width):
    """`pack_mask`'s inverse on the host: int32 [.., W] (numpy) -> bool
    [.., width]."""
    import numpy as np
    words = np.ascontiguousarray(packed).astype("<i4")
    bits = np.unpackbits(words.view(np.uint8), axis=-1, bitorder="little")
    return bits[..., :width].astype(bool)


def _select_plan(t, chunk, topk):
    """Which chunks of `chunk` query rows of a sequence of `t` the
    indexed prefill searches for their `topk` keys: not the first
    `topk // chunk`, whose last row sees no more than `topk` keys, so
    every row of them selects all it may read. Static, from the shapes
    alone; left in the trace ring (`kernel/select_plan`) each time
    `_indexed_causal_attention` is traced."""
    unsearched = min(topk // chunk, t // chunk)
    return dict(t=t, chunk=chunk, topk=topk, chunks_unsearched=unsearched,
                chunks_searched=t // chunk - unsearched,
                passes=_SEARCH_PASSES)


def _attend_selected(queries, index, topk, want_mask, *, in_tiles, dense,
                     tiles, chunk=0):
    """Causal attention of whole sequences with row t's softmax over its
    selected positions alone, for any attention that selects. What
    CHOOSES runs in chunks of query rows (the indexer's product,
    `_selected_mask`): no [Hi, T, T] array is ever whole; the chunks
    that have nothing to choose (`_select_plan`) run neither, their
    selection is the causal mask. What ATTENDS is the caller's, one of
    two forms of the same sums: `in_tiles`, each chunk's selection
    leaves the loop as a mask of one byte a (row, key) and
    `tiles(selected [B, T, T] int8)` attends all of it at once (on the
    chip: the flash forward, a tile a block); else `dense(a chunk of
    `queries`, mask [B, chunk, T])` inside the loop, the chunk's scores
    whole (off the chip; the kernel's oracle). `queries`: a pytree of
    [B, T, ...] arrays, split by chunk for `dense` (None in tiles).
    `chunk`: the query rows a chunk (0: `_INDEX_Q_CHUNK`). Returns (the
    attention's output, joined over the chunks or `tiles`' own; every
    row's selected positions as `pack_mask` packs them, [B, T,
    ceil(T / 32)] int32, or None unless `want_mask`)."""
    qi, ki, w = index
    b, t = qi.shape[:2]
    chunk = math.gcd(t, chunk or _INDEX_Q_CHUNK)
    n_chunks = t // chunk
    plan = _select_plan(t, chunk, topk)
    obs_trace.phase("kernel", "select_plan", 0.0, attrs=plan)
    kpos = jnp.arange(t, dtype=jnp.int32)

    def split(x):          # [B, T, ...] -> [n_chunks, B, chunk, ...]
        return jnp.moveaxis(
            x.reshape((b, n_chunks, chunk) + x.shape[2:]), 1, 0)

    def join(x):           # its inverse
        return jnp.moveaxis(x, 0, 1).reshape((b, t) + x.shape[3:])

    def one(searched, xs):
        start, qc, qic, wc = xs
        rows = start + jnp.arange(chunk, dtype=jnp.int32)
        causal = kpos[None] <= rows[:, None]                # [chunk, T]
        mask = jnp.broadcast_to(causal[None], (b, chunk, t))
        if searched:
            with jax.named_scope("prefill_index_scores"):
                dots = jnp.einsum("bqhd,bkd->bhqk", qic, ki,
                                  precision=_CHOOSING,
                                  preferred_element_type=jnp.float32)
                score = jnp.einsum("bqh,bhqk->bqk", wc.astype(jnp.float32),
                                   jnp.maximum(dots, 0.0),
                                   precision=_CHOOSING)
            score = jnp.where(mask, score, -jnp.inf)
            mask = _selected_mask(score, topk) & mask       # [B, chunk, T]
        packed = pack_mask(mask) if want_mask else None
        if in_tiles:
            return mask.astype(jnp.int8), packed
        return dense(qc, mask), packed

    # two loops of the one body: the chunks with nothing to choose, then
    # the searched ones (which they are is static: no `lax.cond`)
    xs = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk,
          None if in_tiles else jax.tree.map(split, queries),
          split(qi), split(w))

    def walk(searched, lo, hi):
        return jax.lax.scan(lambda _, x: (None, one(searched, x)), None,
                            jax.tree.map(lambda x: x[lo:hi], xs))[1]

    first = plan["chunks_unsearched"]
    parts = [walk(searched, lo, hi) for searched, lo, hi in
             ((False, 0, first), (True, first, n_chunks)) if lo < hi]
    outs, packed = jax.tree.map(lambda *x: jnp.concatenate(x), *parts)
    with jax.named_scope("prefill_selected_attention"):
        out = tiles(join(outs)) if in_tiles else join(outs)
    return out, None if packed is None else join(packed)


def _indexed_causal_attention(q, k, v, index, topk, scale, want_mask):
    """`_attend_selected` for grouped-query attention: on the chip ONE
    call of the flash forward takes the selection a tile a block (the
    scores stay in VMEM, K and V unrepeated, a block wholly above the
    diagonal runs nothing:
    `kernels.flash_attention.attention_form`); elsewhere masked dense
    products inside the loop. Returns (out [B, T, H, D], the packed
    selections or None)."""
    from ..kernels.flash_attention import (attention_form,
                                           dot_product_attention)
    b, t, heads, hd = q.shape
    kv_heads = k.shape[2]

    def dense(qc, mask):
        s = jnp.einsum("bqgid,bkgd->bgiqk", qc, k, precision=_CHOOSING,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bgiqk,bkgd->bqgid", p, v)

    out, packed = _attend_selected(
        q.reshape(b, t, kv_heads, heads // kv_heads, hd), index, topk,
        want_mask, in_tiles=attention_form(t, t, hd, True)
        == "flash_selected", dense=dense,
        tiles=lambda selected: dot_product_attention(
            q, k, v, causal=True, scale=scale, selected=selected))
    return out.reshape(b, t, heads, hd), packed


def _grouped_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    kv = (int(op.attrs["num_kv_heads"]), int(op.attrs["head_dim"]))
    for role, row in (("K", kv), ("V", kv),
                      ("IndexK", (int(op.attrs["index_head_dim"]),))):
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = tuple(x.shape[:-1]) + row, x.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        var.shape = tuple(x.shape[:-1]) + (-(-int(x.shape[1]) // 32),)
        var.dtype = "int32"


@register_op("grouped_attention", infer_shape=_grouped_infer)
def grouped_attention(ctx, ins, attrs):
    """Causal grouped-query attention over whole sequences at positions
    0..S-1 (the text above): X [B, S, d]; Wq [d, H D]; Wk, Wv [d, H_kv
    D]; Wo [H D, d]; QNorm, KNorm [D] (optional, together); with an
    indexer WIq [d, Hi Di], WIk [d, Di], WIw [d, Hi], IKNormScale,
    IKNormBias [Di] -> Out [B, S, d], K and V [B, S, H_kv, D] (a cache's
    rows: K normed and rotated) and IndexK [B, S, Di], and where the op
    has the output, Selected [B, S, ceil(S / 32)] int32: the positions
    every row attended to, one bit a position (`pack_mask`).

    Without an indexer, and with one while S <= index_topk (every row
    then selects all it may read), the attention is
    `dot_product_attention` with the K/V heads repeated up to the query
    heads' count (the flash kernels on a TPU; with `window` their
    window band). Past that the selection prunes:
    `_indexed_causal_attention` (on a TPU the flash forward over the
    selection's tiles)."""
    from ..kernels.flash_attention import dot_product_attention

    if ctx is not None and getattr(ctx, "mesh", None) is not None \
            and ctx.mesh.size > 1:
        raise NotImplementedError("grouped-query attention on a mesh of "
                                  "several chips is not built")
    x = ins["X"][0]
    heads, kv_heads, hd, _, _, topk = _grouped_dims(attrs)
    seq = x.shape[1]
    positions = jnp.arange(seq, dtype=jnp.int32)
    rows = _query_chunk(seq, heads * hd * x.dtype.itemsize)
    if not topk and (rows < seq or attrs.get("window")):
        # K and V never repeated, the query rows a chunk at a time
        _, k, v, _ = _grouped_project(x, ins, positions, attrs,
                                      with_q=False)
        return {"Out": [_chunked_causal_attention(x, ins, k, v, attrs,
                                                  rows)],
                "K": [k], "V": [v]}
    q, k, v, index = _grouped_project(x, ins, positions, attrs)
    want_mask = bool(attrs.get("return_selected", False))
    selected = None
    if index is None or seq <= topk:
        group = heads // kv_heads
        out = dot_product_attention(q, jnp.repeat(k, group, axis=2),
                                    jnp.repeat(v, group, axis=2),
                                    causal=True, scale=_score_scale(attrs))
        from jax.ad_checkpoint import checkpoint_name
        out = checkpoint_name(out, "flash_attn_out")
        if want_mask:
            selected = _causal_selection(x.shape[0], seq)
    else:
        out, selected = _indexed_causal_attention(
            q, k, v, index, topk, 1.0 / float(hd) ** 0.5, want_mask)
    outs = {"Out": [jnp.dot(out.reshape(x.shape[:2] + (heads * hd,)),
                            ins["Wo"][0].astype(x.dtype))],
            "K": [k], "V": [v]}
    if index is not None:
        outs["IndexK"] = [index[1]]
    if selected is not None:
        outs["Selected"] = [selected]
    return outs


def _grouped_decode_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    for pool_in, pool_out in (("KPool", "KOut"), ("VPool", "VOut"),
                              ("IndexPool", "IndexOut")):
        if op.output(pool_out):
            src = block.var(op.input(pool_in)[0])
            dst = block.var(op.output(pool_out)[0])
            dst.shape, dst.dtype = src.shape, src.dtype
    if op.output("Selected"):
        var = block.var(op.output("Selected")[0])
        var.shape = (x.shape[0], int(op.attrs["index_topk"]))
        var.dtype = "int32"


@register_op("grouped_decode_attention", infer_shape=_grouped_decode_infer)
def grouped_decode_attention(ctx, ins, attrs):
    """One new token a slot: X [S, 1, d], the weights of
    `grouped_attention`, KPool and VPool [NB, BS, H_kv, D], Positions
    [S, 1] (absent where no layer rotates), BlockTables, ContextLens (the span INCLUDING the new token)
    -> Out [S, 1, d], KOut, VOut (the pools with each slot's new row
    written). With an indexer also IndexPool [NB, BS, W] (W >= Di:
    columns past it hold zeros) -> IndexOut, and Selected [S,
    index_topk] int32: the positions each slot attended to, highest
    indexer score first, -1 behind min(length, index_topk). With
    `window` the slot reads its newest `window` rows alone, through a
    table whose entries behind the window nothing reads. Pallas
    kernels on a TPU, the gather references elsewhere
    (kernels/paged_attention.py)."""
    from ..kernels import paged_attention as pa

    x = ins["X"][0]
    tables, lens = ins["BlockTables"][0], ins["ContextLens"][0]
    heads, _, hd, _, idim, topk = _grouped_dims(attrs)
    positions = ins["Positions"][0] if ins.get("Positions") else None
    q, k, v, index = _grouped_project(x, ins, positions, attrs)
    k_pool, v_pool = pa.paged_kv_update(
        ins["KPool"][0], ins["VPool"][0], k[:, 0], v[:, 0], tables, lens)
    outs = {"KOut": [k_pool], "VOut": [v_pool]}
    if index is None:
        o = pa.paged_decode_attention(
            q[:, 0], k_pool, v_pool, tables, lens,
            window=int(attrs.get("window", 0)) or None,
            scale=_score_scale(attrs))
    else:
        qi, ki, w = index
        pool = ins["IndexPool"][0]
        wide = (0, pool.shape[-1] - idim)   # the row's lane tiles
        pool = pa.paged_row_update(
            pool, jnp.pad(ki[:, 0], ((0, 0), wide)), tables, lens)
        scores = pa.paged_index_scores(
            jnp.pad(qi[:, 0], ((0, 0), (0, 0), wide)), w[:, 0], pool,
            tables, lens)
        positions, rows, counts, selected = pa.sparse_select(
            scores, tables, lens, topk=topk, block_size=pool.shape[1])
        o = pa.paged_sparse_attention(q[:, 0], k_pool, v_pool, rows, counts,
                                      pages=(tables, lens, selected))
        outs.update(IndexOut=[pool], Selected=[positions])
    out = jnp.dot(o.reshape(o.shape[0], heads * hd),
                  ins["Wo"][0].astype(x.dtype))
    outs["Out"] = [out[:, None]]
    return outs


# ---------------------------------------------------------------------------
# Gated short convolution (LFM2's mixer in the attention's place)
#
#     (B, C, z) = split_3(x W_in)                     each [.., d], no bias
#     u   = B * z
#     c_t = sum_j w_j * u_{t - (L - 1) + j}           w [L, d]: one tap set
#                                                     a channel, causal
#     out = (C * c) W_out
#
# All a sequence leaves behind is the L - 1 rows of u before its next
# token: a STATE, [L - 1, d] a sequence however long, where an attention
# layer leaves a row a token.
# ---------------------------------------------------------------------------

def _short_conv_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    if op.output("StateOut"):
        var = block.var(op.output("StateOut")[0])
        taps = block.var(op.input("Taps")[0]).shape[0]
        var.shape = (block.var(op.input("State")[0]).shape
                     if op.input("State")
                     else (x.shape[0], int(taps) - 1, x.shape[-1]))
        var.dtype = x.dtype


@register_op("short_conv", infer_shape=_short_conv_infer)
def short_conv(ctx, ins, attrs):
    """The text above. X [B, S, d]; WIn [d, 3 d]; Taps [L, d]; WOut [d,
    d] -> Out [B, S, d].

    Whole sequences (no State) at positions 0..S-1, rows before the
    first are zeros; with NTokens [B] int (each row's true length n)
    also StateOut [B, L - 1, d]: rows n - L + 1 .. n - 1 of u, zeros
    where that is before the sequence's first row: what a decode step
    at position n reads, whatever padding follows row n - 1.

    One new token a slot (X [slots, 1, d]) with State [slots, L - 1, d]
    (the rows before the token) and ContextLens [slots] -> Out and
    StateOut, the state a row on: the oldest row out, the token's own u
    in. A slot of length 0 keeps its state as it was."""
    x = ins["X"][0]
    w_in, w_out = ins["WIn"][0].astype(x.dtype), ins["WOut"][0].astype(x.dtype)
    taps = ins["Taps"][0].astype(x.dtype)
    n_taps, d = taps.shape
    with jax.named_scope("short_conv"):
        b, c, z = jnp.split(jnp.dot(x, w_in), 3, axis=-1)
        u = b * z
        if ins.get("State"):
            state = ins["State"][0]
            rows = jnp.concatenate([state.astype(x.dtype), u], axis=1)
            conv = jnp.sum(rows * taps[None], axis=1, keepdims=True)
            live = (ins["ContextLens"][0] > 0)[:, None, None]
            outs = {"StateOut": [jnp.where(live, rows[:, 1:],
                                           state).astype(state.dtype)]}
        else:
            back = jnp.pad(u, ((0, 0), (n_taps - 1, 0), (0, 0)))
            seq = u.shape[1]
            conv = sum(taps[j] * back[:, j:j + seq] for j in range(n_taps))
            outs = {}
            if ins.get("NTokens"):
                # row n - (L - 1) + j of u is row n + j of `back`
                at = ins["NTokens"][0].astype(jnp.int32)[:, None] \
                    + jnp.arange(n_taps - 1, dtype=jnp.int32)[None]
                outs["StateOut"] = [jnp.take_along_axis(
                    back, at[:, :, None], axis=1)]
        outs["Out"] = [jnp.dot(c * conv, w_out)]
    return outs


# ---------------------------------------------------------------------------
# Selective scan (Mamba-1's mixer in the attention's place; the state
# layers of the SambaY decoder-hybrid-decoder, arXiv:2507.06607). u [.., d]
# is the block's normed input; d_inner channels, each with a state of
# d_state columns; no bias but the convolution's and the step's:
#
#     [x | z] = u W_in                                  each [.., d_inner]
#     x_t = silu(b_c + sum_j w_j * x_{t - (L - 1) + j})  w [L, d_inner]:
#                                                       depthwise, causal
#     [dt | B | C] = x W_x                              rank, d_state, d_state
#     D_t = softplus(dt W_dt + b_dt)                    [d_inner]
#     A   = -exp(A_log)                                 [d_inner, d_state]
#     S_t = exp(D_t A) * S_{t-1} + (D_t x_t) B_t^T      [d_inner, d_state]
#     y_t = S_t C_t + D_skip * x_t                      the MEMORY a layer
#                                                       may hand on
#     out = (y_t * silu(z_t)) W_out
#
# All a sequence leaves behind is S at its last token and the L - 1 rows
# of x (before the convolution) before its next one: a STATE, however long
# the sequence. Over a prompt the recurrence runs in CHUNKS of rows: inside
# a chunk an associative scan over (exp(D A), (D x) B^T) pairs, the chunk's
# last S the next chunk's carry, so no more than a chunk's [rows, d_state,
# d_inner] is ever whole. S is kept [d_state, d_inner], the channels on the
# lanes. The scan is float32 and ALL FOUR of the layer's projections run
# at `_CHOOSING`: the two small ones feed the recurrence's exponentials,
# and the in- and out-projections' rounding is what the recurrence sums
# over a prompt and the gate multiplies: at the TPU's one-pass default
# those two alone were over half of a 16-layer model's distance from its
# float32 reference (rms 0.112 of the logits' deviation with them at one
# pass, 0.068 with the layer's products exact: PERF.md section 6, PR 48).
# ---------------------------------------------------------------------------

#: rows of a prompt the selective scan takes at a time
_SCAN_CHUNK = 64


def _scan_pairs(left, right):
    """Two steps of S -> a S + b in a row: (a1, b1) then (a2, b2)."""
    a1, b1 = left
    a2, b2 = right
    return a1 * a2, a2 * b1 + b2


def _selective_scan_chunks(dt, xc, b, c, a, live):
    """The recurrence over whole sequences from a zero state. dt, xc
    [B, S, di] (the step after its softplus, the convolved x); b, c
    [B, S, ds]; a [ds, di] (negative); live [B, S] bool or None: a row
    that is not live leaves the state as it was. Returns (S_t C_t
    [B, S, di], the state after the last row [B, ds, di])."""
    bsz, seq, di = xc.shape
    ds = b.shape[-1]
    chunk = math.gcd(seq, _SCAN_CHUNK)

    def split(x):          # [B, S, ...] -> [n_chunks, B, chunk, ...]
        return jnp.moveaxis(
            x.reshape((bsz, seq // chunk, chunk) + x.shape[2:]), 1, 0)

    if live is None:
        live = jnp.ones((bsz, seq), bool)

    def one(carry, xs):
        dt_c, x_c, b_c, c_c, live_c = xs
        on = live_c[:, :, None, None]
        decay = jnp.where(on, jnp.exp(dt_c[:, :, None, :] * a), 1.0)
        push = jnp.where(on, (dt_c * x_c)[:, :, None, :]
                         * b_c[..., None], 0.0)     # [B, chunk, ds, di]
        push = push.at[:, 0].add(decay[:, 0] * carry)
        _, states = jax.lax.associative_scan(_scan_pairs, (decay, push),
                                             axis=1)
        return states[:, -1], jnp.sum(states * c_c[..., None], axis=2)

    carry, ys = jax.lax.scan(
        one, jnp.zeros((bsz, ds, di), jnp.float32),
        (split(dt), split(xc), split(b), split(c), split(live)))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, seq, di), carry


def _selective_scan_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    taps, di = block.var(op.input("ConvW")[0]).shape
    ds = int(op.attrs["d_state"])
    if op.output("Memory"):
        var = block.var(op.output("Memory")[0])
        var.shape, var.dtype = tuple(x.shape[:-1]) + (int(di),), x.dtype
    for role, rows in (("SsmStateOut", ds), ("ConvStateOut", int(taps) - 1)):
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = (x.shape[0], rows, int(di)), x.dtype


@register_op("selective_scan", infer_shape=_selective_scan_infer)
def selective_scan(ctx, ins, attrs):
    """The text above. X [B, S, d]; WIn [d, 2 di]; ConvW [L, di] (tap j
    weighs the row L - 1 - j before the token); ConvB [di]; WX [di, rank
    + 2 ds]; WDt [rank, di]; BDt [di]; ALog [di, ds]; DSkip [di]; WOut
    [di, d] -> Out [B, S, d] and, where the op has the output, Memory
    [B, S, di]: y before the gate.

    Whole sequences (no SsmState) at positions 0..S-1 from a zero state;
    with NTokens [B] int (each row's true length n) also SsmStateOut
    [B, ds, di] and ConvStateOut [B, L - 1, di]: S after row n - 1 and
    rows n - L + 1 .. n - 1 of x, whatever padding follows row n - 1
    (padding rows do not move the state).

    One new token a slot (X [slots, 1, d]) with SsmState [slots, ds,
    di], ConvState [slots, L - 1, di] and ContextLens [slots] -> Out,
    Memory and both states a row on. A slot of length 0 keeps its state
    as it was."""
    x = ins["X"][0]
    ds, rank = int(attrs["d_state"]), int(attrs["dt_rank"])
    taps = ins["ConvW"][0].astype(jnp.float32)
    n_taps, di = taps.shape
    a = -jnp.exp(ins["ALog"][0].astype(jnp.float32)).T          # [ds, di]

    def steps(xc):
        """The convolved x -> (D [.., di], B, C [.., ds])."""
        proj = jnp.dot(xc, ins["WX"][0].astype(jnp.float32),
                       precision=_CHOOSING)
        dt = jnp.dot(proj[..., :rank], ins["WDt"][0].astype(jnp.float32),
                     precision=_CHOOSING) + ins["BDt"][0]
        return (jnp.logaddexp(dt, 0.0), proj[..., rank:rank + ds],
                proj[..., rank + ds:])

    with jax.named_scope("selective_scan"):
        xs, z = jnp.split(_columns_dot(x, ins["WIn"][0].astype(x.dtype),
                                       _CHOOSING), 2, axis=-1)
        xs = xs.astype(jnp.float32)
        bias = ins["ConvB"][0].astype(jnp.float32)
        outs = {}
        if ins.get("SsmState"):
            state, rows = ins["SsmState"][0], ins["ConvState"][0]
            rows = jnp.concatenate([rows.astype(jnp.float32), xs], axis=1)
            xc = jax.nn.silu(bias + jnp.sum(rows * taps[None], axis=1))
            dt, b, c = steps(xc)                     # [slots, di | ds]
            moved = jnp.exp(dt[:, None, :] * a) * state \
                + (dt * xc)[:, None, :] * b[:, :, None]
            y = jnp.sum(moved * c[:, :, None], axis=1)[:, None]
            xc = xc[:, None]
            live = (ins["ContextLens"][0] > 0)[:, None, None]
            outs["SsmStateOut"] = [jnp.where(live, moved,
                                             state).astype(state.dtype)]
            outs["ConvStateOut"] = [jnp.where(
                live, rows[:, 1:], rows[:, :-1]).astype(state.dtype)]
        else:
            seq = xs.shape[1]
            back = jnp.pad(xs, ((0, 0), (n_taps - 1, 0), (0, 0)))
            xc = jax.nn.silu(bias + sum(taps[j] * back[:, j:j + seq]
                                        for j in range(n_taps)))
            dt, b, c = steps(xc)
            live = None
            if ins.get("NTokens"):
                n = ins["NTokens"][0].astype(jnp.int32)
                live = jnp.arange(seq, dtype=jnp.int32)[None] < n[:, None]
            y, last = _selective_scan_chunks(dt, xc, b, c, a, live)
            if ins.get("NTokens"):
                # row n - (L - 1) + j of x is row n + j of `back`
                at = n[:, None] + jnp.arange(n_taps - 1,
                                             dtype=jnp.int32)[None]
                outs["SsmStateOut"] = [last.astype(x.dtype)]
                outs["ConvStateOut"] = [jnp.take_along_axis(
                    back, at[:, :, None], axis=1).astype(x.dtype)]
        y = (y + ins["DSkip"][0].astype(jnp.float32) * xc).astype(x.dtype)
        outs["Memory"] = [y]
        outs["Out"] = [_columns_dot(y * jax.nn.silu(z),
                                    ins["WOut"][0].astype(x.dtype),
                                    _CHOOSING)]
    return outs


# ---------------------------------------------------------------------------
# Mamba-2 (the state-space duality form, arXiv:2405.21060; Nemotron-H's "M"
# layers): the selective scan with ONE decay a head and a state that is a
# matrix a HEAD. u [.., d] is the layer's normed input; H heads of P
# channels (d_inner = H P), G groups of heads that share B and C (head h
# reads group h // (H / G)), N state columns; no bias but the
# convolution's and the step's:
#
#     [z | xBC | dt] = u W_in                      d_inner | d_inner + 2 G N | H
#     xBC_t = silu(b_c + sum_j w_j * xBC_{t-(L-1)+j})   depthwise, causal
#     [x | B | C] = xBC                            d_inner | G N | G N
#     D_t[h] = softplus(dt_t[h] + b_dt[h])         the bias INSIDE
#     A[h]   = -exp(A_log[h])
#     S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)]   [P, N]
#     y_t[h] = S_t[h] C_t[g(h)] + D_skip[h] x_t[h]
#     v_t    = y_t * silu(z_t)                     the gate BEFORE the norm
#     n_t    = v_t / sqrt(mean over each group's d_inner / G channels of
#              v_t^2 + eps) * w
#     out    = n_t W_out
#
# All a sequence leaves behind is S at its last token ([H, P, N], the N on
# the lanes) and the L - 1 rows of xBC (before the convolution) before its
# next one. Over a prompt the recurrence runs in CHUNKS of rows in the SSD
# form: with cum_t the running sum of D A inside a chunk, a chunk's output
# is ((C B^T) o exp(cum_t - cum_s), s <= t) (D x), matrix products on the
# MXU, plus C (exp(cum_t) S_in); the chunk's last state is the next
# chunk's carry. An associative scan over per-row states, the form
# `_selective_scan_chunks` takes for Mamba-1's [d_state, d_inner] = 0.3 MB
# a row, would hold 2 MB a ROW here. A row at or past the prompt's true
# length has its step set to 0: its decay is 1 and it pushes nothing, so
# the state at the bucket's end is the state at the prompt's end. The scan
# is float32; the in- and out-projections and the chunk's products run at
# `_CHOOSING`, as `selective_scan`'s do and for its reason. A decode step
# moves the state by `kernels.ssd_update.ssd_decode_update`.
# ---------------------------------------------------------------------------

def _ssd_chunks(dt, x, b, c, a, chunk, state=None):
    """The recurrence over whole sequences from a zero state, or from
    `state` [B, H, P, N] (the rows are then a later part of a sequence
    whose earlier rows left it). dt [B, S, H] (after its softplus; 0: the
    row moves nothing); x [B, S, H, P]; b, c [B, S, G, N]; a [H]
    (negative). Returns (S_t C_t [B, S, H, P], the state after the last
    row [B, H, P, N])."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2:]
    rep = heads // groups
    rows = math.gcd(seq, chunk)

    def split(t):          # [B, S, ...] -> [n_chunks, B, rows, ...]
        return jnp.moveaxis(
            t.reshape((bsz, seq // rows, rows) + t.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((rows, rows), bool))

    def dot(spec, left, right):
        return jnp.einsum(spec, left, right, precision=_CHOOSING)

    def one(carry, xs):
        dt_c, x_c, b_c, c_c = xs
        cum = jnp.cumsum(dt_c * a, axis=1)                  # [B, L, H], <= 0
        push = dt_c[..., None] * x_c                        # [B, L, H, P]
        # inside the chunk: row t reads rows s <= t
        cb = dot("btgn,bsgn->bgts", c_c, b_c)               # [B, G, L, L]
        gap = cum[:, :, None, :] - cum[:, None, :, :]       # [B, t, s, H]
        decay = jnp.where(lower[None, :, :, None], jnp.exp(
            jnp.where(lower[None, :, :, None], gap, 0.0)), 0.0)
        mix = jnp.repeat(cb, rep, axis=1) \
            * jnp.moveaxis(decay, 3, 1)                     # [B, H, t, s]
        y = dot("bhts,bshp->bthp", mix, push)
        # and the state the chunk began with, decayed down to row t
        c_h = jnp.repeat(c_c, rep, axis=2)                  # [B, L, H, N]
        y = y + dot("bthn,bhpn->bthp", c_h, carry) \
            * jnp.exp(cum)[..., None]
        # what the chunk leaves: its rows' pushes decayed to its end
        left = jnp.exp(cum[:, -1:, :] - cum)                # [B, L, H]
        b_h = jnp.repeat(b_c, rep, axis=2)
        state = carry * jnp.exp(cum[:, -1])[..., None, None] \
            + dot("bshp,bshn->bhpn", push * left[..., None], b_h)
        return state, y

    if state is None:
        state = jnp.zeros((bsz, heads, p, n), jnp.float32)
    carry, ys = jax.lax.scan(
        one, state, (split(dt), split(x), split(b), split(c)))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, seq, heads, p), carry


def _mamba2_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    taps, width = block.var(op.input("ConvW")[0]).shape
    heads, ds = int(op.attrs["heads"]), int(op.attrs["d_state"])
    di = int(width) - 2 * int(op.attrs["groups"]) * ds
    for role, rows in (("SsmStateOut", (heads, di // heads, ds)),
                       ("ConvStateOut", (int(taps) - 1, int(width)))):
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = (x.shape[0],) + rows, x.dtype


@register_op("mamba2_mixer", infer_shape=_mamba2_infer)
def mamba2_mixer(ctx, ins, attrs):
    """The text above. X [B, S, d]; WIn [d, 2 di + 2 G N + H] (z, xBC,
    dt); ConvW [L, di + 2 G N] (tap j weighs the row L - 1 - j before the
    token); ConvB; BDt, ALog, DSkip [H]; NormW [di]; WOut [di, d] -> Out
    [B, S, d]. attrs: heads, groups, d_state, chunk, epsilon.

    Whole sequences (no SsmState) at positions 0..S-1 from a zero state;
    with NTokens [B] int (each row's true length n) also SsmStateOut
    [B, H, P, N] and ConvStateOut [B, L - 1, di + 2 G N]: S after row
    n - 1 and rows n - L + 1 .. n - 1 of xBC, whatever padding follows
    row n - 1.

    One new token a slot (X [slots, 1, d]) with SsmState [slots, H, P,
    N], ConvState [slots, L - 1, di + 2 G N] and ContextLens [slots] ->
    Out and both states a row on, the matrix by ONE call of
    `kernels.ssd_update.ssd_decode_update` (in place on a TPU). A slot of
    length 0 keeps its state as it was."""
    from ..kernels.ssd_update import ssd_decode_update

    x = ins["X"][0]
    heads, groups = int(attrs["heads"]), int(attrs["groups"])
    ds, eps = int(attrs["d_state"]), float(attrs["epsilon"])
    taps = ins["ConvW"][0].astype(jnp.float32)
    n_taps, width = taps.shape
    di = width - 2 * groups * ds
    p = di // heads
    a = -jnp.exp(ins["ALog"][0].astype(jnp.float32))            # [H]
    lead = x.shape[:2]

    def parts(xbc):
        """The convolved xBC [.., width] -> x [.., H, P], B, C [.., G,
        N]."""
        at = xbc.shape[:-1]
        return (xbc[..., :di].reshape(at + (heads, p)),
                xbc[..., di:di + groups * ds].reshape(at + (groups, ds)),
                xbc[..., di + groups * ds:].reshape(at + (groups, ds)))

    with jax.named_scope("mamba2"):
        proj = _columns_dot(x, ins["WIn"][0], _CHOOSING)
        z = proj[..., :di]
        xbc = proj[..., di:di + width].astype(jnp.float32)
        dt = jnp.logaddexp(proj[..., di + width:].astype(jnp.float32)
                           + ins["BDt"][0].astype(jnp.float32), 0.0)
        bias = ins["ConvB"][0].astype(jnp.float32)
        outs = {}
        if ins.get("SsmState"):
            state, rows = ins["SsmState"][0], ins["ConvState"][0]
            rows = jnp.concatenate([rows.astype(jnp.float32), xbc], axis=1)
            xs, b, c = parts(jax.nn.silu(
                bias + jnp.sum(rows * taps[None], axis=1)))
            live = ins["ContextLens"][0] > 0
            y, moved = ssd_decode_update(state, xs, dt[:, 0], a, b, c, live)
            y, xs = y[:, None], xs[:, None]
            outs["SsmStateOut"] = [moved]
            outs["ConvStateOut"] = [jnp.where(
                live[:, None, None], rows[:, 1:],
                rows[:, :-1]).astype(state.dtype)]
        else:
            seq = xbc.shape[1]
            back = jnp.pad(xbc, ((0, 0), (n_taps - 1, 0), (0, 0)))
            xs, b, c = parts(jax.nn.silu(
                bias + sum(taps[j] * back[:, j:j + seq]
                           for j in range(n_taps))))
            if ins.get("NTokens"):
                n = ins["NTokens"][0].astype(jnp.int32)
                live = jnp.arange(seq, dtype=jnp.int32)[None] < n[:, None]
                dt = jnp.where(live[..., None], dt, 0.0)
            y, last = _ssd_chunks(dt, xs, b, c, a, int(attrs["chunk"]))
            if ins.get("NTokens"):
                # row n - (L - 1) + j of xBC is row n + j of `back`
                at = n[:, None] + jnp.arange(n_taps - 1,
                                             dtype=jnp.int32)[None]
                outs["SsmStateOut"] = [last.astype(x.dtype)]
                outs["ConvStateOut"] = [jnp.take_along_axis(
                    back, at[:, :, None], axis=1).astype(x.dtype)]
        y = y + ins["DSkip"][0].astype(jnp.float32)[:, None] * xs
        v = y.reshape(lead + (di,)) * jax.nn.silu(z.astype(jnp.float32))
        grouped = v.reshape(lead + (groups, di // groups))
        normed = (grouped * jax.lax.rsqrt(jnp.mean(
            jnp.square(grouped), axis=-1, keepdims=True) + eps)).reshape(
                lead + (di,)) * ins["NormW"][0].astype(jnp.float32)
        outs["Out"] = [_columns_dot(normed.astype(x.dtype), ins["WOut"][0],
                                    _CHOOSING)]
    return outs


# ---------------------------------------------------------------------------
# Differential attention (arXiv:2410.05258, as the SambaY decoder applies
# it: no positions, a bias on every projection). u [.., d] is the block's
# normed input; H query heads and H_kv K/V heads of D, each in two sets
# (the halves: heads 0 .. H/2 - 1 and the rest), query head h of either
# set reading K/V head g = h // (H / H_kv) of the same set:
#
#     A1[h] = softmax(q1[h] k1[g]^T / sqrt(D)) [v1[g] | v2[g]]     [.., 2 D]
#     A2[h] = softmax(q2[h] k2[g]^T / sqrt(D)) [v1[g] | v2[g]]
#     lam   = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init
#     O[h]  = RMSNorm_2D(A1[h] - lam A2[h]) * gain * (1 - lam_init)
#     out   = concat_h O[h] W_o + b_o
#
# causal; with `window` W row t reads the rows s with t - s < W. A CROSS
# layer has the query projection alone and reads another layer's K and V.
# What a cache holds of a token is K head g of the first set beside K
# head g of the second ([H_kv / 2, 2 D]: `diff_row`), and V the same: the
# pair a head pair reads, once.
#
# Two ops, one set of weights: `diff_attention` over whole sequences (a
# prefill, a trainer; its queries may be a few rows of the sequence) and
# `diff_decode_attention`, one new token a slot against paged pools, its
# own or another layer's. Every projection and the dense form's products
# run at `_CHOOSING`: the sub-norm divides A1 - lam A2 by its own size,
# which is a fraction of either term's, so V's and the output's rounding
# reach the stream multiplied where a plain softmax's would not.
# ---------------------------------------------------------------------------

def diff_row(x):
    """K (or V) heads [.., H_kv, D] as a cache stores them: [.., H_kv / 2,
    2 D], head g of the first set beside head g of the second."""
    *lead, hk, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, 2, hk // 2, d), -3, -2).reshape(
        *lead, hk // 2, 2 * d)


def _diff_dims(attrs):
    return (int(attrs["num_heads"]), int(attrs["num_kv_heads"]),
            int(attrs["head_dim"]))


def _diff_q(x, ins, attrs):
    heads, _, hd = _diff_dims(attrs)
    q = _columns_dot(x, ins["Wq"][0].astype(x.dtype), _CHOOSING)
    return (q + ins["Bq"][0].astype(x.dtype)).reshape(
        x.shape[:2] + (heads, hd))


def _diff_kv(x, ins, attrs):
    """x [B, S, d] -> K and V as a cache stores them, [B, S, H_kv / 2,
    2 D] each."""
    _, kv_heads, hd = _diff_dims(attrs)
    k = _columns_dot(x, ins["Wk"][0].astype(x.dtype), _CHOOSING) \
        + ins["Bk"][0].astype(x.dtype)
    v = _columns_dot(x, ins["Wv"][0].astype(x.dtype), _CHOOSING) \
        + ins["Bv"][0].astype(x.dtype)
    shape = x.shape[:2] + (kv_heads, hd)
    return diff_row(k.reshape(shape)), diff_row(v.reshape(shape))


def _diff_lambda(ins, attrs):
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(ins["LamQ1"][0].astype(f32)
                            * ins["LamK1"][0].astype(f32)))
            - jnp.exp(jnp.sum(ins["LamQ2"][0].astype(f32)
                              * ins["LamK2"][0].astype(f32)))
            + float(attrs["lambda_init"]))


def _diff_out(diff, x, ins, attrs):
    """A1 - lam A2 [B, S, H / 2, 2 D] -> the layer's output [B, S, d]:
    the sub-norm, its (1 - lam_init), the output projection."""
    o = _rms_over_last(diff, ins["SubNorm"][0], float(attrs["epsilon"])) \
        * (1.0 - float(attrs["lambda_init"]))
    return _columns_dot(o.reshape(x.shape[:2] + (-1,)).astype(x.dtype),
                        ins["Wo"][0].astype(x.dtype), _CHOOSING) \
        + ins["Bo"][0].astype(x.dtype)


def _diff_dense(q, kt, vt, positions, window, scale):
    """The two softmaxes as masked dense products, the oracle's form and
    what a few query rows take: q [B, Sq, H, D] at `positions` [B, Sq],
    kt, vt [B, S, H_kv / 2, 2 D] at 0..S-1 -> (A1, A2) [B, Sq, H / 2,
    2 D]."""
    b, sq, heads, hd = q.shape
    s, pairs = kt.shape[1], kt.shape[2]
    per = heads // (2 * pairs)
    qg = q.reshape(b, sq, 2, pairs, per, hd)
    kg = kt.reshape(b, s, pairs, 2, hd)
    sc = jnp.einsum("bqngjd,bkgnd->bngjqk", qg, kg, precision=_CHOOSING,
                    preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s, dtype=jnp.int32)[None, None]
    qpos = positions.astype(jnp.int32)[:, :, None]
    seen = kpos <= qpos
    if window:
        seen = seen & (kpos > qpos - window)
    sc = jnp.where(seen[:, None, None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(vt.dtype)
    out = jnp.einsum("bngjqk,bkge->bqngje", p, vt, precision=_CHOOSING)
    out = out.reshape(b, sq, 2, heads // 2, 2 * hd)
    return out[:, :, 0], out[:, :, 1]


def _diff_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    src = block.var(op.input("XKV")[0]) if op.input("XKV") else x
    row = (int(op.attrs["num_kv_heads"]) // 2,
           2 * int(op.attrs["head_dim"]))
    for role in ("K", "V"):
        if op.output(role):
            var = block.var(op.output(role)[0])
            var.shape, var.dtype = tuple(src.shape[:-1]) + row, x.dtype


@register_op("diff_attention", infer_shape=_diff_infer)
def diff_attention(ctx, ins, attrs):
    """Causal differential attention over whole sequences (the text
    above): X [B, Sq, d], the rows that ask; Wq [d, H D], Bq; Wo [H D,
    d], Bo; LamQ1, LamK1, LamQ2, LamK2 [D]; SubNorm [2 D]. A self layer
    has Wk, Wv [d, H_kv D], Bk, Bv and projects K and V from X, or from
    XKV [B, S, d] where the rows that ask are fewer than the sequence
    (then QRows [B, Sq] int: their positions in it) -> Out [B, Sq, d], K
    and V [B, S, H_kv / 2, 2 D], a cache's rows. A cross layer has
    KIn and VIn, such rows of another layer, and no K/V weights; it
    returns Out alone. `window`: as the text says.

    Every row asking over its own sequence (no QRows, no cross) is one
    call of `dot_product_attention`, both sets' heads side by side, K
    unrepeated and V at twice the heads' width (the flash forward on a
    TPU, with its window band); otherwise masked dense products."""
    from ..kernels.flash_attention import dot_product_attention

    if ctx is not None and getattr(ctx, "mesh", None) is not None \
            and ctx.mesh.size > 1:
        raise NotImplementedError("differential attention on a mesh of "
                                  "several chips is not built")
    x = ins["X"][0]
    heads, kv_heads, hd = _diff_dims(attrs)
    window = int(attrs.get("window", 0))
    q = _diff_q(x, ins, attrs)
    outs = {}
    if ins.get("KIn"):
        kt, vt = ins["KIn"][0], ins["VIn"][0]
    else:
        kt, vt = _diff_kv(ins["XKV"][0] if ins.get("XKV") else x, ins,
                          attrs)
        outs.update(K=[kt], V=[vt])
    if ins.get("QRows") or q.shape[1] != kt.shape[1]:
        rows = ins["QRows"][0] if ins.get("QRows") else jnp.broadcast_to(
            jnp.arange(q.shape[1], dtype=jnp.int32)[None], q.shape[:2])
        a1, a2 = _diff_dense(q, kt, vt, rows, window, 1.0 / hd ** 0.5)
    else:
        # K heads in the sets' order again; V's pairs once a set
        k = jnp.swapaxes(kt.reshape(kt.shape[:3] + (2, hd)), 2, 3) \
            .reshape(kt.shape[:2] + (kv_heads, hd))
        out = dot_product_attention(
            q, k, jnp.concatenate([vt, vt], axis=2), causal=True,
            window=window or None)
        a1, a2 = out[:, :, :heads // 2], out[:, :, heads // 2:]
    diff = a1.astype(jnp.float32) - _diff_lambda(ins, attrs) \
        * a2.astype(jnp.float32)
    outs["Out"] = [_diff_out(diff, x, ins, attrs)]
    return outs


def _diff_decode_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    for pool_in, pool_out in (("KPool", "KOut"), ("VPool", "VOut")):
        if op.output(pool_out):
            src = block.var(op.input(pool_in)[0])
            dst = block.var(op.output(pool_out)[0])
            dst.shape, dst.dtype = src.shape, src.dtype


@register_op("diff_decode_attention", infer_shape=_diff_decode_infer)
def diff_decode_attention(ctx, ins, attrs):
    """One new token a slot: X [S, 1, d], the weights of
    `diff_attention`, KPool and VPool [NB, BS, H_kv / 2, 2 D],
    BlockTables, ContextLens (the span INCLUDING the new token) -> Out
    [S, 1, d]. A self layer (it has Wk) writes each slot's new row first
    -> KOut, VOut; a cross layer reads the pools as they are, another
    layer's, rows that layer wrote this step included. With `window` the
    slot reads its newest `window` rows alone. One Pallas kernel on a TPU
    (`kernels.paged_attention.paged_diff_attention`: every K and V row
    read once), the gather reference elsewhere."""
    from ..kernels import paged_attention as pa

    x = ins["X"][0]
    tables, lens = ins["BlockTables"][0], ins["ContextLens"][0]
    k_pool, v_pool = ins["KPool"][0], ins["VPool"][0]
    q = _diff_q(x, ins, attrs)
    outs = {}
    if ins.get("Wk"):
        kt, vt = _diff_kv(x, ins, attrs)
        k_pool, v_pool = pa.paged_kv_update(k_pool, v_pool, kt[:, 0],
                                            vt[:, 0], tables, lens)
        outs.update(KOut=[k_pool], VOut=[v_pool])
    diff = pa.paged_diff_attention(
        q[:, 0], k_pool, v_pool, tables, lens, _diff_lambda(ins, attrs),
        window=int(attrs.get("window", 0)) or None)
    outs["Out"] = [_diff_out(diff[:, None], x, ins, attrs)]
    return outs


def _paged_write_infer(op, block):
    for pool_in, pool_out in (("KPool", "KOut"), ("VPool", "VOut")):
        src = block.var(op.input(pool_in)[0])
        dst = block.var(op.output(pool_out)[0])
        dst.shape, dst.dtype = src.shape, src.dtype


@register_op("paged_kv_write", infer_shape=_paged_write_infer)
def paged_kv_write(ctx, ins, attrs):
    """Scatter each slot's new K/V row ([S, 1, H, D]) into its page of the
    pool ([NB, BS, H, D]) at position ContextLens-1. Slots with
    ContextLens 0 write into the reserved null block 0."""
    from ..kernels.paged_attention import paged_kv_update

    k, v = ins["K"][0], ins["V"][0]
    ko, vo = paged_kv_update(ins["KPool"][0], ins["VPool"][0],
                             k[:, 0], v[:, 0],
                             ins["BlockTables"][0], ins["ContextLens"][0])
    return {"KOut": [ko], "VOut": [vo]}


def _paged_attn_infer(op, block):
    q = block.var(op.input("Q")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = q.shape, q.dtype


@register_op("paged_attention", infer_shape=_paged_attn_infer)
def paged_attention(ctx, ins, attrs):
    """Q: [S, 1, H, D] (one decode token per slot) against the paged pool
    through the per-slot block table; ContextLens is the span INCLUDING
    the just-written token. Pallas kernel on TPU shapes, gather-based XLA
    reference elsewhere (kernels/paged_attention.py)."""
    from ..kernels.paged_attention import paged_decode_attention

    q = ins["Q"][0]
    scale = attrs.get("scale", 0.0) or None
    out = paged_decode_attention(q[:, 0], ins["KPool"][0], ins["VPool"][0],
                                 ins["BlockTables"][0],
                                 ins["ContextLens"][0], scale=scale)
    return {"Out": [out[:, None]]}
