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

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core.compat import shard_map
from ..core.registry import register_op, same_shape


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

def rope_rotate(x, positions, theta, interleave=False):
    """x [B, S, H, D] rotated by `positions` ([S], shared by every row,
    or [B, S], each row's own), angles in float32 whatever x's dtype.
    `interleave`: dimensions (2i, 2i+1) are a pair (the published
    DeepSeek / GPT-J form); otherwise (i, i + D/2) are (rotate-half)."""
    pos = positions.astype(jnp.float32)
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (pos[..., None] * inv_freq)[..., None, :]     # [(B,) S, 1, D/2]
    if pos.ndim == 1:
        ang = ang[None]                                 # [1, S, 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
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
# Latent attention (DeepSeek-V2/V3's MLA, `q_lora_rank` null): keys and
# values of all heads are up-projections of one low-rank latent a token,
# and one rotary key is shared by every head.
#
#   q = x Wq -> [.., H, nope + rope] = q_nope | q_rope
#   x Wkva  -> [.., rank + rope]    = c | k_rope;   c = RMSNorm(c) * g
#   c Wkvb  -> [.., H, nope + v]    = k_nope | v
#   RoPE on q_rope and k_rope alone
#   scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)
#
# Two ops, one set of weights and one `_latent_project`: `latent_attention`
# builds K and V from the latent and runs ordinary causal attention with a
# V width of its own (a prefill, a trainer); `latent_decode_attention` is
# the same function regrouped for one new token a slot against a paged
# cache of latent rows [c | k_rope rotated]: with Wkvb split by head into
# Wk_h and Wv_h, q'_h = q_nope_h Wk_h^T scores straight against c, and
# o_h = (P_h c) Wv_h: the cache is read once for all heads and never
# expanded.
# ---------------------------------------------------------------------------

def _latent_dims(attrs):
    return (int(attrs["num_heads"]), int(attrs["kv_lora_rank"]),
            int(attrs["qk_nope_head_dim"]), int(attrs["qk_rope_head_dim"]),
            int(attrs["v_head_dim"]))


def _latent_project(x, wq, wkva, gain, positions, attrs):
    """x [B, S, d] -> q_nope [B, S, H, nope], q_rope [B, S, H, rope]
    rotated, the latent row [B, S, rank + rope] = normed c | rotated
    k_rope: what a cache holds of a token."""
    heads, rank, nope, rope, _ = _latent_dims(attrs)
    theta = float(attrs["rope_theta"])
    inter = bool(attrs["rope_interleave"])
    q = jnp.dot(x, wq.astype(x.dtype)).reshape(
        x.shape[:2] + (heads, nope + rope))
    q_rope = rope_rotate(q[..., nope:], positions, theta, inter)
    kva = jnp.dot(x, wkva.astype(x.dtype))
    cf = kva[..., :rank].astype(jnp.float32)
    c = (cf * jax.lax.rsqrt(jnp.mean(jnp.square(cf), axis=-1,
                                     keepdims=True)
                            + float(attrs["epsilon"]))
         * gain.astype(jnp.float32)).astype(x.dtype)
    k_rope = rope_rotate(kva[..., None, rank:], positions, theta,
                         inter)[..., 0, :]
    return q[..., :nope], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def _latent_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    if op.output("Latent"):
        lat = block.var(op.output("Latent")[0])
        lat.shape = tuple(x.shape[:-1]) + (
            int(op.attrs["kv_lora_rank"])
            + int(op.attrs["qk_rope_head_dim"]),)
        lat.dtype = x.dtype


@register_op("latent_attention", infer_shape=_latent_infer)
def latent_attention(ctx, ins, attrs):
    """Causal latent attention over whole sequences, expanded: X
    [B, S, d]; Wq [d, H (nope + rope)]; Wkva [d, rank + rope]; KvNorm
    [rank]; Wkvb [rank, H (nope + v)]; Wo [H v, d] -> Out [B, S, d] and
    Latent [B, S, rank + rope], each token's cache row. Positions are
    0..S-1. The attention itself is `dot_product_attention` (the flash
    forward kernel on a TPU, with a V width of its own)."""
    from ..kernels.flash_attention import dot_product_attention

    if ctx is not None and getattr(ctx, "mesh", None) is not None \
            and ctx.mesh.size > 1:
        raise NotImplementedError("latent attention on a mesh of several "
                                  "chips is not built")
    x = ins["X"][0]
    heads, rank, nope, rope, vdim = _latent_dims(attrs)
    q_nope, q_rope, latent = _latent_project(
        x, ins["Wq"][0], ins["Wkva"][0], ins["KvNorm"][0],
        jnp.arange(x.shape[1], dtype=jnp.int32), attrs)
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
    return {"Out": [jnp.dot(merged, ins["Wo"][0].astype(x.dtype))],
            "Latent": [latent]}


def _latent_decode_infer(op, block):
    x = block.var(op.input("X")[0])
    out = block.var(op.output("Out")[0])
    out.shape, out.dtype = x.shape, x.dtype
    src = block.var(op.input("Pool")[0])
    dst = block.var(op.output("PoolOut")[0])
    dst.shape, dst.dtype = src.shape, src.dtype


@register_op("latent_decode_attention", infer_shape=_latent_decode_infer)
def latent_decode_attention(ctx, ins, attrs):
    """One new token a slot, absorbed: X [S, 1, d], the weights of
    `latent_attention`, Pool [NB, BS, W] (W >= rank + rope: columns
    past them are padding and hold zeros), Positions [S, 1],
    BlockTables, ContextLens (the span INCLUDING the new token) ->
    Out [S, 1, d], PoolOut (the pool with each slot's new row written).
    Pallas kernel on a TPU, the gather reference elsewhere
    (kernels/flash_attention.py)."""
    from ..kernels.flash_attention import (paged_latent_decode_attention,
                                           paged_row_update)

    x, pool = ins["X"][0], ins["Pool"][0]
    tables, lens = ins["BlockTables"][0], ins["ContextLens"][0]
    heads, rank, nope, rope, vdim = _latent_dims(attrs)
    q_nope, q_rope, row = _latent_project(
        x, ins["Wq"][0], ins["Wkva"][0], ins["KvNorm"][0],
        ins["Positions"][0], attrs)
    q_nope, q_rope, row = q_nope[:, 0], q_rope[:, 0], row[:, 0]
    pad = pool.shape[-1] - (rank + rope)
    pool = paged_row_update(pool, jnp.pad(row, ((0, 0), (0, pad))),
                            tables, lens)
    wkvb = ins["Wkvb"][0].astype(x.dtype).reshape(rank, heads,
                                                  nope + vdim)
    with jax.named_scope("latent_absorb"):
        q_lat = jnp.einsum("shn,rhn->shr", q_nope, wkvb[..., :nope])
    q_full = jnp.pad(jnp.concatenate([q_lat, q_rope], axis=-1),
                     ((0, 0), (0, 0), (0, pad)))
    u = paged_latent_decode_attention(
        q_full, pool, tables, lens, value_width=rank,
        scale=1.0 / float(nope + rope) ** 0.5)
    with jax.named_scope("latent_absorb"):
        o = jnp.einsum("shr,rhv->shv", u, wkvb[..., nope:])
    out = jnp.dot(o.reshape(o.shape[0], heads * vdim),
                  ins["Wo"][0].astype(x.dtype))
    return {"Out": [out[:, None]], "PoolOut": [pool]}


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
    from ..kernels.flash_attention import paged_kv_update

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
    reference elsewhere (kernels/flash_attention.py)."""
    from ..kernels.flash_attention import paged_decode_attention

    q = ins["Q"][0]
    scale = attrs.get("scale", 0.0) or None
    out = paged_decode_attention(q[:, 0], ins["KPool"][0], ins["VPool"][0],
                                 ins["BlockTables"][0],
                                 ins["ContextLens"][0], scale=scale)
    return {"Out": [out[:, None]]}
