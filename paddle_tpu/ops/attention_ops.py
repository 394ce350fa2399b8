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
    x = ins["X"][0]
    pos = ins["Positions"][0].astype(jnp.float32)
    theta = float(attrs.get("theta", 10000.0))
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None] * inv_freq                     # [(B,) S, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[..., None, :]
    if pos.ndim == 1:
        ang = ang[None]                                 # [1, S, 1, D]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    out = xf * jnp.cos(ang) + rot * jnp.sin(ang)
    return {"Out": [out.astype(x.dtype)]}


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
