"""Recurrent ops: fused LSTM/GRU cells and the dynamic_rnn sub-block scanner.

≙ reference recurrent machinery: fused kernels lstm_op/gru_op
(operators/math/{lstm,gru}_compute.cu, paddle/cuda/src/hl_cuda_lstm.cu) and
the sub-block interpreters recurrent_op.cc:222 / DynamicRNN
(layers/control_flow.py:1313). TPU-native: everything is lax.scan over
time-major arrays with length masking — XLA unrolls nothing, the scan body
is one fused step, gradients come from scan's native VJP (the reference
needed StepScopes + hand-written grad sub-blocks, recurrent_op.cc:53).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from ..core.types import device_dtype
from .sequence_ops import time_mask

_ACT = {
    "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "relu": lambda x: jnp.maximum(x, 0),
    "identity": lambda x: x, None: jnp.tanh,
}


def _pallas_lstm_ok(ctx, attrs, use_peep, w_proj, b, h, t):
    """Route to the whole-sequence Pallas kernel (kernels/fused_lstm.py, ≙
    the reference's hl_cuda_lstm.cu persistent-weight tier) when the
    configuration matches its contract and we are on one real TPU device.
    PT_FUSED_LSTM=never reverts to the lax.scan formulation."""
    import os
    if os.environ.get("PT_FUSED_LSTM", "auto") in ("0", "never"):
        return False
    if use_peep or w_proj is not None:
        return False
    if (attrs.get("gate_activation", "sigmoid") != "sigmoid"
            or attrs.get("cell_activation", "tanh") != "tanh"
            or attrs.get("candidate_activation", "tanh") != "tanh"):
        return False
    if ctx is None or getattr(ctx, "mesh", None) is not None:
        return False
    if h % 128 or b % 8 or t < 4:
        return False
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover
        return False


def _lstm_scan(ins, attrs, w_proj=None, pact=None, ctx=None):
    """Shared fused-LSTM scan (lstm_op.cc / lstmp_op.h): one lax.scan whose
    carry is (recurrent_state, cell). For plain LSTM the recurrent state is
    the hidden h [B,H]; for LSTMP it is the projection r = pact(h @ w_proj)
    [B,P] (Sak et al. 2014). Gate layout i,c,f,o per the reference kernel
    (operators/math/detail/lstm_kernel.h); rows past each sequence's length
    hold their last valid state (stacked outputs are zero-masked)."""
    x = ins["Input"][0]
    w = ins["Weight"][0].astype(x.dtype)   # [H,4H] | [P,4H]
    seq_len = ins["SeqLen"][0]
    B, T, H4 = x.shape
    H = H4 // 4
    R = H if w_proj is None else w_proj.shape[1]   # recurrent-state width
    use_peep = attrs.get("use_peepholes", False)
    bias = ins["Bias"][0].astype(x.dtype) if ins.get("Bias") else None
    if bias is not None:
        b_gate = bias.reshape(-1)[:4 * H]
        b_peep = bias.reshape(-1)[4 * H:] if use_peep else None
    else:
        b_gate, b_peep = None, None
    gact = _ACT[attrs.get("gate_activation", "sigmoid")]
    cact = _ACT[attrs.get("cell_activation", "tanh")]
    hact = _ACT[attrs.get("candidate_activation", "tanh")]
    reverse = attrs.get("is_reverse", False)

    xs = jnp.moveaxis(x, 1, 0)  # [T,B,4H]
    mask = jnp.moveaxis(time_mask(seq_len, T, x.dtype), 1, 0)  # [T,B]
    if reverse:
        xs = jnp.flip(xs, 0)
        mask = jnp.flip(mask, 0)

    if ins.get("H0"):
        r0 = ins["H0"][0].astype(x.dtype)          # [B,H] (ref convention)
        if w_proj is not None:
            # lstmp_op.h:174-183: project the initial hidden state
            r0 = pact(r0 @ w_proj)
    else:
        r0 = jnp.zeros((B, R), x.dtype)
    c0 = ins["C0"][0].astype(x.dtype) if ins.get("C0") else jnp.zeros((B, H), x.dtype)

    def step(carry, inp):
        r, c = carry
        xt, m = inp
        gates = xt + r @ w
        if b_gate is not None:
            gates = gates + b_gate
        gi, gc, gf, go = jnp.split(gates, 4, axis=-1)
        if use_peep:
            wic, wfc, woc = jnp.split(b_peep, 3)
            gi = gi + wic * c
            gf = gf + wfc * c
        i = gact(gi)
        f = gact(gf)
        cand = cact(gc)
        c_new = f * c + i * cand
        if use_peep:
            go = go + woc * c_new
        o = gact(go)
        r_new = o * hact(c_new)
        if w_proj is not None:
            r_new = pact(r_new @ w_proj)
        m1 = m[:, None]
        r_new = m1 * r_new + (1 - m1) * r
        c_new = m1 * c_new + (1 - m1) * c
        return (r_new, c_new), (r_new * m1, c_new * m1)

    if _pallas_lstm_ok(ctx, attrs, use_peep, w_proj, B, H, T):
        from ..kernels.fused_lstm import lstm_sequence
        bz = b_gate if b_gate is not None else jnp.zeros((4 * H,), x.dtype)
        rs_c, cs_c = lstm_sequence(xs, w, bz, mask, r0, c0)
        # the op's outputs are the MASKED values; carries come from the
        # kernel (its backward needs them), the mask ride is one fused
        # XLA elementwise
        m3 = mask[:, :, None]
        rs, cs = rs_c * m3.astype(rs_c.dtype), cs_c * m3.astype(cs_c.dtype)
    else:
        (_, _), (rs, cs) = jax.lax.scan(step, (r0, c0), (xs, mask))
    if reverse:
        rs, cs = jnp.flip(rs, 0), jnp.flip(cs, 0)
    return jnp.moveaxis(rs, 0, 1), jnp.moveaxis(cs, 0, 1)


@register_op("dynamic_lstm")
def dynamic_lstm(ctx, ins, attrs):
    """lstm_op.cc. Input [B,T,4H] (pre-projected x*W_x), Weight [H,4H]
    recurrent, Bias [1,4H] (+[1,3H] peephole tail when use_peepholes).
    Outputs Hidden/Cell [B,T,H]."""
    hs, cs = _lstm_scan(ins, attrs, ctx=ctx)
    return {"Hidden": [hs], "Cell": [cs]}


@register_op("lstmp")
def lstmp(ctx, ins, attrs):
    """lstmp_op.cc/.h: LSTM with a recurrent projection layer (LSTMP, Sak
    et al. 2014). Input [B,T,4H] pre-projected; recurrent Weight [P,4H]
    acts on the PROJECTED state r; ProjWeight [H,P] maps cell-output h to
    r = proj_act(h @ ProjWeight). H0 follows the reference convention of a
    HIDDEN state [B,H], projected before the first step (lstmp_op.h:174).
    Outputs Projection [B,T,P] and Cell [B,T,H]."""
    x = ins["Input"][0]
    w_proj = ins["ProjWeight"][0].astype(x.dtype)   # [H, P]
    pact = _ACT[attrs.get("proj_activation", "tanh")]
    rs, cs = _lstm_scan(ins, attrs, w_proj=w_proj, pact=pact)
    return {"Projection": [rs], "Cell": [cs]}


@register_op("dynamic_gru")
def dynamic_gru(ctx, ins, attrs):
    """gru_op.cc. Input [B,T,3H] pre-projected, Weight [H,3H]: layout
    [update u | reset r | candidate c] following gru_compute. Output [B,T,H]."""
    x = ins["Input"][0]
    w = ins["Weight"][0].astype(x.dtype)
    seq_len = ins["SeqLen"][0]
    B, T, H3 = x.shape
    H = H3 // 3
    bias = ins["Bias"][0].astype(x.dtype).reshape(-1) if ins.get("Bias") else None
    gact = _ACT[attrs.get("gate_activation", "sigmoid")]
    cact = _ACT[attrs.get("activation", "tanh")]
    reverse = attrs.get("is_reverse", False)
    w_ur = w[:, :2 * H]
    w_c = w[:, 2 * H:]

    xs = jnp.moveaxis(x, 1, 0)
    mask = jnp.moveaxis(time_mask(seq_len, T, x.dtype), 1, 0)
    if reverse:
        xs = jnp.flip(xs, 0)
        mask = jnp.flip(mask, 0)
    h0 = ins["H0"][0].astype(x.dtype) if ins.get("H0") else jnp.zeros((B, H), x.dtype)

    def step(h, inp):
        xt, m = inp
        xur = xt[:, :2 * H]
        xc = xt[:, 2 * H:]
        gur = xur + h @ w_ur
        if bias is not None:
            gur = gur + bias[:2 * H]
        u, r = jnp.split(gact(gur), 2, axis=-1)
        gc = xc + (r * h) @ w_c
        if bias is not None:
            gc = gc + bias[2 * H:]
        cand = cact(gc)
        # gru_kernel.h:62: out = prev - u*prev + u*cand = (1-u)*prev + u*cand
        h_new = u * cand + (1.0 - u) * h
        m1 = m[:, None]
        h_new = m1 * h_new + (1 - m1) * h
        return h_new, h_new * m1

    _, hs = jax.lax.scan(step, h0, (xs, mask))
    if reverse:
        hs = jnp.flip(hs, 0)
    return {"Hidden": [jnp.moveaxis(hs, 0, 1)]}


@register_op("dynamic_rnn")
def dynamic_rnn(ctx, ins, attrs):
    """The DynamicRNN/recurrent_op sub-block scanner (recurrent_op.cc:222).

    Runs the ops of `sub_block` once per timestep under lax.scan. Step
    inputs are time-sliced from padded [B,T,...] arrays; memories carry with
    length masking; outer vars (parameters) are captured read-only from the
    enclosing environment — the functional equivalent of StepScopes' parent
    lookup (recurrent_op.cc:53).
    """
    from ..core import lowering

    program = ctx.program
    sub = program.block(attrs["sub_block"])
    step_inner = list(attrs["step_input_vars"])     # inner per-step names
    mem_inner = list(attrs["memory_vars"])          # inner memory names
    mem_updates = dict(attrs["memory_updates"])     # inner -> updated name
    mem_init_values = list(attrs["memory_init_values"])
    mem_shapes = list(attrs["memory_shapes"])
    out_inner = list(attrs["output_vars"])

    xs_list = ins["X"]
    seq_len = ins["SeqLen"][0]
    init_mems_in = list(ins.get("InitMems", []))
    has_init = list(attrs.get("memory_has_init", [False] * len(mem_inner)))
    B, T = xs_list[0].shape[0], xs_list[0].shape[1]
    dtype = xs_list[0].dtype if jnp.issubdtype(xs_list[0].dtype, jnp.floating) \
        else jnp.float32
    mem_dtypes = list(attrs.get("memory_dtypes", []))

    init = []
    init_iter = iter(init_mems_in)
    for i, name in enumerate(mem_inner):
        if has_init[i]:
            init.append(next(init_iter))
        else:
            shape = (B,) + tuple(s for s in mem_shapes[i] if s != -1)
            mdt = mem_dtypes[i] if i < len(mem_dtypes) and mem_dtypes[i] else dtype
            # device dtypes are 32-bit (same canonicalization as the executor
            # feed path); jnp.full with "int64" would truncate with a warning
            mdt = device_dtype(str(mdt)) if isinstance(mdt, str) else mdt
            init.append(jnp.full(shape, mem_init_values[i], mdt))

    xs_tm = [jnp.moveaxis(x, 1, 0) for x in xs_list]
    mask_tm = jnp.moveaxis(time_mask(seq_len, T, jnp.float32), 1, 0)  # [T,B]
    outer_env = dict(ctx.env)

    def body(carry, scanned):
        mems = carry
        xts, m = scanned[:-1], scanned[-1]
        env = dict(outer_env)
        for name, xt in zip(step_inner, xts):
            env[name] = xt
        for name, mem in zip(mem_inner, mems):
            env[name] = mem
        lowering.run_op_range(sub.ops, 0, len(sub.ops), env, ctx, sub)
        new_mems = []
        for name, old in zip(mem_inner, mems):
            upd = env[mem_updates.get(name, name)]
            mb = m.reshape((B,) + (1,) * (upd.ndim - 1)) > 0
            new_mems.append(jnp.where(mb, upd, old))
        outs = []
        for name in out_inner:
            v = env[name]
            mb = m.reshape((B,) + (1,) * (v.ndim - 1)) > 0
            outs.append(jnp.where(mb, v, jnp.zeros((), v.dtype)))
        return tuple(new_mems), tuple(outs)

    final_mems, stacked = jax.lax.scan(body, tuple(init),
                                       tuple(xs_tm) + (mask_tm,))
    outs = [jnp.moveaxis(o, 0, 1) for o in stacked]
    return {"Out": outs, "FinalMems": list(final_mems)}
