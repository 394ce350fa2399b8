"""Operations and bytes a call needs, from its shapes alone.

Everything here counts what the ALGORITHM needs, never what an
implementation happens to do: recomputed work (remat, the flash
backward's second pass over QK^T in a split dq / dkdv kernel pair) is
not counted, so a share of the roofline built on these cannot pass 100%
unless the time leaves work out.

The transformer arithmetic is `bench.py`'s (`bench_transformer`: 2 FLOPs
per weight per token forward, attention scores and values on top, x3
for forward + backward), copied so that the benchmark reads nothing
outside its own directory; `bench.py`'s copy is listed in PERF.md for a
later PR to delete.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip. An unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def lm_forward_flops_per_token(*, n_layer, d_model, d_ff, vocab, seq_len,
                               causal=True):
    """Forward FLOPs per token of a GPT-2 block stack with an untied
    head: 2 per weight of the matmuls (4 d^2 attention projections,
    2 d d_ff FFN, d V head) plus QK^T and PV (2 * 2 * S * d each token
    against S keys, halved by the causal mask)."""
    attn_ctx = 4.0 * seq_len * d_model * (0.5 if causal else 1.0)
    per_layer = 2.0 * (4 * d_model * d_model + 2 * d_model * d_ff) \
        + attn_ctx
    return n_layer * per_layer + 2.0 * d_model * vocab


def lm_train_flops_per_token(**shape):
    """Forward + backward: 3x the forward (the backward computes the
    gradient of each matmul's two operands). Recomputation is not
    counted, and neither is the optimizer's elementwise update."""
    return 3.0 * lm_forward_flops_per_token(**shape)


def flash_fwd(*, batch, heads, seq_len, head_dim, dtype_bytes=2,
              causal=True):
    """(flops, bytes) of one causal flash-attention forward call:
    QK^T and PV, each 2*S*S*D per head, halved by the mask; Q, K, V read
    and O written once."""
    half = 0.5 if causal else 1.0
    flops = 4.0 * batch * heads * seq_len * seq_len * head_dim * half
    nbytes = 4.0 * batch * heads * seq_len * head_dim * dtype_bytes
    return flops, nbytes


def flash_bwd(*, batch, heads, seq_len, head_dim, dtype_bytes=2,
              causal=True):
    """(flops, bytes) of the backward of one forward call, however many
    kernels it is split into: five matmuls (QK^T again because P is not
    kept, dV, dP, dQ, dK) against the forward's two; Q, K, V, O, dO read,
    dQ, dK, dV written."""
    fwd, _ = flash_fwd(batch=batch, heads=heads, seq_len=seq_len,
                       head_dim=head_dim, causal=causal)
    nbytes = 8.0 * batch * heads * seq_len * head_dim * dtype_bytes
    return 2.5 * fwd, nbytes


def paged_decode(*, context_tokens, layers, calls, heads, head_dim,
                 slots, dtype_bytes=4):
    """(flops, bytes) of the paged decode-attention calls of `calls`
    decode steps, one call per layer and step: one query per slot
    against the slot's cached rows (`context_tokens` is the sum of the
    context lengths over slots and steps, and every layer reads its own
    K and V rows of them once); QK^T and PV are 2*D each per row and
    head; the queries are read and the outputs written."""
    rows = float(context_tokens) * layers
    flops = 4.0 * rows * heads * head_dim
    nbytes = 2.0 * rows * heads * head_dim * dtype_bytes \
        + 2.0 * calls * layers * slots * heads * head_dim * dtype_bytes
    return flops, nbytes


def least_seconds(flops, nbytes, peak):
    """The roofline: the least time the chip could take, and which of
    the two peaks sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")


# -- the whole step's least (`readers/step_mfu.py`, PR 52) ------------------
# Two functions a module of an architecture gives `serve_step_mfu`'s one
# reader, both at the PUBLISHED widths of `model` and never at what the
# program stores: `decode_least_bytes(counts, **model)`, the parts of the
# least bytes the window's decode steps had to move ({"weights", "cache",
# "states"}; `counts` are the window's counters, `live_rows` the cache
# rows the steps' contexts held a layer, `block_size` beside them), and
# `pass_weight_bytes(**model)`, what ONE pass over the model reads of
# its weights whatever it routes ({"always": every weight outside the
# routed experts, the head with it; "head": the head alone, which an
# admission multiplies one row by; "expert": one routed expert;
# "routed": the routed experts a token must touch, top-k a layer where
# every expert is held here and 0 where a share is (a token's k may all
# fall on other chips)}).


def dense_decode_weight_bytes(*, decode_steps, n_layers, d_model, d_ff,
                              vocab, dtype_bytes=4, **_):
    """Weight bytes the decode steps of a GPT-2 stack must read at least
    once a step: in every layer the four attention projections and the
    two FFN matrices with their biases and the two LayerNorms; once, the
    final LayerNorm and the untied head with its bias. The embedding and
    position rows a step gathers (one a slot) and the K/V it reads are
    not weights: a floor."""
    layer = 4.0 * d_model * d_model + 4.0 * d_model \
        + 2.0 * d_model * d_ff + d_ff + d_model + 4.0 * d_model
    head = d_model * vocab + vocab + 2.0 * d_model
    return dtype_bytes * float(decode_steps) * (n_layers * layer + head)


def decode_least_bytes(counts, *, n_layers, d_model, dtype_bytes=4,
                       **model):
    """Every layer reads its own K and V row (d_model floats each) of
    every live row once."""
    return {
        "weights": dense_decode_weight_bytes(
            decode_steps=counts["decode_steps"], n_layers=n_layers,
            d_model=d_model, dtype_bytes=dtype_bytes, **model),
        "cache": dtype_bytes * 2.0 * d_model * n_layers
        * float(counts["live_rows"]),
        "states": 0.0}


def pass_weight_bytes(*, d_model, vocab, dtype_bytes=4, **model):
    return {"always": dense_decode_weight_bytes(
                decode_steps=1, d_model=d_model, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + vocab + 2.0 * d_model),
            "expert": 0.0, "routed": 0}
