"""Bytes a decode step of a model with experts must read, from its
shapes and the routing counters alone. Beside `flops.py`, which the
add-only rule keeps as it is; same rule as there: what the ALGORITHM
needs, never what an implementation happens to do.
"""

from __future__ import annotations


def decode_experts(*, assignments, experts_touched, d_model, d_ff,
                   dtype_bytes=4, **_):
    """(flops, bytes) of the expert matmuls of decode steps: 2 FLOPs a
    weight for every routed (token, expert) pair, three matrices of
    d_model * d_ff each; and every touched expert's three matrices read
    once (a step's rows, 16 x d_model, are nothing beside them).
    `assignments` and `experts_touched` are `pt_decode_moe_*` counts
    over the steps in question, summed over layers."""
    expert = 3.0 * d_model * d_ff
    return 2.0 * assignments * expert, dtype_bytes * experts_touched * expert


def decode_weight_bytes(*, experts_touched, layer_steps, n_layers,
                        d_model, d_ff, num_experts, vocab,
                        dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least
    once a step: each expert that received a token, its three matrices
    (gate, up, down: 3 * d_model * d_ff); and in every layer of every
    step the four attention projections, the router and the norms'
    gains; and once a step the head and its norm. `experts_touched` and
    `layer_steps` are the window's `pt_decode_moe_*` counters (summed
    over layers and steps). The embedding rows a step gathers (one a
    slot) and the K/V it reads are not weights and are left out, so this
    is a floor."""
    steps = layer_steps / n_layers
    expert = 3.0 * d_model * d_ff
    layer = 4.0 * d_model * d_model + d_model * num_experts \
        + 4.0 * d_model            # ln1, ln2, q-norm, k-norm
    head = d_model * vocab + d_model
    return dtype_bytes * (experts_touched * expert + layer_steps * layer
                          + steps * head)


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, *, n_layers, d_model, dtype_bytes=4,
                       **model):
    """Every layer reads its own K and V row (all heads: d_model floats
    each) of every live row once."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=counts["moe_experts_touched"],
            layer_steps=counts["moe_layer_steps"], n_layers=n_layers,
            d_model=d_model, dtype_bytes=dtype_bytes, **model),
        "cache": dtype_bytes * 2.0 * d_model * n_layers
        * float(counts["live_rows"]),
        "states": 0.0}


def pass_weight_bytes(*, n_layers, d_model, d_ff, vocab, experts_per_tok,
                      dtype_bytes=4, **model):
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=n_layers, n_layers=n_layers,
                d_model=d_model, d_ff=d_ff, vocab=vocab,
                dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 3.0 * d_model * d_ff,
            "routed": experts_per_tok * n_layers}
