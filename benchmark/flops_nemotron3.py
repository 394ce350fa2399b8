"""Operations and bytes of a Nemotron-H cut (Mamba-2 layers whose state is
a matrix a head, attention layers with 16 query heads a K/V head,
two-matrix relu2 experts of which this chip holds a share), from its
shapes and the program's counters alone. Beside `flops.py` and its
siblings, which the add-only rule keeps as they are; same rule as there:
what the ALGORITHM needs, never what an implementation happens to do.
"""

from __future__ import annotations

# (flops, bytes) of the attention layers' paged attention calls: every
# live row read once a layer, its K and V of `kv_heads` heads of
# `head_dim`, each of the `heads` query heads scoring it and taking its
# value: `flops_swa.py`'s count of a full layer, at this model's widths
# (live rows x 2,048 B), under this module's name
from flops_swa import paged_full  # noqa: E402,F401


def ssd_update(*, live_slot_steps, ssm_inner, ssm_state, dtype_bytes=4,
               **_):
    """(flops, bytes) of the state update's calls: each live slot's
    matrix read once and written once a Mamba-2 layer and step
    (`live_slot_steps`: live slots summed over the traced steps AND the
    state layers); a decay, a push and a read a float (5 FLOPs): the
    bytes bound it. The rows of x, B, C and dt (a few KB a slot) are
    noise beside 2 x 2,097,152 B and are left out: a floor."""
    floats = float(live_slot_steps) * ssm_inner * ssm_state
    return 5.0 * floats, 2.0 * dtype_bytes * floats


def state_update_bytes(*, state_slot_steps, ssm_inner, ssm_state, ssm_groups,
                       conv_taps, dtype_bytes=4, **_):
    """Bytes the states cost the steps of a window: every live slot's
    state of every state layer (`state_slot_steps`,
    `pt_decode_state_slot_steps_total`) read once and written once: the
    matrix and the convolution's taps - 1 rows of x, B and C."""
    conv = (conv_taps - 1) * (ssm_inner + 2 * ssm_groups * ssm_state)
    return dtype_bytes * 2.0 * float(state_slot_steps) * (
        ssm_inner * ssm_state + conv)


def decode_experts(*, assignments, experts_touched, d_model, d_ff,
                   dtype_bytes=4, **_):
    """(flops, bytes) of the routed experts' matmuls of decode steps: 2
    FLOPs a weight for every (token, expert) pair that was computed, TWO
    matrices of d_model * d_ff each (`flops_moe.decode_experts` counts a
    gated expert's three); and every touched expert's two matrices read
    once."""
    expert = 2.0 * d_model * d_ff
    return 2.0 * assignments * expert, dtype_bytes * experts_touched * expert


def decode_weight_bytes(*, experts_touched, layer_steps, expert_layers,
                        state_layers, full_layers, d_model, d_ff,
                        shared_width, num_experts, n_heads, n_kv_heads,
                        head_dim, ssm_heads, ssm_inner, ssm_state,
                        ssm_groups, conv_taps, vocab, dtype_bytes=4, **_):
    """Weight bytes the decode steps of a window must read at least once
    a step. `experts_touched` (of the experts held here) and
    `layer_steps` are the window's `pt_decode_moe_*` counters (over the
    layers that HAVE experts). A step reads: in every Mamba-2 layer the
    in-projection (d x (2 d_i + 2 G N + H)), the taps and their bias,
    the three vectors a head, the gated norm's gain and the
    out-projection; in every attention layer its four projections; in
    every expert layer the router, its bias, the shared expert's two
    matrices and the two matrices of each held expert that received a
    token; every layer's norm; once, the head and its norm. The embedding
    rows a step gathers, the cache and the states are not weights: a
    floor."""
    steps = layer_steps / expert_layers
    width = ssm_inner + 2.0 * ssm_groups * ssm_state
    mamba = d_model * (ssm_inner + width + ssm_heads) \
        + (conv_taps + 1.0) * width + 3.0 * ssm_heads + ssm_inner \
        + ssm_inner * d_model
    attention = 2.0 * d_model * n_heads * head_dim \
        + 2.0 * d_model * n_kv_heads * head_dim
    experts = d_model * num_experts + num_experts \
        + 2.0 * d_model * shared_width
    layers = state_layers + full_layers + expert_layers
    every = state_layers * mamba + full_layers * attention \
        + expert_layers * experts + layers * d_model
    head = d_model * vocab + d_model
    return dtype_bytes * (experts_touched * 2.0 * d_model * d_ff
                          + steps * (every + head))


def decode_kv_bytes(*, paged_live_pages, block_size, full_layers,
                    n_kv_heads, head_dim, dtype_bytes=4, **_):
    """K/V bytes the decode steps of a window must read: every live page
    of the attention layers (`paged_live_pages`, a layer)."""
    row = dtype_bytes * 2.0 * n_kv_heads * head_dim
    return row * float(paged_live_pages) * block_size * full_layers


def decode_bytes(*, moe_experts_touched, moe_layer_steps, paged_live_pages,
                 state_slot_steps, block_size, **model):
    """The parts of the least bytes the decode steps of a window must
    move: {"weights", "state", "kv"}."""
    return {
        "weights": decode_weight_bytes(
            experts_touched=moe_experts_touched,
            layer_steps=moe_layer_steps, **model),
        "state": state_update_bytes(state_slot_steps=state_slot_steps,
                                    **model),
        "kv": decode_kv_bytes(paged_live_pages=paged_live_pages,
                              block_size=block_size, **model)}


# -- the whole step's least (`flops.py` has the two functions' text) --------

def decode_least_bytes(counts, **model):
    """`decode_bytes`' parts, an expert at its PUBLISHED 1,856 x 2,688
    (the program stores it wider: what it reads of its padding is its
    own cost and lowers its share)."""
    parts = decode_bytes(
        moe_experts_touched=counts["moe_experts_touched"],
        moe_layer_steps=counts["moe_layer_steps"],
        paged_live_pages=float(counts["live_rows"]) / counts["block_size"],
        state_slot_steps=counts["state_slot_steps"],
        block_size=counts["block_size"], **model)
    return {"weights": parts["weights"], "cache": parts["kv"],
            "states": parts["state"]}


def pass_weight_bytes(*, expert_layers, d_model, d_ff, vocab,
                      dtype_bytes=4, **model):
    """A share of the experts is held: a token's six may all fall on
    other chips, so no routed expert is counted for an admission."""
    return {"always": decode_weight_bytes(
                experts_touched=0, layer_steps=expert_layers,
                expert_layers=expert_layers, d_model=d_model, d_ff=d_ff,
                vocab=vocab, dtype_bytes=dtype_bytes, **model),
            "head": dtype_bytes * (d_model * vocab + d_model),
            "expert": dtype_bytes * 2.0 * d_model * d_ff, "routed": 0}
