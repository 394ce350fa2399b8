"""The mapping of Command A+'s published configuration (`model_type:
cohere2_moe`, `CohereLabs/command-a-plus-05-2026`) onto
`paddle_tpu.models.transformer`, and of the program's weights onto
`reference_cmda.py`'s: the functions `_model_olmoe.py` lists, with
`reference_on` in place of `reference_on_routes` (the kind
`backlog_mapped_win` asks for the compared positions' rows alone: the
whole [6,148, 32,768] logits would not fit beside the weights). A
configuration file names this module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: what the q and k projections' draw is multiplied by, where a
#: checkpoint's are trained. Xavier draws give a head's scores over a
#: context a standard deviation of 0.8: the softmax over 4,096-6,144 rows
#: is nearly flat, which rows are read hardly moves the output, and a
#: window one row long or short would hide under the precision (Keye's
#: finding, PERF.md section 6, PR 33). At 1.8 each the scores' deviation
#: is 2.6, a head's weight sits on tens of rows and the window's edge
#: shows (the configuration's `assumed.qk_gain` has the readings).
QK_GAIN = 1.8

_KIND = {"sliding_attention": "window", "full_attention": "full"}


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["expert_selection_fn"] != "sigmoid" \
            or not config["norm_topk_prob"]:
        raise ValueError("the router built is the sigmoid rule "
                         "renormalised over the chosen")
    if config["position_embedding_type"] != "rope_gptj" \
            or config["rotary_pct"] != 1 \
            or config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("the rotation built is interleaved RoPE over the "
                         "whole head")
    if not (config["use_parallel_block"] and config["tie_word_embeddings"]
            and config["use_gated_activation"]) \
            or config["use_qk_norm"] or config["attention_bias"] \
            or config["hidden_act"] != "silu" \
            or config["first_k_dense_replace"] \
            or config["shared_expert_combination_strategy"] != "average" \
            or config["logit_scale"] != 1:
        raise ValueError("this block is parallel with a tied head and "
                         "gated SiLU experts, shared experts averaged, no "
                         "leading dense layer, no bias, no q/k-norm, no "
                         "logit scale; the configuration says otherwise")
    layers = int(config["num_hidden_layers"])
    period = int(config["layer_switch"])
    kinds = [_KIND[k] for k in config["layer_types"]]
    if layers % period or kinds[:layers] != kinds[:period] * (
            layers // period):
        raise ValueError("the depth is whole periods of layer_types")
    held = config["published"]["held_experts"]
    if int(held["count"]) != int(config["num_experts"]):
        raise ValueError("num_experts is the experts held here")
    shared = int(config["num_shared_experts"])
    width = int(config["intermediate_size"])
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=width,                              # one expert's width
        n_layers=layers,
        window_layers=kinds[:layers].count("window"),
        full_layers=kinds[:layers].count("full"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="layer_norm_gain",
            norm_eps=float(config["layer_norm_eps"]),
            positions="rope", rope_theta=float(config["rope_theta"]),
            rope_interleave=True, bias=False, attention="gqa",
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            ffn="moe_gated",
            num_experts=int(config["published"]["num_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="sigmoid", norm_topk=True,
            shared_width=shared * width, shared_scale=1.0 / shared,
            experts_first=int(held["first"]),
            experts_held=int(held["count"]), parallel=True,
            tied_head=True, window=int(config["sliding_window"]),
            layer_pattern=kinds[:period], full_positions="none"))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (with rotary positions no parameter's shape
    depends on it). The start-up program then draws every q and k
    projection again, `QK_GAIN` times as wide. Returns (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % MAX_PROGRAM_SEED
    with pt.program_guard(main, startup):
        src = layers.data("src_ids", [16], dtype="int64")
        tfm.transformer_lm(src, sz["vocab"], n_layers=sz["n_layers"],
                           d_model=sz["d_model"], n_heads=sz["n_heads"],
                           d_ff=sz["d_ff"], max_len=sz["max_len"],
                           block=sz["block"])
    block = startup.global_block
    for var in main.list_vars():
        if var.persistable and var.name.endswith(("_q_w", "_k_w")):
            fan_in, fan_out = var.shape
            NormalInitializer(scale=QK_GAIN * (2.0 / (fan_in + fan_out))
                              ** 0.5)(block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_LAYER = {"ln": "ln1_{i}_scale", "q": "attn{i}_q_w", "k": "attn{i}_k_w",
          "v": "attn{i}_v_w", "out": "attn{i}_out_w",
          "router": "moe{i}_router_w", "gate": "moe{i}_gate_w",
          "up": "moe{i}_up_w", "down": "moe{i}_down_w",
          "shared_gate": "moe{i}_shared_gate_w",
          "shared_up": "moe{i}_shared_up_w",
          "shared_down": "moe{i}_shared_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_cmda.py` documents (the head
    is the embedding: no weight of its own). No copy is made: the
    reference reads the same device arrays."""
    def get(name):
        v = lookup(name)
        if v is None:
            raise KeyError(f"no weight named {name!r}")
        return v

    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": [{key: get(name.format(i=i))
                        for key, name in _LAYER.items()}
                       for i in range(n_layers)]}


def reference_on(reference, weights: Dict, config: Dict, ids, routes, rows):
    """The plain reference, over the same share of the experts, on the
    experts the program chose ([L, S, k]): (logits of the compared
    positions `rows` [R, V], the experts' shortfall [L, S])."""
    return reference.logits_on_routes(weights, ids,
                                      reference.Hyper.of(config), routes,
                                      rows=rows)


def kernel_shape(sz: Dict) -> Dict:
    """The two paged kernels' calls (`flops_swa.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], window_layers=sz["window_layers"],
                full_layers=sz["full_layers"], window=b["window"],
                heads=sz["n_heads"], kv_heads=b["n_kv_heads"],
                head_dim=b["head_dim"])
