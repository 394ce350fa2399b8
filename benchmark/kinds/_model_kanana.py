"""The mapping of Kanana-2's published configuration (Hugging Face
`DeepseekV3Config` keys, `kakaocorp/kanana-2-30b-a3b-instruct-2601`) onto
`paddle_tpu.models.transformer`, and of the program's weights onto
`reference_kanana.py`'s: the six functions `_model_olmoe.py` lists. A
configuration file names this module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: the selection bias is drawn from the seed, normal at this scale, and
#: not left at the zeros a training run starts from: a bias that is zero
#: would leave "the bias chooses and never weighs" unexercised
ROUTER_BIAS_SCALE = 0.05


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention shares one latent among all "
                         "heads; num_key_value_heads must equal "
                         "num_attention_heads")
    if config.get("q_lora_rank") is not None or config.get("rope_scaling"):
        raise ValueError("a query low-rank (q_lora_rank) and rope_scaling "
                         "are not built")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or config["moe_layer_freq"] != 1:
        raise ValueError("the router built is sigmoid scores with a "
                         "selection bias (noaux_tc) in every layer after "
                         "the leading dense ones")
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu":
        raise ValueError("this block has no bias, an untied head and SiLU "
                         "gates; the configuration says otherwise")
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not nope + rope")
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["moe_intermediate_size"]),   # one expert's width
        n_layers=int(config["num_hidden_layers"]),
        # the longest sequence this deployment serves: with rotary
        # positions no weight depends on it, it sizes the block table
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
            positions="rope", rope_theta=float(config["rope_theta"]),
            bias=False, attention="latent",
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            rope_interleave=bool(config["rope_interleave"]),
            ffn="moe_gated", num_experts=int(config["n_routed_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="sigmoid_bias",
            norm_topk=bool(config["norm_topk_prob"]),
            routed_scale=float(config["routed_scaling_factor"]),
            shared_width=int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"]),
            dense_layers=int(config["first_k_dense_replace"]),
            dense_width=int(config["intermediate_size"])))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (with rotary positions no parameter's shape
    depends on it). The start-up program then draws every layer's
    selection bias (`ROUTER_BIAS_SCALE`) over the zeros the layer gives
    it. Returns (main, startup)."""
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
        if var.persistable and var.name.endswith("_router_bias"):
            NormalInitializer(scale=ROUTER_BIAS_SCALE)(
                block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_ATTENTION = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
              "q": "attn{i}_q_w", "kva": "attn{i}_kva_w",
              "kv_norm": "attn{i}_kvnorm_scale", "kvb": "attn{i}_kvb_w",
              "out": "attn{i}_out_w"}
_DENSE = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
          "down": "ffn{i}_down_w"}
_EXPERTS = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w", "shared_gate": "moe{i}_shared_gate_w",
            "shared_up": "moe{i}_shared_up_w",
            "shared_down": "moe{i}_shared_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_kanana.py` documents. A
    layer is dense where the program has no router for it. No copy is
    made: the reference reads the same device arrays."""
    def get(name):
        v = lookup(name)
        if v is None:
            raise KeyError(f"no weight named {name!r}")
        return v

    def has(name):
        try:
            return lookup(name) is not None
        except KeyError:
            return False

    layers = []
    for i in range(n_layers):
        names = dict(_ATTENTION, **(_EXPERTS if has(f"moe{i}_router_w")
                                    else _DENSE))
        layers.append({key: get(name.format(i=i))
                       for key, name in names.items()})
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def reference_on_routes(reference, weights: Dict, config: Dict, ids,
                        routes):
    return reference.logits_on_routes(weights, ids,
                                      reference.Hyper.of(config), routes)


def kernel_shape(sz: Dict) -> Dict:
    """The latent paged kernel's calls: a row's floats that carry the
    token and the floats of them that are its value."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], heads=sz["n_heads"],
                row_floats=b["kv_lora_rank"] + b["qk_rope_head_dim"],
                value_floats=b["kv_lora_rank"])
