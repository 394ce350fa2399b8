"""The mapping of GLM-5's published configuration (`model_type:
glm_moe_dsa`, `zai-org/GLM-5`) onto `paddle_tpu.models.transformer`, and of
the program's weights onto `reference_glm5.py`'s: the functions
`_model_olmoe.py` lists, with `reference_on` in place of
`reference_on_routes` (the kind `backlog_mapped_sel` hands it the
program's selections beside its routes). A configuration file names this
module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: the selection bias is drawn from the seed, normal at this scale, as
#: Kanana's: a bias that is zero would leave "the bias chooses and never
#: weighs" unexercised
ROUTER_BIAS_SCALE = 0.05

#: the gains of the two low-rank norms, the query's (`gq`) and the
#: latent's (`gkv`), where a start-up program's are 1 and a checkpoint's
#: are trained. With Xavier projections and gains of 1 a head's scores
#: over thousands of rows have a standard deviation of a third: the
#: softmax is flat, the attention's output is a sliver of the stream, and
#: a selection that is ignored or wrong moves the logits by less than the
#: precision does (Keye's finding, PERF.md section 6, PR 33). A head's
#: score is (q_nope . k_nope + q_rope . k_rope) / 16 with q ~ gq, k_nope ~
#: gkv and k_rope (projected straight from the normed stream) ~ 1.35: its
#: variance is gq^2 (0.102 + 0.0058 gkv^2). The configuration's
#: `assumed.low_rank_gain` has the readings at these values.
Q_GAIN = 4.0
KV_GAIN = 4.0


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    rope = config.get("rope_parameters") or {}
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if rope.get("rope_type", "default") != "default":
        raise ValueError("the rotation built is the plain table; a "
                         f"rope_type of {rope.get('rope_type')!r} is not "
                         "built")
    if config.get("index_topk") and not config.get("q_lora_rank"):
        raise ValueError("the indexer projects its query heads from the "
                         "query's low-rank: an indexer without "
                         "q_lora_rank is not built")
    if int(config.get("num_nextn_predict_layers", 0)) > 0 \
            or "num_nextn_predict_layers" not in config["reduced"]:
        raise ValueError("multi-token prediction is not built: the file "
                         "sets num_nextn_predict_layers to 0 and lists it "
                         "under reduced")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention shares one latent among all "
                         "heads; num_key_value_heads must equal "
                         "num_attention_heads")
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
                                 + config["qk_rope_head_dim"]) \
            or config["head_dim"] != config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is nope + rope, and head_dim the "
                         "rotary width")
    held = config["published"]["held_experts"]
    if int(held["count"]) != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts is the experts held here")
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["moe_intermediate_size"]),   # one expert's width
        n_layers=int(config["num_hidden_layers"]),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
            positions="rope", rope_theta=float(rope["rope_theta"]),
            bias=False, attention="latent",
            kv_lora_rank=int(config["kv_lora_rank"]),
            qk_nope_head_dim=int(config["qk_nope_head_dim"]),
            qk_rope_head_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
            rope_interleave=bool(config["rope_interleave"]),
            q_lora_rank=int(config["q_lora_rank"]),
            index_heads=int(config["index_n_heads"]),
            index_head_dim=int(config["index_head_dim"]),
            index_topk=int(config["index_topk"]),
            index_rope_dim=int(config["qk_rope_head_dim"]),
            index_rope_interleave=bool(config["indexer_rope_interleave"]),
            ffn="moe_gated",
            num_experts=int(config["published"]["n_routed_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="sigmoid_bias",
            norm_topk=bool(config["norm_topk_prob"]),
            routed_scale=float(config["routed_scaling_factor"]),
            shared_width=int(config["n_shared_experts"])
            * int(config["moe_intermediate_size"]),
            dense_layers=int(config["first_k_dense_replace"]),
            dense_width=int(config["intermediate_size"]),
            experts_first=int(held["first"]),
            experts_held=int(held["count"]),
            # the dense layer's FFN a chunk of rows at a time: 12,288 rows
            # of 12,288 would be 0.6 GB a product
            row_chunk=2048))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (with rotary positions no parameter's shape
    depends on it). The start-up program then draws every layer's
    selection bias (`ROUTER_BIAS_SCALE`) over the zeros the layer gives
    it, and sets the two low-rank norms' gains (`Q_GAIN`, `KV_GAIN`) over
    their 1. Returns (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.initializer import ConstantInitializer, NormalInitializer
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
    gains = {"_qnorm_scale": Q_GAIN, "_kvnorm_scale": KV_GAIN}
    for var in main.list_vars():
        if not var.persistable:
            continue
        if var.name.endswith("_router_bias"):
            NormalInitializer(scale=ROUTER_BIAS_SCALE)(
                block.var(var.name), block)
        for suffix, gain in gains.items():
            if var.name.endswith(suffix):
                ConstantInitializer(gain)(block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_ATTENTION = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
              "qa": "attn{i}_qa_w", "q_norm": "attn{i}_qnorm_scale",
              "qb": "attn{i}_qb_w", "kva": "attn{i}_kva_w",
              "kv_norm": "attn{i}_kvnorm_scale", "kvb": "attn{i}_kvb_w",
              "out": "attn{i}_out_w", "iq": "attn{i}_iq_w",
              "ik": "attn{i}_ik_w", "iw": "attn{i}_iw_w",
              "ik_norm": "attn{i}_iknorm_scale",
              "ik_bias": "attn{i}_iknorm_bias"}
_DENSE = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
          "down": "ffn{i}_down_w"}
_EXPERTS = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w", "shared_gate": "moe{i}_shared_gate_w",
            "shared_up": "moe{i}_shared_up_w",
            "shared_down": "moe{i}_shared_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_glm5.py` documents. A layer
    is dense where the program has no router for it. No copy is made:
    the reference reads the same device arrays."""
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


def reference_on(reference, weights: Dict, config: Dict, ids, routes,
                 masks, rows):
    """The plain reference on the experts the program chose ([Le, S, k])
    and on what every row's attention read (bool [L, S, S]): (logits of
    the compared positions `rows` [R, V], the experts' shortfall [Le, S],
    the selections' [L, S])."""
    return reference.logits_on(weights, ids, reference.Hyper.of(config),
                               routes, masks, rows=rows)


def kernel_shape(sz: Dict) -> Dict:
    """The sparse latent layer's two kernels' calls (`flops_glm5.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], heads=sz["n_heads"],
                row_floats=b["kv_lora_rank"] + b["qk_rope_head_dim"],
                value_floats=b["kv_lora_rank"],
                index_heads=b["index_heads"],
                index_dim=b["index_head_dim"], topk=b["index_topk"])
