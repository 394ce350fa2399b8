"""The mapping of Keye-VL-2.0-30B-A3B's published configuration (its
language model's keys: Hugging Face `Qwen3MoeConfig`'s plus `sa_config`,
`Kwai-Keye/Keye-VL-2.0-30B-A3B`) onto `paddle_tpu.models.transformer`,
and of the program's weights onto `reference_keye.py`'s: the functions
`_model_olmoe.py` lists, with `reference_on` in place of
`reference_on_routes` (the kind `backlog_mapped_sel` hands it the
program's selections beside its routes). A configuration file names this
module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: the gain of every head's q-norm and k-norm, where a checkpoint's are
#: trained and a start-up program's are 1. With random projections and
#: gains of 1 a head's scores over 3,072 rows have a standard deviation of
#: 1 and its softmax is nearly flat: the attention's output is a fortieth
#: of the stream, and a selection that is ignored or wrong then moves the
#: logits by less than the precision does. At 1.5 the scores' deviation is
#: 2.25, a head's weight sits on tens of rows, and which rows are read
#: decides the output (the configuration's `assumed.qk_gain` has the
#: readings).
QK_GAIN = 1.5


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    sa = config["sa_config"]
    rope = config.get("rope_scaling") or {}
    if rope.get("rope_type", "default") != "default" \
            or sum(rope.get("mrope_section", [])) * 2 != config["head_dim"]:
        raise ValueError("the rotation built is plain RoPE over the whole "
                         "head (M-RoPE's three sections reading one text "
                         "position); another rope_type is not built")
    if config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("every layer has experts in this block")
    if config["use_sliding_window"] or config["sliding_window"]:
        raise ValueError("a sliding window is not built")
    if not config["norm_topk_prob"]:
        raise ValueError("gates left unnormalised are OLMoE's block")
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu":
        raise ValueError("this block has no bias, an untied head and SiLU "
                         "gates; the configuration says otherwise")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer built has one key head")
    if config["num_local_experts"] != config["num_experts"]:
        raise ValueError("num_local_experts and num_experts differ")
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
            qk_norm=True, bias=False, attention="gqa",
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            index_heads=int(sa["indexer_num_heads"]),
            index_head_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]),
            ffn="moe_gated", num_experts=int(config["num_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            norm_topk=True))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (with rotary positions no parameter's shape
    depends on it). The start-up program then sets every q-norm and
    k-norm gain to `QK_GAIN` over the 1 the layer gives it. Returns
    (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.initializer import ConstantInitializer
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
        if var.persistable and var.name.endswith(("_qnorm_scale",
                                                  "_knorm_scale")):
            ConstantInitializer(QK_GAIN)(block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_LAYER = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
          "q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
          "out": "attn{i}_out_w", "q_norm": "attn{i}_qnorm_scale",
          "k_norm": "attn{i}_knorm_scale", "iq": "attn{i}_iq_w",
          "ik": "attn{i}_ik_w", "iw": "attn{i}_iw_w",
          "ik_norm": "attn{i}_iknorm_scale",
          "ik_bias": "attn{i}_iknorm_bias", "router": "moe{i}_router_w",
          "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
          "down": "moe{i}_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_keye.py` documents. No copy
    is made: the reference reads the same device arrays."""
    def get(name):
        v = lookup(name)
        if v is None:
            raise KeyError(f"no weight named {name!r}")
        return v

    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"),
            "layers": [{key: get(name.format(i=i))
                        for key, name in _LAYER.items()}
                       for i in range(n_layers)]}


def reference_on(reference, weights: Dict, config: Dict, ids, routes,
                 masks, rows):
    """The plain reference on the experts the program chose ([L, S, k])
    and on what every row's attention read (bool [L, S, S]): (logits of
    the compared positions `rows` [R, V], the experts' shortfall [L, S],
    the selections' [L, S])."""
    return reference.logits_on(weights, ids, reference.Hyper.of(config),
                               routes, masks, rows=rows)


def kernel_shape(sz: Dict) -> Dict:
    """The sparse layer's two kernels' calls (`flops_dsa.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], heads=sz["n_heads"],
                kv_heads=b["n_kv_heads"], head_dim=b["head_dim"],
                index_heads=b["index_heads"],
                index_dim=b["index_head_dim"], topk=b["index_topk"])
