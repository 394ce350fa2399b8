"""The mapping of MiniCPM-SALA's published configuration (`model_type:
minicpm_sala`) onto `paddle_tpu.models.transformer`, and of the program's
weights onto `reference_minicpm_sala.py`'s: the functions
`_model_olmoe.py` lists, with `reference_on` in place of
`reference_on_routes` (the kind asks for the compared positions' rows
alone, on the blocks the program chose). A configuration file names this
module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: what the q and k projections' draw is multiplied by, where a
#: checkpoint's are trained. With per-head q/k-norm the scores' deviation
#: is what the projections and gains make of it; Xavier draws leave the
#: softmax over 750-2,300 pooled kernels nearly flat: every block's score
#: then ties and rounding alone chooses (Keye's finding, PERF.md section
#: 6, PR 33; the other cells' 1.6)
QK_GAIN = 1.6

_KIND = {"minicpm4": "blocksparse", "lightning-attn": "linear"}


def layer_pattern(config: Dict):
    """Every held layer's kind: the first `num_hidden_layers` entries of
    the published `mixer_types`."""
    return [_KIND[m] for m in config["mixer_types"][
        :int(config["num_hidden_layers"])]]


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["model_type"] != "minicpm_sala" \
            or config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["attn_use_rope"] or not config["lightning_use_rope"] \
            or not config["qk_norm"] or config["tie_word_embeddings"] \
            or not config["use_output_gate"] \
            or not config["use_output_norm"] \
            or not config["attn_use_output_gate"] \
            or config["lightning_scale"] != "1/sqrt(d)" \
            or int(config["lightning_nh"]) != int(
                config["num_attention_heads"]) \
            or int(config["lightning_nkv"]) != int(config["lightning_nh"]) \
            or int(config["lightning_head_dim"]) != int(config["head_dim"]):
        raise ValueError("this block is MiniCPM-SALA: block-sparse "
                         "attention without positions beside linear "
                         "attention with rotary positions, per-head "
                         "q/k-norm, output gates, an output norm on the "
                         "linear layers, a gated-SiLU FFN, an untied head, "
                         "no bias; the configuration says otherwise")
    kinds = layer_pattern(config)
    sparse = config["assumed"]["sparse_config"]
    depth = int(config["published"]["num_hidden_layers"])
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["intermediate_size"]),
        n_layers=len(kinds),
        state_layers=kinds.count("linear"),
        full_layers=kinds.count("blocksparse"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
            positions="none", bias=False, attention="gqa", qk_norm=True,
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]), ffn="gated",
            rope_theta=float(config["rope_theta"]),
            layer_pattern=kinds, layer_ids=list(range(len(kinds))),
            attn_gate=True,
            sparse_kernel=int(sparse["kernel_size"]),
            sparse_stride=int(sparse["kernel_stride"]),
            sparse_block=int(sparse["block_size"]),
            sparse_topk=int(sparse["topk"]),
            sparse_window=int(sparse["window_size"]),
            sparse_init=int(sparse["init_blocks"]),
            sparse_dense_len=int(sparse["dense_len"]),
            linear_positions="rope", decay_layers=depth,
            embed_scale=float(config["scale_emb"]),
            residual_scale=float(config["scale_depth"]) / depth ** 0.5,
            logit_scale=float(config["dim_model_base"])
            / float(config["hidden_size"]),
            ssm_chunk=128, row_chunk=2048))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (no parameter's shape depends on it). The
    start-up program then draws every q and k projection again,
    `QK_GAIN` times as wide. Returns (main, startup)."""
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


_MIXER = {"q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
          "gate": "attn{i}_gate_w", "out": "attn{i}_out_w",
          "qnorm": "attn{i}_qnorm_scale", "knorm": "attn{i}_knorm_scale",
          "ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
          "ffn_gate": "ffn{i}_gate_w", "ffn_up": "ffn{i}_up_w",
          "ffn_down": "ffn{i}_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_minicpm_sala.py` documents.
    What a layer is shows in the weights it has (a linear layer's output
    norm). No copy is made: the reference reads the same device arrays."""
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
        layer = {key: get(name.format(i=i)) for key, name in _MIXER.items()}
        if has(f"attn{i}_onorm_scale"):
            layer["onorm"] = get(f"attn{i}_onorm_scale")
        layers.append(layer)
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def reference_on(reference, weights: Dict, config: Dict, ids, choices,
                 rows, prompt_len):
    """The plain reference on the blocks the program chose ([L_s, S,
    H_kv, NB] bool), the first `prompt_len` rows one call: (logits of the
    compared positions `rows` [R, V], the choices' shortfall [L_s, S,
    H_kv])."""
    return reference.logits_on_choices(
        weights, ids, reference.Hyper.of(config), choices, rows=rows,
        prompt_len=prompt_len)


def kernel_shape(sz: Dict) -> Dict:
    """The block-sparse kernel's calls and the state update's
    (`flops_minicpm_sala.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], full_layers=sz["full_layers"],
                state_layers=sz["state_layers"], heads=sz["n_heads"],
                kv_heads=b["n_kv_heads"], head_dim=b["head_dim"])
