"""The mapping of OLMoE's published configuration (Hugging Face
`OlmoeConfig` keys) onto `paddle_tpu.models.transformer`, and of the
program's weights onto `reference_olmoe.py`'s. A configuration file
names this module and that reference under `harness`; the kind
`backlog_mapped` asks nothing else of an architecture.

What a mapping module gives (the next architecture writes these six
and no kind):

  sizes(config)                       the builders' arguments
  build_params_only(pt, sz, seed)     (main, startup): the LM as a
                                      server is given it
  export_cfg(sz)                      `io.export_decode_model`'s
                                      `model_cfg`
  reference_weights(get, n_layers)    the program's weights, `get(name)`
                                      each, as the reference takes them
  reference_on_routes(reference, weights, config, ids, routes)
                                      the plain reference's [S, V]
                                      logits on those weights and on
                                      the experts the program chose
                                      ([L, S, k]; None without
                                      experts), and the shortfall
                                      [L, S] of that choice (or None)
  kernel_shape(sz)                    layers / heads / head_dim of the
                                      paged decode kernel's calls
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped-query attention is not built yet")
    if config.get("clip_qkv") is not None or config.get("rope_scaling"):
        raise ValueError("clip_qkv / rope_scaling are not built")
    if config["norm_topk_prob"]:
        raise ValueError("gates renormalised over the chosen experts "
                         "are not built")
    if config["attention_bias"] or config["tie_word_embeddings"] \
            or config["hidden_act"] != "silu":
        raise ValueError("OLMoE's block has no bias, an untied head and "
                         "SiLU gates; this configuration says otherwise")
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["intermediate_size"]),      # one expert's width
        n_layers=int(config["num_hidden_layers"]),
        max_len=int(config["max_position_embeddings"]),
        block=dict(norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
                   positions="rope",
                   rope_theta=float(config["rope_theta"]), qk_norm=True,
                   bias=False, ffn="moe_gated",
                   num_experts=int(config["num_experts"]),
                   experts_per_tok=int(config["num_experts_per_tok"])))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given.
    Built at a short length, not at `max_len`: only its start-up
    program runs, and with rotary positions no parameter's shape depends
    on the length. Returns (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % MAX_PROGRAM_SEED
    with pt.program_guard(main, startup):
        src = layers.data("src_ids", [16], dtype="int64")
        tfm.transformer_lm(src, sz["vocab"], n_layers=sz["n_layers"],
                           d_model=sz["d_model"], n_heads=sz["n_heads"],
                           d_ff=sz["d_ff"], max_len=sz["max_len"],
                           block=sz["block"])
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_olmoe.py` documents.
    `lookup(name)` is a scope's `find_var` or the server's
    `weights.__getitem__`. No copy is made: the reference reads the same
    device arrays."""
    def get(name):
        v = lookup(name)
        if v is None:
            raise KeyError(f"no weight named {name!r}")
        return v

    layers = [{
        "ln1": get(f"ln1_{i}_scale"), "ln2": get(f"ln2_{i}_scale"),
        "q": get(f"attn{i}_q_w"), "k": get(f"attn{i}_k_w"),
        "v": get(f"attn{i}_v_w"), "out": get(f"attn{i}_out_w"),
        "q_norm": get(f"attn{i}_qnorm_scale"),
        "k_norm": get(f"attn{i}_knorm_scale"),
        "router": get(f"moe{i}_router_w"), "gate": get(f"moe{i}_gate_w"),
        "up": get(f"moe{i}_up_w"), "down": get(f"moe{i}_down_w"),
    } for i in range(n_layers)]
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def reference_on_routes(reference, weights: Dict, config: Dict, ids,
                        routes):
    return reference.logits_on_routes(weights, ids,
                                      reference.Hyper.of(config), routes)


def kernel_shape(sz: Dict) -> Dict:
    return dict(layers=sz["n_layers"], heads=sz["n_heads"],
                head_dim=sz["d_model"] // sz["n_heads"])
