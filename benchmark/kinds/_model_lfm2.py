"""The mapping of LFM2-24B-A2B's published configuration (`model_type:
lfm2_moe`, `LiquidAI/LFM2-24B-A2B`) onto `paddle_tpu.models.transformer`,
and of the program's weights onto `reference_lfm2.py`'s: the functions
`_model_olmoe.py` lists, with `reference_on` in place of
`reference_on_routes` (the kind `backlog_mapped_state` asks for the
compared positions' rows alone: the whole [6,104, 65,536] logits would
not fit beside the weights). A configuration file names this module and
that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

from kinds._model import MAX_PROGRAM_SEED

#: the selection bias is drawn from the seed, normal at this scale, and
#: not left at the zeros a training run starts from: a bias that is zero
#: would leave "the bias chooses and never weighs" unexercised. At 0.005
#: (where the issue said to start: it moves 14.9% of the (token, choice)
#: pairs) the two faults of the bias hide under the precision: experts
#: chosen by the unbiased score show a shortfall of 0.025 where the
#: program's own near ties show 0.019-0.026, and weights from the biased
#: score move the logits' rms by 0.005 where the program's is 0.025 (my
#: chip run, PR 43). At Kanana's 0.05 both read ten times that (the
#: configuration's `harness.limits_why` has the readings)
ROUTER_BIAS_SCALE = 0.05

#: the gains of every head's q-norm and k-norm, where a checkpoint's are
#: trained and a start-up program's are 1: at 1 a head of 64 scores its
#: rows with a standard deviation of 1 and the softmax over 2-6 k rows
#: is nearly flat, so which rows are read hardly moves the output and a
#: wrong row hides under the precision (Keye's finding, PERF.md section
#: 6, PR 33). Drawn normal about 1.6, a quarter of it wide: the scores'
#: deviation is 1.6 x 1.6 x 1.06 = 2.7. DRAWN and not one constant: a
#: constant gain commutes with the rotation (which keeps every pair's
#: length), so a q/k-norm applied AFTER the rotation would read exactly
#: as one applied before it (3e-6 at a constant 1.6, my chip run, PR 43)
QK_GAIN = 1.6
QK_GAIN_SPREAD = 0.25

_KIND = {"conv": "conv", "full_attention": "full"}


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["model_type"] != "lfm2_moe" or config["conv_bias"] \
            or not config["use_expert_bias"] \
            or not config["norm_topk_prob"] \
            or config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("this block is gated short convolutions without "
                         "a bias beside grouped-query attention with "
                         "default rotary positions, experts chosen by "
                         "sigmoid plus a bias and renormalised over the "
                         "chosen; the configuration says otherwise")
    layers = int(config["num_hidden_layers"])
    kinds = [_KIND[k] for k in config["layer_types"]][:layers]
    # the shortest period that gives these layers
    period = next(p for p in range(1, layers + 1)
                  if all(kinds[i] == kinds[i % p] for i in range(layers)))
    heads = int(config["num_attention_heads"])
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=heads,
        d_ff=int(config["moe_intermediate_size"]),   # one expert's width
        n_layers=layers,
        state_layers=kinds.count("conv"),
        full_layers=kinds.count("full"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="rms_norm", norm_eps=float(config["norm_eps"]),
            positions="rope",
            rope_theta=float(config["rope_parameters"]["rope_theta"]),
            qk_norm=True, bias=False, attention="gqa",
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["hidden_size"]) // heads,
            ffn="moe_gated", num_experts=int(config["num_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="sigmoid_bias", norm_topk=True, norm_topk_eps=1e-6,
            routed_scale=float(config["routed_scaling_factor"]),
            dense_layers=int(config["num_dense_layers"]),
            dense_width=int(config["intermediate_size"]),
            tied_head=True, layer_pattern=kinds[:period],
            conv_taps=int(config["conv_L_cache"])))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (with rotary positions no parameter's shape
    depends on it). The start-up program then draws every layer's
    selection bias (`ROUTER_BIAS_SCALE`) over the zeros the layer gives
    it and draws every q-norm and k-norm gain about `QK_GAIN`; the taps are
    the layer's own draw, normal at 1 / sqrt(3): each of the three
    carries a third of the convolution's variance. Returns (main,
    startup)."""
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
        if not var.persistable:
            continue
        if var.name.endswith("_router_bias"):
            NormalInitializer(scale=ROUTER_BIAS_SCALE)(
                block.var(var.name), block)
        elif var.name.endswith(("_qnorm_scale", "_knorm_scale")):
            NormalInitializer(QK_GAIN, QK_GAIN_SPREAD * QK_GAIN)(
                block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_NORMS = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale"}
_CONV = {"in": "conv{i}_in_w", "taps": "conv{i}_conv_w",
         "out": "conv{i}_out_w"}
_ATTENTION = {"q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w", "q_norm": "attn{i}_qnorm_scale",
              "k_norm": "attn{i}_knorm_scale"}
_DENSE = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
          "down": "ffn{i}_down_w"}
_EXPERTS = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
            "down": "moe{i}_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_lfm2.py` documents (the head
    is the embedding: no weight of its own). A layer is a convolution
    where the program has an in-projection for it, dense where it has no
    router. No copy is made: the reference reads the same device
    arrays."""
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
        names = dict(_NORMS,
                     **(_CONV if has(f"conv{i}_in_w") else _ATTENTION),
                     **(_EXPERTS if has(f"moe{i}_router_w") else _DENSE))
        layers.append({key: get(name.format(i=i))
                       for key, name in names.items()})
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": layers}


def reference_on(reference, weights: Dict, config: Dict, ids, routes, rows):
    """The plain reference on the experts the program chose ([L_moe, S,
    k]): (logits of the compared positions `rows` [R, V], the experts'
    shortfall [L_moe, S])."""
    return reference.logits_on_routes(weights, ids,
                                      reference.Hyper.of(config), routes,
                                      rows=rows)


def kernel_shape(sz: Dict) -> Dict:
    """The one paged kernel's calls (`flops_lfm2.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], full_layers=sz["full_layers"],
                state_layers=sz["state_layers"], heads=sz["n_heads"],
                kv_heads=b["n_kv_heads"], head_dim=b["head_dim"])
