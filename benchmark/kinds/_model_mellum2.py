"""The mapping of Mellum 2's published configuration (`model_type:
mellum`, `JetBrains/Mellum2-12B-A2.5B-Instruct`) onto
`paddle_tpu.models.transformer` for the TRAINER, and of the program's
weights onto `reference_mellum2.py`'s. What a mapping of a trained family
gives the kind `train_stream_mapped` (the next one gives the same):

    sizes(config)                       the published keys under the names
                                        the model builder takes
    build_trainer(pt, sz, seq_len,      (main, startup, loss, the step's
                  seed, train)          expert counts or None)
    reference_weights(lookup, layers)   the program's weights as the
                                        reference documents them
    reference_step(reference, weights,  (the reference's loss of the first
                   config, src, tgt)    step, its own counts of its routes
                                        or None)
    update_leaves(sz)                   the parameters whose first update
                                        the cell holds: ((label, the
                                        program's name, the reference's
                                        (layer, key)), ...)
    reference_gradients(reference,      the reference's gradient of one
        weights, config, src, tgt,      step's loss at each of them, as
        leaves)                         device arrays
    train_flops_per_token(sz, seq_len,  the step's model operations a token
                          pairs)
    kernel_shapes(sz, batch, seq_len,   observations the per-layer readers
                  steps, held_pairs)    price the traced kernels with

A configuration file names this module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

import flops_mellum2
from kinds._model import MAX_PROGRAM_SEED

_KIND = {"sliding_attention": "window", "full_attention": "full"}

#: the standard deviation the embedding's rows are drawn at, where the
#: program's default is 0.02 (the configuration's `assumed.weights` says
#: why: at 0.02 every router sees what attention added, one vector for
#: all tokens, and the step's work is set by the seed's draw of it)
EMBEDDING_SCALE = 1.0


def sizes(config: Dict) -> Dict:
    """What the program cannot do is refused here, not approximated."""
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if config["model_type"] != "mellum" or config["attention_bias"] \
            or config["hidden_act"] != "silu" \
            or not config["norm_topk_prob"] \
            or config["tie_word_embeddings"] \
            or not config["use_sliding_window"] \
            or set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("this block is sequential with an untied head, "
                         "RMSNorm, no bias and gated SiLU experts in "
                         "every layer under a softmax router renormalised "
                         "over the chosen; the configuration says "
                         "otherwise")
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("the rotary tables built are the sliding layers' "
                         "plain one and the full layers' YaRN")
    layers = int(config["num_hidden_layers"])
    kinds = [_KIND[k] for k in config["layer_types"]]
    period = kinds.index("full") + 1
    if layers % period or kinds[:layers] != kinds[:period] * (
            layers // period):
        raise ValueError("the depth is whole periods of layer_types")
    held = config["published"]["held_experts"]
    if int(held["count"]) != int(config["num_experts"]):
        raise ValueError("num_experts is the experts held here")
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["moe_intermediate_size"]),    # one expert's width
        n_layers=layers,
        window_layers=kinds[:layers].count("window"),
        full_layers=kinds[:layers].count("full"),
        block=dict(
            norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
            positions="rope", rope_theta=float(sliding["rope_theta"]),
            bias=False, attention="gqa", qk_norm=False,
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            ffn="moe_gated",
            num_experts=int(config["published"]["num_experts"]),
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="softmax", norm_topk=True,
            experts_first=int(held["first"]),
            experts_held=int(held["count"]),
            window=int(config["sliding_window"]),
            layer_pattern=kinds[:period],
            full_rope_theta=float(full["rope_theta"]),
            full_rope_scaling=[
                float(full[key]) for key in (
                    "factor", "original_max_position_embeddings",
                    "beta_fast", "beta_slow", "attention_factor")]))


def build_trainer(pt, sz: Dict, seq_len: int, seed: int, train: Dict):
    """The LM with its loss and optimizer on the system's normal path:
    `transformer_lm_loss` -> `AdamOptimizer.minimize`, bf16 AMP over f32
    masters; the start-up program then draws the embedding again, at
    `EMBEDDING_SCALE`. Returns (main, startup, loss, the step's expert
    counts)."""
    from paddle_tpu.initializer import NormalInitializer
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % MAX_PROGRAM_SEED
    load = []
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=sz["vocab"], seq_len=seq_len,
            n_layers=sz["n_layers"], d_model=sz["d_model"],
            n_heads=sz["n_heads"], d_ff=sz["d_ff"], max_len=seq_len,
            remat=train.get("remat", False), block=sz["block"],
            collect_moe_load=load)
        pt.optimizer.AdamOptimizer(
            learning_rate=float(train["learning_rate"])).minimize(avg)
    main.amp_dtype = train["amp_dtype"]
    block = startup.global_block
    NormalInitializer(scale=EMBEDDING_SCALE)(block.var("tok_emb"), block)
    return main, startup, avg, load[0]


_LAYER = {"ln1": "ln1_{i}_scale", "ln2": "ln2_{i}_scale",
          "q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
          "out": "attn{i}_out_w", "router": "moe{i}_router_w",
          "gate": "moe{i}_gate_w", "up": "moe{i}_up_w",
          "down": "moe{i}_down_w"}
_MODEL = {"tok_emb": "tok_emb", "ln_f": "ln_f_scale", "head": "lm_head_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_mellum2.py` documents. No
    copy is made: the reference reads the same device arrays."""
    def get(name):
        v = lookup(name)
        if v is None:
            raise KeyError(f"no weight named {name!r}")
        return v

    out = {key: get(name) for key, name in _MODEL.items()}
    out["layers"] = [{key: get(name.format(i=i))
                      for key, name in _LAYER.items()}
                     for i in range(n_layers)]
    return out


def reference_step(reference, weights: Dict, config: Dict, src, tgt):
    """The plain reference over the same share of the experts: (its mean
    loss of src, tgt [B, S]; its own counts of its routes, as the
    program's `pt_train_moe_*` count theirs)."""
    loss, counts, _, ties = reference.loss_and_counts(
        weights, src, tgt, reference.Hyper.of(config),
        int(config["num_experts"]))
    return loss, dict(counts=counts, near_tie_row_share=ties)


def update_leaves(sz: Dict):
    """What the cell's `why` names and a loss near ln(vocab) cannot see:
    q, k and v of the first window layer and of the first full layer (the
    windowed and the unwindowed flash backward, the two rotary tables'
    transposes), the first layer's router and its held experts' three
    stacked matrices (the held share's backward, every wave of it)."""
    pattern = sz["block"]["layer_pattern"]
    out = [(f"{kind}.{key}", _LAYER[key].format(i=pattern.index(kind)),
            (pattern.index(kind), key))
           for kind in ("window", "full") for key in ("q", "k", "v")]
    return out + [(f"experts.{key}", _LAYER[key].format(i=0), (0, key))
                  for key in ("router", "gate", "up", "down")]


def reference_gradients(reference, weights: Dict, config: Dict, src, tgt,
                        leaves):
    return reference.gradients(
        weights, src, tgt, reference.Hyper.of(config),
        [where for _, _, where in leaves])


def train_flops_per_token(sz: Dict, seq_len: int,
                          held_pairs_per_token=None) -> float:
    """The step's model operations a token (`flops_mellum2.py`), at the
    pairs a token and layer that fell on held experts where counted."""
    b = sz["block"]
    return flops_mellum2.train_flops_per_token(
        d_model=sz["d_model"], heads=sz["n_heads"],
        kv_heads=b["n_kv_heads"], head_dim=b["head_dim"],
        window=b["window"], window_layers=sz["window_layers"],
        full_layers=sz["full_layers"], expert_width=sz["d_ff"],
        experts=b["num_experts"], held=b["experts_held"],
        top_k=b["experts_per_tok"], vocab=sz["vocab"], seq_len=seq_len,
        held_pairs_per_token=held_pairs_per_token)


def kernel_shapes(sz: Dict, batch: int, seq_len: int, steps: int,
                  held_pairs=None) -> Dict:
    """What the kernels of `steps` traced steps are priced with
    (`flops_mellum2.py`): the full layers' flash calls, the window
    layers', and the expert layers' products at the `held_pairs` (token,
    expert) pairs the traced steps counted on held experts."""
    b = sz["block"]
    attn = dict(batch=batch, heads=sz["n_heads"], kv_heads=b["n_kv_heads"],
                seq_len=seq_len, head_dim=b["head_dim"])
    out = {"flash_full": dict(attn, calls=steps * sz["full_layers"]),
           "flash_window": dict(attn, calls=steps * sz["window_layers"],
                                window=b["window"])}
    if held_pairs is not None:
        out["expert_traced"] = dict(
            pairs=held_pairs, layer_steps=steps * sz["n_layers"],
            held=b["experts_held"], d_model=sz["d_model"],
            expert_width=sz["d_ff"])
    return out
