"""The mapping of NVIDIA-Nemotron-3-Nano-30B-A3B's published configuration
(`model_type: nemotron_h`) onto `paddle_tpu.models.transformer`, and of
the program's weights onto `reference_nemotron3.py`'s: the functions
`_model_olmoe.py` lists, with `reference_on` in place of
`reference_on_routes` (the kind asks for the compared positions' rows
alone). A configuration file names this module and that reference under
`harness`.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from kinds._model import MAX_PROGRAM_SEED

#: the selection bias is drawn from the seed and not left at the zeros a
#: training run starts from: a bias that is zero would leave "the bias
#: chooses and never weighs" unexercised. Every chip's share of the
#: experts gets the SAME multiset of biases (`balanced_bias`), permuted by
#: the seed: a trained selection bias is what balances the experts'
#: loads, so no share of a deployment is hotter than another by the
#: bias's draw. At LFM2's 0.05 drawn over all 128 at once the 32 held
#: experts took 22.1-24.7% of the pairs from one seed to the next, an
#: expert whose bias was under -0.094 could never be chosen, 24.7-26.6 of
#: 32 were touched a layer and step and the cell's rate moved with them
#: (4,169-4,344 tokens/s in four runs); at 0.03 28.5-29.3 are touched,
#: three seeds read within 1.3%, and gates from s + bias still read twice
#: the check's limit (my chip runs, PR 51; PERF.md section 6)
ROUTER_BIAS_SCALE = 0.03

#: what the q and k projections' draw is multiplied by, where a
#: checkpoint's are trained. Xavier draws give a head of 128 scores with
#: a deviation near 1: a softmax over 1-5 k rows is nearly flat, which
#: rows are read hardly moves the output, and a rotation that should not
#: be there would hide under the precision (Keye's finding, PERF.md
#: section 6, PR 33; Phi-4's and LFM2's 1.6)
QK_GAIN = 1.6

_KIND = {"M": "mamba2", "E": "ffn", "*": "attn"}


def balanced_bias(rng, n_experts: int, share: int) -> np.ndarray:
    """A selection bias [n_experts]: in every run of `share` experts (a
    chip's) the same `share` values, a fixed sample of the normal scaled
    to a mean of 0 and a deviation of `ROUTER_BIAS_SCALE`, in an order of
    `rng`'s."""
    values = np.sort(np.random.RandomState(share).randn(share))
    values = ROUTER_BIAS_SCALE * (values - values.mean()) / values.std()
    return np.concatenate([rng.permutation(values)
                           for _ in range(n_experts // share)])


def layer_pattern(config: Dict):
    """Every held layer's kind: the first `num_hidden_layers` letters of
    the published `hybrid_override_pattern`."""
    return [_KIND[c] for c in config["hybrid_override_pattern"][
        :int(config["num_hidden_layers"])]]


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["model_type"] != "nemotron_h" \
            or config["mlp_hidden_act"] != "relu2" \
            or config["mamba_hidden_act"] != "silu" \
            or int(config["n_group"]) != 1 \
            or int(config["topk_group"]) != 1 \
            or int(config["n_shared_experts"]) != 1 \
            or not config["norm_topk_prob"] \
            or not config["use_conv_bias"] or config["use_bias"] \
            or config["mamba_proj_bias"] or config["attention_bias"] \
            or config["mlp_bias"] or config["tie_word_embeddings"] \
            or config["sliding_window"] is not None:
        raise ValueError("this block is Nemotron-H: Mamba-2 layers with a "
                         "biased convolution, attention without a window, "
                         "two-matrix relu2 experts chosen by sigmoid plus "
                         "a bias with no group limit and renormalised, one "
                         "shared expert, an untied head, no other bias; "
                         "the configuration says otherwise")
    kinds = layer_pattern(config)
    heads = int(config["mamba_num_heads"])
    inner = heads * int(config["mamba_head_dim"])
    whole = int(config["published"]["n_routed_experts"])
    held = int(config["n_routed_experts"])
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        d_ff=int(config["moe_intermediate_size"]),   # one expert's width
        n_layers=len(kinds),
        state_layers=kinds.count("mamba2"),
        full_layers=kinds.count("attn"),
        expert_layers=kinds.count("ffn"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        time_step=(float(config["time_step_min"]),
                   float(config["time_step_max"]),
                   float(config["time_step_floor"])),
        block=dict(
            norm="rms_norm", norm_eps=float(config["layer_norm_epsilon"]),
            positions="none", bias=False, attention="gqa",
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["head_dim"]),
            ffn="moe_gated", num_experts=whole,
            experts_per_tok=int(config["num_experts_per_tok"]),
            router="sigmoid_bias", norm_topk=True,
            routed_scale=float(config["routed_scaling_factor"]),
            shared_width=int(
                config["moe_shared_expert_intermediate_size"]),
            expert_form="relu2",
            experts_first=int(config.get("experts_first", 0)),
            experts_held=held if held != whole else 0,
            layer_pattern=kinds, conv_taps=int(config["conv_kernel"]),
            ssm_inner=inner, ssm_state=int(config["ssm_state_size"]),
            ssm_heads=heads, ssm_groups=int(config["n_groups"]),
            ssm_chunk=int(config["chunk_size"])))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (no parameter's shape depends on it). The
    start-up program then draws, from the seed: every q and k projection
    again, `QK_GAIN` times as wide; every layer's selection bias
    (`balanced_bias`); and the scans' vectors as `mamba_ssm` starts
    them: A uniform in [1, 16] (`a_log` its log), the step bias the
    inverse softplus of a log-uniform draw in [`time_step_min`,
    `time_step_max`] floored at `time_step_floor`; `d_skip` is the
    layer's own 1, moved a fifth about it so that dropping it shows in
    every head; the convolution's bias uniform in +-1/2 (a depthwise
    Conv1d of 4 taps as PyTorch starts it: the layer's own zeros would
    leave the bias unexercised). Returns (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.initializer import (NormalInitializer,
                                        NumpyArrayInitializer)
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
    rng = np.random.RandomState(seed % (2 ** 32))
    lo, hi, floor = sz["time_step"]

    def fixed(name, values):
        NumpyArrayInitializer(np.asarray(values, "float32"))(
            block.var(name), block)

    for var in main.list_vars():
        if not var.persistable:
            continue
        if var.name.endswith(("_q_w", "_k_w")):
            fan_in, fan_out = var.shape
            NormalInitializer(scale=QK_GAIN * (2.0 / (fan_in + fan_out))
                              ** 0.5)(block.var(var.name), block)
        elif var.name.endswith("_router_bias"):
            fixed(var.name, balanced_bias(
                rng, var.shape[0],
                sz["block"]["experts_held"] or var.shape[0]))
        elif var.name.endswith("_dt_b"):
            steps = np.maximum(np.exp(rng.uniform(
                np.log(lo), np.log(hi), var.shape)), floor)
            fixed(var.name, np.log(np.expm1(steps)))
        elif var.name.endswith("_a_log"):
            fixed(var.name, np.log(rng.uniform(1.0, 16.0, var.shape)))
        elif var.name.endswith("_d_skip"):
            fixed(var.name, 1.0 + 0.2 * rng.randn(*var.shape))
        elif var.name.endswith("_conv_b"):
            fixed(var.name, rng.uniform(-0.5, 0.5, var.shape))
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_MAMBA = {"in": "mamba{i}_in_w", "conv_w": "mamba{i}_conv_w",
          "conv_b": "mamba{i}_conv_b", "dt_b": "mamba{i}_dt_b",
          "a_log": "mamba{i}_a_log", "d_skip": "mamba{i}_d_skip",
          "norm": "mamba{i}_norm_scale", "out": "mamba{i}_out_w"}
_ATTENTION = {"q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w"}
_EXPERTS = {"router": "moe{i}_router_w", "router_bias": "moe{i}_router_bias",
            "up": "moe{i}_up_w", "down": "moe{i}_down_w",
            "shared_up": "moe{i}_shared_up_w",
            "shared_down": "moe{i}_shared_down_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_nemotron3.py` documents. What
    a layer is shows in the weights it has. No copy is made: the
    reference reads the same device arrays."""
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
        names = (_MAMBA if has(f"mamba{i}_in_w") else
                 _EXPERTS if has(f"moe{i}_router_w") else _ATTENTION)
        layer = {key: get(name.format(i=i)) for key, name in names.items()}
        layer["ln"] = get(f"ln1_{i}_scale")
        layers.append(layer)
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "head": get("lm_head_w"), "layers": layers}


def reference_on(reference, weights: Dict, config: Dict, ids, routes, rows):
    """The plain reference on the experts the program chose ([L_E, S,
    k]): (logits of the compared positions `rows` [R, V], the experts'
    shortfall [L_E, S])."""
    return reference.logits_on_routes(weights, ids,
                                      reference.Hyper.of(config), routes,
                                      rows=rows)


def kernel_shape(sz: Dict) -> Dict:
    """The paged kernel's calls and the state update's
    (`flops_nemotron3.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], full_layers=sz["full_layers"],
                state_layers=sz["state_layers"], heads=sz["n_heads"],
                kv_heads=b["n_kv_heads"], head_dim=b["head_dim"],
                ssm_inner=b["ssm_inner"], ssm_state=b["ssm_state"])
