"""The mapping of granite-4.0-h-micro's published configuration
(`model_type: granitemoehybrid`, no experts) onto
`paddle_tpu.models.transformer`, and of the program's weights onto
`reference_granite4.py`'s: the functions `_model_nemotron3.py` lists. A
configuration file names this module and that reference under `harness`.

The weights are drawn by the program's own start-up program; where the
configuration serves its matrices in bfloat16 (`serving.weight_dtype`)
the SAME start-up program rounds each matrix as it draws it (a `cast`
behind its initialiser, one fused program a matrix: the 12.8 GB float32
draw of the whole model never exists on the device, where it and a
rounded copy would not fit together). The scope then holds what the
bundle will store: the fingerprints are taken of the rounded values and
the export finds nothing left to round.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from kinds._model import MAX_PROGRAM_SEED

#: what the q and k projections' Xavier draw is multiplied by, where a
#: checkpoint's are trained. The scores are q.k / 64
#: (`attention_multiplier`, a muP constant: 1 / head_dim), an eighth of
#: what 1 / sqrt(64) gives: at a gain of 1 they have a deviation of 0.16
#: and a softmax over 1-5 k rows is flat, so that which rows are read, a
#: rotation that should not be there or the wrong scale would all hide
#: under the precision (Keye's finding, PERF.md section 6, PR 33). 4.4
#: gives the scores the deviation of about 3 that the Nemotron cell's
#: have at its 1.6 and 1 / sqrt(128)
QK_GAIN = 4.4

#: `mamba_ssm`'s defaults for the step bias's draw, which the published
#: configuration does not carry (`time_step_limit` (0, inf): no clamp)
TIME_STEP = (0.001, 0.1, 1e-4)

_KIND = {"mamba": "mamba2_ffn", "attention": "full"}


def layer_pattern(config: Dict):
    """Every layer's kind, from the published `layer_types`: a Mamba-2
    mixer and the FFN, or full attention and the FFN."""
    return [_KIND[t] for t in config["layer_types"][
        :int(config["num_hidden_layers"])]]


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["model_type"] != "granitemoehybrid" \
            or int(config["num_local_experts"]) \
            or int(config["num_experts_per_tok"]) \
            or config["position_embedding_type"] != "nope" \
            or config["hidden_act"] != "silu" \
            or config["normalization_function"] != "rmsnorm" \
            or config["attention_bias"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"] \
            or not config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None \
            or int(config["shared_intermediate_size"]) \
            != int(config["intermediate_size"]) \
            or int(config["mamba_expand"]) * int(config["hidden_size"]) \
            != int(config["mamba_n_heads"]) * int(config["mamba_d_head"]):
        raise ValueError(
            "this block is Granite 4.0-H without experts: Mamba-2 layers "
            "with a biased convolution and attention layers without "
            "positions or bias, each followed by a dense gated-SiLU FFN "
            "of shared_intermediate_size, RMSNorm, a tied head; the "
            "configuration says otherwise (routed experts, positions "
            "and biases are refused, not approximated)")
    kinds = layer_pattern(config)
    heads = int(config["num_attention_heads"])
    ssm_heads = int(config["mamba_n_heads"])
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=heads,
        d_ff=int(config["shared_intermediate_size"]),
        n_layers=len(kinds),
        state_layers=kinds.count("mamba2_ffn"),
        full_layers=kinds.count("full"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        weight_dtype=str(serving.get("weight_dtype", "")),
        # the bytes of a matrix as served (what `serve_step_mfu` divides
        # by to count parameters), and of a state's or a pool's float
        dtype_bytes=2 if serving.get("weight_dtype") == "bfloat16" else 4,
        state_dtype_bytes=4, cache_dtype_bytes=4,
        block=dict(
            norm="rms_norm", norm_eps=float(config["rms_norm_eps"]),
            positions="none", bias=False, attention="gqa",
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["hidden_size"]) // heads,
            ffn="gated", tied_head=True, layer_pattern=kinds,
            conv_taps=int(config["mamba_d_conv"]),
            ssm_inner=ssm_heads * int(config["mamba_d_head"]),
            ssm_state=int(config["mamba_d_state"]), ssm_heads=ssm_heads,
            ssm_groups=int(config["mamba_n_groups"]),
            ssm_chunk=int(config["mamba_chunk_size"]),
            embed_scale=float(config["embedding_multiplier"]),
            residual_scale=float(config["residual_multiplier"]),
            logit_scale=1.0 / float(config["logits_scaling"]),
            attn_scale=float(config["attention_multiplier"])))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (no parameter's shape depends on it). The
    start-up program then draws, from the seed: every q and k projection
    again, `QK_GAIN` times as wide; the scans' vectors as `mamba_ssm`
    starts them: A uniform in [1, 16] (`a_log` its log), the step bias
    the inverse softplus of a log-uniform draw in [0.001, 0.1] floored
    at 1e-4; `d_skip` the layer's own 1, moved a fifth about it so that
    dropping it shows in every head; the convolution's bias uniform in
    +-1/2 (a depthwise Conv1d of 4 taps as PyTorch starts it: the
    layer's own zeros would leave the bias unexercised); and last, where
    the configuration serves bfloat16 matrices, rounds every matrix
    (`io.is_weight_matrix`) where it is drawn. Returns (main,
    startup)."""
    from paddle_tpu import io as pio
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
    lo, hi, floor = TIME_STEP

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
        if sz["weight_dtype"] \
                and pio.is_weight_matrix(var.name, var.shape):
            block.append_op("cast", {"X": var.name}, {"Out": var.name},
                            {"in_dtype": "float32",
                             "out_dtype": sz["weight_dtype"]})
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_FFN = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
        "down": "ffn{i}_down_w", "ln1": "ln1_{i}_scale",
        "ln2": "ln2_{i}_scale"}
_MAMBA = {"in": "mamba{i}_in_w", "conv_w": "mamba{i}_conv_w",
          "conv_b": "mamba{i}_conv_b", "dt_b": "mamba{i}_dt_b",
          "a_log": "mamba{i}_a_log", "d_skip": "mamba{i}_d_skip",
          "norm": "mamba{i}_norm_scale", "out": "mamba{i}_out_w"}
_ATTENTION = {"q": "attn{i}_q_w", "k": "attn{i}_k_w", "v": "attn{i}_v_w",
              "out": "attn{i}_out_w"}


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_granite4.py` documents. What
    a layer's mixer is shows in the weights it has. No copy is made: the
    reference reads the same device arrays (bfloat16 matrices where the
    bundle stores them so: it casts each up where it uses it)."""
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
        names = dict(_MAMBA if has(f"mamba{i}_in_w") else _ATTENTION,
                     **_FFN)
        layers.append({key: get(name.format(i=i))
                       for key, name in names.items()})
    return {"tok_emb": get("tok_emb"), "ln_f": get("ln_f_scale"),
            "layers": layers}


def reference_on(reference, weights: Dict, config: Dict, ids, routes, rows):
    """The plain reference's logits of the compared positions `rows` [R,
    V]; no experts, so no routes and no shortfall (None)."""
    return reference.logits(weights, ids, reference.Hyper.of(config),
                            rows=rows), None


def kernel_shape(sz: Dict) -> Dict:
    """The paged kernel's calls and the state update's
    (`flops_granite4.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], full_layers=sz["full_layers"],
                state_layers=sz["state_layers"], heads=sz["n_heads"],
                kv_heads=b["n_kv_heads"], head_dim=b["head_dim"],
                ssm_inner=b["ssm_inner"], ssm_state=b["ssm_state"])
