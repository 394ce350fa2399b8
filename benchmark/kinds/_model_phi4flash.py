"""The mapping of Phi-4-mini-flash-reasoning's published configuration
(`model_type: phi4flash`, `microsoft/Phi-4-mini-flash-reasoning`) onto
`paddle_tpu.models.transformer`, and of the program's weights onto
`reference_phi4flash.py`'s: the functions `_model_olmoe.py` lists, with
`reference_on` in place of `reference_on_routes` (the kind
`backlog_mapped_hybrid` asks for the compared positions' rows alone). A
configuration file names this module and that reference under `harness`.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from kinds._model import MAX_PROGRAM_SEED

#: what the q and k projections' draw is multiplied by, where a
#: checkpoint's are trained. Xavier draws give a head of 64 scores with a
#: standard deviation of 1: a softmax over 1-3 k rows is nearly flat,
#: which rows are read hardly moves the output, and a window a row long
#: or short, or a cross layer on a window layer's table, would hide under
#: the precision (Keye's finding, PERF.md section 6, PR 33). At 1.6 each
#: the scores' deviation is 2.6 (the configuration's `assumed.qk_gain`).
QK_GAIN = 1.6

#: the range the scans' steps are drawn in, log-uniform (the `mamba_ssm`
#: initialisation: `dt_min`, `dt_max`)
DT_RANGE = (1e-3, 1e-1)


def layer_pattern(config: Dict):
    """Every held layer's kind, by its PUBLISHED index (`layers_held`):
    the reference's `layer_kinds`, written again here because this
    module imports nothing of the reference."""
    half = int(config["published"]["num_hidden_layers"]) // 2
    kinds = []
    for i in (int(i) for i in config["layers_held"]):
        if i < half:
            kinds.append("window" if i % 2 else "mamba")
        elif i in (half, half + 1):
            kinds.append("full" if i % 2 else "memory")
        else:
            kinds.append("cross" if i % 2 else "gmu")
    return kinds


def sizes(config: Dict) -> Dict:
    """The published keys under the names the model builder takes. What
    the program cannot do is refused here, not approximated."""
    if config["model_type"] != "phi4flash" \
            or config["hidden_act"] != "silu" or config["mlp_bias"] \
            or config["lm_head_bias"] \
            or not config["tie_word_embeddings"] \
            or int(config["mb_per_layer"]) != 2:
        raise ValueError("this block is the SambaY decoder-hybrid-decoder: "
                         "selective scans and differential attention in "
                         "turn, gated SiLU FFNs without a bias, a tied "
                         "head; the configuration says otherwise")
    held = [int(i) for i in config["layers_held"]]
    if len(held) != int(config["num_hidden_layers"]):
        raise ValueError("layers_held names num_hidden_layers layers")
    kinds = layer_pattern(config)
    heads = int(config["num_attention_heads"])
    ssm = config["assumed_sizes"]
    serving = config["serving"]
    return dict(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_heads=heads,
        d_ff=int(config["intermediate_size"]),
        n_layers=len(held),
        state_layers=sum(k in ("mamba", "memory") for k in kinds),
        window_layers=kinds.count("window"),
        full_layers=kinds.count("full"),
        reader_layers=kinds.count("cross"),
        gmu_layers=kinds.count("gmu"),
        max_len=int(serving.get("max_context",
                                config["max_position_embeddings"])),
        block=dict(
            norm="layer_norm", norm_eps=float(config["layer_norm_eps"]),
            positions="none", bias=False, attn_bias=True, attention="gqa",
            differential=True,
            n_kv_heads=int(config["num_key_value_heads"]),
            head_dim=int(config["hidden_size"]) // heads,
            ffn="gated", tied_head=True,
            window=int(config["sliding_window"]),
            layer_pattern=kinds, layer_ids=held,
            conv_taps=int(ssm["mamba_d_conv"]),
            ssm_inner=int(ssm["mamba_d_inner"]),
            ssm_state=int(ssm["mamba_d_state"]),
            ssm_dt_rank=int(ssm["mamba_dt_rank"]),
            dense_precision=str(serving.get("dense_precision", ""))))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given,
    built at a short length (no parameter's shape depends on it). The
    start-up program then draws every q and k projection again,
    `QK_GAIN` times as wide, and every scan's step bias as the inverse
    softplus of a log-uniform draw in `DT_RANGE`, from the seed; `A_log`
    (log(1 .. 16) a channel), `D_skip` (1) and the lambda vectors
    (normal at 0.1) are the layers' own. Returns (main, startup)."""
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
    lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
    for var in main.list_vars():
        if not var.persistable:
            continue
        if var.name.endswith(("_q_w", "_k_w")):
            fan_in, fan_out = var.shape
            NormalInitializer(scale=QK_GAIN * (2.0 / (fan_in + fan_out))
                              ** 0.5)(block.var(var.name), block)
        elif var.name.endswith("_dt_b"):
            steps = np.exp(rng.uniform(lo, hi, var.shape))
            NumpyArrayInitializer(np.log(np.expm1(steps)).astype(
                "float32"))(block.var(var.name), block)
    return main, startup


def export_cfg(sz: Dict) -> Dict:
    return dict(vocab_size=sz["vocab"], n_layers=sz["n_layers"],
                d_model=sz["d_model"], n_heads=sz["n_heads"],
                d_ff=sz["d_ff"], max_context=sz["max_len"],
                block=sz["block"])


_EVERY = {"gate": "ffn{i}_gate_w", "up": "ffn{i}_up_w",
          "down": "ffn{i}_down_w"}
_SCAN = {"in": "mamba{i}_in_w", "conv_w": "mamba{i}_conv_w",
         "conv_b": "mamba{i}_conv_b", "x": "mamba{i}_x_w",
         "dt_w": "mamba{i}_dt_w", "dt_b": "mamba{i}_dt_b",
         "a_log": "mamba{i}_a_log", "d_skip": "mamba{i}_d_skip",
         "out": "mamba{i}_out_w"}
_GMU = {"in": "gmu{i}_in_w", "out": "gmu{i}_out_w"}
_CROSS = {"q": "attn{i}_q_w", "q_b": "attn{i}_q_b", "out": "attn{i}_out_w",
          "out_b": "attn{i}_out_b", "lq1": "attn{i}_lq1",
          "lk1": "attn{i}_lk1", "lq2": "attn{i}_lq2", "lk2": "attn{i}_lk2",
          "subnorm": "attn{i}_subnorm_scale"}
_SELF = dict(_CROSS, k="attn{i}_k_w", k_b="attn{i}_k_b", v="attn{i}_v_w",
             v_b="attn{i}_v_b")


def reference_weights(lookup, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them
    for this block, in the shape `reference_phi4flash.py` documents (the
    head is the embedding: no weight of its own). What a layer is shows
    in the weights it has. No copy is made: the reference reads the same
    device arrays."""
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
        names = (_SCAN if has(f"mamba{i}_in_w") else
                 _GMU if has(f"gmu{i}_in_w") else
                 _SELF if has(f"attn{i}_k_w") else _CROSS)
        layer = {key: get(name.format(i=i))
                 for key, name in dict(_EVERY, **names).items()}
        for norm in ("ln1", "ln2"):
            layer[norm] = (get(f"{norm}_{i}_scale"), get(f"{norm}_{i}_bias"))
        layers.append(layer)
    return {"tok_emb": get("tok_emb"),
            "ln_f": (get("ln_f_scale"), get("ln_f_bias")), "layers": layers}


def reference_on(reference, weights: Dict, config: Dict, ids, routes, rows):
    """The plain reference: (logits of the compared positions `rows`
    [R, V], a shortfall of zeros [1, S]: a model without experts has no
    route to force, and the kind's readings take the array all the
    same)."""
    return (reference.logits(weights, ids, reference.Hyper.of(config),
                             rows=rows),
            np.zeros((1, len(ids)), np.float32))


def kernel_shape(sz: Dict) -> Dict:
    """The two paged kernels' calls and the state update
    (`flops_phi4flash.py`)."""
    b = sz["block"]
    return dict(layers=sz["n_layers"], window_layers=sz["window_layers"],
                full_layers=sz["full_layers"],
                reader_layers=sz["reader_layers"],
                state_layers=sz["state_layers"], window=b["window"],
                heads=sz["n_heads"], kv_heads=b["n_kv_heads"],
                head_dim=b["head_dim"])
