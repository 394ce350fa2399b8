"""From a configuration file to the program under test, and from the
program's weights to the plain reference's. The only place that knows
how the published keys map onto `paddle_tpu.models.transformer`."""

from __future__ import annotations

from typing import Dict

MAX_PROGRAM_SEED = 2 ** 31 - 1   # the program's PRNG key is 32 signed bits


def sizes(config: Dict) -> Dict:
    """The published keys (GPT-2 naming) under the names the model
    builder takes."""
    return dict(vocab=int(config["vocab_size"]),
                d_model=int(config["n_embd"]),
                n_heads=int(config["n_head"]),
                d_ff=int(config["n_inner"]),
                n_layers=int(config["n_layer"]),
                max_len=int(config["n_positions"]))


def build_params_only(pt, sz: Dict, seed: int):
    """The LM with no loss and no optimizer: what a server is given.
    Returns (main, startup)."""
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % MAX_PROGRAM_SEED
    with pt.program_guard(main, startup):
        src = layers.data("src_ids", [sz["max_len"]], dtype="int64")
        tfm.transformer_lm(src, sz["vocab"], n_layers=sz["n_layers"],
                           d_model=sz["d_model"], n_heads=sz["n_heads"],
                           d_ff=sz["d_ff"], max_len=sz["max_len"])
    return main, startup


def build_trainer(pt, sz: Dict, seq_len: int, seed: int, train: Dict):
    """The LM with its loss and optimizer, as `chip_smoke.py` builds it:
    bf16 AMP over f32 masters, Adam. Returns (main, startup, loss)."""
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % MAX_PROGRAM_SEED
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=sz["vocab"], seq_len=seq_len,
            n_layers=sz["n_layers"], d_model=sz["d_model"],
            n_heads=sz["n_heads"], d_ff=sz["d_ff"], max_len=seq_len,
            remat=train.get("remat", False))
        pt.optimizer.AdamOptimizer(
            learning_rate=float(train["learning_rate"])).minimize(avg)
    main.amp_dtype = train["amp_dtype"]
    return main, startup, avg


def reference_weights(scope, n_layers: int) -> Dict:
    """The program's weights, by the names `transformer_lm` gives them,
    in the shape `reference.py` documents. No copy is made: the
    reference reads the same device arrays."""
    def get(name):
        v = scope.find_var(name)
        if v is None:
            raise KeyError(f"no weight named {name!r} in the scope")
        return v

    def pair(stem):
        return (get(stem + "_w"), get(stem + "_b"))

    layers = []
    for i in range(n_layers):
        layers.append({
            "ln1": (get(f"ln1_{i}_scale"), get(f"ln1_{i}_bias")),
            "ln2": (get(f"ln2_{i}_scale"), get(f"ln2_{i}_bias")),
            "q": pair(f"attn{i}_q"), "k": pair(f"attn{i}_k"),
            "v": pair(f"attn{i}_v"), "out": pair(f"attn{i}_out"),
            "ffn_in": pair(f"ffn{i}_in"), "ffn_out": pair(f"ffn{i}_out")})
    return {"tok_emb": get("tok_emb"), "pos_emb": get("pos_emb"),
            "ln_f": (get("ln_f_scale"), get("ln_f_bias")),
            "head": pair("lm_head"), "layers": layers}
