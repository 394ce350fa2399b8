#!/usr/bin/env python3
"""The GRADIENTS of `mellum2_12b_train_seq8k`'s program at the published
widths, on the chip, against `jax.grad` of the plain reference: what the
cell's own check is blind to (`kinds/train_stream_mapped.py`: at random
initial weights a loss near ln(vocab) does not see a band one row off,
the plain table on a full layer or a pair dropped in the backward).

    python3 benchmark/tools/mellum2_check_readings.py <seed> [--tiny]
        [--f32 | --amp] [--qk-gain G]

One period (window, window, window, full) of the configuration's file at
a 2,048-row sequence (the band's two edges both crossed: rows 0-1,023 read
a growing triangle, rows 1,024-2,047 a full window), through
`transformer_lm_loss` -> `append_backward` -> `Executor`, TWICE: under bf16
AMP as the cell trains (`program_amp`: at random initial weights the
gradients' signal is small and bfloat16's rounding of the same size, so
these readings give the noise, not a check: the reference in bfloat16
throughout reads the same), and in float32 at the highest matmul precision
(`program_f32_highest`: the same kernels with the rounding taken out, which
IS the check of the band, the tables and the held share's backward).
Compared, each as max |program - reference| over the reference's largest
entry and as the relative distance of the two in the Frobenius norm: the
gradients of layer 0's (window) and layer 3's (full) q, k and v, of held
expert 0's three matrices in layer 0 and of layer 0's router. Then the
same distances for the REFERENCE made wrong in one part and taken for the
program (`fault_*`), and for the reference in bfloat16 throughout: a
check worth having puts the program under every fault. The q and k
projections are drawn `QK_GAIN` times as wide as Xavier's for these
readings (as `_model_cmda.py` does for its cell, and for its reason: at
Xavier's draw a head's softmax over a thousand rows is nearly flat, and
which rows are read hardly moves anything). Also printed: the loss, the
first step's expert counts beside the reference's, and the share of rows
whose eight experts differ between the program's routes and the
reference's (near ties under bf16 activations): the reference's gradients
are computed ON THE PROGRAM'S ROUTES (`mean_loss(routes=...)`), as the
serve cells' checks compare logits, since a row on another expert has
another expert's gradients.

`--tiny` rehearses the same code off the chip at cut widths (`--f32`:
the float32 pass alone; `--amp`: the AMP pass alone, no faults).
`--qk-gain G` draws q and k at G times Xavier's in the place of
`QK_GAIN`: at 1 (the cell's own draw) the scores are a ninth of what they
are at 3 and so is what bfloat16's rounding of them moves, which is what
puts half the rows of the AMP pass on another expert than the reference's
own where `near_tie_row_share` (the router's INPUT rounded, nothing
upstream of it) counts a fifteenth (PERF.md section 6, PR 62).
"""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
import numpy as np  # noqa: E402

import common  # noqa: E402
import reference_mellum2 as ref  # noqa: E402
from kinds import _model_mellum2 as mapping  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import paddle_tpu as pt  # noqa: E402
from paddle_tpu.models import transformer as tfm  # noqa: E402

QK_GAIN = float(sys.argv[sys.argv.index("--qk-gain") + 1]) \
    if "--qk-gain" in sys.argv else 3.0
seed = int(sys.argv[1])
tiny = "--tiny" in sys.argv
cfg = dict(common.Cell(os.path.join(ROOT, "BENCHMARK.json"),
                       "mellum2_12b_train_seq8k").config)
seq_len = 2048
if tiny:
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, vocab_size=128,
               sliding_window=24)
    seq_len = 64
sz = mapping.sizes(cfg)
hp = ref.Hyper.of(cfg)

COMPARED = {("layers", 0, "q"): "attn0_q_w", ("layers", 0, "k"): "attn0_k_w",
            ("layers", 0, "v"): "attn0_v_w", ("layers", 3, "q"): "attn3_q_w",
            ("layers", 3, "k"): "attn3_k_w", ("layers", 3, "v"): "attn3_v_w",
            ("layers", 0, "gate"): "moe0_gate_w",
            ("layers", 0, "up"): "moe0_up_w",
            ("layers", 0, "down"): "moe0_down_w",
            ("layers", 0, "router"): "moe0_router_w"}
EXPERT = ("gate", "up", "down")      # compared on held expert 0 alone

rng = np.random.RandomState(seed % (2 ** 32))
draw = rng.randint(0, sz["vocab"], (1, seq_len + 1))
src, tgt = draw[:, :-1], draw[:, 1:]


def program(amp):
    """(loss, the routes [L, S, k], {compared weight: gradient}, the
    reference's weights) of one step of the training program: under AMP
    as the cell trains, or in float32 at the highest matmul precision
    (the kernels' own arithmetic on the chip, the rounding taken out)."""
    import contextlib
    from paddle_tpu.kernels import flash_attention as fa
    if not amp:
        # float32 tiles of 1,024 x 1,024 do not fit the backward kernels'
        # VMEM (the cell's are bfloat16): this pass runs them at 512
        fa._default_block = lambda rows: 512 if rows % 512 == 0 else 128
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed % mapping.MAX_PROGRAM_SEED
    routes = []
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=sz["vocab"], seq_len=seq_len,
            n_layers=sz["n_layers"], d_model=sz["d_model"],
            n_heads=sz["n_heads"], d_ff=sz["d_ff"], max_len=seq_len,
            block=sz["block"], collect_routes=routes)
        grads = {p.name: g for p, g in pt.backward.append_backward(avg)}
    if amp:
        main.amp_dtype = amp
    scope = pt.Scope()
    with pt.scope_guard(scope), contextlib.nullcontext() if amp \
            else jax.default_matmul_precision("highest"):
        exe = pt.Executor()
        exe.run(startup)
        for name in list(scope.local_var_names()):
            if name.endswith(("_q_w", "_k_w")):
                scope.set_var(name, scope.find_var(name) * QK_GAIN)
        # the embedding at the scale the cell's mapping draws it at (the
        # builder's own default is 0.02)
        scope.set_var("tok_emb", scope.find_var("tok_emb")
                      * (mapping.EMBEDDING_SCALE / 0.02))
        weights = jax.tree_util.tree_map(
            jnp.array, mapping.reference_weights(scope.find_var,
                                                 sz["n_layers"]))
        got = exe.run(main, feed={"src_ids": src, "tgt_ids": tgt[..., None]},
                      fetch_list=[avg] + routes
                      + [grads[n] for n in COMPARED.values()])
    return (float(np.ravel(got[0])[0]),
            np.stack([np.asarray(r)[0] for r in got[1:1 + len(routes)]]),
            dict(zip(COMPARED, got[1 + len(routes):])), weights)


def leaves(tree):
    out = {}
    for key in COMPARED:
        leaf = tree[key[0]][key[1]][key[2]]
        out[key] = leaf[0] if key[2] in EXPERT else leaf
    return out


def reference_grads(hyper, got_routes):
    picked = leaves(weights)

    def loss_of(some):
        tree = dict(weights, layers=[dict(l) for l in weights["layers"]])
        for (_, i, k), v in some.items():
            whole = weights["layers"][i][k]
            tree["layers"][i][k] = whole.at[0].set(v) if k in EXPERT else v
        return ref.mean_loss(tree, src, tgt, hyper, got_routes[None])

    return jax.value_and_grad(loss_of)(picked)


def distances(mine, theirs):
    out = {}
    for key, name in COMPARED.items():
        g = np.asarray(mine[key], np.float32)
        g = g[0] if key[2] in EXPERT and g.ndim == 3 else g
        w = np.asarray(theirs[key], np.float32)
        out[name] = [float(np.max(np.abs(g - w)) / np.max(np.abs(w))),
                     float(np.linalg.norm(g - w) / np.linalg.norm(w))]
    return out


modes = [(cfg["train"]["amp_dtype"], "program_amp")] \
    if "--f32" not in sys.argv else []
modes += [(None, "program_f32_highest")] if "--amp" not in sys.argv else []
for amp, name in modes:
    loss, got_routes, got_grads, weights = program(amp)
    want_loss, want = reference_grads(hp, got_routes)
    ref_loss, counts, ref_routes, ties = ref.loss_and_counts(
        weights, src, tgt, hp, int(cfg["num_experts"]))
    differ = np.mean(np.sort(got_routes, -1) != np.sort(
        np.asarray(ref_routes)[0], -1), axis=-1) > 0
    common.note(check=name, qk_gain=QK_GAIN, loss=loss,
                reference_loss=float(want_loss),
                reference_counts=counts, near_tie_row_share=ties,
                rows_on_other_experts=float(np.mean(differ)),
                max_and_norm=distances(got_grads, want))
# the faults beside the last program's readings, on ITS routes
for name, fault in () if "--amp" in sys.argv else (("bf16_throughout", dict(dtype="bfloat16")),
                    ("fault_window_long", dict(window_off=1)),
                    ("fault_window_short", dict(window_off=-1)),
                    ("fault_full_plain_table", dict(plain_full=True)),
                    ("fault_pair_dropped", dict(drop=True))):
    wrong_loss, wrong = reference_grads(hp._replace(**fault), got_routes)
    common.note(check=name, loss=float(wrong_loss),
                max_and_norm=distances(wrong, want))
