"""Compile-only remat memory report (VERDICT r2 weak #4 / next #7).

Compiles the transformer-LM train step with and without remat on the
*current* JAX backend and records `compiled.memory_analysis()` for both —
no execution, so it is cheap. The committed
artifacts (docs/artifacts/remat_memory_<tag>.json) are the evidence behind
the remat memory claims in tests/test_remat.py and
docs/design_decisions.md; each artifact embeds the exact env + argv that
produced it under "invocation" so it can be regenerated verbatim.

≙ reference memory_optimization_transpiler's published savings tables
(python/paddle/fluid/transpiler/memory_optimization_transpiler.py) — the
reference proves its pass by reporting freed bytes; we prove ours by the
compiled executable's temp-buffer sizes.

Usage (the two committed artifacts):
    BENCH_TFM_BATCH=16 python tools/remat_memory_report.py transformer_bs16
    BENCH_TFM_SEQ=8192 BENCH_TFM_LAYERS=4 BENCH_TFM_BATCH=1 \
        python tools/remat_memory_report.py long_context_8k
"""

import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as pt
from paddle_tpu.core import lowering
from paddle_tpu.models.transformer import transformer_lm_loss


def build(remat, *, vocab, seq_len, n_layers, d_model, n_heads, batch,
          amp_dtype=None):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = 7
    with pt.program_guard(main, startup):
        avg, _ = transformer_lm_loss(vocab_size=vocab, seq_len=seq_len,
                                     n_layers=n_layers, d_model=d_model,
                                     n_heads=n_heads, d_ff=4 * d_model,
                                     max_len=max(seq_len, 2048), remat=remat)
        pt.optimizer.AdamOptimizer(learning_rate=1e-4).minimize(avg)
    if amp_dtype:
        main.amp_dtype = amp_dtype
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, vocab, (batch, seq_len)).astype("int64")
        feed = {"src_ids": ids,
                "tgt_ids": np.roll(ids, -1, 1).reshape(batch, seq_len, 1)}
        state = exe._state_for(main, scope)
        fa = exe._prep_feed(main, feed)
        step, _ = lowering.build_step_fn(main, list(fa), [avg.name],
                                         sorted(state))
        # donate_argnums matches Executor._run_impl's jit: state buffers are
        # aliased into the outputs, so "temp" is the true activation peak
        compiled = (jax.jit(step, donate_argnums=(0,))
                    .lower(state, fa, jax.random.PRNGKey(0)).compile())
        ma = compiled.memory_analysis()
        return {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }


def main():
    tag = sys.argv[1] if len(sys.argv) > 1 else "transformer"
    cfg = {
        "vocab": int(os.environ.get("BENCH_TFM_VOCAB", 32000)),
        "seq_len": int(os.environ.get("BENCH_TFM_SEQ", 1024)),
        "n_layers": int(os.environ.get("BENCH_TFM_LAYERS", 6)),
        "d_model": int(os.environ.get("BENCH_TFM_DMODEL", 2048)),
        "n_heads": int(os.environ.get("BENCH_TFM_HEADS", 16)),
        "batch": int(os.environ.get("BENCH_TFM_BATCH", 4)),
    }
    amp = os.environ.get("BENCH_TFM_AMP", "bfloat16") or None
    dev = jax.devices()[0]
    env = {k: v for k, v in os.environ.items() if k.startswith("BENCH_TFM_")}
    report = {"device": dev.device_kind, "platform": dev.platform,
              "config": cfg, "amp_dtype": amp,
              "invocation": {"argv": sys.argv[1:], "env": env,
                             "tool": "tools/remat_memory_report.py"}}
    for key, remat in (("no_remat", False), ("remat", True)):
        print(f"compiling {key} ...", flush=True)
        report[key] = build(remat, amp_dtype=amp, **cfg)
    nr, r = report["no_remat"]["temp_bytes"], report["remat"]["temp_bytes"]
    report["temp_reduction_pct"] = round(100.0 * (1 - r / nr), 2)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "docs", "artifacts", f"remat_memory_{tag}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
