"""Traffic kind `train_stream`: a token stream from the seed through the
trainer's `run_loop` windows, on one chip (`Executor`) or on the mesh the
configuration names (`transpile` + `ParallelExecutor`).

Observations (what the metric readers read):
  window_s, window_tokens, window_steps   whole run_loop calls that ended
                                          inside --seconds, host clock
                                          ending in the fetch of the losses
  host_s         host_prep + dispatch + fetch of `step_timings()` over them
  compiles_in_window
  flops_per_token, chips, and the shapes of one attention call
"""

from __future__ import annotations

import gc
import time

import numpy as np

import common
import flops
import reference
import workload
from kinds import _model

# The trainer computes in bf16 (AMP) over f32 masters and the reference
# in f32 at the highest matmul precision: at random initial weights the
# mean loss over 8,192 tokens agreed to 1.4e-6 and 4.9e-6 relative on
# the chip (PERF.md, PR 24). 2e-4 leaves room for another reduction
# order and is far inside what a wrong mask, a dropped layer or a
# shifted target give (each moves the loss by more than 1e-2).
LOSS_RTOL = 2e-4


def run(cell, args, device, t_start):
    import jax
    import paddle_tpu as pt

    cfg, tr = cell.config, cell.traffic
    sz = _model.sizes(cfg)
    seq_len, batch = int(tr["seq_len"]), int(tr["sequences_per_step"])
    n_steps = int(tr["steps_per_call"])
    traced = bool(args.trace)

    main, startup, avg = _model.build_trainer(pt, sz, seq_len, args.seed,
                                              cfg["train"])
    mesh_axes = cfg.get("mesh")
    scope = pt.Scope()
    if mesh_axes:
        from paddle_tpu.parallel import ParallelExecutor, make_mesh
        mesh = make_mesh(dict(mesh_axes),
                         devices=jax.devices()[:cell.chips])
        pt.transpiler.transpile(main, mesh=mesh)
    with pt.scope_guard(scope):
        pt.Executor().run(startup)

    windows = workload.token_windows(args.seed, sz["vocab"], n_steps,
                                     batch, seq_len)
    src, tgt = next(windows)
    ref_loss = reference.mean_loss(
        _model.reference_weights(scope, sz["n_layers"]), src[0],
        tgt[0, ..., 0], sz["n_heads"])

    if mesh_axes:
        exe = ParallelExecutor(loss_name=avg.name, main_program=main,
                               mesh=mesh, scope=scope)

        def call(src, tgt):
            return exe.run_loop([avg], feed={"src_ids": src,
                                             "tgt_ids": tgt},
                                n_steps=n_steps, per_step_feeds=True)[0]
    else:
        exe = pt.Executor()

        def call(src, tgt):
            with pt.scope_guard(scope):
                return exe.run_loop(main, feed={"src_ids": src,
                                                "tgt_ids": tgt},
                                    fetch_list=[avg], n_steps=n_steps,
                                    per_step_feeds=True)[0]

    compiles = common.CompileCounter()
    losses = np.ravel(call(src, tgt))        # compiles, or reads the cache
    first_loss = float(losses[0])
    for _ in range(4):   # until a call compiles nothing: the state comes
        seen = compiles.count   # back laid out as the step left it
        t0 = time.perf_counter()
        losses = np.ravel(call(*next(windows)))
        call_s = time.perf_counter() - t0
        if compiles.count == seen:
            break
    correct = bool(abs(first_loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
                   and np.all(np.isfinite(losses)))
    common.note(check="first_step_loss", program=first_loss,
                reference=ref_loss, rtol=LOSS_RTOL, correct=correct,
                warm_call_s=call_s)

    gc.collect()
    gc.freeze()
    tracer = common.Tracer(traced, cell.name, bool(args.rehearse))
    exe.step_timings(reset=True)
    compiles_before = compiles.count
    setup_s = time.perf_counter() - t_start

    # -- the measured window --------------------------------------------------
    calls, window_s = 0, 0.0
    t_open = time.perf_counter()
    while not calls or window_s + call_s <= args.seconds:
        src, tgt = next(windows)
        t0 = time.perf_counter()
        losses = np.ravel(call(src, tgt))
        call_s = max(call_s, time.perf_counter() - t0)
        calls += 1
        window_s = time.perf_counter() - t_open   # data and all
    timings = exe.step_timings()
    compiles_in_window = compiles.count - compiles_before
    correct = correct and bool(np.all(np.isfinite(losses)))

    # a traced run profiles one more call, after the window has closed:
    # the profiler's start and stop cost the host seconds, and inside the
    # window they would be read as the trainer's
    if traced:
        src, tgt = next(windows)
        tracer.start()
        with jax.profiler.TraceAnnotation("bench/run_loop_call"):
            call(src, tgt)
        tracer.stop()

    shape = dict(n_layer=sz["n_layers"], d_model=sz["d_model"],
                 d_ff=sz["d_ff"], vocab=sz["vocab"], seq_len=seq_len)
    dp = int((mesh_axes or {}).get("dp", 1))
    tp = int((mesh_axes or {}).get("tp", 1))
    obs = {
        "setup_s": setup_s,
        "window_s": window_s,
        "window_tokens": calls * n_steps * batch * seq_len,
        "window_steps": calls * n_steps,
        "host_s": (timings["host_prep_s"] + timings["dispatch_s"]
                   + timings["fetch_s"]),
        "compiles_in_window": compiles_in_window,
        "flops_per_token": flops.lm_train_flops_per_token(**shape),
        "chips": cell.chips,
        # one attention call as one chip sees it under the mesh
        "attn_shape": dict(batch=batch // dp, heads=sz["n_heads"] // tp,
                           seq_len=seq_len,
                           head_dim=sz["d_model"] // sz["n_heads"]),
    }
    common.note(window=dict(calls=calls, steps=obs["window_steps"],
                            tokens=obs["window_tokens"], seconds=window_s,
                            last_loss=float(losses[-1])))
    return dict(obs=obs, correct=correct, attempted=calls, failed=0,
                reduced=tracer.reduce())
