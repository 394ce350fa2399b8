#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process drives the two main paths once, through the entry points a
user calls, at the full width of the repo's transformer cell (d_model
2048, 8 heads of 256, d_ff 8192, vocab 32000, seq 1024, batch 8, bf16
AMP over f32 masters, Adam). Weights are random from --seed, data is
synthetic from --seed. Phases, in order:

  1. device  — jax.devices(); anything but a TPU is a failure.
  2. kernels — compile the flash forward, its backward and the paged
               decode kernel at the shapes below; `tpu_custom_call` must
               be in the compiled program (the Pallas branch, not a
               reference, is what the later phases execute).
  3. train   — startup, a few Executor.run steps and one run_loop window
               on a repeated batch; losses finite and falling.
  4. serve   — export_decode_model from the trained scope, load it in a
               ServingEngine, generate for prompts of different lengths
               admitted together, and compare the greedy tokens with a
               teacher-forced full-prefix forward of the same weights.

    python chip_smoke.py              one chip; what the driver runs
    python chip_smoke.py --chips 4    ONLY: the same LM for a few steps on
                                      one chip, then on a dp2 x tp2 mesh
                                      through transpile + ParallelExecutor;
                                      loss curves compared
    python chip_smoke.py --rehearsal  tiny sizes, any backend; walks the
                                      same phases, never prints the
                                      contract line

The last line of stdout is the contract line
`{"ok": true, "device": {...}}`; any phase that raises exits non-zero
without it. Timings printed on the way are smoke readings, not benchmark
numbers.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# the bring-up transformer: widths are never cut; depth is the only
# dimension a cut may touch
FULL = dict(vocab=32000, seqlen=1024, d_model=2048, n_heads=8, d_ff=8192,
            n_layers=6, batch=8)
TINY = dict(vocab=512, seqlen=128, d_model=64, n_heads=2, d_ff=128,
            n_layers=2, batch=4)
DECODE = dict(slots=8, block_size=16, pool_blocks=128, bucket=128)
POOL_IDS = 64          # token ids the synthetic task draws from
LEARNING_RATE = 3e-4
RUN_STEPS = 6          # Executor.run steps after the compiling one
LOOP_STEPS = 32        # one Executor.run_loop window
MESH_STEPS = 4         # --chips 4: steps on each side of the comparison
MESH_RTOL = 2e-2       # bf16 AMP, different reduction order across chips
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'   # a Pallas kernel


def log(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def counting_batch(cfg, seed):
    """Next-token task a few dozen steps can learn: every sequence counts
    up through POOL_IDS ids from its own start; the target is the
    successor. Greedy decoding of a learned model keeps counting."""
    rng = np.random.RandomState(seed)
    start = rng.randint(0, POOL_IDS, (cfg["batch"], 1))
    src = (start + np.arange(cfg["seqlen"])[None, :]) % POOL_IDS
    tgt = (src + 1) % POOL_IDS
    return {"src_ids": src.astype("int64"),
            "tgt_ids": tgt[..., None].astype("int64")}


def build_lm(pt, cfg, seed):
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=cfg["vocab"], seq_len=cfg["seqlen"],
            n_layers=cfg["n_layers"], d_model=cfg["d_model"],
            n_heads=cfg["n_heads"], d_ff=cfg["d_ff"],
            max_len=cfg["seqlen"])
        pt.optimizer.AdamOptimizer(learning_rate=LEARNING_RATE).minimize(avg)
    main.amp_dtype = "bfloat16"
    return main, startup, avg


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- phase 2 ----------------------------------------------------------------

def phase_kernels(jax, cfg, on_tpu):
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import dot_product_attention
    from paddle_tpu.kernels.paged_attention import (
        paged_decode_attention, paged_index_scores,
        paged_latent_decode_attention, paged_sparse_attention)
    hd = cfg["d_model"] // cfg["n_heads"]
    qkv = [jax.ShapeDtypeStruct(
        (cfg["batch"], cfg["seqlen"], cfg["n_heads"], hd), jnp.bfloat16)] * 3
    pool = jax.ShapeDtypeStruct(
        (DECODE["pool_blocks"], DECODE["block_size"], cfg["n_heads"], hd),
        jnp.float32)
    mb = -(-cfg["seqlen"] // DECODE["block_size"])
    paged_args = (
        jax.ShapeDtypeStruct((DECODE["slots"], cfg["n_heads"], hd),
                             jnp.float32), pool, pool,
        jax.ShapeDtypeStruct((DECODE["slots"], mb), jnp.int32),
        jax.ShapeDtypeStruct((DECODE["slots"],), jnp.int32))

    # a latent pool: one row a token for all heads (512 of latent and 64
    # of rotary key in 640 floats), the heads' queries absorbed
    latent_args = (
        jax.ShapeDtypeStruct((DECODE["slots"], cfg["n_heads"], 640),
                             jnp.float32),
        jax.ShapeDtypeStruct(
            (DECODE["pool_blocks"], DECODE["block_size"], 640),
            jnp.float32), *paged_args[3:])

    def latent(q, pool, tables, lens):
        return paged_latent_decode_attention(
            q, pool, tables, lens, value_width=512, scale=192 ** -0.5)

    # a sparse-attention layer: an index key a token (64 floats in 128)
    # scored by 16 index heads, then 2,048 selected rows of 4 K/V heads
    # of 128 gathered for 32 query heads
    index_args = (
        jax.ShapeDtypeStruct((DECODE["slots"], 16, 128), jnp.float32),
        jax.ShapeDtypeStruct((DECODE["slots"], 16), jnp.float32),
        jax.ShapeDtypeStruct(
            (DECODE["pool_blocks"], DECODE["block_size"], 128),
            jnp.float32), *paged_args[3:])
    group_pool = jax.ShapeDtypeStruct(
        (DECODE["pool_blocks"], DECODE["block_size"], 4, 128), jnp.float32)
    sparse_args = (
        jax.ShapeDtypeStruct((DECODE["slots"], 32, 128), jnp.float32),
        group_pool, group_pool,
        jax.ShapeDtypeStruct((DECODE["slots"], 2048), jnp.int32),
        jax.ShapeDtypeStruct((DECODE["slots"],), jnp.int32))

    # the same layer as the step calls it: the slots whose selection is
    # dense by their live pages, whole, with the selection as a mask
    walks_args = sparse_args + (
        paged_args[3],
        jax.ShapeDtypeStruct((DECODE["slots"], mb * DECODE["block_size"]),
                             jnp.bool_))

    def sparse_walks(q, k_pool, v_pool, rows, counts, tables, selected):
        return paged_sparse_attention(q, k_pool, v_pool, rows, counts,
                                      pages=(tables, counts, selected))

    def fwd(q, k, v):
        return dot_product_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    cases = [("flash_fwd", jax.jit(fwd), qkv, 1),
             ("flash_fwd_bwd", jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
              qkv, 3),
             ("paged_decode", jax.jit(paged_decode_attention), paged_args,
              1),
             ("paged_latent_decode", jax.jit(latent), latent_args, 1),
             ("paged_index_scores", jax.jit(paged_index_scores), index_args,
              1),
             ("paged_sparse_attention", jax.jit(paged_sparse_attention),
              sparse_args, 1),
             ("paged_sparse_walks", jax.jit(sparse_walks), walks_args, 2)]
    for name, fn, args, want in cases:
        t0 = time.perf_counter()
        text = fn.lower(*args).compile().as_text()
        n = text.count(CUSTOM_CALL)
        log("kernels", kernel=name, tpu_custom_call=n,
            compile_s=round(time.perf_counter() - t0, 2))
        if on_tpu and n != want:
            raise AssertionError(
                f"{name}: {n} tpu_custom_call in the compiled program, "
                f"expected {want} — a reference path was taken")


# -- phase 3 ----------------------------------------------------------------

def check_losses(name, losses):
    losses = [float(x) for x in np.ravel(losses)]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")
    return [round(x, 4) for x in losses]


def phase_train(jax, pt, cfg, seed, scope):
    main, startup, avg = build_lm(pt, cfg, seed)
    feed = counting_batch(cfg, seed)
    exe = pt.Executor()
    with pt.scope_guard(scope):
        exe.run(startup)
        t0 = time.perf_counter()
        first = exe.run(main, feed=feed, fetch_list=[avg])[0]
        run_compile_s = time.perf_counter() - t0
        losses, step_ms = [first], []
        for _ in range(RUN_STEPS):
            t0 = time.perf_counter()
            losses.append(exe.run(main, feed=feed, fetch_list=[avg])[0])
            step_ms.append((time.perf_counter() - t0) * 1e3)
        run_losses = check_losses("Executor.run", losses)
        t0 = time.perf_counter()
        exe.run_loop(main, feed=feed, fetch_list=[avg], n_steps=LOOP_STEPS)
        loop_first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        (window,) = exe.run_loop(main, feed=feed, fetch_list=[avg],
                                 n_steps=LOOP_STEPS)
        loop_s = time.perf_counter() - t0
        loop_losses = check_losses("Executor.run_loop", window)
        if not loop_losses[-1] < run_losses[0]:
            raise AssertionError("run_loop did not continue the descent")
    log("train", smoke_readings=True,
        run_compile_s=round(run_compile_s, 1),
        run_step_ms=round(float(np.median(step_ms)), 1),
        run_loop_compile_s=round(max(loop_first_s - loop_s, 0.0), 1),
        run_loop_step_ms=round(loop_s / LOOP_STEPS * 1e3, 1),
        run_losses=run_losses,
        run_loop_losses=[loop_losses[0], loop_losses[-1]],
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    exe.close()
    params = [v.name for v in main.list_vars() if v.is_parameter]
    return params


# -- phase 4 ----------------------------------------------------------------

def dir_bytes(path):
    return sum(os.path.getsize(os.path.join(r, f))
               for r, _, files in os.walk(path) for f in files)


def teacher_forced(pt, cfg, scope, rows):
    """Full-prefix forward of the same weights through the Executor:
    logits at every position of prompt+generated, one causal pass."""
    from paddle_tpu import layers
    from paddle_tpu.models import transformer as tfm
    length = DECODE["bucket"]
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        src = layers.data("src_ids", [length], dtype="int64")
        logits = tfm.transformer_lm(
            src, cfg["vocab"], n_layers=cfg["n_layers"],
            d_model=cfg["d_model"], n_heads=cfg["n_heads"],
            d_ff=cfg["d_ff"], max_len=cfg["seqlen"],
            pos_table_len=cfg["seqlen"])
    ids = np.zeros((len(rows), length), "int64")
    for i, row in enumerate(rows):
        ids[i, :len(row)] = row
    with pt.scope_guard(scope):
        (out,) = pt.Executor().run(main.clone(for_test=True),
                                   feed={"src_ids": ids},
                                   fetch_list=[logits])
    return out


def phase_serve(jax, pt, cfg, seed, scope):
    from paddle_tpu import io as pio
    from paddle_tpu.serving import ServingEngine

    # (prompt, max_new): prefixes of the rows the trainer repeated, so
    # the model is asked what it was taught; more requests than slots
    rows = counting_batch(cfg, seed)["src_ids"]
    requests = [([int(t) for t in rows[i % len(rows), :n]], max_new)
                for i, (n, max_new) in enumerate(
                    [(5, 12), (23, 24), (64, 16), (100, 20), (9, 24),
                     (37, 8), (81, 24), (16, 16), (50, 12), (3, 24)])]

    bundle = tempfile.mkdtemp(prefix="chip_smoke_bundle_")
    engine = ServingEngine()
    try:
        t0 = time.perf_counter()
        pio.export_decode_model(
            bundle, dict(vocab_size=cfg["vocab"], n_layers=cfg["n_layers"],
                         d_model=cfg["d_model"], n_heads=cfg["n_heads"],
                         d_ff=cfg["d_ff"], max_context=cfg["seqlen"]),
            scope=scope, length_buckets=(DECODE["bucket"],),
            slots=DECODE["slots"], block_size=DECODE["block_size"],
            pool_blocks=DECODE["pool_blocks"])
        export_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        engine.load_decode_model("lm", bundle)   # warms prefill + decode
        load_s = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        log("serve", smoke_readings=True, bundle_bytes=dir_bytes(bundle),
            export_s=round(export_s, 1), load_and_warm_s=round(load_s, 1),
            bytes_in_use_after_load=stats.get("bytes_in_use"))

        t0 = time.perf_counter()
        handles = [engine.generate("lm", p, max_new_tokens=m)
                   for p, m in requests]
        results = [h.result(timeout=600) for h in handles]
        gen_s = time.perf_counter() - t0
        snap = engine.metrics_snapshot()
    finally:
        engine.shutdown()
        shutil.rmtree(bundle, ignore_errors=True)

    served = [r["tokens"] for r in results]
    for (p, m), toks in zip(requests, served):
        if len(toks) != m:
            raise AssertionError(f"asked {m} tokens, got {len(toks)}")
    logits = teacher_forced(pt, cfg, scope,
                            [p + t for (p, _), t in zip(requests, served)])
    if not np.all(np.isfinite(logits)):
        raise AssertionError("reference logits are not finite")
    mismatches, margins = [], []
    for i, ((p, _), toks) in enumerate(zip(requests, served)):
        rows = logits[i, len(p) - 1:len(p) - 1 + len(toks)]
        ref = rows.argmax(-1)
        top2 = np.sort(rows, axis=-1)[:, -2:]
        margins.append(float((top2[:, 1] - top2[:, 0]).min()))
        if list(ref) != list(toks):
            mismatches.append({"request": i, "served": toks,
                               "reference": [int(t) for t in ref]})
    n_tok = sum(len(t) for t in served)
    log("serve", requests=len(requests), tokens=n_tok,
        prompt_lens=[len(p) for p, _ in requests],
        generate_s=round(gen_s, 2), identical=not mismatches,
        min_reference_margin=round(min(margins), 4),
        sample=served[0], decode_metrics={
            k: v for k, v in snap.get("decode", {}).get("lm", {}).items()
            if isinstance(v, (int, float))},
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    if mismatches:
        raise AssertionError(
            f"greedy tokens differ from the teacher-forced reference: "
            f"{mismatches}")


# -- --chips 4 --------------------------------------------------------------

def phase_mesh(jax, pt, cfg, seed):
    """The same LM, same seed, same global batch: a few steps on one chip
    through Executor, then on dp2 x tp2 through transpile +
    ParallelExecutor. Nothing else runs."""
    from paddle_tpu.parallel import ParallelExecutor, make_mesh
    feed = counting_batch(cfg, seed)
    devices = jax.devices()[:4]

    pt.core.program.reset_unique_names()
    main, startup, avg = build_lm(pt, cfg, seed)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        single = [exe.run(main, feed=feed, fetch_list=[avg])[0].item()
                  for _ in range(MESH_STEPS)]
        exe.close()
    del scope, exe
    gc.collect()   # the one-chip state leaves device 0 before the mesh run
    log("mesh", one_chip_losses=[round(x, 4) for x in single])

    pt.core.program.reset_unique_names()
    main, startup, avg = build_lm(pt, cfg, seed)
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=devices)
    pt.transpiler.transpile(main, mesh=mesh)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        pe = ParallelExecutor(loss_name=avg.name, main_program=main,
                              mesh=mesh, scope=scope)
        t0 = time.perf_counter()
        sharded = [pe.run([avg], feed=feed)[0].item()]
        compile_s = time.perf_counter() - t0
        sharded += [pe.run([avg], feed=feed)[0].item()
                    for _ in range(MESH_STEPS - 1)]
        log("mesh", dp2_tp2_losses=[round(x, 4) for x in sharded],
            compile_s=round(compile_s, 1), smoke_readings=True)

        held = []
        for d in devices:
            stats = d.memory_stats() or {}
            held.append({"device": d.id,
                         "bytes_in_use": stats.get("bytes_in_use"),
                         "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
        log("mesh", per_device=held)
        spread = {}
        for name in ("ffn0_in_w", "ffn0_out_w", "attn0_q_w", "lm_head_w",
                     "tok_emb"):
            v = scope.find_var(name)
            spread[name] = {
                "spec": str(v.sharding.spec),
                "shard_shape": list(v.addressable_shards[0].data.shape),
                "devices": len({s.device.id for s in v.addressable_shards})}
        log("mesh", parameters=spread)
        hlo = pe.compiled_hlo([avg], feed)
        log("mesh", collectives={
            op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")},
            tpu_custom_call=hlo.count(CUSTOM_CALL))

    if not np.all(np.isfinite(single + sharded)):
        raise AssertionError("non-finite loss")
    if not (single[-1] < single[0] and sharded[-1] < sharded[0]):
        raise AssertionError("loss did not fall on a repeated batch")
    np.testing.assert_allclose(sharded, single, rtol=MESH_RTOL)
    if jax.devices()[0].platform == "tpu":
        used = [h["bytes_in_use"] for h in held]
        if min(used) * 2 < max(used):
            raise AssertionError(f"state is not spread over the chips: "
                                 f"{used}")
    if any(s["devices"] != 4 for s in spread.values()):
        raise AssertionError(f"parameters not on all four chips: {spread}")
    log("mesh", agree_within_rtol=MESH_RTOL)


# -- main -------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes, any backend; never prints the "
                         "contract line")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log("device", **device)
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearsal:
        print(f"chip_smoke: no TPU — jax.devices() found {devices}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    import paddle_tpu as pt
    from paddle_tpu.core.compile_cache import (cache_entry_count,
                                               enable_compile_cache)
    cfg = dict(TINY if args.rehearsal else FULL)
    # depth_cut: all 6 layers of the cell fit and run; nothing is cut.
    # compile_cache_entries 0 = every compile below is cold
    log("config", compile_cache=enable_compile_cache(),
        compile_cache_entries=cache_entry_count(), depth_cut=None,
        rehearsal=args.rehearsal, seed=args.seed, **cfg)

    if args.chips == 4:
        phase_mesh(jax, pt, cfg, args.seed)
    else:
        phase_kernels(jax, cfg, on_tpu)
        scope = pt.Scope()
        params = phase_train(jax, pt, cfg, args.seed, scope)
        # the trainer's device state (Adam moments, beta powers) goes
        # before the server's pools and artifacts come
        for name in list(scope.local_var_names()):
            if name not in params:
                scope.erase(name)
        phase_serve(jax, pt, cfg, args.seed, scope)

    if args.rehearsal:
        print(json.dumps({"rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
