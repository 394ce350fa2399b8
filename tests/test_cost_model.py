"""Whole-program static cost model (analysis/cost.py + memory.py + comm.py).

Acceptance pins of the PR-7 issue:
  * static peak-HBM estimate within 15% of tools/remat_memory_report.py's
    committed measured peaks on BOTH transformer configs, remat on AND
    off (the artifacts embed the exact build config, so the estimator is
    judged against real compiled memory_analysis numbers);
  * utils/flops.py subsumed behind the same API (shim parity);
  * PT_MEM_BUDGET_GB refuses over-budget programs with the typed
    MemoryBudgetError BEFORE anything compiles, and a passing budget adds
    no work to the hot path (compile-miss only);
  * the collective audit prices dp/tp/sp placements and flags an
    intentionally mis-sharded program for an accidental all-gather;
  * the roofline declares a bound and never predicts >100% MFU.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import artifacts
from paddle_tpu.analysis.comm import audit_collectives
from paddle_tpu.analysis.cost import (ChipSpec, op_cost, predict_step,
                                      program_cost)
from paddle_tpu.analysis.memory import (MemoryBudgetError,
                                        batch_shard_factor, enforce_budget,
                                        estimate_memory)
from paddle_tpu.analysis import verify_program
from paddle_tpu.models.transformer import transformer_lm_loss
from paddle_tpu.utils.flops import program_forward_flops, program_train_flops

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _build_lm(remat=False, *, vocab=1000, seq_len=64, n_layers=2,
              d_model=64, n_heads=2, d_ff=256, amp=None, optimize=True):
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = transformer_lm_loss(
            vocab_size=vocab, seq_len=seq_len, n_layers=n_layers,
            d_model=d_model, n_heads=n_heads, d_ff=d_ff,
            max_len=max(seq_len, 128), remat=remat)
        if optimize:
            pt.optimizer.AdamOptimizer(learning_rate=1e-4).minimize(avg)
    if amp:
        main.amp_dtype = amp
    return main, avg


# ---------------------------------------------------------------------------
# flops shim parity + the historical undercount
# ---------------------------------------------------------------------------

def test_flops_shim_matches_cost_model_mxu():
    main, _ = _build_lm()
    pc = program_cost(main, batch=4)
    assert program_forward_flops(main, batch=4) == pc.forward.mxu_flops > 0
    assert program_train_flops(main, batch=4) == 3 * pc.forward.mxu_flops


def test_flops_closed_form_transformer_matmuls():
    # the closed-form LM count (benchmark/flops.py; matmul part): per token
    # n_layers*2*(4d^2 + 2*d*d_ff) + attention 4*S*d*n_layers + logits 2*d*V
    d, dff, s, v, L, b = 64, 256, 64, 1000, 2, 4
    main, _ = _build_lm(vocab=v, seq_len=s, n_layers=L, d_model=d,
                        n_heads=2, d_ff=dff)
    per_tok = L * 2 * (4 * d * d + 2 * d * dff) + L * 4 * s * d + 2 * d * v
    got = program_forward_flops(main, batch=b)
    assert abs(got - per_tok * b * s) / (per_tok * b * s) < 0.01, got


def test_vector_flops_cover_the_old_zero_ops():
    # elementwise/normalization/softmax work was priced at ZERO by the
    # pre-PR-7 counter; the cost model carries it as vector flops and
    # include_vector exposes it through the shim API
    main, _ = _build_lm()
    pc = program_cost(main, batch=4)
    assert pc.forward.vector_flops > 0
    assert (program_forward_flops(main, batch=4, include_vector=True)
            == pc.forward.flops > pc.forward.mxu_flops)
    # bytes are priced too — an op stream with zero HBM traffic is not a
    # program
    assert pc.forward.bytes_read > 0 and pc.forward.bytes_written > 0


def test_uncovered_ops_are_visible_not_silent():
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(8,), dtype="float32")
    b.vars["x"].is_data = True
    b.create_var("y", shape=(8,), dtype="float32")
    from paddle_tpu.core.program import OpDesc
    b.ops.append(OpDesc("some_exotic_op", {"X": ["x"]}, {"Out": ["y"]}, {}))
    pc = program_cost(p, batch=2)
    assert pc.uncovered_ops == ["some_exotic_op"]
    # default-modeled as elementwise traffic, not zero
    assert pc.forward.bytes_total > 0


# ---------------------------------------------------------------------------
# the 15% acceptance: static peak vs the committed compiled artifacts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tag", ["transformer_bs16", "long_context_8k"])
@pytest.mark.parametrize("key", ["no_remat", "remat"])
def test_peak_hbm_within_15pct_of_measured(tag, key):
    path = os.path.join(REPO, "docs", "artifacts",
                        f"remat_memory_{tag}.json")
    art = json.load(open(path))
    cfg = art["config"]
    main, _ = _build_lm(remat=(key == "remat"), vocab=cfg["vocab"],
                        seq_len=cfg["seq_len"], n_layers=cfg["n_layers"],
                        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
                        d_ff=4 * cfg["d_model"], amp=art["amp_dtype"])
    est = estimate_memory(main, batch=cfg["batch"])
    # the compiled step donates state, so its true residency is temp
    # (activation watermark) + arguments (state + feeds); outputs alias in
    measured = art[key]["temp_bytes"] + art[key]["argument_bytes"]
    rel = abs(est.peak_bytes - measured) / measured
    assert rel < 0.15, (f"{tag}/{key}: estimate {est.peak_bytes / 1e9:.2f} "
                        f"GB vs measured {measured / 1e9:.2f} GB "
                        f"({rel * 100:.1f}% off)\n{est.to_dict()}")
    # remat must actually shrink the estimated activation watermark
    if key == "remat":
        main_nr, _ = _build_lm(remat=False, vocab=cfg["vocab"],
                               seq_len=cfg["seq_len"],
                               n_layers=cfg["n_layers"],
                               d_model=cfg["d_model"],
                               n_heads=cfg["n_heads"],
                               d_ff=4 * cfg["d_model"],
                               amp=art["amp_dtype"])
        est_nr = estimate_memory(main_nr, batch=cfg["batch"])
        assert est.temp_bytes < est_nr.temp_bytes


def test_memory_breakdown_categories():
    main, _ = _build_lm()
    est = estimate_memory(main, batch=4)
    bd = est.breakdown
    assert set(bd) == {"params", "optimizer_state", "activations", "grads",
                       "kv_pools", "feeds"}
    assert bd["params"] > 0 and bd["grads"] > 0
    # Adam: two moments per param, both f32 — optimizer state ~= 2x params
    assert 1.5 * bd["params"] < bd["optimizer_state"] < 2.5 * bd["params"]
    assert bd["kv_pools"] == 0  # no paged ops in the LM train program
    assert est.peak_bytes >= sum(v for v in bd.values() if v > 0) * 0 \
        and est.peak_bytes > bd["params"]


# ---------------------------------------------------------------------------
# the budget gate
# ---------------------------------------------------------------------------

def _tiny_net():
    x = layers.data("x", [4], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    p = layers.fc(x, size=8)
    loss = layers.mean(layers.square(p - y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_budget_breach_raises_typed_error_before_compile(monkeypatch):
    loss = _tiny_net()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    exe = pt.Executor()
    exe.run(startup)
    monkeypatch.setenv("PT_MEM_BUDGET_GB", "1e-9")
    # pre-compile contract: the gate must fire before ANY tracing happens
    from paddle_tpu.core import lowering

    def boom(*a, **k):
        raise AssertionError("build_step_fn ran: the budget gate fired "
                             "after compile, not before")

    monkeypatch.setattr(lowering, "build_step_fn", boom)
    feed = {"x": np.zeros((2, 4), np.float32),
            "y": np.zeros((2, 1), np.float32)}
    with pytest.raises(MemoryBudgetError) as ei:
        exe.run(main, feed=feed, fetch_list=[loss.name])
    err = ei.value
    assert err.budget_gb == pytest.approx(1e-9)
    assert set(err.breakdown) == {"params", "optimizer_state",
                                  "activations", "grads", "kv_pools",
                                  "feeds"}
    assert "params=" in str(err) and "PT_MEM_BUDGET_GB" in str(err)


def test_budget_pass_is_compile_miss_only(monkeypatch):
    loss = _tiny_net()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    exe = pt.Executor()
    exe.run(startup)
    monkeypatch.setenv("PT_MEM_BUDGET_GB", "64")
    from paddle_tpu.analysis import memory as mem_mod
    calls = {"n": 0}
    real = mem_mod.estimate_memory

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(mem_mod, "estimate_memory", counting)
    feed = {"x": np.zeros((2, 4), np.float32),
            "y": np.zeros((2, 1), np.float32)}
    first = exe.run(main, feed=feed, fetch_list=[loss.name])
    assert calls["n"] == 1  # the one compile miss
    second = exe.run(main, feed=feed, fetch_list=[loss.name])
    assert calls["n"] == 1  # cache hit: the gate never re-runs
    assert np.isfinite(first[0]).all() and np.isfinite(second[0]).all()


def test_budget_unset_is_a_noop(monkeypatch):
    monkeypatch.delenv("PT_MEM_BUDGET_GB", raising=False)
    main, _ = _build_lm()
    assert enforce_budget(main, batch=2) is None
    monkeypatch.setenv("PT_MEM_BUDGET_GB", "0")
    assert enforce_budget(main, batch=2) is None


def test_budget_malformed_value_is_a_named_error(monkeypatch):
    monkeypatch.setenv("PT_MEM_BUDGET_GB", "lots")
    main, _ = _build_lm()
    with pytest.raises(ValueError, match="PT_MEM_BUDGET_GB"):
        enforce_budget(main, batch=2)


def test_budget_gate_prices_per_device_batch_on_a_mesh(monkeypatch):
    # PT_MEM_BUDGET_GB is a PER-DEVICE budget: a dp-sharded program whose
    # per-chip footprint fits must not be refused for its GLOBAL batch
    axes = {"dp": 8}
    main, _ = _transpiled_lm(axes)
    assert batch_shard_factor(main, axes) == 8
    full = estimate_memory(main, batch=64).peak_gb
    per_dev = estimate_memory(main, batch=8).peak_gb
    assert per_dev < full
    monkeypatch.setenv("PT_MEM_BUDGET_GB", f"{(per_dev + full) / 2:.9f}")
    with pytest.raises(MemoryBudgetError):
        enforce_budget(main, batch=64)  # meshless: whole-program estimate
    est = enforce_budget(main, batch=64, mesh=SimpleNamespace(shape=axes))
    assert est is not None and est.peak_bytes == estimate_memory(
        main, batch=8).peak_bytes
    # indivisible batch degrades to replication: the full batch prices
    with pytest.raises(MemoryBudgetError):
        enforce_budget(main, batch=63, mesh=SimpleNamespace(shape=axes))


# ---------------------------------------------------------------------------
# collective audit
# ---------------------------------------------------------------------------

def _transpiled_lm(axes, sp_mode=None):
    from paddle_tpu.transpiler import TranspileStrategy, transpile
    main, avg = _build_lm()
    transpile(main, mesh=SimpleNamespace(shape=axes),
              strategy=TranspileStrategy(sp_mode=sp_mode))
    return main, avg


def test_dp_grad_sync_bytes_are_exact():
    # one fc: W [4, 8] + b [8] f32 grads, ring all-reduce over dp=4:
    # wire = 2 (n-1)/n x payload
    loss = _tiny_net()
    main = pt.default_main_program()
    rep = audit_collectives(main, {"dp": 4}, batch=2)
    grads = [c for c in rep.collectives if c.op_type == "autodiff"]
    assert {c.var for c in grads} >= {"fc_0.w_0", "fc_0.b_0"}
    w = next(c for c in grads if c.var == "fc_0.w_0")
    assert w.kind == "all_reduce" and w.axes == ("dp",) and w.group == 4
    assert w.payload_bytes == 4 * 8 * 4
    assert w.wire_bytes == 2 * 3 * (4 * 8 * 4) // 4
    assert all(c.intentional for c in grads)


def test_zero_grad_sync_is_scatter_plus_gather():
    loss = _tiny_net()
    rep = audit_collectives(pt.default_main_program(), {"dp": 4}, batch=2,
                            zero=True)
    kinds = {c.kind for c in rep.collectives if c.op_type == "autodiff"}
    assert kinds == {"reduce_scatter", "all_gather"}
    assert not rep.flagged


def test_megatron_pair_prices_psum_not_gather():
    main, _ = _transpiled_lm({"dp": 2, "tp": 2})
    rep = audit_collectives(main, {"dp": 2, "tp": 2}, batch=2)
    psums = [c for c in rep.collectives
             if c.kind == "all_reduce" and c.op_type == "mul"]
    # row-parallel second matmuls: attention out-proj + ffn out per layer
    assert len(psums) == 4, [c.var for c in psums]
    assert all(c.axes == ("tp",) and c.intentional for c in psums)
    # the backward mirrors (dX partial sums of the column-parallel halves)
    assert len([c for c in rep.collectives
                if c.op_type == "mul_grad"]) == 4
    # vocab-sharded embedding combine
    assert any(c.op_type == "lookup_table" and c.intentional
               for c in rep.collectives)
    assert not rep.flagged, [c.reason for c in rep.flagged]


@pytest.mark.parametrize("sp_mode,kind", [("ring", "ppermute"),
                                          ("ulysses", "all_to_all")])
def test_sp_attention_collectives_on_dryrun_mesh(sp_mode, kind):
    axes = {"dp": 2, "sp": 2, "tp": 2}
    main, _ = _transpiled_lm(axes, sp_mode=sp_mode)
    rep = audit_collectives(main, axes, batch=2)
    sp_colls = [c for c in rep.collectives if c.kind == kind]
    assert len(sp_colls) == 2  # one per layer
    assert all(c.axes == ("sp",) and c.intentional and c.wire_bytes > 0
               for c in sp_colls)
    assert not rep.flagged, [c.reason for c in rep.flagged]
    # every collective carries its byte volume
    assert all(c.payload_bytes > 0 for c in rep.collectives)


def test_missharded_program_flagged_for_accidental_all_gather():
    # a column-parallel logits projection nobody paired: the vocab-sharded
    # logits hit softmax_with_cross_entropy, which cannot consume a
    # feature-sharded operand — the audit must flag the silent gather
    main, _ = _build_lm()
    main.global_block.var("lm_head_w").sharding = (None, "tp")
    rep = audit_collectives(main, {"dp": 2, "tp": 2}, batch=2)
    assert rep.flagged, "mis-sharded program produced no flag"
    bad = rep.flagged[0]
    assert bad.kind == "all_gather" and "tp" in bad.axes
    assert bad.op_type == "softmax_with_cross_entropy"
    assert bad.wire_bytes > 0
    # ... and it surfaces through the verifier pass as a warning
    res = verify_program(main, feeds=["src_ids", "tgt_ids"],
                         mesh={"dp": 2, "tp": 2})
    hits = [d for d in res if d.code == "accidental-all-gather"]
    assert hits and hits[0].severity == "warning"
    assert "MB on the wire" in hits[0].message
    # a well-sharded program stays quiet
    good, _ = _transpiled_lm({"dp": 2, "tp": 2})
    res2 = verify_program(good, feeds=["src_ids", "tgt_ids"],
                          mesh={"dp": 2, "tp": 2})
    assert not [d for d in res2 if d.code == "accidental-all-gather"]


def test_audit_without_mesh_axes_is_empty_and_pass_skips():
    main, _ = _build_lm()
    assert audit_collectives(main, {}, batch=2).collectives == []
    # the verifier pass no-ops without a mesh (single-chip executor path)
    res = verify_program(main, feeds=["src_ids", "tgt_ids"],
                         passes=["collective-audit"])
    assert res.ok and not res.diagnostics


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def test_roofline_bound_follows_the_binding_leg():
    main, _ = _build_lm()
    fat_hbm = ChipSpec("t", peak_flops=1e9, hbm_gbps=1e6, ici_gbps=1e6)
    assert predict_step(main, batch=2, chip=fat_hbm).bound == "compute"
    fat_mxu = ChipSpec("t", peak_flops=1e18, hbm_gbps=1e-3, ici_gbps=1e6)
    assert predict_step(main, batch=2, chip=fat_mxu).bound == "bandwidth"
    slow_ici = ChipSpec("t", peak_flops=1e18, hbm_gbps=1e6, ici_gbps=1e-6)
    pred = predict_step(main, batch=2, chip=slow_ici, mesh={"dp": 2})
    assert pred.bound == "comm" and pred.comm_bytes > 0


def test_roofline_never_predicts_over_100pct_mfu():
    main, _ = _build_lm()
    absurd = ChipSpec("t", peak_flops=1e-3, hbm_gbps=1e9, ici_gbps=1e9)
    pred = predict_step(main, batch=2, chip=absurd)
    assert 0.0 <= pred.predicted_mfu <= 1.0
    assert pred.predicted_step_ms > 0
    # and the emitted dict passes the artifact prediction floors
    assert artifacts.validate_bench_json({"prediction": pred.to_dict()}) \
        == []


def test_pt_cost_chip_override(monkeypatch):
    from paddle_tpu.analysis.cost import resolve_chip
    monkeypatch.setenv("PT_COST_CHIP", "tpu v5e")
    assert resolve_chip().name == "tpu v5e"
    monkeypatch.setenv("PT_COST_CHIP", "tpu v5p")
    assert resolve_chip().peak_flops == 459e12


# ---------------------------------------------------------------------------
# artifact floor checks over cost outputs (bench save AND load surface)
# ---------------------------------------------------------------------------

def test_prediction_floor_checks():
    ok = {"configs": {"resnet50": {
        "mfu_pct": 31.0, "predicted_mfu_pct": 40.0, "bound": "bandwidth",
        "prediction": {"flops": 10, "hbm_bytes": 5, "comm_bytes": 0,
                       "t_compute_ms": 0.0001, "predicted_step_ms": 0.0002,
                       "predicted_mfu": 0.4, "bound": "bandwidth"}}}}
    assert artifacts.validate_bench_json(ok) == []
    # tiny predicted times are NOT held to the 0.05 ms measurement floor,
    # but zero/negative work and impossible utilization are rejected
    for patch, frag in [
            ({"flops": 0}, "flops"),
            ({"hbm_bytes": -1}, "hbm_bytes"),
            ({"predicted_step_ms": 0.0}, "predicted_step_ms"),
            ({"predicted_mfu": 1.7}, "predicted_mfu"),
            ({"bound": "magic"}, "bound")]:
        doc = {"prediction": {"flops": 10, "hbm_bytes": 5,
                              "predicted_step_ms": 0.001,
                              "predicted_mfu": 0.4, "bound": "compute"}}
        doc["prediction"].update(patch)
        probs = artifacts.validate_bench_json(doc)
        assert probs and frag in probs[0], (patch, probs)
    # measurement keys OUTSIDE prediction objects keep the physical band
    assert artifacts.validate_bench_json({"ms_per_batch": 0.0})
    assert artifacts.validate_bench_json({"mfu_pct": 150.0})


def test_cost_report_schema_check():
    from paddle_tpu.analysis.artifacts import validate_cost_report
    good = {"program": "x", "batch": 2, "cost": {"train_flops": 1,
                                                 "train_bytes": 1},
            "memory": {"peak_bytes": 10, "breakdown": {"params": 5}},
            "prediction": {"predicted_mfu": 0.1, "bound": "compute",
                           "flops": 1, "hbm_bytes": 1,
                           "predicted_step_ms": 0.01}}
    assert validate_cost_report(good) == []
    bad = dict(good, cost={"train_flops": 0, "train_bytes": 1})
    assert any("train_flops" in p for p in validate_cost_report(bad))
    assert any("required section" in p
               for p in validate_cost_report({"program": "x"}))


# ---------------------------------------------------------------------------
# is_data survives serialization (the audit + verifier read it off clones)
# ---------------------------------------------------------------------------

def test_is_data_survives_clone_and_roundtrip():
    _tiny_net()
    main = pt.default_main_program()
    assert main.global_block.var("x").is_data
    clone = main.clone()
    assert clone.global_block.var("x").is_data
    rt = pt.Program.from_dict(main.to_dict())
    assert rt.global_block.var("x").is_data
