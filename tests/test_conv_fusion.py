"""Conv-epilogue fusion (analysis/fuse.py + ops/fused_ops.fused_conv2d
+ kernels/fused_conv.py): legality matrix, fused-vs-unfused parity (fwd
AND bwd), the PT_FUSE=0 bit-for-bit restore, the cost/memory
strict-decrease regressions, the conv-fusion verifier pass, and the
Pallas epilogue's interpret-mode numerics."""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import fuse
from paddle_tpu.core.program import OpDesc

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


def _fused_ops(program):
    return [op for op in program.global_block.ops
            if op.type == "fused_conv2d"]


def _build_residual_net(with_opt=True, amp=None):
    """conv+bn(relu) main path, conv+bn shortcut, residual add + relu —
    the ResNet bottleneck tail shape both fusion patterns must cover."""
    pt.core.program.reset_unique_names()
    main, start = pt.Program(), pt.Program()
    with pt.program_guard(main, start):
        x = layers.data("x", shape=(8, 12, 12), dtype="float32")
        lab = layers.data("y", shape=(1,), dtype="float32")
        c = layers.conv2d(
            x, num_filters=8, filter_size=3, padding=1, bias_attr=False,
            param_attr=pt.ParamAttr(initializer=pt.initializer.Xavier(seed=7)))
        y = layers.batch_norm(c, act="relu")
        sc = layers.conv2d(
            x, num_filters=8, filter_size=1, bias_attr=False,
            param_attr=pt.ParamAttr(initializer=pt.initializer.Xavier(seed=9)))
        sb = layers.batch_norm(sc)
        z = layers.elementwise_add(y, sb)
        r = layers.relu(z)
        p = layers.pool2d(r, pool_type="avg", global_pooling=True)
        f = layers.reshape(p, shape=(-1, 8))
        pred = layers.fc(
            f, size=1,
            param_attr=pt.ParamAttr(initializer=pt.initializer.Xavier(seed=11)))
        loss = layers.mean(layers.square_error_cost(pred, lab))
        if with_opt:
            pt.optimizer.SGD(learning_rate=0.05).minimize(loss)
    if amp:
        main.amp_dtype = amp
    return main, start, loss


def _feed(batch=4):
    rng = np.random.RandomState(0)
    return {"x": rng.randn(batch, 8, 12, 12).astype(np.float32),
            "y": rng.randn(batch, 1).astype(np.float32)}


# ---------------------------------------------------------------------------
# pass legality matrix
# ---------------------------------------------------------------------------

def test_residual_chains_fuse():
    main, _, loss = _build_residual_net()
    before = [op.type for op in main.global_block.ops]
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 2
    ops = _fused_ops(fused)
    assert len(ops) == 2
    # the original program is untouched (rewrite-on-clone contract)
    assert [op.type for op in main.global_block.ops] == before
    # main path: BN's fuse_with_relu folded as the act epilogue
    plain = [op for op in ops if not op.attrs["with_add"]]
    resid = [op for op in ops if op.attrs["with_add"]]
    assert len(plain) == 1 and len(resid) == 1
    assert plain[0].attrs["act"] == "relu"
    assert plain[0].attrs["fused_from"] == ["conv2d", "batch_norm"]
    # shortcut path: absorbed the residual add AND the tail relu, with
    # the main path's output as Addend
    assert resid[0].attrs["act"] == "relu"
    assert resid[0].attrs["fused_from"] == [
        "conv2d", "batch_norm", "elementwise_add", "relu"]
    assert resid[0].input("Addend") == plain[0].output("Output")
    # absorbed ops and their intermediates are gone (the one surviving
    # elementwise_add is the fc bias, not the absorbed residual add)
    kinds = [op.type for op in fused.global_block.ops]
    assert "batch_norm" not in kinds and "relu" not in kinds
    assert kinds.count("elementwise_add") == \
        [op.type for op in main.global_block.ops].count(
            "elementwise_add") - 1
    for op in ops:
        for nm in (op.input("Input") + op.input("Filter")
                   + op.output("Output")):
            assert nm in fused.global_block.vars


def test_multi_consumer_refusal():
    pt.core.program.reset_unique_names()
    main, start = pt.Program(), pt.Program()
    with pt.program_guard(main, start):
        x = layers.data("x", shape=(4, 6, 6), dtype="float32")
        c = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                          bias_attr=False)
        y = layers.batch_norm(c, act="relu")
        # second consumer of the conv output: fusing would erase a value
        # another op still reads
        side = layers.mean(c)
        loss = layers.mean(y) + side
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 0
    assert not _fused_ops(fused)


def test_protected_intermediate_refusal():
    main, _, loss = _build_residual_net(with_opt=False)
    conv_out = next(op for op in main.global_block.ops
                    if op.type == "conv2d").output("Output")[0]
    fused, n = fuse.fuse_program(main, protect=[loss.name, conv_out])
    # the protected chain is refused; the other still fuses
    assert n == 1
    assert all(conv_out not in (op.input("Input") + op.output("Output"))
               or op.type != "fused_conv2d"
               for op in fused.global_block.ops)


def test_dtype_mismatch_refusal():
    pt.core.program.reset_unique_names()
    main, start = pt.Program(), pt.Program()
    with pt.program_guard(main, start):
        x = layers.data("x", shape=(4, 6, 6), dtype="float32")
        c = layers.conv2d(x, num_filters=4, filter_size=3, padding=1,
                          bias_attr=False)
        y = layers.batch_norm(c)
        loss = layers.mean(y)
    bn = next(op for op in main.global_block.ops
              if op.type == "batch_norm")
    main.global_block.vars[bn.output("Y")[0]].dtype = "bfloat16"
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 0


def test_amp_program_fuses():
    main, _, loss = _build_residual_net(amp="bfloat16")
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 2
    assert fused.amp_dtype == "bfloat16"


def test_pt_fuse_off_restores_bit_for_bit(monkeypatch):
    main, _, loss = _build_residual_net()
    fp = main.fingerprint()
    monkeypatch.setenv("PT_FUSE", "0")
    out = fuse.maybe_fuse(main, protect=[loss.name])
    assert out is main
    assert out.fingerprint() == fp
    monkeypatch.setenv("PT_FUSE", "1")
    out = fuse.maybe_fuse(main, protect=[loss.name])
    assert out is not main and _fused_ops(out)
    # memoized: the same (fingerprint, protect) returns the same clone
    assert fuse.maybe_fuse(main, protect=[loss.name]) is out


def test_fusion_never_touches_autodiff_anchors():
    main, _, loss = _build_residual_net(with_opt=True)
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 2
    from paddle_tpu.core.lowering import AUTODIFF_OP
    ad = [op for op in fused.global_block.ops if op.type == AUTODIFF_OP]
    assert len(ad) == 1
    for nm in ad[0].attrs.get("grad_names", []):
        assert nm in fused.global_block.vars


# ---------------------------------------------------------------------------
# parity: fused vs PT_FUSE=0, forward AND backward, through the executor
# ---------------------------------------------------------------------------

def _run_arm(main, start, loss, fuse_on, steps, monkeypatch, amp=None):
    monkeypatch.setenv("PT_FUSE", "1" if fuse_on else "0")
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(start)
        feed = _feed()
        losses = []
        for _ in range(steps):
            (l,) = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            losses.append(np.asarray(l, dtype=np.float32).reshape(-1)[0])
        w = np.asarray(scope.find_var("conv2d_0.w_0"))
        rm = np.asarray(scope.find_var("batch_norm_0.tmp_0"))
    return np.asarray(losses), w, rm


@pytest.mark.parametrize("amp", [None, "bfloat16"])
def test_train_parity_fused_vs_unfused(monkeypatch, amp):
    main, start, loss = _build_residual_net(amp=amp)
    lf, wf, rmf = _run_arm(main, start, loss, True, 3, monkeypatch, amp)
    lu, wu, rmu = _run_arm(main, start, loss, False, 3, monkeypatch, amp)
    # identical math (conv + _bn_train composition) on the same rig:
    # losses, trained weights, and running stats all agree — the bwd
    # through the fused op IS the unfused chain's bwd
    np.testing.assert_allclose(lf, lu, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(wf, wu, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(rmf, rmu, rtol=1e-5, atol=1e-6)


def test_inference_parity_fused_vs_unfused(monkeypatch):
    pt.core.program.reset_unique_names()
    main, start = pt.Program(), pt.Program()
    with pt.program_guard(main, start):
        x = layers.data("x", shape=(6, 10, 10), dtype="float32")
        c = layers.conv2d(
            x, num_filters=4, filter_size=3, padding=1, bias_attr=False,
            param_attr=pt.ParamAttr(initializer=pt.initializer.Xavier(seed=3)))
        y = layers.batch_norm(c, act="relu", is_test=True)
        out = layers.mean(y)
    feed = {"x": np.random.RandomState(1).randn(2, 6, 10, 10)
            .astype(np.float32)}

    def run(on):
        monkeypatch.setenv("PT_FUSE", "1" if on else "0")
        scope = pt.Scope()
        with pt.scope_guard(scope):
            exe = pt.Executor()
            exe.run(start)
            (v,) = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
        return np.asarray(v)

    # inference folds BN into the conv weights/bias — a reassociation,
    # so a small float tolerance (not bit equality) is the contract
    np.testing.assert_allclose(run(True), run(False), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# cost + memory strict decreases
# ---------------------------------------------------------------------------

def test_cost_entry_strict_decrease():
    from paddle_tpu.analysis.cost import program_cost
    main, _, loss = _build_residual_net(with_opt=False)
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 2
    cu = program_cost(main, batch=4)
    cf = program_cost(fused, batch=4)
    # same MXU work (the convs are untouched) ...
    assert cf.forward.mxu_flops == cu.forward.mxu_flops
    # ... strictly fewer HBM bytes: the eliminated BN/add/relu
    # round-trips drop out of the model structurally
    assert cf.forward.bytes_read < cu.forward.bytes_read
    assert cf.forward.bytes_written < cu.forward.bytes_written
    assert cf.train.bytes_read < cu.train.bytes_read
    # and nothing fell out of coverage
    assert not cf.uncovered_ops


def test_memory_estimate_drops_fused_residuals():
    from paddle_tpu.analysis.memory import estimate_memory
    main, _, loss = _build_residual_net(with_opt=True)
    fused, n = fuse.fuse_program(main, protect=[loss.name])
    assert n == 2
    eu = estimate_memory(main, batch=4)
    ef = estimate_memory(fused, batch=4)
    assert ef.details["residual_bytes"] < eu.details["residual_bytes"]
    assert ef.peak_bytes <= eu.peak_bytes


# ---------------------------------------------------------------------------
# verifier conv-fusion pass
# ---------------------------------------------------------------------------

def test_verifier_accepts_legal_fusion():
    from paddle_tpu.analysis import verify_program
    main, _, loss = _build_residual_net()
    fused, _ = fuse.fuse_program(main, protect=[loss.name])
    res = verify_program(fused, feeds=["x", "y"], fetches=[loss.name],
                         passes=["conv-fusion", "dtype-prop", "def-use"])
    assert not [d for d in res.diagnostics if d.severity == "error"]


def _first_fused(program):
    return next(op for op in program.global_block.ops
                if op.type == "fused_conv2d")


def _fusion_errors(program):
    from paddle_tpu.analysis import verify_program
    res = verify_program(program, passes=["conv-fusion"])
    return [d.code for d in res.diagnostics if d.severity == "error"]


def test_verifier_rejects_addend_attr_slot_disagreement():
    main, _, loss = _build_residual_net()
    fused, _ = fuse.fuse_program(main, protect=[loss.name])
    op = _first_fused(fused)
    op.attrs["with_add"] = not op.attrs["with_add"]
    assert "fusion-addend" in _fusion_errors(fused)


def test_verifier_rejects_unknown_act_and_bad_attrs():
    main, _, loss = _build_residual_net()
    fused, _ = fuse.fuse_program(main, protect=[loss.name])
    op = _first_fused(fused)
    op.attrs["act"] = "gelu"
    op.attrs["junk"] = object()          # not JSON-serializable
    errs = _fusion_errors(fused)
    assert "fusion-act" in errs and "fusion-attrs" in errs


def test_verifier_rejects_epilogue_dtype_break():
    main, _, loss = _build_residual_net()
    fused, _ = fuse.fuse_program(main, protect=[loss.name])
    op = _first_fused(fused)
    fused.global_block.vars[op.input("Scale")[0]].dtype = "float16"
    fused.global_block.vars[op.output("Output")[0]].dtype = "bfloat16"
    errs = _fusion_errors(fused)
    assert errs.count("fusion-dtype") >= 2


def test_verifier_rejects_missing_stat_output():
    main, _, loss = _build_residual_net()
    fused, _ = fuse.fuse_program(main, protect=[loss.name])
    op = _first_fused(fused)
    del op.outputs["SavedVariance"]
    assert "fusion-slot" in _fusion_errors(fused)


# ---------------------------------------------------------------------------
# Pallas epilogue numerics (interpret mode) + autotune gate mechanics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("with_add", [True, False])
def test_epilogue_interpret_matches_reference(monkeypatch, relu, with_add):
    from paddle_tpu.kernels import fused_conv as fc
    monkeypatch.setattr(fc, "INTERPRET", True)
    rng = jax.random.PRNGKey(0)
    a = jax.random.normal(rng, (2, 3, 4, 5), jnp.float32)
    add = a * 0.5 if with_add else None
    g = jnp.linspace(0.5, 1.5, 3)
    b = jnp.linspace(-0.1, 0.1, 3)
    rm, rv = jnp.zeros((3,)), jnp.ones((3,))

    def tot(fn):
        def f(a_, g_, b_, add_):
            outs = fn(a_, g_, b_, rm, rv, add_, 1e-5, 0.9, relu)
            return sum(jnp.sum(o * w) for o, w in
                       zip(outs, (1.0, 0.3, 0.3, 0.2, 0.2))), outs
        return f

    argnums = (0, 1, 2) + ((3,) if with_add else ())
    (_, outs_k), gk = jax.value_and_grad(
        tot(fc.fused_conv_epilogue), argnums=argnums, has_aux=True)(
        a, g, b, add)
    (_, outs_r), gr = jax.value_and_grad(
        tot(fc._reference_epilogue), argnums=argnums, has_aux=True)(
        a, g, b, add)
    for yk, yr in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
    for dk, dr in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                                   rtol=1e-4, atol=1e-4)


def test_epilogue_gate_and_cache(monkeypatch, tmp_path):
    from paddle_tpu.kernels import fused_conv as fc
    path = tmp_path / "fused_conv_autotune.json"
    monkeypatch.setenv("PT_FUSE_CACHE", str(path))
    fc._CACHE.reset()
    try:
        monkeypatch.setattr(
            fc, "measure",
            lambda *a, **k: {"xla_ms": 2.0, "pallas_ms": 1.0,
                             "prefers_pallas": True})
        fc.ensure_tuned(4, 8, 16, 16, "float32", relu=True)
        key = fc.shape_key(4, 8, 16, 16, "float32", relu=True)
        assert fc.lookup(key) is True
        # the gate: never wins over any cache entry; off-TPU auto is off
        monkeypatch.setenv("PT_FUSE_EPILOGUE", "never")
        assert not fc.epilogue_enabled(None, 4, 8, 16, 16, "float32")
        monkeypatch.delenv("PT_FUSE_EPILOGUE")
        if jax.default_backend() != "tpu":
            assert not fc.epilogue_enabled(None, 4, 8, 16, 16, "float32")
        # schema-envelope on disk; corrupt file discards, then self-heals
        import json
        doc = json.loads(path.read_text())
        assert doc["schema"] >= 2 and key in doc["entries"]
        path.write_text("{not json")
        fc._CACHE.reset()
        assert fc.lookup(key) is None
        fc.ensure_tuned(4, 8, 16, 16, "float32", relu=True)
        assert fc.lookup(key) is True
    finally:
        fc._CACHE.reset()


def test_fused_autotune_artifact_validation():
    from paddle_tpu.analysis.artifacts import (check_autotune_entry,
                                               validate_autotune_cache)
    ent = {"xla_ms": 2.0, "pallas_ms": 1.0, "prefers_pallas": True}
    assert not check_autotune_entry(
        "k", ent, decision_field="prefers_pallas",
        ms_fields=("xla_ms", "pallas_ms"))
    bad = dict(ent, pallas_ms=0.0)
    assert check_autotune_entry(
        "k", bad, decision_field="prefers_pallas",
        ms_fields=("xla_ms", "pallas_ms"))
    doc = {"schema": 2, "entries": {"k": ent}}
    assert not validate_autotune_cache(
        doc, decision_field="prefers_pallas",
        ms_fields=("xla_ms", "pallas_ms"))


# ---------------------------------------------------------------------------
# the fusion A/B artifact schema (no emitter: ROADMAP D15)
# ---------------------------------------------------------------------------

def test_validate_fusion_ab():
    from paddle_tpu.analysis.artifacts import validate_fusion_ab
    good = {
        "schema_version": 1,
        "arms": {"fused": {"step_ms": 10.0, "steps": 4, "fused_ops": 16},
                 "unfused": {"step_ms": 12.5, "steps": 4}},
        "speedup": 1.25,
        "parity": {"loss_delta_rel": 0.0, "tolerance": 5e-3},
        "op_attribution_coverage": 97.2,
    }
    assert validate_fusion_ab(good) == []
    # slowdown without explanation is rejected; with one it passes
    slow = dict(good, speedup=0.97)
    assert any("explanation" in p for p in validate_fusion_ab(slow))
    slow["explanation"] = "CPU rig: XLA already fuses the lax chain"
    assert validate_fusion_ab(slow) == []
    # parity outside the declared band / missing legs are rejected
    assert any("tolerance" in p for p in validate_fusion_ab(
        dict(good, parity={"loss_delta_rel": 0.1, "tolerance": 5e-3})))
    assert validate_fusion_ab(dict(good, parity=None))
    assert any("fused_ops" in p for p in validate_fusion_ab(
        {**good, "arms": {"fused": {"step_ms": 10.0, "steps": 4,
                                    "fused_ops": 0},
                          "unfused": {"step_ms": 12.5, "steps": 4}}}))
    # the coverage floor is part of the schema
    assert any("coverage" in p for p in validate_fusion_ab(
        dict(good, op_attribution_coverage=80.0)))
    assert validate_fusion_ab(dict(good, speedup=float("nan")))
