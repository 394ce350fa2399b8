"""Static verifier + repo lint (paddle_tpu/analysis/): one seeded program
per defect class, each asserted to surface with op/var names and block
index — the acceptance contract of the round-6 lint-gate issue.

Defect classes: dangling input, dtype mismatch, dead op, double-write,
uneven shard, impossible autotune reading — plus clean-pass pins on real
built programs (a trained fc net single-chip and transpiled) so the
default-on PT_VERIFY gate provably doesn't cry wolf.
"""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.analysis import (ProgramVerificationError, artifacts,
                                 registered_passes, verify_program)
from paddle_tpu.analysis.source_lint import (check_env_knobs,
                                             check_joined_continuation,
                                             declared_knobs_from_flags,
                                             lint_file)
from paddle_tpu.core.program import OpDesc

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _codes(result):
    return {d.code for d in result}


def _find(result, code):
    hits = [d for d in result if d.code == code]
    assert hits, f"no {code!r} diagnostic in:\n{result.report()}"
    return hits


# ---------------------------------------------------------------------------
# seeded defect programs — one per class
# ---------------------------------------------------------------------------

def test_dangling_input_is_reported():
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(2, 2), dtype="float32")
    b.vars["x"].is_data = True
    b.create_var("y", shape=(2, 2), dtype="float32")
    # hand-built op (bypasses append_op) reading a name that exists nowhere
    b.ops.append(OpDesc("relu", {"X": ["ghost"]}, {"Out": ["y"]}, {}))
    res = verify_program(p, fetches=["y"])
    d = _find(res, "dangling-input")[0]
    assert d.severity == "error"
    assert d.var == "ghost" and d.op_type == "relu" and d.block_idx == 0
    with pytest.raises(ProgramVerificationError):
        res.raise_if_errors()


def test_dtype_mismatch_is_reported():
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(2, 2), dtype="float32")
    b.vars["x"].is_data = True
    # recorded as int32, but relu propagates its input's float32
    b.create_var("y", shape=(2, 2), dtype="int32")
    b.ops.append(OpDesc("relu", {"X": ["x"]}, {"Out": ["y"]}, {}))
    res = verify_program(p, fetches=["y"])
    d = _find(res, "dtype-mismatch")[0]
    assert d.severity == "error"
    assert d.var == "y" and d.op_type == "relu" and d.block_idx == 0
    assert "int32" in d.message and "float32" in d.message


def test_dead_op_is_reported_with_prune_suggestion():
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(2, 2), dtype="float32")
    b.vars["x"].is_data = True
    b.create_var("y", shape=(2, 2), dtype="float32")
    b.create_var("z", shape=(2, 2), dtype="float32")
    b.append_op("relu", {"X": "x"}, {"Out": "y"})
    b.append_op("tanh", {"X": "x"}, {"Out": "z"})  # z fetched; y is dead
    res = verify_program(p, feeds=["x"], fetches=["z"])
    d = _find(res, "dead-op")[0]
    assert d.severity == "warning"
    assert d.op_type == "relu" and d.block_idx == 0 and "prune" in d.message
    # the same program with y fetched is clean of dead-ops
    res2 = verify_program(p, feeds=["x"], fetches=["y", "z"])
    assert "dead-op" not in _codes(res2)


def test_double_write_is_reported():
    p = pt.Program()
    b = p.global_block
    b.create_var("c", shape=(1,), dtype="float32")
    b.append_op("fill_constant", {}, {"Out": "c"},
                {"shape": [1], "value": 1.0, "dtype": "float32"})
    b.append_op("fill_constant", {}, {"Out": "c"},
                {"shape": [1], "value": 2.0, "dtype": "float32"})
    res = verify_program(p, fetches=["c"])
    d = _find(res, "double-write")[0]
    assert d.var == "c" and d.block_idx == 0
    assert "op 0" in d.message and "fill_constant" in d.message
    # a read between the writes dissolves the hazard
    p2 = pt.Program()
    b2 = p2.global_block
    b2.create_var("c", shape=(1,), dtype="float32")
    b2.create_var("r", shape=(1,), dtype="float32")
    b2.append_op("fill_constant", {}, {"Out": "c"},
                 {"shape": [1], "value": 1.0, "dtype": "float32"})
    b2.append_op("scale", {"X": "c"}, {"Out": "r"}, {"scale": 2.0})
    b2.append_op("fill_constant", {}, {"Out": "c"},
                 {"shape": [1], "value": 2.0, "dtype": "float32"})
    assert "double-write" not in _codes(verify_program(p2, fetches=["c", "r"]))


def test_uneven_shard_is_reported():
    p = pt.Program()
    b = p.global_block
    v = b.create_var("w", shape=(5, 8), dtype="float32",
                     persistable=True, is_parameter=True)
    v.sharding = ("tp", None)
    res = verify_program(p, mesh={"tp": 4})
    d = _find(res, "uneven-shard")[0]
    # warning, not error: the documented runtime contract degrades a
    # non-divisible dim to replication (pinned by
    # test_sparse_embedding's non-divisible-vocab fallback test)
    assert d.severity == "warning"
    assert d.var == "w" and d.block_idx == 0
    assert "dim 0" in d.message and "5" in d.message
    # evenly divisible is silent
    assert "uneven-shard" not in _codes(verify_program(p, mesh={"tp": 5}))
    v.sharding = ("xx", None)
    # no mesh: an axis outside the dp/tp/pp/sp/ep alphabet is a typo
    d = _find(verify_program(p), "unknown-mesh-axis")[0]
    assert d.severity == "error"
    # concrete mesh: spec_for documents dropping absent axes — warning
    d = _find(verify_program(p, mesh={"tp": 4}), "mesh-axis-dropped")[0]
    assert d.severity == "warning"


def test_impossible_autotune_reading_is_rejected():
    good = {"native_ms": 2.0, "dense_ms": 1.0, "prefers_dense": True}
    zero = {"native_ms": 0.0, "dense_ms": 1.0, "prefers_dense": False}
    nan = {"native_ms": float("nan"), "dense_ms": 1.0, "prefers_dense": False}
    cache = {"k_good": good, "k_zero": zero, "k_nan": nan,
             "k_err": {"error": "RuntimeError: x", "prefers_dense": False}}
    problems = artifacts.validate_autotune_cache(cache)
    assert any("k_zero" in p for p in problems)
    assert any("k_nan" in p for p in problems)
    assert not any("k_good" in p or "k_err" in p for p in problems)
    # load-time self-heal keeps only entries a decision may trust
    kept = artifacts.filter_autotune_cache(cache)
    assert set(kept) == {"k_good", "k_err"}


def test_bench_json_floor_checks():
    doc = {"configs": {"resnet50": {"ms_per_batch": 49.0, "mfu": 0.31},
                       "tfm": {"ms_per_batch": 60.0, "mfu_pct": 61.0},
                       "broken": {"ms_per_batch": 0.0},
                       "sureal": {"ms_per_batch": 9.0, "mfu_pct": 500.0},
                       "over": {"ms_per_batch": 9.0, "hfu": 5.0}},
           "notes": [{"step_ms": -3.0}]}
    problems = artifacts.validate_bench_json(doc)
    assert any("broken" in p for p in problems)
    assert any("step_ms" in p for p in problems)
    # >100% utilization is as impossible as 0.0 ms (pct- and
    # fraction-style bounds)
    assert any("sureal" in p for p in problems)
    assert any("over" in p for p in problems)
    assert not any("resnet50" in p or "tfm" in p for p in problems)


# ---------------------------------------------------------------------------
# structural checks beyond the six classes
# ---------------------------------------------------------------------------

def test_undeclared_output_and_dangling_block():
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(2,), dtype="float32")
    b.vars["x"].is_data = True
    b.ops.append(OpDesc("relu", {"X": ["x"]}, {"Out": ["nowhere"]}, {}))
    b.ops.append(OpDesc("while", {"X": ["x"]}, {}, {"sub_block": 99}))
    res = verify_program(p, fetches=["nowhere"])
    assert {"undeclared-output", "dangling-block"} <= _codes(res)


def test_use_before_def_is_a_warning_not_error():
    p = pt.Program()
    b = p.global_block
    b.create_var("a", shape=(2,), dtype="float32")  # declared, never made
    b.create_var("y", shape=(2,), dtype="float32")
    b.ops.append(OpDesc("relu", {"X": ["a"]}, {"Out": ["y"]}, {}))
    res = verify_program(p, fetches=["y"])
    d = _find(res, "use-before-def")[0]
    assert d.severity == "warning" and d.var == "a"
    # naming it as a feed silences the warning
    assert "use-before-def" not in _codes(
        verify_program(p, feeds=["a"], fetches=["y"]))


# ---------------------------------------------------------------------------
# clean-pass pins: real programs must verify clean (no errors)
# ---------------------------------------------------------------------------

def _build_trained_net():
    x = layers.data("x", [4], dtype="float32")
    y = layers.data("y", [1], dtype="float32")
    h = layers.fc(x, size=8, act="relu")
    p = layers.fc(h, size=1, act=None)
    loss = layers.mean(layers.square(p - y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_trained_program_verifies_clean():
    loss = _build_trained_net()
    main, startup = pt.default_main_program(), pt.default_startup_program()
    assert verify_program(main, feeds=["x", "y"], fetches=[loss.name]).ok, \
        verify_program(main, feeds=["x", "y"], fetches=[loss.name]).report()
    assert verify_program(startup).ok
    # and the executor pre-pass (PT_VERIFY=1 via conftest) accepts it live
    exe = pt.Executor()
    exe.run(startup)
    out = exe.run(main,
                  feed={"x": np.zeros((2, 4), np.float32),
                        "y": np.zeros((2, 1), np.float32)},
                  fetch_list=[loss.name])
    assert np.isfinite(out[0]).all()


def test_transpiled_program_verifies_clean_on_mesh():
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.transpiler import transpile

    x = layers.data("x", [16], dtype="float32")
    h = layers.fc(x, size=32, act="relu")
    h2 = layers.fc(h, size=16, act=None)
    loss = layers.mean(h2)
    pt.append_backward(loss)
    mesh = make_mesh({"dp": 2, "tp": 4})
    main = transpile(pt.default_main_program(), mesh=mesh)
    res = verify_program(main, feeds=["x"], fetches=[loss.name], mesh=mesh)
    assert res.ok, res.report()


def test_executor_prepass_rejects_defective_program():
    assert os.environ.get("PT_VERIFY") == "1"  # conftest default-on
    p = pt.Program()
    b = p.global_block
    b.create_var("y", shape=(2,), dtype="float32")
    b.ops.append(OpDesc("relu", {"X": ["ghost"]}, {"Out": ["y"]}, {}))
    with pytest.raises(ProgramVerificationError, match="dangling-input"):
        pt.Executor().run(p, feed={}, fetch_list=["y"])


def test_host_boundary_enforced_for_host_ops():
    """No in-tree op is host-resident yet (host surfaces are modules, not
    program ops) — synthetic registrations prove the contract the next
    host-resident op lands under."""
    from paddle_tpu.core import registry as reg

    if reg.get_op("__test_host_read") is None:
        reg.register_op("__test_host_read", is_host_op=True)(
            lambda ctx, ins, attrs: {"Out": [None]})
    if reg.get_op("__test_to_device") is None:
        reg.register_op("__test_to_device")(
            lambda ctx, ins, attrs: {"Out": [ins["X"][0]]})

    p = pt.Program()
    b = p.global_block
    for n in ("hrows", "consumed"):
        b.create_var(n, shape=(2,), dtype="float32")
    b.ops.append(OpDesc("__test_host_read", {}, {"Out": ["hrows"]}, {}))
    b.ops.append(OpDesc("relu", {"X": ["hrows"]}, {"Out": ["consumed"]}, {}))
    res = verify_program(p, fetches=["consumed"], passes=["shard-check"])
    d = _find(res, "host-boundary")[0]
    assert d.severity == "error" and d.op_type == "relu" and d.var == "hrows"

    # consuming through a registered boundary op is legal
    reg.register_host_boundary("__test_to_device")
    p2 = pt.Program()
    b2 = p2.global_block
    for n in ("hrows", "dev"):
        b2.create_var(n, shape=(2,), dtype="float32")
    b2.ops.append(OpDesc("__test_host_read", {}, {"Out": ["hrows"]}, {}))
    b2.ops.append(OpDesc("__test_to_device", {"X": ["hrows"]},
                         {"Out": ["dev"]}, {}))
    assert "host-boundary" not in _codes(
        verify_program(p2, fetches=["dev"], passes=["shard-check"]))


# ---------------------------------------------------------------------------
# PR-6 inference-only paged ops: verifier + cost-model coverage on the
# decode-step program (regression — these ops must carry real shapes)
# ---------------------------------------------------------------------------

def _build_decode_step():
    from paddle_tpu.models.transformer import transformer_decode_step
    logits, pools, feed_names = transformer_decode_step(
        200, n_layers=2, d_model=32, n_heads=2, d_ff=64, max_context=64,
        slots=4, block_size=8, pool_blocks=8, max_blocks_per_seq=8)
    fetches = [logits.name] + [n for ko, vo in pools
                               for n in (ko.name, vo.name)]
    return pt.default_main_program(), feed_names, fetches


def test_decode_step_program_verifies_clean():
    main, feed_names, fetches = _build_decode_step()
    res = verify_program(main, feeds=feed_names, fetches=fetches)
    assert res.ok, res.report()
    # dtype-prop actually exercised the paged infer entries: the pool
    # outputs carry the pool's shape/dtype, the attention out carries Q's
    blk = main.global_block
    paged = [op for op in blk.ops
             if op.type in ("paged_attention", "paged_kv_write")]
    assert len(paged) == 2 * 2  # one write + one attend per layer
    for op in paged:
        for n in op.output_names():
            v = blk.var(n)
            assert v.shape and all(int(d) > 0 for d in v.shape), (op.type, n)


def test_decode_step_cost_model_sees_real_shapes():
    from paddle_tpu.analysis.cost import op_cost, program_cost
    main, _, _ = _build_decode_step()
    blk = main.global_block
    pc = program_cost(main, batch=1)
    assert not pc.has_backward  # inference-only by construction
    for op in blk.ops:
        if op.type == "paged_attention":
            c = op_cost(op, blk, batch=1)
            assert c.covered and c.mxu_flops > 0 and c.bytes_read > 0
        elif op.type == "paged_kv_write":
            c = op_cost(op, blk, batch=1)
            assert c.covered and c.bytes_written > 0
            # a scatter writes ROWS, never the whole pool (donation
            # aliases the pool buffers)
            pool_bytes = 4 * int(np.prod(
                blk.var(op.inputs["KPool"][0]).shape))
            assert c.bytes_written < pool_bytes
    # the paged ops dominate nothing silently: they appear in per_op
    types = {t for _, t, _ in pc.per_op}
    assert {"paged_attention", "paged_kv_write"} <= types


def test_decode_step_memory_estimate_prices_kv_pools():
    from paddle_tpu.analysis.memory import estimate_memory
    main, _, _ = _build_decode_step()
    est = estimate_memory(main, batch=1)
    # 2 layers x (K+V) pools of [8, 8, 2, 16] f32
    pool = 8 * 8 * 2 * 16 * 4
    assert est.breakdown["kv_pools"] == 2 * 2 * pool
    assert est.breakdown["grads"] == 0 and est.breakdown[
        "optimizer_state"] == 0
    assert est.peak_bytes > est.breakdown["kv_pools"]


def test_pass_registry_is_extensible():
    names = registered_passes()
    assert names == ["def-use", "dtype-prop", "dead-code", "write-hazard",
                     "shard-check", "wire-codec", "conv-fusion",
                     "collective-audit", "pipeline-stage"]
    # pass subsetting: a dtype-defective program is clean under def-use only
    p = pt.Program()
    b = p.global_block
    b.create_var("x", shape=(2,), dtype="float32")
    b.vars["x"].is_data = True
    b.create_var("y", shape=(2,), dtype="int32")
    b.ops.append(OpDesc("relu", {"X": ["x"]}, {"Out": ["y"]}, {}))
    assert verify_program(p, fetches=["y"], passes=["def-use"]).ok
    assert not verify_program(p, fetches=["y"], passes=["dtype-prop"]).ok


# ---------------------------------------------------------------------------
# repo source lint (tools/lint.py rules)
# ---------------------------------------------------------------------------

# the pre-fix ops/rnn_ops.py:39 predicate, verbatim shape (ADVICE r5):
# three conditions space-joined on one physical line by lost backslashes
_JOINED_FIXTURE = (
    'def f(attrs):\n'
    '    if attrs.get("gate_activation", "sigmoid") != "sigmoid"        '
    '     or attrs.get("cell_activation", "tanh") != "tanh"             '
    'or attrs.get("candidate_activation", "tanh") != "tanh":\n'
    '        return False\n'
    '    return True\n'
)


def test_lint_flags_lost_continuation_fixture():
    findings = check_joined_continuation("fixture.py", _JOINED_FIXTURE)
    assert findings and all(f.code == "joined-continuation"
                            for f in findings)


def test_lint_accepts_parenthesized_form_and_fixed_rnn_ops():
    fixed = (
        'def f(attrs):\n'
        '    if (attrs.get("gate_activation", "sigmoid") != "sigmoid"\n'
        '            or attrs.get("cell_activation", "tanh") != "tanh"\n'
        '            or attrs.get("candidate_activation", "tanh") != "tanh"):\n'
        '        return False\n'
        '    return True\n'
    )
    assert check_joined_continuation("fixture.py", fixed) == []
    # the real file, post-fix, is the standing regression fixture
    rnn_ops = os.path.join(REPO, "paddle_tpu", "ops", "rnn_ops.py")
    declared = declared_knobs_from_flags(
        os.path.join(REPO, "paddle_tpu", "flags.py"))
    assert [f for f in lint_file(rnn_ops, declared)
            if f.code == "joined-continuation"] == []


def test_lint_flags_undeclared_env_knob():
    declared = declared_knobs_from_flags(
        os.path.join(REPO, "paddle_tpu", "flags.py"))
    assert "PT_VERIFY" in declared and "FLAGS_check_nan_inf" in declared
    src = ('import os\n'
           'a = os.environ.get("PT_TOTALLY_NEW_KNOB", "0")\n'
           'b = os.environ["FLAGS_not_a_flag"]\n'
           'c = os.getenv("PT_VERIFY")\n'
           'd = os.environ.get("BENCH_STEPS")\n')  # ungoverned prefix
    findings = check_env_knobs("fixture.py", src, declared)
    names = {f.message.split("'")[1] for f in findings}
    assert names == {"PT_TOTALLY_NEW_KNOB", "FLAGS_not_a_flag"}


def test_lint_flags_device_coercion_in_hot_loop_files():
    from paddle_tpu.analysis.source_lint import check_device_coercion
    src = ('import numpy as np\n'
           'def step(exe, feed, loss, scope):\n'
           '    out = exe.run(feed=feed, fetch_list=[loss])\n'
           '    a = np.asarray(out[0])\n'              # flagged
           '    b = float(out[0])\n'                   # flagged
           '    c = out[0].item()\n'                   # flagged
           '    d = np.asarray(out[0])  # host-sync: ok — logging\n'
           '    e = float("1e-3")\n'                   # literal: fine
           '    f = out[0].item(3)\n'                  # args still sync 
           '    return a, b, c, d, e, f\n')
    # governed path: flags the unmarked coercions only
    hot = check_device_coercion("paddle_tpu/trainer.py", src)
    assert [f.line for f in hot] == [4, 5, 6, 9]
    assert all(f.code == "device-coercion" for f in hot)
    # ungoverned file: same source passes untouched
    assert check_device_coercion("paddle_tpu/metrics.py", src) == []
    assert check_device_coercion("chip_smoke.py", src) == []


def test_lint_flags_hardcoded_axis_spec():
    from paddle_tpu.analysis.source_lint import check_axis_spec_literals
    src = ('from jax.sharding import PartitionSpec\n'
           'spec = PartitionSpec("dp", None)\n'           # flagged
           'v_sharding = (None, "tp")\n'                  # flagged
           'axes = {"ep": 4}\n'                           # flagged
           'ok = ("sp",)  # spec: ok — CLI parses user axis names\n'
           '# spec: ok — marker on the line above also suppresses\n'
           'ok2 = ("pp",)\n'
           'other = "dpx"\n'                              # not an axis name
           'slot = "X"\n')
    findings = check_axis_spec_literals("paddle_tpu/layers/foo.py", src)
    assert [f.line for f in findings] == [2, 3, 4]
    assert all(f.code == "hardcoded-axis-spec" for f in findings)
    # placement truth's own homes are exempt
    assert check_axis_spec_literals(
        "paddle_tpu/parallel/mesh.py", src) == []
    assert check_axis_spec_literals(
        "/abs/repo/paddle_tpu/analysis/planner.py", src) == []
    # a module docstring that IS an axis name does not trip the rule
    assert check_axis_spec_literals("x.py", '"""dp"""\n') == []


def test_repo_source_is_lint_clean():
    from paddle_tpu.analysis.source_lint import default_targets, lint_paths
    findings = lint_paths(default_targets(REPO),
                          os.path.join(REPO, "paddle_tpu", "flags.py"))
    assert findings == [], "\n".join(str(f) for f in findings)
