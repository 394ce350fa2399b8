"""Flags layer, FLAGS_check_nan_inf guard, graphviz debugger.

≙ reference: __bootstrap__ env->gflags forwarding, operator.cc:590
per-op nan/inf validation, debugger.py graphviz dump.
"""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.flags import FLAGS, reset_flags_from_env


class TestFlags:
    def test_env_initialization(self, monkeypatch):
        monkeypatch.setenv("FLAGS_check_nan_inf", "1")
        monkeypatch.setenv("FLAGS_fraction_of_gpu_memory_to_use", "0.5")
        reset_flags_from_env()
        try:
            assert FLAGS.check_nan_inf is True
            assert FLAGS.fraction_of_gpu_memory_to_use == 0.5
        finally:
            monkeypatch.delenv("FLAGS_check_nan_inf")
            monkeypatch.delenv("FLAGS_fraction_of_gpu_memory_to_use")
            reset_flags_from_env()

    def test_bool_parsing_variants(self, monkeypatch):
        for raw, want in (("true", True), ("0", False), ("ON", True),
                          ("no", False)):
            monkeypatch.setenv("FLAGS_check_nan_inf", raw)
            reset_flags_from_env()
            assert FLAGS.check_nan_inf is want, raw
        monkeypatch.delenv("FLAGS_check_nan_inf")
        reset_flags_from_env()

    def test_unknown_flag_raises(self):
        with pytest.raises(AttributeError):
            FLAGS.does_not_exist
        with pytest.raises(AttributeError):
            FLAGS.new_flag = 1

    def test_help_marks_noops(self):
        h = FLAGS.help()
        assert "no-op" in h["use_mkldnn"]
        assert "no-op" not in h["check_nan_inf"]


class TestCheckNanInf:
    def _nan_program(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [2])
            out = layers.log(x)          # nan for negative input
            loss = layers.mean(out)
        return main, startup, loss

    def test_off_returns_nan_silently(self):
        main, startup, loss = self._nan_program()
        exe = pt.Executor()
        exe.run(startup)
        (l,) = exe.run(main, feed={"x": np.array([[-1.0, 2.0]], "float32")},
                       fetch_list=[loss])
        assert np.isnan(l).any()

    def test_on_raises_naming_primitive(self):
        FLAGS.check_nan_inf = True
        try:
            main, startup, loss = self._nan_program()
            exe = pt.Executor()
            exe.run(startup)
            with pytest.raises(Exception, match="nan"):
                exe.run(main,
                        feed={"x": np.array([[-1.0, 2.0]], "float32")},
                        fetch_list=[loss])
            # clean inputs pass
            (l,) = exe.run(main,
                           feed={"x": np.array([[1.0, 2.0]], "float32")},
                           fetch_list=[loss])
            assert np.isfinite(l).all()
        finally:
            FLAGS.check_nan_inf = False


class TestCheckNanInfStateSafety:
    def test_scope_params_survive_a_nan_raise(self):
        """Donation is disabled under the guard: after a nan raise the
        scope's parameters must still be readable and training resumable."""
        FLAGS.check_nan_inf = True
        try:
            main, startup = pt.Program(), pt.Program()
            with pt.program_guard(main, startup):
                x = layers.data("x", [4])
                h = layers.fc(input=x, size=8, act="relu")
                out = layers.log(h)  # nan when h has zeros (relu output)
                loss = layers.mean(out)
                pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(loss)
            scope = pt.Scope()
            with pt.scope_guard(scope):
                exe = pt.Executor()
                exe.run(startup)
                bad = {"x": np.full((2, 4), -1.0, "float32")}  # relu -> 0
                with pytest.raises(Exception, match="nan|inf|div"):
                    exe.run(main, feed=bad, fetch_list=[loss])
                # params are intact, not deleted donated buffers
                w = np.asarray(scope.find_var(
                    main.all_parameters()[0].name))
                assert np.isfinite(w).all()
        finally:
            FLAGS.check_nan_inf = False


class TestMalformedEnvFlags:
    def test_noop_flag_falls_back_with_warning(self, monkeypatch):
        monkeypatch.setenv("FLAGS_fraction_of_gpu_memory_to_use", "80%")
        with pytest.warns(UserWarning, match="FLAGS_fraction"):
            reset_flags_from_env()
        assert FLAGS.fraction_of_gpu_memory_to_use == 0.92
        monkeypatch.delenv("FLAGS_fraction_of_gpu_memory_to_use")
        reset_flags_from_env()

    def test_real_flag_raises_with_name(self, monkeypatch):
        monkeypatch.setenv("FLAGS_check_nan_inf", "maybe")
        # bool parsing never fails (any string maps to False), so use a
        # float-typed real flag scenario via a fresh definition
        from paddle_tpu import flags as flags_mod
        flags_mod.DEFINE_flag("_test_float_flag", float, 1.0, "test")
        monkeypatch.setenv("FLAGS__test_float_flag", "abc")
        with pytest.raises(ValueError, match="FLAGS__test_float_flag"):
            reset_flags_from_env()
        monkeypatch.delenv("FLAGS__test_float_flag")
        monkeypatch.delenv("FLAGS_check_nan_inf")
        FLAGS._defs.pop("_test_float_flag")
        FLAGS._values.pop("_test_float_flag")
        reset_flags_from_env()


class TestDebugger:
    def test_graphviz_dot(self, tmp_path):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4])
            h = layers.fc(input=x, size=8, act="relu")
            layers.mean(h)
        path = str(tmp_path / "prog.dot")
        dot = pt.debugger.draw_block_graphviz(main.global_block, path=path)
        assert dot.startswith("digraph G {") and dot.endswith("}")
        assert '"op_0_mul"' in dot
        assert 'fillcolor="lightblue"' in dot  # parameter node styled
        assert "->" in dot
        assert open(path).read() == dot

    def test_pprint(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4])
            layers.mean(x)
        s = pt.debugger.pprint_program_codes(main)
        assert "mean" in s
