"""Persistence + Trainer/Inferencer + reader decorator tests
(≙ reference book/high-level-api tests + io tests, SURVEY.md §4.4)."""

import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def _linreg_program():
    x = layers.data("x", [4])
    y = layers.data("y", [1])
    pred = layers.fc(x, size=1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    return x, y, pred, loss


def test_save_load_persistables_roundtrip(tmp_path, rng):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        _, _, pred, loss = _linreg_program()
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    xb = rng.randn(8, 4).astype(np.float32)
    yb = xb.sum(1, keepdims=True).astype(np.float32)
    exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])
    params = {v.name: np.asarray(pt.global_scope().find_var(v.name))
              for v in main.global_block.all_parameters()}
    pt.io.save_persistables(exe, str(tmp_path / "model"), main)
    # clobber and restore
    for name in params:
        pt.global_scope().set_var(name, np.zeros_like(params[name]))
    pt.io.load_persistables(exe, str(tmp_path / "model"), main)
    for name, want in params.items():
        np.testing.assert_allclose(
            np.asarray(pt.global_scope().find_var(name)), want)


def test_save_load_inference_model(tmp_path, rng):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        _, _, pred, loss = _linreg_program()
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    xb = rng.randn(8, 4).astype(np.float32)
    yb = xb.sum(1, keepdims=True).astype(np.float32)
    exe.run(main, feed={"x": xb, "y": yb}, fetch_list=[loss])  # one train step

    pt.io.save_inference_model(str(tmp_path / "inf"), ["x"], [pred], exe, main)
    # expected prediction from the saved (post-update) params
    w, b = [np.asarray(pt.global_scope().find_var(v.name))
            for v in main.global_block.all_parameters()]
    want = xb @ (w if w.ndim == 2 else b) + (b if w.ndim == 2 else w)

    prog2, feeds, fetches = pt.io.load_inference_model(str(tmp_path / "inf"), exe)
    assert feeds == ["x"]
    # inference program must not contain optimizer/backward ops
    assert all(op.type not in ("sgd", "autodiff") for op in prog2.global_block.ops)
    got = exe.run(prog2, feed={"x": xb}, fetch_list=fetches)[0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_checkpoint_serial_dirs_and_scroll(tmp_path):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        _, _, _, loss = _linreg_program()
        pt.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    ckpt = str(tmp_path / "ckpt")
    for i in range(5):
        pt.io.save_checkpoint(exe, ckpt, trainer_args={"epoch_id": i, "step_id": 0},
                              main_program=main, max_num_checkpoints=3)
    assert pt.io.get_latest_checkpoint_serial(ckpt) == 4
    dirs = sorted(os.listdir(ckpt))
    assert len(dirs) == 3  # keep-last-3 scroll (io.py:618-735 semantics)
    args = pt.io.load_checkpoint(exe, ckpt, main_program=main)
    assert args["epoch_id"] == 4


def test_reader_decorators():
    r = pt.reader
    base = lambda: iter(range(10))
    assert list(r.firstn(base, 3)()) == [0, 1, 2]
    assert sorted(r.shuffle(base, 5)()) == list(range(10))
    assert list(r.chain(base, base)()) == list(range(10)) * 2
    assert list(r.map_readers(lambda a, b: a + b, base, base)()) == \
        [2 * i for i in range(10)]
    assert list(r.buffered(base, 2)()) == list(range(10))
    batches = list(r.batch(base, 4)())
    assert batches[0] == [0, 1, 2, 3] and batches[-1] == [8, 9]
    assert list(r.batch(base, 4, drop_last=True)())[-1] == [4, 5, 6, 7]
    got = sorted(r.xmap_readers(lambda x: x * 10, base, 2, 4)())
    assert got == [i * 10 for i in range(10)]
    c = r.cache(base)
    assert list(c()) == list(range(10)) and list(c()) == list(range(10))


def test_trainer_end_to_end(tmp_path, rng):
    w_true = rng.randn(4, 1).astype(np.float32)

    def reader():
        rs = np.random.RandomState(7)
        for _ in range(8):
            x = rs.randn(4).astype(np.float32)
            yield (x, (x @ w_true).astype(np.float32))

    def train_func():
        x = layers.data("x", [4])
        y = layers.data("y", [1])
        pred = layers.fc(x, size=1)
        return [layers.mean(layers.square_error_cost(pred, y))]

    losses = []

    def handler(event):
        if isinstance(event, pt.EndStepEvent):
            losses.append(float(np.ravel(event.metrics[0])[0]))

    trainer = pt.Trainer(train_func, lambda: pt.optimizer.SGDOptimizer(0.05))
    trainer.train(num_epochs=6, event_handler=handler,
                  reader=pt.reader.batch(reader, 4))
    assert losses[-1] < losses[0]
    trainer.save_params(str(tmp_path / "params"))

    def infer_func():
        x = layers.data("x", [4])
        return layers.fc(x, size=1)

    # Inferencer reloads by param name: same unique-name sequence because
    # infer_func mirrors train_func's layer order
    pt.core.program.reset_unique_names()
    inferencer = pt.Inferencer(infer_func, str(tmp_path / "params"))
    out = inferencer.infer({"x": np.ones((2, 4), np.float32)})
    assert np.asarray(out[0]).shape == (2, 1)


def test_metrics_accumulators():
    m = pt.metrics.Accuracy()
    m.update(0.5, 10)
    m.update(1.0, 10)
    assert abs(m.eval() - 0.75) < 1e-9
    auc = pt.metrics.Auc(num_thresholds=50)
    preds = np.array([[0.1, 0.9], [0.9, 0.1], [0.2, 0.8], [0.7, 0.3]])
    labels = np.array([1, 0, 1, 0])
    auc.update(preds, labels)
    assert auc.eval() > 0.9


class TestPersistVarsWithoutGrad:
    """≙ reference io.py save/load_persist_vars_without_grad: gradient
    buffers excluded, model+optimizer state round-trips."""

    def test_round_trip_excludes_grads(self, tmp_path):
        rng = np.random.RandomState(0)
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4])
            y = layers.data("y", [1])
            pred = layers.fc(x, size=1)
            loss = layers.mean(layers.square_error_cost(pred, y))
            pt.optimizer.MomentumOptimizer(learning_rate=0.1,
                                           momentum=0.9).minimize(loss)
        exe = pt.Executor()
        scope = pt.Scope()
        feed = {"x": rng.rand(4, 4).astype(np.float32),
                "y": rng.rand(4, 1).astype(np.float32)}
        with pt.scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            # a persistable gradient buffer MUST be excluded by the
            # predicate (grads are non-persistable by default, so force
            # one to actually exercise the exclusion)
            gvar = main.global_block.create_var("fc_x.w_0@GRAD",
                                                shape=(1,), dtype="float32",
                                                persistable=True)
            scope.set_var(gvar.name, np.zeros(1, np.float32))
            pt.io.save_persist_vars_without_grad(exe, str(tmp_path), main,
                                                 scope=scope)
            want = {n: np.asarray(scope.find_var(n))
                    for n in scope.local_var_names()
                    if "@GRAD" not in n}
        import os
        saved = set(os.listdir(str(tmp_path)))
        assert saved and not any("@GRAD" in n for n in saved)

        scope2 = pt.Scope()
        with pt.scope_guard(scope2):
            exe.run(startup)
            pt.io.load_persist_vars_without_grad(exe, str(tmp_path), main,
                                                 scope=scope2)
            compared = 0
            for n, v in want.items():
                if scope2.has_var(n) and scope2.find_var(n) is not None:
                    got = np.asarray(scope2.find_var(n))
                    assert got.shape == v.shape, n
                    np.testing.assert_allclose(got, v, rtol=1e-6)
                    compared += 1
            assert compared >= len([p for p in want if "w_0" in p or "b_0" in p])


_PLAIN_RESNET_OPS = {"conv2d", "batch_norm", "pool2d", "elementwise_add",
                     "relu", "mul", "softmax"}


def _resnet50(is_test):
    from paddle_tpu.models import resnet
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        img = layers.data("img", [3, 224, 224])
        resnet.resnet_imagenet(img, 1000, depth=50, is_test=is_test)
    return main, startup


@pytest.mark.parametrize("is_test", [False, True])
def test_resnet_stride1_bottleneck_is_plain_ops(is_test, monkeypatch):
    """There is one ResNet: every bottleneck, stride 1 included, is the
    plain conv2d / batch_norm ops under their own parameter names, in the
    train and the is_test form, and no environment value selects another
    graph."""
    monkeypatch.delenv("PT_FUSED_BLOCK", raising=False)
    main, startup = _resnet50(is_test)
    types = {op.type for op in main.global_block.ops}
    assert types <= _PLAIN_RESNET_OPS, types - _PLAIN_RESNET_OPS
    params = [v.name for v in startup.global_block.vars.values()
              if v.is_parameter]
    assert len(params) == 161        # 53 conv + 53 x (scale, bias) + fc
    assert all(n.startswith(("conv2d_", "batch_norm_", "fc_"))
               for n in params), params
    want = main.fingerprint()
    for value in ("never", "always"):
        monkeypatch.setenv("PT_FUSED_BLOCK", value)
        assert _resnet50(is_test)[0].fingerprint() == want, value


def test_stride1_bottleneck_trains_and_threads_bn_state(rng):
    """A conv_bn_layer and two stride-1 bottlenecks at 8 x 8, two SGD
    steps on one batch: the loss is finite and falls, and every BN
    running mean and variance has left its initial value."""
    from paddle_tpu.models import resnet
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        img = layers.data("img", [8, 8, 8])
        label = layers.data("label", [1], dtype="int64")
        h = resnet.conv_bn_layer(img, 32, 3, 1, 1)
        h = resnet.bottleneck(h, 8, 1)
        h = resnet.bottleneck(h, 8, 1)
        pool = layers.pool2d(h, pool_type="avg", global_pooling=True)
        logits = layers.fc(pool, size=10, act=None)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.SGDOptimizer(0.05).minimize(loss)
    bn_ops = [op for op in main.global_block.ops if op.type == "batch_norm"]
    assert len(bn_ops) == 7          # no shortcut conv at stride 1
    stats = [op.inputs[slot][0] for op in bn_ops
             for slot in ("Mean", "Variance")]
    data = rng.rand(16, 8, 8, 8).astype(np.float32)
    feed = {"img": data,
            "label": (data[:, 0, 0, 0] * 9.999).astype("int64")
            .reshape(-1, 1)}
    exe = pt.Executor()
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup)
        before = {n: scope.get_numpy(n).copy() for n in stats}
        losses = [float(np.asarray(
            exe.run(main, feed=feed, fetch_list=[loss])[0]).ravel()[0])
            for _ in range(3)]
        after = {n: scope.get_numpy(n) for n in stats}
    assert np.all(np.isfinite(losses)) and losses[2] < losses[0], losses
    for n in stats:
        assert np.all(np.isfinite(after[n])), n
        assert not np.allclose(after[n], before[n]), n
