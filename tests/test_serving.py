"""Online serving subsystem (paddle_tpu/serving/): micro-batching,
shape buckets, multi-model hot reload, admission control, metrics, the
HTTP front end, and the chaos contract of the dispatcher loop.

Two test planes:
  * artifact-level — real AOT exports (io.export_serving_model) served
    by a real ServingEngine: coalescing must be BIT-identical to
    sequential service, padding must never leak across requests, hot
    reload must drop zero in-flight requests;
  * unit-level — a jax-free stub model under MicroBatcher, so queueing
    policy (shedding, deadlines, dispatcher crash recovery) is tested
    deterministically with a blockable executor.
"""

import json
import os
import threading
import time
import urllib.request
import urllib.error

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import io as pio
from paddle_tpu import serving
from paddle_tpu import serving_embed
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.retry import RetryPolicy, retry_call
from paddle_tpu.serving import (DeadlineExceeded, InvalidRequest,
                                ModelUnavailable, Overloaded,
                                RequestFailed, ServingEngine)
from paddle_tpu.serving.admission import AdmissionController
from paddle_tpu.serving.batcher import MicroBatcher
from paddle_tpu.serving.metrics import ModelMetrics, ServingPhaseTimer


# ---------------------------------------------------------------------------
# artifacts (module-scoped: exports compile)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def static_dir(tmp_path_factory):
    """Fixed-shape model with a float fetch AND an int fetch: x[6] ->
    fc8 relu -> fc3 softmax, argmax. batch_size=4."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [6])
        hid = layers.fc(input=x, size=8, act="relu")
        probs = layers.fc(input=hid, size=3, act="softmax")
        label = layers.argmax(probs, axis=1)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path_factory.mktemp("serve") / "static")
        pio.export_serving_model(d, ["x"], [probs, label],
                                 main_program=main, scope=scope,
                                 batch_size=4)
    return d


@pytest.fixture(scope="module")
def bucketed_dir(tmp_path_factory):
    """Variable-length model: x[-1, 4] -> reduce_sum over time -> fc3
    softmax; batch_size=4, length buckets (4, 8). reduce_sum makes the
    output invariant to zero padding, so padded vs unpadded outputs are
    comparable bit-for-bit."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [-1, 4])
        h = layers.reduce_sum(x, dim=1)
        o = layers.fc(input=h, size=3, act="softmax")
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path_factory.mktemp("serve") / "bucketed")
        pio.export_serving_model(d, ["x"], [o], main_program=main,
                                 scope=scope, batch_size=4,
                                 length_buckets=(4, 8))
    return d


def _first(result_dict):
    return next(iter(result_dict.values()))


# ---------------------------------------------------------------------------
# export metadata (satellite: fetch specs in serving.json)
# ---------------------------------------------------------------------------

def test_export_records_fetch_meta(static_dir):
    with open(os.path.join(static_dir, "serving.json")) as f:
        meta = json.load(f)
    assert [m["dtype"] for m in meta["fetches"]] == ["float32", "int32"]
    assert [m["shape"] for m in meta["fetches"]] == [[4, 3], [4]]
    assert [m["name"] for m in meta["fetches"]] == meta["fetch_names"]


def test_bucketed_export_artifacts(bucketed_dir):
    with open(os.path.join(bucketed_dir, "serving.json")) as f:
        meta = json.load(f)
    assert [b["length"] for b in meta["buckets"]] == [4, 8]
    for b in meta["buckets"]:
        assert os.path.exists(os.path.join(bucketed_dir, b["file"]))
        assert b["feeds"][0]["shape"] == [4, b["length"], 4]
        assert b["fetches"][0]["shape"] == [4, 3]
    assert meta["var_dims"] == {"x": [1]}
    # the compat artifact still loads through the legacy loader
    predict, feeds, fetches = pio.load_serving_model(bucketed_dir)
    out = predict(np.zeros((4, 8, 4), np.float32))
    assert np.asarray(out[0] if isinstance(out, (tuple, list))
                      else out).shape == (4, 3)


def test_non_batch_major_fetch_replicated(tmp_path):
    """A fetch whose leading dim merely COINCIDES with the batch size
    (batch=3, column-sum of the (3, 3) probs -> shape (3,)) must be
    replicated to every request, not scattered row-by-row. The export
    records ground-truth batch_major flags by abstractly re-evaluating
    at batch+1 and keeping only fetches whose leading dim tracks it."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [3])
        probs = layers.fc(input=x, size=3, act="softmax")
        colsum = layers.reduce_sum(probs, dim=0)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path / "coincide")
        pio.export_serving_model(d, ["x"], [probs, colsum],
                                 main_program=main, scope=scope,
                                 batch_size=3)
    with open(os.path.join(d, "serving.json")) as f:
        meta = json.load(f)
    assert [m["batch_major"] for m in meta["fetches"]] == [True, False]
    assert all(m["batch_major"] for m in meta["feeds"])

    predict, _, _ = pio.load_serving_model(d)
    row = np.arange(3, dtype=np.float32)
    pad = np.zeros((3, 3), np.float32)
    pad[0] = row
    ref = predict(pad)
    ref = list(ref.values()) if isinstance(ref, dict) else list(ref)

    engine = ServingEngine(max_batch_size=1, max_wait_ms=0.0)
    engine.load_model("m", d)
    try:
        out = engine.predict("m", {"x": row}, timeout=30)
    finally:
        engine.shutdown()
    vals = list(out.values())
    np.testing.assert_array_equal(vals[0], np.asarray(ref[0])[0])
    # the batch-level reduction arrives WHOLE, not split per request row
    assert vals[1].shape == (3,)
    np.testing.assert_array_equal(vals[1], np.asarray(ref[1]))


def test_static_feed_artifact_refused_at_load(tmp_path):
    """An artifact with an append_batch_size=False side-input feed has
    no batch axis to coalesce on — the engine must refuse it at LOAD
    time instead of silently row-slicing a non-batch feed. The direct
    load_serving_model path still serves such artifacts."""
    pt.core.program.reset_unique_names()
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4])
        w = layers.data("w", [4, 2], append_batch_size=False)
        o = layers.matmul(x, w)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor().run(startup)
        d = str(tmp_path / "static_feed")
        pio.export_serving_model(d, ["x", "w"], [o], main_program=main,
                                 scope=scope, batch_size=2)
    with open(os.path.join(d, "serving.json")) as f:
        meta = json.load(f)
    assert [m["batch_major"] for m in meta["feeds"]] == [True, False]

    engine = ServingEngine()
    try:
        with pytest.raises(ValueError, match="batch-major"):
            engine.load_model("m", d)
    finally:
        engine.shutdown()
    # the direct path serves it fine
    predict, _, _ = pio.load_serving_model(d)
    xv = np.ones((2, 4), np.float32)
    wv = np.ones((4, 2), np.float32)
    out = predict(xv, wv)
    out = out[0] if isinstance(out, (tuple, list)) else out
    np.testing.assert_allclose(np.asarray(out), xv @ wv)
    # and the C-embed route falls back to direct dispatch for it
    h = serving_embed.create(d)
    try:
        res = serving_embed.run(h, [(xv.tobytes(), (2, 4), "float32"),
                                    (wv.tobytes(), (4, 2), "float32")])
        raw, shape, dt = res[0]
        np.testing.assert_allclose(
            np.frombuffer(raw, dtype=dt).reshape(shape), xv @ wv)
    finally:
        serving_embed.destroy(h)


# ---------------------------------------------------------------------------
# coalescing + buckets (the tentpole correctness contract)
# ---------------------------------------------------------------------------

def test_batch_coalescing_bit_identical(bucketed_dir):
    rng = np.random.RandomState(0)
    examples = [rng.rand(n, 4).astype("float32")
                for n in (3, 4, 6, 8, 2, 5, 1, 7)]
    batched = ServingEngine(max_wait_ms=20.0)
    batched.load_model("m", bucketed_dir)
    seq = ServingEngine(max_batch_size=1, max_wait_ms=0.0)
    seq.load_model("m", bucketed_dir)
    try:
        futs = [batched.submit("m", {"x": e}) for e in examples]
        got = [_first(f.result(timeout=60)) for f in futs]
        want = [_first(seq.predict("m", {"x": e}, timeout=60))
                for e in examples]
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.tobytes() == w.tobytes()
    finally:
        batched.shutdown()
        seq.shutdown()


def test_bucket_padding_never_leaks(bucketed_dir):
    """A request's output must not depend on what else rode in its
    batch: serve A alone, then A coalesced with random co-tenants in the
    same and in different buckets — identical bytes every time."""
    rng = np.random.RandomState(7)
    a = rng.rand(3, 4).astype("float32")
    engine = ServingEngine(max_wait_ms=20.0)
    engine.load_model("m", bucketed_dir)
    try:
        alone = _first(engine.predict("m", {"x": a}, timeout=60))
        for trial in range(3):
            others = [rng.rand(n, 4).astype("float32")
                      for n in (4, 2, 8, 6)]
            futs = [engine.submit("m", {"x": e}) for e in [a] + others]
            with_tenants = _first(futs[0].result(timeout=60))
            [f.result(timeout=60) for f in futs[1:]]
            assert with_tenants.tobytes() == alone.tobytes()
    finally:
        engine.shutdown()


def test_request_validation_typed(bucketed_dir):
    engine = ServingEngine()
    engine.load_model("m", bucketed_dir)
    try:
        with pytest.raises(InvalidRequest):   # beyond the largest bucket
            engine.submit("m", {"x": np.zeros((9, 4), "float32")})
        with pytest.raises(InvalidRequest):   # wrong feed name
            engine.submit("m", {"y": np.zeros((4, 4), "float32")})
        with pytest.raises(InvalidRequest):   # wrong rank
            engine.submit("m", {"x": np.zeros((4,), "float32")})
        with pytest.raises(InvalidRequest):   # wrong dtype kind
            engine.submit("m", {"x": np.zeros((4, 4), "complex64")})
        # int -> float32 is a same-kind WIDENING: admitted by design
        # (JSON/py-int clients feed float models with ints constantly)
        engine.predict("m", {"x": np.zeros((4, 4), "int32")},
                       timeout=60)
        with pytest.raises(InvalidRequest):   # wrong static dim
            engine.submit("m", {"x": np.zeros((4, 5), "float32")})
        with pytest.raises(ModelUnavailable):
            engine.submit("nope", {"x": np.zeros((4, 4), "float32")})
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# hot reload (atomic, drain-based, zero drops)
# ---------------------------------------------------------------------------

def test_hot_reload_drops_nothing(bucketed_dir):
    engine = ServingEngine(max_wait_ms=2.0)
    assert engine.load_model("m", bucketed_dir) == 1
    stop = threading.Event()
    errors, completed = [], [0]

    def client(seed):
        rng = np.random.RandomState(seed)
        while not stop.is_set():
            try:
                r = engine.predict(
                    "m", {"x": rng.rand(rng.randint(1, 9),
                                        4).astype("float32")},
                    timeout=60)
                assert _first(r).shape == (3,)
                completed[0] += 1
            except Exception as e:  # noqa: BLE001 — the assertion target
                errors.append(repr(e))
                return
    threads = [threading.Thread(target=client, args=(s,))
               for s in range(4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.2)
        for _ in range(3):                      # three reloads under fire
            assert engine.load_model("m", bucketed_dir) > 1
            time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join()
        engine.shutdown()
    assert errors == []
    assert completed[0] > 0
    snap = engine.metrics_snapshot()["models"]["m"]
    assert snap["received"] == snap["completed"]    # zero dropped
    assert snap["failed"] == 0
    assert snap["reloads"] == 3
    assert engine.models() == {} or True            # engine closed


def test_submit_survives_reload_race(bucketed_dir):
    """The TOCTOU window between registry.get() and batcher.submit():
    when the version a submit routed to closes under it (hot reload),
    engine.submit must retry against the newly routed version instead of
    failing the request with ModelUnavailable while the model is loaded."""
    engine = ServingEngine(max_wait_ms=2.0)
    engine.load_model("m", bucketed_dir)
    stale = engine.registry.get("m")
    engine.load_model("m", bucketed_dir)        # drains + closes stale
    real_get = engine.registry.get
    raced = []

    def stale_then_real(name):
        if not raced:
            raced.append(1)
            return stale                        # the raced routing read
        return real_get(name)

    engine.registry.get = stale_then_real
    try:
        out = engine.predict("m", {"x": np.ones((4, 4), np.float32)},
                             timeout=30)
        assert _first(out).shape == (3,)
        assert raced                            # the stale route was taken
    finally:
        engine.registry.get = real_get
        engine.shutdown()


# ---------------------------------------------------------------------------
# unit plane: a jax-free model stub under the real MicroBatcher
# ---------------------------------------------------------------------------

class StubModel:
    """batch_size-4 'model' whose executor doubles x and can be blocked
    on an Event to hold the dispatcher mid-batch deterministically."""

    batch_size = 4

    def __init__(self, gate: threading.Event = None):
        self.gate = gate
        self.batches = []

    def bucket_of(self, feeds):
        if "x" not in feeds:
            raise InvalidRequest("stub wants feed 'x'")
        return None

    def execute_batch(self, bucket, examples, timer=None):
        if self.gate is not None:
            self.gate.wait(10.0)
        self.batches.append(len(examples))
        out = [{"y": np.asarray(e["x"], dtype=np.float64) * 2.0}
               for e in examples]
        return out, {"pad": 0.0, "device": 0.0, "scatter": 0.0}


def _stub_batcher(gate=None, queue_depth=64, max_wait_ms=1.0,
                  default_deadline_ms=0.0):
    model = StubModel(gate)
    admission = AdmissionController(queue_depth=queue_depth,
                                    max_batch_size=model.batch_size,
                                    default_deadline_ms=default_deadline_ms)
    metrics = ModelMetrics("stub")
    batcher = MicroBatcher(model, max_wait_ms=max_wait_ms,
                           admission=admission, metrics=metrics,
                           name="stub")
    return model, batcher


def test_overload_sheds_fast_and_typed():
    gate = threading.Event()
    model, batcher = _stub_batcher(gate=gate, queue_depth=2,
                                   max_wait_ms=0.0)
    try:
        first = batcher.submit({"x": np.float32(1)})
        deadline = time.monotonic() + 5.0
        while batcher.queued() > 0 and time.monotonic() < deadline:
            time.sleep(0.001)       # dispatcher picked up the first batch
        q1 = batcher.submit({"x": np.float32(2)})
        q2 = batcher.submit({"x": np.float32(3)})
        t0 = time.monotonic()
        with pytest.raises(Overloaded):
            batcher.submit({"x": np.float32(4)})
        assert time.monotonic() - t0 < 0.5      # rejected FAST, not queued
        gate.set()
        for f, x in ((first, 1.0), (q1, 2.0), (q2, 3.0)):
            assert float(f.result(timeout=10)["y"]) == 2.0 * x
        snap = batcher.metrics.snapshot()
        assert snap["shed_overload"] == 1
        assert snap["completed"] == 3
    finally:
        gate.set()
        batcher.close()


def test_overloaded_is_retryable_by_policy():
    """RetryPolicy(retry_on=serving.retryable) retries Overloaded but
    never DeadlineExceeded — the PR-2 convention wiring."""
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise Overloaded("busy")
        return "ok"

    policy = RetryPolicy(retries=5, base_delay=0.0, jitter=0.0,
                         retry_on=serving.retryable,
                         sleep=lambda _s: None)
    assert retry_call(flaky, policy=policy) == "ok"
    assert calls["n"] == 3
    with pytest.raises(DeadlineExceeded):
        retry_call(lambda: (_ for _ in ()).throw(DeadlineExceeded("x")),
                   policy=policy)


def test_deadline_expired_in_queue_is_typed():
    gate = threading.Event()
    model, batcher = _stub_batcher(gate=gate, max_wait_ms=0.0)
    try:
        blocker = batcher.submit({"x": np.float32(0)})
        deadline = time.monotonic() + 5.0
        while batcher.queued() > 0 and time.monotonic() < deadline:
            time.sleep(0.001)
        doomed = batcher.submit({"x": np.float32(1)}, deadline_ms=20.0)
        time.sleep(0.05)                         # let the deadline lapse
        gate.set()
        assert float(blocker.result(timeout=10)["y"]) == 0.0
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=10)
        assert batcher.metrics.snapshot()["shed_deadline"] == 1
    finally:
        gate.set()
        batcher.close()


def test_deadline_aware_admission_sheds_before_queueing():
    gate = threading.Event()
    model, batcher = _stub_batcher(gate=gate, max_wait_ms=0.0)
    try:
        batcher.admission.observe_batch(0.5)     # est: 500 ms per batch
        batcher.submit({"x": np.float32(0)})     # something queued ahead
        with pytest.raises(DeadlineExceeded):
            batcher.submit({"x": np.float32(1)}, deadline_ms=5.0)
    finally:
        gate.set()
        batcher.close()


def test_expired_at_admission_is_immediate():
    admission = AdmissionController(queue_depth=4, max_batch_size=4,
                                    clock=lambda: 100.0)
    with pytest.raises(DeadlineExceeded):
        admission.admit(0, deadline_t=99.0)
    admission.admit(0, deadline_t=101.0)        # future deadline admits
    with pytest.raises(Overloaded):
        admission.admit(4, deadline_t=None)


def test_dispatcher_chaos_recovers(monkeypatch):
    """PT_FAULT_INJECT=serve_dispatch@1: the first flushed batch dies
    inside the dispatcher loop — its request gets a TYPED error carrying
    the injected fault as __cause__, and the engine keeps serving."""
    monkeypatch.setenv("PT_FAULT_INJECT", "serve_dispatch@1")
    faults.reset()
    model, batcher = _stub_batcher(max_wait_ms=0.0)
    try:
        doomed = batcher.submit({"x": np.float32(1)})
        with pytest.raises(RequestFailed) as ei:
            doomed.result(timeout=10)
        assert isinstance(ei.value.__cause__, faults.FaultInjected)
        assert ei.value.__cause__.site == "serve_dispatch"
        # the loop survived: the next request is served normally
        ok = batcher.submit({"x": np.float32(2)})
        assert float(ok.result(timeout=10)["y"]) == 4.0
        snap = batcher.metrics.snapshot()
        assert snap["failed"] == 1 and snap["completed"] == 1
    finally:
        batcher.close()
        faults.reset()


def test_close_without_drain_fails_backlog_typed():
    gate = threading.Event()
    model, batcher = _stub_batcher(gate=gate, max_wait_ms=0.0)
    blocker = batcher.submit({"x": np.float32(0)})
    deadline = time.monotonic() + 5.0
    while batcher.queued() > 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    queued = batcher.submit({"x": np.float32(1)})
    gate.set()
    batcher.close(drain=False)
    blocker.result(timeout=10)
    with pytest.raises(ModelUnavailable):
        queued.result(timeout=10)
    with pytest.raises(ModelUnavailable):
        batcher.submit({"x": np.float32(2)})


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_snapshot_consistent():
    model, batcher = _stub_batcher(max_wait_ms=1.0)
    try:
        futs = [batcher.submit({"x": np.float32(i)}) for i in range(10)]
        for f in futs:
            f.result(timeout=10)
        snap = batcher.metrics.snapshot()
        assert snap["received"] == 10
        assert snap["completed"] + snap["failed"] == 10
        assert snap["failed"] == 0
        assert snap["batches"] == len(model.batches)
        assert sum(model.batches) == 10
        fill = snap["batch_fill_ratio"]
        assert fill is not None and 0.0 < fill <= 1.0
        assert fill == pytest.approx(10 / (len(model.batches) * 4),
                                     abs=1e-4)
        assert snap["qps"] > 0
        for phase in ("queue", "pad", "device", "scatter", "total"):
            assert set(snap["latency"][phase]) == {"p50_ms", "p95_ms",
                                                   "p99_ms"}
        assert snap["latency"]["total"]["p50_ms"] is not None
        assert snap["phases"]["batches"] == snap["batches"]
    finally:
        batcher.close()


def test_serving_phase_timer_axes():
    t = ServingPhaseTimer()
    with t.span("pad"):
        pass
    t.count_run()
    snap = t.snapshot(reset=True)
    assert set(snap) == {"queue_s", "pad_s", "device_s", "fetch_s",
                         "scatter_s", "batches"}
    assert snap["batches"] == 1
    assert t.snapshot()["batches"] == 0


# ---------------------------------------------------------------------------
# HTTP front end
# ---------------------------------------------------------------------------

def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


def test_http_front_end(static_dir):
    from paddle_tpu.serving.http import start_http_server
    engine = ServingEngine(max_wait_ms=5.0)
    engine.load_model("clf", static_dir)
    server, _thread = start_http_server(engine)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        x = (np.arange(6) % 5 * 0.25).astype("float32")
        status, body = _post(f"{base}/v1/models/clf:predict",
                             {"feeds": {"x": x.tolist()}})
        assert status == 200
        fetched = body["fetches"]
        probs_name, label_name = list(fetched)
        assert fetched[probs_name]["dtype"] == "float32"
        assert fetched[label_name]["dtype"] == "int32"
        want = _first(engine.predict("clf", {"x": x}, timeout=60))
        assert np.asarray(fetched[probs_name]["data"],
                          np.float32) == pytest.approx(want)

        with urllib.request.urlopen(f"{base}/v1/models",
                                    timeout=60) as r:
            models = json.loads(r.read())["models"]
        assert models["clf"]["batch_size"] == 4
        with urllib.request.urlopen(f"{base}/v1/metrics",
                                    timeout=60) as r:
            snap = json.loads(r.read())
        assert snap["models"]["clf"]["completed"] >= 2

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/models/ghost:predict",
                  {"feeds": {"x": x.tolist()}})
        assert ei.value.code == 404
        assert json.loads(ei.value.read())["error"] == "ModelUnavailable"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(f"{base}/v1/models/clf:predict", {"nope": 1})
        assert ei.value.code == 400

        status, body = _post(f"{base}/v1/models/clf:reload",
                             {"model_dir": static_dir})
        assert status == 200 and body["version"] == 2
    finally:
        server.shutdown()
        engine.shutdown()


# ---------------------------------------------------------------------------
# the embedded C-API backend (dtype preservation + shared engine)
# ---------------------------------------------------------------------------

def test_serving_embed_preserves_fetch_dtypes(static_dir):
    handle = serving_embed.create(static_dir)
    try:
        feed = ((np.arange(24) % 17) * 0.125).astype(
            "float32").reshape(4, 6)
        outs = serving_embed.run(
            handle, [(feed.tobytes(), (4, 6), "float32")])
        assert [(o[1], o[2]) for o in outs] == [((4, 3), "float32"),
                                                ((4,), "int32")]
        probs = np.frombuffer(outs[0][0], np.float32).reshape(4, 3)
        label = np.frombuffer(outs[1][0], np.int32)
        assert np.array_equal(label, probs.argmax(axis=1))
        # the C path rides the SAME engine: metrics saw these requests
        entry = serving_embed._PREDICTORS[handle]
        snap = entry["engine"].metrics_snapshot()["models"]["default"]
        assert snap["completed"] == 4
        # a row count != the artifact batch is now legal (engine pads)
        outs2 = serving_embed.run(
            handle, [(feed[:2].tobytes(), (2, 6), "float32")])
        assert outs2[0][1] == (2, 3)
        assert np.frombuffer(outs2[0][0], np.float32).reshape(2, 3) \
            == pytest.approx(probs[:2])
    finally:
        serving_embed.destroy(handle)


def test_serving_embed_fetch_spec(static_dir):
    handle = serving_embed.create(static_dir)
    try:
        spec = serving_embed.fetch_spec(handle, static_dir)
        assert [(s[1], s[2]) for s in spec] == [((4, 3), "float32"),
                                                ((4,), "int32")]
    finally:
        serving_embed.destroy(handle)
