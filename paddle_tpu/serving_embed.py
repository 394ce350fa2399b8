"""Embedded-interpreter backend for the C serving API.

`native/predictor_capi.cpp` (≙ the reference's C/C++ inference surface:
paddle/contrib/inference/paddle_inference_api.h:46 PaddlePredictor::Run
and paddle/capi/) embeds CPython and drives THIS module with only
ints/bytes/tuples — no numpy C API on the native side. The heavy lifting
(deserializing the jax.export StableHLO artifact, running it) stays in
Python; the compiled program itself is XLA, so the embedded interpreter
only marshals buffers.

Since the serving subsystem landed, the C path and the HTTP path reach
the SAME engine (paddle_tpu/serving/): when the artifact's metadata
carries batch-major fetch specs, `create` loads the model into a
ServingEngine and `run` splits the client's rows into per-example
requests — the micro-batcher coalesces them (with any concurrent
callers) back into full batches, so a C client gets admission control,
metrics, and hot-reload semantics for free, and may send ANY row count
(the engine pads/splits); the artifact's exported batch size is no
longer a protocol constraint. Legacy artifacts without fetch metadata
fall back to the direct single-dispatch path.

Output protocol: [(raw_bytes, shape_tuple, dtype_str), ...] in fetch
order — each fetch's dtype is PRESERVED (an argmax fetch crosses the C
boundary as int32 bytes, not mangled through float32 as before).
"""

from __future__ import annotations

import os
from typing import Dict

_PREDICTORS: Dict[int, dict] = {}
_NEXT = [0]

#: engine model key for the C API's one-model-per-handle view
_MODEL = "default"


def create(model_dir: str) -> int:
    """Load an export_serving_model artifact; returns a handle."""
    import json
    with open(os.path.join(model_dir, "serving.json")) as f:
        meta = json.load(f)
    entry = {"meta": meta, "dir": model_dir}
    fetches = meta.get("fetches")
    batch = int(meta.get("batch_size", 1))

    def _bm(m):
        # export-recorded flag wins; leading-dim test only for artifacts
        # that predate the flag
        if "batch_major" in m:
            return bool(m["batch_major"])
        return bool(m.get("shape")) and int(m["shape"][0]) == batch

    # the engine path slices feeds per row and re-stacks fetch rows, so
    # EVERY feed and fetch must carry the batch axis; anything else
    # (static side-input feeds, reduced/parameter fetches) keeps the
    # direct single-dispatch path, which serves any artifact correctly
    batch_major = (bool(fetches) and all(_bm(m) for m in fetches)
                   and all(_bm(m) for m in meta["feeds"]))
    if batch_major:
        from . import serving as _serving
        engine = _serving.ServingEngine()
        engine.load_model(_MODEL, model_dir)
        entry["engine"] = engine
    else:
        # legacy artifact (no fetch specs) or a fetch without the batch
        # axis (nothing to scatter): direct single-dispatch path
        from . import io as pio
        predict, _feed_names, _fetch_names = pio.load_serving_model(
            model_dir)
        entry["predict"] = predict
    _NEXT[0] += 1
    _PREDICTORS[_NEXT[0]] = entry
    return _NEXT[0]


def feed_spec(handle: int, model_dir: str):
    """[(name, shape, dtype), ...] for the artifact's feeds."""
    import json
    with open(os.path.join(model_dir, "serving.json")) as f:
        meta = json.load(f)
    return [(m["name"], tuple(m["shape"]), m["dtype"])
            for m in meta["feeds"]]


def fetch_spec(handle: int, model_dir: str):
    """[(name, shape, dtype), ...] for the artifact's fetches (empty on
    pre-metadata artifacts)."""
    import json
    with open(os.path.join(model_dir, "serving.json")) as f:
        meta = json.load(f)
    return [(m["name"], tuple(m["shape"]), m["dtype"])
            for m in meta.get("fetches") or ()]


def run(handle: int, feeds):
    """feeds: [(raw_bytes, shape_tuple, dtype_str), ...] in feed order.
    Returns [(raw_bytes, shape_tuple, dtype_str), ...] in fetch order,
    each fetch in its OWN dtype."""
    import numpy as np
    entry = _PREDICTORS[handle]
    arrays = [np.frombuffer(raw, dtype=np.dtype(dt)).reshape(shape)
              for raw, shape, dt in feeds]
    meta = entry["meta"]
    engine = entry.get("engine")
    if engine is not None:
        import time
        from .serving import Overloaded
        feed_names = [m["name"] for m in meta["feeds"]]
        n = int(arrays[0].shape[0])
        # backpressure instead of reject-fast: this caller is synchronous
        # and already owns queued work, so Overloaded mid-burst means
        # "wait for your own outstanding rows", not "fail the call" — any
        # row count must serve regardless of PT_SERVE_QUEUE_DEPTH
        futures, waited = [], 0
        for r in range(n):
            feeds_r = {nm: a[r] for nm, a in zip(feed_names, arrays)}
            while True:
                try:
                    futures.append(engine.submit(_MODEL, feeds_r))
                    break
                except Overloaded:
                    if waited < len(futures):
                        futures[waited].result()
                        waited += 1
                    else:       # queue filled by OTHER clients: yield
                        time.sleep(0.001)
        rows = [f.result() for f in futures]
        outs = [np.stack([row[name] for row in rows])
                for name in meta["fetch_names"]]
    else:
        outs = entry["predict"](*arrays)
        if isinstance(outs, dict):
            outs = list(outs.values())
        elif not isinstance(outs, (list, tuple)):
            outs = [outs]
        outs = [np.asarray(o) for o in outs]
    result = []
    for o in outs:
        a = np.ascontiguousarray(o)
        result.append((a.tobytes(), tuple(int(s) for s in a.shape),
                       a.dtype.name))
    return result


def destroy(handle: int) -> None:
    entry = _PREDICTORS.pop(handle, None)
    if entry and "engine" in entry:
        entry["engine"].shutdown()
