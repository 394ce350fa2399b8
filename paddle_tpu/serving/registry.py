"""Multi-model registry: named, versioned models + atomic hot reload.

A `ModelVersion` is one loaded serving artifact dir (io.py
export_serving_model): the serving.json metadata plus one deserialized
StableHLO executable PER shape bucket. Loading WARMS every bucket — a
zero batch runs through each executable at load time, so the first real
request never pays a compile (and with JAX's persistent compile cache
on, core/compile_cache.py, the warmup itself hits the disk cache after
the first process).

Hot reload is drain-based, not lock-based: the registry builds and warms
the NEW version entirely off to the side, atomically swaps the routing
pointer (one dict store under a mutex), then closes the OLD version's
batcher with drain=True — the old dispatcher finishes every request that
was already queued against it before the version is released. In-flight
requests therefore never see the swap; new requests never see the old
version. Zero requests are dropped by construction, which
tests/test_serving.py asserts under a concurrent submit storm.
"""

from __future__ import annotations

import functools
import os
import threading
from contextlib import contextmanager, nullcontext
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .admission import InvalidRequest, ModelUnavailable

__all__ = ["ModelVersion", "ModelRegistry"]


def load_bundle_weights(model_dir: str, meta: dict) -> Dict:
    """The bundle's weights file on the device, once ({} for a bundle
    whose artifacts inline their weights)."""
    fn = meta.get("weights_file")
    if not fn:
        return {}
    import jax.numpy as jnp
    # the pieces that hold a bfloat16 matrix's bits as uint16 (numpy has
    # no bfloat16 of its own); a bundle without the record has none
    as_bits = set(meta.get("weights", {}).get("stored", {})
                  .get("bfloat16_as_uint16", ()))
    with np.load(os.path.join(model_dir, fn)) as f:
        return {n: jnp.asarray(f[n].view(jnp.bfloat16) if n in as_bits
                               else f[n]) for n in f.files}


def bind_weights(call, weights: Dict, names: Optional[Sequence[str]]):
    """An artifact that takes its weights as first argument, bound to
    the bundle's shared device copy; an inlined one passes through."""
    if names is None:
        return call
    return functools.partial(call, {n: weights[n] for n in names})


class _Bucket:
    """One compiled shape bucket: the executable + its feed/fetch specs."""

    __slots__ = ("length", "call", "feeds", "fetches", "unbound_call",
                 "weight_names")

    def __init__(self, length: Optional[int], call, feeds: List[dict],
                 fetches: Optional[List[dict]], unbound_call,
                 weight_names: Optional[Sequence[str]]):
        self.length = length
        self.call = call
        self.feeds = feeds        # [{"name","shape","dtype"}...]
        self.fetches = fetches    # same, or None on legacy artifacts
        #: the artifact's own call and the names of the weights it takes
        #: as first argument (None: inlined), for a caller that jits
        #: over it and must pass the weights as arguments itself
        self.unbound_call = unbound_call
        self.weight_names = weight_names


class ModelVersion:
    """One immutable loaded artifact. Owns bucket selection, batch
    padding, execution, and scatter — the batcher only does queueing."""

    def __init__(self, model_dir: str, meta: dict, buckets: Dict, *,
                 version: int, weights: Optional[Dict] = None):
        self.model_dir = model_dir
        self.version = version
        #: device-resident weights of a bundle that carries them beside
        #: its artifacts (io.export_decode_model); {} when inlined
        self.weights = weights or {}
        self.batch_size = int(meta["batch_size"])
        self.fetch_names = list(meta["fetch_names"])
        self.feed_names = [m["name"] for m in meta["feeds"]]
        #: feed name -> indices of its bucketed (length) dims, full-shape
        #: coords (0 is the batch dim)
        self.var_dims: Dict[str, List[int]] = {
            k: list(v) for k, v in meta.get("var_dims", {}).items()}
        self._buckets = buckets                    # key(None|int) -> _Bucket
        self.bounds = sorted(k for k in buckets if k is not None)
        # the engine's whole padding/scatter model slices feeds on a
        # leading batch axis; an artifact with a static
        # (append_batch_size=False) feed cannot be coalesced — refuse at
        # load instead of silently mis-serving (the direct
        # load_serving_model path still serves such artifacts)
        static = [m["name"] for m in self._base_bucket().feeds
                  if m.get("batch_major") is False]
        if static:
            raise ValueError(
                f"serving engine requires batch-major feeds; {static} "
                "have no batch axis — serve this artifact via "
                "io.load_serving_model instead")

    # -- loading -------------------------------------------------------------
    @classmethod
    def load(cls, model_dir: str, *, version: int,
             warmup: bool = True) -> "ModelVersion":
        import json
        from ..core.compat import jax_export

        with open(os.path.join(model_dir, "serving.json")) as f:
            meta = json.load(f)
        entries = meta.get("buckets")
        if not entries:
            # legacy artifact: one bucket, the historical filenames, no
            # fetch specs (scatter discovers shapes from the outputs)
            entries = [{"length": None, "file": "serving.stablehlo",
                        "feeds": meta["feeds"], "fetches": None}]
        weights = load_bundle_weights(model_dir, meta)
        buckets: Dict = {}
        for e in entries:
            with open(os.path.join(model_dir, e["file"]), "rb") as f:
                exported = jax_export().deserialize(bytearray(f.read()))
            key = e["length"] if e["length"] is None else int(e["length"])
            buckets[key] = _Bucket(
                key, bind_weights(exported.call, weights, e.get("weights")),
                e["feeds"], e.get("fetches"), exported.call,
                e.get("weights"))
        model = cls(model_dir, meta, buckets, version=version,
                    weights=weights)
        if warmup:
            model.warmup()
        return model

    def warmup(self) -> None:
        """Run a zero batch through EVERY bucket so each executable is
        compiled (or loaded from the persistent compile cache) before the
        first real request arrives."""
        for b in self._buckets.values():
            zeros = [np.zeros(tuple(m["shape"]), dtype=np.dtype(m["dtype"]))
                     for m in b.feeds]
            outs = self._normalize(b.call(*zeros))
            for o in outs:
                np.asarray(o)  # block: warmup must finish before serving

    def bucket(self, key) -> _Bucket:
        """The bucket of one `bounds` entry (or None, a legacy
        artifact's only one)."""
        return self._buckets[key]

    def _base_bucket(self) -> _Bucket:
        return self._buckets[self.bounds[-1] if self.bounds else None]

    def feed_dtypes(self) -> Dict[str, np.dtype]:
        """{feed name: numpy dtype} — the public surface front ends use
        for dtype-faithful request coercion."""
        return {m["name"]: np.dtype(m["dtype"])
                for m in self._base_bucket().feeds}

    # -- request classification ---------------------------------------------
    def bucket_of(self, feeds: Dict[str, np.ndarray]):
        """The bucket key for one EXAMPLE (feeds carry no batch dim), or
        raise InvalidRequest when no exported bucket can hold it."""
        if set(feeds) != set(self.feed_names):
            raise InvalidRequest(
                f"feeds {sorted(feeds)} != model feeds "
                f"{sorted(self.feed_names)}")
        need = 0
        for m in self._base_bucket().feeds:
            name = m["name"]
            ex = np.asarray(feeds[name])
            want = list(m["shape"][1:])   # example coords: drop batch dim
            if ex.ndim != len(want):
                raise InvalidRequest(
                    f"feed {name!r}: rank {ex.ndim} != {len(want)}")
            if not np.can_cast(ex.dtype, np.dtype(m["dtype"]),
                               casting="same_kind"):
                raise InvalidRequest(
                    f"feed {name!r}: dtype {ex.dtype} not same-kind "
                    f"castable to {m['dtype']}")
            var = set(d - 1 for d in self.var_dims.get(name, ()))
            for d, (got, exp) in enumerate(zip(ex.shape, want)):
                if d in var:
                    need = max(need, int(got))
                elif int(got) != int(exp):
                    raise InvalidRequest(
                        f"feed {name!r}: dim {d} is {got}, model wants "
                        f"{exp}")
        if not self.bounds:
            return None
        from ..reader.bucketing import bucket_bound
        if need > self.bounds[-1]:
            raise InvalidRequest(
                f"length {need} exceeds the largest exported bucket "
                f"{self.bounds[-1]} (buckets: {self.bounds})")
        return bucket_bound(max(need, 1), self.bounds)

    # -- execution -----------------------------------------------------------
    @staticmethod
    def _normalize(outs) -> list:
        if isinstance(outs, dict):
            return list(outs.values())
        if not isinstance(outs, (list, tuple)):
            return [outs]
        return list(outs)

    def execute_batch(self, bucket_key, examples: Sequence[Dict[str,
                                                                np.ndarray]],
                      timer=None):
        """Pad `examples` (<= batch_size) into the bucket shape, run the
        compiled executable once, scatter rows back per example. Returns
        (results, phase_s): one {fetch_name: array} dict per example in
        order, plus this batch's pad/device/fetch/scatter seconds
        (`device` ends when the outputs are ready, `fetch` is their
        copy to host numpy). The same intervals are spans of `timer`
        (the model's cumulative phase accounting) when given."""
        import time as _time

        import jax

        b = self._buckets[bucket_key]
        B = self.batch_size
        if len(examples) > B:
            raise ValueError(f"{len(examples)} examples > batch {B}")

        phase_s: Dict[str, float] = {}

        @contextmanager
        def _phase(phase: str):
            t0 = _time.perf_counter()
            with (timer.span(phase) if timer is not None
                  else nullcontext()):
                yield
            phase_s[phase] = _time.perf_counter() - t0

        with _phase("pad"):
            arrays = []
            for m in b.feeds:
                buf = np.zeros(tuple(m["shape"]),
                               dtype=np.dtype(m["dtype"]))
                for r, ex in enumerate(examples):
                    a = np.asarray(ex[m["name"]])
                    buf[(r,) + tuple(slice(0, s) for s in a.shape)] = a
                arrays.append(buf)

        with _phase("device"):
            outs = self._normalize(b.call(*arrays))
            jax.block_until_ready(outs)   # the device sync

        with _phase("fetch"):
            outs = [np.asarray(o) for o in outs]

        with _phase("scatter"):
            results = self._scatter(b, outs, len(examples))
        return results, phase_s

    def _scatter(self, b: _Bucket, outs: list,
                 n: int) -> List[Dict[str, np.ndarray]]:
        B = self.batch_size
        results: List[Dict[str, np.ndarray]] = []
        # batch-major fetches scatter by row; others (reduced scalars,
        # parameter fetches) are replicated. The export-recorded flag is
        # authoritative — a fetch whose leading dim merely coincides with
        # the batch size must NOT be split; the shape test is only the
        # legacy-artifact fallback
        metas = b.fetches or [None] * len(outs)
        for r in range(n):
            row = {}
            for name, o, m in zip(self.fetch_names, outs, metas):
                bm = (m["batch_major"] if m and "batch_major" in m
                      else o.ndim >= 1 and o.shape[0] == B)
                row[name] = o[r].copy() if bm else o.copy()
            results.append(row)
        return results


class _Entry:
    __slots__ = ("name", "model", "batcher")

    def __init__(self, name: str, model: ModelVersion, batcher):
        self.name = name
        self.model = model
        self.batcher = batcher


class ModelRegistry:
    """name -> current (ModelVersion, batcher), with drain-on-swap
    reloads. `make_batcher(name, model)` is injected by the engine so the
    registry stays free of queueing policy."""

    def __init__(self, make_batcher: Callable[[str, ModelVersion], object]):
        self._make_batcher = make_batcher
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}
        self._versions: Dict[str, int] = {}

    def _reserve_version(self, name: str,
                         version: Optional[int]) -> int:
        """Reserve a version id NOW, not after the (slow, unlocked)
        model build — two concurrent reloads must get distinct ids."""
        with self._lock:
            if version is None:
                version = self._versions.get(name, 0) + 1
            self._versions[name] = max(self._versions.get(name, 0),
                                       version)
        return version

    def _publish(self, name: str, model) -> None:
        """The swap tail every load path shares: build the new
        batcher, atomically swap the routing entry, then drain the old
        version's batcher (zero dropped in-flight requests)."""
        batcher = self._make_batcher(name, model)
        with self._lock:
            old = self._entries.get(name)
            self._entries[name] = _Entry(name, model, batcher)
        if old is not None:
            old.batcher.close(drain=True)

    def load(self, name: str, model_dir: str,
             version: Optional[int] = None, *,
             warmup: bool = True) -> int:
        """Load (or hot-reload) `name` from `model_dir`. Returns the
        version id. The new version is fully warmed BEFORE the swap; the
        old version drains all queued requests before release."""
        version = self._reserve_version(name, version)
        model = ModelVersion.load(model_dir, version=version,
                                  warmup=warmup)
        self._publish(name, model)
        return version

    def load_object(self, name: str, model,
                    version: Optional[int] = None) -> int:
        """Register an in-memory model object through the same
        batcher/entry path as an artifact load: anything with
        `batch_size`, `bucket_of(feeds)`, and `execute_batch(bucket,
        examples, timer=)` serves behind the engine's full queueing /
        admission / metrics stack. This is how the fleet bench and the
        unit plane host synthetic replicas — the routing tier above is
        identical either way. Swap semantics match load(): new batcher
        in, old batcher drained."""
        version = self._reserve_version(name, version)
        if getattr(model, "version", None) is None:
            try:
                model.version = version
            except (AttributeError, TypeError):
                pass   # slotted/frozen stubs keep their own identity
        self._publish(name, model)
        return version

    def get(self, name: str) -> _Entry:
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise ModelUnavailable(f"no model named {name!r} is loaded")
        return entry

    def unload(self, name: str) -> None:
        with self._lock:
            entry = self._entries.pop(name, None)
        if entry is not None:
            entry.batcher.close(drain=True)

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    def describe(self) -> dict:
        with self._lock:
            entries = list(self._entries.values())
        out = {}
        for e in entries:
            m = e.model
            # getattr-tolerant: load_object() models (fleet synthetic
            # replicas, unit stubs) describe what they declare
            out[e.name] = {
                "version": getattr(m, "version", None),
                "model_dir": getattr(m, "model_dir", None),
                "batch_size": m.batch_size,
                "buckets": (m.bounds or [None]) if hasattr(m, "bounds")
                else [None],
                "feeds": getattr(m, "feed_names", []),
                "fetches": getattr(m, "fetch_names", []),
            }
        return out

    def close(self, drain: bool = True) -> None:
        with self._lock:
            entries = list(self._entries.values())
            self._entries.clear()
        for e in entries:
            e.batcher.close(drain=drain)
