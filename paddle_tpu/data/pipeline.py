"""Composable input pipeline: source -> shard -> shuffle -> batch ->
parallel decode -> device prefetch.

The production data plane (≙ the tf.data shape: a dataflow of composable
stages with parallel maps, prefetching, and checkpointable iterator
state — PAPERS.md "tf.data: A Machine Learning Data Processing
Framework"). The ad-hoc reader chain this replaces decodes every sample
in the consumer's thread: BENCH r05 measured real-data ResNet training
at 245 img/s vs 2637 on fake data — the device idles ~90% of each step
waiting for input. This subsystem moves decode onto a bounded worker
pool, keeps the host->device upload overlapped through the two-stage
``double_buffer`` (reader/prefetch.py), and pushes augmentation onto the
device itself (data/augment.py), so the consumer's ``next()`` is a queue
pop, not a decode.

A `Dataset` IS a reader (a nullary callable returning an iterator), so
every existing consumer — `Trainer.train`, `DeviceFeeder`,
`resilient_reader`, `double_buffer` — takes one unchanged. On top of the
reader protocol it adds:

    iter_from(n)   iterate with the first n output batches skipped
                   CHEAPLY: raw records are scanned and shuffled (bytes
                   shuffling, exact rng replay) but never decoded or
                   uploaded. This is what makes mid-epoch resume and
                   fault-restart fast AND bit-exact: the resilient
                   reader and the Trainer's resume fast-forward both use
                   it when present.
    set_epoch(e)   pin the epoch index feeding the seeded shuffle and
                   the augmentation rng. The Trainer calls it at each
                   epoch start, so `shuffle(reshuffle_each_epoch=True)`
                   stays deterministic across preempt/resume (the epoch
                   id is restored from trainer_args, never counted from
                   process-local invocations).
    state()/restore(state)
                   checkpointable pipeline position: epoch, the
                   batches-delivered cursor, and the pipeline signature
                   (a wrong-pipeline restore fails loudly).

Determinism contract: same pipeline + same seed + same epoch => the
identical batch stream, regardless of worker count or backend (the
parallel decode preserves source order via an ordered bounded handoff).
Everything downstream — exactly-once under reader faults, bit-exact
resume — reduces to that invariant.

Env knobs (all declared in flags.declare_env_knob): PT_DATA_WORKERS
(decode pool width), PT_DATA_BACKEND (thread | process — the process
pool exists for GIL-bound Python decoders but the tier-1 sandbox has
known multiprocess limits, so nothing in tests exercises it),
PT_DATA_PREFETCH (decoded-batch queue depth).
"""

from __future__ import annotations

import os
import queue
import random
import threading
from typing import Callable, Iterable, List, Optional, Sequence

from ..flags import env_knob_int as _knob_int
from ..reader.prefetch import bounded_put
from .metrics import PipelineMetrics, register as _register_metrics

__all__ = ["Dataset"]

_END = object()


class _Ctx:
    """Per-iteration context threaded through the node chain at iterator
    construction time. `skip` is consumed by the deepest stage that can
    discard cheaply (the batch assembler — upstream of decode); `cursor0`
    keeps the absolute batch index so augmentation rng stays aligned
    after a skip."""

    __slots__ = ("epoch", "skip", "cursor0", "metrics")

    def __init__(self, epoch: int, skip: int,
                 metrics: Optional[PipelineMetrics]):
        self.epoch = epoch
        self.skip = skip
        self.cursor0 = skip
        self.metrics = metrics


class Dataset:
    """One pipeline stage; composition methods each return a new stage
    wrapping `self`. The object you finally hold is the whole pipeline
    and a reader. Stages never mutate their upstream — two pipelines may
    share a prefix."""

    def __init__(self, upstream: Optional["Dataset"] = None):
        self._up = upstream
        self._epoch = 0
        self._delivered = 0
        self._pending_skip = 0
        self._metrics: Optional[PipelineMetrics] = None
        self._name: Optional[str] = None

    # -- sources ------------------------------------------------------------
    @staticmethod
    def from_reader(reader: Callable[[], Iterable]) -> "Dataset":
        """Wrap any reader creator (nullary -> iterator of items)."""
        return _Source(reader)

    @staticmethod
    def from_samples(samples: Sequence) -> "Dataset":
        """In-memory source (tests, warm caches)."""
        return _Source(lambda: iter(samples))

    @staticmethod
    def from_recordio(paths, parallel_files: int = 1) -> "Dataset":
        """Raw-record source over one or more RecordIO files, scanned in
        sorted order (shard files land deterministically).

        parallel_files > 1 is the sharded-reader fast path: up to that
        many files are scanned by concurrent reader threads and their
        records merged by STRICT round-robin over the file order — the
        merge order is a pure function of the file contents, never of
        thread timing, so the determinism/resume contract holds. One
        scan thread tops out near the single-stream RecordIO rate
        (ctypes + crc per record); sharded training data usually ships
        as many files, so read them like it."""
        from .. import recordio
        if isinstance(paths, (str, os.PathLike)):
            paths = [paths]
        paths = sorted(str(p) for p in paths)
        if not paths:
            raise ValueError("from_recordio: no paths given")
        if parallel_files <= 1 or len(paths) == 1:
            def scan_all():
                for p in paths:
                    yield from recordio.scan(p)

            return _Source(scan_all)
        return _Source(lambda: _interleave_files(
            paths, min(parallel_files, len(paths)),
            lambda p: recordio.scan(p)))

    # -- transforms ---------------------------------------------------------
    def shard(self, num_shards: Optional[int] = None,
              index: Optional[int] = None) -> "Dataset":
        """Keep every num_shards-th item starting at `index` (strided:
        shards are disjoint and their union is the full stream). Defaults
        come from the distributed runtime (jax process count/index), so
        multi-host launches shard with zero per-model plumbing."""
        return _Shard(self, num_shards, index)

    def shuffle(self, buf_size: int, seed: int = 0,
                reshuffle_each_epoch: bool = True) -> "Dataset":
        """Seeded pool shuffle (≙ reader.decorator.shuffle, but with OWN
        rng — never the process-global `random` — so the stream is a
        pure function of (seed, epoch)). reshuffle_each_epoch folds the
        epoch from set_epoch() into the rng; with False every epoch
        replays one fixed order."""
        if buf_size < 1:
            raise ValueError("shuffle buf_size must be >= 1")
        return _Shuffle(self, buf_size, seed, reshuffle_each_epoch)

    def map(self, fn: Callable) -> "Dataset":
        """Per-item host transform, in the consumer's thread (cheap
        reshapes; put decode work in map_batches instead)."""
        return _Map(self, fn)

    def batch(self, batch_size: int, drop_last: bool = False) -> "Dataset":
        """Group items into lists of `batch_size`. Also the pipeline's
        cheap-skip point: iter_from(n) discards the first n raw batches
        HERE, upstream of decode."""
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        return _Batch(self, batch_size, drop_last)

    def map_batches(self, fn: Callable, workers: Optional[int] = None,
                    prefetch: Optional[int] = None,
                    backend: Optional[str] = None) -> "Dataset":
        """Parallel decode: fan `fn` out over a bounded worker pool with
        ORDERED delivery (futures queue in submission order — output
        order is the source order, always). `workers` defaults to
        PT_DATA_WORKERS (2); `prefetch` bounds decoded batches in flight
        (PT_DATA_PREFETCH, default 2 x workers); `backend` thread |
        process (PT_DATA_BACKEND — process pools need a picklable fn and
        are NOT exercised by tier-1: the sandbox has known multiprocess
        limits)."""
        return _MapBatches(self, fn, workers, prefetch, backend)

    def encode(self, policy: Optional[str] = None,
               keys: Optional[Sequence[str]] = None,
               out_dtype: str = "float32") -> "Dataset":
        """On-wire feed codec (data/codec.py): host-encode the decoded
        batches so the bytes that cross the host->device pipe are
        int8/bf16, not f32 — the thin-pipe lever (where a ~15 MB/s
        upload pipe, not the CPU, caps real-data training).
        `policy` defaults to PT_FEED_CODEC (none | bf16 | int8); `keys`
        limits encoding to those feed-dict entries (default: every
        floating entry); `out_dtype` is what the device-side decode
        recovers (match your pipeline's pre-encode dtype).

        Composes 1:1 with shard/shuffle/batch — skips stay claimed
        upstream in raw batch units, which ARE encoded units, so the
        iter_from/set_epoch/state resume contract is untouched. The
        matching device-side decode fuses into a downstream `.augment()`
        call or runs as its own traced transform in `.device_prefetch()`
         's upload thread; without either, the consumer receives encoded
        batches (the program-level `apply_wire_codec` path)."""
        from .codec import FeedCodec
        return _Encode(self, FeedCodec(policy, keys, out_dtype))

    def augment(self, aug) -> "Dataset":
        """Device-side augmentation (data/augment.py Augment): applied to
        the uploaded batch as one traced call. When the next stage is
        device_prefetch, the call is hoisted into its upload thread so
        the consumer never touches it. Downstream of an `.encode()`
        stage the dequant fuses INTO the augment program (one compiled
        call, keyed on the codec policy) — the decoded f32 batch exists
        only on device."""
        return _AugmentStage(self, aug, codec=self._upstream_codec())

    def device_prefetch(self, capacity: int = 2) -> "Dataset":
        """Two-stage host->device prefetch (reader/prefetch.py
        double_buffer): decode handoff -> device_put staging -> consumer,
        each stage `capacity` batches ahead."""
        return _DevicePrefetch(self, capacity)

    # alias matching the tf.data verb
    prefetch = device_prefetch

    def named(self, name: str) -> "Dataset":
        """Name this pipeline and register its metrics on the
        process-wide scrape (serving HTTP front end -> pt_data_* family).
        Returns self — terminal sugar, not a new stage."""
        self._name = name
        self._metrics = PipelineMetrics(name)
        _register_metrics(self._metrics)
        return self

    # -- reader protocol ----------------------------------------------------
    def __call__(self):
        skip, self._pending_skip = self._pending_skip, 0
        return self.iter_from(skip)

    def iter_from(self, n_batches: int):
        """Iterate, cheaply skipping the first `n_batches` output batches
        (see module docstring). The delivered-batch cursor continues at
        `n_batches`, so state()/augmentation stay aligned with an
        uninterrupted run."""
        if self._metrics is None:
            self._metrics = PipelineMetrics(self._name or "pipeline")
        met = self._metrics
        ctx = _Ctx(self._epoch, int(n_batches), met)
        inner = self._iter(ctx)
        self._delivered = int(n_batches)

        def delivered():
            clock = met._clock
            it = iter(inner)
            while True:
                t0 = clock()
                try:
                    item = next(it)
                except StopIteration:
                    return
                met.add("queue_wait", clock() - t0, 1,
                        cursor=self._delivered)
                met.on_delivered(_batch_samples(item))
                self._delivered += 1
                yield item

        return delivered()

    # -- checkpointable state ----------------------------------------------
    def set_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)

    def signature(self) -> str:
        """Structural identity of the stage chain — restore() refuses a
        state dict written by a differently-shaped pipeline."""
        parts = []
        node: Optional[Dataset] = self
        while node is not None:
            parts.append(node._sig())
            node = node._up
        return ">".join(reversed(parts))

    def state(self) -> dict:
        """The resume point: restore() + iterating once replays the
        stream from exactly the next undelivered batch."""
        return {"epoch": self._epoch, "delivered": self._delivered,
                "signature": self.signature()}

    def restore(self, state: dict) -> None:
        sig = state.get("signature")
        if sig is not None and sig != self.signature():
            raise ValueError(
                "pipeline state mismatch: saved signature "
                f"{sig!r} != this pipeline's {self.signature()!r} — "
                "restoring would silently resume a different stream")
        self.set_epoch(state.get("epoch", 0))
        self._pending_skip = int(state.get("delivered", 0))

    def metrics_snapshot(self, reset: bool = False) -> dict:
        """Per-stage occupancy snapshot (executor.step_timings()-style);
        see data/metrics.py for the stage semantics."""
        if self._metrics is None:
            self._metrics = PipelineMetrics(self._name or "pipeline")
        return self._metrics.snapshot(reset=reset)

    # -- node internals -----------------------------------------------------
    def _upstream_codec(self):
        """The nearest upstream `_Encode` stage's codec (None if the
        stream is unencoded) — how augment/device_prefetch know to fuse
        the device-side dequant."""
        node: Optional[Dataset] = self
        while node is not None:
            if isinstance(node, _Encode):
                return node._codec
            node = node._up
        return None

    def _iter(self, ctx: _Ctx):
        raise NotImplementedError

    def _sig(self) -> str:
        raise NotImplementedError


def _batch_samples(item) -> int:
    """Best-effort sample count of a delivered batch (metrics only)."""
    if isinstance(item, dict):
        for v in item.values():
            shp = getattr(v, "shape", None)
            if shp:
                return int(shp[0])
        return 1
    if isinstance(item, (list, tuple)):
        return len(item)
    shp = getattr(item, "shape", None)
    return int(shp[0]) if shp else 1


#: records per interleave queue handoff: per-record Queue ops cost more
#: than the 27 KB record they carry; a chunk amortizes the lock + wakeup
_INTERLEAVE_CHUNK = 32


def _interleave_files(paths, width: int, open_fn):
    """Merge per-file record streams by strict round-robin over the file
    order, with each stream pumped by its own daemon thread into a small
    bounded queue (in chunks — see _INTERLEAVE_CHUNK). The consumer
    blocks on queues IN ORDER, so the merged stream is deterministic
    regardless of which reader thread runs when; an exhausted file
    simply drops out of the rotation. Errors surface at the failing
    file's next turn — in stream order."""
    qs = [queue.Queue(maxsize=4) for _ in paths]
    stop = threading.Event()

    def q_put(q, item) -> bool:
        return bounded_put(q, item, stop)

    def pump(path, q):
        try:
            chunk = []
            for rec in open_fn(path):
                chunk.append(rec)
                if len(chunk) >= _INTERLEAVE_CHUNK:
                    if not q_put(q, chunk):
                        return
                    chunk = []
            if chunk:
                q_put(q, chunk)
        except BaseException as e:  # noqa: BLE001 — re-raised in order
            q_put(q, _Err(e))
        finally:
            q_put(q, _END)

    # a bounded thread pool over the files: the first `width` start now,
    # each finishing file hands its slot to the next unopened one
    for i in range(width):
        threading.Thread(target=pump, args=(paths[i], qs[i]),
                         daemon=True, name=f"pt-data-scan-{i}").start()

    try:
        active = list(range(width))
        queued = list(range(width, len(paths)))
        while active:
            nxt = []
            for i in active:
                item = qs[i].get()
                if item is _END:
                    if queued:
                        j = queued.pop(0)
                        threading.Thread(
                            target=pump, args=(paths[j], qs[j]),
                            daemon=True, name=f"pt-data-scan-{j}").start()
                        nxt.append(j)
                    continue
                if isinstance(item, _Err):
                    raise item.exc
                nxt.append(i)
                yield from item
            active = nxt
    finally:
        stop.set()


def _take_skip(ctx: _Ctx) -> int:
    """Claim the pending skip for THIS stage's output. Every stage whose
    output positions don't map 1:1 onto its input positions (batch,
    shard, shuffle — and source as the fallback) must claim the skip
    BEFORE recursing upstream and discard its OWN outputs: forwarding it
    would discard upstream items in the wrong units (shifting shard
    parity, desynchronizing the shuffle pool) and break the bit-exact
    resume contract. Strictly 1:1 stages (map, map_batches, augment,
    device_prefetch) just pass the ctx through."""
    n, ctx.skip = ctx.skip, 0
    return n


def _drop_first(it, n: int):
    """Lazily discard the first n outputs of `it`."""
    if not n:
        return it

    def gen():
        dropped = 0
        for item in it:
            if dropped < n:
                dropped += 1
                continue
            yield item

    return gen()


class _Source(Dataset):
    def __init__(self, fn: Callable[[], Iterable]):
        super().__init__(None)
        self._fn = fn

    def _iter(self, ctx: _Ctx):
        return _drop_first(iter(self._fn()), _take_skip(ctx))

    def _sig(self) -> str:
        return "source"


class _Shard(Dataset):
    def __init__(self, up: Dataset, num_shards: Optional[int],
                 index: Optional[int]):
        super().__init__(up)
        if (num_shards is None) != (index is None):
            raise ValueError("shard: pass both num_shards and index, or "
                             "neither (distributed defaults)")
        if num_shards is not None:
            if num_shards < 1 or not (0 <= index < num_shards):
                raise ValueError(
                    f"shard: need 0 <= index < num_shards, got "
                    f"index={index} num_shards={num_shards}")
        self._n = num_shards
        self._i = index

    def _resolve(self):
        if self._n is not None:
            return self._n, self._i
        import jax
        return jax.process_count(), jax.process_index()

    def _iter(self, ctx: _Ctx):
        n, i = self._resolve()
        # claim the skip BEFORE recursing: output position k is input
        # position k*n+i, so discarding raw inputs upstream would shift
        # the stride parity for the rest of the epoch
        discard = _take_skip(ctx)
        src = self._up._iter(ctx)
        if n == 1:
            # degenerate single-shard: no per-item modulo layer
            return _drop_first(src, discard)

        def gen():
            for k, item in enumerate(src):
                if k % n == i:
                    yield item

        return _drop_first(gen(), discard)

    def _sig(self) -> str:
        return f"shard({self._n},{self._i})"


class _Shuffle(Dataset):
    def __init__(self, up: Dataset, buf_size: int, seed: int,
                 reshuffle_each_epoch: bool):
        super().__init__(up)
        self._buf_size = buf_size
        self._seed = seed
        self._per_epoch = reshuffle_each_epoch

    def _iter(self, ctx: _Ctx):
        # claim the skip BEFORE recursing: a skip applied to the RAW
        # stream would feed the pool different items and desynchronize
        # the whole shuffled order — the replay must discard SHUFFLED
        # outputs (cheap: they are still raw bytes, pre-decode)
        discard = _take_skip(ctx)
        src = self._up._iter(ctx)
        tag = f"{self._seed}:{ctx.epoch}" if self._per_epoch \
            else f"{self._seed}"
        rng = random.Random(f"pt-data-shuffle:{tag}")
        buf_size = self._buf_size

        def gen():
            buf: List = []
            for item in src:
                buf.append(item)
                if len(buf) >= buf_size:
                    rng.shuffle(buf)
                    while buf:
                        yield buf.pop()
            rng.shuffle(buf)
            while buf:
                yield buf.pop()

        return _drop_first(gen(), discard)

    def _sig(self) -> str:
        return f"shuffle({self._buf_size})"


class _Map(Dataset):
    def __init__(self, up: Dataset, fn: Callable):
        super().__init__(up)
        self._fn = fn

    def _iter(self, ctx: _Ctx):
        # 1:1 stage: let upstream discard skipped items so fn never runs
        # on them
        src = self._up._iter(ctx)
        fn = self._fn
        return (fn(item) for item in src)

    def _sig(self) -> str:
        return "map"


class _Batch(Dataset):
    def __init__(self, up: Dataset, batch_size: int, drop_last: bool):
        super().__init__(up)
        self._bs = batch_size
        self._drop_last = drop_last

    def _iter(self, ctx: _Ctx):
        # the cheap-skip point: consume ctx.skip here — raw items are
        # assembled (replaying shard/shuffle decisions exactly) but the
        # skipped batches never reach decode or upload
        discard = _take_skip(ctx)
        src = self._up._iter(ctx)
        bs, drop_last = self._bs, self._drop_last

        def gen():
            skipped = 0
            b: List = []
            for item in src:
                b.append(item)
                if len(b) == bs:
                    if skipped < discard:
                        skipped += 1
                    else:
                        yield b
                    b = []
            if b and not drop_last and skipped >= discard:
                yield b

        return gen()

    def _sig(self) -> str:
        return f"batch({self._bs},{self._drop_last})"


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class _MapBatches(Dataset):
    def __init__(self, up: Dataset, fn: Callable, workers: Optional[int],
                 prefetch: Optional[int], backend: Optional[str]):
        super().__init__(up)
        self._fn = fn
        self._workers = workers
        self._prefetch = prefetch
        self._backend = backend

    def _resolve(self):
        workers = self._workers or _knob_int("PT_DATA_WORKERS", 2)
        backend = self._backend or os.environ.get("PT_DATA_BACKEND",
                                                  "thread") or "thread"
        if backend not in ("thread", "process"):
            raise ValueError(f"PT_DATA_BACKEND must be thread|process, "
                             f"got {backend!r}")
        depth = self._prefetch or _knob_int("PT_DATA_PREFETCH", 2 * workers)
        return workers, backend, depth

    def _iter(self, ctx: _Ctx):
        workers, backend, depth = self._resolve()
        src = self._up._iter(ctx)  # 1:1: upstream already discarded skips
        fn = self._fn
        met = ctx.metrics
        if met is not None:
            met.set_workers(workers)

        cursor0 = ctx.cursor0

        def timed_fn(item, idx=None):
            if met is None:
                return fn(item)
            # the batch cursor rides the decode span (map_batches is
            # 1:1, so submission index + the skip base IS the delivered
            # cursor) — "which batch was decoding" is answerable from
            # the trace
            with met.span("decode",
                          **({} if idx is None
                             else {"cursor": cursor0 + idx})):
                return fn(item)

        def gen():
            if backend == "process":
                # GIL-bound pure-Python decoders only; the native decode
                # kernels release the GIL, so threads are the default.
                # NOT exercised by tier-1 (sandbox multiprocess limits).
                from concurrent.futures import ProcessPoolExecutor
                pool = ProcessPoolExecutor(max_workers=workers)
                work = fn  # child-process time is not attributable here
            else:
                from concurrent.futures import ThreadPoolExecutor
                pool = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix="pt-data")
                work = timed_fn
            # ordered bounded handoff: futures enter the queue in
            # submission (= source) order; the consumer resolves them in
            # that order, so parallelism never reorders the stream and at
            # most `depth` decoded batches are in flight
            q: "queue.Queue" = queue.Queue(maxsize=depth)
            stop = threading.Event()

            def put(item) -> bool:
                return bounded_put(q, item, stop)

            def feed():
                try:
                    for i, item in enumerate(src):
                        if stop.is_set():
                            return
                        # thread backend: pass the submission index so
                        # the decode span carries the batch cursor (the
                        # process pool runs the bare fn — child-process
                        # time is not attributable here anyway)
                        fut = (pool.submit(work, item) if work is fn
                               else pool.submit(work, item, i))
                        if not put(fut):
                            return
                except BaseException as e:  # noqa: BLE001 — re-raised in order
                    put(_Err(e))
                finally:
                    put(_END)

            t = threading.Thread(target=feed, daemon=True,
                                 name="pt-data-feed")
            t.start()
            try:
                while True:
                    item = q.get()
                    if item is _END:
                        return
                    if isinstance(item, _Err):
                        raise item.exc
                    yield item.result()
            finally:
                stop.set()
                pool.shutdown(wait=False, cancel_futures=True)

        return gen()

    def _sig(self) -> str:
        return "map_batches"


class _Encode(Dataset):
    """Host-side wire encode (data/codec.py). Strictly 1:1 — output
    batch k IS input batch k, encoded — so the pending skip passes
    through to be claimed upstream in raw batch units (the PR-8
    skip-units lesson: only non-1:1 stages may claim it). Encoding is a
    pure function of the batch, so a resumed stream re-encodes
    bit-identically."""

    def __init__(self, up: Dataset, codec):
        super().__init__(up)
        self._codec = codec

    def _iter(self, ctx: _Ctx):
        src = self._up._iter(ctx)  # 1:1: upstream discards skipped batches
        codec = self._codec
        met = ctx.metrics

        def gen():
            from .codec import raw_nbytes
            for i, item in enumerate(src):
                if met is None:
                    yield codec.encode_batch(item)
                    continue
                raw = raw_nbytes(item) if isinstance(item, dict) else 0
                with met.span("encode", cursor=ctx.cursor0 + i):
                    out = codec.encode_batch(item)
                met.add_wire(raw, raw_nbytes(out)
                             if isinstance(out, dict) else 0)
                yield out

        return gen()

    def _sig(self) -> str:
        return f"encode({self._codec.policy})"


class _AugmentStage(Dataset):
    def __init__(self, up: Dataset, aug, codec=None):
        super().__init__(up)
        self._aug = aug
        self._codec = codec

    def _iter(self, ctx: _Ctx):
        src = self._up._iter(ctx)
        aug = self._aug
        codec = self._codec
        epoch, cursor0 = ctx.epoch, ctx.cursor0
        met = ctx.metrics

        def gen():
            for i, item in enumerate(src):
                if met is None:
                    yield aug(item, cursor0 + i, epoch, codec=codec)
                    continue
                with met.span("augment", cursor=cursor0 + i):
                    out = aug(item, cursor0 + i, epoch, codec=codec)
                yield out

        return gen()

    def _sig(self) -> str:
        return "augment"


class _DevicePrefetch(Dataset):
    def __init__(self, up: Dataset, capacity: int):
        super().__init__(up)
        if capacity < 1:
            raise ValueError("device_prefetch capacity must be >= 1")
        self._capacity = capacity

    def _iter(self, ctx: _Ctx):
        from ..reader.prefetch import double_buffer
        up = self._up
        transform = None
        if isinstance(up, _AugmentStage):
            # hoist the augmentation into the upload thread: the traced
            # call dispatches right after device_put, off the consumer's
            # critical path (its execution overlaps the training step).
            # An upstream encode stage's dequant fuses into the same call.
            aug, codec = up._aug, up._codec
            epoch, cursor0 = ctx.epoch, ctx.cursor0
            transform = (lambda item, idx:
                         aug(item, cursor0 + idx, epoch, codec=codec))
            up = up._up
        elif isinstance(up, _Encode):
            # encoded but un-augmented stream: the device-side dequant
            # still runs as one traced call in the upload thread — the
            # consumer (and the wire) never see a decoded f32 batch
            codec = up._codec
            transform = (lambda item, idx: codec.decode_batch(item))
        src_iter = up._iter(ctx)
        buffered = double_buffer(lambda: src_iter,
                                 capacity=self._capacity,
                                 transform=transform,
                                 instrument=ctx.metrics,
                                 cursor0=ctx.cursor0)
        return buffered()

    def _sig(self) -> str:
        return f"device_prefetch({self._capacity})"
