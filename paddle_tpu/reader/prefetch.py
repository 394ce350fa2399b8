"""Double-buffered host→device prefetch — a two-stage pipeline.

≙ reference double_buffer (python/paddle/fluid/layers/io.py:556) +
create_double_buffer_reader_op.cc: background stages that prepare the
NEXT batches while the CURRENT one computes. Two decoupled stages, each
its own thread + bounded queue:

  reader/decode  ->  q_host  ->  device_put  ->  q_dev  ->  consumer

so batch N+2's host-side decode overlaps batch N+1's host→device upload
overlaps batch N's device compute. Where upload is the bottleneck (an
earlier remote set-up: real-data 245 img/s vs 2637 fake over a ~15 MB/s
host->device pipe) the single-thread form serialized decode behind
upload inside one worker; splitting them keeps the decode CPU busy through the whole
upload window. jax.device_put itself is asynchronous, so the upload
stage mostly pays host-side staging — but staging is exactly what must
not sit between the reader and the consumer.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Optional

__all__ = ["double_buffer", "DeviceFeeder", "bounded_put"]

_STOP = object()


def bounded_put(q: "queue.Queue", item, stop: "threading.Event",
                timeout: float = 0.1) -> bool:
    """Bounded put that gives up when `stop` is set — the one stop-aware
    queue-handoff primitive shared by every pipeline stage thread here
    and in data/pipeline.py. Without the stop check, an abandoned
    consumer (exception/break in the train loop) would pin producer
    threads, their file handles, and queued device batches forever."""
    while not stop.is_set():
        try:
            q.put(item, timeout=timeout)
            return True
        except queue.Full:
            continue
    return False


class _NullSpan:
    """No-op timing span for the uninstrumented (default) path."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_null_span = _NullSpan()


def double_buffer(reader: Callable, place=None, capacity: int = 2,
                  retry_policy=None, transform=None, instrument=None,
                  cursor0: int = 0):
    """Wrap a feed-dict reader so device uploads overlap compute.

    reader() yields dicts of numpy arrays (or anything jax.device_put
    accepts). A decode thread stays `capacity` batches ahead of an
    upload thread, which stays `capacity` batches ahead of the consumer;
    exceptions from either stage propagate to the consumer in order.
    ≙ layers/io.py:556 double_buffer.

    retry_policy (resilience.RetryPolicy): bound restarts of a flaky
    reader INSIDE the decode thread — the underlying reader is re-invoked
    and fast-forwarded past delivered batches, so the consumer never sees
    a duplicate; exhaustion propagates the original error as before.
    (The Trainer installs its own wrapper upstream — don't pass a policy
    there too, or each error spends two retry budgets. Stacking is now
    DETECTED: a reader already carrying an armed resilient wrapper is
    not re-wrapped — one warning, one budget; see docs/resilience.md.)

    transform(batch, idx): applied in the upload thread AFTER device_put
    (idx = 0-based batch index of this iteration) — the data pipeline's
    device-side augmentation hook: the traced call dispatches off the
    consumer's critical path and its execution overlaps compute.

    instrument: a data.metrics.PipelineMetrics (duck-typed: span()) —
    the upload/augment stages report their busy time through it.
    cursor0 offsets the cursor= attribute their emitted trace spans
    carry, so after a pipeline resume (iter_from(n)) the upload span of
    batch n agrees with its decode/encode spans upstream.
    """
    import jax
    if retry_policy is not None:
        if getattr(reader, "_pt_resilient", False):
            # the double-retry-budget footgun (docs/resilience.md): this
            # reader is ALREADY an armed resilient wrapper — wrapping it
            # again would make every reader error spend two budgets
            # (retries_outer x retries_inner restarts). Dedupe to the
            # existing layer and say so, once, loudly.
            import warnings
            warnings.warn(
                "double_buffer(retry_policy=...) received a reader that "
                "already carries an armed resilient_reader wrapper "
                "(e.g. Trainer.train(reader_retry=...)): ignoring the "
                "double_buffer policy — stacked wrappers would multiply "
                "retry budgets. Pick one layer (docs/resilience.md).",
                stacklevel=2)
        else:
            from ..resilience.retry import resilient_reader
            reader = resilient_reader(reader, policy=retry_policy)

    def buffered():
        q_host: "queue.Queue" = queue.Queue(maxsize=capacity)
        q_dev: "queue.Queue" = queue.Queue(maxsize=capacity)
        stop = threading.Event()
        err = []

        def put(q, item) -> bool:
            return bounded_put(q, item, stop)

        def get(q):
            """Bounded get for the MIDDLE stage (the consumer's own get
            can block hard — it is the one who sets stop)."""
            while not stop.is_set():
                try:
                    return q.get(timeout=0.1)
                except queue.Empty:
                    continue
            return _STOP

        def decode_worker():
            """Stage 1: pull (and thereby decode) reader batches."""
            try:
                for batch in reader():
                    if stop.is_set():
                        return
                    if not put(q_host, batch):
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err.append(e)
            finally:
                put(q_host, _STOP)

        def upload_worker():
            """Stage 2: stage batches onto the device (then run the
            optional transform — device-side augmentation — on the
            uploaded batch). A single thread, so batch order is
            preserved end to end."""
            idx = 0
            try:
                while True:
                    item = get(q_host)
                    if item is _STOP:
                        return
                    span = (instrument.span("upload",
                                            cursor=cursor0 + idx)
                            if instrument else _null_span)
                    with span:
                        if isinstance(item, dict):
                            item = {k: jax.device_put(v)
                                    for k, v in item.items()}
                        else:
                            item = jax.device_put(item)
                    if transform is not None:
                        span = (instrument.span("augment",
                                                cursor=cursor0 + idx)
                                if instrument else _null_span)
                        with span:
                            item = transform(item, idx)
                    idx += 1
                    if not put(q_dev, item):
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err.append(e)
            finally:
                put(q_dev, _STOP)

        td = threading.Thread(target=decode_worker, daemon=True)
        tu = threading.Thread(target=upload_worker, daemon=True)
        td.start()
        tu.start()
        try:
            while True:
                item = q_dev.get()
                if item is _STOP:
                    if err:
                        raise err[0]
                    return
                yield item
        finally:
            stop.set()  # unblock + terminate both workers on early exit

    #: stacking detection (docs/resilience.md double-retry footgun):
    #: Trainer.train(reader_retry=...) checks this mark so a policy baked
    #: in here is never silently multiplied by a trainer-level budget
    buffered._pt_retry_policy = retry_policy
    return buffered


class DeviceFeeder:
    """DataFeeder + double_buffer in one: converts raw reader rows with a
    DataFeeder and keeps the uploads ahead of compute."""

    def __init__(self, feeder, reader: Callable, capacity: int = 2,
                 retry_policy=None):
        self._feeder = feeder
        self._reader = reader
        self._capacity = capacity
        self._retry_policy = retry_policy

    def __iter__(self):
        def feed_reader():
            for data in self._reader():
                # dict batches are already feed-shaped (pre-batched readers,
                # e.g. RecordIO -> native batcher); rows go through the feeder
                yield data if isinstance(data, dict) else self._feeder.feed(data)

        yield from double_buffer(feed_reader, capacity=self._capacity,
                                 retry_policy=self._retry_policy)()
