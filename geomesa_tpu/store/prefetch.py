"""Pipelined host I/O: bounded read-ahead over partition files.

Ref role: Accumulo tablet servers stream ranges to a scan in PARALLEL
(BatchScanner readahead threads); the rebuild's out-of-core scan, FS
staging and bulk ingest paths were reading, decoding and staging
partitions SERIALLY on the consumer thread, so the device slab pump (and
the disk) sat idle behind host decode — BENCH_r05 measured the streamed
scan at 12 MB/s sustained with the device side double-buffered.

This module is the shared host-side half of that overlap: an ordered,
bounded, threaded map. ``prefetch_map(fn, items)`` runs ``fn`` on worker
threads with a bounded number of items in flight and yields the results
IN INPUT ORDER, so host work on item i+k (file read, Arrow decode,
``stage_columns_host``) overlaps both the disk and whatever the consumer
does with item i (typically a device kernel). The heavy per-item work —
pyarrow reads/decompression, numpy copies/astype — releases the GIL, so
worker threads scale on multi-core hosts; on a single core the pipeline
still overlaps the consumer's device dispatches with the next read.

Memory bound: at most ``depth`` results exist at once (completed results
waiting in the queue additionally respect ``byte_budget`` — topping up
stops while completed-but-unconsumed results exceed it, so peak host
memory is roughly ``byte_budget`` + ``workers`` x one item). Ordered
delivery means a slow head item back-pressures the whole pipeline rather
than reordering results — deterministic output is the contract every
caller (scan parity, ingest replay) relies on.

Failure discipline: an ``fn`` exception surfaces to the consumer at that
item's position in the stream; the executor is then drained and shut
down (queued items cancelled, running ones finish and are discarded), so
a decode error mid-stream can neither deadlock the queue nor leak
threads. Closing the generator early (consumer abandons the scan — e.g.
a query deadline expired) runs the same cleanup. TRANSIENT read errors
(OSError — flaky NFS, the ``fail.read.io`` failpoint) are retried on the
worker with bounded exponential backoff BEFORE surfacing (``io.retries``
x ``io.backoff.ms``, doubling; ``geomesa_store_read_retries_total``
counts them); FileNotFoundError and domain failures (e.g. a checksum
quarantine) stay immediate and loud.

Knobs resolve from the ``io.*`` system properties (``io.workers``,
``io.readahead``, ``io.queue.bytes`` — see :mod:`geomesa_tpu.conf`) when
no explicit :class:`PrefetchConfig` is given; ``workers=0`` disables the
threads entirely (the serial baseline, and the right setting for
spinning disks or tiny partitions where thread handoff costs more than
the overlap wins). Observability: ``geomesa_io_*`` metrics (read/decode/
stage seconds observed by the callers, prefetch depth, queue bytes,
chunk counter) ride :mod:`geomesa_tpu.metrics`.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass

from geomesa_tpu.locking import checked_lock

__all__ = ["PrefetchConfig", "prefetch_map", "batch_nbytes"]

#: thread-name prefix for every prefetch worker (tests assert cleanup)
WORKER_PREFIX = "geomesa-io"


@dataclass(frozen=True)
class PrefetchConfig:
    """Host-I/O pipeline knobs.

    ``workers`` is the decode thread count (0 = serial, no threads);
    ``depth`` bounds items in flight (submitted but not yet consumed;
    0 = auto, ``2 * workers``); ``byte_budget`` bounds the bytes of
    COMPLETED results waiting for the consumer (0 = unbounded) — the
    queue-occupancy half of the memory bound documented above."""

    workers: int = 4
    depth: int = 0
    byte_budget: int = 256 << 20

    @property
    def effective_depth(self) -> int:
        return self.depth if self.depth > 0 else max(2 * self.workers, 2)

    @staticmethod
    def from_props() -> "PrefetchConfig":
        from geomesa_tpu.conf import sys_prop

        return PrefetchConfig(
            workers=int(sys_prop("io.workers")),
            depth=int(sys_prop("io.readahead")),
            byte_budget=int(sys_prop("io.queue.bytes")),
        )

    @staticmethod
    def coerce(io) -> "PrefetchConfig":
        """None -> the ``io.*`` system properties (resolved NOW, so a
        test's ``prop_override`` takes effect per call); an int -> that
        worker count with defaults; a config passes through."""
        if io is None:
            return PrefetchConfig.from_props()
        if isinstance(io, PrefetchConfig):
            return io
        if isinstance(io, int):
            return PrefetchConfig(workers=io)
        raise TypeError(
            f"io must be a PrefetchConfig, int worker count or None, "
            f"not {type(io).__name__}"
        )


def batch_nbytes(batch) -> int:
    """Rough host bytes of a FeatureBatch (numpy columns only; object
    columns count pointer width — good enough for a queue budget)."""
    try:
        return int(
            sum(int(v.nbytes) for v in batch.columns.values())
            + int(batch.fids.nbytes)
        )
    except Exception:  # lint: disable=GT011(queue-budget sizing heuristic: an unsizable batch counts as 0 and the budget stays conservative elsewhere)
        return 0


def _with_retries(fn):
    """Transient-read resilience for the pipeline workers: retry ``fn``
    on OSError with bounded, JITTERED exponential backoff —
    ``io.retries`` extra attempts, ``io.backoff.ms`` base doubling per
    attempt scaled 0.5-1.5x (a fleet of workers hitting the same
    flapping disk de-correlates), the CUMULATIVE sleep capped by
    ``io.backoff.cap.ms`` so a flapping disk can never stall a worker
    for unbounded wall-clock (once the budget is spent the next error
    surfaces immediately). Reads are idempotent, so re-running the
    whole work item is safe. NOT retried: FileNotFoundError (a real
    state — e.g. another writer GC'd the generation mid-scan, which a
    refresh must resolve, not a sleep) and non-OSError domain failures
    (checksum quarantines stay loud)."""
    from geomesa_tpu.conf import sys_prop

    retries = int(sys_prop("io.retries"))
    if retries <= 0:
        return fn

    def call(item):
        import time as _time

        from geomesa_tpu import metrics
        from geomesa_tpu.resilience import backoff_sleeps

        # per-item budget, resolved per call so prop_override applies
        sleeps = backoff_sleeps(
            retries,
            float(sys_prop("io.backoff.ms")),
            float(sys_prop("io.backoff.cap.ms")),
        )
        while True:
            try:
                return fn(item)
            except FileNotFoundError:
                raise
            except OSError:
                delay = next(sleeps, None)
                if delay is None:
                    raise  # retries/budget exhausted: surface the error
                metrics.store_read_retries.inc()
                _time.sleep(delay)

    return call


def prefetch_map(fn, items, config=None, size_of=None):
    """Ordered pipelined map: ``fn(item)`` runs on worker threads with
    bounded read-ahead; results yield in input order (see the module
    docstring for the memory bound and failure discipline). Transient
    OSErrors from ``fn`` are retried per the ``io.retries`` /
    ``io.backoff.ms`` properties (see :func:`_with_retries`).

    ``items`` is only ever advanced on the consumer thread, so plain
    generators are fine as input. ``size_of(result)`` opts results into
    the byte budget. With ``workers <= 0`` this is exactly
    ``map(fn, items)`` — no threads, the serial baseline (retries still
    apply)."""
    cfg = PrefetchConfig.coerce(config)
    fn = _with_retries(fn)
    if cfg.workers <= 0:
        for item in items:
            yield fn(item)
        return
    yield from _prefetch_threads(fn, items, cfg, size_of)


def _prefetch_threads(fn, items, cfg: PrefetchConfig, size_of):
    from geomesa_tpu import metrics
    from geomesa_tpu.pyarrow_compat import preload_pyarrow
    from geomesa_tpu.spawn import ContextPool

    it = iter(items)
    depth = cfg.effective_depth
    budget = cfg.byte_budget
    lock = checked_lock("prefetch.queued")
    queued = {"bytes": 0}  # completed-but-unconsumed result bytes

    def run(item):
        # request context (trace spans, cost collector, degradation,
        # compile scope) crosses the pool via the blessed ContextPool:
        # contextvars are per-thread, so without the submit-time
        # capture/attach the workers' read/decode/stage spans would
        # silently vanish from the request's trace and bytes read on a
        # worker would charge nobody (tracing.py module docstring)
        out = fn(item)
        b = 0
        if size_of is not None and budget:
            try:
                b = int(size_of(out))
            except Exception:  # lint: disable=GT011(queue-budget sizing heuristic: an unsizable item is uncounted, the pipeline result is untouched)
                b = 0
            with lock:
                queued["bytes"] += b
            if b:
                metrics.io_queue_bytes.inc(b)
        return out, b

    pending: deque = deque()
    # the workers read and decode partition files: pyarrow's FIRST import
    # must happen on this (spawning) thread, or a later parquet write in
    # this process segfaults (pyarrow_compat contract)
    preload_pyarrow()
    ex = ContextPool(cfg.workers, thread_name_prefix=WORKER_PREFIX)
    # gauges are updated by DELTA (inc/dec), never set: several
    # pipelines commonly run at once (concurrent queries on a threaded
    # server) and each must contribute only its own share
    try:
        exhausted = False
        while True:
            while not exhausted and len(pending) < depth:
                if budget and pending and queued["bytes"] >= budget:
                    # queue over budget: stop topping up, but always keep
                    # >= 1 item in flight so the pipeline cannot stall
                    break
                try:
                    item = next(it)
                except StopIteration:
                    exhausted = True
                    break
                pending.append(ex.submit(run, item))
                metrics.io_prefetch_depth.inc()
            if not pending:
                break
            # resolve BEFORE popping: if fn raised, the future stays in
            # `pending` so the finally's gauge retraction still counts it
            out, b = pending[0].result()
            pending.popleft()
            metrics.io_prefetch_depth.dec()
            if b:
                with lock:
                    queued["bytes"] -= b
                metrics.io_queue_bytes.dec(b)
            metrics.io_chunks.inc()
            yield out
    finally:
        # error or early close: cancel what never started, let running
        # items finish (fn may hold external resources mid-call), and
        # join the workers — nothing leaks past this frame
        for f in pending:
            f.cancel()
        ex.shutdown(wait=True, cancel_futures=True)
        # after the join, retract this pipeline's leftover contribution
        # (unconsumed completed items and their accounted bytes)
        metrics.io_prefetch_depth.dec(len(pending))
        metrics.io_queue_bytes.dec(queued["bytes"])
        queued["bytes"] = 0
