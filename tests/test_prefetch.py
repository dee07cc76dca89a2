"""Host-I/O prefetch pipeline (store/prefetch.py) and its integrations:
ordered delivery, bounded read-ahead, error/cancel hygiene (no deadlocks,
no leaked threads), serial-vs-pipelined result parity for the out-of-core
scan / FS store / bulk ingest, the scheduler-deadline drain, and the
bench smoke leg."""

import threading
import time

import numpy as np
import pytest

from geomesa_tpu.filter.ecql import parse_instant
from geomesa_tpu.store.fs import FileSystemDataStore
from geomesa_tpu.store.oocscan import StreamedDeviceScan
from geomesa_tpu.store.prefetch import (
    WORKER_PREFIX,
    PrefetchConfig,
    prefetch_map,
)

ECQL = (
    "BBOX(geom, -10, 0, 40, 45) AND "
    "dtg DURING 2020-01-05T00:00:00Z/2020-01-20T00:00:00Z"
)


def _io_threads() -> list:
    return [
        t for t in threading.enumerate() if t.name.startswith(WORKER_PREFIX)
    ]


def _assert_io_threads_gone(timeout_s: float = 5.0) -> None:
    """Prefetch workers must be joined when their pipeline ends — poll
    briefly (executor shutdown joins, but give the OS a beat)."""
    deadline = time.monotonic() + timeout_s
    while _io_threads():
        if time.monotonic() > deadline:
            raise AssertionError(f"leaked io threads: {_io_threads()}")
        time.sleep(0.01)


# -- prefetch_map core -------------------------------------------------------


@pytest.mark.parametrize("workers", [0, 1, 4])
def test_order_and_results(workers):
    """Results arrive in input order at every worker count — including
    when late items finish before early ones."""
    def fn(i):
        time.sleep(0.002 * ((7 - i) % 5))  # early items are SLOW
        return i * i

    got = list(prefetch_map(fn, range(12), PrefetchConfig(workers=workers)))
    assert got == [i * i for i in range(12)]
    _assert_io_threads_gone()


def test_serial_workers0_spawns_no_threads():
    before = threading.active_count()
    assert list(prefetch_map(lambda i: i, range(8), 0)) == list(range(8))
    assert threading.active_count() == before


_FIRST_IMPORT_CHILD = """
import sys, threading
from geomesa_tpu.store.prefetch import PrefetchConfig, prefetch_map

def read(i):
    seen = "pyarrow" in sys.modules
    import pyarrow.parquet  # noqa: F401
    return seen

seen = list(prefetch_map(read, range(4), PrefetchConfig(workers={workers})))

def write():
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({{"a": [1, 2, 3]}}), pa.BufferOutputStream())

t = threading.Thread(target=write)
t.start()
t.join()
print(all(seen))
"""


@pytest.mark.parametrize("workers", [1, 2])
def test_workers_never_import_pyarrow_first(workers):
    """In a fresh process, pyarrow is already imported when a prefetch
    worker starts: a first import on a worker thread corrupts pyarrow's
    native init, and the process's next parquet write on another thread
    segfaults (the crash a flush after a merge read used to hit)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _FIRST_IMPORT_CHILD.format(workers=workers)],
        cwd=root, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["True"]


def test_readahead_is_bounded_and_overlaps():
    """At most ``depth`` items are in flight, and with workers > 1 the
    pipeline genuinely overlaps (two fn calls concurrent at some point).
    """
    live = {"now": 0, "max": 0}
    lock = threading.Lock()

    def fn(i):
        with lock:
            live["now"] += 1
            live["max"] = max(live["max"], live["now"])
        time.sleep(0.01)
        with lock:
            live["now"] -= 1
        return i

    cfg = PrefetchConfig(workers=4, depth=3)
    consumed = 0
    for _ in prefetch_map(fn, range(12), cfg):
        consumed += 1
        time.sleep(0.002)
    assert consumed == 12
    assert live["max"] <= 3  # never more than depth in flight
    assert live["max"] >= 2  # and the overlap actually happened


def test_items_iterator_stays_on_consumer_thread():
    """The items generator is advanced only on the consuming thread (the
    documented contract that lets plain generators feed the pipeline)."""
    main = threading.current_thread()
    seen = []

    def items():
        for i in range(6):
            seen.append(threading.current_thread())
            yield i

    assert list(prefetch_map(lambda i: i, items(), 2)) == list(range(6))
    assert all(t is main for t in seen)


def test_byte_budget_throttles_but_completes():
    """A byte budget far below the stream size stalls top-up, never the
    pipeline: everything still arrives, in order."""
    cfg = PrefetchConfig(workers=4, depth=8, byte_budget=100)
    out = list(prefetch_map(
        lambda i: bytes(64), range(10), cfg, size_of=len
    ))
    assert len(out) == 10
    _assert_io_threads_gone()


def test_error_propagates_at_position_and_cleans_up():
    """An fn exception surfaces at ITS position; the pipeline then shuts
    down without deadlocking or leaking threads, and items beyond the
    read-ahead window were never started."""
    started = []

    def fn(i):
        started.append(i)
        if i == 3:
            raise RuntimeError("decode failed")
        return i

    cfg = PrefetchConfig(workers=2, depth=2)
    got = []
    with pytest.raises(RuntimeError, match="decode failed"):
        for v in prefetch_map(fn, range(100), cfg):
            got.append(v)
    assert got == [0, 1, 2]
    assert len(started) < 100  # the tail was cancelled, not run
    _assert_io_threads_gone()
    # the failed item must not leak into the in-flight gauge (regression:
    # it was popped before .result() raised, skipping its decrement)
    from geomesa_tpu.metrics import io_prefetch_depth, io_queue_bytes

    assert io_prefetch_depth.value() == 0
    assert io_queue_bytes.value() == 0


def test_close_mid_stream_cancels():
    """Closing the generator early (consumer abandons the scan) joins
    the workers and stops consuming items."""
    pulled = []

    def items():
        for i in range(1000):
            pulled.append(i)
            yield i

    gen = prefetch_map(lambda i: i, items(), PrefetchConfig(workers=2, depth=4))
    assert next(gen) == 0
    assert next(gen) == 1
    gen.close()
    _assert_io_threads_gone()
    assert len(pulled) <= 2 + 4 + 1  # consumed + read-ahead, not the stream


# -- store integration -------------------------------------------------------


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("prefetch")
    ds = FileSystemDataStore(str(tmp / "s"), partition_size=1 << 11)
    ds.create_schema(
        "t", "val:Int,tone:Float,dtg:Date,*geom:Point:srid=4326"
    )
    n = 40_000
    rng = np.random.default_rng(23)
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-02-01T00:00:00")
    ds.write("t", {
        "val": rng.integers(0, 100, n),
        "tone": rng.uniform(-10, 10, n).astype(np.float32),
        "dtg": rng.integers(t0, t1, n),
        "geom": np.stack(
            [rng.uniform(-60, 60, n), rng.uniform(-50, 50, n)], axis=1
        ),
    }, fids=np.arange(n))
    ds.flush("t")
    return ds


@pytest.mark.parametrize("workers", [1, 4])
def test_oocscan_parity_prefetched_vs_serial(store, workers):
    """count AND query parity, same hits in the SAME order, between the
    serial baseline (io=0) and the pipelined path — the byte-identical
    contract of the acceptance criteria."""
    serial = StreamedDeviceScan(store, "t", slab_rows=1 << 13, io=0)
    piped = StreamedDeviceScan(
        store, "t", slab_rows=1 << 13, io=PrefetchConfig(workers=workers)
    )
    for q in (ECQL, ECQL + " AND val < 30", "BBOX(geom, 170, 80, 171, 81)"):
        assert piped.count(q) == serial.count(q)
        got, want = piped.query(q), serial.query(q)
        assert list(map(str, got.fids)) == list(map(str, want.fids))
        np.testing.assert_array_equal(
            got.column("val"), want.column("val")
        )
    _assert_io_threads_gone()


def test_oocscan_pairs_alignment_regression(store):
    """Regression for the old ``groups.pop(0)`` side channel: every
    (host_cols, source_batch) pair the pipeline yields must be
    self-consistent — the staged planes ARE the staging of that exact
    batch — even when pairs are materialized out of lockstep with the
    consumer (the prefetcher runs chunks ahead). Under the old implicit
    chunk<->batch pairing, consuming the chunk stream ahead of the
    gather desynced the two lists; explicit tuples make that skew
    structurally impossible."""
    from geomesa_tpu.ops.scan import stage_columns_host

    scan = StreamedDeviceScan(
        store, "t", slab_rows=1 << 12, io=PrefetchConfig(workers=4)
    )
    plan, parts = scan._parts(ECQL)
    names = plan.compiled.device_cols
    pairs = list(scan._pairs(parts, names))  # materialize ALL ahead
    assert len(pairs) > 3  # multi-chunk stream or the test proves nothing
    for cols, batch in pairs:
        want = stage_columns_host(batch, names)
        assert set(cols) == set(want)
        for k in names:
            assert len(cols[k]) == len(batch)
            np.testing.assert_array_equal(cols[k], want[k])


def test_oocscan_under_exclusive_lock_degrades_to_serial(store):
    """A scan issued by a thread HOLDING the store's exclusive lock (an
    in-place maintenance job) must degrade to in-line serial reads:
    worker threads could neither see the holder's re-entrant lock depth
    nor take a shared flock against our own exclusive one — without the
    guard this deadlocks, then dies with LockTimeout."""
    want = StreamedDeviceScan(store, "t", slab_rows=1 << 13).count(ECQL)
    scan = StreamedDeviceScan(
        store, "t", slab_rows=1 << 13, io=PrefetchConfig(workers=4)
    )
    with store._exclusive():
        assert scan.count(ECQL) == want


def test_query_partitions_under_exclusive_lock_degrades(store):
    """Iterating query_partitions from a thread holding the store's
    exclusive lock worked serially pre-pipeline (the re-entrant lock
    depth short-circuits _shared); with workers it must DEGRADE to that
    serial path rather than deadlock workers on the consumer-held
    _mem_lock."""
    try:
        store.io = PrefetchConfig(workers=4)
        want = sum(len(b) for b in store.query_partitions("t", ECQL))
        with store._exclusive():
            got = sum(len(b) for b in store.query_partitions("t", ECQL))
    finally:
        store.io = None
    assert got == want > 0


def test_oocscan_stream_cache_lru_bounded(store):
    """Satellite: the compiled-stream cache must not grow without bound
    across many distinct filters — and eviction must not break results."""
    scan = StreamedDeviceScan(store, "t", slab_rows=1 << 13)
    cap = StreamedDeviceScan.STREAM_CACHE_MAX
    counts = {}
    for i in range(cap + 5):
        q = f"BBOX(geom, {-10 - i}, 0, 40, 45)"
        counts[q] = scan.count(q)
        assert len(scan._streams) <= cap
    # the oldest filters were evicted; re-querying them still answers
    # exactly (a fresh stream is compiled on demand)
    for q, want in list(counts.items())[:3]:
        assert scan.count(q) == want


def test_oocscan_decode_error_no_deadlock_no_leak(store, monkeypatch):
    """A decode error mid-stream must surface as the scan's exception —
    not hang the bounded queue — and must leave no worker threads
    behind; the store then serves the next scan normally."""
    real = FileSystemDataStore._read_part_table
    calls = {"n": 0}

    def flaky(self, type_name, p):
        calls["n"] += 1
        if calls["n"] == 3:
            raise ValueError("corrupt partition file")
        return real(self, type_name, p)

    store._types["t"].cache = {}  # cached partitions would skip the read
    monkeypatch.setattr(FileSystemDataStore, "_read_part_table", flaky)
    scan = StreamedDeviceScan(
        store, "t", slab_rows=1 << 12, io=PrefetchConfig(workers=4)
    )
    with pytest.raises(ValueError, match="corrupt partition"):
        scan.count(ECQL)
    _assert_io_threads_gone()
    monkeypatch.undo()
    # the failed scan released the store lock: fresh scans still answer
    want = len(store.query("t", ECQL).batch)
    assert StreamedDeviceScan(store, "t", slab_rows=1 << 12).count(ECQL) == want


def test_scheduler_deadline_drains_inflight_prefetch(store, monkeypatch):
    """The scheduler's deadline path (HTTP 504 in the server) while a
    prefetch is in flight: the single device worker is busy with an
    oocscan whose pipeline is mid-read-ahead, a second request expires
    in the queue (-> DeadlineExpired to its waiter, the 504), and the
    in-flight pipeline still runs to completion, answers exactly, and
    winds down without leaking a thread."""
    from geomesa_tpu.sched import DeadlineExpired, QueryScheduler, SchedConfig

    real = FileSystemDataStore._read_part_table
    started = threading.Event()

    def slow(self, type_name, p):
        started.set()
        time.sleep(0.02)  # keep the prefetch in flight past the deadline
        return real(self, type_name, p)

    scan = StreamedDeviceScan(
        store, "t", slab_rows=1 << 12, io=PrefetchConfig(workers=2)
    )
    want = len(store.query("t", ECQL).batch)  # BEFORE the slow patch
    # drop pinned partitions so the scheduled scan actually hits the
    # (slowed) read path — cached reads would finish inside the deadline
    store._types["t"].cache = {}
    monkeypatch.setattr(FileSystemDataStore, "_read_part_table", slow)
    with QueryScheduler(SchedConfig(max_inflight=1)) as sched:
        inflight = sched.submit(fn=lambda: scan.count(ECQL))
        assert started.wait(timeout=10.0)  # its prefetch is running NOW
        expired = sched.submit(
            fn=lambda: scan.count(ECQL), deadline_ms=30.0
        )
        with pytest.raises(DeadlineExpired):
            sched.wait(expired)  # the 504: expired while queued
        # ...and the in-flight scan's pipeline drains to the exact count
        assert sched.wait(inflight) == want
    _assert_io_threads_gone()
    monkeypatch.undo()
    assert StreamedDeviceScan(store, "t", slab_rows=1 << 12).count(ECQL) == want


def test_fs_query_parity_across_io_workers(store):
    """The FS store's own scan (plan + per-partition read + merge) is
    byte-identical with the pipeline on and off."""
    try:
        store.io = 0
        base = store.query("t", ECQL)
        store.io = PrefetchConfig(workers=4)
        res = store.query("t", ECQL)
    finally:
        store.io = None
    assert list(map(str, res.batch.fids)) == list(map(str, base.batch.fids))
    assert res.scanned == base.scanned


def test_fs_read_all_merge_parity(tmp_path):
    """Flush-merge (_read_all rides the pipeline under the exclusive
    lock): a second write merges with partitions read in parallel, and
    the merged dataset is exactly the union."""
    ds = FileSystemDataStore(
        str(tmp_path / "s"), partition_size=1 << 8,
        io=PrefetchConfig(workers=4),
    )
    ds.create_schema("t", "val:Int,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(5)
    t0 = parse_instant("2020-01-01T00:00:00")

    def rows(n, base):
        return {
            "val": np.arange(base, base + n),
            "dtg": rng.integers(t0, t0 + 10_000_000, n),
            "geom": np.stack(
                [rng.uniform(-60, 60, n), rng.uniform(-50, 50, n)], axis=1
            ),
        }

    ds.write("t", rows(3000, 0), fids=np.arange(3000))
    ds.flush("t")
    ds.write("t", rows(2000, 3000), fids=np.arange(3000, 5000))
    ds.flush("t")  # merge path: _read_all over ~12 partitions
    got = ds.query("t", "INCLUDE").batch
    assert sorted(int(v) for v in got.column("val")) == list(range(5000))


def test_parallel_ingest_pipelined_deterministic_and_error_isolated(tmp_path):
    """Bulk ingest through the pipeline: file order in = write order in
    (deterministic replay), a bad file is reported without killing the
    run, and worker counts do not change the stored result."""
    from geomesa_tpu.jobs import parallel_ingest

    conv = {"type": "delimited-text", "format": "csv", "fields": [
        {"name": "val", "transform": "$1::int"},
        {"name": "geom", "transform": "point($2::double, $3::double)"},
    ]}
    files = []
    for i in range(6):
        p = tmp_path / f"in-{i}.csv"
        p.write_text("".join(
            f"{i * 10 + j},{float(i)},{float(j)}\n" for j in range(10)
        ))
        files.append(str(p))
    bad = tmp_path / "missing.csv"  # never created -> open() fails
    files.insert(3, str(bad))

    def run(root, workers):
        ds = FileSystemDataStore(str(tmp_path / root), partition_size=1 << 10)
        ds.create_schema("t", "val:Int,*geom:Point:srid=4326")
        rep = parallel_ingest(ds, "t", conv, files, workers=workers)
        vals = [int(v) for v in ds.query("t", "INCLUDE").batch.column("val")]
        return rep, vals

    rep4, vals4 = run("w4", 4)
    rep0, vals0 = run("w0", 0)
    assert rep4.success == rep0.success == 60
    assert [e[0] for e in rep4.errors] == [str(bad)]
    assert [e[0] for e in rep0.errors] == [str(bad)]
    assert sorted(vals4) == sorted(vals0) == list(range(60))
    assert vals4 == vals0  # write order identical at every worker count
    _assert_io_threads_gone()


def test_io_metrics_exported():
    """The geomesa_io_* series ride the registry (ops dashboards key on
    the names)."""
    from geomesa_tpu.metrics import REGISTRY

    text = REGISTRY.prometheus_text()
    for name in (
        "geomesa_io_read_seconds",
        "geomesa_io_decode_seconds",
        "geomesa_io_stage_seconds",
        "geomesa_io_prefetch_depth",
        "geomesa_io_queue_bytes",
        "geomesa_io_chunks_total",
    ):
        assert name in text


# -- bench smoke leg (CI guard) ---------------------------------------------


def _bench_args(**kw):
    import argparse

    ns = argparse.Namespace(
        n=None, check=False, smoke=True, io_workers=0, iters=3
    )
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


def test_bench_oocscan_smoke_leg():
    """The fast CI leg: store-integrated serial vs pipelined sustained
    MB/s with the regression guard, at a size tier-1 can afford."""
    bench = pytest.importorskip("bench")
    out = bench._bench_oocscan_store(_bench_args(n=1 << 15), smoke=True)
    assert out["oocscan_smoke"] is True
    assert out["oocscan_serial_mbps"] > 0
    assert out["oocscan_pipelined_mbps"] > 0
    # serial and pipelined counted the same hits (asserted inside too)
    assert out["oocscan_store_hits"] >= 0


@pytest.mark.slow
def test_bench_oocscan_full_leg():
    """The full leg (device pump + big store leg) — slow by design; the
    driver's bench run records it, tier-1 skips it."""
    bench = pytest.importorskip("bench")
    out = bench.bench_oocscan(_bench_args(smoke=False, n=1 << 20))
    assert out["oocscan_sustained_mbps"] > 0
    assert out["oocscan_pipelined_mbps"] > 0
