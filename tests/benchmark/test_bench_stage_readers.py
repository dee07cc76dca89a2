"""The readers of the program's stage counters and annotations
(``benchmark/stage_counters.py``, ``metrics/<name>.py``): on a Ctx built
from two real ``/metrics`` scrapes around resident requests on the CPU,
on an empty one (a program without the counters), and on traces."""

from __future__ import annotations

import os
import sys
import time
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, run, trace_reduce  # noqa: E402

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiles_trimmed.xplane.pb")

#: the stage-counter readers, and what each reads with no series
COUNTER_READERS = {
    "request_ms": None,
    "encode_ms": None,
    "execute_ms": None,
    "execute_cpu_ms": None,
    "fetch_ms": None,
    "stage_decode_s": None,
    "compile_s": 0.0,
}


class _Ctx:
    window_s = 1.0


@pytest.fixture(scope="module")
def scraped(tmp_path_factory):
    """Ctx around one resident /count and one /density of a scheduled
    server over a file store, staged before the first scrape."""
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.fs import FileSystemDataStore

    ds = FileSystemDataStore(str(tmp_path_factory.mktemp("store")),
                             partition_size=1024)
    ds.create_schema("ev", "name:String,dtg:Date,*geom:Point:srid=4326")
    n = 4000
    rng = np.random.default_rng(31)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("ev", {
        "name": rng.choice(["a", "b", "c"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack([rng.uniform(-20, 20, n),
                          rng.uniform(-20, 20, n)], axis=1),
    }, fids=np.arange(n))
    ds.flush("ev")
    server, _ = serve_background(ds, resident=True, sched=True)
    base = "http://%s:%d" % server.server_address[:2]
    try:
        run.http_get(base, "/count/ev?cql=INCLUDE")  # stages the type
        ctx = _Ctx()
        ctx.prom_before = run.prom(base)
        cql = urllib.request.quote(
            "BBOX(geom, -5, -5, 5, 5) AND "
            "dtg DURING 2020-01-01T00:00:00Z/2020-01-01T20:00:00Z")
        run.http_get(base, f"/count/ev?cql={cql}")
        run.http_get(base, "/density/ev?bbox=-5,-5,5,5&width=16&height=8"
                     f"&cql={cql}")
        key = 'geomesa_stage_seconds_count{stage="http.request"}'
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            ctx.prom_after = run.prom(base)
            if ctx.prom_after.get(key, 0) - ctx.prom_before.get(key, 0) >= 2:
                break
            time.sleep(0.01)
    finally:
        server.shutdown()
    return ctx


@pytest.mark.parametrize("metric", sorted(COUNTER_READERS))
def test_counter_reader(scraped, metric):
    read = cells.load_reader(metric)
    v = read(scraped)
    assert v is not None and v >= 0.0 and np.isfinite(v)
    if metric in ("request_ms", "execute_ms", "stage_decode_s"):
        assert v > 0.0
    empty = _Ctx()
    empty.prom_before, empty.prom_after = {}, {}
    assert read(empty) == COUNTER_READERS[metric]


def test_counter_readings_hold_together(scraped):
    got = {m: cells.load_reader(m)(scraped) for m in COUNTER_READERS}
    assert got["execute_cpu_ms"] <= got["execute_ms"]
    assert got["encode_ms"] < got["request_ms"]
    assert got["fetch_ms"] < got["execute_ms"]


def _synthetic(ops, host):
    red = trace_reduce.Reduction.__new__(trace_reduce.Reduction)
    dev = trace_reduce.Device.__new__(trace_reduce.Device)
    dev.ops = [(a, b, "jit_count_fn/%count_fn.1", i)
               for i, (a, b) in enumerate(ops)]
    red.devices = {"/device:TPU:0": dev}
    red.host_start = np.array([h[0] for h in host], np.int64)
    red.host_end = np.array([h[1] for h in host], np.int64)
    red.host_name = [h[2] for h in host]
    return red


def test_idle_in_execute_from_one_annotated_gap():
    ms = 1_000_000
    ctx = _Ctx()  # a 1 s window: busy 0-200 ms and 600-700 ms
    ctx.trace = _synthetic(
        [(0, 200 * ms), (600 * ms, 700 * ms)],
        [(100 * ms, 500 * ms, "sched.execute"),   # idle 200-500 inside
         (150 * ms, 450 * ms, "sched.execute"),   # nested: counted once
         (0, 1000 * ms, "scheduler.py:602 _execute")],
    )
    read = cells.load_reader("idle_in_execute_pct")
    # 300 ms of the 700 ms idle lie under an open execution
    assert read(ctx) == pytest.approx(100.0 * 300 / 700)
    ctx.trace = _synthetic([(0, 200 * ms)],
                           [(0, 1000 * ms, "scheduler.py:602 _execute")])
    assert read(ctx) is None


def test_idle_in_execute_none_without_annotations():
    ctx = _Ctx()
    ctx.window_s = 0.25
    ctx.trace = trace_reduce.Reduction(TRACE)
    assert ctx.trace.devices and ctx.trace.host_name
    assert cells.load_reader("idle_in_execute_pct")(ctx) is None
    ctx.trace = None
    assert cells.load_reader("idle_in_execute_pct")(ctx) is None
