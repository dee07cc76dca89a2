"""Elastic restart: device state is a cache; durable storage is truth.

SURVEY section 5 failure-elastic story: the
design claims a process can die and be rebuilt from Parquet + partition
manifest (persisted layer) + durable log replay (recent live writes).
This proves it end-to-end: build a DeviceIndex over an FS store plus a
live layer backed by a FileFeatureLog, record query results, throw every
object away, reopen from disk alone, and require identical results.
"""

import numpy as np
import pytest

from geomesa_tpu.device_cache import DeviceIndex
from geomesa_tpu.store.fs import FileSystemDataStore
from geomesa_tpu.stream.live import LiveFeatureStore
from geomesa_tpu.stream.log import FileFeatureLog

SPEC = "name:String,count:Int,dtg:Date,*geom:Point:srid=4326"
QUERIES = [
    "BBOX(geom, -5, 42, 8, 51) AND dtg DURING 2020-01-05T00:00:00Z/2020-02-20T00:00:00Z",
    "BBOX(geom, -120, 20, -60, 55) AND count > 40",
    "name = 'alpha'",
]


def _cols(rng, n, t0=1_578_000_000_000, t1=1_580_000_000_000):
    return {
        "name": rng.choice(["alpha", "beta", "gamma"], n),
        "count": rng.integers(0, 100, n),
        "dtg": rng.integers(t0, t1, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }


def _combined_fids(store, live, query):
    """Query the persisted layer (via its DeviceIndex cache) and the live
    layer; live wins per fid (the lambda-merge view)."""
    di = DeviceIndex(store, "ev")
    persisted = set(di.query(query).fids.tolist())
    live_hits = set(live.query(query).fids.tolist())
    live_all = set(live.snapshot().fids.tolist())
    # live supersedes: any fid present in the live layer is answered there
    return (persisted - live_all) | live_hits


def test_restart_from_parquet_manifest_and_log_replay(tmp_path):
    rng = np.random.default_rng(21)
    data_dir = tmp_path / "fsstore"
    log_path = tmp_path / "live.log"

    # ---- original process: durable writes + recent live writes ----------
    store = FileSystemDataStore(str(data_dir), partition_size=2048)
    sft = store.create_schema("ev", SPEC)
    store.write("ev", _cols(rng, 10_000), fids=np.arange(10_000))
    store.flush("ev")

    live = LiveFeatureStore(sft, log=FileFeatureLog(str(log_path), sft))
    # recent writes: some brand-new fids, some overwriting persisted ones
    live.put(_cols(rng, 500), fids=np.arange(10_000, 10_500))
    live.put(_cols(rng, 200), fids=np.arange(200))  # upserts
    # delete fids that ARE in the live layer, so remove-replay is exercised
    live.remove(np.arange(10_480, 10_500))

    before = {q: _combined_fids(store, live, q) for q in QUERIES}
    assert any(len(v) for v in before.values())
    n_live_before = len(live)

    # ---- crash: every in-memory object is gone --------------------------
    live.log.close()
    del store, live

    # ---- fresh process: reopen from disk alone --------------------------
    store2 = FileSystemDataStore(str(data_dir), partition_size=2048)
    assert "ev" in store2.type_names  # manifest + metadata reopened
    sft2 = store2.get_schema("ev")
    live2 = LiveFeatureStore(sft2, log=FileFeatureLog(str(log_path), sft2))
    assert len(live2) == n_live_before  # log replay rebuilt the cache

    after = {q: _combined_fids(store2, live2, q) for q in QUERIES}
    assert after == before

    # the rebuilt device cache serves counts identical to a fresh scan
    di = DeviceIndex(store2, "ev")
    for q in QUERIES:
        assert di.count(q) == len(di.query(q))


def test_restart_survives_torn_log_tail(tmp_path):
    """A crash mid-append leaves a torn record; reopen must drop ONLY the
    torn tail and keep every complete record."""
    rng = np.random.default_rng(3)
    log_path = tmp_path / "live.log"
    from geomesa_tpu.features.sft import SimpleFeatureType

    sft = SimpleFeatureType.create("ev", SPEC)
    live = LiveFeatureStore(sft, log=FileFeatureLog(str(log_path), sft))
    live.put(_cols(rng, 100), fids=np.arange(100))
    live.put(_cols(rng, 50), fids=np.arange(100, 150))
    live.log.close()

    with open(log_path, "ab") as fh:
        fh.write(b"\x90\x01\x00\x00partial-record-torn")  # torn tail

    live2 = LiveFeatureStore(sft, log=FileFeatureLog(str(log_path), sft))
    assert len(live2) == 150
    np.testing.assert_array_equal(
        np.sort(live2.snapshot().fids.astype(np.int64)), np.arange(150)
    )
