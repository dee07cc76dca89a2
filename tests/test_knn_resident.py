"""One-dispatch resident kNN: DeviceIndex.knn
is a single fused distance + mask + lax.top_k dispatch; it must match the
expanding-window store search (ref KNNQuery, SURVEY section 2.4
[UNVERIFIED - empty reference mount]) on results, tie rules, radius caps,
filters, auths and eviction.
"""

from __future__ import annotations

import numpy as np
import pytest

from geomesa_tpu.device_cache import DeviceIndex, StreamingDeviceIndex
from geomesa_tpu.process.knn import _dist_deg, knn
from geomesa_tpu.store.memory import MemoryDataStore

T0 = 1_577_836_800_000


def _store(n=4000, seed=3, lon=(-180, 180), lat=(-90, 90)):
    rng = np.random.default_rng(seed)
    ds = MemoryDataStore()
    ds.create_schema("ais", "val:Int,dtg:Date,*geom:Point:srid=4326")
    ds.write("ais", {
        "val": rng.integers(0, 100, n),
        "dtg": rng.integers(T0, T0 + 30 * 86_400_000, n),
        "geom": np.stack(
            [rng.uniform(*lon, n), rng.uniform(*lat, n)], axis=1
        ).astype(np.float32),
    })
    return ds


def _oracle(ds, px, py, k, pred=None, max_r=45.0):
    """Host float32-coordinate oracle with the same metric and caps."""
    batch = ds.query("ais").batch
    x, y = batch.point_coords("geom")
    x = x.astype(np.float32)
    y = y.astype(np.float32)
    keep = (np.abs(x - np.float32(px)) <= max_r) & (
        np.abs(y - np.float32(py)) <= max_r
    )
    if pred is not None:
        keep &= pred(batch)
    d = _dist_deg(x, y, np.float32(px), np.float32(py))
    idx = np.nonzero(keep)[0]
    order = idx[np.argsort(d[idx], kind="stable")[:k]]
    return batch.fids[order], d[order]


def test_one_dispatch_matches_oracle():
    ds = _store()
    di = DeviceIndex(ds, "ais")
    batch, dists = di.knn(2.0, 48.0, 50)
    fids, want = _oracle(ds, 2.0, 48.0, 50)
    np.testing.assert_array_equal(batch.fids, fids)
    np.testing.assert_allclose(dists, want, rtol=1e-5)


def test_process_routes_to_resident_one_dispatch(monkeypatch):
    """knn(..., device_index=) must answer via DeviceIndex.knn (one
    dispatch), never the probing loop."""
    ds = _store()
    di = DeviceIndex(ds, "ais")
    calls = []
    orig = DeviceIndex.knn

    def spy(self, *a, **kw):
        calls.append(a)
        return orig(self, *a, **kw)

    monkeypatch.setattr(DeviceIndex, "knn", spy)
    monkeypatch.setattr(
        DeviceIndex, "bbox_window_query",
        lambda *a, **k: pytest.fail("expanding window probed"),
    )
    batch, d = knn(ds, "ais", 2.0, 48.0, k=10, device_index=di)
    assert len(calls) == 1 and len(batch) == 10


def test_tie_at_kth_distance_prefers_earlier_row():
    """Exact duplicate points at the k-th distance: top_k must keep the
    earlier row, the host stable-argsort rule."""
    ds = MemoryDataStore()
    ds.create_schema("ais", "val:Int,dtg:Date,*geom:Point:srid=4326")
    # rows 0,1 at the target; rows 2,3,4 identical at distance 1.0
    pts = np.array([
        [0.0, 0.0], [0.1, 0.0], [1.0, 0.0], [1.0, 0.0], [1.0, 0.0],
    ], np.float32)
    ds.write("ais", {
        "val": np.arange(5), "dtg": np.full(5, T0), "geom": pts,
    })
    di = DeviceIndex(ds, "ais")
    batch, d = di.knn(0.0, 0.0, 3)
    assert list(batch.column("val")) == [0, 1, 2]  # row 2 wins the tie
    batch4, _ = di.knn(0.0, 0.0, 4)
    assert list(batch4.column("val")) == [0, 1, 2, 3]


def test_k_exceeding_rows_returns_all():
    ds = _store(n=7)
    di = DeviceIndex(ds, "ais")
    # radius cap wider than the globe: every row is a candidate
    batch, d = di.knn(0.0, 0.0, 100, max_radius_deg=360.0)
    assert len(batch) == 7
    assert np.all(np.diff(d) >= 0)


def test_max_radius_box_excludes_far_rows():
    ds = _store(n=500, seed=5)
    di = DeviceIndex(ds, "ais")
    batch, d = di.knn(0.0, 0.0, 500, max_radius_deg=5.0)
    x, y = batch.point_coords("geom")
    assert len(batch) < 500
    assert np.all(np.abs(x) <= 5.0) and np.all(np.abs(y) <= 5.0)
    fids, _ = _oracle(ds, 0.0, 0.0, 500, max_r=5.0)
    np.testing.assert_array_equal(batch.fids, fids)


def test_base_filter_applies_on_device():
    ds = _store()
    di = DeviceIndex(ds, "ais")
    batch, d = di.knn(10.0, 20.0, 25, query="val < 50")
    assert len(batch) == 25 and np.all(batch.column("val") < 50)
    fids, _ = _oracle(
        ds, 10.0, 20.0, 25, pred=lambda b: b.column("val") < 50
    )
    np.testing.assert_array_equal(batch.fids, fids)
    # and through the process surface
    b2, _ = knn(ds, "ais", 10.0, 20.0, k=25, base_filter="val < 50",
                device_index=di)
    np.testing.assert_array_equal(b2.fids, fids)


def test_host_residual_filter_falls_back_to_windows():
    """A filter with host-side residuals cannot fuse: DeviceIndex.knn
    returns None and the process path still answers via windows."""
    ds2 = MemoryDataStore()
    ds2.create_schema("ais", "name:String,dtg:Date,*geom:Point:srid=4326")
    n = 200
    rng = np.random.default_rng(0)
    ds2.write("ais", {
        "name": np.array(["ship-%d" % i for i in range(n)], object),
        "dtg": np.full(n, T0),
        "geom": np.stack(
            [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
        ),
    })
    di2 = DeviceIndex(ds2, "ais")
    assert di2.knn(0.0, 0.0, 5, query="name LIKE 'ship-1%'") is None
    batch, _ = knn(ds2, "ais", 0.0, 0.0, k=5,
                   base_filter="name LIKE 'ship-1%'", device_index=di2)
    assert len(batch) == 5
    assert all(str(v).startswith("ship-1") for v in batch.column("name"))


def test_auths_fail_closed_on_resident_knn():
    from geomesa_tpu.features.batch import FeatureBatch

    ds = MemoryDataStore()
    ds.create_schema("ais", "val:Int,dtg:Date,*geom:Point:srid=4326")
    n = 300
    rng = np.random.default_rng(1)
    vis = np.array([None, "secret"], object)[rng.integers(0, 2, n)]
    batch = FeatureBatch.from_columns(
        ds.get_schema("ais"),
        {
            "val": rng.integers(0, 9, n),
            "dtg": np.full(n, T0),
            "geom": np.stack(
                [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
            ),
        },
        fids=np.arange(n),
    ).with_visibility(vis)
    ds.write("ais", batch)
    di = DeviceIndex(ds, "ais")
    got_none, _ = di.knn(0.0, 0.0, n)
    got_all, _ = di.knn(0.0, 0.0, n, auths=("secret",))
    labeled = sum(1 for v in vis if v is not None)
    assert len(got_none) == n - labeled  # fail closed
    assert len(got_all) == n


def test_streaming_eviction_respected():
    ds = _store(n=400, seed=9)
    di = StreamingDeviceIndex(ds, "ais")
    first, _ = di.knn(0.0, 0.0, 5)
    di.evict(first.fids[:2])
    after, _ = di.knn(0.0, 0.0, 5)
    assert not set(first.fids[:2].tolist()) & set(after.fids.tolist())


def test_empty_index():
    ds = MemoryDataStore()
    ds.create_schema("ais", "val:Int,dtg:Date,*geom:Point:srid=4326")
    di = DeviceIndex(ds, "ais")
    batch, d = di.knn(0.0, 0.0, 5)
    assert len(batch) == 0 and len(d) == 0
