"""Proximity / route / date-offset / conversion processes."""

import io

import numpy as np
import pytest

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.geom import LineString, Point
from geomesa_tpu.process import (
    arrow_conversion,
    bin_conversion,
    date_offset,
    parse_duration_ms,
    proximity_search,
    route_search,
)
from geomesa_tpu.store.memory import MemoryDataStore

SPEC = "name:String,heading:Double,dtg:Date,*geom:Point"


@pytest.fixture()
def store():
    ds = MemoryDataStore()
    sft = SimpleFeatureType.create("ships", SPEC)
    ds.create_schema(sft)
    # three points along the x-axis route, one far away
    ds.write(
        "ships",
        {
            "name": ["a", "b", "c", "far"],
            "heading": [90.0, 270.0, 85.0, 0.0],
            "dtg": [1000, 2000, 3000, 4000],
            "geom": np.array(
                [[0.5, 0.05], [1.5, -0.08], [2.5, 0.0], [10.0, 5.0]]
            ),
        },
        fids=["a", "b", "c", "far"],
    )
    return ds


def test_proximity_search(store):
    batch, dist = proximity_search(
        store, "ships", [Point(0.5, 0.0), Point(2.5, 0.2)], 0.25
    )
    assert sorted(batch.column("name")) == ["a", "c"]
    assert (dist <= 0.25).all()


def test_proximity_search_segment_input(store):
    # a line input catches everything within buffer of the whole segment
    line = LineString(np.array([[0.0, 0.0], [3.0, 0.0]]))
    batch, dist = proximity_search(store, "ships", [line], 0.1)
    assert sorted(batch.column("name")) == ["a", "b", "c"]


def test_route_search_orders_along_route(store):
    route = np.array([[0.0, 0.0], [3.0, 0.0]])
    batch, dist, along = route_search(store, "ships", route, 0.2)
    assert list(batch.column("name")) == ["a", "b", "c"]
    assert np.all(np.diff(along) > 0)
    np.testing.assert_allclose(along, [0.5, 1.5, 2.5], atol=1e-9)


def test_route_search_heading_filter(store):
    route = np.array([[0.0, 0.0], [3.0, 0.0]])  # bearing 90 (due east)
    batch, _, _ = route_search(
        store, "ships", route, 0.2, heading_attr="heading",
        heading_tolerance_deg=30.0,
    )
    # a (90) and c (85) match; b (270) is opposite
    assert sorted(batch.column("name")) == ["a", "c"]
    batch2, _, _ = route_search(
        store, "ships", route, 0.2, heading_attr="heading",
        heading_tolerance_deg=30.0, bidirectional=True,
    )
    assert sorted(batch2.column("name")) == ["a", "b", "c"]


def test_date_offset():
    assert parse_duration_ms("P1D") == 86400_000
    assert parse_duration_ms("PT6H30M") == 23400_000
    assert parse_duration_ms("-PT15S") == -15_000
    assert parse_duration_ms(250) == 250
    with pytest.raises(ValueError):
        parse_duration_ms("nope")
    sft = SimpleFeatureType.create("t", "dtg:Date,*geom:Point")
    b = FeatureBatch.from_columns(
        sft, {"dtg": [1000, 2000], "geom": np.array([[0.0, 0.0], [1.0, 1.0]])}
    )
    out = date_offset(b, "PT1M")
    assert out.column("dtg").tolist() == [61000, 62000]
    assert b.column("dtg").tolist() == [1000, 2000]  # input untouched


def test_arrow_conversion_roundtrip(store):
    from geomesa_tpu.arrow_io import read_feature_stream

    payload = arrow_conversion(store, "ships", "BBOX(geom, 0, -1, 3, 1)")
    batches = list(read_feature_stream(io.BytesIO(payload)))
    names = sorted(
        n for b in batches for n in (b.column("name") if len(b) else [])
    )
    assert names == ["a", "b", "c"]


def test_bin_conversion(store):
    from geomesa_tpu.process import decode_bin

    payload = bin_conversion(
        store, "ships", "name", query="BBOX(geom, 0, -1, 3, 1)", sort=True
    )
    rec = decode_bin(payload)
    assert len(rec) == 3
    assert list(rec["dtg"]) == [1, 2, 3]  # seconds, sorted


def test_knn_resident_matches_store_path():
    """kNN over a resident DeviceIndex returns exactly the store path's
    neighbors (same expanding-window algorithm, fused window scans)."""
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.process.knn import knn
    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore()
    ds.create_schema("kp", "c:Int,*geom:Point:srid=4326")
    rng = np.random.default_rng(9)
    n = 3000
    ds.write("kp", {
        "c": np.arange(n),
        "geom": np.stack(
            [rng.uniform(-30, 30, n), rng.uniform(-30, 30, n)], axis=1
        ),
    })
    di = DeviceIndex(ds, "kp")
    b_store, d_store = knn(ds, "kp", 2.0, 5.0, k=25)
    b_res, d_res = knn(ds, "kp", 2.0, 5.0, k=25, device_index=di)
    np.testing.assert_array_equal(b_res.fids, b_store.fids)
    np.testing.assert_allclose(d_res, d_store)


def test_tube_and_proximity_resident_match_store_path():
    """Tube select and proximity search over a resident DeviceIndex (one
    union-of-windows dispatch) return exactly the store path's results."""
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.process.proximity import proximity_search
    from geomesa_tpu.process.tube import tube_select
    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore()
    ds.create_schema("ais", "c:Int,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(12)
    n = 5000
    t0 = 1_577_836_800_000
    ds.write("ais", {
        "c": np.arange(n),
        "dtg": t0 + rng.integers(0, 86_400_000, n),
        "geom": np.stack(
            [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
        ),
    })
    di = DeviceIndex(ds, "ais")
    # a 12-segment track crossing the data
    m = 13
    track = np.stack(
        [np.linspace(-8, 8, m), np.linspace(-6, 7, m) + 0.5 * np.sin(np.arange(m))],
        axis=1,
    )
    track_t = t0 + np.linspace(0, 86_400_000, m).astype(np.int64)
    b_store = tube_select(ds, "ais", track, track_t, 1.5, 3_600_000)
    b_res = tube_select(
        ds, "ais", track, track_t, 1.5, 3_600_000, device_index=di
    )
    assert len(b_store) > 0
    np.testing.assert_array_equal(
        np.sort(b_res.fids), np.sort(b_store.fids)
    )

    pts = [(-5.0, -2.0), (3.0, 4.0), (8.0, -8.0)]
    bp_store, dp_store = proximity_search(ds, "ais", pts, 1.0)
    bp_res, dp_res = proximity_search(
        ds, "ais", pts, 1.0, device_index=di
    )
    assert len(bp_store) > 0
    np.testing.assert_array_equal(
        np.sort(bp_res.fids), np.sort(bp_store.fids)
    )
    np.testing.assert_allclose(
        dp_res[np.argsort(bp_res.fids)], dp_store[np.argsort(bp_store.fids)]
    )


def test_tube_with_base_filter_stays_one_dispatch(monkeypatch):
    """A corridor query WITH a CQL base filter must still run the
    union-of-windows kernel (the base's compiled mask fuses into the
    same dispatch — it used to fall back to the
    76s-class per-segment store path) and match the store path exactly."""
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.process.proximity import proximity_search
    from geomesa_tpu.process.tube import tube_select
    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore()
    ds.create_schema("ais", "c:Int,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(21)
    n = 4000
    t0 = 1_577_836_800_000
    ds.write("ais", {
        "c": np.arange(n),
        "dtg": t0 + rng.integers(0, 86_400_000, n),
        "geom": np.stack(
            [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
        ),
    })
    di = DeviceIndex(ds, "ais")
    union_calls = []
    orig = DeviceIndex.window_union_query

    def spy(self, *a, **kw):
        out = orig(self, *a, **kw)
        union_calls.append(out is not None)
        return out

    monkeypatch.setattr(DeviceIndex, "window_union_query", spy)
    store_probes = []
    orig_q = MemoryDataStore.query

    def qspy(self, *a, **kw):
        store_probes.append(a)
        return orig_q(self, *a, **kw)

    m = 9
    track = np.stack(
        [np.linspace(-8, 8, m), np.linspace(-6, 7, m)], axis=1
    )
    track_t = t0 + np.linspace(0, 86_400_000, m).astype(np.int64)
    base = "c < 2000"
    b_store = tube_select(ds, "ais", track, track_t, 1.5, 3_600_000,
                          base_filter=base)
    monkeypatch.setattr(MemoryDataStore, "query", qspy)
    b_res = tube_select(ds, "ais", track, track_t, 1.5, 3_600_000,
                        base_filter=base, device_index=di)
    assert union_calls == [True], "union kernel skipped with base filter"
    assert not store_probes, "per-segment store queries ran"
    assert len(b_res) > 0
    assert np.all(b_res.column("c") < 2000)
    np.testing.assert_array_equal(
        np.sort(b_res.fids), np.sort(b_store.fids)
    )

    # proximity with a base filter: same one-dispatch contract
    union_calls.clear()
    pts = [(-5.0, -2.0), (3.0, 4.0)]
    bp_res, _ = proximity_search(ds, "ais", pts, 1.0, base_filter=base,
                                 device_index=di)
    monkeypatch.setattr(MemoryDataStore, "query", orig_q)
    bp_store, _ = proximity_search(ds, "ais", pts, 1.0, base_filter=base)
    assert union_calls == [True]
    np.testing.assert_array_equal(
        np.sort(bp_res.fids), np.sort(bp_store.fids)
    )

    # a base with host residuals cannot fuse: falls back, still correct
    union_calls.clear()
    got = di.window_union_query(
        np.array([[-10, -10, 10, 10]]), base="c < 2000 AND dtg IS NULL"
    )
    assert got is None or len(got) == 0  # IS NULL never matches here


def test_processes_honor_auths_on_both_paths():
    """tube/proximity/knn auths reach the STORE fallback path too (a
    base filter forces it) — labeled rows must not silently vanish."""
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.process.knn import knn
    from geomesa_tpu.process.proximity import proximity_search
    from geomesa_tpu.process.tube import tube_select
    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore()
    ds.create_schema("s", "c:Int,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(4)
    n = 500
    t0 = 1_577_836_800_000
    batch = FeatureBatch.from_columns(
        ds.get_schema("s"),
        {
            "c": np.arange(n),
            "dtg": t0 + rng.integers(0, 86_400_000, n),
            "geom": np.stack(
                [rng.uniform(-5, 5, n), rng.uniform(-5, 5, n)], axis=1
            ),
        },
        fids=np.arange(n),
    ).with_visibility(["secret"] * n)
    ds.write("s", batch)
    di = DeviceIndex(ds, "s")
    track = np.array([[-4.0, -4.0], [4.0, 4.0]])
    track_t = np.array([t0, t0 + 86_400_000])
    for base in (None, "c >= 0"):  # device path, then forced store path
        b = tube_select(
            ds, "s", track, track_t, 2.0, 90_000_000,
            base_filter=base, device_index=di, auths=("secret",),
        )
        assert len(b) > 0, f"tube base={base!r}"
        p, _ = proximity_search(
            ds, "s", [(0.0, 0.0)], 2.0,
            base_filter=base, device_index=di, auths=("secret",),
        )
        assert len(p) > 0, f"proximity base={base!r}"
    got, _ = knn(ds, "s", 0.0, 0.0, k=5, base_filter="c >= 0",
                 device_index=di, auths=("secret",))
    assert len(got) == 5
    # and no auths = fail closed everywhere
    b0 = tube_select(ds, "s", track, track_t, 2.0, 90_000_000,
                     device_index=di)
    assert len(b0) == 0
