"""Dim-plane resident key scans: the
de-interleaved z3 layout (nx, ny, packed bt) must serve DeviceIndex's
loose path with exact parity against the interleaved masked-compare
engine and the host oracle, across binned windows, streaming appends
(including a bin_base rebase), fused aggregations and per-auth serving.

Ref role: Z3Iterator, the reference's hottest scan (SURVEY section 3.1
[UNVERIFIED - empty reference mount]) — the loose-bbox key-only scan must
run the repo's fastest kernel, not a bench-local copy of it.
"""

from __future__ import annotations

import numpy as np
import pytest

from geomesa_tpu.device_cache import (
    Z_BIN,
    Z_BT,
    Z_HI,
    Z_LO,
    Z_NX,
    Z_NY,
    DeviceIndex,
    StreamingDeviceIndex,
)
from geomesa_tpu.store.memory import MemoryDataStore

DAY_MS = 86_400_000
T0 = 1_577_836_800_000  # 2020-01-01


def _store(n=4000, t_lo=T0, t_hi=T0 + 60 * DAY_MS, seed=7, name="gdelt"):
    rng = np.random.default_rng(seed)
    ds = MemoryDataStore()
    ds.create_schema(name, "val:Int,dtg:Date,*geom:Point:srid=4326")
    ds.write(name, {
        "val": rng.integers(0, 100, n),
        "dtg": rng.integers(t_lo, t_hi, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    })
    return ds


ECQL = (
    "BBOX(geom, -10, 35, 30, 60) AND "
    "dtg DURING 2020-01-10T00:00:00Z/2020-01-25T00:00:00Z"
)
BBOX_ONLY = "BBOX(geom, -10, 35, 30, 60)"


def test_dim_mode_on_by_default_for_z3():
    di = DeviceIndex(_store(), "gdelt", z_planes=True)
    assert di._z_kind == "z3" and di._dim_mode
    assert Z_NX in di._cols and Z_NY in di._cols and Z_BT in di._cols
    # the interleaved planes are NOT staged twice: same 12B/row as before
    assert Z_HI not in di._cols and Z_LO not in di._cols
    assert Z_BIN not in di._cols


def test_dim_staging_matches_host_oracle():
    """Device-encoded nx/ny/bt planes == the host numpy packing."""
    from geomesa_tpu.curves.binnedtime import to_binned_time
    from geomesa_tpu.index.keyplanes import schema_kind
    from geomesa_tpu.ops import zscan

    ds = _store()
    di = DeviceIndex(ds, "gdelt", z_planes=True)
    assert di._dim_mode and not di._z_encode_failed
    assert di._dim_encode_jit is not None  # device path actually ran
    batch = ds.query("gdelt").batch
    _, sfc = schema_kind(di.sft)
    x, y = batch.point_coords("geom")
    bins, off = to_binned_time(batch.column("dtg"), sfc.period)
    nx = np.asarray(sfc.lon.normalize(x)).astype(np.uint32)
    ny = np.asarray(sfc.lat.normalize(y)).astype(np.uint32)
    nt = np.asarray(
        sfc.time.normalize(np.asarray(off, np.float64))
    ).astype(np.uint32)
    enx, eny, ebt = zscan.z3_dim_planes(
        sfc, nx, ny, nt, bins.astype(np.uint32), di._bt_base
    )
    np.testing.assert_array_equal(np.asarray(di._cols[Z_NX]), enx)
    np.testing.assert_array_equal(np.asarray(di._cols[Z_NY]), eny)
    np.testing.assert_array_equal(np.asarray(di._cols[Z_BT]), ebt)


@pytest.mark.parametrize("ecql", [ECQL, BBOX_ONLY])
def test_dim_loose_parity_vs_masked_compare(ecql):
    """The dim-plane loose answer == the interleaved masked-compare
    answer, bit for bit (two independent engines over two layouts)."""
    ds = _store()
    dim = DeviceIndex(ds, "gdelt", z_planes=True)
    cmp_ = DeviceIndex(ds, "gdelt", z_planes=True, dim_planes=False)
    assert dim._dim_mode and not cmp_._dim_mode
    np.testing.assert_array_equal(
        dim.mask(ecql, loose=True), cmp_.mask(ecql, loose=True)
    )
    assert dim.count(ecql, loose=True) == cmp_.count(ecql, loose=True)


def test_dim_loose_is_superset_of_exact():
    di = DeviceIndex(_store(), "gdelt", z_planes=True)
    loose = di.mask(ECQL, loose=True)
    exact = di.mask(ECQL, loose=False)
    assert not np.any(exact & ~loose)  # superset contract
    assert loose.sum() < len(loose)  # pruning actually happens


def test_dim_loose_count_uses_pallas_kernel(monkeypatch):
    """count(loose=True) must dispatch the Pallas dim kernel (not the
    XLA mask + host sum)."""
    di = DeviceIndex(_store(), "gdelt", z_planes=True)
    calls = []
    orig = di._dim_kernel

    def spy(r):
        fns = orig(r)
        calls.append(r)
        return fns

    monkeypatch.setattr(di, "_dim_kernel", spy)
    n = di.count(ECQL, loose=True)
    assert calls, "Pallas dim kernel was not used for the loose count"
    assert n == int(di.mask(ECQL, loose=True).sum())


def test_dim_kernel_single_compile_across_windows():
    """One R bucket == one compiled kernel: distinct windows reuse it."""
    di = DeviceIndex(_store(), "gdelt", z_planes=True)
    a = di.count(ECQL, loose=True)
    b = di.count(
        "BBOX(geom, 0, 0, 90, 80) AND "
        "dtg DURING 2020-02-01T00:00:00Z/2020-02-12T00:00:00Z",
        loose=True,
    )
    c = di.count(BBOX_ONLY, loose=True)
    assert a >= 0 and b >= 0 and c >= 0
    # every one-range window shares the R=1 bucket; no per-window entries
    assert set(di._dim_kernels) <= {1, 2, 4, 8}


def test_loose_scan_kernel_is_dim_and_matches_count():
    """The bench hook returns the dim kernel + resident planes and its
    count equals the serving count."""
    di = DeviceIndex(_store(), "gdelt", z_planes=True)
    got = di.loose_scan_kernel(ECQL)
    assert got is not None
    fn, args = got
    assert len(args) == 4  # (qarr, nx, ny, bt): the dim signature
    assert int(fn(*args)) == di.count(ECQL, loose=True)


def test_wide_bin_span_falls_back_to_masked_compare():
    """Data spanning >= 2^11 - 1 weekly bins cannot pack the bt word:
    staging must keep the interleaved layout and loose must still work."""
    from geomesa_tpu.ops.zscan import BT_BIN_SPAN

    wide = _store(
        n=1500, t_lo=T0 - (BT_BIN_SPAN + 10) * 7 * DAY_MS, t_hi=T0
    )
    di = DeviceIndex(wide, "gdelt", z_planes=True)
    assert not di._dim_mode
    assert Z_HI in di._cols and Z_NX not in di._cols
    loose = di.mask(BBOX_ONLY, loose=True)
    exact = di.mask(BBOX_ONLY, loose=False)
    assert not np.any(exact & ~loose)


def test_dim_planes_true_raises_on_wide_span():
    from geomesa_tpu.ops.zscan import BT_BIN_SPAN

    wide = _store(
        n=500, t_lo=T0 - (BT_BIN_SPAN + 10) * 7 * DAY_MS, t_hi=T0
    )
    with pytest.raises(ValueError, match="span"):
        DeviceIndex(wide, "gdelt", z_planes=True, dim_planes=True)


def test_dim_planes_true_raises_on_non_point():
    """Non-point schemas (xz keys) cannot pack dim planes."""
    from geomesa_tpu.geom.wkt import parse_wkt

    ds = MemoryDataStore()
    ds.create_schema("polys", "val:Int,*geom:Polygon:srid=4326")
    ds.write("polys", {
        "val": np.arange(2),
        "geom": np.array([
            parse_wkt("POLYGON((0 0, 1 0, 1 1, 0 0))"),
            parse_wkt("POLYGON((2 2, 3 2, 3 3, 2 2))"),
        ], dtype=object),
    })
    with pytest.raises(ValueError, match="z3/z2"):
        DeviceIndex(ds, "polys", z_planes=True, dim_planes=True)


class TestZ2Dim:
    """Date-less point schemas stage the 2-plane dim layout."""

    def _z2_store(self, n=3000, seed=4):
        rng = np.random.default_rng(seed)
        ds = MemoryDataStore()
        ds.create_schema("z2t", "val:Int,*geom:Point:srid=4326")
        ds.write("z2t", {
            "val": rng.integers(0, 100, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)],
                axis=1,
            ),
        })
        return ds

    def test_dim_mode_default_and_planes(self):
        di = DeviceIndex(self._z2_store(), "z2t", z_planes=True)
        assert di._z_kind == "z2" and di._dim_mode
        assert Z_NX in di._cols and Z_NY in di._cols
        assert Z_BT not in di._cols  # no time in the key
        assert Z_HI not in di._cols

    def test_loose_parity_vs_masked_compare(self):
        ds = self._z2_store()
        dim = DeviceIndex(ds, "z2t", z_planes=True)
        cmp_ = DeviceIndex(ds, "z2t", z_planes=True, dim_planes=False)
        np.testing.assert_array_equal(
            dim.mask(BBOX_ONLY, loose=True),
            cmp_.mask(BBOX_ONLY, loose=True),
        )
        assert dim.count(BBOX_ONLY, loose=True) == cmp_.count(
            BBOX_ONLY, loose=True
        )
        # superset of exact
        loose = dim.mask(BBOX_ONLY, loose=True)
        exact = dim.mask(BBOX_ONLY, loose=False)
        assert not np.any(exact & ~loose)

    def test_kernel_and_fused_paths(self):
        ds = self._z2_store()
        di = DeviceIndex(ds, "z2t", z_planes=True)
        got = di.loose_scan_kernel(BBOX_ONLY)
        assert got is not None
        fn, args = got
        assert len(args) == 3  # (qarr, nx, ny): the 2-plane signature
        assert int(fn(*args)) == di.count(BBOX_ONLY, loose=True)
        seq = di.stats(BBOX_ONLY, "Count()", loose=True)
        assert seq.stats[0].count == di.count(BBOX_ONLY, loose=True)

    def test_streaming_append(self):
        ds = self._z2_store(n=1000)
        di = StreamingDeviceIndex(ds, "z2t", z_planes=True, capacity=8192)
        extra = self._z2_store(n=500, seed=9)
        di.append(extra.query("z2t").batch)
        assert di.delta_appends == 1 and di._dim_mode
        loose = di.mask(BBOX_ONLY, loose=True)
        exact = di.mask(BBOX_ONLY, loose=False)
        assert not np.any(exact & ~loose)
        assert exact.sum() > 0


def test_fused_stats_on_dim_planes():
    """Count + MinMax through the fused loose dispatch on dim planes must
    match the masked-compare index's results."""
    ds = _store()
    dim = DeviceIndex(ds, "gdelt", z_planes=True)
    cmp_ = DeviceIndex(ds, "gdelt", z_planes=True, dim_planes=False)
    a = dim.stats(ECQL, 'Count();MinMax("val")', loose=True)
    b = cmp_.stats(ECQL, 'Count();MinMax("val")', loose=True)
    assert a.stats[0].count == b.stats[0].count
    assert (a.stats[1].min, a.stats[1].max) == (b.stats[1].min, b.stats[1].max)


def test_fused_density_on_dim_planes():
    from geomesa_tpu.geom import Envelope

    ds = _store(n=6000)
    dim = DeviceIndex(ds, "gdelt", z_planes=True)
    cmp_ = DeviceIndex(ds, "gdelt", z_planes=True, dim_planes=False)
    env = Envelope(-10, 35, 30, 60)
    ga = dim.density(ECQL, env, 32, 16, loose=True)
    gb = cmp_.density(ECQL, env, 32, 16, loose=True)
    assert ga is not None and gb is not None
    np.testing.assert_array_equal(ga, gb)


def test_dim_auths_fail_closed_and_serve_per_request():
    rng = np.random.default_rng(5)
    n = 3000
    from geomesa_tpu.features.batch import FeatureBatch

    ds = MemoryDataStore()
    ds.create_schema("sec", "val:Int,dtg:Date,*geom:Point:srid=4326")
    vis = np.array(
        [None, "admin", "admin&ops"], dtype=object
    )[rng.integers(0, 3, n)]
    batch = FeatureBatch.from_columns(
        ds.get_schema("sec"),
        {
            "val": rng.integers(0, 9, n),
            "dtg": rng.integers(T0, T0 + 30 * DAY_MS, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
            ),
        },
        fids=np.arange(n),
    ).with_visibility(vis)
    ds.write("sec", batch)
    di = DeviceIndex(ds, "sec", z_planes=True)
    assert di._dim_mode
    none_ct = di.count(BBOX_ONLY, loose=True)
    admin_ct = di.count(BBOX_ONLY, loose=True, auths=("admin",))
    all_ct = di.count(BBOX_ONLY, loose=True, auths=("admin", "ops"))
    assert none_ct < admin_ct < all_ct
    m = di.mask(BBOX_ONLY, loose=True, auths=("admin",))
    assert int(m.sum()) == admin_ct


def test_z3_interval_hint_reaches_resident_planes():
    """``geomesa.z3.interval`` must drive the SAME period in the resident
    key planes as in the durable key space (they diverged before round
    4: schema_kind hardcoded WEEK)."""
    from geomesa_tpu.curves.binnedtime import TimePeriod
    from geomesa_tpu.index.keyplanes import schema_kind
    from geomesa_tpu.index.keyspaces import keyspace_for

    rng = np.random.default_rng(3)
    n = 800
    ds = MemoryDataStore()
    ds.create_schema(
        "d", "dtg:Date,*geom:Point:srid=4326;geomesa.z3.interval=day"
    )
    ds.write("d", {
        "dtg": rng.integers(T0, T0 + 7 * DAY_MS, n),
        "geom": np.stack(
            [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
        ),
    })
    sft = ds.get_schema("d")
    _, sfc = schema_kind(sft)
    assert sfc.period == TimePeriod.DAY
    assert keyspace_for(sft, "z3").period == TimePeriod.DAY
    di = DeviceIndex(ds, "d", z_planes=True)
    assert di._dim_mode  # day precision is still 21 bits
    ecql = (
        "BBOX(geom, -5, -5, 5, 5) AND "
        "dtg DURING 2020-01-02T00:00:00Z/2020-01-04T00:00:00Z"
    )
    loose = di.mask(ecql, loose=True)
    exact = di.mask(ecql, loose=False)
    assert not np.any(exact & ~loose) and exact.sum() > 0
    # masked-compare engine agrees under the same period
    cmp_ = DeviceIndex(ds, "d", z_planes=True, dim_planes=False)
    np.testing.assert_array_equal(loose, cmp_.mask(ecql, loose=True))


def test_fuzz_dim_vs_masked_compare_random_windows():
    """Differential fuzz: 40 random bbox(+during) windows over z3 AND z2
    dim-mode indexes must match the masked-compare engine bit for bit
    (covers qarr construction, bin-range clamping, range merging and the
    R-bucket padding across window shapes)."""
    rng = np.random.default_rng(99)
    ds3 = _store(n=5000, seed=31)
    dim3 = DeviceIndex(ds3, "gdelt", z_planes=True)
    cmp3 = DeviceIndex(ds3, "gdelt", z_planes=True, dim_planes=False)

    ds2 = MemoryDataStore()
    n = 4000
    ds2.create_schema("z2f", "val:Int,*geom:Point:srid=4326")
    ds2.write("z2f", {
        "val": rng.integers(0, 9, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    })
    dim2 = DeviceIndex(ds2, "z2f", z_planes=True)
    cmp2 = DeviceIndex(ds2, "z2f", z_planes=True, dim_planes=False)

    from geomesa_tpu.filter import ast

    for i in range(40):
        x0, y0 = rng.uniform(-185, 175), rng.uniform(-95, 85)
        w = 10 ** rng.uniform(-2, 2.3)
        h = 10 ** rng.uniform(-2, 2)
        bbox = ast.BBox("geom", x0, y0, min(x0 + w, 180), min(y0 + h, 90))
        # z3: random windows incl. degenerate/outside/bin-straddling
        t_lo = T0 + int(rng.uniform(-30, 90) * DAY_MS)
        t_hi = t_lo + int(10 ** rng.uniform(3, 7.2))
        f3 = ast.And((bbox, ast.During("dtg", t_lo, t_hi)))
        np.testing.assert_array_equal(
            dim3.mask(f3, loose=True), cmp3.mask(f3, loose=True),
            err_msg=f"z3 window {i}",
        )
        np.testing.assert_array_equal(
            dim2.mask(bbox, loose=True), cmp2.mask(bbox, loose=True),
            err_msg=f"z2 window {i}",
        )


class TestStreamingDim:
    def test_append_keeps_dim_mode_and_parity(self):
        ds = _store(n=2000)
        di = StreamingDeviceIndex(ds, "gdelt", z_planes=True, capacity=8192)
        assert di._dim_mode
        extra = _store(n=1000, seed=11, t_lo=T0 + 30 * DAY_MS,
                       t_hi=T0 + 90 * DAY_MS)
        di.append(ds.query("gdelt").batch.__class__.concat(
            [extra.query("gdelt").batch]
        ))
        assert di.delta_appends == 1 and di._dim_mode
        # parity against a cold full-restage index over the same rows
        merged = MemoryDataStore()
        merged.create_schema("gdelt", "val:Int,dtg:Date,*geom:Point:srid=4326")
        b = di._live_rows()
        merged.write("gdelt", {
            "val": b.column("val"), "dtg": b.column("dtg"),
            "geom": np.stack(b.point_coords("geom"), axis=1),
        })
        cold = DeviceIndex(merged, "gdelt", z_planes=True)
        assert di.count(ECQL, loose=True) == cold.count(ECQL, loose=True)

    def test_append_below_base_rebases(self):
        """A delta OLDER than every staged row forces a bt repack (the
        sentinel would wrongly hide it from loose supersets)."""
        ds = _store(n=1500, t_lo=T0 + 30 * DAY_MS, t_hi=T0 + 60 * DAY_MS)
        di = StreamingDeviceIndex(ds, "gdelt", z_planes=True)
        base_before = di._bt_base
        old = _store(n=800, seed=13, t_lo=T0, t_hi=T0 + 7 * DAY_MS)
        restages_before = di.restages
        di.append(old.query("gdelt").batch)
        assert di.restages == restages_before + 1  # rebase happened
        assert di._bt_base < base_before
        # loose still answers the OLD window (superset incl. the delta)
        m = di.mask(
            "dtg DURING 2020-01-01T00:00:00Z/2020-01-08T00:00:00Z",
            loose=True,
        )
        exact = di.mask(
            "dtg DURING 2020-01-01T00:00:00Z/2020-01-08T00:00:00Z",
            loose=False,
        )
        assert not np.any(exact & ~m)
        assert exact.sum() > 0

    def test_eviction_respected_by_dim_loose(self):
        ds = _store(n=1200)
        di = StreamingDeviceIndex(ds, "gdelt", z_planes=True)
        hits = np.nonzero(di.mask(BBOX_ONLY, loose=True))[0]
        assert len(hits) > 2
        victim_fids = di._host_rows().fids[hits[:2]]
        di.evict(victim_fids)
        m = di.mask(BBOX_ONLY, loose=True)
        assert not m[hits[0]] and not m[hits[1]]
        assert di.count(BBOX_ONLY, loose=True) == int(m.sum())
