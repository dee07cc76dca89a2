"""Device-resident index: pinned columns, repeated queries, refresh."""

import numpy as np

from geomesa_tpu.device_cache import DeviceIndex
from geomesa_tpu.filter.compile import evaluate_host
from geomesa_tpu.filter.ecql import parse_ecql, parse_instant
from geomesa_tpu.store.memory import MemoryDataStore

SPEC = "name:String,val:Int,dtg:Date,*geom:Point:srid=4326"


def _store(n=20000, seed=23):
    ds = MemoryDataStore()
    ds.create_schema("t", SPEC)
    rng = np.random.default_rng(seed)
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    ds.write(
        "t",
        {
            "name": rng.choice(["a", "b", "c"], n),
            "val": rng.integers(0, 100, n),
            "dtg": rng.integers(t0, t1, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    return ds


def test_resident_count_and_query_match_oracle():
    ds = _store()
    di = DeviceIndex(ds, "t")
    assert len(di) == 20000 and di.nbytes > 0
    all_batch = ds.query("t").batch
    for ecql in [
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z",
        "val >= 50 AND BBOX(geom, 0, 0, 90, 90)",
        "BBOX(geom, -180, -90, 180, 90)",
    ]:
        expect = evaluate_host(parse_ecql(ecql), all_batch)
        assert di.count(ecql) == int(expect.sum()), ecql
        got = di.query(ecql)
        np.testing.assert_array_equal(
            np.sort(got.fids), np.sort(all_batch.fids[expect])
        )


def test_residual_filters_still_exact():
    ds = _store(n=2000)
    di = DeviceIndex(ds, "t")
    # string equality is not a device predicate -> residual path
    ecql = "name = 'a' AND BBOX(geom, -90, -45, 90, 45)"
    all_batch = ds.query("t").batch
    expect = evaluate_host(parse_ecql(ecql), all_batch)
    assert di.count(ecql) == int(expect.sum())
    np.testing.assert_array_equal(
        np.sort(di.query(ecql).fids), np.sort(all_batch.fids[expect])
    )


def test_refresh_after_write():
    ds = _store(n=100)
    di = DeviceIndex(ds, "t")
    assert di.count("INCLUDE") == 100
    ds.write(
        "t",
        {
            "name": ["z"],
            "val": [1],
            "dtg": [parse_instant("2020-01-15T00:00:00")],
            "geom": np.array([[1.0, 2.0]]),
        },
        fids=["extra"],
    )
    assert di.count("INCLUDE") == 100  # stale until refresh
    di.refresh()
    assert di.count("INCLUDE") == 101


def test_attach_live_refreshes():
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.stream import LiveFeatureStore

    sft = SimpleFeatureType.create("t", SPEC)
    live = LiveFeatureStore(sft)

    class LiveAdapter:
        """Minimal store facade over the live layer for DeviceIndex."""

        def get_schema(self, _):
            return sft

        def query(self, _, q=None):
            from geomesa_tpu.query.runner import QueryResult

            b = live.snapshot()
            return QueryResult(b, None, len(b), len(b))

    di = DeviceIndex(LiveAdapter(), "t")
    di.attach_live(live)
    live.put(
        {
            "name": ["a"],
            "val": [5],
            "dtg": [0],
            "geom": np.array([[3.0, 4.0]]),
        },
        ["f0"],
    )
    assert di.count("INCLUDE") == 1  # listener refreshed the residency


def test_detach_live_listener():
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.stream import LiveFeatureStore

    sft = SimpleFeatureType.create("t", SPEC)
    live = LiveFeatureStore(sft)

    calls = []

    class Adapter:
        def get_schema(self, _):
            return sft

        def query(self, _, q=None):
            from geomesa_tpu.query.runner import QueryResult

            calls.append(1)
            b = live.snapshot()
            return QueryResult(b, None, len(b), len(b))

    di = DeviceIndex(Adapter(), "t")
    detach = di.attach_live(live)
    live.put({"name": ["a"], "val": [1], "dtg": [0],
              "geom": np.zeros((1, 2))}, ["f0"])
    n_after_put = len(calls)
    detach()
    live.put({"name": ["b"], "val": [2], "dtg": [0],
              "geom": np.zeros((1, 2))}, ["f1"])
    assert len(calls) == n_after_put  # no refresh after detach


# -- streaming delta refresh -----------------------


def _oracle(ds, ecql):
    b = ds.query("t").batch
    return b, evaluate_host(parse_ecql(ecql), b)


class TestStreamingDeviceIndex:
    ECQL = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )

    def _batch(self, sft, n, seed, fid0=0):
        from geomesa_tpu.features.batch import FeatureBatch

        rng = np.random.default_rng(seed)
        t0 = parse_instant("2020-01-01T00:00:00")
        t1 = parse_instant("2020-03-01T00:00:00")
        return FeatureBatch.from_columns(
            sft,
            {
                "name": rng.choice(["a", "b", "c"], n),
                "val": rng.integers(0, 100, n),
                "dtg": rng.integers(t0, t1, n),
                "geom": np.stack(
                    [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)],
                    axis=1,
                ),
            },
            fids=np.arange(fid0, fid0 + n),
        )

    def test_append_path_matches_full_restage(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=5000)
        di = StreamingDeviceIndex(ds, "t", capacity=1 << 15)
        base_restages = di.restages
        sft = ds.get_schema("t")
        for k in range(8):
            b = self._batch(sft, 500, seed=100 + k, fid0=100_000 + 500 * k)
            ds.write("t", dict(b.columns), fids=b.fids)
            di.append(b)
        assert di.restages == base_restages  # all appends took the delta path
        assert di.delta_appends == 8
        all_batch, expect = _oracle(ds, self.ECQL)
        assert len(di) == 9000
        assert di.count(self.ECQL) == int(expect.sum())
        np.testing.assert_array_equal(
            np.sort(di.query(self.ECQL).fids.astype(np.int64)),
            np.sort(all_batch.fids[expect].astype(np.int64)),
        )

    def test_growth_compacts_and_stays_exact(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=1000)
        di = StreamingDeviceIndex(ds, "t", capacity=1024)
        sft = ds.get_schema("t")
        for k in range(6):  # overflows 1024 quickly -> growth path
            b = self._batch(sft, 700, seed=7 + k, fid0=50_000 + 700 * k)
            ds.write("t", dict(b.columns), fids=b.fids)
            di.append(b)
        assert di.restages > 1
        all_batch, expect = _oracle(ds, self.ECQL)
        assert di.count(self.ECQL) == int(expect.sum())

    def test_evict_and_upsert(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=4000)
        di = StreamingDeviceIndex(ds, "t")
        di.evict(np.arange(1000, 1100))
        assert len(di) == 3900
        # count over INCLUDE sees only live rows
        assert di.count("INCLUDE") == 3900
        # upsert moves a fid's attributes; old row must not answer
        sft = ds.get_schema("t")
        b = self._batch(sft, 50, seed=5, fid0=0)  # overwrite fids 0..49
        b.columns["geom"][:] = [[170.0, 80.0]]  # park them far away
        di.upsert(b)
        assert len(di) == 3900
        got = di.query("BBOX(geom, 169, 79, 171, 81)")
        assert set(got.fids.astype(np.int64).tolist()) >= set(range(50))

    def test_pallas_engine_ands_the_validity_plane(self, monkeypatch):
        """On a TPU the exact filter runs on the Pallas tile kernel even
        for the padded streaming buffers (the server's resident index):
        the kernel ANDs the validity plane, so evicted rows never count.
        The CPU picks XLA, so the TPU choice is forced here (interpret
        mode runs the same kernel code)."""
        import jax

        from geomesa_tpu.device_cache import StreamingDeviceIndex
        from geomesa_tpu.filter.compile import CompiledFilter

        def tpu_jitted_scan(self):
            if not hasattr(self, "_jitted_scan"):
                count_fn, mask_fn = self.pallas_scan()
                self._jitted_scan = (jax.jit(count_fn), jax.jit(mask_fn))
                self.scan_engine = "pallas"
            return self._jitted_scan

        monkeypatch.setattr(CompiledFilter, "jitted_scan", tpu_jitted_scan)
        ds = _store(n=4000)
        di = StreamingDeviceIndex(ds, "t", capacity=1 << 13)
        di.evict(np.arange(1000, 1500))
        assert di._device_valid() is not None
        all_batch, expect = _oracle(ds, self.ECQL)
        live = ~np.isin(all_batch.fids.astype(np.int64), np.arange(1000, 1500))
        assert di.count(self.ECQL) == int((expect & live).sum())
        assert di._compiled[repr(parse_ecql(self.ECQL))][0].scan_engine == (
            "pallas"
        )
        np.testing.assert_array_equal(
            np.sort(di.query(self.ECQL).fids.astype(np.int64)),
            np.sort(all_batch.fids[expect & live].astype(np.int64)),
        )

    def test_residual_and_host_filters_respect_validity(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=2000)
        di = StreamingDeviceIndex(ds, "t")
        all_batch = ds.query("t").batch
        ecql = "name = 'a' AND BBOX(geom, -90, -45, 90, 45)"
        expect = evaluate_host(parse_ecql(ecql), all_batch)
        victims = all_batch.fids[expect][:20]
        di.evict(victims)
        assert di.count(ecql) == int(expect.sum()) - 20
        got = set(di.query(ecql).fids.tolist())
        assert not (got & set(victims.tolist()))
        # pure-host filter path too
        host_ecql = "name = 'a'"
        h_expect = evaluate_host(parse_ecql(host_ecql), all_batch)
        assert di.count(host_ecql) == int(h_expect.sum()) - 20

    def test_attach_live_applies_deltas_not_restages(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex
        from geomesa_tpu.features.sft import SimpleFeatureType
        from geomesa_tpu.query.runner import QueryResult
        from geomesa_tpu.stream import LiveFeatureStore

        sft = SimpleFeatureType.create("t", SPEC)
        live = LiveFeatureStore(sft)

        class Adapter:
            def get_schema(self, _):
                return sft

            def query(self, _, q=None):
                b = live.snapshot()
                return QueryResult(b, None, len(b), len(b))

        di = StreamingDeviceIndex(Adapter(), "t", capacity=4096)
        di.attach_live(live)
        base_restages = di.restages
        for k in range(10):
            live.put(
                {
                    "name": ["a"],
                    "val": [k],
                    "dtg": [parse_instant("2020-01-15T00:00:00")],
                    "geom": np.array([[float(k), 2.0]]),
                },
                [f"f{k}"],
            )
        assert len(di) == 10
        assert di.count("INCLUDE") == 10
        assert di.restages == base_restages  # puts rode the delta path
        live.remove(np.array(["f3", "f4"], dtype=object))
        assert len(di) == 8
        assert di.count("val >= 0") == 8
        # upsert via live layer: same fid, new position
        live.put(
            {
                "name": ["z"],
                "val": [99],
                "dtg": [parse_instant("2020-01-15T00:00:00")],
                "geom": np.array([[100.0, 50.0]]),
            },
            ["f0"],
        )
        assert len(di) == 8
        assert di.count("BBOX(geom, 99, 49, 101, 51)") == 1

    def test_sustained_ingest_rate(self):
        """The delta path must sustain ingest without per-append restaging:
        200 appends of 1k rows -> at most a handful of growth restages and
        a measured rows/sec figure (printed, not asserted -- CI machines
        vary)."""
        import time

        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=1000)
        sft = ds.get_schema("t")
        di = StreamingDeviceIndex(ds, "t", capacity=1 << 18)
        batches = [
            self._batch(sft, 1000, seed=k, fid0=1_000_000 + 1000 * k)
            for k in range(200)
        ]
        di.count(self.ECQL)  # compile before timing
        t0 = time.perf_counter()
        for b in batches:
            di.append(b)
        dt = time.perf_counter() - t0
        assert di.restages <= 2  # capacity hint absorbs the whole run
        assert len(di) == 201_000
        rate = 200_000 / dt
        print(f"\nsustained ingest: {rate:,.0f} rows/s over 200 appends")
        # correctness after the burst: mirror the appends into the store
        # first so the oracle sees the same rows
        for b in batches:
            ds.write("t", dict(b.columns), fids=b.fids)
        all_batch, expect = _oracle(ds, self.ECQL)
        assert di.count(self.ECQL) == int(expect.sum())


# -- loose (key-only) scans (ref geomesa.loose.bbox) ------------------------


class TestLooseZScan:
    ECQL = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )

    def _cell_oracle(self, batch, ecql_env, window_ms):
        """Quantized-cell (loose) semantics computed independently."""
        from geomesa_tpu.curves.binnedtime import (
            bins_for_interval,
            to_binned_time,
        )
        from geomesa_tpu.curves.z3 import Z3SFC

        sfc = Z3SFC()
        x, y = batch.point_coords()
        dtg = batch.column("dtg")
        bins, off = to_binned_time(dtg, sfc.period)
        nx = np.asarray(sfc.lon.normalize(x)).astype(np.int64)
        ny = np.asarray(sfc.lat.normalize(y)).astype(np.int64)
        nt = np.asarray(sfc.time.normalize(off)).astype(np.int64)
        x0, y0, x1, y1 = ecql_env
        sp = (
            (nx >= int(sfc.lon.normalize(x0)))
            & (nx <= int(sfc.lon.normalize(x1)))
            & (ny >= int(sfc.lat.normalize(y0)))
            & (ny <= int(sfc.lat.normalize(y1)))
        )
        tm = np.zeros(len(batch), bool)
        for b, lo, hi in bins_for_interval(window_ms[0], window_ms[1], sfc.period):
            tm |= (
                (bins == b)
                & (nt >= int(sfc.time.normalize(lo)))
                & (nt <= int(sfc.time.normalize(hi)))
            )
        return sp & tm

    def test_loose_matches_cell_oracle_and_contains_exact(self):
        ds = _store(n=20000)
        di = DeviceIndex(ds, "t", z_planes=True)
        all_batch = ds.query("t").batch
        got = di.mask(self.ECQL, loose=True)
        w = (parse_instant("2020-01-10T00:00:00"),
             parse_instant("2020-02-01T00:00:00"))
        expect = self._cell_oracle(all_batch, (-10, 35, 30, 60), w)
        np.testing.assert_array_equal(got, expect)
        # loose is a superset of exact
        exact = evaluate_host(parse_ecql(self.ECQL), all_batch)
        assert not np.any(exact & ~got)
        assert di.count(self.ECQL, loose=True) == int(expect.sum())
        fids = di.query(self.ECQL, loose=True).fids
        np.testing.assert_array_equal(
            np.sort(fids), np.sort(all_batch.fids[expect])
        )

    def test_loose_prop_enables_globally(self):
        from geomesa_tpu.conf import prop_override

        ds = _store(n=3000)
        di = DeviceIndex(ds, "t", z_planes=True)
        exact = di.count(self.ECQL)
        with prop_override("query.loose.bbox", True):
            loose = di.count(self.ECQL)
        assert loose >= exact  # cell-granular superset

    def test_non_bbox_filters_fall_back(self):
        ds = _store(n=3000)
        di = DeviceIndex(ds, "t", z_planes=True)
        # val compare is not answerable from the key: loose must fall
        # back to the exact path and still be correct
        ecql = "val >= 50 AND BBOX(geom, 0, 0, 90, 90)"
        all_batch = ds.query("t").batch
        expect = evaluate_host(parse_ecql(ecql), all_batch)
        assert di.count(ecql, loose=True) == int(expect.sum())

    def test_bbox_only_uses_observed_bin_range(self):
        ds = _store(n=5000)
        di = DeviceIndex(ds, "t", z_planes=True)
        all_batch = ds.query("t").batch
        got = di.mask("BBOX(geom, -10, 35, 30, 60)", loose=True)
        t_lo = int(all_batch.column("dtg").min())
        t_hi = int(all_batch.column("dtg").max())
        expect = self._cell_oracle(
            all_batch, (-10, 35, 30, 60), (t_lo, t_hi)
        )
        np.testing.assert_array_equal(got, expect)

    def test_streaming_loose_respects_validity(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=4000)
        di = StreamingDeviceIndex(ds, "t", z_planes=True)
        before = di.count(self.ECQL, loose=True)
        hit_fids = di.query(self.ECQL, loose=True).fids
        di.evict(hit_fids[:10])
        assert di.count(self.ECQL, loose=True) == before - 10
        got = set(di.query(self.ECQL, loose=True).fids.tolist())
        assert not (got & set(hit_fids[:10].tolist()))

    def test_streaming_append_widens_bins(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex
        from geomesa_tpu.features.batch import FeatureBatch

        ds = _store(n=2000)
        di = StreamingDeviceIndex(ds, "t", z_planes=True, capacity=8192)
        sft = ds.get_schema("t")
        # append rows in a LATER time bin than any original row
        t_new = parse_instant("2020-06-15T00:00:00")
        b = FeatureBatch.from_columns(
            sft,
            {
                "name": ["x"] * 50,
                "val": np.arange(50),
                "dtg": np.full(50, t_new),
                "geom": np.tile([[5.0, 50.0]], (50, 1)),
            },
            fids=np.arange(90000, 90050),
        )
        di.append(b)
        q = ("BBOX(geom, 0, 45, 10, 55) AND "
             "dtg DURING 2020-06-14T00:00:00Z/2020-06-16T00:00:00Z")
        assert di.count(q, loose=True) == 50

    def test_z2_planes_for_dateless_schema(self):
        ds = MemoryDataStore()
        ds.create_schema("p", "val:Int,*geom:Point")
        rng = np.random.default_rng(3)
        n = 5000
        ds.write(
            "p",
            {
                "val": rng.integers(0, 10, n),
                "geom": np.stack(
                    [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], 1
                ),
            },
            fids=np.arange(n),
        )
        di = DeviceIndex(ds, "p", z_planes=True)
        all_batch = ds.query("p").batch
        got = di.mask("BBOX(geom, -10, 35, 30, 60)", loose=True)
        from geomesa_tpu.curves.z2 import Z2SFC

        sfc = Z2SFC()
        x, y = all_batch.point_coords()
        nx = np.asarray(sfc.lon.normalize(x)).astype(np.int64)
        ny = np.asarray(sfc.lat.normalize(y)).astype(np.int64)
        expect = (
            (nx >= int(sfc.lon.normalize(-10)))
            & (nx <= int(sfc.lon.normalize(30)))
            & (ny >= int(sfc.lat.normalize(35)))
            & (ny <= int(sfc.lat.normalize(60)))
        )
        np.testing.assert_array_equal(got, expect)
        # at 31-bit cells loose == exact for any practical box
        exact = evaluate_host(
            parse_ecql("BBOX(geom, -10, 35, 30, 60)"), all_batch
        )
        assert not np.any(exact & ~got)


# -- pushdown stats (StatsIterator analog) ----------------------------------


class TestDeviceStats:
    ECQL = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )
    SPEC = 'Count();MinMax("val");MinMax("dtg");Histogram("val",10,0,100)'

    def _host_oracle(self, ds, ecql, spec):
        from geomesa_tpu.process import run_stats

        return run_stats(ds, "t", ecql, spec)

    def test_fused_stats_match_host_oracle(self):
        ds = _store(n=20000)
        di = DeviceIndex(ds, "t")
        got = di.stats(self.ECQL, self.SPEC)
        exp = self._host_oracle(ds, self.ECQL, self.SPEC)
        g, e = got.to_json(), exp.to_json()
        assert g[0] == e[0]  # count
        assert g[1]["min"] == e[1]["min"] and g[1]["max"] == e[1]["max"]
        assert g[2]["min"] == e[2]["min"] and g[2]["max"] == e[2]["max"]  # dtg i64
        assert g[3]["counts"] == e[3]["counts"]

    def test_host_fallback_parts_still_exact(self):
        ds = _store(n=5000)
        di = DeviceIndex(ds, "t")
        spec = 'Count();TopK("name")'  # TopK is a host stat
        got = di.stats(self.ECQL, spec)
        exp = self._host_oracle(ds, self.ECQL, spec)
        assert got.to_json() == exp.to_json()

    def test_residual_filter_falls_back_entirely(self):
        ds = _store(n=5000)
        di = DeviceIndex(ds, "t")
        ecql = "name = 'a' AND BBOX(geom, -90, -45, 90, 45)"
        got = di.stats(ecql, 'Count();MinMax("val")')
        exp = self._host_oracle(ds, ecql, 'Count();MinMax("val")')
        assert got.to_json() == exp.to_json()

    def test_loose_stats_use_key_planes(self):
        ds = _store(n=8000)
        di = DeviceIndex(ds, "t", z_planes=True)
        got = di.stats(self.ECQL, "Count()", loose=True)
        assert got.stats[0].count == di.count(self.ECQL, loose=True)

    def test_streaming_stats_respect_validity(self):
        from geomesa_tpu.device_cache import StreamingDeviceIndex

        ds = _store(n=6000)
        di = StreamingDeviceIndex(ds, "t")
        before = di.stats(self.ECQL, 'Count();MinMax("val")')
        n0 = before.stats[0].count
        hits = di.query(self.ECQL)
        di.evict(hits.fids[:15])
        after = di.stats(self.ECQL, "Count()")
        assert after.stats[0].count == n0 - 15

    def test_empty_result_leaves_minmax_unset(self):
        ds = _store(n=1000)
        di = DeviceIndex(ds, "t")
        got = di.stats("BBOX(geom, 170, 80, 171, 81) AND "
                       "dtg DURING 2020-01-10T00:00:00Z/2020-01-11T00:00:00Z",
                       'Count();MinMax("val")')
        if got.stats[0].count == 0:
            assert got.stats[1].min is None

    def test_repeated_calls_reuse_compiled_fused_fn(self):
        ds = _store(n=2000)
        di = DeviceIndex(ds, "t")
        di.stats(self.ECQL, self.SPEC)
        assert len(di._agg_cache) == 1
        di.stats(self.ECQL, self.SPEC)
        assert len(di._agg_cache) == 1

    def test_inverted_time_window_loose_returns_empty(self):
        """Regression: an inverted DURING window must yield an empty loose
        result, not crash in np.stack over zero bins."""
        ds = _store(n=500)
        di = DeviceIndex(ds, "t", z_planes=True)
        q = ("BBOX(geom, -10, 35, 30, 60) AND "
             "dtg DURING 2020-02-01T00:00:00Z/2020-01-01T00:00:00Z")
        assert di.count(q, loose=True) == 0
        assert len(di.query(q, loose=True)) == 0

    def test_two_histograms_same_attr_do_not_collide(self):
        ds = _store(n=3000)
        di = DeviceIndex(ds, "t")
        spec = 'Histogram("val",10,0,100);Histogram("val",5,0,50)'
        got = di.stats(self.ECQL, spec)
        exp = self._host_oracle(ds, self.ECQL, spec)
        assert got.to_json() == exp.to_json()

    def test_stats_on_empty_index(self):
        ds = MemoryDataStore()
        ds.create_schema("t", SPEC)
        di = DeviceIndex(ds, "t")
        got = di.stats("INCLUDE", 'Count();MinMax("val")')
        assert got.stats[0].count == 0
        assert got.stats[1].min is None

    def test_missing_resident_columns_fall_back_to_host(self):
        import warnings

        ds = _store(n=2000)
        di = DeviceIndex(ds, "t", columns=["val"])  # no geom planes
        all_batch = ds.query("t").batch
        ecql = "BBOX(geom, -10, 35, 30, 60) AND val >= 50"
        expect = evaluate_host(parse_ecql(ecql), all_batch)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            assert di.count(ecql) == int(expect.sum())
            np.testing.assert_array_equal(
                np.sort(di.query(ecql).fids),
                np.sort(all_batch.fids[expect]),
            )


def test_streaming_index_tracks_live_expiry():
    """Expiry is a state change like any Remove: an attached delta cache
    must see it, not silently diverge (live.py _expire notifies
    listeners with the expired fids)."""
    from geomesa_tpu.device_cache import StreamingDeviceIndex
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.query.runner import QueryResult
    from geomesa_tpu.stream import LiveFeatureStore

    now = [1_000_000]
    sft = SimpleFeatureType.create("t", SPEC)
    live = LiveFeatureStore(sft, expiry_ms=500, clock=lambda: now[0])

    class Adapter:
        def get_schema(self, _):
            return sft

        def query(self, _, q=None):
            b = live.snapshot()
            return QueryResult(b, None, len(b), len(b))

    di = StreamingDeviceIndex(Adapter(), "t", capacity=4096)
    di.attach_live(live)
    live.put({"name": ["a"] * 5, "val": np.arange(5), "dtg": np.zeros(5),
              "geom": np.zeros((5, 2))}, [f"f{i}" for i in range(5)])
    assert len(di) == 5
    now[0] += 300
    live.put({"name": ["b"] * 2, "val": np.arange(2), "dtg": np.zeros(2),
              "geom": np.zeros((2, 2))}, ["g0", "g1"])
    assert len(di) == 7
    now[0] += 300  # first 5 rows are now older than 500ms
    assert len(live) == 2  # triggers expiry + listener notification
    assert len(di) == 2, "device cache missed the expiry"
    assert di.count("INCLUDE") == 2


# -- non-point (XZ extent-curve) resident serving ---------------------------

POLY_SPEC = "name:String,dtg:Date,*geom:Polygon:srid=4326"


def _poly_wkt(x, y, w, h):
    return (
        f"POLYGON (({x} {y}, {x + w} {y}, {x + w} {y + h}, "
        f"{x} {y + h}, {x} {y}))"
    )


def _poly_store(n=4000, seed=7, with_time=True):
    spec = POLY_SPEC if with_time else "name:String,*geom:Polygon:srid=4326"
    ds = MemoryDataStore()
    ds.create_schema("p", spec)
    rng = np.random.default_rng(seed)
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    cols = {
        "name": rng.choice(["a", "b", "c"], n),
        "geom": np.array(
            [
                _poly_wkt(
                    rng.uniform(-170, 160),
                    rng.uniform(-85, 75),
                    rng.uniform(0.01, 5.0),
                    rng.uniform(0.01, 5.0),
                )
                for _ in range(n)
            ],
            dtype=object,
        ),
    }
    if with_time:
        cols["dtg"] = rng.integers(t0, t1, n)
    ds.write("p", cols, fids=np.arange(n))
    return ds


def test_nonpoint_stages_xz_key_planes():
    from geomesa_tpu.device_cache import Z_BIN, Z_HI, Z_LO

    ds = _poly_store(n=500)
    di = DeviceIndex(ds, "p", z_planes=True)
    assert di._z_kind == "xz3"
    assert Z_BIN in di._cols and Z_HI in di._cols and Z_LO in di._cols
    ds2 = _poly_store(n=500, with_time=False)
    di2 = DeviceIndex(ds2, "p", z_planes=True)
    assert di2._z_kind == "xz2"
    assert Z_HI in di2._cols and Z_BIN not in di2._cols


def test_nonpoint_loose_scan_is_superset_and_exact_query_matches():
    """Loose xz mask: cell-granular superset of the exact bbox hits; the
    exact (non-loose) path equals the store oracle."""
    ds = _poly_store()
    di = DeviceIndex(ds, "p", z_planes=True)
    all_batch = ds.query("p").batch
    ecql = (
        "BBOX(geom, -5, 42, 8, 51) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )
    expect = evaluate_host(parse_ecql(ecql), all_batch)
    exact = di.count(ecql, loose=False)
    assert exact == int(expect.sum())
    loose = di.count(ecql, loose=True)
    assert loose >= exact
    lm = di.mask(ecql, loose=True)
    em = di.mask(ecql, loose=False)
    assert not np.any(em & ~lm), "loose xz mask dropped an exact hit"
    # exact query results identical to the oracle
    got = di.query(ecql, loose=False)
    np.testing.assert_array_equal(
        np.sort(got.fids), np.sort(all_batch.fids[expect])
    )


def test_nonpoint_xz2_loose_scan():
    ds = _poly_store(with_time=False)
    di = DeviceIndex(ds, "p", z_planes=True)
    all_batch = ds.query("p").batch
    ecql = "BBOX(geom, -5, 42, 8, 51)"
    expect = evaluate_host(parse_ecql(ecql), all_batch)
    assert di.count(ecql, loose=False) == int(expect.sum())
    lm = di.mask(ecql, loose=True)
    em = di.mask(ecql, loose=False)
    assert lm.sum() >= em.sum()
    assert not np.any(em & ~lm)
    # pruning actually happens for a small window
    assert lm.sum() < len(all_batch)


def test_nonpoint_loose_stats_fused():
    """Count stat through the fused loose path on xz key planes."""
    ds = _poly_store()
    di = DeviceIndex(ds, "p", z_planes=True)
    ecql = (
        "BBOX(geom, -5, 42, 8, 51) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )
    seq = di.stats(ecql, "Count()", loose=True)
    assert seq.stats[0].count == di.count(ecql, loose=True)


def test_staging_device_encode_matches_numpy_oracle():
    """Staging encodes keys on DEVICE; planes
    must be bit-identical to the host numpy oracle for every kind."""
    from geomesa_tpu.device_cache import _z_planes_np

    for mk, kind in [
        # dim_planes=False: z3 exercises the INTERLEAVED device encode
        # here (the dim-plane staging parity lives in test_dimplane_cache)
        (lambda: _store(n=3000), "z3"),
        (lambda: _poly_store(n=1500), "xz3"),
        (lambda: _poly_store(n=1500, with_time=False), "xz2"),
    ]:
        ds = mk()
        tn = ds.type_names[0]
        di = DeviceIndex(ds, tn, z_planes=True, dim_planes=False)
        assert di._z_kind == kind
        # the DEVICE path must have produced the planes: a latched fallback
        # would make this parity test vacuously compare oracle to oracle
        assert not di._z_encode_failed and di._z_encode_jit is not None
        batch = ds.query(tn).batch
        np_kind, np_planes, _bins = _z_planes_np(batch, di.sft)
        assert np_kind == kind
        for k, v in np_planes.items():
            np.testing.assert_array_equal(
                np.asarray(di._cols[k])[: len(batch)], v, err_msg=f"{kind}:{k}"
            )


def test_staging_device_encode_z2_and_x64_scoping():
    """z2 staging parity + the scoped-x64 encode must not leak x64 into
    the caller's config."""
    import jax

    from geomesa_tpu.device_cache import _z_planes_np

    ds = MemoryDataStore()
    ds.create_schema("z2t", "val:Int,*geom:Point:srid=4326")
    rng = np.random.default_rng(3)
    n = 2000
    ds.write(
        "z2t",
        {
            "val": rng.integers(0, 9, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
            ),
        },
    )
    before = jax.config.jax_enable_x64
    # dim_planes=False: this test checks the INTERLEAVED z2 encode parity
    # (z2 now stages dim planes by default; see test_dimplane_cache)
    di = DeviceIndex(ds, "z2t", z_planes=True, dim_planes=False)
    assert jax.config.jax_enable_x64 == before
    assert di._z_kind == "z2"
    batch = ds.query("z2t").batch
    _, np_planes, _bins = _z_planes_np(batch, di.sft)
    for k, v in np_planes.items():
        np.testing.assert_array_equal(np.asarray(di._cols[k]), v)


# -- pushdown density + BIN -------------------------


class TestFusedDensityAndBin:
    ECQL = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-01T00:00:00Z"
    )

    def test_density_fused_matches_host(self):
        from geomesa_tpu.geom import Envelope
        from geomesa_tpu.process.density import _density_host

        ds = _store(n=8000)
        di = DeviceIndex(ds, "t", z_planes=True)
        env = Envelope(-10, 35, 30, 60)
        grid = di.density(self.ECQL, env, 64, 32)
        assert grid is not None and grid.shape == (32, 64)
        # host oracle over the exact hit set
        all_batch = ds.query("t").batch
        m = evaluate_host(parse_ecql(self.ECQL), all_batch)
        x, y = all_batch.point_coords()
        ref = _density_host(x[m], y[m], np.ones(int(m.sum())), env, 64, 32)
        np.testing.assert_allclose(grid, ref, rtol=1e-5)
        assert grid.sum() > 0

    def test_density_weighted_and_loose_superset(self):
        from geomesa_tpu.geom import Envelope

        ds = _store(n=6000)
        di = DeviceIndex(ds, "t", z_planes=True)
        env = Envelope(-10, 35, 30, 60)
        gw = di.density(self.ECQL, env, 32, 32, weight_attr="val")
        assert gw is not None
        all_batch = ds.query("t").batch
        m = evaluate_host(parse_ecql(self.ECQL), all_batch)
        w = all_batch.column("val")[m].astype(np.float64)
        np.testing.assert_allclose(float(gw.sum()), w.sum(), rtol=1e-5)
        # loose mode: cell-granular superset -> total mass >= exact
        gl = di.density(self.ECQL, env, 32, 32, loose=True)
        assert gl is not None
        ge = di.density(self.ECQL, env, 32, 32, loose=False)
        assert gl.sum() >= ge.sum()

    def test_density_process_routes_through_resident(self, monkeypatch):
        """process.density with a device_index must not materialize a
        feature batch from the store."""
        from geomesa_tpu.geom import Envelope
        from geomesa_tpu.process import density as density_fn

        ds = _store(n=3000)
        di = DeviceIndex(ds, "t", z_planes=True)
        calls = []
        real_query = ds.query
        monkeypatch.setattr(
            ds, "query", lambda *a, **k: (calls.append(1), real_query(*a, **k))[1]
        )
        env = Envelope(-10, 35, 30, 60)
        grid = density_fn(ds, "t", self.ECQL, env, 32, 32, device_index=di)
        assert not calls, "resident density still hit the store query path"
        assert grid.shape == (32, 32)

    def test_bin_export_matches_batch_encoder(self):
        from geomesa_tpu.process.binexport import decode_bin, encode_bin

        ds = _store(n=4000)
        di = DeviceIndex(ds, "t", z_planes=True)
        data = di.bin_export(self.ECQL, track_attr="name", sort=True)
        # oracle: full query then the batch-level encoder
        hits = ds.query("t", self.ECQL).batch
        ref = encode_bin(hits, "name", sort=True)
        assert data == ref
        rec = decode_bin(data)
        assert len(rec) == len(hits)

    def test_run_stats_routes_through_device_index(self, monkeypatch):
        from geomesa_tpu.process import run_stats

        ds = _store(n=3000)
        di = DeviceIndex(ds, "t", z_planes=True)
        calls = []
        real_query = ds.query
        monkeypatch.setattr(
            ds, "query", lambda *a, **k: (calls.append(1), real_query(*a, **k))[1]
        )
        seq = run_stats(ds, "t", self.ECQL, "Count()", device_index=di)
        assert not calls, "resident stats still hit the store query path"
        all_batch = real_query("t").batch
        m = evaluate_host(parse_ecql(self.ECQL), all_batch)
        assert seq.stats[0].count == int(m.sum())

    def test_density_viewport_is_runtime_not_recompile(self):
        """Different bboxes reuse ONE compiled dispatch (the viewport is a
        runtime array, not a trace constant)."""
        from geomesa_tpu.geom import Envelope

        ds = _store(n=2000)
        di = DeviceIndex(ds, "t", z_planes=True)
        g1 = di.density(self.ECQL, Envelope(-10, 35, 30, 60), 32, 32)
        n_cached = len(di._agg_cache)
        g2 = di.density(self.ECQL, Envelope(0, 40, 20, 55), 32, 32)
        assert len(di._agg_cache) == n_cached  # same entry, new viewport
        assert g1 is not None and g2 is not None
        assert not np.array_equal(g1, g2)  # different windows, real effect


# -- per-auth resident serving -----------------------


class TestPerAuthResident:
    def _labeled_store(self, n=4000, seed=19, labels=("", "A", "B", "A&B", "A|B")):
        from geomesa_tpu.features.batch import FeatureBatch

        ds = MemoryDataStore()
        ds.create_schema("s", SPEC)
        rng = np.random.default_rng(seed)
        t0 = parse_instant("2020-01-01T00:00:00")
        t1 = parse_instant("2020-03-01T00:00:00")
        batch = FeatureBatch.from_columns(
            ds.get_schema("s"),
            {
                "name": rng.choice(["a", "b"], n),
                "val": rng.integers(0, 100, n),
                "dtg": rng.integers(t0, t1, n),
                "geom": np.stack(
                    [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)],
                    axis=1,
                ),
            },
            fids=np.arange(n),
        ).with_visibility(rng.choice(labels, n))
        ds.write("s", batch)
        return ds

    ECQL = (
        "BBOX(geom, -60, -30, 60, 30) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-02-20T00:00:00Z"
    )

    def _oracle_fids(self, ds, ecql, auths):
        from geomesa_tpu.query.plan import Query

        return set(
            ds.query("s", Query(ecql, hints={"auths": auths}))
            .batch.fids.tolist()
        )

    def test_query_count_stats_match_store_per_auth(self):
        ds = self._labeled_store()
        di = DeviceIndex(ds, "s", z_planes=True)
        for auths in [(), ("A",), ("B",), ("A", "B"), ("C",), None]:
            want = self._oracle_fids(ds, self.ECQL, auths or ())
            got = di.query(self.ECQL, auths=auths)
            assert set(got.fids.tolist()) == want, f"auths={auths}"
            assert di.count(self.ECQL, auths=auths) == len(want)
            seq = di.stats(self.ECQL, "Count()", auths=auths)
            assert seq.stats[0].count == len(want)

    def test_default_fails_closed(self):
        """No auths argument at all behaves exactly like auths=() —
        labeled rows hidden."""
        ds = self._labeled_store()
        di = DeviceIndex(ds, "s", z_planes=True)
        want = self._oracle_fids(ds, self.ECQL, ())
        assert set(di.query(self.ECQL).fids.tolist()) == want

    def test_loose_per_auth_superset(self):
        ds = self._labeled_store()
        di = DeviceIndex(ds, "s", z_planes=True)
        exact = di.count(self.ECQL, auths=("A",), loose=False)
        loose = di.count(self.ECQL, auths=("A",), loose=True)
        assert loose >= exact > 0
        em = di.mask(self.ECQL, auths=("A",), loose=False)
        lm = di.mask(self.ECQL, auths=("A",), loose=True)
        assert not np.any(em & ~lm)

    def test_density_per_auth(self):
        from geomesa_tpu.geom import Envelope

        ds = self._labeled_store()
        di = DeviceIndex(ds, "s", z_planes=True)
        env = Envelope(-60, -30, 60, 30)
        g_a = di.density(self.ECQL, env, 32, 32, auths=("A", "B"))
        g_none = di.density(self.ECQL, env, 32, 32)
        assert g_a.sum() == di.count(self.ECQL, auths=("A", "B"))
        assert g_none.sum() == di.count(self.ECQL)
        assert g_a.sum() > g_none.sum()

    def test_fuzz_random_filters_vs_store(self):
        """Differential fuzz: random bbox/attr filters x auth sets, the
        resident per-auth result set must equal the store path's."""
        from geomesa_tpu.query.plan import Query

        ds = self._labeled_store(n=2500, seed=31)
        di = DeviceIndex(ds, "s", z_planes=True)
        rng = np.random.default_rng(7)
        auth_sets = [(), ("A",), ("B",), ("A", "B"), ("Z",)]
        for i in range(12):
            x0 = rng.uniform(-180, 120)
            y0 = rng.uniform(-90, 60)
            w = rng.uniform(5, 120)
            v = rng.integers(0, 100)
            ecql = (
                f"BBOX(geom, {x0:.3f}, {y0:.3f}, {x0 + w:.3f}, "
                f"{y0 + w / 2:.3f}) AND val >= {v}"
            )
            auths = auth_sets[i % len(auth_sets)]
            want = self._oracle_fids(ds, ecql, auths)
            got = set(di.query(ecql, auths=auths).fids.tolist())
            assert got == want, f"{ecql} auths={auths}"

    def test_vocab_overflow_falls_back_public_only(self):
        """Past VIS_VOCAB_MAX distinct labels, labeled rows leave the
        resident copy (loudly) and only public rows serve."""
        import pytest

        ds = self._labeled_store(
            n=300, labels=tuple(f"L{i}" for i in range(40)) + ("",)
        )
        class Small(DeviceIndex):
            VIS_VOCAB_MAX = 8

        with pytest.warns(RuntimeWarning, match="vocabulary"):
            di = Small(ds, "s", z_planes=True)
        # resident copy holds only the public rows now
        from geomesa_tpu.query.plan import Query

        pub = self._oracle_fids(ds, "INCLUDE", ())
        assert set(di.query("INCLUDE", auths=("L1",)).fids.tolist()) == pub
        # the store path still serves the labeled rows
        with_l1 = self._oracle_fids(ds, "INCLUDE", ("L1",))
        assert with_l1 > pub

    def test_streaming_labeled_appends(self):
        """Labels arriving mid-stream on an unlabeled store trigger the
        plane-introducing restage; per-auth results stay exact."""
        from geomesa_tpu.device_cache import StreamingDeviceIndex
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.query.plan import Query

        ds = _store(n=1000)  # unlabeled base
        di = StreamingDeviceIndex(ds, "t", z_planes=True)
        sft = ds.get_schema("t")
        rng = np.random.default_rng(5)
        t0 = parse_instant("2020-01-15T00:00:00")
        labeled = FeatureBatch.from_columns(
            sft,
            {
                "name": ["a"] * 50,
                "val": rng.integers(0, 100, 50),
                "dtg": np.full(50, t0),
                "geom": np.stack(
                    [rng.uniform(-10, 10, 50), rng.uniform(-10, 10, 50)],
                    axis=1,
                ),
            },
            fids=np.arange(90_000, 90_050),
        ).with_visibility(["secret"] * 50)
        ds.write("t", labeled)
        di.upsert(labeled)
        ecql = "BBOX(geom, -10, -10, 10, 10)"
        no_auth = di.count(ecql)
        with_auth = di.count(ecql, auths=("secret",))
        assert with_auth == no_auth + 50
        want = set(
            ds.query("t", Query(ecql, hints={"auths": ("secret",)}))
            .batch.fids.tolist()
        )
        got = set(di.query(ecql, auths=("secret",)).fids.tolist())
        assert got == want
