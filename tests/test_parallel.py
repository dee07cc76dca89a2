"""Multi-chip (8 virtual CPU devices) mesh tests: sharded scan count,
radix-exchange distributed sort."""

import numpy as np
import pytest

from geomesa_tpu.curves import Z3SFC
from geomesa_tpu.parallel import (
    distributed_z3_sort,
    make_mesh,
    sharded_build_and_query_step,
    sharded_count_scan,
)
from geomesa_tpu.parallel.dist import distributed_sort


@pytest.fixture(scope="module")
def mesh():
    import jax

    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(8)


def test_sharded_count_matches_host(mesh, rng):
    import jax.numpy as jnp

    n = 8 * 1024
    x = rng.uniform(-180, 180, n).astype(np.float32)
    y = rng.uniform(-90, 90, n).astype(np.float32)

    def device_fn(cols):
        return (cols["x"] >= -10) & (cols["x"] <= 30) & (cols["y"] >= 0)

    count = int(
        sharded_count_scan(
            mesh, device_fn, {"x": jnp.asarray(x), "y": jnp.asarray(y)}
        )
    )
    assert count == int(((x >= -10) & (x <= 30) & (y >= 0)).sum())


def test_distributed_sort_globally_ordered(mesh, rng):
    import jax.numpy as jnp

    n = 8 * 2048
    hi = rng.integers(0, 1 << 31, n).astype(np.uint32)
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    sh, sl, sv = distributed_z3_sort(mesh, jnp.asarray(hi), jnp.asarray(lo))
    sh, sl, sv = np.asarray(sh), np.asarray(sl), np.asarray(sv)
    per = len(sh) // 8
    all_valid = []
    prev_max = -1
    for s in range(8):
        h = sh[s * per : (s + 1) * per]
        l = sl[s * per : (s + 1) * per]
        v = sv[s * per : (s + 1) * per]
        z = (h[v].astype(np.uint64) << np.uint64(32)) | l[v].astype(np.uint64)
        assert np.all(np.diff(z.astype(np.int64)) >= 0), f"shard {s} not sorted"
        if len(z):
            assert int(z[0]) >= prev_max, "shards out of global order"
            prev_max = int(z[-1])
        all_valid.append(z)
    merged = np.concatenate(all_valid)
    expected = np.sort(
        (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    )
    # no drops with uniform data at capacity 2x
    np.testing.assert_array_equal(merged, expected)


class TestExchangeAtScale:
    """The exchange's capacity math and wall
    clock, proven at 2^22 rows over 8 virtual devices — uniform, sorted,
    all-duplicate and clustered layouts must all complete with ZERO
    overflow at the default capacity factor, return a correct global
    sort with an intact row-id payload, and finish within a wall-clock
    bound."""

    N = 1 << 22

    def _layout(self, name, rng):
        n = self.N
        if name == "uniform":
            hi = rng.integers(0, 1 << 31, n).astype(np.uint32)
            lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(
                np.uint32
            )
        elif name == "sorted":
            hi = np.sort(rng.integers(0, 1 << 31, n)).astype(np.uint32)
            lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(
                np.uint32
            )
        elif name == "duplicate":
            hi = np.full(n, 0x12345678, np.uint32)
            lo = np.full(n, 0x9ABCDEF0, np.uint32)
        else:  # clustered: 99% of keys in 4 tiny hot ranges
            centers = np.array(
                [0x100, 0x7FFF0000, 0x40000000, 0x2AAA0000], np.uint32
            )
            which = rng.integers(0, 4, n)
            off = rng.integers(0, 64, n).astype(np.uint32)
            hi = centers[which] + off
            cold = rng.random(n) < 0.01
            hi[cold] = rng.integers(0, 1 << 31, int(cold.sum())).astype(
                np.uint32
            )
            lo = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(
                np.uint32
            )
        return hi, lo

    @pytest.mark.parametrize(
        "layout", ["uniform", "sorted", "duplicate", "clustered"]
    )
    def test_2m_rows_zero_overflow_sorted_with_payload(self, mesh, layout):
        import time

        import jax.numpy as jnp

        rng = np.random.default_rng(hash(layout) % (1 << 31))
        hi, lo = self._layout(layout, rng)
        rid = np.arange(self.N, dtype=np.uint32)
        t0 = time.perf_counter()
        # on_overflow='raise' IS the zero-overflow assertion at the
        # default capacity_factor
        (sh, sl), pay, sv = distributed_sort(
            mesh, (jnp.asarray(hi), jnp.asarray(lo)),
            payload={"rid": jnp.asarray(rid)},
        )
        sh = np.asarray(sh)
        wall = time.perf_counter() - t0
        # generous bound: 2^22 rows through two all_to_all passes + local
        # sorts on an 8-virtual-device CPU mesh takes ~1-5s; a capacity
        # or routing regression shows up as minutes (or a raise above)
        assert wall < 120, f"{layout}: exchange took {wall:.0f}s"
        sl, sv = np.asarray(sl), np.asarray(sv)
        rid_out = np.asarray(pay["rid"])
        z = (sh.astype(np.uint64) << np.uint64(32)) | sl.astype(np.uint64)
        zin = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(
            np.uint64
        )
        per = len(sh) // 8
        prev_max = -1
        got = []
        for s in range(8):
            vs = sv[s * per : (s + 1) * per]
            zs = z[s * per : (s + 1) * per][vs]
            assert np.all(np.diff(zs.astype(np.int64)) >= 0), (
                f"{layout}: shard {s} not locally sorted"
            )
            if len(zs):
                assert int(zs[0]) >= prev_max, (
                    f"{layout}: shards out of global order"
                )
                prev_max = int(zs[-1])
            got.append(zs)
            # the payload permutation must reproduce the keys it rode with
            rs = rid_out[s * per : (s + 1) * per][vs]
            np.testing.assert_array_equal(
                zin[rs], zs, err_msg=f"{layout}: rid payload mispermuted"
            )
        merged = np.concatenate(got)
        assert len(merged) == self.N  # zero rows lost
        np.testing.assert_array_equal(merged, np.sort(zin))


def test_full_build_and_query_step(mesh, rng):
    import jax.numpy as jnp

    n = 8 * 1024
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    t = rng.uniform(0, 604800, n)
    sfc = Z3SFC()
    bounds = (-10.0, 0.0, 30.0, 40.0, 10000.0, 300000.0)
    sh, sl, sv, count, key_count, hit_rids, hit_valid = (
        sharded_build_and_query_step(
            mesh, sfc, jnp.asarray(x), jnp.asarray(y), jnp.asarray(t), bounds
        )
    )
    expected = int(
        (
            (x >= bounds[0])
            & (x <= bounds[2])
            & (y >= bounds[1])
            & (y <= bounds[3])
            & (t >= bounds[4])
            & (t <= bounds[5])
        ).sum()
    )
    assert int(count) == expected
    # sorted keys match host-side encode of the same points
    z_host = np.sort(sfc.index(x, y, t))
    sh, sl, sv = np.asarray(sh), np.asarray(sl), np.asarray(sv)
    z_dev = (
        (sh[sv].astype(np.uint64) << np.uint64(32)) | sl[sv].astype(np.uint64)
    )
    # global order: concatenation of shards ascending
    np.testing.assert_array_equal(np.sort(z_dev), z_host)
    # the query THROUGH the sorted index returns the exact row-id set the
    # quantized-cell host oracle predicts (weak #6: key corruption in the
    # exchange would change this set)
    nx = sfc.lon.normalize(x).astype(np.int64)
    ny = sfc.lat.normalize(y).astype(np.int64)
    nt = sfc.time.normalize(t).astype(np.int64)
    cell = (
        (nx >= int(sfc.lon.normalize(bounds[0])))
        & (nx <= int(sfc.lon.normalize(bounds[2])))
        & (ny >= int(sfc.lat.normalize(bounds[1])))
        & (ny <= int(sfc.lat.normalize(bounds[3])))
        & (nt >= int(sfc.time.normalize(bounds[4])))
        & (nt <= int(sfc.time.normalize(bounds[5])))
    )
    got = np.asarray(hit_rids)[np.asarray(hit_valid)]
    assert int(key_count) == int(cell.sum()) == len(got)
    np.testing.assert_array_equal(np.sort(got), np.nonzero(cell)[0])


def test_sharded_query_scan_returns_features(mesh, rng):
    """The mesh-wide scan streams back row ids AND payload columns, not a
    count (the BatchScanPlan analog); truncation is loud."""
    import jax.numpy as jnp
    import pytest

    from geomesa_tpu.parallel import sharded_query_scan

    n = 8 * 512
    x = rng.uniform(-180, 180, n)
    val = rng.integers(0, 1000, n).astype(np.int32)
    rid = np.arange(n, dtype=np.uint32)
    fn = lambda local: (local["x"] >= 0) & (local["x"] <= 20)  # noqa: E731
    ids, valid, pay, total = sharded_query_scan(
        make_mesh(8),
        fn,
        {"x": jnp.asarray(x)},
        jnp.asarray(rid),
        payload={"val": jnp.asarray(val)},
    )
    expect = (x >= 0) & (x <= 20)
    got_ids = np.asarray(ids)[np.asarray(valid)]
    assert int(total) == int(expect.sum()) == len(got_ids)
    np.testing.assert_array_equal(np.sort(got_ids), np.nonzero(expect)[0])
    # payload rows ride aligned with their ids
    got_val = np.asarray(pay["val"])[np.asarray(valid)]
    order = np.argsort(got_ids)
    np.testing.assert_array_equal(got_val[order], val[expect])
    # a tiny cap truncates loudly
    with pytest.raises(RuntimeError, match="truncated"):
        sharded_query_scan(
            make_mesh(8),
            fn,
            {"x": jnp.asarray(x)},
            jnp.asarray(rid),
            cap_per_shard=1,
            payload={"val": jnp.asarray(val)},
        )


def test_sampled_splitters_survive_skew(mesh):
    """All points in one hot cell: radix routing overflows one destination
    and drops rows; sampled splitters keep every row and stay globally
    sorted (SURVEY hard part #5, GDELT skew)."""
    import jax.numpy as jnp

    n = 4096
    rng = np.random.default_rng(3)
    # a single ~1km cell: all z keys share their high bits
    x = rng.uniform(2.350, 2.351, n)
    y = rng.uniform(48.850, 48.851, n)
    t = rng.uniform(0, 3600.0, n)
    sfc = Z3SFC()
    hi, lo = sfc.index_jax_hi_lo(jnp.asarray(x), jnp.asarray(y), jnp.asarray(t))

    # radix routing overflows and must be LOUD by default
    with pytest.raises(RuntimeError, match="dropped"):
        distributed_z3_sort(mesh, hi, lo, splitters="radix")
    with pytest.warns(RuntimeWarning, match="dropped"):
        rh, rl, rv = distributed_z3_sort(
            mesh, hi, lo, splitters="radix", on_overflow="warn"
        )
    dropped_radix = n - int(np.asarray(rv).sum())
    assert dropped_radix > 0  # the skew actually defeats radix routing

    sh, sl, sv = distributed_z3_sort(mesh, hi, lo, splitters="sampled")
    assert int(np.asarray(sv).sum()) == n  # nothing dropped
    # global sortedness: concatenated valid keys are non-decreasing
    h = np.asarray(sh)[np.asarray(sv)]
    l = np.asarray(sl)[np.asarray(sv)]
    z = (h.astype(np.uint64) << np.uint64(32)) | l.astype(np.uint64)
    # per-shard slices are sorted and shard s's max <= shard s+1's min
    per = np.asarray(sv).reshape(8, -1)
    zs = np.asarray(sh).astype(np.uint64).reshape(8, -1) << np.uint64(32)
    zs |= np.asarray(sl).astype(np.uint64).reshape(8, -1)
    prev_max = None
    for s in range(8):
        vals = zs[s][per[s]]
        assert np.all(np.diff(vals.astype(np.int64)) >= 0)
        if len(vals):
            if prev_max is not None:
                assert vals[0] >= prev_max
            prev_max = vals[-1]


def test_multihost_helpers_single_process(mesh, rng):
    """The multi-host entry points must work unchanged on one process:
    initialize() no-ops, host slices become globally sharded arrays that
    collectives consume."""
    import jax

    from geomesa_tpu.parallel import (
        host_batches_to_global,
        initialize,
        sharded_count_scan,
    )
    from geomesa_tpu.parallel.multihost import global_mesh

    initialize()  # no coordinator configured -> no-op
    gm = global_mesh()
    assert gm.shape["shard"] == len(jax.devices())

    n = 1024
    cols = {
        "x": rng.uniform(-180, 180, n).astype(np.float32),
        "y": rng.uniform(-90, 90, n).astype(np.float32),
    }
    gcols = host_batches_to_global(mesh, cols)
    assert all(v.shape == (n,) for v in gcols.values())

    def fn(local):
        return (
            (local["x"] >= -10)
            & (local["x"] <= 30)
            & (local["y"] >= 35)
            & (local["y"] <= 60)
        )

    got = int(sharded_count_scan(mesh, fn, cols))
    want = int(
        (
            (cols["x"] >= -10)
            & (cols["x"] <= 30)
            & (cols["y"] >= 35)
            & (cols["y"] <= 60)
        ).sum()
    )
    assert got == want


def test_sampled_sort_adversarial_layouts(mesh):
    """Already-globally-sorted input (each source holds one quantile) and
    all-duplicate keys: both defeat naive splitter routing; the rebalance
    pass + tie spreading must keep every row."""
    import jax.numpy as jnp

    n = 4096
    # adversarial 1: globally sorted keys
    z = np.sort(np.random.default_rng(0).integers(0, 2**62, n).astype(np.uint64))
    hi = jnp.asarray((z >> np.uint64(32)).astype(np.uint32))
    lo = jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    sh, sl, sv = distributed_z3_sort(mesh, hi, lo, splitters="sampled")
    assert int(np.asarray(sv).sum()) == n
    got = np.sort(
        (np.asarray(sh).astype(np.uint64) << np.uint64(32))
        | np.asarray(sl).astype(np.uint64)
    )[:n]
    np.testing.assert_array_equal(np.sort(got), np.sort(z))

    # adversarial 2: every key identical
    hi2 = jnp.full(n, np.uint32(7), dtype=jnp.uint32)
    lo2 = jnp.full(n, np.uint32(9), dtype=jnp.uint32)
    sh2, sl2, sv2 = distributed_z3_sort(mesh, hi2, lo2, splitters="sampled")
    assert int(np.asarray(sv2).sum()) == n


def test_device_index_build_matches_host(mesh):
    """The mesh sort carries row payloads, so the
    device path builds a real queryable BuiltIndex -- bit-identical sorted
    keys and the same query results as the host lexsort build."""
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.index.build import build_index_device
    from geomesa_tpu.query.runner import run_query
    from geomesa_tpu.store import MemoryDataStore

    store = MemoryDataStore(partition_size=2048)
    store.create_schema("pts", "name:String,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(17)
    n = 20000
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    store.write(
        "pts",
        {
            "name": rng.choice(["a", "b", "c"], n),
            "dtg": rng.integers(t0, t1, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    ecql = (
        "BBOX(geom, -5, 42, 8, 51) AND "
        "dtg DURING 2020-01-05T00:00:00Z/2020-02-20T00:00:00Z"
    )
    plan = store.plan("pts", ecql)  # flushes + builds host indices
    assert plan.index_name == "z3"
    host_built = store._state("pts").indices["z3"]
    dev_built = build_index_device(
        host_built.keyspace, store._state("pts").data, mesh, partition_size=2048
    )
    # bit-identical sorted key columns (device encode == host encode)
    np.testing.assert_array_equal(dev_built.keys["bin"], host_built.keys["bin"])
    np.testing.assert_array_equal(dev_built.keys["z"], host_built.keys["z"])
    np.testing.assert_array_equal(dev_built.batch.fids, host_built.batch.fids)
    assert len(dev_built.partitions) == len(host_built.partitions)
    # the same query plan scans both indices to the same result set
    r_host = run_query(host_built, plan)
    r_dev = run_query(dev_built, plan)
    assert len(r_host) > 0
    assert set(r_dev.batch.fids.tolist()) == set(r_host.batch.fids.tolist())


def test_distributed_sort_payload_travels_with_rows(mesh):
    """Column payloads (not just row ids) ride the exchange: each surviving
    row's payload must still equal f(key)."""
    import jax.numpy as jnp

    from geomesa_tpu.parallel import distributed_sort

    n = 8 * 512
    rng = np.random.default_rng(5)
    z = rng.integers(0, 2**62, n).astype(np.uint64)
    hi = jnp.asarray((z >> np.uint64(32)).astype(np.uint32))
    lo = jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    # payload derived from the key so misrouting is detectable
    pay = {
        "f": jnp.asarray((z % 1000).astype(np.float32)),
        "i": jnp.asarray((z % 255).astype(np.uint8)),
    }
    (sh, sl), pout, sv = distributed_sort(mesh, (hi, lo), payload=pay)
    sh, sl, sv = np.asarray(sh), np.asarray(sl), np.asarray(sv)
    zz = ((sh.astype(np.uint64) << np.uint64(32)) | sl.astype(np.uint64))[sv]
    np.testing.assert_array_equal(np.sort(zz), np.sort(z))
    np.testing.assert_array_equal(
        np.asarray(pout["f"])[sv], (zz % 1000).astype(np.float32)
    )
    np.testing.assert_array_equal(
        np.asarray(pout["i"])[sv], (zz % 255).astype(np.uint8)
    )


def test_sampled_sort_periodic_interleaved_clusters(mesh):
    """Rows alternating between two clusters (interleaved ingest from two
    sources) resonate with a plain i%n round-robin rebalance; the hashed
    shuffle must keep every row. Also covers tiny inputs where the
    per-destination mean is ~1 row."""
    import jax.numpy as jnp

    for n in (64, 4096):
        i = np.arange(n)
        z = np.where(i % 2 == 0, i * 7, (1 << 61) + i * 13).astype(np.uint64)
        hi = jnp.asarray((z >> np.uint64(32)).astype(np.uint32))
        lo = jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        sh, sl, sv = distributed_z3_sort(mesh, hi, lo, splitters="sampled")
        assert int(np.asarray(sv).sum()) == n, f"rows lost at n={n}"
        got = (
            (np.asarray(sh).astype(np.uint64) << np.uint64(32))
            | np.asarray(sl).astype(np.uint64)
        )[np.asarray(sv)]
        np.testing.assert_array_equal(got, np.sort(z))


def test_device_build_rejects_out_of_range_bins():
    """A bin beyond the int32 bias must raise, not silently mis-sort."""
    from geomesa_tpu.index.build import _BIN_BIAS, build_index_device
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.index.keyspaces import Z3KeySpace

    sft = SimpleFeatureType.create("b", "dtg:Date,*geom:Point:srid=4326")
    # dtg in ms; a WEEK bin of 2**31 needs ms ~ 2**31 * 604800000 -- beyond
    # int64? no: 1.3e18 < 9.2e18, representable
    ms = np.array([(2**31 + 5) * 604800000], dtype=np.int64)
    batch = FeatureBatch.from_columns(
        sft, {"dtg": ms, "geom": np.array([[0.0, 0.0]])}, np.arange(1)
    )
    with pytest.raises(ValueError, match="device-sortable"):
        build_index_device(Z3KeySpace("geom", "dtg"), batch, make_mesh(8))


def test_device_build_stable_over_duplicate_keys():
    """All-identical (bin, z) keys: the trailing row-id lane must make the
    device sort reproduce the host lexsort's stable tie order exactly."""
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.index.build import build_index, build_index_device
    from geomesa_tpu.index.keyspaces import Z3KeySpace

    sft = SimpleFeatureType.create("dup", "dtg:Date,*geom:Point:srid=4326")
    n = 64
    batch = FeatureBatch.from_columns(
        sft,
        {
            "dtg": np.full(n, 1577836800000, dtype=np.int64),
            "geom": np.tile([[2.35, 48.85]], (n, 1)),
        },
        np.arange(n),
    )
    ks = Z3KeySpace("geom", "dtg")
    host = build_index(ks, batch)
    for n_dev in (8, 1):
        dev = build_index_device(ks, batch, make_mesh(n_dev))
        np.testing.assert_array_equal(dev.batch.fids, host.batch.fids)
        np.testing.assert_array_equal(dev.keys["z"], host.keys["z"])


def test_distributed_sort_single_device_mesh(rng):
    """n_shards == 1 must skip the exchange (no radix lane assumptions)
    and still produce a sorted, loss-free result -- including for lanes
    with bit 31 set (biased bins)."""
    import jax.numpy as jnp

    from geomesa_tpu.parallel import distributed_sort

    n = 512
    lane0 = (rng.integers(0, 1 << 32, n, dtype=np.uint64)).astype(np.uint32)
    lane1 = (rng.integers(0, 1 << 32, n, dtype=np.uint64)).astype(np.uint32)
    (s0, s1), _, v = distributed_sort(
        make_mesh(1), (jnp.asarray(lane0), jnp.asarray(lane1))
    )
    assert int(np.asarray(v).sum()) == n
    z = (np.asarray(s0).astype(np.uint64) << np.uint64(32)) | np.asarray(
        s1
    ).astype(np.uint64)
    np.testing.assert_array_equal(
        z, np.sort((lane0.astype(np.uint64) << np.uint64(32)) | lane1)
    )


def test_radix_bit31_lane_no_silent_loss(mesh, rng):
    """A 32-bit lane 0 (bit 31 set) would previously scatter out of bounds
    and vanish rows without touching the overflow counter; dest clamping
    must keep them accounted for: every row either survives or is counted
    in the loud overflow error."""
    import jax.numpy as jnp

    from geomesa_tpu.parallel import distributed_sort

    n = 8 * 512
    lane0 = (rng.integers(0, 1 << 32, n, dtype=np.uint64)).astype(np.uint32)
    lane1 = (rng.integers(0, 1 << 32, n, dtype=np.uint64)).astype(np.uint32)
    try:
        (s0, s1), _, v = distributed_sort(
            mesh,
            (jnp.asarray(lane0), jnp.asarray(lane1)),
            splitters="radix",
            on_overflow="raise",
        )
        survivors = int(np.asarray(v).sum())
        assert survivors == n  # no error -> nothing may be missing
    except RuntimeError as e:
        # overflow is allowed (clamping skews the top half onto the last
        # shard) but it must be LOUD and fully accounted
        assert "dropped" in str(e)


def test_sharded_zscan_count_matches_host(mesh):
    """Mesh-wide key-only scan: per-shard masked compare + psum equals
    the host quantized-cell oracle."""
    import jax.numpy as jnp

    from geomesa_tpu.curves.binnedtime import to_binned_time
    from geomesa_tpu.curves.z3 import Z3SFC
    from geomesa_tpu.ops import zscan
    from geomesa_tpu.parallel.dist import sharded_zscan_count

    sfc = Z3SFC()
    rng = np.random.default_rng(31)
    n = 1 << 14
    lon = rng.uniform(-180, 180, n)
    lat = rng.uniform(-90, 90, n)
    t0 = np.datetime64("2020-01-06").astype("datetime64[ms]").astype(np.int64)
    t = t0 + rng.integers(0, 21 * 86400_000, n)
    bins_np, off = to_binned_time(t, sfc.period)
    z = sfc.index(lon, lat, off)
    bounds, ids = zscan.z3_query_bounds(
        sfc, -30.0, 20.0, 60.0, 70.0,
        int(t0 + 2 * 86400_000), int(t0 + 9 * 86400_000),
    )
    bounds, ids = zscan.pad_bins(bounds, ids)
    got = int(sharded_zscan_count(
        mesh,
        jnp.asarray(bins_np.astype(np.int32)),
        jnp.asarray((z >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        bounds, ids,
    ))
    expect = np.asarray(zscan.z3_zscan_mask(
        jnp.asarray((z >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray(bins_np.astype(np.int32)),
        jnp.asarray(bounds), jnp.asarray(ids),
    )).sum()
    assert got == int(expect)


def test_device_index_build_xz_matches_host(mesh):
    """The device build accepts the XZ (non-point)
    key spaces — bit-identical sorted keys and fids vs the host build."""
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.index.build import build_index, build_index_device
    from geomesa_tpu.index.keyspaces import XZ2KeySpace, XZ3KeySpace

    rng = np.random.default_rng(5)
    n = 10_000
    xs = rng.uniform(-170, 160, n)
    ys = rng.uniform(-85, 75, n)
    ws = rng.uniform(0.01, 5.0, n)
    hs = rng.uniform(0.01, 5.0, n)
    wkt = np.array(
        [
            f"POLYGON (({x} {y}, {x+w} {y}, {x+w} {y+h}, {x} {y+h}, {x} {y}))"
            for x, y, w, h in zip(xs, ys, ws, hs)
        ],
        dtype=object,
    )
    sft3 = SimpleFeatureType.create("pg3", "dtg:Date,*geom:Polygon:srid=4326")
    batch3 = FeatureBatch.from_columns(
        sft3,
        {
            "dtg": rng.integers(1_577_836_800_000, 1_583_020_800_000, n),
            "geom": wkt,
        },
        np.arange(n),
    )
    ks3 = XZ3KeySpace("geom", "dtg")
    host3 = build_index(ks3, batch3, partition_size=2048)
    dev3 = build_index_device(ks3, batch3, mesh, partition_size=2048)
    np.testing.assert_array_equal(dev3.keys["bin"], host3.keys["bin"])
    np.testing.assert_array_equal(dev3.keys["xz"], host3.keys["xz"])
    np.testing.assert_array_equal(dev3.batch.fids, host3.batch.fids)
    assert dev3.keys["xz"].dtype == host3.keys["xz"].dtype

    sft2 = SimpleFeatureType.create("pg2", "*geom:Polygon:srid=4326")
    batch2 = FeatureBatch.from_columns(sft2, {"geom": wkt}, np.arange(n))
    ks2 = XZ2KeySpace("geom")
    host2 = build_index(ks2, batch2, partition_size=2048)
    dev2 = build_index_device(ks2, batch2, mesh, partition_size=2048)
    np.testing.assert_array_equal(dev2.keys["xz"], host2.keys["xz"])
    np.testing.assert_array_equal(dev2.batch.fids, host2.batch.fids)


def test_device_index_build_z2_matches_host(mesh):
    """The date-less point key space (z2) also builds on device."""
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.index.build import build_index, build_index_device
    from geomesa_tpu.index.keyspaces import Z2KeySpace

    rng = np.random.default_rng(6)
    n = 8192
    sft = SimpleFeatureType.create("p2", "*geom:Point:srid=4326")
    batch = FeatureBatch.from_columns(
        sft,
        {"geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        )},
        np.arange(n),
    )
    ks = Z2KeySpace("geom")
    host = build_index(ks, batch, partition_size=1024)
    dev = build_index_device(ks, batch, mesh, partition_size=1024)
    np.testing.assert_array_equal(dev.keys["z"], host.keys["z"])
    np.testing.assert_array_equal(dev.batch.fids, host.batch.fids)


def test_exchange_at_scale_adversarial_layouts(mesh):
    """The capacity math (dist.py) proven beyond
    toy n — ~2^22 rows over 8 virtual devices, adversarial layouts
    (uniform, pre-sorted, all-duplicate, hot-cluster), ZERO overflow at
    the default capacity factor, and bounded wall clock."""
    import time

    import jax.numpy as jnp

    from geomesa_tpu.parallel import distributed_sort

    n = 1 << 22
    rng_ = np.random.default_rng(11)
    uniform = rng_.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    layouts = {
        "uniform": uniform,
        "presorted": np.sort(uniform),
        "all-duplicate": np.full(n, 0x1234ABCD, np.uint32),
        # hot cluster: 90% of rows in one tiny key neighborhood (GDELT
        # city-cluster skew, SURVEY hard part #5)
        "clustered": np.where(
            rng_.random(n) < 0.9,
            (0x40000000 + rng_.integers(0, 1024, n)).astype(np.uint32),
            uniform,
        ),
    }
    lo_lane = rng_.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    for name, hi_lane in layouts.items():
        t0 = time.perf_counter()
        (sh, sl), _, v = distributed_sort(
            mesh,
            (jnp.asarray(hi_lane), jnp.asarray(lo_lane)),
            on_overflow="raise",  # zero overflow at DEFAULT capacity
        )
        sh = np.asarray(sh)
        sv = np.asarray(v)
        dt = time.perf_counter() - t0
        assert sv.sum() == n, f"{name}: lost rows"
        z = np.asarray(sh)[sv]
        # shard concatenation is globally sorted on the hi lane
        assert np.all(np.diff(z.astype(np.int64)) >= 0), f"{name}: unsorted"
        # wall-clock bound: generous (covers jit compile + loaded CI
        # hosts) but still catches a degenerated exchange, which would
        # take many minutes at this size
        assert dt < 300, f"{name}: exchange took {dt:.1f}s"
