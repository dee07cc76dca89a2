"""Multi-chip sharded serving (ISSUE 8): ShardedDeviceIndex parity with
single-device serving across shard counts — including a non-power-of-two
count, adversarial layouts and padding edges — plus the mesh server
endpoints, the distributed-sort engines, and the degraded-build ladder.

Runs in-process on the 8-virtual-device CPU harness conftest provides.
"""

import json
import urllib.request
from urllib.parse import quote

import numpy as np
import pytest

from geomesa_tpu.conf import prop_override
from geomesa_tpu.device_cache import DeviceIndex, ShardedDeviceIndex
from geomesa_tpu.parallel.mesh import make_mesh
from geomesa_tpu.store import MemoryDataStore

T0 = 1577836800000  # 2020-01-01


def _write(store, name, x, y, t):
    n = len(x)
    store.create_schema(
        name, "name:String,v:Integer,dtg:Date,*geom:Point:srid=4326"
    )
    rng = np.random.default_rng(len(x))
    store.write(
        name,
        {
            "name": rng.choice(["a", "b", "c"], n),
            "v": rng.integers(0, 100, n).astype(np.int32),
            "dtg": np.asarray(t, dtype=np.int64),
            "geom": np.stack([x, y], axis=1),
        },
        fids=np.arange(n),
    )


def _layout(kind, n, rng):
    """Adversarial coordinate layouts: uniform, pre-sorted along x,
    all-duplicate (one point), and GDELT-style hot city clusters."""
    if kind == "uniform":
        x = rng.uniform(-180, 180, n)
        y = rng.uniform(-90, 90, n)
    elif kind == "presorted":
        x = np.sort(rng.uniform(-180, 180, n))
        y = rng.uniform(-90, 90, n)
    elif kind == "duplicate":
        x = np.full(n, 2.3522)
        y = np.full(n, 48.8566)
    else:  # clustered: 90% of points in 4 tiny city cells
        centers = np.array(
            [[2.35, 48.85], [-74.0, 40.7], [139.7, 35.7], [28.0, -26.2]]
        )
        which = rng.integers(0, 4, n)
        x = centers[which, 0] + rng.uniform(-0.01, 0.01, n)
        y = centers[which, 1] + rng.uniform(-0.01, 0.01, n)
        cold = rng.random(n) < 0.1
        x[cold] = rng.uniform(-180, 180, int(cold.sum()))
        y[cold] = rng.uniform(-90, 90, int(cold.sum()))
    t = T0 + rng.integers(0, 30 * 86400_000, n)
    return x, y, t


CQLS = (
    "BBOX(geom, -10, 35, 30, 60)",
    "BBOX(geom, 2.34, 48.84, 2.36, 48.86)",  # the Paris hot cell
    "BBOX(geom, -10, 35, 30, 60) AND "
    "dtg DURING 2020-01-05T00:00:00Z/2020-01-20T00:00:00Z",
    "INCLUDE",
    "BBOX(geom, 100, -20, 140, 20) AND v < 50",  # residual predicate
)


@pytest.mark.parametrize("layout", ["uniform", "presorted", "duplicate",
                                    "clustered"])
def test_sharded_parity_matrix(layout):
    """count / query / fused count / fused query bit-identical to the
    single-device DeviceIndex across shard counts {1, 2, 8} and a
    non-power-of-two count (3), for every adversarial layout. n is NOT
    shard-divisible, so the padding/valid-mask edge is always live."""
    rng = np.random.default_rng(hash(layout) % (1 << 31))
    n = 6007  # prime: pads under every shard count
    x, y, t = _layout(layout, n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    base = DeviceIndex(store, "pts", z_planes=True)
    fuseable = [CQLS[0], CQLS[1], "BBOX(geom, -120, 20, -60, 55)"]
    for ns in (1, 2, 3, 8):
        di = ShardedDeviceIndex(store, "pts", mesh=make_mesh(ns))
        assert di.mesh_shards == ns
        for cql in CQLS:
            assert di.count(cql) == base.count(cql), (layout, ns, cql)
            np.testing.assert_array_equal(
                di.query(cql).fids, base.query(cql).fids,
                err_msg=f"{layout}/{ns}/{cql}",
            )
        with prop_override("query.loose.bbox", True):
            for cql in CQLS[:3]:
                assert di.count(cql, loose=True) == base.count(
                    cql, loose=True
                ), (layout, ns, cql)
                np.testing.assert_array_equal(
                    di.query(cql, loose=True).fids,
                    base.query(cql, loose=True).fids,
                    err_msg=f"loose {layout}/{ns}/{cql}",
                )
            fb = base.fused_loose_counts(fuseable, loose=True)
            fs = di.fused_loose_counts(fuseable, loose=True)
            assert fb == fs, (layout, ns)
            qb = base.fused_loose_query(fuseable, loose=True)
            qs = di.fused_loose_query(fuseable, loose=True)
            for b, s in zip(qb, qs):
                np.testing.assert_array_equal(b.fids, s.fids)


def test_sharded_rider_parity():
    """The non-count riders — density grid, kNN, stats — answer
    identically from the mesh-sharded planes."""
    from geomesa_tpu.geom import Envelope

    rng = np.random.default_rng(9)
    n = 8000
    x, y, t = _layout("clustered", n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    base = DeviceIndex(store, "pts", z_planes=True)
    di = ShardedDeviceIndex(store, "pts", mesh=make_mesh(8))
    cql = CQLS[0]
    gb = base.density(cql, Envelope(-10, 35, 30, 60), 32, 32)
    gs = di.density(cql, Envelope(-10, 35, 30, 60), 32, 32)
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(gs))
    kb, db = base.knn(2.35, 48.85, 7)
    ks, ds = di.knn(2.35, 48.85, 7)
    np.testing.assert_array_equal(kb.fids, ks.fids)
    np.testing.assert_allclose(np.asarray(db), np.asarray(ds))
    sb = base.stats("INCLUDE", 'Count();MinMax("v")')
    ss = di.stats("INCLUDE", 'Count();MinMax("v")')
    assert sb.to_json() == ss.to_json()


def test_shard_ranges_are_contiguous_z_ranges():
    """The mesh manifest: shard s's key range ends before shard s+1's
    begins (contiguous global Z-ranges), rows sum to the dataset, and
    the staged layout equals the host (bin, hi, lo, rid) lexsort."""
    rng = np.random.default_rng(4)
    n = 10000
    x, y, t = _layout("uniform", n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    di = ShardedDeviceIndex(store, "pts", mesh=make_mesh(8))
    stats = di.mesh_stats()
    assert stats["shards"] == 8 and stats["rows"] == n
    assert stats["build_engine"] == "mesh"
    ranges = stats["shard_ranges"]
    assert sum(r["rows"] for r in ranges) == n
    prev_hi = None
    for r in ranges:
        if not r["rows"]:
            continue
        assert tuple(r["key_lo"]) <= tuple(r["key_hi"])
        if prev_hi is not None:
            assert tuple(r["key_lo"]) >= prev_hi
        prev_hi = tuple(r["key_hi"])


def test_mesh_build_degrades_to_host_sort(monkeypatch):
    """A mesh-sort fault must not fail staging: the build falls back to
    the host lexsort (identical layout), counts the fallback and keeps
    serving — PR 7's classification applied to the build path."""
    from geomesa_tpu import metrics
    from geomesa_tpu.parallel import dist

    rng = np.random.default_rng(11)
    n = 5000
    x, y, t = _layout("uniform", n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    ref = ShardedDeviceIndex(store, "pts", mesh=make_mesh(8))

    def boom(*a, **k):
        raise RuntimeError("injected mesh sort fault")

    monkeypatch.setattr(dist, "distributed_sort", boom)
    before = metrics.mesh_build_fallbacks.value()
    with pytest.warns(RuntimeWarning, match="mesh build sort failed"):
        di = ShardedDeviceIndex(store, "pts", mesh=make_mesh(8))
    assert metrics.mesh_build_fallbacks.value() == before + 1
    assert di.mesh_stats()["build_engine"] == "host-fallback"
    # identical staged layout and answers either way
    cql = CQLS[2]
    assert di.count(cql) == ref.count(cql)
    np.testing.assert_array_equal(di.query(cql).fids, ref.query(cql).fids)


def test_distributed_sort_engine_parity():
    """The device engine (single fused all_to_all + measured-capacity
    retry) and the host-staged engine return the same sorted key
    multiset and loss-free payloads, including under adversarial
    pre-sorted input that forces the device engine's capacity retry."""
    import jax.numpy as jnp

    from geomesa_tpu import metrics
    from geomesa_tpu.parallel.dist import distributed_sort

    mesh = make_mesh(8)
    n = 1 << 14
    rng = np.random.default_rng(2)
    for name, z in {
        "uniform": rng.integers(0, 2**62, n, dtype=np.uint64),
        "presorted": np.sort(rng.integers(0, 2**62, n, dtype=np.uint64)),
        "duplicate": np.full(n, 12345678901234, np.uint64),
    }.items():
        hi = jnp.asarray((z >> np.uint64(32)).astype(np.uint32))
        lo = jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))
        pay = {"f": jnp.asarray((z % 1000).astype(np.float32)),
               "i": jnp.asarray((z % 251).astype(np.uint8)),
               "d": jnp.asarray((z % 97).astype(np.float64))}
        results = {}
        for engine in ("host", "device"):
            (sh, sl), p, v = distributed_sort(
                mesh, (hi, lo), payload=pay, engine=engine,
                on_overflow="raise",
            )
            sh_, sl_, v_ = np.asarray(sh), np.asarray(sl), np.asarray(v)
            zz = ((sh_.astype(np.uint64) << np.uint64(32)) | sl_)[v_]
            assert len(zz) == n, (name, engine)
            np.testing.assert_array_equal(np.sort(zz), np.sort(z),
                                          err_msg=f"{name}/{engine}")
            # payloads still satisfy payload == f(key) row for row
            np.testing.assert_array_equal(
                np.asarray(p["f"])[v_], (zz % 1000).astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(p["i"])[v_], (zz % 251).astype(np.uint8))
            np.testing.assert_array_equal(
                np.asarray(p["d"])[v_], (zz % 97).astype(np.float64))
            results[engine] = zz
        np.testing.assert_array_equal(results["host"], results["device"])


def test_device_engine_capacity_retry_counts():
    """Pre-sorted input defeats the optimistic first-launch capacity;
    the device engine must relaunch at the measured bound (counted)
    instead of dropping rows."""
    import jax.numpy as jnp

    from geomesa_tpu import metrics
    from geomesa_tpu.parallel.dist import distributed_sort

    mesh = make_mesh(8)
    n = 1 << 14
    z = np.sort(
        np.random.default_rng(0).integers(0, 2**62, n, dtype=np.uint64)
    )
    before = metrics.mesh_exchange_retries.value()
    (sh, sl), _, v = distributed_sort(
        mesh,
        (jnp.asarray((z >> np.uint64(32)).astype(np.uint32)),
         jnp.asarray((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))),
        engine="device", on_overflow="raise",
    )
    assert int(np.asarray(v).sum()) == n  # loss-free
    assert metrics.mesh_exchange_retries.value() > before


def test_mesh_server_endpoints():
    """Resident mesh serving over HTTP: parity with the store, the
    /stats/mesh topology document, and the /stats roll-up with compile
    cache hit/miss."""
    from geomesa_tpu.server import serve_background

    rng = np.random.default_rng(21)
    n = 9001  # non-divisible: padding live on the serving path
    x, y, t = _layout("clustered", n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    server, _ = serve_background(store, resident=True, mesh=True)
    try:
        host, port = server.server_address[:2]
        base = f"http://{host}:{port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=120) as r:
                return r.status, json.loads(r.read())

        cql = quote(CQLS[0])
        st, doc = get(f"/count/pts?cql={cql}")
        oracle = len(store.query("pts", CQLS[0]))
        assert st == 200 and doc["count"] == oracle
        st, doc = get(f"/features/pts?cql={cql}")
        assert st == 200 and len(doc["features"]) == oracle
        st, doc = get("/stats/mesh")
        assert st == 200 and doc["enabled"]
        mt = doc["types"]["pts"]
        assert mt["shards"] == 8 and mt["rows"] == n
        assert sum(r["rows"] for r in mt["shard_ranges"]) == n
        st, doc = get("/stats")
        assert st == 200
        assert {"compile_cache", "mesh"} <= set(doc)
        cc = doc["compile_cache"]
        assert {"hits", "misses", "requests", "enabled"} <= set(cc)
    finally:
        server.shutdown()


def test_mesh_conf_keys_declared():
    """GT008 contract: the mesh.* keys resolve and the engine key
    validates."""
    from geomesa_tpu.conf import declared_keys, sys_prop

    for key in ("mesh.enabled", "mesh.devices", "mesh.replicas",
                "mesh.sort.engine"):
        assert key in declared_keys()
        sys_prop(key)
    with prop_override("mesh.sort.engine", "host"):
        assert sys_prop("mesh.sort.engine") == "host"
    with pytest.raises(ValueError):
        with prop_override("mesh.sort.engine", "banana"):
            pass


def test_replicated_mesh_parity():
    """mesh.replicas > 1: the shard x replica factoring still answers
    bit-identically (whole-index replication across the replica axis)."""
    rng = np.random.default_rng(6)
    n = 4001
    x, y, t = _layout("uniform", n, rng)
    store = MemoryDataStore()
    _write(store, "pts", x, y, t)
    base = DeviceIndex(store, "pts", z_planes=True)
    mesh = make_mesh(8, axes=("shard", "replica"), replicas=2)
    di = ShardedDeviceIndex(store, "pts", mesh=mesh)
    assert di.mesh_shards == 4
    assert di.mesh_stats()["replicas"] == 2
    for cql in CQLS[:3]:
        assert di.count(cql) == base.count(cql), cql
        np.testing.assert_array_equal(
            di.query(cql).fids, base.query(cql).fids
        )


def test_empty_and_tiny_types():
    """Padding edges: an empty type and a type smaller than the shard
    count (every shard but one empty) stage and answer."""
    store = MemoryDataStore()
    _write(store, "tiny", np.array([2.35, 100.0, -74.0]),
           np.array([48.85, 10.0, 40.7]),
           np.full(3, T0))
    store.create_schema("empty", "dtg:Date,*geom:Point:srid=4326")
    base = DeviceIndex(store, "tiny", z_planes=True)
    di = ShardedDeviceIndex(store, "tiny", mesh=make_mesh(8))
    assert len(di) == 3
    assert di.count("BBOX(geom, 0, 40, 10, 55)") == 1
    np.testing.assert_array_equal(
        di.query("INCLUDE").fids, base.query("INCLUDE").fids
    )
    de = ShardedDeviceIndex(store, "empty", mesh=make_mesh(8))
    assert len(de) == 0
    assert de.count("INCLUDE") == 0
