"""The point-in-polygon kernel (ops/pallas_scan.py) against the plain
reference (benchmark/reference.polygon_count), in interpret mode and on
the XLA path: zones of every edge bucket, multipart zones and holes, a
time window whose blocks and whole tiles the kernel skips; zones well
below their bucket, whose edge loop stops at their real edges; its tile,
block and edge counters; the engine the resident count records and its
counters; one compiled program per edge bucket."""

import os
import sys

import numpy as np
import pytest

from geomesa_tpu import metrics
from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import compile as compile_mod
from geomesa_tpu.filter.compile import compile_filter
from geomesa_tpu.filter.ecql import parse_ecql
from geomesa_tpu.ops import pallas_scan
from geomesa_tpu.ops.scan import stage_columns

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference  # noqa: E402

SPEC = "fare:Float,dtg:Date,*geom:Point:srid=4326"
SFT = SimpleFeatureType.create("t", SPEC)
T0 = 1_420_070_400_000  # 2015-01-01
DAY = 86_400_000
WINDOW = "dtg DURING 2015-01-20T00:00:00Z/2015-02-03T00:00:00Z"
W0, W1 = T0 + 19 * DAY, T0 + 33 * DAY


def _ring(k: int, cx: float, cy: float, r0: float, r1: float) -> np.ndarray:
    """A closed star of ``k`` edges (radii alternating r0, r1), its
    vertices float32-representable."""
    a = np.linspace(0.0, 2.0 * np.pi, k, endpoint=False)
    r = np.where(np.arange(k) % 2 == 0, r0, r1)
    ring = np.stack([cx + r * np.cos(a), cy + r * np.sin(a)], axis=1)
    return np.vstack([ring, ring[:1]]).astype(np.float32).astype(np.float64)


def _wkt_ring(ring) -> str:
    return "(" + ", ".join(f"{float(x)!r} {float(y)!r}" for x, y in ring) + ")"


def zone(n_edges: int, kind: str):
    """(WKT, reference parts) of a zone of ``n_edges`` edges: one ring,
    a shell with a hole, or three parts."""
    if kind == "single":
        parts = [[_ring(n_edges, 0.0, 0.0, 60.0, 25.0)]]
    elif kind == "hole":
        parts = [[_ring(n_edges - 8, 0.0, 0.0, 60.0, 25.0),
                  _ring(8, 1.0, 2.0, 15.0, 9.0)]]
    else:
        k = n_edges // 3
        parts = [[_ring(k, -100.0, 0.0, 50.0, 20.0)],
                 [_ring(k, 0.0, 30.0, 40.0, 20.0)],
                 [_ring(n_edges - 2 * k, 100.0, -20.0, 45.0, 30.0)]]
    polys = ["(" + ", ".join(_wkt_ring(r) for r in rings) + ")"
             for rings in parts]
    if len(polys) == 1:
        return "POLYGON " + polys[0], parts
    return "MULTIPOLYGON (" + ", ".join(polys) + ")", parts


def _data(n: int, seed: int):
    """Seeded points over the globe in ascending time (so a window
    covers a run of rows and the kernel skips the blocks outside it)."""
    rng = np.random.default_rng(seed)
    lon = rng.uniform(-180, 180, n).astype(np.float32)
    lat = rng.uniform(-90, 90, n).astype(np.float32)
    dtg = np.sort(rng.integers(T0, T0 + 60 * DAY, n))
    batch = FeatureBatch.from_columns(SFT, {
        "fare": rng.uniform(0, 50, n),
        "dtg": dtg,
        "geom": np.stack([lon, lat], axis=1).astype(np.float64),
    })
    return batch, reference.Table(lon, lat, dtg)


@pytest.fixture(scope="module")
def data():
    return _data(6000, 26)


def _kernel(cql: str, **kw):
    cf = compile_filter(parse_ecql(cql), SFT)
    assert cf.fully_on_device
    scan = cf.pallas_scan(**kw)
    assert scan is not None, "the polygon did not reach the kernel"
    return cf, scan


def _intersects(n_edges: int, kind: str):
    """(CQL, reference parts) of INTERSECTS a zone of ``kind``; for
    ``or``, a single zone OR a 201-edge one apart from it (bucket 256,
    which no other zone of these tests fills: each polygon's loop runs
    its own trip count)."""
    if kind != "or":
        wkt, parts = zone(n_edges, kind)
        return f"INTERSECTS(geom, {wkt})", parts
    wkt, parts = zone(n_edges, "single")
    far = _ring(201, 120.0, 50.0, 40.0, 20.0)
    return (f"(INTERSECTS(geom, {wkt}) OR "
            f"INTERSECTS(geom, POLYGON ({_wkt_ring(far)})))",
            parts + [[far]])


@pytest.mark.parametrize("kind", ["single", "hole", "multipart", "or"])
@pytest.mark.parametrize("n_edges", [16, 17, 64, 65, 100, 512, 1001, 1024])
def test_kernel_and_xla_path_match_reference(data, n_edges, kind):
    """Zones that fill their bucket, and zones well below it whose edge
    count is no multiple of the loop's unroll (17 in 32, 100 in 128,
    1,001 in 1,024)."""
    batch, tab = data
    pred, parts = _intersects(n_edges, kind)
    cf, (count_fn, mask_fn) = _kernel(f"{pred} AND {WINDOW}", block_rows=32)
    cols = stage_columns(batch, cf.device_cols)
    want = reference.polygon_count(tab, parts, W0, W1)
    assert want > 0
    assert int(count_fn(cols)[0]) == want
    mask = np.asarray(mask_fn(cols))
    assert int(mask.sum()) == want
    np.testing.assert_array_equal(mask, cf.host_mask(batch))
    # the XLA path: the same filter, edges broadcast
    assert int(np.asarray(cf.device_fn(cols)).sum()) == want


@pytest.mark.parametrize("bucket", [16, 32, 64, 128, 256, 512, 1024, 2048,
                                    4096])
def test_every_edge_bucket(data, bucket):
    """The padding edges never straddle: a polygon just over the next
    smaller bucket and one that fills the bucket count alike."""
    batch, tab = data
    for n_edges in (bucket // 2 + 1, bucket):
        wkt, parts = zone(n_edges, "single")
        cf, (count_fn, _) = _kernel(f"INTERSECTS(geom, {wkt})")
        out = count_fn(stage_columns(batch, cf.device_cols))
        assert pallas_scan.padded_edges(cf.device_part) == bucket
        assert pallas_scan.edge_bucket(n_edges) == bucket
        assert int(out[0]) == reference.polygon_count(tab, parts, T0,
                                                      T0 + 60 * DAY)


def test_over_the_largest_bucket_takes_the_xla_path(data):
    wkt, _ = zone(pallas_scan.MAX_KERNEL_EDGES + 1, "single")
    cf = compile_filter(parse_ecql(f"INTERSECTS(geom, {wkt})"), SFT)
    assert cf.pallas_scan() is None


def test_blocks_tested_and_skipped_add_up(data):
    """A window over a few of the rows' days: the kernel tests fewer
    blocks than it has, and tested + skipped is its block count."""
    batch, tab = data
    wkt, parts = zone(200, "single")
    cf, (count_fn, _) = _kernel(f"INTERSECTS(geom, {wkt}) AND {WINDOW}",
                                block_rows=128)
    cols = stage_columns(batch, cf.device_cols)
    tile = 128 * pallas_scan.LANES
    blocks = -(-len(batch) // tile) * (128 // pallas_scan.SKIP_ROWS)
    hits, tested, skipped, walked, dead = np.asarray(
        count_fn(cols)).tolist()
    assert hits == reference.polygon_count(tab, parts, W0, W1)
    assert tested + skipped == blocks
    assert 0 < tested < blocks
    assert (walked, dead) == (1, 0)


def test_validity_plane_and_padding(data):
    """Rows a validity plane marks dead never count; the grid's padding
    rows never count."""
    import jax.numpy as jnp

    batch, tab = data
    wkt, parts = zone(100, "hole")
    cf, (count_fn, mask_fn) = _kernel(f"INTERSECTS(geom, {wkt})",
                                      block_rows=32)
    cols = stage_columns(batch, cf.device_cols)
    live = np.arange(len(batch)) % 3 != 0
    expect = cf.host_mask(batch) & live
    assert int(count_fn(cols, jnp.asarray(live))[0]) == int(expect.sum())
    np.testing.assert_array_equal(
        np.asarray(mask_fn(cols, jnp.asarray(live))), expect)


#: rows of a grid tile at ``block_rows=32``, and rows over about ten of
#: them, the last one partial (the grid's padding tail)
TILE = 32 * pallas_scan.LANES
N_TILED = 10 * TILE - 1000


def _iso(ms: int) -> str:
    return f"{np.datetime64(int(ms), 'ms')}Z"


def _during(t0: int, t1: int) -> str:
    return f"dtg DURING {_iso(t0)}/{_iso(t1)}"


def _minutes(n: int, seed: int):
    """Seeded points over the globe, a minute apart from 2015-01-01: a
    window of a few days covers a run of whole tiles."""
    rng = np.random.default_rng(seed)
    lon = rng.uniform(-180, 180, n).astype(np.float32)
    lat = rng.uniform(-90, 90, n).astype(np.float32)
    dtg = T0 + np.arange(n, dtype=np.int64) * 60_000
    return lon, lat, dtg, rng.uniform(0, 50, n)


def _table(lon, lat, dtg, fare):
    batch = FeatureBatch.from_columns(SFT, {
        "fare": fare, "dtg": dtg,
        "geom": np.stack([lon, lat], axis=1).astype(np.float64)})
    return batch, reference.Table(lon, lat, dtg)


@pytest.fixture(scope="module")
def tiled():
    return _table(*_minutes(N_TILED, 27))


def _tiles(count_fn, cols, br: int, valid=None):
    """(hits, blocks tested, tiles walked, tiles skipped) of a count,
    checking that blocks and tiles add up to the grid's."""
    grid = -(-len(next(iter(cols.values()))) // (br * pallas_scan.LANES))
    hits, tested, skipped, walked, dead = np.asarray(
        count_fn(cols, valid)).tolist()
    assert tested + skipped == grid * (br // pallas_scan.SKIP_ROWS)
    assert walked + dead == grid
    return hits, tested, walked, dead


@pytest.mark.parametrize("br", [32, 64])
@pytest.mark.parametrize("days,dead_tiles", [((5, 12), True),
                                             ((-1, 40), False)],
                         ids=["window-kills-tiles", "window-covers-all"])
def test_tile_pass_skips_only_dead_tiles(tiled, br, days, dead_tiles):
    """A window over a few of the rows' days skips the tiles outside it
    and counts as the reference does; one over every row skips none."""
    batch, tab = tiled
    wkt, parts = zone(200, "single")
    t0, t1 = T0 + days[0] * DAY, T0 + days[1] * DAY
    cf, (count_fn, mask_fn) = _kernel(
        f"INTERSECTS(geom, {wkt}) AND {_during(t0, t1)}", block_rows=br)
    cols = stage_columns(batch, cf.device_cols)
    want = reference.polygon_count(tab, parts, t0, t1)
    assert want > 0
    hits, tested, walked, dead = _tiles(count_fn, cols, br)
    assert hits == want
    np.testing.assert_array_equal(np.asarray(mask_fn(cols)),
                                  cf.host_mask(batch))
    assert (dead > 0) == dead_tiles
    assert 0 < tested <= walked * (br // pallas_scan.SKIP_ROWS)


@pytest.mark.parametrize("br", [32, 64])
@pytest.mark.parametrize("row", [3 * 64 * pallas_scan.LANES - 1, N_TILED - 1],
                         ids=["tile-end", "last-live-row"])
def test_tile_with_one_passing_last_row_is_walked(br, row):
    """The only row that passes the pretest is the last live row of its
    tile (a full tile's, or the one before the padding tail): the tile
    is walked and the row counted."""
    lon, lat, dtg, fare = _minutes(N_TILED, 28)
    lon[row], lat[row] = 0.0, 0.0  # inside the zone
    batch, tab = _table(lon, lat, dtg, fare)
    wkt, parts = zone(200, "single")
    t = int(dtg[row])
    cf, (count_fn, mask_fn) = _kernel(
        f"INTERSECTS(geom, {wkt}) AND {_during(t, t)}", block_rows=br)
    cols = stage_columns(batch, cf.device_cols)
    assert reference.polygon_count(tab, parts, t, t) == 1
    hits, tested, walked, dead = _tiles(count_fn, cols, br)
    assert (hits, tested, walked) == (1, 1, 1)
    assert np.flatnonzero(np.asarray(mask_fn(cols))).tolist() == [row]


def test_dead_tile_and_padding_tail_never_count(tiled):
    """A validity plane that marks a whole tile dead skips it; the
    grid's padding rows (zeros: the zone's centre, at 1970-01-01) pass
    a window from 1969 and still never count."""
    import jax.numpy as jnp

    batch, tab = tiled
    wkt, parts = zone(200, "single")
    cf, (count_fn, mask_fn) = _kernel(
        f"INTERSECTS(geom, {wkt}) AND "
        "dtg DURING 1969-12-31T00:00:00Z/2016-01-01T00:00:00Z",
        block_rows=32)
    cols = stage_columns(batch, cf.device_cols)
    live = np.ones(len(batch), bool)
    live[2 * TILE: 3 * TILE] = False
    live[5 * TILE + 7] = False
    expect = cf.host_mask(batch) & live
    assert 0 < int(expect.sum()) < int(cf.host_mask(batch).sum())
    hits, _, walked, dead = _tiles(count_fn, cols, 32, jnp.asarray(live))
    assert hits == int(expect.sum())
    assert dead == 1
    np.testing.assert_array_equal(
        np.asarray(mask_fn(cols, jnp.asarray(live))), expect)


@pytest.mark.parametrize("form", ["NOT INTERSECTS(geom, {z})",
                                  "NOT INTERSECTS(geom, {z}) OR fare > 10",
                                  "{w} OR NOT INTERSECTS(geom, {z})"],
                         ids=["not", "or-fare", "or-window"])
def test_filter_without_pretest_walks_every_tile(tiled, form):
    """A Not, or an Or with a branch that has no pretest: no tile pass,
    every tile is walked (even where a window would have killed tiles)
    and the count is the host's."""
    batch, _ = tiled
    wkt, _ = zone(200, "single")
    cql = form.format(z=wkt, w=_during(T0 + 5 * DAY, T0 + 12 * DAY))
    cf, (count_fn, mask_fn) = _kernel(cql, block_rows=32)
    cols = stage_columns(batch, cf.device_cols)
    want = cf.host_mask(batch)
    hits, tested, walked, dead = _tiles(count_fn, cols, 32)
    assert hits == int(want.sum())
    assert dead == 0 and walked == tested == -(-len(batch) // TILE)
    np.testing.assert_array_equal(np.asarray(mask_fn(cols)), want)


def _resident(n: int = 4000, cls=None, **kw):
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.store.memory import MemoryDataStore

    batch, tab = _data(n, 5)
    ds = MemoryDataStore()
    ds.create_schema("t", SPEC)
    ds.write("t", batch)
    return (cls or DeviceIndex)(ds, "t", **kw), tab


def _counters():
    return (metrics.pip_blocks.value(outcome="tested"),
            metrics.pip_blocks.value(outcome="skipped"),
            metrics.pip_edge_tests.value(),
            metrics.pip_edge_loop_tests.value())


def _engines():
    return {e: metrics.pip_queries.value(engine=e)
            for e in ("pallas", "xla", "host")}


def test_engine_counter_and_one_program_per_bucket(monkeypatch):
    """On a (simulated) TPU pick, a 1,024-edge zone is counted under
    ``pallas``; zones of one edge bucket under other windows compile one
    program (the compile ledger's signature for the bucket); a polygon
    relation the kernel cannot run is counted under ``host``."""
    from geomesa_tpu.ledger import COMPILES

    monkeypatch.setattr(compile_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(pallas_scan, "_PIP_PROGRAMS", {})
    di, tab = _resident()
    before = _engines()
    wkt, parts = zone(1024, "multipart")
    cql = f"INTERSECTS(geom, {wkt}) AND {WINDOW}"
    assert di.count(cql) == reference.polygon_count(tab, parts, W0, W1)
    after = _engines()
    assert after["pallas"] - before["pallas"] == 1
    assert after["xla"] == before["xla"] and after["host"] == before["host"]

    def compiles(sig):
        doc = COMPILES.snapshot(top=1 << 20)["by_signature"]
        return doc.get(sig, {}).get("compiles", 0)

    sig = "cache.scan:pip:e=128"
    c0 = compiles(sig)
    steps = set()
    for n_edges, day in ((65, 30), (100, 3), (110, 9), (128, 20)):
        wkt, parts = zone(n_edges, "single")
        t0 = T0 + day * DAY
        q = (f"INTERSECTS(geom, {wkt}) AND dtg DURING "
             f"{np.datetime64(t0, 'ms')}Z/{np.datetime64(t0 + 7 * DAY, 'ms')}Z")
        assert di.count(q) == reference.polygon_count(tab, parts, t0,
                                                      t0 + 7 * DAY)
        # the loop's trip count is an operand, not part of the program
        plan = pallas_scan._TilePlan(parse_ecql(q), SFT, lift=True)
        steps.add(plan.hi[0])
    assert steps == {9, 13, 14, 16}
    assert len([k for k in pallas_scan._PIP_PROGRAMS if "128)" in k[0]]) == 1
    assert compiles(sig) - c0 == 1

    wkt, parts = zone(64, "single")
    before = _engines()
    di.count(f"CROSSES(geom, {wkt})")
    after = _engines()
    assert after["host"] - before["host"] == 1
    assert after["pallas"] == before["pallas"]


@pytest.mark.parametrize("n_edges", [200, 256], ids=["below", "fills"])
def test_resident_count_moves_block_and_edge_counters(monkeypatch, n_edges):
    """A resident count on the polygon kernel moves the block counters
    by its tested and skipped blocks, the edge tests by the tested
    blocks' rows times the padded edges (256), and the loop's edge tests
    by those rows times the edges its loop ran: the zone's own, alike
    where the zone fills its bucket."""
    monkeypatch.setattr(compile_mod, "_on_tpu", lambda: True)
    di, tab = _resident()
    wkt, parts = zone(n_edges, "single")
    cql = f"INTERSECTS(geom, {wkt}) AND {WINDOW}"
    t0, s0, e0, l0 = _counters()
    assert di.count(cql) == reference.polygon_count(tab, parts, W0, W1)
    t1, s1, e1, l1 = _counters()
    cols = compile_filter(parse_ecql(cql), SFT).device_cols
    br = pallas_scan._pick_block_rows(len(cols))
    blocks = -(-di._staged_len() // (br * pallas_scan.LANES)) * (
        br // pallas_scan.SKIP_ROWS)
    assert (t1 - t0) + (s1 - s0) == blocks
    assert t1 - t0 > 0
    rows = pallas_scan.SKIP_ROWS * pallas_scan.LANES
    assert e1 - e0 == (t1 - t0) * rows * 256
    assert l1 - l0 == (t1 - t0) * rows * n_edges


@pytest.mark.parametrize("day,walked", [(19, 1), (400, 0)],
                         ids=["window-in-data", "window-past-data"])
def test_resident_count_moves_tile_counters(monkeypatch, day, walked):
    """A resident count on the polygon kernel moves the tile counter by
    its one tile: walked where the window holds rows, else skipped with
    every one of its blocks."""
    monkeypatch.setattr(compile_mod, "_on_tpu", lambda: True)
    di, tab = _resident()
    wkt, parts = zone(200, "single")
    t0, t1 = T0 + day * DAY, T0 + (day + 14) * DAY
    cql = f"INTERSECTS(geom, {wkt}) AND {_during(t0, t1)}"

    def tiles():
        return (metrics.pip_tiles.value(outcome="walked"),
                metrics.pip_tiles.value(outcome="skipped"))

    (w0, s0), (b0, *_) = tiles(), _counters()
    assert di.count(cql) == reference.polygon_count(tab, parts, t0, t1)
    (w1, s1), (b1, *_) = tiles(), _counters()
    assert (w1 - w0, s1 - s0) == (walked, 1 - walked)
    assert (b1 - b0 > 0) == bool(walked)


def test_mesh_index_counts_its_xla_scan(monkeypatch):
    """A mesh index keeps the XLA scan even where the platform picks the
    Pallas kernel (a Pallas call is not SPMD-partitionable): the engine
    counter says ``xla``, the entry records it, and the polygon kernel's
    counters do not move."""
    from geomesa_tpu.device_cache import ShardedDeviceIndex
    from geomesa_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(compile_mod, "_on_tpu", lambda: True)
    di, tab = _resident(cls=ShardedDeviceIndex, mesh=make_mesh(2))
    wkt, parts = zone(300, "multipart")
    cql = f"INTERSECTS(geom, {wkt}) AND {WINDOW}"
    before, c0 = _engines(), _counters()
    assert di.count(cql) == reference.polygon_count(tab, parts, W0, W1)
    after = _engines()
    assert after["xla"] - before["xla"] == 1
    assert after["pallas"] == before["pallas"]
    assert _counters() == c0
    compiled, _, _, engine = di._compiled[repr(parse_ecql(cql))]
    assert compiled.scan_engine == "pallas" and engine == "xla"
