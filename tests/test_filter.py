"""Filter layer: ECQL parsing, bound extraction, host/device evaluation."""

import numpy as np
import pytest

from geomesa_tpu.features import FeatureBatch, SimpleFeatureType
from geomesa_tpu.filter import (
    And,
    BBox,
    Compare,
    During,
    Exclude,
    Include,
    Intersects,
    Not,
    Or,
    compile_filter,
    extract_geometries,
    extract_intervals,
    parse_ecql,
)
from geomesa_tpu.filter.compile import evaluate_host
from geomesa_tpu.filter.ecql import parse_instant

SPEC = "name:String,count:Int,dtg:Date,*geom:Point:srid=4326"
SFT = SimpleFeatureType.create("t", SPEC)


def make_batch(n=1000, seed=5):
    rng = np.random.default_rng(seed)
    return FeatureBatch.from_columns(
        SFT,
        {
            "name": rng.choice(["alpha", "beta", "gamma"], n),
            "count": rng.integers(0, 50, n),
            "dtg": rng.integers(
                parse_instant("2020-01-01T00:00:00"),
                parse_instant("2020-02-01T00:00:00"),
                n,
            ),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(30, 60, n)], axis=1
            ),
        },
    )


class TestParse:
    def test_bbox_and_during(self):
        f = parse_ecql(
            "BBOX(geom, -5, 42, 8, 51) AND dtg DURING 2020-01-05T00:00:00Z/2020-01-10T00:00:00Z"
        )
        assert isinstance(f, And)
        bbox, during = f.children
        assert bbox == BBox("geom", -5, 42, 8, 51)
        assert during.t0 == parse_instant("2020-01-05T00:00:00")

    def test_intersects_polygon(self):
        f = parse_ecql("INTERSECTS(geom, POLYGON ((0 0, 10 0, 10 10, 0 10, 0 0)))")
        assert isinstance(f, Intersects)
        assert f.geometry.envelope.xmax == 10

    def test_comparisons(self):
        f = parse_ecql("count >= 5 AND count < 40 AND name = 'alpha'")
        ops = [c.op for c in f.children]
        assert ops == [">=", "<", "="]
        assert f.children[2].value == "alpha"

    def test_or_not_nesting(self):
        f = parse_ecql("(count > 5 OR count < 2) AND NOT name = 'beta'")
        assert isinstance(f, And)
        assert isinstance(f.children[0], Or)
        assert isinstance(f.children[1], Not)

    def test_between_in_like_null(self):
        f = parse_ecql(
            "count BETWEEN 5 AND 10 OR name IN ('a', 'b') OR name LIKE 'al%' OR name IS NULL"
        )
        assert len(f.children) == 4

    def test_date_compare_quoted(self):
        f = parse_ecql("dtg >= '2020-01-05T00:00:00' AND dtg AFTER 2020-01-01T00:00:00Z")
        assert f.children[0].value == parse_instant("2020-01-05T00:00:00")
        assert f.children[1].op == ">"

    def test_include_exclude(self):
        assert parse_ecql("INCLUDE") is Include
        assert parse_ecql("EXCLUDE") is Exclude

    def test_errors(self):
        for bad in ["count >=", "BBOX(geom, 1, 2, 3)", "name SMELLS 'x'"]:
            with pytest.raises(ValueError):
                parse_ecql(bad)

    def test_geometry_literal_is_one_token_read_exactly(self):
        """A WKT literal reaches the WKT reader whole, as one token, and
        every vertex of a many-vertex multipolygon (shell, hole, second
        part) parses back to the float it was printed from."""
        from geomesa_tpu.filter.ecql import _P

        rng = np.random.default_rng(11)
        a = np.linspace(0.0, 2.0 * np.pi, 1024, endpoint=False)
        shell = np.stack([np.cos(a), np.sin(a)], 1) * rng.uniform(
            5, 10, (1024, 1))
        shell = np.vstack([shell, shell[:1]]).astype(np.float32)
        hole = np.array([[0.5, 0.5], [1, 0.5], [1, 1], [0.5, 0.5]])
        part = hole + 40.0

        def ring(r):
            return "(" + ", ".join(f"{float(x)!r} {float(y)!r}"
                                   for x, y in r) + ")"

        wkt = (f"MULTIPOLYGON (({ring(shell)}, {ring(hole)}), "
               f"({ring(part)}))")
        text = f"INTERSECTS(geom, {wkt}) AND count > 3"
        toks = _P(text).toks
        assert ("wkt", wkt) in toks and len(toks) == 10
        f = parse_ecql(text)
        first, second = f.children[0].geometry.polygons
        np.testing.assert_array_equal(first.shell, shell.astype(np.float64))
        np.testing.assert_array_equal(first.holes[0], hole)
        np.testing.assert_array_equal(second.shell, part)
        # a column named like a geometry type is still a column
        assert parse_ecql("point = 5") == Compare("=", "point", 5)

    @pytest.mark.parametrize("bad", [
        "INTERSECTS(geom, POLYGON((0 0, 1 0, 1 1, 0 0))",
        "INTERSECTS(geom, POLYGON((0 0 0, 1 0 0, 1 1 0, 0 0 0)))",
        "INTERSECTS(geom, POLYGON((0 0; 1 0, 1 1, 0 0)))",
        "INTERSECTS(geom, POLYGON(()))",
        "INTERSECTS(geom, POLYGON((0 0, 1 0, 1 1, 0 0), ))",
        "INTERSECTS(geom, POLYGON EMPTY)",
        "INTERSECTS(geom, CIRCLE(1 2))",
    ], ids=["unclosed", "3d", "semicolon", "empty-ring", "trailing-comma",
            "empty", "unknown-type"])
    def test_malformed_geometry_literal(self, bad):
        with pytest.raises(ValueError):
            parse_ecql(bad)


class TestExtract:
    def test_bbox_and_interval(self):
        f = parse_ecql(
            "BBOX(geom, -5, 42, 8, 51) AND dtg DURING 2020-01-05T00:00:00Z/2020-01-10T00:00:00Z AND count > 3"
        )
        g = extract_geometries(f, "geom")
        assert len(g.values) == 1
        env = g.values[0][0]
        assert (env.xmin, env.ymax) == (-5, 51)
        t = extract_intervals(f, "dtg")
        assert t.values == (
            (parse_instant("2020-01-05T00:00:00"), parse_instant("2020-01-10T00:00:00")),
        )

    def test_and_intersection(self):
        f = parse_ecql("BBOX(geom, 0, 0, 10, 10) AND BBOX(geom, 5, 5, 20, 20)")
        g = extract_geometries(f, "geom")
        env = g.values[0][0]
        assert (env.xmin, env.ymin, env.xmax, env.ymax) == (5, 5, 10, 10)

    def test_and_disjoint_is_empty(self):
        f = parse_ecql("BBOX(geom, 0, 0, 1, 1) AND BBOX(geom, 5, 5, 6, 6)")
        assert extract_geometries(f, "geom").empty

    def test_or_union(self):
        f = parse_ecql("BBOX(geom, 0, 0, 1, 1) OR BBOX(geom, 5, 5, 6, 6)")
        assert len(extract_geometries(f, "geom").values) == 2

    def test_or_with_unbounded_branch(self):
        f = parse_ecql("BBOX(geom, 0, 0, 1, 1) OR count > 5")
        assert extract_geometries(f, "geom").unbounded

    def test_not_unbounded(self):
        f = parse_ecql("NOT BBOX(geom, 0, 0, 1, 1)")
        assert extract_geometries(f, "geom").unbounded

    def test_open_interval(self):
        f = parse_ecql("dtg >= '2020-01-05T00:00:00'")
        t = extract_intervals(f, "dtg")
        assert t.values[0][0] == parse_instant("2020-01-05T00:00:00")


class TestEvaluate:
    def test_host_bbox_during(self):
        b = make_batch()
        f = parse_ecql(
            "BBOX(geom, -5, 42, 8, 51) AND dtg DURING 2020-01-05T00:00:00Z/2020-01-10T00:00:00Z"
        )
        m = evaluate_host(f, b)
        x, y = b.point_coords()
        dtg = b.column("dtg")
        expected = (
            (x >= -5) & (x <= 8) & (y >= 42) & (y <= 51)
            & (dtg >= parse_instant("2020-01-05T00:00:00"))
            & (dtg <= parse_instant("2020-01-10T00:00:00"))
        )
        np.testing.assert_array_equal(m, expected)

    def test_host_string_ops(self):
        b = make_batch()
        m = evaluate_host(parse_ecql("name LIKE 'al%'"), b)
        np.testing.assert_array_equal(m, b.column("name") == "alpha")
        m = evaluate_host(parse_ecql("name IN ('alpha', 'gamma')"), b)
        np.testing.assert_array_equal(
            m, np.isin(b.column("name"), ["alpha", "gamma"])
        )

    def test_host_intersects_points(self):
        b = make_batch()
        f = parse_ecql("INTERSECTS(geom, POLYGON ((-5 40, 10 40, 10 55, -5 55, -5 40)))")
        m = evaluate_host(f, b)
        x, y = b.point_coords()
        expected = (x > -5) & (x < 10) & (y > 40) & (y < 55)
        # interior points agree (boundary measure zero for random data)
        np.testing.assert_array_equal(m, expected)

    def test_device_split_and_equivalence(self):
        import jax.numpy as jnp

        b = make_batch()
        f = parse_ecql(
            "BBOX(geom, -5, 42, 8, 51) AND count > 10 AND name = 'alpha'"
        )
        cf = compile_filter(f, SFT)
        assert not cf.fully_on_device  # name = 'alpha' is host residual
        assert cf.device_cols == ["count", "geom__x", "geom__y"]
        x, y = b.point_coords()
        cols = {
            "geom__x": jnp.asarray(x),
            "geom__y": jnp.asarray(y),
            "count": jnp.asarray(b.column("count")),
        }
        dev_mask = np.asarray(cf.device_fn(cols))
        res_mask = cf.residual_mask(b)
        np.testing.assert_array_equal(dev_mask & res_mask, cf.host_mask(b))

    def test_device_full_filter(self):
        import jax
        import jax.numpy as jnp

        b = make_batch()
        f = parse_ecql(
            "INTERSECTS(geom, POLYGON ((-5 40, 10 40, 10 55, -5 55, -5 40))) AND count BETWEEN 5 AND 30"
        )
        cf = compile_filter(f, SFT)
        assert cf.fully_on_device
        x, y = b.point_coords()
        cols = {
            "geom__x": jnp.asarray(x),
            "geom__y": jnp.asarray(y),
            "count": jnp.asarray(b.column("count")),
        }
        dev_mask = np.asarray(jax.jit(cf.device_fn)(cols))
        np.testing.assert_array_equal(dev_mask, cf.host_mask(b))

    def test_exclude_include(self):
        b = make_batch(10)
        assert evaluate_host(Include, b).all()
        assert not evaluate_host(Exclude, b).any()


class TestNonPointDeviceBBox:
    """Non-point geometries: device BBOX = envelope-overlap on the staged
    bbox planes (exact: BBOX semantics for non-points IS envelope
    intersection), and residual spatial predicates get a device envelope
    prefilter."""

    def _poly_batch(self, n=400, seed=12):
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.features.sft import SimpleFeatureType
        from geomesa_tpu.geom import Polygon

        sft = SimpleFeatureType.create("polys", "val:Int,*geom:Polygon")
        rng = np.random.default_rng(seed)
        polys = []
        for i in range(n):
            cx, cy = rng.uniform(-170, 170), rng.uniform(-80, 80)
            r = rng.uniform(0.1, 3.0)
            ang = np.linspace(0, 2 * np.pi, 8)
            ring = np.stack(
                [cx + r * np.cos(ang), cy + r * np.sin(ang)], axis=1
            )
            ring[-1] = ring[0]
            polys.append(Polygon(ring))
        return sft, FeatureBatch.from_columns(
            sft,
            {"val": rng.integers(0, 100, n),
             "geom": np.array(polys, dtype=object)},
            fids=np.arange(n),
        )

    def test_device_bbox_matches_host(self):
        from geomesa_tpu.filter.compile import compile_filter, evaluate_host
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.ops.scan import stage_columns

        sft, batch = self._poly_batch()
        f = parse_ecql("BBOX(geom, -20, -20, 40, 30)")
        c = compile_filter(f, sft)
        assert c.fully_on_device, "non-point bbox should be device-only now"
        cols = stage_columns(batch, c.device_cols)
        got = np.asarray(c.device_fn(cols))
        expect = evaluate_host(f, batch)
        np.testing.assert_array_equal(got, expect)

    def test_intersects_gets_envelope_prefilter(self):
        from geomesa_tpu.filter.compile import compile_filter, evaluate_host
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.ops.scan import stage_columns

        sft, batch = self._poly_batch()
        f = parse_ecql(
            "INTERSECTS(geom, POLYGON((0 0, 25 0, 25 20, 0 20, 0 0)))"
        )
        c = compile_filter(f, sft)
        assert not c.fully_on_device  # exact test stays residual
        assert c.device_cols, "prefilter should stage envelope planes"
        cols = stage_columns(batch, c.device_cols)
        pre = np.asarray(c.device_fn(cols))
        exact = evaluate_host(f, batch)
        assert not np.any(exact & ~pre), "prefilter dropped a true hit"
        assert pre.sum() < len(batch), "prefilter pruned nothing"

    def test_device_index_over_polygons(self):
        from geomesa_tpu.device_cache import DeviceIndex
        from geomesa_tpu.filter.compile import evaluate_host
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.store.memory import MemoryDataStore

        sft, batch = self._poly_batch(n=300)
        ds = MemoryDataStore()
        ds.create_schema("polys", "val:Int,*geom:Polygon")
        ds.write("polys", dict(batch.columns), fids=batch.fids)
        di = DeviceIndex(ds, "polys")
        all_batch = ds.query("polys").batch
        for ecql in [
            "BBOX(geom, -20, -20, 40, 30)",
            "BBOX(geom, -20, -20, 40, 30) AND val >= 50",
            "INTERSECTS(geom, POLYGON((0 0, 25 0, 25 20, 0 20, 0 0)))",
            "DWITHIN(geom, POINT(10 10), 5, kilometers)",
        ]:
            expect = evaluate_host(parse_ecql(ecql), all_batch)
            assert di.count(ecql) == int(expect.sum()), ecql
            np.testing.assert_array_equal(
                np.sort(di.query(ecql).fids), np.sort(all_batch.fids[expect]),
                err_msg=ecql,
            )

    def test_pallas_tile_kernel_handles_envelope_planes(self):
        from geomesa_tpu.filter.compile import compile_filter, evaluate_host
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.ops.pallas_scan import build_pallas_scan
        from geomesa_tpu.ops.scan import stage_columns

        sft, batch = self._poly_batch()
        f = parse_ecql("BBOX(geom, -20, -20, 40, 30)")
        count_fn, mask_fn, cols_needed = build_pallas_scan(
            f, sft, interpret=True
        )
        cols = stage_columns(batch, cols_needed)
        expect = evaluate_host(f, batch)
        np.testing.assert_array_equal(np.asarray(mask_fn(cols)), expect)
        assert int(count_fn(cols)[0]) == int(expect.sum())
