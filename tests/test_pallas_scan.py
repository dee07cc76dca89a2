"""Pallas fused-scan kernel vs the exact host oracle (interpret mode on
CPU -- the same kernel code the TPU runs, per SURVEY.md section 4 rebuild
test plan)."""

import numpy as np

import pytest

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter.compile import compile_filter
from geomesa_tpu.filter.ecql import parse_ecql
from geomesa_tpu.ops.scan import stage_columns

SFT = SimpleFeatureType.create(
    "t", "count:Int,score:Float,dtg:Date,*geom:Point:srid=4326"
)


T0 = 1_577_836_800_000  # 2020-01-01 in epoch-ms


def make_batch(rng, n):
    return FeatureBatch.from_columns(
        SFT,
        {
            "count": rng.integers(0, 100, n),
            "score": rng.uniform(0, 1, n),
            "dtg": rng.integers(T0, T0 + 90 * 86400_000, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
            ),
        },
    )


FILTERS = [
    "BBOX(geom, -10, 35, 30, 60)",
    "BBOX(geom, -10, 35, 30, 60) AND "
    "dtg DURING 2020-01-10T00:00:00Z/2020-02-15T00:00:00Z",
    "count > 50 AND score <= 0.25",
    "count BETWEEN 10 AND 20 OR NOT BBOX(geom, 0, 0, 90, 45)",
    "count IN (1, 2, 3, 42)",
    "dtg > '2020-02-01T00:00:00Z'",
    "INTERSECTS(geom, POLYGON((-10 0, 40 10, 20 50, -30 40, -10 0)))",
    "DWITHIN(geom, POINT(5 45), 10, kilometers)",
]


def test_mosaic_mod_recursion_repro():
    """Minimal repro of the Mosaic bug that kept the point-in-polygon
    Pallas kernel off the TPU through round 3: with x64 enabled,
    lowering `int32_array % 2` recurses forever in
    jax/_src/pallas/mosaic/lowering.py::_convert_element_type_lowering_rule
    (the weak Python-int literal round-trips through i64 and
    _convert_helper re-enters itself until RecursionError). `x & 1` is
    the working spelling — ops/pallas_scan.py's crossing-parity test uses
    it. This repro only exercises the real Mosaic lowering, so it runs
    on TPU only (interpret mode never hits Mosaic).

    Verified against the installed stack (jax 0.9 line): `% 2` raises
    RecursionError, `& 1` compiles and runs.
    """
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("Mosaic lowering repro requires a real TPU backend")
    import sys

    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    old = sys.getrecursionlimit()
    sys.setrecursionlimit(20000)
    try:
        with jax.enable_x64(True):

            def kern_mod(x_ref, o_ref):
                o_ref[...] = x_ref[...].astype(jnp.int32) % 2

            def kern_and(x_ref, o_ref):
                o_ref[...] = x_ref[...].astype(jnp.int32) & 1

            x = jnp.ones((256, 128), jnp.float32)
            shape = jax.ShapeDtypeStruct((256, 128), jnp.int32)
            with pytest.raises(RecursionError):
                jax.block_until_ready(
                    pl.pallas_call(kern_mod, out_shape=shape)(x)
                )
            out = jax.block_until_ready(
                pl.pallas_call(kern_and, out_shape=shape)(x)
            )
            assert int(out.sum()) == 256 * 128
    finally:
        sys.setrecursionlimit(old)


def test_pip_kernel_parity_under_x64():
    """The polygon kernel must produce oracle-exact results with x64
    enabled (the bench enables x64 for data generation; round-3 shipped
    with the Pallas engine disabled under exactly this flag)."""
    import jax

    rng = np.random.default_rng(7)
    batch = make_batch(rng, 4096)
    ecql = FILTERS[6]
    compiled = compile_filter(parse_ecql(ecql), SFT)
    with jax.enable_x64(True):
        scan = compiled.pallas_scan()
        assert scan is not None
        cols = stage_columns(batch, list(compiled.device_cols))
        got = np.asarray(scan[1](cols))[: len(batch)]
    expect = compiled.host_mask(batch)
    np.testing.assert_array_equal(got, expect)


class TestPallasScanParity:
    @pytest.mark.parametrize("ecql", FILTERS)
    def test_count_and_mask_match_oracle(self, rng, ecql):
        batch = make_batch(rng, 777)  # deliberately not a tile multiple
        cf = compile_filter(parse_ecql(ecql), SFT)
        assert cf.fully_on_device, ecql
        scan = cf.pallas_scan(block_rows=32)  # force multi-tile grids
        assert scan is not None, f"pallas rejected {ecql}"
        count_fn, mask_fn = scan
        cols = stage_columns(batch, cf.device_cols)
        expect = cf.host_mask(batch)
        got_mask = np.asarray(mask_fn(cols))
        assert got_mask.shape == expect.shape
        np.testing.assert_array_equal(got_mask, expect)
        assert int(count_fn(cols)) == int(expect.sum())

    @pytest.mark.parametrize("ecql", FILTERS[:2] + FILTERS[6:7])
    def test_validity_plane_masks_rows(self, rng, ecql):
        """The padded buffers of a streaming resident index pass a bool
        validity plane: dead rows never match, in count and mask."""
        batch = make_batch(rng, 777)
        cf = compile_filter(parse_ecql(ecql), SFT)
        count_fn, mask_fn = cf.pallas_scan(block_rows=32)
        cols = stage_columns(batch, cf.device_cols)
        valid = rng.uniform(size=len(batch)) < 0.7
        expect = cf.host_mask(batch) & valid
        import jax.numpy as jnp

        got = np.asarray(mask_fn(cols, jnp.asarray(valid)))
        np.testing.assert_array_equal(got, expect)
        assert int(count_fn(cols, jnp.asarray(valid))) == int(expect.sum())

    def test_single_partial_tile(self, rng):
        batch = make_batch(rng, 17)
        cf = compile_filter(parse_ecql("count >= 0"), SFT)
        count_fn, mask_fn = cf.pallas_scan()
        cols = stage_columns(batch, cf.device_cols)
        assert int(count_fn(cols)) == 17
        assert np.asarray(mask_fn(cols)).sum() == 17

    def test_i64_word_boundary(self):
        """Values straddling the 2^32 word boundary and negatives
        (pre-1970) must compare exactly under the hi/lo split."""
        vals = np.array(
            [
                -(1 << 40),
                -1,
                0,
                1,
                (1 << 32) - 1,
                1 << 32,
                (1 << 32) + 1,
                (1 << 45) + 7,
            ],
            dtype=np.int64,
        )
        n = len(vals)
        batch = FeatureBatch.from_columns(
            SFT,
            {
                "count": np.zeros(n, np.int32),
                "score": np.zeros(n),
                "dtg": vals,
                "geom": np.zeros((n, 2)),
            },
        )
        for op in ("<", "<=", "=", "<>", ">=", ">"):
            for pivot in (-1, 0, (1 << 32) - 1, 1 << 32):
                from geomesa_tpu.filter import ast

                cf = compile_filter(ast.Compare(op, "dtg", pivot), SFT)
                count_fn, mask_fn = cf.pallas_scan()
                cols = stage_columns(batch, cf.device_cols)
                expect = cf.host_mask(batch)
                np.testing.assert_array_equal(
                    np.asarray(mask_fn(cols)), expect, err_msg=f"{op} {pivot}"
                )

    def test_float_bounds_on_i64_column(self, rng):
        batch = make_batch(rng, 64)
        from geomesa_tpu.filter import ast

        lo = int(np.asarray(batch.column("dtg")).min())
        for op in ("<", "<=", ">", ">="):
            cf = compile_filter(ast.Compare(op, "dtg", lo + 0.5), SFT)
            count_fn, _ = cf.pallas_scan()
            cols = stage_columns(batch, cf.device_cols)
            d = np.asarray(batch.column("dtg"))
            expect = {
                "<": d < lo + 0.5,
                "<=": d <= lo + 0.5,
                ">": d > lo + 0.5,
                ">=": d >= lo + 0.5,
            }[op]
            assert int(count_fn(cols)) == int(expect.sum()), op

    def test_unsupported_falls_back(self):
        sft = SimpleFeatureType.create("u", "name:String,*geom:Point")
        cf = compile_filter(parse_ecql("name = 'x'"), sft)
        assert cf.pallas_scan() is None  # string col -> host residual

    def test_jnp_device_fn_i64_split_agrees(self, rng):
        """The non-pallas device path reads the same hi/lo planes."""
        import jax

        batch = make_batch(rng, 256)
        cf = compile_filter(
            parse_ecql("dtg DURING 2020-01-10T00:00:00Z/2020-02-15T00:00:00Z"),
            SFT,
        )
        assert cf.device_cols == ["dtg__hi", "dtg__lo"]
        cols = stage_columns(batch, cf.device_cols)
        got = np.asarray(jax.jit(cf.device_fn)(cols))
        np.testing.assert_array_equal(got, cf.host_mask(batch))

    def test_float64_boundary_precision_preserved(self):
        """On the CPU (x64) parity path the kernel must compare staged
        float64 coordinate planes at full precision -- an implicit f32
        truncation would flip sub-f32-ulp boundary comparisons against
        the host oracle."""
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.filter import ast
        from geomesa_tpu.filter.compile import evaluate_host

        sft = SimpleFeatureType.create("t", "*geom:Point")
        # point above the box edge by 5e-10 in f64, identical in f32
        xmax = float(np.float32(10.1)) - 1e-9
        x = np.full(4, np.float32(10.1) - 5e-10, dtype=np.float64)
        batch = FeatureBatch.from_columns(
            sft, {"geom": np.stack([x, np.zeros(4)], axis=1)}, np.arange(4)
        )
        f = ast.BBox("geom", -20.0, -1.0, xmax, 1.0)
        cf = compile_filter(f, sft)
        cols = stage_columns(batch, cf.device_cols)
        assert cols["geom__x"].dtype == np.float64
        host = int(evaluate_host(f, batch).sum())
        count_fn, mask_fn = cf.pallas_scan()
        assert host == int(count_fn(cols)) == 0
        assert int(np.asarray(mask_fn(cols)).sum()) == 0
