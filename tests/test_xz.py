"""XZ2/XZ3 extent-curve properties.

Key invariant (the XZ coverage property): for any set of boxes and any query
window, every box that intersects the query must have its code inside the
emitted ranges (no false negatives); boxes far from the query should mostly
be excluded.
"""

import numpy as np
import pytest

from geomesa_tpu.curves import XZ2SFC, XZ3SFC


def _covered(codes, ranges):
    arr = np.array([(r.lower, r.upper) for r in ranges], dtype=np.int64)
    idx = np.searchsorted(arr[:, 0], codes, side="right") - 1
    return (idx >= 0) & (codes <= arr[np.clip(idx, 0, len(arr) - 1), 1])


def _rand_boxes(rng, n, x0, y0, x1, y1, max_size):
    xmin = rng.uniform(x0, x1 - max_size, n)
    ymin = rng.uniform(y0, y1 - max_size, n)
    w = rng.uniform(0, max_size, n)
    h = rng.uniform(0, max_size, n)
    return xmin, ymin, xmin + w, ymin + h


class TestXZ2:
    def test_point_boxes_deterministic(self):
        sfc = XZ2SFC()
        c1 = sfc.index(np.array([2.0]), np.array([48.0]), np.array([2.0]), np.array([48.0]))
        c2 = sfc.index(np.array([2.0]), np.array([48.0]), np.array([2.0]), np.array([48.0]))
        assert c1[0] == c2[0] >= 0

    def test_codes_within_keyspace(self, rng):
        sfc = XZ2SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 5000, -180, -90, 180, 90, 5.0)
        codes = sfc.index(xmin, ymin, xmax, ymax)
        max_code = (4 ** (sfc.g + 1) - 1) // 3
        assert np.all(codes >= 0)
        assert np.all(codes <= max_code)

    def test_no_false_negatives(self, rng):
        sfc = XZ2SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 5000, -20, 20, 30, 60, 2.0)
        codes = sfc.index(xmin, ymin, xmax, ymax)
        q = (-5.0, 42.0, 8.0, 51.0)
        ranges = sfc.ranges(*q)
        hits = _covered(codes, ranges)
        intersecting = (
            (xmax >= q[0]) & (xmin <= q[2]) & (ymax >= q[1]) & (ymin <= q[3])
        )
        assert np.all(hits[intersecting]), "false negatives in XZ2 ranges"

    def test_prunes_far_boxes(self, rng):
        sfc = XZ2SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 5000, 100, -80, 170, -40, 2.0)
        codes = sfc.index(xmin, ymin, xmax, ymax)
        ranges = sfc.ranges(-5.0, 42.0, 8.0, 51.0)
        assert np.mean(_covered(codes, ranges)) < 0.05

    def test_large_geometries_low_level(self):
        # a hemisphere-sized box is stored at level 1 (every box fits some
        # level-1 enlarged cell, which spans the whole space), so its code is
        # one of the four level-1 quadrant codes.
        sfc = XZ2SFC()
        code = sfc.index(
            np.array([-170.0]), np.array([-80.0]), np.array([170.0]), np.array([80.0])
        )
        step = (4**sfc.g - 1) // 3
        assert int(code[0]) in {1 + q * step for q in range(4)}


class TestValidation:
    def test_inverted_box_rejected(self):
        sfc = XZ2SFC()
        with pytest.raises(ValueError, match="antimeridian"):
            sfc.index(
                np.array([170.0]), np.array([0.0]), np.array([-170.0]), np.array([1.0])
            )

    def test_g_capacity_limits(self):
        from geomesa_tpu.curves.xz import XZSFC

        with pytest.raises(ValueError, match="int64"):
            XZSFC(32, dims=2)
        with pytest.raises(ValueError, match="int64"):
            XZSFC(21, dims=3)
        XZSFC(31, dims=2)
        XZSFC(20, dims=3)


class TestXZ3:
    def test_no_false_negatives(self, rng):
        sfc = XZ3SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 3000, -20, 20, 30, 60, 2.0)
        tmin = rng.uniform(0, 500000, 3000)
        tmax = tmin + rng.uniform(0, 3600, 3000)
        codes = sfc.index(xmin, ymin, tmin, xmax, ymax, np.minimum(tmax, 604800))
        q = (-5.0, 42.0, 86400.0, 8.0, 51.0, 259200.0)
        ranges = sfc.ranges(*q)
        hits = _covered(codes, ranges)
        inter = (
            (xmax >= q[0])
            & (xmin <= q[3])
            & (ymax >= q[1])
            & (ymin <= q[4])
            & (tmax >= q[2])
            & (tmin <= q[5])
        )
        assert np.all(hits[inter]), "false negatives in XZ3 ranges"

    def test_prunes_far_boxes(self, rng):
        sfc = XZ3SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 3000, 100, -80, 170, -40, 2.0)
        tmin = rng.uniform(400000, 500000, 3000)
        codes = sfc.index(xmin, ymin, tmin, xmax, ymax, tmin + 100)
        ranges = sfc.ranges(-5.0, 42.0, 1000.0, 8.0, 51.0, 2000.0)
        assert np.mean(_covered(codes, ranges)) < 0.05


def _u64(hi, lo):
    return (np.asarray(hi).astype(np.uint64) << np.uint64(32)) | np.asarray(
        lo
    ).astype(np.uint64)


class TestDeviceEncode:
    """index_jax_hi_lo must agree bit-for-bit with the host encode under
    float64 (the CPU/x64 test platform)."""

    def test_xz2_parity_random(self, rng):
        import jax
        import jax.numpy as jnp

        sfc = XZ2SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 50_000, -180, -90, 179, 89, 3.0)
        xmax = np.minimum(xmax, 180.0)
        ymax = np.minimum(ymax, 90.0)
        host = sfc.index(xmin, ymin, xmax, ymax).astype(np.uint64)
        hi, lo = jax.jit(sfc.index_jax_hi_lo)(
            *map(jnp.asarray, (xmin, ymin, xmax, ymax))
        )
        np.testing.assert_array_equal(_u64(hi, lo), host)

    def test_xz2_parity_adversarial(self):
        import jax
        import jax.numpy as jnp

        sfc = XZ2SFC()
        # degenerate points, whole world, exact power-of-two extents,
        # lat/lon maxima
        xmin = np.array([-180.0, 0.0, -180.0, 10.0, -45.0, 179.9])
        ymin = np.array([-90.0, 0.0, -90.0, 10.0, -45.0, 89.9])
        xmax = np.array(
            [180.0, 0.0, -180.0 + 360.0 * 0.25, 10.0 + 360 * 2**-10,
             -45.0 + 360 * 2**-12, 180.0]
        )
        ymax = np.array(
            [90.0, 0.0, -90.0 + 180.0 * 0.25, 10.0 + 180 * 2**-10,
             -45.0 + 180 * 2**-12, 90.0]
        )
        host = sfc.index(xmin, ymin, xmax, ymax).astype(np.uint64)
        hi, lo = jax.jit(sfc.index_jax_hi_lo)(
            *map(jnp.asarray, (xmin, ymin, xmax, ymax))
        )
        np.testing.assert_array_equal(_u64(hi, lo), host)

    def test_xz3_parity_random(self, rng):
        import jax
        import jax.numpy as jnp

        sfc = XZ3SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 50_000, -180, -90, 179, 89, 3.0)
        xmax = np.minimum(xmax, 180.0)
        ymax = np.minimum(ymax, 90.0)
        tmin = rng.uniform(0, sfc.t_max, len(xmin))
        tmax = np.minimum(
            tmin + rng.uniform(0, sfc.t_max * 0.01, len(xmin)), sfc.t_max
        )
        host = sfc.index(xmin, ymin, tmin, xmax, ymax, tmax).astype(np.uint64)
        hi, lo = jax.jit(sfc.index_jax_hi_lo)(
            *map(jnp.asarray, (xmin, ymin, tmin, xmax, ymax, tmax))
        )
        np.testing.assert_array_equal(_u64(hi, lo), host)


class TestDeviceRangeMask:
    """The device xz key-range mask must agree with the host range cover
    (same ranges, same codes) and keep the no-false-negative invariant."""

    def test_xz2_mask_matches_host_cover(self, rng):
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        sfc = XZ2SFC()
        xmin, ymin, xmax, ymax = _rand_boxes(rng, 20_000, -20, 20, 30, 60, 2.0)
        codes = sfc.index(xmin, ymin, xmax, ymax)
        q = (-5.0, 42.0, 8.0, 51.0)
        bounds = zscan.pad_ranges(zscan.xz2_query_bounds(sfc, *q))
        hi = (codes.astype(np.uint64) >> np.uint64(32)).astype(np.uint32)
        lo = (codes.astype(np.uint64) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        m = np.asarray(
            jax.jit(zscan.xz_range_mask)(
                jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(bounds)
            )
        )
        # no false negatives vs true box intersection
        intersecting = (
            (xmax >= q[0]) & (xmin <= q[2]) & (ymax >= q[1]) & (ymin <= q[3])
        )
        assert np.all(m[intersecting])
        # the device mask equals the HOST cover for the same budgeted ranges
        host_cover = _covered(
            codes, sfc.ranges(*q, max_ranges=128)
        )
        np.testing.assert_array_equal(m, host_cover)
        # and it prunes: far boxes mostly excluded
        assert m.mean() < 0.5

    def test_xz3_mask_binned(self, rng):
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.curves.binnedtime import to_binned_time
        from geomesa_tpu.ops import zscan

        sfc = XZ3SFC()
        n = 20_000
        xmin, ymin, xmax, ymax = _rand_boxes(rng, n, -20, 20, 30, 60, 2.0)
        # ~5 weeks of instantaneous rows
        ms = rng.integers(1_577_836_800_000, 1_580_860_800_000, n)
        bins, off = to_binned_time(ms, sfc.period)
        offf = off.astype(np.float64)
        codes = sfc.index(xmin, ymin, offf, xmax, ymax, offf)
        q = (-5.0, 42.0, 8.0, 51.0)
        t0, t1 = 1_578_441_600_000, 1_580_256_000_000  # inner window
        bounds, ids = zscan.xz3_query_bounds(sfc, *q, t0, t1)
        bounds, ids = zscan.pad_bins(bounds, ids)
        hi = (codes.astype(np.uint64) >> np.uint64(32)).astype(np.uint32)
        lo = (codes.astype(np.uint64) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        m = np.asarray(
            jax.jit(zscan.xz3_range_mask)(
                jnp.asarray(hi), jnp.asarray(lo),
                jnp.asarray(bins.astype(np.int32)),
                jnp.asarray(bounds), jnp.asarray(ids),
            )
        )
        intersecting = (
            (xmax >= q[0]) & (xmin <= q[2]) & (ymax >= q[1]) & (ymin <= q[3])
            & (ms >= t0) & (ms <= t1)
        )
        assert intersecting.sum() > 0
        assert np.all(m[intersecting]), "false negatives in device xz3 mask"
        # rows entirely outside the time window's bins never match
        outside_bins = ~np.isin(bins, ids[ids >= 0])
        assert not np.any(m[outside_bins])
