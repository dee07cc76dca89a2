"""Pallas density kernel: pixel histograms as
one-hot MXU contractions must match the scatter engine and the host
oracle, for weighted and unweighted grids, odd grid shapes, empty inputs,
and through DeviceIndex.density / the process surface.

Boundary note: the viewport multiply quantizes differently across XLA
fusion choices (FMA vs separate mul), so borderline pixels can land one
cell over between engines. Exactness tests therefore use PIXEL-CENTER
data (no coordinate within 1e-3 of a cell edge); random-data tests
compare total mass with a small tolerance.
"""

from __future__ import annotations

import numpy as np
import pytest

from geomesa_tpu.ops.density_pallas import build_density_pallas, density_oracle

ENV = np.array([-60.0, -45.0, 100.0, 60.0], np.float32)
W, H = 256, 256


def _center_data(n=20000, seed=3, width=W, height=H, env=ENV):
    """Points at pixel centers: engine-independent pixel assignment."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    px = rng.integers(0, width, n)
    py = rng.integers(0, height, n)
    x = env[0] + (px + 0.5) * (env[2] - env[0]) / width
    y = env[1] + (py + 0.5) * (env[3] - env[1]) / height
    m = (rng.random(n) < 0.7).astype(np.int8)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    return (
        jnp.asarray(x.astype(np.float32)),
        jnp.asarray(y.astype(np.float32)),
        jnp.asarray(m),
        jnp.asarray(w),
    )


def test_unweighted_exact_vs_oracle():
    import jax
    import jax.numpy as jnp

    x, y, m, _ = _center_data()
    fn = build_density_pallas(W, H, False)
    out = np.asarray(jax.jit(fn)(jnp.asarray(ENV), x, y, m))
    want = density_oracle(
        np.asarray(x), np.asarray(y), np.asarray(m), None, ENV, W, H
    )
    np.testing.assert_array_equal(out, want)
    assert out.sum() == int(np.asarray(m).sum())  # all hits inside


def test_weighted_close_vs_oracle():
    import jax
    import jax.numpy as jnp

    x, y, m, w = _center_data()
    fn = build_density_pallas(W, H, True)
    out = np.asarray(jax.jit(fn)(jnp.asarray(ENV), x, y, m, w))
    want = density_oracle(
        np.asarray(x), np.asarray(y), np.asarray(m), np.asarray(w),
        ENV, W, H,
    )
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=1e-3)


@pytest.mark.parametrize("wh", [(100, 37), (512, 64), (16, 16)])
def test_odd_grid_shapes(wh):
    import jax
    import jax.numpy as jnp

    width, height = wh
    x, y, m, _ = _center_data(n=5000, width=width, height=height)
    fn = build_density_pallas(width, height, False)
    out = np.asarray(jax.jit(fn)(jnp.asarray(ENV), x, y, m))
    want = density_oracle(
        np.asarray(x), np.asarray(y), np.asarray(m), None,
        ENV, width, height,
    )
    assert out.shape == (height, width)
    np.testing.assert_array_equal(out, want)


def test_outside_rows_and_empty():
    import jax
    import jax.numpy as jnp

    fn = build_density_pallas(64, 64, False)
    # all rows outside the viewport
    x = jnp.asarray(np.full(500, 150.0, np.float32))
    y = jnp.asarray(np.full(500, 80.0, np.float32))
    m = jnp.asarray(np.ones(500, np.int8))
    env = jnp.asarray(np.array([0, 0, 10, 10], np.float32))
    assert np.asarray(jax.jit(fn)(env, x, y, m)).sum() == 0
    # empty input
    e = jnp.asarray(np.empty(0, np.float32))
    out = np.asarray(fn(env, e, e, jnp.asarray(np.empty(0, np.int8))))
    assert out.shape == (64, 64) and out.sum() == 0


def test_random_data_mass_close_to_scatter():
    """General (borderline-bearing) data: per-cell equality is not
    guaranteed across engines, but total mass must agree within the
    handful of viewport-edge rows."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    n = 50000
    x = jnp.asarray(rng.uniform(-180, 180, n).astype(np.float32))
    y = jnp.asarray(rng.uniform(-90, 90, n).astype(np.float32))
    m = jnp.asarray((rng.random(n) < 0.5).astype(np.int8))
    fn = build_density_pallas(W, H, False)
    got = float(np.asarray(jax.jit(fn)(jnp.asarray(ENV), x, y, m)).sum())
    want = float(
        density_oracle(
            np.asarray(x), np.asarray(y), np.asarray(m), None, ENV, W, H
        ).sum()
    )
    assert abs(got - want) <= 4


def test_device_index_density_uses_pallas(monkeypatch):
    """DeviceIndex.density must serve grids <= 512x512 via the kernel."""
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.geom import Envelope
    from geomesa_tpu.store.memory import MemoryDataStore

    rng = np.random.default_rng(5)
    n = 4000
    width, height = 128, 64
    env = Envelope(-60, -45, 100, 60)
    px = rng.integers(0, width, n)
    py = rng.integers(0, height, n)
    ds = MemoryDataStore()
    ds.create_schema("d", "val:Double,dtg:Date,*geom:Point:srid=4326")
    ds.write("d", {
        "val": rng.uniform(0.5, 2.0, n),
        "dtg": rng.integers(1_577_836_800_000, 1_580_000_000_000, n),
        "geom": np.stack([
            env.xmin + (px + 0.5) * (env.xmax - env.xmin) / width,
            env.ymin + (py + 0.5) * (env.ymax - env.ymin) / height,
        ], axis=1),
    })
    di = DeviceIndex(ds, "d")
    import geomesa_tpu.ops.density_pallas as dpal

    built = []
    orig = dpal.build_density_pallas

    def spy(*a, **k):
        built.append(a)
        return orig(*a, **k)

    monkeypatch.setattr(dpal, "build_density_pallas", spy)
    cql = "BBOX(geom, -179, -89, 179, 89)"
    grid = di.density(cql, env, width, height)
    assert built, "DeviceIndex.density did not build the Pallas kernel"
    # INCLUDE (no filter) also serves from the resident path: the fused
    # hook uses a constant-true mask (a full-viewport render must not
    # fall back to the store)
    g_inc = di.density("INCLUDE", env, width, height)
    assert g_inc is not None
    np.testing.assert_array_equal(g_inc, grid)  # bbox covers everything
    assert grid is not None and grid.shape == (height, width)
    # parity vs the host oracle on the same rows (pixel-center data)
    batch = ds.query("d").batch
    x, y = batch.point_coords("geom")
    want = density_oracle(
        x.astype(np.float32), y.astype(np.float32),
        np.ones(n, np.int8), None,
        np.array([env.xmin, env.ymin, env.xmax, env.ymax], np.float32),
        width, height,
    )
    np.testing.assert_array_equal(grid, want)
    # weighted through the same path
    gw = di.density(cql, env, width, height, weight_attr="val")
    ww = density_oracle(
        x.astype(np.float32), y.astype(np.float32),
        np.ones(n, np.int8), batch.column("val"),
        np.array([env.xmin, env.ymin, env.xmax, env.ymax], np.float32),
        width, height,
    )
    np.testing.assert_allclose(gw, ww, rtol=2e-5, atol=1e-3)


def test_large_grid_falls_back_to_scatter(monkeypatch):
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.geom import Envelope
    from geomesa_tpu.store.memory import MemoryDataStore

    rng = np.random.default_rng(6)
    n = 500
    ds = MemoryDataStore()
    ds.create_schema("d", "dtg:Date,*geom:Point:srid=4326")
    ds.write("d", {
        "dtg": rng.integers(1_577_836_800_000, 1_580_000_000_000, n),
        "geom": np.stack(
            [rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)], axis=1
        ),
    })
    di = DeviceIndex(ds, "d")
    import geomesa_tpu.ops.density_pallas as dpal

    monkeypatch.setattr(
        dpal, "build_density_pallas",
        lambda *a, **k: pytest.fail("kernel built for an oversize grid"),
    )
    grid = di.density(
        "BBOX(geom, -179, -89, 179, 89)",
        Envelope(-10, -10, 10, 10), 1024, 1024,
    )
    assert grid is not None and grid.sum() == n
