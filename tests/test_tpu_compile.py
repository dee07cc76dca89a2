"""The serving kernels compile for a TPU v5e, at real widths, without one.

Each test lowers a kernel of the resident serving path for a *described*
``v5e:2x2`` topology (the TPU compiler is installed; no chip is needed)
at 2^24 rows and compiles it: what Mosaic or XLA:TPU would refuse on the
chip (VMEM overruns, unaligned tiles, i64 index maps) is refused here.
Interpret-mode tests cannot see any of that. A compile that passes is
not a chip run: nothing executes, so results and times are untested.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import numpy as np
import pytest

N = 1 << 24  # rows: a real resident partition, not a toy

FLAGSHIP = (
    "BBOX(geom, -10, 35, 30, 60) AND "
    "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
)
POLYGON = (
    "INTERSECTS(geom, POLYGON((-5 40, 20 37, 28 52, 12 47, 10 58, "
    "-8 50, -5 40))) AND dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
)


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """SingleDeviceSharding on chip 0, with the persistent compile cache
    off: an entry compiled for a described chip cannot be read back
    without one, and the suite writes nothing into the tree."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "cql,which,valid",
    [
        (FLAGSHIP, "count", False),
        (FLAGSHIP, "count", True),
        (FLAGSHIP, "mask", True),
        (POLYGON, "count", True),
        (POLYGON, "mask", False),
    ],
    ids=["flagship-count", "flagship-count-valid", "flagship-mask-valid",
         "polygon-count-valid", "polygon-mask"],
)
def test_pallas_filter_scan_compiles(one_chip, cql, which, valid):
    """The exact-filter tile kernel (ops/pallas_scan.py), with and
    without the streaming index's validity plane."""
    import jax.numpy as jnp

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.compile import compile_filter
    from geomesa_tpu.filter.ecql import parse_ecql
    from geomesa_tpu.ops.pallas_scan import build_pallas_scan

    sft = SimpleFeatureType.create(
        "gdelt", "event_id:Long,tone:Float,dtg:Date,*geom:Point:srid=4326"
    )
    cf = compile_filter(parse_ecql(cql), sft)
    count_fn, mask_fn, cols = build_pallas_scan(
        cf.device_part, sft, interpret=False
    )
    dtypes = {"geom__x": jnp.float32, "geom__y": jnp.float32,
              "dtg__hi": jnp.int32, "dtg__lo": jnp.uint32}
    coldict = {c: _spec(one_chip, (N,), dtypes[c]) for c in cols}
    fn = count_fn if which == "count" else mask_fn
    args = (coldict,)
    if valid:
        args += (_spec(one_chip, (N,), jnp.bool_),)
    _assert_kernel(_compile(fn, *args))


@pytest.mark.parametrize("which", ["count", "mask"])
@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
def test_dimplane_scan_compiles(one_chip, which, x64):
    """The loose-count dim-plane kernel with runtime query bounds
    (ops/zscan.build_z3_dimscan_rt), under x64 on and off: serving
    turns x64 on process-wide mid-run."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.ops.zscan import build_z3_dimscan_rt

    r = 2
    count_fn, mask_fn = build_z3_dimscan_rt(r, interpret=False)
    fn = count_fn if which == "count" else mask_fn
    plane = _spec(one_chip, (N,), jnp.uint32)
    with jax.enable_x64(x64):
        compiled = _compile(
            fn, _spec(one_chip, (4 + 2 * r,), jnp.uint32),
            plane, plane, plane,
        )
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "size,weighted",
    [(256, False), (512, False), (512, True)],
    ids=["256-unweighted", "512-unweighted", "512-weighted"],
)
def test_density_kernel_compiles(one_chip, size, weighted):
    """The one-hot MXU density kernel for every grid DeviceIndex.density
    sends it; weighted 512x512 was refused for VMEM before its rows per
    step were sized from the grid."""
    import jax.numpy as jnp

    from geomesa_tpu.ops.density_pallas import build_density_pallas

    fn = build_density_pallas(size, size, weighted, interpret=False)
    f32 = _spec(one_chip, (N,), jnp.float32)
    args = [_spec(one_chip, (4,), jnp.float32), f32, f32,
            _spec(one_chip, (N,), jnp.bool_)]
    if weighted:
        args.append(f32)
    _assert_kernel(_compile(fn, *args))


@pytest.mark.parametrize("which", ["count", "compact"])
def test_join_refine_kernels_compile(one_chip, which):
    """The device join's count -> compact launches (ops/join.py): plain
    XLA, so no custom call; they must fit and compile at real widths."""
    import jax.numpy as jnp

    from geomesa_tpu.ops import join as jops

    R, C, m = 4096, 1 << 20, 10_000
    i32 = _spec(one_chip, (R,), jnp.int32)
    plane = _spec(one_chip, (N,), jnp.float32)
    args = (
        (plane, plane), i32, i32, i32, i32,
        _spec(one_chip, (R,), jnp.bool_),
        _spec(one_chip, (m, 4), jnp.float32),
        _spec(one_chip, (), jnp.int32),
        None,
    )
    if which == "count":
        fn = jops.count_kernel(C, 2, False, np.float32)
    else:
        fn = jops.compact_kernel(C, 1 << 16, 2, False, np.float32)
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
