"""The nyc-taxi-trips configuration and its zone mix on the CPU: the
generator is deterministic and stores every field of the record, the
zones partition the extent, the pool's longest request line fits the
HTTP server's limit, and the zone cell's readers read their fixtures
(and nothing without them)."""

from __future__ import annotations

import os
import sys
from urllib.parse import urlencode

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, reference, traffic  # noqa: E402

CELL = "taxi-zones-closed"


@pytest.fixture(scope="module")
def taxi():
    return cells.load_config("nyc-taxi-trips")


def test_generator_is_deterministic_and_full_width(taxi):
    from geomesa_tpu.features.sft import SimpleFeatureType

    cfg, mod = taxi
    a, b = mod.generate(cfg, 2**31 + 3, 2000), mod.generate(cfg, 2**31 + 3,
                                                            2000)
    sft = SimpleFeatureType.create(cfg["type_name"], cfg["spec"])
    assert len(sft.attributes) == 18  # the 19 fields, pickup as the point
    assert sft.dtg_field == "dtg"
    for attr in sft.attributes:
        keys = ("lon", "lat") if attr.is_geometry else (attr.name,)
        for k in keys:
            assert len(a[k]) == 2000, k
            assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["lon"], mod.generate(cfg, 4, 2000)["lon"])
    assert np.all(np.diff(a["dtg"]) >= 0)
    assert np.all(a["tpep_dropoff_datetime"] > a["dtg"])
    assert set(a["store_and_fwd_flag"]) <= {"N", "Y"}
    pts = mod.points(cfg, 2**31 + 3, 2000)
    assert all(np.array_equal(pts[k], a[k]) for k in ("lon", "lat", "dtg"))
    assert mod.shapes(cfg) is mod.shapes(dict(cfg))  # built once


def test_zones_partition_the_extent(taxi):
    """Every pickup lies in exactly one zone: summed over the 263 zones,
    the reference counts every row (all lie inside the extent)."""
    cfg, mod = taxi
    zones = mod.shapes(cfg)
    assert len(zones) == 263
    pts = mod.points(cfg, 17, 1 << 16)
    x0, y0, x1, y1 = mod.extent(cfg)
    assert pts["lon"].min() >= x0 and pts["lon"].max() < x1
    assert pts["lat"].min() >= y0 and pts["lat"].max() < y1
    tab = reference.Table(**pts)
    t0, t1 = int(pts["dtg"][0]), int(pts["dtg"][-1])
    total = sum(reference.polygon_count(tab, z["parts"], t0, t1)
                for z in zones)
    assert total == 1 << 16
    edges = np.array([z["edges"] for z in zones])
    assert edges.min() >= 16 and edges.max() <= 1024
    assert 0.03 <= np.mean([len(z["parts"]) > 1 for z in zones]) <= 0.07
    for z in zones:  # float32 vertices, printed exactly
        for rings in z["parts"]:
            for r in rings:
                assert np.array_equal(r.astype(np.float32), r)
                assert np.array_equal(r[0], r[-1])


def test_longest_request_line_fits(taxi):
    """Python's http.server answers 414 to a request line over 64 KiB."""
    cfg, mod = taxi
    mix = cells.load_mix(cells.cell(cells.manifest(), CELL)["traffic"])
    plan = traffic.Plan(mix, cfg, mod, 5, 20.0)
    urls, _ = plan.wire()
    assert len(urls) == 64
    longest = max(len(f"GET {u}&{urlencode({'lane': 'batch'})} HTTP/1.1")
                  for u in urls)
    assert longest < 60 * 1024


class _Trace:
    """The reduction's kernel lookup, as a chip trace of the zone cell
    reads: (program runs, seconds) of the ops a pattern matches."""

    def __init__(self, runs: int, seconds: float):
        self.runs, self.seconds = runs, seconds

    def kernel(self, patterns):
        hit = any(p.startswith("^jit_pip_count/") for p in patterns)
        return (self.runs, self.seconds) if hit else (0, 0.0)


def _ctx(before: dict, after: dict, trace=None):
    class Ctx:
        rows = 1 << 25
        peak = cells.peaks("TPU v5 lite")

    ctx = Ctx()
    ctx.prom_before, ctx.prom_after, ctx.trace = before, after, trace
    return ctx


BEFORE = {'geomesa_stage_seconds_count{stage="cql.parse"}': 10.0,
          'geomesa_stage_seconds_sum{stage="cql.parse"}': 0.05,
          'geomesa_pip_blocks_total{outcome="tested"}': 100.0,
          'geomesa_pip_blocks_total{outcome="skipped"}': 900.0,
          "geomesa_pip_edge_tests_total": 1e9}
AFTER = {'geomesa_stage_seconds_count{stage="cql.parse"}': 110.0,
         'geomesa_stage_seconds_sum{stage="cql.parse"}': 0.85,
         'geomesa_pip_blocks_total{outcome="tested"}': 2100.0,
         'geomesa_pip_blocks_total{outcome="skipped"}': 38900.0,
         "geomesa_pip_edge_tests_total": 5.1e10}


def test_zone_readers_read_their_fixture():
    read = cells.load_reader
    ctx = _ctx(BEFORE, AFTER, _Trace(100, 0.5))
    assert read("parse_ms.zones")(ctx) == pytest.approx(8.0)
    assert read("pip_tested_pct.zones")(ctx) == pytest.approx(5.0)
    assert read("pip_edge_rate.zones")(ctx) == pytest.approx(100.0)
    # 100 calls x (2^25 x 16 B + the edge table) at 819 GB/s, over 0.5 s
    least = 100 * ((1 << 25) * 16 + 4 * 1024 * 4) / 819e9
    assert read("pip_roofline.zones")(ctx) == pytest.approx(
        100 * least / 0.5)


@pytest.mark.parametrize("metric", ["parse_ms.zones", "pip_tested_pct.zones",
                                    "pip_edge_rate.zones",
                                    "pip_roofline.zones"])
def test_zone_readers_read_nothing_without_it(metric):
    """A program without the counters, or a trace without the kernel,
    reads None (never 0)."""
    read = cells.load_reader(metric)
    assert read(_ctx({}, {}, None)) is None
    assert read(_ctx({}, {}, _Trace(0, 0.0))) is None


@pytest.mark.parametrize("seed", [7, 2**31 + 13])
def test_control_is_not_correct(seed):
    """The reference in bfloat16 (the precision below the configuration's
    float32) fails the zone mix's count_err limit."""
    from benchmark import control

    mix = cells.load_mix(cells.cell(cells.manifest(), CELL)["traffic"])
    ctl = control.readings(CELL, seed, 2.0, {
        "config": {"rows": 1 << 16},
        "mix": {"views": dict(mix["views"], count=16)}})
    assert ctl["count_err"]["value"] > 100 * ctl["count_err"]["limit"]


def test_zone_kernel_readers_on_a_chip_trace():
    """A trace recorded on a TPU v5e: three calls of the polygon
    kernel over 2^25 rows (a 64-edge bucket over 7 days, a 1,024-edge
    bucket over 1 and 30 days). The readers find the kernel by the name
    its ``pallas_call`` carries."""
    from benchmark import trace_reduce

    red = trace_reduce.Reduction(os.path.join(
        os.path.dirname(__file__), "data", "pip_probe.xplane.pb"))
    runs, seconds = red.kernel([r"^jit_pip_count/%pip_count"])
    assert runs == 3 and 0.04 < seconds < 0.06
    ctx = _ctx({"geomesa_pip_edge_tests_total": 0.0},
               {"geomesa_pip_edge_tests_total": 1e10}, red)
    roof = cells.load_reader("pip_roofline.zones")(ctx)
    least = 3 * ((1 << 25) * 16 + 4 * 1024 * 4) / 819e9
    assert roof == pytest.approx(100 * least / seconds)
    assert 0 < roof < 100
    assert cells.load_reader("pip_edge_rate.zones")(ctx) == pytest.approx(
        1e10 / seconds / 1e9)
