#!/usr/bin/env python3
"""On-chip smoke test of the resident serving path.

Generates a GDELT-shaped point table from ``--seed`` (the schema and
filters of ``bench.py``'s pipeline leg), loads it through the normal
write path (Parquet -> ``ParquetConverter`` -> ``FileSystemDataStore``
flush), serves it in this process with ``serve_background(ds,
resident=True)`` -- the code path of ``python -m geomesa_tpu.tools serve
--resident`` -- and checks every HTTP answer against a host reference
built from the seeded arrays (numpy + ``evaluate_host``).

Nothing here falls back quietly. The run fails (nonzero exit, last line
``{"ok": false, ...}``) when the device is not a TPU, when any response
carries ``X-Degraded`` or ``geomesa_resilience_degraded_total`` moves,
when the flagship exact count is not served by the Pallas kernel, when
any answer differs from its reference, or when any phase raises. On a
TPU that passes, the last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py                 # one chip, 2^26 rows
    python chip_smoke.py --chips 4       # mesh-sharded serving, 2^27 rows
    python chip_smoke.py --rows 65536    # CPU rehearsal: runs every phase,
                                         # then fails (platform is not tpu)

``--chips 4`` runs only the mesh path (``serve --resident --mesh``,
``ShardedDeviceIndex`` over ``serving_mesh(4)``) and what it is compared
with: the reference, and the same requests served by a one-chip resident
index over the same store. It is slow: at 2^26 rows the mesh staging
alone took 372 s on a v5e 2x2 host (PR 21), so give the chip tool a
long timeout. Earlier lines are human-readable progress; the timings
they print are first-call (compile) and warm seconds of a smoke run, not
benchmark results.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
TYPE = "gdelt"
SPEC = "event_id:Long,tone:Float,dtg:Date,*geom:Point:srid=4326"
T0_ISO, T1_ISO = "2020-01-01T00:00:00", "2020-03-01T00:00:00"
WINDOW = "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
FLAGSHIP = f"BBOX(geom, -10, 35, 30, 60) AND {WINDOW}"
FLAGSHIP_ENV = (-10.0, 35.0, 30.0, 60.0)
POLYGON = (
    "INTERSECTS(geom, POLYGON((-5 40, 20 37, 28 52, 12 47, 10 58, "
    f"-8 50, -5 40))) AND {WINDOW}"
)
CITY = (  # Paris, the whole two months
    "BBOX(geom, 2.0, 48.5, 3.0, 49.5) AND "
    "dtg DURING 2019-12-31T00:00:00Z/2020-03-02T00:00:00Z"
)
KNN_AT = (2.35, 48.85)
WARM_REPS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_rss_gib() -> float:
    """This process's peak resident set so far (Linux reports KiB)."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


class Failed(Exception):
    """A check that did not hold (the message says which)."""


# -- data ------------------------------------------------------------------


def generate(rows: int, seed: int) -> dict:
    """GDELT-shaped columns, seeded: uniform lon/lat (float32, as GDELT
    ships them), epoch-ms timestamps over two months, a float32 tone.
    ``event_id`` is the row number, and so the feature id the converter
    assigns."""
    import numpy as np

    from geomesa_tpu.filter.ecql import parse_instant

    rng = np.random.default_rng(seed)
    return {
        "event_id": np.arange(rows, dtype=np.int64),
        "ts": rng.integers(parse_instant(T0_ISO), parse_instant(T1_ISO), rows),
        "lon": rng.uniform(-180, 180, rows).astype(np.float32),
        "lat": rng.uniform(-90, 90, rows).astype(np.float32),
        "tone": rng.uniform(-10, 10, rows).astype(np.float32),
    }


def load_store(cols: dict, work: str):
    """Parquet file -> ParquetConverter -> FileSystemDataStore flush."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from geomesa_tpu.convert import ParquetConverter
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.store.fs import FileSystemDataStore

    path = os.path.join(work, "gdelt.parquet")
    t = time.perf_counter()
    pq.write_table(pa.table(cols), path)
    log(f"  parquet written: {os.path.getsize(path) / 2**20:.0f} MiB "
        f"in {time.perf_counter() - t:.1f}s")
    sft = SimpleFeatureType.create(TYPE, SPEC)
    conv = ParquetConverter({"fields": [
        {"name": "event_id", "path": "event_id"},
        {"name": "tone", "path": "tone"},
        {"name": "dtg", "path": "ts"},
        {"name": "geom", "transform": "point($lon, $lat)"},
    ]}, sft)
    t = time.perf_counter()
    batch = conv.process(path).batch
    log(f"  converted {len(batch):,} rows in {time.perf_counter() - t:.1f}s"
        f" (host peak RSS {peak_rss_gib():.1f} GiB)")
    os.remove(path)
    ds = FileSystemDataStore(os.path.join(work, "store"))
    ds.create_schema(sft)
    t = time.perf_counter()
    ds.write(TYPE, batch)
    del batch
    ds.flush(TYPE)
    rows = ds.manifest_rows(TYPE)
    log(f"  FS store flushed: {rows:,} rows in {time.perf_counter() - t:.1f}s"
        f" (host peak RSS {peak_rss_gib():.1f} GiB)")
    return ds, rows


# -- host reference --------------------------------------------------------


class Reference:
    """Answers computed on the host from the seeded arrays: numpy
    prefilters by envelope and time, ``evaluate_host`` decides exactly."""

    def __init__(self, cols: dict):
        import numpy as np

        self.np = np
        # float32 as generated (exact: the store widens them losslessly);
        # exact tests cast the candidates to float64
        self.x = cols["lon"]
        self.y = cols["lat"]
        self.t = cols["ts"]
        self.w = cols["tone"]
        self.fid = cols["event_id"]  # == row number

    def _candidates(self, env, t_lo, t_hi):
        np = self.np
        # a superset: widened past float32 rounding of the bounds
        xmin, ymin, xmax, ymax = env
        m = (self.x >= xmin - 1e-3) & (self.x <= xmax + 1e-3)
        m &= (self.y >= ymin - 1e-3) & (self.y <= ymax + 1e-3)
        m &= (self.t >= t_lo) & (self.t <= t_hi)
        return np.nonzero(m)[0]

    def _batch(self, idx):
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.features.sft import SimpleFeatureType

        np = self.np
        return FeatureBatch.from_columns(
            SimpleFeatureType.create(TYPE, SPEC),
            {
                "event_id": self.fid[idx],
                "tone": self.w[idx],
                "dtg": self.t[idx],
                "geom": np.stack([self.x[idx], self.y[idx]], axis=1).astype(
                    np.float64),
            },
            self.fid[idx],
        )

    def rows(self, cql: str, env, t_lo, t_hi):
        """Row ids matching ``cql``; env/time bound a superset of them."""
        from geomesa_tpu.filter.compile import evaluate_host
        from geomesa_tpu.filter.ecql import parse_ecql

        idx = self._candidates(env, t_lo, t_hi)
        return idx[evaluate_host(parse_ecql(cql), self._batch(idx))]

    def loose_count(self, env, t_lo, t_hi) -> int:
        """Cell-granular (loose) semantics of a bbox+window, computed
        from the Z3 curve's quantization independently of the kernels."""
        from geomesa_tpu.curves.binnedtime import (
            bins_for_interval,
            to_binned_time,
        )
        from geomesa_tpu.curves.z3 import Z3SFC

        np = self.np
        sfc = Z3SFC()
        x0, y0, x1, y1 = env
        nx = np.asarray(sfc.lon.normalize(self.x.astype(np.float64)))
        ny = np.asarray(sfc.lat.normalize(self.y.astype(np.float64)))
        sp = (nx >= int(sfc.lon.normalize(x0))) & (
            nx <= int(sfc.lon.normalize(x1)))
        sp &= (ny >= int(sfc.lat.normalize(y0))) & (
            ny <= int(sfc.lat.normalize(y1)))
        idx = np.nonzero(sp)[0]
        bins, off = to_binned_time(self.t[idx], sfc.period)
        nt = np.asarray(sfc.time.normalize(off))
        tm = np.zeros(len(idx), bool)
        for b, lo, hi in bins_for_interval(t_lo, t_hi, sfc.period):
            tm |= (bins == b) & (nt >= int(sfc.time.normalize(lo))) & (
                nt <= int(sfc.time.normalize(hi)))
        return int(tm.sum())

    def density(self, idx, env, width, height, weighted: bool):
        from geomesa_tpu.process.density import _density_host

        np = self.np
        w = self.w[idx].astype(np.float64) if weighted else np.ones(len(idx))
        return _density_host(self.x[idx].astype(np.float64),
                             self.y[idx].astype(np.float64), w, env, width,
                             height)

    def dist(self, fid: int, px, py) -> float:
        from geomesa_tpu.process.knn import _dist_deg

        return float(_dist_deg(float(self.x[fid]), float(self.y[fid]), px,
                               py))

    def knn(self, px, py, k, max_r=45.0):
        from geomesa_tpu.process.knn import _dist_deg

        np = self.np
        m = (np.abs(self.x - px) <= max_r) & (np.abs(self.y - py) <= max_r)
        idx = np.nonzero(m)[0]
        d = _dist_deg(self.x[idx].astype(np.float64),
                      self.y[idx].astype(np.float64), px, py)
        order = np.argsort(d, kind="stable")[:k]
        return self.fid[idx[order]], d[order]


# -- HTTP client -----------------------------------------------------------


class Client:
    def __init__(self, server):
        host, port = server.server_address[:2]
        self.base = f"http://{host}:{port}"

    def get(self, path: str, **params):
        url = f"{self.base}{path}?{urllib.parse.urlencode(params)}"
        with urllib.request.urlopen(url, timeout=900) as r:
            body = json.loads(r.read())
            degraded = r.headers.get("X-Degraded")
        if degraded:
            raise Failed(f"GET {path} answered degraded: {degraded}")
        return body

    def degraded_total(self) -> float:
        with urllib.request.urlopen(self.base + "/metrics", timeout=60) as r:
            text = r.read().decode()
        return sum(
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith("geomesa_resilience_degraded_total")
        )


def timed(fn):
    """(answer, first-call seconds, median warm seconds); every repeat
    must return the first call's answer."""
    t = time.perf_counter()
    first = fn()
    cold = time.perf_counter() - t
    warm = []
    for _ in range(WARM_REPS):
        t = time.perf_counter()
        again = fn()
        warm.append(time.perf_counter() - t)
        if json.dumps(again, sort_keys=True, default=str) != json.dumps(
                first, sort_keys=True, default=str):
            raise Failed("a repeated request changed its answer")
    return first, cold, sorted(warm)[len(warm) // 2]


# -- the requests ----------------------------------------------------------


def engines(di, cql: str) -> dict:
    """Which device engine serves ``cql`` on the resident index ``di``:
    the exact filter's kernel (Pallas tiles or XLA-fused jnp, set by
    ``CompiledFilter.jitted_scan``) and the loose key-plane engine."""
    from geomesa_tpu.filter.ecql import parse_ecql

    f = parse_ecql(cql)
    exact = di._compiled.get(repr(f))
    if exact is None:
        eng = "not compiled"
    else:
        eng = getattr(exact[0], "scan_engine", "host")
        if eng == "pallas" and not di._pallas_tiles:
            eng = "xla (mesh-partitioned)"
    lb = di._loose_bounds(f)
    loose = "none" if lb is None else (
        "dim-plane" if len(lb) == 3 and lb[0] == "dim" else "masked-compare")
    return {"exact": eng, "loose": loose}


def density_engine(di, width: int, height: int, weighted: bool) -> str:
    """The Pallas one-hot kernel when ``DeviceIndex.density`` built one
    for this grid, else the XLA scatter."""
    kernels = getattr(di, "_density_kernels", {})
    return "pallas-onehot" if (width, height, weighted) in kernels \
        else "xla-scatter"


def run_requests(server, ref: Reference, cli: Client) -> dict:
    """Send every request, check each against the reference, and return
    {name: answer summary} for the cross-run comparison. Each request is
    its own phase: all run, then one Failed names every check that did
    not hold."""
    import numpy as np

    from geomesa_tpu.filter.ecql import parse_instant

    results: dict = {}
    failures: list = []
    w_lo = parse_instant("2020-01-10T00:00:00")
    w_hi = parse_instant("2020-01-15T00:00:00")
    world = (-180.0, -90.0, 180.0, 90.0)

    def resident():
        return server.RequestHandlerClass._resident_cache[TYPE]

    def phase(name, fn):
        try:
            summary, line = fn()
            results[name] = summary
            log(f"  [ok]   {name}: {line}")
        except Exception as e:  # each request is reported, then the run fails
            failures.append(f"{name}: {e!r}")
            log(f"  [FAIL] {name}: {e!r}")
            traceback.print_exc(file=sys.stdout)

    def count_exact():
        body, cold, warm = timed(
            lambda: cli.get(f"/count/{TYPE}", cql=FLAGSHIP, loose="0"))
        want = len(ref.rows(FLAGSHIP, FLAGSHIP_ENV, w_lo, w_hi))
        eng = engines(resident(), FLAGSHIP)
        if body["count"] != want:
            raise Failed(f"count {body['count']} != reference {want}")
        if eng["exact"] != "pallas" and resident()._pallas_tiles:
            raise Failed(f"flagship served by engine {eng['exact']!r}, "
                         "not the Pallas kernel")
        return body["count"], (
            f"count={body['count']:,} == reference; engine={eng['exact']}; "
            f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    def count_loose():
        body, cold, warm = timed(
            lambda: cli.get(f"/count/{TYPE}", cql=FLAGSHIP, loose="1"))
        want = ref.loose_count(FLAGSHIP_ENV, w_lo, w_hi)
        eng = engines(resident(), FLAGSHIP)
        if body["count"] != want:
            raise Failed(f"loose count {body['count']} != cell reference "
                         f"{want}")
        if eng["loose"] != "dim-plane" and resident()._pallas_tiles:
            raise Failed(f"loose count served by {eng['loose']!r}, not the "
                         "dim-plane kernel")
        return body["count"], (
            f"count={body['count']:,} == cell reference; "
            f"engine={eng['loose']}; first={cold:.3f}s "
            f"warm={warm * 1e3:.2f}ms")

    def count_polygon():
        body, cold, warm = timed(
            lambda: cli.get(f"/count/{TYPE}", cql=POLYGON, loose="0"))
        want = len(ref.rows(POLYGON, (-8.0, 37.0, 28.0, 58.0), w_lo, w_hi))
        eng = engines(resident(), POLYGON)
        if body["count"] != want:
            raise Failed(f"polygon count {body['count']} != reference "
                         f"{want}")
        return body["count"], (
            f"count={body['count']:,} == reference; engine={eng['exact']}; "
            f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    def features():
        body, cold, warm = timed(
            lambda: cli.get(f"/features/{TYPE}", cql=CITY))
        got = sorted(int(f["id"]) for f in body["features"])
        want = sorted(int(i) for i in ref.fid[ref.rows(
            CITY, (2.0, 48.5, 3.0, 49.5), parse_instant(T0_ISO) - 1,
            parse_instant(T1_ISO))])
        if got != want:
            raise Failed(f"{len(got)} fids != reference's {len(want)} "
                         f"(symmetric difference "
                         f"{len(set(got) ^ set(want))})")
        return got, (f"{len(got):,} features, fid set == reference; "
                     f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    def density_256():
        bbox = ",".join(str(v) for v in FLAGSHIP_ENV)
        body, cold, warm = timed(lambda: cli.get(
            f"/density/{TYPE}", cql=WINDOW, bbox=bbox, width=256,
            height=256))
        grid = np.asarray(body["counts"], np.float64)
        idx = ref.rows(WINDOW, world, w_lo, w_hi)
        want = ref.density(idx, FLAGSHIP_ENV, 256, 256, weighted=False)
        in_window = len(ref.rows(FLAGSHIP, FLAGSHIP_ENV, w_lo, w_hi))
        if grid.shape != (256, 256):
            raise Failed(f"grid shape {grid.shape}")
        if int(grid.sum()) != in_window:
            raise Failed(f"grid mass {grid.sum()} != in-window count "
                         f"{in_window}")
        moved = int((grid != want).sum())
        if moved > max(16, in_window // 10_000):
            raise Failed(f"{moved} cells differ from the host grid")
        return int(grid.sum()), (
            f"256x256 mass={int(grid.sum()):,} == in-window count; "
            f"{moved} border cells differ from the host grid; "
            f"engine={density_engine(resident(), 256, 256, False)}; "
            f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    def knn():
        body, cold, warm = timed(lambda: cli.get(
            f"/knn/{TYPE}", x=KNN_AT[0], y=KNN_AT[1], k=10))
        got_fid = [int(f["id"]) for f in body["features"]]
        got_d = np.array([f["properties"]["knn_distance_deg"]
                          for f in body["features"]])
        want_fid, want_d = ref.knn(KNN_AT[0], KNN_AT[1], 10)
        # the device computes distances in float32: at 49 degrees
        # adjacent float32 values lie 3.8e-6 degrees apart
        tol = 1e-5
        if len(got_d) != 10 or not np.allclose(got_d, want_d, rtol=0,
                                               atol=tol):
            raise Failed(f"distances {got_d} != reference {want_d}")
        swapped = set(got_fid) ^ {int(i) for i in want_fid}
        if any(abs(ref.dist(i, *KNN_AT) - want_d[-1]) > tol
               for i in swapped):
            raise Failed(f"neighbours {got_fid} != reference "
                         f"{list(want_fid)} beyond float32 ties")
        return got_fid, (f"k=10 distances match the reference (max abs "
                         f"diff {np.abs(got_d - want_d).max():.2e} deg); "
                         f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    def density_weighted_512():
        from geomesa_tpu.geom import Envelope

        di = resident()
        env = Envelope(*FLAGSHIP_ENV)

        def call():
            grid = di.density(WINDOW, env, 512, 512, weight_attr="tone")
            if grid is None:
                raise Failed("weighted density was not served on device")
            return np.asarray(grid, np.float64).tolist()

        got, cold, warm = timed(call)
        got = np.asarray(got)
        idx = ref.rows(WINDOW, world, w_lo, w_hi)
        want = ref.density(idx, FLAGSHIP_ENV, 512, 512, weighted=True)
        diff = np.abs(got - want)
        # a border pixel may land one cell over between engines; only
        # the total and the interior are compared exactly-ish
        tol = 1e-3 * max(1.0, float(np.abs(want).max()))
        bad = int((diff > tol).sum())
        mass_err = abs(float(got.sum()) - float(want.sum()))
        mass_tol = 1e-5 * float(np.abs(ref.w[idx]).sum()) + 1e-3
        if got.shape != (512, 512) or mass_err > mass_tol or bad > max(
                16, len(idx) // 10_000):
            raise Failed(f"weighted grid: mass error {mass_err:.4g} "
                         f"(tolerance {mass_tol:.3g}), {bad} cells off")
        # engines sum float32 weights in different orders: the cross-run
        # summary is only that the reference matched
        return "matches reference", (
            f"512x512 weighted (library API) mass={got.sum():.3f} vs "
            f"reference {want.sum():.3f}; {bad} cells off; "
            f"engine={density_engine(di, 512, 512, True)}; "
            f"first={cold:.3f}s warm={warm * 1e3:.2f}ms")

    for name, fn in (
        ("count exact bbox+during", count_exact),
        ("count loose bbox+during", count_loose),
        ("count polygon+during", count_polygon),
        ("features city window", features),
        ("density 256x256", density_256),
        ("knn k=10", knn),
        ("density weighted 512x512", density_weighted_512),
    ):
        phase(name, fn)
    if failures:
        raise Failed("; ".join(failures))
    return results


# -- the runs --------------------------------------------------------------


def serve_and_check(ds, ref, mesh: bool, label: str) -> dict:
    from geomesa_tpu.server import serve_background

    t = time.perf_counter()
    server, thread = serve_background(ds, resident=True, mesh=mesh)
    try:
        cli = Client(server)
        log(f"{label}: serving on {cli.base} "
            f"(started in {time.perf_counter() - t:.1f}s)")
        before = cli.degraded_total()
        t = time.perf_counter()
        cli.get(f"/count/{TYPE}", cql="INCLUDE")  # first touch stages
        di = server.RequestHandlerClass._resident_cache[TYPE]
        log(f"  resident staging: {type(di).__name__}, "
            f"{di.nbytes / 2**30:.2f} GiB on device, in "
            f"{time.perf_counter() - t:.1f}s (host peak RSS "
            f"{peak_rss_gib():.1f} GiB)")
        if mesh:
            check_mesh(di)
        answers = run_requests(server, ref, cli)
        moved = cli.degraded_total() - before
        if moved:
            raise Failed(f"geomesa_resilience_degraded_total moved by {moved}")
        log(f"  geomesa_resilience_degraded_total unchanged ({before:g})")
        stats = jax_device_memory()
        if stats:
            log(f"  device 0 peak bytes in use: {stats / 2**30:.2f} GiB")
        return answers
    finally:
        server.shutdown()
        thread.join(timeout=60)
        # free the resident planes before the next run stages its own
        server.RequestHandlerClass._resident_cache.clear()
        gc.collect()


def jax_device_memory() -> "int | None":
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_mesh(di) -> None:
    from geomesa_tpu.device_cache import ShardedDeviceIndex

    if not isinstance(di, ShardedDeviceIndex):
        raise Failed(f"mesh serving staged a {type(di).__name__}")
    if di._build_engine != "mesh":
        raise Failed(f"mesh build engine is {di._build_engine!r}, not 'mesh' "
                     "(the mesh sort fell back to the host sort)")
    spread = {}
    for name, arr in di._cols.items():
        devs = {s.device.id for s in arr.addressable_shards}
        spread[name] = len(devs)
        if len(devs) != 4:
            raise Failed(f"plane {name!r} has shards on {len(devs)} devices")
    log(f"  _build_engine == 'mesh'; {len(spread)} staged planes, each "
        f"sharded over 4 distinct devices: {sorted(spread)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="rows to generate (default 2^26, 2^27 with "
                    "--chips 4); set it to rehearse on the CPU")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    try:
        import geomesa_tpu
    except ImportError as e:
        geomesa_tpu = e
    pkg = getattr(geomesa_tpu, "__file__", None) or ""
    if os.path.dirname(os.path.dirname(pkg)) != HERE:
        print(f"chip_smoke: no geomesa_tpu package beside this script "
              f"({geomesa_tpu!r})", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: {device}")
    problems = []
    if device["platform"] != "tpu":
        problems.append(f"platform is {device['platform']!r}, not 'tpu'")
        if args.rows is None:
            # a full-size run on the host CPU proves nothing and takes
            # tens of GiB: fail now; --rows rehearses every phase
            print(json.dumps({"ok": False, "device": device,
                              "error": problems[0]}))
            return 1
    if len(devs) < args.chips:
        problems.append(f"{args.chips} chips asked, {len(devs)} visible")
        print(json.dumps({"ok": False, "device": device,
                          "error": problems[-1]}))
        return 1
    rows = args.rows or (1 << (27 if args.chips == 4 else 26))

    from geomesa_tpu import native

    lib = native.get_lib()
    log(f"native library: {'loaded' if lib is not None else 'NOT loaded'} "
        f"(planning {'native' if lib is not None else 'numpy fallback'})")

    work = os.path.join(HERE, ".smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        t = time.perf_counter()
        cols = generate(rows, args.seed)
        log(f"generated {rows:,} rows (seed {args.seed}) in "
            f"{time.perf_counter() - t:.1f}s")
        ds, stored = load_store(cols, work)
        if stored != rows:
            raise Failed(f"store holds {stored} rows, {rows} written")
        ref = Reference(cols)
        del cols
        gc.collect()
        if args.chips == 4:
            mesh_answers = serve_and_check(ds, ref, True, "4-chip mesh")
            one_answers = serve_and_check(ds, ref, False, "1-chip")
            if mesh_answers != one_answers:
                diff = [k for k in mesh_answers
                        if mesh_answers[k] != one_answers.get(k)]
                raise Failed(f"mesh answers differ from one-chip: {diff}")
            log("mesh answers == one-chip answers == reference")
        else:
            serve_and_check(ds, ref, False, "1-chip")
    except Exception as e:
        traceback.print_exc(file=sys.stdout)
        problems.append(repr(e))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    if problems:
        print(json.dumps({"ok": False, "device": device,
                          "error": "; ".join(problems)}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
