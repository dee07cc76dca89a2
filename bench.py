#!/usr/bin/env python
"""Benchmark: bbox+time filter throughput through the real framework path.

Shape of BASELINE config #1 (GDELT bbox+during): synthetic GDELT-like
points resident on device, one ECQL filter compiled by
``geomesa_tpu.filter.compile_filter``, its fused device mask + count jitted
and timed. Metric: features/sec/chip scanned by the fused predicate kernel
(the north-star counts features *evaluated* per second against the
baseline's >= 62.5M features/sec/chip target).

Roofline honesty: K scan invocations are chained inside ONE dispatched jit
(``lax.scan`` whose body is tied to the loop carry with an
``optimization_barrier`` so XLA cannot hoist the loop-invariant kernel),
synced once with a scalar fetch. Per-invocation time therefore excludes
the per-dispatch host latency, and the JSON line reports achieved GB/s
against the v5e HBM peak alongside features/sec.

The default mode runs BOTH the filter scan and the Z3 build benchmarks and
prints exactly one JSON line to stdout with the build metric as a field of
the same line; all logs go to stderr.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

V5E_HBM_PEAK_GBPS = 819.0  # TPU v5e: 16GB HBM2 @ ~819 GB/s per chip


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _default_n(args, platform: str) -> int:
    """Rows resident on device: 2^28 = 3-4GB of planes fits v5e HBM with
    headroom and amortizes dispatch latency; smaller elsewhere."""
    return args.n or (
        (1 << 28) if platform == "tpu"
        else (1 << 27) if platform != "cpu"
        else (1 << 20)
    )


def _measure(chain, inputs, args, k: int, n: int, bytes_per_row: int,
             platform: str, label: str) -> dict:
    """Timed protocol shared by the scan benchmarks: one scalar fetch per
    chain dispatch is the only sync point. Reports MEDIAN-derived numbers
    as the headline (``value``/``gbps``/``hbm_pct``) plus the best
    iteration and the raw iteration spread — the chip shows real
    run-to-run bandwidth variance, and a JSON
    line recording only the median makes a slow run read as a code
    regression."""
    times = []
    for _ in range(args.iters):
        t = time.perf_counter()
        int(chain(*inputs))
        times.append(time.perf_counter() - t)
    best = min(times) / k
    per_inv = sorted(times)[len(times) // 2] / k
    feats_per_sec = n / per_inv
    gbps = n * bytes_per_row / per_inv / 1e9
    hbm_pct = (
        round(100.0 * gbps / V5E_HBM_PEAK_GBPS, 1)
        if platform == "tpu"
        else None
    )
    log(
        f"{label} best={best*1e3:.2f}ms median={per_inv*1e3:.2f}ms per "
        f"invocation ({bytes_per_row}B/row) -> "
        f"{feats_per_sec/1e9:.2f}B features/sec/chip, {gbps:.0f} GB/s"
        + (f" ({hbm_pct}% of v5e HBM peak)" if hbm_pct is not None else "")
    )
    return {
        "value": round(feats_per_sec, 1),
        "gbps": round(gbps, 1),
        "hbm_pct": hbm_pct,
        "per_invocation_ms": round(per_inv * 1e3, 3),
        "best_feats_per_sec": round(n / best, 1),
        "best_gbps": round(n * bytes_per_row / best / 1e9, 1),
        "spread_ms": [
            round(min(times) / k * 1e3, 3), round(max(times) / k * 1e3, 3)
        ],
    }


def _chain(scan_fn, k):
    """One jitted dispatch running ``scan_fn`` k times: the barrier ties
    every input to the loop carry, so the loop body cannot be hoisted or
    CSE'd, yet no data is copied. Returns the jitted chain fn (uint32
    checksum output = the single scalar sync point)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(*args):
        def body(carry, _):
            args_b, carry_b = jax.lax.optimization_barrier((args, carry))
            return carry_b + scan_fn(*args_b).astype(jnp.uint32), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.uint32), None, length=k
        )
        return total

    return chain


def bench_filter(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    platform = jax.devices()[0].platform
    n = _default_n(args, platform)
    log(f"platform={platform} device={jax.devices()[0]} n={n:,}")

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.compile import compile_filter
    from geomesa_tpu.filter.ecql import parse_ecql, parse_instant

    sft = SimpleFeatureType.create(
        "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
    )
    # Europe bbox + 5-day window over a 60-day span (GDELT-style selectivity)
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    ecql = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    compiled = compile_filter(parse_ecql(ecql), sft)
    assert compiled.fully_on_device

    # generate data on device: float32 coords; int64 epoch-ms materialized
    # as the storage-format hi/lo word planes (ops/int64lanes.py)
    log("generating device-resident columns...")
    from geomesa_tpu.jaxconf import require_x64

    require_x64()  # only for generating the i64 oracle column
    key = jax.random.PRNGKey(42)
    kx, ky, kt = jax.random.split(key, 3)

    @jax.jit
    def make_cols():
        dtg = jax.random.randint(kt, (n,), t0, t1, jnp.int64)
        return {
            "geom__x": jax.random.uniform(kx, (n,), jnp.float32, -180.0, 180.0),
            "geom__y": jax.random.uniform(ky, (n,), jnp.float32, -90.0, 90.0),
            "dtg__hi": (dtg >> 32).astype(jnp.int32),
            "dtg__lo": (dtg & 0xFFFFFFFF).astype(jnp.uint32),
        }

    # only the scan planes stay resident: keeping the 8B/row int64 dtg
    # alive through the timed loop would waste 2GB of HBM at n=2^28;
    # the --check host oracle recomputes it from the same PRNG key
    cols = jax.block_until_ready(make_cols())
    assert sorted(compiled.device_cols) == sorted(cols)
    bytes_per_row = sum(v.dtype.itemsize for v in cols.values())

    if args.engine == "pallas":
        scan = compiled.pallas_scan()
        assert scan is not None, "filter not pallas-tileable"
        scan_fn = scan[0]
    else:
        def scan_fn(c):
            return compiled.device_fn(c).sum()
    scan_count = jax.jit(scan_fn)

    # compile + warmup the single-invocation kernel (used for the check)
    t_compile = time.perf_counter()
    hits = int(scan_count(cols))
    log(f"compiled in {time.perf_counter() - t_compile:.1f}s; hits={hits:,} "
        f"(selectivity {hits / n:.4%})")

    if args.check:
        if n <= (1 << 27):
            x = np.asarray(cols["geom__x"])
            y = np.asarray(cols["geom__y"])
            d = np.asarray(jax.jit(
                lambda: jax.random.randint(kt, (n,), t0, t1, jnp.int64)
            )())
            expect = int(
                (
                    (x >= -10) & (x <= 30) & (y >= 35) & (y <= 60)
                    & (d >= parse_instant("2020-01-10T00:00:00"))
                    & (d <= parse_instant("2020-01-15T00:00:00"))
                ).sum()
            )
            oracle = "host numpy oracle"
        else:
            # fetching 4+GB of columns to the host for the numpy oracle
            # is slower than the whole benchmark; cross-check
            # against the OTHER engine so the two independent kernels must
            # agree (pallas <-> XLA-fused)
            if args.engine == "pallas":
                other = jax.jit(lambda c: compiled.device_fn(c).sum())
                oracle = "independent XLA-engine count"
            else:
                other = jax.jit(compiled.pallas_scan()[0])
                oracle = "independent Pallas-engine count"
            expect = int(other(cols))
        assert hits == expect, f"device {hits} != oracle {expect}"
        log(f"count verified against {oracle}")

    k = args.chain
    chain = _chain(scan_fn, k)
    t_compile = time.perf_counter()
    total = int(chain(cols))
    log(f"chain (K={k}) compiled in {time.perf_counter() - t_compile:.1f}s")
    # the chain must have run the same kernel K times
    assert total == (k * hits) % (1 << 32), (total, hits, k)

    m = _measure(chain, (cols,), args, k, n, bytes_per_row, platform, "filter")
    baseline_per_chip = 62.5e6  # BASELINE.json north star / 8 chips
    return {
        "metric": "bbox+time filter throughput (fused device scan)",
        "value": m["value"],
        "unit": "features/sec/chip",
        # headline discipline: `value` is the MEDIAN-derived rate; best_*
        # and spread_ms bound the chip's run-to-run variance so a
        # round-over-round delta is attributable
        "headline": "median",
        "vs_baseline": round(m["value"] / baseline_per_chip, 2),
        "gbps": m["gbps"],
        "hbm_pct": m["hbm_pct"],
        "best_feats_per_sec": m["best_feats_per_sec"],
        "best_gbps": m["best_gbps"],
        "spread_ms": m["spread_ms"],
        "chain": k,
        "per_invocation_ms": m["per_invocation_ms"],
        "n": n,
    }


def bench_zscan(args) -> dict:
    """Z3Iterator-analog scan THROUGH the serving path: a DeviceIndex
    stages synthetic GDELT-like rows (device key encode), and the timed
    kernel is exactly what ``count(ecql, loose=True)`` dispatches —
    obtained via ``DeviceIndex.loose_scan_kernel`` (the measured engine
    must BE the serving engine, not a bench-local copy). The resident
    layout is the de-interleaved dim-plane key (nx,
    ny uint32 + packed (bin<<21|nt) word, ~12 VPU ops/row vs ~46 for the
    interleaved masked compare; 12B/row either way). Loose cell
    semantics — what the reference's Z3Iterator answers without residual
    refinement.

    Metric note: this kernel is ROW-RATE bound (~52B rows/s on v5e,
    above the attribute filter's ~46B) — it reads 12B/row to the
    filter's 16, so its GB/s and HBM% read LOWER even while it scans
    MORE features per second. Compare feats/sec across legs, not HBM%.
    """
    import jax
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.features.batch import FeatureBatch
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.store.direct import BatchStore

    platform = jax.devices()[0].platform
    # through-the-store staging holds a host mirror: 2^26 keeps the
    # staging pass tens-of-seconds while the key planes (800MB) stay far
    # beyond any cache — per-row throughput is n-independent here
    n = args.n or ((1 << 26) if platform == "tpu" else (1 << 20))
    log(f"platform={platform} device={jax.devices()[0]} n={n:,} (zscan mode)")
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    ecql = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )

    rng = np.random.default_rng(42)
    sft = SimpleFeatureType.create("gdelt", "dtg:Date,*geom:Point:srid=4326")

    def _mk_batch(nn, r):
        return FeatureBatch.from_columns(sft, {
            "dtg": r.integers(t0, t1, nn),
            "geom": np.stack(
                [r.uniform(-180, 180, nn), r.uniform(-90, 90, nn)], axis=1
            ).astype(np.float32),
        }, fids=np.arange(nn))

    # BatchStore: the resident-cache-first store — DeviceIndex IS the
    # index, so the bench pays no host-side sorted-index build (that path
    # has its own benchmark: build mode)
    t_stage = time.perf_counter()
    di = DeviceIndex(
        BatchStore(_mk_batch(n, rng)), "gdelt", z_planes=True
    )
    assert di._dim_mode, "z3 resident cache must stage the dim-plane layout"
    log(f"staged {n:,} rows through DeviceIndex in "
        f"{time.perf_counter() - t_stage:.1f}s ({di.nbytes / 1e9:.2f} GB)")

    got = di.loose_scan_kernel(ecql)
    assert got is not None, "loose engine must answer the flagship filter"
    scan_fn, kargs = got
    bytes_per_row = 12  # 3x uint32 dim planes
    hits = int(jax.jit(scan_fn)(*kargs))
    log(f"hits={hits:,} (selectivity {hits / n:.4%}, loose cell semantics)")
    assert hits == di.count(ecql, loose=True)  # the serving path agrees

    if args.check:
        # independent engine: a SECOND DeviceIndex staged with the
        # interleaved masked-compare layout (Morton-encoded by a separate
        # kernel) must agree bit-for-bit. Reduced n: two full layouts at
        # bench scale would double HBM+host residency, and engine
        # equivalence is size-independent.
        nc = min(n, 1 << 22)
        ds_c = BatchStore(_mk_batch(nc, np.random.default_rng(17)))
        dim_c = DeviceIndex(ds_c, "gdelt", z_planes=True)
        cmp_c = DeviceIndex(ds_c, "gdelt", z_planes=True, dim_planes=False)
        assert dim_c._dim_mode and not cmp_c._dim_mode
        a = dim_c.mask(ecql, loose=True)
        b = cmp_c.mask(ecql, loose=True)
        assert np.array_equal(a, b), "dim-plane != masked-compare engine"
        log(f"engines agree at n={nc:,}: dim-plane == masked-compare "
            f"({int(a.sum()):,} hits)")
        # and the MEASURED full-n Pallas count against the XLA dim-plane
        # engine over the SAME resident planes (catches size-dependent
        # bugs — padding/index overflows — the reduced-n check cannot)
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        lb = di._loose_bounds(di._parse(ecql))
        assert lb[0] == "dim"
        full_xla = int(jax.jit(
            lambda q, a_, b_, c_: zscan.z3_dimscan_mask_rt(
                a_, b_, c_, q, lb[2]
            ).sum(dtype=jnp.int32)
        )(*kargs))
        assert hits == full_xla, f"pallas {hits} != xla {full_xla} at n={n}"
        log(f"full-n pallas count verified against XLA engine ({hits:,})")

    k = args.chain
    chain = _chain(scan_fn, k)
    t_c = time.perf_counter()
    total = int(chain(*kargs))
    log(f"zscan chain (K={k}) compiled in {time.perf_counter() - t_c:.1f}s")
    assert total == (k * hits) % (1 << 32), (total, hits, k)

    m = _measure(
        chain, kargs, args, k, n, bytes_per_row, platform,
        "zscan(dim-plane pallas, via DeviceIndex)",
    )
    m.update({
        "metric": "key-only z scan (Z3Iterator analog, dim-plane kernel)",
        "unit": "features/sec/chip",
        "n": n,
    })

    # CONTROL:
    # the SAME kernel padded to 16B/row by an extra data-dependent
    # uint32 plane. If the scan were bandwidth-bound, rows/s would drop
    # ~25% (12B -> 16B at fixed GB/s); if row-rate bound, rows/s drops
    # only by the added per-row op cost while achieved GB/s RISES. The
    # recorded pair (zscan vs zscan_pad16) is the roofline proof.
    if platform == "tpu":
        import jax.numpy as jnp

        from geomesa_tpu.ops.zscan import build_z3_dimscan_rt

        lb = di._loose_bounds(di._parse(ecql))
        qarr, n_ranges = kargs[0], lb[2] if len(lb) == 3 else None
        # same R bucket as the measured serving kernel
        R = (len(np.asarray(qarr)) - 4) // 2
        cf_pad, _ = build_z3_dimscan_rt(R, extra_planes=1)
        key_d = jax.random.PRNGKey(5)
        dummy = jax.random.randint(
            key_d, (n,), 1, 1 << 30, jnp.int32
        ).astype(jnp.uint32)
        jax.block_until_ready(dummy)
        pad_args = tuple(kargs) + (dummy,)
        pad_scan = lambda q, a_, b_, c_, d_: cf_pad(  # noqa: E731
            q, a_, b_, c_, d_
        )
        chain_pad = _chain(pad_scan, k)
        assert int(chain_pad(*pad_args)) == (k * hits) % (1 << 32)
        mp = _measure(
            chain_pad, pad_args, args, k, n, 16, platform,
            "zscan 16B/row control",
        )
        m["zscan_pad16_feats_per_sec"] = mp["value"]
        m["zscan_pad16_gbps"] = mp["gbps"]
        m["zscan_pad16_hbm_pct"] = mp["hbm_pct"]
        m["zscan_roofline_note"] = (
            "row-rate bound: padding 12B->16B/row raises achieved GB/s "
            "while rows/s falls only by the extra plane's op cost"
        )
    return m


def _gdelt_cols(args, n, skew: bool = False):
    """Device-resident GDELT-shaped scan planes (x/y f32 + dtg hi/lo).
    ``skew=True`` draws 90% of points from 64 city-sized Gaussian
    clusters (GDELT's spatial skew, SURVEY hard part #5) instead of the
    uniform sphere."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.jaxconf import require_x64

    require_x64()  # epoch-ms randint needs i64 while generating
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    key = jax.random.PRNGKey(43 if skew else 42)
    # distinct subkeys per draw: reusing a key across draws makes cluster
    # ids deterministically correlated with timestamps, distorting the
    # space/time independence the skew experiment measures
    kx, ky, kt, kc, km, kn1, kn2, kp = jax.random.split(key, 8)

    @jax.jit
    def make_cols():
        if skew:
            # cluster centres drawn once; points = centre + sigma noise
            cx = jax.random.uniform(kc, (64,), jnp.float32, -170.0, 170.0)
            cy = jax.random.uniform(km, (64,), jnp.float32, -80.0, 80.0)
            cid = jax.random.randint(kp, (n,), 0, 64)
            noise_x = jax.random.normal(kn1, (n,), jnp.float32) * 0.2
            noise_y = jax.random.normal(kn2, (n,), jnp.float32) * 0.2
            ux = jax.random.uniform(kx, (n,), jnp.float32, -180.0, 180.0)
            uy = jax.random.uniform(ky, (n,), jnp.float32, -90.0, 90.0)
            take_cluster = jax.random.uniform(
                jax.random.fold_in(kp, 1), (n,)
            ) < 0.9
            x = jnp.where(take_cluster, cx[cid] + noise_x, ux)
            y = jnp.where(take_cluster, cy[cid] + noise_y, uy)
            x = jnp.clip(x, -180.0, 180.0)
            y = jnp.clip(y, -90.0, 90.0)
        else:
            x = jax.random.uniform(kx, (n,), jnp.float32, -180.0, 180.0)
            y = jax.random.uniform(ky, (n,), jnp.float32, -90.0, 90.0)
        dtg = jax.random.randint(kt, (n,), t0, t1, jnp.int64)
        return {
            "geom__x": x,
            "geom__y": y,
            "dtg__hi": (dtg >> 32).astype(jnp.int32),
            "dtg__lo": (dtg & 0xFFFFFFFF).astype(jnp.uint32),
        }

    import jax as _jax

    return _jax.block_until_ready(make_cols())


def _scan_metric(args, cols, ecql, label, engine=None):
    """Compile one ECQL filter over resident cols, chain-time it, return
    the _measure dict + hit count."""
    import jax

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.compile import compile_filter
    from geomesa_tpu.filter.ecql import parse_ecql

    platform = jax.devices()[0].platform
    sft = SimpleFeatureType.create(
        "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
    )
    compiled = compile_filter(parse_ecql(ecql), sft)
    assert compiled.fully_on_device, ecql
    engine = engine or args.engine
    scan_fn = None
    if engine == "pallas":
        scan = compiled.pallas_scan()
        if scan is not None:
            scan_fn = scan[0]
    if scan_fn is None:
        def scan_fn(c):
            return compiled.device_fn(c).sum()
    n = len(next(iter(cols.values())))
    sub = {k: cols[k] for k in compiled.device_cols}
    bytes_per_row = sum(v.dtype.itemsize for v in sub.values())
    hits = int(jax.jit(scan_fn)(sub))
    k = args.chain
    chain = _chain(scan_fn, k)
    total = int(chain(sub))
    assert total == (k * hits) % (1 << 32)
    m = _measure(chain, (sub,), args, k, n, bytes_per_row, platform, label)
    m["hits"] = hits
    m["selectivity"] = round(hits / n, 6)
    return m


def bench_polygon(args) -> dict:
    """BASELINE config #3 shape (NYC-taxi borough polygon + time range):
    polygon-INTERSECTS + during over device-resident points — the device
    point-in-polygon kernel (filter/compile points_in_polygon_jax), not a
    bbox approximation."""
    import jax

    platform = jax.devices()[0].platform
    n = _default_n(args, platform)
    log(f"platform={platform} n={n:,} (polygon mode)")
    cols = _gdelt_cols(args, n)
    # an 8-vertex non-convex "borough" over western Europe
    poly = (
        "POLYGON ((-10 35, 5 33, 12 38, 20 36, 25 47, 10 52, 2 48, "
        "-6 50, -10 35))"
    )
    ecql = (
        f"INTERSECTS(geom, {poly}) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    # Pallas engine: the crossing-parity kernel (round-3's Mosaic
    # `% 2`-under-x64 recursion is fixed by the `& 1` spelling —
    # tests/test_pallas_scan.py::test_mosaic_mod_recursion_repro).
    # Compute-bound (~10ms/invocation at 2^26): a medium chain suffices
    pargs = argparse.Namespace(**vars(args))
    pargs.chain = min(args.chain, 32)
    m = _scan_metric(pargs, cols, ecql, "polygon")
    if args.check:
        # the two engines must agree exactly (independent lowerings)
        import jax

        from geomesa_tpu.features.sft import SimpleFeatureType
        from geomesa_tpu.filter.compile import compile_filter
        from geomesa_tpu.filter.ecql import parse_ecql

        sft = SimpleFeatureType.create(
            "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
        )
        compiled = compile_filter(parse_ecql(ecql), sft)
        sub = {k: cols[k] for k in compiled.device_cols}
        xla_hits = int(jax.jit(
            lambda c: compiled.device_fn(c).sum()
        )(sub))
        assert m["hits"] == xla_hits, (m["hits"], xla_hits)
        log(f"polygon pallas count verified against XLA engine "
            f"({xla_hits:,})")
    log(f"polygon hits={m['hits']:,} (selectivity {m['selectivity']:.4%})")
    m["polygon_vertices"] = 8

    # second datapoint: a borough-complexity
    # MULTIPOLYGON — two components, jittered-radial shells of 220
    # vertices each with 80-vertex holes (604 vertices total) — so the
    # headline can't be an artifact of 8-vertex convexity. The crossing-
    # parity kernel's work scales with the EDGE count; rows/s divides
    # accordingly and that is the honest number for real borough shapes.
    import numpy as np

    rng = np.random.default_rng(77)

    def _ring(cx, cy, base_r, kv):
        # jittered-even angles: pure-random angles can leave arcs where
        # a "hole" vertex pokes outside the shell (round-4 fuzz note)
        ang = (np.arange(kv) + rng.uniform(0.1, 0.9, kv)) * (
            2 * np.pi / kv
        )
        rad = base_r * rng.uniform(0.7, 1.0, kv)
        xs, ys = cx + rad * np.cos(ang), cy + rad * np.sin(ang)
        pts = ", ".join(f"{x:.4f} {y:.4f}" for x, y in zip(xs, ys))
        return f"({pts}, {xs[0]:.4f} {ys[0]:.4f})"

    comps = []
    nverts = 0
    for cx, cy, r0 in ((5.0, 45.0, 6.0), (17.0, 40.0, 5.0)):
        shell = _ring(cx, cy, r0, 220)
        hole = _ring(cx, cy, r0 * 0.3, 80)  # 0.3r < 0.7r: inside shell
        comps.append(f"({shell}, {hole})")
        nverts += 220 + 80 + 2
    mp_wkt = "MULTIPOLYGON (" + ", ".join(comps) + ")"
    ecql_c = (
        f"INTERSECTS(geom, {mp_wkt}) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    cargs = argparse.Namespace(**vars(args))
    cargs.chain = min(args.chain, 4)
    cargs.iters = min(args.iters, 4)
    mc = _scan_metric(cargs, cols, ecql_c, "polygon-complex")
    if args.check:
        from geomesa_tpu.features.sft import SimpleFeatureType
        from geomesa_tpu.filter.compile import compile_filter
        from geomesa_tpu.filter.ecql import parse_ecql

        sft = SimpleFeatureType.create(
            "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
        )
        comp_c = compile_filter(parse_ecql(ecql_c), sft)
        sub_c = {k: cols[k] for k in comp_c.device_cols}
        xla_c = int(jax.jit(lambda c: comp_c.device_fn(c).sum())(sub_c))
        assert mc["hits"] == xla_c, (mc["hits"], xla_c)
        log(f"complex-polygon pallas count verified against XLA ({xla_c:,})")
    log(f"complex polygon ({nverts} vertices incl. holes) "
        f"hits={mc['hits']:,} -> {mc['value']/1e9:.2f}B feats/s")
    m["polygon_complex_feats_per_sec"] = mc["value"]
    m["polygon_complex_vertices"] = nverts
    m["polygon_complex_selectivity"] = mc["selectivity"]
    m["polygon_complex_gbps"] = mc["gbps"]
    return m


def bench_density_knn(args) -> dict:
    """BASELINE config #4 shape (AIS kNN + spatio-temporal density):
    the fused density dispatch (filter mask + the Pallas one-hot-matmul
    binning kernel that DeviceIndex.density serves — pixel histograms as
    MXU contractions, ops/density_pallas) timed at scan scale, plus the
    end-to-end kNN process wall clock on a resident store."""
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    n = args.n or ((1 << 26) if platform == "tpu" else (1 << 20))
    log(f"platform={platform} n={n:,} (density mode)")
    cols = _gdelt_cols(args, n)

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.compile import compile_filter
    from geomesa_tpu.filter.ecql import parse_ecql
    from geomesa_tpu.ops.density_pallas import build_density_pallas

    sft = SimpleFeatureType.create(
        "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
    )
    ecql = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    compiled = compile_filter(parse_ecql(ecql), sft)
    W = H = 256
    kern = build_density_pallas(W, H, False)
    env = jnp.asarray([-10.0, 35.0, 30.0, 60.0], jnp.float32)

    def density_fn(c):
        m = compiled.device_fn(c)
        grid = kern(env, c["geom__x"], c["geom__y"], m)
        return grid.sum().astype(jnp.uint32)  # scalar sync point

    if args.check:
        # cross-check against the XLA scatter engine over the SAME
        # device data; small tolerance for borderline pixels (XLA may
        # fuse the viewport multiply differently between the engines)
        nc = min(n, 1 << 22)
        subc = {k_: v[:nc] for k_, v in cols.items()}

        def scatter_fn(c):
            m_ = compiled.device_fn(c)
            x, y = c["geom__x"], c["geom__y"]
            px = jnp.clip(jnp.floor((x - env[0]) * (W / 40.0)), 0, W - 1)
            py = jnp.clip(jnp.floor((y - env[1]) * (H / 25.0)), 0, H - 1)
            g = jnp.zeros(H * W, jnp.float32)
            return g.at[
                py.astype(jnp.int32) * W + px.astype(jnp.int32)
            ].add(m_.astype(jnp.float32)).sum()
        mass_kern = float(jax.jit(
            lambda c: kern(env, c["geom__x"], c["geom__y"],
                           compiled.device_fn(c)).sum()
        )(subc))
        mass_scat = float(jax.jit(scatter_fn)(subc))
        assert abs(mass_kern - mass_scat) <= 8, (mass_kern, mass_scat)
        log(f"density mass agrees with scatter engine at n={nc:,} "
            f"({mass_kern:.0f} vs {mass_scat:.0f}, borderline tolerance)")

    import numpy as np

    sub = {k_: cols[k_] for k_ in compiled.device_cols}
    bytes_per_row = sum(v.dtype.itemsize for v in sub.values())
    k = min(args.chain, 8)  # ~45ms/invocation: a long chain buys nothing
    chain = _chain(density_fn, k)
    int(chain(sub))
    m = _measure(
        chain, (sub,), args, k, n, bytes_per_row, platform, "density"
    )

    # kNN end-to-end through the store surface (host planning + device
    # scans; n kept modest — this measures the PROCESS, not the kernel)
    import numpy as np
    import time as _t

    from geomesa_tpu.process.knn import knn
    from geomesa_tpu.store.memory import MemoryDataStore

    kn = min(1 << 18, n)  # end-to-end process metric; store path re-stages
    # columns per window query, so row count mostly scales constant costs
    rng = np.random.default_rng(3)
    ds = MemoryDataStore()
    ds.create_schema("ais", "dtg:Date,*geom:Point:srid=4326")
    ds.write("ais", {
        "dtg": rng.integers(1_577_836_800_000, 1_583_020_800_000, kn),
        "geom": np.stack(
            [rng.uniform(-180, 180, kn), rng.uniform(-90, 90, kn)], axis=1
        ),
    })
    # resident serving: the windows scan pinned columns (one fused
    # dispatch per probe) instead of re-staging the store's columns on
    # every expanding-window query
    from geomesa_tpu.device_cache import DeviceIndex

    di = DeviceIndex(ds, "ais")
    t0 = _t.perf_counter()
    batch, _d = knn(ds, "ais", 2.35, 48.85, k=100, device_index=di)
    cold_ms = (_t.perf_counter() - t0) * 1e3
    assert len(batch) == 100
    # the serving number is the WARM call (one fused dispatch; the cold
    # call is dominated by the one-time top_k kernel compile, recorded
    # separately): a map client's 2nd..Nth kNN never recompiles
    reps = []
    for _ in range(5):
        t0 = _t.perf_counter()
        b2, _d2 = knn(ds, "ais", 2.35, 48.85, k=100, device_index=di)
        reps.append((_t.perf_counter() - t0) * 1e3)
    knn_ms = sorted(reps)[len(reps) // 2]
    assert np.array_equal(b2.fids, batch.fids)
    log(f"kNN k=100 over {kn:,} resident rows: {knn_ms:.0f}ms warm "
        f"({cold_ms:.0f}ms cold incl. compile)")
    m["knn_ms"] = round(knn_ms, 1)
    m["knn_cold_ms"] = round(cold_ms, 1)
    m["knn_n"] = kn
    m.update(_bench_agg_pushdown(args))
    return m


def _bench_agg_pushdown(args) -> dict:
    """Aggregation pushdown vs row rescan (ISSUE 6): density and count
    over an FS store with chunked v2 partitions, answered from the
    manifest's chunk pre-aggregates (interior chunks never read,
    boundary chunks row-refined) vs the full row-scan path on a
    cold-cache store. The rescan baseline is what BENCH_r05 measured
    density as: every aggregate re-touches raw rows."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from geomesa_tpu import metrics as gm
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.geom import Envelope
    from geomesa_tpu.process.density import density
    from geomesa_tpu.query.plan import Query
    from geomesa_tpu.store.fs import FileSystemDataStore

    n = min(args.n or (1 << 18), 1 << 20)
    part_rows = max(1 << 12, n // 32)
    grid = 64
    tmp = tempfile.mkdtemp(prefix="geomesa_aggpush_")
    try:
        t0 = parse_instant("2020-01-01T00:00:00")
        t1 = parse_instant("2020-02-01T00:00:00")
        with prop_override("store.chunk.rows", max(1 << 10, part_rows // 8)), \
                prop_override("store.chunk.grid", grid), \
                prop_override("store.fsync", False):
            ds = FileSystemDataStore(
                os.path.join(tmp, "s"), partition_size=part_rows
            )
            ds.create_schema(
                "t", "val:Int,dtg:Date,*geom:Point:srid=4326"
            )
            rng = np.random.default_rng(11)
            ds.write("t", {
                "val": rng.integers(0, 100, n),
                "dtg": rng.integers(t0, t1, n),
                "geom": np.stack(
                    [rng.uniform(-60, 60, n), rng.uniform(-50, 50, n)],
                    axis=1,
                ),
            }, fids=np.arange(n))
            ds.flush("t")
        # the "visible layer heatmap" shape: a grid-aligned window over
        # most of the data -- the aggregate a map client refreshes
        cw, ch = 360.0 / grid, 180.0 / grid
        env = Envelope(
            -180 + round((-55 + 180) / cw) * cw,
            -90 + round((-45 + 90) / ch) * ch,
            -180 + round((55 + 180) / cw) * cw,
            -90 + round((45 + 90) / ch) * ch,
        )
        ecql = (
            f"BBOX(geom, {env.xmin}, {env.ymin}, {env.xmax}, {env.ymax})"
        )
        rescan_q = Query(filter=ecql, hints={"agg.pushdown": False})
        W = H = 256

        def cold():
            # pre-opened store (a server holds it open across requests)
            # whose PARTITION CACHE is cold: the rescan baseline pays
            # the file reads pushdown exists to avoid
            return FileSystemDataStore(
                os.path.join(tmp, "s"), partition_size=part_rows
            )

        # one untimed pass per path: filter compile + first-jax-import
        # costs are one-time per process and must not land on whichever
        # leg happens to run first
        density(cold(), "t", ecql, env, W, H, use_device=False)
        density(cold(), "t", rescan_q, env, W, H, use_device=False)
        # density: pushdown (manifest cells + boundary refinement) vs
        # the row-rescan baseline
        ds_p, ds_s = cold(), cold()
        t = time.perf_counter()
        g_push = density(ds_p, "t", ecql, env, W, H, use_device=False)
        push_s = time.perf_counter() - t
        t = time.perf_counter()
        g_scan = density(ds_s, "t", rescan_q, env, W, H, use_device=False)
        scan_s = time.perf_counter() - t
        mass_p = float(g_push.sum(dtype=np.float64))
        mass_s = float(g_scan.sum(dtype=np.float64))
        assert abs(mass_p - mass_s) <= 0.5, (mass_p, mass_s)
        d_speed = round(scan_s / push_s, 1) if push_s > 0 else None
        # count, windowed: exact pushdown (interior from manifest,
        # boundary chunks row-refined) vs cold-cache rescan
        cold().count("t", ecql)  # warm the count plan path
        ds_p, ds_s = cold(), cold()
        t = time.perf_counter()
        c_push = ds_p.count("t", ecql)
        cpush_s = time.perf_counter() - t
        t = time.perf_counter()
        c_scan = len(ds_s.query("t", rescan_q).batch)
        cscan_s = time.perf_counter() - t
        assert c_push == c_scan, (c_push, c_scan)
        c_speed = round(cscan_s / cpush_s, 1) if cpush_s > 0 else None
        # count, full layer (INCLUDE): the pure pre-aggregate answer —
        # every chunk interior, zero file reads (the dashboard/"how many
        # features in this layer" shape the reference serves from stats)
        ds_p, ds_s = cold(), cold()
        t = time.perf_counter()
        c_full = ds_p.count("t")
        cfull_push_s = time.perf_counter() - t
        t = time.perf_counter()
        c_full_scan = len(
            ds_s.query("t", Query(hints={"agg.pushdown": False})).batch
        )
        cfull_scan_s = time.perf_counter() - t
        assert c_full == c_full_scan == n, (c_full, c_full_scan, n)
        cf_speed = (
            round(cfull_scan_s / cfull_push_s, 1)
            if cfull_push_s > 0
            else None
        )
        log(
            f"agg pushdown @n={n:,}: density {scan_s*1e3:.0f}ms rescan -> "
            f"{push_s*1e3:.0f}ms pushdown ({d_speed}x, mass "
            f"{mass_p:.0f}); windowed count {cscan_s*1e3:.0f}ms -> "
            f"{cpush_s*1e3:.0f}ms ({c_speed}x, {c_push:,} rows); "
            f"full-layer count {cfull_scan_s*1e3:.0f}ms -> "
            f"{cfull_push_s*1e3:.0f}ms ({cf_speed}x, zero reads)"
        )
        return {
            "agg_pushdown_n": n,
            "density_rescan_ms": round(scan_s * 1e3, 1),
            "density_pushdown_ms": round(push_s * 1e3, 1),
            "density_pushdown_speedup": d_speed,
            "density_pushdown_mass": mass_p,
            "count_rescan_ms": round(cscan_s * 1e3, 1),
            "count_pushdown_ms": round(cpush_s * 1e3, 1),
            "count_pushdown_speedup": c_speed,
            "count_full_rescan_ms": round(cfull_scan_s * 1e3, 1),
            "count_full_pushdown_ms": round(cfull_push_s * 1e3, 1),
            "count_full_pushdown_speedup": cf_speed,
            "agg_pushdown_rows_preagg": gm.agg_pushdown_rows.value(),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_sweep(args, cols) -> list:
    """Selectivity sweep over the resident uniform columns: city-, country-
    and continent-scale windows (round 2 measured ONE point in filter
    space; selectivity-dependent effects were invisible)."""
    out = []
    for label, box in (
        ("city", "BBOX(geom, 2.0, 48.5, 2.7, 49.0)"),
        ("country", "BBOX(geom, -10, 35, 30, 60)"),
        ("continent", "BBOX(geom, -30, 10, 60, 75)"),
    ):
        ecql = (
            f"{box} AND "
            "dtg DURING 2020-01-10T00:00:00Z/2020-02-20T00:00:00Z"
        )
        m = _scan_metric(args, cols, ecql, f"sweep:{label}")
        out.append({
            "window": label,
            "selectivity": m["selectivity"],
            "feats_per_sec": m["value"],
            "gbps": m["gbps"],
        })
    return out


def _measure_build(args, build_step, inputs, n: int, label: str) -> float:
    """Shared build-bench timing protocol: K chained invocations per
    dispatch (the order-dependent checksum inside ``build_step`` forces
    the full sorted arrays to materialize — returning only extremes
    would let XLA reduce the sort to min/max), median over
    --iters. Returns rows/sec."""
    k = args.chain_build
    chain = _chain(build_step, k)
    t0 = time.perf_counter()
    chk = int(chain(*inputs))
    log(f"{label} chain (K={k}) compiled+first in "
        f"{time.perf_counter() - t0:.1f}s (chk {chk})")
    times = []
    for _ in range(args.iters):
        t1 = time.perf_counter()
        int(chain(*inputs))  # scalar fetch = hard sync point
        times.append(time.perf_counter() - t1)
    per_inv = sorted(times)[len(times) // 2] / k
    rate = n / per_inv
    log(f"{label} median={per_inv*1e3:.2f}ms per build -> "
        f"{rate/1e6:.0f}M rows/sec/chip")
    return rate


def bench_build(args) -> dict:
    """Z3 index build on device: fused quantize+interleave key encode
    (hi/lo uint32 lanes) + lexicographic sort carrying a row-id payload
    lane -- the permutation a real build needs, not just sorted keys
    (BASELINE config #2 shape: OSM-GPS-style points, full build path
    minus file IO)."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.curves import Z3SFC

    platform = jax.devices()[0].platform
    n = args.n or ((1 << 26) if platform != "cpu" else (1 << 20))
    log(f"platform={platform} device={jax.devices()[0]} n={n:,} (build mode)")
    sfc = Z3SFC()
    key = jax.random.PRNGKey(7)
    kx, ky, kt = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n,), jnp.float32, -180.0, 180.0)
    y = jax.random.uniform(ky, (n,), jnp.float32, -90.0, 90.0)
    t = jax.random.uniform(kt, (n,), jnp.float32, 0.0, 604800.0)
    jax.block_until_ready((x, y, t))

    def build_step(xc, yc, tc):
        hi, lo = sfc.index_jax_hi_lo(xc, yc, tc)
        rid = jnp.arange(n, dtype=jnp.uint32)
        hi_s, lo_s, rid_s = jax.lax.sort((hi, lo, rid), num_keys=2)
        # order-dependent checksum: forces the full sorted arrays (keys AND
        # permutation) to materialize (returning only extremes would let
        # XLA reduce the sort to min/max)
        w = jnp.arange(n, dtype=jnp.uint32)
        return (hi_s * w).sum() + (lo_s * w).sum() + (rid_s * w).sum()

    if args.check:
        import numpy as np

        # reduced-n check: the oracle fetches the full sorted arrays to
        # the host, and pulling GBs takes longer than the whole
        # benchmark; sort correctness is size-independent
        nc = min(n, 1 << 22)
        xc_, yc_, tc_ = x[:nc], y[:nc], t[:nc]

        @jax.jit
        def build_full(xc, yc, tc):
            hi, lo = sfc.index_jax_hi_lo(xc, yc, tc)
            rid = jnp.arange(nc, dtype=jnp.uint32)
            return jax.lax.sort((hi, lo, rid), num_keys=2)

        hi_s, lo_s, rid_s = build_full(xc_, yc_, tc_)
        hi_s = np.asarray(hi_s).astype(np.uint64)
        lo_s = np.asarray(lo_s).astype(np.uint64)
        got = (hi_s << np.uint64(32)) | lo_s
        # oracle for the sort: the same device encode (f32 lanes -- the
        # f64-parity of the encode itself is covered by the unit tests),
        # host-sorted, must equal the device-sorted output exactly; the
        # rid permutation must reproduce the unsorted keys
        hi_u, lo_u = jax.jit(sfc.index_jax_hi_lo)(xc_, yc_, tc_)
        z_u = (np.asarray(hi_u).astype(np.uint64) << np.uint64(32)) | np.asarray(
            lo_u
        ).astype(np.uint64)
        assert np.array_equal(got, np.sort(z_u)), "device sort != host sort"
        perm = np.asarray(rid_s).astype(np.int64)
        assert np.array_equal(z_u[perm], got), "rid payload mis-permuted"
        del hi_s, lo_s, rid_s, got, z_u, perm
        log("sorted keys + rid permutation verified against host oracle")

    pts_per_sec = _measure_build(args, build_step, (x, y, t), n, "z3 build")

    # stage breakdown. Encode
    # and sort timed separately prove where the time goes: the fused
    # quantize+interleave encode runs at ~4.4B pts/s; jax.lax.sort of
    # the (hi, lo, rid) lanes is ~96% of the build. Alternatives
    # measured and rejected on this hardware: fewer-lane sorts scale
    # sub-linearly (1-lane 214ms / +rid 287ms / full 369ms at 2^26), a
    # two-pass stable word sort with gathers is 7x SLOWER (TPU random
    # gather ~1s per 2^26 u32 pass), and a scatter-based radix needs
    # scatter throughput the TPU doesn't offer. The sort IS the
    # roofline; beating it needs a different machine primitive, not a
    # different schedule.
    def encode_step(xc, yc, tc):
        hi, lo = sfc.index_jax_hi_lo(xc, yc, tc)
        w = jnp.arange(n, dtype=jnp.uint32)
        return (hi * w).sum() + (lo * w).sum()

    hi0, lo0 = jax.jit(sfc.index_jax_hi_lo)(x, y, t)
    jax.block_until_ready((hi0, lo0))

    def sort_step(hi, lo):
        rid = jnp.arange(n, dtype=jnp.uint32)
        hi_s, lo_s, rid_s = jax.lax.sort((hi, lo, rid), num_keys=2)
        w = jnp.arange(n, dtype=jnp.uint32)
        return (hi_s * w).sum() + (lo_s * w).sum() + (rid_s * w).sum()

    enc_rate = _measure_build(
        args, encode_step, (x, y, t), n, "z3 encode-only"
    )
    sort_rate = _measure_build(
        args, sort_step, (hi0, lo0), n, "z3 sort-only"
    )
    enc_ms = n / enc_rate * 1e3
    sort_ms = n / sort_rate * 1e3
    return {
        "metric": "Z3 index build (encode + device sort + rid payload)",
        "value": round(pts_per_sec, 1),
        "unit": "pts/sec/chip",
        "vs_baseline": None,  # BASELINE.json: 'TBD at first measurement'
        "build_chain": args.chain_build,
        "build_n": n,
        "build_breakdown": {
            "encode_ms": round(enc_ms, 1),
            "sort_ms": round(sort_ms, 1),
            "sort_frac": round(sort_ms / (enc_ms + sort_ms), 3),
            "note": "sort-bound: lax.sort of (hi,lo,rid) is the "
                    "roofline; 2-pass word sort 7x slower (gathers), "
                    "radix needs scatter throughput the TPU lacks",
        },
    }


def bench_xz_build(args) -> dict:
    """BASELINE config #5 shape (building-footprint XZ2/XZ3 non-point
    indexing): device XZ extent-curve encode (the quad/octree walk in
    uint32 hi/lo lanes) + lexicographic sort with a row-id payload — the
    single-chip slice of the pod-scale non-point build (the mesh exchange
    leg is proven by dryrun_multichip's xz3 parity check)."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.curves import XZ3SFC

    platform = jax.devices()[0].platform
    n = args.n or ((1 << 24) if platform != "cpu" else (1 << 18))
    log(f"platform={platform} n={n:,} (xz build mode)")
    sfc = XZ3SFC()
    key = jax.random.PRNGKey(9)
    kx, ky, kw, kh, kt = jax.random.split(key, 5)
    xmin = jax.random.uniform(kx, (n,), jnp.float32, -170.0, 160.0)
    ymin = jax.random.uniform(ky, (n,), jnp.float32, -85.0, 75.0)
    xmax = xmin + jax.random.uniform(kw, (n,), jnp.float32, 0.001, 5.0)
    ymax = ymin + jax.random.uniform(kh, (n,), jnp.float32, 0.001, 5.0)
    off = jax.random.uniform(kt, (n,), jnp.float32, 0.0, float(sfc.t_max))
    jax.block_until_ready((xmin, ymin, xmax, ymax, off))

    def build_step(x0, y0, x1, y1, t):
        hi, lo = sfc.index_jax_hi_lo(x0, y0, t, x1, y1, t)
        rid = jnp.arange(n, dtype=jnp.uint32)
        hi_s, lo_s, rid_s = jax.lax.sort((hi, lo, rid), num_keys=2)
        w = jnp.arange(n, dtype=jnp.uint32)
        return (hi_s * w).sum() + (lo_s * w).sum() + (rid_s * w).sum()

    if args.check:
        import numpy as np

        # reduced-n check (host transfer; sort math is size-independent):
        # the device SORT must equal a host sort of the same device encode
        # (f32 lanes — the encode's own f64 parity is covered by the unit
        # tests, same convention as the z3 build check)
        nc = min(n, 1 << 20)
        sub = (xmin[:nc], ymin[:nc], xmax[:nc], ymax[:nc], off[:nc])

        @jax.jit
        def enc(x0, y0, x1, y1, t):
            hi, lo = sfc.index_jax_hi_lo(x0, y0, t, x1, y1, t)
            rid = jnp.arange(nc, dtype=jnp.uint32)
            return hi, lo, jax.lax.sort((hi, lo, rid), num_keys=2)

        hi_u, lo_u, (hi_s, lo_s, rid_s) = enc(*sub)
        got = (np.asarray(hi_s).astype(np.uint64) << np.uint64(32)) | (
            np.asarray(lo_s).astype(np.uint64)
        )
        raw = (np.asarray(hi_u).astype(np.uint64) << np.uint64(32)) | (
            np.asarray(lo_u).astype(np.uint64)
        )
        assert np.array_equal(got, np.sort(raw)), \
            "device xz sort != host sort of the same keys"
        # the rid payload (which determines real row order in a build)
        # must reproduce the sorted keys when applied to the unsorted ones
        perm = np.asarray(rid_s).astype(np.int64)
        assert np.array_equal(raw[perm], got), "xz rid payload mis-permuted"
        log(f"xz device sort + rid permutation verified at n={nc:,}")

    rate = _measure_build(
        args, build_step, (xmin, ymin, xmax, ymax, off), n, "xz build"
    )
    return {
        "metric": "XZ3 non-point index build (device tree-walk + sort)",
        "value": round(rate, 1),
        "unit": "envelopes/sec/chip",
        "xz_build_chain": args.chain_build,
        "xz_build_n": n,
    }


#: BENCH_r05 join leg: 250,406 pairs/s through the old per-window
#: window_pairs_query coarse pass at 1M x 10K — the baseline the smoke
#: guard holds the engine to (>= 10x)
R05_JOIN_PAIRS_PER_SEC = 250_406.4


def _join_reference(x, y, envs):
    """Exact envelope-join oracle (numpy, window-major pairs): what the
    engine must match BIT-IDENTICALLY — same pairs, same order."""
    import numpy as np

    xo = np.argsort(x, kind="stable")
    xs = x[xo]
    out_r, out_w = [], []
    for j in range(len(envs)):
        a, b, c, d = envs[j]
        lo = np.searchsorted(xs, a, side="left")
        hi = np.searchsorted(xs, c, side="right")
        cand = xo[lo:hi]
        ids = np.sort(cand[(y[cand] >= b) & (y[cand] <= d)])
        if len(ids):
            out_r.append(ids)
            out_w.append(np.full(len(ids), j, np.int64))
    if not out_r:
        e = np.empty(0, np.int64)
        return e, e.copy()
    return (
        np.concatenate(out_r).astype(np.int64), np.concatenate(out_w),
    )


def _join_leg(eng, envs, label):
    """One timed engine join, warmed on the FULL window set outside the
    timing — a prefix would compile smaller power-of-two candidate
    buckets than the measurement uses on the device engine."""
    eng.join(envs)
    t = time.perf_counter()
    res = eng.join(envs)
    wall = time.perf_counter() - t
    log(
        f"join[{label}]: {len(envs):,} windows -> {res.pairs:,} pairs in "
        f"{wall*1e3:.0f}ms = {res.pairs/wall/1e6:.2f}M pairs/s "
        f"(strategy={res.strategy} engine={res.engine} "
        f"candidates={res.candidates:,} splits={res.splits})"
    )
    return res, wall


def bench_join(args) -> dict:
    """Device-side spatial join engine (ISSUE 11): the r05 workload
    (1M left x 10K 2-degree windows, ~3.5M pairs) through the join
    planner — Z-range co-partitioned candidate runs, adaptive strategy
    selection, batched count->cap->compact refinement — EXACT (bit-
    identical to the numpy envelope-join oracle), vs BENCH_r05's 250K
    candidate pairs/s through the old per-window coarse pass. Legs:
    auto + forced-strategy points, a layout-aligned (Z-sorted staged
    order) fast path, polygon-polygon topological interlinking over the
    XZ layout, enrichment against a streamed live layer, and a mesh
    co-partitioned scaling leg (zero cross-shard exchange). ``--smoke``
    shrinks the workload and guards rate >= 10x the r05 baseline with
    full-parity asserts (CI tier-1 safe)."""
    import jax
    import numpy as np

    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.join import JoinEngine
    from geomesa_tpu.store.memory import MemoryDataStore

    platform = jax.devices()[0].platform
    smoke = bool(args.smoke)
    n = args.n or ((1 << 18) if smoke else (1 << 20))
    m = 2_048 if smoke else 10_000
    log(f"platform={platform} n={n:,} |R|={m:,} (join mode)")
    rng = np.random.default_rng(3)
    x = rng.uniform(-60, 60, n)
    y = rng.uniform(-50, 50, n)
    ds = MemoryDataStore()
    ds.create_schema("t", "dtg:Date,*geom:Point:srid=4326")
    ds.write("t", {
        "dtg": rng.integers(1_577_836_800_000, 1_583_020_800_000, n),
        "geom": np.stack([x, y], axis=1),
    })
    di = DeviceIndex(ds, "t")
    x0 = rng.uniform(-60, 58, m)
    y0 = rng.uniform(-50, 48, m)
    envs = np.stack([x0, y0, x0 + 2, y0 + 2], axis=1)

    eng = JoinEngine(di)
    t = time.perf_counter()
    eng.prepare()  # the join layout build (cached per staged generation)
    prep_s = time.perf_counter() - t
    res, wall = _join_leg(eng, envs, "auto")
    out = {
        # legacy trajectory keys (BENCH_r0* continuity) — NOTE the new
        # engine emits EXACT pairs where the old coarse pass emitted
        # candidates, so pairs/s now measures finished join work
        "join_windows_per_sec": round(m / wall, 1),
        "join_pairs_per_sec": round(res.pairs / wall, 1),
        "join_n_left": n,
        "join_n_right": m,
        "join_pairs": int(res.pairs),
        "join_wall_s": round(wall, 2),
        "join_exact": True,
        "join_strategy": res.strategy,
        "join_engine": res.engine,
        "join_level": res.level,
        "join_candidates": int(res.candidates),
        "join_skew_splits": int(res.splits),
        "join_prep_s": round(prep_s, 3),
        "join_plan_s": round(res.plan_s, 4),
        "join_refine_s": round(res.refine_s, 4),
        "join_speedup_vs_r05": round(
            res.pairs / wall / R05_JOIN_PAIRS_PER_SEC, 1
        ),
    }

    # parity: FULL bit-identity at smoke scale, sampled windows at scale
    # (the oracle runs over the STAGED row order — pairs index into the
    # resident mirror, which the store Z-orders on write)
    if args.check or smoke:
        sx, sy = di._host_rows().point_coords("geom")
        sub = envs if smoke else envs[:256]
        rr, rw = _join_reference(
            np.asarray(sx, np.float64), np.asarray(sy, np.float64), sub
        )
        got = eng.join(sub)
        assert np.array_equal(got.rows, rr) and np.array_equal(
            got.wins, rw
        ), (
            f"join != reference: {got.pairs} vs {len(rr)} pairs"
        )
        log(f"join bit-identical to the oracle on {len(sub)} windows "
            f"({len(rr):,} pairs)")

    # forced-strategy legs (same workload; parity asserted under smoke)
    for strat in ("grouped", "zmerge"):
        with prop_override("join.strategy", strat):
            sres, swall = _join_leg(eng, envs, strat)
        out[f"join_{strat}_pairs_per_sec"] = round(sres.pairs / swall, 1)
        out[f"join_{strat}_candidates"] = int(sres.candidates)
        if args.check or smoke:
            assert sres.pairs == res.pairs and np.array_equal(
                sres.rows, res.rows
            ), f"forced {strat} diverged from auto"
    bm = min(64, m)
    with prop_override("join.strategy", "broadcast"):
        bres, bwall = _join_leg(eng, envs[:bm], "broadcast")
    out["join_broadcast_windows"] = bm
    out["join_broadcast_pairs_per_sec"] = round(bres.pairs / bwall, 1)

    # layout-aligned leg: a date-less point type written Z-SORTED (what
    # an FS store's flush order gives staging) — identity permutation,
    # emission order free
    from geomesa_tpu.curves.z2 import Z2SFC

    zo = np.argsort(Z2SFC().index(x, y), kind="stable")
    ds.create_schema("ts", "*geom:Point:srid=4326")
    ds.write("ts", {"geom": np.stack([x[zo], y[zo]], axis=1)})
    dis = DeviceIndex(ds, "ts")
    engs = JoinEngine(dis)
    engs.prepare()
    ares, awall = _join_leg(engs, envs, "aligned")
    out["join_aligned_pairs_per_sec"] = round(ares.pairs / awall, 1)
    if args.check or smoke:
        assert ares.pairs == res.pairs, "aligned layout changed the join"

    out.update(_bench_join_poly(args, smoke, rng))
    out.update(_bench_join_stream(args, smoke, rng))
    if len(jax.devices()) > 1:
        out.update(_bench_join_mesh(args, smoke, di, envs, res))

    if smoke:
        rate = out["join_pairs_per_sec"]
        floor = 10 * R05_JOIN_PAIRS_PER_SEC
        assert rate >= floor, (
            f"join smoke guard: {rate:,.0f} pairs/s is under 10x the "
            f"r05 baseline ({floor:,.0f})"
        )
        log(f"join smoke guard ok: {rate/R05_JOIN_PAIRS_PER_SEC:.1f}x r05")
        out["join_smoke_guard_x"] = round(
            rate / R05_JOIN_PAIRS_PER_SEC, 1
        )
    return out


def _bench_join_poly(args, smoke, rng) -> dict:
    """Polygon-polygon topological interlinking (JedAI-spatial): box
    polygons joined on exact st_intersects through the XZ join layout +
    per-window predicate residual — the frame-level path."""
    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.geom import Polygon
    from geomesa_tpu.sql.frame import SpatialFrame
    from geomesa_tpu.store.memory import MemoryDataStore

    n = (1 << 13) if smoke else (1 << 15)
    m = 256 if smoke else 1_024

    def boxes(k, wmin, wmax):
        cx = rng.uniform(-60, 60, k)
        cy = rng.uniform(-50, 50, k)
        w = rng.uniform(wmin, wmax, k)
        h = rng.uniform(wmin, wmax, k)
        return np.array(
            [
                Polygon(np.array([
                    [cx[i] - w[i], cy[i] - h[i]],
                    [cx[i] + w[i], cy[i] - h[i]],
                    [cx[i] + w[i], cy[i] + h[i]],
                    [cx[i] - w[i], cy[i] + h[i]],
                    [cx[i] - w[i], cy[i] - h[i]],
                ]))
                for i in range(k)
            ],
            dtype=object,
        )

    ds = MemoryDataStore()
    ds.create_schema("pl", "*geom:Geometry:srid=4326")
    ds.write("pl", {"geom": boxes(n, 0.02, 0.3)})
    ds.create_schema("pr", "*geom:Geometry:srid=4326")
    ds.write("pr", {"geom": boxes(m, 0.5, 2.0)})
    di = DeviceIndex(ds, "pl")
    fl, fr = SpatialFrame(ds, "pl"), SpatialFrame(ds, "pr")
    fl.spatial_join(
        SpatialFrame(ds, "pr").limit(32), device_index=di
    )  # warm
    t = time.perf_counter()
    left, right, pairs = fl.spatial_join(fr, device_index=di)
    wall = time.perf_counter() - t
    log(
        f"join[poly-xz]: {n:,} x {m:,} polygons -> {len(pairs):,} exact "
        f"st_intersects pairs in {wall*1e3:.0f}ms = "
        f"{len(pairs)/wall/1e6:.2f}M pairs/s"
    )
    if args.check or smoke:
        rl, rr_, rpairs = fl.spatial_join(fr)  # numpy oracle path
        a = sorted((left.fids[i], j) for i, j in pairs)
        b = sorted((rl.fids[i], j) for i, j in rpairs)
        assert a == b, "polygon join != oracle"
        log(f"polygon join bit-identical to the oracle ({len(b):,} pairs)")
    return {
        "join_poly_n_left": n,
        "join_poly_n_right": m,
        "join_poly_pairs": int(len(pairs)),
        "join_poly_pairs_per_sec": round(len(pairs) / wall, 1),
    }


def _bench_join_stream(args, smoke, rng) -> dict:
    """Enrichment join against a STREAMED live layer: acked-but-
    uncompacted rows join immediately (the live merged view is the
    engine's left side; its layout is not Z-sorted, so this leg also
    exercises the permutation + re-canonicalization path)."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.join import JoinEngine
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.stream import StreamingStore

    n_seed = (1 << 14) if smoke else (1 << 17)
    n_live = (1 << 11) if smoke else (1 << 14)
    m = 512 if smoke else 2_048
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-join-stream-")
    try:
        ds = FileSystemDataStore(os.path.join(tmp, "s"))
        ds.create_schema("e", "dtg:Date,*geom:Point:srid=4326")
        xs = rng.uniform(-60, 60, n_seed)
        ys = rng.uniform(-50, 50, n_seed)
        ds.write("e", {
            "dtg": rng.integers(0, 10**9, n_seed),
            "geom": np.stack([xs, ys], axis=1),
        }, fids=np.arange(n_seed))
        ds.flush("e")
        layer = StreamingStore(ds)
        try:
            xl = rng.uniform(-60, 60, n_live)
            yl = rng.uniform(-50, 50, n_live)
            for a in range(0, n_live, 2048):
                b = min(a + 2048, n_live)
                layer.append("e", {
                    "dtg": rng.integers(0, 10**9, b - a),
                    "geom": np.stack([xl[a:b], yl[a:b]], axis=1),
                }, fids=np.arange(n_seed + a, n_seed + b))
            di = DeviceIndex(layer, "e")
            eng = JoinEngine(di)
            eng.prepare()
            x0 = rng.uniform(-60, 58, m)
            y0 = rng.uniform(-50, 48, m)
            envs = np.stack([x0, y0, x0 + 2, y0 + 2], axis=1)
            eng.join(envs)  # warm the timed shapes
            t = time.perf_counter()
            res = eng.join(envs)
            wall = time.perf_counter() - t
            log(
                f"join[stream-enrich]: {n_seed + n_live:,} rows "
                f"({n_live:,} live) x {m:,} windows -> {res.pairs:,} "
                f"pairs in {wall*1e3:.0f}ms = "
                f"{res.pairs/wall/1e6:.2f}M pairs/s"
            )
            if args.check or smoke:
                # oracle over the STAGED (merged-view) row order —
                # full bit-identity on rows AND windows, not a count
                gx, gy = di._host_rows().point_coords("geom")
                rr, rw = _join_reference(
                    np.asarray(gx, np.float64),
                    np.asarray(gy, np.float64), envs,
                )
                assert np.array_equal(res.rows, rr) and np.array_equal(
                    res.wins, rw
                ), (
                    f"stream enrichment join != oracle "
                    f"({res.pairs} vs {len(rr)} pairs)"
                )
                log("stream enrichment join bit-identical to the oracle "
                    f"({len(rr):,} pairs over the merged live view)")
            return {
                "join_stream_rows": n_seed + n_live,
                "join_stream_live_rows": n_live,
                "join_stream_pairs": int(res.pairs),
                "join_stream_pairs_per_sec": round(res.pairs / wall, 1),
            }
        finally:
            layer.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_join_mesh(args, smoke, di, envs, base_res) -> dict:
    """Mesh co-partitioned scaling leg: the SAME join across shard
    counts, runs clipped at shard row boundaries so every refinement
    launch is pure shard-local compute — zero cross-shard row exchange
    by construction (the kernels contain no collectives). Pairs must be
    bit-identical at every shard count. (On a 1-core virtual-device
    harness wall-clock does not improve with shards — the honest
    artifact PR 8 recorded for serve qps; the leg proves partitioning +
    parity, real meshes get the speedup.)"""
    import jax
    import numpy as np

    from geomesa_tpu.join import JoinEngine
    from geomesa_tpu.parallel.mesh import make_mesh

    ndev = len(jax.devices())
    counts = [c for c in (1, 2, 4, 8) if c <= ndev]
    sub = envs[: (256 if smoke else 2_048)]
    rates = {}
    for s in counts:
        mesh = make_mesh(n_devices=s)
        eng = JoinEngine(di, mesh=mesh)
        eng.join(sub)  # warm the timed shapes
        t = time.perf_counter()
        res = eng.join(sub)
        wall = time.perf_counter() - t
        rates[str(s)] = round(res.pairs / wall, 1)
        ref = JoinEngine(di).join(sub)
        assert np.array_equal(res.rows, ref.rows) and np.array_equal(
            res.wins, ref.wins
        ), f"mesh join diverged at {s} shards"
        log(
            f"join[mesh s={s}]: {res.pairs:,} pairs in {wall*1e3:.0f}ms "
            f"({rates[str(s)]/1e6:.2f}M pairs/s, bit-identical, "
            "exchanged_bytes=0)"
        )
    return {
        "join_mesh_pairs_per_sec": rates,
        "join_mesh_parity": True,
        "join_mesh_exchanged_bytes": 0,
    }


def bench_oocscan(args) -> dict:
    """Out-of-core streamed scan: the raw device pump ceiling
    (_bench_oocscan_pump) plus the STORE-INTEGRATED leg
    (_bench_oocscan_store) that measures what BENCH_r05 showed as the
    roofline — host partition read/decode/stage — serial vs pipelined
    (store/prefetch.py). ``--smoke`` runs only the store leg at small N
    with a sustained-MB/s regression guard (CI tier-1 safe); the full
    pump leg is the slow one."""
    if getattr(args, "smoke", False):
        return _bench_oocscan_store(args, smoke=True)
    out = _bench_oocscan_pump(args)
    out.update(_bench_oocscan_store(args, smoke=False))
    return out


def _bench_oocscan_store(args, smoke: bool) -> dict:
    """Store-integrated out-of-core scan: real Parquet partition files
    on disk streamed through StreamedDeviceScan, once SERIAL (io=0, the
    pre-pipeline baseline: read+decode+stage+device strictly in turn on
    one thread) and once PIPELINED (io.workers threads read/decode/stage
    with bounded read-ahead while the device consumes). Records
    sustained MB/s for both, the speedup, and the host-read breakdown
    (geomesa_io_* read/decode/stage seconds) so a regression in any
    stage is attributable. Counts must match exactly between the runs
    (the full result-parity matrix lives in tests/test_prefetch.py).

    The speedup ceiling is machine-dependent: worker threads scale the
    GIL-releasing pyarrow/numpy work across cores, so the >= 4x target
    (worker count >= 4) needs >= 4 usable cores; a 1-core CI box only
    gets the read/device overlap. The smoke guard therefore asserts
    no-regression (pipelined >= 0.5x serial), not the multi-core
    target."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from geomesa_tpu import metrics as gm
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.oocscan import StreamedDeviceScan
    from geomesa_tpu.store.prefetch import PrefetchConfig

    n = args.n or ((1 << 17) if smoke else (1 << 21))
    workers = getattr(args, "io_workers", 0) or 4
    part_rows = max(1 << 10, n // (16 if smoke else 64))
    log(f"oocscan store leg: n={n:,} part_rows={part_rows:,} "
        f"io_workers={workers} (smoke={smoke})")
    from geomesa_tpu.conf import prop_override

    tmp = tempfile.mkdtemp(prefix="geomesa_ooc_store_")
    try:
        # several chunks per partition so the chunk-prune leg below has
        # sub-partition granularity to work with (v2 default format);
        # the 256-row floor keeps tiny CI sizes at >= 8 chunks/partition
        with prop_override("store.chunk.rows", max(1 << 8, part_rows // 8)):
            ds = FileSystemDataStore(
                os.path.join(tmp, "s"), partition_size=part_rows
            )
            ds.create_schema(
                "t", "val:Int,tone:Float,dtg:Date,*geom:Point:srid=4326"
            )
            rng = np.random.default_rng(7)
            t0 = parse_instant("2020-01-01T00:00:00")
            t1 = parse_instant("2020-02-01T00:00:00")
            ds.write("t", {
                "val": rng.integers(0, 100, n),
                "tone": rng.uniform(-10, 10, n).astype(np.float32),
                "dtg": rng.integers(t0, t1, n),
                "geom": np.stack(
                    [rng.uniform(-60, 60, n), rng.uniform(-50, 50, n)],
                    axis=1,
                ),
            }, fids=np.arange(n))
            ds.flush("t")
        ecql = (
            "BBOX(geom, -10, 0, 40, 45) AND "
            "dtg DURING 2020-01-05T00:00:00Z/2020-01-20T00:00:00Z"
        )

        def hist_sums():
            return {
                k: float(h.stats().get("sum", 0.0))
                for k, h in (
                    ("read", gm.io_read_seconds),
                    ("decode", gm.io_decode_seconds),
                    ("stage", gm.io_stage_seconds),
                )
            }

        # smoke sizes finish in tens of ms where a scheduler hiccup or a
        # concurrent process on a small box swamps the measurement — time
        # several iterations and keep the BEST (the one least disturbed
        # by outside load); the full leg is long enough for one pass
        iters = 3 if smoke else 1

        def run(io, label):
            scan = StreamedDeviceScan(
                ds, "t", slab_rows=part_rows * 4, io=io
            )
            scan.count(ecql)  # warm: kernel compile + OS page cache
            hits, wall, nbytes, brk = None, None, None, None
            for _ in range(iters):
                b0 = sum(s.bytes_streamed for s in scan._streams.values())
                h0 = hist_sums()
                t = time.perf_counter()
                hits = scan.count(ecql)
                w = time.perf_counter() - t
                if wall is None or w < wall:
                    wall = w
                    nbytes = (
                        sum(s.bytes_streamed
                            for s in scan._streams.values()) - b0
                    )
                    brk = {
                        k: round(v - h0[k], 3)
                        for k, v in hist_sums().items()
                    }
            mbps = nbytes / 2**20 / wall if wall > 0 else 0.0
            log(
                f"oocscan[{label}]: {n:,} rows in {wall:.2f}s -> "
                f"{mbps:.0f}MB/s sustained (host read={brk['read']:.2f}s "
                f"decode={brk['decode']:.2f}s stage={brk['stage']:.2f}s)"
            )
            return hits, wall, mbps, brk

        # the serial-vs-pipelined legs measure the HOST I/O pipeline on
        # the full stream: chunk pruning/pushdown off so every byte
        # still flows (the pruning win is its own leg below)
        with prop_override("store.chunk.prune", False), \
                prop_override("store.chunk.pushdown", False):
            hits_serial, wall_s, mbps_s, brk_s = run(0, "serial")
            hits_piped, wall_p, mbps_p, brk_p = run(
                PrefetchConfig(workers=workers), f"workers={workers}"
            )
        # byte-identical results between serial and pipelined is the
        # non-negotiable contract; the bench double-checks what the
        # parity tests prove
        assert hits_piped == hits_serial, (hits_piped, hits_serial)
        speedup = round(mbps_p / mbps_s, 2) if mbps_s else None
        log(f"oocscan store: serial {mbps_s:.0f}MB/s -> pipelined "
            f"{mbps_p:.0f}MB/s ({speedup}x, {workers} workers)")
        out = {
            "oocscan_store_n": n,
            "oocscan_store_hits": int(hits_piped),
            "oocscan_io_workers": workers,
            "oocscan_serial_mbps": round(mbps_s, 1),
            "oocscan_pipelined_mbps": round(mbps_p, 1),
            "oocscan_pipeline_speedup": speedup,
            "oocscan_serial_wall_s": round(wall_s, 2),
            "oocscan_pipelined_wall_s": round(wall_p, 2),
            "oocscan_host_read_s": brk_p["read"],
            "oocscan_host_decode_s": brk_p["decode"],
            "oocscan_host_stage_s": brk_p["stage"],
            "oocscan_serial_read_s": brk_s["read"],
            "oocscan_serial_decode_s": brk_s["decode"],
            "oocscan_serial_stage_s": brk_s["stage"],
        }
        if smoke:
            # regression guard: the pipeline must never make the scan
            # PATHOLOGICALLY slower than serial, whatever the core count.
            # Deliberately loose (0.3x, best-of-3 walls): at smoke sizes
            # the walls are tens of ms of page-cached reads, so thread
            # handoff + outside load produce real 0.7-1.0x scatter on a
            # 1-core box — the guard exists to catch a deadlocked or
            # serialized-by-accident pipeline (order-of-magnitude drops),
            # not to certify the multi-core speedup the full leg records
            assert mbps_p >= 0.3 * mbps_s, (
                f"oocscan pipeline regression: {mbps_p:.0f}MB/s pipelined "
                f"vs {mbps_s:.0f}MB/s serial"
            )
            out["oocscan_smoke"] = True

        # -- chunk-prune leg (ISSUE 6): the selective window again, with
        # the chunk Z/bbox/time pruning index deciding what streams at
        # all. Pushdown stays off so the leg isolates PRUNING: surviving
        # chunks still read/decode/stream through the device; identical
        # hit counts are the non-negotiable contract. The pruned-bytes
        # ratio is real file bytes (skipped parquet row groups).
        scan_pr = StreamedDeviceScan(
            ds, "t", slab_rows=part_rows * 4,
            io=PrefetchConfig(workers=workers),
        )
        with prop_override("store.chunk.pushdown", False):
            scan_pr.count(ecql)  # warm
            cr0 = gm.store_chunks_read.value()
            cs0 = gm.store_chunks_skipped.value()
            bs0 = gm.store_chunk_bytes_skipped.value()
            br0 = gm.io_bytes_read.value()
            t = time.perf_counter()
            hits_pruned = scan_pr.count(ecql)
            wall_pr = time.perf_counter() - t
        chunks_read = int(gm.store_chunks_read.value() - cr0)
        chunks_skipped = int(gm.store_chunks_skipped.value() - cs0)
        bytes_skipped = int(gm.store_chunk_bytes_skipped.value() - bs0)
        bytes_read = int(gm.io_bytes_read.value() - br0)
        pruned_ratio = (
            round(bytes_skipped / (bytes_skipped + bytes_read), 3)
            if (bytes_skipped + bytes_read)
            else 0.0
        )
        assert hits_pruned == hits_piped, (hits_pruned, hits_piped)
        prune_speedup = round(wall_p / wall_pr, 2) if wall_pr > 0 else None
        log(
            f"oocscan chunk prune: {chunks_skipped}/{chunks_read + chunks_skipped}"
            f" chunks skipped, {pruned_ratio:.0%} of bytes pruned, "
            f"{wall_pr:.2f}s ({prune_speedup}x vs unpruned pipelined), "
            f"hits identical"
        )
        # ...and the count-pushdown short-circuit on the same window
        # (interior chunks from the manifest, boundary chunks streamed)
        with prop_override("store.chunk.prune", True):
            scan_pd = StreamedDeviceScan(
                ds, "t", slab_rows=part_rows * 4,
                io=PrefetchConfig(workers=workers),
            )
            scan_pd.count(ecql)  # warm
            t = time.perf_counter()
            hits_pd = scan_pd.count(ecql)
            wall_pd = time.perf_counter() - t
        assert hits_pd == hits_piped, (hits_pd, hits_piped)
        out.update({
            "oocscan_chunks_read": chunks_read,
            "oocscan_chunks_skipped": chunks_skipped,
            "oocscan_pruned_bytes_ratio": pruned_ratio,
            "oocscan_pruned_wall_s": round(wall_pr, 3),
            "oocscan_prune_speedup": prune_speedup,
            "oocscan_pushdown_wall_s": round(wall_pd, 3),
            "oocscan_pushdown_speedup": (
                round(wall_p / wall_pd, 2) if wall_pd > 0 else None
            ),
        })
        if smoke:
            # regression guard (acceptance): the selective window must
            # skip at least half the file bytes with identical hits
            assert pruned_ratio >= 0.5, (
                f"chunk pruning skipped only {pruned_ratio:.0%} of bytes "
                "on the selective window"
            )
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _bench_oocscan_pump(args) -> dict:
    """Raw device slab pump ceiling: a multi-GB
    dataset streamed through the double-buffered device slab pump
    (store/oocscan.SlabStream) with the flagship compiled filter fused
    per slab — the path that serves datasets LARGER than HBM (device
    memory holds two slabs, dataset size is bounded by disk). Chunks
    are deterministic per-chunk PRNG (modeling partition reads; the
    real store integration is measured by _bench_oocscan_store and
    parity-proven in tests/test_oocscan.py).

    The leg records BOTH phases — ``oocscan_burst_mbps`` over its first
    ~1GB and the sustained whole-stream figure — so a pump that slows
    mid-stream shows as a gap between them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.compile import compile_filter
    from geomesa_tpu.filter.ecql import parse_ecql, parse_instant
    from geomesa_tpu.store.oocscan import SlabStream

    platform = jax.devices()[0].platform
    # default 2^26 (1.1GiB through a 0.27GiB slab window): demonstrates
    # the mechanism at 4x slab capacity
    n_total = args.n or ((1 << 26) if platform == "tpu" else (1 << 22))
    slab = (1 << 24) if platform == "tpu" else (1 << 18)
    slab = min(slab, n_total)
    n_slabs = (n_total + slab - 1) // slab
    log(f"platform={platform} n={n_total:,} slab={slab:,} x {n_slabs} "
        "(oocscan mode)")
    sft = SimpleFeatureType.create(
        "gdelt", "count:Int,dtg:Date,*geom:Point:srid=4326"
    )
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    ecql = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    compiled = compile_filter(parse_ecql(ecql), sft)
    assert compiled.fully_on_device

    def chunk(i: int, with_ms: bool = False):
        rng = np.random.default_rng(9000 + i)
        rows = min(slab, n_total - i * slab)
        ms = rng.integers(t0, t1, rows)
        cols = {
            "geom__x": rng.uniform(-180, 180, rows).astype(np.float32),
            "geom__y": rng.uniform(-90, 90, rows).astype(np.float32),
            "dtg__hi": (ms >> 32).astype(np.int32),
            "dtg__lo": (ms & 0xFFFFFFFF).astype(np.uint32),
        }
        return (cols, ms) if with_ms else cols

    def agg(cols, valid):
        return jnp.sum(compiled.device_fn(cols) & valid, dtype=jnp.int32)

    # burst phase: the first ~1GB (compile excluded by streaming slab 0
    # twice: its first pass carries the compile)
    stream = SlabStream(agg)
    burst_slabs = max(1, (1 << 30) // (slab * 17))  # ~1GB at 17B/row
    pre = [chunk(i) for i in range(min(burst_slabs + 1, n_slabs))]
    stream.run(pre[:1])  # compile (no host prep concurrent with it)
    b0 = stream.bytes_streamed
    t = time.perf_counter()
    outs_burst = stream.run(iter(pre))
    burst_s = time.perf_counter() - t
    burst_bytes = stream.bytes_streamed - b0
    burst_mbps = burst_bytes / 2**20 / burst_s
    # full stream (sustained)
    outs = list(outs_burst)
    t_wall = time.perf_counter()
    outs += stream.run(chunk(i) for i in range(len(pre), n_slabs))
    wall = burst_s + (time.perf_counter() - t_wall)
    total = int(sum(int(o) for o in outs))
    bytes_streamed = stream.bytes_streamed - b0
    if args.check:
        want = 0
        for i in range(min(n_slabs, 4)):  # spot-check slabs
            cols, ms = chunk(i, with_ms=True)
            m = (
                (cols["geom__x"] >= -10) & (cols["geom__x"] <= 30)
                & (cols["geom__y"] >= 35) & (cols["geom__y"] <= 60)
                & (ms >= parse_instant("2020-01-10T00:00:00"))
                & (ms <= parse_instant("2020-01-15T00:00:00"))
            )
            want += int(m.sum())
            assert int(outs[i]) == int(m.sum()), (i, int(outs[i]), int(m.sum()))
        log(f"oocscan per-slab parity verified on {min(n_slabs, 4)} slabs")
    rate = n_total / wall
    log(
        f"oocscan: {n_total:,} rows ({bytes_streamed/2**30:.1f}GiB) in "
        f"{wall:.1f}s -> {rate/1e6:.1f}M rows/s sustained; burst "
        f"{burst_mbps:.0f}MB/s over first {burst_bytes/2**30:.1f}GiB"
    )
    return {
        "oocscan_rows_per_sec": round(rate, 1),
        "oocscan_n": n_total,
        "oocscan_slab_rows": slab,
        "oocscan_slabs": n_slabs,
        "oocscan_gib_streamed": round(bytes_streamed / 2**30, 2),
        "oocscan_wall_s": round(wall, 1),
        "oocscan_burst_mbps": round(burst_mbps, 0),
        "oocscan_sustained_mbps": round(bytes_streamed / 2**20 / wall, 0),
        "oocscan_hits": total,
    }


def bench_pipeline(args) -> dict:
    """BASELINE config #1 is "GDELT bbox+during VIA PARQUET" — this leg
    measures the real path the kernel benchmarks hide: a deterministic
    GDELT-like Parquet file -> converter
    ingest -> FileSystemDataStore flush (device-mesh sorted-index build)
    -> resident DeviceIndex staging -> first loose query (compile) ->
    repeated loose query. Each stage is timed separately; the JSON
    carries per-stage seconds and the staging/ingest rates."""
    import os
    import shutil
    import tempfile

    import jax
    import numpy as np

    from geomesa_tpu.convert import ParquetConverter
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.features.sft import SimpleFeatureType
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.store.fs import FileSystemDataStore

    platform = jax.devices()[0].platform
    n = args.n or ((1 << 22) if platform == "tpu" else (1 << 18))
    log(f"platform={platform} n={n:,} (pipeline mode)")
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    ecql = (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z"
    )
    out: dict = {"pipeline_n": n}
    tmp = tempfile.mkdtemp(prefix="geomesa_pipe_")
    try:
        # stage 0: deterministic GDELT-like Parquet file
        import pyarrow as pa
        import pyarrow.parquet as pq

        rng = np.random.default_rng(1234)
        t = time.perf_counter()
        table = pa.table({
            "event_id": np.arange(n, dtype=np.int64),
            "ts": rng.integers(t0, t1, n),
            "lon": rng.uniform(-180, 180, n).astype(np.float32),
            "lat": rng.uniform(-90, 90, n).astype(np.float32),
            "tone": rng.uniform(-10, 10, n).astype(np.float32),
        })
        pq_path = os.path.join(tmp, "gdelt.parquet")
        pq.write_table(table, pq_path)
        out["pipeline_gen_s"] = round(time.perf_counter() - t, 2)

        # stage 1: converter ingest (Parquet -> FeatureBatch)
        sft = SimpleFeatureType.create(
            "gdelt", "event_id:Long,tone:Float,dtg:Date,"
            "*geom:Point:srid=4326"
        )
        conv = ParquetConverter({
            "fields": [
                {"name": "event_id", "path": "event_id"},
                {"name": "tone", "path": "tone"},
                {"name": "dtg", "path": "ts"},
                {"name": "geom", "transform": "point($lon, $lat)"},
            ],
        }, sft)
        t = time.perf_counter()
        res = conv.process(pq_path)
        ingest_s = time.perf_counter() - t
        assert len(res.batch) == n
        out["pipeline_ingest_s"] = round(ingest_s, 2)
        out["pipeline_ingest_rows_per_sec"] = round(n / ingest_s, 1)

        # stage 2: FS flush — sorted-index build on the device mesh.
        # A tiny scratch-store flush first: the device encode + exchange
        # sort compile once per process, and a one-shot timing that is 90% first-compile says nothing
        # about the flush path. The warmup cost is recorded separately.
        from geomesa_tpu.parallel import make_mesh

        mesh = make_mesh(len(jax.devices()))
        t = time.perf_counter()
        warm = FileSystemDataStore(os.path.join(tmp, "warm"), mesh=mesh)
        warm.create_schema(sft)
        # must clear MESH_BUILD_MIN_ROWS (or the warmup routes to the
        # host lexsort and compiles nothing) AND land in the same
        # power-of-two shape bucket as the real flush (the device build
        # pads to pow2 so jit shapes are bounded; a different bucket
        # would compile twice)
        bucket = 1 << max(n - 1, 0).bit_length()
        n_warm = max(
            min(n, 2 * FileSystemDataStore.MESH_BUILD_MIN_ROWS),
            bucket // 2 + 1,
        )
        warm.write("gdelt", res.batch.take(np.arange(n_warm)))
        warm.flush("gdelt")
        out["pipeline_warmup_s"] = round(time.perf_counter() - t, 2)

        ds = FileSystemDataStore(os.path.join(tmp, "store"), mesh=mesh)
        ds.create_schema(sft)
        t = time.perf_counter()
        ds.write("gdelt", res.batch)
        ds.flush("gdelt")
        flush_s = time.perf_counter() - t
        out["pipeline_flush_s"] = round(flush_s, 2)
        out["pipeline_flush_rows_per_sec"] = round(n / flush_s, 1)

        # stage 3: resident staging (device key encode + column upload).
        # Cold includes the store read and the first-in-process compile/
        # executable loads (persistent cache); the RESTAGE is the steady
        # state a serving system pays after writes (di.refresh) — both
        # recorded, per the round-4 variance-honesty rule.
        t = time.perf_counter()
        di = DeviceIndex(ds, "gdelt", z_planes=True)
        stage_s = time.perf_counter() - t
        out["pipeline_stage_s"] = round(stage_s, 2)
        out["pipeline_stage_rows_per_sec"] = round(n / stage_s, 1)
        # restage = the steady-state staging read path: its partition
        # reads+decodes ride the host-I/O prefetch pipeline, and the
        # geomesa_io_* deltas attribute the restage wall between file
        # read and Arrow decode (the breakdown that showed staging
        # collapsing at 32M rows, ISSUE 2)
        from geomesa_tpu import metrics as _gm

        io0 = (
            float(_gm.io_read_seconds.stats().get("sum", 0.0)),
            float(_gm.io_decode_seconds.stats().get("sum", 0.0)),
        )
        t = time.perf_counter()
        di.refresh()
        restage_s = time.perf_counter() - t
        out["pipeline_restage_s"] = round(restage_s, 2)
        out["pipeline_restage_rows_per_sec"] = round(n / restage_s, 1)
        out["pipeline_restage_read_s"] = round(
            float(_gm.io_read_seconds.stats().get("sum", 0.0)) - io0[0], 2
        )
        out["pipeline_restage_decode_s"] = round(
            float(_gm.io_decode_seconds.stats().get("sum", 0.0)) - io0[1], 2
        )

        # stage 4: serving warmup (DeviceIndex.warmup pre-compiles every
        # kernel family — what `serve --resident --warm` runs before
        # accepting traffic, so no request pays a compile).
        # The cold-start story (kernel_warmup + first_query) is told at
        # the standard 2^22 size only: per-SHAPE server compiles are
        # n-independent theater (~10min for the full family at 2^25),
        # so scaled legs warm their one query kernel untimed and report
        # the serving rates.
        if n <= (1 << 22):
            t = time.perf_counter()
            di.warmup()
            out["pipeline_kernel_warmup_s"] = round(
                time.perf_counter() - t, 2
            )
            t = time.perf_counter()
            hits = di.count(ecql, loose=True)
            out["pipeline_first_query_ms"] = round(
                (time.perf_counter() - t) * 1e3, 1
            )
        else:
            hits = di.count(ecql, loose=True)  # untimed shape warm
        # ...and the served repeated query (median of 5)
        reps = []
        for _ in range(5):
            t = time.perf_counter()
            assert di.count(ecql, loose=True) == hits
            reps.append(time.perf_counter() - t)
        out["pipeline_query_ms"] = round(
            sorted(reps)[len(reps) // 2] * 1e3, 1
        )
        # end-to-end sanity: the pipeline answer matches the store path
        if args.check:
            store_hits = len(ds.query("gdelt", ecql).batch)
            assert hits >= store_hits, (hits, store_hits)
            exact = di.count(ecql, loose=False)
            assert exact == store_hits, (exact, store_hits)
            log(f"pipeline counts verified (loose {hits:,} >= exact "
                f"{store_hits:,})")
        log(
            "pipeline: gen=%.1fs ingest=%.1fs flush=%.1fs stage=%.1fs "
            "first=%.0fms repeat=%.0fms"
            % (out["pipeline_gen_s"], out["pipeline_ingest_s"],
               out["pipeline_flush_s"], out["pipeline_stage_s"],
               out.get("pipeline_first_query_ms", float("nan")),
               out["pipeline_query_ms"])
        )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def bench_flush(args) -> dict:
    """Durable-flush overhead guard (ISSUE 3). The crash-consistent
    flush writes generation-scoped files + per-partition checksums and
    fsyncs file contents, directories and the manifest before GC'ing
    the old generation; this leg measures that path against the same
    flush with ``store.fsync=off`` (the seed's fire-and-forget write
    behavior — checksums, being O(bytes) crc32 at memory speed, stay on
    in both and are charged to the durable side's budget). ``--smoke``
    (and ``--check``) assert the durable flush costs < 15% extra on the
    flush leg; the full leg runs the 1M-row (2^20) size, smoke a 2^18
    CI-sized one. Medians over fresh-store flushes (5 reps at smoke
    size, 3 at full)."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.store.fs import FileSystemDataStore

    # smoke stays big enough that the per-FILE fsync cost (fixed: ~4
    # partition files either way) is amortized the way the 1M-row leg
    # amortizes it — smaller sizes measure fsync latency, not the flush
    n = args.n or ((1 << 18) if args.smoke else (1 << 20))
    log(f"n={n:,} (flush mode: durable vs store.fsync=off)")
    rng = np.random.default_rng(99)
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = parse_instant("2020-03-01T00:00:00")
    cols = {
        "name": rng.choice(["alpha", "beta", "gamma"], n),
        "dtg": rng.integers(t0, t1, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }
    fids = np.arange(n)

    def one_flush(fsync: bool) -> float:
        tmp = tempfile.mkdtemp(prefix="geomesa_flush_")
        try:
            with prop_override("store.fsync", fsync):
                ds = FileSystemDataStore(
                    os.path.join(tmp, "s"), partition_size=1 << 15
                )
                ds.create_schema(
                    "gdelt", "name:String,dtg:Date,*geom:Point:srid=4326"
                )
                ds.write("gdelt", cols, fids=fids)
                t = time.perf_counter()
                ds.flush("gdelt")
                return time.perf_counter() - t
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # more reps at smoke size: an 80ms flush needs a sturdier median
    # against scheduler noise than the multi-second 1M-row leg
    reps = 5 if args.smoke else 3
    # interleave so drifting page-cache state cannot bias one side
    durable_s, base_s = [], []
    for _ in range(reps):
        base_s.append(one_flush(False))
        durable_s.append(one_flush(True))
    durable = sorted(durable_s)[reps // 2]
    base = sorted(base_s)[reps // 2]
    overhead = durable / base - 1.0
    out = {
        "flush_n": n,
        "flush_durable_s": round(durable, 3),
        "flush_nofsync_s": round(base, 3),
        "flush_durable_rows_per_sec": round(n / durable, 1),
        "flush_overhead_pct": round(overhead * 100, 1),
        "flush_durable_spread_s": [round(v, 3) for v in sorted(durable_s)],
        "flush_nofsync_spread_s": [round(v, 3) for v in sorted(base_s)],
    }
    log(
        f"flush: durable {durable:.2f}s vs no-fsync {base:.2f}s "
        f"({overhead:+.1%} overhead) at {n:,} rows"
    )
    if args.smoke or args.check:
        assert overhead < 0.15, (
            f"durable flush overhead {overhead:.1%} >= 15% "
            f"({durable:.2f}s vs {base:.2f}s at {n:,} rows)"
        )
        log("flush smoke guard passed (< 15% overhead)")
    return out


def bench_serving(args) -> dict:
    """Concurrent-serving leg (the device query scheduler): M client
    threads fire loose bbox counts at ``serve_background(resident=True,
    sched=...)`` with ONE in-flight device worker, so compatible queries
    pile into the admission queue and the micro-batcher executes them as
    shared stacked launches. Records throughput, p50/p99 latency and the
    fusion factor (queries per device launch > 1 is the win; 1.0 means
    the scheduler degraded to serial) — the scheduler regression signal
    in the BENCH_* trajectory. Every response is checked against the
    warmup (serially-executed) count for the same window, and --check
    additionally compares against the unscheduled DeviceIndex oracle."""
    import threading
    import urllib.request
    from urllib.parse import quote

    import jax
    import numpy as np

    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.sched import SchedConfig
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.memory import MemoryDataStore

    platform = jax.devices()[0].platform
    n = args.n or ((1 << 22) if platform == "tpu" else (1 << 16))
    n_threads, reqs_per = 8, 24
    log(f"platform={platform} n={n:,} serving: {n_threads} threads x "
        f"{reqs_per} loose bbox counts, 1 device worker")
    ds = MemoryDataStore()
    ds.create_schema("gdelt", "name:String,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(7)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("gdelt", {
        "name": rng.choice(["a", "b"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }, fids=np.arange(n))
    server, _ = serve_background(
        ds, resident=True,
        sched=SchedConfig(
            max_inflight=1, fusion_window_ms=5.0, max_queue=1024,
            default_deadline_ms=None,  # slow platforms must not 504
        ),
    )
    host, port = server.server_address[:2]
    # four distinct city/continent windows, all bbox-only (same full-
    # range time decomposition => one fused R bucket)
    windows = [
        (-10.0, 35.0, 30.0, 60.0),
        (-75.0, 38.0, -72.0, 42.0),
        (100.0, -10.0, 140.0, 25.0),
        (-60.0, -35.0, -40.0, -10.0),
    ]
    urls = [
        f"http://{host}:{port}/count/gdelt"
        f"?cql={quote(f'BBOX(geom, {w[0]}, {w[1]}, {w[2]}, {w[3]})')}"
        "&loose=1"
        for w in windows
    ]

    def get_count(u):
        with urllib.request.urlopen(u, timeout=600) as r:
            return json.loads(r.read())["count"]

    # warmup: stage + compile, and capture the serially-executed counts
    # (single requests fuse nothing) as the per-window parity oracle
    expect = [get_count(u) for u in urls]
    if args.check:
        di = server.RequestHandlerClass._resident_cache["gdelt"]
        for w, e in zip(windows, expect):
            cql = f"BBOX(geom, {w[0]}, {w[1]}, {w[2]}, {w[3]})"
            assert di.count(cql, loose=True) == e, (w, e)
        log("serving counts verified against the unscheduled oracle")
    s0 = server.scheduler.snapshot()
    lats: list = []
    bad: list = []
    lock = threading.Lock()

    import urllib.error

    def worker(tid: int):
        for i in range(reqs_per):
            j = (tid + i) % len(urls)
            t = time.perf_counter()
            try:
                c = get_count(urls[j])
            except urllib.error.HTTPError as e:
                with lock:  # shed/expired requests must not kill the thread
                    bad.append((j, f"HTTP {e.code}", expect[j]))
                continue
            dt = time.perf_counter() - t
            with lock:
                lats.append(dt)
                if c != expect[j]:
                    bad.append((j, c, expect[j]))

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(n_threads)
    ]
    t = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t
    s1 = server.scheduler.snapshot()
    # metrics + trace snapshot: the bench JSON carries what /metrics and
    # /debug/traces saw for this leg, so a regression in the BENCH_*
    # trajectory comes with its own attribution data
    snapshot = _serve_observability_snapshot(f"http://{host}:{port}")
    server.shutdown()
    # stop the worker threads too: their cv poll would perturb the
    # timing-sensitive legs that follow in all-mode
    server.scheduler.shutdown(timeout=2.0)
    assert not bad, f"fused counts diverged from serial: {bad[:5]}"
    assert lats, "every serving request failed"
    queries = s1["queries"] - s0["queries"]
    launches = s1["launches"] - s0["launches"]
    lats.sort()
    out = {
        "serve_n": n,
        "serve_threads": n_threads,
        "serve_requests": len(lats),
        "serve_qps": round(len(lats) / wall, 1),
        "serve_p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
        "serve_p99_ms": round(
            lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3, 2
        ),
        "serve_queries": queries,
        "serve_launches": launches,
        "serve_fusion_factor": (
            round(queries / launches, 2) if launches else None
        ),
        "serve_rejected": s1["rejected"] - s0["rejected"],
        "serve_expired": s1["expired"] - s0["expired"],
    }
    out.update(snapshot)
    log(
        "serving: %.0f req/s p50=%.1fms p99=%.1fms fusion=%.2f "
        "(%d queries / %d launches)"
        % (out["serve_qps"], out["serve_p50_ms"], out["serve_p99_ms"],
           out["serve_fusion_factor"] or 1.0, queries, launches)
    )
    return out


def bench_results(args) -> dict:
    """``--mode results``: the Arrow-native result plane (ISSUE 12).
    Serves the SAME ~100K-row resident result as GeoJSON, streamed
    Arrow IPC and BIN track records, recording rows/s and bytes for
    each, then guards the tentpole claims: (1) the Arrow path beats
    GeoJSON rows/s by >= 5x (no per-feature Python on the hot path),
    (2) the Arrow stream round-trips BIT-IDENTICALLY to the served
    row set (every column, numpy array_equal on the decoded buffers),
    and (3) the BIN response is byte-identical to the DeviceIndex
    host-twin oracle. ``--smoke`` is the CI leg (fewer reps, same
    guards)."""
    import io as _io
    import urllib.request

    import jax
    import numpy as np

    from geomesa_tpu.arrow_io import read_feature_stream
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.memory import MemoryDataStore

    platform = jax.devices()[0].platform
    n = args.n or 100_000
    reps = 2 if args.smoke else max(args.iters, 2)
    log(f"platform={platform} results plane: {n:,}-row result, "
        f"geojson vs arrow vs bin x{reps}")
    ds = MemoryDataStore()
    ds.create_schema(
        "gdelt", "track:Integer,name:String,dtg:Date,*geom:Point:srid=4326"
    )
    rng = np.random.default_rng(11)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("gdelt", {
        "track": rng.integers(0, 512, n),
        "name": rng.choice(["alpha", "beta", "gamma", "delta"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }, fids=np.arange(n))
    server, _ = serve_background(ds, resident=True)
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=600) as r:
            return r.read()

    legs = {
        "geojson": "/features/gdelt",
        "arrow": "/features/gdelt?f=arrow",
        "bin": "/features/gdelt?f=bin&track=track",
    }
    out: dict = {"results_n": n}
    bodies: dict = {}
    for fmt, path in legs.items():
        bodies[fmt] = get(path)  # warmup: staging + compiles + dicts
        t = time.perf_counter()
        for _ in range(reps):
            get(path)
        dt = (time.perf_counter() - t) / reps
        out[f"results_{fmt}_rows_per_sec"] = round(n / dt, 1)
        out[f"results_{fmt}_bytes"] = len(bodies[fmt])
        out[f"results_{fmt}_ms"] = round(dt * 1e3, 2)
        log("results %-7s %12.0f rows/s  %8.1fms  %s bytes"
            % (fmt, n / dt, dt * 1e3, f"{len(bodies[fmt]):,}"))
    # guard 2: the Arrow stream round-trips bit-identically to the
    # served row set (the resident index's Z-sorted order)
    di = server.RequestHandlerClass._resident_cache["gdelt"]
    oracle = di.query("INCLUDE")
    decoded = list(read_feature_stream(_io.BytesIO(bodies["arrow"])))
    from geomesa_tpu.features.batch import FeatureBatch

    got = FeatureBatch.concat(decoded)
    assert len(got) == len(oracle) == n, (len(got), len(oracle), n)
    assert np.array_equal(
        got.fids, np.asarray([str(f) for f in oracle.fids])
    ), "arrow fids diverged"
    for name in oracle.sft.attribute_names:
        a, b = got.column(name), oracle.column(name)
        assert a.dtype == b.dtype and np.array_equal(a, b), (
            f"arrow column {name!r} not bit-identical"
        )
    # guard 3: the BIN response equals the host-twin oracle bytes
    assert bodies["bin"] == di.bin_export("INCLUDE", "track"), (
        "BIN response diverged from the DeviceIndex host twin"
    )
    # the device rider must agree bit-for-bit too (forced engine; on
    # all-CPU the serving default is the host twin, same bytes either way)
    from geomesa_tpu.conf import prop_override

    with prop_override("results.bin.engine", "device"):
        from geomesa_tpu.results import resident_bin

        assert resident_bin(di, "INCLUDE", "track") == bodies["bin"], (
            "device BIN rider diverged from the host twin"
        )
    server.shutdown()
    # guard 1: the regression cliff this mode exists for
    ratio = (
        out["results_arrow_rows_per_sec"]
        / out["results_geojson_rows_per_sec"]
    )
    out["results_arrow_vs_geojson"] = round(ratio, 2)
    assert ratio >= 5.0, (
        f"arrow path only {ratio:.1f}x geojson rows/s (need >= 5x)"
    )
    log(f"results: arrow beats geojson {ratio:.1f}x (guard >= 5x), "
        "round-trip bit-identical, BIN rider == host twin")
    return out


def bench_serve_chaos(args) -> dict:
    """``--mode serve --chaos-smoke``: the serve-path chaos smoke
    (ISSUE 7). Injects (1) a persistent device-launch failure — the
    resident count must degrade to the store rung with the SAME answer,
    the device breaker must open within the failure budget and half-open
    recover once the fault clears — and (2) a staging OOM on the store
    scan path — the batch-halving recovery must return the exact row
    set. Finishes with a draining shutdown and asserts the scheduler
    drained clean (no request lost, queue and running both zero). Fast
    and deterministic: the CI chaos step."""
    import urllib.request
    from urllib.parse import quote

    import numpy as np

    from geomesa_tpu import failpoints, metrics, resilience
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.sched import SchedConfig
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.memory import MemoryDataStore

    n = args.n or (1 << 14)
    resilience.reset()
    ds = MemoryDataStore()
    ds.create_schema("gdelt", "name:String,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(7)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("gdelt", {
        "name": rng.choice(["a", "b"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }, fids=np.arange(n))
    server, _ = serve_background(
        ds, resident=True,
        sched=SchedConfig(max_inflight=1, max_queue=64,
                          default_deadline_ms=None),
    )
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"

    def get(path):
        with urllib.request.urlopen(f"{base}{path}", timeout=120) as r:
            return r.status, dict(r.headers), json.loads(r.read())

    cql = quote("BBOX(geom, -10.0, 35.0, 30.0, 60.0)")
    count_path = f"/count/gdelt?cql={cql}"
    feat_path = f"/features/gdelt?cql={cql}&properties=name"
    _, _, doc = get(count_path)  # warm: stage + compile
    expect = doc["count"]
    _, _, doc = get(feat_path)
    expect_rows = len(doc["features"])
    log(f"chaos-smoke: n={n:,}, oracle count={expect}")

    # -- leg 1: persistent device-launch failure ----------------------
    with prop_override("resilience.retries", 0), \
            prop_override("resilience.breaker.failures", 1), \
            prop_override("resilience.breaker.cooldown.s", 0.3):
        with failpoints.failpoint_override("fail.device.launch", "raise"):
            st, hd, doc = get(count_path)
            assert st == 200 and doc["count"] == expect, (st, doc)
            assert "device-launch-failed" in hd.get("X-Degraded", ""), hd
            st, hd, doc = get(count_path)  # breaker open: skip the rung
            assert doc["count"] == expect
            assert "device-breaker-open" in hd.get("X-Degraded", ""), hd
            assert resilience.device_breaker().state == "open"
        time.sleep(0.35)  # cooldown: the half-open probe runs clean
        st, hd, doc = get(count_path)
        assert st == 200 and doc["count"] == expect
        assert "X-Degraded" not in hd, hd
        assert resilience.device_breaker().state == "closed"
    log("chaos-smoke: device-launch leg ok "
        "(degraded-correct, breaker open -> half-open -> closed)")

    # -- leg 2: staging OOM on the store scan path --------------------
    o0 = metrics.resilience_oom_recoveries.value()
    with failpoints.failpoint_override("fail.stage.oom", "raise:1"):
        st, hd, doc = get(feat_path)
    assert st == 200 and len(doc["features"]) == expect_rows
    ooms = int(metrics.resilience_oom_recoveries.value() - o0)
    assert ooms >= 1, "staging OOM never engaged the halving recovery"
    log(f"chaos-smoke: staging-OOM leg ok ({ooms} halvings, exact rows)")

    # -- leg 3: draining shutdown -------------------------------------
    st, _, doc = get("/readyz")
    assert st == 200 and doc["ready"]
    server.shutdown()  # draining: admission off, in-flight finished
    snap = server.scheduler.snapshot()
    assert snap["queue_depth"] == 0 and snap["running"] == 0, snap
    server.scheduler.shutdown(timeout=2.0)
    log("chaos-smoke: drained clean (queue 0, running 0)")
    return {
        "serve_chaos_n": n,
        "serve_chaos_count": expect,
        "serve_chaos_oom_recoveries": ooms,
        "serve_chaos_breaker_opens":
            resilience.device_breaker().snapshot()["opens"],
        "serve_chaos_ok": True,
    }


def bench_slo_smoke(args) -> dict:
    """``--mode serve --slo-smoke``: the SLO engine / flight recorder
    CI smoke (ISSUE 9 acceptance demo). Three legs against a live
    resident+scheduled server over an FS store:

    - **fault-free**: a healthy run must trip NOTHING — no burning SLO,
      no flight-recorder bundle;
    - **injected slow query**: a latency failpoint on the device launch
      breaches the fast window — ``/stats/slo`` shows the burn,
      ``/readyz`` reports the burning SLO as degraded detail (still
      200/ready), a ``/metrics`` latency exemplar resolves to a captured
      trace in ``/debug/traces``, and a ``burn-rate`` bundle lands under
      ``<root>/_flightrec``;
    - **injected launch fault**: a persistent device failure opens the
      breaker — the ``breaker-open`` bundle names the device domain and
      carries the compile-attribution table (the compile that ate the
      cold-start budget)."""
    import os
    import shutil
    import tempfile
    import urllib.request
    from urllib.parse import quote

    import numpy as np

    from geomesa_tpu import failpoints, ledger, resilience, slo
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.filter.ecql import parse_instant
    from geomesa_tpu.sched import SchedConfig
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.fs import FileSystemDataStore

    n = args.n or (1 << 13)
    tmp = tempfile.mkdtemp(prefix="geomesa_slo_smoke_")
    resilience.reset()
    slo.FLIGHTREC.reset()
    ledger.LEDGER.reset()
    try:
        ds = FileSystemDataStore(os.path.join(tmp, "s"))
        ds.create_schema(
            "gdelt", "name:String,dtg:Date,*geom:Point:srid=4326"
        )
        rng = np.random.default_rng(7)
        t0 = parse_instant("2020-01-01T00:00:00")
        ds.write("gdelt", {
            "name": rng.choice(["a", "b"], n),
            "dtg": t0 + rng.integers(0, 10**8, n),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)],
                axis=1,
            ),
        }, fids=np.arange(n))
        ds.flush("gdelt")
        with slo.fresh_engine():
            server, _ = serve_background(
                ds, resident=True,
                sched=SchedConfig(max_inflight=1, default_deadline_ms=None),
            )
            host, port = server.server_address[:2]
            base = f"http://{host}:{port}"

            def get(path):
                with urllib.request.urlopen(
                    f"{base}{path}", timeout=120
                ) as r:
                    return r.status, json.loads(r.read())

            cql = quote("BBOX(geom, -10.0, -10.0, 10.0, 10.0)")
            count_path = f"/count/gdelt?cql={cql}&loose=1"
            # warmup OUTSIDE slo accounting: the cold compile is leg 2's
            # attribution subject, not a fault-free-leg breach
            with prop_override("slo.enabled", False):
                get(count_path)

            # -- leg 0: fault-free must trip nothing ------------------
            for _ in range(5):
                st, _doc = get(count_path)
                assert st == 200
            _, doc = get("/stats/slo")
            assert not doc["slos"]["interactive"]["burning"], doc["slos"]
            _, ready = get("/readyz")
            assert ready["slo_burning"] == [], ready
            assert slo.FLIGHTREC.bundle_names() == [], (
                "fault-free serving must not write a flight bundle"
            )
            log("slo-smoke: fault-free leg ok (no burn, no bundle)")

            # -- leg 1: injected slow query trips the fast burn -------
            with prop_override("slo.interactive.threshold.ms", 20.0), \
                    prop_override("slo.flightrec.interval.s", 0.0), \
                    failpoints.failpoint_override(
                        "fail.device.launch", "sleep:60"
                    ):
                for _ in range(5):
                    st, _doc = get(count_path)
                    assert st == 200
                # the fold runs on the server thread after the response:
                # poll (inside the override scope) until all 5 landed
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    _, doc = get("/stats/slo")
                    if doc["slos"]["interactive"]["bad"] >= 5:
                        break
                    time.sleep(0.02)
            s = doc["slos"]["interactive"]
            assert s["bad"] >= 5 and s["burn"]["fast"]["rate"] > 1.0, s
            _, ready = get("/readyz")
            assert ready["ready"] and "interactive" in ready["slo_burning"]
            bundles = slo.FLIGHTREC.bundle_names()
            assert any(b.endswith("burn-rate") for b in bundles), bundles
            # the /metrics exemplar (OpenMetrics negotiation) resolves
            # to a captured trace
            req = urllib.request.Request(
                f"{base}/metrics",
                headers={"Accept": "application/openmetrics-text"},
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                text = r.read().decode()
            tids = {
                ln.split('trace_id="')[1].split('"')[0]
                for ln in text.splitlines()
                if ln.startswith("geomesa_slo_latency_seconds_bucket")
                and "trace_id=" in ln
            }
            resolved = 0
            for tid in tids:
                try:
                    st, tr = get(f"/debug/traces/{tid}")
                    resolved += int(tr.get("trace_id") == tid)
                except Exception:
                    pass
            assert resolved, f"no exemplar resolved to a trace: {tids}"
            log(
                f"slo-smoke: slow-query leg ok (burn "
                f"{s['burn']['fast']['rate']:.0f}, bundle + exemplar)"
            )

            # -- leg 2: breaker-open bundle names breaker + compile ---
            with prop_override("resilience.retries", 0), \
                    prop_override("resilience.breaker.failures", 1), \
                    prop_override("slo.flightrec.interval.s", 0.0), \
                    failpoints.failpoint_override(
                        "fail.device.launch", "raise"
                    ):
                st, doc = get(count_path)
                assert st == 200  # degraded to the store rung, correct
            bundles = slo.FLIGHTREC.bundle_names()
            bo = [b for b in bundles if b.endswith("breaker-open")]
            assert bo, bundles
            bdir = os.path.join(slo.FLIGHTREC.dir, bo[-1])
            with open(os.path.join(bdir, "reason.json")) as fh:
                reason = json.load(fh)
            assert reason["detail"]["domain"] == "device"
            with open(os.path.join(bdir, "breakers.json")) as fh:
                breakers = json.load(fh)
            assert breakers["device"]["state"] == "open"
            with open(os.path.join(bdir, "ledger.json")) as fh:
                led = json.load(fh)
            assert led["compile"]["by_signature"], (
                "the bundle must carry the compile-attribution table"
            )
            log("slo-smoke: breaker leg ok (bundle names device breaker "
                f"+ {led['compile']['compiles']} attributed compiles)")
            server.shutdown()
            server.scheduler.shutdown(timeout=2.0)
            return {
                "slo_smoke_n": n,
                "slo_smoke_burn_fast": s["burn"]["fast"]["rate"],
                "slo_smoke_bundles": len(slo.FLIGHTREC.bundle_names()),
                "slo_smoke_compiles_attributed":
                    led["compile"]["compiles"],
                "slo_smoke_ok": True,
            }
    finally:
        resilience.reset()
        slo.FLIGHTREC.reset()
        shutil.rmtree(tmp, ignore_errors=True)


def _serve_observability_snapshot(base: str) -> dict:
    """Scrape /metrics (the geomesa_* scalar series) and the newest
    /debug/traces entry from the serving leg's own server, for embedding
    in the bench JSON. Best-effort: an empty dict never fails the leg."""
    import urllib.request

    out: dict = {}
    try:
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        wanted = (
            "geomesa_sched_", "geomesa_traces_", "geomesa_slow_",
            "geomesa_queries_total",
        )
        met: dict = {}
        for line in text.splitlines():
            if line.startswith("#") or " " not in line:
                continue
            series, val = line.rsplit(" ", 1)
            if series.split("{")[0].startswith(wanted):
                try:
                    met[series] = float(val)
                except ValueError:
                    pass
        out["serve_metrics"] = met
        with urllib.request.urlopen(
            f"{base}/debug/traces?limit=1", timeout=30
        ) as r:
            traces = json.loads(r.read()).get("traces", [])
        if traces:
            with urllib.request.urlopen(
                f"{base}/debug/traces/{traces[0]['trace_id']}", timeout=30
            ) as r:
                out["serve_trace"] = json.loads(r.read())
        # windowed SLO percentiles + the compile-attribution split: the
        # bench JSON records not just how fast the leg went but where
        # the machine time WENT (device vs compile vs host I/O)
        with urllib.request.urlopen(f"{base}/stats/slo", timeout=30) as r:
            slo_doc = json.loads(r.read())
        out["serve_windowed"] = {
            key: {
                "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                "p999_ms": s["p999_ms"], "requests": s["requests"],
                "bad": s["bad"],
            }
            for key, s in slo_doc.get("series", {}).items()
        }
        out["serve_burn"] = {
            name: {
                "fast": s["burn"]["fast"]["rate"],
                "slow": s["burn"]["slow"]["rate"],
                "burning": s["burning"],
            }
            for name, s in slo_doc.get("slos", {}).items()
        }
        with urllib.request.urlopen(
            f"{base}/stats/ledger", timeout=30
        ) as r:
            led = json.loads(r.read())
        shapes = led.get("shapes", {})
        device_s = sum(
            a["cost"].get("device_seconds", 0.0) for a in shapes.values()
        )
        compile_s = sum(
            a["cost"].get("compile_seconds", 0.0) for a in shapes.values()
        )
        out["serve_cost_split"] = {
            "requests": led.get("requests", 0),
            "device_s": round(device_s, 4),
            "compile_s": round(compile_s, 4),
            "compile_pct_of_cost": round(
                compile_s / (device_s + compile_s) * 100, 2
            ) if (device_s + compile_s) > 0 else None,
            "compile_signatures": led.get("compile", {}).get(
                "by_signature", {}
            ),
        }
    except Exception as e:
        log(f"observability snapshot failed (non-fatal): {e!r}")
    return out


def bench_stream(args) -> dict:
    """``--mode stream``: sustained streaming ingest CONCURRENT with a
    serving load over the merged live layer (ISSUE 10). An appender
    POSTs batches to ``/append`` (honoring 429 Retry-After) while a
    query thread hammers ``/count`` and samples ``/stats/stream``;
    records append rows/s, serve qps and the live-layer state. Guards
    (always, ``--smoke`` is just the small-N variant):

    - **read amplification**: the sampled live-run count never exceeds
      ``wal.max.generations`` (backpressure, not unbounded growth);
    - **immediate visibility**: once the appender finishes, the very
      next ``/count`` equals seed + acked rows — no flush on the path;
    - **acked-row durability**: after a draining shutdown the store
      reopens (WAL replay) to exactly seed + acked rows.
    """
    import os
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from geomesa_tpu import resilience
    from geomesa_tpu.conf import prop_override, sys_prop
    from geomesa_tpu.sched import SchedConfig
    from geomesa_tpu.server import serve_background
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.stream import StreamingStore

    smoke = bool(args.smoke)
    seed_n = args.n or (1 << 12 if smoke else 1 << 15)
    batch_rows = 256 if smoke else 2048
    n_batches = 40 if smoke else 192
    resilience.reset()
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-stream-")
    root = os.path.join(tmp, "store")
    rng = np.random.default_rng(7)

    def mk(n, fid0):
        return {
            "val": rng.integers(0, 100, n),
            "dtg": rng.integers(0, 10**9, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)],
                axis=1,
            ),
        }, np.arange(fid0, fid0 + n)

    try:
        with prop_override("stream.memtable.rows", 4096 if smoke else 1 << 16), \
                prop_override("stream.run.rows", batch_rows):
            ds = FileSystemDataStore(root, partition_size=1 << 14)
            ds.create_schema(
                "gdelt", "val:Int,dtg:Date,*geom:Point:srid=4326"
            )
            cols, fids = mk(seed_n, 0)
            ds.write("gdelt", cols, fids=fids)
            ds.flush("gdelt")
            server, _ = serve_background(
                ds, resident=True, stream=True,
                sched=SchedConfig(max_queue=256,
                                  default_deadline_ms=None),
            )
            host, port = server.server_address[:2]
            base = f"http://{host}:{port}"

            def get(path):
                with urllib.request.urlopen(base + path, timeout=120) as r:
                    return json.loads(r.read())

            def post(doc):
                req = urllib.request.Request(
                    f"{base}/append/gdelt",
                    data=json.dumps(doc).encode(),
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=120) as r:
                    return r.status, json.loads(r.read())

            assert get("/count/gdelt")["count"] == seed_n  # warm resident
            max_gens = int(sys_prop("wal.max.generations"))
            max_runs_seen = [0]
            qps_n = [0]
            stop = threading.Event()
            errors: list = []

            def server_load():
                try:
                    while not stop.is_set():
                        get("/count/gdelt")
                        qps_n[0] += 1
                        st = get("/stats/stream")
                        t = st["types"].get("gdelt")
                        if t:
                            max_runs_seen[0] = max(
                                max_runs_seen[0], len(t["runs"])
                            )
                except Exception as e:  # pragma: no cover - fails the guard
                    errors.append(e)

            th = threading.Thread(target=server_load, daemon=True)
            th.start()
            acked = 0
            shed = 0
            fid0 = 10_000_000
            t0 = time.perf_counter()
            for i in range(n_batches):
                cols, fids = mk(batch_rows, fid0)
                doc = {
                    "columns": {
                        "val": cols["val"].tolist(),
                        "dtg": cols["dtg"].tolist(),
                        "geom": cols["geom"].tolist(),
                    },
                    "fids": fids.tolist(),
                }
                while True:
                    try:
                        status, out = post(doc)
                    except urllib.error.HTTPError as e:
                        if e.code == 429:  # backpressured: honor the hint
                            shed += 1
                            time.sleep(
                                min(float(e.headers.get(
                                    "Retry-After", 1)), 2.0)
                            )
                            continue
                        raise
                    assert out["acked"] == batch_rows, out
                    acked += batch_rows
                    fid0 += batch_rows
                    break
            append_s = time.perf_counter() - t0
            stop.set()
            th.join(timeout=10)
            assert not errors, errors[:1]
            # guard: bounded read amplification under sustained ingest
            assert max_runs_seen[0] <= max_gens, (
                f"live runs {max_runs_seen[0]} exceeded "
                f"wal.max.generations={max_gens}"
            )
            # guard: every acked row queryable with NO flush on the path
            total = get("/count/gdelt")["count"]
            assert total == seed_n + acked, (total, seed_n, acked)
            stream_doc = get("/stats/stream")
            server.shutdown()
        # guard: durability — reopen (WAL replay + watermark) and the
        # acked rows are all there, exactly once
        ds2 = FileSystemDataStore(root, partition_size=1 << 14)
        layer2 = StreamingStore(ds2)
        try:
            reopened = layer2.count("gdelt")
            assert reopened == seed_n + acked, (reopened, seed_n, acked)
        finally:
            layer2.close()
        rate = acked / append_s if append_s > 0 else 0.0
        log(
            f"stream: {acked:,} rows acked in {append_s:.2f}s "
            f"({rate:,.0f} rows/s) concurrent with {qps_n[0]} serving "
            f"reads; max live runs {max_runs_seen[0]}/{max_gens}, "
            f"{shed} backpressure sheds, "
            f"{int(stream_doc['counters']['compactions'])} compactions"
        )
        return {
            "stream_seed_rows": seed_n,
            "stream_acked_rows": acked,
            "stream_append_rows_per_sec": rate,
            "stream_serve_reads": qps_n[0],
            "stream_serve_qps": qps_n[0] / append_s if append_s else 0.0,
            "stream_max_live_runs": max_runs_seen[0],
            "stream_max_generations": max_gens,
            "stream_backpressure_sheds": shed,
            "stream_compactions": int(
                stream_doc["counters"]["compactions"]
            ),
            "stream_reopened_rows": seed_n + acked,
            "stream_ok": True,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


#: subprocess body for the stream chaos SIGKILL leg: append batches,
#: fsync an ack record per batch, then die at the armed WAL instant
_STREAM_CRASH_BODY = r"""
import os, sys
import numpy as np
from geomesa_tpu import failpoints
from geomesa_tpu.conf import set_prop
from geomesa_tpu.store.fs import FileSystemDataStore
from geomesa_tpu.store.stream import StreamingStore

root, acked_path = sys.argv[1], sys.argv[2]
set_prop("stream.run.rows", 64)
set_prop("stream.memtable.rows", 1 << 20)
set_prop("wal.max.generations", 64)
ds = FileSystemDataStore(root, partition_size=1 << 12)
layer = StreamingStore(ds)
fh = open(acked_path, "a")
rng = np.random.default_rng(11)
for i in range(3):
    n = 64
    layer.append("gdelt", {
        "val": rng.integers(0, 100, n),
        "dtg": rng.integers(0, 10**9, n),
        "geom": np.stack([rng.uniform(-180, 180, n),
                          rng.uniform(-90, 90, n)], axis=1),
    }, fids=np.arange(5_000_000 + i * 100, 5_000_000 + i * 100 + n))
    fh.write(f"{i}\n"); fh.flush(); os.fsync(fh.fileno())
failpoints.set_failpoint("fail.wal.append", "kill")
n = 64
layer.append("gdelt", {
    "val": rng.integers(0, 100, n),
    "dtg": rng.integers(0, 10**9, n),
    "geom": np.stack([rng.uniform(-180, 180, n),
                      rng.uniform(-90, 90, n)], axis=1),
}, fids=np.arange(6_000_000, 6_000_000 + n))
os._exit(42)  # unreachable: the failpoint kills
"""


def bench_stream_chaos(args) -> dict:
    """``--mode stream --chaos-smoke``: the streaming-ingest chaos
    smoke, mirroring the PR 7 serve chaos step. Legs:

    1. transient WAL faults ride the ``wal``-domain retry budget (the
       append still acks, rows still serve);
    2. a persistent WAL fault opens the ``wal`` breaker — appends fail
       fast 503 (no ack against a dead log) and recover after cooldown;
    3. a compaction that publishes but fails before WAL truncation
       neither loses nor re-applies rows across a reopen (watermark);
    4. a REAL SIGKILL mid-append in a subprocess: the reopened store
       serves exactly the acked rows.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    import numpy as np

    from geomesa_tpu import failpoints, resilience
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.stream import (
        StreamingStore,
        WalUnavailableError,
    )

    resilience.reset()
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-streamchaos-")
    root = os.path.join(tmp, "store")
    rng = np.random.default_rng(3)

    def mk(n, fid0):
        return {
            "val": rng.integers(0, 100, n),
            "dtg": rng.integers(0, 10**9, n),
            "geom": np.stack(
                [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)],
                axis=1,
            ),
        }, np.arange(fid0, fid0 + n)

    try:
        with prop_override("stream.memtable.rows", 1 << 20):
            ds = FileSystemDataStore(root, partition_size=1 << 12)
            ds.create_schema(
                "gdelt", "val:Int,dtg:Date,*geom:Point:srid=4326"
            )
            cols, fids = mk(1024, 0)
            ds.write("gdelt", cols, fids=fids)
            ds.flush("gdelt")
            layer = StreamingStore(ds)
            total = 1024

            # -- leg 1: transient WAL faults retry and still ack ------
            with failpoints.failpoint_override("fail.wal.append", "raise:2"):
                cols, fids = mk(64, 1_000_000)
                layer.append("gdelt", cols, fids=fids)
                total += 64
            assert layer.count("gdelt") == total
            log("stream-chaos: transient-WAL leg ok (retried, acked, "
                "served)")

            # -- leg 2: persistent WAL fault opens the wal breaker ----
            with prop_override("resilience.retries", 0), \
                    prop_override("resilience.breaker.failures", 1), \
                    prop_override("resilience.breaker.cooldown.s", 0.3):
                with failpoints.failpoint_override(
                    "fail.wal.append", "raise"
                ):
                    try:
                        cols, fids = mk(64, 1_100_000)
                        layer.append("gdelt", cols, fids=fids)
                        raise AssertionError("append acked against a "
                                             "failing WAL")
                    except OSError:
                        pass  # the injected fault, retries exhausted
                    assert resilience.wal_breaker().state == "open"
                    try:
                        cols, fids = mk(64, 1_200_000)
                        layer.append("gdelt", cols, fids=fids)
                        raise AssertionError("append acked through an "
                                             "open wal breaker")
                    except WalUnavailableError:
                        pass  # fail-fast: no ack against a dead log
                assert layer.count("gdelt") == total  # nothing phantom
                time.sleep(0.35)  # cooldown: half-open probe
                cols, fids = mk(64, 1_300_000)
                layer.append("gdelt", cols, fids=fids)
                total += 64
                assert resilience.wal_breaker().state == "closed"
            assert layer.count("gdelt") == total
            log("stream-chaos: wal-breaker leg ok (fail-fast 503, "
                "half-open recovery)")

            # -- leg 3: publish-then-fail compaction, watermark skip --
            from geomesa_tpu.failpoints import FailpointError

            with failpoints.failpoint_override(
                "fail.compact.publish", "raise"
            ):
                try:
                    layer.compact_now("gdelt")
                    raise AssertionError("failpoint did not fire")
                except FailpointError:
                    pass
            assert layer.count("gdelt") == total
            layer.close()
            ds2 = FileSystemDataStore(root, partition_size=1 << 12)
            layer2 = StreamingStore(ds2)
            assert layer2.count("gdelt") == total, (
                "watermark failed: rows lost or re-applied across reopen"
            )
            layer2.close()
            log("stream-chaos: compact-publish leg ok (no loss, no "
                "double-apply across reopen)")

            # -- leg 4: real SIGKILL mid-append in a subprocess -------
            acked_path = os.path.join(tmp, "acked.txt")
            env = dict(os.environ)
            env.setdefault("JAX_PLATFORMS", "cpu")
            p = subprocess.run(
                [sys.executable, "-c", _STREAM_CRASH_BODY, root,
                 acked_path],
                env=env, timeout=240,
            )
            assert p.returncode == -signal.SIGKILL, p.returncode
            with open(acked_path) as fh:
                acked = [int(x) for x in fh.read().split()]
            expected = total + len(acked) * 64
            ds3 = FileSystemDataStore(root, partition_size=1 << 12)
            layer3 = StreamingStore(ds3)
            got = layer3.query("gdelt").batch
            assert len(got) == len({str(f) for f in got.fids}), (
                "rows double-applied after crash"
            )
            assert layer3.count("gdelt") == expected, (
                layer3.count("gdelt"), expected
            )
            assert ds3.verify_chunk_stats("gdelt") == []
            layer3.close()
            log(f"stream-chaos: SIGKILL leg ok ({len(acked)} acked "
                "batches served exactly after reopen)")
        return {
            "stream_chaos_rows": expected,
            "stream_chaos_acked_batches": len(acked),
            "stream_chaos_ok": True,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


#: subprocess body for one replicated serving node: bind (with a short
#: EADDRINUSE retry so a drained predecessor can finish closing), write
#: the bound port via tmp+rename, serve until drained, free the port,
#: exit 0 — the exact lifecycle ``fleet restart`` orchestrates
_REPLICA_NODE_BODY = r"""
import os, sys, time
from geomesa_tpu.conf import set_prop
from geomesa_tpu.replica import ReplicaConfig
from geomesa_tpu.server import serve_background
from geomesa_tpu.store.fs import FileSystemDataStore

root, portfile, port, role, leader = sys.argv[1:6]
lease_s, poll_ms, failover_s, peers = sys.argv[6:10]
set_prop("replica.lease.s", float(lease_s))
set_prop("replica.poll.ms", float(poll_ms))
set_prop("replica.failover.s", float(failover_s))
# the chaos smoke's zero-acked-row-loss assertion is only sound when
# acks wait for a follower apply: with local acks a SIGKILLed leader
# legally takes acked-but-unshipped rows down with it
set_prop("replica.ack", "replica")
set_prop("stream.memtable.rows", 1 << 20)
deadline = time.monotonic() + 15
while True:
    try:
        server, thread = serve_background(
            FileSystemDataStore(root, partition_size=1 << 12),
            port=int(port), stream=True,
            replica=ReplicaConfig(
                role=role, leader_url=leader,
                peers=tuple(p for p in peers.split(",") if p),
            ),
        )
        break
    except OSError:
        if time.monotonic() > deadline:
            raise
        time.sleep(0.2)  # predecessor still releasing the port
with open(portfile + ".tmp", "w") as fh:
    fh.write(str(server.server_address[1]))
    fh.flush(); os.fsync(fh.fileno())
os.replace(portfile + ".tmp", portfile)
thread.join()  # returns when a drain (POST /admin/shutdown) completes
server.server_close()  # a restarted successor needs the port
os._exit(0)
"""


def bench_replica_chaos(args) -> dict:
    """``--mode replica --chaos-smoke``: the replicated-tier chaos
    smoke guarding the ISSUE 14 acceptance criteria. Legs:

    1. **Leader SIGKILL under load.** Three node subprocesses (leader +
       2 WAL-shipping followers) behind an in-process router; reader
       threads and an appender run through the router while the leader
       is SIGKILLed. Asserts ZERO failed reads across the whole window,
       promotion within the conf-declared ``replica.failover.s`` bound,
       and post-failover counts bit-identical across survivors and
       exactly seed ∪ acked appends (modulo the one in-flight batch the
       kill raced — the same ambiguity a crashed single node has).
    2. **Rolling restart under load.** The killed node rejoins as a
       follower, then ``fleet.rolling_restart`` cycles the whole group
       while the load keeps running: zero failed reads, append shedding
       bounded (every non-acked attempt is a 503 shed, never an error),
       counts re-verified bit-identical after every step, and the new
       leader's ``/stats/ledger`` snapshot recording the ship traffic.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from geomesa_tpu import resilience
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.router import route_background
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.tools import fleet

    resilience.reset()
    LEASE_S, POLL_MS, FAILOVER_S = 1.5, 30.0, 10.0
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-replicachaos-")
    rng = np.random.default_rng(7)
    seed_n = 2048

    def _get(url, path, timeout=30):
        with urllib.request.urlopen(url + path, timeout=timeout) as r:
            return json.loads(r.read())

    def _append(url, fids):
        n = len(fids)
        doc = {
            "columns": {
                "val": list(range(n)),
                "dtg": [1000 + i for i in range(n)],
                "geom": [[10.0, 10.0]] * n,
            },
            "fids": list(fids),
        }
        req = urllib.request.Request(
            url + "/append/gdelt", data=json.dumps(doc).encode(),
            method="POST", headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs: dict = {}  # url -> Popen
    ports: dict = {}  # url -> port

    def spawn(root, port, role, leader_url, peers=""):
        portfile = os.path.join(
            tmp, f"port-{os.path.basename(root)}-{time.monotonic_ns()}"
        )
        p = subprocess.Popen(
            [sys.executable, "-c", _REPLICA_NODE_BODY, root, portfile,
             str(port), role, leader_url, str(LEASE_S), str(POLL_MS),
             str(FAILOVER_S), peers],
            env=env,
        )
        deadline = time.monotonic() + 120
        while not os.path.exists(portfile):
            assert p.poll() is None, f"node {root} died during startup"
            assert time.monotonic() < deadline, f"node {root} never bound"
            time.sleep(0.05)
        bound = int(open(portfile).read())
        url = f"http://127.0.0.1:{bound}"
        procs[url] = p
        ports[url] = bound
        return url

    try:
        roots = {}
        r0 = os.path.join(tmp, "n0")
        ds = FileSystemDataStore(r0, partition_size=1 << 12)
        ds.create_schema("gdelt", "val:Int,dtg:Date,*geom:Point:srid=4326")
        ds.write("gdelt", {
            "val": rng.integers(0, 100, seed_n),
            "dtg": rng.integers(0, 10**9, seed_n),
            "geom": np.stack([rng.uniform(-180, 180, seed_n),
                              rng.uniform(-90, 90, seed_n)], axis=1),
        }, fids=np.arange(seed_n))
        ds.flush("gdelt")
        del ds
        for i in (1, 2):
            shutil.copytree(r0, os.path.join(tmp, f"n{i}"))

        # pre-allocate the three ports so every node can be told the
        # FULL peer list up front — the election electorate (a follower
        # with empty peers can only elect itself: split brain)
        import socket as _socket

        fixed_ports = []
        socks = []
        for _ in range(3):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            fixed_ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        node_urls = [f"http://127.0.0.1:{p}" for p in fixed_ports]
        peers_arg = ",".join(node_urls)
        lurl = spawn(r0, fixed_ports[0], "leader", "", peers_arg)
        furls = [
            spawn(os.path.join(tmp, f"n{i}"), fixed_ports[i], "follower",
                  lurl, peers_arg)
            for i in (1, 2)
        ]
        assert [lurl] + furls == node_urls
        urls = [lurl] + furls
        for u, root in zip(urls, (r0, os.path.join(tmp, "n1"),
                                  os.path.join(tmp, "n2"))):
            roots[u] = root

        with prop_override("router.health.ms", 100.0):
            rsrv, _ = route_background(urls)
            rbase = "http://%s:%s" % rsrv.server_address[:2]
            fleet.verify_converged(urls, timeout_s=60)
            log(f"replica-chaos: 3-node group converged at {seed_n} rows; "
                f"router {rbase}")

            # -- concurrent load: readers + appender through the router
            read_failures: list = []
            reads = [0]
            stop = threading.Event()

            def reader():
                while not stop.is_set():
                    try:
                        _get(rbase, "/count/gdelt", timeout=10)
                        reads[0] += 1
                    except Exception as e:
                        read_failures.append(repr(e))
                    time.sleep(0.01)

            acked: set = set()
            inflight: set = set()
            sheds = [0]
            append_errors: list = []
            fid_next = [5_000_000]

            def append_one(batch=16):
                fids = list(range(fid_next[0], fid_next[0] + batch))
                fid_next[0] += batch
                inflight.update(fids)
                try:
                    out = _append(rbase, fids)
                    if out.get("acked") and out.get("replicated", True):
                        acked.update(fids)
                        inflight.difference_update(fids)
                    # acked but NOT replicated (follower lag at the ack
                    # timeout): durable on the leader only — stays in
                    # the ambiguous in-flight set, exactly like a batch
                    # the kill raced
                except urllib.error.HTTPError as e:
                    try:
                        body = e.read().decode("utf-8", "replace")
                    except Exception:
                        body = ""
                    e.close()
                    if e.code == 503:
                        sheds[0] += 1  # bounded shed, not an error
                        if "unknown" not in body:
                            # plain shed: the router never forwarded it.
                            # "outcome unknown" (transport died mid-send)
                            # stays in-flight — the dying leader may have
                            # made it durable and shipped it
                            inflight.difference_update(fids)
                    else:
                        append_errors.append(e.code)
                except Exception as e:
                    append_errors.append(repr(e))

            readers = [threading.Thread(target=reader) for _ in range(2)]
            for t in readers:
                t.start()
            for _ in range(15):
                append_one()
                time.sleep(0.02)
            assert len(acked) > 0, "no appends acked before the kill"

            # -- leg 1: SIGKILL the leader under the running load ------
            killer = threading.Timer(
                0.005, lambda: procs[lurl].send_signal(signal.SIGKILL)
            )
            t_kill = time.monotonic()
            killer.start()
            append_one()  # races the kill: ack outcome may be unknown
            procs[lurl].wait(60)
            new_leader = fleet.wait_leader(furls, timeout_s=FAILOVER_S + 5)
            promote_s = time.monotonic() - t_kill
            assert promote_s <= FAILOVER_S, (
                f"promotion took {promote_s:.2f}s, past the declared "
                f"replica.failover.s={FAILOVER_S}"
            )
            # keep the load running across the promotion, then settle
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline:
                append_one()
                time.sleep(0.05)
            stop.set()
            for t in readers:
                t.join(10)
            assert read_failures == [], (
                f"{len(read_failures)} failed reads during failover "
                f"(first: {read_failures[0]})"
            )
            assert append_errors == [], (
                f"append errors (not sheds) during failover: "
                f"{append_errors[:5]}"
            )
            counts = fleet.verify_converged(furls, timeout_s=60)
            feats = _get(
                new_leader,
                "/features/gdelt?cql=INCLUDE&maxFeatures=1000000",
                timeout=60,
            )
            got = {int(f["id"]) for f in feats["features"]}
            expected_floor = set(range(seed_n)) | acked
            assert expected_floor <= got, (
                f"lost {len(expected_floor - got)} acked rows"
            )
            assert got <= expected_floor | inflight, (
                f"{len(got - expected_floor - inflight)} phantom rows"
            )
            assert counts["gdelt"] == len(got), "double-applied rows"
            log(f"replica-chaos: SIGKILL leg ok (promotion {promote_s:.2f}s"
                f" <= {FAILOVER_S}s, {reads[0]} reads 0 failed, "
                f"{len(acked)} acked rows all served, {sheds[0]} sheds)")

            # -- leg 2: rolling restart under the same load ------------
            spawn_root = roots.pop(lurl)
            del procs[lurl]

            def restart(url, role, leader_url):
                old = procs.pop(url, None)
                if old is not None:
                    old.wait(30)  # the drain exits the process
                port = ports[url]
                root = roots.get(url, spawn_root)
                u2 = spawn(root, port, role, leader_url, peers_arg)
                assert u2 == url, (u2, url)

            # the killed ex-leader rejoins as a follower of its successor
            rejoin = spawn(spawn_root, ports[lurl], "follower", new_leader,
                           peers_arg)
            assert rejoin == lurl
            roots[lurl] = spawn_root
            fleet.wait_ready(lurl, timeout_s=60)
            fleet.wait_caught_up(lurl, timeout_s=60)
            stop.clear()
            read_failures.clear()
            append_errors.clear()
            sheds[0] = 0
            readers = [threading.Thread(target=reader) for _ in range(2)]
            for t in readers:
                t.start()
            appending = threading.Event()
            appending.set()

            def append_loop():
                while appending.is_set():
                    append_one(batch=4)
                    time.sleep(0.05)

            at = threading.Thread(target=append_loop)
            at.start()
            try:
                report = fleet.rolling_restart(
                    urls, restart, timeout_s=90.0, log=log,
                )
            finally:
                appending.clear()
                at.join(10)
                stop.set()
                for t in readers:
                    t.join(10)
            assert read_failures == [], (
                f"{len(read_failures)} failed reads during the rolling "
                f"restart (first: {read_failures[0]})"
            )
            assert append_errors == [], (
                f"append errors (not sheds) during the rolling restart: "
                f"{append_errors[:5]}"
            )
            final_leader = fleet.wait_leader(urls, timeout_s=30)
            ledger_doc = _get(final_leader, "/stats/ledger", timeout=30)
            assert "wal-ship" in json.dumps(ledger_doc), (
                "leader ledger snapshot records no replication ship cost"
            )
            log(f"replica-chaos: rolling-restart leg ok "
                f"({len(report['steps'])} cycles, counts "
                f"{report['final_counts']}, {sheds[0]} bounded sheds, "
                f"0 failed reads)")
            rsrv.shutdown()
            rsrv.server_close()
        return {
            "replica_chaos_seed_rows": seed_n,
            "replica_chaos_promotion_s": round(promote_s, 3),
            "replica_chaos_failover_bound_s": FAILOVER_S,
            "replica_chaos_acked_rows": len(acked),
            "replica_chaos_rows_served": len(got),
            "replica_chaos_restart_steps": len(report["steps"]),
            "replica_chaos_restart_wall_s": report["wall_s"],
            "replica_chaos_sheds": sheds[0],
            "replica_chaos_ok": True,
        }
    finally:
        for p in procs.values():
            try:
                p.kill()
                p.wait(10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


#: subprocess body for one soak-fleet node: the replica node lifecycle
#: with the soak knobs dialed for fault density — tiny WAL segments and
#: a small memtable (compaction races every snapshot stream), a short
#: follower-retention window (a node held down past it earns the 410
#: snapshot-reprovision cliff on purpose), and the reprovision bound
_SOAK_NODE_BODY = r"""
import os, sys, time
from geomesa_tpu.conf import set_prop
from geomesa_tpu.replica import ReplicaConfig
from geomesa_tpu.server import serve_background
from geomesa_tpu.store.fs import FileSystemDataStore

root, portfile, port, role, leader = sys.argv[1:6]
lease_s, poll_ms, failover_s, peers, retain_s = sys.argv[6:11]
set_prop("replica.lease.s", float(lease_s))
set_prop("replica.poll.ms", float(poll_ms))
set_prop("replica.failover.s", float(failover_s))
set_prop("replica.retain.s", float(retain_s))
set_prop("replica.reprovision.s", 30.0)
set_prop("replica.ack", "replica")
# fault density: rotate segments constantly, compact constantly (every
# snapshot stream races a compaction), keep the pin TTL comfortably
# above one stream so only a DEAD stream's pin could ever age out
set_prop("wal.segment.bytes", 4096)
set_prop("stream.memtable.rows", 256)
set_prop("snapshot.pin.ttl.s", 60.0)
set_prop("sub.heartbeat.s", 0.5)
deadline = time.monotonic() + 15
while True:
    try:
        server, thread = serve_background(
            FileSystemDataStore(root, partition_size=1 << 12),
            port=int(port), stream=True,
            replica=ReplicaConfig(
                role=role, leader_url=leader,
                peers=tuple(p for p in peers.split(",") if p),
            ),
        )
        break
    except OSError:
        if time.monotonic() > deadline:
            raise
        time.sleep(0.2)
with open(portfile + ".tmp", "w") as fh:
    fh.write(str(server.server_address[1]))
    fh.flush(); os.fsync(fh.fileno())
os.replace(portfile + ".tmp", portfile)
thread.join()
server.server_close()
os._exit(0)
"""


def bench_soak(args) -> dict:
    """``--mode soak``: the randomized self-healing soak (ISSUE 15).
    A 3-node replica group behind the router takes a SEEDED random
    fault schedule while readers and an appender run through the
    router the whole time:

    - ``kill-follower`` / ``kill-leader`` — SIGKILL + rejoin (the
      leader kill exercises election + the ex-leader's follower rejoin)
    - ``corrupt-wal`` — a killed follower's newest WAL segment gets a
      torn garbage tail before restart (recovery truncates, tailing
      heals the lost suffix)
    - ``diverge`` — a killed follower's WAL grows records the leader
      never assigned (a forked tail); on restart the tail loop detects
      local-ahead-of-leader and self-heals via snapshot reprovision
    - ``gap-410`` — a follower held down past ``replica.retain.s``
      while the leader keeps compacting returns to a WAL that was
      GC'd past its position: the honest 410 answer, healed by
      snapshot reprovision

    Every node runs with ``fail.snapshot.stream=raise:2`` armed, so
    the first snapshot streams truncate mid-ship and the per-file
    resume path (``?id=&from_file=``) is exercised under compaction.
    Asserts ZERO failed reads, zero append errors (sheds are bounded
    503s, never errors), at least one completed snapshot reprovision
    per self-heal round, lag back to 0 after every round, exactly one
    leader at the end (no fork), bit-identical converged counts, and
    acked ⊆ served ⊆ acked ∪ in-flight (zero acked-row loss, zero
    phantom rows). ``--smoke`` runs one round of each fault kind (CI);
    the full mode runs a longer schedule. ``--seed`` fixes the
    schedule for reproduction."""
    import os
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    from geomesa_tpu import resilience
    from geomesa_tpu.conf import prop_override
    from geomesa_tpu.router import route_background
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.wal import WriteAheadLog
    from geomesa_tpu.tools import fleet

    resilience.reset()
    LEASE_S, POLL_MS, FAILOVER_S, RETAIN_S = 1.5, 25.0, 12.0, 1.0
    seed = getattr(args, "seed", None) or 20260805
    rng = np.random.default_rng(seed)
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-soak-")
    seed_n = 1024

    def _get(url, path, timeout=30):
        with urllib.request.urlopen(url + path, timeout=timeout) as r:
            return json.loads(r.read())

    def _append(url, fids):
        n = len(fids)
        doc = {
            "columns": {
                "val": list(range(n)),
                "dtg": [1000 + i for i in range(n)],
                "geom": [[10.0, 10.0]] * n,
            },
            "fids": list(fids),
        }
        req = urllib.request.Request(
            url + "/append/gdelt", data=json.dumps(doc).encode(),
            method="POST", headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # transient snapshot-stream truncation on every node: the resume
    # path runs under real compaction instead of only when a kill
    # happens to land mid-stream
    env["GEOMESA_TPU_FAILPOINTS"] = "fail.snapshot.stream=raise:2"
    procs: dict = {}
    ports: dict = {}
    roots: dict = {}

    def spawn(root, port, role, leader_url, peers=""):
        portfile = os.path.join(
            tmp, f"port-{os.path.basename(root)}-{time.monotonic_ns()}"
        )
        p = subprocess.Popen(
            [sys.executable, "-c", _SOAK_NODE_BODY, root, portfile,
             str(port), role, leader_url, str(LEASE_S), str(POLL_MS),
             str(FAILOVER_S), peers, str(RETAIN_S)],
            env=env,
        )
        deadline = time.monotonic() + 120
        while not os.path.exists(portfile):
            assert p.poll() is None, f"node {root} died during startup"
            assert time.monotonic() < deadline, f"node {root} never bound"
            time.sleep(0.05)
        url = f"http://127.0.0.1:{int(open(portfile).read())}"
        procs[url] = p
        ports[url] = int(url.rsplit(":", 1)[1])
        roots[url] = root
        return url

    def _stats(url, timeout=5):
        return _get(url, "/stats/replica", timeout=timeout)

    def _wait(pred, timeout_s, msg):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if pred():
                    return
            except Exception:
                pass
            time.sleep(0.1)
        raise AssertionError(f"soak: timed out waiting for {msg}")

    try:
        r0 = os.path.join(tmp, "n0")
        ds = FileSystemDataStore(r0, partition_size=1 << 12)
        ds.create_schema("gdelt", "val:Int,dtg:Date,*geom:Point:srid=4326")
        ds.write("gdelt", {
            "val": rng.integers(0, 100, seed_n),
            "dtg": rng.integers(0, 10**9, seed_n),
            "geom": np.stack([rng.uniform(-180, 180, seed_n),
                              rng.uniform(-90, 90, seed_n)], axis=1),
        }, fids=np.arange(seed_n))
        ds.flush("gdelt")
        del ds
        for i in (1, 2):
            shutil.copytree(r0, os.path.join(tmp, f"n{i}"))

        import socket as _socket

        fixed_ports, socks = [], []
        for _ in range(3):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            fixed_ports.append(s.getsockname()[1])
            socks.append(s)
        for s in socks:
            s.close()
        node_urls = [f"http://127.0.0.1:{p}" for p in fixed_ports]
        peers_arg = ",".join(node_urls)
        lurl = spawn(r0, fixed_ports[0], "leader", "", peers_arg)
        for i in (1, 2):
            spawn(os.path.join(tmp, f"n{i}"), fixed_ports[i],
                  "follower", lurl, peers_arg)
        urls = list(node_urls)

        with prop_override("router.health.ms", 100.0):
            rsrv, _ = route_background(urls)
            rbase = "http://%s:%s" % rsrv.server_address[:2]
            fleet.verify_converged(urls, timeout_s=60)

            read_failures: list = []
            reads = [0]
            stop = threading.Event()

            def reader():
                # idempotent GETs get ONE immediate retry: a SIGKILL
                # can truncate a body the router already started
                # relaying (headers sent -- nothing upstream can retry
                # that), which a fresh request heals instantly. Only a
                # read that fails TWICE in a row counts: that is a real
                # unroutable window, not the kill instant itself.
                while not stop.is_set():
                    for attempt in (0, 1):
                        try:
                            _get(rbase, "/count/gdelt", timeout=10)
                            reads[0] += 1
                            break
                        except Exception as e:
                            if attempt:
                                read_failures.append(repr(e))
                    time.sleep(0.01)

            acked: set = set()
            acked_seqs: set = set()
            inflight: set = set()
            sheds = [0]
            append_errors: list = []
            fid_next = [5_000_000]

            def append_one(batch=8):
                fids = list(range(fid_next[0], fid_next[0] + batch))
                fid_next[0] += batch
                inflight.update(fids)
                try:
                    out = _append(rbase, fids)
                    if out.get("acked") and out.get("replicated", True):
                        acked.update(fids)
                        inflight.difference_update(fids)
                        if out.get("seq") is not None:
                            acked_seqs.add(int(out["seq"]))
                except urllib.error.HTTPError as e:
                    try:
                        body = e.read().decode("utf-8", "replace")
                    except Exception:
                        body = ""
                    e.close()
                    if e.code == 503:
                        sheds[0] += 1
                        if "unknown" not in body:
                            inflight.difference_update(fids)
                    else:
                        append_errors.append(e.code)
                except Exception as e:
                    append_errors.append(repr(e))

            appending = threading.Event()
            appending.set()

            def append_loop():
                while not stop.is_set():
                    if appending.is_set():
                        append_one()
                    time.sleep(0.04)

            threads = [threading.Thread(target=reader) for _ in range(2)]
            threads.append(threading.Thread(target=append_loop))
            for t in threads:
                t.start()

            kinds = ["kill-follower", "kill-leader", "corrupt-wal",
                     "diverge", "gap-410"]
            if getattr(args, "smoke", False):
                schedule = [str(k) for k in rng.permutation(kinds)]
            else:
                schedule = [str(k) for k in rng.permutation(kinds)]
                schedule += [str(k) for k in rng.choice(kinds, size=5)]
            reprovisions = 0
            log(f"soak: schedule (seed {seed}): {schedule}")

            def current_roles():
                lead, followers = None, []
                for u in urls:
                    try:
                        doc = _stats(u)
                    except Exception:
                        continue
                    if doc.get("role") == "leader":
                        lead = u
                    else:
                        followers.append(u)
                return lead, followers

            # the pubsub leg: ONE standing subscriber rides the whole
            # fault schedule, reconnecting from its acked cursor after
            # every kill — at the end every quorum-acked append seq
            # must have been delivered exactly once (zero missed, zero
            # duplicate across however many promotions happened)
            sub_delivered: set = set()
            sub_dup = [0]
            sub_cursor = [-1]
            sub_stop = threading.Event()
            sub_state: dict = {"id": None}

            def subscriber():
                while not sub_stop.is_set():
                    try:
                        lead, _f = current_roles()
                        if lead is None:
                            time.sleep(0.2)
                            continue
                        if sub_state["id"] is None:
                            req = urllib.request.Request(
                                lead + "/subscribe/gdelt?tenant=soaksub",
                                data=json.dumps(
                                    {"bbox": [-180.0, -90.0, 180.0, 90.0]}
                                ).encode(),
                                method="POST",
                                headers={"Content-Type": "application/json"},
                            )
                            with urllib.request.urlopen(req, timeout=10) as r:
                                sub_state["id"] = json.loads(r.read())["id"]
                        u = (lead + "/subscribe/gdelt?id=" + sub_state["id"]
                             + "&from=" + str(sub_cursor[0]))
                        with urllib.request.urlopen(u, timeout=10) as resp:
                            buf = b""
                            while not sub_stop.is_set():
                                chunk = resp.read1(65536)
                                if not chunk:
                                    break
                                buf += chunk
                                while b"\n\n" in buf:
                                    frame, buf = buf.split(b"\n\n", 1)
                                    if b"event: match" not in frame:
                                        continue
                                    for ln in frame.split(b"\n"):
                                        if ln.startswith(b"id: "):
                                            sq = int(ln[4:])
                                            if sq <= sub_cursor[0]:
                                                sub_dup[0] += 1
                                            else:
                                                sub_cursor[0] = sq
                                            sub_delivered.add(sq)
                    except Exception:
                        time.sleep(0.2)

            sub_thread = threading.Thread(target=subscriber, daemon=True)
            sub_thread.start()
            _wait(lambda: sub_state["id"] is not None, 30,
                  "the standing subscription to register")

            def wal_dir(url):
                return os.path.join(roots[url], "gdelt", "_wal")

            def wait_healed(url, need_reprovision):
                if need_reprovision:
                    _wait(
                        lambda: _stats(url).get("reprovision", {})
                        .get("completed", 0) >= 1,
                        45, f"{url} to complete a snapshot reprovision",
                    )
                _wait(
                    lambda: not _stats(url).get("reprovision", {})
                    .get("pending"), 45, f"{url} reprovision queue empty",
                )
                fleet.wait_ready(url, timeout_s=45)
                fleet.wait_caught_up(url, timeout_s=45)

            for round_no, kind in enumerate(schedule):
                lead, followers = current_roles()
                assert lead is not None, "soak: no leader before round"
                target = (
                    lead if kind == "kill-leader"
                    else followers[int(rng.integers(len(followers)))]
                )
                log(f"soak: round {round_no} {kind} -> {target}")
                if kind in ("diverge",):
                    appending.clear()  # the fork must stay ahead
                    time.sleep(0.3)
                procs[target].send_signal(signal.SIGKILL)
                procs[target].wait(30)
                del procs[target]
                need_reprovision = False
                if kind == "corrupt-wal":
                    d = wal_dir(target)
                    segs = sorted(
                        f for f in os.listdir(d) if f.startswith("wal-")
                    ) if os.path.isdir(d) else []
                    if segs:
                        with open(os.path.join(d, segs[-1]), "ab") as fh:
                            fh.write(bytes(rng.integers(
                                0, 256, 64, dtype=np.uint8)))
                elif kind == "diverge":
                    wal = WriteAheadLog(wal_dir(target))
                    payloads = [p for _, p in wal.read_from(-1)]
                    if payloads:
                        for _ in range(400):
                            wal.append_at(wal.next_seq, payloads[-1])
                        need_reprovision = True
                    wal.close()
                elif kind == "gap-410":
                    # held down past replica.retain.s while the leader
                    # keeps compacting: its WAL position falls off the
                    # leader's retained log
                    time.sleep(RETAIN_S + 2.5)
                    need_reprovision = True
                if kind == "kill-leader":
                    new_lead = fleet.wait_leader(
                        [u for u in urls if u != target],
                        timeout_s=FAILOVER_S + 10,
                    )
                    spawn(roots[target], ports[target], "follower",
                          new_lead, peers_arg)
                else:
                    lead2, _ = current_roles()
                    spawn(roots[target], ports[target], "follower",
                          lead2 or lead, peers_arg)
                appending.set()
                wait_healed(target, need_reprovision)
                if need_reprovision:
                    reprovisions += 1
                counts = fleet.verify_converged(urls, timeout_s=60)
                log(f"soak: round {round_no} healed; converged "
                    f"{counts['gdelt']} rows")

            stop.set()
            for t in threads:
                t.join(10)
            # the push tier must drain: every quorum-acked seq reaches
            # the standing subscriber (the commit gate holds alerts for
            # unreplicated tails, so acked == eventually-delivered)
            _wait(lambda: acked_seqs <= sub_delivered, 60,
                  "the standing subscriber to drain every acked seq")
            sub_stop.set()
            sub_thread.join(15)
            missed_alerts = sorted(acked_seqs - sub_delivered)
            assert missed_alerts == [], (
                f"pubsub: {len(missed_alerts)} acked seqs never reached "
                f"the standing subscriber (first: {missed_alerts[:5]})"
            )
            assert sub_dup[0] == 0, (
                f"pubsub: {sub_dup[0]} duplicate deliveries at or below "
                "the subscriber's acked cursor"
            )
            assert read_failures == [], (
                f"{len(read_failures)} failed reads during the soak "
                f"(first: {read_failures[0]})"
            )
            assert append_errors == [], (
                f"append errors (not sheds): {append_errors[:5]}"
            )
            lead, followers = current_roles()
            assert lead is not None and len(followers) == 2, (
                f"forked or shrunken fleet: leader={lead}, "
                f"followers={followers}"
            )
            for u in followers:
                fleet.wait_caught_up(u, timeout_s=45)
            counts = fleet.verify_converged(urls, timeout_s=60)
            feats = _get(
                lead, "/features/gdelt?cql=INCLUDE&maxFeatures=1000000",
                timeout=60,
            )
            got = {int(f["id"]) for f in feats["features"]}
            expected_floor = set(range(seed_n)) | acked
            assert expected_floor <= got, (
                f"lost {len(expected_floor - got)} acked rows"
            )
            assert got <= expected_floor | inflight, (
                f"{len(got - expected_floor - inflight)} phantom rows"
            )
            assert counts["gdelt"] == len(got), "count/feature drift"
            assert reprovisions >= 2, (
                f"schedule ran but only {reprovisions} self-heal "
                "reprovision(s) completed"
            )
            log(f"soak: ok — {len(schedule)} rounds, {reprovisions} "
                f"snapshot reprovisions, {reads[0]} reads 0 failed, "
                f"{len(acked)} acked rows all served, {sheds[0]} "
                f"bounded sheds, {counts['gdelt']} converged rows, "
                f"{len(acked_seqs)} acked seqs all pushed exactly once")
            rsrv.shutdown()
            rsrv.server_close()
        return {
            "soak_seed": seed,
            "soak_rounds": len(schedule),
            "soak_reprovisions": reprovisions,
            "soak_acked_rows": len(acked),
            "soak_rows_served": len(got),
            "soak_reads": reads[0],
            "soak_sheds": sheds[0],
            "soak_pubsub_acked_seqs": len(acked_seqs),
            "soak_pubsub_delivered": len(sub_delivered),
            "soak_pubsub_dups": sub_dup[0],
            "soak_ok": True,
        }
    finally:
        for p in procs.values():
            try:
                p.kill()
                p.wait(10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


_PUBSUB_NODE_BODY = r"""
import os, sys, time
from geomesa_tpu.conf import set_prop
from geomesa_tpu.server import serve_background
from geomesa_tpu.store.fs import FileSystemDataStore

root, portfile, port = sys.argv[1:4]
set_prop("stream.memtable.rows", 1 << 20)
set_prop("sub.heartbeat.s", 0.5)
deadline = time.monotonic() + 15
while True:
    try:
        server, thread = serve_background(
            FileSystemDataStore(root, partition_size=1 << 12),
            port=int(port), stream=True,
        )
        break
    except OSError:
        if time.monotonic() > deadline:
            raise
        time.sleep(0.2)
with open(portfile + ".tmp", "w") as fh:
    fh.write(str(server.server_address[1]))
    fh.flush(); os.fsync(fh.fileno())
os.replace(portfile + ".tmp", portfile)
thread.join()
server.server_close()
os._exit(0)
"""


def bench_pubsub(args) -> dict:
    """``--mode pubsub``: the continuous-query push tier (ISSUE 16).

    Two legs:

    - **matrix** — subscriptions x append-batches in-process: every
      acked batch must cost exactly ONE fused join launch no matter how
      many subscriptions are armed (asserted per cell), and the
      end-to-end matched-append latency p50/p99 is recorded per cell.
    - **crash** — a single-node server takes appends under a live SSE
      subscriber, is SIGKILLed mid-stream, and restarts on the same
      root; the subscriber reconnects from its acked cursor and must
      see every acked seq EXACTLY once — zero missed, zero duplicate.

    ``--smoke`` shrinks both legs to CI size."""
    import os
    import shutil
    import signal  # noqa: F401 (SIGKILL spelled via Popen.kill below)
    import subprocess
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    from geomesa_tpu.pubsub import PubSubHub
    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.store.stream import StreamingStore

    smoke = bool(args.smoke)
    spec = "val:Int,dtg:Date,*geom:Point:srid=4326"
    rng = np.random.default_rng(20260806)

    # -- leg 1: subscriptions x append-rate matrix, in-process ----------
    sub_counts = (4, 32) if smoke else (8, 64, 512)
    batches = 8 if smoke else 32
    rows = 256 if smoke else 1024
    matrix = []
    for n_subs in sub_counts:
        tmp = tempfile.mkdtemp(prefix="geomesa-bench-pubsub-")
        hub = None
        try:
            ds = FileSystemDataStore(tmp, partition_size=1 << 12)
            ds.create_schema("gdelt", spec)
            layer = StreamingStore(ds)
            hub = PubSubHub(layer)
            for k in range(n_subs):
                x = float(rng.uniform(-170.0, 150.0))
                y = float(rng.uniform(-80.0, 60.0))
                hub.subscribe(
                    "gdelt", {"bbox": [x, y, x + 20.0, y + 20.0]},
                    tenant=f"bench{k % 8}", auths=None,
                )
            launches0 = hub.matcher.launches
            matched0 = hub.matched_records
            times = []
            fid = 0
            for _ in range(batches):
                cols = {
                    "val": rng.integers(0, 100, rows),
                    "dtg": rng.integers(0, 10**9, rows),
                    "geom": np.stack(
                        [rng.uniform(-180, 180, rows),
                         rng.uniform(-90, 90, rows)], axis=1),
                }
                t0 = time.perf_counter()
                layer.append("gdelt", cols, fids=np.arange(fid, fid + rows))
                times.append(time.perf_counter() - t0)
                fid += rows
            launches = hub.matcher.launches - launches0
            assert launches == batches, (
                f"matching must be ONE fused launch per acked batch: "
                f"{n_subs} subs x {batches} batches took {launches} launches"
            )
            ts = sorted(times)
            cell = {
                "subs": n_subs,
                "batches": batches,
                "rows_per_batch": rows,
                "fused_launches": launches,
                "matched_records": hub.matched_records - matched0,
                "append_match_p50_ms": round(ts[len(ts) // 2] * 1e3, 3),
                "append_match_p99_ms": round(
                    ts[min(len(ts) - 1, int(len(ts) * 0.99))] * 1e3, 3),
            }
            matrix.append(cell)
            log("pubsub: %4d subs  %d batches -> %d launches, "
                "p50 %.2fms p99 %.2fms, %d matched rows"
                % (n_subs, batches, launches, cell["append_match_p50_ms"],
                   cell["append_match_p99_ms"], cell["matched_records"]))
        finally:
            if hub is not None:
                hub.close()
            shutil.rmtree(tmp, ignore_errors=True)

    # -- leg 2: SIGKILL + reconnect, exactly-once over the cursor -------
    n1 = 6 if smoke else 20     # batches before the kill
    n2 = 6 if smoke else 20     # batches after the restart
    crash_rows = 8
    tmp = tempfile.mkdtemp(prefix="geomesa-bench-pubsub-crash-")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs: list = []
    try:
        root = os.path.join(tmp, "node")
        ds = FileSystemDataStore(root, partition_size=1 << 12)
        ds.create_schema("gdelt", spec)
        del ds

        def spawn():
            portfile = os.path.join(tmp, f"port-{time.monotonic_ns()}")
            p = subprocess.Popen(
                [sys.executable, "-c", _PUBSUB_NODE_BODY, root, portfile,
                 "0"], env=env,
            )
            deadline = time.monotonic() + 120
            while not os.path.exists(portfile):
                assert p.poll() is None, "pubsub node died during startup"
                assert time.monotonic() < deadline, "pubsub node never bound"
                time.sleep(0.05)
            procs.append(p)
            return p, f"http://127.0.0.1:{int(open(portfile).read())}"

        p, url = spawn()
        url_box = [url]
        req = urllib.request.Request(
            url + "/subscribe/gdelt?tenant=bench",
            data=json.dumps({"bbox": [-180.0, -90.0, 180.0, 90.0]}).encode(),
            method="POST", headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            sid = json.loads(r.read())["id"]

        delivered: list = []
        dups = [0]
        cursor = [-1]
        stop_read = threading.Event()

        def read_stream():
            # reconnect-from-cursor loop: survives the SIGKILL window by
            # retrying until the restarted node binds (url_box updated)
            while not stop_read.is_set():
                try:
                    u = (url_box[0] + "/subscribe/gdelt?id=" + sid
                         + "&from=" + str(cursor[0]))
                    with urllib.request.urlopen(u, timeout=10) as resp:
                        buf = b""
                        while not stop_read.is_set():
                            chunk = resp.read1(65536)
                            if not chunk:
                                break
                            buf += chunk
                            while b"\n\n" in buf:
                                frame, buf = buf.split(b"\n\n", 1)
                                if b"event: match" not in frame:
                                    continue
                                for line in frame.split(b"\n"):
                                    if line.startswith(b"id: "):
                                        seq = int(line[4:])
                                        if seq <= cursor[0]:
                                            dups[0] += 1
                                        else:
                                            cursor[0] = seq
                                        delivered.append(seq)
                except Exception:
                    time.sleep(0.1)

        reader = threading.Thread(target=read_stream, daemon=True)
        reader.start()

        acked: set = set()
        fid_next = [0]

        def append_one():
            fids = list(range(fid_next[0], fid_next[0] + crash_rows))
            fid_next[0] += crash_rows
            doc = {
                "columns": {
                    "val": list(range(crash_rows)),
                    "dtg": [1000 + i for i in range(crash_rows)],
                    "geom": [[10.0, 10.0]] * crash_rows,
                },
                "fids": fids,
            }
            rq = urllib.request.Request(
                url_box[0] + "/append/gdelt", data=json.dumps(doc).encode(),
                method="POST", headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(rq, timeout=30) as r:
                acked.add(int(json.loads(r.read())["seq"]))

        def _wait(pred, timeout_s, msg):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                if pred():
                    return
                time.sleep(0.05)
            raise AssertionError(f"pubsub crash leg: timed out on {msg}")

        for _ in range(n1):
            append_one()
        # kill MID-delivery: at least half the acked seqs seen, then die
        _wait(lambda: len(delivered) >= n1 // 2, 30,
              f"{n1 // 2} of {n1} pre-kill deliveries")
        p.kill()   # SIGKILL: no shutdown hooks, the WAL is the truth
        p.wait(30)
        p, url = spawn()
        url_box[0] = url
        for _ in range(n2):
            append_one()
        _wait(lambda: acked <= set(delivered), 60,
              "every acked seq to reach the resumed subscriber")
        stop_read.set()
        reader.join(15)
        missed = sorted(acked - set(delivered))
        assert missed == [], f"missed acked seqs across the kill: {missed}"
        assert dups[0] == 0, f"{dups[0]} duplicate deliveries across the kill"
        assert len(delivered) == len(set(delivered)), "raw duplicate frames"
        log("pubsub: crash leg ok — %d acked seqs, %d delivered, "
            "0 missed, 0 duplicates across SIGKILL + cursor resume"
            % (len(acked), len(delivered)))
    finally:
        for pr in procs:
            try:
                pr.kill()
                pr.wait(10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)

    return {
        "pubsub_matrix": matrix,
        "pubsub_crash_acked": len(acked),
        "pubsub_crash_delivered": len(delivered),
        "pubsub_crash_missed": 0,
        "pubsub_crash_dups": 0,
        "pubsub_ok": True,
    }


def bench_trace_overhead(args) -> dict:
    """The --trace-overhead check: the serving leg with tracing at its
    DEFAULT sampling (trace.sample=1, slow capture on) must stay within
    3% of the leg with recording fully off (trace.sample=0 +
    trace.slow_ms=0 — spans become no-ops). The leg's throughput is
    strongly bimodal on contended/slow hosts (identical configs have
    measured 150 vs 600+ qps back to back — fusion-window timing), so
    the guard is NOISE-CALIBRATED like the ledger half below: three
    interleaved reps per config, medians compared, and the same-config
    relative spread is the epsilon."""
    from geomesa_tpu.conf import prop_override

    def run(sample: float, slow_ms: float) -> float:
        with prop_override("trace.sample", sample), \
                prop_override("trace.slow_ms", slow_ms):
            return bench_serving(args)["serve_qps"]

    reps = 3
    offs, ons = [], []
    for _ in range(reps):  # interleaved: drift cannot bias one side
        offs.append(run(0.0, 0.0))
        ons.append(run(1.0, 500.0))
    off = sorted(offs)[reps // 2]
    on = sorted(ons)[reps // 2]
    noise_pct = max(
        (max(offs) - min(offs)) / off if off else 0.0,
        (max(ons) - min(ons)) / on if on else 0.0,
    ) * 100.0
    pct = (off - on) / off * 100.0 if off else 0.0
    out = {
        "trace_overhead_off_qps": off,
        "trace_overhead_on_qps": on,
        "trace_overhead_pct": round(pct, 2),
        "trace_overhead_noise_pct": round(noise_pct, 2),
        "trace_overhead_off_spread_qps": [round(v, 1) for v in sorted(offs)],
        "trace_overhead_on_spread_qps": [round(v, 1) for v in sorted(ons)],
    }
    log(
        "trace overhead: %.0f qps (tracing off) vs %.0f qps (default "
        "sampling) = %.2f%% (same-config noise %.2f%%)"
        % (off, on, pct, noise_pct)
    )
    assert pct < 3.0 or pct <= noise_pct, (
        f"tracing at default sampling costs {pct:.2f}% on the serve leg "
        f"(budget: <3%, beyond the {noise_pct:.2f}% same-config noise)"
    )
    out.update(bench_ledger_overhead(args))
    return out


def bench_ledger_overhead(args) -> dict:
    """The ledger/SLO half of the --trace-overhead guard: the serving
    leg with the cost ledger + SLO engine on vs fully off must stay
    within 1% on p50 (ISSUE 9's fault-free budget). The serve leg's
    p50 jitters with fusion-window dynamics far more than 1% on slow
    platforms, so the guard is NOISE-CALIBRATED: three interleaved
    reps per config, medians compared, and the same-config spread is
    the epsilon — a delta indistinguishable from run-to-run noise
    passes; a delta that exceeds what identical configs produce fails."""
    from geomesa_tpu.conf import prop_override

    reps = 3
    offs, ons = [], []
    for _ in range(reps):  # interleaved: drift cannot bias one side
        with prop_override("ledger.enabled", False), \
                prop_override("slo.enabled", False):
            offs.append(bench_serving(args)["serve_p50_ms"])
        with prop_override("ledger.enabled", True), \
                prop_override("slo.enabled", True):
            ons.append(bench_serving(args)["serve_p50_ms"])
    off = sorted(offs)[reps // 2]
    on = sorted(ons)[reps // 2]
    noise = max(max(offs) - min(offs), max(ons) - min(ons), 0.05)
    pct = (on - off) / off * 100.0 if off else 0.0
    # the deterministic half of the guard: time the ACTUAL accounting
    # path (collect + charges + fold into ledger/SLO engine) per
    # request. The A/B above cannot resolve a <1% budget against
    # multi-ms fusion-timing noise; this can (measured ~0.1ms against
    # a ~10ms CPU p50), and it is what the budget is really about.
    per_cost_ms = _ledger_accounting_cost_ms()
    direct_pct = per_cost_ms / off * 100.0 if off else 0.0
    out = {
        "ledger_overhead_off_p50_ms": off,
        "ledger_overhead_on_p50_ms": on,
        "ledger_overhead_pct": round(pct, 2),
        "ledger_overhead_noise_ms": round(noise, 3),
        "ledger_overhead_off_spread_ms": [round(v, 2) for v in sorted(offs)],
        "ledger_overhead_on_spread_ms": [round(v, 2) for v in sorted(ons)],
        "ledger_accounting_cost_ms": round(per_cost_ms, 4),
        "ledger_accounting_pct_of_p50": round(direct_pct, 3),
    }
    log(
        "ledger/slo overhead: p50 %.2fms (off) vs %.2fms (on) = %.2f%% "
        "(same-config noise %.2fms); direct accounting cost "
        "%.3fms/request = %.2f%% of p50"
        % (off, on, pct, noise, per_cost_ms, direct_pct)
    )
    assert direct_pct < 1.0, (
        f"per-request ledger/SLO accounting measures {per_cost_ms:.3f}ms "
        f"= {direct_pct:.2f}% of the fault-free p50 (budget: <1%)"
    )
    assert pct < 1.0 or (on - off) <= 1.5 * noise, (
        f"ledger/SLO A/B delta {on - off:.2f}ms p50 ({pct:.2f}%) exceeds "
        f"1.5x the same-config noise ({noise:.2f}ms) — a real regression, "
        "not measurement scatter (budget: <1% fault-free)"
    )
    return out


def _ledger_accounting_cost_ms(n: int = 4000) -> float:
    """Median-of-3 direct timing of one request's FULL accounting path:
    cost collection, the typical charge set a fused resident count
    makes, and the finish fold into the process ledger + SLO engine."""
    from geomesa_tpu import ledger

    class _Done:  # a finished-trace stand-in (duration + id only)
        dur_s = 0.01
        trace_id = "bench"
        recording = False

    charges = (
        ("device_launches", 1), ("device_seconds", 0.001),
        ("fusion_width", 4), ("read_seconds", 0.001),
        ("read_bytes", 1024), ("decode_seconds", 0.001),
    )
    runs = []
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            with ledger.collect_cost(
                tenant=f"bench-{i % 8}", endpoint="count",
                lane="interactive", shape="count:BBOX:loose",
            ) as cost:
                for field, v in charges:
                    ledger.charge(field, v)
                cost.status = 200
            ledger.finish_request(cost, _Done)
        runs.append((time.perf_counter() - t0) / n * 1e3)
    return sorted(runs)[1]


_MESHBUILD_SNIPPET = r"""
from geomesa_tpu.jaxconf import force_cpu_devices
force_cpu_devices(8)
import json, time
import numpy as np
import jax, jax.numpy as jnp
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.parallel.dist import distributed_sort

mesh = make_mesh(8)
n = 1 << 22
rng = np.random.default_rng(0)
hi = jnp.asarray(rng.integers(0, 1 << 31, n).astype(np.uint32))
lo = jnp.asarray(
    rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
)
rid = jnp.asarray(np.arange(n, dtype=np.uint32))
def run():
    (sh, sl), pay, sv = distributed_sort(
        mesh, (hi, lo), payload={"rid": rid}
    )
    jax.block_until_ready((sh, sl, pay["rid"], sv))
run()  # compile + correctness (overflow would raise)
times = []
for _ in range(5):
    t0 = time.perf_counter(); run(); times.append(time.perf_counter() - t0)
med = sorted(times)[len(times) // 2]
print(json.dumps({
    "mesh_build_rows_per_sec": round(n / med, 1),
    "mesh_build_n": n,
    "mesh_build_devices": 8,
    "mesh_build_ms": round(med * 1e3, 1),
}))
"""


#: BENCH_r05's recorded mesh-build rate (rows/sec) — the bar the
#: rebuilt exchange must beat; the --smoke/--check CI guard pins it
_R05_MESH_BUILD_ROWS_PER_SEC = 0.47e6


def bench_meshbuild(args) -> dict:
    """Mesh exchange-sort throughput (the build's distribution leg): a
    2^22-row distributed sort with a row-id payload over an 8-virtual-
    device CPU mesh (SURVEY section 2.6 bulk-sort row). Runs in a SUBPROCESS
    because the bench process owns the TPU backend and the virtual-device
    flag must precede jax init. A CPU-mesh rate is not a TPU/ICI rate —
    it proves the exchange executes at scale and tracks regressions.

    A subprocess failure PROPAGATES: the rc and stderr tail land in the
    bench JSON, and ``--check``/``--smoke`` runs raise (exit nonzero)
    instead of recording ``None`` with the error buried in the log.
    ``--smoke``/``--check`` additionally guard the measured rate against
    the BENCH_r05 baseline (0.47M rows/s)."""
    import json as _json
    import subprocess
    import sys as _sys

    log("mesh build: 2^22-row distributed sort on an 8-device CPU mesh "
        "(subprocess)")
    out = subprocess.run(
        [_sys.executable, "-c", _MESHBUILD_SNIPPET],
        capture_output=True, text=True, timeout=900,
    )
    if out.returncode != 0:
        tail = out.stderr[-800:]
        log(f"meshbuild FAILED rc={out.returncode}: {tail[-500:]}")
        if args.check or args.smoke:
            raise RuntimeError(
                f"meshbuild subprocess failed rc={out.returncode}: {tail}"
            )
        return {
            "mesh_build_rows_per_sec": None,
            "mesh_build_rc": out.returncode,
            "mesh_build_stderr_tail": tail,
        }
    line = out.stdout.strip().splitlines()[-1]
    got = _json.loads(line)
    got["mesh_build_rc"] = 0
    rate = got["mesh_build_rows_per_sec"]
    got["mesh_build_vs_r05_x"] = round(rate / _R05_MESH_BUILD_ROWS_PER_SEC, 2)
    log(f"mesh build: {rate/1e6:.1f}M rows/s "
        f"({got['mesh_build_ms']}ms for 2^22 rows over 8 devices; "
        f"{got['mesh_build_vs_r05_x']}x the r05 baseline)")
    if args.check or args.smoke:
        assert rate > _R05_MESH_BUILD_ROWS_PER_SEC, (
            f"mesh build {rate/1e6:.2f}M rows/s does not beat the r05 "
            f"baseline {_R05_MESH_BUILD_ROWS_PER_SEC/1e6:.2f}M rows/s"
        )
    return got


_MULTICHIP_SNIPPET = r"""
import sys
nd, n, serve_n, reqs = (int(a) for a in sys.argv[1:5])
from geomesa_tpu.jaxconf import force_cpu_devices
force_cpu_devices(max(nd, 2))  # nd=1 still simulates on the CPU platform
import json, time
import numpy as np
import jax, jax.numpy as jnp
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.parallel.dist import distributed_sort

mesh = make_mesh(nd)
rng = np.random.default_rng(0)
hi = jnp.asarray(rng.integers(0, 1 << 31, n).astype(np.uint32))
lo = jnp.asarray(
    rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
)
rid = jnp.asarray(np.arange(n, dtype=np.uint32))
def run():
    (sh, sl), pay, sv = distributed_sort(mesh, (hi, lo), payload={"rid": rid})
    jax.block_until_ready((sh, sl, pay["rid"], sv))
run()  # compile + correctness (overflow would raise)
times = []
for _ in range(3):
    t0 = time.perf_counter(); run(); times.append(time.perf_counter() - t0)
build = n / sorted(times)[1]
del hi, lo, rid

# fused mesh serving: mesh-sharded resident index + scheduler micro-batches
from geomesa_tpu.store import MemoryDataStore
from geomesa_tpu.device_cache import DeviceIndex, ShardedDeviceIndex
from geomesa_tpu.sched import FusableQuery, QueryScheduler, SchedConfig
from geomesa_tpu.conf import prop_override

store = MemoryDataStore()
store.create_schema("pts", "dtg:Date,*geom:Point:srid=4326")
t0ms = 1577836800000
store.write("pts", {
    "dtg": t0ms + rng.integers(0, 30 * 86400_000, serve_n),
    "geom": np.stack(
        [rng.uniform(-180, 180, serve_n), rng.uniform(-90, 90, serve_n)],
        axis=1,
    ),
}, fids=np.arange(serve_n))
di = (
    ShardedDeviceIndex(store, "pts", mesh=mesh)
    if nd > 1
    else DeviceIndex(store, "pts", z_planes=True)
)
qs = [f"BBOX(geom, {-170 + 20 * i}, -40, {-140 + 20 * i}, 40)"
      for i in range(16)]
sched = QueryScheduler(SchedConfig(
    max_inflight=1, max_queue=8192, fusion_window_ms=0.5,
    default_deadline_ms=None,
))
with prop_override("query.loose.bbox", True):
    expect = [di.count(q, loose=True) for q in qs]  # warm the kernels
    warm = [sched.submit(fuse=FusableQuery(di, qs[i % 16], "count",
                                           loose=True))
            for i in range(64)]
    for p in warm:
        sched.wait(p)  # warm the fused launch shapes
    t0 = time.perf_counter()
    pend = [sched.submit(fuse=FusableQuery(di, qs[i % 16], "count",
                                           loose=True))
            for i in range(reqs)]
    got = [sched.wait(p) for p in pend]
    qps = reqs / (time.perf_counter() - t0)
for i, g in enumerate(got):
    assert g == expect[i % 16], (i, g, expect[i % 16])
snap = sched.snapshot()
sched.close(timeout=10)
print(json.dumps({
    "devices": nd,
    "build_rows_per_sec": round(build, 1),
    "build_n": n,
    "serve_fused_qps": round(qps, 1),
    "serve_rows": serve_n,
    "serve_fusion_factor": snap["fusion_factor"],
}))
"""


def bench_multichip(args) -> dict:
    """The multi-chip SCALING leg (promotes MULTICHIP_r0*.json from a
    dryrun smoke to a first-class bench): for 1/2/4/8 virtual CPU
    devices, record the distributed-sort build rate AND the fused
    resident-serving qps through the scheduler's micro-batcher over a
    mesh-sharded index, each in a fresh subprocess (the device-count
    flag must precede jax init). The curve is written to the next
    MULTICHIP_r0*.json next to this file. ``--smoke`` runs smaller
    shapes and (like ``--check``) raises on any leg failure and guards
    the 8-device build rate against the r05 baseline."""
    import json as _json
    import os
    import re as _re
    import subprocess
    import sys as _sys

    n = args.n or ((1 << 20) if args.smoke else (1 << 22))
    serve_n = (1 << 16) if args.smoke else (1 << 18)
    reqs = 256 if args.smoke else 512
    curve: list = []
    for nd in (1, 2, 4, 8):
        out = subprocess.run(
            [_sys.executable, "-c", _MULTICHIP_SNIPPET,
             str(nd), str(n), str(serve_n), str(reqs)],
            capture_output=True, text=True, timeout=1800,
        )
        if out.returncode != 0:
            tail = out.stderr[-800:]
            log(f"multichip[{nd}] FAILED rc={out.returncode}: {tail[-300:]}")
            if args.check or args.smoke:
                raise RuntimeError(
                    f"multichip leg ({nd} devices) failed "
                    f"rc={out.returncode}: {tail}"
                )
            curve.append({
                "devices": nd, "rc": out.returncode, "stderr_tail": tail,
            })
            continue
        got = _json.loads(out.stdout.strip().splitlines()[-1])
        got["rc"] = 0
        log(f"multichip[{nd}]: build {got['build_rows_per_sec']/1e6:.2f}M "
            f"rows/s, fused serve {got['serve_fused_qps']:.0f} qps "
            f"(fusion factor {got['serve_fusion_factor']})")
        curve.append(got)
    res: dict = {"multichip_scaling": curve, "multichip_build_n": n}
    eight = next(
        (c for c in curve if c.get("devices") == 8 and c.get("rc") == 0),
        None,
    )
    if eight:
        res["mesh_build_rows_per_sec_8dev"] = eight["build_rows_per_sec"]
        res["mesh_build_vs_r05_x"] = round(
            eight["build_rows_per_sec"] / _R05_MESH_BUILD_ROWS_PER_SEC, 2
        )
        if args.check or args.smoke:
            assert eight["build_rows_per_sec"] > \
                _R05_MESH_BUILD_ROWS_PER_SEC, (
                    "8-device mesh build "
                    f"{eight['build_rows_per_sec']/1e6:.2f}M rows/s does "
                    "not beat the r05 baseline "
                    f"{_R05_MESH_BUILD_ROWS_PER_SEC/1e6:.2f}M rows/s"
                )
    # record the curve as the next first-class MULTICHIP artifact (a
    # scaling record replaces the old dryrun-smoke format); a bench
    # re-run overwrites its own latest scaling record instead of
    # minting a file per invocation
    try:
        root = os.path.dirname(os.path.abspath(__file__))
        existing = sorted(
            f for f in os.listdir(root)
            if _re.match(r"MULTICHIP_r\d+\.json$", f)
        )
        nxt = 1
        if existing:
            last = existing[-1]
            with open(os.path.join(root, last)) as f:
                prev = _json.load(f)
            num = int(_re.search(r"r(\d+)", last).group(1))
            nxt = num if "scaling" in prev else num + 1
        path = os.path.join(root, f"MULTICHIP_r{nxt:02d}.json")
        with open(path, "w") as f:
            _json.dump({
                "ok": all(c.get("rc") == 0 for c in curve),
                "smoke": bool(args.smoke),
                "build_n": n,
                "serve_rows": serve_n,
                "scaling": curve,
            }, f, indent=2)
            f.write("\n")
        log(f"multichip scaling curve recorded in {os.path.basename(path)}")
    except OSError as e:  # read-only checkout: the JSON line still has it
        log(f"could not record the MULTICHIP artifact: {e}")
    return res


def _coldstart_store(n: int):
    """GDELT-shaped MemoryDataStore both coldstart children rebuild
    identically (seeded): same data, same shapes, same jit keys."""
    import numpy as np

    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore()
    ds.create_schema("gdelt", "name:String,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(17)
    t0 = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ds.write("gdelt", {
        "name": rng.choice(["a", "b", "c"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
        ),
    }, fids=np.arange(n))
    return ds


def _bench_coldstart_child(args) -> dict:
    """One coldstart measurement leg, run in a FRESH process: stage a
    resident index, optionally AOT-warm it (--coldstart-child warm),
    then time the FIRST serving call of every base kernel-family leg
    (the warmup_plan enumeration IS the serving surface) plus a short
    steady-state p50 per leg. The compile ledger is reset between
    warmup and serving, so ``serving_compiles`` is exactly the number
    of XLA compiles the serving path paid — the warmed child must
    report 0 (the fleet warm-handoff guarantee, scored the same way
    a restarted node is scored against /stats/ledger)."""
    import time as _time
    from statistics import median

    from geomesa_tpu import ledger, warmup
    from geomesa_tpu.device_cache import DeviceIndex

    n = args.n or ((1 << 14) if args.smoke else (1 << 18))
    ds = _coldstart_store(n)
    t0 = _time.perf_counter()
    di = DeviceIndex(ds, "gdelt", z_planes=True)
    di.count("INCLUDE")  # force staging before the clock starts
    stage_s = _time.perf_counter() - t0
    wdoc = None
    if args.coldstart_child == "warm":
        wdoc = warmup.run({"gdelt": di})
    legs = di.warmup_plan()  # the base kernel-family serving surface
    ledger.COMPILES.reset()
    first_ms: dict = {}
    for name, fn in legs:
        t = _time.perf_counter()
        fn()
        first_ms[name] = round((_time.perf_counter() - t) * 1e3, 3)
    reps = 3 if args.smoke else 7
    steady: dict = {}
    for name, fn in legs:
        ts = []
        for _ in range(reps):
            t = _time.perf_counter()
            fn()
            ts.append(_time.perf_counter() - t)
        steady[name] = round(median(ts) * 1e3, 3)
    comp = ledger.COMPILES.snapshot()
    return {
        "leg": args.coldstart_child,
        "n": n,
        "stage_s": round(stage_s, 3),
        "first_ms": first_ms,
        "steady_p50_ms": steady,
        "serving_compiles": comp["compiles"],
        "serving_compile_s": comp["total_s"],
        "warmup": wdoc,
    }


def bench_coldstart(args) -> dict:
    """The compile-cliff scenario bench (--mode coldstart): two fresh
    subprocesses share one initially-EMPTY persistent compile cache.
    The ``cold`` child serves with no warmup — its first-query p100
    per kernel family is the cliff (and its compiles populate the
    cache, exactly what a prior deploy's process does). The ``warm``
    child then models the rolling-restart handoff: AOT warmup (warming
    from the now-primed cache) before serving. Guards: warmed
    first-query latency must stay under ``slo.coldstart.threshold.ms``
    AND within 2x the leg's warm steady-state p50 (with a small
    absolute floor for host dispatch jitter), and the warmed child's
    serving path must attribute ZERO compiles in the ledger."""
    if getattr(args, "coldstart_child", None):
        return _bench_coldstart_child(args)
    import os
    import shutil
    import subprocess

    from geomesa_tpu.conf import sys_prop

    from geomesa_tpu.jaxconf import DEFAULT_CACHE_DIR

    # a fixed path (the cache key includes it), emptied so the cold
    # child starts from nothing; the warm child then reads what it wrote
    cache = os.path.join(DEFAULT_CACHE_DIR, "coldstart")
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)

    def child(leg: str) -> dict:
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--mode", "coldstart", "--coldstart-child", leg,
        ]
        if args.n:
            cmd += ["--n", str(args.n)]
        if args.smoke:
            cmd += ["--smoke"]
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
        env.pop("GEOMESA_TPU_COMPILE_CACHE", None)  # this leg needs it on
        out = subprocess.run(
            cmd, capture_output=True, text=True, timeout=3600, env=env
        )
        sys.stderr.write(out.stderr[-3000:])
        if out.returncode != 0:
            raise RuntimeError(
                f"coldstart {leg} child failed: {out.stderr[-500:]}"
            )
        return json.loads(out.stdout.strip().splitlines()[-1])

    log("coldstart: cold child (no warmup, empty persistent cache)")
    cold = child("cold")
    log("coldstart: warm child (AOT warmup from the primed cache)")
    warm = child("warm")

    thresh_ms = float(sys_prop("slo.coldstart.threshold.ms"))
    # absolute floor under the 2x-steady guard: at CPU-smoke scale a
    # steady p50 is single-digit ms and host scheduling jitter alone
    # can double a first call — sub-100ms "regressions" are noise, not
    # compile cliffs (a compile is 3-5 orders of magnitude, not 2x)
    floor_ms = 100.0
    violations: list = []
    for fam, wf in warm["first_ms"].items():
        sp50 = float(warm["steady_p50_ms"].get(fam, 0.0))
        if wf > thresh_ms:
            violations.append(
                f"{fam}: warmed first query {wf}ms exceeds "
                f"slo.coldstart.threshold.ms={thresh_ms}"
            )
        if wf > max(2.0 * sp50, floor_ms):
            violations.append(
                f"{fam}: warmed first query {wf}ms > 2x steady p50 "
                f"{sp50}ms"
            )
    if int(warm.get("serving_compiles", 0)) != 0:
        violations.append(
            "warmed serving path paid "
            f"{warm['serving_compiles']} compiles (ledger attribution "
            "must be 0 — the warmup plan missed a serving signature)"
        )
    cliff = {
        fam: round(
            float(cold["first_ms"][fam])
            / max(float(warm["steady_p50_ms"].get(fam, 0.0)), 0.1),
            1,
        )
        for fam in cold["first_ms"]
    }
    worst = max(cliff, key=cliff.get) if cliff else None
    log(
        "coldstart: worst cliff "
        f"{worst}: {cold['first_ms'].get(worst)}ms cold first vs "
        f"{warm['steady_p50_ms'].get(worst)}ms warm steady "
        f"({cliff.get(worst)}x); warmed first-query p100 "
        f"{max(warm['first_ms'].values())}ms, serving compiles "
        f"cold={cold['serving_compiles']} warm={warm['serving_compiles']}"
    )
    out = {
        "coldstart_n": cold["n"],
        "coldstart_cold_first_ms": cold["first_ms"],
        "coldstart_cold_serving_compiles": cold["serving_compiles"],
        "coldstart_warm_first_ms": warm["first_ms"],
        "coldstart_warm_first_p100_ms": max(warm["first_ms"].values()),
        "coldstart_warm_steady_p50_ms": warm["steady_p50_ms"],
        "coldstart_warm_serving_compiles": warm["serving_compiles"],
        "coldstart_warmup": warm.get("warmup"),
        "coldstart_cliff_x": cliff,
        "coldstart_threshold_ms": thresh_ms,
        "coldstart_violations": violations,
    }
    if violations:
        raise AssertionError(
            "coldstart SLO violated:\n  " + "\n  ".join(violations)
        )
    return out


def main() -> None:
    # deep jaxpr traces (polygon crossing-number unroll under the
    # compile path) exceed the default 1000-frame recursion limit
    sys.setrecursionlimit(100_000)
    from geomesa_tpu.jaxconf import enable_compilation_cache

    # re-runs skip the ~2min compile warmup
    compile_cache_dir = enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None, help="rows resident on device")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument(
        "--chain",
        type=int,
        default=512,
        help="scan invocations chained per dispatch, amortizing the "
        "per-dispatch host overhead over K kernel runs",
    )
    ap.add_argument(
        "--chain-build",
        type=int,
        default=8,
        help="build invocations chained per dispatch (build mode)",
    )
    ap.add_argument("--check", action="store_true", help="verify count vs host oracle")
    ap.add_argument(
        "--smoke", action="store_true",
        help="oocscan mode: ONLY the small-N store-integrated leg with "
        "the sustained-MB/s regression guard (fast; tier-1/CI safe). "
        "Without it the full leg runs the slow multi-GB device pump "
        "too. soak mode: one round of each fault kind instead of the "
        "full randomized schedule.",
    )
    ap.add_argument(
        "--io-workers", type=int, default=0,
        help="host-I/O pipeline workers for the oocscan store leg "
        "(0 = default 4)",
    )
    ap.add_argument(
        "--trace-overhead", action="store_true",
        help="serve mode: additionally compare the serving leg with "
        "tracing at default sampling vs recording off, asserting the "
        "overhead stays under 3%%",
    )
    ap.add_argument(
        "--chaos-smoke", action="store_true",
        help="serve mode: ONLY the fault-injection smoke (fast; CI "
        "safe) — inject a device-launch failure and a staging OOM, "
        "assert degraded-but-correct responses, breaker open/half-open "
        "recovery and a clean drain (bench_serve_chaos)",
    )
    ap.add_argument(
        "--slo-smoke", action="store_true",
        help="serve mode: ONLY the SLO/flight-recorder smoke (fast; CI "
        "safe) — an injected slow query must trip the fast-window burn "
        "and emit a flight-recorder bundle (with a resolving /metrics "
        "exemplar), a fault-free run must not, and a breaker-open "
        "bundle must name the breaker + the attributed compiles",
    )
    ap.add_argument(
        "--seed", type=int, default=None,
        help="soak mode: fault-schedule RNG seed (printed in the log; "
        "re-run with the same seed to reproduce a failing schedule)",
    )
    ap.add_argument(
        "--coldstart-child",
        choices=("cold", "warm"),
        help=argparse.SUPPRESS,  # internal: one coldstart measurement
        # leg in a fresh process (bench_coldstart spawns these)
    )
    ap.add_argument(
        "--engine",
        choices=("pallas", "xla"),
        default="pallas",
        help="fused scan kernel: hand-written Pallas tiles or XLA-fused jnp",
    )
    ap.add_argument(
        "--mode",
        choices=(
            "all", "filter", "zscan", "build", "polygon", "density", "sweep",
            "xzbuild", "meshbuild", "multichip", "pipeline", "oocscan",
            "join", "serve", "flush", "stream", "results", "replica",
            "soak", "pubsub", "coldstart",
        ),
        default="all",
        help="all: every benchmark, one JSON line with everything (what "
        "the driver records); any other value runs that one alone",
    )
    args = ap.parse_args()

    if args.mode == "filter":
        out = bench_filter(args)
    elif args.mode == "zscan":
        out = bench_zscan(args)
    elif args.mode == "build":
        out = bench_build(args)
    elif args.mode == "polygon":
        out = bench_polygon(args)
    elif args.mode == "density":
        out = bench_density_knn(args)
    elif args.mode == "sweep":
        import jax

        n = _default_n(args, jax.devices()[0].platform)
        out = {"sweep": bench_sweep(args, _gdelt_cols(args, n))}
    elif args.mode == "xzbuild":
        out = bench_xz_build(args)
    elif args.mode == "meshbuild":
        out = bench_meshbuild(args)
    elif args.mode == "multichip":
        out = bench_multichip(args)
    elif args.mode == "pipeline":
        out = bench_pipeline(args)
    elif args.mode == "oocscan":
        out = bench_oocscan(args)
    elif args.mode == "join":
        out = bench_join(args)
    elif args.mode == "serve":
        if args.chaos_smoke:
            out = bench_serve_chaos(args)
        elif args.slo_smoke:
            out = bench_slo_smoke(args)
        else:
            out = bench_serving(args)
            if args.trace_overhead:
                out.update(bench_trace_overhead(args))
    elif args.mode == "results":
        out = bench_results(args)
    elif args.mode == "flush":
        out = bench_flush(args)
    elif args.mode == "stream":
        if args.chaos_smoke:
            out = bench_stream_chaos(args)
        else:
            out = bench_stream(args)
    elif args.mode == "replica":
        # the replicated tier only has a chaos leg; --chaos-smoke is
        # how CI invokes it, but the bare mode runs the same thing
        out = bench_replica_chaos(args)
    elif args.mode == "soak":
        out = bench_soak(args)
    elif args.mode == "pubsub":
        out = bench_pubsub(args)
    elif args.mode == "coldstart":
        out = bench_coldstart(args)
    else:
        # zscan FIRST: its DeviceIndex staging runs on an empty device
        z = bench_zscan(args)
        out = bench_filter(args)
        out["zscan_feats_per_sec"] = z["value"]
        out["zscan_gbps"] = z["gbps"]
        out["zscan_hbm_pct"] = z["hbm_pct"]
        out["zscan_best_feats_per_sec"] = z["best_feats_per_sec"]
        out["zscan_spread_ms"] = z["spread_ms"]
        for k in ("zscan_pad16_feats_per_sec", "zscan_pad16_gbps",
                  "zscan_pad16_hbm_pct", "zscan_roofline_note"):
            if k in z:
                out[k] = z[k]
        # BASELINE config #3: polygon-intersects + time over resident points
        p = bench_polygon(args)
        out["polygon_feats_per_sec"] = p["value"]
        out["polygon_gbps"] = p["gbps"]
        out["polygon_hbm_pct"] = p["hbm_pct"]
        out["polygon_selectivity"] = p["selectivity"]
        for k in ("polygon_vertices", "polygon_complex_feats_per_sec",
                  "polygon_complex_vertices", "polygon_complex_selectivity",
                  "polygon_complex_gbps"):
            if k in p:
                out[k] = p[k]
        # BASELINE config #4: fused density + end-to-end kNN
        d = bench_density_knn(args)
        out["density_feats_per_sec"] = d["value"]
        out["density_hbm_pct"] = d["hbm_pct"]
        out["knn_ms"] = d["knn_ms"]
        out["knn_cold_ms"] = d["knn_cold_ms"]
        # skewed (clustered) data: same flagship filter over GDELT-like
        # city clusters — selectivity shifts, throughput must hold.
        # Half-size columns: earlier phases' frees leave fragmented HBM,
        # and a throughput sample needs bandwidth-saturating n, not max n
        import gc

        import jax as _jax

        gc.collect()
        n_sk = args.n or (
            (1 << 27) if _jax.devices()[0].platform == "tpu" else (1 << 20)
        )
        skew_cols = _gdelt_cols(args, n_sk, skew=True)
        sk = _scan_metric(
            args, skew_cols,
            "BBOX(geom, -10, 35, 30, 60) AND "
            "dtg DURING 2020-01-10T00:00:00Z/2020-01-15T00:00:00Z",
            "skewed-scan",
        )
        out["skew_feats_per_sec"] = sk["value"]
        out["skew_selectivity"] = sk["selectivity"]
        del skew_cols
        gc.collect()
        # selectivity sweep on uniform data
        out["sweep"] = bench_sweep(args, _gdelt_cols(args, n_sk))
        build = bench_build(args)
        out["build_pts_per_sec"] = build["value"]
        out["build_chain"] = build["build_chain"]
        out["build_n"] = build["build_n"]
        if "build_breakdown" in build:
            out["build_breakdown"] = build["build_breakdown"]
        # BASELINE config #5: non-point (XZ3) build on device
        xzb = bench_xz_build(args)
        out["xz_build_envelopes_per_sec"] = xzb["value"]
        out["xz_build_chain"] = xzb["xz_build_chain"]
        out["xz_build_n"] = xzb["xz_build_n"]
        # the build's exchange leg at scale (8-virtual-device CPU mesh)
        out.update(bench_meshbuild(args))
        # the multi-chip scaling curve: build rate + fused serve qps at
        # 1/2/4/8 devices (records the next MULTICHIP_r0*.json)
        out.update(bench_multichip(args))
        # spatial join engine (planned, co-partitioned, batched refinement)
        out.update(bench_join(args))
        # concurrent serving through the device query scheduler: the
        # fusion factor (queries per launch) and tail latency under an
        # 8-thread client load against one device worker
        out.update(bench_serving(args))
        # BASELINE config #1 "via Parquet": the full ingest->query path.
        # Every leg runs in THIS process: it holds the chip, so a child
        # process that needs it would fail or hang
        out.update(bench_pipeline(args))
        # the same pipeline at 2^25: at GB scale the host stages contend
        # with disk writeback, so per-row rates differ from 2^22 —
        # record the real thing rather than extrapolating
        if args.n is None and _jax.devices()[0].platform == "tpu":
            out.update({
                f"pipeline25_{k.removeprefix('pipeline_')}": v
                for k, v in bench_pipeline(
                    argparse.Namespace(**{**vars(args), "n": 1 << 25})
                ).items()
            })
        # the larger-than-HBM streamed scan
        gc.collect()
        out.update(bench_oocscan(args))
    # cold-cost numbers (knn_cold_ms, pipeline_warmup_s) depend on
    # whether the persistent compile cache had entries: record it
    out["compile_cache"] = compile_cache_dir is not None
    print(json.dumps(out))


if __name__ == "__main__":
    main()
