"""Device-side spatial join engine: prepared Z-sorted join layouts,
adaptive planning, and batched count->cap->compact refinement.

The engine joins a LEFT side (a resident :class:`DeviceIndex`'s host
mirror, or any FeatureBatch) against M right-side envelope windows and
returns exact envelope-join pairs — the coarse+refine core every join
predicate builds on (``intersects`` over boxes is final here; polygon
topological predicates and ``dwithin`` refine the emitted pairs with the
exact geometry residual in ``sql/frame.py`` / ``process/join.py``).

Layout: the engine keeps its OWN spatial key layout per staged
generation (``JoinIndex``) — Z2 Morton keys for point schemas, XZ2
extent codes for non-point — exactly like the durable store keeps
separate key spaces per query class. When the staged rows already
arrive Z-sorted (FS stores flush Z-ordered; sharded indexes mesh-sort)
the permutation is the identity and emission order is free; otherwise
the engine sorts once at prepare (native radix) and re-canonicalizes
emitted pairs per join.

Execution engines (``join.engine`` = auto | device | host):

- ``device``: candidate runs refine in BATCHED device launches (one
  launch per ``join.batch.candidates``-bounded run group, shapes
  bucketed power-of-two) with fixed-shape count->cap->compact pair
  emission — the ``_mesh_hits`` discipline — replacing the per-window
  dispatch of the old coarse pass. With a mesh, runs are CO-PARTITIONED
  at shard row boundaries and every shard refines its own rows in one
  SPMD launch with zero cross-shard row movement.
- ``host``: the numpy twin (bit-identical oracle). ``auto`` resolves to
  host on all-CPU platforms — XLA:CPU gathers lose to numpy just as
  its sorts lose to radix (the ``mesh.sort.engine`` precedent) — and
  device otherwise.

Refinement batches ride the scheduler when one is supplied
(``sched.run`` on the batch lane, device-marked launches under the
watchdog/ledger like every other resident scan).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from geomesa_tpu.conf import sys_prop
from geomesa_tpu.join import planner as jp
from geomesa_tpu.ops import join as jops


def _join_conf() -> dict:
    return {
        "engine": sys_prop("join.engine"),
        "strategy": sys_prop("join.strategy"),
        "broadcast_windows": int(sys_prop("join.broadcast.windows")),
        "split_rows": max(int(sys_prop("join.split.rows")), 1024),
        "batch_candidates": max(
            int(sys_prop("join.batch.candidates")), 4096
        ),
        "hist_bits": min(max(int(sys_prop("join.hist.bits")), 4), 10),
        "xz_ranges": max(int(sys_prop("join.xz.ranges")), 4),
    }


class JoinIndex:
    """Per-generation join layout over one left side: sorted spatial
    keys, the sort permutation (None when the staged order was already
    key-sorted), the sorted coordinate planes, and the coarse world-grid
    histogram the planner estimates selectivity/skew from."""

    def __init__(self, kind, sfc, keys, perm, planes, lon, lat,
                 hist_prefix, hist_bits, gen=0):
        self.kind = kind          # "z2" | "xz2"
        self.sfc = sfc
        self.keys = keys          # sorted uint64 codes
        self.perm = perm          # sorted-row -> original-row, or None
        self.planes = planes      # sorted host planes (x,y | x0,y0,x1,y1)
        self.lon = lon
        self.lat = lat
        self.hist_prefix = hist_prefix
        self.hist_bits = hist_bits
        self.gen = gen
        self._dev = None          # single-device staged planes
        self._mesh_dev = None     # (mesh-id, planes, local_n)

    @property
    def n(self) -> int:
        return int(len(self.keys))

    @property
    def point(self) -> bool:
        return self.kind == "z2"

    def to_orig(self, rows: np.ndarray) -> np.ndarray:
        return rows if self.perm is None else self.perm[rows]

    def sort_gate(self, gate):
        """Original-row bool gate -> sorted-layout order."""
        if gate is None:
            return None
        return gate if self.perm is None else gate[self.perm]

    # -- device staging ----------------------------------------------------

    def device_planes(self):
        """Stage the sorted coordinate planes once per generation.
        float64 planes need 64-bit lanes (exact device refinement); a
        platform without them stages float32 and the engine re-tests
        emitted candidates against the float64 host planes."""
        if self._dev is None:
            import jax.numpy as jnp

            try:
                import jax

                with jax.enable_x64(True):
                    dev = {
                        k: jnp.asarray(v) for k, v in self.planes.items()
                    }
                if any(
                    d.dtype != np.float64 for d in dev.values()
                ):  # silently narrowed: treat as the f32 candidate path
                    raise TypeError("x64 unavailable")
            except Exception:  # lint: disable=GT011(x64 capability probe: the f32 candidate path + _post_exact pass IS the designed fallback, not a fault)
                dev = {
                    k: jnp.asarray(v.astype(np.float32))
                    for k, v in self.planes.items()
                }
            self._dev = dev
        return self._dev

    def mesh_planes(self, mesh, axis: str = "shard"):
        """Shard the sorted planes by CONTIGUOUS key ranges over the
        mesh (equal row slabs of the globally Z-sorted layout, padded at
        the global tail) — the PR 8 partitioning primitive applied to
        the join layout. Returns (planes, local_n)."""
        key = jops.mesh_key(mesh)
        if self._mesh_dev is None or self._mesh_dev[0] != key:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            shards = int(mesh.shape[axis])
            local_n = max(-(-self.n // shards), 1)
            cap = local_n * shards
            sharding = NamedSharding(mesh, P(axis))
            out = {}
            import jax

            with jax.enable_x64(True):
                for k, v in self.planes.items():
                    a = np.asarray(v, np.float64)  # lint: disable=GT004(host-side plane coercion BEFORE device_put: staging, not a device fetch)
                    if cap > self.n:
                        a = np.concatenate(
                            [a, np.zeros(cap - self.n, a.dtype)]
                        )
                    out[k] = jax.device_put(a, sharding)
            self._mesh_dev = (key, out, local_n)
        return self._mesh_dev[1], self._mesh_dev[2]


@dataclass
class JoinResult:
    """Exact envelope-join pairs plus the execution report."""

    rows: np.ndarray              # left row ids (original layout order)
    wins: np.ndarray              # right window ids, pair-aligned
    strategy: str = "broadcast"
    level: int = 0
    engine: str = "host"
    launches: int = 0
    candidates: int = 0
    splits: int = 0
    shards: int = 0
    plan_s: float = 0.0
    refine_s: float = 0.0
    stats: "jp.JoinStats | None" = None

    @property
    def pairs(self) -> int:
        return len(self.rows)

    def report(self) -> dict:
        return {
            "strategy": self.strategy,
            "level": self.level,
            "engine": self.engine,
            "pairs": self.pairs,
            "candidates": self.candidates,
            "launches": self.launches,
            "skew_splits": self.splits,
            "shards": self.shards,
            "plan_s": round(self.plan_s, 4),
            "refine_s": round(self.refine_s, 4),
            "stats": self.stats.to_json() if self.stats else None,
        }


def _empty_result(**kw) -> JoinResult:
    e = np.empty(0, np.int64)
    return JoinResult(e, e.copy(), **kw)


def build_join_index(batch, sft, hist_bits: int, gen: int = 0) -> JoinIndex:
    """Build the join layout for one left side: spatial keys, sort
    permutation (skipped when the rows already arrive key-sorted), the
    sorted coordinate planes and the coarse histogram."""
    geom = sft.geom_field
    if geom is None:
        raise ValueError(
            f"spatial join needs a geometry field on {sft.type_name!r}"
        )
    n = len(batch)
    if sft.descriptor(geom).is_point:
        from geomesa_tpu.curves.z2 import Z2SFC

        sfc = Z2SFC()
        x, y = batch.point_coords(geom)
        x = np.asarray(x, np.float64)
        y = np.asarray(y, np.float64)
        keys = np.asarray(sfc.index(x, y), np.uint64) if n else np.empty(
            0, np.uint64
        )
        planes = {"x": x, "y": y}
        kind, lon, lat = "z2", sfc.lon, sfc.lat
        hx, hy = x, y
    else:
        from geomesa_tpu.curves.normalize import (
            NormalizedLat,
            NormalizedLon,
        )
        from geomesa_tpu.curves.xz2 import XZ2SFC

        sfc = XZ2SFC(sft.xz_precision)
        bb = batch.bboxes(geom) if n else np.zeros((0, 4))
        keys = (
            np.asarray(
                sfc.index(bb[:, 0], bb[:, 1], bb[:, 2], bb[:, 3]),
                np.uint64,
            )
            if n
            else np.empty(0, np.uint64)
        )
        planes = {
            "x0": np.asarray(bb[:, 0], np.float64),
            "y0": np.asarray(bb[:, 1], np.float64),
            "x1": np.asarray(bb[:, 2], np.float64),
            "y1": np.asarray(bb[:, 3], np.float64),
        }
        kind = "xz2"
        lon, lat = NormalizedLon(jp._BITS), NormalizedLat(jp._BITS)
        hx = (planes["x0"] + planes["x1"]) * 0.5
        hy = (planes["y0"] + planes["y1"]) * 0.5
    perm = None
    if n > 1 and not bool(np.all(keys[1:] >= keys[:-1])):
        perm = jp._argsort_u64(keys)
        keys = keys[perm]
        planes = {k: v[perm] for k, v in planes.items()}
        hx, hy = (planes["x"], planes["y"]) if kind == "z2" else (
            hx[perm], hy[perm]
        )
    hist_prefix = None
    if n:
        s = jp._BITS - hist_bits
        cx = np.asarray(lon.normalize(hx), np.int64) >> s
        cy = np.asarray(lat.normalize(hy), np.int64) >> s
        side = 1 << hist_bits
        H = np.bincount(
            (cy << hist_bits) | cx, minlength=side * side
        ).reshape(side, side)
        S = np.zeros((side + 1, side + 1), np.int64)
        S[1:, 1:] = H.cumsum(0).cumsum(1)
        hist_prefix = S
    return JoinIndex(
        kind, sfc, keys, perm, planes, lon, lat, hist_prefix, hist_bits,
        gen=gen,
    )


def build_envelope_layout(
    envs, hist_bits: "int | None" = None, precision: int = 12,
    gen: int = 0,
) -> JoinIndex:
    """XZ-encode raw ``(n, 4)`` [xmin, ymin, xmax, ymax] envelopes into
    a join layout with no FeatureBatch behind them — the continuous-
    query registry's subscription side: its geofences are encoded ONCE
    per registry generation here, then every acked append batch joins
    against the layout as one fused launch (`JoinEngine(jidx=...)`).
    Envelope-overlap pairs are exact for box predicates; dwithin/
    attribute residuals refine the emitted pairs."""
    from geomesa_tpu.curves.normalize import NormalizedLat, NormalizedLon
    from geomesa_tpu.curves.xz2 import XZ2SFC

    if hist_bits is None:
        hist_bits = _join_conf()["hist_bits"]
    bb = np.asarray(envs, np.float64).reshape(-1, 4)
    n = len(bb)
    sfc = XZ2SFC(precision)
    keys = (
        np.asarray(
            sfc.index(bb[:, 0], bb[:, 1], bb[:, 2], bb[:, 3]), np.uint64
        )
        if n
        else np.empty(0, np.uint64)
    )
    planes = {
        "x0": np.asarray(bb[:, 0], np.float64),
        "y0": np.asarray(bb[:, 1], np.float64),
        "x1": np.asarray(bb[:, 2], np.float64),
        "y1": np.asarray(bb[:, 3], np.float64),
    }
    lon, lat = NormalizedLon(jp._BITS), NormalizedLat(jp._BITS)
    perm = None
    if n > 1 and not bool(np.all(keys[1:] >= keys[:-1])):
        perm = jp._argsort_u64(keys)
        keys = keys[perm]
        planes = {k: v[perm] for k, v in planes.items()}
    hist_prefix = None
    if n:
        hx = (planes["x0"] + planes["x1"]) * 0.5
        hy = (planes["y0"] + planes["y1"]) * 0.5
        s = jp._BITS - hist_bits
        cx = np.asarray(lon.normalize(hx), np.int64) >> s
        cy = np.asarray(lat.normalize(hy), np.int64) >> s
        side = 1 << hist_bits
        H = np.bincount(
            (cy << hist_bits) | cx, minlength=side * side
        ).reshape(side, side)
        S = np.zeros((side + 1, side + 1), np.int64)
        S[1:, 1:] = H.cumsum(0).cumsum(1)
        hist_prefix = S
    return JoinIndex(
        "xz2", sfc, keys, perm, planes, lon, lat, hist_prefix, hist_bits,
        gen=gen,
    )


class JoinEngine:
    """One joinable left side. Construct over a resident index (the
    layout caches on it per staged generation) or a raw FeatureBatch.

    >>> eng = JoinEngine(di)
    >>> res = eng.join(envs)           # exact envelope-join pairs
    >>> res.rows, res.wins, res.report()
    """

    def __init__(self, di=None, batch=None, sft=None, sched=None,
                 mesh=None, jidx=None):
        if di is None and batch is None and jidx is None:
            raise ValueError(
                "JoinEngine needs a DeviceIndex, a batch or a prebuilt "
                "JoinIndex"
            )
        self.di = di
        self._batch = batch
        self._sft = sft if sft is not None else (
            di.sft if di is not None else None
        )
        self.sched = sched
        self.mesh = mesh
        #: a prebuilt layout (``build_envelope_layout``) — the push
        #: tier's encode-once subscription side
        self._own_jidx = jidx

    # -- layout ------------------------------------------------------------

    def prepare(self, conf=None) -> JoinIndex:
        """Build (or fetch the cached) join layout for the current
        staged generation — the join twin of the resident refresh."""
        conf = conf or _join_conf()
        if self.di is not None:
            gen = getattr(self.di, "_gen", 0)
            cached = self.di.__dict__.get("_join_index")
            if cached is not None and cached.gen == gen:
                return cached
            jidx = build_join_index(
                self.di._host_rows(), self._sft, conf["hist_bits"], gen=gen,
            )
            self.di.__dict__["_join_index"] = jidx
            return jidx
        if self._own_jidx is None:
            self._own_jidx = build_join_index(
                self._batch, self._sft, conf["hist_bits"],
            )
        return self._own_jidx

    # -- join --------------------------------------------------------------

    def join(self, envs, gate=None) -> JoinResult:
        """Exact envelope-join of the left side against ``envs``
        ((m, 4) [xmin, ymin, xmax, ymax]): for point layouts a pair
        means the point lies inside the window (inclusive, float64
        exact); for non-point layouts the row's envelope OVERLAPS the
        window (the topological-join coarse pass — the exact predicate
        refines the emitted pairs). ``gate`` is an optional bool mask
        over the left rows (base filter / visibility / validity) ANDed
        into every pair. Pairs come back sorted (window, row)."""
        from geomesa_tpu import ledger, metrics
        from geomesa_tpu.tracing import span

        conf = _join_conf()
        envs = np.asarray(envs, np.float64).reshape(-1, 4)
        m = len(envs)
        jidx = self.prepare(conf)
        if jidx.n == 0 or m == 0:
            return _empty_result(strategy="broadcast", engine="none")
        auto = _di_gate(self.di, jidx.n) if self.di is not None else None
        if auto is not None:
            gate = auto if gate is None else (gate & auto)
        t0 = time.perf_counter()
        with span("join.plan", windows=m, rows=jidx.n, kind=jidx.kind) as sp:
            plan = jp.plan_join(jidx, envs, conf)
            sp.set(
                strategy=plan.strategy, level=plan.level,
                runs=plan.n_runs, splits=plan.splits,
                est_candidates=plan.stats.est_candidates,
                est_pairs=plan.stats.est_pairs,
                skew=round(plan.stats.skew, 2),
            )
        plan_s = time.perf_counter() - t0
        engine = conf["engine"]
        if engine == "auto":
            # an attached mesh means device refinement; otherwise the
            # numpy twin on all-CPU platforms (mesh.sort.engine rule).
            # An EXPLICIT host pin always wins — it is the bit-identical
            # debug/oracle engine — and simply ignores the mesh.
            engine = "device" if self.mesh is not None else (
                "host" if _platform() == "cpu" else "device"
            )
        gate_sorted = jidx.sort_gate(gate)
        t1 = time.perf_counter()
        shards = 0
        with span(
            "join.refine", engine=engine, strategy=plan.strategy,
            runs=plan.n_runs,
        ) as sp:
            if engine == "device" and self.mesh is not None:
                zrows, wins, launches = self._execute_mesh(
                    jidx, plan, envs, gate_sorted, conf
                )
                shards = int(self.mesh.shape["shard"])
            elif engine == "device":
                zrows, wins, launches = self._execute_device(
                    jidx, plan, envs, gate_sorted, conf
                )
            else:
                zrows, wins, launches = self._execute_host(
                    jidx, plan, envs, gate_sorted, conf
                )
            orig = jidx.to_orig(zrows)
            if jidx.perm is not None or shards > 1:
                order = _pair_order(wins, orig)
                orig, wins = orig[order], wins[order]
            sp.set(
                launches=launches, candidates=plan.candidates,
                pairs=len(orig),
            )
        refine_s = time.perf_counter() - t1
        metrics.join_queries.inc(strategy=plan.strategy)
        metrics.join_candidates.inc(plan.candidates)
        metrics.join_pairs.inc(len(orig))
        metrics.join_launches.inc(launches)
        if plan.splits:
            metrics.join_skew_splits.inc(plan.splits)
        metrics.join_plan_seconds.observe(plan_s)
        metrics.join_refine_seconds.observe(refine_s)
        ledger.charge("join_candidates", plan.candidates)
        ledger.charge("join_pairs", len(orig))
        return JoinResult(
            orig, wins.astype(np.int64), strategy=plan.strategy,
            level=plan.level, engine=engine, launches=launches,
            candidates=plan.candidates, splits=plan.splits, shards=shards,
            plan_s=plan_s, refine_s=refine_s, stats=plan.stats,
        )

    # -- execution engines -------------------------------------------------

    def _run(self, fn, device: bool):
        """One refinement batch, riding the scheduler when present (the
        batch lane: joins are bulk analytics; device batches arm the
        launch watchdog like every other resident launch)."""
        if self.sched is None:
            return fn()
        from geomesa_tpu.sched.scheduler import LANE_BATCH

        return self.sched.run(
            fn=fn, lane=LANE_BATCH, device=device, deadline_ms=None
        )

    def _batches(self, plan, budget: int):
        """Run-aligned batch boundaries: maximal run prefixes whose
        candidate totals stay under the launch budget (skew-splitting
        bounded every run below it)."""
        lens = (plan.ends - plan.starts).astype(np.int64)
        csum = np.cumsum(lens)
        R = len(lens)
        out = []
        i = 0
        done = 0
        while i < R:
            j = int(np.searchsorted(csum, done + budget, side="right"))
            j = max(j, i + 1)
            out.append((i, j))
            done = int(csum[j - 1])
            i = j
        return out

    def _execute_host(self, jidx, plan, envs, gate, conf):
        rows_out: list = []
        wins_out: list = []
        launches = 0
        pl = jidx.planes
        for i, j in self._batches(plan, conf["batch_candidates"]):

            def _one(i=i, j=j):
                rows, winv, iflag = jops.expand_runs(
                    plan.starts[i:j], plan.ends[i:j] - plan.starts[i:j],
                    plan.wins[i:j], plan.interior[i:j],
                )
                if jidx.point:
                    hit = jops.refine_host(
                        pl["x"], pl["y"], envs, rows, winv, iflag, gate
                    )
                else:
                    hit = jops.refine_host_env(
                        pl["x0"], pl["y0"], pl["x1"], pl["y1"], envs,
                        rows, winv, iflag, gate,
                    )
                return rows[hit], winv[hit]

            r, w = self._run(_one, device=False)
            launches += 1
            if len(r):
                rows_out.append(r)
                wins_out.append(w)
        if not rows_out:
            e = np.empty(0, np.int64)
            return e, e.copy(), launches
        return np.concatenate(rows_out), np.concatenate(wins_out), launches

    def _device_args(self, jidx, plan, i, j, envs_dev):
        """Pad one run batch to its power-of-two buckets and stage the
        small run arrays (starts/lens/csum/wins/interior)."""
        import jax.numpy as jnp

        starts = plan.starts[i:j]
        lens = (plan.ends[i:j] - plan.starts[i:j]).astype(np.int64)
        winv = plan.wins[i:j]
        iflag = plan.interior[i:j]
        keep = lens > 0
        if not np.all(keep):
            starts, lens, winv, iflag = (
                starts[keep], lens[keep], winv[keep], iflag[keep],
            )
        total = int(lens.sum())
        if total == 0:
            return None
        R = jops.next_pow2(max(len(lens), 16))
        C = jops.next_pow2(max(total, 1024))
        csum = np.cumsum(lens)
        pad = R - len(lens)
        if pad:
            starts = np.concatenate([starts, np.zeros(pad, np.int64)])
            lens = np.concatenate([lens, np.zeros(pad, np.int64)])
            winv = np.concatenate([winv, np.zeros(pad, np.int64)])
            iflag = np.concatenate([iflag, np.zeros(pad, bool)])
            csum = np.concatenate([csum, np.full(pad, total, np.int64)])
        return (
            jnp.asarray(starts.astype(np.int32)),
            jnp.asarray(lens.astype(np.int32)),
            jnp.asarray(csum.astype(np.int32)),
            jnp.asarray(winv.astype(np.int32)),
            jnp.asarray(iflag),
            envs_dev,
            np.int32(total),
        ), R, C, total

    def _execute_device(self, jidx, plan, envs, gate, conf):
        import jax.numpy as jnp

        planes = jidx.device_planes()
        names = ("x", "y") if jidx.point else ("x0", "y0", "x1", "y1")
        pvals = tuple(planes[k] for k in names)
        dt = np.dtype(pvals[0].dtype)
        exact = dt == np.float64
        envs_dev = _stage_envs(envs, dt)
        gate_dev = jnp.asarray(gate) if gate is not None else None
        gated = gate_dev is not None
        n_pl = len(pvals)
        rows_out: list = []
        wins_out: list = []
        launches = 0
        from geomesa_tpu import ledger

        for i, j in self._batches(plan, conf["batch_candidates"]):
            packed = self._device_args(jidx, plan, i, j, envs_dev)
            if packed is None:
                continue
            args, R, C, total = packed

            def _one(args=args, C=C):
                with ledger.compile_scope(f"join.refine:C={C}"), \
                        _lane_ctx(exact):
                    cfn = jops.count_kernel(C, n_pl, gated, dt)
                    cnt = int(cfn(pvals, *args, gate_dev))
                    if cnt == 0:
                        return None, 1  # count launch only
                    cap = min(jops.next_pow2(cnt), C)
                    kfn = jops.compact_kernel(C, cap, n_pl, gated, dt)
                    rbuf, wbuf, k = kfn(pvals, *args, gate_dev)
                k = int(k)
                return (
                    np.asarray(rbuf)[:k].astype(np.int64),  # lint: disable=GT004(intended sync: the compacted-pairs fetch that ENDS this launch)
                    np.asarray(wbuf)[:k].astype(np.int64),  # lint: disable=GT004(intended sync: the compacted-pairs fetch that ENDS this launch)
                ), 2

            got, ran = self._run(_one, device=True)
            launches += ran
            if got is not None and len(got[0]):
                rows_out.append(got[0])
                wins_out.append(got[1])
        if not rows_out:
            e = np.empty(0, np.int64)
            return e, e.copy(), launches
        rows = np.concatenate(rows_out)
        wins = np.concatenate(wins_out)
        if not exact:
            rows, wins = _post_exact(jidx, rows, wins, envs)
        return rows, wins, launches

    def _execute_mesh(self, jidx, plan, envs, gate, conf):
        """Co-partitioned SPMD refinement: runs clip at shard row
        boundaries (``join.partition``), then every batch is ONE
        count launch + ONE compact launch across the whole mesh — each
        shard expands and refines only its own resident slab, so no row
        ever crosses a shard (exchanged_bytes=0 by construction)."""
        import jax.numpy as jnp
        from geomesa_tpu.tracing import span

        mesh = self.mesh
        axis = "shard"
        S = int(mesh.shape[axis])
        planes, local_n = jidx.mesh_planes(mesh, axis)
        names = ("x", "y") if jidx.point else ("x0", "y0", "x1", "y1")
        pvals = tuple(planes[k] for k in names)
        n_pl = len(pvals)
        with span("join.partition", shards=S, runs=plan.n_runs) as sp:
            shard_runs = jp.clip_runs_to_shards(plan, local_n, S)
            sp.set(
                clipped_runs=sum(len(r[0]) for r in shard_runs),
                exchanged_bytes=0,
            )
        if gate is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            g = gate
            if S * local_n > len(g):
                g = np.concatenate(
                    [g, np.zeros(S * local_n - len(g), bool)]
                )
            gate_dev = jax.device_put(g, NamedSharding(mesh, P(axis)))
        else:
            gate_dev = None
        gated = gate_dev is not None
        dt = np.dtype(pvals[0].dtype)
        exact = dt == np.float64
        envs_dev = _stage_envs(envs, dt)
        budget = conf["batch_candidates"]
        # per-shard batch boundaries (each shard advances greedily under
        # the budget; the launch count is the max across shards)
        cursors = [0] * S
        csums = []
        for s in range(S):
            lens = shard_runs[s][1]
            csums.append(np.cumsum(lens) if len(lens) else np.zeros(0))
        rows_out: list = []
        wins_out: list = []
        launches = 0
        from geomesa_tpu import ledger

        while any(
            cursors[s] < len(shard_runs[s][0]) for s in range(S)
        ):
            batch = []
            maxR = 16
            maxC = 1024
            for s in range(S):
                st, ln, wn, fl = shard_runs[s]
                i = cursors[s]
                if i >= len(st):
                    batch.append(None)
                    continue
                done = csums[s][i - 1] if i else 0
                j = int(
                    np.searchsorted(csums[s], done + budget, side="right")
                )
                j = max(j, i + 1)
                batch.append((i, j))
                cursors[s] = j
                maxR = max(maxR, j - i)
                maxC = max(maxC, int(csums[s][j - 1] - done))
            R = jops.next_pow2(maxR)
            C = jops.next_pow2(maxC)
            starts = np.zeros(S * R, np.int32)
            lens = np.zeros(S * R, np.int32)
            csum = np.zeros(S * R, np.int32)
            winv = np.zeros(S * R, np.int32)
            iflag = np.zeros(S * R, bool)
            for s in range(S):
                if batch[s] is None:
                    continue
                i, j = batch[s]
                st, ln, wn, fl = shard_runs[s]
                k = j - i
                starts[s * R: s * R + k] = st[i:j]
                lens[s * R: s * R + k] = ln[i:j]
                winv[s * R: s * R + k] = wn[i:j]
                iflag[s * R: s * R + k] = fl[i:j]
                c = np.cumsum(ln[i:j])
                csum[s * R: s * R + k] = c
                csum[s * R + k: (s + 1) * R] = c[-1] if k else 0
            sharded = _shard_small(
                mesh, axis, starts, lens, csum, winv, iflag
            )
            with ledger.compile_scope(f"join.mesh:C={C}"), \
                    _lane_ctx(exact):
                cfn = jops.mesh_count_kernel(
                    mesh, axis, C, n_pl, gated, dt
                )
                args = list(pvals) + sharded + [envs_dev]
                if gated:
                    args.append(gate_dev)
                counts = np.asarray(cfn(*args))  # lint: disable=GT004(intended sync: the per-shard count fetch that ends the mesh count launch)
                launches += 1
                top = int(counts.max()) if len(counts) else 0
                if top:
                    cap = min(jops.next_pow2(top), C)
                    kfn = jops.mesh_join_kernel(
                        mesh, axis, C, cap, n_pl, gated, dt
                    )
                    rbuf, wbuf, cnts = kfn(*args)
                    launches += 1
                    rbuf = np.asarray(rbuf)  # lint: disable=GT004(intended sync: the result-buffer fetch that ends the mesh join launch)
                    wbuf = np.asarray(wbuf)  # lint: disable=GT004(intended sync: the result-buffer fetch that ends the mesh join launch)
                    cnts = np.asarray(cnts)  # lint: disable=GT004(intended sync: the result-buffer fetch that ends the mesh join launch)
                    for s in range(S):
                        k = int(cnts[s])
                        if k:
                            rows_out.append(
                                rbuf[s * cap: s * cap + k].astype(np.int64)
                            )
                            wins_out.append(
                                wbuf[s * cap: s * cap + k].astype(np.int64)
                            )
        if not rows_out:
            e = np.empty(0, np.int64)
            return e, e.copy(), launches
        rows = np.concatenate(rows_out)
        wins = np.concatenate(wins_out)
        keep = rows < jidx.n  # global-tail padding can never match, but
        rows, wins = rows[keep], wins[keep]  # clamp defensively anyway
        if not exact:
            rows, wins = _post_exact(jidx, rows, wins, envs)
        return rows, wins, launches


def filter_gate(di, f) -> np.ndarray:
    """One row gate from a filter over a resident index's staged rows
    (the frame/process join entry points share this): ``di.mask``
    evaluates ANY filter shape — device kernels with host fallback —
    with validity and the fail-closed visibility verdict ANDed in; rows
    past the mask's length stay gated off."""
    m = np.asarray(di.mask(f))
    n = len(di._host_rows())
    g = np.zeros(n, bool)
    g[: min(len(m), n)] = m[:n]
    return g


def _di_gate(di, n: int) -> "np.ndarray | None":
    """The resident index's implicit row gate: validity (streaming
    eviction / padding) ANDed with the fail-closed visibility verdict
    (no auths on the library join path — labeled rows hide, the store
    semantics). None when the index has neither."""
    hv = di._host_valid()
    vis = getattr(di, "_visid_np", None)
    if hv is None and vis is None:
        return None
    g = np.ones(n, bool)
    if hv is not None:
        k = min(len(hv), n)
        g[:k] &= hv[:k]
    if vis is not None:
        g = di._apply_auths_np(g, None)
    return g


def _lane_ctx(exact: bool):
    """64-bit lane scope for float64-exact device refinement (the
    kernels must TRACE under it, not just receive f64 operands); f32
    candidate refinement traces under the platform default."""
    if not exact:
        from contextlib import nullcontext

        return nullcontext()
    import jax

    return jax.enable_x64(True)


def _stage_envs(envs: np.ndarray, dt: np.dtype):
    """Stage the window envelopes at the planes' dtype. float64 planes
    get the envelopes bit-exact (64-bit lanes); float32 storage widens
    each envelope one ulp OUTWARD so the device pass stays a candidate
    superset — the emitted pairs then re-test against the float64 host
    planes (:func:`_post_exact`), bit-identical either way."""
    import jax.numpy as jnp

    env_host = envs.astype(dt)
    if dt != np.float64:
        env_host[:, 0] = np.nextafter(env_host[:, 0], dt.type(-np.inf))
        env_host[:, 1] = np.nextafter(env_host[:, 1], dt.type(-np.inf))
        env_host[:, 2] = np.nextafter(env_host[:, 2], dt.type(np.inf))
        env_host[:, 3] = np.nextafter(env_host[:, 3], dt.type(np.inf))
        return jnp.asarray(env_host)
    try:
        import jax

        with jax.enable_x64(True):
            out = jnp.asarray(env_host)
        if out.dtype == np.float64:
            return out
    except Exception:  # pragma: no cover - platform without x64  # lint: disable=GT011(x64 capability probe: the f32 staging below is the designed fallback)
        pass
    return jnp.asarray(env_host.astype(np.float32))


def _post_exact(jidx, rows, wins, envs):
    """Float32 exactness pass: re-test device-emitted candidate pairs
    against the float64 host planes (interior-run pairs pass
    trivially — their membership argument lives in integer cell space)."""
    pl = jidx.planes
    iflag = np.zeros(len(rows), bool)
    if jidx.point:
        hit = jops.refine_host(
            pl["x"], pl["y"], envs, rows, wins, iflag, None
        )
    else:
        hit = jops.refine_host_env(
            pl["x0"], pl["y0"], pl["x1"], pl["y1"], envs, rows, wins,
            iflag, None,
        )
    return rows[hit], wins[hit]


def _shard_small(mesh, axis, *arrays):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis))
    return [jax.device_put(a, sharding) for a in arrays]


def _pair_order(wins, orig) -> np.ndarray:
    """Canonical (window, original-row) pair order — native radix with
    the numpy lexsort fallback (bit-identical)."""
    from geomesa_tpu import native

    got = native.radix_argsort([wins, orig])
    if got is not None:
        return got
    return np.lexsort((orig, wins))


def _platform() -> str:
    import jax

    return jax.devices()[0].platform
