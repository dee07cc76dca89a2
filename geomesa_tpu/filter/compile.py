"""Filter compilation: AST -> vectorized evaluators.

Two targets (mirrors the reference's split between key-range planning and
per-feature iterator evaluation, ref: geomesa-accumulo iterators/
FilterTransformIterator + Z3Iterator [UNVERIFIED - empty reference mount]):

- **host**: exact numpy evaluation over a FeatureBatch. Supports the whole
  AST including object columns (strings, non-point geometries). This is the
  correctness oracle and the residual evaluator.
- **device**: a jit-compatible function over a dict of jax arrays for the
  device-scannable subset (numeric/temporal compares, bbox, point-in-polygon
  on point columns). The filter is CNF-split: supported conjuncts fuse into
  one device mask; the remainder becomes the host residual applied to
  device-surviving candidates.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import ast
from geomesa_tpu.geom import Envelope, Point, Polygon, points_in_polygon
from geomesa_tpu.geom.predicates import (
    geometry_intersects,
    geometry_within,
    points_in_polygon_jax,
)


# ---------------------------------------------------------------------------
# host (exact, numpy)
# ---------------------------------------------------------------------------


def evaluate_host(f: ast.Filter, batch: FeatureBatch) -> np.ndarray:
    """Exact boolean mask for the full filter over a batch."""
    n = len(batch)
    if f is ast.Include:
        return np.ones(n, dtype=bool)
    if f is ast.Exclude:
        return np.zeros(n, dtype=bool)
    if isinstance(f, ast.And):
        m = np.ones(n, dtype=bool)
        for c in f.children:
            m &= evaluate_host(c, batch)
        return m
    if isinstance(f, ast.Or):
        m = np.zeros(n, dtype=bool)
        for c in f.children:
            m |= evaluate_host(c, batch)
        return m
    if isinstance(f, ast.Not):
        return ~evaluate_host(f.child, batch)
    if isinstance(f, ast.BBox):
        return _host_bbox(f, batch)
    if isinstance(f, (ast.Intersects, ast.DWithin)):
        return _host_spatial(f, batch)
    if isinstance(f, ast.During):
        col = batch.column(f.attr)
        return (col >= f.t0) & (col <= f.t1)
    if isinstance(f, ast.Between):
        col = batch.column(f.attr)
        return (col >= f.lo) & (col <= f.hi)
    if isinstance(f, ast.Compare):
        col = batch.column(f.attr)
        v = f.value
        if f.op == "=":
            return col == v
        if f.op == "<>":
            return col != v
        if f.op == "<":
            return col < v
        if f.op == "<=":
            return col <= v
        if f.op == ">":
            return col > v
        if f.op == ">=":
            return col >= v
        raise ValueError(f.op)
    if isinstance(f, ast.In):
        col = batch.column(f.attr)
        return np.isin(col, np.array(list(f.values), dtype=col.dtype if col.dtype != object else object))
    if isinstance(f, ast.Like):
        col = batch.column(f.attr)
        pat = re.compile(f.regex())
        return np.array(
            [v is not None and pat.match(str(v)) is not None for v in col],
            dtype=bool,
        )
    if isinstance(f, ast.IsNull):
        col = batch.column(f.attr)
        if col.dtype == object:
            m = np.array([v is None for v in col], dtype=bool)
        else:
            m = np.zeros(len(col), dtype=bool)
        return ~m if f.negate else m
    raise TypeError(f"cannot evaluate {type(f)}")


def _host_bbox(f: ast.BBox, batch: FeatureBatch) -> np.ndarray:
    desc = batch.sft.descriptor(f.attr)
    if desc.is_point:
        x, y = batch.point_coords(f.attr)
        return (x >= f.xmin) & (x <= f.xmax) & (y >= f.ymin) & (y <= f.ymax)
    bb = batch.bboxes(f.attr)
    return (
        (bb[:, 2] >= f.xmin)
        & (bb[:, 0] <= f.xmax)
        & (bb[:, 3] >= f.ymin)
        & (bb[:, 1] <= f.ymax)
    )


def _host_spatial(f, batch: FeatureBatch) -> np.ndarray:
    desc = batch.sft.descriptor(f.attr)
    geom = f.geometry
    if isinstance(f, ast.DWithin):
        # expand: for point query geometry, distance test; else envelope pad
        if desc.is_point and isinstance(geom, Point):
            x, y = batch.point_coords(f.attr)
            return (x - geom.x) ** 2 + (y - geom.y) ** 2 <= f.distance**2
        e = geom.envelope
        env = Envelope(
            e.xmin - f.distance,
            e.ymin - f.distance,
            e.xmax + f.distance,
            e.ymax + f.distance,
        )
        return _host_bbox(
            ast.BBox(f.attr, env.xmin, env.ymin, env.xmax, env.ymax), batch
        )
    if isinstance(f, ast.Intersects) and f.op in (
        "crosses",
        "touches",
        "overlaps",
        "equals",
        "relate",
    ):
        return _host_relation(f, batch, desc)
    if desc.is_point:
        x, y = batch.point_coords(f.attr)
        if f.op == "contains" and not isinstance(geom, Point):
            # a point can only contain a point
            return np.zeros(len(batch), dtype=bool)
        if isinstance(geom, Point):
            m = (x == geom.x) & (y == geom.y)
        elif hasattr(geom, "rings"):
            m = points_in_polygon(x, y, geom.rings()) if isinstance(geom, Polygon) else _points_in_multi(x, y, geom)
            # boundary note: crossing-number treats boundary points per
            # half-open rule; GeoMesa/JTS intersects includes boundaries --
            # acceptable divergence at float boundary measure zero.
        else:  # linestring vs point: envelope fallback
            e = geom.envelope
            m = (x >= e.xmin) & (x <= e.xmax) & (y >= e.ymin) & (y <= e.ymax)
        return ~m if f.op == "disjoint" else m
    # non-point data: bbox prefilter then exact per-candidate
    bb = batch.bboxes(f.attr)
    e = geom.envelope
    cand = (
        (bb[:, 2] >= e.xmin)
        & (bb[:, 0] <= e.xmax)
        & (bb[:, 3] >= e.ymin)
        & (bb[:, 1] <= e.ymax)
    )
    col = batch.column(f.attr)
    out = np.zeros(len(batch), dtype=bool)
    if f.op == "within":  # data geometry within query geometry
        for i in np.nonzero(cand)[0]:
            out[i] = geometry_within(col[i], geom)
        return out
    if f.op == "contains":  # data geometry contains query geometry
        for i in np.nonzero(cand)[0]:
            out[i] = geometry_within(geom, col[i])
        return out
    for i in np.nonzero(cand)[0]:
        out[i] = geometry_intersects(col[i], geom)
    return ~out if f.op == "disjoint" else out


def _host_relation(f: "ast.Intersects", batch: FeatureBatch, desc) -> np.ndarray:
    """CROSSES / TOUCHES / OVERLAPS / EQUALS / RELATE residual evaluation:
    bbox prefilter, then the exact DE-9IM-lite predicate per candidate
    (data geometry as first operand, matching ECQL argument order).
    RELATE patterns can match disjoint features, so it skips the prefilter."""
    from geomesa_tpu.geom.predicates import (
        geometry_crosses,
        geometry_overlaps,
        geometry_relate_matches,
        geometry_touches,
    )

    geom = f.geometry
    if desc.is_point:
        x, y = batch.point_coords(f.attr)

        def rowgeom(i):
            return Point(float(x[i]), float(y[i]))

    else:
        col = batch.column(f.attr)

        def rowgeom(i):
            return col[i]

    if f.op == "relate":
        cand = np.arange(len(batch))
        fn = lambda g: geometry_relate_matches(g, geom, f.pattern)
    else:
        e = geom.envelope
        cand = np.nonzero(
            _host_bbox(ast.BBox(f.attr, e.xmin, e.ymin, e.xmax, e.ymax), batch)
        )[0]
        if f.op == "crosses":
            fn = lambda g: geometry_crosses(g, geom)
        elif f.op == "touches":
            fn = lambda g: geometry_touches(g, geom)
        elif f.op == "overlaps":
            fn = lambda g: geometry_overlaps(g, geom)
        else:  # equals via the DE-9IM equality mask
            fn = lambda g: geometry_relate_matches(g, geom, "T*F**FFF*")
    out = np.zeros(len(batch), dtype=bool)
    for i in cand:
        out[i] = fn(rowgeom(i))
    return out


def _points_in_multi(x, y, geom) -> np.ndarray:
    m = np.zeros(len(x), dtype=bool)
    for p in getattr(geom, "polygons", ()):
        m |= points_in_polygon(x, y, p.rings())
    return m


# ---------------------------------------------------------------------------
# device (jax)
# ---------------------------------------------------------------------------


def _device_supported(f: ast.Filter, sft: SimpleFeatureType) -> bool:
    if f in (ast.Include, ast.Exclude):
        return True
    if isinstance(f, (ast.And, ast.Or)):
        return all(_device_supported(c, sft) for c in f.children)
    if isinstance(f, ast.Not):
        return _device_supported(f.child, sft)
    if isinstance(f, ast.BBox):
        # point: coordinate compare; non-point: envelope-overlap compare on
        # the staged bbox planes — which IS the exact BBOX semantics
        # (_host_bbox evaluates envelope intersection for non-points)
        return sft.descriptor(f.attr).is_geometry
    if isinstance(f, ast.Intersects):
        return (
            sft.descriptor(f.attr).is_point
            and hasattr(f.geometry, "rings")
            and f.op in ("intersects", "within", "disjoint")
        )
    if isinstance(f, ast.DWithin):
        # (point, Point): exact distance compare. Every other shape's
        # exact host semantics (_host_spatial) IS the padded-envelope
        # bbox — the same compare runs on device instead.
        return sft.descriptor(f.attr).is_geometry
    if isinstance(f, (ast.During, ast.Between)):
        dtype = sft.descriptor(f.attr).column_dtype
        return dtype is not None and dtype != np.bool_
    if isinstance(f, (ast.Compare, ast.In)):
        dtype = sft.descriptor(f.attr).column_dtype
        return (
            dtype is not None
            and dtype != np.bool_
            and all(
                isinstance(v, (int, float))
                for v in (f.values if isinstance(f, ast.In) else (f.value,))
            )
        )
    return False


def _is_i64(sft: SimpleFeatureType, attr: str) -> bool:
    return sft.descriptor(attr).column_dtype == np.int64


def device_columns_for(f: ast.Filter, sft: SimpleFeatureType) -> list[str]:
    """Device column names needed: ``attr`` for scalars, ``attr__x/__y`` for
    point geometries, ``attr__hi/__lo`` int32/uint32 planes for int64
    scalars (Date/Long -- see ops/int64lanes.py)."""
    cols: list[str] = []
    for attr in sorted(ast.attributes_of(f)):
        desc = sft.descriptor(attr)
        if desc.is_point:
            cols += [f"{attr}__x", f"{attr}__y"]
        elif desc.is_geometry:
            # non-point geometries: per-row envelope planes
            cols += [f"{attr}__x0", f"{attr}__y0",
                     f"{attr}__x1", f"{attr}__y1"]
        elif desc.column_dtype == np.int64:
            cols += [f"{attr}__hi", f"{attr}__lo"]
        elif desc.column_dtype is not None:
            cols.append(attr)
    return cols


def build_device_fn(f: ast.Filter, sft: SimpleFeatureType) -> Callable:
    """AST -> fn(cols: dict[str, jnp.ndarray]) -> bool mask. Caller must
    have checked _device_supported."""

    def rec(node):
        import jax.numpy as jnp

        if node is ast.Include:
            return lambda cols, n: jnp.ones(n, dtype=bool)
        if node is ast.Exclude:
            return lambda cols, n: jnp.zeros(n, dtype=bool)
        if isinstance(node, ast.And):
            fns = [rec(c) for c in node.children]
            def f_and(cols, n, fns=fns):
                m = fns[0](cols, n)
                for fn in fns[1:]:
                    m = m & fn(cols, n)
                return m
            return f_and
        if isinstance(node, ast.Or):
            fns = [rec(c) for c in node.children]
            def f_or(cols, n, fns=fns):
                m = fns[0](cols, n)
                for fn in fns[1:]:
                    m = m | fn(cols, n)
                return m
            return f_or
        if isinstance(node, ast.Not):
            fn = rec(node.child)
            return lambda cols, n, fn=fn: ~fn(cols, n)
        if isinstance(node, ast.BBox):
            if not sft.descriptor(node.attr).is_point:
                pre = f"{node.attr}__"
                def f_bbenv(cols, n, node=node, pre=pre):
                    # envelope overlap == exact BBOX for non-points
                    return (
                        (cols[pre + "x1"] >= node.xmin)
                        & (cols[pre + "x0"] <= node.xmax)
                        & (cols[pre + "y1"] >= node.ymin)
                        & (cols[pre + "y0"] <= node.ymax)
                    )
                return f_bbenv
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"
            def f_bbox(cols, n, node=node, ax=ax, ay=ay):
                x, y = cols[ax], cols[ay]
                return (
                    (x >= node.xmin)
                    & (x <= node.xmax)
                    & (y >= node.ymin)
                    & (y <= node.ymax)
                )
            return f_bbox
        if isinstance(node, ast.Intersects):
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"
            rings = node.geometry.rings()
            neg = node.op == "disjoint"
            def f_int(cols, n, rings=rings, ax=ax, ay=ay, neg=neg):
                m = points_in_polygon_jax(cols[ax], cols[ay], rings)
                return ~m if neg else m
            return f_int
        if isinstance(node, ast.DWithin):
            if not (
                sft.descriptor(node.attr).is_point
                and isinstance(node.geometry, Point)
            ):
                # padded-envelope bbox == the exact host semantics for
                # these shapes (_host_spatial)
                e = node.geometry.envelope
                return rec(ast.BBox(
                    node.attr,
                    e.xmin - node.distance, e.ymin - node.distance,
                    e.xmax + node.distance, e.ymax + node.distance,
                ))
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"
            def f_dw(cols, n, node=node, ax=ax, ay=ay):
                dx = cols[ax] - node.geometry.x
                dy = cols[ay] - node.geometry.y
                return dx * dx + dy * dy <= node.distance**2
            return f_dw
        if isinstance(node, (ast.During, ast.Between)):
            lo = node.t0 if isinstance(node, ast.During) else node.lo
            hi = node.t1 if isinstance(node, ast.During) else node.hi
            attr = node.attr
            if _is_i64(sft, attr):
                import math

                from geomesa_tpu.ops.int64lanes import cmp_jax

                def f_rng64(
                    cols, n, attr=attr, lo=math.ceil(lo), hi=math.floor(hi)
                ):
                    chi, clo = cols[f"{attr}__hi"], cols[f"{attr}__lo"]
                    return cmp_jax(">=", chi, clo, lo) & cmp_jax(
                        "<=", chi, clo, hi
                    )
                return f_rng64
            def f_rng(cols, n, attr=attr, lo=lo, hi=hi):
                c = cols[attr]
                return (c >= lo) & (c <= hi)
            return f_rng
        if isinstance(node, ast.Compare):
            attr, op, v = node.attr, node.op, node.value
            if _is_i64(sft, attr):
                import math

                from geomesa_tpu.ops.int64lanes import cmp_jax

                # Non-integer literals vs int64 lanes: round the bound so the
                # integer compare is equivalent ('>5.5' == '>=6' == '>5').
                if v != math.floor(v):
                    if op in ("=", "<>"):
                        const = op == "<>"
                        def f_const(cols, n, const=const):
                            import jax.numpy as jnp

                            some = next(iter(cols.values()))
                            return jnp.full(some.shape, const, dtype=bool)
                        return f_const
                    # c < 5.5 == c <= 5 ; c > 5.5 == c >= 6
                    if op in ("<", "<="):
                        op, v = "<=", math.floor(v)
                    else:
                        op, v = ">=", math.ceil(v)
                else:
                    v = int(v)

                def f_cmp64(cols, n, attr=attr, op=op, v=v):
                    return cmp_jax(op, cols[f"{attr}__hi"], cols[f"{attr}__lo"], v)
                return f_cmp64
            ops = {
                "=": lambda c: c == v,
                "<>": lambda c: c != v,
                "<": lambda c: c < v,
                "<=": lambda c: c <= v,
                ">": lambda c: c > v,
                ">=": lambda c: c >= v,
            }
            fn0 = ops[op]
            return lambda cols, n, attr=attr, fn0=fn0: fn0(cols[attr])
        if isinstance(node, ast.In):
            attr, vals = node.attr, node.values
            if _is_i64(sft, attr):
                import math

                from geomesa_tpu.ops.int64lanes import cmp_jax

                ivals = [int(v) for v in vals if v == math.floor(v)]

                def f_in64(cols, n, attr=attr, ivals=ivals):
                    import jax.numpy as jnp

                    chi, clo = cols[f"{attr}__hi"], cols[f"{attr}__lo"]
                    if not ivals:
                        return jnp.zeros(chi.shape, dtype=bool)
                    m = cmp_jax("=", chi, clo, ivals[0])
                    for v in ivals[1:]:
                        m = m | cmp_jax("=", chi, clo, v)
                    return m
                return f_in64
            def f_in(cols, n, attr=attr, vals=vals):
                c = cols[attr]
                m = c == vals[0]
                for v in vals[1:]:
                    m = m | (c == v)
                return m
            return f_in
        raise TypeError(f"not device-supported: {type(node)}")

    inner = rec(f)

    def device_fn(cols: dict):
        n = next(iter(cols.values())).shape[0] if cols else 0
        return inner(cols, n)

    return device_fn


# ---------------------------------------------------------------------------
# CompiledFilter
# ---------------------------------------------------------------------------


@dataclass
class CompiledFilter:
    filter: ast.Filter
    sft: SimpleFeatureType
    device_part: ast.Filter  # conjuncts evaluable on device
    residual_part: ast.Filter  # exact host remainder (Include if none)
    device_fn: Callable  # dict[str, jnp.ndarray] -> bool mask
    device_cols: list

    @property
    def fully_on_device(self) -> bool:
        return self.residual_part is ast.Include

    def pallas_scan(self, **kw):
        """(count_fn, mask_fn) Pallas TPU kernels for the device part, or
        None when the filter can't be tiled (callers use device_fn). Cached
        per CompiledFilter and option set."""
        if not hasattr(self, "_pallas"):
            self._pallas = {}
        key = tuple(sorted(kw.items()))
        if key not in self._pallas:
            from geomesa_tpu.ops.pallas_scan import (
                PallasUnsupported,
                build_pallas_scan,
            )

            try:
                count_fn, mask_fn, _ = build_pallas_scan(
                    self.device_part, self.sft, **kw
                )
                self._pallas[key] = (count_fn, mask_fn)
            except PallasUnsupported:
                self._pallas[key] = None
        return self._pallas[key]

    def jitted_scan(self):
        """(count_fn, mask_fn), jitted, choosing the Pallas tile kernels on
        real TPUs and XLA-fused jnp elsewhere (interpret-mode pallas would
        crawl) or when the filter isn't tileable. The single source of the
        kernel-selection rule (used by the query runner and DeviceIndex);
        cached per CompiledFilter."""
        if not hasattr(self, "_jitted_scan"):
            import jax

            scan = (
                self.pallas_scan()
                if jax.devices()[0].platform == "tpu"
                else None
            )
            if scan is not None:
                count_fn, mask_fn = jax.jit(scan[0]), jax.jit(scan[1])
            else:
                mask_fn = jax.jit(self.device_fn)
                count_fn = jax.jit(lambda c: self.device_fn(c).sum())
            self._jitted_scan = (count_fn, mask_fn)
            # which engine serves this filter (read by chip_smoke.py)
            self.scan_engine = "pallas" if scan is not None else "xla"
        return self._jitted_scan

    def host_mask(self, batch: FeatureBatch) -> np.ndarray:
        """Exact full-filter mask (oracle path)."""
        return evaluate_host(self.filter, batch)

    def residual_mask(self, batch: FeatureBatch) -> np.ndarray:
        return evaluate_host(self.residual_part, batch)


def _envelope_prefilter(c: ast.Filter, sft: SimpleFeatureType):
    """Device BBox prefilter implied by a residual spatial conjunct, or
    None. Safe only for ops where a hit's envelope must intersect the
    query geometry's envelope (everything except disjoint/relate — the
    complement/arbitrary-matrix cases)."""
    if isinstance(c, ast.Intersects) and c.op in (
        "intersects", "within", "contains", "crosses", "touches",
        "overlaps", "equals",
    ):
        if not sft.descriptor(c.attr).is_geometry:
            return None
        e = c.geometry.envelope
        return ast.BBox(c.attr, e.xmin, e.ymin, e.xmax, e.ymax)
    return None


def compile_filter(f: ast.Filter, sft: SimpleFeatureType) -> CompiledFilter:
    conjuncts = list(f.children) if isinstance(f, ast.And) else [f]
    dev = [c for c in conjuncts if _device_supported(c, sft)]
    res = [c for c in conjuncts if not _device_supported(c, sft)]
    # residual spatial conjuncts still contribute a device envelope
    # prefilter (the classic bbox-then-exact split): the conjunct stays in
    # the residual for exactness, but the device mask prunes candidates
    for c in res:
        pre = _envelope_prefilter(c, sft)
        if pre is not None and _device_supported(pre, sft):
            dev.append(pre)
    device_part: ast.Filter = (
        ast.Include if not dev else (dev[0] if len(dev) == 1 else ast.And(tuple(dev)))
    )
    residual_part: ast.Filter = (
        ast.Include if not res else (res[0] if len(res) == 1 else ast.And(tuple(res)))
    )
    return CompiledFilter(
        filter=f,
        sft=sft,
        device_part=device_part,
        residual_part=residual_part,
        device_fn=build_device_fn(device_part, sft),
        device_cols=device_columns_for(device_part, sft),
    )
