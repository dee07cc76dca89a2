"""Pallas TPU kernel for the fused predicate scan (count + mask).

This is the rebuild's server-side hot loop -- the reference's per-KV
``Z3Iterator.accept`` + ``FilterTransformIterator`` predicate evaluation
(geomesa-accumulo .../iterators/Z3Iterator.scala, FilterTransformIterator
[UNVERIFIED - empty reference mount]) -- expressed as one Pallas kernel:
each grid step DMAs a (block_rows, 128) tile of every referenced column
HBM->VMEM, evaluates the whole conjunction on the VPU in one pass, and
emits either a per-tile hit count (SMEM scalar) or the boolean mask tile.
One HBM read per byte of scanned data; no intermediate materialization.

Columns reaching the kernel are 32-bit lanes only: float32/int32/uint32
scalars, point coords as ``__x``/``__y`` float32, and int64 (Date/Long)
columns pre-split into ``__hi``/``__lo`` word planes (ops/int64lanes.py).
Filters whose device part needs anything else (float64 columns, polygons
over :data:`MAX_KERNEL_EDGES` edges) fall back to the XLA-fused jnp path
in filter/compile.py -- same semantics, same staged columns.

A filter with a point-in-polygon conjunct takes the polygon kernel
(:func:`_build_pip_scan`): the polygon's edge table, its envelope, the
edge loop's trip count and the DURING/BBOX bounds ride in SMEM as
runtime operands, so one compiled program serves every filter of the
same shape and edge bucket, the loop stops at each polygon's own edges
(not at its bucket's), and it runs only on the row blocks where some
row passes the cheap exact pretests the predicate implies (the
envelope, the time window); a grid tile where no row passes them skips
its block walk.

On CPU jax (tests / CI) the kernel runs in interpret mode, so the whole
suite exercises the identical kernel code without a TPU.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import ast
from geomesa_tpu.ops.int64lanes import cmp_jax

LANES = 128
#: a polygon's edge table is padded to a bucket of the shape ladder (a
#: power of two by default) between these, with degenerate y_lo == y_hi
#: edges, which never straddle: one compiled kernel serves every polygon
#: of a bucket, its loop running to the polygon's real edges. The table
#: rides in SMEM; polygons over the largest bucket take the XLA path
MIN_EDGE_BUCKET = 16
MAX_KERNEL_EDGES = 4096
#: sublane rows of a skip block of the polygon kernel (x 128 lanes): the
#: pretest and the edge loop run per block. 32 is the int8 tile of the
#: mask and of the validity plane; on a TPU v5e blocks of 8 rows spent
#: ~8.5 ms a call over 2^25 rows in their pretests alone (PERF.md)
SKIP_ROWS = 32
_EDGE_UNROLL = 8
_VMEM_BUDGET = 6 * 1024 * 1024


class PallasUnsupported(Exception):
    """Filter shape not expressible in the tile kernel; use device_fn."""


def _check(cond, why: str):
    if not cond:
        raise PallasUnsupported(why)


def supported_columns(f: ast.Filter, sft: SimpleFeatureType) -> list[str]:
    """Device columns the kernel will read; raises PallasUnsupported."""
    from geomesa_tpu.filter.compile import device_columns_for

    cols = device_columns_for(f, sft)
    for c in cols:
        if c.endswith(("__x", "__y", "__hi", "__lo",
                       "__x0", "__y0", "__x1", "__y1")):
            continue
        dtype = sft.descriptor(c).column_dtype
        _check(
            dtype in (np.float32, np.int32, np.float64),
            f"column {c}: dtype {dtype} not 32-bit-lane representable",
        )
        # float64 attribute columns are staged as-is for the jnp path; the
        # kernel would need a f32 downcast that can flip boundary compares.
        _check(dtype != np.float64, f"column {c} is float64")
    return cols


def _tile_shape(cols: dict):
    return next(iter(cols.values())).shape


def _tile_mats(cols: list, br: int, coldict: dict, valid):
    """(rows, grid steps, the columns as zero-padded (steps * br, 128)
    tiles, then the validity plane as int8, if any)."""
    import jax.numpy as jnp

    n = int(_tile_shape(coldict)[0])
    if n > 2**31 - 1 - br * LANES:
        raise PallasUnsupported("partition too large for int32 indexing")
    grid = max(1, -(-n // (br * LANES)))
    pad = grid * br * LANES - n
    mats = [jnp.pad(coldict[c], (0, pad)).reshape(grid * br, LANES)
            for c in cols]
    if valid is not None:
        # int8 tile (Mosaic has no bool memref); sublane rows are a
        # multiple of 32, the int8 tiling
        mats.append(jnp.pad(valid.astype(jnp.int8), (0, pad)).reshape(
            grid * br, LANES))
    return n, grid, mats


def _pick_block_rows(n_cols: int) -> int:
    rows = _VMEM_BUDGET // max(1, n_cols * LANES * 4)
    rows = max(64, min(1024, rows))
    return (rows // 32) * 32  # int8/int32 sublane multiple


# -- point in polygon ------------------------------------------------------


def edge_bucket(n_edges: int) -> int:
    """The bucket a polygon's edge table is padded to: the shape ladder's
    rung (a power of two by default), a multiple of the loop's unroll."""
    from geomesa_tpu.bucketing import bucket_cap

    b = bucket_cap(n_edges, MIN_EDGE_BUCKET)
    return -(-b // _EDGE_UNROLL) * _EDGE_UNROLL


def _real_edges(geometry) -> int:
    """A polygon's edges before its table is padded."""
    return sum(len(r) - 1 for r in geometry.rings())


def _loop_steps(n_edges: int) -> int:
    """Steps of the edge loop over ``n_edges`` real edges."""
    return -(-n_edges // _EDGE_UNROLL)


def edge_table(rings) -> np.ndarray:
    """(4, bucket) float32 table of closed rings' edges: per edge its
    lower y, upper y, the x at the lower y, and dx/dy. Endpoints are
    ordered by y, so an edge two rings share tests alike in both. The
    padding edges have y_lo == y_hi == 0 and never straddle."""
    from geomesa_tpu.geom.predicates import polygon_edges

    x1, y1, x2, y2 = polygon_edges(rings)
    up = y1 <= y2
    xa = np.where(up, x1, x2)
    ya, yb = np.where(up, y1, y2), np.where(up, y2, y1)
    dy = yb - ya
    slope = np.divide(np.where(up, x2, x1) - xa, dy, out=np.zeros_like(dy),
                      where=dy != 0)
    t = np.zeros((4, edge_bucket(len(xa))), np.float32)
    t[:, : len(xa)] = (ya, yb, xa, slope)
    return t


def _f32_bounds(lo: float, hi: float, outward: bool):
    """float32 bounds for ``lo <= v <= hi`` over float32 ``v``: exact
    (the nearest float32 inside), or ``outward`` (the nearest outside,
    for an envelope that must hold every vertex)."""
    a, b = np.float32(lo), np.float32(hi)
    down, up = np.float32(-np.inf), np.float32(np.inf)
    if outward:
        a = np.nextafter(a, down) if a > lo else a
        b = np.nextafter(b, up) if b < hi else b
    else:
        a = np.nextafter(a, up) if a < lo else a
        b = np.nextafter(b, down) if b > hi else b
    return a, b


class _TilePlan:
    """A filter's device part as a predicate over a tile of columns, for
    both kernels: ``full(cols, q)`` is the predicate; ``cheap(cols, q)``
    is implied by it and runs no edge loop (None: no pretest, every
    block is tested).

    With ``lift`` (the polygon kernel) BBOX bounds, polygon envelopes and
    edge tables go to ``floats``, the int64 bounds of DURING/BETWEEN to
    ``hi``/``lo`` as int32/uint32 words, each polygon's edge-loop trip
    count to ``hi`` (its ``lo`` word unused); ``q`` is (floats, hi, lo), read
    from SMEM at run time, so the filters of one ``key`` (attributes,
    ops, edge buckets, and the literals that stay in the program) share
    one program. Without it (the bbox kernel) every literal is baked in
    and ``q`` is unused: one program per filter text."""

    def __init__(self, f: ast.Filter, sft: SimpleFeatureType, lift: bool):
        self.sft, self.lift = sft, lift
        self._floats: list = []
        self._n_floats = 0
        self.hi: list = []
        self.lo: list = []
        self._key: list = []
        self.cheap, self.full = self._rec(f)
        self.key = " ".join(self._key)
        self.floats = np.concatenate(self._floats + [np.zeros(1, np.float32)])

    def _add_floats(self, vals) -> int:
        a = np.asarray(vals, np.float32).ravel()
        off = self._n_floats
        self._floats.append(a)
        self._n_floats += len(a)
        return off

    def _rec(self, node):
        """(cheap, full) of a node; ``cheap is full`` where the node
        holds no polygon."""
        import jax.numpy as jnp

        from geomesa_tpu.filter.compile import _device_supported, \
            build_device_fn
        from geomesa_tpu.geom import Point
        from geomesa_tpu.ops.int64lanes import cmp_lanes_jax, split_value

        sft, lift = self.sft, self.lift
        if isinstance(node, (ast.And, ast.Or)):
            is_and = isinstance(node, ast.And)
            self._key.append("And(" if is_and else "Or(")
            parts = [self._rec(c) for c in node.children]
            self._key.append(")")
            full = _fold([p[1] for p in parts], is_and)
            if all(c is fl for c, fl in parts):
                return full, full
            cheaps = [c for c, _ in parts if c is not None]
            if not is_and and len(cheaps) < len(parts):
                return None, full
            return (_fold(cheaps, is_and) if cheaps else None), full
        if isinstance(node, ast.Not):
            self._key.append("Not(")
            cheap, inner = self._rec(node.child)
            self._key.append(")")

            def neg(cols, q, inner=inner):
                return ~inner(cols, q)

            # the complement of a pretest is not implied by the complement
            return (neg if cheap is inner else None), neg
        if node is ast.Include or node is ast.Exclude:
            val = node is ast.Include
            self._key.append(repr(node))

            def const(cols, q, val=val):
                return jnp.full(_tile_shape(cols), val, dtype=bool)

            return const, const
        if isinstance(node, ast.BBox) and sft.descriptor(node.attr).is_point:
            if lift:
                x0, x1 = _f32_bounds(node.xmin, node.xmax, outward=False)
                y0, y1 = _f32_bounds(node.ymin, node.ymax, outward=False)
                fn = _box_fn(node.attr, self._add_floats([x0, y0, x1, y1]))
                self._key.append(f"BBox({node.attr})")
                return fn, fn
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"

            def f_bbox(cols, q, node=node, ax=ax, ay=ay):
                x, y = cols[ax], cols[ay]
                return (
                    (x >= node.xmin)
                    & (x <= node.xmax)
                    & (y >= node.ymin)
                    & (y <= node.ymax)
                )

            self._key.append(repr(node))
            return f_bbox, f_bbox
        if isinstance(node, ast.DWithin) and sft.descriptor(
                node.attr).is_point and isinstance(node.geometry, Point):
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"

            def f_dw(cols, q, node=node, ax=ax, ay=ay):
                dx = cols[ax] - node.geometry.x
                dy = cols[ay] - node.geometry.y
                return dx * dx + dy * dy <= node.distance**2

            self._key.append(repr(node))
            return f_dw, f_dw
        if lift and isinstance(node, ast.Intersects):
            _check(_device_supported(node, sft),
                   "intersects shape not kernelizable")
            table = edge_table(node.geometry.rings())
            n_edges = table.shape[1]
            _check(
                n_edges <= MAX_KERNEL_EDGES,
                f"{n_edges} polygon edges > kernel edge bucket",
            )
            e = node.geometry.envelope
            x0, x1 = _f32_bounds(e.xmin, e.xmax, outward=True)
            y0, y1 = _f32_bounds(e.ymin, e.ymax, outward=True)
            env = _box_fn(node.attr, self._add_floats([x0, y0, x1, y1]))
            off = self._add_floats(table)
            # the loop's trip count is an operand: the key keeps the bucket
            k = len(self.hi)
            self.hi.append(_loop_steps(_real_edges(node.geometry)))
            self.lo.append(0)
            self._key.append(f"Intersects({node.attr},{node.op},{n_edges})")
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"

            def inside(cols, q, env=env, off=off, n=n_edges, k=k, ax=ax,
                       ay=ay):
                # every hit lies in the envelope: the crossing parity can
                # flip a point an ulp outside it, the envelope cannot
                return env(cols, q) & _crossing_parity(
                    cols[ax], cols[ay], q[0], off, n, q[1][k]
                )

            if node.op == "disjoint":
                return None, lambda cols, q, inside=inside: ~inside(cols, q)
            return env, inside
        if lift and isinstance(node, (ast.During, ast.Between)) and \
                sft.descriptor(node.attr).column_dtype == np.int64:
            import math

            lo = node.t0 if isinstance(node, ast.During) else node.lo
            hi = node.t1 if isinstance(node, ast.During) else node.hi
            k = len(self.hi)
            for v in (math.ceil(lo), math.floor(hi)):
                vhi, vlo = split_value(v)
                self.hi.append(vhi)
                self.lo.append(vlo)
            chi, clo = f"{node.attr}__hi", f"{node.attr}__lo"

            def window(cols, q, k=k, chi=chi, clo=clo):
                h, lw = cols[chi], cols[clo]
                return cmp_lanes_jax(">=", h, lw, q[1][k], q[2][k]) & \
                    cmp_lanes_jax("<=", h, lw, q[1][k + 1], q[2][k + 1])

            self._key.append(f"{type(node).__name__}({node.attr})")
            return window, window
        # any other leaf keeps its literals in the program (and in the
        # key): the XLA path's own closure over the tile, so the i64
        # hi/lo rewrite, float-bound rounding and the envelope compares
        # of non-point geometries stay in one place
        _check(_device_supported(node, sft), f"node {type(node).__name__}")
        inner = build_device_fn(node, sft)
        self._key.append(repr(node))

        def baked(cols, q, inner=inner):
            return inner(cols)

        return baked, baked


def _fold(fns: list, is_and: bool):
    def fold(cols, q, fns=fns, is_and=is_and):
        m = fns[0](cols, q)
        for fn in fns[1:]:
            m = (m & fn(cols, q)) if is_and else (m | fn(cols, q))
        return m

    return fold


def _box_fn(attr: str, off: int):
    """``x0 <= x <= x1 and y0 <= y <= y1`` with the bounds at ``off`` of
    the float table."""
    ax, ay = f"{attr}__x", f"{attr}__y"

    def box(cols, q):
        fq, x, y = q[0], cols[ax], cols[ay]
        return (x >= fq[off]) & (y >= fq[off + 1]) & (x <= fq[off + 2]) & (
            y <= fq[off + 3])

    return box


def _crossing_parity(px, py, fq, off: int, n_edges: int, steps):
    """Crossing-number parity of points against the edge table at
    ``off`` of the float table (``n_edges`` wide, the bucket), the edges
    read from SMEM in a loop of ``steps`` (a traced int32: the polygon's
    real edges over the unroll, rounded up; the padding edges past them
    never straddle): an edge crosses the ray east of a point when
    y_lo <= py < y_hi and px lies west of the edge at py."""
    import jax
    import jax.numpy as jnp

    # int32 offsets and bounds: under x64 a Python int (and a static
    # fori_loop's counter) traces as i64, whose convert Mosaic's lowering
    # recurses on. So the loop runs over traced int32 bounds, and unrolls
    # by hand: _EDGE_UNROLL edges a step (buckets are multiples of it)
    o_lo, o_hi, o_x, o_dx = (jnp.int32(off + j * n_edges) for j in range(4))

    def edges(step, odd):
        k0 = step * jnp.int32(_EDGE_UNROLL)
        for u in range(_EDGE_UNROLL):
            k = k0 + jnp.int32(u)
            y_lo = fq[o_lo + k]
            x = fq[o_x + k] + (py - y_lo) * fq[o_dx + k]
            hit = (py >= y_lo) & (py < fq[o_hi + k]) & (px < x)
            odd = odd ^ hit.astype(jnp.int32)
        return odd

    odd = jax.lax.fori_loop(
        jnp.int32(0), steps, edges, jnp.zeros(px.shape, jnp.int32),
    )
    return odd != jnp.int32(0)


#: (filter shape, columns, block rows, interpret) -> jitted (count, mask)
_PIP_PROGRAMS: dict = {}


def _pip_programs(plan: _TilePlan, cols: list, br: int, interpret: bool):
    """The jitted polygon programs for a plan's shape, built once per
    process: filters that differ only in lifted literals share them."""
    key = (plan.key, tuple(cols), br, interpret)
    progs = _PIP_PROGRAMS.get(key)
    if progs is None:
        progs = _PIP_PROGRAMS.setdefault(
            key, _pip_kernels(plan.cheap, plan.full, cols, br, interpret)
        )
    return progs


def _pip_kernels(cheap, full, cols: list, br: int, interpret: bool):
    """Jitted ``pip_count(coldict, fq, iq, uq, valid)`` -> int32
    [hits, blocks tested, blocks skipped, tiles walked, tiles skipped]
    and ``pip_mask(...)`` -> bool mask. Each grid step first tests its
    whole tile: where no live row passes ``cheap``, the tile is skipped
    in one scalar branch. Otherwise it walks the tile in blocks of
    ``SKIP_ROWS`` x 128; a block whose live rows all fail ``cheap``
    skips the predicate (its edge loop). A filter with no pretest walks
    every tile and tests every block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _zero():
        return jnp.int32(0)

    def _any(m):
        return jnp.max(m.astype(jnp.int32)) > jnp.int32(0)

    def _walk(n: int, has_valid: bool, on_block, on_dead_tile):
        """The kernel body over one tile: ``on_block(r0, sub, pre, q)``
        for each block of ``SKIP_ROWS`` sublanes, ``pre`` the live rows
        that pass the pretest; or ``on_dead_tile()`` alone where no row
        of the tile passes it."""

        def body(fq, iq, uq, in_refs):
            q = (fq, iq, uq)
            i = pl.program_id(0)

            def pretest(r0, rows: int):
                """(the columns' rows [r0, r0 + rows), the live ones of
                them that pass the pretest); the whole tile where ``r0``
                is None."""
                at = slice(None) if r0 is None else pl.ds(r0, rows)
                sub = {c: r[at, :] for c, r in zip(cols, in_refs)}
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
                idx = (
                    i * jnp.int32(br * LANES)
                    + (row if r0 is None else r0 + row) * jnp.int32(LANES)
                    + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
                )
                pre = idx < jnp.int32(n)
                if has_valid:
                    pre = pre & (in_refs[-1][at, :] != jnp.int8(0))
                if cheap is not None:
                    pre = pre & cheap(sub, q)
                return sub, pre

            def block(b, carry):
                # int32 literals throughout: see _crossing_parity
                r0 = pl.multiple_of(b * jnp.int32(SKIP_ROWS), SKIP_ROWS)
                on_block(r0, *pretest(r0, SKIP_ROWS), q)
                return carry

            def walk():
                jax.lax.fori_loop(_zero(), jnp.int32(br // SKIP_ROWS), block,
                                  _zero())

            if cheap is None:
                walk()
                return
            live = _any(pretest(None, br)[1])
            pl.when(live)(walk)
            pl.when(jnp.logical_not(live))(on_dead_tile)

        return body

    def _specs(n_in: int, out_specs, grid: int):
        return pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(grid,),
            in_specs=[pl.BlockSpec((br, LANES), lambda i, *_: (i, _zero()))
                      for _ in range(n_in)],
            out_specs=out_specs,
        )

    def pip_count(coldict, fq, iq, uq, valid=None):
        n, grid, mats = _tile_mats(cols, br, coldict, valid)

        def kernel(fq_ref, iq_ref, uq_ref, *refs):
            in_refs, (hits_ref, tested_ref, dead_ref) = refs[:-3], refs[-3:]

            @pl.when(pl.program_id(0) == 0)
            def _():
                hits_ref[...] = jnp.zeros((1, LANES), jnp.int32)
                tested_ref[...] = jnp.zeros((1, LANES), jnp.int32)
                dead_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            def on_block(r0, sub, pre, q):
                @pl.when(_any(pre))
                def _():
                    m = pre & full(sub, q)
                    hits_ref[...] += jnp.sum(
                        m.astype(jnp.int32), axis=0, dtype=jnp.int32,
                        keepdims=True)
                    tested_ref[...] += jnp.int32(1)

            def on_dead_tile():
                dead_ref[...] += jnp.int32(1)

            _walk(n, valid is not None, on_block, on_dead_tile)(
                fq_ref, iq_ref, uq_ref, in_refs)

        acc = pl.BlockSpec((1, LANES), lambda i, *_: (_zero(), _zero()))
        hits, tested, dead = pl.pallas_call(
            kernel,
            grid_spec=_specs(len(mats), [acc, acc, acc], grid),
            out_shape=[jax.ShapeDtypeStruct((1, LANES), jnp.int32)] * 3,
            interpret=interpret,
            name="pip_count",
        )(fq, iq, uq, *mats)
        blocks = jnp.int32(grid * (br // SKIP_ROWS))
        return jnp.stack([jnp.sum(hits, dtype=jnp.int32), tested[0, 0],
                          blocks - tested[0, 0], jnp.int32(grid) - dead[0, 0],
                          dead[0, 0]])

    def pip_mask(coldict, fq, iq, uq, valid=None):
        n, grid, mats = _tile_mats(cols, br, coldict, valid)

        def kernel(fq_ref, iq_ref, uq_ref, *refs):
            in_refs, out_ref = refs[:-1], refs[-1]

            def on_block(r0, sub, pre, q):
                out_ref[pl.ds(r0, SKIP_ROWS), :] = jnp.zeros((SKIP_ROWS, LANES),
                                                        jnp.int8)

                @pl.when(_any(pre))
                def _():
                    out_ref[pl.ds(r0, SKIP_ROWS), :] = (
                        pre & full(sub, q)).astype(jnp.int8)

            def on_dead_tile():
                out_ref[...] = jnp.zeros((br, LANES), jnp.int8)

            _walk(n, valid is not None, on_block, on_dead_tile)(
                fq_ref, iq_ref, uq_ref, in_refs)

        m = pl.pallas_call(
            kernel,
            grid_spec=_specs(len(mats), pl.BlockSpec(
                (br, LANES), lambda i, *_: (i, _zero())), grid),
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
            name="pip_mask",
        )(fq, iq, uq, *mats)
        return m.reshape(-1)[:n].astype(bool)

    return jax.jit(pip_count), jax.jit(pip_mask)


def _polygon_edges(f: ast.Filter, edges) -> int:
    """``edges(real edge count)`` summed over a filter's polygons."""
    if isinstance(f, (ast.And, ast.Or)):
        return sum(_polygon_edges(c, edges) for c in f.children)
    if isinstance(f, ast.Not):
        return _polygon_edges(f.child, edges)
    if isinstance(f, ast.Intersects) and hasattr(f.geometry, "rings"):
        return edges(_real_edges(f.geometry))
    return 0


def padded_edges(f: ast.Filter) -> int:
    """The width of the polygon kernel's edge tables for a filter: each
    polygon's edge count padded to its bucket, summed (the bucket of the
    program it compiles)."""
    return _polygon_edges(f, edge_bucket)


def looped_edges(f: ast.Filter) -> int:
    """The edges the polygon kernel's loop runs for a filter: each
    polygon's edge count rounded up to the loop's unroll, summed. A row
    of a tested block is tested against each."""
    return _polygon_edges(f, lambda n: _EDGE_UNROLL * _loop_steps(n))


def _build_pip_scan(f, sft, cols, br, interpret):
    """(count_fn, mask_fn) of the polygon kernel, bound to this filter's
    runtime operands (see :func:`build_pallas_scan`)."""
    import jax
    import jax.numpy as jnp

    from geomesa_tpu import ledger

    plan = _TilePlan(f, sft, lift=True)
    count_prog, mask_prog = _pip_programs(plan, cols, br, interpret)
    with jax.ensure_compile_time_eval():  # resident, even if built in a trace
        ops = (jnp.asarray(plan.floats),
               jnp.asarray(np.asarray(plan.hi + [0], np.int32)),
               jnp.asarray(np.asarray(plan.lo + [0], np.uint32)))

    # one compiled program per filter shape and edge bucket
    scope = f"cache.scan:pip:e={padded_edges(f)}"

    def count_fn(coldict, valid=None):
        with ledger.compile_scope(scope):
            return count_prog(coldict, *ops, valid)

    def mask_fn(coldict, valid=None):
        with ledger.compile_scope(scope):
            return mask_prog(coldict, *ops, valid)

    return count_fn, mask_fn


def build_pallas_scan(
    f: ast.Filter,
    sft: SimpleFeatureType,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Compile the filter's device part to jitted Pallas count/mask
    callables.

    Returns ``(count_fn, mask_fn, cols)`` where each fn takes a dict of
    staged 1-D device columns (see ops/scan.stage_columns) and returns
    int32 [hits, row blocks tested, row blocks skipped] / the bool mask
    for the whole array: the polygon kernel skips whole tiles and blocks
    (``SKIP_ROWS`` x 128 rows), and its count adds [tiles walked, tiles
    skipped]; the bbox kernel tests every tile of its grid. Either fn
    takes an optional bool ``valid`` plane (the padded buffers of a
    streaming resident index): rows where it is False never match. Raises
    PallasUnsupported when the filter can't be tiled; callers fall back to
    CompiledFilter.device_fn.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    cols = supported_columns(f, sft)
    _check(bool(cols), "no device columns (constant filter)")
    br = block_rows or _pick_block_rows(len(cols))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    from geomesa_tpu.filter.compile import has_polygon

    if has_polygon(f):
        return (*_build_pip_scan(f, sft, cols, br, interpret), cols)
    plan = _TilePlan(f, sft, lift=False)

    def tile_fn(cols):
        return plan.full(cols, None)

    def _valid_mask(n, has_valid):
        # rows past n (tile padding), and rows the validity plane marks
        # dead, must not count as hits
        def tail(m, in_refs):
            i = pl.program_id(0)
            idx = (
                i * br * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
            )
            m = m & (idx < n)
            if has_valid:
                m = m & (in_refs[-1][...] != 0)
            return m

        return tail

    # index-map literals must be int32: under x64 a bare python 0 traces
    # as an i64 constant, which Mosaic refuses to lower
    _zero = lambda: jnp.int32(0)

    def _in_specs(n_in):
        return [
            pl.BlockSpec((br, LANES), lambda i: (i, _zero()))
            for _ in range(n_in)
        ]

    def count_fn(coldict, valid=None):
        n, grid, mats = _tile_mats(cols, br, coldict, valid)
        tail = _valid_mask(n, valid is not None)

        def kernel(*refs):
            # TPU grids run sequentially per core, so a single (1, LANES)
            # output revisited by every step is a race-free accumulator.
            # Per-LANE partials, NOT a scalar: a scalar-output reduce takes
            # Mosaic's proxy path, which re-traces jnp.sum at LOWERING
            # time under the global dtype config -- with x64 enabled that
            # injects an int64 convert Mosaic cannot lower. The axis-0
            # reduce keeps a (1, LANES) vector and lowers directly.
            *in_refs, out_ref = refs
            m = tail(
                tile_fn({c: r[...] for c, r in zip(cols, in_refs)}), in_refs
            )

            @pl.when(pl.program_id(0) == 0)
            def _():
                out_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            out_ref[...] = out_ref[...] + jnp.sum(
                m.astype(jnp.int32), axis=0, dtype=jnp.int32, keepdims=True
            )

        partials = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=_in_specs(len(mats)),
            out_specs=pl.BlockSpec((1, LANES), lambda i: (_zero(), _zero())),
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            interpret=interpret,
        )(*mats)
        # final 128-way fold runs in XLA outside the kernel
        return jnp.stack([jnp.sum(partials, dtype=jnp.int32),
                          jnp.int32(grid), jnp.int32(0)])

    def mask_fn(coldict, valid=None):
        n, grid, mats = _tile_mats(cols, br, coldict, valid)
        tail = _valid_mask(n, valid is not None)

        def kernel(*refs):
            *in_refs, out_ref = refs
            m = tail(
                tile_fn({c: r[...] for c, r in zip(cols, in_refs)}), in_refs
            )
            out_ref[...] = m.astype(jnp.int8)

        m = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=_in_specs(len(mats)),
            out_specs=pl.BlockSpec((br, LANES), lambda i: (i, _zero())),
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
        )(*mats)
        return m.reshape(-1)[:n].astype(bool)

    return jax.jit(count_fn), jax.jit(mask_fn), cols
