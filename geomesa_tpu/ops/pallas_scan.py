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
Filters whose device part needs anything else (float64 columns, huge
polygon edge lists) fall back to the XLA-fused jnp path in
filter/compile.py -- same semantics, same staged columns.

On CPU jax (tests / CI) the kernel runs in interpret mode, so the whole
suite exercises the identical kernel code without a TPU.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import ast
from geomesa_tpu.ops.int64lanes import cmp_jax

LANES = 128
# Unrolled edge budget for in-kernel point-in-polygon; bigger rings fall
# back to the jnp path (broadcasting (n, E) there is fine in HBM).
MAX_KERNEL_EDGES = 64
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


def _build_tile_fn(f: ast.Filter, sft: SimpleFeatureType):
    """AST -> fn(cols: dict[str, 2-D tile]) -> bool tile. Mirrors
    filter/compile.build_device_fn but restricted to ops that lower to
    Pallas TPU (elementwise VPU work on 32-bit lanes, static unrolls)."""

    def rec(node):
        import jax.numpy as jnp

        if node is ast.Include:
            return lambda cols: jnp.full(_tile_shape(cols), True, dtype=bool)
        if node is ast.Exclude:
            return lambda cols: jnp.full(_tile_shape(cols), False, dtype=bool)
        if isinstance(node, (ast.And, ast.Or)):
            fns = [rec(c) for c in node.children]
            is_and = isinstance(node, ast.And)

            def f_bool(cols, fns=fns, is_and=is_and):
                m = fns[0](cols)
                for fn in fns[1:]:
                    m = (m & fn(cols)) if is_and else (m | fn(cols))
                return m

            return f_bool
        if isinstance(node, ast.Not):
            fn = rec(node.child)
            return lambda cols, fn=fn: ~fn(cols)
        if isinstance(node, ast.BBox):
            if not sft.descriptor(node.attr).is_point:
                # envelope-overlap tile: delegate so the compare stays
                # bit-identical to the XLA path (single source, same as
                # the During/Compare delegation below)
                from geomesa_tpu.filter.compile import build_device_fn

                inner = build_device_fn(node, sft)
                return lambda cols, inner=inner: inner(cols)
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"

            def f_bbox(cols, node=node, ax=ax, ay=ay):
                x, y = cols[ax], cols[ay]
                return (
                    (x >= node.xmin)
                    & (x <= node.xmax)
                    & (y >= node.ymin)
                    & (y <= node.ymax)
                )

            return f_bbox
        if isinstance(node, ast.DWithin):
            from geomesa_tpu.geom import Point

            if not (
                sft.descriptor(node.attr).is_point
                and isinstance(node.geometry, Point)
            ):
                # padded-envelope bbox: delegate to the single XLA-path
                # implementation (build_device_fn rewrites to BBox)
                from geomesa_tpu.filter.compile import build_device_fn

                inner = build_device_fn(node, sft)
                return lambda cols, inner=inner: inner(cols)
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"

            def f_dw(cols, node=node, ax=ax, ay=ay):
                dx = cols[ax] - node.geometry.x
                dy = cols[ay] - node.geometry.y
                return dx * dx + dy * dy <= node.distance**2

            return f_dw
        if isinstance(node, ast.Intersects):
            _check(
                sft.descriptor(node.attr).is_point
                and hasattr(node.geometry, "rings")
                and node.op in ("intersects", "within", "disjoint"),
                "intersects shape not kernelizable",
            )
            from geomesa_tpu.geom.predicates import polygon_edges

            x1, y1, x2, y2 = polygon_edges(node.geometry.rings())
            _check(
                len(x1) <= MAX_KERNEL_EDGES,
                f"{len(x1)} polygon edges > kernel unroll budget",
            )
            edges = [
                (float(a), float(b), float(c), float(d))
                for a, b, c, d in zip(x1, y1, x2, y2)
            ]
            ax, ay = f"{node.attr}__x", f"{node.attr}__y"
            neg = node.op == "disjoint"

            def f_pip(cols, edges=edges, ax=ax, ay=ay, neg=neg):
                # crossing-number test, edges unrolled as scalar constants
                px, py = cols[ax], cols[ay]
                crossings = jnp.zeros(px.shape, dtype=jnp.int32)
                for ex1, ey1, ex2, ey2 in edges:
                    straddle = (ey1 > py) != (ey2 > py)
                    denom = (ey2 - ey1) if ey2 != ey1 else 1.0
                    xint = ex1 + (py - ey1) * (ex2 - ex1) / denom
                    crossings = crossings + (straddle & (px < xint))
                # parity via bitwise AND: `crossings % 2` trips an
                # infinite _convert_element_type recursion in the Mosaic
                # lowering when x64 is enabled (the weak int literal
                # round-trips through i64) — pinned by
                # tests/test_pallas_scan.py::test_mosaic_mod_recursion_repro
                m = (crossings & 1) == 1
                return ~m if neg else m

            return f_pip
        if isinstance(node, (ast.During, ast.Between, ast.Compare, ast.In)):
            # identical numeric semantics to build_device_fn -- delegate so
            # the i64 hi/lo rewrite and float-bound rounding stay in one
            # place (the inner closures are pure elementwise jnp).
            from geomesa_tpu.filter.compile import (
                _device_supported,
                build_device_fn,
            )

            _check(_device_supported(node, sft), f"{type(node).__name__}")
            inner = build_device_fn(node, sft)
            return lambda cols, inner=inner: inner(cols)
        raise PallasUnsupported(f"node {type(node).__name__}")

    import jax.numpy as jnp  # noqa: F401 (closures above)

    return rec(f)


def _tile_shape(cols: dict):
    return next(iter(cols.values())).shape


def _pick_block_rows(n_cols: int) -> int:
    rows = _VMEM_BUDGET // max(1, n_cols * LANES * 4)
    rows = max(64, min(1024, rows))
    return (rows // 32) * 32  # int8/int32 sublane multiple


def build_pallas_scan(
    f: ast.Filter,
    sft: SimpleFeatureType,
    *,
    block_rows: int | None = None,
    interpret: bool | None = None,
):
    """Compile the filter's device part to Pallas count/mask callables.

    Returns ``(count_fn, mask_fn, cols)`` where each fn takes a dict of
    staged 1-D device columns (see ops/scan.stage_columns) and returns the
    int32 hit count / bool mask for the whole array. Either fn takes an
    optional bool ``valid`` plane (the padded buffers of a streaming
    resident index): rows where it is False never match. Raises
    PallasUnsupported when the filter can't be tiled; callers fall back to
    CompiledFilter.device_fn.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    cols = supported_columns(f, sft)
    _check(bool(cols), "no device columns (constant filter)")
    tile_fn = _build_tile_fn(f, sft)
    br = block_rows or _pick_block_rows(len(cols))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    def _prep(coldict, valid):
        n = int(_tile_shape(coldict)[0])
        if n > 2**31 - 1 - br * LANES:
            raise PallasUnsupported("partition too large for int32 indexing")
        grid = max(1, -(-n // (br * LANES)))
        pad = grid * br * LANES - n
        mats = [
            jnp.pad(coldict[c], (0, pad)).reshape(grid * br, LANES)
            for c in cols
        ]
        if valid is not None:
            # int8 tile (Mosaic has no bool memref); sublane rows are a
            # multiple of 32, the int8 tiling
            mats.append(
                jnp.pad(valid.astype(jnp.int8), (0, pad)).reshape(
                    grid * br, LANES
                )
            )
        return n, grid, pad, mats

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
        n, grid, pad, mats = _prep(coldict, valid)
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
        return jnp.sum(partials, dtype=jnp.int32)

    def mask_fn(coldict, valid=None):
        n, grid, pad, mats = _prep(coldict, valid)
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

    return count_fn, mask_fn, cols
