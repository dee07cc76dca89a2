"""Z-only compare scan: filter rows by their resident z-keys alone.

Ref role: Z3Iterator / Z2Iterator (geomesa-index-api .../iterators —
[UNVERIFIED - empty reference mount]): the reference's hottest scan never
deserializes the feature — it bounds-checks the row KEY. The TPU analog
keeps the index key planes (uint32 hi/lo) resident and reads 8 bytes/row
instead of the 16 bytes/row of coordinate+time planes.

The kernel needs no de-interleave: Morton bit-spreading is monotonic per
dimension, so ``extract_d(z) ∈ [lo_d, hi_d]`` is exactly
``spread_d(lo_d) <= (z & dim_mask_d) <= spread_d(hi_d)`` — three ANDs and
six 64-bit compares per row, carried as uint32 hi/lo lane pairs (the TPU
VPU has no 64-bit integer lanes).

Time-binned Z3 keys (bin, z) get per-bin bounds: the query window maps to
one (possibly partial) offset range per period bin, and the mask is
``any_b(bin == b AND z within bounds_b)``. The bin count is static at
trace time; pad ``bin_ids`` with -1 (never matches) to bound recompiles.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.curves import zorder

U = np.uint64
_LO32 = U(0xFFFFFFFF)


def _hi_lo(v: np.ndarray) -> tuple[int, int]:
    hi, lo = zorder.u64_hi_lo(v)
    return int(hi), int(lo)


def _dim_bounds(qlo: tuple, qhi: tuple, split, max_mask: int, n_dims: int):
    """Per-dimension masked-compare bounds for one z cell box: per dim d
    the columns are (mask_hi, mask_lo, lo_hi, lo_lo, hi_hi, hi_lo), where
    mask keeps only dim d's interleaved bit positions and lo/hi are the
    spread (inclusive) cell bounds."""
    out = np.empty((n_dims, 6), np.uint32)
    for d in range(n_dims):
        mask = split(np.uint64(max_mask)) << U(d)
        blo = split(np.uint64(qlo[d])) << U(d)
        bhi = split(np.uint64(qhi[d])) << U(d)
        out[d, 0:2] = _hi_lo(mask)
        out[d, 2:4] = _hi_lo(blo)
        out[d, 4:6] = _hi_lo(bhi)
    return out


def z3_dim_bounds(qlo: tuple, qhi: tuple) -> np.ndarray:
    """(3, 6) uint32 bounds for one Z3 cell box (21-bit x/y/t corners)."""
    return _dim_bounds(qlo, qhi, zorder.split_3d_np, zorder.MAX_MASK_3D, 3)


def z2_dim_bounds(qlo: tuple, qhi: tuple) -> np.ndarray:
    """(2, 6) uint32 bounds for one Z2 cell box (31-bit x/y corners)."""
    return _dim_bounds(qlo, qhi, zorder.split_2d_np, zorder.MAX_MASK_2D, 2)


def _ge64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def _le64(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _dims_mask(z_hi, z_lo, bounds, n_dims: int):
    """AND of the per-dimension masked compares; bounds is (n_dims, 6)."""
    m = None
    for d in range(n_dims):
        mask_hi, mask_lo = bounds[d, 0], bounds[d, 1]
        zm_hi = z_hi & mask_hi
        zm_lo = z_lo & mask_lo
        md = _ge64(zm_hi, zm_lo, bounds[d, 2], bounds[d, 3]) & _le64(
            zm_hi, zm_lo, bounds[d, 4], bounds[d, 5]
        )
        m = md if m is None else (m & md)
    return m


def z3_zscan_mask(z_hi, z_lo, bins, bounds, bin_ids):
    """Boolean hit mask from key planes alone.

    z_hi/z_lo: uint32 (n,) key planes. bins: int32 (n,) period-bin plane.
    bounds: uint32 (B, 3, 6) per-bin dim bounds. bin_ids: int32 (B,), -1
    entries are padding and never match. B is static at trace time.
    """
    import jax.numpy as jnp

    total = jnp.zeros(z_hi.shape, bool)
    for b in range(bounds.shape[0]):
        total = total | (
            (bins == bin_ids[b]) & _dims_mask(z_hi, z_lo, bounds[b], 3)
        )
    return total


def z2_zscan_mask(z_hi, z_lo, bounds):
    """Boolean hit mask for unbinned Z2 keys; bounds is (2, 6) uint32."""
    return _dims_mask(z_hi, z_lo, bounds, 2)


def z3_query_bounds(
    sfc,
    xmin: float,
    ymin: float,
    xmax: float,
    ymax: float,
    tmin_ms: int,
    tmax_ms: int,
) -> tuple[np.ndarray, np.ndarray]:
    """(bounds (B,3,6), bin_ids (B,)) for a bbox + absolute-ms window.

    One entry per period bin the window touches; edge bins get partial
    offset ranges, interior bins the full offset span — the same per-bin
    decomposition Z3IndexKeySpace feeds its per-bin Z3SFC.ranges calls
    with (loose semantics: cell-granular, no residual refinement).
    """
    from geomesa_tpu.curves.binnedtime import bins_for_interval

    qx = (int(sfc.lon.normalize(xmin)), int(sfc.lon.normalize(xmax)))
    qy = (int(sfc.lat.normalize(ymin)), int(sfc.lat.normalize(ymax)))
    bounds, ids = [], []
    for b, lo_off, hi_off in bins_for_interval(tmin_ms, tmax_ms, sfc.period):
        qt = (
            int(sfc.time.normalize(lo_off)),
            int(sfc.time.normalize(hi_off)),
        )
        bounds.append(
            z3_dim_bounds((qx[0], qy[0], qt[0]), (qx[1], qy[1], qt[1]))
        )
        ids.append(b)
    if not bounds:  # empty/inverted window: zero bins, matches nothing
        return np.zeros((0, 3, 6), np.uint32), np.array([], np.int32)
    return np.stack(bounds), np.array(ids, np.int32)


# -- XZ (extent-curve) key scans ---------------------------------------------
#
# XZ codes are pre-order tree walks, not Morton interleaves, so there is no
# masked-compare trick: a query decomposes into a SMALL list of inclusive
# [lo, hi] code ranges (budget-bounded, over-covering on truncation — see
# curves/xz.py ranges()), and the device mask tests each row's hi/lo code
# lanes against every range. R is static at trace time; pad with
# never-matching entries (lo > hi) to bound recompiles.


def xz_range_bounds(ranges) -> np.ndarray:
    """IndexRange list -> (R, 4) uint32 rows [lo_hi, lo_lo, hi_hi, hi_lo]."""
    out = np.empty((len(ranges), 4), np.uint32)
    for i, r in enumerate(ranges):
        out[i, 0:2] = _hi_lo(np.uint64(r.lower))
        out[i, 2:4] = _hi_lo(np.uint64(r.upper))
    return out


_NEVER_RANGE = np.array(
    [0xFFFFFFFF, 0xFFFFFFFF, 0, 0], np.uint32
)  # lo = 2^64-1 > hi = 0: matches nothing


def pad_ranges(bounds: np.ndarray, min_r: int = 1) -> np.ndarray:
    """Pad the range axis (last-but-one) up to the compile-shape ladder
    (:mod:`geomesa_tpu.bucketing`; next power of two on the default
    ladder) with never-matching entries so jit sees a bounded set of R
    shapes."""
    from geomesa_tpu.bucketing import bucket_cap

    r = bounds.shape[-2]
    cap = max(min_r, bucket_cap(r))
    if cap == r:
        return bounds
    pad_shape = bounds.shape[:-2] + (cap - r, 4)
    return np.concatenate(
        [bounds, np.broadcast_to(_NEVER_RANGE, pad_shape)], axis=-2
    )


def xz_range_mask(xz_hi, xz_lo, bounds):
    """Boolean hit mask for unbinned XZ2 keys; bounds is (R, 4) uint32.

    One broadcasted compare over the range axis (not a Python unroll):
    the (R, n) intermediates fuse into the reduction, and the trace stays
    O(1) nodes regardless of R."""
    import jax.numpy as jnp

    zh, zl = xz_hi[None, :], xz_lo[None, :]
    ge = _ge64(zh, zl, bounds[:, 0:1], bounds[:, 1:2])
    le = _le64(zh, zl, bounds[:, 2:3], bounds[:, 3:4])
    return jnp.any(ge & le, axis=0)


def xz3_range_mask(xz_hi, xz_lo, bins, bounds, bin_ids):
    """Boolean hit mask for binned XZ3 keys.

    bounds: uint32 (B, R, 4) per-bin ranges; bin_ids: int32 (B,), -1 is
    padding and never matches. The bin axis unrolls (B <= 64, typically
    <= 8); the range axis is one broadcasted compare per bin.
    """
    import jax.numpy as jnp

    total = jnp.zeros(xz_hi.shape, bool)
    for b in range(bounds.shape[0]):
        total = total | (
            (bins == bin_ids[b]) & xz_range_mask(xz_hi, xz_lo, bounds[b])
        )
    return total


def xz2_query_bounds(
    sfc, xmin: float, ymin: float, xmax: float, ymax: float,
    max_ranges: int = 128,
) -> np.ndarray:
    """(R, 4) uint32 range bounds for one bbox (loose cell semantics: an
    over-covering superset; truncation at max_ranges stays a superset)."""
    return xz_range_bounds(sfc.ranges(xmin, ymin, xmax, ymax,
                                      max_ranges=max_ranges))


def xz3_query_bounds(
    sfc,
    xmin: float,
    ymin: float,
    xmax: float,
    ymax: float,
    tmin_ms: int,
    tmax_ms: int,
    max_ranges: int = 128,
) -> tuple[np.ndarray, np.ndarray]:
    """(bounds (B, R, 4), bin_ids (B,)) for a bbox + absolute-ms window.

    One entry per period bin, partial time extents on edge bins — the
    XZ3 analog of :func:`z3_query_bounds`; interior whole-period bins
    share one decomposition. Per-bin range lists are padded to a common R
    with never-matching entries.
    """
    from geomesa_tpu.curves.binnedtime import bins_for_interval, max_offset

    mx = max_offset(sfc.period)
    per_bin: list = []
    ids: list = []
    whole_cache = None
    # the spatial box is bin-invariant: build its arrays once, outside
    # the per-bin loop (only the time offsets vary per bin)
    ax, ay = np.array([xmin]), np.array([ymin])
    bx, by = np.array([xmax]), np.array([ymax])
    for b, lo_off, hi_off in bins_for_interval(tmin_ms, tmax_ms, sfc.period):
        whole = lo_off == 0 and hi_off == mx
        if whole and whole_cache is not None:
            rs = whole_cache
        else:
            rs = sfc.ranges(
                ax, ay,
                np.array([float(lo_off)]),  # lint: disable=GT004(host-side scalar range planning; no device arrays in this loop)
                bx, by,
                np.array([float(hi_off)]),  # lint: disable=GT004(host-side scalar range planning; no device arrays in this loop)
                max_ranges=max_ranges,
            )
            if whole:
                whole_cache = rs
        per_bin.append(xz_range_bounds(rs))
        ids.append(b)
    if not per_bin:
        return np.zeros((0, 1, 4), np.uint32), np.array([], np.int32)
    from geomesa_tpu.bucketing import bucket_cap

    longest = max(len(p) for p in per_bin)
    r_max = bucket_cap(longest)  # same ladder as pad_ranges
    stacked = np.stack([pad_ranges(p, min_r=r_max) for p in per_bin])
    return stacked, np.array(ids, np.int32)


# -- de-interleaved key-plane scans ------------------------------------------
#
# Morton order exists for SORTING (contiguous key ranges on disk / in the
# exchange); a resident SCAN is free to choose its own layout. Comparing
# the interleaved key needs ~46 VPU ops/row (three masked 64-bit compares
# in hi/lo lanes) and measures compute-bound on v5e; storing the SAME key
# de-interleaved — nx, ny uint32 planes plus ONE packed bt word
# ((bin - bin_base) << 21 | nt) — answers the identical cell-granular
# query with ~12 ops/row and reaches the roofline. 12B/row either way.
# Contiguous query bins merge into a single bt range, so a multi-week
# window costs 2 compares, not 2 per bin.

BT_TIME_BITS = 21  # nt occupies the low 21 bits of bt
BT_BIN_SPAN = 1 << (32 - BT_TIME_BITS)  # max bins representable (2^11)


def z3_dim_planes(sfc, nx, ny, nt, bins, bin_base: int):
    """Pack quantized dims + bins into the scan planes (host or device
    arrays; works under numpy and jnp, including inside jit).

    Rows whose ``bins - bin_base`` falls outside [0, BT_BIN_SPAN - 1) get
    the SENTINEL bt 0xFFFFFFFF — the top packable bin's space, which the
    query builder refuses to address — so out-of-window rows are
    deterministically unmatchable rather than silently wrapping into
    another bin's key space. Callers derive bin_base from the data's min
    bin (and fall back to the masked-compare planes for spans that do not
    fit)."""
    if sfc.precision != BT_TIME_BITS:
        # nt wider than 21 bits would silently bleed into the bin field
        raise ValueError(
            f"dim-plane packing requires precision {BT_TIME_BITS} "
            f"(got {sfc.precision}); use the masked-compare planes"
        )
    rel = (bins - bin_base).astype(nx.dtype)  # negatives wrap huge (u32)
    bt = (rel << BT_TIME_BITS) | nt
    oob = rel >= (BT_BIN_SPAN - 1)
    if hasattr(bt, "at") and not isinstance(bt, np.ndarray):  # jnp path
        import jax.numpy as jnp

        bt = jnp.where(oob, jnp.uint32(0xFFFFFFFF), bt)
    else:
        bt = np.where(oob, np.uint32(0xFFFFFFFF), bt)
    return nx, ny, bt


def z3_dim_plane_query(
    sfc,
    xmin: float,
    ymin: float,
    xmax: float,
    ymax: float,
    tmin_ms: int,
    tmax_ms: int,
    bin_base: int,
) -> "tuple[tuple, tuple, list] | None":
    """(qnx, qny, bt_ranges) for the dim-plane scan, or None when a query
    bin falls outside the packable window. Contiguous bins merge into
    single inclusive bt ranges."""
    from geomesa_tpu.curves.binnedtime import bins_for_interval

    if sfc.precision != BT_TIME_BITS:
        return None  # planes for this sfc cannot have been packed

    qnx = (int(sfc.lon.normalize(xmin)), int(sfc.lon.normalize(xmax)))
    qny = (int(sfc.lat.normalize(ymin)), int(sfc.lat.normalize(ymax)))
    ranges: list = []
    for b, lo_off, hi_off in bins_for_interval(tmin_ms, tmax_ms, sfc.period):
        rel = b - bin_base
        # top bin reserved: it is the out-of-window SENTINEL space of
        # z3_dim_planes and must never be addressable by a query
        if not (0 <= rel < BT_BIN_SPAN - 1):
            return None
        lo = (rel << BT_TIME_BITS) | int(sfc.time.normalize(lo_off))
        hi = (rel << BT_TIME_BITS) | int(sfc.time.normalize(hi_off))
        if ranges and lo == ranges[-1][1] + 1:
            ranges[-1] = (ranges[-1][0], hi)
        else:
            ranges.append((lo, hi))
    return qnx, qny, ranges


def z3_dim_plane_qarr(
    sfc,
    env,
    window,
    bin_base: int,
    bin_range: "tuple | None",
    max_ranges: int = 8,
) -> "tuple[np.ndarray, int] | None":
    """RUNTIME query vector for the dim-plane scan: uint32
    ``[qnx_lo, qnx_hi, qny_lo, qny_hi, (bt_lo, bt_hi) * R]`` with R padded
    to a power of two by inverted (never-matching) ranges. One compiled
    kernel per R bucket serves EVERY window — the serving path must not
    pay a recompile per viewport the way baked-constant kernels do.

    ``bin_range`` clamps to the bins actually staged (query bins outside
    it match nothing by construction). Returns None when a surviving
    query bin falls outside the packable window relative to ``bin_base``
    (the caller falls back to another engine) or when the merged range
    count exceeds ``max_ranges``.
    """
    from geomesa_tpu.curves.binnedtime import bins_for_interval

    if sfc.precision != BT_TIME_BITS:
        return None  # planes for this sfc cannot have been packed
    xmin, ymin, xmax, ymax = env
    qnx = (int(sfc.lon.normalize(xmin)), int(sfc.lon.normalize(xmax)))
    qny = (int(sfc.lat.normalize(ymin)), int(sfc.lat.normalize(ymax)))
    ranges: list = []
    for b, lo_off, hi_off in bins_for_interval(
        int(window[0]), int(window[1]), sfc.period
    ):
        if bin_range is not None and not (bin_range[0] <= b <= bin_range[1]):
            continue  # bin not staged: matches nothing
        rel = b - bin_base
        # top bin reserved: the out-of-window SENTINEL space of
        # z3_dim_planes must never be addressable by a query
        if not (0 <= rel < BT_BIN_SPAN - 1):
            return None
        lo = (rel << BT_TIME_BITS) | int(sfc.time.normalize(lo_off))
        hi = (rel << BT_TIME_BITS) | int(sfc.time.normalize(hi_off))
        if ranges and lo == ranges[-1][1] + 1:
            ranges[-1] = (ranges[-1][0], hi)
        else:
            ranges.append((lo, hi))
    if len(ranges) > max_ranges:
        return None
    from geomesa_tpu.bucketing import bucket_cap

    r = bucket_cap(len(ranges))  # same ladder as pad_ranges
    out = np.empty(4 + 2 * r, np.uint32)
    if ranges:
        out[0:4] = [qnx[0], qnx[1], qny[0], qny[1]]
    else:
        out[0:4] = [1, 0, 1, 0]  # inverted: matches nothing
    for k in range(r):
        lo, hi = ranges[k] if k < len(ranges) else (0xFFFFFFFF, 0)
        out[4 + 2 * k] = lo
        out[5 + 2 * k] = hi
    return out, r


def z2_dim_plane_qarr(sfc, env) -> np.ndarray:
    """RUNTIME query vector for the UNBINNED 2-plane dim scan: uint32
    ``[qnx_lo, qnx_hi, qny_lo, qny_hi]`` (the z2 analog of
    :func:`z3_dim_plane_qarr`; no bt ranges — the key has no time)."""
    xmin, ymin, xmax, ymax = env
    return np.array(
        [
            int(sfc.lon.normalize(xmin)), int(sfc.lon.normalize(xmax)),
            int(sfc.lat.normalize(ymin)), int(sfc.lat.normalize(ymax)),
        ],
        np.uint32,
    )


def z2_dimscan_mask_rt(nx, ny, qarr):
    """XLA-fused 2-plane dim mask with RUNTIME bounds (z2 schemas)."""
    m = (nx >= qarr[0]) & (nx <= qarr[1])
    return m & (ny >= qarr[2]) & (ny <= qarr[3])


def build_z2_dimscan_rt(
    *,
    block_rows: int = 1024,
    interpret: "bool | None" = None,
):
    """Pallas 2-plane dim kernel with RUNTIME bounds: (count_fn, mask_fn)
    over ``(qarr, nx, ny)`` — the z2 sibling of
    :func:`build_z3_dimscan_rt` (4 compares/row over 8B/row)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    LANES = 128
    br = block_rows
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    _zero = lambda: jnp.int32(0)  # noqa: E731 (int32 index-map literal)

    def _tile_mask(q_ref, nx_t, ny_t):
        m = (nx_t >= q_ref[0]) & (nx_t <= q_ref[1])
        return m & (ny_t >= q_ref[2]) & (ny_t <= q_ref[3])

    def _prep(nx, ny):
        n = int(nx.shape[0])
        grid = max(1, -(-n // (br * LANES)))
        pad = grid * br * LANES - n
        # never-match padding; see the z3 builder's rationale
        mats = [
            jnp.pad(a, (0, pad), constant_values=np.uint32(0xFFFFFFFF)).reshape(
                grid * br, LANES
            )
            for a in (nx, ny)
        ]
        return n, grid, mats

    def count_fn(qarr, nx, ny):
        n, grid, mats = _prep(nx, ny)

        def kernel(q_ref, a_ref, b_ref, out_ref):
            m = _tile_mask(q_ref, a_ref[...], b_ref[...])

            @pl.when(pl.program_id(0) == 0)
            def _():
                out_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            out_ref[...] = out_ref[...] + jnp.sum(
                m.astype(jnp.int32), axis=0, dtype=jnp.int32, keepdims=True
            )

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((br, LANES), lambda i, q: (i, _zero()))
            ] * 2,
            out_specs=pl.BlockSpec(
                (1, LANES), lambda i, q: (_zero(), _zero())
            ),
        )
        partials = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            interpret=interpret,
        )(qarr, *mats)
        return jnp.sum(partials, dtype=jnp.int32)

    def mask_fn(qarr, nx, ny):
        n, grid, mats = _prep(nx, ny)

        def kernel(q_ref, a_ref, b_ref, out_ref):
            m = _tile_mask(q_ref, a_ref[...], b_ref[...])
            out_ref[...] = m.astype(jnp.int8)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((br, LANES), lambda i, q: (i, _zero()))
            ] * 2,
            out_specs=pl.BlockSpec((br, LANES), lambda i, q: (i, _zero())),
        )
        m = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
        )(qarr, *mats)
        return m.reshape(-1)[:n].astype(bool)

    return count_fn, mask_fn


def z3_dimscan_mask_rt(nx, ny, bt, qarr, n_ranges: int):
    """XLA-fused dim-plane mask with RUNTIME bounds (the fused-agg /
    streaming engine; the Pallas kernel below is the count champion).
    ``qarr`` is the vector from :func:`z3_dim_plane_qarr`; ``n_ranges``
    is static (one trace per R bucket)."""
    import jax.numpy as jnp

    m = (nx >= qarr[0]) & (nx <= qarr[1])
    m &= (ny >= qarr[2]) & (ny <= qarr[3])
    tm = None
    for k in range(n_ranges):
        r = (bt >= qarr[4 + 2 * k]) & (bt <= qarr[5 + 2 * k])
        tm = r if tm is None else (tm | r)
    return m & tm


def build_z3_dimscan_rt(
    n_ranges: int,
    *,
    block_rows: int = 1024,
    interpret: "bool | None" = None,
    extra_planes: int = 0,
):
    """Pallas dim-plane kernel with RUNTIME query bounds: (count_fn,
    mask_fn) over ``(qarr, nx, ny, bt)``. The query vector rides in SMEM
    via scalar prefetch, so ONE compiled kernel (per power-of-two R
    bucket) serves every window — the serving-path requirement the
    baked-constant builder below cannot meet. Same measured tiling as
    :func:`build_z3_dimscan_pallas` (block_rows=512, 128 lanes).

    ``extra_planes`` is a MEASUREMENT control, not a serving feature: it
    threads that many extra uint32 planes through the kernel whose
    values fold into the mask data-dependently (so Mosaic cannot elide
    the reads) but never change the result for nonzero fill. Padding
    the 12B/row kernel to 16B/row this way settles whether the scan is
    bandwidth-bound or row-rate-bound: if rows/s
    holds while bytes/row grows, the bound is per-row VPU ops, and the
    12B kernel's lower HBM%% is arithmetic, not inefficiency.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    LANES = 128
    br = block_rows
    E = int(extra_planes)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    _zero = lambda: jnp.int32(0)  # noqa: E731 (int32 index-map literal)

    def _tile_mask(q_ref, nx_t, ny_t, bt_t, *extra_t):
        m = (nx_t >= q_ref[0]) & (nx_t <= q_ref[1])
        m &= (ny_t >= q_ref[2]) & (ny_t <= q_ref[3])
        tm = None
        for k in range(n_ranges):
            r = (bt_t >= q_ref[4 + 2 * k]) & (bt_t <= q_ref[5 + 2 * k])
            tm = r if tm is None else (tm | r)
        m = m & tm
        for e_t in extra_t:
            # data-dependent fold (always true for the nonzero fill the
            # caller provides) — the read cannot be optimized away
            m = m & (e_t != jnp.uint32(0))
        return m

    def _prep(nx, ny, bt, extra):
        n = int(nx.shape[0])
        grid = max(1, -(-n // (br * LANES)))
        pad = grid * br * LANES - n
        # NEVER-MATCH padding (0xFFFFFFFF > any 21-bit query bound, and
        # the bt sentinel space is unaddressable by construction) instead
        # of a per-tile row-index tail mask: the kernel is VPU-bound at
        # ~52B rows/s, and the tail's iota+compare cost ~4 ops of the
        # ~17/row -- dropping it buys ~20% (measured 626 -> 745 GB/s)
        mats = [
            jnp.pad(a, (0, pad), constant_values=np.uint32(0xFFFFFFFF)).reshape(
                grid * br, LANES
            )
            for a in (nx, ny, bt) + tuple(extra)
        ]
        return n, grid, mats

    def count_fn(qarr, nx, ny, bt, *extra):
        assert len(extra) == E
        n, grid, mats = _prep(nx, ny, bt, extra)

        def kernel(q_ref, *refs):
            out_ref = refs[-1]
            m = _tile_mask(q_ref, *(r[...] for r in refs[:-1]))

            @pl.when(pl.program_id(0) == 0)
            def _():
                out_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            out_ref[...] = out_ref[...] + jnp.sum(
                m.astype(jnp.int32), axis=0, dtype=jnp.int32, keepdims=True
            )

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            # index maps receive the prefetched scalar ref as a trailing
            # arg; literal indices must be int32 (a raw Python 0 traces
            # to an i64 constant under x64, which Mosaic cannot legalize)
            in_specs=[
                pl.BlockSpec((br, LANES), lambda i, q: (i, _zero()))
            ] * (3 + E),
            out_specs=pl.BlockSpec(
                (1, LANES), lambda i, q: (_zero(), _zero())
            ),
        )
        partials = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            interpret=interpret,
        )(qarr, *mats)
        return jnp.sum(partials, dtype=jnp.int32)

    def mask_fn(qarr, nx, ny, bt, *extra):
        assert len(extra) == E
        n, grid, mats = _prep(nx, ny, bt, extra)

        def kernel(q_ref, *refs):
            out_ref = refs[-1]
            # padding rows never match (see _prep); [:n] slices them off
            m = _tile_mask(q_ref, *(r[...] for r in refs[:-1]))
            out_ref[...] = m.astype(jnp.int8)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((br, LANES), lambda i, q: (i, _zero()))
            ] * (3 + E),
            out_specs=pl.BlockSpec((br, LANES), lambda i, q: (i, _zero())),
        )
        m = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
        )(qarr, *mats)
        return m.reshape(-1)[:n].astype(bool)

    return count_fn, mask_fn


def _dim_tile_mask(qnx, qny, bt_ranges):
    import jax.numpy as jnp

    def tile_mask(nx_t, ny_t, bt_t):
        m = (nx_t >= jnp.uint32(qnx[0])) & (nx_t <= jnp.uint32(qnx[1]))
        m &= (ny_t >= jnp.uint32(qny[0])) & (ny_t <= jnp.uint32(qny[1]))
        tm = None
        for lo, hi in bt_ranges:
            r = (bt_t >= jnp.uint32(lo)) & (bt_t <= jnp.uint32(hi))
            tm = r if tm is None else (tm | r)
        if tm is None:  # empty window
            tm = jnp.zeros(nx_t.shape, bool)
        return m & tm

    return tile_mask


def z3_dimscan_mask(nx, ny, bt, qnx, qny, bt_ranges):
    """XLA-fused dim-plane mask (CI / cross-check engine; the Pallas tile
    kernel below is the TPU bandwidth champion)."""
    return _dim_tile_mask(qnx, qny, bt_ranges)(nx, ny, bt)


def build_z3_dimscan_pallas(
    qnx,
    qny,
    bt_ranges,
    *,
    block_rows: int = 512,
    interpret: "bool | None" = None,
):
    """BAKED-CONSTANT Pallas tile kernel over the de-interleaved key
    planes: (count_fn, mask_fn) over (nx, ny, bt) uint32 1-D device
    planes, query bounds compiled in as uint32 constants.

    Kept as a cross-check engine (tests compare it against the
    runtime-bounds kernel and the XLA mask). SERVING uses
    :func:`build_z3_dimscan_rt` instead — same tiling and speed (runtime
    bounds measured within noise of baked constants), but one compile
    per range bucket serves every window where this builder pays a
    compile per distinct query.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    LANES = 128
    br = block_rows
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    tile_mask = _dim_tile_mask(qnx, qny, bt_ranges)

    _zero = lambda: jnp.int32(0)  # noqa: E731 (int32 index-map literal)
    in_specs = [pl.BlockSpec((br, LANES), lambda i: (i, _zero()))] * 3

    def _prep(nx, ny, bt):
        n = int(nx.shape[0])
        grid = max(1, -(-n // (br * LANES)))
        pad = grid * br * LANES - n
        mats = [
            jnp.pad(a, (0, pad)).reshape(grid * br, LANES)
            for a in (nx, ny, bt)
        ]
        return n, grid, mats

    def _tail(n):
        def apply(m):
            i = pl.program_id(0)
            idx = (
                i * br * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
            )
            return m & (idx < n)

        return apply

    def count_fn(nx, ny, bt):
        n, grid, mats = _prep(nx, ny, bt)
        tail = _tail(n)

        def kernel(a_ref, b_ref, c_ref, out_ref):
            m = tail(tile_mask(a_ref[...], b_ref[...], c_ref[...]))

            @pl.when(pl.program_id(0) == 0)
            def _():
                out_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            out_ref[...] = out_ref[...] + jnp.sum(
                m.astype(jnp.int32), axis=0, dtype=jnp.int32, keepdims=True
            )

        partials = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, LANES), lambda i: (_zero(), _zero())),
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            interpret=interpret,
        )(*mats)
        return jnp.sum(partials, dtype=jnp.int32)

    def mask_fn(nx, ny, bt):
        n, grid, mats = _prep(nx, ny, bt)
        tail = _tail(n)

        def kernel(a_ref, b_ref, c_ref, out_ref):
            m = tail(tile_mask(a_ref[...], b_ref[...], c_ref[...]))
            out_ref[...] = m.astype(jnp.int8)

        m = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((br, LANES), lambda i: (i, _zero())),
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
        )(*mats)
        return m.reshape(-1)[:n].astype(bool)

    return count_fn, mask_fn


def kind_mask_fn(kind: str):
    """Key-plane mask function for an index-key kind — the ONE dispatch
    table shared by the direct loose path and the fused-stats closure
    (binned kinds take (hi, lo, bins, bounds, ids); unbinned (hi, lo,
    bounds))."""
    return {
        "z3": z3_zscan_mask,
        "z2": z2_zscan_mask,
        "xz3": xz3_range_mask,
        "xz2": xz_range_mask,
    }[kind]


def batched_kind_mask(kind: str):
    """Q-stacked variant of :func:`kind_mask_fn` for micro-batch scan
    fusion (the device query scheduler): the query bounds/ids gain a
    leading query axis and the key planes broadcast, so Q compatible
    queries resolve in ONE device launch returning a (Q, n) hit matrix.
    Binned kinds take (hi, lo, bins, bounds[Q,...], ids[Q, B]); unbinned
    (hi, lo, bounds[Q, ...])."""
    import jax

    mf = kind_mask_fn(kind)
    if kind in ("z3", "xz3"):
        return jax.vmap(mf, in_axes=(None, None, None, 0, 0))
    return jax.vmap(mf, in_axes=(None, None, 0))


def batched_dim_mask_rt(n_ranges: int):
    """Q-stacked dim-plane mask with runtime bounds: ``qmat`` is the
    (Q, 4 + 2R) stack of :func:`z3_dim_plane_qarr` vectors (or (Q, 4)
    :func:`z2_dim_plane_qarr` vectors when ``n_ranges == 0``) and the
    result is (Q, n). The scheduler's fusion path uses the XLA engine —
    the per-query Pallas SMEM prefetch does not batch — which is
    cross-checked against the Pallas count champion elsewhere."""
    import jax

    if n_ranges == 0:
        return jax.vmap(z2_dimscan_mask_rt, in_axes=(None, None, 0))
    return jax.vmap(
        lambda nx, ny, bt, q: z3_dimscan_mask_rt(nx, ny, bt, q, n_ranges),
        in_axes=(None, None, None, 0),
    )


def build_z3_pallas_scan(
    bounds: np.ndarray,
    bin_ids: np.ndarray,
    *,
    block_rows: "int | None" = None,
    interpret: "bool | None" = None,
):
    """BAKED-CONSTANT Pallas kernel for the INTERLEAVED masked-compare
    key scan: (count_fn, mask_fn) over (bins int32, z_hi uint32, z_lo
    uint32) 1-D device planes — a cross-check engine for the interleaved
    layout (the resident cache serves z3/z2 from dim planes via
    build_z3_dimscan_rt; the interleaved layout remains for xz kinds and
    wide-bin-span schemas, served by the XLA kind_mask_fn path).

    Query bounds bake in as uint32 constants; padded bin entries
    (id < 0) are skipped at trace time, costing nothing. Same tiling discipline as
    ops/pallas_scan.py: (block_rows, 128) tiles DMA'd HBM->VMEM, a
    (1, 128) revisited accumulator tile for the count (TPU grids run
    sequentially per core), tail mask so padding rows never count, and
    interpret mode off-TPU so CI runs the identical kernel code.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    LANES = 128
    br = block_rows or 512
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    entries = [
        (int(bin_ids[b]), [[int(v) for v in bounds[b, d]] for d in range(3)])
        for b in range(len(bin_ids))
        if int(bin_ids[b]) >= 0
    ]

    def tile_mask(bins_t, zh_t, zl_t):
        m = None
        for bid, dims in entries:
            mb = bins_t == jnp.int32(bid)
            for mask_hi, mask_lo, lo_hi, lo_lo, hi_hi, hi_lo in dims:
                zm_hi = zh_t & jnp.uint32(mask_hi)
                zm_lo = zl_t & jnp.uint32(mask_lo)
                ge = (zm_hi > jnp.uint32(lo_hi)) | (
                    (zm_hi == jnp.uint32(lo_hi)) & (zm_lo >= jnp.uint32(lo_lo))
                )
                le = (zm_hi < jnp.uint32(hi_hi)) | (
                    (zm_hi == jnp.uint32(hi_hi)) & (zm_lo <= jnp.uint32(hi_lo))
                )
                mb = mb & ge & le
            m = mb if m is None else (m | mb)
        if m is None:  # all bins padded out: constant-false scan
            m = jnp.zeros(bins_t.shape, bool)
        return m

    _zero = lambda: jnp.int32(0)  # noqa: E731 (int32 index-map literal)
    in_specs = [pl.BlockSpec((br, LANES), lambda i: (i, _zero()))] * 3

    def _prep(bins, z_hi, z_lo):
        n = int(bins.shape[0])
        grid = max(1, -(-n // (br * LANES)))
        pad = grid * br * LANES - n
        mats = [
            jnp.pad(a, (0, pad)).reshape(grid * br, LANES)
            for a in (bins, z_hi, z_lo)
        ]
        return n, grid, mats

    def _tail(n):
        def apply(m):
            i = pl.program_id(0)
            idx = (
                i * br * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0) * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
            )
            return m & (idx < n)

        return apply

    def count_fn(bins, z_hi, z_lo):
        n, grid, mats = _prep(bins, z_hi, z_lo)
        tail = _tail(n)

        def kernel(b_ref, zh_ref, zl_ref, out_ref):
            m = tail(tile_mask(b_ref[...], zh_ref[...], zl_ref[...]))

            @pl.when(pl.program_id(0) == 0)
            def _():
                out_ref[...] = jnp.zeros((1, LANES), jnp.int32)

            out_ref[...] = out_ref[...] + jnp.sum(
                m.astype(jnp.int32), axis=0, dtype=jnp.int32, keepdims=True
            )

        partials = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, LANES), lambda i: (_zero(), _zero())),
            out_shape=jax.ShapeDtypeStruct((1, LANES), jnp.int32),
            interpret=interpret,
        )(*mats)
        return jnp.sum(partials, dtype=jnp.int32)

    def mask_fn(bins, z_hi, z_lo):
        n, grid, mats = _prep(bins, z_hi, z_lo)
        tail = _tail(n)

        def kernel(b_ref, zh_ref, zl_ref, out_ref):
            m = tail(tile_mask(b_ref[...], zh_ref[...], zl_ref[...]))
            out_ref[...] = m.astype(jnp.int8)

        m = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((br, LANES), lambda i: (i, _zero())),
            out_shape=jax.ShapeDtypeStruct((grid * br, LANES), jnp.int8),
            interpret=interpret,
        )(*mats)
        return m.reshape(-1)[:n].astype(bool)

    return count_fn, mask_fn


def pad_bins(bounds: np.ndarray, bin_ids: np.ndarray, min_b: int = 1):
    """Pad the bin axis up to the compile-shape ladder (>= min_b; next
    power of two on the default ladder) so jit sees a bounded set of B
    shapes; pad ids are -1 (match nothing)."""
    from geomesa_tpu.bucketing import bucket_cap

    b = len(bin_ids)
    cap = max(min_b, bucket_cap(b))
    if cap == b:
        return bounds, bin_ids
    pb = np.zeros((cap,) + bounds.shape[1:], bounds.dtype)
    pb[:b] = bounds
    pi = np.full(cap, -1, np.int32)
    pi[:b] = bin_ids
    return pb, pi
