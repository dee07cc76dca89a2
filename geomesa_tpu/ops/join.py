"""Batched spatial-join refinement kernels.

The join engine (``geomesa_tpu/join``) plans candidate RUNS — contiguous
row ranges of the Z-sorted join layout, one per (window, covering cell) —
and this module turns run batches into emitted (row, window) pairs:

- **expansion**: run p of the batch contributes rows ``starts[p] ..
  starts[p] + lens[p]`` against window ``wins[p]``; the flat candidate
  index space is segmented by the run-length cumsum (a vectorized
  ``searchsorted``, no per-run dispatch).
- **refinement**: each candidate row's coordinates test against its
  window's envelope — except candidates from INTERIOR runs (cells
  strictly inside the window's covering ring), which are hits by
  construction and skip the coordinate fetch entirely.
- **emission**: fixed-shape count -> cap -> compact (the ``_mesh_hits``
  discipline): a cheap count launch sizes a power-of-two compaction cap,
  then the compact launch scatters the surviving pairs into bounded
  buffers fetched once. Order is preserved end to end (runs are planned
  window-major with ascending rows), so emission needs no sort.

The host (numpy) twins are the bit-identical oracle the device kernels
are tested against AND the production engine on all-CPU harnesses, where
XLA:CPU gathers lose to numpy (the ``mesh.sort.engine`` precedent).
"""

from __future__ import annotations

import numpy as np

# jit caches keyed by static kernel shape buckets (candidate bucket C,
# run bucket R, compaction cap, dtype, gating): bounded — every bucket
# edge sits on the conf-declared compile-shape ladder (next power of
# two on the default ladder)
_COUNT_JITS: dict = {}
_COMPACT_JITS: dict = {}
_MESH_JITS: dict = {}


def next_pow2(n: int) -> int:
    """Round a candidate/run capacity onto the canonical compile-shape
    ladder (:mod:`geomesa_tpu.bucketing`). The name survives from the
    pow2-only era — the default ladder IS next-power-of-two."""
    from geomesa_tpu.bucketing import bucket_cap

    return bucket_cap(n)


def mesh_key(mesh) -> tuple:
    """Stable identity for a mesh: device ids + axis shape. Keying the
    jit caches on ``id(mesh)`` would grow one executable set per mesh
    OBJECT ever constructed (and pin each dead mesh alive through the
    kernel closures); keyed on identity, equal meshes share entries and
    the cache is bounded by the distinct device topologies in use."""
    return (
        tuple(int(d.id) for d in np.ravel(mesh.devices)),
        tuple(mesh.shape.items()),
    )


# -- host expansion + refinement (the oracle engine) -----------------------


def expand_runs(starts, lens, wins, interior):
    """Flatten candidate runs into aligned (rows, wins, interior) arrays.

    ``rows`` enumerates ``starts[p] .. starts[p]+lens[p]`` for each run p
    in order — one cumsum over the candidate space, no per-run python.
    Zero-length runs are dropped before expansion."""
    lens = np.asarray(lens, np.int64)
    keep = lens > 0
    if not np.all(keep):
        starts = np.asarray(starts)[keep]
        wins = np.asarray(wins)[keep]
        interior = np.asarray(interior)[keep]
        lens = lens[keep]
    if len(lens) == 0:
        e = np.empty(0, np.int64)
        return e, e.copy(), np.empty(0, bool)
    total = int(lens.sum())
    csum = np.cumsum(lens)
    # rows via delta-encoded cumsum: position 0 starts the first run and
    # every run boundary jumps from the previous run's end to the next
    # run's start; everything else increments by one
    deltas = np.ones(total, np.int64)
    deltas[0] = int(starts[0])
    deltas[csum[:-1]] = np.asarray(starts[1:], np.int64) - (
        np.asarray(starts[:-1], np.int64) + lens[:-1] - 1
    )
    rows = np.cumsum(deltas)
    winv = np.repeat(np.asarray(wins, np.int64), lens)
    iflag = np.repeat(np.asarray(interior, bool), lens)
    return rows, winv, iflag


def refine_host(xs, ys, envs, rows, winv, iflag, gate=None):
    """Exact envelope refinement of expanded candidates on host: hit
    mask over the candidates. Interior candidates skip the coordinate
    fetch (hits by construction); ``gate`` is an optional per-row bool
    plane (base filter / visibility) ANDed into every candidate."""
    hit = iflag.copy()
    bidx = np.nonzero(~iflag)[0]
    if len(bidx):
        brow = rows[bidx]
        e = envs[winv[bidx]]
        px = xs[brow]
        py = ys[brow]
        bh = (
            (px >= e[:, 0])
            & (px <= e[:, 2])
            & (py >= e[:, 1])
            & (py <= e[:, 3])
        )
        hit[bidx] = bh
    if gate is not None:
        hit &= gate[rows]
    return hit


def refine_host_env(ex0, ey0, ex1, ey1, envs, rows, winv, iflag, gate=None):
    """Envelope-OVERLAP refinement for non-point left sides (per-row
    envelope planes vs window envelopes) — the coarse pass of a
    topological join; the exact predicate refines the emitted pairs."""
    hit = iflag.copy()
    bidx = np.nonzero(~iflag)[0]
    if len(bidx):
        brow = rows[bidx]
        e = envs[winv[bidx]]
        bh = (
            (ex1[brow] >= e[:, 0])
            & (ex0[brow] <= e[:, 2])
            & (ey1[brow] >= e[:, 1])
            & (ey0[brow] <= e[:, 3])
        )
        hit[bidx] = bh
    if gate is not None:
        hit &= gate[rows]
    return hit


# -- device kernels (count -> cap -> compact) ------------------------------


def _expand_refine(planes, starts, lens, csum, winv, iflag, envs, total,
                   gate, C, n_planes):
    """Shared traced body: expand the run batch into the C-sized
    candidate space and compute the hit vector. ``planes`` is (x, y) for
    point layouts or (x0, y0, x1, y1) envelope planes for non-point
    (overlap test)."""
    import jax.numpy as jnp

    R = starts.shape[0]
    p = jnp.arange(C, dtype=jnp.int32)
    seg = jnp.searchsorted(csum, p, side="right").astype(jnp.int32)
    segc = jnp.minimum(seg, R - 1)
    base = csum[segc] - lens[segc]
    row = starts[segc] + (p - base)
    row = jnp.clip(row, 0, planes[0].shape[0] - 1)
    win = winv[segc]
    valid = p < total
    e = envs[win]
    if n_planes == 2:
        px = planes[0][row]
        py = planes[1][row]
        env_hit = (
            (px >= e[:, 0]) & (px <= e[:, 2])
            & (py >= e[:, 1]) & (py <= e[:, 3])
        )
    else:
        env_hit = (
            (planes[2][row] >= e[:, 0]) & (planes[0][row] <= e[:, 2])
            & (planes[3][row] >= e[:, 1]) & (planes[1][row] <= e[:, 3])
        )
    hit = valid & (iflag[segc] | env_hit)
    if gate is not None:
        hit = hit & gate[row]
    return row, win, hit


def count_kernel(C: int, n_planes: int, gated: bool, dtype):
    """Jitted candidate-count launch for one (C, planes, gate) bucket:
    returns the number of surviving pairs (a scalar fetch that sizes the
    compact launch's cap)."""
    import jax
    import jax.numpy as jnp

    key = ("count", C, n_planes, gated, np.dtype(dtype).str)
    fn = _COUNT_JITS.get(key)
    if fn is None:

        def _count(planes, starts, lens, csum, winv, iflag, envs, total,
                   gate):
            _, _, hit = _expand_refine(
                planes, starts, lens, csum, winv, iflag, envs, total,
                gate, C, n_planes,
            )
            return jnp.sum(hit, dtype=jnp.int32)

        fn = jax.jit(_count)
        _COUNT_JITS[key] = fn
    return fn


def compact_kernel(C: int, cap: int, n_planes: int, gated: bool, dtype):
    """Jitted compact launch for one (C, cap, planes, gate) bucket:
    scatters surviving (row, window) pairs — order preserved — into
    cap-sized buffers plus the true count (callers slice ``[:count]``)."""
    import jax
    import jax.numpy as jnp

    key = ("compact", C, cap, n_planes, gated, np.dtype(dtype).str)
    fn = _COMPACT_JITS.get(key)
    if fn is None:

        def _compact(planes, starts, lens, csum, winv, iflag, envs, total,
                     gate):
            row, win, hit = _expand_refine(
                planes, starts, lens, csum, winv, iflag, envs, total,
                gate, C, n_planes,
            )
            pos = jnp.cumsum(hit.astype(jnp.int32)) - 1
            idx = jnp.where(hit & (pos < cap), pos, cap)  # cap = trash slot
            rbuf = jnp.zeros((cap + 1,), jnp.int32).at[idx].set(row)
            wbuf = jnp.zeros((cap + 1,), jnp.int32).at[idx].set(win)
            return rbuf[:cap], wbuf[:cap], jnp.sum(hit, dtype=jnp.int32)

        fn = jax.jit(_compact)
        _COMPACT_JITS[key] = fn
    return fn


def mesh_count_kernel(mesh, axis: str, C: int, n_planes: int,
                      gated: bool, dtype):
    """Per-shard candidate counts for one co-partitioned run batch —
    the count half of the mesh count -> cap -> compact discipline (one
    cheap (shards,)-vector fetch sizes the compact launch's cap)."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("mesh-count", mesh_key(mesh), axis, C, n_planes, gated,
           np.dtype(dtype).str)
    fn = _MESH_JITS.get(key)
    if fn is None:
        spec = P(axis)

        @partial(
            shard_map, mesh=mesh,
            in_specs=(spec,) * n_planes + (spec,) * 5 + (P(),)
            + ((spec,) if gated else ()),
            out_specs=spec, check_vma=False,
        )
        def _mesh_count(*args):
            planes = args[:n_planes]
            starts, lens, csum, winv, iflag = args[n_planes:n_planes + 5]
            envs = args[n_planes + 5]
            gate = args[n_planes + 6] if gated else None
            total = csum[-1]
            _, _, hit = _expand_refine(
                planes, starts, lens, csum,
                winv.astype(jnp.int32), iflag, envs, total,
                gate, C, n_planes,
            )
            return jnp.sum(hit, dtype=jnp.int32)[None]

        fn = jax.jit(_mesh_count)
        _MESH_JITS[key] = fn
    return fn


def mesh_join_kernel(mesh, axis: str, C: int, cap: int, n_planes: int,
                     gated: bool, dtype):
    """Co-partitioned mesh refinement: ONE SPMD launch where every shard
    expands and refines ITS OWN run batch against ITS OWN resident rows
    and compacts local pairs into a fixed (cap) buffer — row ids are
    globalized in-kernel from the shard index. There is NO cross-shard
    collective anywhere in the body: co-partitioned planning (runs
    clipped at shard row boundaries) already guaranteed every candidate
    is shard-local, so the launch is pure local compute + one gather of
    the fixed-shape output buffers (zero row exchange)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    key = ("mesh", mesh_key(mesh), axis, C, cap, n_planes, gated,
           np.dtype(dtype).str)
    fn = _MESH_JITS.get(key)
    if fn is None:
        spec = P(axis)

        from functools import partial

        @partial(
            shard_map, mesh=mesh,
            in_specs=(spec,) * n_planes + (spec,) * 5 + (P(),)
            + ((spec,) if gated else ()),
            out_specs=(spec, spec, spec), check_vma=False,
        )
        def _mesh_body(*args):
            planes = args[:n_planes]
            starts, lens, csum, winv, iflag = args[n_planes:n_planes + 5]
            envs = args[n_planes + 5]
            gate = args[n_planes + 6] if gated else None
            total = csum[-1]
            row, win, hit = _expand_refine(
                planes, starts, lens, csum,
                winv.astype(jnp.int32), iflag, envs, total,
                gate, C, n_planes,
            )
            shard = jax.lax.axis_index(axis).astype(jnp.int32)
            grow = row + shard * planes[0].shape[0]
            pos = jnp.cumsum(hit.astype(jnp.int32)) - 1
            idx = jnp.where(hit & (pos < cap), pos, cap)
            rbuf = jnp.zeros((cap + 1,), jnp.int32).at[idx].set(grow)
            wbuf = jnp.zeros((cap + 1,), jnp.int32).at[idx].set(win)
            return rbuf[:cap], wbuf[:cap], jnp.sum(hit, dtype=jnp.int32)[None]

        fn = jax.jit(_mesh_body)
        _MESH_JITS[key] = fn
    return fn
