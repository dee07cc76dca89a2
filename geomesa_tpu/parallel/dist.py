"""Distributed index build + scan steps (shard_map + XLA collectives).

The pod-scale Z-order sort (SURVEY.md section 2.6 row "Z-order bulk sort"
and section 7 hard part #5): each chip buckets its local rows by sort key,
exchanges buckets over ICI with ``all_to_all``, and locally sorts --
yielding a globally sorted, shard-partitioned index. Row payloads
(feature ids / column pytrees) ride the same exchange, so the device sort
produces a queryable permutation, not just keys. Scans run shard-local
fused masks merged with ``psum``.

Exchange architecture (rebuilt in ISSUE 8; the PR 5-era version ran a
round-robin rebalance pass + a quantile-routing pass, each its own
all_to_all, with a flat 2x capacity factor):

- **One fused pass.** Splitters are sampled from the raw layout and rows
  route straight to their destination range -- no rebalance pass. The
  per-(source, destination) block maximum is measured exactly on device
  and psum-maxed; when the optimistic capacity guess overflows, the
  wrapper relaunches once at the measured bound (geometric bucket, so
  jit shapes stay bounded) -- adversarial layouts (pre-sorted input,
  GDELT hot cells) cost one extra launch, not a standing 2x buffer tax
  on every ordinary sort.
- **One packed buffer.** Key lanes, the validity word and EVERY payload
  leaf -- any dtype, any trailing shape -- are bitcast/widened into u32
  columns and stacked into a single exchange buffer, so the whole pass
  costs exactly one all_to_all (per-collective latency dominates at
  these block sizes). The PR 5 version exchanged non-4-byte leaves one
  collective each.
- **Local sort, single-chip lane layout.** The post-exchange sort is the
  same ``lax.sort`` over uint32 key lanes (+ validity + permutation)
  the single-chip build uses, so build and serve cannot drift.
- **Host-radix engine for CPU meshes.** On an all-CPU mesh (the
  8-virtual-device test/bench harness, and any host-only deploy) the
  node-local stages run numpy's radix sort -- XLA:CPU's comparison sort
  measures ~20x off the radix floor on these key widths -- while the
  exchange itself still crosses the real XLA ``all_to_all``. Accelerator
  meshes keep everything on device. ``mesh.sort.engine`` (auto | device
  | host) pins the choice.

All device-engine functions are pure and jittable over a Mesh; fixed
shapes throughout (bucket capacity is static per launch). Rows that
would exceed a destination's capacity are counted with a ``psum`` and
surfaced on the host via ``on_overflow`` (raise by default -- silent
loss is not an option for an index build).
"""

from __future__ import annotations

import warnings
from functools import partial

import numpy as np

_SENTINEL = 0xFFFFFFFF

# jitted exchange-step cache: a fresh ``jax.jit(step)`` per call would
# RE-COMPILE the whole exchange on every invocation (jit's in-memory
# cache lives on the wrapper object) — a full compile per flush.
# Keyed by every static the step closure bakes in;
# input shapes are handled by the cached wrapper's own jit cache.
_STEP_CACHE: dict = {}


def _resolve_engine(engine: "str | None", mesh) -> str:
    """auto -> ``host`` iff every mesh device is CPU-backed (node-local
    sorts then run the numpy radix engine; the exchange stays XLA)."""
    if engine is None:
        from geomesa_tpu.conf import sys_prop

        engine = str(sys_prop("mesh.sort.engine"))
    if engine not in ("auto", "device", "host"):
        raise ValueError(f"unknown mesh sort engine {engine!r}")
    if engine == "auto":
        try:
            cpu = all(d.platform == "cpu" for d in mesh.devices.flat)
        except Exception:  # pragma: no cover - exotic mesh objects
            cpu = False
        engine = "host" if cpu else "device"
    return engine


def sharded_count_scan(mesh, device_fn, cols: dict, axis: str = "shard"):
    """Data-parallel fused-mask count: each shard scans its resident slice,
    psum merges (the BatchScanner fan-out + client merge)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = P(axis)
    sharded_cols = {
        k: jax.device_put(v, NamedSharding(mesh, spec)) for k, v in cols.items()
    }

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec,) * len(sharded_cols),
        out_specs=P(),
        check_vma=False,
    )
    def step(*arrs):
        local = dict(zip(sorted(sharded_cols), arrs))
        mask = device_fn(local)
        return jax.lax.psum(mask.sum(), axis)

    ordered = tuple(sharded_cols[k] for k in sorted(sharded_cols))
    return jax.jit(step)(*ordered)


# -- payload leaf <-> uint32 column packing ----------------------------------
#
# Every payload leaf rides the ONE stacked exchange buffer as uint32
# columns: 4-byte scalars bitcast 1:1, 8-byte scalars split into two
# words, 1/2-byte scalars widen (value-preserving round trip), bools ride
# as 0/1 words, and trailing dims flatten to one column each. The same
# descriptor drives the numpy (host engine) and jnp (device engine)
# packers so the two engines cannot disagree about layout.


def _leaf_n_cols(shape, dtype) -> int:
    dt = np.dtype(dtype)
    flat = int(np.prod(shape[1:], dtype=np.int64)) if len(shape) > 1 else 1
    per = 2 if dt.itemsize == 8 else 1
    if dt.itemsize not in (1, 2, 4, 8):
        raise ValueError(
            f"payload dtype {dt} (itemsize {dt.itemsize}) cannot ride the "
            "packed exchange buffer"
        )
    return flat * per


def _np_leaf_cols(a: np.ndarray) -> list:
    """Host leaf -> list of 1-D uint32 columns (lossless round trip)."""
    flat = a.reshape(len(a), -1) if a.ndim > 1 else a[:, None]
    cols: list = []
    for i in range(flat.shape[1]):
        p = np.ascontiguousarray(flat[:, i])
        dt = p.dtype
        if dt == np.bool_:
            cols.append(p.astype(np.uint32))
        elif dt.itemsize == 4:
            cols.append(p.view(np.uint32))
        elif dt.itemsize == 8:
            w = p.view(np.uint32).reshape(-1, 2)
            cols += [np.ascontiguousarray(w[:, 0]),
                     np.ascontiguousarray(w[:, 1])]
        elif dt.itemsize == 2:
            cols.append(p.view(np.uint16).astype(np.uint32))
        else:  # itemsize 1
            cols.append(p.view(np.uint8).astype(np.uint32))
    return cols


def _np_leaf_restore(cols: list, shape, dtype) -> np.ndarray:
    """Inverse of :func:`_np_leaf_cols` for rows of a different length
    (the exchange changes per-shard row counts)."""
    dt = np.dtype(dtype)
    n = len(cols[0])
    parts: list = []
    it = iter(cols)
    flat_cols = _leaf_n_cols(shape, dtype) // (2 if dt.itemsize == 8 else 1)
    for _ in range(flat_cols):
        if dt == np.bool_:
            parts.append(next(it) != 0)
        elif dt.itemsize == 4:
            parts.append(np.ascontiguousarray(next(it)).view(dt))
        elif dt.itemsize == 8:
            w = np.stack([next(it), next(it)], axis=1)
            parts.append(np.ascontiguousarray(w).view(dt).reshape(-1))
        elif dt.itemsize == 2:
            parts.append(
                next(it).astype(np.uint16).view(dt)
            )
        else:
            parts.append(next(it).astype(np.uint8).view(dt))
    out = np.stack(parts, axis=1) if len(parts) > 1 else parts[0][:, None]
    return np.ascontiguousarray(out.reshape((n,) + tuple(shape[1:])))


def _jnp_leaf_cols(x) -> list:
    """Traced leaf -> list of 1-D uint32 columns (device engine)."""
    import jax
    import jax.numpy as jnp

    flat = x.reshape(x.shape[0], -1) if x.ndim > 1 else x[:, None]
    cols: list = []
    for i in range(flat.shape[1]):
        p = flat[:, i]
        dt = np.dtype(p.dtype)
        if dt == np.bool_:
            cols.append(p.astype(jnp.uint32))
        elif dt.itemsize == 4:
            cols.append(jax.lax.bitcast_convert_type(p, jnp.uint32))
        elif dt.itemsize == 8:
            w = jax.lax.bitcast_convert_type(p, jnp.uint32)  # (n, 2)
            cols += [w[:, 0], w[:, 1]]
        elif dt.itemsize == 2:
            cols.append(
                jax.lax.bitcast_convert_type(p, jnp.uint16).astype(jnp.uint32)
            )
        elif dt.itemsize == 1:
            cols.append(
                jax.lax.bitcast_convert_type(p, jnp.uint8).astype(jnp.uint32)
            )
        else:
            raise ValueError(
                f"payload dtype {dt} cannot ride the packed exchange buffer"
            )
    return cols


def _jnp_leaf_restore(cols: list, shape, dtype):
    import jax
    import jax.numpy as jnp

    dt = np.dtype(dtype)
    n = cols[0].shape[0]
    parts: list = []
    it = iter(cols)
    flat_cols = _leaf_n_cols(shape, dtype) // (2 if dt.itemsize == 8 else 1)
    for _ in range(flat_cols):
        if dt == np.bool_:
            parts.append(next(it) != 0)
        elif dt.itemsize == 4:
            parts.append(jax.lax.bitcast_convert_type(next(it), dt))
        elif dt.itemsize == 8:
            w = jnp.stack([next(it), next(it)], axis=1)
            parts.append(jax.lax.bitcast_convert_type(w, dt))
        elif dt.itemsize == 2:
            parts.append(
                jax.lax.bitcast_convert_type(next(it).astype(jnp.uint16), dt)
            )
        else:
            parts.append(
                jax.lax.bitcast_convert_type(next(it).astype(jnp.uint8), dt)
            )
    out = jnp.stack(parts, axis=1) if len(parts) > 1 else parts[0][:, None]
    return out.reshape((n,) + tuple(shape[1:]))


def _cap_bucket(b: int) -> int:
    """Round a measured capacity up to the next power-of-two bucket so
    the retry launch's jit shapes come from a bounded set."""
    return 1 << max(int(b) - 1, 0).bit_length()


def distributed_sort(
    mesh,
    keys,
    axis: str = "shard",
    capacity_factor: "float | None" = None,
    splitters: str = "sampled",
    sample_per_shard: int = 64,
    payload=None,
    valid=None,
    on_overflow: str = "raise",
    engine: "str | None" = None,
):
    """Exchange-sort rows across the mesh by lexicographic uint32 key lanes.

    ``keys`` is a tuple of same-length uint32 arrays, most-significant lane
    first (a 63-bit z key is ``(hi, lo)``; a binned-time z3 key is
    ``(bin, hi, lo)`` -- TPU-friendly 32-bit lanes instead of uint64).
    ``payload`` is an optional pytree of arrays with leading dim ``n`` whose
    rows travel with their keys through the exchange. ``valid`` marks real
    rows (False = padding added by the caller to reach a shard-divisible
    length).

    Returns ``(keys, payload, valid)``: shard s of the output holds the s-th
    globally-sorted key range, locally sorted, with padding masked by
    ``valid`` (invalid rows carry sentinel keys and sort last per shard).

    ``splitters='sampled'`` (default) routes by globally-sampled key
    quantiles in ONE all_to_all pass: the per-(source, destination) block
    maximum is measured exactly in-launch, and an optimistic capacity
    guess (``capacity_factor`` x the uniform mean) that overflows is
    retried once at the measured bound -- so ordinary layouts pay one
    tight pass and adversarial ones (pre-sorted, all-duplicate, GDELT
    hot cells; SURVEY hard part #5) pay one extra launch instead of
    losing rows. Rows equal to a splitter spread round-robin across the
    tied range, so duplicate-heavy data cannot overload one destination.
    ``'radix'`` routes by the top 16 bits of lane 0 in a single pass with
    a flat ``capacity_factor`` budget: cheaper, but requires lane 0 to
    spread (31 significant bits) and a hot cell overflows loudly.

    ``engine`` picks where the node-local stages run: ``device`` (one
    jitted step, everything on-chip — accelerator meshes), ``host``
    (numpy radix sorts + XLA all_to_all — CPU meshes, where XLA's
    comparison sort is ~20x off the radix floor), or None/``auto``
    (the ``mesh.sort.engine`` conf key; auto picks by mesh platform).

    Overflowed rows are *counted on device* (psum across the mesh) and the
    count is checked on host: ``on_overflow='raise'`` (default) raises
    RuntimeError, ``'warn'`` warns, ``'ignore'`` skips the device fetch
    (the ``valid`` output still reports survivors). Works for any shard-
    axis size, power of two or not.
    """
    import jax

    if splitters not in ("sampled", "radix"):
        raise ValueError(f"unknown splitter strategy {splitters!r}")
    if on_overflow not in ("raise", "warn", "ignore"):
        raise ValueError(f"unknown on_overflow mode {on_overflow!r}")
    if capacity_factor is None:
        # sampled: a tight first-launch guess — the measured-capacity
        # relaunch absorbs anything past it. radix: NO retry exists
        # (dest is a static bit slice, remeasuring would not change it),
        # so it keeps the PR 5-era flat 2x budget
        capacity_factor = 1.25 if splitters == "sampled" else 2.0

    n_shards = mesh.shape[axis]
    payload_leaves, payload_def = jax.tree.flatten(
        {} if payload is None else payload
    )
    engine = _resolve_engine(engine, mesh)
    if engine == "host" and splitters == "sampled":
        keys_out, leaves_out, valid_out = _host_staged_sort(
            mesh, axis, n_shards, keys, payload_leaves, valid,
            sample_per_shard,
        )
        return keys_out, jax.tree.unflatten(payload_def, leaves_out), valid_out
    return _device_sort(
        mesh, axis, n_shards, keys, payload_leaves, payload_def, valid,
        capacity_factor, splitters, sample_per_shard, on_overflow,
    )


# -- host-staged engine (CPU meshes) -----------------------------------------


def _a2a_jitted(mesh, axis: str):
    """Cached jitted shard_map all_to_all over (n*n, cap, C) blocks."""
    import jax
    from jax.sharding import PartitionSpec as P

    key = ("a2a", mesh, axis)
    fn = _STEP_CACHE.get(key)
    if fn is None:

        @partial(
            jax.shard_map, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
            check_vma=False,
        )
        def step(blocks):
            return jax.lax.all_to_all(blocks, axis, 0, 0, tiled=False)

        fn = jax.jit(step)
        _STEP_CACHE[key] = fn
    return fn


def _host_lex_order(lanes: list) -> np.ndarray:
    """Stable ascending order over uint32 lanes (most-significant first):
    the SAME native byte-wise LSD radix engine the single-chip host
    build sorts with (native/sort.cpp, ~2.5x numpy's stable argsort
    here), falling back to numpy's radix — the host twin of the device
    ``lax.sort`` lane layout."""
    from geomesa_tpu import native

    if native.enabled():
        order = native.radix_argsort(list(lanes))
        if order is not None:
            return order
    if len(lanes) == 1:
        return np.argsort(lanes[0], kind="stable")
    if len(lanes) == 2:
        k64 = (lanes[0].astype(np.uint64) << np.uint64(32)) | lanes[1]
        return np.argsort(k64, kind="stable")
    return np.lexsort(tuple(reversed(lanes)))


def _host_dest(ks: list, spl: list, n_shards: int) -> np.ndarray:
    """Destination shard per row: lexicographic rank among the sampled
    splitters, full-key-equal ties spread round-robin across the tied
    range (equal keys are order-free; spreading keeps duplicate-heavy
    data from overloading one destination). One vectorized compare per
    splitter — for the handful of splitters a mesh has, that is ~3x
    cheaper than per-row binary searches."""
    n = len(ks[0])
    dtype = np.uint8 if n_shards <= 255 else np.int32
    d_lo = np.zeros(n, dtype)
    d_hi = np.zeros(n, dtype)
    if len(ks) <= 2:
        if len(ks) == 1:
            k64 = ks[0].astype(np.uint64)
            s64 = spl[0].astype(np.uint64)
        else:
            k64 = (ks[0].astype(np.uint64) << np.uint64(32)) | ks[1]
            s64 = (spl[0].astype(np.uint64) << np.uint64(32)) | spl[1]
        for sp in s64.tolist():
            d_lo += k64 > sp
            d_hi += k64 >= sp
    else:
        gt = np.zeros((n, n_shards - 1), bool)
        eq = np.ones((n, n_shards - 1), bool)
        for lane, sp in zip(ks, spl):
            gt |= eq & (lane[:, None] > sp[None, :])
            eq &= lane[:, None] == sp[None, :]
        d_lo = gt.sum(axis=1).astype(dtype)
        d_hi = (gt | eq).sum(axis=1).astype(dtype)
    ties = d_hi != d_lo
    if ties.any():
        # spread only the tied rows: the modulo pass over every row is
        # pure waste on tie-free (typical) layouts
        span = (d_hi[ties] - d_lo[ties]).astype(np.int64) + 1
        d_lo = d_lo.astype(dtype, copy=True)
        d_lo[ties] += (np.nonzero(ties)[0] % span).astype(dtype)
    return d_lo


def _host_staged_sort(
    mesh, axis: str, n_shards: int, keys, payload_leaves, valid,
    sample_per_shard: int,
):
    """The CPU-mesh engine: splitter planning, bucketing and the local
    sorts run host-side on numpy's radix machinery; the exchange itself
    is the real XLA ``all_to_all`` over the mesh. Capacity is EXACT
    (per-block counts are known before the buffers are built), so this
    engine can never drop a row."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_lanes = len(keys)
    ks = [np.ascontiguousarray(np.asarray(k, dtype=np.uint32)) for k in keys]
    n = int(ks[0].shape[0])
    leaves = [np.asarray(p) for p in payload_leaves]
    leaf_meta = [(p.shape, p.dtype) for p in leaves]
    v = np.ones(n, bool) if valid is None else np.asarray(valid).astype(bool)
    sharding = NamedSharding(mesh, P(axis))

    if n == 0:
        put = lambda a: jax.device_put(a)  # noqa: E731 - nothing to shard
        return (
            tuple(put(k) for k in ks),
            [put(p) for p in leaves],
            put(v),
        )
    if n % n_shards:
        raise ValueError(
            f"row count {n} must divide the shard axis ({n_shards}); pad "
            "with valid=False rows"
        )
    local_n = n // n_shards

    # --- splitters from per-shard samples (valid rows first) ---
    k_samp = max(1, min(sample_per_shard, local_n))
    samp_idx: list = []
    for s in range(n_shards):
        base = s * local_n
        vi = np.nonzero(v[base : base + local_n])[0]
        if len(vi):
            stride = max(1, len(vi) // k_samp)
            samp_idx.append(vi[::stride][:k_samp] + base)
    if samp_idx:
        si = np.concatenate(samp_idx)
        samp = [k[si] for k in ks]
        order = _host_lex_order(samp)
        m = len(order)
        qpos = (np.arange(1, n_shards) * m) // n_shards
        spl = [lane[order][qpos] for lane in samp]
        dest = _host_dest(ks, spl, n_shards)
    else:  # all padding: route everything to shard 0
        dest = np.zeros(n, np.int64)

    # --- bucket rows by destination; EXACT per-block capacity ---
    cols = [k for k in ks]
    for p in leaves:
        cols += _np_leaf_cols(p)
    C = len(cols)
    M = np.stack(cols, axis=1) if C else np.zeros((n, 0), np.uint32)
    all_valid = bool(v.all())
    bucket_dtype = dest.dtype if n_shards <= 255 else np.int32
    counts = np.zeros((n_shards, n_shards), np.int64)
    orders: list = []
    for s in range(n_shards):
        base = s * local_n
        dm = dest[base : base + local_n]
        if not all_valid:
            dm = np.where(v[base : base + local_n], dm, n_shards).astype(
                bucket_dtype
            )
        # narrow dtype: numpy's stable argsort is a radix pass per byte,
        # so bucketing on uint8 destinations is one pass, not eight
        orders.append(np.argsort(dm, kind="stable"))
        counts[s] = np.bincount(dm, minlength=n_shards + 1)[:n_shards]
    cap = int(max(1, counts.max()))
    blocks = np.zeros((n_shards, n_shards, cap, C), np.uint32)
    for s in range(n_shards):
        # one gather into destination order, then pure-slice block copies
        Ms = M[s * local_n : (s + 1) * local_n][orders[s]]
        pos = 0
        for d in range(n_shards):
            c = int(counts[s, d])
            if c:
                blocks[s, d, :c] = Ms[pos : pos + c]
            pos += c

    # --- ONE all_to_all over the mesh ---
    if n_shards > 1:
        dev = jax.device_put(
            blocks.reshape(n_shards * n_shards, cap, max(C, 1)), sharding
        )
        recv = np.asarray(_a2a_jitted(mesh, axis)(dev)).reshape(
            n_shards, n_shards, cap, max(C, 1)
        )
    else:
        recv = blocks

    # --- node-local radix sort per destination shard ---
    r_counts = counts.T  # [dst, src]
    out_rows = r_counts.sum(axis=1)
    L = int(out_rows.max())
    out_lanes = [np.full((n_shards, L), _SENTINEL, np.uint32)
                 for _ in range(n_lanes)]
    out_valid = np.zeros((n_shards, L), bool)
    out_pay = [np.zeros((n_shards, L), np.uint32) for _ in range(C - n_lanes)]
    for d in range(n_shards):
        segs = [recv[d, s, : r_counts[d, s]] for s in range(n_shards)
                if r_counts[d, s]]
        if not segs:
            continue
        Rm = np.concatenate(segs, axis=0)
        lanes_d = [np.ascontiguousarray(Rm[:, i]) for i in range(n_lanes)]
        R = len(Rm)
        o = _host_lex_order(lanes_d)
        for i in range(n_lanes):
            out_lanes[i][d, :R] = lanes_d[i][o]
        out_valid[d, :R] = True
        for j in range(C - n_lanes):
            out_pay[j][d, :R] = Rm[:, n_lanes + j][o]

    # --- back onto the mesh, shard-partitioned ---
    put = lambda a: jax.device_put(  # noqa: E731
        np.ascontiguousarray(a.reshape((n_shards * L,) + a.shape[2:])),
        sharding,
    )
    keys_out = tuple(put(ol) for ol in out_lanes)
    leaves_out: list = []
    ci = 0
    for shape, dtype in leaf_meta:
        nc = _leaf_n_cols(shape, dtype)
        flat = [out_pay[ci + j].reshape(-1) for j in range(nc)]
        ci += nc
        leaves_out.append(
            jax.device_put(_np_leaf_restore(flat, shape, dtype), sharding)
        )
    valid_out = put(out_valid)
    return keys_out, leaves_out, valid_out


# -- device engine (accelerator meshes; also the radix path) -----------------


def _device_sort(
    mesh, axis, n_shards, keys, payload_leaves, payload_def, valid,
    capacity_factor, splitters, sample_per_shard, on_overflow,
):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_lanes = len(keys)
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    keys = tuple(jax.device_put(k, sharding) for k in keys)
    payload_leaves = [jax.device_put(p, sharding) for p in payload_leaves]
    n_extras = len(payload_leaves)
    if valid is not None:
        valid = jax.device_put(valid, sharding)
    local_n = keys[0].shape[0] // n_shards
    leaf_meta = [(p.shape, p.dtype) for p in payload_leaves]
    # optimistic first-launch capacity: the uniform mean + fluctuation
    # slack. A layout that exceeds it is relaunched at the measured
    # per-block maximum (exact, psum-maxed in the failed attempt).
    cap0 = int(np.ceil(local_n / n_shards * max(capacity_factor, 1.0))) + 16
    cap0 = min(cap0, max(local_n, 1))
    k_samp = min(sample_per_shard, local_n)

    def run(cap: int):
        cache_key = (
            "sort", mesh, axis, n_lanes, n_extras, valid is not None,
            splitters, local_n, cap, k_samp,
            tuple((str(d), tuple(s[1:])) for s, d in leaf_meta),
        )
        jitted = _STEP_CACHE.get(cache_key)
        if jitted is None:
            jitted = jax.jit(_make_device_step(
                mesh, axis, n_shards, n_lanes, leaf_meta, valid is not None,
                splitters, local_n, cap, k_samp,
            ))
            _STEP_CACHE[cache_key] = jitted
        args = tuple(keys) + tuple(payload_leaves)
        if valid is not None:
            args = args + (valid,)
        return jitted(*args), cap

    out, cap = run(cap0)
    overflow = int(out[-2])
    if overflow and splitters == "sampled":
        # relaunch once at the exact measured block bound — adversarial
        # layouts cost one extra pass, never rows
        bmax = int(out[-1])
        cap_retry = min(_cap_bucket(max(bmax, cap0 + 1)), max(local_n, 1))
        if cap_retry > cap:
            try:
                from geomesa_tpu import metrics

                metrics.mesh_exchange_retries.inc()
            except Exception:  # pragma: no cover - metrics must not break
                pass
            out, cap = run(cap_retry)
            overflow = int(out[-2])
    keys_out = out[:n_lanes]
    payload_out = jax.tree.unflatten(
        payload_def, list(out[n_lanes : n_lanes + n_extras])
    )
    valid_out = out[n_lanes + n_extras]
    if overflow and on_overflow != "ignore":
        hint = (
            "Raise capacity_factor or use splitters='sampled'."
            if splitters == "radix"
            else "Raise capacity_factor."
        )
        msg = (
            f"distributed_sort dropped {overflow} rows: a destination shard "
            f"exceeded its exchange capacity ({cap}/pass). " + hint
        )
        if on_overflow == "raise":
            raise RuntimeError(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)
    return keys_out, payload_out, valid_out


def _make_device_step(
    mesh, axis, n_shards, n_lanes, leaf_meta, has_valid, splitters,
    local_n, cap, k_samp,
):
    """Build the single-launch exchange step: splitter plan + one packed
    all_to_all + the single-chip-layout local ``lax.sort``. Returns
    ``keys + leaves + (valid, overflow, block_max)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    spec = P(axis)
    n_extras = len(leaf_meta)

    def exchange(ks, leaf_arrs, v, dest, block_cap):
        """Bucket rows by dest and ship EVERYTHING — key lanes, the
        validity word and every payload leaf's u32 columns — in ONE
        stacked buffer through a single all_to_all. Invalid rows sort to
        the end of their bucket so they can never displace valid rows;
        valid rows past capacity are dropped and counted."""
        # clamp: an out-of-range dest would scatter out of bounds, and jax
        # drops OOB scatter updates SILENTLY -- rows would vanish without
        # being counted by the overflow accounting
        dest = jnp.clip(dest, 0, n_shards - 1)
        sort_key = dest * 2 + (~v).astype(jnp.int32)
        order = jnp.argsort(sort_key, stable=True)
        pay_cols: list = []
        for a in leaf_arrs:
            pay_cols += _jnp_leaf_cols(a)
        cols = [k for k in ks] + [v.astype(jnp.uint32)] + pay_cols
        cols = [c[order] for c in cols]
        d_s = dest[order]
        v_s = cols[n_lanes] != 0
        start = jnp.searchsorted(d_s, jnp.arange(n_shards), side="left")
        within = jnp.arange(v.shape[0]) - start[d_s]
        keep = (within < block_cap) & v_s
        dropped = (v_s & ~keep).sum()
        # non-kept rows scatter into a trash slot past the buffer
        flat_idx = jnp.where(
            keep, d_s * block_cap + within, n_shards * block_cap
        )
        slots = n_shards * block_cap + 1
        stacked = jnp.stack(cols, axis=1)
        fill_row = jnp.array(
            [_SENTINEL] * n_lanes + [0] * (1 + len(pay_cols)),
            dtype=jnp.uint32,
        )
        buf = jnp.broadcast_to(fill_row, (slots, stacked.shape[1]))
        buf = buf.at[flat_idx].set(stacked)
        buf = buf[:-1].reshape((n_shards, block_cap, stacked.shape[1]))
        got = jax.lax.all_to_all(buf, axis, 0, 0, tiled=False)
        got = got.reshape((-1, stacked.shape[1]))
        ks_r = [got[:, i] for i in range(n_lanes)]
        v_r = got[:, n_lanes] != 0
        leaf_r: list = []
        ci = n_lanes + 1
        for shape, dtype in leaf_meta:
            nc = _leaf_n_cols(shape, dtype)
            leaf_r.append(_jnp_leaf_restore(
                [got[:, ci + j] for j in range(nc)], shape, dtype
            ))
            ci += nc
        return ks_r, leaf_r, v_r, dropped

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec,) * (n_lanes + n_extras + has_valid),
        out_specs=((spec,) * (n_lanes + n_extras) + (spec, P(), P())),
        check_vma=False,
    )
    def step(*args):
        ks = list(args[:n_lanes])
        leaf_arrs = list(args[n_lanes : n_lanes + n_extras])
        if has_valid:
            v = args[-1]
        else:
            v = jnp.ones(ks[0].shape, dtype=bool)
        dropped_total = jnp.zeros((), jnp.int32)
        block_max = jnp.zeros((), jnp.int32)
        if n_shards == 1:
            pass  # nothing to exchange: straight to the local sort
        elif splitters == "sampled":
            # sample the local keys valid-first, all_gather, sort
            # globally, take n_shards-1 quantile splitters; route by
            # lexicographic lane comparison against them — ONE pass,
            # no rebalance (capacity is measured, not guessed)
            order = jnp.argsort(~v, stable=True)
            stride = max(1, local_n // k_samp) if k_samp else 1
            samp = [k[order][::stride][:k_samp] for k in ks]
            gathered = [
                jax.lax.all_gather(s, axis).reshape(-1) for s in samp
            ]
            gathered = jax.lax.sort(tuple(gathered), num_keys=n_lanes)
            m = gathered[0].shape[0]
            q = (jnp.arange(1, n_shards) * m) // n_shards
            sps = [g[q] for g in gathered]  # (n_shards-1,) per lane
            # lexicographic >, >= against every splitter
            gt = jnp.zeros((ks[0].shape[0], n_shards - 1), dtype=bool)
            eq = jnp.ones((ks[0].shape[0], n_shards - 1), dtype=bool)
            for lane, sp in zip(ks, sps):
                gt = gt | (eq & (lane[:, None] > sp[None, :]))
                eq = eq & (lane[:, None] == sp[None, :])
            # rows equal to splitter keys may land on ANY shard in the
            # tied range without breaking global order (equal keys are
            # order-free) -- spread them round-robin so duplicate-heavy
            # data cannot overload one destination
            d_lo = gt.sum(axis=1).astype(jnp.int32)
            d_hi = (gt | eq).sum(axis=1).astype(jnp.int32)
            span = d_hi - d_lo + 1
            dest = d_lo + (
                jnp.arange(ks[0].shape[0]).astype(jnp.int32) % span
            )
            # exact per-destination counts (this shard's outgoing block
            # sizes); the mesh max sizes the retry capacity
            hist = jnp.sum(
                (dest[:, None] == jnp.arange(n_shards)[None, :]) & v[:, None],
                axis=0, dtype=jnp.int32,
            )
            block_max = jax.lax.pmax(jnp.max(hist), axis)
            ks, leaf_arrs, v, d1 = exchange(ks, leaf_arrs, v, dest, cap)
            dropped_total += d1.astype(jnp.int32)
        else:
            # radix: scale lane 0's top 16 bits onto [0, n_shards) --
            # for pow2 n this reduces to the plain high-bit shift, and it
            # works for any n. Lane 0 is assumed to carry 31 significant
            # bits (a z3 hi lane); a lane with bit 31 set would compute
            # dest == n_shards, which the exchange clamps to the last
            # shard (skewed routing, but no row loss).
            top16 = (ks[0] >> 15).astype(jnp.uint32)
            dest = ((top16 * jnp.uint32(n_shards)) >> 16).astype(jnp.int32)
            ks, leaf_arrs, v, d1 = exchange(ks, leaf_arrs, v, dest, cap)
            dropped_total += d1.astype(jnp.int32)
        # local sort by key lanes; invalid rows are forced to the sentinel
        # key in every lane so they sort last within the shard — the SAME
        # lax.sort lane layout (uint32 lanes + validity + permutation) the
        # single-chip build's sorted staging uses
        ks = [jnp.where(v, k, jnp.uint32(_SENTINEL)) for k in ks]
        perm = jnp.arange(ks[0].shape[0], dtype=jnp.int32)
        sorted_ops = jax.lax.sort(
            tuple(ks) + (v, perm), num_keys=n_lanes
        )
        ks = list(sorted_ops[:n_lanes])
        v, perm = sorted_ops[n_lanes], sorted_ops[n_lanes + 1]
        leaf_arrs = [e[perm] for e in leaf_arrs]
        overflow = jax.lax.psum(dropped_total, axis)
        return tuple(ks) + tuple(leaf_arrs) + (v, overflow, block_max)

    return step


def distributed_z3_sort(
    mesh,
    hi,
    lo,
    axis: str = "shard",
    capacity_factor: "float | None" = None,
    splitters: str = "sampled",
    sample_per_shard: int = 64,
    payload=None,
    on_overflow: str = "raise",
    engine: "str | None" = None,
):
    """Exchange-sort of (hi, lo) uint32 z-key pairs across the mesh.

    Returns ``(hi, lo, valid)`` -- or ``(hi, lo, payload, valid)`` when a
    payload pytree rides along -- where shard s holds the s-th globally-
    sorted key range, locally sorted; ``valid`` masks padding introduced by
    the fixed-capacity exchange. See :func:`distributed_sort` for splitter
    strategies, engines and overflow semantics.
    """
    (sh, sl), pay, valid = distributed_sort(
        mesh,
        (hi, lo),
        axis=axis,
        capacity_factor=capacity_factor,
        splitters=splitters,
        sample_per_shard=sample_per_shard,
        payload=payload,
        on_overflow=on_overflow,
        engine=engine,
    )
    if payload is None:
        return sh, sl, valid
    return sh, sl, pay, valid


def sharded_zscan_count(
    mesh, bins, z_hi, z_lo, bounds, bin_ids, axis: str = "shard"
):
    """Mesh-wide key-only scan (the Z3Iterator analog at pod scale): each
    shard masked-compares its resident key planes, psum merges. 8 bytes
    of key per row per chip, no attribute reads — the distributed form
    of ops/zscan."""
    import jax.numpy as jnp

    from geomesa_tpu.ops import zscan

    bounds = jnp.asarray(bounds)
    bin_ids = jnp.asarray(bin_ids)

    def mask_fn(local):
        return zscan.z3_zscan_mask(
            local["__zhi"], local["__zlo"], local["__zbin"], bounds, bin_ids
        )

    return sharded_count_scan(
        mesh,
        mask_fn,
        {"__zbin": bins, "__zhi": z_hi, "__zlo": z_lo},
        axis=axis,
    )


def sharded_query_scan(
    mesh,
    device_fn,
    cols: dict,
    rids,
    cap_per_shard: "int | None" = None,
    payload: "dict | None" = None,
    axis: str = "shard",
    on_overflow: str = "raise",
):
    """Mesh-wide FEATURE-RETURNING scan — the distributed analog of
    ``DeviceIndex.query()`` and of the reference's ``BatchScanPlan``
    streaming features back from every tablet (SURVEY section 3.1), not a
    psum count: each shard fuses the filter mask over its resident column
    slice, compacts the matching row ids (and optional payload planes)
    into a fixed-capacity buffer on device, and the shard-partitioned
    buffers concatenate into the result stream.

    ``cols`` are 1-D device planes (sharded over ``axis``); ``rids`` is
    the row-id plane riding alongside; ``payload`` maps names to extra
    planes gathered for the matching rows (the "columns of the streamed
    features"). ``cap_per_shard`` bounds output size (default: the full
    local slice, i.e. lossless); rows past the cap are counted and
    surfaced per ``on_overflow`` ('raise' | 'warn' | 'ignore').

    Returns ``(ids, valid, payload_out, total_hits)`` where ids is
    ``(n_shards * cap,)``, ``valid`` marks real entries, ``payload_out``
    mirrors ``payload`` row-for-row with ids, and ``total_hits`` is the
    exact mesh-wide match count (> valid.sum() iff truncated).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if on_overflow not in ("raise", "warn", "ignore"):
        raise ValueError(f"unknown on_overflow mode {on_overflow!r}")
    n_shards = mesh.shape[axis]
    spec = P(axis)
    sharding = NamedSharding(mesh, spec)
    names = sorted(cols)
    planes = [jax.device_put(cols[k], sharding) for k in names]
    rids = jax.device_put(rids, sharding)
    pay_names = sorted(payload) if payload else []
    pay_planes = [jax.device_put(payload[k], sharding) for k in pay_names]
    local_n = rids.shape[0] // n_shards
    cap = local_n if cap_per_shard is None else min(cap_per_shard, local_n)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec,) * (1 + len(planes) + len(pay_planes)),
        out_specs=(spec, spec) + (spec,) * len(pay_planes) + (P(),),
        check_vma=False,
    )
    def step(rid_l, *arrs):
        local = dict(zip(names, arrs[: len(names)]))
        pays = arrs[len(names):]
        mask = device_fn(local)
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        keep = mask & (pos < cap)
        idx = jnp.where(keep, pos, cap)  # slot `cap` is the trash slot

        def compact(plane):
            buf = jnp.zeros((cap + 1,), plane.dtype).at[idx].set(plane)
            return buf[:cap]

        hits_local = jnp.sum(mask, dtype=jnp.int32)
        out_valid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(
            hits_local, cap
        )
        total = jax.lax.psum(hits_local, axis)
        return (
            (compact(rid_l), out_valid)
            + tuple(compact(p) for p in pays)
            + (total,)
        )

    out = jax.jit(step)(rids, *planes, *pay_planes)
    ids, valid = out[0], out[1]
    pay_out = dict(zip(pay_names, out[2:-1]))
    total_hits = out[-1]
    if on_overflow != "ignore":
        th, got = int(total_hits), int(valid.sum())
        if th > got:
            msg = (
                f"sharded_query_scan truncated {th - got} of {th} matches "
                f"(cap_per_shard={cap}); raise cap_per_shard"
            )
            if on_overflow == "raise":
                raise RuntimeError(msg)
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
    return ids, valid, pay_out, total_hits


def sharded_build_and_query_step(mesh, sfc, x, y, t, query_bounds, axis: str = "shard"):
    """One full distributed 'index build + query' step, end to end on the
    mesh: z3 hi/lo key encode (data-parallel) -> all_to_all splitter
    exchange + local sort, row ids riding as payload (index build) ->
    key-only zscan mask over the SORTED key lanes + row-id compaction +
    gather (query THROUGH the built index, so key corruption in the
    exchange is caught), plus the exact
    pre-sort coordinate count as an independent cross-check.

    Returns (sorted_hi, sorted_lo, valid, exact_count, key_count,
    hit_rids, hit_valid). This is the step
    ``__graft_entry__.dryrun_multichip`` compiles over N virtual devices.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from geomesa_tpu.ops import zscan

    spec = P(axis)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, spec))
    x, y, t = put(x), put(y), put(t)
    xmin, ymin, xmax, ymax, tmin, tmax = query_bounds

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, P()),
        check_vma=False,
    )
    def encode_and_count(xl, yl, tl):
        hi, lo = sfc.index_jax_hi_lo(xl, yl, tl)
        mask = (
            (xl >= xmin)
            & (xl <= xmax)
            & (yl >= ymin)
            & (yl <= ymax)
            & (tl >= tmin)
            & (tl <= tmax)
        )
        count = jax.lax.psum(mask.sum(), axis)
        return hi, lo, count

    hi, lo, exact_count = jax.jit(encode_and_count)(x, y, t)
    rid = jnp.arange(hi.shape[0], dtype=jnp.uint32)
    (sh, sl), pay, sv = distributed_sort(
        mesh, (hi, lo), axis=axis, payload={"rid": rid}, on_overflow="raise"
    )
    # query THROUGH the index: cell-granular key compare on the sorted
    # lanes (the Z3Iterator semantics; t is an offset within one period
    # here, so a single unbinned bounds entry covers the window)
    qb = zscan.z3_dim_bounds(
        (int(sfc.lon.normalize(xmin)), int(sfc.lat.normalize(ymin)),
         int(sfc.time.normalize(tmin))),
        (int(sfc.lon.normalize(xmax)), int(sfc.lat.normalize(ymax)),
         int(sfc.time.normalize(tmax))),
    )
    qb_dev = jnp.asarray(qb)

    def key_mask(local):
        m = zscan._dims_mask(local["hi"], local["lo"], qb_dev, 3)
        return m & local["valid"]

    hit_rids, hit_valid, _, key_count = sharded_query_scan(
        mesh,
        key_mask,
        {"hi": sh, "lo": sl, "valid": sv},
        pay["rid"],
        axis=axis,
        on_overflow="ignore",  # cap == local slice: lossless by design
    )
    return sh, sl, sv, exact_count, key_count, hit_rids, hit_valid
