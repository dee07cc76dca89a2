"""Index build: key compute -> global sort -> partition manifest.

The rebuild's analog of bulk ingest + table splits (ref: geomesa-accumulo
bulk ingest MapReduce sort + AccumuloIndexAdapter table splits, SURVEY.md
section 2.6 "Z-order bulk sort"). Host path uses numpy lexsort; the device
path (:func:`build_index_device`) encodes z keys on the mesh and globally
sorts rows with the all_to_all splitter exchange, row ids riding the
exchange as payload -- the MapReduce-bulk-sort-on-ICI analog, producing
the same BuiltIndex the host path does.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.index.api import BuiltIndex, PartitionMeta

DEFAULT_PARTITION_SIZE = 1 << 20  # ~1M rows per partition

# key spaces build_index_device can marshal encode inputs for — the ONE
# dispatch list (keyspaces with a device encode still need an entry in the
# per-kind input marshaling below); callers gate mesh routing on this
DEVICE_BUILD_KINDS = ("z3", "z2", "xz3", "xz2")

# time bins (weeks/months/... since epoch) can be negative; bias them into
# non-negative uint32 lane values so the lexicographic uint32 device sort
# matches the host's signed-int sort. Full int32 bias: a smaller bias would
# wrap far-past bins around to huge lane values and silently mis-sort.
_BIN_BIAS = 1 << 31

# per-curve device-encode jit wrappers (sfc dataclasses are frozen and
# hashable); see the cache note at the use site
_ENCODE_JITS: dict = {}


def build_index(
    keyspace,
    batch: FeatureBatch,
    partition_size: int = DEFAULT_PARTITION_SIZE,
    mesh=None,
) -> BuiltIndex:
    if mesh is not None:
        return build_index_device(keyspace, batch, mesh, partition_size)
    keys = keyspace.index_keys(batch)
    cols = [keys[c] for c in keyspace.key_columns]
    order = _sort_order(cols)
    sorted_batch = batch.take(order)
    sorted_keys = {k: v[order] for k, v in keys.items()}
    partitions = make_partitions(keyspace, sorted_batch, sorted_keys, partition_size)
    return BuiltIndex(keyspace, sorted_batch, sorted_keys, partitions)


def build_index_device(
    keyspace,
    batch: FeatureBatch,
    mesh,
    partition_size: int = DEFAULT_PARTITION_SIZE,
    axis: str = "shard",
) -> BuiltIndex:
    """Mesh-path index build for the spatial key spaces (z3/z2/xz3/xz2).

    The keys are encoded on device (hi/lo uint32 lanes; point schemas get
    Morton z keys, non-point schemas the XZ extent codes of their geometry
    envelopes), and rows are globally sorted across the mesh by
    ([bin,] key_hi, key_lo, row_id) via the all_to_all splitter exchange
    -- the trailing row-id lane makes the device sort stable over
    duplicate keys, so ties order exactly like the host's stable lexsort
    and the resulting permutation materializes the same sorted batch +
    partition manifest bit for bit. Overflow in the exchange raises (a
    build must never silently lose rows).
    """
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.jaxconf import enable_compilation_cache, require_x64
    from geomesa_tpu.parallel.dist import distributed_sort

    enable_compilation_cache()  # the exchange/encode compiles are heavy

    # host-parity encode needs float64 quantization; without it the jnp
    # coords silently downcast to float32 and the device keys disagree
    # with the host planner's ranges
    require_x64()

    kind = keyspace.name
    sfc = getattr(keyspace, "sfc", None)
    if sfc is None or not hasattr(sfc, "index_jax_hi_lo"):
        raise ValueError(
            f"device build requires a key space with a hi/lo device encode; "
            f"{kind!r} has none (use the host build)"
        )
    if kind not in DEVICE_BUILD_KINDS:
        # the encode dispatch below is positional per kind; a custom key
        # space with a device encode still needs a dispatch entry here
        raise ValueError(
            f"device build has no input dispatch for key space {kind!r} "
            "(supported: z3/z2/xz3/xz2)"
        )
    n = len(batch)
    if n == 0:
        return build_index(keyspace, batch, partition_size)

    n_shards = mesh.shape[axis]
    binned = kind in ("z3", "xz3")
    # one shared kind-dispatch for encode-input marshaling (same table the
    # resident cache stages with, so build and staging cannot drift)
    from geomesa_tpu.index.keyplanes import encode_inputs

    coords, b = encode_inputs(
        batch, kind, sfc, keyspace.geom_field,
        getattr(keyspace, "dtg_field", None),
    )
    if binned and (
        int(b.min()) < -_BIN_BIAS or int(b.max()) >= _BIN_BIAS - 1
    ):
        raise ValueError(
            f"time bins [{b.min()}, {b.max()}] exceed the "
            "device-sortable int32 range"
        )

    # pad to a POWER-OF-TWO row bucket (then to a shard multiple): the
    # encode + exchange jits retrace per input shape, and a compile per
    # distinct flush size would dominate every flush.
    # Bucketing bounds the shape set; the valid mask hides the padding.
    cap = 1 << max(n - 1, 0).bit_length()
    cap += (-cap) % n_shards
    pad = cap - n
    if pad:
        coords = [np.concatenate([c, np.zeros(pad)]) for c in coords]
        if binned:
            b = np.concatenate([b, np.zeros(pad, dtype=b.dtype)])
    valid = np.arange(n + pad) < n
    rid = np.arange(n + pad, dtype=np.uint32)

    enc = _ENCODE_JITS.get(sfc)
    if enc is None:
        # cached wrapper: a fresh jax.jit per build would re-compile the
        # encode every flush (the jit cache lives on the wrapper)
        enc = jax.jit(sfc.index_jax_hi_lo)
        _ENCODE_JITS[sfc] = enc
    hi, lo = enc(*map(jnp.asarray, coords))

    lanes = (hi, lo, jnp.asarray(rid))
    if binned:
        lanes = (jnp.asarray((b + _BIN_BIAS).astype(np.uint32)),) + lanes
    sorted_lanes, _, v = distributed_sort(
        mesh, lanes, axis=axis, valid=jnp.asarray(valid), on_overflow="raise"
    )
    v = np.asarray(v)
    kr = sorted_lanes[-1]
    kh, kl = np.asarray(sorted_lanes[-3]), np.asarray(sorted_lanes[-2])
    order = np.asarray(kr)[v].astype(np.int64)
    if order.shape[0] != n:  # pragma: no cover - overflow already raises
        raise RuntimeError(
            f"device build lost rows: {order.shape[0]} of {n} survived"
        )
    sorted_batch = batch.take(order)
    key64 = (kh.astype(np.uint64) << np.uint64(32)) | kl.astype(np.uint64)
    key_name = "z" if kind in ("z3", "z2") else "xz"
    sorted_keys = {
        key_name: key64[v]
        if kind in ("z3", "z2")
        else key64[v].astype(np.int64)  # xz codes are int64 on the host
    }
    if binned:
        kb = np.asarray(sorted_lanes[0])
        sorted_keys["bin"] = (kb[v].astype(np.int64) - _BIN_BIAS).astype(
            np.int32
        )
    partitions = make_partitions(
        keyspace, sorted_batch, sorted_keys, partition_size
    )
    return BuiltIndex(keyspace, sorted_batch, sorted_keys, partitions)


def _sort_order(cols: list) -> np.ndarray:
    from geomesa_tpu import native

    if native.enabled():
        # byte-wise LSD radix argsort (native/sort.cpp): linear instead
        # of comparison sort, ~5x lexsort on the z3 (bin, hi, lo) lanes
        order = native.radix_argsort(cols)
        if order is not None:
            return order
    if len(cols) == 1:
        return np.argsort(cols[0], kind="stable")
    # np.lexsort: last key is primary -> reverse
    return np.lexsort(tuple(reversed(cols)))


def make_partitions(
    keyspace,
    sorted_batch: FeatureBatch,
    sorted_keys: dict,
    partition_size: int,
) -> "list[PartitionMeta]":
    n = len(sorted_batch)
    sft = sorted_batch.sft
    geom = sft.geom_field
    dtg = sft.dtg_field
    key_cols = [sorted_keys[c] for c in keyspace.key_columns]
    starts = np.arange(0, max(n, 1), partition_size)
    starts = starts[starts < max(n, 1)]
    # per-partition reductions via reduceat: one pass per statistic over
    # the whole column instead of materializing an (n, 4) bbox array (a
    # full extra copy of the coordinate data) and slicing it per partition
    bb_mins = bb_maxs = None
    if geom is not None and n:
        col = sorted_batch.columns[geom]
        if col.dtype != object:
            x = np.ascontiguousarray(col[:, 0])
            y = np.ascontiguousarray(col[:, 1])
            bb_mins = (
                np.minimum.reduceat(x, starts), np.minimum.reduceat(y, starts)
            )
            bb_maxs = (
                np.maximum.reduceat(x, starts), np.maximum.reduceat(y, starts)
            )
        else:
            bb = sorted_batch.bboxes(geom)
            bb_mins = (
                np.minimum.reduceat(bb[:, 0], starts),
                np.minimum.reduceat(bb[:, 1], starts),
            )
            bb_maxs = (
                np.maximum.reduceat(bb[:, 2], starts),
                np.maximum.reduceat(bb[:, 3], starts),
            )
    t_mins = t_maxs = None
    if dtg is not None and n:
        d_all = sorted_batch.column(dtg)
        t_mins = np.minimum.reduceat(d_all, starts)
        t_maxs = np.maximum.reduceat(d_all, starts)
    partitions = []
    for pid, start in enumerate(starts.tolist() if n else [0]):
        stop = min(start + partition_size, n)
        if stop <= start:
            break
        key_lo = tuple(_item(c[start]) for c in key_cols)
        key_hi = tuple(_item(c[stop - 1]) for c in key_cols)
        bbox = None
        if bb_mins is not None:
            bbox = (
                float(bb_mins[0][pid]), float(bb_mins[1][pid]),
                float(bb_maxs[0][pid]), float(bb_maxs[1][pid]),
            )
        time_range = None
        if t_mins is not None:
            time_range = (int(t_mins[pid]), int(t_maxs[pid]))
        partitions.append(
            PartitionMeta(pid, start, stop, key_lo, key_hi, stop - start, bbox, time_range)
        )
    return partitions


def _item(v):
    """numpy scalar -> python scalar for tuple comparisons; uint64 z values
    stay exact via int()."""
    if isinstance(v, np.generic):
        return v.item()
    return v
