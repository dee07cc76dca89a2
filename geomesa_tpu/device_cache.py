"""Device-resident index: pin a type's scan columns in accelerator memory
and serve repeated queries at memory bandwidth.

Ref role: the tablet-server block cache + the rebuild plan's "device
partition refresh" (SURVEY.md section 7.9) [UNVERIFIED - empty reference
mount]. The reference keeps hot tablets in tablet-server RAM; here the hot
partitions' columnar scan planes (float32 coords, int32/uint32 hi/lo
planes) live in HBM, so a query is one fused kernel launch with no
host->device transfer. The durable store stays the source of truth; the
resident copy is a cache refreshed after writes (or driven by a live
layer's listener).
"""

from __future__ import annotations

import contextlib
from functools import partial, wraps

import numpy as np

from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import ast
from geomesa_tpu.ops.scan import stage_columns
from geomesa_tpu.query.plan import internal_query


def _stageable_planes(sft: SimpleFeatureType) -> list:
    """Device column names for every attribute the scan kernels can read."""
    planes: list = []
    for a in sft.attributes:
        if a.is_geometry:
            if a.is_point:
                planes += [f"{a.name}__x", f"{a.name}__y"]
            else:
                # non-point geometries: envelope planes (device bbox +
                # envelope prefilters for exact residual predicates)
                planes += [f"{a.name}__x0", f"{a.name}__y0",
                           f"{a.name}__x1", f"{a.name}__y1"]
            continue
        dtype = a.column_dtype
        if dtype == np.int64:
            planes += [f"{a.name}__hi", f"{a.name}__lo"]
        elif dtype in (np.float32, np.float64, np.int32):
            planes.append(a.name)
    return planes


# reserved names for the index-key planes (leading underscore cannot clash
# with attribute planes, which are always "<attr>" or "<attr>__suffix")
Z_BIN, Z_HI, Z_LO = "__zbin", "__zhi", "__zlo"
# de-interleaved z3 key planes (dim-plane layout, ops/zscan.py rationale):
# quantized nx/ny plus ONE packed (bin - base) << 21 | nt word — the same
# 12B/row as (bin, hi, lo) but ~12 VPU ops/row to test instead of ~46
Z_NX, Z_NY, Z_BT = "__znx", "__zny", "__zbt"
# reserved name for the visibility label-id plane (per-auth resident
# serving: each row carries the id of its label expression in a small
# vocabulary; a per-request auth table gathers to a bool mask on device)
VIS_ID = "__visid"
# CQL strings whose parsed filter a DeviceIndex remembers (``_keyed``)
_TEXTS_MAX = 4096


class _VisOverflow(Exception):
    """Label vocabulary exceeded VIS_VOCAB_MAX: per-auth residency is
    disabled and labeled rows fall back to the store path."""


class _BtRebase(Exception):
    """A delta batch's period bins fall outside the packable dim-plane
    window relative to the staged ``bin_base``: the bt plane must be
    repacked around a new base (full restage). Marking the rows with the
    sentinel instead would silently violate the loose-superset contract."""


def _thin_transfer(c):
    """float64 coord array -> the cheapest LOSSLESS device transfer.

    encode_inputs upcasts coords to float64 for the exact host oracle,
    but most geometry columns store float32 — in that case every value
    round-trips f64->f32->f64 exactly, and shipping the f32 halves the
    staging transfer (the encode upcasts back to f64 on device under the
    scoped-x64 jit, bit-identically). The O(n) host check costs far less
    than the bytes it saves; any value that would not round-trip keeps
    the f64 transfer. Arrays already on device pass through untouched."""
    if not isinstance(c, np.ndarray):
        return c  # jax array: already device-resident, nothing to thin
    if c.dtype != np.float64:
        return c
    f32 = c.astype(np.float32)
    if np.array_equal(f32.astype(np.float64), c):
        return f32
    return c


# split-jit cache for _stage_packed, keyed by (n_rows_in_matrix, dtypes):
# a fresh jax.jit per staging call would recompile the (cheap) split on
# every refresh
_SPLIT_JITS: dict = {}


def _stage_packed(host_cols: dict) -> dict:
    """Upload a dict of host planes in as FEW device transfers as
    possible: every 1-D 4-byte plane rides ONE packed (k, n) uint32
    matrix (a single H2D transfer + one split dispatch that bitcasts the
    rows back to their dtypes); other dtypes transfer individually.

    Each transfer pays a fixed round-trip latency and small transfers
    never reach peak bandwidth, so one packed transfer beats one per
    plane. Identical array OBJECTS (e.g. encode
    inputs aliasing an attribute plane) are uploaded once and fanned out.
    """
    import jax
    import jax.numpy as jnp

    four = {
        k: v
        for k, v in host_cols.items()
        if isinstance(v, np.ndarray) and v.ndim == 1 and v.dtype.itemsize == 4
    }
    out = {
        k: (v if isinstance(v, jax.Array) else jnp.asarray(v))
        for k, v in host_cols.items()
        if k not in four
    }
    if not four:
        return out
    names = sorted(four)
    # dedupe by object identity: aliased planes share one matrix row
    row_of: dict = {}
    uniq: list = []
    for k in names:
        key = id(four[k])
        if key not in row_of:
            row_of[key] = len(uniq)
            uniq.append(four[k])
    n = uniq[0].shape[0]
    mat = np.empty((len(uniq), n), np.uint32)
    for i, v in enumerate(uniq):
        mat[i] = v.view(np.uint32)
    dts = tuple(str(v.dtype) for v in uniq)
    split = _SPLIT_JITS.get(dts)
    if split is None:

        def _split(m, _dts=dts):
            return [
                jax.lax.bitcast_convert_type(m[i], np.dtype(d))
                for i, d in enumerate(_dts)
            ]

        split = jax.jit(_split)
        _SPLIT_JITS[dts] = split
    parts = split(jnp.asarray(mat))
    for k in names:
        out[k] = parts[row_of[id(four[k])]]
    return out


from geomesa_tpu.curves.zorder import u64_hi_lo as _split_u64


from geomesa_tpu.index.keyplanes import (
    encode_inputs as _encode_inputs_shared,
    schema_kind as _z_schema_kind,
)


def _encode_inputs(batch, sft: SimpleFeatureType, kind, sfc):
    return _encode_inputs_shared(batch, kind, sfc, sft.geom_field,
                                 sft.dtg_field)


def _staging_query():
    """The resident-cache staging scan: every row, visibility labels kept
    raw (the cache enforces per-request auths itself via the label-id
    plane) -- never expose this to user-facing queries."""
    from geomesa_tpu.query.plan import Query

    return Query(
        filter=ast.Include, hints={"internal": True, "raw_visibility": True}
    )


def _fetch(conv, x):
    """``conv(x)``: the blocking host copy of a dispatched device result,
    timed as the ``device.fetch`` stage (dispatch return to host value)."""
    from geomesa_tpu.tracing import span

    with span("device.fetch"):
        return conv(x)


def _ready(value):
    """The fetch of a count already on the host (``_launch_count``)."""
    return lambda: value


def _z_planes_np(batch, sft: SimpleFeatureType):
    """(kind, planes, bins) via the HOST encode — the oracle the device
    staging path must match, and the fallback when the device encode is
    unavailable."""
    kind, sfc = _z_schema_kind(sft)
    if kind is None:
        return None, {}, None
    coords, bins = _encode_inputs(batch, sft, kind, sfc)
    hi, lo = _split_u64(np.asarray(sfc.index(*coords)))
    planes = {Z_HI: hi, Z_LO: lo}
    if bins is not None:
        planes[Z_BIN] = bins.astype(np.int32)
    return kind, planes, bins


def _scan_scoped(fn):
    """Ambient ``cache.scan`` compile attribution for the resident scan
    entry points: a per-filter kernel a count/mask dispatch compiles is
    claimed by this family unless a narrower scope (fused.*, knn,
    join.*) already holds -- the serving-path recompile tripwire
    (analysis/compilecheck.py) requires every live compile to carry a
    blessed family."""

    @wraps(fn)
    def wrapped(*args, **kwargs):
        from geomesa_tpu import ledger

        with ledger.compile_scope("cache.scan"):
            return fn(*args, **kwargs)

    return wrapped


def _snapshot(fn):
    """One resident read under the index's snapshot: ``self._lock``
    (appends, evictions and restages swap or donate the planes under
    it), then the index's ``_read_scope`` hook inside it. Every public
    read of :class:`DeviceIndex` enters here once: ``count`` through
    ``_launch_count``, so its fetch waits outside the lock; the others
    for their whole call. A read nested in another (``query`` ->
    ``mask``) re-enters the re-entrant lock."""
    op = fn.__name__.lstrip("_")

    @wraps(fn)
    def read(self, *args, **kwargs):
        with self._lock, self._read_scope(op):
            return fn(self, *args, **kwargs)

    return read


# donated padded-buffer slot-writes, keyed by (delta rows, plane names)
_SLOT_WRITES: dict = {}


def _slot_write(pad: int, names: tuple):
    """The donated jit ``(cols, valid, delta, n, m) -> (cols, valid)``:
    writes a ``pad``-row delta into the fixed-capacity planes at row
    ``n`` and marks its first ``m`` rows live in the validity plane."""
    key = (pad, names)
    write = _SLOT_WRITES.get(key)
    if write is None:
        import jax
        import jax.numpy as jnp

        def _write(cols, valid, delta, n, m):
            out = {
                k: jax.lax.dynamic_update_slice_in_dim(
                    buf, delta[k].astype(buf.dtype), n, 0
                )
                for k, buf in cols.items()
            }
            live = jnp.arange(pad) < m
            return out, jax.lax.dynamic_update_slice_in_dim(valid, live, n, 0)

        write = _SLOT_WRITES[key] = jax.jit(_write, donate_argnums=(0, 1))
    return write


def _pad_rows(cols: dict, rows: int) -> dict:
    """Every plane zero-padded at its tail to ``rows`` rows."""
    import jax.numpy as jnp

    return {
        k: jnp.concatenate([v, jnp.zeros(rows - v.shape[0], v.dtype)])
        if rows > v.shape[0]
        else v
        for k, v in cols.items()
    }


class DeviceIndex:
    """Resident scan cache over one store type.

    >>> di = DeviceIndex(store, "gdelt")
    >>> di.count("BBOX(geom, -10, 35, 30, 60) AND dtg DURING ...")
    >>> batch = di.query(...)        # mask on device, take on host
    >>> store.write(...); store.flush(...); di.refresh()

    With ``z_planes=True`` the index-key planes (Z3 bin + z hi/lo, or Z2
    for date-less point schemas; XZ3/XZ2 extent-curve keys for non-point
    schemas) stay resident too, and bbox(+during)
    queries can be answered straight from the key at cell granularity —
    the reference's loose-bbox mode (``geomesa.loose.bbox``): a superset
    of the exact answer, one masked compare per row, 8-12B/row instead
    of reading the coordinate planes. Opt in per call (``loose=True``)
    or globally (``query.loose.bbox`` system property).

    Visibility (per-auth resident serving, ref Accumulo cell
    visibility): staging keeps EVERY row plus a compact label-id plane
    (the distinct label expressions form a small vocabulary, capped at
    ``VIS_VOCAB_MAX``). Each request's auths evaluate the vocabulary
    once host-side into a bool table; the device scan gathers it by
    label id and ANDs it into the hit mask, so secured features serve
    from the fast path under the correct auths. No auths (the default)
    means labeled rows are hidden — fail closed, the store semantics.
    If the vocabulary overflows the cap, labeled rows are dropped from
    the resident copy (served by the store path only) with a warning.

    Read discipline, declared once here for every subclass: each public
    read enters ``_snapshot``, which holds ``self._lock`` -- the base
    index never mutates its planes in place, so its lock is a no-op;
    the streaming and mesh indexes set a checked re-entrant lock that
    their mutations take too -- and the ``_read_scope`` hook inside it.
    ``count`` holds the snapshot for its launch alone: the fetch reads
    only the launched output, which a later donation cannot touch.

    Padded buffers (both subclasses): fixed-capacity planes, a device
    validity plane (``_valid``) masking the slots past the live rows,
    one donated slot-write per delta (``_write_delta``), and a host
    mirror kept as parts and concatenated at its next read
    (``_host_rows``).
    """

    #: the snapshot every public read holds (``_snapshot``): reusable,
    #: re-entrant, and a no-op for an index that never mutates in place
    _lock = contextlib.nullcontext()

    #: distinct visibility expressions the resident cache will track
    VIS_VOCAB_MAX = 4096

    #: smallest padded-buffer delta (rows); tiny batches pad up to this
    MIN_DELTA_ROWS = 256

    #: exact filters and density grids may run on Pallas kernels: a
    #: single device only, since a Mosaic call is not SPMD-partitionable
    _pallas_tiles = True

    #: 64-window groups chained per window_pairs_query dispatch (the
    #: scan's K-chaining trick applied to the join coarse pass); at 8
    #: the bit-plane output of one dispatch is G x 8B/row
    PAIRS_GROUPS_PER_DISPATCH = 8

    def __init__(
        self,
        store,
        type_name: str,
        columns: "list[str] | None" = None,
        z_planes: bool = False,
        dim_planes: "bool | None" = None,
    ):
        from geomesa_tpu.jaxconf import enable_compilation_cache

        enable_compilation_cache()  # resident serving is compile-heavy
        self.store = store
        self.type_name = type_name
        self.sft = store.get_schema(type_name)
        self._planes = columns or _stageable_planes(self.sft)
        self._want_z = z_planes
        self._z_kind = None
        self._bin_range = None  # (min, max) period bins present
        # dim-plane layout preference: None = auto (z3 schemas whose bin
        # span fits the packable window), False = force masked-compare
        # (the cross-check engine), True = require (raises if unusable)
        self._dim_pref = dim_planes
        self._dim_mode = False
        self._bt_base = None  # bin_base the bt plane is packed around
        self._dim_kernels: dict = {}  # R bucket -> (count_fn, mask_fn)
        self._host_batch = None  # the mirror's concat (None: stale)
        self._host_parts: list = []  # the mirror's staged batches
        self._n = 0  # staged rows (mirror length; may exceed live rows)
        self._valid = None  # device validity plane; None = all live
        self._cols = None
        self._compiled: dict = {}
        self._texts: dict = {}  # CQL text -> (filter, _compiled key)
        self._z_jit = None
        self._z_encode_jit = None
        self._dim_encode_jit = None
        self._z_encode_failed = False
        self._loose_cache: dict = {}  # (repr(f), bin_range) -> bounds
        self._fused_jits: dict = {}  # fusion-shape key -> jitted launch
        self._vis_vocab: "dict | None" = None  # label expr -> id
        self._vis_disabled = False  # vocabulary overflowed: public-only
        self._auth_tables: dict = {}  # sorted-auths tuple -> device table
        self._visid_np = None  # host mirror of the VIS_ID plane
        self._bin_jits: dict = {}  # (shape, cap) -> jitted BIN pack
        self._bin_lanes: dict = {}  # lane-matrix cache (latest staging)
        self.refresh()

    def _stage_batch(self, batch) -> dict:
        """Attribute planes + (optionally) index-key planes for a batch.
        Widens the observed bin range; callers doing a full restage reset
        ``_bin_range`` to None first.

        Transfer discipline: every 4-byte plane — attribute planes AND
        the key-encode inputs — is packed into ONE uint32 matrix and
        uploaded in a single H2D transfer (_stage_packed); encode inputs
        that equal an attribute plane bit-for-bit (point coords) share
        its matrix row. Per-plane uploads each pay a transfer's latency
        and never reach peak bandwidth."""
        import jax.numpy as jnp

        from geomesa_tpu.ops.scan import stage_columns_host

        # staged-generation token: any staging (full restage, streaming
        # delta, sharded delta) invalidates layouts derived from the
        # resident rows (the join engine's cached JoinIndex keys off it)
        self._gen = getattr(self, "_gen", 0) + 1
        host = stage_columns_host(batch, self._planes)
        pack = dict(host)
        enc_pre = None
        if self._want_z and len(batch) and not self._z_encode_failed:
            kind, sfc = _z_schema_kind(self.sft)
            if kind is not None:
                coords_host, ebins = _encode_inputs(
                    batch, self.sft, kind, sfc
                )
                geom = self.sft.geom_field
                thin = [_thin_transfer(c) for c in coords_host]
                for i, tc in enumerate(thin):
                    if tc.dtype.itemsize != 4:
                        continue  # f64 residue: the encode transfers it
                    if kind in ("z3", "z2") and i < 2:
                        cand = host.get(f"{geom}__{'xy'[i]}")
                        if (
                            cand is not None
                            and cand.dtype == tc.dtype
                            and np.array_equal(cand, tc)
                        ):
                            thin[i] = cand  # alias: share the matrix row
                    pack[f"__enc_{i}"] = np.ascontiguousarray(thin[i])
                if ebins is not None:
                    pack["__enc_bins"] = ebins.astype(np.uint32)
                enc_pre = (kind, sfc, coords_host, thin, ebins)
        cols = _stage_packed(pack)
        pre = None
        if enc_pre is not None:
            kind, sfc, coords_host, thin, ebins = enc_pre
            coords_dev = [
                cols.pop(f"__enc_{i}", thin[i]) for i in range(len(thin))
            ]
            bins_dev = cols.pop("__enc_bins", None)
            pre = (coords_host, coords_dev, ebins, bins_dev)
        if self._want_z:
            self._z_kind, zp, zbins = self._z_planes(batch, pre=pre)
            if self._z_kind in ("z3", "xz3") and len(batch):
                lo, hi = int(zbins.min()), int(zbins.max())
                rng = (
                    (lo, hi)
                    if self._bin_range is None
                    else (min(self._bin_range[0], lo),
                          max(self._bin_range[1], hi))
                )
                if rng != self._bin_range:
                    self._bin_range = rng
                    self._loose_cache.clear()  # stale keyed entries
            for k, v in zp.items():
                cols[k] = jnp.asarray(v)
        self._stage_vis(batch, cols)
        return cols

    # -- visibility plane --------------------------------------------------

    def _stage_vis(self, batch, cols: dict) -> None:
        """Stage the label-id plane for a batch (extends the vocabulary;
        raises _VisOverflow past VIS_VOCAB_MAX). Pure-public schemas (no
        label ever seen) stage no plane at all."""
        import jax.numpy as jnp

        vis = batch.visibilities
        norm = None
        if vis is not None:
            norm = np.array(
                ["" if v is None else str(v) for v in vis], dtype=object
            )
        labeled = norm is not None and bool(np.any(norm != ""))
        if self._vis_disabled:
            if labeled:
                raise _VisOverflow()
            return
        if self._vis_vocab is None:
            if not labeled:
                return  # no labels anywhere: zero overhead
            self._vis_vocab = {"": 0}
        if norm is None:
            ids = np.zeros(len(batch), np.int32)
        else:
            ids = self._vocab_ids(norm)
        cols[VIS_ID] = jnp.asarray(ids)
        self._visid_np = (
            ids
            if self._visid_np is None
            else np.concatenate([self._visid_np, ids])
        )

    def _vocab_ids(self, labels: np.ndarray) -> np.ndarray:
        uniq, inv = np.unique(labels.astype(str), return_inverse=True)
        mapped = np.empty(len(uniq), np.int32)
        grew = False
        for i, lab in enumerate(uniq.tolist()):
            vid = self._vis_vocab.get(lab)
            if vid is None:
                if len(self._vis_vocab) >= self.VIS_VOCAB_MAX:
                    raise _VisOverflow()
                vid = len(self._vis_vocab)
                self._vis_vocab[lab] = vid
                grew = True
            mapped[i] = vid
        if grew:
            self._auth_tables.clear()  # tables are per-vocabulary
        return mapped[inv].astype(np.int32)

    def _auth_table(self, auths):
        """Device bool table over the vocabulary for one auth set: entry
        v is True iff label v is visible under ``auths`` (None/() = no
        authorizations: labeled rows hide, fail closed). Padded to a
        power of two so jit shapes stay bounded as the vocabulary grows.
        """
        import jax.numpy as jnp

        from geomesa_tpu.security import VisibilityEvaluator

        key = tuple(sorted(str(a) for a in (auths or ())))
        tab = self._auth_tables.get(key)
        if tab is None:
            if len(self._auth_tables) >= 256:
                # bounded: the auth set comes straight from untrusted
                # request input; an attacker cycling made-up auth strings
                # must not grow device allocations without limit
                self._auth_tables.clear()
            cap = max(16, _next_pow2(len(self._vis_vocab)))
            vals = np.zeros(cap, dtype=bool)
            ev = VisibilityEvaluator(auths or ())
            for lab, vid in self._vis_vocab.items():
                vals[vid] = ev.can_see(lab if lab else None)
            tab = jnp.asarray(vals)
            self._auth_tables[key] = tab
        return tab

    def _apply_auths_np(self, m: np.ndarray, auths) -> np.ndarray:
        """Host-side auth AND over a hit mask (the mask/query path; the
        fused paths apply the same table on device)."""
        if self._visid_np is None:
            return m
        tab = np.asarray(self._auth_table(auths))
        return m & tab[self._visid_np[: len(m)]]

    def _stage_checked(self, batch):
        """(batch, cols) with the vocabulary-overflow fallback: on
        overflow, per-auth residency is disabled and labeled rows are
        dropped from the resident copy (the store path still serves
        them), loudly."""
        try:
            return batch, self._stage_batch(batch)
        except _VisOverflow:
            import warnings

            warnings.warn(
                f"visibility vocabulary exceeds {self.VIS_VOCAB_MAX} "
                "distinct labels; labeled rows leave the resident cache "
                "and are served by the store path only",
                RuntimeWarning,
                stacklevel=3,
            )
            self._vis_disabled = True
            self._vis_vocab = None
            self._auth_tables.clear()
            self._visid_np = None
            vis = batch.visibilities
            keep = np.array(
                [v is None or str(v) == "" for v in vis], dtype=bool
            )
            batch = batch.take(np.nonzero(keep)[0])
            return batch, self._stage_batch(batch)

    def _dim_usable(self, kind, sfc, bins) -> bool:
        """Whether THIS install can pack the dim-plane layout: a z2 key
        (always packs — 31-bit dims in uint32 planes, no time), or a z3
        key with 21-bit time precision and the data's bin span inside the
        packable window (top bin reserved for the out-of-range
        sentinel)."""
        from geomesa_tpu.ops.zscan import BT_BIN_SPAN, BT_TIME_BITS

        if self._dim_pref is False or kind not in ("z3", "z2"):
            if self._dim_pref is True:
                raise ValueError(
                    "dim_planes=True requires a z3/z2 (point) schema"
                )
            return False
        if kind == "z2":
            return True
        if sfc.precision != BT_TIME_BITS:
            if self._dim_pref is True:
                raise ValueError(
                    f"dim_planes=True requires time precision "
                    f"{BT_TIME_BITS} (got {sfc.precision})"
                )
            return False
        if bins is None or len(bins) == 0:
            return True  # base established by the first non-empty batch
        span_ok = int(bins.max()) - int(bins.min()) < BT_BIN_SPAN - 1
        if not span_ok and self._dim_pref is True:
            raise ValueError(
                f"dim_planes=True but the data spans >= {BT_BIN_SPAN - 1} "
                "period bins; the bt word cannot pack them"
            )
        return span_ok

    def _dim_planes_z2(self, sfc, coords, coords_dev=None):
        """{Z_NX, Z_NY} planes for a z2 batch in dim mode (no time in
        the key; no bin packing, so streaming appends never rebase).
        ``coords_dev`` are pre-staged device coords (the packed-transfer
        path); the host ``coords`` remain the exact-encode fallback."""
        import jax
        import jax.numpy as jnp

        x, y = coords
        if len(x) == 0:
            e = np.empty(0, np.uint32)
            return {Z_NX: e, Z_NY: e.copy()}
        if not self._z_encode_failed:
            dx, dy = coords_dev if coords_dev is not None else (x, y)
            try:
                with jax.enable_x64(True):
                    if self._dim_encode_jit is None:

                        def _enc2(x, y):
                            # f32-transferred coords upcast HERE (see
                            # _thin_transfer): bit-identical quantize
                            x = x.astype(jnp.float64)
                            y = y.astype(jnp.float64)
                            nx = sfc.lon.normalize_jax(x).astype(jnp.uint32)
                            ny = sfc.lat.normalize_jax(y).astype(jnp.uint32)
                            return nx, ny

                        self._dim_encode_jit = jax.jit(_enc2)
                    nx, ny = self._dim_encode_jit(
                        jnp.asarray(_thin_transfer(dx)),
                        jnp.asarray(_thin_transfer(dy)),
                    )
                    ny.block_until_ready()
                return {Z_NX: nx, Z_NY: ny}
            except Exception as e:  # pragma: no cover - platform (no f64)
                import warnings

                warnings.warn(
                    f"device key encode unavailable ({type(e).__name__}: "
                    f"{e}); staging falls back to the host encode for "
                    "this index",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self._z_encode_failed = True
                self._dim_encode_jit = None
        nx = np.asarray(sfc.lon.normalize(x)).astype(np.uint32)
        ny = np.asarray(sfc.lat.normalize(y)).astype(np.uint32)
        return {Z_NX: nx, Z_NY: ny}

    def _dim_planes_for(self, sfc, coords, bins, coords_dev=None,
                        bins_dev=None):
        """{Z_NX, Z_NY, Z_BT} planes for a z3 batch in dim mode. Devices
        encode when possible (scoped x64 quantize, same latched fallback
        as the interleaved path); establishes ``_bt_base`` on the first
        non-empty batch and raises :class:`_BtRebase` when a delta's bins
        fall outside the packed window. ``coords_dev``/``bins_dev`` are
        pre-staged device arrays (the packed-transfer path); the host
        ``coords``/``bins`` remain the bookkeeping + fallback source."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        if bins is None or len(bins) == 0:
            e = np.empty(0, np.uint32)
            return {Z_NX: e, Z_NY: e.copy(), Z_BT: e.copy()}
        if self._bt_base is None:
            self._bt_base = int(bins.min())
        lo, hi = int(bins.min()), int(bins.max())
        if not (
            self._bt_base <= lo
            and hi - self._bt_base < zscan.BT_BIN_SPAN - 1
        ):
            raise _BtRebase()
        x, y, off = coords
        if not self._z_encode_failed:
            dx, dy, doff = (
                coords_dev if coords_dev is not None else (x, y, off)
            )
            try:
                with jax.enable_x64(True):
                    if self._dim_encode_jit is None:

                        def _enc(x, y, off, bins_u32, base):
                            # f32-transferred coords upcast HERE (see
                            # _thin_transfer): bit-identical quantize
                            x = x.astype(jnp.float64)
                            y = y.astype(jnp.float64)
                            off = off.astype(jnp.float64)
                            nx = sfc.lon.normalize_jax(x).astype(jnp.uint32)
                            ny = sfc.lat.normalize_jax(y).astype(jnp.uint32)
                            nt = sfc.time.normalize_jax(off).astype(
                                jnp.uint32
                            )
                            return zscan.z3_dim_planes(
                                sfc, nx, ny, nt, bins_u32, base
                            )

                        self._dim_encode_jit = jax.jit(_enc)
                    nx, ny, bt = self._dim_encode_jit(
                        jnp.asarray(_thin_transfer(dx)),
                        jnp.asarray(_thin_transfer(dy)),
                        jnp.asarray(_thin_transfer(doff)),
                        bins_dev
                        if bins_dev is not None
                        else jnp.asarray(np.asarray(bins).astype(np.uint32)),
                        jnp.uint32(self._bt_base),
                    )
                    bt.block_until_ready()
                return {Z_NX: nx, Z_NY: ny, Z_BT: bt}
            except Exception as e:  # pragma: no cover - platform (no f64)
                import warnings

                warnings.warn(
                    f"device key encode unavailable ({type(e).__name__}: "
                    f"{e}); staging falls back to the host encode for "
                    "this index",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self._z_encode_failed = True
                self._dim_encode_jit = None
        nx = np.asarray(sfc.lon.normalize(x)).astype(np.uint32)
        ny = np.asarray(sfc.lat.normalize(y)).astype(np.uint32)
        nt = np.asarray(sfc.time.normalize(off)).astype(np.uint32)
        nx, ny, bt = zscan.z3_dim_planes(
            sfc, nx, ny, nt, bins.astype(np.uint32), self._bt_base
        )
        return {Z_NX: nx, Z_NY: ny, Z_BT: bt}

    def _z_planes(self, batch, pre=None):
        """Key planes for a batch: the jitted DEVICE encode (quantize +
        interleave / XZ tree walk run on-chip — staging 2^24+ rows was a
        multi-second host CPU pass), falling back
        to the numpy oracle when the device cannot run the float64-exact
        encode. Geometry envelope extraction and time binning stay on host
        (cheap vectorized passes; geometry parsing is host-side anyway).

        ``pre`` = (coords_host, coords_dev, bins, bins_dev) from the
        packed staging transfer (_stage_batch): the device arrays feed
        the encode with no further H2D round trips, the host arrays keep
        the bookkeeping + exact fallback.

        Returns (kind, planes, bins). For z3 schemas the planes are the
        DE-INTERLEAVED dim layout (Z_NX/Z_NY/Z_BT — the bandwidth-champion
        scan) whenever the bin span packs;
        otherwise the interleaved (Z_BIN, Z_HI, Z_LO) masked-compare
        layout."""
        import jax
        import jax.numpy as jnp

        kind, sfc = _z_schema_kind(self.sft)
        if kind is None:
            return None, {}, None
        if pre is not None:
            coords, coords_dev, bins, bins_dev = pre
        else:
            coords, bins = _encode_inputs(batch, self.sft, kind, sfc)
            coords_dev = bins_dev = None
        if self._bin_range is None:
            # (re)decided at install time (refresh/_install reset the bin
            # range before staging); delta batches keep the staged layout
            self._dim_mode = self._dim_usable(kind, sfc, bins)
        if self._dim_mode:
            if kind == "z2":
                return kind, self._dim_planes_z2(
                    sfc, coords, coords_dev=coords_dev
                ), bins
            return kind, self._dim_planes_for(
                sfc, coords, bins, coords_dev=coords_dev, bins_dev=bins_dev
            ), bins
        if len(batch) == 0:
            return _z_planes_np(batch, self.sft)
        if self._z_encode_failed:
            # latched: pay the trace-and-fail cost once, not per batch
            hi, lo = _split_u64(np.asarray(sfc.index(*coords)))
        else:
            try:
                # scoped x64: the encode must quantize in float64 to match
                # the host oracle bit-for-bit, without flipping the
                # process-wide dtype default (callers may run float32
                # everywhere else)
                with jax.enable_x64(True):
                    if self._z_encode_jit is None:

                        def _enc_hl(*cs):
                            # f32-transferred coords upcast HERE (see
                            # _thin_transfer): bit-identical quantize
                            return sfc.index_jax_hi_lo(
                                *[c.astype(jnp.float64) for c in cs]
                            )

                        self._z_encode_jit = jax.jit(_enc_hl)
                    hi, lo = self._z_encode_jit(
                        *[
                            jnp.asarray(_thin_transfer(c))
                            for c in (
                                coords_dev
                                if coords_dev is not None
                                else coords
                            )
                        ]
                    )
                    hi.block_until_ready()
            except Exception as e:  # pragma: no cover - platform (no f64)
                import warnings

                # loud latch: a silent fallback would hide a real device-
                # encode regression behind the slow host pass it replaces
                warnings.warn(
                    f"device key encode unavailable ({type(e).__name__}: "
                    f"{e}); staging falls back to the host encode for "
                    "this index",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self._z_encode_failed = True
                self._z_encode_jit = None
                hi, lo = _split_u64(np.asarray(sfc.index(*coords)))
        planes = {Z_HI: hi, Z_LO: lo}
        if bins is not None:
            planes[Z_BIN] = np.asarray(bins, np.int32)
        return kind, planes, bins

    # -- cache lifecycle ---------------------------------------------------

    def refresh(self) -> None:
        """Re-stage from the backing store (after writes / age-off).
        Compiled filters are data-independent and persist; jit re-compiles
        on its own if the row count changes shape.

        Stores that publish manifest chunk statistics (partition format
        v2, store/chunkstats.py) make this cheap to plan: the staging
        scan's full-scan shape rides the store's PRE-SIZED assembly
        (buffers sized from the manifest's chunk row counts, zero-row
        chunks skipped — one dataset copy at peak instead of the
        collect-then-concat two), and the row total is known before any
        file is read, so the traced span carries it up front."""
        from geomesa_tpu.tracing import span

        rows_hint = getattr(self.store, "manifest_rows", None)
        hint = int(rows_hint(self.type_name)) if rows_hint else -1
        from geomesa_tpu import ledger

        with span("cache.stage", type=self.type_name, rows_hint=hint), \
                ledger.compile_scope("cache.stage"):
            res = self.store.query(self.type_name, _staging_query())
            self._cols = self._stage_full(res.batch)

    def __len__(self) -> int:
        return self._n

    def refresh_delta(self, batch) -> str:
        """Incrementally fold freshly appended rows into the resident
        planes (the streaming live layer's per-append hook). The base
        cache has no validity plane or capacity headroom, so its only
        correct move is the full restage; the streaming and sharded
        flavors override this with true in-place deltas behind their
        validity planes. Returns the mode taken (``"delta"`` /
        ``"restage"``) and counts it on
        ``geomesa_stream_delta_refreshes_total``."""
        from geomesa_tpu import metrics

        self.refresh()
        metrics.stream_delta_refreshes.inc(mode="restage")
        return "restage"

    @property
    def nbytes(self) -> int:
        """Resident device bytes (planes and validity plane)."""
        valid = 0 if self._valid is None else self._valid.nbytes
        return int(sum(v.nbytes for v in self._cols.values()) + valid)

    def attach_live(self, live_store):
        """Refresh on every applied live-layer change (coarse; the
        streaming refinement is per-partition donation). Returns a
        zero-arg detach callable that unregisters the listener, releasing
        this index for garbage collection."""
        listener = lambda _msg: self.refresh()  # noqa: E731
        live_store.add_listener(listener)

        def detach() -> None:
            remove = getattr(live_store, "remove_listener", None)
            if remove is not None:
                remove(listener)

        return detach

    # -- loose (key-only) scans --------------------------------------------

    def _bbox_during_parts(self, f):
        """Split a filter into (envelope, window) when it is EXACTLY a
        bbox on the default geometry, a during on the default date, or a
        conjunction of the two — the only shapes the key planes answer."""
        geom, dtg = self.sft.geom_field, self.sft.dtg_field
        parts = f.children if isinstance(f, ast.And) else (f,)
        env = window = None
        for p in parts:
            if isinstance(p, ast.BBox) and p.attr == geom and env is None:
                env = (p.xmin, p.ymin, p.xmax, p.ymax)
            elif (
                isinstance(p, ast.During) and p.attr == dtg and window is None
            ):
                window = (int(p.t0), int(p.t1))
            else:
                return None
        return env, window

    def _loose_bounds(self, f):
        """Device (bounds, ids) for the key-only scan, or None when the
        filter shape / resident planes cannot answer it. ids is None for
        the unbinned Z2 case. Cached per (filter, observed bin range) so
        repeated loose queries stay single-dispatch — the loose analog of
        the exact path's ``_compiled`` cache."""
        key = (repr(f), self._bin_range)
        if key in self._loose_cache:
            return self._loose_cache[key]
        lb = self._loose_bounds_uncached(f)
        self._loose_cache[key] = lb
        return lb

    def _loose_bounds_uncached(self, f):
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        if self._z_kind is None:
            return None
        parts = self._bbox_during_parts(f)
        if parts is None:
            return None
        env, window = parts
        if env is None and window is None:
            return None  # INCLUDE: nothing to prune, use the normal path
        # the SAME sfc the key planes were staged with (one dispatch table;
        # a different curve here would silently break the loose-superset
        # invariant)
        _, sfc = _z_schema_kind(self.sft)
        if self._z_kind == "z2":
            if window is not None:
                return None  # no time in the key
            if self._dim_mode:
                # 2-plane dim scan; R=0 tags the unbinned kernel variant
                qarr = zscan.z2_dim_plane_qarr(sfc, env)
                return ("dim", jnp.asarray(qarr), 0)
            qlo = (int(sfc.lon.normalize(env[0])), int(sfc.lat.normalize(env[1])))
            qhi = (int(sfc.lon.normalize(env[2])), int(sfc.lat.normalize(env[3])))
            return jnp.asarray(zscan.z2_dim_bounds(qlo, qhi)), None
        if self._z_kind == "xz2":
            if window is not None:
                return None  # no time in the key
            bounds = zscan.pad_ranges(
                zscan.xz2_query_bounds(sfc, env[0], env[1], env[2], env[3])
            )
            return jnp.asarray(bounds), None
        binned_sfc = sfc
        if env is None:
            env = (-180.0, -90.0, 180.0, 90.0)
        if window is None:
            if self._bin_range is None:
                return None  # empty index; normal path returns empty too
            from geomesa_tpu.curves.binnedtime import (
                bin_to_millis,
                max_offset,
                offset_to_millis,
            )

            p = binned_sfc.period
            window = (
                int(bin_to_millis(self._bin_range[0], p)),
                int(bin_to_millis(self._bin_range[1], p))
                + int(offset_to_millis(max_offset(p), p)),
            )
        if self._dim_mode and self._z_kind == "z3":
            if self._bt_base is None:
                return None  # nothing staged; normal path returns empty too
            q = zscan.z3_dim_plane_qarr(
                binned_sfc, env, window, self._bt_base, self._bin_range
            )
            if q is None:
                return None  # unpackable window: exact path still answers
            qarr, r = q
            return ("dim", jnp.asarray(qarr), r)
        if self._z_kind == "z3":
            bounds, ids = zscan.z3_query_bounds(
                binned_sfc, env[0], env[1], env[2], env[3],
                window[0], window[1],
            )
            empty_bounds = np.zeros((1, 3, 6), np.uint32)
        else:  # xz3
            bounds, ids = zscan.xz3_query_bounds(
                binned_sfc, env[0], env[1], env[2], env[3],
                window[0], window[1],
            )
            empty_bounds = np.broadcast_to(
                zscan._NEVER_RANGE, (1, 1, 4)
            ).copy()
        if self._bin_range is not None:
            keep = (ids >= self._bin_range[0]) & (ids <= self._bin_range[1])
            bounds, ids = bounds[keep], ids[keep]
        if len(ids) == 0:
            bounds = empty_bounds
            ids = np.full(1, -1, np.int32)  # matches nothing
        if len(ids) > 64 or bounds.size > 8192:
            # absurd window (or a bins x ranges product whose per-row test
            # cost exceeds the key-scan's bandwidth win): normal scan
            return None
        bounds, ids = zscan.pad_bins(bounds, ids)
        return jnp.asarray(bounds), jnp.asarray(ids)

    def _dim_args(self, lb):
        """(count_fn, mask_fn, operands) for a dim-tagged loose-bounds
        result — the ONE assembly point for the dim-plane kernel and its
        resident operands (count(), mask() and loose_scan_kernel must
        dispatch the identical kernel or the benchmarked engine drifts
        from the served one)."""
        _, qarr, r = lb
        count_fn, mask_fn = self._dim_kernel(r)
        if r == 0:  # unbinned z2: 2-plane kernel
            return count_fn, mask_fn, (
                qarr, self._cols[Z_NX], self._cols[Z_NY]
            )
        return count_fn, mask_fn, (
            qarr, self._cols[Z_NX], self._cols[Z_NY], self._cols[Z_BT]
        )

    def _dim_kernel(self, n_ranges: int):
        """(count_fn, mask_fn) Pallas dim-plane kernels for one R bucket —
        runtime query bounds, so ONE compile serves every window. JITTED:
        the raw builders chain several host-visible ops (pad, reshape,
        pallas_call, sum) and each op is a separate dispatch; one jit
        makes a serve one dispatch."""
        import jax

        from geomesa_tpu.ops import zscan

        fns = self._dim_kernels.get(n_ranges)
        if fns is None:
            if n_ranges == 0:  # unbinned z2: 2-plane kernel
                cf, mf = zscan.build_z2_dimscan_rt()
            else:
                cf, mf = zscan.build_z3_dimscan_rt(n_ranges)
            fns = (jax.jit(cf), jax.jit(mf))
            self._dim_kernels[n_ranges] = fns
        return fns

    def _z_mask_dev(self, lb):
        """Device bool mask from the key planes (pre-validity). ``lb`` is
        a _loose_bounds result: ("dim", qarr, R) for the dim-plane layout,
        else (bounds, ids) for the masked-compare/range engines."""
        import jax

        from geomesa_tpu.ops import zscan

        if len(lb) == 3 and lb[0] == "dim":
            _, mask_fn, kargs = self._dim_args(lb)
            return mask_fn(*kargs)
        bounds, ids = lb
        if self._z_jit is None:
            self._z_jit = {
                k: jax.jit(zscan.kind_mask_fn(k))
                for k in ("z3", "z2", "xz3", "xz2")
            }
        if ids is None:  # unbinned: z2 masked-compare or xz2 range list
            return self._z_jit[self._z_kind](
                self._cols[Z_HI], self._cols[Z_LO], bounds
            )
        return self._z_jit[self._z_kind](
            self._cols[Z_HI], self._cols[Z_LO], self._cols[Z_BIN],
            bounds, ids,
        )

    def _resolve_loose(self, loose: "bool | None") -> bool:
        if loose is None:
            from geomesa_tpu.conf import sys_prop

            loose = bool(sys_prop("query.loose.bbox"))
        return bool(loose) and self._z_kind is not None

    def _loose_mask(self, f) -> "np.ndarray | None":
        """Host bool mask over staged rows via the key planes, or None."""
        lb = self._loose_bounds(f)
        if lb is None:
            return None
        m = np.asarray(self._z_mask_dev(lb))[: self._staged_len()]
        hv = self._host_valid()
        return (m & hv) if hv is not None else m

    # -- host mirror, padded-buffer deltas, read hooks ---------------------

    def _stage_full(self, batch) -> dict:
        """Full (re)stage: stage ``batch`` afresh (``_stage_checked``)
        and install what it kept as the whole host mirror. Returns the
        staged planes."""
        self._bin_range = None
        self._bt_base = None
        self._visid_np = None
        batch, cols = self._stage_checked(batch)
        self._host_parts = [batch]
        self._host_batch = batch
        self._n = len(batch)
        return cols

    def _host_rows(self):
        """Host mirror aligned row-for-row with the device columns:
        deltas collect as parts and pay ONE concat at the next host-side
        read instead of one per append."""
        if self._host_batch is None:
            from geomesa_tpu.features.batch import FeatureBatch

            self._host_batch = FeatureBatch.concat(self._host_parts)
            self._host_parts = [self._host_batch]
        return self._host_batch

    def _delta_pad(self, m: int) -> int:
        """Rows an ``m``-row delta pads to (a bounded set of shapes)."""
        return max(_next_pow2(m), self.MIN_DELTA_ROWS)

    def _write_delta(self, batch, delta: dict, pad: int) -> bool:
        """Write a batch's staged ``delta`` planes into the padded
        planes at row ``_n`` (one donated :func:`_slot_write` launch
        that also marks its rows live) and append it to the host
        mirror. The caller has checked that ``pad`` rows fit. False,
        with nothing written, when the delta's planes differ from the
        buffers' (the first labeled rows bring a plane with no slot):
        the caller restages."""
        if set(delta) != set(self._cols):
            return False
        m = len(batch)
        write = _slot_write(pad, tuple(sorted(delta)))
        self._cols, self._valid = write(
            self._cols, self._valid, _pad_rows(delta, pad), self._n, m
        )
        self._host_parts.append(batch)
        self._host_batch = None
        self._n += m
        return True

    def _host_valid(self) -> "np.ndarray | None":
        """Host-side validity over the mirror rows; None = all live."""
        return None

    def _device_valid(self):
        """Device validity plane over staged rows; None = all live."""
        return self._valid

    def _staged_len(self) -> int:
        """Rows staged on device (mirror length; may exceed live rows)."""
        return self._n

    def _read_scope(self, op: str):
        """Entered by every public read inside its snapshot
        (``_snapshot``; ``op`` names the read): a no-op here."""
        return contextlib.nullcontext()

    def _make_scan_fns(self, compiled):
        """(count_fn, mask_fn, engine) over the resident column subset:
        count_fn launches the count and returns the zero-arg fetch of its
        host value, mask_fn returns the device mask, and
        ``engine`` names the scan both dispatch ("pallas" or "xla").

        When a device validity plane exists (padded buffers: streaming
        deltas, mesh shards) it is ANDed into the fused scan — padding
        rows stage as zeros and CAN match a filter. The plane is read
        at CALL time (appends/refreshes replace it), and an index whose
        plane appears only after a later restage still dispatches the
        valid-aware jit from then on. The Pallas tile kernels take the
        plane as one more input; a mesh index keeps the XLA-fused scan,
        which jit partitions across the shards (a Pallas call is not
        SPMD-partitionable). A count on the polygon kernel moves the
        point-in-polygon tile, block and edge-test counters."""
        import jax

        from geomesa_tpu import metrics
        from geomesa_tpu.filter.compile import has_polygon, xla_counts

        plain_count, plain_mask = compiled.jitted_scan()
        engine = compiled.scan_engine
        if engine == "pallas" and not self._pallas_tiles:
            engine = "xla"
            plain_mask = jax.jit(compiled.device_fn)
            plain_count = jax.jit(lambda c: xla_counts(compiled.device_fn(c)))
        if engine == "pallas":
            count_jit, mask_jit = plain_count, plain_mask
        else:
            mask_jit = jax.jit(
                lambda cols, valid: compiled.device_fn(cols) & valid
            )
            count_jit = jax.jit(
                lambda cols, valid: xla_counts(
                    compiled.device_fn(cols) & valid)
            )

        def dispatch(cols):
            dv = self._device_valid()
            return count_jit(cols, dv) if dv is not None else \
                plain_count(cols)

        def mask_fn(cols):
            dv = self._device_valid()
            return mask_jit(cols, dv) if dv is not None else plain_mask(cols)

        edge_tests = loop_tests = 0  # per tested block
        if engine == "pallas" and has_polygon(compiled.device_part):
            from geomesa_tpu.ops import pallas_scan as ps

            rows = ps.SKIP_ROWS * ps.LANES
            edge_tests = rows * ps.padded_edges(compiled.device_part)
            loop_tests = rows * ps.looped_edges(compiled.device_part)

        def count_fn(cols):
            out = dispatch(cols)

            def fetch() -> int:
                got = _fetch(np.asarray, out).tolist()
                if edge_tests:
                    _, tested, skipped, walked, dead = got
                    metrics.pip_blocks.inc_key(metrics.PIP_TESTED, tested)
                    metrics.pip_blocks.inc_key(metrics.PIP_SKIPPED, skipped)
                    metrics.pip_edge_tests.inc(tested * edge_tests)
                    metrics.pip_edge_loop_tests.inc(tested * loop_tests)
                    metrics.pip_tiles.inc_key(metrics.PIP_WALKED, walked)
                    metrics.pip_tiles.inc_key(metrics.PIP_TILE_SKIPPED, dead)
                return got[0]

            return fetch

        return count_fn, mask_fn, engine

    # -- queries -----------------------------------------------------------

    def _compiled_for(self, query, key=None):
        """The ``_compiled`` entry of a filter, keyed by its ``repr``
        (``key``, when the caller already holds it from :meth:`_keyed`)."""
        from geomesa_tpu.filter.compile import compile_filter

        f = self._parse(query)
        if key is None:
            key = repr(f)
        if key not in self._compiled:
            compiled = compile_filter(f, self.sft)
            missing = [c for c in compiled.device_cols if c not in self._cols]
            if missing:
                # a custom columns= list omits planes this filter wants on
                # device: degrade to exact host evaluation rather than
                # refusing a query the full-mirror path can answer
                import warnings

                warnings.warn(
                    f"columns {missing} not resident; evaluating "
                    f"{key!r} on host (pass columns= including them "
                    f"for the device path)",
                    stacklevel=3,
                )
                self._compiled[key] = (compiled, None, None, "host")
            else:
                self._compiled[key] = (compiled, *self._make_scan_fns(
                    compiled))
        return self._compiled[key]

    def _resident_subset(self, compiled) -> dict:
        return {c: self._cols[c] for c in compiled.device_cols}

    def _parse(self, query):
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.query.plan import Query

        if isinstance(query, Query):
            # a Query's hints (auths!) would be silently ignored here --
            # refuse loudly instead of serving rows under the wrong auths
            raise TypeError(
                "DeviceIndex takes a CQL string or filter AST; pass "
                "auths= explicitly (Query hints are store-path plumbing)"
            )
        if not isinstance(query, str):
            return query
        return self._keyed(query)[0]

    def _keyed(self, query):
        """(filter, ``_compiled`` key) of a CQL string; an AST comes back
        with key None (:meth:`_compiled_for` prints it). A string's pair
        is remembered (filters are immutable and the parse reads no index
        state), so a repeated request skips both the parse and the key's
        ``repr`` -- each walks every vertex of a polygon literal. The memo
        holds at most ``_TEXTS_MAX`` strings and starts over when full.
        The ``cql.parse`` stage times the lookup, and the parse on a
        miss."""
        from geomesa_tpu.filter.ecql import parse_ecql
        from geomesa_tpu.tracing import span

        if not isinstance(query, str):
            return self._parse(query), None
        with span("cql.parse"):
            hit = self._texts.get(query)
            if hit is None:
                f = parse_ecql(query)
                hit = (f, repr(f))
                if len(self._texts) >= _TEXTS_MAX:
                    self._texts.clear()
                self._texts[query] = hit
            return hit

    def count(
        self, query, loose: "bool | None" = None, auths=None
    ) -> int:
        """Fused device count; exact when the filter is fully on-device,
        else falls through to query(). With loose=True (or the
        query.loose.bbox property) bbox(+during) filters are answered at
        cell granularity from the resident key planes. ``auths`` applies
        per-request row security against the staged label-id plane
        (None/() hides labeled rows — fail closed)."""
        return self._launch_count(query, loose=loose, auths=auths)()

    @_snapshot
    @_scan_scoped
    def _launch_count(self, query, loose=None, auths=None):
        """:meth:`count` up to its device launch: returns the zero-arg
        fetch of the count. The launch reads the resident planes; the
        fetch only waits for the launched work, so the snapshot
        (``_snapshot``) covers the launch alone and one count's copy
        overlaps the next count's launch."""
        import jax.numpy as jnp

        from geomesa_tpu.failpoints import fail_point

        fail_point("fail.device.launch")  # chaos: resident count launch
        f, key = self._keyed(query)
        if VIS_ID in (self._cols or {}):
            # labeled data: the auth table must AND into the device mask
            if self._staged_len() == 0:
                return _ready(0)
            outs = self._fused_agg(
                f, loose, ("count",),
                lambda cols, m: {"__count": jnp.sum(m, dtype=jnp.int32)},
                auths=auths,
            )
            if outs is not None:
                return partial(_fetch, int, outs["__count"])
            return _ready(int(self.mask(f, loose=loose, auths=auths).sum()))
        if self._resolve_loose(loose):
            lb = self._loose_bounds(f)
            if lb is not None:
                dv = self._device_valid()
                if len(lb) == 3 and lb[0] == "dim" and dv is None:
                    # the bandwidth-champion path: Pallas dim-plane count,
                    # one dispatch, 12B/row
                    count_fn, _, kargs = self._dim_args(lb)
                    return partial(_fetch, int, count_fn(*kargs))
                m = self._z_mask_dev(lb)
                if dv is not None:
                    m = m & dv
                return partial(_fetch, int, m.sum())
        compiled, count_fn, _, engine = self._compiled_for(f, key)
        on_device = bool(compiled.device_cols) and count_fn is not None
        from geomesa_tpu.filter.compile import has_polygon

        if has_polygon(compiled.filter):
            # where the point-in-polygon test runs
            from geomesa_tpu import metrics

            metrics.pip_queries.inc(
                engine=engine if on_device and has_polygon(
                    compiled.device_part) else "host")
        if not on_device:
            m = compiled.host_mask(self._host_rows())
            hv = self._host_valid()
            return _ready(int((m & hv).sum() if hv is not None else m.sum()))
        if not compiled.fully_on_device:
            return _ready(len(self.query(query)))
        return count_fn(self._resident_subset(compiled))

    def loose_scan_kernel(self, query):
        """(count_fn, args) — the EXACT kernel + resident operands that
        ``count(query, loose=True)`` dispatches, exposed so a benchmark
        can chain K invocations inside one dispatch (bench.py measures
        the serving path through this hook, not a bench-local copy).
        Returns None when the loose engine cannot answer the filter or
        a validity/visibility plane would change the result."""
        f = self._parse(query)
        lb = self._loose_bounds(f)
        if lb is None or self._device_valid() is not None \
                or VIS_ID in (self._cols or {}):
            return None
        if len(lb) == 3 and lb[0] == "dim":
            count_fn, _, kargs = self._dim_args(lb)
            return count_fn, kargs
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        bounds, ids = lb
        mf = zscan.kind_mask_fn(self._z_kind)
        if ids is None:
            fn = lambda hi, lo, b: jnp.sum(  # noqa: E731
                mf(hi, lo, b), dtype=jnp.int32
            )
            return fn, (self._cols[Z_HI], self._cols[Z_LO], bounds)
        fn = lambda hi, lo, bn, b, i: jnp.sum(  # noqa: E731
            mf(hi, lo, bn, b, i), dtype=jnp.int32
        )
        return fn, (
            self._cols[Z_HI], self._cols[Z_LO], self._cols[Z_BIN],
            bounds, ids,
        )

    # -- micro-batch scan fusion (device query scheduler) ------------------

    @_snapshot
    def fused_loose_counts(self, queries, loose: "bool | None" = None):
        """Answer Q compatible loose queries in ONE batched device
        launch: each query's z-range set stacks along a leading query
        axis (padded to power-of-two Q/B/R buckets so jit shapes stay
        bounded) and a single vmapped zscan dispatch returns every count.
        Results equal ``[count(q, loose=True) for q in queries]``
        exactly. Returns None when the group cannot fuse — mixed scan
        engines or R buckets, labeled rows staged (per-request auth
        tables are per-query state), a filter the key planes cannot
        answer, or loose mode off — and the caller falls back to serial
        execution."""
        out = self._fused_loose(queries, loose, want="count")
        if out is None:
            return None
        return [int(v) for v in np.asarray(out)]

    @_snapshot
    def fused_loose_query(self, queries, loose: "bool | None" = None):
        """Batched sibling of :meth:`query`: one device launch computes
        the (Q, n) hit matrix, then per-query host takes demux the rows.
        Returns a list of FeatureBatch aligned with ``queries``, or None
        when the group cannot fuse (see :meth:`fused_loose_counts`)."""
        m = self._fused_loose(queries, loose, want="mask")
        if m is None:
            return None
        m = np.asarray(m)[:, : self._staged_len()]
        hv = self._host_valid()
        if hv is not None:
            m = m & hv[None, : m.shape[1]]
        rows = self._host_rows()
        return [rows.take(np.nonzero(r)[0]) for r in m]

    def _fused_loose(self, queries, loose, want: str):
        """(Q,) counts or (qcap, n) mask matrix for a fusable group, or
        None. The count variant ANDs the device validity plane in-launch
        (mirroring the serial count path); the mask variant leaves
        validity to the host-side AND in fused_loose_query (mirroring
        _loose_mask)."""
        from geomesa_tpu.failpoints import fail_point

        fail_point("fail.device.launch")  # chaos: fused resident launch
        if not queries:
            return None
        if VIS_ID in (self._cols or {}):
            return None
        if not self._resolve_loose(loose) or self._staged_len() == 0:
            return None
        lbs = []
        for q in queries:
            lb = self._loose_bounds(self._parse(q))
            if lb is None:
                return None
            lbs.append(lb)
        n_dim = sum(1 for lb in lbs if len(lb) == 3 and lb[0] == "dim")
        if n_dim and n_dim != len(lbs):
            return None  # mixed engines: serial fallback
        qcap = _next_pow2(len(lbs))
        if n_dim:
            return self._fused_dim(lbs, qcap, want)
        return self._fused_compare(lbs, qcap, want)

    def _fused_dim(self, lbs, qcap, want: str):
        """Stacked dim-plane launch: per-query qarr vectors pad to the
        group's largest R bucket with never-matching bt ranges (the
        z3_dim_plane_qarr padding convention), queries pad to qcap with
        fully inverted vectors."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        rs = [lb[2] for lb in lbs]
        r = max(rs)
        if r and 0 in rs:
            return None  # a z2 (no bt plane) query cannot join a z3 group
        qmat = np.empty((qcap, 4 + 2 * r), np.uint32)
        qmat[:] = np.array(
            [1, 0, 1, 0] + [0xFFFFFFFF, 0] * r, np.uint32
        )  # inverted: matches nothing
        for i, lb in enumerate(lbs):
            qa = np.asarray(lb[1])
            qmat[i, : len(qa)] = qa
        key = ("fdim", r, qcap, want)
        fn = self._fused_jits.get(key)
        _note_jit_cache(fn is not None)
        if fn is None:
            bm = zscan.batched_dim_mask_rt(r)

            def _run(planes, qmat, valid, _bm=bm, _want=want):
                m = _bm(*planes, qmat)
                if _want == "count":
                    if valid is not None:
                        m = m & valid[None, :]
                    return jnp.sum(m, axis=1, dtype=jnp.int32)
                return m

            fn = jax.jit(_run)
            self._fused_jits[key] = fn
        planes = (
            (self._cols[Z_NX], self._cols[Z_NY])
            if r == 0
            else (self._cols[Z_NX], self._cols[Z_NY], self._cols[Z_BT])
        )
        from geomesa_tpu import ledger

        # r and qcap are pow2-bucketed: the signature space stays bounded.
        # The result slice stays inside the scope: it is an eager device
        # op whose (qcap, len) signature compiles its own tiny kernel.
        with ledger.compile_scope(f"fused.dim:r={r}:q={qcap}:{want}"):
            out = fn(
                planes,
                jnp.asarray(qmat),
                self._device_valid() if want == "count" else None,
            )
            return out[: len(lbs)]

    def _fused_compare(self, lbs, qcap, want: str):
        """Stacked masked-compare / range-list launch: per-query bounds
        pad to the group's bin/range maxima (ids -1 and inverted ranges
        match nothing), queries pad to qcap the same way."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.ops import zscan

        kind = self._z_kind
        binned = kind in ("z3", "xz3")
        if binned:
            bs = [np.asarray(lb[0]) for lb in lbs]
            ids = [np.asarray(lb[1]) for lb in lbs]
            bmax = max(len(i) for i in ids)  # pow2 already (pad_bins)
            if kind == "xz3":
                rmax = max(b.shape[1] for b in bs)
                bs = [zscan.pad_ranges(b, min_r=rmax) for b in bs]
                tail = (rmax, 4)
            else:
                tail = (3, 6)
            bounds = np.zeros((qcap, bmax) + tail, np.uint32)
            idm = np.full((qcap, bmax), -1, np.int32)
            for i, (b, bi) in enumerate(zip(bs, ids)):
                bounds[i, : len(bi)] = b
                idm[i, : len(bi)] = bi
        else:
            bs = [np.asarray(lb[0]) for lb in lbs]
            if kind == "xz2":
                rmax = max(b.shape[0] for b in bs)
                bs = [zscan.pad_ranges(b, min_r=rmax) for b in bs]
                never = np.broadcast_to(zscan._NEVER_RANGE, (rmax, 4))
            else:  # z2 masked-compare: (2, 6) rows, lo_lo=1 > hi=0
                never = np.zeros((2, 6), np.uint32)
                never[:, 3] = 1
            bounds = np.empty((qcap,) + never.shape, np.uint32)
            bounds[:] = never
            for i, b in enumerate(bs):
                bounds[i] = b
            idm = None
        key = ("fcmp", kind, bounds.shape, want)
        fn = self._fused_jits.get(key)
        _note_jit_cache(fn is not None)
        if fn is None:
            bm = zscan.batched_kind_mask(kind)

            def _run(hi, lo, bins, bounds, ids, valid, _bm=bm, _want=want):
                if ids is None:
                    m = _bm(hi, lo, bounds)
                else:
                    m = _bm(hi, lo, bins, bounds, ids)
                if _want == "count":
                    if valid is not None:
                        m = m & valid[None, :]
                    return jnp.sum(m, axis=1, dtype=jnp.int32)
                return m

            fn = jax.jit(_run)
            self._fused_jits[key] = fn
        from geomesa_tpu import ledger

        # slice inside the scope: the eager trim compiles its own kernel
        with ledger.compile_scope(f"fused.cmp:{kind}:q={qcap}:{want}"):
            out = fn(
                self._cols[Z_HI],
                self._cols[Z_LO],
                self._cols.get(Z_BIN) if binned else None,
                jnp.asarray(bounds),
                jnp.asarray(idm) if idm is not None else None,
                self._device_valid() if want == "count" else None,
            )
            return out[: len(lbs)]

    @_snapshot
    @_scan_scoped
    def mask(
        self, query, loose: "bool | None" = None, auths=None
    ) -> np.ndarray:
        """Boolean hit mask over the staged rows; rows absent from the
        live set (evicted, in subclasses) are always False. When a
        label-id plane is staged, the per-request ``auths`` verdict is
        ANDed in (fail closed on None/())."""
        from geomesa_tpu.failpoints import fail_point

        fail_point("fail.device.launch")  # chaos: resident scan launch
        f, key = self._keyed(query)
        if self._resolve_loose(loose):
            lm = self._loose_mask(f)
            if lm is not None:
                return self._apply_auths_np(lm, auths)
        compiled, _, mask_fn, _ = self._compiled_for(f, key)
        if not compiled.device_cols or mask_fn is None:
            m = compiled.host_mask(self._host_rows())
            hv = self._host_valid()
            m = (m & hv) if hv is not None else m
            return self._apply_auths_np(m, auths)
        m = np.asarray(mask_fn(self._resident_subset(compiled)))
        m = m[: self._staged_len()]
        if not compiled.fully_on_device:
            idx = np.nonzero(m)[0]
            out = np.zeros(len(m), dtype=bool)
            if len(idx):
                keep = compiled.residual_mask(self._host_rows().take(idx))
                out[idx[keep]] = True
            m = out
        return self._apply_auths_np(m, auths)

    @_snapshot
    def query(self, query, loose: "bool | None" = None, auths=None):
        """FeatureBatch of hits (host-side take over the device mask)."""
        return self._host_rows().take(
            np.nonzero(self.mask(query, loose=loose, auths=auths))[0]
        )

    def warmup_plan(
        self,
        k: int = 10,
        density_px: int = 256,
        knn_kmax: "int | None" = None,
        fusion_max: "int | None" = None,
    ) -> "list[tuple[str, object]]":
        """The AOT warmup plan: ``(signature, thunk)`` legs covering the
        bucket x kernel-family set this index can serve — the closed
        enumeration :mod:`geomesa_tpu.warmup` pre-compiles at server
        start. Base legs exercise the scan/agg families at two window
        scales (the common zrange R-buckets) plus mask, window-union,
        window-pairs, density and stats; when ``knn_kmax`` is given the
        kNN ``k`` compile ladder (:func:`geomesa_tpu.bucketing.ladder`)
        gets one leg per rung up to it, and ``fusion_max`` adds one
        fused micro-batch leg per width rung (count + query variants).
        Signatures are bounded leg names prefixed by their
        ``ledger.SCOPE_FAMILIES`` family where one applies; a thunk of
        ``None`` in the returned list never occurs — unavailable legs
        (non-point schema, empty staging) are simply not planned."""
        from geomesa_tpu.filter import ast as _ast

        legs: list = []
        geom = self.sft.geom_field
        if geom is None or self._staged_len() == 0:
            return legs
        # a data-adjacent center makes the warm queries realistic, but
        # any coordinates compile the same kernels: points use their
        # coordinate planes, non-point schemas their envelope planes,
        # and a schema with neither staged still warms at (0, 0)
        gx, gy = f"{geom}__x", f"{geom}__y"
        is_point = gx in (self._cols or {})
        if is_point:
            cx = float(np.asarray(self._cols[gx][:1])[0])
            cy = float(np.asarray(self._cols[gy][:1])[0])
        elif f"{geom}__x0" in (self._cols or {}):
            cx = float(np.asarray(self._cols[f"{geom}__x0"][:1])[0])
            cy = float(np.asarray(self._cols[f"{geom}__y0"][:1])[0])
        else:
            cx = cy = 0.0
        dtg = self.sft.dtg_field

        def bbox(half):
            f = _ast.BBox(geom, cx - half, cy - half, cx + half, cy + half)
            if dtg is not None:
                col = self._host_rows().columns.get(dtg)
                if col is not None and len(col):
                    ms = np.asarray(col).astype("datetime64[ms]")
                    t0, t1 = int(ms.min().astype(np.int64)), int(
                        ms.max().astype(np.int64)
                    )
                    f = _ast.And([f, _ast.During(dtg, t0, t1)])
            return f

        # two window scales exercise the common zrange R-buckets of the
        # loose kernels plus the exact compiled scan
        for name, half in (("city", 0.05), ("country", 5.0)):
            q = bbox(half)
            legs.append((f"count_loose_{name}",
                         lambda q=q: self.count(q, loose=True)))
            legs.append((f"count_exact_{name}",
                         lambda q=q: self.count(q, loose=False)))
        legs.append(("mask", lambda: self.mask(bbox(1.0))))
        if is_point:  # kNN/density scan the point coordinate planes
            legs.append(("knn", lambda: self.knn(cx, cy, k)))
            if knn_kmax is not None:
                # one leg per k-bucket rung: k requests in (prev, rung]
                # all dispatch the rung's executable (satellite: k=7 and
                # k=8 share one compile), so warming the rungs closes
                # the kNN compile space up to kmax
                from geomesa_tpu.bucketing import ladder as _ladder

                for kk in _ladder(min(int(knn_kmax),
                                      max(self._staged_len(), 1))):
                    legs.append((f"knn:k={kk}",
                                 lambda kk=kk: self.knn(cx, cy, kk)))
        env1 = np.array(
            [[cx - 0.5, cy - 0.5, cx + 0.5, cy + 0.5]], np.float64
        )
        legs.append(("window_union",
                     lambda: self.window_union_query(env1)))
        legs.append(("window_pairs",
                     lambda: self.window_pairs_query(env1)))
        from geomesa_tpu.geom import Envelope as _Env

        if is_point:
            legs.append((
                "density",
                lambda: self.density(
                    _ast.Include,
                    _Env(cx - 5, cy - 5, cx + 5, cy + 5),
                    density_px,
                    density_px,
                ),
            ))
        legs.append(("stats", lambda: self.stats(_ast.Include, "Count()")))
        if fusion_max is not None:
            # the fused micro-batch Q-capacity ladder (fused.dim /
            # fused.cmp families): one leg per width rung up to the
            # scheduler's max fusion, count + row-demux variants
            from geomesa_tpu.bucketing import ladder as _ladder

            q = bbox(0.05)
            for w in _ladder(max(int(fusion_max), 1)):
                legs.append((
                    f"fused_counts:q={w}",
                    lambda q=q, w=w: self.fused_loose_counts([q] * w),
                ))
                legs.append((
                    f"fused_query:q={w}",
                    lambda q=q, w=w: self.fused_loose_query([q] * w),
                ))
        return legs

    def warmup(self, k: int = 10, density_px: int = 256) -> dict:
        """Pre-compile the hot serving kernels (loose + exact scans at
        city/country window scales, kNN, window-union, density, stats)
        so the first real request never pays an XLA compile — the
        explicit warmup entry for ``serve --resident`` (ref: the
        reference's serving path has no compile step to hide; ours does,
        ~14s for the fused top_k alone on a cold process). Combined with
        the persistent compilation cache (jaxconf.enable_compilation_
        cache) a restarted server warms from disk instead of
        recompiling. Returns {leg: seconds} (None = leg unavailable for
        this schema / staging, e.g. non-point geometry for kNN).

        This synchronous entry runs the base :meth:`warmup_plan` legs
        inline; the server's background AOT pass
        (:mod:`geomesa_tpu.warmup`) runs the FULL plan (kNN k-ladder,
        fused width ladder) in a bounded pool under the ``_system``
        ledger tenant instead."""
        import time as _time
        import warnings

        out: dict = {}
        legs = self.warmup_plan(k=k, density_px=density_px)
        for name, fn in legs:
            t0 = _time.perf_counter()
            try:
                fn()
                out[name] = round(_time.perf_counter() - t0, 3)
            except Exception as e:  # warmup must never break serving
                warnings.warn(f"warmup leg {name!r} failed: {e!r}")
                out[name] = None
        if "knn" not in out:
            out["knn"] = None  # non-point schema: leg unavailable
        if "density" not in out:
            out["density"] = None
        return out

    @_snapshot
    def window_union_query(self, envs, times=None, auths=None, base=None):
        """Candidate rows matching ANY of m runtime windows in ONE
        dispatch — the corridor/buffer coarse pass (tube select: one
        bbox+time window per track segment; proximity: one expanded bbox
        per input geometry). Issuing them as separate queries would pay a
        per-window kernel compile AND a per-window dispatch; here the
        windows are runtime arrays (padded to a power of two, so one
        compiled kernel serves any track length) broadcast against the
        resident planes.

        ``envs``: (m, 4) [xmin, ymin, xmax, ymax]; ``times``: optional
        (m, 2) int64 [t_lo, t_hi] epoch-ms tested against the default
        date field's hi/lo planes. ``base``: an optional extra filter
        whose compiled device mask is ANDed into the union inside the
        SAME dispatch (one compile per distinct base; the windows stay
        runtime) — a corridor query with a CQL base filter must not fall
        back to the per-segment store path.
        Returns matching host rows, or None when the needed planes (or a
        device-expressible base) are not resident. Bounds widen one ulp
        outward (float32 residency can only over-include — candidate
        semantics; callers run an exact refinement pass)."""
        import jax
        import jax.numpy as jnp

        geom = self.sft.geom_field
        gx, gy = f"{geom}__x", f"{geom}__y"
        if geom is None or gx not in self._cols:
            return None
        dtg = self.sft.dtg_field
        thi = tlo = None
        if times is not None:
            thi, tlo = f"{dtg}__hi", f"{dtg}__lo"
            if dtg is None or thi not in self._cols:
                return None
        compiled = None
        base_f = self._parse(base) if base is not None else None
        if base_f is ast.Include:
            base_f = None
        if base_f is not None:
            compiled, cfn, _, _ = self._compiled_for(base_f)
            if (
                not compiled.device_cols
                or not compiled.fully_on_device
                or cfn is None  # wanted planes not resident
            ):
                return None  # base not fusable: store path instead
        envs = np.asarray(envs, np.float64).reshape(-1, 4)
        m = envs.shape[0]
        cap = _next_pow2(max(m, 1))
        dt = np.dtype(self._cols[gx].dtype)
        env_pad = np.empty((cap, 4), dt)
        env_pad[:m, 0] = np.nextafter(envs[:, 0].astype(dt), dt.type(-np.inf))
        env_pad[:m, 1] = np.nextafter(envs[:, 1].astype(dt), dt.type(-np.inf))
        env_pad[:m, 2] = np.nextafter(envs[:, 2].astype(dt), dt.type(np.inf))
        env_pad[:m, 3] = np.nextafter(envs[:, 3].astype(dt), dt.type(np.inf))
        env_pad[m:] = [1.0, 1.0, 0.0, 0.0]  # inverted: matches nothing
        targs = ()
        if times is not None:
            times = np.asarray(times, np.int64).reshape(-1, 2)
            tp = np.zeros((cap, 2), np.int64)
            tp[:m] = times
            tp[m:] = [1, 0]  # inverted window
            # int64 bounds as hi/lo uint32 lane pairs (TPU-safe)
            targs = (
                jnp.asarray((tp >> 32).astype(np.int32)),
                jnp.asarray((tp & 0xFFFFFFFF).astype(np.uint32)),
            )
        use_time = times is not None
        has_vis = VIS_ID in self._cols
        jit_key = (
            "union", use_time, has_vis,
            repr(base_f) if compiled is not None else None,
        )
        if not hasattr(self, "_union_jits"):
            self._union_jits = {}
        fn = self._union_jits.get(jit_key)
        _note_jit_cache(fn is not None)
        if fn is None:
            def umask(cols, env, tb, valid, auth_tab):
                x = cols[gx][:, None]
                y = cols[gy][:, None]
                hit = (
                    (x >= env[None, :, 0])
                    & (x <= env[None, :, 2])
                    & (y >= env[None, :, 1])
                    & (y <= env[None, :, 3])
                )
                if tb is not None:
                    from geomesa_tpu.ops.int64lanes import cmp_lanes_jax

                    bh, bl = tb
                    vh = cols[thi][:, None]
                    vl = cols[tlo][:, None]
                    ge = cmp_lanes_jax(
                        ">=", vh, vl, bh[None, :, 0], bl[None, :, 0]
                    )
                    le = cmp_lanes_jax(
                        "<=", vh, vl, bh[None, :, 1], bl[None, :, 1]
                    )
                    hit = hit & ge & le
                mask = jnp.any(hit, axis=1)
                if compiled is not None:
                    mask = mask & compiled.device_fn(cols)
                if valid is not None:
                    mask = mask & valid
                if auth_tab is not None:
                    mask = mask & auth_tab[cols[VIS_ID]]
                return mask

            fn = jax.jit(umask)
            self._union_jits[jit_key] = fn
        sub = {gx: self._cols[gx], gy: self._cols[gy]}
        if use_time:
            sub[thi] = self._cols[thi]
            sub[tlo] = self._cols[tlo]
        if compiled is not None:
            for c in compiled.device_cols:
                sub[c] = self._cols[c]
        if has_vis:
            sub[VIS_ID] = self._cols[VIS_ID]
        from geomesa_tpu import ledger

        # window cap and base filter are the only compile dims (windows
        # themselves are runtime arrays): the union scan is a resident
        # per-filter kernel, so it compiles under the cache.scan family
        with ledger.compile_scope("cache.scan"):
            mask = np.asarray(
                fn(
                    sub,
                    jnp.asarray(env_pad),
                    targs if use_time else None,
                    self._device_valid(),
                    self._auth_table(auths) if has_vis else None,
                )
            )[: self._staged_len()]
        return self._host_rows().take(np.nonzero(mask)[0])

    @_snapshot
    def knn(
        self,
        px: float,
        py: float,
        k: int,
        query=None,
        auths=None,
        max_radius_deg: float = 45.0,
    ):
        """k nearest neighbors in ONE device dispatch: lat-corrected
        squared distance + optional filter/validity/auth mask +
        ``jax.lax.top_k`` over the resident coordinate planes — the
        TPU-native re-design of the reference's expanding-window KNNQuery
        (a fully resident columnar cache never needs to probe windows).

        Returns (batch, distances_deg) nearest-first, or None when the
        planes or the filter are not device-resident (callers fall back
        to the expanding-window store search). Matches the window search's
        contract: candidates outside the ``max_radius_deg`` box around
        the target are excluded, fewer than k rows yield fewer results,
        and ties at equal distance prefer the earlier row.
        """
        import jax
        import jax.numpy as jnp

        geom = self.sft.geom_field
        gx, gy = f"{geom}__x", f"{geom}__y"
        if geom is None or gx not in self._cols:
            return None
        # parse once; Include normalizes to no-filter so both spellings
        # share one compiled kernel
        f = self._parse(query) if query is not None else None
        if f is ast.Include:
            f = None
        compiled = None
        if f is not None:
            compiled, cfn, _, _ = self._compiled_for(f)
            if (
                not compiled.device_cols
                or not compiled.fully_on_device
                or cfn is None  # wanted planes not resident (columns=)
            ):
                return None  # cannot fuse: window path instead
        n_staged = self._staged_len()
        if n_staged == 0:
            empty = self._host_rows().take(np.array([], np.int64))
            return empty, np.array([], np.float64)
        # top_k length: power-of-two bucket bounds recompiles across k;
        # clamped to the plane length (top_k requires k <= n)
        plane_n = int(self._cols[gx].shape[0])
        kk = min(_next_pow2(max(k, 1)), plane_n)
        has_vis = VIS_ID in self._cols
        key = ("knn", repr(f) if f is not None else None, kk, has_vis)
        if not hasattr(self, "_knn_jits"):
            self._knn_jits = {}
        fn = self._knn_jits.get(key)
        _note_jit_cache(fn is not None)
        if fn is None:

            def fused(cols, q, valid, auth_tab):
                x, y = cols[gx], cols[gy]
                dx = (x - q[0]) * jnp.cos(jnp.radians(q[1]))
                dy = y - q[1]
                d2 = dx * dx + dy * dy
                m = (jnp.abs(x - q[0]) <= q[2]) & (jnp.abs(y - q[1]) <= q[2])
                if compiled is not None:
                    m = m & compiled.device_fn(cols)
                if valid is not None:
                    m = m & valid
                if auth_tab is not None:
                    m = m & auth_tab[cols[VIS_ID]]
                d2 = jnp.where(m, d2, jnp.float32(jnp.inf))
                # top_k on the negated key: equal values prefer the lower
                # index — the same tie rule as the host stable argsort
                neg, idx = jax.lax.top_k(-d2, kk)
                return -neg, idx

            fn = jax.jit(fused)
            self._knn_jits[key] = fn
        q = jnp.asarray(
            np.array([px, py, max_radius_deg], np.float32)
        )
        wanted = [gx, gy] + ([VIS_ID] if has_vis else [])
        if compiled is not None:
            wanted += [c for c in compiled.device_cols if c not in wanted]
        sub = {c: self._cols[c] for c in wanted}
        from geomesa_tpu import ledger

        # compile attribution: a cold kNN kernel is THE headline compile
        # cliff (ROADMAP item 4) — tag it so the compile ledger can say
        # which k-bucket ate whose deadline (kk is pow2: bounded sigs)
        with ledger.compile_scope(f"knn:k={kk}:filtered={f is not None}"):
            d2, idx = fn(
                sub, q, self._device_valid(),
                self._auth_table(auths) if has_vis else None,
            )
        d2 = np.asarray(d2)
        idx = np.asarray(idx)
        ok = np.isfinite(d2)
        # drop the pow2 padding and any beyond-k ties the bucket admitted
        idx, d2 = idx[ok][:k], d2[ok][:k]
        return self._host_rows().take(idx), np.sqrt(d2.astype(np.float64))

    @_snapshot
    def window_pairs_query(self, envs, auths=None, base=None):
        """Candidate (row, window) PAIRS for m runtime envelope windows —
        the device coarse pass of a spatial JOIN (each right-side feature
        contributes one envelope; the exact predicate refines per pair on
        host). Where :meth:`window_union_query` collapses the window axis
        with ``any``, this keeps it: windows are processed in groups of
        64 with the per-row hit vector BIT-PACKED into two uint32 planes,
        so each group's dispatch fetches 8B/row regardless of m.

        ``envs``: (m, 4) [xmin, ymin, xmax, ymax]; ``base``: optional
        extra filter fused on device (same contract as
        window_union_query). Returns (rows, wins) int64 arrays (aligned;
        candidate semantics — envelopes widen one ulp) or None when the
        needed planes / base are not resident."""
        import jax
        import jax.numpy as jnp

        geom = self.sft.geom_field
        gx, gy = f"{geom}__x", f"{geom}__y"
        if geom is None or gx not in self._cols:
            return None
        compiled = None
        base_f = self._parse(base) if base is not None else None
        if base_f is ast.Include:
            base_f = None
        if base_f is not None:
            compiled, cfn, _, _ = self._compiled_for(base_f)
            if (
                not compiled.device_cols
                or not compiled.fully_on_device
                or cfn is None
            ):
                return None
        envs = np.asarray(envs, np.float64).reshape(-1, 4)
        m = envs.shape[0]
        dt = np.dtype(self._cols[gx].dtype)
        has_vis = VIS_ID in self._cols
        n_staged = self._staged_len()
        plane_n = int(self._cols[gx].shape[0])
        # chain G 64-window groups per dispatch (lax.scan over the group
        # axis) and COMPACT each group's hits on device (stable sort by
        # has-hits flag, slice the top C rows): |R|=10k right rows
        # previously cost ceil(10k/64)=157 sequential dispatches, each
        # fetching a FULL 8B/row bit-plane — 1.3GB of D2H for a few
        # million pairs. The compacted fetch is C-BOUNDED per group
        # (G x C x 12B per dispatch, C >= 4096 — vs 8B x n per group
        # before: ~32x less at plane_n=2^20); a group whose candidates
        # overflow C falls back to its full bit-plane fetch, loudly
        # correct.
        ngroups = max(1, -(-m // 64))
        G = min(self.PAIRS_GROUPS_PER_DISPATCH, _next_pow2(ngroups))
        C = min(plane_n, max(4096, _next_pow2(plane_n // 32)))
        jit_key = (
            "pairs", has_vis, repr(base_f) if compiled else None, G, C
        )
        if not hasattr(self, "_union_jits"):
            self._union_jits = {}
        fn = self._union_jits.get(jit_key)
        _note_jit_cache(fn is not None)
        if fn is None:

            def packed(cols, envs3, valid, auth_tab):
                # the per-row gate (base filter, validity, auths) is
                # window-independent: compute it ONCE, not per group
                row_ok = None
                if compiled is not None:
                    row_ok = compiled.device_fn(cols)
                if valid is not None:
                    row_ok = valid if row_ok is None else (row_ok & valid)
                if auth_tab is not None:
                    av = auth_tab[cols[VIS_ID]]
                    row_ok = av if row_ok is None else (row_ok & av)
                x = cols[gx][:, None]
                y = cols[gy][:, None]
                w = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
                rid = jnp.arange(x.shape[0], dtype=jnp.uint32)

                def body(carry, env):  # env: (64, 4)
                    hit = (
                        (x >= env[None, :, 0])
                        & (x <= env[None, :, 2])
                        & (y >= env[None, :, 1])
                        & (y <= env[None, :, 3])
                    )  # (n, 64)
                    if row_ok is not None:
                        hit = hit & row_ok[:, None]
                    lo = (hit[:, :32].astype(jnp.uint32) * w[None, :]).sum(
                        axis=1, dtype=jnp.uint32
                    )
                    hi = (hit[:, 32:].astype(jnp.uint32) * w[None, :]).sum(
                        axis=1, dtype=jnp.uint32
                    )
                    # device compaction: hits-first stable order, top C
                    flag = (lo | hi) != 0
                    cnt = flag.sum(dtype=jnp.uint32)
                    key = (~flag).astype(jnp.uint32)
                    _, rid_s, lo_s, hi_s = jax.lax.sort(
                        (key, rid, lo, hi), num_keys=2
                    )
                    return carry, (
                        rid_s[:C], lo_s[:C], hi_s[:C], cnt
                    )

                _, outs = jax.lax.scan(body, None, envs3)
                return outs  # (G, C) x3 + (G,) counts

            fn = jax.jit(packed)
            self._union_jits[jit_key] = fn
        sub = {gx: self._cols[gx], gy: self._cols[gy]}
        if compiled is not None:
            for c in compiled.device_cols:
                sub[c] = self._cols[c]
        if has_vis:
            sub[VIS_ID] = self._cols[VIS_ID]
        rows_out: list = []
        wins_out: list = []

        from geomesa_tpu import metrics
        from geomesa_tpu.tracing import span as _span

        with _span("join.pairs", windows=m, groups=ngroups) as sp:
            overflows = self._pairs_dispatch(
                envs, m, n_staged, dt, G, C, fn, sub, has_vis,
                compiled, base_f, auths, rows_out, wins_out,
            )
            sp.set(overflows=overflows)
        if overflows:
            # the compaction-cap overflow relaunch is the expensive rare
            # path: counted, and stamped on the span so the ledger's
            # trace-derived costs attribute the extra full-plane fetches
            metrics.join_pair_overflows.inc(overflows)
        if not rows_out:
            e = np.array([], np.int64)
            return e, e.copy()
        return np.concatenate(rows_out), np.concatenate(wins_out)

    def _pairs_dispatch(self, envs, m, n_staged, dt, G, C, fn, sub,
                        has_vis, compiled, base_f, auths, rows_out,
                        wins_out):
        """window_pairs_query's dispatch loop (one lax.scan launch per
        G-group chunk, device-compacted fetches, full bit-plane refetch
        for groups past the cap). Returns the overflow-relaunch count."""
        import jax.numpy as jnp

        overflows = 0
        wspan = 64 * G

        def decode(rids, los, his, g0):
            """(candidate rows, their bit words) -> aligned pair lists."""
            bits = (
                (np.stack([los, his], axis=1)[:, :, None]
                 >> np.arange(32, dtype=np.uint32)) & 1
            ).astype(bool).reshape(len(rids), 64)  # (c, 64) win bits
            r, w = np.nonzero(bits)
            keep = (w + g0 < m) & (rids[r] < n_staged)
            rows_out.append(rids[r[keep]].astype(np.int64))
            wins_out.append((w[keep] + g0).astype(np.int64))

        for c0 in range(0, max(m, 1), wspan):
            chunk = envs[c0 : c0 + wspan]
            k = len(chunk)
            env_pad = np.empty((wspan, 4), dt)
            env_pad[:k, 0] = np.nextafter(
                chunk[:, 0].astype(dt), dt.type(-np.inf)
            )
            env_pad[:k, 1] = np.nextafter(
                chunk[:, 1].astype(dt), dt.type(-np.inf)
            )
            env_pad[:k, 2] = np.nextafter(
                chunk[:, 2].astype(dt), dt.type(np.inf)
            )
            env_pad[:k, 3] = np.nextafter(
                chunk[:, 3].astype(dt), dt.type(np.inf)
            )
            env_pad[k:] = [1.0, 1.0, 0.0, 0.0]  # inverted: no matches
            rid_c, lo_c, hi_c, cnts = fn(
                sub, jnp.asarray(env_pad.reshape(G, 64, 4)),
                self._device_valid(),
                self._auth_table(auths) if has_vis else None,
            )
            cnts = np.asarray(cnts)
            rid_c = np.asarray(rid_c)
            lo_c = np.asarray(lo_c)
            hi_c = np.asarray(hi_c)
            for g in range(G):
                g0 = c0 + g * 64
                if g0 >= m:
                    break
                cnt = int(cnts[g])
                if cnt == 0:
                    continue
                if cnt <= C:
                    decode(rid_c[g, :cnt], lo_c[g, :cnt], hi_c[g, :cnt], g0)
                else:
                    # dense group: the compaction cap overflowed — refetch
                    # this group's full bit-planes (correct, just bigger)
                    overflows += 1
                    lo_f, hi_f = self._pairs_full_group(
                        sub, env_pad[g * 64 : (g + 1) * 64], has_vis,
                        compiled, base_f, auths,
                    )
                    nz = np.nonzero(lo_f | hi_f)[0]
                    decode(nz.astype(np.uint32), lo_f[nz], hi_f[nz], g0)
        return overflows

    def _pairs_full_group(self, sub, env64, has_vis, compiled, base_f,
                          auths):
        """Full (uncompacted) bit-planes for ONE dense 64-window group —
        the overflow fallback of window_pairs_query."""
        import jax
        import jax.numpy as jnp

        geom = self.sft.geom_field
        gx, gy = f"{geom}__x", f"{geom}__y"
        jit_key = ("pairs_full", has_vis, repr(base_f) if compiled else None)
        fn = self._union_jits.get(jit_key)
        if fn is None:

            def packed_full(cols, env, valid, auth_tab):
                x = cols[gx][:, None]
                y = cols[gy][:, None]
                hit = (
                    (x >= env[None, :, 0])
                    & (x <= env[None, :, 2])
                    & (y >= env[None, :, 1])
                    & (y <= env[None, :, 3])
                )
                row_ok = None
                if compiled is not None:
                    row_ok = compiled.device_fn(cols)
                if valid is not None:
                    row_ok = valid if row_ok is None else (row_ok & valid)
                if auth_tab is not None:
                    av = auth_tab[cols[VIS_ID]]
                    row_ok = av if row_ok is None else (row_ok & av)
                if row_ok is not None:
                    hit = hit & row_ok[:, None]
                w = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
                lo = (hit[:, :32].astype(jnp.uint32) * w[None, :]).sum(
                    axis=1, dtype=jnp.uint32
                )
                hi = (hit[:, 32:].astype(jnp.uint32) * w[None, :]).sum(
                    axis=1, dtype=jnp.uint32
                )
                return lo, hi

            fn = jax.jit(packed_full)
            self._union_jits[jit_key] = fn
        lo, hi = fn(
            sub, jnp.asarray(env64), self._device_valid(),
            self._auth_table(auths) if has_vis else None,
        )
        return np.asarray(lo), np.asarray(hi)

    def bbox_window_query(self, xmin, ymin, xmax, ymax, auths=None):
        """Bbox query with RUNTIME bounds: one compiled kernel serves
        every window, where query()'s per-filter compile-and-cache would
        pay a recompile per distinct bbox — the expanding-window search
        pattern (kNN) probes dozens of bboxes per call. The m=1 case of
        :meth:`window_union_query` (same kernel, widening, validity and
        auth plumbing)."""
        return self.window_union_query(
            np.array([[xmin, ymin, xmax, ymax]], np.float64), auths=auths
        )

    # -- pushdown stats (StatsIterator analog) -----------------------------

    @_snapshot
    def stats(
        self, query, spec: str, loose: "bool | None" = None, auths=None
    ):
        """Stat-DSL aggregation fused with the filter scan in ONE device
        dispatch (ref StatsIterator: stats computed server-side during
        the scan, never shipping features). Count, MinMax over resident
        numeric/date planes, and fixed-bin Histogram over resident
        float/int planes reduce on device; any other stat (strings, HLL,
        TopK, Z3Histogram) observes the masked host rows instead. Filters
        that are not fully device-expressible fall back to host
        observation entirely.

        Precision: MinMax over a float64 attribute reflects the device
        STORAGE format — float32 on TPU (README design stance), float64
        on the CPU test platform. Date (int64) MinMax is always exact via
        lexicographic hi/lo reduction."""
        from geomesa_tpu.stats import parse_stat
        from geomesa_tpu.stats.sketches import CountStat, Histogram, MinMax

        seq = parse_stat(spec)
        f = self._parse(query)

        device_parts, host_parts = [], []
        for s in seq.stats:
            if isinstance(s, CountStat):
                device_parts.append(("count", s))
            elif isinstance(s, MinMax) and (
                s.attr in self._cols or f"{s.attr}__hi" in self._cols
            ):
                device_parts.append(("minmax", s))
            elif (
                isinstance(s, Histogram)
                and s.attr in self._cols
                and self._cols[s.attr].dtype.kind in "fiu"
            ):
                device_parts.append(("hist", s))
            else:
                host_parts.append(s)

        if self._staged_len() == 0:
            return seq  # nothing staged: zero-size reductions have no identity
        outs = self._stats_fused(
            f, loose, device_parts, need_mask=bool(host_parts), auths=auths
        )
        if outs is None:  # filter not fully device-expressible
            seq.observe_batch(self.query(f, loose=loose, auths=auths))
            return seq
        n_hits = _fetch(int, outs["__count"])
        for i, (tag, s) in enumerate(device_parts):
            if tag == "count":
                s.count += n_hits
            elif tag == "minmax" and n_hits:
                s.count += n_hits
                if f"{s.attr}__hi" in self._cols:
                    mn = (int(outs[f"{i}__mnhi"]) << 32) | int(
                        outs[f"{i}__mnlo"]
                    )
                    mx = (int(outs[f"{i}__mxhi"]) << 32) | int(
                        outs[f"{i}__mxlo"]
                    )
                else:
                    mn = outs[f"{i}__mn"].item()
                    mx = outs[f"{i}__mx"].item()
                s.min = mn if s.min is None else min(s.min, mn)
                s.max = mx if s.max is None else max(s.max, mx)
            elif tag == "hist":
                s.counts += np.asarray(outs[f"{i}__hist"]).astype(np.int64)
        if host_parts:
            # the fused dispatch already evaluated the filter: reuse its
            # mask instead of paying a second full scan
            hm = np.asarray(outs["__mask"])[: self._staged_len()]
            rows = self._host_rows().take(np.nonzero(hm)[0])
            from geomesa_tpu.stats.dsl import _observe_on_batch

            for s in host_parts:
                _observe_on_batch(s, rows)
        return seq

    def _fused_agg(self, f, loose, agg_key, agg_build, extra=(), auths=None):
        """The pushdown-aggregation hook: ONE device dispatch computing
        the filter mask (exact compiled predicate, or the loose key-plane
        compare) fused with an arbitrary aggregation over the resident
        columns — the generalized form of the reference's server-side
        iterators (StatsIterator / DensityIterator / BinAggregating-
        Iterator all aggregate next to the data without shipping
        features). ``agg_build(cols, mask) -> dict of outputs`` runs
        inside the jit; the compiled dispatch is cached per
        (filter, kind, agg_key). ``extra`` is a tuple of RUNTIME device
        arrays forwarded to ``agg_build(cols, mask, *extra)`` — values
        that vary per call (e.g. a density viewport) belong there, not in
        the closure/cache key, or every distinct value pays a recompile.
        Returns the outputs dict, or None when the filter is not fully
        device-expressible (caller falls back to a host path)."""
        import jax

        from geomesa_tpu.failpoints import fail_point

        fail_point("fail.device.launch")  # chaos: fused-agg launch
        kind = None
        lb = None
        if self._resolve_loose(loose):
            lb = self._loose_bounds(f)
            if lb is not None:
                kind = "loose"
        compiled = None
        if kind is None and f is ast.Include and self._cols:
            # no filter: constant-true mask (full-viewport density /
            # whole-type stats must not fall back to the store path)
            kind = "include"
        if kind is None:
            compiled, cfn, _, _ = self._compiled_for(f)
            if compiled.device_cols and compiled.fully_on_device and cfn:
                kind = "exact"
            else:
                return None
        if not hasattr(self, "_agg_cache"):
            self._agg_cache = {}
        has_vis = VIS_ID in self._cols
        dim_loose = kind == "loose" and len(lb) == 3 and lb[0] == "dim"
        # the dim qarr is a RUNTIME arg, but its R bucket is a trace shape:
        # one compiled dispatch per (filter, kind, R) serves every window
        key = (repr(f), kind, agg_key, has_vis,
               lb[2] if dim_loose else None)
        cached = self._agg_cache.get(key)
        _note_jit_cache(cached is not None)
        if cached is None:
            z_kind = self._z_kind
            n_ranges = lb[2] if dim_loose else 0

            def fused(cols, mask_args, valid, extra_args, auth_tab):
                if kind == "include":
                    import jax.numpy as jnp

                    m = jnp.ones(
                        next(iter(cols.values())).shape[0], bool
                    )
                elif dim_loose:
                    from geomesa_tpu.ops import zscan

                    if n_ranges == 0:  # unbinned z2: 2-plane mask
                        m = zscan.z2_dimscan_mask_rt(
                            cols[Z_NX], cols[Z_NY], mask_args
                        )
                    else:
                        m = zscan.z3_dimscan_mask_rt(
                            cols[Z_NX], cols[Z_NY], cols[Z_BT],
                            mask_args, n_ranges,
                        )
                elif kind == "loose":
                    from geomesa_tpu.ops import zscan

                    loose_fn = zscan.kind_mask_fn(z_kind)
                    bounds, ids = mask_args
                    if ids is None:
                        m = loose_fn(cols[Z_HI], cols[Z_LO], bounds)
                    else:
                        m = loose_fn(
                            cols[Z_HI], cols[Z_LO], cols[Z_BIN], bounds, ids
                        )
                else:
                    m = compiled.device_fn(cols)
                if valid is not None:
                    m = m & valid
                if auth_tab is not None:
                    # per-request row security: gather the auth verdict
                    # by label id (Accumulo cell visibility, on device)
                    m = m & auth_tab[cols[VIS_ID]]
                return agg_build(cols, m, *extra_args)

            cached = jax.jit(fused)
            self._agg_cache[key] = cached
        from geomesa_tpu import ledger

        # agg_key may nest stat-spec tuples: keep only its plain-string
        # tags so the compile signature stays a short bounded token
        agg_tag = "+".join(
            a for a in agg_key if isinstance(a, str)
        ) or "stats"
        with ledger.compile_scope(f"fused.agg:{kind}:{agg_tag}"):
            return cached(
                self._cols,
                (lb[1] if dim_loose else lb) if kind == "loose" else None,
                self._device_valid(),
                extra,
                self._auth_table(auths) if has_vis else None,
            )

    def _stats_fused(self, f, loose, device_parts, need_mask, auths=None):
        """Stat-DSL reductions on the pushdown hook: mask + every device
        reduction in one dispatch (None = caller falls back to host)."""
        import jax
        import jax.numpy as jnp

        parts_spec = tuple(
            (tag, s.attr if hasattr(s, "attr") else "",
             getattr(s, "bins", 0), getattr(s, "lo", 0.0),
             getattr(s, "hi", 0.0))
            for tag, s in device_parts
        )

        def agg_build(cols, m):
            out = {"__count": jnp.sum(m, dtype=jnp.int32)}
            if need_mask:
                out["__mask"] = m
            # outputs keyed by PART INDEX: two stats over the same
            # attribute (e.g. histograms with different bin params)
            # must not collide on one output slot
            for i, (tag, attr, bins, lo, hi) in enumerate(parts_spec):
                if tag == "minmax" and f"{attr}__hi" in cols:
                    vhi, vlo = cols[f"{attr}__hi"], cols[f"{attr}__lo"]
                    i32mx, i32mn = jnp.int32(2**31 - 1), jnp.int32(-(2**31))
                    mnhi = jnp.min(jnp.where(m, vhi, i32mx))
                    mxhi = jnp.max(jnp.where(m, vhi, i32mn))
                    u32mx = jnp.uint32(0xFFFFFFFF)
                    mnlo = jnp.min(
                        jnp.where(m & (vhi == mnhi), vlo, u32mx)
                    )
                    mxlo = jnp.max(
                        jnp.where(m & (vhi == mxhi), vlo, jnp.uint32(0))
                    )
                    out[f"{i}__mnhi"] = mnhi
                    out[f"{i}__mnlo"] = mnlo
                    out[f"{i}__mxhi"] = mxhi
                    out[f"{i}__mxlo"] = mxlo
                elif tag == "minmax":
                    v = cols[attr]
                    big = (
                        jnp.inf
                        if v.dtype.kind == "f"
                        else jnp.iinfo(v.dtype).max
                    )
                    small = (
                        -jnp.inf
                        if v.dtype.kind == "f"
                        else jnp.iinfo(v.dtype).min
                    )
                    out[f"{i}__mn"] = jnp.min(jnp.where(m, v, big))
                    out[f"{i}__mx"] = jnp.max(jnp.where(m, v, small))
                elif tag == "hist":
                    # bin in the widest float available so the edges
                    # match the host Histogram.bin_of (float64 under
                    # x64/CPU; float32 is the TPU storage precision)
                    wide = (
                        jnp.float64
                        if jax.config.jax_enable_x64
                        else jnp.float32
                    )
                    v = cols[attr].astype(wide)
                    scale = bins / (hi - lo) if hi > lo else 0.0
                    idx = jnp.clip(
                        jnp.floor((v - lo) * scale).astype(jnp.int32),
                        0,
                        bins - 1,
                    )
                    out[f"{i}__hist"] = (
                        jnp.zeros(bins, jnp.int32)
                        .at[idx]
                        .add(m.astype(jnp.int32))
                    )
            return out

        part_key = ("stats", parts_spec, need_mask)
        return self._fused_agg(f, loose, part_key, agg_build, auths=auths)

    # -- pushdown density + BIN (Density/BinAggregating iterator analogs) --

    @_snapshot
    def density(
        self,
        query,
        envelope,
        width: int,
        height: int,
        weight_attr: "str | None" = None,
        loose: "bool | None" = None,
        auths=None,
    ) -> "np.ndarray | None":
        """Fused density rasterization: filter mask + pixel binning in
        ONE device dispatch — no feature batch is ever materialized (ref
        DensityIterator aggregates next to the data). Returns a
        (height, width) float32 grid, or None when the filter or the
        needed planes are not device-resident (caller falls back to the
        store path).

        Engine: the Pallas one-hot-matmul kernel (ops/density_pallas —
        10x the XLA scatter on v5e) for grids up to 512x512 on a single
        device; larger grids, and mesh-sharded planes, keep the scatter
        (the kernel's VMEM-resident accumulator and one-hot width scale
        with the grid axes, and XLA cannot partition a Mosaic call)."""
        import jax.numpy as jnp

        from geomesa_tpu.process.density import _pixel_ids

        geom = self.sft.geom_field
        gx, gy = f"{geom}__x", f"{geom}__y"
        if gx not in self._cols or gy not in self._cols:
            return None  # non-point (or unstaged) geometry: host path
        if weight_attr is not None and weight_attr not in self._cols:
            return None
        f = self._parse(query)

        kern = None
        if max(width, height) <= 512 and self._pallas_tiles:
            from geomesa_tpu.ops.density_pallas import build_density_pallas

            if not hasattr(self, "_density_kernels"):
                self._density_kernels = {}
            kkey = (width, height, weight_attr is not None)
            kern = self._density_kernels.get(kkey)
            _note_jit_cache(kern is not None)
            if kern is None:
                kern = build_density_pallas(
                    width, height, weight_attr is not None
                )
                self._density_kernels[kkey] = kern

        # scatter engine (grids past the Pallas tile bound): the canvas
        # CAPACITY buckets onto the compile ladder and width/height ride
        # as runtime scalars, so one compiled scatter serves every grid
        # size in the bucket — pixel ids are computed from the runtime
        # dims, cells past height*width stay zero and the host slice
        # drops them, so the grid is bit-identical to the exact-shape
        # dispatch. (The Pallas kernel keeps exact shapes: its VMEM
        # accumulator and one-hot width are compile-time tile geometry,
        # and map-tile grids are a small closed set already.)
        cap = 0 if kern is not None else _next_pow2(height * width)

        def agg_build(cols, m, env_arr, wh):
            if kern is not None:
                return {"grid": kern(
                    env_arr, cols[gx], cols[gy], m,
                    cols[weight_attr].astype(jnp.float32)
                    if weight_attr else None,
                )}
            px, py, inside = _pixel_ids(
                cols[gx], cols[gy], env_arr, wh[0], wh[1], jnp
            )
            w = (
                cols[weight_attr].astype(jnp.float32)
                if weight_attr
                else jnp.float32(1.0)
            )
            contrib = jnp.where(m & inside, w, jnp.float32(0.0))
            grid = jnp.zeros(cap, jnp.float32)
            return {"grid": grid.at[py * wh[0] + px].add(contrib)}

        from geomesa_tpu import ledger

        # the viewport is a RUNTIME argument: one compiled kernel per
        # (filter, canvas bucket) serves every bbox a panning map client
        # sends, instead of a recompile + retained cache entry per bbox.
        # The eager viewport converts compile tiny kernels of their own,
        # so they sit inside the family scope too (the launch below
        # overrides with its narrower _fused_agg signature).
        with ledger.compile_scope("fused.agg:density"):
            env_arr = jnp.asarray(
                [envelope.xmin, envelope.ymin, envelope.xmax, envelope.ymax]
            )
            wh = jnp.asarray([width, height], jnp.int32)
            agg_key = (
                ("density", width, height, weight_attr)
                if kern is not None
                else ("density", cap, weight_attr)
            )
            outs = self._fused_agg(
                f, loose, agg_key, agg_build, extra=(env_arr, wh),
                auths=auths,
            )
        if outs is None:
            return None
        grid = _fetch(np.asarray, outs["grid"])
        if kern is None:
            grid = grid[: height * width].reshape(height, width)
        return grid

    @_snapshot
    def bin_export(
        self,
        query,
        track_attr: str,
        dtg_attr: "str | None" = None,
        geom_attr: "str | None" = None,
        label_attr: "str | None" = None,
        sort: bool = False,
        loose: "bool | None" = None,
        auths=None,
    ) -> bytes:
        """BIN track records over the device hit mask without
        materializing a feature batch: only the 3-5 needed columns of
        matching rows are touched on host (ref BinAggregatingIterator
        builds the compact records server-side during the scan)."""
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.process.binexport import encode_bin_arrays

        idx = np.nonzero(self.mask(query, loose=loose, auths=auths))[0]
        host = self._host_rows()
        # O(hits) coordinate extraction: slice the geometry column FIRST,
        # then decode coords on the selected rows only
        gname = geom_attr or self.sft.geom_field
        mini = FeatureBatch(
            self.sft, host.fids[idx], {gname: host.column(gname)[idx]}
        )
        x, y = mini.point_coords(gname)
        dtg_attr = dtg_attr or self.sft.dtg_field
        return encode_bin_arrays(
            host.column(track_attr)[idx],
            host.column(dtg_attr)[idx],
            x,
            y,
            host.column(label_attr)[idx] if label_attr else None,
            sort=sort,
        )

    # -- device-side BIN rider (results/ plane) ----------------------------

    def _device_hit_mask(self, f, loose):
        """Device-RESIDENT boolean hit mask (never fetched to host), or
        None when the filter is not fully device-expressible — the host
        twin (:meth:`bin_export`) serves those shapes. Labeled stagings
        always return None: per-request auths evaluate host-side."""
        import jax.numpy as jnp

        if VIS_ID in (self._cols or {}):
            return None
        if isinstance(f, type(ast.Include)):
            # match-everything (ast.Include is a singleton instance):
            # the validity plane IS the mask
            dv = self._device_valid()
            return dv if dv is not None else jnp.ones(
                self._staged_len(), bool
            )
        if self._resolve_loose(loose):
            lb = self._loose_bounds(f)
            if lb is not None:
                m = self._z_mask_dev(lb)
                dv = self._device_valid()
                return (m & dv) if dv is not None else m
        compiled, _, mask_fn, _ = self._compiled_for(f)
        if (
            not compiled.device_cols
            or mask_fn is None
            or not compiled.fully_on_device
        ):
            return None
        return mask_fn(self._resident_subset(compiled))

    def _bin_lane_matrix(self, track_attr, dtg_attr, gname, label_attr):
        """The BIN record lanes as ONE device-resident (L, rows) uint32
        matrix: [track hash, dtg seconds, lat f32, lon f32] (+ label
        i64 as lo/hi words). Built once per staging generation (vector
        host passes, single H2D transfer — the _stage_packed transfer
        discipline) and gathered by every pack launch after that."""
        import jax.numpy as jnp

        from geomesa_tpu.process.binexport import _label_pack, _track_hash

        key = (
            track_attr, dtg_attr, gname, label_attr,
            getattr(self, "_gen", 0),
        )
        mat = self._bin_lanes.get(key)
        if mat is not None:
            return mat
        host = self._host_rows()
        col = host.column(gname)
        lanes = [
            _track_hash(np.asarray(host.column(track_attr))).view(np.uint32),
            (host.column(dtg_attr) // 1000).astype(np.int32).view(np.uint32),
            np.ascontiguousarray(col[:, 1]).astype(np.float32).view(np.uint32),
            np.ascontiguousarray(col[:, 0]).astype(np.float32).view(np.uint32),
        ]
        if label_attr:
            lab = _label_pack(np.asarray(host.column(label_attr)))
            words = lab.view(np.uint32).reshape(-1, 2)
            # little-endian i64: low word first == the record byte layout
            lanes.append(np.ascontiguousarray(words[:, 0]))
            lanes.append(np.ascontiguousarray(words[:, 1]))
        mat = jnp.asarray(np.ascontiguousarray(np.stack(lanes)))
        self._bin_lanes = {key: mat}  # latest staging only (bounds HBM)
        return mat

    @_snapshot
    def bin_rider(
        self,
        query,
        track_attr: str,
        dtg_attr: "str | None" = None,
        geom_attr: "str | None" = None,
        label_attr: "str | None" = None,
        sort: bool = False,
        loose: "bool | None" = None,
        auths=None,
    ) -> "bytes | None":
        """BIN track records packed ON DEVICE as a fused launch pair
        riding the ``_mesh_hits`` count→cap→compact discipline: the hit
        mask stays device-resident, a count launch sizes a power-of-two
        compaction cap, and one pack launch cumsum-compacts the record
        lanes into a (L, cap) uint32 buffer — only packed record bytes
        ever cross back to host (O(hits), not O(rows)). Bit-identical
        to the host twin :meth:`bin_export`. Returns None when the
        shape is not device-expressible (labeled staging, host-residual
        filter, non-point geometry) — callers fall to the twin."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.process.binexport import DTYPE_16, DTYPE_24

        f = self._parse(query)
        host = self._host_rows()
        gname = geom_attr or self.sft.geom_field
        if host is None or host.column(gname).dtype == object:
            return None  # non-point geometry: host twin decodes coords
        if len(host) == 0:
            return b""
        m = self._device_hit_mask(f, loose)
        if m is None:
            return None
        mat = self._bin_lane_matrix(
            track_attr, dtg_attr or self.sft.dtg_field, gname, label_attr
        )
        n_lanes, rows = int(mat.shape[0]), int(mat.shape[1])
        if int(m.shape[0]) < rows:
            return None  # mirror/plane layout mismatch: twin is exact
        n = int(jnp.sum(m[:rows], dtype=jnp.int32))  # the count launch
        dt = DTYPE_24 if label_attr else DTYPE_16
        if n == 0:
            return b""
        cap = min(_next_pow2(n), rows)
        key = ("bin-pack", n_lanes, rows, cap)
        fn = self._bin_jits.get(key)
        if fn is None:

            def pack(mask, lanes):
                mk = mask[:rows]
                pos = jnp.cumsum(mk.astype(jnp.int32)) - 1
                keep = mk & (pos < cap)
                idx = jnp.where(keep, pos, cap)  # cap = trash slot
                buf = jnp.zeros((n_lanes, cap + 1), jnp.uint32)
                return buf.at[:, idx].set(lanes)[:, :cap]

            fn = jax.jit(pack)
            self._bin_jits[key] = fn
        out = np.asarray(fn(m, mat))  # one D2H: the packed records
        from geomesa_tpu import metrics

        metrics.results_bin_device_launches.inc()
        rec = np.frombuffer(
            np.ascontiguousarray(out[:, :n].T).tobytes(), dtype=dt
        )
        if sort:
            rec = rec[np.argsort(rec["dtg"], kind="stable")]
        return rec.tobytes()


def _next_pow2(n: int) -> int:
    """Round a dynamic dimension up onto the canonical compile-shape
    ladder (bucketing.py). The name survives from the pow2-only era —
    the default ladder (compile.bucket.growth=2) IS next-power-of-two,
    but the rung set is conf-declared now so warmup can enumerate it
    and deployments can trade padding waste against compile count."""
    from geomesa_tpu.bucketing import bucket_cap

    return bucket_cap(n)


def _note_jit_cache(hit: bool) -> None:
    """Count an in-process jit-cache probe on the tier-labeled compile
    cache metric: ``tier=inproc`` hits are dispatches that reused an
    already-built executable from this process's own jit dicts, vs the
    ``tier=disk`` hits jaxconf's persistent-cache listener counts."""
    if hit:
        from geomesa_tpu import metrics

        metrics.compile_cache_hits.inc(tier="inproc")


class StreamingDeviceIndex(DeviceIndex):
    """Delta-refreshed resident index: appends and evictions touch only
    the changed rows instead of restaging every column (ref role: the
    Kafka consumer keeping tablet caches warm, SURVEY section 2.6
    Kafka-consumer row [UNVERIFIED - empty reference mount]).

    Device columns live in the base class's padded buffers: an append is
    ONE donated slot-write (``_write_delta``) at the current row count;
    an eviction flips validity bits. Deltas pad to power-of-two row
    buckets so jit recompiles stay bounded. When an append would
    overflow capacity, or dead rows pass ``compact_threshold``, the
    index compacts: one full restage at double capacity (amortized O(1)
    per appended row).

    Scans are the base class's, with the validity plane ANDed in (the
    Pallas tile kernels take it as an input). ``self._lock`` is the
    snapshot every read holds (``_snapshot``) and every mutation takes.
    ``attach_live`` applies per-message deltas: Put -> upsert, Remove ->
    evict, Clear -> full refresh.
    """

    def __init__(
        self,
        store,
        type_name: str,
        columns: "list[str] | None" = None,
        capacity: "int | None" = None,
        compact_threshold: float = 0.5,
        z_planes: bool = False,
    ):
        from geomesa_tpu.locking import checked_rlock

        self._capacity_hint = capacity
        self.compact_threshold = compact_threshold
        self.restages = 0  # full restages (init, growth, compaction)
        self.delta_appends = 0  # appends served by the delta path
        self._evict_jit = None
        # live-store listeners run OUTSIDE the store's lock (stream/live.py
        # invokes callbacks unlocked, possibly from several producer
        # threads), and the delta paths are order-sensitive stateful
        # mutations of donated buffers -- serialize every mutation and scan.
        # blocking_ok: refresh/scan hold it across store reads + device
        # staging by design (that serialization is the lock's purpose)
        self._lock = checked_rlock("device_cache.delta", blocking_ok=True)
        super().__init__(store, type_name, columns, z_planes=z_planes)

    # -- cache lifecycle ---------------------------------------------------

    def refresh(self) -> None:
        with self._lock:
            res = self.store.query(self.type_name, _staging_query())
            self._install(res.batch)

    def _install(self, batch, min_cap: int = 0) -> None:
        """Full (re)stage of ``batch`` into fresh capacity-padded buffers."""
        from geomesa_tpu import ledger
        from geomesa_tpu.tracing import span

        # same attribution as the base-class refresh(): every full
        # restage (init, growth, compaction) is a cache.stage compile,
        # and the serving-path tripwire (analysis/compilecheck.py)
        # holds this path to it
        with span("cache.stage", type=self.type_name, rows=len(batch)), \
                ledger.compile_scope("cache.stage"):
            self._install_locked(batch, min_cap)

    def _install_locked(self, batch, min_cap: int = 0) -> None:
        import jax.numpy as jnp

        cols = self._stage_full(batch)
        n = self._n
        cap = _next_pow2(
            max(n, min_cap, self._capacity_hint or 0, self.MIN_DELTA_ROWS)
        )
        self._cols = _pad_rows(cols, cap)
        self._valid = jnp.arange(cap) < n
        self._cap = cap
        self._n_dead = 0
        self._valid_np = np.ones(n, dtype=bool)
        self._row_of = {
            f: i for i, f in enumerate(self._host_rows().fids.tolist())
        }
        self.restages += 1

    def _live_rows(self):
        """Host batch of only the live (non-evicted) rows."""
        return self._host_rows().take(np.nonzero(self._valid_np)[0])

    # -- deltas ------------------------------------------------------------

    def append(self, batch) -> None:
        """Stage only the new rows; one donated device update per call.
        Fids must be new — use upsert() when overwrites are possible."""
        from geomesa_tpu import ledger

        # incremental staging compiles (delta pack, pad concat, the
        # donated slot-write) carry the same family as a full restage
        with self._lock, ledger.compile_scope("cache.stage"):
            self._append_locked(batch)

    def _append_locked(self, batch) -> None:
        from geomesa_tpu.features.batch import FeatureBatch

        m = len(batch)
        if m == 0:
            return
        pad = self._delta_pad(m)
        n0 = self._n
        if n0 + pad > self._cap:
            # grow: compact out dead rows, double capacity for headroom
            merged = FeatureBatch.concat([self._live_rows(), batch])
            self._install(merged, min_cap=2 * len(merged))
            return
        try:
            delta = self._stage_batch(batch)  # widens _bin_range / vocab
        except (_VisOverflow, _BtRebase):
            # vocabulary overflow mid-stream (the restage applies the
            # public-only fallback consistently), or delta bins outside
            # the packed bt window (the restage repacks around a new
            # bin_base)
            delta = None
        if delta is None or not self._write_delta(batch, delta, pad):
            # (or the delta brought a plane with no slot, e.g. the first
            # labeled rows of an unlabeled stream: dropping it would
            # serve labeled rows as public)
            merged = FeatureBatch.concat([self._live_rows(), batch])
            self._install(merged, min_cap=self._cap)
            return
        self._valid_np = np.concatenate(
            [self._valid_np, np.ones(m, dtype=bool)]
        )
        for i, f in enumerate(batch.fids.tolist()):
            self._row_of[f] = n0 + i
        self.delta_appends += 1

    def evict(self, fids) -> None:
        """Drop rows by fid: flips validity bits on device, no restage."""
        from geomesa_tpu import ledger

        with self._lock, ledger.compile_scope("cache.stage"):
            self._evict_locked(fids)

    def _evict_locked(self, fids) -> None:
        import jax
        import jax.numpy as jnp

        rows = [
            self._row_of.pop(f)
            for f in np.asarray(fids).tolist()
            if f in self._row_of
        ]
        if not rows:
            return
        self._gen = getattr(self, "_gen", 0) + 1  # live set changed
        self._valid_np[rows] = False
        self._n_dead += len(rows)
        pad = max(_next_pow2(len(rows)), 64)
        # out-of-range sentinel pads; mode='drop' discards them
        idx = np.full(pad, self._cap, dtype=np.int32)
        idx[: len(rows)] = rows
        if self._evict_jit is None:
            self._evict_jit = jax.jit(
                lambda valid, rows: valid.at[rows].set(False, mode="drop"),
                donate_argnums=(0,),
            )
        self._valid = self._evict_jit(self._valid, jnp.asarray(idx))
        if self._n_dead > self.compact_threshold * max(self._n, 1):
            self._install(self._live_rows(), min_cap=self._cap)

    def upsert(self, batch) -> None:
        """Evict any existing rows for the batch's fids, then append."""
        from geomesa_tpu import ledger

        with self._lock, ledger.compile_scope("cache.stage"):
            existing = [f for f in batch.fids.tolist() if f in self._row_of]
            if existing:
                self._evict_locked(np.asarray(existing, dtype=object))
            self._append_locked(batch)

    def clear(self) -> None:
        with self._lock:
            self._install(
                self._host_parts[0].take(np.array([], dtype=np.int64))
            )

    def refresh_delta(self, batch) -> str:
        """Streamed-append hook: fresh fids delta-append — one donated
        device update, no restage. A batch carrying a fid this index
        already holds is ambiguous (a duplicate-fid append, which the
        store path serves as TWO rows, or a re-delivery racing a full
        restage that already staged it): the backing store's merged
        view is authoritative for both, so restage from it rather than
        guess — upserting here would silently diverge from the store
        path's duplicate-row semantics."""
        from geomesa_tpu import metrics

        with self._lock:
            if any(f in self._row_of for f in batch.fids.tolist()):
                self.refresh()
                mode = "restage"
            else:
                before = self.restages
                self.append(batch)
                mode = "restage" if self.restages > before else "delta"
        metrics.stream_delta_refreshes.inc(mode=mode)
        return mode

    def attach_live(self, live_store):
        """Apply per-message deltas from a live store: Put upserts only
        the changed rows, Remove evicts, Clear (or anything else) falls
        back to a full refresh. Returns a detach callable."""
        from geomesa_tpu.features.batch import FeatureBatch
        from geomesa_tpu.stream.log import Put, Remove

        def listener(msg):
            if isinstance(msg, Put):
                self.upsert(
                    FeatureBatch.from_columns(self.sft, msg.columns, msg.fids)
                )
            elif isinstance(msg, Remove):
                self.evict(np.asarray(msg.fids))
            else:
                self.refresh()

        live_store.add_listener(listener)

        def detach() -> None:
            remove = getattr(live_store, "remove_listener", None)
            if remove is not None:
                remove(listener)

        return detach

    def __len__(self) -> int:
        return self._n - self._n_dead

    def _host_valid(self):
        return self._valid_np


class ShardedDeviceIndex(DeviceIndex):
    """Mesh-resident index: one logical resident cache whose scan planes
    shard across a ``Mesh`` by CONTIGUOUS GLOBAL Z-KEY RANGES, so every
    query — serial count/mask/query, the scheduler's fused micro-batch
    launches, stats/density/kNN riders — runs mesh-wide in single SPMD
    launches with device-side partial results reduced over the mesh (no
    per-query host round-trips per device).

    Staging is the MESH BUILD: the (bin, hi, lo, rid) key lanes run the
    all_to_all splitter-exchange sort (``parallel/dist.distributed_sort``
    — the rid lane makes ties deterministic, so results are bit-identical
    across shard counts), the host mirror is reordered by the resulting
    permutation, and every staged plane is placed with a
    ``NamedSharding`` over the ``shard`` axis — shard s holds the s-th
    globally-sorted key range. Schemas without a spatial key shard
    positionally. Rows pad to a shard multiple at the GLOBAL TAIL with a
    device validity plane masking the padding (the base class's padded
    buffers), so every inherited scan stays exact.

    Reads are the base class's, under its one read discipline
    (``_snapshot``): ``self._lock`` is the snapshot, and this index's
    ``_read_scope`` adds one ``mesh.scan`` span and one
    ``geomesa_mesh_launches_total`` count per outermost read. Only
    ``query`` is its own, for the per-shard compaction.

    A failed mesh sort degrades to the host sort (stamped
    ``mesh-degraded``, counted) rather than failing the refresh; a failed
    mesh scan launch surfaces to the server's device-breaker ladder like
    any other launch fault and the request answers from the store rung.

    With ``mesh.replicas`` > 1 the mesh factors shard x replica and the
    resident planes replicate across the replica axis (whole-index
    replication: fan-out capacity and a warm copy surviving a shard-
    group failure). The dim-plane Pallas engine is single-chip-only and
    is disabled here (the masked-compare engine shards; same results),
    and so are the Pallas filter tiles and the Pallas density kernel
    (XLA refuses to partition a Mosaic call; the XLA-fused scan and the
    scatter density shard).
    """

    _pallas_tiles = False

    def __init__(
        self,
        store,
        type_name: str,
        columns: "list[str] | None" = None,
        z_planes: bool = True,
        mesh=None,
        replicas: "int | None" = None,
        reserve_rows: int = 0,
    ):
        from geomesa_tpu.locking import checked_rlock
        from geomesa_tpu.parallel.mesh import serving_mesh

        # refresh republishes the mirror + sharded planes together; a
        # scan between the two assignments would read misaligned state.
        # blocking_ok: refresh holds it across store reads + the mesh
        # sort + device staging by design (that serialization is the
        # lock's purpose — the streaming-index discipline)
        self._lock = checked_rlock("device_cache.mesh", blocking_ok=True)
        self._mesh = mesh if mesh is not None else serving_mesh(
            replicas=replicas
        )
        self._axis = "shard"
        self._n_shards = int(self._mesh.shape[self._axis])
        self._replicas = int(dict(self._mesh.shape).get("replica", 1))
        self._in_read = False  # inside an outermost read (_read_scope)
        self._rid_plane = None
        self._shards: list = []
        self._build_seconds = 0.0
        self._build_engine = None  # "mesh" | "host-fallback" | None
        self._hits_jits: dict = {}
        #: extra plane capacity staged behind the validity plane so
        #: streamed appends land as in-place deltas instead of a full
        #: mesh restage (0 = pad to the shard multiple only — the
        #: batch-serving default)
        self._reserve_rows = max(int(reserve_rows), 0)
        self._deltas = 0  # streamed delta refreshes since last restage
        super().__init__(
            store, type_name, columns, z_planes=z_planes, dim_planes=False
        )

    @property
    def mesh_shards(self) -> int:
        return self._n_shards

    # -- cache lifecycle ---------------------------------------------------

    def refresh(self) -> None:
        import time as _time

        from geomesa_tpu import metrics, tracing
        from geomesa_tpu.tracing import span

        from geomesa_tpu import ledger

        rows_hint = getattr(self.store, "manifest_rows", None)
        hint = int(rows_hint(self.type_name)) if rows_hint else -1
        t0 = _time.perf_counter()
        # the whole build is a stage: the mesh-sort's splitter-exchange
        # launches compile here too, not just the final plane staging
        with self._lock, span(
            "mesh.build", type=self.type_name, shards=self._n_shards,
            rows_hint=hint,
        ), ledger.compile_scope("cache.stage"):
            res = self.store.query(self.type_name, _staging_query())
            batch = res.batch
            order = self._mesh_order(batch)
            if order is not None:
                batch = batch.take(order)
            self._cols = self._shard_cols(self._stage_full(batch))
        self._build_seconds = _time.perf_counter() - t0
        metrics.mesh_build_seconds.observe(self._build_seconds)
        metrics.mesh_shards.set(self._n_shards)
        self._record_shards(tracing.capture(), t0, self._build_seconds)

    def _mesh_order(self, batch) -> "np.ndarray | None":
        """Global Z-order permutation computed BY THE MESH: the
        splitter-exchange distributed sort over (bin?, hi, lo, rid) key
        lanes — rid makes duplicate keys deterministic, so the staged
        layout is bit-identical across shard counts and equal to the
        host lexsort. None = the schema has no spatial key (positional
        sharding). A mesh-sort fault degrades to the host sort."""
        n = len(batch)
        if n <= 1:
            return None
        kind, planes, _bins = _z_planes_np(batch, self.sft)
        if kind is None:
            return None
        lanes: list = []
        if Z_BIN in planes:
            # bias signed period bins into uint32 lane order
            lanes.append(
                (np.asarray(planes[Z_BIN]).astype(np.int64) + (1 << 31))
                .astype(np.uint32)
            )
        lanes.append(np.asarray(planes[Z_HI]).astype(np.uint32))
        lanes.append(np.asarray(planes[Z_LO]).astype(np.uint32))
        rid = np.arange(n, dtype=np.uint32)
        pad = (-n) % self._n_shards
        if pad:
            lanes = [
                np.concatenate([l, np.full(pad, 0xFFFFFFFF, l.dtype)])
                for l in lanes
            ]
            rid = np.concatenate([rid, np.zeros(pad, np.uint32)])
        valid = np.arange(n + pad) < n
        from geomesa_tpu.parallel.dist import distributed_sort

        try:
            sorted_lanes, _pay, v = distributed_sort(
                self._mesh, tuple(lanes) + (rid,), axis=self._axis,
                valid=valid, on_overflow="raise",
            )
            v = np.asarray(v)
            order = np.asarray(sorted_lanes[-1])[v].astype(np.int64)
            if len(order) != n:
                raise RuntimeError(
                    f"mesh sort returned {len(order)} of {n} rows"
                )
            self._build_engine = "mesh"
            return order
        except Exception as e:
            import warnings

            from geomesa_tpu import metrics, resilience

            warnings.warn(
                f"mesh build sort failed ({type(e).__name__}: {e}); "
                "staging falls back to the host sort",
                RuntimeWarning,
                stacklevel=2,
            )
            metrics.mesh_build_fallbacks.inc()
            resilience.note_degraded("mesh-degraded")
            self._build_engine = "host-fallback"
            real = [l[:n] for l in lanes] + [rid[:n]]
            return np.lexsort(tuple(reversed(real)))

    def _shard_cols(self, cols: dict) -> dict:
        """Place every staged plane with a NamedSharding over the shard
        axis, padding to a shard multiple at the GLOBAL TAIL (masked by
        the device validity plane; the host mirror keeps only real
        rows, and mask truncation at ``_staged_len`` drops the tail)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = self._n
        self._deltas = 0
        self._fids_seen = None  # delta duplicate-check set: rebuild lazily
        # reserve_rows of delta headroom, rounded to a shard multiple:
        # streamed appends update slots [n, cap) in place behind the
        # validity plane until the reserve is spent (then full restage)
        want = n + self._reserve_rows
        pad = (want - n) + ((-want) % self._n_shards)
        cap = n + pad
        if cap == 0:
            self._valid = None
            self._rid_plane = None
            return {k: jnp.asarray(np.asarray(v)) for k, v in cols.items()}
        sharding = NamedSharding(self._mesh, P(self._axis))
        out = {}
        # pop as we go: resharding routes through the single-device
        # staging buffers (base _stage_batch), and keeping both copies
        # alive for the whole loop would transiently double residency —
        # dropping each plane after its sharded put bounds the overlap
        # to one plane. (Staging the planes sharded from the start is
        # the remaining follow-up; the encode runs on device 0 today.)
        for k in list(cols):
            vcol = cols.pop(k)
            a = np.asarray(vcol)
            del vcol
            if pad:
                a = np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], a.dtype)]
                )
            out[k] = jax.device_put(a, sharding)
        self._valid = jax.device_put(np.arange(cap) < n, sharding)
        self._rid_plane = jax.device_put(
            np.arange(cap, dtype=np.uint32), sharding
        )
        return out

    def _record_shards(self, ctx, t0: float, dur: float) -> None:
        """Per-shard residency manifest (ShardMeta) + gauges + one
        retroactive ``mesh.shard`` span per shard (they ran concurrently
        inside the one SPMD build, so they share the build's timing).

        The boundary-key gathers below are eager device reads that
        compile per sharding layout — build bookkeeping, so they carry
        the stage family (caller runs right after the scoped build)."""
        from geomesa_tpu import ledger, metrics, tracing
        from geomesa_tpu.index.api import ShardMeta

        with ledger.compile_scope("cache.stage"):
            self._record_shards_scoped(ctx, t0, dur)

    def _record_shards_scoped(self, ctx, t0: float, dur: float) -> None:
        from geomesa_tpu import metrics, tracing
        from geomesa_tpu.index.api import ShardMeta

        self._shards = []
        n = self._n
        # REAL plane capacity (reserve_rows headroom included): the
        # per-shard slot width comes from the staged layout, not the
        # no-reserve formula — with reserve on, real rows concentrate
        # in the leading shards and the manifest must say so
        cap = (
            int(self._valid.shape[0])
            if self._valid is not None
            else n
        )
        per = cap // self._n_shards if self._n_shards and cap else 0
        # boundary-only key fetches: 2 elements per shard instead of
        # gathering the whole sharded key planes back to host
        have_z = bool(n) and Z_HI in self._cols
        have_bins = have_z and Z_BIN in self._cols

        def _key_at(i: int) -> tuple:
            hi_w = int(np.asarray(self._cols[Z_HI][i]))
            lo_w = int(np.asarray(self._cols[Z_LO][i]))
            key = ((hi_w << 32) | lo_w,)
            if have_bins:
                key = (int(np.asarray(self._cols[Z_BIN][i])),) + key
            return key

        per_bytes = self.nbytes / max(self._n_shards, 1)
        for s in range(self._n_shards):
            lo_i = min(s * per, n)
            hi_i = min((s + 1) * per, n)
            rows = max(0, hi_i - lo_i)
            key_lo = key_hi = None
            if have_z and rows:
                key_lo = _key_at(lo_i)
                key_hi = _key_at(hi_i - 1)
            self._shards.append(ShardMeta(s, rows, key_lo, key_hi))
            metrics.mesh_resident_rows.set(rows, shard=str(s))
            metrics.mesh_resident_bytes.set(per_bytes, shard=str(s))
            tracing.record_span(
                ctx, "mesh.shard", t0, dur, shard=s, rows=rows,
            )

    def mesh_stats(self) -> dict:
        """The per-type ``/stats/mesh`` document."""
        return {
            "type": self.type_name,
            "devices": int(self._mesh.devices.size),
            "shards": self._n_shards,
            "replicas": self._replicas,
            "rows": self._n,
            "resident_bytes": self.nbytes,
            "build_seconds": round(self._build_seconds, 4),
            "build_engine": self._build_engine,
            "reserve_rows": self._reserve_rows,
            "delta_refreshes": self._deltas,
            "shard_ranges": [m.to_json() for m in self._shards],
        }

    def refresh_delta(self, batch) -> str:
        """Streamed-append hook: fold the new rows into the RESERVED
        tail slots behind the validity plane — one donated mesh-wide
        update per plane set, no restage — while capacity, the packed
        bt window and the vis vocabulary allow; anything else (reserve
        spent, ``_BtRebase``/``_VisOverflow``, a plane the fixed
        buffers have no slot for, a duplicate fid) falls back to the
        full mesh restage. Delta rows are NOT globally Z-sorted — the
        scans are masked compares over the planes with validity ANDed
        in, so answers stay exact; the next restage re-sorts."""
        from geomesa_tpu import metrics

        with self._lock:
            try:
                mode = self._delta_locked(batch)
            except (_VisOverflow, _BtRebase):
                mode = None
            if mode is None:
                self.refresh()
                mode = "restage"
        metrics.stream_delta_refreshes.inc(mode=mode)
        return mode

    def _delta_locked(self, batch) -> "str | None":
        m = len(batch)
        if m == 0:
            return "delta"
        if self._valid is None:
            return None  # nothing sharded yet: restage establishes it
        pad = self._delta_pad(m)
        if self._n + pad > int(self._valid.shape[0]):
            return None  # reserve spent
        # duplicate fids cannot update in place (no per-row eviction on
        # the sharded planes): restage folds them through the store
        if any(f in self._row_of_sharded() for f in batch.fids.tolist()):
            return None
        delta = self._stage_batch(batch)  # may raise _VisOverflow/_BtRebase
        if not self._write_delta(batch, delta, pad):
            return None  # a plane with no buffer slot (first labels etc.)
        self._deltas += 1
        for f in batch.fids.tolist():
            self._fids_seen.add(f)
        return "delta"

    def _row_of_sharded(self) -> set:
        """Lazily built fid membership set for the delta duplicate
        check: built once per restage (``_shard_cols`` resets it to
        None), kept incrementally current by ``_delta_locked``. A
        None-flag, NOT a length comparison — staged data may
        legitimately hold duplicate fids (the store serves them as two
        rows), and a length test would misfire on them forever,
        forcing the full mirror concat back onto every ack."""
        if getattr(self, "_fids_seen", None) is None:
            self._fids_seen = set(self._host_rows().fids.tolist())
        return self._fids_seen

    # -- reads (the base class's; mesh-wide launches + observability) ------

    @contextlib.contextmanager
    def _read_scope(self, op: str):
        """One ``mesh.scan`` span and one ``geomesa_mesh_launches_total``
        count per outermost read; a read nested in another (``query`` ->
        ``mask``) adds neither. ``_snapshot`` enters this under the
        lock, so the nesting flag needs no lock of its own."""
        if self._in_read:
            yield
            return
        from geomesa_tpu import metrics
        from geomesa_tpu.tracing import span

        self._in_read = True
        try:
            with span(
                "mesh.scan", op=op, shards=self._n_shards,
                type=self.type_name,
            ):
                yield
        finally:
            self._in_read = False
        metrics.mesh_launches.inc()

    @_snapshot
    def query(self, query, loose: "bool | None" = None, auths=None):
        """Hit stream via per-shard device-side COMPACTION when the
        key-plane engine answers the filter: each shard compacts its
        matching row ids into a sized buffer and the shard-partitioned
        buffers gather ONCE — id bytes instead of a full boolean plane
        for selective queries. Anything else takes the inherited
        mask-and-take path (identical results)."""
        f = self._parse(query)
        if (
            self._resolve_loose(loose)
            and VIS_ID not in (self._cols or {})
            and self._staged_len() > 0
            and self._n_shards > 1
        ):
            lb = self._loose_bounds(f)
            if lb is not None and not (len(lb) == 3 and lb[0] == "dim"):
                ids = self._mesh_hits(lb)
                if ids is not None:
                    return self._host_rows().take(ids)
        return super().query(query, loose=loose, auths=auths)

    def _mesh_hits(self, lb) -> "np.ndarray | None":
        """Two sharded launches: per-shard hit counts (cheap scalar
        vector) size a power-of-two compaction cap, then each shard
        compacts its matching GLOBAL row ids on device and the
        fixed-shape buffers gather once. Returns ascending staged-row
        indices (identical to ``nonzero(mask)``)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from geomesa_tpu.ops import zscan
        from jax import shard_map

        bounds, ids = lb
        kind = self._z_kind
        binned = ids is not None
        plane_names = [Z_HI, Z_LO] + ([Z_BIN] if binned else [])
        try:
            planes = [self._cols[p] for p in plane_names]
        except KeyError:
            return None
        local_n = planes[0].shape[0] // self._n_shards
        if local_n == 0:
            return None
        mf = zscan.kind_mask_fn(kind)
        has_valid = self._valid is not None
        axis = self._axis
        mesh = self._mesh
        n_shards = self._n_shards
        spec = P(axis)
        n_pl = len(planes)

        def local_mask(args):
            pl = args[:n_pl]
            if binned:
                m = mf(pl[0], pl[1], pl[2], args[n_pl], args[n_pl + 1])
            else:
                m = mf(pl[0], pl[1], args[n_pl])
            if has_valid:
                m = m & args[-1]
            return m

        ckey = ("mhits-count", kind, binned, has_valid)
        cfn = self._hits_jits.get(ckey)
        if cfn is None:
            n_in = n_pl + (2 if binned else 1) + has_valid

            @partial(
                shard_map, mesh=mesh,
                in_specs=(spec,) * n_pl + (P(),) * (2 if binned else 1)
                + (spec,) * has_valid,
                out_specs=spec, check_vma=False,
            )
            def count_step(*args):
                return jnp.sum(local_mask(args), dtype=jnp.int32)[None]

            cfn = jax.jit(count_step)
            self._hits_jits[ckey] = cfn
        operands = list(planes) + [bounds] + ([ids] if binned else [])
        if has_valid:
            operands.append(self._valid)
        counts = np.asarray(cfn(*operands))
        top = int(counts.max()) if len(counts) else 0
        if top == 0:
            return np.zeros(0, np.int64)
        cap = min(_next_pow2(top), local_n)
        gkey = ("mhits-gather", kind, binned, has_valid, cap)
        gfn = self._hits_jits.get(gkey)
        if gfn is None:

            @partial(
                shard_map, mesh=mesh,
                in_specs=(spec,) + (spec,) * n_pl
                + (P(),) * (2 if binned else 1) + (spec,) * has_valid,
                out_specs=(spec, spec), check_vma=False,
            )
            def gather_step(rid_l, *args):
                m = local_mask(args)
                pos = jnp.cumsum(m.astype(jnp.int32)) - 1
                keep = m & (pos < cap)
                idx = jnp.where(keep, pos, cap)  # cap = trash slot
                buf = jnp.zeros((cap + 1,), rid_l.dtype).at[idx].set(rid_l)
                hits = jnp.sum(m, dtype=jnp.int32)
                out_valid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(
                    hits, cap
                )
                return buf[:cap], out_valid

            gfn = jax.jit(gather_step)
            self._hits_jits[gkey] = gfn
        got, gvalid = gfn(self._rid_plane, *operands)
        out = np.asarray(got)[np.asarray(gvalid)].astype(np.int64)
        # shard buffers concatenate in shard order and each shard's ids
        # ascend, so the stream is globally ascending == nonzero(mask)
        return out[out < self._n]
