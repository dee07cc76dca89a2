"""FeatureBatch: the columnar SimpleFeature collection.

Struct-of-arrays: one numpy array per attribute (Arrow-compatible), plus a
feature-id array. This replaces the reference's per-row Kryo-serialized
values (ref: geomesa-features KryoFeatureSerializer) with a layout the TPU
can scan directly -- the design stance of SURVEY.md section 7.

Column conventions:
- Point geometry  -> (n, 2) float64 array [x, y]
- other geometry  -> object array of geomesa_tpu.geom Geometry + a cached
                     (n, 4) float64 bbox array [xmin, ymin, xmax, ymax]
                     (device prefilter operates on the bboxes)
- Date            -> int64 epoch milliseconds
- numeric/bool    -> matching numpy dtype
- String/UUID/Bytes -> object array (host-only; dictionary-encoded on
                     export)
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from geomesa_tpu import metrics
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.geom import Geometry, Point, parse_wkt, to_wkt


@dataclass
class FeatureBatch:
    sft: SimpleFeatureType
    fids: np.ndarray
    columns: dict
    _bboxes: dict = field(default_factory=dict, repr=False)

    # -- construction ------------------------------------------------------

    @staticmethod
    def from_columns(sft: SimpleFeatureType, columns: dict, fids=None) -> "FeatureBatch":
        """Build from {attribute: values}. Geometry columns may be given as
        (n,2) point arrays, object arrays of Geometry, or WKT strings; dates
        as int64 millis or numpy datetime64."""
        n = None
        out: dict = {}
        for attr in sft.attributes:
            if attr.name not in columns:
                raise ValueError(f"missing column {attr.name!r}")
            vals = columns[attr.name]
            if attr.is_geometry:
                col = _coerce_geometry(vals, attr.is_point)
            elif attr.type_name == "Date":
                col = _coerce_date(vals)
            elif attr.column_dtype is not None:
                col = np.asarray(vals).astype(attr.column_dtype)
            else:
                col = np.asarray(vals, dtype=object)
            m = len(col)
            if n is None:
                n = m
            elif m != n:
                raise ValueError(
                    f"column {attr.name!r} has {m} rows, expected {n}"
                )
            out[attr.name] = col
        if n is None:
            n = 0
        from geomesa_tpu.security import VIS_COLUMN

        if VIS_COLUMN in columns:
            # visibility rides along as a reserved column (it is not an SFT
            # attribute); dropping it here would silently de-classify rows
            # on any columns-dict round trip (e.g. live-layer Put replay)
            vis = np.asarray(columns[VIS_COLUMN], dtype=object)
            if len(vis) != n:
                raise ValueError("visibility length mismatch")
            out[VIS_COLUMN] = vis
        if fids is None:
            fids = np.arange(n)
        fids = np.asarray(fids)
        if len(fids) != n:
            raise ValueError("fids length mismatch")
        return FeatureBatch(sft, fids, out)

    @staticmethod
    def concat(batches: "list[FeatureBatch]") -> "FeatureBatch":
        from geomesa_tpu.security import VIS_COLUMN

        if not batches:
            raise ValueError("no batches")
        sft = batches[0].sft
        names = set()
        for b in batches:
            names.update(b.columns)
        cols = {}
        for name in names:
            parts = []
            for b in batches:
                if name in b.columns:
                    parts.append(b.columns[name])
                elif name == VIS_COLUMN:
                    # unlabeled batches mixed with labeled ones: public rows
                    parts.append(np.array([""] * len(b), dtype=object))
                else:
                    raise KeyError(
                        f"column {name!r} missing from a concatenated batch"
                    )
            cols[name] = np.concatenate(parts)
        fids = np.concatenate([b.fids for b in batches])
        return FeatureBatch(sft, fids, cols)

    # -- basics ------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.fids)

    def column(self, name: str) -> np.ndarray:
        return self.columns[name]

    def take(self, indices, allow_alias: bool = False) -> "FeatureBatch":
        """Row gather -> new batch. ``take`` COPIES by default — callers
        (e.g. stream/live snapshots) rely on it as a defensive copy
        against in-place writers. ``allow_alias=True`` lets an identity
        index set return ``self`` un-copied: ONLY for internal read-only
        pipelines whose downstream merge copies anyway (the fs store's
        per-partition scans), where the full-table gather is pure
        waste."""
        idx = np.asarray(indices)
        n = len(self)
        if (
            allow_alias
            and len(idx) == n
            and n
            and idx.dtype.kind in "iu"
            and idx[0] == 0
            and idx[-1] == n - 1
            and bool(np.all(idx[1:] > idx[:-1]))
        ):
            # n strictly-increasing ints starting at 0 ending at n-1 ARE
            # the identity: skip the full-copy gather (a full-table scan
            # otherwise pays it per partition)
            return self
        return FeatureBatch(
            self.sft,
            self.fids[idx],
            {k: v[idx] for k, v in self.columns.items()},
        )

    def with_visibility(self, vis) -> "FeatureBatch":
        """Attach per-feature visibility labels (security.VIS_COLUMN
        reserved column; ref 'geomesa.feature.visibility' user data)."""
        from geomesa_tpu.security import VIS_COLUMN

        vis = np.asarray(vis, dtype=object)
        if len(vis) != len(self):
            raise ValueError("visibility length mismatch")
        cols = dict(self.columns)
        cols[VIS_COLUMN] = vis
        return FeatureBatch(self.sft, self.fids, cols)

    @property
    def visibilities(self) -> "np.ndarray | None":
        from geomesa_tpu.security import VIS_COLUMN

        return self.columns.get(VIS_COLUMN)

    def point_coords(self, name: str | None = None):
        """(x, y) float64 arrays for a Point column (default geometry)."""
        name = name or self.sft.geom_field
        col = self.columns[name]
        if col.dtype == object:
            raise TypeError(f"{name!r} is not a Point column")
        return np.ascontiguousarray(col[:, 0]), np.ascontiguousarray(col[:, 1])

    def bboxes(self, name: str | None = None) -> np.ndarray:
        """(n, 4) [xmin, ymin, xmax, ymax] for any geometry column."""
        name = name or self.sft.geom_field
        col = self.columns[name]
        if col.dtype != object:
            return np.stack(
                [col[:, 0], col[:, 1], col[:, 0], col[:, 1]], axis=1
            )
        if name not in self._bboxes:
            bb = np.empty((len(col), 4), dtype=np.float64)
            for i, g in enumerate(col):
                e = g.envelope
                bb[i] = (e.xmin, e.ymin, e.xmax, e.ymax)
            self._bboxes[name] = bb
        return self._bboxes[name]

    # -- Arrow interop -----------------------------------------------------

    def to_arrow(self):
        """pyarrow Table; points become x/y float64 struct-ish columns
        ``<name>_x``/``<name>_y``; other geometries are WKT strings.

        (ref role: geomesa-arrow ArrowSimpleFeatureVector; fixed-width point
        child vectors match its PointVector layout.)
        """
        import pyarrow as pa

        from geomesa_tpu.security import VIS_COLUMN

        fids = self.fids
        arrays = {
            "__fid__": pa.array(
                fids if fids.dtype != object else fids.tolist()
            )
        }
        if VIS_COLUMN in self.columns:
            arrays[VIS_COLUMN] = pa.array(
                [str(v) for v in self.columns[VIS_COLUMN]], pa.string()
            )
        for attr in self.sft.attributes:
            col = self.columns[attr.name]
            if attr.is_geometry:
                if col.dtype != object:
                    arrays[f"{attr.name}_x"] = pa.array(
                        np.ascontiguousarray(col[:, 0])
                    )
                    arrays[f"{attr.name}_y"] = pa.array(
                        np.ascontiguousarray(col[:, 1])
                    )
                else:
                    arrays[attr.name] = pa.array([to_wkt(g) for g in col])
            elif attr.type_name == "Date":
                arrays[attr.name] = pa.array(col, type=pa.timestamp("ms"))
            elif col.dtype.kind in "iufb":
                arrays[attr.name] = pa.array(col)  # zero-conversion path
            else:
                arrays[attr.name] = pa.array(col.tolist())
        return pa.table(arrays)

    @staticmethod
    def from_arrow(table, sft: SimpleFeatureType) -> "FeatureBatch":
        cols: dict = {}
        names = set(table.column_names)
        for attr in sft.attributes:
            if attr.is_geometry and f"{attr.name}_x" in names:
                x = table.column(f"{attr.name}_x").to_numpy()
                y = table.column(f"{attr.name}_y").to_numpy()
                cols[attr.name] = np.stack([x, y], axis=1)
            elif attr.is_geometry:
                wkts = table.column(attr.name).to_pylist()
                cols[attr.name] = np.array(
                    [parse_wkt(w) for w in wkts], dtype=object
                )
            elif attr.type_name == "Date":
                arr = table.column(attr.name).cast("timestamp[ms]").to_numpy()
                cols[attr.name] = arr.astype("datetime64[ms]").astype(np.int64)
            else:
                arr = table.column(attr.name)
                if attr.column_dtype is not None:
                    cols[attr.name] = arr.to_numpy().astype(attr.column_dtype)
                else:
                    cols[attr.name] = decode_strings(arr)
        fids = None
        if "__fid__" in names:
            fids = table.column("__fid__")
            fids = (
                decode_strings(fids)
                if is_string_type(fids.type)
                else fids.to_numpy(zero_copy_only=False)
            )
        batch = FeatureBatch.from_columns(sft, cols, fids)
        from geomesa_tpu.security import VIS_COLUMN

        if VIS_COLUMN in names:
            batch = batch.with_visibility(
                decode_strings(table.column(VIS_COLUMN))
            )
        return batch


def is_string_type(t) -> bool:
    """Arrow ``string``/``large_string``, plain or dictionary-encoded."""
    import pyarrow as pa

    if pa.types.is_dictionary(t):
        t = t.value_type
    return pa.types.is_string(t) or pa.types.is_large_string(t)


def decode_strings(col) -> np.ndarray:
    """Arrow column (Array or ChunkedArray) -> object array, equal element
    for element to ``np.array(col.to_pylist(), dtype=object)``: ``str``
    values, ``None`` for nulls.

    String columns never build an Arrow scalar per value. A dictionary
    chunk converts its dictionary once and gathers it by the indices
    (nulls index an appended ``None``), so every row holding one distinct
    value of the chunk shares one ``str``; a plain column takes Arrow's
    vectorized conversion. (``to_numpy`` on a dictionary column does not
    keep its nulls, hence the gather.) Other types keep ``to_pylist``."""
    import pyarrow as pa

    t = col.type
    if not is_string_type(t):
        return np.array(col.to_pylist(), dtype=object)
    if not pa.types.is_dictionary(t):
        out = col.to_numpy(zero_copy_only=False)
        metrics.io_decode_string_values.inc(len(out), path="plain")
        return out
    chunks = col.chunks if isinstance(col, pa.ChunkedArray) else [col]
    out = np.empty(len(col), dtype=object)
    off = 0
    for ch in chunks:
        d = len(ch.dictionary)
        vals = np.empty(d + 1, dtype=object)
        vals[:d] = ch.dictionary.to_numpy(zero_copy_only=False)
        idx = ch.indices
        if idx.null_count:  # widened first: d may not fit an int8 index
            idx = idx.cast(pa.int64()).fill_null(d)
        out[off:off + len(ch)] = vals[idx.to_numpy()]
        off += len(ch)
    metrics.io_decode_string_values.inc(len(out), path="dictionary")
    return out


def _coerce_geometry(vals, is_point: bool) -> np.ndarray:
    if isinstance(vals, np.ndarray) and vals.dtype != object and vals.ndim == 2:
        return np.asarray(vals, dtype=np.float64)
    vals = list(vals)
    if not vals:
        return (
            np.zeros((0, 2), dtype=np.float64)
            if is_point
            else np.array([], dtype=object)
        )
    if is_point:
        try:  # fast path: homogeneous (x, y) pairs
            arr = np.asarray(vals, dtype=np.float64)
            if arr.ndim == 2 and arr.shape[1] == 2:
                return arr
        except (ValueError, TypeError):
            pass

        # per-ROW coercion: a column may mix WKT strings, Point objects,
        # and coordinate pairs (e.g. rows collected by a feature writer)
        def xy(v):
            if isinstance(v, str):
                v = parse_wkt(v)
            if isinstance(v, Point):
                return (v.x, v.y)
            if isinstance(v, (tuple, list, np.ndarray)):
                return tuple(np.asarray(v, dtype=np.float64))
            raise TypeError(f"cannot coerce {type(v)} to Point column")

        return np.asarray([xy(v) for v in vals], dtype=np.float64)
    out = [parse_wkt(v) if isinstance(v, str) else v for v in vals]
    if isinstance(out[0], Geometry):
        return np.array(out, dtype=object)
    raise TypeError(f"cannot coerce {type(out[0])} to geometry column")


def _coerce_date(vals) -> np.ndarray:
    a = np.asarray(vals)
    if np.issubdtype(a.dtype, np.datetime64):
        return a.astype("datetime64[ms]").astype(np.int64)
    if a.dtype == object or a.dtype.kind in "US":
        return (
            np.array(a, dtype="datetime64[ms]").astype(np.int64)
        )
    return a.astype(np.int64)
