"""SFT <-> Arrow schema mapping with typed geometry vectors.

Layout parity with the reference's geomesa-arrow-jts vectors
(vector/GeometryVector: PointVector = 2 fixed-width float8 children;
LineStringVector = list over point struct; PolygonVector adds a ring
nesting level [UNVERIFIED - empty reference mount]). The SFT spec string is
carried in schema metadata so readers reconstruct the feature type from
the stream alone (ref ArrowEncodedSft).
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.features.batch import FeatureBatch, decode_strings
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.geom.base import (
    LineString,
    MultiLineString,
    MultiPoint,
    MultiPolygon,
    Point,
    Polygon,
)

SFT_NAME_KEY = b"geomesa.sft.name"
SFT_SPEC_KEY = b"geomesa.sft.spec"
GEOM_TYPE_KEY = b"geomesa.geom.type"
#: stream-level "batches form ascending runs of this key" stamp — the
#: result plane's Z-sorted resident exports set it without re-sorting.
#: The value is either a stream COLUMN name — consumers can then k-way
#: merge streams by it (merge_delta_streams with that key) — or an
#: order TAG naming an ordering the stream does not materialize as a
#: column (``"z"``: the resident index's Z-curve order; same-tag
#: streams are sorted runs of the same global order but cannot be
#: value-merged without the key column)
SORT_KEY_META = b"geomesa.sort.key"

_SCALAR_TYPES = {
    "String": "string",
    "Integer": "int32",
    "Long": "int64",
    "Float": "float32",
    "Double": "float64",
    "Boolean": "bool_",
    "Date": None,  # timestamp("ms")
}


def _point_struct():
    import pyarrow as pa

    return pa.struct([("x", pa.float64()), ("y", pa.float64())])


def _geom_arrow_type(type_name: str):
    import pyarrow as pa

    pt = _point_struct()
    return {
        "Point": pt,
        "MultiPoint": pa.list_(pt),
        "LineString": pa.list_(pt),
        "MultiLineString": pa.list_(pa.list_(pt)),
        "Polygon": pa.list_(pa.list_(pt)),
        "MultiPolygon": pa.list_(pa.list_(pa.list_(pt))),
        "Geometry": pa.string(),  # mixed columns fall back to WKT
    }[type_name]


def arrow_schema_for(
    sft: SimpleFeatureType,
    dict_encode: "tuple[str, ...] | None" = None,
    with_visibility: bool = False,
):
    """Arrow schema with fid column, typed geometry vectors, SFT metadata.

    dict_encode: string attributes to dictionary-encode (default: all of
    them -- the reference dictionary-encodes strings for the wire).
    """
    import pyarrow as pa

    fields = [pa.field("__fid__", pa.string())]
    if with_visibility:
        from geomesa_tpu.security import VIS_COLUMN

        fields.append(pa.field(VIS_COLUMN, pa.string()))
    for attr in sft.attributes:
        if attr.is_geometry:
            f = pa.field(
                attr.name,
                _geom_arrow_type(attr.type_name),
                metadata={GEOM_TYPE_KEY: attr.type_name.encode()},
            )
        elif attr.type_name == "Date":
            f = pa.field(attr.name, pa.timestamp("ms"))
        else:
            t = getattr(pa, _SCALAR_TYPES.get(attr.type_name) or "string")()
            if attr.type_name == "String" and (
                dict_encode is None or attr.name in dict_encode
            ):
                t = pa.dictionary(pa.int32(), pa.string())
            f = pa.field(attr.name, t)
        fields.append(f)
    meta = {SFT_NAME_KEY: sft.type_name.encode(), SFT_SPEC_KEY: sft.spec.encode()}
    return pa.schema(fields, metadata=meta)


def sft_from_schema(schema) -> SimpleFeatureType:
    """Reconstruct the SFT from stream metadata (ArrowEncodedSft role)."""
    meta = schema.metadata or {}
    if SFT_SPEC_KEY not in meta:
        raise ValueError("schema carries no geomesa SFT metadata")
    return SimpleFeatureType.create(
        meta[SFT_NAME_KEY].decode(), meta[SFT_SPEC_KEY].decode()
    )


# -- geometry column encode/decode ------------------------------------------


def _pt(xy) -> dict:
    return {"x": float(xy[0]), "y": float(xy[1])}


def _line_pts(coords) -> list:
    return [_pt(c) for c in np.asarray(coords)]


def _poly_rings(p: Polygon) -> list:
    return [_line_pts(r) for r in p.rings()]


def _encode_geom_column(col: np.ndarray, type_name: str, arrow_type):
    import pyarrow as pa

    if type_name == "Point":
        if col.dtype != object:  # (n, 2) packed points
            x = pa.array(col[:, 0], pa.float64())
            y = pa.array(col[:, 1], pa.float64())
            return pa.StructArray.from_arrays([x, y], ["x", "y"])
        return pa.array([None if g is None else _pt((g.x, g.y)) for g in col],
                        type=arrow_type)
    enc = {
        "MultiPoint": lambda g: [_pt((p.x, p.y)) for p in g.points],
        "LineString": lambda g: _line_pts(g.coords),
        "MultiLineString": lambda g: [_line_pts(l.coords) for l in g.lines],
        "Polygon": _poly_rings,
        "MultiPolygon": lambda g: [_poly_rings(p) for p in g.polygons],
    }
    if type_name in enc:
        fn = enc[type_name]
        return pa.array(
            [None if g is None else fn(g) for g in col], type=arrow_type
        )
    from geomesa_tpu.geom.wkt import to_wkt

    return pa.array([None if g is None else to_wkt(g) for g in col])


def _decode_geom_column(arr, type_name: str) -> np.ndarray:
    if type_name == "Point":
        x = np.asarray(arr.field("x"))
        y = np.asarray(arr.field("y"))
        return np.stack([x, y], axis=1)

    def pts(v) -> np.ndarray:
        return np.array([(p["x"], p["y"]) for p in v], dtype=np.float64)

    dec = {
        "MultiPoint": lambda v: MultiPoint(
            tuple(Point(p["x"], p["y"]) for p in v)
        ),
        "LineString": lambda v: LineString(pts(v)),
        "MultiLineString": lambda v: MultiLineString(
            tuple(LineString(pts(l)) for l in v)
        ),
        "Polygon": lambda v: Polygon(pts(v[0]), tuple(pts(h) for h in v[1:])),
        "MultiPolygon": lambda v: MultiPolygon(
            tuple(
                Polygon(pts(rs[0]), tuple(pts(h) for h in rs[1:])) for rs in v
            )
        ),
    }
    if type_name in dec:
        fn = dec[type_name]
        vals = arr.to_pylist()
        return np.array(
            [None if v is None else fn(v) for v in vals], dtype=object
        )
    from geomesa_tpu.geom.wkt import parse_wkt

    return np.array(
        [None if w is None else parse_wkt(w) for w in arr.to_pylist()],
        dtype=object,
    )


# -- batch <-> RecordBatch ---------------------------------------------------


def _encode_fids(fids: np.ndarray):
    """Feature ids as an Arrow string array with NO per-feature Python
    on the common dtypes: integer fids cast in C++ (Arrow compute),
    numpy unicode wraps directly; only true object arrays pay the
    str() loop (matches the GeoJSON path's ``str(fid)`` rendering)."""
    import pyarrow as pa

    if fids.dtype.kind in "iu":
        import pyarrow.compute as pc

        return pc.cast(pa.array(fids), pa.string())
    if fids.dtype.kind == "U":
        return pa.array(fids, pa.string())
    return pa.array([str(f) for f in fids], pa.string())


def batch_to_arrow(batch: FeatureBatch, schema=None, string_encoder=None):
    """FeatureBatch -> pyarrow RecordBatch under the typed-vector schema.

    string_encoder: optional hook ``(attr_name, col, field) -> Array | None``
    for dictionary fields (the DeltaWriter supplies one that encodes against
    its monotonically growing dictionaries); None falls back to per-batch
    encoding."""
    import pyarrow as pa

    from geomesa_tpu.security import VIS_COLUMN

    sft = batch.sft
    if schema is None:
        schema = arrow_schema_for(
            sft, with_visibility=VIS_COLUMN in batch.columns
        )
    arrays = [_encode_fids(batch.fids)]
    if schema.get_field_index(VIS_COLUMN) >= 0:
        vis = batch.columns.get(VIS_COLUMN)
        arrays.append(
            pa.array(
                [""] * len(batch) if vis is None else [str(v) for v in vis],
                pa.string(),
            )
        )
    for attr in sft.attributes:
        col = batch.columns[attr.name]
        field = schema.field(attr.name)
        if attr.is_geometry:
            a = _encode_geom_column(col, attr.type_name, field.type)
        elif attr.type_name == "Date":
            a = pa.array(col, type=pa.timestamp("ms"))
        elif attr.type_name == "String":
            a = None
            if string_encoder is not None and pa.types.is_dictionary(field.type):
                a = string_encoder(attr.name, col, field)
            if a is None:
                a = pa.array(
                    [None if v is None else str(v) for v in col], pa.string()
                )
                if pa.types.is_dictionary(field.type):
                    a = a.dictionary_encode()
        else:
            a = pa.array(col, type=field.type)
        arrays.append(a)
    return pa.RecordBatch.from_arrays(arrays, schema=schema)


def arrow_to_batch(rb, sft: "SimpleFeatureType | None" = None) -> FeatureBatch:
    """RecordBatch/Table -> FeatureBatch; SFT from metadata if omitted."""
    sft = sft or sft_from_schema(rb.schema)
    cols: dict = {}
    for attr in sft.attributes:
        arr = rb.column(rb.schema.get_field_index(attr.name))
        if attr.type_name == "String":
            cols[attr.name] = decode_strings(arr)
            continue
        if hasattr(arr, "combine_chunks"):
            arr = arr.combine_chunks()
        if attr.is_geometry:
            cols[attr.name] = _decode_geom_column(arr, attr.type_name)
        elif attr.type_name == "Date":
            cols[attr.name] = (
                arr.cast("timestamp[ms]")
                .to_numpy(zero_copy_only=False)
                .astype("datetime64[ms]")
                .astype(np.int64)
            )
        elif attr.column_dtype is not None:
            cols[attr.name] = arr.to_numpy(zero_copy_only=False).astype(
                attr.column_dtype
            )
        else:
            cols[attr.name] = np.array(arr.to_pylist(), dtype=object)
    idx = rb.schema.get_field_index("__fid__")
    fids = np.array(rb.column(idx).to_pylist()) if idx >= 0 else None
    batch = FeatureBatch.from_columns(sft, cols, fids)
    from geomesa_tpu.security import VIS_COLUMN

    vidx = rb.schema.get_field_index(VIS_COLUMN)
    if vidx >= 0:
        batch = batch.with_visibility(decode_strings(rb.column(vidx)))
    return batch
