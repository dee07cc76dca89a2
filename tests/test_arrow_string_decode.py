"""Arrow String columns -> host object arrays (``features.batch.decode_strings``).

The decode must equal ``np.array(col.to_pylist(), dtype=object)`` element
for element -- ``str`` values, ``None`` for nulls, never ``numpy.str_`` --
whatever the column's layout: plain ``string``/``large_string``, or
dictionary chunks (what the FS store's parquet reader hands over). Covered
through ``FeatureBatch.from_arrow``, ``arrow_to_batch`` and the FS store's
read paths, plus the counter that says which path decoded.
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from geomesa_tpu import metrics
from geomesa_tpu.arrow_io.schema import arrow_to_batch, batch_to_arrow
from geomesa_tpu.conf import prop_override
from geomesa_tpu.features.batch import FeatureBatch, decode_strings
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter.ecql import parse_instant
from geomesa_tpu.security import VIS_COLUMN
from geomesa_tpu.store.fs import FileSystemDataStore

N = 6


def _dict_chunks(*chunks):
    """Dictionary chunks, each with its own dictionary (built in a
    different order per chunk, so equal values carry different codes)."""
    out = []
    for vals, order in chunks:
        d = pa.array(order, pa.string())
        codes = pa.array(
            [None if v is None else order.index(v) for v in vals], pa.int32()
        )
        out.append(pa.DictionaryArray.from_arrays(codes, d))
    return pa.chunked_array(out)


COLUMNS = {
    "plain": lambda: pa.chunked_array(
        [pa.array(["a", "bb", "a"], pa.string()),
         pa.array(["c", "", "bb"], pa.string())]
    ),
    "large_string": lambda: pa.chunked_array(
        [pa.array(["x", None, "yy", "x", "", "z"], pa.large_string())]
    ),
    "plain_nulls": lambda: pa.chunked_array(
        [pa.array([None, "a", None], pa.string()),
         pa.array(["b", None, "a"], pa.string())]
    ),
    "dict_differing": lambda: _dict_chunks(
        (["a", "b", "a"], ["a", "b"]),
        (["c", "a", "c"], ["c", "a"]),
    ),
    "dict_nulls": lambda: _dict_chunks(
        (["a", None, "b"], ["b", "a"]),
        ([None, None, "c"], ["c"]),
    ),
    "dict_all_null": lambda: _dict_chunks(
        ([None, None, None], []), ([None, None, None], [])
    ),
    "plain_all_null": lambda: pa.chunked_array(
        [pa.array([None] * N, pa.string())]
    ),
    "dict_empty_commonest": lambda: _dict_chunks(
        (["", "", "q", "", "", "r"], ["q", "", "r"])
    ),
    "non_ascii": lambda: pa.chunked_array(
        [pa.array(["Zürich", "東京", "ñ", None, "😀", "Zürich"], pa.string())]
    ).dictionary_encode(),
    "dict_sliced": lambda: pa.chunked_array(
        [pa.array(["u", "v", "w", "u", "v", "w", "u", "v"]).dictionary_encode()
         .slice(2, N)]
    ),
    "dict_int8_full": lambda: pa.chunked_array(
        [pa.DictionaryArray.from_arrays(
            pa.array([0, None, 127, 5, None, 127], pa.int8()),
            pa.array([f"w{i}" for i in range(128)]),
        )]
    ),
    "dict_large_values": lambda: pa.chunked_array(
        [pa.array(["p", None, "q", "p", "q", "p"], pa.large_string())
         .dictionary_encode()]
    ),
}


def _assert_same(got, col):
    want = np.array(col.to_pylist(), dtype=object)
    assert isinstance(got, np.ndarray) and got.dtype == object
    assert got.shape == want.shape
    for g, w in zip(got, want):
        assert type(g) is type(w) and g == w, (g, w)
        assert type(g) in (str, type(None))


@pytest.mark.parametrize("kind", sorted(COLUMNS))
def test_decode_strings_matches_to_pylist(kind):
    col = COLUMNS[kind]()
    _assert_same(decode_strings(col), col)
    # an Array decodes like a one-chunk ChunkedArray
    _assert_same(decode_strings(col.combine_chunks()), col)


@pytest.mark.parametrize("typ", ["string", "large_string", "dictionary"])
def test_decode_strings_zero_rows(typ):
    t = pa.dictionary(pa.int32(), pa.string()) if typ == "dictionary" else (
        getattr(pa, typ)()
    )
    for col in (pa.chunked_array([], t), pa.array([], t)):
        got = decode_strings(col)
        assert got.dtype == object and got.shape == (0,)


def test_decode_strings_shares_one_str_per_dictionary_value():
    col = COLUMNS["dict_differing"]()
    got = decode_strings(col)
    assert got[0] is got[2]  # same chunk, same value: one object
    assert got[3] is got[5]


def _base_batch():
    sft = SimpleFeatureType.create("t", "name:String,n:Int,*geom:Point:srid=4326")
    rng = np.random.default_rng(3)
    return FeatureBatch.from_columns(
        sft,
        {
            "name": ["s"] * N,
            "n": np.arange(N),
            "geom": np.stack(
                [rng.uniform(-10, 10, N), rng.uniform(-10, 10, N)], axis=1
            ),
        },
        fids=np.array([f"f{i}" for i in range(N)], dtype=object),
    ).with_visibility(["a"] * N)


def _with(table, name, col):
    return table.set_column(table.schema.get_field_index(name), name, col)


@pytest.mark.parametrize(
    "kind", [k for k in sorted(COLUMNS) if len(COLUMNS[k]()) == N]
)
@pytest.mark.parametrize("reader", ["from_arrow", "arrow_to_batch"])
def test_batch_readers_match_to_pylist(kind, reader):
    b = _base_batch()
    col = COLUMNS[kind]()
    if reader == "from_arrow":
        t = _with(b.to_arrow(), "name", col)
        back = FeatureBatch.from_arrow(t, b.sft)
    else:
        t = _with(pa.Table.from_batches([batch_to_arrow(b)]), "name", col)
        back = arrow_to_batch(t, b.sft)
    _assert_same(back.columns["name"], col)
    np.testing.assert_array_equal(back.columns["n"], np.arange(N))


@pytest.mark.parametrize("reader", ["from_arrow", "arrow_to_batch"])
@pytest.mark.parametrize("encode", [False, True], ids=["plain", "dictionary"])
def test_fid_and_visibility_columns(reader, encode):
    b = _base_batch()
    fids = pa.chunked_array([pa.array([f"f{i}" for i in range(N)])])
    vis = pa.chunked_array([pa.array(["a", "", "a&b", "a", "", "a"])])
    if encode:
        fids, vis = fids.dictionary_encode(), vis.dictionary_encode()
    if reader == "from_arrow":
        t = _with(_with(b.to_arrow(), "__fid__", fids), VIS_COLUMN, vis)
        back = FeatureBatch.from_arrow(t, b.sft)
        _assert_same(back.fids, fids)
    else:
        t = _with(pa.Table.from_batches([batch_to_arrow(b)]), VIS_COLUMN, vis)
        back = arrow_to_batch(t, b.sft)
        assert list(back.fids) == [f"f{i}" for i in range(N)]
    _assert_same(back.visibilities, vis)


# -- the counter ---------------------------------------------------------------


def _counts():
    return {
        p: metrics.io_decode_string_values.value(path=p)
        for p in ("dictionary", "plain")
    }


@pytest.mark.parametrize(
    "kind,path",
    [
        ("plain", "plain"),
        ("large_string", "plain"),
        ("plain_all_null", "plain"),
        ("dict_differing", "dictionary"),
        ("dict_nulls", "dictionary"),
        ("dict_sliced", "dictionary"),
    ],
)
def test_counter_counts_each_value_once_on_its_path(kind, path):
    col = COLUMNS[kind]()
    c0 = _counts()
    decode_strings(col)
    c1 = _counts()
    other = "plain" if path == "dictionary" else "dictionary"
    assert c1[path] - c0[path] == len(col)
    assert c1[other] == c0[other]


def test_counter_ignores_non_string_columns_and_is_exported():
    c0 = _counts()
    got = decode_strings(pa.chunked_array([pa.array([b"x", None], pa.binary())]))
    assert list(got) == [b"x", None]
    assert _counts() == c0
    # from_arrow counts every String value: attribute, string fid, vis
    b = _base_batch()
    FeatureBatch.from_arrow(b.to_arrow(), b.sft)
    c1 = _counts()
    assert c1["plain"] - c0["plain"] == 3 * N
    assert 'geomesa_io_decode_string_values_total{path="plain"}' in (
        metrics.REGISTRY.prometheus_text()
    )


# -- FS store round trip ------------------------------------------------------

SPEC = (
    "actor:String,place:String,url:String,code:Int,"
    "dtg:Date,*geom:Point:srid=4326"
)
ROWS = 3000


def _string_cols(rng, n):
    vocab = np.array(["", "USA", "Zürich", "東京", "GOV", "ñandú"], dtype=object)
    actor = vocab[rng.integers(0, len(vocab), n)]
    actor[rng.random(n) < 0.1] = None
    place = np.array([f"place-{i % 97}" for i in range(n)], dtype=object)
    url = np.array([f"http://s/{i // 4}" for i in range(n)], dtype=object)
    url[rng.random(n) < 0.05] = None
    return {"actor": actor, "place": place, "url": url}


def _write_store(root, encoding):
    rng = np.random.default_rng(7)
    t0 = parse_instant("2020-01-01T00:00:00")
    cols = {
        **_string_cols(rng, ROWS),
        "code": rng.integers(0, 50, ROWS),
        "dtg": rng.integers(t0, t0 + 30 * 86400_000, ROWS),
        "geom": np.stack(
            [rng.uniform(-60, 60, ROWS), rng.uniform(-50, 50, ROWS)], axis=1
        ),
    }
    with prop_override("store.chunk.rows", 128):
        ds = FileSystemDataStore(root, partition_size=512, encoding=encoding)
        ds.create_schema("t", SPEC)
        ds.write("t", cols, fids=np.arange(ROWS))
        ds.flush("t")
    return cols


def _assert_rows(batch, cols, rows):
    assert sorted(batch.fids.tolist()) == sorted(int(r) for r in rows)
    by_fid = {int(f): i for i, f in enumerate(batch.fids)}
    for name in ("actor", "place", "url"):
        col = batch.columns[name]
        assert col.dtype == object
        for r in rows:
            got, want = col[by_fid[int(r)]], cols[name][r]
            assert type(got) is type(want) and got == want, (name, r)
    for r in rows[:50]:
        assert batch.columns["code"][by_fid[int(r)]] == cols["code"][r]


@pytest.mark.parametrize("verify", ["off", "always"])
@pytest.mark.parametrize("encoding", ["parquet", "orc"])
def test_store_full_scan_round_trip(tmp_path, encoding, verify):
    root = str(tmp_path / "s")
    cols = _write_store(root, encoding)
    c0 = _counts()
    with prop_override("store.verify", verify):
        ds = FileSystemDataStore(root)  # cold: no partition cache
        res = ds.query("t", "INCLUDE")
    _assert_rows(res.batch, cols, np.arange(ROWS))
    c1 = _counts()
    # parquet hands the writer's dictionary pages to the gather; ORC
    # arrives plain. Every String value of the type is counted once.
    path = "dictionary" if encoding == "parquet" else "plain"
    assert c1[path] - c0[path] >= 3 * ROWS


@pytest.mark.parametrize("verify", ["off", "always"])
@pytest.mark.parametrize("encoding", ["parquet", "orc"])
def test_store_chunk_pruned_read_round_trip(tmp_path, encoding, verify):
    root = str(tmp_path / "s")
    cols = _write_store(root, encoding)
    with prop_override("store.verify", verify):
        ds = FileSystemDataStore(root)
        st = ds._types["t"]
        p = st.partitions[0]
        cs = p.chunks
        assert cs is not None and len(cs) > 2
        sel = [0, len(cs) - 1]
        # parquet serves the selection by row group; ORC by row slice
        assert (ds._row_groups_for(st, p, sel) is None) == (encoding == "orc")
        full = ds._read_partition("t", p, cache=False)
        got = ds._read_partition("t", p, cache=False, chunk_sel=sel)
    idx = np.concatenate([np.arange(cs.starts[i], cs.stops[i]) for i in sel])
    want = full.take(idx)
    assert list(got.fids) == list(want.fids)
    _assert_rows(got, cols, want.fids.astype(np.int64))


def test_slice_table_chunks_keeps_dictionary_columns(tmp_path):
    """The row-slice fallback for chunk-selective reads passes dictionary
    columns through; the decode then gathers each slice's codes."""
    root = str(tmp_path / "s")
    cols = _write_store(root, "parquet")
    ds = FileSystemDataStore(root)
    p = ds._types["t"].partitions[0]
    t = pq.read_table(
        ds._part_path("t", p), read_dictionary=["actor", "place", "url"]
    )
    assert pa.types.is_dictionary(t.schema.field("actor").type)
    cs = p.chunks
    sel = [1, len(cs) - 1]
    sliced = FileSystemDataStore._slice_table_chunks(t, cs, sel)
    assert pa.types.is_dictionary(sliced.schema.field("actor").type)
    back = FeatureBatch.from_arrow(sliced, ds.get_schema("t"))
    plain = pq.read_table(ds._part_path("t", p))
    want = FileSystemDataStore._slice_table_chunks(plain, cs, sel)
    for name in ("actor", "place", "url"):
        _assert_same(back.columns[name], want.column(name))
    _assert_rows(back, cols, back.fids.astype(np.int64))
