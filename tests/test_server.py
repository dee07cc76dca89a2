"""HTTP serving bridge: capabilities, features (geojson/arrow), count,
explain, density."""

import json
import urllib.request

import numpy as np
import pytest

from geomesa_tpu.filter.ecql import parse_instant
from geomesa_tpu.server import serve_background
from geomesa_tpu.store.memory import MemoryDataStore

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


@pytest.fixture(scope="module")
def server_url():
    ds = MemoryDataStore()
    ds.create_schema("gdelt", SPEC)
    n = 2000
    rng = np.random.default_rng(17)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write(
        "gdelt",
        {
            "name": rng.choice(["a", "b"], n),
            "dtg": t0 + rng.integers(0, 10**8, n),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    server, _ = serve_background(ds)
    host, port = server.server_address[:2]
    yield f"http://{host}:{port}", ds
    server.shutdown()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


def test_capabilities(server_url):
    url, _ = server_url
    status, ctype, body = _get(f"{url}/capabilities")
    assert status == 200 and "json" in ctype
    doc = json.loads(body)
    assert "gdelt" in doc["types"]
    assert doc["types"]["gdelt"]["geometry"] == "geom"


def test_features_geojson_matches_store(server_url):
    url, ds = server_url
    cql = "BBOX(geom, -5, -5, 5, 5)"
    status, _, body = _get(
        f"{url}/features/gdelt?cql={urllib.request.quote(cql)}"
    )
    assert status == 200
    doc = json.loads(body)
    expected = len(ds.query("gdelt", cql))
    assert len(doc["features"]) == expected
    f0 = doc["features"][0]
    assert f0["geometry"]["type"] == "Point"
    assert set(f0["properties"]) == {"name", "dtg"}


def test_features_arrow(server_url):
    url, ds = server_url
    status, ctype, body = _get(f"{url}/features/gdelt?f=arrow&maxFeatures=50")
    assert status == 200 and "arrow" in ctype
    import io

    from geomesa_tpu.arrow_io import read_feature_stream

    batches = list(read_feature_stream(io.BytesIO(body)))
    assert sum(len(b) for b in batches) == 50


def test_count_and_explain(server_url):
    url, ds = server_url
    cql = urllib.request.quote("name = 'a'")
    status, _, body = _get(f"{url}/count/gdelt?cql={cql}")
    assert status == 200
    assert json.loads(body)["count"] == len(ds.query("gdelt", "name = 'a'"))
    status, ctype, body = _get(f"{url}/explain/gdelt?cql={cql}")
    assert status == 200 and "text/plain" in ctype
    assert b"Chosen index" in body


def test_density_grid(server_url):
    url, ds = server_url
    status, _, body = _get(
        f"{url}/density/gdelt?bbox=-20,-20,20,20&width=16&height=8"
    )
    assert status == 200
    doc = json.loads(body)
    counts = np.asarray(doc["counts"])
    assert counts.shape == (8, 16)
    assert counts.sum() == 2000  # every point lands in the grid


def test_nan_values_serialize_as_null():
    from geomesa_tpu.export import feature_collection

    ds = MemoryDataStore()
    ds.create_schema("t", "v:Double,*geom:Point")
    ds.write("t", {"v": [float("nan"), 1.5], "geom": np.zeros((2, 2))}, [0, 1])
    doc = feature_collection(ds.query("t").batch)
    text = json.dumps(doc)
    json.loads(text)  # strict parse succeeds
    assert "NaN" not in text
    vals = sorted(
        (f["properties"]["v"] is None, f["properties"]["v"]) for f in doc["features"]
    )
    assert vals[0][1] == 1.5 and vals[1][1] is None


def test_errors(server_url):
    url, _ = server_url
    status, _, body = _get_allow_error(f"{url}/features/nope")
    assert status == 404
    status, _, body = _get_allow_error(f"{url}/features/gdelt?cql=BAD%20CQL(")
    assert status == 400
    status, _, body = _get_allow_error(f"{url}/bogus")
    assert status == 404
    status, _, body = _get_allow_error(f"{url}/density/gdelt")
    assert status == 400 and b"bbox" in body


def _get_allow_error(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.status, resp.headers.get("Content-Type"), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type"), e.read()


# -- stats endpoint + resident mode -----------------------------------------


def test_stats_endpoint(server_url):
    url, ds = server_url
    cql = "BBOX(geom, -5, -5, 5, 5)"
    spec = 'Count();MinMax("dtg")'
    status, _, body = _get(
        f"{url}/stats/gdelt?cql={urllib.request.quote(cql)}"
        f"&stats={urllib.request.quote(spec)}"
    )
    assert status == 200
    doc = json.loads(body)
    from geomesa_tpu.process import run_stats

    exp = run_stats(ds, "gdelt", cql, spec).to_json()
    assert doc == exp


def test_stats_endpoint_requires_spec(server_url):
    url, _ = server_url
    import urllib.error

    try:
        _get(f"{url}/stats/gdelt")
        raise AssertionError("should have 400'd")
    except urllib.error.HTTPError as e:
        assert e.code == 400


@pytest.fixture(scope="module")
def resident_url():
    ds = MemoryDataStore()
    ds.create_schema("gdelt", SPEC)
    n = 3000
    rng = np.random.default_rng(5)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write(
        "gdelt",
        {
            "name": rng.choice(["a", "b"], n),
            "dtg": t0 + rng.integers(0, 10**8, n),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    server, _ = serve_background(ds, resident=True)
    host, port = server.server_address[:2]
    yield f"http://{host}:{port}", ds
    server.shutdown()


def test_resident_count_and_features_match_store(resident_url):
    url, ds = resident_url
    cql = "BBOX(geom, -5, -5, 5, 5)"
    expect = len(ds.query("gdelt", cql))
    status, _, body = _get(
        f"{url}/count/gdelt?cql={urllib.request.quote(cql)}"
    )
    assert status == 200 and json.loads(body)["count"] == expect
    status, _, body = _get(
        f"{url}/features/gdelt?cql={urllib.request.quote(cql)}"
    )
    doc = json.loads(body)
    assert len(doc["features"]) == expect


def test_resident_loose_is_superset(resident_url):
    url, ds = resident_url
    cql = "BBOX(geom, -5, -5, 5, 5)"
    exact = len(ds.query("gdelt", cql))
    status, _, body = _get(
        f"{url}/count/gdelt?cql={urllib.request.quote(cql)}&loose=1"
    )
    assert status == 200
    assert json.loads(body)["count"] >= exact


def test_resident_stats_pushdown(resident_url):
    url, ds = resident_url
    spec = 'Count();MinMax("dtg")'
    status, _, body = _get(
        f"{url}/stats/gdelt?stats={urllib.request.quote(spec)}"
        f"&cql={urllib.request.quote('BBOX(geom, -5, -5, 5, 5)')}"
    )
    assert status == 200
    doc = json.loads(body)
    from geomesa_tpu.process import run_stats

    exp = run_stats(
        ds, "gdelt", "BBOX(geom, -5, -5, 5, 5)", spec
    ).to_json()
    assert doc == exp


def test_resident_refresh_after_write(resident_url):
    url, ds = resident_url
    status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE")
    before = json.loads(body)["count"]
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write(
        "gdelt",
        {"name": ["z"], "dtg": [t0], "geom": np.array([[0.0, 0.0]])},
        fids=["fresh-row"],
    )
    # snapshot semantics: stale until refresh
    status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE")
    assert json.loads(body)["count"] == before
    status, _, body = _get(f"{url}/refresh/gdelt")
    assert status == 200 and json.loads(body)["rows"] == before + 1
    status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE")
    assert json.loads(body)["count"] == before + 1


def test_refresh_rejected_without_resident_mode(server_url):
    url, _ = server_url
    import urllib.error

    try:
        _get(f"{url}/refresh/gdelt")
        raise AssertionError("should have 400'd")
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_resident_respects_max_features_cap(resident_url):
    url, _ = resident_url
    from geomesa_tpu.conf import prop_override

    with prop_override("query.max.features", 7):
        status, _, body = _get(f"{url}/features/gdelt?cql=INCLUDE")
        assert status == 200
        assert len(json.loads(body)["features"]) == 7
        # interceptor parity: an EXPLICIT maxFeatures overrides the
        # global cap, exactly like MaxFeaturesInterceptor
        status, _, body = _get(
            f"{url}/features/gdelt?cql=INCLUDE&maxFeatures=20"
        )
        assert len(json.loads(body)["features"]) == 20
        # /count applies the global cap like the plain path counts the
        # capped result
        status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE")
        assert json.loads(body)["count"] == 7
    # explicit maxFeatures caps the resident count like the plain path
    status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE&maxFeatures=5")
    assert json.loads(body)["count"] == 5


def test_resident_count_max_features_zero(resident_url):
    url, _ = resident_url
    # explicit 0 caps to 0 (interceptor parity edge case)
    status, _, body = _get(f"{url}/count/gdelt?cql=INCLUDE&maxFeatures=0")
    assert status == 200 and json.loads(body)["count"] == 0


def test_metrics_endpoint(server_url):
    url, _ = server_url
    _get(f"{url}/count/gdelt?cql=INCLUDE")  # generate at least one query metric
    status, ctype, body = _get(f"{url}/metrics")
    assert status == 200 and "text/plain" in ctype
    text = body.decode()
    assert "geomesa_queries_total" in text
    assert "# TYPE geomesa_query_duration_seconds histogram" in text


def test_resident_density_fused(resident_url):
    """/density in resident mode runs the fused device path and matches
    the store-path grid."""
    url, ds = resident_url
    cql = "BBOX(geom, -5, -5, 5, 5)"
    status, _, body = _get(
        f"{url}/density/gdelt?bbox=-5,-5,5,5&width=16&height=8"
        f"&cql={urllib.request.quote(cql)}"
    )
    assert status == 200
    doc = json.loads(body)
    from geomesa_tpu.geom import Envelope
    from geomesa_tpu.process import density

    ref = density(ds, "gdelt", cql, Envelope(-5, -5, 5, 5), 16, 8)
    np.testing.assert_allclose(np.array(doc["counts"]), ref, rtol=1e-5)
    assert np.array(doc["counts"]).sum() > 0


def _stage_counts(url) -> dict:
    """{stage: observations} of ``geomesa_stage_seconds`` on /metrics,
    plus the store's per-file decodes under ``"io.decode"``."""
    _, _, body = _get(f"{url}/metrics")
    out = {}
    for line in body.decode().splitlines():
        if line.startswith('geomesa_stage_seconds_count{stage="'):
            out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
        elif line.startswith("geomesa_io_decode_seconds_count "):
            out["io.decode"] = float(line.rsplit(" ", 1)[1])
    return out


def test_resident_requests_move_each_stage_once(tmp_path):
    """One resident /count and one /density through the scheduler each
    move http.request, http.encode, sched.execute and device.fetch by
    exactly one; staging an FS store counts its decodes; the stage
    label carries only the declared names."""
    import time

    from geomesa_tpu.store.fs import FileSystemDataStore
    from geomesa_tpu.tracing import STAGES

    ds = FileSystemDataStore(str(tmp_path), partition_size=1024)
    ds.create_schema("gdelt", SPEC)
    n = 4000
    rng = np.random.default_rng(23)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write(
        "gdelt",
        {
            "name": rng.choice(["a", "b"], n),
            "dtg": t0 + rng.integers(0, 10**8, n),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    ds.flush("gdelt")
    server, _ = serve_background(ds, resident=True, sched=True)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    try:
        staged0 = _stage_counts(url)
        _get(f"{url}/count/gdelt?cql=INCLUDE")  # the first query stages
        time.sleep(0.2)
        staged = _stage_counts(url)
        assert staged.get("io.decode", 0) > staged0.get("io.decode", 0)
        cql = urllib.request.quote(
            "BBOX(geom, -5, -5, 5, 5) AND "
            "dtg DURING 2020-01-01T00:00:00Z/2020-01-01T20:00:00Z"
        )
        watched = ("http.request", "http.encode", "sched.execute",
                   "device.fetch")
        for path in (
            f"/count/gdelt?cql={cql}",
            f"/density/gdelt?bbox=-5,-5,5,5&width=16&height=8&cql={cql}",
        ):
            before = _stage_counts(url)
            status, _, body = _get(url + path)
            assert status == 200 and json.loads(body)
            # the handler observes http.request after its last byte:
            # poll until it lands, then settle and read exactly once
            deadline = time.time() + 10
            while time.time() < deadline:
                after = _stage_counts(url)
                if after.get("http.request", 0) > before.get(
                        "http.request", 0):
                    break
                time.sleep(0.01)
            time.sleep(0.2)
            after = _stage_counts(url)
            moved = {s: after.get(s, 0) - before.get(s, 0) for s in watched}
            assert moved == dict.fromkeys(watched, 1), (path, moved)
        assert set(_stage_counts(url)) - {"io.decode"} <= set(STAGES)
    finally:
        server.shutdown()


def _scrape(url) -> dict:
    """/metrics as {``name{labels}``: value}."""
    _, _, body = _get(f"{url}/metrics")
    out = {}
    for line in body.decode().splitlines():
        if line and not line.startswith("#"):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


def test_resident_polygon_count_parses_once_and_counts_its_kernel(
        monkeypatch):
    """A resident /count of INTERSECTS(zone) AND DURING through the
    scheduler, on a (simulated) TPU pick: the CQL parse is observed once,
    the engine counter names the polygon kernel (and the host for a
    relation the kernel cannot run), and the kernel's tested plus
    skipped blocks are its block count."""
    import time

    from geomesa_tpu.filter import compile as compile_mod
    from geomesa_tpu.filter.compile import compile_filter, evaluate_host
    from geomesa_tpu.filter.ecql import parse_ecql
    from geomesa_tpu.ops import pallas_scan

    monkeypatch.setattr(compile_mod, "_on_tpu", lambda: True)
    ds = MemoryDataStore()
    ds.create_schema("gdelt", SPEC)
    n = 3000
    rng = np.random.default_rng(29)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write(
        "gdelt",
        {
            "name": rng.choice(["a", "b"], n),
            "dtg": t0 + rng.integers(0, 10**8, n),
            "geom": np.stack(
                [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
            ),
        },
        fids=np.arange(n),
    )
    a = np.linspace(0.0, 2.0 * np.pi, 1024, endpoint=False)
    r = np.where(np.arange(1024) % 2 == 0, 12.0, 6.0)
    ring = np.stack([r * np.cos(a), r * np.sin(a)], axis=1).astype(
        np.float32)
    ring = np.vstack([ring, ring[:1]])
    cql = ("INTERSECTS(geom, POLYGON((" + ", ".join(
        f"{float(x)!r} {float(y)!r}" for x, y in ring) + "))) AND "
        "dtg DURING 2020-01-01T00:00:00Z/2020-01-01T20:00:00Z")
    server, _ = serve_background(ds, resident=True, sched=True)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    try:
        _get(f"{url}/count/gdelt?cql=INCLUDE")  # the first query stages
        before = _scrape(url)
        status, _, body = _get(
            f"{url}/count/gdelt?cql={urllib.request.quote(cql)}")
        assert status == 200
        f = parse_ecql(cql)
        batch = ds.query("gdelt", "INCLUDE").batch
        assert json.loads(body)["count"] == int(
            evaluate_host(f, batch).sum())
        time.sleep(0.2)
        after = _scrape(url)

        def moved(key):
            return after.get(key, 0.0) - before.get(key, 0.0)

        assert moved('geomesa_stage_seconds_count{stage="cql.parse"}') == 1
        assert moved('geomesa_pip_queries_total{engine="pallas"}') == 1
        assert moved('geomesa_pip_queries_total{engine="xla"}') == 0
        # the served index pads its buffers: the kernel walks them all
        di = server.RequestHandlerClass._resident_cache["gdelt"]
        staged = di._staged_len()
        rows = pallas_scan.SKIP_ROWS
        cols = compile_filter(f, ds.get_schema("gdelt")).device_cols
        br = pallas_scan._pick_block_rows(len(cols))
        blocks = -(-staged // (br * pallas_scan.LANES)) * (br // rows)
        tested = moved('geomesa_pip_blocks_total{outcome="tested"}')
        assert 0 < tested < blocks
        assert tested + moved(
            'geomesa_pip_blocks_total{outcome="skipped"}') == blocks
        assert moved("geomesa_pip_edge_tests_total") == (
            tested * rows * pallas_scan.LANES * 1024)
        # the zone fills its bucket: the loop runs every padded edge
        assert moved("geomesa_pip_edge_loop_tests_total") == moved(
            "geomesa_pip_edge_tests_total")
        # a relation the kernel cannot run is tested on the host
        crosses = cql.replace("INTERSECTS(", "CROSSES(", 1)
        status, _, _ = _get(
            f"{url}/count/gdelt?cql={urllib.request.quote(crosses)}")
        assert status == 200
        time.sleep(0.2)
        host = _scrape(url)
        assert host.get('geomesa_pip_queries_total{engine="host"}', 0.0) \
            - after.get('geomesa_pip_queries_total{engine="host"}', 0.0) == 1
    finally:
        server.shutdown()


def test_server_auths_param_resident_and_store():
    """auths=A,B serves labeled rows from the resident fast path; absent
    auths fail closed. Store-path (non-resident) behavior is identical."""
    from geomesa_tpu.features.batch import FeatureBatch

    for resident in (True, False):
        ds = MemoryDataStore()
        ds.create_schema("sec", SPEC)
        n = 400
        rng = np.random.default_rng(13)
        t0 = parse_instant("2020-01-01T00:00:00")
        batch = FeatureBatch.from_columns(
            ds.get_schema("sec"),
            {
                "name": rng.choice(["a", "b"], n),
                "dtg": t0 + rng.integers(0, 10**8, n),
                "geom": np.stack(
                    [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
                ),
            },
            fids=np.arange(n),
        ).with_visibility(rng.choice(["", "A", "A&B"], n))
        ds.write("sec", batch)
        server, _ = serve_background(ds, resident=resident)
        host, port = server.server_address[:2]
        url = f"http://{host}:{port}"
        try:
            from geomesa_tpu.query.plan import Query

            cql = "BBOX(geom, -20, -20, 20, 20)"
            for auths in ((), ("A",), ("A", "B")):
                want = len(
                    ds.query("sec", Query(cql, hints={"auths": auths})).batch
                )
                qs = f"&auths={','.join(auths)}" if auths else ""
                status, _, body = _get(
                    f"{url}/count/sec?cql={urllib.request.quote(cql)}{qs}"
                )
                assert status == 200
                assert json.loads(body)["count"] == want, (resident, auths)
                status, _, body = _get(
                    f"{url}/features/sec?cql={urllib.request.quote(cql)}{qs}"
                )
                assert len(json.loads(body)["features"]) == want
        finally:
            server.shutdown()


def test_knn_endpoint(server_url):
    """/knn returns k nearest features with distances, matching the
    process-layer result."""
    from urllib.parse import quote

    from geomesa_tpu.process.knn import knn

    url, ds = server_url
    status, _, body = _get(f"{url}/knn/gdelt?x=2.0&y=5.0&k=7")
    assert status == 200
    doc = json.loads(body)
    assert len(doc["features"]) == 7
    dists = [f["properties"]["knn_distance_deg"] for f in doc["features"]]
    assert dists == sorted(dists)
    batch, want = knn(ds, "gdelt", 2.0, 5.0, k=7)
    got_ids = [f["id"] for f in doc["features"]]
    assert got_ids == [str(f) for f in batch.fids]
    # with a base filter
    status, _, body = _get(
        f"{url}/knn/gdelt?x=2.0&y=5.0&k=5&cql={quote(chr(39).join(['name = ', 'a', '']))}"
    )
    assert status == 200
    doc = json.loads(body)
    assert len(doc["features"]) == 5
    assert all(f["properties"]["name"] == "a" for f in doc["features"])


def test_tube_endpoint(server_url):
    url, ds = server_url
    t0 = parse_instant("2020-01-01T00:00:00")
    t1 = t0 + 10**8
    track = f"-10,-10,{t0};0,0,{(t0 + t1) // 2};10,10,{t1}"
    status, _, body = _get(
        f"{url}/tube/gdelt?track={track}&buffer=2.0&maxDt={10**8}"
    )
    assert status == 200
    doc = json.loads(body)
    from geomesa_tpu.process.tube import tube_select

    want = tube_select(
        ds, "gdelt",
        np.array([[-10, -10], [0, 0], [10, 10]], float),
        np.array([t0, (t0 + t1) // 2, t1], np.int64),
        buffer_deg=2.0, max_dt_ms=10**8,
    )
    assert sorted(f["id"] for f in doc["features"]) == sorted(
        str(f) for f in want.fids
    )
    assert len(doc["features"]) > 0


def test_proximity_endpoint(server_url):
    url, ds = server_url
    status, _, body = _get(
        f"{url}/proximity/gdelt?points=0,0;5,5&distance=1.5"
    )
    assert status == 200
    doc = json.loads(body)
    from geomesa_tpu.geom.base import Point
    from geomesa_tpu.process.proximity import proximity_search

    want, wd = proximity_search(
        ds, "gdelt", [Point(0, 0), Point(5, 5)], 1.5
    )
    assert sorted(f["id"] for f in doc["features"]) == sorted(
        str(f) for f in want.fids
    )
    assert len(doc["features"]) > 0
    for f in doc["features"]:
        assert f["properties"]["proximity_distance_deg"] <= 1.5 + 1e-9


def test_process_endpoints_resident_mode():
    """The process endpoints work identically in resident mode (served
    by the one-dispatch device paths)."""
    ds = MemoryDataStore()
    ds.create_schema("r", SPEC)
    n = 1500
    rng = np.random.default_rng(23)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("r", {
        "name": rng.choice(["a", "b"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
        ),
    }, fids=np.arange(n))
    server, _ = serve_background(ds, resident=True)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    try:
        s1, _, b1 = _get(f"{url}/knn/r?x=1.0&y=1.0&k=9")
        assert s1 == 200
        from geomesa_tpu.process.knn import knn

        want, _ = knn(ds, "r", 1.0, 1.0, k=9)
        got = [f["id"] for f in json.loads(b1)["features"]]
        assert got == [str(f) for f in want.fids]
        s2, _, b2 = _get(f"{url}/proximity/r?points=2,2&distance=1.0")
        assert s2 == 200 and len(json.loads(b2)["features"]) > 0
    finally:
        server.shutdown()


def test_warm_server_precompiles_and_serves():
    """make_server(warm=True) stages every type and pre-compiles the
    serving kernels before accepting traffic; requests then serve with
    no first-touch build."""
    from geomesa_tpu.server import make_server
    import threading

    ds = MemoryDataStore()
    ds.create_schema("gdelt", SPEC)
    n = 500
    rng = np.random.default_rng(23)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("gdelt", {
        "name": rng.choice(["a", "b"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
        ),
    }, fids=np.arange(n))
    server = make_server(ds, resident=True, warm=True)
    # the resident cache is populated BEFORE the first request
    assert "gdelt" in server.RequestHandlerClass._resident_cache
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        host, port = server.server_address[:2]
        status, _, body = _get(
            f"http://{host}:{port}/count/gdelt?cql=INCLUDE"
        )
        assert status == 200 and json.loads(body)["count"] == n
    finally:
        server.shutdown()


def test_device_index_warmup_legs():
    """warmup() compiles every serving kernel family and reports a
    per-leg duration (None only for legs the schema can't serve)."""
    from geomesa_tpu.device_cache import DeviceIndex

    ds = MemoryDataStore()
    ds.create_schema("t", SPEC)
    n = 300
    rng = np.random.default_rng(5)
    t0 = parse_instant("2020-01-01T00:00:00")
    ds.write("t", {
        "name": rng.choice(["a", "b"], n),
        "dtg": t0 + rng.integers(0, 10**8, n),
        "geom": np.stack(
            [rng.uniform(-20, 20, n), rng.uniform(-20, 20, n)], axis=1
        ),
    }, fids=np.arange(n))
    di = DeviceIndex(ds, "t", z_planes=True)
    out = di.warmup()
    assert {"knn", "density", "stats", "mask", "window_union"} <= set(out)
    assert all(v is not None for v in out.values()), out
    # warmed: a real request compiles nothing. The bound distinguishes
    # "no compile" (~10ms on the CPU mesh) from "compiled here"
    # (seconds) with slack for a loaded CI box — NOT a latency SLO.
    import time as _t
    t = _t.perf_counter()
    di.knn(0.0, 0.0, 5)
    assert (_t.perf_counter() - t) < 2.0


def test_device_index_warmup_non_point_schema():
    """Non-point schemas warm their envelope-plane kernels; only the
    point-only legs (kNN, density) report unavailable."""
    from geomesa_tpu.device_cache import DeviceIndex
    from geomesa_tpu.sql.functions import st_makeBBOX

    ds = MemoryDataStore()
    ds.create_schema("zones", "name:String,dtg:Date,*geom:Polygon:srid=4326")
    t0 = parse_instant("2020-01-01T00:00:00")
    polys = np.array(
        [st_makeBBOX(i, i, i + 1, i + 1) for i in range(40)], dtype=object
    )
    ds.write("zones", {
        "name": [f"z{i}" for i in range(40)],
        "dtg": t0 + np.arange(40) * 10**6,
        "geom": polys,
    }, fids=np.arange(40))
    di = DeviceIndex(ds, "zones", z_planes=True)
    out = di.warmup()
    assert out["knn"] is None and out["density"] is None
    others = {k: v for k, v in out.items() if k not in ("knn", "density")}
    assert others and all(v is not None for v in others.values()), out
