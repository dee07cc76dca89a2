"""HTTP serving bridge: WFS-shaped JSON + Arrow IPC endpoints.

Ref role: geomesa-gs-plugin -- the GeoServer packaging that exposes stores
over OGC protocols -- plus the WPS process endpoints (geomesa-process)
[UNVERIFIED - empty reference mount]. The reference keeps the serving
layer out of the query hot path (GeoServer calls the same DataStore API);
this bridge does the same: a thin stdlib ThreadingHTTPServer over any
store object, with all planning/scan work done by the store.

Endpoints (all GET):

- ``/capabilities``                 -- type names + schemas (GetCapabilities)
- ``/features/<type>?cql=&maxFeatures=&properties=&f=geojson|arrow``
                                     -- GetFeature; Arrow IPC when f=arrow
- ``/count/<type>?cql=``            -- hit count
- ``/explain/<type>?cql=``          -- query plan text
- ``/density/<type>?cql=&bbox=&width=&height=`` -- heatmap grid (WPS
  DensityProcess analog), JSON {"counts": [[...]], "bbox": [...]}
- ``/stats/<type>?cql=&stats=<Stat-DSL spec>&loose=`` -- server-side
  aggregation (StatsProcess / StatsIterator analog), JSON stat list
- ``/knn/<type>?x=&y=&k=&cql=&maxRadius=`` -- k nearest features with
  distances (KNearestNeighborSearchProcess analog; resident mode = one
  fused distance+top_k dispatch)
- ``/tube/<type>?track=x,y,t;...&buffer=&maxDt=&cql=`` -- corridor
  search around a track (TubeSelectProcess analog)
- ``/proximity/<type>?points=x,y;...&distance=&cql=`` -- features near
  any input point, with distances (ProximitySearchProcess analog)
- ``/metrics``                      -- Prometheus exposition text
- ``/healthz``                      -- liveness: 200 while the process
  is up, draining included (only readiness flips on drain)
- ``/readyz``                       -- readiness: breaker states per
  failure domain, scheduler pressure, degraded domains; 503 while
  draining (load balancers pull the instance), 200 otherwise — a
  DEGRADED instance keeps serving and says so in the body
- ``/stats/sched``                  -- device query scheduler counters
  (sched mode: queue depth, wait time, fusion factor, rejections)
- ``/stats/store``                  -- store durability/integrity snapshot
  (FS stores: generations, quarantined partitions, recovery counters)
- ``/stats/mesh``                   -- serving-mesh topology + per-type
  shard residency (rows/bytes/Z-key range per shard, build engine)
- ``/stats/slo``                    -- windowed SLO engine: per-SLO
  objective/threshold, fast+slow burn rates, burning flags, and
  windowed p50/p99/p999 per endpoint/lane (slo.py)
- ``/stats/ledger``                 -- per-request cost ledger roll-up:
  per-tenant and per-shape cost aggregates, the top-K most expensive
  requests (with trace ids), and the compile-attribution table
  (ledger.py)
- ``/stats``                        -- roll-up: sched + store + mesh +
  slo + ledger + persistent compile-cache hit/miss in one scrape
- ``/debug/traces``                 -- recent request traces (summaries;
  ``?limit=``)
- ``/debug/traces/<id>``            -- one trace's full span tree;
  ``?format=perfetto`` emits Chrome-trace/Perfetto JSON
- ``/refresh/<type>``               -- restage a resident type after writes
- ``/wal/<type>?from=&waitMs=&follower=`` -- replication ship: chunked
  stream of checksummed WAL records (on-disk framing) with seq >= from;
  long-polls when empty, 410 Gone below the compaction watermark
- ``/stats/replica``                -- replication role/lag/failover doc
  (replica.py; {"enabled": false} when unreplicated)

POST ``/append/<type>`` ingests into the streaming live layer (WAL-first
ack; followers answer 503 + the leader's URL), and POST
``/admin/shutdown`` triggers the draining shutdown remotely (the fleet
rolling-restart drain trigger; the response acks before draining
starts).

Tracing: every non-debug request runs under a root span (tracing.py) —
an inbound ``X-Request-Id`` header becomes the trace id (echoed on the
response; generated when absent), spans from the scheduler, planner,
device launches and store reads nest beneath it, and retention follows
``trace.sample`` / ``trace.slow_ms`` (slow requests also append to the
store's ``_slow_queries.jsonl``, full trace embedded).

Scheduler mode (``make_server(store, sched=True)`` or a SchedConfig, CLI
``serve --sched``) routes query/count/density/knn/stats work through the
device query scheduler (:mod:`geomesa_tpu.sched`): bounded admission
(queue-full -> 429 + Retry-After), per-request deadlines (``deadlineMs=``
-> 504 on expiry), priority lanes (``lane=interactive|batch``),
per-tenant fairness (``tenant=``, defaulting to the client address), and
micro-batch fusion — compatible concurrent resident bbox queries execute
as ONE stacked device launch instead of N.

Resident mode (``make_server(store, resident=True)``, CLI ``serve
--resident``) pins each type's scan columns AND index-key planes in
device memory (DeviceIndex, the tablet-server block-cache analog):
``/count``, ``/features`` and ``/stats`` answer from HBM in one fused
dispatch, and ``loose=1`` switches bbox(+during) filters to the key-only
cell-granular scan (geomesa.loose.bbox). The resident copy is a
SNAPSHOT: after writing to the backing store, hit ``/refresh/<type>``
(or restart) to restage — the durable store stays the source of truth,
exactly the DeviceIndex contract.

Fault tolerance (resilience.py, ISSUE 7): device-rung work (resident
count/features/stats/density) runs behind the ``device`` circuit
breaker with jittered retries of transient faults; when the breaker is
open, a launch fails or the resident cache cannot stage, requests fall
down the degradation ladder (resident -> store scan; exact -> chunk
pre-aggregates under brownout) instead of failing — every degraded
response carries an ``X-Degraded: <reason,...>`` header and the audit
event records the same reasons. Shutdown DRAINS: admission stops
(query endpoints 503 + Retry-After, ``/readyz`` flips 503 while
``/healthz`` stays 200 so the orchestrator de-routes without killing),
in-flight scheduler work finishes, audit/slow logs flush, then the
accept loop stops.

SLOs + cost accounting (slo.py / ledger.py, ISSUE 9): every query
request is measured against its lane's SLO (``slo.<lane>.*`` conf
keys) in time-rotated latency windows, multi-window burn rates ride
``/stats/slo`` and ``/readyz`` (burning = degraded detail, NOT
unready), and a per-request cost ledger — device launches/seconds,
compile attribution, host I/O, chunks pruned, retries, degradations —
aggregates per tenant/shape on ``/stats/ledger``. When the fast-window
burn crosses ``slo.flightrec.burn`` or a breaker opens, the flight
recorder snapshots a postmortem bundle to ``<root>/_flightrec/``.

Errors return JSON ``{"error": ...}`` with 4xx/5xx status; 429/504/5xx
responses carry ``X-Request-Id`` too, and shed / deadline-expired
requests are stamped into the audit log (outcome field).
"""

from __future__ import annotations

import json
import math
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, unquote, urlparse

from geomesa_tpu.spawn import spawn_thread


class _GeomesaHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer whose ``shutdown`` is a DRAINING shutdown:
    admission stops first (the ``draining`` event flips query endpoints
    to 503 + Retry-After and ``/readyz`` to 503; ``/healthz`` liveness
    stays 200 so the orchestrator de-routes, not kills), in-flight
    scheduler work finishes (``QueryScheduler.close`` — bounded, joins
    the workers; leaving workers mid-device-launch lets a CLI/test
    process exit with work half-executed), the audit and slow-query
    logs flush, and only then does the accept loop stop."""

    scheduler = None
    store = None  # wired by make_server (audit flush at drain)
    stream_layer = None  # StreamingStore, when the live layer is on
    replica = None  # Replicator, when this server is in a group
    pubsub = None  # PubSubHub, when the push tier is on

    def __init__(self, *args, **kwargs):
        self.draining = threading.Event()
        # compilecheck serving-window bracket (set by make_server once
        # the server is fully wired; flag keeps double-shutdown balanced)
        self._ccheck_live = False
        super().__init__(*args, **kwargs)

    def shutdown(self):
        self.draining.set()  # stop admission BEFORE finishing in-flight
        if self.replica is not None:
            # stop tailing/failover first: a follower must not promote
            # because ITS OWN drain made the leader look dead
            try:
                self.replica.close()
            except Exception:  # lint: disable=GT011(shutdown teardown: a failing close must not stop the drain)  # close is best-effort on the way down
                pass
        if self.scheduler is not None:
            self.scheduler.close(timeout=5.0)
        if self.pubsub is not None:
            # detach the matcher from the stream and wake every push
            # connection BEFORE the live layer seals its WAL
            try:
                self.pubsub.close()
            except Exception:  # lint: disable=GT011(shutdown teardown: a failing close must not stop the drain)  # close is best-effort on the way down
                pass
        if self.stream_layer is not None:
            # stop the compactor and seal the WAL; acked-but-uncompacted
            # rows stay durable in the log and replay on the next open
            try:
                self.stream_layer.close()
            except Exception:  # lint: disable=GT011(shutdown teardown: a failing close must not stop the drain)  # close is best-effort on the way down
                pass
        aw = getattr(self.store, "audit_writer", None)
        if aw is not None:
            try:
                aw.flush()
            except Exception:  # lint: disable=GT011(shutdown teardown: a failing audit flush must not stop the drain)  # flush is best-effort on the way down
                pass
        super().shutdown()
        if self._ccheck_live:
            # after the accept loop stops: compiles during the drain are
            # still serving-path compiles and stay checked
            self._ccheck_live = False
            from geomesa_tpu.analysis import compilecheck

            compilecheck.CHECKER.serving_down()


class _Handler(BaseHTTPRequestHandler):
    # HTTP/1.1: chunked transfer encoding for the streamed result
    # plane (first record batch flushes while later batches are still
    # assembling); every buffered response carries Content-Length so
    # keep-alive semantics hold. The socket timeout bounds how long an
    # IDLE keep-alive connection may pin a handler thread (the stdlib
    # turns the timeout into close_connection) — without it every
    # half-open client would hold a ThreadingHTTPServer thread forever.
    # make_server resolves the declared ``http.keepalive.s`` conf key
    # over this class default (router→backend persistent connections
    # share the same knob)
    protocol_version = "HTTP/1.1"
    timeout = 60

    store = None  # injected by make_server
    resident = False  # serve from device-pinned DeviceIndex caches
    mesh = False  # shard resident indexes across the device mesh
    scheduler = None  # QueryScheduler (admission + micro-batch fusion)
    stream = None  # StreamingStore live layer (None = batch-only)
    replica = None  # Replicator (None = unreplicated single process)
    pubsub = None  # PubSubHub continuous-query tier (needs stream)
    _resident_cache: dict = {}  # per-server-class: type -> DeviceIndex
    _resident_lock = None  # per-server-class construction lock

    def _di(self, type_name: str):
        """Resident index for a type (resident mode only). Streaming
        flavor: its internal lock serializes refresh against concurrent
        handler-thread scans. The dict read is the GIL-safe fast path;
        the construction lock only guards first-touch builds (a duplicate
        build would stage the whole dataset into device memory twice).

        First-touch builds run behind the ``cache`` circuit breaker
        (resilience.py): a staging failure (device OOM, store fault)
        degrades the request to the store path — returns None, stamped
        — instead of 500ing, and repeated failures open the breaker so
        requests stop paying the staging attempt until its half-open
        probe. A breaker-gated failure never evicts an ALREADY-staged
        healthy index (the dict hit above short-circuits)."""
        if not self.resident:
            return None
        di = self._resident_cache.get(type_name)
        if di is not None:
            return di
        from geomesa_tpu import resilience

        if not resilience.degrade_allowed():
            return self._build_locked(type_name)[0]
        br = resilience.cache_breaker()
        if not br.allow():
            resilience.note_degraded("cache-breaker-open")
            return None
        try:
            di = self._build_locked(type_name)[0]
        except Exception as e:
            if resilience.classify(e) == resilience.FATAL:
                # unknown type / bad request: surface, not degrade —
                # and free a held half-open probe slot (no health
                # signal either way)
                br.release_probe()
                raise
            br.record_failure()
            resilience.note_degraded("resident-unavailable")
            return None
        br.record_success()
        return di

    @staticmethod
    def _loose(q: dict) -> "bool | None":
        v = q.get("loose")
        return None if v is None else v.lower() in ("1", "true", "yes")

    @staticmethod
    def _auths(q: dict) -> tuple:
        """Request authorizations (``auths=A,B``); absent = none — labeled
        features hide, fail closed, on both serving paths."""
        v = q.get("auths")
        if not v:
            return ()
        return tuple(a for a in (s.strip() for s in v.split(",")) if a)

    @staticmethod
    def _cap(q: dict) -> "int | None":
        """Result cap with interceptor parity, shared by every resident
        endpoint: an EXPLICIT maxFeatures (including 0) overrides the
        global query.max.features, which applies only when the request is
        unbounded (MaxFeaturesInterceptor semantics). None = uncapped."""
        mf = q.get("maxFeatures")
        if mf is not None:
            return max(0, int(mf))  # negatives behave like 0 (plain path)
        from geomesa_tpu.conf import sys_prop

        g = int(sys_prop("query.max.features") or 0)
        return g if g > 0 else None

    def _build_locked(self, type_name: str):
        """First-touch resident build under the construction lock;
        returns (index, built_now). Mesh mode (``mesh.enabled`` or
        ``make_server(mesh=True)``) with more than one visible device
        stages a :class:`~geomesa_tpu.device_cache.ShardedDeviceIndex`
        — the type's planes shard across the serving mesh by global
        Z-key range and every scan launches mesh-wide."""
        cache = self._resident_cache
        with self._resident_lock:
            if type_name in cache:
                return cache[type_name], False
            di = _make_resident_index(
                self.store, type_name, self.mesh,
                streaming=self.stream is not None,
            )
            cache[type_name] = di
            return di, True

    def _observe_resident(self, type_name: str, cql: str, t0, t1, hits):
        """Metrics + audit parity with the store query pipeline (resident
        scans bypass store.query, which would otherwise record these)."""
        try:
            from geomesa_tpu.audit import AuditedEvent
            from geomesa_tpu.metrics import queries_run, query_seconds
            from geomesa_tpu.resilience import current_degraded
            from geomesa_tpu.tracing import current_trace_id

            queries_run.inc(store="resident", type=type_name)
            query_seconds.observe(t1 - t0)
            if self.scheduler is None:
                # unscheduled resident serving: the scheduler would have
                # charged the ledger for this launch — do it here instead
                from geomesa_tpu import ledger

                ledger.charge("device_launches", 1)
                ledger.charge("device_seconds", t1 - t0)
                ledger.charge("fusion_width", 1)
            aw = getattr(self.store, "audit_writer", None)
            if aw is not None:
                aw.write(AuditedEvent(
                    store="resident", type_name=type_name, filter=cql,
                    planning_ms=0.0, scanning_ms=(t1 - t0) * 1e3, hits=hits,
                    trace_id=current_trace_id(),
                    degraded=",".join(current_degraded()),
                ))
        except Exception:  # pragma: no cover - observability must not break  # lint: disable=GT011(audit emission is observability; a failed write must not fail the query it records)
            pass

    # quiet default request logging; hook point for real deployments
    def log_message(self, fmt, *args):  # noqa: D102
        pass

    def _stamp_response_headers(self, code: int, headers=()) -> None:
        """The shared response stamping between ``send_response`` and
        ``end_headers``: ledger status, request-id echo, degradation
        header — identical for buffered and streamed responses."""
        cost = getattr(self, "_cost", None)
        if cost is not None:
            # the ledger/SLO layer classifies good vs bad by this code
            cost.status = code
        tr = getattr(self, "_trace", None)
        if tr is not None:
            # the trace id rides the response whether or not the trace
            # was retained — clients correlate logs by it either way
            self.send_header("X-Request-Id", tr.trace_id)
            tr.root.set(status=code)
        else:
            # untraced paths (parse errors, monitoring endpoints) still
            # echo a sanitized inbound id: a client correlating a 400/
            # 429/5xx against its own logs needs it most on errors
            from geomesa_tpu.tracing import _clean_id

            rid = _clean_id(self.headers.get("X-Request-Id"))
            if rid:
                self.send_header("X-Request-Id", rid)
        reasons = getattr(self, "_degraded", None)
        if reasons:
            # the degradation contract: an approximate or partial answer
            # is never silent — the client can see (and log) the rung
            self.send_header("X-Degraded", ",".join(reasons))
            if tr is not None:
                tr.root.set(degraded=",".join(reasons))
        for name, value in headers:
            self.send_header(name, value)

    def _send(self, code: int, body: bytes, ctype: str, headers=()) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self._stamp_response_headers(code, headers)
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, doc) -> None:
        """JSON answer; numpy arrays and scalars in ``doc`` turn into
        lists and numbers inside the ``http.encode`` stage."""
        from geomesa_tpu.tracing import span

        with span("http.encode", fmt="json"):
            body = json.dumps(doc, default=_tolist).encode("utf-8")
        self._send(code, body, "application/json")

    def _observe_encode(self, fmt: str, enc_s: float, write_s: float,
                        total: int, rows, batches: int) -> None:
        """Fold one response's serialization cost into the ledger
        (GT009 fields), the results metrics, and two SIBLING spans —
        ``http.encode`` (serialization only) and ``http.write`` (socket
        only), split so a slow client can no longer pollute encode
        attribution in the slow-query log or ``/stats/ledger``."""
        import time as _time

        from geomesa_tpu import ledger, metrics
        from geomesa_tpu.tracing import capture, record_span

        now = _time.perf_counter()
        parent = capture()
        record_span(
            parent, "http.encode", now - enc_s - write_s, enc_s,
            fmt=fmt, rows=rows, batches=batches, bytes=total,
        )
        record_span(parent, "http.write", now - write_s, write_s,
                    bytes=total)
        ledger.charge("encode_seconds", enc_s)
        ledger.charge("response_bytes", total)
        metrics.results_encode_seconds.observe(enc_s)
        metrics.results_write_seconds.observe(write_s)
        metrics.results_batches.inc(batches, fmt=fmt)
        metrics.results_bytes.inc(total, fmt=fmt)

    def _send_encoded(self, code: int, body: bytes, ctype: str, fmt: str,
                      enc_s: float, rows=None, headers=()) -> None:
        """Buffered response whose serialization the caller already
        timed (``enc_s``); the socket write is measured here."""
        import time as _time

        t0 = _time.perf_counter()
        self._send(code, body, ctype, headers=headers)
        self._observe_encode(
            fmt, enc_s, _time.perf_counter() - t0, len(body), rows, 1
        )

    @staticmethod
    def _timed_batches(batches, cell: list):
        """Wrap a batch iterator, accumulating time spent PRODUCING
        batches (store partition read/decode on the streamed store
        rung) into ``cell[0]`` — _send_stream subtracts it so
        encode_seconds stays pure serialization time (the store's own
        instrumentation already charges read/decode fields; counting
        those seconds as encode would re-pollute the very attribution
        the encode/write split exists to clean up)."""
        import time as _time

        it = iter(batches)
        try:
            while True:
                t0 = _time.perf_counter()
                b = next(it, None)
                cell[0] += _time.perf_counter() - t0
                if b is None:
                    return
                yield b
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _send_stream(self, code: int, ctype: str, chunks, fmt: str,
                     rows=None, headers=(), upstream: "list | None" = None,
                     ) -> None:
        """Chunked streaming response: the FIRST chunk is produced
        before the status line goes out (late planning/encode errors
        still surface as clean HTTP errors), every later chunk flushes
        to the socket while the next is still assembling. Serialization
        time (pulling the generator) and socket-write time accumulate
        separately for the encode/write span split. A mid-stream
        failure AFTER headers cannot become an error response — the
        chunked stream ends WITHOUT its terminating 0-chunk and the
        connection drops, so clients detect truncation instead of
        parsing a partial result as complete."""
        import time as _time

        it = iter(chunks)
        t0 = _time.perf_counter()
        first = next(it, b"")
        enc = _time.perf_counter() - t0
        if self.request_version < "HTTP/1.1":
            # RFC 9112: never send chunked framing to a 1.0 peer — it
            # would read the hex chunk sizes as body bytes. Buffer the
            # whole stream (the pre-streaming behavior) and close.
            t1 = _time.perf_counter()
            body = first + b"".join(it)
            enc += _time.perf_counter() - t1
            if upstream is not None:
                enc = max(enc - upstream[0], 0.0)
            self.close_connection = True
            return self._send_encoded(
                code, body, ctype, fmt, enc, rows=rows, headers=headers
            )
        write_s = 0.0
        total = 0
        nchunks = 0
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self._stamp_response_headers(code, headers)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        clean = False
        try:
            piece = first
            while True:
                if piece:
                    nchunks += 1
                    t1 = _time.perf_counter()
                    self.wfile.write(b"%x\r\n" % len(piece))
                    self.wfile.write(piece)
                    self.wfile.write(b"\r\n")
                    write_s += _time.perf_counter() - t1
                    total += len(piece)
                t1 = _time.perf_counter()
                piece = next(it, None)
                enc += _time.perf_counter() - t1
                if piece is None:
                    clean = True
                    break
        except BrokenPipeError:
            self.close_connection = True
        except Exception as e:
            # headers are gone: signal truncation, never a fake success
            self.close_connection = True
            tr = getattr(self, "_trace", None)
            if tr is not None:
                tr.root.set(stream_error=f"{type(e).__name__}: {e}")
        finally:
            close = getattr(it, "close", None)
            if close is not None:
                # deterministic teardown on abandonment: the encoder's
                # finally closes its writer and the partition stream
                # joins its prefetch workers NOW, not at GC time
                close()
        if clean:
            try:
                self.wfile.write(b"0\r\n\r\n")
            except BrokenPipeError:
                self.close_connection = True
        if upstream is not None:
            # generator pulls included upstream batch PRODUCTION time
            # (partition read/decode); encode keeps serialization only
            enc = max(enc - upstream[0], 0.0)
        self._observe_encode(fmt, enc, write_s, total, rows, nchunks)

    def _sched_run(self, q: dict, fn=None, fuse=None, device=None):
        """Route one unit of query work through the device query
        scheduler when one is configured (admission control, deadlines,
        micro-batch fusion for compatible resident queries); direct
        execution otherwise. Request knobs: ``lane=interactive|batch``,
        ``tenant=`` (defaults to the client address, the per-tenant
        fairness key), ``deadlineMs=``."""
        sched = self.scheduler
        if sched is None:
            if fn is not None:
                return fn()
            return fuse.run_serial()
        dl = q.get("deadlineMs")
        tenant = q.get("tenant")
        if not tenant and self.client_address:
            tenant = str(self.client_address[0])
        kw = {}
        if dl:  # absent: the scheduler's configured default applies
            kw["deadline_ms"] = float(dl)
        return sched.run(
            fn=fn,
            fuse=fuse,
            lane=q.get("lane", "interactive"),
            tenant=tenant or "",
            device=device,
            **kw,
        )

    def _degradable(self, q: dict, reason: str, fallback, fn=None,
                    fuse=None):
        """Run device-rung work with the full fault discipline: the
        ``device`` circuit breaker gates entry (open -> straight to the
        fallback rung, stamped — nobody queues behind a dead device),
        transient faults retry with jittered backoff
        (``resilience.retries``), and a non-retryable / still-failing
        launch falls to ``fallback`` with ``reason`` noted. Flow-control
        signals (429/504) and FATAL faults (bad requests) always
        propagate — backpressure and errors are part of the client
        contract, not something to degrade away. The fallback runs
        OUTSIDE the scheduler by design: it is the emergency rung, and
        the scheduler meters the device it no longer touches."""
        from geomesa_tpu import resilience
        from geomesa_tpu.sched import DeadlineExpired, RejectedError

        if not resilience.enabled():
            return self._sched_run(q, fn=fn, fuse=fuse, device=True)
        br = resilience.device_breaker()
        can_fall = fallback is not None and resilience.degrade_allowed()
        if can_fall and not br.allow():
            resilience.note_degraded("device-breaker-open")
            return fallback()
        try:
            res = resilience.retry_call(
                lambda: self._sched_run(q, fn=fn, fuse=fuse, device=True),
                domain="device",
            )
        except (RejectedError, DeadlineExpired):
            # a shed/expired half-open probe carried no health signal:
            # free the slot so the next caller probes immediately, or a
            # saturated queue would pin the breaker half-open (and all
            # traffic on the degraded rung) one full cooldown per shed
            if can_fall:
                br.release_probe()
            raise
        except Exception as e:
            if resilience.classify(e) == resilience.FATAL:
                # a bad REQUEST says nothing about device health: free
                # a held half-open probe slot instead of pinning the
                # breaker (and all traffic on the degraded rung) for
                # another cooldown
                if can_fall:
                    br.release_probe()
                raise
            stuck = isinstance(e, resilience.LaunchStuckError)
            if not stuck:
                # the watchdog already charged the stuck launch to the
                # breaker — once per FAULT; re-recording here would add
                # one count per fused rider and open the breaker after
                # a single wedged group
                br.record_failure()
            if not can_fall:
                raise
            resilience.note_degraded("launch-stuck" if stuck else reason)
            return fallback()
        br.record_success()
        return res

    def do_GET(self) -> None:  # noqa: N802 (stdlib API)
        try:
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            q = {k: v[0] for k, v in parse_qs(url.query).items()}
        except Exception as e:
            # clear ALL per-request state: on a keep-alive connection
            # this handler instance served the previous request, and a
            # stale cost/degraded carry-over would mis-stamp this 400
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._json(400, {"error": str(e)})
        # observability endpoints are not themselves traced — scrapes,
        # trace reads and the stats snapshots must not churn the trace
        # ring (a monitoring poll would evict real query traces).
        # /stats/<type> with a real type name IS a query and stays
        # traced; the same disambiguation _dispatch routes by.
        untraced = (
            parts and parts[0] in ("metrics", "debug", "healthz", "readyz")
        ) or (
            parts == ["stats", "sched"] and self.scheduler is not None
        ) or (
            parts == ["stats", "store"]
            and hasattr(self.store, "store_stats")
        ) or parts == ["stats", "mesh"] or parts == ["stats", "slo"] \
            or parts == ["stats", "ledger"] or parts == ["stats", "stream"] \
            or parts == ["stats", "replica"] or parts[:1] == ["wal"] \
            or parts[:1] == ["snapshot"] or parts == ["stats"] \
            or parts == ["stats", "pubsub"] or parts[:1] == ["subscribe"]
        if untraced:
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._dispatch_safe(url, parts, q)
        from geomesa_tpu import ledger, resilience
        from geomesa_tpu.tracing import TRACER, span

        tenant = q.get("tenant") or (
            str(self.client_address[0]) if self.client_address else ""
        )
        # error handling lives INSIDE the trace: the error response is
        # sent (status attr stamped, its time counted) before the trace
        # finishes and retention / the slow-query log fire. The
        # degradation collector wraps the same scope: any layer that
        # answers below the requested rung notes a reason here, and the
        # response/audit stamping reads it back. The cost collector
        # rides along too — it is finalized AFTER the trace completes
        # (the span tree is whole at that point) and folded into the
        # process ledger + the SLO engine's latency windows. The
        # http.request stage wraps the whole traced request, to its
        # last byte written; the trace's root span is its tree node.
        with span("http.request"), TRACER.trace(
            f"GET {url.path}",
            trace_id=self.headers.get("X-Request-Id"),
            attrs={"path": url.path, "query": url.query[:512]},
        ) as tr, resilience.collect_degraded() as reasons, \
                ledger.collect_cost(
                    tenant=tenant,
                    endpoint=_cost_endpoint(parts),
                    lane=q.get("lane", "interactive"),
                    shape=_query_shape(parts, q),
                ) as cost:
            self._trace = tr
            self._degraded = reasons
            self._cost = cost
            if cost is not None:
                # stamped NOW (not at finish) so a mid-request compile
                # ledger entry can name the trace that blocked on it
                cost.trace_id = tr.trace_id
            self._dispatch_safe(url, parts, q)
        ledger.finish_request(cost, tr)

    def _admin_authorized(self) -> bool:
        """Gate for operator-plane endpoints (``/admin/*``). With
        ``admin.token`` set, the caller must present the exact shared
        secret in ``X-Admin-Token`` (compared constant-time). With no
        token configured the plane stays usable for local tooling but
        only from loopback peers — a reachable serving port must not
        expose an unauthenticated kill switch."""
        import hmac

        from geomesa_tpu.conf import sys_prop

        token = str(sys_prop("admin.token"))
        if token:
            offered = self.headers.get("X-Admin-Token") or ""
            return hmac.compare_digest(offered, token)
        peer = str(self.client_address[0]) if self.client_address else ""
        return peer in ("127.0.0.1", "::1", "::ffff:127.0.0.1")

    def do_POST(self) -> None:  # noqa: N802 (stdlib API)
        """POST ``/append/<type>``: the streaming-ingest endpoint. Body
        ``{"columns": {...}, "fids": [...], "visibilities": [...]}``;
        the response acks rows that are WAL-durable and queryable NOW
        (no flush/restage on this path). Backpressure surfaces as 429 +
        Retry-After — from the scheduler's admission bound or the live
        layer's ``wal.max.generations`` read-amplification bound."""
        from geomesa_tpu.conf import sys_prop

        try:
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            q = {k: v[0] for k, v in parse_qs(url.query).items()}
            length = int(self.headers.get("Content-Length") or 0)
            cap = int(sys_prop("stream.append.max.bytes"))
            if cap and length > cap:
                # bounded-everything discipline: one append becomes one
                # WAL record and one memtable run — refuse BEFORE
                # buffering (nothing is read, nothing is acked)
                self._trace = None
                self._degraded = None
                self._cost = None
                return self._json(413, {
                    "error": f"append body {length} bytes exceeds "
                             f"stream.append.max.bytes={cap}"
                })
            body = self.rfile.read(length) if length else b""
        except Exception as e:
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._json(400, {"error": str(e)})
        if parts == ["admin", "shutdown"]:
            # the fleet-restart drain trigger: respond FIRST (the
            # orchestrator needs the ack), then run the draining
            # shutdown off-thread — shutdown() joins in-flight work
            # and would deadlock the handler thread serving this very
            # request
            self._trace = None
            self._degraded = None
            self._cost = None
            if not self._admin_authorized():
                return self._json(403, {
                    "error": "admin endpoint refused: present the "
                             "X-Admin-Token header (admin.token), or "
                             "call from loopback when no token is "
                             "configured"
                })
            self._json(200, {"draining": True})
            spawn_thread(
                self.server.shutdown, name="admin-shutdown", context=False
            ).start()
            return
        if len(parts) == 2 and parts[0] == "subscribe":
            # subscription CRUD is control-plane traffic: untraced (like
            # the ship endpoints), leader-pinned (the registry WAL must
            # not fork), replicated to followers via /wal/_pubsub
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._run_safe(
                lambda: self._subscribe_post(parts, q, body), parts, q
            )
        if len(parts) != 2 or parts[0] != "append":
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._json(
                404, {"error": f"no such POST endpoint {url.path!r}"}
            )
        # appends default to the dedicated ingest lane (top priority:
        # sub-ms host work must not queue behind device scans)
        q.setdefault("lane", "ingest")
        from geomesa_tpu import ledger, resilience
        from geomesa_tpu.tracing import TRACER, span

        tenant = q.get("tenant") or (
            str(self.client_address[0]) if self.client_address else ""
        )
        with span("http.request"), TRACER.trace(
            f"POST {url.path}",
            trace_id=self.headers.get("X-Request-Id"),
            attrs={"path": url.path, "bytes": len(body)},
        ) as tr, resilience.collect_degraded() as reasons, \
                ledger.collect_cost(
                    tenant=tenant,
                    endpoint="append",
                    lane=q["lane"],
                    shape="append",
                ) as cost:
            self._trace = tr
            self._degraded = reasons
            self._cost = cost
            if cost is not None:
                cost.trace_id = tr.trace_id
            self._run_safe(
                lambda: self._append_post(parts, q, body), parts, q
            )
        ledger.finish_request(cost, tr)

    def _append_post(self, parts: list, q: dict, body: bytes) -> None:
        from geomesa_tpu.features.batch import FeatureBatch

        type_name = unquote(parts[1])
        if self._draining():
            return self._send(
                503,
                json.dumps(
                    {"error": "server is draining"}
                ).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        rep = self.replica
        if rep is not None and not rep.is_leader():
            # appends pin to the leader: a follower taking writes would
            # fork the WAL seq space. 503 + Retry-After (not 4xx) —
            # during promotion the SAME url becomes writable, so the
            # client/router should retry, not give up
            # the bounce carries the epoch alongside the leader url so
            # the router/load-driver re-discover in one hop, without a
            # /stats/replica round trip — and can ignore a bounce from
            # a staler epoch than one they already followed
            return self._send(
                503,
                json.dumps({
                    "error": "not the leader "
                             f"(role={rep.role}); appends go to the "
                             "leader",
                    "leader": rep.leader_url,
                    "epoch": int(rep.epoch),
                }).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        stream = self.stream
        if stream is None:
            return self._json(
                400,
                {"error": "server is not running with the streaming "
                          "live layer (stream.enabled / serve --stream)"},
            )
        doc = json.loads(body.decode("utf-8")) if body else {}
        cols = doc.get("columns")
        if not isinstance(cols, dict) or not cols:
            raise ValueError(
                'append body needs {"columns": {...}, "fids": [...]}'
            )
        sft = self.store.get_schema(type_name)  # KeyError -> 404
        batch = FeatureBatch.from_columns(sft, cols, doc.get("fids"))
        vis = doc.get("visibilities")
        if vis is not None:
            batch = batch.with_visibility(vis)
        res = self._sched_run(
            q, fn=lambda: stream.append(type_name, batch)
        )
        replicated = None
        if rep is not None and rep.ack_mode() == "replica" \
                and int(res["rows"]):
            from geomesa_tpu.conf import sys_prop
            from geomesa_tpu.resilience import note_degraded

            replicated = rep.await_replicated(
                type_name, int(res["seq"]),
                float(sys_prop("replica.ack.timeout.s")),
            )
            if not replicated:
                # acked local-durable only: rows are WAL-safe here but
                # a leader loss before ship could lose them — stamp the
                # response degraded instead of failing a durable write
                note_degraded("replica-lag")
        doc = {"acked": int(res["rows"]), "seq": int(res["seq"])}
        if rep is not None:
            # fencing token: a client (or router) holding a higher
            # epoch from elsewhere can spot a stale leader in the ack
            doc["epoch"] = int(rep.epoch)
        if replicated is not None:
            doc["replicated"] = bool(replicated)
        self._json(200, doc)

    # -- continuous queries (the pubsub push tier) -------------------------

    def _pubsub_hub(self):
        if self.pubsub is None:
            raise ValueError(
                "server is not running the continuous-query push tier "
                "(needs the streaming live layer: stream.enabled / "
                "serve --stream)"
            )
        return self.pubsub

    def _subscribe_post(self, parts: list, q: dict, body: bytes) -> None:
        """POST ``/subscribe/<type>``: register a standing continuous
        query. Body: any of ``{"bbox": [...], "cql": "...", "dwithin":
        {"x","y","distance"}, "auths": [...]}``. The response carries
        the subscription id and its initial cursor (the data-WAL seq it
        is armed from). Leader-pinned: the registry WAL replicates to
        followers, so the same 503 + leader bounce as appends."""
        hub = self._pubsub_hub()
        if self._draining():
            return self._send(
                503,
                json.dumps({"error": "server is draining"}).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        rep = self.replica
        if rep is not None and not rep.is_leader():
            return self._send(
                503,
                json.dumps({
                    "error": "not the leader "
                             f"(role={rep.role}); subscriptions go to "
                             "the leader",
                    "leader": rep.leader_url,
                    "epoch": int(rep.epoch),
                }).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        type_name = unquote(parts[1])
        doc = json.loads(body.decode("utf-8")) if body else {}
        tenant = q.get("tenant") or (
            str(self.client_address[0]) if self.client_address else ""
        )
        auths = doc.get("auths")
        if auths is None:
            auths = self._auths(q)
        out = hub.subscribe(type_name, doc, tenant=tenant, auths=auths)
        if rep is not None:
            out["epoch"] = int(rep.epoch)
        self._json(200, out)

    def do_DELETE(self) -> None:  # noqa: N802 (stdlib API)
        """DELETE ``/subscribe/<type>?id=<sub>``: cancel a standing
        subscription (leader-pinned, replicated like registration)."""
        try:
            url = urlparse(self.path)
            parts = [p for p in url.path.split("/") if p]
            q = {k: v[0] for k, v in parse_qs(url.query).items()}
        except Exception as e:
            self._trace = None
            self._degraded = None
            self._cost = None
            return self._json(400, {"error": str(e)})
        self._trace = None
        self._degraded = None
        self._cost = None
        if len(parts) != 2 or parts[0] != "subscribe":
            return self._json(
                404, {"error": f"no such DELETE endpoint {url.path!r}"}
            )
        return self._run_safe(
            lambda: self._subscribe_delete(parts, q), parts, q
        )

    def _subscribe_delete(self, parts: list, q: dict) -> None:
        hub = self._pubsub_hub()
        rep = self.replica
        if rep is not None and not rep.is_leader():
            return self._send(
                503,
                json.dumps({
                    "error": f"not the leader (role={rep.role})",
                    "leader": rep.leader_url,
                    "epoch": int(rep.epoch),
                }).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        sub_id = q.get("id")
        if not sub_id:
            raise ValueError("DELETE /subscribe/<type> needs ?id=<sub>")
        if not hub.cancel(sub_id):
            raise KeyError(sub_id)
        self._json(200, {"cancelled": sub_id})

    def _subscribe_stream(self, type_name: str, q: dict) -> None:
        """GET ``/subscribe/<type>?id=&from=&f=``: the long-lived push
        stream. ``from`` (or the SSE ``Last-Event-ID`` header) is the
        subscriber's acked seq watermark — delivery resumes exactly-once
        above it; omitted, it defaults to the subscription's creation
        cursor. Formats ride the results plane: geojson = SSE ``match``
        events with ``:keepalive`` heartbeats, arrow = IPC stream with a
        ``match_seq`` column, bin = track records (resume via explicit
        ``from=``)."""
        from geomesa_tpu.conf import sys_prop
        from geomesa_tpu.pubsub import CursorGoneError
        from geomesa_tpu.pubsub.delivery import (
            arrow_push_chunks,
            bin_push_chunks,
            sse_chunks,
        )
        from geomesa_tpu.results import PUSH_CONTENT_TYPES, negotiate_format

        hub = self._pubsub_hub()
        if self._draining():
            return self._send(
                503,
                json.dumps({"error": "server is draining"}).encode("utf-8"),
                "application/json",
                headers=(("Retry-After", "1"),),
            )
        sub_id = q.get("id")
        if not sub_id:
            raise ValueError("GET /subscribe/<type> needs ?id=<sub>")
        sub = hub.registry.get(sub_id)
        if sub is None or sub.type_name != type_name:
            raise KeyError(sub_id)
        fmt = negotiate_format(q, self.headers.get("Accept"))
        frm = q.get("from")
        if frm is None:
            frm = self.headers.get("Last-Event-ID")
        from_seq = int(frm) if frm is not None else int(sub.created_seq)
        sft = self.store.get_schema(type_name)
        try:
            events = hub.events(
                type_name, sub_id, from_seq,
                float(sys_prop("sub.heartbeat.s")),
            )
        except CursorGoneError as e:
            return self._json(410, {"error": str(e)})
        # a push connection is idle ON PURPOSE between matches: exempt
        # it from the keep-alive reap (heartbeats bound detection of a
        # dead peer instead) and never reuse the socket afterwards
        self.connection.settimeout(None)
        self.close_connection = True
        if fmt == "arrow":
            chunks = arrow_push_chunks(events, sft)
        elif fmt == "bin":
            track = q.get("track") or sft.attribute_names[0]
            chunks = bin_push_chunks(events, track)
        else:
            chunks = sse_chunks(events, type_name, sub_id)
        ctype = PUSH_CONTENT_TYPES[fmt]
        self._send_stream(
            200, ctype, self._deliver_guard(chunks, sub), fmt,
            headers=(("Cache-Control", "no-cache"),),
        )

    def _deliver_guard(self, chunks, sub):
        """Per-chunk delivery wrapper: the ``fail.sub.deliver`` fault
        hook plus byte accounting charged to the subscriber tenant."""
        from geomesa_tpu import ledger, metrics
        from geomesa_tpu.failpoints import fail_point

        sent = 0
        try:
            for piece in chunks:
                fail_point("fail.sub.deliver")
                sent += len(piece)
                yield piece
        finally:
            if sent:
                metrics.pubsub_deliver_bytes.inc(float(sent))
                if ledger.enabled():
                    cost = ledger.RequestCost(
                        tenant=sub.tenant,
                        endpoint="subscribe",
                        lane="interactive",
                        shape="push-stream",
                    )
                    cost.status = 200
                    cost.charge("sub_deliver_bytes", float(sent))
                    ledger.LEDGER.record(cost)

    def _registry_ship(self, q: dict) -> None:
        """``GET /wal/_pubsub?from=``: ship the subscription-registry
        WAL to followers. Same framing as the data ship, but the
        registry log is never truncated (bounded by subscription churn)
        so there is no watermark and no 410 — a follower can always
        catch up from any position."""
        from geomesa_tpu.store.wal import pack_record

        hub = self._pubsub_hub()
        wal = hub.registry.wal
        frm = max(int(q.get("from", 0)), 0)
        rep = self.replica
        if rep is not None:
            try:
                rep.observe_epoch(int(q.get("epoch", 0)))
            except (TypeError, ValueError):
                pass
        nxt = int(wal.next_seq)

        def chunks():
            buf = bytearray()
            for seq, payload in wal.read_from(frm - 1):
                if seq >= nxt:
                    break
                buf += pack_record(seq, payload)
                if len(buf) >= (512 << 10):
                    yield bytes(buf)
                    buf.clear()
            if buf:
                yield bytes(buf)

        role = rep.role if rep is not None else "leader"
        self._send_stream(
            200, "application/x-geomesa-wal", chunks(), "wal",
            headers=(
                ("X-Wal-Next-Seq", str(nxt)),
                ("X-Wal-Watermark", "-1"),
                ("X-Replica-Role", role),
                ("X-Replica-Epoch",
                 str(rep.epoch if rep is not None else 0)),
            ),
        )

    def _audit_outcome(self, parts: list, q: dict, outcome: str) -> None:
        """Stamp a shed (429) or deadline-expired (504) request into the
        audit log — operators sizing admission need the requests that
        did NOT run, not just the ones that did. Best-effort: auditing
        must never break the error response it annotates."""
        try:
            aw = getattr(self.store, "audit_writer", None)
            if aw is None:
                return
            from geomesa_tpu.audit import AuditedEvent
            from geomesa_tpu.resilience import current_degraded
            from geomesa_tpu.tracing import current_trace_id

            aw.write(AuditedEvent(
                store="server",
                type_name=parts[1] if len(parts) > 1 else "",
                filter=q.get("cql", ""),
                hits=0,
                trace_id=current_trace_id(),
                outcome=outcome,
                degraded=",".join(current_degraded()),
            ))
        except Exception:  # pragma: no cover - observability must not break  # lint: disable=GT011(audit emission is observability; a failed write must not fail the query it records)
            pass

    def _dispatch_safe(self, url, parts: list, q: dict) -> None:
        return self._run_safe(
            lambda: self._dispatch(url, parts, q), parts, q
        )

    def _run_safe(self, fn, parts: list, q: dict) -> None:
        try:
            return fn()
        except KeyError as e:
            self._json(404, {"error": f"unknown schema or attribute {e}"})
        except ValueError as e:
            self._json(400, {"error": str(e)})
        except BrokenPipeError:
            pass
        except Exception as e:
            from geomesa_tpu.sched import DeadlineExpired, RejectedError
            from geomesa_tpu.store.stream import WalUnavailableError

            if isinstance(e, WalUnavailableError):
                # the wal breaker is open: appends fail fast until its
                # half-open probe — 503 says "not you, come back"
                return self._send(
                    503,
                    json.dumps({"error": str(e)}).encode("utf-8"),
                    "application/json",
                    headers=(("Retry-After", "1"),),
                )
            if isinstance(e, RejectedError):
                # backpressure: shed load explicitly instead of queueing
                # unboundedly; clients should honor Retry-After (derived
                # from live queue depth + drain rate, jittered — see
                # QueryScheduler._retry_after_locked)
                self._audit_outcome(parts, q, "shed")
                return self._send(
                    429,
                    json.dumps({"error": str(e)}).encode("utf-8"),
                    "application/json",
                    # RFC 9110 delta-seconds is integral: standard client
                    # retry machinery (urllib3 et al.) rejects fractions.
                    # Ceil keeps the estimate an upper bound; the jitter
                    # survives rounding at multi-second queue depths
                    headers=(
                        ("Retry-After", str(math.ceil(e.retry_after_s))),
                    ),
                )
            if isinstance(e, DeadlineExpired):
                self._audit_outcome(parts, q, "deadline-expired")
                return self._json(504, {"error": str(e)})
            self._json(500, {"error": f"{type(e).__name__}: {e}"})

    def _draining(self) -> bool:
        ev = getattr(self.server, "draining", None)
        return ev is not None and ev.is_set()

    def _healthz(self) -> None:
        """Liveness: 200 for as long as the process is up — INCLUDING
        while draining. Failing liveness makes an orchestrator KILL the
        instance (restart, not de-route), which would lose exactly the
        in-flight work the draining shutdown exists to finish; traffic
        removal is ``/readyz``'s job, and it flips 503 the moment
        draining starts."""
        self._json(
            200, {"status": "draining" if self._draining() else "ok"}
        )

    def _readyz(self) -> None:
        """Readiness, driven by breaker state: the body reports every
        failure domain's breaker, the open (unhealthy) domains,
        scheduler queue pressure and any BURNING SLOs. A DEGRADED or
        burning instance is still READY (200) — it serves, just
        lower-rung or over budget, and says so; only draining,
        mid-reprovision, and (with ``compile.warmup.gate=ready``) a
        still-running AOT warmup pass flip 503 (nothing new should be
        routed here)."""
        from geomesa_tpu import resilience, slo

        breakers = resilience.snapshot()
        degraded = sorted(
            d for d, s in breakers.items()
            if isinstance(s, dict) and s.get("state") != "closed"
        )
        if breakers.get("partition_open"):
            degraded.append("partition")
        # burning SLOs are degraded DETAIL, never unready: pulling a
        # burning instance from rotation would shift its load onto the
        # others and burn THEIR budgets faster
        burning = slo.ENGINE.burning() if slo.enabled() else []
        doc = {
            "ready": not self._draining(),
            "draining": self._draining(),
            "degraded_domains": degraded,
            "slo_burning": burning,
            "breakers": breakers,
        }
        if self.scheduler is not None:
            queued, max_queue = self.scheduler.queue_pressure()
            doc["sched"] = {"queued": queued, "max_queue": max_queue}
        if self.replica is not None:
            # the router's health poll keys append-routing off this
            doc["replica_role"] = self.replica.role
            inst = self.replica.reprovisioning
            if inst:
                # mid-reprovision this node's store is being swapped
                # out from under its query surface: not-ready, so the
                # router routes reads to healthy replicas until the
                # install finishes and lag returns to 0
                doc["ready"] = False
                doc["reprovisioning"] = inst
        if getattr(self, "_warmup_started", False):
            from geomesa_tpu import warmup
            from geomesa_tpu.conf import sys_prop

            gate = str(sys_prop("compile.warmup.gate"))
            if gate != "off" and warmup.warming():
                # the AOT pre-compile pass over the bucket x
                # kernel-family set is still running: gate="ready"
                # holds readiness so a rolling restart (fleet
                # wait_ready) never routes traffic at a cold process;
                # gate="stamp" serves immediately but says so
                doc["warming"] = True
                if gate == "ready":
                    doc["ready"] = False
        self._json(200 if doc["ready"] else 503, doc)

    def _dispatch(self, url, parts: list, q: dict) -> None:
        if parts == ["capabilities"]:
            return self._capabilities()
        if parts == ["healthz"]:
            return self._healthz()
        if parts == ["readyz"]:
            return self._readyz()
        if parts == ["metrics"]:
            from geomesa_tpu.metrics import REGISTRY

            # content negotiation: exemplars (trace-id suffixes) are
            # only valid in the OpenMetrics format — the classic 0.0.4
            # parser would fail the WHOLE scrape on one suffixed line
            om = "application/openmetrics-text" in (
                self.headers.get("Accept") or ""
            )
            return self._send(
                200,
                REGISTRY.prometheus_text(openmetrics=om).encode("utf-8"),
                "application/openmetrics-text; version=1.0.0; "
                "charset=utf-8" if om else "text/plain; version=0.0.4",
            )
        if parts[:2] == ["debug", "traces"]:
            return self._debug_traces(parts, q)
        if parts == ["stats", "sched"] and self.scheduler is not None:
            return self._json(200, self.scheduler.snapshot())
        if parts == ["stats", "store"] and hasattr(
            self.store, "store_stats"
        ):
            return self._json(200, self.store.store_stats())
        if parts == ["stats", "mesh"]:
            return self._json(200, self._mesh_stats())
        if parts == ["stats", "slo"]:
            from geomesa_tpu import slo

            return self._json(200, slo.ENGINE.snapshot())
        if parts == ["stats", "ledger"]:
            from geomesa_tpu.ledger import LEDGER

            return self._json(200, LEDGER.snapshot())
        if parts == ["stats", "stream"]:
            return self._json(
                200,
                self.stream.stream_stats()
                if self.stream is not None
                else {"enabled": False},
            )
        if parts == ["stats", "replica"]:
            return self._json(
                200,
                self.replica.stats()
                if self.replica is not None
                else {"enabled": False},
            )
        if parts == ["stats", "pubsub"]:
            return self._json(
                200,
                self.pubsub.stats()
                if self.pubsub is not None
                else {"enabled": False},
            )
        if len(parts) == 2 and parts[0] == "subscribe":
            # the long-lived push stream (SSE/arrow/bin); served by ANY
            # replica — matching runs off the local WAL feed
            return self._subscribe_stream(unquote(parts[1]), q)
        if parts == ["stats"]:
            return self._json(200, self._stats_index())
        if len(parts) == 2 and parts[0] == "wal":
            # replication shipping stays OPEN while draining: the fleet
            # restart drains a leader exactly so followers can catch up
            return self._wal_ship(unquote(parts[1]), q)
        if len(parts) == 2 and parts[0] == "snapshot":
            # snapshot bootstrap stays OPEN while draining too: a
            # reprovisioning follower mid-download must be able to
            # finish against a draining leader
            return self._snapshot_ship(unquote(parts[1]), q)
        if len(parts) == 2 and parts[0] in (
            "features", "count", "explain", "density", "stats",
            "refresh", "knn", "tube", "proximity",
        ):
            if self._draining():
                # admission is closed: a draining instance finishes
                # what it has, it does not take on more
                return self._send(
                    503,
                    json.dumps(
                        {"error": "server is draining"}
                    ).encode("utf-8"),
                    "application/json",
                    headers=(("Retry-After", "1"),),
                )
            handler = getattr(self, f"_{parts[0]}")
            return handler(unquote(parts[1]), q)
        self._json(404, {"error": f"no such endpoint {url.path!r}"})

    def _mesh_stats(self) -> dict:
        """``/stats/mesh``: serving-mesh topology + per-type shard
        residency (rows, bytes, Z-key range and build engine per shard)
        for every mesh-resident type staged so far."""
        import jax

        doc: dict = {
            "enabled": bool(self.mesh),
            "devices_visible": len(jax.devices()),
            "types": {},
        }
        for name, di in list(self._resident_cache.items()):
            stats = getattr(di, "mesh_stats", None)
            if stats is not None:
                doc["types"][name] = stats()
        return doc

    def _wal_ship(self, type_name: str, q: dict) -> None:
        """``GET /wal/<type>?from=<seq>&waitMs=&follower=`` — the
        replication ship endpoint: a chunked stream of checksummed WAL
        records (the on-disk framing, ``pack_record``) with
        ``seq >= from``, read through the never-mutating
        :meth:`~geomesa_tpu.store.wal.WriteAheadLog.read_from` cursor —
        safe against the live appender, and servable by ANY replica
        (an election loser tails the winner before it even promotes).
        ``waitMs`` long-polls an empty log so followers ride one
        request per batch instead of hot-polling; ``follower`` is the
        caller's advertised URL, folded into the leader's applied-seq
        accounting (``replica.ack=replica``). 410 Gone when the
        requested position was compacted away below the watermark —
        tailing cannot help; the follower must re-provision from a
        snapshot."""
        import time as _time

        from geomesa_tpu import ledger, metrics
        from geomesa_tpu.conf import sys_prop
        from geomesa_tpu.store.wal import pack_record

        stream = self.stream
        if stream is None:
            return self._json(
                400,
                {"error": "server is not running with the streaming "
                          "live layer (stream.enabled / serve --stream)"},
            )
        from geomesa_tpu.pubsub import REGISTRY_SHIP_NAME

        if type_name == REGISTRY_SHIP_NAME:
            # the subscription registry ships through the same endpoint
            # as a reserved pseudo-type (no schema, never truncated)
            return self._registry_ship(q)
        self.store.get_schema(type_name)  # KeyError -> 404
        ts = stream._ts(type_name)
        frm = max(int(q.get("from", 0)), 0)
        after = frm - 1
        wait_ms = min(max(float(q.get("waitMs", 0.0)), 0.0), 30_000.0)
        rep = self.replica
        if rep is not None:
            rep.note_follower(q.get("follower", ""), type_name, after)
            try:
                rep.observe_epoch(int(q.get("epoch", 0)))
            except (TypeError, ValueError):
                pass
        watermark = int(self.store._types[type_name].wal_watermark)
        if frm <= watermark:
            first = ts.wal.first_seq()
            if first < 0 or frm < first:
                # compaction GC'd the asked-for records: they live only
                # in the partition files now, which shipping cannot
                # replay — the follower needs a snapshot re-provision
                return self._json(410, {
                    "error": f"WAL records below seq {first} were "
                             "compacted away; re-provision this "
                             "follower from a store snapshot",
                    "first_seq": first,
                    "watermark": watermark,
                })
        # long-poll BEFORE the headers: X-Wal-Next-Seq must reflect the
        # position the stream actually serves through. next_seq is a
        # GIL-safe int read — no segment scan while waiting.
        deadline = _time.monotonic() + wait_ms / 1e3
        while (
            ts.wal.next_seq <= frm
            and _time.monotonic() < deadline
            and not self._draining()
        ):
            _time.sleep(0.01)
        nxt = int(ts.wal.next_seq)
        state = {"bytes": 0, "records": 0}

        def chunks():
            buf = bytearray()
            prev = after
            for seq, payload in ts.wal.read_from(after):
                if seq >= nxt:
                    break  # a fixed upper bound keeps the stream finite
                if seq > prev + 1 and prev >= frm:
                    # a segment vanished mid-walk (compaction racing the
                    # cursor): never ship across the hole — ending the
                    # stream early makes the follower re-ask from its
                    # true position and hit the 410/gap machinery
                    break
                prev = seq
                buf += pack_record(seq, payload)
                state["records"] += 1
                if len(buf) >= (512 << 10):
                    state["bytes"] += len(buf)
                    yield bytes(buf)
                    buf.clear()
            if buf:
                state["bytes"] += len(buf)
                yield bytes(buf)

        role = rep.role if rep is not None else "leader"
        self._send_stream(
            200, "application/x-geomesa-wal", chunks(), "wal",
            headers=(
                ("X-Wal-Next-Seq", str(nxt)),
                ("X-Wal-Watermark", str(watermark)),
                ("X-Replica-Role", role),
                ("X-Replica-Epoch",
                 str(rep.epoch if rep is not None else 0)),
            ),
        )
        if state["records"]:
            metrics.replica_ship_records.inc(state["records"])
            metrics.replica_ship_bytes.inc(state["bytes"])
            if ledger.enabled():
                cost = ledger.RequestCost(
                    tenant="_system", endpoint="wal", lane="batch",
                    shape="wal-ship",
                )
                cost.status = 200
                cost.charge("replica_ship_bytes", state["bytes"])
                ledger.LEDGER.record(cost)

    def _snapshot_ship(self, type_name: str, q: dict) -> None:
        """``GET /snapshot/<type>[?id=&from_file=]`` — the snapshot
        bootstrap endpoint: captures a consistent, GC-pinned snapshot
        of the type's published generation under the publish lock and
        ships it as a chunked stream of length-prefixed, checksummed
        file records (store/snapshot.py framing; the manifest ships
        last, the same order the installer publishes in). ``id`` +
        ``from_file`` resume an earlier stream off its still-pinned
        snapshot, skipping files already landed; 410 Gone when that pin
        was released or aged out (``snapshot.pin.ttl.s``) — the client
        restarts with a fresh capture. The pin is released when the
        stream completes; a truncated stream leaves it for the resume
        or the TTL sweep. Role/epoch ride the response headers so a
        reprovisioning follower can refuse a snapshot seeded by a
        stale leader."""
        from geomesa_tpu import ledger, metrics
        from geomesa_tpu.store import snapshot as snapshot_mod

        stream = self.stream
        if stream is None:
            return self._json(
                400,
                {"error": "server is not running with the streaming "
                          "live layer (stream.enabled / serve --stream)"},
            )
        self.store.get_schema(type_name)  # KeyError -> 404
        store = stream.store
        sid = str(q.get("id", "") or "")
        try:
            from_file = max(int(q.get("from_file", 0) or 0), 0)
        except (TypeError, ValueError):
            from_file = 0
        if sid:
            doc = snapshot_mod.load_pin(store, type_name, sid)
            if doc is None:
                return self._json(410, {
                    "error": f"snapshot {sid!r} was released or its "
                             "pin aged out; restart with a fresh "
                             "GET /snapshot",
                })
            # the resumed stream holds the pin live again
            store._active_pins.add((type_name, sid))
        else:
            doc = snapshot_mod.capture(store, type_name)
            sid = doc["snapshot_id"]
            from_file = 0
        rep = self.replica
        role = rep.role if rep is not None else "leader"
        state = {"bytes": 0, "done": False}

        def chunks():
            try:
                for b in snapshot_mod.iter_stream(
                    store, type_name, doc, from_file=from_file
                ):
                    state["bytes"] += len(b)
                    yield b
                state["done"] = True
            finally:
                if state["done"]:
                    # complete hand-off: unpin, GC may reclaim on the
                    # next sweep
                    snapshot_mod.release(store, type_name, sid)
                else:
                    # truncated (client gone, disk error, failpoint):
                    # the on-disk pin stays for a resume, but this
                    # process stops holding it live — an abandoned
                    # stream's pin ages out under snapshot.pin.ttl.s
                    store._active_pins.discard((type_name, sid))

        self._send_stream(
            200, snapshot_mod.SNAPSHOT_CONTENT_TYPE, chunks(),
            "snapshot",
            headers=(
                ("X-Snapshot-Id", sid),
                ("X-Wal-Watermark", str(int(doc.get("wal_watermark", -1)))),
                ("X-Snapshot-Files", str(len(doc.get("files", ())))),
                ("X-Replica-Role", role),
                ("X-Replica-Epoch",
                 str(rep.epoch if rep is not None else 0)),
            ),
        )
        if state["bytes"]:
            metrics.snapshot_ship_bytes.inc(state["bytes"])
            if state["done"]:
                metrics.snapshot_ship_files.inc(
                    max(len(doc.get("files", ())) - from_file, 0)
                )
            if ledger.enabled():
                cost = ledger.RequestCost(
                    tenant="_system", endpoint="snapshot", lane="batch",
                    shape="snapshot-ship",
                )
                cost.status = 200 if state["done"] else 499
                cost.charge("snapshot_ship_bytes", state["bytes"])
                ledger.LEDGER.record(cost)

    def _stats_index(self) -> dict:
        """``/stats``: one roll-up document — scheduler, store, mesh,
        SLO engine, cost ledger, the persistent compile cache
        (hit/miss) and AOT warmup progress in a single scrape."""
        from geomesa_tpu import slo, warmup
        from geomesa_tpu.jaxconf import compile_cache_stats
        from geomesa_tpu.ledger import LEDGER

        doc: dict = {
            "compile_cache": compile_cache_stats(),
            "warmup": warmup.progress(),
        }
        if self.scheduler is not None:
            doc["sched"] = self.scheduler.snapshot()
        if hasattr(self.store, "store_stats"):
            doc["store"] = self.store.store_stats()
        doc["mesh"] = self._mesh_stats()
        doc["slo"] = slo.ENGINE.snapshot()
        doc["ledger"] = LEDGER.snapshot()
        if self.stream is not None:
            doc["stream"] = self.stream.stream_stats()
        if self.replica is not None:
            doc["replica"] = self.replica.stats()
        if self.pubsub is not None:
            doc["pubsub"] = self.pubsub.stats()
        return doc

    def _debug_traces(self, parts: list, q: dict) -> None:
        """``/debug/traces`` (recent summaries) and
        ``/debug/traces/<id>`` (full span tree; ``?format=perfetto``)."""
        from geomesa_tpu.tracing import TRACER

        if len(parts) == 2:
            limit = int(q.get("limit", 50))
            return self._json(200, {"traces": TRACER.recent(limit)})
        if len(parts) != 3:
            return self._json(404, {"error": "use /debug/traces[/<id>]"})
        t = TRACER.get(unquote(parts[2]))
        if t is None:
            return self._json(
                404,
                {"error": f"no trace {parts[2]!r} (evicted, or neither "
                          "sampled nor slow — see trace.sample / "
                          "trace.slow_ms)"},
            )
        if q.get("format") == "perfetto":
            return self._json(200, t.to_perfetto())
        return self._json(200, t.to_dict())

    # -- endpoints ---------------------------------------------------------

    def _capabilities(self) -> None:
        doc = {"types": {}}
        for name in self.store.type_names:
            sft = self.store.get_schema(name)
            doc["types"][name] = {
                "spec": sft.spec,
                "geometry": sft.geom_field,
                "dtg": sft.dtg_field,
                "attributes": [
                    {"name": a.name, "type": a.type_name}
                    for a in sft.attributes
                ],
            }
        self._json(200, doc)

    def _query(self, type_name: str, q: dict):
        from geomesa_tpu.query.plan import Query

        max_features = q.get("maxFeatures")
        props = q.get("properties")
        return self.store.query(
            type_name,
            Query(
                filter=q.get("cql", "INCLUDE"),
                max_features=int(max_features) if max_features else None,
                properties=props.split(",") if props else None,
                hints={"auths": self._auths(q)},
            ),
        )

    def _features(self, type_name: str, q: dict) -> None:
        from geomesa_tpu import results

        fmt = results.negotiate_format(q, self.headers.get("Accept"))
        di = self._di(type_name)
        if fmt == "bin":
            return self._features_bin(type_name, q, di)
        presorted = None
        if di is not None and not q.get("properties"):
            import time as _time

            import numpy as np

            from geomesa_tpu.sched import FusableQuery

            t0 = _time.perf_counter()
            cql = q.get("cql", "INCLUDE")
            fell: list = []

            def fallback():
                # store rung: exact, audited by the store path itself
                fell.append(True)
                return self._query(type_name, q).batch

            batch = self._degradable(
                q, "device-launch-failed", fallback,
                fuse=FusableQuery(
                    di, cql, "query",
                    loose=self._loose(q), auths=self._auths(q),
                ),
            )
            cap = self._cap(q)
            if cap is not None and len(batch) > cap:
                batch = batch.take(np.arange(cap))
            if not fell:
                self._observe_resident(
                    type_name, cql, t0, _time.perf_counter(), len(batch)
                )
                # the host mirror is Z-sorted and the compacted row ids
                # ascend, so resident hit batches ARE sorted runs of the
                # index key: stamp it, never re-sort on host
                presorted = "z"
            batches = [batch]
            sft = batch.sft
        elif fmt == "arrow" and not q.get("properties"):
            # store rung, streamed: per-partition batches ride the
            # host-I/O prefetch pipeline straight into the encoder —
            # the first record batch hits the wire while later
            # partitions are still being read/decoded
            fetch = [0.0]
            batches = self._timed_batches(
                self._store_batches(type_name, q), fetch
            )
            sft = self.store.get_schema(type_name)
            batch = None
        else:
            batch = self._sched_run(
                q, fn=lambda: self._query(type_name, q).batch
            )
            batches = [batch]
            sft = batch.sft
        if fmt == "arrow":
            # dictionary-delta record batches: clients consume
            # incrementally and dictionaries never retransmit (ref
            # DeltaWriter protocol); per-chunk memory is bounded by
            # results.batch.rows — the whole-response BytesIO is gone
            return self._send_stream(
                200, results.CONTENT_TYPES["arrow"],
                results.arrow_stream_chunks(
                    batches, sft, presorted=presorted
                ),
                "arrow",
                rows=None if batch is None else len(batch),
                upstream=None if batch is not None else fetch,
            )
        self._emit_geojson(batch)

    def _store_batches(self, type_name: str, q: dict):
        """Store-rung result batches as an ITERATOR for the streamed
        encoders. FS stores without the live layer stream one filtered
        batch per surviving partition through the prefetch pipeline
        (bounded read-ahead; visibility applied per partition, the cap
        trimmed across the stream). The streaming live layer and plain
        memory stores materialize the merged view — correctness first:
        a partition iterator would miss memtable rows."""
        from geomesa_tpu import results
        from geomesa_tpu.query.plan import Query

        qp = getattr(self.store, "query_partitions", None)
        if (
            qp is not None
            and self.stream is None
            and not q.get("properties")
        ):
            query = Query(
                filter=q.get("cql", "INCLUDE"),
                hints={"auths": self._auths(q)},
            )
            return results.capped_batches(
                qp(type_name, query), self._cap(q)
            )
        return iter(
            [self._sched_run(q, fn=lambda: self._query(type_name, q).batch)]
        )

    def _features_bin(self, type_name: str, q: dict, di) -> None:
        """``f=bin``: the 16/24-byte track records. Resident indexes
        pack on device (``results.bin.engine``; the fused
        count→cap→compact rider) with the numpy twin as fallback rung;
        the store rung streams per-batch records. ``track=`` names the
        track-id attribute (required), ``label=`` widens to 24-byte
        records, ``sortBin=1`` orders by dtg seconds."""
        import time as _time

        from geomesa_tpu import results

        track = q.get("track")
        if not track:
            raise ValueError("f=bin needs track=<attribute>")
        label = q.get("label") or None
        sort = (q.get("sortBin") or "").lower() in ("1", "true", "yes")
        ctype = results.CONTENT_TYPES["bin"]
        rec = 24 if label else 16
        if di is not None and self._cap(q) is None \
                and not q.get("properties"):
            cql = q.get("cql", "INCLUDE")
            fell: list = []

            def fallback():
                fell.append(True)
                return None

            t0 = _time.perf_counter()

            def device_work():
                return results.resident_bin(
                    di, cql, track, dtg_attr=q.get("dtg"),
                    label_attr=label, sort=sort,
                    loose=self._loose(q), auths=self._auths(q),
                )

            data = self._degradable(
                q, "device-launch-failed", fallback, fn=device_work
            )
            t1 = _time.perf_counter()
            if data is not None:
                if not fell:
                    self._observe_resident(
                        type_name, cql, t0, t1, len(data) // rec
                    )
                return self._send_encoded(
                    200, data, ctype, "bin", t1 - t0,
                    rows=len(data) // rec,
                )
        fetch = [0.0]
        batches = self._timed_batches(
            self._store_batches(type_name, q), fetch
        )
        self._send_stream(
            200, ctype,
            results.bin_stream_chunks(
                batches, track, dtg_attr=q.get("dtg"),
                label_attr=label, sort=sort,
            ),
            "bin",
            upstream=fetch,
        )

    def _emit_geojson(self, batch) -> None:
        """GeoJSON feature collection with the encode/write split."""
        import time as _time

        from geomesa_tpu.export import feature_collection

        t0 = _time.perf_counter()
        body = json.dumps(feature_collection(batch)).encode("utf-8")
        self._send_encoded(
            200, body, "application/json", "geojson",
            _time.perf_counter() - t0, rows=len(batch),
        )

    def _emit_features(self, batch, q: dict, extra=None) -> None:
        """Emit a process result batch in the NEGOTIATED format —
        ``/knn``/``/tube``/``/proximity`` honor ``f=arrow``/``f=bin``
        through the result plane. Extra per-feature outputs (kNN
        distances …) become real typed columns via an extended SFT
        (Arrow columns / GeoJSON properties), not a per-feature zip."""
        from geomesa_tpu import results

        fmt = results.negotiate_format(q, self.headers.get("Accept"))
        if extra:
            batch = results.with_extra_columns(batch, extra)
        if fmt == "arrow":
            return self._send_stream(
                200, results.CONTENT_TYPES["arrow"],
                results.arrow_stream_chunks([batch], batch.sft),
                "arrow", rows=len(batch),
            )
        if fmt == "bin":
            track = q.get("track")
            if not track:
                raise ValueError("f=bin needs track=<attribute>")
            sort = (q.get("sortBin") or "").lower() in (
                "1", "true", "yes"
            )
            return self._send_stream(
                200, results.CONTENT_TYPES["bin"],
                results.bin_stream_chunks(
                    [batch], track, dtg_attr=q.get("dtg"),
                    label_attr=q.get("label") or None, sort=sort,
                ),
                "bin", rows=len(batch),
            )
        self._emit_geojson(batch)

    # -- WPS process endpoints (knn / tube select / proximity search) ------

    def _knn(self, type_name: str, q: dict) -> None:
        """``/knn/<type>?x=&y=&k=&cql=&maxRadius=`` — k nearest features
        (KNearestNeighborSearchProcess analog). In resident mode this is
        ONE fused distance+top_k dispatch on the pinned columns."""
        from geomesa_tpu.process.knn import knn

        px, py = float(q["x"]), float(q["y"])
        k = int(q.get("k", 10))
        kwargs = {}
        if q.get("maxRadius"):
            kwargs["max_radius_deg"] = float(q["maxRadius"])
        batch, dists = self._sched_run(
            q,
            fn=lambda: knn(
                self.store, type_name, px, py, k,
                base_filter=q.get("cql"),
                device_index=self._di(type_name),
                auths=self._auths(q),
                **kwargs,
            ),
        )
        import numpy as np

        self._emit_features(
            batch, q,
            extra={"knn_distance_deg": np.asarray(dists, np.float64)},
        )

    def _tube(self, type_name: str, q: dict) -> None:
        """``/tube/<type>?track=x,y,t;x,y,t;...&buffer=&maxDt=&cql=`` —
        corridor search around a track (TubeSelectProcess analog; one
        union-of-windows dispatch in resident mode)."""
        import numpy as np

        pts = [p for p in q["track"].split(";") if p]
        trk = np.array([[float(v) for v in p.split(",")] for p in pts])
        if trk.ndim != 2 or trk.shape[1] != 3 or len(trk) < 2:
            raise ValueError(
                "track must be 'x,y,t_ms;x,y,t_ms;...' with >= 2 points"
            )
        from geomesa_tpu.process.tube import tube_select

        batch = tube_select(
            self.store, type_name, trk[:, :2], trk[:, 2].astype(np.int64),
            buffer_deg=float(q.get("buffer", 0.1)),
            max_dt_ms=int(q.get("maxDt", 3_600_000)),
            base_filter=q.get("cql"),
            device_index=self._di(type_name),
            auths=self._auths(q),
        )
        self._emit_features(batch, q)

    def _proximity(self, type_name: str, q: dict) -> None:
        """``/proximity/<type>?points=x,y;x,y&distance=&cql=`` — features
        within a distance of any input point (ProximitySearchProcess
        analog; one union-of-windows dispatch in resident mode)."""
        from geomesa_tpu.geom.base import Point
        from geomesa_tpu.process.proximity import proximity_search

        pts = [p for p in q["points"].split(";") if p]
        geoms = [
            Point(*(float(v) for v in p.split(","))) for p in pts
        ]
        batch, dists = proximity_search(
            self.store, type_name, geoms,
            distance_deg=float(q.get("distance", 0.1)),
            base_filter=q.get("cql"),
            device_index=self._di(type_name),
            auths=self._auths(q),
        )
        import numpy as np

        self._emit_features(
            batch, q,
            extra={"proximity_distance_deg": np.asarray(dists, np.float64)},
        )

    def _agg_shaped(self, type_name: str, cql: str) -> bool:
        """Pre-screen for the brownout rung: True when the filter is a
        shape the chunk pre-aggregates can answer (bbox+time
        conjunctions — `is_aggregate_shape`) AND the store actually has
        chunk statistics for the type. Anything else would row-scan
        inside store.count/density, and brownout runs on the HANDLER
        thread outside scheduler admission precisely because it is
        supposed to be near-free: an unmetered full scan there would
        amplify the overload it exists to relieve."""
        from geomesa_tpu.query.plan import Query, is_aggregate_shape

        has_stats = getattr(self.store, "has_chunk_stats", None)
        if has_stats is None or not has_stats(type_name):
            return False  # v1/legacy/memory store: no pre-aggregates
        try:
            return bool(is_aggregate_shape(
                Query(filter=cql).parsed(),
                self.store.get_schema(type_name),
            ))
        except Exception:  # lint: disable=GT011(eligibility probe: an unparseable filter just means no pushdown; the full path classifies it)
            return False

    def _pushdown_eligible(self, q: dict) -> bool:
        """May a count answer from ``store.count`` (chunk pre-aggregates
        + internal row-scan fallback)? Caps and auths force the full
        query path — the ONE eligibility rule for the store-rung
        fallback, the brownout rung, and the non-resident route."""
        return (
            self._cap(q) is None
            and not self._auths(q)
            and hasattr(self.store, "count")
        )

    def _count_fallback(self, type_name: str, q: dict) -> int:
        """Store-rung count: the chunk-pushdown path when eligible
        (audited there), the full query path otherwise — exact either
        way, just not device-resident."""
        if self._pushdown_eligible(q):
            return int(
                self.store.count(type_name, q.get("cql", "INCLUDE"))
            )
        return len(self._query(type_name, q))

    def _count(self, type_name: str, q: dict) -> None:
        di = self._di(type_name)
        if di is not None:
            import time as _time

            from geomesa_tpu import resilience
            from geomesa_tpu.sched import FusableQuery

            t0 = _time.perf_counter()
            cql = q.get("cql", "INCLUDE")
            if resilience.brownout(self.scheduler) and \
                    self._pushdown_eligible(q) and \
                    self._agg_shaped(type_name, cql):
                # brownout rung: the admission queue is near its 429
                # cliff — answer from the store's chunk pre-aggregates
                # (exact; interior chunks never read) WITHOUT queueing
                # another device launch behind the saturated scheduler
                resilience.note_degraded("brownout-pushdown")
                n = int(self.store.count(type_name, cql))
                return self._json(200, {"count": n})
            fell: list = []

            def fallback():
                fell.append(True)
                return self._count_fallback(type_name, q)

            n = self._degradable(
                q, "device-launch-failed", fallback,
                fuse=FusableQuery(
                    di, cql, "count",
                    loose=self._loose(q), auths=self._auths(q),
                ),
            )
            cap = self._cap(q)
            if cap is not None:
                n = min(n, cap)  # the plain path counts the capped result
            if not fell:
                self._observe_resident(
                    type_name, cql, t0, _time.perf_counter(), n
                )
            return self._json(200, {"count": n})
        if self._pushdown_eligible(q):
            # store.count answers bbox+time counts from the v2 chunk
            # pre-aggregates (interior chunks never read) and falls back
            # to the row scan internally for anything else
            n = self._sched_run(
                q,
                fn=lambda: self.store.count(
                    type_name, q.get("cql", "INCLUDE")
                ),
            )
            return self._json(200, {"count": int(n)})
        res = self._sched_run(q, fn=lambda: self._query(type_name, q))
        self._json(200, {"count": len(res)})

    def _refresh(self, type_name: str, q: dict) -> None:
        """Restage a type's resident planes from the backing store (call
        after writes — the resident copy is a snapshot by design)."""
        if not self.resident:
            return self._json(
                400, {"error": "server is not running in resident mode"}
            )
        # freshness is decided under the construction lock (inside
        # _build_locked): a build that STARTED before the caller's writes
        # may finish after them, and skipping refresh on that stale
        # snapshot would lose the writes this endpoint exists to surface
        di, built_now = self._build_locked(type_name)
        if not built_now:  # a fresh build already staged post-write state
            di.refresh()
        self._json(200, {"refreshed": type_name, "rows": len(di)})

    def _stats(self, type_name: str, q: dict) -> None:
        spec = q.get("stats")
        if not spec:
            raise ValueError("stats endpoint needs stats=<Stat-DSL spec>")

        def store_work():
            # store rung: run_stats consults the chunk-stat pushdown
            # internally (PR 6) and row-scans what it cannot pre-answer
            from geomesa_tpu.process import run_stats
            from geomesa_tpu.query.plan import Query

            return run_stats(
                self.store,
                type_name,
                Query(
                    filter=q.get("cql", "INCLUDE"),
                    hints={"auths": self._auths(q)},
                ),
                spec,
            )

        di = self._di(type_name)
        if di is not None:
            import time as _time

            def device_work():
                t0 = _time.perf_counter()
                cql = q.get("cql", "INCLUDE")
                seq = di.stats(
                    cql, spec, loose=self._loose(q), auths=self._auths(q)
                )
                self._observe_resident(
                    type_name, cql, t0, _time.perf_counter(), 0
                )
                return seq

            seq = self._degradable(
                q, "device-launch-failed", store_work, fn=device_work
            )
        else:
            seq = self._sched_run(q, fn=store_work)
        self._json(200, seq.to_json())

    def _explain(self, type_name: str, q: dict) -> None:
        text = self.store.explain(type_name, q.get("cql", "INCLUDE"))
        self._send(200, text.encode("utf-8"), "text/plain")

    def _density(self, type_name: str, q: dict) -> None:
        from geomesa_tpu.process import density

        if "bbox" not in q:
            raise ValueError("density needs bbox=xmin,ymin,xmax,ymax")
        bbox = tuple(float(v) for v in q["bbox"].split(","))
        if len(bbox) != 4:
            raise ValueError("bbox must be xmin,ymin,xmax,ymax")
        width = int(q.get("width", 256))
        height = int(q.get("height", 256))
        from geomesa_tpu.geom import Envelope

        cql = q.get("cql", "INCLUDE")
        env = Envelope(*bbox)

        def store_work():
            # store rung: process.density consults the chunk-histogram
            # pushdown internally (PR 6 — mass-exact, cell placement
            # within coarse-cell tolerance on aligned rasters), records
            # its own metrics (observe_query) and honors the SAME auths
            # the resident path would have
            return density(
                self.store, type_name, cql, env, width, height,
                auths=self._auths(q),
            )

        di = self._di(type_name)
        if di is not None:
            from geomesa_tpu import resilience

            if resilience.brownout(self.scheduler) and \
                    self._agg_shaped(type_name, cql):
                # brownout rung: heatmaps are the classic overload
                # amplifier — answer from the chunk pre-aggregates
                # (within the PR 6 parity bounds) without queueing
                # another device launch behind the saturated scheduler
                resilience.note_degraded("brownout-pushdown")
                grid = store_work()
            else:
                import time as _time

                def device_work():
                    t0 = _time.perf_counter()
                    grid = di.density(
                        cql, env, width, height,
                        loose=self._loose(q), auths=self._auths(q),
                    )
                    if grid is None:
                        # filter/planes not device-expressible: a normal
                        # routing outcome, not a fault — resolved OUTSIDE
                        # _degradable so store-path errors are never
                        # retried/recorded under the DEVICE domain
                        return None
                    # unweighted: the grid mass IS the in-window count
                    self._observe_resident(
                        type_name, cql, t0, _time.perf_counter(),
                        int(round(float(grid.sum()))),
                    )
                    return grid

                grid = self._degradable(
                    q, "device-launch-failed", store_work, fn=device_work
                )
                if grid is None:
                    # the store resolution of a not-device-expressible
                    # filter is NORMAL routing, not an emergency rung:
                    # it goes back through the scheduler's admission
                    # control and deadline like any other unit of work
                    grid = self._sched_run(q, fn=store_work)
        else:
            grid = self._sched_run(q, fn=store_work)
        self._json(
            200,
            {
                "bbox": list(bbox),
                "width": width,
                "height": height,
                "counts": grid,  # to a list inside http.encode
            },
        )


#: the query endpoints the ledger/SLO layer labels by — anything else
#: (typo'd paths that 404, novel routes) collapses into "other" so a
#: URL scanner cannot mint unbounded metric series or ring keys
_KNOWN_ENDPOINTS = frozenset({
    "features", "count", "explain", "density", "stats", "refresh",
    "knn", "tube", "proximity", "capabilities", "append", "wal",
    "subscribe",
})


def _tolist(o):
    """``json.dumps`` default: numpy arrays and scalars as plain lists
    and numbers (the body is what ``o.tolist()`` would have given)."""
    if hasattr(o, "tolist"):
        return o.tolist()
    raise TypeError(f"Object of type {type(o).__name__} is not JSON "
                    "serializable")


def _cost_endpoint(parts: list) -> str:
    ep = parts[0] if parts else "-"
    return ep if ep in _KNOWN_ENDPOINTS else "other"


def _query_shape(parts: list, q: dict) -> str:
    """The ledger's query-shape key: endpoint + the filter's leading
    predicate + the loose flag — coarse on purpose (per-tenant detail
    lives in the trace; the shape key exists to group compile/cost
    attribution by KERNEL family, the measurement substrate the
    shape-bucketing work needs). The ledger bounds the key space, so an
    adversarial filter cannot mint unbounded aggregates."""
    endpoint = _cost_endpoint(parts)
    cql = (q.get("cql") or "INCLUDE").strip()
    words = cql.split("(", 1)[0].split()
    head = (words[0].upper()[:16] if words else "INCLUDE") or "INCLUDE"
    if not head.replace("_", "").isalnum():
        head = "EXPR"
    shape = f"{endpoint}:{head}"
    if q.get("loose"):
        shape += ":loose"
    return shape


def _mesh_serving_enabled(mesh) -> bool:
    """Resolve the mesh-serving switch: an explicit ``make_server``
    argument wins, else the ``mesh.enabled`` conf key; either way the
    mesh path needs more than one visible device (a 1-device mesh is
    just single-chip serving with extra steps)."""
    from geomesa_tpu.conf import sys_prop

    if mesh is None:
        mesh = bool(sys_prop("mesh.enabled"))
    if not mesh:
        return False
    import jax

    n = int(sys_prop("mesh.devices")) or len(jax.devices())
    return min(n, len(jax.devices())) > 1


def _make_resident_index(store, type_name: str, mesh: bool,
                         streaming: bool = False):
    """One resident index, mesh-sharded when mesh serving is on. With
    the streaming live layer attached, the mesh flavor reserves
    ``stream.memtable.rows`` of plane headroom so streamed appends land
    as in-place deltas behind the validity plane instead of full mesh
    restages (the single-chip StreamingDeviceIndex delta-appends
    natively)."""
    if mesh:
        from geomesa_tpu.device_cache import ShardedDeviceIndex

        reserve = 0
        if streaming:
            from geomesa_tpu.conf import sys_prop

            reserve = int(sys_prop("stream.memtable.rows"))
        return ShardedDeviceIndex(
            store, type_name, z_planes=True, reserve_rows=reserve
        )
    from geomesa_tpu.device_cache import StreamingDeviceIndex

    capacity = None
    if streaming:
        # pre-size the delta buffers so the first streamed appends land
        # as in-place deltas instead of an immediate growth restage
        from geomesa_tpu.conf import sys_prop

        rows = getattr(store, "manifest_rows", None)
        capacity = int(sys_prop("stream.memtable.rows")) + (
            int(rows(type_name)) if rows else 0
        )
    return StreamingDeviceIndex(
        store, type_name, z_planes=True, capacity=capacity
    )


def make_server(
    store, host: str = "127.0.0.1", port: int = 0, resident: bool = False,
    warm: bool = False, sched=None, io=None, mesh: "bool | None" = None,
    stream: "bool | None" = None, replica=None,
):
    """Build a ThreadingHTTPServer bound to (host, port); port 0 picks an
    ephemeral port (see ``server.server_address``). ``resident=True``
    serves count/features/stats from device-pinned DeviceIndex caches
    (built lazily per type on first access). ``warm=True`` (resident
    only) stages every type and pre-compiles its serving kernels BEFORE
    the server accepts traffic (DeviceIndex.warmup), so no request pays
    a first-touch staging or XLA compile; with the persistent
    compilation cache (on by default, see jaxconf) a restarted server
    warms from disk in seconds.

    ``sched`` enables the device query scheduler (admission control +
    micro-batch scan fusion + per-tenant fairness, see
    :mod:`geomesa_tpu.sched`): pass ``True`` for the default
    :class:`~geomesa_tpu.sched.SchedConfig` or a config instance.
    Queue-full requests get HTTP 429 + ``Retry-After``; expired
    deadlines (``deadlineMs=``) get 504; ``/stats/sched`` reports queue
    depth, wait time and the fusion factor.

    ``io`` overrides the store's host-I/O pipeline for partition scans
    (a :class:`~geomesa_tpu.store.prefetch.PrefetchConfig` or an int
    worker count; None keeps the store's own / the ``io.*`` system
    properties). Prefetch health is visible on ``/metrics`` as the
    ``geomesa_io_*`` series.

    ``mesh`` (or the ``mesh.enabled`` conf key) shards each resident
    type across the serving device mesh by global Z-key range
    (ShardedDeviceIndex): every count/features/stats/density/kNN scan —
    including the scheduler's fused micro-batches — runs as ONE
    mesh-wide SPMD launch, ``/stats/mesh`` reports the topology and
    per-shard residency, and a failed shard launch degrades down the
    PR 7 ladder instead of failing the query. Needs > 1 visible jax
    device; topology comes from ``mesh.devices`` / ``mesh.replicas``.

    ``replica`` joins this server to a replication group: pass a
    :class:`~geomesa_tpu.replica.ReplicaConfig` (or a pre-built
    :class:`~geomesa_tpu.replica.Replicator`). Leaders serve the WAL
    ship endpoint (``GET /wal/<type>``); followers tail the leader,
    apply records at the LEADER's seqs through the replay-idempotent
    live layer, reject POST ``/append`` with 503 + the leader's URL,
    and promote within ``replica.failover.s`` when the leader's lease
    expires. Requires the streaming live layer (the WAL is the thing
    being shipped).

    The persistent XLA compile cache is wired here (jaxconf's one
    location rule; serving is compile-heavy, a restarted server warms
    from disk) — hit/miss counts ride
    ``/stats`` and the ``geomesa_compile_cache_*`` metrics."""
    import os as _os

    from geomesa_tpu import ledger as _ledger
    from geomesa_tpu import slo as _slo
    from geomesa_tpu.jaxconf import enable_compilation_cache
    from geomesa_tpu.pyarrow_compat import preload_pyarrow
    from geomesa_tpu.tracing import TRACER

    enable_compilation_cache()
    _ledger.install()  # compile-time attribution via jax.monitoring
    mesh_on = resident and _mesh_serving_enabled(mesh)
    preload_pyarrow()  # handler threads serve Arrow; see pyarrow_compat
    if io is not None and hasattr(store, "io"):
        store.io = io
    # the slow-query log lives next to the store's audit log
    # (<root>/_slow_queries.jsonl); memory stores keep traces ring-only
    root_dir = getattr(store, "root", None)
    if root_dir:
        TRACER.slow_log_path = _os.path.join(
            str(root_dir), "_slow_queries.jsonl"
        )
    scheduler = None
    if sched:
        from geomesa_tpu.sched import QueryScheduler, SchedConfig

        # sched=True (no explicit config) defers to QueryScheduler's
        # default -- SchedConfig.from_props(), so the sched.* conf keys
        # / GEOMESA_TPU_SCHED_* env overrides actually apply here
        scheduler = QueryScheduler(
            sched if isinstance(sched, SchedConfig) else None
        )
    # streaming live layer: wrap the store so every serving path —
    # endpoints AND resident DeviceIndex staging — reads the merged
    # (memtable ∪ partitions) view; POST /append goes WAL-first and
    # serves immediately. Needs a real filesystem store (the WAL and
    # crash-consistent compaction live under its root).
    stream_layer = None
    from geomesa_tpu.store.stream import StreamingStore, streaming_enabled

    stream_on = streaming_enabled() if stream is None else bool(stream)
    if stream_on:
        if not (root_dir and hasattr(store, "_exclusive")):
            import warnings

            warnings.warn(
                "streaming live layer needs a FileSystemDataStore "
                "(a WAL directory under the store root); stream.enabled "
                "ignored for this store"
            )
        else:
            stream_layer = StreamingStore(store, scheduler=scheduler)
            store = stream_layer
    from geomesa_tpu.locking import checked_lock

    replicator = None
    if replica is not None:
        from geomesa_tpu.replica import ReplicaConfig, Replicator

        if stream_layer is None:
            raise ValueError(
                "replication needs the streaming live layer (the WAL is "
                "what gets shipped); pass stream=True / stream.enabled"
            )
        if isinstance(replica, Replicator):
            replicator = replica
        elif isinstance(replica, ReplicaConfig):
            replicator = Replicator(replica)
        else:
            raise TypeError(
                "replica must be a ReplicaConfig or Replicator, "
                f"got {type(replica).__name__}"
            )
        replicator.attach(stream_layer)
    # continuous-query push tier: rides the live layer (the data WAL
    # seq is the delivery cursor; no WAL, no cursor). The hub wires its
    # own seq listener and retention floor into the stream here.
    pubsub_hub = None
    if stream_layer is not None:
        from geomesa_tpu.pubsub import PubSubHub

        pubsub_hub = PubSubHub(stream_layer, sched=scheduler)
        if replicator is not None:
            # followers tail /wal/_pubsub alongside the data types and
            # a promotion re-arms matching from the replicated registry
            replicator.pubsub = pubsub_hub
            # under replica.ack=replica the leader's hub must not push
            # an alert until the record is replication-durable: a
            # failover could void the unreplicated tail and reassign
            # its seqs, silently breaking the cursor resume
            pubsub_hub.commit_gate = replicator.commit_floor
    from geomesa_tpu.conf import sys_prop as _sys_prop

    handler = type(
        "BoundHandler",
        (_Handler,),
        {
            "store": store,
            "resident": resident,
            "mesh": mesh_on,
            "scheduler": scheduler,
            "stream": stream_layer,
            "replica": replicator,
            "pubsub": pubsub_hub,
            # idle keep-alive bound, declared (GT008) instead of the
            # class-default literal; router→backend pooled connections
            # read the same key
            "timeout": float(_sys_prop("http.keepalive.s")),
            "_resident_cache": {},
            # blocking_ok: first-touch resident builds hold it across
            # store reads + device staging BY DESIGN (a duplicate build
            # would stage the dataset into device memory twice)
            "_resident_lock": checked_lock(
                "server.resident", blocking_ok=True
            ),
        },
    )
    if resident and warm:
        import warnings

        for tn in store.type_names:
            # a type that fails to stage (e.g. device OOM) must not keep
            # the OTHER types from serving — same isolation the lazy
            # first-touch path gives: that type just isn't resident
            try:
                di = _make_resident_index(
                    store, tn, mesh_on,
                    streaming=stream_layer is not None,
                )
            except Exception as e:
                warnings.warn(f"warm staging failed for {tn!r}: {e!r}")
                continue
            handler._resident_cache[tn] = di
        # staging is synchronous (the resident cache is populated when
        # make_server returns); the AOT pre-compile over the bucket x
        # kernel-family set moves to a bounded background pool charged
        # to the _system ledger tenant, with /readyz gating or stamping
        # `warming` per compile.warmup.gate — a fleet rolling restart
        # (wait_ready) therefore never routes traffic at a cold process
        if handler._resident_cache:
            if bool(_sys_prop("compile.warmup.enabled")):
                from geomesa_tpu import warmup as _warmup

                handler._warmup_started = True
                _warmup.start(dict(handler._resident_cache))
            else:
                # warmup.enabled=false keeps the pre-ladder contract:
                # base kernels compile inline before traffic is accepted
                for tn, di in handler._resident_cache.items():
                    try:
                        di.warmup()
                    except Exception as e:  # pragma: no cover - defensive
                        warnings.warn(f"warmup failed for {tn!r}: {e!r}")
    # flight recorder: bundles land next to the store's data (memory
    # stores have no root — the recorder stays disabled unless a test
    # configured a directory of its own); sched/store/mesh snapshots
    # register as bundle providers
    providers: dict = {}
    if scheduler is not None:
        providers["sched"] = scheduler.snapshot
    if hasattr(store, "store_stats"):
        providers["store"] = store.store_stats

    def _mesh_snapshot(h=handler):
        doc = {"enabled": bool(h.mesh), "types": {}}
        for name, di in list(h._resident_cache.items()):
            stats = getattr(di, "mesh_stats", None)
            if stats is not None:
                doc["types"][name] = stats()
        return doc

    providers["mesh"] = _mesh_snapshot
    if pubsub_hub is not None:
        providers["pubsub"] = pubsub_hub.stats
    if stream_layer is not None:
        providers["stream"] = stream_layer.stream_stats

        def _stream_delta(tname, batch, h=handler):
            """Per-append incremental resident refresh: fold the acked
            batch into an already-staged index's planes (delta path —
            no restage on the ack path). The cache probe happens UNDER
            the construction lock: an append acked between a first-
            touch build's staging snapshot and its cache publication
            must wait for the build and then deliver (refresh_delta is
            re-delivery-safe — duplicate fids force a restage through
            the merged view), or the staged index would be missing
            acked rows with no future delta to repair it. A failure
            evicts the index so the next query restages a correct
            copy; the streaming layer stamps ``ingest-degraded`` and
            the rows keep serving from the merged store path either
            way."""
            with h._resident_lock:
                di = h._resident_cache.get(tname)
            if di is None:
                return  # first query stages the merged view lazily
            try:
                di.refresh_delta(batch)
            except Exception:
                h._resident_cache.pop(tname, None)
                raise

        stream_layer.add_delta_listener(_stream_delta)
    _slo.FLIGHTREC.configure(
        _os.path.join(str(root_dir), "_flightrec")
        if root_dir
        else _slo.FLIGHTREC.dir,
        providers=providers,
    )
    server = _GeomesaHTTPServer((host, port), handler)
    server.scheduler = scheduler  # callers may inspect / shut down
    server.store = store  # the draining shutdown flushes its audit log
    server.stream_layer = stream_layer  # closed by the draining shutdown
    server.pubsub = pubsub_hub  # closed (before the stream) at drain
    if replicator is not None:
        # the bound ephemeral port is only known NOW — default the
        # advertised URL from it so tests/CLI may pass port=0
        if not replicator.cfg.self_url:
            addr = server.server_address
            replicator.cfg.self_url = f"http://{addr[0]}:{addr[1]}"
        if replicator.cfg.role == "leader" and not replicator._leader_url:
            replicator._leader_url = replicator.cfg.self_url
        server.replica = replicator
        replicator.start()  # follower tail thread spawns here
    from geomesa_tpu.analysis import compilecheck

    if compilecheck.enabled():
        # serving is live from here: every backend compile must carry an
        # allowed compile_scope (analysis/compilecheck.py)
        server._ccheck_live = True
        compilecheck.CHECKER.serving_up()
    return server


def serve_background(
    store, host: str = "127.0.0.1", port: int = 0, resident: bool = False,
    warm: bool = False, sched=None, io=None, mesh: "bool | None" = None,
    stream: "bool | None" = None, replica=None,
):
    """Start serving on a daemon thread; returns (server, thread). Stop
    with ``server.shutdown()``."""
    server = make_server(
        store, host, port, resident=resident, warm=warm, sched=sched,
        io=io, mesh=mesh, stream=stream, replica=replica,
    )
    thread = spawn_thread(
        server.serve_forever, name="geomesa-serve", context=False
    )
    thread.start()
    return server, thread
