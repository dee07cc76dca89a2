"""Metrics registry with Prometheus text exposition (ref: geomesa-metrics
-- dropwizard/micrometer reporters, micrometer/PrometheusSetup, wired into
ingest/converters [UNVERIFIED - empty reference mount]).

Tiny dependency-free core: Counter / Gauge / Histogram(+timer) with label
support, a process-global registry, and the Prometheus text format for
scraping. Converters and store write/query paths increment these; hosts
can serve ``prometheus_text()`` from any HTTP endpoint.
"""

from __future__ import annotations

import time
from bisect import bisect_left

from geomesa_tpu.locking import checked_lock


class _Metric:
    def __init__(self, name: str, help_: str, kind: str):
        self.name = name
        self.help = help_
        self.kind = kind
        self._values: dict = {}
        self._lock = checked_lock(f"metrics.{name}")

    def labels(self, **labels) -> tuple:
        return tuple(sorted(labels.items()))


class Counter(_Metric):
    def __init__(self, name, help_=""):
        super().__init__(name, help_, "counter")

    def inc(self, amount: float = 1.0, **labels) -> None:
        self.inc_key(self.labels(**labels), amount)

    def inc_key(self, key: tuple, amount: float = 1.0) -> None:
        """``inc`` with the label key already built (``labels(...)``):
        for hot paths whose label values come from a fixed set."""
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        return self._values.get(self.labels(**labels), 0.0)


class Gauge(_Metric):
    def __init__(self, name, help_=""):
        super().__init__(name, help_, "gauge")

    def set(self, v: float, **labels) -> None:
        with self._lock:
            self._values[self.labels(**labels)] = float(v)

    def inc(self, amount: float = 1.0, **labels) -> None:
        """Delta update — the right form when several concurrent actors
        contribute to one gauge (each adds/removes its own share; a
        ``set`` from any one of them would clobber the others)."""
        key = self.labels(**labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + float(amount)

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        return self._values.get(self.labels(**labels), 0.0)


DEFAULT_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 60.0
)


class Histogram(_Metric):
    """Cumulative-bucket histogram; ``time()`` context manager included.

    ``observe(..., exemplar={"trace_id": tid})`` attaches an OpenMetrics
    exemplar to the bucket the value lands in (last writer wins): the
    exposition then links a bucket — say the p99 one — to an actual
    captured trace id, so a latency violation on ``/metrics`` resolves
    to its ``/debug/traces`` entry."""

    def __init__(self, name, help_="", buckets=DEFAULT_BUCKETS):
        super().__init__(name, help_, "histogram")
        self.buckets = tuple(sorted(buckets))

    def observe(self, v: float, *, exemplar=None, **labels) -> None:
        self.observe_key(self.labels(**labels), v, exemplar)

    def observe_key(self, key: tuple, v: float, exemplar=None) -> None:
        """``observe`` with the label key already built (``labels(...)``):
        for hot paths whose label values come from a fixed set."""
        with self._lock:
            st = self._values.get(key)
            if st is None:  # not setdefault: its default is built per call
                st = self._values[key] = {
                    "counts": [0] * (len(self.buckets) + 1), "sum": 0.0,
                    "n": 0,
                }
            # le-bucket: first bound >= v (cumulated at exposition time);
            # past the last bound lands in the trailing +Inf slot
            b = bisect_left(self.buckets, v)
            st["counts"][b] += 1
            st["sum"] += v
            st["n"] += 1
            if exemplar:
                st.setdefault("exemplars", {})[b] = (
                    dict(exemplar), float(v)
                )

    def time(self, **labels):
        return _Timer(self, labels)

    def stats(self, **labels) -> dict:
        return self._values.get(self.labels(**labels), {"counts": [], "sum": 0.0, "n": 0})


class _Timer:
    def __init__(self, hist: Histogram, labels: dict):
        self.hist = hist
        self.labels = labels

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.hist.observe(time.perf_counter() - self.t0, **self.labels)


class MetricsRegistry:
    def __init__(self):
        self._metrics: dict = {}
        self._lock = checked_lock("metrics.registry")

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get(name, lambda: Counter(name, help_), Counter)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get(name, lambda: Gauge(name, help_), Gauge)

    def histogram(self, name: str, help_: str = "", buckets=DEFAULT_BUCKETS) -> Histogram:
        return self._get(
            name, lambda: Histogram(name, help_, buckets), Histogram
        )

    def _get(self, name, factory, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory()
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} is a {m.kind}")
            return m

    def prometheus_text(self, openmetrics: bool = False) -> str:
        """Prometheus exposition. Default: the classic text format
        (text/plain; version 0.0.4) — NO exemplars, because the 0.0.4
        parser rejects anything but an optional timestamp after the
        value and one suffixed line would fail the WHOLE scrape.
        ``openmetrics=True`` (the server sets it when the scraper's
        Accept header negotiates application/openmetrics-text) emits
        the OpenMetrics form: exemplar suffixes on histogram buckets
        and the terminating ``# EOF``.

        Every mutable structure is SNAPSHOTTED under its metric's lock
        before formatting: writers mutate ``_values`` (and histogram
        ``counts`` lists) concurrently on serving threads, and iterating
        them live can raise mid-scrape or emit a histogram whose buckets
        disagree with its count."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        lines: list = []
        for name, m in metrics:
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {m.kind}")
            if isinstance(m, (Counter, Gauge)):
                with m._lock:
                    values = sorted(m._values.items())
                for key, v in values:
                    lines.append(f"{name}{_fmt_labels(key)} {_fmt_val(v)}")
            else:
                with m._lock:
                    stats = sorted(
                        (
                            key, list(st["counts"]), st["sum"], st["n"],
                            dict(st.get("exemplars", ())),
                        )
                        for key, st in m._values.items()
                    )
                for key, counts, total, n, exemplars in stats:
                    cum = 0
                    for i, (b, c) in enumerate(
                        zip(m.buckets + (float("inf"),), counts)
                    ):
                        cum += c
                        lb = "+Inf" if b == float("inf") else _fmt_val(b)
                        line = (
                            f"{name}_bucket"
                            f"{_fmt_labels(key + (('le', lb),))} {cum}"
                        )
                        ex = exemplars.get(i) if openmetrics else None
                        if ex is not None:
                            # OpenMetrics exemplar: "<line> # {labels} value"
                            line += (
                                f" # {_fmt_labels(tuple(sorted(ex[0].items())))}"
                                f" {_fmt_val(ex[1])}"
                            )
                        lines.append(line)
                    lines.append(f"{name}_sum{_fmt_labels(key)} {_fmt_val(total)}")
                    lines.append(f"{name}_count{_fmt_labels(key)} {n}")
        if openmetrics:
            lines.append("# EOF")
        return "\n".join(lines) + "\n"

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()


def _esc_label(v) -> str:
    """Prometheus-spec label value escaping (backslash, double quote,
    newline) — a filter string carried in a label must not be able to
    break out of its quotes or split the exposition line."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _fmt_labels(key) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_esc_label(v)}"' for k, v in key)
    return "{" + inner + "}"


def _fmt_val(v: float) -> str:
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


REGISTRY = MetricsRegistry()

# canonical framework metrics (ref instruments converters + ingest)
features_ingested = REGISTRY.counter(
    "geomesa_features_ingested_total", "features written to stores"
)
features_failed = REGISTRY.counter(
    "geomesa_convert_failures_total", "converter records failed"
)
queries_run = REGISTRY.counter(
    "geomesa_queries_total", "queries executed"
)
query_seconds = REGISTRY.histogram(
    "geomesa_query_duration_seconds", "end-to-end query latency"
)

# device query scheduler (geomesa_tpu/sched): the serving path's
# admission/fusion observability — queue pressure, wait time, fusion
# factor (sched_queries_total / sched_launches_total), and shed load
sched_queue_depth = REGISTRY.gauge(
    "geomesa_sched_queue_depth", "requests waiting in the scheduler queue"
)
sched_queries = REGISTRY.counter(
    "geomesa_sched_queries_total", "requests executed by the scheduler"
)
sched_launches = REGISTRY.counter(
    "geomesa_sched_launches_total", "device scan launches dispatched"
)
sched_fused = REGISTRY.counter(
    "geomesa_sched_fused_queries_total",
    "queries answered by a shared (fused) device launch",
)
sched_rejected = REGISTRY.counter(
    "geomesa_sched_rejections_total", "requests rejected at admission"
)
sched_expired = REGISTRY.counter(
    "geomesa_sched_deadline_expired_total",
    "requests that expired before or during execution",
)
sched_wait_seconds = REGISTRY.histogram(
    "geomesa_sched_wait_seconds", "queue wait before execution"
)

# host-I/O prefetch pipeline (store/prefetch.py): where the out-of-core
# scan / FS staging / bulk ingest host time goes (read vs decode vs
# stage), how deep the read-ahead runs, and queue occupancy in bytes
io_read_seconds = REGISTRY.histogram(
    "geomesa_io_read_seconds", "partition file read time (per file)"
)
io_decode_seconds = REGISTRY.histogram(
    "geomesa_io_decode_seconds",
    "Arrow-to-FeatureBatch decode time (per file)",
)
io_decode_string_values = REGISTRY.counter(
    "geomesa_io_decode_string_values_total",
    "Arrow String values decoded into host object arrays, by path "
    "(dictionary gather or plain conversion)",
)
io_stage_seconds = REGISTRY.histogram(
    "geomesa_io_stage_seconds",
    "host column staging time (per slab chunk)",
)
io_prefetch_depth = REGISTRY.gauge(
    "geomesa_io_prefetch_depth", "prefetch chunks in flight"
)
io_queue_bytes = REGISTRY.gauge(
    "geomesa_io_queue_bytes",
    "decoded chunk bytes waiting in the prefetch queue",
)
io_chunks = REGISTRY.counter(
    "geomesa_io_chunks_total", "chunks delivered by the prefetch pipeline"
)
io_bytes_read = REGISTRY.counter(
    "geomesa_io_bytes_read_total", "partition file bytes read from disk"
)

# crash-consistent FS store (store/fs.py): generation publishes, what
# the recovery sweep reclaimed from interrupted flushes, checksum
# verification failures (and the partitions they quarantined), and
# transient-read retries spent by the prefetch workers
store_generations = REGISTRY.counter(
    "geomesa_store_generations_published_total",
    "partition-file generations atomically published by flushes",
)
store_orphan_files = REGISTRY.counter(
    "geomesa_store_orphan_files_reclaimed_total",
    "orphaned partition/tmp files reclaimed by the recovery sweep",
)
store_orphan_bytes = REGISTRY.counter(
    "geomesa_store_orphan_bytes_reclaimed_total",
    "bytes reclaimed by the recovery sweep",
)
store_checksum_failures = REGISTRY.counter(
    "geomesa_store_checksum_failures_total",
    "partition files that failed checksum verification",
)
store_quarantined = REGISTRY.gauge(
    "geomesa_store_partitions_quarantined",
    "partitions currently quarantined by checksum failures (best-effort:"
    " summed over store instances; /stats/store has the exact per-type"
    " sets)",
)
store_read_retries = REGISTRY.counter(
    "geomesa_store_read_retries_total",
    "transient partition-read retries by the prefetch workers",
)

# chunked partition format v2 (store/chunkstats.py): how much of the
# streamed-scan workload the chunk-level Z/bbox/time pruning index
# removed BEFORE read/decode (bytes skipped are real file bytes -- the
# pruned parquet row groups), and fsck's chunk-stat drift findings
store_chunks_read = REGISTRY.counter(
    "geomesa_store_chunks_read_total",
    "v2 partition chunks read by chunk-planned scans",
)
store_chunks_skipped = REGISTRY.counter(
    "geomesa_store_chunks_skipped_total",
    "v2 partition chunks pruned before read/decode",
)
store_chunk_bytes_skipped = REGISTRY.counter(
    "geomesa_store_chunk_bytes_skipped_total",
    "encoded partition-file bytes skipped by chunk pruning",
)
store_chunk_stat_drift = REGISTRY.counter(
    "geomesa_store_chunk_stat_drift_total",
    "chunk-stat records that disagreed with decoded rows (fsck)",
)

# aggregation pushdown (store/pushdown.py): density/count/stats queries
# answered from chunk pre-aggregates -- how often it engages (by kind),
# how often an eligible-looking query fell back, and the interior rows
# that were never read vs the boundary chunks that row-refined
agg_pushdown_queries = REGISTRY.counter(
    "geomesa_agg_pushdown_queries_total",
    "aggregate queries answered from chunk pre-aggregates",
)
agg_pushdown_fallbacks = REGISTRY.counter(
    "geomesa_agg_pushdown_fallback_total",
    "aggregate queries that fell back to the row-scan path",
)
agg_pushdown_rows = REGISTRY.counter(
    "geomesa_agg_pushdown_rows_preaggregated_total",
    "rows answered from interior-chunk summaries without being read",
)
agg_pushdown_chunks_refined = REGISTRY.counter(
    "geomesa_agg_pushdown_chunks_refined_total",
    "boundary chunks that descended to row-level refinement",
)

# fault-tolerant serving (resilience.py): breaker state machines per
# failure domain (0 closed / 1 half-open / 2 open; the keyed partition
# domain exposes open counts via /readyz instead), serving-path
# retries, degraded answers by reason, watchdog interventions, OOM
# batch-halving recoveries and scheduler-worker crash-replacements
resilience_breaker_state = REGISTRY.gauge(
    "geomesa_resilience_breaker_state",
    "circuit-breaker state per domain (0=closed 1=half-open 2=open)",
)
resilience_breaker_transitions = REGISTRY.counter(
    "geomesa_resilience_breaker_transitions_total",
    "circuit-breaker state transitions (domain, to)",
)
resilience_retries = REGISTRY.counter(
    "geomesa_resilience_retries_total",
    "serving-path retries of retryable faults (by domain)",
)
resilience_degraded = REGISTRY.counter(
    "geomesa_resilience_degraded_total",
    "requests answered degraded, by (bounded) reason",
)
resilience_watchdog_timeouts = REGISTRY.counter(
    "geomesa_resilience_watchdog_timeouts_total",
    "stuck device launches failed by the scheduler watchdog",
)
resilience_oom_recoveries = REGISTRY.counter(
    "geomesa_resilience_oom_recoveries_total",
    "staging/HBM OOMs recovered by halving the scan batch",
)
sched_worker_failures = REGISTRY.counter(
    "geomesa_sched_worker_failures_total",
    "scheduler worker crashes survived (requests failed typed, worker "
    "kept serving)",
)
sched_drains = REGISTRY.counter(
    "geomesa_sched_drains_total",
    "graceful drains completed (admission stopped, in-flight finished)",
)

# multi-chip sharded serving (parallel/dist.py + device_cache.py): mesh
# topology and residency per shard (bounded labels: shard indexes are
# capped by the device count), mesh-wide scan launches, exchange
# capacity retries (an adversarial layout relaunched at the measured
# block bound) and mesh builds that fell back to the host sort
mesh_shards = REGISTRY.gauge(
    "geomesa_mesh_shards",
    "shards in the serving mesh (0 = single-device serving)",
)
mesh_resident_rows = REGISTRY.gauge(
    "geomesa_mesh_resident_rows",
    "resident rows per mesh shard (shard label; padding excluded)",
)
mesh_resident_bytes = REGISTRY.gauge(
    "geomesa_mesh_resident_bytes",
    "resident device bytes per mesh shard (shard label)",
)
mesh_launches = REGISTRY.counter(
    "geomesa_mesh_launches_total",
    "mesh-wide sharded scan launches (fused groups count once)",
)
mesh_build_seconds = REGISTRY.histogram(
    "geomesa_mesh_build_seconds",
    "mesh-resident index build time (distributed sort + shard staging)",
)
mesh_exchange_retries = REGISTRY.counter(
    "geomesa_mesh_exchange_retries_total",
    "distributed-sort exchanges relaunched at the measured capacity",
)
mesh_build_fallbacks = REGISTRY.counter(
    "geomesa_mesh_build_fallbacks_total",
    "mesh index builds that degraded to the host sort",
)

# persistent serving compile cache (jaxconf.py): task-level hit/miss as
# observed through jax's compilation-cache monitoring events, split by
# tier — tier="disk" counts executables loaded from the persistent
# on-disk cache instead of compiled, tier="inproc" counts dispatches
# that reused an executable already built in this process's jit caches
# (device_cache._note_jit_cache)
compile_cache_hits = REGISTRY.counter(
    "geomesa_compile_cache_hits_total",
    "XLA executable reuse by tier (disk = persistent cache load, "
    "inproc = in-process jit-cache hit)",
)
compile_cache_requests = REGISTRY.counter(
    "geomesa_compile_cache_requests_total",
    "XLA compilations eligible for the persistent cache (misses = "
    "requests - hits)",
)

# AOT warmup (warmup.py): progress of the start-time pre-compile pass
# over the bucket x kernel-family signature set, by state label —
# state="total" planned signatures, state="compiled" legs that paid a
# backend compile, state="from_cache" legs satisfied entirely from the
# persistent/in-process caches, state="failed" legs that raised
warmup_signatures = REGISTRY.gauge(
    "geomesa_warmup_signatures",
    "AOT warmup signatures by state (total/compiled/from_cache/failed)",
)

# per-request tracing (tracing.py): how many traces the ring retained
# (head-sampled or slow-captured) and how many crossed the slow-query
# threshold (trace.slow_ms) — the rate the slow-query log grows at
traces_captured = REGISTRY.counter(
    "geomesa_traces_captured_total",
    "request traces retained in the recent-trace ring",
)
slow_queries = REGISTRY.counter(
    "geomesa_slow_queries_total",
    "requests slower than trace.slow_ms (always-captured + slow-logged)",
)

# point-in-polygon filters on the resident count path: which engine
# answered (pallas kernel, XLA broadcast, host), and the polygon kernel's
# work: rows of the blocks it tested times their padded edges, and times
# the edges its loop ran (each zone's real edges, rounded up to the
# loop's unroll), and its blocks by whether the pretests (envelope,
# window) let it skip the edges
pip_queries = REGISTRY.counter(
    "geomesa_pip_queries_total",
    "resident counts of a point-in-polygon filter, by engine",
)
pip_edge_tests = REGISTRY.counter(
    "geomesa_pip_edge_tests_total",
    "polygon kernel: rows of tested blocks x padded polygon edges",
)
pip_edge_loop_tests = REGISTRY.counter(
    "geomesa_pip_edge_loop_tests_total",
    "polygon kernel: rows of tested blocks x polygon edges the loop ran",
)
pip_blocks = REGISTRY.counter(
    "geomesa_pip_blocks_total",
    "polygon kernel row blocks, by outcome (tested|skipped)",
)
PIP_TESTED = pip_blocks.labels(outcome="tested")
PIP_SKIPPED = pip_blocks.labels(outcome="skipped")
# its grid tiles: skipped where no row of the tile passes the pretests,
# else walked block by block
pip_tiles = REGISTRY.counter(
    "geomesa_pip_tiles_total",
    "polygon kernel grid tiles, by outcome (walked|skipped)",
)
PIP_WALKED = pip_tiles.labels(outcome="walked")
PIP_TILE_SKIPPED = pip_tiles.labels(outcome="skipped")

# served-path stages (tracing.STAGES): every span of a declared stage
# observes its wall time and adds its thread CPU time here, whether or
# not the request's trace is recorded. The stage label takes only the
# names of that fixed tuple
stage_seconds = REGISTRY.histogram(
    "geomesa_stage_seconds",
    "wall time per declared serving/staging stage (stage)",
    buckets=(
        0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
        0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    ),
)
stage_cpu_seconds = REGISTRY.counter(
    "geomesa_stage_cpu_seconds_total",
    "thread CPU time spent inside live spans of a declared stage (stage)",
)

# serving SLO engine (slo.py): latency observations per endpoint/lane
# (bucket exemplars carry trace ids — a p99 violation on /metrics
# resolves to a captured trace), good/bad per SLO name, burn-rate
# gauges per (slo, fast|slow) window, flight-recorder bundles by
# (bounded) reason
slo_latency = REGISTRY.histogram(
    "geomesa_slo_latency_seconds",
    "request latency per endpoint/lane (buckets carry trace exemplars)",
    buckets=(
        0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
        0.75, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
    ),
)
slo_requests = REGISTRY.counter(
    "geomesa_slo_requests_total", "requests measured against an SLO"
)
slo_bad = REGISTRY.counter(
    "geomesa_slo_bad_total",
    "requests over their SLO latency threshold or failed 5xx",
)
slo_burn = REGISTRY.gauge(
    "geomesa_slo_burn_rate",
    "error-budget burn rate per (slo, window); > 1 consumes budget "
    "faster than it accrues",
)
flightrec_bundles = REGISTRY.counter(
    "geomesa_flightrec_bundles_total",
    "flight-recorder postmortem bundles written, by reason",
)

# per-request cost ledger (ledger.py): requests folded into the
# process aggregates, attributed device/compile seconds, and the raw
# compile events the compile ledger observed through jax.monitoring
ledger_requests = REGISTRY.counter(
    "geomesa_ledger_requests_total",
    "requests folded into the cost ledger",
)
ledger_device_seconds = REGISTRY.counter(
    "geomesa_ledger_device_seconds_total",
    "fair-share device seconds attributed to ledgered requests",
)
ledger_compile_seconds = REGISTRY.counter(
    "geomesa_ledger_compile_seconds_total",
    "XLA compile seconds ledgered requests blocked on",
)
compile_events = REGISTRY.counter(
    "geomesa_compile_events_total",
    "XLA backend compiles observed by the compile ledger",
)
compile_event_seconds = REGISTRY.counter(
    "geomesa_compile_event_seconds_total",
    "total XLA backend compile seconds observed by the compile ledger",
)

# streaming live layer (store/stream.py + store/wal.py): WAL-backed
# incremental ingest, the in-memory generation it serves from, and the
# backpressured background compaction into the partition files
stream_appends = REGISTRY.counter(
    "geomesa_stream_appends_total", "acked streaming append calls"
)
stream_rows = REGISTRY.counter(
    "geomesa_stream_rows_total", "rows acked through the streaming layer"
)
stream_wal_bytes = REGISTRY.counter(
    "geomesa_stream_wal_bytes_total", "bytes appended to WAL segments"
)
stream_wal_fsyncs = REGISTRY.counter(
    "geomesa_stream_wal_fsyncs_total", "WAL fsync calls (durability acks)"
)
stream_wal_replay_rows = REGISTRY.counter(
    "geomesa_stream_wal_replay_rows_total",
    "rows recovered into the memtable by WAL replay at open",
)
stream_wal_truncations = REGISTRY.counter(
    "geomesa_stream_wal_truncations_total",
    "torn WAL tails truncated at the last valid checksum during replay",
)
stream_memtable_rows = REGISTRY.gauge(
    "geomesa_stream_memtable_rows",
    "rows live in the in-memory generation (not yet compacted)",
)
stream_memtable_runs = REGISTRY.gauge(
    "geomesa_stream_memtable_runs",
    "Z-sorted memtable runs live (the per-query read amplification)",
)
stream_backpressure = REGISTRY.counter(
    "geomesa_stream_backpressure_total",
    "appends rejected 429-style at the wal.max.generations bound",
)
stream_compactions = REGISTRY.counter(
    "geomesa_stream_compactions_total",
    "memtable generations compacted into partition files",
)
stream_compact_seconds = REGISTRY.histogram(
    "geomesa_stream_compact_seconds",
    "background compaction duration (merge + flush + WAL truncate)",
)
stream_compact_yields = REGISTRY.counter(
    "geomesa_stream_compact_yields_total",
    "compactor pauses yielded to serving load (brownout signal)",
)
stream_delta_refreshes = REGISTRY.counter(
    "geomesa_stream_delta_refreshes_total",
    "resident-index refreshes from streamed appends, by mode "
    "(delta = incremental into the validity-planed buffers, "
    "restage = fallback full restage)",
)

# runtime lock-order checker (analysis/lockcheck.py): the acquisition
# graph's size and its findings -- nonzero cycles or blocking events in
# a checked process is a concurrency regression (gauges, set whenever
# LockCheck.report() runs; zero and flat is the healthy shape)
lockcheck_locks = REGISTRY.gauge(
    "geomesa_lockcheck_locks", "checked locks registered this process"
)
lockcheck_edges = REGISTRY.gauge(
    "geomesa_lockcheck_edges", "distinct lock acquisition-order edges"
)
lockcheck_cycles = REGISTRY.gauge(
    "geomesa_lockcheck_cycles",
    "lock-order cycles detected (ABBA deadlock potentials)",
)
lockcheck_blocking = REGISTRY.gauge(
    "geomesa_lockcheck_blocking_events",
    "blocking calls observed under a held (non-blocking_ok) lock",
)

# runtime context-propagation checker (analysis/ctxcheck.py) and
# serving-path recompile tripwire (analysis/compilecheck.py): same
# contract as the lockcheck gauges -- set on report(), zero findings is
# the healthy shape
ctxcheck_tasks = REGISTRY.gauge(
    "geomesa_ctxcheck_tasks", "blessed worker tasks observed this process"
)
ctxcheck_findings = REGISTRY.gauge(
    "geomesa_ctxcheck_findings",
    "context-propagation findings (leaks, mismatched/orphaned accounting)",
)
compilecheck_compiles = REGISTRY.gauge(
    "geomesa_compilecheck_serving_compiles",
    "backend compiles observed while serving was live",
)
compilecheck_violations = REGISTRY.gauge(
    "geomesa_compilecheck_violations",
    "serving-path compiles outside the allowed compile_scope namespace",
)

# device-side spatial join engine (join/): planner strategy choices
# (bounded label: the strategy enum), candidate/pair volumes, batched
# refinement launches, the skew-splitting escape, and the legacy
# window-pairs coarse pass's compaction-cap overflow relaunches
join_queries = REGISTRY.counter(
    "geomesa_join_queries_total",
    "spatial joins executed, by planner strategy",
)
join_candidates = REGISTRY.counter(
    "geomesa_join_candidates_total",
    "candidate (row, window) pairs expanded by join refinement",
)
join_pairs = REGISTRY.counter(
    "geomesa_join_pairs_total", "pairs emitted by the join engine"
)
join_launches = REGISTRY.counter(
    "geomesa_join_launches_total",
    "batched join refinement launches (count + compact each count one)",
)
join_skew_splits = REGISTRY.counter(
    "geomesa_join_skew_splits_total",
    "candidate runs split by the skew escape (hot-cell bound)",
)
join_pair_overflows = REGISTRY.counter(
    "geomesa_join_pair_overflows_total",
    "window-pairs groups whose compaction cap overflowed into a full "
    "bit-plane refetch",
)
join_plan_seconds = REGISTRY.histogram(
    "geomesa_join_plan_seconds", "join planning time (per join)"
)
join_refine_seconds = REGISTRY.histogram(
    "geomesa_join_refine_seconds",
    "join refinement time (expansion + launches + emission, per join)",
)

# Arrow-native result plane (results/): wire-format serving and export
# throughput by (bounded) format label, encode time split from the
# socket write, and the fused device BIN rider's launch count
results_batches = REGISTRY.counter(
    "geomesa_results_batches_total",
    "wire record batches / chunks emitted by the result plane (fmt)",
)
results_bytes = REGISTRY.counter(
    "geomesa_results_bytes_total",
    "response/export body bytes encoded by the result plane (fmt)",
)
results_encode_seconds = REGISTRY.histogram(
    "geomesa_results_encode_seconds",
    "wire-format serialization time per response (socket write excluded)",
)
results_write_seconds = REGISTRY.histogram(
    "geomesa_results_write_seconds",
    "socket write time per response (serialization excluded)",
)
results_bin_device_launches = REGISTRY.counter(
    "geomesa_results_bin_device_launches_total",
    "fused device BIN pack launches (count->cap->compact pairs count one)",
)

# replicated serving tier (replica.py + router.py): WAL shipping volume,
# follower apply/lag, failover accounting and the router front tier's
# per-backend routing outcomes
replica_ship_bytes = REGISTRY.counter(
    "geomesa_replica_ship_bytes_total",
    "WAL record bytes a leader shipped to followers over /wal/<type>",
)
replica_ship_records = REGISTRY.counter(
    "geomesa_replica_ship_records_total",
    "WAL records a leader shipped to followers",
)
replica_apply_records = REGISTRY.counter(
    "geomesa_replica_apply_records_total",
    "shipped WAL records a follower applied into its live layer",
)
replica_apply_skipped = REGISTRY.counter(
    "geomesa_replica_apply_skipped_total",
    "shipped records skipped as already durable here (idempotent replay)",
)
replica_lag_records = REGISTRY.gauge(
    "geomesa_replica_lag_records",
    "records the leader holds that this follower has not applied yet "
    "(summed across types)",
)
replica_failovers = REGISTRY.counter(
    "geomesa_replica_failovers_total",
    "promotions this process performed after a leader-lease expiry",
)
replica_failover_seconds = REGISTRY.histogram(
    "geomesa_replica_failover_seconds",
    "lease-expiry-to-leader-role promotion time per failover",
)
replica_role = REGISTRY.gauge(
    "geomesa_replica_role",
    "replication role of this process (0=follower, 1=promoting, 2=leader)",
)
replica_demotions = REGISTRY.counter(
    "geomesa_replica_demotions_total",
    "leader roles this process surrendered after observing a higher "
    "election epoch (fencing: a stale leader must not take appends)",
)
replica_reprovisions = REGISTRY.counter(
    "geomesa_replica_reprovisions_total",
    "snapshot reprovisions this follower completed (410-gone, gap, "
    "diverged tail or repeated apply failure turned into a rebuild)",
)
replica_reprovision_seconds = REGISTRY.histogram(
    "geomesa_replica_reprovision_seconds",
    "trigger-to-tailing-again time per snapshot reprovision",
)

# snapshot plane (store/snapshot.py + the /snapshot/<type> ship
# endpoint): capture/pin accounting and shipped/installed volume
snapshot_captures = REGISTRY.counter(
    "geomesa_snapshot_captures_total",
    "consistent snapshots captured (pin written under the publish lock)",
)
snapshot_ship_bytes = REGISTRY.counter(
    "geomesa_snapshot_ship_bytes_total",
    "bytes shipped over GET /snapshot/<type> streams",
)
snapshot_ship_files = REGISTRY.counter(
    "geomesa_snapshot_ship_files_total",
    "file records shipped over GET /snapshot/<type> streams",
)
snapshot_installs = REGISTRY.counter(
    "geomesa_snapshot_installs_total",
    "downloaded snapshots swapped into a live tree (write-new-then-"
    "publish install)",
)
snapshot_install_bytes = REGISTRY.counter(
    "geomesa_snapshot_install_bytes_total",
    "bytes of verified snapshot files installed into a live tree",
)
snapshot_pins_reclaimed = REGISTRY.counter(
    "geomesa_snapshot_pins_reclaimed_total",
    "orphaned snapshot pins aged out past snapshot.pin.ttl.s by the "
    "GC/recovery sweep",
)
router_requests = REGISTRY.counter(
    "geomesa_router_requests_total",
    "requests the router front tier completed",
)
router_retries = REGISTRY.counter(
    "geomesa_router_retries_total",
    "reads re-tried on another replica after a backend failure",
)
router_sheds = REGISTRY.counter(
    "geomesa_router_sheds_total",
    "appends shed 503+Retry-After because no leader is known (promotion)",
)
router_backend_errors = REGISTRY.counter(
    "geomesa_router_backend_errors_total",
    "backend attempts that failed (connection error or 5xx)",
)

# continuous-query push tier (pubsub/): registry size, fused match
# launches/latency on the ingest path, delivery/replay volume and the
# teardown/heartbeat accounting on long-lived push streams
pubsub_subscriptions = REGISTRY.gauge(
    "geomesa_pubsub_subscriptions",
    "standing subscriptions currently armed in the registry",
)
pubsub_match_batches = REGISTRY.counter(
    "geomesa_pubsub_match_batches_total",
    "acked append batches matched against the subscription layout "
    "(one fused join launch each, regardless of subscription count)",
)
pubsub_match_pairs = REGISTRY.counter(
    "geomesa_pubsub_match_pairs_total",
    "subscription×feature pairs that survived exact residual + "
    "visibility refinement",
)
pubsub_match_seconds = REGISTRY.histogram(
    "geomesa_pubsub_match_seconds",
    "fused batch×subscriptions match time per acked append batch",
)
pubsub_events_delivered = REGISTRY.counter(
    "geomesa_pubsub_events_delivered_total",
    "alert events written to connected push streams",
)
pubsub_deliver_bytes = REGISTRY.counter(
    "geomesa_pubsub_deliver_bytes_total",
    "push-stream body bytes written to subscribers",
)
pubsub_replay_records = REGISTRY.counter(
    "geomesa_pubsub_replay_records_total",
    "WAL records re-matched below a resuming subscriber's cursor",
)
pubsub_heartbeats = REGISTRY.counter(
    "geomesa_pubsub_heartbeats_total",
    "SSE :keepalive comments written to idle push streams",
)
pubsub_stream_overflows = REGISTRY.counter(
    "geomesa_pubsub_stream_overflows_total",
    "push streams torn down because their live event queue overflowed "
    "(the client resumes exactly-once from its cursor)",
)
pubsub_rearms = REGISTRY.counter(
    "geomesa_pubsub_rearms_total",
    "matcher re-arms from the replicated registry (promotion/recovery)",
)
