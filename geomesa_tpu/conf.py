"""Runtime system properties.

Ref role: geomesa-utils .../conf/GeoMesaSystemProperties [UNVERIFIED -
empty reference mount] -- the third config tier (SURVEY.md section 5:
store params / SFT user-data / JVM system properties). Each property has a
default, an environment override (``GEOMESA_TPU_<NAME>`` with dots as
underscores), and a programmatic override for tests
(``set_prop``/``clear_prop`` or the ``prop_override`` context manager).

Properties:

- ``scan.ranges.target``        max z-ranges per query plan (ref
                                geomesa.scan.ranges.target)
- ``query.timeout``             per-query wall-clock budget in ms; 0 = off
                                (ref geomesa.query.timeout)
- ``query.block.full.table``    raise instead of running a full-table scan
                                (ref geomesa.scan.block.full.table)
- ``query.max.features``        global cap on returned features; 0 = off
- ``scan.chunk``                KV scan deserialization chunk size
- ``io.workers``                host-I/O pipeline decode threads (0 =
                                serial; store/prefetch.py)
- ``io.readahead``              partition chunks in flight ahead of the
                                consumer (0 = auto: 2 x workers)
- ``io.queue.bytes``            byte budget for decoded chunks waiting
                                in the prefetch queue (0 = unbounded)
- ``io.retries``                transient-read retries per partition read
                                beyond the first attempt (0 = no retry)
- ``io.backoff.ms``             base backoff before a read retry, doubling
                                per attempt (bounded exponential)
- ``store.verify``              partition checksum verification: ``off``,
                                ``open`` (verify every file at store
                                open), ``always`` (verify on every read)
- ``store.fsync``               fsync partition files, directories and
                                manifests on flush (crash durability;
                                ``off`` trades it for speed, e.g. tmpfs
                                or throwaway benchmark stores)
- ``store.format.version``      partition manifest format written by
                                flushes: 2 = chunked columnar with
                                per-chunk statistics (the default),
                                1 = legacy (no chunk stats; what
                                pre-chunk stores read as)
- ``store.chunk.rows``          rows per chunk in v2 partition files
                                (parquet row groups align 1:1)
- ``store.chunk.grid``          coarse density-histogram grid edge
                                (grid x grid world cells per chunk)
- ``store.chunk.prune``         prune non-intersecting chunks from
                                streamed scans before read/decode
- ``store.chunk.pushdown``      answer chunk-tolerant density/count/
                                stats queries from the manifest's
                                pre-aggregates (boundary chunks still
                                row-refine; exact for count/stats)
- ``trace.sample``              head-sampling probability for request
                                traces (0..1; tracing.py). Sampled
                                traces are retained in the recent-trace
                                ring regardless of duration
- ``trace.slow_ms``             always-capture threshold: any request
                                slower than this is retained AND
                                appended to the slow-query log, sampled
                                or not (0 disables slow capture; with
                                ``trace.sample=0`` that turns span
                                recording off entirely)
- ``trace.device.dir``          when set, sampled queries wrap their
                                device launch in a ``jax.profiler``
                                trace dumped to this directory
                                (profiling.device_trace); "" = off
- ``io.backoff.cap.ms``         cumulative cap on the transient-read
                                backoff a single partition read may
                                sleep (retries stop once spent)
- ``resilience.enabled``        master switch for the fault-tolerance
                                layer (resilience.py): breakers, serving
                                retries, watchdog, degradation ladder
- ``resilience.degrade``        allow degraded (approximate / partial,
                                stamped ``X-Degraded``) answers instead
                                of failing when a domain is unhealthy
- ``resilience.retries``        serving-path retries of RETRYABLE
                                faults beyond the first attempt
- ``resilience.backoff.ms``     base serving-retry backoff, doubling
                                per attempt, jittered 0.5-1.5x
- ``resilience.backoff.cap.ms`` cumulative serving-retry backoff cap
- ``resilience.breaker.failures``  consecutive failures that open a
                                circuit breaker
- ``resilience.breaker.cooldown.s``  seconds a breaker stays open
                                before half-opening for one probe
- ``resilience.launch.timeout.s``  device-launch watchdog budget: a
                                scheduler execution stuck longer is
                                failed and its worker replaced (0 =
                                watchdog off)
- ``resilience.brownout.queue.frac``  scheduler-queue fill fraction
                                past which exact aggregate answers
                                yield to chunk-pushdown approximations
                                (0 disables brownout)
- ``mesh.enabled``              serve resident indexes sharded across a
                                device mesh (ShardedDeviceIndex) when
                                more than one jax device is visible
- ``mesh.devices``              devices in the serving mesh (0 = all
                                visible devices)
- ``mesh.replicas``             replica axis size: the mesh factors as
                                shard x replica and the resident planes
                                replicate across the replica axis (1 =
                                pure sharding)
- ``mesh.sort.engine``          distributed-sort node-local stage
                                engine: ``auto`` (host radix on all-CPU
                                meshes, device otherwise), ``device``
                                (everything in one jitted launch) or
                                ``host`` (numpy radix local sorts + XLA
                                all_to_all exchange)
- ``compile.bucket.growth``     geometric ratio of the canonical
                                compile-shape ladder (bucketing.py)
                                every dynamic trace shape rounds up
                                onto; 2.0 (default) = next power of
                                two, <= 1 disables bucketing (the
                                parity-test oracle)
- ``compile.bucket.min``        smallest ladder rung
- ``compile.warmup.enabled``    AOT warmup master switch (warmup.py):
                                pre-compile the closed bucket x
                                kernel-family signature set at server
                                start (``serve --resident --warm``)
- ``compile.warmup.gate``       /readyz behavior while warmup runs:
                                ``ready`` (default) holds 503 until
                                warm — a fleet rolling restart then
                                never routes to a cold process;
                                ``stamp`` serves immediately but
                                stamps ``warming``; ``off`` hides
                                warmup from readiness
- ``compile.warmup.threads``    bounded background compile pool size
- ``compile.warmup.knn.kmax``   largest kNN k the warmup k-ladder
                                pre-compiles
- ``slo.coldstart.threshold.ms``  the cold-start SLO: bench.py
                                ``--mode coldstart`` fails if a WARMED
                                first query per kernel family answers
                                over this bar
- ``slo.enabled``               serving SLO engine master switch
                                (slo.py): windowed latency tracking,
                                burn rates, /stats/slo, the flight
                                recorder
- ``slo.<name>.objective``      fraction of requests that must answer
                                under the threshold (error budget =
                                1 - objective); one set of keys per
                                registered SLO name (slo.SLO_NAMES:
                                ``interactive``, ``batch``, ``ingest``)
- ``slo.<name>.threshold.ms``   the latency bar a GOOD request answers
                                under (5xx responses are always bad)
- ``slo.<name>.window.s``       the slow burn window (and the windowed
                                histogram ring span) for this SLO
- ``slo.burn.fast.s``           the fast burn window shared by every
                                SLO (classic multi-window burn alerts:
                                fast 5m / slow 1h)
- ``slo.flightrec.burn``        fast-window burn rate at which the
                                flight recorder snapshots a postmortem
                                bundle (0 disables the burn trigger;
                                breaker-open triggers stay on)
- ``slo.flightrec.keep``        bundles retained under _flightrec/
                                (oldest pruned past this)
- ``slo.flightrec.interval.s``  min seconds between bundles PER REASON
                                (a sustained burn must not disk-flood)
- ``ledger.enabled``            per-request cost ledger master switch
                                (ledger.py): request cost collection,
                                compile attribution, /stats/ledger
- ``ledger.topk``               entries per ranking in the
                                /stats/ledger document (tenants,
                                shapes, top requests, compile sigs)
- ``stream.enabled``            streaming live layer (store/stream.py):
                                WAL-backed ``append`` with an in-memory
                                generation that serves immediately and
                                background compaction into the store's
                                generation files
- ``wal.segment.bytes``         write-ahead-log segment rotation size;
                                a full segment is sealed (fsynced) and
                                a new one opened
- ``wal.max.generations``       live memtable runs allowed before
                                appends backpressure (429-style) — the
                                read-amplification bound: every merged
                                query scans at most this many runs on
                                top of the resident/on-disk data
- ``stream.memtable.rows``      memtable rows that trigger background
                                compaction into the partition files
- ``stream.run.rows``           target rows per Z-sorted memtable run:
                                appends coalesce into the tail run
                                until it reaches this size (bounds the
                                per-append re-sort AND run count)
- ``stream.compact.yield.ms``   per-check pause the compactor yields
                                to serving load while the scheduler
                                queue is brownout-saturated (bounded:
                                it compacts regardless once appends
                                are backpressured)
- ``stream.stall.s``            seconds without a successful compaction
                                while appends are backpressured before
                                the flight recorder snapshots an
                                ``ingest-stall`` postmortem bundle
- ``stream.append.max.bytes``   request-body bound for POST /append
                                (413 past it): one append becomes one
                                WAL record and one memtable run, so an
                                unbounded body would be an unbounded
                                allocation (0 disables the bound)
- ``join.engine``               spatial-join refinement engine
                                (join/engine.py): ``auto`` (numpy host
                                twin on all-CPU platforms — the
                                mesh.sort.engine precedent — batched
                                device launches otherwise), ``device``
                                or ``host``
- ``join.strategy``             pin the join planner's strategy:
                                ``auto`` (adaptive selection from the
                                staged histogram), ``broadcast``,
                                ``grouped`` or ``zmerge``
- ``join.broadcast.windows``    right-side size at or below which the
                                planner broadcasts (whole-side scans
                                per window; planning would cost more
                                than it prunes)
- ``join.split.rows``           skew-splitting escape: candidate runs
                                longer than this split into bounded
                                sub-runs (hot cells must not blow a
                                launch's candidate budget or unbalance
                                co-partitioned shards)
- ``join.batch.candidates``     candidate budget per refinement batch
                                (one count + one compact launch per
                                batch; bounds device scratch and the
                                host chunk working set)
- ``join.hist.bits``            left-side statistics grid (2^bits per
                                axis) the planner estimates
                                selectivity/skew from; also the
                                ``grouped`` strategy's cell level
- ``join.xz.ranges``            XZ code ranges per window when the
                                left side is a non-point (extent
                                curve) layout
- ``results.batch.rows``        rows per streamed wire record batch on
                                the Arrow-native result plane
                                (results/): bounds per-chunk memory on
                                /features streaming and bulk exports
- ``results.bin.engine``        BIN track-record encoder engine
                                (results/binrider.py): ``auto`` (numpy
                                host twin on all-CPU platforms — the
                                mesh.sort.engine precedent — fused
                                device pack otherwise), ``device`` or
                                ``host``
- ``http.keepalive.s``          idle socket timeout for persistent
                                HTTP/1.1 connections, both server-side
                                (server.py handler read timeout) and on
                                router->backend pooled connections --
                                the PR 12 hard-coded 60s, now tunable
- ``replica.poll.ms``           follower tail-loop pause between ship
                                cycles (the long-poll ``waitMs`` on
                                ``GET /wal/<type>`` covers latency;
                                this bounds the idle re-dial rate)
- ``replica.wait.ms``           long-poll budget a leader holds an
                                empty ``/wal/<type>`` ship open waiting
                                for new records before answering
- ``replica.lease.s``           leader lease: a follower that cannot
                                reach its leader for this long declares
                                it dead and starts an election
- ``replica.failover.s``        the declared promotion bound: failover
                                (detect -> elect -> promote) must
                                complete within it; exceeding it stamps
                                degraded and logs loudly
- ``replica.ack``               append acknowledgement mode: ``local``
                                (leader WAL durability only -- the
                                PR 10 contract) or ``replica`` (the 200
                                also waits until at least one follower
                                has applied the record's seq)
- ``replica.ack.timeout.s``     max wall-clock an append holds its
                                response open for a follower ack in
                                ``replica.ack=replica`` mode; past it
                                the row is acked local-only and
                                ``replica-lag`` is stamped degraded
- ``replica.retain.s``          follower-retention window on the
                                leader's WAL garbage collection: the
                                compactor never truncates segments past
                                the lowest position reported by a
                                follower seen within this window, so a
                                briefly-lagging follower keeps tailing
                                instead of falling off a 410 cliff
                                (a follower silent LONGER than this
                                stops pinning the log -- bounded disk)
- ``replica.reprovision.s``     bound on one snapshot reprovision
                                attempt (fetch -> verify -> install ->
                                swap): a follower past its leader's
                                compaction horizon must be tailing
                                again within it or the attempt aborts,
                                logs loudly and retries next cycle
- ``snapshot.pin.ttl.s``        GC-pin time-to-live: a snapshot pin
                                whose file has not been touched for
                                this long (stream dead, e.g. SIGKILLed
                                mid-ship) stops protecting its
                                generation and is reclaimed by the
                                next recovery/GC sweep; live streams
                                refresh their pin as they ship
- ``snapshot.chunk.bytes``      buffer size for snapshot stream file
                                reads/writes (``GET /snapshot/<type>``
                                and the install download)
- ``backup.wal.trailing``       ``backup`` copies the WAL segments
                                trailing the snapshot watermark (the
                                acked-but-uncompacted rows) so restore
                                replays them; 0 = snapshot only
- ``router.retries``            read retries across DISTINCT replicas
                                beyond the first backend the router
                                tries (router.py)
- ``router.health.ms``          router health-poll cadence: each
                                backend's ``/readyz`` and
                                ``/stats/replica`` are probed this
                                often to drive routing, breaker probes
                                and leader discovery
- ``admin.token``               shared secret gating POST
                                ``/admin/shutdown`` (sent as the
                                ``X-Admin-Token`` header); empty
                                (default) restricts the endpoint to
                                loopback peers instead -- any reachable
                                client being able to terminate the
                                process is not an operator plane
"""

from __future__ import annotations

import os
from contextlib import contextmanager


def _parse_bool(v) -> bool:
    return str(v).strip().lower() in ("true", "1", "t", "yes", "on")


def _parse_format(v) -> int:
    n = int(v)
    if n not in (1, 2):
        raise ValueError(f"store.format.version must be 1 or 2, not {v!r}")
    return n


def _parse_verify(v) -> str:
    s = str(v).strip().lower()
    if s not in ("off", "open", "always"):
        raise ValueError(
            f"store.verify must be off, open or always, not {v!r}"
        )
    return s


def _parse_sort_engine(v) -> str:
    s = str(v).strip().lower()
    if s not in ("auto", "device", "host"):
        raise ValueError(
            f"mesh.sort.engine must be auto, device or host, not {v!r}"
        )
    return s


def _parse_join_engine(v) -> str:
    s = str(v).strip().lower()
    if s not in ("auto", "device", "host"):
        raise ValueError(
            f"join.engine must be auto, device or host, not {v!r}"
        )
    return s


def _parse_results_bin_engine(v) -> str:
    s = str(v).strip().lower()
    if s not in ("auto", "device", "host"):
        raise ValueError(
            f"results.bin.engine must be auto, device or host, not {v!r}"
        )
    return s


def _parse_replica_ack(v) -> str:
    s = str(v).strip().lower()
    if s not in ("local", "replica"):
        raise ValueError(
            f"replica.ack must be local or replica, not {v!r}"
        )
    return s


def _parse_join_strategy(v) -> str:
    s = str(v).strip().lower()
    if s not in ("auto", "broadcast", "grouped", "zmerge"):
        raise ValueError(
            "join.strategy must be auto, broadcast, grouped or zmerge, "
            f"not {v!r}"
        )
    return s


def _parse_warmup_gate(v) -> str:
    s = str(v).strip().lower()
    if s not in ("ready", "stamp", "off"):
        raise ValueError(
            f"compile.warmup.gate must be ready, stamp or off, not {v!r}"
        )
    return s


from geomesa_tpu.curves.zranges import DEFAULT_MAX_RANGES

_DEFS = {
    "scan.ranges.target": (DEFAULT_MAX_RANGES, int),
    "query.timeout": (0, int),  # ms; 0 = unlimited
    "query.block.full.table": (False, _parse_bool),
    # answer bbox(+during) queries straight from the index key at cell
    # granularity, skipping residual refinement (ref geomesa.loose.bbox)
    "query.loose.bbox": (False, _parse_bool),
    "query.max.features": (0, int),  # 0 = unlimited
    "scan.chunk": (8192, int),  # KV scan deserialization chunk rows
    # host-I/O prefetch pipeline (store/prefetch.py): partition reads,
    # Arrow decode and column staging overlap the consumer on threads
    "io.workers": (4, int),  # 0 = serial host I/O (no pipeline threads)
    "io.readahead": (0, int),  # chunks in flight; 0 = auto (2 x workers)
    "io.queue.bytes": (256 << 20, int),  # decoded-queue byte budget; 0 = off
    # transient-read resilience (prefetch workers): retries beyond the
    # first attempt, with io.backoff.ms * 2^attempt sleeps between them
    "io.retries": (2, int),
    "io.backoff.ms": (25.0, float),
    # crash-consistency knobs (store/fs.py): read-side checksum
    # verification scope, and whether flushes fsync what they publish
    "store.verify": ("off", _parse_verify),
    "store.fsync": (True, _parse_bool),
    # chunked partition format v2 (store/fs.py + store/chunkstats.py):
    # write-format selector, chunk size (= parquet row-group size), the
    # coarse density-histogram grid, and the two read-side switches --
    # chunk-level scan pruning (oocscan) and aggregation pushdown
    "store.format.version": (2, _parse_format),
    "store.chunk.rows": (1 << 16, int),
    "store.chunk.grid": (64, int),
    "store.chunk.prune": (True, _parse_bool),
    "store.chunk.pushdown": (True, _parse_bool),
    # per-request tracing (tracing.py): head-sampling probability, the
    # slow-query always-capture threshold, and the optional jax.profiler
    # device-trace dump directory for sampled launches
    "trace.sample": (1.0, float),
    "trace.slow_ms": (500.0, float),
    "trace.device.dir": ("", str),
    # device query scheduler defaults (sched/scheduler.py,
    # SchedConfig.from_props): admission queue bound, worker/inflight
    # cap, fusion window + width, default deadline (<= 0 = none) and the
    # 429 Retry-After hint
    "sched.max.queue": (128, int),
    "sched.max.inflight": (2, int),
    "sched.fusion.window.ms": (2.0, float),
    "sched.max.fusion": (64, int),
    "sched.default.deadline.ms": (30_000.0, float),
    "sched.retry.after.s": (1.0, float),
    # transient-read backoff cumulative cap (store/prefetch.py): with
    # io.retries x io.backoff.ms doubling AND jitter, this bounds the
    # total wall-clock one read may sleep before surfacing the error
    "io.backoff.cap.ms": (1000.0, float),
    # fault-tolerant serving (resilience.py): master switch, the
    # degraded-answers switch, serving-retry budget/backoff, breaker
    # thresholds, the device-launch watchdog and the brownout ladder
    "resilience.enabled": (True, _parse_bool),
    "resilience.degrade": (True, _parse_bool),
    "resilience.retries": (2, int),
    "resilience.backoff.ms": (25.0, float),
    "resilience.backoff.cap.ms": (2000.0, float),
    "resilience.breaker.failures": (5, int),
    "resilience.breaker.cooldown.s": (5.0, float),
    "resilience.launch.timeout.s": (30.0, float),
    "resilience.brownout.queue.frac": (0.8, float),
    # multi-chip sharded serving (parallel/, device_cache.py): mesh
    # topology for the resident-index shards and the distributed-sort
    # node-local engine selector
    "mesh.enabled": (False, _parse_bool),
    "mesh.devices": (0, int),
    "mesh.replicas": (1, int),
    "mesh.sort.engine": ("auto", _parse_sort_engine),
    # canonical compile-shape bucketing (bucketing.py): the geometric
    # capacity ladder every dynamic trace shape rounds up onto (growth
    # 2.0 = the historical next-power-of-two; <= 1 disables bucketing
    # -- the parity-test oracle, never a serving configuration)
    "compile.bucket.growth": (2.0, float),
    "compile.bucket.min": (1, int),
    # AOT warmup (warmup.py): pre-compile the closed bucket x kernel-
    # family signature set at server start -- master switch, the
    # /readyz behavior while compiling ("ready" holds 503, "stamp"
    # serves but stamps warming, "off" hides warmup from readiness),
    # the bounded background compile pool and the kNN k-ladder bound
    "compile.warmup.enabled": (True, _parse_bool),
    "compile.warmup.gate": ("ready", _parse_warmup_gate),
    "compile.warmup.threads": (2, int),
    "compile.warmup.knn.kmax": (64, int),
    # cold-start SLO (bench.py --mode coldstart): the bar a WARMED
    # first query per kernel family must answer under
    "slo.coldstart.threshold.ms": (2000.0, float),
    # serving SLO engine (slo.py): master switch, one
    # objective/threshold/window triple per registered SLO name
    # (slo.SLO_NAMES), the shared fast burn window, and the flight
    # recorder's trigger threshold / retention / rate limit
    "slo.enabled": (True, _parse_bool),
    "slo.interactive.objective": (0.999, float),
    "slo.interactive.threshold.ms": (500.0, float),
    "slo.interactive.window.s": (3600.0, float),
    "slo.batch.objective": (0.99, float),
    "slo.batch.threshold.ms": (5000.0, float),
    "slo.batch.window.s": (3600.0, float),
    # the streaming-append lane's own budget (a 429-shed append is the
    # backpressure contract, not an SLO breach; 5xx and slow acks are)
    "slo.ingest.objective": (0.999, float),
    "slo.ingest.threshold.ms": (100.0, float),
    "slo.ingest.window.s": (3600.0, float),
    "slo.burn.fast.s": (300.0, float),
    "slo.flightrec.burn": (8.0, float),
    "slo.flightrec.keep": (8, int),
    "slo.flightrec.interval.s": (60.0, float),
    # per-request cost ledger (ledger.py): master switch and the
    # /stats/ledger ranking size
    "ledger.enabled": (True, _parse_bool),
    "ledger.topk": (10, int),
    # streaming live layer (store/stream.py + store/wal.py): master
    # switch, WAL segment rotation, the read-amplification bound
    # (appends backpressure past it), memtable sizing and the
    # compactor's yield/stall knobs
    "stream.enabled": (False, _parse_bool),
    "wal.segment.bytes": (4 << 20, int),
    "wal.max.generations": (8, int),
    "stream.memtable.rows": (1 << 15, int),
    "stream.run.rows": (8192, int),
    "stream.compact.yield.ms": (50.0, float),
    "stream.stall.s": (30.0, float),
    "stream.append.max.bytes": (32 << 20, int),
    # device-side spatial join engine (join/): execution engine +
    # planner strategy selectors, the skew-split bound, per-launch
    # candidate budget, the statistics grid and the non-point (XZ)
    # per-window range budget
    "join.engine": ("auto", _parse_join_engine),
    "join.strategy": ("auto", _parse_join_strategy),
    "join.broadcast.windows": (64, int),
    "join.split.rows": (1 << 16, int),
    "join.batch.candidates": (1 << 20, int),
    "join.hist.bits": (8, int),
    "join.xz.ranges": (32, int),
    # Arrow-native result plane (results/): rows per streamed wire
    # record batch (bounds per-chunk memory on /features streaming and
    # bulk exports) and the BIN track-record encoder engine selector
    "results.batch.rows": (8192, int),
    "results.bin.engine": ("auto", _parse_results_bin_engine),
    # replicated serving tier (replica.py + router.py): persistent-
    # connection idle timeout, follower tail cadence + leader long-poll
    # budget, the leader lease / declared failover bound, the append
    # acknowledgement mode, and the router's retry/health knobs
    "http.keepalive.s": (60.0, float),
    "replica.poll.ms": (50.0, float),
    "replica.wait.ms": (1000.0, float),
    "replica.lease.s": (3.0, float),
    "replica.failover.s": (10.0, float),
    "replica.ack": ("local", _parse_replica_ack),
    "replica.ack.timeout.s": (2.0, float),
    "replica.retain.s": (600.0, float),
    "replica.reprovision.s": (60.0, float),
    # snapshot plane (store/snapshot.py, ISSUE 15): consistent-snapshot
    # GC pin TTL (orphaned pins from a killed stream age out under it),
    # the ship/stream chunk size, and backup's trailing-WAL toggle
    "snapshot.pin.ttl.s": (300.0, float),
    "snapshot.chunk.bytes": (512 << 10, int),
    "backup.wal.trailing": (1, int),
    "router.retries": (2, int),
    "router.health.ms": (250.0, float),
    # operator plane: shared secret for POST /admin/shutdown (empty =
    # loopback peers only)
    "admin.token": ("", str),
    # continuous-query push tier (pubsub/): SSE heartbeat cadence on
    # idle push streams, the per-connection live event-queue bound
    # (overflow tears the stream down — the client resumes from its
    # cursor), how long a disconnected subscriber's cursor keeps
    # pinning WAL GC, and the per-type registry bound
    "sub.heartbeat.s": (15.0, float),
    "sub.queue.events": (1024, int),
    "sub.retain.s": (600.0, float),
    "sub.max.per.type": (4096, int),
}

_overrides: dict = {}


def declared_keys() -> "frozenset[str]":
    """Every declared system-property key -- the GT008 key registry
    (analysis/rules/gt008_conf_keys.py validates string literals used
    via this module against it)."""
    return frozenset(_DEFS)


def _env_key(name: str) -> str:
    return "GEOMESA_TPU_" + name.upper().replace(".", "_")


#: GEOMESA_TPU_* environment variables that are NOT system-property
#: overrides (other subsystems' switches) -- exempt from the
#: unknown-key warning below
_NON_PROP_ENV = frozenset(
    {
        "GEOMESA_TPU_ROOT",  # tools/cli.py default store root
        "GEOMESA_TPU_FAILPOINTS",  # failpoints.py activation list
        "GEOMESA_TPU_LOCKCHECK",  # analysis/lockcheck.py switch
        "GEOMESA_TPU_CTXCHECK",  # analysis/ctxcheck.py switch
        "GEOMESA_TPU_COMPILECHECK",  # analysis/compilecheck.py switch
        "GEOMESA_TPU_NO_NATIVE",  # native.py opt-out
        "GEOMESA_TPU_COMPILE_CACHE",  # jaxconf.py persistent-cache off switch
    }
)

_env_checked = False


def _warn_unknown_env() -> None:
    """One warning per process for each ``GEOMESA_TPU_*`` environment
    variable that maps to no declared key: an override for a key that
    does not exist (typo'd ``GEOMESA_TPU_IO_WORKER``) would otherwise be
    silently ignored -- the quiet twin of the GT008 lint rule."""
    global _env_checked
    if _env_checked:
        return
    _env_checked = True
    known = {_env_key(n) for n in _DEFS}
    unknown = [
        k
        for k in sorted(os.environ)
        if k.startswith("GEOMESA_TPU_")
        and k not in known
        and k not in _NON_PROP_ENV
    ]
    if unknown:
        import logging

        for k in unknown:
            logging.getLogger(__name__).warning(
                "environment variable %s matches no declared system "
                "property (see conf._DEFS) and is ignored", k,
            )


def sys_prop(name: str):
    """Resolve a property: programmatic override > env > default."""
    _warn_unknown_env()
    if name not in _DEFS:
        raise KeyError(f"unknown system property {name!r}")
    default, parse = _DEFS[name]
    if name in _overrides:
        return _overrides[name]
    env = os.environ.get(_env_key(name))
    if env is not None:
        return parse(env)
    return default


def set_prop(name: str, value) -> None:
    if name not in _DEFS:
        raise KeyError(f"unknown system property {name!r}")
    _overrides[name] = _DEFS[name][1](value)


def clear_prop(name: str) -> None:
    _overrides.pop(name, None)


_MISSING = object()


@contextmanager
def prop_override(name: str, value):
    prev = _overrides.get(name, _MISSING)
    set_prop(name, value)
    try:
        yield
    finally:
        if prev is _MISSING:
            clear_prop(name)
        else:
            _overrides[name] = prev


class QueryTimeout(RuntimeError):
    """Raised when a query exceeds the ``query.timeout`` budget."""
