"""Fault-tolerant serving (ISSUE 7): fault classification, circuit breakers,
launch watchdog, staging-OOM recovery, the degradation ladder,
healthz/readyz + draining shutdown, adaptive Retry-After, and the chaos
contract — every admitted request gets EXACTLY ONE response (success,
degraded, or typed error; never a hang or a bare 500)."""

import json
import threading
import time
import urllib.error
import urllib.request
from urllib.parse import quote

import numpy as np
import pytest

from geomesa_tpu import failpoints, metrics, resilience
from geomesa_tpu.conf import prop_override
from geomesa_tpu.filter.ecql import parse_instant
from geomesa_tpu.sched import QueryScheduler, SchedConfig

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


@pytest.fixture(autouse=True)
def _fresh_breakers():
    resilience.reset()
    yield
    resilience.reset()


def _mem_store(n=2000, seed=17, audit=None):
    from geomesa_tpu.store.memory import MemoryDataStore

    ds = MemoryDataStore(audit_writer=audit)
    ds.create_schema("gdelt", SPEC)
    rng = np.random.default_rng(seed)
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
    return ds


def _fs_store(root, n=600, partition_size=128, audit=False):
    from geomesa_tpu.store.fs import FileSystemDataStore

    ds = FileSystemDataStore(
        str(root), partition_size=partition_size, audit=audit
    )
    ds.create_schema("t", "val:Int,dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(3)
    ds.write("t", {
        "val": rng.integers(0, 100, n),
        "dtg": rng.integers(0, 10**9, n),
        "geom": np.stack(
            [rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)], axis=1
        ),
    }, fids=np.arange(n))
    ds.flush("t")
    return ds


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, dict(r.headers), r.read()


def _get_err(url):
    try:
        _get(url)
        return None
    except urllib.error.HTTPError as e:
        return e


# -- fault classification ---------------------------------------------------------


def test_classify_fault_classes():
    from geomesa_tpu.sched.scheduler import DeadlineExpired, RejectedError
    from geomesa_tpu.store.fs import PartitionCorruptError

    C = resilience.classify
    assert C(RejectedError(1.0)) == resilience.FATAL
    assert C(DeadlineExpired()) == resilience.FATAL
    assert C(ValueError("bad cql")) == resilience.FATAL
    assert C(KeyError("nosuch")) == resilience.FATAL
    assert C(FileNotFoundError("gone")) == resilience.FATAL
    assert C(OSError("flaky disk")) == resilience.RETRYABLE
    assert C(failpoints.FailpointError("x")) == resilience.RETRYABLE
    assert C(MemoryError()) == resilience.DEGRADABLE
    assert (
        C(RuntimeError("RESOURCE_EXHAUSTED: out of memory while ..."))
        == resilience.DEGRADABLE
    )
    assert C(resilience.LaunchStuckError("stuck")) == resilience.DEGRADABLE
    assert (
        C(resilience.PartitionUnavailableError("t", 3, "io"))
        == resilience.DEGRADABLE
    )
    assert C(PartitionCorruptError("bad crc")) == resilience.DEGRADABLE
    assert C(RuntimeError("anything else")) == resilience.FATAL


def test_classify_jax_runtime_error_is_retryable():
    """jax 0.9 raises device runtime faults as jax.errors.JaxRuntimeError
    (the old XlaRuntimeError name is gone): a non-OOM one is transient,
    a RESOURCE_EXHAUSTED one is an OOM."""
    import jax

    C = resilience.classify
    assert (
        C(jax.errors.JaxRuntimeError("INTERNAL: failed to execute"))
        == resilience.RETRYABLE
    )
    assert (
        C(jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                                     "while trying to allocate 4.00G"))
        == resilience.DEGRADABLE
    )


def test_vmem_compile_refusal_is_not_oom():
    """A kernel the TPU compiler refuses for VMEM reads RESOURCE_EXHAUSTED
    but is a bug, not a load condition: not an OOM, FATAL, never
    degraded away (the weighted 512x512 density kernel was refused so)."""
    import jax

    e = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %fn.1 = f32[512,512] custom-call(...), "
        'custom_call_target="tpu_custom_call"'
    )
    assert resilience.is_compile_refusal(e)
    assert not resilience.is_oom(e)
    assert resilience.classify(e) == resilience.FATAL
    hbm = jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 8.00G"
    )
    assert not resilience.is_compile_refusal(hbm)
    assert resilience.is_oom(hbm)


def test_backoff_sleeps_jitter_and_cumulative_cap():
    # jitter: each delay is base*2^k scaled into [0.5, 1.5)
    for _ in range(20):
        ds = list(resilience.backoff_sleeps(3, 100, 0))
        assert len(ds) == 3
        for k, d in enumerate(ds):
            lo, hi = 0.05 * (1 << k), 0.15 * (1 << k)
            assert lo <= d < hi
    # cumulative cap: total sleep never exceeds the budget
    for _ in range(20):
        ds = list(resilience.backoff_sleeps(10, 50, 120))
        assert sum(ds) <= 0.120 + 1e-9
        assert len(ds) < 10  # the cap stopped the schedule early
    # base 0 = immediate retries: the retry COUNT must survive the cap
    # (regression: zero-delay sleeps must not read as budget-exhausted)
    assert list(resilience.backoff_sleeps(3, 0.0, 1000.0)) == [0, 0, 0]


def test_retry_call_retries_then_raises():
    calls = []

    def flaky():
        calls.append(1)
        raise OSError("transient")

    with prop_override("resilience.retries", 3), \
            prop_override("resilience.backoff.ms", 1.0):
        r0 = metrics.resilience_retries.value(domain="device")
        with pytest.raises(OSError):
            resilience.retry_call(flaky, domain="device")
        assert len(calls) == 4  # first attempt + 3 retries
        assert metrics.resilience_retries.value(domain="device") - r0 == 3

    # FATAL faults never retry
    calls.clear()

    def bad():
        calls.append(1)
        raise ValueError("bad request")

    with pytest.raises(ValueError):
        resilience.retry_call(bad)
    assert len(calls) == 1


# -- circuit breakers -------------------------------------------------------


def test_breaker_state_machine():
    b = resilience.CircuitBreaker(
        "t", domain="device", failures=3, cooldown_s=0.1
    )
    assert b.state == "closed" and b.allow()
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"
    b.record_success()  # success resets the consecutive count
    b.record_failure()
    b.record_failure()
    b.record_failure()
    assert b.state == "open"
    assert not b.allow()
    time.sleep(0.12)
    assert b.allow()  # the half-open probe
    assert b.state == "half-open"
    assert not b.allow()  # only ONE probe at a time
    b.record_failure()  # failed probe: re-open
    assert b.state == "open" and not b.allow()
    time.sleep(0.12)
    assert b.allow()
    b.record_success()
    assert b.state == "closed" and b.allow()
    snap = b.snapshot()
    assert snap["opens"] == 2 and snap["state"] == "closed"


def test_breaker_disabled_by_master_switch():
    b = resilience.CircuitBreaker("t", domain="device", failures=1,
                                  cooldown_s=60)
    b.record_failure()
    assert b.state == "open"
    with prop_override("resilience.enabled", False):
        assert b.allow()  # disabled: never gates


def test_partition_breakers_are_scoped():
    a = resilience.partition_breaker("rootA:t", 0)
    b = resilience.partition_breaker("rootB:t", 0)
    assert a is not b
    assert resilience.partition_breaker("rootA:t", 0) is a
    for _ in range(a.failures):
        a.record_failure()
    assert a.state == "open" and b.state == "closed"
    assert resilience.open_partition_breakers() == 1
    assert resilience.snapshot()["partition_open"] == 1


# -- scheduler: watchdog, worker crash, adaptive Retry-After ----------------


def test_watchdog_fails_stuck_launch_and_replaces_worker():
    unwedge = threading.Event()
    sched = QueryScheduler(SchedConfig(
        max_queue=8, max_inflight=1, default_deadline_ms=None
    ))
    try:
        with prop_override("resilience.launch.timeout.s", 0.3):
            t0 = time.monotonic()
            req = sched.submit(fn=lambda: unwedge.wait(10), device=True)
            with pytest.raises(resilience.LaunchStuckError):
                sched.wait(req)
            # failed promptly (not after the 10s wedge)
            assert time.monotonic() - t0 < 5.0
            # the wedged worker was replaced: the scheduler still serves
            assert sched.run(fn=lambda: 42) == 42
            snap = sched.snapshot()
            assert snap["watchdog_timeouts"] == 1
            assert snap["running"] == 0  # the abandoned group retired
            # the abandoned entry was POPPED, not just flagged: the
            # wedged worker never returns to retire it, and a leaked
            # entry would pin the group (closures, results) forever
            # while the watchdog rescans it every tick
            with sched._cv:
                assert not sched._inflight
            # the device breaker recorded the stuck launch
            assert (
                resilience.device_breaker().snapshot()[
                    "consecutive_failures"
                ] >= 1
            )
    finally:
        unwedge.set()
        sched.close(timeout=2.0)


def test_watchdog_exactly_once_when_stuck_fn_returns():
    """The abandoned worker's late completion must NOT overwrite the
    watchdog's answer (idempotent _finish) and the late worker exits."""
    release = threading.Event()
    sched = QueryScheduler(SchedConfig(
        max_queue=8, max_inflight=1, default_deadline_ms=None
    ))
    try:
        with prop_override("resilience.launch.timeout.s", 0.2):
            req = sched.submit(
                fn=lambda: release.wait(10) or "late", device=True
            )
            with pytest.raises(resilience.LaunchStuckError):
                sched.wait(req)
            release.set()  # the wedged fn now completes
            time.sleep(0.3)
            # the first (watchdog) completion stands
            assert isinstance(req.error, resilience.LaunchStuckError)
            assert req.result is None
            assert sched.run(fn=lambda: 7) == 7
    finally:
        release.set()
        sched.close(timeout=2.0)


def test_watchdog_exempts_host_groups():
    """A long-but-progressing HOST scan (fn work not flagged device)
    must not be failed as a stuck launch nor charged to the DEVICE
    breaker — only its deadline and the io.* retry budget bound it."""
    sched = QueryScheduler(SchedConfig(
        max_queue=8, max_inflight=1, default_deadline_ms=None
    ))
    try:
        with prop_override("resilience.launch.timeout.s", 0.2):
            c0 = resilience.device_breaker().snapshot()[
                "consecutive_failures"
            ]
            # runs 3x past the launch timeout, then finishes normally
            assert sched.run(fn=lambda: time.sleep(0.6) or "done") == "done"
            snap = sched.snapshot()
            assert snap["watchdog_timeouts"] == 0
            assert (
                resilience.device_breaker().snapshot()[
                    "consecutive_failures"
                ] == c0
            )
    finally:
        sched.close(timeout=2.0)


def test_watchdog_stall_clock_restarts_on_rider_progress():
    """A fusion-declined group executed serially makes progress launch
    by launch: the watchdog must time the CURRENT launch's stall, not
    the group's cumulative wall-clock."""
    sched = QueryScheduler(SchedConfig(
        max_queue=16, max_inflight=1, fusion_window_ms=200,
        max_fusion=8, default_deadline_ms=None,
    ))

    class _Serial:
        """Fusable by key, but execute_group always declines (no
        DeviceIndex) so the group runs serially via run_serial."""

        fusable = True
        key = ("k",)

        def run_serial(self):
            time.sleep(0.15)
            return "ok"

    try:
        with prop_override("resilience.launch.timeout.s", 0.3):
            # 4 riders x 0.15s = 0.6s group wall-clock, 2x the launch
            # timeout — but each launch completes well within it
            reqs = [sched.submit(fuse=_Serial()) for _ in range(4)]
            assert [sched.wait(r) for r in reqs] == ["ok"] * 4
            assert sched.snapshot()["watchdog_timeouts"] == 0
    finally:
        sched.close(timeout=2.0)


def test_fatal_probe_releases_the_slot():
    """A half-open probe that dies on a FATAL fault (bad request) says
    nothing about device health: the slot must free, same as a shed
    probe (tested below via release_probe directly)."""
    with prop_override("resilience.breaker.failures", 1), \
            prop_override("resilience.breaker.cooldown.s", 30.0):
        br = resilience.CircuitBreaker("fatal-probe-test", "device")
        br.record_failure()
        br._opened_at -= 31.0  # cooldown elapsed
        assert br.allow() and br.state == "half-open"
        br.release_probe()  # what _degradable does on a FATAL probe
        assert br.allow()  # fresh probe without another cooldown


def test_partition_breaker_registry_hard_bound():
    """With every keyed breaker open (store-wide outage) the registry
    must still evict — the bound is hard, not best-effort."""
    from geomesa_tpu.resilience import _PARTITION_BREAKERS_MAX, _breakers

    with prop_override("resilience.breaker.failures", 1):
        for i in range(_PARTITION_BREAKERS_MAX + 50):
            resilience.partition_breaker("hb:t", i).record_failure()
        keyed = [k for k in _breakers if isinstance(k, tuple)]
        assert len(keyed) <= _PARTITION_BREAKERS_MAX
        # the newest breakers survived; the oldest were evicted
        assert ("partition", "hb:t", _PARTITION_BREAKERS_MAX + 49) in _breakers


def test_shed_half_open_probe_frees_the_slot():
    """A probe request shed by flow control (429/504) carries no health
    signal: the slot must free immediately, not after another cooldown,
    or a saturated queue pins the breaker half-open indefinitely."""
    with prop_override("resilience.breaker.failures", 1), \
            prop_override("resilience.breaker.cooldown.s", 0.05):
        br = resilience.CircuitBreaker("probe-release-test", "device")
        br.record_failure()
        assert br.state == "open"
        time.sleep(0.06)
        assert br.allow()  # the half-open probe slot
        assert br.state == "half-open"
        assert not br.allow()  # one probe in flight at a time
        br.release_probe()  # the probe got shed: no outcome to report
        assert br.allow()  # a fresh probe, without waiting out a cooldown
        br.record_success()
        assert br.state == "closed"
        br.release_probe()  # closed: a no-op
        assert br.state == "closed"


def test_sched_worker_crash_fails_typed_and_keeps_serving():
    sched = QueryScheduler(SchedConfig(
        max_queue=8, max_inflight=1, default_deadline_ms=None
    ))
    try:
        with failpoints.failpoint_override("fail.sched.worker", "raise:1"):
            with pytest.raises(failpoints.FailpointError):
                sched.run(fn=lambda: 1)
            assert sched.run(fn=lambda: 2) == 2  # same worker, alive
        assert sched.snapshot()["worker_failures"] == 1
    finally:
        sched.close(timeout=2.0)


def test_exactly_once_under_worker_chaos():
    """Admitted requests each complete exactly once — success or typed
    error — under injected worker crashes."""
    sched = QueryScheduler(SchedConfig(
        max_queue=64, max_inflight=2, default_deadline_ms=None
    ))
    try:
        with failpoints.failpoint_override("fail.sched.worker", "raise:5"):
            reqs = [sched.submit(fn=lambda i=i: i) for i in range(20)]
            ok, failed = 0, 0
            for i, r in enumerate(reqs):
                try:
                    assert sched.wait(r) == i
                    ok += 1
                except failpoints.FailpointError:
                    failed += 1
            assert ok + failed == 20
            assert failed >= 1 and ok >= 1
    finally:
        sched.close(timeout=2.0)


def test_retry_after_computed_and_jittered():
    from geomesa_tpu.sched import RejectedError

    block = threading.Event()
    sched = QueryScheduler(SchedConfig(
        max_queue=1, max_inflight=1, default_deadline_ms=None,
        retry_after_s=2.0,
    ))
    try:
        # a few completions seed the service-time EWMA
        for _ in range(3):
            sched.run(fn=lambda: time.sleep(0.01))
        held = sched.submit(fn=lambda: block.wait(5))
        time.sleep(0.05)  # claimed; the single queue slot is free
        queued = sched.submit(fn=lambda: None)
        values = []
        for _ in range(8):
            with pytest.raises(RejectedError) as ei:
                sched.submit(fn=lambda: None)
            values.append(ei.value.retry_after_s)
        assert all(0.05 <= v <= 30.0 for v in values)
        # jitter: a fleet must not all get the same comeback time
        assert len({round(v, 6) for v in values}) > 1
        assert sched.snapshot()["retry_after_estimate_s"] > 0
        block.set()
        sched.wait(held)
        sched.wait(queued)
    finally:
        block.set()
        sched.close(timeout=2.0)


# -- staging-OOM recovery ---------------------------------------------------


def test_stage_oom_halves_and_retries_with_parity():
    ds = _mem_store(n=512)
    cql = "BBOX(geom, -10, -10, 10, 10)"
    expect = sorted(int(f) for f in ds.query("gdelt", cql).batch.fids)
    o0 = metrics.resilience_oom_recoveries.value()
    with failpoints.failpoint_override("fail.stage.oom", "raise:1"):
        got = sorted(int(f) for f in ds.query("gdelt", cql).batch.fids)
    assert got == expect
    assert metrics.resilience_oom_recoveries.value() - o0 >= 1


def test_device_launch_failure_degrades_to_host_mask():
    ds = _mem_store(n=256)
    cql = "BBOX(geom, -10, -10, 10, 10)"
    expect = sorted(int(f) for f in ds.query("gdelt", cql).batch.fids)
    with failpoints.failpoint_override("fail.device.launch", "raise"), \
            resilience.collect_degraded() as reasons:
        got = sorted(int(f) for f in ds.query("gdelt", cql).batch.fids)
    assert got == expect  # host mask is the exact same predicate
    assert "device-launch-failed" in reasons
    # strict mode: the same fault propagates
    with failpoints.failpoint_override("fail.device.launch", "raise"), \
            prop_override("resilience.degrade", False):
        with pytest.raises(failpoints.FailpointError):
            ds.query("gdelt", cql)


def test_streamed_scan_degrade_reason_matches_fault_domain():
    """The streamed scan's degradation rung must stamp the reason of
    the DOMAIN that failed: a corrupt partition or exhausted disk
    retries labeled ``device-launch-failed`` would send the operator
    to the accelerator for a disk fault (and vice versa)."""
    from geomesa_tpu.store.fs import PartitionCorruptError
    from geomesa_tpu.store.oocscan import StreamedDeviceScan

    cases = [
        (failpoints.FailpointError("x", name="fail.device.launch"),
         "device-launch-failed"),
        (failpoints.FailpointError("x", name="fail.stage.oom"),
         "device-oom"),
        (MemoryError("staging"), "device-oom"),
        (OSError("disk gave up"), "partition-unavailable"),
        (failpoints.FailpointError("x", name="fail.read.io"),
         "partition-unavailable"),
        (PartitionCorruptError("pid 3"), "partition-unavailable"),
        (resilience.PartitionUnavailableError("t", 3, "retries exhausted"),
         "partition-unavailable"),
    ]
    for exc, want in cases:
        with resilience.collect_degraded() as reasons:
            StreamedDeviceScan._degrade_or_raise(exc)
        assert reasons == [want], (type(exc).__name__, reasons, want)


# -- prefetch backoff cap / slow-read injection -----------------------------


def test_prefetch_backoff_cumulative_cap_bounds_wall_clock():
    from geomesa_tpu.store.prefetch import prefetch_map

    def always_fails(i):
        raise OSError("flapping")

    with prop_override("io.retries", 50), \
            prop_override("io.backoff.ms", 20.0), \
            prop_override("io.backoff.cap.ms", 60.0):
        t0 = time.monotonic()
        with pytest.raises(OSError):
            list(prefetch_map(always_fails, [1], config=0))
        elapsed = time.monotonic() - t0
    # 50 un-capped doubling retries from 20ms would sleep for days;
    # the cumulative cap bounds it to ~60ms of sleep
    assert elapsed < 2.0


def test_slow_read_failpoint_injects_latency_not_errors(tmp_path):
    ds = _fs_store(tmp_path / "s")
    expect = sorted(int(f) for f in ds.query("t").batch.fids)
    with failpoints.failpoint_override("fail.read.slow", "sleep:20"):
        got = sorted(int(f) for f in ds.query("t").batch.fids)
    assert got == expect


# -- partition-domain degradation ------------------------------------------


def _corrupt_file(path):
    with open(path, "r+b") as fh:
        fh.seek(20)
        fh.write(b"\xde\xad\xbe\xef")


def test_partition_breaker_short_circuits_repeat_failures(tmp_path):
    ds = _fs_store(tmp_path / "s")
    st = ds._types["t"]
    assert len(st.partitions) >= 2
    victim = st.partitions[0]
    all_fids = sorted(int(f) for f in ds.query("t").batch.fids)
    victim_fids = {int(f) for f in ds._read_partition("t", victim).fids}
    _corrupt_file(ds._part_path("t", victim))
    from geomesa_tpu.store.fs import FileSystemDataStore

    with prop_override("store.verify", "always"), \
            prop_override("resilience.breaker.failures", 1), \
            prop_override("resilience.breaker.cooldown.s", 30.0):
        fresh = FileSystemDataStore(str(tmp_path / "s"), partition_size=128)
        expect = sorted(set(all_fids) - victim_fids)
        with resilience.collect_degraded() as r1:
            got1 = sorted(int(f) for f in fresh.query("t").batch.fids)
        assert got1 == expect and "partition-unavailable" in r1
        # the victim's breaker opened on the first failure: the second
        # query degrades WITHOUT touching the file again
        br = resilience.partition_breaker(f"{fresh.root}:t", victim.pid)
        assert br.state == "open"
        c0 = metrics.store_checksum_failures.value()
        with resilience.collect_degraded() as r2:
            got2 = sorted(int(f) for f in fresh.query("t").batch.fids)
        assert got2 == expect and "partition-unavailable" in r2
        assert metrics.store_checksum_failures.value() == c0  # no re-read


def test_query_without_collector_raises_instead_of_silent_partial(tmp_path):
    """Outside a serving request there is no X-Degraded header or audit
    event to stamp: a library/CLI caller of store.query() must get the
    typed partition-scoped error, never a silently-partial batch."""
    ds = _fs_store(tmp_path / "s")
    st = ds._types["t"]
    victim = st.partitions[0]
    _corrupt_file(ds._part_path("t", victim))
    from geomesa_tpu.store.fs import FileSystemDataStore

    with prop_override("store.verify", "always"):
        fresh = FileSystemDataStore(str(tmp_path / "s"), partition_size=128)
        assert resilience.capture_degraded() is None
        with pytest.raises(resilience.PartitionUnavailableError) as ei:
            fresh.query("t")
        assert ei.value.pid == victim.pid


def test_query_partitions_surfaces_partition_scoped_error(tmp_path):
    ds = _fs_store(tmp_path / "s")
    st = ds._types["t"]
    victim = st.partitions[-1]
    _corrupt_file(ds._part_path("t", victim))
    from geomesa_tpu.store.fs import FileSystemDataStore

    with prop_override("store.verify", "always"):
        fresh = FileSystemDataStore(str(tmp_path / "s"), partition_size=128)
        # bulk/export consumers get a TYPED error naming the partition,
        # never a silent partial result
        with pytest.raises(resilience.PartitionUnavailableError) as ei:
            for _ in fresh.query_partitions("t"):
                pass
        assert ei.value.pid == victim.pid


# -- recovery sweep racing live serving (satellite) -------------------------


def test_recover_races_live_queries_never_half_published(tmp_path):
    """A recover() sweep racing in-flight query/query_partitions must
    only ever expose FULLY published generations: every successful
    observation equals the row set of some completed flush (a prefix of
    the writes), never a mix. Runs under the suite-wide lockcheck."""
    ds = _fs_store(tmp_path / "s", n=200)
    base = {int(f) for f in ds.query("t").batch.fids}
    rounds = 4
    batch_n = 60
    # every legal observation, known A PRIORI (fids are deterministic):
    # the base set plus a prefix of the flushed batches — a reader must
    # never see anything else, no matter how the sweep interleaves
    valid = [
        base | set(range(10_000, 10_000 + k * batch_n))
        for k in range(rounds + 1)
    ]
    stop = threading.Event()
    errors: list = []
    observations: list = []
    obs_lock = threading.Lock()

    def writer():
        try:
            fid0 = 10_000
            rng = np.random.default_rng(9)
            for i in range(rounds):
                n = batch_n
                ds.write("t", {
                    "val": rng.integers(0, 100, n),
                    "dtg": rng.integers(0, 10**9, n),
                    "geom": np.stack([
                        rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)
                    ], axis=1),
                }, fids=np.arange(fid0, fid0 + n))
                fid0 += n
                ds.flush("t")
                ds.recover("t")
                time.sleep(0.01)  # give the readers scan windows
        except Exception as e:  # pragma: no cover - fails the test below
            errors.append(e)
        finally:
            stop.set()

    def reader(use_partitions: bool):
        while True:
            done = stop.is_set()  # observe at least once after the end
            try:
                if use_partitions:
                    got: set = set()
                    for b in ds.query_partitions("t"):
                        got |= {int(f) for f in b.fids}
                else:
                    got = {int(f) for f in ds.query("t").batch.fids}
            except (FileNotFoundError,
                    resilience.PartitionUnavailableError):
                if done:
                    break
                continue  # a GC'd stale generation mid-iteration: retry
            with obs_lock:
                observations.append(got)
            if done:
                break

    threads = [
        threading.Thread(target=writer),
        threading.Thread(target=reader, args=(False,)),
        threading.Thread(target=reader, args=(True,)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert observations
    for got in observations:
        assert got in valid, (
            f"observed a row set matching NO published generation "
            f"(sizes: got={len(got)}, valid={[len(v) for v in valid]})"
        )
    # the final state is the fully written one
    assert {int(f) for f in ds.query("t").batch.fids} == valid[-1]


# -- server end-to-end: ladder, headers, health, drain, audit ---------------


@pytest.fixture()
def resident_server(tmp_path):
    from geomesa_tpu.server import serve_background

    ds = _fs_store(tmp_path / "srv", n=400, audit=True)
    server, _ = serve_background(
        ds, resident=True,
        sched=SchedConfig(max_queue=32, max_inflight=1,
                          default_deadline_ms=None),
    )
    host, port = server.server_address[:2]
    yield f"http://{host}:{port}", ds, server
    server.shutdown()
    server.scheduler.shutdown(timeout=2.0)


def test_server_device_failure_degrades_breaker_recovers(resident_server):
    url, ds, server = resident_server
    cql = quote("BBOX(geom, -90, -45, 90, 45)")
    target = f"{url}/count/t?cql={cql}"
    status, hdrs, body = _get(target)  # warm: stage + count
    expect = json.loads(body)["count"]
    assert status == 200 and "X-Degraded" not in hdrs
    with prop_override("resilience.retries", 0), \
            prop_override("resilience.breaker.failures", 1), \
            prop_override("resilience.breaker.cooldown.s", 0.2):
        with failpoints.failpoint_override("fail.device.launch", "raise"):
            status, hdrs, body = _get(target)
            assert status == 200
            assert json.loads(body)["count"] == expect
            assert "device-launch-failed" in hdrs.get("X-Degraded", "")
            assert hdrs.get("X-Request-Id")
            # breaker open now: the next request skips the device rung
            status, hdrs, body = _get(target)
            assert json.loads(body)["count"] == expect
            assert "device-breaker-open" in hdrs.get("X-Degraded", "")
        # fault cleared + cooldown over: the half-open probe recovers
        time.sleep(0.25)
        status, hdrs, body = _get(target)
        assert status == 200 and json.loads(body)["count"] == expect
        assert "X-Degraded" not in hdrs
        assert resilience.device_breaker().state == "closed"
    # degraded answers were audited with their reasons
    ds.audit_writer.flush()
    events = ds.audit_writer.read_events()
    assert any("device-launch-failed" in e.degraded for e in events)


def test_server_features_degrade_parity(resident_server):
    url, ds, server = resident_server
    cql = quote("BBOX(geom, -90, -45, 90, 45)")
    target = f"{url}/features/t?cql={cql}"
    _, _, body = _get(target)
    expect = {
        f["id"] for f in json.loads(body)["features"]
    }
    with prop_override("resilience.retries", 0), \
            failpoints.failpoint_override("fail.device.launch", "raise"):
        status, hdrs, body = _get(target)
    assert status == 200
    got = {f["id"] for f in json.loads(body)["features"]}
    assert got == expect
    assert "device-launch-failed" in hdrs.get("X-Degraded", "")


def test_server_healthz_readyz_and_draining(resident_server):
    url, ds, server = resident_server
    status, _, body = _get(f"{url}/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"
    status, _, body = _get(f"{url}/readyz")
    doc = json.loads(body)
    assert status == 200 and doc["ready"] and "breakers" in doc
    assert "device" in doc["breakers"]
    # an open breaker shows as a degraded domain; still READY (200)
    for _ in range(resilience.device_breaker().failures):
        resilience.device_breaker().record_failure()
    status, _, body = _get(f"{url}/readyz")
    doc = json.loads(body)
    assert status == 200 and "device" in doc["degraded_domains"]
    resilience.device_breaker().record_success()
    # draining flips readiness + admission; liveness and monitoring
    # stay up (failing /healthz would get the instance KILLED mid-drain
    # instead of de-routed — readiness is the traffic-removal signal)
    server.draining.set()
    try:
        status, _, body = _get(f"{url}/healthz")
        assert status == 200
        assert json.loads(body)["status"] == "draining"
        e = _get_err(f"{url}/readyz")
        assert e is not None and e.code == 503
        assert json.loads(e.read())["draining"] is True
        e = _get_err(f"{url}/count/t")
        assert e is not None and e.code == 503
        assert e.headers.get("Retry-After")
        status, _, _ = _get(f"{url}/metrics")  # scrapes keep working
        assert status == 200
    finally:
        server.draining.clear()
    status, _, _ = _get(f"{url}/count/t")
    assert status == 200


def test_server_error_responses_carry_request_id(resident_server):
    url, _, _ = resident_server
    e = _get_err(f"{url}/features/nosuchtype")
    assert e is not None and e.code == 404
    assert e.headers.get("X-Request-Id")
    # an inbound id echoes back even on errors
    req = urllib.request.Request(
        f"{url}/features/nosuchtype",
        headers={"X-Request-Id": "client-id-123"},
    )
    try:
        urllib.request.urlopen(req, timeout=10)
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e2:
        assert e2.headers.get("X-Request-Id") == "client-id-123"


def test_server_shed_and_expired_requests_audited(tmp_path):
    from geomesa_tpu.server import serve_background

    ds = _fs_store(tmp_path / "srv2", n=200, audit=True)
    server, _ = serve_background(
        ds, resident=True,
        sched=SchedConfig(max_queue=1, max_inflight=1,
                          default_deadline_ms=None, fusion_window_ms=0.0),
    )
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    try:
        _get(f"{url}/count/t")  # warm/stage
        # wedge the single worker directly, so HTTP requests pile into
        # the 1-slot queue: the first queued expires its deadline (504),
        # the rest are shed (429)
        block = threading.Event()
        held = server.scheduler.submit(fn=lambda: block.wait(10))
        time.sleep(0.05)  # claimed: the queue slot is free
        codes: list = []
        lock = threading.Lock()

        def fire(path):
            e = _get_err(f"{url}{path}")
            with lock:
                codes.append(e.code if e else 200)

        t504 = threading.Thread(
            target=fire, args=("/count/t?deadlineMs=60&tenant=dl",)
        )
        t504.start()
        time.sleep(0.02)  # let it take the queue slot
        t429s = [
            threading.Thread(
                target=fire, args=(f"/count/t?tenant=w{i}",)
            )
            for i in range(4)
        ]
        for t in t429s:
            t.start()
        for t in [t504] + t429s:
            t.join(timeout=30)
        block.set()
        server.scheduler.wait(held)
        assert codes and all(c in (200, 429, 504) for c in codes)
        assert 429 in codes or 504 in codes
    finally:
        server.shutdown()
        server.scheduler.shutdown(timeout=2.0)
    ds.audit_writer.flush()
    events = ds.audit_writer.read_events()
    outcomes = {e.outcome for e in events}
    if 429 in codes:
        assert "shed" in outcomes
    if 504 in codes:
        assert "deadline-expired" in outcomes
    # shed/expired audit events carry a trace id for correlation
    assert all(
        e.trace_id for e in events if e.outcome in ("shed",
                                                    "deadline-expired")
    )


def test_server_resident_staging_failure_degrades_to_store(tmp_path):
    """A resident cache that cannot stage (cache domain) falls to the
    store path: correct answers, stamped, cache breaker opens."""
    from geomesa_tpu.server import serve_background

    ds = _fs_store(tmp_path / "srv3", n=200)
    server, _ = serve_background(ds, resident=True)
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    expect = len(ds.query("t").batch)
    try:
        import geomesa_tpu.server as srv

        handler = server.RequestHandlerClass

        def boom(self, type_name):
            raise RuntimeError("RESOURCE_EXHAUSTED: staging OOM")

        orig = srv._Handler._build_locked
        handler._build_locked = boom
        try:
            with prop_override("resilience.breaker.failures", 1), \
                    prop_override("resilience.breaker.cooldown.s", 30.0):
                status, hdrs, body = _get(f"{url}/count/t")
                assert status == 200
                assert json.loads(body)["count"] == expect
                assert "resident-unavailable" in hdrs.get("X-Degraded", "")
                # breaker open: next request skips the staging attempt
                status, hdrs, body = _get(f"{url}/count/t")
                assert json.loads(body)["count"] == expect
                assert "cache-breaker-open" in hdrs.get("X-Degraded", "")
        finally:
            handler._build_locked = orig
    finally:
        server.shutdown()


def test_brownout_gate_requires_aggregate_shape(tmp_path):
    """Brownout may only flip to the pre-aggregate rung for filters the
    chunk stats can actually answer (bbox+time conjunctions): anything
    else would FULL-row-scan on the handler thread, outside scheduler
    admission, amplifying the very overload brownout relieves."""
    from types import SimpleNamespace

    from geomesa_tpu.server import _Handler

    ds = _fs_store(tmp_path / "gate", n=200)
    fake = SimpleNamespace(store=ds)
    ok = _Handler._agg_shaped
    assert ok(fake, "t", "INCLUDE")
    assert ok(fake, "t", "BBOX(geom, -10, 35, 30, 60)")
    assert ok(fake, "t", (
        "BBOX(geom, -10, 35, 30, 60) AND "
        "dtg DURING 1970-01-01T00:00:00Z/1970-01-02T00:00:00Z"
    ))
    # attribute predicates row-scan inside store.count/density: not
    # brownout-eligible (they take the normal metered path instead)
    assert not ok(fake, "t", "val > 10")
    assert not ok(fake, "t", "BBOX(geom, -10, 35, 30, 60) OR val = 1")
    assert not ok(fake, "nosuch", "INCLUDE")  # unknown type: never eligible
    assert not ok(fake, "t", "NOT VALID CQL ((")
    # a store WITHOUT chunk statistics (v1/legacy/memory) has no
    # pre-aggregates: the 'brownout' answer would quietly row-scan
    assert not ok(SimpleNamespace(store=object()), "t", "INCLUDE")
    nostats = SimpleNamespace(store=SimpleNamespace(
        has_chunk_stats=lambda t: False, get_schema=ds.get_schema
    ))
    assert not ok(nostats, "t", "INCLUDE")


def test_server_brownout_serves_pushdown_density(tmp_path):
    """Scheduler saturation flips aggregate answers to the chunk
    pre-aggregates (PR 6): mass stays within the pushdown parity
    bounds, the response is stamped, and nothing queues behind the
    saturated device lane."""
    from geomesa_tpu.process import density as density_proc
    from geomesa_tpu.geom import Envelope
    from geomesa_tpu.server import serve_background

    ds = _fs_store(tmp_path / "srv4", n=400)
    server, _ = serve_background(
        ds, resident=True,
        sched=SchedConfig(max_queue=8, max_inflight=1,
                          default_deadline_ms=None),
    )
    host, port = server.server_address[:2]
    url = f"http://{host}:{port}"
    bbox = "-180,-90,180,90"
    target = f"{url}/density/t?bbox={bbox}&width=64&height=32"
    try:
        _get(f"{url}/count/t")  # stage
        exact = density_proc(
            ds, "t", "INCLUDE", Envelope(-180, -90, 180, 90), 64, 32
        )
        block = threading.Event()
        # saturate: wedge the worker and sit 2 requests in the queue
        held = [
            server.scheduler.submit(fn=lambda: block.wait(10))
            for _ in range(3)
        ]
        try:
            with prop_override("resilience.brownout.queue.frac", 0.1):
                status, hdrs, body = _get(target)
        finally:
            block.set()
            for h in held:
                server.scheduler.wait(h)
        assert status == 200
        assert "brownout-pushdown" in hdrs.get("X-Degraded", "")
        doc = json.loads(body)
        grid = np.asarray(doc["counts"], dtype=float)
        # PR 6 parity bound: total mass is exact
        assert np.isclose(grid.sum(), float(np.asarray(exact).sum()))
        # healthy again after the queue drains: exact resident answers
        status, hdrs, _ = _get(target)
        assert status == 200 and "X-Degraded" not in hdrs
    finally:
        server.shutdown()
        server.scheduler.shutdown(timeout=2.0)
