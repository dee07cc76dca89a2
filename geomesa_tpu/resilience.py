"""Failure-domain isolation and graceful degradation for the serving path.

Ref role: production geo-serving survives partial failure by degrading,
not dying — GeoMesa's layered fallbacks (loose -> exact, stats -> scan)
and the strategy switching "Adaptive Geospatial Joins for Modern
Hardware" motivates [UNVERIFIED - empty reference mount]. PRs 1-6 built
the layers (sched admission, prefetch pipeline, crash-consistent store,
tracing, chunk pre-aggregates); this module threads ONE fault classification
through all of them so a failed device launch, a flaky disk or a
saturated queue turns into a retried, degraded or typed answer instead
of an unhandled 500.

Three pieces:

- **Fault classification.** :func:`classify` maps any exception on the serving
  path to ``RETRYABLE`` (transient — I/O hiccups, injected
  ``FailpointError``, non-OOM device runtime errors: retry with jittered
  backoff), ``DEGRADABLE`` (the work is lost but a cheaper rung can still
  answer — device OOM, a stuck launch, a corrupt/unreachable partition)
  or ``FATAL`` (bad requests, programming errors, and the typed
  flow-control signals 429/504 which must reach the client untouched).

- **Per-domain circuit breakers.** :class:`CircuitBreaker` instances for
  the ``device`` (launch failures), ``cache`` (resident staging) and
  ``partition`` (per-partition reads, keyed) domains: ``closed`` until
  ``resilience.breaker.failures`` consecutive failures, then ``open``
  (callers skip the domain and take the degradation rung immediately —
  no queueing behind a dead device) for ``resilience.breaker.cooldown.s``,
  then ``half-open`` — ONE probe request is let through; success closes
  the breaker, failure re-opens it.

- **Degradation accounting.** Any layer that answers below the requested
  rung calls :func:`note_degraded` with a bounded reason enum; the server
  installs a collector per request (:func:`collect_degraded`) and stamps
  the reasons into the ``X-Degraded`` response header and the audit
  event. The collector crosses the scheduler's worker threads explicitly
  (:func:`capture_degraded` / :func:`attach_degraded`), exactly like
  tracing contexts.

The ladder itself lives where the knowledge lives: the server falls
resident -> store path when the device or cache domain is unhealthy,
the planner-facing store paths fall exact -> chunk-pushdown under
brownout (:func:`brownout` consults scheduler saturation), and the FS
store serves partial results (stamped degraded) around an unreachable
partition. Everything is gated by ``resilience.enabled`` /
``resilience.degrade`` and observable via the ``geomesa_resilience_*``
metrics and ``/readyz``.
"""

from __future__ import annotations

import random
import time
from contextlib import contextmanager

import contextvars

from geomesa_tpu.locking import checked_lock

__all__ = [
    "RETRYABLE",
    "DEGRADABLE",
    "FATAL",
    "CircuitBreaker",
    "LaunchStuckError",
    "PartitionUnavailableError",
    "attach_degraded",
    "breaker",
    "brownout",
    "capture_degraded",
    "classify",
    "collect_degraded",
    "current_degraded",
    "degrade_allowed",
    "device_breaker",
    "cache_breaker",
    "enabled",
    "is_compile_refusal",
    "is_oom",
    "note_degraded",
    "partition_breaker",
    "reset",
    "retry_call",
    "snapshot",
]

RETRYABLE = "retryable"
DEGRADABLE = "degradable"
FATAL = "fatal"

#: breaker-state gauge encoding (geomesa_resilience_breaker_state)
_STATE_CODE = {"closed": 0, "half-open": 1, "open": 2}


class LaunchStuckError(RuntimeError):
    """A device launch exceeded the watchdog budget: the request is
    failed (or degraded) so the submitter unblocks; the wedged worker
    thread is abandoned and replaced (device launches cannot be
    cancelled mid-flight)."""


class PartitionUnavailableError(RuntimeError):
    """Reads of ONE partition keep failing (retries exhausted or its
    breaker is open): a partition-scoped fault — the rest of the
    dataset keeps serving (degraded) or the query fails typed, never a
    pipeline teardown."""

    def __init__(self, type_name: str, pid, cause: str):
        super().__init__(
            f"dataset {type_name!r} partition {pid} is unavailable: {cause}"
        )
        self.type_name = type_name
        self.pid = pid


def enabled() -> bool:
    from geomesa_tpu.conf import sys_prop

    return bool(sys_prop("resilience.enabled"))


def degrade_allowed() -> bool:
    """Whether degraded (approximate/partial, stamped) answers may be
    served instead of failing — the ``resilience.degrade`` knob on top
    of the master ``resilience.enabled`` switch."""
    from geomesa_tpu.conf import sys_prop

    return enabled() and bool(sys_prop("resilience.degrade"))


# -- fault classification ---------------------------------------------------------


def is_compile_refusal(exc: BaseException) -> bool:
    """The compiler refused a kernel: Mosaic could not lower it, or its
    tiles do not fit the core's fast memory (VMEM). Such a refusal
    reads RESOURCE_EXHAUSTED like a device OOM, but it is a bug in the
    kernel's shape rules, not a load condition: the same kernel fails
    the same way at any load, so it is FATAL, never degraded away."""
    s = str(exc)
    return "memory space vmem" in s or "Mosaic failed to compile" in s


def is_oom(exc: BaseException) -> bool:
    """Device/host memory exhaustion — XLA surfaces HBM OOM as a
    RESOURCE_EXHAUSTED ``jax.errors.JaxRuntimeError``; staging can also
    hit host MemoryError. OOM is special-cased by the scan paths: halve
    the batch and retry before degrading. A compiler's VMEM refusal is
    not an OOM (:func:`is_compile_refusal`)."""
    if isinstance(exc, MemoryError):
        return True
    if is_compile_refusal(exc):
        return False
    s = str(exc)
    return (
        "RESOURCE_EXHAUSTED" in s
        or "Out of memory" in s
        or "out of memory" in s
    )


def classify(exc: BaseException) -> str:
    """Map a serving-path exception to its fault class (module
    docstring). Flow-control signals (429 RejectedError, 504
    DeadlineExpired) are FATAL here on purpose: they are the
    backpressure contract with the client and must never be retried or
    degraded away server-side."""
    from geomesa_tpu.sched.scheduler import DeadlineExpired, RejectedError

    if isinstance(exc, (RejectedError, DeadlineExpired)):
        return FATAL
    if isinstance(exc, (LaunchStuckError, PartitionUnavailableError)):
        return DEGRADABLE
    if is_compile_refusal(exc):
        return FATAL
    if is_oom(exc):
        return DEGRADABLE
    try:
        from geomesa_tpu.store.fs import PartitionCorruptError

        if isinstance(exc, PartitionCorruptError):
            return DEGRADABLE
    except ImportError:  # pragma: no cover - fs always importable here
        pass
    if isinstance(exc, FileNotFoundError):
        return FATAL  # a real state (GC'd generation) -- refresh, not retry
    if isinstance(exc, OSError):
        return RETRYABLE  # incl. FailpointError -- transient injection
    import jax

    if isinstance(exc, jax.errors.JaxRuntimeError):
        return RETRYABLE  # transient device runtime fault (non-OOM)
    if isinstance(exc, (ValueError, KeyError, TypeError)):
        return FATAL  # bad request / programming error: surface loudly
    return FATAL


# -- bounded jittered retry -------------------------------------------------

_rng = random.Random()


def backoff_sleeps(retries: int, base_ms: float, cap_ms: float):
    """Yield jittered exponential backoff sleeps (seconds): the k-th is
    ``base * 2^k`` scaled by a uniform [0.5, 1.5) jitter factor — a
    fleet of clients retrying the same fault decorrelates instead of
    re-spiking in lockstep. ``cap_ms > 0`` bounds the CUMULATIVE sleep:
    the generator stops once the budget is spent, so a flapping
    dependency can never stall a worker for unbounded wall-clock."""
    total = 0.0
    base = max(float(base_ms), 0.0)
    for attempt in range(max(int(retries), 0)):
        d = base * (1 << attempt) * (0.5 + _rng.random())
        # d == 0 (base 0: immediate retries) consumes no budget and must
        # not trip the exhaustion check — the retry COUNT still bounds it
        if cap_ms > 0 and d > 0:
            d = min(d, cap_ms - total)
            if d <= 0:
                return
        total += d
        yield d / 1e3


def retry_call(fn, domain: str = "device"):
    """Run ``fn()`` with bounded jittered-backoff retries of RETRYABLE
    faults (``resilience.retries`` x ``resilience.backoff.ms``, doubling,
    cumulative-capped by ``resilience.backoff.cap.ms``). Non-retryable
    faults — and the final retryable one — propagate to the caller,
    which classifies and degrades/fails."""
    from geomesa_tpu.conf import sys_prop

    if not enabled():
        return fn()
    sleeps = backoff_sleeps(
        int(sys_prop("resilience.retries")),
        float(sys_prop("resilience.backoff.ms")),
        float(sys_prop("resilience.backoff.cap.ms")),
    )
    while True:
        try:
            return fn()
        except Exception as e:
            if classify(e) != RETRYABLE:
                raise
            delay = next(sleeps, None)
            if delay is None:
                raise  # retry budget exhausted: the caller degrades
            from geomesa_tpu import ledger, metrics

            metrics.resilience_retries.inc(domain=domain)
            ledger.charge("retries", 1)
            time.sleep(delay)


# -- circuit breakers -------------------------------------------------------


class CircuitBreaker:
    """Per-domain failure isolation (see the module docstring's state
    machine). Thread-safe; durations are monotonic. ``domain`` is the
    BOUNDED metric label ("device" / "cache" / "partition"); keyed
    instances (per-partition) share their domain's label."""

    def __init__(
        self,
        name: str,
        domain: "str | None" = None,
        failures: "int | None" = None,
        cooldown_s: "float | None" = None,
    ):
        self.name = name
        self.domain = domain or name
        # None = resolve from the resilience.* properties PER USE, so a
        # runtime re-tune (or a test's prop_override) applies to
        # breakers that already exist
        self._failures = None if failures is None else int(failures)
        self._cooldown_s = None if cooldown_s is None else float(cooldown_s)
        self._lock = checked_lock(f"resilience.breaker.{domain or name}")
        self._state = "closed"
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_at = 0.0
        self.opens = 0  # lifetime open transitions (snapshot)

    @property
    def failures(self) -> int:
        if self._failures is not None:
            return self._failures
        from geomesa_tpu.conf import sys_prop

        return int(sys_prop("resilience.breaker.failures"))

    @property
    def cooldown_s(self) -> float:
        if self._cooldown_s is not None:
            return self._cooldown_s
        from geomesa_tpu.conf import sys_prop

        return float(sys_prop("resilience.breaker.cooldown.s"))

    # call under self._lock
    def _transition_locked(self, to: str) -> None:
        if to == self._state:
            return
        self._state = to
        if to == "open":
            self.opens += 1
            self._opened_at = time.monotonic()
        from geomesa_tpu import metrics

        metrics.resilience_breaker_transitions.inc(
            domain=self.domain, to=to
        )
        if self.domain in ("device", "cache"):
            # singleton domains publish their state directly; the keyed
            # partition domain publishes open-breaker counts instead
            metrics.resilience_breaker_state.set(
                _STATE_CODE[to], domain=self.domain
            )

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self) -> bool:
        """May a request use this domain right now? True while closed.
        While open: False until the cooldown elapses, then the breaker
        half-opens and exactly ONE caller gets True (the probe; a probe
        that never reports back frees the slot after another cooldown).
        The winner MUST call :meth:`record_success` or
        :meth:`record_failure` with its outcome."""
        if not enabled():
            return True
        with self._lock:
            if self._state == "closed":
                return True
            now = time.monotonic()
            if self._state == "open":
                if now - self._opened_at < self.cooldown_s:
                    return False
                self._transition_locked("half-open")
                self._probe_at = now
                return True
            # half-open: one probe in flight at a time
            if now - self._probe_at >= self.cooldown_s:
                self._probe_at = now  # probe lost: hand out another
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            if self._state != "closed":
                self._transition_locked("closed")

    def release_probe(self) -> None:
        """Give back a half-open probe slot WITHOUT an outcome: the
        probe was shed or deadline-expired before it could exercise the
        domain — flow control, not a health signal either way. The next
        :meth:`allow` hands out a fresh probe immediately instead of
        holding every caller on the degraded rung for another full
        cooldown. No-op unless half-open."""
        with self._lock:
            if self._state == "half-open":
                self._probe_at = time.monotonic() - self.cooldown_s

    def record_failure(self) -> None:
        opened = False
        with self._lock:
            self._consecutive += 1
            if self._state == "half-open":
                self._transition_locked("open")  # failed probe: re-open
                opened = True
            elif (
                self._state == "closed"
                and self._consecutive >= self.failures
            ):
                self._transition_locked("open")
                opened = True
        if opened:
            # postmortem snapshot OUTSIDE the breaker lock (the bundle
            # write is file I/O); rate limiting and the enabled gates
            # live in the recorder
            try:
                from geomesa_tpu import slo

                slo.on_breaker_open(self.domain)
            except Exception:  # pragma: no cover - must not break serving
                pass

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "state": self._state,
                "consecutive_failures": self._consecutive,
                "failure_threshold": self.failures,
                "cooldown_s": self.cooldown_s,
                "opens": self.opens,
            }


_breakers_lock = checked_lock("resilience.breakers")
_breakers: "dict[object, CircuitBreaker]" = {}
#: keyed (per-partition) breakers kept at most this many (hard bound);
#: closed ones are evicted first so an open breaker survives to its
#: half-open whenever anything closed remains to evict instead
_PARTITION_BREAKERS_MAX = 1024


def breaker(domain: str) -> CircuitBreaker:
    """The process-wide breaker for a singleton domain."""
    with _breakers_lock:
        b = _breakers.get(domain)
        if b is None:
            b = _breakers[domain] = CircuitBreaker(domain, domain=domain)
        return b


def device_breaker() -> CircuitBreaker:
    return breaker("device")


def cache_breaker() -> CircuitBreaker:
    return breaker("cache")


def wal_breaker() -> CircuitBreaker:
    """The breaker guarding write-ahead-log I/O (streaming ingest): an
    open breaker fails appends fast — acks must never be promised
    against a log that cannot take them."""
    return breaker("wal")


def partition_breaker(type_name: str, pid) -> CircuitBreaker:
    """The keyed breaker guarding reads of ONE partition. Bounded
    registry (HARD bound): when full, closed keyed breakers evict
    insertion-order first (open ones keep their cooldown state); with
    nothing closed — a store-wide outage — the oldest keyed breaker is
    evicted anyway. Losing an open breaker's state merely means that
    partition's next read probes and re-opens it; unbounded growth
    would be a memory leak sized by the outage."""
    key = ("partition", type_name, pid)
    with _breakers_lock:
        b = _breakers.get(key)
        if b is None:
            keyed = [
                k for k in _breakers if isinstance(k, tuple)
            ]
            if len(keyed) >= _PARTITION_BREAKERS_MAX:
                for k in keyed:
                    if _breakers[k]._state == "closed":
                        del _breakers[k]
                        break
                else:
                    del _breakers[keyed[0]]
            b = _breakers[key] = CircuitBreaker(
                f"partition:{type_name}:{pid}", domain="partition"
            )
        return b


def open_partition_breakers() -> int:
    with _breakers_lock:
        keyed = [
            b for k, b in _breakers.items() if isinstance(k, tuple)
        ]
    return sum(1 for b in keyed if b.state != "closed")


def snapshot() -> dict:
    """Breaker states for ``/readyz`` and ``/stats``-style docs. The
    singleton domains always appear (created closed on first ask) so a
    health probe sees the full domain list from the first scrape."""
    device_breaker()
    cache_breaker()
    wal_breaker()
    with _breakers_lock:
        singles = {
            k: b for k, b in _breakers.items() if isinstance(k, str)
        }
    doc = {k: b.snapshot() for k, b in sorted(singles.items())}
    doc["partition_open"] = open_partition_breakers()
    return doc


def reset() -> None:
    """Drop every breaker and its state (tests / bench isolation)."""
    from geomesa_tpu import metrics

    with _breakers_lock:
        _breakers.clear()
    metrics.resilience_breaker_state.set(0, domain="device")
    metrics.resilience_breaker_state.set(0, domain="cache")
    metrics.resilience_breaker_state.set(0, domain="wal")


# -- degradation accounting -------------------------------------------------

#: the per-request degradation collector; None outside a serving request
_collector: contextvars.ContextVar = contextvars.ContextVar(
    "geomesa_degraded", default=None
)

# observer seams for the runtime context checker (ctxcheck): armed only
# by its install(); None costs one comparison per attach/stamp
_attach_observer = None
_degraded_observer = None


def set_attach_observer(fn) -> None:
    global _attach_observer
    _attach_observer = fn


def set_degraded_observer(fn) -> None:
    global _degraded_observer
    _degraded_observer = fn

#: bounded reason enum (metric label discipline): every note_degraded
#: reason must come from here — an unlisted reason still collects but
#: is counted under "other" so label cardinality stays fixed
REASONS = frozenset(
    {
        "device-breaker-open",
        "device-launch-failed",
        "launch-stuck",
        "device-oom",
        "resident-unavailable",
        "cache-breaker-open",
        "partition-unavailable",
        "brownout-pushdown",
        "mesh-degraded",
        "ingest-degraded",
        "wal-replay-truncated",
        "replica-lag",
        "replica-degraded",
        "reprovision-installing",
    }
)


@contextmanager
def collect_degraded():
    """Install a fresh per-request collector; yields the (mutable,
    ordered, deduplicated) reason list the request accumulated."""
    reasons: list = []
    token = _collector.set(reasons)
    if _attach_observer is not None:
        _attach_observer(reasons, True)
    try:
        yield reasons
    finally:
        if _attach_observer is not None:
            _attach_observer(reasons, False)
        _collector.reset(token)


def note_degraded(reason: str) -> None:
    """Record that the current request was answered below its requested
    rung. Reasons are the bounded enum above; collection is a no-op
    outside a request, the metric always counts."""
    from geomesa_tpu import ledger, metrics

    metrics.resilience_degraded.inc(
        reason=reason if reason in REASONS else "other"
    )
    ledger.charge("degraded", 1)
    reasons = _collector.get()
    if _degraded_observer is not None:
        _degraded_observer(reasons, reason)
    if reasons is not None and reason not in reasons:
        reasons.append(reason)


def current_degraded() -> "list[str]":
    reasons = _collector.get()
    return list(reasons) if reasons else []


def capture_degraded():
    """The current collector, for EXPLICIT propagation onto worker
    threads (contextvars are per-thread — same discipline as
    tracing.capture/attach)."""
    return _collector.get()


@contextmanager
def attach_degraded(reasons):
    """Attach a captured collector around work executing on another
    thread (scheduler workers); None attaches nothing."""
    if reasons is None:
        yield
        return
    token = _collector.set(reasons)
    if _attach_observer is not None:
        _attach_observer(reasons, True)
    try:
        yield
    finally:
        if _attach_observer is not None:
            _attach_observer(reasons, False)
        _collector.reset(token)


def brownout(scheduler) -> bool:
    """Is the serving path under enough load that exact answers should
    yield to cheap pre-aggregated ones? True when the scheduler's
    admission queue is past ``resilience.brownout.queue.frac`` of its
    bound (the 429 cliff is right behind it)."""
    if scheduler is None or not degrade_allowed():
        return False
    from geomesa_tpu.conf import sys_prop

    frac = float(sys_prop("resilience.brownout.queue.frac"))
    if frac <= 0:
        return False
    snap = scheduler.queue_pressure()
    return snap[0] >= frac * max(snap[1], 1)
