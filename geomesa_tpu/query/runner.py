"""Query execution: partition prune -> device mask scan -> residual ->
local post-processing.

(ref: the scan side of AccumuloQueryPlan.BatchScanPlan + LocalQueryRunner
[UNVERIFIED - empty reference mount]. The reference fans ranges out to
tablet servers; here partitions are scanned with one jitted fused mask --
same shape = one XLA executable -- and non-device predicates run as an
exact numpy residual over surviving candidates only.)
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.index.api import BuiltIndex
from geomesa_tpu.ops.scan import stage_columns
from geomesa_tpu.query.plan import QueryPlan


@dataclass
class QueryResult:
    batch: FeatureBatch
    plan: QueryPlan
    scanned: int  # rows device-scanned after pruning
    total: int  # rows in the index

    def __len__(self) -> int:
        return len(self.batch)


MAX_RUN_PARTS = 8


def _contiguous_runs(parts) -> "list[tuple[int, int]]":
    """Merge adjacent surviving partitions into [start, stop) runs: the
    predicate is elementwise, so one staging + one kernel launch per run
    instead of per partition (a BatchScanner coalescing its ranges).
    Runs cap at MAX_RUN_PARTS partitions so the set of kernel shapes --
    and therefore jit recompiles across differently-pruned queries --
    stays small."""
    runs: list = []
    counts: list = []
    for p in parts:
        if runs and runs[-1][1] == p.start and counts[-1] < MAX_RUN_PARTS:
            runs[-1][1] = p.stop
            counts[-1] += 1
        else:
            runs.append([p.start, p.stop])
            counts.append(1)
    return [(a, b) for a, b in runs]


def run_query(built: BuiltIndex, plan: QueryPlan) -> QueryResult:
    from geomesa_tpu.tracing import span

    with span("query.scan") as sp:
        res = _run_query(built, plan)
        sp.set(scanned=res.scanned, hits=len(res))
        return res


#: OOM-recovery recursion bound: halving a run more times than this
#: means the device cannot hold even a sliver — give up loudly
_MAX_OOM_SPLITS = 8


def _scan_run(built, compiled, jitted, start: int, stop: int,
              depth: int = 0) -> np.ndarray:
    """One staged device launch over rows [start, stop) returning the
    fetched mask. Staging/HBM OOM (or the ``fail.stage.oom`` injection)
    recovers by HALVING the run and retrying each half — a transient
    memory squeeze (concurrent staging, fragmentation) costs extra
    launches, not the query; anything else propagates to the fault
    classification upstream."""
    from geomesa_tpu.failpoints import FailpointError, fail_point
    from geomesa_tpu.tracing import span

    try:
        import time as _time

        from geomesa_tpu import ledger

        t_stage = _time.perf_counter()
        with span("device.launch", rows=int(stop - start)), \
                ledger.compile_scope("store.scan"):
            fail_point("fail.device.launch")
            fail_point("fail.stage.oom")
            cols = stage_columns(
                built.batch, compiled.device_cols, start, stop
            )
            t_launch = _time.perf_counter()
            out = np.asarray(jitted(cols))  # lint: disable=GT004(the mask fetch IS the launch's intended sync point -- one per contiguous run, not per row)
        # store-path launches never pass through the scheduler's device
        # accounting: charge the requesting ledger here instead — the
        # host column staging charges as STAGE time, only the jitted
        # dispatch+fetch as device time (the cross-tenant device-time
        # sums must mean what they say)
        done = _time.perf_counter()
        ledger.charge("stage_seconds", t_launch - t_stage)
        ledger.charge("device_launches", 1)
        ledger.charge("device_seconds", done - t_launch)
        return out
    except Exception as e:
        from geomesa_tpu import resilience

        # fail.stage.oom's FailpointError SIMULATES an OOM at this site;
        # a real one surfaces as RESOURCE_EXHAUSTED / MemoryError. Match
        # on WHICH failpoint fired — fail.device.launch raises the same
        # type here and must take the launch-failure path, not halving
        oom = resilience.is_oom(e) or (
            isinstance(e, FailpointError)
            and getattr(e, "name", None) == "fail.stage.oom"
        )
        if oom and resilience.enabled() and stop - start > 1 \
                and depth < _MAX_OOM_SPLITS:
            from geomesa_tpu import metrics

            metrics.resilience_oom_recoveries.inc()
            mid = (start + stop) // 2
            return np.concatenate([
                _scan_run(built, compiled, jitted, start, mid, depth + 1),
                _scan_run(built, compiled, jitted, mid, stop, depth + 1),
            ])
        if (
            resilience.degrade_allowed()
            and resilience.classify(e) != resilience.FATAL
        ):
            # device rung unavailable (launch failed / stuck / OOM too
            # small to split): evaluate the SAME predicate on the host
            # rows — exact, just slower — so the store scan path keeps
            # answering with a dead accelerator. The residual re-applies
            # downstream; it is a subset of the full host predicate, so
            # the double application is idempotent.
            resilience.note_degraded(
                "device-oom" if oom else "device-launch-failed"
            )
            rows = built.batch.take(np.arange(start, stop))
            return np.asarray(compiled.host_mask(rows), dtype=bool)
        raise


def _run_query(built: BuiltIndex, plan: QueryPlan) -> QueryResult:
    import jax

    parts = built.prune(plan.ranges)
    compiled = plan.compiled
    n_scanned = sum(p.count for p in parts)

    hit_chunks: list[np.ndarray] = []
    if parts:
        use_device = bool(compiled.device_cols)
        jitted = None
        if use_device:
            _, jitted = compiled.jitted_scan()
        for start, stop in _contiguous_runs(parts):
            if use_device:
                # one span per kernel launch: stage + dispatch + the
                # mask fetch (np.asarray is the sync point)
                mask = _scan_run(built, compiled, jitted, start, stop)
            else:
                mask = np.ones(stop - start, dtype=bool)
            idx = np.nonzero(mask)[0]
            if len(idx) and not compiled.fully_on_device:
                cand = built.batch.take(idx + start)
                idx = idx[compiled.residual_mask(cand)]
            if len(idx):
                hit_chunks.append(idx + start)

    if hit_chunks:
        rows = np.concatenate(hit_chunks)
    else:
        rows = np.array([], dtype=np.int64)

    # internal per-partition scans (fs store) feed a merge that copies;
    # let a full-match scan skip the identity gather there. User-facing
    # results always copy (a caller mutating its result must never tear
    # the store's partition cache).
    internal = bool(plan.query.hints.get("internal_scan"))
    result = built.batch.take(rows, allow_alias=internal)
    result = _post_process(result, plan)
    return QueryResult(result, plan, n_scanned, built.n)


def _post_process(batch: FeatureBatch, plan: QueryPlan) -> FeatureBatch:
    """visibility / sort / max-features / projection (ref
    LocalQueryRunner + Accumulo cell-visibility filtering)."""
    q = plan.query
    # Accumulo semantics: a labeled feature is hidden unless the query's
    # auths satisfy it -- including when no auths were supplied at all.
    # Internal per-partition scans (fs store) defer this to the outer,
    # global post-process so the real auths are the ones applied.
    # raw_visibility is the resident-cache STAGING escape hatch: the
    # DeviceIndex stages every row plus a label-id plane and enforces
    # visibility itself per request (device auth-table gather); it must
    # never be set on a user-facing query.
    if not q.hints.get("internal_scan") and not q.hints.get("raw_visibility"):
        from geomesa_tpu.security import filter_by_visibility

        m = filter_by_visibility(batch, q.hints.get("auths", ()))
        if m is not None:
            batch = batch.take(np.nonzero(m)[0])
    if q.sort_by:
        order = np.argsort(batch.column(q.sort_by), kind="stable")
        if q.sort_desc:
            order = order[::-1]
        batch = batch.take(order)
    if q.max_features is not None and len(batch) > q.max_features:
        batch = batch.take(np.arange(q.max_features))
    if q.properties:
        from geomesa_tpu.features.sft import SimpleFeatureType

        attrs = tuple(
            batch.sft.descriptor(p) for p in q.properties
        )
        sub_sft = SimpleFeatureType(
            batch.sft.type_name, attrs, batch.sft.user_data
        )
        batch = FeatureBatch(
            sub_sft,
            batch.fids,
            {p: batch.columns[p] for p in q.properties},
        )
    return batch
