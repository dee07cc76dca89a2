"""Workaround for a pyarrow native-init thread hazard.

Observed in this environment (pyarrow + glibc build): if pyarrow is
FIRST imported on a non-main thread (e.g. an HTTP handler serving an
Arrow response, or an ingest worker), its native initialization is
corrupted and a LATER parquet read from the main thread segfaults inside
``read_table``, as does a later parquet write on any other thread
(inside ``ParquetWriter``: a store flush after a prefetched merge read).
Importing pyarrow from the spawning thread before any worker threads
start avoids it entirely.

Every component that spawns threads which may touch Arrow/Parquet calls
``preload_pyarrow()`` first (server, jobs, partitioned-log consumers,
parallel frame scans, the store's read-ahead workers and flush
writers). Importing ``geomesa_tpu`` itself stays side-effect free —
the preload happens at thread-pool construction, not package import.
"""

from __future__ import annotations


def preload_pyarrow() -> None:
    """Import pyarrow (and its parquet module) on the CALLING thread.
    Idempotent and cheap after the first call; a missing pyarrow is the
    caller's problem later, not here."""
    try:
        import pyarrow  # noqa: F401
        import pyarrow.parquet  # noqa: F401
    except ImportError:  # pragma: no cover - pyarrow is baked in
        pass
