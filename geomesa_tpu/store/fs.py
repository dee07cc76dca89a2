"""Parquet/ORC filesystem DataStore.

The geomesa-fs analog (ref: geomesa-fs .../FileSystemDataStore,
storage/api/PartitionScheme, parquet/ParquetFileSystemStorage and
orc/OrcFileSystemStorage [UNVERIFIED - empty reference mount]): data lives
as sorted Parquet (or ORC) partition files plus a JSON manifest; queries
prune partitions by the manifest's key bounds (the partition-scheme prune +
parquet min/max pushdown, rolled together) and device-scan only surviving
files.

Layout under ``root/<type_name>/``:

- ``schema.json``   -- SFT spec + primary index + partition metadata
- ``schema.json.gen`` -- tiny staleness sidecar (the manifest generation)
- ``part-<gen>-NNNNN.parquet`` (or ``.orc``) -- sorted partition files,
  generation-scoped (legacy ``part-NNNNN.*`` names still read)

Durable state is exactly this directory (the reference's "source of truth
stays on the object store" elasticity model, SURVEY.md section 5): a store
can be reopened from disk alone, and device/host memory is a cache.

Crash consistency (write-new-then-publish, the immutable-file discipline
of spatial-Parquet lakes / chunked Zarr stores): every flush writes a
NEW generation of partition files next to the old one, fsyncs file
contents and directories, atomically publishes the manifest (itself
fsynced), and only then garbage-collects the previous generation — a
``kill -9`` at any instant leaves a store that reopens to exactly the
old or the new state. Interrupted-flush leftovers are reclaimed by the
recovery sweep at open (:meth:`FileSystemDataStore.recover`, the CLI
``fsck``). Each partition file carries a checksum + byte length in the
manifest, verified per the ``store.verify`` knob (``off``/``open``/
``always``); a corrupt file quarantines ONLY that partition
(:class:`PartitionCorruptError`) while the rest keep serving. The
``fail.flush.*``/``fail.read.*`` failpoints (:mod:`geomesa_tpu.failpoints`)
are evaluated at every step so the chaos suite can kill a flushing
process at each instant.
"""

from __future__ import annotations

import json
import os
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from geomesa_tpu.features.batch import FeatureBatch
from geomesa_tpu.features.sft import SimpleFeatureType
from geomesa_tpu.filter import ast
from geomesa_tpu.index.api import BuiltIndex, KeyRange, PartitionMeta
from geomesa_tpu.index.build import DEFAULT_PARTITION_SIZE, build_index
from geomesa_tpu.index.keyspaces import default_indices, keyspace_for
from geomesa_tpu.query.plan import (
    Query,
    QueryPlan,
    as_query,
    internal_query,
    plan_query,
)
from geomesa_tpu.query.runner import QueryResult, run_query


@dataclass
class _FsTypeState:
    sft: SimpleFeatureType
    primary: str
    partitions: "list[PartitionMeta]" = field(default_factory=list)
    pending: "list[FeatureBatch]" = field(default_factory=list)
    data_interval: "tuple[int, int] | None" = None
    cache: "dict[int, FeatureBatch]" = field(default_factory=dict)
    encoding: str = "parquet"
    scheme: "object | None" = None  # PartitionScheme, from SFT user data
    stats: "object | None" = None  # SeqStat rebuilt at flush, persisted
    generation: "str | None" = None  # manifest token last read/written
    #: generation token embedded in the partition FILE names
    #: (``part-<file_gen>-NNNNN.*``); None = legacy un-scoped names
    file_gen: "str | None" = None
    #: manifest format version (chunkstats.FORMAT_V1/V2): v2 partitions
    #: carry per-chunk statistics and parquet row groups aligned to the
    #: chunk boundaries. Lazily upgraded -- any rewrite (flush/compact/
    #: reindex/repartition) re-publishes at ``store.format.version``
    format_version: int = 1
    # legacy manifests only: a pre-generation-era flush failed AFTER
    # unlinking its files, so the rows exist only in that writer's
    # memory. Readers of such a manifest fail loudly instead of seeing
    # an empty-but-valid dataset. New flushes never set this (the old
    # generation stays published until the new one lands).
    dirty: bool = False
    # process-local (never persisted/refreshed): True only in the process
    # whose failed flush raised the quarantine -- the one holding the data
    # in `pending`. Only that process may flush (and thereby lift) it.
    quarantine_owner: bool = False
    #: process-local per-PARTITION quarantine: pid -> checksum error.
    #: Reads of a quarantined partition raise PartitionCorruptError;
    #: sibling partitions keep serving. Cleared when a new generation
    #: is read or published.
    quarantined: "dict[int, str]" = field(default_factory=dict)
    #: highest WAL sequence folded into the published generation (the
    #: streaming layer's recovery watermark, store/stream.py): replay
    #: at open skips records at or below it — they are already in the
    #: partition files. -1 = nothing streamed/compacted yet. Persisted
    #: ATOMICALLY with the manifest, so a crash between publish and
    #: WAL truncation re-applies nothing.
    wal_watermark: int = -1


class PartitionCorruptError(RuntimeError):
    """A partition file failed checksum verification (or was already
    quarantined by an earlier failure). Scoped to ONE partition: queries
    pruned away from it keep serving; queries touching it fail loudly
    instead of silently dropping rows."""


def _write_table(table, path: str, encoding: str) -> None:
    if encoding == "orc":
        import pyarrow.orc as orc

        orc.write_table(table, path)
    else:
        import pyarrow as pa
        import pyarrow.parquet as pq

        # dictionary-encode ONLY string-ish columns (fids, vis labels,
        # WKT): dictionary pages on float/int data cost ~2.7x the write
        # time for zero size win, and parquet column statistics duplicate
        # what the partition manifest already records (key ranges, bbox,
        # time range)
        dict_cols = [
            f.name
            for f in table.schema
            if pa.types.is_string(f.type)
            or pa.types.is_large_string(f.type)
            or pa.types.is_binary(f.type)
        ]
        pq.write_table(
            table, path,
            use_dictionary=dict_cols or False,
            write_statistics=False,
        )


def _read_table(path: str, encoding: str, row_groups=None):
    """Read a partition file; ``row_groups`` (parquet only) reads ONLY
    those row groups -- the chunk-selective pruned read. Callers pass it
    only for v2 files whose chunks align 1:1 with row groups
    (:meth:`FileSystemDataStore._row_groups_for`)."""
    if encoding == "orc":
        import pyarrow.orc as orc

        return orc.read_table(path)
    import pyarrow.parquet as pq

    if row_groups is None:
        return pq.read_table(path)
    return pq.ParquetFile(path).read_row_groups(list(row_groups))


def _encode_table(table, encoding: str, row_group_rows=None) -> bytes:
    """Arrow table -> parquet/orc bytes in memory: the durable write
    path checksums (and fsyncs) the exact bytes that land on disk.
    ``row_group_rows`` (parquet only) sizes row groups to the v2 chunk
    boundaries so chunk-pruned reads skip real file bytes."""
    import pyarrow as pa

    sink = pa.BufferOutputStream()
    if encoding == "orc":
        import pyarrow.orc as orc

        orc.write_table(table, sink)
    else:
        import pyarrow.parquet as pq

        # same dictionary policy as _write_table (see above)
        dict_cols = [
            f.name
            for f in table.schema
            if pa.types.is_string(f.type)
            or pa.types.is_large_string(f.type)
            or pa.types.is_binary(f.type)
        ]
        kwargs = {}
        if row_group_rows:
            kwargs["row_group_size"] = int(row_group_rows)
        pq.write_table(
            table, sink,
            use_dictionary=dict_cols or False,
            write_statistics=False,
            **kwargs,
        )
    return sink.getvalue().to_pybytes()


def _parse_table(data: bytes, encoding: str, row_groups=None):
    """Verified-read counterpart of :func:`_read_table`: parse a table
    from bytes already checksummed in memory (``row_groups`` as in
    :func:`_read_table` -- the whole file was read for the checksum, but
    only the surviving row groups pay the decompress/decode)."""
    import pyarrow as pa

    buf = pa.BufferReader(pa.py_buffer(data))
    if encoding == "orc":
        import pyarrow.orc as orc

        return orc.read_table(buf)
    import pyarrow.parquet as pq

    if row_groups is None:
        return pq.read_table(buf)
    return pq.ParquetFile(buf).read_row_groups(list(row_groups))


def _row_group_nbytes(data: bytes) -> "list[int]":
    """Per-row-group compressed byte sizes of encoded parquet bytes --
    recorded in the v2 manifest so chunk pruning can account the file
    bytes it skipped without opening the file."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    md = pq.ParquetFile(pa.BufferReader(pa.py_buffer(data))).metadata
    out = []
    for i in range(md.num_row_groups):
        rg = md.row_group(i)
        out.append(
            sum(rg.column(j).total_compressed_size for j in range(rg.num_columns))
        )
    return out


# resolved ONCE: a failed import is not cached by Python, and paying a
# sys.path scan per partition write/verified read would add up fast
try:
    from crc32c import crc32c as _crc32c  # optional accelerator
except ImportError:
    _crc32c = None


def checksum_bytes(data: bytes) -> "tuple[str, int]":
    """``(algo, value)`` content checksum. Prefers hardware crc32c when
    the optional module is present, zlib crc32 (always available)
    otherwise; the algo name persists in the manifest so verification
    works in an environment with a different preferred algo."""
    if _crc32c is not None:
        return "crc32c", int(_crc32c(data))
    import zlib

    return "crc32", int(zlib.crc32(data) & 0xFFFFFFFF)


def verify_bytes(data: bytes, checksum: dict) -> "str | None":
    """None when ``data`` matches the manifest checksum record, an
    error description otherwise. Unknown/unavailable algos fall back to
    the (always-checked) byte length rather than failing the read."""
    length = checksum.get("length")
    if length is not None and len(data) != int(length):
        return f"length {len(data)} != manifest {int(length)}"
    algo = checksum.get("algo")
    if algo == "crc32":
        import zlib

        got = int(zlib.crc32(data) & 0xFFFFFFFF)
    elif algo == "crc32c":
        if _crc32c is None:
            return None  # length already checked above
        got = int(_crc32c(data))
    else:
        return None
    want = int(checksum.get("value", -1))
    if got != want:
        return f"{algo} {got:#010x} != manifest {want:#010x}"
    return None


def _write_file(path: str, data: bytes, fsync: bool) -> None:
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        # os.write may land fewer bytes than asked (signals; Linux caps a
        # single write at ~2GB): loop, or a giant partition file would
        # silently truncate while its manifest checksum covers the whole
        view = memoryview(data)
        while view:
            view = view[os.write(fd, view):]
        if fsync:
            os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(d: str) -> None:
    """Durably record a directory's entries (new/renamed files). Best
    effort: some filesystems refuse directory fsync; the file-content
    fsyncs still stand."""
    try:
        fd = os.open(d, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _write_part_file(
    table, path: str, encoding: str, fsync: bool, chunk_rows=None
) -> "tuple[dict, list | None]":
    """Write one partition file durably — encode to bytes, checksum,
    single write (+fsync) — returning ``(checksum_record,
    chunk_nbytes)``. With ``chunk_rows`` set (v2 parquet), row groups
    align to the chunk boundaries and ``chunk_nbytes`` carries their
    compressed sizes for the manifest; None otherwise."""
    data = _encode_table(table, encoding, row_group_rows=chunk_rows)
    algo, value = checksum_bytes(data)
    chunk_nbytes = None
    if chunk_rows and encoding == "parquet":
        chunk_nbytes = _row_group_nbytes(data)
    _write_file(path, data, fsync)
    return {"algo": algo, "value": value, "length": len(data)}, chunk_nbytes


class _Sized:
    """Audit shim for pushdown-served aggregates: observe_query only
    needs ``len(result)`` (the hit count for the audit event)."""

    def __init__(self, n: int):
        self._n = int(n)

    def __len__(self) -> int:
        return self._n


class _PartFailure:
    """Sentinel a DEGRADABLE partition read returns instead of raising:
    the prefetch pipeline keeps flowing (an exception at item i would
    tear the whole scan down), and the CONSUMER decides — skip the
    partition and stamp the result degraded (``resilience.degrade``
    on), or surface the partition-scoped error."""

    __slots__ = ("p", "error")

    def __init__(self, p, error):
        self.p = p
        self.error = error


class _PresizedSink:
    """Streaming assembly of a FULL-scan result into buffers pre-sized
    from the manifest's row counts (the chunk-stats/manifest contract:
    recorded rows == file rows). The generic path collects every
    partition batch in a list and then concatenates — peak host memory
    is ~2x the dataset at exactly the moment the resident DeviceIndex
    stages it. This sink copies each batch into its slice as it arrives
    and drops it, so the peak is ONE dataset copy plus the in-flight
    prefetch chunks. Buffers grow (rare: manifest drift) and trim (a
    batch shorter than recorded) defensively, so the result is correct
    even when the pre-size hint was wrong."""

    def __init__(self, sft, total: int):
        self.sft = sft
        self.cap = int(total)
        self.filled = 0
        self._cols: "dict | None" = None
        self._fids = None

    def _alloc(self, like: np.ndarray, fill=None) -> np.ndarray:
        buf = np.empty((self.cap,) + like.shape[1:], dtype=like.dtype)
        if fill is not None:
            buf[: self.filled] = fill
        return buf

    def _grow(self, need: int) -> None:
        self.cap = max(self.cap * 2, need)
        for k, v in self._cols.items():
            nb = np.empty((self.cap,) + v.shape[1:], dtype=v.dtype)
            nb[: self.filled] = v[: self.filled]
            self._cols[k] = nb
        nf = np.empty(self.cap, dtype=self._fids.dtype)
        nf[: self.filled] = self._fids[: self.filled]
        self._fids = nf

    def add(self, batch: FeatureBatch) -> None:
        from geomesa_tpu.security import VIS_COLUMN

        n = len(batch)
        if n == 0:
            return
        if self._cols is None:
            self.cap = max(self.cap, n)
            self._cols = {
                k: self._alloc(v) for k, v in batch.columns.items()
            }
            self._fids = self._alloc(batch.fids)
        if self.filled + n > self.cap:
            self._grow(self.filled + n)
        a, b = self.filled, self.filled + n
        for k, buf in self._cols.items():
            v = batch.columns.get(k)
            if v is None:
                if k != VIS_COLUMN:
                    raise KeyError(f"column {k!r} missing from a partition")
                v = np.array([""] * n, dtype=object)
            if not np.can_cast(v.dtype, buf.dtype, casting="same_kind"):
                # preserve trailing dims (e.g. (n, 2) point columns):
                # a bare np.empty(0, dtype) template would allocate 1-D
                promoted = self._alloc(
                    np.empty(
                        (0,) + buf.shape[1:],
                        np.promote_types(buf.dtype, v.dtype),
                    )
                )
                promoted[:a] = buf[:a]
                self._cols[k] = buf = promoted
            buf[a:b] = v
        for k in batch.columns:
            if k not in self._cols:
                # a later partition introduces visibility labels: prior
                # rows are public ("") — same semantics as concat()
                self._cols[k] = self._alloc(batch.columns[k], fill="")
                self._cols[k][a:b] = batch.columns[k]
        if not np.can_cast(
            batch.fids.dtype, self._fids.dtype, casting="same_kind"
        ):
            nf = np.empty(
                self.cap,
                np.promote_types(self._fids.dtype, batch.fids.dtype),
            )
            nf[:a] = self._fids[:a]
            self._fids = nf
        self._fids[a:b] = batch.fids
        self.filled = b

    def finish(self) -> "FeatureBatch | None":
        if self._cols is None:
            return None
        n = self.filled
        return FeatureBatch(
            self.sft,
            self._fids[:n],
            {k: v[:n] for k, v in self._cols.items()},
        )


class FileSystemDataStore:
    def __init__(
        self,
        root: str,
        partition_size: int = DEFAULT_PARTITION_SIZE,
        audit: bool = False,
        encoding: str = "parquet",
        mesh=None,
        io=None,
    ):
        """``mesh``: an optional ``jax.sharding.Mesh`` — flushes then build
        their sorted indexes ON the device mesh (device key encode +
        all_to_all exchange sort, bit-identical to the host build; falls
        back to the host path for key spaces without a device encode).

        ``io``: host-I/O pipeline config for multi-partition reads
        (queries, flush merges, ``query_partitions`` — see
        store/prefetch.py): a PrefetchConfig, an int worker count, or
        None for the ``io.*`` system properties. 0 disables the pipeline
        (serial reads)."""
        if encoding not in ("parquet", "orc"):
            raise ValueError(f"unsupported encoding {encoding!r}")
        import threading

        from geomesa_tpu.locking import checked_rlock

        self.root = root
        self.partition_size = partition_size
        self.mesh = mesh
        self.io = io
        self.encoding = encoding
        self._types: dict[str, _FsTypeState] = {}
        os.makedirs(root, exist_ok=True)
        # inter-process coordination (DistributedLocking analog): one
        # flock sentinel per store root; exclusive for in-place rewrites
        # (flush/compact/reindex/repartition), shared for file reads so a
        # reader never observes a half-rewritten directory
        self._lock_path = os.path.join(root, ".lock")
        self._lock_tl = threading.local()
        # flock serializes PROCESSES; this RLock serializes THREADS of
        # this process (a ThreadingHTTPServer shares one store object,
        # and _refresh_from_disk mutates shared state in place).
        # blocking_ok: maintenance holds it across partition file I/O BY
        # DESIGN (the scan-consistency window); the lock-free worker
        # reads of PR 2 exist precisely because of that.
        self._mem_lock = checked_rlock("store.fs.mem", blocking_ok=True)
        self.audit_writer = None
        #: what the open-time recovery sweep reclaimed, per type — folded
        #: into the next explicit recover() so fsck reports the crash
        #: cleanup its own store open already performed
        self._open_recovery: dict = {}
        #: (type_name, snapshot_id) pins THIS process's snapshot streams
        #: hold: exempt from the on-disk pin TTL so a slow-but-live
        #: local stream is never torn by its own store's sweep
        self._active_pins: "set[tuple[str, str]]" = set()
        if audit:  # the <catalog>_queries table analog
            from geomesa_tpu.audit import FileAuditWriter

            self.audit_writer = FileAuditWriter(
                os.path.join(root, "_queries.jsonl")
            )
        for name in sorted(os.listdir(root)):
            meta_path = os.path.join(root, name, "schema.json")
            if os.path.exists(meta_path):
                self._load_type(name)
        self._recover_on_open()

    def _recover_on_open(self) -> None:
        """Crash recovery at open: under the exclusive lock (no flush can
        be mid-write), reclaim interrupted-flush leftovers and repair a
        lagging generation sidecar; ``store.verify=open`` additionally
        checksums every partition file, quarantining failures. A held
        lock elsewhere must not brick opening — the sweep is skipped
        with a warning and runs on the next open/fsck instead."""
        if not self._types:
            return
        import logging

        from geomesa_tpu.conf import sys_prop
        from geomesa_tpu.locking import LockTimeout

        verify_open = sys_prop("store.verify") == "open"
        for name in list(self._types):
            try:
                with self._exclusive():
                    self._refresh_from_disk(name)
                    self._open_recovery[name] = self._recover_locked(name)
                    if verify_open:
                        self._verify_type(name)
            except LockTimeout as e:
                logging.getLogger(__name__).warning(
                    "dataset %r: recovery sweep skipped at open (%s)",
                    name, e,
                )

    # -- inter-process locking ---------------------------------------------

    @contextmanager
    def _exclusive(self):
        """Exclusive store lock, re-entrant per thread (a locked rewrite
        reads existing files through _read_partition)."""
        from geomesa_tpu.locking import file_lock

        depth = getattr(self._lock_tl, "depth", 0)
        if depth > 0:
            self._lock_tl.depth = depth + 1
            try:
                yield
            finally:
                self._lock_tl.depth -= 1
            return
        with self._mem_lock, file_lock(self._lock_path):
            self._lock_tl.depth = 1
            try:
                yield
            finally:
                self._lock_tl.depth = 0

    @contextmanager
    def _shared(self):
        from geomesa_tpu.locking import file_lock

        if getattr(self._lock_tl, "depth", 0) > 0:
            yield  # already under this thread's exclusive lock
            return
        with self._mem_lock, file_lock(self._lock_path, shared=True):
            yield

    # -- schema / persistence ---------------------------------------------

    def _dir(self, type_name: str) -> str:
        return os.path.join(self.root, type_name)

    def _load_type(self, name: str) -> None:
        self._types[name] = self._read_state(name)

    def _read_state(self, name: str) -> "_FsTypeState":
        # shared lock: never read the manifest mid-rewrite (writers hold
        # the exclusive lock across the atomic os.replace of schema.json)
        with self._shared():
            with open(os.path.join(self._dir(name), "schema.json")) as fh:
                meta = json.load(fh)
        sft = SimpleFeatureType.create(name, meta["spec"])
        from geomesa_tpu.store.chunkstats import FORMAT_V1, chunkset_from_json

        parts = [
            PartitionMeta(
                pid=p["pid"],
                start=p["start"],
                stop=p["stop"],
                key_lo=tuple(p["key_lo"]),
                key_hi=tuple(p["key_hi"]),
                count=p["count"],
                bbox=tuple(p["bbox"]) if p.get("bbox") else None,
                time_range=tuple(p["time_range"]) if p.get("time_range") else None,
                leaf=p.get("leaf"),
                checksum=p.get("checksum"),
                chunks=self._load_chunks(chunkset_from_json, p.get("chunks")),
                gen=meta.get("file_gen"),
            )
            for p in meta["partitions"]
        ]
        return _FsTypeState(
            sft,
            meta["primary"],
            parts,
            data_interval=tuple(meta["data_interval"])
            if meta.get("data_interval")
            else None,
            encoding=meta.get("encoding", "parquet"),
            scheme=self._scheme_of(sft, strict=False),
            stats=self._load_stats(meta.get("stats")),
            generation=meta.get("generation"),
            file_gen=meta.get("file_gen"),
            format_version=int(meta.get("format", FORMAT_V1)),
            dirty=bool(meta.get("dirty", False)),
            wal_watermark=int(meta.get("wal_watermark", -1)),
        )

    @staticmethod
    def _load_chunks(parse, raw):
        if not raw:
            return None
        try:
            return parse(raw)
        except Exception:  # lint: disable=GT011(sidecar stats are advisory: corrupt chunk metadata degrades to a full scan, never a failed open)
            return None  # chunk stats are advisory; never block opening

    @staticmethod
    def _load_stats(raw):
        if not raw:
            return None
        from geomesa_tpu.stats.sketches import seq_from_json

        try:
            return seq_from_json(raw)
        except Exception:  # lint: disable=GT011(sidecar stats are advisory: corrupt sketches degrade estimates, never a failed open)
            return None  # stats are advisory; never block opening

    @staticmethod
    def _scheme_of(sft: SimpleFeatureType, strict: bool = True):
        from geomesa_tpu.store.partitions import USER_DATA_KEY, scheme_for

        spec = sft.user_data.get(USER_DATA_KEY)
        if not spec:
            return None
        try:
            scheme = scheme_for(str(spec))
            scheme.validate(sft)
        except ValueError:
            if strict:  # create_schema: fail fast, before any writes
                raise
            # loading persisted state: an invalid scheme must not brick
            # the whole catalog -- files stay readable via their recorded
            # leaf paths, only leaf pruning is lost
            import logging

            logging.getLogger(__name__).warning(
                "type %r: invalid partition scheme %r ignored on load",
                sft.type_name,
                spec,
            )
            return None
        return scheme

    def _save_meta(self, name: str) -> None:
        import uuid

        from geomesa_tpu.store.chunkstats import chunkset_to_json

        st = self._types[name]
        st.generation = uuid.uuid4().hex  # new manifest token
        meta = {
            "generation": st.generation,
            "file_gen": st.file_gen,
            "format": st.format_version,
            "dirty": st.dirty,
            "wal_watermark": st.wal_watermark,
            "spec": st.sft.spec,
            "primary": st.primary,
            "encoding": st.encoding,
            "data_interval": st.data_interval,
            "stats": st.stats.to_json() if st.stats is not None else None,
            "partitions": [
                {
                    "pid": p.pid,
                    "start": p.start,
                    "stop": p.stop,
                    "key_lo": list(p.key_lo),
                    "key_hi": list(p.key_hi),
                    "count": p.count,
                    "bbox": list(p.bbox) if p.bbox else None,
                    "time_range": list(p.time_range) if p.time_range else None,
                    "leaf": p.leaf,
                    "checksum": p.checksum,
                    "chunks": chunkset_to_json(p.chunks),
                }
                for p in st.partitions
            ],
        }
        self._publish_manifest(
            os.path.join(self._dir(name), "schema.json"),
            json.dumps(meta),
            st.generation,
        )

    @staticmethod
    def _publish_manifest(path: str, body: str, generation: str) -> None:
        """Atomically publish ``schema.json`` AND its ``.gen`` staleness
        sidecar, fsyncing file contents and the directory: a crash at
        any instant leaves either the old or the new manifest, never a
        truncated one. The sidecar derives FROM this manifest write (one
        source of truth); a crash between the two replaces leaves it
        lagging by exactly one generation, which the recovery sweep
        repairs from the manifest on the next open."""
        from geomesa_tpu.conf import sys_prop

        fsync = bool(sys_prop("store.fsync"))
        tmp = path + ".tmp"
        _write_file(tmp, body.encode("utf-8"), fsync)
        os.replace(tmp, path)
        # tiny sidecar: staleness checks read ONLY this, not the whole
        # manifest (which carries the full partition list)
        gen_tmp = path + ".gen.tmp"
        _write_file(gen_tmp, generation.encode("utf-8"), fsync)
        os.replace(gen_tmp, path + ".gen")
        if fsync:
            _fsync_dir(os.path.dirname(path))

    def create_schema(self, sft: "SimpleFeatureType | str", spec: "str | None" = None):
        if isinstance(sft, str):
            sft = SimpleFeatureType.create(sft, spec)
        if sft.type_name in self._types:
            raise ValueError(f"schema {sft.type_name!r} exists")
        primary = default_indices(sft)[0]
        os.makedirs(self._dir(sft.type_name), exist_ok=True)
        self._types[sft.type_name] = _FsTypeState(
            sft, primary, encoding=self.encoding, scheme=self._scheme_of(sft)
        )
        self._save_meta(sft.type_name)
        return sft

    def get_schema(self, type_name: str) -> SimpleFeatureType:
        return self._types[type_name].sft

    @property
    def type_names(self) -> list:
        return list(self._types)

    # -- writes ------------------------------------------------------------

    def write(self, type_name: str, columns_or_batch, fids=None) -> int:
        st = self._types[type_name]
        if isinstance(columns_or_batch, FeatureBatch):
            batch = columns_or_batch
        else:
            batch = FeatureBatch.from_columns(st.sft, columns_or_batch, fids)
        st.pending.append(batch)
        return len(batch)

    def flush(self, type_name: str) -> None:
        """Merge pending + existing into freshly sorted partition files (the
        compaction step; ref geomesa-fs CompactCommand semantics)."""
        st = self._types[type_name]
        if not st.pending:  # checked before locking: queries flush eagerly
            return
        with self._exclusive():
            self._refresh_from_disk(type_name)
            self._flush_locked(type_name)

    def _refresh_from_disk(self, type_name: str) -> None:
        """Re-read the on-disk manifest under the HELD exclusive lock:
        another process may have rewritten the directory since this
        process snapshotted it, and merging from the stale view would
        read deleted part files. Buffered pending rows survive; the disk
        wins on everything else (partitions, primary, scheme, stats)."""
        meta_path = os.path.join(self._dir(type_name), "schema.json")
        if not os.path.exists(meta_path):
            return
        st = self._types.get(type_name)
        try:
            gen_path = meta_path + ".gen"
            if os.path.exists(gen_path):
                with open(gen_path) as fh:
                    disk_gen = fh.read().strip() or None
            else:  # pre-sidecar manifest: full parse fallback
                with open(meta_path) as fh:
                    disk_gen = json.load(fh).get("generation")
        except (OSError, json.JSONDecodeError):
            return  # unreadable manifest: keep our view
        if st is not None and disk_gen == st.generation:
            # nobody else wrote since we last read/wrote: our in-memory
            # state may be deliberately AHEAD of disk (failed-flush
            # recovery holds everything in pending; deletions may not be
            # persisted yet) and must win
            return
        new = self._read_state(type_name)
        if st is None:
            self._types[type_name] = new
            return
        # in-place: callers (delete, plan, query) hold references to the
        # state object across flushes -- rebinding would strand them on a
        # dead object. Buffered pending rows survive; disk wins on the
        # rest.
        st.sft = new.sft
        st.primary = new.primary
        st.partitions = new.partitions
        st.data_interval = new.data_interval
        st.encoding = new.encoding
        st.scheme = new.scheme
        st.stats = new.stats
        st.generation = new.generation
        st.file_gen = new.file_gen
        st.format_version = new.format_version
        st.dirty = new.dirty
        st.wal_watermark = new.wal_watermark
        st.cache = {}
        # a new generation means new files: stale per-partition
        # quarantines must not outlive the files they indicted
        self._clear_quarantine(st)
        if getattr(self._lock_tl, "depth", 0) > 0:
            # already under the exclusive lock (a maintenance op noticed
            # another process's rewrite): reclaim anything a crashed
            # writer left behind while it is safe to do so
            return self._recover_locked(type_name)

    def _flush_locked(self, type_name: str) -> None:
        st = self._types[type_name]
        if st.dirty and not st.quarantine_owner:
            # a LEGACY (pre-generation) manifest recording a flush that
            # failed after unlinking its files: that process alone holds
            # the lost rows in memory. Flushing our own pending here
            # would publish a clean manifest with only OUR rows --
            # turning the loud failure back into silent loss.
            raise RuntimeError(
                f"dataset {type_name!r} is quarantined: a flush failed "
                "mid-rewrite in another process; retry there or restore "
                "the files"
            )
        if not st.pending:
            return
        orig_pending = list(st.pending)
        batches = orig_pending
        if st.partitions:
            batches = [self._read_all(type_name)] + batches
        data = batches[0] if len(batches) == 1 else FeatureBatch.concat(batches)
        # resolve the keyspace BEFORE clearing pending: a bad primary must
        # not drop the buffered writes
        ks = keyspace_for(st.sft, st.primary)
        st.pending = []
        gen0 = st.generation
        try:
            self._write_sorted(type_name, st, ks, data)
        except BaseException:
            # write-new-then-publish: the PREVIOUS generation is still
            # published and intact, so readers (this process and others)
            # lose nothing; _write_sorted already restored the manifest
            # view and swept its partial files. Restore the buffered
            # batches so a corrected retry merges exactly the same rows
            # -- unless the manifest actually advanced (a post-publish
            # failpoint/GC error), where a restore would duplicate them.
            # Prepended, not assigned: concurrent write() calls may have
            # buffered new batches while the flush ran.
            if st.generation == gen0:
                st.pending = orig_pending + st.pending
            raise

    def _write_sorted(self, type_name, st, ks, data) -> None:
        """Crash-consistent rewrite (write-new-then-publish): the new
        generation's ``part-<gen>-*`` files land NEXT TO the previous
        generation, are fsynced (contents, then directories), and only
        then does the manifest atomically flip — after which the old
        generation is garbage-collected. A crash at ANY instant leaves a
        store that reopens to exactly the previous or the new state;
        leftovers of an interrupted flush are unpublished and reclaimed
        by the recovery sweep. The ``fail.flush.*`` failpoints bracket
        each step for the chaos suite."""
        import dataclasses
        import uuid

        from geomesa_tpu.conf import sys_prop
        from geomesa_tpu.failpoints import fail_point
        from geomesa_tpu.pyarrow_compat import preload_pyarrow

        # the writer threads import pyarrow.parquet/orc: the FIRST pyarrow
        # import must happen on this (spawning) thread or a later
        # main-thread read segfaults (pyarrow_compat contract)
        preload_pyarrow()
        d = self._dir(type_name)
        fsync = bool(sys_prop("store.fsync"))
        new_gen = uuid.uuid4().hex[:8]
        # partition format v2: fixed-size chunks with manifest statistics
        # (store/chunkstats.py); parquet row groups align to the chunk
        # boundaries so chunk-pruned reads skip real bytes. v1 keeps the
        # legacy single-row-group layout bit-for-bit.
        from geomesa_tpu.store.chunkstats import FORMAT_V2, build_chunk_set

        fmt = int(sys_prop("store.format.version"))
        chunk_rows = max(int(sys_prop("store.chunk.rows")), 1)
        chunk_grid = max(int(sys_prop("store.chunk.grid")), 1)
        v2 = fmt == FORMAT_V2
        prev = (
            st.partitions, st.file_gen, st.stats, st.data_interval,
            st.generation, st.dirty, st.quarantine_owner, st.format_version,
        )
        # partition files stream out on writer threads (pyarrow releases
        # the GIL; at GB scale the writes are disk-writeback-bound) while
        # the main thread computes stats/manifest — joined BEFORE the
        # manifest publishes, so readers never see it ahead of the files
        writes: "list[tuple]" = []  # (PartitionMeta, Future[checksum])
        dirs = {d}  # every directory holding a new file gets fsynced
        publishing = False
        from geomesa_tpu.spawn import ContextPool

        # blessed pool: the writer threads charge write I/O to the
        # flushing request's collector (carried by submit-time capture)
        ex = ContextPool(2, thread_name_prefix="fs-flush")
        try:
            if st.scheme is not None and len(data):
                # group rows by directory leaf; each leaf is sorted +
                # manifested independently (the partition-scheme layout)
                leaves = st.scheme.leaves(data)
                pid = 0
                for leaf in sorted(set(leaves)):
                    sub = data.take(np.nonzero(leaves == leaf)[0])
                    built = self._build(ks, sub)
                    leaf_dir = d
                    for seg in leaf.split("/"):
                        leaf_dir = os.path.join(leaf_dir, seg)
                        dirs.add(leaf_dir)
                    os.makedirs(leaf_dir, exist_ok=True)
                    # ONE arrow conversion per leaf; partition files are
                    # zero-copy slices (a per-partition take + to_arrow
                    # paid a full column conversion for every file)
                    table = built.batch.to_arrow()
                    for p in built.partitions:
                        part = dataclasses.replace(
                            p,
                            pid=pid,
                            leaf=leaf,
                            chunks=build_chunk_set(
                                ks, built.batch, built.keys,
                                p.start, p.stop, chunk_rows, chunk_grid,
                            ) if v2 else None,
                        )
                        writes.append((part, ex.submit(
                            _write_part_file,
                            table.slice(p.start, p.stop - p.start),
                            self._part_path(type_name, part, gen=new_gen),
                            st.encoding,
                            fsync,
                            chunk_rows if v2 else None,
                        )))
                        pid += 1
                full = data
                z3_keys = None
            else:
                built = self._build(ks, data)
                table = built.batch.to_arrow()
                for p in built.partitions:
                    part = dataclasses.replace(
                        p,
                        chunks=build_chunk_set(
                            ks, built.batch, built.keys,
                            p.start, p.stop, chunk_rows, chunk_grid,
                        ) if v2 else None,
                    )
                    writes.append((part, ex.submit(
                        _write_part_file,
                        table.slice(p.start, p.stop - p.start),
                        self._part_path(type_name, part, gen=new_gen),
                        st.encoding,
                        fsync,
                        chunk_rows if v2 else None,
                    )))
                full = built.batch
                # the build already encoded every row's (bin, z): reuse
                # for the Z3 histogram instead of a second full encode
                z3_keys = (
                    (built.keys["bin"], built.keys["z"])
                    if getattr(ks, "name", None) == "z3"
                    else None
                )
            dtg = st.sft.dtg_field
            interval = st.data_interval
            if dtg is not None and len(full):
                col = full.column(dtg)
                interval = (int(col.min()), int(col.max()))
            from geomesa_tpu.store.memory import build_default_stats

            stats = build_default_stats(st.sft, full, z3_keys=z3_keys)
            # join: a failed write must fail the flush loudly, BEFORE
            # anything publishes; the checksums (and v2 per-chunk
            # row-group byte sizes) ride back with the joins
            parts = []
            for p, w in writes:
                checksum, chunk_nbytes = w.result()
                if (
                    p.chunks is not None
                    and chunk_nbytes is not None
                    and len(chunk_nbytes) == len(p.chunks)
                ):
                    p.chunks.nbytes = np.asarray(chunk_nbytes, dtype=np.int64)
                parts.append(
                dataclasses.replace(p, checksum=checksum, gen=new_gen)
            )
            fail_point("fail.flush.after_write")
            if fsync:
                for dd in sorted(dirs):
                    _fsync_dir(dd)
            st.partitions = parts
            st.file_gen = new_gen
            st.format_version = fmt
            st.data_interval = interval
            st.stats = stats
            st.cache = {}
            self._clear_quarantine(st)
            st.dirty = False
            st.quarantine_owner = False
            fail_point("fail.flush.before_publish")
            publishing = True
            self._save_meta(type_name)
        except BaseException:
            # abort: the previous generation is still the published one.
            # Restore the in-memory view to it and remove our partial
            # files — unless the manifest write itself was interrupted
            # (it may or may not have flipped); then the files stay and
            # the recovery sweep reconciles against the REAL manifest.
            # Queued writes are cancelled (their slices would only be
            # unlinked below); in-flight ones must land before unlinking.
            ex.shutdown(wait=True, cancel_futures=True)
            published_gen = st.generation if publishing else None
            (st.partitions, st.file_gen, st.stats, st.data_interval,
             st.generation, st.dirty, st.quarantine_owner,
             st.format_version) = prev
            st.cache = {}
            if publishing:
                # the manifest replace may have landed before the
                # failure (e.g. the SIDECAR write raised): the disk
                # decides which generation this process is on now. If
                # it flipped, adopt the new state — restoring the old
                # view would defeat _flush_locked's duplicate guard and
                # re-queue rows the manifest already owns. The lagging
                # sidecar is repaired by the next sweep/open.
                try:
                    with open(os.path.join(d, "schema.json")) as fh:
                        disk_gen = json.load(fh).get("generation")
                except (OSError, json.JSONDecodeError):
                    disk_gen = None
                if disk_gen == published_gen:
                    st.partitions, st.file_gen = parts, new_gen
                    st.data_interval, st.stats = interval, stats
                    st.generation = published_gen
                    st.format_version = fmt
                    st.dirty = False
                    st.quarantine_owner = False
            else:
                import logging

                for p, _ in writes:
                    path = self._part_path(type_name, p, gen=new_gen)
                    try:
                        os.unlink(path)
                    except FileNotFoundError:
                        pass
                    except OSError as e:
                        # the file is merely an unpublished orphan now --
                        # but operators should know the sweep owes work
                        logging.getLogger(__name__).warning(
                            "dataset %r: could not remove aborted flush "
                            "file %r: %s", type_name, path, e,
                        )
            raise
        finally:
            ex.shutdown(wait=True)
        from geomesa_tpu import metrics

        metrics.store_generations.inc()
        fail_point("fail.flush.after_publish")
        # the new generation is durable and published: the old one is
        # garbage — GC failures leave harmless orphans for the sweep
        self._gc_stale_parts(type_name)

    #: below this row count a mesh build is routed to the host lexsort
    #: anyway: per-shape jit traces + host->device transfer of tiny (e.g.
    #: per-leaf) batches cost more than the sort they accelerate
    MESH_BUILD_MIN_ROWS = 1 << 16

    def _build(self, ks, data) -> BuiltIndex:
        """Sorted-index build for a flush: on the device mesh when one was
        supplied, the key space has a device encode, and the batch is big
        enough to amortize the dispatch; host lexsort otherwise. Both
        produce bit-identical BuiltIndexes (proven by the parity suite),
        so the manifest/files do not depend on the path."""
        from geomesa_tpu.index.build import DEVICE_BUILD_KINDS

        if (
            self.mesh is not None
            and self.mesh.size > 1
            and getattr(ks, "name", None) in DEVICE_BUILD_KINDS
            and len(data) >= self.MESH_BUILD_MIN_ROWS
        ):
            # the mesh path earns its keep by PARALLELISM (the exchange
            # sort scales across shards); a single-device mesh pays the
            # host->device->host round trip of every lane for none.
            # Bit-identical either way (parity suite).
            return build_index(ks, data, self.partition_size, mesh=self.mesh)
        return build_index(ks, data, self.partition_size)

    #: sentinel: "use the type's published file generation"
    _GEN_CURRENT = object()

    def _part_path(
        self, type_name: str, p: PartitionMeta, gen=_GEN_CURRENT
    ) -> str:
        """Path of a partition file. ``gen`` defaults to the generation
        stamped on the META (falling back to the type's published file
        generation for unstamped metas; None = legacy un-scoped names);
        a flush mid-rewrite passes its NEW generation explicitly. Meta-
        faithful resolution is what keeps a scan over a pre-flush
        partition snapshot on ITS generation's files — it must never
        silently read a newer generation's file for the same pid."""
        from geomesa_tpu.store.partitions import part_file_name

        st = self._types[type_name]
        d = self._dir(type_name)
        if p.leaf:
            d = os.path.join(d, p.leaf)
        if gen is self._GEN_CURRENT:
            gen = p.gen if p.gen is not None else st.file_gen
        return os.path.join(d, part_file_name(p.pid, st.encoding, gen))

    # -- crash recovery / integrity ----------------------------------------

    @staticmethod
    def _clear_quarantine(st: "_FsTypeState") -> None:
        if st.quarantined:
            from geomesa_tpu import metrics

            metrics.store_quarantined.dec(len(st.quarantined))
            st.quarantined = {}

    def _quarantine(self, type_name: str, st, p, path: str, err: str) -> None:
        """Quarantine ONE partition after a checksum failure: loud
        per-partition error, the rest of the dataset keeps serving."""
        import logging

        from geomesa_tpu import metrics

        if p.pid not in st.quarantined:
            st.quarantined[p.pid] = err
            metrics.store_checksum_failures.inc()
            metrics.store_quarantined.inc()
            logging.getLogger(__name__).error(
                "dataset %r partition %d (%s): checksum verification "
                "failed (%s) -- partition quarantined; queries not "
                "touching it keep serving",
                type_name, p.pid, path, err,
            )

    def recover(self, type_name: str) -> dict:
        """Recovery sweep: under the exclusive lock (no flush can be
        mid-write), re-sync with the on-disk manifest, repair a lagging
        ``.gen`` sidecar, and reclaim files left by interrupted flushes
        (unpublished generations, ``*.tmp``). Idempotent; runs
        automatically at store open and from the CLI ``fsck``. Returns
        ``{"files": n, "bytes": b, "gen_repaired": bool}``."""
        with self._exclusive():
            # the refresh itself sweeps when it notices a newer on-disk
            # generation: fold that report in rather than dropping it
            pre = self._refresh_from_disk(type_name)
            rep = self._recover_locked(type_name)
            # fold in sweeps this call didn't run itself but whose work
            # would otherwise go unreported: the open-time sweep (fsck
            # opens the store, which already reclaimed the orphans) and
            # a refresh-triggered one
            for extra in (pre, self._open_recovery.pop(type_name, None)):
                if extra:
                    rep = {
                        "files": rep["files"] + extra["files"],
                        "bytes": rep["bytes"] + extra["bytes"],
                        "gen_repaired": rep["gen_repaired"]
                        or extra["gen_repaired"],
                    }
            return rep

    def _recover_locked(self, type_name: str) -> dict:
        import logging

        from geomesa_tpu import metrics

        repaired = self._repair_gen_sidecar(type_name)
        files, nbytes = self._gc_stale_parts(type_name)
        if files:
            metrics.store_orphan_files.inc(files)
            metrics.store_orphan_bytes.inc(nbytes)
            logging.getLogger(__name__).warning(
                "dataset %r: recovery sweep reclaimed %d orphan file(s), "
                "%d bytes, from an interrupted flush",
                type_name, files, nbytes,
            )
        return {"files": files, "bytes": nbytes, "gen_repaired": repaired}

    def _repair_gen_sidecar(self, type_name: str) -> bool:
        """A crash between the manifest replace and the sidecar replace
        leaves ``.gen`` one generation behind ``schema.json`` (whose
        value is the truth): republish the sidecar from the manifest."""
        st = self._types[type_name]
        if not st.generation:
            return False
        from geomesa_tpu.conf import sys_prop

        gen_path = os.path.join(self._dir(type_name), "schema.json.gen")
        disk = None
        try:
            with open(gen_path) as fh:
                disk = fh.read().strip() or None
        except OSError:
            pass
        if disk == st.generation:
            return False
        _write_file(
            gen_path + ".tmp",
            st.generation.encode("utf-8"),
            bool(sys_prop("store.fsync")),
        )
        os.replace(gen_path + ".tmp", gen_path)
        return True

    def _gc_stale_parts(self, type_name: str) -> "tuple[int, int]":
        """Remove part/tmp files not referenced by the current manifest
        (the previous generation right after a publish; interrupted-flush
        leftovers during a recovery sweep). Caller holds the exclusive
        lock. Returns (files, bytes) removed.

        Snapshot pins (store/snapshot.py) extend the keep-set: a pinned
        generation's files survive even after a newer manifest
        supersedes them, so an in-flight ``GET /snapshot`` stream never
        has a file reclaimed from under it; the pin helper also ages
        out orphaned pins (``snapshot.pin.ttl.s``) so a SIGKILLed
        stream delays GC boundedly instead of wedging it. Underscore
        directories (``_wal``, ``_pins``, ``_snapstage``) are never
        descended into — the WAL/pin/stage planes manage their own
        files."""
        import logging

        from geomesa_tpu.store import snapshot

        st = self._types[type_name]
        expected = {
            os.path.abspath(self._part_path(type_name, p))
            for p in st.partitions
        }
        expected |= snapshot.pinned_paths(self, type_name)
        files = nbytes = 0
        for dirpath, dirnames, names in os.walk(self._dir(type_name)):
            dirnames[:] = [d for d in dirnames if not d.startswith("_")]
            for f in names:
                if not (f.startswith("part-") or f.endswith(".tmp")):
                    continue
                path = os.path.join(dirpath, f)
                if os.path.abspath(path) in expected:
                    continue
                try:
                    sz = os.path.getsize(path)
                    os.unlink(path)
                except FileNotFoundError:
                    continue
                except OSError as e:
                    logging.getLogger(__name__).warning(
                        "dataset %r: could not reclaim %r: %s",
                        type_name, path, e,
                    )
                    continue
                files += 1
                nbytes += sz
        return files, nbytes

    def verify_partitions(self, type_name: str) -> "list[tuple]":
        """Full checksum verification of every partition file (the
        ``fsck`` pass, and what ``store.verify=open`` runs at store
        open): returns ``[(pid, path, error)]`` for the failures, each
        of which is quarantined."""
        with self._shared():
            self._refresh_from_disk(type_name)
            return self._verify_type(type_name)

    def _verify_type(self, type_name: str) -> "list[tuple]":
        st = self._types[type_name]
        errors = []
        for p in st.partitions:
            path = self._part_path(type_name, p)
            err = None
            try:
                with open(path, "rb") as fh:
                    data = fh.read()
            except OSError as e:
                err = f"unreadable: {e}"
            else:
                if p.checksum is not None:
                    err = verify_bytes(data, p.checksum)
            if err:
                self._quarantine(type_name, st, p, path, err)
                errors.append((p.pid, path, err))
        return errors

    def store_stats(self) -> dict:
        """Durability/integrity snapshot (the ``/stats/store`` endpoint
        and the ``fsck`` report): per-type generations, partition and
        quarantine state, plus the process-wide ``geomesa_store_*``
        counters."""
        from geomesa_tpu import metrics
        from geomesa_tpu.conf import sys_prop

        types = {}
        for name, st in self._types.items():
            chunked = [p for p in st.partitions if p.chunks is not None]
            types[name] = {
                "generation": st.generation,
                "file_generation": st.file_gen,
                "encoding": st.encoding,
                "format": int(st.format_version),
                "partitions": len(st.partitions),
                "rows": int(sum(p.count for p in st.partitions)),
                "dirty": bool(st.dirty),
                "wal_watermark": int(st.wal_watermark),
                # format-mix / chunk-stats coverage: how much of the
                # type the pruning + pushdown machinery can serve (v1
                # partitions linger until a compact lazily upgrades)
                "chunked_partitions": len(chunked),
                "chunks": int(sum(len(p.chunks) for p in chunked)),
                "chunk_rows_covered": int(
                    sum(p.count for p in chunked)
                ),
                "quarantined": {
                    int(pid): err for pid, err in st.quarantined.items()
                },
            }
        return {
            "root": self.root,
            "verify": sys_prop("store.verify"),
            "types": types,
            "counters": {
                "generations_published": metrics.store_generations.value(),
                "orphan_files_reclaimed": metrics.store_orphan_files.value(),
                "orphan_bytes_reclaimed": metrics.store_orphan_bytes.value(),
                "checksum_failures": metrics.store_checksum_failures.value(),
                "partitions_quarantined": metrics.store_quarantined.value(),
                "read_retries": metrics.store_read_retries.value(),
                "chunks_read": metrics.store_chunks_read.value(),
                "chunks_skipped": metrics.store_chunks_skipped.value(),
                "chunk_bytes_skipped":
                    metrics.store_chunk_bytes_skipped.value(),
                "chunk_stat_drift": metrics.store_chunk_stat_drift.value(),
                "pushdown_queries": {
                    k: metrics.agg_pushdown_queries.value(kind=k)
                    for k in ("count", "density", "stats")
                },
                "pushdown_fallbacks": {
                    k: metrics.agg_pushdown_fallbacks.value(kind=k)
                    for k in ("count", "density", "stats")
                },
                "pushdown_rows_preaggregated":
                    metrics.agg_pushdown_rows.value(),
            },
        }

    def delete(self, type_name: str, fids) -> int:
        """Drop features by id and compact the partition files. One
        exclusive section end to end: a writer slipping between the read
        and the rewrite would have its rows resurrected or duplicated."""
        with self._exclusive():
            self._refresh_from_disk(type_name)
            st = self._types[type_name]
            self._flush_locked(type_name)
            if not st.partitions:
                return 0
            data = self._read_all(type_name)
            # object dtype: a mixed int/str id list must not collapse to
            # all-str
            keep = ~np.isin(
                data.fids, np.asarray(list(fids), dtype=object)
            )
            removed = int((~keep).sum())
            if removed:
                st.pending = [data.take(np.nonzero(keep)[0])]
                st.partitions = []
                self._flush_locked(type_name)
            return removed

    def age_off(self, type_name: str, before_ms: int) -> int:
        from geomesa_tpu.store.ageoff import age_off

        return age_off(self, type_name, self._types[type_name].sft, before_ms)

    def update_user_data(self, type_name: str, updates: dict) -> None:
        """Set (or, with None values, remove) schema user-data entries and
        persist the manifest (ref: UpdateSftCommand / KeywordsCommand).
        Exclusive + refresh: _save_meta serializes the full partition
        list, and writing it from a stale view would clobber another
        process's flushed manifest."""
        with self._exclusive():
            self._refresh_from_disk(type_name)
            st = self._types[type_name]
            for k, v in updates.items():
                if v is None:
                    st.sft.user_data.pop(k, None)
                else:
                    st.sft.user_data[k] = v
            self._save_meta(type_name)

    def compact(self, type_name: str) -> None:
        """Rewrite all partition files merged + freshly sorted (ref:
        geomesa-fs CompactCommand)."""
        self._rebuild_files(type_name)

    # -- maintenance jobs (ref geomesa-jobs index back-population) ---------

    def _rebuild_files(self, type_name: str) -> None:
        """Re-sort + re-write every partition file under the current
        primary/scheme (pending data included)."""
        with self._exclusive():
            self._refresh_from_disk(type_name)
            self._rebuild_locked(type_name)

    def _rebuild_locked(self, type_name: str) -> None:
        st = self._types[type_name]
        if st.partitions:
            st.pending = [self._read_all(type_name)] + st.pending
            st.partitions = []
        self._flush_locked(type_name)
        # persists primary/scheme even when empty
        self._save_meta(type_name)

    def reindex(self, type_name: str, primary: str) -> None:
        """Switch the primary index and rebuild the sorted files (ref:
        geomesa-jobs attribute re-index / index back-population; here the
        sort order IS the index, so re-indexing is a rewrite)."""
        with self._exclusive():
            self._refresh_from_disk(type_name)  # BEFORE the mutation
            st = self._types[type_name]
            keyspace_for(st.sft, primary)  # validate against the schema
            st.primary = primary
            self._rebuild_locked(type_name)

    def repartition(self, type_name: str, scheme_spec: "str | None") -> None:
        """Change (or drop) the directory partition scheme and rewrite the
        layout."""
        from geomesa_tpu.store.partitions import USER_DATA_KEY, scheme_for

        with self._exclusive():
            self._refresh_from_disk(type_name)  # BEFORE the mutation
            st = self._types[type_name]
            if scheme_spec:
                scheme = scheme_for(scheme_spec)
                scheme.validate(st.sft)
                st.sft.user_data[USER_DATA_KEY] = scheme.spec
            else:
                scheme = None
                st.sft.user_data.pop(USER_DATA_KEY, None)
            st.scheme = scheme
            self._rebuild_locked(type_name)

    def _cache_slice(
        self, st, p: PartitionMeta, chunk_sel
    ) -> "FeatureBatch | None":
        """Serve a chunk-selective read from an already-cached FULL
        partition batch (chunk row offsets are partition-relative slices
        of the file order), or None on a cache miss. Chunk-selective
        results are never themselves pinned -- a partial batch in the
        cache would silently truncate later full reads. Cache keys are
        (generation, pid): a pid recurs across generations with
        different contents, so a stale-snapshot scan must neither hit a
        newer generation's bytes nor publish its own where a
        current-generation reader would find them."""
        full = st.cache.get((p.gen, p.pid))
        if full is None:
            return None
        cs = p.chunks
        idx = np.concatenate(
            [
                np.arange(int(cs.starts[i]), int(cs.stops[i]), dtype=np.int64)
                for i in chunk_sel
            ]
        ) if len(chunk_sel) else np.array([], dtype=np.int64)
        return full.take(idx)

    def _read_partition(
        self,
        type_name: str,
        p: PartitionMeta,
        cache: bool = True,
        chunk_sel=None,
    ) -> FeatureBatch:
        """``cache=False`` reads without pinning the batch in the
        per-type partition cache — the out-of-core streaming scan reads
        every partition exactly once, and pinning them would accumulate
        the whole dataset in host RAM (the thing that scan exists to
        avoid). ``chunk_sel`` reads only those chunks of a v2 partition
        (pruned row groups; never cached)."""
        st = self._types[type_name]
        if chunk_sel is not None:
            hit = self._cache_slice(st, p, chunk_sel)
            if hit is not None:
                return hit
        elif (p.gen, p.pid) in st.cache:
            return st.cache[(p.gen, p.pid)]
        with self._shared():  # never read a half-rewritten directory
            # chunk_sel only rides when set: monkeypatch/test doubles of
            # _read_part_table keep the legacy 3-arg call shape
            t = (
                self._read_part_table(type_name, p, chunk_sel=chunk_sel)
                if chunk_sel is not None
                else self._read_part_table(type_name, p)
            )
        # decode OUTSIDE the lock: _shared() is thread-exclusive
        # in-process (_mem_lock), and the Arrow->FeatureBatch conversion
        # is the heavy half — concurrent readers must overlap it
        return self._decode_part_table(
            type_name, p, t, cache and chunk_sel is None
        )

    def _read_partition_unlocked(
        self,
        type_name: str,
        p: PartitionMeta,
        cache: bool = False,
        chunk_sel=None,
    ) -> FeatureBatch:
        """Read + decode one partition file with NO locking — the caller
        must already hold the store lock (shared or exclusive) for the
        read's whole enclosing scan. This is the worker-thread read of
        the prefetch pipeline under a consumer-held lock (_query_locked,
        _read_all): workers beneath it must not touch the
        (thread-serializing) lock themselves, or the pipeline deadlocks
        against its own consumer."""
        st = self._types[type_name]
        if chunk_sel is not None:
            hit = self._cache_slice(st, p, chunk_sel)
            if hit is not None:
                return hit
        elif (p.gen, p.pid) in st.cache:
            return st.cache[(p.gen, p.pid)]
        t = (
            self._read_part_table(type_name, p, chunk_sel=chunk_sel)
            if chunk_sel is not None
            else self._read_part_table(type_name, p)
        )
        return self._decode_part_table(
            type_name, p, t, cache and chunk_sel is None
        )

    def _read_partition_prefetch(
        self, type_name: str, p: PartitionMeta, chunk_sel=None
    ) -> FeatureBatch:
        """Worker-thread partition read for the out-of-core stream.
        Guards against a mid-rewrite directory with the file lock ALONE:
        shared flock is concurrent across threads (each acquisition is
        its own fd, see locking.py), while _mem_lock — whose job is
        in-memory state, not files — would serialize the workers AND
        block every other thread's store use for the read's duration.
        Writers still exclude these reads via the exclusive flock. Never
        pins the partition cache (the streaming scan reads each
        partition exactly once)."""
        from geomesa_tpu.locking import file_lock

        st = self._types[type_name]
        if chunk_sel is not None:
            hit = self._cache_slice(st, p, chunk_sel)
            if hit is not None:
                return hit
        elif (p.gen, p.pid) in st.cache:
            return st.cache[(p.gen, p.pid)]
        # writer fence: touch (acquire+release) _mem_lock BEFORE taking
        # the shared flock. A same-process writer holds _mem_lock while
        # it polls for the exclusive flock; without the fence, N workers'
        # overlapping SH flocks give near-continuous coverage and the
        # non-blocking EX poll can starve into LockTimeout. With it, new
        # readers queue behind the writer, in-flight reads drain (each
        # bounded by one file), and the writer wins within ~one read.
        # (A writer in ANOTHER process has no such fence — it may wait
        # out in-flight reads up to its lock timeout, same flock
        # semantics as any concurrent reader fleet.)
        with self._mem_lock:
            pass
        with file_lock(self._lock_path, shared=True):
            t = (
                self._read_part_table(type_name, p, chunk_sel=chunk_sel)
                if chunk_sel is not None
                else self._read_part_table(type_name, p)
            )
        return self._decode_part_table(type_name, p, t, cache=False)

    def _read_partition_degradable(
        self, type_name: str, p: PartitionMeta, cache: bool = False,
        locked: bool = False,
    ):
        """Breaker-guarded partition read for the SERVING scan paths:
        transient errors retry on the worker (the ``io.*`` jittered,
        cumulative-capped budget), retries-exhausted and corrupt reads
        record a failure on THIS partition's circuit breaker and return
        a :class:`_PartFailure` sentinel (partition-scoped — the scan's
        pipeline and sibling partitions are untouched), and an OPEN
        breaker short-circuits the read entirely until its half-open
        probe. With ``resilience.degrade`` off this is exactly the
        plain read (errors propagate and fail the query loudly).
        ``locked`` selects the per-read-locking flavor
        (query_partitions holds no lock across its yields)."""
        from geomesa_tpu import resilience

        plain = (
            self._read_partition if locked else self._read_partition_unlocked
        )
        if not resilience.degrade_allowed():
            return plain(type_name, p, cache=cache)
        # breaker scope includes the store root: two stores (or a test
        # and its tmp sibling) with the same type name must not share
        # failure state
        br = resilience.partition_breaker(
            f"{self.root}:{type_name}", p.pid
        )
        if not br.allow():
            return _PartFailure(
                p,
                resilience.PartitionUnavailableError(
                    type_name, p.pid, "circuit breaker open"
                ),
            )
        from geomesa_tpu.store.prefetch import _with_retries

        read = _with_retries(lambda pp: plain(type_name, pp, cache=cache))
        try:
            batch = read(p)
        except FileNotFoundError:
            raise  # a real state (GC'd generation): refresh, not degrade
        except (OSError, PartitionCorruptError) as e:
            br.record_failure()
            return _PartFailure(p, e)
        br.record_success()
        return batch

    @staticmethod
    def _skip_part_failure(type_name: str, failure: "_PartFailure"):
        """Consumer half of the degradable read: note the degradation
        (header/audit stamping + metric) and log the skipped partition.
        Callers ``continue`` past the partition afterwards."""
        import logging

        from geomesa_tpu import resilience

        resilience.note_degraded("partition-unavailable")
        logging.getLogger(__name__).warning(
            "dataset %r partition %d unavailable (%s) -- serving "
            "DEGRADED result without it",
            type_name, failure.p.pid, failure.error,
        )

    def scan_lock_held(self) -> bool:
        """True when THIS thread holds the store's exclusive lock —
        prefetch consumers must then run their reads in-line (a worker
        thread's SH flock on a fresh fd conflicts with this process's
        held EX flock, and the worker cannot see the holder's
        thread-local depth)."""
        return getattr(self._lock_tl, "depth", 0) > 0

    def _row_groups_for(self, st, p: PartitionMeta, chunk_sel):
        """Row-group indices for a chunk-selective read, or None when
        the file cannot serve one (v1, ORC, or chunk stats without the
        write-time row-group record). v2 parquet writes size row groups
        to the chunk boundaries and record their byte sizes, so
        ``chunks align 1:1 with row groups`` holds by construction --
        the fsck chunk cross-check verifies it stays true on disk."""
        if chunk_sel is None:
            return None
        cs = p.chunks
        if (
            st.encoding != "parquet"
            or cs is None
            or cs.nbytes is None
            or len(cs.nbytes) != len(cs)
        ):
            return None
        return [int(i) for i in chunk_sel]

    @staticmethod
    def _slice_table_chunks(t, cs, chunk_sel):
        """Row-slice fallback for chunk-selective reads of files without
        aligned row groups (ORC): the whole table was read, only the
        selected chunks' rows survive to the (heavy) decode."""
        import pyarrow as pa

        slices = [
            t.slice(int(cs.starts[i]), int(cs.stops[i] - cs.starts[i]))
            for i in chunk_sel
        ]
        if not slices:
            return t.slice(0, 0)
        return pa.concat_tables(slices)

    def _read_part_table(
        self, type_name: str, p: PartitionMeta, chunk_sel=None
    ):
        """File -> Arrow table (timed; the prefetch pipeline's 'read'
        stage). Locking is the CALLER's concern. Honors the
        ``fail.read.*`` failpoints; under ``store.verify=always`` the
        raw bytes are checksummed against the manifest BEFORE parsing,
        and a mismatch quarantines this one partition and raises a
        loud :class:`PartitionCorruptError` (siblings keep serving).

        ``chunk_sel`` (v2 partitions) reads only the selected chunks:
        aligned parquet row groups skip the pruned chunks' file bytes
        outright (checksum verification, when on, still reads the whole
        file -- the checksum covers all bytes -- but only surviving row
        groups pay decompress/decode); other encodings read fully and
        row-slice before decode."""
        from geomesa_tpu import metrics
        from geomesa_tpu.conf import sys_prop
        from geomesa_tpu.failpoints import fail_hit, fail_point

        st = self._types[type_name]
        if p.pid in st.quarantined:
            raise PartitionCorruptError(
                f"dataset {type_name!r} partition {p.pid} is quarantined: "
                f"{st.quarantined[p.pid]}"
            )
        path = self._part_path(type_name, p)
        fail_point("fail.read.io")  # transient: the prefetch retry path
        injected = fail_hit("fail.read.corrupt")
        verify = injected or sys_prop("store.verify") == "always"
        row_groups = self._row_groups_for(st, p, chunk_sel)
        import time as _time

        from geomesa_tpu import ledger
        from geomesa_tpu.tracing import span

        t_read = _time.perf_counter()
        with span("store.read", pid=p.pid, rows=int(p.count)) as sp, \
                metrics.io_read_seconds.time():
            if not verify:
                t = _read_table(path, st.encoding, row_groups=row_groups)
            else:
                with open(path, "rb") as fh:
                    data = fh.read()
                err = (
                    "injected corruption (failpoint fail.read.corrupt)"
                    if injected
                    else verify_bytes(data, p.checksum)
                    if p.checksum is not None
                    else None
                )
                if err:
                    self._quarantine(type_name, st, p, path, err)
                    raise PartitionCorruptError(
                        f"dataset {type_name!r} partition {p.pid} "
                        f"({path}): {err}"
                    )
                t = _parse_table(data, st.encoding, row_groups=row_groups)
            if chunk_sel is not None and row_groups is None:
                t = self._slice_table_chunks(t, p.chunks, chunk_sel)
        ledger.charge("read_seconds", _time.perf_counter() - t_read)
        try:
            if row_groups is not None and not verify:
                # pruned read: account the bytes actually fetched (the
                # selected row groups' manifest-recorded sizes), not the
                # file size -- the skipped remainder is the pruning win
                size = int(p.chunks.nbytes[list(chunk_sel)].sum())
            else:
                size = os.path.getsize(path)
            metrics.io_bytes_read.inc(size)
            ledger.charge("read_bytes", size)
            sp.set(bytes=int(size))
            if chunk_sel is not None:
                sp.set(chunks=len(chunk_sel), chunk_total=len(p.chunks))
                ledger.charge("chunks_read", len(chunk_sel))
                ledger.charge(
                    "chunks_pruned", len(p.chunks) - len(chunk_sel)
                )
        except OSError:
            pass
        return t

    def _decode_part_table(
        self, type_name: str, p: PartitionMeta, t, cache: bool
    ) -> FeatureBatch:
        """Arrow table -> FeatureBatch (timed; the pipeline's 'decode'
        stage), optionally pinning the partition cache."""
        from geomesa_tpu import metrics

        from geomesa_tpu.tracing import span

        import time as _time

        from geomesa_tpu import ledger

        st = self._types[type_name]
        t_dec = _time.perf_counter()
        with span("store.decode", pid=p.pid) as sp, \
                metrics.io_decode_seconds.time():
            batch = FeatureBatch.from_arrow(t, st.sft)
        ledger.charge("decode_seconds", _time.perf_counter() - t_dec)
        sp.set(rows=len(batch))
        if cache:
            st.cache[(p.gen, p.pid)] = batch
        return batch

    def _read_all(self, type_name: str) -> FeatureBatch:
        """Merge-read every partition through the prefetch pipeline
        (reads + Arrow decode on worker threads, concat in partition
        order). Callers hold the exclusive lock (flush/delete/rebuild),
        so the lock-free worker reads are safe."""
        from geomesa_tpu.store.prefetch import (
            batch_nbytes,
            prefetch_map,
        )

        st = self._types[type_name]
        return FeatureBatch.concat(
            list(
                prefetch_map(
                    lambda p: self._read_partition_unlocked(type_name, p),
                    st.partitions,
                    self.io,
                    size_of=batch_nbytes,
                )
            )
        )

    # -- queries -----------------------------------------------------------

    def plan(self, type_name: str, query: "Query | str | ast.Filter") -> QueryPlan:
        self.flush(type_name)
        with self._shared():
            self._refresh_from_disk(type_name)  # another process may have written
            return self._plan_locked(type_name, query)

    def _plan_locked(self, type_name: str, query) -> QueryPlan:
        st = self._types[type_name]
        if st.dirty and not st.pending:
            # another process's flush failed after unlinking the old files;
            # the data exists only in THAT process's memory. An empty
            # result here would be silent data loss -- fail loudly. (The
            # quarantined writer itself still has `pending` and may serve
            # and retry.)
            raise RuntimeError(
                f"dataset {type_name!r} is quarantined: a flush failed "
                "mid-rewrite in another process; retry there or restore "
                "the files"
            )
        ks = keyspace_for(st.sft, st.primary)
        return plan_query(
            st.sft,
            {st.primary: ks},
            as_query(query),
            data_interval=st.data_interval,
            stats=st.stats,
        )

    def _pruned_parts(self, type_name: str, plan: QueryPlan) -> list:
        """Partition-scheme leaf prune, then manifest key-range prune."""
        st = self._types[type_name]
        parts = st.partitions
        if st.scheme is not None:
            from geomesa_tpu.store.partitions import scheme_matches

            parts = [
                p
                for p in parts
                if p.leaf is None or scheme_matches(st.scheme, p.leaf, plan)
            ]
        if plan.ranges is not None:
            parts = [
                p for p in parts if any(p.overlaps(r) for r in plan.ranges)
            ]
        return parts

    def query_partitions(self, type_name: str, query=ast.Include):
        """Yield one filtered FeatureBatch per surviving partition (the
        Spark SpatialRDDProvider analog: 1 partition per range group, so
        callers can process partitions in parallel).

        Row-local post-processing (visibility filtering, projection)
        applies per partition; global sort / max-features do NOT -- they
        have cross-partition semantics, same as Spark RDD partitions.
        """
        import dataclasses

        st = self._types[type_name]
        plan = self.plan(type_name, query)
        ks = keyspace_for(st.sft, st.primary)
        inner_plan = dataclasses.replace(
            plan,
            query=Query(filter=plan.filter, hints={"internal_scan": True}),
        )
        # per-partition outer pass: visibility + projection, no sort/limit
        outer_plan = dataclasses.replace(
            plan,
            query=dataclasses.replace(
                plan.query, sort_by=None, max_features=None
            ),
        )
        from geomesa_tpu.query.runner import _post_process
        from geomesa_tpu.store.prefetch import batch_nbytes, prefetch_map

        parts = self._pruned_parts(type_name, plan)
        # read-ahead while the CALLER processes each yielded batch. No
        # lock is held across the yields (callers may write/flush between
        # partitions), so the workers go through the store's own LOCKED
        # per-read path — reads serialize briefly on the store lock,
        # decodes still overlap. If THIS thread holds the exclusive lock
        # (a maintenance job iterating partitions in-place), workers
        # would block forever on the consumer-held _mem_lock — degrade
        # to the in-line serial reads, whose _shared() short-circuits on
        # the re-entrant thread-local depth.
        batches = prefetch_map(
            lambda p: self._read_partition_degradable(
                type_name, p, cache=True, locked=True
            ),
            parts,
            0 if self.scan_lock_held() else self.io,
            size_of=batch_nbytes,
        )
        try:
            for p, batch in zip(parts, batches):
                if isinstance(batch, _PartFailure):
                    # bulk/export consumers must never silently lose a
                    # partition: the fault surfaces as a TYPED,
                    # partition-scoped error naming exactly what is
                    # unreachable (retries already exhausted on the
                    # worker) — not an anonymous pipeline teardown
                    from geomesa_tpu import resilience

                    raise resilience.PartitionUnavailableError(
                        type_name, batch.p.pid, str(batch.error)
                    ) from batch.error
                local = BuiltIndex(
                    ks,
                    batch,
                    {},
                    [PartitionMeta(0, 0, len(batch), p.key_lo, p.key_hi, len(batch))],
                )
                sub = run_query(local, inner_plan)
                if len(sub.batch):
                    out = _post_process(sub.batch, outer_plan)
                    if len(out):
                        if out is batch:
                            # the internal_scan alias fast path can surface
                            # the partition's (cache-pinned) batch itself
                            # when the outer post-process is a no-op — copy
                            # before yielding (same guard as _query_locked;
                            # `is batch` rather than scanning st.cache,
                            # which prefetch workers mutate concurrently)
                            out = out.take(np.arange(len(out)))
                        yield out
        finally:
            batches.close()

    def query(self, type_name: str, query: "Query | str | ast.Filter" = ast.Include) -> QueryResult:
        """Partition-pruned scan over parquet files. The SHARED lock is
        held across plan + every partition read, so a concurrent writer's
        in-place rewrite can neither unlink files mid-scan nor mix rows
        from two manifest generations into one result."""
        import time as _time

        from geomesa_tpu.tracing import span

        t0 = _time.perf_counter()
        with span("store.query", store="fs", type=type_name) as sp:
            # flush BEFORE the shared lock: exclusive if pending
            self.flush(type_name)
            with self._shared():
                res = self._query_locked(type_name, query, t0)
            sp.set(hits=len(res), scanned=res.scanned)
            return res

    def _query_locked(self, type_name: str, query, t0) -> QueryResult:
        import time as _time

        self._refresh_from_disk(type_name)
        st = self._types[type_name]
        plan = self._plan_locked(type_name, query)
        t1 = _time.perf_counter()
        parts = self._pruned_parts(type_name, plan)
        # scan each surviving file through the shared runner by wrapping it
        # as a single-partition BuiltIndex
        ks = keyspace_for(st.sft, st.primary)
        chunks = []
        scanned = 0
        # per-partition scans must not apply projection/sort/limit -- that
        # happens once, globally, after the merge
        import dataclasses

        inner_plan = dataclasses.replace(
            plan,
            query=Query(filter=plan.filter, hints={"internal_scan": True}),
        )
        from geomesa_tpu.conf import QueryTimeout, sys_prop
        from geomesa_tpu.store.prefetch import batch_nbytes, prefetch_map

        timeout_ms = sys_prop("query.timeout")
        deadline = t0 + timeout_ms / 1000.0 if timeout_ms else None
        # partition reads + Arrow decode run ahead on the prefetch
        # pipeline (this method executes under the held shared lock, so
        # the workers' lock-free reads are safe) while this thread scans;
        # cache=True keeps the partition-cache semantics of the serial
        # path. A deadline abort closes the pipeline (workers drained)
        # via the generator's finally.
        batches = prefetch_map(
            lambda p: self._read_partition_degradable(
                type_name, p, cache=True
            ),
            parts,
            self.io,
            size_of=batch_nbytes,
        )
        # FULL scans (Include, no ranges — notably the resident
        # DeviceIndex staging query) stream into buffers pre-sized from
        # the manifest's chunk/partition row counts instead of the
        # collect-then-concat path: one dataset copy instead of two at
        # peak, and zero-row partitions never touch the buffers
        sink = (
            _PresizedSink(st.sft, sum(int(q.count) for q in parts))
            if plan.filter is ast.Include
            and plan.ranges is None
            and len(parts) > 1
            else None
        )
        sources = []  # the read batch behind each chunk (alias guard)
        try:
            for p, batch in zip(parts, batches):
                if deadline and _time.perf_counter() > deadline:
                    raise QueryTimeout(
                        f"query on {type_name!r} exceeded {timeout_ms}ms"
                    )
                if isinstance(batch, _PartFailure):
                    from geomesa_tpu import resilience

                    if resilience.capture_degraded() is None:
                        # no request collector to stamp: a library/CLI
                        # caller would get a SILENT partial — fail with
                        # the typed partition-scoped error instead (the
                        # serving path installs collect_degraded and
                        # rides the branch below)
                        raise resilience.PartitionUnavailableError(
                            type_name, batch.p.pid, str(batch.error)
                        ) from batch.error
                    # partition-scoped fault: serve the siblings, stamp
                    # the result degraded (never a silent partial)
                    self._skip_part_failure(type_name, batch)
                    continue
                scanned += len(batch)
                local = BuiltIndex(
                    ks,
                    batch,
                    {},
                    [
                        PartitionMeta(
                            0, 0, len(batch), p.key_lo, p.key_hi, len(batch)
                        )
                    ],
                )
                sub = run_query(local, inner_plan)
                if len(sub.batch):
                    if sink is not None:
                        sink.add(sub.batch)  # copies; batch drops now
                    else:
                        chunks.append(sub.batch)
                        sources.append(batch)
        finally:
            batches.close()
        total = sum(p.count for p in st.partitions)
        if sink is not None and sink.filled:
            out = sink.finish()
        elif chunks:
            if len(chunks) == 1:
                out = chunks[0]
                if out is sources[0]:
                    # the aliasing fast path above only holds WITHIN this
                    # scan: a single-chunk full match would hand the
                    # (cache-pinned) partition batch to the caller — copy.
                    # Checked against the scan's OWN source list: another
                    # thread's prefetch workers mutate st.cache lock-free,
                    # so iterating st.cache.values() here would race.
                    out = out.take(np.arange(len(out)))
            else:
                out = FeatureBatch.concat(chunks)
        else:
            empty = self._read_partition(type_name, st.partitions[0]).take(
                np.array([], dtype=np.int64)
            ) if st.partitions else FeatureBatch.from_columns(
                st.sft, {a.name: [] for a in st.sft.attributes}
            )
            out = empty
        from geomesa_tpu.query.runner import _post_process
        from geomesa_tpu.audit import observe_query

        out = _post_process(out, plan)
        result = QueryResult(out, plan, scanned, total)
        observe_query(
            "fs", type_name, plan, t0, t1, _time.perf_counter(), result,
            self.audit_writer,
        )
        return result

    def explain(self, type_name: str, query) -> str:
        return self.plan(type_name, query).explain()

    # -- aggregation pushdown (partition format v2) ------------------------

    def manifest_rows(self, type_name: str) -> int:
        """Total rows recorded by the manifest (== file rows by the
        manifest contract) — the pre-size hint resident staging and the
        pushdown paths consume without reading any file."""
        return int(sum(p.count for p in self._types[type_name].partitions))

    def has_chunk_stats(self, type_name: str) -> bool:
        """True when every partition of ``type_name`` carries v2 chunk
        statistics, i.e. aggregate pushdown can answer bbox+time shapes
        without row scans. The server's brownout rung consults this —
        over a v1/legacy dataset the 'pre-aggregate' path would quietly
        row-scan, the opposite of a brownout."""
        st = self._types.get(type_name)
        if st is None:
            return False
        # snapshot: flush replaces st.partitions wholesale, never mutates
        return all(p.chunks is not None for p in list(st.partitions))

    def count(self, type_name: str, query=ast.Include) -> int:
        """Filtered count; bbox+time-shaped filters on a v2 store are
        answered from chunk pre-aggregates (interior chunks from the
        manifest, boundary chunks row-refined — bit-identical to the
        row scan, proven by the parity tests) without reading interior
        rows. Anything the chunk stats cannot decide exactly falls back
        to the full query path. Pushdown-served counts are audited and
        counted exactly like scanned ones."""
        import time as _time

        from geomesa_tpu.audit import observe_query
        from geomesa_tpu.store.pushdown import count_pushdown

        t0 = _time.perf_counter()
        self.flush(type_name)
        with self._shared():
            self._refresh_from_disk(type_name)
            t1 = _time.perf_counter()
            out = count_pushdown(self, type_name, query)
        if out is not None:
            n, plan = out
            observe_query(
                "fs", type_name, plan, t0, t1, _time.perf_counter(),
                _Sized(n), self.audit_writer,
            )
            return n
        return len(self.query(type_name, query))

    def density_pushdown(
        self, type_name: str, query, envelope, width: int, height: int
    ):
        """Chunk-granular density grid (see store/pushdown.py), or None
        when the query needs the row-scan path. Interior chunks prorate
        their coarse world-grid histograms onto the raster; boundary
        chunks read + rasterize exactly — total mass matches the row
        scan, per-pixel placement is within coarse-cell tolerance."""
        from geomesa_tpu.store.pushdown import density_pushdown

        self.flush(type_name)
        with self._shared():
            self._refresh_from_disk(type_name)
            return density_pushdown(
                self, type_name, query, envelope, width, height
            )

    def stats_pushdown(self, type_name: str, query, stat_spec: str):
        """Stat-DSL aggregation from chunk partials (Count/MinMax specs
        with bbox+time filters; exact — interior chunks merge their
        manifest sketches, boundary chunks observe their rows), or None
        for the row-scan path."""
        from geomesa_tpu.store.pushdown import stats_pushdown

        self.flush(type_name)
        with self._shared():
            self._refresh_from_disk(type_name)
            return stats_pushdown(self, type_name, query, stat_spec)

    def verify_chunk_stats(self, type_name: str) -> "list[tuple]":
        """fsck's chunk-stat cross-check: decode every v2 partition and
        recompute per-chunk row counts, key min/max, bbox, time range,
        density-cell mass and MinMax partials against the manifest (plus
        parquet row-group alignment). Returns ``[(pid, chunk, error)]``
        for every drifted record — drift means pruning/pushdown could
        return wrong answers, so fsck fails nonzero on it."""
        from geomesa_tpu.store.pushdown import verify_chunk_stats

        with self._shared():
            self._refresh_from_disk(type_name)
            return verify_chunk_stats(self, type_name)



