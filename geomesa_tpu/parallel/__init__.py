"""Multi-chip scaling: device meshes + XLA collectives (maps reference
section 2.6 parallelism inventory).

The reference's distribution mechanisms (key-space sharding across tablets,
range-parallel BatchScanner fan-out, MapReduce Z-sort, Thrift/protobuf RPC)
become, TPU-natively:

- a ``jax.sharding.Mesh`` with a ``shard`` axis (data partitions over chips)
  and optional ``replica`` axis (query fan-out)
- fused mask scans under ``shard_map`` with ``psum``/``all_gather`` merges
  (the BatchScanner + client merge)
- Z-order index build as local ``lax.sort`` + ``all_to_all`` radix exchange
  on the high z bits (the MapReduce bulk-sort; ICI is the compiler-scheduled
  NCCL analog)

Everything compiles against virtual CPU meshes for tests and dry runs.
"""

from geomesa_tpu.parallel.mesh import make_mesh, serving_mesh
from geomesa_tpu.parallel.dist import (
    sharded_count_scan,
    distributed_sort,
    distributed_z3_sort,
    sharded_build_and_query_step,
    sharded_query_scan,
)
from geomesa_tpu.parallel.multihost import (
    global_mesh,
    host_batches_to_global,
    initialize,
)

__all__ = [
    "make_mesh",
    "serving_mesh",
    "sharded_count_scan",
    "distributed_sort",
    "distributed_z3_sort",
    "sharded_build_and_query_step",
    "sharded_query_scan",
    "initialize",
    "global_mesh",
    "host_batches_to_global",
]
