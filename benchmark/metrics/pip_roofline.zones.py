"""Roofline share of the point-in-polygon count kernel
(ops/pallas_scan.py, ``pip_count``) under the zone mix's
INTERSECTS(zone) AND DURING filters: the least time of its calls by
bytes (resident rows x 16 B: lon, lat float32 and dtg int64, plus the
largest edge table of the pool, 4 x 1,024 float32), over the chip's HBM
bandwidth, over their summed device time in the trace.

The kernel is bound by compute, not bytes: each row of a tested block
is tested against every padded edge of its zone, so this share reads
low by design. It moves when the kernel reads fewer bytes or tests
fewer blocks; ``pip_edge_rate.zones`` gives the compute side."""

from benchmark.measure import roofline_pct

PATTERNS = [r"^jit_pip_count/%pip_count"]
BYTES_PER_ROW = 16
EDGE_TABLE_BYTES = 4 * 1024 * 4


def read(ctx):
    return roofline_pct(ctx, PATTERNS,
                        ctx.rows * BYTES_PER_ROW + EDGE_TABLE_BYTES)
