"""Share of the point-in-polygon kernel's row blocks that ran the edge
loop over the window, in %: tested over tested plus skipped, from the
program counter ``geomesa_pip_blocks_total{outcome}``. A block is
skipped when none of its live rows passes the pretests the predicate
implies (the zone's envelope and the DURING window). Lower is less
work."""

from benchmark.measure import prom_delta

KEY = "geomesa_pip_blocks_total"


def read(ctx):
    tested = prom_delta(ctx, KEY + '{outcome="tested"}')
    skipped = prom_delta(ctx, KEY + '{outcome="skipped"}')
    if tested + skipped <= 0:
        return None
    return 100.0 * tested / (tested + skipped)
