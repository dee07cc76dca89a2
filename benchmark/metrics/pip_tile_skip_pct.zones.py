"""Share of the point-in-polygon kernel's grid tiles skipped whole over
the window, in %: skipped over walked plus skipped, from the program
counter ``geomesa_pip_tiles_total{outcome}``. A tile is skipped when
none of its live rows passes the pretests the predicate implies (the
zone's envelope and the DURING window), so its blocks pay no pretest.
Higher is less work."""

from benchmark.measure import prom_delta

KEY = "geomesa_pip_tiles_total"


def read(ctx):
    walked = prom_delta(ctx, KEY + '{outcome="walked"}')
    skipped = prom_delta(ctx, KEY + '{outcome="skipped"}')
    if walked + skipped <= 0:
        return None
    return 100.0 * skipped / (walked + skipped)
