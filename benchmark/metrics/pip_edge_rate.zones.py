"""Edge tests of the point-in-polygon count kernel per second of its
device time, in G/s: the change of the program counter
``geomesa_pip_edge_tests_total`` (rows of the blocks the kernel tested
x their zone's padded edges) across the window, over the summed device
time of the kernel's calls in the traced window (``pip_roofline.zones``
matches the same calls). The counter and the trace span the same
window, so the ratio is the kernel's compute rate."""

from benchmark.measure import prom_delta

PATTERNS = [r"^jit_pip_count/%pip_count"]


def read(ctx):
    if ctx.trace is None:
        return None
    n, seconds = ctx.trace.kernel(PATTERNS)
    tests = prom_delta(ctx, "geomesa_pip_edge_tests_total")
    if n == 0 or seconds <= 0 or tests <= 0:
        return None
    return tests / seconds / 1e9
