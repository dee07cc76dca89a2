"""Mean time from a result in hand to its response body over the
window (JSON dumps, the density grid's ``tolist`` included): the
``http.encode`` stage (program counter ``geomesa_stage_seconds``, sum
over count)."""

from benchmark.stage_counters import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "http.encode")
