"""Mean handler-thread time of a traced request over the window, from
its first line parsed to its last byte written: the ``http.request``
stage (program counter ``geomesa_stage_seconds``, sum over count)."""

from benchmark.stage_counters import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "http.request")
