"""Thread CPU time of the scheduler's executions over the window, over
their count: the ``sched.execute`` stage's
``geomesa_stage_cpu_seconds_total`` (program counter). Below
``execute_ms`` by the time the worker waited: the GIL, locks, the
device."""

from benchmark.stage_counters import window_cpu_mean_ms


def read(ctx):
    return window_cpu_mean_ms(ctx, "sched.execute")
