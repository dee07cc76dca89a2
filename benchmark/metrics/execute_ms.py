"""Mean wall time a scheduler worker holds one execution over the
window: the ``sched.execute`` stage (program counter
``geomesa_stage_seconds``, sum over count)."""

from benchmark.stage_counters import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "sched.execute")
