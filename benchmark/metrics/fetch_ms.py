"""Mean time from a resident count's or density's dispatch returning to
its value on the host (the blocking ``int``/``np.asarray`` copy) over
the window: the ``device.fetch`` stage (program counter
``geomesa_stage_seconds``, sum over count)."""

from benchmark.stage_counters import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "device.fetch")
