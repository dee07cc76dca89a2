"""Readings of the program's stage counters (``tracing.STAGES``): the
wall histogram ``geomesa_stage_seconds{stage}`` and the thread CPU
counter ``geomesa_stage_cpu_seconds_total{stage}``, from the
``/metrics`` scrapes the harness takes around the window
(``ctx.prom_before``, ``ctx.prom_after``). Each returns None when the
program exports no such series, as a program without the stage
counters does."""

from __future__ import annotations

from benchmark.measure import prom_delta

WALL = "geomesa_stage_seconds"
CPU = "geomesa_stage_cpu_seconds_total"


def _key(name: str, stage: str) -> str:
    return f'{name}{{stage="{stage}"}}'


def window_mean_ms(ctx, stage: str) -> "float | None":
    """Mean wall time of the stage's spans that ended in the window."""
    n = prom_delta(ctx, _key(WALL + "_count", stage))
    if n <= 0:
        return None
    return prom_delta(ctx, _key(WALL + "_sum", stage)) / n * 1e3


def window_cpu_mean_ms(ctx, stage: str) -> "float | None":
    """Thread CPU time of the stage's live spans in the window, over
    the number of its spans."""
    n = prom_delta(ctx, _key(WALL + "_count", stage))
    if n <= 0 or _key(CPU, stage) not in ctx.prom_after:
        return None
    return prom_delta(ctx, _key(CPU, stage)) / n * 1e3
