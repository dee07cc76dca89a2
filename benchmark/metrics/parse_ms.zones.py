"""Mean time of the resident index's CQL parse over the window: the
``cql.parse`` stage (program counter ``geomesa_stage_seconds``, sum
over count). The stage covers the lookup of the request's text among
the texts the index has parsed, and on a miss the parse of a WKT polygon
of up to about a thousand vertices; a program without that memo parses
every request."""

from benchmark.stage_counters import window_mean_ms


def read(ctx):
    return window_mean_ms(ctx, "cql.parse")
