"""XLA backend compile seconds before the window (set-up: staging and
warm-up), from the compile ledger's
``geomesa_compile_event_seconds_total`` (program counter). The counter
has no sample until something compiles, so a set-up that compiled
nothing reads 0.0."""


def read(ctx):
    return ctx.prom_before.get("geomesa_compile_event_seconds_total", 0.0)
