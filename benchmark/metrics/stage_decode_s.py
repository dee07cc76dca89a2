"""Seconds the file store spent decoding partition files' Arrow tables
into feature batches (``FeatureBatch.from_arrow``, the String columns'
``to_pylist``) before the window: in this cell, the resident index's
staging scan. The store's ``geomesa_io_decode_seconds`` histogram sum
(program counter), which times each file's decode on the read worker
that ran it, so it sums over the workers."""


def read(ctx):
    return ctx.prom_before.get("geomesa_io_decode_seconds_sum")
