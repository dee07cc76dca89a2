"""Share of the device's idle time in the traced window during which at
least one scheduler execution was open on the host: the union of the
``sched.execute`` annotations on the host planes (the program enters
``jax.profiler.TraceAnnotation`` for each live span of a declared
stage) minus the device's busy intervals, over the window minus them,
averaged over the devices that ran (device trace). None when the trace
holds no such annotation."""

STAGE = "sched.execute"


def _union(iv: list) -> list:
    out: list = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(xs: list, ys: list) -> int:
    """Summed intersection of two sorted lists of disjoint intervals."""
    tot, i, j = 0, 0, 0
    while i < len(xs) and j < len(ys):
        lo = max(xs[i][0], ys[j][0])
        hi = min(xs[i][1], ys[j][1])
        if hi > lo:
            tot += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    ex = _union([(int(a), int(b)) for a, b, name in zip(
        tr.host_start, tr.host_end, tr.host_name) if name == STAGE])
    if not ex:
        return None
    open_ns = sum(b - a for a, b in ex)
    shares = []
    for dev in tr.devices.values():
        busy = _union([(o[0], o[1]) for o in dev.ops])
        idle = ctx.window_s * 1e9 - sum(b - a for a, b in busy)
        if idle > 0:
            shares.append(100.0 * (open_ns - _overlap(ex, busy)) / idle)
    return sum(shares) / len(shares) if shares else None
