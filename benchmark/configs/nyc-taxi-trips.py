"""Data of the nyc-taxi-trips configuration, made from ``--seed`` alone.

Every field of a 2015 TLC yellow-trip record is stored (the
configuration's ``spec``); the pickup longitude and latitude are the
point. Pickups cluster in hot spots along Manhattan and at the two
airports, fixed by ``layout_seed``; pickup times follow a two-peak daily
profile and come out sorted, so the reference can slice a time window by
binary search (the store Z-orders rows on write, so the row order is not
an input the program sees). Row i's feature id is i.

The zones (:func:`shapes`) partition the configuration's extent and do
not depend on ``--seed``: a k-d split of the extent, the cell with the
largest sqrt(pickups x area) split first, whose cell sides become
chains of float32 vertices that the two cells on either side share.
"""

from __future__ import annotations

import math

import numpy as np

DAY_MS = 86_400_000
MINUTE_MS = 60_000


def _ms(iso: str) -> int:
    return int(np.datetime64(iso, "ms").astype(np.int64))


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([abs(int(seed)), tag]))


def _layout_rng(cfg: dict, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(cfg["layout_seed"]), tag])


def extent(cfg: dict) -> np.ndarray:
    """(x0, y0, x1, y1) as float32: the zones' outer vertices."""
    return np.asarray(cfg["extent"], np.float32)


def hot_spots(cfg: dict) -> "tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]":
    """(x, y, sigma, weight) of the pickup hot spots, fixed by
    ``layout_seed``: centres strung along the Manhattan axis (denser
    towards midtown), then the airports."""
    r = _layout_rng(cfg, 1)
    k = int(cfg["hot_spots"])
    (ax, ay), (bx, by) = cfg["manhattan_axis"]
    t = r.beta(2.0, 3.0, k)
    side = r.normal(0.0, 0.004, k)  # across the island
    norm = math.hypot(bx - ax, by - ay)
    x = ax + t * (bx - ax) - side * (by - ay) / norm
    y = ay + t * (by - ay) + side * (bx - ax) / norm
    s0, s1 = cfg["hot_spot_sigma_deg"]
    sigma = np.exp(r.uniform(math.log(s0), math.log(s1), k))
    w = np.exp(r.uniform(0.0, 2.0, k))
    w *= float(cfg["hot_spot_share"]) / w.sum()
    ports = list(cfg["airports"].values())
    x = np.concatenate([x, [p[0] for p in ports]])
    y = np.concatenate([y, [p[1] for p in ports]])
    sigma = np.concatenate([sigma, [float(cfg["airport_sigma_deg"])] * len(ports)])
    w = np.concatenate([w, [p[2] for p in ports]])
    return x, y, sigma, w


def _pickup_cdf(cfg: dict) -> "tuple[np.ndarray, np.ndarray]":
    """(minute starts in epoch ms, cumulative share) of the two-peak
    daily profile over the configured span."""
    start, end = _ms(cfg["time_start"]), _ms(cfg["time_end"])
    minutes = np.arange(start, end + MINUTE_MS, MINUTE_MS, dtype=np.int64)
    h = ((minutes[:-1] - start) % DAY_MS) / 3_600_000.0
    rate = (0.25 + np.exp(-0.5 * ((h - 8.5) / 1.5) ** 2)
            + 1.3 * np.exp(-0.5 * ((h - 19.0) / 2.5) ** 2)
            + 0.6 * np.exp(-0.5 * ((h - 24.5) / 2.0) ** 2))
    cdf = np.concatenate([[0.0], np.cumsum(rate)])
    return minutes, cdf / cdf[-1]


def _sorted_times(rng, cfg: dict, n: int) -> np.ndarray:
    """n pickup times in ascending order: sorted uniforms (normalized
    sums of exponential gaps, no sort of n values) through the inverse
    of the daily profile's distribution."""
    c = np.cumsum(rng.standard_exponential(n + 1))
    u = c[:-1] / c[-1]
    minutes, cdf = _pickup_cdf(cfg)
    t = np.interp(u, cdf, minutes.astype(np.float64))
    return np.floor(t).astype(np.int64)


def points(cfg: dict, seed: int, rows: int) -> dict:
    """``lon``/``lat`` float32 inside the extent and ``dtg`` epoch ms in
    ascending order: all that the reference reads."""
    rng = _rng(seed, 1)
    x0, y0, x1, y1 = extent(cfg)
    cx, cy, sigma, w = hot_spots(cfg)
    u = rng.random(rows, dtype=np.float32)
    spot = np.searchsorted(np.cumsum(w).astype(np.float32), u, "right")
    clustered = spot < len(w)
    spot = np.minimum(spot, len(w) - 1)
    s = sigma.astype(np.float32)[spot]
    lon = cx.astype(np.float32)[spot] + rng.standard_normal(
        rows, dtype=np.float32) * s
    lat = cy.astype(np.float32)[spot] + rng.standard_normal(
        rows, dtype=np.float32) * s
    spread = np.flatnonzero(~clustered)
    lon[spread] = rng.uniform(x0, x1, len(spread))
    lat[spread] = rng.uniform(y0, y1, len(spread))
    del u, spot, clustered, s, spread
    # inside [x0, x1) x [y0, y1): every pickup lies in exactly one zone
    np.clip(lon, x0, np.nextafter(x1, x0), out=lon)
    np.clip(lat, y0, np.nextafter(y1, y0), out=lat)
    dtg = _sorted_times(rng, cfg, rows)
    return {"lon": lon, "lat": lat, "dtg": dtg}


def generate(cfg: dict, seed: int, rows: int) -> dict:
    """Every attribute of the spec but the point, plus ``lon``/``lat``
    float32 for the pickup point and ``dtg`` (tpep_pickup_datetime)
    epoch ms in ascending order."""
    out = points(cfg, seed, rows)
    lon, lat, dtg = out["lon"], out["lat"], out["dtg"]
    rng = _rng(seed, 2)
    miles = np.round(np.exp(rng.normal(math.log(1.7), 0.8, rows)), 2)
    minutes = miles / 11.0 * 60.0 + rng.exponential(3.0, rows)
    bearing = rng.uniform(0.0, 2.0 * math.pi, rows)
    deg = miles / 69.0
    x0, y0, x1, y1 = (float(v) for v in extent(cfg))
    drop_lon = np.clip(lon + deg * np.cos(bearing) / 0.76, x0, x1)
    drop_lat = np.clip(lat + deg * np.sin(bearing), y0, y1)
    hour = (dtg % DAY_MS) // 3_600_000
    card = rng.random(rows) < 0.6
    fare = np.ceil((2.5 + 2.5 * miles + 0.5 * rng.exponential(2.0, rows))
                   * 2.0) / 2.0
    extra = np.where((hour >= 20) | (hour < 6), 0.5,
                     np.where((hour >= 16) & (hour < 20), 1.0, 0.0))
    tip = np.where(card, np.round(0.2 * fare, 2), 0.0)
    tolls = np.where(rng.random(rows) < 0.04, 5.54, 0.0)
    out.update({
        "VendorID": rng.integers(1, 3, rows).astype(np.int32),
        "tpep_dropoff_datetime": dtg + np.round(minutes * MINUTE_MS).astype(
            np.int64),
        "passenger_count": rng.choice(
            np.arange(1, 7, dtype=np.int32), rows,
            p=[0.70, 0.14, 0.04, 0.02, 0.06, 0.04]),
        "trip_distance": miles,
        "RateCodeID": rng.choice(
            np.array([1, 2, 3, 4, 5], np.int32), rows,
            p=[0.970, 0.020, 0.002, 0.003, 0.005]),
        "store_and_fwd_flag": np.array(["N", "Y"], dtype=object)[
            (rng.random(rows) < 0.005).astype(np.intp)],
        "dropoff_longitude": drop_lon,
        "dropoff_latitude": drop_lat,
        "payment_type": np.where(card, 1, rng.choice(
            np.array([2, 3, 4], np.int32), rows, p=[0.96, 0.03, 0.01])
        ).astype(np.int32),
        "fare_amount": fare,
        "extra": extra,
        "mta_tax": np.full(rows, 0.5),
        "tip_amount": tip,
        "tolls_amount": tolls,
        "improvement_surcharge": np.full(rows, 0.3),
        "total_amount": np.round(fare + extra + 0.5 + tip + tolls + 0.3, 2),
    })
    return out


# -- zones -----------------------------------------------------------------


def _split_cells(cfg: dict, n_cells: int) -> list:
    """``n_cells`` float32 rectangles [x0, y0, x1, y1] tiling the extent:
    the cell with the largest sqrt(pickup share x area share) is split
    across its longer side (in metres), halfway between its middle and
    its pickups' median."""
    sample = points(cfg, int(cfg["layout_seed"]), 1 << 17)
    px = sample["lon"].astype(np.float64)
    py = sample["lat"].astype(np.float64)
    ext = [float(v) for v in extent(cfg)]
    area = (ext[2] - ext[0]) * (ext[3] - ext[1])
    cells = [(ext, np.arange(len(px)))]

    def weight(c):
        (x0, y0, x1, y1), idx = c
        return math.sqrt((len(idx) + 1) / len(px)
                         * (x1 - x0) * (y1 - y0) / area)

    while len(cells) < n_cells:
        k = max(range(len(cells)), key=lambda i: weight(cells[i]))
        (x0, y0, x1, y1), idx = cells.pop(k)
        wide = (x1 - x0) * 0.76 >= (y1 - y0)
        lo, hi = (x0, x1) if wide else (y0, y1)
        v = px[idx] if wide else py[idx]
        mid = 0.5 * (lo + hi)
        at = 0.5 * (mid + (float(np.median(v)) if len(v) else mid))
        at = float(np.float32(min(max(at, lo + 0.25 * (hi - lo)),
                                  hi - 0.25 * (hi - lo))))
        below = v < at
        if wide:
            parts = [((x0, y0, at, y1), idx[below]),
                     ((at, y0, x1, y1), idx[~below])]
        else:
            parts = [((x0, y0, x1, at), idx[below]),
                     ((x0, at, x1, y1), idx[~below])]
        cells += parts
    return [np.asarray(c[0], np.float64) for c in cells]


def _sides(cells: list):
    """Every cell's boundary as atomic segments: the pieces of its four
    sides between the corners (its own and its neighbours') that lie on
    them. Returns ({segment: [cells]}, [[(segment, forward)] per cell in
    counter-clockwise order]); a segment is ((x, y), (x, y)) with its
    ends in ascending order."""
    corners = {(c[i], c[j]) for c in cells for i in (0, 2) for j in (1, 3)}
    by_y: dict = {}
    by_x: dict = {}
    for x, y in corners:
        by_y.setdefault(y, []).append(x)
        by_x.setdefault(x, []).append(y)
    by_y = {k: np.sort(v) for k, v in by_y.items()}
    by_x = {k: np.sort(v) for k, v in by_x.items()}

    def along(line: dict, c, a, b):
        v = line[c]
        return v[(v >= a) & (v <= b)]

    owners: dict = {}
    rings = []
    for ci, (x0, y0, x1, y1) in enumerate(cells):
        ring = []
        xs = along(by_y, y0, x0, x1)  # bottom, west to east
        ring += [(((a, y0), (b, y0)), True) for a, b in zip(xs[:-1], xs[1:])]
        ys = along(by_x, x1, y0, y1)  # east side, south to north
        ring += [(((x1, a), (x1, b)), True) for a, b in zip(ys[:-1], ys[1:])]
        xs = along(by_y, y1, x0, x1)[::-1]  # top, east to west
        ring += [(((b, y1), (a, y1)), False) for a, b in zip(xs[:-1], xs[1:])]
        ys = along(by_x, x0, y0, y1)[::-1]  # west side, north to south
        ring += [(((x0, b), (x0, a)), False) for a, b in zip(ys[:-1], ys[1:])]
        for seg, _ in ring:
            owners.setdefault(seg, []).append(ci)
        rings.append(ring)
    return owners, rings


def _fit_vertices(cfg, owners: dict, rings: list, zone_of: list,
                  targets: np.ndarray) -> dict:
    """Interior vertices per segment so that each zone's edge count
    comes near its target: multiplicative steps on the segments a zone
    holds, each segment pulled by the zones on its two sides, capped by
    the minimum vertex spacing."""
    gap = float(cfg["min_vertex_spacing_deg"])
    segs = list(owners)
    cap = np.array([max(0, int(math.dist(*s) / gap) - 1) for s in segs])
    zones_of = [[zone_of[c] for c in owners[s]] for s in segs]
    pos = {s: i for i, s in enumerate(segs)}
    zone_segs: list = [[] for _ in range(len(targets))]
    for ci, ring in enumerate(rings):
        zone_segs[zone_of[ci]] += [pos[s] for s, _ in ring]
    n = np.zeros(len(segs))
    for _ in range(60):
        edges = np.array([np.sum(n[z] + 1) for z in zone_segs])
        ratio = targets / edges
        f = np.array([math.exp(np.mean(np.log(ratio[z]))) for z in zones_of])
        n = np.clip((n + 1) * f - 1, 0, cap)
    return {s: int(round(v)) for s, v in zip(segs, n)}


def _chain(seg, n: int, outer: bool, room: float, rng) -> np.ndarray:
    """The ``n`` interior vertices of a segment, from its first end to
    its second: evenly spaced along it, offset across it by a smooth
    noise that tapers to 0 at both ends (its amplitude at most ``room``);
    the extent's own sides stay straight."""
    (ax, ay), (bx, by) = seg
    t = np.arange(1, n + 1) / (n + 1)
    k = np.arange(1, 4)[:, None]
    c = rng.uniform(-1.0, 1.0, (3, 1))
    phase = rng.uniform(0.0, 2.0 * math.pi, (3, 1))
    g = np.sum(c * np.sin(2.0 * math.pi * k * t + phase), axis=0) / np.sum(
        np.abs(c))
    off = 0.0 if outer else room * np.sin(math.pi * t) * g
    if ay == by:
        pts = np.stack([ax + t * (bx - ax), ay + off + 0.0 * t], axis=1)
    else:
        pts = np.stack([ax + off + 0.0 * t, ay + t * (by - ay)], axis=1)
    return pts.astype(np.float32).astype(np.float64)


_ZONES: dict = {}


def shapes(cfg: dict) -> list:
    """The configuration's zones, fixed by ``layout_seed``: a list of
    ``{"wkt", "parts", "edges"}``, ``parts`` a list of parts, each a list
    of closed rings of float32-representable (x, y) float64 vertices."""
    key = (int(cfg["layout_seed"]), int(cfg["zones"]), tuple(cfg["extent"]))
    if key not in _ZONES:
        _ZONES[key] = _make_zones(cfg)
    return _ZONES[key]


def _make_zones(cfg: dict) -> list:
    r = _layout_rng(cfg, 2)
    n_zones = int(cfg["zones"])
    multi = r.permutation(n_zones)[: int(round(n_zones * float(
        cfg["multipart_share"])))]
    extra = {int(z): int(r.integers(1, 3)) for z in multi}
    cells = _split_cells(cfg, n_zones + sum(extra.values()))
    owners, rings = _sides(cells)
    # a multipart zone takes cells that are not its neighbours
    order = [int(i) for i in r.permutation(len(cells))]
    zone_of = [-1] * len(cells)
    touching: dict = {}
    for cs in owners.values():
        for a in cs:
            touching.setdefault(a, set()).update(cs)
    for z in range(n_zones):
        zone_of[order[z]] = z
    spare = order[n_zones:]
    for z, k in extra.items():
        mine = [c for c in range(len(cells)) if zone_of[c] == z]
        for _ in range(k):
            c = next(c for c in spare
                     if not any(m in touching[c] for m in mine))
            spare.remove(c)
            zone_of[c] = z
            mine.append(c)
    lo, hi = cfg["zone_edges"]
    targets = np.exp(r.uniform(math.log(lo), math.log(hi), n_zones))
    counts = _fit_vertices(cfg, owners, rings, zone_of, targets)
    chains = {}
    for seg, cs in owners.items():
        (ax, ay), (bx, by) = seg
        if ay == by:  # room across: the heights of the cells beside it
            room = min([cells[c][3] - cells[c][1] for c in cs] + [bx - ax])
        else:
            room = min([cells[c][2] - cells[c][0] for c in cs] + [by - ay])
        chains[seg] = _chain(seg, counts[seg], len(cs) == 1, 0.15 * room, r)
    parts: list = [[] for _ in range(n_zones)]
    for ci, ring in enumerate(rings):
        pts = []
        for seg, fwd in ring:
            ch = chains[seg]
            start = seg[0] if fwd else seg[1]
            pts.append(np.asarray([start], np.float64))
            pts.append(ch if fwd else ch[::-1])
        pts.append(pts[0])
        parts[zone_of[ci]].append([np.concatenate(pts)])
    return [{"parts": p, "wkt": _wkt(p),
             "edges": sum(len(r) - 1 for rings in p for r in rings)}
            for p in parts]


def _num(v: float) -> str:
    """The shortest text that parses back to exactly ``v``."""
    return repr(float(v))


def _wkt(parts: list) -> str:
    def ring(r):
        return "(" + ", ".join(f"{_num(x)} {_num(y)}" for x, y in r) + ")"

    polys = ["(" + ", ".join(ring(r) for r in rings) + ")" for rings in parts]
    if len(polys) == 1:
        return "POLYGON " + polys[0]
    return "MULTIPOLYGON (" + ", ".join(polys) + ")"
