"""k-nearest-neighbors by iterative expanding-window search.

(ref: geomesa-process .../knn/KNearestNeighborSearchProcess + KNNQuery's
expanding-window algorithm [UNVERIFIED - empty reference mount]): query a
small bbox around the target; if fewer than k hits, grow the window and
retry; finish with a confidence pass at the k-th distance radius so no
closer neighbor outside the last window is missed.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.filter import ast
from geomesa_tpu.query.plan import internal_query


def _dist_deg(x, y, px, py):
    """Equirectangular-approx distance in degrees (lat-corrected lon)."""
    dx = (x - px) * np.cos(np.radians(py))
    dy = y - py
    return np.sqrt(dx * dx + dy * dy)


def _k_nearest(batch, geom: str, px: float, py: float, k: int):
    """(top-k batch, distances) of one candidate batch, nearest first."""
    if len(batch) == 0:
        return batch, np.array([])
    x, y = batch.point_coords(geom)
    d = _dist_deg(x, y, px, py)
    order = np.argsort(d, kind="stable")[:k]
    return batch.take(order), d[order]


def knn(
    store,
    type_name: str,
    px: float,
    py: float,
    k: int,
    base_filter: "ast.Filter | str | None" = None,
    initial_radius_deg: float = 0.05,
    max_radius_deg: float = 45.0,
    device_index=None,
    auths=None,
):
    """Returns (batch_of_k_nearest, distances_deg), nearest first.

    If fewer than k features exist inside the ``max_radius_deg`` box
    around the target, only those are returned — the search never widens
    past that box, so a sparse region costs one max-radius scan instead
    of an unbounded base-filter scan.

    With a resident ``device_index`` each expanding-window probe is one
    fused device scan over the pinned columns (no per-query column
    staging — the store path re-uploads the scan planes on every window,
    which dominates the search's wall clock); ``auths`` applies row
    security on BOTH paths (absent = none, fail closed)."""
    from geomesa_tpu.filter.ecql import parse_ecql

    base = (
        parse_ecql(base_filter)
        if isinstance(base_filter, str)
        else (base_filter or ast.Include)
    )
    sft = store.get_schema(type_name)
    geom = sft.geom_field

    if device_index is not None:
        # TPU-native path: a fully resident cache answers kNN in ONE
        # fused dispatch (distance + mask + lax.top_k) — the expanding
        # windows below exist for the STORE path, where each probe pays
        # a column (re)staging
        got = device_index.knn(
            px, py, k,
            query=None if base is ast.Include else base,
            auths=auths,
            max_radius_deg=max_radius_deg,
        )
        if got is not None:
            return got

    def window(rx: float, ry: float):
        if device_index is not None and base is ast.Include:
            # runtime-bounds kernel: ONE compile serves every window of
            # the expanding search (per-filter compile would dominate)
            got = device_index.bbox_window_query(
                px - rx, py - ry, px + rx, py + ry, auths=auths
            )
            if got is not None:
                return got
        f = ast.And((ast.BBox(geom, px - rx, py - ry, px + rx, py + ry), base))
        if device_index is not None:
            return device_index.query(f, auths=auths)
        return store.query(type_name, internal_query(f, auths=auths)).batch

    r = initial_radius_deg
    batch = None
    last_r = None  # radius of the last window actually scanned
    while r <= max_radius_deg:
        res = window(r, r)
        last_r = r
        if len(res) >= k:
            batch = res
            break
        r *= 2
    if batch is None:
        # The expanding window exhausted max_radius_deg without reaching k
        # hits. One final pass at exactly the max radius (skipped when the
        # loop already scanned that box) and we are done: fewer than k
        # features exist in the search area, and a confidence pass capped
        # at the same radius could only re-scan a subset of this box.
        if last_r != max_radius_deg:
            res = window(max_radius_deg, max_radius_deg)
        return _k_nearest(res, geom, px, py, k)
    _, d = _k_nearest(batch, geom, px, py, k)
    kth = float(d[-1]) if len(d) else 0.0
    # confidence pass: any point with corrected distance <= kth lies inside
    # the raw-degree box of half-extents (kth/cos(lat), kth) around the
    # target -- the k-th circle can poke outside the search window, and the
    # window's lon extent under-covers because the metric shrinks lon.
    # ... but never wider than max_radius_deg: points beyond the cap are
    # outside the search contract, and near the poles rx could otherwise
    # blow up to 100x kth
    rx = min(kth / max(np.cos(np.radians(py)), 0.01), max_radius_deg)
    return _k_nearest(window(rx, min(kth, max_radius_deg)), geom, px, py, k)
