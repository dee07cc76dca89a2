"""Minimal WKT reader/writer for the geometry subset.

Supports POINT, LINESTRING, POLYGON, MULTIPOINT, MULTILINESTRING,
MULTIPOLYGON and GeoTools' ENVELOPE(x1, x2, y1, y2) extension (note the
GeoTools argument order: xmin, xmax, ymin, ymax -- used by CQL BBOX
literals). (ref: geomesa-utils .../text/WKTUtils [UNVERIFIED].)
"""

from __future__ import annotations

import re

import numpy as np

from geomesa_tpu.geom.base import (
    Envelope,
    Geometry,
    LineString,
    MultiLineString,
    MultiPoint,
    MultiPolygon,
    Point,
    Polygon,
)

_TOKEN = re.compile(r"\s*([A-Za-z]+|\(|\)|,|-?\d+\.?\d*(?:[eE][-+]?\d+)?)")


class _Tokens:
    def __init__(self, s: str):
        self.toks = _TOKEN.findall(s)
        self.i = 0

    def peek(self):
        return self.toks[self.i] if self.i < len(self.toks) else None

    def next(self):
        t = self.peek()
        if t is None:
            raise ValueError("unexpected end of WKT")
        self.i += 1
        return t

    def expect(self, t):
        got = self.next()
        if got != t:
            raise ValueError(f"expected {t!r}, got {got!r}")


def _number(tk: _Tokens) -> float:
    return float(tk.next())


def _coord_seq(tk: _Tokens) -> np.ndarray:
    """``(x y, x y, ...)`` as an (n, 2) array, read a sequence at a time:
    the tokens up to the ``)`` must be numbers in pairs between commas."""
    tk.expect("(")
    toks, i = tk.toks, tk.i
    try:
        j = toks.index(")", i)
    except ValueError:
        raise ValueError("unexpected end of WKT") from None
    seq = toks[i:j]
    if len(seq) % 3 != 2 or seq[2::3].count(",") != len(seq) // 3:
        raise ValueError(f"bad coordinate sequence {' '.join(seq[:9])!r}")
    tk.i = j + 1
    return np.stack([np.array(seq[0::3], np.float64),
                     np.array(seq[1::3], np.float64)], axis=1)


def _rings(tk: _Tokens) -> list[np.ndarray]:
    tk.expect("(")
    rings = [_coord_seq(tk)]
    while tk.peek() == ",":
        tk.next()
        rings.append(_coord_seq(tk))
    tk.expect(")")
    return rings


def parse_wkt(s: str) -> Geometry | Envelope:
    tk = _Tokens(s)
    tag = tk.next().upper()
    if tag == "POINT":
        c = _coord_seq(tk)
        return Point(float(c[0, 0]), float(c[0, 1]))
    if tag == "LINESTRING":
        return LineString(_coord_seq(tk))
    if tag == "POLYGON":
        rings = _rings(tk)
        return Polygon(rings[0], tuple(rings[1:]))
    if tag == "MULTIPOINT":
        # both MULTIPOINT(1 2, 3 4) and MULTIPOINT((1 2), (3 4)) appear
        tk.expect("(")
        pts = []
        while True:
            if tk.peek() == "(":
                c = _coord_seq(tk)
                pts.append(Point(float(c[0, 0]), float(c[0, 1])))
            else:
                pts.append(Point(_number(tk), _number(tk)))
            t = tk.next()
            if t == ")":
                break
            if t != ",":
                raise ValueError(f"bad separator {t!r}")
        return MultiPoint(tuple(pts))
    if tag == "MULTILINESTRING":
        return MultiLineString(tuple(LineString(r) for r in _rings(tk)))
    if tag == "MULTIPOLYGON":
        tk.expect("(")
        polys = [Polygon(r[0], tuple(r[1:])) for r in [_rings(tk)]]
        while tk.peek() == ",":
            tk.next()
            r = _rings(tk)
            polys.append(Polygon(r[0], tuple(r[1:])))
        tk.expect(")")
        return MultiPolygon(tuple(polys))
    if tag == "ENVELOPE":
        tk.expect("(")
        x1 = _number(tk)
        tk.expect(",")
        x2 = _number(tk)
        tk.expect(",")
        y1 = _number(tk)
        tk.expect(",")
        y2 = _number(tk)
        tk.expect(")")
        return Envelope(x1, y1, x2, y2)
    raise ValueError(f"unsupported WKT type {tag!r}")


def _fmt(v: float) -> str:
    return f"{v:.10g}"


def _seq_wkt(coords: np.ndarray) -> str:
    return "(" + ", ".join(f"{_fmt(x)} {_fmt(y)}" for x, y in coords) + ")"


def to_wkt(g) -> str:
    if isinstance(g, Point):
        return f"POINT ({_fmt(g.x)} {_fmt(g.y)})"
    if isinstance(g, LineString):
        return "LINESTRING " + _seq_wkt(g.coords)
    if isinstance(g, Polygon):
        return "POLYGON (" + ", ".join(_seq_wkt(r) for r in g.rings()) + ")"
    if isinstance(g, MultiPoint):
        return "MULTIPOINT (" + ", ".join(
            f"({_fmt(p.x)} {_fmt(p.y)})" for p in g.points
        ) + ")"
    if isinstance(g, MultiLineString):
        return "MULTILINESTRING (" + ", ".join(_seq_wkt(l.coords) for l in g.lines) + ")"
    if isinstance(g, MultiPolygon):
        return "MULTIPOLYGON (" + ", ".join(
            "(" + ", ".join(_seq_wkt(r) for r in p.rings()) + ")" for p in g.polygons
        ) + ")"
    if isinstance(g, Envelope):
        return (
            f"ENVELOPE ({_fmt(g.xmin)}, {_fmt(g.xmax)}, {_fmt(g.ymin)}, {_fmt(g.ymax)})"
        )
    raise TypeError(f"cannot write WKT for {type(g)}")
