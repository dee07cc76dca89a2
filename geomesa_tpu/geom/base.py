"""Geometry value types: immutable, numpy-backed coordinate arrays."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Sequence

import numpy as np


@dataclass(frozen=True)
class Envelope:
    """Axis-aligned bounding box (inclusive)."""

    xmin: float
    ymin: float
    xmax: float
    ymax: float

    def intersects(self, other: "Envelope") -> bool:
        return not (
            other.xmin > self.xmax
            or other.xmax < self.xmin
            or other.ymin > self.ymax
            or other.ymax < self.ymin
        )

    def contains_env(self, other: "Envelope") -> bool:
        return (
            self.xmin <= other.xmin
            and self.xmax >= other.xmax
            and self.ymin <= other.ymin
            and self.ymax >= other.ymax
        )

    def intersection(self, other: "Envelope") -> "Envelope | None":
        xmin, xmax = max(self.xmin, other.xmin), min(self.xmax, other.xmax)
        ymin, ymax = max(self.ymin, other.ymin), min(self.ymax, other.ymax)
        if xmin > xmax or ymin > ymax:
            return None
        return Envelope(xmin, ymin, xmax, ymax)

    def expand(self, other: "Envelope") -> "Envelope":
        return Envelope(
            min(self.xmin, other.xmin),
            min(self.ymin, other.ymin),
            max(self.xmax, other.xmax),
            max(self.ymax, other.ymax),
        )

    @staticmethod
    def world() -> "Envelope":
        return Envelope(-180.0, -90.0, 180.0, 90.0)


class Geometry:
    """Base class; subclasses expose ``envelope`` and coordinate arrays."""

    @property
    def envelope(self) -> Envelope:  # pragma: no cover - abstract
        raise NotImplementedError


@dataclass(frozen=True)
class Point(Geometry):
    x: float
    y: float

    @property
    def envelope(self) -> Envelope:
        return Envelope(self.x, self.y, self.x, self.y)


def _coords_array(coords) -> np.ndarray:
    a = np.asarray(coords, dtype=np.float64)
    if a.ndim != 2 or a.shape[1] != 2:
        raise ValueError(f"expected (n, 2) coordinates, got {a.shape}")
    return a


def _coords_repr(a: np.ndarray) -> str:
    """Every coordinate, exactly: the resident caches key compiled
    filters on ``repr``, and numpy's repr rounds and elides."""
    return repr(a.tolist())


@dataclass(frozen=True)
class LineString(Geometry):
    coords: np.ndarray  # (n, 2)

    def __post_init__(self):
        object.__setattr__(self, "coords", _coords_array(self.coords))

    def __repr__(self):
        return f"LineString({_coords_repr(self.coords)})"

    @property
    def envelope(self) -> Envelope:
        c = self.coords
        return Envelope(c[:, 0].min(), c[:, 1].min(), c[:, 0].max(), c[:, 1].max())


@dataclass(frozen=True)
class Polygon(Geometry):
    """Exterior shell plus optional interior rings (holes). Rings are closed
    (first == last coordinate) per WKT convention."""

    shell: np.ndarray  # (n, 2)
    holes: tuple = ()

    def __post_init__(self):
        object.__setattr__(self, "shell", _coords_array(self.shell))
        object.__setattr__(
            self, "holes", tuple(_coords_array(h) for h in self.holes)
        )

    def __repr__(self):
        holes = ", ".join(_coords_repr(h) for h in self.holes)
        return f"Polygon({_coords_repr(self.shell)}, holes=({holes}))"

    @property
    def envelope(self) -> Envelope:
        c = self.shell
        return Envelope(c[:, 0].min(), c[:, 1].min(), c[:, 0].max(), c[:, 1].max())

    def rings(self):
        return (self.shell, *self.holes)


@dataclass(frozen=True)
class MultiPoint(Geometry):
    points: tuple

    @property
    def envelope(self) -> Envelope:
        e = self.points[0].envelope
        for p in self.points[1:]:
            e = e.expand(p.envelope)
        return e


@dataclass(frozen=True)
class MultiLineString(Geometry):
    lines: tuple

    @property
    def envelope(self) -> Envelope:
        e = self.lines[0].envelope
        for l in self.lines[1:]:
            e = e.expand(l.envelope)
        return e


@dataclass(frozen=True)
class MultiPolygon(Geometry):
    polygons: tuple

    @property
    def envelope(self) -> Envelope:
        e = self.polygons[0].envelope
        for p in self.polygons[1:]:
            e = e.expand(p.envelope)
        return e

    def rings(self):
        out = []
        for p in self.polygons:
            out.extend(p.rings())
        return tuple(out)
