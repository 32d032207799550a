"""The historical per-cuboid isoperimetry loops, kept as the property-test
oracle for the vectorized ``repro.network.isoperimetry`` engine.

This is the pre-vectorization implementation (one Python loop over
``sub_cuboids``, one ``cuboid_cut`` call per geometry) with the PR-5
semantics applied so engine == oracle can be asserted exactly:

* the Theorem 3.1 bound uses complement symmetry for ``t > n/2``
  (``cut(S) == cut(S̄)``, so the bound at ``n - t`` applies — the old code
  set ``bound = cut`` there, making tightness vacuous);
* ``optimal``/``worst`` validation is aligned (``ValueError`` outside
  ``(0, n]`` for both);
* ties break deterministically: the lexicographically-*smallest*
  canonical geometry among the min cuts, the *largest* among the max —
  the same tie-breaks as ``repro.core.bgq``'s best/worst partitions.

``benchmarks/bench_isoperimetry.py`` times these loops against the engine.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

from repro.network.geometry import (
    Geometry,
    bisection_links,
    canonical,
    cuboid_cut,
    cuboid_interior,
    sub_cuboids,
    theorem31_bound,
    volume,
)


def _ref_dims(torus_or_dims) -> Geometry:
    return canonical(getattr(torus_or_dims, "dims", torus_or_dims))


def reference_cut_table(torus_or_dims, t: int) -> List[Tuple[Geometry, int]]:
    """(geometry, cut) for every fitting cuboid of volume t, lexicographically
    ascending — the per-cuboid counterpart of ``cut_table(...).items()``."""
    a = _ref_dims(torus_or_dims)
    return sorted((c, cuboid_cut(a, c)) for c in sub_cuboids(a, t))


def _subset_bound(a: Geometry, n: int, t: int) -> float:
    return theorem31_bound(a, min(t, n - t))


def reference_optimal_cuboid(
    torus_or_dims, t: int
) -> Optional[Tuple[Geometry, int, float]]:
    """(geometry, cut, bound) of the min-cut cuboid, or None if none fits."""
    a = _ref_dims(torus_or_dims)
    n = volume(a)
    if t <= 0 or t > n:
        raise ValueError(f"t must be in (0, {n}], got {t}")
    best_geom, best_cut = None, None
    for c in sorted(sub_cuboids(a, t)):
        cut = cuboid_cut(a, c)
        if best_cut is None or cut < best_cut:
            best_geom, best_cut = c, cut
    if best_geom is None:
        return None
    return best_geom, best_cut, _subset_bound(a, n, t)


def reference_worst_cuboid(
    torus_or_dims, t: int
) -> Optional[Tuple[Geometry, int, float]]:
    """(geometry, cut, bound) of the max-cut cuboid, or None if none fits."""
    a = _ref_dims(torus_or_dims)
    n = volume(a)
    if t <= 0 or t > n:
        raise ValueError(f"t must be in (0, {n}], got {t}")
    worst_geom, worst_cut = None, None
    for c in sorted(sub_cuboids(a, t)):
        cut = cuboid_cut(a, c)
        if worst_cut is None or cut >= worst_cut:
            worst_geom, worst_cut = c, cut
    if worst_geom is None:
        return None
    return worst_geom, worst_cut, _subset_bound(a, n, t)


def reference_small_set_expansion(torus_or_dims, t: int) -> float:
    """h_t over cuboid witnesses by the full double loop (sizes x cuboids),
    computing the interior explicitly per cuboid."""
    a = _ref_dims(torus_or_dims)
    best = math.inf
    for size in range(1, t + 1):
        for c in sub_cuboids(a, size):
            cut = cuboid_cut(a, c)
            interior = cuboid_interior(a, c)
            denom = interior + cut
            if denom == 0:
                continue
            best = min(best, cut / denom)
    return best


def _scaled_node_dims(
    geometry: Geometry, unit_node_dims: Optional[Sequence[int]]
) -> Geometry:
    if unit_node_dims is None:
        return geometry
    unit = tuple(int(u) for u in unit_node_dims)
    scaled = tuple(g * u for g, u in zip(geometry, unit[: len(geometry)]))
    return canonical(scaled + unit[len(geometry):])


def reference_bisection_table(
    torus_or_dims, units: int, unit_node_dims: Optional[Sequence[int]] = None
) -> List[Tuple[Geometry, int]]:
    """(geometry, internal bisection links) per fitting geometry of a size,
    lexicographically ascending, via one ``bisection_links`` call each."""
    a = _ref_dims(torus_or_dims)
    return sorted(
        (c, bisection_links(_scaled_node_dims(c, unit_node_dims)))
        for c in sub_cuboids(a, units)
    )
