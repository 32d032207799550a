"""Vectorized edge-isoperimetric analysis of torus graphs (paper Section 3).

This is the engine behind the paper's central tool — certifying whether a
partition geometry has optimal internal bisection — promoted from the
historical per-cuboid Python loops (kept as the property-test oracle in
``tests/reference_isoperimetry.py``) to one batched NumPy pass:

* ``cut_table``             — every cuboid geometry of a given volume that
  fits a torus, with its exact minimum cut, from a single divisor-meshgrid
  enumeration (no per-cuboid loop, no per-permutation loop).
* ``bollobas_leader_bound`` — Theorem 2.1 (cubic tori, Bollobás & Leader).
* ``theorem31_bound``       — Theorem 3.1, the paper's generalisation to
  arbitrary dimension sizes (re-exported from `repro.network.geometry`).
* ``lemma32_cut``           — the explicit optimal-cuboid construction S_r
  of Lemma 3.2 and its exact cut size.
* ``optimal_cuboid`` / ``worst_cuboid`` — exact min-/max-cut cuboids with a
  Theorem 3.1 tightness certificate.  For ``t > n/2`` the bound uses
  complement symmetry (``cut(S) == cut(S̄)``, so the Theorem 3.1 bound at
  ``n - t`` applies) — the historical code set ``bound = cut`` there,
  making ``CuboidOptimum.tight`` vacuously True.
* ``small_set_expansion``   — h_t(G) over cuboid witnesses via the
  regularity identity (Eq. 1), so only the batched min-cuts are needed.
* ``bisection_table`` / ``ranked_geometries`` / ``best_bisection_geometry``
  / ``worst_bisection_geometry`` — internal bisection of every same-volume
  geometry (node-level when ``unit_node_dims`` is given, the paper's
  tables), backing the allocation policies' preference ranking.
* ``is_isoperimetrically_optimal`` / ``advise_partition`` /
  ``advise_policy_table`` — the partition advisor: rank an allocation
  policy's admissible geometries by internal bisection, certify the
  optimum with Theorem 3.1, predict the contention-bound speedup of
  switching (paper Tables 4-6) and optionally cross-check it against the
  flow-level simulator (:mod:`repro.network.netsim`).

All cut sizes are in links with unit capacity ("normalized bisection
bandwidth"), under the fully-wrapped Blue Gene/Q double-link convention of
:mod:`repro.network.geometry`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .backend import resolve_backend
from .fabric import as_fabric
from .geometry import (
    Geometry,
    canonical,
    cuboid_cut,
    theorem31_bound,
    volume,
)

__all__ = [
    "BisectionTable",
    "CuboidOptimum",
    "CutTable",
    "PartitionAdvice",
    "advise_partition",
    "advise_policy_table",
    "best_bisection_geometry",
    "bisection_of_geometry",
    "bisection_table",
    "bollobas_leader_bound",
    "cut_table",
    "fitting_geometries",
    "is_isoperimetrically_optimal",
    "lemma32_cut",
    "optimal_cuboid",
    "ranked_geometries",
    "small_set_expansion",
    "theorem31_bound",
    "worst_bisection_geometry",
    "worst_cuboid",
]


def _divisors(t: int, cap: Optional[int] = None) -> np.ndarray:
    """Divisors of t, optionally only those <= cap (a side can never exceed
    the longest torus dimension, so the enumeration caps there)."""
    hi = t if cap is None else min(t, cap)
    d = np.arange(1, hi + 1, dtype=np.int64)
    return d[t % d == 0]


def _aligned_assignments(a: Geometry, t: int) -> np.ndarray:
    """All aligned side assignments of volume t into torus dims ``a``.

    Row k is ``(s_1, ..., s_D)`` with ``s_i | t``, ``s_i <= a_i`` and
    ``prod s_i == t`` — every feasible embedding of every fitting cuboid
    geometry, built dimension by dimension as a pruned divisor meshgrid
    (each step crosses the surviving partial assignments with the divisor
    list, keeping rows whose remaining volume divides out and still fits
    in the remaining dimensions).  Empty (shape (0, D)) when nothing fits.
    """
    D = len(a)
    divs = _divisors(t, cap=max(a, default=0))
    suffix = [1] * (D + 1)  # suffix[i] = prod(a[i:])
    for i in range(D - 1, -1, -1):
        suffix[i] = suffix[i + 1] * a[i]
    rows = np.zeros((1, 0), dtype=np.int64)
    rem = np.array([t], dtype=np.int64)
    for i, ai in enumerate(a):
        cand = divs[divs <= ai]
        ok = (rem[:, None] % cand[None, :]) == 0
        nrem = rem[:, None] // cand[None, :]
        ok &= nrem <= suffix[i + 1]
        r, c = np.nonzero(ok)
        rows = np.concatenate([rows[r], cand[c][:, None]], axis=1)
        rem = nrem[r, c]
    return rows[rem == 1]


# ---------------------------------------------------------------------------
# The batched cut engine.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CutTable:
    """Every canonical cuboid geometry of volume ``t`` fitting ``dims``,
    with its exact minimum cut (links, double-link convention).

    ``geometries`` is a (G, D) int array of canonical (sorted-descending)
    rows in ascending lexicographic order; ``cuts`` the matching (G,)
    minimum cut per geometry (minimised over all feasible embeddings).
    """

    dims: Geometry
    t: int
    geometries: np.ndarray
    cuts: np.ndarray

    def __len__(self) -> int:
        return len(self.geometries)

    def geometry(self, i: int) -> Geometry:
        """The i-th canonical geometry as a plain tuple."""
        return tuple(int(x) for x in self.geometries[i])

    def items(self) -> List[Tuple[Geometry, int]]:
        """(geometry, cut) pairs in the table's lexicographic row order."""
        return [(self.geometry(i), int(self.cuts[i])) for i in range(len(self))]

    def min_cut_geometry(self) -> Tuple[Geometry, int]:
        """Lexicographically-smallest geometry attaining the minimum cut."""
        i = int(np.nonzero(self.cuts == self.cuts.min())[0][0])
        return self.geometry(i), int(self.cuts[i])

    def max_cut_geometry(self) -> Tuple[Geometry, int]:
        """Lexicographically-largest geometry attaining the maximum cut."""
        i = int(np.nonzero(self.cuts == self.cuts.max())[0][-1])
        return self.geometry(i), int(self.cuts[i])


def cut_table(torus_or_dims, t: int, backend: Optional[str] = None) -> CutTable:
    """Batched exact cuts of *all* cuboid geometries of volume ``t``.

    One divisor-meshgrid enumeration of every aligned embedding, one
    vectorized closed-form cut evaluation, one group-by-canonical-geometry
    minimisation — no per-cuboid Python loop.  An aligned box's cut is the
    sum over dimensions of ``t / s_k`` times the cut of its side in that
    dimension's line (:meth:`~repro.network.fabric.Fabric.line_cuts`): on
    a torus a side ``s`` in dimension ``a`` contributes ``0`` if
    ``s == a`` else ``2 t / s`` — equal to
    :func:`repro.network.geometry.cuboid_cut` exactly (property-pinned
    against the reference oracle); on a
    :class:`~repro.network.fabric.HyperXFabric` it contributes
    ``t K_k (S_k - s)`` (:func:`repro.network.hamming.hamming_cut_aligned`).
    ``backend="xla"`` evaluates the sum in the compiled backend (int64
    arithmetic — identical values); the divisor enumeration and group-by
    stay host-side.

    >>> from .fabric import HyperXFabric
    >>> cut_table(HyperXFabric((4, 4)), 4).items()
    [((2, 2), 16), ((4, 1), 12)]

    >>> cut_table((4, 4, 2), 8).items()
    [((2, 2, 2), 16), ((4, 2, 1), 16)]
    """
    if t < 1:
        raise ValueError(f"t must be >= 1, got {t}")
    fab = as_fabric(torus_or_dims).canonical()
    a = fab.dims
    S = _aligned_assignments(a, t)
    if S.shape[0] == 0:
        return CutTable(a, t, S.reshape(0, len(a)), np.zeros(0, dtype=np.int64))
    line = fab.line_cuts(S)
    if resolve_backend(backend) == "xla":
        from .backend import xla_cut_scores

        cuts = xla_cut_scores(S, line, t)
    else:
        cuts = ((t // S) * line).sum(axis=1)
    G = -np.sort(-S, axis=1)  # canonical (descending) rows
    # Group by geometry via a positional integer key (base max(a)+1): a 1-D
    # unique on int64 keys, much cheaper than np.unique(axis=0)'s row-view
    # argsort, with the identical ascending-lexicographic row order.
    base = int(max(a)) + 1
    key = G[:, 0].copy()
    for j in range(1, G.shape[1]):
        key = key * base + G[:, j]
    _, index, inv = np.unique(key, return_index=True, return_inverse=True)
    uniq = G[index]
    best = np.full(len(index), np.iinfo(np.int64).max, dtype=np.int64)
    np.minimum.at(best, inv.ravel(), cuts)
    return CutTable(a, t, uniq, best)


def fitting_geometries(torus_or_dims, units: int) -> np.ndarray:
    """All canonical cuboid geometries of ``units`` vertices that fit, as a
    (G, D) int array in ascending lexicographic row order (the batched
    counterpart of :func:`repro.network.geometry.sub_cuboids`)."""
    return cut_table(torus_or_dims, units).geometries


# ---------------------------------------------------------------------------
# Bounds and constructions (paper Theorems 2.1/3.1, Lemma 3.2).
# ---------------------------------------------------------------------------
def bollobas_leader_bound(n: int, D: int, t: int) -> float:
    """Theorem 2.1: lower bound on |E(S, S̄)| for |S| = t in the cubic torus [n]^D."""
    if t < 0 or t > n**D // 2:
        raise ValueError("t must satisfy 0 <= t <= |V|/2")
    if t == 0:
        return 0.0
    best = math.inf
    for r in range(D):
        val = 2.0 * (D - r) * n ** (r / (D - r)) * t ** ((D - r - 1) / (D - r))
        best = min(best, val)
    return best


# theorem31_bound is implemented once in repro.network.geometry (it also
# backs the odd-dimension bisection fallback there) and re-exported here.


def lemma32_cut(dims: Sequence[int], t: int, r: int) -> Optional[Tuple[Geometry, int]]:
    """Lemma 3.2: the explicit cuboid S_r and its exact cut, if it exists.

    S_r fully covers the r smallest dimensions and is a cube of side
    s = (t / k)^(1/(D-r)) in the remaining D-r dimensions, where k is the
    product of the r smallest dims.  Returns ``None`` when s is not an
    integer or S_r does not fit.
    """
    a = canonical(dims)
    D = len(a)
    if not 0 <= r < D:
        raise ValueError(f"r must be in [0, {D}), got {r}")
    k = math.prod(a[D - r:]) if r > 0 else 1
    if t % k != 0:
        return None
    q = t // k
    s = round(q ** (1.0 / (D - r)))
    if s ** (D - r) != q:
        return None
    if s > min(a[: D - r]):
        return None  # the cube side must fit in each uncovered dimension
    geometry = canonical((s,) * (D - r) + tuple(a[D - r:]))
    return geometry, cuboid_cut(a, geometry)


# ---------------------------------------------------------------------------
# Optimal / worst cuboids with the Theorem 3.1 certificate.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class CuboidOptimum:
    """A min- or max-cut cuboid with its Theorem 3.1 lower bound; ``tight``
    certifies that the cut meets the bound exactly."""

    geometry: Geometry
    cut: int
    bound: float

    @property
    def tight(self) -> bool:
        """Whether the cut achieves the Theorem 3.1 bound (certificate)."""
        return math.isclose(self.cut, self.bound, rel_tol=1e-9)


def optimal_cuboid(torus_or_dims, t: int) -> Optional[CuboidOptimum]:
    """Exact minimum-cut cuboid of size t inside the torus (Lemma 3.3 optimum).

    Accepts any fabric or a plain dims tuple.  Returns ``None`` when no
    cuboid of exactly ``t`` vertices fits; raises ``ValueError`` for t
    outside (0, n].  Ties break toward the lexicographically-smallest
    canonical geometry.  The certificate is the fabric's bound on any
    subset's cut (:meth:`~repro.network.fabric.Fabric.subset_cut_bound`):
    Theorem 3.1 on a torus; on a
    :class:`~repro.network.fabric.HyperXFabric` the Lindsey/edge-identity
    bound of :mod:`repro.network.hamming` (exact under uniform link
    multiplicity, so ``tight`` still certifies against *all* subsets, not
    just boxes):

    >>> opt = optimal_cuboid((4, 4, 2), 8)
    >>> opt.geometry, opt.cut, opt.tight
    ((2, 2, 2), 16, True)
    """
    fab = as_fabric(torus_or_dims).canonical()
    n = fab.num_cells
    if t <= 0 or t > n:
        raise ValueError(f"t must be in (0, {n}], got {t}")
    tbl = cut_table(fab, t)
    if len(tbl) == 0:
        return None
    geom, cut = tbl.min_cut_geometry()
    return CuboidOptimum(geom, cut, fab.subset_cut_bound(t))


def worst_cuboid(torus_or_dims, t: int) -> Optional[CuboidOptimum]:
    """Maximum-cut cuboid of size t — the adversarial partition geometry.

    Validation matches :func:`optimal_cuboid` (``ValueError`` outside
    (0, n]; the historical version silently returned ``None``), and the
    bound uses complement symmetry for t > n/2, so ``tight`` is a real
    certificate instead of being vacuously True there.
    """
    fab = as_fabric(torus_or_dims).canonical()
    n = fab.num_cells
    if t <= 0 or t > n:
        raise ValueError(f"t must be in (0, {n}], got {t}")
    tbl = cut_table(fab, t)
    if len(tbl) == 0:
        return None
    geom, cut = tbl.max_cut_geometry()
    return CuboidOptimum(geom, cut, fab.subset_cut_bound(t))


def small_set_expansion(torus_or_dims, t: int) -> float:
    """h_t(G) over cuboid witnesses: min_{|A|<=t} cut(A) / (interior(A)+cut(A)).

    By the regularity identity (Eq. 1), interior(A) = (k|A| - cut(A)) / 2,
    so the witness expansion 2·cut / (k|A| + cut) is monotone in the cut and
    only the batched per-size *minimum* cuts are needed — the historical
    version walked every cuboid of every size.
    """
    fab = as_fabric(torus_or_dims).canonical()
    if t < 1:
        raise ValueError(f"t must be >= 1, got {t}")
    k = fab.degree
    best = math.inf
    for size in range(1, t + 1):
        tbl = cut_table(fab, size)
        if len(tbl) == 0:
            continue
        cut = int(tbl.cuts.min())
        denom = k * size + cut
        if denom == 0:
            continue
        best = min(best, 2.0 * cut / denom)
    return best


# ---------------------------------------------------------------------------
# Internal bisection of same-volume geometries (the allocator's ranking).
# ---------------------------------------------------------------------------
def bisection_of_geometry(dims: Sequence[int]) -> int:
    """Internal bisection (links) of a fully-wrapped torus partition with the
    given dims — engine-backed, exactly equal to
    :func:`repro.network.geometry.bisection_links` (property-pinned)."""
    a = canonical(dims)
    n = volume(a)
    if n == 1:
        return 0
    L = a[0]
    if L % 2 == 0:
        return 2 * n // L
    if L == 1:
        return 0
    tbl = cut_table(a, n // 2)
    if len(tbl) == 0:
        # No cuboid of size exactly floor(n/2); analytic fallback, matching
        # geometry.bisection_links.
        return math.ceil(theorem31_bound(a, n // 2))
    return int(tbl.cuts.min())


@dataclass(frozen=True)
class BisectionTable:
    """Internal bisection of every cuboid geometry of one volume fitting a
    machine torus — the quantity the paper's allocation policies rank by.

    ``geometries`` is the (G, D) canonical row array of
    :func:`fitting_geometries`; ``bisections`` the matching internal
    bisection in links of each geometry as its own fully-wrapped torus,
    evaluated at node level when the table was built with
    ``unit_node_dims`` (the paper's Tables 4-7 convention).
    """

    dims: Geometry
    units: int
    geometries: np.ndarray
    bisections: np.ndarray
    unit_node_dims: Optional[Geometry] = None

    def __len__(self) -> int:
        return len(self.geometries)

    def _geometry(self, i: int) -> Geometry:
        return tuple(int(x) for x in self.geometries[i])

    def best(self) -> Tuple[Geometry, int]:
        """Max-bisection geometry (lexicographically smallest on ties —
        the :meth:`repro.core.bgq.BlueGeneQ.best_partition` tie-break)."""
        i = int(np.nonzero(self.bisections == self.bisections.max())[0][0])
        return self._geometry(i), int(self.bisections[i])

    def worst(self) -> Tuple[Geometry, int]:
        """Min-bisection geometry (lexicographically largest on ties —
        the adversarial baseline)."""
        i = int(np.nonzero(self.bisections == self.bisections.min())[0][-1])
        return self._geometry(i), int(self.bisections[i])

    def bisection_of(self, geometry: Sequence[int]) -> int:
        """Bisection of one geometry in the table; ValueError if absent.
        Unit dims are normalised away, so ``(2, 2, 1)`` on a 2-D machine
        matches the ``(2, 2)`` row."""
        g = tuple(x for x in canonical(geometry) if x > 1)
        if len(g) > len(self.dims):
            raise ValueError(
                f"geometry {tuple(geometry)} is not a fitting {self.units}-unit "
                f"cuboid of {self.dims}"
            )
        row = np.array(g + (1,) * (len(self.dims) - len(g)), dtype=np.int64)
        hits = np.nonzero((self.geometries == row[None, :]).all(axis=1))[0]
        if len(hits) == 0:
            raise ValueError(
                f"geometry {tuple(geometry)} is not a fitting {self.units}-unit "
                f"cuboid of {self.dims}"
            )
        return int(self.bisections[hits[0]])

    def ranked(self) -> List[Tuple[Geometry, int]]:
        """(geometry, bisection) pairs, best bisection first, ties toward
        the lexicographically-smallest geometry."""
        pairs = [(self._geometry(i), int(self.bisections[i])) for i in range(len(self))]
        pairs.sort(key=lambda p: (-p[1], p[0]))
        return pairs


def bisection_table(
    torus_or_dims,
    units: int,
    unit_node_dims: Optional[Sequence[int]] = None,
) -> BisectionTable:
    """Batched internal bisections of every ``units``-sized geometry
    (:meth:`~repro.network.fabric.Fabric.box_bisections`).

    On a torus even-longest-dimension geometries (after node scaling,
    every Blue Gene/Q partition) are closed-form ``2N/L`` in one
    vectorized pass; odd longest dimensions fall back to the exact cuboid
    search per geometry.  On a :class:`~repro.network.fabric.HyperXFabric`
    each box is its own Hamming graph (multiplicities inherited
    tightest-fit) with the exact Lindsey half-set cut; ``unit_node_dims``
    node scaling is the BG/Q torus convention and is rejected there.
    Raises ``ValueError`` when no cuboid of that size fits.

    >>> from .fabric import HyperXFabric
    >>> bisection_table(HyperXFabric((16, 4)), 16).ranked()
    [((16, 1), 64), ((4, 4), 16), ((8, 2), 8)]
    """
    fab = as_fabric(torus_or_dims).canonical()
    fab.check_unit_node_dims(unit_node_dims)
    geoms = fitting_geometries(fab, units)
    if geoms.shape[0] == 0:
        raise ValueError(f"no cuboid of {units} units fits in {fab.dims}")
    bis = fab.box_bisections(geoms, unit_node_dims)
    unit = None if unit_node_dims is None else tuple(int(u) for u in unit_node_dims)
    return BisectionTable(fab.dims, units, geoms, bis, unit)


def ranked_geometries(
    torus_or_dims,
    units: int,
    unit_node_dims: Optional[Sequence[int]] = None,
) -> List[Tuple[Geometry, int]]:
    """All fitting geometries of a size as (geometry, bisection_links)
    pairs, best internal bisection first — the batched replacement for
    sorting :func:`repro.network.geometry.sub_cuboids` by per-geometry
    ``bisection_links`` calls (identical ordering, property-pinned)."""
    return bisection_table(torus_or_dims, units, unit_node_dims).ranked()


def best_bisection_geometry(
    torus_or_dims, units: int, unit_node_dims: Optional[Sequence[int]] = None
) -> Tuple[Geometry, int]:
    """The fitting geometry with maximal internal bisection (links)."""
    return bisection_table(torus_or_dims, units, unit_node_dims).best()


def worst_bisection_geometry(
    torus_or_dims, units: int, unit_node_dims: Optional[Sequence[int]] = None
) -> Tuple[Geometry, int]:
    """The fitting geometry with minimal internal bisection — the
    adversarial baseline of the avoidable-contention ratio."""
    return bisection_table(torus_or_dims, units, unit_node_dims).worst()


def is_isoperimetrically_optimal(
    torus_or_dims,
    geometry: Sequence[int],
    unit_node_dims: Optional[Sequence[int]] = None,
) -> bool:
    """Theorem 3.1 optimality check: does this partition geometry attain the
    maximal internal bisection among all same-volume cuboids that fit the
    machine?  (The paper's criterion for a scheduler's geometry table.)"""
    tbl = bisection_table(torus_or_dims, volume(geometry), unit_node_dims)
    return tbl.bisection_of(geometry) == tbl.best()[1]


# ---------------------------------------------------------------------------
# The partition advisor (paper Tables 4-6 as a decision aid).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PartitionAdvice:
    """Current-policy vs isoperimetric-optimal geometry for one job size.

    Bisections are in links (node-level when the advisor was given
    ``unit_node_dims``); ``predicted_speedup`` is the pairing-benchmark
    time ratio current/optimal (the paper's Tables 4-6 / Figures 3-4
    quantity), ``simulated_speedup`` the flow-simulated makespan ratio
    when the advisor ran with ``simulate=True``; ``bound`` is the Theorem
    3.1 floor on the optimal geometry's bisection *cut*, so ``certified``
    means the optimum's bisection is pinned analytically, not only by
    exhaustive search.
    """

    units: int
    current_geometry: Geometry
    current_bisection: int
    optimal_geometry: Geometry
    optimal_bisection: int
    bound: float
    predicted_speedup: float
    simulated_speedup: Optional[float] = None

    @property
    def bisection_efficiency(self) -> float:
        """current / optimal internal bisection (1.0 when already optimal)."""
        if self.optimal_bisection == 0:
            return 1.0
        return self.current_bisection / self.optimal_bisection

    @property
    def is_current_optimal(self) -> bool:
        """Whether the current geometry already attains the optimum."""
        return self.current_bisection == self.optimal_bisection

    @property
    def certified(self) -> bool:
        """Whether Theorem 3.1 certifies the optimum's bisection exactly."""
        return math.isclose(self.optimal_bisection, self.bound, rel_tol=1e-9)


def advise_partition(
    torus_or_dims,
    units: int,
    current_geometry: Optional[Sequence[int]] = None,
    *,
    unit_node_dims: Optional[Sequence[int]] = None,
    simulate: bool = False,
    backend: Optional[str] = None,
) -> PartitionAdvice:
    """Advise one job size: current (or worst, when None) vs optimal geometry.

    The predicted speedup is the ratio of the two boxes' contention
    benchmark loads (:meth:`~repro.network.fabric.Fabric.exchange_box` /
    ``exchange_max_load``): on a torus the static pairing benchmark on the
    node-level dims (:func:`repro.network.routing.pairing_speedup`); on a
    :class:`~repro.network.fabric.HyperXFabric` all-to-all inside the box
    — HyperX dimensions have diameter 1, so pairing never contends and
    cannot separate geometries; all-to-all stresses the internal
    bisection exactly as the paper's benchmark does on a torus.
    ``simulate=True`` additionally drains that benchmark on both boxes
    through the flow-level simulator and reports the measured makespan
    ratio — for these translation-invariant patterns the two agree
    exactly (the §7 validation property), so a divergence flags a
    modeling bug rather than a worse prediction.  ``bound`` is the
    fabric's subset bound on the optimum's half-set cut.

    >>> from .fabric import HyperXFabric
    >>> adv = advise_partition(HyperXFabric((16, 4)), 16, (4, 4))
    >>> adv.optimal_geometry, adv.current_bisection, adv.optimal_bisection
    ((16, 1), 16, 64)
    >>> adv.predicted_speedup, adv.is_current_optimal, adv.certified
    (4.0, False, True)

    >>> adv = advise_partition((4, 4, 3, 2), 4, (4, 1, 1, 1),
    ...                        unit_node_dims=(4, 4, 4, 4, 2))
    >>> adv.optimal_geometry, adv.current_bisection, adv.optimal_bisection
    ((2, 2, 1, 1), 256, 512)
    >>> round(adv.predicted_speedup, 2), adv.is_current_optimal, adv.certified
    (2.0, False, True)
    """
    fab = as_fabric(torus_or_dims).canonical()
    tbl = bisection_table(fab, units, unit_node_dims)
    opt_geom, opt_bis = tbl.best()
    if current_geometry is None:
        cur_geom, cur_bis = tbl.worst()
    else:
        cur_geom = canonical(
            tuple(current_geometry) + (1,) * (len(fab.dims) - len(tuple(current_geometry)))
        )
        if volume(cur_geom) != units:
            raise ValueError(
                f"current geometry {cur_geom} has volume {volume(cur_geom)}, "
                f"expected {units}"
            )
        cur_bis = tbl.bisection_of(cur_geom)
    box_cur = fab.exchange_box(cur_geom, unit_node_dims)
    box_opt = fab.exchange_box(opt_geom, unit_node_dims)
    load_cur = box_cur.exchange_max_load()
    load_opt = box_opt.exchange_max_load()
    predicted = load_cur / load_opt if load_opt > 0.0 else 1.0
    simulated: Optional[float] = None
    if simulate:
        from . import patterns
        from .netsim import simulate_traffic

        t_cur, t_opt = (
            simulate_traffic(
                box, getattr(patterns, box.exchange_pattern)(box.dims), backend=backend
            ).makespan
            for box in (box_cur, box_opt)
        )
        simulated = t_cur / t_opt if t_opt > 0.0 else 1.0
    return PartitionAdvice(
        units=units,
        current_geometry=cur_geom,
        current_bisection=cur_bis,
        optimal_geometry=opt_geom,
        optimal_bisection=opt_bis,
        bound=box_opt.subset_cut_bound(box_opt.num_cells // 2),
        predicted_speedup=predicted,
        simulated_speedup=simulated,
    )


def advise_policy_table(
    torus_or_dims,
    policy_table: Mapping[int, Sequence[int]],
    *,
    unit_node_dims: Optional[Sequence[int]] = None,
    simulate: bool = False,
    sizes: Optional[Sequence[int]] = None,
    backend: Optional[str] = None,
) -> List[PartitionAdvice]:
    """Advise every size of an allocation policy's admissible geometry table
    (e.g. Mira's scheduler partition list from :mod:`repro.core.bgq`):
    one :class:`PartitionAdvice` per size, ascending."""
    chosen = sorted(policy_table) if sizes is None else [s for s in sizes if s in policy_table]
    return [
        advise_partition(
            torus_or_dims,
            size,
            policy_table[size],
            unit_node_dims=unit_node_dims,
            simulate=simulate,
            backend=backend,
        )
        for size in chosen
    ]
