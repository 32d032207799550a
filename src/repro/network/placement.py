"""Vectorized cuboid-placement engine over occupancy grids.

The allocation problem of the paper — where does a cuboid partition land in
the host torus — reduces to: given a boolean occupancy grid over the machine
torus and an oriented cuboid extent, find every free *translate* of the
cuboid, then pick one.  The historical implementation scanned every
orientation x every torus offset in Python with a per-candidate meshgrid
check; it survives verbatim as the test oracle in
``tests/reference_placement.py``.  This module replaces it with array work:

* :func:`free_offset_mask` — all free translates of one orientation in one
  shot.  The number of occupied cells covered by the cuboid placed at offset
  ``j`` is the circular correlation of the occupancy grid with the cuboid's
  indicator kernel; free offsets are exactly its zeros.  The correlation is
  computed as a separable per-dimension *circular windowed sum* (append the
  first ``w-1`` slices, one cumsum, one subtraction per dimension — integer
  exact, no FFT round-off), so torus wraparound falls out naturally and the
  total work is O(D * N) regardless of cuboid size.
* :func:`first_fit` — bit-identical to the reference walker's choice: try
  orientations in ``sorted(set(permutations(g)))`` order and offsets in
  C (row-major lexicographic) order, which is precisely
  ``itertools.product(*(range(a) for a in dims))``.
* :func:`candidate_scores` / :func:`best_placement` — scored selection.
  Candidates are ranked by
    1. internal bisection of the (canonical) geometry — a property of the
       geometry, so it orders *which* cuboid to request (the isoperimetric
       policy), not where it lands;
    2. predicted neighbour contention: the job's traffic routed on the
       *machine* torus with the PR-1 DOR engine, summed over links already
       carrying existing placements' traffic.  The job's load field
       translates with its offset, so the score for *every* offset is a
       circular cross-correlation of the base load field with the
       background-usage mask (:func:`contention_field`).  Pairing traffic
       is provably isolated between disjoint cuboids under minimal DOR
       (spans never exceed half a ring), so the score uses intra-job
       all-to-all, whose beyond-half-span routes genuinely transit foreign
       territory — the shared-fabric model (TPU ICI without slice
       isolation; 0 for BG/Q-style electrically-isolated partitions);
    3. contact (anti-fragmentation): occupied cells in the one-cell shell
       around the candidate — placing against existing allocations keeps the
       remaining free set contiguous.  Computed for *all* candidates at once
       with the same windowed-sum trick on a dilated window.

Everything here operates on raw grids; :class:`repro.network.allocation.
MachineState` is the stateful wrapper.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from functools import lru_cache
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import TRACER as _TRACER
from .backend import resolve_backend
from .geometry import Geometry, bisection_links, canonical, volume

Coord = Tuple[int, ...]


# ---------------------------------------------------------------------------
# Geometry normalisation (the truncation-bug fix lives here).
# ---------------------------------------------------------------------------
def pad_geometry(geometry: Sequence[int], ndim: int) -> Geometry:
    """Canonicalise and pad a requested geometry to the machine's rank.

    Trailing 1s beyond the machine rank are harmless and stripped; a
    geometry with more *non-trivial* dimensions than the machine is an
    error.  (The historical scan silently truncated it instead — the
    ``g + (1,) * (len(dims) - len(g))`` pad is a no-op for negative counts
    and the subsequent ``zip`` dropped the extra axes, allocating fewer
    cells than the requested volume.)
    """
    g = canonical(geometry)
    while len(g) > ndim and g[-1] == 1:
        g = g[:-1]
    if len(g) > ndim:
        raise ValueError(
            f"geometry {canonical(geometry)} has {len(g)} non-trivial dims; "
            f"machine has only {ndim}"
        )
    return g + (1,) * (ndim - len(g))


def orientations(geometry: Sequence[int], dims: Sequence[int]) -> List[Tuple[int, ...]]:
    """Distinct axis-assignments of the cuboid that fit the machine, in the
    reference scan's order: ``sorted(set(permutations(padded)))``."""
    dims = tuple(dims)
    g = pad_geometry(geometry, len(dims))
    return [
        perm
        for perm in sorted(set(itertools.permutations(g)))
        if all(s <= a for s, a in zip(perm, dims))
    ]


# ---------------------------------------------------------------------------
# The correlation core.
# ---------------------------------------------------------------------------
def _circular_window_sums(occ: np.ndarray, extents: Sequence[int]) -> np.ndarray:
    """S[j] = number of occupied cells in the axis-aligned box of the given
    extents whose lowest corner sits at offset j (circular in every dim).

    Separable: one pass per dimension, each a cumsum over the grid extended
    by its own first ``w - 1`` slices.  Integer arithmetic throughout.
    """
    s = occ.astype(np.int64, copy=False)
    for k, w in enumerate(extents):
        w = int(w)
        a = s.shape[k]
        if w == 1:
            continue
        if not 1 <= w <= a:
            raise ValueError(f"window {w} exceeds grid extent {a} in dim {k}")
        ext = np.concatenate([s, np.take(s, range(w - 1), axis=k)], axis=k)
        c = np.cumsum(ext, axis=k)
        upper = np.take(c, range(w - 1, a + w - 1), axis=k)
        lower = np.concatenate(
            [np.zeros_like(np.take(c, [0], axis=k)), np.take(c, range(a - 1), axis=k)],
            axis=k,
        )
        s = upper - lower
    return s


def free_offset_mask(grid: np.ndarray, oriented: Sequence[int]) -> np.ndarray:
    """Boolean mask over all torus offsets: True where the oriented cuboid
    placed at that offset covers only free cells."""
    return _circular_window_sums(grid, tuple(oriented)) == 0


def shell_contact(grid: np.ndarray, oriented: Sequence[int]) -> np.ndarray:
    """Occupied-cell count in the one-cell shell around the cuboid at every
    offset (valid wherever the offset itself is free).

    Computed as the windowed sum over the cuboid dilated by one cell on each
    side (window ``w + 2`` starting one cell earlier), clipped to the full
    ring where the dilated window would wrap onto itself; for a free
    placement the interior contributes zero, so the dilated sum *is* the
    shell occupancy.
    """
    dims = grid.shape
    extents = tuple(min(w + 2, a) for w, a in zip(oriented, dims))
    sums = _circular_window_sums(grid, extents)
    shift = [1 if e == w + 2 else 0 for e, w in zip(extents, oriented)]
    if any(shift):
        sums = np.roll(sums, shift, axis=tuple(range(len(dims))))
    return sums


# ---------------------------------------------------------------------------
# Enumeration and first-fit.
# ---------------------------------------------------------------------------
def iter_free_placements(
    grid: np.ndarray, geometry: Sequence[int]
) -> Iterator[Tuple[Tuple[int, ...], np.ndarray]]:
    """Yield ``(oriented, free_mask)`` per fitting orientation, in reference
    order.  ``free_mask`` has the grid's shape."""
    for perm in orientations(geometry, grid.shape):
        yield perm, free_offset_mask(grid, perm)


def first_fit(
    grid: np.ndarray, geometry: Sequence[int]
) -> Optional[Tuple[Tuple[int, ...], Coord]]:
    """First free translate of any orientation — identical choice to the
    brute-force reference scan (orientation order, then C-order offsets)."""
    for perm, free in iter_free_placements(grid, geometry):
        flat = np.flatnonzero(free.ravel(order="C"))
        if flat.size:
            return perm, tuple(int(x) for x in np.unravel_index(flat[0], grid.shape))
    return None


# Largest packed cuboid table, in bytes before deduplication (orientations
# x offsets x ceil(N / 8)), that :class:`FitTest` builds for one geometry:
# every geometry of Mira's 96-midplane torus or JUQUEEN's (7, 2, 2, 2) fits
# far inside it; a node-level torus does not and keeps :func:`first_fit`.
_PACKED_TABLE_BUDGET = 4 << 20


@lru_cache(maxsize=64)
def _packed_cuboids(dims: Geometry, geometry: Geometry) -> Optional[np.ndarray]:
    """Every placement :func:`first_fit` may find for ``geometry`` as a
    packed cell mask: one ``uint8`` row of ``ceil(N / 8)`` bytes (the C-order
    flattened grid, :func:`numpy.packbits` order) per distinct cell set over
    all orientations and torus offsets.  None where the table would pass
    the budget.  Memoised — callers must not mutate the result."""
    perms = orientations(geometry, dims)
    n = volume(dims)
    if len(perms) * n * -(-n // 8) > _PACKED_TABLE_BUDGET:
        return None
    rows = [np.zeros((0, -(-n // 8)), dtype=np.uint8)]
    for perm in perms:
        # Offset o covers cell c iff (c_k - o_k) mod a_k < w_k in every dim:
        # the (offsets, cells) mask is the Kronecker product of one such
        # (a_k, a_k) matrix per dim, rows and columns both in C order.
        mask = np.ones((1, 1), dtype=np.uint8)
        for w, a in zip(perm, dims):
            ring = np.arange(a)
            covers = np.subtract.outer(ring, ring).T % a < w  # [o, c]
            mask = np.kron(mask, covers.astype(np.uint8))
        rows.append(np.packbits(mask, axis=1))
    table = np.unique(np.concatenate(rows), axis=0)
    table.setflags(write=False)
    return table


class FitTest:
    """Whether some geometry of a list fits on an occupancy grid:
    ``FitTest(dims, geometries)(grid)`` is
    ``any(first_fit(grid, g) is not None for g in geometries)``.

    Where the machine is small enough, each geometry's placements are a
    memoised table of packed cell masks, and all of them are tested in one
    vectorised ``&`` with the packed grid (a fit is a row with no occupied
    bit); the geometries whose table would pass the budget, on larger
    machines, go through :func:`first_fit`.  ``packed`` says whether the
    table path runs."""

    __slots__ = ("rows", "loose")

    def __init__(self, dims: Sequence[int], geometries: Sequence[Sequence[int]]):
        dims = tuple(int(a) for a in dims)
        tables, self.loose = [], []
        for g in geometries:
            table = _packed_cuboids(dims, pad_geometry(g, len(dims)))
            if table is None:
                self.loose.append(g)
            else:
                tables.append(table)
        self.rows = np.concatenate(tables) if tables else None

    @property
    def packed(self) -> bool:
        return self.rows is not None

    def __call__(self, grid: np.ndarray) -> bool:
        if self.rows is not None:
            if not (self.rows & np.packbits(grid)).any(axis=1).all():
                return True
        return any(first_fit(grid, g) is not None for g in self.loose)


def placement_cells(
    dims: Sequence[int], oriented: Sequence[int], offset: Coord
) -> Tuple[np.ndarray, ...]:
    """Open-mesh index (``np.ix_``) of the cells covered by the placement —
    usable directly for grid assignment and reads."""
    return np.ix_(
        *[
            (int(offset[k]) + np.arange(int(oriented[k]))) % int(a)
            for k, a in enumerate(dims)
        ]
    )


# ---------------------------------------------------------------------------
# Traffic-aware scoring.
# ---------------------------------------------------------------------------
def _relative_cells(oriented: Tuple[int, ...]) -> np.ndarray:
    n = int(np.prod(oriented))
    return np.stack(np.unravel_index(np.arange(n), oriented), axis=1).astype(np.int64)


def placement_pairing_traffic(
    dims: Sequence[int], oriented: Sequence[int], offset: Coord
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The job's bisection-pairing traffic in *machine* coordinates: every
    cell of the placed cuboid sends unit volume to its cuboid-antipode
    (offset by ``oriented // 2`` within the cuboid, wrapped cuboid-locally).

    Note this pattern cannot interfere across placements: pairing distances
    are at most ``ceil(w/2) <= ceil(a/2)`` per ring, so under minimal DOR
    the traffic never leaves the cuboid's own cells except via exact-half
    ties on a ``w == a - 1`` span — and the single foreign ring position
    that touches is too narrow for any neighbour to route over (pinned in
    ``tests/test_placement.py::test_pairing_traffic_is_isolated``).  It is
    the *intra*-partition contention model; use all-to-all for the
    cross-placement score.
    """
    dims = tuple(int(a) for a in dims)
    oriented = tuple(int(w) for w in oriented)
    rel = _relative_cells(oriented)
    half = np.asarray([w // 2 for w in oriented], dtype=np.int64)
    dst_rel = (rel + half) % np.asarray(oriented, dtype=np.int64)
    off = np.asarray(offset, dtype=np.int64)
    d = np.asarray(dims, dtype=np.int64)
    src = (rel + off) % d
    dst = (dst_rel + off) % d
    keep = ~(src == dst).all(axis=1)
    return src[keep], dst[keep], np.ones(int(keep.sum()), dtype=np.float64)


def placement_all_to_all_traffic(
    dims: Sequence[int], oriented: Sequence[int], offset: Coord
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Intra-job all-to-all in machine coordinates, volume ``1/n`` per
    ordered pair so every cell injects ~unit volume regardless of job size.

    This is the contention-scoring pattern: messages between cells at
    within-span distance beyond half the ring route the short way around —
    *through* foreign territory — so placements with long spans genuinely
    load links that other placements use.
    """
    dims = tuple(int(a) for a in dims)
    oriented = tuple(int(w) for w in oriented)
    n = int(np.prod(oriented))
    rel = _relative_cells(oriented)
    off = np.asarray(offset, dtype=np.int64)
    d = np.asarray(dims, dtype=np.int64)
    cells = (rel + off) % d
    si = np.repeat(np.arange(n), n)
    di = np.tile(np.arange(n), n)
    keep = si != di
    si, di = si[keep], di[keep]
    vol = np.full(si.shape[0], 1.0 / n, dtype=np.float64)
    return cells[si], cells[di], vol


def placement_loads(
    dims: Sequence[int],
    oriented: Sequence[int],
    offset: Coord,
    pattern: str = "all-to-all",
) -> np.ndarray:
    """Link loads of the placement's traffic on the machine torus.

    ``pattern``: ``"all-to-all"`` (the cross-placement contention model,
    default) or ``"pairing"`` (the paper's intra-partition benchmark).

    Loads are translation-covariant, so the all-to-all path rolls the
    memoised origin field instead of re-routing — do not mutate the
    returned array (it may be the cache itself, which is write-locked).
    """
    dims = tuple(int(a) for a in dims)
    if pattern == "all-to-all":
        base = base_loads(dims, tuple(oriented))
        off = tuple(int(o) % a for o, a in zip(offset, dims))
        if not any(off):
            return base
        return np.roll(base, off, axis=tuple(range(2, 2 + len(dims))))
    if pattern != "pairing":
        raise ValueError(f"unknown traffic pattern {pattern!r}")
    from .routing import route_dor

    src, dst, vol = placement_pairing_traffic(dims, oriented, offset)
    if src.shape[0] == 0:
        return np.zeros((len(dims), 2) + dims)
    return route_dor(dims, src, dst, vol)


def shared_link_contention(job_loads: np.ndarray, background: np.ndarray) -> float:
    """Traffic volume the job routes over links already carrying neighbour
    traffic — the avoidable-interference proxy used for scoring."""
    return float(job_loads[background > 0.0].sum())


@dataclass(frozen=True)
class ScoredPlacement:
    """One scored candidate; :func:`best_placement` picks the minimum of
    (contention, -contact, orientation, offset)."""

    oriented: Tuple[int, ...]
    offset: Coord
    bisection: int  # of the canonical geometry (orientation-invariant)
    contact: int  # occupied cells touching the placement's shell
    contention: float  # job traffic on links shared with neighbours

    spilling: bool = False  # some span routes beyond its own cells


def is_spilling(oriented: Sequence[int], dims: Sequence[int]) -> bool:
    """Whether any span of the orientation routes all-to-all traffic outside
    its own cells: within-span distances reach ``w - 1``, which routes the
    short way around — through foreign territory — when it exceeds half the
    ring (``2w - 2 > a``), and *also* at exactly half (``2w - 2 == a``)
    because the default split-tie routing sends half that volume backward.
    A span covering the full ring wraps internally and never spills."""
    return any(2 * w - 2 >= a and w < a for w, a in zip(oriented, dims))


def fabric_can_interfere(dims: Sequence[int]) -> bool:
    """Whether any two *disjoint* cuboid placements can share a link on this
    machine.  Sharing needs a spilling span (``2w - 2 >= a``, ``w < a``)
    *and* a partner with its own dim-k traffic inside the spill corridor —
    extent >= 2 over the ``a - w`` free positions, so ``w <= a - 2``.  Both
    hold for some ``w`` iff the ring has length >= 6: Mira-class tori
    (rings <= 4) are contention-isolated for every cuboid workload, while
    JUQUEEN's 7-ring and 16-wide TPU pods are not.  (Single spans can still
    *spill* on rings of 4 and 5 — ``is_spilling`` — but no disjoint
    neighbour can route over the corridor.)"""
    return any(a >= 6 for a in dims)


def base_loads(dims: Geometry, oriented: Tuple[int, ...]) -> np.ndarray:
    """The job's all-to-all load field for a placement at the origin.

    Loads translate with the placement (torus translation invariance), so
    this one field serves every offset of the orientation.  Memoised —
    callers must not mutate the returned array.
    """
    return _base_loads_cached(tuple(int(a) for a in dims), tuple(int(w) for w in oriented))


@lru_cache(maxsize=512)
def _base_loads_cached(dims: Geometry, oriented: Tuple[int, ...]) -> np.ndarray:
    from .routing import route_dor

    src, dst, vol = placement_all_to_all_traffic(dims, oriented, (0,) * len(dims))
    if src.shape[0] == 0:
        arr = np.zeros((len(dims), 2) + dims)
    else:
        arr = route_dor(dims, src, dst, vol)
    arr.setflags(write=False)
    return arr


def int_base_loads(dims: Geometry, oriented: Tuple[int, ...]) -> np.ndarray:
    """The placement's all-to-all load field at the origin, scaled by
    ``2 * n`` (n = cells in the placement) so every value is an exact
    ``int64``.

    :func:`placement_loads` routes volume ``1/n`` per ordered pair, so raw
    per-link loads are multiples of ``1/(2n)`` (the ``1/2`` from antipodal
    tie splitting) — not exactly representable when ``n`` is not a power of
    two, which is why float accumulation across placements of different
    sizes can never be subtracted back out bit-exactly.  Routing the same
    messages with volume ``2`` instead makes every contribution — including
    split ties — an integer, so the field is exact and placement sums live
    in int64 where addition *and subtraction* are lossless:
    ``placement_loads(...) == int_base_loads(...) / (2 * n)`` up to one
    float rounding, with identical support.  This is the representation
    :class:`repro.network.allocation.MachineState` maintains incrementally.
    Memoised — callers must not mutate the returned array.
    """
    return _int_base_loads_cached(
        tuple(int(a) for a in dims), tuple(int(w) for w in oriented)
    )


@lru_cache(maxsize=512)
def _int_base_loads_cached(dims: Geometry, oriented: Tuple[int, ...]) -> np.ndarray:
    from .routing import route_dor

    src, dst, _ = placement_all_to_all_traffic(dims, oriented, (0,) * len(dims))
    if src.shape[0] == 0:
        arr = np.zeros((len(dims), 2) + dims, dtype=np.int64)
    else:
        # Volume 2 per ordered pair: whole messages contribute 2 per link,
        # split antipodal ties 1 per direction — every partial sum is an
        # integer-valued float (exact below 2**53), so rint is a no-op
        # safeguard rather than a rounding step.
        raw = route_dor(dims, src, dst, np.full(src.shape[0], 2.0))
        arr = np.rint(raw).astype(np.int64)
    arr.setflags(write=False)
    return arr


def int_placement_loads(
    dims: Sequence[int], oriented: Sequence[int], offset: Coord
) -> np.ndarray:
    """:func:`int_base_loads` translated to ``offset`` (loads are
    translation-covariant, so this is a roll of the memoised origin field).
    Do not mutate the returned array — at the origin it *is* the cache."""
    dims = tuple(int(a) for a in dims)
    base = int_base_loads(dims, tuple(int(w) for w in oriented))
    off = tuple(int(o) % a for o, a in zip(offset, dims))
    if not any(off):
        return base
    return np.roll(base, off, axis=tuple(range(2, 2 + len(dims))))


def interference_mask(
    grid: np.ndarray, background_loads: Optional[np.ndarray] = None
) -> np.ndarray:
    """(D, 2, *dims) boolean mask of links a new job should avoid loading:
    links leaving an occupied cell (transit through foreign territory —
    interference whether or not the owner is routing there *right now*),
    plus links already carrying background traffic (e.g. a neighbour's
    spill over free corridor cells)."""
    D = len(grid.shape)
    mask = np.broadcast_to(grid.astype(bool), (D, 2) + grid.shape).copy()
    if background_loads is not None:
        mask |= background_loads > 0.0
    return mask


def _mask_plane_ffts(mask: np.ndarray) -> List[List[Optional[np.ndarray]]]:
    """FFTs of each (dimension, direction) mask plane (None where the plane
    is empty) — hoisted out of :func:`contention_field` so a multi-
    orientation search transforms the mask once, not once per orientation."""
    D = mask.shape[0]
    return [
        [
            np.fft.fftn(mask[k, d].astype(np.float64)) if mask[k, d].any() else None
            for d in range(2)
        ]
        for k in range(D)
    ]


def contention_field(
    dims: Sequence[int],
    oriented: Sequence[int],
    mask: np.ndarray,
    mask_ffts: Optional[List[List[Optional[np.ndarray]]]] = None,
    backend: Optional[str] = None,
) -> np.ndarray:
    """Predicted interference for *every* offset of an orientation in one
    shot: the job's traffic volume over masked links
    (:func:`interference_mask`).

    The job's load field translates with its offset, so its overlap with the
    mask is a circular cross-correlation, evaluated per (dimension,
    direction) load plane with FFTs:

        C[o] = sum_{k,d,v} J[k,d][(v - o) mod dims] * mask[k,d][v]

    O(D * N log N) for all N offsets — the same trick that finds the free
    translates, applied to the score.  A candidate's own cells are free in
    the pre-commit grid, so its internal links never self-count.  The
    correlation runs on the integer field :func:`int_base_loads`, so every
    value is an integer that the FFT's round-off (far below 1/2) cannot
    move: it is rounded back exactly, and the field returned is that exact
    integer over ``2n``.

    ``backend="xla"`` computes the same exact field as a direct integer
    correlation on the compiled path (``mask_ffts`` is ignored there); the
    two backends agree bit for bit.
    """
    dims = tuple(int(a) for a in dims)
    if resolve_backend(backend) == "xla":
        from .backend import xla_contention_field

        return xla_contention_field(dims, tuple(oriented), mask)
    if mask_ffts is None:
        mask_ffts = _mask_plane_ffts(mask)
    J = int_base_loads(dims, tuple(oriented))
    out = np.zeros(dims, dtype=np.float64)
    for k in range(len(dims)):
        for d in range(2):
            F = mask_ffts[k][d]
            plane = J[k, d]
            if F is None or not plane.any():
                continue
            corr = np.fft.ifftn(F * np.conj(np.fft.fftn(plane)))
            out += np.real(corr)
    return np.rint(out).astype(np.int64) / (2 * volume(tuple(oriented)))


def best_placement(
    grid: np.ndarray,
    geometry: Sequence[int],
    background_loads: Optional[np.ndarray] = None,
    backend: Optional[str] = None,
) -> Optional[ScoredPlacement]:
    """Scored placement of one geometry: among all free translates of all
    orientations, minimise predicted interference (the job's all-to-all
    traffic over links leaving occupied cells or already carrying the
    existing placements' traffic, evaluated for every candidate via
    :func:`contention_field`).  Ties break toward the snuggest candidate
    (max :func:`shell_contact`) on spill-free fabrics, then the reference
    scan order, so the choice is fully deterministic.

    ``background_loads`` is the (D, 2, *dims) load tensor of the existing
    placements' traffic (see :func:`placement_loads`); None or all-zero
    makes contention vanish and the choice purely contact-driven.

    With tracing enabled (:mod:`repro.obs`) the search records a
    ``placement.search`` span annotated with the winning orientation /
    offset / contention; the choice is identical either way.
    """
    if not _TRACER.enabled:
        return _best_placement_impl(grid, geometry, background_loads, backend)
    with _TRACER.span(
        "placement.search", geometry=tuple(int(g) for g in geometry)
    ) as span:
        out = _best_placement_impl(grid, geometry, background_loads, backend)
        if out is not None:
            span.annotate(
                oriented=out.oriented, offset=out.offset, contention=out.contention
            )
        else:
            span.annotate(placed=False)
        return out


def _best_placement_impl(
    grid: np.ndarray,
    geometry: Sequence[int],
    background_loads: Optional[np.ndarray],
    backend: Optional[str],
) -> Optional[ScoredPlacement]:
    dims = grid.shape
    bis = bisection_links(pad_geometry(geometry, len(dims)))
    mask = interference_mask(grid, background_loads)
    have_bg = bool(mask.any())
    mask_ffts = _mask_plane_ffts(mask) if have_bg else None
    # Snug (max-contact) tie-breaking keeps the free set contiguous, but on
    # fabrics where placements can share links it measurably *increases*
    # realised interference: snug-packed layouts drift away from the
    # origin-aligned packings that stack spill corridors on the same ring
    # positions, and later strips get forced through neighbours.  On
    # interference-free fabrics (every ring <= 5, e.g. Mira's midplane
    # torus) adjacency is provably free, so the anti-fragmentation
    # tie-break is enabled exactly there.
    use_contact = not fabric_can_interfere(dims)
    best: Optional[Tuple[tuple, ScoredPlacement]] = None
    for perm, free in iter_free_placements(grid, geometry):
        flat = np.flatnonzero(free.ravel(order="C"))
        if not flat.size:
            continue
        contact = shell_contact(grid, perm).ravel(order="C")[flat]
        if have_bg:
            cont = contention_field(
                dims, perm, mask, mask_ffts, backend=backend
            ).ravel(order="C")[flat]
        else:
            cont = np.zeros(flat.shape[0])
        rank_contact = contact if use_contact else np.zeros_like(contact)
        # argmin over (contention, -contact, C-order offset) within this
        # orientation, without materialising Python tuples per candidate.
        order = np.lexsort((flat, -rank_contact, np.round(cont, 9)))
        i = order[0]
        offset = tuple(int(x) for x in np.unravel_index(flat[i], dims))
        # Report the same rounded value used for ranking, so FFT round-off
        # (~1e-16) never surfaces as spurious nonzero contention.
        contention = round(float(cont[i]), 9)
        key = (contention, -int(rank_contact[i]), perm, offset)
        if best is None or key < best[0]:
            best = (
                key,
                ScoredPlacement(
                    oriented=perm,
                    offset=offset,
                    bisection=bis,
                    contact=int(contact[i]),
                    contention=contention,
                    spilling=is_spilling(perm, dims),
                ),
            )
    return best[1] if best else None
