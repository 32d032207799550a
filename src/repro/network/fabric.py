"""Unified torus fabric model: one class for BG/Q- and TPU-style tori.

This merges what used to be two separate models:

* ``repro.core.torus.Torus`` — the paper's fully-wrapped Blue Gene/Q torus
  with double links on length-2 dimensions, used by the isoperimetric
  analysis; and
* ``repro.core.collectives.TorusFabric`` — the TPU-adapted fabric with
  per-dimension wrap flags and single links on length-2 dimensions.

Both are now parameterisations of :class:`TorusFabric`; the thin
:class:`Torus` wrapper keeps the historical geometry-only API and delegates
every computation to :mod:`repro.network.geometry`.

Hardware conventions (see DESIGN.md):

* Blue Gene/Q: a partition *always* retains wrap-around links (a partition of
  midplane geometry g is itself a torus), and a dimension of length 2 has two
  parallel physical links — ``TorusFabric.bgq(dims)``.
* TPU ICI: a slice gets wrap-around links in a dimension only when it spans
  that full pod dimension, and a length-2 dimension has a single link —
  ``TorusFabric.tpu(dims, wrap)`` / :func:`slice_fabric`.

Every engine above this module (routing, netsim, placement, allocation,
the scheduler, the planner, the contention report) takes a
:class:`Fabric` (or plain dims, through :func:`as_fabric`) and asks it,
never its type, what differs between fabric kinds: whether disjoint
boxes share links, which exchange separates box geometries, the cut
closed forms and the subset bound, the routing modes, and the ring
stand-in for collective pricing.  This module imports only
:mod:`~repro.network.geometry` and :mod:`~repro.network.hamming`.
"""

from __future__ import annotations

import abc
import math
from dataclasses import dataclass
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import geometry, hamming
from .geometry import (
    Geometry,
    canonical,
    pairing_max_load,
    scaled_node_dims,
    theorem31_bound,
    volume,
)

# TPU v5e-class constants (per chip / per link, bytes per second).
DEFAULT_LINK_BW = 50e9  # ~50 GB/s per ICI link per direction (prompt spec)
POD_DCI_BW = 12.5e9  # inter-pod (data-center network) per-chip share, est.


# ---------------------------------------------------------------------------
# The fabric interface: explicit link incidence + per-dimension structure.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class LinkTable:
    """Explicit directed-link incidence of a fabric.

    Parallel arrays: ``link[i]`` is the flat link id (an index into the
    fabric's dense id space of ``n_slots`` slots — some slots may be
    unused, e.g. length-1 torus dimensions), ``src[i]``/``dst[i]`` the
    endpoint cells as flat C-order indices into the cell grid, and
    ``capacity[i]`` the link bandwidth in bytes/s (parallel physical
    links — BG/Q double links, HyperX trunking — fold into capacity).
    """

    link: np.ndarray  # (L,) int64 flat link ids, unique
    src: np.ndarray  # (L,) int64 source cell (flat C-order)
    dst: np.ndarray  # (L,) int64 destination cell (flat C-order)
    capacity: np.ndarray  # (L,) float bytes/s
    n_slots: int  # size of the dense link-id space

    def __len__(self) -> int:
        return int(self.link.shape[0])

    def dense_capacities(self) -> np.ndarray:
        """Per-slot capacities (bytes/s), zero on unused slots — the
        vector :func:`repro.network.netsim.build_paths` hands the drain
        for fabrics without the torus tensor layout."""
        cap = np.zeros(self.n_slots, dtype=np.float64)
        cap[self.link] = self.capacity
        return cap

    def neighbors_of(self, cell: int) -> np.ndarray:
        """Sorted unique flat cell indices one link away from ``cell``."""
        return np.unique(self.dst[self.src == int(cell)])


class Fabric(abc.ABC):
    """Abstract interconnect fabric: cells joined by capacitated links.

    The contract every engine above routing programs against: a dense
    cell grid of per-dimension sizes ``dims`` (cuboid placement and the
    occupancy machinery need per-dim structure), an explicit link
    incidence (:meth:`links` — netsim builds its link x flow waterfilling
    from it), neighbor queries, and an internal-bisection figure.
    Implementations: :class:`TorusFabric` (rings per dimension) and
    :class:`HyperXFabric` (a clique per dimension — the Hamming graph).

    The members below carry every fact the engines used to branch on by
    type.  Boxes are aligned sub-boxes (cuboid placements), given by
    their sides ``geometry``; ``unit_node_dims`` is the Blue Gene/Q
    allocation-unit-to-node scaling
    (:func:`repro.network.geometry.scaled_node_dims`).
    """

    dims: Tuple[int, ...]
    link_bw: float
    #: Whether two disjoint boxes can load the same link.  False when every
    #: minimal path between cells of a box stays inside the box — then
    #: there is no cross-placement background field and placement scoring
    #: has nothing to rank.
    boxes_share_links: bool
    #: The (base, adaptive) routing modes of
    #: :func:`repro.network.netsim.build_paths`; the first is the default.
    routing_modes: Tuple[str, str]
    #: The :mod:`repro.network.patterns` traffic (by function name) whose
    #: contention separates box geometries on this fabric:
    #: :meth:`exchange_max_load` is its closed form.
    exchange_pattern: str
    #: Whether a length-2 dimension has two parallel links (the Blue
    #: Gene/Q torus) — netsim's ``double_link_on_2`` for this fabric.
    double_link_on_2: bool

    @property
    def num_cells(self) -> int:
        """Number of cells (allocation units) in the fabric."""
        return volume(self.dims)

    @property
    def dim_sizes(self) -> Tuple[int, ...]:
        """Per-dimension cell counts (the placement grid's shape)."""
        return tuple(self.dims)

    @abc.abstractmethod
    def links(self) -> LinkTable:
        """The explicit ``(link, src_cell, dst_cell, capacity)`` table."""

    @abc.abstractmethod
    def bisection_links(self) -> int:
        """Internal bisection of the fabric in (unit-capacity) links."""

    def neighbors(self, cell: int) -> np.ndarray:
        """Flat cell indices adjacent to ``cell`` (sorted, unique)."""
        return self.links().neighbors_of(cell)

    # -- isomorphism and boxes -------------------------------------------------
    @abc.abstractmethod
    def canonical(self) -> "Fabric":
        """The same graph with ``dims`` sorted descending (per-dimension
        parameters permuted along, stable on ties) — the form the
        isoperimetry engine's tables are keyed by."""

    @abc.abstractmethod
    def sub_fabric(self, sides: Sequence[int]) -> "Fabric":
        """The fabric of an aligned sub-box with these sides (matched to
        this fabric's dimensions tightest-fit); ``ValueError`` if it does
        not fit."""

    def check_unit_node_dims(self, unit_node_dims: Optional[Sequence[int]]) -> None:
        """Raise ``ValueError`` where allocation units cannot be scaled to
        nodes; a no-op on fabrics with the torus node convention."""

    @abc.abstractmethod
    def box_bisections(
        self, geometries, unit_node_dims: Optional[Sequence[int]] = None
    ) -> np.ndarray:
        """Internal bisection (links) of a box of each geometry (rows of
        ``geometries``) as a partition of its own — the quantity the
        allocation policies rank by and record."""

    # -- the geometry-sensitive exchange -------------------------------------
    @abc.abstractmethod
    def exchange_box(
        self, geometry_: Sequence[int], unit_node_dims: Optional[Sequence[int]] = None
    ) -> "Fabric":
        """The fabric a box of ``geometry_`` runs its contention benchmark
        (:attr:`exchange_pattern`) on."""

    @abc.abstractmethod
    def exchange_max_load(self) -> float:
        """Max effective link load of :attr:`exchange_pattern` on this
        fabric at unit volume per message (closed form, exact)."""

    @abc.abstractmethod
    def pairing_time(self, bytes_per_node: float, node_dims: Sequence[int]) -> float:
        """Seconds to drain a bisection-pairing exchange of
        ``bytes_per_node`` bytes per node on this fabric, whose partition
        stands for the node-level dims ``node_dims`` (the planner's
        pairing-stress price)."""

    @abc.abstractmethod
    def ring_equivalent(self) -> "TorusFabric":
        """The wrapped torus on which ring collectives over this fabric's
        dimensions cost the same — what the collective cost model and the
        rank mapper price."""

    # -- edge-isoperimetry ----------------------------------------------------
    @abc.abstractmethod
    def line_cuts(self, sides: np.ndarray) -> np.ndarray:
        """Per entry ``sides[i, k]``: the links cut by a segment of that
        many cells of one dimension-k line.  An aligned box of volume
        ``t`` cuts ``sum_k (t / s_k) * line_cuts[k]`` links — the product
        graph's cut decomposes per dimension."""

    @abc.abstractmethod
    def subset_cut_bound(self, t: int) -> float:
        """Lower bound on the cut of any ``t``-cell set (complement
        symmetry built in)."""

    # -- per-link load layout -------------------------------------------------
    @property
    @abc.abstractmethod
    def load_shape(self) -> Tuple[int, ...]:
        """Shape of a per-link load array on this fabric (the layout the
        routing engine and :meth:`repro.network.netsim.FlowPaths.link_loads`
        produce)."""

    @abc.abstractmethod
    def slot_label(self, slot: int) -> Tuple[int, int, Tuple[int, ...]]:
        """``(dim, direction, cell)`` of a flat load index: the link
        leaving ``cell`` along ``dim``; ``direction`` is 0 (+1) or 1 (-1)
        on a torus and the destination coordinate on HyperX."""

    def internal_links(self, member: np.ndarray) -> np.ndarray:
        """Boolean array of :attr:`load_shape`: the links with both
        endpoints in the cell set ``member`` (a ``dims``-shaped mask)."""
        table = self.links()
        flat = np.asarray(member, dtype=bool).ravel()
        own = np.zeros(table.n_slots, dtype=bool)
        own[table.link[flat[table.src] & flat[table.dst]]] = True
        return own.reshape(self.load_shape)


def as_fabric(fabric) -> Fabric:
    """The :class:`Fabric` a public entry point works on: a fabric as
    given; plain dims or a :class:`Torus` become ``TorusFabric.bgq(dims)``
    (the paper's torus, with the dims' order kept).

    >>> as_fabric((4, 2))
    TorusFabric(dims=(4, 2), wrap=(True, True), link_bw=50000000000.0, double_link_on_2=True)
    """
    if isinstance(fabric, Fabric):
        return fabric
    return TorusFabric.bgq(getattr(fabric, "dims", fabric))


@dataclass(frozen=True)
class TorusFabric(Fabric):
    """A physical torus (or mesh) fabric: a machine, a pod, or a slice.

    ``dims`` are chip/midplane counts per dimension, ``wrap`` flags the
    presence of the wrap-around link per dimension, ``link_bw`` is bytes/s
    per link per direction, and ``double_link_on_2`` selects the Blue
    Gene/Q convention (two parallel links on a length-2 dimension) vs the
    TPU ICI single link.

    >>> bgq = TorusFabric.bgq((4, 4, 4))
    >>> bgq.num_chips, bgq.bisection_links()
    (64, 32)
    >>> chain = TorusFabric.tpu((4, 2), wrap=(True, False))
    >>> chain.bisection_links()  # unwrapped dim is cut once, not twice
    4

    The partition facts the allocator ranks and records by
    (:meth:`box_bisections`, :meth:`exchange_box`, :meth:`line_cuts`,
    :meth:`subset_cut_bound`, :attr:`degree`) follow the paper's
    convention whatever the flags: every partition is a fully wrapped
    torus with double links on length-2 dimensions.  :meth:`sub_fabric`,
    :meth:`exchange_max_load` and :meth:`pairing_time` keep this fabric's
    own link convention, as the planner prices it.
    """

    dims: Tuple[int, ...]
    wrap: Tuple[bool, ...]  # wrap-around link present per dimension
    link_bw: float = DEFAULT_LINK_BW  # bytes/s per link per direction
    double_link_on_2: bool = False  # Blue Gene/Q: True, TPU: False

    # A ring wraps traffic of a box through cells outside it (an antipodal
    # tie splits both ways round), so disjoint boxes can share links.
    boxes_share_links = True
    routing_modes = ("dor", "adaptive")
    exchange_pattern = "bisection_pairing"

    def __post_init__(self):
        if len(self.dims) != len(self.wrap):
            raise ValueError("dims and wrap must have equal length")

    # -- constructors ----------------------------------------------------------
    @classmethod
    def bgq(cls, dims: Sequence[int], link_bw: float = DEFAULT_LINK_BW) -> "TorusFabric":
        """Blue Gene/Q convention: fully wrapped, double links on a==2."""
        d = tuple(int(a) for a in dims)
        return cls(d, (True,) * len(d), link_bw, double_link_on_2=True)

    @classmethod
    def tpu(
        cls,
        dims: Sequence[int],
        wrap: Optional[Sequence[bool]] = None,
        link_bw: float = DEFAULT_LINK_BW,
    ) -> "TorusFabric":
        """TPU ICI convention: explicit wrap flags, single links on a==2."""
        d = tuple(int(a) for a in dims)
        w = tuple(bool(x) for x in wrap) if wrap is not None else (True,) * len(d)
        return cls(d, w, link_bw, double_link_on_2=False)

    # -- basic quantities ------------------------------------------------------
    @property
    def num_chips(self) -> int:
        """Number of allocation units (chips / midplanes) in the fabric."""
        return volume(self.dims)

    @property
    def num_vertices(self) -> int:
        """Alias of :attr:`num_chips` for graph-flavoured callers."""
        return self.num_chips

    @property
    def is_fully_wrapped(self) -> bool:
        """Whether every non-trivial dimension keeps its wrap-around link."""
        return all(self.wrap[k] for k, a in enumerate(self.dims) if a > 1)

    def links_across_dim(self, k: int) -> int:
        """Links crossing a perpendicular plane of dimension k (per plane)."""
        return self.num_chips // self.dims[k]

    def bisection_links(self) -> int:
        """Internal bisection in links.

        For fully-wrapped double-link fabrics (the paper's BG/Q convention)
        this is the exact edge-isoperimetric computation, including the
        cuboid search for odd longest dimensions.  For partially-wrapped or
        single-link fabrics it is the min-over-dimensions halving cut: a
        wrapped dimension is cut in two places, an unwrapped (chain)
        dimension in one; a length-2 wrapped dimension with double links
        contributes 2 parallel links.
        """
        if self.is_fully_wrapped and self.double_link_on_2:
            return geometry.bisection_links(self.dims)
        best = None
        for k, a in enumerate(self.dims):
            if a == 1:
                continue
            planes = 2 if (self.wrap[k] and a > 2) else 1
            if a == 2 and self.wrap[k] and self.double_link_on_2:
                planes = 2
            cut = planes * self.links_across_dim(k)
            best = cut if best is None else min(best, cut)
        return 0 if best is None else best

    def bisection_bandwidth(self) -> float:
        """Bytes/s across the bisection, both directions of each link."""
        return 2.0 * self.bisection_links() * self.link_bw

    # -- geometry delegation ---------------------------------------------------
    def contains_cuboid(self, cuboid: Sequence[int]) -> bool:
        """Whether the cuboid geometry fits this fabric (up to rotation)."""
        return geometry.contains_cuboid(self.dims, cuboid)

    def sub_cuboids(self, size: int) -> Iterator[Geometry]:
        """All canonical cuboid geometries of ``size`` units that fit."""
        return geometry.sub_cuboids(self.dims, size)

    @property
    def degree(self) -> int:
        """Links per cell of the fully wrapped double-link torus on these
        dims (two per non-trivial dimension)."""
        return geometry.degree(self.dims)

    # -- the Fabric interface --------------------------------------------------
    def canonical(self) -> "TorusFabric":
        """The same torus with ``dims`` sorted descending (wrap flags
        permuted along)."""
        order = sorted(range(len(self.dims)), key=lambda k: -self.dims[k])
        if order == list(range(len(self.dims))):
            return self
        return TorusFabric(
            tuple(self.dims[k] for k in order),
            tuple(self.wrap[k] for k in order),
            self.link_bw,
            self.double_link_on_2,
        )

    def sub_fabric(self, sides: Sequence[int]) -> "TorusFabric":
        """A sub-box kept as its own fully wrapped torus — the Blue Gene/Q
        partition semantics — with this fabric's link convention and
        bandwidth.  (TPU slices wrap only where they span a wrapped pod
        dimension: that is :func:`slice_fabric`.)

        >>> TorusFabric.bgq((4, 4, 2)).sub_fabric((2, 4)).dims
        (4, 2, 1)
        """
        g, _ = _fit_sides(self.dims, sides, "sub-box", "fabric")
        return TorusFabric(g, (True,) * len(g), self.link_bw, self.double_link_on_2)

    def box_bisections(
        self, geometries, unit_node_dims: Optional[Sequence[int]] = None
    ) -> np.ndarray:
        """Each box as its own fully wrapped double-link torus, at node
        level when ``unit_node_dims`` is given (the paper's Tables 4-7).
        Even longest node dimensions are closed-form ``2N/L`` in one
        vectorized pass; odd ones take the exact search of
        :func:`repro.network.geometry.bisection_links`."""
        geoms = np.asarray(geometries, dtype=np.int64)
        unit = None if unit_node_dims is None else tuple(int(u) for u in unit_node_dims)
        if unit is not None and len(unit) < len(self.dims):
            raise ValueError(
                f"unit_node_dims {unit} has fewer dims than the machine {self.dims}; "
                f"every allocation-unit dimension needs a node-scale factor"
            )
        n_total = geoms.prod(axis=1)
        if unit is None:
            node = geoms
            extras_max = 0
        else:
            node = geoms * np.array(unit[: geoms.shape[1]], dtype=np.int64)[None, :]
            extras_max = max(unit[geoms.shape[1]:], default=0)
            n_total = n_total * math.prod(unit)
        L = np.maximum(node.max(axis=1), extras_max)
        bis = np.zeros(len(geoms), dtype=np.int64)
        even = (L % 2 == 0) & (L > 1)
        bis[even] = 2 * n_total[even] // L[even]
        for i in np.nonzero((~even) & (L > 1))[0]:
            g = tuple(int(x) for x in geoms[i])
            bis[i] = geometry.bisection_links(scaled_node_dims(g, unit))
        return bis

    def exchange_box(
        self, geometry_: Sequence[int], unit_node_dims: Optional[Sequence[int]] = None
    ) -> "TorusFabric":
        """The box's node-level torus in the paper's convention: the
        bisection-pairing benchmark runs there."""
        return TorusFabric.bgq(scaled_node_dims(geometry_, unit_node_dims), self.link_bw)

    def exchange_max_load(self) -> float:
        """Max link load of bisection pairing on this torus
        (:func:`repro.network.geometry.pairing_max_load`)."""
        return pairing_max_load(self.dims, double_link_on_2=self.double_link_on_2)

    def pairing_time(self, bytes_per_node: float, node_dims: Sequence[int]) -> float:
        """Pairing on the node torus ``node_dims`` under this fabric's link
        convention and bandwidth."""
        load = pairing_max_load(node_dims, double_link_on_2=self.double_link_on_2)
        return bytes_per_node * (load / self.link_bw)

    def ring_equivalent(self) -> "TorusFabric":
        """A torus prices its own rings."""
        return self

    def line_cuts(self, sides: np.ndarray) -> np.ndarray:
        """A ring segment short of the whole ring has two faces (a length-2
        ring's double link counts twice); a whole ring cuts nothing."""
        S = np.asarray(sides, dtype=np.int64)
        return np.where(S == np.array(self.dims, dtype=np.int64)[None, :], 0, 2)

    def subset_cut_bound(self, t: int) -> float:
        """Theorem 3.1 at ``min(t, n - t)``."""
        return theorem31_bound(self.dims, min(t, self.num_cells - t))

    @property
    def load_shape(self) -> Tuple[int, ...]:
        """``(D, 2, *dims)``: dimension, direction (+1, -1), source cell."""
        return (len(self.dims), 2) + tuple(self.dims)

    def slot_label(self, slot: int) -> Tuple[int, int, Tuple[int, ...]]:
        """Unravel a flat index of the ``(D, 2, *dims)`` layout."""
        k, direction, *cell = np.unravel_index(int(slot), self.load_shape)
        return int(k), int(direction), tuple(int(c) for c in cell)

    def links(self) -> LinkTable:
        """Directed ring links, ids matching the flattened ``(D, 2, *dims)``
        load-tensor layout of :func:`repro.network.routing.route_dor` (slot
        ``(k * 2 + direction) * N + cell``).  Length-1 dimensions carry no
        links (their slots stay unused); a length-2 dimension's two
        parallel physical links (BG/Q) fold into doubled capacity, exactly
        mirroring :func:`repro.network.netsim.link_capacities`.  ``wrap``
        affects bisection accounting, not the routed incidence — DOR
        always routes the full ring, matching ``route_dor``.
        """
        dims = self.dims
        n = self.num_cells
        d = len(dims)
        cells = np.arange(n, dtype=np.int64)
        coords = np.stack(np.unravel_index(cells, dims), axis=1) if d else cells[:, None]
        link, src, dst, cap = [], [], [], []
        for k, a in enumerate(dims):
            if a <= 1:
                continue
            c = 2.0 * self.link_bw if (a == 2 and self.double_link_on_2) else self.link_bw
            for direction, step in ((0, 1), (1, -1)):
                nb = coords.copy()
                nb[:, k] = (nb[:, k] + step) % a
                link.append((k * 2 + direction) * n + cells)
                src.append(cells)
                dst.append(np.ravel_multi_index(tuple(nb.T), dims))
                cap.append(np.full(n, c))
        empty = np.zeros(0, dtype=np.int64)
        return LinkTable(
            link=np.concatenate(link) if link else empty,
            src=np.concatenate(src) if src else empty.copy(),
            dst=np.concatenate(dst) if dst else empty.copy(),
            capacity=np.concatenate(cap) if cap else np.zeros(0),
            n_slots=2 * d * n,
        )


@dataclass(frozen=True)
class Torus:
    """A fully-wrapped D-dimensional torus graph (the paper's object).

    Thin compatibility wrapper over :mod:`repro.network.geometry`; all edge
    counting follows the Blue Gene/Q double-link convention.  Prefer
    ``TorusFabric.bgq(dims)`` for new bandwidth-aware code.
    """

    dims: Geometry

    def __init__(self, dims: Iterable[int]):
        object.__setattr__(self, "dims", canonical(dims))

    @property
    def D(self) -> int:
        return len(self.dims)

    @property
    def num_vertices(self) -> int:
        return volume(self.dims)

    @property
    def degree(self) -> int:
        return geometry.degree(self.dims)

    @property
    def num_edges(self) -> int:
        return geometry.num_edges(self.dims)

    def fabric(self, link_bw: float = DEFAULT_LINK_BW) -> TorusFabric:
        """The equivalent bandwidth-aware fabric (BG/Q convention)."""
        return TorusFabric.bgq(self.dims, link_bw)

    def contains_cuboid(self, cuboid: Sequence[int]) -> bool:
        return geometry.contains_cuboid(self.dims, cuboid)

    def cuboid_cut(self, cuboid: Sequence[int]) -> int:
        return geometry.cuboid_cut(self.dims, cuboid)

    def cuboid_cut_aligned(self, sides: Sequence[int]) -> int:
        return geometry.cuboid_cut_aligned(self.dims, sides)

    def cuboid_interior(self, cuboid: Sequence[int]) -> int:
        return geometry.cuboid_interior(self.dims, cuboid)

    def sub_cuboids(self, size: int) -> Iterator[Geometry]:
        return geometry.sub_cuboids(self.dims, size)

    def bisection_links(self) -> int:
        return geometry.bisection_links(self.dims)


# ---------------------------------------------------------------------------
# HyperX: a clique per dimension (the Hamming graph H(S_1, ..., S_D)).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class HyperXFabric(Fabric):
    """A HyperX fabric: per-dimension diameter-1 all-to-all wiring.

    Every cell connects directly to every other cell of each of its
    dimension lines (the Hamming graph — Ahn et al.'s HyperX; Cano et
    al.'s resource-allocation setting), with an optional per-dimension
    link multiplicity ``K_k`` (trunked parallel links fold into
    capacity).  Cut structure is the *opposite* of a torus: covering a
    dimension removes its whole cut contribution, so elongated boxes have
    the largest internal bisection (see :mod:`repro.network.hamming`).

    >>> hx = HyperXFabric((16, 4))
    >>> hx.num_cells, hx.degree, hx.bisection_links()
    (64, 18, 64)
    >>> hx.sub_fabric((4, 4)).bisection_links()  # compact box: 4x worse
    16
    """

    dims: Tuple[int, ...]
    link_multiplicity: Optional[Tuple[int, ...]] = None  # K_k, default all 1
    link_bw: float = DEFAULT_LINK_BW  # bytes/s per (single) link per direction

    # Box closure: a minimal (or DAL) path corrects one coordinate per hop,
    # straight to its destination value, so it never leaves the box.
    boxes_share_links = False
    routing_modes = ("minimal", "dal")
    # Pairing never contends across diameter-1 lines; all-to-all stresses
    # the internal bisection as pairing does on a torus.
    exchange_pattern = "all_to_all"
    # A size-2 line is one clique link; trunking folds into capacity.
    double_link_on_2 = False

    def __post_init__(self):
        object.__setattr__(self, "dims", tuple(int(a) for a in self.dims))
        if any(a < 1 for a in self.dims):
            raise ValueError(f"dims must be >= 1, got {self.dims}")
        mult = self.link_multiplicity
        mult = (1,) * len(self.dims) if mult is None else tuple(int(k) for k in mult)
        if len(mult) != len(self.dims) or any(k < 1 for k in mult):
            raise ValueError(
                f"link_multiplicity {self.link_multiplicity} must be one "
                f"positive entry per dim of {self.dims}"
            )
        object.__setattr__(self, "link_multiplicity", mult)

    # -- basic quantities ------------------------------------------------------
    @property
    def num_chips(self) -> int:
        """Alias of :attr:`Fabric.num_cells` (fabric-API symmetry)."""
        return self.num_cells

    @property
    def num_vertices(self) -> int:
        """Alias of :attr:`Fabric.num_cells` for graph-flavoured callers."""
        return self.num_cells

    @property
    def degree(self) -> int:
        """Links per cell: ``sum_k K_k * (S_k - 1)``."""
        return hamming.hamming_degree(self.dims, self.link_multiplicity)

    def bisection_links(self) -> int:
        """Exact internal bisection: the Lindsey lex half-set's cut (see
        :func:`repro.network.hamming.hamming_bisection_links`)."""
        return hamming.hamming_bisection_links(self.dims, self.link_multiplicity)

    def bisection_bandwidth(self) -> float:
        """Bytes/s across the bisection, both directions of each link."""
        return 2.0 * self.bisection_links() * self.link_bw

    def contains_cuboid(self, cuboid: Sequence[int]) -> bool:
        """Whether an aligned box with these sides fits (up to rotation) —
        any ``c_k <= S_k`` subset of coordinates spans a valid sub-box in
        a clique dimension, so this is the sorted-containment test."""
        return geometry.contains_cuboid(self.dims, cuboid)

    # -- the Fabric interface --------------------------------------------------
    def links(self) -> LinkTable:
        """Directed clique links.  Dense id layout: dimension k occupies
        the slot block ``N * sum_{i<k} S_i``, and the link from cell
        ``u`` to destination coordinate ``j`` in dim k has slot
        ``block_k + flat(u) * S_k + j`` — the ``j == u_k`` self-slots
        stay unused.  Capacity is ``K_k * link_bw`` (trunking folds in).
        """
        dims = self.dims
        n = self.num_cells
        cells = np.arange(n, dtype=np.int64)
        coords = np.stack(np.unravel_index(cells, dims), axis=1)
        link, src, dst, cap = [], [], [], []
        base = 0
        for k, a in enumerate(dims):
            if a > 1:
                for j in range(a):
                    take = coords[:, k] != j
                    nb = coords[take].copy()
                    nb[:, k] = j
                    link.append(base + cells[take] * a + j)
                    src.append(cells[take])
                    dst.append(np.ravel_multi_index(tuple(nb.T), dims))
                    cap.append(
                        np.full(int(take.sum()), self.link_multiplicity[k] * self.link_bw)
                    )
            base += n * a
        empty = np.zeros(0, dtype=np.int64)
        return LinkTable(
            link=np.concatenate(link) if link else empty,
            src=np.concatenate(src) if src else empty.copy(),
            dst=np.concatenate(dst) if dst else empty.copy(),
            capacity=np.concatenate(cap) if cap else np.zeros(0),
            n_slots=n * sum(dims),
        )

    def canonical(self) -> "HyperXFabric":
        """The same Hamming graph with ``dims`` sorted descending
        (multiplicities permuted along)."""
        order = sorted(range(len(self.dims)), key=lambda k: -self.dims[k])
        if order == list(range(len(self.dims))):
            return self
        return HyperXFabric(
            tuple(self.dims[k] for k in order),
            tuple(self.link_multiplicity[k] for k in order),
            self.link_bw,
        )

    def check_unit_node_dims(self, unit_node_dims: Optional[Sequence[int]]) -> None:
        """Node scaling is the Blue Gene/Q torus convention; HyperX takes
        allocation-unit boxes directly."""
        if unit_node_dims is not None:
            raise ValueError(
                "unit_node_dims is the BG/Q torus node-scaling convention; "
                "HyperX fabrics take allocation-unit boxes directly"
            )

    def box_bisections(
        self, geometries, unit_node_dims: Optional[Sequence[int]] = None
    ) -> np.ndarray:
        """Each box is its own Hamming graph (:meth:`sub_fabric`), whose
        bisection is the exact Lindsey half-set cut."""
        self.check_unit_node_dims(unit_node_dims)
        return np.array(
            [
                self.sub_fabric(tuple(int(x) for x in g)).bisection_links()
                for g in np.asarray(geometries)
            ],
            dtype=np.int64,
        )

    def exchange_box(
        self, geometry_: Sequence[int], unit_node_dims: Optional[Sequence[int]] = None
    ) -> "HyperXFabric":
        """The box's own sub-fabric: all-to-all runs inside it."""
        self.check_unit_node_dims(unit_node_dims)
        return self.sub_fabric(geometry_)

    def exchange_max_load(self) -> float:
        """All-to-all closed form
        (:func:`repro.network.hamming.hyperx_all_to_all_max_load`)."""
        return hamming.hyperx_all_to_all_max_load(self)

    def pairing_time(self, bytes_per_node: float, node_dims: Sequence[int]) -> float:
        """Halving-doubling partners differ in one coordinate of the split
        (longest) dimension, so every pair has its own direct clique link:
        one contention-free stage over a ``K_k``-trunked link.  A box is
        priced on its own dims; ``node_dims`` equals them."""
        sides = self.dims
        if max(sides) <= 1:
            return 0.0
        k = max(range(len(sides)), key=lambda i: sides[i])
        return bytes_per_node / (self.link_bw * self.link_multiplicity[k])

    def ring_equivalent(self) -> "TorusFabric":
        """Wrapped-torus stand-in for pricing ring schedules on this box.

        A ring pass along one dim of a clique uses one direct link per hop
        stage, exactly like a fully-wrapped torus dim — so ring-collective
        times on ``H(S)`` equal those on the wrapped torus of the same dims
        with per-link bandwidth ``K_k * link_bw`` (exact when the trunking
        is uniform; the min-multiplicity floor makes the price conservative
        otherwise).  Single links on length 2: a size-2 clique dim has its
        ``K_k`` trunked links already counted in the bandwidth scale, not
        the torus's two parallel wrap links.
        """
        bw = self.link_bw * min(self.link_multiplicity)
        return TorusFabric(
            self.dims, (True,) * len(self.dims), bw, double_link_on_2=False
        )

    def line_cuts(self, sides: np.ndarray) -> np.ndarray:
        """``s`` cells of a ``K_k``-trunked clique of ``S_k`` cells cut
        ``K_k * s * (S_k - s)`` links."""
        S = np.asarray(sides, dtype=np.int64)
        av = np.array(self.dims, dtype=np.int64)[None, :]
        mult = np.array(self.link_multiplicity, dtype=np.int64)[None, :]
        return mult * S * (av - S)

    def subset_cut_bound(self, t: int) -> float:
        """The Lindsey/edge-identity bound
        (:func:`repro.network.hamming.hamming_subset_bound`), exact under
        uniform link multiplicity."""
        return float(hamming.hamming_subset_bound(self.dims, t, self.link_multiplicity))

    @property
    def load_shape(self) -> Tuple[int, ...]:
        """Flat ``(N * sum(S_k),)``: the dense slot layout of :meth:`links`."""
        return (self.num_cells * sum(self.dims),)

    def slot_label(self, slot: int) -> Tuple[int, int, Tuple[int, ...]]:
        """Decode ``block_k + flat(cell) * S_k + j`` (see :meth:`links`)."""
        i = int(slot)
        base = 0
        for k, a in enumerate(self.dims):
            if i < base + self.num_cells * a:
                rel = i - base
                cell = np.unravel_index(rel // a, self.dims)
                return k, rel % a, tuple(int(c) for c in cell)
            base += self.num_cells * a
        raise ValueError(f"slot {slot} is outside the {base} slots of H{self.dims}")

    def sub_fabric(self, sides: Sequence[int]) -> "HyperXFabric":
        """The fabric of an aligned sub-box: any ``c_k``-subset of a
        clique dimension is itself a ``K_{c_k}`` clique, so a HyperX
        sub-box is the Hamming graph ``H(c)`` — wrap semantics never
        enter (contrast :func:`slice_fabric`).  Sides match machine
        dimensions tightest-fit and inherit their multiplicities.
        """
        g, picks = _fit_sides(self.dims, sides, "sub-box", "fabric")
        mult = tuple(self.link_multiplicity[i] for i in picks)
        return HyperXFabric(g, mult, self.link_bw)


def _fit_sides(
    dims: Sequence[int], sides: Sequence[int], what: str, where: str
) -> Tuple[Geometry, List[int]]:
    """Canonical sides padded to ``len(dims)``, and the dimension each is
    matched to, tightest fit first; ``ValueError`` when they do not fit."""
    g = canonical(sides)
    g = g + (1,) * (len(dims) - len(g))
    if len(g) > len(dims):
        raise ValueError(f"{what} {g} has more dims than {where} {tuple(dims)}")
    avail = sorted(range(len(dims)), key=lambda i: dims[i])
    picks: List[int] = []
    for side in g:
        pick = next((i for i in avail if i not in picks and dims[i] >= side), None)
        if pick is None:
            raise ValueError(f"{what} {g} does not fit in {where} {tuple(dims)}")
        picks.append(pick)
    return g, picks


# ---------------------------------------------------------------------------
# Slice planning (the paper's technique at the job level).
# ---------------------------------------------------------------------------
def _require_ring_fabric(pod, where: str) -> None:
    """Slice planning computes wrap-aware torus bisections; anything
    without per-dim ring structure (e.g. :class:`HyperXFabric`) would get
    silently wrong geometries, so fail loudly instead."""
    if not isinstance(pod, TorusFabric):
        raise TypeError(
            f"{where} requires a TorusFabric (per-dimension ring structure with "
            f"wrap semantics); got {type(pod).__name__} — for HyperX fabrics use "
            f"HyperXFabric.sub_fabric / repro.network.isoperimetry.ranked_geometries"
        )


def slice_fabric(pod: TorusFabric, geometry_: Sequence[int]) -> TorusFabric:
    """The fabric of a cuboid slice allocated from a pod.

    TPU semantics: wrap in a dimension only where the slice covers the full
    (wrapped) pod dimension.  Slice sides are matched to pod dims tightest-fit.
    Raises ``TypeError`` for fabrics without per-dim ring structure.
    """
    _require_ring_fabric(pod, "slice_fabric")
    g, picks = _fit_sides(pod.dims, geometry_, "slice", "pod")
    wrap = tuple(pod.wrap[i] and side == pod.dims[i] for side, i in zip(g, picks))
    return TorusFabric(g, wrap, pod.link_bw, pod.double_link_on_2)


def ranked_slice_geometries(pod: TorusFabric, chips: int) -> List[Tuple[Geometry, int]]:
    """All cuboid slice geometries of the requested size that fit the pod,
    as (geometry, bisection_links) pairs, best first (max bisection, ties
    broken toward the lexicographically-smallest canonical geometry).  This
    single ranking backs both the geometry-only planner
    (:func:`best_slice_geometry`) and the occupancy-aware planner
    (``repro.launch.mesh.plan_slice``), so they cannot drift apart.
    Each slice's bisection is the exact wrap-aware :func:`slice_fabric`
    computation.  Raises ``TypeError`` for fabrics without per-dim ring
    structure."""
    _require_ring_fabric(pod, "ranked_slice_geometries")
    ranked = sorted(
        (
            (g, slice_fabric(pod, g).bisection_links())
            for g in geometry.sub_cuboids(pod.dims, chips)
        ),
        key=lambda t: (-t[1], t[0]),
    )
    if not ranked:
        raise ValueError(f"no cuboid slice of {chips} chips fits in pod {pod.dims}")
    return ranked


def best_slice_geometry(pod: TorusFabric, chips: int) -> Tuple[Geometry, int]:
    """Among all cuboid slices of the requested size that fit the pod, return
    the geometry with maximal internal bisection (links)."""
    return ranked_slice_geometries(pod, chips)[0]


def worst_slice_geometry(pod: TorusFabric, chips: int) -> Tuple[Geometry, int]:
    """The fitting cuboid slice with *minimal* internal bisection (links) —
    the adversarial baseline of the avoidable-contention ratio."""
    _require_ring_fabric(pod, "worst_slice_geometry")
    worst: Optional[Tuple[Geometry, int]] = None
    for g in geometry.sub_cuboids(pod.dims, chips):
        fab = slice_fabric(pod, g)
        b = fab.bisection_links()
        if worst is None or b < worst[1] or (b == worst[1] and g > worst[0]):
            worst = (g, b)
    if worst is None:
        raise ValueError(f"no cuboid slice of {chips} chips fits in pod {pod.dims}")
    return worst
