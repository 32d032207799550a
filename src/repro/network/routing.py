"""Vectorized dimension-ordered routing (DOR) link-load engine.

Models minimal dimension-ordered routing on a torus and computes per-directed
-link loads for arbitrary batches of ``(src, dst, vol)`` traffic with NumPy
array operations — no per-hop Python loops.  The completion time of a
bulk-synchronous communication phase is estimated as

    T = max_link_load / link_bandwidth

which is exact for the bisection-pairing benchmark of the paper (each node
exchanges fixed-size messages with the node at maximal hop distance) and a
good model for any contention-bound pattern.

Three levels of machinery:

* :func:`route_dor` — the vectorized engine.  For each dimension it reduces
  every message to a cyclic link segment ``(ring, start, hops, direction)``
  and accumulates all segments at once via a difference-array + bincount +
  cumsum sweep: O(M + N) array work total instead of O(M * hops) Python
  steps.
* :class:`LinkLoads` — the historical accumulate-then-query API, now backed
  by the vectorized engine (the old per-hop walker survives only as a test
  reference under ``tests/reference_dor.py``).
* :func:`all_to_all_max_load` (and, in :mod:`repro.network.geometry`,
  ``uniform_offset_max_load`` / ``pairing_max_load``) — O(D) closed forms
  for translation-invariant patterns, exact by symmetry, cross-checked
  against the engine in the test suite.

The HyperX engine (:func:`route_hyperx`, minimal and DAL) lives here too;
:func:`repro.network.netsim.build_paths` picks the engine by the fabric's
routing mode.

Tie-breaking: when the hop distance along a ring is exactly half the ring
length, minimal routing may use either direction.  ``split_ties=True``
(default) splits the volume evenly — this models BG/Q's and TPU ICI's
adaptive/balanced routing and is what the paper's predictions assume.

Dimensions of length 2 have *two* physical links between each vertex pair
under the Blue Gene/Q convention; traffic is balanced across them, halving
the effective load (``double_link_on_2`` in :func:`max_link_load`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .backend import resolve_backend
from .fabric import HyperXFabric
from .geometry import bisection_links, canonical, pairing_max_load, volume

Coord = Tuple[int, ...]

__all__ = [
    "Coord",
    "LinkLoads",
    "PairingPrediction",
    "all_to_all_max_load",
    "hyperx_max_link_load",
    "max_link_load",
    "pairing_speedup",
    "predict_pairing_time",
    "route_dor",
    "route_hyperx",
    "simulate_pattern",
]


# ---------------------------------------------------------------------------
# The vectorized engine.
# ---------------------------------------------------------------------------
def route_dor(
    dims: Sequence[int],
    src: np.ndarray,
    dst: np.ndarray,
    vol: np.ndarray,
    split_ties: bool = True,
    backend: Optional[str] = None,
) -> np.ndarray:
    """Per-directed-link loads for a batch of messages under DOR routing.

    Arguments
    ---------
    dims : torus dimension lengths (length D)
    src, dst : int arrays of shape (M, D) — message endpoints
    vol : float array of shape (M,) (or scalar) — message volumes
    split_ties : split exactly-antipodal ring traffic across both directions
    backend : ``"numpy"`` (default) or ``"xla"`` — see
        :func:`repro.network.backend.resolve_backend`; both produce the
        identical load tensor (exactly, for integer/dyadic volumes)

    Returns
    -------
    loads : float array of shape (D, 2, *dims); ``loads[k, d, *v]`` is the
        volume on the link leaving vertex v in dimension k, direction d
        (0: +1, 1: -1).  Raw link loads — double-link normalisation is a
        query-time concern (:func:`max_link_load`).
    """
    dims = tuple(int(a) for a in dims)
    D = len(dims)
    src = np.atleast_2d(np.asarray(src, dtype=np.int64))
    dst = np.atleast_2d(np.asarray(dst, dtype=np.int64))
    if src.shape != dst.shape or src.shape[1] != D:
        raise ValueError(f"src/dst must have shape (M, {D}); got {src.shape}/{dst.shape}")
    M = src.shape[0]
    vol = np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,))
    loads = np.zeros((D, 2) + dims, dtype=np.float64)
    if M == 0:
        return loads
    if resolve_backend(backend) == "xla":
        from .backend import xla_route_loads

        return xla_route_loads(dims, src, dst, vol, split_ties)

    for k, a in enumerate(dims):
        if a == 1:
            continue
        # DOR: dims < k already routed (current coord = dst), dims > k still
        # at the source coordinate.
        other_coords = [dst[:, j] for j in range(k)] + [src[:, j] for j in range(k + 1, D)]
        other_dims = dims[:k] + dims[k + 1:]
        if other_coords:
            line = np.ravel_multi_index(other_coords, other_dims)
        else:
            line = np.zeros(M, dtype=np.int64)
        n_lines = volume(other_dims) if other_dims else 1

        s = src[:, k]
        delta = dst[:, k] - s
        np.add(delta, a, out=delta, where=delta < 0)  # delta mod a, branch-free
        rev = a - delta
        hops = np.minimum(delta, rev)
        tie = delta * 2 == a
        fwd = delta <= rev  # ties route forward in the primary segment

        # Primary segment: every message contributes exactly one cyclic link
        # segment (start, hops, direction); ties carry half volume when split,
        # and delta == 0 messages carry zero (hops == 0 would otherwise leave
        # a stray +v when the em != 0 cancellation test coincides with ring
        # position 0).
        v1 = np.where(tie, vol * (0.5 if split_ties else 1.0), vol)
        v1[hops == 0] = 0.0
        # forward: links leaving s, s+1, ..., s+hops-1; backward: links
        # leaving s, s-1, ..., s-hops+1 == the cyclic segment of length hops
        # starting at (s - hops + 1) mod a in the '-' load plane.
        bstart = s - hops + 1
        np.add(bstart, a, out=bstart, where=bstart < 0)
        start = np.where(fwd, s, bstart)
        base = line * a
        np.add(base, n_lines * a, out=base, where=~fwd)  # '-' plane offset

        seg_start = [start]
        seg_hops = [hops]
        seg_vol = [v1]
        seg_base = [base]
        if split_ties and tie.any():
            # Secondary segment: the backward half of each split tie.
            seg_start.append(bstart[tie])
            seg_hops.append(hops[tie])
            seg_vol.append(vol[tie] * 0.5)
            seg_base.append(n_lines * a + line[tie] * a)

        if len(seg_start) > 1:
            start = np.concatenate(seg_start)
            hops = np.concatenate(seg_hops)
            v = np.concatenate(seg_vol)
            base = np.concatenate(seg_base)
        else:
            v = v1

        # Difference-array accumulation over (direction, line, ring position):
        # a segment [start, start+hops) on the ring adds +v at start and -v at
        # end (mod a); a wrapped segment additionally covers the ring prefix,
        # handled by a +v at position 0 (weight-zeroed otherwise).  A single
        # cumsum then recovers the loads.  hops <= floor(a/2) < a, so no
        # segment covers the whole ring.
        end = start + hops
        wrapped = end > a  # segment covers the ring prefix [0, end - a)
        em = end
        np.subtract(em, a, out=em, where=end >= a)  # end mod a (end < 2a)
        n_seg = start.shape[0]
        idx = np.empty(3 * n_seg, dtype=np.int64)
        w = np.empty(3 * n_seg, dtype=np.float64)
        np.add(base, start, out=idx[:n_seg])
        w[:n_seg] = v
        np.add(base, em, out=idx[n_seg: 2 * n_seg])
        np.negative(v, out=w[n_seg: 2 * n_seg])
        w[n_seg: 2 * n_seg][em == 0] = 0.0
        idx[2 * n_seg:] = base
        w2 = w[2 * n_seg:]
        w2[:] = 0.0
        np.copyto(w2, v, where=wrapped)
        diff = np.bincount(idx, weights=w, minlength=2 * n_lines * a)
        ring_loads = np.cumsum(diff.reshape(2, n_lines, a), axis=-1)
        # Clamp accumulated float error on positions after all segments ended.
        np.maximum(ring_loads, 0.0, out=ring_loads)
        # Reshape (n_lines, a) back to the torus layout with axis k last,
        # then move it home.
        full = ring_loads.reshape((2,) + other_dims + (a,))
        loads[k] = np.moveaxis(full, -1, 1 + k)
    return loads


def max_link_load(
    dims: Sequence[int], loads: np.ndarray, double_link_on_2: bool = True
) -> float:
    """Maximum per-physical-link load of a :func:`route_dor` result.

    Under the Blue Gene/Q convention a dimension of length 2 has two parallel
    links per vertex pair and traffic balances across them, halving the
    effective load; TPU-style fabrics pass ``double_link_on_2=False``.
    """
    dims = tuple(dims)
    m = 0.0
    for k, a in enumerate(dims):
        if a == 1:
            continue
        scale = 0.5 if (a == 2 and double_link_on_2) else 1.0
        m = max(m, scale * float(loads[k].max()))
    return m


@dataclass
class LinkLoads:
    """Directed-link load accounting on a torus under DOR routing.

    API-compatible with the historical per-hop walker, but batched: paths are
    buffered and routed in one vectorized sweep on first query.  Use
    :meth:`add_batch` to feed array traffic directly (preferred).
    """

    dims: Tuple[int, ...]
    split_ties: bool = True
    double_link_on_2: bool = True
    _src: List[np.ndarray] = field(default_factory=list, repr=False)
    _dst: List[np.ndarray] = field(default_factory=list, repr=False)
    _vol: List[np.ndarray] = field(default_factory=list, repr=False)
    _loads: Optional[np.ndarray] = field(default=None, repr=False)

    def __post_init__(self):
        self.dims = tuple(int(a) for a in self.dims)

    def add_path(self, src: Coord, dst: Coord, vol: float) -> None:
        """Route vol from src to dst (buffered; computed lazily)."""
        self.add_batch([src], [dst], [vol])

    def add_batch(
        self,
        src: Sequence[Sequence[int]],
        dst: Sequence[Sequence[int]],
        vol,
    ) -> None:
        src = np.atleast_2d(np.asarray(src, dtype=np.int64))
        dst = np.atleast_2d(np.asarray(dst, dtype=np.int64))
        vol = np.broadcast_to(np.asarray(vol, dtype=np.float64), (src.shape[0],))
        self._src.append(src)
        self._dst.append(dst)
        self._vol.append(np.array(vol))
        self._loads = None

    def _compute(self) -> np.ndarray:
        if self._loads is None:
            if self._src:
                self._loads = route_dor(
                    self.dims,
                    np.concatenate(self._src),
                    np.concatenate(self._dst),
                    np.concatenate(self._vol),
                    split_ties=self.split_ties,
                )
            else:
                self._loads = np.zeros((len(self.dims), 2) + self.dims)
        return self._loads

    @property
    def loads(self) -> List[List[np.ndarray]]:
        """Historical layout: loads[k][d] has the torus shape.

        Unlike the old per-hop walker these are *snapshots* of the lazily
        computed load tensor, not live accumulators: a later ``add_path`` /
        ``add_batch`` triggers a fresh routing pass and previously returned
        arrays do not update (and must not be mutated).  Re-read the
        property (or :meth:`load_array`) after adding traffic.
        """
        arr = self._compute()
        return [[arr[k, d] for d in range(2)] for k in range(len(self.dims))]

    def load_array(self) -> np.ndarray:
        """The (D, 2, *dims) load tensor."""
        return self._compute()

    def max_load(self) -> float:
        """Maximum load on any directed physical link (double links halve)."""
        return max_link_load(self.dims, self._compute(), self.double_link_on_2)

    def total_hop_volume(self) -> float:
        return float(self._compute().sum())


def simulate_pattern(
    dims: Sequence[int],
    traffic: Iterable[Tuple[Coord, Coord, float]],
    split_ties: bool = True,
) -> LinkLoads:
    """Route explicit (src, dst, vol) traffic; accepts any iterable of triples."""
    ll = LinkLoads(tuple(dims), split_ties=split_ties)
    triples = list(traffic)
    if triples:
        srcs, dsts, vols = zip(*triples)
        ll.add_batch(np.asarray(srcs), np.asarray(dsts), np.asarray(vols, dtype=np.float64))
    return ll


# ---------------------------------------------------------------------------
# Closed forms for translation-invariant patterns.
# ---------------------------------------------------------------------------
def all_to_all_max_load(
    dims: Sequence[int],
    vol_per_pair: float = 1.0,
    split_ties: bool = True,
    double_link_on_2: bool = True,
) -> float:
    """Max link load of a full all-to-all (every ordered pair exchanges
    vol_per_pair), computed analytically for DOR routing.

    Under DOR every message routes its whole dim-k distance on exactly one
    dim-k ring, and by translation symmetry each ring sees every (start,
    offset) combination equally often: with N = prod(dims), each of the N/a_k
    rings carries N*a_k messages, N per ordered ring offset delta.  The
    per-direction hop volumes are counted *explicitly* (an offset delta
    strictly below a/2 walks delta forward links; strictly above, a - delta
    backward links; the exact-half tie is split or sent forward), rather than
    assuming the two directions balance — on every torus the reflection
    delta <-> a - delta makes them equal when ties are split, but with
    ``split_ties=False`` the forward direction carries the whole antipodal
    volume and the directions genuinely differ.  Cross-checked against the
    exact simulator (including small odd tori) in the test suite.
    """
    dims = tuple(dims)
    n = volume(dims)
    worst = 0.0
    for k, a in enumerate(dims):
        if a == 1:
            continue
        fwd_hop_vol = 0.0  # per-ring hop volume in the + direction
        bwd_hop_vol = 0.0
        for delta in range(1, a):
            d = min(delta, a - delta)
            if 2 * delta == a:  # antipodal tie
                if split_ties:
                    fwd_hop_vol += n * d / 2.0
                    bwd_hop_vol += n * d / 2.0
                else:
                    fwd_hop_vol += n * d
            elif delta < a - delta:
                fwd_hop_vol += n * d
            else:
                bwd_hop_vol += n * d
        # Uniform over the a links of each direction of the ring.
        load = max(fwd_hop_vol, bwd_hop_vol) * vol_per_pair / a
        if a == 2 and double_link_on_2:
            load /= 2.0
        worst = max(worst, load)
    return worst


# ---------------------------------------------------------------------------
# Paper experiment A: the bisection-pairing benchmark.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PairingPrediction:
    dims: Tuple[int, ...]
    max_link_load: float  # per unit message volume
    time_per_volume: float  # seconds per byte of per-pair message volume
    bisection_links: int


def predict_pairing_time(
    dims: Sequence[int],
    message_bytes: float,
    link_bw_bytes_s: float,
    split_ties: bool = True,
    double_link_on_2: bool = True,
) -> PairingPrediction:
    """Predicted completion time of one round of the pairing benchmark."""
    dims = canonical(dims)
    load = pairing_max_load(
        dims, split_ties=split_ties, double_link_on_2=double_link_on_2
    )
    return PairingPrediction(
        dims=dims,
        max_link_load=load,
        time_per_volume=load / link_bw_bytes_s,
        bisection_links=bisection_links(dims),
    )


def pairing_speedup(
    dims_a: Sequence[int], dims_b: Sequence[int], split_ties: bool = True
) -> float:
    """Predicted execution-time ratio T(a) / T(b) of the pairing benchmark
    between two equal-size partition geometries (paper Figures 3-4)."""
    a = predict_pairing_time(dims_a, 1.0, 1.0, split_ties)
    b = predict_pairing_time(dims_b, 1.0, 1.0, split_ties)
    return a.max_link_load / b.max_link_load


# ---------------------------------------------------------------------------
# HyperX routing: minimal (dimension-ordered direct hops) and DAL.
# ---------------------------------------------------------------------------
def _hyperx_blocks(dims: Tuple[int, ...]) -> Tuple[List[int], int]:
    """Per-dimension slot-block starts of the HyperX link-id layout
    (matching :meth:`repro.network.fabric.HyperXFabric.links`) and the
    total dense slot count ``N * sum(S_k)``."""
    n = volume(dims)
    bases: List[int] = []
    b = 0
    for a in dims:
        bases.append(b)
        b += n * a
    return bases, b


def _hyperx_order_links(
    dims: Tuple[int, ...],
    src: np.ndarray,
    dst: np.ndarray,
    order: Sequence[int],
) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Per-hop link incidence of every message under one dimension order.

    In HyperX each dimension correction is a single direct hop within the
    current cell's dim-k clique, so a message's path visits one link per
    differing coordinate.  Returns ``(link_ids, message_idx)`` pairs, one
    per dimension that any message hops in.
    """
    bases, _ = _hyperx_blocks(dims)
    cur = src.copy()
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for k in order:
        a = dims[k]
        if a > 1:
            idx = np.flatnonzero(cur[:, k] != dst[:, k])
            if idx.shape[0]:
                flat = np.ravel_multi_index(tuple(cur[idx].T), dims)
                out.append((bases[k] + flat * a + dst[idx, k], idx))
        cur[:, k] = dst[:, k]
    return out


def _hyperx_candidate_orders(D: int) -> List[Tuple[int, ...]]:
    """DAL's candidate dimension orders: the D cyclic rotations of the
    canonical order (rotation 0 *is* minimal routing).  Rotations reach
    every dimension as the first correction while keeping the candidate
    count linear in D."""
    base = tuple(range(D))
    return [base[r:] + base[:r] for r in range(max(D, 1))]


def _hyperx_flows(
    fabric: HyperXFabric,
    src: np.ndarray,
    dst: np.ndarray,
    vol: np.ndarray,
    mode: str = "minimal",
    rounds: int = 2,
    balance_rtol: float = 1e-9,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Expand messages into routed subflows on a HyperX fabric.

    Returns ``(msg, fvol, link_ids, flow_ids)`` in the shape
    :class:`repro.network.netsim.FlowPaths` consumes.  ``mode="minimal"``
    routes canonical dimension order (one subflow per message).
    ``mode="dal"`` is dimensionally-adaptive load-balanced routing:
    every message may *split* its volume across the candidate dimension
    orders, weighted by the inverse of each order's bottleneck link load
    under the current field, iterated ``rounds`` times from the minimal
    field.  Messages whose candidate bottlenecks are balanced (within
    ``balance_rtol``) keep the canonical minimal order exactly — so on a
    steady translation-invariant pattern (uniform field) DAL *is*
    minimal routing, bit for bit, mirroring the torus
    ``compare_routing`` finding; only genuinely skewed fields trigger
    splitting.  Fractional splitting (rather than 0/1 re-ordering) makes
    the iteration stable — simultaneous all-or-nothing switches
    oscillate on hotspots.
    """
    dims = fabric.dims
    D = len(dims)
    src = np.atleast_2d(np.asarray(src, dtype=np.int64))
    dst = np.atleast_2d(np.asarray(dst, dtype=np.int64))
    if src.shape != dst.shape or src.shape[1] != D:
        raise ValueError(f"src/dst must have shape (M, {D}); got {src.shape}/{dst.shape}")
    M = src.shape[0]
    vol = np.array(np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,)))
    _, n_slots = _hyperx_blocks(dims)
    empty = np.zeros(0, dtype=np.int64)
    if M == 0:
        return empty, np.zeros(0), empty.copy(), empty.copy()

    orders = _hyperx_candidate_orders(D)
    per_order = [_hyperx_order_links(dims, src, dst, o) for o in orders]

    if mode == "minimal":
        weights = np.zeros((M, len(orders)))
        weights[:, 0] = 1.0
    elif mode == "dal":
        weights = np.zeros((M, len(orders)))
        weights[:, 0] = 1.0
        tiny = 1e-300
        for _ in range(max(rounds, 1)):
            loads = np.zeros(n_slots)
            for r, hops in enumerate(per_order):
                w = weights[:, r] * vol
                for ids, idx in hops:
                    np.add.at(loads, ids, w[idx])
            cost = np.zeros((M, len(orders)))
            for r, hops in enumerate(per_order):
                for ids, idx in hops:
                    np.maximum.at(cost[:, r], idx, loads[ids])
            cmax = cost.max(axis=1)
            cmin = cost.min(axis=1)
            skewed = cmax - cmin > balance_rtol * np.maximum(cmax, tiny)
            inv = 1.0 / np.maximum(cost, tiny)
            frac = inv / inv.sum(axis=1, keepdims=True)
            weights[skewed] = frac[skewed]
            keep = ~skewed
            weights[keep] = 0.0
            weights[keep, 0] = 1.0
    else:
        raise ValueError(f"unknown HyperX routing mode {mode!r}; expected 'minimal' or 'dal'")

    msg_l: List[np.ndarray] = []
    fvol_l: List[np.ndarray] = []
    link_l: List[np.ndarray] = []
    flow_l: List[np.ndarray] = []
    f_base = 0
    for r, hops in enumerate(per_order):
        live = np.flatnonzero(weights[:, r] > 0.0)
        if not live.shape[0]:
            continue
        pos = np.full(M, -1, dtype=np.int64)
        pos[live] = f_base + np.arange(live.shape[0])
        msg_l.append(live)
        fvol_l.append(weights[live, r] * vol[live])
        for ids, idx in hops:
            sel = pos[idx] >= 0
            link_l.append(ids[sel])
            flow_l.append(pos[idx][sel])
        f_base += live.shape[0]
    return (
        np.concatenate(msg_l) if msg_l else empty,
        np.concatenate(fvol_l) if fvol_l else np.zeros(0),
        np.concatenate(link_l) if link_l else empty.copy(),
        np.concatenate(flow_l) if flow_l else empty.copy(),
    )


def route_hyperx(
    fabric: HyperXFabric,
    src: np.ndarray,
    dst: np.ndarray,
    vol,
    mode: str = "minimal",
    rounds: int = 2,
) -> np.ndarray:
    """Per-directed-link loads of a message batch on a HyperX fabric.

    Returns a flat ``(N * sum(S_k),)`` load vector in the dense link-id
    layout of :meth:`repro.network.fabric.HyperXFabric.links` (unused
    self-slots stay zero).  ``mode="minimal"`` corrects coordinates in
    canonical dimension order — every hop is direct, path length equals
    Hamming distance; ``mode="dal"`` additionally load-balances across
    dimension orders (see :func:`_hyperx_flows`).  The per-hop reference
    oracle lives in ``tests``/``benchmarks/bench_hyperx.py``; loads are
    exact sums, so engine and oracle agree bit-for-bit.

    >>> import numpy as np
    >>> hx = HyperXFabric((4, 4))
    >>> loads = route_hyperx(hx, np.array([[0, 0]]), np.array([[2, 3]]), 1.0)
    >>> float(loads.sum())   # two direct hops: dim 0 then dim 1
    2.0
    """
    M = np.atleast_2d(np.asarray(src)).shape[0]
    vol = np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,))
    msg, fvol, link_ids, flow_ids = _hyperx_flows(fabric, src, dst, vol, mode, rounds)
    _, n_slots = _hyperx_blocks(fabric.dims)
    if not link_ids.shape[0]:
        return np.zeros(n_slots)
    return np.bincount(link_ids, weights=fvol[flow_ids], minlength=n_slots)


def hyperx_max_link_load(fabric: HyperXFabric, loads: np.ndarray) -> float:
    """Max per-physical-link load of a :func:`route_hyperx` vector —
    dimension k's trunked ``K_k`` parallel links share their dim's
    traffic, dividing the effective load (the HyperX analogue of the
    torus double-link halving)."""
    dims = fabric.dims
    n = volume(dims)
    m = 0.0
    base = 0
    for k, a in enumerate(dims):
        block = loads[base: base + n * a]
        if block.shape[0]:
            m = max(m, float(block.max()) / fabric.link_multiplicity[k])
        base += n * a
    return m
