"""Vectorized flow-level torus network simulator — the dynamic validation leg.

The routing engine (:mod:`repro.network.routing`) *predicts* contention
statically: a bulk-synchronous phase takes ``T = max_link_load / link_bw``.
The paper's claims rest on a second leg — benchmarking experiments that
*validate* those predictions (§7 of the paper) — and this module is that leg
in simulation form: a discrete-time **flow-level** simulator that turns any
traffic pattern into per-flow completion times under max-min fair link
sharing, so speedup claims are derived from dynamics instead of pinned as
constants.

Model
-----
* Every message becomes one **flow** along a concrete minimal path.
  Antipodal ties (ring distance exactly half the ring) split into two
  half-volume subflows per tied dimension, matching the engine's
  ``split_ties=True`` accounting; a message completes when its last
  subflow drains.
* Paths come from one of two routers:

  - ``mode="dor"`` — dimension-ordered routing.  The enumerated links are
    load-identical to :func:`repro.network.routing.route_dor`
    (property-pinned in ``tests/test_netsim.py``).
  - ``mode="adaptive"`` — minimal-adaptive: each flow routes one whole
    dimension per round and picks, among its unrouted dimensions, the one
    whose first-hop link currently carries the least committed volume
    (directions stay minimal, so paths never lengthen).  This quantifies
    how much avoidable contention routing alone can recover: for
    translation-invariant patterns the answer is *none* — link loads are
    already uniform — which is the paper's argument for fixing partition
    geometry rather than the router.

  A HyperX fabric routes ``mode="minimal"`` or ``"dal"`` instead
  (:func:`build_paths` takes the modes from the fabric).

* Each simulation step shares every link's bandwidth **max-min fairly**
  among the flows crossing it: progressive filling over the link x flow
  incidence with ``np.bincount`` sweeps — no per-packet (or per-flow)
  Python loops.  Time then advances to the next flow completion, flows
  drain, and the loop repeats; the step count is bounded by the number of
  distinct completion times, not by a fixed tick width.
* Link capacities follow the fabric convention: a length-2 dimension has
  two parallel physical links under BG/Q (``double_link_on_2=True``,
  doubling its capacity) and a single link on TPU ICI.

Outputs are per-flow and per-message completion times, the makespan, a
per-step link-utilization timeline, and the measured **slowdown** versus
the zero-contention bound (the line-rate time of the largest single
message, ``max_m vol_m / link_bw`` — so for unit-volume patterns the
slowdown is exactly the paper's contention multiplier).

:func:`validate_prediction` packages the paper's validation experiment as
a property: for steady (translation-invariant) patterns the simulated
makespan equals ``max_link_load / link_bw`` exactly, and it can never beat
it (conservation through the most loaded link) — both are enforced by the
hypothesis suite in ``tests/test_netsim.py``.  :func:`simulate_phases`
runs phased collective schedules (e.g. ring all-reduce as ``2(n-1)``
dependent phases) so the closed forms in
:mod:`repro.network.collectives` can be cross-checked dynamically.

The per-flow pure-Python reference lives in ``tests/reference_netsim.py``;
``benchmarks/bench_netsim.py`` pins the vectorized speedup (>= 10x,
``BENCH_netsim.json``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import TRACER as _TRACER
from .backend import resolve_backend
from .fabric import as_fabric
from .geometry import volume
from .routing import _hyperx_flows, max_link_load

Coord = Tuple[int, ...]
Traffic = Tuple[np.ndarray, np.ndarray, np.ndarray]

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Flow expansion: messages -> minimal-path subflows.
# ---------------------------------------------------------------------------
def _expand_tie_flows(
    dims: Tuple[int, ...],
    src: np.ndarray,
    dst: np.ndarray,
    vol: np.ndarray,
    split_ties: bool,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Expand messages into minimal-path subflows.

    Returns ``(src, dst, vol, msg, fwd)``: per-subflow endpoints and
    volumes, the originating message index, and the chosen ring direction
    per dimension (``fwd[f, k]`` — True routes +1).  With ``split_ties``
    a message is duplicated once per antipodal-tie dimension, each copy
    carrying half the volume and one of the two directions (volume is
    conserved exactly); without, ties route forward, matching
    ``route_dor(split_ties=False)``.
    """
    d_arr = np.asarray(dims, dtype=np.int64)
    src = np.array(np.atleast_2d(np.asarray(src, dtype=np.int64)))
    dst = np.array(np.atleast_2d(np.asarray(dst, dtype=np.int64)))
    M = src.shape[0]
    vol = np.array(np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,)))
    msg = np.arange(M, dtype=np.int64)
    delta = (dst - src) % d_arr
    fwd = delta * 2 <= d_arr  # ties start forward; duplicates flip below
    if split_ties:
        for k, a in enumerate(dims):
            if a <= 1:
                continue
            tie = ((dst[:, k] - src[:, k]) % a) * 2 == a
            if not tie.any():
                continue
            vol[tie] *= 0.5
            idx = np.flatnonzero(tie)
            src = np.concatenate([src, src[idx]])
            dst = np.concatenate([dst, dst[idx]])
            vol = np.concatenate([vol, vol[idx]])
            msg = np.concatenate([msg, msg[idx]])
            fwd = np.concatenate([fwd, fwd[idx]])
            fwd[-idx.shape[0]:, k] = False
    return src, dst, vol, msg, fwd


def _strides(dims: Tuple[int, ...]) -> np.ndarray:
    """C-order ravel strides of the vertex grid."""
    s = np.ones(len(dims), dtype=np.int64)
    for k in range(len(dims) - 2, -1, -1):
        s[k] = s[k + 1] * dims[k + 1]
    return s


def _segment_links(
    a: int,
    stride: int,
    plane_base: np.ndarray,
    base_vflat: np.ndarray,
    start: np.ndarray,
    hops: np.ndarray,
    fwd: np.ndarray,
    flow_idx: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Enumerate the directed links of a batch of ring segments.

    A forward segment from ring position ``s`` of ``h`` hops uses the '+'
    links leaving ``s, s+1, .., s+h-1``; a backward one the '-' links
    leaving ``s, s-1, .., s-h+1`` — the same link sets ``route_dor``
    accumulates.  Returns flat link ids and the owning flow per link.
    """
    tot = int(hops.sum())
    if tot == 0:
        empty = np.zeros(0, dtype=np.int64)
        return empty, empty.copy()
    rep = np.repeat(np.arange(hops.shape[0]), hops)
    j = np.arange(tot) - np.repeat(np.cumsum(hops) - hops, hops)
    sgn = np.where(fwd, 1, -1)[rep]
    pos = (start[rep] + sgn * j) % a
    links = plane_base[rep] + base_vflat[rep] + pos * stride
    return links, flow_idx[rep]


@dataclass(frozen=True)
class FlowPaths:
    """The routed form of a traffic pattern: one entry per (flow, link).

    ``msg[f]`` maps subflow f back to its originating message, ``vol[f]``
    is the subflow volume (tie splits halve), and the parallel arrays
    ``link_ids`` / ``flow_ids`` are the link x flow incidence the
    simulator waterfills over.  Link ids index the flattened
    ``(D, 2, *dims)`` load tensor layout of ``route_dor``.
    """

    dims: Tuple[int, ...]
    n_messages: int
    msg: np.ndarray  # (F,) originating message per subflow
    vol: np.ndarray  # (F,) subflow volumes
    link_ids: np.ndarray  # (P,) flat directed-link ids
    flow_ids: np.ndarray  # (P,) owning subflow per entry
    mode: str = "dor"
    # Non-torus fabrics carry their own dense per-slot capacities (in units
    # of link_bw — parallel trunked links fold in); None keeps the historical
    # torus layout, whose capacities come from ``link_capacities`` instead.
    capacities: Optional[np.ndarray] = None

    @property
    def n_flows(self) -> int:
        """Number of subflows (>= number of messages when ties split)."""
        return int(self.vol.shape[0])

    def link_loads(self) -> np.ndarray:
        """Total routed volume per directed link — shaped ``(D, 2, *dims)``
        for torus paths (for ``mode="dor"`` this is exactly
        ``route_dor``'s tensor), or flat ``(L,)`` in the fabric's own link
        layout when the paths carry explicit ``capacities``."""
        if self.capacities is not None:
            return np.bincount(
                self.link_ids,
                weights=self.vol[self.flow_ids],
                minlength=self.capacities.shape[0],
            )
        n = volume(self.dims)
        flat = np.bincount(
            self.link_ids,
            weights=self.vol[self.flow_ids],
            minlength=2 * len(self.dims) * n,
        )
        return flat.reshape((len(self.dims), 2) + self.dims)

    def max_link_load(self, double_link_on_2: bool = True) -> float:
        """Max per-physical-link routed volume (double links halve; on
        explicit-capacity fabrics each slot's load is normalized by its
        relative capacity instead)."""
        if self.capacities is not None:
            loads = self.link_loads()
            pos = self.capacities > 0.0
            if not pos.any():
                return 0.0
            return float((loads[pos] / self.capacities[pos]).max())
        return max_link_load(self.dims, self.link_loads(), double_link_on_2)


def _dor_links(
    dims: Tuple[int, ...],
    src: np.ndarray,
    dst: np.ndarray,
    fwd: np.ndarray,
    hops: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray]:
    """Link incidence of already-expanded flows under dimension order."""
    strides = _strides(dims)
    n = volume(dims)
    cur = src.copy()
    all_links: List[np.ndarray] = []
    all_flows: List[np.ndarray] = []
    for k, a in enumerate(dims):
        if a <= 1:
            continue
        act = np.flatnonzero(hops[:, k] > 0)
        if act.shape[0]:
            s = cur[act, k]
            base_vflat = cur[act] @ strides - s * strides[k]
            plane = np.where(fwd[act, k], 2 * k, 2 * k + 1) * n
            links, flows = _segment_links(
                a, int(strides[k]), plane, base_vflat, s, hops[act, k], fwd[act, k], act
            )
            all_links.append(links)
            all_flows.append(flows)
        cur[:, k] = dst[:, k]
    empty = np.zeros(0, dtype=np.int64)
    return (
        np.concatenate(all_links) if all_links else empty,
        np.concatenate(all_flows) if all_flows else empty.copy(),
    )


def dor_paths(
    dims: Sequence[int],
    src: np.ndarray,
    dst: np.ndarray,
    vol,
    split_ties: bool = True,
) -> FlowPaths:
    """Dimension-ordered paths for a batch of messages.

    Link-for-link identical to what :func:`repro.network.routing.route_dor`
    accumulates (property-pinned): dimension k routes at coordinate
    ``(dst[:k], src[k:])``, ties split into half-volume subflows.
    """
    dims = tuple(int(a) for a in dims)
    src, dst, vol, msg, fwd = _expand_tie_flows(dims, src, dst, np.asarray(vol), split_ties)
    n_messages = int(msg.max()) + 1 if msg.shape[0] else 0
    d_arr = np.asarray(dims, dtype=np.int64)
    hops = np.minimum((dst - src) % d_arr, (src - dst) % d_arr)
    link_ids, flow_ids = _dor_links(dims, src, dst, fwd, hops)
    return FlowPaths(
        dims=dims,
        n_messages=n_messages,
        msg=msg,
        vol=vol,
        link_ids=link_ids,
        flow_ids=flow_ids,
        mode="dor",
    )


def _cyclic_prefixes(dims: Tuple[int, ...], loads: np.ndarray) -> List[List[np.ndarray]]:
    """Per-(dimension, direction) cumulative sums of a load tensor along
    its own axis (flattened C-order), so any cyclic segment sum reduces
    to two gathers plus an optional full-ring term."""
    out: List[List[np.ndarray]] = []
    for k in range(len(dims)):
        out.append(
            [np.ascontiguousarray(np.cumsum(loads[k, d], axis=k)).ravel() for d in (0, 1)]
        )
    return out


def _cyclic_segment_sums(
    prefix: List[List[np.ndarray]],
    k: int,
    a: int,
    stride: int,
    base_vflat: np.ndarray,
    start: np.ndarray,
    hops: np.ndarray,
    fwd: np.ndarray,
) -> np.ndarray:
    """Load-field sum over the cyclic segment ``[start, start + hops)`` of
    each flow's candidate ring, per the flow's direction plane."""
    end = start + hops - 1
    out = np.empty(start.shape[0])
    for d in (0, 1):
        m = fwd if d == 0 else ~fwd
        if not m.any():
            continue
        cs = prefix[k][d]
        b = base_vflat[m]
        s = start[m]
        e = end[m]
        t_end = cs[b + (e % a) * stride]
        t_sm1 = np.where(s > 0, cs[b + np.maximum(s - 1, 0) * stride], 0.0)
        ring = cs[b + (a - 1) * stride]
        out[m] = t_end - t_sm1 + np.where(e >= a, ring, 0.0)
    return out


def adaptive_paths(
    dims: Sequence[int],
    src: np.ndarray,
    dst: np.ndarray,
    vol,
    split_ties: bool = True,
    divert_margin: float = 0.75,
) -> FlowPaths:
    """Minimal-adaptive paths: per-flow least-loaded dimension order.

    Two passes.  Pass 1 routes everything with DOR and accumulates the
    steady link-load field the pattern would produce.  Pass 2 re-routes
    every flow against that frozen field: at each step the flow compares
    the *mean load along the whole candidate segment* of each unrouted
    dimension (cyclic prefix sums — no per-hop loops) and leaves DOR's
    lowest-dimension-first order only when some dimension is cheaper than
    the default by more than the ``divert_margin`` factor.  All decisions
    are simultaneous, so a translation-invariant pattern — whose load
    field, hence whose decisions, are translation-invariant — keeps
    exactly DOR's uniform loads and makespan: minimal-adaptive routing
    recovers *nothing* of the paper's geometry-induced contention, while
    genuinely skewed patterns (hotspot rows, bad permutations) do
    rebalance.  Directions stay minimal and ties still split, so the
    total hop volume always equals DOR's.
    """
    dims = tuple(int(a) for a in dims)
    src, dst, vol, msg, fwd = _expand_tie_flows(dims, src, dst, np.asarray(vol), split_ties)
    n_messages = int(msg.max()) + 1 if msg.shape[0] else 0
    d_arr = np.asarray(dims, dtype=np.int64)
    strides = _strides(dims)
    n = volume(dims)
    D = len(dims)
    hops = np.minimum((dst - src) % d_arr, (src - dst) % d_arr)

    # Pass 1: the steady DOR field of the expanded flows, held as
    # per-(dim, direction) cyclic prefix sums so pass 2 prices any
    # candidate segment with two gathers.
    links0, flows0 = _dor_links(dims, src, dst, fwd, hops)
    field = np.bincount(
        links0, weights=vol[flows0], minlength=2 * D * n
    ).reshape((D, 2) + dims)
    prefix = _cyclic_prefixes(dims, field)

    cur = src.copy()
    remaining = hops > 0
    all_links: List[np.ndarray] = []
    all_flows: List[np.ndarray] = []
    for _ in range(D):
        act = np.flatnonzero(remaining.any(axis=1))
        if not act.shape[0]:
            break
        cost = np.full((src.shape[0], D), np.inf)
        for k, a in enumerate(dims):
            rows = np.flatnonzero(remaining[:, k])
            if not rows.shape[0]:
                continue
            h = hops[rows, k]
            s = cur[rows, k]
            fw = fwd[rows, k]
            start = np.where(fw, s, (s - h + 1) % a)
            base_vflat = cur[rows] @ strides - s * strides[k]
            seg = _cyclic_segment_sums(
                prefix, k, a, int(strides[k]), base_vflat, start, h, fw
            )
            cost[rows, k] = seg / h
        best = np.argmin(cost, axis=1)
        default = np.argmax(remaining, axis=1)  # lowest remaining dim index
        rowsel = np.arange(src.shape[0])
        divert = cost[rowsel, best] < divert_margin * cost[rowsel, default]
        choice = np.where(divert, best, default)
        for k, a in enumerate(dims):
            g = act[np.flatnonzero((choice[act] == k) & remaining[act, k])]
            if not g.shape[0]:
                continue
            s = cur[g, k]
            base_vflat = cur[g] @ strides - s * strides[k]
            plane = np.where(fwd[g, k], 2 * k, 2 * k + 1) * n
            links, flows = _segment_links(
                a, int(strides[k]), plane, base_vflat, s, hops[g, k], fwd[g, k], g
            )
            all_links.append(links)
            all_flows.append(flows)
            cur[g, k] = dst[g, k]
            remaining[g, k] = False
    empty = np.zeros(0, dtype=np.int64)
    return FlowPaths(
        dims=dims,
        n_messages=n_messages,
        msg=msg,
        vol=vol,
        link_ids=np.concatenate(all_links) if all_links else empty,
        flow_ids=np.concatenate(all_flows) if all_flows else empty.copy(),
        mode="adaptive",
    )


def build_paths(
    fabric,
    traffic: Traffic,
    mode: Optional[str] = None,
    split_ties: bool = True,
) -> FlowPaths:
    """Route a ``(src, dst, vol)`` pattern on any fabric (or plain torus
    dims) with one of its :attr:`~repro.network.fabric.Fabric.routing_modes`,
    the first by default.

    A torus routes ``"dor"`` (:func:`dor_paths`) or ``"adaptive"``
    (:func:`adaptive_paths`) in the ``(D, 2, *dims)`` link layout of
    ``route_dor``.  HyperX routes ``"minimal"`` or ``"dal"`` with
    :func:`repro.network.routing.route_hyperx`'s flow expansion, and the
    paths carry the fabric's dense per-slot capacities, so the same
    max-min-fair drain prices trunked clique links.
    """
    fab = as_fabric(fabric)
    mode = mode or fab.routing_modes[0]
    if mode not in fab.routing_modes:
        base, adaptive = fab.routing_modes
        raise ValueError(
            f"unknown routing mode {mode!r}; expected {base!r} or {adaptive!r}"
        )
    src, dst, vol = traffic
    if mode == "dor":
        return dor_paths(fab.dims, src, dst, vol, split_ties=split_ties)
    if mode == "adaptive":
        return adaptive_paths(fab.dims, src, dst, vol, split_ties=split_ties)
    M = np.atleast_2d(np.asarray(src)).shape[0]
    volb = np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,))
    msg, fvol, link_ids, flow_ids = _hyperx_flows(fab, src, dst, volb, mode)
    return FlowPaths(
        dims=fab.dims,
        n_messages=M,
        msg=msg,
        vol=fvol,
        link_ids=link_ids,
        flow_ids=flow_ids,
        mode=mode,
        capacities=fab.links().dense_capacities() / fab.link_bw,
    )


# ---------------------------------------------------------------------------
# Link capacities and max-min fair sharing.
# ---------------------------------------------------------------------------
def link_capacities(
    dims: Sequence[int], link_bw: float = 1.0, double_link_on_2: bool = True
) -> np.ndarray:
    """Per-directed-link bandwidth, shaped ``(D, 2, *dims)``.

    A length-2 dimension has two parallel physical links per vertex pair
    under the BG/Q convention, doubling its capacity; TPU-style fabrics
    pass ``double_link_on_2=False``.
    """
    dims = tuple(int(a) for a in dims)
    cap = np.full((len(dims), 2) + dims, float(link_bw))
    if double_link_on_2:
        for k, a in enumerate(dims):
            if a == 2:
                cap[k] *= 2.0
    return cap


def _max_min_rates(
    flow_of_entry: np.ndarray,
    link_of_entry: np.ndarray,
    n_flows: int,
    n_links: int,
    cap: np.ndarray,
    active: np.ndarray,
) -> np.ndarray:
    """Max-min fair rates by progressive filling, fully vectorized.

    All unfrozen flows grow at a common rate; each iteration finds the
    bottleneck links (least remaining capacity per crossing flow),
    saturates them, and freezes their flows — at least one link saturates
    per iteration, so the loop runs at most ``n_links`` times with
    O(entries) array work each.
    """
    rate = np.zeros(n_flows)
    growing = active.copy()
    cap_rem = cap.astype(np.float64).copy()
    for _ in range(n_links + 1):
        e = growing[flow_of_entry]
        cnt = np.bincount(link_of_entry[e], minlength=n_links)
        open_links = cnt > 0
        if not open_links.any():
            break
        share = np.full(n_links, np.inf)
        share[open_links] = cap_rem[open_links] / cnt[open_links]
        inc = share.min()
        rate[growing] += inc
        cap_rem[open_links] -= inc * cnt[open_links]
        saturated = open_links & (share <= inc * (1.0 + 1e-9))
        hit = saturated[link_of_entry] & e
        growing[flow_of_entry[hit]] = False
        if not growing.any():
            break
    return rate


# ---------------------------------------------------------------------------
# The simulator.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class UtilizationSample:
    """One step of the link-utilization timeline: the interval
    ``[start, end)``, the max/mean utilization over links carrying any
    active flow, the active subflow count, and (when the simulator is
    asked to record it) the full per-link utilization tensor."""

    start: float
    end: float
    max_utilization: float
    mean_utilization: float
    active_flows: int
    utilization: Optional[np.ndarray] = None  # (D, 2, *dims) when recorded


@dataclass(frozen=True)
class FlowSimResult:
    """Outcome of one flow-level simulation.

    ``completion[m]`` is the finish time of message m (the last of its
    subflows), ``makespan`` the overall finish, ``ideal_time`` the
    zero-contention bound (largest message at line rate) and ``slowdown``
    their ratio — the measured contention multiplier the static engine
    predicts as ``max_link_load``.  ``timeline`` holds the per-step
    utilization samples when the simulation ran with
    ``record_utilization=True`` (empty otherwise).
    """

    dims: Tuple[int, ...]
    mode: str
    completion: np.ndarray  # (n_messages,) per-message finish times
    flow_completion: np.ndarray  # (F,) per-subflow finish times
    makespan: float
    steps: int
    ideal_time: float
    link_loads: np.ndarray  # (D, 2, *dims) total routed volume
    timeline: List[UtilizationSample] = field(default_factory=list)

    @property
    def slowdown(self) -> float:
        """Makespan over the zero-contention bound (>= 1 whenever any
        message moves; 1.0 for empty traffic)."""
        if self.ideal_time <= 0.0:
            return 1.0
        return self.makespan / self.ideal_time


def _package_result(
    paths: FlowPaths,
    flow_completion: np.ndarray,
    steps: int,
    timeline: List[UtilizationSample],
    link_bw: float,
) -> FlowSimResult:
    """Assemble a :class:`FlowSimResult` from per-subflow finish times —
    shared tail of the numpy and xla simulation paths."""
    F = paths.n_flows
    vol = paths.vol
    completion = np.zeros(paths.n_messages)
    if F:
        np.maximum.at(completion, paths.msg, flow_completion)
    msg_vol = (
        np.bincount(paths.msg, weights=vol, minlength=paths.n_messages)
        if F
        else np.zeros(paths.n_messages)
    )
    return FlowSimResult(
        dims=paths.dims,
        mode=paths.mode,
        completion=completion,
        flow_completion=flow_completion,
        makespan=float(flow_completion.max()) if F else 0.0,
        steps=steps,
        ideal_time=float(msg_vol.max()) / link_bw if msg_vol.shape[0] else 0.0,
        link_loads=paths.link_loads(),
        timeline=timeline,
    )


def simulate_flows(
    paths: FlowPaths,
    link_bw: float = 1.0,
    double_link_on_2: bool = True,
    record_utilization: bool = False,
    max_steps: int = 100_000,
    backend: Optional[str] = None,
) -> FlowSimResult:
    """Drain a routed pattern under max-min fair link sharing.

    With tracing enabled (:mod:`repro.obs`) the drain records a
    ``netsim.drain`` span annotated with the flow count, step count, and
    makespan; results are bit-identical either way (spans only measure).

    Each step computes fair rates over the link x flow incidence
    (:func:`_max_min_rates`), advances time to the next subflow
    completion, and removes drained subflows; the step count is therefore
    bounded by the number of distinct completion times.  Raises
    ``RuntimeError`` after ``max_steps`` steps (a guard, not a tick
    width).  ``record_utilization=True`` additionally keeps the per-step
    link-utilization timeline (stats plus the full per-link tensor) —
    off by default, since the extra per-step sweep is pure overhead for
    callers that only need completion times.

    ``backend="xla"`` drains through the compiled fixed-shape simulator
    (:mod:`repro.network.backend`): same completion order, makespans
    within 1e-9 relative of the numpy engine.  The timeline sweep is a
    host-side diagnostic, so ``record_utilization=True`` is numpy-only.
    """
    if not _TRACER.enabled:
        return _simulate_flows_impl(
            paths, link_bw, double_link_on_2, record_utilization, max_steps, backend
        )
    with _TRACER.span(
        "netsim.drain",
        flows=paths.n_flows,
        mode=paths.mode,
        backend=resolve_backend(backend),
    ) as span:
        res = _simulate_flows_impl(
            paths, link_bw, double_link_on_2, record_utilization, max_steps, backend
        )
        span.annotate(steps=res.steps, makespan=res.makespan)
        return res


def _simulate_flows_impl(
    paths: FlowPaths,
    link_bw: float,
    double_link_on_2: bool,
    record_utilization: bool,
    max_steps: int,
    backend: Optional[str],
) -> FlowSimResult:
    if link_bw <= 0.0:
        raise ValueError("link_bw must be positive")
    if resolve_backend(backend) == "xla":
        if record_utilization:
            raise ValueError(
                "record_utilization is a numpy-only diagnostic; "
                "use backend='numpy' to capture the timeline"
            )
        from .backend import drain, prepare_drain

        plan = prepare_drain(paths, link_bw, double_link_on_2)
        flow_completion, steps = drain(plan, max_steps=max_steps)
        return _package_result(paths, flow_completion, steps, [], link_bw)
    dims = paths.dims
    F = paths.n_flows
    vol = paths.vol
    if paths.capacities is not None:
        cap = paths.capacities * link_bw
    else:
        cap = link_capacities(dims, link_bw, double_link_on_2).ravel()
    n_links = cap.shape[0]  # flat ids are already compact: 2 * D * N
    link_of_entry = paths.link_ids
    flow_of_entry = paths.flow_ids

    has_links = np.zeros(F, dtype=bool)
    has_links[flow_of_entry] = True
    remaining = vol.astype(np.float64).copy()
    flow_completion = np.zeros(F)
    active = has_links & (remaining > _EPS)

    timeline: List[UtilizationSample] = []
    t = 0.0
    steps = 0
    while active.any():
        steps += 1
        if steps > max_steps:
            raise RuntimeError(f"flow simulation exceeded {max_steps} steps")
        rates = _max_min_rates(flow_of_entry, link_of_entry, F, n_links, cap, active)
        act_idx = np.flatnonzero(active)
        ratio = remaining[act_idx] / rates[act_idx]
        dt = float(ratio.min())
        t += dt
        remaining[act_idx] -= rates[act_idx] * dt
        remaining[act_idx[np.argmin(ratio)]] = 0.0
        finished = active & (remaining <= np.maximum(vol, 1.0) * _EPS)
        flow_completion[finished] = t
        active &= ~finished
        if record_utilization:
            used = np.bincount(
                link_of_entry, weights=rates[flow_of_entry], minlength=n_links
            )
            util = np.divide(used, cap, out=np.zeros_like(used), where=cap > 0.0)
            busy = util[used > 0.0]
            timeline.append(
                UtilizationSample(
                    start=t - dt,
                    end=t,
                    max_utilization=float(busy.max()) if busy.shape[0] else 0.0,
                    mean_utilization=float(busy.mean()) if busy.shape[0] else 0.0,
                    active_flows=int(act_idx.shape[0]),
                    utilization=(
                        util
                        if paths.capacities is not None
                        else util.reshape((len(dims), 2) + dims)
                    ),
                )
            )

    return _package_result(paths, flow_completion, steps, timeline, link_bw)


def simulate_traffic(
    fabric,
    traffic: Traffic,
    mode: Optional[str] = None,
    split_ties: bool = True,
    link_bw: float = 1.0,
    double_link_on_2: bool = True,
    record_utilization: bool = False,
    backend: Optional[str] = None,
) -> FlowSimResult:
    """Route (:func:`build_paths`) and drain a ``(src, dst, vol)`` pattern
    on any fabric or plain torus dims in one call.  ``double_link_on_2``
    applies to the torus link layout; paths that carry their fabric's own
    capacities (HyperX) ignore it."""
    paths = build_paths(fabric, traffic, mode=mode, split_ties=split_ties)
    return simulate_flows(
        paths,
        link_bw=link_bw,
        double_link_on_2=double_link_on_2,
        record_utilization=record_utilization,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# The paper's validation experiment as an API.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PredictionValidation:
    """Static prediction vs simulated makespan for one pattern.

    ``predicted_time`` is the engine's ``max_link_load / link_bw``;
    ``simulated_time`` the flow simulator's makespan.  For steady
    (translation-invariant) patterns the two coincide; no pattern can
    ever finish faster (conservation through the most loaded link).
    """

    dims: Tuple[int, ...]
    predicted_time: float
    simulated_time: float
    rtol: float

    @property
    def ratio(self) -> float:
        """Simulated over predicted (1.0 when both are zero)."""
        if self.predicted_time <= 0.0:
            return 1.0
        return self.simulated_time / self.predicted_time

    @property
    def matched(self) -> bool:
        """Whether simulation confirms the prediction within ``rtol``."""
        return abs(self.simulated_time - self.predicted_time) <= (
            self.rtol * max(self.predicted_time, _EPS)
        )

    @property
    def bounded(self) -> bool:
        """Whether the simulation respects the prediction as a lower
        bound (it always should; False flags a simulator bug)."""
        return self.simulated_time >= self.predicted_time * (1.0 - self.rtol) - _EPS


def validate_prediction(
    dims: Sequence[int],
    traffic: Traffic,
    link_bw: float = 1.0,
    split_ties: bool = True,
    double_link_on_2: bool = True,
    rtol: float = 1e-6,
    backend: Optional[str] = None,
) -> PredictionValidation:
    """Run the paper's §7 validation experiment for one pattern.

    Routes the traffic with DOR, simulates the drain, and packages the
    static prediction next to the measured makespan:

    >>> from repro.network.patterns import bisection_pairing
    >>> v = validate_prediction((4, 4), bisection_pairing((4, 4)))
    >>> v.predicted_time, v.simulated_time, v.matched
    (1.0, 1.0, True)
    """
    dims = tuple(int(a) for a in dims)
    paths = dor_paths(dims, traffic[0], traffic[1], traffic[2], split_ties=split_ties)
    predicted = paths.max_link_load(double_link_on_2) / link_bw
    res = simulate_flows(
        paths, link_bw=link_bw, double_link_on_2=double_link_on_2, backend=backend
    )
    return PredictionValidation(
        dims=dims,
        predicted_time=predicted,
        simulated_time=res.makespan,
        rtol=rtol,
    )


# ---------------------------------------------------------------------------
# Phased collective schedules.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PhasedSimResult:
    """Outcome of a dependent-phase schedule: per-phase results and the
    serial total (phase k+1 starts when phase k drains)."""

    phases: Tuple[FlowSimResult, ...]
    total_time: float


def simulate_phases(
    dims: Sequence[int],
    phases: Sequence[Traffic],
    mode: str = "dor",
    split_ties: bool = True,
    link_bw: float = 1.0,
    double_link_on_2: bool = True,
    backend: Optional[str] = None,
) -> PhasedSimResult:
    """Simulate a sequence of dependent communication phases.

    Each phase is a full ``(src, dst, vol)`` pattern that must drain
    before the next begins — the shape of a ring collective (ring
    all-reduce over an axis of size n is ``2(n-1)`` neighbour-shift
    phases; see :func:`repro.network.patterns.ring_all_reduce_phases`).
    The serial total cross-checks the closed forms in
    :mod:`repro.network.collectives` dynamically.  Repeated occurrences
    of the *same* traffic tuple (identity, the shape the phase builders
    emit) are simulated once and their result reused.
    """
    results = []
    total = 0.0
    memo: dict = {}
    for traffic in phases:
        key = id(traffic)
        res = memo.get(key)
        if res is None:
            res = simulate_traffic(
                dims,
                traffic,
                mode=mode,
                split_ties=split_ties,
                link_bw=link_bw,
                double_link_on_2=double_link_on_2,
                backend=backend,
            )
            memo[key] = res
        results.append(res)
        total += res.makespan
    return PhasedSimResult(phases=tuple(results), total_time=total)


# ---------------------------------------------------------------------------
# Routing-mode comparison (what routing alone can recover).
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RoutingComparison:
    """Base vs adaptive routing makespans for one pattern on one fabric
    (``dor_makespan`` holds the base mode's: DOR on a torus, minimal on
    HyperX)."""

    dims: Tuple[int, ...]
    dor_makespan: float
    adaptive_makespan: float

    @property
    def recovered_fraction(self) -> float:
        """Fraction of the DOR makespan the adaptive router removed
        (0.0 when routing cannot help — e.g. any translation-invariant
        pattern, whose load field is already uniform)."""
        if self.dor_makespan <= 0.0:
            return 0.0
        return (self.dor_makespan - self.adaptive_makespan) / self.dor_makespan


def compare_routing(
    fabric,
    traffic: Traffic,
    split_ties: bool = True,
    link_bw: float = 1.0,
    double_link_on_2: bool = True,
    backend: Optional[str] = None,
) -> RoutingComparison:
    """Quantify how much of a pattern's contention routing alone recovers.

    Runs the same traffic under the fabric's base and adaptive routing
    modes (torus: DOR vs minimal-adaptive; HyperX: minimal vs DAL) and
    reports both makespans.  The paper's argument is geometric: for
    the contention its partition geometries avoid, the recovered fraction
    here is ~0 on both topologies — no minimal router can spread a
    uniform load field any flatter — whereas geometry changes the field
    itself.
    """
    fab = as_fabric(fabric)
    base_mode, adaptive_mode = fab.routing_modes
    t_base = simulate_traffic(
        fab, traffic, mode=base_mode, split_ties=split_ties,
        link_bw=link_bw, double_link_on_2=double_link_on_2, backend=backend,
    ).makespan
    t_adp = simulate_traffic(
        fab, traffic, mode=adaptive_mode, split_ties=split_ties,
        link_bw=link_bw, double_link_on_2=double_link_on_2, backend=backend,
    ).makespan
    return RoutingComparison(
        dims=fab.dims, dor_makespan=t_base, adaptive_makespan=t_adp
    )


__all__ = [
    "FlowPaths",
    "FlowSimResult",
    "PhasedSimResult",
    "PredictionValidation",
    "RoutingComparison",
    "UtilizationSample",
    "adaptive_paths",
    "build_paths",
    "compare_routing",
    "dor_paths",
    "link_capacities",
    "simulate_flows",
    "simulate_phases",
    "simulate_traffic",
    "validate_prediction",
]
