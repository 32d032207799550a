"""Processor-allocation policies, the placement engine wrapper, and an
online queue simulator with arrival streams and backfill.

This is the paper's contribution turned into a deployable scheduler
component: given a machine fabric (a torus of allocation units — midplanes
on Blue Gene/Q, chips on a TPU pod) and a stream of jobs, allocate cuboid
partitions.  Policies differ in which geometry they pick for a given size
and (for the scored policy) where it lands:

* ``ElongatedPolicy``     — worst-case baseline: most elongated cuboid that
  fits (models "fill dimension-by-dimension" schedulers; JUQUEEN worst case).
* ``ListPolicy``          — a fixed geometry per size (models Mira's
  predefined partition list).
* ``IsoperimetricPolicy`` — the paper's policy: the geometry of maximal
  internal bisection bandwidth that fits the current free space, preferring
  better-bisection geometries even when fragmentation makes them harder to
  place (falls back in bisection order).
* ``HintedPolicy``        — isoperimetric for jobs flagged contention-bound,
  first-fit otherwise (Section 5's scheduler-hint proposal).
* ``ContentionScoredPolicy`` — isoperimetric geometry choice plus *scored
  placement*: among all free translates, pick the candidate minimising
  predicted interference with existing placements (the job's intra-slice
  all-to-all traffic routed on the machine torus by the DOR engine —
  pairing traffic is provably isolated and would score zero everywhere),
  breaking ties toward snug, fragmentation-avoiding offsets on
  interference-free fabrics.

Placement is exact and vectorized: an occupancy grid over the machine torus
is correlated with the cuboid kernel (:mod:`repro.network.placement`), so
all free translates of all orientations come out of O(D·N) array work —
the historical Python scan survives as ``tests/reference_placement.py``.
Wrap-around placement is allowed, since torus partitions remain tori (BG/Q)
— for TPU-style fabrics the resulting slice's wrap flags are recomputed by
:func:`repro.network.fabric.slice_fabric`.

The queue simulator is event-driven: jobs carry ``arrival`` timestamps,
head-of-line blocking is FCFS-exact, and ``backfill=True`` enables
EASY-style conservative backfill — a later job may jump the blocked head
only if it terminates before the head's reservation (the earliest time the
head is guaranteed to fit, found by a search over the pending frees).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import TRACER as _TRACER
from .fabric import HyperXFabric, TorusFabric
from .geometry import Geometry, bisection_links, canonical, sub_cuboids
from .isoperimetry import ranked_geometries, scaled_node_dims
from .mapping import RankMapping, map_ranks
from .netsim import dor_paths, simulate_flows
from .placement import (
    ScoredPlacement,
    best_placement,
    first_fit,
    int_placement_loads,
    pad_geometry,
    placement_all_to_all_traffic,
    placement_cells,
    placement_loads,
)
from .routing import max_link_load, predict_pairing_time

Coord = Tuple[int, ...]


@dataclass(frozen=True)
class JobRequest:
    """One job in the queue: ``units`` allocation units (midplanes/chips),
    an ``arrival`` timestamp and a ``duration``, both in the simulator's
    abstract time units; ``contention_bound`` is the Section-5 scheduler
    hint consumed by :class:`HintedPolicy`.

    ``geometry`` optionally carries a planner-chosen partition geometry
    (e.g. :meth:`repro.launch.planner.SlicePlan.to_request`): every policy
    tries it first and only then falls back to its own preference list, so
    a fleet-planner decision survives scheduling without a custom policy.
    """

    job_id: int
    units: int  # allocation units (midplanes / chips)
    contention_bound: bool = True
    duration: float = 1.0  # abstract time units, for the queue simulator
    arrival: float = 0.0  # submission time (0 = all queued up front)
    geometry: Optional[Geometry] = None  # planner-requested partition shape

    def __post_init__(self):
        if self.geometry is not None:
            g = canonical(self.geometry)
            n = 1
            for a in g:
                n *= a
            if n != self.units:
                raise ValueError(
                    f"requested geometry {tuple(self.geometry)} has volume "
                    f"{n}, but the request asks for {self.units} units"
                )
            object.__setattr__(self, "geometry", g)


@dataclass(frozen=True)
class Placement:
    """A committed allocation: canonical ``geometry``, the per-machine-dim
    ``oriented`` extents actually placed at ``offset`` (cells may wrap),
    its internal ``bisection_links`` (links, not bandwidth) and the
    ``predicted_contention`` shared-link score (traffic-volume units; 0.0
    for unscored policies)."""

    job_id: int
    geometry: Geometry  # canonical (sorted desc)
    oriented: Tuple[int, ...]  # per-machine-dimension extent actually placed
    offset: Coord
    bisection_links: int
    predicted_contention: float = 0.0  # shared-link score (scored policies)


class MachineState:
    """Occupancy grid over the machine's allocation-unit torus.

    A thin stateful wrapper around :mod:`repro.network.placement`: the grid,
    the live placement table, and a lazily maintained background-traffic
    load tensor (the sum of every placement's pairing traffic routed on the
    machine torus) used by contention-scored allocation.

    ``backend`` selects the compiled backend for the scored-allocation
    contention fields (:func:`repro.network.placement.best_placement`);
    the first-fit occupancy scans are integer windowed sums and always
    run in NumPy (see DESIGN.md "Compiled backends").
    """

    def __init__(self, dims: Sequence[int], backend: Optional[str] = None):
        # Accepts plain allocation-unit dims (torus semantics, historical
        # default) or a Fabric.  HyperX occupancy uses the same grid: a
        # clique dimension is invariant under coordinate relabeling, so a
        # wrapped translate of a box is just another valid aligned box.
        if isinstance(dims, (TorusFabric, HyperXFabric)):
            self.fabric: Optional[object] = dims
            self.dims = dims.dims
        else:
            self.fabric = None
            self.dims = tuple(int(d) for d in dims)
        self.grid = np.zeros(self.dims, dtype=bool)
        self.placements: Dict[int, Placement] = {}
        # Exact accumulator: per placement size n, the int64 sum of the
        # live placements' integer-scaled load fields (value 2·n·load, see
        # placement.int_base_loads).  Integer add/subtract is lossless, so
        # release subtracts a placement back out bit-exactly instead of
        # discarding the cache and re-correlating every live job.
        self._int_loads: Dict[int, np.ndarray] = {}
        self._loads: Optional[np.ndarray] = None  # lazy float recombination
        self.backend = backend

    @property
    def free_units(self) -> int:
        return int((~self.grid).sum())

    @property
    def fabric_or_dims(self):
        """The fabric this machine was built from, or its plain dims — the
        value fabric-dispatching engines (isoperimetry, routing) accept."""
        return self.fabric if self.fabric is not None else self.dims

    def _geometry_bisection(self, geometry: Geometry) -> int:
        """Internal bisection of a canonical geometry under this machine's
        fabric convention (Hamming sub-box on HyperX, wrapped torus else)."""
        if isinstance(self.fabric, HyperXFabric):
            return self.fabric.sub_fabric(geometry).bisection_links()
        return bisection_links(geometry)

    def cells(self, oriented: Sequence[int], offset: Coord) -> Tuple[np.ndarray, ...]:
        return placement_cells(self.dims, oriented, offset)

    def find_placement(self, geometry: Sequence[int]) -> Optional[Tuple[Tuple[int, ...], Coord]]:
        """First free translate of any orientation of the cuboid; None if
        full.  Identical choice to the brute-force reference scan; raises
        ``ValueError`` if the geometry has more non-trivial dims than the
        machine (the historical scan silently truncated it)."""
        return first_fit(self.grid, geometry)

    def _recombine(
        self,
        exclude_size: Optional[int] = None,
        exclude_field: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        total = np.zeros((len(self.dims), 2) + self.dims)
        for n in sorted(self._int_loads):
            acc = self._int_loads[n]
            if n == exclude_size:
                acc = acc - exclude_field
            total += acc / (2.0 * n)
        return total

    def traffic_loads(self, exclude: Optional[int] = None) -> np.ndarray:
        """(D, 2, *dims) link loads of all current placements' intra-job
        all-to-all traffic on the machine torus (the scored policies'
        background; see :func:`repro.network.placement.placement_loads`).

        Maintained *exactly*: commits add and releases subtract each
        placement's integer-scaled field
        (:func:`repro.network.placement.int_base_loads`) in int64, and
        this recombines the per-size sums as ``Σ_n S_n / (2n)`` — each
        int64 value converts to float without rounding (they stay far
        below 2**53), so the background after any alloc/release stream is
        bit-identical to a fresh recompute over the surviving placements
        (property-pinned) with no O(live jobs × grid) rebuild on release.

        ``exclude`` removes one live job's own field before recombining —
        again in the integer domain, hence exactly — which is the measured
        -contention background of that job (callers previously subtracted
        the float field after the fact and relied on the residue staying
        under the sharing threshold)."""
        if isinstance(self.fabric, HyperXFabric):
            raise TypeError(
                "traffic_loads is the torus-routed background field; on a "
                "HyperX fabric disjoint aligned boxes share no links (every "
                "minimal path stays inside its own box), so there is no "
                "cross-placement background to maintain"
            )
        if exclude is not None:
            p = self.placements[exclude]
            return self._recombine(
                int(np.prod(p.oriented)),
                int_placement_loads(self.dims, p.oriented, p.offset),
            )
        if self._loads is None:
            self._loads = self._recombine()
        return self._loads

    def _commit(
        self,
        job_id: int,
        geometry: Sequence[int],
        oriented: Tuple[int, ...],
        offset: Coord,
        predicted_contention: float = 0.0,
        bisection: Optional[int] = None,
    ) -> Placement:
        cells = self.cells(oriented, offset)
        self.grid[cells] = True
        p = Placement(
            job_id=job_id,
            geometry=canonical(geometry),
            oriented=oriented,
            offset=offset,
            bisection_links=(
                self._geometry_bisection(canonical(geometry))
                if bisection is None
                else bisection
            ),
            predicted_contention=predicted_contention,
        )
        self.placements[job_id] = p
        n = int(np.prod(oriented))
        delta = int_placement_loads(self.dims, oriented, offset)
        if delta.any():  # single-cell placements route no traffic
            acc = self._int_loads.get(n)
            if acc is None:
                self._int_loads[n] = np.array(delta)  # cached field is read-only
            else:
                acc += delta
        self._loads = None  # recombined lazily (exact, O(sizes · grid))
        return p

    def allocate(self, job_id: int, geometry: Sequence[int]) -> Optional[Placement]:
        """First-fit allocation (reference-identical choice)."""
        spot = self.find_placement(geometry)
        if spot is None:
            return None
        oriented, offset = spot
        return self._commit(job_id, geometry, oriented, offset)

    def allocate_scored(self, job_id: int, geometry: Sequence[int]) -> Optional[Placement]:
        """Contention/contact-scored allocation of one geometry.

        On a HyperX machine placement scoring is vacuous — minimal (and
        DAL) paths of an aligned box never leave the box's own links, so
        every free translate predicts exactly zero shared-link contention
        — and this degrades to first-fit with a 0.0 score."""
        if isinstance(self.fabric, HyperXFabric):
            return self.allocate(job_id, geometry)
        cand: Optional[ScoredPlacement] = best_placement(
            self.grid, geometry, self.traffic_loads(), backend=self.backend
        )
        if cand is None:
            return None
        return self._commit(
            job_id, geometry, cand.oriented, cand.offset, cand.contention
        )

    def commit(
        self,
        job_id: int,
        geometry: Sequence[int],
        oriented: Tuple[int, ...],
        offset: Coord,
        predicted_contention: float = 0.0,
        bisection: Optional[int] = None,
    ) -> Placement:
        """Commit an externally chosen placement (e.g. from
        :func:`repro.launch.mesh.plan_slice`), validating it first.

        ``bisection`` overrides the recorded ``bisection_links`` when the
        caller's fabric convention differs from the fully-wrapped torus
        default (e.g. wrap-aware TPU slice bisection)."""
        if job_id in self.placements:
            raise ValueError(f"job {job_id} already placed")
        oriented = tuple(int(w) for w in oriented)
        if len(oriented) != len(self.dims) or any(
            w < 1 or w > a for w, a in zip(oriented, self.dims)
        ):
            raise ValueError(f"orientation {oriented} does not fit machine {self.dims}")
        if tuple(sorted(oriented, reverse=True)) != pad_geometry(geometry, len(self.dims)):
            raise ValueError(
                f"orientation {oriented} is not an arrangement of geometry "
                f"{canonical(geometry)}"
            )
        if self.grid[self.cells(oriented, offset)].any():
            raise ValueError(
                f"placement {oriented}@{offset} overlaps occupied cells"
            )
        return self._commit(
            job_id, geometry, oriented, offset, predicted_contention, bisection
        )

    def release(self, job_id: int) -> None:
        """Free the job's cells and subtract its traffic field *exactly*
        (int64 accumulators — see :meth:`traffic_loads`), so the next
        scored allocation recombines a handful of per-size tensors instead
        of re-routing every live placement."""
        p = self.placements.pop(job_id)
        self.grid[self.cells(p.oriented, p.offset)] = False
        delta = int_placement_loads(self.dims, p.oriented, p.offset)
        if delta.any():
            n = int(np.prod(p.oriented))
            acc = self._int_loads[n]
            acc -= delta
            if not acc.any():
                # Nonnegative fields: a zero sum means every commit of this
                # size has been released — drop the bucket.
                del self._int_loads[n]
        self._loads = None  # recombined lazily (exact, O(sizes · grid))


# ---------------------------------------------------------------------------
# Policies.
# ---------------------------------------------------------------------------
def _honor_requested_geometry(
    prefs: List[Geometry], request: JobRequest
) -> List[Geometry]:
    """Move a request's planner-chosen geometry to the front of a policy's
    preference list (dropping the duplicate further down); identity when
    the request carries no geometry."""
    if request.geometry is None:
        return prefs
    g = request.geometry
    return [g] + [p for p in prefs if p != g]


class AllocationPolicy:
    """Base policy: a preference-ordered geometry list per request, placed
    first-fit down the list (scored policies override :meth:`allocate`)."""

    name = "base"

    def geometry_preferences(self, machine: MachineState, units: int) -> List[Geometry]:
        """Geometries to try, in preference order."""
        raise NotImplementedError

    def preferences_for(self, machine: MachineState, request: JobRequest) -> List[Geometry]:
        """Request-aware preference list: the policy's ranking with the
        request's own geometry first.  Traced as ``allocation.rank``."""
        if _TRACER.enabled:
            with _TRACER.span("allocation.rank", units=request.units):
                return _honor_requested_geometry(
                    self._ranked(machine, request), request
                )
        return _honor_requested_geometry(self._ranked(machine, request), request)

    def _ranked(self, machine: MachineState, request: JobRequest) -> List[Geometry]:
        """The policy's geometry ranking for one request (hinted policies
        override)."""
        return self.geometry_preferences(machine, request.units)

    def allocate(self, machine: MachineState, request: JobRequest) -> Optional[Placement]:
        """Place the request on the machine, or return None.  Default:
        first-fit down the preference list."""
        for g in self.preferences_for(machine, request):
            placed = machine.allocate(request.job_id, g)
            if placed is not None:
                return placed
        return None


class ElongatedPolicy(AllocationPolicy):
    """Most elongated geometry first (adversarial / naive filler)."""

    name = "elongated"

    def geometry_preferences(self, machine: MachineState, units: int) -> List[Geometry]:
        geoms = list(sub_cuboids(machine.dims, units))
        return sorted(geoms, key=lambda g: (-g[0], g))


class IsoperimetricPolicy(AllocationPolicy):
    """The paper's policy: maximal internal bisection bandwidth first.

    The ranking comes from the isoperimetry engine's batched bisection
    table (:func:`repro.network.isoperimetry.ranked_geometries`) — one
    vectorized pass instead of a per-geometry ``bisection_links`` loop,
    with identical ordering (property-pinned)."""

    name = "isoperimetric"

    def geometry_preferences(self, machine: MachineState, units: int) -> List[Geometry]:
        try:
            return [g for g, _ in ranked_geometries(machine.fabric_or_dims, units)]
        except ValueError:
            return []  # no cuboid of this size fits (matches the old empty sort)


class ListPolicy(AllocationPolicy):
    """A fixed geometry per size (Mira's predefined scheduler list)."""

    name = "list"

    def __init__(self, table: Dict[int, Geometry]):
        self.table = dict(table)

    def geometry_preferences(self, machine: MachineState, units: int) -> List[Geometry]:
        if units not in self.table:
            return []
        return [canonical(self.table[units])]


class HintedPolicy(AllocationPolicy):
    """Contention-bound jobs get isoperimetric geometries; others first-fit."""

    name = "hinted"

    def __init__(self):
        self.iso = IsoperimetricPolicy()
        self.any = ElongatedPolicy()

    def geometry_preferences(
        self, machine: MachineState, units: int, contention_bound: bool = True
    ) -> List[Geometry]:
        pol = self.iso if contention_bound else self.any
        return pol.geometry_preferences(machine, units)

    def _ranked(self, machine: MachineState, request: JobRequest) -> List[Geometry]:
        return self.geometry_preferences(
            machine, request.units, request.contention_bound
        )


class ContentionScoredPolicy(AllocationPolicy):
    """Isoperimetric geometry choice + contention/contact-scored placement.

    Geometries are tried in bisection order (the paper's policy); within the
    first geometry that fits, the placement engine scores every free
    translate — predicted shared-link contention with existing placements
    first, snugness (anti-fragmentation contact) as the tie-break — instead
    of taking the first fit.

    ``min_bisection_efficiency`` adds a bisection-aware admissibility
    floor: geometries whose internal bisection falls below that fraction
    of the size-optimal bisection are dropped from the preference list
    entirely, so a contention-bound job *waits* for an efficient partition
    instead of accepting an elongated one when the machine is fragmented.
    The size-optimal geometry always meets the floor, so no request ever
    becomes impossible that was possible before — only later.  The default
    (0.0) keeps the historical behaviour exactly.
    """

    name = "contention-scored"

    def __init__(self, min_bisection_efficiency: float = 0.0):
        if not 0.0 <= min_bisection_efficiency <= 1.0:
            raise ValueError(
                f"min_bisection_efficiency must be in [0, 1], got "
                f"{min_bisection_efficiency}"
            )
        self.min_bisection_efficiency = float(min_bisection_efficiency)

    def geometry_preferences(self, machine: MachineState, units: int) -> List[Geometry]:
        try:
            ranked = ranked_geometries(machine.fabric_or_dims, units)
        except ValueError:
            return []
        if self.min_bisection_efficiency > 0.0 and ranked[0][1] > 0:
            floor = self.min_bisection_efficiency * ranked[0][1]
            ranked = [(g, b) for g, b in ranked if b >= floor - 1e-12]
        return [g for g, _ in ranked]

    def allocate(self, machine: MachineState, request: JobRequest) -> Optional[Placement]:
        for g in self.preferences_for(machine, request):
            placed = machine.allocate_scored(request.job_id, g)
            if placed is not None:
                return placed
        return None


# ---------------------------------------------------------------------------
# Queue simulator.
# ---------------------------------------------------------------------------
@dataclass
class ScheduledJob:
    request: JobRequest
    placement: Placement
    start: float
    end: float
    predicted_comm_time: float  # pairing-benchmark proxy, seconds/byte
    mapping: Optional[RankMapping] = None  # set when the simulator maps ranks
    #: Static max-load proxy on the job's own traffic alone — the lower
    #: bound no dynamic schedule can beat (contention="simulated" only).
    comm_lower_bound: float = 0.0
    #: Flow-simulated completion of the job's traffic against the
    #: placements live at start time (contention="simulated" only).
    simulated_comm_time: Optional[float] = None
    #: Internal bisection of the granted geometry over the best achievable
    #: bisection for this size on this machine (the isoperimetry engine's
    #: optimum) — 1.0 means the job got an isoperimetrically optimal
    #: partition, recorded for every scheduled job.
    bisection_efficiency: float = 1.0

    @property
    def simulated_slowdown(self) -> float:
        """Simulated completion over the static max-load lower bound
        (>= 1.0 by conservation; 1.0 when the job was not simulated or
        moves no traffic)."""
        if self.simulated_comm_time is None or self.comm_lower_bound <= 0.0:
            return 1.0
        return self.simulated_comm_time / self.comm_lower_bound


@dataclass
class SimulationResult:
    policy: str
    jobs: List[ScheduledJob] = field(default_factory=list)
    rejected: List[int] = field(default_factory=list)

    @property
    def mean_comm_time(self) -> float:
        """Mean predicted pairing-benchmark time over scheduled jobs
        (seconds per byte of per-pair message volume)."""
        if not self.jobs:
            return 0.0
        return float(np.mean([j.predicted_comm_time for j in self.jobs]))

    @property
    def makespan(self) -> float:
        """Completion time of the last job (simulator time units)."""
        return max((j.end for j in self.jobs), default=0.0)

    @property
    def mean_wait(self) -> float:
        """Mean queueing delay (start - arrival) over scheduled jobs."""
        if not self.jobs:
            return 0.0
        return float(np.mean([j.start - j.request.arrival for j in self.jobs]))

    @property
    def mean_contention(self) -> float:
        """Mean predicted shared-link contention score at placement time."""
        if not self.jobs:
            return 0.0
        return float(np.mean([j.placement.predicted_contention for j in self.jobs]))

    @property
    def mean_simulated_slowdown(self) -> float:
        """Mean flow-simulated slowdown over the static max-load bound
        (jobs scheduled under ``contention="simulated"``; 1.0 otherwise)."""
        simulated = [
            j.simulated_slowdown for j in self.jobs if j.simulated_comm_time is not None
        ]
        if not simulated:
            return 1.0
        return float(np.mean(simulated))

    @property
    def mean_bisection_efficiency(self) -> float:
        """Mean granted-over-optimal internal bisection across scheduled
        jobs (1.0 = every job got an isoperimetrically optimal geometry)."""
        if not self.jobs:
            return 1.0
        return float(np.mean([j.bisection_efficiency for j in self.jobs]))


# Traffic-sharing threshold of the measured-contention proxy (a load
# magnitude, not a time): a link is "shared" when the background carries
# more than this.  The event *clock* no longer uses a fixed epsilon — the
# scheduler service's scale-aware time_eps owns simultaneity (see
# repro.network.scheduler).
_EPS = 1e-12


def simulate_queue(
    machine_dims: Sequence[int],
    jobs: Iterable[JobRequest],
    policy: AllocationPolicy,
    unit_node_dims: Optional[Sequence[int]] = None,
    link_bw: float = 1.0,
    *,
    backfill: bool = False,
    measure_contention: bool = False,
    contention: Optional[str] = None,
    mapping_pattern: Optional[str] = None,
    double_link_on_2: bool = True,
    backend: Optional[str] = None,
) -> SimulationResult:
    """Online queue simulation with exact cuboid placement.

    Event-driven: jobs arrive at ``request.arrival`` (all-zero arrivals
    reproduce the historical FCFS batch semantics), are served head-of-line
    FCFS, and with ``backfill=True`` a later job may start while the head is
    blocked provided it completes before the head's reservation — EASY
    backfill, so the head is never delayed by a backfilled job.  The event
    loop itself lives in :class:`repro.network.scheduler.SchedulerService`
    — this function is a thin batch driver over the service (submit the
    sorted stream, run to quiescence, return the result), so simultaneity
    follows the service's deterministic ``(time, kind, seq)`` ordering
    with a scale-aware tolerance rather than the historical fixed
    ``1e-12``.

    A request is rejected only if it cannot be placed even on an empty
    machine (impossible geometry/size for this torus).

    ``unit_node_dims``: node dims per allocation unit (e.g. (4,4,4,4,2) for a
    BG/Q midplane); the contention proxy is evaluated at node level.

    ``measure_contention=True`` additionally routes every placed job's
    intra-job all-to-all traffic and records its volume on links shared
    with the other placements live at start time
    (``placement.predicted_contention``), so first-fit and scored policies
    report a comparable interference number.

    ``contention`` names the contention model explicitly: ``None`` (no
    measurement), ``"static"`` (identical to ``measure_contention=True``
    — the max-load proxy), or ``"simulated"`` — everything static does,
    plus a flow-level simulation (:mod:`repro.network.netsim`) of the
    job's traffic against the placements live at its start: the job's
    messages and every live job's messages drain together under max-min
    fair link sharing, and the job records its simulated completion
    (``ScheduledJob.simulated_comm_time``, seconds at ``link_bw``) next
    to the static lower bound ``ScheduledJob.comm_lower_bound`` (its own
    max link load alone — by conservation the simulation can never beat
    it, so ``simulated_slowdown >= 1`` on every job; the contention the
    static proxy only scores is here *derived* as extra completion time).

    Every scheduled job additionally records its
    ``ScheduledJob.bisection_efficiency`` — the granted geometry's internal
    bisection over the best achievable for that size (isoperimetry-engine
    optimum) — next to the simulated slowdown, so replays can report how
    much bisection a policy trades away
    (``SimulationResult.mean_bisection_efficiency``).

    ``mapping_pattern`` (requires ``measure_contention=True``) applies a
    per-job rank mapping when computing that measured number: each placed
    job's traffic is the named pattern (:data:`repro.network.mapping.
    MAPPING_PATTERNS`) on its logical grid, embedded by
    :func:`repro.network.map_ranks` (congestion-minimising), and the
    shared-link volume is measured against the *mapped* loads of the jobs
    live at start time — all-to-all is mapping-invariant, so this is how
    mapping-sensitive workloads (halo, ring, pairing) are replayed.  The
    chosen mapping is recorded on ``ScheduledJob.mapping``.
    ``double_link_on_2`` is the machine's link convention for the mapping
    engine's congestion metric: True (default) models BG/Q's two parallel
    links on length-2 dimensions; TPU-style single-link fabrics pass
    False.  ``backend`` selects the compiled backend for the
    ``"simulated"`` contention drains (identical schedules either way;
    see :mod:`repro.network.backend`).

    ``machine_dims`` may also be a :class:`~repro.network.fabric.
    TorusFabric` or :class:`~repro.network.fabric.HyperXFabric`; placements
    and bisection accounting then follow that fabric's convention.  The
    contention models are torus replays (on HyperX, disjoint aligned
    boxes structurally share no links — see
    :meth:`MachineState.allocate_scored`), so ``contention``/
    ``measure_contention``/``mapping_pattern`` raise ``ValueError`` on a
    HyperX machine instead of measuring a structural zero with torus
    routing.

    Example (two 4-midplane jobs on a tiny torus, FCFS, no backfill):

    >>> jobs = [JobRequest(0, 4, duration=1.0), JobRequest(1, 4, duration=1.0)]
    >>> res = simulate_queue((2, 2, 2), jobs, IsoperimetricPolicy())
    >>> [(j.placement.geometry, j.start) for j in res.jobs]
    [((2, 2, 1), 0.0), ((2, 2, 1), 0.0)]
    """
    if contention is None:
        contention = "static" if measure_contention else None
    elif contention not in ("static", "simulated"):
        raise ValueError(
            f"contention must be None, 'static' or 'simulated'; got {contention!r}"
        )
    measure = contention is not None
    if mapping_pattern is not None and not measure:
        raise ValueError(
            "mapping_pattern requires measure_contention=True (or contention=)"
        )
    # One event loop, not two: the batch simulation is a thin driver over
    # the event-sourced service (repro.network.scheduler) — jobs are
    # submitted in (arrival, submission-index) order and the contention
    # measurements ride on the service's start/release hooks.
    from .scheduler import SchedulerService

    fabric = (
        machine_dims
        if isinstance(machine_dims, (TorusFabric, HyperXFabric))
        else None
    )
    dims = fabric.dims if fabric is not None else tuple(int(d) for d in machine_dims)
    if isinstance(fabric, HyperXFabric) and (measure or mapping_pattern is not None):
        raise ValueError(
            "contention measurement replays torus routing; on a HyperX "
            "machine disjoint boxes share no links, so there is nothing to "
            "measure — run without contention=/measure_contention/"
            "mapping_pattern"
        )

    # Live per-job *mapped* loads (mapping_pattern only): the measured
    # shared-link background under a mapping is the running sum of these,
    # not the all-to-all tensor MachineState maintains for placement
    # scoring.  The total is maintained incrementally (add on start,
    # subtract on release); cancellation residue is ~1e-13 at replay
    # magnitudes, well under the _EPS=1e-12 sharing threshold.
    live_mapped: Dict[int, np.ndarray] = {}
    mapped_total = (
        np.zeros((len(dims), 2) + dims) if mapping_pattern is not None else None
    )
    # Live jobs' message-level traffic (contention="simulated" only): the
    # flow simulation at a job's start drains its messages together with
    # every live job's.
    live_traffic: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}

    def on_start(service, job: ScheduledJob) -> None:
        nonlocal mapped_total
        if not measure:
            return
        machine = service.machine
        placed = job.placement
        mapping: Optional[RankMapping] = None
        if mapping_pattern is not None:
            mapping = map_ranks(
                machine.dims, placed.oriented, placed.offset,
                pattern=mapping_pattern, double_link_on_2=double_link_on_2,
            )
            job_loads = mapping.loads
            background = np.maximum(mapped_total, 0.0)
            live_mapped[placed.job_id] = job_loads
            mapped_total += job_loads
        else:
            job_loads = placement_loads(machine.dims, placed.oriented, placed.offset)
            # The job's own field is excluded in the exact integer domain —
            # the historical float subtraction left a ~1e-16 residue that
            # only the _EPS threshold kept invisible.
            background = machine.traffic_loads(exclude=placed.job_id)
        job.mapping = mapping
        job.placement = dataclasses.replace(
            placed,
            predicted_contention=float(job_loads[background > _EPS].sum()),
        )
        if contention == "simulated":
            if mapping is not None:
                job_traffic = mapping.machine_traffic()
            else:
                job_traffic = placement_all_to_all_traffic(
                    machine.dims, placed.oriented, placed.offset
                )
            job.comm_lower_bound = (
                max_link_load(machine.dims, job_loads, double_link_on_2) / link_bw
            )
            background_traffic = list(live_traffic.values())
            n_bg = sum(t[2].shape[0] for t in background_traffic)
            if job_traffic[2].shape[0]:
                triples = background_traffic + [job_traffic]
                paths = dor_paths(
                    machine.dims,
                    np.concatenate([t[0] for t in triples]),
                    np.concatenate([t[1] for t in triples]),
                    np.concatenate([t[2] for t in triples]),
                )
                sim = simulate_flows(
                    paths,
                    link_bw=link_bw,
                    double_link_on_2=double_link_on_2,
                    backend=backend,
                )
                job.simulated_comm_time = float(sim.completion[n_bg:].max())
            else:
                job.simulated_comm_time = 0.0
            live_traffic[placed.job_id] = job_traffic

    def on_release(service, job_id: int) -> None:
        nonlocal mapped_total
        released = live_mapped.pop(job_id, None)
        if released is not None:
            mapped_total -= released
        live_traffic.pop(job_id, None)

    service = SchedulerService(
        fabric if fabric is not None else dims,
        policy,
        unit_node_dims=unit_node_dims,
        link_bw=link_bw,
        backfill=backfill,
        on_start=on_start,
        on_release=on_release,
    )
    for _, req in sorted(enumerate(jobs), key=lambda t: (t[1].arrival, t[0])):
        service.submit(req)
    return service.run().result()


def _node_dims(geometry: Geometry, unit_node_dims: Optional[Sequence[int]]) -> Geometry:
    # Each allocation-unit dim scales the node torus; extra unit dims (the
    # BG/Q internal 5th dimension) are appended — one implementation, shared
    # with the isoperimetry engine's node-level bisection tables.
    return scaled_node_dims(geometry, unit_node_dims)


def avoidable_contention_ratio(
    machine_dims: Sequence[int],
    units: int,
    unit_node_dims: Optional[Sequence[int]] = None,
) -> float:
    """Worst/best predicted pairing time over geometries of a given size —
    the paper's 'avoidable contention' factor (×2 for many BG/Q sizes)."""
    times = []
    for g in sub_cuboids(machine_dims, units):
        node_dims = _node_dims(g, unit_node_dims)
        times.append(predict_pairing_time(node_dims, 1.0, 1.0).time_per_volume)
    if not times:
        raise ValueError(f"no cuboid of {units} units fits in {machine_dims}")
    return max(times) / min(times)
