"""Per-link contention attribution and the avoidable-contention gauge.

The paper's argument is that contention is *avoidable*: a partition's
communication time is pinned by its bisection, and the isoperimetry
engine certifies how far any granted geometry sits above the best
achievable one.  This module turns that into a continuously-observable
report over a live :class:`~repro.network.allocation.MachineState` (or
any explicit per-job traffic decomposition):

* **per-link attribution** — each live job's all-to-all load field,
  split into *self* traffic (links whose both endpoints are the job's
  own cells) and *cross* traffic (links it loads through foreign
  territory — the spill corridors of
  :func:`repro.network.placement.is_spilling`);
* **hotspot links** — the most loaded links of the summed background,
  each broken down by owning job;
* **avoidable contention** — per partition, the measured max link load
  of its granted geometry against the pairing load of the
  certified-optimal geometry from
  :func:`repro.network.isoperimetry.advise_partition` (whose ``bound``
  is the Theorem 3.1 floor): ``avoidable_ratio`` is the paper's
  headline current/optimal time ratio (1.0 = nothing avoidable),
  ``avoidable_excess`` the same minus one.

Rendered as a text dashboard (:func:`render_dashboard`, see
``examples/telemetry_dashboard.py``) and machine-readable JSON
(:meth:`ContentionReport.to_dict`).

>>> from repro.network.allocation import MachineState
>>> m = MachineState((4, 4, 4))
>>> _ = m.allocate(0, (2, 2, 2))
>>> rep = attribute_contention(m)
>>> [j.job_id for j in rep.jobs], rep.jobs[0].avoidable_ratio
([0], 1.0)
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "HotspotLink",
    "JobContention",
    "ContentionReport",
    "attribute_contention",
    "attribute_traffic",
    "render_dashboard",
]


@dataclass(frozen=True)
class JobContention:
    """Attribution record for one live partition."""

    job_id: int
    units: int
    geometry: Tuple[int, ...]
    oriented: Tuple[int, ...]
    offset: Tuple[int, ...]
    self_load: float  # job traffic on links internal to its own cells
    cross_load: float  # job traffic routed through foreign territory
    max_link_load: float  # measured peak of the job's own field
    pairing_load: float  # pairing-benchmark peak of the granted geometry
    optimal_geometry: Optional[Tuple[int, ...]]  # advisor's certified best
    optimal_max_load: float  # pairing peak of the optimal geometry
    bound: float  # Theorem 3.1 floor on the optimal bisection cut
    avoidable_ratio: float  # pairing time current/optimal (>= 1.0)
    certified: bool  # optimum pinned analytically by the bound

    @property
    def avoidable_excess(self) -> float:
        """Avoidable fraction of the job's communication time: 0.0 when
        the granted geometry is isoperimetrically optimal, ~1.0 when the
        paper's worst geometry doubles it."""
        return self.avoidable_ratio - 1.0


@dataclass(frozen=True)
class HotspotLink:
    """One heavily loaded directed link with its per-job load shares."""

    dim: int
    direction: int
    cell: Tuple[int, ...]
    load: float
    shares: Dict[int, float]  # job_id -> load contribution


@dataclass(frozen=True)
class ContentionReport:
    """Machine-wide contention attribution snapshot."""

    dims: Tuple[int, ...]
    jobs: Tuple[JobContention, ...]
    hotspots: Tuple[HotspotLink, ...]
    total_load: float  # summed background volume over all links
    max_link_load: float  # peak of the summed background
    cross_load: float = 0.0  # summed cross traffic over all jobs

    def to_dict(self) -> Dict[str, Any]:
        """Machine-readable JSON form of the report."""
        return {
            "dims": list(self.dims),
            "total_load": self.total_load,
            "max_link_load": self.max_link_load,
            "cross_load": self.cross_load,
            "jobs": [
                {
                    "job_id": j.job_id,
                    "units": j.units,
                    "geometry": list(j.geometry),
                    "oriented": list(j.oriented),
                    "offset": list(j.offset),
                    "self_load": j.self_load,
                    "cross_load": j.cross_load,
                    "max_link_load": j.max_link_load,
                    "pairing_load": j.pairing_load,
                    "optimal_geometry": (
                        None
                        if j.optimal_geometry is None
                        else list(j.optimal_geometry)
                    ),
                    "optimal_max_load": j.optimal_max_load,
                    "theorem31_bound": j.bound,
                    "avoidable_ratio": j.avoidable_ratio,
                    "avoidable_excess": j.avoidable_excess,
                    "certified": j.certified,
                }
                for j in self.jobs
            ],
            "hotspots": [
                {
                    "dim": h.dim,
                    "direction": h.direction,
                    "cell": list(h.cell),
                    "load": h.load,
                    "shares": {str(k): v for k, v in sorted(h.shares.items())},
                }
                for h in self.hotspots
            ],
        }

    def to_json(self, path: Optional[str] = None) -> str:
        """Serialise :meth:`to_dict`; also write to ``path`` when given."""
        text = json.dumps(self.to_dict(), indent=1)
        if path is not None:
            with open(path, "w") as fh:
                fh.write(text)
        return text


def _advise(
    fabric,
    units: int,
    geometry: Tuple[int, ...],
    unit_node_dims: Optional[Sequence[int]],
) -> Tuple[Optional[Tuple[int, ...]], float, float, float, float, bool]:
    """(optimal_geometry, pairing_load, optimal_load, bound, ratio,
    certified) for one partition, via the isoperimetry advisor.  The loads
    are the fabric's contention benchmark on each box
    (:meth:`~repro.network.fabric.Fabric.exchange_box`): pairing on a
    torus, the box-internal all-to-all on HyperX (pairing never contends
    across diameter-1 dimensions)."""
    from repro.network.isoperimetry import advise_partition

    try:
        advice = advise_partition(
            fabric, units, geometry, unit_node_dims=unit_node_dims
        )
    except ValueError:
        return None, 0.0, 0.0, 0.0, 1.0, False
    cur_load = fabric.exchange_box(geometry, unit_node_dims).exchange_max_load()
    opt_load = fabric.exchange_box(
        advice.optimal_geometry, unit_node_dims
    ).exchange_max_load()
    return (
        tuple(advice.optimal_geometry),
        float(cur_load),
        float(opt_load),
        float(advice.bound),
        float(advice.predicted_speedup),
        bool(advice.certified),
    )


def attribute_traffic(
    dims: Sequence[int],
    loads_by_job: Dict[int, np.ndarray],
    placements: Optional[Dict[int, Any]] = None,
    *,
    fabric=None,
    unit_node_dims: Optional[Sequence[int]] = None,
    top_hotspots: int = 5,
) -> ContentionReport:
    """Build a :class:`ContentionReport` from explicit per-job load
    arrays, each in the fabric's link layout
    (:attr:`~repro.network.fabric.Fabric.load_shape`: ``(D, 2, *dims)``
    on a torus — e.g. a netsim result's ``link_loads`` split by the job
    that injected each flow).

    ``placements`` optionally maps job ids to
    :class:`~repro.network.allocation.Placement` records; with them the
    self/cross split and the avoidable-contention gauge are computed,
    without them the report is attribution-only (geometry fields empty).

    ``fabric`` (default: the torus of ``dims``) sets the layout; a
    :class:`~repro.network.fabric.HyperXFabric` takes flat per-slot
    vectors in its dense link layout, and its hotspot ``direction`` is
    the destination *coordinate* of the clique link.  ``dims`` is then
    ignored in favour of the fabric's own.
    """
    from repro.network.fabric import as_fabric
    from repro.network.placement import placement_cells

    fab = as_fabric(dims if fabric is None else fabric)
    dims = fab.dims
    shape = fab.load_shape
    placements = placements or {}
    total = np.zeros(shape, dtype=np.float64)
    jobs: List[JobContention] = []
    cross_total = 0.0
    for jid in sorted(loads_by_job):
        loads = np.asarray(loads_by_job[jid], dtype=np.float64)
        if loads.shape != shape:
            raise ValueError(
                f"job {jid} loads must have shape {shape}; got {loads.shape}"
            )
        total += loads
        p = placements.get(jid)
        if p is not None:
            oriented = tuple(int(w) for w in p.oriented)
            offset = tuple(int(o) for o in p.offset)
            geometry = tuple(int(g) for g in p.geometry)
            units = int(np.prod(oriented))
            member = np.zeros(dims, dtype=bool)
            member[placement_cells(dims, oriented, offset)] = True
            own = fab.internal_links(member)
            self_load = float(loads[own].sum())
            cross_load = float(loads[~own].sum())
            opt_geom, cur_load, opt_load, bound, ratio, certified = _advise(
                fab, units, geometry, unit_node_dims
            )
        else:
            oriented = offset = geometry = ()
            units = 0
            self_load = float(loads.sum())
            cross_load = 0.0
            opt_geom, cur_load, opt_load, bound, ratio, certified = (
                None, 0.0, 0.0, 0.0, 1.0, False,
            )
        cross_total += cross_load
        jobs.append(
            JobContention(
                job_id=int(jid),
                units=units,
                geometry=geometry,
                oriented=oriented,
                offset=offset,
                self_load=self_load,
                cross_load=cross_load,
                max_link_load=float(loads.max()) if loads.size else 0.0,
                pairing_load=cur_load,
                optimal_geometry=opt_geom,
                optimal_max_load=opt_load,
                bound=bound,
                avoidable_ratio=ratio,
                certified=certified,
            )
        )

    hotspots: List[HotspotLink] = []
    flat = total.ravel()
    if flat.size and top_hotspots > 0:
        k = min(int(top_hotspots), int((flat > 0.0).sum()))
        if k > 0:
            idx = np.argpartition(flat, -k)[-k:]
            idx = idx[np.argsort(-flat[idx], kind="stable")]
            for i in idx:
                kdim, direction, cell = fab.slot_label(int(i))
                shares = {}
                for jid in sorted(loads_by_job):
                    share = float(np.asarray(loads_by_job[jid]).ravel()[int(i)])
                    if share > 0.0:
                        shares[int(jid)] = share
                hotspots.append(
                    HotspotLink(
                        dim=kdim,
                        direction=direction,
                        cell=cell,
                        load=float(flat[int(i)]),
                        shares=shares,
                    )
                )
    return ContentionReport(
        dims=dims,
        jobs=tuple(jobs),
        hotspots=tuple(hotspots),
        total_load=float(total.sum()),
        max_link_load=float(total.max()) if total.size else 0.0,
        cross_load=cross_total,
    )


def attribute_contention(
    machine,
    *,
    unit_node_dims: Optional[Sequence[int]] = None,
    top_hotspots: int = 5,
) -> ContentionReport:
    """Decompose a live :class:`~repro.network.allocation.MachineState`
    into per-link load by owning job, with the avoidable-contention
    gauge per partition (see the module docstring).

    Each job's field is its all-to-all contention model
    (:func:`repro.network.placement.placement_loads` — the same tensor
    the scored policies stack into the background), so the per-job
    fields sum exactly to ``machine.traffic_loads()``.

    On a fabric whose disjoint boxes share no links (HyperX) there is no
    background field: each box's all-to-all is routed with the fabric's
    base routing mode instead (:func:`repro.network.netsim.build_paths`);
    its cross traffic is structurally zero — minimal paths never leave
    the box — so the report's gauge is purely the geometry-internal ratio.
    """
    from repro.network.netsim import build_paths
    from repro.network.placement import (
        placement_all_to_all_traffic,
        placement_loads,
    )

    fabric = machine.fabric
    dims = tuple(int(a) for a in machine.dims)
    if fabric.boxes_share_links:
        loads_by_job = {
            jid: placement_loads(dims, p.oriented, p.offset)
            for jid, p in machine.placements.items()
        }
    else:
        loads_by_job = {}
        for jid, p in machine.placements.items():
            src, dst, _ = placement_all_to_all_traffic(dims, p.oriented, p.offset)
            loads_by_job[jid] = build_paths(fabric, (src, dst, 1.0)).link_loads()
    return attribute_traffic(
        dims,
        loads_by_job,
        dict(machine.placements),
        fabric=fabric,
        unit_node_dims=unit_node_dims,
        top_hotspots=top_hotspots,
    )


def render_dashboard(report: ContentionReport, width: int = 30) -> str:
    """Text dashboard of a :class:`ContentionReport`: per-partition
    avoidable-contention gauges (with a bar over ``avoidable_excess``)
    and the hotspot-link breakdown."""
    lines = [
        f"contention report — machine {report.dims}",
        f"  total link load {report.total_load:.3f}, "
        f"peak {report.max_link_load:.3f}, "
        f"cross traffic {report.cross_load:.3f}",
        "",
        f"{'job':>5} {'units':>6} {'geometry':>14} {'pairing':>8} {'opt':>8} "
        f"{'avoid x':>8} {'cert':>5}  avoidable",
    ]
    max_excess = max((j.avoidable_excess for j in report.jobs), default=0.0)
    scale = max(max_excess, 1.0)
    for j in report.jobs:
        bar = "#" * int(round(width * j.avoidable_excess / scale))
        geom = "x".join(str(g) for g in j.geometry) if j.geometry else "-"
        lines.append(
            f"{j.job_id:>5} {j.units:>6} {geom:>14} {j.pairing_load:>8.3f} "
            f"{j.optimal_max_load:>8.3f} {j.avoidable_ratio:>8.2f} "
            f"{'yes' if j.certified else 'no':>5}  {bar}"
        )
    if report.hotspots:
        lines.append("")
        lines.append("hotspot links (dim, dir, cell -> load; shares by job):")
        for h in report.hotspots:
            shares = ", ".join(
                f"{jid}:{load:.3f}" for jid, load in sorted(h.shares.items())
            )
            lines.append(
                f"  d{h.dim}{'+' if h.direction == 0 else '-'} {h.cell} "
                f"-> {h.load:.3f}  [{shares}]"
            )
    return "\n".join(lines)
