"""Fleet planner: joint geometry x mapping x sharding search per model.

The paper's thesis is that partition *geometry* — not routing or raw link
bandwidth — decides avoidable contention.  The repo derives that end to end
for synthetic workloads (isoperimetry advisor, netsim, scheduler); this
module carries the conclusion to the production question the ROADMAP
north-star asks: *which slice should serve Mixtral-8x7B?*

For one (config, chip budget) pair the planner jointly searches

* **partition geometry** — every admissible cuboid slice, enumerated and
  bisection-ranked by :func:`repro.network.fabric.ranked_slice_geometries`
  (TPU slice semantics) or :func:`repro.network.isoperimetry.ranked_geometries`
  (fully-wrapped node-torus semantics, the paper's Tables 4-6 setting);
* **sharding rule** — explicit PartitionSpec-style rule sets over the
  ``(data, fsdp, tensor, expert)`` logical axes, enumerated from the
  divisor lattice of the chip budget and validated by
  :func:`repro.distributed.sharding.validate_partition_spec`;
* **rank mapping** — :func:`repro.network.mapping.map_ranks` over the
  rule's own rank-space traffic (ring halos per collective axis, expert
  all-to-all groups, the gradient pairing stress), with the whole strategy
  catalogue scored in one ``score_candidates`` batched call when the
  ``xla`` backend is active,

and prices every (geometry, rule, mapping) triple with

* ring-collective times from ``assign_axes(mapping=)`` **measured**
  embeddings (:data:`repro.network.collectives.COLLECTIVE_TIME`),
* a bisection-stress term: the geometry-sensitive share of the traffic
  (the first halving-doubling exchange of the gradient all-reduce and the
  slice-spanning share of the expert all-to-all) priced as the paper's
  pairing benchmark on the node-level dims
  (:func:`repro.network.routing.predict_pairing_time`) — by the section-7
  validation property this static price is *exactly* what the flow
  simulator measures for the same pattern, so every emitted comm time is
  reproducible by standalone ``assign_axes(mapping=)`` + netsim,
* roofline compute/memory terms from
  :func:`repro.analysis.analytic.cell_cost`.

Rows are ranked by exact ``(step_time, geometry rank, axis sizes)`` — a
total order on floats the brute-force oracle (``tests/reference_planner.py``)
reproduces row-identically, and that is bit-identical between the numpy
and xla scoring backends (``score_candidates`` is exact).

>>> from repro.network.fabric import TorusFabric
>>> plan = plan_model("mixtral-8x7b", 8, pod=TorusFabric.tpu((4, 4)),
...                   shape="decode_32k")
>>> plan.geometry, plan.best.axis_sizes  # (data, fsdp, tensor, expert)
((4, 2), (1, 1, 8, 1))
>>> plan.best.simulated_slowdown >= 1.0
True
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.analysis.analytic import BF16, cell_cost
from repro.analysis.roofline import HBM_BW, PEAK_FLOPS
from repro.configs import SHAPES, ArchConfig, ShapeConfig, all_archs, get_arch
from repro.network.collectives import (
    COLLECTIVE_TIME,
    AxisAssignment,
    CollectiveCostModel,
    assign_axes,
)
from repro.network.fabric import (
    Fabric,
    TorusFabric,
    ranked_slice_geometries,
    slice_fabric,
)
from repro.network.geometry import Geometry, canonical, scaled_node_dims, volume
from repro.network.isoperimetry import ranked_geometries
from repro.network.mapping import RankMapping, map_ranks
from repro.network.netsim import simulate_traffic
from repro.obs.trace import TRACER as _TRACER

__all__ = [
    "AXES",
    "HBM_BYTES",
    "ORDER_HINT",
    "PlanCandidate",
    "ShardingRuleSet",
    "SlicePlan",
    "default_chip_budget",
    "enumerate_rules",
    "format_table",
    "pairing_stress_volume",
    "plan_fleet",
    "plan_model",
    "price_candidate",
    "rule_rank_traffic",
    "rule_traffic",
]

#: Logical mesh axes of every candidate sharding rule, in the row-major
#: rank-ravel order used for the mapping (insertion order of
#: ``assign_axes``'s ``axis_sizes`` dict).
AXES: Tuple[str, ...] = ("data", "fsdp", "tensor", "expert")

#: Axis priority for the physical assignment: heaviest collective pressure
#: first (per-layer tensor exchanges > expert all-to-all > parameter
#: gather/scatter > once-per-step gradient reduce).
ORDER_HINT: Tuple[str, ...] = ("tensor", "expert", "fsdp", "data")

#: Usable HBM per chip (weights-only feasibility filter; v5e-class 16 GB).
HBM_BYTES = 16e9


# ---------------------------------------------------------------------------
# Sharding rules.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShardingRuleSet:
    """One candidate sharding of a config over the ``AXES`` logical mesh.

    ``axis_sizes`` is ``(data, fsdp, tensor, expert)`` parallelism degrees
    (product == chip budget); ``specs`` are the explicit PartitionSpec-style
    rules (name, per-dimension entries) the rule set stands for, validated
    against the mesh by ``repro.distributed.sharding.validate_partition_spec``.
    """

    axis_sizes: Tuple[int, int, int, int]
    specs: Tuple[Tuple[str, Tuple], ...]

    @property
    def mesh_shape(self) -> Dict[str, int]:
        """Logical axis sizes as the ``assign_axes`` dict (AXES order)."""
        return dict(zip(AXES, self.axis_sizes))

    @property
    def order_hint(self) -> List[str]:
        return list(ORDER_HINT)


def _rule_specs(axis_sizes: Tuple[int, int, int, int], moe: bool):
    """Explicit PartitionSpec-style rules of one parallelism split.

    Size-1 axes are dropped from the specs (a trivial axis shards nothing),
    matching how :class:`repro.distributed.sharding.ShardingRules` degrades.
    """
    d, f, t, e = axis_sizes
    D = "data" if d > 1 else None
    F = "fsdp" if f > 1 else None
    T = "tensor" if t > 1 else None
    E = "expert" if e > 1 else None
    batch = tuple(a for a in (D, F) if a is not None)
    specs = [
        ("embed", (T, F)),
        ("attn.wq", (F, T, None)),
        ("attn.wo", (T, None, F)),
        ("batch", (batch if batch else None, None)),
    ]
    if moe:
        specs.append(("moe.wi", (E, F, T)))
        specs.append(("moe.wo", (E, T, F)))
    else:
        specs.append(("mlp.wi", (F, T)))
        specs.append(("mlp.wo", (T, F)))
    return tuple(specs)


def _validate_specs(rule: ShardingRuleSet) -> None:
    """Cross-check the rule's specs with the distributed-layer validator.

    Lazy import: ``repro.distributed.sharding`` pulls in jax; the planner
    itself stays importable on numpy alone (the validator is pure Python,
    only its module needs jax, so a missing jax degrades to no check).
    """
    try:
        from repro.distributed.sharding import validate_partition_spec
    except ImportError:  # pragma: no cover - jax is present in CI
        return
    mesh_axes = list(AXES)
    for _name, spec in rule.specs:
        validate_partition_spec(spec, mesh_axes)


def _divisors(n: int) -> List[int]:
    return [k for k in range(1, n + 1) if n % k == 0]


def enumerate_rules(cfg: ArchConfig, chips: int) -> List[ShardingRuleSet]:
    """All candidate ``(data, fsdp, tensor, expert)`` splits of a budget.

    ``tensor`` must divide the head count (head-sharded attention),
    ``expert`` must divide the expert count (1 for non-MoE configs), and
    ``data``/``fsdp`` absorb the rest.  Splits whose per-chip weight
    residency ``2 * params / (tensor * expert * fsdp)`` exceeds
    :data:`HBM_BYTES` are filtered out (ZeRO-3 weights-only feasibility);
    if *nothing* survives — a budget too small for the model — the filter
    is waived so the planner still ranks the least-bad rules.  Enumeration
    order is deterministic: ascending ``tensor``, then ``expert``, then
    ``fsdp``.
    """
    n_experts = cfg.moe.num_experts if cfg.moe is not None else 1
    param_bytes = float(BF16) * cfg.param_count()
    rules: List[ShardingRuleSet] = []
    for t in _divisors(chips):
        if cfg.n_heads % t != 0:
            continue
        for e in _divisors(chips // t):
            if n_experts % e != 0:
                continue
            rest = chips // (t * e)
            for f in _divisors(rest):
                d = rest // f
                rules.append(
                    ShardingRuleSet((d, f, t, e), _rule_specs((d, f, t, e), cfg.moe is not None))
                )
    feasible = [
        r for r in rules
        if param_bytes / (r.axis_sizes[1] * r.axis_sizes[2] * r.axis_sizes[3]) <= HBM_BYTES
    ]
    chosen = feasible if feasible else rules
    for r in chosen:
        _validate_specs(r)
    return chosen


# ---------------------------------------------------------------------------
# Traffic model: per-axis collective volumes of one (config, shape, rule).
# ---------------------------------------------------------------------------
def rule_traffic(
    cfg: ArchConfig, shape: ShapeConfig, axis_sizes: Tuple[int, int, int, int]
) -> List[Tuple[str, str, float]]:
    """Per-chip collective bytes of one step, as ``(axis, collective, bytes)``.

    The closed forms (bf16 activations/params, GSPMD-standard schedule):

    * ``tensor``: per-layer activation all-gather + reduce-scatter pairs of
      the Megatron block (2 exchanges/layer; x3 in training for fwd + bwd +
      remat recompute);
    * ``expert``: token dispatch/combine all-to-all (top-k x capacity
      tokens, 2 exchanges per layer at inference, 4 in training);
    * ``fsdp``: ZeRO-3 parameter all-gather (+ gradient reduce-scatter and
      the backward re-gather in training) of the ``1/(tensor*expert)``
      weight shard;
    * ``data``: the once-per-step gradient all-reduce of the fsdp-sharded
      gradient (training only).

    The entry order is the fixed pricing order (tensor, expert, fsdp,
    data); the differential oracle duplicates these formulas verbatim, so
    an edit here must be made twice to pass the harness.
    """
    d, f, t, e = axis_sizes
    L = cfg.n_layers
    B, S = shape.global_batch, shape.seq_len
    params = float(cfg.param_count())
    p_shard = BF16 * params / (t * e)
    tokens = float(B * S) if shape.kind in ("train", "prefill") else float(B)
    tokens_local = tokens / (d * f)
    act = tokens_local * cfg.d_model * BF16
    entries: List[Tuple[str, str, float]] = []
    if t > 1:
        mult = 3.0 if shape.kind == "train" else 1.0
        entries.append(("tensor", "all-gather", 2.0 * L * mult * act))
        entries.append(("tensor", "reduce-scatter", 2.0 * L * mult * act))
    if e > 1 and cfg.moe is not None:
        n_exchanges = 4.0 if shape.kind == "train" else 2.0
        a2a = (
            n_exchanges * L * tokens_local * cfg.moe.top_k
            * cfg.moe.capacity_factor * cfg.d_model * BF16
        )
        entries.append(("expert", "all-to-all", a2a))
    if f > 1:
        if shape.kind == "train":
            entries.append(("fsdp", "all-gather", 2.0 * p_shard))
            entries.append(("fsdp", "reduce-scatter", p_shard))
        else:
            entries.append(("fsdp", "all-gather", p_shard))
    if d > 1 and shape.kind == "train":
        entries.append(("data", "all-reduce", p_shard / f))
    return entries


def pairing_stress_volume(
    entries: Sequence[Tuple[str, str, float]],
    axis_sizes: Tuple[int, int, int, int],
) -> float:
    """Per-chip bytes of the geometry-sensitive (bisection-crossing) share.

    Ring collectives see identical analytic times on every fully-wrapped
    geometry of one volume; what geometry *does* change is the bisection
    load of the non-ring phases — the first halving-doubling exchange of
    the gradient all-reduce (half the reduced bytes cross the bisection)
    and the slice-spanning share of the expert all-to-all (a ``1/e``
    fraction pairs with the far half).  This is exactly the paper's pairing
    benchmark, priced per node via
    :func:`repro.network.routing.predict_pairing_time`.
    """
    _, _, _, e = axis_sizes
    vol = 0.0
    for axis, collective, v in entries:
        if axis == "data" and collective == "all-reduce":
            vol += 0.5 * v
        if axis == "expert" and collective == "all-to-all":
            vol += v / e
    return vol


def rule_rank_traffic(
    axis_sizes: Tuple[int, int, int, int],
    entries: Sequence[Tuple[str, str, float]],
    pair_volume: float,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Rank-space ``(src, dst, vol)`` messages of a rule's collectives.

    Ring collectives become bidirectional nearest-neighbour exchanges on
    their logical axis (half the axis volume each way), the expert
    all-to-all becomes literal all-pairs messages within each expert
    group, and the pairing stress pairs each rank with its data-axis
    antipode.  Message order is deterministic (AXES order, +1 before -1,
    ascending all-to-all offset, pairing last).  Returns ``None`` when the
    rule moves no bytes (single-chip or communication-free shapes).
    """
    shape = tuple(axis_sizes)
    n = int(np.prod(shape))
    per_axis: Dict[str, float] = {a: 0.0 for a in AXES}
    a2a_volume = 0.0
    for axis, collective, v in entries:
        if axis == "expert" and collective == "all-to-all":
            a2a_volume += v
        else:
            per_axis[axis] += v
    ranks = np.arange(n, dtype=np.int64)
    coords = np.stack(np.unravel_index(ranks, shape), axis=1)
    srcs: List[np.ndarray] = []
    dsts: List[np.ndarray] = []
    vols: List[np.ndarray] = []

    def _send(dst_coords: np.ndarray, v: float) -> None:
        dst = np.ravel_multi_index(tuple(dst_coords.T), shape)
        srcs.append(ranks)
        dsts.append(dst.astype(np.int64))
        vols.append(np.full(n, v, dtype=np.float64))

    for k, axis in enumerate(AXES):
        s, v = shape[k], per_axis[axis]
        if s <= 1 or v <= 0.0:
            continue
        for step in (1, -1):
            nb = coords.copy()
            nb[:, k] = (nb[:, k] + step) % s
            _send(nb, v / 2.0)
    e = shape[3]
    if e > 1 and a2a_volume > 0.0:
        for off in range(1, e):
            nb = coords.copy()
            nb[:, 3] = (nb[:, 3] + off) % e
            _send(nb, a2a_volume / e)
    d = shape[0]
    if d > 1 and pair_volume > 0.0:
        nb = coords.copy()
        nb[:, 0] = (nb[:, 0] + d // 2) % d
        _send(nb, pair_volume)
    if not srcs:
        return None
    return np.concatenate(srcs), np.concatenate(dsts), np.concatenate(vols)


# ---------------------------------------------------------------------------
# Candidate pricing.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PlanCandidate:
    """One priced (geometry, mapping, sharding rule) triple."""

    geometry: Geometry
    geometry_rank: int  # index in the bisection-ranked geometry list
    bisection_links: int
    bisection_efficiency: float  # this geometry's bisection / best rankable
    fabric: Fabric
    rule: ShardingRuleSet
    mapping: Optional[RankMapping]
    assignment: AxisAssignment
    traffic: Tuple[Tuple[str, str, float], ...]
    pair_volume_node: float  # node-level pairing-stress bytes
    node_dims: Geometry  # dims the pairing term is priced on
    ring_time: float
    pairing_time: float
    compute_time: float
    memory_time: float
    simulated_slowdown: float = 1.0

    @property
    def axis_sizes(self) -> Tuple[int, int, int, int]:
        return self.rule.axis_sizes

    @property
    def comm_time(self) -> float:
        """Total predicted communication seconds per step."""
        return self.ring_time + self.pairing_time

    @property
    def step_time(self) -> float:
        """Roofline step time: overlapped compute/memory + exposed comm."""
        return max(self.compute_time, self.memory_time) + self.comm_time

    @property
    def mapping_strategy(self) -> str:
        return self.mapping.strategy if self.mapping is not None else "none"

    def row(self) -> Tuple:
        """Comparable scalar row (what the differential oracle reproduces)."""
        return (
            self.geometry,
            self.axis_sizes,
            self.mapping_strategy,
            self.ring_time,
            self.pairing_time,
            self.compute_time,
            self.memory_time,
            self.step_time,
        )

    def sort_key(self) -> Tuple:
        """Exact deterministic ranking key (documented tie-break: predicted
        step time, then bisection-rank of the geometry, then axis sizes)."""
        return (self.step_time, self.geometry_rank, self.axis_sizes)


def _decode_cache_bytes(cfg: ArchConfig, shape: ShapeConfig) -> float:
    """Whole-fleet KV-cache bytes for decode shapes (attention archs)."""
    if shape.kind != "decode" or cfg.is_attention_free:
        return 0.0
    return (
        2.0 * cfg.n_layers * shape.global_batch * shape.seq_len
        * cfg.n_kv_heads * cfg.resolved_head_dim * BF16
    )


def price_candidate(
    cfg: ArchConfig,
    shape: ShapeConfig,
    fabric: Fabric,
    node_dims: Geometry,
    n_compute: int,
    rule: ShardingRuleSet,
    backend: Optional[str] = None,
) -> Optional[Tuple[Optional[RankMapping], AxisAssignment, Tuple, float, float, float, float, float]]:
    """Price one (fabric, rule) pair; None when the rule cannot embed.

    The comm price has two parts, each standalone-reproducible:

    * ring time — ``assign_axes(fabric, mesh_shape, ORDER_HINT, mapping=)``
      then :data:`COLLECTIVE_TIME` per traffic entry, summed in entry
      order;
    * pairing time — the node-level stress volume times
      ``predict_pairing_time(node_dims).time_per_volume`` (equal to the
      netsim makespan of ``bisection_pairing(node_dims)`` at unit volume).

    When :mod:`repro.obs` tracing is enabled each pricing emits a
    ``planner.price`` span annotated with the fabric geometry, rule name,
    and whether the rule embedded.
    """
    if not _TRACER.enabled:
        return _price_candidate_impl(
            cfg, shape, fabric, node_dims, n_compute, rule, backend
        )
    with _TRACER.span(
        "planner.price", fabric=tuple(fabric.dims), rule=tuple(rule.axis_sizes)
    ) as sp:
        priced = _price_candidate_impl(
            cfg, shape, fabric, node_dims, n_compute, rule, backend
        )
        sp.annotate(embedded=priced is not None)
        return priced


def _price_candidate_impl(
    cfg: ArchConfig,
    shape: ShapeConfig,
    fabric: Fabric,
    node_dims: Geometry,
    n_compute: int,
    rule: ShardingRuleSet,
    backend: Optional[str] = None,
) -> Optional[Tuple[Optional[RankMapping], AxisAssignment, Tuple, float, float, float, float, float]]:
    chips = fabric.num_chips
    ring_fab = fabric.ring_equivalent()
    entries = rule_traffic(cfg, shape, rule.axis_sizes)
    pair_chip = pairing_stress_volume(entries, rule.axis_sizes)
    traffic = rule_rank_traffic(rule.axis_sizes, entries, pair_chip)
    mesh_shape = rule.mesh_shape
    mapping = None
    try:
        if traffic is not None:
            mapping = map_ranks(
                ring_fab.dims,
                ring_fab.dims,
                logical_dims=tuple(rule.axis_sizes),
                traffic=traffic,
                double_link_on_2=ring_fab.double_link_on_2,
                refine=False,  # the catalogue is oracle-enumerable; greedy
                wrap=ring_fab.wrap,  # refinement is seeded local search
                backend=backend,
            )
        assignment = assign_axes(
            ring_fab, mesh_shape, order_hint=rule.order_hint, mapping=mapping
        )
    except ValueError:
        return None  # rule does not embed in this geometry
    cost_model = CollectiveCostModel(ring_fab, assignment)
    ring_time = 0.0
    for axis, collective, vol in entries:
        ring_time += cost_model.time(collective, axis, vol)
    # Node-level pairing stress: per-chip volume rescaled to the node torus
    # (identity on chip-level fabrics where volume(node_dims) == chips).
    pair_node = pair_chip * chips / volume(node_dims)
    pairing_time = fabric.pairing_time(pair_node, node_dims) if pair_node > 0.0 else 0.0
    cost = cell_cost(
        cfg, shape, float(cfg.param_count()),
        cache_bytes=_decode_cache_bytes(cfg, shape),
    )
    compute_time = cost.flops_compiled / (n_compute * PEAK_FLOPS)
    memory_time = cost.bytes_hbm / (n_compute * HBM_BW)
    return (
        mapping, assignment, tuple(entries), pair_node,
        ring_time, pairing_time, compute_time, memory_time,
    )


# ---------------------------------------------------------------------------
# The plan.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class SlicePlan:
    """The planner's answer for one (config, chip budget): a ranked table
    of priced (geometry, mapping, rule) triples, best first."""

    arch: str
    shape: str
    chips: int
    pod_dims: Geometry
    wrap_mode: str
    table: Tuple[PlanCandidate, ...]

    @property
    def best(self) -> PlanCandidate:
        return self.table[0]

    @property
    def geometry(self) -> Geometry:
        return self.best.geometry

    @property
    def step_time(self) -> float:
        return self.best.step_time

    @property
    def bisection_efficiency(self) -> float:
        return self.best.bisection_efficiency

    @property
    def simulated_slowdown(self) -> float:
        return self.best.simulated_slowdown

    def geometry_preferences(self) -> List[Geometry]:
        """Distinct geometries in ranked-row order (for occupancy walks)."""
        seen, out = set(), []
        for cand in self.table:
            if cand.geometry not in seen:
                seen.add(cand.geometry)
                out.append(cand.geometry)
        return out

    def to_request(self, job_id: int, duration: float = 1.0, arrival: float = 0.0):
        """The plan as a scheduler :class:`repro.network.allocation.JobRequest`
        carrying the planner-chosen geometry."""
        from repro.network.allocation import JobRequest

        return JobRequest(
            job_id=job_id,
            units=self.chips,
            duration=duration,
            arrival=arrival,
            geometry=self.geometry,
        )


def default_chip_budget(cfg: ArchConfig) -> int:
    """Smallest power-of-two budget whose ZeRO-3 weight shards fit HBM
    (bf16 weights only; optimizer/cache headroom is the caller's concern)."""
    need = BF16 * cfg.param_count() / HBM_BYTES
    return max(4, 2 ** math.ceil(math.log2(max(need, 1.0))))


def plan_model(
    arch: Union[str, ArchConfig],
    chips: Optional[int] = None,
    *,
    pod: Optional[Fabric] = None,
    shape: Union[str, ShapeConfig] = "decode_32k",
    wrap_mode: str = "slice",
    unit_node_dims: Optional[Sequence[int]] = None,
    simulate_top_k: int = 0,
    backend: Optional[str] = None,
) -> SlicePlan:
    """Jointly search geometry x mapping x sharding for one config.

    ``wrap_mode="slice"`` (default) uses TPU slice semantics: geometries
    from :func:`ranked_slice_geometries`, wrap links only where a slice
    spans a full pod dimension.  ``wrap_mode="torus"`` uses the paper's
    Blue Gene/Q semantics: every partition is its own fully-wrapped torus
    (:func:`ranked_geometries`), with ``unit_node_dims`` scaling allocation
    units to the node level (Tables 4-6).

    ``simulate_top_k`` drains the top-k ranked rows' mapped traffic
    through the flow simulator and records the measured contention
    multiplier on ``simulated_slowdown`` (1.0 analytic default —
    tier-1 tests keep k=0 so no netsim runs on the hot path).

    A :class:`~repro.network.fabric.HyperXFabric` pod is also accepted.
    There the slice/torus wrap distinction collapses — an aligned sub-box
    of a clique dimension is itself a clique, so every partition has full
    wrap along every dim regardless of where it sits — and both
    ``wrap_mode`` values rank the same Lindsey-exact bisection table
    (:func:`repro.network.isoperimetry.ranked_geometries` on the fabric).
    ``unit_node_dims`` node scaling is the Blue Gene/Q torus convention
    and is rejected on HyperX pods.
    """
    cfg = arch if isinstance(arch, ArchConfig) else get_arch(arch)
    shape_cfg = shape if isinstance(shape, ShapeConfig) else SHAPES[shape]
    pod = pod or _default_pod()
    budget = chips if chips is not None else min(default_chip_budget(cfg), pod.num_chips)
    if wrap_mode not in ("slice", "torus"):
        raise ValueError(f"wrap_mode must be 'slice' or 'torus', got {wrap_mode!r}")
    if wrap_mode == "slice" and pod.boxes_share_links:
        ranked = ranked_slice_geometries(pod, budget)
        fabrics = [(g, bis, slice_fabric(pod, g)) for g, bis in ranked]
        nodes = [fab.dims for _, _, fab in fabrics]
    else:
        # Where boxes share no links (HyperX) a box is its own sub-fabric
        # wherever it sits, so slice and torus semantics coincide.
        ranked = ranked_geometries(pod, budget, unit_node_dims)
        fabrics = [(g, bis, pod.sub_fabric(g)) for g, bis in ranked]
        nodes = [scaled_node_dims(g, unit_node_dims) for g, _ in ranked]
    best_bis = ranked[0][1]
    rules = enumerate_rules(cfg, budget)
    rows: List[PlanCandidate] = []
    for gi, ((geom, bis, fabric), node_dims) in enumerate(zip(fabrics, nodes)):
        n_compute = volume(node_dims)
        for rule in rules:
            priced = price_candidate(
                cfg, shape_cfg, fabric, node_dims, n_compute, rule, backend=backend
            )
            if priced is None:
                continue
            mapping, assignment, entries, pair_node, ring, pairing, compute, memory = priced
            rows.append(
                PlanCandidate(
                    geometry=canonical(geom),
                    geometry_rank=gi,
                    bisection_links=int(bis),
                    bisection_efficiency=(bis / best_bis if best_bis else 1.0),
                    fabric=fabric,
                    rule=rule,
                    mapping=mapping,
                    assignment=assignment,
                    traffic=entries,
                    pair_volume_node=pair_node,
                    node_dims=canonical(node_dims),
                    ring_time=ring,
                    pairing_time=pairing,
                    compute_time=compute,
                    memory_time=memory,
                )
            )
    if not rows:
        raise ValueError(
            f"no (geometry, rule) candidate of {budget} chips embeds in pod "
            f"{pod.dims} for arch {cfg.name}"
        )
    rows.sort(key=PlanCandidate.sort_key)
    if simulate_top_k > 0:
        simulated = []
        for cand in rows[:simulate_top_k]:
            simulated.append(replace(cand, simulated_slowdown=_simulate(cand)))
        rows = simulated + rows[simulate_top_k:]
    return SlicePlan(
        arch=cfg.name,
        shape=shape_cfg.name,
        chips=budget,
        pod_dims=canonical(pod.dims),
        wrap_mode=wrap_mode,
        table=tuple(rows),
    )


def _simulate(cand: PlanCandidate) -> float:
    """Flow-simulated contention multiplier of one row's mapped traffic."""
    if cand.mapping is None:
        return 1.0
    src, dst, vol = cand.mapping.machine_traffic()
    if len(vol) == 0 or float(np.sum(vol)) <= 0.0:
        return 1.0
    sim = simulate_traffic(
        cand.fabric, (src, dst, vol),
        link_bw=cand.fabric.link_bw,
        double_link_on_2=cand.fabric.double_link_on_2,
    )
    # netsim's zero-contention bound assumes a single link; on doubled
    # size-2 dims a contention-free pattern beats it (ratio 0.5).  The
    # planner reports a contention *multiplier*, floored at 1.
    return max(1.0, float(sim.slowdown))


def _default_pod() -> TorusFabric:
    from repro.launch.mesh import pod_fabric

    return pod_fabric()


def format_table(plan: SlicePlan, top: int = 8) -> str:
    """Human-readable ranked table of a plan (dry-run output)."""
    head = (
        f"{plan.arch} · {plan.shape} · {plan.chips} chips on pod "
        f"{plan.pod_dims} ({plan.wrap_mode})"
    )
    cols = (
        f"{'geometry':>12} {'d,f,t,e':>12} {'mapping':>16} {'comm(ms)':>9} "
        f"{'step(ms)':>9} {'bis.eff':>8} {'slowdown':>9}"
    )
    lines = [head, cols]
    for cand in plan.table[:top]:
        lines.append(
            f"{str(cand.geometry):>12} {str(cand.axis_sizes):>12} "
            f"{cand.mapping_strategy:>16} {cand.comm_time * 1e3:>9.3f} "
            f"{cand.step_time * 1e3:>9.3f} {cand.bisection_efficiency:>8.2f} "
            f"{cand.simulated_slowdown:>9.3f}"
        )
    if len(plan.table) > top:
        lines.append(f"... {len(plan.table) - top} more rows")
    return "\n".join(lines)


def plan_fleet(
    archs: Optional[Sequence[Union[str, ArchConfig]]] = None,
    **kwargs,
) -> List[SlicePlan]:
    """One :class:`SlicePlan` per config (default: every registered arch,
    name-sorted), each at its :func:`default_chip_budget` unless ``chips``
    is passed through ``kwargs``."""
    if archs is None:
        archs = sorted(all_archs())
    return [plan_model(a, **kwargs) for a in archs]
