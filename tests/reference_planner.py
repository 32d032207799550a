"""Brute-force fleet-planner oracle, kept as a test reference.

Re-derives the whole planner pipeline sequentially — sharding-rule
enumeration, per-rule traffic volumes, rank-space messages, the mapping
catalogue walk, the greedy axis->dimension grouping, and the final
``(step_time, geometry rank, axis sizes)`` ranking — with plain Python
loops, duplicating the closed-form volume formulas of
``repro.launch.planner`` *verbatim* (an edit to a formula must be made in
both places to keep the differential harness green).  Pricing calls the
same public primitives the planner itself promises to be reproducible
from (``AxisEmbedding.from_mapping``, :data:`COLLECTIVE_TIME`,
``predict_pairing_time``, ``cell_cost``), summed in the same order so the
floats are bit-identical.  Do not use in library code.
"""

from __future__ import annotations

import itertools
import math
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.analytic import BF16, cell_cost
from repro.analysis.roofline import HBM_BW, PEAK_FLOPS
from repro.configs import SHAPES, ArchConfig, ShapeConfig
from repro.launch.planner import AXES, HBM_BYTES, ORDER_HINT
from repro.network.collectives import COLLECTIVE_TIME, AxisEmbedding
from repro.network.fabric import TorusFabric, ranked_slice_geometries, slice_fabric
from repro.network.geometry import canonical, volume
from repro.network.geometry import scaled_node_dims
from repro.network.isoperimetry import ranked_geometries
from repro.network.mapping import (
    axis_order_coords,
    axis_permutation_orders,
    identity_mapping,
    score_mapping,
    snake_mapping,
)
from repro.network.routing import predict_pairing_time


def reference_rules(cfg: ArchConfig, chips: int) -> List[Tuple[int, int, int, int]]:
    """Candidate (data, fsdp, tensor, expert) splits, sequential loops."""
    n_experts = cfg.moe.num_experts if cfg.moe is not None else 1
    param_bytes = float(BF16) * cfg.param_count()
    rules = []
    for t in range(1, chips + 1):
        if chips % t or cfg.n_heads % t:
            continue
        for e in range(1, chips // t + 1):
            if (chips // t) % e or n_experts % e:
                continue
            rest = chips // (t * e)
            for f in range(1, rest + 1):
                if rest % f:
                    continue
                rules.append((rest // f, f, t, e))
    feasible = [r for r in rules if param_bytes / (r[1] * r[2] * r[3]) <= HBM_BYTES]
    return feasible if feasible else rules


def reference_traffic(
    cfg: ArchConfig, shape: ShapeConfig, axis_sizes: Tuple[int, int, int, int]
) -> List[Tuple[str, str, float]]:
    """(axis, collective, per-chip bytes) entries — formulas duplicated."""
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


def reference_pair_volume(entries, axis_sizes) -> float:
    e = axis_sizes[3]
    vol = 0.0
    for axis, collective, v in entries:
        if axis == "data" and collective == "all-reduce":
            vol += 0.5 * v
        if axis == "expert" and collective == "all-to-all":
            vol += v / e
    return vol


def reference_rank_traffic(axis_sizes, entries, pair_volume):
    """Rank-space messages, per-rank Python loops (planner order)."""
    shape = tuple(axis_sizes)
    n = int(np.prod(shape))
    per_axis = {a: 0.0 for a in AXES}
    a2a_volume = 0.0
    for axis, collective, v in entries:
        if axis == "expert" and collective == "all-to-all":
            a2a_volume += v
        else:
            per_axis[axis] += v
    coords = [tuple(np.unravel_index(r, shape)) for r in range(n)]
    ravel = {c: r for r, c in enumerate(coords)}
    srcs, dsts, vols = [], [], []

    def send(k: int, step: int, v: float) -> None:
        for r, c in enumerate(coords):
            nb = list(c)
            nb[k] = (nb[k] + step) % shape[k]
            srcs.append(r)
            dsts.append(ravel[tuple(nb)])
            vols.append(v)

    for k, axis in enumerate(AXES):
        s, v = shape[k], per_axis[axis]
        if s <= 1 or v <= 0.0:
            continue
        send(k, 1, v / 2.0)
        send(k, -1, v / 2.0)
    e = shape[3]
    if e > 1 and a2a_volume > 0.0:
        for off in range(1, e):
            send(3, off, a2a_volume / e)
    d = shape[0]
    if d > 1 and pair_volume > 0.0:
        send(0, d // 2, pair_volume)
    if not srcs:
        return None
    return (
        np.array(srcs, dtype=np.int64),
        np.array(dsts, dtype=np.int64),
        np.array(vols, dtype=np.float64),
    )


def reference_choose_mapping(fabric: TorusFabric, traffic):
    """Sequential catalogue walk: identity, axis permutations, gray-snake;
    first (congestion, dilation) minimum wins (``map_ranks`` semantics,
    ``refine=False``)."""
    dims, oriented = fabric.dims, fabric.dims
    offset = (0,) * len(dims)
    cands = [("identity", identity_mapping(dims, oriented, offset))]
    for perm, rev in axis_permutation_orders(oriented):
        if all(p == i for i, p in enumerate(perm)) and not any(rev):
            continue
        cands.append(
            ("axis-permutation", axis_order_coords(dims, oriented, offset, perm, rev))
        )
    cands.append(("gray-snake", snake_mapping(dims, oriented, offset)))
    scored = [
        (name, c, score_mapping(dims, c, traffic, True, fabric.double_link_on_2))
        for name, c in cands
    ]
    return min(scored, key=lambda t: t[2].key())


def reference_dim_groups(
    fabric: TorusFabric, axis_sizes: Tuple[int, int, int, int]
) -> Optional[Dict[str, Tuple[int, ...]]]:
    """The greedy whole-dimension grouping of ``assign_axes`` (ORDER_HINT
    priority, smallest group, wrapped dims preferred); None = inadmissible."""
    remaining = list(range(len(fabric.dims)))
    groups: Dict[str, Tuple[int, ...]] = {}
    sizes = dict(zip(AXES, axis_sizes))
    for name in ORDER_HINT:
        size = sizes[name]
        if size == 1:
            groups[name] = ()
            continue
        got = None
        for k in range(1, len(remaining) + 1):
            options = []
            for combo in itertools.combinations(remaining, k):
                if math.prod(fabric.dims[i] for i in combo) == size:
                    n_wrapped = sum(bool(fabric.wrap[i]) for i in combo)
                    options.append((-n_wrapped, combo))
            if options:
                got = min(options)[1]
                break
        if got is None:
            return None
        groups[name] = got
        for i in got:
            remaining.remove(i)
    return groups


def reference_price(
    cfg: ArchConfig,
    shape: ShapeConfig,
    fabric: TorusFabric,
    node_dims,
    n_compute: int,
    axis_sizes: Tuple[int, int, int, int],
):
    """Sequentially price one (fabric, rule) pair; None when inadmissible.

    Returns the oracle row ``(geometry-free fields): (axis_sizes, strategy,
    ring, pairing, compute, memory, step)``.
    """
    if reference_dim_groups(fabric, axis_sizes) is None:
        return None
    entries = reference_traffic(cfg, shape, axis_sizes)
    pair_chip = reference_pair_volume(entries, axis_sizes)
    traffic = reference_rank_traffic(axis_sizes, entries, pair_chip)
    strategy = "none"
    ring_time = 0.0
    if traffic is not None:
        strategy, coords, _score = reference_choose_mapping(fabric, traffic)
        mapping_ns = SimpleNamespace(
            dims=fabric.dims, coords=coords, wrap=fabric.wrap
        )
        for axis, collective, vol in entries:
            emb = AxisEmbedding.from_mapping(
                mapping_ns, tuple(axis_sizes), AXES.index(axis)
            )
            ring_time += COLLECTIVE_TIME[collective](vol, emb, fabric.link_bw)
    pair_node = pair_chip * fabric.num_chips / volume(node_dims)
    pairing_time = 0.0
    if pair_node > 0.0:
        pred = predict_pairing_time(
            node_dims, 1.0, fabric.link_bw,
            double_link_on_2=fabric.double_link_on_2,
        )
        pairing_time = pair_node * pred.time_per_volume
    cache = 0.0
    if shape.kind == "decode" and not cfg.is_attention_free:
        cache = (
            2.0 * cfg.n_layers * shape.global_batch * shape.seq_len
            * cfg.n_kv_heads * cfg.resolved_head_dim * BF16
        )
    cost = cell_cost(cfg, shape, float(cfg.param_count()), cache_bytes=cache)
    compute_time = cost.flops_compiled / (n_compute * PEAK_FLOPS)
    memory_time = cost.bytes_hbm / (n_compute * HBM_BW)
    step = max(compute_time, memory_time) + (ring_time + pairing_time)
    return (
        tuple(axis_sizes), strategy, ring_time, pairing_time,
        compute_time, memory_time, step,
    )


def reference_plan(
    cfg: ArchConfig,
    chips: int,
    pod: TorusFabric,
    shape,
    wrap_mode: str = "slice",
    unit_node_dims: Optional[Sequence[int]] = None,
) -> List[Tuple]:
    """The oracle's ranked table: rows in the planner's ``row()`` layout,
    every (geometry, rule) triple priced sequentially and sorted by the
    documented ``(step_time, geometry rank, axis sizes)`` key."""
    shape_cfg = shape if isinstance(shape, ShapeConfig) else SHAPES[shape]
    if wrap_mode == "slice":
        ranked = ranked_slice_geometries(pod, chips)
        fabs = [(g, slice_fabric(pod, g)) for g, _ in ranked]
        nodes = [fab.dims for _, fab in fabs]
    else:
        ranked = ranked_geometries(pod.dims, chips, unit_node_dims)
        fabs = [
            (g, TorusFabric(g, (True,) * len(g), pod.link_bw,
                            double_link_on_2=pod.double_link_on_2))
            for g, _ in ranked
        ]
        nodes = [scaled_node_dims(g, unit_node_dims) for g, _ in ranked]
    rows = []
    for gi, ((geom, fabric), node_dims) in enumerate(zip(fabs, nodes)):
        for rule in reference_rules(cfg, chips):
            priced = reference_price(
                cfg, shape_cfg, fabric, node_dims, volume(node_dims), rule
            )
            if priced is None:
                continue
            axes, strategy, ring, pairing, compute, memory, step = priced
            rows.append(
                (step, gi, axes,
                 (canonical(geom), axes, strategy, ring, pairing,
                  compute, memory, step))
            )
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    return [r[3] for r in rows]
