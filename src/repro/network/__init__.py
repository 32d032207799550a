"""repro.network — the unified fabric modeling subsystem.

Single home of every geometry / fabric / routing primitive in the repo
(see DESIGN.md):

  geometry    — canonical geometries, factorizations, exact cuboid cut and
                interior counts, exact bisection search, ExplicitTorus.
  isoperimetry— vectorized edge-isoperimetric engine: batched cuts of every
                same-volume geometry via divisor meshgrids, Theorem 2.1/3.1
                bounds with tightness certificates, bisection tables, and
                the partition advisor (current-policy vs optimal geometry
                with predicted + simulated speedups); the cut forms and
                bounds come from the fabric (torus or HyperX).
  fabric      — the abstract Fabric interface (explicit ``links()``
                incidence tables, plus every fact that differs between
                fabric kinds: box closure, the geometry-sensitive
                exchange, cut forms and subset bounds, routing modes, the
                ring stand-in) with TorusFabric (per-dimension wrap
                flags, BG/Q double-link vs TPU single-link conventions)
                and HyperXFabric (Hamming graph: per-dim cliques with
                trunked link multiplicities) implementations, ``as_fabric``
                (plain dims -> the BG/Q torus), Torus compat wrapper,
                slice planning.
  hamming     — Hamming-graph edge-isoperimetry closed forms: aligned-box
                cuts, Lindsey lex bound, packed-edges fallback, exact
                bisections.
  routing     — vectorized NumPy DOR link-load engine, closed-form
                translation-invariant fast paths, pairing predictions;
                the HyperX minimal + DAL engine ``route_hyperx``.
  patterns    — traffic-pattern library (bisection pairing, all-to-all,
                halo exchange, ring collectives, permutations, transpose).
  netsim      — vectorized flow-level simulator: max-min fair link
                sharing over any fabric's paths (torus DOR or
                minimal-adaptive, HyperX minimal or DAL), phased
                collective schedules, prediction validation.
  collectives — jax.lax collective cost model + mesh-axis assignment.
  placement   — vectorized cuboid-placement engine: all free translates via
                circular windowed sums, contention/contact scoring.
  allocation  — partition allocation policies and the online queue
                simulator (arrival streams, EASY backfill).
  scheduler   — event-sourced scheduler service over the allocation
                engine: append-only event log (Arrival/Start/Complete/
                Fail/Preempt/Reclaim), deterministic (time, kind, seq)
                ordering with a scale-aware clock tolerance, priority
                queues with preemption/reclaim, failure evacuation wired
                to runtime/fault_tolerance, backpressure shedding, and a
                seeded scenario generator; simulate_queue is a thin batch
                driver over it.
  mapping     — topology-aware rank mapping inside a placement: strategy
                catalogue (identity / axis-permutation / gray-snake /
                greedy refinement) scored by congestion + dilation.
  backend     — compiled (jax.jit) backends for the hot engines: DOR link
                loads, the progressive-filling drain, the FFT contention
                field, closed-form cut scoring, and the vmap-batched
                candidate scorer; numpy stays the default + exact oracle.

The historical
``repro.core.{torus,contention,collectives,allocation,isoperimetry}``
modules re-export from here and are deprecated.
"""

from .backend import (
    BACKENDS,
    HAVE_JAX,
    DrainPlan,
    drain,
    drain_batch,
    prepare_drain,
    resolve_backend,
    score_candidates,
    xla_contention_field,
    xla_cut_scores,
    xla_route_loads,
)
from .geometry import (
    ExplicitTorus,
    Geometry,
    all_divisor_geometries,
    canonical,
    contains_cuboid,
    cuboid_cut,
    cuboid_cut_aligned,
    cuboid_interior,
    degree_contribution,
    enumerate_vertices,
    factorizations,
    pairing_max_load,
    scaled_node_dims,
    sub_cuboids,
    uniform_offset_max_load,
    volume,
)
from .geometry import bisection_links as torus_bisection_links
from .isoperimetry import (
    BisectionTable,
    CuboidOptimum,
    CutTable,
    PartitionAdvice,
    advise_partition,
    advise_policy_table,
    best_bisection_geometry,
    bisection_of_geometry,
    bisection_table,
    bollobas_leader_bound,
    cut_table,
    fitting_geometries,
    is_isoperimetrically_optimal,
    lemma32_cut,
    optimal_cuboid,
    ranked_geometries,
    small_set_expansion,
    theorem31_bound,
    worst_bisection_geometry,
    worst_cuboid,
)
from .fabric import (
    DEFAULT_LINK_BW,
    POD_DCI_BW,
    Fabric,
    HyperXFabric,
    LinkTable,
    Torus,
    TorusFabric,
    as_fabric,
    best_slice_geometry,
    ranked_slice_geometries,
    slice_fabric,
    worst_slice_geometry,
)
from .hamming import (
    hamming_bisection_links,
    hamming_cut_aligned,
    hamming_cut_of_set,
    hamming_subset_bound,
    hyperx_all_to_all_max_load,
    lindsey_bound,
)
from .routing import (
    LinkLoads,
    PairingPrediction,
    all_to_all_max_load,
    hyperx_max_link_load,
    max_link_load,
    pairing_speedup,
    predict_pairing_time,
    route_dor,
    route_hyperx,
    simulate_pattern,
)
from .patterns import (
    all_to_all,
    bisection_pairing,
    furthest_offset,
    hotspot_line,
    nearest_neighbor_halo,
    pairing_pairs,
    random_permutation,
    ring_all_gather,
    ring_all_reduce_phases,
    ring_shift,
    transpose,
    uniform_shift,
    vertices,
)
from .netsim import (
    FlowPaths,
    FlowSimResult,
    PhasedSimResult,
    PredictionValidation,
    RoutingComparison,
    UtilizationSample,
    adaptive_paths,
    build_paths,
    compare_routing,
    dor_paths,
    link_capacities,
    simulate_flows,
    simulate_phases,
    simulate_traffic,
    validate_prediction,
)
from .collectives import (
    AxisAssignment,
    AxisEmbedding,
    COLLECTIVE_TIME,
    CollectiveCostModel,
    assign_axes,
    collective_permute_time,
    ring_all_gather_time,
    ring_all_reduce_time,
    ring_all_to_all_time,
    ring_reduce_scatter_time,
    simulated_ring_all_reduce_time,
)
from .placement import (
    ScoredPlacement,
    best_placement,
    fabric_can_interfere,
    first_fit,
    free_offset_mask,
    is_spilling,
    iter_free_placements,
    pad_geometry,
    placement_cells,
    placement_loads,
    shell_contact,
)
from .mapping import (
    MAPPING_PATTERNS,
    MappingScore,
    RankMapping,
    axis_permutation_orders,
    identity_mapping,
    map_ranks,
    mapping_loads,
    mapping_traffic,
    mesh_axis_hops,
    pattern_traffic,
    placement_cell_coords,
    score_mapping,
    snake_mapping,
    toroidal_hops,
)
from .allocation import (
    AllocationPolicy,
    ContentionScoredPolicy,
    ElongatedPolicy,
    HintedPolicy,
    IsoperimetricPolicy,
    JobRequest,
    ListPolicy,
    MachineState,
    Placement,
    ScheduledJob,
    SimulationResult,
    avoidable_contention_ratio,
    simulate_queue,
)
from .scheduler import (
    Event,
    Scenario,
    SchedulerService,
    apply_monitor_failures,
    generate_scenario,
    replay_events,
    run_scenario,
    scheduler_throughput,
    time_close,
    time_eps,
)
