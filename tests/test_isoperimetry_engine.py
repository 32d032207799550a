"""Property tests pinning the vectorized isoperimetry engine to the
historical per-cuboid oracle (``tests/reference_isoperimetry.py``), plus the
stack wiring: policy ranking, queue-replay bisection efficiency, slice
planning, and the partition advisor's paper-table reproduction."""

import math

import pytest
from _hypothesis_compat import given, settings, st

from reference_isoperimetry import (
    reference_bisection_table,
    reference_cut_table,
    reference_optimal_cuboid,
    reference_small_set_expansion,
    reference_worst_cuboid,
)
from repro.core.bgq import (
    JUQUEEN,
    MIDPLANE_DIMS,
    MIRA,
    MIRA_PROPOSED_PARTITIONS,
    MIRA_SCHEDULER_PARTITIONS,
)
from repro.launch.mesh import plan_slice
from repro.network import (
    ContentionScoredPolicy,
    IsoperimetricPolicy,
    JobRequest,
    MachineState,
    TorusFabric,
    simulate_queue,
)
from repro.network.fabric import ranked_slice_geometries, slice_fabric
from repro.network.geometry import bisection_links, sub_cuboids, volume
from repro.network.isoperimetry import (
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
    worst_cuboid,
)

dims_upto_4d = st.lists(st.integers(1, 6), min_size=1, max_size=4).map(tuple)


# ---------------------------------------------------------------------------
# Engine == oracle.
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(dims=dims_upto_4d, data=st.data())
def test_property_cut_table_equals_oracle(dims, data):
    """The batched cut table equals the per-cuboid loop exactly: same
    geometry set, same minimum cut per geometry, same row order."""
    n = volume(dims)
    t = data.draw(st.integers(1, n))
    assert cut_table(dims, t).items() == reference_cut_table(dims, t)


@settings(max_examples=60, deadline=None)
@given(dims=dims_upto_4d, data=st.data())
def test_property_optimal_and_worst_equal_oracle(dims, data):
    """optimal/worst cuboid match the oracle including the deterministic
    tie-breaks and the complement-symmetry bound."""
    n = volume(dims)
    t = data.draw(st.integers(1, n))
    opt, ref_opt = optimal_cuboid(dims, t), reference_optimal_cuboid(dims, t)
    wst, ref_wst = worst_cuboid(dims, t), reference_worst_cuboid(dims, t)
    if ref_opt is None:
        assert opt is None and wst is None and ref_wst is None
        return
    assert (opt.geometry, opt.cut) == ref_opt[:2]
    assert opt.bound == pytest.approx(ref_opt[2])
    assert (wst.geometry, wst.cut) == ref_wst[:2]
    assert wst.bound == pytest.approx(ref_wst[2])


@settings(max_examples=20, deadline=None)
@given(dims=st.lists(st.integers(1, 5), min_size=1, max_size=3).map(tuple), data=st.data())
def test_property_small_set_expansion_equals_oracle(dims, data):
    """The regularity-identity shortcut (only min cuts needed) equals the
    full double loop over sizes x cuboids with explicit interiors."""
    n = volume(dims)
    t = data.draw(st.integers(1, min(n, 12)))
    assert small_set_expansion(dims, t) == pytest.approx(
        reference_small_set_expansion(dims, t)
    )


@settings(max_examples=40, deadline=None)
@given(dims=st.lists(st.integers(2, 8), min_size=2, max_size=4).map(tuple), data=st.data())
def test_property_lemma32_consistent_with_batched_cuts(dims, data):
    """Wherever the Lemma 3.2 construction S_r exists, its geometry appears
    in the batched cut table with the identical cut, and the batched
    minimum never exceeds it (S_r is a witness, the table is exhaustive)."""
    n = volume(dims)
    t = data.draw(st.integers(1, n // 2))
    tbl = dict(cut_table(dims, t).items())
    for r in range(len(dims)):
        got = lemma32_cut(dims, t, r)
        if got is None:
            continue
        geom, cut = got
        assert tbl[geom] == cut
        assert min(tbl.values()) <= cut


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_property_bollobas_leader_equals_theorem31_on_cubic(data):
    """On cubic tori [n]^D the generalized Theorem 3.1 bound reduces to the
    Bollobás-Leader Theorem 2.1 bound for every t."""
    n = data.draw(st.sampled_from([2, 3, 4, 5, 6, 8]))
    D = data.draw(st.integers(1, 3))
    t = data.draw(st.integers(0, n**D // 2))
    assert math.isclose(
        theorem31_bound((n,) * D, t), bollobas_leader_bound(n, D, t)
    )


# ---------------------------------------------------------------------------
# Bisection tables and rankings.
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(dims=dims_upto_4d, data=st.data())
def test_property_bisection_table_matches_reference(dims, data):
    """Batched internal bisections equal per-geometry ``bisection_links``
    (closed form and odd-longest-dimension search alike), and the ranked
    ordering equals the historical sorted-by-bisection preference list."""
    n = volume(dims)
    t = data.draw(st.integers(1, n))
    ref = reference_bisection_table(dims, t)
    if not ref:
        with pytest.raises(ValueError):
            bisection_table(dims, t)
        return
    tbl = bisection_table(dims, t)
    got = [(tuple(int(x) for x in g), int(b)) for g, b in zip(tbl.geometries, tbl.bisections)]
    assert got == ref
    old_ranking = sorted(sub_cuboids(dims, t), key=lambda g: (-bisection_links(g), g))
    assert [g for g, _ in ranked_geometries(dims, t)] == old_ranking


@settings(max_examples=40, deadline=None)
@given(dims=dims_upto_4d)
def test_property_bisection_of_geometry_matches_geometry_module(dims):
    assert bisection_of_geometry(dims) == bisection_links(dims)


def test_bisection_table_node_level_matches_bgq_partitions():
    """Node-level tables reproduce the paper machines' best/worst partition
    choices (geometry and bandwidth, including tie-breaks) for every size."""
    for machine in (MIRA, JUQUEEN):
        for mp in machine.partition_sizes():
            tbl = bisection_table(machine.midplane_dims, mp, MIDPLANE_DIMS)
            assert tbl.best() == machine.best_partition(mp)
            assert tbl.worst() == machine.worst_partition(mp)


def test_is_isoperimetrically_optimal_certificate():
    # Mira's 4-midplane scheduler geometry is *not* optimal; the proposed is.
    assert not is_isoperimetrically_optimal(
        MIRA.midplane_dims, (4, 1, 1, 1), MIDPLANE_DIMS
    )
    assert is_isoperimetrically_optimal(
        MIRA.midplane_dims, (2, 2, 1, 1), MIDPLANE_DIMS
    )
    with pytest.raises(ValueError):
        is_isoperimetrically_optimal(MIRA.midplane_dims, (5, 1, 1, 1), MIDPLANE_DIMS)


def test_fitting_geometries_empty_when_nothing_fits():
    assert fitting_geometries((4, 2), 5).shape[0] == 0
    with pytest.raises(ValueError):
        best_bisection_geometry((4, 2), 5)


def test_bisection_table_rejects_short_unit_node_dims():
    """A unit_node_dims with fewer dims than the machine would silently drop
    allocation dimensions — it must be a descriptive error, not a numpy
    broadcast failure."""
    from repro.network.geometry import scaled_node_dims

    with pytest.raises(ValueError, match="fewer dims"):
        bisection_table((4, 4, 3, 2), 4, unit_node_dims=(2, 2))
    with pytest.raises(ValueError, match="fewer dims"):
        scaled_node_dims((2, 2, 1, 1), (2, 2))


def test_bisection_of_handles_dim_count_mismatches():
    tbl = bisection_table((4, 4), 4)
    # unit dims normalise away: (2, 2, 1) on the 2-D machine is the (2, 2) row
    assert tbl.bisection_of((2, 2, 1)) == tbl.bisection_of((2, 2))
    # a genuinely 3-D geometry of matching volume is a descriptive error
    with pytest.raises(ValueError, match="not a fitting"):
        bisection_table((4, 4), 8).bisection_of((2, 2, 2))
    with pytest.raises(ValueError, match="not a fitting"):
        advise_partition((4, 4), 8, (2, 2, 2))


# ---------------------------------------------------------------------------
# The partition advisor (paper Tables 4-6).
# ---------------------------------------------------------------------------
def test_advisor_reproduces_mira_proposed_partitions():
    """For every size where the paper proposes an improvement (Table 1 /
    Table 6), the advisor's optimum is exactly the proposed geometry, the
    predicted speedup is the bisection ratio (x2 for the Fig-3 pairs), and
    the current geometry is certified non-optimal."""
    advice = advise_policy_table(
        MIRA.midplane_dims, MIRA_SCHEDULER_PARTITIONS, unit_node_dims=MIDPLANE_DIMS
    )
    by_size = {a.units: a for a in advice}
    for mp, proposed in MIRA_PROPOSED_PARTITIONS.items():
        a = by_size[mp]
        assert a.optimal_geometry == proposed
        assert not a.is_current_optimal
        assert a.predicted_speedup == pytest.approx(
            a.optimal_bisection / a.current_bisection
        )
        assert 0.5 <= a.bisection_efficiency < 1.0
    for mp in (4, 8, 16):  # the Fig-3 pairs: exactly x2
        assert by_size[mp].predicted_speedup == pytest.approx(2.0)
    # sizes with no proposal are already optimal (no improvement exists)
    for mp, a in by_size.items():
        if mp not in MIRA_PROPOSED_PARTITIONS:
            assert a.is_current_optimal and a.predicted_speedup == pytest.approx(1.0)


def test_advisor_simulated_cross_check_matches_prediction():
    """simulate=True cross-checks the static pairing prediction against the
    flow simulator: for these translation-invariant patterns the two agree
    exactly (the §7 validation property at the advisor level)."""
    a = advise_partition(
        MIRA.midplane_dims, 4, (4, 1, 1, 1), unit_node_dims=MIDPLANE_DIMS,
        simulate=True,
    )
    assert a.simulated_speedup == pytest.approx(a.predicted_speedup, rel=1e-9)
    assert a.predicted_speedup == pytest.approx(2.0)
    assert a.certified  # Theorem 3.1 pins the optimum's bisection exactly


def test_advisor_defaults_to_worst_geometry_baseline():
    a = advise_partition(JUQUEEN.midplane_dims, 8, unit_node_dims=MIDPLANE_DIMS)
    worst = JUQUEEN.worst_partition(8)
    best = JUQUEEN.best_partition(8)
    assert (a.current_geometry, a.current_bisection) == worst
    assert (a.optimal_geometry, a.optimal_bisection) == best
    assert a.predicted_speedup == pytest.approx(2.0)


def test_advisor_validates_current_geometry():
    with pytest.raises(ValueError):
        advise_partition(MIRA.midplane_dims, 4, (2, 2, 2, 1), unit_node_dims=MIDPLANE_DIMS)


# ---------------------------------------------------------------------------
# Stack wiring: policies, queue replay, slice planning.
# ---------------------------------------------------------------------------
def test_contention_scored_floor_validation():
    with pytest.raises(ValueError):
        ContentionScoredPolicy(min_bisection_efficiency=1.5)


def test_contention_scored_floor_prunes_inefficient_geometries():
    m = MachineState((4, 4, 3, 2))
    default = ContentionScoredPolicy()
    strict = ContentionScoredPolicy(min_bisection_efficiency=1.0)
    assert default.geometry_preferences(m, 4) == [(2, 2, 1, 1), (4, 1, 1, 1)]
    assert strict.geometry_preferences(m, 4) == [(2, 2, 1, 1)]
    # the optimum always meets the floor, so no size becomes impossible
    for units in (1, 2, 4, 8, 16, 24):
        assert strict.geometry_preferences(m, units)


def test_contention_scored_floor_waits_instead_of_degrading():
    """On a fragmented machine the floored policy delays a job rather than
    granting an elongated partition: a (4, 3) resident leaves only a
    (4, 1) line free, which the relaxed policy grants at half efficiency
    while the floored policy waits for a (2, 2)."""
    jobs = [
        JobRequest(0, 12, duration=4.0),  # (4, 3): leaves a (4, 1) line free
        JobRequest(1, 4, duration=1.0, arrival=0.5),
    ]
    relaxed = simulate_queue((4, 4), jobs, ContentionScoredPolicy(), backfill=False)
    strict = simulate_queue(
        (4, 4), jobs,
        ContentionScoredPolicy(min_bisection_efficiency=1.0), backfill=False,
    )
    r_job = next(j for j in relaxed.jobs if j.request.job_id == 1)
    s_job = next(j for j in strict.jobs if j.request.job_id == 1)
    assert r_job.placement.geometry == (4, 1)
    assert r_job.bisection_efficiency == pytest.approx(0.5)
    assert s_job.placement.geometry == (2, 2)
    assert s_job.bisection_efficiency == pytest.approx(1.0)
    assert s_job.start > r_job.start  # efficiency is bought with waiting
    assert strict.mean_bisection_efficiency > relaxed.mean_bisection_efficiency


def test_simulate_queue_records_bisection_efficiency():
    jobs = [JobRequest(i, 4, duration=1.0) for i in range(6)]
    res = simulate_queue(MIRA.midplane_dims, jobs, IsoperimetricPolicy())
    assert all(0.0 < j.bisection_efficiency <= 1.0 for j in res.jobs)
    # the first job lands on an empty machine: the optimal geometry fits
    assert res.jobs[0].bisection_efficiency == pytest.approx(1.0)
    assert 0.0 < res.mean_bisection_efficiency <= 1.0


def test_plan_slice_reports_bisection_efficiency():
    assert plan_slice(16).bisection_efficiency == pytest.approx(1.0)
    state = MachineState((16, 16))
    state.grid[0:16:2, :] = True  # only 1-wide stripes free: (4, 4) cannot fit
    plan = plan_slice(16, state=state)
    assert plan.slice_geometry == (16, 1)
    assert plan.bisection_efficiency == pytest.approx(0.5)


@settings(max_examples=25, deadline=None)
@given(
    dims=st.lists(st.integers(1, 6), min_size=1, max_size=3).map(tuple),
    data=st.data(),
)
def test_property_ranked_slice_geometries_engine_backed_unchanged(dims, data):
    """The engine-backed candidate enumeration leaves the slice ranking
    bit-identical to the historical sub_cuboids-based ranking, for both
    fabric conventions."""
    n = volume(dims)
    chips = data.draw(st.integers(1, n))
    bgq = TorusFabric.bgq(dims)
    tpu = TorusFabric.tpu(dims)
    for pod in (bgq, tpu):
        old = sorted(
            (
                (g, slice_fabric(pod, g).bisection_links())
                for g in sub_cuboids(pod.dims, chips)
            ),
            key=lambda t: (-t[1], t[0]),
        )
        if not old:
            with pytest.raises(ValueError):
                ranked_slice_geometries(pod, chips)
            continue
        assert ranked_slice_geometries(pod, chips) == old
