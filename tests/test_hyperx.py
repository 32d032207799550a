"""HyperX (Hamming graph) behaviour: closed forms, routing, and the stack.

Pins the four levels of the HyperX story against brute force and each
other:

    hamming closed forms   ==  edge counting over explicitly enumerated
                               cells / subsets (cuts, Lindsey bisection)
    route_hyperx minimal   ==  per-hop Python reference oracle, link for
                               link, and DAL conserves minimal hop volume
    all-to-all max load    ==  closed form == netsim makespan (steady
                               pattern, so static == simulated exactly)
    the stack              ==  advisor certification, box-closure (zero
                               cross-box links), scheduler/planner/obs
                               integration goldens

The geometry preference flips against the torus: on HyperX, covering a
dimension removes it from the bottleneck, so *elongated* boxes win.
"""

import itertools

import numpy as np
import pytest

from repro.network import (
    HyperXFabric,
    IsoperimetricPolicy,
    JobRequest,
    MachineState,
    advise_partition,
    bisection_table,
    compare_routing,
    cut_table,
    hamming_bisection_links,
    hamming_cut_aligned,
    hamming_cut_of_set,
    hamming_subset_bound,
    hyperx_all_to_all_max_load,
    hyperx_max_link_load,
    optimal_cuboid,
    route_hyperx,
    simulate_traffic,
    simulate_queue,
)
from repro.network.backend import HAVE_JAX
from repro.network.geometry import volume
from repro.network.patterns import all_to_all, bisection_pairing, hotspot_line
from repro.obs.contention import attribute_contention


# ---------------------------------------------------------------------------
# Hamming closed forms vs brute force.
# ---------------------------------------------------------------------------
def _brute_cut(dims, cells):
    """Edges (unit multiplicity) leaving ``cells`` — direct enumeration."""
    inside = set(map(tuple, cells))
    cut = 0
    for c in inside:
        for k, a in enumerate(dims):
            for j in range(a):
                if j == c[k]:
                    continue
                nb = list(c)
                nb[k] = j
                if tuple(nb) not in inside:
                    cut += 1
    return cut


def _box_cells(dims, sides):
    return list(itertools.product(*(range(s) for s in sides)))


@pytest.mark.parametrize("dims", [(4, 4), (6, 3), (5, 2, 2)])
def test_aligned_cut_closed_form_matches_enumeration(dims):
    for sides in itertools.product(*(range(1, a + 1) for a in dims)):
        cells = _box_cells(dims, sides)
        want = _brute_cut(dims, cells)
        assert hamming_cut_aligned(dims, sides) == want
        got = hamming_cut_of_set(dims, np.array(cells))
        assert got == want


def test_lindsey_bound_sound_and_tight_by_brute_force():
    """On small uniform Hamming graphs the lex bound equals the true
    minimum over *every* n-subset (Lindsey's theorem), not just boxes."""
    dims = (4, 2)
    n_cells = volume(dims)
    cells = list(itertools.product(*(range(a) for a in dims)))
    for n in range(1, n_cells):
        best = min(
            _brute_cut(dims, subset)
            for subset in itertools.combinations(cells, n)
        )
        assert hamming_subset_bound(dims, n) == best


def test_bisection_links_exact_on_h8x2():
    """H(8,2) half-set: brute force over all C(16,8) subsets."""
    dims = (8, 2)
    cells = list(itertools.product(range(8), range(2)))
    best = min(
        _brute_cut(dims, subset) for subset in itertools.combinations(cells, 8)
    )
    assert best == hamming_bisection_links(dims) == 8


def test_trunked_cut_scales_by_multiplicity():
    base = hamming_cut_aligned((4, 4), (2, 2))
    assert hamming_cut_aligned((4, 4), (2, 2), mult=(3, 3)) == 3 * base


# ---------------------------------------------------------------------------
# Routing engine vs per-hop reference oracle.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dims", [(4, 4), (6, 3, 2), (8, 2)])
def test_route_hyperx_minimal_matches_oracle(dims):
    fab = HyperXFabric(dims)
    rng = np.random.default_rng(5)
    n = volume(dims)
    flat_s = rng.integers(0, n, size=60)
    flat_d = (flat_s + rng.integers(1, n, size=60)) % n
    src = np.stack(np.unravel_index(flat_s, dims), axis=1)
    dst = np.stack(np.unravel_index(flat_d, dims), axis=1)
    vol = rng.uniform(0.5, 2.0, size=60)
    from reference_hyperx import oracle_minimal_loads

    got = route_hyperx(fab, src, dst, vol, mode="minimal")
    np.testing.assert_allclose(got, oracle_minimal_loads(fab, src, dst, vol))


@pytest.mark.parametrize("mode", ["minimal", "dal"])
def test_route_hyperx_conserves_hop_volume(mode):
    """Every DAL order still corrects each differing dim exactly once, so
    total routed volume is vol x Hamming distance for both modes."""
    dims = (8, 4)
    fab = HyperXFabric(dims)
    rng = np.random.default_rng(9)
    n = volume(dims)
    flat_s = rng.integers(0, n, size=50)
    flat_d = (flat_s + rng.integers(1, n, size=50)) % n
    src = np.stack(np.unravel_index(flat_s, dims), axis=1)
    dst = np.stack(np.unravel_index(flat_d, dims), axis=1)
    vol = rng.uniform(0.5, 2.0, size=50)
    loads = route_hyperx(fab, src, dst, vol, mode=mode)
    dist = (src != dst).sum(axis=1)
    assert float(loads.sum()) == pytest.approx(float((vol * dist).sum()))
    assert np.all(loads >= 0.0)


def test_dal_equals_minimal_on_steady_pairing():
    """Hysteresis keeps the canonical order when per-order costs balance:
    on the translation-invariant pairing the DAL load field is
    bit-identical to minimal routing (routing recovers nothing)."""
    dims = (8, 4)
    fab = HyperXFabric(dims)
    src, dst, vol = bisection_pairing(dims)
    a = route_hyperx(fab, src, dst, vol, mode="minimal")
    b = route_hyperx(fab, src, dst, vol, mode="dal")
    np.testing.assert_array_equal(a, b)
    cmp = compare_routing(fab, (src, dst, vol))
    assert cmp.recovered_fraction == 0.0


@pytest.mark.parametrize("dims", [(8, 8), (16, 4), (8, 4)])
def test_dal_beats_minimal_on_hotspot(dims):
    fab = HyperXFabric(dims)
    cmp = compare_routing(fab, hotspot_line(dims))
    assert cmp.dor_makespan == pytest.approx(2.0)
    assert cmp.adaptive_makespan == pytest.approx(10.0 / 7.0)
    assert cmp.recovered_fraction == pytest.approx(2.0 / 7.0)


# ---------------------------------------------------------------------------
# All-to-all: engine == closed form == simulated makespan.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "dims", [(4, 4), (16, 1), (8, 2), (6, 3), (4, 2, 2)]
)
def test_all_to_all_closed_form_matches_engine_and_netsim(dims):
    fab = HyperXFabric(dims)
    src, dst, vol = all_to_all(dims)
    loads = route_hyperx(fab, src, dst, vol)
    want = hyperx_all_to_all_max_load(fab)
    assert hyperx_max_link_load(fab, loads) == pytest.approx(want)
    sim = simulate_traffic(fab, (src, dst, vol))
    assert sim.makespan == pytest.approx(want)  # steady: static == simulated


def test_all_to_all_trunking_divides_load():
    fab = HyperXFabric((4, 4), link_multiplicity=(2, 2))
    assert hyperx_all_to_all_max_load(fab) == pytest.approx(2.0)


def test_elongated_boxes_win_on_hyperx():
    """Same volume, opposite preference to the torus: the geometry
    covering a full dimension has the strictly smallest all-to-all load."""
    pod = HyperXFabric((16, 4))
    loads = {
        g: hyperx_all_to_all_max_load(pod.sub_fabric(g))
        for g in [(16, 1), (8, 2), (4, 4)]
    }
    assert loads[(16, 1)] == 1.0
    assert loads[(16, 1)] < loads[(4, 4)] < loads[(8, 2)]
    assert loads[(8, 2)] / loads[(16, 1)] == pytest.approx(8.0)


@pytest.mark.skipif(not HAVE_JAX, reason="jax backend unavailable")
def test_hyperx_netsim_xla_parity():
    dims = (8, 4)
    fab = HyperXFabric(dims)
    src, dst, vol = hotspot_line(dims)
    a = simulate_traffic(fab, (src, dst, vol), backend="numpy")
    b = simulate_traffic(fab, (src, dst, vol), backend="xla")
    assert a.makespan == pytest.approx(b.makespan)
    np.testing.assert_allclose(a.completion, b.completion)


# ---------------------------------------------------------------------------
# Isoperimetry: tables, optimum, advisor certification.
# ---------------------------------------------------------------------------
def test_cut_table_golden():
    assert list(cut_table(HyperXFabric((4, 4)), 4).items()) == [
        ((2, 2), 16),
        ((4, 1), 12),
    ]


def test_bisection_table_golden():
    assert bisection_table(HyperXFabric((16, 4)), 16).ranked() == [
        ((16, 1), 64),
        ((4, 4), 16),
        ((8, 2), 8),
    ]
    with pytest.raises(ValueError, match="unit_node_dims"):
        bisection_table(HyperXFabric((16, 4)), 16, unit_node_dims=(2, 2))


def test_optimal_cuboid_certified():
    opt = optimal_cuboid(HyperXFabric((16, 4)), 16)
    assert opt.geometry == (16, 1)
    assert opt.cut == 48
    assert opt.bound == 48
    assert opt.tight


def test_advise_partition_certifies_and_simulates():
    adv = advise_partition(
        HyperXFabric((16, 4)), 16, (8, 2), simulate=True
    )
    assert adv.optimal_geometry == (16, 1)
    assert (adv.current_bisection, adv.optimal_bisection) == (8, 64)
    assert adv.predicted_speedup == pytest.approx(8.0)
    assert adv.simulated_speedup == pytest.approx(8.0)  # steady pattern: exact
    assert adv.certified
    assert not adv.is_current_optimal


# ---------------------------------------------------------------------------
# Allocation / scheduler / obs / planner on a HyperX machine.
# ---------------------------------------------------------------------------
def test_box_closure_disjoint_placements_share_no_links():
    """Minimal (and DAL) paths between cells of an aligned box never leave
    the box, so two disjoint jobs' load fields touch disjoint link sets —
    inter-job contention is structurally zero on HyperX."""
    pod = HyperXFabric((16, 4))
    machine = MachineState(pod)
    p1 = machine.allocate(1, (4, 2))
    p2 = machine.allocate(2, (8, 2))
    assert p1 is not None and p2 is not None
    fields = []
    for placement in (p1, p2):
        mesh = machine.cells(placement.oriented, placement.offset)
        grids = np.meshgrid(*(np.asarray(c).ravel() for c in mesh), indexing="ij")
        coords = np.stack([g.ravel() for g in grids], axis=1)
        n = coords.shape[0]
        si = np.repeat(np.arange(n), n)
        di = np.tile(np.arange(n), n)
        keep = si != di
        fields.append(
            route_hyperx(pod, coords[si[keep]], coords[di[keep]], 1.0)
        )
    overlap = (fields[0] > 0) & (fields[1] > 0)
    assert not overlap.any()


def test_traffic_loads_rejects_hyperx():
    machine = MachineState(HyperXFabric((8, 4)))
    machine.allocate(1, (4, 2))
    with pytest.raises(TypeError, match="share no links"):
        machine.traffic_loads()


def test_simulate_queue_on_hyperx_machine():
    pod = HyperXFabric((16, 4))
    jobs = [JobRequest(job_id=i, units=16, duration=1.0) for i in range(3)]
    res = simulate_queue(pod, jobs, IsoperimetricPolicy())
    assert len(res.jobs) == 3 and not res.rejected
    # The isoperimetric preference on HyperX is the *elongated* box.
    assert res.jobs[0].placement.geometry == (16, 1)
    with pytest.raises(ValueError):
        simulate_queue(pod, jobs, IsoperimetricPolicy(), measure_contention=True)
    with pytest.raises(ValueError):
        simulate_queue(pod, jobs, IsoperimetricPolicy(), unit_node_dims=(2, 2))


def test_scheduler_predicted_time_uses_hyperx_closed_form():
    pod = HyperXFabric((16, 4))
    jobs = [
        JobRequest(job_id=0, units=16, duration=1.0, geometry=(16, 1)),
        JobRequest(job_id=1, units=16, duration=1.0, geometry=(8, 2)),
    ]
    res = simulate_queue(pod, jobs, IsoperimetricPolicy())
    by_id = {j.request.job_id: j for j in res.jobs}
    t_good = by_id[0].predicted_comm_time
    t_bad = by_id[1].predicted_comm_time
    assert t_bad / t_good == pytest.approx(8.0)


def test_obs_attribution_cross_traffic_structurally_zero():
    pod = HyperXFabric((16, 4))
    machine = MachineState(pod)
    machine.allocate(1, (4, 2))
    machine.allocate(2, (8, 2))
    report = attribute_contention(machine)
    for job in report.jobs:
        assert job.cross_load == pytest.approx(0.0)
        assert job.self_load > 0.0


def test_planner_accepts_hyperx_pod():
    from repro.launch.planner import plan_model

    pod = HyperXFabric((16, 4))
    plan = plan_model("mixtral-8x7b", 16, pod=pod, shape="decode_32k",
                      simulate_top_k=1)
    assert plan.chips == 16
    assert plan.best.simulated_slowdown >= 1.0
    geoms = {c.geometry for c in plan.table}
    assert geoms == {(16, 1), (8, 2), (4, 4)}
    with pytest.raises(ValueError, match="unit_node_dims"):
        plan_model("mixtral-8x7b", 16, pod=pod, wrap_mode="torus",
                   unit_node_dims=(2, 2))
