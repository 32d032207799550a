"""Conformance suite for the abstract Fabric interface.

Every implementation (TorusFabric, HyperXFabric) must expose the same
contract — an explicit ``links()`` incidence table the rest of the stack
programs against, and the facts the engines above it ask instead of
branching on the fabric's type — so the checks here run identically over
both:

    link-id hygiene        unique ids inside the dense slot space
    links <-> neighbors    neighbors() derivable from the table, symmetric
    capacity symmetry      src->dst trunk capacity == dst->src
    netsim incidence       build_paths routes only over links() slots,
                           with the table's own per-slot capacities
    one entry point        build_paths / simulate_traffic bit-for-bit the
                           torus-only dor_paths on every torus spelling
    Fabric members         each checked against an independent oracle:
                           geometry's closed forms and reference_dor on a
                           torus, brute-force enumeration over the link
                           table and reference_hyperx on HyperX

plus the regression for the slice planners' clear TypeError on non-ring
fabrics (wrap semantics are meaningless on cliques), and the layering
check that ``fabric`` imports nothing above it.
"""

import ast
import itertools
from pathlib import Path

import numpy as np
import pytest

from reference_dor import ReferenceLinkLoads
from reference_hyperx import oracle_minimal_loads
from repro.network import (
    HyperXFabric,
    Torus,
    TorusFabric,
    as_fabric,
    build_paths,
    cut_table,
    dor_paths,
    fitting_geometries,
    hyperx_max_link_load,
    predict_pairing_time,
    ranked_slice_geometries,
    route_dor,
    simulate_flows,
    simulate_traffic,
    slice_fabric,
    worst_slice_geometry,
)
from repro.network import geometry, patterns
from repro.network.geometry import canonical, scaled_node_dims, volume
from repro.network.netsim import link_capacities
from repro.network.placement import first_fit, placement_cells

FABRICS = [
    pytest.param(TorusFabric.bgq((4, 4, 2)), id="torus-bgq-4x4x2"),
    pytest.param(TorusFabric.tpu((4, 2), wrap=(True, False)), id="torus-tpu-4x2-chain"),
    pytest.param(TorusFabric.tpu((8, 1)), id="torus-tpu-8x1"),
    pytest.param(HyperXFabric((4, 4)), id="hyperx-4x4"),
    pytest.param(HyperXFabric((6, 3, 2)), id="hyperx-6x3x2"),
    pytest.param(HyperXFabric((4, 3), link_multiplicity=(2, 3)), id="hyperx-trunked"),
    pytest.param(HyperXFabric((5, 1)), id="hyperx-5x1"),
]


def _random_traffic(fabric, rng, n_msgs=40):
    """Random (src, dst, vol) coordinate traffic with no self-messages."""
    dims = fabric.dims
    n = volume(dims)
    src = rng.integers(0, n, size=n_msgs)
    dst = (src + rng.integers(1, n, size=n_msgs)) % n
    vol = rng.uniform(0.5, 2.0, size=n_msgs)
    to_coords = lambda flat: np.stack(np.unravel_index(flat, dims), axis=1)
    return to_coords(src), to_coords(dst), vol


def _geometries(fabric, max_units=12):
    """Every fitting box geometry of 1..max_units cells."""
    for t in range(1, min(fabric.num_cells, max_units) + 1):
        for row in fitting_geometries(fabric, t):
            yield tuple(int(x) for x in row)


def _brute_cut(fabric, member):
    """Unit-capacity links leaving the flat cell set ``member`` — direct
    enumeration over the fabric's own link table."""
    table = fabric.links()
    leave = member[table.src] & ~member[table.dst]
    return float((table.capacity[leave] / fabric.link_bw).sum())


def _box_member(fabric, sides):
    """Flat mask of the aligned box with these per-dimension sides at the
    origin."""
    member = np.zeros(fabric.dims, dtype=bool)
    member[tuple(slice(0, s) for s in sides)] = True
    return member.ravel()


# ---------------------------------------------------------------------------
# The links() table itself.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fabric", FABRICS)
def test_link_ids_unique_and_in_slot_space(fabric):
    table = fabric.links()
    assert len(np.unique(table.link)) == len(table)
    if len(table):
        assert table.link.min() >= 0
        assert table.link.max() < table.n_slots
    assert np.all(table.capacity > 0.0)
    n = fabric.num_cells
    assert np.all((table.src >= 0) & (table.src < n))
    assert np.all((table.dst >= 0) & (table.dst < n))
    assert np.all(table.src != table.dst)


@pytest.mark.parametrize("fabric", FABRICS)
def test_neighbors_match_table_and_are_symmetric(fabric):
    table = fabric.links()
    n = fabric.num_cells
    adj = {c: set() for c in range(n)}
    for s, d in zip(table.src, table.dst):
        adj[int(s)].add(int(d))
    for cell in range(n):
        nbrs = fabric.neighbors(cell)
        assert list(nbrs) == sorted(adj[cell])
        assert np.all(nbrs != cell)
        for other in nbrs:
            assert cell in adj[int(other)]  # directed table covers both ways


@pytest.mark.parametrize("fabric", FABRICS)
def test_capacity_symmetric_per_cell_pair(fabric):
    table = fabric.links()
    cap = {}
    for s, d, c in zip(table.src, table.dst, table.capacity):
        cap[(int(s), int(d))] = cap.get((int(s), int(d)), 0.0) + float(c)
    for (s, d), c in cap.items():
        assert cap[(d, s)] == pytest.approx(c)


@pytest.mark.parametrize("fabric", FABRICS)
def test_dense_capacities_zero_only_on_unused_slots(fabric):
    table = fabric.links()
    dense = table.dense_capacities()
    assert dense.shape == (table.n_slots,)
    np.testing.assert_allclose(dense[table.link], table.capacity)
    used = np.zeros(table.n_slots, dtype=bool)
    used[table.link] = True
    assert np.all(dense[~used] == 0.0)


def test_torus_link_table_matches_netsim_capacities():
    """The torus table folds BG/Q double links into capacity exactly as
    netsim's ``link_capacities`` tensor does, slot for slot."""
    for fab in (TorusFabric.bgq((4, 2, 2)), TorusFabric.tpu((4, 2))):
        dense = fab.links().dense_capacities()
        ref = link_capacities(
            fab.dims, link_bw=fab.link_bw, double_link_on_2=fab.double_link_on_2
        ).ravel()
        np.testing.assert_allclose(dense, ref)


def test_hyperx_degree_and_link_count():
    fab = HyperXFabric((4, 3), link_multiplicity=(2, 3))
    table = fab.links()
    # One directed table row per (cell, same-dim peer); trunking folds
    # into capacity, not row count.
    assert len(table) == fab.num_cells * sum(a - 1 for a in fab.dims)
    assert fab.degree == sum(k * (a - 1) for a, k in zip(fab.dims, fab.link_multiplicity))
    nbrs = fab.neighbors(0)
    assert len(nbrs) == sum(a - 1 for a in fab.dims)


# ---------------------------------------------------------------------------
# netsim builds its incidence from the same table, through one entry point.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fabric", FABRICS)
def test_netsim_routes_only_over_fabric_links(fabric):
    rng = np.random.default_rng(7)
    src, dst, vol = _random_traffic(fabric, rng)
    paths = build_paths(fabric, (src, dst, vol))
    table = fabric.links()
    assert np.all(np.isin(paths.link_ids, table.link))
    if isinstance(fabric, HyperXFabric):
        np.testing.assert_allclose(
            paths.capacities, table.dense_capacities() / fabric.link_bw
        )
    else:
        assert paths.capacities is None  # historical torus layout


def test_fabric_sim_bit_identical_to_torus_sim():
    """The generic entry point on a torus is the torus-only engine path:
    ``dor_paths`` drained by ``simulate_flows``, bit for bit, with
    ``route_dor``'s load tensor."""
    fab = TorusFabric.bgq((4, 4))
    rng = np.random.default_rng(11)
    src, dst, vol = _random_traffic(fab, rng)
    a = simulate_traffic(
        fab, (src, dst, vol), link_bw=fab.link_bw, double_link_on_2=True
    )
    b = simulate_flows(
        dor_paths(fab.dims, src, dst, vol), link_bw=fab.link_bw, double_link_on_2=True
    )
    assert a.makespan == b.makespan
    assert a.slowdown == b.slowdown
    np.testing.assert_array_equal(a.completion, b.completion)
    np.testing.assert_array_equal(a.link_loads, b.link_loads)
    np.testing.assert_allclose(
        a.link_loads, route_dor(fab.dims, src, dst, vol), atol=1e-12
    )


def test_build_paths_torus_bit_for_bit_every_spelling():
    dims = (4, 4, 2)
    rng = np.random.default_rng(3)
    fab = TorusFabric.bgq(dims)
    src, dst, vol = _random_traffic(fab, rng)
    want = dor_paths(dims, src, dst, vol).link_loads()
    for spelling in (fab, Torus(dims), dims):
        got = build_paths(spelling, (src, dst, vol)).link_loads()
        np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(want, route_dor(dims, src, dst, vol), atol=1e-12)


def test_build_paths_rejects_foreign_modes():
    traffic = (np.array([[0, 0]]), np.array([[1, 1]]), 1.0)
    with pytest.raises(ValueError, match="expected 'dor' or 'adaptive'"):
        build_paths(TorusFabric.bgq((4, 4)), traffic, mode="dal")
    with pytest.raises(ValueError, match="expected 'minimal' or 'dal'"):
        build_paths(HyperXFabric((4, 4)), traffic, mode="dor")


def test_build_paths_hyperx_returns_flat_loads():
    hx = HyperXFabric((4, 4))
    paths = build_paths(hx, (np.array([[0, 0]]), np.array([[2, 3]]), 1.0))
    loads = paths.link_loads()
    assert loads.shape == (hx.links().n_slots,)
    assert float(loads.sum()) == 2.0  # Hamming distance 2, one unit each hop


# ---------------------------------------------------------------------------
# The Fabric members, each against an independent oracle.
# ---------------------------------------------------------------------------
def test_as_fabric_coerces_every_spelling():
    bgq = TorusFabric.bgq((4, 2))
    assert as_fabric((4, 2)) == bgq
    assert as_fabric(np.array([4, 2])) == bgq
    assert as_fabric(Torus((2, 4))) == Torus((2, 4)).fabric()
    for fab in (bgq, TorusFabric.tpu((4, 2)), HyperXFabric((4, 2))):
        assert as_fabric(fab) is fab


@pytest.mark.parametrize("fabric", FABRICS)
def test_canonical_is_an_isomorphic_relabeling(fabric):
    can = fabric.canonical()
    assert can.dims == canonical(fabric.dims)
    assert can.canonical() is can
    assert type(can) is type(fabric)
    assert can.bisection_links() == fabric.bisection_links()
    a, b = fabric.links(), can.links()
    assert len(a) == len(b)
    np.testing.assert_allclose(np.sort(a.capacity), np.sort(b.capacity))


@pytest.mark.parametrize("fabric", FABRICS)
def test_sub_fabric_bisection_matches_oracle(fabric):
    for g in _geometries(fabric):
        sub = fabric.sub_fabric(g)
        assert sub.dims == canonical(g) + (1,) * (len(fabric.dims) - len(g))
        assert sub.link_bw == fabric.link_bw
        if isinstance(fabric, TorusFabric):
            assert all(sub.wrap)
            assert sub.double_link_on_2 == fabric.double_link_on_2
            if fabric.double_link_on_2:
                assert sub.bisection_links() == geometry.bisection_links(g)
            continue
        # HyperX: the bisection is achievable, and exact for uniform
        # multiplicity, against every half-set of the sub-box.
        n = sub.num_cells
        cuts = []
        for half in itertools.combinations(range(n), n // 2):
            member = np.zeros(n, dtype=bool)
            member[list(half)] = True
            cuts.append(_brute_cut(sub, member))
        assert min(cuts) <= sub.bisection_links()
        if len(set(sub.link_multiplicity)) == 1:
            assert sub.bisection_links() == min(cuts)
    with pytest.raises(ValueError):
        fabric.sub_fabric((max(fabric.dims) + 1,))


@pytest.mark.parametrize("fabric", FABRICS)
def test_box_bisections_match_oracle(fabric):
    for t in range(1, fabric.num_cells + 1):
        geoms = fitting_geometries(fabric, t)
        bis = fabric.box_bisections(geoms)
        assert bis.dtype == np.int64 and bis.shape == (len(geoms),)
        for g, b in zip(geoms, bis):
            g = tuple(int(x) for x in g)
            if isinstance(fabric, TorusFabric):
                assert b == geometry.bisection_links(g)  # the paper's convention
            else:
                assert b == fabric.sub_fabric(g).bisection_links()
    unit = (2,) * (len(fabric.dims) + 1)
    geoms = fitting_geometries(fabric, 2)
    if isinstance(fabric, TorusFabric):
        got = fabric.canonical().box_bisections(geoms, unit)
        want = [geometry.bisection_links(scaled_node_dims(g, unit)) for g in geoms]
        assert list(got) == want
    else:
        with pytest.raises(ValueError, match="unit_node_dims"):
            fabric.box_bisections(geoms, unit)
        with pytest.raises(ValueError, match="unit_node_dims"):
            fabric.check_unit_node_dims(unit)


@pytest.mark.parametrize("fabric", FABRICS)
def test_boxes_share_links_matches_routing(fabric):
    """Box closure, by routing: a box's all-to-all under the fabric's base
    mode loads a link outside the box for some box exactly when the
    fabric says disjoint boxes can share links."""
    leaks = False
    for g in _geometries(fabric, max_units=fabric.num_cells - 1):
        oriented, offset = first_fit(np.zeros(fabric.dims, dtype=bool), g)
        member = np.zeros(fabric.dims, dtype=bool)
        member[placement_cells(fabric.dims, oriented, offset)] = True
        cells = np.stack(np.nonzero(member), axis=1)
        si, di = np.nonzero(~np.eye(len(cells), dtype=bool))
        if not len(si):
            continue
        loads = build_paths(fabric, (cells[si], cells[di], 1.0)).link_loads()
        outside = loads[~fabric.internal_links(member)]
        leaks |= bool((outside > 0.0).any())
    assert leaks == fabric.boxes_share_links


@pytest.mark.parametrize("fabric", FABRICS)
def test_exchange_max_load_matches_routed_oracle(fabric):
    """The closed form of the box's exchange equals the per-hop oracle's
    max link load: bisection pairing under reference DOR on a torus,
    all-to-all under reference minimal routing on HyperX."""
    for g in _geometries(fabric, max_units=8):
        box = fabric.exchange_box(g)
        src, dst, vol = getattr(patterns, box.exchange_pattern)(box.dims)
        if isinstance(box, TorusFabric):
            assert box.exchange_pattern == "bisection_pairing"
            ref = ReferenceLinkLoads(box.dims)
            for s, d, v in zip(src, dst, vol):
                ref.add_path(tuple(s), tuple(d), float(v))
            want = ref.max_load()  # BG/Q double-link halving
        else:
            assert box.exchange_pattern == "all_to_all"
            want = hyperx_max_link_load(box, oracle_minimal_loads(box, src, dst, vol))
        assert box.exchange_max_load() == pytest.approx(want)


@pytest.mark.parametrize("fabric", FABRICS)
def test_pairing_time_matches_routed_oracle(fabric):
    """The planner's pairing price: the routed max link load of the
    pairing exchange (antipodal on a torus's node dims; halving-doubling
    across the longest dimension on HyperX) over the link bandwidth."""
    nbytes = 3.0e6
    dims = fabric.dims
    if isinstance(fabric, TorusFabric):
        node = canonical(dims)
        src, dst, vol = patterns.bisection_pairing(node)
        ref = ReferenceLinkLoads(node)
        for s, d, v in zip(src, dst, vol):
            ref.add_path(tuple(s), tuple(d), float(v))
        arr = ref.load_array()
        load = max(
            float(arr[k].max()) * (0.5 if a == 2 and fabric.double_link_on_2 else 1.0)
            for k, a in enumerate(node)
        )
        pred = predict_pairing_time(
            node, 1.0, fabric.link_bw, double_link_on_2=fabric.double_link_on_2
        )
        assert fabric.pairing_time(nbytes, node) == nbytes * pred.time_per_volume
    else:
        k = max(range(len(dims)), key=lambda i: dims[i])
        src = patterns.vertices(dims)
        dst = src.copy()
        dst[:, k] = (dst[:, k] + dims[k] // 2) % dims[k]
        vol = np.ones(len(src))
        load = hyperx_max_link_load(fabric, oracle_minimal_loads(fabric, src, dst, vol))
    assert fabric.pairing_time(nbytes, dims) == pytest.approx(nbytes * load / fabric.link_bw)


@pytest.mark.parametrize("fabric", FABRICS)
def test_routing_modes_drive_build_paths(fabric):
    base, adaptive = fabric.routing_modes
    rng = np.random.default_rng(5)
    traffic = _random_traffic(fabric, rng)
    default = build_paths(fabric, traffic)
    assert default.mode == base
    np.testing.assert_array_equal(
        default.link_loads(), build_paths(fabric, traffic, mode=base).link_loads()
    )
    other = build_paths(fabric, traffic, mode=adaptive)
    assert other.mode == adaptive
    # Both modes are minimal: they move the same hop volume.
    assert other.link_loads().sum() == pytest.approx(default.link_loads().sum())


@pytest.mark.parametrize("fabric", FABRICS)
def test_ring_stand_in_prices_ring_shifts(fabric):
    """A one-step ring shift along any dimension longer than 2 drains on
    the ring stand-in no faster than on the fabric itself — exactly as
    fast under uniform link multiplicity.  (On a length-2 ring both
    directions reach the neighbour, so the torus splits the tie.)"""
    ring = fabric.ring_equivalent()
    assert isinstance(ring, TorusFabric) and ring.dims == fabric.dims
    if isinstance(fabric, TorusFabric):
        assert ring is fabric
    uniform = len(set(getattr(fabric, "link_multiplicity", (1,)))) == 1
    for k, a in enumerate(fabric.dims):
        if a <= 2:
            continue
        traffic = patterns.ring_shift(fabric.dims, k, 1, vol=1.0e6)
        t_fab = simulate_traffic(
            fabric, traffic, link_bw=fabric.link_bw,
            double_link_on_2=fabric.double_link_on_2,
        ).makespan
        t_ring = simulate_traffic(
            ring, traffic, link_bw=ring.link_bw, double_link_on_2=ring.double_link_on_2
        ).makespan
        assert t_ring >= t_fab * (1 - 1e-12)
        if uniform:
            assert t_ring == pytest.approx(t_fab)


@pytest.mark.parametrize("fabric", FABRICS)
def test_line_cuts_give_aligned_box_cuts(fabric):
    """``sum_k (t / s_k) * line_cuts`` is the box's cut: the paper's
    double-link wrapped torus form on a torus, brute-force edge counting
    over the link table on HyperX."""
    for sides in itertools.product(*(range(1, a + 1) for a in fabric.dims)):
        S = np.array([sides], dtype=np.int64)
        t = volume(sides)
        got = int(((t // S) * fabric.line_cuts(S)).sum())
        if isinstance(fabric, TorusFabric):
            assert got == geometry.cuboid_cut_aligned(fabric.dims, sides)
        else:
            assert got == _brute_cut(fabric, _box_member(fabric, sides))


@pytest.mark.parametrize("fabric", FABRICS)
def test_subset_cut_bound_is_sound(fabric):
    """The bound never exceeds the best box's cut; on a uniform Hamming
    graph it is the exact minimum over every subset (Lindsey)."""
    n = fabric.num_cells
    uniform_hx = len(set(getattr(fabric, "link_multiplicity", (0, 1)))) == 1
    for t in range(1, n):
        bound = fabric.subset_cut_bound(t)
        rows = fitting_geometries(fabric, t)
        if len(rows):
            assert bound <= cut_table(fabric, t).cuts.min() + 1e-9
        if uniform_hx and n <= 16:
            best = min(
                _brute_cut(fabric, np.isin(np.arange(n), subset))
                for subset in itertools.combinations(range(n), t)
            )
            assert bound == pytest.approx(best)


@pytest.mark.parametrize("fabric", FABRICS)
def test_load_layout_and_slot_labels(fabric):
    table = fabric.links()
    assert volume(fabric.load_shape) == table.n_slots
    for link, src, dst in zip(table.link, table.src, table.dst):
        k, direction, cell = fabric.slot_label(int(link))
        assert cell == tuple(int(c) for c in np.unravel_index(int(src), fabric.dims))
        to = list(cell)
        a = fabric.dims[k]
        if isinstance(fabric, TorusFabric):
            to[k] = (to[k] + (1 if direction == 0 else -1)) % a
        else:
            to[k] = direction
        assert int(np.ravel_multi_index(tuple(to), fabric.dims)) == int(dst)


@pytest.mark.parametrize("fabric", FABRICS)
def test_internal_links_match_table(fabric):
    table = fabric.links()
    rng = np.random.default_rng(13)
    for _ in range(5):
        member = rng.random(fabric.dims) < 0.5
        own = fabric.internal_links(member)
        assert own.shape == fabric.load_shape
        flat = member.ravel()
        np.testing.assert_array_equal(
            own.ravel()[table.link], flat[table.src] & flat[table.dst]
        )


def test_fabric_imports_only_geometry_and_hamming():
    """``fabric`` sits below routing: it imports nothing from routing,
    netsim, placement, allocation, scheduler or isoperimetry (function-
    local imports included)."""
    import repro.network.fabric as mod

    tree = ast.parse(Path(mod.__file__).read_text())
    package = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.level > 0:
            if node.module:
                package.add(node.module.split(".")[0])
            else:
                package.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and (node.module or "").startswith("repro"):
            package.add(node.module)
        elif isinstance(node, ast.Import):
            package.update(a.name for a in node.names if a.name.startswith("repro"))
    assert package == {"geometry", "hamming"}


# ---------------------------------------------------------------------------
# Slice planning stays ring-only (regression for the clear TypeError).
# ---------------------------------------------------------------------------
def test_slice_planners_reject_hyperx_with_clear_type_error():
    hx = HyperXFabric((4, 4))
    with pytest.raises(TypeError, match="ring"):
        slice_fabric(hx, (2, 2))
    with pytest.raises(TypeError, match="ring"):
        ranked_slice_geometries(hx, 4)
    with pytest.raises(TypeError, match="ring"):
        worst_slice_geometry(hx, 4)
