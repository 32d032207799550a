"""Property and behaviour tests for the compiled network backends.

Pins the tentpole's exactness contracts:

    numpy route_dor          ==  xla route_dor        (bit-exact loads)
    numpy simulate_flows     ~=  xla drain            (<= 1e-9 rel rates)
    sequential score_mapping ==  batched score_candidates   (row-exact)
    numpy cut_table          ==  xla cut_table        (int64-exact)
    numpy contention_field   ==  xla contention_field (bit-exact)

plus the dispatch machinery (env variable, explicit argument, error
paths) and the golden Mira / JUQUEEN partition parity the acceptance
criteria name.  Property tests sample random fabrics up to 4D with
integer volumes (where exactness is meaningful) and skip cleanly when
jax is not installed; the dispatch tests run everywhere.
"""

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

import repro.network.backend as backend_mod
from repro.network import (
    HAVE_JAX,
    bisection_pairing,
    cut_table,
    dor_paths,
    resolve_backend,
    route_dor,
    score_candidates,
    simulate_flows,
    simulate_traffic,
)
from repro.network.backend import drain, drain_batch, prepare_drain
from repro.network.mapping import map_ranks, pattern_traffic, score_mapping

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")

# Small random fabrics: exact parity is shape-independent, and tiny dims
# keep the per-example jit compiles cheap.
dims_strategy = st.lists(st.integers(1, 5), min_size=1, max_size=4).map(tuple)


def _random_messages(rng_seed, dims, n_msgs):
    rng = np.random.default_rng(rng_seed)
    src = np.stack([rng.integers(0, a, n_msgs) for a in dims], axis=1)
    dst = np.stack([rng.integers(0, a, n_msgs) for a in dims], axis=1)
    vol = rng.integers(1, 5, n_msgs).astype(np.float64)
    return src, dst, vol


# ---------------------------------------------------------------------------
# Dispatch (runs with or without jax).
# ---------------------------------------------------------------------------
def test_default_backend_is_numpy(monkeypatch):
    monkeypatch.delenv("REPRO_NETWORK_BACKEND", raising=False)
    assert resolve_backend() == "numpy"
    assert resolve_backend(None) == "numpy"


def test_env_variable_selects_backend(monkeypatch):
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "numpy")
    assert resolve_backend() == "numpy"
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "")
    assert resolve_backend() == "numpy"  # empty value falls back to default
    if HAVE_JAX:
        monkeypatch.setenv("REPRO_NETWORK_BACKEND", "xla")
        assert resolve_backend() == "xla"
        assert resolve_backend("numpy") == "numpy"  # explicit argument wins


def test_unknown_backend_raises(monkeypatch):
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda")
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "nonsense")
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend()


def test_pallas_slot_reserved():
    with pytest.raises(NotImplementedError, match="pallas"):
        resolve_backend("pallas")


def test_xla_without_jax_raises(monkeypatch):
    monkeypatch.setattr(backend_mod, "HAVE_JAX", False)
    with pytest.raises(RuntimeError, match="requires jax"):
        resolve_backend("xla")


@needs_jax
def test_record_utilization_is_numpy_only():
    paths = dor_paths((4, 4), *bisection_pairing((4, 4)))
    with pytest.raises(ValueError, match="record_utilization"):
        simulate_flows(paths, record_utilization=True, backend="xla")


# ---------------------------------------------------------------------------
# Route-load exactness.
# ---------------------------------------------------------------------------
@needs_jax
@settings(max_examples=10, deadline=None)
@given(
    dims=dims_strategy,
    seed=st.integers(0, 2**31 - 1),
    n_msgs=st.integers(1, 24),
    split_ties=st.booleans(),
)
def test_route_loads_exact(dims, seed, n_msgs, split_ties):
    src, dst, vol = _random_messages(seed, dims, n_msgs)
    loads_np = route_dor(dims, src, dst, vol, split_ties=split_ties)
    loads_x = route_dor(dims, src, dst, vol, split_ties=split_ties, backend="xla")
    assert loads_np.shape == loads_x.shape
    assert np.array_equal(loads_np, loads_x)


@needs_jax
def test_route_loads_empty_and_scalar_vol():
    empty = np.zeros((0, 2), dtype=np.int64)
    out = route_dor((4, 3), empty, empty, np.zeros(0), backend="xla")
    assert out.shape == (2, 2, 4, 3) and not out.any()
    src, dst, _ = _random_messages(7, (4, 3), 5)
    assert np.array_equal(
        route_dor((4, 3), src, dst, 2.0),
        route_dor((4, 3), src, dst, 2.0, backend="xla"),
    )


# ---------------------------------------------------------------------------
# Max-min drain parity.
# ---------------------------------------------------------------------------
@needs_jax
@settings(max_examples=6, deadline=None)
@given(
    dims=st.lists(st.integers(2, 4), min_size=2, max_size=3).map(tuple),
    seed=st.integers(0, 2**31 - 1),
    n_msgs=st.integers(1, 12),
)
def test_simulate_flows_rates_match(dims, seed, n_msgs):
    src, dst, vol = _random_messages(seed, dims, n_msgs)
    paths = dor_paths(dims, src, dst, vol)
    res_np = simulate_flows(paths)
    res_x = simulate_flows(paths, backend="xla")
    assert np.array_equal(res_np.link_loads, res_x.link_loads)
    scale = max(res_np.makespan, 1.0)
    assert abs(res_np.makespan - res_x.makespan) <= 1e-9 * scale
    np.testing.assert_allclose(
        res_np.flow_completion, res_x.flow_completion, rtol=1e-9, atol=1e-12
    )
    assert res_np.steps == res_x.steps


@needs_jax
def test_drain_batch_lanes_match_single_drains():
    paths = dor_paths((4, 4, 2), *bisection_pairing((4, 4, 2)))
    plan = prepare_drain(paths)
    rng = np.random.default_rng(3)
    vols = rng.integers(1, 4, size=(4, plan.n_flows)).astype(np.float64)
    fc_b, steps_b = drain_batch(plan, vols)
    for i in range(vols.shape[0]):
        fc_i, steps_i = drain(plan, vols[i])
        assert np.array_equal(fc_b[i], fc_i)
        assert steps_b[i] == steps_i


@needs_jax
def test_drain_input_validation():
    paths = dor_paths((4, 4), *bisection_pairing((4, 4)))
    with pytest.raises(ValueError, match="link_bw"):
        prepare_drain(paths, link_bw=0.0)
    plan = prepare_drain(paths)
    with pytest.raises(ValueError, match="shape"):
        drain(plan, np.ones(plan.n_flows + 1))
    with pytest.raises(ValueError, match="shape"):
        drain_batch(plan, np.ones((2, plan.n_flows + 1)))


# ---------------------------------------------------------------------------
# Batched candidate scoring.
# ---------------------------------------------------------------------------
@needs_jax
@settings(max_examples=6, deadline=None)
@given(
    dims=st.lists(st.integers(2, 4), min_size=2, max_size=3).map(tuple),
    seed=st.integers(0, 2**31 - 1),
    batch=st.integers(1, 6),
)
def test_score_candidates_rows_match_sequential(dims, seed, batch):
    rng = np.random.default_rng(seed)
    n_cells = int(np.prod(dims))
    n_ranks = min(6, n_cells)
    traffic = pattern_traffic((n_ranks,), "ring")
    cells = np.stack(
        [rng.choice(n_cells, n_ranks, replace=False) for _ in range(batch)]
    )
    coords = np.stack(np.unravel_index(cells, dims), axis=-1).astype(np.int64)
    cong_x, dil_x = score_candidates(dims, coords, traffic, backend="xla")
    for i in range(batch):
        ref = score_mapping(dims, coords[i], traffic)
        assert cong_x[i] == ref.congestion
        assert dil_x[i] == ref.dilation


@needs_jax
def test_score_candidates_edge_shapes():
    traffic = pattern_traffic((4,), "ring")
    coords = np.stack(np.unravel_index(np.arange(4), (2, 2)), axis=-1)
    cong2d, dil2d = score_candidates((2, 2), coords, traffic, backend="xla")
    assert cong2d.shape == (1,) and dil2d.shape == (1,)
    empty = np.zeros(0, dtype=np.int64)
    cong0, dil0 = score_candidates(
        (2, 2), coords, (empty, empty.copy(), np.zeros(0)), backend="xla"
    )
    assert cong0.shape == (1,) and cong0[0] == 0.0 and dil0[0] == 0.0
    with pytest.raises(ValueError, match="coords"):
        score_candidates((2, 2), np.zeros((3,), dtype=np.int64), traffic)


@needs_jax
def test_map_ranks_backend_parity():
    m_np = map_ranks((4, 8), (2, 8), (0, 0), logical_dims=(8, 2), pattern="halo")
    m_x = map_ranks(
        (4, 8), (2, 8), (0, 0), logical_dims=(8, 2), pattern="halo", backend="xla"
    )
    assert m_np.strategy == m_x.strategy
    assert m_np.score == m_x.score
    assert m_np.identity_score == m_x.identity_score
    assert np.array_equal(m_np.coords, m_x.coords)


# ---------------------------------------------------------------------------
# Cut scoring.
# ---------------------------------------------------------------------------
@needs_jax
@settings(max_examples=10, deadline=None)
@given(
    dims=dims_strategy,
    t=st.integers(1, 32),
)
def test_cut_table_backend_parity(dims, t):
    t_np = cut_table(dims, t)
    t_x = cut_table(dims, t, backend="xla")
    assert t_np.items() == t_x.items()
    assert t_x.cuts.dtype == np.int64


# ---------------------------------------------------------------------------
# Contention field: exact integer correlation on both backends.
# ---------------------------------------------------------------------------
@needs_jax
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_contention_field_backend_parity(seed):
    from repro.network.allocation import MachineState
    from repro.network.placement import contention_field, interference_mask

    rng = np.random.default_rng(seed)
    dims = tuple(int(rng.integers(2, 8)) for _ in range(int(rng.integers(1, 4))))
    m = MachineState(dims)
    for job in range(3):
        m.allocate(job, tuple(int(rng.integers(1, d + 1)) for d in dims))
    mask = interference_mask(m.grid, m.traffic_loads())
    oriented = tuple(int(rng.integers(1, d + 1)) for d in dims)
    f_np = contention_field(dims, oriented, mask)
    f_x = contention_field(dims, oriented, mask, backend="xla")
    assert f_x.dtype == np.float64 and np.array_equal(f_np, f_x)


# ---------------------------------------------------------------------------
# Golden partition pairs (the acceptance criterion's concrete fabrics).
# ---------------------------------------------------------------------------
@needs_jax
@pytest.mark.parametrize(
    "dims", [(16, 4, 4, 4, 2), (8, 8, 4, 4, 2)], ids=["mira-4mp", "juqueen-4mp"]
)
def test_golden_partition_parity(dims):
    src, dst, vol = bisection_pairing(dims)
    assert np.array_equal(
        route_dor(dims, src, dst, vol),
        route_dor(dims, src, dst, vol, backend="xla"),
    )
    res_np = simulate_traffic(dims, (src, dst, vol))
    res_x = simulate_traffic(dims, (src, dst, vol), backend="xla")
    assert abs(res_np.makespan - res_x.makespan) <= 1e-9 * res_np.makespan


# ---------------------------------------------------------------------------
# Env-variable dispatch end to end.
# ---------------------------------------------------------------------------
@needs_jax
def test_env_backend_reaches_engines(monkeypatch):
    src, dst, vol = _random_messages(11, (4, 3), 8)
    expected = route_dor((4, 3), src, dst, vol)
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "xla")
    assert np.array_equal(route_dor((4, 3), src, dst, vol), expected)
    res = simulate_traffic((4, 4), bisection_pairing((4, 4)))
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "numpy")
    ref = simulate_traffic((4, 4), bisection_pairing((4, 4)))
    assert abs(res.makespan - ref.makespan) <= 1e-9 * max(ref.makespan, 1.0)


# ---------------------------------------------------------------------------
# Fleet-planner backend parity: the ranked table is bit-identical whether
# candidate mappings are scored sequentially (numpy) or batched (xla).
# ---------------------------------------------------------------------------
@needs_jax
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_planner_table_backend_parity(shape):
    from repro.configs import ArchConfig, MoEConfig
    from repro.launch.planner import plan_model
    from repro.network.fabric import TorusFabric

    tiny = ArchConfig(
        name="tiny-moe-backend", family="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
        moe=MoEConfig(num_experts=4, top_k=2),
    )
    pod = TorusFabric.tpu((4, 4))
    p_np = plan_model(tiny, 8, pod=pod, shape=shape, backend="numpy")
    p_x = plan_model(tiny, 8, pod=pod, shape=shape, backend="xla")
    assert [c.row() for c in p_np.table] == [c.row() for c in p_x.table]


@needs_jax
def test_planner_env_backend_dispatch(monkeypatch):
    from repro.launch.planner import plan_model
    from repro.network.fabric import TorusFabric

    pod = TorusFabric.tpu((4, 4))
    ref = plan_model("mixtral-8x7b", 8, pod=pod, shape="decode_32k")
    monkeypatch.setenv("REPRO_NETWORK_BACKEND", "xla")
    env = plan_model("mixtral-8x7b", 8, pod=pod, shape="decode_32k")
    assert [c.row() for c in ref.table] == [c.row() for c in env.table]
