"""Property tests: sharding rules produce valid, divisible PartitionSpecs for
every architecture x mesh size combination (the dry-run's core invariant)."""

import jax
import pytest
from jax.sharding import PartitionSpec as P
from _hypothesis_compat import given, settings, st

from repro.configs import all_archs, get_arch
from repro.distributed.sharding import ShardingRules, axis_size
from repro.models import build_model


class FakeMesh:
    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)


MESHES = [
    {"data": 16, "model": 16},
    {"pod": 2, "data": 16, "model": 16},
    {"data": 4, "model": 8},
    {"data": 1, "model": 1},
]

ARCHS = sorted(all_archs())


def _check_specs(arch_name, mesh_shape):
    arch = get_arch(arch_name)
    mesh = FakeMesh(mesh_shape)
    rules = ShardingRules(arch, mesh)
    model = build_model(arch)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    specs = rules.params_specs(params)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if axis is None:
                continue
            sz = axis_size(mesh, axis)
            assert dim % sz == 0, (path, leaf.shape, spec)


@pytest.mark.parametrize("arch_name", ARCHS)
@pytest.mark.parametrize("mesh_shape", MESHES, ids=lambda m: "x".join(map(str, m.values())))
def test_param_specs_valid_and_divisible(arch_name, mesh_shape):
    _check_specs(arch_name, mesh_shape)


@settings(max_examples=25, deadline=None)
@given(
    arch_name=st.sampled_from(ARCHS),
    data=st.sampled_from([1, 2, 4, 8, 16]),
    model=st.sampled_from([1, 2, 4, 8, 16, 32]),
)
def test_property_specs_for_random_mesh_sizes(arch_name, data, model):
    _check_specs(arch_name, {"data": data, "model": model})


@pytest.mark.parametrize("arch_name", ARCHS)
def test_cache_specs_valid(arch_name):
    arch = get_arch(arch_name)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = ShardingRules(arch, mesh)
    model = build_model(arch)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    specs = rules.cache_specs(cache)
    flat_c = jax.tree_util.tree_flatten_with_path(cache)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_c, flat_s):
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if axis is None:
                continue
            assert dim % axis_size(mesh, axis) == 0, (path, leaf.shape, spec)


@pytest.mark.parametrize(
    "mesh_shape,want",
    [({"data": 4, "model": 8}, P("data", None, "model")),
     ({"data": 2, "model": 64}, P("data", "model", None))],
    ids=["whole-heads", "sequence"],
)
def test_zamba2_flat_kv_splits_whole_heads_or_the_sequence(mesh_shape, want):
    """Zamba2's per-application ``(B, S, K*hd)`` leaves: the flat axis goes
    over the model axis only where that splits the 32 kv heads whole (8
    ways: 4 heads a shard), else the sequence does (64 ways)."""
    arch = get_arch("zamba2-7b")
    rules = ShardingRules(arch, FakeMesh(mesh_shape))
    cache = jax.eval_shape(lambda: build_model(arch).init_cache(8, 1024))
    specs = rules.cache_specs(cache)
    assert cache["k"][0].shape == (8, 1024, arch.n_kv_heads * arch.resolved_head_dim)
    assert specs["k"] == specs["v"] == [want] * len(arch.shared_applications)


def test_pure_dp_layout_no_duplicate_axes():
    """opt4 layout: model axis disabled, batch/moments over both mesh axes —
    the ZeRO-1 opt_specs path must not emit duplicate axis entries."""
    arch = get_arch("rwkv6-3b")
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = ShardingRules(
        arch, mesh, fsdp_axes=("data", "model"), model_axis="none", zero_stage=1
    )
    model = build_model(arch)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    pspecs = rules.params_specs(params)
    for spec in jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)):
        assert all(s is None for s in spec)  # ZeRO-1 + no TP: replicated params
    ospecs = rules.opt_specs(params)
    used = set()
    for spec in jax.tree.leaves(ospecs, is_leaf=lambda x: isinstance(x, P)):
        flat = []
        for entry in spec:
            if entry is None:
                continue
            flat.extend(entry if isinstance(entry, tuple) else (entry,))
        assert len(flat) == len(set(flat)), spec  # no duplicate mesh axes
        used |= set(flat)
    assert used  # moments are actually sharded

# ---------------------------------------------------------------------------
# Constructor + spec validation (the planner's sharding-rule contract).
# ---------------------------------------------------------------------------
from repro.distributed.sharding import validate_partition_spec  # noqa: E402


def test_unknown_fsdp_axis_rejected():
    arch = get_arch("rwkv6-3b")
    mesh = FakeMesh({"data": 16, "model": 16})
    with pytest.raises(ValueError, match="fsdp"):
        ShardingRules(arch, mesh, fsdp_axes=("data", "replica"))


def test_model_axis_in_fsdp_axes_rejected():
    arch = get_arch("rwkv6-3b")
    mesh = FakeMesh({"data": 16, "model": 16})
    with pytest.raises(ValueError, match="model"):
        ShardingRules(arch, mesh, fsdp_axes=("data", "model"), model_axis="model")


def test_duplicate_fsdp_axes_rejected():
    arch = get_arch("rwkv6-3b")
    mesh = FakeMesh({"data": 16, "model": 16})
    with pytest.raises(ValueError, match="repeat"):
        ShardingRules(arch, mesh, fsdp_axes=("data", "data"))


def test_validate_partition_spec_accepts_valid():
    validate_partition_spec(["data", "model", None], FakeMesh({"data": 4, "model": 8}))
    validate_partition_spec([("data", "model"), None], {"data": 4, "model": 8})
    validate_partition_spec([None, None], ["data", "model"])


def test_validate_partition_spec_rejects_absent_axis():
    with pytest.raises(ValueError, match="absent"):
        validate_partition_spec(["data", "expert"], {"data": 4, "model": 8})


def test_validate_partition_spec_rejects_reused_axis():
    with pytest.raises(ValueError, match="reuse|more than once|duplicate"):
        validate_partition_spec(["model", ("data", "model")], {"data": 4, "model": 8})
