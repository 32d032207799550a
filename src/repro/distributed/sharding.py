"""GSPMD sharding rules: parameter / batch / cache PartitionSpecs per arch.

Policy (Megatron-TP x ZeRO-FSDP hybrid, the standard large-model recipe):

* "model" axis — tensor parallelism: attention heads, FFN hidden, experts
  (expert parallelism when E divides the axis), vocab where divisible.
* fsdp axes ("pod","data" on the multi-pod mesh) — parameters and optimizer
  state sharded on a non-TP dimension (ZeRO-3); XLA inserts the all-gathers.
* batch is sharded over the fsdp axes (pure data parallelism for
  activations).

Every rule degrades gracefully: a dimension is sharded only when divisible
by the full axis size — otherwise it is replicated (e.g. InternVL2's 14
heads on a 16-way model axis).  KV caches fall back to sequence sharding
when kv_heads don't divide the model axis (nemotron: 8 kv heads, 16-way TP
-> the 32k cache shards over sequence instead).

The *physical* meaning of the mesh axes (which ICI rings they map to) is
decided by the paper-driven axis assignment in launch/mesh.py.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

PyTree = Any


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


def _shard_if(dim: int, axis, mesh: Mesh):
    return axis if axis is not None and dim % axis_size(mesh, axis) == 0 else None


def _flatten_spec_axes(spec) -> list:
    """Mesh-axis names referenced by one PartitionSpec-style entry tuple."""
    flat = []
    for entry in spec:
        if entry is None:
            continue
        flat.extend(entry if isinstance(entry, tuple) else (entry,))
    return flat


def validate_partition_spec(spec, mesh_axes) -> None:
    """Reject ill-formed PartitionSpec-style rules.

    ``spec`` is a sequence of per-dimension entries (``None``, an axis
    name, or a tuple of axis names); ``mesh_axes`` is the mesh's axis-name
    collection (a ``Mesh``, a dict of sizes, or an iterable of names).
    Raises ``ValueError`` when a mesh axis is reused across dimensions (or
    twice within one dimension group) — GSPMD would reject it at lowering,
    but a cost model fed such a rule silently double-counts the axis and
    prices wrong collective volumes — and when a rule references an axis
    that does not exist on the mesh.
    """
    names = getattr(mesh_axes, "axis_names", None)
    if names is None:
        names = tuple(mesh_axes)
    known = set(names)
    flat = _flatten_spec_axes(spec)
    unknown = [a for a in flat if a not in known]
    if unknown:
        raise ValueError(
            f"partition spec {tuple(spec)} references axes {unknown} absent "
            f"from mesh axes {tuple(names)}"
        )
    if len(flat) != len(set(flat)):
        dupes = sorted({a for a in flat if flat.count(a) > 1})
        raise ValueError(
            f"partition spec {tuple(spec)} reuses mesh axes {dupes} across "
            f"conflicting tensor dimensions"
        )


class ShardingRules:
    """Computes PartitionSpecs for a (cfg, mesh) pair."""

    def __init__(self, cfg: ArchConfig, mesh: Mesh, fsdp_axes: Optional[Tuple[str, ...]] = None,
                 model_axis: str = "model", zero_stage: int = 3):
        """``zero_stage``: 3 = params+optimizer FSDP-sharded (default);
        1 = params replicated over the data axes (TP-sharded only), optimizer
        moments still FSDP-sharded — eliminates per-layer weight/activation
        gathers at the price of replicated bf16 params (viable when
        params*2/TP fits HBM; the dW gradients then reduce locally)."""
        self.cfg = cfg
        self.mesh = mesh
        self.zero_stage = zero_stage
        names = mesh.axis_names
        if fsdp_axes is None:
            fsdp_axes = tuple(n for n in names if n != model_axis)
        unknown = [a for a in fsdp_axes if a not in names]
        if unknown:
            raise ValueError(
                f"fsdp_axes {tuple(fsdp_axes)} reference axes {unknown} absent "
                f"from mesh axes {tuple(names)}"
            )
        if model_axis in names and model_axis in fsdp_axes:
            raise ValueError(
                f"model_axis {model_axis!r} also appears in fsdp_axes "
                f"{tuple(fsdp_axes)}: one mesh axis cannot shard both a "
                f"tensor-parallel dimension and the FSDP dimension of the "
                f"same parameter (the rules would emit conflicting specs "
                f"with silently wrong collective volumes)"
            )
        if len(set(fsdp_axes)) != len(tuple(fsdp_axes)):
            raise ValueError(f"fsdp_axes {tuple(fsdp_axes)} repeat a mesh axis")
        self.fsdp: Tuple[str, ...] = tuple(fsdp_axes)
        self.model = model_axis if model_axis in names else None

    # -- helpers ---------------------------------------------------------------
    def fs(self, dim: int):
        """fsdp sharding for a dimension (whole group or nothing)."""
        if self.zero_stage < 3:
            return None
        return _shard_if(dim, self.fsdp, self.mesh)

    def fs_opt(self, dim: int):
        """Optimizer-state sharding (always FSDP — ZeRO-1 keeps moments sharded)."""
        return _shard_if(dim, self.fsdp, self.mesh)

    def opt_specs(self, params_shapes: PyTree) -> PyTree:
        """Optimizer-moment specs: FSDP-sharded regardless of zero stage."""
        if self.zero_stage >= 3:
            return self.params_specs(params_shapes)
        full = ShardingRules(
            self.cfg, self.mesh, self.fsdp,
            self.model if self.model is not None else "__none__",
            zero_stage=3,
        )
        return full.params_specs(params_shapes)

    def tp(self, dim: int):
        return _shard_if(dim, self.model, self.mesh)

    def dp_spec(self) -> Tuple[str, ...]:
        return self.fsdp

    # -- parameters ---------------------------------------------------------------
    def param_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        # the leading stacked-layer dim is never sharded (zamba's layers
        # are lists, one leaf per layer, with nothing stacked)
        stack = 1 if "layers" in names else 0
        core = shape[stack:]
        leaf = names[-1] if names else ""
        spec = [None] * stack + list(self._core_spec(names, leaf, core))
        validate_partition_spec(spec, self.mesh)
        return P(*spec)

    def _core_spec(self, names, leaf, core) -> Sequence:
        cfg, mesh = self.cfg, self.mesh
        if len(core) <= 1:
            return [None] * len(core)
        # embeddings / heads
        if leaf == "embed":
            V, d = core
            return [self.tp(V), self.fs(d)]
        if leaf in ("lm_head",):
            d, V = core
            return [self.fs(d), self.tp(V)]
        if leaf == "lm_heads":  # (nq, d, V)
            _, d, V = core
            return [None, self.fs(d), self.tp(V)]
        # attention
        if leaf == "wq":
            if len(core) == 3:
                d, H, hd = core
                return [self.fs(d), self.tp(H), None]
        if leaf in ("wk", "wv") and len(core) == 3:
            d, K, hd = core
            return [self.fs(d), self.tp(K), None]
        if leaf == "wo" and len(core) == 3:
            H, hd, d = core
            return [self.tp(H), None, self.fs(d)]
        if leaf in ("bq", "bk", "bv"):
            return [self.tp(core[0]), None]
        # MoE
        if "moe" in names:
            if leaf == "router":
                return [self.fs(core[0]), None]
            E = core[0]
            ep = self.tp(E)
            if leaf in ("wi", "wg"):  # (E, d, ff)
                _, d, ff = core
                if ep is not None:
                    return [ep, self.fs(d), None]
                return [None, self.fs(d), self.tp(ff)]
            if leaf == "wo":  # (E, ff, d)
                _, ff, d = core
                if ep is not None:
                    return [ep, None, self.fs(d)]
                return [None, self.tp(ff), self.fs(d)]
        # dense MLP (and rwkv channel mix wk/wv with 2D shapes)
        if leaf in ("wi", "wg") and len(core) == 2:
            d, ff = core
            return [self.fs(d), self.tp(ff)]
        if leaf == "wo" and len(core) == 2:
            ff, d = core
            return [self.tp(ff), self.fs(d)]
        if leaf == "wk" and len(core) == 2 and "channel_mix" in names:
            d, ff = core
            return [self.fs(d), self.tp(ff)]
        if leaf == "wv" and len(core) == 2 and "channel_mix" in names:
            ff, d = core
            return [self.tp(ff), self.fs(d)]
        # rwkv time mix square projections
        if leaf in ("wr", "wk", "wv", "wg") and len(core) == 2:
            d, d2 = core
            return [self.fs(d), self.tp(d2)]
        if leaf == "wo" and len(core) == 2:
            d2, d = core
            return [self.tp(d2), self.fs(d)]
        if leaf in ("wa", "wb"):
            return [self.fs(core[0]), None]
        # mamba projections
        if leaf == "in_proj":
            d, po = core
            return [self.fs(d), self.tp(po)]
        if leaf == "out_proj":
            d_in, d = core
            return [self.tp(d_in), self.fs(d)]
        # fallback: fsdp on the largest dim
        big = max(range(len(core)), key=lambda i: core[i])
        spec = [None] * len(core)
        spec[big] = self.fs(core[big])
        return spec

    def params_specs(self, params_shapes: PyTree) -> PyTree:
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.param_spec(path, leaf.shape), params_shapes
        )

    # -- batches ---------------------------------------------------------------
    def batch_specs(self, batch_shapes: Dict[str, Any]) -> Dict[str, P]:
        out = {}
        for k, v in batch_shapes.items():
            shape = v.shape
            dp = _shard_if(shape[0], self.fsdp, self.mesh)
            out[k] = P(*([dp] + [None] * (len(shape) - 1)))
        return out

    def logits_spec(self, ndim: int) -> P:
        """Sharding for the lm logits: batch over dp, vocab over model
        (only when the padded vocab divides the model axis)."""
        v_axis = self.tp(self.cfg.padded_vocab_size)
        dp = self.fsdp
        return P(*([dp] + [None] * (ndim - 2) + [v_axis]))

    # -- caches ---------------------------------------------------------------
    def cache_specs(self, cache_shapes: PyTree) -> PyTree:
        def spec(path, leaf):
            # zamba's cache entries are lists: the name is the list's key
            leafname = next(str(p.key) for p in reversed(path) if hasattr(p, "key"))
            shape = leaf.shape
            if leafname in ("k", "v") and len(shape) == 3:
                # zamba's per application (B, S, K*hd): the flat axis over
                # the model axis only where that splits whole heads
                B, S, _ = shape
                dp = _shard_if(B, self.fsdp, self.mesh)
                k_axis = self.tp(self.cfg.n_kv_heads)
                s_axis = self.tp(S) if k_axis is None else None
                return P(dp, s_axis, k_axis)
            if leafname in ("k", "v"):
                # the transformer's stacked ring buffer (L, B, S, K, hd)
                stack = len(shape) - 4
                B, S, K, hd = shape[stack:]
                dp = _shard_if(B, self.fsdp, self.mesh)
                k_axis = self.tp(K)
                s_axis = self.tp(S) if k_axis is None else None
                return P(*([None] * stack), dp, s_axis, k_axis, None)
            if leafname == "wkv":  # (L, B, H, P, P)
                _, B, H, _, _ = shape
                dp = _shard_if(B, self.fsdp, self.mesh)
                return P(None, dp, self.tp(H), None, None)
            if leafname == "ssm":  # per layer (B, H, N, P)
                dp = _shard_if(shape[0], self.fsdp, self.mesh)
                return P(dp, self.tp(shape[1]), None, None)
            if leafname == "conv":  # per layer (B, K-1, C)
                dp = _shard_if(shape[0], self.fsdp, self.mesh)
                return P(dp, None, self.tp(shape[2]))
            if leafname in ("shift_t", "shift_c"):  # (L, B, d)
                dp = _shard_if(shape[1], self.fsdp, self.mesh)
                return P(None, dp, None)
            return P(*([None] * leaf.ndim))

        return jax.tree_util.tree_map_with_path(spec, cache_shapes)


def named(mesh: Mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)
