import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first backend initialisation.  512 host devices back the
# production meshes (16x16 single-pod, 2x16x16 multi-pod) for compile-only
# dry-runs; nothing is ever allocated (ShapeDtypeStruct inputs only).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the model and the step function (train_step / forward / decode),
  2. jits it with the ShardingRules in/out shardings on the production mesh,
  3. ``.lower(**ShapeDtypeStruct inputs).compile()`` — success proves the
     distribution config is coherent (sharding divisibility, collective
     legality, memory layout); ``memory_analysis()`` proves it fits,
  4. derives roofline terms:
       - compute/memory: exact analytic model (analysis/analytic.py) —
         XLA's cost_analysis counts while-loop bodies once, so scanned
         programs are undercounted; the analytic model is validated against
         cost_analysis on unrolled configs (tests/test_roofline.py),
       - collectives: parsed from *calibration* compiles at two unrolled
         depths (L0, L1) and extrapolated linearly in depth — exact for
         homogeneous stacks, and collective-free inner scans make the
         unrolled counts exact.
  5. writes a JSON record consumed by benchmarks + EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import analytic, roofline
from repro.analysis.axis_attribution import per_axis_collectives
from repro.configs import SHAPES, all_archs, cells, get_arch
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.optim import AdamWConfig, adamw
from repro.train import make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# Activation-memory knob per arch for train_4k (microbatch count).
MICROBATCHES = {
    "nemotron-4-340b": 8,
    "qwen1.5-110b": 4,
    "command-r-35b": 4,
    "mixtral-8x7b": 4,
    "phi3.5-moe-42b-a6.6b": 4,
    "granite-3-8b": 2,
    "musicgen-large": 2,
    "zamba2-2.7b": 2,
    "rwkv6-3b": 2,
    "internvl2-1b": 1,
}


def batch_specs_struct(arch, shape):
    """ShapeDtypeStructs for the cell's inputs."""
    B, S = shape.global_batch, shape.seq_len
    f32 = jnp.float32
    i32 = jnp.int32
    if shape.is_decode:
        if arch.frontend == "audio":
            return {"frame_embeds": jax.ShapeDtypeStruct((B, 1, arch.d_model), f32)}
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    if arch.frontend == "audio":
        return {
            "frame_embeds": jax.ShapeDtypeStruct((B, S, arch.d_model), f32),
            "targets": jax.ShapeDtypeStruct((B, S, arch.n_codebooks), i32),
        }
    out = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if arch.frontend == "vlm":
        out["patch_embeds"] = jax.ShapeDtypeStruct((B, arch.num_patches, arch.d_model), f32)
    return out


def shard_bytes(shardings, shapes) -> float:
    """Exact per-device bytes for a pytree of NamedShardings + structs."""
    total = 0
    for shd, struct in zip(jax.tree.leaves(shardings), jax.tree.leaves(shapes)):
        shard_shape = shd.shard_shape(struct.shape)
        n = 1
        for d in shard_shape:
            n *= d
        total += n * struct.dtype.itemsize
    return float(total)


def _lower_cell(arch, shape, mesh, *, microbatches, unroll, remat="block",
                loss_chunk=None, zero_stage=3, model_axis="model",
                fsdp_axes=None):
    """Build + lower one cell.  Returns (lowered, per-device state bytes)."""
    rules = ShardingRules(
        arch, mesh, zero_stage=zero_stage, model_axis=model_axis,
        fsdp_axes=tuple(fsdp_axes) if fsdp_axes else None,
    )
    model = build_model(
        arch, attn_impl="xla", remat=remat, unroll=unroll
    )
    model = dataclasses.replace(
        model,
        logits_sharding=lambda ndim: NamedSharding(mesh, rules.logits_spec(ndim)),
        loss_chunk=loss_chunk,
    )
    params_shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    param_specs = rules.params_specs(params_shapes)
    param_shd = jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs)
    batch_struct = batch_specs_struct(arch, shape)
    batch_shd = {
        k: NamedSharding(mesh, s) for k, s in rules.batch_specs(batch_struct).items()
    }

    if shape.kind == "train":
        opt_shapes = jax.eval_shape(adamw.init, params_shapes)
        moment_specs = rules.opt_specs(params_shapes)
        opt_specs = adamw.AdamWState(step=P(), m=moment_specs, v=moment_specs)
        opt_shd = jax.tree.map(lambda s: NamedSharding(mesh, s), opt_specs)
        grad_shd = jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs)
        step_fn = make_train_step(
            model, AdamWConfig(), microbatches=microbatches, grad_shardings=grad_shd,
            unroll_loop=unroll,
        )
        jitted = jax.jit(
            step_fn,
            in_shardings=(param_shd, opt_shd, batch_shd),
            out_shardings=(param_shd, opt_shd, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_shapes, opt_shapes, batch_struct)
        # params + grads (bf16) + m + v (f32)
        state_bytes = 2 * shard_bytes(param_shd, params_shapes) + 2 * shard_bytes(
            jax.tree.map(lambda s: NamedSharding(mesh, s), opt_specs.m), opt_shapes.m
        )
        cache_bytes = 0.0
    elif shape.kind == "prefill":
        def prefill(params, batch):
            logits, _ = model.forward(params, batch)
            return logits[:, -1]

        jitted = jax.jit(prefill, in_shardings=(param_shd, batch_shd))
        lowered = jitted.lower(params_shapes, batch_struct)
        state_bytes = shard_bytes(param_shd, params_shapes)
        cache_bytes = 0.0
    else:  # decode
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len)
        )
        cache_specs = rules.cache_specs(cache_shapes)
        cache_shd = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs)

        def decode(params, cache, batch, position):
            return model.decode_step(params, cache, batch, position)

        pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(
            decode,
            in_shardings=(param_shd, cache_shd, batch_shd, NamedSharding(mesh, P())),
            out_shardings=(None, cache_shd),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_shapes, cache_shapes, batch_struct, pos_struct)
        cache_bytes = shard_bytes(cache_shd, cache_shapes)
        state_bytes = shard_bytes(param_shd, params_shapes) + cache_bytes
    return lowered, state_bytes, cache_bytes, params_shapes


def _calib_depths(arch):
    """(L0, L1): two depths to extrapolate from.  A hybrid's are the depths
    just past its first and its second shared-block application (the gaps
    of the pattern vary, so the extrapolation is approximate there)."""
    apps = arch.shared_applications
    if len(apps) >= 2:
        return apps[0] + 1, apps[1] + 1
    return 2, 4


def run_cell(arch_name: str, shape_name: str, mesh_kind: str, force: bool = False,
             skip_calibration: bool = False, variant: dict = None) -> dict:
    """``variant``: optional perf-iteration overrides
    {tag, microbatches, remat, loss_chunk} — results cached under the tag."""
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    variant = variant or {}
    tag = f"__{variant['tag']}" if variant.get("tag") else ""
    out_path = RESULTS_DIR / f"{arch_name}__{shape_name}__{mesh_kind}{tag}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    chips = mesh.devices.size
    mb = MICROBATCHES.get(arch_name, 1) if shape.kind == "train" else 1
    mb = variant.get("microbatches", mb)
    remat = variant.get("remat", "block")
    loss_chunk = variant.get("loss_chunk")
    zero_stage = variant.get("zero_stage", 3)
    model_axis = variant.get("model_axis", "model")
    fsdp_axes = variant.get("fsdp_axes")

    # -- 1) production compile: the coherence + memory proof ---------------------
    t0 = time.time()
    lowered, state_bytes, cache_bytes_dev, params_shapes = _lower_cell(
        arch, shape, mesh, microbatches=mb, unroll=False, remat=remat,
        loss_chunk=loss_chunk, zero_stage=zero_stage, model_axis=model_axis,
        fsdp_axes=fsdp_axes,
    )
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem_dict = {}
    mem = compiled.memory_analysis()
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        if mem is not None and hasattr(mem, attr):
            try:
                mem_dict[attr] = int(getattr(mem, attr))
            except Exception:
                pass
    raw_cost = roofline.xla_cost_analysis(compiled)
    prod_stats = roofline.collective_stats(compiled.as_text())

    # -- 2) collective calibration: unrolled depths L0 < L1 ----------------------
    if skip_calibration:
        coll_stats = prod_stats
        per_axis = {}
        coll_note = "production-scan counts (loop bodies counted once)"
    else:
        # Exact bilinear calibration: collective bytes/counts are
        # F(L, m) = a + b*L + c*m + d*L*m  (per-layer-per-microbatch weight
        # gathers, per-layer activation reductions, per-microbatch top-level
        # terms, constants).  Four unrolled compiles at (L0,1),(L1,1),(L0,2),
        # (L1,2) determine the coefficients exactly; prefill/decode cells use
        # the depth-only linear model (two compiles).
        L0, L1 = _calib_depths(arch)
        mbs = (1, 2) if (shape.kind == "train" and mb > 1) else (1,)
        meas = {}
        ax_meas = {}
        for m_i in mbs:
            for L in (L0, L1):
                sub = dataclasses.replace(arch, n_layers=L)
                lw, _, _, _ = _lower_cell(
                    sub, shape, mesh, microbatches=m_i, unroll=True, remat=remat,
                    loss_chunk=loss_chunk, zero_stage=zero_stage,
                    model_axis=model_axis, fsdp_axes=fsdp_axes,
                )
                txt = lw.compile().as_text()
                meas[(L, m_i)] = roofline.collective_stats(txt)
                ax_meas[(L, m_i)] = per_axis_collectives(txt, mesh_shape)

        Lf = arch.n_layers

        def bilinear(get) -> float:
            f00 = get(meas[(L0, 1)] if (L0, 1) in meas else {})
            f10 = get(meas[(L1, 1)])
            if len(mbs) == 1:
                slope = (f10 - f00) / (L1 - L0)
                return max(0.0, f00 + slope * (Lf - L0))
            f01 = get(meas[(L0, 2)])
            f11 = get(meas[(L1, 2)])
            d = (f11 - f01 - f10 + f00) / (L1 - L0)
            b = (f10 - f00) / (L1 - L0) - d
            c = f01 - f00 - d * L0
            a = f00 - b * L0 - c - d * L0
            return max(0.0, a + b * Lf + c * mb + d * Lf * mb)

        def bil_ax(table, ax, field) -> float:
            def get(stats):
                return stats.get(ax, {}).get(field, 0.0)

            f00 = get(table[(L0, 1)])
            f10 = get(table[(L1, 1)])
            if len(mbs) == 1:
                slope = (f10 - f00) / (L1 - L0)
                return max(0.0, f00 + slope * (Lf - L0))
            f01 = get(table[(L0, 2)])
            f11 = get(table[(L1, 2)])
            d = (f11 - f01 - f10 + f00) / (L1 - L0)
            b = (f10 - f00) / (L1 - L0) - d
            c = f01 - f00 - d * L0
            a = f00 - b * L0 - c - d * L0
            return max(0.0, a + b * Lf + c * mb + d * Lf * mb)

        coll_stats = {}
        for key in meas[(L0, 1)]:
            coll_stats[key] = {
                "bytes": bilinear(lambda s, k=key: s[k]["bytes"]),
                "count": bilinear(lambda s, k=key: s[k]["count"]),
            }
        axes = set()
        for t in ax_meas.values():
            axes |= set(t)
        per_axis = {
            ax: {
                "bytes": bil_ax(ax_meas, ax, "bytes"),
                "count": bil_ax(ax_meas, ax, "count"),
            }
            for ax in axes
        }
        coll_note = (
            f"bilinear calibration: depths {L0},{L1} x microbatches {list(mbs)}"
        )
    coll_bytes = roofline.total_collective_bytes(coll_stats)

    # -- 3) analytic compute/memory terms ---------------------------------------
    n_matmul = roofline.matmul_param_count(params_shapes)
    cost = analytic.cell_cost(
        arch, shape, n_matmul,
        cache_bytes=cache_bytes_dev * chips,
        microbatches=mb,
    )

    report = roofline.RooflineReport(
        arch=arch_name,
        shape=shape_name,
        mesh=mesh_kind,
        chips=chips,
        hlo_flops=cost.flops_compiled / chips,
        hlo_bytes=cost.bytes_hbm / chips,
        collective_bytes=coll_bytes,
        collectives=coll_stats,
        model_flops=cost.flops_useful,
        bytes_per_device=state_bytes,
        notes=f"microbatches={mb}; collectives: {coll_note}",
    )
    record = report.to_json()
    record.update(
        lower_seconds=round(t_lower, 1),
        compile_seconds=round(t_compile, 1),
        memory_analysis=mem_dict,
        raw_cost_analysis={k: raw_cost[k] for k in ("flops", "bytes accessed") if k in raw_cost},
        production_collectives=prod_stats,
        per_axis_collectives=per_axis,
        flops_breakdown=cost.breakdown,
        variant=variant,
        ok=True,
    )
    out_path.write_text(json.dumps(record, indent=1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-calibration", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    jobs = []
    if args.all:
        for name, arch in sorted(all_archs().items()):
            for shape in cells(arch):
                for m in meshes:
                    jobs.append((name, shape, m))
    else:
        assert args.arch and args.shape
        jobs = [(args.arch, args.shape, m) for m in meshes]

    failures = []
    for arch_name, shape_name, mesh_kind in jobs:
        tag = f"{arch_name} x {shape_name} x {mesh_kind}"
        try:
            rec = run_cell(
                arch_name, shape_name, mesh_kind,
                force=args.force, skip_calibration=args.skip_calibration,
            )
            print(
                f"[OK] {tag}: flops/dev={rec['hlo_flops']:.3e} "
                f"bytes/dev={rec['hlo_bytes']:.3e} coll={rec['collective_bytes']:.3e} "
                f"bottleneck={rec['bottleneck']} "
                f"(compile {rec.get('compile_seconds', 0)}s)",
                flush=True,
            )
        except Exception as e:
            failures.append((tag, repr(e)))
            print(f"[FAIL] {tag}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: {[t for t, _ in failures]}")
    print(f"all {len(jobs)} dry-run cells compiled OK")


if __name__ == "__main__":
    main()
