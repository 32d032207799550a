"""Run both main paths of this repository once, on one TPU chip, in one process.

1. Device check: print what JAX found; exit non-zero unless it is a TPU.
2. Fabric engines: the ``xla`` network backend, called explicitly, with
   every result compared with the NumPy oracle in the same process to the
   contract ``repro.network.backend`` states — golden Mira/JUQUEEN parity,
   a batched drain on a 32^3 torus, 4096-candidate mapping scoring, cut
   scores, and a 16^3 scheduler replay that places jobs through the
   contention field.
3. Serving: ``zamba2-2.7b`` at its published widths and depth answers 8
   seeded requests (prompt 128, 32 generated tokens) through
   ``repro.launch.serve``.  The logits that prefill through the cache gives
   at the last prompt position are compared with ``model.forward`` on the
   same prompts, once with XLA attention and once with the Pallas flash
   kernel compiled for the chip.

    python chip_smoke.py

Wall times are printed for orientation only; they are not metrics.  The
last line of standard output is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every check passed.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARGS = [
    "--arch", "zamba2-2.7b", "--full",
    "--requests", "8", "--prompt-len", "128", "--gen-len", "32", "--seed", "0",
]

# Logits at the last prompt position, compared as ||a - b|| / ||b|| over the
# whole (requests, vocab) block.  Weights and activations are bfloat16, and
# the two paths round at different points of each of the 63 sublayers (54
# Mamba2 layers, 9 applications of two shared blocks): the recurrent decode
# against the chunked scan, cache attention against full attention.  At
# this depth a copy of the config 256 wide drifts 0.028 (decode against
# forward, 16 prompt tokens) and 0.026 (XLA against flash attention) on the
# CPU; a wrong cache position, rope offset or state carry moves the logits
# by O(1).
DECODE_VS_FORWARD_RTOL = 0.15
XLA_VS_FLASH_RTOL = 0.15

# Scheduler replay: jobs of a bursty, heavy-tailed stream with cell failures.
REPLAY_MACHINE = (16, 16, 16)
REPLAY_JOBS = 80
DRAIN_LANES = 4  # volume lanes per job geometry in the 32^3 batched drain


def check_device():
    import jax

    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"device: platform={info['platform']} kind={info['kind']} count={info['count']}",
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform!r}")
    return info


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'PASS' if ok else 'FAIL'} {name}{': ' + detail if detail else ''}", flush=True)
    if not ok:
        raise SystemExit(f"check failed: {name}")


def fabric_phase() -> None:
    from benchmarks.bench_backend import (
        GOLDEN_PAIRS, JOB_GEOMETRIES, MACHINE, SCORER_BATCH, SCORER_DIMS,
        SCORER_LOGICAL, SCORER_RANKS,
    )
    from repro.network import (
        bisection_pairing, cut_table, dor_paths, drain_batch, prepare_drain,
        route_dor, score_candidates, simulate_flows,
    )
    from repro.network.allocation import ContentionScoredPolicy
    from repro.network.mapping import pattern_traffic
    from repro.network.scheduler import generate_scenario, run_scenario
    from repro.obs.metrics import REGISTRY

    rng = np.random.default_rng(0)

    for name, dims in GOLDEN_PAIRS:
        src, dst, vol = bisection_pairing(dims)
        exact = np.array_equal(
            route_dor(dims, src, dst, vol), route_dor(dims, src, dst, vol, backend="xla")
        )
        check(f"golden {name} route loads", exact, "bit-exact")
        paths = dor_paths(dims, src, dst, vol)
        m_np = simulate_flows(paths).makespan
        m_x = simulate_flows(paths, backend="xla").makespan
        rel = abs(m_np - m_x) / m_np
        check(f"golden {name} makespan", rel <= 1e-9, f"rel diff {rel:.3e} <= 1e-9")

    worst = 0.0
    for geom in JOB_GEOMETRIES:
        paths = dor_paths(MACHINE, *bisection_pairing(geom))
        vols = rng.integers(1, 3, size=(DRAIN_LANES, paths.n_flows)).astype(np.float64)
        fc, _ = drain_batch(prepare_drain(paths), vols)
        for i in range(DRAIN_LANES):
            ref = simulate_flows(dataclasses.replace(paths, vol=vols[i])).makespan
            worst = max(worst, abs(ref - float(fc[i].max())) / ref)
    check(f"32^3 batched drain ({len(JOB_GEOMETRIES) * DRAIN_LANES} lanes)", worst <= 1e-9,
          f"max rel makespan diff {worst:.3e} <= 1e-9")

    n_cells = int(np.prod(SCORER_DIMS))
    cells = np.stack(
        [rng.choice(n_cells, SCORER_RANKS, replace=False) for _ in range(SCORER_BATCH)]
    )
    coords = np.stack(np.unravel_index(cells, SCORER_DIMS), axis=-1).astype(np.int64)
    traffic = pattern_traffic(SCORER_LOGICAL, "pairing")
    cong_x, dil_x = score_candidates(SCORER_DIMS, coords, traffic, backend="xla")
    cong_np, dil_np = score_candidates(SCORER_DIMS, coords, traffic, backend="numpy")
    check(f"score_candidates ({SCORER_BATCH} rows)",
          np.array_equal(cong_x, cong_np) and np.array_equal(dil_x, dil_np), "rows exact")

    for dims in ((16, 16, 16), (32, 32, 32), (16, 4, 4, 4, 2)):
        for t in (8, 64, 512, 4096):
            c_np, c_x = cut_table(dims, t), cut_table(dims, t, backend="xla")
            exact = c_x.cuts.dtype == np.int64 and np.array_equal(c_np.cuts, c_x.cuts)
            check(f"cut scores {dims} t={t}", exact, "int64 exact")

    scenario = generate_scenario(
        REPLAY_MACHINE, REPLAY_JOBS, seed=3, burst_gap=30.0, mean_duration=80.0,
        failure_rate=0.002, repair_delay=150.0,
    )
    calls = REGISTRY.counter("backend.dispatches", fn="contention_field")
    before = calls.value
    log_x = run_scenario(scenario, ContentionScoredPolicy(), backfill=True, backend="xla").log
    n_fields = calls.value - before
    log_np = run_scenario(scenario, ContentionScoredPolicy(), backfill=True, backend="numpy").log
    check(f"16^3 scheduler replay ({REPLAY_JOBS} jobs)", log_x == log_np and n_fields > 0,
          f"{len(log_x)} events identical, {n_fields} contention fields on the chip")


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def serving_phase(kind: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch import serve

    args = serve.parse_args(SERVE_ARGS)
    out = serve.serve(args)
    cfg = out.model.cfg
    V = cfg.vocab_size
    B, P = out.prompts.shape
    print(f"serve {cfg.name} on {kind}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"requests={B} prompt={P} generated={out.tokens.shape[1]}", flush=True)
    print(f"prefill wall time on {kind}: {out.prefill_s:.3f} s ({P} cached decode steps, "
          f"first-call compile included)", flush=True)
    print(f"decode wall time on {kind}: {out.decode_s:.3f} s ({out.tokens.shape[1]} steps)",
          flush=True)
    check("generated ids", out.tokens.shape == (B, args.gen_len)
          and int(out.tokens.min()) >= 0 and int(out.tokens.max()) < V,
          f"shape {out.tokens.shape}, all in [0, {V})")

    check("model phase runs without x64", not jax.config.jax_enable_x64
          and out.prefill_logits.dtype == jnp.bfloat16,
          f"logits {out.prefill_logits.dtype}")
    cached = np.asarray(out.prefill_logits[:, :V], np.float32)
    check("prefill logits finite", bool(np.isfinite(cached).all()))
    batch = {"tokens": jnp.asarray(out.prompts)}
    full = {}
    for impl in ("xla", "pallas"):
        model = dataclasses.replace(out.model, attn_impl=impl)
        forward = jax.jit(model.forward).lower(out.params, batch).compile()
        if impl == "pallas":
            check("flash kernel compiled into forward[pallas]",
                  "tpu_custom_call" in forward.as_text())
        logits, _ = forward(out.params, batch)
        full[impl] = np.asarray(logits[:, -1, :V], np.float32)
        err = rel_err(cached, full[impl])
        check(f"decode-through-cache vs forward[{impl}]", err <= DECODE_VS_FORWARD_RTOL,
              f"rel diff {err:.4f} <= {DECODE_VS_FORWARD_RTOL}")
    err = rel_err(full["pallas"], full["xla"])
    check("forward[pallas] vs forward[xla]", err <= XLA_VS_FLASH_RTOL,
          f"rel diff {err:.4f} <= {XLA_VS_FLASH_RTOL}")


def main() -> None:
    device = check_device()
    from repro.utils.env import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    fabric_phase()
    serving_phase(device["kind"])
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
