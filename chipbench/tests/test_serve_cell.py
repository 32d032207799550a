"""The ``zamba2-7b.chat`` cell on the CPU at the reduced same-family size,
past the look for a chip: a whole run through the program's normal path
(``serve`` with the configuration's ``serve_args``) reads ``correct``
against the plain reference, its per-layer readers read what the CPU has,
and faults in the timed path make ``correct`` false.  Then the readers on a
hand-made span list and trace, and the FLOP and byte functions against
hand arithmetic at the published widths.

    python -m pytest -q chipbench/tests
"""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import serve_cost  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 4321  # beyond 32 signed bits, as a run's --seed may be
CONFIG = json.loads((BENCH / "configs" / "zamba2-7b.json").read_text())


def reduced_config():
    """The configuration's keys at the program's reduced widths
    (``get_arch("zamba2-7b").reduced()``: 7 layers, hybrids at 1, 4, 6)."""
    from repro.configs import get_arch
    from repro.models import zamba

    a = get_arch("zamba2-7b").reduced()
    return {**CONFIG, **zamba.published_config(a), "intermediate_size": a.d_ff,
            "serve_args": ["--arch", "zamba2-7b", "--reduced"]}


# bf16 at width 64 against the float32 reference reads 0.026-0.063 on four
# seeds (and width 3584 on the chip reads less: PERF.md); the faults below
# read 1.18 and more.  The chip's limits are set from the chip's readings.
CPU_CHECKS = {"limits": {"prefill_logits_rel_l2": 0.2, "decode_logits_rel_l2": 0.2}}


def chat_cell(trace=False):
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(requests=3, prompt_len=6, gen_len=3)
    return run.run_cell("zamba2-7b.chat", SEED, 0.5, trace, require_tpu=False, cache=False,
                        overrides={"config": reduced_config(), "mix": mix, "peaks": PEAKS,
                                   "checks": CPU_CHECKS})


def test_chat_run_is_correct():
    out = chat_cell(trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 3 and out["failed"] == 0
    assert set(out["checks"]) == {"prefill_logits_rel_l2", "decode_logits_rel_l2"}
    # the CPU's trace has no device plane: the device's readers find nothing
    assert set(out["metrics"]) >= {"serve_step_ms", "launcher_ms_per_call", "mfu.serve",
                                   "device_idle.serve"}
    assert "decode_step_device_ms" not in out["metrics"]


def test_chat_untraced_run_reports_token_times():
    """Untraced, the cell reports its end-to-end metrics: ``setup_s`` and
    ``decision_p95_ms``, the time between two tokens of the batch."""
    out = chat_cell(trace=False)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "decision_p95_ms"}
    assert out["metrics"]["decision_p95_ms"]["unit"] == "ms"
    assert 0 < out["metrics"]["decision_p95_ms"]["value"] < 1e3 * out["metrics"]["setup_s"]["value"]


def test_decision_p95_is_over_every_token_gap_of_the_window():
    """The 95th percentile pools the gaps of every unit: one slow gap in
    forty moves it less than the slowest."""
    import numpy as np

    driver = run.resolve("zamba2-7b.chat")["driver"]
    finite = np.zeros((2, 4))
    units = [{"decisions": np.full(20, 0.030), "prompts": np.zeros((2, 1)),
              "prefill_logits": finite, "last_logits": finite} for _ in range(2)]
    units[1]["decisions"] = np.r_[np.full(19, 0.040), 1.0]
    work = driver.end_to_end(units)
    assert work["metrics"]["decision_p95_ms"] == pytest.approx(40.0)
    assert work["attempted"] == 4 and work["failed"] == 0


def test_chat_window_compiles_nothing(tmp_path):
    """With the compile cache on, as a run on the chip has it, set-up leaves
    nothing to compile inside the window: the kept rows' gather is warmed
    and nothing on the device takes the window's own lengths.  In a child
    process, so that the cache's settings stay out of this one."""
    script = f"""
import json, sys
sys.path[:0] = [{str(BENCH)!r}, {str(Path(__file__).parent)!r}]
import test_serve_cell as t
mix = json.loads((t.BENCH / "traffic" / "chat.json").read_text())
mix.update(requests=3, prompt_len=6, gen_len=3)
t.run.run_cell("zamba2-7b.chat", t.SEED, 0.5, False, require_tpu=False, cache=True,
               overrides={{"config": t.reduced_config(), "mix": mix, "peaks": t.PEAKS,
                           "checks": t.CPU_CHECKS}})
"""
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path), "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "compiles in window: 0 (XLA)" in out.stdout, out.stdout[-2000:]


def inject(monkeypatch, fault):
    """A fault of the program: the two shared blocks swapped, Mamba2's gated
    norm taken over the whole width instead of within each group, or every
    RMSNorm's scale ignored.  (The embedding left out of the blocks' input
    moves the logits too little at width 64 to clear bf16's noise here; the
    float32 tests in ``tests/test_zamba2.py`` catch it.)"""
    import jax.numpy as jnp
    from repro.models import mamba2, zamba

    if fault == "blocks_swapped":
        monkeypatch.setattr(zamba, "block_of", lambda cfg, j: (j + 1) % cfg.n_shared_blocks)
    elif fault == "norm_whole_width":
        orig = mamba2.gated_rms_norm
        monkeypatch.setattr(mamba2, "gated_rms_norm",
                            lambda y, z, w, groups, eps: orig(y, z, w, 1, eps))
    else:
        orig = zamba.apply_norm
        monkeypatch.setattr(zamba, "apply_norm", lambda p, x, cfg: orig(
            {**p, "scale": jnp.ones_like(p["scale"])}, x, cfg))


@pytest.mark.parametrize("fault", ["blocks_swapped", "norm_whole_width", "norm_scale_ignored"])
def test_chat_fault_is_caught(monkeypatch, fault):
    inject(monkeypatch, fault)
    out = chat_cell()
    assert not out["correct"], out["checks"]


def test_chat_control_is_not_correct():
    """The program loaded with the weights rounded through float8_e4m3fn
    (0.50-0.85 on three seeds) fails the limits that the program meets."""
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(requests=3, prompt_len=6, gen_len=3)
    driver = run.resolve("zamba2-7b.chat")["driver"]
    cfg = reduced_config()
    state = driver.setup(cfg, mix, SEED)
    units, _ = run.run_window(driver, state, 0.5, False)
    assert run.judge(driver.check(cfg, mix, CPU_CHECKS, units, SEED))
    assert not run.judge(driver.control(cfg, mix, CPU_CHECKS, units, SEED))


def test_parent_program_fails_in_setup(monkeypatch):
    """A program without the configuration (the parent of the change that
    added it) exits in set-up, before any window."""
    from repro.configs import base, get_arch

    get_arch("zamba2-7b")  # the registry fills on first use; then take the config out
    monkeypatch.delitem(base._REGISTRY, "zamba2-7b")
    with pytest.raises((SystemExit, KeyError)):
        chat_cell()


def reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


SPANS = [  # one serve() call: init, cache, 6 prefill and 3 decode steps
    {"name": "serve.init", "ts": 0, "dur": 2000, "tid": 1, "args": {"layers": 7}},
    {"name": "serve.cache", "ts": 2000, "dur": 500, "tid": 1, "args": {"kv_bytes": 10}},
    {"name": "serve.prefill", "ts": 2500, "dur": 600, "tid": 1, "args": {"steps": 6}},
    {"name": "serve.decode", "ts": 3100, "dur": 900, "tid": 1, "args": {"steps": 3}},
]
TRACE = {"window_s": 2.0, "idle_share": 0.25,
         "modules": {"jit_decode_step(7)": {"count": 9, "seconds": 0.045},
                     "jit_init": {"count": 1, "seconds": 0.5}}}


def ctx(spans=SPANS, trace=TRACE):
    mix = {"requests": 64, "prompt_len": 192, "gen_len": 64}
    return {"config": CONFIG, "mix": mix, "units": [{"prompts": None}], "spans": spans,
            "trace": trace, "peaks": PEAKS}


@pytest.mark.parametrize("name,value", [
    ("serve_step_ms", 1.5 / 9),
    ("launcher_ms_per_call", 2.5),
    ("decode_step_device_ms", 5.0),
    ("device_idle.serve", 25.0),
    ("decode_step_roofline", 100 * serve_cost.mean_step_bytes(
        CONFIG, {"requests": 64, "prompt_len": 192, "gen_len": 64}) / 819e9 / 5e-3),
    ("mfu.serve", 100 * serve_cost.unit_flops(
        CONFIG, {"requests": 64, "prompt_len": 192, "gen_len": 64}) / (2.0 * 197e12)),
])
def test_serve_reader_value(name, value):
    assert reader(name)(ctx()) == pytest.approx(value)


@pytest.mark.parametrize("name", ["serve_step_ms", "launcher_ms_per_call", "decode_step_device_ms",
                                  "decode_step_roofline"])
def test_serve_reader_finds_nothing(name):
    """A program without the spans' ``steps`` and ``serve.init`` (the
    parent's), or a trace without the decode step, gives no reading."""
    old = [dict(s, args={}) for s in SPANS if s["name"] in ("serve.prefill", "serve.decode")]
    assert reader(name)(ctx(spans=old, trace={**TRACE, "modules": {}})) is None


def test_costs_by_hand_at_published_widths():
    """One layer of each kind at the published widths, by hand."""
    d, d_in, H, GN, ff, r = 3584, 7168, 112, 2 * 2 * 64, 14336, 128
    mamba = d * (2 * d_in + GN + H) + 5 * (d_in + GN) + 3 * H + d_in + d_in * d + d
    assert mamba == serve_cost.mamba_layer_params(CONFIG) == 78_437_456
    block = 3 * 7168 * 7168 + 7168 * d + 3 * d * ff + 7168 + d
    assert block == serve_cost.block_params(CONFIG) == 333_982_208
    assert serve_cost.adapter_params(CONFIG) == d * r + r * 2 * ff + d * d == 16_973_824
    assert serve_cost.params(CONFIG) == (24 * mamba + 2 * block + 4 * 16_973_824
                                         + 32000 * d + d) == 2_733_050_240
    # state of one request: 24 layers x (SSM 112 x 64 x 64 + conv 3 x 7424) x 4 B
    assert serve_cost.state_bytes(CONFIG) == 24 * 4 * (H * 64 * 64 + 3 * (d_in + GN))
    assert serve_cost.kv_bytes_per_position(CONFIG) == 4 * 2 * 32 * 224 * 2 == 114_688
    # a step at 64 requests and 128.5 positions: weights once per use
    weights = 24 * (d * (2 * d_in + GN + H) + d_in * d) + 4 * (
        3 * 7168 * 7168 + 7168 * d + 3 * d * ff + 16_973_824) + 32000 * d
    mix = {"requests": 64, "prompt_len": 192, "gen_len": 64}
    assert serve_cost.mean_step_bytes(CONFIG, mix) == pytest.approx(
        2 * weights + 2 * 64 * serve_cost.state_bytes(CONFIG) + 64 * 128.5 * 114_688)
    flops = 64 * (2 * weights + 4 * 4 * 128.5 * 32 * 224
                  + 24 * (5 * H * 64 * 64 + 2 * 4 * (d_in + GN)))
    assert serve_cost.decode_step_flops(CONFIG, 64, 128.5) == pytest.approx(flops)
    assert serve_cost.unit_flops(CONFIG, mix) == pytest.approx(256 * flops)
