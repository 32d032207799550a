"""The launch surface: compile-cache placement, the serve driver's options
and split, x64 scoping between the fabric backend and model steps, and
``chip_smoke.py`` refusing to run without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch import serve
from repro.models import build_model
from repro.utils import env

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# Compile cache.
# ---------------------------------------------------------------------------
def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert env.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert env.compile_cache_dir() == str(REPO / ".jax_cache")
    assert env.compile_cache_dir() == env.compile_cache_dir()


def test_enable_compile_cache_sets_only_the_missing_dir(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert env.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None  # jax reads the variable
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert env.enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ---------------------------------------------------------------------------
# Serve driver.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "argv,reduced",
    [([], True), (["--reduced"], True), (["--full"], False)],
    ids=["default", "reduced", "full"],
)
def test_serve_width_flags(argv, reduced):
    arch = serve.resolve_arch(serve.parse_args(["--arch", "zamba2-2.7b", *argv]))
    full = get_arch("zamba2-2.7b")
    assert arch == (full.reduced() if reduced else full)


def test_serve_prefill_logits_match_forward():
    args = serve.parse_args(
        ["--arch", "zamba2-2.7b", "--requests", "2", "--prompt-len", "8", "--gen-len", "3"]
    )
    out = serve.serve(args)
    V = out.model.cfg.vocab_size
    assert out.tokens.shape == (2, 3)
    assert 0 <= int(out.tokens.min()) and int(out.tokens.max()) < V
    full, _ = jax.jit(out.model.forward)(out.params, {"tokens": jnp.asarray(out.prompts)})
    a = np.asarray(out.prefill_logits[:, :V], np.float32)
    b = np.asarray(full[:, -1, :V], np.float32)
    # bf16 weights and activations, 7 reduced layers: rounding noise only
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 0.05


def test_serve_layers_flag_cuts_the_published_pattern():
    arch = serve.resolve_arch(serve.parse_args(["--arch", "zamba2-7b", "--full", "--layers", "24"]))
    assert arch.n_layers == 24 and arch.d_model == 3584
    assert arch.shared_applications == (6, 11, 17, 23)
    with pytest.raises(SystemExit):
        serve.resolve_arch(serve.parse_args(["--arch", "zamba2-7b", "--full", "--layers", "82"]))


def test_serve_traces_the_launcher_and_returns_last_logits():
    """``serve.init`` / ``serve.cache`` spans (the cache's bytes by kind, also
    as counters), ``steps`` on the loop's spans, and the last decode step's
    logits equal to ``model.forward`` over the prompt and generated tokens."""
    from repro import obs

    obs.clear_telemetry()
    obs.enable_tracing(clear=True)
    try:
        out = serve.serve(serve.parse_args(
            ["--arch", "zamba2-7b", "--requests", "2", "--prompt-len", "5", "--gen-len", "3"]))
    finally:
        obs.disable_tracing()
    spans = {e["name"]: e.get("args", {}) for e in obs.export_chrome_trace()["traceEvents"]}
    assert set(spans) >= {"serve.init", "serve.cache", "serve.prefill", "serve.decode"}
    assert spans["serve.prefill"]["steps"] == 5 and spans["serve.decode"]["steps"] == 3
    cfg = out.model.cfg
    sizes = {k: spans["serve.cache"][f"{k}_bytes"] for k in ("ssm", "conv", "kv")}
    assert sizes["ssm"] == cfg.n_layers * 2 * 4 * cfg.ssm.expand * cfg.d_model * cfg.ssm.state_dim
    assert sizes["kv"] == 3 * 2 * 2 * 8 * cfg.n_kv_heads * cfg.resolved_head_dim * 2  # bf16
    counters = obs.metrics_snapshot()["counters"]
    assert all(counters[f"serve.cache_bytes{{kind={k}}}"] == n for k, n in sizes.items())
    obs.clear_telemetry()

    seq = np.concatenate([out.prompts, out.tokens], axis=1)
    full, _ = jax.jit(out.model.forward)(out.params, {"tokens": jnp.asarray(seq)})
    V = cfg.vocab_size
    a = np.asarray(out.last_logits[:, :V], np.float32)
    b = np.asarray(full[:, -1, :V], np.float32)
    assert np.linalg.norm(a - b) / np.linalg.norm(b) < 0.05  # bf16 rounding, 7 layers


def test_serve_takes_params_and_keeps_rows_logits():
    """Given parameters are served as they are (the same greedy tokens as
    the call that drew them), and ``keep_rows`` keeps those requests'
    logits at every decode step, the last equal to ``last_logits``."""
    args = serve.parse_args(
        ["--arch", "zamba2-7b", "--requests", "3", "--prompt-len", "4", "--gen-len", "3"])
    first = serve.serve(args)
    out = serve.serve(args, first.params, keep_rows=[0, 2])
    np.testing.assert_array_equal(out.tokens, first.tokens)
    assert out.params is first.params and first.kept_logits is None
    assert out.kept_logits.shape == (2, 3, out.model.cfg.padded_vocab_size)
    np.testing.assert_array_equal(np.asarray(out.kept_logits[:, -1]),
                                  np.asarray(out.last_logits[jnp.array([0, 2])]))


def test_serve_streams_each_token_as_it_reaches_the_host():
    """``on_token`` sees every generated column of the batch once, in order,
    equal to the returned tokens."""
    seen = []
    out = serve.serve(serve.parse_args(
        ["--arch", "zamba2-7b", "--requests", "2", "--prompt-len", "3", "--gen-len", "4"]),
        on_token=lambda i, tokens: seen.append((i, tokens.copy())))
    assert [i for i, _ in seen] == [0, 1, 2, 3]
    np.testing.assert_array_equal(np.concatenate([t for _, t in seen], axis=1), out.tokens)


def test_decode_step_carries_the_hybrid_scopes():
    """The profiler's names for the hybrid's parts (``jax.named_scope``)."""
    model = build_model(get_arch("zamba2-7b").reduced())
    params = model.init_shapes()
    cache = jax.eval_shape(lambda: model.init_cache(2, 4))
    text = jax.jit(model.decode_step).lower(
        params, cache, {"tokens": jnp.zeros((2, 1), jnp.int32)}, jnp.array(0)
    ).as_text(debug_info=True)
    for scope in ("zamba2.mamba", "zamba2.shared_block", "zamba2.head"):
        assert scope in text


# ---------------------------------------------------------------------------
# x64 stays inside the fabric backend's calls.
# ---------------------------------------------------------------------------
def test_xla_backend_call_leaves_model_dtypes_alone():
    from repro.network import bisection_pairing, route_dor

    model = build_model(get_arch("zamba2-2.7b").reduced())
    params = model.init(jax.random.key(0))
    batch = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    step = lambda c: model.decode_step(params, c, batch, jnp.array(0))
    before = jax.eval_shape(step, model.init_cache(2, 4))

    src, dst, vol = bisection_pairing((4, 4))
    loads = route_dor((4, 4), src, dst, vol, backend="xla")
    assert loads.dtype == np.float64  # the backend itself ran under x64
    assert not jax.config.jax_enable_x64

    after = jax.jit(step)(model.init_cache(2, 4))
    assert jax.tree.map(lambda x: x.dtype, after) == jax.tree.map(lambda x: x.dtype, before)
    assert jnp.zeros(1).dtype == jnp.float32 and jnp.arange(2).dtype == jnp.int32


# ---------------------------------------------------------------------------
# chip_smoke.py refuses to run off the chip.
# ---------------------------------------------------------------------------
def test_chip_smoke_refuses_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "platform=cpu" in proc.stdout
