"""Chat traffic: closed batches of short requests through ``repro.launch.serve``.

A unit is one ``serve()`` call on the program's normal path, with the
configuration's ``serve_args`` (for ``zamba2-7b``: ``--arch zamba2-7b
--full --layers 24``): ``requests`` prompts of ``prompt_len`` ids drawn
uniformly from the vocabulary, fed through the cache one decode step
each, then ``gen_len`` tokens decoded greedily each, on a cache of
``prompt_len + gen_len`` positions.  The unit's seed, drawn from
``--seed`` and the unit's index, draws the prompts and the
``checked_requests`` requests whose logits the unit keeps on the host at
every decode step (besides every request's logits at the last prompt
position and the last step); nothing of the program's cache stays on the
device past its end.  The unit takes the time on its own clock as each
token of the batch reaches the host (``serve``'s ``on_token``, the
stream a server sends its clients): ``decision_p95_ms`` is the 95th
percentile, over every pair of successive tokens of the window, of the
time between them, one decode step for all requests (time per output
token).

The weights are drawn once per run, in set-up, from ``--seed``: by the
reference's ``draw_weights``, in the published checkpoint's layout, and
loaded into the program by its own loader (``zamba.from_published``), as
a replica loads a checkpoint; the program's init is not used.  Set-up
then serves one short call (``warm_prompt_len`` + ``warm_gen_len`` steps)
at the same batch and cache length, which compiles the decode step.

The check draws one unit of the window from the seed, draws the same
weights again, and runs the plain float32 reference
(``reference/zamba2.py``) over each kept request's prompt and generated
tokens.  Compared: the relative L2 error, over the vocabulary, of the
program's logits against the reference's at the last prompt position
(prefill through the cache) and at every decode step, the largest over the
kept requests and the steps.  The control loads the same weights rounded
through float8_e4m3fn into the program and replays the unit's tokens.
"""

from __future__ import annotations

import time

import numpy as np

WARM_INDEX = 1 << 30  # unit index of the set-up call, apart from the window's
WEIGHTS_INDEX = WARM_INDEX + 1  # index of the run's weights' seed


def unit_seed(seed: int, index: int) -> int:
    """A 31-bit seed for one unit, from the run's seed and the unit's index."""
    return int(np.random.SeedSequence([seed, index]).generate_state(1)[0] >> 1)


def _args(cfg: dict, mix: dict, prompt_len: int, gen_len: int, seed: int):
    from repro.launch import serve

    return serve.parse_args([
        *cfg["serve_args"], "--requests", str(mix["requests"]),
        "--prompt-len", str(prompt_len), "--gen-len", str(gen_len),
        "--cache-len", str(mix["prompt_len"] + mix["gen_len"]), "--seed", str(seed),
    ])


def served_arch(cfg: dict, args):
    """The program's config for ``args``; exits unless its widths, depth and
    pattern are the configuration's."""
    from repro.launch import serve
    from repro.models import zamba

    arch = serve.resolve_arch(args)
    got = zamba.published_config(arch)
    diff = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
    if diff:
        raise SystemExit(f"the program serves other shapes than the configuration: {diff}")
    return arch


def load(cfg: dict, arch, weights: dict, leaf=None):
    """The program's parameters for the run's weights (``weights``: their
    seed and dtype), drawn in the published layout and loaded by the
    program's loader; ``leaf`` is applied to each drawn weight."""
    import jax
    from reference.zamba2 import draw_weights
    from repro.models import zamba

    return jax.block_until_ready(zamba.from_published(
        draw_weights(cfg, weights["seed"], weights["dtype"], leaf), arch))


def setup(cfg: dict, mix: dict, seed: int) -> dict:
    from repro.launch import serve

    warm_seed = unit_seed(seed, WARM_INDEX)
    args = _args(cfg, mix, mix["warm_prompt_len"], mix["warm_gen_len"], warm_seed)
    arch = served_arch(cfg, args)
    weights = {"seed": unit_seed(seed, WEIGHTS_INDEX), "dtype": arch.param_dtype}
    params = load(cfg, arch, weights)
    # kept rows as a unit keeps them, so their gather compiles here
    serve.serve(args, params, keep_rows=checked_rows(mix, warm_seed))
    return {"cfg": cfg, "mix": mix, "seed": seed, "params": params, "weights": weights}


def checked_rows(mix: dict, seed: int) -> np.ndarray:
    """The requests of a unit whose every step is checked, from its seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    n = min(mix["checked_requests"], mix["requests"])
    return np.sort(rng.choice(mix["requests"], size=n, replace=False))


def unit(state: dict, index: int) -> dict:
    import jax
    from repro.launch import serve

    cfg, mix = state["cfg"], state["mix"]
    s = unit_seed(state["seed"], index)
    rows = checked_rows(mix, s)
    V = cfg["vocab_size"]
    arrivals = []  # when each token of the batch reached the host

    def on_token(_i, _tokens):
        arrivals.append(time.perf_counter())

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("serve:unit"):
        out = serve.serve(_args(cfg, mix, mix["prompt_len"], mix["gen_len"], s), state["params"],
                          keep_rows=rows, on_token=on_token)
        # sliced on the host: no device program of the window's own shapes
        prefill = np.asarray(out.prefill_logits, np.float32)[:, :V]
        last = np.asarray(out.last_logits, np.float32)[:, :V]
        kept = np.asarray(out.kept_logits, np.float32)[..., :V]
    wall = time.perf_counter() - t0
    return {"index": index, "seed": s, "wall_s": wall, "prompts": out.prompts,
            "tokens": out.tokens, "prefill_logits": prefill, "last_logits": last,
            "rows": rows, "kept_logits": kept, "weights": state["weights"],
            "prefill_s": out.prefill_s, "decode_s": out.decode_s,
            "decisions": np.diff(arrivals)}


def end_to_end(units: list) -> dict:
    """``decision_p95_ms`` over the time between successive tokens; a
    request fails when its logits are not finite."""
    failed = sum(int((~np.isfinite(u["prefill_logits"]).all(1)
                      | ~np.isfinite(u["last_logits"]).all(1)).sum()) for u in units)
    decisions = np.concatenate([u["decisions"] for u in units])
    return {"metrics": {"decision_p95_ms": 1e3 * float(np.quantile(decisions, 0.95))},
            "attempted": sum(len(u["prompts"]) for u in units), "failed": failed}


def replay(model, params, seq: np.ndarray, positions) -> np.ndarray:
    """The program's logits (B, len(positions), padded vocab) at
    ``positions`` for ``seq`` (B, T) fed through the cache one step at a
    time, as ``serve`` feeds it."""
    import jax
    import jax.numpy as jnp

    cache = model.init_cache(seq.shape[0], seq.shape[1])
    decode = jax.jit(model.decode_step, donate_argnums=1)
    out = {}
    for t in range(seq.shape[1]):
        tokens = {"tokens": jnp.asarray(seq[:, t:t + 1])}
        logits, cache = decode(params, cache, tokens, jnp.array(t))
        if t in positions:
            out[t] = np.asarray(logits[:, -1], np.float32)
    return np.stack([out[t] for t in positions], axis=1)


def sample(units: list, seed: int) -> dict:
    """One unit of the window, drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    return units[int(rng.integers(len(units)))]


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    """The largest over rows (every axis but the last) of ||a - b|| / ||b||."""
    return float(np.max(np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)))


def _compare(cfg, mix, checks, units, seed, produced) -> dict:
    """``produced(u, seq)`` gives the logits compared, over the vocabulary:
    at the last prompt position (n, V) and at every decode step (n, gen, V)
    of the kept requests' sequences ``seq`` (n, prompt + gen)."""
    from reference.zamba2 import draw_weights, forward

    u = sample(units, seed)
    P, G, V = mix["prompt_len"], mix["gen_len"], cfg["vocab_size"]
    seq = np.concatenate([u["prompts"], u["tokens"]], axis=1)[u["rows"]]
    prefill, steps = produced(u, seq)
    w = u["weights"]
    ref = np.asarray(forward(draw_weights(cfg, w["seed"], w["dtype"]), cfg, seq)[..., :V])
    lim = checks["limits"]
    return {
        "prefill_logits_rel_l2": {"value": rel_l2(prefill, ref[:, P - 1]),
                                  "limit": lim["prefill_logits_rel_l2"]},
        "decode_logits_rel_l2": {"value": rel_l2(steps, ref[:, P:P + G]),
                                 "limit": lim["decode_logits_rel_l2"]},
    }


def check(cfg: dict, mix: dict, checks: dict, units: list, seed: int) -> dict:
    return _compare(cfg, mix, checks, units, seed,
                    lambda u, _seq: (u["prefill_logits"][u["rows"]], u["kept_logits"]))


def control(cfg: dict, mix: dict, checks: dict, units: list, seed: int) -> dict:
    """The program loaded with the run's weights rounded through
    float8_e4m3fn, replaying the kept requests' prompts and generated
    tokens, in the program's place."""
    import jax.numpy as jnp
    from repro.models import build_model

    def fp8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    def produced(u, seq):
        P, V = mix["prompt_len"], cfg["vocab_size"]
        arch = served_arch(cfg, _args(cfg, mix, P, mix["gen_len"], u["seed"]))
        params = load(cfg, arch, u["weights"], leaf=fp8)
        got = replay(build_model(arch), params, seq, list(range(P - 1, seq.shape[1])))[..., :V]
        return got[:, 0], got[:, 1:]

    return _compare(cfg, mix, checks, units, seed, produced)
