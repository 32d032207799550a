"""Batched serving driver: greedy generation for a fixed batch of requests.

All requests share one prompt length and one dense cache, and advance
together: the prompt is fed through the cache one token per
``decode_step`` (teacher-forced prefill), then every request decodes
``--gen-len`` tokens greedily.  Runs on the default device; ``--full``
serves the published widths and depth, the default is the reduced
same-family config; ``--layers N`` serves the first N layers of either.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
      --requests 8 --prompt-len 16 --gen-len 24
  PYTHONPATH=src python -m repro.launch.serve --arch zamba2-7b --full --layers 24 \
      --requests 64 --prompt-len 192 --gen-len 64

With tracing on (``repro.obs``) a call records the spans ``serve.init``
(model build, and parameter init unless the caller gives parameters),
``serve.cache`` (cache allocation, with
its bytes by kind), ``serve.prefill`` and ``serve.decode`` (each with its
``steps``); ``serve.cache_bytes{kind=...}`` counts the cache's bytes.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig, get_arch
from repro.obs import REGISTRY
from repro.obs import timer as obs_timer
from repro.models import Model, build_model
from repro.utils.env import enable_compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="serve the published widths and depth")
    ap.add_argument("--layers", type=int, default=None,
                    help="serve only the first N layers of the config's layer pattern")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=24)
    ap.add_argument("--cache-len", type=int, default=None,
                    help="cache positions (default prompt + gen): a longer cache "
                         "keeps the decode step's shapes of longer requests")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--plan-chips", type=int, default=None,
        help="dry-run: print the fleet planner's ranked slice plan for this "
             "arch at the given chip budget, then exit (no model is built)",
    )
    ap.add_argument("--plan-shape", default="decode_32k")
    return ap.parse_args(argv)


def resolve_arch(args: argparse.Namespace) -> ArchConfig:
    """The served config: published, or its reduced variant by default."""
    arch = get_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    if args.layers is not None:
        if not 1 <= args.layers <= arch.n_layers:
            raise SystemExit(f"--layers {args.layers}: {arch.name} has {arch.n_layers} layers")
        arch = dataclasses.replace(arch, n_layers=args.layers)
    if arch.frontend != "none":
        raise SystemExit("serve driver supports token LMs (use token archs)")
    return arch


@dataclass
class Served:
    """What one :func:`serve` call produced."""

    model: Model
    params: Any
    prompts: np.ndarray  # (B, prompt_len) int32
    prefill_logits: jax.Array  # (B, padded vocab): logits at the last prompt position
    last_logits: jax.Array  # (B, padded vocab): logits of the last decode step
    # (len(keep_rows), gen_len, padded vocab), on the host: the kept
    # requests' logits at every decode step (positions prompt_len ..
    # prompt_len + gen_len - 1)
    kept_logits: Optional[np.ndarray]
    tokens: np.ndarray  # (B, gen_len) generated ids, each fed to the next step
    prefill_s: float
    decode_s: float


def cache_bytes(cache: Any) -> Dict[str, int]:
    """Bytes of a cache by kind (its top-level keys: ``k``/``v`` together as
    ``kv``, ``ssm``, ``conv``, ...)."""
    out: Dict[str, int] = {}
    for name, leaves in cache.items():
        kind = "kv" if name in ("k", "v") else name
        out[kind] = out.get(kind, 0) + sum(x.nbytes for x in jax.tree.leaves(leaves))
    return out


def serve(
    args: argparse.Namespace, params: Any = None, keep_rows: Optional[Sequence[int]] = None,
    on_token: Optional[Callable[[int, np.ndarray], None]] = None,
) -> Served:
    """Build the model, prefill the seeded prompts through the cache and
    decode greedily.  ``params`` are the model's parameters (a replica
    loads them once, e.g. by ``zamba.from_published``); without them they
    are initialised from ``--seed`` under ``jit``.  ``keep_rows`` names
    requests whose logits at every decode step are kept (``kept_logits``).
    ``on_token(i, tokens)`` is called with the batch's ``i``-th generated
    ids (B, 1) as soon as they reach the host, as a server streams them."""
    arch = resolve_arch(args)
    with obs_timer("serve.init", arch=arch.name, layers=arch.n_layers):
        model = build_model(arch)
        if params is None:
            params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(args.seed)))
    B = args.requests
    cache_len = args.cache_len or args.prompt_len + args.gen_len
    if cache_len < args.prompt_len + args.gen_len:
        raise SystemExit(f"--cache-len {cache_len} < prompt + gen")
    with obs_timer("serve.cache", requests=B, positions=cache_len) as tm:
        cache = jax.block_until_ready(model.init_cache(B, cache_len))
        sizes = cache_bytes(cache)
        tm.annotate(**{f"{kind}_bytes": n for kind, n in sizes.items()})
    for kind, n in sizes.items():
        REGISTRY.counter("serve.cache_bytes", kind=kind).incr(n)
    # the cache is donated: each step updates it in place instead of
    # keeping a second copy alive
    decode = jax.jit(model.decode_step, donate_argnums=1)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, arch.vocab_size, (B, args.prompt_len), dtype=np.int32)

    # prefill via teacher-forced decode (exact cache population)
    logits = None
    with obs_timer("serve.prefill", requests=B, tokens=args.prompt_len,
                   steps=args.prompt_len) as tm:
        for t in range(args.prompt_len):
            logits, cache = decode(
                params, cache, {"tokens": jnp.asarray(prompts[:, t : t + 1])}, jnp.array(t)
            )
        jax.block_until_ready(logits)
    prefill_s = tm.elapsed
    prefill_logits = logits[:, -1]

    # batched greedy decode
    out_tokens = []
    kept = []
    rows = None if keep_rows is None else jnp.asarray(keep_rows, jnp.int32)
    tok = jnp.argmax(logits[:, -1, : arch.vocab_size], axis=-1)[:, None].astype(jnp.int32)
    with obs_timer("serve.decode", requests=B, tokens=args.gen_len, steps=args.gen_len) as tm:
        for i in range(args.gen_len):
            out_tokens.append(np.asarray(tok))
            if on_token is not None:
                on_token(i, out_tokens[-1])
            logits, cache = decode(
                params, cache, {"tokens": tok}, jnp.array(args.prompt_len + i)
            )
            if rows is not None:
                kept.append(logits[rows, -1])
            tok = jnp.argmax(logits[:, -1, : arch.vocab_size], axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
    decode_s = tm.elapsed

    return Served(
        model=model,
        params=params,
        prompts=prompts,
        prefill_logits=prefill_logits,
        last_logits=logits[:, -1],
        # stacked on the host, so no device program depends on gen_len
        kept_logits=np.stack([np.asarray(k) for k in kept], axis=1) if kept else None,
        tokens=np.concatenate(out_tokens, axis=1),
        prefill_s=prefill_s,
        decode_s=decode_s,
    )


def main(argv=None):
    args = parse_args(argv)

    if args.plan_chips is not None:
        from repro.launch.planner import format_table, plan_model

        plan = plan_model(
            args.arch, args.plan_chips, shape=args.plan_shape, simulate_top_k=1
        )
        print(format_table(plan))
        return plan

    enable_compile_cache()
    out = serve(args)
    arch = out.model.cfg
    B, gen = out.tokens.shape
    tps = B * gen / out.decode_s
    print(f"arch={arch.name} requests={B} prompt={args.prompt_len} gen={gen}")
    print(f"prefill {out.prefill_s*1e3:.1f} ms; decode {out.decode_s*1e3:.1f} ms "
          f"({tps:.1f} tok/s aggregate)")
    print("sample generations (token ids):")
    for b in range(min(B, 3)):
        print(f"  req{b}: {out.tokens[b, :12].tolist()}...")
    assert out.tokens.shape == (B, args.gen_len)
    assert int(out.tokens.max()) < arch.vocab_size
    return tps


if __name__ == "__main__":
    main()
