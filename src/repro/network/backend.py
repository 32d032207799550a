"""Compiled (``jax.jit``) backends for the hot network engines.

Every analysis layer of :mod:`repro.network` runs on exact NumPy — the
default and the oracle.  This module ports the four hot inner passes to
XLA behind a ``KernelType``-style dispatch (mirroring the kernel layers'
reference/compiled idiom), so consumers can score thousands of
(geometry, mapping, traffic) candidates per compiled call instead of one
per Python-loop iteration:

=============  =============================================================
``numpy``      The existing exact engines (default).  Always available.
``xla``        ``jax.jit`` ports: the DOR difference-array link-load tensor
               (:func:`xla_route_loads`), max-min progressive filling as a
               fixed-shape masked ``lax.while_loop`` (:func:`prepare_drain`
               / :func:`drain`), the integer contention cross-correlation
               (:func:`xla_contention_field`), the closed-form cut scoring
               (:func:`xla_cut_scores`), and the ``vmap``-batched candidate
               scorer (:func:`score_candidates`).
``pallas``     Reserved slot for a Pallas port of the bincount/segment-sum
               inner loop of progressive filling; raises
               ``NotImplementedError`` until it lands.
=============  =============================================================

Selection: every threaded entry point takes ``backend=None``, resolved by
:func:`resolve_backend` — an explicit argument wins, else the
``REPRO_NETWORK_BACKEND`` environment variable, else ``numpy``.

Exactness contract.  Every xla program is traced, fed and run under
``jax.enable_x64`` (:func:`_x64`), scoped to the backend's own calls so
model code in the same process keeps 32-bit defaults.  Parity is
bit-meaningful: link loads are sums of integer (or tie-halved dyadic)
volumes, so the ``numpy`` and ``xla`` load tensors are **equal exactly**,
not merely close, and the contention field is an exact integer
correlation on both sides.  Max-min rates and makespans agree to <= 1e-9
relative (XLA's multiply-add fusion reorders a handful of float ops); the
property suite in ``tests/test_backend.py`` pins both, and
``benchmarks/bench_backend.py`` gates the >= 10x throughput claims.

What stays NumPy and why: host-side path building and ELL compaction
(irregular ``np.unique``/argsort prep), greedy refinement and first-fit
(small irregular calls where dispatch overhead dominates), and every
result-packaging step.  See DESIGN.md "Compiled backends".

>>> resolve_backend(None) if "REPRO_NETWORK_BACKEND" not in __import__("os").environ else "numpy"
'numpy'
>>> resolve_backend("numpy")
'numpy'
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import numpy as np

from ..obs.metrics import REGISTRY as _METRICS
from ..obs.trace import TRACER as _TRACER
from .geometry import volume
from ..utils.env import have_jax

__all__ = [
    "BACKENDS",
    "HAVE_JAX",
    "DrainPlan",
    "drain",
    "drain_batch",
    "prepare_drain",
    "resolve_backend",
    "score_candidates",
    "xla_contention_field",
    "xla_cut_scores",
    "xla_route_loads",
]

#: Recognised backend names, in preference order.
BACKENDS = ("numpy", "xla", "pallas")

#: Whether jax is importable (spec lookup only; importing this module never
#: imports jax).
HAVE_JAX = have_jax()

_EPS = 1e-12

_JAX: Optional[tuple] = None


def _jax():
    """Import jax lazily (importing this module never imports jax)."""
    global _JAX
    if _JAX is None:
        import jax
        import jax.numpy as jnp

        _JAX = (jax, jnp)
    return _JAX


def _x64():
    """The context every compiled-backend program is traced, fed and run
    in: 64-bit types for the exactness contract, scoped to the call."""
    return _jax()[0].enable_x64(True)


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve a backend name: explicit argument, else the
    ``REPRO_NETWORK_BACKEND`` environment variable, else ``"numpy"``.

    Raises ``ValueError`` for unknown names, ``NotImplementedError`` for
    the reserved ``"pallas"`` slot, and ``RuntimeError`` for ``"xla"``
    when jax is not installed — so a mis-set environment variable fails
    loudly at the first dispatch, not with silent numpy fallback.

    >>> resolve_backend("numpy")
    'numpy'
    """
    if backend is None:
        backend = os.environ.get("REPRO_NETWORK_BACKEND") or "numpy"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "pallas":
        raise NotImplementedError(
            "the pallas backend is a reserved slot for the progressive-filling "
            "inner loop; use 'numpy' or 'xla'"
        )
    if backend == "xla" and not HAVE_JAX:
        raise RuntimeError(
            "backend 'xla' requires jax; install jax[cpu] or use backend='numpy'"
        )
    return backend


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# First-touch jit signatures, for the compile-vs-execute telemetry split:
# a dispatch whose (function, static args, padded shapes) signature is new
# triggers an XLA compile, so its span is annotated phase="compile" and
# the ``backend.jit_compiles`` counter increments; repeat signatures are
# phase="execute".  (lru_cache eviction can re-compile a signature seen
# long ago — the counter tracks first touches, the steady-state measure.)
_JIT_SIGNATURES: set = set()


def _to_host(out):
    """A dispatch's device array (or tuple of them) as NumPy: the blocking
    device-to-host fetch."""
    if isinstance(out, tuple):
        return tuple(np.asarray(o) for o in out)
    return np.asarray(out)


def _dispatch(name: str, sig: tuple, call, **annotations):
    """Run one compiled-backend dispatch and fetch its result to the host,
    with telemetry: jit-compile / dispatch counters in
    :data:`repro.obs.REGISTRY` (always on — one dict update per coarse
    call) and, when tracing is enabled, a ``backend.<name>`` span with the
    compile-vs-execute phase holding two children: ``xla.call`` (entering
    x64 and the jitted call returning device arrays) and ``xla.fetch``
    (the blocking conversion to NumPy)."""
    compiling = sig not in _JIT_SIGNATURES
    if compiling:
        _JIT_SIGNATURES.add(sig)
        _METRICS.counter("backend.jit_compiles", fn=name).incr()
    _METRICS.counter("backend.dispatches", fn=name).incr()
    if not _TRACER.enabled:
        with _x64():
            out = call()
        return _to_host(out)
    with _TRACER.span(
        f"backend.{name}",
        phase="compile" if compiling else "execute",
        **annotations,
    ):
        with _TRACER.span("xla.call"), _x64():
            out = call()
        with _TRACER.span("xla.fetch"):
            return _to_host(out)


# ---------------------------------------------------------------------------
# (1) DOR link loads — the difference-array/bincount tensor, jitted.
# ---------------------------------------------------------------------------
@lru_cache(maxsize=128)
def _route_loads_fn(dims: Tuple[int, ...], split_ties: bool):
    """Jitted mirror of :func:`repro.network.routing.route_dor` for one
    (dims, split_ties) signature; recompiles per padded message count."""
    jax, jnp = _jax()
    D = len(dims)

    def fn(src, dst, vol):
        per_dim = []
        for k, a in enumerate(dims):
            if a == 1:
                per_dim.append(jnp.zeros((2,) + dims, dtype=jnp.float64))
                continue
            other_dims = dims[:k] + dims[k + 1:]
            n_lines = volume(other_dims) if other_dims else 1
            strides = []
            acc = 1
            for w in reversed(other_dims):
                strides.append(acc)
                acc *= w
            strides = list(reversed(strides))
            line = jnp.zeros(src.shape[0], dtype=jnp.int32)
            pos_i = 0
            for j in range(D):
                if j == k:
                    continue
                cj = dst[:, j] if j < k else src[:, j]
                line = line + cj * strides[pos_i]
                pos_i += 1

            s = src[:, k]
            delta = (dst[:, k] - s) % a
            rev = a - delta
            hops = jnp.minimum(delta, rev)
            tie = delta * 2 == a
            fwd = delta <= rev
            v1 = jnp.where(tie, vol * (0.5 if split_ties else 1.0), vol)
            v1 = jnp.where(hops == 0, 0.0, v1)
            bstart = (s - hops + 1) % a
            start_p = jnp.where(fwd, s, bstart)
            base_p = line * a + jnp.where(fwd, 0, n_lines * a)
            segments = [(start_p, v1, base_p)]
            if split_ties:
                # Secondary segment: the backward half of each split tie
                # (zero-weight for every non-tie message — shapes stay
                # static, the loads do not change).
                v2 = jnp.where(tie, vol * 0.5, 0.0)
                segments.append((bstart, v2, n_lines * a + line * a))
            idx_parts, w_parts = [], []
            for start, v, base in segments:
                end = start + hops
                em = jnp.where(end >= a, end - a, end)
                wrapped = end > a
                idx_parts += [base + start, base + em, base]
                w_parts += [v, jnp.where(em == 0, 0.0, -v), jnp.where(wrapped, v, 0.0)]
            idx = jnp.concatenate(idx_parts)
            w = jnp.concatenate(w_parts)
            diff = jnp.zeros(2 * n_lines * a, dtype=jnp.float64).at[idx].add(w)
            ring = jnp.cumsum(diff.reshape(2, n_lines, a), axis=-1)
            ring = jnp.maximum(ring, 0.0)
            full = ring.reshape((2,) + other_dims + (a,))
            per_dim.append(jnp.moveaxis(full, -1, 1 + k))
        return jnp.stack(per_dim, axis=0)

    return jax.jit(fn)


def xla_route_loads(
    dims: Sequence[int],
    src: np.ndarray,
    dst: np.ndarray,
    vol,
    split_ties: bool = True,
) -> np.ndarray:
    """XLA port of :func:`repro.network.routing.route_dor`: the
    ``(D, 2, *dims)`` per-directed-link load tensor of a message batch.

    Message counts are padded to the next power of two with zero-volume
    messages (which route nowhere), so the number of distinct compilations
    is bounded by ``dims x log2(M)`` rather than one per batch size.  For
    integer (or tie-halved dyadic) volumes the result equals the NumPy
    engine's tensor **exactly**; arbitrary float volumes agree to float64
    summation order.
    """
    dims = tuple(int(a) for a in dims)
    D = len(dims)
    src = np.atleast_2d(np.asarray(src, dtype=np.int64))
    dst = np.atleast_2d(np.asarray(dst, dtype=np.int64))
    if src.shape != dst.shape or src.shape[1] != D:
        raise ValueError(
            f"src/dst must have shape (M, {D}); got {src.shape}/{dst.shape}"
        )
    M = src.shape[0]
    vol = np.broadcast_to(np.asarray(vol, dtype=np.float64), (M,))
    if M == 0:
        return np.zeros((D, 2) + dims, dtype=np.float64)
    Mp = _next_pow2(M)
    if Mp != M:
        pad = Mp - M
        src = np.concatenate([src, np.zeros((pad, D), dtype=np.int64)])
        dst = np.concatenate([dst, np.zeros((pad, D), dtype=np.int64)])
        vol = np.concatenate([vol, np.zeros(pad)])
    fn = _route_loads_fn(dims, bool(split_ties))
    _METRICS.counter("backend.padding_bucket", bucket=Mp).incr()
    return _dispatch(
        "route_loads",
        ("route_loads", dims, bool(split_ties), Mp),
        lambda: fn(src.astype(np.int32), dst.astype(np.int32), vol),
        messages=M,
        bucket=Mp,
    )


# ---------------------------------------------------------------------------
# (2) Max-min progressive filling — fixed-shape ELL drain, jitted.
# ---------------------------------------------------------------------------
@dataclass
class DrainPlan:
    """Compiled-drain form of one routed scenario: the link x flow
    incidence compacted to ELL (fixed-width padded index lists) so the
    progressive-filling loop has static shapes.

    ``lf[l]`` lists the flows crossing used link ``l`` (padded with the
    dummy flow ``n_flows``); ``fl[f]`` the used links of flow ``f``
    (padded with the dummy link ``n_links_used``).  ``vol`` is the
    original scenario's subflow volumes — :func:`drain` accepts per-lane
    overrides, so one plan serves every translate of a
    translation-invariant scenario family (same incidence structure,
    different volumes).
    """

    dims: Tuple[int, ...]
    n_flows: int
    n_links_used: int
    lf: object  # (Lu, d) int32 device array
    fl: object  # (F, h) int32 device array
    cap: object  # (Lu,) float64 device array
    has_links: np.ndarray  # (F,) bool
    vol: np.ndarray  # (F,) float64 — the plan's own scenario volumes
    max_iters: int


def prepare_drain(paths, link_bw: float = 1.0, double_link_on_2: bool = True) -> DrainPlan:
    """Compact a :class:`repro.network.netsim.FlowPaths` into a
    :class:`DrainPlan` (host-side ``np.unique``/argsort work — the
    irregular prep that stays NumPy by design)."""
    from .netsim import link_capacities

    if link_bw <= 0.0:
        raise ValueError("link_bw must be positive")
    _, jnp = _jax()
    if getattr(paths, "capacities", None) is not None:
        # Explicit-capacity fabrics (HyperX) carry their own dense slot
        # capacities in units of link_bw; the torus double-link rule does
        # not apply to them.
        capfull = np.asarray(paths.capacities, dtype=np.float64) * link_bw
    else:
        capfull = link_capacities(paths.dims, link_bw, double_link_on_2).ravel()
    F = paths.n_flows
    link = paths.link_ids
    flow = paths.flow_ids
    uniq, inv = np.unique(link, return_inverse=True)
    Lu = int(uniq.shape[0])
    cap = capfull[uniq]
    order = np.argsort(inv, kind="stable")
    li = inv[order]
    fi = flow[order]
    starts = np.searchsorted(li, np.arange(Lu))
    pos = np.arange(li.shape[0]) - starts[li]
    d = int(pos.max()) + 1 if li.shape[0] else 0
    lf = np.full((Lu, max(d, 1)), F, dtype=np.int32)
    if li.shape[0]:
        lf[li, pos] = fi
    order2 = np.argsort(flow, kind="stable")
    fi2 = flow[order2]
    li2 = inv[order2]
    s2 = np.searchsorted(fi2, np.arange(F))
    pos2 = np.arange(fi2.shape[0]) - s2[fi2]
    h = int(pos2.max()) + 1 if fi2.shape[0] else 0
    fl = np.full((F, max(h, 1)), Lu, dtype=np.int32)
    if fi2.shape[0]:
        fl[fi2, pos2] = li2
    has_links = np.zeros(F, dtype=bool)
    has_links[flow] = True
    with _x64():
        lf, fl, cap = jnp.asarray(lf), jnp.asarray(fl), jnp.asarray(cap)
    return DrainPlan(
        dims=paths.dims,
        n_flows=F,
        n_links_used=Lu,
        lf=lf,
        fl=fl,
        cap=cap,
        has_links=has_links,
        vol=np.asarray(paths.vol, dtype=np.float64),
        max_iters=Lu + 1,
    )


_DRAIN = None


def _drain_fn():
    """The jitted single-scenario drain (built once; specialises per
    (F, Lu, d, h, max_iters, max_steps) signature)."""
    global _DRAIN
    if _DRAIN is not None:
        return _DRAIN
    jax, jnp = _jax()

    def _drain_one(lf, fl, cap, vol, active0, max_iters, max_steps):
        F = vol.shape[0]
        tolv = jnp.maximum(vol, 1.0) * _EPS

        def rates_of(growing0):
            # Progressive filling with masked convergence: every unfrozen
            # flow grows at the common increment, bottleneck links saturate
            # and freeze their flows; `done` masks out iterations after
            # convergence so the fixed loop bound compiles cleanly.
            def cond(s):
                return (s[0] < max_iters) & (~s[4])

            def body(s):
                it, growing, cap_rem, rate, done = s
                gpad = jnp.concatenate([growing, jnp.zeros(1, bool)])
                cnt = gpad[lf].sum(axis=1).astype(jnp.float64)
                open_ = cnt > 0
                openany = open_.any()
                share = jnp.where(open_, cap_rem / jnp.where(open_, cnt, 1.0), jnp.inf)
                inc = share.min()
                rate2 = jnp.where(growing, rate + inc, rate)
                cap2 = jnp.where(open_, cap_rem - inc * cnt, cap_rem)
                sat = open_ & (share <= inc * (1.0 + 1e-9))
                spad = jnp.concatenate([sat, jnp.zeros(1, bool)])
                growing2 = growing & ~spad[fl].any(axis=1)
                done2 = (~openany) | (~growing2.any())
                return (
                    it + 1,
                    jnp.where(openany, growing2, growing),
                    jnp.where(openany, cap2, cap_rem),
                    jnp.where(openany, rate2, rate),
                    done2,
                )

            s0 = (0, growing0, cap, jnp.zeros(F), ~growing0.any())
            return jax.lax.while_loop(cond, body, s0)[3]

        def cond(s):
            return s[3].any() & (s[4] < max_steps)

        def body(s):
            t, remaining, fc, active, steps = s
            rates = rates_of(active)
            ratio = jnp.where(active, remaining / jnp.where(active, rates, 1.0), jnp.inf)
            amin = jnp.argmin(ratio)
            dt = ratio[amin]
            t2 = t + dt
            rem2 = jnp.where(active, remaining - rates * dt, remaining).at[amin].set(0.0)
            finished = active & (rem2 <= tolv)
            return (t2, rem2, jnp.where(finished, t2, fc), active & ~finished, steps + 1)

        s0 = (0.0, vol + 0.0, jnp.zeros(F), active0, 0)
        _, _, fc, active, steps = jax.lax.while_loop(cond, body, s0)
        return fc, steps, active.any()

    _DRAIN = jax.jit(_drain_one, static_argnames=("max_iters", "max_steps"))
    return _DRAIN


def drain(
    plan: DrainPlan,
    vol: Optional[np.ndarray] = None,
    max_steps: int = 100_000,
) -> Tuple[np.ndarray, int]:
    """Drain one scenario through the compiled max-min simulator.

    Returns ``(flow_completion, steps)`` matching
    :func:`repro.network.netsim.simulate_flows` (makespans agree to
    <= 1e-9 relative; the outer loop's completion order is identical).
    ``vol`` overrides the plan's subflow volumes (same flow ordering) —
    the batched-scenario idiom.  Raises ``RuntimeError`` past
    ``max_steps``, mirroring the NumPy engine.
    """
    v = plan.vol if vol is None else np.asarray(vol, dtype=np.float64)
    if v.shape != (plan.n_flows,):
        raise ValueError(f"vol must have shape ({plan.n_flows},); got {v.shape}")
    active0 = plan.has_links & (v > _EPS)
    if plan.n_flows == 0 or plan.n_links_used == 0 or not active0.any():
        return np.zeros(plan.n_flows), 0
    fn = _drain_fn()
    fc, steps, unfinished = _dispatch(
        "drain",
        (
            "drain",
            plan.n_flows,
            plan.n_links_used,
            tuple(int(s) for s in plan.lf.shape),
            tuple(int(s) for s in plan.fl.shape),
            plan.max_iters,
            int(max_steps),
        ),
        lambda: fn(
            plan.lf, plan.fl, plan.cap, v, active0,
            max_iters=plan.max_iters, max_steps=int(max_steps),
        ),
        flows=plan.n_flows,
        links=plan.n_links_used,
    )
    if bool(unfinished):
        raise RuntimeError(f"flow simulation exceeded {max_steps} steps")
    return fc, int(steps)


def drain_batch(
    plan: DrainPlan,
    vols: np.ndarray,
    max_steps: int = 100_000,
) -> Tuple[np.ndarray, np.ndarray]:
    """Drain a batch of volume lanes through one plan: ``vols`` is
    ``(B, F)``, one scenario per row, all sharing the plan's incidence
    structure (e.g. translates of one job geometry).

    Lanes run through the jitted single-scenario drain in a host loop —
    measured faster than any ``vmap``/batched layout on CPU, because the
    per-scenario working set stays cache-resident and batched lanes all
    pay the slowest lane's iteration count.  Returns
    ``(flow_completion (B, F), steps (B,))``.
    """
    vols = np.asarray(vols, dtype=np.float64)
    if vols.ndim != 2 or vols.shape[1] != plan.n_flows:
        raise ValueError(f"vols must have shape (B, {plan.n_flows}); got {vols.shape}")
    B = vols.shape[0]
    fc = np.zeros((B, plan.n_flows))
    steps = np.zeros(B, dtype=np.int64)
    for i in range(B):
        fc[i], steps[i] = drain(plan, vols[i], max_steps=max_steps)
    return fc, steps


# ---------------------------------------------------------------------------
# (vmap entry point) batched candidate scoring.
# ---------------------------------------------------------------------------
@lru_cache(maxsize=128)
def _score_fn(dims: Tuple[int, ...], split_ties: bool, double_link_on_2: bool):
    """Jitted, vmapped (congestion, dilation) scorer for one machine
    signature; specialises per (B, n_ranks, M) shape."""
    jax, jnp = _jax()
    D = len(dims)

    def one(c, rsrc, rdst, vol):
        src = c[rsrc]
        dst = c[rdst]
        cong = jnp.zeros(())
        dil = jnp.zeros(())
        for k, a in enumerate(dims):
            s = src[:, k]
            delta = (dst[:, k] - s) % a
            hops = jnp.minimum(delta, a - delta)
            dil = dil + (vol * hops).sum()
            if a == 1:
                continue
            other_dims = dims[:k] + dims[k + 1:]
            n_lines = volume(other_dims) if other_dims else 1
            strides = []
            acc = 1
            for w in reversed(other_dims):
                strides.append(acc)
                acc *= w
            strides = list(reversed(strides))
            line = jnp.zeros(rsrc.shape[0], dtype=jnp.int32)
            pos_i = 0
            for j in range(D):
                if j == k:
                    continue
                cj = dst[:, j] if j < k else src[:, j]
                line = line + cj * strides[pos_i]
                pos_i += 1
            tie = delta * 2 == a
            fwd = delta <= a - delta
            v1 = jnp.where(tie, vol * (0.5 if split_ties else 1.0), vol)
            v1 = jnp.where(hops == 0, 0.0, v1)
            bstart = (s - hops + 1) % a
            wp = jnp.where(fwd, v1, 0.0)
            wm = jnp.where(~fwd, v1, 0.0)
            if split_ties:
                wm = wm + jnp.where(tie, vol * 0.5, 0.0)
            pos = jnp.arange(a, dtype=jnp.int32)
            covp = ((pos[None, :] - s[:, None]) % a) < hops[:, None]
            covm = ((pos[None, :] - bstart[:, None]) % a) < hops[:, None]
            onehot = (line[:, None] == jnp.arange(n_lines, dtype=jnp.int32)[None, :]).astype(
                jnp.float64
            )
            pp = onehot.T @ (wp[:, None] * covp)
            pm = onehot.T @ (wm[:, None] * covm)
            scale = 0.5 if (a == 2 and double_link_on_2) else 1.0
            cong = jnp.maximum(cong, scale * jnp.maximum(pp.max(), pm.max()))
        return cong, dil

    return jax.jit(jax.vmap(one, in_axes=(0, None, None, None)))


def score_candidates(
    dims: Sequence[int],
    coords: np.ndarray,
    traffic,
    split_ties: bool = True,
    double_link_on_2: bool = True,
    backend: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Score a batch of candidate rank mappings in one compiled call.

    ``coords`` is ``(B, n_ranks, D)`` — B candidate rank->cell embeddings
    on the ``dims`` machine — and ``traffic`` the shared rank-space
    ``(src_rank, dst_rank, vol)``.  Returns ``(congestion, dilation)``
    arrays of shape ``(B,)``, row-identical to calling
    :func:`repro.network.mapping.score_mapping` per candidate (exactly —
    the property suite pins it).  The ``xla`` backend evaluates all B
    candidates under one ``jax.vmap``-of-``jit``; ``numpy`` runs the
    sequential oracle loop.  Memory for the xla path is
    O(B * M * n_lines) per dimension — sized for advisor-scale jobs
    (hundreds of ranks), not full-machine permutations.
    """
    backend = resolve_backend(backend)
    dims = tuple(int(a) for a in dims)
    coords = np.asarray(coords, dtype=np.int64)
    if coords.ndim == 2:
        coords = coords[None]
    if coords.ndim != 3 or coords.shape[2] != len(dims):
        raise ValueError(
            f"coords must have shape (B, n_ranks, {len(dims)}); got {coords.shape}"
        )
    B = coords.shape[0]
    rsrc, rdst, vol = traffic
    rsrc = np.asarray(rsrc, dtype=np.int64)
    rdst = np.asarray(rdst, dtype=np.int64)
    if B == 0 or rsrc.shape[0] == 0:
        return np.zeros(B), np.zeros(B)
    vol = np.broadcast_to(np.asarray(vol, dtype=np.float64), rsrc.shape)
    if backend == "numpy":
        from .mapping import score_mapping

        cong = np.zeros(B)
        dil = np.zeros(B)
        for i in range(B):
            s = score_mapping(
                dims, coords[i], (rsrc, rdst, vol), split_ties, double_link_on_2
            )
            cong[i] = s.congestion
            dil[i] = s.dilation
        return cong, dil
    fn = _score_fn(dims, bool(split_ties), bool(double_link_on_2))
    cong, dil = _dispatch(
        "score_candidates",
        (
            "score_candidates",
            dims,
            bool(split_ties),
            bool(double_link_on_2),
            B,
            coords.shape[1],
            int(rsrc.shape[0]),
        ),
        lambda: fn(
            coords.astype(np.int32), rsrc.astype(np.int32), rdst.astype(np.int32), vol
        ),
        candidates=B,
    )
    return cong, dil


# ---------------------------------------------------------------------------
# (3) Contention cross-correlation — exact, on the integer matmul path.
# ---------------------------------------------------------------------------
@lru_cache(maxsize=64)
def _contention_fn(dims: Tuple[int, ...], n_chunks: int):
    """Jitted direct circular cross-correlation for one machine (longest
    axis first) and one chunk count.  Inputs are int8: the 0/1 mask planes
    ``(P, a, n_rest)`` and the 7-bit chunks of the integer load planes
    ``(P, n_chunks * a, n_rest)``; the output is the exact int64 field
    ``(a, n_rest)``."""
    jax, jnp = _jax()
    a, rest = dims[0], dims[1:]
    n_rest = volume(rest) if rest else 1

    def fn(mask, chunks):
        # idx[o', u'] = flat index of (u' + o') mod rest, the torus shift
        # of the remaining axes.
        o = jnp.arange(n_rest, dtype=jnp.int32)[:, None]
        u = jnp.arange(n_rest, dtype=jnp.int32)[None, :]
        idx = jnp.zeros((n_rest, n_rest), jnp.int32)
        stride = 1
        for r in reversed(rest):
            idx = idx + (((o // stride) % r + (u // stride) % r) % r) * stride
            stride *= r
        shifted = mask[:, :, idx]  # (P, a, o', u'): mask[p, w, u' + o']
        # q[p, o', w, (c, u1)] = sum_u' mask[p, w, u' + o'] * chunk_c[p, u1, u']
        # — int8 x int8 products summed in int32 (<= 127 * n_rest terms).
        q = jnp.einsum(
            "pwxu,pvu->pxwv", shifted, chunks, preferred_element_type=jnp.int32
        ).reshape(mask.shape[0], n_rest, a, n_chunks, a)
        o1 = jnp.arange(a, dtype=jnp.int32)[:, None]
        u1 = jnp.arange(a, dtype=jnp.int32)[None, :]
        g = q[:, :, (o1 + u1) % a, :, jnp.broadcast_to(u1, (a, a))]  # (o1, u1, p, o', c)
        weights = jnp.asarray([128**c for c in range(n_chunks)], jnp.int64)
        return (g.astype(jnp.int64) * weights).sum(axis=(1, 2, 4))

    return jax.jit(fn)


def xla_contention_field(
    dims: Sequence[int], oriented: Sequence[int], mask: np.ndarray
) -> np.ndarray:
    """XLA port of :func:`repro.network.placement.contention_field`: the
    predicted interference of one orientation at every torus offset.

    The job's integer load field (:func:`~repro.network.placement.int_base_loads`)
    is split into 7-bit chunks and correlated with the 0/1 mask planes as
    one batched int8 matmul with int32 accumulation, so the field is an
    exact integer — no FFT, no float64, nothing the chip's compiler
    refuses — divided by ``2n`` on the host.  Equal to the NumPy engine's
    field bit for bit, so placement choices are identical.  Work is
    ``N**2 / dims[k]`` multiply-adds per plane (``k`` the longest axis).
    """
    from .placement import int_base_loads

    dims = tuple(int(a) for a in dims)
    oriented = tuple(int(w) for w in oriented)
    D = len(dims)
    J = int_base_loads(dims, oriented)
    k = int(np.argmax(dims))
    order = (k,) + tuple(j for j in range(D) if j != k)
    lead = (2 * D, dims[k], -1)
    loads = np.moveaxis(J, 2 + k, 2).reshape(lead)
    planes = np.moveaxis(np.asarray(mask, dtype=bool), 2 + k, 2).reshape(lead)
    n_chunks = -(-max(int(J.max()).bit_length(), 1) // 7)
    chunks = np.concatenate(
        [(loads >> (7 * c)) & 127 for c in range(n_chunks)], axis=1
    ).astype(np.int8)
    moved = tuple(dims[j] for j in order)
    fn = _contention_fn(moved, n_chunks)
    field = _dispatch(
        "contention_field",
        ("contention_field", moved, n_chunks),
        lambda: fn(planes.astype(np.int8), chunks),
    )
    field = np.moveaxis(field.reshape(moved), 0, k)
    return field / (2 * volume(oriented))


# ---------------------------------------------------------------------------
# (4) Closed-form cut scoring.
# ---------------------------------------------------------------------------
_CUT = None


def _cut_fn():
    global _CUT
    if _CUT is None:
        jax, jnp = _jax()

        def fn(S, L):
            # t / S_k is the product of the other sides: no integer
            # division, which 64-bit TPU arithmetic can only emulate
            D = S.shape[1]
            cut = jnp.zeros(S.shape[0], S.dtype)
            for k in range(D):
                others = jnp.prod(S[:, [j for j in range(D) if j != k]], axis=1)
                cut = cut + others * L[:, k]
            return cut

        _CUT = jax.jit(fn)
    return _CUT


def xla_cut_scores(assignments: np.ndarray, line_cuts: np.ndarray, t: int) -> np.ndarray:
    """XLA port of the isoperimetry engine's closed-form cut evaluation:
    for each aligned side assignment ``S`` of a volume-``t`` cuboid, the
    exact cut ``sum_k (t / S_k) * line_cuts_k`` given the per-side line
    cuts of the fabric (:meth:`repro.network.fabric.Fabric.line_cuts`) —
    int64 arithmetic under x64, so the scores equal the NumPy engine's
    **exactly**.  Every row's sides must multiply to ``t``."""
    S = np.asarray(assignments, dtype=np.int64)
    L = np.asarray(line_cuts, dtype=np.int64)
    if S.shape[0] == 0:
        return np.zeros(0, dtype=np.int64)
    if not (S.prod(axis=1) == int(t)).all():
        raise ValueError(f"every assignment's sides must multiply to t={t}")
    with _x64():
        return np.asarray(_cut_fn()(S, L), dtype=np.int64)
