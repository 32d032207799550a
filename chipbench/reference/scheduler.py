"""Plain reference of the scheduler deployment's semantics, for the fabric cells' check.

The same operations on the same job stream give the same event log.  It
imports nothing of the program and is written plainly, with one table of
placements per box arrangement so that a whole stream replays in seconds:

* The machine is a torus of allocation units (Mira: 4x4x3x2 midplanes);
  links are directed, one per cell, dimension and direction, and a ring of
  length 2 joins its two cells by two links (Blue Gene/Q).
* A job of ``n`` units asks for a box.  Boxes of that volume that fit are
  tried best internal bisection first (the fewest links cut when the box
  is halved by a sub-box of ``n // 2`` cells, the least over such
  sub-boxes), lexicographically smallest on ties.  The first box with a free
  placement is taken.
* Among the free placements of that box (every axis arrangement that fits,
  at every offset, wrapping round the torus) the one whose traffic
  interferes least wins: the box's all-to-all traffic (volume 1/n for each
  ordered pair of its cells), routed dimension by dimension along the
  shorter way round each ring with an exactly antipodal message split half
  each way, summed over the links that leave an occupied cell or already
  carry a running job's traffic; rounded to 9 decimals.  Ties go to the
  placement touching most occupied cells in the one-cell shell around it
  (only where no ring is 6 or longer), then to the arrangement and the
  offset that come first.
* The event loop: events closer than 64 machine epsilons of their
  magnitude are one instant, applied completions first, then failures,
  preemptions, repairs and arrivals, each in the order they were handed in.
  Then the queue is served first come first served.  A head that does not
  fit gets a reservation: the earliest pending completion or repair after
  which some box of its size fits.  With backfill a later job may start
  only if it ends by that reservation.  The reservation is kept until
  cells are freed.  A failure evacuates the jobs on the failed cells, which
  requeue with the time they had left; the cells stay out until repaired.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EPS_REL = 64.0 * float(np.finfo(np.float64).eps)
RANK = {"complete": 0, "fail": 1, "preempt": 2, "reclaim": 3, "arrival": 4}


def same_instant(a: float, b: float) -> bool:
    return abs(a - b) <= EPS_REL * max(1.0, abs(a), abs(b))


def at_or_before(a: float, b: float) -> bool:
    return a <= b or same_instant(a, b)


def strictly_before(a: float, b: float) -> bool:
    return a < b and not same_instant(a, b)


# ---------------------------------------------------------------------------
# Geometry.
# ---------------------------------------------------------------------------
def boxes_of(dims: Sequence[int], n: int) -> List[Tuple[int, ...]]:
    """Every box of ``n`` cells that fits the torus, sides sorted descending."""
    D = len(dims)
    top = sorted(dims, reverse=True)
    out = set()

    def grow(prefix, rest):
        if len(prefix) == D:
            if rest == 1:
                out.add(tuple(sorted(prefix, reverse=True)))
            return
        for s in range(1, rest + 1):
            if rest % s == 0:
                grow(prefix + [s], rest // s)

    grow([], n)
    return [b for b in out if all(s <= a for s, a in zip(b, top))]


def box_cut(torus: Sequence[int], box: Sequence[int]) -> int:
    """Links between a box (aligned with the torus's axes) and the rest."""
    cells = int(np.prod(box))
    return sum(2 * cells // b for b, a in zip(box, torus) if b < a)


def bisection(box: Sequence[int]) -> int:
    """Fewest links cut by halving the box (as a torus of its own) with a sub-box."""
    n = int(np.prod(box))
    if n == 1:
        return 0
    cuts = [box_cut(box, p) for p in itertools.product(*(range(1, b + 1) for b in box))
            if int(np.prod(p)) == n // 2]
    if not cuts:
        raise ValueError(f"no sub-box halves {tuple(box)}")
    return min(cuts)


def preferences(dims: Sequence[int], n: int) -> List[Tuple[int, ...]]:
    return sorted(boxes_of(dims, n), key=lambda b: (-bisection(b), b))


def arrangements(box: Sequence[int], dims: Sequence[int]) -> List[Tuple[int, ...]]:
    return [p for p in sorted(set(itertools.permutations(box)))
            if all(s <= a for s, a in zip(p, dims))]


def all_to_all_loads(dims: Sequence[int], extents: Sequence[int]) -> np.ndarray:
    """Link loads (dim, direction +/-, *cells) of the box's all-to-all traffic
    placed at the origin, in units of 1/(2n): volume 2 for each ordered pair."""
    D = len(dims)
    loads = np.zeros((D, 2) + tuple(dims), dtype=np.int64)
    cells = list(itertools.product(*(range(w) for w in extents)))
    for src in cells:
        for dst in cells:
            if src == dst:
                continue
            cur = list(src)
            for k, a in enumerate(dims):
                delta = (dst[k] - cur[k]) % a
                if delta:
                    ways = []
                    if 2 * delta < a:
                        ways = [(0, delta, 2)]
                    elif 2 * delta > a:
                        ways = [(1, a - delta, 2)]
                    else:
                        ways = [(0, delta, 1), (1, delta, 1)]
                    for direction, hops, vol in ways:
                        pos = list(cur)
                        for _ in range(hops):
                            loads[(k, direction) + tuple(pos)] += vol
                            pos[k] = (pos[k] + (1 if direction == 0 else -1)) % a
                cur[k] = dst[k]
    return loads


# ---------------------------------------------------------------------------
# The machine.
# ---------------------------------------------------------------------------
class Machine:
    """Occupancy, running jobs' traffic, and for every box arrangement the
    tables of its placements: the cells, the shell and the link loads at each
    of the torus's offsets (offsets in row-major order)."""

    def __init__(self, dims: Sequence[int], scored: bool = True):
        self.dims = tuple(int(a) for a in dims)
        self.offsets = list(itertools.product(*(range(a) for a in self.dims)))
        self.grid = np.zeros(self.dims, dtype=bool)
        self.traffic = np.zeros((len(self.dims), 2) + self.dims, dtype=np.int64)
        self.placed: Dict[int, Tuple[Tuple[int, ...], Tuple[int, ...]]] = {}
        self.scored = scored
        self.touch_shell = not any(a >= 6 for a in self.dims)
        self._tables: Dict[Tuple[int, ...], dict] = {}
        self._prefs: Dict[int, List[Tuple[int, ...]]] = {}

    def _flat(self, idx) -> np.ndarray:
        return np.ravel_multi_index(np.meshgrid(*idx, indexing="ij"), self.dims).ravel()

    def table(self, ext: Tuple[int, ...]) -> dict:
        if ext not in self._tables:
            base = all_to_all_loads(self.dims, ext)
            axes = tuple(range(2, 2 + len(self.dims)))
            shell = [[((o - 1 + np.arange(w + 2)) % a) if w + 2 <= a else np.arange(a)
                      for o, w, a in zip(off, ext, self.dims)] for off in self.offsets]
            self._tables[ext] = {
                "cells": np.stack([self._flat([(o + np.arange(w)) % a for o, w, a in
                                               zip(off, ext, self.dims)]) for off in self.offsets]),
                "shell": np.stack([self._flat(idx) for idx in shell]),
                "loads": np.stack([np.roll(base, off, axis=axes).ravel() for off in self.offsets]),
            }
        return self._tables[ext]

    def prefs(self, n: int) -> List[Tuple[int, ...]]:
        if n not in self._prefs:
            self._prefs[n] = preferences(self.dims, n)
        return self._prefs[n]

    def free(self, grid, ext) -> np.ndarray:
        """Indices of the offsets where the arrangement covers only free cells."""
        return np.flatnonzero(~grid.ravel()[self.table(ext)["cells"]].any(axis=1))

    def fits(self, grid, n: int) -> bool:
        return any(self.free(grid, ext).size for box in self.prefs(n)
                   for ext in arrangements(box, self.dims))

    def choose(self, n: int):
        """(extents, offset, contention) of the job's placement, or None."""
        busy = (np.broadcast_to(self.grid, self.traffic.shape) | (self.traffic > 0)).ravel()
        for box in self.prefs(n):
            best = None
            for ext in arrangements(box, self.dims):
                tab = self.table(ext)
                for i in self.free(self.grid, ext):
                    if not self.scored:  # the control: first free placement
                        return ext, self.offsets[i], 0.0
                    cont = round(int(tab["loads"][i] @ busy) / (2 * n), 9)
                    touch = int(self.grid.ravel()[tab["shell"][i]].sum()) if self.touch_shell else 0
                    key = (cont, -touch, ext, self.offsets[i])
                    if best is None or key < best[0]:
                        best = (key, (ext, self.offsets[i], cont))
            if best is not None:
                return best[1]
        return None

    def _index(self, off) -> int:
        return self.offsets.index(tuple(off))

    def commit(self, job: int, ext, off) -> None:
        tab, i = self.table(ext), self._index(off)
        self.grid.ravel()[tab["cells"][i]] = True
        self.traffic += tab["loads"][i].reshape(self.traffic.shape)
        self.placed[job] = (ext, off)

    def release(self, job: int) -> None:
        ext, off = self.placed.pop(job)
        tab, i = self.table(ext), self._index(off)
        self.grid.ravel()[tab["cells"][i]] = False
        self.traffic -= tab["loads"][i].reshape(self.traffic.shape)

    def cells_of(self, job: int) -> np.ndarray:
        ext, off = self.placed[job]
        return self.table(ext)["cells"][self._index(off)]


# ---------------------------------------------------------------------------
# The service.
# ---------------------------------------------------------------------------
class Service:
    """The event loop, driven as the program's service is: ``submit``,
    ``inject_failure``, ``inject_reclaim`` and ``run(until=)``.  ``log`` holds
    one tuple per event: (time, kind, job, extents, offset, contention, cells,
    reason)."""

    def __init__(self, dims: Sequence[int], backfill: bool = True, scored: bool = True):
        self.m = Machine(dims, scored)
        self.backfill = backfill
        self.now = 0.0
        self.log: List[tuple] = []
        self.pending: List[tuple] = []
        self.push_no = itertools.count()
        self.queue: List[Tuple[dict, int]] = []  # (job, order), first come first served
        self.order = itertools.count()
        self.running: Dict[int, dict] = {}
        self.starts = itertools.count()
        self.failed: set = set()
        self.blocked: Optional[Tuple[int, float]] = None

    def _push(self, time, kind, data):
        heapq.heappush(self.pending, (float(time), RANK[kind], next(self.push_no), kind, data))

    def _log(self, kind, job=None, ext=None, off=None, cont=None, cells=None, reason=None):
        self.log.append((self.now, kind, job, ext, off, cont, cells, reason))

    def submit(self, job_id: int, units: int, duration: float, arrival: float) -> None:
        self._push(arrival, "arrival", {"id": job_id, "units": units, "duration": duration})

    def inject_failure(self, time: float, cells) -> None:
        self._push(time, "fail", tuple(tuple(int(c) for c in cell) for cell in cells))

    def inject_reclaim(self, time: float, cells) -> None:
        self._push(time, "reclaim", tuple(tuple(int(c) for c in cell) for cell in cells))

    def run(self, until: Optional[float] = None) -> "Service":
        while self.pending:
            t0 = self.pending[0][0]
            if until is not None and strictly_before(until, t0):
                break
            self.now = max(self.now, t0)
            while True:
                batch = []
                while self.pending and at_or_before(self.pending[0][0], self.now):
                    batch.append(heapq.heappop(self.pending))
                if not batch:
                    break
                for _, _, _, kind, data in sorted(batch, key=lambda e: (e[1], e[2])):
                    self._apply(kind, data)
                self._schedule()
        if until is not None and until > self.now:
            self.now = until
        return self

    def _enqueue(self, job: dict) -> None:
        self.queue.append((job, next(self.order)))
        self._log("arrival", job["id"])

    def _apply(self, kind, data):
        if kind == "arrival":
            self._enqueue(data)
        elif kind == "complete":
            job_id, start_no = data
            run = self.running.get(job_id)
            if run is None or run["start_no"] != start_no:
                return  # the job was evacuated since
            del self.running[job_id]
            self.m.release(job_id)
            self._log("complete", job_id)
            self.blocked = None
        elif kind == "fail":
            self._log("fail", cells=data)
            hit = np.ravel_multi_index(np.array(data).T, self.m.dims)
            victims = sorted(
                (j for j in self.running if np.isin(self.m.cells_of(j), hit).any()),
                key=lambda j: self.running[j]["start_no"])
            for j in victims:
                run = self.running.pop(j)
                self.m.release(j)
                left = max(0.0, run["end"] - self.now)
                self._log("preempt", j, reason="failure")
                self.blocked = None
                self._enqueue(dict(run["job"], duration=left))
            for cell in data:
                if cell not in self.failed:
                    self.failed.add(cell)
                    self.m.grid[cell] = True
            self.blocked = None
        elif kind == "reclaim":
            self._log("reclaim", cells=data)
            for cell in data:
                if cell in self.failed:
                    self.failed.discard(cell)
                    self.m.grid[cell] = False
                    self.blocked = None

    def _start(self, job: dict) -> bool:
        choice = self.m.choose(job["units"])
        if choice is None:
            return False
        ext, off, cont = choice
        self.m.commit(job["id"], ext, off)
        start_no = next(self.starts)
        end = self.now + job["duration"]
        self.running[job["id"]] = {"job": job, "end": end, "start_no": start_no}
        self._log("start", job["id"], ext, off, cont)
        self._push(end, "complete", (job["id"], start_no))
        return True

    def _reservation(self, n: int) -> Optional[float]:
        frees = [(r["end"], r["start_no"], ("job", j)) for j, r in self.running.items()]
        frees += [(t, no, ("cells", data)) for t, _, no, kind, data in self.pending
                  if kind == "reclaim" and data]
        grid = self.m.grid.copy()
        for t, _, (what, x) in sorted(frees, key=lambda f: (f[0], f[1])):
            if what == "job":
                grid.ravel()[self.m.cells_of(x)] = False
            else:
                for cell in x:
                    if cell in self.failed:
                        grid[cell] = False
            if self.m.fits(grid, n):
                return t
        return self.now if self.m.fits(grid, n) else None

    def _schedule(self):
        while self.queue:
            head, _ = self.queue[0]
            if self.blocked is not None and self.blocked[0] == head["id"]:
                t_res = self.blocked[1]
            else:
                if self._start(head):
                    self.queue.pop(0)
                    continue
                t_res = self._reservation(head["units"])
                if t_res is None:
                    self._log("reject", head["id"], reason="impossible")
                    self.queue.pop(0)
                    continue
                self.blocked = (head["id"], t_res)
            if self.backfill:
                self.queue[1:] = [
                    (job, no) for job, no in self.queue[1:]
                    if not (at_or_before(self.now + job["duration"], t_res) and self._start(job))
                ]
            break
