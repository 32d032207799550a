"""Readings that a cell's limits are set from: the program's and the control's.

    python chipbench/control.py --workload <cell> --seeds 11,12,13 [--seconds 10] [--out FILE]

For each seed, a short window of the cell's own traffic at its own size
through the timed path, then the numbers the check compares, read once for
the program (``check``) and once for the control put in its place
(``control``: for the scheduler, the plain reference with its
least-contention placement broken).  Each side is judged by the same
comparison as a run's ``correct`` (``run.judge``).  Set-up is paid once for
all seeds.  Needs the chip, as ``run.py`` does; prints one JSON line per
seed and exits non-zero unless every program reading is correct and every
control reading is not.
"""

from __future__ import annotations

import argparse
import json

import run


def readings(driver, r: dict, state: dict, seed: int, seconds: float) -> dict:
    state["seed"] = seed
    units, _ = run.run_window(driver, state, seconds, False)
    prog = driver.check(r["config"], r["mix"], r["checks"], units, seed)
    ctrl = driver.control(r["config"], r["mix"], r["checks"], units, seed)
    return {"seed": seed, "units": len(units),
            "program": prog, "program_correct": run.judge(prog),
            "control": ctrl, "control_correct": run.judge(ctrl)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=10.0, help="window per seed")
    ap.add_argument("--out", default=None, help="also write the readings here (JSON)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    r = run.resolve(args.workload)
    run.find_device(r["cell"]["chips"])
    from repro.utils.env import enable_compile_cache

    enable_compile_cache()
    driver = r["driver"]
    state = driver.setup(r["config"], r["mix"], seeds[0])
    rows = []
    for seed in seeds:
        row = readings(driver, r, state, seed, args.seconds)
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    if not all(row["program_correct"] and not row["control_correct"] for row in rows):
        raise SystemExit("a program reading is not correct, or a control reading is")


if __name__ == "__main__":
    main()
