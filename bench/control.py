#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload comfs_ingest --seconds 10 \\
        --seeds 101,102,103

For each seed, one run of the cell (set-up, the window at the cell's own
size and load, the read-back) and two comparisons with the plain
reference by the same path a run takes (``harness.compare``): the
program's (the lower reading) and the control's (the upper reading). The
control is the reference itself put in the program's place, computed in
the nearest precision below the configuration's: 9-bit fingerprints where
the configuration states 10. It answers the same requests at the same
stream positions. One JSON line per seed, with ``correct`` for each side.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def as_control(cell, m):
    """The measured run ``m`` with the control in the program's place: the
    reference at ``F / 2`` gives the state and every answer."""
    from types import SimpleNamespace
    from bench.harness import answered, reference_answers
    from bench.reference import Reference
    ctl = Reference(dict(cell.sketch, F=cell.sketch["F"] // 2),
                    m.plan.stream.slice(0, m.n_total))
    reqs, _, n_at, _ = answered(m)
    got = reference_answers(ctl, reqs, n_at)
    c = SimpleNamespace(**vars(m))
    c.state = ctl.state()
    n_win = 0
    if m.plan.queries is not None:
        n_win = len(m.plan.queries["reqs"])
        c.res = dict(m.res, answers=got[:n_win])
    if m.chk is not None:
        c.chk = dict(m.chk, answers=got[n_win:])
    return c


def readings(cell, m) -> dict:
    """Both sides' numbers and ``correct``, through ``harness.compare``."""
    from bench import harness
    out = {}
    for side, run in (("program", m), ("control", as_control(cell, m))):
        checks = harness.compare(cell, run)
        out[side] = {k: v["value"] for k, v in checks.items()}
        out[side]["correct"] = harness.is_correct(checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 1
    from bench import harness
    cell = harness.Cell(args.workload)
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        m = harness.measure(cell, seed, args.seconds, False, T_START)
        row = {"workload": args.workload, "seed": seed, "edges": m.n_total,
               "answers": len(harness.answered(m)[0])}
        row.update(readings(cell, m))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
