#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json`` (``bench/workloads/<cell>.json``
and the files it names), builds the served ``SketchServer`` over a sketch
created on the device, warms every shape the cell's traffic uses, measures
for ``--seconds``, checks what the window produced against the plain
reference (``bench/reference.py``), and prints one JSON line as the last
line of standard output: with ``--trace 0`` the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window. The numbers compared and their limits close standard error and the
result line (``checks``). ``setup_s`` runs from the moment JAX has its
devices to the first timed edge: program imports, stream generation,
the state on the device, compile-cache loads and the warm-up ingest.

It needs the TPU the cell asks for: without one, or without the program
(``src/repro``) beside it, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the program (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    devs = jax.devices()
    # set-up counts from here: the TPU runtime's start, which varies from
    # host to host by seconds and is no part of the system, is logged apart
    t_ready = time.perf_counter()
    if devs[0].platform != "tpu":
        print(f"bench/run.py: needs a TPU, JAX found {devs[0].platform} "
              "devices only; there is no CPU fallback", file=sys.stderr)
        return 1
    from bench import harness
    harness.log(f"set-up: process start to {len(devs)} TPU device(s) in "
                f"{t_ready - T_START:.3f} s (not in setup_s)")
    cell = harness.Cell(args.workload)
    if len(devs) < cell.chips:
        print(f"bench/run.py: {args.workload} needs {cell.chips} chips, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 1
    harness.log(f"compile cache: {harness.enable_cache()}")
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_ready)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
