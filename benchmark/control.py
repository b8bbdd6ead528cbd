"""Readings that set the limits of a cell's comparison, in one process.

    python benchmark/control.py --workload <cell> --seconds <s> --seeds 1,2,3
        [--no-control] [--fault frozen_fit]

Sets the cell up once, then for each seed runs a window of ``--seconds``
at the cell's own load and prints one JSON line with the numbers the
program's rounds give and those of the control: the reference computed in
bfloat16 (``reference/precision.py``) in the program's place. With
``--fault frozen_fit`` the program runs with its hyper-parameter fit
switched off (0 Adam steps; the reference keeps the configured steps), a
fault that the comparison has to catch. The benchmark's own runs never run
the control or a fault.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def freeze_fit():
    """The program's GP fit runs no Adam step: its hyper-parameters stay at
    the warm start (the cold start, round after round)."""
    from orion_tpu.algo.tpu_bo import TPUBO

    step_kw = TPUBO._step_kw

    def frozen(self):
        return dict(step_kw(self), fit_steps=0, refit_steps=0)

    TPUBO._step_kw = frozen


FAULTS = {"frozen_fit": freeze_fit}


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args(argv)
    if args.fault:
        FAULTS[args.fault]()
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.Cell(args.workload)
    run = harness.Run(cell, seeds[0], args.seconds, False)
    run.setup()
    for seed in seeds:
        run.seed = seed
        run.loop = harness.LOOPS[cell.traffic["entry"]](cell, seed)
        run.window()
        run.release()
        t = time.perf_counter()
        program = run.check()
        line = {"seed": seed, "rounds": len(run.latencies), "window_s": run.window_s,
                "window_compiles": run.window_compiles, "program": program,
                "reference_s": time.perf_counter() - t, "info": dict(run.info)}
        if not args.no_control:
            line["control"] = run.check(control=True)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
