"""Host-side profiler for the full hunt loop.

The device decomposition (`runner --op suggest`) answers "what is the TPU
doing"; this tool answers "what is the HOST doing" — the producer/storage/
codec cycle that bounds trials/sec on the q-batch presets.  It warms the jit
caches with a short run first so compile time doesn't drown the steady-state
signal (round 5's storage copy-on-write, the inline scalar copy fast path,
and the cheap ASHA naive copies all came out of exactly this profile).

Run: ``python -m orion_tpu.benchmarks.host_profile [preset] [--trials N]``
(defaults: asha-ackley50, 2048 trials, batch 512).  Force
``JAX_PLATFORMS=cpu`` to profile host logic without a device in the loop.
"""

import argparse
import cProfile
import io
import pstats


def main(argv=None):
    from orion_tpu.benchmarks.runner import PRESETS, run_preset

    parser = argparse.ArgumentParser(prog="orion_tpu.benchmarks.host_profile")
    parser.add_argument("preset", nargs="?", default="asha-ackley50",
                        choices=list(PRESETS))
    parser.add_argument("--trials", type=int, default=2048)
    parser.add_argument("--batch", type=int, default=512)
    parser.add_argument("--top", type=int, default=20,
                        help="rows of the tottime table to print")
    args = parser.parse_args(argv)

    # Warm pass: absorbs jit compiles and import time at a quarter budget.
    run_preset(args.preset, seed=0, max_trials=max(args.trials // 4, args.batch),
               batch_size=args.batch)

    profiler = cProfile.Profile()
    profiler.enable()
    out = run_preset(args.preset, seed=1, max_trials=args.trials,
                     batch_size=args.batch)
    profiler.disable()

    print(out)
    stream = io.StringIO()
    pstats.Stats(profiler, stream=stream).sort_stats("tottime").print_stats(args.top)
    print(stream.getvalue())


if __name__ == "__main__":
    main()
