"""`orion-tpu hunt`: run the optimization loop.

Capability parity: reference `src/orion/core/cli/hunt.py` — build/branch the
experiment from args, then `workon` it.
"""

import sys

from orion_tpu.cli.base import add_experiment_args, build_from_args
from orion_tpu.core.worker import format_stats, workon
from orion_tpu.utils.exceptions import BrokenExperiment


def add_subparser(subparsers):
    parser = subparsers.add_parser("hunt", help="run optimization")
    add_experiment_args(parser)
    group = parser.add_argument_group("worker")
    group.add_argument("--max-trials", type=int, default=None, help="total completed-trial budget")
    group.add_argument(
        "--worker-trials",
        type=int,
        default=None,
        help="trials this worker executes before exiting (default: unlimited)",
    )
    group.add_argument("--pool-size", type=int, default=None,
                       help="suggestions per producer round")
    group.add_argument("--working-dir", default=None, help="permanent trial working directory")
    group.add_argument("--max-broken", type=int, default=None, help="broken-trial budget")
    group.add_argument(
        "--heartbeat",
        type=float,
        default=None,
        help="seconds before a silent reserved trial counts as lost",
    )
    group.add_argument(
        "--max-idle-time",
        type=float,
        default=None,
        help="seconds the producer may go without registering a new point",
    )
    group.add_argument(
        "--pipeline-depth",
        type=int,
        default=None,
        help="speculative producer rounds kept in flight on device while "
        "host work (storage commit, codec, telemetry) runs underneath "
        "(default 1 = the classic single-slot pipeline; see "
        "docs/performance.md)",
    )
    group.add_argument(
        "--n-workers",
        type=int,
        default=1,
        help="run this many asynchronous workers against the shared storage "
        "(this process plus N-1 spawned ones).  One process owns the "
        "device: the spawned workers never load it (JAX_PLATFORMS=cpu) and "
        "suggest through a gateway this process serves (or the one "
        "ORION_SERVE_ADDRESS already names)",
    )
    group.add_argument(
        "--profile",
        metavar="DIR",
        default=None,
        help="write a jax.profiler trace of the whole hunt to DIR "
        "(inspect with TensorBoard / xprof)",
    )
    parser.set_defaults(func=main)
    return parser


# Children must never re-spawn.  Argv surgery is unsound both ways: flag
# stripping misses argparse prefix abbreviations (--n-worker), and an
# appended override lands inside the user_args REMAINDER, so the child
# still parses the original count — either way a fork bomb.  An env
# sentinel is immune to every argv form and leaves user args untouched.
_SPAWNED_ENV = "ORION_TPU_SPAWNED_WORKER"


def _spawn_workers(args, experiment):
    """N-1 child processes running the identical hunt (the reference's
    'submit the same command N times' cluster recipe, built in).  The
    experiment is built/branched BEFORE spawning so children resume it.

    A chip belongs to one process, so the children never load it: they run
    with ``JAX_PLATFORMS=cpu`` (the space codec still runs jnp on the host
    side) and their algorithms are gateway tenants — of the gateway the
    experiment is already configured with, else of one this process
    serves in-process on localhost.  Returns ``(processes, gateway)``;
    ``gateway`` is None when the experiment already had one."""
    from orion_tpu.storage.documents import MemoryDB
    from orion_tpu.utils.exceptions import CheckError

    if isinstance(getattr(experiment.storage, "db", None), MemoryDB):
        raise CheckError(
            "--n-workers needs storage processes can share (--storage-path "
            "file, sqlite, or a network server); in-memory storage is "
            "per-process."
        )
    import os
    import secrets
    import subprocess

    argv = list(getattr(args, "_argv", []) or [])
    if not argv:
        # Programmatic callers building args by hand have no invocation to
        # replay; spawning bare children would print help and "fail".
        raise CheckError(
            "--n-workers requires the CLI invocation (argv) to replay in "
            "child processes; call through orion_tpu.cli.main, or launch "
            "workers yourself."
        )
    env = dict(os.environ)
    env[_SPAWNED_ENV] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    gateway = None
    if not experiment.serve_config:
        from orion_tpu.serve.gateway import GatewayServer

        secret = secrets.token_hex(16)
        gateway = GatewayServer(host="127.0.0.1", port=0, secret=secret)
        host, port = gateway.serve_background()
        env["ORION_SERVE_ADDRESS"] = f"{host}:{port}"
        env["ORION_SERVE_SECRET"] = secret
        for key in ("ORION_SERVE_ADDRESSES", "ORION_SERVE_SECRET_FILE"):
            env.pop(key, None)
    try:
        procs = [
            subprocess.Popen([sys.executable, "-m", "orion_tpu.cli", *argv], env=env)
            for _ in range(args.n_workers - 1)
        ]
    except BaseException:
        _stop_gateway(gateway)
        raise
    return procs, gateway


def _stop_gateway(gateway):
    if gateway is not None:
        gateway.shutdown()
        gateway.server_close()


def _run_worker(experiment, parser, args):
    from contextlib import nullcontext

    from orion_tpu.compiler_plane import profiler_capture

    profile_dir = getattr(args, "profile", None)
    # The shared capture helper: `orion-tpu profile --capture DIR` wraps its
    # analysis pass in the same context manager, so both commands print the
    # identical artifact summary line.
    capture = profiler_capture(profile_dir) if profile_dir else nullcontext()
    with capture:
        workon(
            experiment,
            parser,
            worker_trials=args.worker_trials,
            max_idle_time=experiment.max_idle_time,
            # Pacemaker must beat the sweep threshold comfortably or live
            # trials get recovered as lost.
            heartbeat_interval=experiment.heartbeat / 2.0,
        )


def main(args):
    import os

    experiment, parser = build_from_args(args)
    experiment.instantiate()
    workers, gateway = [], None
    if getattr(args, "n_workers", 1) > 1 and not os.environ.get(_SPAWNED_ENV):
        workers, gateway = _spawn_workers(args, experiment)
    try:
        try:
            _run_worker(experiment, parser, args)
        except BrokenExperiment as exc:
            print(f"Error: {exc}", file=sys.stderr)
            # Children hit the same broken budget and stop on their own.
            for proc in workers:
                proc.wait()
            return 1
        # Stats must reflect the WHOLE cohort's work, so join EVERY child
        # first (a list, not a short-circuiting any(): stragglers would
        # outlive the command and keep consuming budget).
        codes = [proc.wait() for proc in workers]
    except BaseException:
        # Any other parent failure (storage errors, Ctrl-C): the cohort
        # must not be orphaned to keep consuming the budget in the
        # background after the command "exited".
        for proc in workers:
            proc.terminate()
        for proc in workers:
            proc.wait()
        raise
    finally:
        # The children's gateway outlives them: stop it only once they
        # are all joined.
        _stop_gateway(gateway)
    if not os.environ.get(_SPAWNED_ENV):
        # Only the parent reports; N interleaved copies of the same stats
        # block from the children would drown the terminal.
        print(format_stats(experiment))
    return 1 if any(code != 0 for code in codes) else 0
