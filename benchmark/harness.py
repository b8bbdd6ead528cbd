"""The benchmark's one general harness.

A cell is found by its name in ``BENCHMARK.json``; its configuration file,
its traffic mix (``benchmark/traffic/<traffic>.json``), its limits
(``benchmark/workloads/<cell>.json``) and its per-layer metric readers
(``benchmark/metrics/<metric>.py``) are found by name, so a new cell or
metric is new files and new entries, never an edit here.

One run: check the chips, build the seeded inputs, warm up with one whole
experiment of the cell's shapes, measure for ``--seconds``, compare what the
timed path produced with the plain reference, print one JSON line.
"""

import argparse
import contextlib
import copy
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(HERE, ".out")

#: jax.monitoring events that mean a program was compiled or loaded.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
LOAD_EVENTS = ("/jax/compilation_cache/cache_hits",)
HOST_SPANS = ("bench.suggest", "bench.objective", "bench.observe")
#: What a traffic mix may set. The loop is closed, with one client.
TRAFFIC_KEYS = {"entry", "keep_fraction", "check_rounds", "trace_seconds"}


class BenchError(RuntimeError):
    """The run cannot measure what it was asked to."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    def __init__(self, name, root=ROOT):
        spec = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.root = root
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = load_json(os.path.join(root, configs[self.entry["config"]]["file"]))
        here = os.path.join(root, "benchmark")
        self.traffic = load_json(os.path.join(here, "traffic", self.entry["traffic"] + ".json"))
        unknown = set(self.traffic) - TRAFFIC_KEYS
        if unknown:
            raise BenchError(f"traffic {self.entry['traffic']!r} sets what the harness does "
                             f"not implement: {sorted(unknown)}")
        self.limits = load_json(os.path.join(here, "workloads", name + ".json"))["limits"]
        e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
        self.end_to_end = e2e
        reported = {m["name"] for m in e2e}
        self.per_layer = [
            m for m in spec["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in reported else [])
        ]
        cfg = self.config
        (self.algo_name, self.algo), = cfg["algorithm"].items()
        self.q = int(cfg["q"])
        self.dims = int(cfg["dims"])
        self.low, self.high = float(cfg["low"]), float(cfg["high"])
        self.names = [f"x{i:02d}" for i in range(self.dims)]
        if self.q < 8 or self.q & (self.q - 1):
            raise BenchError("q must be a power of two of at least 8")
        if (self.algo_name, self.algo.get("acq"), self.algo.get("kernel")) != (
                "tpu_bo", "thompson", "matern52"):
            raise BenchError("the reference covers tpu_bo with Thompson and Matern-5/2")

    def objective(self, x):
        from benchmark.reference.objectives import OBJECTIVES

        return OBJECTIVES[self.config["objective"]](x)

    def priors(self):
        return {n: f"uniform({self.low}, {self.high})" for n in self.names}


def require_devices(chips):
    """The TPU devices of this run; no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise BenchError(f"{len(devices)} TPU chips, the cell needs {chips}")
    return devices


def configure_jax():
    """The program's own persistent compilation cache (its directory policy:
    ``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), keeping
    every program however short its compile, so that a run after the first
    compiles nothing."""
    import jax

    from orion_tpu.utils.jit_cache import enable_persistent_compilation_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if enable_persistent_compilation_cache() is None:
        raise BenchError("the program's compilation cache is off")


class CompileCounter:
    """Counts compiles and persistent-cache loads since the last ``take``."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.loads = 0

        def on_duration(event, *args, **kw):
            if event in COMPILE_EVENTS:
                self.compiles += 1

        def on_event(event, *args, **kw):
            if event in LOAD_EVENTS:
                self.loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def take(self):
        out = (self.compiles, self.loads)
        self.compiles = self.loads = 0
        return out


class History:
    """The benchmark's own record of one experiment's observations, as the
    program holds them (float32 unit-cube rows and float32 targets)."""

    def __init__(self, cell):
        self.cell = cell
        self.x = np.zeros((0, cell.dims), np.float32)
        self.y = np.zeros((0,), np.float32)

    def add(self, params, values):
        from benchmark.reference.compare import encode

        self.x = np.concatenate([self.x, encode(params, self.cell.low, self.cell.high)])
        self.y = np.concatenate([self.y, np.asarray(values, np.float32)])

    @property
    def n(self):
        return self.y.shape[0]


class AlgorithmLoop:
    """Closed loop through the algorithm's public API: ``create_algo``,
    then ``suggest_batch(q)`` and ``observe`` of every returned row."""

    def __init__(self, cell, seed):
        from orion_tpu.space.dsl import build_space

        self.cell, self.seed = cell, seed
        self.space = build_space(cell.priors())

    def start(self, k):
        from orion_tpu.algo.base import create_algo

        cell = self.cell
        rng = np.random.default_rng([self.seed, k])
        self.algo = create_algo(self.space, {cell.algo_name: copy.deepcopy(cell.algo)},
                                seed=int(rng.integers(2**31)))
        self.hist = History(cell)
        n0 = int(cell.config["seed_observations"])
        if n0:
            x0 = rng.random((n0, cell.dims))
            params = cell.low + x0 * (cell.high - cell.low)
            values = cell.objective(x0)
            self.algo.observe([dict(zip(cell.names, row)) for row in params.tolist()],
                              [{"objective": v} for v in values.tolist()])
            self.hist.add(params, values)
        return int(cell.config["trials_per_experiment"]) // cell.q

    def pre(self):
        state = self.algo._gp_state
        return self.algo.rng_key, (None if state is None else state.hypers)

    def model(self):
        return self.algo

    def warm_up(self):
        return contextlib.nullcontext()

    def round(self, annotate):
        cell = self.cell
        with annotate("bench.suggest"):
            t0 = time.perf_counter()
            batch = self.algo.suggest_batch(cell.q)
            latency = time.perf_counter() - t0
        with annotate("bench.objective"):
            params = np.column_stack([batch.params.column(n) for n in cell.names])
            values = cell.objective((params - cell.low) / (cell.high - cell.low))
        with annotate("bench.observe"):
            self.algo.observe(batch.params, [{"objective": v} for v in values.tolist()])
        return latency, params, values, batch.cube

    def post(self):
        return self.algo._gp_state, self.algo.rng_key

    def checks(self):
        return {}


class OptimizeLoop:
    """The loop of ``orion_tpu.client.experiment.optimize()`` (memory
    storage, producer, trial documents, a batch evaluator), driven step by
    step so that each suggest request can be timed."""

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.runs = []

    def start(self, k):
        from orion_tpu.client.experiment import ExperimentClient
        from orion_tpu.core.experiment import build_experiment
        from orion_tpu.storage.base import create_storage

        cell = self.cell
        rng = np.random.default_rng([self.seed, k])
        storage = create_storage({"type": "memory"})
        budget = int(cell.config["trials_per_experiment"])
        experiment = build_experiment(
            storage, f"bench-{k}", priors=cell.priors(), max_trials=budget,
            algorithms={cell.algo_name: copy.deepcopy(cell.algo)}, strategy=None,
            pool_size=cell.q,
        ).instantiate(seed=int(rng.integers(2**31)))
        self.client = ExperimentClient(experiment)
        self.hist = History(cell)
        self.stored = []  # rows this experiment stored, for the warm-up
        self.runs.append([self.client, 0])
        return budget // cell.q

    def pre(self):
        algo = self.client.producer.algorithm
        state = algo._gp_state
        return algo.rng_key, (None if state is None else state.hypers)

    def model(self):
        return self.client.producer.algorithm

    def repeats(self, num):
        """How many stored rows the warm-up puts into a suggest of ``num``
        rows: the producer's retry then asks for that many, in the next
        smaller q bucket (q, then q/2 down to 16, then 8)."""
        if num == self.cell.q:
            return 3 * num // 4
        if num > 16:
            return 1 << ((num - 1).bit_length() - 1)
        return 1 if num > 1 else 0

    @contextlib.contextmanager
    def warm_up(self):
        """Set-up only: suggests repeat rows stored before, so that the
        producer's retry after duplicates (a re-sync that leaves a lie for
        each of the round's trials in its naive copy, a suggest for the
        missing rows, their register and observe) runs here in every q
        bucket it can ask for, and compiles nothing in the window. The
        retries' backoff sleeps are skipped, and this experiment's producer
        gives a round 10 minutes, not 60 s: on a cold cache a round's chain
        of retries compiles a step for each q bucket."""
        from orion_tpu.algo.tpu_bo import TPUBO
        from orion_tpu.core.producer import Producer

        self.client.producer.max_idle_time = 600.0
        suggest, sleep = TPUBO._suggest_cube, Producer._sleep_backoff

        def repeat(algo, num):
            rows = np.array(suggest(algo, num))
            n = self.repeats(num)
            if 0 < n <= len(self.stored):
                rows[:n] = self.stored[:n]
            else:
                n = 0
            self.stored.extend(rows[n:])
            return rows

        def count_only(producer):
            producer.failure_count += 1

        TPUBO._suggest_cube = repeat
        Producer._sleep_backoff = count_only
        try:
            yield
        finally:
            TPUBO._suggest_cube = suggest
            Producer._sleep_backoff = sleep
        self.warm_row_slices()

    def warm_row_slices(self):
        """The step's rows come back cut to the count asked for (``rows[:num]``
        on the device), a small program for each count: warm every count a
        retry can ask for, 1 to q - 1, at its q bucket, on rows placed as the
        step's are (on the default device, uncommitted)."""
        import jax.numpy as jnp

        width = self.model().space.n_cols
        for num in range(1, self.cell.q):
            bucket = max(8, 1 << (num - 1).bit_length())
            rows = jnp.asarray(np.zeros((bucket, width), np.float32))
            rows[:num].block_until_ready()

    def round(self, annotate):
        cell, client = self.cell, self.client
        with annotate("bench.suggest"):
            t0 = time.perf_counter()
            if client.is_done:
                raise BenchError("experiment finished before its last round")
            trials = client.suggest(cell.q)
            latency = time.perf_counter() - t0
        with annotate("bench.objective"):
            space = client.experiment.space
            cube = space.encode_flat(space.params_to_arrays([t.params for t in trials]))
            values = cell.objective(np.asarray(cube, np.float64))
            params = cell.low + np.asarray(cube, np.float64) * (cell.high - cell.low)
        with annotate("bench.observe"):
            client.observe_all(trials, [float(v) for v in values])
        self.runs[-1][1] += len(trials)
        return latency, params, values, None

    def post(self):
        return self.client.producer.naive_algorithm._gp_state, \
            self.client.producer.algorithm.rng_key

    def checks(self):
        """Storage checks over the window's experiments: every suggested
        trial stored once, every observation reached the algorithm."""
        unstored = lost = 0
        for client, handed in self.runs:
            exp = client.experiment
            ids = [t.id for t in exp.fetch_trials()]
            completed = len(exp.fetch_trials_by_status("completed"))
            client.producer.update()
            observed = client.producer.algorithm.n_observed
            unstored += abs(handed - len(set(ids))) + len(ids) - len(set(ids))
            lost += abs(completed - observed) + abs(handed - completed)
        return {"unstored_suggestions": unstored, "lost_observations": lost}


LOOPS = {"algorithm": AlgorithmLoop, "optimize": OptimizeLoop}


#: Stands in for ``jax.profiler.TraceAnnotation`` outside the window.
null_annotation = contextlib.nullcontext


class Run:
    """One run of one cell: set-up, window, reference, result line."""

    def __init__(self, cell, seed, seconds, trace, require_tpu=True, t_start=None):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.require_tpu = require_tpu
        self.info = {}

    def setup(self):
        import jax

        configure_jax()
        self.all_devices = (require_devices(self.cell.chips) if self.require_tpu
                            else jax.devices())
        self.devices = self.all_devices[:self.cell.chips]
        self.counter = CompileCounter()
        loop_cls = LOOPS[self.cell.traffic["entry"]]
        self.loop = loop_cls(self.cell, self.seed)
        # Warm-up: one whole experiment of the window's shapes.
        t = time.perf_counter()
        rounds = self.loop.start(0)
        with self.loop.warm_up():
            for _ in range(rounds):
                self.loop.round(null_annotation)
        # Background prewarm compiles started by the warm-up finish before
        # the window.
        prewarmer = getattr(self.loop.model(), "_prewarmer", None)
        if prewarmer is not None:
            prewarmer.wait()
        if isinstance(self.loop, OptimizeLoop):
            self.loop.runs.clear()
        self.info["setup_parts_s"] = [t - self.t_start, time.perf_counter() - t]
        self.info["setup_compiles_loads"] = list(self.counter.take())

    def window(self):
        import jax

        cell, traffic = self.cell, self.cell.traffic
        rng = np.random.default_rng([self.seed, 2**32 - 1])
        keep_p = float(traffic["keep_fraction"])
        annotate = jax.profiler.TraceAnnotation
        n_init = int(cell.algo["n_init"])
        self.latencies, self.kept = [], []
        self.suggestions = 0
        trace_dir = None
        if self.trace:
            trace_dir = os.path.join(OUT_DIR, "trace", f"{cell.name}-{self.seed}")
            os.makedirs(trace_dir, exist_ok=True)
        k = 0
        tracing = False
        self.setup_s = time.perf_counter() - self.t_start
        self.counter.take()
        t0 = time.perf_counter()
        deadline = t0 + self.seconds
        trace_end = t0 + float(traffic["trace_seconds"])
        if self.trace:
            from orion_tpu.telemetry import TELEMETRY

            TELEMETRY.drain_spans()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # Python call events swamp the host
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tracing = True
        now = t0
        while now < deadline:
            k += 1
            rounds = self.loop.start(k)
            for r in range(rounds):
                keep = rng.random() < keep_p or r == rounds - 1
                n_obs = self.loop.hist.n
                pre = self.loop.pre() if keep else None
                latency, params, values, rows = self.loop.round(annotate)
                self.latencies.append(latency)
                self.suggestions += params.shape[0]
                if keep and n_obs >= n_init:
                    state, key_after = self.loop.post()
                    self.kept.append(dict(
                        exp=k, round=r, n_obs=n_obs, rng_key=pre[0], warm=pre[1],
                        state=state, key_after=key_after, rows=rows, params=params,
                        hist=self.loop.hist,
                    ))
                self.loop.hist.add(params, values)
                now = time.perf_counter()
                if tracing and now >= trace_end:
                    jax.profiler.stop_trace()
                    tracing = False
                if now >= deadline:
                    break
        self.window_s = now - t0
        if tracing:
            jax.profiler.stop_trace()
        self.window_compiles = sum(self.counter.take())
        if self.trace:
            from orion_tpu.telemetry import TELEMETRY

            self.telemetry_spans = TELEMETRY.drain_spans()
        self.trace_dir = trace_dir
        self.experiments = k
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in self.devices)

    # -- correctness ---------------------------------------------------------
    def check(self, control=False):
        """Numbers of the comparison (worst over the sampled rounds)."""
        from benchmark.reference import compare
        from benchmark.reference.draws import round_draws

        cell = self.cell
        rng = np.random.default_rng([self.seed, 2**32 - 2])
        kept = self.kept
        n_check = int(cell.traffic["check_rounds"])
        if not kept:
            raise BenchError("no model round finished in the window")
        longest = max(range(len(kept)), key=lambda i: kept[i]["n_obs"])
        others = [i for i in range(len(kept)) if i != longest]
        picks = [longest] + list(rng.choice(others, size=min(n_check - 1, len(others)),
                                           replace=False))
        per_round = []
        skipped = 0
        for i in sorted(picks):
            rnd = dict(kept[i])
            st = rnd["state"]
            if st is None:
                # The program ran no model round where one was due.
                per_round.append({"fit_rows_unknown": 0, "fit_set_wrong": 1})
                continue
            rnd["state"] = {
                "x": np.asarray(st.x), "y": np.asarray(st.y), "mask": np.asarray(st.mask),
                "hypers": np.concatenate([np.asarray(st.hypers.log_lengthscales, np.float64),
                                          [float(st.hypers.log_amplitude),
                                           float(st.hypers.log_noise)]]),
                "mll": float(st.mll), "y_mean": float(st.y_mean), "y_std": float(st.y_std),
                "alpha": np.asarray(st.alpha),
            }
            warm = rnd["warm"]
            if warm is not None:
                rnd["warm"] = np.concatenate([np.asarray(warm.log_lengthscales, np.float64),
                                              [float(warm.log_amplitude),
                                               float(warm.log_noise)]])
            key = np.asarray(rnd["rng_key"])
            raw = round_draws(key, cell.dims, cell.q, int(cell.algo["n_candidates"]),
                              float(cell.algo["local_frac"]))
            if not np.array_equal(raw["next_key"], np.asarray(rnd["key_after"])):
                # More than one suggest ran in this round (a retry after a
                # duplicate): its inputs are not the ones regenerated here.
                skipped += 1
                continue
            hist = rnd["hist"]
            unknown, wrong, raw_y = compare.fit_set_numbers(
                rnd["state"], hist.x, hist.y, rnd["n_obs"],
                int(cell.algo["tr_local_m"]) if cell.algo["trust_region"] else 0)
            rnd["raw_y"] = raw_y
            rnd["fit_set"] = {"fit_rows_unknown": unknown, "fit_set_wrong": wrong}
            if rnd["rows"] is None:
                rnd["rows"] = compare.encode(rnd["params"], cell.low, cell.high)
                rnd["params"] = None
            if unknown:
                per_round.append(dict(rnd["fit_set"]))
                continue
            per_round.append(compare.judge_round(rnd, raw, cell.algo, cell.low, cell.high,
                                                 control=control))
        numbers = compare.worst(per_round) if per_round else {}
        numbers.update(self.storage_checks)
        self.info["checked_rounds"] = len(per_round)
        self.info["skipped_rounds"] = skipped
        if not per_round:
            raise BenchError("no round could be compared")
        return numbers

    def release(self):
        """Move what the comparison needs to the host and drop the
        program's state before the reference runs."""
        import jax

        self.storage_checks = self.loop.checks()
        fields = ("state", "warm", "rng_key", "key_after")
        host = jax.device_get([[rnd[f] for f in fields] for rnd in self.kept])
        for rnd, values in zip(self.kept, host):
            rnd.update(zip(fields, values))
        self.loop = None

    # -- per-layer -----------------------------------------------------------
    def layer_metrics(self):
        from benchmark import trace as tr

        out = {}
        reduction = None
        if self.trace_dir is not None:
            events = tr.load(tr.find_xplane(self.trace_dir))
            shutil.rmtree(self.trace_dir)  # read once; a run writes little to disk
            reduction = tr.Trace(events, self.cell.chips)
        ctx = LayerContext(self, reduction)
        for metric in self.cell.per_layer:
            try:
                value = load_reader(self.cell.root, metric["name"]).read(ctx)
            except tr.NotFound as exc:
                # What the cell runs is missing from its trace: renamed,
                # left anonymous, or never run. The run fails; it never
                # reads as 0 or drops the metric.
                raise BenchError(f"{metric['name']}: {exc}") from exc
            if value is not None:
                out[metric["name"]] = {"value": value, "unit": metric["unit"]}
        return out, ctx

    def end_to_end(self):
        lat = np.asarray(self.latencies) * 1e3
        values = {
            "suggestions_per_s": self.suggestions / self.window_s,
            "suggest_p50_ms": float(np.percentile(lat, 50)),
            "suggest_p95_ms": float(np.percentile(lat, 95)),
            "setup_s": self.setup_s,
        }
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in self.cell.end_to_end}


class LayerContext:
    """What a per-layer metric reader may read: the trace reduction (or
    None), the traced slice, counters and spans of the run, the cell."""

    def __init__(self, run, reduction):
        self.cell = run.cell
        self.trace = reduction
        self.window_compiles = run.window_compiles
        self.telemetry_spans = getattr(run, "telemetry_spans", [])
        self.n_devices = run.cell.chips
        self.window_rounds = len(run.latencies)
        self.device_kind = run.devices[0].device_kind
        if reduction is not None:
            lo = min(s for s, _ in reduction.host_spans("bench.suggest"))
            hi = max(e for _, e in reduction.host_spans("bench.observe"))
            self.lo, self.hi = lo, hi
            self.busy_ns = reduction.mean_busy_ns(lo, hi)
            self.window_ns = hi - lo
            self.rounds = len([s for s, _ in reduction.host_spans("bench.suggest")])

    def peaks(self):
        table = load_json(os.path.join(HERE, "peaks.json"))
        if self.device_kind not in table:
            raise BenchError(f"no peaks for device kind {self.device_kind!r}")
        return table[self.device_kind]


def load_reader(root, name):
    """The reader module ``benchmark/metrics/<name>.py`` under ``root``."""
    path = os.path.join(root, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark.metrics.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on this machine's TPU.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start=None):
    args = parse_args(argv)
    try:
        cell = Cell(args.workload)
        run = Run(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start)
        if args.trace:
            from orion_tpu.telemetry import TELEMETRY

            TELEMETRY.enable()
        run.setup()
        run.window()
        metrics = {}
        breakdown = None
        device = {
            "platform": run.devices[0].platform,
            "kind": run.devices[0].device_kind,
            "count": len(run.all_devices),
            "memory_peak_bytes": int(run.memory_peak),
        }
        run.release()
        t_ref = time.perf_counter()
        numbers = run.check()
        run.info["reference_s"] = time.perf_counter() - t_ref
        if args.trace:
            metrics, ctx = run.layer_metrics()
            device["busy_s"] = ctx.busy_ns / 1e9
            device["window_s"] = ctx.window_ns / 1e9
            breakdown = {
                "device_ops": ctx.trace.top_ops(ctx.lo, ctx.hi),
                "idle_gaps": ctx.trace.idle_gaps(ctx.lo, ctx.hi, HOST_SPANS),
            }
        else:
            metrics = run.end_to_end()
    except BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return 2
    compared = {}
    correct = True
    for name, limit in cell.limits.items():
        value = numbers.get(name, float("nan"))
        ok = value == value and value <= limit
        correct &= ok
        compared[name] = {"value": value, "limit": limit}
    info = dict(run.info, rounds=len(run.latencies), experiments=run.experiments,
                window_s=run.window_s, window_compiles=run.window_compiles,
                unlimited={k: v for k, v in numbers.items() if k not in cell.limits})
    print(json.dumps({"info": info}), file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": len(run.latencies), "failed": 0,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line))
    sys.stdout.flush()
    return 0
