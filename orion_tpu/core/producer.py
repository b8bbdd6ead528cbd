"""Producer: turn algorithm suggestions into registered trials.

Capability parity: reference `src/orion/core/worker/producer.py` — observe
completed trials in the real algorithm + strategy; build a *naive* copy that
additionally observes fantasized results ("lies") for incomplete trials;
suggest from the naive copy so concurrent suggestion stays diverse; register
trials with lineage parents; gaussian-jitter backoff on duplicate points and
a `max_idle_time` guard against algorithms that stop producing new points.
"""

import copy
import inspect
import logging
import os
import time
from collections import deque

import numpy as np

from orion_tpu.core.trial import RESERVABLE_STATUSES, Result, Trial, TrialBatch
from orion_tpu.devmem import sample_memory
from orion_tpu.health import FLIGHT, flight_events_as_spans
from orion_tpu.storage.retry import RetryPolicy
from orion_tpu.telemetry import TELEMETRY, current_trace_context

from orion_tpu.utils.exceptions import (
    AlgorithmExhausted,
    DuplicateKeyError,
    SampleTimeout,
)

log = logging.getLogger(__name__)


def _base_register_suggestion():
    """The BaseAlgorithm no-op ``register_suggestion`` (lazy import: the
    algo package is heavier than this module and not otherwise needed)."""
    from orion_tpu.algo.base import BaseAlgorithm

    return BaseAlgorithm.register_suggestion


def _observe_accepts_cube(algo):
    """True when the algorithm's ``observe`` takes the columnar ``cube``
    kwarg (the BaseAlgorithm contract).  Pre-columnar third-party plugins
    that override ``observe(params_list, results)`` keep working through
    the dict path."""
    try:
        sig = inspect.signature(type(algo).observe)
    except (TypeError, ValueError):  # pragma: no cover - exotic callables
        return False
    return any(
        p.name == "cube" or p.kind is inspect.Parameter.VAR_KEYWORD
        for p in sig.parameters.values()
    )


class Producer:
    #: Minimum seconds between metrics-snapshot upserts: _flush_timings
    #: runs from both update() and produce(), and the snapshot (every
    #: histogram's full bucket array) is the heaviest telemetry write —
    #: a q-round's worth of freshness is plenty for `orion-tpu info`.
    METRICS_FLUSH_INTERVAL = 2.0

    def __init__(self, experiment, max_idle_time=None, pipeline_depth=None):
        from orion_tpu.core.experiment import (
            DEFAULT_MAX_IDLE_TIME,
            DEFAULT_PIPELINE_DEPTH,
        )

        if max_idle_time is None:
            max_idle_time = DEFAULT_MAX_IDLE_TIME
        # Pipeline depth resolution: explicit arg > experiment worker-level
        # knob > ORION_TPU_PIPELINE_DEPTH env > default 1 (the pre-ring
        # single-slot behavior, differentially pinned).
        if pipeline_depth is None:
            pipeline_depth = getattr(experiment, "pipeline_depth", None)
        if pipeline_depth is None:
            pipeline_depth = os.environ.get("ORION_TPU_PIPELINE_DEPTH")
        self.pipeline_depth = max(
            1, int(pipeline_depth or DEFAULT_PIPELINE_DEPTH)
        )
        if experiment.algorithm is None:
            raise RuntimeError("Experiment not instantiated (call instantiate())")
        self.experiment = experiment
        self.algorithm = experiment.algorithm
        self.strategy = experiment.strategy
        self.max_idle_time = max_idle_time
        self.naive_algorithm = None
        self._observed_ids = set()  # replaces reference TrialsHistory dedup
        self._leaf_ids = []  # lineage: children of observed DAG (trials_history.py)
        # Columnar observe cache: trial id -> (D,) float32 unit-cube row
        # (Space.params_to_cube encoding).  Lies re-observe every in-flight
        # trial every round; without this each round re-parses O(in-flight)
        # param dicts through the codec.  Keyed by the STORAGE trial id —
        # a stored string on fetched trials, so cache lookups never pay the
        # md5-over-params hash_params would recompute per access.  Rows are
        # evicted once their trial completes and feeds the real algorithm
        # (never needed again — _observed_ids gates re-observation) and
        # swept for stopped trials.
        self._cube_cache = {}
        # Third-party plugins may predate the columnar contract and override
        # observe(params_list, results) without the cube kwarg — detect once
        # and fall back to the dict path for them (same semantics, slower).
        # Algorithms that declare uses_observe_cube=False (purely dict-keyed
        # observation handling, e.g. ASHA rung bookkeeping) skip the cube
        # build/cache too — it would be pure waste for them.
        self._observe_takes_cube = getattr(
            self.algorithm, "uses_observe_cube", True
        ) and _observe_accepts_cube(self.algorithm)
        self.failure_count = 0
        self._backoff_policy = RetryPolicy(
            base_delay=0.01, max_delay=0.5, jitter=0.5, deadline=None
        )
        self._n_in_flight = 0  # status == reserved (someone is executing)
        self._n_reservable = 0  # new/suspended/interrupted (worker can consume)
        self._pending_timings = []
        # One optimization-health record per produce round (orion_tpu
        # .health), built at round end and flushed through the storage
        # health channel next to the spans/metrics.
        self._pending_health = None
        self._round_index = 0
        self._last_metrics_flush = float("-inf")
        self._n_completed_seen = 0
        self._update_epoch = 0
        # The speculative ring: up to ``pipeline_depth`` in-flight rounds,
        # oldest first.  Each entry is ``(handle, algo, t0, ctx)`` — the
        # unforced device handle, the naive copy that dispatched it, and
        # the per-entry open ``device.dispatch`` telemetry window
        # (perf_counter at dispatch + the ambient TraceContext of the
        # round that dispatched it; both None when telemetry is off).
        # Round k's storage commit, codec work and telemetry flush all run
        # while rounds k+1..k+N sit here — the depth-1 configuration is
        # behaviorally identical to the pre-ring single-slot pipeline
        # (tests/unit/test_producer_pipeline.py pins the storage op
        # sequence and the suggestion bit-stream).
        self._spec_ring = deque()
        # Whether the algorithm actually implements register_suggestion:
        # the per-slot call is a per-point plugin API, and paying a q-row
        # dict materialization per round to invoke the base no-op would
        # defeat the columnar commit.  Re-resolved at the top of every
        # produce round (_refresh_register_suggestion_gate) so
        # instance-assigned hooks and post-construction monkeypatches keep
        # firing exactly as the pre-gate code's dynamic call did.
        self._needs_register_suggestion = True
        self._refresh_register_suggestion_gate()
        # Trial ids already conditioned (register_suggestion + lie) onto the
        # CURRENT naive copy by _dispatch_speculative: the pipelined commit
        # may re-invoke it on the same instance (mid-loop dispatch opted
        # out, post-loop retry), and re-observing the same lies would skew
        # opt-in model-based speculation.  Reset whenever the naive copy is
        # rebuilt.
        self._spec_conditioned = set()
        # Probe the EVC family ONCE: walking the tree costs extra collection
        # scans per round (each a full lock/unpickle on the file backend),
        # which an un-branched experiment should never pay.  A branch
        # appearing mid-run is picked up by the next worker process.
        self._has_evc_family = bool(experiment.refers.get("parent_id")) or bool(
            experiment.storage.fetch_experiments(
                {"refers.parent_id": experiment.id}, projection={"_id": 1}
            )
        )
        # Incremental tree fetcher: topology + adapted family trials cached,
        # only changed trials re-read/re-adapted each round (VERDICT r1 #7).
        self._tree_fetcher = None
        if self._has_evc_family:
            from orion_tpu.evc.experiment import TreeTrialsFetcher

            self._tree_fetcher = TreeTrialsFetcher(experiment)

    # --- observation --------------------------------------------------------
    def update(self):
        """Sync algorithm state with storage (reference `producer.py:103-132`).

        Trials come through the EVC tree: a branched child warm-starts from
        its ancestors' completed trials, adapted hop by hop (reference
        `evc/experiment.py:154-226` — the point of branching).

        The round's snapshot comes from storage.fetch_update_view, which
        count-gates the completed history on capable backends (update()
        runs every produce round AND every backoff; re-reading the whole
        completed history each time costs O(trials) per call) and keeps
        the single full fetch elsewhere — see its docstring for the
        consistency and ordering contract."""
        with TELEMETRY.span("producer.update"):
            if self._tree_fetcher is not None:
                trials = self._tree_fetcher.fetch()
            else:
                # Every 16th sync forces the gate open: the count gate assumes
                # the completed count only grows, which a concurrent
                # db-level remove of a completed trial (offset by a fresh
                # completion) could violate — the periodic full read bounds
                # that staleness window instead of trusting the invariant
                # forever.
                self._update_epoch += 1
                known = self._n_completed_seen if self._update_epoch % 16 else -1
                trials, self._n_completed_seen = (
                    self.experiment.storage.fetch_update_view(self.experiment, known)
                )
            completed = [t for t in trials if t.status == "completed" and t.objective]
            incomplete = [t for t in trials if not t.is_stopped]
            # Exhaustion/backoff accounting counts THIS experiment's trials only:
            # the EVC tree fetch includes the family's trials, which this worker
            # can never reserve and whose completions feed ancestors, not us.
            own_id = self.experiment.id
            own = [t for t in trials if t.experiment == own_id]
            self._n_in_flight = sum(t.status == "reserved" for t in own)
            self._n_reservable = sum(t.status in RESERVABLE_STATUSES for t in own)
            self._update_algorithm(completed)
            # Bound the columnar cache: stopped trials are never lied about
            # again, so their rows are dead weight.  Completed-with-objective
            # trials were just observed (and evicted) above; this sweep covers
            # broken / interrupted / objective-less terminals, which would
            # otherwise leak one row per failed trial forever.  (A resumed
            # interrupted trial simply re-encodes on its next cache miss.)
            if self._cube_cache:
                for t in trials:
                    if t.is_stopped:
                        self._cube_cache.pop(t.id, None)
            self._update_naive_algorithm(incomplete)
            self._flush_timings()

    def _update_algorithm(self, completed):
        fresh = [t for t in completed if t.id not in self._observed_ids]
        if fresh:
            params = [t.params for t in fresh]
            results = [_trial_results(t) for t in fresh]
            cube = self._cube_rows_for(fresh)
            t0 = time.perf_counter()
            with TELEMETRY.span("producer.observe", args={"count": len(fresh)}):
                if cube is not None:
                    self.algorithm.observe(params, results, cube=cube)
                else:  # pre-columnar plugin signature
                    self.algorithm.observe(params, results)
            self._record_timing("observe", time.perf_counter() - t0, len(fresh))
            self.strategy.observe(params, results)
            for t in fresh:
                self._observed_ids.add(t.id)
                self._cube_cache.pop(t.id, None)
            self._leaf_ids = [t.id for t in fresh]

    def _cube_rows_for(self, trials):
        """(n, D) columnar rows for ``trials`` — cache hits plus ONE bulk
        ``params_to_cube`` call for the misses.  Bit-identical to the
        per-call dict encode the algorithms would otherwise run (same
        single pipeline, row-independent codec), so the columnar and dict
        observe paths cannot diverge.  Returns None (dict fallback) for
        pre-columnar plugin algorithms."""
        if not self._observe_takes_cube:
            return None
        space = self.algorithm.space
        # lint: disable=PERF001 -- one dict probe per row against the id
        # cache (no codec work); misses below encode in ONE bulk call.
        rows = [self._cube_cache.get(t.id) for t in trials]
        missing = [i for i, r in enumerate(rows) if r is None]
        if missing:
            encoded = space.params_to_cube([trials[i].params for i in missing])
            for j, i in enumerate(missing):
                # Copy each row out: a view into `encoded` would pin the
                # whole (n_missing, D) batch for as long as any one row
                # survives in the cache.
                row = np.array(encoded[j])
                self._cube_cache[trials[i].id] = row
                rows[i] = row
        if not rows:
            return None
        return np.stack(rows)

    def _record_timing(self, op, duration, count):
        """Buffer a timing sample for the storage timings channel (read by
        ``orion-tpu info``); flushed once per produce()/update() round so
        telemetry never adds a storage write inside the hot retry loop.
        The registry's ``producer.{op}`` span of the same phase is a
        ``with`` span around it at the call site."""
        self._pending_timings.append((op, duration, count))

    def _flush_timings(self, force_metrics=False):
        """Telemetry must never break the run (SURVEY §5 timing hooks).

        Flushes the buffered timing samples through the legacy storage
        channel AND, when the telemetry registry is enabled, this worker's
        new span records (drained once each) + a metrics snapshot upsert —
        so ``orion-tpu info``/``trace`` aggregate across worker processes.
        The snapshot upsert is time-gated (METRICS_FLUSH_INTERVAL): this
        runs from update() AND produce(), and re-upserting an
        all-histograms snapshot twice per round would tax the very storage
        hot path the pipelined commit freed.  ``force_metrics`` (the
        end-of-run flush) bypasses the gate so final totals always land."""
        samples, self._pending_timings = self._pending_timings, []
        if not samples and not TELEMETRY.enabled and not FLIGHT.enabled:
            return
        try:
            if samples:
                self.experiment.storage.record_timings(self.experiment, samples)
            # The storage channel's own cursor: in-process readers of the
            # registry (the default cursor) still see every span.
            spans = TELEMETRY.drain_spans("storage") if TELEMETRY.enabled else []
            if FLIGHT.enabled:
                # Mirror drained flight events into the spans channel as
                # flight.* records, so `orion-tpu flight-record -n NAME`
                # can reconstruct this worker's recent history.
                spans = spans + flight_events_as_spans(FLIGHT.drain())
            if spans:
                self.experiment.storage.record_spans(self.experiment, spans)
            health, self._pending_health = self._pending_health, None
            if health:
                self.experiment.storage.record_health(self.experiment, health)
            if TELEMETRY.enabled:
                now = time.monotonic()
                if (
                    force_metrics
                    or now - self._last_metrics_flush >= self.METRICS_FLUSH_INTERVAL
                ):
                    # Device-memory/compile-cache gauges ride the same
                    # low-frequency gate (rate-limited again inside), so a
                    # snapshot never ships stale memory numbers.
                    sample_memory(force=force_metrics)
                    self._sample_serve_placement()
                    self.experiment.storage.record_metrics(
                        self.experiment, TELEMETRY.snapshot()
                    )
                    self._last_metrics_flush = now
        except Exception:  # pragma: no cover - read-only/remote storage quirks
            log.debug("could not record telemetry", exc_info=True)

    def _sample_serve_placement(self):
        """Mirror the remote algorithm's fleet placement into gauges
        (fleet-served experiments only — ``placement()`` is None for
        local algorithms and single-gateway tenants).  Rides the metrics
        snapshot flush, so `orion-tpu top`/`info` show which gateway this
        worker's tenant lives on and how often it failed over."""
        placement = getattr(self.algorithm, "placement", None)
        if placement is None:
            return
        try:
            record = placement()
        except Exception:  # pragma: no cover - observability never breaks
            return
        if not record:
            return
        TELEMETRY.set_gauge(
            "serve.client.fleet_epoch", float(record.get("epoch") or 0)
        )
        TELEMETRY.set_gauge(
            "serve.client.fleet_members", float(record.get("members") or 0)
        )
        TELEMETRY.set_gauge(
            "serve.client.failovers", float(record.get("failovers") or 0)
        )
        TELEMETRY.set_gauge(
            "serve.client.adoptions", float(record.get("adoptions") or 0)
        )

    def _update_naive_algorithm(self, incomplete):
        """Naive algo = deepcopy of real + lies for in-flight trials
        (reference `producer.py:159-174`)."""
        self.naive_algorithm = copy.deepcopy(self.algorithm)
        self._spec_conditioned.clear()  # fresh copy: nothing conditioned yet
        lying = self._produce_lies(incomplete)
        # The lies observed right below ARE conditioning: seed the set with
        # their source ids, or a mid-round backoff (rebuild here, then the
        # next iteration's speculative dispatch) would observe the same
        # in-flight trials' lies a second time on this very copy.
        self._spec_conditioned.update(src.id for src, _ in lying)
        if lying:
            params = [lt.params for _, lt in lying]
            results = [{"objective": lt.lie.value} for _, lt in lying]
            # Columnar: lies re-feed every in-flight point every round, so
            # this is the hottest dict->cube boundary in the loop — row
            # cache + one bulk encode for first-seen points.  Keyed by the
            # SOURCE trial (its storage id is a stored string; the lying
            # twin's id would be a fresh md5 per access AND would never
            # match the eviction sweep's keys).
            cube = self._cube_rows_for([src for src, _ in lying])
            if cube is not None:
                self.naive_algorithm.observe(params, results, cube=cube)
            else:  # pre-columnar plugin signature
                self.naive_algorithm.observe(params, results)

    def _produce_lies(self, incomplete):
        """(source_trial, lying_trial) pairs for every liable in-flight
        trial — the source carries the storage identity, the lying twin the
        fantasy result."""
        lying = []
        for trial in incomplete:
            lie = self.strategy.lie(trial)
            if lie is None or lie.value is None:
                continue
            lying_trial = Trial(
                experiment=trial.experiment,
                params=dict(trial.params),
                results=[Result(lie.name, "lie", lie.value)],
            )
            try:
                self.experiment.register_lie(lying_trial)
            except DuplicateKeyError:
                pass  # lie already registered in a previous round
            lying.append((trial, lying_trial))
        return lying

    # --- production ---------------------------------------------------------
    def produce(self, pool_size=None, own_in_flight=0):
        """Register `pool_size` new trials (reference `producer.py:69-101`).

        The round's storage commit is PIPELINED: once the final batch is
        built, the next round's device suggest is dispatched first and the
        batched register (one transaction / one wire request) runs while
        that computation is in flight — storage latency and device latency
        overlap instead of adding up.

        ``own_in_flight``: how many of the experiment's reserved trials THE
        CALLER itself is holding.  An opt-out normally backs off while
        reserved trials exist (their completions can revive the algorithm),
        but waiting on the caller's own reservations would deadlock the
        caller against itself (``ExperimentClient.suggest`` holding a
        partial batch) — so the wait only applies when reserved trials
        beyond the caller's own exist."""
        # root=True: every produce round IS one distributed trace — the
        # storage commits, wire hops and server-side applies it causes all
        # stamp this round's trace_id, which is what `orion-tpu trace
        # --attribute` buckets the round's wall time by.
        with TELEMETRY.span("producer.round", root=True):
            return self._produce(pool_size, own_in_flight)

    def _refresh_register_suggestion_gate(self):
        """Resolve whether ``register_suggestion`` must be invoked per slot.

        Looked up on the INSTANCE (not the class) and refreshed every
        produce round: a plugin assigning the hook in ``__init__`` or a
        test monkeypatching it after construction must keep receiving the
        per-point callbacks, exactly like the pre-gate dynamic call."""
        hook = getattr(self.algorithm, "register_suggestion", None)
        self._needs_register_suggestion = (
            hook is not None
            and getattr(hook, "__func__", hook)
            is not _base_register_suggestion()
        )

    def _effective_pipeline_depth(self, algo):
        """Ring depth actually used for ``algo``.

        Deep rings are provably free ONLY for algorithms that declare
        ``speculation_safe`` at the CLASS level (observation-independent:
        random, grid — any depth is bit-identical to depth 1).  Opt-in
        model-based speculation (`speculative_suggest=True` sets the flag
        per-INSTANCE) keeps the async-BO contract "each in-flight round is
        conditioned on the previous one's lies", which a burst of N
        dispatches from one posterior would break — every extra entry
        would re-sample the same optimum, and the resulting duplicate
        slots would discard the whole ring every round.  Such algorithms
        stay 1-deep regardless of the knob."""
        if getattr(type(algo), "speculation_safe", False):
            return self.pipeline_depth
        return 1

    def _produce(self, pool_size, own_in_flight):
        pool_size = pool_size or self.experiment.pool_size
        self._refresh_register_suggestion_gate()
        registered = 0
        start = time.time()
        speculative = self._take_speculative(pool_size)
        registered_trials = []
        retry = False
        while registered < pool_size:
            if retry:
                # A pass after one that hit duplicates: its backoff,
                # re-suggest and register are one ``producer.retry``.
                with TELEMETRY.span("producer.retry"):
                    self.backoff()
                    outcome = self._produce_pass(
                        pool_size, own_in_flight, registered, registered_trials,
                        start, None,
                    )
            else:
                outcome = self._produce_pass(
                    pool_size, own_in_flight, registered, registered_trials,
                    start, speculative,
                )
                speculative = None
            if outcome is None:
                break
            registered, retry = outcome
        self._round_index += 1
        if TELEMETRY.enabled:
            # One optimization-health record per round (orion_tpu.health):
            # the naive copy ran this round's fused suggest (its GPState
            # carries the packed device health), the REAL algorithm holds
            # the honest host truth (no fantasy lies in its incumbent) —
            # merge with the real instance's fields winning.
            self._pending_health = self._build_health(registered)
        if FLIGHT.enabled:
            FLIGHT.record(
                "producer.round",
                args={"round": self._round_index, "registered": registered},
            )
        self._flush_timings()
        if len(self._spec_ring) < self._effective_pipeline_depth(
            self.naive_algorithm
        ):
            self._dispatch_speculative(pool_size, registered_trials)
        return registered

    def _produce_pass(self, pool_size, own_in_flight, registered,
                      registered_trials, start, speculative):
        """One suggest-and-register pass of a produce round.  Returns the
        round's registered count and whether a slot was a duplicate, or
        None when the round should end with what it has."""
        if time.time() - start > self.max_idle_time:
            raise SampleTimeout(
                f"algorithm produced no new unique point in {self.max_idle_time}s"
            )
        if speculative is not None:
            # Already timed by _take_speculative (the residual transfer).
            suggested = speculative
        else:
            # Columnar flow: the suggestion crosses the boundary as a (q, d)
            # array; batch.params is a LAZY ParamBatch — the storage
            # documents build straight from its columns, and per-point dicts
            # only materialize at plugin-compat boundaries
            # (register_suggestion overrides, lie strategy).
            suggested = self._suggest_from_naive(pool_size - registered)
        if suggested is None:
            log.debug("algorithm opted out of suggesting")
            # Re-sync first: the opt-out may come from a stale view.
            self.update()
            if registered or self._n_reservable:
                # The worker can make progress without new points — consume
                # what is already registered (this round's partial batch or
                # a concurrent producer's); exhaustion re-fires on the next
                # dry production round.
                return None
            if self._n_in_flight > own_in_flight:
                # Executing trials beyond the caller's own exist; their
                # completions may change the algorithm's state — wait.
                self._sleep_backoff()
                return registered, False
            suggested = self._suggest_from_naive(pool_size - registered)
            if suggested is None:
                # Nothing pending, nothing running, and a fresh-state retry
                # still opts out: no observation can ever arrive, so the
                # state producing this opt-out is final.
                raise AlgorithmExhausted(
                    "algorithm opted out of suggesting with no trials "
                    "in flight; the search space is exhausted"
                )
        return self._register_batch(suggested, pool_size, registered, registered_trials)

    def _suggest_from_naive(self, num):
        """One suggest of ``num`` rows from the naive copy: its params (a
        lazy ParamBatch), or None when the algorithm opts out."""
        t0 = time.perf_counter()
        with TELEMETRY.span("producer.suggest", args={"count": num}):
            batch = self.naive_algorithm.suggest_batch(num)
        suggested = batch.params if batch is not None else None
        # Advance ONLY the real algo's RNG stream, never its full state:
        # the naive copy has observed fantasy lies, and syncing its whole
        # state_dict would permanently inject those rows into the real
        # algorithm (compounding every round).
        self.algorithm.rng_key = self.naive_algorithm.rng_key
        if suggested is not None:
            self._record_timing("suggest", time.perf_counter() - t0, len(suggested))
        return suggested

    def _register_batch(self, suggested, pool_size, registered, registered_trials):
        """Register one pass's suggestions; returns the round's registered
        count and whether any slot was a duplicate (the caller then backs
        off and retries)."""
        # Columnar commit: the round's chunk stays a lazy ParamBatch
        # (or a host scheduler's dict list) wrapped by a TrialBatch —
        # ids and storage documents are built in ONE columnar pass
        # (core.trial), never q Trial constructions.  Trial objects
        # materialize only at the plugin-compat boundary below
        # (speculative lie conditioning, register_suggestion overrides).
        batch = TrialBatch(suggested[: pool_size - registered])
        # Pipelined commit (the storage twin of speculative suggest):
        # when this batch fills the round, stamp identities now —
        # freezing ids, so the speculative lie path and cube cache key
        # correctly — top the speculative ring up to pipeline_depth
        # in-flight rounds, and only then write storage, so the commit
        # overlaps jax async dispatch instead of serializing with it.
        # Presuming the batch registers is safe: a slot that turns out
        # duplicate IS durably registered (by whoever won the race), so
        # the speculative conditioning stays truthful; the ring is
        # discarded below if any slot fails to register.
        prepared = registered + len(batch) >= pool_size
        overlapped = False
        if prepared:
            self.experiment.prepare_trial_batch(batch, parents=self._leaf_ids)
            if getattr(self.naive_algorithm, "speculation_safe", False):
                overlapped = self._dispatch_speculative(
                    pool_size, registered_trials + batch.trials()
                )
        # Batch registration: ONE storage round — a single transaction
        # on SQL backends, one wire request on the network driver
        # (q=4096 would otherwise pay q serialized RTTs); per-trial
        # DuplicateKeyError comes back as that slot's outcome.
        t0 = time.perf_counter()
        try:
            with TELEMETRY.span("producer.register", args={"count": len(batch)}):
                outcomes = self.experiment.register_trial_batch(
                    batch, parents=self._leaf_ids, prepared=prepared
                )
        except Exception:
            if overlapped:
                # Transport-level commit failure (no per-slot outcomes):
                # the batch's fate is unknown, so every ring entry
                # conditioned on it must go — same contract as the
                # per-slot discard below.
                self._discard_spec_ring()
            raise
        self._record_timing("register", time.perf_counter() - t0, len(batch))
        duplicates = 0
        batch_error = None
        spec_capable = getattr(self.naive_algorithm, "speculation_safe", False)
        # lint: disable=PERF001 -- per-slot outcome handling: the
        # register_suggestion hook is a per-point plugin API (gated to
        # algorithms that actually override it), everything else here
        # is O(1) bookkeeping per slot.
        for slot, outcome in enumerate(outcomes):
            if isinstance(outcome, DuplicateKeyError):
                # The point IS durably registered (by us earlier or by a
                # concurrent worker) — the algorithm must still learn it
                # is consumed, or it will re-suggest it forever.
                if self._needs_register_suggestion:
                    self.algorithm.register_suggestion(batch.params[slot])
                log.debug("duplicate suggestion %s", batch.ids[slot])
                duplicates += 1
            elif isinstance(outcome, Exception):
                # Remember but keep walking the outcomes: later slots of
                # the same pipelined round trip WERE durably registered,
                # and skipping their register_suggestion would make the
                # algorithm re-suggest them all next round.
                batch_error = batch_error or outcome
            else:
                if self._needs_register_suggestion:
                    self.algorithm.register_suggestion(batch.params[slot])
                registered += 1
                # Trial views only materialize for the speculative
                # conditioning path; their ids ride the columnar batch
                # (no md5 recomputation — the cube cache keys on them).
                if spec_capable:
                    registered_trials.append(batch.trial_at(slot))
        if duplicates:
            TELEMETRY.count("producer.duplicate_rows", duplicates)
        if overlapped and (duplicates or batch_error is not None):
            # The speculative copies were conditioned on slots that did
            # not register; drop the whole ring — the post-loop dispatch
            # (or the next round's) redoes it from the true set.
            self._discard_spec_ring()
        if batch_error is not None:
            raise batch_error
        return registered, duplicates > 0

    def _build_health(self, registered):
        """Merge naive-copy device health over real-instance host truth
        into one per-round record; never raises (observability must not
        break a run) and returns None for algorithms that report nothing
        (plugins without the BaseAlgorithm contract included)."""
        try:
            record = {}
            naive = self.naive_algorithm
            if naive is not None:
                record.update(
                    getattr(naive, "health_record", lambda: None)() or {}
                )
            record.update(
                getattr(self.algorithm, "health_record", lambda: None)() or {}
            )
            if not record:
                return None
            record["round"] = self._round_index
            record["registered"] = int(registered)
            record["time"] = time.time()
            # Device-memory stamp (orion_tpu.devmem publishes the gauge,
            # rate-limited): gauges are last-write-wins, so the health
            # record is the ONLY stored time series — the doctor's
            # memory-growth trend rule (DX044) reads it from here.
            mem = TELEMETRY.gauge_value("memory.device_live_bytes")
            if mem is not None:
                record["mem_bytes"] = float(mem)
            return record
        except Exception:  # pragma: no cover - observability never breaks a run
            log.debug("could not build health record", exc_info=True)
            return None

    # --- speculative overlap ------------------------------------------------
    @property
    def _speculative(self):
        """Oldest in-flight speculative round as a ``(handle, algo)`` pair,
        or None — the pre-ring single-slot surface, kept for the
        speculation-contract tests and external introspection."""
        if not self._spec_ring:
            return None
        handle, algo, _t0, _ctx = self._spec_ring[0]
        return (handle, algo)

    def _close_entry_window(self, t0, ctx, outcome):
        """Close one ring entry's ``device.dispatch`` span: the async
        device work window from speculative dispatch to finalize/discard."""
        # t0 is only ever stamped with telemetry enabled, but the args dict
        # below must provably not allocate on the disabled path, so the
        # guard is explicit (it also closes the window cleanly if the
        # registry was disabled mid-run).
        if t0 is not None and TELEMETRY.enabled:
            TELEMETRY.record_span(
                "device.dispatch", start=t0, args={"outcome": outcome},
                parent_ctx=ctx,
            )

    def _discard_spec_ring(self):
        """Drop every in-flight speculative round (commit failure, duplicate
        slots, naive-copy invalidation): their conditioning presumed a
        registration set that did not hold, so none may be consumed."""
        while self._spec_ring:
            _handle, _algo, t0, ctx = self._spec_ring.popleft()
            self._close_entry_window(t0, ctx, "discarded")

    def _dispatch_speculative(self, pool_size, registered_trials):
        """Top the speculative ring up to ``pipeline_depth`` in-flight
        rounds before this round's trials execute (VERDICT r2 #3: the
        small-batch presets were pinned to one blocking ~100ms
        host<->device round trip per round; ISSUE 13 generalizes the
        single slot to a depth-N ring).

        Only algorithms declaring ``speculation_safe`` are speculated.
        Observation-independent algorithms (random search) declare it by
        class — zero regret cost by construction, and dispatching N rounds
        ahead consumes the SAME rng/cursor stream the synchronous path
        would, in the same order (rounds are finalized oldest-first), so
        any depth is bit-identical to depth 1.  Model-based algorithms opt
        in (`speculative_suggest=True`, async-BO semantics): the naive
        copy first observes constant-liar lies for the just-registered
        batch, so the in-flight round is conditioned like an async
        worker's round would be — and such algorithms are CAPPED at an
        effective depth of 1 (_effective_pipeline_depth): N dispatches
        from one posterior would violate that conditioning contract and
        re-sample the same optimum N times.  Lie conditioning happens
        ONCE per registered batch (``_spec_conditioned``).
        jax's async dispatch runs the computations and transfers while the
        host executes trials; successive produce() calls drain the ring.

        Returns True when at least one speculative round is in flight
        after the call — the pipelined commit path uses this to know the
        storage write it is about to issue overlaps live device work."""
        algo = self.naive_algorithm
        if algo is None or not getattr(algo, "speculation_safe", False):
            # A non-speculative algorithm must never leave stale handles
            # behind (the pre-ring code reset its slot unconditionally).
            self._discard_spec_ring()
            return False
        t_dispatch = time.perf_counter() if TELEMETRY.enabled else None
        dispatched = 0
        try:
            # Condition each trial onto this naive copy AT MOST ONCE: the
            # pipelined commit may re-invoke this on the same instance
            # (mid-loop dispatch opted out, post-loop retry), and
            # re-observing the same lies would double-count fantasies for
            # opt-in model-based speculation.  The set resets with every
            # naive rebuild (_update_naive_algorithm).
            # lint: disable=PERF001 -- plugin-compat boundary: the lie
            # strategy and register_suggestion hooks are per-point APIs;
            # this path only runs for speculation-safe algorithms.
            fresh = [
                t for t in registered_trials
                if t.id not in self._spec_conditioned
            ]
            if fresh:
                # The dispatch copy predates this round's registrations (it
                # was deepcopied in update()): mark the just-registered
                # points consumed on IT too, or cursor-based algorithms
                # (grid) would speculatively re-suggest the exact batch just
                # written and pay a round of DuplicateKeyError + backoff.
                for trial in fresh:
                    if self._needs_register_suggestion:
                        algo.register_suggestion(trial.params)
                    self._spec_conditioned.add(trial.id)
                lie_trials, lie_results = [], []
                for trial in fresh:
                    lie = self.strategy.lie(trial)
                    if lie is not None and lie.value is not None:
                        lie_trials.append(trial)
                        lie_results.append({"objective": lie.value})
                if lie_trials:
                    lie_params = [dict(t.params) for t in lie_trials]
                    lie_cube = self._cube_rows_for(lie_trials)
                    if lie_cube is not None:
                        algo.observe(lie_params, lie_results, cube=lie_cube)
                    else:  # pre-columnar plugin signature
                        algo.observe(lie_params, lie_results)
            depth = self._effective_pipeline_depth(algo)
            while len(self._spec_ring) < depth:
                t0 = time.perf_counter() if TELEMETRY.enabled else None
                handle = algo.dispatch_suggest(pool_size)
                if handle is None:
                    break
                ctx = current_trace_context() if t0 is not None else None
                self._spec_ring.append((handle, algo, t0, ctx))
                dispatched += 1
        except Exception:  # pragma: no cover - speculation must never break a run
            log.debug("speculative dispatch failed", exc_info=True)
            return bool(self._spec_ring)
        if t_dispatch is not None:
            # Host-side cost of conditioning + async dispatch (the span the
            # issue calls ``speculative_dispatch``); the device-work windows
            # are the per-entry open ``device.dispatch`` spans above.
            TELEMETRY.record_span(
                "producer.speculative_dispatch",
                start=t_dispatch,
                args={"dispatched": dispatched},
            )
        if dispatched:
            # Keep the real algo's rng stream ahead of the speculative
            # draws, or the next naive copy would replay the same keys and
            # duplicate them.
            self.algorithm.rng_key = algo.rng_key
        return bool(self._spec_ring)

    def _take_speculative(self, pool_size):
        if not self._spec_ring:
            return None
        handle, algo, t0, ctx = self._spec_ring.popleft()
        try:
            t_fin = time.perf_counter()
            with TELEMETRY.span("producer.suggest", args={"count": pool_size}):
                out = algo.finalize_suggest_batch(handle).params[:pool_size]
            # Timed as "suggest": what remains of the device round trip
            # after the overlap (ideally just the residual transfer).
            self._record_timing("suggest", time.perf_counter() - t_fin, len(out))
            self._close_entry_window(t0, ctx, "finalized")
            return out
        except Exception:  # pragma: no cover - speculation must never break a run
            log.debug("speculative finalize failed", exc_info=True)
            self._close_entry_window(t0, ctx, "failed")
            # Later entries share the failed handle's lineage (same naive
            # copy, same device stream) — discard rather than trust them.
            self._discard_spec_ring()
            return None

    def backoff(self):
        """Re-sync with storage + jittered sleep (reference `producer.py:61-67`)."""
        self.update()
        self._sleep_backoff()

    def _sleep_backoff(self):
        # The unified backoff policy (storage/retry.py): exponential from
        # 10ms, capped at the same 0.5s ceiling the old gaussian sleep
        # had, jittered so concurrent producers de-synchronize.
        self._backoff_policy.sleep(
            self.failure_count, op="producer.backoff", span="producer.backoff"
        )
        self.failure_count += 1


def _trial_results(trial):
    out = {"objective": trial.objective.value if trial.objective else None}
    if trial.gradient is not None:
        out["gradient"] = trial.gradient.value
    if trial.constraints:
        out["constraint"] = [c.value for c in trial.constraints]
    return out
