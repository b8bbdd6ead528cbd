"""TPU-native batched Bayesian optimizer — the framework's flagship.

No reference counterpart (Oríon v0.1.7 has only random search + ASHA); this
implements BASELINE.json's north star: `suggest`/`observe` as jitted batched
device code — GP posterior via masked Cholesky on power-of-2 padded buffers,
acquisition (Thompson/EI/UCB) vmapped over thousands of candidates, q-batch
selection in a single compiled call, optionally sharded across a device mesh
(`orion_tpu.parallel`).

The producer's lie fantasization (constant-liar strategies) composes on top:
lies arrive through `observe` like real results, which is exactly the
fantasize-don't-refit design SURVEY.md §7 calls for — the naive-algo copy
refits its posterior with fantasy rows instead of waiting on stragglers.
"""

import contextlib
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from orion_tpu.compiler_plane import (
    COMPILE_REGISTRY,
    fields_from_plan_signature,
    lowered_analysis_fn,
    signature_fields,
)
from orion_tpu.telemetry import TELEMETRY

from orion_tpu.algo.base import BaseAlgorithm, algo_registry
from orion_tpu.algo.gp.acquisition import (
    acquire,
    expected_improvement,
    joint_thompson,
    select_q,
)
from orion_tpu.algo.gp.gp import GPHypers, fit_gp, init_hypers, posterior_norm
from orion_tpu.algo.history import (
    DeviceHistory,
    HostHistory,
    _next_pow2,
    prewarm_local_subset,
)
from orion_tpu.algo.prewarm import (
    DEFAULT_PREWARM_FILL,
    BucketPrewarmer,
    completed_prewarm_count,
    plan_fused_step_bucket,
    plan_next_bucket,
)
from orion_tpu.algo.sampling import clamp_objectives, reflect_unit
from orion_tpu.algo.sharding import mesh_health_fields
from orion_tpu.parallel import candidate_sharding, device_mesh, replicated


class WarmStart(NamedTuple):
    """Restored GP warm-start carrier: quacks like the slice of GPState the
    suggest path reads before the first post-restore fit lands (``hypers``
    for the refit init; ``mll``/``health`` absent)."""

    hypers: "GPHypers"
    mll: None = None
    health: None = None


def copula_transform(y):
    """Rank -> normal quantile on host (monotone: argmin preserved).

    The HOT path no longer runs this: the fused suggest step applies the
    same transform on device (``sampling.masked_copula_transform``, routed
    through ``fit_gp(y_transform="copula")``), so the full-history y is
    never re-ranked on host or re-uploaded per round.  This host twin
    remains the parity reference (``tests/unit/test_copula_device.py``
    pins device == host within float32 tolerance) and the entry point for
    host-side consumers.  The inner sort is ``kind="stable"`` so duplicate
    objectives get first-occurrence ranks — the tie order jax's (stable)
    ``argsort`` uses on device."""
    from scipy.special import ndtri

    order = np.argsort(np.argsort(y, kind="stable"))
    return ndtri((order + 0.5) / y.shape[0]).astype(np.float32)


def local_subset_indices(x, center, m):
    """Indices of the m nearest rows to ``center`` (local-GP selection).

    Host reference implementation; the algorithms now gather the subset on
    device (``DeviceHistory.local_view``) so the fit set never crosses the
    host boundary."""
    d2 = ((x - center[None, :]) ** 2).sum(axis=1)
    return np.argpartition(d2, m)[:m]


def tr_update(length, succ, fail, improved, *, succ_tol, fail_tol,
              length_init, length_min, length_max):
    """One trust-region bookkeeping step (TuRBO schedule), shared by every
    algorithm hosting a box: expand after ``succ_tol`` consecutive improving
    rounds, halve after ``fail_tol`` stagnating ones, restart wide on
    collapse (history is kept — only the box resets).  Returns
    ``(length, succ, fail, restarted)``."""
    if improved:
        succ, fail = succ + 1, 0
    else:
        succ, fail = 0, fail + 1
    if succ >= succ_tol:
        length, succ = min(2.0 * length, length_max), 0
    elif fail >= fail_tol:
        length, fail = length / 2.0, 0
    restarted = length < length_min
    if restarted:
        length, succ, fail = length_init, 0, 0
    return length, succ, fail, restarted


def tr_update_batch(length, succ, fail, prev_best, objectives, *, chunk,
                    succ_tol, fail_tol, length_init, length_min, length_max,
                    improve_tol):
    """Run the TuRBO schedule over ONE observe round, splitting a batch
    larger than ``chunk`` into sequential sub-rounds (arrival order, running
    incumbent).

    The schedule's unit of evidence is a *round* of samples from the current
    box — but its cadence must not be coupled to the caller's batch size
    (VERDICT r4 weak #2): at q=256 a once-per-round update gives the box 4
    adaptations over a 1024-trial run, vs the 128 that made batch-8 TuRBO
    match CMA-ES on rosenbrock20.  Sub-rounds approximate the small-batch
    schedule — later chunks came from the same (not-yet-shrunk) box, so the
    box only lacks the per-chunk *sampling* feedback, not the success/failure
    signal.  Batches ≤ ``chunk`` keep the exact one-update-per-round
    behavior."""
    y = np.asarray(objectives, dtype=np.float64).ravel()
    best = float(prev_best)
    n_restarts = 0
    for i in range(0, y.shape[0], chunk):
        chunk_best = float(np.min(y[i : i + chunk]))
        improved = chunk_best < best - improve_tol * abs(best)
        length, succ, fail, restarted = tr_update(
            length, succ, fail, improved,
            succ_tol=succ_tol, fail_tol=fail_tol, length_init=length_init,
            length_min=length_min, length_max=length_max,
        )
        n_restarts += restarted
        best = min(best, chunk_best)
    return length, succ, fail, n_restarts


@algo_registry.register("tpu_bo")
class TPUBO(BaseAlgorithm):
    """Batched GP-BO on device.

    Parameters
    ----------
    n_init: random (prior) points before the GP engages.
    n_candidates: candidate-set size per suggest call (split between global
        uniform exploration and gaussian perturbations around incumbents).
    acq: "thompson" (default; diverse q-batches), "joint_thompson", "ei", "ucb".
    kernel: "matern52" (default) or "rbf".
    fit_steps: adam steps on the marginal likelihood for the FIRST fit.
    refit_steps: steps for warm-started refits (default: fit_steps).  Each
        round resumes from the previous round's hyperparameters, so fewer
        refit steps are viable where GP fitting dominates the round.
    local_frac: fraction of candidates drawn around the current best point.
    y_transform: "copula" (default) rank-Gaussianizes objectives before the
        GP fit (ranks mapped through the normal quantile function).
        Monotone, so acquisition order is preserved — but the GP sees a
        unit-scale, outlier-free target even when raw objectives span
        orders of magnitude (Rosenbrock-class landscapes), which is exactly
        where raw-y GPs go blind: the valley floor normalizes to one flat
        value and every gradient signal lives in the first percentile.
        "none" fits raw objectives (useful when their scale itself is the
        signal, e.g. already-standardized targets).
    trust_region: TuRBO-style local BO (Eriksson et al. 2019), ON by
        default — measured on the chip it is what keeps the default config
        robust on ill-conditioned landscapes (rosenbrock20 regret ~700-1100
        vs 1.3e4 for the global-candidate scheme, VERDICT r3 weak #2) while
        holding Hartmann6 parity (0.129-0.143 over 3 seeds, anchor 0.187).
        The trust box starts at most of the cube (0.8) and expands to
        super-global (1.6) while improving, so easy landscapes degrade
        gracefully to near-global search.  The local
        candidate fraction is drawn from a box around the incumbent whose
        per-dimension side lengths follow the fitted GP lengthscales; the
        box expands after ``tr_succ_tol`` consecutive improving rounds,
        halves after ``tr_fail_tol`` stagnating ones, and restarts at
        ``tr_length_init`` when it collapses below ``tr_length_min``.  This
        is what lets the GP concentrate samples inside high-D curved
        valleys (Rosenbrock-class landscapes) where a global-uniform +
        fixed-sigma-ball scheme plateaus.
    prewarm: background-compile the next pow-2 history bucket's fused
        suggest step before the history crosses the boundary, so mid-run
        bucket growth costs a jit-cache hit instead of a synchronous
        multi-second compile stall (docs/performance.md, "The
        zero-reupload round").  ``prewarm_fill`` is the bucket-fill
        fraction that triggers the compile (default 0.75).
    tr_update_every: the box adaptation cadence in *observations*, not
        rounds — an observe round larger than this is split into
        sequential sub-rounds for the TuRBO schedule (tr_update_batch),
        so q=256 users get ~32 adaptations per round instead of 1 and the
        default config stays robust at any batch size.
    n_devices: shard candidates over this many devices (None = all visible).
    """

    supports_async_suggest = True

    def __init__(
        self,
        space,
        seed=None,
        n_init=16,
        n_candidates=8192,
        acq="thompson",
        kernel="matern52",
        fit_steps=50,
        refit_steps=None,
        beta=2.0,
        local_frac=0.5,
        local_sigma=0.1,
        y_transform="copula",
        trust_region=True,
        tr_length_init=0.8,
        tr_length_min=0.5**7,
        tr_length_max=1.6,
        tr_succ_tol=3,
        tr_fail_tol=4,
        tr_improve_tol=1e-3,
        tr_local_m=256,
        tr_perturb_dims=20,
        tr_update_every=8,
        speculative_suggest=False,
        prewarm=True,
        prewarm_fill=DEFAULT_PREWARM_FILL,
        n_devices=None,
        use_mesh=False,
    ):
        super().__init__(
            space,
            seed=seed,
            n_init=n_init,
            n_candidates=n_candidates,
            acq=acq,
            kernel=kernel,
            fit_steps=fit_steps,
            refit_steps=refit_steps,
            beta=beta,
            local_frac=local_frac,
            local_sigma=local_sigma,
            y_transform=y_transform,
            trust_region=trust_region,
            tr_length_init=tr_length_init,
            tr_length_min=tr_length_min,
            tr_length_max=tr_length_max,
            tr_succ_tol=tr_succ_tol,
            tr_fail_tol=tr_fail_tol,
            tr_improve_tol=tr_improve_tol,
            tr_local_m=tr_local_m,
            tr_perturb_dims=tr_perturb_dims,
            tr_update_every=tr_update_every,
            speculative_suggest=speculative_suggest,
            prewarm=prewarm,
            prewarm_fill=prewarm_fill,
        )
        self.n_init = n_init
        self.n_candidates = n_candidates
        self.acq = acq
        self.kernel = kernel
        self.fit_steps = fit_steps
        # None = warm refits also use fit_steps (run_suggest_step owns the
        # default); opt in where GP fitting genuinely dominates the round.
        self.refit_steps = refit_steps
        self.beta = beta
        self.local_frac = local_frac
        self.local_sigma = local_sigma
        self.y_transform = y_transform
        self.trust_region = trust_region
        self.tr_length_init = tr_length_init
        self.tr_length_min = tr_length_min
        self.tr_length_max = tr_length_max
        self.tr_succ_tol = tr_succ_tol
        self.tr_fail_tol = tr_fail_tol
        self.tr_improve_tol = tr_improve_tol
        self.tr_local_m = tr_local_m
        self.tr_perturb_dims = tr_perturb_dims
        self.tr_update_every = tr_update_every
        # Opt-in async-BO semantics: let the producer dispatch next round's
        # suggest conditioned on constant-liar fantasies for the in-flight
        # batch.  Hides the device round trip behind trial execution, at the
        # one-round-stale conditioning cost every async multi-worker setup
        # already accepts (measured on Hartmann6: regret 0.13 -> 0.21).
        self.speculation_safe = bool(speculative_suggest)
        self.prewarm = bool(prewarm)
        self.prewarm_fill = float(prewarm_fill)
        self.use_mesh = use_mesh
        self._mesh = device_mesh(n_devices) if use_mesh else None
        d = space.n_cols
        # Host history: amortized-growth capped buffers with O(batch)
        # appends and an incrementally-tracked incumbent — the old
        # np.concatenate mirrors cost O(n) host work per observe.  Only
        # bookkeeping that genuinely needs host floats reads it
        # (trust-region schedule, restart-center scans, state_dict).
        self._host = HostHistory(d)
        # Device-resident twin: incrementally appended on observe so the
        # suggest path never re-uploads rows the device already holds
        # (docs/algorithms.md, "Device-resident history").  The copula
        # y-transform and local-subset selection run on these buffers
        # in-jit, so a steady-state round's upload is O(batch) rows.
        self._hist = DeviceHistory(d)
        self._gp_state = None
        # Shape-bucket AOT prewarm: compiles the next pow-2 bucket's fused
        # step on a background thread before the history crosses the
        # boundary (docs/performance.md, "The zero-reupload round").
        self._prewarmer = BucketPrewarmer()
        self._last_q_bucket = None
        self._tr_length = tr_length_init
        self._tr_succ = 0
        self._tr_fail = 0
        # Fresh-restart override: row index the trust box centers on after a
        # collapse with no progress (None = the global incumbent).
        self._tr_center = None
        # Steady-path dispatch prep (docs/performance.md, "Attributing the
        # round"): the statics part of `_step_kw` and the resolved
        # _PlanPrep ride per-instance caches so a steady round skips the
        # dict rebuild and the 16-tuple prep-key probe entirely.
        self._step_kw_cache = None
        self._prep_token = PlanPrepToken()
        # Fit steps of the last dispatched round, held while telemetry is on
        # until the host holds its rows (_materialize_batch counts them).
        self._fit_steps_uncounted = None

    # Naive-copy sharing (base __deepcopy__): the mesh handle and the
    # prewarmer's threads/locks are not copyable (and the jit cache they
    # warm is process-wide — one warm covers every clone); the fitted GP
    # state is immutable-by-rebinding.  `_hist` and `_host` are
    # deliberately NOT here: their own __deepcopy__ implements
    # copy-on-write sharing of the buffers (a plain by-ref share would let
    # the clone's in-place appends clobber the real algorithm's history).
    # `_step_kw_cache` (never mutated after build) and `_prep_token`
    # (atomic-by-rebinding pinned pair) ARE shared: a naive clone prepares
    # the same signatures, so it should ride the same warm caches — and a
    # deepcopy of either would walk the mesh handle / device scalars.
    _share_by_ref = (
        "space", "_mesh", "_gp_state", "_prewarmer",
        "_step_kw_cache", "_prep_token",
    )

    # Back-compat views of the observation history (tests and host-side
    # consumers read these; appends go through `_host`).
    @property
    def _x(self):
        return self._host.x

    @property
    def _y(self):
        return self._host.y

    # --- observation --------------------------------------------------------
    def observe_arrays(self, cube, objectives, params_list=None, fidelities=None):
        with TELEMETRY.span("algo.encode"):
            objectives = clamp_objectives(objectives, self._y)
            if objectives is None:
                return
            rows32 = np.asarray(cube, dtype=np.float32)
            y32 = np.asarray(objectives, dtype=np.float32)
        prev_n = self._host.count
        prev_best = self._host.best_y  # O(1): tracked incrementally
        # O(batch) host append + O(batch) device append: only the new rows
        # cross the boundary, and no O(n) concatenate/argmin runs on host.
        with TELEMETRY.span("algo.append"):
            self._host.append(rows32, y32)
            self._hist.append(rows32, y32)
        with TELEMETRY.span("algo.prewarm"):
            self._maybe_prewarm(batch=y32.shape[0])
        # Trust-region bookkeeping counts MODEL rounds only: observations of
        # the random init phase say nothing about the local model's quality.
        if self.trust_region and prev_n >= self.n_init:
            with TELEMETRY.span("algo.trust_region"):
                # Decoupled from batch size: a big observe round is split into
                # tr_update_every-sized sub-rounds (see tr_update_batch) so the
                # box gets the same adaptation count a small-batch run would.
                (self._tr_length, self._tr_succ, self._tr_fail,
                 n_restarts) = tr_update_batch(
                    self._tr_length, self._tr_succ, self._tr_fail,
                    prev_best, objectives, chunk=self.tr_update_every,
                    succ_tol=self.tr_succ_tol, fail_tol=self.tr_fail_tol,
                    length_init=self.tr_length_init,
                    length_min=self.tr_length_min,
                    length_max=self.tr_length_max,
                    improve_tol=self.tr_improve_tol,
                )
                new_best = self._host.best_y
                if new_best < prev_best - self.tr_improve_tol * abs(prev_best):
                    # Progress: the box belongs back on the true incumbent.
                    self._tr_center = None
                elif n_restarts:
                    # Collapse without progress: re-centering the fresh box on
                    # the SAME stuck incumbent replays the failed search (the
                    # round-4 tail diagnosis — the worst seed's box cycled
                    # 0.4 -> 0.0125 -> restart four times around one point).
                    # Restart around the best observation that is at least an
                    # average-distance/4 away instead.
                    self._tr_center = self._fresh_restart_center()

    def _fresh_restart_center(self):
        """Index of the best observation usefully FAR from the incumbent
        (>= a quarter of the mean distance to it); None when nothing
        qualifies (early runs whose points all cluster).  The O(n) distance
        scan only runs on a box collapse without progress — a rare event,
        not steady-state observe cost."""
        best_idx = self._host.best_idx
        d = np.sqrt(((self._x - self._x[best_idx]) ** 2).sum(axis=1))
        far = d >= max(float(d.mean()) / 4.0, 1e-6)
        if not far.any():
            return None
        candidates = np.where(far)[0]
        return int(candidates[np.argmin(self._y[candidates])])

    # --- suggestion ---------------------------------------------------------
    def _step_kw(self):
        return dict(
            n_candidates=self.n_candidates,
            kernel=self.kernel,
            acq=self.acq,
            fit_steps=self.fit_steps,
            refit_steps=self.refit_steps,
            local_frac=self.local_frac,
            local_sigma=self.local_sigma,
            beta=self.beta,
            trust_region=self.trust_region,
            tr_length=self._tr_length,
            tr_perturb_dims=self.tr_perturb_dims,
            y_transform=self.y_transform,
            mesh=self._mesh,
        )

    def _maybe_prewarm(self, batch=0):
        maybe_prewarm_fused_step(self, batch=batch)

    def fused_step_plan(self, num):
        """This round's fused suggest step as a :class:`FusedPlan`, or None
        while the random-init phase is still running (nothing fused to
        dispatch).  The plan is CONSUMING: it advances the RNG stream and
        stamps the q bucket exactly as a direct suggest would, so a caller
        holding a plan MUST run it (standalone via :func:`run_fused_plan`,
        or stacked with other tenants' same-signature plans through the
        serve gateway's coalescer) and feed the resulting GPState back via
        :meth:`consume_fused_step` — which is precisely what
        ``_suggest_cube`` does.  One prep path for both the standalone and
        the coalesced dispatch is what makes them bit-identical."""
        n = self._host.count
        if n < self.n_init:
            return None
        with TELEMETRY.span("algo.plan"):
            self._last_q_bucket = _next_pow2(num, floor=8)
            center_idx = (
                self._tr_center
                if self._tr_center is not None and self._tr_center < n
                else self._host.best_idx  # O(1): tracked incrementally
            )
            best_x = self._host.x[center_idx]
            step_kw = self._step_kw_cache
            if step_kw is None:
                # tr_length is the per-round traced input (passed explicitly
                # below); every other `_step_kw` entry is frozen at __init__,
                # so the dict rides the instance and is never mutated after
                # build (shared by ref with naive clones).
                step_kw = dict(self._step_kw())
                step_kw.pop("tr_length", None)
                self._step_kw_cache = step_kw
            if self.trust_region and n > self.tr_local_m:
                # LOCAL GP (the TuRBO design): fit only the tr_local_m nearest
                # observations to the incumbent.  A global fit has to average
                # lengthscales over the whole landscape, washing out exactly the
                # local structure the trust region is trying to exploit — and a
                # 4x smaller buffer makes the per-round Cholesky ~64x cheaper.
                # The subset is gathered ON DEVICE from the resident buffers
                # (masked top_k, DeviceHistory.local_view): no O(n·d) host
                # distance scan, no host gather, no upload — only the center
                # row crosses the boundary.
                x_dev, y_dev, mask_dev, _ = self._hist.local_view(
                    best_x, self.tr_local_m
                )
            else:
                # Full-history fast path: the fit set IS the full history,
                # which already lives on device — no O(n) re-pad or re-upload.
                # The copula transform (whose ranks change globally with every
                # new observation) runs in-jit over the masked device y, so
                # nothing history-sized crosses the boundary here either.
                x_dev, y_dev, mask_dev, _ = self._hist.fit_view()
            plan = make_fused_plan(
                self.next_key(), x_dev, y_dev, mask_dev, best_x,
                self._gp_state, num, tr_length=self._tr_length,
                prep_token=self._prep_token, **step_kw,
            )
            if TELEMETRY.enabled:
                self._fit_steps_uncounted = plan.statics["fit_steps"]
            return plan

    def consume_fused_step(self, state):
        """Accept the GPState a fused-plan dispatch produced (warm-start
        source for the next round's fit + packed device health)."""
        self._gp_state = state

    def _suggest_cube(self, num):
        plan = self.fused_step_plan(num)
        if plan is None:
            return jax.random.uniform(self.next_key(), (num, self.space.n_cols))
        # Single fused jit call: warm-started GP refit + on-device copula
        # y-transform + candidate generation + acquisition + on-device
        # dedup/EI-fill + gather.  One dispatch and one (q, d) transfer per
        # suggest — dispatch latency otherwise dominates (each host->device
        # round trip costs ~ms).  With a mesh, the same compiled step
        # shards the candidate axis over it (SPMD collectives inserted by
        # XLA, see orion_tpu.parallel).
        rows, state = run_fused_plan(plan, prewarmer=self._prewarmer)
        self.consume_fused_step(state)
        return rows

    def _materialize_batch(self, cube, num=None):
        batch = super()._materialize_batch(cube, num)
        steps, self._fit_steps_uncounted = self._fit_steps_uncounted, None
        if steps is not None and TELEMETRY.enabled:
            # The rows are on the host, so the step (and its fit) is done:
            # reading the fit's count transfers ready data, it does not sync.
            TELEMETRY.count("gp.fit.steps", steps)
            TELEMETRY.count(
                "gp.fit.nonfinite_steps", int(self._gp_state.fit_nonfinite)
            )
        return batch

    # --- health -------------------------------------------------------------
    def health_record(self):
        """Per-round optimization health (orion_tpu.health): incumbent +
        trust-region box from the host trackers (all O(1) reads), GP fit /
        acquisition / dedup fields unpacked from the packed device vector
        the last fused step attached to its GPState (already computed —
        reading it transfers ready data, it does not sync the device)."""
        from orion_tpu.health import unpack_device_health

        record = {
            "algo": type(self).__name__.lower(),
            "n_obs": int(self._host.count),
            "tr_length": float(self._tr_length),
            "tr_succ": int(self._tr_succ),
            "tr_fail": int(self._tr_fail),
        }
        if self._host.count:
            record["best_y"] = float(self._host.best_y)
        if self._mesh is not None:
            # serve_width-style placement fields: device count always;
            # measured per-device byte fractions once a fused round has
            # produced sharded state to read placement from (metadata-only,
            # no transfers — see sharding.placement_fractions).
            sample = () if self._gp_state is None else (self._gp_state.chol,)
            record.update(mesh_health_fields(self._mesh, *sample))
        state = self._gp_state
        if state is not None and state.health is not None:
            record.update(unpack_device_health(state.health))
        return record

    # --- state --------------------------------------------------------------
    def state_dict(self):
        out = super().state_dict()
        out["x"] = self._x.tolist()
        out["y"] = self._y.tolist()
        out["tr"] = [self._tr_length, self._tr_succ, self._tr_fail]
        out["tr_center"] = self._tr_center
        # GP warm start: the fitted hyperparameters the next round's refit
        # resumes from.  Without them a restored instance cold-fits from
        # init_hypers and the suggestion stream FORKS at the restore point
        # — the serve gateway's --persist restart pins bit-identical
        # continuation on exactly this field (tests/unit/test_serve.py).
        if self._gp_state is not None:
            hypers = self._gp_state.hypers
            out["gp_hypers"] = [
                np.asarray(hypers.log_lengthscales).tolist(),
                float(hypers.log_amplitude),
                float(hypers.log_noise),
            ]
        return out

    def set_state(self, state):
        super().set_state(state)
        d = self.space.n_cols
        x = np.asarray(state["x"], dtype=np.float32).reshape(-1, d)
        y = np.asarray(state["y"], dtype=np.float32)
        # Rebuild the host buffers (incumbent tracking resumes) and the
        # device-resident twin with ONE bulk upload; incremental appends
        # resume from here.
        self._host = HostHistory.from_host(x, y)
        self._hist = DeviceHistory.from_host(x, y)
        saved = state.get("gp_hypers")
        if saved is not None:
            # Warm-restart shim: only .hypers feeds the next fused plan
            # (the fit rebuilds chol/alpha on device); .health/.mll absent
            # until the first restored round replaces this with a full
            # GPState via consume_fused_step.
            self._gp_state = WarmStart(
                hypers=GPHypers(
                    log_lengthscales=jnp.asarray(saved[0], jnp.float32),
                    log_amplitude=jnp.asarray(saved[1], jnp.float32),
                    log_noise=jnp.asarray(saved[2], jnp.float32),
                )
            )
        else:
            self._gp_state = None  # refit (cold) on the next suggest
        tr = state.get("tr")
        if tr is not None:
            self._tr_length, self._tr_succ, self._tr_fail = tr[0], int(tr[1]), int(tr[2])
        center = state.get("tr_center")
        self._tr_center = int(center) if center is not None else None


@algo_registry.register("turbo")
class TuRBO(TPUBO):
    """Trust-region GP-BO: :class:`TPUBO` with TuRBO candidate generation on
    by default and a 90/10 local/global candidate split.  Same fused-jit
    suggest step, same public API — only the candidate scheme and its
    host-side box bookkeeping differ."""

    def __init__(self, space, seed=None, **kwargs):
        kwargs.setdefault("trust_region", True)
        kwargs.setdefault("local_frac", 0.9)
        kwargs.setdefault("y_transform", "copula")
        super().__init__(space, seed=seed, **kwargs)


@partial(jax.jit, static_argnums=(1, 2, 4))
def _make_candidates(key, n_candidates, n_dims, best_x, local_frac, local_sigma):
    """Candidate set: global uniform + gaussian ball around the incumbent.

    Boundary handling is reflection, not clipping — clipping would pile local
    candidates onto the exact floats 0.0/1.0 whenever the incumbent sits near
    an edge, producing duplicate suggestions (see sampling.reflect_unit)."""
    k1, k2 = jax.random.split(key)
    n_local = int(n_candidates * local_frac)
    n_global = n_candidates - n_local
    global_c = jax.random.uniform(k1, (n_global, n_dims))
    local_c = best_x[None, :] + local_sigma * jax.random.normal(k2, (n_local, n_dims))
    return jnp.concatenate([global_c, reflect_unit(local_c)], axis=0)


def _topk_cov_chol(x, y, mask, n_dims, k=64):
    """Cholesky factor of the covariance of the k best observed points.

    The elite set's spread tracks the local geometry of the descent (a
    curved valley stretches it along the valley's direction), giving a
    ROTATED sampling distribution that an axis-aligned trust box cannot
    express — the same signal CMA-ES distills into its covariance, read
    directly off the history instead of adapted generation by generation."""
    y_sorted_idx = jnp.argsort(jnp.where(mask > 0, y, jnp.inf))
    elite = jnp.take(x, y_sorted_idx[:k], axis=0)
    # CMA-style log weights: best points dominate the estimate.  Padded
    # buffer rows sort last but can still land inside the top k when fewer
    # than k real observations exist — zero their weight or the (0,...,0)
    # padding rows drag mu toward the origin and the covariance toward the
    # padding geometry.
    w = jnp.log(k + 0.5) - jnp.log(jnp.arange(1, k + 1, dtype=x.dtype))
    w = w * jnp.take(mask, y_sorted_idx[:k])
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    mu = jnp.sum(elite * w[:, None], axis=0)
    centered = elite - mu[None, :]
    cov = (centered * w[:, None]).T @ centered
    # Ridge: elite sets collapsed to a subspace (or duplicates) must still
    # factorize; 1e-6 in cube units is far below any useful step.
    chol = jnp.linalg.cholesky(cov + 1e-6 * jnp.eye(n_dims, dtype=x.dtype))
    return chol, mu


def _tr_box(center, tr_length, lengthscales):
    """Trust-box bounds: per-dimension half-widths follow the GP
    lengthscales normalized to geometric mean 1, clipped to the cube."""
    scale = lengthscales / jnp.exp(jnp.mean(jnp.log(lengthscales)))
    half = 0.5 * tr_length * scale
    lb = jnp.clip(center - half, 0.0, 1.0)
    ub = jnp.clip(center + half, 0.0, 1.0)
    return lb, ub


def _polish_candidates(
    state, kernel, starts, lb, ub, n_steps=30, lr=0.02, fixed_tail_cols=0
):
    """Multi-start adam descent on the GP posterior mean, box-clipped every
    step — in-jit acquisition optimization.  Random candidates locate the
    posterior's basins; 30 gradient steps walk the floor of the basin, which
    random sampling cannot hit in high D.  The polished points join the
    candidate pool; acquisition still chooses the batch, so this sharpens
    exploitation without giving up Thompson's batch diversity."""
    import optax

    def mean_of(x_free):
        x_full = x_free
        if fixed_tail_cols:
            x_full = jnp.concatenate(
                [x_free, jnp.ones((fixed_tail_cols,), x_free.dtype)]
            )
        m, _ = posterior_norm(state, x_full[None, :], kind=kernel)
        return m[0]

    grad_fn = jax.grad(mean_of)
    opt = optax.adam(lr)

    def run_one(x0):
        def step(carry, _):
            x_cur, opt_state = carry
            g = jnp.nan_to_num(grad_fn(x_cur))
            updates, opt_state = opt.update(g, opt_state)
            x_cur = jnp.clip(optax.apply_updates(x_cur, updates), lb, ub)
            return (x_cur, opt_state), None

        (x_fin, _), _ = jax.lax.scan(step, (x0, opt.init(x0)), None, length=n_steps)
        return x_fin

    return jax.vmap(run_one)(starts)


def _make_tr_candidates(
    key, n_candidates, n_dims, center, tr_length, lengthscales, local_frac,
    cov_chol, elite_mu, perturb_dims=20,
):
    """TuRBO-style candidates: the local fraction split between the trust
    box and elite-covariance gaussian steps, the remainder global uniform
    (restart-free exploration floor).

    The box's per-dimension half-widths follow the fitted GP lengthscales
    normalized to geometric mean 1 (long-lengthscale = flat direction = wide
    box side), clipped to the unit cube.  Each box candidate perturbs a
    random ~min(20, d)-dim subset of coordinates and inherits the incumbent
    elsewhere — in high D, moving every coordinate at once almost surely
    leaves the valley (TuRBO's perturbation mask).  The covariance source
    samples ``center + L_elite z`` — rotated steps along the elite set's
    principal directions (see _topk_cov_chol), which is what actually walks
    curved valleys.  Traced on ``tr_length``/``cov_chol`` so box resizing
    and covariance updates never recompile."""
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    n_local = int(n_candidates * local_frac)
    n_cov = n_local // 6
    n_dir = n_local // 6
    n_cem = n_local // 6
    n_box = n_local - n_cov - n_dir - n_cem
    n_global = n_candidates - n_local
    lb, ub = _tr_box(center, tr_length, lengthscales)
    u = jax.random.uniform(k1, (n_box, n_dims))
    box = lb[None, :] + u * (ub - lb)[None, :]
    p_perturb = min(1.0, perturb_dims / n_dims)
    if p_perturb < 1.0:
        mask = jax.random.bernoulli(k2, p_perturb, (n_box, n_dims))
        # Guarantee at least one perturbed coordinate per candidate.
        forced = (
            jax.nn.one_hot(
                jax.random.randint(k3, (n_box,), 0, n_dims), n_dims
            )
            > 0
        )
        mask = jnp.where(jnp.any(mask, axis=1, keepdims=True), mask, forced)
        box = jnp.where(mask, box, center[None, :])
    z = jax.random.normal(k4, (n_cov, n_dims))
    # Half unit-scale steps, half double — the elite spread lags the true
    # local scale while the search is still descending.
    sigma = jnp.where(jnp.arange(n_cov)[:, None] % 2 == 0, 1.0, 2.0)
    cov_c = reflect_unit(center[None, :] + sigma * (z @ cov_chol.T))
    # Directional extrapolation: the elite mean trails the incumbent while
    # the search descends, so (center - mu) spans the descent path — step
    # at assorted magnitudes BOTH ways (t symmetric: valley landscapes
    # reward pushing past the incumbent, basin landscapes reward stepping
    # back toward the elite mean; acquisition judges which) with a little
    # covariance-shaped noise (the momentum CMA-ES gets from moving its
    # recombination mean).
    t = jax.random.normal(k5, (n_dir, 1)) * 2.0
    zd = jax.random.normal(k6, (n_dir, n_dims))
    dir_c = reflect_unit(
        center[None, :] + t * (center - elite_mu)[None, :] + 0.5 * (zd @ cov_chol.T)
    )
    # CEM-style recombination: samples around the elite MEAN.  Averaging the
    # top-k concentrates each coordinate ~sqrt(k)x tighter than any single
    # elite point, so on basin landscapes mu sits far closer to the optimum
    # than the incumbent — a move no incumbent-centered source can make.
    zc = jax.random.normal(k7, (n_cem, n_dims))
    cem_c = reflect_unit(elite_mu[None, :] + zc @ cov_chol.T)
    global_c = jax.random.uniform(jax.random.fold_in(k1, 1), (n_global, n_dims))
    return jnp.concatenate([global_c, box, cov_c, dir_c, cem_c], axis=0)


def maybe_prewarm_fused_step(algo, batch=0):
    """Observe-side prewarm trigger shared by the GP algorithms (`tpu_bo`,
    `asha_bo` — any algorithm exposing the `_host`/`_hist`/`_step_kw`
    surface): when the history nears the next pow-2 bucket, background-
    compile that bucket's fused step so the crossing costs a jit-cache hit
    instead of a synchronous multi-second stall.  O(1) planning per
    observe; needs one prior suggest to know the q bucket.

    In the local-TR regime (``count > tr_local_m``) the fused step's fit
    shape is pinned, but the on-device subset gather still re-buckets with
    the history — its (much smaller) compile is prewarmed instead; the
    approach INTO the regime warms the gather's first shape the same
    way."""
    if not algo.prewarm or algo._last_q_bucket is None:
        return
    count = algo._host.count
    if count < algo.n_init:
        return

    def warm_gather(m_hist):
        width = algo._hist.n_cols
        dist_cols = width - algo._step_kw().get("fixed_tail_cols", 0)
        floor = algo._hist.floor
        m = algo.tr_local_m
        algo._prewarmer.maybe_start(
            ("local_subset", m_hist, width, m, dist_cols),
            lambda: prewarm_local_subset(
                m_hist, width, m, dist_cols, floor=floor
            ),
        )

    if algo.trust_region and count > algo.tr_local_m:
        target_m = plan_next_bucket(
            count, floor=algo._hist.floor, fill=algo.prewarm_fill,
            batch=batch,
        )
        if target_m is not None:
            warm_gather(target_m)
        return
    if algo.trust_region and (
        count >= algo.prewarm_fill * algo.tr_local_m
        or count + batch > algo.tr_local_m
    ):
        # Approaching the full->local switch (by fill, or because one more
        # batch of this size lands past it): the first local_view call
        # feeds the gather an x of shape next_pow2 of that landing count —
        # warm that first signature too.
        warm_gather(
            _next_pow2(
                max(algo.tr_local_m + 1, count + batch),
                floor=algo._hist.floor,
            )
        )
    target_m = plan_fused_step_bucket(
        count,
        floor=algo._hist.floor,
        fill=algo.prewarm_fill,
        batch=batch,
        trust_region=algo.trust_region,
        tr_local_m=algo.tr_local_m,
    )
    if target_m is not None:
        start_bucket_prewarm(
            algo._prewarmer,
            target_m,
            algo._hist.n_cols,
            algo._last_q_bucket,
            algo._step_kw(),
            warm_refit=algo._gp_state is not None,
        )


def start_bucket_prewarm(prewarmer, target_m, width, q_bucket, step_kw, *,
                         warm_refit=False, fixed_tail_cols=0):
    """Hand the prewarmer a compile closure replaying the fused step's
    EXACT static-arg signature at the ``(target_m, width)`` bucket.  The
    dedup key is built from the same statics, so each signature compiles
    at most once per prewarmer.  ``warm_refit``: steady-state boundary
    calls run ``refit_steps`` when configured (the refit path is warm), so
    the prewarm signature must bake that in or it warms the wrong cache
    entry.  Shared by ``tpu_bo`` and ``asha_bo``."""
    kw = dict(step_kw)
    kw.pop("tr_length", None)
    fixed_tail_cols = kw.pop("fixed_tail_cols", fixed_tail_cols)
    refit_steps = kw.pop("refit_steps", None)
    if warm_refit and refit_steps is not None:
        kw["fit_steps"] = refit_steps
    key = (
        target_m,
        width,
        q_bucket,
        fixed_tail_cols,
        tuple(sorted((k, str(v)) for k, v in kw.items())),
    )

    def compile_and_record():
        t0 = time.perf_counter()
        prewarm_suggest_step(
            target_m, width, q_bucket, fixed_tail_cols=fixed_tail_cols, **kw
        )
        if TELEMETRY.enabled:
            # Compiler plane: record the EXACT signature this warm covers
            # (built from the same statics `make_fused_plan` hashes into
            # the plan signature, split-fit adjustment included) — a later
            # retrace at this signature is a prewarm bug (DX052).
            statics = dict(kw, q=q_bucket, fixed_tail_cols=fixed_tail_cols)
            mesh = statics.get("mesh")
            if mesh is not None and mesh.devices.size > 1:
                statics["fit_steps"] = 0
            COMPILE_REGISTRY.record_prewarm(
                "fused_plan",
                signature_fields((target_m, width), statics),
                seconds=time.perf_counter() - t0,
            )

    return prewarmer.maybe_start(key, compile_and_record)


def prewarm_suggest_step(
    m,
    width,
    q_bucket,
    *,
    n_candidates,
    kernel,
    acq,
    fit_steps,
    local_frac,
    local_sigma,
    beta,
    trust_region=False,
    tr_perturb_dims=20,
    y_transform="none",
    fixed_tail_cols=0,
    mesh=None,
):
    """Compile the fused suggest step for the ``(m, width)`` buffer bucket
    by CALLING the jitted function on zero dummies — the call populates the
    jit cache (AOT ``lower().compile()`` would not), so the first real call
    at this bucket is a cache hit.  Runs on the prewarmer's background
    thread; XLA compilation releases the GIL, so the main thread keeps
    producing rounds.  Deliberately bypasses ``run_suggest_step_arrays``:
    a prewarm compile must never book a ``jax.retraces`` sample (that
    counter reports the synchronous stalls a suggest actually paid)."""
    zeros = jnp.zeros((m, width), jnp.float32)
    split_fit = mesh is not None and mesh.devices.size > 1
    if split_fit:
        # Multi-device mesh plans split the hyper-opt into `_fit_gp_host`
        # and run the fused step solve-only (make_fused_plan); warm BOTH
        # entries, each at the signature the real round will hit.
        _fit_gp_host(
            zeros, zeros[:, 0], zeros[:, 0], init_hypers(width),
            kernel=kernel, fit_steps=fit_steps, y_transform=y_transform,
        )
    rows, _ = _suggest_step(
        jax.random.PRNGKey(0),
        zeros,
        zeros[:, 0],
        zeros[:, 0],
        # best_x carries only the FREE columns: multi-fidelity callers pass
        # the incumbent without the context tail, and jit caches on shape —
        # a (width,) dummy would warm an entry the real call never hits.
        jnp.zeros((width - fixed_tail_cols,), jnp.float32),
        init_hypers(width),
        jnp.asarray(1.0, jnp.float32),
        q=q_bucket,
        n_candidates=n_candidates,
        kernel=kernel,
        acq=acq,
        fit_steps=0 if split_fit else fit_steps,
        local_frac=local_frac,
        local_sigma=local_sigma,
        beta=beta,
        trust_region=trust_region,
        tr_perturb_dims=tr_perturb_dims,
        y_transform=y_transform,
        fixed_tail_cols=fixed_tail_cols,
        mesh=mesh,
    )
    # No block_until_ready: the first call compiles SYNCHRONOUSLY (the
    # cache insert happens before it returns); only the dummy's execution
    # is async, and waiting on it would hold the prewarmer's completed
    # bookkeeping tens of ms past the insert — exactly the window in which
    # the retrace detector would misread the growth.
    del rows


def run_suggest_step(
    key,
    x_obs,
    y_obs,
    best_x,
    warm_state,
    num,
    *,
    n_candidates,
    kernel,
    acq,
    fit_steps,
    refit_steps=None,
    local_frac,
    local_sigma,
    beta,
    trust_region=False,
    tr_length=None,
    tr_perturb_dims=20,
    y_transform="none",
    fixed_tail_cols=0,
    mesh=None,
):
    """Host wrapper around the fused jit: pow-2 pad the observation buffers
    on host, upload, and delegate to :func:`run_suggest_step_arrays`.

    No longer on the algorithms' hot path — both the full-history and the
    local-subset (trust-region) fit sets now come straight off the
    device-resident :class:`DeviceHistory` buffers (``fit_view`` /
    ``local_view``), so nothing history-sized is re-padded or re-uploaded
    per round.  This entry remains for host-array callers and as the
    re-upload REFERENCE the bit-equality regression tests compare the
    resident path against (``tests/unit/test_device_history.py``,
    ``tests/unit/test_host_history.py``).
    """
    n, width = np.asarray(x_obs).shape
    n_pad = _next_pow2(n)
    x = np.zeros((n_pad, width), dtype=np.float32)
    y = np.zeros((n_pad,), dtype=np.float32)
    mask = np.zeros((n_pad,), dtype=np.float32)
    x[:n] = x_obs
    y[:n] = y_obs
    mask[:n] = 1.0
    return run_suggest_step_arrays(
        key,
        jnp.asarray(x),
        jnp.asarray(y),
        jnp.asarray(mask),
        best_x,
        warm_state,
        num,
        n_candidates=n_candidates,
        kernel=kernel,
        acq=acq,
        fit_steps=fit_steps,
        refit_steps=refit_steps,
        local_frac=local_frac,
        local_sigma=local_sigma,
        beta=beta,
        trust_region=trust_region,
        tr_length=tr_length,
        tr_perturb_dims=tr_perturb_dims,
        y_transform=y_transform,
        fixed_tail_cols=fixed_tail_cols,
        mesh=mesh,
    )


class FusedPlan(NamedTuple):
    """One prepared (not yet dispatched) fused suggest step.

    ``arrays`` holds the traced inputs of ``_suggest_step`` in call order
    (key, x, y, mask, best_x, warm hypers, tr_length); ``statics`` its
    exact static-arg kwargs — warm-vs-cold fit_steps and the pow-2 q bucket
    already folded in, so two plans with equal ``signature`` are guaranteed
    to hit the SAME jit entry and can be stacked along a leading tenant
    axis and dispatched as ONE device call (``orion_tpu.serve.coalesce``).
    ``signature`` is that grouping key: buffer shapes + every static.
    """

    signature: tuple
    arrays: tuple
    statics: dict
    num: int


class _PlanPrep(NamedTuple):
    """The signature-invariant part of a :class:`FusedPlan`, cached per
    distinct (shape bucket, statics) so the steady suggest path skips
    rebuilding it every round (the statics dict, the stringified
    signature — ``str(mesh)`` formats the whole device array — the cold
    ``init_hypers`` leaves, and the default tr_length upload were the
    largest host lines inside the bench's ``dispatch`` stage)."""

    statics: dict
    signature: tuple
    cold_hypers: object
    default_tr: object
    #: Multi-device mesh mode: the hyper-opt loop runs in its own
    #: single-device jit (`_fit_gp_host`) and the plan's in-step fit is the
    #: solve-only ``fit_steps=0`` — see :func:`make_fused_plan`.
    split_fit: bool
    host_fit_steps: int


_PLAN_PREP_CACHE = {}
#: Distinct trust-region lengths a token's device-scalar cache may hold.
#: The TuRBO schedule walks a short halving/doubling ladder, so a runaway
#: set means a caller feeds free-form floats — the cache resets rather
#: than grow without bound.
_TR_CACHE_MAX = 64


class PlanPrepToken:
    """Per-algorithm-instance steady-path dispatch-prep cache.

    ``_PLAN_PREP_CACHE`` already skips re-deriving the signature-invariant
    plan leaves, but *probing* it still costs building the 16-element
    ``prep_key`` (hashing the mesh handle included) plus the ``_step_kw``
    statics-dict rebuild, every round.  A token pins the resolved
    :class:`_PlanPrep` for ONE instance and revalidates only what that
    instance can change between rounds — the history shape bucket, the q
    bucket, warm-vs-cold, the quantized ``local_sigma`` ladder
    (``asha_bo``), and the fit-step knobs; every other static is frozen at
    ``__init__``.  A caller that mutates a frozen static mid-run must drop
    the token (``algo._prep_token = PlanPrepToken()``).

    ``pinned`` is the ``(fast_key, prep)`` pair, swapped as ONE tuple:
    immutable-by-rebinding, so a concurrent reader (gateway dispatch
    thread vs producer clone sharing the token by ref) can never observe a
    torn key/prep mix.  ``tr_cache`` re-uses the uploaded device scalar
    per distinct trust-region length.  Donation safety: ``_suggest_step``
    declares no ``donate_argnums``, so re-passing the same device buffer
    (tr scalar, ``default_tr``, ``cold_hypers``) round after round can
    never alias a donated input — the same COW discipline as
    ``DeviceHistory._append_donating``, where a buffer handed to a
    donating jit is never re-entered.
    """

    __slots__ = ("pinned", "tr_cache")

    def __init__(self):
        self.pinned = None
        self.tr_cache = {}

    def __deepcopy__(self, memo):
        # Fallback for algos that don't share the token by ref: a true
        # deepcopy would walk device buffers; a clone starting cold only
        # costs one full prep probe.
        return type(self)()


def _finish_plan(
    prep,
    key,
    x,
    y,
    mask,
    best_x,
    warm_state,
    warm_is_none,
    num,
    tr_length,
    tr_cache,
    kernel,
    y_transform,
):
    """Per-round plan tail shared by the token fast path and the full prep
    path — ONE implementation, so a token hit is bit-identical by
    construction to the plan the full path would have built."""
    warm = prep.cold_hypers if warm_is_none else warm_state.hypers
    if prep.split_fit:
        # A warm state comes back from the sharded step committed to the
        # whole mesh; fed as is, it compiled this fit a second time, as a
        # multi-device program (18.9 s on the first warm round on a v5e
        # 2x2), whose reductions need not match the one-device fit.  The
        # hypers are a few floats of a step already read back: taking them
        # through the host keeps every round's fit the one single-device
        # program.
        warm = _fit_gp_host(
            x, y, mask, jax.device_get(warm),
            kernel=kernel,
            fit_steps=prep.host_fit_steps,
            y_transform=y_transform,
        )
    # tr_length is dynamic (traced) so success/failure box resizing never
    # recompiles; always an array — jit caches on dtype, not value.  The
    # token's tr_cache skips the per-round host->device upload for lengths
    # the TuRBO ladder already visited (safe to re-pass: no donation, see
    # PlanPrepToken).
    if tr_length is None:
        tr = prep.default_tr
    else:
        tr = tr_cache.get(tr_length) if tr_cache is not None else None
        if tr is None:
            tr = jnp.asarray(tr_length, jnp.float32)
            if tr_cache is not None:
                if len(tr_cache) >= _TR_CACHE_MAX:
                    tr_cache.clear()
                tr_cache[tr_length] = tr
    arrays = (key, x, y, mask, jnp.asarray(best_x), warm, tr)
    return FusedPlan(prep.signature, arrays, prep.statics, int(num))


def make_fused_plan(
    key,
    x,
    y,
    mask,
    best_x,
    warm_state,
    num,
    *,
    n_candidates,
    kernel,
    acq,
    fit_steps,
    refit_steps=None,
    local_frac,
    local_sigma,
    beta,
    trust_region=False,
    tr_length=None,
    tr_perturb_dims=20,
    y_transform="none",
    fixed_tail_cols=0,
    mesh=None,
    prep_token=None,
):
    """Fold the per-round dynamics (warm refit steps, q bucket, tr_length
    boxing) into a :class:`FusedPlan`.  This is THE prep path — the
    standalone dispatch (:func:`run_fused_plan`) and the gateway's
    coalesced dispatch both consume plans built here, so their inputs
    cannot drift.

    The signature-invariant leaves (statics dict, stringified signature,
    cold-start hypers, default tr_length array) are cached per
    :class:`_PlanPrep` key: on the steady path every round re-requests the
    same bucket, and re-deriving them was the largest host line in the
    bench's ``dispatch`` stage.  The cache key folds in everything the
    cached values depend on — including ``warm_state is None`` (fit-steps
    selection) — so a hit can never change the plan that would have been
    built.

    ``prep_token`` (a :class:`PlanPrepToken` private to one algorithm
    instance) layers the steady-path shortcut on top: when the token's
    pinned fast key still matches, the 16-element ``prep_key`` build and
    cache probe are skipped entirely and the round goes straight to the
    shared plan tail (:func:`_finish_plan`).  The fast key deliberately
    omits the instance-frozen statics; see :class:`PlanPrepToken` for the
    contract.

    Telemetry counts both caches: ``plan.token.hits``/``misses`` (the
    token, per call that carries one) and ``plan.prep.hits``/``misses``
    (the per-signature cache, per probe); the time they save shows in the
    ``algo.plan`` span's histogram.
    """
    warm_is_none = warm_state is None
    fast_key = None
    if prep_token is not None:
        fast_key = (
            tuple(x.shape),
            _next_pow2(num, floor=8),
            warm_is_none,
            local_sigma,
            fit_steps,
            refit_steps,
        )
        pinned = prep_token.pinned
        if pinned is not None and pinned[0] == fast_key:
            plan = _finish_plan(
                pinned[1], key, x, y, mask, best_x, warm_state,
                warm_is_none, num, tr_length, prep_token.tr_cache,
                kernel, y_transform,
            )
            TELEMETRY.count("plan.token.hits")
            return plan
    width = x.shape[1]
    prep_key = (
        tuple(x.shape),
        _next_pow2(num, floor=8),
        warm_is_none,
        n_candidates,
        kernel,
        acq,
        fit_steps,
        refit_steps,
        local_frac,
        local_sigma,
        beta,
        trust_region,
        tr_perturb_dims,
        y_transform,
        fixed_tail_cols,
        mesh,
    )
    prep = _PLAN_PREP_CACHE.get(prep_key)
    if prep is None:
        steps = fit_steps
        if not warm_is_none and refit_steps is not None:
            steps = refit_steps
        # Multi-device mesh: the marginal-likelihood hyper-opt LOOP moves to
        # a separate single-device jit (`_fit_gp_host`) and the fused step
        # keeps only the solve (fit_steps=0).  XLA's SPMD pipeline compiles
        # the loop's reductions differently per mesh size — even fully
        # replicated — so an in-step loop drifts the sharded fit away from
        # the one-device fit, while the solve is stable across module
        # variants (verified by the parity pins).  On a 1-device
        # mesh nothing splits, keeping the sharded path bit-identical to
        # the unsharded single-jit round.
        split_fit = mesh is not None and mesh.devices.size > 1
        statics = dict(
            q=_next_pow2(num, floor=8),
            n_candidates=n_candidates,
            kernel=kernel,
            acq=acq,
            fit_steps=0 if split_fit else steps,
            local_frac=local_frac,
            local_sigma=local_sigma,
            beta=beta,
            trust_region=trust_region,
            tr_perturb_dims=tr_perturb_dims,
            y_transform=y_transform,
            fixed_tail_cols=fixed_tail_cols,
            mesh=mesh,
        )
        # The exact coalescing key (prewarm.start_bucket_prewarm builds its
        # dedup key from the same statics): fit-buffer shape bucket + q
        # bucket + every static arg.  Plans whose signatures match compile
        # to the same jit entry, so stacking them is safe; anything else
        # must not coalesce.
        signature = (
            tuple(x.shape),
            tuple(sorted((k, str(v)) for k, v in statics.items())),
        )
        prep = _PlanPrep(
            statics,
            signature,
            init_hypers(width) if warm_is_none else None,
            jnp.asarray(1.0, jnp.float32),
            split_fit,
            steps,
        )
        _PLAN_PREP_CACHE[prep_key] = prep
        TELEMETRY.count("plan.prep.misses")
    else:
        TELEMETRY.count("plan.prep.hits")
    if prep_token is not None:
        # One-tuple swap: a concurrent fast-path reader sees either the old
        # or the new (key, prep) pair, never a torn mix.
        prep_token.pinned = (fast_key, prep)
    plan = _finish_plan(
        prep, key, x, y, mask, best_x, warm_state, warm_is_none, num,
        tr_length, prep_token.tr_cache if prep_token is not None else None,
        kernel, y_transform,
    )
    if prep_token is not None:
        TELEMETRY.count("plan.token.misses")
    return plan


def run_suggest_step_arrays(
    key,
    x,
    y,
    mask,
    best_x,
    warm_state,
    num,
    *,
    n_candidates,
    kernel,
    acq,
    fit_steps,
    refit_steps=None,
    local_frac,
    local_sigma,
    beta,
    trust_region=False,
    tr_length=None,
    tr_perturb_dims=20,
    y_transform="none",
    fixed_tail_cols=0,
    mesh=None,
    prewarmer=None,
):
    """Device-array entry to the fused jit: ``(x, y, mask)`` are already
    pow-2-padded device (or device-ready) buffers — typically
    ``DeviceHistory.fit_view`` slices, so no O(n) host re-pad or re-upload
    happens here.  Warm-starts from a previous GPState (warm refits run
    ``refit_steps`` optimizer steps, cold first fits ``fit_steps``) and
    buckets q (a static arg — the producer's retry loop shrinks its request
    per round and each distinct q would otherwise recompile the whole
    graph).  Shared by ``tpu_bo`` and the multi-fidelity ``asha_bo``.
    """
    plan = make_fused_plan(
        key,
        x,
        y,
        mask,
        best_x,
        warm_state,
        num,
        n_candidates=n_candidates,
        kernel=kernel,
        acq=acq,
        fit_steps=fit_steps,
        refit_steps=refit_steps,
        local_frac=local_frac,
        local_sigma=local_sigma,
        beta=beta,
        trust_region=trust_region,
        tr_length=tr_length,
        tr_perturb_dims=tr_perturb_dims,
        y_transform=y_transform,
        fixed_tail_cols=fixed_tail_cols,
        mesh=mesh,
    )
    return run_fused_plan(plan, prewarmer=prewarmer)


def run_fused_plan(plan, prewarmer=None):
    """Dispatch ONE prepared :class:`FusedPlan` through the fused jit,
    with the retrace-vs-cache-hit telemetry bracket.  Returns
    ``(rows[:num], state)`` exactly as the pre-plan entry did."""
    if TELEMETRY.enabled:
        rows, state = _dispatch_traced(plan, prewarmer)
    else:
        rows, state = _suggest_step(*plan.arrays, **plan.statics)
    # Dedup ordered unique draws first, so the first `num` rows are the ones
    # the un-padded call would have returned.  Rows come back as a DEVICE
    # array slice: jax dispatch is asynchronous, so callers that defer the
    # host transfer (BaseAlgorithm.suggest's np.asarray, or the producer's
    # speculative prefetch) overlap the device round trip with host work
    # instead of blocking here.
    return rows[:plan.num], state


def _dispatch_traced(plan, prewarmer):
    """:func:`run_fused_plan`'s dispatch with telemetry on."""
    num = plan.num
    x = plan.arrays[1]
    # Telemetry: jax dispatch is asynchronous, so this span is the HOST
    # cost of the fused step — tracing + lowering + compile on a cache
    # miss, ~argument-handling microseconds on a hit.  The jit cache size
    # before/after distinguishes the two (a growth IS a retrace), which is
    # how `orion-tpu info` counts recompiles a production hunt paid.  The
    # span is on the profiler's clock as ``jax.suggest_step.dispatch``; its
    # record is renamed ``jax.suggest_step.compile`` on a retrace.
    cache_size = getattr(_suggest_step, "_cache_size", None)
    try:
        tel_before = cache_size() if cache_size is not None else -1
    except Exception:  # private jax API — degrade, never raise into suggest
        cache_size, tel_before = None, -1
    # Background prewarm compiles insert cache entries too: sample the
    # completed-prewarm count around the dispatch so a prewarm landing
    # mid-window is not booked as a synchronous retrace (jax.retraces
    # must report only the stalls THIS call paid).  Scoped to the
    # caller's own prewarmer when given — only ITS compiles share
    # these jit signatures; the process-global fallback would let an
    # unrelated instance's warm mask a genuine retrace here.
    tel_completed = (
        prewarmer.completed_count
        if prewarmer is not None
        else completed_prewarm_count
    )
    tel_prewarms_before = tel_completed()
    tel_t0 = time.perf_counter()
    with TELEMETRY.span(
        "jax.suggest_step.dispatch", args={"q": int(num), "n": int(x.shape[0])}
    ) as span:
        rows, state = _suggest_step(*plan.arrays, **plan.statics)
        try:
            retraced = (
                cache_size is not None
                and cache_size() > tel_before
                # A prewarm that completed during this window explains the
                # growth; classify as a cached dispatch (conservative: a
                # genuine retrace coinciding with a completing prewarm
                # goes uncounted rather than a cache hit being booked as a
                # stall).  Prewarm compiles are synchronous inside the
                # jitted call and bookkeeping follows within microseconds
                # (no block_until_ready on the dummy), so the completed
                # delta is a tight proxy for "an insert landed here" — a
                # blanket in-flight check would instead blind the counter
                # to genuine retraces for the whole life of a compile.
                and tel_completed() == tel_prewarms_before
            )
        except Exception:  # private jax API — degrade, never raise into suggest
            retraced = False
        if retraced:
            span.rename("jax.suggest_step.compile")
    if retraced:
        TELEMETRY.count("jax.retraces")
        # Compiler-plane attribution (orion_tpu.compiler_plane): the
        # registry diffs this signature against the nearest prior one
        # in the fused_plan family, emits the flight `jax.retrace`
        # event naming the changed statics (the timeline entry a crash
        # post-mortem wants), and keeps a lazy cost/memory closure —
        # shape specs only, never the arrays — for cold-path analysis
        # (bench's compiler block, `orion-tpu profile`).
        COMPILE_REGISTRY.record_retrace(
            "fused_plan",
            fields_from_plan_signature(plan.signature),
            seconds=time.perf_counter() - tel_t0,
            analysis_fn=lowered_analysis_fn(
                _suggest_step, plan.arrays, plan.statics
            ),
        )
    return rows, state


@partial(jax.jit, static_argnames=("kernel", "fit_steps", "y_transform"))
def _fit_gp_host(x, y, mask, warm, *, kernel, fit_steps, y_transform):
    """The hyper-opt loop as its OWN single-device jit (multi-device mesh
    mode only).  Dispatched by :func:`make_fused_plan` right before the
    sharded fused step; only the fitted hypers cross into the plan — the
    posterior factorization is re-solved inside the step (bit-stable), so
    the warm-start chain through ``consume_fused_step`` is unchanged."""
    return fit_gp(
        x, y, mask, kind=kernel, n_steps=fit_steps, init=warm,
        y_transform=y_transform,
    ).hypers


def _dedup_fill_device(idx, ei_rank, q):
    """On-device first-occurrence dedup of ``idx`` with EI-ranked backfill.

    Sort-by-priority-key trick, all static shapes: unique draws keep their
    draw position as key, duplicates and already-drawn fill candidates get
    pushed past everything usable, EI fills slot in after the draws.  If the
    distinct pool is exhausted the tail recycles duplicates (storage
    dedup/DuplicateKeyError rejects them downstream, as before).
    """
    k = ei_rank.shape[0]
    pos_q = jnp.arange(q)
    pos_k = jnp.arange(k)
    # Sort-based dup/membership tests: the O(q^2) pairwise masks (and the
    # O(q*k) membership mask, k = 4q) cap q around 4k before the mask alone
    # outweighs the candidate pool — at q=64k they would materialize
    # multi-GB booleans.  A stable sort puts equal draws adjacent with the
    # FIRST occurrence first, so "has an earlier equal" is one neighbor
    # compare scattered back; membership is a searchsorted probe into the
    # same sorted order.  Both produce booleans identical to the pairwise
    # masks, so the keys — and therefore the returned q-batch — stay
    # bit-identical at every q.
    sort_perm = jnp.argsort(idx, stable=True)
    sorted_idx = idx[sort_perm]
    dup_sorted = jnp.concatenate(
        [jnp.zeros((1,), bool), sorted_idx[1:] == sorted_idx[:-1]]
    )
    is_dup = jnp.zeros((q,), bool).at[sort_perm].set(dup_sorted)
    probe = jnp.searchsorted(sorted_idx, ei_rank)
    is_member = sorted_idx[jnp.clip(probe, 0, q - 1)] == ei_rank
    big = q + k + 1
    key_draws = jnp.where(is_dup, big + pos_q, pos_q)
    key_fills = jnp.where(is_member, big + q + pos_k, q + pos_k)
    all_idx = jnp.concatenate([idx, ei_rank])
    order = jnp.argsort(jnp.concatenate([key_draws, key_fills]))
    return all_idx[order][:q]


@partial(
    jax.jit,
    static_argnames=(
        "q",
        "n_candidates",
        "kernel",
        "acq",
        "fit_steps",
        "local_frac",
        "local_sigma",
        "beta",
        "trust_region",
        "tr_perturb_dims",
        "y_transform",
        "fixed_tail_cols",
        "mesh",
    ),
)
def _suggest_step(
    key,
    x,
    y,
    mask,
    best_x,
    warm_hypers,
    tr_length=None,  # required (traced scalar) when trust_region=True
    *,
    q,
    n_candidates,
    kernel,
    acq,
    fit_steps,
    local_frac,
    local_sigma,
    beta,
    trust_region=False,
    tr_perturb_dims=20,
    y_transform="none",
    fixed_tail_cols=0,
    mesh=None,
):
    """The whole GP-BO suggest round as ONE compiled computation.

    ``y_transform="copula"`` rank-Gaussianizes the masked targets in-jit
    (``fit_gp`` applies ``masked_copula_transform``); ``y`` arrives RAW, so
    the device-resident buffers feed this step directly with no per-round
    host transform or y re-upload.  The transform is monotone, so every
    rank-based consumer below (elite covariance, EI incumbent) is
    unaffected by reading raw ``y``.

    ``fixed_tail_cols``: the last k input columns are context, not free
    variables — candidates are generated over the leading columns only and
    the tail is pinned to 1.0 when scoring (multi-fidelity BO pins the
    fidelity column to max budget so selection optimizes the predicted
    FULL-budget value).  Returned rows include only the free columns.
    """
    # Named scopes tag the step's ops by stage (fit, candidates, acquire,
    # posterior, select, health) in the HLO metadata and the device trace.
    with jax.named_scope("fit"):
        if mesh is not None:
            # Pin the fit side REPLICATED before anything touches it: sharding
            # propagation from the candidate constraint below would otherwise
            # partition the O(n^2) GP fit too, re-ordering its reductions — the
            # fit is tiny next to the O(m·F) candidate work, and replicating it
            # keeps every device computing the single-device fit (the sharded
            # gate's parity contract, sharding.compare_rows).
            rep = replicated(mesh)
            x, y, mask, best_x, warm_hypers = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, rep),
                (x, y, mask, best_x, warm_hypers),
            )
        state = fit_gp(
            x, y, mask, kind=kernel, n_steps=fit_steps, init=warm_hypers,
            y_transform=y_transform,
        )
        if mesh is not None:
            state = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, rep), state
            )
    with jax.named_scope("candidates"):
        k_cand, k_acq = jax.random.split(key)
        d_free = x.shape[1] - fixed_tail_cols
        if trust_region:
            cov_chol, elite_mu = _topk_cov_chol(
                x[:, :d_free], y, mask, d_free, k=min(64, x.shape[0])
            )
            lengthscales = jnp.exp(state.hypers.log_lengthscales[:d_free])
            free_candidates = _make_tr_candidates(
                k_cand,
                n_candidates,
                d_free,
                best_x[:d_free],
                tr_length,
                lengthscales,
                local_frac,
                cov_chol,
                elite_mu,
                perturb_dims=tr_perturb_dims,
            )
            # Gradient-polish a handful of elite-covariance-jittered incumbent
            # copies on the posterior mean and splice them over the pool's tail
            # (keeps the pool size, and with it the candidates-divide-mesh
            # invariant, unchanged) — acquisition still judges them against the
            # random candidates, so exploitation sharpens without another full
            # posterior pass over the pool.
            k_polish = jax.random.fold_in(k_cand, 7)
            lb, ub = _tr_box(best_x[:d_free], tr_length, lengthscales)
            # Scale the exploiter count with the batch: at q=512 eight polished
            # points would be a rounding error in the pool.  Clamped to half the
            # pool — a small-n_candidates config must not have the splice eat the
            # whole pool (changing the candidate count breaks the
            # candidates-divide-mesh invariant and select_q's k <= pool assert).
            n_polish = max(1, min(64, max(8, q // 16), n_candidates // 2))
            starts = jnp.clip(
                best_x[None, :d_free]
                + 0.5 * jax.random.normal(k_polish, (n_polish, d_free)) @ cov_chol.T,
                lb,
                ub,
            )
            if mesh is not None:
                # Pin the polish segment REPLICATED on both sides.  Without the
                # pins, the candidate constraint below back-propagates into this
                # tail-of-pool computation and XLA compiles the tiny start
                # matmul and the 30-step descent scan per-partition — with a
                # different float association than the single-device module
                # (measured: the splice rows drift by ulps, which moves
                # suggestion rows AND acq_ei_mean).  Pinned, the segment
                # compiles once, replicated, bit-identical to unsharded.
                rep = replicated(mesh)
                starts = jax.lax.with_sharding_constraint(starts, rep)
            polished = _polish_candidates(
                state, kernel, starts, lb, ub, fixed_tail_cols=fixed_tail_cols
            )
            if mesh is not None:
                polished = jax.lax.with_sharding_constraint(polished, rep)
            free_candidates = jnp.concatenate(
                [free_candidates[:-n_polish], polished], axis=0
            )
        else:
            free_candidates = _make_candidates(
                k_cand, n_candidates, d_free, best_x[:d_free], local_frac, local_sigma
            )
        if mesh is not None:
            # Data-parallel over the candidate axis: XLA's SPMD partitioner
            # splits generation+scoring per shard and inserts the ICI
            # collectives for the cross-candidate argmin/top-k reductions.
            free_candidates = jax.lax.with_sharding_constraint(
                free_candidates, candidate_sharding(mesh)
            )
        if fixed_tail_cols:
            candidates = jnp.concatenate(
                [
                    free_candidates,
                    jnp.ones(
                        (free_candidates.shape[0], fixed_tail_cols),
                        free_candidates.dtype,
                    ),
                ],
                axis=1,
            )
        else:
            candidates = free_candidates
    with jax.named_scope("acquire"):
        y_norm = (state.y - state.y_mean) / state.y_std
        if fixed_tail_cols:
            # Candidates are scored at max context (tail pinned to 1), so the EI
            # incumbent must be the best observation AT the top context tier — a
            # lucky low-fidelity value would otherwise be unattainable for every
            # candidate and flatten EI to ~0.
            s_col = x[:, -1]
            s_max = jnp.max(jnp.where(mask > 0, s_col, -jnp.inf))
            top = (mask > 0) & (s_col >= s_max - 1e-6)
            best = jnp.min(jnp.where(top, y_norm, jnp.inf))
        else:
            best = jnp.min(jnp.where(state.mask > 0, y_norm, jnp.inf))
    # Candidate scoring sees the mesh as its ambient one: the fused Pallas
    # gram, which XLA cannot partition, splits its rows over it itself.
    with (
        jax.sharding.use_abstract_mesh(mesh.abstract_mesh)
        if mesh is not None
        else contextlib.nullcontext()
    ):
        with jax.named_scope("acquire"):
            if acq == "joint_thompson":
                idx = joint_thompson(k_acq, state, candidates, q, kind=kernel)
            else:
                idx = acquire(
                    k_acq, state, candidates, q, kind=kernel, acq=acq, best=best,
                    beta=beta,
                )
        with jax.named_scope("posterior"):
            mean, std = posterior_norm(state, candidates, kind=kernel)
    with jax.named_scope("posterior"):
        ei = expected_improvement(mean, std, best)
    with jax.named_scope("select"):
        ei_rank = select_q(ei, min(4 * q, n_candidates))
        if trust_region:
            # Guarantee one pure-exploitation member per batch: the pool's
            # posterior-mean minimizer (usually a gradient-polished point).
            # Thompson noise rarely selects it, yet it is the single highest
            # expected payoff — CMA-style descent wants it evaluated every round.
            # UNLESS it is already observed: once the box has converged, polish
            # lands on the incumbent bit-for-bit every round, and injecting it
            # again would re-suggest a stored point each batch — the producer
            # then loops on DuplicateKeyError until SampleTimeout (small pools
            # hit this within two rounds).
            exploit_idx = jnp.argmin(mean)
            exploit_cand = jnp.take(free_candidates, exploit_idx, axis=0)
            d2_obs = jnp.sum((x[:, :d_free] - exploit_cand[None, :]) ** 2, axis=1)
            already_observed = jnp.any((d2_obs < 1e-12) & (mask > 0))
            injected = jnp.where(already_observed, idx[0], exploit_idx)
            idx = jnp.concatenate([injected[None], idx])[:q]
        final_idx = _dedup_fill_device(idx, ei_rank, q)
        rows = jnp.take(free_candidates, final_idx, axis=0)
    with jax.named_scope("health"):
        # Packed per-round health vector (health.DEVICE_HEALTH_FIELDS), built
        # entirely from intermediates this step already computed — a handful of
        # reductions, attached to the returned state so no signature changes
        # and no extra device->host syncs (the vector is read lazily after the
        # q-row transfer already materialized the round).
        ls = jnp.exp(state.hypers.log_lengthscales[:d_free])
        sorted_idx = jnp.sort(final_idx)
        n_unique = 1.0 + jnp.sum((sorted_idx[1:] != sorted_idx[:-1]).astype(ls.dtype))
        ei_stats = ei
        if mesh is not None:
            # Health-only copy of the EI vector, gathered replicated: a mean
            # over the SHARDED axis is per-shard partials + all-reduce, whose
            # float association (and so the last ulp of acq_ei_mean) would vary
            # with the mesh size.  The gather pins the reduction to the
            # single-device association — one all-gather of m floats on the
            # health path, nothing on the selection path.
            ei_stats = jax.lax.with_sharding_constraint(ei, replicated(mesh))
        health = jnp.stack(
            [
                state.mll,
                jnp.min(ls),
                jnp.mean(ls),
                jnp.max(ls),
                jnp.exp(state.hypers.log_noise),
                state.fit_nonfinite.astype(jnp.float32),
                jnp.max(ei_stats),
                jnp.mean(ei_stats),
                n_unique / q,
            ]
        ).astype(jnp.float32)
        state = state._replace(health=health)
    return rows, state

