"""Algorithm interface.

Capability parity: reference `src/orion/algo/base.py` (BaseAlgorithm:
suggest/observe/is_done/score/judge/should_suspend/state_dict/set_state/
seed_rng, nested config instantiation, Factory plugin discovery).

TPU-first redesign: algorithms speak **flat unit-cube arrays**.  ``suggest``
produces a ``(num, D)`` array in [0,1]^D through jitted device code and the
framework decodes it to structured params via the Space codec; ``observe``
receives the encoded array plus an objective vector.  The stateful-RNG
contract of the reference (numpy RandomState in state_dict) becomes a JAX
PRNGKey threaded through state — seeding is explicit and resumable.
"""

from typing import NamedTuple, Optional

import numpy as np
import jax

from orion_tpu.space.space import Space
from orion_tpu.telemetry import TELEMETRY
from orion_tpu.utils.registry import Registry

algo_registry = Registry("algo")


class SuggestionBatch(NamedTuple):
    """One suggest round in columnar form.

    ``params`` is the storage-document edge: the per-point dicts trials are
    registered from (built ONCE, by one vectorized ``decode_flat_np`` +
    bulk dict zip).  ``cube`` is the raw ``(n, D)`` unit-cube rows the
    device produced, or None for host-scheduled algorithms (ASHA
    promotions, grid cursors) that never had a cube.  Note ``cube`` is the
    SUGGEST-time encoding; the observe-side columnar rows are defined by
    ``Space.params_to_cube`` over the registered params (quantized dims
    decode lossily), which is what the producer caches and feeds back.
    """

    params: list
    #: Raw suggest-time rows, for array-native consumers (benchmarks,
    #: custom drivers that skip the dict edge entirely).  The producer
    #: registers trials from ``params`` and builds its observe-side rows
    #: via ``params_to_cube`` — it does NOT feed this cube back.
    cube: Optional[np.ndarray]


def _effective_share(cls):
    """Union of ``_share_by_ref`` / ``_share_dicts`` over the MRO, so a
    subclass's declaration extends rather than shadows its parents'.

    Cached on the class itself (not a module-level lru_cache, which would
    pin a strong reference to every algorithm class ever copied and keep
    dynamically created classes — plugin reloads, test subclasses — alive
    forever).  The ``cls.__dict__`` guard makes the cache per-class rather
    than inherited: a subclass must not reuse its parent's union."""
    cached = cls.__dict__.get("__effective_share__")
    if cached is not None:
        return cached
    ref, dicts = set(), set()
    for klass in cls.__mro__:
        ref.update(klass.__dict__.get("_share_by_ref", ()))
        dicts.update(klass.__dict__.get("_share_dicts", ()))
    out = (frozenset(ref), frozenset(dicts))
    cls.__effective_share__ = out
    return out


class BaseAlgorithm:
    """Base class for optimization algorithms.

    Subclasses implement ``_suggest_cube(num)`` returning a ``(num, D)``
    unit-cube array (or None to opt out this round, reference
    `base.py:142-163`) and may override ``observe_arrays``.
    """

    requires_fidelity = False

    # True for algorithms whose `_suggest_cube` returns an UNFORCED device
    # array (jax dispatch is asynchronous): the producer may then split
    # suggestion into dispatch_suggest/finalize_suggest and overlap the
    # device round trip with trial execution.  Algorithms that override
    # `suggest` itself with host-side scheduling (ASHA's promotions) or
    # compute on host must leave this False.
    supports_async_suggest = False

    # True ONLY when a suggestion conditioned on round-(k-1) state is
    # EXACTLY as good as one conditioned on round k (i.e. suggestions do
    # not depend on observations at all — random/grid).  The producer
    # speculatively dispatches the next round's suggest for such
    # algorithms.  Model-based algorithms must leave this False: measured
    # on Hartmann6, fantasy-conditioned speculation costs real regret
    # (0.13 -> 0.21) because constant-liar lies mark the previous batch's
    # genuinely-good region as bad.
    speculation_safe = False

    # True when observe() actually consumes the columnar ``cube`` rows.
    # Algorithms whose observation handling is purely dict-keyed (ASHA's
    # rung bookkeeping) set this False so the producer skips building and
    # caching cube rows it would only throw away.  Orthogonal to signature
    # compatibility: the producer ALSO sniffs the observe signature, so
    # pre-columnar plugin overrides fall back to the dict path either way.
    uses_observe_cube = True

    # The producer deepcopies the algorithm every round for its naive copy
    # (lie fantasization); these class attributes let subclasses exempt
    # fields from that copy without each reimplementing __deepcopy__:
    # - _share_by_ref: immutable-by-rebinding values (Space, fitted GP
    #   state, mesh handles, append-only observation arrays that are
    #   rebound via np.concatenate, never mutated).
    # - _share_dicts: dicts WHOSE VALUES follow that discipline but which
    #   are themselves mutated by key assignment — shallow-copied so the
    #   clone's inserts don't leak back.
    # The effective sets are the UNION over the class's MRO (see
    # _effective_share): a subclass declaring its own tuple extends its
    # parents' instead of silently shadowing them (bohb's tier dicts once
    # hid ASHA's _bracket_of exactly that way).
    _share_by_ref = ("space",)
    _share_dicts = ()

    def __deepcopy__(self, memo):
        import copy as _copy

        cls = type(self)
        share_ref, share_dicts = _effective_share(cls)
        clone = cls.__new__(cls)
        memo[id(self)] = clone
        for key, value in self.__dict__.items():
            if key in share_ref:
                setattr(clone, key, value)
            elif key in share_dicts:
                setattr(clone, key, dict(value))
            else:
                setattr(clone, key, _copy.deepcopy(value, memo))
        return clone

    def __init__(self, space, seed=None, **params):
        if not isinstance(space, Space):
            raise TypeError(f"space must be a Space, got {type(space)}")
        self.space = space
        self._params = dict(params)
        self._seed = seed
        if seed is None:
            # Each unseeded instance gets its own stream (as the reference's
            # unseeded numpy RandomState does): concurrent workers sharing a
            # fixed default key would all suggest the IDENTICAL point
            # sequence and grind on DuplicateKeyError until SampleTimeout.
            import os

            seed = int.from_bytes(os.urandom(4), "little")
        self.rng_key = jax.random.PRNGKey(seed)
        # Observation history, host-side mirrors of device state.
        self._n_observed = 0

    # --- RNG ---------------------------------------------------------------
    def seed_rng(self, seed):
        """Reset the algorithm's PRNG stream (reference `base.py:121-128`)."""
        self._seed = seed
        self.rng_key = jax.random.PRNGKey(seed)

    def next_key(self):
        """Split off a fresh subkey (functional replacement for RandomState)."""
        self.rng_key, sub = jax.random.split(self.rng_key)
        return sub

    # --- state -------------------------------------------------------------
    def state_dict(self):
        """Serializable snapshot; must capture everything ``set_state`` needs
        to resume identically (reference `base.py:130-140`)."""
        return {
            "rng_key": np.asarray(self.rng_key).tolist(),
            "n_observed": self._n_observed,
        }

    def set_state(self, state):
        self.rng_key = jax.numpy.asarray(np.asarray(state["rng_key"], dtype=np.uint32))
        self._n_observed = state["n_observed"]

    # --- core contract -----------------------------------------------------
    def _materialize_batch(self, cube, num=None):
        """Decode a device cube (its first ``num`` rows, all by default) to
        a :class:`SuggestionBatch`: ONE bulk device->host transfer, where
        the host waits for the step (``algo.fetch``), then host-side decode
        (``algo.decode``) — per-dimension device decode would pay a
        host<->device round trip per dim (orion_tpu.space.dims host codec
        mirror)."""
        with TELEMETRY.span("algo.fetch"):
            cube = np.asarray(cube, dtype=np.float32)[:num]
        with TELEMETRY.span("algo.decode"):
            arrays = self.space.decode_flat_np(cube)
            params = self.space.arrays_to_params(
                arrays, fidelity_value=self._fidelity_for_new()
            )
        return SuggestionBatch(params, cube)

    def suggest(self, num=1):
        """Return ``num`` new points as a list of param dicts, or None to
        signal a temporary opt-out (producer backs off and retries).

        Deliberately does NOT route through :meth:`suggest_batch`: a
        subclass override of ``suggest`` that delegates to
        ``super().suggest()`` must reach this implementation directly
        (suggest_batch routes overriders back to ``self.suggest`` — going
        through it here would make that pattern infinitely recursive).
        """
        with TELEMETRY.span("algo.suggest"):
            cube = self._suggest_cube(num)
            if cube is None:
                return None
            return self._materialize_batch(cube).params

    def suggest_batch(self, num=1):
        """Columnar twin of :meth:`suggest`: returns a
        :class:`SuggestionBatch` (params + the raw cube rows) or None on
        opt-out.  This is the producer's entry point — suggestions flow as
        arrays and the per-point dicts are built exactly once, at the
        storage-document edge.

        Algorithms that override ``suggest`` itself with host-side
        scheduling (ASHA's promotions, grid cursors, plugins) are routed
        through their override and yield ``cube=None``.
        """
        if type(self).suggest is not BaseAlgorithm.suggest:
            params = self.suggest(num)
            return SuggestionBatch(params, None) if params is not None else None
        with TELEMETRY.span("algo.suggest"):
            cube = self._suggest_cube(num)
            if cube is None:
                return None
            return self._materialize_batch(cube)

    def _suggest_cube(self, num):
        raise NotImplementedError

    # --- asynchronous suggestion (device-overlap path) ----------------------
    def dispatch_suggest(self, num=1):
        """Start the device computation for ``num`` suggestions WITHOUT
        forcing the result to host.  Returns an opaque handle for
        :meth:`finalize_suggest`, or None (opt-out / unsupported).  The
        computation and the device->host transfer proceed in the background
        (jax async dispatch), so the caller can run trials, write storage,
        etc. before finalizing."""
        if not self.supports_async_suggest:
            return None
        cube = self._suggest_cube(num)
        if cube is None:
            return None
        return (num, cube)

    def finalize_suggest(self, handle):
        """Force a :meth:`dispatch_suggest` handle to concrete params.

        Like :meth:`suggest`, this is the direct implementation — it must
        not route through the batch twin, so subclass overrides delegating
        to ``super().finalize_suggest()`` cannot recurse."""
        num, cube = handle
        return self._materialize_batch(cube, num).params

    def finalize_suggest_batch(self, handle):
        """Columnar finalize: force a :meth:`dispatch_suggest` handle to a
        :class:`SuggestionBatch` — the dict build happens here, at the
        storage edge, once.  Plugins that override ``finalize_suggest``
        itself (custom handles / post-processing) are routed through their
        override and yield ``cube=None``."""
        if type(self).finalize_suggest is not BaseAlgorithm.finalize_suggest:
            return SuggestionBatch(self.finalize_suggest(handle), None)
        num, cube = handle
        return self._materialize_batch(cube, num)

    def _fidelity_for_new(self):
        """Fidelity assigned to fresh points (max budget unless multi-fidelity
        algorithms override with rung budgets)."""
        fid = self.space.fidelity
        return fid.high if fid is not None else None

    def observe(self, params_list, results, cube=None):
        """Feed evaluated points back.

        ``results`` is a list of dicts with at least ``objective`` (reference
        `base.py:165-191`).  The default implementation encodes points to the
        unit cube and forwards to :meth:`observe_arrays`.

        ``cube`` is the columnar fast path: pre-encoded ``(n, D)`` unit-cube
        rows for ``params_list``, as produced by ``Space.params_to_cube``
        (the producer caches these per trial).  When given, the per-point
        dict parse + encode is skipped entirely; the rows MUST be the
        ``params_to_cube`` encoding — feeding anything else (e.g. raw
        suggest-time cube rows for quantized dims) would diverge from the
        dict path.
        """
        if not params_list:
            return
        with TELEMETRY.span("algo.observe"):
            with TELEMETRY.span("algo.encode"):
                if cube is None:
                    cube = self.space.params_to_cube(params_list)
                else:
                    cube = np.asarray(cube, dtype=np.float32)
                    if cube.shape[0] != len(params_list):
                        raise ValueError(
                            f"cube has {cube.shape[0]} rows for "
                            f"{len(params_list)} params"
                        )
                objectives = np.asarray(
                    [float(r["objective"]) for r in results], dtype=np.float64
                )
                fidelities = None
                fid = self.space.fidelity
                if fid is not None:
                    from orion_tpu.space.params import ParamBatch

                    if isinstance(params_list, ParamBatch) and params_list.has_column(
                        fid.name
                    ):
                        # Columnar fast path: the fidelity column comes straight
                        # out of the batch view — no per-trial dict probes.
                        col = params_list.column(fid.name)
                    else:
                        col = [p[fid.name] for p in params_list]
                    fidelities = np.asarray(col, dtype=np.int64)
            self.observe_arrays(
                cube, objectives, params_list=params_list, fidelities=fidelities
            )
            self._n_observed += len(params_list)

    def observe_arrays(self, cube, objectives, params_list=None, fidelities=None):
        """Device-facing observation hook; default is stateless."""

    def register_suggestion(self, params):
        """Called by the producer after a suggested point is durably
        registered as a trial.  Algorithms with in-flight bookkeeping (ASHA's
        pending rung slots) override this so state survives across producer
        rounds — the *naive* copy that produced the suggestion is discarded
        every round."""

    def health_record(self):
        """One optimization-health snapshot dict, or None when the
        algorithm has nothing to report (the default).

        Contract (orion_tpu.health): host-side truth only from the
        instance itself (incumbent value, observation count, trust-region
        box, rung occupancy), device-side GP/acquisition fields unpacked
        from the last fused step's packed health vector — reading it must
        never force a device sync beyond transferring already-computed
        values.  The producer merges the real instance's host fields over
        the naive copy's device fields (the copy is the one that actually
        suggested, but its host history contains fantasy lies) and flushes
        one record per round through ``storage.record_health``."""
        return None

    @property
    def n_observed(self):
        return self._n_observed

    @property
    def is_done(self):
        """True when the algo cannot improve further (reference `base.py:193-196`)."""
        return False

    def score(self, params):  # pragma: no cover - default
        """Prior preference score for a candidate point (reference `base.py:198-208`)."""
        return 0

    def judge(self, params, measurements):  # pragma: no cover - default
        """Online early-stopping hook (reference `base.py:210-232`)."""
        return None

    @property
    def should_suspend(self):  # pragma: no cover - default
        return False

    # --- configuration -----------------------------------------------------
    @property
    def configuration(self):
        """Dict form used for storage/EVC comparison (reference `base.py:241-256`)."""
        name = type(self).__name__.lower()
        cfg = dict(self._params)
        if self._seed is not None:
            cfg["seed"] = self._seed
        return {name: cfg}


_BUILTIN_MODULES = (
    "random_search",
    "asha",
    "asha_bo",
    "bohb",
    "cmaes",
    "de",
    "hyperband",
    "grid_search",
    "tpe",
    "tpu_bo",
)


def _import_builtins():
    """Register built-in algorithms (entry points cover third-party ones)."""
    import importlib

    for mod in _BUILTIN_MODULES:
        try:
            importlib.import_module(f"orion_tpu.algo.{mod}")
        except ImportError:  # pragma: no cover - during incremental build only
            pass


def create_algo(space, config=None, seed=None):
    """Instantiate an algorithm from config.

    ``config`` is either a name string (``"random"``) or a one-key dict
    ``{"asha": {...kwargs}}`` like the reference's nested instantiation
    (`base.py:104-119`).  Unknown names raise with available choices listed.
    """
    _import_builtins()
    # Every algorithm instantiation path funnels through here: turn on the
    # persistent XLA compilation cache so repeated processes (workers,
    # benches, tests) skip the tens-of-seconds TPU compile per jit bucket.
    from orion_tpu.utils.jit_cache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    config = config or "random"
    if isinstance(config, str):
        name, kwargs = config, {}
    elif isinstance(config, dict):
        if len(config) != 1:
            raise ValueError(f"Algorithm config must have exactly one key: {config}")
        name, kwargs = next(iter(config.items()))
        kwargs = dict(kwargs or {})
    else:
        raise TypeError(f"Bad algorithm config {config!r}")
    if seed is not None:
        kwargs.setdefault("seed", seed)
    return algo_registry.create(name, space, **kwargs)
