"""The compiler plane: a process-wide registry of XLA compilations.

Every other plane of the stack is observable (spans, /metrics, health
records, flight events, doctor) but the plane that actually decides TPU
performance — the XLA compiler — was a black box: ``jax.retraces`` counted
synchronous stalls without ever explaining *which static changed*, compile
storms at bucket crossings had no budget, and nobody recorded what each
:class:`~orion_tpu.algo.tpu_bo.FusedPlan`'s compiled executable costs in
FLOPs and HBM bytes (ROADMAP item 1's "extend the q-scaling curve into the
HBM-bound regime" was unanswerable without hardware).  This module makes
the compiler a first-class telemetry plane:

- :class:`CompileRegistry` records, for every fused-plan/stacked/append jit
  compilation, the full static-arg **signature** (a flat field dict —
  ``fit_bucket``, ``width``, ``q``, every static), the compile wall time (a
  ``jax.compile`` span with the signature in args, histogrammed as
  ``jax.compile`` → ``orion_tpu_jax_compile_seconds`` on /metrics, counted
  as ``jax.compiles`` → ``..._jax_compiles_total``), and — lazily, on cold
  paths only — the compiled artifact's ``cost_analysis()`` /
  ``memory_analysis()`` numbers (FLOPs, bytes accessed, argument/output/
  temp/generated-code bytes → a per-plan **HBM footprint** and a predicted
  HBM-bound q for the current device).

- **Retrace attribution**: on every retrace the registry diffs the new
  signature against the nearest prior signature in the same plan family and
  emits a flight ``jax.retrace`` event (mirrored into the spans channel as
  ``flight.jax.retrace``) naming the changed statics — ``fit_bucket
  64→128``, ``q 256→512``, ``warm True→False`` — so ``retraces_after_warm
  == 0`` failures are self-diagnosing.  Prewarm completions record the
  signature they warmed, so an attributed retrace also says whether prewarm
  *should* have covered it (``jax.retraces.prewarm_covered`` — a firing
  count is a prewarm bug, doctor rule DX052).

Cost discipline: ``cost_analysis()`` via AOT ``lower().compile()`` is a
SECOND full XLA compile of the signature, so it must never run on the
synchronous suggest path or a /metrics scrape.  The registry stores a
zero-arg ``analysis_fn`` per entry and runs it only from declared cold
paths (:meth:`CompileRegistry.analyze_all` — bench, ``orion-tpu profile``,
tests).  Lint rule PERF003 pins exactly this: compiler introspection
outside this module is flagged.

Zero-overhead-when-disabled: every ``record_*`` mutator early-returns on
one ``TELEMETRY.enabled`` attribute read, allocating nothing — the same
discipline the telemetry registry itself keeps (TEL003/TEL004).
"""

import sys
import threading
from contextlib import contextmanager

from orion_tpu.analysis.sanitizer import TSAN
from orion_tpu.health import FLIGHT
from orion_tpu.telemetry import TELEMETRY

#: Diff rendering order: the fields operators reason about first (the
#: pow-2 buckets and the warm flag) lead; everything else is alphabetical.
_FIELD_PRIORITY = ("fit_bucket", "width", "q", "warm", "fit_steps")

#: Fallback per-device HBM capacities by ``device_kind`` substring, used
#: when ``device.memory_stats()`` exposes no ``bytes_limit`` (interop
#: backends).  Sources: the public TPU system architecture tables.
_HBM_CAPACITY_BY_KIND = (
    ("v5e", 16e9),
    ("v5p", 95e9),
    ("v4", 32e9),
    ("v3", 32e9),
    ("v2", 16e9),
    ("v6e", 32e9),
)


def signature_fields(shape, statics):
    """Flatten a fused-plan-style signature — the ``(x.shape, statics)``
    pair the coalescer and prewarmer already key on — into the registry's
    comparable field dict: ``fit_bucket``/``width`` from the fit-buffer
    shape, every static stringified exactly as the plan signature does
    (``str(v)``), so a prewarm-recorded signature and the retrace-recorded
    one can never disagree on formatting."""
    fields = {"fit_bucket": int(shape[0]), "width": int(shape[1])}
    items = statics.items() if isinstance(statics, dict) else statics
    for key, value in items:
        fields[str(key)] = value if isinstance(value, str) else str(value)
    return fields


def fields_from_plan_signature(signature):
    """Field dict from a :class:`FusedPlan`'s ``signature`` attribute
    (``(tuple(x.shape), tuple(sorted((k, str(v)) ...)))``)."""
    shape, items = signature
    return signature_fields(shape, items)


def _field_order(key):
    try:
        return (_FIELD_PRIORITY.index(key), key)
    except ValueError:
        return (len(_FIELD_PRIORITY), key)


def diff_fields(old, new):
    """``["fit_bucket 64→128", ...]`` — every field differing between two
    signature field dicts, priority fields first."""
    changed = []
    for key in sorted(set(old) | set(new), key=_field_order):
        a, b = old.get(key), new.get(key)
        if a != b:
            changed.append(f"{key} {a}→{b}")
    return changed


def format_fields(fields):
    """One-line signature rendering for span args and tables."""
    return " ".join(
        f"{k}={fields[k]}" for k in sorted(fields, key=_field_order)
    )


def _fields_key(fields):
    return tuple(sorted(fields.items()))


def jit_cache_size(fn):
    """Entry count of a jitted function's call cache via the private
    ``_cache_size`` accessor, or None when unavailable — the shared probe
    behind every retrace bracket (growth during a call = a compile)."""
    accessor = getattr(fn, "_cache_size", None)
    if accessor is None:
        return None
    try:
        return accessor()
    except Exception:  # private jax API — degrade, never raise
        return None


def analysis_from_compiled(compiled):
    """Cost/memory numbers off one ``Compiled`` executable, every field
    None-degrading (interop backends return None or partial dicts).

    Returns ``{"flops", "bytes_accessed", "argument_bytes", "output_bytes",
    "temp_bytes", "generated_code_bytes", "hbm_bytes", "tpu_custom_calls"}``
    — ``hbm_bytes`` is the per-plan HBM footprint: arguments + outputs +
    temporaries + generated code, i.e. what the executable pins while
    running; ``tpu_custom_calls`` counts the Pallas (Mosaic) kernels in the
    compiled text — 0 means the fused gram did not dispatch."""
    out = {
        "flops": None,
        "bytes_accessed": None,
        "argument_bytes": None,
        "output_bytes": None,
        "temp_bytes": None,
        "generated_code_bytes": None,
        "hbm_bytes": None,
        "tpu_custom_calls": None,
    }
    try:
        out["tpu_custom_calls"] = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"'
        )
    except Exception:  # pragma: no cover - backend quirk, degrade
        pass
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # one entry per device
            cost = cost[0] if cost else None
        if cost:
            flops = cost.get("flops")
            out["flops"] = float(flops) if flops is not None else None
            accessed = cost.get("bytes accessed")
            out["bytes_accessed"] = (
                float(accessed) if accessed is not None else None
            )
    except Exception:  # pragma: no cover - backend quirk, degrade
        pass
    try:
        mem = compiled.memory_analysis()
        if isinstance(mem, (list, tuple)):
            mem = mem[0] if mem else None
        if mem is not None:
            pairs = (
                ("argument_bytes", "argument_size_in_bytes"),
                ("output_bytes", "output_size_in_bytes"),
                ("temp_bytes", "temp_size_in_bytes"),
                ("generated_code_bytes", "generated_code_size_in_bytes"),
            )
            total = 0.0
            seen = False
            for field, attr in pairs:
                value = getattr(mem, attr, None)
                if value is None:
                    continue
                out[field] = float(value)
                total += float(value)
                seen = True
            if seen:
                out["hbm_bytes"] = total
    except Exception:  # pragma: no cover - backend quirk, degrade
        pass
    return out


def lowered_analysis_fn(jitted, arrays, statics):
    """Zero-arg cold-path analysis closure for a jit call site.

    Captures ``ShapeDtypeStruct`` specs (never the arrays — an analysis
    entry must not pin device buffers) and, when invoked, pays the AOT
    ``lower().compile()`` — a SECOND full XLA compile of the signature,
    which is exactly why this closure only ever runs from
    :meth:`CompileRegistry.analyze_all` on declared cold paths."""
    import jax

    specs = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), tuple(arrays)
    )
    statics = dict(statics)

    def analyze():
        compiled = jitted.lower(*specs, **statics).compile()
        return analysis_from_compiled(compiled)

    return analyze


def device_hbm_capacity(device=None):
    """Accelerator memory capacity in bytes for ``device`` (default: the
    first local device), or None when unknowable (CPU interop backends) —
    the denominator of the HBM-headroom line and doctor rule DX053."""
    try:
        import jax

        device = device if device is not None else jax.devices()[0]
    except Exception:
        return None
    stats = getattr(device, "memory_stats", None)
    if callable(stats):
        try:
            limit = (stats() or {}).get("bytes_limit")
            if limit:
                return int(limit)
        except Exception:
            pass
    kind = str(getattr(device, "device_kind", "")).lower()
    for needle, capacity in _HBM_CAPACITY_BY_KIND:
        if needle in kind:
            return int(capacity)
    return None


def predict_hbm_bound_q(fields, hbm_bytes, capacity):
    """Predicted q at which this plan's HBM footprint fills the device.

    The fused step's dominant buffers (candidate pool, q-batch posterior
    draws, temporaries) scale ~linearly in q at fixed history bucket, so
    ``q_bound ≈ q · capacity / hbm_bytes`` extrapolates the measured
    footprint to the HBM-bound regime — the answer to ROADMAP item 1's
    open tail, without hardware.  None when any input is unknown."""
    if not hbm_bytes or not capacity:
        return None
    try:
        q = int(fields.get("q"))
    except (TypeError, ValueError):
        return None
    if q <= 0:
        return None
    return int(q * float(capacity) / float(hbm_bytes))


class _Entry:
    """One recorded compilation: family + signature fields + wall seconds
    + the lazy cost/memory analysis."""

    __slots__ = ("family", "fields", "seconds", "kind", "analysis_fn", "cost")

    def __init__(self, family, fields, seconds, kind, analysis_fn):
        self.family = family
        self.fields = dict(fields)
        self.seconds = seconds
        self.kind = kind
        self.analysis_fn = analysis_fn
        self.cost = None

    def as_dict(self):
        out = {
            "family": self.family,
            "kind": self.kind,
            "signature": format_fields(self.fields),
            "compile_ms": (
                round(self.seconds * 1e3, 3) if self.seconds is not None else None
            ),
        }
        cost = self.cost or {}
        out["flops"] = cost.get("flops")
        out["bytes_accessed"] = cost.get("bytes_accessed")
        out["hbm_bytes"] = cost.get("hbm_bytes")
        return out


class CompileRegistry:
    """Process-wide record of jit compilations, keyed by plan family.

    Families are the stack's jit call sites: ``fused_plan`` (the fused
    suggest step), ``stacked`` (the gateway's coalesced dispatch),
    ``append`` (the device-history append twins).  Thread-safe — prewarm
    compiles record from their background threads."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = []
        self._warmed = {}
        self._cost_cache = {}
        self._retraces = 0
        self._attributed = 0
        self._prewarm_covered = 0

    # --- recording (hot-path adjacent: one enabled check, then cold) -----
    def record_compile(self, family, fields, seconds=None, kind="compile",
                       analysis_fn=None):
        """Book one compilation.  Emits the ``jax.compiles`` counter and a
        ``jax.compile`` span carrying the plan signature in args (the span
        feeds the ``jax.compile`` histogram → compile_seconds on
        /metrics).  Returns the entry, or None when telemetry is off."""
        if not TELEMETRY.enabled:
            return None
        entry = _Entry(family, fields, seconds, kind, analysis_fn)
        with self._lock:
            TSAN.write("CompileRegistry._entries", self)
            self._entries.append(entry)
        TELEMETRY.count("jax.compiles")
        TELEMETRY.record_span(
            "jax.compile",
            duration=seconds or 0.0,
            args={
                "family": family,
                "kind": kind,
                "signature": format_fields(entry.fields),
            },
        )
        return entry

    def record_prewarm(self, family, fields, seconds=None, analysis_fn=None):
        """Book a background prewarm compile AND remember the signature it
        warmed — a later retrace at this exact signature is a prewarm bug
        (the warm should have made it a jit-cache hit)."""
        if not TELEMETRY.enabled:
            return None
        entry = self.record_compile(
            family, fields, seconds=seconds, kind="prewarm",
            analysis_fn=analysis_fn,
        )
        if entry is not None:
            with self._lock:
                TSAN.write("CompileRegistry._entries", self)
                self._warmed.setdefault(family, set()).add(
                    _fields_key(entry.fields)
                )
        return entry

    def record_retrace(self, family, fields, seconds=None, analysis_fn=None):
        """Book a synchronous retrace WITH attribution.

        Diffs ``fields`` against the nearest prior signature in the same
        family (fewest differing fields; ties go to the most recent) and
        emits a flight ``jax.retrace`` event naming the changed statics.
        Counts ``jax.retraces.attributed`` (the smoke gate's invariant:
        every ``jax.retraces`` sample must have a twin here) and
        ``jax.retraces.prewarm_covered`` when a completed prewarm recorded
        this exact signature.  Returns the attribution dict."""
        if not TELEMETRY.enabled:
            return None
        entry = _Entry(family, fields, seconds, "retrace", analysis_fn)
        with self._lock:
            TSAN.write("CompileRegistry._entries", self)
            nearest = None
            nearest_diff = None
            for prior in reversed(self._entries):
                if prior.family != family:
                    continue
                candidate = diff_fields(prior.fields, entry.fields)
                if nearest_diff is None or len(candidate) < len(nearest_diff):
                    nearest, nearest_diff = prior, candidate
                    if not candidate:
                        break
            covered = _fields_key(entry.fields) in self._warmed.get(
                family, ()
            )
            self._entries.append(entry)
            self._retraces += 1
            self._attributed += 1
            if covered:
                self._prewarm_covered += 1
        if nearest is None:
            changed = [f"first {family} signature (cold start)"]
        elif not nearest_diff:
            changed = ["identical signature (jit cache evicted or bypassed)"]
        else:
            changed = nearest_diff
        TELEMETRY.count("jax.retraces.attributed")
        if covered:
            TELEMETRY.count("jax.retraces.prewarm_covered")
        TELEMETRY.count("jax.compiles")
        TELEMETRY.record_span(
            "jax.compile",
            duration=seconds or 0.0,
            args={
                "family": family,
                "kind": "retrace",
                "signature": format_fields(entry.fields),
                "changed": "; ".join(changed),
            },
        )
        if FLIGHT.enabled:
            FLIGHT.record(
                "jax.retrace",
                args={
                    "family": family,
                    "changed": "; ".join(changed),
                    "covered_by_prewarm": covered,
                    "signature": format_fields(entry.fields),
                },
            )
        return {
            "family": family,
            "changed": changed,
            "covered_by_prewarm": covered,
            "against": dict(nearest.fields) if nearest is not None else None,
        }

    # --- cold-path analysis ----------------------------------------------
    def analyze_all(self, families=None, limit=None):
        """Run the pending cost/memory analyses — each one an AOT
        ``lower().compile()``, a SECOND full XLA compile, which is why
        this only runs from declared cold paths (bench's compiler block,
        ``orion-tpu profile``, tests).  Deduplicates by exact signature
        (a prewarm and the retrace it failed to cover share one analysis)
        and returns ``{"analyzed", "skipped"}`` so callers that bound the
        work (``limit``) can report the cap instead of silently
        truncating."""
        with self._lock:
            TSAN.read("CompileRegistry._entries", self)
            pending = [
                e for e in self._entries
                if e.analysis_fn is not None
                and (families is None or e.family in families)
            ]
        analyzed = skipped = 0
        done = set()
        for entry in pending:
            fields_key = _fields_key(entry.fields)
            key = (entry.family, fields_key)
            if key in done:
                continue
            done.add(key)
            with self._lock:
                cached = self._cost_cache.get(key)
            if cached is None:
                if limit is not None and analyzed >= limit:
                    skipped += 1
                    continue
                try:
                    # Outside the lock on purpose: this is a full XLA
                    # compile and must not block dispatch-side recording.
                    cached = entry.analysis_fn()
                except Exception:  # degrade: analysis must never break a bench
                    cached = None
                analyzed += 1
                if cached is not None:
                    with self._lock:
                        self._cost_cache[key] = cached
            self._apply_cost(entry.family, fields_key, cached)
        self.publish_gauges()
        return {"analyzed": analyzed, "skipped": skipped}

    def _apply_cost(self, family, key, cost):
        with self._lock:
            TSAN.write("CompileRegistry._entries", self)
            for entry in self._entries:
                if (
                    entry.family == family
                    and _fields_key(entry.fields) == key
                ):
                    entry.cost = cost

    # --- surfacing --------------------------------------------------------
    def publish_gauges(self):
        """Refresh the ``compiler.*`` gauges from already-analyzed entries
        (no compiles here — safe per /metrics scrape)."""
        if not TELEMETRY.enabled:
            return
        summary = self.summary()
        if summary["compile_ms_total"] is not None:
            TELEMETRY.set_gauge(
                "compiler.compile_ms_total", summary["compile_ms_total"]
            )
        if summary["plan_hbm_bytes_max"] is not None:
            TELEMETRY.set_gauge(
                "compiler.hbm_bytes_max", summary["plan_hbm_bytes_max"]
            )
        if summary["hbm_capacity_bytes"] is not None:
            TELEMETRY.set_gauge(
                "compiler.hbm_capacity_bytes", summary["hbm_capacity_bytes"]
            )
        if summary["hbm_bound_q"] is not None:
            TELEMETRY.set_gauge("compiler.hbm_bound_q", summary["hbm_bound_q"])

    def entries(self, family=None):
        with self._lock:
            TSAN.read("CompileRegistry._entries", self)
            return [
                e for e in self._entries
                if family is None or e.family == family
            ]

    def summary(self):
        """The JSON-able registry digest: totals + the per-plan table —
        the bench payload's ``compiler`` block and ``orion-tpu profile``'s
        local leg both render exactly this."""
        with self._lock:
            TSAN.read("CompileRegistry._entries", self)
            entries = list(self._entries)
            retraces = self._retraces
            attributed = self._attributed
            covered = self._prewarm_covered
        per_plan = [e.as_dict() for e in entries]
        seconds = [e.seconds for e in entries if e.seconds is not None]
        hbm = [
            e.cost["hbm_bytes"]
            for e in entries
            if e.cost and e.cost.get("hbm_bytes")
        ]
        capacity = device_hbm_capacity()
        bound_qs = [
            q
            for q in (
                predict_hbm_bound_q(
                    e.fields, (e.cost or {}).get("hbm_bytes"), capacity
                )
                for e in entries
            )
            if q is not None
        ]
        return {
            "compiles": len(entries),
            "compile_ms_total": (
                round(sum(seconds) * 1e3, 3) if seconds else None
            ),
            "retraces": retraces,
            "retraces_attributed": attributed,
            "retraces_prewarm_covered": covered,
            "plan_hbm_bytes_max": max(hbm) if hbm else None,
            "hbm_capacity_bytes": capacity,
            "hbm_bound_q": min(bound_qs) if bound_qs else None,
            "per_plan": per_plan,
        }

    def reset(self):
        """Tests only — the registry is process-wide state."""
        with self._lock:
            TSAN.write("CompileRegistry._entries", self)
            self._entries = []
            self._warmed = {}
            self._cost_cache = {}
            self._retraces = 0
            self._attributed = 0
            self._prewarm_covered = 0


#: THE process-wide registry — every jit family records here, exactly as
#: every span lands in the one TELEMETRY ring.
COMPILE_REGISTRY = CompileRegistry()


@contextmanager
def profiler_capture(directory):
    """One shared ``jax.profiler`` capture path: ``hunt --profile`` wraps
    the whole worker loop in this, ``orion-tpu profile --capture DIR``
    wraps its registry-analysis pass — both print the SAME artifact
    summary line, so tooling that greps for the trace location works on
    either."""
    import jax

    jax.profiler.start_trace(directory)
    try:
        yield directory
    finally:
        jax.profiler.stop_trace()
        print(f"jax profiler trace written to {directory}", file=sys.stderr)
