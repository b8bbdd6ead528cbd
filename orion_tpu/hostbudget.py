"""The host-budget factor — ONE source of truth for every consumer.

ROADMAP item 5 (the host-tail endgame) tightens the steady-state bar to
``host_ms_per_round <= 1.25 x device_ms_per_round``.  Three consumers
read the SAME knob so they can never drift apart:

- ``bench.py``'s ``_check_host_budget`` (WARN on full runs, hard
  SystemExit under ``--smoke``);
- the doctor's DX004 ``HostBudgetBreach`` rule — its threshold over the
  ``producer.round`` / ``device.dispatch`` histogram means is derived as
  ``1.0 + host_budget_factor()`` because the producer round CONTAINS the
  device window (host tax of F x device makes the round (1+F) x device);
- ``orion-tpu top``/``info``'s live host/device ratio column, which
  flags workers over the same derived bar.

``ORION_TPU_HOST_BUDGET_FACTOR`` overrides everywhere at once, so an
unusual runner (e.g. a host with pathological transfer latency) re-tunes
the whole stack without editing any gate.
"""

import logging
import os

log = logging.getLogger(__name__)

#: The ROADMAP item-5 bar: host tax per steady-state round may cost at
#: most this multiple of the device time (was 2.0 through ISSUE 13).
DEFAULT_HOST_BUDGET_FACTOR = 1.25

ENV_VAR = "ORION_TPU_HOST_BUDGET_FACTOR"


def host_budget_factor():
    """The effective host-budget factor: env override, else the default.

    Read at call time (not import time) so a test or runner can flip the
    env var without re-importing every consumer.  A malformed override
    falls back to the default (warned once per call site's logger config)
    rather than crashing the bench, the doctor AND the CLIs together."""
    raw = os.environ.get(ENV_VAR, "").strip()
    if raw:
        try:
            return float(raw)
        except ValueError:
            log.warning(
                "ignoring malformed %s=%r (want a float); using %s",
                ENV_VAR, raw, DEFAULT_HOST_BUDGET_FACTOR,
            )
    return DEFAULT_HOST_BUDGET_FACTOR


def round_budget_factor():
    """DX004's derived threshold over ``producer.round`` vs
    ``device.dispatch``: the round INCLUDES the device window, so a host
    budget of F x device bounds the whole round at (1 + F) x device."""
    return 1.0 + host_budget_factor()
