"""Persistent XLA compilation cache for the framework's jitted steps.

The fused suggest step compiles once per (padded-buffer-size, q-bucket,
config) signature; a TPU compile costs tens of seconds, and every fresh
process pays it again for each bucket its history growth crosses.  Pointing
jax at an on-disk cache makes every later process warm.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory, which jax itself reads
  at import; no code here sets another.
- unset: ``<checkout>/.jax_cache`` (git-ignored).  The path is part of the
  cache key, so it is fixed — a directory under ``$HOME`` would also be
  thrown away by machines that keep only the checkout.

``ORION_TPU_JIT_CACHE=off`` turns the cache off.
"""

import logging
import os

log = logging.getLogger(__name__)

_DISABLE = ("0", "off", "false", "no")

#: The checkout this package lives in (``orion_tpu/utils/`` -> root).
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_compilation_cache():
    """Idempotent; returns the cache dir in effect (None when disabled)."""
    import jax

    configured = jax.config.jax_compilation_cache_dir
    if configured:  # JAX_COMPILATION_CACHE_DIR, or an earlier call
        return configured
    if os.environ.get("ORION_TPU_JIT_CACHE", "").strip().lower() in _DISABLE:
        return None
    try:
        os.makedirs(CHECKOUT_CACHE_DIR, exist_ok=True)
    except OSError as exc:  # read-only checkout
        log.debug("persistent compilation cache unavailable: %s", exc)
        return None
    # Catch the acquisition sub-jits too; below ~0.5s the write
    # amplification outweighs the win.  Respect a user-tuned threshold
    # (only replace jax's default), and set the dir LAST so the return
    # value always matches the enabled/disabled state.
    if jax.config.jax_persistent_cache_min_compile_time_secs == 1.0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
