"""The random numbers of one suggest round, drawn again from its key.

The algorithm states its randomness as JAX PRNG draws from the key it
splits off per round: ``key = split(rng_key)[1]``, then
``k_cand, k_acq = split(key)``. The global part of the candidate pool is
``uniform(fold_in(split(k_cand, 7)[0], 1), (n_global, d))``; the Thompson
draw takes ``k_w, k_g, k_b, k_theta = split(k_acq, 4)`` for the feature
frequencies (normal, and a gamma for the Student-t), the phases and the
weight noise. Only the bits come from ``jax.random``; the reference does
all arithmetic on them in NumPy.
"""

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference.thompson import N_FEATURES


def global_pool(k_cand, d, n_global):
    k1 = jax.random.split(k_cand, 7)[0]
    return jax.random.uniform(jax.random.fold_in(k1, 1), (n_global, d))


def thompson_noise(k_acq, d, q):
    k_w, k_g, k_b, k_theta = jax.random.split(k_acq, 4)
    return {
        "z": jax.random.normal(k_w, (N_FEATURES, d), dtype=jnp.float32),
        "g": 2.0 * jax.random.gamma(k_g, 2.5, (N_FEATURES, 1), dtype=jnp.float32),
        "b": jax.random.uniform(k_b, (N_FEATURES,), dtype=jnp.float32, maxval=2.0 * jnp.pi),
        "eps": jax.random.normal(k_theta, (N_FEATURES, q), dtype=jnp.float32),
    }


@partial(jax.jit, static_argnames=("d", "q", "n_global"))
def _draw(rng_key, *, d, q, n_global):
    next_key, key = jax.random.split(rng_key)
    k_cand, k_acq = jax.random.split(key)
    out = thompson_noise(k_acq, d, q)
    out["pool"] = global_pool(k_cand, d, n_global)
    out["next_key"] = next_key
    return out


def round_draws(rng_key, d, q, n_candidates, local_frac):
    """Host copies of the round's global candidates and Thompson noise, and
    the key the algorithm holds after one suggest."""
    n_global = n_candidates - int(n_candidates * local_frac)
    return jax.device_get(_draw(rng_key, d=d, q=q, n_global=n_global))
