"""The cells' objectives in float64 NumPy, on points of the unit cube.

Written from the published definitions (Surjanovic & Bingham, "Virtual
Library of Simulation Experiments"), not from the program: the benchmark
evaluates suggestions with these, off the chip, as a user's jobs would.
Each function maps an (n, d) array in [0, 1]^d to (n,) values.
"""

import numpy as np

_H6_ALPHA = np.array([1.0, 1.2, 3.0, 3.2])
_H6_A = np.array(
    [
        [10.0, 3.0, 17.0, 3.5, 1.7, 8.0],
        [0.05, 10.0, 17.0, 0.1, 8.0, 14.0],
        [3.0, 3.5, 1.7, 10.0, 17.0, 8.0],
        [17.0, 8.0, 0.05, 10.0, 0.1, 14.0],
    ]
)
_H6_P = 1e-4 * np.array(
    [
        [1312.0, 1696.0, 5569.0, 124.0, 8283.0, 5886.0],
        [2329.0, 4135.0, 8307.0, 3736.0, 1004.0, 9991.0],
        [2348.0, 1451.0, 3522.0, 2883.0, 3047.0, 6650.0],
        [4047.0, 8828.0, 8732.0, 5743.0, 1091.0, 381.0],
    ]
)


def hartmann6(u):
    """Hartmann 6-D on [0, 1]^6; global minimum -3.32237."""
    u = np.asarray(u, np.float64)
    inner = np.einsum("ij,nij->ni", _H6_A, (u[:, None, :] - _H6_P[None]) ** 2)
    return -np.exp(-inner) @ _H6_ALPHA


def rosenbrock(u, low=-5.0, high=10.0):
    """Rosenbrock n-D on [-5, 10]^n (the unit cube scaled); minimum 0."""
    x = low + np.asarray(u, np.float64) * (high - low)
    return np.sum(100.0 * (x[:, 1:] - x[:, :-1] ** 2) ** 2 + (1.0 - x[:, :-1]) ** 2, axis=1)


def ackley(u, low=-32.768, high=32.768):
    """Ackley n-D on [-32.768, 32.768]^n (the unit cube scaled); minimum 0."""
    x = low + np.asarray(u, np.float64) * (high - low)
    d = x.shape[1]
    term1 = -20.0 * np.exp(-0.2 * np.sqrt(np.sum(x * x, axis=1) / d))
    term2 = -np.exp(np.sum(np.cos(2.0 * np.pi * x), axis=1) / d)
    return term1 + term2 + 20.0 + np.e


OBJECTIVES = {"hartmann6": hartmann6, "rosenbrock": rosenbrock, "ackley": ackley}
