"""Traffic schedules drawn from the seed before the window opens: who asks,
and (open loop) when each request is due. NumPy only; never imports JAX,
because the load generators' parent may not hold the chip.
"""

from __future__ import annotations

import numpy as np


def zipf_users(rng: np.random.Generator, n_users: int, exponent: float, count: int):
    """``count`` user indices: rank r of a seeded permutation of the user
    table is asked for with probability proportional to ``r^-exponent``."""
    weights = np.arange(1, n_users + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(count), side="right")
    np.minimum(ranks, n_users - 1, out=ranks)
    return rng.permutation(n_users)[ranks].astype(np.int64)


def poisson_due_times(rng: np.random.Generator, rate_qps: float, start_s: float, stop_s: float):
    """Due times of a Poisson process of ``rate_qps`` on ``[start_s, stop_s)``,
    in seconds relative to the window's start (the ramp is negative)."""
    span = stop_s - start_s
    n = int(rate_qps * span + 6.0 * np.sqrt(rate_qps * span) + 16)
    due = start_s + np.cumsum(rng.exponential(1.0 / rate_qps, n))
    return due[due < stop_s]


def open_loop_schedule(seed: int, n_users: int, traffic: dict, seconds: float):
    """``(due_s, users)`` for one open-loop run. The same seed, rate and
    length give the same schedule."""
    rng = np.random.default_rng([int(seed), 0])
    due = poisson_due_times(
        rng, float(traffic["rate_qps"]), -float(traffic["ramp_s"]), float(seconds)
    )
    users = zipf_users(rng, n_users, traffic["user_zipf_exponent"], len(due))
    return due, users


def closed_loop_users(seed: int, n_users: int, traffic: dict, count: int):
    """The users a closed loop asks for, in order; each connection takes the
    next one when its reply is in."""
    rng = np.random.default_rng([int(seed), 0])
    return zipf_users(rng, n_users, traffic["user_zipf_exponent"], count)
