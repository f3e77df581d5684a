"""The plain references that decide ``correct``. Nothing here imports the
program's ``ops/``: serving is checked against ``top_k(V @ u)`` in plain
``jax.numpy`` at float32 ``Precision.HIGHEST``, one query at a time, and a
train against a NumPy float64 solve of single items' normal equations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# |served score - reference score| may reach this share of Σ_f |u_f · v_f|:
# what ONE bf16 pass on the MXU guarantees (each operand rounded to 8 bits of
# mantissa, 2^-9 each). The batched serve program runs its score product that
# way on the chip today: XLA's default precision for a float32 dot at
# [B, 128] x [128, 5.7 M] converts the operands to bf16, and the served
# scores differ from the float32 reference by up to 1.54e-3 = 2^-9.3 of
# Σ|u·v| (my chip runs, PR 23, 24 runs; PR 22's 2^-23 was the single-query
# matrix-vector program, which never reaches the MXU). A benchmark PR may not
# change the program, so the tolerance is the bound of what runs: anything
# lower than one bf16 pass (fp8, int8 tables) fails it and has to say so, and
# a later benchmark PR tightens it to 2^-16 once the program asks for float32.
SCORE_TOLERANCE_FACTOR = 2.0**-8

_HIGHEST = lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_reference(user_factors, item_factors, uidx, served_ids, k: int):
    u = user_factors[uidx]
    scores = jnp.dot(item_factors, u, precision=_HIGHEST)
    magnitude = jnp.dot(jnp.abs(item_factors), jnp.abs(u), precision=_HIGHEST)
    ref_scores, ref_ids = lax.top_k(scores, k)
    return ref_scores, ref_ids, scores[served_ids], magnitude[served_ids], magnitude[ref_ids]


def check_topk(user_factors, item_factors, uidx: int, served_ids, served_scores):
    """Is one served answer the reference's? ``(ok, worst)``, where
    ``worst`` is the largest |Δscore| ÷ Σ|u·v| over the served items.

    Every served score lies within the tolerance of the reference's score
    for that item, and the ids are the reference's ids in its order, except
    where the reference itself scores the two candidates for a place within
    the tolerance (a tie that two float32 summation orders may break
    differently)."""
    served_ids = np.asarray(served_ids, np.int32)
    served_scores = np.asarray(served_scores, np.float32)
    k = int(served_ids.shape[0])
    ref_scores, ref_ids, at_served, mag_served, mag_ref = (
        np.asarray(x)
        for x in _topk_reference(
            user_factors, item_factors, jnp.int32(uidx), jnp.asarray(served_ids), k
        )
    )
    rel = np.abs(served_scores - at_served) / mag_served
    ok = bool(np.all(rel <= SCORE_TOLERANCE_FACTOR))
    differ = served_ids != ref_ids
    tie = np.abs(ref_scores - at_served) <= SCORE_TOLERANCE_FACTOR * np.maximum(
        mag_served, mag_ref
    )
    ok = ok and bool(np.all(~differ | tie)) and len(set(served_ids.tolist())) == k
    return ok, float(rel.max())


def item_half_step_check(
    user_factors: np.ndarray,
    item_factors: np.ndarray,
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    chosen: np.ndarray,
    reg: float,
) -> tuple[float, float]:
    """The last half-step of an explicit ALS train, checked item by item.

    ``_als_step`` solves the users first and the items last, from the new
    user factors, so the returned row y of item i solves
    ``A y = b`` with ``A = Σ x_u x_uᵀ + reg·max(n_i, 1)·I`` and
    ``b = Σ r_ui x_u`` over the n_i users who rated i (``reg_scaling``
    "auto" resolves to the rating-count-scaled ALS-WR form for explicit
    feedback: ``ops/als._solve_blocked``). A and b are formed here in
    float64 from the returned user factors. Returns, each the largest over
    ``chosen``: the residual ``‖A y − b‖ ÷ ‖b‖``, which a system's
    conditioning does not amplify and which ``correct`` is held to, and the
    distance ``‖y − A⁻¹b‖ ÷ ‖A⁻¹b‖`` to the float64 solution, recorded
    beside it.
    """
    wanted = np.zeros(item_factors.shape[0], bool)
    wanted[chosen] = True
    sel = wanted[items]
    s_items, s_users, s_ratings = items[sel], users[sel], ratings[sel]
    order = np.argsort(s_items, kind="stable")
    s_items, s_users, s_ratings = s_items[order], s_users[order], s_ratings[order]
    starts = np.searchsorted(s_items, chosen, side="left")
    stops = np.searchsorted(s_items, chosen, side="right")
    f = user_factors.shape[1]
    worst_residual = worst_distance = 0.0
    for item, lo, hi in zip(chosen.tolist(), starts.tolist(), stops.tolist()):
        X = user_factors[s_users[lo:hi]].astype(np.float64)
        b = X.T @ s_ratings[lo:hi].astype(np.float64)
        A = X.T @ X + reg * max(hi - lo, 1) * np.eye(f)
        y = item_factors[item].astype(np.float64)
        exact = np.linalg.solve(A, b)
        worst_residual = max(worst_residual, float(np.linalg.norm(A @ y - b) / np.linalg.norm(b)))
        worst_distance = max(
            worst_distance, float(np.linalg.norm(y - exact) / np.linalg.norm(exact))
        )
    return worst_residual, worst_distance


def heldout_rmse(user_factors, item_factors, users, items, ratings) -> float:
    pred = np.einsum("ij,ij->i", user_factors[users], item_factors[items])
    return float(np.sqrt(np.mean((pred - ratings) ** 2)))
