"""Operations and bytes of the measured programs, as functions of their
shapes: the numerators of the roofline shares. The yardstick's own
arithmetic: it imports nothing from the program, so a change to the
program's traffic model neither moves these numbers nor breaks them.

``solver_hbm_bytes_per_iter`` is a copy of
``predictionio_tpu.ops.als.solver_hbm_bytes_per_iter`` as it stood at commit
c4ac7c0 (PERF.md records that the two agreed on the day it was taken).
"""

from __future__ import annotations


def serve_batch_flops(batch: float, n_items: int, rank: int) -> float:
    """The ``[B, f] @ [f, n]`` score product: one multiply and one add for
    every query, item and factor."""
    return 2.0 * batch * n_items * rank


def serve_batch_bytes(batch: float, n_items: int, rank: int) -> float:
    """What one batched serve as the program writes it has to move: the
    float32 item table read once (``n·f·4``), and the ``[B, n]`` float32
    scores written by the product, rewritten by the mask and read by the
    top-k (``3·B·n·4``). The gathered user rows and the ``[B, 2, k]``
    result are thousands of times smaller and are left out. A fused
    score-and-select kernel would not move the scores at all: a share
    above 100% of this bound is such a kernel, not an error."""
    return float(n_items) * rank * 4 + 3.0 * batch * n_items * 4


def solver_hbm_bytes_per_iter(
    nb_u: int,
    nb_i: int,
    d: int,
    f: int,
    n_users: int,
    n_items: int,
    *,
    gather_dtype: str = "f32",
    solver: str = "cg",
    implicit: bool = False,
) -> int:
    """HBM traffic one ALS iteration (both half-solves) requires, in bytes.

    Per half-solve with NB blocks of width d over n_ent (+1 dummy) entities:
    block stream ``NB·d·(9 + f·gb)`` (cols int32, vals f32, mask int8 and
    the gathered factor rows of ``f·gb`` bytes, gb = 4, or 2 under a bf16
    gather); Gram scatter-adds ``2·NB·(f²+f+1)·4``; assembly
    ``2·n_ent·f²·4``; the solve: stock ``cg`` re-reads the systems in each
    of its f+4 matvecs and moves about eight f-vectors a step,
    ``(f+4)·n_ent·(f²+8f)·4``, any other solver is counted as two passes,
    ``2·n_ent·f²·4``; implicit mode adds one read of the opposite table.
    """
    gb = 2 if gather_dtype == "bf16" else 4
    total = 0
    for nb, n_ent, n_opp in (
        (nb_u, n_users + 1, n_items + 1),
        (nb_i, n_items + 1, n_users + 1),
    ):
        stream = nb * d * (9 + f * gb)
        gram_scatter = 2 * nb * (f * f + f + 1) * 4
        assemble = 2 * n_ent * f * f * 4
        if solver == "cg":
            solve = (f + 4) * n_ent * (f * f + 8 * f) * 4
        else:
            solve = 2 * n_ent * f * f * 4
        shared = n_opp * f * 4 if implicit else 0
        total += stream + gram_scatter + assemble + solve + shared
    return int(total)


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict):
    """``(share in %, which bound)``: the least time the chip could take,
    the larger of operations over peak FLOP/s and bytes over peak bytes/s,
    over the time it took."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops > t_bytes else "bandwidth"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
