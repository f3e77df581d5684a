"""One ALS iteration's share of its bandwidth roofline: the HBM bytes the
formulation requires (``benchmark/shapes.solver_hbm_bytes_per_iter`` at the
block counts the train reported) over peak bytes a second, over the step
program's mean device time from the trace. The model counts no operations:
the solver is bound by bandwidth by construction of the model."""

from benchmark import shapes


def read(run, program: str):
    trace, peak, t = run.trace, run.peak, run.timings
    if trace is None or peak is None or t is None or program not in trace.programs:
        return None
    s = run.shapes
    nbytes = shapes.solver_hbm_bytes_per_iter(
        t["nb_u"], t["nb_i"], t["d"], s["rank"], s["n_users"], s["n_items"],
        gather_dtype=s["gather_dtype"], solver=s["solver"], implicit=s["implicit"],
    )
    row = trace.programs[program]
    return 100.0 * nbytes / peak["hbm_bytes_per_s"] / (row["seconds"] / row["count"])
