"""Mean device milliseconds of one execution of a compiled program in the
traced slice, from the trace's ``XLA Modules`` line: the program is named as
XLA names it, ``jit_<function>``."""


def read(run, program: str):
    if run.trace is None or program not in run.trace.programs:
        return None
    row = run.trace.programs[program]
    return 1e3 * row["seconds"] / row["count"]
