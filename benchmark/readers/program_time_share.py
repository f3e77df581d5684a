"""The share (%) of the device time of ALL compiled programs in the traced
slice that the programs ``programs`` took (named as XLA names them,
``jit_<function>``): the passes' part of a generating cell's programs. None
of ``programs`` in the trace (another backbone, the parent): None."""


def read(run, programs: list):
    if run.trace is None:
        return None
    seen = run.trace.programs
    if not any(name in seen for name in programs):
        return None
    total = sum(row["seconds"] for row in seen.values())
    return 100.0 * sum(seen[name]["seconds"] for name in programs if name in seen) / total if total > 0 else None
