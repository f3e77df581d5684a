"""Device time of the operations under one ``jax.named_scope`` of a
compiled program, per execution of the program in the traced slice, times
``scale`` (1e3: milliseconds; 1: seconds). An operation is under a scope
when the scope is a component of its ``op_name``
(``jit(_als_step)/solve/while/body/matvec/dot_general``: under ``solve``)
between the program's ``jit(<function>)`` and the operation's own name.
Loops' own events are left out, as in ``trace_reduce``: their intervals
span their bodies'. XLA names a fusion by its root, so a scope is credited
with the fusions it ends.

``scopes`` lists all the program's scopes. An executable loaded from a
compile cache that was written before the scopes existed carries none
(the cache's key is taken after debug information is stripped): where no
operation of the program is under any of ``scopes`` the reader says so and
returns nothing, never 0. A scope with no operation in a trace that shows
the others returns nothing too."""

import functools
import sys

from benchmark import trace_reduce
from benchmark.readers import _slice


@functools.lru_cache(maxsize=None)
def _say_once(message: str) -> None:
    print(message, file=sys.stderr)


def read(run, program: str, scope: str, scopes: list, scale: float = 1.0):
    profile = _slice.load(run)
    row = run.trace.programs.get(f"jit_{program}") if profile is not None else None
    if not row:
        return None
    root = f"jit({program})"
    seconds = {name: 0.0 for name in scopes}
    found = {name: False for name in scopes}
    for start, end, hlo, op_names in profile.ops:
        start, end = max(start, profile.start_ns), min(end, profile.end_ns)
        if end <= start or trace_reduce._CONTAINER.search(hlo):
            continue
        under = set()
        for op_name in op_names:
            path = op_name.split("/")
            if path[0] == root:
                under.update(path[1:-1])
        for name in under.intersection(scopes):
            seconds[name] += (end - start) * 1e-9
            found[name] = True
    if not any(found.values()):
        _say_once(
            f"benchmark: no operation of {root} is under any of {scopes}: the executables "
            "were loaded from a cache written without scopes"
        )
        return None
    return scale * seconds[scope] / row["count"] if found.get(scope) else None
