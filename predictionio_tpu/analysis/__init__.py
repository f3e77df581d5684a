"""TPU-aware static analysis (`pio lint`).

AST-based checks that catch the classic JAX serving failure modes at review
time instead of at 3am on a pod: tracer-unsafe Python control flow inside
jitted functions, recompile hazards (unhashable/scalar args, jit wrappers
built per request, mutable closure capture), host-sync stalls on the serving
path, unlocked shared state in threaded modules, and storage backends that
drift from the ``storage/base.py`` abstract contract.

Since ISSUE 16 the engine is whole-program: a cross-file call graph
(``callgraph.py``) plus reachability from declared entry points
(``reachability.py``, ``LintConfig.entry_points``) scope the
context-sensitive rules, and two new families guard the pod-scale work:
``mesh-*`` (axis-name agreement, single-host materialization, per-shard
top-k merging) and ``async-blocking-call`` (blocking I/O on fleet event
loops, transitively through the call graph).

Public surface:

- :func:`analyze_paths` / :func:`analyze_source` — run the rule registry.
- :class:`Finding`, :class:`Severity`, :class:`LintConfig`,
  :class:`Report`, :class:`EntryPoint`.
- ``predictionio_tpu.analysis.cli:main`` — the ``pio lint`` / ``lint``
  console entry point.

Inline suppression: ``# pio-lint: disable=rule-id[,rule-id...] -- reason``
on the offending line (or alone on the line above); file-level with
``# pio-lint: disable-file=rule-id``. Suppressions should carry a reason.

This package must stay importable without jax/numpy: `pio lint` runs in
CI and pre-commit hooks where pulling in an accelerator runtime (and
taking the chip from the process that holds it) is exactly what we are
trying to avoid.
"""

from predictionio_tpu.analysis.core import (
    Finding,
    LintConfig,
    Report,
    Severity,
    all_rules,
    analyze_paths,
    analyze_source,
)
from predictionio_tpu.analysis.reachability import EntryPoint

# importing the rule modules registers their checkers
from predictionio_tpu.analysis import (  # noqa: F401  (registration side effect)
    rules_async,
    rules_concurrency,
    rules_fleet,
    rules_hostsync,
    rules_mesh,
    rules_obs,
    rules_recompile,
    rules_storage,
    rules_stream,
    rules_tracer,
    rules_train,
)

__all__ = [
    "EntryPoint",
    "Finding",
    "LintConfig",
    "Report",
    "Severity",
    "all_rules",
    "analyze_paths",
    "analyze_source",
]
