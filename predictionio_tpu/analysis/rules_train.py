"""Training-path rules.

``train-unaccounted-sync``: a bare device->host sync inside a
training-loop module. The xray step profiler's contract is that the
per-phase timeline **tiles the train wall clock** and that device time is
explicitly accounted (``pio_train_device_seconds_total``, the
``deviceTimeFrac`` every manifest carries). A raw
``jax.block_until_ready`` / ``jax.device_get`` / one-arg ``np.asarray`` /
``.item()`` on a device value stalls the host for a device round-trip
that *no instrument sees* — the profile under-reports device time and the
roofline math in PERF.md silently rots. Sanctioned forms:

- ``obs.jaxprof.timed_block_until_ready(x, registry, where=…)``
- ``obs.xray.device_fetch(x, where=…)`` / ``TrainProfile.device_barrier``
- an inline suppression with a reason, for syncs that ARE the instrument
  (e.g. ``ops/als.fetch_barrier``) or host-side ``np.asarray`` the
  heuristic can't prove harmless.

Scope (since ISSUE 16): every function REACHABLE from a declared train
entry point (``LintConfig.entry_points``, category ``train`` — the
training-loop modules seed every def), plus module-level statements in
those modules. A sync inside a helper another module provides to the train
loop is in scope even though no glob names it. ``np.asarray`` is only
flagged in its one-argument form — the two-argument
``np.asarray(x, np.float32)`` idiom is how this codebase converts *host*
inputs (a dtype on a device fetch would be a copy anyway), while the bare
one-argument form is exactly the device-readback idiom.
"""

from __future__ import annotations

import ast

from predictionio_tpu.analysis import astutil
from predictionio_tpu.analysis.core import (
    FileContext,
    Finding,
    Severity,
    matches_any_glob,
    register_checker,
    register_rule,
)
from predictionio_tpu.analysis.reachability import CATEGORY_TRAIN

register_rule(
    "train-unaccounted-sync",
    "hostsync",
    Severity.ERROR,
    "bare device->host sync (block_until_ready/device_get/one-arg "
    "np.asarray/.item()) in a training-loop module; route it through "
    "obs.jaxprof.timed_block_until_ready or obs.xray.device_fetch so the "
    "stall lands in the train profile, or suppress with a reason",
)

_SYNC_DOTTED_LAST2 = frozenset(
    {
        ("jax", "device_get"),
        ("jax", "block_until_ready"),
    }
)
_ASARRAY_LAST2 = frozenset(
    {
        ("np", "asarray"),
        ("numpy", "asarray"),
        ("onp", "asarray"),
    }
)


def _sync_label(call: ast.Call) -> str | None:
    func = call.func
    if isinstance(func, ast.Attribute):
        if func.attr == "block_until_ready":
            return ".block_until_ready()"
        if func.attr == "item" and not call.args and not call.keywords:
            return ".item()"
        d = astutil.dotted(func)
        if d:
            parts = tuple(d.split("."))
            if len(parts) >= 2:
                if parts[-2:] in _SYNC_DOTTED_LAST2:
                    return d + "()"
                if (
                    parts[-2:] in _ASARRAY_LAST2
                    and len(call.args) == 1
                    and not call.keywords
                ):
                    return d + "(x)"
    elif isinstance(func, ast.Name) and func.id in (
        "device_get",
        "block_until_ready",
    ):
        return func.id + "()"
    return None


_MESSAGE = (
    "is an unaccounted device->host sync on the "
    "training path; device time leaks out of the train "
    "profile — use timed_block_until_ready / "
    "obs.xray.device_fetch (or suppress with a reason)"
)


@register_checker
def check_train_unaccounted_sync(ctx: FileContext):
    state = ctx.project()
    findings: list[Finding] = []
    train_globs = state.reach.entry_module_globs(CATEGORY_TRAIN)
    if matches_any_glob(ctx.graph_path, train_globs):
        for node in astutil.walk_skipping_nested_functions(
            astutil.module_level_statements(ctx.tree)
        ):
            if isinstance(node, ast.Call):
                label = _sync_label(node)
                if label:
                    findings.append(
                        ctx.finding(
                            "train-unaccounted-sync",
                            node,
                            f"{label} {_MESSAGE}",
                        )
                    )
    for fn, origin in state.reach.iter_reachable_in_file(
        ctx.graph_path, CATEGORY_TRAIN
    ):
        note = state.reach.reach_note(fn, origin)
        for node in astutil.walk_skipping_nested_functions(fn.node.body):
            if not isinstance(node, ast.Call):
                continue
            label = _sync_label(node)
            if label:
                findings.append(
                    ctx.finding(
                        "train-unaccounted-sync",
                        node,
                        f"{label} {_MESSAGE}{note}",
                    )
                )
    return findings
