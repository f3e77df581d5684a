"""Device-free XLA cost-analysis roofline for the registered jit buckets.

Every serving/training kernel family in this repo is a jitted program
with static shapes — which means XLA can *price* it without running it:
``jitted.lower(...).compile().cost_analysis()`` returns the compiler's
own flops and bytes-accessed accounting for the optimized HLO. This
module lowers one representative shape per registered bucket family
(ops/topk's three programs, ann search, twotower towers, als
sweep/solve), reads that accounting into per-kernel **arithmetic
intensity** (flops/byte), and projects it onto a device roofline
(``max(flops/peak_flops, bytes/peak_bw)``) to get a per-model
"device cost per 1k queries" in USD.

This runs entirely on the CPU backend — lowering + compiling never
touches a device — so every sandbox-measured claim gains
an analytic device anchor *before* any hardware window opens (ROADMAP
item 5: "no hardware window is wasted"). ALX (PAPERS.md) sized its TPU
ALS from exactly this per-kernel flops/bytes accounting.

Consumer: ``pio doctor --roofline`` (JSON report). Imports jax lazily —
the module is importable (and listable) from stdlib-light CLI paths.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak envelope of one accelerator (or host) for the roofline
    projection. Peaks are dense bf16/f32 marketing peaks — the model
    prices the *floor* of device time, not a prediction of achieved
    time; measured utilization rides on top."""

    name: str
    peak_flops: float  # FLOP/s
    peak_bytes_per_s: float  # HBM (or DRAM) bandwidth, B/s
    usd_per_hour: float  # on-demand list price per device

    def to_json_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


# the devices a report can be priced against (tpu-v5e, the chip this
# repository is measured on, carries benchmark/peaks.json's figures);
# cpu-host is the sandbox floor (one modern server socket, DDR bandwidth)
DEVICE_SPECS: dict[str, DeviceSpec] = {
    "tpu-v4": DeviceSpec("tpu-v4", 275e12, 1.2e12, 3.22),
    "tpu-v5e": DeviceSpec("tpu-v5e", 197e12, 0.82e12, 1.20),
    "tpu-v5p": DeviceSpec("tpu-v5p", 459e12, 2.77e12, 4.20),
    "cpu-host": DeviceSpec("cpu-host", 1.0e12, 0.1e12, 0.40),
}
DEFAULT_DEVICE = "tpu-v5e"


def _struct_bytes(tree) -> int:
    import jax

    return sum(
        int(math.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")
    )


def _lower_cost(
    family: str,
    kernel: str,
    fn: Callable,
    args: tuple,
    static_kwargs: dict[str, Any] | None = None,
) -> dict[str, Any]:
    """Lower+compile one jitted bucket at its representative shape and
    read the compiler's cost accounting. ``bytesAccessed`` falls back to
    arg+out buffer sizes when the backend omits it (a lower bound: every
    operand crosses memory at least once)."""
    import jax

    static_kwargs = static_kwargs or {}
    lowered = fn.lower(*args, **static_kwargs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()  # a dict in jax 0.9.0
    arg_bytes = _struct_bytes(args)
    # jitted-fn eval_shape respects static_argnames (the plain
    # jax.eval_shape would trace the static kwargs as abstract values)
    out_bytes = _struct_bytes(fn.eval_shape(*args, **static_kwargs))
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    if not math.isfinite(flops) or flops < 0:
        flops = 0.0
    if not math.isfinite(bytes_accessed) or bytes_accessed <= 0:
        bytes_accessed = float(arg_bytes + out_bytes)
    return {
        "family": family,
        "kernel": kernel,
        "flops": flops,
        "bytesAccessed": bytes_accessed,
        "argBytes": arg_bytes,
        "outBytes": out_bytes,
        "arithmeticIntensity": flops / max(bytes_accessed, 1.0),
    }


# --------------------------------------------------------------- families
# Each builder returns (kernel cost dicts, queries-per-invocation of the
# family's headline kernel — the unit the per-1k-queries price is in).
# Shapes are small but structurally faithful (the masked matmul, the
# flattened-slab ann gather, the blocked ALS normal equations): cost
# *ratios* and arithmetic intensity are shape-stable, and small shapes
# keep the CPU compile under a second per kernel.


def topk_costs(
    *, n: int = 4096, f: int = 32, b: int = 32, q: int = 8, k: int = 10
) -> tuple[list[dict[str, Any]], int]:
    """The score->mask->top-k serving programs (ops/topk.PROGRAMS), each
    with the operands its engines pass: the by-index front its all-true
    [n] mask and its item table at the width ``ServingIndex`` stores it in,
    the other two a [B, n] mask and no weights."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import topk

    S = jax.ShapeDtypeStruct
    table = S((n, f), jnp.float32)
    mask = S((b, n), jnp.bool_)
    operands = {
        "_serve_by_index_batch": (
            S((b,), jnp.int32),
            table,
            S((n, f), topk.item_table_dtype()),
            S((n,), jnp.bool_),
        ),
        "_dot_top_k": (table, S((b, f), jnp.float32), mask, None),
        "_gather_sum_top_k": (
            table, S((b, q), jnp.int32), S((b, q), jnp.float32), mask, None,
        ),
    }
    return [
        _lower_cost(
            "topk", fn.__name__.lstrip("_"), fn, operands[fn.__name__], {"k": k}
        )
        for fn in topk.PROGRAMS
    ], b


def ann_costs(
    *,
    c: int = 64,
    cap: int = 32,
    f: int = 32,
    b: int = 32,
    nprobe: int = 4,
    k: int = 10,
) -> tuple[list[dict[str, Any]], int]:
    """The clustered ANN probe->gather->score->top-k bucket (ann/search)."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ann import search as ann_search

    search, _excl, _masked, _q8 = ann_search._kernels()
    S = jax.ShapeDtypeStruct
    args = (
        S((c, f), jnp.float32),  # centroids
        S((c, cap * f), jnp.float32),  # bucket_flat
        S((c, cap), jnp.int32),  # bucket_ids
        S((b, f), jnp.float32),  # queries
    )
    return [
        _lower_cost("ann", "search", search, args, {"nprobe": nprobe, "k": k})
    ], b


def als_costs(
    *,
    rank: int = 16,
    n_users: int = 64,
    n_items: int = 64,
    nb: int = 32,
    d: int = 8,
    block_chunk: int = 8,
) -> tuple[list[dict[str, Any]], int]:
    """The blocked ALS sweep (both half-steps) and the batched SPD solve
    it is built on (ops/als)."""
    import functools

    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import als as A

    S = jax.ShapeDtypeStruct
    step_args = (
        S((n_users + 1, rank), jnp.float32),
        S((n_items + 1, rank), jnp.float32),
        S((nb,), jnp.int32),
        S((nb, d), jnp.int32),
        S((nb, d), jnp.float32),
        S((nb, d), jnp.int8),
        S((nb,), jnp.int32),
        S((nb, d), jnp.int32),
        S((nb, d), jnp.float32),
        S((nb, d), jnp.int8),
    )
    step_kwargs = {
        "n_users": n_users,
        "n_items": n_items,
        "reg": 0.05,
        "implicit": False,
        "alpha": 40.0,
        "block_chunk": block_chunk,
        "degree_scaled_reg": True,
        "solver": "cg",
        "gather_dtype": "f32",
    }
    solve = jax.jit(functools.partial(A._batched_spd_solve, solver="cg"))
    solve_args = (
        S((n_users, rank, rank), jnp.float32),
        S((n_users, rank), jnp.float32),
    )
    costs = [
        _lower_cost("als", "als_step", A._als_step, step_args, step_kwargs),
        _lower_cost("als", "spd_solve_cg", solve, solve_args),
    ]
    return costs, n_users + n_items  # rows re-solved per sweep


def twotower_costs(
    *,
    n_users: int = 128,
    n_items: int = 256,
    embed_dim: int = 32,
    hidden: tuple[int, ...] = (64,),
    out_dim: int = 16,
    b: int = 32,
) -> tuple[list[dict[str, Any]], int]:
    """The two-tower serving encoders (models/twotower): params come from
    ``jax.eval_shape`` over init — no real initialization runs."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.models.twotower.model import TwoTower, TwoTowerConfig

    cfg = TwoTowerConfig(
        n_users=n_users,
        n_items=n_items,
        embed_dim=embed_dim,
        hidden=hidden,
        out_dim=out_dim,
    )
    model = TwoTower(config=cfg)
    S = jax.ShapeDtypeStruct
    ids = S((b,), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)
    user_fn = jax.jit(
        lambda p, u: model.apply(p, u, method=TwoTower.embed_users)
    )
    item_fn = jax.jit(
        lambda p, i: model.apply(p, i, method=TwoTower.embed_items)
    )
    return [
        _lower_cost("twotower", "embed_users", user_fn, (params, ids)),
        _lower_cost("twotower", "embed_items", item_fn, (params, ids)),
    ], b


FAMILY_BUILDERS: dict[str, Callable[[], tuple[list[dict[str, Any]], int]]] = {
    "topk": topk_costs,
    "ann": ann_costs,
    "als": als_costs,
    "twotower": twotower_costs,
}


# ---------------------------------------------------------------- roofline
def roofline_time_s(cost: dict[str, Any], spec: DeviceSpec) -> dict[str, Any]:
    """Roofline floor for one kernel invocation on ``spec``: the larger
    of compute time and memory time, with which wall it hit."""
    t_compute = cost["flops"] / spec.peak_flops
    t_memory = cost["bytesAccessed"] / spec.peak_bytes_per_s
    return {
        "modelTimeS": max(t_compute, t_memory),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "ridgeIntensity": spec.peak_flops / spec.peak_bytes_per_s,
    }


def analyze(
    families: list[str] | None = None,
    device: str | DeviceSpec = DEFAULT_DEVICE,
) -> dict[str, Any]:
    """The full report behind ``pio doctor --roofline``: per-kernel
    flops/bytes/AI + roofline projection, per-family totals, and the
    per-1k-queries device price. A family whose lowering fails records
    an ``errors`` entry instead of sinking the report."""
    spec = DEVICE_SPECS[device] if isinstance(device, str) else device
    report: dict[str, Any] = {
        "device": spec.to_json_dict(),
        "families": {},
        "errors": {},
    }
    for fam in families or list(FAMILY_BUILDERS):
        try:
            kernels, batch = FAMILY_BUILDERS[fam]()
        except Exception as exc:  # noqa: BLE001 - report the rest regardless
            report["errors"][fam] = f"{type(exc).__name__}: {exc}"
            continue
        for cost in kernels:
            cost.update(roofline_time_s(cost, spec))
        total_flops = sum(c["flops"] for c in kernels)
        total_bytes = sum(c["bytesAccessed"] for c in kernels)
        # the family's headline kernel (first recipe) is the per-query
        # serving program; its roofline floor prices a query batch
        head = kernels[0]
        per_query_s = head["modelTimeS"] / max(batch, 1)
        report["families"][fam] = {
            "kernels": kernels,
            "batch": batch,
            "totalFlops": total_flops,
            "totalBytes": total_bytes,
            "arithmeticIntensity": total_flops / max(total_bytes, 1.0),
            "perQueryModelTimeS": per_query_s,
            "costPer1kQueriesUsd": per_query_s
            * 1000.0
            * (spec.usd_per_hour / 3600.0),
        }
    return report


__all__ = [
    "DEFAULT_DEVICE",
    "DEVICE_SPECS",
    "DeviceSpec",
    "FAMILY_BUILDERS",
    "analyze",
    "als_costs",
    "ann_costs",
    "roofline_time_s",
    "topk_costs",
    "twotower_costs",
]
