"""Op-level attribution of the ALS solver from an XLA profiler trace.

Round-4 verdict task #3: the ~0.5 s/iter ML-20M solver is *claimed*
gather-bound; this script produces the evidence. It trains ALS twice
(cold run compiles; the traced run is warm), captures a profiler trace
of the warm train, then aggregates the trace's XLA op events into a
top-N table by total device time — enough to show whether gathers /
scatters / einsums / CG matvecs dominate the iteration.

Usage (on the TPU; CPU works for plumbing checks):

    python scripts/profile_als.py --scale ml1m --iterations 3 \
        --trace-dir /tmp/als_trace

Prints the table and writes it as markdown next to the trace. Cite the
output in PERF.md once captured on hardware.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
from collections import defaultdict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_and_trace(scale: str, iterations: int, trace_dir: str) -> dict:
    import numpy as np

    sys.path.insert(0, REPO)
    from predictionio_tpu.utils.platform import configure_jax

    configure_jax()  # before the jax import below
    from bench import _scale_params, synthesize_ratings
    from predictionio_tpu.ops.als import ALSConfig, als_train

    import jax

    _, n_users, n_items, n_ratings, rank, _ = _scale_params("cpu")
    if scale:
        os.environ["PIO_BENCH_SCALE"] = scale
        _, n_users, n_items, n_ratings, rank, _ = _scale_params("tpu")
    users, items, vals = synthesize_ratings(n_users, n_items, n_ratings)
    cfg = ALSConfig(rank=rank, iterations=iterations, reg=0.05, chunk=65536)
    print(f"[profile] cold train (compile), scale={scale} it={iterations}")
    als_train(users, items, vals, n_users, n_items, cfg)
    print("[profile] warm train under trace")
    timings: dict = {}
    with jax.profiler.trace(trace_dir):
        als_train(users, items, vals, n_users, n_items, cfg, timings=timings)
    print(f"[profile] timings: { {k: round(v, 3) if isinstance(v, float) else v for k, v in timings.items()} }")
    return timings


def attribute(
    trace_dir: str, top_n: int | None = 30
) -> list[tuple[str, float, int]]:
    """Aggregate XLA op events from the newest .trace.json.gz under
    trace_dir; returns [(op_name, total_ms, count)] sorted by total.

    Only DEVICE-lane events are summed when the trace has device process
    lanes (process_name metadata matching TPU/device); host runtime rows
    also carry ``dur`` and would otherwise swamp the op table. Falls back
    to all lanes (with a notice) for traces without device lanes (CPU)."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise SystemExit(f"no .trace.json.gz under {trace_dir}")
    with gzip.open(paths[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    proc_names: dict[object, str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            proc_names[ev.get("pid")] = str(
                (ev.get("args") or {}).get("name", "")
            )
    device_pids = {
        pid
        for pid, nm in proc_names.items()
        if any(tag in nm.lower() for tag in ("tpu", "device", "accelerator"))
    }
    if not device_pids:
        print(
            "[profile] no device lanes in trace "
            f"({sorted(set(proc_names.values()))}); aggregating ALL lanes",
            file=sys.stderr,
        )
    totals: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for ev in events:
        dur = ev.get("dur")  # microseconds
        name = ev.get("name")
        if not dur or not name:
            continue
        if device_pids and ev.get("pid") not in device_pids:
            continue
        totals[name] += dur / 1000.0
        counts[name] += 1
    rows = sorted(totals.items(), key=lambda kv: -kv[1])
    if top_n is not None:
        rows = rows[:top_n]
    return [(name, ms, counts[name]) for name, ms in rows]


_CATEGORIES = (
    ("gather", ("gather",)),
    ("scatter", ("scatter",)),
    ("matmul", ("dot", "einsum", "conv")),
    ("sort", ("sort",)),
    ("collective", ("all-reduce", "all-gather", "all-to-all", "ppermute",
                    "reduce-scatter", "collective")),
    ("copy/transpose", ("copy", "transpose", "bitcast", "reshape")),
    ("fusion (opaque)", ("fusion",)),
)


def categorize(rows: list[tuple[str, float, int]]) -> list[tuple[str, float]]:
    """Roll op rows up into coarse buckets by root op name — the one-line
    answer to 'is the iteration gather-bound?'. Fused ops stay opaque
    (XLA hides their internals) but fusion names usually embed the
    dominant op on TPU traces."""
    buckets: dict[str, float] = defaultdict(float)
    for name, ms, _ in rows:
        low = name.lower()
        for cat, keys in _CATEGORIES:
            if any(k in low for k in keys):
                buckets[cat] += ms
                break
        else:
            buckets["other"] += ms
    return sorted(buckets.items(), key=lambda kv: -kv[1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="", help="ml100k|ml1m|ml20m (default: cpu-scale)")
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--trace-dir", default="/tmp/als_trace")
    ap.add_argument("--top", type=int, default=30)
    ap.add_argument("--skip-train", action="store_true",
                    help="only parse an existing trace")
    args = ap.parse_args()

    if not args.skip_train:
        run_and_trace(args.scale, args.iterations, args.trace_dir)
    all_rows = attribute(args.trace_dir, top_n=None)
    rows = all_rows[: args.top]
    top_ms = sum(ms for _, ms, _ in rows)
    lines = [
        "| op | total ms | calls | % of top-N |",
        "|---|---|---|---|",
    ]
    for name, ms, cnt in rows:
        lines.append(
            f"| `{name[:80]}` | {ms:.1f} | {cnt} | {100.0 * ms / top_ms:.1f}% |"
        )
    # the category verdict must cover ALL rows, not the top-N: a long tail
    # of small gathers below rank N is exactly the gather-bound signature
    total_ms = sum(ms for _, ms, _ in all_rows)
    cat_lines = ["", "| category | total ms | % of all |", "|---|---|---|"]
    for cat, ms in categorize(all_rows):
        cat_lines.append(f"| {cat} | {ms:.1f} | {100.0 * ms / total_ms:.1f}% |")
    table = "\n".join(lines) + "\n" + "\n".join(cat_lines)
    print(table)
    out_md = os.path.join(args.trace_dir, "attribution.md")
    with open(out_md, "w") as f:
        f.write(f"# ALS op-level attribution (scale={args.scale or 'cpu'})\n\n")
        f.write(table + "\n")
    print(f"\n[profile] wrote {out_md}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
