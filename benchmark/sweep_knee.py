"""Find a steady cell's knee once, on the chip: not part of a run.

``python benchmark/sweep_knee.py --workload <cell> --rates 400,800,... [--seconds 12]``

Deploys the cell's configuration once and offers its open-loop traffic at
each fixed rate in turn. The knee is the highest rate at which no request
fails or times out, the requests sent in time for the window's end and not
answered by it are under 1% of those sent, and p95 is at most ten times the
p50 of the lowest rate swept. The cell's ``rate_qps`` is 0.8 x the knee,
rounded to two figures, written by hand into ``benchmark/cells/<cell>.json``
with the table in PERF.md.
"""

import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True, help="comma-separated q/s, rising")
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default="chiprun_out/sweep_knee.json")
    args = parser.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark import harness

    rows = []
    try:
        with harness.open_cell(
            ROOT, args.workload, args.seed, args.seconds, False, PROCESS_START
        ) as (_, ctx, engine, driver, _devices):
            import numpy as np

            deployment = engine.serving(ctx)
            try:
                for rate in (float(r) for r in args.rates.split(",")):
                    at = dataclasses.replace(ctx, traffic={**ctx.traffic, "rate_qps": rate})
                    run = driver.measure(at, engine, deployment)
                    lat = run.series["latency_ms"]
                    row = {
                        "rate_qps": rate,
                        "attempted": run.attempted,
                        "failed": run.failed,
                        "unanswered_at_end_share": run.counts["unanswered_at_end"]
                        / max(1, run.counts["sent_by_end"]),
                        "p50_ms": float(np.percentile(lat, 50)),
                        "p95_ms": float(np.percentile(lat, 95)),
                        "p99_ms": float(np.percentile(lat, 99)),
                        "late_p99_ms": float(np.percentile(run.series["late_ms"], 99)),
                        "batch_size": run.grown("batcher.queries_dispatched")
                        / max(1.0, run.grown("batcher.batches_dispatched")),
                        "correct": run.correct,
                    }
                    rows.append(row)
                    print(json.dumps(row), flush=True)
                    time.sleep(2.0)  # let a queue that grew drain before the next rate
            finally:
                deployment.stop()
    except harness.Refused as exc:
        print(f"sweep_knee: refused: {exc}", file=sys.stderr)
        return 3
    floor_p50 = rows[0]["p50_ms"]
    holding = [
        r["rate_qps"]
        for r in rows
        if r["failed"] == 0
        and r["unanswered_at_end_share"] < 0.01
        and r["p95_ms"] <= 10 * floor_p50
    ]
    knee = max(holding) if holding else None
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with open(out, "w") as f:
        json.dump(
            {"workload": args.workload, "seconds": args.seconds, "rows": rows, "knee_qps": knee},
            f, indent=1,
        )
    print(json.dumps({"knee_qps": knee, "rate_qps": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
