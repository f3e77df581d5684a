"""The batched serve program's share of its roofline in the traced slice:
the least time the chip could take for the queries it really scored
(``benchmark/shapes.py``: padding rows of a bucket are not work) over the
program's device time from the trace. Says on stderr which peak bounds it."""

import sys

from benchmark import shapes


def read(run, program: str):
    trace, peak = run.trace, run.peak
    if trace is None or peak is None or program not in trace.programs:
        return None
    batches = run.grown("batcher.batches_dispatched")
    queries = run.grown("batcher.queries_dispatched")
    if batches <= 0:
        return None
    batch = queries / batches  # both bounds are linear in the batch
    n, f = run.shapes["n_items"], run.shapes["rank"]
    row = trace.programs[program]
    share, bound = shapes.roofline_share(
        shapes.serve_batch_flops(batch, n, f),
        shapes.serve_batch_bytes(batch, n, f),
        row["seconds"] / row["count"],
        peak,
    )
    print(f"benchmark: {program} is bound by {bound} at a mean batch of {batch:.1f}", file=sys.stderr)
    return share
