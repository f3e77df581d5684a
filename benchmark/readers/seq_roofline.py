"""A kernel's share of its roofline inside the session program
(``session_vectors``), in the traced slice: the least time the chip could
take for ONE layer's work at the window's mean program
(``benchmark/shapes_olmoe.py``; padded tokens count, the chip computes them)
times the layers, over the device time of the operations under the kernel's
``jax.named_scope`` per execution of the program. The mean program comes from
the program's counters: ``pio_seq_rows_total{bucket}`` (rows launched by
length bucket) over ``pio_seq_programs_total{bucket}``. Both bounds are
convex in a program's size, so the bound at the mean program is at most the
mean of the bounds: the share errs low, never high. Says on stderr which peak
bounds it. Nothing to read (the parent, no trace, no scopes): None."""

import re
import sys

from benchmark import shapes, shapes_olmoe
from benchmark.readers import scope_mean_ms

PROGRAM = "session_vectors"
SCOPES = ["embed", "attn", "router", "experts", "head"]
_BUCKET = re.compile(r'bucket="(\d+)"')


def _by_bucket(run, counter: str) -> dict[int, float]:
    out = {}
    for key in run.counters_end:
        if key.startswith(counter + "{"):
            m = _BUCKET.search(key)
            if m and run.grown(key) > 0:
                out[int(m.group(1))] = run.grown(key)
    return out


def read(run, kernel: str):
    if run.trace is None or run.peak is None:
        return None
    seconds = scope_mean_ms.read(run, PROGRAM, kernel, SCOPES, 1.0)
    rows = _by_bucket(run, "pio_seq_rows_total")
    programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
    if not seconds or not rows or programs <= 0:
        return None
    config = run.shapes
    tokens = sum(r * bucket for bucket, r in rows.items()) / programs
    if kernel == "experts":
        flops = shapes_olmoe.experts_flops(tokens, config)
        nbytes = shapes_olmoe.experts_bytes(tokens, config)
    elif kernel == "attn":
        flops = sum(shapes_olmoe.attn_flops(r, bucket, config) for bucket, r in rows.items()) / programs
        nbytes = shapes_olmoe.attn_bytes(tokens, config)
    else:
        raise ValueError(f"no roofline for scope {kernel!r}")
    layers = config["num_hidden_layers"]
    share, bound = shapes.roofline_share(layers * flops, layers * nbytes, seconds, run.peak)
    print(
        f"benchmark: {kernel} is bound by {bound} at a mean program of {tokens:.0f} padded tokens",
        file=sys.stderr,
    )
    return share
