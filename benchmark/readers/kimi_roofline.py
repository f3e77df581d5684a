"""A kernel's share of its roofline inside the Kimi-Linear session program
(``session_vectors`` with the scopes ``kda``, ``mla``, ``experts``), in the
traced slice: as ``seq_roofline`` reads OLMoE's, with this program's scopes
and ``benchmark/shapes_kimi_linear.py``: the least time the chip could take
for one layer's work at the window's mean program times the layers of that
kind, over the device time of the operations under the kernel's scope per
execution of the program. Says on stderr which peak bounds it. Nothing to
read (no trace, no scopes, another program's counters): None."""

import sys

from benchmark import shapes, shapes_kimi_linear
from benchmark.readers import scope_mean_ms
from benchmark.readers.seq_roofline import PROGRAM, _by_bucket

SCOPES = ["embed", "kda", "mla", "dense", "router", "experts", "shared", "head"]
# kernel -> (its scope, the kind of layer it runs in)
KERNELS = {"kda": ("kda", "kda"), "mla": ("mla", "mla"), "experts_held": ("experts", "sparse")}


def read(run, kernel: str):
    if run.trace is None or run.peak is None or "linear_attn_config" not in run.shapes:
        return None
    scope, kind = KERNELS[kernel]
    seconds = scope_mean_ms.read(run, PROGRAM, scope, SCOPES, 1.0)
    rows = _by_bucket(run, "pio_seq_rows_total")
    programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
    if not seconds or not rows or programs <= 0:
        return None
    config = run.shapes
    tokens = sum(r * bucket for bucket, r in rows.items()) / programs
    if kernel == "kda":
        flops = shapes_kimi_linear.kda_flops(tokens, config)
        nbytes = shapes_kimi_linear.kda_bytes(tokens, config)
    elif kernel == "mla":
        flops = sum(shapes_kimi_linear.mla_flops(r, bucket, config) for bucket, r in rows.items()) / programs
        nbytes = shapes_kimi_linear.mla_bytes(tokens, config)
    else:
        flops = shapes_kimi_linear.experts_held_flops(tokens, config)
        nbytes = shapes_kimi_linear.experts_held_bytes(tokens, config)
    layers = shapes_kimi_linear.layer_counts(config)[kind]
    share, bound = shapes.roofline_share(layers * flops, layers * nbytes, seconds, run.peak)
    print(
        f"benchmark: {kernel} is bound by {bound} at a mean program of {tokens:.0f} padded tokens",
        file=sys.stderr,
    )
    return share
