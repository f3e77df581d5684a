"""The LFM2 session program (``session_vectors`` with the scopes ``conv``,
``attn``, ``dense``, ``router``, ``experts``) in the traced slice, two
readings.

``read(run, kernel=)``: a kernel's share of its roofline, as ``kimi_roofline``
reads Kimi-Linear's with this program's scopes and
``benchmark/shapes_lfm2.py``: the least time the chip could take for one
layer's work at the window's mean program times the layers of that kind, over
the device time of the operations under the kernel's scope per execution of
the program. Says on stderr which peak bounds it.

``read(run, share_of=)``: the share (%) of the program's device time that the
operations under the scopes ``share_of`` took (``mixer_time_share``: ``conv``
and ``attn``, whether the new mechanism does most of the work).

Nothing to read (no trace, no scopes, another program's shapes): None."""

import sys

from benchmark import shapes, shapes_lfm2
from benchmark.readers import scope_mean_ms
from benchmark.readers.seq_roofline import PROGRAM, _by_bucket

SCOPES = ["embed", "conv", "attn", "dense", "router", "experts", "head"]
# kernel -> (its scope, the kind of layer it runs in)
KERNELS = {"conv": ("conv", "conv"), "attn64": ("attn", "attn"), "experts_held8": ("experts", "sparse")}


def read(run, kernel: str | None = None, share_of: list | None = None):
    if run.trace is None or "layer_types" not in run.shapes:
        return None
    if share_of is not None:
        row = run.trace.programs.get(f"jit_{PROGRAM}")
        seconds = [scope_mean_ms.read(run, PROGRAM, scope, SCOPES, 1.0) for scope in share_of]
        if not row or row["seconds"] <= 0 or None in seconds:
            return None
        return 100.0 * sum(seconds) * row["count"] / row["seconds"]
    if run.peak is None:
        return None
    scope, kind = KERNELS[kernel]
    seconds = scope_mean_ms.read(run, PROGRAM, scope, SCOPES, 1.0)
    rows = _by_bucket(run, "pio_seq_rows_total")
    programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
    if not seconds or not rows or programs <= 0:
        return None
    config = run.shapes
    tokens = sum(r * bucket for bucket, r in rows.items()) / programs
    if kernel == "conv":
        flops, nbytes = shapes_lfm2.conv_flops(tokens, config), shapes_lfm2.conv_bytes(tokens, config)
    elif kernel == "attn64":
        flops = sum(shapes_lfm2.attn_flops(r, bucket, config) for bucket, r in rows.items()) / programs
        nbytes = shapes_lfm2.attn_bytes(tokens, config)
    else:
        flops = shapes_lfm2.experts_held_flops(tokens, config)
        nbytes = shapes_lfm2.experts_held_bytes(tokens, config)
    layers = shapes_lfm2.layer_counts(config)[kind]
    share, bound = shapes.roofline_share(layers * flops, layers * nbytes, seconds, run.peak)
    print(
        f"benchmark: {kernel} is bound by {bound} at a mean program of {tokens:.0f} padded tokens",
        file=sys.stderr,
    )
    return share
