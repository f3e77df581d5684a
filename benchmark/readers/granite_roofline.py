"""The Granite session program (``session_vectors`` with the scopes ``mamba``
(inside it ``in_proj``, ``conv``, ``ssd``, ``gate_norm``, ``out_proj``),
``attn``, ``router``, ``experts``, ``shared``) in the traced slice, two
readings.

``read(run, kernel=)``: a kernel's share of its roofline, as ``lfm2_roofline``
reads LFM2's with this program's scopes and ``benchmark/shapes_granite.py``:
the least time the chip could take for one layer's work at the window's mean
program times the layers of that kind, over the device time of the operations
under the kernel's scope(s) per execution of the program. Says on stderr which
peak bounds it.

``read(run, share_of=)``: the share (%) of the program's device time that the
operations under the scopes ``share_of`` took (``mamba_time_share``: whether
the new mechanism does most of the work).

Nothing to read (no trace, no scopes, another program's shapes): None."""

import sys

from benchmark import shapes, shapes_granite
from benchmark.readers import scope_mean_ms
from benchmark.readers.seq_roofline import PROGRAM, _by_bucket

SCOPES = [
    "embed", "mamba", "in_proj", "conv", "ssd", "gate_norm", "out_proj", "attn", "router", "experts", "shared", "head",
]
# kernel -> (its scopes, the kind of layer it runs in, its functions' name in ``shapes_granite``)
KERNELS = {
    "ssd": (["ssd"], "mamba", "ssd"), "mamba_proj": (["in_proj", "out_proj"], "mamba", "mamba_proj"),
    "attn128": (["attn"], "attn", "attn"), "experts_held36": (["experts"], "sparse", "experts_held"),
}


def read(run, kernel: str | None = None, share_of: list | None = None):
    if run.trace is None or "mamba_n_heads" not in run.shapes:
        return None
    if share_of is not None:
        row = run.trace.programs.get(f"jit_{PROGRAM}")
        seconds = [scope_mean_ms.read(run, PROGRAM, scope, SCOPES, 1.0) for scope in share_of]
        if not row or row["seconds"] <= 0 or None in seconds:
            return None
        return 100.0 * sum(seconds) * row["count"] / row["seconds"]
    if run.peak is None:
        return None
    scopes, kind, counted = KERNELS[kernel]
    seconds = [scope_mean_ms.read(run, PROGRAM, scope, SCOPES, 1.0) for scope in scopes]
    rows = _by_bucket(run, "pio_seq_rows_total")
    programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
    if None in seconds or not sum(seconds) or not rows or programs <= 0:
        return None
    config = run.shapes
    tokens = sum(r * bucket for bucket, r in rows.items()) / programs
    flops_of, bytes_of = (getattr(shapes_granite, f"{counted}_{what}") for what in ("flops", "bytes"))
    if counted == "attn":  # a row's causal square: by the rows of each length, not by the mean program
        flops = sum(flops_of(r, bucket, config) for bucket, r in rows.items()) / programs
    else:
        flops = flops_of(tokens, config)
    nbytes = bytes_of(tokens, config)
    layers = shapes_granite.layer_counts(config)[kind]
    share, bound = shapes.roofline_share(layers * flops, layers * nbytes, sum(seconds), run.peak)
    print(
        f"benchmark: {kernel} is bound by {bound} at a mean program of {tokens:.0f} padded tokens",
        file=sys.stderr,
    )
    return share
