"""A kernel's share of its roofline in the kanana programs, in the traced
slice, as ``sdar_roofline`` reads SDAR's, with ``benchmark/shapes_kanana.py``:

- ``mla_expanded``: the scope ``mla`` of the prefill (``session_vectors``) at
  the window's mean stream: the layers but the last whole, the last for its
  latent and expansion;
- ``mla_absorbed`` and ``decode_experts``: the scopes ``mla_absorbed`` and
  ``experts`` of a step (``decode_step``), every layer's;
- ``decode_step``: the whole step program.

The rows of a step are the batch's sessions; the slots it reads are the
batch's real session tokens (``pio_seq_tokens_total{kind="real"}`` a batch)
and the positions generated by the mean step (half the batch's steps a
session); the experts a step has to read are those the program counted as
reached by a real row (``pio_moe_experts_reached_total`` over
``pio_moe_experts_offered_total``, times a layer's experts). Says on stderr
which peak bounds it. Nothing to read (no trace, no scopes, another program's
counters, the parent): None."""

import sys

from benchmark import shapes, shapes_kanana
from benchmark.readers import program_mean_ms, scope_mean_ms
from benchmark.readers.sdar_roofline import REACHED, _a_batch, _say_scopes
from benchmark.readers.seq_roofline import PROGRAM, _by_bucket

STEP = "decode_step"
STEPS = 'pio_seq_passes_total{kind="decode"}'
PREFILL_SCOPES = ["embed", "mla", "dense", "router", "experts", "shared", "cache", "head"]
STEP_SCOPES = ["embed", "cache", "mla_absorbed", "dense", "router", "experts", "shared", "head", "pick"]
KERNELS = ("mla_expanded", "mla_absorbed", "decode_experts", "decode_step")


def read(run, kernel: str):
    if run.trace is None or run.peak is None or "rope_interleave" not in run.shapes:
        return None
    if STEPS not in run.counters_end:
        return None
    config = run.shapes
    layers = config["num_hidden_layers"]
    if kernel == "mla_expanded":
        seconds = scope_mean_ms.read(run, PROGRAM, "mla", PREFILL_SCOPES, 1.0)
        rows = _by_bucket(run, "pio_seq_rows_total")
        programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
        if not seconds or not rows or programs <= 0:
            return None
        tokens = sum(r * bucket for bucket, r in rows.items()) / programs
        whole = sum(shapes_kanana.mla_expanded_flops(r, bucket, config) for bucket, r in rows.items()) / programs
        flops = (layers - 1) * whole + shapes_kanana.mla_latent_flops(tokens, config)
        nbytes = layers * shapes_kanana.mla_expanded_bytes(tokens, config)
        what = f"a mean stream of {tokens:.0f} tokens"
    else:
        sessions = sum(_by_bucket(run, "pio_seq_sessions_total").values())
        batches = run.grown("pio_seq_batches_total{}")
        tokens, steps = _a_batch(run, 'pio_seq_tokens_total{kind="real"}'), _a_batch(run, STEPS)
        offered = run.grown("pio_moe_experts_offered_total{}") if REACHED in run.counters_end else 0
        if batches <= 0 or sessions <= 0 or not tokens or not steps or offered <= 0:
            return None
        rows = sessions / batches
        slots = tokens + rows * (steps + 1) / 2
        reached = config["n_routed_experts"] * run.grown(REACHED) / offered
        if kernel == "mla_absorbed":
            seconds = scope_mean_ms.read(run, STEP, "mla_absorbed", STEP_SCOPES, 1.0)
            flops = layers * shapes_kanana.mla_absorbed_flops(rows, slots, config)
            nbytes = layers * shapes_kanana.mla_absorbed_bytes(rows, slots, config)
        elif kernel == "decode_experts":
            sparse = shapes_kanana.layer_counts(config)["sparse"]
            seconds = scope_mean_ms.read(run, STEP, "experts", STEP_SCOPES, 1.0)
            flops = sparse * shapes_kanana.experts_flops(rows, config)
            nbytes = sparse * shapes_kanana.experts_bytes(rows, config, reached)
        else:
            ms = program_mean_ms.read(run, f"jit_{STEP}")
            seconds = None if ms is None else ms / 1e3
            flops = shapes_kanana.step_flops(rows, slots, config)
            nbytes = shapes_kanana.step_bytes(rows, slots, config, reached)
        if not seconds:
            return None
        what = (
            f"a mean step of {rows:.0f} rows over {slots:.0f} cached slots, "
            f"{reached:.1f} of a layer's {config['n_routed_experts']} experts reached"
        )
    if kernel == "decode_step":
        _say_scopes(run, STEP, STEP_SCOPES)
        _say_scopes(run, PROGRAM, PREFILL_SCOPES)
    share, bound = shapes.roofline_share(flops, nbytes, seconds, run.peak)
    print(f"benchmark: {kernel} is bound by {bound} at {what}", file=sys.stderr)
    return share
