"""A kernel's share of its roofline in the SDAR programs, in the traced
slice, as ``seq_roofline`` reads OLMoE's, with ``benchmark/shapes_sdar.py``:

- ``sdar_experts`` and ``gqa_attn``: the scopes ``experts`` and ``attn`` of
  the prefill (``session_vectors``) at the window's mean stream, times the
  layers that run them there (the last layer makes keys and values only, so
  it is left out of both);
- ``denoise_experts``: the scope ``experts`` of a pass (``denoise_pass``),
  every layer's;
- ``denoise_pass``: the whole pass program.

The rows of a pass are the batch's sessions times the block; the keys it
reads are the batch's real session tokens (``pio_seq_tokens_total{kind=
"real"}`` a batch: a little more than the cache shows a pass, which errs
high by under a block a session of hundreds); the experts a pass has to read
are those the program counted as reached by a real row
(``pio_moe_experts_reached_total`` over ``pio_moe_experts_offered_total``,
times a layer's experts). Says on stderr which peak bounds it. Nothing to
read (no trace, no scopes, another program's counters, the parent): None."""

import sys

from benchmark import shapes, shapes_sdar
from benchmark.readers import program_mean_ms, scope_mean_ms
from benchmark.readers.seq_roofline import PROGRAM, _by_bucket

PASS = "denoise_pass"
REACHED = "pio_moe_experts_reached_total{}"
PREFILL_SCOPES = ["embed", "attn", "router", "experts", "cache"]
PASS_SCOPES = ["embed", "cache", "attn", "router", "experts", "head", "unmask"]
KERNELS = ("sdar_experts", "gqa_attn", "denoise_experts", "denoise_pass")


def _say_scopes(run, program: str, scopes: list) -> None:
    """One line on stderr: the program's mean device time by scope, and the
    share of it the scopes cover (PERF.md section 5 is written from it)."""
    whole = program_mean_ms.read(run, f"jit_{program}")
    times = {scope: scope_mean_ms.read(run, program, scope, scopes, 1e3) for scope in scopes}
    if not whole or not any(times.values()):
        return
    named = sum(ms for ms in times.values() if ms)
    listed = ", ".join(f"{scope} {ms:.3f}" for scope, ms in times.items() if ms)
    print(
        f"benchmark: {program} takes {whole:.3f} ms a program, by scope: {listed} "
        f"({100 * named / whole:.1f}% under a scope)",
        file=sys.stderr,
    )


def _a_batch(run, counter: str):
    batches = run.grown("pio_seq_batches_total{}")
    return run.grown(counter) / batches if batches > 0 and counter in run.counters_end else None


def read(run, kernel: str):
    if run.trace is None or run.peak is None or "moe_intermediate_size" not in run.shapes:
        return None
    if 'pio_seq_passes_total{kind="denoise"}' not in run.counters_end:
        return None
    config = run.shapes
    layers = config["num_hidden_layers"]
    if kernel in ("sdar_experts", "gqa_attn"):
        scope = "experts" if kernel == "sdar_experts" else "attn"
        seconds = scope_mean_ms.read(run, PROGRAM, scope, PREFILL_SCOPES, 1.0)
        rows = _by_bucket(run, "pio_seq_rows_total")
        programs = sum(_by_bucket(run, "pio_seq_programs_total").values())
        if not seconds or not rows or programs <= 0:
            return None
        tokens = sum(r * bucket for bucket, r in rows.items()) / programs
        if kernel == "sdar_experts":
            flops, nbytes = shapes_sdar.experts_flops(tokens, config), shapes_sdar.experts_bytes(tokens, config)
        else:
            flops = sum(shapes_sdar.gqa_attn_flops(r, bucket, config) for bucket, r in rows.items()) / programs
            nbytes = shapes_sdar.gqa_attn_bytes(tokens, config)
        flops, nbytes, what = (layers - 1) * flops, (layers - 1) * nbytes, f"a mean stream of {tokens:.0f} tokens"
    else:
        sessions = sum(_by_bucket(run, "pio_seq_sessions_total").values())
        batches = run.grown("pio_seq_batches_total{}")
        cached = _a_batch(run, 'pio_seq_tokens_total{kind="real"}')
        if batches <= 0 or sessions <= 0 or not cached:
            return None
        sessions /= batches
        rows = sessions * config["generation"]["block_length"]
        offered = run.grown("pio_moe_experts_offered_total{}") if REACHED in run.counters_end else 0
        if offered <= 0:
            return None
        reached = config["num_experts"] * run.grown(REACHED) / offered
        if kernel == "denoise_experts":
            seconds = scope_mean_ms.read(run, PASS, "experts", PASS_SCOPES, 1.0)
            flops = layers * shapes_sdar.experts_flops(rows, config)
            nbytes = layers * shapes_sdar.experts_bytes(rows, config, reached)
        else:
            ms = program_mean_ms.read(run, f"jit_{PASS}")
            seconds = None if ms is None else ms / 1e3
            flops = shapes_sdar.pass_flops(rows, cached, sessions, config)
            nbytes = shapes_sdar.pass_bytes(rows, cached, config, reached)
        if not seconds:
            return None
        what = (
            f"a mean pass of {rows:.0f} positions over {cached:.0f} cached keys, "
            f"{reached:.1f} of a layer's {config['num_experts']} experts reached"
        )
    if kernel == "denoise_pass":
        _say_scopes(run, PASS, PASS_SCOPES)
        _say_scopes(run, PROGRAM, PREFILL_SCOPES)
    share, bound = shapes.roofline_share(flops, nbytes, seconds, run.peak)
    print(f"benchmark: {kernel} is bound by {bound} at {what}", file=sys.stderr)
    return share
