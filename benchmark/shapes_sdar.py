"""Operations and bytes of the SDAR programs' kernels, as functions of their
shapes: the numerators of ``sdar_experts_roofline`` and ``gqa_attn_roofline``
(the prefill, ``session_vectors``) and of ``denoise_pass_roofline`` and
``denoise_experts_roofline`` (a pass, ``denoise_pass``). The yardstick's own
arithmetic: it imports nothing from the program.

``config`` holds the configuration file's keys: the published ``config.json``
keys as run and ``generation`` (block length, steps). Work is the LEAST a
kernel must do whatever implements it: every weight read once a program, the
tokens' rows in and out once, the block-causal half of a stream's square
(which is more than the segment-masked kernel does), an expert's matrices
only if a real row reaches it, a session's cached keys and values once a
pass. So a share cannot pass 100% by an over-count: a kernel that does or
moves more reads lower. The experts REACHED are counted where a few rows meet
many experts: a pass's 128 rows reach what the program counted
(``pio_moe_experts_reached_total``: a skewed router leaves some of a layer's
128 unread, and a session that is done rides along with no real row); a
prefill's thousands of rows reach every expert of any router that sends a
token to 8, and there an even router's reach is taken.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream is float32
CACHE_BYTES = 2  # keys and values are kept in bfloat16


def attn_weights(config: dict) -> float:
    """Parameters of one layer's four attention matrices: q over all heads,
    k and v over the key/value heads, o."""
    h, d = config["hidden_size"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    return float(h * heads * d + 2 * h * kv_heads * d + heads * d * h)


def expert_weights(config: dict) -> float:
    """Parameters of ONE expert: gate, up and down."""
    return 3.0 * config["hidden_size"] * config["moe_intermediate_size"]


def experts_reached(rows: float, config: dict) -> float:
    """Experts an even router reaches with ``rows`` tokens of ``k`` distinct
    experts each: ``E (1 - (1 - k / E) ** rows)``; all of them from a few
    dozen rows on."""
    e, k = config["num_experts"], config["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def kv_bytes_a_token(config: dict) -> float:
    """Bytes of ONE layer's key and value of one token."""
    return 2.0 * config["num_key_value_heads"] * config["head_dim"] * CACHE_BYTES


# ------------------------------------------------------------- the prefill


def experts_flops(tokens: float, config: dict) -> float:
    """One layer's grouped products over ``tokens`` rows: ``gate``, ``up``
    and ``down`` for each of a token's ``k`` experts."""
    return 2.0 * tokens * config["num_experts_per_tok"] * expert_weights(config)


def experts_bytes(tokens: float, config: dict, reached: float | None = None) -> float:
    """One layer: the ``reached`` experts' matrices once (an even router's
    reach where the program counted none), each token's row read and its
    result written once."""
    if reached is None:
        reached = experts_reached(tokens, config)
    return reached * expert_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def gqa_attn_flops(rows: float, length: int, config: dict) -> float:
    """One layer's attention block over ``rows`` streams of ``length``: the
    four projections and the block-causal half of the two products (queries
    of every head with keys and values of 128: ``2 · L · heads · d`` a token)."""
    tokens = rows * length
    heads, d = config["num_attention_heads"], config["head_dim"]
    return tokens * (2.0 * attn_weights(config) + 2.0 * length * heads * d)


def gqa_attn_bytes(tokens: float, config: dict) -> float:
    """One layer: the four matrices once, the stream read and written once,
    the keys and values written once at the key/value heads' width."""
    return (
        attn_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES
        + tokens * kv_bytes_a_token(config)
    )


# ------------------------------------------------------------------ a pass


def pass_flops(rows: float, cached: float, sessions: float, config: dict) -> float:
    """One pass over ``rows`` block positions of ``sessions`` sessions that
    hold ``cached`` keys between them: in every layer the projections, each
    position's products with its own session's keys and values and its ``k``
    experts; then ``lm_head``."""
    heads, d = config["num_attention_heads"], config["head_dim"]
    own = cached / max(sessions, 1.0)
    a_layer = rows * (2.0 * attn_weights(config) + 2.0 * 2 * own * heads * d) + experts_flops(rows, config)
    return config["num_hidden_layers"] * a_layer + 2.0 * rows * config["hidden_size"] * config["vocab_size"]


def pass_bytes(rows: float, cached: float, config: dict, reached: float | None = None) -> float:
    """One pass: in every layer the ``reached`` experts' matrices, the four
    attention matrices and the sessions' cached keys and values, each once;
    ``lm_head`` once; the positions' rows in and out."""
    a_layer = (
        experts_bytes(rows, config, reached) + attn_weights(config) * WEIGHT_BYTES + cached * kv_bytes_a_token(config)
    )
    return config["num_hidden_layers"] * a_layer + config["vocab_size"] * config["hidden_size"] * WEIGHT_BYTES
