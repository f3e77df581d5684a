"""Operations and bytes of the OLMoE session program's kernels, as functions
of their shapes: the numerators of ``experts_roofline`` and ``attn_roofline``.
The yardstick's own arithmetic: it imports nothing from the program.

``config`` holds the published ``config.json`` keys as the configuration file
runs them. Work is counted for PADDED tokens: the chip computes a bucket's
padding like any other position. Bytes are the least a kernel has to move
(every weight once a program, the tokens' rows in and out once), so a share
cannot pass 100% by an over-count: a kernel that moves more reads lower.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream is float32


def experts_flops(tokens: float, config: dict) -> float:
    """One layer's grouped products: ``gate``, ``up`` and ``down``, one
    multiply and one add for each of a token's ``k`` experts, width and
    hidden unit."""
    k, h, w = config["num_experts_per_tok"], config["hidden_size"], config["intermediate_size"]
    return 2.0 * 3 * tokens * k * h * w


def experts_bytes(tokens: float, config: dict) -> float:
    """One layer: every expert's three matrices read once (with 8 of 64
    experts a token, 64 tokens already reach them all), each token's row
    read and its result written once."""
    e, h, w = config["num_experts"], config["hidden_size"], config["intermediate_size"]
    return float(e) * 3 * h * w * WEIGHT_BYTES + 2.0 * tokens * h * STREAM_BYTES


def attn_flops(rows: float, length: int, config: dict) -> float:
    """One layer's attention block over ``rows`` sessions of ``length``: the
    four projections (q, k, v, o) and the causal half of the two products
    with the keys and the values (``2·L·h`` a token)."""
    h = config["hidden_size"]
    tokens = rows * length
    return tokens * (2.0 * 4 * h * h + 2.0 * length * h)


def attn_bytes(tokens: float, config: dict) -> float:
    """One layer: the four projection matrices once, the stream read and
    written once."""
    h = config["hidden_size"]
    return 4.0 * h * h * WEIGHT_BYTES + 2.0 * tokens * h * STREAM_BYTES


def program_flops(rows: float, length: int, config: dict) -> float:
    """All layers of one ``[rows, length]`` program, attention and experts
    (the router, the norms and the head are under 1%)."""
    per_layer = attn_flops(rows, length, config) + experts_flops(rows * length, config)
    return config["num_hidden_layers"] * per_layer
