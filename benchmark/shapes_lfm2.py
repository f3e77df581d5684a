"""Operations and bytes of the LFM2 session program's kernels, as functions
of their shapes: the numerators of ``conv_roofline``, ``attn64_roofline`` and
``experts_held8_roofline``. The yardstick's own arithmetic: it imports
nothing from the program.

``config`` holds the configuration file's keys: the published ``config.json``
keys as run (``num_experts`` is the experts HELD here), and the published
count under ``published``. Work is counted for PADDED tokens (the chip
computes a stream's padding like any other position) and is the LEAST a
kernel must do: every weight read once a program, the tokens' rows in and out
once, the causal half of a row's attention products (a kernel that skips by
segment does less still: at the cell's sessions the four projections are
seven tenths of the count), a held expert's products for the copies an even
router sends it and no tile's padding. So a share cannot pass 100% by an
over-count: a kernel that does or moves more reads lower.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream is float32


def layer_counts(config: dict) -> dict:
    """How many of the layers that run are of each kind."""
    n = int(config["num_hidden_layers"])
    conv = sum(kind == "conv" for kind in config["layer_types"][:n])
    dense = min(int(config["num_dense_layers"]), n)
    return {"conv": conv, "attn": n - conv, "dense": dense, "sparse": n - dense}


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def conv_weights(config: dict) -> float:
    """Parameters of one gated short convolution: ``in_proj`` (hidden to
    three times hidden), the taps, ``out_proj``."""
    h = config["hidden_size"]
    return float(h * 3 * h + config["conv_L_cache"] * h + h * h)


def conv_flops(tokens: float, config: dict) -> float:
    """One convolution layer: a multiply and an add for every weight and
    token (the two projections, the taps); the two gates' one multiply a
    channel is left out."""
    return tokens * 2.0 * conv_weights(config)


def conv_bytes(tokens: float, config: dict) -> float:
    """One convolution layer: its weights once, the stream read and written once."""
    return conv_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def attn_weights(config: dict) -> float:
    """Parameters of one attention mixer: q and o at all the heads, k and v
    at the key/value heads."""
    h, d = config["hidden_size"], head_dim(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return float(2 * h * heads * d + 2 * h * kv * d)


def attn_flops(rows: float, length: int, config: dict) -> float:
    """One attention layer over ``rows`` streams of ``length``: the four
    projections, and the causal half of the two products at the head's
    width (``length * d`` multiply-adds a token and head each, halved)."""
    tokens = rows * length
    products = config["num_attention_heads"] * length * 2.0 * head_dim(config)
    return tokens * (2.0 * attn_weights(config) + products)


def attn_bytes(tokens: float, config: dict) -> float:
    return attn_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def held_copies(tokens: float, config: dict) -> float:
    """Copies of ``tokens`` an even router sends to the experts held here."""
    return tokens * config["num_experts_per_tok"] * config["num_experts"] / config["published"]["num_experts"]


def experts_held_flops(tokens: float, config: dict) -> float:
    """One sparse layer's grouped products over the held experts: ``gate``,
    ``up`` and ``down`` for the copies routed here."""
    return 2.0 * 3 * held_copies(tokens, config) * config["hidden_size"] * config["moe_intermediate_size"]


def experts_held_bytes(tokens: float, config: dict) -> float:
    """One sparse layer: every held expert's three matrices once, each
    token's row read and its result written once."""
    h, w = config["hidden_size"], config["moe_intermediate_size"]
    return float(config["num_experts"]) * 3 * h * w * WEIGHT_BYTES + 2.0 * tokens * h * STREAM_BYTES
