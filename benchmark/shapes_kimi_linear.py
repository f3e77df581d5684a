"""Operations and bytes of the Kimi-Linear session program's kernels, as
functions of their shapes: the numerators of ``kda_roofline``,
``mla_roofline`` and ``experts_held_roofline``. The yardstick's own
arithmetic: it imports nothing from the program.

``config`` holds the configuration file's keys: the published ``config.json``
keys as run (``num_experts`` is the experts HELD here), and the published
counts under ``published``. Work is counted for PADDED tokens (the chip
computes a bucket's padding like any other position) and is the LEAST a
kernel must do: every weight read once a program, the tokens' rows in and
out once, the causal half of attention's products, the recurrence's own
operations and none of the chunked form's extra ones. So a share cannot pass
100% by an over-count: a kernel that does or moves more reads lower.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream is float32


def layer_counts(config: dict) -> dict:
    """How many of the layers that run are of each kind."""
    n = int(config["num_hidden_layers"])
    layers = range(1, n + 1)
    kda = sum(i in config["linear_attn_config"]["kda_layers"] for i in layers)
    dense = sum(i <= int(config["first_k_dense_replace"]) for i in layers)
    return {"kda": kda, "mla": n - kda, "dense": dense, "sparse": n - dense}


def kda_weights(config: dict) -> float:
    """Parameters of one KDA mixer: q, k, v, o; the two low-rank gates; the
    step size; the three convolutions."""
    h = config["hidden_size"]
    spec = config["linear_attn_config"]
    heads, d, taps = spec["num_heads"], spec["head_dim"], spec["short_conv_kernel_size"]
    wide = heads * d
    return 4.0 * h * wide + 2 * (h * d + d * wide) + h * heads + 3 * taps * wide


def kda_flops(tokens: float, config: dict) -> float:
    """One KDA layer: a multiply and an add for every weight and token (the
    projections, the gates, the convolutions), and the recurrence's own
    operations a token and head: the decay of the state (d_k d_v), its
    product with the key, the rank-one update and its product with the
    query (2 d_k d_v each): 7 d_k d_v."""
    spec = config["linear_attn_config"]
    heads, d = spec["num_heads"], spec["head_dim"]
    return tokens * (2.0 * kda_weights(config) + 7.0 * heads * d * d)


def kda_bytes(tokens: float, config: dict) -> float:
    """One KDA layer: its weights once, the stream read and written once."""
    return kda_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def mla_weights(config: dict) -> float:
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, d_v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rank = config["kv_lora_rank"]
    return float(h * heads * (nope + rope) + h * (rank + rope) + rank * heads * (nope + d_v) + heads * d_v * h)


def mla_flops(rows: float, length: int, config: dict) -> float:
    """One latent-attention layer over ``rows`` sessions of ``length``: the
    projections, and the causal half of the two products, with the keys at
    ``nope + rope`` and with the values at ``v_head_dim``."""
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    tokens = rows * length
    return tokens * (2.0 * mla_weights(config) + heads * length * (qk + config["v_head_dim"]))


def mla_bytes(tokens: float, config: dict) -> float:
    return mla_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def held_copies(tokens: float, config: dict) -> float:
    """Copies of ``tokens`` an even router sends to the experts held here."""
    return tokens * config["num_experts_per_token"] * config["num_experts"] / config["published"]["num_experts"]


def experts_held_flops(tokens: float, config: dict) -> float:
    """One sparse layer's grouped products over the held experts: ``gate``,
    ``up`` and ``down`` for the copies routed here."""
    return 2.0 * 3 * held_copies(tokens, config) * config["hidden_size"] * config["moe_intermediate_size"]


def experts_held_bytes(tokens: float, config: dict) -> float:
    """One sparse layer: every held expert's three matrices once, each
    token's row read and its result written once."""
    h, w = config["hidden_size"], config["moe_intermediate_size"]
    return float(config["num_experts"]) * 3 * h * w * WEIGHT_BYTES + 2.0 * tokens * h * STREAM_BYTES
