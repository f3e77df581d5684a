"""Operations and bytes of the kanana programs' kernels, as functions of their
shapes: the numerators of ``mla_expanded_roofline`` (the prefill,
``session_vectors``) and of ``decode_step_roofline``, ``mla_absorbed_roofline``
and ``decode_experts_roofline`` (a step, ``decode_step``). The yardstick's own
arithmetic: it imports nothing from the program.

``config`` holds the configuration file's keys: the published ``config.json``
keys as run. Work is the LEAST a kernel must do whatever implements it: every
weight read once a program, the tokens' rows in and out once, the causal half
of a stream's square, an expert's matrices only if a real row reaches it, a
session's cached latents (512 + 64 values a token and layer) once a step, and
a step's attention in the ABSORBED form (a query multiplied into the latent's
space meets the cache as it lies: expanding the cache to every head's keys and
values a step would be a hundred times the work). So a share cannot pass 100%
by an over-count: a kernel that does or moves more reads lower. In the
prefill's last layer the queries, the output projection and the products are
the sessions' last positions' alone: they are left out, and that layer counts
for its latent and its expansion.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream is float32
CACHE_BYTES = 2  # the latent cache is kept in bfloat16


def layer_counts(config: dict) -> dict[str, int]:
    dense = int(config["first_k_dense_replace"])
    return {"dense": dense, "sparse": int(config["num_hidden_layers"]) - dense}


def latent_width(config: dict) -> int:
    """Values a token leaves a layer: the latent and the rotary key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def mla_parts(config: dict) -> dict[str, float]:
    """Parameters of one layer's four attention matrices."""
    h, heads, rank = config["hidden_size"], config["num_attention_heads"], config["kv_lora_rank"]
    nope, rot, d_v = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return {
        "wq": float(h * heads * (nope + rot)), "w_kva": float(h * (rank + rot)),
        "w_kvb": float(rank * heads * (nope + d_v)), "wo": float(heads * d_v * h),
    }


def mla_weights(config: dict) -> float:
    return sum(mla_parts(config).values())


def expert_weights(config: dict) -> float:
    """Parameters of ONE routed expert: gate, up and down."""
    return 3.0 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_weights(config: dict) -> float:
    return config["n_shared_experts"] * expert_weights(config)


def dense_weights(config: dict) -> float:
    return 3.0 * config["hidden_size"] * config["intermediate_size"]


def router_weights(config: dict) -> float:
    return float(config["hidden_size"] * config["n_routed_experts"])


def latent_bytes_a_token(config: dict) -> float:
    """Bytes ONE layer keeps of one token."""
    return float(latent_width(config) * CACHE_BYTES)


# ------------------------------------------------------------- the prefill


def mla_expanded_flops(rows: float, length: int, config: dict) -> float:
    """One layer's attention block over ``rows`` streams of ``length`` in the
    expanded form: the four projections and the causal half of the two
    products (a head's queries and keys at 128 + 64, its values at 128:
    ``L · heads · (192 + 128)`` a token)."""
    heads = config["num_attention_heads"]
    wide = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] + config["v_head_dim"]
    return rows * length * (2.0 * mla_weights(config) + length * heads * wide)


def mla_latent_flops(tokens: float, config: dict) -> float:
    """The LAST layer's part that every token takes: its latent and the
    expansion to the keys and values the last positions read."""
    parts = mla_parts(config)
    return tokens * 2.0 * (parts["w_kva"] + parts["w_kvb"])


def mla_expanded_bytes(tokens: float, config: dict) -> float:
    """One layer: the four matrices once, the stream read and written once,
    the tokens' latents written once."""
    return (
        mla_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES
        + tokens * latent_bytes_a_token(config)
    )


# ------------------------------------------------------------------ a step


def mla_absorbed_flops(rows: float, slots: float, config: dict) -> float:
    """One layer's attention block of a step, ``rows`` new positions of
    sessions that hold ``slots`` cached positions between them: the
    projections of a row (``W_uk`` and ``W_uv``, the halves of ``w_kvb``, meet
    the row's 32 queries and sums, not the cache), and every head's products
    with its own session's slots: scores over 512 + 64, sums over 512."""
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    return rows * 2.0 * mla_weights(config) + 2.0 * heads * slots * (latent_width(config) + rank)


def mla_absorbed_bytes(rows: float, slots: float, config: dict) -> float:
    """One layer: the four matrices once, the sessions' cached latents once
    (for all 32 heads), the rows in and out."""
    return (
        mla_weights(config) * WEIGHT_BYTES + slots * latent_bytes_a_token(config)
        + 2.0 * rows * config["hidden_size"] * STREAM_BYTES
    )


def experts_flops(rows: float, config: dict) -> float:
    """One sparse layer's grouped products over ``rows`` rows: ``gate``,
    ``up`` and ``down`` for each of a row's ``k`` experts."""
    return 2.0 * rows * config["num_experts_per_tok"] * expert_weights(config)


def experts_bytes(rows: float, config: dict, reached: float) -> float:
    """One sparse layer: the ``reached`` experts' matrices once, each row
    read and its result written once."""
    return reached * expert_weights(config) * WEIGHT_BYTES + 2.0 * rows * config["hidden_size"] * STREAM_BYTES


def step_flops(rows: float, slots: float, config: dict) -> float:
    """One step: every layer's attention block, the dense layer's and the
    shared experts' products, the routers and the routed experts, then
    ``lm_head``."""
    counts = layer_counts(config)
    a_sparse = experts_flops(rows, config) + rows * 2.0 * (shared_weights(config) + router_weights(config))
    return (
        config["num_hidden_layers"] * mla_absorbed_flops(rows, slots, config)
        + counts["dense"] * rows * 2.0 * dense_weights(config) + counts["sparse"] * a_sparse
        + 2.0 * rows * config["hidden_size"] * config["vocab_size"]
    )


def step_bytes(rows: float, slots: float, config: dict, reached: float) -> float:
    """One step: in every layer the attention matrices and the sessions'
    cached latents; the dense layer's matrices; in a sparse layer the
    ``reached`` experts', the shared experts' and the router's; ``lm_head``;
    each once."""
    counts = layer_counts(config)
    a_sparse = experts_bytes(rows, config, reached) + (shared_weights(config) + router_weights(config)) * WEIGHT_BYTES
    return (
        config["num_hidden_layers"] * mla_absorbed_bytes(rows, slots, config)
        + counts["dense"] * dense_weights(config) * WEIGHT_BYTES + counts["sparse"] * a_sparse
        + config["vocab_size"] * config["hidden_size"] * WEIGHT_BYTES
    )
