"""Operations and bytes of the Granite session program's kernels, as functions
of their shapes: the numerators of ``ssd_roofline`` and
``experts_held36_roofline`` (and of the Mamba projections' and the
attention's shares, which ``readers/granite_roofline.py`` reads by the same
table). The yardstick's own arithmetic: it imports nothing from the program.

``config`` holds the configuration file's keys: the published ``config.json``
keys as run (``num_local_experts`` is the experts HELD here), and the
published counts under ``published``. Work is counted for PADDED tokens (the
chip computes a stream's padding like any other position) and is the LEAST a
kernel must do: every weight read once a program, the tokens' rows in and out
once, the scan's RECURRENCE and none of the chunked form's triangles (they
grow with the chunk the program chooses; the recurrence does not), the causal
half of a row's attention products, a held expert's products for the copies
an even router sends it and no tile's padding. So a share cannot pass 100% by
an over-count: a kernel that does or moves more reads lower.
"""

from __future__ import annotations

WEIGHT_BYTES = 2  # bfloat16, as the configuration states
STREAM_BYTES = 4  # the residual stream, and everything in the scan, is float32


def layer_counts(config: dict) -> dict:
    """How many of the layers that run are of each kind (all are sparse)."""
    n = int(config["num_hidden_layers"])
    mamba = sum(kind == "mamba" for kind in config["layer_types"][:n])
    return {"mamba": mamba, "attn": n - mamba, "sparse": n}


def mamba_inner(config: dict) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def mamba_proj_weights(config: dict) -> float:
    """Parameters of one Mamba-2 mixer's two projections: ``in_proj`` (hidden
    to ``z``, ``x``, ``B``, ``C`` and ``dt``) and ``out_proj``."""
    h, inner = config["hidden_size"], mamba_inner(config)
    return float(h * (2 * inner + 2 * config["mamba_d_state"] + config["mamba_n_heads"]) + inner * h)


def mamba_proj_flops(tokens: float, config: dict) -> float:
    """One Mamba-2 layer's projections: a multiply and an add for every weight and token."""
    return tokens * 2.0 * mamba_proj_weights(config)


def mamba_proj_bytes(tokens: float, config: dict) -> float:
    """The two matrices once, the stream read and written once."""
    return mamba_proj_weights(config) * WEIGHT_BYTES + 2.0 * tokens * config["hidden_size"] * STREAM_BYTES


def ssd_flops(tokens: float, config: dict) -> float:
    """One layer's state-space scan, by its recurrence: a token and head
    decays the state (``p n`` multiplies), adds ``step x B^T`` (``p n``
    multiply-adds) and reads it with ``C`` (``p n`` multiply-adds): ``5 p n``
    operations, and ``2 p`` more for the skip ``D x``."""
    heads, p, n = config["mamba_n_heads"], config["mamba_d_head"], config["mamba_d_state"]
    return tokens * heads * (5.0 * p * n + 2.0 * p)


def ssd_bytes(tokens: float, config: dict) -> float:
    """One layer's scan: ``x``, ``B``, ``C`` and the step read once and ``y``
    written once, float32 (its state never has to leave the chip)."""
    inner, n, heads = mamba_inner(config), config["mamba_d_state"], config["mamba_n_heads"]
    return tokens * (2.0 * inner + 2 * n + heads) * STREAM_BYTES


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


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
    share = config["num_local_experts"] / config["published"]["num_local_experts"]
    return tokens * config["num_experts_per_tok"] * share


def experts_held_flops(tokens: float, config: dict) -> float:
    """One layer's grouped products over the held experts: ``gate``, ``up``
    and ``down`` for the copies routed here."""
    return 2.0 * 3 * held_copies(tokens, config) * config["hidden_size"] * config["intermediate_size"]


def experts_held_bytes(tokens: float, config: dict) -> float:
    """One layer: every held expert's three matrices once, each token's row
    read and its result written once."""
    h, w = config["hidden_size"], config["intermediate_size"]
    return float(config["num_local_experts"]) * 3 * h * w * WEIGHT_BYTES + 2.0 * tokens * h * STREAM_BYTES
