"""Operations and bytes the algorithm needs, computed from shapes.

``model`` is the ``model`` group of a configuration file (the repo's
``GPTConfig`` fields). Nothing here looks at the program.
"""

from __future__ import annotations


def _ffn(model: dict) -> int:
    return int(model.get("d_ff") or 4 * model["d_model"])


def block_params(model: dict) -> int:
    """Weights and biases of one transformer block (two layer norms, fused
    qkv, attention out, MLP up and down)."""
    d, f = model["d_model"], _ffn(model)
    return 4 * d * d + 2 * d * f + 9 * d + f


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the blocks and the output head (the tied embedding counts once, as the
    head; an embedding or position lookup does no arithmetic)."""
    return (model["n_layer"] * block_params(model)
            + model["vocab_size"] * model["d_model"])


def total_params(model: dict) -> int:
    d, v = model["d_model"], model["vocab_size"]
    n = model["n_layer"] * block_params(model) + v * d + 2 * d
    if not model.get("tie_embeddings", True):
        n += v * d
    if not model.get("rotary", False):
        n += model["max_seq_len"] * d
    return n


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward and backward, 6N + 12*L*d*T (Megatron's count, full causal
    square: the convention MFU figures are published under). Recomputation is
    not counted."""
    return (6.0 * matmul_params(model)
            + 12.0 * model["n_layer"] * model["d_model"] * seq_len)


def cache_layers(model: dict) -> int:
    """Key and value layers a decode step walks: one a block."""
    return model["n_layer"]


def kv_bytes_per_token(model: dict, kv_dtype_bytes: int = 2) -> int:
    """Keys and values of one cached token over all layers."""
    return 2 * model["n_layer"] * model["d_model"] * kv_dtype_bytes


def decode_step_bytes(model: dict, live_kv_tokens: float,
                      weight_dtype_bytes: int = 2,
                      kv_dtype_bytes: int = 2) -> float:
    """What one decode step over the slot array has to read from HBM: every
    block weight and the head once, and the live keys and values of the
    running requests (from the block tables' lengths). Activations and the
    tokens' own embedding rows are left out: they are thousands of times
    smaller."""
    return (matmul_params(model) * weight_dtype_bytes
            + live_kv_tokens * kv_bytes_per_token(model, kv_dtype_bytes))
