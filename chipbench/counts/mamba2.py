"""Operations and bytes of one fused decode step of a Mamba-2 model.

Sizes come from the configuration's file.  One step feeds one token to
each of ``slots`` lanes through every layer and the tied output head.
"""

from __future__ import annotations

from typing import Any, Mapping, Tuple

WORD = 4   # float32 weights and state


def widths(c: Mapping[str, Any]) -> Tuple[int, int, int]:
    """(d_inner, in-projection width, state elements per lane and layer)."""
    d_inner = c["ssm_expand"] * c["d_model"]
    width = 2 * d_inner + 2 * c["ssm_groups"] * c["ssm_state"] \
        + c["ssm_heads"]
    state = c["ssm_heads"] * c["ssm_head_dim"] * c["ssm_state"]
    return d_inner, width, state


def matmul_params(c: Mapping[str, Any]) -> int:
    """Weights each token multiplies: in and out projections of every
    layer, and the output head (the embedding, tied)."""
    d_inner, width, _ = widths(c)
    per_layer = c["d_model"] * width + d_inner * c["d_model"]
    return c["n_layers"] * per_layer + c["vocab"] * c["d_model"]


def weight_bytes(c: Mapping[str, Any]) -> int:
    """Every weight of the model, which one step reads once."""
    d_inner, width, _ = widths(c)
    h = c["ssm_heads"]
    per_layer = (c["d_model"] * width + d_inner * c["d_model"]
                 + 3 * h + d_inner + c["d_model"])
    return WORD * (c["n_layers"] * per_layer + c["vocab"] * c["d_model"]
                   + c["d_model"])


def token_flops(c: Mapping[str, Any]) -> float:
    """FLOPs of one token through the step: two per multiplied weight,
    and five per state element and layer (decay, input outer product,
    sum, and the output contraction's multiply and add)."""
    _, _, state = widths(c)
    return 2.0 * matmul_params(c) + 5.0 * state * c["n_layers"]


def step_bytes(c: Mapping[str, Any], slots: int) -> float:
    """Least bytes one step moves: every weight once, and every slot's
    recurrent state read and written."""
    _, _, state = widths(c)
    return float(weight_bytes(c)
                 + 2 * WORD * state * c["n_layers"] * slots)
