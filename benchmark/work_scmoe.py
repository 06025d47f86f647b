"""Operations and bytes the serving step of a shortcut-connected stack of
sparse-expert layers over latent attention needs, computed from the
model's shapes: the algorithm's work, whatever implements it.

``model`` is the ``model`` group of a configuration file: ``d_model``,
``n_layers``, ``n_heads``, ``d_ff``, ``vocab_size``, ``sub_blocks``,
``latent`` (``q_rank``, ``kv_rank``, ``nope_dim``, ``rope_dim``,
``v_dim``) and ``experts`` (``n_routed``, ``n_identity``, ``top_k``,
``d_expert``, ``held``); weights and cache in 2-byte elements unless
``bytes_per_element`` says otherwise.

A layer outside its experts: ``sub_blocks`` latent attention blocks and
gated feed-forwards, their norms, and the router. An expert is a gated
feed-forward of width ``d_expert``; a step multiplies a token with the
held experts it picked and reads each held expert that any token picked,
once. A cached token is one row of ``kv_rank + rope_dim`` values in each
of ``n_layers x sub_blocks`` cache layers (the algorithm's row: the pool
pads it to whole 128-value tiles).
"""

from __future__ import annotations


def attention_params(model: dict) -> int:
    """One latent attention block: query down and up, key/value down
    and up, output; two norm scales."""
    d, h, la = model["d_model"], model["n_heads"], model["latent"]
    return (d * la["q_rank"]
            + la["q_rank"] * h * (la["nope_dim"] + la["rope_dim"])
            + d * (la["kv_rank"] + la["rope_dim"])
            + la["kv_rank"] * h * (la["nope_dim"] + la["v_dim"])
            + h * la["v_dim"] * d
            + la["q_rank"] + la["kv_rank"])


def ffn_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff"]


def router_params(model: dict) -> int:
    ex = model["experts"]
    outputs = ex["n_routed"] + ex.get("n_identity", 0)
    return model["d_model"] * outputs + outputs        # matrix and bias


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["experts"]["d_expert"]


def layer_params(model: dict) -> int:
    """One layer outside its experts."""
    sub = model.get("sub_blocks", 1)
    return (sub * (attention_params(model) + ffn_params(model)
                   + 2 * model["d_model"]) + router_params(model))


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["d_model"]


def held_params(model: dict) -> int:
    """Every parameter this share of the model holds: the layers with
    their held experts, embedding, untied head, final norm."""
    return (model["n_layers"] * (layer_params(model)
                                 + model["experts"]["held"]
                                 * expert_params(model))
            + 2 * head_params(model) + model["d_model"])


def cache_layers(model: dict) -> int:
    return model["n_layers"] * model.get("sub_blocks", 1)


def row_values(model: dict) -> int:
    return model["latent"]["kv_rank"] + model["latent"]["rope_dim"]


def kv_row_bytes(model: dict, bytes_per_element: int = 2) -> int:
    """Bytes one cached token holds: a latent row in every cache layer."""
    return cache_layers(model) * row_values(model) * bytes_per_element


def expert_bytes(model: dict, bytes_per_element: int = 2) -> int:
    return expert_params(model) * bytes_per_element


def decode_fixed_bytes(model: dict, bytes_per_element: int = 2) -> float:
    """Bytes of weights one decode step reads whatever its batch and its
    routing: the layers outside their experts and the head."""
    return float(bytes_per_element) * (
        model["n_layers"] * layer_params(model) + head_params(model))


def decode_step_bytes(model: dict, rows_read: float, experts_touched: float,
                      bytes_per_element: int = 2) -> float:
    """Bytes one decode step must move: the fixed weights, each held
    expert that received a token (summed over the layers), and every
    live row of the cache."""
    return (decode_fixed_bytes(model, bytes_per_element)
            + experts_touched * expert_bytes(model, bytes_per_element)
            + rows_read * kv_row_bytes(model, bytes_per_element))


def attention_flops_per_row(model: dict) -> float:
    """FLOPs of one query against one visible row in every cache layer,
    in the absorbed form: per head a score over the whole row and a
    value sum over its first ``kv_rank`` values."""
    return (2.0 * model["n_heads"]
            * (row_values(model) + model["latent"]["kv_rank"])
            * cache_layers(model))


def expected_local_picks(model: dict) -> float:
    """The picks a token sends to experts held here, over all layers,
    under a router with no preference: ``top_k x held / outputs`` a
    layer."""
    ex = model["experts"]
    outputs = ex["n_routed"] + ex.get("n_identity", 0)
    return model["n_layers"] * ex["top_k"] * ex["held"] / outputs


def token_flops(model: dict, rows_attended: float,
                local_picks: float | None = None) -> float:
    """FLOPs to produce one token's logits with ``rows_attended`` rows
    visible to it: two per parameter the token is actually multiplied
    with (the layers outside their experts, the head, and one expert for
    each of its ``local_picks``; an identity expert multiplies nothing),
    and the absorbed attention."""
    if local_picks is None:
        local_picks = expected_local_picks(model)
    return (2.0 * (model["n_layers"] * layer_params(model)
                   + head_params(model)
                   + local_picks * expert_params(model))
            + attention_flops_per_row(model) * rows_attended)


def prompt_flops(model: dict, n_prompt: int,
                 local_picks: float | None = None) -> float:
    """A prompt of ``n_prompt`` tokens through prefill: every token
    through the layers, the head once, causal attention over ``n (n +
    1) / 2`` pairs (counted in the absorbed form, the cheaper one)."""
    if local_picks is None:
        local_picks = expected_local_picks(model)
    return (2.0 * n_prompt * (model["n_layers"] * layer_params(model)
                              + local_picks * expert_params(model))
            + 2.0 * head_params(model)
            + attention_flops_per_row(model) * n_prompt * (n_prompt + 1) / 2)
