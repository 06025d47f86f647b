"""Operations and bytes a looped decoder's serving step needs, computed
from the model's shapes: the algorithm's work, whatever implements it.

``model`` is the ``model`` group of a configuration file (``d_model``,
``n_layers``, ``n_heads``, ``d_ff``, ``vocab_size``, ``passes``; the
weights and the cache in 2-byte elements unless ``bytes_per_element``
says otherwise). A layer is four square projections and a gated
feed-forward: ``4 d^2 + 3 d d_ff`` parameters, used once per pass; the
output head is ``vocab x d``, used once per token. Every pass of every
layer keeps its own keys and values, so a cached row is ``passes x
n_layers`` cache layers of ``2 d`` elements.
"""

from __future__ import annotations


def layer_params(model: dict) -> int:
    d = model["d_model"]
    return 4 * d * d + 3 * d * model["d_ff"]


def stack_params(model: dict) -> int:
    """Parameters of the layers, each counted once."""
    return model["n_layers"] * layer_params(model)


def head_params(model: dict) -> int:
    return model["vocab_size"] * model["d_model"]


def cache_layers(model: dict) -> int:
    return model["n_layers"] * model.get("passes", 1)


def kv_row_bytes(model: dict, bytes_per_element: int = 2) -> int:
    """Bytes one cached token holds: K and V of every cache layer."""
    return cache_layers(model) * 2 * model["d_model"] * bytes_per_element


def token_flops(model: dict, rows_attended: float) -> float:
    """FLOPs to produce one token's logits with ``rows_attended`` keys
    visible to it: two per parameter and pass of the stack, two per
    parameter of the head, and per cache layer ``4 d`` per key (q.k and
    p.v over all heads)."""
    passes = model.get("passes", 1)
    return (2.0 * stack_params(model) * passes + 2.0 * head_params(model)
            + 4.0 * model["d_model"] * cache_layers(model) * rows_attended)


def prompt_flops(model: dict, n_prompt: int) -> float:
    """A prompt of ``n_prompt`` tokens through prefill: every token
    through the stack, the head once (the last position's logits are
    the ones used), and causal attention over ``n (n + 1) / 2`` pairs."""
    passes = model.get("passes", 1)
    return (2.0 * stack_params(model) * passes * n_prompt
            + 2.0 * head_params(model)
            + 4.0 * model["d_model"] * cache_layers(model)
            * n_prompt * (n_prompt + 1) / 2)


def decode_weight_bytes(model: dict, bytes_per_element: int = 2) -> float:
    """Bytes of weights one decode step must read whatever its batch:
    the layers once per pass, the head once."""
    return float(bytes_per_element) * (
        stack_params(model) * model.get("passes", 1) + head_params(model))


def decode_step_bytes(model: dict, live_rows: float,
                      bytes_per_element: int = 2) -> float:
    """Bytes one decode step must move: the weights and every live row
    of the cache (``live_rows`` summed over the step's sequences)."""
    return (decode_weight_bytes(model, bytes_per_element)
            + live_rows * kv_row_bytes(model, bytes_per_element))
