"""Operations the algorithm needs, computed from shapes.

``train_step_flops`` is 6*N per token for the forward and backward
matmuls plus the attention term, halved under a causal mask (the
second yardstick it was copied from went with PR 31: this is the only
one). Recomputed operations do not count.
"""


def train_step_flops(model: dict, batch: int, n_params: int) -> float:
    """Model FLOPs of one train step of ``batch`` sequences."""
    seq = model["max_seq_len"]
    causal_factor = 0.5 if model.get("causal", True) else 1.0
    attn = (model["n_layers"] * 12 * batch * seq ** 2
            * model["d_model"] * causal_factor)
    return 6 * n_params * batch * seq + attn
