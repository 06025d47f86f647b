"""What both runners need: a dotted path resolved, a model
configuration built from its JSON keys, a seed folded to 31 bits."""

from __future__ import annotations

import importlib

import jax.numpy as jnp


def resolve(dotted: str):
    """``pkg.module.attr`` -> the attribute."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def model_config(config: dict):
    """The program's configuration object from the file's ``model``
    keys; a key that ends in ``dtype`` names a ``jax.numpy`` type."""
    keys = {k: (getattr(jnp, v) if k.endswith("dtype") and v else v)
            for k, v in config["model"].items()}
    return resolve(config["model_config"])(**keys)


def fold_seed(seed: int) -> int:
    """``--seed`` may pass 2**31; a PRNG key is made from 31 bits."""
    return (seed ^ (seed >> 31)) & 0x7FFFFFFF
