"""Random weights from ``--seed``, made on the device in one jitted call.

The configuration's reference module (``chipbench/references/<name>.py``)
draws the tree, in the layout the program takes for the configuration's
architecture and in its ``param_dtype``; the reference takes the same
tree, so both sides start from one set of weights that neither made.
"""
from __future__ import annotations

from typing import Dict

import jax

from chipbench import common


def prng_key(seed: int):
    """A key for any whole-number seed, also one past 32 bits."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def make(seed: int, cfg: Dict, shardings=None):
    """The weights of configuration file ``cfg`` for ``seed``, placed
    on ``shardings`` (a tree like the weights) or the default device."""
    ref = common.reference(cfg)
    fn = jax.jit(lambda key: ref.init_tree(key, cfg["model"],
                                           cfg["init_std"],
                                           cfg["param_dtype"]),
                 out_shardings=shardings)
    return fn(prng_key(seed))
