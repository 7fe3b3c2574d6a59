"""Random weights for a decoder configuration, made on the device from the
seed in one jitted call, in the type they are served in.

The layout is a tree of ``WSpec``: shape, type and scale of each leaf
(normal with that standard deviation, or ones).  The model's reference
module gives it (``weight_specs``), as the serving engine's parameter tree;
``check_layout`` holds it against the program's own abstract tree, so a
change of layout fails loudly instead of serving something else.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

from perfbench import core


class WSpec(NamedTuple):
    shape: Tuple[int, ...]
    dtype: str
    std: Optional[float]  # None: ones


def check_layout(specs: dict, mcfg) -> None:
    """The program's parameter tree for ``mcfg`` has the layout ``specs``,
    leaf for leaf."""
    import jax

    from repro.models import model as model_lib
    from repro.models.params import is_spec

    theirs = model_lib.abstract_params(mcfg)
    a = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, WSpec))[0]
    b = jax.tree_util.tree_flatten_with_path(theirs, is_leaf=is_spec)[0]
    got = [(jax.tree_util.keystr(p), tuple(s.shape), str(s.dtype))
           for p, s in a]
    want = [(jax.tree_util.keystr(p), tuple(s.shape), str(s.dtype))
            for p, s in b]
    if got != want:
        raise core.BenchError(
            f"the program's parameter layout changed: {want} != {got}")


def make(specs: dict, seed: int):
    """The weights of layout ``specs`` for ``seed``, on the default
    device."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, WSpec))

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, s in zip(keys, leaves):
            if s.std is None:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                x = jax.random.normal(k, s.shape, jnp.float32) * s.std
                out.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    w0, w1 = core.seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(w0), w1)
    params = jax.jit(build)(key)
    jax.block_until_ready(params)
    return params
