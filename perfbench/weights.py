"""Random weights for a decoder configuration, made on the device from the
seed in one jitted call, in the type they are served in.

The layout is the serving engine's parameter tree (one scanned period of
stacked layers); ``check_layout`` holds it against the program's own
abstract tree, so a change of layout fails loudly instead of serving
something else.  Scales: normal with standard deviation 1/sqrt(fan-in)
for every projection, 0.02 for the embedding, ones for the norms.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

from perfbench import core


class WSpec(NamedTuple):
    shape: Tuple[int, ...]
    dtype: str
    std: Optional[float]  # None: ones


def sizes(mcfg) -> dict:
    return dict(L=mcfg.n_layers, M=mcfg.d_model, H=mcfg.n_heads,
                Hkv=mcfg.n_kv_heads, D=mcfg.head_dim, F=mcfg.d_ff,
                V=mcfg.vocab_size, tied=bool(mcfg.tie_embeddings),
                qk_norm=bool(mcfg.qk_norm))


def weight_specs(mcfg) -> dict:
    """The layout for a Qwen3-style block: per-head query and key norms,
    and the output head tied to the embedding where the config says so."""
    s = sizes(mcfg)
    L, M, H, Hkv, D, F, V = (s[k] for k in "L M H Hkv D F V".split())
    pd = mcfg.param_dtype
    inv = lambda n: float(n) ** -0.5  # noqa: E731
    layer = {
        "ln1": WSpec((L, M), "float32", None),
        "attn": {
            "wq": WSpec((L, M, H, D), pd, inv(M)),
            "wk": WSpec((L, M, Hkv, D), pd, inv(M)),
            "wv": WSpec((L, M, Hkv, D), pd, inv(M)),
            "wo": WSpec((L, H, D, M), pd, inv(H * D)),
            "q_norm": WSpec((L, D), "float32", None),
            "k_norm": WSpec((L, D), "float32", None),
        },
        "ln2": WSpec((L, M), "float32", None),
        "mlp": {
            "w_gate": WSpec((L, M, F), pd, inv(M)),
            "w_up": WSpec((L, M, F), pd, inv(M)),
            "w_down": WSpec((L, F, M), pd, inv(F)),
        },
    }
    out = {
        "embed": WSpec((V, M), pd, 0.02),
        "stack": {"body": [layer], "rem": []},
        "final_norm": WSpec((M,), "float32", None),
    }
    if not s["tied"]:
        out["unembed"] = WSpec((M, V), pd, inv(M))
    return out


def check_layout(mcfg) -> None:
    """The program's parameter tree has this layout, leaf for leaf."""
    import jax

    from repro.models import model as model_lib
    from repro.models.params import is_spec

    ours = weight_specs(mcfg)
    theirs = model_lib.abstract_params(mcfg)
    a = jax.tree_util.tree_flatten_with_path(
        ours, is_leaf=lambda x: isinstance(x, WSpec))[0]
    b = jax.tree_util.tree_flatten_with_path(theirs, is_leaf=is_spec)[0]
    got = [(jax.tree_util.keystr(p), tuple(s.shape), str(s.dtype))
           for p, s in a]
    want = [(jax.tree_util.keystr(p), tuple(s.shape), str(s.dtype))
            for p, s in b]
    if got != want:
        raise core.BenchError(
            f"the program's parameter layout changed: {want} != {got}")


def make(mcfg, seed: int):
    """The weights for ``seed``, on the default device."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(
        weight_specs(mcfg), is_leaf=lambda x: isinstance(x, WSpec))

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
