"""Plain float32 reference of a tiny Qwen3-MoE-style decoder: the Qwen3
block of ``perfbench/reference/decoder.py`` with every layer's MLP a
mixture of experts (a float32 softmax router over ``num_experts``, the
``num_experts_per_tok`` largest probabilities renormalised to sum to 1,
each chosen expert a SiLU-gated MLP of width ``moe_intermediate_size``).

A test places it under ``perfbench/reference/`` of a copy of the
benchmark, as a model configuration would bring its reference, and runs
the serving harness on it unchanged.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from perfbench import counts
from perfbench.reference import decoder
from perfbench.weights import WSpec

FIXED = (("model_type", "qwen3_moe"), ("attention_bias", False),
         ("hidden_act", "silu"), ("use_sliding_window", False),
         ("rope_scaling", None), ("norm_topk_prob", True),
         ("decoder_sparse_step", 1), ("mlp_only_layers", []))


def program_config(model: dict) -> dict:
    decoder.check_fixed(model, FIXED)
    return dict(family="moe", n_layers=model["num_hidden_layers"],
                d_model=model["hidden_size"],
                n_heads=model["num_attention_heads"],
                n_kv_heads=model["num_key_value_heads"],
                head_dim=model["head_dim"],
                d_ff=model["intermediate_size"],
                vocab_size=model["vocab_size"], qk_norm=True,
                n_experts=model["num_experts"],
                top_k=model["num_experts_per_tok"],
                d_ff_expert=model["moe_intermediate_size"], moe_period=1,
                tie_embeddings=bool(model["tie_word_embeddings"]),
                rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]),
                dtype=model["torch_dtype"], param_dtype=model["torch_dtype"])


def sizes(model: dict) -> tuple:
    decoder.check_fixed(model, FIXED)
    dense = dict(decoder.sizes(dict(model, model_type="qwen3")))
    return tuple(sorted(dict(
        dense, E=int(model["num_experts"]),
        K=int(model["num_experts_per_tok"]),
        Fe=int(model["moe_intermediate_size"])).items()))


def weight_specs(sz) -> dict:
    s = dict(sz)
    L, M, E, Fe, pd = s["L"], s["M"], s["E"], s["Fe"], s["dtype"]
    inv = lambda n: float(n) ** -0.5  # noqa: E731
    return decoder.model_specs(s, {"moe": {
        "w_router": WSpec((L, M, E), "float32", inv(M)),
        "w_gate": WSpec((L, E, M, Fe), pd, inv(M)),
        "w_up": WSpec((L, E, M, Fe), pd, inv(M)),
        "w_down": WSpec((L, E, Fe, M), pd, inv(Fe)),
    }})


def moe_mlp(w, h, s, low):
    m = w["moe"]
    probs = jax.nn.softmax(decoder.mm("blm,me->ble", h, m["w_router"], (2,),
                                      (0,), low), -1)
    top, ids = jax.lax.top_k(probs, s["K"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(ids, s["E"]) * top[..., None], -2)
    g = decoder.mm("blm,emf->blef", h, m["w_gate"], (2,), (1,), low)
    u = decoder.mm("blm,emf->blef", h, m["w_up"], (2,), (1,), low)
    y = decoder.mm("blef,efm->blem", jax.nn.silu(g) * u, m["w_down"], (3,),
                   (1,), low)
    return jnp.einsum("blem,ble->blm", y, gate, precision=decoder.HI)


block_gaps = jax.jit(partial(decoder.gaps, mlp=moe_mlp),
                     static_argnames=("sz", "control", "chunk"))


def decode_counts(sz, batch: int, pos: int) -> tuple:
    """(flops, bytes): each token's attention, router and its experts;
    every weight read once (at this batch every expert is chosen), the
    valid keys and values read and the new ones written."""
    s = dict(sz)
    L, M, H, Hkv, D, V = s["L"], s["M"], s["H"], s["Hkv"], s["D"], s["V"]
    attn = M * H * D + 2 * M * Hkv * D + H * D * M
    per_token = L * (attn + M * s["E"] + s["K"] * 3 * M * s["Fe"]) + M * V
    flops = 2 * batch * per_token + L * 4 * batch * H * D * (pos + 1)
    leaves = jax.tree_util.tree_leaves(
        weight_specs(sz), is_leaf=lambda x: isinstance(x, WSpec))
    weights = sum(math.prod(w.shape) * counts.DTYPE_BYTES[w.dtype]
                  for w in leaves)
    kv = L * 2 * batch * (pos + 1) * Hkv * D * counts.DTYPE_BYTES[s["dtype"]]
    return flops, weights + kv


def token_flops(sz, pos: int) -> int:
    return decode_counts(sz, 1, pos)[0]
