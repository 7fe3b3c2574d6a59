"""Plain float32 reference of the served decoder, and its lower-precision
control.

The forward pass of a Qwen3 decoder as the configuration file states it
(pre-norm residual layers with RMS norms; grouped-query attention, each
key/value head shared by ``H / Hkv`` query heads; an RMS norm over each
head's queries and keys, then rotary embeddings in the rotate-half
convention over the whole head; causal softmax attention scaled by
``1/sqrt(head_dim)``; a SiLU-gated MLP; the output head tied to the
embedding where the file says so), in straightforward ``jax.numpy`` with
every matrix product at ``Precision.HIGHEST``.  It imports nothing of the
program.  It reads the weights the benchmark made from the seed, by the
names of the benchmark's own layout (``perfbench/weights.py``).

``block_gaps`` runs it over rows of tokens teacher-forced at positions
0..L-1 and returns, at every position, how far the served token's logit
lies below the reference's best.  With ``control=True`` it also runs the
same forward with every matrix product's operands rounded to float8
e4m3 (weights per output channel, activations per row), the precision
next below the configuration's bfloat16, and returns the gap of the token
that the control puts first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8 e4m3fn


def sizes_from_config(model: dict) -> tuple:
    """The static sizes of the forward, from the configuration file's
    model keys (a hashable tuple of pairs)."""
    if model["model_type"] != "qwen3" or model["attention_bias"]:
        raise ValueError("the reference is the Qwen3 block, without "
                         "attention bias")
    return tuple(sorted(dict(
        L=int(model["num_hidden_layers"]), M=int(model["hidden_size"]),
        H=int(model["num_attention_heads"]),
        Hkv=int(model["num_key_value_heads"]), D=int(model["head_dim"]),
        F=int(model["intermediate_size"]), V=int(model["vocab_size"]),
        theta=float(model["rope_theta"]), eps=float(model["rms_norm_eps"]),
        tied=bool(model["tie_word_embeddings"]),
    ).items()))


def _fq(x, axes):
    """Round to float8 e4m3 with one scale per slice across ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, a_axes, b_axes, low):
    if low:
        a, b = _fq(a, a_axes), _fq(b, b_axes)
    return jnp.einsum(spec, a, b, precision=HI)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs  # (L, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _hidden(params, tokens, s, low):
    """Final normed hidden states (B, L, M) for tokens (B, L)."""
    B, L = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)
    if low:
        x = _fq(x, (2,))  # the embedding table's rows, each on its own scale
    pos = jnp.arange(L)
    causal = pos[None, :] <= pos[:, None]  # (q, k)
    G = s["H"] // s["Hkv"]

    def layer(x, w):
        w = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
        a = w["attn"]
        h = _norm(x, w["ln1"], s["eps"])
        q = _mm("blm,mhd->blhd", h, a["wq"], (2,), (0,), low)
        k = _mm("blm,mhd->blhd", h, a["wk"], (2,), (0,), low)
        v = _mm("blm,mhd->blhd", h, a["wv"], (2,), (0,), low)
        q = _rope(_norm(q, a["q_norm"], s["eps"]), pos, s["theta"])
        k = _rope(_norm(k, a["k_norm"], s["eps"]), pos, s["theta"])
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        scores = _mm("bqhd,bkhd->bhqk", q, k, (3,), (3,), low)
        scores = scores / jnp.sqrt(jnp.float32(s["D"]))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = _mm("bhqk,bkhd->bqhd", p, v, (3,), (1,), low)
        x = x + _mm("blhd,hdm->blm", o, a["wo"], (2, 3), (0, 1), low)
        m = w["mlp"]
        h = _norm(x, w["ln2"], s["eps"])
        g = _mm("blm,mf->blf", h, m["w_gate"], (2,), (0,), low)
        u = _mm("blm,mf->blf", h, m["w_up"], (2,), (0,), low)
        x = x + _mm("blf,fm->blm", jax.nn.silu(g) * u, m["w_down"], (2,),
                    (0,), low)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stack"]["body"][0])
    return _norm(x, params["final_norm"].astype(jnp.float32), s["eps"])


@partial(jax.jit, static_argnames=("sz", "control", "chunk"))
def block_gaps(params, tokens, served, *, sz, control=False, chunk=128):
    """Per position of ``tokens`` (B, L): the reference's best logit minus
    its logit of ``served`` (B, L); with ``control``, also the same gap of
    the control's first token.  L must be a multiple of ``chunk``."""
    s = dict(sz)
    B, L = tokens.shape
    hr = _hidden(params, tokens, s, False)
    hc = _hidden(params, tokens, s, True) if control else hr
    w = (params["embed"].T if s["tied"] else params["unembed"]).astype(
        jnp.float32)
    wc = _fq(w, (0,)) if control else w
    n = L // chunk
    split = lambda a: jnp.moveaxis(  # noqa: E731
        a.reshape(B, n, chunk, *a.shape[2:]), 1, 0)

    def one(args):
        xr, xc, sv = args
        lr = jnp.einsum("bcm,mv->bcv", xr, w, precision=HI)
        best = jnp.max(lr, -1)
        gap = best - jnp.take_along_axis(lr, sv[..., None], -1)[..., 0]
        if not control:
            return gap, jnp.zeros_like(gap)
        lc = _mm("bcm,mv->bcv", xc, wc, (2,), (0,), True)
        top = jnp.argmax(lc, -1)
        return gap, best - jnp.take_along_axis(lr, top[..., None], -1)[..., 0]

    gap, gap_c = jax.lax.map(one, (split(hr), split(hc), split(served)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(B, L)  # noqa: E731
    return join(gap), join(gap_c)
