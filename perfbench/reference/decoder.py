"""Plain float32 reference of the served Qwen3 decoder, its lower-precision
control, and every other part of the model that the serving harness needs
(``engine_cell.Model`` names the interface).

The forward pass of a Qwen3 decoder as the configuration file states it
(pre-norm residual layers with RMS norms; grouped-query attention, each
key/value head shared by ``H / Hkv`` query heads; an RMS norm over each
head's queries and keys, then rotary embeddings in the rotate-half
convention over the whole head; causal softmax attention scaled by
``1/sqrt(head_dim)``; a SiLU-gated MLP; the output head tied to the
embedding where the file says so), in straightforward ``jax.numpy`` with
every matrix product at ``Precision.HIGHEST``.  It imports nothing of the
program.  It reads the weights the benchmark made from the seed, by the
names of the layout ``weight_specs`` gives.

``block_gaps`` runs it over rows of tokens teacher-forced at positions
0..L-1 and returns, at every position, how far the served token's logit
lies below the reference's best.  With ``control=True`` it also runs the
same forward with every matrix product's operands rounded to float8
e4m3 (weights per output channel, activations per row), the precision
next below the configuration's bfloat16, and returns the gap of the token
that the control puts first.

The layer's attention and its MLP are separate functions (``hidden``'s
``mlp``), so that a reference of a related block can reuse the rest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench import core, counts
from perfbench.weights import WSpec

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8 e4m3fn


# the configuration keys this reference runs only at one value: the
# program's dense decoder has no other
FIXED = (("model_type", "qwen3"), ("attention_bias", False),
         ("hidden_act", "silu"), ("use_sliding_window", False),
         ("rope_scaling", None))


def check_fixed(model: dict, fixed=FIXED) -> None:
    """Refuse a configuration whose ``fixed`` keys hold another value."""
    for key, want in fixed:
        if model[key] != want:
            raise core.BenchError(
                f"the engine's decoder runs {key}={want!r}, the configuration "
                f"states {model[key]!r}")


def program_config(model: dict) -> dict:
    """The program's ``ModelConfig`` keywords (all but ``name``) for the
    configuration file's model keys."""
    check_fixed(model)
    return dict(family="dense", n_layers=model["num_hidden_layers"],
                d_model=model["hidden_size"],
                n_heads=model["num_attention_heads"],
                n_kv_heads=model["num_key_value_heads"],
                head_dim=model["head_dim"],
                d_ff=model["intermediate_size"],
                vocab_size=model["vocab_size"], qk_norm=True,
                tie_embeddings=bool(model["tie_word_embeddings"]),
                rope_theta=float(model["rope_theta"]),
                norm_eps=float(model["rms_norm_eps"]),
                dtype=model["torch_dtype"], param_dtype=model["torch_dtype"])


def sizes(model: dict) -> tuple:
    """The static sizes of the forward, from the configuration file's
    model keys (a hashable tuple of pairs)."""
    check_fixed(model)
    return tuple(sorted(dict(
        L=int(model["num_hidden_layers"]), M=int(model["hidden_size"]),
        H=int(model["num_attention_heads"]),
        Hkv=int(model["num_key_value_heads"]), D=int(model["head_dim"]),
        F=int(model["intermediate_size"]), V=int(model["vocab_size"]),
        theta=float(model["rope_theta"]), eps=float(model["rms_norm_eps"]),
        tied=bool(model["tie_word_embeddings"]),
        dtype=str(model["torch_dtype"]),
    ).items()))


def _inv(n) -> float:
    return float(n) ** -0.5


def model_specs(s: dict, layer_mlp: dict) -> dict:
    """The whole tree: one scanned period of ``L`` stacked layers (the
    attention with its per-head query and key norms, and the MLP weights
    ``layer_mlp``, a key and its tree), the embedding, the final norm, and
    the output head unless it is tied to the embedding."""
    L, M, H, Hkv, D, V = s["L"], s["M"], s["H"], s["Hkv"], s["D"], s["V"]
    pd = s["dtype"]
    attn = {
        "wq": WSpec((L, M, H, D), pd, _inv(M)),
        "wk": WSpec((L, M, Hkv, D), pd, _inv(M)),
        "wv": WSpec((L, M, Hkv, D), pd, _inv(M)),
        "wo": WSpec((L, H, D, M), pd, _inv(H * D)),
        "q_norm": WSpec((L, D), "float32", None),
        "k_norm": WSpec((L, D), "float32", None),
    }
    layer = {"ln1": WSpec((L, M), "float32", None), "attn": attn,
             "ln2": WSpec((L, M), "float32", None), **layer_mlp}
    out = {
        "embed": WSpec((V, M), pd, 0.02),
        "stack": {"body": [layer], "rem": []},
        "final_norm": WSpec((M,), "float32", None),
    }
    if not s["tied"]:
        out["unembed"] = WSpec((M, V), pd, _inv(M))
    return out


def weight_specs(sz) -> dict:
    """The layout of the weights made from the seed: the serving engine's
    parameter tree.  Normal with standard deviation 1/sqrt(fan-in) for
    every projection, 0.02 for the embedding, ones for the norms."""
    s = dict(sz)
    L, M, F = s["L"], s["M"], s["F"]
    pd = s["dtype"]
    return model_specs(s, {"mlp": {
        "w_gate": WSpec((L, M, F), pd, _inv(M)),
        "w_up": WSpec((L, M, F), pd, _inv(M)),
        "w_down": WSpec((L, F, M), pd, _inv(F)),
    }})


def decode_counts(sz, batch: int, pos: int) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` rows at cache
    position ``pos`` (``counts.decode_step``)."""
    s = dict(sz, qk_norm=True)
    nbytes = counts.DTYPE_BYTES[s["dtype"]]
    return counts.decode_step(s, batch, pos, nbytes, nbytes)


def token_flops(sz, pos: int) -> int:
    """Model operations of one delivered token at cache position ``pos``."""
    return counts.token_flops(dict(sz), pos)


def _fq(x, axes):
    """Round to float8 e4m3 with one scale per slice across ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(spec, a, b, a_axes, b_axes, low):
    """``einsum(spec, a, b)`` at HIGHEST; with ``low``, its operands rounded
    to float8 first, one scale per slice across ``a_axes`` / ``b_axes``."""
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


def dense_mlp(w, h, s, low):
    """The SiLU-gated MLP of one layer, on its normed input ``h``."""
    m = w["mlp"]
    g = mm("blm,mf->blf", h, m["w_gate"], (2,), (0,), low)
    u = mm("blm,mf->blf", h, m["w_up"], (2,), (0,), low)
    return mm("blf,fm->blm", jax.nn.silu(g) * u, m["w_down"], (2,), (0,),
               low)


def hidden(params, tokens, s, low, mlp=dense_mlp):
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
        q = mm("blm,mhd->blhd", h, a["wq"], (2,), (0,), low)
        k = mm("blm,mhd->blhd", h, a["wk"], (2,), (0,), low)
        v = mm("blm,mhd->blhd", h, a["wv"], (2,), (0,), low)
        q = _rope(_norm(q, a["q_norm"], s["eps"]), pos, s["theta"])
        k = _rope(_norm(k, a["k_norm"], s["eps"]), pos, s["theta"])
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k, (3,), (3,), low)
        scores = scores / jnp.sqrt(jnp.float32(s["D"]))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v, (3,), (1,), low)
        x = x + mm("blhd,hdm->blm", o, a["wo"], (2, 3), (0, 1), low)
        x = x + mlp(w, _norm(x, w["ln2"], s["eps"]), s, low)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stack"]["body"][0])
    return _norm(x, params["final_norm"].astype(jnp.float32), s["eps"])


def gaps(params, tokens, served, *, sz, control=False, chunk=128,
         mlp=dense_mlp):
    """Per position of ``tokens`` (B, L): the reference's best logit minus
    its logit of ``served`` (B, L); with ``control``, also the same gap of
    the control's first token.  L must be a multiple of ``chunk``."""
    s = dict(sz)
    B, L = tokens.shape
    hr = hidden(params, tokens, s, False, mlp)
    hc = hidden(params, tokens, s, True, mlp) if control else hr
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
        lc = mm("bcm,mv->bcv", xc, wc, (2,), (0,), True)
        top = jnp.argmax(lc, -1)
        return gap, best - jnp.take_along_axis(lr, top[..., None], -1)[..., 0]

    gap, gap_c = jax.lax.map(one, (split(hr), split(hc), split(served)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(B, L)  # noqa: E731
    return join(gap), join(gap_c)


block_gaps = jax.jit(gaps, static_argnames=("sz", "control", "chunk"))
