"""GQA attention with chunked online-softmax, sliding windows and KV cache.

Every model path, on CPU and TPU, runs the pure-XLA contraction below; the
Pallas kernels ``repro.kernels.flash_attention`` (prefill) and
``repro.kernels.decode_attention`` (decode) are not wired into it yet.
Queries are processed in chunks under ``lax.scan`` so the score matrix never
materialises beyond (B, Hkv, G, chunk, Skv), bounding live memory the same
way a flash kernel bounds VMEM.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import active_mesh, constrain
from repro.models.common import apply_rope, rms_norm
from repro.models.params import ParamSpec

NEG_INF = -1e30


def _tp_size() -> int:
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def attn_specs(cfg: ModelConfig) -> dict:
    M, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    specs = {
        "wq": ParamSpec((M, H, D), pd, ("embed_p", "heads", None)),
        "wk": ParamSpec((M, Hkv, D), pd, ("embed_p", "kv_heads", None)),
        "wv": ParamSpec((M, Hkv, D), pd, ("embed_p", "kv_heads", None)),
        "wo": ParamSpec((H, D, M), pd, ("heads", None, "embed_p")),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((D,), "float32", (None,), init="ones")
        specs["k_norm"] = ParamSpec((D,), "float32", (None,), init="ones")
    return specs


def _attend_chunk(q, k, v, q_pos, k_pos, kv_len, causal, window,
                  kv_sharded=False):  # noqa: D401
    """q: (B,Cq,Hkv,G,D) k/v: (B,Skv,Hkv,D) -> (B,Cq,Hkv,G,D)."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = k_pos[:, None, :] < kv_len[:, :, None]  # (B,1,Skv) valid entries
    if causal:
        mask = mask & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    mask = mask[:, None, None, :, :]  # (B,1,1,Cq,Skv)
    scores = jnp.where(mask, scores, NEG_INF)
    if kv_sharded:
        # long-KV decode: keep scores sharded over the KV shards so the
        # softmax runs distributed (flash-decode) instead of gathering the
        # cache.  Never applied on the train path (it would force score
        # replication over "model" — EXPERIMENTS.md §Perf H2/H4 post-mortem).
        scores = constrain(scores, "batch", None, None, None, "kv_seq")
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - jax.lax.stop_gradient(jnp.maximum(m, NEG_INF / 2)))
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.maximum(denom, 1e-30)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def multihead_attention(
    params: dict,
    x,
    cfg: ModelConfig,
    positions,
    window: Optional[int] = None,
    cache: Optional[dict] = None,
    cache_len=None,
    q_chunk: int = 1024,
):
    """Returns (y, new_cache).  ``cache`` is {"k","v"} of (B, L, Hkv, D)."""
    B, S, M = x.shape
    H, Hkv, D, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv

    q = jnp.einsum("bsm,mhd->bshd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsm,mhd->bshd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsm,mhd->bshd", x, params["wv"].astype(x.dtype))
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)

    # token positions for rope: (B,S) or (B,S,3) for M-RoPE
    if cfg.rope_kind == "mrope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        pos_1d = positions[..., 0]
    elif cfg.rope_kind == "default":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        pos_1d = positions
    else:
        pos_1d = positions if positions.ndim == 2 else positions[..., 0]

    new_cache = None
    if cache is not None:
        # decode / incremental: write new k,v at cache_len, attend over cache
        ck, cv = cache["k"], cache["v"]
        Lmax = ck.shape[1]
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_len, 0, 0))
        ck = constrain(ck, "batch", "kv_seq", "kv_heads", None)
        cv = constrain(cv, "batch", "kv_seq", "kv_heads", None)
        new_cache = {"k": ck, "v": cv}
        k_att, v_att = ck.astype(x.dtype), cv.astype(x.dtype)
        k_pos = jnp.broadcast_to(jnp.arange(Lmax, dtype=jnp.int32), (B, Lmax))
        kv_len = jnp.full((B, 1), cache_len + S, jnp.int32)
    else:
        k_att, v_att = k, v
        k_pos = pos_1d.astype(jnp.int32)
        kv_len = jnp.max(k_pos, axis=-1, keepdims=True) + 1  # all keys valid

    q_pos = pos_1d.astype(jnp.int32)

    if S > 1 and G > 1:
        # prefill/train: repeat KV to full head count so the contraction
        # stays sharded on a mesh-divisible "heads" axis (XLA fuses the
        # broadcast; no materialised 4x KV).  Decode keeps the grouped form:
        # the cache is KV-sequence-sharded and heads are replicated.
        k_att = jnp.repeat(k_att, G, axis=2)
        v_att = jnp.repeat(v_att, G, axis=2)
        qg = q.reshape(B, S, H, 1, D)
        Hg, Gg = H, 1
        tp = _tp_size()
        if Hg % tp:
            # pad heads to a mesh-divisible count (qwen2-vl: 28 -> 32) so
            # the score tensor shards over "model" instead of replicating
            hp = -(-Hg // tp) * tp
            qg = jnp.pad(qg, [(0, 0), (0, 0), (0, hp - Hg), (0, 0), (0, 0)])
            k_att = jnp.pad(k_att, [(0, 0), (0, 0), (0, hp - Hg), (0, 0)])
            v_att = jnp.pad(v_att, [(0, 0), (0, 0), (0, hp - Hg), (0, 0)])
            Hg = hp
        qg = constrain(qg, "batch", "seq", "heads", None, None)
        k_att = constrain(k_att, "batch", "seq", "heads", None)
        v_att = constrain(v_att, "batch", "seq", "heads", None)
    else:
        qg = q.reshape(B, S, Hkv, G, D)
        Hg, Gg = Hkv, G

    decode_mode = cache is not None and S == 1
    if S <= q_chunk:
        out = _attend_chunk(qg, k_att, v_att, q_pos, k_pos, kv_len,
                            cfg.causal, window, kv_sharded=decode_mode)
    else:
        assert S % q_chunk == 0, (S, q_chunk)
        n = S // q_chunk
        qs = qg.reshape(B, n, q_chunk, Hg, Gg, D).transpose(1, 0, 2, 3, 4, 5)
        ps = q_pos.reshape(B, n, q_chunk).transpose(1, 0, 2)

        # checkpoint: recompute per-chunk attention probabilities in the
        # backward pass (flash-attention-style) instead of saving them
        @jax.checkpoint
        def body(_, qp):
            qc, pc = qp
            oc = _attend_chunk(qc, k_att, v_att, pc, k_pos, kv_len, cfg.causal, window)
            return (), oc

        _, outs = jax.lax.scan(body, (), (qs, ps))
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, Hg, Gg, D)

    if Hg * Gg != H:  # slice off padded heads
        out = out.reshape(B, S, Hg * Gg, D)[:, :, :H, :]
    out = out.reshape(B, S, H, D)
    y = jnp.einsum("bshd,hdm->bsm", out, params["wo"].astype(x.dtype))
    # reduce-scatter the TP-partial output into the sequence-sharded residual
    # (Megatron-SP output half; halves wire vs an all-reduce to full seq)
    return constrain(y, "batch", "seq_sp", None), new_cache
