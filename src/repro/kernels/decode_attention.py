"""Pallas TPU flash-decode: one query token against a long KV cache.

Grid: (B*H, n_kv_blocks) — KV blocks sequential, online-softmax state in
VMEM scratch.  The query row is padded to 8 sublanes for TPU tiling; KV
blocks default to 512 tokens (VMEM: 2 * 512 * D * 4B = 512 KB at D=128).
``kv_len`` masks the valid cache prefix, so one compiled kernel serves any
current sequence length (the engine's paged cache re-packs pages into this
dense layout per batch lane).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUB = 8  # TPU sublane padding for the single query row


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, scale, bk, n_kv):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (SUB, D) — row 0 is real
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    kv_len = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (SUB, bk)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (SUB, bk), 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention(q, k, v, kv_len, *, scale=None, bk=512, interpret=False):
    """q: (B, H, D); k,v: (B, H, L, D); kv_len: (B,) -> (B, H, D)."""
    B, H, L, D = k.shape
    scale = float(scale if scale is not None else 1.0 / (D ** 0.5))
    bk = min(bk, L)
    assert L % bk == 0, (L, bk)
    n_kv = L // bk
    qf = jnp.zeros((B * H, SUB, D), q.dtype).at[:, 0, :].set(
        q.reshape(B * H, D)
    )
    kf = k.reshape(B * H, L, D)
    vf = v.reshape(B * H, L, D)
    lens = jnp.repeat(kv_len.astype(jnp.int32), H).reshape(B * H)

    kernel = functools.partial(_dec_kernel, scale=scale, bk=bk, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_kv),
        in_specs=[
            # whole (B*H,) length vector in SMEM: a (1,) block of a rank-1
            # SMEM array is refused by the TPU compiler
            pl.BlockSpec((B * H,), lambda b, j: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, SUB, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, SUB, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, SUB, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((SUB, 1), jnp.float32),
            pltpu.VMEM((SUB, D), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out[:, 0, :].reshape(B, H, D)
