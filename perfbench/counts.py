"""Operations and bytes that the served work needs, from shapes alone.

``decode_step`` counts one decode step of the whole batch at a cache
position: the matrix products of every layer and of the output head, the
attention over the valid cache entries, and the bytes that must move at
least once: every weight (the norms in float32), the embedding table's
rows looked up (unless the output head is the table itself, read whole
anyway), the valid keys and values, and the new ones written.
``lags_select`` counts one call of the Load-Credit tick kernel.  The
roofline time of a piece of work is the larger of its operations over the
chip's peak rate and its bytes over the chip's memory bandwidth.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_matmul_params(s: dict) -> int:
    M, H, Hkv, D, F = s["M"], s["H"], s["Hkv"], s["D"], s["F"]
    return M * H * D + 2 * M * Hkv * D + H * D * M + 3 * M * F


def decode_step(s: dict, batch: int, pos: int, wbytes: int = 2,
                kvbytes: int = 2) -> tuple:
    """(flops, bytes) of one decode step of ``batch`` rows whose new token
    sits at cache position ``pos`` (so ``pos + 1`` keys are attended)."""
    L, M, H, Hkv, D, V = s["L"], s["M"], s["H"], s["Hkv"], s["D"], s["V"]
    ctx = pos + 1
    matmul = L * layer_matmul_params(s) + M * V
    flops = 2 * batch * matmul + L * 4 * batch * H * D * ctx
    norms = (2 * L + 1) * M * 4  # the layers' and the final norm
    if s.get("qk_norm"):
        norms += 2 * L * D * 4
    rows = 0 if s.get("tied") else batch * M * wbytes
    weights = matmul * wbytes + norms + rows
    kv_read = L * 2 * batch * pos * Hkv * D * kvbytes
    kv_write = L * 2 * batch * Hkv * D * kvbytes
    return flops, weights + kv_read + kv_write


def token_flops(s: dict, pos: int) -> int:
    """Model operations of one delivered token at cache position ``pos``."""
    return decode_step(s, 1, pos)[0]


def lags_select(T: int, k: int) -> tuple:
    """(flops, bytes) of one tick over ``T`` tenants picking ``k``: two
    updates of two multiply-adds per tenant, then ``k`` passes of compare
    and select over the padded vector; four (1, Tp) float32 inputs read,
    two written, ``k`` int32 picks."""
    Tp = -(-T // 128) * 128
    flops = 4 * Tp + k * 3 * Tp
    return flops, 6 * Tp * 4 + k * 4


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
