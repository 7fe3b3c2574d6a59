"""The yardstick's arithmetic: operation and byte counts at Qwen3-1.7B's
shapes against hand counts, and the peaks table."""
import pytest

from perfbench import core, counts

# Qwen3-1.7B as served: 28 layers, width 2048, 16 query heads of 128 over
# 8 key/value heads, MLP 6144, vocabulary 151936, head tied to the embedding
S = dict(L=28, M=2048, H=16, Hkv=8, D=128, F=6144, V=151936, tied=True,
         qk_norm=True)


def test_layer_params_by_hand():
    # q and o: 2 x 2048 x 2048; k and v: 2 x 2048 x 1024; gate, up, down:
    # 3 x 2048 x 6144
    assert counts.layer_matmul_params(S) == (2 * 2048 * 2048
                                             + 2 * 2048 * 1024
                                             + 3 * 2048 * 6144)


@pytest.mark.parametrize("pos", [0, 511, 1022])
def test_decode_step_by_hand(pos):
    per_layer = 12_582_912 + 37_748_736  # 50,331,648
    head = 2048 * 151936  # 311,164,928
    matmul = 28 * per_layer + head  # 1,720,451,072
    flops, nbytes = counts.decode_step(S, 16, pos)
    attn = 28 * 4 * 16 * 16 * 128 * (pos + 1)
    assert flops == 2 * 16 * matmul + attn
    # tied head: the table is read whole once, no separate row lookups;
    # norms: 57 of width 2048 and 56 of 128, in float32
    weights = 2 * matmul + 4 * 2048 * 57 + 4 * 128 * 56
    kv = 28 * 2 * 16 * 8 * 128 * 2 * (pos + 1)  # read pos, write 1
    assert nbytes == weights + kv


def test_decode_weights_dominate_at_1k():
    flops, nbytes = counts.decode_step(S, 16, 1022)
    # 3.44 GB of weights and 1.88 GB of valid cache at the last position
    assert 5.2e9 < nbytes < 5.4e9
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t = counts.roofline_s(flops, nbytes, peak)
    assert t == pytest.approx(nbytes / 819e9)  # bandwidth-bound


def test_token_flops_is_one_row():
    assert counts.token_flops(S, 100) * 16 == counts.decode_step(S, 16,
                                                                 100)[0]


@pytest.mark.parametrize("T,Tp", [(1024, 1024), (1000, 1024), (65536, 65536)])
def test_lags_select_by_hand(T, Tp):
    flops, nbytes = counts.lags_select(T, 16)
    assert flops == 4 * Tp + 16 * 3 * Tp
    assert nbytes == 6 * Tp * 4 + 16 * 4


def test_peaks_table_has_v5e():
    p = core.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_peaks_table_refuses_an_unknown_device():
    with pytest.raises(core.BenchError, match="no peaks"):
        core.peaks("TPU v9 imaginary")
