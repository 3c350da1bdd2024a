"""Operation and byte counts against hand counts at small shapes."""
import pytest

from benchmarks.chip import flops

M = {"num_layers": 2, "d_model": 8, "num_heads": 4, "num_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10, "gated_mlp": True}


def test_paged_decode_hand_count():
    # two slots attending 3 and 5 keys; 4 heads of dim 2, 2 kv heads
    f, b = flops.paged_decode([3, 5], heads=4, kv_heads=2, dim=2,
                              pool_itemsize=4, act_itemsize=2)
    # QK and PV: 2 ops per (head, key, dim) each -> 4*H*D per key
    assert f == 4 * 4 * 2 * (3 + 5)
    # K and V rows read: 2 * Hkv * D * 4 B per key; q, out (H*D each)
    # and the new k, v rows (Hkv*D each) per slot at 2 B
    assert b == 2 * 2 * 2 * 4 * 8 + 2 * 2 * (2 * 4 + 2 * 2) * 2


def test_paged_window_hand_count():
    # one slot: 4 cached rows, 3 new rows -> keys 5 + 6 + 7 = 18
    f, b = flops.paged_window([(4, 3)], heads=4, kv_heads=2, dim=2,
                              pool_itemsize=4, act_itemsize=2)
    assert f == 4 * 4 * 2 * 18
    assert b == 2 * 2 * 2 * 4 * 4 + 3 * 2 * (2 * 4 + 2 * 2) * 2
    # the window is additive over slots
    f2, b2 = flops.paged_window([(4, 3), (0, 1)], 4, 2, 2, 4, 2)
    g, c = flops.paged_window([(0, 1)], 4, 2, 2, 4, 2)
    assert (f2, b2) == (f + g, b + c)


def test_model_counts():
    p = flops.matmul_params(M)
    # q, o: 8*8 each; k, v: 8*4 each; gated mlp: 3*8*16
    assert p["layer"] == 64 + 64 + 32 + 32 + 384
    assert p["head"] == 80
    # decode: 2 per weight, plus 4*H*D per key per layer
    assert flops.decode_token_flops(M, 7) == 2 * (2 * 576 + 80) + 2 * 4 * 4 * 2 * 7
    # training: 3x the forward at the mean causal context (seq+1)/2
    fwd = 2 * (2 * 576 + 80) + 2 * 4 * 4 * 2 * (9 + 1) / 2
    assert flops.train_token_flops(M, 9) == 3 * fwd
    # prefill of 3 rows after 4 cached: keys 5 + 6 + 7, the head once
    assert flops.prefill_flops(M, 4, 3) == (2 * 3 * 2 * 576 + 2 * 80
                                            + 2 * 4 * 4 * 2 * 18)
    plain = dict(M, gated_mlp=False)
    assert flops.matmul_params(plain)["layer"] == 64 + 64 + 32 + 32 + 256


def test_roofline_bound():
    pk = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000, 50, pk) == (10.0, "compute")
    assert flops.roofline_s(100, 50, pk) == (5.0, "memory")


def test_peaks_known_and_unknown():
    pk = flops.peak("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
