"""The ops/bytes functions against hand counts."""
import pytest

import costs
from peaks import peaks

G = {"d_model": 8, "n_heads": 2, "n_kv_heads": 1, "head_dim": 4, "d_ff": 16,
     "num_layers": 3, "vocab_size": 10, "activation": "gelu"}


def test_matmul_hand_count():
    # (4 x 8) @ (8 x 16): 4*8*16 MACs; x 32 B, w 128 B, out 64 x 2 B,
    # multipliers 16 x 4 B
    assert costs.matmul(4, 8, 16, 2) == (2 * 4 * 8 * 16,
                                         32 + 128 + 128 + 64)


def test_encoder_call_hand_count():
    w = costs.encoder_call(G, batch=2, seq=5)
    rows = 10
    # q (8x8), k (8x4), v (8x4), o (8x8), up (8x16), down (16x8)
    macs = rows * (64 + 32 + 32 + 64 + 128 + 128)
    assert w["matmul"][0] == 3 * 2 * macs
    # per row of each sequence 5 keys, QK and PV, 2 heads of 4
    assert w["attention"][0] == 3 * 2 * 2 * (2 * 5 * 5) * 2 * 4
    # q + out: 2 x 5 x 8 B; K and V of 5 rows: 2 x 5 x 4 B; per sequence
    assert w["attention"][1] == 3 * 2 * (80 + 40)
    assert w["model_ops"] == w["matmul"][0] + w["attention"][0] \
        + 2 * 2 * 8 * 10


def test_decode_fold_moves_wo_without_losing_work():
    plain = costs.decode_step(G, [3, 0])
    folded = costs.decode_step(G, [3, 0], fold_wo=True)
    assert plain["model_ops"] == folded["model_ops"]
    wo = 3 * 2 * 2 * 8 * 8
    assert plain["matmul"][0] - folded["matmul"][0] == wo
    # lane at 3 sees 4 keys, lane at 0 sees 1
    assert plain["decode_attention"][0] == 3 * 2 * 2 * (4 + 1) * 2 * 4


def test_prefill_counts_only_real_rows_and_live_keys():
    w = costs.prefill_chunk(G, [(4, 2)])
    # rows 4 and 5 of the lane see 5 and 6 keys
    assert w["prefill_attention"][0] == 3 * 2 * 2 * (5 + 6) * 2 * 4
    assert w["matmul"][0] == 3 * costs.block_matmul_work(G, 2)[0]
    assert costs.prefill_chunk(G, [])["matmul"][0] == 0


def test_least_time_names_its_bound():
    p = peaks("TPU v5 lite")
    assert costs.least_time(393e12, 1, p) == (pytest.approx(1.0), "ops")
    assert costs.least_time(1, 819e9, p) == (pytest.approx(1.0), "bytes")


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        peaks("cpu")
