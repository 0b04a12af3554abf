import pytest

import kernel_costs


def test_paged_decode_bytes_and_flops_by_hand():
    # 2 lanes holding 100 + 28 = 128 live tokens, 32 query heads over 8 KV
    # heads of 128, bf16 cache.
    #   K and V rows: 128 tokens x 8 heads x 128 x 2 bytes x 2 = 524288
    #   queries in (bf16) + outputs out (f32): 2 x 32 x 128 x (2 + 4) = 49152
    #   FLOPs: q.k and p.v, 2 a multiply-add: 2 x 2 x 128 x 32 x 128 = 2097152
    cost = kernel_costs.paged_decode(128, 2, 32, 8, 128)
    assert cost == {"bytes": 524288 + 49152, "flops": 2097152}


def test_roofline_names_the_bound():
    peak = kernel_costs.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    assert peak["bf16_flops_per_s"] == 197e12
    mem = kernel_costs.roofline({"bytes": 819e6, "flops": 1e6}, peak)
    assert mem["bound"] == "memory" and mem["seconds"] == pytest.approx(1e-3)
    cpu = kernel_costs.roofline({"bytes": 1.0, "flops": 197e9}, peak)
    assert cpu["bound"] == "compute" and cpu["seconds"] == pytest.approx(1e-3)
    # paged decode is memory-bound on a v5e: 4 FLOPs a KV element per query
    # head of the group, against 197e12 / 819e9 = 240 FLOPs a byte
    best = kernel_costs.roofline(
        kernel_costs.paged_decode(20000, 32, 32, 8, 128), peak)
    assert best["bound"] == "memory"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        kernel_costs.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        kernel_costs.peaks("cpu")
