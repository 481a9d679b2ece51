"""The yardstick's operation and byte counts against figures worked by hand."""

import json

import pytest

from benchmark import flops
from benchmark.harness import cell as cells

#: required GFLOP per trained token, by hand (ISSUE 23): 6 x (218.1 M layer +
#: 131.1 M head) + 3 x 4 x 32 x 128 x mean keys; Mixtral: 2 of 8 experts + router
BY_HAND = {"mistral7b-pretrain-4k": 2.196, "mistral7b-pretrain-32k": 2.284,
           "mixtral8x7b-pretrain-4k-ep4": 3.253}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_required_flops_per_token(name):
    c = cells.load_cell(name)
    got = flops.train_flops_per_token(c.model, c.traffic["seq_length"])
    assert got["total"] / 1e9 == pytest.approx(BY_HAND[name], rel=0.01)
    assert got["total"] == got["dense"] + got["attention"]


def test_attention_keys_are_capped_at_the_window():
    assert flops.mean_visible_keys(4096, None) == 2048.5          # (s + 1) / 2
    assert flops.mean_visible_keys(4096, 4096) == 2048.5          # window = causal
    # 4096 ramp-up queries then 28672 queries that see exactly 4096 keys
    by_hand = (4096 * 4097 / 2 + 28672 * 4096) / 32768
    assert flops.mean_visible_keys(32768, 4096) == by_hand
    assert by_hand < 4096 < (32768 + 1) / 2
    m = cells.load_cell("mistral7b-pretrain-32k").model
    capped = flops.train_flops_per_token(m, 32768)["attention"]
    free = flops.train_flops_per_token({**m, "sliding_window": None}, 32768)["attention"]
    assert free / capped == pytest.approx(16384.5 / by_hand)


def test_flash_kernel_counts_per_call():
    m = cells.load_cell("mistral7b-pretrain-4k").model
    call = flops.flash_call(m, 4096, 1)
    visible = 32 * 4096 * 2048.5            # heads x queries x mean keys
    assert call["fwd"]["flops"] == 2 * 2 * visible * 128     # QK^T, PV
    assert call["dq"]["flops"] == 2 * 3 * visible * 128      # QK^T, dO V^T, dS K
    assert call["dkv"]["flops"] == 2 * 4 * visible * 128
    q, kv, row = 32 * 4096 * 128 * 2, 8 * 4096 * 128 * 2, 32 * 4096 * 4
    assert call["fwd"]["bytes"] == 2 * q + 2 * kv + row
    assert call["dq"]["bytes"] == 3 * q + 2 * kv + 2 * row
    assert call["dkv"]["bytes"] == 2 * q + 4 * kv + 2 * row
    assert flops.flash_call(m, 4096, 4)["fwd"]["flops"] == 4 * call["fwd"]["flops"]


def test_peaks_table_and_unknown_device():
    v5e = flops.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError, match="not in peaks.json"):
        flops.peaks_for("TPU v99")
    roof = flops.roofline_seconds(197e12, 1.0, v5e)
    assert roof == {"seconds": 1.0, "bound": "compute"}
    assert flops.roofline_seconds(1.0, 819e9, v5e)["bound"] == "memory"
    with open(flops.PEAKS_FILE) as f:
        assert all("source" in row for row in json.load(f).values())
