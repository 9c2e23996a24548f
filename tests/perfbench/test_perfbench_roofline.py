"""Operations and bytes from shapes, against hand-worked numbers."""

import json
import os

import pytest

from perfbench import roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name, "config.json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flash_prefill_call_by_hand():
    # 2048 tokens, 32 heads over 8 kv heads of 128: the lower triangle has
    # 2048 * 2049 / 2 = 2,098,176 pairs; QK^T and PV are 2 * 128
    # multiply-adds a pair and head = 4 * 128 operations
    got = roofline.flash_prefill_call(2048, 32, 8, 128)
    assert got["flops"] == 4 * 2098176 * 128 * 32 == 34376515584
    # q and o: 2048 * 32 * 128 elements each; k and v: 2048 * 8 * 128 each
    assert got["bytes"] == (2 * 8388608 + 2 * 2097152) * 2 == 41943040


def test_flash_prefill_is_compute_bound_on_a_v5e():
    call = roofline.flash_prefill_call(2048, 32, 8, 128)
    least = roofline.least_seconds(call["flops"], call["bytes"], PEAKS)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(34376515584 / 197e12)


def test_dense_decode_step_reads_every_matrix_once():
    cfg = config("qwen3-8b-int8")
    # a layer: q and o 4096 x 4096, k and v 4096 x 1024, MLP 3 x 4096 x 12288
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert layer == 192937984
    want = 36 * layer + 151936 * 4096
    assert roofline.decode_weight_bytes(cfg, 1.0, 12) == want == 7568097280
    assert roofline.decode_weight_bytes(cfg, 2.0, 1) == 2 * want


def test_sparse_decode_step_reads_the_experts_its_tokens_touch():
    cfg = config("qwen3-30b-a3b-int8-l12")
    attn = 2 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 768
    router = 2048 * 128
    head = 151936 * 2048
    # one token touches its 8 experts
    one = 12 * (attn + router + 8 * expert) + head
    assert roofline.decode_weight_bytes(cfg, 1.0, 1) == pytest.approx(one)
    # very many tokens touch all 128
    every = 12 * (attn + router + 128 * expert) + head
    assert roofline.decode_weight_bytes(cfg, 1.0, 10000) == pytest.approx(every)
    # eight tokens: 128 * (1 - (15/16)^8) = 51.6 experts expected
    assert roofline.expected_distinct_experts(128, 8, 8) == pytest.approx(
        128 * (1 - (15 / 16) ** 8)
    )


def test_least_seconds_names_what_binds():
    got = roofline.least_seconds(1e12, 819e9, PEAKS)
    assert got == {"seconds": 1.0, "bound": "memory"}
