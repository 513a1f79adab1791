"""``opcount`` against numbers worked by hand from the published configurations."""

import pytest

from harness import opcount, registry


def arch(name, **over):
    a = registry.load_config(name)
    a.update(over)
    return a


def test_gpt2_large_parameters():
    a = arch("gpt2-large")
    # block: c_attn 1280*3840+3840, c_proj 1280*1280+1280, mlp 1280*5120+5120 + 5120*1280+1280, 2 LN of 2*1280
    assert opcount.layer_params(a) == 4919040 + 1639680 + 6558720 + 6554880 + 5120 == 19677440
    # 36 blocks + wte 50304*1280 (padded rows are stored) + wpe 1024*1280 + ln_f 2560
    assert opcount.num_params(a) == 36 * 19677440 + 64389120 + 1310720 + 2560 == 774090240
    # the published 774,030,080 with the unpadded 50257 rows
    assert opcount.num_params(arch("gpt2-large", padded_vocab_size=50257)) == 774030080


def test_gpt2_xl_parameters():
    a = arch("gpt2-xl")
    assert opcount.layer_params(a) == 30740800
    assert opcount.num_params(arch("gpt2-xl", padded_vocab_size=50257)) == 1557611200


def test_mistral_parameters_at_published_depth():
    a = arch("mistral-7b-v0.1", num_hidden_layers=32)
    # q 4096*4096, k and v 4096*1024 each, o 4096*4096, three 4096*14336, two norms
    assert opcount.layer_params(a) == 16777216 + 2 * 4194304 + 16777216 + 3 * 58720256 + 8192 == 218112000
    assert opcount.num_params(a) == 32 * 218112000 + 2 * 131072000 + 4096 == 7241732096  # 7.24 B, as published


@pytest.mark.parametrize("name,expect", [
    # 6 * (params - position table) + 12 * L * d * T / 2
    ("gpt2-large", 6 * (774090240 - 1310720) + 12 * 36 * 1280 * 1024 // 2),
    ("gpt2-xl", 6 * (1557686400 - 1638400) + 12 * 48 * 1600 * 1024 // 2),
])
def test_train_flops_per_token(name, expect):
    assert opcount.train_flops_per_token(arch(name), 1024) == expect


def test_flash_flops_per_sequence():
    # 36 layers * 7 matmuls * 2*T*T*d / 2 (causal)
    assert opcount.flash_train_flops_per_seq(arch("gpt2-large"), 1024) == 36 * 7 * 1024 * 1024 * 1280


def test_decode_step_bytes():
    a = arch("mistral-7b-v0.1", num_hidden_layers=22)
    assert opcount.kv_bytes_per_token(a) == 2 * 8 * 128 * 2 * 22 == 90112
    weights = 22 * 218112000 + 131072000 + 4096 + 40 * 4096  # blocks, head, final norm, 40 embedding rows
    assert opcount.decode_step_min_bytes(a, 30720, 40) == 2 * weights + 30720 * 90112
