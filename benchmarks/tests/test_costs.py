"""The FLOP and byte functions against counts made by hand."""
from benchmarks import common, costs


def cfg(name):
    return common.load_json("configs", name + ".json")


def test_gpt2_flops_per_token_by_hand():
    c = cfg("gpt2-124m")
    n = 50257 * 768 + 1024 * 768 + 12 * 12 * 768 * 768 + 2 * 768
    assert costs.gpt2_param_count(c) == n == 124_320_000
    want = 6 * n + 12 * 12 * 768 * 1024
    assert costs.gpt2_train_flops_per_token(c, 1024) == want
    assert abs(want - 859.2e6) < 0.1e6


def test_flash_flops_and_bytes_by_hand():
    # one layer, B24 T1024 H12 D64: forward 2 matmuls of 2*B*H*T*T*D,
    # halved by the mask; backward 2.5x
    fwd = 2 * 2 * 24 * 12 * 1024 * 1024 * 64 / 2
    assert costs.flash_causal_flops(24, 1024, 12, 64, False) == fwd
    assert costs.flash_causal_flops(24, 1024, 12, 64) == 3.5 * fwd
    t = 24 * 1024 * 12 * 64 * 2
    assert costs.flash_bytes(24, 1024, 12, 64, False) == 4 * t
    assert costs.flash_bytes(24, 1024, 12, 64) == 12 * t


def test_mistral_bytes_by_hand():
    d16 = cfg("mistral-7b-v0.3-d16")
    whole = dict(d16, num_hidden_layers=32)
    layer = (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
             + 3 * 4096 * 14336) * 2
    assert costs.llama_layer_weight_bytes(d16) == layer == 436_207_616
    assert costs.llama_kv_bytes_per_token(d16) == 2 * 8 * 128 * 16 * 2
    assert costs.llama_kv_bytes_per_token(whole) == 131_072   # 128 KiB
    head = 32768 * 4096 * 2
    # 32 slots holding 10,000 tokens between them
    want = 16 * layer + head + 32 * 4096 * 2 + (10_000 + 32) * 65_536
    assert costs.llama_decode_step_bytes(d16, 10_000, 32) == want
    # weights dominate: 7.0 GB of layers
    assert abs(16 * layer - 6.98e9) < 0.01e9
    assert abs(32 * layer + head - 14.2e9) < 0.1e9
