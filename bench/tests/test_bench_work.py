"""Operation and byte counts of one layer against hand arithmetic."""
import pytest

from bench import work

SMOLLM = dict(num_hidden_layers=1, hidden_size=960, num_attention_heads=15,
              num_key_value_heads=5, head_dim=64, intermediate_size=2560,
              vocab_size=49152)
QWEN3 = dict(num_hidden_layers=1, hidden_size=2560, num_attention_heads=32,
             num_key_value_heads=8, head_dim=128, intermediate_size=9728,
             vocab_size=151936)


@pytest.mark.parametrize("conf, per_token", [
    # q 960x960, k/v 960x320 each, o 960x960, gate/up/down 3 x 960x2560
    (SMOLLM, 921600 + 2 * 307200 + 921600 + 3 * 2457600),
    # q 2560x4096, k/v 2560x1024 each, o 4096x2560, 3 x 2560x9728
    (QWEN3, 10485760 + 2 * 2621440 + 10485760 + 3 * 24903680),
])
def test_products_per_token(conf, per_token):
    s = work.sizes(conf)
    assert work.layer_params(s) == per_token
    assert work.prefill(s, 4, 128)["products"] == 512 * per_token
    assert work.decode(s, 8, [40, 41])["products"] == 8 * per_token


@pytest.mark.parametrize("conf", [SMOLLM, QWEN3])
def test_prefill_attention_and_head(conf):
    s = work.sizes(conf)
    H, dh, D, V = s["H"], s["dh"], s["D"], s["V"]
    c = work.prefill(s, 4, 128)
    causal_pairs = 4 * 128 * 129 // 2
    assert c["flops"] == 4 * H * dh * causal_pairs + 2 * 4 * D * V


@pytest.mark.parametrize("conf", [SMOLLM, QWEN3])
def test_decode_keys_and_bytes(conf):
    s = work.sizes(conf)
    H, KV, dh, D, V = s["H"], s["KV"], s["dh"], s["D"], s["V"]
    c = work.decode(s, 8, [33, 100])
    keys = 33 + 100 + 6            # six rows without a request: one key
    assert c["flops"] == 4 * H * dh * keys + 2 * 8 * D * V
    lin = sum(8 * k + k * n + 2 * 8 * n for k, n in [
        (D, H * dh), (D, KV * dh), (D, KV * dh), (H * dh, D),
        (D, s["F"]), (D, s["F"]), (s["F"], D)])
    kv = 2 * keys * 2 * KV * dh
    head = 2 * (D * V + 8 * (D + V))
    assert c["bytes"] == lin + kv + head


def test_least_time_takes_the_larger_bound():
    peak = {"int8_ops_s": 400e12, "bf16_flops_s": 200e12,
            "hbm_bytes_s": 800e9}
    # compute: 2 * 1e12 / 400e12 + 2e12 / 200e12 = 0.015 s
    assert work.least_seconds({"products": 1e12, "flops": 2e12,
                               "bytes": 8e9}, peak) == pytest.approx(0.015)
    # memory: 16e9 / 800e9 = 0.02 s
    assert work.least_seconds({"products": 1e12, "flops": 2e12,
                               "bytes": 16e9}, peak) == pytest.approx(0.02)
