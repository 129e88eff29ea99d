"""FLOP and byte functions against counts made by hand."""
import flops

# a toy config: d 8, 2 heads of 4 (kv 1), ff 16, 3 layers, vocab 10
CFG = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
       "intermediate_size": 16, "num_hidden_layers": 3, "vocab_size": 10}


def test_linear():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate+up+down 3 * 8x16 = 576 MACs/layer
    assert flops.linear_flops_per_token(CFG) == 2 * 576 * 3


def test_attention():
    # 2 new tokens after 5 cached: 6 + 7 keys; 2 heads x 4 dims, QK and PV
    assert flops.attention_flops(CFG, 5, 2) == 2 * 2 * 2 * 4 * 13 * 3


def test_prefill_and_decode():
    lin, lg = 2 * 576 * 3, 2 * 8 * 10
    assert flops.prefill_flops(CFG, 0, 3) == 3 * lin + 2 * 2 * 8 * 6 * 3 + lg
    assert flops.decode_flops(CFG, 4) == lin + 2 * 2 * 8 * 5 * 3 + lg


def test_bytes():
    assert flops.gather_bytes(3, 16, 4096) == 2 * 3 * 16 * 4096
    # qwen1.5-0.5b: 24 layers x (k + v) x 16 heads x 64 x 2 bytes
    qwen = dict(CFG, hidden_size=1024, num_attention_heads=16,
                num_key_value_heads=16, num_hidden_layers=24)
    assert flops.kv_bytes_per_token(qwen) == 98304
