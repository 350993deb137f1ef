"""``chipbench.flops`` and the dense family's counts against counts made by
hand at a small shape."""

from chipbench import flops as F
from chipbench import loader

D = loader.load_family(loader.ROOT / "chipbench" / "configs" / "qwen3-4b.json")

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
     "d_ff": 16, "vocab_size": 10}


def test_layer_params_by_hand():
    # q 8*4*2=64, k 8*2*2=32, v 32, o 4*2*8=64; gated MLP 8*2*16 + 16*8 = 384
    assert D.layer_matmul_params(M) == 64 + 32 + 32 + 64 + 384


def test_causal_pairs():
    assert F.causal_pairs(0, 3) == 1 + 2 + 3
    assert F.causal_pairs(3, 5) == 4 + 5
    assert F.causal_pairs(0, 5) == F.causal_pairs(0, 3) + F.causal_pairs(3, 5)


def test_prefill_and_decode_by_hand():
    dense = 2 * 3 * 2 * 576                     # 3 tokens, 2 layers, 576 weights
    attn = 4 * 2 * 4 * 2 * 6                    # 2 layers, 4 heads, head 2, 6 pairs
    head = 2 * 1 * 8 * 10                       # logits for the last token
    assert D.prefill_flops(M, 3) == dense + attn + head
    # token at position 3 attends 4 keys
    assert D.decode_flops(M, 3) == 2 * 2 * 576 + 4 * 2 * 4 * 2 * 4 + 2 * 8 * 10


def test_sequence_is_prefill_plus_decodes():
    want = D.prefill_flops(M, 3) + D.decode_flops(M, 3) + D.decode_flops(M, 4)
    assert D.sequence_flops(M, 3, 3) == want
    assert D.sequence_flops(M, 3, 1) == D.prefill_flops(M, 3)


def test_flash_cost_and_roofline():
    ops, nbytes = D.kernel_cost(M, batch=2, seq=3)
    assert ops == 4 * 2 * 4 * 2 * 6
    assert nbytes == 2 * 2 * 3 * 2 * (2 * 4 + 2 * 2)   # Q, O, K, V in bf16
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_time_s(ops, nbytes, peak) == max(ops / 100.0, nbytes / 10.0)
