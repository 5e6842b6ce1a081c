"""The benchmark's FLOP counter (``bench/flops.py``) against a hand count
for the tiny Qwen3 sizes."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402

TINY = {"hidden_size": 256, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 512,
        "num_hidden_layers": 2, "vocab_size": 512}


def test_linear_flops_hand_count():
    # per layer: q 256x256, k and v 256x64, o 256x256, gate/up/down 256x512
    per_layer = 65_536 + 16_384 + 16_384 + 65_536 + 3 * 131_072
    head = 256 * 512                       # tied unembedding
    assert flops.linear_flops(TINY) == 2 * (2 * per_layer + head) == 2_490_368


def test_attention_grows_with_position():
    # scores and weighted sum: 2 x 2 FLOPs per key, head dim 32, 8 heads,
    # 2 layers
    assert flops.attn_coeff(TINY) == 2 * 2 * 32 * 8 * 2 == 2_048
    assert flops.token_flops(TINY, 0) == 2_490_368 + 2_048
    assert flops.token_flops(TINY, 9) == 2_490_368 + 10 * 2_048


def test_span_is_the_sum_of_its_tokens():
    for lo, hi in ((0, 1), (0, 7), (5, 12), (100, 164), (3, 3)):
        assert flops.span_flops(TINY, lo, hi) == sum(
            flops.token_flops(TINY, p) for p in range(lo, hi))


def test_published_sizes():
    """Qwen3-0.6B: 0.44 B linear parameters and the 0.16 B tied head, so
    about 1.19 GFLOP a token before attention."""
    c = dict(TINY, hidden_size=1024, num_attention_heads=16,
             num_key_value_heads=8, head_dim=128, intermediate_size=3072,
             num_hidden_layers=28, vocab_size=151_936)
    per_layer = 1024 * 32 * 128 + 2048 * 1024 + 3 * 1024 * 3072
    assert flops.linear_flops(c) == 2 * (28 * per_layer + 1024 * 151_936)
    assert 1.18e9 < flops.linear_flops(c) < 1.20e9
