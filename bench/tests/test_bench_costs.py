"""Operation and byte counts against hand counts at small shapes, and the
peaks table."""
import pytest

from bench.arch import olmo
from bench.lib import costs, spec
from bench.lib.peaks import PEAKS, peaks

# two layers of attention (2 heads, 1 KV head, width 4) and a SwiGLU MLP of
# width 16 over d = 8: 576 matmul parameters a layer
S = costs.Shape(attn_layers=2, heads=2, kv_heads=1, head_dim=4,
                body_params=2 * 576, d=8, vocab=10)
HAND = {"model_type": "olmo", "vocab_size": 10, "hidden_size": 8,
        "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4}


def test_block_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, three 8x16 MLP matrices, per layer
    assert costs.Shape.of(HAND) == S
    assert S.body_params == 2 * (64 + 32 + 32 + 64 + 3 * 128)


def test_olmo_counts_every_layer_as_attention_and_mlp():
    """At OLMo-1B's published widths every one of the 16 layers holds
    attention and the MLP, as the counts took it before the shape moved to
    the architecture's module."""
    cfg = spec.config(spec.load(), "olmo_1b")
    s = olmo.shape(cfg)
    block = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert (s.attn_layers, s.heads, s.kv_heads, s.head_dim) == (16, 16, 16,
                                                                128)
    assert s.body_params == 16 * block and (s.d, s.vocab) == (2048, 50304)
    assert costs.body_flops(s, 3) == 2.0 * 16 * block * 3


def test_body_and_head_flops_by_hand():
    assert costs.body_flops(S, 3) == 2 * 2 * 576 * 3
    assert costs.head_flops(S, 5) == 2 * 8 * 10 * 5


def test_attention_counts_live_prefix_by_hand():
    # queries at positions 4, 5, 6 see 5, 6 and 7 keys
    keys = 5 + 6 + 7
    assert costs.attn_flops(S, 4, 3) == 2 * keys * 2 * (4 + 4) * 2
    # K and V of positions 0..6 for one KV head, q and o of 3 queries x 2
    # heads, 2 bytes each, 2 layers
    assert costs.attn_bytes(S, 4, 3) == (2 * 7 * 4 + 2 * 3 * 2 * 4) * 2 * 2
    # a decode step at position 0 reads one key
    assert costs.attn_flops(S, 0, 1) == 2 * 1 * 2 * 8 * 2


def test_useful_flops_sums_steps():
    class T:
        chunks = [(4, 3), (10, 1)]
        emitted = 2
    want = (costs.body_flops(S, 4) + costs.attn_flops(S, 4, 3)
            + costs.attn_flops(S, 10, 1) + costs.head_flops(S, 2))
    assert costs.useful_flops([T(), T()], S) == 2 * want


def test_least_time_takes_the_larger_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000.0, 5.0, p) == 10.0
    assert costs.least_time(10.0, 50.0, p) == 5.0


def test_peaks_of_v5e_with_source():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert all("source" in v for v in PEAKS.values())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
