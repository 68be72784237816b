"""The byte and FLOP functions against sums made by hand: the Llama
family's counts, and ``roofline.py`` handing on to them by the family a
configuration names."""

import json
import os

import pytest

from chipbench import roofline
from chipbench.counts import llama_family as counts

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


# Mistral-7B-v0.3's published widths under weight-only int8: no cell
# yet (PERF.md section 7), and the second shape the functions are held to.
MISTRAL_INT8 = {"hidden_size": 4096, "intermediate_size": 14336,
                "num_hidden_layers": 32, "num_attention_heads": 32,
                "num_key_value_heads": 8, "vocab_size": 32768,
                "chipbench": {"family": "llama_family",
                              "quantization": "int8"}}


def test_qwen_sums():
    c = cfg("qwen2.5-3b")
    # q 2048x2048, k and v 2048x256, o 2048x2048, three of 2048x11008.
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert per_layer == 77_070_336
    assert counts.layer_matmul_params(c) == per_layer
    assert counts.head_params(c) == 2048 * 151936
    # 36 layers x 2 (K, V) x 2 heads x 128 x 2 bytes = 36 KiB a token.
    assert counts.kv_bytes_per_token(c) == 36_864
    weights = 36 * per_layer * 2 + 2048 * 151936 * 2
    assert weights == 6_171_394_048
    assert roofline.decode_step_bytes(c, 0) == weights
    assert roofline.decode_step_bytes(c, 45_000) == weights + 45_000 * 36_864


def test_mistral_int8_sums():
    c = MISTRAL_INT8
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert counts.layer_matmul_params(c) == per_layer
    assert counts.kv_bytes_per_token(c) == 131_072
    # int8 projections are one byte each; the head stays bfloat16.
    weights = 32 * per_layer + 4096 * 32768 * 2
    assert weights == 7_247_757_312
    assert roofline.decode_step_bytes(c, 1000) == weights + 131_072_000


def test_prefill_flops_by_hand():
    c = MISTRAL_INT8
    per_layer = 218_103_808
    # One chunk of 4 tokens at the start of a prompt, not the last:
    # projections 2 x 32 x per_layer x 4; attention sees 1+2+3+4 = 10
    # positions, 4 x 128 x 32 heads x 32 layers each.
    want = 2 * 32 * per_layer * 4 + 4 * 32 * 32 * 128 * 10
    assert roofline.prefill_flops(c, [(0, 4, False)]) == want
    # The same chunk 512 tokens into the prompt attends 4 x 512 more,
    # and as the last chunk pays for the head once.
    more = 4 * 32 * 32 * 128 * (4 * 512) + 2 * 4096 * 32768
    assert roofline.prefill_flops(c, [(512, 4, True)]) == want + more


def test_an_unknown_device_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("source")
