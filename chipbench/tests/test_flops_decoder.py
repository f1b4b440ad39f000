"""``flops_decoder.py`` against hand counts."""

import pytest

from chipbench import flops_decoder

MODEL = {"num_attention_heads": 4, "head_dim": 16, "experts_held": [0, 2], "num_experts": 8, "num_experts_per_tok": 2,
         "layer_types": ["sliding_attention", "full_attention"], "sliding_window": 8}
SHAPES = {
    "embed": (64, 32), "head": (32, 64), "value_head": (32, 1), "norm_out": (32,),
    "layer_0/wq": (32, 64), "layer_0/wk": (32, 32), "layer_0/wv": (32, 32), "layer_0/wg": (32, 64), "layer_0/wo": (64, 32),
    "layer_0/norm_in": (32,), "layer_0/mlp/w1": (32, 96), "layer_0/mlp/w3": (32, 96), "layer_0/mlp/w2": (96, 32),
    "layer_1/wq": (32, 64), "layer_1/wk": (32, 32), "layer_1/wv": (32, 32), "layer_1/wg": (32, 64), "layer_1/wo": (64, 32),
    "layer_1/moe/router": (32, 8), "layer_1/moe/router_bias": (8,),
    "layer_1/moe/shared/w1": (32, 16), "layer_1/moe/shared/w3": (32, 16), "layer_1/moe/shared/w2": (16, 32),
    "layer_1/moe/experts/w1": (2, 32, 16), "layer_1/moe/experts/w3": (2, 32, 16), "layer_1/moe/experts/w2": (2, 16, 32),
}


def test_forward_per_token_by_hand():
    attention = 2 * (32 * 64 * 3 + 32 * 32 * 2)  # q, gate, out; k, v
    dense = 2 * 3 * 32 * 96
    shared = 2 * 3 * 32 * 16
    routed = 2 * 3 * 32 * 16 * 2 * 2 / 8  # 2 a token over 8 experts, 2 of them held: half an expert a token
    heads = 2 * (32 * 64 + 32)
    products = 2 * 2 * 64 * (5.0 + 11.0)  # scores and weighted values, 64 = 4 heads x 16, at contexts of 5 and 11 keys
    want = 2 * attention + dense + 2 * 32 * 8 + shared + routed + heads + products
    assert flops_decoder.forward_per_token(SHAPES, MODEL, 5.0, 11.0) == pytest.approx(want)


def test_mean_context_by_hand():
    # one length only: positions 0..L-1 see 1..L keys, under a window min(., W)
    assert flops_decoder.mean_context(16, 16) == pytest.approx(sum(range(1, 17)) / 16)
    assert flops_decoder.mean_context(16, 16, window=4) == pytest.approx((1 + 2 + 3 + 4 * 13) / 16)
    assert flops_decoder.mean_context(4, 4, window=8) == pytest.approx(2.5)
    # the cell's traffic: the full layer sees about 2300 keys a step, a window layer at most its 2048
    full, window = flops_decoder.mean_context(1024, 8192), flops_decoder.mean_context(1024, 8192, window=2048)
    assert 2200 < full < 2400 and 1300 < window < 1500


def test_a_dispatch_is_one_forward_of_the_rollout_and_four_of_the_update():
    per_update = flops_decoder.ppo_decoder(SHAPES, MODEL, tokens=64, update_epochs=1, num_minibatches=4, len_min=16, len_max=16)
    forward = flops_decoder.forward_per_token(
        SHAPES, MODEL, flops_decoder.mean_context(16, 16, 8), flops_decoder.mean_context(16, 16))
    assert per_update == pytest.approx(64 * forward * 5 / 4)
