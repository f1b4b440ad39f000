"""``flops_hybrid.py`` against hand counts: one conv layer, one attention layer, one sparse layer."""

import pytest

from chipbench import flops_decoder, flops_hybrid

MODEL = {"hidden_size": 32, "num_attention_heads": 4, "head_dim": 16, "experts_held": [0, 2], "num_experts": 8,
         "num_experts_per_tok": 2, "layer_types": ["conv", "full_attention"]}
SHAPES = {
    "embed": (64, 32), "head": (32, 64), "value_head": (32, 1), "norm_out": (32,),
    "layer_0/w_in": (32, 96), "layer_0/conv_w": (3, 32), "layer_0/w_out": (32, 32), "layer_0/norm_in": (32,),
    "layer_0/mlp/w1": (32, 96), "layer_0/mlp/w3": (32, 96), "layer_0/mlp/w2": (96, 32),
    "layer_1/wq": (32, 64), "layer_1/wk": (32, 32), "layer_1/wv": (32, 32), "layer_1/wo": (64, 32), "layer_1/q_norm": (16,),
    "layer_1/moe/router": (32, 8), "layer_1/moe/router_bias": (8,),
    "layer_1/moe/experts/w1": (2, 32, 16), "layer_1/moe/experts/w3": (2, 32, 16), "layer_1/moe/experts/w2": (2, 16, 32),
}


def test_forward_per_token_by_hand():
    conv = 2 * (32 * 96 + 32 * 32) + 2 * 3 * 32 + 2 * 32  # in and out projections; 3 taps a channel; the two gates
    dense = 2 * 3 * 32 * 96
    attention = 2 * (32 * 64 * 2 + 32 * 32 * 2)  # q, out; k, v: no gate
    products = 2 * 2 * 64 * 11.0  # scores and weighted values, 64 = 4 heads x 16, at a context of 11 keys: the attention layer alone
    sparse = 2 * 32 * 8 + 2 * 3 * 32 * 16 * 2 * 2 / 8  # router; 2 a token over 8 experts, 2 of them held: half an expert a token
    heads = 2 * (32 * 64 + 32)
    assert flops_hybrid.forward_per_token(SHAPES, MODEL, 0.0, 11.0) == pytest.approx(conv + dense + attention + products + sparse + heads)


def test_a_dispatch_is_one_forward_of_the_rollout_and_four_of_the_update_and_a_model_without_a_window_counts_none():
    per_update = flops_hybrid.ppo_hybrid(SHAPES, MODEL, tokens=64, update_epochs=1, num_minibatches=4, len_min=16, len_max=16)
    forward = flops_hybrid.forward_per_token(SHAPES, MODEL, 0.0, flops_decoder.mean_context(16, 16))
    assert per_update == pytest.approx(64 * forward * 5 / 4)


def test_an_attention_only_model_counts_as_flops_decoder_counts_it():
    from chipbench.tests.test_flops_decoder import MODEL as TRINITY, SHAPES as TRINITY_SHAPES

    model = dict(TRINITY, hidden_size=32)
    assert flops_hybrid.forward_per_token(TRINITY_SHAPES, model, 5.0, 11.0) == pytest.approx(
        flops_decoder.forward_per_token(TRINITY_SHAPES, TRINITY, 5.0, 11.0))
    assert flops_hybrid.ppo_hybrid(TRINITY_SHAPES, model, 64, 1, 4, 16, 16) == pytest.approx(
        flops_decoder.ppo_decoder(TRINITY_SHAPES, TRINITY, 64, 1, 4, 16, 16))
