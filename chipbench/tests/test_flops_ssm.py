"""``flops_ssm.py`` against hand counts: one Mamba-2 layer, one attention layer, one sparse feed-forward, each a
layer of one part."""

import pytest

from chipbench import flops_decoder, flops_ssm

MODEL = {"hidden_size": 32, "num_attention_heads": 4, "head_dim": 16, "experts_held": [0, 2], "num_experts": 8,
         "num_experts_per_tok": 2, "layer_types": ["mamba2", "moe", "full_attention"],
         "ssm_heads": 4, "ssm_head_dim": 8, "ssm_groups": 2, "ssm_state_size": 16}
SHAPES = {
    "embed": (64, 32), "head": (32, 64), "value_head": (32, 1), "norm_out": (32,),
    "layer_0/norm_in": (32,), "layer_0/w_in": (32, 32 + 96 + 4), "layer_0/conv_w": (4, 96), "layer_0/conv_b": (96,),
    "layer_0/dt_bias": (4,), "layer_0/A_log": (4,), "layer_0/D": (4,), "layer_0/norm_gate": (32,), "layer_0/w_out": (32, 32),
    "layer_1/norm_pre_mlp": (32,), "layer_1/moe/router": (32, 8), "layer_1/moe/router_bias": (8,),
    "layer_1/moe/shared/w1": (32, 24), "layer_1/moe/shared/w2": (24, 32),
    "layer_1/moe/experts/w1": (2, 32, 16), "layer_1/moe/experts/w2": (2, 16, 32),
    "layer_2/norm_in": (32,), "layer_2/wq": (32, 64), "layer_2/wk": (32, 32), "layer_2/wv": (32, 32), "layer_2/wo": (64, 32),
}


def test_forward_per_token_by_hand():
    mamba = 2 * (32 * 132 + 32 * 32) + 2 * 4 * 96  # in and out projections; 4 taps a channel of [x, B, C]
    state = 4 * 4 * 8 * 16  # the update and the read: a multiply-add each an entry of 4 heads x 8 x 16
    sparse = 2 * 32 * 8 + 2 * 2 * 32 * 24 + 2 * 2 * 32 * 16 * 2 * 2 / 8  # router; shared, two matrices; 2 a token over 8, 2 held
    attention = 2 * (32 * 64 * 2 + 32 * 32 * 2)  # q, out; k, v: no gate
    products = 2 * 2 * 64 * 11.0  # scores and weighted values at a context of 11 keys: the attention layer alone
    heads = 2 * (32 * 64 + 32)
    assert flops_ssm.state_per_token(MODEL) == state
    assert flops_ssm.forward_per_token(SHAPES, MODEL, 0.0, 11.0) == pytest.approx(mamba + state + sparse + attention + products + heads)


def test_a_dispatch_is_one_forward_of_the_rollout_and_four_of_the_update():
    per_update = flops_ssm.ppo_ssm(SHAPES, MODEL, tokens=64, update_epochs=1, num_minibatches=4, len_min=16, len_max=16)
    forward = flops_ssm.forward_per_token(SHAPES, MODEL, 0.0, flops_decoder.mean_context(16, 16))
    assert per_update == pytest.approx(64 * forward * 5 / 4)


def test_the_published_cut_counts_the_state_at_four_times_its_entries_a_layer():
    model = dict(MODEL, ssm_heads=64, ssm_head_dim=64, ssm_state_size=128, layer_types=["mamba2", "moe"] * 2)
    assert flops_ssm.state_per_token(model) == 4 * 64 * 64 * 128
    with_state = flops_ssm.forward_per_token(SHAPES, model, 0.0, 0.0)
    assert with_state - flops_ssm.forward_per_token(SHAPES, dict(model, layer_types=["moe"]), 0.0, 0.0) == 2 * 4 * 64 * 64 * 128


def test_a_model_without_a_state_space_layer_counts_as_flops_hybrid_counts_it():
    from chipbench import flops_hybrid
    from chipbench.tests.test_flops_decoder import MODEL as TRINITY, SHAPES as TRINITY_SHAPES

    model = dict(TRINITY, hidden_size=32)
    assert flops_ssm.forward_per_token(TRINITY_SHAPES, model, 5.0, 11.0) == pytest.approx(
        flops_hybrid.forward_per_token(TRINITY_SHAPES, model, 5.0, 11.0))
