"""The analytic operation counts against hand counts."""

import pytest

from chipbench import flops

PPO = {
    "params/feature_extractor/cnn_encoder/conv_0/kernel": (4, 4, 3, 32), "params/feature_extractor/cnn_encoder/conv_0/bias": (32,),
    "params/feature_extractor/cnn_encoder/conv_1/kernel": (4, 4, 32, 64), "params/feature_extractor/cnn_encoder/conv_1/bias": (64,),
    "params/feature_extractor/cnn_encoder/conv_2/kernel": (4, 4, 64, 64), "params/feature_extractor/cnn_encoder/conv_2/bias": (64,),
    "params/feature_extractor/cnn_proj/kernel": (4096, 512), "params/feature_extractor/cnn_proj/bias": (512,),
    "params/actor/dense_0/kernel": (512, 512), "params/actor/head/kernel": (512, 5),
    "params/critic/dense_0/kernel": (512, 512), "params/critic/head/kernel": (512, 1),
}
# by hand, multiply-adds x 2: convolutions at 32x32, 16x16 and 8x8 output positions, then the dense layers
PPO_FORWARD = 2 * (1536 * 1024 + 32768 * 256 + 65536 * 64 + 4096 * 512 + 512 * 512 + 512 * 5 + 512 * 512 + 512)


def test_pixel_ppo_forward_is_the_hand_count():
    assert PPO_FORWARD == 33_560_576  # the 33.5 MFLOP a frame that PERF.md reckons with
    assert flops.kernel_forward_flops(PPO) == PPO_FORWARD


def test_ppo_fused_per_update():
    # 512 envs x 128 steps: one forward when collected, three passes in each of 3 epochs; 12 updates a dispatch
    per_update = flops.ppo_fused(PPO, 512, 128, 3, 4)
    assert per_update == pytest.approx(65536 * PPO_FORWARD * 10 / 12)


def test_transposed_convolutions_count_one_multiply_add_per_tap_and_input_position():
    dec = {"observation_model/deconv_0/kernel": (4, 4, 256, 128), "observation_model/deconv_1/kernel": (4, 4, 128, 64),
           "observation_model/deconv_2/kernel": (4, 4, 64, 32), "observation_model/deconv_out/kernel": (4, 4, 32, 3)}
    by_hand = 2 * (16 * 256 * 128 * 4 * 4 + 16 * 128 * 64 * 8 * 8 + 16 * 64 * 32 * 16 * 16 + 16 * 32 * 3 * 32 * 32)
    assert flops.kernel_forward_flops(dec) == by_hand


def test_dv3_per_update_is_the_sum_of_its_parts():
    shapes = {
        "world_model/params/encoder/conv_0/kernel": (4, 4, 3, 8),
        "world_model/params/recurrent_model/in/kernel": (20, 16), "world_model/params/transition_model/dense_0/kernel": (16, 16),
        "world_model/params/reward_model/head/kernel": (32, 255),
        "actor/params/head/kernel": (32, 5), "critic/params/head/kernel": (32, 255), "target_critic/params/head/kernel": (32, 255),
    }
    conv, rec, trans, rew = 2 * 16 * 3 * 8 * 32 * 32, 2 * 20 * 16, 2 * 16 * 16, 2 * 32 * 255
    actor, critic = 2 * 32 * 5, 2 * 32 * 255
    B, L, H = 4, 8, 5
    by_hand = 3 * B * L * (conv + rec + trans + rew) + B * L * H * (rec + trans + 3 * actor) + B * L * (H + 1) * (3 * critic + critic)
    assert flops.dv3(shapes, B, L, H) == pytest.approx(by_hand)


def test_shapes_of_walks_a_nested_tree():
    import numpy as np

    tree = {"a": {"kernel": np.zeros((2, 3)), "bias": np.zeros((3,))}, "n": 4}
    assert flops.shapes_of(tree) == {"a/kernel": (2, 3), "a/bias": (3,)}
