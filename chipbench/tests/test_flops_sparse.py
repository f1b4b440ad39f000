"""``flops_sparse.py`` against hand counts: one layer of learned sparse attention and its sparse feed-forward."""

import pytest

from chipbench import flops_decoder, flops_sparse

MODEL = {"hidden_size": 32, "num_attention_heads": 4, "head_dim": 16, "experts_held": [0, 2], "num_experts": 8,
         "num_experts_per_tok": 2, "layer_types": ["sparse_attention"], "index_heads": 2, "index_head_dim": 8, "index_topk": 6}
SHAPES = {
    "embed": (64, 32), "head": (32, 64), "value_head": (32, 1), "norm_out": (32,),
    "layer_0/norm_in": (32,), "layer_0/wq": (32, 64), "layer_0/wk": (32, 32), "layer_0/wv": (32, 32), "layer_0/wo": (64, 32),
    "layer_0/q_norm": (16,), "layer_0/k_norm": (16,),
    "layer_0/index/wq": (32, 16), "layer_0/index/wk": (32, 8), "layer_0/index/norm": (8,), "layer_0/index/norm_bias": (8,),
    "layer_0/index/ww": (32, 2),
    "layer_0/norm_pre_mlp": (32,), "layer_0/moe/router": (32, 8),
    "layer_0/moe/experts/w1": (2, 32, 16), "layer_0/moe/experts/w3": (2, 32, 16), "layer_0/moe/experts/w2": (2, 16, 32),
}


def test_forward_per_token_by_hand():
    attention = 2 * (32 * 64 * 2 + 32 * 32 * 2)  # q, out; k, v: no gate
    index = 2 * (32 * 16 + 32 * 8 + 32 * 2)  # the indexer's queries, key and heads' weights
    sparse = 2 * 32 * 8 + 3 * 2 * 32 * 16 * 2 * 2 / 8  # router; three matrices, 2 a token over 8, 2 held
    scores = 2 * 2 * (8 + 1) * 20.0  # 2 index heads: a dot of 8 and the weight on its relu, over 20 written positions
    products = 2 * 2 * 64 * 5.0  # scores and weighted values over 5 selected keys
    heads = 2 * (32 * 64 + 32)
    assert flops_sparse.sparse_per_token(MODEL, 20.0, 5.0) == scores + products
    assert flops_sparse.forward_per_token(SHAPES, MODEL, 20.0, 5.0) == pytest.approx(attention + index + sparse + scores + products + heads)


def test_a_dispatch_is_one_forward_of_the_rollout_and_four_of_the_update_with_l_i():
    per_update = flops_sparse.ppo_sparse(SHAPES, MODEL, tokens=64, update_epochs=1, num_minibatches=2, len_min=16, len_max=16)
    ctx_index, ctx_selected = flops_decoder.mean_context(16, 16), flops_decoder.mean_context(16, 16, 6)
    assert ctx_index == pytest.approx(8.5) and ctx_selected == pytest.approx((21 + 6 * 10) / 16)  # 1 .. 6, then 6 keys
    forward = flops_sparse.forward_per_token(SHAPES, MODEL, ctx_index, ctx_selected)
    index_loss = (4 + 4) * ctx_selected  # the heads' probabilities summed, and the divergence over the selected
    assert per_update == pytest.approx(64 * (forward + 4 * (forward + index_loss)) / 2)


def test_the_published_cut_reads_the_selected_keys_and_scores_every_written_one():
    """At the cell's traffic (episodes log-uniform on 8,192 to 32,768) a token attends over about 1,930 selected keys
    and its indexer scores about 10,240 (the length law's means), 16 heads of 64 lanes."""
    ctx_index, ctx_selected = flops_decoder.mean_context(8192, 32768), flops_decoder.mean_context(8192, 32768, 2048)
    assert 10000 < ctx_index < 10500 and 1900 < ctx_selected < 1960
    model = dict(MODEL, num_attention_heads=32, head_dim=128, index_heads=16, index_head_dim=64)
    assert flops_sparse.sparse_per_token(model, ctx_index, ctx_selected) == pytest.approx(
        2 * 16 * 65 * ctx_index + 4 * 4096 * ctx_selected)
