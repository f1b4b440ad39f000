"""``exp=ppo_tokens``: the decoder core through ``cli.run`` and the fused ``ppo_recurrent.anakin_phase``.

One tiny run gives the lowered phase (the named scopes) and the span log; one rehearsal of the benchmark's
cell under its probes gives what ``correct`` compares, i.e. the masked loss, its gradients (Adam's first
moment after the first dispatch) and the parameters' change, each against the plain reference."""

import re

import pytest

from sheeprl_tpu.parallel.fabric import Fabric
from sheeprl_tpu.telemetry.spans import SPANS

TINY = [
    "exp=ppo_tokens", "algo/decoder@algo.decoder=tiny", "env.wrapper.vocab_size=64", "env.wrapper.prompt_min=2",
    "env.wrapper.prompt_max=4", "env.wrapper.len_min=24", "env.wrapper.len_max=32",
    "env.num_envs=4", "algo.rollout_steps=8", "algo.per_rank_batch_size=16", "algo.total_steps=96",
    "fabric.accelerator=cpu", "fabric.devices=1", "fabric.precision=32-true", "checkpoint.every=64",
    "metric.log_every=32", "print_config=False", "seed=7",
]
POLICY_SCOPES = ("policy.attn.window", "policy.attn.full", "policy.moe.route", "policy.moe.experts",
                 "policy.moe.shared", "policy.head")


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    from sheeprl_tpu.cli import run

    lowered = []
    real = Fabric.compile

    class Probe:
        def __init__(self, aot):
            self.aot = aot

        def __getattr__(self, name):
            return getattr(self.aot, name)

        def __call__(self, *args):
            if not lowered:
                lowered.append(self.aot.lower(*args).as_text(debug_info=True))
            return self.aot(*args)

    def probed(fabric, fn, **kwargs):
        aot = real(fabric, fn, **kwargs)
        return Probe(aot) if kwargs.get("name") == "ppo_recurrent.anakin_phase" else aot

    SPANS.reset()
    Fabric.compile = probed
    try:
        run(TINY + [f"log_dir={tmp_path_factory.mktemp('ppo_tokens')}"])
    finally:
        Fabric.compile = real
    return {"lowered": lowered[0], "records": SPANS.records()}


def test_the_lowered_phase_carries_the_policy_scopes_inside_the_known_ones(tiny_run):
    from chipbench.scopes import SCOPES  # the one list of the programs' scopes

    text = tiny_run["lowered"]
    for outer in ("rollout.policy", "rollout.env_step", "gae", "update.gather", "update.loss", "update.optim"):
        assert outer in SCOPES and outer in text, outer
    for name in POLICY_SCOPES:
        assert re.search(r"rollout\.policy/[^\"]*" + re.escape(name), text), f"{name} not inside rollout.policy"
        assert re.search(r"update\.loss\)?/[^\"]*" + re.escape(name), text), f"{name} not inside update.loss"


def test_spans_and_counters_of_a_run(tiny_run):
    records = tiny_run["records"]
    names = {r.name for r in records}
    assert {"iter", "update.dispatch", "stats.pull", "exec.ppo_recurrent.anakin_phase", "exec.ppo_recurrent.prefill"} <= names
    iters = [r for r in records if r.name == "iter"]
    assert [r.iteration for r in iters] == [1, 2, 3]
    pulls = [r for r in records if r.name == "stats.pull"]
    assert len(pulls) == 3
    for r in pulls:
        assert set(r.counts) == {"moe_load_max", "moe_load_mean", "beyond_window", "steps"}
        assert r.counts["steps"] == 4 * 8 and 0 <= r.counts["beyond_window"] <= 32
        assert r.counts["moe_load_max"] >= r.counts["moe_load_mean"] > 0
    assert sum(r.counts["beyond_window"] for r in pulls) > 0  # warm-started past the window of 8


@pytest.fixture(scope="module")
def rehearsal():
    """The benchmark's cell at rehearsal size under the harness's probes, kept with what they copied."""
    from chipbench import harness
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.config.compose import compose

    SPANS.reset()
    h = harness.Harness("trinity_tokens_longgen", 2147483693, 1.0, True, rehearse=True)
    overrides = h.overrides()
    h.cfg = compose(overrides).as_dict()
    h.install()
    try:
        run(overrides)
    except harness.WindowClosed:
        pass
    finally:
        h.uninstall()
    return h


def test_masked_loss_gradients_and_change_match_the_reference(rehearsal):
    """float32 against float32: the first minibatch's masked losses, Adam's first moment after the first
    dispatch (the clipped gradients of its minibatches), the parameters' change after three dispatches, the
    rollout's log-probabilities and values through the caches, and the router's counts."""
    from chipbench import harness

    h = rehearsal
    correct, compared, numbers, _ = harness.judge(h.program, h.cfg, h.snap, h.spec["config"], h.compiles_in_window)
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert set(gaps) == {"logprob_gap", "value_gap", "first_loss_gap", "moment_gap", "change_gap", "load_gap"}
    assert correct and max(gaps.values()) < 5e-4, gaps
    assert numbers["where"]["value"]["skipped"] == [f"layer_{i}/moe/router_bias" for i in (1, 2, 3, 4)]


def test_the_cell_s_readers_return_numbers(rehearsal):
    from chipbench import harness

    h = rehearsal
    ctx = {"window": h.window, "calls": h.calls, "cfg": h.cfg, "trace": None, "chips": 1, "peak": None,
           "program": h.program, "param_shapes": h.program.param_shapes(h.snap["inputs"][0])}
    assert harness.load_module("metrics", "tokens.dispatch_ms").read(ctx) > 0
    assert harness.load_module("metrics", "moe.load_max_over_mean").read(ctx) >= 1.0
    assert 0.0 < harness.load_module("metrics", "cache.beyond_window_pct").read(ctx) <= 100.0
    assert harness.load_module("metrics", "loop.host_ms_per_iter").read(ctx) >= 0.0
    assert h.program.flops_per_update(h.cfg, ctx["param_shapes"]) > 0


def test_the_decoder_core_needs_the_fused_path():
    from sheeprl_tpu.cli import run

    with pytest.raises(ValueError, match="fused path"):
        run([o for o in TINY if not o.startswith("env.wrapper")] + ["env=gym", "env.id=CartPole-v1", "env.capture_video=False", "env.sync_env=True",
             "algo.mlp_keys.encoder=[state]", "log_dir=/tmp/_ppo_tokens_never"])
