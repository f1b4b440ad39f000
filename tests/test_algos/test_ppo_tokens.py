"""``exp=ppo_tokens``: the decoder core through ``cli.run`` and the fused ``ppo_recurrent.anakin_phase``.

One tiny run gives the lowered phase (the named scopes) and the span log; one rehearsal of the benchmark's
cell under its probes gives what ``correct`` compares, i.e. the masked loss, its gradients (Adam's first
moment after the first dispatch) and the parameters' change, each against the plain reference."""

import re
import time

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
HYBRID = [o.replace("decoder=tiny", "decoder=tiny_hybrid") for o in TINY]
HYBRID_SCOPES = ("policy.conv", "policy.attn.full", "policy.moe.route", "policy.moe.experts", "policy.head")
SSM = [o.replace("decoder=tiny", "decoder=tiny_ssm") for o in TINY]
SSM_SCOPES = ("policy.ssm", "policy.ssm/policy.ssm.scan", "policy.attn.full", "policy.moe.route", "policy.moe.experts",
              "policy.moe.shared", "policy.head")
SPARSE = [o.replace("decoder=tiny", "decoder=tiny_sparse") for o in TINY]
SPARSE_SCOPES = ("policy.attn.index", "policy.attn.sparse", "policy.moe.route", "policy.moe.experts", "policy.head")


def run_probed(overrides, tmp_path_factory):
    """One short run: the lowered phase's text, the span log and the recorder's events."""
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.telemetry.recorder import RECORDER

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
    t0 = time.time()
    try:
        run(overrides + [f"log_dir={tmp_path_factory.mktemp('ppo_tokens')}"])
    finally:
        Fabric.compile = real
    return {"lowered": lowered[0], "records": SPANS.records(),
            "carry_events": [e for e in RECORDER.snapshot() if e["kind"] == "decoder.carry" and e["t"] >= t0]}


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    return run_probed(TINY, tmp_path_factory)


@pytest.fixture(scope="module")
def hybrid_run(tmp_path_factory):
    return run_probed(HYBRID, tmp_path_factory)


@pytest.fixture(scope="module")
def ssm_run(tmp_path_factory):
    return run_probed(SSM, tmp_path_factory)


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
        assert set(r.counts) == {"moe_load_max", "moe_load_mean", "beyond_window", "steps", "carry_bytes", "cache_read", "cache_held",
                                 "moe_rows_run", "moe_rows_all"}
        assert r.counts["moe_rows_all"] == 4 * 8 * 2 * 4  # tokens x k x expert layers
        assert 0 < r.counts["moe_rows_run"] <= 256 and r.counts["moe_rows_run"] % 32 == 0  # under one tile: a pass's 32 rows, or none where no pair came here
        assert r.counts["cache_read"] == r.counts["cache_held"] == 4 * 8 * (4 * 8 + 32)  # caches this short are read whole
        assert r.counts["steps"] == 4 * 8 and 0 <= r.counts["beyond_window"] <= 32
        assert r.counts["moe_load_max"] >= r.counts["moe_load_mean"] > 0
        assert r.counts["carry_bytes"] == (4 * 8 + 32) * 2 * 16 * 2 * 4 + 4  # four rings of 8, a cache of 32, float32; pos
    assert sum(r.counts["beyond_window"] for r in pulls) > 0  # warm-started past the window of 8


def test_the_hybrid_runs_through_the_cli_with_its_scopes_its_event_and_its_counts(hybrid_run):
    """``algo/decoder@algo.decoder=tiny_hybrid`` through ``cli.run`` on the fused path: ``policy.conv`` beside
    ``policy.attn.full`` inside the known scopes (no window layer, no shared expert: neither scope), one
    ``decoder.carry`` event, ``carry_bytes`` on every ``stats.pull`` and no step counted beyond a window."""
    text = hybrid_run["lowered"]
    for name in HYBRID_SCOPES:
        assert re.search(r"rollout\.policy/[^\"]*" + re.escape(name), text), f"{name} not inside rollout.policy"
        assert re.search(r"update\.loss\)?/[^\"]*" + re.escape(name), text), f"{name} not inside update.loss"
    assert "policy.attn.window" not in text and "policy.moe.shared" not in text
    (event,) = hybrid_run["carry_events"]
    by_kind = {"conv": 4 * 2 * 64 * 4, "full_attention": 32 * 2 * 16 * 2 * 4, "pos": 4}  # float32 under 32-true
    assert event["layers"] == {"conv": 4, "full_attention": 1} and event["bytes_per_env"] == by_kind
    pulls = [r for r in hybrid_run["records"] if r.name == "stats.pull"]
    assert len(pulls) == 3 and {"exec.ppo_recurrent.prefill", "exec.ppo_recurrent.anakin_phase"} <= {r.name for r in hybrid_run["records"]}
    for r in pulls:
        assert r.counts["carry_bytes"] == sum(by_kind.values()) and r.counts["beyond_window"] == 0
        assert r.counts["moe_load_max"] >= r.counts["moe_load_mean"] > 0


def test_the_state_space_hybrid_runs_through_the_cli_with_its_scopes_its_event_and_its_counts(ssm_run):
    """``algo/decoder@algo.decoder=tiny_ssm`` through ``cli.run`` on the fused path, prefill and checkpoint included:
    ``policy.ssm`` and inside it ``policy.ssm.scan`` beside ``policy.attn.full`` inside the known scopes (no window
    layer, no conv layer: neither scope), one ``decoder.carry`` event with the new kind and its bytes, and on every
    ``stats.pull`` the bytes of state and window the dispatch's decode steps read and wrote."""
    text = ssm_run["lowered"]
    for name in SSM_SCOPES:
        assert re.search(r"rollout\.policy/[^\"]*" + re.escape(name), text), f"{name} not inside rollout.policy"
        assert re.search(r"update\.loss\)?/[^\"]*" + re.escape(name), text), f"{name} not inside update.loss"
    assert "policy.attn.window" not in text and "policy.conv" not in text
    (event,) = ssm_run["carry_events"]
    state_and_window = 4 * 16 * 8 * 4 + 3 * (64 + 2 * 2 * 8) * 4  # float32 both under 32-true
    by_kind = {"mamba2": 4 * state_and_window, "full_attention": 32 * 2 * 16 * 2 * 4, "pos": 4}
    assert event["layers"] == {"mamba2": 4, "moe": 4, "full_attention": 1} and event["bytes_per_env"] == by_kind
    names = {r.name for r in ssm_run["records"]}
    assert {"exec.ppo_recurrent.prefill", "exec.ppo_recurrent.anakin_phase", "ckpt.save"} <= names
    pulls = [r for r in ssm_run["records"] if r.name == "stats.pull"]
    assert len(pulls) == 3
    for r in pulls:
        assert set(r.counts) == {"moe_load_max", "moe_load_mean", "beyond_window", "steps", "carry_bytes", "cache_read",
                                 "cache_held", "ssm_state_bytes", "moe_rows_run", "moe_rows_all"}
        assert 0 < r.counts["moe_rows_run"] <= r.counts["moe_rows_all"]
        assert r.counts["steps"] == 4 * 8 and r.counts["ssm_state_bytes"] == 4 * 8 * 4 * 2 * state_and_window
        assert r.counts["carry_bytes"] == sum(by_kind.values()) and r.counts["beyond_window"] == 0
        assert r.counts["cache_read"] == r.counts["cache_held"] == 4 * 8 * 32  # one attention layer of nine holds a cache
        assert r.counts["moe_load_max"] >= r.counts["moe_load_mean"] > 0


def test_the_trinity_run_records_its_carry_too(tiny_run):
    (event,) = tiny_run["carry_events"]
    assert event["layers"] == {"sliding_attention": 4, "full_attention": 1}
    assert event["bytes_per_env"] == {"sliding_attention": 4 * 8 * 2 * 16 * 2 * 4, "full_attention": 32 * 2 * 16 * 2 * 4, "pos": 4}


def rehearse(cell):
    """A benchmark cell at rehearsal size under the harness's probes, kept with what they copied."""
    from chipbench import harness
    from sheeprl_tpu.cli import run
    from sheeprl_tpu.config.compose import compose

    SPANS.reset()
    h = harness.Harness(cell, 2147483693, 1.0, True, rehearse=True)
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


@pytest.fixture(scope="module")
def rehearsal():
    return rehearse("trinity_tokens_longgen")


@pytest.fixture(scope="module")
def hybrid_rehearsal():
    return rehearse("lfm2_tokens_longgen")


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
    assert harness.load_module("metrics", "cache.read_pct").read(ctx) == 100.0  # the tiny caches are read whole
    assert 50.0 < harness.load_module("metrics", "moe.rows_run_pct").read(ctx) <= 100.0  # a tiny update's 32 rows are under one tile: all of a pass, or none
    assert harness.load_module("metrics", "loop.host_ms_per_iter").read(ctx) >= 0.0
    assert h.program.flops_per_update(h.cfg, ctx["param_shapes"]) > 0


def test_the_hybrid_cell_matches_its_reference_and_its_readers_return_numbers(hybrid_rehearsal):
    """float32 against float32 for the hybrid: the six numbers and the two over the steps whose taps reach
    outside the segment; then every reader of the cell's per-layer metrics that needs no device trace."""
    from chipbench import harness

    h = hybrid_rehearsal
    correct, compared, numbers, _ = harness.judge(h.program, h.cfg, h.snap, h.spec["config"], h.compiles_in_window)
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert set(gaps) == {"logprob_gap", "value_gap", "moment_gap", "change_gap", "load_gap", "carry_tap_gap", "reset_tap_gap"}
    assert correct and max(gaps.values()) < 5e-4, gaps
    assert numbers["first_loss_gap"]["value"] < 5e-4  # read and recorded, not judged (PERF.md section 2 says why)
    assert numbers["where"]["value"]["skipped"] == [f"layer_{i}/moe/router_bias" for i in (1, 2, 3, 4)]
    ctx = {"window": h.window, "calls": h.calls, "cfg": h.cfg, "trace": None, "chips": 1, "peak": None,
           "program": h.program, "param_shapes": h.program.param_shapes(h.snap["inputs"][0])}
    assert harness.load_module("metrics", "tokens.dispatch_ms").read(ctx) > 0
    assert harness.load_module("metrics", "moe.load_max_over_mean").read(ctx) >= 1.0
    assert harness.load_module("metrics", "carry.mb_per_env").read(ctx) == pytest.approx((4 * 2 * 64 * 4 + 32 * 2 * 16 * 2 * 4 + 4) / 1e6)
    assert harness.load_module("metrics", "cache.beyond_window_pct").read(ctx) == 0.0  # not one of this cell's: no window
    assert harness.load_module("metrics", "loop.host_ms_per_iter").read(ctx) >= 0.0
    assert h.program.flops_per_update(h.cfg, ctx["param_shapes"]) > 0
    cell_metrics = harness.metric_names(h.spec["bench"], "lfm2_tokens_longgen", "per_layer")
    assert "carry.mb_per_env" in cell_metrics and "cache.beyond_window_pct" not in cell_metrics
    assert "cache.read_pct" in cell_metrics and harness.load_module("metrics", "cache.read_pct").read(ctx) == 100.0
    assert "moe.rows_run_pct" in cell_metrics and 50.0 < harness.load_module("metrics", "moe.rows_run_pct").read(ctx) <= 100.0


@pytest.mark.parametrize("reader, counted", [("carry.mb_per_env", ("carry_bytes",)), ("cache.read_pct", ("cache_read", "cache_held")),
                                             ("ssm.state_mb_per_step", ("ssm_state_bytes",)), ("moe.rows_run_pct", ("moe_rows_run", "moe_rows_all")),
                                             ("index.mb_per_step", ("index_bytes",))])
def test_a_count_s_reader_finds_nothing_in_a_program_that_does_not_count_it(hybrid_rehearsal, monkeypatch, reader, counted):
    """On a checkout from before its counter a reader returns nothing and does not raise."""
    from chipbench import harness, spanlog

    h = hybrid_rehearsal
    stripped = [type("R", (), {"name": r.name, "start": r.start, "end": r.end,
                               "counts": {k: v for k, v in (r.counts or {}).items() if k not in counted}})() for r in SPANS.records()]
    monkeypatch.setattr(spanlog, "records", lambda: stripped)
    assert harness.load_module("metrics", reader).read({"window": h.window}) is None
    monkeypatch.setattr(spanlog, "records", lambda: None)
    assert harness.load_module("metrics", reader).read({"window": h.window}) is None


def test_the_decoder_core_needs_the_fused_path():
    from sheeprl_tpu.cli import run

    with pytest.raises(ValueError, match="fused path"):
        run([o for o in TINY if not o.startswith("env.wrapper")] + ["env=gym", "env.id=CartPole-v1", "env.capture_video=False", "env.sync_env=True",
             "algo.mlp_keys.encoder=[state]", "log_dir=/tmp/_ppo_tokens_never"])


@pytest.fixture(scope="module")
def ssm_rehearsal():
    return rehearse("nemotron3_tokens_longgen")


def test_the_state_space_cell_matches_its_reference_and_its_readers_return_numbers(ssm_rehearsal):
    """float32 against float32 for the state-space hybrid: the five numbers of the other token cells, the carry's
    states after the first dispatch (``state_gap``), the first dispatch's first steps (``carry_gap``) and its steps just
    after an episode's start (``reset_gap``); then
    every reader of the cell's per-layer metrics that needs no device trace."""
    from chipbench import harness

    h = ssm_rehearsal
    correct, compared, numbers, _ = harness.judge(h.program, h.cfg, h.snap, h.spec["config"], h.compiles_in_window)
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert set(gaps) == {"logprob_gap", "value_gap", "moment_gap", "change_gap", "load_gap", "state_gap", "carry_gap", "reset_gap"}
    assert correct and max(gaps.values()) < 5e-4, gaps
    assert numbers["first_loss_gap"]["value"] < 5e-4  # read and recorded, not judged
    assert numbers["where"]["value"]["skipped"] == [f"layer_{i}/moe/router_bias" for i in (1, 3, 6, 8)]
    assert sum(numbers["where"]["value"]["reset_steps"]) > 0 and len(numbers["where"]["value"]["state_gaps"]) == 4  # an episode started inside the first dispatch
    assert len(h.snap["outputs"][0]["ssm_states"]) == 4 and "ssm_states" not in h.snap["outputs"][1]
    ctx = {"window": h.window, "calls": h.calls, "cfg": h.cfg, "trace": None, "chips": 1, "peak": None,
           "program": h.program, "param_shapes": h.program.param_shapes(h.snap["inputs"][0])}
    state_and_window = 4 * 16 * 8 * 4 + 3 * (64 + 2 * 2 * 8) * 4
    read = lambda name: harness.load_module("metrics", name).read(ctx)  # noqa: E731
    assert read("ssm.state_mb_per_step") == pytest.approx(4 * 4 * 2 * state_and_window / 1e6)  # envs x layers x read and write
    assert read("carry.mb_per_env") == pytest.approx((4 * state_and_window + 32 * 2 * 16 * 2 * 4 + 4) / 1e6)
    assert read("tokens.dispatch_ms") > 0 and read("moe.load_max_over_mean") >= 1.0 and read("cache.read_pct") == 100.0
    assert read("loop.host_ms_per_iter") >= 0.0 and h.program.flops_per_update(h.cfg, ctx["param_shapes"]) > 0
    cell_metrics = harness.metric_names(h.spec["bench"], "nemotron3_tokens_longgen", "per_layer")
    assert 50.0 < read("moe.rows_run_pct") <= 100.0
    assert {"ssm.state_mb_per_step", "carry.mb_per_env", "cache.read_pct", "tokens.dispatch_ms", "moe.load_max_over_mean", "moe.rows_run_pct",
            "loop.stall_ms_per_iter", "loop.untracked_ms_per_iter", "step.mfu_pct", "device.idle_pct"} <= set(cell_metrics)
    assert "cache.beyond_window_pct" not in cell_metrics
    assert "ssm.state_mb_per_step" not in harness.metric_names(h.spec["bench"], "lfm2_tokens_longgen", "per_layer")


@pytest.fixture(scope="module")
def sparse_run(tmp_path_factory):
    return run_probed(SPARSE, tmp_path_factory)


def test_the_sparse_decoder_runs_through_the_cli_with_its_scopes_its_event_and_its_counts(sparse_run):
    """``algo/decoder@algo.decoder=tiny_sparse`` through ``cli.run`` on the fused path, prefill and checkpoint included:
    ``policy.attn.index`` and ``policy.attn.sparse`` inside the known scopes, ``policy.attn.index_loss`` inside the
    update's alone (no window, full-attention or shared-expert scope), one ``decoder.carry`` event with the new kind and
    its bytes (keys, values and index keys), and on every ``stats.pull`` the rows the decode steps fetched of their
    caches (at most 6 a layer and step) and the index keys they scored (every written position)."""
    text = sparse_run["lowered"]
    for name in SPARSE_SCOPES:
        assert re.search(r"rollout\.policy/[^\"]*" + re.escape(name), text), f"{name} not inside rollout.policy"
        assert re.search(r"update\.loss\)?/[^\"]*" + re.escape(name), text), f"{name} not inside update.loss"
    assert re.search(r"update\.loss\)?/[^\"]*policy\.attn\.index_loss", text)
    assert not re.search(r"rollout\.policy/[^\"]*policy\.attn\.index_loss", text)
    assert "policy.attn.window" not in text and "policy.attn.full" not in text and "policy.moe.shared" not in text
    (event,) = sparse_run["carry_events"]
    by_kind = {"sparse_attention": 2 * 32 * (2 * 2 * 16 + 8) * 4, "pos": 4}  # float32 under 32-true
    assert event["layers"] == {"sparse_attention": 2} and event["bytes_per_env"] == by_kind
    names = {r.name for r in sparse_run["records"]}
    assert {"exec.ppo_recurrent.prefill", "exec.ppo_recurrent.anakin_phase", "ckpt.save"} <= names
    pulls = [r for r in sparse_run["records"] if r.name == "stats.pull"]
    assert len(pulls) == 3
    for r in pulls:
        assert set(r.counts) == {"moe_load_max", "moe_load_mean", "beyond_window", "steps", "carry_bytes", "cache_read",
                                 "cache_held", "index_bytes", "moe_rows_run", "moe_rows_all"}
        assert r.counts["steps"] == 4 * 8 and r.counts["cache_held"] == 4 * 8 * 2 * 32
        assert 2 * 4 * 8 <= r.counts["cache_read"] <= 2 * 4 * 8 * 6  # 1 to 6 selected rows a layer and step
        assert r.counts["index_bytes"] % (2 * 8 * 4) == 0 and r.counts["index_bytes"] >= 8 * 4 * r.counts["cache_read"]
        assert r.counts["carry_bytes"] == sum(by_kind.values())
    assert sum(r.counts["cache_read"] for r in pulls) < sum(2 * 8 * 4 * 6 for _ in pulls)  # some steps stand before the 6th position


@pytest.fixture(scope="module")
def sparse_rehearsal():
    return rehearse("keye_tokens_longctx")


def test_the_sparse_cell_matches_its_reference_and_its_readers_return_numbers(sparse_rehearsal):
    """float32 against float32 for the learned sparse attention: the six numbers of the Trinity cell and the
    selection itself (``select_gap``: every step of the first dispatch, the same positions as the reference's); then
    every reader of the cell's per-layer metrics that needs no device trace."""
    from chipbench import harness

    h = sparse_rehearsal
    correct, compared, numbers, _ = harness.judge(h.program, h.cfg, h.snap, h.spec["config"], h.compiles_in_window)
    gaps = {k: v["value"] for k, v in compared.items() if k != "compiles_in_window"}
    assert set(gaps) == {"logprob_gap", "value_gap", "first_loss_gap", "moment_gap", "change_gap", "load_gap", "select_gap"}
    assert correct and max(gaps.values()) < 5e-4 and gaps["select_gap"] == 0.0, gaps
    assert numbers["where"]["value"]["skipped"] == [] and len(numbers["where"]["value"]["select_gaps"]) == 2
    assert h.snap["outputs"][0]["selected"].shape == (8, 4, 2, 6) and "selected" not in h.snap["outputs"][1]
    ctx = {"window": h.window, "calls": h.calls, "cfg": h.cfg, "trace": None, "chips": 1, "peak": None,
           "program": h.program, "param_shapes": h.program.param_shapes(h.snap["inputs"][0])}
    read = lambda name: harness.load_module("metrics", name).read(ctx)  # noqa: E731
    assert 4 * 2 * 32 <= read("index.mb_per_step") * 1e6 <= 4 * 2 * 32 * 32  # envs x layers x 1 to 32 positions x 8 float32 lanes
    assert read("carry.mb_per_env") == pytest.approx((2 * 32 * (2 * 2 * 16 + 8) * 4 + 4) / 1e6)
    assert 0.0 < read("cache.read_pct") < 6 / 32 * 100 + 1e-9  # at most 6 of 32 rows a layer
    assert read("tokens.dispatch_ms") > 0 and read("moe.load_max_over_mean") >= 1.0 and 0.0 < read("moe.rows_run_pct") <= 100.0
    assert read("loop.host_ms_per_iter") >= 0.0 and h.program.flops_per_update(h.cfg, ctx["param_shapes"]) > 0
    cell_metrics = harness.metric_names(h.spec["bench"], "keye_tokens_longctx", "per_layer")
    assert {"index.mb_per_step", "carry.mb_per_env", "cache.read_pct", "tokens.dispatch_ms", "moe.load_max_over_mean", "moe.rows_run_pct",
            "loop.stall_ms_per_iter", "loop.untracked_ms_per_iter", "step.mfu_pct", "device.idle_pct", "setup.prefill_s"} <= set(cell_metrics)
    assert "cache.beyond_window_pct" not in cell_metrics and "ssm.state_mb_per_step" not in cell_metrics
    assert "index.mb_per_step" not in harness.metric_names(h.spec["bench"], "trinity_tokens_longgen", "per_layer")
