"""PolicyService + HTTP surface E2E over real committed dryrun checkpoints.

The heavyweight fixtures (tiny trained agents) are session-scoped in
conftest.py; everything here serves from them.
"""

import threading

import numpy as np
import pytest

from sheeprl_tpu.config.compose import ConfigError
from sheeprl_tpu.serve import PolicyService
from sheeprl_tpu.serve.loader import resolve_checkpoint
from sheeprl_tpu.utils.profiler import COMPILE_MONITOR


def _zero_obs(player):
    return {k: np.zeros(shape, np.dtype(dt)) for k, (shape, dt) in player.obs_spec.items()}


# -- loader: discovery spellings ---------------------------------------------


def test_resolve_checkpoint_spellings(ppo_ckpt, tmp_path):
    import pathlib

    step_dir = pathlib.Path(ppo_ckpt)
    assert resolve_checkpoint(step_dir) == step_dir
    # checkpoint root → newest committed snapshot
    assert resolve_checkpoint(step_dir.parent) == step_dir
    # version dir and run dir → same
    assert resolve_checkpoint(step_dir.parent.parent) == step_dir
    assert resolve_checkpoint(step_dir.parent.parent.parent) == step_dir
    with pytest.raises(ConfigError):
        resolve_checkpoint(tmp_path / "nope")


def test_resolve_checkpoint_rejects_torn_snapshot(ppo_ckpt, tmp_path):
    import os
    import pathlib

    from sheeprl_tpu.checkpoint.protocol import step_dir_name, write_shard

    torn = tmp_path / step_dir_name(999)
    os.makedirs(torn)
    write_shard(torn, 0, {"agent": {}})
    with pytest.raises(ConfigError, match="torn|COMMIT"):
        resolve_checkpoint(torn)
    # a root holding ONLY a torn snapshot has no servable checkpoint
    with pytest.raises(ConfigError, match="no committed checkpoint"):
        resolve_checkpoint(pathlib.Path(tmp_path))


# -- service -----------------------------------------------------------------


@pytest.fixture(scope="module")
def ppo_service(ppo_ckpt):
    svc = PolicyService.from_checkpoint(
        ppo_ckpt, ["serve.max_wait_ms=2", "serve.watch_commits=False"]
    )
    svc.start()
    yield svc
    svc.stop()


def test_service_single_and_concurrent_requests(ppo_service):
    obs = _zero_obs(ppo_service.player)
    a = ppo_service.act(obs, timeout=60.0)
    assert a.shape == ppo_service.player.action_shape
    # concurrent burst: every caller gets exactly one row back, none dropped
    results, errors = [], []

    def caller(i):
        try:
            results.append(ppo_service.act(obs, greedy=(i % 2 == 0), timeout=60.0))
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(i,)) for i in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    assert not errors
    assert len(results) == 24
    stats = ppo_service.stats()
    assert stats["errors"] == 0
    assert stats["served"] >= 25


def test_steady_state_never_recompiles(ppo_service):
    """The acceptance gate: after warm-up, Compile/* counters stay flat no
    matter how ragged the arrival pattern is (padding hits warmed rungs)."""
    obs = _zero_obs(ppo_service.player)
    ppo_service.act(obs, timeout=60.0)  # ensure fully settled
    before, _ = COMPILE_MONITOR.totals()
    for burst in (1, 3, 7, 12, 30):  # pads to rungs 1/8/8/32/32
        threads = [
            threading.Thread(target=ppo_service.act, args=(obs,), kwargs={"timeout": 60.0})
            for _ in range(burst)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    after, _ = COMPILE_MONITOR.totals()
    assert after == before, f"steady-state serving recompiled: {after - before} new executables"


def test_service_stats_shape(ppo_service):
    # a latency needs a served request: under xdist this test can be the first of its worker
    ppo_service.act(_zero_obs(ppo_service.player), timeout=60.0)
    stats = ppo_service.stats()
    for field in (
        "served", "batches", "errors", "avg_batch", "padded_frac",
        "generation", "checkpoint_step", "batch_ladder",
        "compile_executables", "p50_ms", "p99_ms",
    ):
        assert field in stats
    assert stats["checkpoint_step"] > 0
    assert np.isfinite(stats["p50_ms"])


# -- HTTP surface ------------------------------------------------------------


def test_http_round_trip(ppo_service):
    from sheeprl_tpu.serve.client import PolicyClient, ServerError
    from sheeprl_tpu.serve.server import PolicyServer

    server = PolicyServer(ppo_service)
    # service is already started (module fixture); bring up just the socket
    server._thread = threading.Thread(target=server._httpd.serve_forever, daemon=True)
    server._thread.start()
    try:
        client = PolicyClient(server.url)
        health = client.health()
        assert health["ok"] and health["algo"] == "ppo"

        obs = _zero_obs(ppo_service.player)
        a = client.act(obs, greedy=True)
        assert a.shape == ppo_service.player.action_shape

        packed = PolicyClient(server.url, packed=True)
        a2 = packed.act(obs, greedy=True)
        np.testing.assert_array_equal(a, a2)  # same greedy action, both codecs

        client.reset("some-session")
        stats = client.stats()
        assert stats["served"] >= 2

        with pytest.raises(ServerError) as exc:
            client._call("POST", "/v1/act", {"obs": {}})  # missing keys
        assert exc.value.status == 400
        with pytest.raises(ServerError) as exc:
            client._call("GET", "/nope")
        assert exc.value.status == 404
    finally:
        server._httpd.shutdown()
        server._httpd.server_close()


# -- evaluation CLI rides the same path --------------------------------------


def test_evaluation_cli_through_loader(ppo_ckpt):
    """cli:evaluation resolves + rebuilds through serve.loader, including the
    run-dir spelling the server accepts (not just an explicit file)."""
    import pathlib

    from sheeprl_tpu.cli import evaluation

    run_dir = pathlib.Path(ppo_ckpt).parent.parent
    evaluation([f"checkpoint_path={run_dir}", "env.capture_video=False"])


# -- dreamer_v3: stateful sessions (slow: XS world model still compiles) -----


@pytest.mark.slow
def test_dreamer_v3_sessions(dv3_ckpt):
    svc = PolicyService.from_checkpoint(
        dv3_ckpt,
        ["serve.batch_ladder=[1,8]", "serve.max_wait_ms=2", "serve.watch_commits=False"],
    )
    svc.start()
    try:
        assert svc.player.stateful
        obs = _zero_obs(svc.player)
        a1 = svc.act(obs, session="ep-1", timeout=120.0)
        assert svc.stats()["sessions"] == 1
        a2 = svc.act(obs, session="ep-1", timeout=120.0)
        assert a1.shape == a2.shape == svc.player.action_shape
        svc.reset_session("ep-1")
        assert svc.stats()["sessions"] == 0
        # sessionless requests run from a zero carry and leak no state
        svc.act(obs, timeout=120.0)
        assert svc.stats()["sessions"] == 0
        assert svc.stats()["errors"] == 0
    finally:
        svc.stop()
