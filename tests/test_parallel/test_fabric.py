import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel.fabric import Fabric, Precision, get_single_device_fabric


def test_precision_policies():
    p = Precision.from_string("bf16-mixed")
    assert p.param_dtype == jnp.float32 and p.compute_dtype == jnp.bfloat16
    assert Precision.from_string("32-true").compute_dtype == jnp.float32
    assert Precision.from_string("bf16-true").param_dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        Precision.from_string("fp16-mixed")


def test_mesh_and_sharding():
    fab = Fabric(devices=8, accelerator="cpu")
    assert fab.world_size == 8
    x = fab.shard_batch(np.zeros((16, 4), np.float32))
    assert "data" in str(x.sharding.spec)
    y = fab.replicate(np.zeros((3,)))
    assert y.sharding.is_fully_replicated


def test_mesh_shape_extra_axes():
    # {data: -1, model: 2} → 4x2 mesh; model-axis sharding available
    fab = Fabric(devices=8, accelerator="cpu", mesh_shape={"data": -1, "model": 2})
    assert dict(fab.mesh.shape) == {"data": 4, "model": 2}
    w = jax.device_put(np.zeros((8, 6), np.float32), fab.sharding(None, "model"))
    assert w.sharding.spec == jax.sharding.PartitionSpec(None, "model")
    # a matmul with model-sharded weights executes under jit
    x = fab.shard_batch(np.ones((8, 8), np.float32))
    out = jax.jit(lambda a, b: a @ b)(x, w)
    assert out.shape == (8, 6)


def test_too_many_devices_raises():
    with pytest.raises(ValueError):
        Fabric(devices=64, accelerator="cpu")


def test_single_device_fabric():
    fab = Fabric(devices=8, accelerator="cpu")
    single = get_single_device_fabric(fab)
    assert single.world_size == 1
    assert single.device == fab.device


def test_to_host_never_aliases():
    fab = Fabric(devices=1, accelerator="cpu")
    x = fab.replicate(jnp.ones((4,)))
    host_copy = fab.to_host(x)
    assert host_copy.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()


def test_local_world_size_single_process():
    fab = Fabric(devices=4, accelerator="cpu")
    # single-process: every mesh device is local
    assert fab.local_world_size == fab.world_size == 4


def test_shard_batch_multihost_path(monkeypatch):
    # Force the process_count()>1 branch: host_local_array_to_global_array is
    # the sanctioned multi-host assembly path and must produce the same
    # mesh-sharded result as device_put does single-process.
    fab = Fabric(devices=4, accelerator="cpu")
    monkeypatch.setattr(Fabric, "num_processes", property(lambda self: 2))
    x = fab.shard_batch(np.arange(32, dtype=np.float32).reshape(8, 4))
    assert "data" in str(x.sharding.spec)
    np.testing.assert_array_equal(np.asarray(x).reshape(8, 4)[:, 0], np.arange(0, 32, 4))


def test_player_sync_deferred_semantics():
    from sheeprl_tpu.parallel.fabric import PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {"deferred_sync": True, "sync_every": 1, "device": "host"}}})
    psync = PlayerSync(fab, cfg, extract=lambda p: p["actor"])
    p0 = {"actor": jnp.zeros(2)}
    player = psync.init(p0)
    assert psync.staleness == 0
    # dispatch window 1: deferred -> player unchanged, refresh pending
    p1 = {"actor": jnp.ones(2)}
    player = psync.after_dispatch(p1, player_params=player)
    assert float(np.asarray(player)[0]) == 0.0
    # the player now acts on init weights while window-1 weights are
    # pending: one window of (visible) staleness
    assert psync.staleness == 1
    # window 2 start: the pending params land
    player = psync.before_dispatch(player)
    assert float(np.asarray(player)[0]) == 1.0
    assert psync.staleness == 0
    assert psync.metrics()["Player/param_staleness_max"] == 1.0
    # nothing pending: no-op
    assert psync.before_dispatch(player) is player


def test_player_sync_immediate_and_cadence():
    from sheeprl_tpu.parallel.fabric import PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {"deferred_sync": False, "sync_every": 2, "device": "host"}}})
    psync = PlayerSync(fab, cfg, extract=lambda p: p["actor"])
    player = psync.init({"actor": jnp.zeros(2)})
    # first completed training window: off-cadence (1 % 2), skipped entirely
    player = psync.after_dispatch({"actor": jnp.ones(2)}, player_params=player)
    assert float(np.asarray(player)[0]) == 0.0
    assert psync.staleness == 1
    # second window: on-cadence, immediate copy
    player = psync.after_dispatch({"actor": jnp.ones(2)}, player_params=player)
    assert float(np.asarray(player)[0]) == 1.0
    assert psync.staleness == 0
    # the immediate-sync staleness bound is sync_every (the off-cadence
    # window before each refresh) — the metric proves it never exceeded it
    assert psync.staleness_max <= psync.sync_every


def test_player_sync_cadence_counts_training_windows_not_updates():
    """The cadence gate must key on COMPLETED TRAINING WINDOWS: with a
    fractional replay_ratio the env-loop update counter fires training on a
    fixed parity, and an update-based gate could miss every training update
    (player stuck on init weights — r2 review finding)."""
    from sheeprl_tpu.parallel.fabric import PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {"deferred_sync": False, "sync_every": 2, "device": "host"}}})
    psync = PlayerSync(fab, cfg, extract=lambda p: p)
    player = psync.init(jnp.zeros(2))
    # training fires on odd env updates only (replay_ratio 0.5): the sync
    # must still happen on every 2nd *training* window
    synced = 0
    for window in range(1, 7):
        player = psync.after_dispatch(jnp.full(2, float(window)), player_params=player)
        if float(np.asarray(player)[0]) == float(window):
            synced += 1
    assert synced == 3  # windows 2, 4, 6


def test_player_sync_staleness_bound_deferred_cadence():
    """ISSUE 12 satellite: the deferred-sync staleness is now observable
    and must respect its bound — at most ``sync_every`` windows behind
    (the pending refresh lands one ``before_dispatch`` later) over a long
    window stream, with the running max reported as a metric."""
    from sheeprl_tpu.parallel.fabric import PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    sync_every = 3
    cfg = dotdict({"algo": {"player": {"deferred_sync": True, "sync_every": sync_every, "device": "host"}}})
    psync = PlayerSync(fab, cfg, extract=lambda p: p)
    player = psync.init(jnp.zeros(2))
    for window in range(1, 20):
        player = psync.before_dispatch(player)
        assert psync.staleness <= sync_every, (window, psync.staleness)
        player = psync.after_dispatch(jnp.full(2, float(window)), player_params=player)
        assert psync.staleness <= sync_every, (window, psync.staleness)
    m = psync.metrics()
    assert m["Player/param_staleness_max"] <= sync_every
    # the bound is tight: the cadence really does let the player lag
    assert m["Player/param_staleness_max"] >= sync_every - 1


def test_player_device_selection():
    from unittest import mock

    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    # on a CPU fabric host_device == device, so a wrong branch would be
    # invisible; pin host_device to a sentinel to assert the branch taken
    sentinel = object()
    with mock.patch.object(type(fab), "host_device", new_callable=mock.PropertyMock, return_value=sentinel):
        assert fab.player_device(dotdict({"algo": {}})) is sentinel
        assert (
            fab.player_device(dotdict({"algo": {"player": {"device": "accelerator"}}}))
            is fab.device
        )
    with pytest.raises(ValueError):
        fab.player_device(dotdict({"algo": {"player": {"device": "gpu"}}}))


@pytest.mark.parametrize(
    "asked, mib, on_accelerator",
    [
        ("auto", 1, False),  # SAC's actor: stays on the host
        ("auto", 64, True),  # DV3-S: beside the train state
        (None, 64, True),  # a config without the key is `auto`
        ("host", 64, False),
        ("accelerator", 1, True),
    ],
)
def test_player_placement_follows_the_bytes_a_refresh_pulls(asked, mib, on_accelerator):
    from unittest import mock

    from sheeprl_tpu.parallel.fabric import PLAYER_PULL_BYTES, PlayerSync, tree_bytes
    from sheeprl_tpu.telemetry.recorder import RECORDER
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {} if asked is None else {"device": asked}}})
    # shapes are enough to decide: nothing of this size is allocated
    params = {
        "actor": {"w": jax.ShapeDtypeStruct((mib, 2**18), jnp.float32), "b": jax.ShapeDtypeStruct((7,), jnp.bfloat16)},
        "critic": jax.ShapeDtypeStruct((2**28,), jnp.float32),  # never pulled, never counted
    }
    pulled = mib * 2**20 + 14
    assert tree_bytes(params["actor"]) == pulled and (pulled > PLAYER_PULL_BYTES) == (mib == 64)
    sentinel = mock.Mock(platform="cpu")
    RECORDER.clear()
    with mock.patch.object(type(fab), "host_device", new_callable=mock.PropertyMock, return_value=sentinel):
        psync = PlayerSync(fab, cfg, extract=lambda p: p["actor"], params=params)
        assert psync.device is (fab.device if on_accelerator else sentinel)
        # without a tree (the on-policy and decoupled loops) only an explicit value leaves the host
        assert fab.player_device(cfg) is (fab.device if asked == "accelerator" else sentinel)
    (event,) = [e for e in RECORDER.snapshot() if e["kind"] == "player.placement"]
    assert event["tree_bytes"] == pulled and event["threshold_bytes"] == PLAYER_PULL_BYTES
    assert event["asked"] == (asked or "auto") and event["device"] == str(psync.device)


def test_same_device_refresh_is_one_executable_and_a_real_copy():
    """A player beside the train state: the refresh is one dispatch that writes into the player's
    own buffers (no output is allocated), and what it returns outlives the donation of its source
    (the train phase donates ``params``; under deferred sync the player acts on window N-1's
    weights while window N's dispatch donates them)."""
    from unittest import mock

    from sheeprl_tpu.parallel import fabric as fabric_mod
    from sheeprl_tpu.parallel.fabric import PlayerSync
    from sheeprl_tpu.utils.structured import dotdict

    fab = Fabric(devices=1, accelerator="cpu")
    cfg = dotdict({"algo": {"player": {"device": "accelerator"}}})
    params = fab.replicate(
        {"actor": {f"layer_{i}": {"w": jnp.full((8, 8), float(i)), "b": jnp.arange(8.0)} for i in range(20)},
         "log_alpha": jnp.array(0.5)}  # weak-typed, as SAC's is
    )
    psync = PlayerSync(fab, cfg, extract=lambda p: p, params=params)
    step = jax.jit(lambda t: jax.tree.map(lambda x: x + 1, t), donate_argnums=0)
    with mock.patch.object(fabric_mod, "_copy_tree", wraps=fabric_mod._copy_tree) as fresh, \
            mock.patch.object(fabric_mod, "_copy_tree_into", wraps=fabric_mod._copy_tree_into) as in_place, \
            mock.patch.object(type(jnp.ones(1)), "copy", side_effect=AssertionError("a copy per leaf")):
        first = psync.init(params)
        held = [x.unsafe_buffer_pointer() for x in jax.tree.leaves(first)]
        params = step(params)  # window 1 trains: every weight moves by one
        player = psync.after_dispatch(params, first)  # deferred: pending
        assert player is first
        player = psync.before_dispatch(player)  # the refresh
        assert (fresh.call_count, in_place.call_count) == (1, 1)
    assert all(x.is_deleted() for x in jax.tree.leaves(first))  # handed over: the caller rebinds
    assert [x.unsafe_buffer_pointer() for x in jax.tree.leaves(player)] == held
    sources = jax.tree.leaves(params)
    for got, src in zip(jax.tree.leaves(player), sources):
        assert got.unsafe_buffer_pointer() != src.unsafe_buffer_pointer()
        assert got.weak_type == src.weak_type and got.sharding == src.sharding
    params = step(params)  # window 2 donates what the player was copied from
    assert all(x.is_deleted() for x in sources)
    assert float(player["actor"]["layer_7"]["w"][0, 0]) == 8.0 and float(player["log_alpha"]) == 1.5
    assert float(params["actor"]["layer_7"]["w"][0, 0]) == 9.0
    # a tree of another structure is no place to write into: a fresh copy, and it is left alone
    other = fab.replicate({"w": jnp.zeros(3)})
    again = fab.copy_to(params, fab.device, into=other)
    assert float(again["log_alpha"]) == 2.5 and not other["w"].is_deleted()


def test_host_collectives_single_process():
    fab = Fabric(devices=2, accelerator="cpu")
    assert fab.broadcast_object({"a": 1}) == {"a": 1}
    assert fab.all_gather_object("x") == ["x"]
    fab.barrier()  # no-op single process


def test_seed_everything_rank_offsets_host_rng_only():
    """Host RNG (replay sampling, random prefill) must differ per rank, while
    the returned jax key (agent init + train-dispatch stream) must be
    IDENTICAL on every process — replicated global-program inputs have to
    agree across ranks (r2 review finding: rank-identical seeding made
    multi-host DP collect the same data num_processes times)."""
    from unittest import mock

    fab = Fabric(devices=1, accelerator="cpu")
    draws, keys = [], []
    for rank in (0, 1):
        with mock.patch("jax.process_index", return_value=rank):
            keys.append(np.asarray(fab.seed_everything(42)))
            draws.append(np.random.random(4))
    assert np.array_equal(keys[0], keys[1])  # shared jax stream
    assert not np.allclose(draws[0], draws[1])  # per-rank host RNG


def test_env_sharding_plan():
    fab = Fabric(devices=2, accelerator="cpu")
    sharded, global_envs = fab.env_sharding_plan(4, "PPO")
    assert sharded and global_envs == 4  # single-process: no inflation
    sharded, global_envs = fab.env_sharding_plan(3, "PPO")
    assert not sharded and global_envs == 3  # falls back to replication
    # multi-host: indivisible env counts must fail fast, BEFORE any rollout
    from unittest import mock

    with mock.patch("jax.process_count", return_value=2):
        with pytest.raises(ValueError, match="divisible"):
            fab.env_sharding_plan(3, "PPO")


_CACHE_PROBE = """
import glob, jax, jax.numpy as jnp
from sheeprl_tpu.config.compose import compose
from sheeprl_tpu.parallel.fabric import build_fabric
build_fabric(compose([
    "env=dummy", "env.id=discrete_dummy", "algo=ppo", "algo.total_steps=1",
    "algo.per_rank_batch_size=1", "fabric=tpu", "fabric.accelerator=cpu", "fabric.devices=1",
]))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: (x @ x.T).sum() + 41)(jnp.ones((64, 64))).block_until_ready()
d = jax.config.jax_compilation_cache_dir
print("CACHE_DIR=" + str(d), "ENTRIES=" + str(len(glob.glob(str(d) + "/*"))))
"""


def _cache_probe(env_dir):
    """Build a ``fabric=tpu`` runtime in a FRESH process (the cache location
    is decided once per process) and report where its compile cache went."""
    import os
    import subprocess
    import sys

    from sheeprl_tpu.parallel.fabric import COMPILE_CACHE_DIR

    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], env=env, capture_output=True, text=True,
        timeout=300, cwd=os.path.dirname(COMPILE_CACHE_DIR),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("CACHE_DIR="))
    cache_dir, entries = line.split(" ENTRIES=")
    return cache_dir[len("CACHE_DIR="):], int(entries)


def test_compile_cache_env_var_wins_over_fabric_tpu(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache directory
    in code: a ``fabric=tpu`` run keeps JAX's own setting and writes there."""
    cache_dir, entries = _cache_probe(tmp_path)
    assert cache_dir == str(tmp_path)
    assert entries > 0, "no cache entries written where the environment said"


def test_compile_cache_default_is_fixed_in_checkout():
    """Unset, every process uses the same directory inside the checkout — no
    temp dir, uid, pid or time enters the path."""
    from sheeprl_tpu.parallel.fabric import COMPILE_CACHE_DIR

    first, _ = _cache_probe(None)
    second, _ = _cache_probe(None)
    import os

    import sheeprl_tpu

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(sheeprl_tpu.__file__)))
    assert first == second == COMPILE_CACHE_DIR == os.path.join(checkout, ".jax_cache")


def test_explicit_accelerator_without_device_raises():
    """``fabric.accelerator=tpu`` on a process that sees no chip is an error
    naming what JAX does see — never a silent run on the CPU."""
    with pytest.raises(RuntimeError, match=r"no 'tpu' device.*platforms visible: \['cpu'\]"):
        Fabric(devices=1, accelerator="tpu")


def test_packed_copy_bit_identical():
    """_packed_copy (the single-transfer cross-platform player pull) must
    return the same values/shapes/dtypes as per-leaf device_put."""
    import numpy as np
    from sheeprl_tpu.parallel.fabric import _packed_copy

    rng = np.random.default_rng(0)
    leaves = [
        jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(7,)).astype(np.float32)),
        jnp.asarray(rng.integers(0, 255, (2, 2, 3)).astype(np.uint8)),
        jnp.asarray(rng.normal(size=()).astype(np.float32)),
        jnp.asarray(np.zeros((0, 5), np.float32)),  # empty leaf
    ]
    dev = jax.devices()[0]
    got = _packed_copy(leaves, dev)
    assert len(got) == len(leaves)
    for g, want in zip(got, leaves):
        assert g.dtype == want.dtype and g.shape == want.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))
        assert set(g.devices()) == {dev}


def test_packed_copy_preserves_weak_type():
    from sheeprl_tpu.parallel.fabric import _packed_copy

    leaves = [
        jnp.asarray([1.0, 2.0]),          # strong f32
        jnp.asarray([3.0]),               # strong f32
        jnp.array(0.5),                   # WEAK f32 scalar (log-alpha style)
    ]
    assert leaves[2].weak_type
    got = _packed_copy(leaves, jax.devices()[0])
    assert got[2].weak_type, "packed copy must not strip weak_type"
    assert not got[0].weak_type


def test_copy_to_survives_source_donation_on_same_platform_mesh():
    """The player-refresh pull must be a REAL copy even when the mesh and
    the player device share a platform: jax.device_put of a replicated
    multi-device array onto one of its own devices can be a zero-copy
    alias (jax 0.4.37 CPU), and the train step DONATES the source params —
    an aliased player copy would die mid-rollout with 'buffer has been
    deleted or donated'.  (Cross-platform TPU→host pulls always
    materialize, which is why real-chip runs never saw this.)"""
    from sheeprl_tpu.parallel.fabric import Fabric

    fab = Fabric(devices=8, accelerator="cpu", mesh_shape={"data": 2, "model": 4})
    params = fab.shard_params(
        {"kernel": jnp.ones((16, 8)), "bias": jnp.arange(4.0)}
    )
    host_copy = fab.copy_to(params, fab.host_device)
    jax.block_until_ready(host_copy)
    for leaf in jax.tree.leaves(params):
        leaf.delete()  # what donation does to the source tree
    for leaf in jax.tree.leaves(host_copy):
        np.asarray(leaf)  # must still be readable
    np.testing.assert_array_equal(np.asarray(host_copy["bias"]), np.arange(4.0))
