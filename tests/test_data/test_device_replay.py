"""DeviceReplay: the zero-copy device-resident replay contract.

Four claims, each a test family:

* **Seeded parity** — for the SAME PRNG-drawn index stream, on-device
  uniform and sequence gathers are bit-identical to the host-numpy
  ``ReplayBuffer``/``SequentialReplayBuffer`` gather at those coordinates
  (the gather path carries no law of its own).
* **Signature stability** — 50 add + fused-sample+update iterations reuse
  ONE compiled executable: cursor motion is device data, not signature.
* **Mesh sharding** — on a 2x4 ``(data, model)`` fake-device mesh the ring
  arrays carry ``PartitionSpec(None, 'data')`` and donated writes preserve
  it (the layout ``fabric.shard_batch`` gives shipped batches).
* **Spill chaos** — a stalled/raising/truncating spill tier (fault site
  ``replay.spill``) slows or degrades capacity eviction but never blocks or
  corrupts the device ring or the compiled step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.data.buffers import ReplayBuffer
from sheeprl_tpu.data.device_replay import (
    DeviceReplay,
    HostSpill,
    device_memory,
    fit_hbm_window,
    from_stored,
    fused_uniform_train,
    ring_device_bytes,
    steady_guard,
    stored_feature,
    update_chunks,
)


def _fill(cap=16, n_envs=3, steps=23, feat=4, seed=0, extra_keys=("next_obs", "rewards")):
    """Identically-filled (DeviceReplay, host ReplayBuffer) pair."""
    rng = np.random.default_rng(seed)
    dev = DeviceReplay(cap, n_envs)
    host = ReplayBuffer(cap, n_envs, obs_keys=("obs",))
    for _ in range(steps):
        data = {"obs": rng.normal(size=(1, n_envs, feat)).astype(np.float32)}
        for k in extra_keys:
            width = feat if k.startswith("next") else 1
            data[k] = rng.normal(size=(1, n_envs, width)).astype(np.float32)
        dev.add(data)
        host.add(data)
    return dev, host


# --------------------------------------------------------------------------
# seeded parity with the host-numpy sampling path
# --------------------------------------------------------------------------

class TestSeededParity:
    def test_uniform_batches_match_host_gather(self):
        dev, host = _fill()
        key = jax.random.PRNGKey(7)
        batch = dev.sample_uniform(dev.buffers, dev.cursor, key, batch_size=5, n_samples=4)
        # identical PRNG stream -> identical indices -> identical batches
        step, env = dev.uniform_indices(dev.cursor, key, 20)
        step, env = np.asarray(step), np.asarray(env)
        expected = host._gather(step, env, sample_next_obs=False)
        for k in ("obs", "next_obs", "rewards"):
            np.testing.assert_array_equal(
                np.asarray(batch[k]).reshape(20, -1), expected[k].reshape(20, -1)
            )

    def test_uniform_ring_content_matches_host_after_wrap(self):
        dev, host = _fill(cap=8, steps=37)
        for k in dev.keys():
            np.testing.assert_array_equal(np.asarray(dev.buffers[k]), host.buffer[k])

    def test_derived_next_obs_matches_successor_row(self):
        dev, host = _fill(cap=16, steps=10, extra_keys=())
        key = jax.random.PRNGKey(3)
        batch = dev.sample_uniform(
            dev.buffers, dev.cursor, key, batch_size=6, n_samples=1, derive_next=("obs",)
        )
        step, env = dev.uniform_indices(dev.cursor, key, 6, sample_next_obs=True)
        step, env = np.asarray(step), np.asarray(env)
        expected = host._gather(step, env, sample_next_obs=True)
        np.testing.assert_array_equal(
            np.asarray(batch["next_obs"]).reshape(6, -1), expected["next_obs"]
        )

    def test_uniform_never_draws_beyond_filled(self):
        dev, _ = _fill(cap=32, steps=5)
        step, _ = dev.uniform_indices(dev.cursor, jax.random.PRNGKey(0), 512)
        assert int(np.max(np.asarray(step))) < 5

    def test_sequence_batches_match_host_gather(self):
        cap, n_envs, L = 16, 2, 4
        rng = np.random.default_rng(1)
        dev = DeviceReplay(cap, n_envs)
        rows = []
        for t in range(30):  # wraps
            d = {"x": rng.normal(size=(1, n_envs, 3)).astype(np.float32)}
            rows.append(d["x"][0])
            dev.add(d)
        full_history = np.stack(rows)  # (T, E, 3)
        ring = full_history[-cap:]  # what the ring holds, in ring order:
        # ring slot s holds history step (30 - cap) + ((s - pos) % cap)
        key = jax.random.PRNGKey(9)
        total = 12
        t_idx, env = dev.sequence_indices(dev.cursor, key, total, L)
        t_idx, env = np.asarray(t_idx), np.asarray(env)
        batch = dev.sample_sequences(
            dev.buffers, dev.cursor, key, batch_size=4, sequence_length=L, n_samples=3
        )
        got = np.asarray(batch["x"]).swapaxes(1, 2).reshape(total, L, 3)
        expected = np.asarray(dev.buffers["x"])[t_idx, env[:, None]]
        np.testing.assert_array_equal(got, expected)
        # sequences are contiguous history (never cross the write head):
        pos = int(np.asarray(dev.cursor["pos"])[0])
        age = (t_idx - pos) % cap  # position in oldest->newest order
        assert np.all(np.diff(age, axis=1) == 1)
        for i in range(total):
            np.testing.assert_array_equal(
                got[i], full_history[30 - cap + age[i], env[i]]
            )

    def test_sequence_sampling_respects_partial_envs(self):
        """Envs with fewer than L steps get zero sampling mass (the host
        multinomial-eligibility law)."""
        dev = DeviceReplay(16, 2)
        for t in range(6):
            dev.add({"x": np.full((1, 1, 1), t, np.float32)}, indices=[0])
        dev.add({"x": np.full((1, 1, 1), 99.0, np.float32)}, indices=[1])  # env 1: 1 step
        _, env = dev.sequence_indices(dev.cursor, jax.random.PRNGKey(0), 256, 4)
        assert set(np.asarray(env).tolist()) == {0}


# --------------------------------------------------------------------------
# pixel leaves: stored lane-dense, seen by every caller in their own shape
# --------------------------------------------------------------------------

#: a feature that is a lane multiple as it is, and one that needs padding
PIXEL_FEATS = [pytest.param((8, 8, 2), id="lane_multiple"), pytest.param((5, 5, 3), id="padded")]


def _fill_pixels(feat, cap=8, n_envs=3, steps=5, seed=0):
    """(DeviceReplay, host ReplayBuffer) given the same pixel and flag rows."""
    rng = np.random.default_rng(seed)
    dev = DeviceReplay(cap, n_envs)
    host = ReplayBuffer(cap, n_envs, obs_keys=("rgb",))
    for _ in range(steps):
        data = {
            "rgb": rng.integers(0, 256, size=(1, n_envs) + feat, dtype=np.uint8),
            "rewards": rng.normal(size=(1, n_envs, 1)).astype(np.float32),
        }
        dev.add(data)
        host.add(data)
    return dev, host


@pytest.mark.parametrize("feat", PIXEL_FEATS)
@pytest.mark.parametrize("steps", [5, 21], ids=["partial", "wrapped"])
class TestPixelLeafParity:
    def test_store_is_one_padded_axis_and_vectors_are_untouched(self, feat, steps):
        dev, host = _fill_pixels(feat, steps=steps)
        flat = int(np.prod(feat))
        assert stored_feature(feat) == (-(-flat // 128) * 128,)
        assert dev.buffers["rgb"].shape == (8, 3) + stored_feature(feat)
        assert dev.buffers["rewards"].shape == (8, 3, 1)
        assert dev.leaf_specs["rgb"] == (feat, np.uint8)
        stored = np.asarray(dev.buffers["rgb"])
        np.testing.assert_array_equal(from_stored(stored, feat), host.buffer["rgb"])
        assert not stored[..., flat:].any()  # the padding stays zero
        assert dev.sampled_bytes_per_update(4, 2) == 4 * 2 * (flat + 4)

    def test_gather_at_matches_host_rows(self, feat, steps):
        dev, host = _fill_pixels(feat, steps=steps)
        rng = np.random.default_rng(1)
        t_idx = rng.integers(0, min(steps, 8), size=(6, 2))
        e_idx = rng.integers(0, 3, size=(6, 2))
        got = np.asarray(dev.gather_at("rgb", t_idx, e_idx))
        assert got.shape == (6, 2) + feat
        np.testing.assert_array_equal(got, host.buffer["rgb"][t_idx, e_idx])

    def test_sample_sequences_matches_host_rows(self, feat, steps):
        dev, host = _fill_pixels(feat, steps=steps)
        key = jax.random.PRNGKey(5)
        t_idx, env = (np.asarray(x) for x in dev.sequence_indices(dev.cursor, key, 6, 3))
        batch = jax.jit(
            lambda b, c, k: dev.sample_sequences(b, c, k, batch_size=2, sequence_length=3, n_samples=3)
        )(dev.buffers, dev.cursor, key)
        assert batch["rgb"].shape == (3, 3, 2) + feat and batch["rgb"].dtype == jnp.uint8
        for k in ("rgb", "rewards"):
            got = np.asarray(batch[k]).swapaxes(1, 2).reshape(6, 3, *host.buffer[k].shape[2:])
            np.testing.assert_array_equal(got, host.buffer[k][t_idx, env[:, None]])

    def test_sample_uniform_with_derived_next_matches_host_rows(self, feat, steps):
        dev, host = _fill_pixels(feat, steps=steps)
        key = jax.random.PRNGKey(3)
        batch = jax.jit(
            lambda b, c, k: dev.sample_uniform(b, c, k, batch_size=3, n_samples=2, derive_next=("rgb",))
        )(dev.buffers, dev.cursor, key)
        step, env = (np.asarray(x) for x in dev.uniform_indices(dev.cursor, key, 6, sample_next_obs=True))
        expected = host._gather(step, env, sample_next_obs=True)
        for k in ("rgb", "next_rgb"):
            assert batch[k].shape == (2, 3) + feat
            np.testing.assert_array_equal(np.asarray(batch[k]).reshape(6, *feat), expected[k].reshape(6, *feat))

    def test_checkpoint_holds_feature_shapes_and_the_parents_layout_loads(self, feat, steps):
        dev, host = _fill_pixels(feat, steps=steps)
        state = dev.state_dict()
        assert state["buffer"]["rgb"].shape == (8, 3) + feat
        np.testing.assert_array_equal(state["buffer"]["rgb"], host.buffer["rgb"])
        # what the (W, E, *feat) store wrote before pixel leaves were flattened: the host ring's own arrays
        parents = {
            "buffer": {k: np.array(host.buffer[k]) for k in ("rgb", "rewards")},
            "pos": np.array(dev._pos_h), "filled": np.array(dev._filled_h),
            "buffer_size": 8, "n_envs": 3, "device_replay": {"from_spill": False},
        }
        for saved in (state, parents):
            fresh = DeviceReplay(8, 3).load_state_dict(saved)
            assert fresh.buffers["rgb"].shape == dev.buffers["rgb"].shape
            np.testing.assert_array_equal(np.asarray(fresh.buffers["rgb"]), np.asarray(dev.buffers["rgb"]))
            np.testing.assert_array_equal(np.asarray(fresh.cursor["pos"]), np.asarray(dev.cursor["pos"]))


def test_a_key_keeps_the_feature_shape_it_was_declared_with():
    dev = DeviceReplay(8, 2)
    dev.add({"rgb": np.zeros((1, 2, 8, 8, 3), np.uint8)})
    with pytest.raises(ValueError, match="holds rows of shape"):
        dev.add({"rgb": np.zeros((1, 2, 8, 24), np.uint8)})  # the same 192 bytes, another feature


# --------------------------------------------------------------------------
# compile-once: no signature churn from cursors
# --------------------------------------------------------------------------

class TestSignatureStability:
    def test_fused_sample_update_reuses_one_executable_over_50_iters(self):
        from sheeprl_tpu.parallel.fabric import Fabric

        fabric = Fabric(devices=1, accelerator="cpu")
        rb = DeviceReplay(32, 2, mesh=fabric.mesh, data_axis=fabric.data_axis)

        def train_phase(p, o, batch, k, counter):
            loss = jnp.mean(batch["obs"]) + jnp.mean(batch["rewards"])
            return p + loss * 1e-3, o, loss

        fused = fused_uniform_train(
            fabric, train_phase, rb, batch_size=4,
            prep=lambda b: {"obs": b["obs"], "rewards": b["rewards"][..., 0]},
            name="test.fused",
        )
        params = jax.device_put(jnp.zeros(3))
        opt = jax.device_put(jnp.zeros(3))
        counter = jax.device_put(np.int32(0))
        key = jax.random.PRNGKey(0)
        rng = np.random.default_rng(0)
        for i in range(50):
            rb.add({
                "obs": rng.normal(size=(1, 2, 4)).astype(np.float32),
                "rewards": rng.normal(size=(1, 2, 1)).astype(np.float32),
            })
            key, tk = jax.random.split(key)
            # steady guard armed past the first window: the fused dispatch
            # must perform ZERO implicit H2D (cursors/counter are device data)
            with steady_guard(i >= 1):
                params, opt, counter, _ = fused(
                    params, opt, rb.buffers, rb.cursor, tk, counter, n_samples=2
                )
        assert fused.cache_size() == 1
        assert int(counter) == 100

    def test_update_chunks_power_of_two_decomposition(self):
        assert update_chunks(1) == [1]
        assert update_chunks(7) == [4, 2, 1]
        assert update_chunks(8) == [8]
        assert update_chunks(1300, cap=64) == [64] * 20 + [16, 4]
        # chunk set stays small: a burst mints few distinct signatures
        assert len(set(update_chunks(1023))) == 10


# --------------------------------------------------------------------------
# mesh sharding (2x4 fake-device mesh from conftest's 8 virtual devices)
# --------------------------------------------------------------------------

class TestMeshSharding:
    @pytest.fixture()
    def mesh_fabric(self):
        from sheeprl_tpu.parallel.fabric import Fabric

        return Fabric(devices=8, accelerator="cpu", mesh_shape={"data": 2, "model": 4})

    def test_ring_carries_data_axis_partition_spec(self, mesh_fabric):
        rb = DeviceReplay(16, 4, mesh=mesh_fabric.mesh, data_axis=mesh_fabric.data_axis)
        rng = np.random.default_rng(0)
        for _ in range(5):
            rb.add({"obs": rng.normal(size=(1, 4, 6)).astype(np.float32)})
        assert rb.buffers["obs"].sharding.spec == P(None, "data")
        # donated in-place writes preserve the placement
        rb.add({"obs": rng.normal(size=(1, 4, 6)).astype(np.float32)})
        assert rb.buffers["obs"].sharding.spec == P(None, "data")

    def test_indivisible_env_count_replicates(self, mesh_fabric):
        from sheeprl_tpu.parallel.sharding import replay_partition_spec

        assert replay_partition_spec(4, mesh_fabric.mesh) == P(None, "data")
        assert replay_partition_spec(3, mesh_fabric.mesh) == P()

    def test_sampling_on_mesh_produces_constrained_batches(self, mesh_fabric):
        rb = DeviceReplay(16, 4, mesh=mesh_fabric.mesh, data_axis=mesh_fabric.data_axis)
        rng = np.random.default_rng(0)
        for _ in range(8):
            rb.add({"obs": rng.normal(size=(1, 4, 6)).astype(np.float32)})
        key = jax.random.PRNGKey(0)
        b = rb.sample_uniform(rb.buffers, rb.cursor, key, batch_size=4, n_samples=2)
        assert b["obs"].shape == (2, 4, 6)
        s = rb.sample_sequences(rb.buffers, rb.cursor, key, 4, 3, n_samples=2)
        assert s["obs"].shape == (2, 3, 4, 6)

    @pytest.mark.parametrize("feat", PIXEL_FEATS)
    def test_pixel_leaf_keeps_the_env_axis_on_data_and_samples_its_own_shape(self, mesh_fabric, feat):
        rb = DeviceReplay(16, 4, mesh=mesh_fabric.mesh, data_axis=mesh_fabric.data_axis)
        rng = np.random.default_rng(0)
        rows = [rng.integers(0, 256, size=(1, 4) + feat, dtype=np.uint8) for _ in range(8)]
        for r in rows:
            rb.add({"rgb": r})
        assert rb.buffers["rgb"].shape == (16, 4) + stored_feature(feat)
        assert rb.buffers["rgb"].sharding.spec == P(None, "data")  # one env column a data shard
        key = jax.random.PRNGKey(0)
        s = jax.jit(lambda b, c, k: rb.sample_sequences(b, c, k, 4, 3, n_samples=2))(rb.buffers, rb.cursor, key)
        assert s["rgb"].shape == (2, 3, 4) + feat
        assert s["rgb"].sharding.spec == P(None, None, "data")
        t_idx, env = (np.asarray(x) for x in rb.sequence_indices(rb.cursor, key, 8, 3))
        got = np.asarray(s["rgb"]).swapaxes(1, 2).reshape(8, 3, *feat)
        np.testing.assert_array_equal(got, np.concatenate(rows)[t_idx, env[:, None]])


# --------------------------------------------------------------------------
# spill tier + replay.spill chaos
# --------------------------------------------------------------------------

class TestSpillTier:
    def test_spill_shadows_full_capacity(self):
        spill = HostSpill(32, 2)
        rb = DeviceReplay(8, 2, spill=spill)
        for t in range(20):
            rb.add({"x": np.full((1, 2, 1), t, np.float32)})
        assert spill.flush(30.0)
        # HBM window holds the last 8 steps; the spill ring all 20
        assert len(spill.buffer) == 20
        np.testing.assert_array_equal(
            spill.buffer.buffer["x"][:20, 0, 0], np.arange(20, dtype=np.float32)
        )
        # checkpoint prefers the (bigger) spill history
        state = rb.state_dict()
        assert state["device_replay"]["from_spill"]
        spill.close()

    def test_spill_checkpoint_roundtrips_into_a_fresh_device_ring(self):
        """A spill-tier checkpoint must restore under the SAME config that
        wrote it: full shadow history reloaded, HBM window rebuilt at the
        saved cursors (the preemption auto-resume path)."""
        spill = HostSpill(32, 2)
        rb = DeviceReplay(8, 2, spill=spill)
        rng = np.random.default_rng(3)
        for _ in range(20):  # wraps the window
            rb.add({
                "x": rng.normal(size=(1, 2, 3)).astype(np.float32),
                "truncated": np.zeros((1, 2, 1), np.float32),
            })
        state = rb.state_dict()
        assert state["device_replay"]["from_spill"]
        # the spill snapshot carries the tail-consistency patch too: the
        # write-head row must not look continuable on resume
        tail = (int(state["pos"]) - 1) % int(state["buffer_size"])
        assert np.all(np.asarray(state["buffer"]["truncated"])[tail] == 1.0)
        # ...applied to the snapshot COPY, not the live spill ring
        assert np.all(np.asarray(spill.buffer["truncated"])[tail] == 0.0)
        spill2 = HostSpill(32, 2)
        rb2 = DeviceReplay(8, 2, spill=spill2).load_state_dict(state)
        np.testing.assert_array_equal(
            np.asarray(rb2.buffers["x"]), np.asarray(rb.buffers["x"])
        )
        assert np.array_equal(rb2._pos_h, rb._pos_h)
        assert np.array_equal(rb2._filled_h, rb._filled_h)
        # the restored spill holds the FULL 20-step history, not just the window
        spill2.flush(30.0)
        assert len(spill2.buffer) == 20
        spill.close(); spill2.close()

    def test_sequential_spill_tracks_per_env_subset_adds(self):
        """The dreamer add path appends reset rows to done envs only
        (``indices=``): the sequential spill must keep per-env streams
        aligned (EnvIndependent sub-buffers, not a shared cursor)."""
        spill = HostSpill(64, 2, sequential=True)
        rb = DeviceReplay(16, 2, spill=spill)
        for t in range(10):
            rb.add({"x": np.full((1, 2, 1), t, np.float32)})
            if t % 3 == 0:  # extra reset row for env 1 only
                rb.add({"x": np.full((1, 1, 1), 100 + t, np.float32)}, indices=[1])
        spill.flush(30.0)
        # per-env spill streams match the device ring's per-env history
        for env in range(2):
            n = int(rb._filled_h[env])
            dev_rows = np.asarray(rb.buffers["x"])[:n, env, 0]
            sub = spill.buffer.buffer[env]
            np.testing.assert_array_equal(np.asarray(sub["x"])[:n, 0, 0], dev_rows)
        assert len(spill.buffer.buffer[0]) != len(spill.buffer.buffer[1])
        # and the checkpoint written from this spill restores cleanly
        state = rb.state_dict()
        rb2 = DeviceReplay(16, 2, spill=HostSpill(64, 2, sequential=True)).load_state_dict(state)
        np.testing.assert_array_equal(
            np.asarray(rb2.buffers["x"])[:, :, 0] * (np.arange(16)[:, None] < rb._filled_h[None, :]),
            np.asarray(rb.buffers["x"])[:, :, 0] * (np.arange(16)[:, None] < rb._filled_h[None, :]),
        )
        rb2.spill.close(); spill.close()

    @pytest.mark.parametrize(
        "capacity,budget,requested,want",
        [
            (10_000, 1000 * 4, None, (500, True)),  # over budget: shrink + spill
            (100, 1000 * 4, None, (100, False)),  # fits whole
            (10_000, 1000 * 4, 200, (200, True)),  # explicit window honored
            (10_000, 1000 * 4, 800, (500, True)),  # ...but still budget-capped
        ],
    )
    def test_fit_hbm_window(self, capacity, budget, requested, want):
        """The window is the longest ring whose COMPILED allocation fits the
        byte budget (on CPU the layout does not pad: 2 envs x 1 f32 = 8 B/step)."""
        specs = {"x": ((1,), np.float32)}
        assert fit_hbm_window(capacity, 2, specs, budget, requested=requested) == want

    def test_fit_hbm_window_raises_when_min_window_cannot_fit(self):
        with pytest.raises(RuntimeError, match="replay ring needs"):
            fit_hbm_window(10_000, 2, {"x": ((1,), np.float32)}, 64, min_window=16)

    def test_ring_device_bytes_asks_the_compiled_allocation(self):
        from sheeprl_tpu.parallel.fabric import Fabric
        from sheeprl_tpu.parallel.sharding import replay_sharding

        specs = {"rgb": ((8, 8, 3), np.uint8), "r": ((1,), np.float32)}
        # the pixel leaf as it is stored: one axis, 192 padded to 256 lanes
        raw = 32 * 4 * (stored_feature((8, 8, 3))[0] + 4)
        assert raw == 32 * 4 * (256 + 4)
        assert ring_device_bytes(specs, 32, 4) == raw
        # sharded over the env axis: each device holds its share only
        fabric = Fabric(devices=4, accelerator="cpu")
        sh = replay_sharding(fabric.mesh, 4, fabric.data_axis)
        assert ring_device_bytes(specs, 32, 4, sh) == raw // 4

    def test_device_memory_is_what_the_device_reports(self):
        class Dev:
            def __init__(self, platform, stats):
                self.platform, self._stats = platform, stats

            def memory_stats(self):
                return self._stats

        assert device_memory(Dev("tpu", {"bytes_limit": 100, "bytes_in_use": 7})) == (100, 7)
        # a CPU device reports nothing: its memory is the host's
        limit, in_use = device_memory(jax.devices("cpu")[0])
        assert limit > 2**28 and in_use == 0
        # any other device without a limit is an error, never an assumed size
        for stats in (None, {}, {"bytes_in_use": 5}):
            with pytest.raises(RuntimeError, match="reports no memory limit"):
                device_memory(Dev("tpu", stats))

    def _plan(self, spec):
        from sheeprl_tpu.resilience.faults import FaultPlan, install_plan

        install_plan(FaultPlan.from_specs([spec], seed=1))

    def teardown_method(self):
        from sheeprl_tpu.resilience.faults import clear_plan

        clear_plan()

    def test_stalled_spill_never_blocks_the_compiled_step(self):
        """A latency fault in the spill worker slows eviction bookkeeping
        (the queue backs up) but append + on-device sampling proceed — the
        train step never touches the spill tier."""
        import time

        self._plan({"site": "replay.spill", "kind": "latency", "every": 1, "seconds": 0.2})
        spill = HostSpill(64, 2)
        rb = DeviceReplay(8, 2, spill=spill)
        t0 = time.perf_counter()
        for t in range(10):
            rb.add({"x": np.full((1, 2, 1), t, np.float32)})
        append_wall = time.perf_counter() - t0
        # 10 x 0.2 s of injected latency runs on the WORKER thread
        assert append_wall < 1.0, f"appends blocked on the spill tier ({append_wall:.2f}s)"
        batch = rb.sample_uniform(rb.buffers, rb.cursor, jax.random.PRNGKey(0), 4, 1)
        assert batch["x"].shape == (1, 4, 1)
        assert spill.flush(30.0) and not spill.degraded
        assert len(spill.buffer) == 10
        spill.close()

    def test_raising_spill_degrades_without_corrupting_the_ring(self):
        self._plan({"site": "replay.spill", "kind": "raise", "at": 2})
        spill = HostSpill(64, 2)
        rb = DeviceReplay(8, 2, spill=spill)
        with pytest.warns(RuntimeWarning, match="spill tier degraded"):
            for t in range(5):
                rb.add({"x": np.full((1, 2, 1), t, np.float32)})
            spill.flush(30.0)
        assert spill.degraded
        # the device ring is intact: every appended step is present
        ring = np.asarray(rb.buffers["x"])[:5, 0, 0]
        np.testing.assert_array_equal(ring, np.arange(5, dtype=np.float32))
        # and checkpointing falls back to the (authoritative) device ring
        assert not rb.state_dict()["device_replay"]["from_spill"]
        spill.close()

    def test_truncate_fault_halves_spilled_rows_only(self):
        self._plan({"site": "replay.spill", "kind": "truncate", "at": 1})
        spill = HostSpill(64, 1)
        rb = DeviceReplay(16, 1, spill=spill)
        rb.add({"x": np.arange(8, dtype=np.float32).reshape(8, 1, 1)})
        spill.flush(30.0)
        assert len(spill.buffer) == 4  # tail-halved by the fault
        # device ring holds the full 8 rows regardless
        np.testing.assert_array_equal(
            np.asarray(rb.buffers["x"])[:8, 0, 0], np.arange(8, dtype=np.float32)
        )
        spill.close()


# --------------------------------------------------------------------------
# host-buffer API parity pieces the loops rely on
# --------------------------------------------------------------------------

class TestLoopContract:
    def test_repair_tail_marks_truncation(self):
        rb = DeviceReplay(8, 2)
        for t in range(3):
            rb.add({
                "x": np.full((1, 2, 1), t, np.float32),
                "truncated": np.zeros((1, 2, 1), np.float32),
                "terminated": np.zeros((1, 2, 1), np.float32),
            })
        rb.repair_tail(1)
        assert np.asarray(rb.buffers["truncated"])[2, 1, 0] == 1.0
        assert np.asarray(rb.buffers["truncated"])[2, 0, 0] == 0.0

    def test_state_dict_roundtrip(self):
        rb = DeviceReplay(8, 2)
        rng = np.random.default_rng(0)
        for t in range(11):
            rb.add({"x": rng.normal(size=(1, 2, 3)).astype(np.float32)})
        state = rb.state_dict()
        rb2 = DeviceReplay(8, 2).load_state_dict(state)
        np.testing.assert_array_equal(np.asarray(rb2.buffers["x"]), np.asarray(rb.buffers["x"]))
        assert np.array_equal(rb2._pos_h, rb._pos_h)
        assert np.array_equal(
            np.asarray(rb2.cursor["filled"]), np.asarray(rb.cursor["filled"])
        )

    def test_state_dict_applies_tail_consistency_patch(self):
        """The checkpoint callback's _consistent_tail contract: the write-head
        row must not look continuable on resume (no next_* rows stored) —
        only truncated/dones are forced; terminated is a value-semantics
        flag and must survive untouched (a real episode end at the head
        would otherwise bootstrap across a true terminal after resume)."""
        rb = DeviceReplay(8, 1)
        for t in range(3):
            rb.add({
                "x": np.full((1, 1, 1), t, np.float32),
                "truncated": np.zeros((1, 1, 1), np.float32),
                "terminated": np.full((1, 1, 1), float(t == 2), np.float32),
            })
        state = rb.state_dict()
        assert state["buffer"]["truncated"][2, 0, 0] == 1.0
        assert state["buffer"]["terminated"][2, 0, 0] == 1.0  # preserved
        # the live ring is NOT patched (the patch lands on the host copy)
        assert np.asarray(rb.buffers["truncated"])[2, 0, 0] == 0.0

    def test_eligibility_shadows(self):
        rb = DeviceReplay(16, 2)
        assert not rb.can_sample()
        rb.add({"x": np.zeros((1, 2, 1), np.float32)})
        assert rb.can_sample() and not rb.can_sample_sequences(4)
        for _ in range(5):
            rb.add({"x": np.zeros((1, 2, 1), np.float32)})
        assert rb.can_sample_sequences(4)
        assert len(rb) == 12
