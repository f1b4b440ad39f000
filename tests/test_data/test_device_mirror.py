"""DeviceReplay ring primitives at explicit coordinates + the mirror shim.

Migrated off the PR 9 deprecation shims (ISSUE 11 satellite): the parity
law the old ``DeviceMirror`` tests pinned — a device gather at
host-sampled ring coordinates is bit-identical to the host ring's fancy
indexing — is a property of ``DeviceReplay.write_at``/``gather_at``, and
is asserted on that API directly.  The ``attach_mirror`` /
``maybe_attach_mirror`` shims exist ONLY for external callers now; one
compat test per shim pins that they still honor the old contract (and
warn).  The old ``device_mirror`` True/False e2e equivalence runs became
vacuous when the loops stopped reading ``buffer.device_mirror`` — the live
e2e coverage of the device-resident dataflow is
``tests/test_data/test_device_replay_e2e.py`` and run_ci stage 8.
"""

import numpy as np
import pytest

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, ReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_replay import DeviceReplay


def _frame(t, e, hw=8):
    return np.full((hw, hw, 3), (t * 7 + e * 31) % 256, np.uint8)


class _HostRing:
    """Reference host ring writing the same explicit slots."""

    def __init__(self, size, n_envs, hw=8):
        self.buf = np.zeros((size, n_envs, hw, hw, 3), np.uint8)
        self.size = size

    def write(self, rows, time_pos, env_cols):
        for i, e in enumerate(env_cols):
            self.buf[np.asarray(time_pos)[:, i], e] = rows[:, i]

    def gather(self, t_idx, e_idx):
        return self.buf[np.asarray(t_idx), np.asarray(e_idx)]


# --------------------------------------------------------------------------
# write_at/gather_at parity at explicit coordinates (the mirror law)
# --------------------------------------------------------------------------

class TestRingPrimitivesParity:
    def _pair(self, size=8, n_envs=2):
        return DeviceReplay(size, n_envs), _HostRing(size, n_envs)

    def test_basic_write_gather(self):
        dev, host = self._pair(size=16)
        rng = np.random.default_rng(3)
        for t in range(10):
            rows = np.stack([[_frame(t, e)] for e in range(2)], axis=1).reshape(1, 2, 8, 8, 3)
            pos = np.full((1, 2), t % 16)
            dev.write_at("rgb", rows, pos, [0, 1])
            host.write(rows, pos, [0, 1])
        t_idx = rng.integers(0, 10, (3, 4, 2))
        e_idx = rng.integers(0, 2, (3, 4, 2))
        np.testing.assert_array_equal(
            np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host.gather(t_idx, e_idx)
        )

    def test_wraparound(self):
        dev, host = self._pair(size=8)
        rng = np.random.default_rng(4)
        for t in range(37):  # several full wraps of the size-8 ring
            rows = np.stack([[_frame(t, e)] for e in range(2)], axis=1).reshape(1, 2, 8, 8, 3)
            pos = np.full((1, 2), t % 8)
            dev.write_at("rgb", rows, pos, [0, 1])
            host.write(rows, pos, [0, 1])
        t_idx = rng.integers(0, 8, (2, 3, 4))
        e_idx = rng.integers(0, 2, (2, 3, 4))
        np.testing.assert_array_equal(
            np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host.gather(t_idx, e_idx)
        )

    def test_divergent_env_streams(self):
        # per-env write heads: one column runs ahead (the reset-row case)
        dev, host = self._pair(size=12)
        rng = np.random.default_rng(5)
        pos_per_env = [0, 0]
        for t in range(9):
            for e in range(2):
                extra = 1 if (e == 1 and t % 3 == 0) else 0
                for rep in range(1 + extra):
                    rows = _frame(t * 10 + rep, e)[None, None]
                    dev.write_at("rgb", rows, np.full((1, 1), pos_per_env[e] % 12), [e])
                    host.write(rows, np.full((1, 1), pos_per_env[e] % 12), [e])
                    pos_per_env[e] += 1
        assert pos_per_env[0] != pos_per_env[1]
        t_idx = rng.integers(0, 9, (4, 3))
        e_idx = rng.integers(0, 2, (4, 3))
        np.testing.assert_array_equal(
            np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host.gather(t_idx, e_idx)
        )

    def test_multi_key_rings(self):
        dev, host_a = self._pair(size=8)
        host_b = _HostRing(8, 2)
        for t in range(6):
            rows = np.stack([[_frame(t, e)] for e in range(2)], axis=1).reshape(1, 2, 8, 8, 3)
            pos = np.full((1, 2), t)
            dev.write_at("rgb", rows, pos, [0, 1])
            dev.write_at("next_rgb", rows + 1, pos, [0, 1])
            host_a.write(rows, pos, [0, 1])
            host_b.write(rows + 1, pos, [0, 1])
        t_idx = np.arange(6).reshape(2, 3)
        e_idx = np.zeros((2, 3), int)
        np.testing.assert_array_equal(np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host_a.gather(t_idx, e_idx))
        np.testing.assert_array_equal(np.asarray(dev.gather_at("next_rgb", t_idx, e_idx)), host_b.gather(t_idx, e_idx))


# --------------------------------------------------------------------------
# host-buffer-driven parity: the ring the SHIM used to sync, exercised
# through DeviceReplay directly via the buffers' sample-index tracking
# --------------------------------------------------------------------------

def _seq_step(t, n_envs=2, hw=8):
    rgb = np.zeros((1, n_envs, hw, hw, 3), np.uint8)
    for e in range(n_envs):
        rgb[0, e] = (t * 7 + e * 31) % 256
    return {"rgb": rgb, "rewards": np.full((1, n_envs), float(t), np.float32)}


class TestHostSampledGather:
    def test_sequential_sample_indices_gather(self):
        """Sample on the host ring, gather the SAME draw on device through
        write_at/gather_at — bit-identical pixels (no shim in the loop)."""
        np.random.seed(3)
        rb = EnvIndependentReplayBuffer(16, n_envs=2, buffer_cls=SequentialReplayBuffer)
        dev = DeviceReplay(16, 2)
        for t in range(10):
            step = _seq_step(t)
            rb.add(step)
            dev.write_at("rgb", step["rgb"], np.full((1, 2), t % 16), [0, 1])
        state = np.random.get_state()
        host = rb.sample(3, n_samples=2, sequence_length=4)
        np.random.set_state(state)
        rb.sample(3, n_samples=2, sequence_length=4, keys=("rewards",), track_indices=True)
        t_idx, e_idx = rb.last_sample_indices
        np.testing.assert_array_equal(
            np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host["rgb"]
        )

    def test_track_indices_rejects_non_sequential_sub_buffers(self):
        # uniform sub-buffers never record their drawn ring slots — the
        # flag must fail loudly, not AttributeError mid-sample
        rb = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=ReplayBuffer)
        rb.add({"obs": np.zeros((1, 2, 3), np.float32)})
        with pytest.raises(ValueError, match="track_indices"):
            rb.sample(3, track_indices=True)

    def test_uniform_sample_indices_gather(self):
        np.random.seed(11)
        rb = ReplayBuffer(16, n_envs=2)
        dev = DeviceReplay(16, 2)
        for t in range(10):
            step = _seq_step(t)
            rb.add(step)
            dev.write_at("rgb", step["rgb"], np.full((1, 2), t % 16), [0, 1])
        state = np.random.get_state()
        host = rb.sample(4, n_samples=3)
        np.random.set_state(state)
        rb.sample(4, n_samples=3, keys=("rewards",), track_indices=True)
        t_idx, e_idx = rb.last_sample_indices
        np.testing.assert_array_equal(
            np.asarray(dev.gather_at("rgb", t_idx, e_idx)), host["rgb"]
        )


# --------------------------------------------------------------------------
# shim compat: external callers of the deprecated surface keep working
# --------------------------------------------------------------------------

class TestDeprecatedShims:
    def test_attach_mirror_warns_and_keeps_contract(self):
        np.random.seed(7)
        rb = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=SequentialReplayBuffer)
        for t in range(13):  # includes a pre-attach wrap (attach-time sync)
            rb.add(_seq_step(t))
        with pytest.warns(DeprecationWarning, match="attach_mirror is deprecated"):
            rb.attach_mirror(["rgb"])
        state = np.random.get_state()
        host = rb.sample(3, n_samples=2, sequence_length=3)
        np.random.set_state(state)
        rb.sample(3, n_samples=2, sequence_length=3, keys=("rewards",))
        t_idx, e_idx = rb.last_sample_indices
        np.testing.assert_array_equal(
            np.asarray(rb.mirror.gather("rgb", t_idx, e_idx)), host["rgb"]
        )

    def test_attach_requires_sequential_sub_buffers(self):
        # rejected before the shim constructs (so no deprecation warning)
        rb = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=ReplayBuffer)
        with pytest.raises(ValueError):
            rb.attach_mirror(["rgb"])

    def test_maybe_attach_mirror_policy(self, monkeypatch):
        from sheeprl_tpu.data.buffers import maybe_attach_mirror

        class _Cfg(dict):
            __getattr__ = dict.__getitem__

        def cfg(value):
            return _Cfg(buffer=_Cfg({"device_mirror": value}))

        import gymnasium as gym

        space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (8, 8, 3), np.uint8)})
        monkeypatch.delenv("SHEEPRL_MIRROR_BUDGET_BYTES", raising=False)
        rb = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=SequentialReplayBuffer)
        # auto + cpu -> off; auto + tpu -> on; explicit False -> off
        assert not maybe_attach_mirror(rb, cfg("auto"), "cpu", space, ("rgb",))
        with pytest.warns(DeprecationWarning):
            assert maybe_attach_mirror(rb, cfg("auto"), "tpu", space, ("rgb",))
        rb2 = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=SequentialReplayBuffer)
        assert not maybe_attach_mirror(rb2, cfg(False), "tpu", space, ("rgb",))
        # budget refusal path
        monkeypatch.setenv("SHEEPRL_MIRROR_BUDGET_BYTES", "100")
        rb3 = EnvIndependentReplayBuffer(8, n_envs=2, buffer_cls=SequentialReplayBuffer)
        assert not maybe_attach_mirror(rb3, cfg(True), "tpu", space, ("rgb",))
