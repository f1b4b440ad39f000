"""Environment factory.

Parity with the reference factory (reference: sheeprl/utils/env.py:26-231):
``make_env(cfg, seed, rank, ...)`` returns a thunk producing a fully-wrapped
``gym.Env`` whose observation space is ALWAYS a ``gym.spaces.Dict``, with the
wrapper pipeline: suite wrapper → ActionRepeat → velocity masking →
image normalization (resize / grayscale) → FrameStack → actions-as-obs →
reward-as-obs → reward clipping → TimeLimit → RecordEpisodeStatistics →
RecordVideo (rank 0, env 0 only).

TPU-first convention: images are channel-last ``(H, W, C)`` uint8 (XLA TPU
convolutions are natively NHWC); the reference uses torch's ``(C, H, W)``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import gymnasium as gym
import numpy as np
from gymnasium import spaces

from sheeprl_tpu.envs.dummy import (
    ContinuousDummyEnv,
    DiscreteDummyEnv,
    MultiDiscreteDummyEnv,
    PixelGridDummyEnv,
)
from sheeprl_tpu.envs.wrappers import (
    ActionRepeat,
    ActionsAsObservationWrapper,
    FrameStack,
    MaskVelocityWrapper,
    RewardAsObservationWrapper,
)

DUMMY_ENVS = {
    "discrete_dummy": DiscreteDummyEnv,
    "multidiscrete_dummy": MultiDiscreteDummyEnv,
    "continuous_dummy": ContinuousDummyEnv,
    "pixel_grid_dummy": PixelGridDummyEnv,
}


def get_dummy_env(env_id: str, **kwargs: Any) -> gym.Env:
    if env_id not in DUMMY_ENVS:
        raise ValueError(f"Unknown dummy env '{env_id}'; options: {list(DUMMY_ENVS)}")
    return DUMMY_ENVS[env_id](**kwargs)


def _wrapper_config(cfg: Any) -> Dict[str, Any]:
    """Normalize ``cfg.env.wrapper`` (dict, bare suite name, or the "???"
    placeholder) into a dict with a ``kind`` entry."""
    wrapper_cfg = cfg.env.get("wrapper") or {}
    if not isinstance(wrapper_cfg, dict):  # "???" placeholder or suite name
        wrapper_cfg = {"kind": str(wrapper_cfg)} if wrapper_cfg != "???" else {}
    return {"kind": "gym", **wrapper_cfg}


def _make_base_env(
    cfg: Any, seed: Optional[int], render_mode: str, rank: int = 0, vector_env_idx: int = 0
) -> gym.Env:
    env_id = cfg.env.id
    if env_id in DUMMY_ENVS:
        # wrapper kwargs pass through to the dummy constructors like every
        # other suite (episode_len, random_start, grid, ...)
        dummy_cfg = _wrapper_config(cfg)
        return get_dummy_env(
            env_id, **{k: v for k, v in dummy_cfg.items() if k not in ("kind", "id")}
        )
    wrapper_cfg = _wrapper_config(cfg)
    kind = wrapper_cfg["kind"]
    if kind == "gym":
        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return gym.make(env_id, render_mode=render_mode, **kwargs)
    if kind == "atari":
        from sheeprl_tpu.envs.atari import make_atari_env

        return make_atari_env(env_id, cfg, render_mode=render_mode)
    if kind == "dmc":
        from sheeprl_tpu.envs.dmc import DMCWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return DMCWrapper(env_id, seed=seed, **kwargs)
    if kind == "crafter":
        from sheeprl_tpu.envs.crafter import CrafterWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return CrafterWrapper(env_id, **kwargs)
    if kind == "minedojo":
        from sheeprl_tpu.envs.minedojo import MineDojoWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return MineDojoWrapper(env_id, seed=seed, **kwargs)
    if kind == "minerl":
        from sheeprl_tpu.envs.minerl import MineRLWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return MineRLWrapper(env_id, seed=seed, **kwargs)
    if kind == "diambra":
        from sheeprl_tpu.envs.diambra import DiambraWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        # each parallel env needs its own engine slot (reference:
        # sheeprl/utils/env.py:72 uses rank * num_envs + vector_env_idx)
        kwargs.setdefault("rank", rank * int(cfg.env.num_envs) + vector_env_idx)
        return DiambraWrapper(env_id, render_mode=render_mode, **kwargs)
    if kind == "super_mario_bros":
        from sheeprl_tpu.envs.super_mario_bros import SuperMarioBrosWrapper

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        return SuperMarioBrosWrapper(env_id, render_mode=render_mode, **kwargs)
    if kind == "jax":
        # pure-JAX env behind the gymnasium API: every existing loop runs
        # it unmodified; on-policy loops may bypass this path entirely and
        # fuse the rollout on device (envs/jax/anakin.py)
        from sheeprl_tpu.envs.jax.adapter import JaxToGymAdapter
        from sheeprl_tpu.envs.jax.registry import make_jax_env

        kwargs = {k: v for k, v in wrapper_cfg.items() if k not in ("kind", "id")}
        # difficulty axis: the top-level env.level override reaches the
        # adapter path too (same contract as registry.jax_env_from_cfg)
        if cfg.env.get("level") is not None:
            kwargs.setdefault("level", float(cfg.env.level))
        return JaxToGymAdapter(make_jax_env(wrapper_cfg.get("id") or env_id, **kwargs))
    raise ValueError(f"Unknown env wrapper kind '{kind}'")


class _DictObs(gym.ObservationWrapper):
    """Normalize any observation space into a Dict: vectors → 'state',
    images → 'rgb' (reference behavior: sheeprl/utils/env.py:117-159)."""

    def __init__(self, env: gym.Env):
        super().__init__(env)
        obs_space = env.observation_space
        if isinstance(obs_space, spaces.Dict):
            self._key_map = None
            self.observation_space = obs_space
        else:
            key = "rgb" if len(obs_space.shape or ()) == 3 else "state"
            self._key_map = key
            self.observation_space = spaces.Dict({key: obs_space})

    def observation(self, observation: Any) -> Dict[str, Any]:
        if self._key_map is None:
            return observation
        return {self._key_map: observation}


class _ImageTransform(gym.ObservationWrapper):
    """Resize / grayscale every cnn key to ``(screen, screen, C)`` uint8
    (reference: sheeprl/utils/env.py:161-196, rewritten channel-last)."""

    def __init__(self, env: gym.Env, cnn_keys: list, screen_size: int, grayscale: bool):
        super().__init__(env)
        import cv2  # local import: heavy

        self._cv2 = cv2
        self._cnn_keys = cnn_keys
        self._screen = screen_size
        self._gray = grayscale
        new_spaces = dict(env.observation_space.spaces)
        channels = 1 if grayscale else 3
        for k in cnn_keys:
            new_spaces[k] = spaces.Box(0, 255, (screen_size, screen_size, channels), np.uint8)
        self.observation_space = spaces.Dict(new_spaces)

    def _transform(self, img: np.ndarray) -> np.ndarray:
        cv2 = self._cv2
        img = np.asarray(img)
        if img.ndim == 2:
            img = img[..., None]
        if img.shape[0] in (1, 3) and img.shape[-1] not in (1, 3):
            img = np.transpose(img, (1, 2, 0))  # CHW → HWC
        if img.shape[:2] != (self._screen, self._screen):
            img = cv2.resize(img, (self._screen, self._screen), interpolation=cv2.INTER_AREA)
            if img.ndim == 2:
                img = img[..., None]
        if self._gray and img.shape[-1] == 3:
            img = cv2.cvtColor(img, cv2.COLOR_RGB2GRAY)[..., None]
        elif not self._gray and img.shape[-1] == 1:
            img = np.repeat(img, 3, axis=-1)
        return img.astype(np.uint8)

    def observation(self, observation: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(observation)
        for k in self._cnn_keys:
            out[k] = self._transform(observation[k])
        return out


def make_env(
    cfg: Any,
    seed: Optional[int],
    rank: int = 0,
    run_name: Optional[str] = None,
    prefix: str = "",
    vector_env_idx: int = 0,
) -> Callable[[], gym.Env]:
    """Build a thunk creating one fully-wrapped environment instance."""

    def thunk() -> gym.Env:
        if cfg.env.get("restart_on_exception", False):
            # auto-recreate the WHOLE wrapped pipeline on env crashes
            # (reference wraps every DreamerV3 thunk, dreamer_v3.py:385-400)
            from sheeprl_tpu.envs.wrappers import RestartOnException

            return RestartOnException(_build)
        return _build()

    def _build() -> gym.Env:
        capture = bool(cfg.env.capture_video) and rank == 0 and vector_env_idx == 0 and run_name is not None
        render_mode = "rgb_array" if capture else cfg.env.get("render_mode", "rgb_array")
        env = _make_base_env(cfg, seed, render_mode, rank=rank, vector_env_idx=vector_env_idx)

        # Suites that repeat actions inside their own engine (atari via
        # frame_skip, DIAMBRA via WrappersSettings.repeat_action) must not be
        # wrapped again or frames/rewards would be consumed twice
        # (reference: sheeprl/utils/env.py:76-81 excludes both).
        if cfg.env.action_repeat > 1 and _wrapper_config(cfg)["kind"] not in ("atari", "diambra"):
            env = ActionRepeat(env, cfg.env.action_repeat)
        if cfg.env.get("mask_velocities", False):
            env = MaskVelocityWrapper(env)

        env = _DictObs(env)

        cnn_keys = [
            k
            for k in env.observation_space.spaces
            if len(env.observation_space[k].shape) in (2, 3)
        ]
        if cnn_keys:
            env = _ImageTransform(env, cnn_keys, cfg.env.screen_size, cfg.env.grayscale)
        if cfg.env.frame_stack > 1 and cnn_keys:
            env = FrameStack(env, cfg.env.frame_stack, cnn_keys, cfg.env.frame_stack_dilation)

        aao = cfg.env.get("actions_as_observation", {})
        if aao and aao.get("num_stack", -1) > 0:
            env = ActionsAsObservationWrapper(env, aao["num_stack"], aao["noop"], aao.get("dilation", 1))
        if cfg.env.reward_as_observation:
            env = RewardAsObservationWrapper(env)
        if cfg.env.clip_rewards:
            env = gym.wrappers.TransformReward(env, lambda r: float(np.tanh(r)))
        if cfg.env.max_episode_steps is not None and cfg.env.max_episode_steps > 0:
            env = gym.wrappers.TimeLimit(env, cfg.env.max_episode_steps)
        env = gym.wrappers.RecordEpisodeStatistics(env)
        if capture:
            import os

            video_dir = os.path.join(run_name, prefix + "_videos" if prefix else "videos")
            env = gym.wrappers.RecordVideo(env, video_dir, disable_logger=True)

        if seed is not None:
            env.reset(seed=seed + rank * cfg.env.num_envs + vector_env_idx)
            env.action_space.seed(seed + rank * cfg.env.num_envs + vector_env_idx)

        # chaos drills: fire the env.step/env.reset injection sites — only
        # wrapped when an active fault plan targets them, so the disabled
        # path adds no wrapper (and no per-step overhead) at all.  Applied
        # after seeding (construction resets are not injection targets) and
        # INSIDE RestartOnException, so injected crashes exercise the real
        # restart path and injected hangs wedge the vector worker the
        # step-deadline watchdog guards against.
        from sheeprl_tpu.resilience.faults import active_plan

        plan = active_plan()
        if plan is not None and plan.targets("env."):
            from sheeprl_tpu.envs.wrappers import FaultInjectionEnv

            env = FaultInjectionEnv(env)
        return env

    return thunk


def episode_stats(info: Dict[str, Any]):
    """Extract finished-episode (return, length) pairs from vector-env info
    (gymnasium 1.x layout: masked dict-of-arrays under ``final_info``)."""
    out = []
    src = None
    if isinstance(info.get("final_info"), dict) and "episode" in info["final_info"]:
        src = info["final_info"]
    elif "episode" in info:
        src = info
    if src is not None:
        ep = src["episode"]
        mask = np.asarray(src.get("_episode", ep.get("_r", np.ones_like(ep["r"], bool))))
        for i in np.nonzero(mask)[0]:
            out.append((float(ep["r"][i]), int(ep["l"][i])))
    return out


def final_obs_rows(info: Dict[str, Any], env_indices: np.ndarray, obs_keys) -> Optional[Dict[str, np.ndarray]]:
    """Stack the real final observations of the given env rows from vector
    info (``final_obs`` is an object array with None for running envs)."""
    fo = info.get("final_obs")
    if fo is None:
        return None
    rows = []
    for i in env_indices:
        entry = fo[i]
        if entry is None:
            return None
        if not isinstance(entry, dict):
            return None
        rows.append(entry)
    return {k: np.stack([np.asarray(r[k]) for r in rows]) for k in obs_keys}


class StepDeadlineVectorEnv:
    """Liveness watchdog around ``AsyncVectorEnv``: a wedged env worker
    (deadlocked engine, NFS stall, injected hang) no longer deadlocks the
    run forever.

    ``RestartOnException`` (inside each worker) only catches *exceptions*; a
    worker that simply stops answering leaves ``AsyncVectorEnv.step``
    blocked with no timeout.  This wrapper drives the async pair itself —
    ``step_async`` + ``step_wait(timeout=deadline_s)`` (and the same for
    ``reset``) — and on a deadline miss tears the whole vector env down
    (``close(terminate=True)`` SIGTERMs the stuck workers), recreates it
    from the original thunks, resets, and reports the break to the train
    loop through the same ``info["restart_on_exception"]`` contract the
    per-env restart wrapper uses, so sequence replay patches its tail
    (``ReplayBuffer.repair_tail``) instead of bootstrapping across the gap.

    At most ``max_restarts`` teardowns within ``window_s`` seconds; beyond
    that the timeout propagates as ``RuntimeError`` — a persistently wedged
    fleet should fail the run, not loop silently.
    """

    def __init__(
        self,
        make_vec: Callable[[], gym.vector.VectorEnv],
        deadline_s: float,
        max_restarts: int = 3,
        window_s: float = 600.0,
    ):
        from collections import deque

        self._make_vec = make_vec
        self._deadline = float(deadline_s)
        self._max_restarts = int(max_restarts)
        self._window = float(window_s)
        self._restart_times: Any = deque()
        self._env = make_vec()

    def __getattr__(self, name: str) -> Any:
        # spaces, num_envs, call(), metadata… all delegate to the live env.
        # Private names never delegate: looking up self._env before __init__
        # finished (failed construction) must raise, not recurse.
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._env, name)

    @property
    def unwrapped(self) -> gym.vector.VectorEnv:
        return self._env

    def _spend_restart_budget(self, reason: str) -> None:
        now = time.monotonic()
        while self._restart_times and now - self._restart_times[0] > self._window:
            self._restart_times.popleft()
        if len(self._restart_times) >= self._max_restarts:
            # watchdog teardown exhausted its budget: this kills the run, so
            # leave the evidence NOW — the stall/restart event trail plus
            # this giveup — even if something swallows the raise upstream
            from sheeprl_tpu.telemetry.recorder import RECORDER

            RECORDER.record(
                "watchdog.giveup", reason=reason, restarts=len(self._restart_times)
            )
            RECORDER.dump("watchdog")
            raise RuntimeError(
                f"vector env wedged {len(self._restart_times) + 1} times within "
                f"{self._window}s ({reason}); giving up"
            )
        self._restart_times.append(now)

    def _teardown_and_recreate(self, reason: str) -> Dict[str, Any]:
        import multiprocessing as mp
        import warnings

        from sheeprl_tpu.utils.profiler import RESILIENCE_MONITOR

        # the recovery reset gets the SAME deadline as a step — a worker
        # that wedges during reset too must spend restart budget per
        # attempt and eventually propagate, not hang the watchdog itself
        while True:
            self._spend_restart_budget(reason)
            warnings.warn(
                f"vector env watchdog: {reason}; terminating workers and recreating",
                RuntimeWarning,
            )
            RESILIENCE_MONITOR.record_stall("vecenv.step")
            RESILIENCE_MONITOR.record_env_restart(getattr(self._env, "num_envs", 1))
            try:
                self._env.close(timeout=5.0, terminate=True)
            except (mp.TimeoutError, OSError, RuntimeError, EOFError):
                pass
            self._env = self._make_vec()
            try:
                self._env.reset_async()
                obs, info = self._env.reset_wait(timeout=self._deadline)
                break
            except mp.TimeoutError:
                reason = f"recovery reset exceeded the {self._deadline}s deadline"
        info = dict(info)
        # every env restarted: the whole batch of streams broke
        info["restart_on_exception"] = np.ones(self._env.num_envs, dtype=bool)
        return {"obs": obs, "info": info}

    def step(self, actions: Any):
        import multiprocessing as mp

        try:
            self._env.step_async(actions)
            return self._env.step_wait(timeout=self._deadline)
        except mp.TimeoutError:
            out = self._teardown_and_recreate(
                f"step exceeded the {self._deadline}s deadline"
            )
            n = self._env.num_envs
            return (
                out["obs"],
                np.zeros(n, dtype=np.float64),
                np.zeros(n, dtype=bool),
                np.zeros(n, dtype=bool),
                out["info"],
            )

    def reset(self, **kwargs: Any):
        import multiprocessing as mp

        try:
            self._env.reset_async(**kwargs)
            return self._env.reset_wait(timeout=self._deadline)
        except mp.TimeoutError:
            out = self._teardown_and_recreate(
                f"reset exceeded the {self._deadline}s deadline"
            )
            return out["obs"], out["info"]

    def close(self, **kwargs: Any) -> None:
        self._env.close(**kwargs)


def _span_step(envs: Any) -> Any:
    """Put the ``env.step`` span (telemetry/spans.py) around ``envs.step`` as
    the loops call it: one span per loop step over all envs, which is the
    env layer's boundary whatever steps under it (gym processes, the
    ``JaxToGymAdapter``s of a jax env).  The vector env keeps its type."""
    from sheeprl_tpu.telemetry.spans import SPANS

    step = envs.step

    def spanned_step(actions: Any):
        token = SPANS.push("env.step", phase=False)
        try:
            return step(actions)
        finally:
            SPANS.pop(token)

    envs.step = spanned_step
    return envs


def vectorize(cfg: Any, thunks: list) -> gym.vector.VectorEnv:
    """Vectorize with SAME_STEP autoreset so rollout loops observe the
    pre-1.0 gymnasium semantics the algorithms are written against
    (final observations surfaced via ``info["final_obs"]``).

    The async path is wrapped in :class:`StepDeadlineVectorEnv` when
    ``env.step_deadline_s`` > 0 (the default), so a wedged worker is
    detected and restarted instead of deadlocking the run; the sync path
    runs envs on the caller thread where a hang IS the caller hanging —
    nothing to watchdog from inside the process."""
    from gymnasium.vector import AutoresetMode

    from sheeprl_tpu.telemetry.spans import SPANS

    def make() -> gym.vector.VectorEnv:
        return gym.vector.AsyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)

    deadline = float(cfg.env.get("step_deadline_s", 0) or 0)
    with SPANS.setup_span("setup.env"):
        if cfg.env.sync_env:
            envs = gym.vector.SyncVectorEnv(thunks, autoreset_mode=AutoresetMode.SAME_STEP)
        elif deadline > 0:
            envs = StepDeadlineVectorEnv(
                make,
                deadline,
                max_restarts=int(cfg.env.get("max_vecenv_restarts", 3) or 3),
                window_s=float(cfg.env.get("vecenv_restart_window_s", 600.0) or 600.0),
            )
        else:
            envs = make()
    return _span_step(envs)
