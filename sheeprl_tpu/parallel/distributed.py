"""First-class ``jax.distributed`` init: the pod fabric's front door.

Three ways a process learns it is one rank of a pod, resolved in order by
:func:`ensure_distributed` (called by ``build_fabric`` BEFORE anything
touches the JAX backend — ``jax.distributed.initialize`` must run before
the first ``jax.devices()`` call or the process binds a single-host
backend and can never join the pod):

1. **Fake-DCN cell** — ``SHEEPRL_DCN_PROCESS_ID`` is set (by the
   launcher below, the pod supervisor, or a test harness).  The process
   forces the CPU platform + gloo collectives and joins the coordinator
   at ``SHEEPRL_DCN_COORD``.  This is the CI substrate: N real OS
   processes, one CPU device each, a real coordination service — every
   cross-host code path exercised on one machine.
2. **Fake-DCN launcher** — ``SHEEPRL_FAKE_DCN=N`` with no process id:
   this process re-executes itself N times as cells (fresh coordinator
   port, rank-prefixed output) and exits with the worst child return
   code, so ``SHEEPRL_FAKE_DCN=2 python -m sheeprl_tpu ...`` "just
   works".
3. **Real pods** — explicit ``fabric.distributed.coordinator_address``
   (+ ``num_processes``/``process_id``), or env-var autodetect
   (``fabric.distributed.enabled=auto``, the default): on Cloud TPU pod
   slices ``jax.distributed.initialize()`` discovers everything from the
   metadata server, so a recognised TPU-pod environment initializes with
   no arguments.

The module also owns the pod's *liveness* primitive: a
:class:`PeerWatchdog` heart-beating through the jax.distributed KV store
(the same client ``Fabric._coordination_client`` exposes) so a rank whose
peer dies stops within ``heartbeat_grace_s`` instead of sitting out a
collective timeout — "no rank trains past a dead peer".
"""

from __future__ import annotations

import base64
import os
import socket
import subprocess
import sys
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "PeerLost",
    "PeerWatchdog",
    "distributed_cfg",
    "ensure_distributed",
    "free_port",
    "is_fake_dcn",
    "launch_fake_dcn",
    "process_index",
    "process_count",
    "rank_zero_warn",
]

# env-var protocol between the fake-DCN launcher and its cells (also what
# the pod supervisor and the subprocess tests set by hand)
ENV_FAKE = "SHEEPRL_FAKE_DCN"
ENV_PROCESS_ID = "SHEEPRL_DCN_PROCESS_ID"
ENV_NUM_PROCESSES = "SHEEPRL_DCN_NUM_PROCESSES"
ENV_COORD = "SHEEPRL_DCN_COORD"

def _tpu_pod_env() -> bool:
    """True when the environment describes a MULTI-host TPU pod (worth an
    argument-less ``jax.distributed.initialize()``).  A one-host TPU VM also
    sets ``TPU_WORKER_HOSTNAMES`` (to ``localhost``) and a worker id; there
    the argument-less call has no cluster to find and may wait on a metadata
    server that does not exist."""
    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if h.strip()]
    return len(hosts) > 1 or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ


class PeerLost(RuntimeError):
    """A pod peer stopped heart-beating (crashed host / SIGKILLed rank)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def process_index() -> int:
    """This process's pod rank WITHOUT touching the JAX backend (safe to
    call before/without ``jax.distributed.initialize``)."""
    try:
        from jax._src import distributed

        # global_state.process_id DEFAULTS to 0 before initialize — only
        # trust it once the coordination client actually exists, else a
        # rank-3 cell warning before init would claim to be rank 0
        if distributed.global_state.client is not None:
            return int(distributed.global_state.process_id or 0)
    except Exception:
        pass
    return int(os.environ.get(ENV_PROCESS_ID, 0) or 0)


def process_count() -> int:
    """Pod size without touching the backend (1 when not distributed)."""
    try:
        from jax._src import distributed

        if distributed.global_state.client is not None:
            return int(distributed.global_state.num_processes or 1)
    except Exception:
        pass
    return int(os.environ.get(ENV_NUM_PROCESSES, 1) or 1)


def is_fake_dcn() -> bool:
    return bool(os.environ.get(ENV_FAKE))


_WARNED_KEYS: set = set()


def rank_zero_warn(message: str, category: type = RuntimeWarning, *, key: Optional[str] = None) -> None:
    """``warnings.warn`` for *global* facts: emitted by rank 0 only (an
    N-host pod should log one copy of a pod-wide warning, not N), and at
    most once per ``key`` per process (defaults to the message text)."""
    if process_index() != 0:
        return
    k = key or message
    if k in _WARNED_KEYS:
        return
    _WARNED_KEYS.add(k)
    warnings.warn(message, category, stacklevel=3)


def distributed_cfg(cfg: Any) -> Dict[str, Any]:
    """The ``fabric.distributed`` group as a plain dict ({} when absent)."""
    try:
        fab = cfg.get("fabric") if hasattr(cfg, "get") else None
        group = fab.get("distributed") if fab is not None else None
        return dict(group) if group else {}
    except Exception:
        return {}


def _force_cpu_gloo() -> None:
    """Fake-DCN cells collectivize over gloo on the host platform — set
    BEFORE the first backend touch."""
    import jax

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def ensure_distributed(cfg: Any) -> str:
    """Resolve and perform distributed init for this process.

    Returns ``"cell"`` (joined a fake-DCN pod), ``"pod"`` (joined a real
    pod), or ``"single"``.  Raises :class:`SystemExit` from launcher mode
    after the fake-DCN children finish.  Idempotent: a second call after a
    successful init is a no-op.
    """
    import jax

    try:
        from jax._src import distributed as _dist

        if _dist.global_state.client is not None:  # already initialized
            return "cell" if is_fake_dcn() else "pod"
    except Exception:
        pass

    dcfg = distributed_cfg(cfg)

    # 1) fake-DCN cell: the launcher/supervisor/test set the full protocol
    if os.environ.get(ENV_PROCESS_ID) is not None:
        coord = os.environ.get(ENV_COORD)
        num = int(os.environ.get(ENV_NUM_PROCESSES, 0) or 0)
        pid = int(os.environ[ENV_PROCESS_ID])
        if not coord or num <= 0:
            raise RuntimeError(
                f"{ENV_PROCESS_ID} is set but {ENV_COORD}/{ENV_NUM_PROCESSES} are not — "
                "fake-DCN cells need the full coordinator protocol"
            )
        _force_cpu_gloo()
        init_timeout = int(dcfg.get("init_timeout_s", 120) or 120)
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=num,
            process_id=pid,
            initialization_timeout=init_timeout,
        )
        return "cell"

    # 2) fake-DCN launcher: re-exec this command as N cells
    fake = int(os.environ.get(ENV_FAKE, 0) or 0)
    if fake > 1:
        raise SystemExit(launch_fake_dcn(fake))

    # 3) real pods: explicit coordinator, or TPU-pod env autodetect
    coord = dcfg.get("coordinator_address")
    if coord:
        kwargs: Dict[str, Any] = {"coordinator_address": str(coord)}
        if dcfg.get("num_processes") is not None:
            kwargs["num_processes"] = int(dcfg["num_processes"])
        if dcfg.get("process_id") is not None:
            kwargs["process_id"] = int(dcfg["process_id"])
        if dcfg.get("init_timeout_s"):
            kwargs["initialization_timeout"] = int(dcfg["init_timeout_s"])
        jax.distributed.initialize(**kwargs)
        return "pod"

    enabled = dcfg.get("enabled", "auto")
    if enabled is True or (
        str(enabled) == "auto" and _tpu_pod_env()
    ):
        try:
            jax.distributed.initialize()
            return "pod"
        except Exception as e:  # autodetect is best-effort; explicit is not
            if enabled is True:
                raise
            rank_zero_warn(
                f"fabric.distributed autodetect found pod env vars but "
                f"jax.distributed.initialize() failed ({e}); continuing single-process",
                key="distributed.autodetect",
            )
    return "single"


def launch_fake_dcn(
    num: int,
    argv: Optional[List[str]] = None,
    *,
    env: Optional[Dict[str, str]] = None,
    prefix_output: bool = True,
) -> int:
    """Spawn ``num`` copies of this command as fake-DCN cells and wait.

    Each child gets the full cell protocol (coordinator on a fresh local
    port, its process id, one forced CPU device) and a rank-prefixed
    stdout relay.  Returns the worst child return code.
    """
    argv = list(sys.argv if argv is None else argv)
    if argv and argv[0].endswith("__main__.py"):
        # a `python -m pkg` launch shows up as .../pkg/__main__.py in argv —
        # re-exec'ing that path directly would put pkg/ (not its parent) on
        # sys.path and the cells would fail to import the package
        spec = getattr(sys.modules.get("__main__"), "__spec__", None)
        name = getattr(spec, "name", None)
        if name:
            mod = name[: -len(".__main__")] if name.endswith(".__main__") else name
            argv = ["-m", mod] + argv[1:]
    coord = f"127.0.0.1:{free_port()}"
    base_env = dict(os.environ if env is None else env)
    base_env.pop(ENV_PROCESS_ID, None)
    xla_flags = base_env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        base_env["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=1").strip()
    children: List[subprocess.Popen] = []
    relays: List[threading.Thread] = []
    for rank in range(num):
        child_env = dict(base_env)
        child_env.update(
            {
                ENV_FAKE: str(num),
                ENV_PROCESS_ID: str(rank),
                ENV_NUM_PROCESSES: str(num),
                ENV_COORD: coord,
                "JAX_PLATFORMS": "cpu",
            }
        )
        child = subprocess.Popen(
            [sys.executable] + argv[:],
            env=child_env,
            stdout=subprocess.PIPE if prefix_output else None,
            stderr=subprocess.STDOUT if prefix_output else None,
            text=prefix_output,
        )
        children.append(child)
        if prefix_output:

            def _relay(c=child, r=rank):
                for line in c.stdout:  # type: ignore[union-attr]
                    sys.stdout.write(f"[dcn:{r}] {line}")
                    sys.stdout.flush()

            t = threading.Thread(target=_relay, name=f"dcn-relay[{rank}]", daemon=True)
            t.start()
            relays.append(t)
    rcs = [c.wait() for c in children]
    for t in relays:
        t.join(timeout=5)
    return max(abs(rc) for rc in rcs)


#: one lock for EVERY coordination-service call in this process: jax's KV
#: client is not thread-safe — concurrent calls from two threads (a
#: watchdog beating while the main thread publishes the front address)
#: segfault the process under the gloo CPU backend.
_KV_LOCK = threading.RLock()


class _SafeKV:
    """Thread-safe face of jax's coordination-service client.

    Two hazards observed under the gloo CPU backend (not re-checked on
    jaxlib 0.9; the serialization below is cheap and stays):
    concurrent client calls from two threads can segfault the process,
    and ``blocking_key_value_get_bytes`` segfaults whenever it SUCCEEDS
    off the main thread (the bytes-return binding) — exactly the
    PeerWatchdog's watcher-thread usage.  So every call serializes under
    :data:`_KV_LOCK`, byte payloads ride the STRING key-value API
    base64-armored (the string bindings are thread-clean), and the long
    blocking get is re-implemented as short lock-slices (~200 ms per
    slice, lock released between): an actor cell waiting minutes for the
    learner front's address must not starve the watchdog's heartbeats —
    silence past ``grace_s`` reads as a dead host.
    """

    _SLICE_MS = 200

    def __init__(self, client: Any) -> None:
        self._client = client

    def key_value_set_bytes(self, key: str, value: bytes) -> None:
        armored = base64.b64encode(bytes(value)).decode("ascii")
        with _KV_LOCK:
            self._client.key_value_set(key, armored)

    def blocking_key_value_get_bytes(self, key: str, timeout_ms: int) -> bytes:
        deadline = time.monotonic() + max(int(timeout_ms), 1) / 1000.0
        while True:
            remaining_ms = int((deadline - time.monotonic()) * 1000)
            slice_ms = max(1, min(self._SLICE_MS, remaining_ms))
            with _KV_LOCK:
                try:
                    raw = self._client.blocking_key_value_get(  # graftlint: disable=prng-key-reuse
                        key, slice_ms
                    )
                except Exception:
                    if remaining_ms <= self._SLICE_MS:
                        raise
                else:
                    return base64.b64decode(raw)
            time.sleep(0.01)

    def __getattr__(self, name: str) -> Any:
        attr = getattr(self._client, name)
        if not callable(attr):
            return attr

        def locked(*args: Any, **kwargs: Any) -> Any:
            with _KV_LOCK:
                return attr(*args, **kwargs)

        return locked


def _kv_client() -> Any:
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("PeerWatchdog needs jax.distributed to be initialized")
    return _SafeKV(client)


class PeerWatchdog:
    """KV-store heartbeats between pod ranks.

    Every rank writes ``sheeprl_tpu/hb/<rank>/<seq>`` each
    ``heartbeat_s``; a watcher thread blocks on each peer's next sequence
    key with a ``grace_s`` timeout.  A peer that stops writing (crashed
    process, SIGKILLed host) times the watcher out → ``on_peer_lost(rank)``
    fires exactly once and — unless the callback raised SystemExit itself —
    a delayed hard-exit timer guarantees the process cannot keep training
    past the dead peer even if the main thread is wedged inside a
    collective.

    ``stop()`` before teardown: a clean shutdown writes a goodbye marker
    so surviving watchers treat the silence as departure, not death.
    """

    _PREFIX = "sheeprl_tpu/hb"
    _GOODBYE = b"__goodbye__"

    def __init__(
        self,
        rank: int,
        world: int,
        *,
        heartbeat_s: float = 1.0,
        grace_s: float = 15.0,
        on_peer_lost: Optional[Callable[[int], None]] = None,
        hard_exit_after_s: float = 10.0,
        exit_code: int = 75,  # EX_TEMPFAIL: the supervisor restarts the pod
        client: Any = None,
    ) -> None:
        self.rank = int(rank)
        self.world = int(world)
        self.heartbeat_s = float(heartbeat_s)
        self.grace_s = float(grace_s)
        self.on_peer_lost = on_peer_lost
        self.hard_exit_after_s = float(hard_exit_after_s)
        self.exit_code = int(exit_code)
        self._client = client or _kv_client()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._lost_lock = threading.Lock()
        self.lost_peer: Optional[int] = None

    # -- key schema -----------------------------------------------------------
    def _key(self, rank: int, seq: int) -> str:
        return f"{self._PREFIX}/{rank}/{seq}"

    # -- beat side ------------------------------------------------------------
    def _beat_loop(self) -> None:
        seq = 0
        while not self._stop.wait(self.heartbeat_s if seq else 0.0):
            try:
                self._client.key_value_set_bytes(self._key(self.rank, seq), b"%d" % seq)
                if seq >= 20:  # bound KV growth; watchers resync within the window
                    self._client.key_value_delete(self._key(self.rank, seq - 20))
            except Exception:
                return  # coordinator gone: the watcher side decides
            seq += 1
        try:  # clean departure: silence after a goodbye is not a death
            self._client.key_value_set_bytes(self._key(self.rank, seq), self._GOODBYE)
        except Exception:
            pass

    # -- watch side -----------------------------------------------------------
    def _get(self, key: str, timeout_ms: int) -> Optional[bytes]:
        try:
            return self._client.blocking_key_value_get_bytes(key, timeout_ms)
        except Exception:
            return None

    def _watch_peer(self, peer: int) -> None:
        seq = 0
        grace_ms = max(int(self.grace_s * 1000), 1000)
        while not self._stop.is_set():
            val = self._get(self._key(peer, seq), grace_ms)
            if self._stop.is_set():
                return
            if val is not None:
                if val == self._GOODBYE:
                    return
                seq += 1
                continue
            # missed seq: resync forward inside the retention window before
            # declaring death (a slow watcher must not kill a healthy pod)
            for ahead in range(1, 21):
                val = self._get(self._key(peer, seq + ahead), 50)
                if val is not None:
                    seq += ahead + (0 if val == self._GOODBYE else 1)
                    if val == self._GOODBYE:
                        return
                    break
            else:
                self._declare_lost(peer)
                return

    def _declare_lost(self, peer: int) -> None:
        with self._lost_lock:
            if self.lost_peer is not None or self._stop.is_set():
                return
            self.lost_peer = peer
        if self.hard_exit_after_s > 0:
            t = threading.Timer(self.hard_exit_after_s, os._exit, args=(self.exit_code,))
            t.daemon = True
            t.start()
        if self.on_peer_lost is not None:
            try:
                self.on_peer_lost(peer)
            except Exception:
                pass

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "PeerWatchdog":
        beat = threading.Thread(target=self._beat_loop, name="dcn.heartbeat", daemon=True)
        beat.start()
        self._threads.append(beat)
        for peer in range(self.world):
            if peer == self.rank:
                continue
            w = threading.Thread(
                target=self._watch_peer, args=(peer,), name=f"dcn.watch[{peer}]", daemon=True
            )
            w.start()
            self._threads.append(w)
        return self

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
