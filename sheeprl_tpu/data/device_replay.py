"""Zero-copy device-resident replay: sharded HBM dataflow with on-device sampling.

The steady-state dataflow gap this closes (ROADMAP item 4; MindSpeed RL,
arXiv:2507.19017; Podracer/Anakin, arXiv:2104.06272): every algorithm used
to sample replay on the host with numpy and ship a fresh ``(U, ..., B, *)``
batch H2D on every update window, and the ``DeviceMirror`` pixel path was
per-device and probe-gated rather than mesh-sharded.  :class:`DeviceReplay`
makes HBM the home of replay:

* **Storage** is one pytree of device arrays ``(capacity, n_envs, *feat)``,
  sharded over the mesh ``data`` axis along the env dimension
  (:func:`sheeprl_tpu.parallel.sharding.replay_sharding`) so the ring's
  layout matches what ``fabric.shard_batch`` would give a shipped batch.
  A feature of more than one axis (pixels) is stored lane-dense, as ONE
  axis padded to a multiple of 128 (:func:`stored_feature`): the device then
  indexes the ring in place, where a last axis of 3 made it re-lay the whole
  ring around every write and every gather.  Callers never see that shape.
* **Writes are donated in-place**: the actor path appends host rows with one
  explicit ``device_put`` per key plus a jitted ``buffer.at[slots].set(rows)``
  whose ring argument is donated — no HBM reallocation, no 2x spike.
* **Sampling is compiled into the update step**: :meth:`sample_uniform` /
  :meth:`sample_sequences` are pure jit-traceable functions over
  ``(buffers, cursor, key)``; :func:`fused_uniform_train` /
  :func:`fused_sequence_train` fold index generation + gather + the algo's
  existing train phase into ONE ``fabric.compile`` AOT executable.  In steady
  state the update dispatch performs **zero host-to-device transfers** — a
  contract ``steady_guard`` can enforce with ``jax.transfer_guard``.
* **Capacity beyond the HBM window spills to the host asynchronously**
  (:class:`HostSpill`, the ``checkpoint/writer.py`` background-thread
  pattern): appends enqueue host rows to a full-capacity shadow ring
  (optionally memmapped) without ever blocking the compiled step; a stalled
  spill tier (chaos site ``replay.spill``) slows eviction bookkeeping only.

Cursors (``pos``/``filled`` per env) live on device as ``int32`` data, so 50
windows of sample+update reuse ONE executable — cursor motion is values, not
signatures (asserted by ``tests/test_data/test_device_replay.py``).
"""

from __future__ import annotations

import contextlib
import math
import os
import queue
import threading
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

Arrays = Dict[str, np.ndarray]


# --------------------------------------------------------------------------
# config resolution
# --------------------------------------------------------------------------

def resolve_device_replay(cfg: Any, fabric_accelerator: str) -> bool:
    """One policy for every algo's ``buffer.device`` handling: ``auto`` means
    on iff training on a real accelerator (on CPU the "device ring" would be
    a host-RAM duplicate of the host ring — same RAM, none of the H2D win);
    True/False force it (tests force True on CPU to exercise the path)."""
    mode = cfg.buffer.get("device", "auto")
    if isinstance(mode, str) and mode.lower() == "auto":
        return fabric_accelerator != "cpu"
    return bool(mode)


#: ring row layout: key -> (per-step feature shape, dtype)
LeafSpecs = Dict[str, Tuple[Tuple[int, ...], Any]]

#: elements in a TPU lane row: a stored pixel feature is a multiple of it
LANES = 128


def stored_feature(feat: Sequence[int]) -> Tuple[int, ...]:
    """The shape the ring stores a per-step feature in.

    One axis (vectors, actions, flags): as it is.  More (pixels): flattened
    to one axis and padded to the next multiple of :data:`LANES`.  A TPU lays
    ``u8[W, E, 64, 64, 3]`` out W-minor, so an index on the leading axes needs
    the whole array in another layout (two ring-sized copies a write, one a
    gather); ``u8[W, E, 12288]`` it lays out row-major and indexes in place
    (XLA's memory analysis for a v5e: no temporaries, 1.00 device bytes per
    raw byte).  A flat axis that is no lane multiple (84*84*4 = 28224) falls
    back to the W-minor layout, hence the padding (28288, 0.2%)."""
    feat = tuple(int(d) for d in feat)
    if len(feat) < 2:
        return feat
    return (-(-math.prod(feat) // LANES) * LANES,)


def to_stored(rows: np.ndarray) -> np.ndarray:
    """Host rows ``(T, K, *feat)`` as the ring stores them, zero-padded."""
    feat = rows.shape[2:]
    stored = stored_feature(feat)
    if stored == feat:
        return rows
    flat = rows.reshape(rows.shape[:2] + (-1,))
    pad = stored[0] - flat.shape[-1]
    return np.pad(flat, ((0, 0), (0, 0), (0, pad))) if pad else flat


def from_stored(x: Any, feat: Sequence[int]) -> Any:
    """``(..., *stored_feature(feat))`` back to ``(..., *feat)``: the inverse of
    :func:`to_stored` on any leading axes, for a numpy array (a checkpoint) or
    a traced one (a gathered batch: the reshape touches the batch, never the
    ring)."""
    feat = tuple(int(d) for d in feat)
    if len(feat) < 2:
        return x
    size = math.prod(feat)
    if size != x.shape[-1]:
        x = x[..., :size]
    return x.reshape(x.shape[:-1] + feat)


def _zeros_program(shape: Tuple[int, ...], dtype: Any, sharding: Any) -> Any:
    """THE ring allocation program: zeros born directly in their final
    placement (never staged whole on device 0 and re-laid).  ``_ensure``
    runs it; :func:`ring_device_bytes` asks its compiled form for the bytes."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def ring_device_bytes(
    leaf_specs: LeafSpecs, window: int, n_envs: int, sharding: Any = None
) -> float:
    """REAL per-device bytes of a ``(window, n_envs, *stored_feature(feat))``
    ring: the output size of the compiled allocation.  ``nbytes`` of the
    abstract shape is not enough — the device layout may pad, and by how much
    depends on the whole shape, so the question is asked at the size that
    will be allocated."""
    import jax

    total = 0
    for feat, dtype in leaf_specs.values():
        shape = (int(window), int(n_envs)) + stored_feature(feat)
        try:
            compiled = _zeros_program(shape, dtype, sharding).lower().compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            return math.inf  # the compiler refuses it outright: larger than the device
        total += int(compiled.memory_analysis().output_size_in_bytes)
    return total


def device_memory(device: Any) -> Tuple[int, int]:
    """``(bytes_limit, bytes_in_use)`` as the device itself reports them.

    A CPU device reports nothing; its arrays live in host RAM, so its limit
    is the machine's physical memory (the forced-on CPU test path).  Any
    other device that reports no limit is an error: the ring is never sized
    against an assumed capacity."""
    stats = device.memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))
    if device.platform == "cpu":
        return int(os.sysconf("SC_PHYS_PAGES")) * int(os.sysconf("SC_PAGE_SIZE")), 0
    raise RuntimeError(
        f"{device} reports no memory limit (memory_stats()={stats!r}); the "
        "device replay ring cannot be sized — run with buffer.device=False"
    )


def program_extra_bytes(compiled: Any) -> int:
    """Device bytes a compiled program needs BEYOND its resident arguments
    (XLA's own memory analysis): temporaries, outputs that alias no donated
    input, and its code."""
    m = compiled.memory_analysis()
    return int(
        m.temp_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.generated_code_size_in_bytes
    )


def fit_hbm_window(
    capacity: int,
    n_envs: int,
    leaf_specs: LeafSpecs,
    budget_bytes: int,
    sharding: Any = None,
    requested: Optional[int] = None,
    min_window: int = 1,
) -> Tuple[int, bool]:
    """``(hbm_window_steps, spill_needed)``: the longest per-env ring, at most
    the requested window (``buffer.hbm_window``, default the full capacity),
    whose REAL device bytes (:func:`ring_device_bytes`) fit ``budget_bytes``
    per device.  Anything beyond the window pages to the host spill tier.
    The storage layout is whatever the device gives the ring's stored shape
    (:func:`stored_feature`) — this only measures it."""
    window = int(capacity) if requested is None else min(int(requested), int(capacity))
    need = ring_device_bytes(leaf_specs, window, n_envs, sharding)
    while need > budget_bytes and window > min_window:
        # padding makes the real bytes non-linear in the window: rescale
        # (halve while the compiler refuses the allocation outright), then
        # ask the compiled allocation again
        scale = budget_bytes / need if math.isfinite(need) else 0.5
        window = max(int(min_window), min(window - 1, int(window * scale)))
        need = ring_device_bytes(leaf_specs, window, n_envs, sharding)
    if need > budget_bytes:
        raise RuntimeError(
            f"a {window}-step replay ring needs {need} device bytes but only "
            f"{int(budget_bytes)} are left beside the train program; shrink the "
            "model/batch or run with buffer.device=False"
        )
    return window, window < int(capacity)


def build_device_replay(
    fabric: Any,
    cfg: Any,
    capacity: int,
    n_envs: int,
    leaf_specs: LeafSpecs,
    make_fused: Callable[["DeviceReplay"], Any],
    train_state: Tuple[Any, ...],
    first_window: int,
    batch_bytes: float,
    sequential: bool = False,
    memmap_dir: Optional[Union[str, os.PathLike]] = None,
    min_window: int = 1,
) -> Tuple["DeviceReplay", Any]:
    """Size, build and return ``(ring, fused train program)`` for one loop.

    ``make_fused(ring)`` builds the loop's :func:`fused_uniform_train` /
    :func:`fused_sequence_train` program; ``train_state`` is what it takes ahead of
    the ring (``(params, opt_state)``, plus the health state when guarded),
    ``first_window`` the updates the loop's first train window will owe and
    ``batch_bytes`` one update's gathered bytes — together they name the
    largest dispatch the run makes (:func:`update_chunks`).

    The ring gets what the device has left: its own reported limit, less
    what is already resident (params, optimizer state), less what XLA's
    memory analysis says the programs that touch the ring need on top of
    their arguments.  Nothing about that need is assumed:

    * the fused program is AOT-compiled at that largest dispatch against a
      probe ring that takes an eighth of the free memory;
    * the ring's own gather and donated-scatter programs are compiled at the
      same size (:meth:`DeviceReplay.access_extra_bytes`).  Where the device
      cannot index a leaf in place it re-lays the WHOLE leaf into temporaries
      (a v5e did, for pixel leaves, until they were stored lane-dense:
      :func:`stored_feature`), and that need — with the train program's share
      that is the gather's — grows with the ring; the write may be in flight
      beside a train dispatch, so both count.  With every leaf indexed in
      place both read near zero and the line below prints about 1.0.

    ``need(ring) = ring * (1 + (read + write) / probe_ring) + (train - read)``
    then gives the longest window that fits.  When that is the probe's own
    window (every ring under an eighth of the memory) the probe's executable
    reaches the first real dispatch through the persistent compilation cache.
    """
    from sheeprl_tpu.parallel.sharding import replay_sharding

    limit, in_use = device_memory(fabric.mesh.devices.flat[0])
    free = limit - in_use
    sharding = replay_sharding(fabric.mesh, n_envs, fabric.data_axis)
    requested = cfg.buffer.get("hbm_window")

    def make(window: int) -> Tuple["DeviceReplay", Any]:
        ring = DeviceReplay(window, n_envs, mesh=fabric.mesh, data_axis=fabric.data_axis)
        return ring, make_fused(ring)

    def fit(budget: float) -> Tuple[int, bool]:
        return fit_hbm_window(
            capacity, n_envs, leaf_specs, int(budget), sharding, requested, min_window
        )

    probe_window, _ = fit(free / 8)
    rb, fused = make(probe_window)
    probe_ring = ring_device_bytes(leaf_specs, probe_window, n_envs, sharding)
    import jax

    largest = update_chunks(max(int(first_window), 1), bytes_per_update=batch_bytes)[0]
    train = program_extra_bytes(
        fused.lower(
            # a stand-in key and counter: only their shapes are lowered against
            *train_state, rb.abstract_buffers(leaf_specs), rb.cursor, jax.random.PRNGKey(0),
            fabric.replicate(np.int32(0)), n_samples=largest,
        ).compile()
    )
    read, write = rb.access_extra_bytes(leaf_specs)
    per_ring_byte = 1.0 + (read + write) / probe_ring
    fixed = train - read
    window, spill_needed = fit((free - fixed) / per_ring_byte)
    print(
        f"[sheeprl_tpu] buffer.device: {limit / 2**30:.2f} GiB limit, "
        f"{in_use / 2**30:.2f} GiB resident; at a {probe_ring / 2**30:.2f} GiB probe ring the "
        f"U={largest} train program needs {train / 2**30:.2f} GiB, ring reads "
        f"{read / 2**30:.2f} GiB, ring writes {write / 2**30:.2f} GiB -> {per_ring_byte:.2f} "
        f"device bytes per ring byte + {fixed / 2**30:.2f} GiB fixed -> {window} of "
        f"{capacity} steps/env in the ring"
        + (" (older data pages to the host spill tier)" if spill_needed else ""),
        flush=True,
    )
    if window != probe_window:
        rb, fused = make(window)
    if spill_needed:
        rb.spill = HostSpill(
            capacity, n_envs, sequential=sequential, memmap=cfg.buffer.memmap, memmap_dir=memmap_dir
        )
    return rb, fused


def sampled_bytes(
    leaf_specs: LeafSpecs,
    batch_size: int,
    sequence_length: int = 1,
    derive_next: Sequence[str] = (),
) -> float:
    """Raw bytes of ONE update's gathered batch for a ring of ``leaf_specs``
    rows (``derive_next`` keys are gathered twice: the row and its successor)."""
    total = 0.0
    for k, (feat, dtype) in leaf_specs.items():
        row = int(np.prod(feat)) * np.dtype(dtype).itemsize
        total += row * int(batch_size) * int(sequence_length) * (2 if k in derive_next else 1)
    return total


def update_chunks(
    n_updates: int, cap: Optional[int] = None, bytes_per_update: float = 0.0
) -> List[int]:
    """Split an update window into power-of-two dispatch chunk sizes.

    Replaces the byte-probed ``utils.window_chunks``: with device-resident
    replay nothing ships H2D, but two budgets remain —

    * COMPILE reuse: every distinct chunk length U is its own abstract
      signature, and a remote-TPU compile costs minutes.  Powers of two
      (largest first, greedy remainder) keep a burst window (the
      post-``learning_starts`` ratio repayment) to a handful of executables
      whose small tail sizes coincide with the steady-state window sizes.
      ``cap`` (default ``SHEEPRL_MAX_WINDOW_UPDATES``, 1024) bounds any
      single scanned dispatch.
    * HBM: the fused program still MATERIALIZES the gathered ``(U, ...)``
      block on device before scanning it — a U=1024 DV3-S pixel burst is
      ~12.9 GiB raw / ~2x padded, the exact alloc that killed the round-5
      TPU capture.  Pass the per-update gathered bytes (see
      ``DeviceReplay.sampled_bytes_per_update``) and the cap also honors
      ``SHEEPRL_MAX_HBM_WINDOW_BYTES`` (default 2 GiB, the same knob the
      retired ``window_chunks`` used for on-device gathered blocks).
    """
    if cap is None:
        cap = int(os.environ.get("SHEEPRL_MAX_WINDOW_UPDATES", 1024))
    if bytes_per_update > 0.0:
        hbm_budget = float(os.environ.get("SHEEPRL_MAX_HBM_WINDOW_BYTES", 2**31))
        cap = min(int(cap), max(1, int(hbm_budget // bytes_per_update)))
    cap = 1 << (max(1, int(cap)).bit_length() - 1)
    chunks: List[int] = []
    remaining = int(n_updates)
    while remaining > 0:
        step = min(cap, 1 << (remaining.bit_length() - 1))
        chunks.append(step)
        remaining -= step
    return chunks


@contextlib.contextmanager
def steady_guard(enabled: bool):
    """Arm ``jax.transfer_guard_host_to_device("disallow")`` around a
    steady-state train window: any IMPLICIT host→device transfer inside
    raises (explicit ``device_put`` staging stays legal).  This is the
    red/green spelling of the zero-copy claim — the guard the
    ``run_ci.sh`` replay stage arms around whole training runs
    (``buffer.transfer_guard=True``) and the tier-1 replay tests around
    their windows.

    Scoped to the H2D direction on purpose: device-to-device movement (the
    per-window PRNG key broadcasting onto a multi-device mesh, GSPMD
    resharding) rides ICI and is not host traffic, and device-to-host pulls
    are the metrics/logging path — neither is the copy this guard exists to
    outlaw."""
    if not enabled:
        yield
        return
    import jax

    with jax.transfer_guard_host_to_device("disallow"):
        yield


# --------------------------------------------------------------------------
# async host spill tier
# --------------------------------------------------------------------------

class HostSpill:
    """Asynchronous full-capacity host shadow of a :class:`DeviceReplay`.

    Reuses the ``checkpoint/writer.py`` split of work: the CALLER (env/actor
    path) copies the incoming host rows and enqueues; ONE daemon worker
    drains the queue into a host ring (``ReplayBuffer`` /
    ``SequentialReplayBuffer``, optionally memmapped) so capacity beyond the
    HBM window survives without ever blocking the compiled train step — the
    train step never touches this tier at all.  The ``replay.spill`` fault
    site (latency / raise / truncate) instruments the worker's write:

    * latency/hang → eviction bookkeeping falls behind (queue grows), the
      device ring and sampling are unaffected;
    * raise → the error is parked, :attr:`degraded` flips, later writes
      continue (a dead spill disk degrades capacity, not training);
    * truncate → the queued rows are tail-halved before the write (the
      chaos drill for torn spill writes).
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        sequential: bool = False,
        memmap: bool = False,
        memmap_dir: Optional[Union[str, os.PathLike]] = None,
        queue_size: int = 256,
    ):
        from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, ReplayBuffer

        if sequential:
            # per-env sub-buffers, NOT a shared-cursor ring: the dreamer add
            # path appends reset rows to done envs only (``indices=``), and a
            # shared cursor would advance every env's stream for a subset
            # write, misaligning the shadow history
            self._rb: Any = EnvIndependentReplayBuffer(
                int(capacity), n_envs=int(n_envs), memmap=memmap, memmap_dir=memmap_dir
            )
        else:
            self._rb = ReplayBuffer(int(capacity), int(n_envs), memmap=memmap, memmap_dir=memmap_dir)
        self._queue: "queue.Queue[Optional[Tuple[Arrays, Optional[List[int]]]]]" = queue.Queue(
            maxsize=max(1, int(queue_size))
        )
        self._error: Optional[BaseException] = None
        self._idle = threading.Event()
        self._idle.set()
        self._pending = 0
        self._lock = threading.Lock()
        self._closed = False
        self._thread = threading.Thread(target=self._loop, name="replay-spill", daemon=True)
        self._thread.start()

    # -- worker --------------------------------------------------------------
    def _loop(self) -> None:
        from sheeprl_tpu.resilience.faults import fault_rows

        while True:
            job = self._queue.get()
            if job is None:
                self._queue.task_done()
                return
            data, indices = job
            try:
                data = fault_rows("replay.spill", data)
                self._rb.add(data, indices=indices)
            except BaseException as e:  # parked: spill degrades, never kills
                if self._error is None:
                    self._error = e
                    warnings.warn(
                        f"replay spill tier degraded ({type(e).__name__}: {e}); the "
                        "device ring keeps training, capacity beyond the HBM window "
                        "is no longer persisted",
                        RuntimeWarning,
                    )
            finally:
                self._queue.task_done()
                with self._lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.set()

    # -- API -----------------------------------------------------------------
    @property
    def degraded(self) -> bool:
        return self._error is not None

    @property
    def backlog(self) -> int:
        return self._queue.unfinished_tasks

    @property
    def buffer(self) -> Any:
        """The host ring (drain with :meth:`flush` before reading)."""
        return self._rb

    def submit(self, data: Arrays, indices: Optional[Sequence[int]] = None) -> None:
        """Enqueue one append.  Rows are COPIED here (the caller reuses its
        step arrays).  Blocks only when the bounded queue is full — back
        pressure on the (host) actor path, never on the device step."""
        if self._closed:
            return
        copied = {k: np.array(v, copy=True) for k, v in data.items()}
        with self._lock:
            self._pending += 1
            self._idle.clear()
        self._queue.put((copied, list(indices) if indices is not None else None))

    def flush(self, timeout_s: Optional[float] = 60.0) -> bool:
        return self._idle.wait(timeout_s)

    def state_dict(self) -> Dict[str, Any]:
        self.flush()
        return self._rb.state_dict()

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.flush()
        self._rb.load_state_dict(state)

    def close(self, timeout_s: float = 30.0) -> None:
        if self._closed:
            return
        self._closed = True
        self._idle.wait(timeout_s)
        try:
            self._queue.put(None, timeout=5.0)
        except queue.Full:
            pass
        self._thread.join(5.0)


# --------------------------------------------------------------------------
# the device-resident ring
# --------------------------------------------------------------------------

class DeviceReplay:
    """Mesh-sharded device-resident replay ring over ``Dict[str, (W, E, *)]``.

    ``W`` is the HBM window (steps per env), ``E`` the env count.  A leaf whose
    feature has more than one axis is held as ``(W, E, F_pad)``
    (:func:`stored_feature`); ``add``/``write_at`` flatten host rows on the way
    in, the samplers and ``gather_at`` restore the feature shape of the
    *gathered batch*, and a checkpoint keeps ``(W, E, *feat)``.  Arrays are
    placed with ``PartitionSpec(None, 'data', ...)`` when the env axis
    divides the mesh ``data`` axis (else replicated) — the same layout
    ``fabric.shard_batch`` gives shipped batches, so gathers stay mostly
    shard-local and GSPMD inserts the cross-shard collectives where a
    sampled batch needs them.

    Write path: host ``(T, B, *)`` rows → one explicit ``device_put`` per
    key → a donated jitted scatter at ring slots derived from per-env
    cursors.  Cursors live twice: as ``int32`` device arrays (``cursor``
    — sampling consumes them INSIDE the compiled update, so their motion is
    data, not signature) and as host numpy shadows (``len()``/eligibility
    checks without device syncs).
    """

    def __init__(
        self,
        capacity: int,
        n_envs: int,
        mesh: Optional[Any] = None,
        data_axis: str = "data",
        spill: Optional[HostSpill] = None,
    ):
        import jax
        import jax.numpy as jnp

        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if n_envs <= 0:
            raise ValueError(f"n_envs must be positive, got {n_envs}")
        self._capacity = int(capacity)
        self._n_envs = int(n_envs)
        self._mesh = mesh
        self._data_axis = data_axis
        self.spill = spill
        self._buf: Dict[str, Any] = {}
        self._feat: Dict[str, Tuple[int, ...]] = {}  # key -> feature shape as callers see it
        self._sharding = None
        if mesh is not None:
            from sheeprl_tpu.parallel.sharding import replay_sharding

            self._sharding = replay_sharding(mesh, n_envs, data_axis)
        self._pos_h = np.zeros(self._n_envs, np.int64)
        self._filled_h = np.zeros(self._n_envs, np.int64)
        zeros = jnp.zeros(self._n_envs, jnp.int32)
        if self._sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            replicated = NamedSharding(mesh, P())
            zeros = jax.device_put(zeros, replicated)
        self.cursor: Dict[str, Any] = {"pos": zeros, "filled": zeros}
        self._scatter = None
        self._gather = None
        self._advance = None

    # -- geometry / introspection -------------------------------------------
    @property
    def capacity(self) -> int:
        return self._capacity

    @property
    def buffer_size(self) -> int:  # host-buffer API parity
        return self._capacity

    @property
    def n_envs(self) -> int:
        return self._n_envs

    @property
    def buffers(self) -> Dict[str, Any]:
        """The device pytree, in its stored shapes — pass it (with
        :attr:`cursor`) into a fused train program; never copied, never
        donated.  Read rows through the samplers or :meth:`gather_at`."""
        return self._buf

    @property
    def leaf_specs(self) -> LeafSpecs:
        """The allocated ring's rows as callers see them: feature shape, dtype."""
        return {k: (self._feat[k], buf.dtype) for k, buf in self._buf.items()}

    @property
    def full(self) -> bool:
        return bool((self._filled_h >= self._capacity).all())

    @property
    def empty(self) -> bool:
        return not self._buf

    def __len__(self) -> int:
        return int(self._filled_h.sum())

    def __contains__(self, key: str) -> bool:
        return key in self._buf

    def keys(self) -> Tuple[str, ...]:
        return tuple(self._buf.keys())

    def abstract_buffers(self, leaf_specs: LeafSpecs) -> Dict[str, Any]:
        """The ring as ``ShapeDtypeStruct``s (stored shape, dtype, placement)
        — what a fused program is lowered against before the ring is
        allocated."""
        import jax

        return {
            k: jax.ShapeDtypeStruct(
                self._declare(k, feat), np.dtype(dtype), sharding=self._sharding
            )
            for k, (feat, dtype) in leaf_specs.items()
        }

    def access_extra_bytes(self, leaf_specs: LeafSpecs) -> Tuple[int, int]:
        """``(read, write)``: device bytes this ring's gather and its donated
        scatter need BEYOND the ring itself, summed over its leaves, by XLA's
        memory analysis of the two programs at this ring's size.  Near zero
        where the device indexes the ring in place (every leaf in its stored
        shape, on a v5e); where it re-lays a whole leaf into temporaries the
        figure grows with the ring."""
        import jax

        scatter, gather, _ = self._ops()
        index_sharding = None
        if self._sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            index_sharding = NamedSharding(self._mesh, P())

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=index_sharding)

        n = self._n_envs
        t, e = spec((1, n), np.int32), spec((n,), np.int32)
        read = write = 0
        for k, arr in self.abstract_buffers(leaf_specs).items():
            read += program_extra_bytes(gather.lower(arr, t, e, self._feat[k]).compile())
            rows = spec((1, n) + arr.shape[2:], arr.dtype)
            write += program_extra_bytes(scatter.lower(arr, rows, t, e).compile())
        return read, write

    @property
    def hbm_bytes(self) -> int:
        """Resident ring bytes."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in self._buf.values())

    def sampled_bytes_per_update(
        self,
        batch_size: int,
        sequence_length: int = 1,
        derive_next: Sequence[str] = (),
    ) -> float:
        """HBM bytes one update's gathered batch materializes on device —
        the ``bytes_per_update`` input to :func:`update_chunks`, computed
        exactly from the allocated ring (call after the first ``add``)."""
        return sampled_bytes(self.leaf_specs, batch_size, sequence_length, derive_next)

    def can_sample(self, min_steps: int = 1) -> bool:
        return bool((self._filled_h >= max(1, int(min_steps))).any())

    def can_sample_sequences(self, sequence_length: int) -> bool:
        # host-law parity: EnvIndependent requires len(b) > seq_len somewhere
        return bool((self._filled_h > int(sequence_length)).any())

    # -- jitted primitives ---------------------------------------------------
    def _ops(self):
        if self._scatter is None:
            import jax

            # donate the ring: updates are in-place, no 2x HBM spike; pin the
            # output back onto the replay sharding so a multi-device scatter
            # cannot drift the layout update-over-update
            def scoped_set(arr, rows, t, e):
                with jax.named_scope("replay.write"):
                    return arr.at[t, e[None, :]].set(rows)

            # a lambda still: the jitted function's name is the module's name, and
            # that is part of the compile cache's key where the scope is not
            self._scatter = jax.jit(
                lambda arr, rows, t, e: scoped_set(arr, rows, t, e),
                donate_argnums=0,
                out_shardings=self._sharding,
            )
            self._gather = jax.jit(
                lambda arr, t, e, feat: from_stored(arr[t, e], feat), static_argnums=3
            )

            def advance(pos, filled, steps, mask):
                new_pos = (pos + steps) % self._capacity
                new_filled = jax.numpy.minimum(filled + steps, self._capacity)
                return (
                    jax.numpy.where(mask, new_pos, pos),
                    jax.numpy.where(mask, new_filled, filled),
                )

            # no donation: the cursor vectors are a few bytes, and pos/filled
            # start life aliased to one zeros buffer (double-donation trap)
            self._advance = jax.jit(advance)
        return self._scatter, self._gather, self._advance

    def _declare(self, key: str, feat_shape: Sequence[int]) -> Tuple[int, ...]:
        """Record ``key``'s feature shape; the shape its leaf is stored in."""
        feat = tuple(int(d) for d in feat_shape)
        if self._feat.setdefault(key, feat) != feat:
            raise ValueError(
                f"replay key {key!r} holds rows of shape {self._feat[key]}, got {feat}"
            )
        return (self._capacity, self._n_envs) + stored_feature(feat)

    def _ensure(self, key: str, feat_shape: Sequence[int], dtype: Any) -> None:
        shape = self._declare(key, feat_shape)
        if key not in self._buf:
            self._buf[key] = _zeros_program(shape, dtype, self._sharding)()

    def _put(self, x: np.ndarray) -> Any:
        """Explicit H2D staging (transfer-guard-legal) of host rows/indices."""
        import jax

        if self._sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(np.asarray(x), NamedSharding(self._mesh, P()))
        return jax.device_put(np.asarray(x))

    # -- write path ----------------------------------------------------------
    def add(self, data: Arrays, indices: Optional[Sequence[int]] = None) -> None:
        """Append ``T`` steps of ``(T, B, *)`` host data for all (or
        ``indices``) envs — the host-buffer ``add`` contract, device-resident."""
        if not isinstance(data, dict) or not data:
            raise ValueError("add() expects a non-empty dict of (T, B, *) arrays")
        first = next(iter(data.values()))
        if np.ndim(first) < 2:
            raise ValueError("Buffer data must be (T, B, *)")
        steps = int(np.shape(first)[0])
        if self.spill is not None:
            # the spill tier shadows FULL capacity: hand it the whole block
            # BEFORE the HBM-window truncation below (its own ring applies
            # its own, larger, truncation law)
            self.spill.submit(data, indices=indices)
        if steps > self._capacity:
            data = {k: np.asarray(v)[-self._capacity:] for k, v in data.items()}
            steps = self._capacity
        env_sel = np.arange(self._n_envs) if indices is None else np.asarray(list(indices))
        if np.shape(first)[1] != len(env_sel):
            raise ValueError(
                f"data has {np.shape(first)[1]} envs, expected {len(env_sel)}"
            )
        for k, v in data.items():
            self._ensure(k, np.shape(v)[2:], np.asarray(v).dtype)
        # ring slots each env is about to write (host math, no device sync)
        t_idx = np.stack(
            [(self._pos_h[e] + np.arange(steps)) % self._capacity for e in env_sel],
            axis=1,
        ).astype(np.int32)  # (T, K)
        # host→ring staging is its own telemetry phase (replay.write): the
        # H2D stage + donated scatter dispatch the rollout pays per append
        from sheeprl_tpu.telemetry.spans import span

        with span("replay.write"):
            scatter, _, advance = self._ops()
            t_dev = self._put(t_idx)
            e_dev = self._put(env_sel.astype(np.int32))
            for k, v in data.items():
                rows = self._put(to_stored(np.asarray(v)[-steps:]))
                self._buf[k] = scatter(self._buf[k], rows, t_dev, e_dev)
            mask = np.zeros(self._n_envs, bool)
            mask[env_sel] = True
            self.cursor["pos"], self.cursor["filled"] = advance(
                self.cursor["pos"],
                self.cursor["filled"],
                self._put(np.int32(steps)),
                self._put(mask),
            )
        self._pos_h[env_sel] = (self._pos_h[env_sel] + steps) % self._capacity
        self._filled_h[env_sel] = np.minimum(self._filled_h[env_sel] + steps, self._capacity)

    def repair_tail(self, env: int = 0) -> None:
        """Mark the last written step of ``env`` as a truncation (stream
        broke: crashed-and-restarted env) — host-buffer contract."""
        if self._filled_h[env] == 0:
            return
        tail = int((self._pos_h[env] - 1) % self._capacity)
        for key, value in (("truncated", 1.0), ("terminated", 0.0), ("is_first", 0.0)):
            if key in self._buf:
                row = np.full((1, 1) + self._feat[key], value, dtype=np.dtype(self._buf[key].dtype))
                self.write_at(key, row, np.asarray([[tail]], np.int32), [env])

    # -- mirror-compatible primitives (the attach_mirror shim rides these) ---
    def write_at(self, key: str, rows: np.ndarray, time_pos: np.ndarray, env_cols: Sequence[int]) -> None:
        """Scatter ``rows (T, K, *)`` at explicit ring slots ``time_pos
        (T, K)`` for env columns ``env_cols (K,)`` — cursors untouched."""
        rows = np.asarray(rows)
        self._ensure(key, rows.shape[2:], rows.dtype)
        scatter, _, _ = self._ops()
        self._buf[key] = scatter(
            self._buf[key],
            self._put(to_stored(rows)),
            self._put(np.asarray(time_pos, np.int32)),
            self._put(np.asarray(env_cols, np.int32)),
        )

    def gather_at(self, key: str, time_idx: np.ndarray, env_idx: np.ndarray) -> Any:
        """Device gather at explicit ring coordinates (mirror contract)."""
        _, gather, _ = self._ops()
        return gather(
            self._buf[key],
            self._put(np.asarray(time_idx, np.int32)),
            self._put(np.asarray(env_idx, np.int32)),
            self._feat[key],
        )

    # -- on-device sampling (jit-traceable over buffers/cursor/key) ----------
    def uniform_indices(self, cursor: Dict[str, Any], key: Any, total: int, sample_next_obs: bool = False):
        """``(step, env)`` index vectors for ``total`` uniform draws — the
        host ``ReplayBuffer._valid_steps`` law, traced: all envs share the
        ring head (they advance in lockstep on the uniform layouts), so env
        0's cursor is THE cursor; when full and successor rows are needed the
        slot before the write head is excluded by basing draws at ``pos``."""
        import jax
        import jax.numpy as jnp

        pos = cursor["pos"][0]
        filled = cursor["filled"][0]
        full = filled >= self._capacity
        trim = 1 if sample_next_obs else 0
        valid = jnp.where(full, self._capacity - trim, jnp.maximum(filled - trim, 0))
        k_step, k_env = jax.random.split(key)
        r = jax.random.randint(k_step, (total,), 0, jnp.maximum(valid, 1))
        step = jnp.where(
            jnp.logical_and(full, sample_next_obs), (pos + r) % self._capacity, r
        )
        env = jax.random.randint(k_env, (total,), 0, self._n_envs)
        return step, env

    def sample_uniform(
        self,
        buffers: Dict[str, Any],
        cursor: Dict[str, Any],
        key: Any,
        batch_size: int,
        n_samples: int = 1,
        keys: Optional[Sequence[str]] = None,
        derive_next: Sequence[str] = (),
        constrain: bool = True,
    ) -> Dict[str, Any]:
        """Uniform ``(n_samples, batch_size, *)`` batches gathered on device.

        ``derive_next`` lists observation keys whose successor row should be
        emitted as ``next_<k>`` (layouts that do not store next rows); when
        empty, draws never exclude the write-head predecessor — exactly the
        host law.  Call INSIDE a jitted train program: the index generation
        and gather compile into the update step."""
        import jax

        total = int(batch_size) * int(n_samples)
        with jax.named_scope("replay.sample_index"):
            step, env = self.uniform_indices(cursor, key, total, sample_next_obs=bool(derive_next))
        out: Dict[str, Any] = {}
        with jax.named_scope("replay.gather"):
            for k, buf in buffers.items():
                if keys is not None and k not in keys:
                    continue
                out[k] = from_stored(buf[step, env], self._feat[k]).reshape(
                    n_samples, batch_size, *self._feat[k]
                )
            for k in derive_next:
                if k in buffers:
                    nxt = (step + 1) % self._capacity
                    out[f"next_{k}"] = from_stored(buffers[k][nxt, env], self._feat[k]).reshape(
                        n_samples, batch_size, *self._feat[k]
                    )
            return self._constrain(out, batch_axis=1) if constrain else out

    def sequence_indices(self, cursor: Dict[str, Any], key: Any, total: int, sequence_length: int):
        """``(t_idx (total, L), env (total,))`` for contiguous sequence draws
        — the ``EnvIndependentReplayBuffer`` law, traced: envs weighted by
        occupancy among those holding >= L steps, start uniform over the
        env's valid range, sequences never crossing that env's write head."""
        import jax
        import jax.numpy as jnp

        L = int(sequence_length)
        pos = cursor["pos"]
        filled = cursor["filled"]
        full = filled >= self._capacity
        max_start = jnp.where(full, self._capacity - L, filled - L)  # per env
        weights = jnp.where(filled >= L, filled, 0).astype(jnp.float32)
        logits = jnp.where(weights > 0, jnp.log(jnp.maximum(weights, 1e-9)), -jnp.inf)
        k_env, k_start = jax.random.split(key)
        env = jax.random.categorical(k_env, logits, shape=(total,))
        valid = jnp.maximum(jnp.take(max_start, env) + 1, 1)
        start = jax.random.randint(k_start, (total,), 0, valid)
        base = jnp.where(jnp.take(full, env), jnp.take(pos, env), 0)
        t_idx = (base[:, None] + start[:, None] + jnp.arange(L)[None, :]) % self._capacity
        return t_idx.astype(jnp.int32), env.astype(jnp.int32)

    def sample_sequences(
        self,
        buffers: Dict[str, Any],
        cursor: Dict[str, Any],
        key: Any,
        batch_size: int,
        sequence_length: int,
        n_samples: int = 1,
        keys: Optional[Sequence[str]] = None,
        constrain: bool = True,
    ) -> Dict[str, Any]:
        """Contiguous ``(n_samples, L, batch_size, *)`` sequence batches
        gathered on device — the Dreamer-family sampling layout."""
        import jax

        total = int(batch_size) * int(n_samples)
        L = int(sequence_length)
        with jax.named_scope("replay.sample_index"):
            t_idx, env = self.sequence_indices(cursor, key, total, L)
        out: Dict[str, Any] = {}
        with jax.named_scope("replay.gather"):
            for k, buf in buffers.items():
                if keys is not None and k not in keys:
                    continue
                g = from_stored(buf[t_idx, env[:, None]], self._feat[k])  # (total, L, *feat)
                g = g.reshape(n_samples, batch_size, L, *self._feat[k])
                out[k] = g.swapaxes(1, 2)  # (n_samples, L, batch, *feat)
            return self._constrain(out, batch_axis=2) if constrain else out

    def _constrain(self, tree: Dict[str, Any], batch_axis: int) -> Dict[str, Any]:
        """Re-lay sampled batches over the mesh ``data`` axis (the
        ``fabric.shard_batch`` layout) so the consuming update step starts
        from the canonical data-parallel placement."""
        if self._mesh is None or int(np.prod(list(self._mesh.shape.values()))) == 1:
            return tree
        n_data = int(self._mesh.shape.get(self._data_axis, 1))
        if n_data <= 1:
            return tree
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(x):
            if x.shape[batch_axis] % n_data != 0:
                return x
            spec = [None] * x.ndim
            spec[batch_axis] = self._data_axis
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self._mesh, P(*spec))
            )

        return {k: put(v) for k, v in tree.items()}

    # -- persistence ---------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        """Host snapshot.  Prefers the spill tier's full-capacity ring when
        armed AND healthy (it holds MORE history than the HBM window — a
        degraded or backlogged-past-timeout spill falls back to the device
        ring, never snapshotting a half-drained shadow); otherwise one D2H
        fetch of the ring with the checkpoint tail-consistency patch applied
        to the host COPY (the callback's ``_consistent_tail`` contract: the
        step at each env's write head must not look continuable on resume —
        only ``truncated``/``dones`` are forced, NEVER ``terminated``, which
        is a value-semantics bootstrap-killing flag).  Either way the arrays
        are ``(W, E, *feat)``: the stored shape never reaches a checkpoint."""
        if self.spill is not None and not self.spill.degraded:
            if self.spill.flush(self._spill_flush_timeout_s):
                state = self.spill.state_dict()
                _patch_spill_tail(state)
                state["device_replay"] = {
                    "pos": np.array(self._pos_h),
                    "filled": np.array(self._filled_h),
                    "from_spill": True,
                }
                return state
            warnings.warn(
                "replay spill tier did not drain in time; checkpointing the "
                "device ring (HBM window) instead of the full spill history",
                RuntimeWarning,
            )
        buf = {k: from_stored(np.asarray(v), self._feat[k]) for k, v in self._buf.items()}
        if buf and not any(k.startswith("next_") for k in buf):
            # writable copies for just the patched flag keys (np.asarray of a
            # device array is a read-only view)
            for key in ("truncated", "dones"):
                if key in buf:
                    buf[key] = np.array(buf[key], copy=True)
            for env in range(self._n_envs):
                if self._filled_h[env] == 0:
                    continue
                tail = int((self._pos_h[env] - 1) % self._capacity)
                for key in ("truncated", "dones"):
                    if key in buf:
                        buf[key][tail, env] = 1.0
        return {
            "buffer": buf,
            "pos": np.array(self._pos_h),
            "filled": np.array(self._filled_h),
            "buffer_size": self._capacity,
            "n_envs": self._n_envs,
            "device_replay": {"from_spill": False},
        }

    def load_state_dict(self, state: Dict[str, Any]) -> "DeviceReplay":
        meta = state.get("device_replay") or {}
        if meta.get("from_spill"):
            return self._load_from_spill(state, meta)
        if int(state.get("n_envs", self._n_envs)) != self._n_envs:
            raise ValueError(
                f"Checkpointed replay has {state.get('n_envs')} envs, expected "
                f"{self._n_envs} (resume requires the same world size)"
            )
        if "buffers" in state:
            raise ValueError(
                "this checkpoint was written by the host EnvIndependent buffer "
                "backend; restore it with buffer.device=False or re-collect — "
                "host->device restore is only supported through the spill tier"
            )
        saved_cap = int(state.get("buffer_size", self._capacity))
        buf = state["buffer"]
        pos = np.asarray(state["pos"]).reshape(-1)
        filled = np.asarray(state["filled"]).reshape(-1)
        if pos.size == 1:  # host ReplayBuffer scalar-cursor checkpoints
            pos = np.full(self._n_envs, int(pos[0]))
            filled = np.full(
                self._n_envs, saved_cap if state.get("full") else int(pos[0])
            )
        if saved_cap != self._capacity:
            raise ValueError(
                f"Checkpointed replay window {saved_cap} != {self._capacity}"
            )
        for k, v in buf.items():
            v = np.asarray(v)
            self.write_at(k, v, np.tile(np.arange(saved_cap)[:, None], (1, self._n_envs)), list(range(self._n_envs)))
        self._pos_h = pos.astype(np.int64).copy()
        self._filled_h = np.minimum(filled.astype(np.int64), self._capacity).copy()
        # rebuild the device cursors from the host shadows (explicit puts)
        self.cursor = {
            "pos": self._put(self._pos_h.astype(np.int32)),
            "filled": self._put(self._filled_h.astype(np.int32)),
        }
        return self

    #: how long ``state_dict`` waits for the spill worker before falling back
    #: to a device-ring snapshot
    _spill_flush_timeout_s: float = 60.0

    def _load_from_spill(self, state: Dict[str, Any], meta: Dict[str, Any]) -> "DeviceReplay":
        """Restore a spill-tier checkpoint: reload the full-capacity host
        shadow ring, then rebuild the HBM window from each env's newest rows
        at exactly the saved device cursors — save and resume round-trip
        regardless of which tier wrote the snapshot."""
        if self.spill is None:
            raise ValueError(
                "checkpoint was written from the replay spill tier but this "
                "run has no spill armed — keep the same buffer.size / "
                "buffer.hbm_window as the saved run"
            )
        spill_state = {k: v for k, v in state.items() if k != "device_replay"}
        self.spill.load_state_dict(spill_state)
        pos = np.asarray(meta["pos"]).reshape(-1).astype(np.int64)
        filled = np.minimum(
            np.asarray(meta["filled"]).reshape(-1).astype(np.int64), self._capacity
        )
        if pos.size != self._n_envs:
            raise ValueError(
                f"spill checkpoint has {pos.size} env cursors, expected {self._n_envs}"
            )
        for env in range(self._n_envs):
            history = self._spill_env_history(env)  # key -> (L_e, *) oldest->newest
            if not history:
                continue
            length = next(iter(history.values())).shape[0]
            n = int(min(filled[env], length))
            if n == 0:
                continue
            slots = ((pos[env] - n + np.arange(n)) % self._capacity).astype(np.int32)
            for k, rows in history.items():
                self.write_at(k, rows[-n:][:, None], slots[:, None], [env])
            filled[env] = n
        self._pos_h = pos.copy()
        self._filled_h = filled.copy()
        self.cursor = {
            "pos": self._put(self._pos_h.astype(np.int32)),
            "filled": self._put(self._filled_h.astype(np.int32)),
        }
        return self

    def _spill_env_history(self, env: int) -> Dict[str, np.ndarray]:
        """One env's stored rows from the spill host ring, oldest -> newest."""
        from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer

        host = self.spill.buffer
        if isinstance(host, EnvIndependentReplayBuffer):
            sub = host.buffer[env]
            length = len(sub)
            if length == 0:
                return {}
            if sub.full:
                idx = (sub._pos + np.arange(sub.buffer_size)) % sub.buffer_size
            else:
                idx = np.arange(length)
            return {k: np.asarray(sub[k])[idx, 0] for k in sub.keys()}
        length = len(host)
        if length == 0:
            return {}
        if host.full:
            idx = (host._pos + np.arange(host.buffer_size)) % host.buffer_size
        else:
            idx = np.arange(length)
        return {k: np.asarray(host[k])[idx, env] for k in host.keys()}


def _patch_spill_tail(state: Dict[str, Any]) -> None:
    """Checkpoint tail-consistency patch for a spill-tier snapshot — the
    ``utils.callback._consistent_tail`` contract applied to the state COPY
    (the callback's isinstance dispatch never matches a ``DeviceReplay``, so
    this module owns the invariant for both snapshot branches): each ring's
    write-head row is forced ``truncated``/``dones`` = 1 so the stored tail
    never looks continuable on resume.  ``terminated`` is untouched (a
    value-semantics flag) and layouts storing ``next_<k>`` rows need no
    patch (every row is self-contained)."""

    def patch_one(sub: Dict[str, Any]) -> None:
        buf = sub.get("buffer") or {}
        if not buf or any(k.startswith("next_") for k in buf):
            return
        filled = int(sub["buffer_size"]) if sub.get("full") else int(sub.get("pos", 0))
        if filled == 0:
            return
        tail = (int(sub["pos"]) - 1) % int(sub["buffer_size"])
        for key in ("truncated", "dones"):
            if key in buf:
                # copy before writing: state_dict arrays can be live views
                # of (or memmap references into) the spill's host ring
                arr = np.array(np.asarray(buf[key]), copy=True)
                arr[tail] = 1.0
                buf[key] = arr

    if "buffers" in state:  # EnvIndependent spill: one sub-state per env
        for sub in state["buffers"]:
            patch_one(sub)
    else:
        patch_one(state)


# --------------------------------------------------------------------------
# fused sample+update programs
# --------------------------------------------------------------------------

def fused_uniform_train(
    fabric: Any,
    train_phase: Callable,
    replay: DeviceReplay,
    batch_size: int,
    prep: Callable[[Dict[str, Any]], Dict[str, Any]],
    name: str,
    derive_next: Sequence[str] = (),
    max_recompiles: Optional[int] = None,
    health: bool = False,
) -> Any:
    """Fold uniform index generation + device gather + ``prep`` + the algo's
    existing ``train_phase(p, o, batches, key, counter)`` into ONE
    ``fabric.compile`` AOT executable: ``fused(p, o, buffers, cursor, key,
    counter, n_samples=U)`` → ``(p, o, counter + U, metrics)``.

    The counter is threaded through the program as device data (not rebuilt
    host-side per window) so a transfer-guarded steady state performs zero
    implicit H2D; ``n_samples`` is static — distinct window lengths compile
    distinct executables exactly as the shipped-batch path did (chunked by
    :func:`update_chunks` for reuse).

    ``health=True``: ``train_phase`` is a health-guarded program
    (``resilience/health.py``) with the sentinel state threaded first —
    the fused signature becomes ``fused(p, o, h, buffers, cursor, key,
    counter, n_samples=U)`` → ``(p, o, h, counter + U, metrics)``, with
    ``h`` donated alongside params/opt-state (device data like the
    counter, so the guarded steady state stays one executable)."""
    import jax

    if health:
        def fused_h(p, o_state, h, buffers, cursor, k, counter, n_samples):
            k_sample, k_train = jax.random.split(k)
            batch = replay.sample_uniform(
                buffers, cursor, k_sample, batch_size, int(n_samples), derive_next=derive_next
            )
            h, p, o_state, metrics = train_phase(h, p, o_state, prep(batch), k_train, counter)
            return p, o_state, h, counter + int(n_samples), metrics

        return fabric.compile(
            fused_h,
            name=name,
            static_argnames=("n_samples",),
            donate_argnums=(0, 1, 2),
            max_recompiles=max_recompiles,
        )

    def fused(p, o_state, buffers, cursor, k, counter, n_samples):
        k_sample, k_train = jax.random.split(k)
        batch = replay.sample_uniform(
            buffers, cursor, k_sample, batch_size, int(n_samples), derive_next=derive_next
        )
        p, o_state, metrics = train_phase(p, o_state, prep(batch), k_train, counter)
        return p, o_state, counter + int(n_samples), metrics

    return fabric.compile(
        fused,
        name=name,
        static_argnames=("n_samples",),
        donate_argnums=(0, 1),
        max_recompiles=max_recompiles,
    )


def fused_sequence_train(
    fabric: Any,
    train_phase: Callable,
    replay: DeviceReplay,
    batch_size: int,
    sequence_length: int,
    prep: Callable[[Dict[str, Any]], Dict[str, Any]],
    name: str,
    max_recompiles: Optional[int] = None,
    health: bool = False,
) -> Any:
    """Sequence-sampling twin of :func:`fused_uniform_train` (the Dreamer
    family): ``fused(p, o, buffers, cursor, key, counter, n_samples=U)``
    samples ``(U, L, B, *)`` blocks on device and runs the scanned update.
    ``health=True`` threads the sentinel state exactly like the uniform
    variant."""
    import jax

    if health:
        def fused_h(p, o_state, h, buffers, cursor, k, counter, n_samples):
            k_sample, k_train = jax.random.split(k)
            blocks = replay.sample_sequences(
                buffers, cursor, k_sample, batch_size, sequence_length, int(n_samples)
            )
            h, p, o_state, metrics = train_phase(h, p, o_state, prep(blocks), k_train, counter)
            return p, o_state, h, counter + int(n_samples), metrics

        return fabric.compile(
            fused_h,
            name=name,
            static_argnames=("n_samples",),
            donate_argnums=(0, 1, 2),
            max_recompiles=max_recompiles,
        )

    def fused(p, o_state, buffers, cursor, k, counter, n_samples):
        k_sample, k_train = jax.random.split(k)
        blocks = replay.sample_sequences(
            buffers, cursor, k_sample, batch_size, sequence_length, int(n_samples)
        )
        p, o_state, metrics = train_phase(p, o_state, prep(blocks), k_train, counter)
        return p, o_state, counter + int(n_samples), metrics

    return fabric.compile(
        fused,
        name=name,
        static_argnames=("n_samples",),
        donate_argnums=(0, 1),
        max_recompiles=max_recompiles,
    )


# --------------------------------------------------------------------------
# on-policy donated staging
# --------------------------------------------------------------------------

def stage_rollout(fabric: Any, tree: Arrays, axis: int, sharded: bool) -> Any:
    """Explicit device staging for on-policy rollout blocks (PPO/A2C family).

    One ``device_put`` per leaf onto the mesh layout — EXPLICIT transfers,
    so a ``steady_guard``-armed train window accepts them — replacing the
    former per-leaf ``jnp.asarray`` (an implicit transfer the guard rejects).
    The staged block is meant to be DONATED into the train phase (its HBM is
    reused for activations) — on-policy loops consume each rollout exactly
    once per dispatch, which is what makes the donation legal."""
    host = {k: np.asarray(v) for k, v in tree.items()}
    if sharded:
        return fabric.shard_batch(host, axis=axis)
    return fabric.replicate(host)


def stage_scalar(value: Any, dtype: Any = np.float32) -> Any:
    """Explicitly staged device scalar (annealed coefficients, counters) —
    ``jnp.float32(x)`` is an implicit transfer under the steady guard."""
    import jax

    return jax.device_put(np.asarray(value, dtype))
