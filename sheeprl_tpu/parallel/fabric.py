"""Single-controller SPMD runtime over a ``jax.sharding.Mesh``.

This is the TPU-native replacement for the reference's Lightning Fabric layer
(reference: sheeprl/configs/fabric/default.yaml and the ``fabric.*`` calls all
over sheeprl/algos/*): device selection, the device mesh, precision policy,
checkpointing callbacks, and host collectives.

Design differences from the reference, on purpose (SURVEY.md §2.2/§7):

* The reference spawns one Python process per device and synchronizes with
  NCCL/Gloo DDP all-reduce.  Here ONE controller process drives all local
  devices: parameters are *replicated* over the mesh, batches are *sharded*
  over the ``data`` axis, and a jitted train step whose loss is a mean over
  the batch makes XLA insert the gradient all-reduce over ICI automatically
  (GSPMD).  There is no process-group bookkeeping to port.
* Multi-host (DCN) uses ``jax.distributed.initialize`` + the same mesh
  spanning all hosts; host-side object exchange (log dirs, configs) rides
  :meth:`broadcast_object` built on ``multihost_utils``.
* "world_size" therefore means the total number of devices in the mesh (the
  data-parallel degree), and "global_rank" the process index — which is what
  the reference uses each for (batch splitting vs. rank-0-only logging).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sheeprl_tpu.telemetry.recorder import RECORDER
from sheeprl_tpu.telemetry.spans import SPANS, span


@dataclass(frozen=True)
class Precision:
    """Maps the reference's Lightning precision strings to JAX dtype policy.

    ``param_dtype`` is the dtype parameters are stored in, ``compute_dtype``
    the dtype activations are computed in (models cast inputs / params at
    call sites).  On TPU, bf16 compute hits the MXU fast path while fp32
    params keep optimizer numerics stable.
    """

    name: str
    param_dtype: Any
    compute_dtype: Any

    @staticmethod
    def from_string(precision: str) -> "Precision":
        table = {
            "32-true": (jnp.float32, jnp.float32),
            "bf16-mixed": (jnp.float32, jnp.bfloat16),
            "bf16-true": (jnp.bfloat16, jnp.bfloat16),
        }
        if precision not in table:
            raise ValueError(f"Unknown precision '{precision}'; choose from {list(table)}")
        param, compute = table[precision]
        return Precision(precision, param, compute)


#: ``fabric.accelerator`` spellings -> JAX platform names
_ACCELERATOR_PLATFORMS = {"tpu": "tpu", "cuda": "gpu", "gpu": "gpu", "cpu": "cpu"}

_AUTO_CHOICE_LOGGED = False


def _resolve_accelerator(accelerator: str) -> str:
    """``auto`` prefers tpu, then gpu, then cpu among the platforms JAX sees
    (logged once per process); anything else names its platform outright."""
    global _AUTO_CHOICE_LOGGED
    if accelerator in ("auto", None):
        platforms = {d.platform for d in jax.devices()}
        chosen = next((p for p in ("tpu", "gpu") if p in platforms), "cpu")
        if not _AUTO_CHOICE_LOGGED:
            _AUTO_CHOICE_LOGGED = True
            print(
                f"[sheeprl_tpu] fabric.accelerator=auto -> {chosen} "
                f"(platforms visible: {sorted(platforms)})",
                flush=True,
            )
        return chosen
    return _ACCELERATOR_PLATFORMS.get(accelerator, accelerator)


#: the one compile-cache location when the environment names none: inside
#: the checkout (git-ignored), resolved from the package's own location so
#: every process of every run agrees on it — the path is part of the cache
#: key's world, a directory that moves never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure_compilation_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache somewhere stable.

    One rule: when ``JAX_COMPILATION_CACHE_DIR`` is in the environment the
    program sets no cache directory in code (JAX reads the variable itself;
    an empty value disables the cache).  Otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`.  JAX's own min-compile-time threshold
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``) keeps tiny programs
    out.  Returns the active directory (None when disabled).
    """
    if (
        "JAX_COMPILATION_CACHE_DIR" not in os.environ
        and not jax.config.jax_compilation_cache_dir
    ):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir or None


class Fabric:
    """Runtime facade handed to every algorithm ``main(fabric, cfg)``."""

    def __init__(
        self,
        devices: Union[int, str] = 1,
        num_nodes: int = 1,
        strategy: str = "auto",
        accelerator: str = "auto",
        precision: str = "32-true",
        callbacks: Optional[Dict[str, Any]] = None,
        mesh_shape: Optional[Dict[str, int]] = None,
        tp_min_param_size: int = 2**18,
        sharding: Optional[Dict[str, Any]] = None,
    ):
        self.strategy = strategy
        self.tp_min_param_size = int(tp_min_param_size)
        #: the ``sharding`` config group (rules table selection, user rules,
        #: undivisible policy, explain flag); resolved lazily into a concrete
        #: rule table by :attr:`sharding_rules`.  A bare ``Fabric(...)`` with
        #: no config keeps the legacy size-threshold behavior.
        self.sharding_cfg: Dict[str, Any] = dict(sharding or {})
        self._sharding_rules: Optional[Tuple[Any, ...]] = None
        self.precision = Precision.from_string(precision)
        self.callbacks: List[Any] = []
        self._callback_cfg = callbacks or {}
        #: set by get_checkpoint_manager once a train loop binds its log_dir
        self.checkpoint_manager: Optional[Any] = None
        ensure_compilation_cache()

        if accelerator == "cpu":
            # make CPU the default backend too (not just the device list), so
            # uncommitted arrays and jitted computations land where the user
            # asked even when the process also sees an accelerator
            jax.config.update("jax_platforms", "cpu")
        platform = _resolve_accelerator(accelerator)
        try:
            all_devices = jax.devices(platform)
        except RuntimeError as e:
            # no fallback: an explicit accelerator that is not there is an
            # error, never a silent run on whatever else JAX found
            seen = sorted({d.platform for d in jax.devices()})
            raise RuntimeError(
                f"fabric.accelerator='{accelerator}' requested, but JAX sees no "
                f"'{platform}' device in this process (platforms visible: {seen}; "
                f"jax_platforms={jax.config.jax_platforms!r} — JAX_PLATFORMS or an "
                "earlier Fabric(accelerator='cpu') in this process pins it)"
            ) from e
        if devices in ("auto", -1, "-1", None):
            n = len(all_devices)
        else:
            n = int(devices)
        if n > len(all_devices):
            raise ValueError(
                f"Requested {n} devices but only {len(all_devices)} {platform} devices exist"
            )
        self.devices: List[Any] = all_devices[:n]
        self.accelerator = platform

        # Mesh: default a single "data" axis (DDP semantics).  mesh_shape may
        # request extra axes, e.g. {"data": -1, "model": 2} for TP sharding.
        if mesh_shape:
            names = tuple(mesh_shape.keys())
            sizes = list(mesh_shape.values())
            minus = [i for i, s in enumerate(sizes) if s in (-1, None)]
            fixed = int(np.prod([s for s in sizes if s not in (-1, None)])) or 1
            if minus:
                sizes[minus[0]] = n // fixed
            dev_array = np.asarray(self.devices).reshape(tuple(int(s) for s in sizes))
            self.mesh = Mesh(dev_array, names)
        else:
            self.mesh = Mesh(np.asarray(self.devices), ("data",))
        self.data_axis = self.mesh.axis_names[0]

    # -- topology ---------------------------------------------------------
    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def local_world_size(self) -> int:
        """Mesh devices owned by THIS process.  Data sizing must use this,
        not ``world_size``: under multi-host, each process contributes its
        own local shard and ``shard_batch`` assembles the global batch from
        the per-process locals — sampling ``per_rank * world_size`` rows per
        process would multiply the global batch by ``num_processes``.
        Single-process, this equals ``world_size``."""
        me = jax.process_index()
        return int(sum(1 for d in self.mesh.devices.flat if d.process_index == me))

    @property
    def global_rank(self) -> int:
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    @property
    def is_global_zero(self) -> bool:
        return self.global_rank == 0

    @property
    def device(self) -> Any:
        return self.devices[0]

    @property
    def host_device(self) -> Any:
        """The host (CPU) device used for the env-interaction "player" copy
        of the policy.  A per-env-step device round-trip (dispatch + D2H of
        the action) is never free; inference for action selection runs on
        host and the train step refreshes the host params once per
        iteration — the single-process analogue of the reference's decoupled
        player/trainer split (reference: sheeprl/algos/ppo/ppo_decoupled.py).

        Needs the CPU backend next to the accelerator: a process started with
        ``JAX_PLATFORMS=tpu`` has none (use ``tpu,cpu`` or leave it unset)."""
        try:
            return jax.local_devices(backend="cpu")[0]
        except RuntimeError as e:
            raise RuntimeError(
                "the host player needs JAX's CPU backend, which this process "
                f"did not initialize (jax_platforms={jax.config.jax_platforms!r}): "
                "start it with JAX_PLATFORMS unset or 'tpu,cpu', or set "
                "algo.player.device=accelerator"
            ) from e

    def to_host(self, tree: Any) -> Any:
        """Copy a pytree to the host CPU device (one bulk transfer)."""
        return self.copy_to(tree, self.host_device)

    def copy_to(self, tree: Any, device: Any, into: Any = None) -> Any:
        """Copy a pytree onto ``device``.

        ALWAYS a real copy: when the source already lives on the target
        device, ``device_put`` would be a no-op alias — and the training
        step donates its params input, which would invalidate the player's
        copy mid-rollout.  A tree that lives on the target whole is copied
        by ONE compiled program (``_copy_tree``: one dispatch where a
        ``.copy()`` per leaf made 75 for DV3-S, 24 ms on a v5e's host); a
        lone such leaf among others still takes ``.copy()``.  ``into``, a
        tree the caller is done with (the player's previous copy), is
        donated to that program where it lives on the target too: the new
        copy is written into its buffers and the caller must drop it.  On
        a v5e's host every fresh output buffer costs the dispatch 45 us,
        so the refresh of DV3-S's 75 leaves is 0.5 ms into the old copy
        and 3.6 ms into new memory (PERF.md section 6, PR 31).

        Cross-platform trees (the host-player param pull) take the PACKED
        path: per-leaf transfers cost one D2H round-trip each (a player tree
        has ~40 leaves), so same-dtype leaves are flattened into one
        device-side buffer per dtype, moved in one transfer, and split on
        the target.
        """
        # chaos-drill injection site: raise simulates a failed param pull,
        # latency a slow one (no-op unless a fault plan targets
        # fabric.copy_to)
        from sheeprl_tpu.resilience.faults import fault_point

        fault_point("fabric.copy_to")
        leaves, treedef = jax.tree.flatten(tree)
        if leaves and _lives_on(leaves, device):
            # the whole tree already lives on the target (a player beside the
            # train state): one executable copies every leaf, nothing leaves
            # the device
            old_leaves, old_def = jax.tree.flatten(into)
            if old_def == treedef and _lives_on(old_leaves, device):
                return _copy_tree_into(tree, into)
            return _copy_tree(tree)
        if all(isinstance(x, jax.Array) and x.is_fully_addressable for x in leaves):
            # replicated multi-device params (any real mesh) carry the full
            # value in every shard — pack from the process-local one
            single = [
                x if len(x.devices()) == 1
                else (x.addressable_shards[0].data if x.sharding.is_fully_replicated else None)
                for x in leaves
            ]
            src = {next(iter(x.devices())) for x in single if x is not None}
            if (
                len(leaves) > 1
                and all(x is not None for x in single)
                and len(src) == 1
                and next(iter(src)).platform != device.platform
            ):
                return treedef.unflatten(_packed_copy(single, device))

        def put(x: Any) -> Any:
            if isinstance(x, jax.Array) and not x.is_fully_addressable:
                # multi-host global array: device_put rejects it.  Replicated
                # arrays (params) carry the FULL value in every local shard —
                # copy from the process-local one.
                if not x.sharding.is_fully_replicated:
                    raise ValueError(
                        "copy_to got a non-replicated multi-host array; only "
                        "replicated (player/param) trees can be copied to a "
                        "single device"
                    )
                x = x.addressable_shards[0].data
            if isinstance(x, jax.Array) and x.committed and set(x.devices()) == {device}:
                return x.copy()
            out = jax.device_put(x, device)
            if (
                isinstance(x, jax.Array)
                and len(x.devices()) > 1
                and next(iter(x.devices())).platform == device.platform
            ):
                # same-platform mesh → single device: device_put may be a
                # ZERO-COPY alias of the shard already living on `device`
                # (it shares shard 0's buffer pointer on jax 0.9.0).  The
                # train step donates the source params, which would
                # invalidate the player's "copy" mid-rollout — break the
                # alias.  Cross-platform transfers (the TPU→host pull) always
                # materialize and skip this extra dispatch.
                out = out.copy()
            return out

        return jax.tree.map(put, tree)

    def player_device(self, cfg: Any, refresh_bytes: int = 0) -> Any:
        """The device the env-interaction player runs on.

        ``algo.player.device=host`` pins rollout inference to the host CPU;
        ``accelerator`` runs it on the first process-local mesh device.  Left
        at ``auto`` (the default) the code decides from ``refresh_bytes``,
        the weights one refresh moves (``PlayerSync`` counts them): above
        ``PLAYER_PULL_BYTES`` the player runs beside the train state and its
        refresh never leaves the device, below it on the host.  Measured on
        a v5e (PERF.md section 6, PR 31): DV3-S pulled 67 MB in 54 ms every
        iteration of 140; beside the train state the refresh is 1.2 ms and
        the iteration 93 (27.3 -> 42.1 env-steps/s on one machine).

        Without a count the answer is the host: the on-policy and decoupled
        loops call this with ``cfg`` alone and keep the host player (their
        rollouts need the current weights in one transfer, and none of them
        was measured on the chip)."""
        choice = (cfg.algo.get("player", {}) or {}).get("device", "auto")
        if choice not in ("auto", "host", "accelerator"):
            raise ValueError(
                f"algo.player.device must be 'auto', 'host' or 'accelerator', got {choice!r}"
            )
        if choice == "auto":
            choice = "accelerator" if refresh_bytes > PLAYER_PULL_BYTES else "host"
        if choice == "accelerator":
            # PROCESS-LOCAL first device: self.device is globally enumerated
            # and non-addressable from worker hosts in multi-host runs (the
            # on-pod scenario this option exists for)
            local = [d for d in jax.local_devices() if d.platform == self.accelerator]
            return local[0] if local else self.device
        return self.host_device

    # -- sharding helpers --------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    @property
    def batch_sharded(self) -> NamedSharding:
        """Shard the leading axis over the data axis of the mesh."""
        return NamedSharding(self.mesh, P(self.data_axis))

    def shard_batch(self, tree: Any, axis: int = 0) -> Any:
        """Place a host batch on device, split along ``axis`` over the mesh.

        Single-process: a plain ``device_put`` onto the mesh-wide sharding.
        Multi-host (DCN): each process holds its *own* locally-sampled shard,
        and ``device_put`` onto a non-fully-addressable sharding is not the
        sanctioned path — assemble the global array from per-process locals
        via ``multihost_utils.host_local_array_to_global_array`` instead.
        """
        multi_host = self.num_processes > 1
        if multi_host:
            from jax.experimental import multihost_utils

        def put(x: Any) -> Any:
            spec = [None] * np.ndim(x)
            if np.ndim(x) > axis:
                # validate HERE, not in XLA: an indivisible batch used to
                # surface as an opaque "sharding ... is not divisible" deep
                # inside device_put/compile
                if not multi_host:
                    n = int(self.mesh.shape[self.data_axis])
                    dim = int(np.shape(x)[axis])
                    if dim % n != 0:
                        raise ValueError(
                            f"shard_batch: leaf of shape {np.shape(x)} cannot "
                            f"shard axis {axis} ({dim} rows) over the "
                            f"'{self.data_axis}' mesh axis ({n} devices); batch/"
                            f"env counts must be multiples of the data-parallel "
                            f"degree (mesh {dict(self.mesh.shape)})"
                        )
                spec[axis] = self.data_axis
            pspec = P(*spec)
            if multi_host:
                return multihost_utils.host_local_array_to_global_array(
                    np.asarray(x), self.mesh, pspec
                )
            return jax.device_put(x, NamedSharding(self.mesh, pspec))

        return jax.tree.map(put, tree)

    def replicate(self, tree: Any) -> Any:
        """Replicate a pytree (params/opt state) across the mesh."""
        return jax.device_put(tree, self.replicated)

    # -- tensor parallelism ------------------------------------------------
    @property
    def model_axis(self) -> Optional[str]:
        """Name of the tensor-parallel mesh axis, or None when the mesh has
        no ``model`` axis of size > 1 (``fabric.mesh_shape={data: -1, model: k}``)."""
        if "model" in self.mesh.axis_names and self.mesh.shape["model"] > 1:
            return "model"
        return None

    @property
    def pipeline_axis(self) -> Optional[str]:
        """Name of the pipeline mesh axis, or None when the mesh has no
        ``pipeline`` axis of size > 1
        (``fabric.mesh_shape={data: d, pipeline: s, model: k}`` — the stage
        sub-groups of parallel/pipeline.py, docs/pipeline.md)."""
        if "pipeline" in self.mesh.axis_names and self.mesh.shape["pipeline"] > 1:
            return "pipeline"
        return None

    @property
    def sharding_rules(self) -> Tuple[Any, ...]:
        """The resolved partition-rule table (``parallel/sharding.py``):
        user ``sharding.rules`` overrides prepended to the selected base
        table — the per-algo curated table under ``table: auto`` (DreamerV3
        family: RSSM dense stacks, decoder deconvs, actor/critic MLPs), or
        the legacy size-threshold fallback parameterized by the
        ``tp_min_param_size`` compat knob.  With a ``pipeline`` mesh axis
        the table is composed through
        :func:`sheeprl_tpu.parallel.pipeline.compose_pipeline_rules`: every
        model-sharded dim tiles over the ``(pipeline, model)`` product so
        each stage sub-group owns its weight slice."""
        if self._sharding_rules is None:
            from sheeprl_tpu.parallel.sharding import resolve_rules

            rules = resolve_rules(
                self.sharding_cfg, tp_min_param_size=self.tp_min_param_size
            )
            if self.pipeline_axis is not None:
                from sheeprl_tpu.parallel.pipeline import compose_pipeline_rules

                rules = compose_pipeline_rules(
                    rules,
                    pipeline_axis=self.pipeline_axis,
                    has_model=self.model_axis is not None,
                )
            self._sharding_rules = rules
        return self._sharding_rules

    def param_sharding(
        self, tree: Any, min_size: Optional[int] = None, rules: Optional[Any] = None
    ) -> Any:
        """Per-leaf ``NamedSharding``s for a param-shaped pytree, resolved
        through :func:`sheeprl_tpu.parallel.sharding.match_partition_rules`
        over :attr:`sharding_rules` (regex on tree path → ``PartitionSpec``,
        first match wins, unmatched/scalar leaves replicate over the whole
        mesh).  GSPMD propagates the annotations through the train step and
        inserts the matching collectives (scaling-book recipe: annotate
        weights, let XLA place the all-gathers/psums).

        With no ``model`` axis every leaf is replicated, so this is a strict
        generalization of ``replicate``.  Every produced spec is validated
        against the mesh up front (axis exists, dims divide) — the
        ``sharding.undivisible`` policy decides between a clear error and a
        demotion to replicated; XLA never sees an unplaceable spec.

        ``min_size`` is the ``tp_min_param_size`` compat hook: passing it
        explicitly selects the legacy size-threshold table at that
        threshold, bypassing the configured rules."""
        if self.model_axis is None and self.pipeline_axis is None:
            return jax.tree.map(lambda _: self.replicated, tree)
        if self.num_processes > 1:
            # the player-sync path (copy_to/to_host) materializes params on
            # one device from the process-local replica — a column-sharded
            # array has no such replica across hosts.  Multi-host TP/PP needs
            # a gather-to-host protocol; fail with the fix spelled out
            # instead of crashing at the first player refresh.
            raise NotImplementedError(
                "model sharding (fabric.mesh_shape with a 'model' or 'pipeline' "
                "axis) is currently single-controller only; multi-host runs "
                "must use a pure data mesh (drop mesh_shape or set model: 1 "
                "and pipeline: 1)"
            )
        from sheeprl_tpu.parallel import sharding as shd

        if rules is None:
            rules = (
                shd.size_threshold_rules(int(min_size))
                if min_size is not None
                else self.sharding_rules
            )
        undivisible = str(self.sharding_cfg.get("undivisible", "replicate"))
        specs = shd.partition_specs(rules, tree, self.mesh, undivisible=undivisible)
        if self.sharding_cfg.get("explain"):
            self.print(shd.explain(rules, tree, self.mesh, undivisible=undivisible))
        return shd.named_sharding_tree(self.mesh, specs)

    def shard_params(
        self, tree: Any, min_size: Optional[int] = None, rules: Optional[Any] = None
    ) -> Any:
        """Place a param-shaped pytree per ``param_sharding``.  Also correct
        for optimizer states: Adam/RMSProp moments live under tree paths
        containing the same module/kernel suffix their params do, so the
        same regex rules place them consistently with their params."""
        return jax.device_put(tree, self.param_sharding(tree, min_size, rules))

    def explain_sharding(self, tree: Any, title: str = "partition rules") -> str:
        """Human-readable resolved spec per leaf (``sharding.explain`` and
        interactive debugging): which rule matched, what got demoted, what
        stays replicated."""
        from sheeprl_tpu.parallel import sharding as shd

        return shd.explain(
            self.sharding_rules,
            tree,
            self.mesh,
            undivisible=str(self.sharding_cfg.get("undivisible", "replicate")),
            title=title,
        )

    def setup_module(self, tree: Any) -> Any:  # reference-API parity alias
        return self.replicate(tree)

    def jit(
        self,
        fn: Callable,
        in_shardings: Any = None,
        out_shardings: Any = None,
        donate_argnums: Tuple[int, ...] = (),
        static_argnums: Tuple[int, ...] = (),
    ) -> Callable:
        """``jax.jit`` bound to this fabric's mesh."""
        return jax.jit(
            fn,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=donate_argnums,
            static_argnums=static_argnums,
        )

    def compile(
        self,
        fn: Callable,
        *,
        name: Optional[str] = None,
        static_argnums: Tuple[int, ...] = (),
        static_argnames: Tuple[str, ...] = (),
        donate_argnums: Tuple[int, ...] = (),
        in_shardings: Any = None,
        out_shardings: Any = None,
        max_recompiles: Optional[int] = None,
    ) -> Any:
        """The compile-once entry point (see ``parallel/compile.py``):
        returns an :class:`~sheeprl_tpu.parallel.compile.AOTFunction` whose
        executables are AOT-lowered/compiled per abstract signature, counted
        in the recompile detector, and warmable from :attr:`compile_pool`.
        Drop-in replacement for decorating ``fn`` with ``jax.jit``.

        ``in_shardings``/``out_shardings`` take ``NamedSharding`` pytrees
        (``None`` entries = unspecified).  Train phases pass their param and
        opt-state sharding trees on both sides plus ``donate_argnums`` so the
        partition-rules placement is pinned across updates and the state is
        updated in place — build the tuples with
        :func:`sheeprl_tpu.parallel.compile.state_io_shardings`."""
        from sheeprl_tpu.parallel.compile import compile_once

        return compile_once(
            fn,
            name=name,
            static_argnums=static_argnums,
            static_argnames=static_argnames,
            donate_argnums=donate_argnums,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            max_recompiles=max_recompiles,
        )

    @property
    def compile_pool(self) -> Any:
        """Process-wide parallel compile warm-up pool (lazily created)."""
        from sheeprl_tpu.parallel.compile import get_compile_pool

        return get_compile_pool()

    # -- host collectives --------------------------------------------------
    #
    # Two transports:
    # * TPU pods: XLA collectives over ICI/DCN via ``multihost_utils`` —
    #   native, fast, and the path real deployments exercise.
    # * CPU multiprocess (the test rig): the ``jax.distributed``
    #   coordination-service KV store.  XLA-CPU gloo collectives silently
    #   zero-fill payloads when the host is CPU-oversubscribed (observed on
    #   a 2-core container: the int64 length psum lands, the back-to-back
    #   uint8 payload psum arrives all-zero on non-source ranks, no error
    #   raised) — host OBJECT exchange is control-plane traffic, which the
    #   coordination service transports reliably over gRPC.
    _kv_seq: int = 0

    def _coordination_client(self) -> Any:
        """The jax.distributed KV client when host objects should ride it
        (CPU backend + real multiprocess), else None."""
        if self.num_processes == 1 or self.accelerator != "cpu":
            return None
        from jax._src import distributed

        if distributed.global_state.client is None:
            return None
        # the thread-safe wrapper: raw client calls from two threads (the
        # PeerWatchdog beating during a host collective) segfault
        from sheeprl_tpu.parallel.distributed import _SafeKV

        return _SafeKV(distributed.global_state.client)

    @staticmethod
    def _kv_timeout_ms() -> int:
        # generous: a trainer blocks here for a full player rollout in the
        # dedicated decoupled topology
        return int(float(os.environ.get("SHEEPRL_KV_TIMEOUT_S", 600)) * 1000)

    def _next_kv_seq(self) -> int:
        # collective calls execute in the same order on every rank, so a
        # per-rank counter stays in lockstep and namespaces each exchange
        seq, self._kv_seq = self._kv_seq, self._kv_seq + 1
        return seq

    def _kv_all_gather(self, client: Any, obj: Any) -> List[Any]:
        seq, timeout = self._next_kv_seq(), self._kv_timeout_ms()
        prefix = f"sheeprl_tpu/ag/{seq}"
        mine = f"{prefix}/{self.global_rank:08d}"
        client.key_value_set_bytes(mine, bytes(_pickle_to_u8(obj).tobytes()))
        out = [
            _u8_to_obj(
                np.frombuffer(
                    client.blocking_key_value_get_bytes(f"{prefix}/{r:08d}", timeout),
                    dtype=np.uint8,
                )
            )
            for r in range(self.num_processes)
        ]
        # every rank has read every entry once the barrier clears; each rank
        # deletes its own key so the KV store stays bounded on long runs
        client.wait_at_barrier(f"{prefix}/done", timeout)
        client.key_value_delete(mine)
        return out

    def _kv_broadcast(self, client: Any, obj: Any, src: int) -> Any:
        seq, timeout = self._next_kv_seq(), self._kv_timeout_ms()
        key = f"sheeprl_tpu/bc/{seq}"
        if self.global_rank == src:
            client.key_value_set_bytes(key, bytes(_pickle_to_u8(obj).tobytes()))
            out = obj
        else:
            out = _u8_to_obj(
                np.frombuffer(
                    client.blocking_key_value_get_bytes(key, timeout), dtype=np.uint8
                )
            )
        client.wait_at_barrier(f"{key}/done", timeout)
        if self.global_rank == src:
            client.key_value_delete(key)
        return out

    def all_gather_object(self, obj: Any) -> List[Any]:
        if self.num_processes == 1:
            return [obj]
        client = self._coordination_client()
        if client is not None:
            return self._kv_all_gather(client, obj)
        from jax.experimental import multihost_utils

        payload = _pickle_to_u8(obj)
        # process_allgather needs equal shapes: agree on max length, pad.
        lengths = multihost_utils.process_allgather(
            np.asarray([payload.size], dtype=np.int64)
        ).reshape(-1)
        max_len = int(lengths.max())
        padded = np.zeros(max_len, dtype=np.uint8)
        padded[: payload.size] = payload
        gathered = multihost_utils.process_allgather(padded)
        return [
            _u8_to_obj(np.asarray(row[: int(n)]))
            for row, n in zip(np.atleast_2d(gathered), lengths)
        ]

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        if self.num_processes == 1:
            return obj
        client = self._coordination_client()
        if client is not None:
            return self._kv_broadcast(client, obj, src)
        from jax.experimental import multihost_utils

        is_source = self.global_rank == src
        payload = _pickle_to_u8(obj) if is_source else None
        # broadcast_one_to_all sources from process 0 unless told otherwise —
        # src != 0 (e.g. the trainer→player weight refresh of the dedicated
        # decoupled topology) must pass is_source explicitly
        length = multihost_utils.broadcast_one_to_all(
            np.asarray([0 if payload is None else payload.size], dtype=np.int64),
            is_source=is_source,
        )[0]
        buf = payload if payload is not None else np.zeros(int(length), dtype=np.uint8)
        out = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
        if is_source:
            # skip re-deserializing our own payload (sync-A rollouts are
            # ~100MB/iteration in the dedicated decoupled topology)
            return obj
        return _u8_to_obj(np.asarray(out))

    def barrier(self) -> None:
        if self.num_processes > 1:
            client = self._coordination_client()
            if client is not None:
                client.wait_at_barrier(
                    f"sheeprl_tpu/barrier/{self._next_kv_seq()}", self._kv_timeout_ms()
                )
                return
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("sheeprl_tpu_barrier")

    # -- checkpoint callbacks ---------------------------------------------
    def register_callback(self, callback: Any) -> None:
        self.callbacks.append(callback)

    def call(self, hook: str, **kwargs: Any) -> None:
        for cb in self.callbacks:
            fn = getattr(cb, hook, None)
            if fn is not None:
                fn(fabric=self, **kwargs)

    # -- persistence -------------------------------------------------------
    def get_checkpoint_manager(self, cfg: Any, log_dir: Union[str, os.PathLike]) -> Any:
        """The run's :class:`~sheeprl_tpu.checkpoint.CheckpointManager`,
        created on first call (train loops bind it right after resolving
        their ``log_dir``) and cached on the fabric so the checkpoint
        callback can reach it through ``fabric.checkpoint_manager``."""
        if self.checkpoint_manager is None:
            from sheeprl_tpu.checkpoint import CheckpointManager

            self.checkpoint_manager = CheckpointManager(self, cfg, log_dir)
        return self.checkpoint_manager

    def save(self, path: Union[str, os.PathLike], state: Dict[str, Any]) -> None:
        """Legacy single-file save (rank 0 only + barrier).  Train loops now
        checkpoint through the manager/commit protocol instead; this remains
        for tests, tools, and external callers."""
        from sheeprl_tpu.utils.checkpoint import save_checkpoint

        if self.is_global_zero:
            save_checkpoint(path, state)
        self.barrier()

    def load(self, path: Union[str, os.PathLike]) -> Dict[str, Any]:
        """Load a legacy ``.ckpt`` file or a committed snapshot directory
        (this rank's shard, falling back to shard 0)."""
        from sheeprl_tpu.utils.checkpoint import load_checkpoint

        with SPANS.setup_span("setup.resume"):
            return load_checkpoint(path, rank=self.global_rank)

    # -- misc ---------------------------------------------------------------
    def print(self, *args: Any, **kwargs: Any) -> None:
        if self.is_global_zero:
            print(*args, **kwargs)

    def seed_everything(self, seed: int) -> jax.Array:
        """Seed host RNGs PER-RANK and return the SHARED jax key.

        The returned key seeds agent init and the train-dispatch stream,
        which must be identical on every process: replicated inputs of the
        global program (params, train keys) have to agree across ranks.
        Host-side RNG (replay sampling, random prefill actions) must DIFFER
        per rank or multi-host data parallelism collects/samples the same
        data ``num_processes`` times.  Per-rank player sampling keys are
        derived in the loops via ``fold_in(key, global_rank)``."""
        np.random.seed(seed + self.global_rank)
        import random

        random.seed(seed + self.global_rank)
        return jax.random.PRNGKey(seed)

    def env_sharding_plan(self, num_envs: int, algo: str = "") -> Tuple[bool, int]:
        """Whether per-rank env rollouts can shard over the data axis, and
        the GLOBAL env count the train program then sees.  Multi-host
        requires shardability — validated here ONCE, before any rollout is
        collected."""
        sharded = num_envs % self.local_world_size == 0
        if not sharded and self.num_processes > 1:
            raise ValueError(
                f"multi-host {algo or 'training'} requires env.num_envs "
                f"({num_envs}) divisible by the local device count "
                f"({self.local_world_size})"
            )
        return sharded, num_envs * (self.num_processes if sharded else 1)


class PlayerSync:
    """Overlap env interaction with (async-dispatched) device training.

    JAX dispatches the train phase asynchronously; what serializes the loop
    is pulling the fresh params to the player right after the dispatch — the
    next ``player_step`` then blocks on the whole train phase.  In deferred
    mode the pull happens at the START of the next optimization window
    instead: the env steps of window N+1 run on window N-1's weights while
    the device trains window N — the single-controller analogue of the
    reference's decoupled trainer→player broadcast
    (reference: sheeprl/algos/ppo/ppo_decoupled.py:32-365,
    sac_decoupled.py:250-305).  With ``sync_every=1`` that is one training
    window of weight staleness — the decoupled topology's semantics; set
    ``algo.player.deferred_sync=False`` for the strict coupled behavior.

    ``sync_every`` additionally rate-limits refreshes to every k-th
    TRAINING window (``algo.player.sync_every``, sac_decoupled sets 10);
    the player then acts on weights up to k (+1 when deferred) training
    windows old — the reference's player↔trainer refresh cadence.

    Where the player lives is decided here, once, from ``params``: the
    builder hands over the train state's params (or their shapes) and
    :meth:`Fabric.player_device` weighs ``extract(params)``, the tree every
    refresh moves, against ``PLAYER_PULL_BYTES`` unless
    ``algo.player.device`` pins the answer.  Beside the train state the
    refresh is one on-device tree copy (``Fabric.copy_to``) under the same
    protocol — deferred, cadence, staleness — so the player acts on the
    weights it acted on when they crossed to the host; the ``bytes`` of the
    ``player.sync`` span count only what crosses.  The decision is printed
    once and recorded as the ``player.placement`` recorder event.
    """

    def __init__(
        self, fabric: "Fabric", cfg: Any, extract: Callable[[Any], Any], params: Any = None
    ):
        player_cfg = cfg.algo.get("player", {}) or {}
        self.fabric = fabric
        self.extract = extract
        # `params` (the train state's, or their shapes) is what lets
        # `algo.player.device=auto` decide: the bytes one refresh moves
        refresh_bytes = 0 if params is None else tree_bytes(extract(params))
        self.device = fabric.player_device(cfg, refresh_bytes)
        self.deferred = bool(player_cfg.get("deferred_sync", True))
        self.sync_every = max(1, int(player_cfg.get("sync_every", 1)))
        if params is not None:
            placed = {
                "device": str(self.device),
                "asked": player_cfg.get("device", "auto"),
                "tree_bytes": refresh_bytes,
                "threshold_bytes": PLAYER_PULL_BYTES,
            }
            RECORDER.record("player.placement", **placed)
            fabric.print(
                "player on {device} (algo.player.device={asked}): a refresh moves {tree_bytes} bytes, "
                "the player leaves the host above {threshold_bytes}".format(**placed)
            )
        self._pending: Any = None
        self._windows = 0  # completed training windows (dispatches)
        # staleness accounting (ISSUE 12 satellite): which window produced
        # the weights the player is CURRENTLY acting with (0 = init params)
        self._player_version = 0
        self._pending_version = 0
        self.staleness_max = 0

    def init(self, params: Any) -> Any:
        self._player_version = self._windows
        self._pending = None
        return self.fabric.copy_to(self.extract(params), self.device)

    @property
    def staleness(self) -> int:
        """Completed training windows the player's weights are behind —
        the deferred-sync/cadence staleness, previously invisible.  Bound:
        ``sync_every - 1`` with immediate sync (the off-cadence windows
        before each refresh), ``sync_every`` deferred (the pending params
        land one ``before_dispatch`` later)."""
        return self._windows - self._player_version

    def _observe_staleness(self) -> None:
        self.staleness_max = max(self.staleness_max, self.staleness)

    def metrics(self) -> Dict[str, float]:
        """``Player/*`` staleness gauges for ``flush_metrics`` callers."""
        return {
            "Player/param_staleness_windows": float(self.staleness),
            "Player/param_staleness_max": float(self.staleness_max),
        }

    def _pull(self, token: Any, params: Any, player_params: Any) -> Any:
        """The refresh itself.  ``bytes`` on the open ``player.sync`` span count
        what crosses to the host: the tree's bytes where the player's platform
        is not the train state's, none where the player sits beside it (there
        the copy is written into ``player_params``, which the caller drops)."""
        tree = self.extract(params)
        if token is not None and self.device.platform != self.fabric.accelerator:
            token.count(bytes=tree_bytes(tree))
        return self.fabric.copy_to(tree, self.device, into=player_params)

    def before_dispatch(self, player_params: Any) -> Any:
        """Pull the previous window's (long since finished) train output.
        Rebind the player's tree to what this returns, as with
        ``after_dispatch``: a refresh may write into the old one's buffers."""
        with span("player.sync", phase=False) as token:
            if self._pending is not None:
                pending, self._pending = self._pending, None
                self._player_version = self._pending_version
                self._observe_staleness()
                return self._pull(token, pending, player_params)
            self._observe_staleness()
            return player_params

    def after_dispatch(self, params: Any, player_params: Any) -> Any:
        with span("player.sync", phase=False) as token:
            return self._after_dispatch(token, params, player_params)

    def _after_dispatch(self, token: Any, params: Any, player_params: Any) -> Any:
        # Gate on COMPLETED TRAINING WINDOWS, not the env-loop update counter:
        # with a fractional replay_ratio the Ratio governor fires training on
        # a fixed update parity, and an `update % sync_every` gate can then
        # systematically never coincide with a training update (player runs
        # on init weights forever).
        self._windows += 1
        if self._windows % self.sync_every != 0:
            self._observe_staleness()
            return player_params
        if self.deferred:
            self._pending = params
            self._pending_version = self._windows
            self._observe_staleness()
            return player_params
        self._player_version = self._windows
        self._observe_staleness()
        return self._pull(token, params, player_params)

    # -- checkpointing ------------------------------------------------------
    def state_dict(self) -> Dict[str, int]:
        """Cadence position, so a resumed run keeps FUTURE refreshes on the
        same training-window parity as an uninterrupted one.  ``_pending``
        is deliberately NOT saved: ``init`` on resume starts the player from
        the checkpointed (latest) params — so at the resume point itself the
        player is one refresh AHEAD of an uninterrupted run (which would
        still act on the last on-cadence weights); exact mid-interval
        staleness is not reproduced, only the refresh schedule."""
        return {"windows": self._windows}

    def load_state_dict(self, state: Dict[str, int]) -> None:
        self._windows = int(state.get("windows", 0))
        # resume starts the player from the checkpointed (latest) params —
        # see state_dict: staleness restarts at zero, only cadence persists
        self._player_version = self._windows
        self._pending = None


# Where `algo.player.device=auto` moves the player off the host: the bytes one
# refresh would pull.  The pull runs with the chip drained at 1.3 GB/s of a
# v5e's host (DV3-S: 67 MB in 54 ms).  Beside the train state the refresh is
# 1.2 ms, but every player step queues behind the train dispatch, so what the
# host did under that dispatch it now does after it.  In dv3s_forage_coupled,
# whose envs step on the chip and waited for the train dispatch already, that is
# the player step, its copies and the ring write: the iteration less the pull
# is 85 ms on the host and 92 beside the train state, 7 ms or 9 MB of pull
# (PERF.md section 6, PR 31).  With envs stepped on the host it is all the
# loop does between two dispatches, 25 to 35 ms there: 32 to 45 MB.  The lower
# end of that, so that no preset smaller than the one measured (DV3-S) moves
# unless its pull costs more than the most a host player can win back.
PLAYER_PULL_BYTES = 32 * 2**20


def tree_bytes(tree: Any) -> int:
    """Bytes of a pytree of arrays or of their shapes (``jax.ShapeDtypeStruct``)."""
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))


def _lives_on(leaves: Any, device: Any) -> bool:
    return all(
        isinstance(x, jax.Array) and x.committed and set(x.devices()) == {device} for x in leaves
    )


@jax.jit
def _copy_tree(tree: Any) -> Any:
    """Every leaf copied on the device it lives on, in one executable.  Real
    copies: the inputs are not donated, so XLA gives each output a buffer of
    its own (``jnp.copy`` keeps jit from forwarding the input array itself)."""
    return jax.tree.map(jnp.copy, tree)


@functools.partial(jax.jit, donate_argnums=1, keep_unused=True)
def _copy_tree_into(tree: Any, into: Any) -> Any:
    """``_copy_tree`` written into the donated buffers of ``into`` (same
    shapes): no output buffer is allocated.  ``keep_unused`` keeps the
    donated argument, which the computation never reads, in the executable."""
    return jax.tree.map(jnp.copy, tree)


def _packed_copy(leaves: Any, device: Any) -> Any:
    """Move a flat list of same-device arrays to ``device`` in ONE transfer
    per dtype: flatten+concatenate on the SOURCE device (one fused program),
    ship the packed buffer, split+reshape on the target.  Values are
    bit-identical to per-leaf ``device_put`` (no casts — leaves group by
    exact dtype, and weak-typed leaves go per-leaf: concatenate would
    strip weak_type and change downstream promotion).
    See ``Fabric.copy_to`` for why this exists."""
    by_dtype: Dict[Any, list] = {}
    for i, x in enumerate(leaves):
        by_dtype.setdefault((x.dtype, bool(getattr(x, "weak_type", False))), []).append(i)
    out: list = [None] * len(leaves)
    for (dtype, weak), idxs in by_dtype.items():
        if len(idxs) == 1 or weak:
            for i in idxs:
                out[i] = jax.device_put(leaves[i], device)
            continue
        packed = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        packed = jax.device_put(packed, device)
        offset = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = packed[offset : offset + n].reshape(leaves[i].shape)
            offset += n
    return out


def _pickle_to_u8(obj: Any) -> np.ndarray:
    import pickle

    return np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()


def _u8_to_obj(arr: np.ndarray) -> Any:
    import pickle

    return pickle.loads(arr.tobytes())


# process-wide latch for the tp_min_param_size deprecation notice
_TP_MIN_PARAM_SIZE_WARNED = False


def build_fabric(cfg: Any) -> Fabric:
    """Instantiate the runtime from ``cfg.fabric`` (+ register callbacks)."""
    global _TP_MIN_PARAM_SIZE_WARNED
    fab_cfg = cfg.fabric
    # distributed init FIRST: jax.distributed.initialize must run before
    # the first backend touch (Fabric.__init__ calls jax.devices()), or the
    # process binds a single-host backend and can never join the pod
    from sheeprl_tpu.parallel.distributed import ensure_distributed

    ensure_distributed(cfg)
    if "tp_min_param_size" in fab_cfg and not _TP_MIN_PARAM_SIZE_WARNED:
        # fire ONCE per process, not per build_fabric call: long runs build
        # fabrics repeatedly (supervisor relaunch probes, player clones) and
        # a per-call DeprecationWarning floods the log —
        # and "default"-filtered warnings dedupe per call SITE, which this
        # single callsite defeats.  Pinned by
        # tests/test_sharding/test_deprecation.py.  In a pod, only rank 0
        # speaks: the knob is global config, so N hosts repeating the same
        # deprecation is noise (rank_zero_warn also latches per-process).
        from sheeprl_tpu.parallel.distributed import rank_zero_warn

        _TP_MIN_PARAM_SIZE_WARNED = True
        rank_zero_warn(
            "fabric.tp_min_param_size is deprecated: parameter placement is "
            "now decided by the sharding rules engine (sharding.rules / "
            "sharding.table, see docs/sharding.md). The knob still "
            "parameterizes the legacy 'size_threshold' fallback table only.",
            DeprecationWarning,
            key="fabric.tp_min_param_size",
        )
    # the sharding config group travels with the algo name so `table: auto`
    # can resolve the curated per-algo rule table at first use
    sharding_cfg = dict(cfg.get("sharding") or {})
    sharding_cfg.setdefault("algo", (cfg.get("algo") or {}).get("name"))
    fabric = Fabric(
        devices=fab_cfg.get("devices", 1),
        num_nodes=fab_cfg.get("num_nodes", 1),
        strategy=fab_cfg.get("strategy", "auto"),
        accelerator=fab_cfg.get("accelerator", "auto"),
        precision=fab_cfg.get("precision", "32-true"),
        callbacks=fab_cfg.get("callbacks", {}),
        mesh_shape=fab_cfg.get("mesh_shape", None),
        tp_min_param_size=fab_cfg.get("tp_min_param_size", 2**18),
        sharding=sharding_cfg,
    )
    if fabric.num_processes > 1:
        _validate_pod_device_view(fabric)
    cb_cfg = fab_cfg.get("callbacks", {}) or {}
    if "checkpoint" in cb_cfg:
        from sheeprl_tpu.utils.callback import CheckpointCallback

        fabric.register_callback(CheckpointCallback(keep_last=cb_cfg["checkpoint"].get("keep_last", 5)))
    # graceful preemption (SIGTERM/SIGINT latch) is armed by the FIRST
    # CheckpointManager.should_save poll, not here: surfaces that never poll
    # the latch (dedicated lockstep topologies, the evaluation CLI) must keep
    # the default signal disposition — latching a signal nobody reads would
    # swallow the preemption grace window entirely
    return fabric


def _validate_pod_device_view(fabric: Fabric) -> None:
    """Multi-process sanity of the per-process device view.

    Hard requirements: this process must SEE the whole pod (a process
    whose ``jax.devices()`` is local-only never initialized the
    distributed backend) and must own at least one local device.  Soft
    requirement (warned, rank 0 only): the mesh should cover every
    process — a mesh that excludes a rank's devices is legal for
    host-collective-only fabrics but no pod topology can train on it.
    """
    from sheeprl_tpu.parallel.distributed import rank_zero_warn

    procs_seen = {d.process_index for d in jax.devices(fabric.accelerator)}
    if len(procs_seen) < fabric.num_processes:
        raise RuntimeError(
            f"fabric.distributed: jax reports {fabric.num_processes} processes but this "
            f"rank's device view covers only processes {sorted(procs_seen)} — "
            "distributed init ran after a backend touch, or the pod is partitioned"
        )
    mesh_procs = {d.process_index for d in fabric.mesh.devices.flat}
    if len(mesh_procs) < fabric.num_processes:
        rank_zero_warn(
            f"fabric.devices={len(fabric.devices)} leaves some processes with no mesh "
            "devices; pod topologies need fabric.devices=auto (the global mesh)",
            key="fabric.pod_device_view",
        )


def trainer_device_count(fabric: Fabric, player_process: int = 0) -> int:
    """Number of mesh devices in the trainer group of the dedicated
    decoupled topology — THE sizing rule both sides of the protocol share
    (the player can't build the trainer fabric itself but must agree on
    ``batch_size = per_rank_batch_size * trainer_world``)."""
    return sum(1 for d in fabric.mesh.devices.flat if d.process_index != player_process)


def clone_with_devices(fabric: Fabric, devices: List[Any]) -> Fabric:
    """A fabric sharing ``fabric``'s policy state (precision, callbacks,
    sharding config, checkpoint manager) whose 1-D ``data`` mesh spans only
    ``devices`` — THE device-subset surgery shared by the dedicated-player
    trainer group and the Sebulba learner sub-mesh.  New ``Fabric.__init__``
    state must be mirrored here, in ONE place."""
    sub = Fabric.__new__(Fabric)
    sub.strategy = fabric.strategy
    sub.precision = fabric.precision
    sub.callbacks = fabric.callbacks
    sub._callback_cfg = fabric._callback_cfg
    sub.devices = list(devices)
    sub.accelerator = fabric.accelerator
    sub.mesh = Mesh(np.asarray(list(devices)), ("data",))
    sub.data_axis = "data"
    sub.tp_min_param_size = fabric.tp_min_param_size
    sub.sharding_cfg = dict(fabric.sharding_cfg)
    sub._sharding_rules = None
    sub.checkpoint_manager = fabric.checkpoint_manager
    return sub


def get_trainer_fabric(fabric: Fabric, player_process: int = 0) -> Fabric:
    """A fabric whose mesh spans only the devices NOT owned by the dedicated
    player process — the trainer group of the cross-process decoupled
    topology (reference: the trainer-only ``optimization_pg`` DDP subgroup,
    sheeprl/algos/ppo/ppo_decoupled.py:645-666).  Programs jitted on this
    mesh must be launched by every trainer process and by no other."""
    trainer_devices = [
        d for d in fabric.mesh.devices.flat if d.process_index != player_process
    ]
    if not trainer_devices:
        raise ValueError(
            "dedicated-player topology needs at least one device owned by a "
            "non-player process (got none; run with >= 2 processes)"
        )
    return clone_with_devices(fabric, trainer_devices)


def get_single_device_fabric(fabric: Fabric, device: Optional[Any] = None) -> Fabric:
    """A fabric pinned to one device, for inference-only "player" models
    (reference: sheeprl/utils/fabric.py:8-35).  Pass ``device`` to pin to a
    specific one — e.g. ``fabric.host_device`` for the dedicated player of
    the cross-process decoupled topology."""
    device = fabric.device if device is None else device
    single = Fabric.__new__(Fabric)
    single.strategy = fabric.strategy
    single.precision = fabric.precision
    single.callbacks = []
    single._callback_cfg = {}
    single.devices = [device]
    single.accelerator = fabric.accelerator
    single.mesh = Mesh(np.asarray([device]), ("data",))
    single.data_axis = "data"
    single.tp_min_param_size = fabric.tp_min_param_size
    single.sharding_cfg = dict(fabric.sharding_cfg)
    single._sharding_rules = None
    single.checkpoint_manager = None
    return single


def host_tree_to_mesh(tree: Any, mesh: Mesh, axis: int = 0, shard: bool = True) -> Any:
    """Assemble global device arrays ON a (possibly multi-process) mesh from
    host numpy values every participating process holds in full — the
    trainer-side batch landing of the dedicated decoupled topology.  Uses
    ``jax.make_array_from_callback``: no communication, each process serves
    its addressable shards.  ``shard=False`` replicates instead (the
    fallback when the batch axis does not divide the mesh)."""

    def put(x: Any) -> Any:
        x = np.asarray(x)
        spec: List[Any] = [None] * x.ndim
        if shard and x.ndim > axis:
            spec[axis] = mesh.axis_names[0]
        sh = NamedSharding(mesh, P(*spec))
        return jax.make_array_from_callback(x.shape, sh, lambda idx, _x=x: _x[idx])

    return jax.tree.map(put, tree)


def fetch_local(tree: Any) -> Any:
    """Pull a (replicated) device pytree to host numpy via the process-local
    shard — works on non-fully-addressable multi-process arrays where
    ``np.asarray`` alone would fail."""
    return jax.tree.map(
        lambda x: np.asarray(x.addressable_shards[0].data)
        if isinstance(x, jax.Array)
        else np.asarray(x),
        tree,
    )
