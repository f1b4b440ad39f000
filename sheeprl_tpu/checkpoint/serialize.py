"""Host-side checkpoint serialization primitives.

Three layers, each reusable on its own:

* **Tree capture** — :func:`snapshot_tree` takes a consistent point-in-time
  copy of a live training state WITHOUT blocking on device compute: device
  arrays are copied on-device (an async dispatch — breaking any later
  donation alias) and host numpy arrays are memcpy'd (they keep mutating as
  the env loop runs).  :func:`to_host_tree` then materializes everything to
  host numpy — typed PRNG key arrays (extended dtypes, on which
  ``np.asarray`` chokes) are unwrapped via ``jax.random.key_data`` into a
  :class:`KeyArrayRef` and re-wrapped with ``jax.random.wrap_key_data`` by
  :func:`from_host_tree` on load, so RNG state round-trips bit-exactly.
* **Durable bytes** — :func:`durable_write` is the only way checkpoint bytes
  reach disk: tmp file in the target directory, ``fsync`` of the file BEFORE
  ``os.replace``, ``fsync`` of the parent directory AFTER, so a power loss
  can never leave an empty-but-renamed file behind.
* **Legacy single-file API** — :func:`save_checkpoint` / :func:`load_checkpoint`
  keep the original one-pickle-per-path surface (``fabric.save``, the model
  manager, old ``.ckpt`` files) on top of the same primitives.
  :func:`load_checkpoint` also accepts a committed step DIRECTORY from the
  commit protocol (see ``protocol.py``) and loads the right rank shard.
"""

from __future__ import annotations

import os
import pickle
import tempfile
import zlib
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class KeyArrayRef:
    """Pickle-stable stand-in for a typed PRNG key array: the uint32 key
    data plus the impl name (``threefry2x32``, ...) needed to re-wrap it."""

    impl: str
    data: np.ndarray


def _is_key_array(x: Any) -> bool:
    return isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.extended)


def snapshot_tree(tree: Any) -> Any:
    """Point-in-time copy of a live state tree, safe to hand to a writer
    thread while training continues.

    * fully-addressable ``jax.Array`` leaves are host-fetched HERE
      (``device_get``): donation-safe by construction — the value is on host
      before save() returns, so the original may be donated to the next
      jitted update.  (An on-device ``.copy()`` per leaf would compile one
      tiny XLA program per distinct leaf shape per process — multi-second
      overhead for a small checkpoint.)
    * typed PRNG keys and the process-local replica of multi-host arrays
      take an on-device ``.copy()`` — an asynchronously-dispatched device op
      that breaks the donation alias; the writer thread fetches it later.
    * numpy leaves: host memcpy (the env loop keeps writing into replay
      storage; the checkpoint must capture THIS step's contents).
    * ``MemmapArray`` leaves: kept as references — their persistence IS the
      backing file (see data/memmap.py), same semantics as the reference.
    * everything else (scalars, strings, small state dicts): passed through;
      pytree mapping already rebuilds fresh containers.
    """
    from sheeprl_tpu.data.memmap import MemmapArray

    def leaf(x: Any) -> Any:
        if isinstance(x, MemmapArray):
            return x
        if isinstance(x, jax.Array):
            if x.is_fully_addressable and not _is_key_array(x):
                # np.array (not asarray): device_get on the CPU backend can
                # be zero-copy, and the caller may donate the original
                # buffer right after save() returns
                return np.array(jax.device_get(x))
            if not x.is_fully_addressable:
                # multi-host arrays: checkpoint state is replicated
                # (params/opt state); copy the process-local replica
                if not x.sharding.is_fully_replicated:
                    raise ValueError(
                        "checkpoint state contains a non-replicated multi-host "
                        "array; only replicated state trees can be snapshotted"
                    )
                return x.addressable_shards[0].data.copy()
            return x.copy()
        if isinstance(x, np.ndarray):
            return np.array(x, copy=True)
        return x

    return jax.tree.map(
        leaf,
        tree,
        is_leaf=lambda x: isinstance(x, (jax.Array, MemmapArray)),
    )


def to_host_tree(tree: Any) -> Any:
    """Materialize every device leaf to host numpy (blocking).

    Typed PRNG key arrays become :class:`KeyArrayRef` (``np.asarray`` has no
    representation for extended dtypes); :func:`from_host_tree` reverses it.
    """
    from sheeprl_tpu.data.memmap import MemmapArray

    def leaf(x: Any) -> Any:
        if _is_key_array(x):
            return KeyArrayRef(
                impl=str(jax.random.key_impl(x)),
                data=np.asarray(jax.device_get(jax.random.key_data(x))),
            )
        if isinstance(x, jax.Array):
            return np.asarray(jax.device_get(x))
        return x

    return jax.tree.map(
        leaf,
        tree,
        is_leaf=lambda x: isinstance(x, (jax.Array, MemmapArray)),
    )


def from_host_tree(tree: Any) -> Any:
    """Re-wrap :class:`KeyArrayRef` leaves into typed PRNG key arrays."""

    def leaf(x: Any) -> Any:
        if isinstance(x, KeyArrayRef):
            return jax.random.wrap_key_data(jnp.asarray(x.data), impl=x.impl)
        return x

    return jax.tree.map(leaf, tree, is_leaf=lambda x: isinstance(x, KeyArrayRef))


def dump_bytes(obj: Any) -> Tuple[bytes, int]:
    """Pickle ``obj`` and return ``(payload, crc32)``."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    return payload, zlib.crc32(payload) & 0xFFFFFFFF


def fsync_dir(path: Union[str, os.PathLike]) -> None:
    """fsync a directory so a just-renamed entry survives power loss.  Best
    effort: some filesystems (and all of Windows) refuse O_RDONLY dir fds."""
    try:
        fd = os.open(os.fspath(path), os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def durable_write(path: Union[str, os.PathLike], payload: bytes) -> None:
    """Atomically and durably write ``payload`` to ``path``:
    tmp file in the same directory → flush → ``fsync(file)`` → ``os.replace``
    → ``fsync(parent dir)``.  Without the first fsync a crash after the
    rename can leave a correctly-named EMPTY file (data still in the page
    cache); without the second the rename itself may not be on disk."""
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        fsync_dir(path.parent)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def save_checkpoint(path: Union[str, os.PathLike], state: Dict[str, Any]) -> int:
    """Legacy single-file save: host-fetch + durable atomic pickle.
    Returns the number of bytes written."""
    payload, _ = dump_bytes(to_host_tree(snapshot_tree(state)))
    durable_write(path, payload)
    return len(payload)


def load_checkpoint(path: Union[str, os.PathLike], rank: int = 0) -> Dict[str, Any]:
    """Load a checkpoint from a legacy ``.ckpt`` file OR a committed step
    directory of the commit protocol (picks the shard for ``rank``).

    ``MemmapArray`` references whose backing files moved hosts rehydrate
    in-memory with a warning instead of raising ``FileNotFoundError`` deep
    inside unpickling (see ``MemmapArray.__setstate__``)."""
    path = Path(path)
    if path.is_dir():
        from sheeprl_tpu.checkpoint.protocol import load_step_dir

        return load_step_dir(path, rank=rank)
    with open(path, "rb") as f:
        return from_host_tree(pickle.load(f))
