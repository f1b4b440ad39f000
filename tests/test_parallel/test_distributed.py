"""Pod-scale fault-tolerance fabric (docs/distributed.md).

Fast cells pin the single-process halves of the DCN contracts:
:class:`DcnParamBroadcast`'s versioned staleness gate (cursors advance on
``note_applied``, never at serve time), the :class:`LearnerFront` /
:class:`PodClient` loopback round-trip (CRC-verified segments, torn
rejects, backpressure-never-drop, the ``/poll`` control plane), the
shared-checkpoint-root probe, per-rank shard verification, and the
rank-0 warning dedupe.

The ``slow`` cells launch REAL 2-process pods over the fake-DCN env
protocol (the ``SHEEPRL_FAKE_DCN`` cell branch of ``ensure_distributed``)
and pin the multi-host fabric view — global mesh over both processes,
``shard_batch``'s global-assembly semantics, cross-host reductions — and
the transport contracts ACROSS the process boundary: param fetch +
staleness gating and torn-segment rejection with the learner and actor
in different processes.
"""

import json
import os
import pickle
import subprocess
import sys
import textwrap
import urllib.request
import warnings
import zlib
from pathlib import Path

import numpy as np
import pytest

from sheeprl_tpu.checkpoint.protocol import (
    MANIFEST_FILE,
    SHARED_ROOT_ERROR,
    probe_shared_root,
    shard_name,
    step_dir_name,
    verify_checkpoint,
    write_commit,
    write_shard,
    write_shared_root_probe,
)
from sheeprl_tpu.parallel.distributed import (
    ENV_COORD,
    ENV_FAKE,
    ENV_NUM_PROCESSES,
    ENV_PROCESS_ID,
    free_port,
    rank_zero_warn,
)
from sheeprl_tpu.parallel.topology import StalenessExceeded
from sheeprl_tpu.sebulba.queues import TornTrajectory, TrajQueue
from sheeprl_tpu.sebulba.transport import DcnParamBroadcast, LearnerFront, PodClient

REPO_ROOT = Path(__file__).resolve().parents[2]


# ---------------------------------------------------------------------------
# DcnParamBroadcast: the cross-host staleness gate
# ---------------------------------------------------------------------------


class TestDcnParamBroadcast:
    def test_publish_serves_versioned_crc_payload(self):
        b = DcnParamBroadcast([1, 2], max_staleness=2)
        params = {"w": np.arange(4.0, dtype=np.float32)}
        v = b.publish(params, version=3)
        assert v == 3 and b.version == 3
        served = b.payload_for(-1)
        assert served is not None
        payload, crc, version = served
        assert version == 3
        assert zlib.crc32(payload) & 0xFFFFFFFF == crc
        np.testing.assert_array_equal(pickle.loads(payload)["w"], params["w"])
        # nothing newer than what the caller already has -> None (HTTP 204)
        assert b.payload_for(3) is None

    def test_serving_does_not_advance_gate(self):
        b = DcnParamBroadcast([1, 2], max_staleness=0, gate_timeout_s=0.2)
        b.publish({"w": np.zeros(2)}, version=0)  # first publish seeds cursors
        assert b.gate(timeout_s=0.2) >= 0.0
        b.publish({"w": np.ones(2)}, version=1)
        # a fetch lost on the wire must not satisfy the gate: serving the
        # payload repeatedly advances nothing
        for _ in range(3):
            assert b.payload_for(0) is not None
        with pytest.raises(StalenessExceeded):
            b.gate(timeout_s=0.2)
        # a poll still reporting the OLD version records the lag but does
        # not advance the cursor
        b.note_applied(1, 0)
        assert b.staleness_max == 1
        # /poll reporting the installed version is what advances the cursor
        b.note_applied(1, 1)
        with pytest.raises(StalenessExceeded):
            b.gate(timeout_s=0.2)  # rank 2 still behind
        b.note_applied(2, 1)
        b.gate(timeout_s=0.2)

    def test_note_applied_ignores_unknown_rank(self):
        b = DcnParamBroadcast([1], max_staleness=0, gate_timeout_s=0.2)
        b.publish({"w": np.zeros(2)}, version=0)
        b.publish({"w": np.ones(2)}, version=1)
        b.note_applied(99, 1)  # not an actor rank: no cursor to advance
        with pytest.raises(StalenessExceeded):
            b.gate(timeout_s=0.2)

    def test_device_fetch_is_refused(self):
        b = DcnParamBroadcast([1])
        with pytest.raises(NotImplementedError):
            b.fetch(0)

    def test_metrics_report_dcn_bytes(self):
        b = DcnParamBroadcast([1])
        b.publish({"w": np.zeros(8, dtype=np.float32)}, version=0)
        m = b.metrics()
        assert m["Dcn/broadcast_publishes"] == 1.0
        assert m["Dcn/broadcast_bytes"] > 0.0


# ---------------------------------------------------------------------------
# LearnerFront + PodClient loopback: one process, real HTTP, real TrajQueue
# ---------------------------------------------------------------------------


@pytest.fixture()
def front_client():
    queue = TrajQueue(4, 3, None, stage=False, timeout_s=5.0)
    broadcast = DcnParamBroadcast([1], max_staleness=0, gate_timeout_s=2.0)
    front = LearnerFront(
        queue,
        broadcast,
        [1],
        host="127.0.0.1",
        port=0,
        heartbeat_grace_s=60.0,
        first_contact_grace_s=60.0,
        put_timeout_s=0.5,
    ).start()
    client = PodClient(
        front.address, 1, push_deadline_s=10.0, request_timeout_s=5.0, heartbeat_grace_s=60.0
    )
    try:
        yield queue, broadcast, front, client
    finally:
        front.stop()
        queue.close()


class TestFrontLoopback:
    def test_param_fetch_roundtrip(self, front_client):
        _, broadcast, _, client = front_client
        params = {"w": np.arange(6.0, dtype=np.float32), "b": np.zeros(2)}
        broadcast.publish(params, version=0)
        fetched = client.fetch_params(-1)
        assert fetched is not None
        got, version = fetched
        assert version == 0
        np.testing.assert_array_equal(got["w"], params["w"])
        # already current -> 204 -> None
        assert client.fetch_params(0) is None
        assert client.fetches == 1

    def test_torn_broadcast_is_refetched_never_applied(self, front_client):
        _, broadcast, _, client = front_client
        broadcast.publish({"w": np.arange(4.0)}, version=0)
        # damage the stored payload but keep the stamped CRC: exactly what
        # wire corruption past the CRC stamp looks like to the client
        with broadcast._lock:
            broadcast._payload = broadcast._payload[:-1] + b"\x00"
        assert client.fetch_params(-1) is None
        assert client.fetch_crc_rejects == 1
        broadcast.publish({"w": np.arange(4.0)}, version=1)  # clean refetch
        fetched = client.fetch_params(-1)
        assert fetched is not None and fetched[1] == 1

    def test_segment_roundtrip_with_meta(self, front_client):
        queue, _, front, client = front_client
        seg = {"obs": np.ones((3, 2), np.float32), "rew": np.zeros((3, 2), np.float32)}
        client.push_segment(seg, meta={"worker": 7, "version": 0})
        items = queue.get_many(1, timeout_s=5.0)
        got, meta = items[0]
        np.testing.assert_array_equal(got["obs"], seg["obs"])
        assert meta["worker"] == 7
        assert front.segments_accepted == 1 and client.segments_pushed == 1

    def test_torn_segment_rejected_never_enqueued(self, front_client):
        queue, _, front, client = front_client
        # wrong leading (time) axis: structurally torn — the queue's own
        # validation holds across the process boundary, and retrying the
        # same buffer can never succeed, so the client fails loudly NOW
        with pytest.raises(TornTrajectory):
            client.push_segment({"obs": np.ones((2, 2), np.float32)})
        assert front.segments_rejected == 1
        assert queue.total_put == 0 and queue.qsize() == 0

    def test_wire_crc_mismatch_is_rejected_with_409(self, front_client):
        queue, _, front, client = front_client
        payload = pickle.dumps({"obs": np.ones((3, 2), np.float32)})
        req = urllib.request.Request(
            f"http://{front.address}/segment",
            data=payload,
            headers={"X-Sheeprl-CRC32": "12345", "X-Sheeprl-Rank": "1"},
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(req, timeout=5.0)
        assert exc_info.value.code == 409
        assert b"crc mismatch" in exc_info.value.read()
        assert front.segments_rejected == 1 and queue.total_put == 0
        # the same segment with its true CRC goes through: a torn wire
        # costs a retry, never a segment
        client.push_segment({"obs": np.ones((3, 2), np.float32)})
        assert front.segments_accepted == 1

    def test_backpressure_retries_until_drained_never_drops(self, front_client):
        queue, _, front, client = front_client
        for _ in range(queue.capacity):
            queue.put({"obs": np.zeros((3, 2), np.float32)})
        import threading
        import time as _time

        def drain():
            _time.sleep(0.8)
            queue.get_many(2, timeout_s=5.0)

        t = threading.Thread(target=drain)
        t.start()
        client.push_segment({"obs": np.ones((3, 2), np.float32)})  # rides a 503 retry
        t.join()
        assert front.backpressured >= 1
        assert front.segments_accepted == 1
        assert queue.total_put == queue.capacity + 1  # nothing dropped

    def test_poll_control_plane(self, front_client):
        _, broadcast, front, client = front_client
        broadcast.publish({"w": np.zeros(2)}, version=0)
        resp = client.poll(0)
        assert resp == {
            "version": 0,
            "commit_step": -1,
            "commit_steps": [],
            "preempt": False,
            "done": False,
        }
        front.set_commit(7)
        assert client.poll(0)["commit_step"] == 7
        # back-to-back announcements accumulate instead of coalescing —
        # a fast learner's async commit manager can announce two saves
        # between actor polls, and BOTH need shards
        front.set_commit(14)
        resp = client.poll(0)
        assert resp["commit_step"] == 14
        assert resp["commit_steps"] == [7, 14]
        # gate clears off the poll's applied_version report
        broadcast.publish({"w": np.ones(2)}, version=1)
        client.poll(1)
        broadcast.gate(timeout_s=1.0)
        # the actor's preemption latch crosses to the learner...
        assert not front.actor_latched
        client.poll(1, latched=True)
        assert front.actor_latched
        # ...and reflects back to every cell as a pod-wide preempt
        assert client.poll(1)["preempt"] is True
        # per-cell hub snapshots land rank-prefixed in the learner stream
        client.poll(1, hub={"Loss/x": 2.0, "rank1/Game/y": 3.0})
        metrics = front.metrics()
        assert metrics["rank1/Loss/x"] == 2.0
        assert metrics["rank1/Game/y"] == 3.0  # no double prefix
        front.set_done()
        assert client.poll(1)["done"] is True

    def test_done_front_tells_pushers_to_stop(self, front_client):
        queue, _, front, client = front_client
        from sheeprl_tpu.serve.batcher import ServiceStopped

        front.set_done()
        queue.close()
        with pytest.raises(ServiceStopped):
            client.push_segment({"obs": np.ones((3, 2), np.float32)})

    def test_goodbye_completes_shutdown(self, front_client):
        _, _, front, client = front_client
        assert not front.wait_goodbyes(0.2)
        client.goodbye("rollout complete")
        assert front.wait_goodbyes(5.0)


# ---------------------------------------------------------------------------
# Shared checkpoint root: fail fast, name the missing ranks
# ---------------------------------------------------------------------------


class TestSharedRoot:
    def test_rank_nonzero_fails_fast_without_probe(self, tmp_path):
        with pytest.raises(RuntimeError) as exc_info:
            probe_shared_root(tmp_path, rank=1, timeout_s=0.3)
        assert SHARED_ROOT_ERROR in str(exc_info.value)
        assert "shared storage" in SHARED_ROOT_ERROR

    def test_probe_passes_once_rank_zero_wrote(self, tmp_path):
        write_shared_root_probe(tmp_path)
        probe_shared_root(tmp_path, rank=1, timeout_s=0.3)  # no raise

    def _commit_two_rank_checkpoint(self, root, step=10):
        step_dir = root / step_dir_name(step)
        step_dir.mkdir(parents=True)
        for rank in range(2):
            write_shard(step_dir, rank, {"pod_rank": rank, "policy_step": step})
        assert write_commit(step_dir, step=step, world=2, timeout_s=5.0)
        return step_dir

    def test_verify_reports_which_rank_shard_is_missing(self, tmp_path):
        step_dir = self._commit_two_rank_checkpoint(tmp_path)
        assert verify_checkpoint(step_dir) == []
        (step_dir / shard_name(1)).unlink()
        problems = verify_checkpoint(step_dir)
        assert problems and any("(rank 1)" in p for p in problems)
        assert not any("(rank 0)" in p for p in problems)

    def test_verify_reports_unlisted_ranks(self, tmp_path):
        step_dir = self._commit_two_rank_checkpoint(tmp_path)
        manifest = json.loads((step_dir / MANIFEST_FILE).read_text())
        manifest["world"] = 3  # a rank whose shard the manifest never saw
        (step_dir / MANIFEST_FILE).write_text(json.dumps(manifest))
        problems = verify_checkpoint(step_dir)
        assert any("ranks [2] are not listed" in p for p in problems)


# ---------------------------------------------------------------------------
# rank_zero_warn: one copy per pod, once per process
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "env,is_pod",
    [
        ({}, False),
        # a one-host TPU VM (what a v5e host sets): NOT a pod — an argument-less
        # jax.distributed.initialize() there has no cluster to find
        ({"TPU_WORKER_HOSTNAMES": "localhost", "TPU_WORKER_ID": "0"}, False),
        ({"TPU_WORKER_HOSTNAMES": "10.0.0.2,10.0.0.3"}, True),
        ({"MEGASCALE_COORDINATOR_ADDRESS": "10.0.0.2:8080"}, True),
    ],
)
def test_pod_autodetect_needs_more_than_one_host(monkeypatch, env, is_pod):
    from sheeprl_tpu.parallel.distributed import _tpu_pod_env

    for name in ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert _tpu_pod_env() is is_pod


class TestRankZeroWarn:
    def test_rank_zero_warns_once_per_key(self, monkeypatch):
        monkeypatch.setenv(ENV_PROCESS_ID, "0")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rank_zero_warn("pod-wide fact", key="test.dedupe.a")
            rank_zero_warn("pod-wide fact (again)", key="test.dedupe.a")
        assert len(caught) == 1
        assert "pod-wide fact" in str(caught[0].message)

    def test_nonzero_rank_is_silent(self, monkeypatch):
        monkeypatch.setenv(ENV_PROCESS_ID, "3")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            rank_zero_warn("pod-wide fact", key="test.dedupe.b")
        assert caught == []


# ---------------------------------------------------------------------------
# Real 2-process pods over the fake-DCN env protocol
# ---------------------------------------------------------------------------


def _run_pod_cells(worker_src: str, tmp_path: Path, timeout: float = 240.0):
    """Launch ``worker_src`` as 2 fake-DCN cells (the exact env protocol
    ``PodSupervisor._spawn`` / ``launch_fake_dcn`` set) and return the
    combined outputs after asserting both exited 0."""
    script = tmp_path / "cell.py"
    script.write_text(worker_src)
    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            {
                ENV_FAKE: "2",
                ENV_PROCESS_ID: str(rank),
                ENV_NUM_PROCESSES: "2",
                ENV_COORD: coord,
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                "PYTHONPATH": str(REPO_ROOT),
            }
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script), str(rank)],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                cwd=str(tmp_path),
            )
        )
    outputs = []
    for rank, proc in enumerate(procs):
        out, _ = proc.communicate(timeout=timeout)
        outputs.append(out)
    for rank, (proc, out) in enumerate(zip(procs, outputs)):
        assert proc.returncode == 0, f"cell {rank} failed:\n{out}"
        assert f"rank {rank} OK" in out, f"cell {rank} never reached OK:\n{out}"
    return outputs


_MESH_WORKER = textwrap.dedent(
    """
    import sys

    import numpy as np

    rank = int(sys.argv[1])

    from sheeprl_tpu.parallel.distributed import ensure_distributed

    assert ensure_distributed({}) == "cell"

    import jax
    import jax.numpy as jnp

    assert jax.process_count() == 2
    assert jax.process_index() == rank

    from sheeprl_tpu.parallel.fabric import Fabric

    fab = Fabric(devices="auto", accelerator="cpu")
    # the global mesh spans BOTH processes; each contributes one device
    assert fab.num_processes == 2
    assert fab.world_size == 2, fab.world_size
    assert fab.local_world_size == 1
    assert fab.global_rank == rank
    assert fab.is_global_zero == (rank == 0)

    # shard_batch assembles the global batch from per-process locals:
    # each process feeds its OWN 4-row shard, the global array is 8 rows
    local = np.full((4, 3), float(rank), dtype=np.float32)
    g = fab.shard_batch({"x": local})["x"]
    assert g.shape == (8, 3), g.shape
    shards = list(g.addressable_shards)
    assert len(shards) == 1
    np.testing.assert_array_equal(np.asarray(shards[0].data), local)

    # a jitted reduction over the global array is a REAL cross-host
    # collective: 4*3 zeros from rank 0 + 4*3 ones from rank 1
    total = jax.jit(jnp.sum)(g)
    assert float(np.asarray(total.addressable_data(0))) == 12.0

    # copy_to pulls the process-local view to host as a true copy
    host = fab.copy_to({"x": np.asarray(shards[0].data)}, fab.host_device)
    np.testing.assert_array_equal(np.asarray(host["x"]), local)

    # host-object collectives ride the coordinator KV store on CPU pods
    gathered = fab.all_gather_object({"rank": rank})
    assert [g["rank"] for g in gathered] == [0, 1]
    word = fab.broadcast_object("from-zero" if rank == 0 else None, src=0)
    assert word == "from-zero"
    fab.barrier()

    print(f"rank {rank} OK")
    """
)


_TRANSPORT_WORKER = textwrap.dedent(
    """
    import sys
    import time

    import numpy as np

    rank = int(sys.argv[1])

    from sheeprl_tpu.parallel.distributed import ensure_distributed

    assert ensure_distributed({}) == "cell"

    deadline = time.monotonic() + 120.0

    if rank == 0:
        from sheeprl_tpu.sebulba.queues import TrajQueue
        from sheeprl_tpu.sebulba.transport import (
            DcnParamBroadcast,
            LearnerFront,
            publish_front_address,
        )

        queue = TrajQueue(4, 3, None, stage=False, timeout_s=60.0)
        broadcast = DcnParamBroadcast([1], max_staleness=0, gate_timeout_s=90.0)
        front = LearnerFront(
            queue, broadcast, [1], host="127.0.0.1",
            heartbeat_grace_s=60.0, first_contact_grace_s=90.0,
        ).start()
        publish_front_address(front.address)
        broadcast.publish({"w": np.arange(4.0, dtype=np.float32)}, version=0)
        front.wait_for_cells(90.0)

        # the actor pushed one torn segment first (rejected, never
        # enqueued) and one good one (the only thing the queue ever saw)
        seg, meta = queue.get_many(1, timeout_s=90.0)[0]
        assert seg["obs"].shape == (3, 2), seg["obs"].shape
        assert meta["worker"] == 7
        assert front.segments_rejected >= 1
        assert front.segments_accepted == 1
        assert queue.total_put == 1

        # the cross-host staleness gate: v1 with max_staleness=0 blocks
        # the learner until the remote cell REPORTS it applied v1
        broadcast.publish({"w": np.arange(4.0, dtype=np.float32) + 1.0}, version=1)
        broadcast.gate()
        assert broadcast.staleness_max >= 1

        front.set_done()
        assert front.wait_goodbyes(60.0)
        front.stop()
        queue.close()
    else:
        from sheeprl_tpu.sebulba.queues import TornTrajectory
        from sheeprl_tpu.sebulba.transport import PodClient, lookup_front_address

        client = PodClient(
            lookup_front_address(timeout_s=90.0), 1,
            push_deadline_s=60.0, request_timeout_s=10.0, heartbeat_grace_s=60.0,
        )
        fetched = None
        while fetched is None and time.monotonic() < deadline:
            fetched = client.fetch_params(-1)
            if fetched is None:
                time.sleep(0.1)
        assert fetched is not None, "never fetched initial params"
        params, applied = fetched
        assert applied == 0
        np.testing.assert_array_equal(params["w"], np.arange(4.0, dtype=np.float32))

        # structurally torn segment: rejected across the process boundary
        try:
            client.push_segment({"obs": np.ones((2, 2), np.float32)}, meta={"worker": 7})
            raise AssertionError("torn segment was accepted")
        except TornTrajectory:
            pass
        client.push_segment({"obs": np.ones((3, 2), np.float32)}, meta={"worker": 7})

        # control loop: poll, fetch what the learner published, report it
        while time.monotonic() < deadline:
            resp = client.poll(applied)
            if resp is None:
                time.sleep(0.1)
                continue
            if resp["version"] > applied:
                got = client.fetch_params(applied)
                if got is not None:
                    params, applied = got
                    np.testing.assert_array_equal(
                        params["w"], np.arange(4.0, dtype=np.float32) + 1.0
                    )
                continue
            if resp["done"]:
                break
            time.sleep(0.1)
        assert applied == 1, f"never applied v1 (applied={applied})"
        client.goodbye("test complete")

    print(f"rank {rank} OK")
    """
)


@pytest.mark.slow
class TestFakeDcnPod:
    def test_two_process_global_mesh_semantics(self, tmp_path):
        _run_pod_cells(_MESH_WORKER, tmp_path)

    def test_cross_host_broadcast_gate_and_torn_segments(self, tmp_path):
        _run_pod_cells(_TRANSPORT_WORKER, tmp_path)
