"""Dist fabric units (ISSUE 20): the codec's torn-frame ladder, the
2-worker echo path, reply ordering/dedup, hedging, and the deterministic
chunk math — the fault-free half of the contract (the failure schedules
live in tests/chaos/test_dist_chaos.py)."""
import hashlib
import io
import threading

import pytest

from consensus_specs_tpu.dist import codec, dispatch, fabric as fabmod
from consensus_specs_tpu.dist.dispatch import TaskSpec
from consensus_specs_tpu.dist.fabric import Fabric
from consensus_specs_tpu.dist.workloads import _chunk_bounds
from consensus_specs_tpu.persist import atomic


@pytest.fixture(autouse=True)
def _fresh_stats():
    dispatch.reset_stats()
    fabmod.reset_stats()
    yield


# -- codec ---------------------------------------------------------------------


def test_codec_roundtrip():
    buf = io.BytesIO()
    codec.write_frame(buf, "task", {"id": "t0", "kind": "echo"}, b"payload")
    codec.write_frame(buf, "reply", {"ok": True}, b"")
    buf.seek(0)
    assert codec.read_frame(buf) == ("task", {"id": "t0", "kind": "echo"},
                                     b"payload")
    assert codec.read_frame(buf) == ("reply", {"ok": True}, b"")
    assert codec.read_frame(buf) is None  # clean EOF at a frame boundary


def test_codec_torn_frame_is_detected():
    raw = codec.encode_frame("task", {"id": "t0"}, b"x" * 100)
    for cut in (2, 5, len(raw) - 1):  # mid-prefix, mid-header, mid-digest
        with pytest.raises(atomic.ArtifactCorrupt):
            codec.read_frame(io.BytesIO(raw[:cut]))


def test_codec_flipped_bit_is_detected():
    raw = bytearray(codec.encode_frame("task", {"id": "t0"}, b"x" * 64))
    raw[len(raw) // 2] ^= 0x01
    with pytest.raises(atomic.ArtifactCorrupt):
        codec.read_frame(io.BytesIO(bytes(raw)))


def test_codec_foreign_protocol_tag_is_stale():
    env = atomic.envelope(b'{"a":1}\x00body', "task", "dist-v0")
    import struct
    raw = struct.pack("<I", len(env)) + env
    with pytest.raises(atomic.ArtifactStaleTag):
        codec.read_frame(io.BytesIO(raw))


def test_codec_insane_length_prefix_is_corrupt():
    import struct
    raw = struct.pack("<I", codec.MAX_FRAME + 1) + b"zzzz"
    with pytest.raises(atomic.ArtifactCorrupt):
        codec.read_frame(io.BytesIO(raw))


# -- the 2-worker echo path ----------------------------------------------------


def _echo_expect(i):
    body = f"chunk-{i}".encode()
    return hashlib.sha256(body).digest() + body


def test_two_worker_echo_batch():
    with Fabric(n_workers=2, heartbeat_interval=0.1) as fab:
        tasks = [TaskSpec("echo", {}, f"chunk-{i}".encode())
                 for i in range(6)]
        out = dispatch.run_tasks(fab, tasks, deadline_s=20.0)
    assert [body for _, body in out] == [_echo_expect(i) for i in range(6)]
    assert all(meta["ok"] for meta, _ in out)
    snap = dispatch.snapshot()
    # fault-free: nothing re-dispatched, nothing hedged, nothing lost
    assert snap["redispatched_chunks"] == 0
    assert snap["hedged_tasks"] == 0
    assert snap["worker_losses"] == 0
    assert snap["replies"] == 6
    fsnap = fabmod.snapshot()
    assert fsnap["spawned"] == 2
    assert fsnap["corrupt_replies"] == 0


def test_results_come_back_in_task_order():
    """Replies arrive out of order (task 0 is the slowest) but the merge
    surface is task-ordered — the fixed-merge-order contract every
    workload builds on."""
    with Fabric(n_workers=2, heartbeat_interval=0.1) as fab:
        tasks = [TaskSpec("sleep_echo", {"seconds": 0.4}, b"slow"),
                 TaskSpec("echo", {}, b"fast-1"),
                 TaskSpec("echo", {}, b"fast-2")]
        out = dispatch.run_tasks(fab, tasks, deadline_s=20.0)
    bodies = [body[32:] for _, body in out]
    assert bodies == [b"slow", b"fast-1", b"fast-2"]


def test_worker_scope_reaches_the_worker_process():
    """Each worker reports its CSTPU_DIST_PROC scope back in replies —
    the addressing a scoped chaos plan relies on."""
    with Fabric(n_workers=2, heartbeat_interval=0.1) as fab:
        tasks = [TaskSpec("echo", {}, bytes([i])) for i in range(4)]
        out = dispatch.run_tasks(fab, tasks, deadline_s=20.0)
    procs = {meta["proc"] for meta, _ in out}
    assert procs == {"proc1", "proc2"}  # round-robin touched both


def test_worker_env_pins_jax_to_cpu_unconditionally(monkeypatch):
    """A chip belongs to one process: a coordinator started on the TPU
    platform (or handing one in through ``env``) still gets CPU workers."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    fab = Fabric(n_workers=1, env={"JAX_PLATFORMS": "tpu"})
    env = fab._worker_env(fab._workers[0])
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["CSTPU_DIST_PROC"] == "proc1"


def test_coordinator_wears_proc0_scope_inside_fabric_extent():
    from consensus_specs_tpu import faults

    assert faults.process_scope() is None
    with Fabric(n_workers=1, heartbeat_interval=0.1):
        assert faults.process_scope() == "proc0"
    assert faults.process_scope() is None


def test_hedge_duplicates_a_straggler():
    """A chunk in flight past hedge_s gets one duplicate on the second
    worker; the hedge is NOT a re-dispatched chunk (the fault-free gate
    keys on that distinction)."""
    with Fabric(n_workers=2, heartbeat_interval=0.1) as fab:
        tasks = [TaskSpec("sleep_echo", {"seconds": 0.6}, b"straggler")]
        out = dispatch.run_tasks(fab, tasks, deadline_s=30.0, hedge_s=0.15)
    assert out[0][1][32:] == b"straggler"
    snap = dispatch.snapshot()
    assert snap["hedged_tasks"] == 1
    assert snap["redispatched_chunks"] == 0


def test_duplicate_replies_are_discarded_by_task_id():
    """Unit-level dedup: a second reply for a settled task id is counted
    and dropped, never merged."""
    run = dispatch._DispatchRun.__new__(dispatch._DispatchRun)
    run.fabric = None
    pending = dispatch._Pending("r0.t0", 0, TaskSpec("echo", {}, b""))
    pending.workers = {"proc1"}
    run._inflight = {"r0.t0": pending}
    run._results = {}
    run._done = set()
    run._n = 1

    class _NoFabric:
        def worker(self, proc):
            return None

    run.fabric = _NoFabric()
    first = fabmod.Event("reply", "proc1", {"id": "r0.t0", "ok": True}, b"a")
    dupe = fabmod.Event("reply", "proc2", {"id": "r0.t0", "ok": True}, b"b")
    run._on_reply(first)
    run._on_reply(dupe)
    assert run._results[0][1] == b"a"  # first valid reply won
    assert dispatch.snapshot()["duplicate_replies"] == 1


def test_shutdown_is_clean():
    fab = Fabric(n_workers=2, heartbeat_interval=0.1).start()
    procs = [w.popen for w in fab.alive_workers()]
    fab.close()
    assert all(p.poll() is not None for p in procs)
    # close() is idempotent
    fab.close()


# -- deterministic chunk math --------------------------------------------------


def test_chunk_bounds_cover_and_are_deterministic():
    for n in (1, 2, 7, 16, 100):
        for k in (1, 2, 3, 8):
            bounds = _chunk_bounds(n, k)
            assert bounds == _chunk_bounds(n, k)
            assert bounds[0][0] == 0 and bounds[-1][1] == n
            for (a, b), (c, d) in zip(bounds, bounds[1:]):
                assert b == c and b > a
            sizes = [hi - lo for lo, hi in bounds]
            assert max(sizes) - min(sizes) <= 1


def test_chunk_bounds_degenerate():
    assert _chunk_bounds(3, 10) == [(0, 1), (1, 2), (2, 3)]
    assert _chunk_bounds(5, 1) == [(0, 5)]


# -- telemetry surface ---------------------------------------------------------


def test_snapshots_ride_the_telemetry_bus():
    from consensus_specs_tpu import telemetry

    tree = telemetry.snapshot()["providers"]
    assert "redispatched_chunks" in tree["dist.dispatch"]
    assert "corrupt_replies" in tree["dist.fabric"]


def test_stats_are_lock_guarded():
    """Counter bumps from many threads never lose increments (the reader
    threads and the dispatch loop all write these)."""
    def spin():
        for _ in range(1000):
            dispatch._bump("replies")

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert dispatch.snapshot()["replies"] == 4000
