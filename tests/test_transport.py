"""Transport-contract tests (ISSUE 17): framing, the socket data plane's
fault shapes (torn frame, half-open peer, heartbeat stall), drain-ack
at-least-once redelivery, prefix-block hashing, and the socket-transport
mirror of the fleet kill/stall e2e rings — the same router, hot-swap and
goodput machinery must run unchanged over either wire."""

import json
import os
import socket
import struct
import time

import numpy as np
import pytest

from distributed_pipeline_tpu.chaos import (
    CHAOS_PLAN_ENV,
    aggregate_serving,
    goodput,
    read_attempts,
)
from distributed_pipeline_tpu.serving.transport import (
    MAX_FRAME_BYTES,
    FileReplicaClient,
    ReplicaPaths,
    SocketReplicaClient,
    TransportError,
    WorkerSocketEndpoint,
    prefix_block_hashes,
    recv_frame,
    send_frame,
)

from tests.test_fleet import (
    _drive,
    _expected_tokens,
    _fake_ckpt,
    _start_fleet,
)

# ================================================================= framing


def test_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        for obj in ({"op": "hb"}, {"op": "submit",
                                   "req": {"id": 3, "prompt": [1, 2, 3]}},
                    {"ok": True, "results": [], "unicode": "héllo"}):
            send_frame(a, obj)
            assert recv_frame(b) == obj
    finally:
        a.close()
        b.close()


def test_torn_frame_raises_transport_error():
    a, b = socket.socketpair()
    try:
        # header promises 100 bytes; only 10 arrive before EOF
        a.sendall(struct.pack(">I", 100) + b"x" * 10)
        a.close()
        with pytest.raises(TransportError, match="torn frame"):
            recv_frame(b)
    finally:
        b.close()


def test_clean_peer_close_is_transport_error_not_garbage():
    a, b = socket.socketpair()
    a.close()
    try:
        with pytest.raises(TransportError, match="peer closed"):
            recv_frame(b)
    finally:
        b.close()


def test_oversized_frame_rejected_both_directions():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1))
        with pytest.raises(TransportError, match="too large"):
            recv_frame(b)
    finally:
        a.close()
        b.close()


# =========================================================== prefix hashes


def test_prefix_block_hashes_leading_match_semantics():
    page = 4
    a = prefix_block_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], page)
    b = prefix_block_hashes([1, 2, 3, 4, 9, 9, 9, 9], page)
    assert len(a) == 2 and len(b) == 2  # partial trailing block ignored
    assert a[0] == b[0] and a[1] != b[1]
    # cumulative: sharing block k requires sharing every block before it
    c = prefix_block_hashes([9, 9, 9, 9, 5, 6, 7, 8], page)
    assert c[1] != a[1]
    # cross-process stable (CRC32, not hash()): pin a literal value
    assert prefix_block_hashes([1, 2, 3, 4], 4) == (
        prefix_block_hashes([1, 2, 3, 4], 4))
    assert prefix_block_hashes([], 4) == ()
    assert len(prefix_block_hashes(list(range(400)), 2,
                                   max_blocks=32)) == 32


# ============================================ endpoint/client in-process


@pytest.fixture()
def endpoint_pair(tmp_path):
    paths = ReplicaPaths.at(str(tmp_path / "replica0"), 0).ensure()
    ep = WorkerSocketEndpoint(paths, 0, attempt=0)
    client = SocketReplicaClient(paths, hb_cache_s=0.0)
    yield ep, client, paths
    client.close()
    ep.close()


def test_socket_submit_and_drain_roundtrip(endpoint_pair):
    ep, client, _ = endpoint_pair
    client.submit({"id": 7, "prompt": [1, 2], "max_new_tokens": 4})
    client.submit({"id": 8, "prompt": [3], "max_new_tokens": 2})
    got = ep.take_submits()
    assert [r["id"] for r in got] == [7, 8]
    assert ep.take_submits() == []

    ep.queue_result({"id": 7, "tokens": [11, 12]})
    first = client.consume_results()
    assert [r["id"] for r in first] == [7]
    # at-least-once: the worker buffers a result until a LATER drain
    # acks it. Simulate the reply dying on the wire (the router never
    # saw batch 1, so it never acks it) — the result is RE-delivered
    client._pending_ack = []
    again = client.consume_results()
    assert [r["id"] for r in again] == [7]
    # healthy path: the next drain acks batch 2, clearing the buffer
    assert client.consume_results() == []


def test_socket_heartbeat_age_tracks_main_loop_tick(endpoint_pair):
    ep, client, _ = endpoint_pair
    now = time.time()
    ep.tick(now)
    age = client.beacon_age_s(now + 0.5)
    assert age == pytest.approx(0.5, abs=0.2)
    # STALL: the endpoint thread still answers, but the stamp is stale —
    # age grows exactly like a frozen beacon mtime would
    age2 = client.beacon_age_s(now + 20.0)
    assert age2 == pytest.approx(20.0, abs=0.5)


def test_socket_prefix_index_rides_heartbeat(endpoint_pair):
    ep, client, _ = endpoint_pair
    assert tuple(client.prefix_index()) == ()
    ep.tick(time.time(), extra={"prefix_index": [11, 22, 33]})
    assert list(client.prefix_index()) == [11, 22, 33]


def test_socket_half_open_degrades_to_replica_down(endpoint_pair):
    ep, client, paths = endpoint_pair
    t0 = time.time()
    ep.tick(t0)
    client.submit({"id": 1, "prompt": [1], "max_new_tokens": 1})
    # kill the server abruptly but keep the advertisement on disk: the
    # established connection goes half-open once the handler notices
    # the stop (its recv timeout is 0.5s), and reconnects are refused
    ep._stop = True
    ep._srv.close()
    time.sleep(0.8)
    with pytest.raises((TransportError, ConnectionError)):
        client.submit({"id": 2, "prompt": [2], "max_new_tokens": 1})
    # liveness signal keeps growing from the last good tick — the
    # router's stale_beacon_s gate takes it out like any dead replica
    age = client.beacon_age_s(t0 + 30.0)
    assert age is not None and age >= 29.0
    assert client.consume_results() == []  # degrades, never raises


def test_socket_endpoint_close_unpublishes(tmp_path):
    paths = ReplicaPaths.at(str(tmp_path / "r"), 0).ensure()
    ep = WorkerSocketEndpoint(paths, 0, attempt=1)
    assert os.path.exists(paths.endpoint_path)
    ep.close()
    assert not os.path.exists(paths.endpoint_path)
    client = SocketReplicaClient(paths, hb_cache_s=0.0)
    with pytest.raises(TransportError, match="no endpoint"):
        client.submit({"id": 0, "prompt": [0], "max_new_tokens": 1})


def test_file_client_unchanged_semantics(tmp_path):
    """The extracted FileReplicaClient keeps the r13 mailbox contract:
    atomic submit files, consume-deletes, torn results impossible."""
    paths = ReplicaPaths.at(str(tmp_path / "r"), 0).ensure()
    client = FileReplicaClient(paths)
    client.submit({"id": 4, "prompt": [9], "max_new_tokens": 2})
    assert os.path.exists(paths.req_path(4))
    with open(paths.result_path(4), "w") as f:
        json.dump({"id": 4, "tokens": [1, 2]}, f)
    os.replace(paths.result_path(4), paths.result_path(4))
    assert [r["id"] for r in client.consume_results()] == [4]
    assert client.consume_results() == []  # consumed = deleted


# =================================================== socket-fleet e2e rings


@pytest.mark.chaos
def test_socket_fleet_serves_token_identical(tmp_path):
    """The plain e2e over the socket transport: same router, same
    deterministic tokens, ledger accounts to 1.0 — nothing above the
    transport seam noticed the wire change."""
    ckpt = tmp_path / "ckpts"
    _fake_ckpt(ckpt, 1, salt=2)
    fleet, router = _start_fleet(tmp_path, 2, ckpt, transport="socket")
    try:
        prompts = [np.arange(i + 1, i + 5, dtype=np.int32)
                   for i in range(6)]
        for p in prompts:
            router.submit(p, 8)
        _drive(router, fleet)
    finally:
        fleet.stop()
    assert router.completed == 6
    for rec, prompt in zip(sorted(router.records.values(),
                                  key=lambda r: r.id), prompts):
        assert rec.tokens == _expected_tokens(prompt, 8, salt=2)
    agg = aggregate_serving(str(tmp_path / "fleet"))
    assert agg["accounted_frac"] == pytest.approx(1.0, abs=0.05)


@pytest.mark.chaos
def test_socket_fleet_kill_replica_replays_token_identical(tmp_path,
                                                           monkeypatch):
    """The kill_replica e2e mirrored over SocketReplicaClient: results
    still in the victim's MEMORY die with it, the journaled requests
    replay on a sibling, and every token matches the deterministic
    decode — the documented socket durability story, proven."""
    ckpt = tmp_path / "ckpts"
    _fake_ckpt(ckpt, 1, salt=3)
    plan = {"faults": [{"kind": "kill_replica", "step": 1, "rank": 1,
                        "sig": "SIGKILL"}]}
    monkeypatch.setenv(CHAOS_PLAN_ENV, json.dumps(plan))
    fleet, router = _start_fleet(tmp_path, 3, ckpt, transport="socket")
    try:
        prompts = [np.arange(i + 1, i + 5, dtype=np.int32)
                   for i in range(9)]
        for p in prompts:
            router.submit(p, 12)
        _drive(router, fleet)
    finally:
        fleet.stop()
    recs = sorted(router.records.values(), key=lambda r: r.id)
    assert router.submitted == 9 and router.completed == 9
    assert router.replayed >= 1, "the kill never forced a replay"
    for rec, prompt in zip(recs, prompts):
        assert rec.tokens == _expected_tokens(prompt, 12, salt=3), (
            f"request {rec.id} (replays={rec.replays}) tokens diverged")
    victim_recs = read_attempts(goodput.replica_dir(
        str(tmp_path / "fleet"), 1))
    assert len(victim_recs) >= 2  # killed + respawned
    agg = aggregate_serving(str(tmp_path / "fleet"))
    assert agg["accounted_frac"] == pytest.approx(1.0, abs=0.05)
    events = goodput.read_journal(
        goodput.serving_journal_path(str(tmp_path / "fleet")))
    assert any(e["ev"] == "replay" for e in events)


@pytest.mark.chaos
def test_socket_fleet_affinity_routes_to_warm_replica(tmp_path):
    """Prefix-affinity over the socket transport: a shared-prefix
    workload concentrates on the replica whose heartbeat advertises the
    warm blocks, and the router's gauges record the wins."""
    ckpt = tmp_path / "ckpts"
    _fake_ckpt(ckpt, 1, salt=1)
    fleet, router = _start_fleet(
        tmp_path, 2, ckpt, transport="socket", affinity=True,
        extra_argv=("--prefix_cache", "true", "--page_size", "4"))
    try:
        shared = np.asarray([5, 6, 7, 8, 1, 2, 3, 4], np.int32)
        # seed request warms ONE replica's cache; completing it first
        # makes the advertisement visible before the followers place
        seed = router.submit(shared, 4)
        _drive(router, fleet, timeout_s=30.0)
        warm = seed.replica
        # ... once the heartbeat that carries it has come round: under a
        # loaded box the followers could be placed before it did
        deadline = time.time() + 10.0
        while (not router.clients[warm].prefix_index()
               and time.time() < deadline):
            time.sleep(0.02)
        for i in range(6):
            p = np.concatenate([shared[:4],
                                np.asarray([10 + i] * 4, np.int32)])
            router.submit(p, 4)
        _drive(router, fleet, timeout_s=30.0)
    finally:
        fleet.stop()
    assert router.completed == 7
    followers = [r for r in router.records.values() if r.id != seed.id]
    hits = [r for r in followers if r.replica == warm]
    assert router.affinity_placements >= 6
    assert router.affinity_hits >= len(hits) >= 5, (
        f"warm replica {warm} got {len(hits)}/6 followers")
