"""Parameter-server mode: tables, RPC service, communicator, fleet glue.

Ref intent: python/paddle/fluid/tests/unittests/test_dist_base.py
(start_pserver + trainer procs on localhost) and
test_dist_fleet_ps*.py — here servers run as in-process threads on
ephemeral localhost ports, which exercises the identical TCP/RPC path.
"""

import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import ps


@pytest.fixture(autouse=True)
def _restore_ps_runtime():
    """`_runtime_for` installs a process-wide PS runtime. Put back what
    was there: a later test file of the same worker would otherwise
    meet a runtime whose servers are gone (`fleet.metrics` reduces
    through whatever runtime it finds)."""
    import paddle_tpu.distributed.ps.runtime as rtmod

    prev = rtmod._runtime
    yield
    rtmod._runtime = prev


@pytest.fixture()
def two_servers():
    s1 = ps.PSServer("127.0.0.1:0").start()
    s2 = ps.PSServer("127.0.0.1:0").start()
    eps = [f"127.0.0.1:{s1.port}", f"127.0.0.1:{s2.port}"]
    client = ps.PSClient(eps)
    yield client, eps
    client.close()
    s1.stop()
    s2.stop()


def _runtime_for(client, eps, mode="sync", n_trainers=1, geo_step=2):
    rm = ps.PSRoleMaker(server_endpoints=eps, role="TRAINER",
                        trainer_id=0, n_trainers=n_trainers)
    rt = ps.PSRuntime(rm, mode=mode, geo_step=geo_step)
    rt._client = client
    from paddle_tpu.distributed.ps.service import Communicator

    rt._communicator = Communicator(client, mode=mode,
                                    geo_step=geo_step).start()
    import paddle_tpu.distributed.ps.runtime as rtmod

    rtmod._runtime = rt
    return rt


def test_dense_table_sgd(two_servers):
    client, _ = two_servers
    client.create_dense_table("w", [3], optimizer="sgd", lr=0.1,
                              initial=np.array([1.0, 2.0, 3.0], np.float32))
    client.push_dense_grad("w", np.array([1.0, 1.0, 1.0], np.float32))
    got = client.pull_dense("w")
    np.testing.assert_allclose(got, [0.9, 1.9, 2.9], rtol=1e-6)


def test_sparse_table_partitioned_pull_push(two_servers):
    client, _ = two_servers
    client.create_sparse_table("emb", 4, optimizer="sgd", lr=0.5,
                               init_range=0.0)  # zero init
    ids = np.array([0, 1, 2, 3, 10, 11], np.int64)  # both shards
    rows = client.pull_sparse("emb", ids)
    np.testing.assert_allclose(rows, 0.0)
    client.push_sparse_grad("emb", ids, np.ones((6, 4), np.float32))
    rows = client.pull_sparse("emb", ids)
    np.testing.assert_allclose(rows, -0.5, rtol=1e-6)
    # rows actually live on different servers
    assert client._call(0, "table_size", "emb") > 0
    assert client._call(1, "table_size", "emb") > 0


def test_sparse_lazy_init_deterministic(two_servers):
    client, _ = two_servers
    client.create_sparse_table("e2", 8, init_range=0.1)
    a = client.pull_sparse("e2", np.array([7], np.int64))
    b = client.pull_sparse("e2", np.array([7], np.int64))
    np.testing.assert_allclose(a, b)
    assert np.abs(a).max() <= 0.1 and np.abs(a).sum() > 0


def test_save_load_roundtrip(two_servers):
    client, _ = two_servers
    client.create_sparse_table("e3", 2, optimizer="sgd", lr=1.0,
                               init_range=0.0)
    ids = np.arange(6, dtype=np.int64)
    client.push_sparse_grad("e3", ids, -np.ones((6, 2), np.float32))
    state = client.save()
    client.push_sparse_grad("e3", ids, np.full((6, 2), 5.0, np.float32))
    client.load(state)
    rows = client.pull_sparse("e3", ids)
    np.testing.assert_allclose(rows, 1.0, rtol=1e-6)


def test_distributed_embedding_trains(two_servers):
    client, eps = two_servers
    _runtime_for(client, eps, mode="sync")
    emb = ps.DistributedEmbedding("demb", 8, optimizer="sgd", lr=2.0,
                                  init_range=0.01)
    ids = paddle.to_tensor(np.array([[1, 3], [5, 3]], np.int64))
    losses = []
    for _ in range(40):
        out = emb(ids)  # [2, 2, 8]
        loss = ((out - 1.0) ** 2).mean()
        loss.backward()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.05, (losses[0], losses[-1])


def test_ps_optimizer_dense_round(two_servers):
    client, eps = two_servers
    _runtime_for(client, eps, mode="sync")
    paddle.seed(0)
    lin = paddle.nn.Linear(4, 1)
    opt = ps.PSOptimizer(lin.parameters(), lr=0.1, optimizer="sgd")
    rng = np.random.RandomState(0)
    w = rng.randn(4, 1).astype(np.float32)
    losses = []
    for _ in range(40):
        x = paddle.to_tensor(rng.randn(8, 4).astype(np.float32))
        y = paddle.to_tensor(np.asarray(x.numpy() @ w, np.float32))
        loss = ((lin(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.1, (losses[0], losses[-1])


def test_async_communicator_concurrent_trainers(two_servers):
    client, eps = two_servers
    client.create_sparse_table("hog", 4, optimizer="sgd", lr=0.1,
                               init_range=0.0)
    from paddle_tpu.distributed.ps.service import Communicator

    comm = Communicator(client, mode="async").start()
    n_push = 50

    def trainer(tid):
        ids = np.array([tid], np.int64)
        for _ in range(n_push):
            comm.push_sparse("hog", ids, np.ones((1, 4), np.float32))

    threads = [threading.Thread(target=trainer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    comm.stop()
    rows = client.pull_sparse("hog", np.arange(4, dtype=np.int64))
    # every push must land exactly once: row = -lr * n_push
    np.testing.assert_allclose(rows, -0.1 * n_push, rtol=1e-5)


def test_geo_mode_delta_push(two_servers):
    client, eps = two_servers
    rt = _runtime_for(client, eps, mode="geo", geo_step=2)
    emb = ps.DistributedEmbedding("gemb", 4, lr=0.5, init_range=0.0)
    comm = rt.communicator
    ids = paddle.to_tensor(np.array([2], np.int64))

    emb(ids).sum().backward()
    comm.step_end()  # step 1: no flush yet
    rows = client.pull_sparse("gemb", np.array([2], np.int64))
    np.testing.assert_allclose(rows, 0.0)

    emb(ids).sum().backward()
    comm.step_end()  # step 2: flush -lr * (g1+g2) = -0.5 * 2
    rows = client.pull_sparse("gemb", np.array([2], np.int64))
    np.testing.assert_allclose(rows, -1.0, rtol=1e-6)


def test_fleet_ps_roles(two_servers):
    client, eps = two_servers
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.a_sync = True
    rm = ps.PSRoleMaker(server_endpoints=eps, role="TRAINER",
                        trainer_id=0, n_trainers=1)
    fleet.init(rm, strategy=strategy)
    assert fleet.is_worker() and not fleet.is_server()
    rt = fleet.fleet.ps_runtime
    assert rt.mode == "async"
    rt._client = client  # reuse fixture servers
    fleet.init_worker()
    client.create_dense_table("fw", [2], lr=0.5,
                              initial=np.zeros(2, np.float32))
    rt.communicator.push_dense("fw", np.ones(2, np.float32))
    rt.communicator.flush()
    np.testing.assert_allclose(client.pull_dense("fw"), -0.5)
    fleet.stop_worker()


def test_server_subprocess_roundtrip(tmp_path):
    """Real process isolation: server in a subprocess via the env
    contract (TRAINING_ROLE=PSERVER), trainer in this process."""
    import os
    import socket
    import subprocess
    import sys
    import time

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    code = (
        "import os\n"
        "from paddle_tpu.distributed import ps\n"
        "rm = ps.PSRoleMaker()\n"
        "assert rm.is_server()\n"
        "rt = ps.PSRuntime(rm)\n"
        "rt.run_server()\n"
    )
    env = dict(os.environ, TRAINING_ROLE="PSERVER",
               PADDLE_PORT=str(port), POD_IP="127.0.0.1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH="/root/repo")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env)
    try:
        client = ps.PSClient([f"127.0.0.1:{port}"])
        deadline = time.monotonic() + 30
        while True:
            try:
                client.create_dense_table(
                    "sub", [2], lr=1.0, initial=np.zeros(2, np.float32))
                break
            except (ConnectionError, OSError):
                client.close()
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        client.push_dense_grad("sub", np.ones(2, np.float32))
        np.testing.assert_allclose(client.pull_dense("sub"), -1.0)
        client.stop_servers()
        assert proc.wait(timeout=20) == 0
    finally:
        if proc.poll() is None:
            proc.kill()


# ---------------------------------------------------------------------------
# durable PS: WAL recovery, exactly-once, failover, fencing (ISSUE 10)
# ---------------------------------------------------------------------------


def _state_bytes(states):
    """save() output -> comparable bytes (bitwise equality probe)."""
    out = []
    for sd in states:
        out.append({name: {k: np.asarray(v).tobytes()
                           for k, v in table.items()}
                    for name, table in sd.items()})
    return out


def _push_workload(client, n=5):
    """The canonical mixed dense+sparse push sequence used by the
    recovery-parity tests (adagrad on both so optimizer state matters)."""
    client.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
    client.create_sparse_table("emb", 8, optimizer="adagrad", lr=0.1,
                               init_range=0.05, seed=3)
    for i in range(n):
        client.push_dense_grad("w", np.full(4, i + 1, np.float32))
        client.push_sparse_grad("emb", np.array([1, 2, 3], np.int64),
                                np.full((3, 8), 0.5, np.float32))


def test_wal_recovery_bitwise(tmp_path):
    """kill the transport mid-life (nothing flushed gracefully), restart
    over the same WAL dir: table state replays bitwise-identical."""
    s = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    c = ps.PSClient([s.endpoint])
    _push_workload(c)
    want = c.save()
    s.kill_transport()  # ungraceful: no close/checkpoint/final fsync

    s2 = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    assert s2.recovered_records == 10
    c2 = ps.PSClient([s2.endpoint])
    c2._sparse_dims["emb"] = 8
    assert _state_bytes(c2.save()) == _state_bytes(want)
    c2.stop_servers()
    s2.stop()


def test_wal_checkpoint_rotation_bounds_replay(tmp_path):
    """checkpoint() folds the log into a snapshot; replay afterwards
    covers only post-checkpoint records and stays bitwise (adagrad
    accumulators ride in the snapshot)."""
    s = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    c = ps.PSClient([s.endpoint])
    _push_workload(c, n=3)
    c.checkpoint()
    c.push_dense_grad("w", np.ones(4, np.float32))
    want = c.save()
    s.kill_transport()

    s2 = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    assert s2.recovered_records == 1  # only the post-checkpoint push
    c2 = ps.PSClient([s2.endpoint])
    c2._sparse_dims["emb"] = 8
    assert _state_bytes(c2.save()) == _state_bytes(want)
    c2.stop_servers()
    s2.stop()


def test_wal_torn_tail_tolerated(tmp_path):
    """A torn tail (partial record a crash can leave) cleanly ends
    replay instead of poisoning recovery."""
    from paddle_tpu.distributed.ps.wal import WriteAheadLog

    path = str(tmp_path / "t.wal")
    wal = WriteAheadLog(path, generation=0)
    wal.append(("c", 0, "push_dense_grad", ("w",)), sync_interval=1)
    wal.append(("c", 1, "push_dense_grad", ("w",)), sync_interval=1)
    wal.close()
    with open(path, "ab") as f:
        f.write(b"\x13\x37garbage-torn-tail")
    gen, records = WriteAheadLog.replay(path)
    assert gen == 0 and len(records) == 2
    assert records[1][1] == 1


def test_push_retry_dedups_exactly_once(tmp_path):
    """ps.push@N:raise fires after the WAL append, before the apply: the
    client retries transparently and the trajectory matches the
    never-faulted run. A duplicate (client_id, seq) re-sent on the wire
    — a retry whose first attempt DID apply but whose ack was lost — is
    suppressed by the server watermark; and the duplicate record the
    faulted attempt left in the WAL dedupes again at replay time."""
    from paddle_tpu.framework import faults, monitor

    ref_s = ps.PSServer("127.0.0.1:0").start()
    rc = ps.PSClient([ref_s.endpoint])
    _push_workload(rc)
    want = rc.save()

    s = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    c = ps.PSClient([s.endpoint], retry_backoff_s=0.01)
    with faults.inject("ps.push@3:raise"):
        _push_workload(c)
    assert _state_bytes(c.save()) == _state_bytes(want)

    # ack-lost retry: replay the last dense push verbatim (same seq)
    seq = c._seqs[(0, "w")]
    before = monitor.stat_get("ps.dedup_hits")
    c._call(0, "push_dense_grad",
            ("w", np.full(4, 5, np.float32), c.client_id, seq))
    assert monitor.stat_get("ps.dedup_hits") == before + 1
    assert _state_bytes(c.save()) == _state_bytes(want)  # not re-applied

    # the faulted attempt logged its record, raised before applying, and
    # the retry logged it AGAIN — recovery must dedup the duplicate
    s.kill_transport()
    before = monitor.stat_get("ps.dedup_hits")
    s2 = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    assert monitor.stat_get("ps.dedup_hits") == before + 1
    c2 = ps.PSClient([s2.endpoint])
    c2._sparse_dims["emb"] = 8
    assert _state_bytes(c2.save()) == _state_bytes(want)
    c2.stop_servers()
    s2.stop()
    rc.stop_servers()
    ref_s.stop()


def test_push_crash_recovery_subprocess(tmp_path):
    """Satellite 3: deterministic ps.push@N:crash through the fault
    grammar — the server process dies with exit 137 mid-push (after the
    WAL append), a restarted server replays the log, and the client's
    transparent retry lands exactly once: state equals the uninterrupted
    run bitwise."""
    import os
    import socket
    import subprocess
    import sys
    import threading
    import time

    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]

    code = (
        "from paddle_tpu.distributed import ps\n"
        "rt = ps.PSRuntime(ps.PSRoleMaker())\n"
        "rt.run_server()\n"
    )
    base_env = dict(os.environ, TRAINING_ROLE="PSERVER",
                    PADDLE_PORT=str(port), POD_IP="127.0.0.1",
                    JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo",
                    PADDLE_PS_WAL_DIR=str(tmp_path))
    env = dict(base_env, PADDLE_TPU_FAULTS="ps.push@4:crash")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env)
    relaunched = []
    try:
        c = ps.PSClient([f"127.0.0.1:{port}"], op_deadline_s=60.0,
                        retry_backoff_s=0.05)
        deadline = time.monotonic() + 30
        while True:
            try:
                c.create_dense_table("w", [4], optimizer="adagrad",
                                     lr=0.1)
                break
            except (ConnectionError, OSError):
                c.close()
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.2)

        def relauncher():
            # the moment the faulted server dies (exit 137), bring up a
            # clean one on the same port + WAL dir — the supervisor role
            assert proc.wait(timeout=60) == 137
            p2 = subprocess.Popen([sys.executable, "-c", code],
                                  env=base_env)
            relaunched.append(p2)

        t = threading.Thread(target=relauncher, daemon=True)
        t.start()

        # push 4 fires the crash mid-push; the client retries through
        # the death, across the restart, and the WAL+dedup make it
        # apply exactly once
        for i in range(6):
            c.push_dense_grad("w", np.full(4, i + 1, np.float32))
        t.join(timeout=60)
        got = c.pull_dense("w")

        ref_s = ps.PSServer("127.0.0.1:0").start()
        rc = ps.PSClient([ref_s.endpoint])
        rc.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
        for i in range(6):
            rc.push_dense_grad("w", np.full(4, i + 1, np.float32))
        want = rc.pull_dense("w")
        assert got.tobytes() == want.tobytes()
        rc.stop_servers()
        ref_s.stop()
        c.stop_servers()
        if relaunched:
            assert relaunched[0].wait(timeout=20) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
        for p in relaunched:
            if p.poll() is None:
                p.kill()


def test_failover_exactly_once_with_fencing(tmp_path):
    """Primary dies mid-stream: the client promotes the backup (epoch
    bump), the retried push applies exactly once there, and optimizer
    state matches the no-fault trajectory. A zombie primary restarted
    at the stale epoch is fenced."""
    from paddle_tpu.framework import monitor

    backup = ps.PSServer("127.0.0.1:0").start()
    primary = ps.PSServer("127.0.0.1:0", backup=backup.endpoint).start()
    c = ps.PSClient([primary.endpoint], backups=[backup.endpoint],
                    op_deadline_s=20.0, retry_backoff_s=0.02)
    c.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
    for i in range(3):
        c.push_dense_grad("w", np.full(4, i + 1, np.float32))

    ref_s = ps.PSServer("127.0.0.1:0").start()
    rc = ps.PSClient([ref_s.endpoint])
    rc.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
    for i in range(4):
        rc.push_dense_grad("w", np.full(4, i + 1, np.float32))
    want = rc.pull_dense("w")

    primary.kill_transport()
    fo = monitor.stat_get("ps.failovers")
    c.push_dense_grad("w", np.full(4, 4, np.float32))  # rides failover
    assert monitor.stat_get("ps.failovers") == fo + 1
    assert c.endpoints[0] == backup.endpoint
    assert c.server_epoch() == (1, False)
    assert c.pull_dense("w").tobytes() == want.tobytes()

    # zombie: old primary relaunched at stale epoch 0 still forwarding
    # to the (now-promoted) backup — first replicate gets FencedError,
    # the zombie marks itself fenced and refuses further mutations
    z = ps.PSServer("127.0.0.1:0", backup=backup.endpoint,
                    epoch=0).start()
    zc = ps.PSClient([z.endpoint], op_deadline_s=3.0)
    zc.create_dense_table("zz", [2])
    with pytest.raises(RuntimeError, match="FencedError"):
        zc.push_dense_grad("zz", np.ones(2, np.float32))
    assert z._fenced
    with pytest.raises(RuntimeError, match="FencedError"):
        zc.push_dense_grad("zz", np.ones(2, np.float32))

    rc.stop_servers()
    ref_s.stop()
    zc.close()
    z.stop()
    c.stop_servers()
    backup.stop()


def test_replicated_pushes_dedup_on_backup():
    """Sync replication forwards (cid, seq), so a push that was applied
    AND replicated — but whose ack never reached the client — gets
    retried across the failover and DEDUPED by the promoted backup:
    exactly-once even though two servers saw it. A transient fault at
    the backup's own push site must stay invisible to the client (link
    retry), not surface as a hard error."""
    from paddle_tpu.framework import faults, monitor

    backup = ps.PSServer("127.0.0.1:0").start()
    primary = ps.PSServer("127.0.0.1:0", backup=backup.endpoint).start()
    c = ps.PSClient([primary.endpoint], backups=[backup.endpoint],
                    retry_backoff_s=0.01, op_deadline_s=20.0)
    c.create_dense_table("w", [2], optimizer="sgd", lr=1.0)
    # hit 4 lands on the BACKUP's ps.push site (order: p1, b2, p3, b4):
    # the replica link retries the transient errR instead of failing
    with faults.inject("ps.push@4:raise"):
        c.push_dense_grad("w", np.ones(2, np.float32))
        c.push_dense_grad("w", np.ones(2, np.float32))
    c.push_dense_grad("w", np.ones(2, np.float32))
    np.testing.assert_allclose(backup._tables["w"].pull(), -3.0)

    # primary dies after applying + replicating seq=2, before its ack:
    # the client's retry re-sends the same (client_id, seq) and rides
    # the failover to the backup, which already holds it
    seq = c._seqs[(0, "w")]
    primary.kill_transport()
    before = monitor.stat_get("ps.dedup_hits")
    c._call(0, "push_dense_grad",
            ("w", np.ones(2, np.float32), c.client_id, seq))
    assert monitor.stat_get("ps.dedup_hits") == before + 1
    assert c.endpoints[0] == backup.endpoint
    np.testing.assert_allclose(c.pull_dense("w"), -3.0)
    c.stop_servers()
    backup.stop()
    primary.stop()


def test_socket_cache_reconnect_after_restart(tmp_path):
    """Satellite 1: a server restart leaves a dead cached socket —
    the client must detect the broken pipe, drop it, and redial instead
    of failing forever."""
    s = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path)).start()
    port = s.port
    c = ps.PSClient([s.endpoint], op_deadline_s=20.0,
                    retry_backoff_s=0.05)
    c.create_dense_table("w", [2], optimizer="sgd", lr=1.0)
    c.push_dense_grad("w", np.ones(2, np.float32))
    assert c._socks[0] is not None  # connection is cached
    s.kill_transport()
    # same port, same WAL dir: the restarted rank
    s2 = ps.PSServer(f"127.0.0.1:{port}", wal_dir=str(tmp_path)).start()
    c.push_dense_grad("w", np.ones(2, np.float32))  # transparent redial
    np.testing.assert_allclose(c.pull_dense("w"), -2.0)
    c.stop_servers()
    s2.stop()


def test_client_deadline_exhaustion_raises_unavailable():
    """With no server and no backup, a retriable call fails with
    PSUnavailableError (a ConnectionError subclass, so bootstrap polls
    keep working) once its deadline is spent."""
    import socket
    import time

    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        port = sk.getsockname()[1]
    c = ps.PSClient([f"127.0.0.1:{port}"], op_deadline_s=0.5,
                    retry_backoff_s=0.05)
    t0 = time.monotonic()
    with pytest.raises(ps.PSUnavailableError):
        c.pull_dense("w")
    assert time.monotonic() - t0 < 10.0
    assert isinstance(ps.PSUnavailableError("x"), ConnectionError)


def test_geo_staleness_bound_forces_flush(two_servers):
    """Satellite/tentpole (d): geo accumulation is bounded — once
    FLAGS_ps_geo_staleness pending update rows accumulate, the
    Communicator force-flushes without waiting for the geo_step
    cadence."""
    from paddle_tpu.framework import monitor

    client, eps = two_servers
    client.create_sparse_table("geo", 4, optimizer="sum", lr=1.0,
                               init_range=0.0)
    from paddle_tpu.distributed.ps.service import Communicator

    paddle.set_flags({"FLAGS_ps_geo_staleness": 4})
    try:
        comm = Communicator(client, mode="geo", geo_step=1000)
        comm.set_geo_scale("geo", -0.5)
        forced = monitor.stat_get("ps.geo_forced_flushes")
        ids = np.array([0, 1], np.int64)
        comm.push_sparse("geo", ids, np.ones((2, 4), np.float32))
        # 2 pending rows: under the bound, nothing on the server yet
        np.testing.assert_allclose(
            client.pull_sparse("geo", ids), 0.0)
        comm.push_sparse("geo", ids, np.ones((2, 4), np.float32))
        # 4th pending row hits the bound -> forced sync flush
        assert monitor.stat_get("ps.geo_forced_flushes") == forced + 1
        np.testing.assert_allclose(
            client.pull_sparse("geo", ids), -1.0, rtol=1e-6)
        assert comm._geo_pending == 0
    finally:
        paddle.set_flags({"FLAGS_ps_geo_staleness": 64})


def test_ps_chaos_schedule_certified(tmp_path):
    """ChaosSchedule over the PS fault sites: every planned fault fires
    (fired == planned), and the final state shows zero lost and zero
    double-applied updates."""
    from paddle_tpu.framework import faults

    backup = ps.PSServer("127.0.0.1:0").start()
    primary = ps.PSServer("127.0.0.1:0", wal_dir=str(tmp_path),
                          backup=backup.endpoint).start()
    c = ps.PSClient([primary.endpoint], backups=[backup.endpoint],
                    retry_backoff_s=0.01, op_deadline_s=20.0)

    ref_s = ps.PSServer("127.0.0.1:0").start()
    rc = ps.PSClient([ref_s.endpoint])

    n = 8
    with faults.ChaosSchedule("ps.push@3:raise", "ps.push@6:raise",
                              "ps.pull@2:delay:0.01",
                              "ps.wal_append@5:delay:0.01") as chaos:
        c.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
        for i in range(n):
            c.push_dense_grad("w", np.full(4, i + 1, np.float32))
            c.pull_dense("w")
        fired = chaos.verify()   # fired == planned, else AssertionError
    assert fired["ps.push"] == 2

    rc.create_dense_table("w", [4], optimizer="adagrad", lr=0.1)
    for i in range(n):
        rc.push_dense_grad("w", np.full(4, i + 1, np.float32))
    # zero lost + zero duplicated == bitwise trajectory parity, on the
    # primary AND the sync backup
    assert c.pull_dense("w").tobytes() == rc.pull_dense("w").tobytes()
    assert (backup._tables["w"].pull().tobytes()
            == rc.pull_dense("w").tobytes())
    rc.stop_servers()
    ref_s.stop()
    c.stop_servers()
    primary.stop()
    backup.stop()


def test_ps_prometheus_gauges():
    """Satellite 6: the durable-PS gauge family is exported with stable
    names and mirrored in the JSON snapshot."""
    from paddle_tpu import observe
    from paddle_tpu.framework import monitor

    monitor.stat_add("ps.wal_bytes", 0)     # ensure stats exist
    text = observe.prometheus_text()
    for name in ("paddle_ps_wal_bytes",
                 "paddle_ps_replication_lag_updates",
                 "paddle_ps_failovers_total",
                 "paddle_ps_dedup_hits_total"):
        assert text.count(f"# TYPE {name} ") == 1, name
        assert any(line.startswith(name + " ")
                   for line in text.splitlines()), name
    snap = observe.snapshot()
    assert set(snap["ps"]) == {"wal_bytes", "replication_lag_updates",
                               "failovers", "dedup_hits"}
