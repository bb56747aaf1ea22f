"""Cross-process parameter-server table service (reference
brpc_ps_client/server pull-push over the_one_ps; here distributed.rpc +
the in-process tables as shard backend — distributed/ps/service.py).

Topology under test: 2 server processes + 2 worker processes, sparse
rows sharded id%2 across servers, dense table on its hash owner."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


ROLE_SCRIPT = textwrap.dedent("""
    import os, sys
    import numpy as np
    from paddle_tpu.distributed.ps import PaddleCloudRoleMaker
    from paddle_tpu.distributed.ps.service import DistributedPS

    master = os.environ["TEST_MASTER"]
    ps = DistributedPS(PaddleCloudRoleMaker(), master_endpoint=master)
    role = os.environ["TRAINING_ROLE"]
    if role == "PSERVER":
        ps.run_server()
        sys.exit(0)

    wid = int(os.environ["PADDLE_TRAINER_ID"])
    dense = ps.create_dense_table("w", (4,), optimizer="sgd", lr=0.5)
    emb = ps.create_sparse_table("emb", 4, lr=0.1)
    ps.barrier()

    if wid == 0:
        dense.load(np.arange(4, dtype=np.float32))
    ps.barrier()
    # both workers see the loaded value
    np.testing.assert_allclose(dense.pull(),
                               np.arange(4, dtype=np.float32))
    # barrier BEFORE the push: without it worker1 can race ahead (its
    # own check + push) while worker0 sits between the previous barrier
    # and its pull, observing the post-push value — the intermittent
    # full-suite failure of rounds 3-5 was exactly this TOCTOU
    ps.barrier()
    if wid == 1:
        dense.push(np.ones(4, np.float32))  # sgd lr=0.5 -> -0.5
    ps.barrier()
    np.testing.assert_allclose(dense.pull(),
                               np.arange(4, dtype=np.float32) - 0.5)

    # sparse rows span BOTH shards (even ids -> server0, odd -> server1)
    ids = np.array([0, 1, 2, 3, 7], np.int64)
    if wid == 0:
        before = emb.pull(ids)           # lazy-init on owning servers
        grads = np.full((5, 4), 2.0, np.float32)
        emb.push(ids, grads)
        after = emb.pull(ids)
        np.testing.assert_allclose(after, before - 0.1 * 2.0, rtol=1e-6)
    ps.barrier()
    # worker1 sees worker0's rows (shared server state) and total size
    if wid == 1:
        assert emb.size() == 5
        row0 = emb.pull(np.array([7], np.int64))
        assert row0.shape == (1, 4)
    ps.barrier()

    # geo-async table (reference memory_sparse_geo_table): local-replica
    # training, explicit flush, deltas from BOTH workers merge on refresh
    geo = ps.create_geo_sparse_table("gemb", 4, geo_step=100, lr=0.1)
    ps.barrier()
    gids = np.array([2, 5], np.int64)
    base = geo.pull(gids).copy()       # lazy-init on servers, same view
    g = np.full((2, 4), float(wid + 1), np.float32)
    for _ in range(3):
        geo.push(gids, g)              # local only: geo_step=100
    np.testing.assert_allclose(geo.pull(gids), base - 0.1 * 3 * g,
                               rtol=1e-5)
    ps.barrier()
    geo.flush()                        # ship accumulated deltas
    ps.barrier()                       # every worker's deltas are in
    geo.refresh(gids)
    merged = base - 0.1 * 3 * (np.full((2, 4), 1.0) +
                               np.full((2, 4), 2.0))
    np.testing.assert_allclose(geo.pull(gids), merged, rtol=1e-5)

    ps.barrier()
    if wid == 0:
        ps.stop_servers()
    ps.shutdown()
    print("PS-WORKER-OK", wid)
""")


def test_ps_service_two_servers_two_workers(tmp_path):
    from proc_utils import proc_timeout, shed_parent_memory

    shed_parent_memory()
    port = _free_port()
    script = tmp_path / "role.py"
    script.write_text(ROLE_SCRIPT)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    servers = "127.0.0.1:1,127.0.0.1:2"   # layout only (count matters)
    workers = "127.0.0.1:3,127.0.0.1:4"
    procs = []
    for role, n in (("PSERVER", 2), ("TRAINER", 2)):
        for i in range(n):
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
                "TEST_MASTER": f"127.0.0.1:{port}",
                "TRAINING_ROLE": role,
                "PADDLE_TRAINER_ID": str(i),
                "PADDLE_PSERVERS_IP_PORT_LIST": servers,
                "PADDLE_TRAINER_ENDPOINTS": workers,
                # children need no device mesh: rewrite only the suite's
                # device-count flag (preserving any other XLA flags) so
                # each of the 4 interpreters inits one cheap CPU device
                "XLA_FLAGS": " ".join(
                    [f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith(
                         "--xla_force_host_platform_device_count")]
                    + ["--xla_force_host_platform_device_count=1"]),
            })
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
    try:
        # generous deadline: the whole suite shares ONE core, and four
        # fresh interpreters importing jax under that load can take
        # minutes before the barriers even form. Poll ALL procs: one
        # child dying leaves its peers blocked in a barrier forever, so
        # sequential communicate() would burn the whole budget before
        # reporting the actual failure.
        import time

        deadline = time.time() + proc_timeout(600)
        while time.time() < deadline:
            rcs = [p.poll() for p in procs]
            if any(rc not in (None, 0) for rc in rcs) or \
                    all(rc == 0 for rc in rcs):
                break
            time.sleep(0.5)
        # self-exited failures carry the real traceback; peers blocked
        # in a barrier get killed and must be reported AFTER it, or
        # pytest shows a SIGKILLed bystander instead of the cause
        failed = [(p, rc) for p, rc in zip(procs, rcs)
                  if rc not in (None, 0)]
        hung = [p for p, rc in zip(procs, rcs) if rc is None]
        for p in procs:
            if p.poll() is None:
                p.kill()
        outs = {p: p.communicate()[0] for p in procs}
        for p, rc in failed:
            raise AssertionError(f"child rc={rc}: {outs[p][-1500:]}")
        if hung:
            # no child crashed: the harness deadline itself expired (a
            # genuine distributed hang) — say so instead of reporting a
            # SIGKILLed bystander as the failure
            raise AssertionError(
                f"harness deadline exceeded with {len(hung)} children "
                "still running; tails:\n" + "\n---\n".join(
                    outs[p][-600:] for p in hung))
        for p in procs:
            assert p.returncode == 0, outs[p][-1500:]
        joined = "\n".join(outs.values())
        assert "PS-WORKER-OK 0" in joined and "PS-WORKER-OK 1" in joined
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
