"""Multi-host launcher integration (reference
`launch/controllers/master.py:27,65` peer-list sync + the
`test_dist_base.py:943` spawn-N-ranks-on-localhost pattern).

Two launcher invocations — each simulating one host with 1 process and 4
virtual CPU devices — rendezvous through the TCPStore master, receive the
synced `PADDLE_TRAINER_ENDPOINTS`/`PADDLE_COORDINATOR` env, and
`fleet.init` forms ONE 8-device JAX world across both processes; a
cross-process reduction agrees on every rank."""
import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAINER = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    # one world across both launcher-spawned processes
    assert jax.process_count() == 2, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()
    # endpoints were synced: both ranks see the same non-loopback-default
    # 2-entry list, and this rank's endpoint is in it
    eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
    assert len(eps) == 2 and os.environ["PADDLE_CURRENT_ENDPOINT"] in eps

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = fleet.get_hybrid_communicate_group().mesh
    x = jax.device_put(np.arange(8.0), NamedSharding(mesh, P("dp")))
    total = float(jax.jit(lambda a: a.sum())(x))  # psum over both hosts
    assert total == 28.0, total
    print("RANK", os.environ["PADDLE_TRAINER_ID"], "OK", total, flush=True)
""")


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_two_node_world_allreduce(tmp_path):
    from proc_utils import proc_timeout, shed_parent_memory

    shed_parent_memory()
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    master = f"127.0.0.1:{_free_port()}"

    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--master", master, "--nnodes", "2", "--rank", str(rank),
             "--nproc_per_node", "1", "--max_restarts", "0",
             "--log_dir", str(tmp_path / f"log{rank}"), str(script)],
            env=env, cwd=str(tmp_path)))
    deadline = time.time() + proc_timeout(300)
    for p in procs:
        rc = p.wait(timeout=max(5, deadline - time.time()))
        assert rc == 0, _logs(tmp_path)
    logs = _logs(tmp_path)
    assert "RANK 0 OK 28.0" in logs and "RANK 1 OK 28.0" in logs, logs


def _logs(tmp_path):
    out = []
    for rank in range(2):
        f = tmp_path / f"log{rank}" / "workerlog.0"
        if f.exists():
            out.append(f"--- node {rank} ---\n" + f.read_text())
    return "\n".join(out)
