"""Elastic SIGKILL drill: a 2-process multi-controller job loses one
worker to kill -9 mid-query and the survivor finishes it anyway.

Worker 1 SIGKILLs itself the moment its first stage checkpoint commits
(``recovery.killAfterCheckpoints=1`` — a real power-cut, no unwind, no
goodbye).  Worker 0 must detect the loss through the elastic protocol
(heartbeat staleness / deadline-guarded collectives), re-form the mesh
on its surviving devices, resume the checkpointed stage from its local
recovery store and return the q3-shaped answer bit-identical to the CPU
oracle — with ``peer_lost``/``mesh_shrink`` accounted in the metrics.
"""
import os
import socket
import subprocess
import sys

import pytest
from conftest import REPO, cpu_worker_env


@pytest.mark.slow
@pytest.mark.fault_injection
def test_sigkill_one_worker_mid_query_survivor_completes(tmp_path):
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    coordinator = f"127.0.0.1:{port}"
    script = os.path.join(os.path.dirname(__file__),
                          "mp_elastic_worker.py")
    repo = REPO
    env = cpu_worker_env()
    hb_dir = str(tmp_path / "heartbeats")
    rec_root = str(tmp_path / "recovery")

    procs = [subprocess.Popen(
        [sys.executable, script, coordinator, "2", str(pid), hb_dir,
         rec_root],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=repo) for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=480)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("elastic drill workers timed out (the survivor "
                    "wedged instead of detecting the dead peer):\n"
                    + "\n".join(o or "" for o in outs))
    if any("Multiprocess computations aren't implemented" in (o or "")
           for o in outs):
        pytest.skip("this jax build's CPU backend lacks multi-process "
                    "collectives (same limitation as "
                    "test_multiprocess) — no mesh to shrink")
    # worker 1 must have died by ITS OWN SIGKILL, not finished
    assert procs[1].returncode == -9, \
        f"worker 1 rc={procs[1].returncode} (expected SIGKILL):" \
        f"\n{outs[1][-4000:]}"
    assert "MPE RESULT OK pid=1" not in (outs[1] or "")
    # worker 0 survived, shrank, resumed and verified against the oracle
    assert procs[0].returncode == 0, \
        f"survivor rc={procs[0].returncode}:\n{outs[0][-4000:]}"
    assert "MPE RESULT OK pid=0" in outs[0], outs[0][-4000:]
