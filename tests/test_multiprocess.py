"""Multi-process distributed execution: 2 OS processes, 8 global
devices, one shuffled TPC-H-shaped join+agg, oracle-equal on every
controller.

Reference analogue: the multi-executor UCX shuffle deployment the
reference only ever exercised on real clusters (SURVEY §4 "Multi-node
without a real cluster: they don't simulate it") — this closes that gap
with a hermetic 2-process CPU fixture over jax.distributed + gloo.
"""
import os
import socket
import subprocess
import sys

import pytest
from conftest import REPO, cpu_worker_env


def _cpu_collectives_unavailable() -> str:
    """Multi-process jax.distributed on the CPU backend needs the gloo
    TCP collectives; some jaxlib builds ship without them, and every
    worker then dies in ``jax.distributed.initialize``.  Detect that
    at collection time instead of burning a subprocess timeout on the
    known-doomed drill (ROADMAP "Known environment caveats")."""
    if os.environ.get("JAX_PLATFORMS", "").lower() not in ("", "cpu"):
        return ""
    try:
        from jax.lib import xla_extension
    except Exception as e:  # noqa: BLE001 — no jaxlib = no drill either
        return f"jax.lib.xla_extension unavailable: {e!r}"
    if not hasattr(xla_extension, "make_gloo_tcp_collectives"):
        return ("this jaxlib build has no gloo TCP collectives "
                "(xla_extension.make_gloo_tcp_collectives missing) — "
                "multi-process CPU collectives cannot initialize")
    return ""


_SKIP_REASON = _cpu_collectives_unavailable()
if _SKIP_REASON:
    pytest.skip(_SKIP_REASON, allow_module_level=True)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_shuffled_join_oracle_equal(tmp_path):
    # pre-create a multi-file parquet dataset (>= 8 files so every
    # global shard owns at least one split) for the ownership check
    import numpy as np

    import spark_rapids_tpu as srt

    rng = np.random.RandomState(7)
    scan_dir = os.path.join(str(tmp_path), "scan")
    srt.Session(tpu_enabled=False).create_dataframe(
        {"g": rng.randint(0, 5, 4000),
         "v": (rng.rand(4000) * 100).round(6)},
        n_partitions=8).write_parquet(scan_dir)

    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    script = os.path.join(os.path.dirname(__file__),
                          "mp_worker_script.py")
    env = cpu_worker_env()
    repo = REPO

    procs = [subprocess.Popen(
        [sys.executable, script, coordinator, "2", str(pid), scan_dir],
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
        pytest.fail("multi-process workers timed out:\n"
                    + "\n".join(o or "" for o in outs))
    opened = {}
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, \
            f"worker {pid} rc={p.returncode}:\n{out[-4000:]}"
        assert f"MP RESULT OK pid={pid}" in out, out[-4000:]
        for line in out.splitlines():
            if line.startswith(f"MP OPENED pid={pid} "):
                opened[pid] = set(
                    line.split("files=", 1)[1].split(","))
    # per-process split ownership: disjoint file-open sets covering
    # the dataset (reference: GpuParquetScan.scala:174)
    assert set(opened) == {0, 1}, opened
    assert opened[0] and opened[1]
    assert not (opened[0] & opened[1]), opened
    all_files = {f for f in os.listdir(scan_dir)
                 if f.startswith("part-")}
    assert opened[0] | opened[1] == all_files, (opened, all_files)
