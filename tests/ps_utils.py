"""Localhost PS-topology harness for tests.

Reference test strategy (SURVEY.md §4): launch a REAL scheduler + real
CPU server(s) + N real worker processes on 127.0.0.1 (the reference's
run_byteps_test.sh + BYTEPS_FORCE_DISTRIBUTED pattern) and assert numerics
in the workers. No mock transport anywhere.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def topology_env(num_workers: int, num_servers: int, port: int,
                 extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_NUM_SERVER": str(num_servers),
        "PS_HEARTBEAT_INTERVAL": "1",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.update(extra or {})
    return env


def spawn_role(role: str, env: Dict[str, str]) -> subprocess.Popen:
    e = dict(env)
    e["DMLC_ROLE"] = role
    return subprocess.Popen(
        [sys.executable, "-m", "byteps_tpu.server"], env=e,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def spawn_worker(script: str, env: Dict[str, str], rank: int,
                 mode: str = "", extra: Optional[Dict[str, str]] = None
                 ) -> subprocess.Popen:
    e = dict(env)
    e["DMLC_ROLE"] = "worker"
    e["DMLC_WORKER_ID"] = str(rank)
    e["BPS_TEST_MODE"] = mode
    e.update(extra or {})
    return subprocess.Popen(
        [sys.executable, script], env=e,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def run_topology(num_workers: int, num_servers: int, worker_script: str,
                 mode: str = "", extra: Optional[Dict[str, str]] = None,
                 timeout: float = 90.0) -> List[str]:
    """Launch scheduler + servers + workers; wait; return worker outputs.

    Raises AssertionError (with captured output) if any process fails.
    """
    port = free_port()
    env = topology_env(num_workers, num_servers, port, extra)
    procs = [("scheduler", spawn_role("scheduler", env))]
    for _ in range(num_servers):
        procs.append(("server", spawn_role("server", env)))
    workers = []
    for r in range(num_workers):
        p = spawn_worker(worker_script, env, r, mode)
        procs.append((f"worker{r}", p))
        workers.append(p)

    outputs = []
    failed = []
    try:
        for name, p in procs:
            out, _ = p.communicate(timeout=timeout)
            if p.returncode != 0:
                failed.append((name, p.returncode, out))
            if name.startswith("worker"):
                outputs.append(out)
    finally:
        for _, p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    if failed:
        msgs = "\n".join(
            f"--- {n} exited {rc} ---\n{out}" for n, rc, out in failed)
        raise AssertionError(f"topology processes failed:\n{msgs}")
    return outputs


# The van derives a connection's transport (ISSUE 38): on this sandbox
# every peer is local, so a fleet that says nothing runs on shm rings. A
# test that means the TCP wire — the one a remote peer gets — says so.
TCP = {"BYTEPS_VAN_TYPE": "tcp"}


def van_conns(out: str) -> Dict[str, int]:
    """The worker's `van_conns {...}` line (tests/_ps_worker.py): its
    dialled connections by transport and the ring offers that fell back
    (the van's counters)."""
    line = [ln for ln in out.splitlines() if ln.startswith("van_conns ")][-1]
    return json.loads(line[len("van_conns "):])


def assert_transport(outs: List[str], want: str, dialled: int) -> None:
    """Every worker dialled `dialled` connections, all on `want`, by the
    counters AND by the van's DEBUG line — needs BYTEPS_LOG_LEVEL=DEBUG."""
    other = "tcp" if want == "shm" else "shm"
    mark = {"shm": "data path -> shm ring", "tcp": "data path -> tcp socket"}
    for o in outs:
        assert van_conns(o) == {want: dialled, other: 0, "fallback": 0}, \
            o[-2000:]
        assert o.count(mark[want]) == dialled, o[-2000:]
        assert mark[other] not in o, o[-2000:]
