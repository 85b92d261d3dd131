"""Shared harness for link-shaped PS fleet benchmarks.

Spawns a real localhost topology (scheduler + S servers + N workers) with
the DCN emulated by kernel TCP pacing (`BYTEPS_PACING_RATE`, van.cc):
every data connection is rate-capped by the kernel's internal pacing, so
— unlike a userspace relay proxy — the emulation itself costs the 1-core
host nothing and the fleet under test keeps the whole CPU. Used by
tools/bench_scaling.py (scaling curve, priority quantification) and
tools/bench_overlap_bw.py (overlap-vs-bandwidth).

Link model: per-connection pacing at ``nic_bytes / num_servers`` makes a
worker's aggregate egress across its server connections equal one NIC of
``nic_bytes``/s, and (with servers == workers) each server's ingress the
same — the balanced equal-NIC fabric BytePS's bandwidth-optimality
argument assumes (SURVEY.md §6 north star).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def cpu_busy_since(prev=None):
    """(busy_fraction_since_prev, snapshot). Reads /proc/stat aggregate so
    each bench point can report whether the HOST (not the emulated link)
    bound the measurement — the honesty flag the 1-core box needs."""
    with open("/proc/stat") as f:
        parts = f.readline().split()[1:]
    vals = [int(x) for x in parts]
    idle = vals[3] + (vals[4] if len(vals) > 4 else 0)  # idle + iowait
    total = sum(vals)
    if prev is None:
        return None, (idle, total)
    didle, dtotal = idle - prev[0], total - prev[1]
    busy = 1.0 - (didle / dtotal) if dtotal > 0 else 0.0
    return round(busy, 3), (idle, total)


def run_fleet(workers: int, servers: int, worker_argv, env_extra=None,
              timeout: int = 1800):
    """Launch scheduler + servers + workers; return (rc, records) where
    records are the JSON lines each worker printed. Always reaps the
    whole fleet, including on timeout/crash."""
    port = free_port()
    env = dict(os.environ)
    env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(workers),
        "DMLC_NUM_SERVER": str(servers),
        "PYTHONPATH": REPO,
    })
    env.update(env_extra or {})
    aux = []
    for role, count in (("scheduler", 1), ("server", servers)):
        for _ in range(count):
            e = dict(env)
            e["DMLC_ROLE"] = role
            aux.append(subprocess.Popen(
                [sys.executable, "-m", "byteps_tpu.server"], env=e))
    wprocs = []
    for r in range(workers):
        e = dict(env)
        e["DMLC_ROLE"] = "worker"
        e["DMLC_WORKER_ID"] = str(r)
        wprocs.append(subprocess.Popen(
            [sys.executable] + list(worker_argv), env=e,
            stdout=subprocess.PIPE, text=True))
    rc = 0
    records = []
    try:
        deadline = time.time() + timeout
        for wp in wprocs:
            left = max(1.0, deadline - time.time())
            try:
                sout, _ = wp.communicate(timeout=left)
            except subprocess.TimeoutExpired:
                rc |= 1
                continue
            for ln in sout.splitlines():
                if ln.startswith("{"):
                    records.append(json.loads(ln))
            rc |= wp.returncode
    finally:
        for p in wprocs:
            if p.poll() is None:
                p.kill()
        for p in aux:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
                rc |= 1
    return rc, records


def load_model_sizes(model: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "model_shapes.json")
    with open(path) as f:
        shapes = json.load(f)
    if model not in shapes:
        raise SystemExit(
            f"unknown model {model!r}; have {sorted(shapes)} "
            "(regenerate with tools/dump_model_shapes.py)")
    return shapes[model]
