"""The PS fleet of a cell: scheduler and server children on loopback.

Copied from ``chip_smoke.ps_fleet`` (proven on the chip in PR 21) so that
the yardstick does not move when the program's scripts do. The children
never import JAX (``byteps_tpu/server/__init__.py``), so this process stays
the only one that holds the chip.
"""

from __future__ import annotations

import contextlib
import os
import socket
import subprocess
import sys


# glibc's mallopt parameters (malloc.h)
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3


def steady_malloc(settings: dict, env: dict) -> None:
    """Fix glibc malloc's two dynamic thresholds, in this process (mallopt)
    and, through ``env``, in the children it will start. Left dynamic, the
    thresholds follow the history of frees: whether a step's 498 MB of host
    buffers (most leaves are 2.4-9.4 MB) come from the heap or are mapped and
    page-faulted anew then flips between and inside runs, and with it the
    step time (my chip runs, PR 22)."""
    import ctypes

    libc = ctypes.CDLL(None)
    libc.mallopt(M_MMAP_THRESHOLD, settings["mmap_threshold"])
    libc.mallopt(M_TRIM_THRESHOLD, settings["trim_threshold"])
    env["MALLOC_MMAP_THRESHOLD_"] = str(settings["mmap_threshold"])
    env["MALLOC_TRIM_THRESHOLD_"] = str(settings["trim_threshold"])


def _tail(path: str, n: int = 30) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


@contextlib.contextmanager
def ps_fleet(repo: str, log_dir: str, shape: dict, env: dict):
    """Start the fleet ``shape`` asks for, point this process at it as
    worker 0 for the body, then require every child to exit 0 on its own
    (the body ends with ``bps.shutdown()``). Children's output is kept in
    ``log_dir`` and tailed to stderr on any failure. No child outlives
    the block."""
    if shape["workers"] != 1 or shape["schedulers"] != 1:
        raise ValueError(f"one worker (this process) and one scheduler: {shape}")
    os.makedirs(log_dir, exist_ok=True)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = {"DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": str(shape["servers"])}
    saved_env = dict(os.environ)
    children = []
    try:
        for role in ["scheduler"] + ["server"] * shape["servers"]:
            child_env = dict(os.environ, **base, DMLC_ROLE=role)
            child_env["PYTHONPATH"] = (repo + os.pathsep
                                       + child_env.get("PYTHONPATH", ""))
            log_path = os.path.join(log_dir, f"{role}{len(children)}.log")
            with open(log_path, "w") as log:
                children.append((role, log_path, subprocess.Popen(
                    [sys.executable, "-m", "byteps_tpu.server"],
                    env=child_env, stdout=log, stderr=subprocess.STDOUT)))
        os.environ.update(base, DMLC_ROLE="worker", DMLC_WORKER_ID="0", **env)
        yield
        for role, log_path, proc in children:
            rc = proc.wait(timeout=60)
            if rc != 0:
                raise RuntimeError(f"{role} exited {rc} after shutdown")
    except BaseException:
        for role, log_path, _ in children:
            print(f"--- {role} log tail ({log_path}) ---\n{_tail(log_path)}",
                  file=sys.stderr, flush=True)
        raise
    finally:
        for _, _, proc in children:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        os.environ.clear()
        os.environ.update(saved_env)


def pushed_bytes() -> int:
    """Payload bytes this worker has pushed to the servers so far: the C
    core's own counter."""
    from byteps_tpu.core import ffi

    return int(ffi.metrics_snapshot()["counters"]["bps_push_bytes_total"])


def round_summary() -> dict:
    from byteps_tpu.core import ffi

    return ffi.round_summary()
