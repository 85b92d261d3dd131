"""Build the byteps_tpu C++ core into libbyteps_core.so.

Run as ``python -m byteps_tpu.core.build`` (reference analogue: the
setup.py c_lib extension build, SURVEY.md §2.6). No external deps — plain
g++; OpenMP is enabled when available (the PS summation hot loop,
cpu_reducer.cc, parallelises across the server's spare cores).
"""

from __future__ import annotations

import fcntl
import hashlib
import json
import os
import platform
import shutil
import subprocess
import sys

CORE_DIR = os.path.dirname(os.path.abspath(__file__))
CSRC = os.path.join(CORE_DIR, "csrc")
LIB_PATH = os.path.join(CORE_DIR, "libbyteps_core.so")

SOURCES = [
    "debug.cc",
    "crc32c.cc",
    "trace.cc",
    "tenancy.cc",
    "roundstats.cc",
    "events.cc",
    "van.cc",
    "postoffice.cc",
    "cpu_reducer.cc",
    "compressor.cc",
    "ckpt.cc",
    "server.cc",
    "worker.cc",
    "c_api.cc",
]

_BASE_FLAGS = ["-std=c++17", "-fPIC", "-shared", "-pthread", "-Wall"]
_SANITIZE_FLAGS = ["-O1", "-g", "-fno-omit-frame-pointer"]
# -march=native: cpu_reducer.cc's summation loops vectorise to the widest
# SIMD the host has (and its fp16 path needs F16C+AVX), crc32c.cc takes
# the SSE4.2 instruction. The stamp's CPU-feature digest keeps a library
# built for another machine from being reused here.
_OPTIONAL_FLAGS = ["-march=native", "-fopenmp"]


def _supports_flag(cxx: str, flag: str) -> bool:
    probe = subprocess.run(
        [cxx, flag, "-x", "c++", "-", "-fsyntax-only"],
        input="int main(){return 0;}", text=True,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    return probe.returncode == 0


def _sha256(*chunks: bytes) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(c)
    return h.hexdigest()


def _read(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _cpu_features() -> bytes:
    """What ``-march=native`` resolves against: the CPU's feature flags.
    A library built on another machine (a copied working tree) must not
    be handed back — its SIMD may be illegal here."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    return line
    except OSError:
        pass
    return platform.processor().encode()


def _inputs_digest(cxx: str, sanitize: str) -> str:
    """Everything the library's bytes depend on: sources, headers, the
    requested flags, the compiler binary and this CPU's features."""
    names = sorted(SOURCES + [h for h in os.listdir(CSRC)
                              if h.endswith(".h")])
    cxx_path = shutil.which(cxx) or cxx
    try:
        st = os.stat(cxx_path)
        cxx_id = f"{cxx_path}:{st.st_size}:{st.st_mtime_ns}"
    except OSError:
        cxx_id = cxx_path
    chunks = [repr((_BASE_FLAGS, _SANITIZE_FLAGS, _OPTIONAL_FLAGS, sanitize,
                    cxx_id)).encode(), _cpu_features()]
    for n in names:
        chunks += [n.encode(), _read(os.path.join(CSRC, n))]
    return _sha256(*chunks)


def _is_current(lib_path: str, inputs: str) -> bool:
    """True iff the stamp beside ``lib_path`` proves it was built here
    from the current inputs AND the library's bytes are the ones the
    stamp was written for (a foreign .so dropped over ours fails this)."""
    try:
        with open(lib_path + ".stamp") as f:
            stamp = json.load(f)
        return (stamp.get("inputs") == inputs
                and stamp.get("lib") == _sha256(_read(lib_path)))
    except (OSError, ValueError):
        return False


def build(force: bool = False, verbose: bool = True,
          sanitize: str = "") -> str:
    """Compile unless a stamp proves the library current. Returns its path.

    The library is reused only when ``<lib>.stamp`` matches a digest of
    the sources, headers, flags, compiler and CPU features, and the
    library's own bytes. Builds are serialised by a lock file and land by
    ``os.replace``, so roles that start together on a clean tree compile
    once and never load a half-written library.

    ``sanitize``: "address" or "thread" builds an instrumented variant
    (libbyteps_core.asan.so / .tsan.so). The reference relies on CHECK
    macros alone (SURVEY.md §5 "no TSAN/ASAN CI"); these builds are how
    byteps_tpu races/UAFs get caught — an exit-order use-after-free in the
    shutdown path was found exactly this way. Run with:

        BPS_CORE_LIB=.../libbyteps_core.asan.so \
        LD_PRELOAD=$(g++ -print-file-name=libasan.so) python ...
    """
    lib_path = LIB_PATH
    if sanitize:
        if sanitize not in ("address", "thread"):
            raise ValueError(f"sanitize must be address|thread: {sanitize!r}")
        suffix = {"address": ".asan.so", "thread": ".tsan.so"}[sanitize]
        lib_path = LIB_PATH[:-3] + suffix
    cxx = os.environ.get("CXX", "g++")
    inputs = _inputs_digest(cxx, sanitize)
    if not force and _is_current(lib_path, inputs):
        return lib_path

    with open(lib_path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        # Another role may have finished the same build while we waited.
        if not force and _is_current(lib_path, inputs):
            return lib_path
        if sanitize:
            flags = _BASE_FLAGS + _SANITIZE_FLAGS + [f"-fsanitize={sanitize}"]
        else:
            flags = _BASE_FLAGS + ["-O3"] + [
                f for f in _OPTIONAL_FLAGS if _supports_flag(cxx, f)]
        srcs = [os.path.join(CSRC, s) for s in SOURCES]
        tmp = lib_path + ".tmp"
        # -lrt: shm_open/shm_unlink (the shm van transport) live in librt
        # on glibc < 2.34; on newer glibc the library is an empty stub, so
        # linking it unconditionally is safe and keeps dlopen from failing
        # with "undefined symbol: shm_open" on older hosts.
        cmd = [cxx, *flags, *srcs, "-o", tmp, "-lrt"]
        if verbose:
            print("[byteps_tpu.core.build]", " ".join(cmd))
        try:
            subprocess.run(cmd, check=True)
            digest = _sha256(_read(tmp))
            os.replace(tmp, lib_path)
            with open(tmp, "w") as f:
                json.dump({"inputs": inputs, "lib": digest}, f)
            os.replace(tmp, lib_path + ".stamp")
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    return lib_path


if __name__ == "__main__":
    san = ""
    if "--asan" in sys.argv:
        san = "address"
    elif "--tsan" in sys.argv:
        san = "thread"
    print(build(force="--force" in sys.argv, sanitize=san))
