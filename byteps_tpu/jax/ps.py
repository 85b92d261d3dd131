"""JAX ↔ C++ parameter-server bridge (PS mode).

This is the DCN leg of the hierarchy (SURVEY.md §3.3): gradients leave the
chips ici-reduced (XLA collectives inside the jitted step), cross the host
boundary once, and the C++ core partitions / compresses / priority-schedules
/ pushes them over TCP to the CPU-summation servers, pulling the aggregate
back into the same buffers. One BytePS worker per controller process; the
reduction denominator factorises as (local chips via pmean) x (worker
hosts via PS average).

Reference analogues: byteps/torch/ops.py (push_pull on framework tensors)
and the COPYD2H → PUSH → PULL → COPYH2D pipeline stages. As there, the
pipeline is per tensor: a leaf is handed to the C core as soon as it is on
the host and handed back to the device as soon as its handle has settled, so
both host-boundary transfers run under the C core round.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import byteps_tpu.jax as bps

# --- host spans ---------------------------------------------------------------
# The PS leg's spans in a ``jax.profiler`` capture (``/host:CPU``, the
# capture's own clock; free while no capture runs). This table is the one
# place that names them: docs/timeline.md and benchmark/layers/bridge.py
# mirror it and tests/test_ps_spans.py compares the three.
SPAN_STEP_GRAD = "bps.step.grad"    # training.py: dispatch of the grad program
SPAN_STEP_PS = "bps.step.ps"        # training.py: ps_push_pull + decompress
SPAN_STEP_APPLY = "bps.step.apply"  # training.py: dispatch of the apply program
SPAN_PUSH_PULL = "bps.ps.push_pull"  # bridge thread: _ps_push_pull_impl, whole
SPAN_D2H = "bps.ps.d2h"             # every leaf's D2H issued, the first landed
SPAN_STAGE = "bps.ps.stage"         # per leaf: land, stage, enqueue into the C core
SPAN_WAIT = "bps.ps.wait"           # per leaf: settle, device_put; to the last settle
SPAN_H2D = "bps.ps.h2d"             # last device_put + reshape/astype dispatch
SPANS = (SPAN_STEP_GRAD, SPAN_STEP_PS, SPAN_STEP_APPLY, SPAN_PUSH_PULL,
         SPAN_D2H, SPAN_STAGE, SPAN_WAIT, SPAN_H2D)

# --- ordered bridge execution ----------------------------------------------
# Wire keys are (declaration-order id << 16 | partition) — worker.cc's
# Declare assigns ids by LOCAL declaration order, so every worker must
# declare tensors in the same order or the servers sum unrelated tensors
# under one key. A single FIFO bridge thread gives that order a single
# authority: every host-boundary PS op (sync or async) executes on it in
# submission order, and submissions happen in the caller's program order.
_pool = None
_pool_lock = threading.Lock()
_POOL_PREFIX = "bps_bridge"


def _ensure_pool():
    global _pool
    with _pool_lock:
        if _pool is None:
            import concurrent.futures
            _pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=_POOL_PREFIX)
        return _pool


def _on_pool_thread() -> bool:
    return threading.current_thread().name.startswith(_POOL_PREFIX)


def _run_ordered(fn, *args, **kwargs):
    """Execute fn on the bridge thread and wait. Re-entrant: a call that is
    already ON the bridge thread (an async op's PS leg) runs inline — a
    submit-and-wait there would deadlock the single-worker FIFO."""
    if _on_pool_thread():
        return fn(*args, **kwargs)
    return _ensure_pool().submit(fn, *args, **kwargs).result()


def submit_ordered(fn, *args, **kwargs):
    """Queue fn on the bridge thread and return the Future (the async
    handle path). Caller must not already be on the bridge thread."""
    assert not _on_pool_thread(), "async submit from the bridge thread"
    return _ensure_pool().submit(fn, *args, **kwargs)


def drain_bridge() -> None:
    """Settle every queued bridge op and retire the pool (shutdown path:
    the C++ client must not be torn down under an in-flight async op)."""
    global _pool
    with _pool_lock:
        p, _pool = _pool, None
    if p is not None:
        p.shutdown(wait=True)

# (prefix, n_leaves) -> list of tensor ids. Declares are per-tensor-
# lifetime, not per-step: each declare is a ctypes call into the C core's
# locked registry (and, on first sight, a blocking INIT_KEY round trip to
# every owning server) — pure per-step overhead once the tree shape is
# fixed. Cleared by bps.init()/shutdown() via reset_declare_cache().
_tid_cache: dict = {}
# Steps that declared at least one NEW tensor (test hook: after warm-up
# this must stop growing — one registration per tensor lifetime).
declare_steps: int = 0
# The newest ps_push_pull's hand-back (test hook, and the stats of its
# ``bps.ps.h2d`` span): ``put_early_bytes`` had their device_put issued
# before the last handle settled — under the round — of ``bytes`` in all.
put_stats: dict = {"put_early_bytes": 0, "bytes": 0}
# The newest ps_push_pull's staging (test hook, and the stats of its
# ``bps.ps.stage`` span): ``reused_bytes`` of the ``bytes`` staged went into
# buffers an earlier call had left in the pool, the rest into new ones.
stage_stats: dict = {"reused_bytes": 0, "bytes": 0}


class _Slot:
    """The staging buffer of one declared tensor: ps_push_pull copies the
    landed leaf into it, the C core pushes from it and pulls into it in
    place, and ``jax.device_put`` uploads from it — every step the same
    memory, so its pages are mapped and faulted in once per tensor lifetime
    and not once per step (a 154 MB leaf is above glibc's mmap ceiling: a
    fresh copy of it is mmap, ≈ 37,700 page faults and munmap; PERF.md,
    PR 25). A tid has one element count and one wire dtype for its lifetime
    (the C core refuses a re-declare), so the buffer never changes size.

    ``result`` is a WEAK reference to the array the caller got back for the
    leaf staged here last: ``device_put`` returns before the bytes have
    left the host, and the runtime reads ``buf`` until they have, so the
    next write into ``buf`` first waits for that array if anyone still holds
    it (ready implies uploaded). Weak, because a strong one would keep a
    whole tree of device memory alive into the caller's next program; the
    price is that a result the caller has already dropped is not waited
    for — its bytes can then be read only by device work queued on it, and
    a caller whose next tree depends on that work (the train step: the
    next gradients come from the parameters these uploads updated) has
    waited for it by having the next tree on the host at all."""

    __slots__ = ("buf", "result")

    def __init__(self, size: int, dtype):
        self.buf = np.empty(size, dtype)
        self.result = None

    def fill(self, host: np.ndarray) -> np.ndarray:
        """Copy ``host`` in (one pass; a half-precision leaf under a codec
        is upcast to the float32 wire by the same pass) and return the
        buffer in ``host``'s shape."""
        prev = self.result() if self.result is not None else None
        if prev is not None and not prev.is_deleted():
            prev.block_until_ready()
        arr = self.buf.reshape(host.shape)
        np.copyto(arr, host, casting="safe")
        return arr


# tensor id -> _Slot: one per tensor ps_push_pull has declared and staged,
# dropped with the tid cache (a restarted fleet never sees a stale slot).
_slots: dict = {}


def reset_declare_cache() -> None:
    _tid_cache.clear()
    _slots.clear()


def _is_host_memory_of(dev, arr: np.ndarray) -> bool:
    """``dev`` — what ``jax.device_put`` made of ``arr`` — IS ``arr``'s
    memory. The CPU backend does that for a buffer that happens to be
    64-byte aligned (jax 0.9.0: about every other ``np.empty``, small or
    4 MB) and copies otherwise; an accelerator's memory is never the
    host's."""
    if all(d.platform != "cpu" for d in dev.devices()):
        return False
    return dev.unsafe_buffer_pointer() == arr.ctypes.data


def _writable(arr: np.ndarray) -> np.ndarray:
    """A buffer the C core may push FROM and pull INTO in place, for the
    callers that stage a fresh one per call (``ps_broadcast``, the async and
    the bucketed step; ``ps_push_pull`` stages into its pool, ``_Slot``).
    A ``jax.Array`` hands back a read-only host array — on the CPU backend
    a zero-copy view of the jax buffer, on the TPU the array's cached host
    copy (196 of 196 leaves of a GPT-2 tree; PERF.md, PR 24) — and
    writing through one would mutate the (immutable) source array, so
    un-alias exactly when the runtime says the buffer isn't ours: a new
    allocation and a copy of every such leaf, every call."""
    arr = np.ascontiguousarray(arr)
    if not arr.flags.writeable:
        arr = np.array(arr)
    return arr


def _as_arrays(leaves):
    """Normalise pytree leaves: Python scalars (ints/floats in opt state
    trees) become 0-d numpy arrays so size/dtype/shape queries work."""
    return [l if hasattr(l, "dtype") and hasattr(l, "size")
            else np.asarray(l) for l in leaves]


def _wait_all(client, staged):
    """Settle EVERY staged handle before surfacing a failure. client.wait
    raises on the first failed handle; bailing out of the loop there would
    free the numpy staging buffers of the not-yet-waited handles while
    live-server partitions are still in flight — the C core's pull
    callbacks would then memcpy into freed memory (the same use-after-free
    the Wait/Poll settle semantics in worker.cc prevent one layer down).
    Collect errors, wait everything, then re-raise the first."""
    first_err = None
    for h, _, _ in staged:
        try:
            client.wait(h)
        except Exception as e:  # noqa: BLE001 — must settle all handles
            if first_err is None:
                first_err = e
    if first_err is not None:
        raise first_err


def _codec_active(st) -> bool:
    """A fleet-default codec (BYTEPS_COMPRESSOR) is configured. Mirrors
    the C core's rule: ANY non-empty config makes declares codec-bearing
    (and the codecs are float32-domain — worker.cc guards the declare)."""
    import os
    return bool(getattr(st.config, "compressor", "")
                or os.environ.get("BYTEPS_COMPRESSOR", ""))


def _wire_plan(leaves, codec: bool):
    """Per-leaf (declare dtype, compression override) so half-precision
    wire and lossy codecs compose instead of fail-stopping:

    - float32 + codec: inherit the default codec (None).
    - bfloat16/float16 + codec: declare FLOAT32 and upcast the staged
      host buffer — the in-jit half cast still halves the dominant
      device<->host boundary both ways; the C codec (e.g. onebit, 32x)
      takes the DCN leg from there.
    - non-float leaves (int step counters in optimizer trees): declare
      with compression="" — quantising integers is meaningless and the
      core would reject them.
    """
    plan = []
    for leaf in leaves:
        name = np.dtype(leaf.dtype).name
        if not codec:
            plan.append((name, None))
        elif name == "float32":
            plan.append((name, None))
        elif name in ("bfloat16", "float16"):
            plan.append(("float32", None))
        else:
            plan.append((name, ""))
    return plan


def _tids(client, prefix: str, leaves, plan):
    global declare_steps
    # Shape/dtype signature in the key: a same-named tree with different
    # leaf sizes must re-declare (the C core rejects size changes).
    sig = tuple((int(l.size), str(l.dtype)) for l in leaves)
    key = (prefix, sig, tuple(p[0] for p in plan))
    tids = _tid_cache.get(key)
    if tids is None:
        declare_steps += 1
        # The shape signature goes INTO the wire name: two different-shaped
        # trees under the same prefix (e.g. two unnamed push_pull call
        # sites) must land on distinct server tensors — re-declaring a
        # name with a new size is a deliberate fatal in the C core. The
        # digest is content-derived, so it is identical on every worker
        # (python's hash() is salted per process and would NOT be).
        import zlib
        shape_key = zlib.crc32(repr(key).encode())
        tids = [
            client.declare(f"{prefix}_{shape_key:08x}_{i}", int(leaf.size),
                           wire_dtype, compression=comp)
            for i, (leaf, (wire_dtype, comp)) in enumerate(zip(leaves,
                                                               plan))
        ]
        _tid_cache[key] = tids
    return tids


def ps_push_pull(tree, average: bool = True, prefix: str = "grad",
                 async_mode: Optional[bool] = None):
    """Sum (or average) a pytree across worker hosts via the CPU PS fleet.

    Host-level call (use on the outputs of a jitted step). All leaves are
    enqueued before any wait, so partitions from every tensor pipeline
    through the priority-scheduled push queue together — large trees
    overlap compression, network, and summation across partitions exactly
    like the reference's per-partition scheduling.

    Host-boundary discipline (reference: shared_memory.cc + ps-lite
    zero-copy SArray, SURVEY.md §7 hard part #2): a per-leaf pipeline.
    Every leaf's D2H transfer is started up front and each leaf is enqueued
    the moment IT has landed, so the C core round begins with the first
    leaf and not after the last. What is copied where: the landed leaf (the
    runtime's read-only host copy) is copied ONCE, into the staging buffer
    its tensor id owns across calls (``_Slot``; allocated on the tensor's
    first call, warm pages from then on); the C core pushes from that
    buffer and pulls the sum back into it in place, and ``device_put``
    uploads from it — no other host copy, no per-call allocation. Each
    leaf's H2D transfer is issued the moment its handle has settled, so
    only the last leaf's upload is left after the round. Tensor declares
    are cached for the tree's lifetime instead of re-registering every
    step. Executes on the FIFO bridge thread so declares keep a
    fleet-consistent order against async ops.
    """
    return _run_ordered(_ps_push_pull_impl, tree, average, prefix,
                        async_mode)


def _ps_push_pull_impl(tree, average, prefix, async_mode):
    st = bps._st()
    client = st.ps_client
    if client is None:
        raise RuntimeError(
            "PS mode is not active (init with BYTEPS_PS_MODE=ps / "
            "DMLC_NUM_SERVER>0)")
    if async_mode is None:
        async_mode = st.config.enable_async
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = _as_arrays(leaves)
    nbytes = [l.size * l.dtype.itemsize for l in leaves]
    total = sum(nbytes)
    # mono_ns is CLOCK_MONOTONIC, the C core's NowUs() clock, read at the
    # span's start: (mono_ns - the event's ts) maps the core's stamps onto
    # the capture's clock (utils/timeline.py, docs/timeline.md).
    with jax.profiler.TraceAnnotation(
            SPAN_PUSH_PULL, mono_ns=time.monotonic_ns(), leaves=len(leaves),
            bytes=total):
        plan = _wire_plan(leaves, _codec_active(st))
        tids = _tids(client, prefix, leaves, plan)
        # (handle, staged buffer, leaf) per enqueued leaf, and how many of
        # the handles have settled. A leaf's staged buffer (its tid's
        # _Slot, in the leaf's shape) is push source, pull destination and
        # device_put source; the C core owns it until its handle settles.
        staged, settled, devs = [], 0, []
        wire_nbytes = [l.size * np.dtype(w).itemsize
                       for l, (w, _) in zip(leaves, plan)]

        def put(i):
            _, arr, leaf = staged[i]
            if arr.dtype == leaf.dtype:
                dev = jax.device_put(arr)
                if _is_host_memory_of(dev, arr):
                    # The result IS the slot's buffer: the buffer goes with
                    # it and the tensor's next call allocates another.
                    del _slots[tids[i]]
            else:
                # Downcast an upcast-staged leaf on the host first so the
                # upload pays half-precision bytes too (the device-side
                # astype is then a no-op).
                dev = jax.device_put(arr.astype(leaf.dtype))
            devs.append(dev)

        try:
            with jax.profiler.TraceAnnotation(SPAN_D2H):
                # Start every transfer now (what jax.device_get does before
                # it blocks; a numpy leaf has nothing to start), so that
                # leaf 0's runs first. A buffer that is ready is copied when
                # asked; the copies of a program still running start at its
                # end, the one asked for LAST first (measured on the TPU
                # runtime: asked in declaration order, leaf 0 lands last,
                # 51 ms after the program's end instead of 2-6; PERF.md,
                # PR 25) — so ask for those in reverse.
                device = [l for l in leaves
                          if hasattr(l, "copy_to_host_async")]
                if device and not device[0].is_ready():
                    device.reverse()
                for leaf in device:
                    leaf.copy_to_host_async()
                np.asarray(leaves[0])
            stage_stats.update(
                reused_bytes=sum(n for tid, n in zip(tids, wire_nbytes)
                                 if tid in _slots),
                bytes=sum(wire_nbytes))
            with jax.profiler.TraceAnnotation(SPAN_STAGE, **stage_stats):
                for tid, leaf, (wire_dtype, _) in zip(tids, leaves, plan):
                    # blocks only until THIS leaf has landed
                    host = np.asarray(leaf)
                    slot = _slots.get(tid)
                    if slot is None:
                        slot = _slots[tid] = _Slot(host.size, wire_dtype)
                    arr = slot.fill(host)
                    h = client.push_pull(tid, arr, average=average,
                                         async_mode=async_mode)
                    staged.append((h, arr, leaf))
            # Handles settle roughly in declaration order (that is their
            # priority), so each leaf goes back to the device while the
            # round still works on the ones behind it — the last leaf at
            # least, when the rest settled while it was being staged — and
            # only the last leaf's device_put waits for the whole round.
            with jax.profiler.TraceAnnotation(SPAN_WAIT):
                for i, (h, _, _) in enumerate(staged):
                    settled += 1  # wait settles h whether it returns or raises
                    client.wait(h)
                    if i < len(staged) - 1:  # the last put is bps.ps.h2d's
                        put(i)
            put_stats.update(put_early_bytes=total - nbytes[-1], bytes=total)
            with jax.profiler.TraceAnnotation(SPAN_H2D, **put_stats):
                put(len(staged) - 1)
                out = [d.reshape(leaf.shape).astype(leaf.dtype)
                       for d, leaf in zip(devs, leaves)]
            for tid, result in zip(tids, out):
                if tid in _slots:
                    _slots[tid].result = weakref.ref(result)
        except BaseException:
            # What _wait_all guarantees, from wherever the failure came:
            # no staging buffer is freed under the C core, and nothing more
            # goes to the device. The first error is the one raised.
            with contextlib.suppress(Exception):
                _wait_all(client, staged[settled:])
            raise
    return jax.tree_util.tree_unflatten(treedef, out)


def ps_broadcast(tree, root_rank: int = 0, prefix: str = "param"):
    """Init-time weight sync across worker hosts through the servers
    (reference: broadcast_parameters, SURVEY.md §3.4). Bridge-thread
    ordered like ps_push_pull."""
    return _run_ordered(_ps_broadcast_impl, tree, root_rank, prefix)


def _ps_broadcast_impl(tree, root_rank, prefix):
    st = bps._st()
    client = st.ps_client
    if client is None:
        raise RuntimeError("PS mode is not active")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = _as_arrays(leaves)
    plan = _wire_plan(leaves, _codec_active(st))
    tids = _tids(client, prefix, leaves, plan)
    host = jax.device_get(leaves)
    staged = []
    for tid, arr, leaf, (wire_dtype, _) in zip(tids, host, leaves, plan):
        arr = _writable(arr)
        if arr.dtype != np.dtype(wire_dtype):
            arr = arr.astype(wire_dtype)
        h = client.broadcast(tid, arr, root_rank=root_rank)
        staged.append((h, arr, leaf))
    _wait_all(client, staged)
    devs = jax.device_put(
        [arr if arr.dtype == getattr(leaf, "dtype", arr.dtype)
         else arr.astype(leaf.dtype)
         for _, arr, leaf in staged])  # one batched H2D
    out = [d.reshape(leaf.shape).astype(leaf.dtype)
           for d, (_, _, leaf) in zip(devs, staged)]
    return jax.tree_util.tree_unflatten(treedef, out)


def ps_barrier() -> None:
    """Fleet-wide worker barrier through the scheduler."""
    st = bps._st()
    if st.ps_client is None:
        raise RuntimeError("PS mode is not active")
    st.ps_client.barrier()
