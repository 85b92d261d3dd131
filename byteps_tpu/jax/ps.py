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
both host-boundary transfers run under the C core round. A push has a source
and a destination: the core sends a landed leaf from where the runtime put it
and pulls the sum into the buffer the tensor owns (``_Slot``), so a leaf that
is already in its wire form is never copied on the host.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from typing import Optional

import jax
import numpy as np

import byteps_tpu.jax as bps

# --- host spans ---------------------------------------------------------------
# The PS leg's spans in a ``jax.profiler`` capture (``/host:CPU``, the
# capture's own clock; free while no capture runs). This table is the one
# place that names them: docs/timeline.md and benchmark/layers/bridge.py
# mirror it and tests/test_ps_spans.py compares the three. The three
# ``bps.step.*`` are written on the caller's thread by both PS step builders
# (training.py's serial step, bucketed.py), with one meaning; the async step
# and ``ps_push_pull`` called alone write only the binding's ``bps.ps.*``:
SPAN_STEP_GRAD = "bps.step.grad"    # the gradient program(s): dispatch
SPAN_STEP_PS = "bps.step.ps"        # the leg as the caller sees it, to the
#                                     summed tree on its way back; stat mono_ns
SPAN_STEP_APPLY = "bps.step.apply"  # dispatch of the apply program
SPAN_PUSH_PULL = "bps.ps.push_pull"  # bridge thread: _ps_push_pull_impl, whole
SPAN_D2H = "bps.ps.d2h"             # every leaf's D2H issued, the first landed
SPAN_STAGE = "bps.ps.stage"         # per leaf: land, enqueue into the C core
SPAN_WAIT = "bps.ps.wait"           # per leaf: settle, device_put; to the last settle
SPAN_H2D = "bps.ps.h2d"             # last device_put + reshape/astype dispatch
SPANS = (SPAN_STEP_GRAD, SPAN_STEP_PS, SPAN_STEP_APPLY, SPAN_PUSH_PULL,
         SPAN_D2H, SPAN_STAGE, SPAN_WAIT, SPAN_H2D)


def step_ps_span():
    """``bps.step.ps`` as both PS step builders open it, on the caller's
    thread: stat ``mono_ns`` is CLOCK_MONOTONIC, the C core's ``NowUs()``
    clock, read at the span's start, so (``mono_ns`` - the event's ts) maps
    the core's stamps onto the capture's clock (utils/timeline.py,
    docs/timeline.md) in the bucketed step too, which writes no
    ``bps.ps.push_pull``."""
    return jax.profiler.TraceAnnotation(SPAN_STEP_PS,
                                        mono_ns=time.monotonic_ns())

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

# (prefix, shape signature, wire dtypes) -> the tree's WireTree. Declares
# are per-tensor-lifetime, not per-step: each declare is a ctypes call
# into the C core's locked registry (and, on first sight, a blocking
# INIT_KEY round trip to every owning server) — pure per-step overhead once
# the tree shape is fixed. Cleared by bps.init()/shutdown() via
# reset_declare_cache().
_tid_cache: dict = {}
# Steps that declared at least one NEW tensor (test hook: after warm-up
# this must stop growing — one registration per tensor lifetime).
declare_steps: int = 0
# The newest ps_push_pull's hand-back (test hook, and the stats of its
# ``bps.ps.h2d`` span): ``put_early_bytes`` had their device_put issued
# before the last handle settled — under the round — of ``bytes`` in all.
put_stats: dict = {"put_early_bytes": 0, "bytes": 0}
# The newest ps_push_pull's staging (test hook, and the stats of its
# ``bps.ps.stage`` span): of the ``bytes`` enqueued, ``direct_bytes`` are
# pushed from the landed array itself, with no copy on the host (the rest
# were copied into their slot first), and ``reused_bytes`` have a slot an
# earlier call had left in the pool, the rest a new one.
stage_stats: dict = {"direct_bytes": 0, "reused_bytes": 0, "bytes": 0}


class _Slot:
    """The host buffer of one declared tensor: the C core pulls the sum
    into it and ``jax.device_put`` uploads from it — and a leaf that cannot
    be the wire's source as it landed (``_is_wire_source``) is copied into
    it first and pushed from it in place. Every step the same memory, so
    its pages are mapped and faulted in once per tensor lifetime and not
    once per step (a 154 MB leaf is above glibc's mmap ceiling: a fresh
    buffer of it is mmap, ≈ 37,700 page faults and munmap; PERF.md,
    PR 25). A tid has one element count and one wire dtype for its lifetime
    (the C core refuses a re-declare), so the buffer never changes size.

    ``result`` is a WEAK reference to the array the caller got back for the
    leaf that came back through here last: ``device_put`` returns before
    the bytes have left the host, and the runtime reads ``buf`` until they
    have, so before ``buf`` is written again — by a copy, or by the core,
    which writes it from the first pull on — ``claim`` waits for that array
    if anyone still holds it (ready implies uploaded). Weak, because a
    strong one would keep a whole tree of device memory alive into the
    caller's next program; the price is that a result the caller has
    already dropped is not waited for — its bytes can then be read only by
    device work queued on it, and a caller whose next tree depends on that
    work (the train step: the next gradients come from the parameters these
    uploads updated) has waited for it by having the next tree on the host
    at all."""

    __slots__ = ("buf", "result")

    def __init__(self, size: int, dtype):
        self.buf = np.empty(size, dtype)
        self.result = None

    def claim(self, shape) -> np.ndarray:
        """The buffer in ``shape``, free to be written: the last upload
        from it has left the host."""
        prev = self.result() if self.result is not None else None
        if prev is not None and not prev.is_deleted():
            prev.block_until_ready()
        return self.buf.reshape(shape)


# tensor id -> _Slot: one per tensor ps_push_pull has declared and pushed,
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


def _is_wire_source(host: np.ndarray, wire_dtype: str) -> bool:
    """The C core can send the landed array ``host`` as it stands: it is in
    the tensor's wire dtype (not a half-precision leaf that a codec's
    float32 wire upcasts) and C-contiguous with at least one axis. Asked of
    the array that landed, not of the leaf: the TPU runtime lands a device
    array in the layout the compiler gave it on the device, which for a
    matrix may be column-major (PERF.md, PR 49). Such an array is only
    ever read — it may be the runtime's read-only host copy, or on the
    CPU backend the device buffer itself."""
    return (host.ndim > 0 and host.dtype == np.dtype(wire_dtype)
            and host.flags.c_contiguous)


def _writable(arr: np.ndarray) -> np.ndarray:
    """A buffer the C core may push FROM and pull INTO in place, for
    ``ps_broadcast``, which stages a fresh one per call (a ``WireTree``
    pulls into the pool, ``_Slot``, and copies only what it must).
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
    Collect errors, wait everything, then re-raise the first. The same
    holds for a push's source, which the core reads again on a resend."""
    first_err = None
    for h, *_ in staged:
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


def _wire_plan(leaves, codec: bool, config: Optional[str] = None):
    """Per-leaf (declare dtype, compression override) so half-precision
    wire and lossy codecs compose instead of fail-stopping. ``config`` is
    a caller's own codec string for this tree (``bind``'s ``compression``)
    and stands where the fleet default would be inherited (None):

    - float32 + codec: inherit the default codec (None).
    - bfloat16/float16 + codec: declare FLOAT32 and upcast into the
      tensor's host buffer — the in-jit half cast still halves the dominant
      device<->host boundary both ways; the C codec (e.g. onebit, 32x)
      takes the DCN leg from there.
    - non-float leaves (int step counters in optimizer trees): declare
      with compression="" — quantising integers is meaningless and the
      core would reject them.
    """
    plan = []
    for leaf in leaves:
        name = np.dtype(leaf.dtype).name
        if not codec or name == "float32":
            plan.append((name, config))
        elif name in ("bfloat16", "float16"):
            plan.append(("float32", config))
        else:
            plan.append((name, ""))
    return plan


class WireTree:
    """A tree bound to the wire (``bind``): the tensor ids its leaves were
    declared under (``tids``, tree order — declaration order is the PS
    priority) and the dtype each crosses in (``wire_dtypes``).

    Leaves move through it a whole tree at once (``push_pull``) or in
    pieces — ``push`` some leaves now, others later, then one ``finish`` —
    for a caller whose leaves become ready program by program. Either way,
    on the bridge thread, a leaf is enqueued as it lands — pushed from the
    landed array itself where that can be the wire's source
    (``_is_wire_source``), else copied into the buffer its tensor id owns
    (``_Slot``) and pushed from there; pulled into that buffer always — and
    goes back to the device from the buffer as its handle settles. An error
    leaves only after every handle in flight has settled. One round at a
    time: ``finish`` or an error ends it."""

    def __init__(self, tids, plan):
        self.tids = tids
        self.wire_dtypes = [wire_dtype for wire_dtype, _ in plan]
        # leaf index -> (handle, buffer, leaf, source) of what is in flight.
        # The buffer (the tid's _Slot, in the leaf's shape) is pull
        # destination and device_put source; the source is what the core
        # sends, and resends: the leaf's landed array, or the buffer. The C
        # core owns both until the handle settles, so neither is let go
        # before.
        self._staged: dict = {}

    def push_pull(self, leaves, average: bool = True,
                  async_mode: Optional[bool] = None):
        """``ps_push_pull`` of the tree's leaves to THESE tensors."""
        return _run_ordered(_ps_push_pull_impl, list(leaves), average, self,
                            async_mode)

    def push(self, indices, leaves, average: bool = True,
             async_mode: Optional[bool] = None) -> None:
        """Hand over ``leaves``, the tree's leaves ``indices``: start their
        D2H, enqueue each as it lands."""
        _run_ordered(self._push, indices, _as_arrays(leaves), average,
                     async_mode)

    def finish(self):
        """Wait for every leaf pushed since the last round, in tree order,
        put each back as it settles; the summed leaves in tree order."""
        return _run_ordered(self._finish)

    def _settle(self):
        # What _wait_all guarantees, from wherever the failure came; the
        # caller raises its own, the first, error.
        with contextlib.suppress(Exception):
            _wait_all(bps._st().ps_client, self._staged.values())
        self._staged.clear()

    def _push(self, indices, leaves, average, async_mode):
        st = bps._st()
        client = st.ps_client
        if async_mode is None:
            async_mode = st.config.enable_async
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
            wire_nbytes = [l.size * np.dtype(self.wire_dtypes[i]).itemsize
                           for i, l in zip(indices, leaves)]
            if not self._staged:  # a round's later pieces add to its first
                stage_stats.update(direct_bytes=0, reused_bytes=0, bytes=0)
            stage_stats["reused_bytes"] += sum(
                n for i, n in zip(indices, wire_nbytes)
                if self.tids[i] in _slots)
            stage_stats["bytes"] += sum(wire_nbytes)
            # direct_bytes is counted as the leaves land and joins the
            # span's stats at its end
            with jax.profiler.TraceAnnotation(
                    SPAN_STAGE, reused_bytes=stage_stats["reused_bytes"],
                    bytes=stage_stats["bytes"]) as span:
                for i, leaf, nbytes in zip(indices, leaves, wire_nbytes):
                    tid = self.tids[i]
                    # blocks only until THIS leaf has landed
                    host = np.asarray(leaf)
                    slot = _slots.get(tid)
                    if slot is None:
                        slot = _slots[tid] = _Slot(host.size,
                                                   self.wire_dtypes[i])
                    # from here the slot is the core's to write
                    arr = slot.claim(host.shape)
                    if _is_wire_source(host, self.wire_dtypes[i]):
                        stage_stats["direct_bytes"] += nbytes
                    else:
                        # one pass; a half-precision leaf under a codec is
                        # upcast to the float32 wire by the same pass
                        np.copyto(arr, host, casting="safe")
                        host = arr
                    h = client.push_pull(tid, host, average=average,
                                         async_mode=async_mode, out=arr)
                    self._staged[i] = (h, arr, leaf, host)
                span.set_metadata(direct_bytes=stage_stats["direct_bytes"])
        except BaseException:
            self._settle()
            raise

    def _put(self, i, arr, leaf):
        if arr.dtype == leaf.dtype:
            dev = jax.device_put(arr)
            if _is_host_memory_of(dev, arr):
                # The result IS the slot's buffer: the buffer goes with
                # it and the tensor's next call allocates another.
                del _slots[self.tids[i]]
            return dev
        # Downcast an upcast-staged leaf on the host first so the upload
        # pays half-precision bytes too (the device's astype is a no-op).
        return jax.device_put(arr.astype(leaf.dtype))

    def _finish(self):
        client = bps._st().ps_client
        last = len(self.tids) - 1
        try:
            # KeyError: finish() before that leaf was pushed
            leaves = [self._staged[i][2] for i in range(last + 1)]
            nbytes = [l.size * l.dtype.itemsize for l in leaves]
            devs = []
            # Handles settle roughly in declaration order (that is their
            # priority), so each leaf goes back to the device while the
            # round still works on the ones behind it — the last leaf at
            # least, when the rest settled while it was being staged — and
            # only the last leaf's device_put waits for the whole round.
            with jax.profiler.TraceAnnotation(SPAN_WAIT):
                for i in range(last + 1):
                    # wait settles h whether it returns or raises; this
                    # frame holds the popped source until it has
                    h, arr, leaf, _source = self._staged.pop(i)
                    client.wait(h)
                    if i < last:  # the last put is bps.ps.h2d's
                        devs.append(self._put(i, arr, leaf))
            put_stats.update(put_early_bytes=sum(nbytes[:-1]),
                             bytes=sum(nbytes))
            with jax.profiler.TraceAnnotation(SPAN_H2D, **put_stats):
                devs.append(self._put(last, arr, leaf))
                out = [d.reshape(leaf.shape).astype(leaf.dtype)
                       for d, leaf in zip(devs, leaves)]
            for tid, result in zip(self.tids, out):
                if tid in _slots:
                    _slots[tid].result = weakref.ref(result)
        except BaseException:
            self._settle()
            raise
        return out


def bind(prefix: str, leaves, compression: Optional[str] = None) -> WireTree:
    """Bind a tree to the wire: declare ``leaves`` (arrays, or anything
    with their ``size`` and ``dtype``, in tree order) under ``prefix`` on
    the bridge thread, once — a tree of the same prefix, leaf sizes and
    dtypes gets the binding an earlier call made. ``compression`` is a
    C-core codec string for this tree's float leaves in place of the fleet
    default (``BYTEPS_COMPRESSOR``)."""
    return _run_ordered(_bind, prefix, _as_arrays(leaves), compression)


def _client():
    client = bps._st().ps_client
    if client is None:
        raise RuntimeError(
            "PS mode is not active (init with BYTEPS_PS_MODE=ps / "
            "DMLC_NUM_SERVER>0)")
    return client


def _bind(prefix, leaves, compression=None):
    global declare_steps
    client = _client()
    plan = _wire_plan(leaves, _codec_active(bps._st()) if compression is None
                      else bool(compression), compression)
    # Shape/dtype signature in the key: a same-named tree with different
    # leaf sizes must re-declare (the C core rejects size changes).
    sig = tuple((int(l.size), str(l.dtype)) for l in leaves)
    key = (prefix, sig, tuple(p[0] for p in plan))
    if compression is not None:
        key += (compression,)
    bound = _tid_cache.get(key)
    if bound is None:
        declare_steps += 1
        # The shape signature goes INTO the wire name: two different-shaped
        # trees under the same prefix (e.g. two unnamed push_pull call
        # sites) must land on distinct server tensors — re-declaring a
        # name with a new size is a deliberate fatal in the C core. The
        # digest is content-derived, so it is identical on every worker
        # (python's hash() is salted per process and would NOT be).
        import zlib
        shape_key = zlib.crc32(repr(key).encode())
        bound = _tid_cache[key] = WireTree([
            client.declare(f"{prefix}_{shape_key:08x}_{i}", int(leaf.size),
                           wire_dtype, compression=comp)
            for i, (leaf, (wire_dtype, comp)) in enumerate(zip(leaves,
                                                               plan))
        ], plan)
    return bound


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
    leaf and not after the last. What is copied where: nothing, for a leaf
    that lands in its wire form — the C core sends the landed leaf (the
    runtime's read-only host copy) from where it is, pulls the sum into the
    buffer its tensor id owns across calls (``_Slot``; allocated on the
    tensor's first call, warm pages from then on), and ``device_put``
    uploads from that buffer. A leaf that has to change on the way (a
    half-precision leaf under a codec, a host scalar, a strided view) is
    copied ONCE, into that buffer, and pushed from it in place — no other
    host copy, no per-call allocation. Each leaf's H2D transfer is issued
    the moment its handle has settled, so
    only the last leaf's upload is left after the round. Tensor declares
    are cached for the tree's lifetime instead of re-registering every
    step (the tree's ``WireTree``, looked up by prefix and shape
    signature). Executes on the FIFO bridge thread so declares keep a
    fleet-consistent order against async ops.
    """
    return _run_ordered(_ps_push_pull_impl, tree, average, prefix,
                        async_mode)


def _ps_push_pull_impl(tree, average, where, async_mode):
    """``where``: the prefix to look the binding up by, or the binding."""
    _client()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = _as_arrays(leaves)
    # mono_ns is CLOCK_MONOTONIC, the C core's NowUs() clock, read at the
    # span's start: (mono_ns - the event's ts) maps the core's stamps onto
    # the capture's clock (utils/timeline.py, docs/timeline.md).
    with jax.profiler.TraceAnnotation(
            SPAN_PUSH_PULL, mono_ns=time.monotonic_ns(), leaves=len(leaves),
            bytes=sum(l.size * l.dtype.itemsize for l in leaves)):
        bound = (where if isinstance(where, WireTree)
                 else _bind(where, leaves))
        bound._push(range(len(leaves)), leaves, average, async_mode)
        out = bound._finish()
    return jax.tree_util.tree_unflatten(treedef, out)


def ps_broadcast(tree, root_rank: int = 0, prefix: str = "param"):
    """Init-time weight sync across worker hosts through the servers
    (reference: broadcast_parameters, SURVEY.md §3.4). Bridge-thread
    ordered like ps_push_pull."""
    return _run_ordered(_ps_broadcast_impl, tree, root_rank, prefix)


def _ps_broadcast_impl(tree, root_rank, prefix):
    client = _client()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    leaves = _as_arrays(leaves)
    bound = _bind(prefix, leaves)
    host = jax.device_get(leaves)
    staged = []
    for tid, arr, leaf, wire_dtype in zip(bound.tids, host, leaves,
                                          bound.wire_dtypes):
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
    _client().barrier()
