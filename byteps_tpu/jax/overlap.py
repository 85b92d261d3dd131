"""Per-layer compute/communication overlap for PS-mode JAX training.

SURVEY.md §7 "hard part #1": the reference's torch plugin registers
per-parameter autograd hooks so each gradient starts its push the moment
backward produces it (byteps/torch/__init__.py _make_hook) — communication
overlaps the *rest of backward*. JAX has no hooks: gradients normally
leave ``value_and_grad`` all at once, so PS-mode pushes can only start
after the whole backward finishes.

This module recovers hook-style streaming inside the jitted program:
every parameter leaf is wrapped in a ``custom_vjp`` identity *tap* whose
backward rule fires a ``jax.experimental.io_callback``. When XLA's
backward pass materialises that parameter's gradient, the callback hands
it straight to the C++ KV worker's priority-credit push queue — while the
device continues with the remaining backward compute. After the step's
dispatch completes, the host waits on the per-tensor handles (pulls) and
applies the optimizer update.

Multi-chip controllers are first-class: the tapped loss runs under
``shard_map`` over the process-local (dcn, ici) mesh, and each tap's
backward rule reduce-scatters the gradient over ALL local mesh axes
inside jit (``lax.psum_scatter`` — the reference's NCCL intra-node
reduce-scatter stage) before any host transfer. Each chip's callback
hands the host only its 1/k shard of the locally-summed gradient, so the
host↔DCN leg carries exactly one gradient's worth of bytes per step
regardless of local chip count — the reference's two-level pipeline
(SURVEY.md §3.3) with XLA playing NCCL. Shards are declared as separate
PS tensors (one ``ps.bind`` of every leaf's shards, leaf by leaf),
preserving declaration-order priority (front-of-model first) at shard
granularity.

Priorities follow parameter declaration order (flattened tree order =
front-of-model first for standard model pytrees), so early layers' pulls
complete first — exactly the reference's scheduling rationale.

Options: ``wire_dtype`` compresses the device->host transfer inside jit
(bf16 2x / int8+scales ~4x, re-expanded to f32 before the PS push);
``backward_passes_per_step`` accumulates K backward passes host-side and
communicates once (the reference's gradient-accumulation contract).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.experimental import io_callback
from jax.sharding import PartitionSpec as P

import byteps_tpu.jax as bps
from byteps_tpu.jax import ps
from byteps_tpu.jax._compat import shard_map as _shard_map


class _TapState:
    """Declared shard tensors + in-flight handles for one step builder."""

    def __init__(self, client, prefix: str, average: bool,
                 compression_config: Optional[str], n_shards: int,
                 wire_dtype: str = "float32", wire_block: int = 256,
                 backward_passes_per_step: int = 1):
        self.client = client
        self.prefix = prefix
        self.average = average
        self.compression_config = compression_config
        self.n_shards = n_shards
        self.wire_dtype = wire_dtype
        self.wire_block = wire_block
        self.bpps = backward_passes_per_step
        self.acc: Dict[Tuple[int, int], np.ndarray] = {}
        self.acc_count: Dict[Tuple[int, int], int] = {}
        # (leaf_idx, shard_idx) -> declared tensor id / in-flight handle
        self.tids: Dict[Tuple[int, int], int] = {}
        self.shard_elems: Dict[int, int] = {}
        self.dtypes: list = []  # leaf idx -> its shards' wire dtype
        self.blocks: Dict[int, int] = {}
        self.cv = threading.Condition()
        self.inflight: Dict[Tuple[int, int], Tuple[int, np.ndarray]] = {}

    def pad_unit(self, idx: int) -> int:
        """Leaf ``idx``'s flat gradient is padded to this multiple before
        scattering (int8 wire additionally needs block-tiled shards).
        The quantization block shrinks with the leaf so a 3-element bias
        is not padded out to k*256 elements of PS traffic."""
        return self.n_shards * self.blocks[idx]

    def declare_all(self, leaves) -> None:
        k = self.n_shards
        shards = []
        for i, leaf in enumerate(leaves):
            n = int(np.size(leaf))
            if self.wire_dtype == "int8":
                self.blocks[i] = min(self.wire_block, max(1, -(-n // k)))
            else:
                self.blocks[i] = 1
            unit = self.pad_unit(i)
            padded = -(-n // unit) * unit
            self.shard_elems[i] = padded // k
            # Quantized/cast wires always land as f32 on the host (the C
            # codecs and summation operate on f32).
            dt = leaf.dtype if self.wire_dtype == "float32" else np.float32
            shards += [jax.ShapeDtypeStruct((self.shard_elems[i],), dt)] * k
        # The binding declares (shape-signed names, on the bridge thread)
        # and is asked for nothing else: the taps' callbacks hand their
        # shards to the client themselves — they run on the runtime's
        # threads, mid-program, and must not wait on the bridge.
        bound = ps.bind(self.prefix, shards,
                        compression=self.compression_config)
        for n, tid in enumerate(bound.tids):
            self.tids[divmod(n, k)] = tid
        self.dtypes = bound.wire_dtypes[::k]

    def push_shard(self, idx: int, j, g: np.ndarray,
                   scales: Optional[np.ndarray] = None) -> None:
        # io_callback may hand a read-only view; the C core sums in place,
        # so stage through a writable copy that also serves as the pull
        # destination. The span is this design's bps.ps.stage (jax/ps.py's
        # table): on a runtime callback thread, once a shard — mid-program
        # by design; on the TPU runtime of PERF.md's PR 52 entry only after
        # the program has ended. direct_bytes (of bytes, pushed from where
        # they landed) is 0 while every shard is copied.
        j = int(j)
        with jax.profiler.TraceAnnotation(ps.SPAN_TAP_PUSH, leaf=idx,
                                          shard=j) as span:
            if scales is not None:
                # int8 wire: dequantize blockwise on the host (cheap
                # vectorised numpy), push f32.
                arr = (np.asarray(g, np.float32).reshape(-1, self.blocks[idx])
                       * np.asarray(scales, np.float32).reshape(-1, 1)
                       ).reshape(-1)
            else:
                arr = np.array(g, dtype=self.dtypes[idx],
                               copy=True).reshape(-1)
            span.set_metadata(bytes=arr.nbytes, direct_bytes=0)
            if self.bpps > 1:
                # Gradient accumulation (reference: DistributedOptimizer
                # backward_passes_per_step): sum K backward passes
                # host-side, communicate once on the K-th. Division by K is
                # the caller's, exactly as in the reference. Under the lock:
                # unordered io_callbacks for the same key can run on
                # different host threads (a straggler from microbatch m
                # racing m+1), and an unguarded read-modify-write here would
                # lose a gradient or an acc_count increment.
                key = (idx, j)
                with self.cv:
                    acc = self.acc.get(key)
                    self.acc[key] = arr if acc is None else acc + arr
                    self.acc_count[key] = self.acc_count.get(key, 0) + 1
                    if self.acc_count[key] < self.bpps:
                        return
                    arr = self.acc.pop(key)
                    self.acc_count[key] = 0
            h = self.client.push_pull(self.tids[(idx, j)], arr,
                                      average=self.average)
            with self.cv:
                self.inflight[(idx, j)] = (h, arr)
                self.cv.notify_all()

    def reset_window(self) -> None:
        """Drop any partial accumulation/in-flight state. Called at the
        start of each accumulation window: if a previous step crashed
        mid-backward (device error after some taps fired), leftover
        acc/acc_count entries would silently mix microbatches from
        different windows on the next retry — bound the damage to the
        failed window instead. The effects barrier first flushes any
        still-queued io_callbacks from the crashed step, so a straggler
        cannot re-pollute the fresh window right after the clear."""
        try:
            jax.effects_barrier()
        except Exception:
            pass  # a dead backend can raise here; clearing still helps
        with self.cv:
            self.acc.clear()
            self.acc_count.clear()
            self.inflight.clear()

    def _pop(self, key: Tuple[int, int], timeout: float):
        """Wait until the tap callback for ``key`` has fired, then take
        its handle. Callbacks are unordered and run on the runtime's own
        threads, so a plain dict pop would race; waiting on the condition
        variable makes collect robust no matter when the callback runs."""
        with self.cv:
            if not self.cv.wait_for(lambda: key in self.inflight, timeout):
                raise RuntimeError(
                    f"gradient tap {key} never fired within {timeout}s "
                    "(io_callback lost or step crashed mid-backward)")
            return self.inflight.pop(key)

    def collect(self, leaves, timeout: Optional[float] = None):
        if timeout is None:
            # A big model's first step (slow compile) plus a cold fleet can
            # exceed any fixed bound — configurable, generous default.
            import os
            timeout = float(os.environ.get("BYTEPS_TAP_TIMEOUT_S", "600"))
        out = []
        # bps.ps.wait, as the binding's: the settle loop up to the last settle
        with jax.profiler.TraceAnnotation(ps.SPAN_WAIT):
            for i, leaf in enumerate(leaves):
                shards = []
                for j in range(self.n_shards):
                    h, arr = self._pop((i, j), timeout)
                    self.client.wait(h)
                    shards.append(arr)
                flat = (shards[0] if self.n_shards == 1
                        else np.concatenate(shards))
                out.append(flat[:int(np.size(leaf))].reshape(np.shape(leaf))
                           .astype(leaf.dtype))
        return out


def _make_tap(state: _TapState, idx: int, axes: Tuple[str, ...], k: int):
    @jax.custom_vjp
    def tap(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        # Fires mid-backward per device: reduce-scatter this gradient over
        # the local chips inside jit (ICI collective), then enqueue each
        # chip's 1/k shard push while the device keeps differentiating
        # earlier layers. With average=True the local level contributes the
        # local mean and the PS level averages over workers — the global
        # mean for a homogeneous fleet (same split as the non-overlapped
        # PS step in training.py).
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % state.pad_unit(idx)
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        if k > 1:
            shard = lax.psum_scatter(flat, axes, scatter_dimension=0,
                                     tiled=True)
            if state.average:
                shard = shard / k
            j = lax.axis_index(axes)
        else:
            shard = flat
            j = jnp.int32(0)
        # On-device wire compression (SURVEY.md §7 step 5): the D2H
        # transfer is the host boundary's scarce resource on real chips —
        # cast (bf16, 2x) or blockwise-quantize (int8 + per-block scales,
        # ~4x) INSIDE jit so fewer bytes cross it. The host re-expands to
        # f32 before the PS push; DCN-leg compression stays the C codec's
        # job. The quantization loss here is per-step (not error-fed).
        if state.wire_dtype == "int8":
            from byteps_tpu.parallel.hierarchical import _blockwise_quantize
            q, scales = _blockwise_quantize(shard, state.blocks[idx])
            io_callback(
                lambda jj, qq, ss: state.push_shard(idx, jj, qq, ss),
                None, j, q, scales, ordered=False)
        elif state.wire_dtype == "bfloat16":
            io_callback(lambda jj, arr: state.push_shard(idx, jj, arr),
                        None, j, shard.astype(jnp.bfloat16),
                        ordered=False)
        else:
            io_callback(lambda jj, arr: state.push_shard(idx, jj, arr),
                        None, j, shard, ordered=False)
        return (g,)

    tap.defvjp(fwd, bwd)
    return tap


def make_overlapped_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    average: bool = True,
    compression_config: Optional[str] = None,
    wire_dtype: str = "float32",
    wire_block: int = 256,
    backward_passes_per_step: int = 1,
    prefix: str = "ograd",
):
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    with hook-style push streaming (see module docstring).

    ``loss_fn(params, batch) -> scalar``. ``batch`` leaves carry this
    worker's batch on the leading axis; it is sharded over the local mesh
    axes (single-chip meshes included). ``compression_config`` is the
    C-core codec string (e.g. ``"type=onebit;ef=vanilla"``) applied per
    shard tensor on the DCN leg. ``wire_dtype`` compresses the
    device->host transfer inside jit: ``"bfloat16"`` (2x, ~1e-3 error)
    or ``"int8"`` (blockwise-quantized, ~4x, ~1e-2 error, not
    error-fed); the host re-expands to f32 before the PS push.
    ``wire_block`` caps the int8 scale-block size (it shrinks
    automatically for small leaves so padding stays proportional).
    ``backward_passes_per_step=K`` accumulates K backward passes
    host-side and communicates once on the K-th (the reference's
    gradient-accumulation contract; divide by K in your optimizer) —
    non-final calls return the params/opt_state unchanged. The
    returned loss is this worker's local loss (mean over its chips).
    """
    st = bps._st()
    client = st.ps_client
    if client is None:
        raise RuntimeError(
            "make_overlapped_train_step needs PS mode (init with "
            "DMLC_NUM_SERVER>0 / BYTEPS_PS_MODE=ps)")
    if (jax.default_backend() == "cpu"
            and jax.local_device_count() == 1):
        # Verified deadlock on this configuration: io_callback_impl
        # device_puts the tap's operands onto the single-threaded XLA:CPU
        # client while the training program occupies that same pool, so
        # materialising the gradient inside the callback waits forever
        # under load (one device == one async worker thread). Two or more
        # host devices widen the pool and the hang disappears.
        import warnings
        warnings.warn(
            "overlapped PS training on a single-device CPU backend can "
            "deadlock in XLA's callback machinery under load; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2 (or more) "
            "for CPU runs", stacklevel=2)
    if wire_dtype not in ("float32", "bfloat16", "int8"):
        raise ValueError(
            f"wire_dtype must be float32|bfloat16|int8, got {wire_dtype!r}")
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    mesh = st.mesh
    axes = tuple(mesh.axis_names)
    k = mesh.size

    state = _TapState(client, prefix, average, compression_config, k,
                      wire_dtype=wire_dtype, wire_block=wire_block,
                      backward_passes_per_step=backward_passes_per_step)
    taps: Dict[int, Callable] = {}

    def tapped_loss(params, batch):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        tapped = [taps[i](leaf) for i, leaf in enumerate(leaves)]
        return loss_fn(jax.tree_util.tree_unflatten(treedef, tapped), batch)

    @jax.jit
    @partial(_shard_map, mesh=mesh, in_specs=(P(), P(axes)),
             out_specs=P(), check_vma=False)
    def grad_device(params, batch):
        # Gradients never leave the program whole: they reach the host
        # only through the taps' reduce-scattered shards.
        loss = jax.value_and_grad(tapped_loss)(params, batch)[0]
        for ax in axes:
            loss = lax.pmean(loss, ax)
        return loss

    def apply_fn(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    # Gradient buffers are fresh per step — donating them lets XLA write
    # the updates in place instead of allocating a second tree.
    apply_jit = jax.jit(apply_fn, donate_argnums=(2,))

    micro = [0]

    def step(params, opt_state, batch):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if not taps:
            state.declare_all(leaves)
            for i in range(len(leaves)):
                taps[i] = _make_tap(state, i, axes, k)
        # Host spans for a jax.profiler capture (names and meaning: jax/ps.py's
        # table). The caller stays in bps.step.grad for the whole program and
        # every callback — the pushes run under it, on the runtime's threads
        # (bps.tap.push).
        try:
            with jax.profiler.TraceAnnotation(ps.SPAN_STEP_GRAD):
                if micro[0] % backward_passes_per_step == 0:
                    # window start: discard any state a crashed step left
                    state.reset_window()
                loss = grad_device(params, batch)
                # Pushes already overlapped the backward pass; the effects
                # barrier flushes any unordered callbacks the runtime hasn't
                # yet run.
                loss.block_until_ready()
                jax.effects_barrier()
            micro[0] += 1
            if micro[0] % backward_passes_per_step:
                # accumulation pass: gradients summed host-side, nothing
                # on the wire yet, parameters unchanged
                return params, opt_state, loss
            with ps.step_ps_span():
                host = state.collect(leaves)
                # ONE batched H2D for the whole collected tree: passing the
                # numpy leaves straight to apply_jit would transfer each
                # leaf individually at dispatch — the same per-leaf pattern
                # the ps.py bridge batches away.
                with jax.profiler.TraceAnnotation(
                        ps.SPAN_H2D, bytes=sum(a.nbytes for a in host)):
                    grads = jax.tree_util.tree_unflatten(
                        treedef, jax.device_put(host))
            with jax.profiler.TraceAnnotation(ps.SPAN_STEP_APPLY):
                params, opt_state = apply_jit(params, opt_state, grads)
            return params, opt_state, loss
        except Exception:
            # A crash mid-window (some taps fired, counter not advanced)
            # would double-count the failed pass on retry; roll back to
            # the window boundary so the next call resets cleanly.
            micro[0] -= micro[0] % backward_passes_per_step
            raise

    return step
