"""byteps_tpu.jax — the JAX framework plugin (the adapter boundary).

Capability parity with the reference's framework plugins (SURVEY.md §2.5,
byteps/torch/__init__.py + ops.py): ``init``, ``rank/size/local_rank/
local_size``, ``push_pull`` (+ ``_async``/``poll``/``synchronize``),
``declare_tensor``, ``DistributedOptimizer``, ``broadcast_parameters``.

TPU-first semantics:

- ``push_pull`` is *per-device* code when called inside ``jax.shard_map``
  (the hot path — the reduction is part of the step program: on a mesh with
  both levels reduce-scatter over ici → dcn level → all-gather over one
  fused buffer, on a single level one all-reduce per leaf), and auto-wraps
  itself in a jitted shard_map when called on stacked per-replica arrays
  outside jit.
- Async handles map onto JAX's asynchronous dispatch: ``push_pull_async``
  returns immediately with arrays whose computation is in flight;
  ``synchronize`` blocks on them (reference: HandleManager + poll/
  synchronize, byteps/torch/handle_manager.cc — on TPU the runtime already
  gives us the async handle table for free).
- ``DistributedOptimizer`` is an optax gradient-transformation wrapper: the
  idiomatic JAX counterpart of wrapping ``optimizer.step()``.
"""

from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from byteps_tpu.config import Config, get_config
from byteps_tpu.jax.compression import Compression, Compressor
from byteps_tpu.parallel import hierarchical as _h
from byteps_tpu.parallel.mesh import build_mesh, set_global_mesh
from byteps_tpu.partition import TensorRegistry

from byteps_tpu.jax._compat import axis_size as _axis_size
from byteps_tpu.jax._compat import shard_map as _shard_map

__all__ = [
    "init", "shutdown", "initialized", "rank", "size", "device_count",
    "local_rank", "local_size", "push_pull", "push_pull_async", "poll", "synchronize",
    "declare_tensor", "broadcast_parameters", "broadcast_optimizer_state",
    "DistributedOptimizer", "Compression", "mesh",
]


@dataclasses.dataclass
class _State:
    config: Config
    mesh: Mesh
    registry: TensorRegistry
    ps_client: Any = None  # C++ KV client (PS mode), wired in core.ffi


_state: Optional[_State] = None
_lock = threading.Lock()


def init(mesh: Optional[Mesh] = None, config: Optional[Config] = None) -> None:
    """Initialise byteps_tpu (reference: bps.init() → byteps_init,
    SURVEY.md §3.2). Builds/installs the (dcn, ici) device mesh, the tensor
    registry, and — in PS mode — the C++ KV client connection to the
    scheduler."""
    global _state
    # Drain BEFORE touching any global state (and before the C core is
    # re-initialised below): a stale async op from a previous session must
    # fully settle against the OLD client, not straddle the re-init.
    from byteps_tpu.jax import ps as _ps_drain
    _ps_drain.drain_bridge()
    with _lock:
        cfg = config or get_config(reload=True)
        if mesh is None:
            mesh = build_mesh(dcn_axis=cfg.dcn_axis, ici_axis=cfg.ici_axis)
        set_global_mesh(mesh)
        registry = TensorRegistry(cfg.partition_bytes,
                                  max(1, cfg.num_server))
        ps_client = None
        if cfg.use_ps:
            try:
                from byteps_tpu.core import ffi as _ffi
            except ImportError as e:
                raise RuntimeError(
                    "PS mode requested (BYTEPS_PS_MODE=ps / DMLC_NUM_SERVER>0"
                    " / BYTEPS_FORCE_DISTRIBUTED=1) but the byteps_tpu C++ "
                    "core is not built. Build it with "
                    "`python -m byteps_tpu.core.build`, or set "
                    "BYTEPS_PS_MODE=collective to use pure XLA collectives."
                ) from e
            ps_client = _ffi.Worker.start(cfg)
        from byteps_tpu.jax import ps as _ps
        _ps.reset_declare_cache()
        _global_run_cache.clear()
        _state = _State(cfg, mesh, registry, ps_client)


def shutdown() -> None:
    """Tear down (reference: byteps_shutdown)."""
    global _state
    from byteps_tpu.jax import ps as _ps
    # Settle in-flight async bridge ops BEFORE taking the lock or touching
    # the C++ client: a pending push_pull_async still holds staged host
    # buffers the core pulls into, and must complete against a live fleet.
    _ps.drain_bridge()
    with _lock:
        if _state is not None and _state.ps_client is not None:
            _state.ps_client.shutdown()
        _ps.reset_declare_cache()
        _global_run_cache.clear()
        _state = None


def initialized() -> bool:
    return _state is not None


def _st() -> _State:
    if _state is None:
        raise RuntimeError("byteps_tpu.jax.init() has not been called")
    return _state


def mesh() -> Mesh:
    return _st().mesh


# --- topology queries (reference: BytePSBasics, byteps/common/__init__.py) --
#
# Horovod-contract note: in the reference, one process drives one GPU, so
# rank/size are simultaneously the process index and the chip index. Under
# single-controller JAX one process drives all its local chips, so the two
# notions split. We keep the Horovod invariant rank() ∈ [0, size()) at the
# *process* level — the level at which users shard input data — and expose
# the chip count separately as device_count() (the gradient-averaging
# denominator, applied internally by push_pull).

def rank() -> int:
    """Index of this controller process in [0, size()).

    PS mode: the fleet-wide worker rank (DMLC_WORKER_ID order) — each
    launcher-spawned worker is its own JAX process, so
    ``jax.process_index()`` would be 0 everywhere and data sharding by
    rank would silently train identical shards. Collective /
    multi-controller mode: ``jax.process_index()``.
    """
    st = _st()
    if st.ps_client is not None:
        return st.ps_client.worker_rank()
    return jax.process_index()


def size() -> int:
    """Number of controller processes (use with rank() for data sharding).

    PS mode: the fleet's worker count; otherwise ``jax.process_count()``
    (see rank()).
    """
    st = _st()
    if st.ps_client is not None:
        return st.ps_client.num_workers()
    return jax.process_count()


def device_count() -> int:
    """Total participating chips — the reduction denominator."""
    return _st().mesh.size


def local_rank() -> int:
    """This process's index among processes on the same host."""
    return _st().config.local_rank


def local_size() -> int:
    """Number of chips driven by this process."""
    _st()
    return jax.local_device_count()


# --- push_pull -------------------------------------------------------------

def _axes():
    st = _st()
    names = st.mesh.axis_names
    ici = st.config.ici_axis if st.config.ici_axis in names else None
    dcn = st.config.dcn_axis if st.config.dcn_axis in names else None
    return ici, dcn


# In-jit push_pull always reduces via XLA collectives over the mesh axes.
# In PS mode the mesh is process-local (one BytePS worker per controller
# process), so those collectives cover exactly the local chips; the
# cross-host DCN level runs at the host boundary through the C++ KV client
# (byteps_tpu.jax.ps.ps_push_pull / _make_ps_train_step).


def _inside_spmd(axis: Optional[str]) -> bool:
    if axis is None:
        return False
    try:
        _axis_size(axis)
        return True
    except Exception:  # unbound axis name outside shard_map
        return False


def push_pull(tree, average: bool = True, name: Optional[str] = None,
              compression: Compressor = Compression.none):
    """Sum (or average) a pytree of gradients across all chips.

    Inside ``shard_map`` this is the hot path, one XLA program with the
    step: on a two-level mesh the hierarchical all-reduce (SURVEY.md §3.3's
    REDUCE→PUSH/PULL→BROADCAST pipeline: scatter over ici, the dcn level,
    gather), on a single level one all-reduce per leaf in the leaf's own
    shape. Outside, arrays must carry a leading replica axis of
    length ``device_count()`` — this process's mesh size — (stacked
    per-chip values) and the same collective runs under a jitted shard_map;
    in PS mode the result then crosses the host boundary once more through
    the C++ KV client, so the reduction is global across worker processes
    (Horovod semantics), not just across this host's chips. ``name`` keys
    the PS registry for that leg; unnamed calls share a shape-keyed name
    and must be issued in the same order on every worker.
    """
    ici, dcn = _axes()
    if _inside_spmd(ici) or _inside_spmd(dcn):
        return _per_device_push_pull(tree, average, compression)
    return _global_push_pull(tree, average, compression, name)


def _per_device_push_pull(tree, average, compression):
    ici, dcn = _axes()
    if compression.name in ("int8_quant", "int8_quant_dcn"):
        # quantization replaces the transport itself (all-to-all of int8
        # chunks + scales), not a pre-cast; see hierarchical.py
        return _h.tree_quantized_all_reduce(
            tree, ici_axis=ici, dcn_axis=dcn, average=average,
            quantize_dcn=compression.name == "int8_quant_dcn")
    orig_dtypes = jax.tree_util.tree_map(lambda x: x.dtype, tree)
    tree = jax.tree_util.tree_map(compression.compress, tree)
    red = _h.tree_all_reduce(
        tree, ici_axis=ici, dcn_axis=dcn, average=average)
    return jax.tree_util.tree_map(
        lambda x, d: compression.decompress(x, d), red, orig_dtypes)


# (mesh, mesh_axes, average, compression) -> jitted host-level reducer.
# Without this cache every host-level push_pull would build a FRESH
# closure, and jax.jit's cache (keyed on function identity) would retrace
# and recompile per call — seconds per step for a per-step API. Cleared by
# init()/shutdown() (a new mesh keys differently anyway).
_global_run_cache: dict = {}


def _global_run(mesh, mesh_axes, average, compression):
    key = (mesh, mesh_axes, average, compression)
    run = _global_run_cache.get(key)
    if run is None:
        @partial(jax.jit)
        @partial(_shard_map, mesh=mesh, in_specs=P(mesh_axes),
                 out_specs=P(), check_vma=False)
        def run(stacked):
            local = jax.tree_util.tree_map(lambda x: x[0], stacked)
            return _per_device_push_pull(local, average, compression)

        _global_run_cache[key] = run
    return run


def _global_push_pull(tree, average, compression, name=None):
    st = _st()
    n = st.mesh.size
    ici, dcn = _axes()
    mesh_axes = tuple(a for a in (dcn, ici) if a)

    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return tree
    for leaf in leaves:
        if leaf.ndim == 0 or leaf.shape[0] != n:
            raise ValueError(
                "push_pull outside shard_map expects arrays stacked over a "
                "leading replica axis of length device_count()="
                f"{n} (this process's mesh size); got shape "
                f"{leaf.shape}. Inside a shard_map'd step, call push_pull "
                "on the per-device gradients directly.")

    out = _global_run(st.mesh, mesh_axes, average, compression)(tree)
    if st.ps_client is not None:
        # Cross-worker DCN leg: the in-jit collective covered only this
        # process's chips (the mesh is process-local in PS mode), so a
        # host-level push_pull must still cross the PS fleet to keep
        # Horovod-global semantics. The denominator factorises: local
        # pmean over n chips, then PS average over equal workers.
        from byteps_tpu.jax import ps as _ps
        out = _ps.ps_push_pull(out, average=average,
                               prefix=name or "push_pull")
    return out


# --- async handle surface (reference: handle_manager.cc + ops.py) ----------

@dataclasses.dataclass
class Handle:
    """An in-flight push_pull. In collective mode JAX's async dispatch IS
    the handle table (``value`` holds not-yet-ready arrays); in PS mode
    ``value`` is a Future for the host-side DCN round trip running on the
    bridge thread."""

    value: Any


def push_pull_async(tree, average: bool = True, name: Optional[str] = None,
                    compression: Compressor = Compression.none) -> Handle:
    """Non-blocking push_pull (reference: push_pull_async + handle table).

    Collective mode: XLA's async dispatch means the jitted collective is
    already in flight when this returns. PS mode: the host-level DCN leg
    (device_get → C++ push/pull → device_put) runs on the ordered bridge
    thread (byteps_tpu.jax.ps) so this call returns immediately, the
    fleet round trip overlaps with the caller's other host work, and
    declares stay in fleet-consistent order against synchronous calls;
    ``synchronize`` joins it.
    """
    st = _st()
    ici, dcn = _axes()
    inside = _inside_spmd(ici) or _inside_spmd(dcn)
    if st.ps_client is not None and not inside:
        from byteps_tpu.jax import ps as _ps
        fut = _ps.submit_ordered(
            _global_push_pull, tree, average, compression, name)
        return Handle(fut)
    return Handle(push_pull(tree, average=average, name=name,
                            compression=compression))


def _is_future(v) -> bool:
    return hasattr(v, "done") and hasattr(v, "result")


def poll(handle: Handle) -> bool:
    """True iff the result is materialised (reference: byteps_torch_poll)."""
    value = handle.value
    if _is_future(value):
        if not value.done():
            return False
        # The bridge op ends with a non-blocking device_put; "done" means
        # the fleet round trip finished, not that the H2D transfers have
        # landed — hold poll() to the same is_ready bar as the
        # collective branch.
        value = value.result()
    leaves = jax.tree_util.tree_leaves(value)
    return all(l.is_ready() for l in leaves if hasattr(l, "is_ready"))


def synchronize(handle: Handle):
    """Block until the result is ready and return it."""
    if _is_future(handle.value):
        return jax.block_until_ready(handle.value.result())
    return jax.block_until_ready(handle.value)


# --- declare / broadcast ----------------------------------------------------

def declare_tensor(name: str, shape, dtype) -> None:
    """Pre-register a tensor (reference: byteps_declare_tensor). Establishes
    declaration-order priority and the partition/key table used by the PS
    path and the trace timeline."""
    _st().registry.declare(name, tuple(shape), jnp.dtype(dtype).name)


def broadcast_parameters(tree, root_rank: int = 0,
                         name: Optional[str] = None):
    """Replicate ``tree`` from ``root_rank``'s copy to all chips (reference:
    broadcast_parameters, SURVEY.md §3.4).

    Inside shard_map: a masked-psum broadcast over both axes. Outside, with
    single-controller JAX, this host's chips are already logically
    replicated, so locally it devolves to installing a fully-replicated
    sharding; in PS mode the tree additionally round-trips through the
    servers so every worker process ends up holding ``root_rank``'s values
    (the reference's init-time weight sync, SURVEY.md §3.4). ``name`` keys
    the PS registry for that leg — distinct same-shaped trees broadcast
    from different call sites should pass distinct names (unnamed calls
    share a shape-keyed name and must be issued in the same order on
    every worker).
    """
    ici, dcn = _axes()
    if _inside_spmd(ici) or _inside_spmd(dcn):
        return _h.tree_broadcast(tree, root=root_rank,
                                 ici_axis=ici, dcn_axis=dcn)
    st = _st()
    if st.ps_client is not None:
        from byteps_tpu.jax import ps as _ps
        tree = _ps.ps_broadcast(tree, root_rank=root_rank,
                                prefix=name or "param")
    repl = jax.sharding.NamedSharding(st.mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, repl), tree)


def broadcast_optimizer_state(opt_state, root_rank: int = 0,
                              name: str = "opt_state"):
    """Replicate optimizer state from ``root_rank`` (reference:
    broadcast_optimizer_state). optax states are pytrees of arrays;
    non-array leaves (python scalars, schedule callables) pass through
    untouched. All array leaves go through ONE broadcast_parameters call
    (one batched host round trip in PS mode, not one per leaf); pass a
    distinct ``name`` when broadcasting several optimizer states."""
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    arr_idx = [i for i, l in enumerate(leaves) if hasattr(l, "dtype")]
    if arr_idx:
        synced = broadcast_parameters([leaves[i] for i in arr_idx],
                                      root_rank=root_rank, name=name)
        for i, v in zip(arr_idx, synced):
            leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- DistributedOptimizer ---------------------------------------------------

def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    average: bool = True,
    compression: Compressor = Compression.none,
    backward_passes_per_step: int = 1,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates are push_pull'd before applying.

    Reference: byteps/torch DistributedOptimizer (SURVEY.md §2.5) — which
    hooks autograd to overlap communication with backward compute. In JAX
    the overlap is XLA's job: call ``update`` inside your shard_map'd jitted
    train step and the reduction (scatter / dcn level / gather on two mesh
    levels, one all-reduce per leaf on one) is scheduled by the compiler
    alongside remaining compute.

    ``backward_passes_per_step`` > 1 reproduces the reference's gradient
    accumulation contract: grads are accumulated locally that many times and
    communicated once (use with ``optax.MultiSteps`` or lax.scan'd
    microbatching; the division by the accumulation count is the caller's,
    exactly as in the reference).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(updates, state, params=None, **extra):
        updates = push_pull(updates, average=average, compression=compression)
        return optimizer.update(updates, state, params, **extra)

    return optax.GradientTransformation(init_fn, update_fn)
