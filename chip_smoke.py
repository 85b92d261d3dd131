"""Does the training path still start — and give the right numbers — on the chip?

One command, ``python chip_smoke.py``, on a machine with one TPU chip. It
drives the framework through its user entry points only (``bps.init()`` →
``make_train_step`` / ``make_bucketed_overlap_step`` → ``step`` →
``bps.shutdown()``, and ``python -m byteps_tpu.server`` for the fleet) at the
full width of GPT-2 124M (12 layers, d 768, 12 heads, vocab 50257, bf16,
seq 512, batch 8 per chip, adamw 1e-4, tokens and weights from ``--seed``).

Phases of the default run, one JSON line each:

  0 device     JAX must have found a TPU; versions; C core rebuilt from csrc/
  1 collective make_train_step vs a plain jax.jit step, 5 steps
  2 ps         scheduler + server children, this process the worker, 3 steps
  3 overlap    the bucketed multi-program PS step, 3 steps
  4 flash      Pallas kernel vs float32 attention; 2 steps with attn "flash"
  5 profile    jax.profiler capture of 2 steps, read back, TPU plane required

``--chips 4`` runs ONLY phase 6 (multichip): the 1x4 (dcn, ici) mesh in
collective and PS mode against the plain single-device step.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase makes it ``"ok": false`` and the exit code non-zero. This is a smoke
run: its timings say the path works, they are not benchmark results.

Only this process touches JAX (a chip belongs to one process); the fleet
children never import it. Children's logs, the profile and the phase lines
go under ``--out`` (default chiprun_out/chip_smoke).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import glob
import json
import math
import os
import re
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# |loss - reference loss| per step. The step under test and the reference
# are different XLA programs over bf16 matmuls (split grad/apply programs,
# another reduction order across chips), so agreement is to bf16 rounding
# of a loss near ln(vocab) ~ 10.8, not bitwise.
LOSS_TOL = 2e-2
# The flash kernel computes attention blockwise in another order than the
# XLA softmax the reference model uses: a wider band for whole-model loss.
FLASH_LOSS_TOL = 5e-2
# Kernel vs float32 attention, max-abs error over max(1, max|reference|):
# bf16 inputs and outputs (2^-8 relative), the bound tests/test_flash_
# attention.py uses for bf16.
FLASH_ERR_BOUND = 5e-2
# The whole run must end inside 1200 s. Past this, dump every thread's
# stack and exit non-zero: a hang becomes a failure that says where.
WATCHDOG_S = 1150


@dataclasses.dataclass(frozen=True)
class Size:
    """What one run is sized by. ``FULL`` is the only size main() uses;
    tests steer a tiny one from outside (tests/test_chip_smoke.py)."""

    model_kw: dict                 # overrides of GPT2Small's fields
    seq: int
    batch_per_chip: int
    flash_shapes: tuple            # (batch, seq, heads, head_dim), causal
    interpret: bool                # Pallas interpret mode (CPU tests only)
    device_plane: str              # substring naming the profiler's device plane


FULL = Size(model_kw={}, seq=512, batch_per_chip=8,
            flash_shapes=((8, 512, 12, 64), (1, 4096, 8, 128)),
            interpret=False, device_plane="/device:TPU")


def emit(record: dict) -> dict:
    print(json.dumps(record), flush=True)
    return record


# --------------------------------------------------------------------------
# The problem: GPT-2 124M, one fixed batch, adamw — shared by every phase.

@dataclasses.dataclass
class Problem:
    loss_fn: object
    flash_loss_fn: object
    tx: object
    params: object                 # host (numpy) copy; phases re-upload
    n_params: int
    tokens: object                 # numpy [global_batch, seq] int32


def make_problem(size: Size, seed: int, n_chips: int) -> Problem:
    import jax
    import numpy as np
    import optax

    from byteps_tpu.models import GPT2Small, lm_loss

    model = GPT2Small(**size.model_kw)
    flash_model = GPT2Small(**size.model_kw, attn_impl="flash")
    tokens = np.random.default_rng(seed).integers(
        0, model.vocab_size, (size.batch_per_chip * n_chips, size.seq),
        dtype=np.int32)
    params = jax.device_get(
        jax.jit(model.init)(jax.random.PRNGKey(seed), tokens[:1]))
    n_params = sum(int(np.size(l)) for l in jax.tree_util.tree_leaves(params))
    return Problem(
        loss_fn=lambda p, b: lm_loss(model.apply(p, b), b),
        flash_loss_fn=lambda p, b: lm_loss(flash_model.apply(p, b), b),
        tx=optax.adamw(1e-4), params=params, n_params=n_params,
        tokens=tokens)


def plain_step(loss_fn, tx, micro_batches: int = 1):
    """The reference: value_and_grad -> adamw -> apply under one jax.jit,
    nothing of byteps_tpu in it. ``micro_batches`` > 1 takes the same
    full-batch mean gradient as a scan over equal row chunks, so the
    four-chip global batch fits one chip's memory."""
    import jax
    import jax.numpy as jnp
    import optax

    @jax.jit
    def step(params, opt_state, batch):
        if micro_batches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            chunks = batch.reshape(micro_batches, -1, *batch.shape[1:])

            def body(acc, chunk):
                l, g = jax.value_and_grad(loss_fn)(params, chunk)
                return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree_util.tree_map(jnp.zeros_like, params))
            (loss, grads), _ = jax.lax.scan(body, zero, chunks)
            loss, grads = jax.tree_util.tree_map(
                lambda x: x / micro_batches, (loss, grads))
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


def place(prob: Problem):
    """Fresh (params, opt_state, batch) on bps's mesh — every leaf placed
    through the library's own helpers, so the step sees on its first call
    the shardings it hands back and compiles once."""
    from byteps_tpu.jax.training import replicate, shard_batch

    return (replicate(prob.params), replicate(prob.tx.init(prob.params)),
            shard_batch(prob.tokens))


def run_steps(step, params, opt_state, batch, n: int):
    """n steps on one fixed batch. Returns (losses, seconds per call);
    call 0 includes tracing and compilation."""
    import jax

    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        secs.append(round(time.perf_counter() - t0, 4))
        losses.append(float(loss))
    return losses, secs


def check_losses(name: str, losses, ref, tol: float) -> float:
    """All finite and within ``tol`` of the reference; returns the largest
    difference."""
    if not all(math.isfinite(l) for l in losses):
        raise RuntimeError(f"{name}: non-finite loss in {losses}")
    diff = max(abs(a - b) for a, b in zip(losses, ref))
    if diff > tol:
        raise RuntimeError(
            f"{name}: losses {losses} differ from the reference "
            f"{list(ref[:len(losses)])} by {diff:.3g} > {tol}")
    return diff


def timing(secs) -> dict:
    return {"compile_and_first_step_s": secs[0], "step_s": secs[1:]}


# --------------------------------------------------------------------------
# The PS fleet: one scheduler and one server, as children that never
# import JAX (byteps_tpu/server/__init__.py) — so this process stays the
# only one that can hold the chip.

def _tail(path: str, n: int = 30) -> str:
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


@contextlib.contextmanager
def ps_fleet(log_dir: str):
    """Start scheduler + server, point this process at them as worker 0
    for the body, then require both children to exit 0 on their own (the
    body ends with ``bps.shutdown()``). Their output is kept in
    ``log_dir`` and tailed to stderr on any failure."""
    os.makedirs(log_dir, exist_ok=True)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = {"DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1"}
    saved_env = dict(os.environ)
    children = []
    try:
        for role in ("scheduler", "server"):
            env = dict(os.environ, **base, DMLC_ROLE=role)
            env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
            log_path = os.path.join(log_dir, f"{role}.log")
            with open(log_path, "w") as log:
                children.append((role, log_path, subprocess.Popen(
                    [sys.executable, "-m", "byteps_tpu.server"], env=env,
                    stdout=log, stderr=subprocess.STDOUT)))
        os.environ.update(base, DMLC_ROLE="worker", DMLC_WORKER_ID="0",
                          BYTEPS_PS_MODE="ps", BYTEPS_FORCE_DISTRIBUTED="1")
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
    """Payload bytes this worker has pushed to the servers so far (the C
    core's own counter)."""
    from byteps_tpu.core import ffi
    return int(ffi.metrics_snapshot()["counters"]["bps_push_bytes_total"])


def check_pushed(name: str, per_step: float, n_params: int) -> float:
    """The DCN leg carried one float32 gradient tree per step — not
    zeros, and not one tree per local chip."""
    ratio = per_step / (4 * n_params)
    if abs(ratio - 1.0) > 0.02:
        raise RuntimeError(
            f"{name}: pushed {per_step:.0f} B/step = {ratio:.3f}x the "
            f"gradient tree ({4 * n_params} B); expected 1x within 2%")
    return round(ratio, 4)


# --------------------------------------------------------------------------
# Phases. Each returns its record; any failure raises.

def phase_device() -> dict:
    """Phase 0: refuse anything but a TPU, say what is installed, place
    the compile cache, rebuild the C core from csrc/ on this machine."""
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU, but JAX found {found} (an unattached "
            "machine silently falls back to the CPU)")

    import importlib.metadata as md

    import jaxlib

    from byteps_tpu.core.build import build
    from byteps_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_entries = len(os.listdir(cache_dir)) if os.path.isdir(
        cache_dir) else 0
    t0 = time.perf_counter()
    # force: a library that came along in a copy is never what runs
    lib = build(force=True, verbose=False)
    build_s = round(time.perf_counter() - t0, 2)
    return emit({
        "phase": "device", "ok": True, "device": found,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": md.version("libtpu"),
        "memory_stats_keys": sorted(devs[0].memory_stats() or {}),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries_at_start": cache_entries,
        "compile_cache_max_size": jax.config.jax_compilation_cache_max_size,
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("JAX_", "XLA_", "TPU_", "LIBTPU"))},
        "core_lib": os.path.relpath(lib, REPO), "core_build_s": build_s,
    })


def phase_collective(prob: Problem, steps: int = 5) -> dict:
    """Phase 1: the collective-mode step against the plain reference.
    Returns the record; ``record["ref_losses"]`` feeds the later phases."""
    import jax

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    params = jax.device_put(prob.params)
    ref_losses, ref_secs = run_steps(
        plain_step(prob.loss_fn, prob.tx), params, prob.tx.init(params),
        jax.device_put(prob.tokens), steps)
    del params

    bps.init()
    try:
        step = make_train_step(prob.loss_fn, prob.tx)
        params, opt_state, batch = place(prob)
        losses, secs = run_steps(step, params, opt_state, batch, steps)
        del params, opt_state
    finally:
        bps.shutdown()
    diff = check_losses("collective", losses, ref_losses, LOSS_TOL)
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"collective: loss did not fall: {losses}")
    return emit({
        "phase": "collective", "ok": True, "n_params": prob.n_params,
        "losses": losses, "ref_losses": ref_losses,
        "max_loss_diff": diff, "tol": LOSS_TOL,
        **timing(secs), "ref": timing(ref_secs),
    })


def _ps_steps(name: str, make_step, prob: Problem, ref_losses, steps: int):
    """Inside a PS session: build, run, check losses and pushed bytes."""
    step = make_step()
    params, opt_state, batch = place(prob)
    before = pushed_bytes()
    losses, secs = run_steps(step, params, opt_state, batch, steps)
    per_step = (pushed_bytes() - before) / steps
    return {
        "losses": losses,
        "max_loss_diff": check_losses(name, losses, ref_losses, LOSS_TOL),
        "pushed_bytes_per_step": per_step,
        "pushed_over_grad_tree": check_pushed(name, per_step, prob.n_params),
        **timing(secs),
    }


def host_boundary_microbench(nbytes: int):
    """D2H / H2D GB/s for one contiguous f32 transfer of ``nbytes``
    (callers pass the model's gradient size). Returns (d2h, h2d, bytes
    actually moved)."""
    import jax
    import numpy as np
    n = nbytes // 4
    nbytes = n * 4  # what the probe actually moves; returned for the record
    reps = 2
    # One device array per repetition: a jax.Array keeps its host copy
    # after the first device_get, so a second get of the same array
    # would time a cache hit.
    make = jax.jit(lambda k: jax.random.normal(k, (n,)))
    devs = [make(jax.random.PRNGKey(i)) for i in range(reps)]
    jax.block_until_ready(devs)
    t0 = time.perf_counter()
    for dev in devs:
        host = jax.device_get(dev)
    d2h = nbytes * reps / (time.perf_counter() - t0)
    host = np.ascontiguousarray(host)
    t0 = time.perf_counter()
    for _ in range(reps):
        back = jax.device_put(host)
        jax.block_until_ready(back)
    h2d = nbytes * reps / (time.perf_counter() - t0)
    return d2h / 1e9, h2d / 1e9, nbytes


def phase_ps(prob: Problem, ref_losses, ref_step_s, out_dir: str,
             steps: int = 3) -> dict:
    """Phase 2: PS mode. With one worker the servers' average is the
    identity, so the losses must be phase 1's."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    d2h, h2d, nbytes = host_boundary_microbench(4 * prob.n_params)
    rec = {"phase": "ps", "ok": True, "d2h_gbps": round(d2h, 3),
           "h2d_gbps": round(h2d, 3), "boundary_probe_bytes": nbytes}
    with ps_fleet(os.path.join(out_dir, "ps")):
        bps.init()
        try:
            cfg = bps._st().config
            rec.update(heartbeat_interval_s=cfg.heartbeat_interval_s,
                       heartbeat_timeout_s=cfg.heartbeat_timeout_s)
            rec.update(_ps_steps(
                "ps", lambda: make_train_step(prob.loss_fn, prob.tx),
                prob, ref_losses, steps))
        finally:
            bps.shutdown()
    rec["collective_step_s"] = ref_step_s
    return emit(rec)


def phase_overlap(prob: Problem, ref_losses, out_dir: str,
                  steps: int = 3) -> dict:
    """Phase 3: the bucketed PS step on a fresh fleet; the losses must be
    phase 1's."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.bucketed import make_bucketed_overlap_step

    rec = {"phase": "overlap", "ok": True}
    with ps_fleet(os.path.join(out_dir, "overlap")):
        bps.init()
        try:
            rec["bucketed"] = _ps_steps(
                "overlap/bucketed",
                lambda: make_bucketed_overlap_step(
                    prob.loss_fn, prob.tx, prefix="bkt"),
                prob, ref_losses, steps)
        finally:
            bps.shutdown()
    return emit(rec)


def _reference_attention(q, k, v):
    """Causal softmax attention in float32 jax.numpy (true-f32 matmuls:
    a TPU's default f32 matmul precision is one bf16 pass)."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi)
    s = s / math.sqrt(q.shape[-1])
    keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=hi)


def _flash_kernel_check(shape, seed: int, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.ops.flash_attention import flash_attention
    from byteps_tpu.parallel.ring_attention import _single_device_attention

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[:3])
    w = jax.random.normal(keys[3], shape, jnp.float32)  # cotangent

    def scalar(attn):
        return lambda q, k, v: (attn(q, k, v).astype(jnp.float32) * w).sum()

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=interpret)

    t0 = time.perf_counter()
    out = jax.jit(flash)(q, k, v)
    grads = jax.jit(jax.grad(scalar(flash), argnums=(0, 1, 2)))(q, k, v)
    jax.block_until_ready((out, grads))
    secs = round(time.perf_counter() - t0, 3)
    ref_out = jax.jit(_reference_attention)(q, k, v)
    ref_grads = jax.jit(jax.grad(scalar(_reference_attention),
                                 argnums=(0, 1, 2)))(q, k, v)

    # The XLA form by name: on the chip full_attention may be the kernel
    # itself, and a kernel compared with itself agrees.
    def xla_form(q, k, v):
        return _single_device_attention(
            q, k, v, causal=True, scale=1.0 / math.sqrt(q.shape[-1]))

    xla_out = jax.jit(xla_form)(q, k, v)
    xla_grads = jax.jit(jax.grad(scalar(xla_form),
                                 argnums=(0, 1, 2)))(q, k, v)

    def err(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if not np.isfinite(got).all():
            raise RuntimeError(f"flash {shape}: non-finite values")
        return float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))

    def errors(want_out, want_grads):
        return {"fwd": err(out, want_out),
                **{f"d{n}": err(g, r)
                   for n, g, r in zip("qkv", grads, want_grads)}}

    errs, vs_xla = errors(ref_out, ref_grads), errors(xla_out, xla_grads)
    worst = max(*errs.values(), *vs_xla.values())
    if worst > FLASH_ERR_BOUND:
        raise RuntimeError(f"flash {shape}: error {errs} against float32, "
                           f"{vs_xla} against _single_device_attention, "
                           f"exceeds {FLASH_ERR_BOUND}")

    def rounded(d):
        return {k: round(e, 5) for k, e in d.items()}

    return {"shape": list(shape), "compile_and_run_s": secs,
            "max_abs_err_over_ref_scale": rounded(errs),
            "max_abs_err_over_single_device_attention": rounded(vs_xla)}


def phase_flash(size: Size, prob: Problem, ref_losses, seed: int,
                steps: int = 2) -> dict:
    """Phase 4: the Pallas kernel alone at real shapes, then inside the
    phase-1 step (``attn_impl="flash"``)."""
    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    kernels = [_flash_kernel_check(shape, seed, size.interpret)
               for shape in size.flash_shapes]
    bps.init()
    try:
        step = make_train_step(prob.flash_loss_fn, prob.tx)
        params, opt_state, batch = place(prob)
        if not size.interpret:
            # The step really holds the compiled Mosaic kernel — not an
            # interpreted one, not XLA attention.
            if "tpu_custom_call" not in step.lower(
                    params, opt_state, batch).as_text():
                raise RuntimeError(
                    "attn_impl='flash' step has no tpu_custom_call")
        losses, secs = run_steps(step, params, opt_state, batch, steps)
        del params, opt_state
    finally:
        bps.shutdown()
    return emit({
        "phase": "flash", "ok": True, "interpret": size.interpret,
        "kernels": kernels, "err_bound": FLASH_ERR_BOUND,
        "losses": losses, "tol": FLASH_LOSS_TOL,
        "max_loss_diff": check_losses("flash step", losses, ref_losses,
                                      FLASH_LOSS_TOL),
        **timing(secs),
    })


def read_profile(trace_dir: str, device_plane: str) -> dict:
    """Reduce a jax.profiler capture to what the next PRs need to know:
    plane and line names, and the longest ops on the device plane."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}: "
                           f"{paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    planes, device_lines = {}, {}
    for plane in data.planes:
        lines = {line.name: [(e.duration_ns, e.name) for e in line.events]
                 for line in plane.lines}
        planes[plane.name] = {n: len(ev) for n, ev in lines.items()}
        if device_plane in plane.name:
            device_lines.update(lines)
    n_device_events = sum(len(ev) for ev in device_lines.values())
    if not n_device_events:
        raise RuntimeError(
            f"no plane named *{device_plane}* with events in the capture; "
            f"planes: {sorted(planes)}")
    # A TPU plane's "XLA Modules" line holds one event per executed
    # program and its "XLA Ops" line the ops inside them, back to back
    # (event names are the HLO instructions' text); "Async XLA Ops" are
    # the copies that overlap them.
    programs = device_lines.get("XLA Modules", [])
    ops = sorted(device_lines.get("XLA Ops")
                 or (ev for evs in device_lines.values() for ev in evs),
                 reverse=True)
    return {
        "xplane": os.path.relpath(paths[0], REPO),
        "xplane_bytes": os.path.getsize(paths[0]),
        "planes": {name: dict(sorted(lines.items(), key=lambda kv: -kv[1])[:8])
                   for name, lines in planes.items()},
        "device_events": n_device_events,
        "device_programs": [{"ns": int(ns), "name": name[:80]}
                            for ns, name in programs[:4]],
        "device_ops_total_ns": int(sum(ns for ns, _ in ops)),
        "longest_device_ops": [{"ns": int(ns), "name": name[:100]}
                               for ns, name in ops[:5]],
    }


def phase_profile(size: Size, prob: Problem, out_dir: str,
                  steps: int = 2) -> dict:
    """Phase 5: a device-side profiler capture of the phase-1 step."""
    import jax

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    trace_dir = os.path.join(out_dir, "profile")
    bps.init()
    try:
        step = make_train_step(prob.loss_fn, prob.tx)
        params, opt_state, batch = place(prob)
        # compile outside the capture
        params, opt_state, _ = step(params, opt_state, batch)
        jax.block_until_ready(params)
        # Device and host-runtime events only: the Python tracer's
        # per-call events would dwarf them. A profiler that cannot start
        # is a failure, not a shorter trace.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.raise_error_on_start_failure = True
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            _, secs = run_steps(step, params, opt_state, batch, steps)
        finally:
            jax.profiler.stop_trace()
    finally:
        bps.shutdown()
    return emit({"phase": "profile", "ok": True, "traced_step_s": secs,
                 **read_profile(trace_dir, size.device_plane)})


def count_collectives(hlo_text: str) -> dict:
    """Collective ops in optimized HLO, by kind (async pairs count once,
    at their -start)."""
    kinds = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
             "collective-permute")
    return {k: len(re.findall(rf"= [^=\n]*\b{k}(?:-start)?\(", hlo_text))
            for k in kinds}


def phase_multichip(prob: Problem, out_dir: str, n_chips: int = 4,
                    steps: int = 5, ps_steps: int = 3) -> dict:
    """Phase 6 (``--chips 4`` only): the (dcn, ici) mesh across chips, in
    collective and PS mode, against the plain single-device step on the
    same global batch."""
    import jax

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    params = jax.device_put(prob.params)
    ref_losses, ref_secs = run_steps(
        plain_step(prob.loss_fn, prob.tx, micro_batches=n_chips), params,
        prob.tx.init(params), jax.device_put(prob.tokens), steps)
    del params

    def placed():
        """State on the mesh — checked, because code that never saw more
        than one chip may put everything on the first."""
        params, opt_state, batch = place(prob)
        for leaf in jax.tree_util.tree_leaves(params):
            devs = {s.device for s in leaf.addressable_shards}
            if len(devs) != n_chips:
                raise RuntimeError(
                    f"replicate(): a parameter leaf lives on {len(devs)} "
                    f"device(s), not {n_chips}")
        rows = {s.device: s.data.shape[0] for s in batch.addressable_shards}
        if (len(rows) != n_chips or set(rows.values())
                != {prob.tokens.shape[0] // n_chips}):
            raise RuntimeError(f"shard_batch(): rows per device {rows}")
        return params, opt_state, batch

    rec = {"phase": "multichip", "ok": True, "ref_losses": ref_losses,
           "ref": timing(ref_secs), "tol": LOSS_TOL}
    bps.init()
    try:
        mesh = bps.mesh()
        rec["mesh"] = dict(mesh.shape)
        if dict(mesh.shape) != {"dcn": 1, "ici": n_chips}:
            raise RuntimeError(f"bps.init() built mesh {dict(mesh.shape)}")
        step = make_train_step(prob.loss_fn, prob.tx)
        params, opt_state, batch = placed()
        rec["collectives"] = count_collectives(
            step.lower(params, opt_state, batch).compile().as_text())
        losses, secs = run_steps(step, params, opt_state, batch, steps)
        del params, opt_state
    finally:
        bps.shutdown()
    rec["collective"] = {
        "losses": losses, **timing(secs),
        "max_loss_diff": check_losses("multichip collective", losses,
                                      ref_losses, LOSS_TOL)}

    # PS mode: ONE worker process drives all the chips; the local
    # reduction runs inside jit, so one gradient tree crosses the host
    # boundary and the wire, not one per chip.
    with ps_fleet(os.path.join(out_dir, "multichip_ps")):
        bps.init()
        try:
            placed()
            rec["ps"] = _ps_steps(
                "multichip ps",
                lambda: make_train_step(prob.loss_fn, prob.tx),
                prob, ref_losses, ps_steps)
        finally:
            bps.shutdown()
    return emit(rec)


# --------------------------------------------------------------------------

def run(args) -> dict:
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    device = phase_device()["device"]
    if device["count"] != args.chips:
        raise RuntimeError(
            f"--chips {args.chips}, but JAX found {device['count']} device(s)")
    prob = make_problem(FULL, args.seed, args.chips)
    if args.chips == 4:
        phase_multichip(prob, out_dir)
        return device
    col = phase_collective(prob)
    ref = col["ref_losses"]
    phase_ps(prob, ref, col["step_s"], out_dir)
    phase_overlap(prob, ref, out_dir)
    phase_flash(FULL, prob, ref, args.seed)
    phase_profile(FULL, prob, out_dir)
    return device


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multichip phase (builder's run)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"))
    args = ap.parse_args()
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    t0 = time.perf_counter()
    try:
        device = run(args)
    except BaseException:
        # the traceback follows on stderr; stdout still ends in one line
        print(json.dumps({"ok": False, "device": None}), flush=True)
        raise
    import jax
    emit({"phase": "total", "ok": True,
          "seconds": round(time.perf_counter() - t0, 1),
          "peak_bytes_in_use": jax.devices()[0].memory_stats()[
              "peak_bytes_in_use"]})
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
