"""On the chip: the delta-rule scan of both hybrid cells (one decay a head
at the Qwen3-Next cell's shapes, one a channel at the Kimi-Linear cell's)
and the 256-wide attention kernels against the plain references — values
and every gradient, tensor by tensor — and the wrong computations the
tolerance must fail.

    chiprun --chips 1 -- python3 tools/scan_check.py [--seed N]
        [--cases gdn,kda,attention,ssd,conv,gate]

What a benchmark run cannot see (``benchmark/lib/reference.py`` compares
three losses to 2e-3: PERF.md section 7, "``correct`` by cell") is held
here, on the device, for the compiled programs the cell runs.

**The scan**: ``kda_attention`` with one decay a head, b 1 x s of the cell,
16 key heads of 128 under 32 value heads of 128, bf16 operands, the file's
chunk (on the chip, since PR 59, the operand kernels of ``ops/gdn_chunk.py``
under the recurrence kernels: whatever ``kda_form`` picks is what is held)
— ``o`` and the gradients of q, k, v, g and beta against
``benchmark/lib/plain_qwen3_next.py::gated_delta_rule``, the recurrence
token by token in float32 with nothing of ``byteps_tpu`` in it. q and k are
unit vectors (q times 128^-1/2), v and the cotangent standard normal, beta a
sigmoid of one, and the log-decay Mamba's rule as the configuration
initialises it: ``-A softplus(n + dt_bias)``, A over (1, 16) and dt over
(1e-3, 1e-1), both spread evenly in the logarithm over the heads, n standard
normal — heads that forget in a token beside heads that remember
thousands. The measure, for each tensor: ``|got - want|_2 / |want|_2``. The controls are the reference
computed wrongly, not the program: a state rounded to bf16 after every
token, a chunk's cumulated log-decay clamped at -20 (what a form that
exponentiates its negation does to stay finite; as per-token decays, so
that the recurrence computes exactly what such a chunked form would), and
value head i reading key head ``i % 16`` and not ``i // 2``. The run fails
— exit 1, ``"ok": false`` — if a tensor of the program reads above
``SCAN_TOLERANCE`` or a control's worst tensor below it.

**The scan, one decay a channel** (since PR 54, whose recurrence kernels
this form's scan runs on the chip): ``kda_attention`` at b 1 x s of the Kimi-Linear
cell, 32 heads of 128 x 128, the file's chunks of 32 in sub-chunks of 8,
against ``benchmark/lib/plain_kimi_linear.py::delta_rule``; the same inputs
with a standard normal a channel under the decay's softplus, the same
measure and tolerance, and the controls that exist there: the bf16 state
(that reference rounds by a cast there and back, which the TPU compiler
may drop as excess precision: the control is compiled with
``xla_allow_excess_precision`` off, it alone) and the clamped decay.

**The kernels**: ``tools/attention_check.py``'s check, called, at 16 query
heads over 2 key heads of 256 under the causal triangle: out, dQ, dK, dV
within its ``TOLERANCE``, out alone within its ``OUT_TOLERANCE``, the other
head grouping and bf16 logits and statistics above them.

**The scan with no delta rule** (since PR 63): ``ssd_scan`` at b 1 x s of
the Nemotron cell, 8 groups of ``B`` and ``C`` of 128 under 64 heads of 64,
bf16 operands, the file's chunk, against ``benchmark/lib/
plain_nemotron_h.py::selective_scan`` — ``y`` and the gradients of x, B, C,
the step and ``A_log`` (the decay ``g = -exp(A_log) dt`` is formed on both
sides, so that both gradients pass through it) —, the step and the rate
Mamba's rule spread over the heads as above, the same measure and
tolerance, and the three controls: the bf16 state, the clamped decay, head i
reading group ``i % 8``. What is held is the form ``ssd_form`` picks on the
backend the tool runs on (on the chip, since PR 65, the kernels of
``ops/ssd_scan.py``; the record's ``form`` says which), and on the chip the
record also times the op alone, forward + backward, in that form and in the
XLA form (``ms``: the median of ``TIMED_RUNS`` calls each). ``--cases ssd``
runs it alone.

**The short convolution** (since PR 64): ``causal_conv`` as the program
runs it on the chip — its forward pass the kernel of ``ops/causal_conv.py``
wherever ``conv_form`` says so, and the compiled program must name it, its
backward pass the XLA form's — against the XLA form ``causal_conv_xla`` at
the four call sites of the cells: Nemotron's ``[s, 6144]`` with a bias,
Qwen3-Next's ``[s, 8192]``, Kimi-Linear's ``[s, 4096]`` (bf16, SiLU, 4
taps) and ZAYA1's ``[s, 1280]`` (float32, no activation, 2 taps), two seeds
each: ``y`` to float32 rounding (``CONV_TOLERANCE["y"]``; it read equal to
the bit at all four, PERF.md section 6, PR 64), ``dx`` to an ulp of its
dtype here and there, ``dw`` and ``dbias`` to the order of a 16,384-row
float32 sum (the same XLA arithmetic on both sides, fused with another
forward pass); each by ``|got - want|_2 / |want|_2``. The controls are the
XLA form computed wrongly: the taps in the other order, and the output
rounded to bf16 (the precision below the op's). ``--cases conv`` runs it
alone, in about three minutes.

**The mixer's output chain** (since PR 69): ``gated_group_norm`` as the
Nemotron mixer calls it on the chip — the kernel pair of
``ops/gated_norm.py`` wherever ``gate_form`` says so, and the compiled
program must name both — at the cell's shapes from the configuration's
file: ``y`` [1, 16384, 4096], ``x`` the first 4,096 columns of the
convolution's ``[s, 6144]`` and ``z`` of the projection's bf16 ``[s,
12288]``, 8 groups, heads of 64, a bf16 result. The result and the five
gradients (``dx`` and ``dz`` as wide as the arrays they lie in) against the
XLA form ``gated_group_norm_xla`` with the same bf16 result, each by ``|got
- want|_2 / |want|_2`` within its ``GATE_TOLERANCE`` (the same float32
arithmetic on both sides: a rounding that fell the other way here and
there), and the result against the XLA form's float32 result within
``GATE_ROUNDING``, the one rounding it is allowed. The controls are the XLA
form computed wrongly, their results against the tolerance of ``out``: the
norm before the gate, groups twice as wide, and the gated product rounded
to bf16 before the norm (the precision below the chain's). On the chip the
record also times the chain alone, forward + backward, in the picked form
and in the XLA form (``ms``). ``--cases gate`` runs it alone, in about two
minutes.

**The selective scan** (since PR 71): ``selective_scan`` — Mamba-1's
recurrence, one decay a channel *and* state entry, the one form every
backend runs — at the Phi-4-mini-flash cell's shapes from the
configuration's file (``[1, s, 5120]`` under 16 state entries, float32):
``y`` and the gradients of x, the step, ``A``, ``B`` and ``C`` against
``benchmark/lib/plain_phi4_flash.py::selective_scan``, the recurrence token
by token, each by ``|got - want|_2 / |want|_2`` within ``SEL_TOLERANCE``
(float32 elementwise arithmetic on both sides; what differs is the order of
a sum over 16 entries, and over the sequence for ``A``'s gradient). The
controls are that reference computed wrongly: a state rounded to bf16 after
every token, the log-decay cumulated inside the scan's chunks clamped at
-20, and one decay a channel (``A``'s mean over the state entries: the
transition Mamba-2 has). On the chip the record also times the op alone,
forward and forward + backward (``ms``). ``--cases sel`` runs it alone, in
about two minutes.

My chip run's readings (PR 50) are in PERF.md section 6. One JSON line a
case, then ``{"ok": ..., "device": ...}``; off the chip both run at a small
size (``tests/test_scan_check.py``).
"""

from __future__ import annotations

import argparse
import collections
import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import attention_check  # noqa: E402

SCAN_TOLERANCE = 1.0e-2     # every tensor of the scan (PERF.md, PR 50)
CLAMP = -20.0
TENSORS = ("o", "dq", "dk", "dv", "dg", "dbeta")

SSD_TENSORS = ("y", "dx", "db", "dc", "ddt", "da_log")
TIMED_RUNS = 10             # calls of the op alone a timing is the median of
SEL_TENSORS = ("y", "dx", "ddt", "da", "db", "dc")
# the selective scan's tensors: float32 elementwise on both sides (PERF.md
# section 6, PR 71, has the readings this lies between)
SEL_TOLERANCE = 1.0e-4

# the short convolution's tensors, each with its own tolerance: y is the
# same float32 arithmetic up to how a multiply-add rounds; dx is rounded
# once to x's dtype on both sides (bf16: an ulp, 2^-8, on the few elements
# whose float32 sums differ in the last bit); dw and dbias are sums over
# every token of the sequence
CONV_TOLERANCE = {"y": 1.0e-6, "dx": 1.0e-4, "dw": 1.0e-5, "dbias": 1.0e-5}
CONV_SEEDS = 2

# the mixer's output chain: ``out`` and ``dz`` are rounded to bf16 on both
# sides (a bf16 ulp, 2^-8, on the few elements whose float32 values differ
# in the last bits); dy and dx are float32; dskip and dweight sums over
# every token
GATE_TENSORS = ("out", "dy", "dx", "dz", "dskip", "dweight")
GATE_TOLERANCE = {"out": 2.0e-4, "dy": 1.0e-5, "dx": 1.0e-5, "dz": 2.0e-4,
                  "dskip": 1.0e-5, "dweight": 1.0e-5}
# a bf16 result against the float32 one: one rounding, 1.7e-3 rms
GATE_ROUNDING = 2.5e-3

ScanCase = collections.namedtuple(
    "ScanCase", "seq key_heads heads key_dim value_dim chunk")
# one decay a channel: as many key heads as value heads, and sub-chunks
ChannelCase = collections.namedtuple(
    "ChannelCase", "seq heads key_dim value_dim chunk sub")
# no delta rule: ``groups`` groups of B and C of ``state`` entries under
# ``heads`` heads of ``channels``
SsdCase = collections.namedtuple(
    "SsdCase", "seq groups heads state channels chunk")
# one call site of ``causal_conv``: ``dtype`` the name of x's
ConvCase = collections.namedtuple(
    "ConvCase", "name seq channels taps dtype biased activation")
# the output chain of a Mamba-2 mixer: ``heads`` heads of ``head_dim``
# channels in ``groups`` groups, ``x`` lying in [x | B | C] with ``state``
# entries a group of B and of C, ``z`` in [z | x | B | C]
GateCase = collections.namedtuple(
    "GateCase", "seq heads head_dim groups state")


# one decay a channel and state entry: ``channels`` under ``state`` entries
SelCase = collections.namedtuple("SelCase", "seq channels state chunk")


def cell_cases():
    """``(the scan's case, the attention's)`` from the configuration's
    file."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    return (ScanCase(cfg["seq_len"], cfg["linear_num_key_heads"],
                     cfg["linear_num_value_heads"],
                     cfg["linear_key_head_dim"],
                     cfg["linear_value_head_dim"], cfg["gdn_chunk"]),
            attention_check.Case("gated", cfg["seq_len"],
                                 cfg["num_attention_heads"],
                                 cfg["num_key_value_heads"],
                                 cfg["head_dim"], None))


def channel_case() -> ChannelCase:
    """The Kimi-Linear cell's scan from its configuration's file."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        cfg = json.load(f)
    linear = cfg["linear_attn_config"]
    return ChannelCase(cfg["seq_len"], linear["num_heads"],
                       linear["head_dim"], linear["head_dim"],
                       cfg["kda_chunk"], cfg["kda_sub_chunk"])


def ssd_case() -> SsdCase:
    """The Nemotron cell's scan from its configuration's file."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    return SsdCase(cfg["seq_len"], cfg["n_groups"], cfg["mamba_num_heads"],
                   cfg["ssm_state_size"], cfg["mamba_head_dim"],
                   cfg["ssm_chunk"])


def sel_case() -> SelCase:
    """The Phi-4-mini-flash cell's scan from its configuration's file."""
    from byteps_tpu.parallel.linear_attention import SEL_CHUNK

    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    return SelCase(cfg["seq_len"], cfg["mamba_expand"] * cfg["hidden_size"],
                   cfg["mamba_d_state"], SEL_CHUNK)


def gate_case() -> GateCase:
    """The Nemotron cell's output chain from its configuration's file."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        cfg = json.load(f)
    return GateCase(cfg["seq_len"], cfg["mamba_num_heads"],
                    cfg["mamba_head_dim"], cfg["n_groups"],
                    cfg["ssm_state_size"])


def conv_cases():
    """The four call sites of ``causal_conv`` in the benchmark's cells, from
    their configurations' files."""
    def cfg(name):
        with open(os.path.join(REPO, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)

    nemotron, qwen, kimi, zaya = (cfg(n) for n in (
        "nemotron-3-nano-30b-a3b", "qwen3-next-80b-a3b",
        "kimi-linear-48b-a3b", "zaya1-8b"))
    linear = kimi["linear_attn_config"]
    return [
        ConvCase("nemotron", nemotron["seq_len"],
                 nemotron["mamba_num_heads"] * nemotron["mamba_head_dim"]
                 + 2 * nemotron["n_groups"] * nemotron["ssm_state_size"],
                 nemotron["conv_kernel"], "bfloat16", True, "silu"),
        ConvCase("qwen3_next", qwen["seq_len"],
                 2 * qwen["linear_num_key_heads"]
                 * qwen["linear_key_head_dim"]
                 + qwen["linear_num_value_heads"]
                 * qwen["linear_value_head_dim"],
                 qwen["linear_conv_kernel_dim"], "bfloat16", False, "silu"),
        ConvCase("kimi_linear", kimi["seq_len"],
                 linear["num_heads"] * linear["head_dim"],
                 linear["short_conv_kernel_size"], "bfloat16", False,
                 "silu"),
        ConvCase("zaya1", zaya["seq_len"],
                 (zaya["num_attention_heads"] + zaya["num_key_value_heads"])
                 * zaya["head_dim"], zaya["cca_time0"], "float32", False,
                 None)]


def clamped_in_chunks(g, chunk: int, floor: float):
    """The per-token log-decays [b, s, h] (or [b, s, h, d_k]) whose
    cumulated sum inside every chunk of ``chunk`` tokens is ``max(G,
    floor)``: what a chunked form that clamps its cumulated log-decay at
    ``floor`` really computes."""
    import jax.numpy as jnp

    G = jnp.maximum(cumulated_in_chunks(g, chunk), floor)
    steps = jnp.diff(G, axis=2, prepend=jnp.zeros_like(G[:, :, :1]))
    return steps.reshape(g.shape[0], -1, *g.shape[2:])[:, :g.shape[1]]


def cumulated_in_chunks(g, chunk: int):
    """[b, n, chunk, h, ...]: ``g`` [b, s, h, ...] cumulated inside each
    chunk of ``chunk`` tokens, zeros after s."""
    import jax.numpy as jnp

    b, s = g.shape[:2]
    pad = ((0, 0), (0, (-s) % chunk)) + ((0, 0),) * (g.ndim - 2)
    return jnp.cumsum(jnp.pad(g, pad).reshape(b, -1, chunk, *g.shape[2:]),
                      axis=2)


def scan_inputs(case, seed: int):
    """(q, k, v, g, beta, cotangent), float32, b 1; g [1, s, h] for a
    ``ScanCase``, [1, s, h, d_k] for a ``ChannelCase``."""
    import jax
    import jax.numpy as jnp
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    s, h = case.seq, case.heads
    per_head = isinstance(case, ScanCase)
    key_heads = case.key_heads if per_head else h
    decays = (1, s, h) if per_head else (1, s, h, case.key_dim)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    # both ranges spread evenly over the heads, so that every seed holds a
    # head that forgets in a token and one that remembers thousands
    spread = jnp.arange(h, dtype=jnp.float32) / max(h - 1, 1)
    rate, dt = 16.0 ** spread, 1e-3 * 100.0 ** spread
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    if not per_head:            # a head's rate and bias for all its channels
        rate, dt_bias = rate[:, None], dt_bias[:, None]
    return (unit(jax.random.normal(keys[0], (1, s, key_heads, case.key_dim)))
            * case.key_dim ** -0.5,
            unit(jax.random.normal(keys[1], (1, s, key_heads, case.key_dim))),
            jax.random.normal(keys[2], (1, s, h, case.value_dim)),
            -rate * jax.nn.softplus(jax.random.normal(keys[3], decays)
                                    + dt_bias),
            jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, h))),
            jax.random.normal(keys[5], (1, s, h, case.value_dim)))


def _judged(record: dict) -> dict:
    """``record`` with its ``ok``: every tensor of the program's scan within
    ``SCAN_TOLERANCE`` and every control's worst tensor above it."""
    record["ok"] = bool(
        max(record["scan"].values()) <= SCAN_TOLERANCE
        and all(max(c.values()) > SCAN_TOLERANCE
                for c in record["controls"].values()))
    return record


def _outputs(fn, operands, w, **compiler_options):
    """``fn(*operands)`` and its gradient for each of the five operands
    under the cotangent ``w``, compiled (with ``compiler_options``) and
    fetched."""
    import jax

    def scalar(*a):
        out = fn(*a)
        return (out * w).sum(), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
            *operands).compile(compiler_options or None)(*operands)
    return jax.device_get((out, *grads))


def ssd_inputs(case: SsdCase, seed: int):
    """(x, B, C, dt, A_log, cotangent), float32, b 1: x, B, C and the
    cotangent standard normal; the step ``softplus(n + dt_bias)`` and the
    rate ``exp(A_log)`` Mamba's rule as the configuration initialises it,
    dt over (1e-3, 1e-1) and A over (1, 16), both spread evenly in the
    logarithm over the heads (``scan_inputs`` has the reason)."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    s, h = case.seq, case.heads
    spread = jnp.arange(h, dtype=jnp.float32) / max(h - 1, 1)
    dt = 1e-3 * 100.0 ** spread
    return (jax.random.normal(keys[0], (1, s, h, case.channels)),
            jax.random.normal(keys[1], (1, s, case.groups, case.state)),
            jax.random.normal(keys[2], (1, s, case.groups, case.state)),
            jax.nn.softplus(jax.random.normal(keys[3], (1, s, h))
                            + dt + jnp.log(-jnp.expm1(-dt))),
            spread * jnp.log(16.0),
            jax.random.normal(keys[4], (1, s, h, case.channels)))


def check_ssd(case: SsdCase, seed: int, scan=None) -> dict:
    """The program's state-space scan (``scan(x, B, C, dt, A_log)``, by
    default ``ssd_scan`` in bf16 at the case's chunk under the decay ``g =
    -exp(A_log) dt``) against ``benchmark/lib/plain_nemotron_h.py::
    selective_scan``, the recurrence token by token in float32: ``y`` and
    the gradients of x, B, C, the step and ``A_log``, each by ``|got -
    want|_2 / |want|_2``, and the controls: a state rounded to bf16 after
    every token, a chunk's cumulated log-decay clamped at -20 and head i
    reading group ``i % groups`` and not ``i // (heads / groups)``."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.plain_nemotron_h import selective_scan

    *operands, w = ssd_inputs(case, seed)

    def decay(dt, a_log):
        return -jnp.exp(a_log) * dt

    if scan is None:
        from byteps_tpu.parallel.linear_attention import ssd_scan

        def scan(x, b, c, dt, a_log):
            return ssd_scan(c, b, x, decay(dt, a_log), dt, chunk=case.chunk,
                            dtype=jnp.bfloat16)

    block = min(128, case.seq)

    def plain(clamp=None, **wrong):
        def fn(x, b, c, dt, a_log):
            g = decay(dt, a_log)
            if clamp is not None:
                g = clamped_in_chunks(g, case.chunk, clamp)
            with jax.default_matmul_precision("highest"):
                return selective_scan(c[0], b[0], x[0], g[0], dt[0],
                                      scan_block=block, **wrong)[None]

        return fn

    def readings(got):
        return dict(zip(SSD_TENSORS,
                        map(attention_check._relative, got, want)))

    from byteps_tpu.parallel.linear_attention import ssd_form

    want = _outputs(plain(), operands, w)
    record = {
        "case": case._asdict(), "seed": seed,
        "form": ssd_form(jax.default_backend(), jnp.bfloat16, case.state,
                         case.channels, case.heads, case.groups, case.chunk),
        "min_chunk_log_decay": float(cumulated_in_chunks(
            decay(*operands[3:]), case.chunk).min()),
        "scan": readings(_outputs(scan, operands, w)),
        "controls": {
            "bf16_state": readings(_outputs(
                plain(state_dtype=jnp.bfloat16), operands, w)),
            "clamped_at_-20": readings(_outputs(
                plain(clamp=CLAMP), operands, w)),
            "heads_interleaved": readings(_outputs(plain(group_of=[
                i % case.groups for i in range(case.heads)]), operands, w))},
        "tolerance": SCAN_TOLERANCE}
    return _judged(record)


def sel_inputs(case: SelCase, seed: int):
    """(x, dt, A, B, C, cotangent), float32, b 1: x, B, C and the cotangent
    standard normal; the step ``softplus`` of a unit normal around Mamba's
    initial steps spread over the channels from 1e-3 to 1 (so that the fast
    channels' chunks pass the controls' floor); ``A = -exp(log U(1, 16))`` a
    channel and entry, the model's initialisation."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    s, ch, n = case.seq, case.channels, case.state
    dt = 1e-3 * 1000.0 ** (jnp.arange(ch, dtype=jnp.float32) / max(ch - 1, 1))
    return (jax.random.normal(keys[0], (1, s, ch)),
            jax.nn.softplus(jax.random.normal(keys[1], (1, s, ch))
                            + dt + jnp.log(-jnp.expm1(-dt))),
            -jax.random.uniform(keys[2], (ch, n), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (1, s, n)),
            jax.random.normal(keys[4], (1, s, n)),
            jax.random.normal(keys[5], (1, s, ch)))


def check_sel(case: SelCase, seed: int, scan=None) -> dict:
    """The program's selective scan (``scan(x, dt, A, B, C)``, by default
    ``selective_scan`` at the case's chunk) against ``benchmark/lib/
    plain_phi4_flash.py::selective_scan``, the recurrence token by token in
    float32: ``y`` and the five gradients, each by ``|got - want|_2 /
    |want|_2`` within ``SEL_TOLERANCE``, and the controls: a state rounded
    to bf16 after every token, a chunk's cumulated log-decay clamped at -20
    and one decay a channel."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.plain_phi4_flash import selective_scan as plain_scan

    *operands, w = sel_inputs(case, seed)
    if scan is None:
        from byteps_tpu.parallel.linear_attention import selective_scan

        def scan(x, dt, a, b, c):
            return selective_scan(x, dt, a, b, c, chunk=case.chunk)

    block = min(128, case.seq)

    def plain(**wrong):
        def fn(x, dt, a, b, c):
            return plain_scan(x[0], dt[0], a, b[0], c[0], scan_block=block,
                              **wrong)[None]

        return fn

    def readings(got):
        return dict(zip(SEL_TENSORS,
                        map(attention_check._relative, got, want)))

    want = _outputs(plain(), operands, w)
    record = {
        "case": case._asdict(), "seed": seed,
        "min_chunk_log_decay": float(
            (cumulated_in_chunks(operands[1], case.chunk)[:, :, -1]
             * operands[2].min(-1)).min()),
        "scan": readings(_outputs(scan, operands, w)),
        "controls": {
            "bf16_state": readings(_outputs(
                plain(state_dtype=jnp.bfloat16), operands, w)),
            "clamped_at_-20": readings(_outputs(
                plain(decay_floor=CLAMP, floor_chunk=case.chunk), operands,
                w)),
            "one_decay_a_channel": readings(_outputs(
                plain(per_channel=True), operands, w))},
        "tolerance": SEL_TOLERANCE}
    record["ok"] = bool(
        max(record["scan"].values()) <= SEL_TOLERANCE
        and all(max(c.values()) > SEL_TOLERANCE
                for c in record["controls"].values()))
    return record


def time_sel(case: SelCase, seed: int) -> dict:
    """Milliseconds of ``selective_scan`` alone at the case's shapes:
    ``forward``, and ``forward_backward`` under a cotangent (all five
    gradients) — each the median of ``TIMED_RUNS`` calls after one that
    compiles."""
    import jax

    from byteps_tpu.parallel.linear_attention import selective_scan

    *operands, w = sel_inputs(case, seed)

    def loss(x, dt, a, b, c, w):
        return (selective_scan(x, dt, a, b, c, chunk=case.chunk) * w).sum()

    return {
        "forward": _median_ms(jax.jit(
            lambda *a: selective_scan(*a, chunk=case.chunk)), operands),
        "forward_backward": _median_ms(jax.jit(jax.grad(
            loss, argnums=(0, 1, 2, 3, 4))), (*operands, w))}


def _median_ms(fn, operands) -> float:
    """The median over ``TIMED_RUNS`` calls of ``fn(*operands)``, in
    milliseconds, after one call that compiles."""
    import statistics
    import time

    import jax

    jax.block_until_ready(fn(*operands))
    runs = []
    for _ in range(TIMED_RUNS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*operands))
        runs.append((time.perf_counter() - start) * 1e3)
    return statistics.median(runs)


def time_ssd(case: SsdCase, seed: int) -> dict:
    """Milliseconds of ``ssd_scan`` alone, forward + backward under a
    cotangent, at the case's shapes: ``picked`` in the form ``ssd_form``
    picks here, ``xla`` with the rule told to refuse — each the median of
    ``TIMED_RUNS`` calls after one that compiles."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.parallel import linear_attention as la

    x, b, c, dt, a_log, w = ssd_inputs(case, seed)

    def loss(x, b, c, dt, a_log):
        return (la.ssd_scan(c, b, x, -jnp.exp(a_log) * dt, dt,
                            chunk=case.chunk, dtype=jnp.bfloat16) * w).sum()

    def timed():
        return _median_ms(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))),
                          (x, b, c, dt, a_log))

    out = {"picked": timed()}
    rule = la.ssd_form
    la.ssd_form = lambda *shapes: "xla"
    try:
        out["xla"] = timed()
    finally:
        la.ssd_form = rule
    return out


def check_conv(case: ConvCase, seed: int, conv=None) -> dict:
    """The program's short convolution (``conv(x, w, bias)``, by default
    ``causal_conv`` as ``conv_form`` runs it here) against
    ``causal_conv_xla``: ``y``, ``dx``, ``dw`` and, with a bias, ``dbias``,
    each by ``|got - want|_2 / |want|_2`` within its ``CONV_TOLERANCE``,
    over ``CONV_SEEDS`` seeds from ``seed`` (the worst reading of each
    tensor is reported), and the controls' ``y`` above ``y``'s. x standard
    normal rounded to the case's dtype, taps of variance 1 / taps as the
    models initialise them, a bias of 0.1, a standard normal cotangent."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models.kimi_linear import (causal_conv, causal_conv_xla,
                                                conv_form)

    if conv is None:
        def conv(x, w, bias):
            return causal_conv(x, w, bias, case.activation)

    def plain(x, w, bias):
        return causal_conv_xla(x, w, bias, case.activation)

    def reversed_taps(x, w, bias):
        return plain(x, w[::-1], bias)

    def bf16_out(x, w, bias):
        # not a cast there and back, which the TPU compiler may drop as
        # excess precision
        return jax.lax.reduce_precision(plain(x, w, bias), 8, 7)

    names = ("y", "dx", "dw", "dbias") if case.biased else ("y", "dx", "dw")

    def outputs(fn, x, w, bias, ct):
        def both(x, w, bias):
            y, vjp = jax.vjp(fn, x, w, bias)
            return (y, *vjp(ct))

        compiled = jax.jit(both).lower(x, w, bias).compile()
        return compiled.as_text(), dict(zip(names, jax.device_get(
            compiled(x, w, bias))))

    form = conv_form(jax.default_backend(), case.seq, case.channels,
                     case.taps)
    worst = {name: 0.0 for name in names}
    controls = {"taps_reversed": 0.0, "bf16_output": 0.0}
    kernel_in_program = True
    for run in range(CONV_SEEDS):
        keys = jax.random.split(jax.random.PRNGKey((seed + run) % 2 ** 31), 4)
        shape = (1, case.seq, case.channels)
        x = jax.random.normal(keys[0], shape).astype(case.dtype)
        w = jax.random.normal(keys[1], (case.taps, case.channels)) \
            * case.taps ** -0.5
        bias = (0.1 * jax.random.normal(keys[2], (case.channels,))
                if case.biased else None)
        ct = jax.random.normal(keys[3], shape)
        _, want = outputs(plain, x, w, bias, ct)
        text, got = outputs(conv, x, w, bias, ct)
        if form == "kernel":
            from byteps_tpu.ops.causal_conv import FWD_NAME

            kernel_in_program = kernel_in_program and FWD_NAME in text
        for name in names:
            worst[name] = max(worst[name], attention_check._relative(
                got[name], want[name]))
        for control, fn in (("taps_reversed", reversed_taps),
                            ("bf16_output", bf16_out)):
            y = jax.device_get(jax.jit(fn)(x, w, bias))
            # the lowest reading is the control's weakest: it must fail too
            reading = attention_check._relative(y, want["y"])
            controls[control] = (reading if run == 0
                                 else min(controls[control], reading))
    return {
        "case": case._asdict(), "seed": seed, "seeds": CONV_SEEDS,
        "form": form, "kernel_in_program": kernel_in_program,
        "conv": worst, "controls": controls,
        "tolerance": {name: CONV_TOLERANCE[name] for name in names},
        "ok": bool(kernel_in_program
                   and all(worst[n] <= CONV_TOLERANCE[n] for n in names)
                   and all(c > CONV_TOLERANCE["y"]
                           for c in controls.values()))}


def gate_inputs(case: GateCase, seed: int):
    """(y, mixed, z, skip, weight, cotangent), b 1: ``y`` and ``mixed`` =
    ``[x | B | C]`` standard normal float32, ``z`` = ``[z | x | B | C]``
    standard normal rounded to bf16, a skip a head about one (0.5 wide), a
    weight a channel about one (0.1 wide), a standard normal cotangent
    rounded to bf16."""
    import jax
    import jax.numpy as jnp

    inner, bc = case.heads * case.head_dim, case.groups * case.state
    keys = jax.random.split(jax.random.PRNGKey(seed % 2 ** 31), 6)
    return (jax.random.normal(keys[0], (1, case.seq, inner)),
            jax.random.normal(keys[1], (1, case.seq, inner + 2 * bc)),
            jax.random.normal(keys[2], (1, case.seq, 2 * inner + 2 * bc)
                              ).astype(jnp.bfloat16),
            1.0 + 0.5 * jax.random.normal(keys[3], (case.heads,)),
            1.0 + 0.1 * jax.random.normal(keys[4], (inner,)),
            jax.random.normal(keys[5], (1, case.seq, inner)
                              ).astype(jnp.bfloat16))


def _gate_call(fn, case: GateCase, lies_in: bool = False, **kw):
    """``fn`` (a form of ``gated_group_norm``) over ``gate_inputs``' first
    five, as the mixer calls it: ``x`` the first columns of ``mixed``, and
    with ``lies_in`` told so."""
    inner = case.heads * case.head_dim

    def call(y, mixed, z, skip, weight):
        return fn(y, mixed[..., :inner], z, skip, weight, groups=case.groups,
                  head_dim=case.head_dim,
                  **({"x_lies_in": mixed} if lies_in else {}), **kw)
    return call


def _with_gradients(fn):
    """``(ct, *operands)`` -> ``fn``'s result and its operands' gradients
    under the cotangent ``ct`` (in the result's dtype), as one function to
    compile."""
    import jax

    def both(ct, *operands):
        out, vjp = jax.vjp(fn, *operands)
        return (out, *vjp(ct.astype(out.dtype)))
    return jax.jit(both)


def check_gate(case: GateCase, seed: int, chain=None) -> dict:
    """The program's output chain (``chain(y, mixed, z, skip, weight)``, by
    default ``gated_group_norm`` as ``gate_form`` runs it here, told where
    ``x`` lies) against ``gated_group_norm_xla``: the bf16 result and the
    five gradients within ``GATE_TOLERANCE``, the result within
    ``GATE_ROUNDING`` of the float32 one, and the three controls' results
    above ``out``'s tolerance."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.models import nemotron_h as nh

    inner = case.heads * case.head_dim
    *operands, ct = gate_inputs(case, seed)
    if chain is None:
        chain = _gate_call(nh.gated_group_norm, case, lies_in=True)
    plain = _gate_call(nh.gated_group_norm_xla, case)

    def outputs(fn):
        compiled = _with_gradients(fn).lower(ct, *operands).compile()
        return compiled.as_text(), dict(zip(GATE_TENSORS, jax.device_get(
            compiled(ct, *operands))))

    def wrong(y, mixed, z, skip, weight, *, norm_first=False, groups=None,
              bf16_gate=False):
        f32 = jnp.float32
        groups = groups or case.groups
        u = y + jnp.repeat(skip, case.head_dim) * mixed[..., :inner]
        gate = jax.nn.silu(z[..., :inner].astype(f32))
        h = u if norm_first else u * gate
        if bf16_gate:   # not a cast there and back (excess precision)
            h = jax.lax.reduce_precision(h, 8, 7)
        grouped = h.reshape(1, case.seq, groups, inner // groups)
        normed = (grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True)
            + 1e-5)).reshape(1, case.seq, inner) * weight
        return (normed * gate if norm_first else normed).astype(jnp.bfloat16)

    form = nh.gate_form(jax.default_backend(), case.seq, inner, case.groups,
                        case.head_dim, jnp.bfloat16)
    _, want = outputs(plain)
    text, got = outputs(chain)
    kernel_in_program = True
    if form == "kernel":
        from byteps_tpu.ops.gated_norm import BWD_NAME, FWD_NAME

        kernel_in_program = FWD_NAME in text and BWD_NAME in text
    readings = {name: attention_check._relative(got[name], want[name])
                for name in GATE_TENSORS}
    exact = jax.device_get(jax.jit(_gate_call(
        nh.gated_group_norm_xla, case, dtype=jnp.float32))(*operands))
    rounding = attention_check._relative(got["out"], exact)
    controls = {"norm_before_gate": {"norm_first": True},
                "bf16_gated_product": {"bf16_gate": True}}
    if case.groups % 2 == 0:
        controls["groups_twice_as_wide"] = {"groups": case.groups // 2}
    controls = {
        name: attention_check._relative(jax.device_get(jax.jit(
            functools.partial(wrong, **kw))(*operands)), want["out"])
        for name, kw in controls.items()}
    return {
        "case": case._asdict(), "seed": seed, "form": form,
        "kernel_in_program": kernel_in_program, "gate": readings,
        "rounding": rounding, "controls": controls,
        "tolerance": {**GATE_TOLERANCE, "rounding": GATE_ROUNDING},
        "ok": bool(kernel_in_program and rounding <= GATE_ROUNDING
                   and all(readings[n] <= GATE_TOLERANCE[n]
                           for n in GATE_TENSORS)
                   and all(c > GATE_TOLERANCE["out"]
                           for c in controls.values()))}


def time_gate(case: GateCase, seed: int) -> dict:
    """Milliseconds of ``gated_group_norm`` alone, forward + backward under
    a cotangent, at the case's shapes: ``picked`` in the form ``gate_form``
    picks here, ``xla`` in the XLA form — each the median of ``TIMED_RUNS``
    calls after one that compiles."""
    from byteps_tpu.models import nemotron_h as nh

    *operands, ct = gate_inputs(case, seed)
    return {name: _median_ms(_with_gradients(fn), (ct, *operands))
            for name, fn in (
                ("picked", _gate_call(nh.gated_group_norm, case,
                                      lies_in=True)),
                ("xla", _gate_call(nh.gated_group_norm_xla, case)))}


def check_scan(case, seed: int, scan=None) -> dict:
    """The program's scan (``scan(q, k, v, g, beta)``, by default
    ``kda_attention`` in bf16 at the case's chunk) against the recurrence
    of the case's kind, and the controls. ``scan`` is a test's handle on a
    wrong program."""
    import jax
    import jax.numpy as jnp

    per_head = isinstance(case, ScanCase)
    if per_head:
        from benchmark.lib.plain_qwen3_next import gated_delta_rule as rule
    else:
        from benchmark.lib.plain_kimi_linear import delta_rule as rule

    *operands, w = scan_inputs(case, seed)
    if scan is None:
        from byteps_tpu.parallel.linear_attention import kda_attention

        def scan(q, k, v, g, beta):
            return kda_attention(
                q, k, v, g, beta, chunk=case.chunk,
                sub=case.chunk if per_head else case.sub, dtype=jnp.bfloat16)

    def run(fn, **compiler_options):
        return _outputs(fn, operands, w, **compiler_options)

    block = min(128, case.seq)

    def plain(**wrong):
        def fn(q, k, v, g, beta):
            with jax.default_matmul_precision("highest"):
                return rule(q[0], k[0], v[0], g[0], beta[0],
                            scan_block=block, **wrong)[None]

        return fn

    def readings(got):
        return dict(zip(TENSORS, map(attention_check._relative, got, want)))

    want = run(plain())
    sound = plain()
    controls = {
        # the per-channel reference rounds by a cast there and back
        "bf16_state": readings(run(
            plain(state_dtype=jnp.bfloat16),
            **({} if per_head else {"xla_allow_excess_precision": False}))),
        "clamped_at_-20": readings(run(lambda q, k, v, g, beta: sound(
            q, k, v, clamped_in_chunks(g, case.chunk, CLAMP), beta)))}
    if per_head:
        controls["heads_interleaved"] = readings(run(plain(key_head_of=[
            i % case.key_heads for i in range(case.heads)])))
    record = {
        "case": case._asdict(), "seed": seed,
        "min_chunk_log_decay": float(
            cumulated_in_chunks(operands[3], case.chunk).min()),
        "scan": readings(run(scan)), "controls": controls,
        "tolerance": SCAN_TOLERANCE}
    return _judged(record)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cases", default="gdn,kda,attention,ssd,conv,gate,sel",
                    help="which of the seven to run, by name")
    args = ap.parse_args()
    cases = args.cases.split(",")

    import jax

    device = jax.devices()[0]
    ok = device.platform == "tpu"        # never a CPU's figures by mistake
    if ok:
        scan_case, attention_case = cell_cases()
        for name, check, case in (("gdn", check_scan, scan_case),
                                  ("kda", check_scan, channel_case()),
                                  ("ssd", check_ssd, ssd_case())):
            if name in cases:
                record = check(case, args.seed)
                if name == "ssd":
                    record["ms"] = time_ssd(case, args.seed)
                ok = ok and record["ok"]
                print(json.dumps(record), flush=True)
        if "conv" in cases:
            for case in conv_cases():
                record = check_conv(case, args.seed)
                ok = ok and record["ok"]
                print(json.dumps(record), flush=True)
        if "gate" in cases:
            record = check_gate(gate_case(), args.seed)
            record["ms"] = time_gate(gate_case(), args.seed)
            ok = ok and record["ok"]
            print(json.dumps(record), flush=True)
        if "sel" in cases:
            record = check_sel(sel_case(), args.seed)
            record["ms"] = time_sel(sel_case(), args.seed)
            ok = ok and record["ok"]
            print(json.dumps(record), flush=True)
        if "attention" in cases:
            record = attention_check.check(attention_case, args.seed)
            ok = ok and record["ok"] and record["kernel_in_program"]
            print(json.dumps(record), flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": device.platform, "kind": device.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
