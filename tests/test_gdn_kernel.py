"""Where the decay is one number a head, ``kda_attention`` may compute a
chunk's operands with the Pallas kernel pair of ``ops/gdn_chunk.py`` and
scan the chunks with the recurrence kernels (``kda_form``'s
``"head_kernel"``), or in XLA (``"head"``). The rule as a pure function of
backend and shapes, the kernels (interpret mode: their own code on the CPU)
against the XLA form — the six operands one by one, then ``o`` and every
gradient through the whole op — and the trace-time counters.
``tests/test_qwen3_next.py`` holds the XLA form to the token-by-token
recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.parallel.linear_attention as la
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.linear_attention import (
    GDN_SCAN_SCOPE, HEAD_KERNEL_SITES, HEAD_SITES, KERNEL_SITES,
    RECURRENCE_KERNEL_SITES, SCAN_SITES, _head_operands, chunk_log_decay,
    chunked, kda_attention, kda_form)

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("args, form", [
    # the Qwen3-Next cell: 16 key heads under 32 value heads of 128 x 128
    (("tpu", 32, 128, 128, BF16, 32, True, 16), "head_kernel"),
    (("tpu", 32, 128, 128, BF16, 16, True, 16), "head_kernel"),
    (("tpu", 32, 128, 128, BF16, 32, True, 32), "head_kernel"),   # h_k = h
    (("tpu", 8, 128, 128, BF16, 8, True, 8), "head_kernel"),
    # the edges of the rows a chunk may hold (tests/test_chip_compile.py)
    (("tpu", 64, 128, 128, BF16, 16, True, 32), "head_kernel"),
    (("tpu", 128, 128, 128, BF16, 8, True, 64), "head_kernel"),
    # each refusal: the XLA form of a decay of that rank
    (("cpu", 32, 128, 128, BF16, 32, True, 16), "head"),
    (("gpu", 32, 128, 128, BF16, 32, True, 16), "head"),
    (("tpu", 32, 128, 128, F32, 32, True, 16), "head"),
    (("tpu", 32, 64, 64, BF16, 32, True, 16), "head"),
    (("tpu", 32, 128, 64, BF16, 32, True, 16), "head"),
    (("tpu", 32, 256, 128, BF16, 32, True, 16), "head"),
    (("tpu", 12, 128, 128, BF16, 32, True, 12), "head"),   # value heads
    (("tpu", 8, 128, 128, BF16, 32, True, 4), "head"),     # key heads
    (("tpu", 32, 128, 128, BF16, 20, True, 16), "head"),   # no sublane groups
    (("tpu", 32, 128, 128, BF16, 256, True, 16), "head"),  # over 128 lanes
    (("tpu", 32, 128, 128, BF16, 64, True, 16), "head"),   # pairs unrolled
    (("tpu", 8, 128, 128, BF16, 128, True, 8), "head"),
    (("tpu", 64, 128, 128, BF16, 32, True, 16), "head"),   # rows in VMEM
    (("tpu", 128, 128, 128, BF16, 32, True, 64), "head"),
    (("tpu", 256, 128, 128, BF16, 8, True, 128), "head"),
    (("cpu", 4, 8, 6, F32, 16, True, 2), "head"),          # the CPU tests'
    # one decay a channel knows no key heads: what it was
    (("tpu", 32, 128, 128, BF16, 32, False, 16), "kernel"),
    (("tpu", 32, 128, 128, BF16, 32, False, 4), "kernel"),
    (("cpu", 32, 128, 128, BF16, 32, False, 16), "xla"),
])
def test_the_rule_is_a_pure_function_of_backend_and_shapes(args, form):
    assert kda_form(*args) == form
    assert kda_form(*args[:4], np.dtype(args[4]), *args[5:]) == form


def _inputs(s, groups=2, b=1, h_k=8, d_k=8, d_v=6, strength=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = h_k * groups

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (b, s, h_k, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h_k, d_k))),
            jax.random.normal(ks[2], (b, s, h, d_v)),
            -strength * jax.nn.softplus(jax.random.normal(ks[3], (b, s, h))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
            jax.random.normal(ks[5], (b, s, h, d_v)))


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.mark.parametrize("s, chunk, groups, strength", [
    (80, 32, 2, 1.0),      # the cell's chunks and grouping; 16 zero tokens
    (80, 32, 1, 1.0),      # as many key heads as value heads
    (64, 32, 2, 8.0),      # a chunk's cumulated log-decay goes under -88
    (64, 32, 1, 8.0),
    (40, 16, 2, 1.0),
    (100, 16, 1, 1.0),     # 12 zero tokens close the last chunk
])
def test_kernel_gradients_are_jax_grad_of_the_xla_form(monkeypatch, s, chunk,
                                                       groups, strength):
    """``o`` and the gradients with respect to q, k, v, g and beta, float32
    ``dtype`` on both sides. 5e-5: the kernels multiply a pair's float32
    operands in three bf16 passes, as the chip does for the XLA form's
    ``EXACT``, where the CPU multiplies them in float32, and solve by
    substitution where the XLA form inverts by doubling; nothing discrete.
    No overflow and no clamp at the strong decay: every gradient is finite
    and is the XLA form's."""
    *args, weight = _inputs(s, groups, strength=strength)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda *a: (kda_attention(*a, chunk=chunk, sub=chunk, dtype=F32)
                        * weight).sum(), argnums=(0, 1, 2, 3, 4)))(*args)

    want = run()
    monkeypatch.setattr(la, "kda_form", lambda *shapes: "head_kernel")
    got = run()
    assert abs(float(got[0]) - float(want[0])) <= 5e-5 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        assert _rel(g, w) <= 5e-5
    if strength >= 8.0:
        G = chunk_log_decay(args[3], chunk)
        assert float(G.min()) < -88.8
        assert not bool(jnp.isfinite(jnp.exp(-G)).all())


@pytest.mark.parametrize("groups", [1, 2])
def test_the_kernel_s_operands_are_the_xla_form_s_in_bf16(groups):
    """The six operands one by one, ``dtype`` bf16 as on the chip, in the
    layout and dtypes the recurrence kernels read: what only ever is a
    matmul operand leaves in bf16 (the pairs too), ``U_v`` and ``e^{G_C}``
    in float32, ``e^{G_C}`` [b, n, h, 1]. 1.6e-2: two roundings to bf16 of
    the same number's neighbours (the XLA form rounds ``T`` and ``beta b``
    before their product, the kernel its result)."""
    from byteps_tpu.ops.gdn_chunk import head_operands

    q, k, v, g, beta, _ = _inputs(64, groups, d_k=16, d_v=16)
    tokens = [chunked(x, 32) for x in (q, k, v, beta)]
    got = head_operands(*tokens, chunked(g, 32), BF16)       # cumulates g
    want = _head_operands(*(x.swapaxes(2, 3) for x in (
        *tokens, chunk_log_decay(g, 32))), BF16)
    h = v.shape[2]
    for name, a, b in zip(("w", "u_v", "q_g", "k_d", "gamma", "a_q"), got,
                          want):
        if name != "gamma":
            a = a.swapaxes(2, 3)
        assert a.shape == b.shape, name
        assert a.dtype == (F32 if name in ("u_v", "gamma") else BF16), name
        assert _rel(a.astype(F32), b.astype(F32)) <= 1.6e-2, name
    assert got[4].shape == (1, 2, h, 1)
    assert got[0].shape == (1, 2, 32, h, 16) == got[2].shape == got[3].shape
    assert got[5].shape == (1, 2, 32, h, 32)


def test_the_counters_and_the_names_in_the_lowered_program(monkeypatch):
    """Bumped while tracing, one a call site: on the CPU a site with one
    decay a head is counted as a scan and a per-head site and as no kernel
    of any kind, and its program holds no ``pallas_call``; told it is on a
    TPU at shapes the rule admits, the site takes both kernel pairs, whose
    four calls are named under the scan's scope, forward and backward."""
    from byteps_tpu.ops import gdn_chunk, kda_recurrence

    names = (gdn_chunk.FWD_NAME, gdn_chunk.BWD_NAME, kda_recurrence.FWD_NAME,
             kda_recurrence.BWD_NAME)
    *args, weight = _inputs(32)

    def text():
        return jax.jit(jax.grad(
            lambda *a: (kda_attention(*a, chunk=16, sub=16, dtype=F32)
                        * weight).sum(), argnums=(0, 1, 2, 3, 4))).lower(
                            *args).as_text(debug_info=True)

    def sites():
        return tuple(metrics.counter(name) for name in (
            SCAN_SITES, HEAD_SITES, HEAD_KERNEL_SITES,
            RECURRENCE_KERNEL_SITES, KERNEL_SITES))

    before = sites()
    xla = text()
    assert sites() == tuple(np.add(before, (1, 1, 0, 0, 0)))
    assert "pallas_call" not in xla
    monkeypatch.setattr(la, "kda_form", lambda *shapes: "head_kernel")
    kernel = text()
    assert sites() == tuple(np.add(before, (2, 2, 1, 1, 0)))
    assert f"/jvp({GDN_SCAN_SCOPE})/jit(_fwd_impl)" in kernel
    assert f"/transpose(jvp({GDN_SCAN_SCOPE}))/jit(_bwd_impl)" in kernel
    for name in names:
        assert f'"{name}/pallas_call"' in kernel, name


def test_rows_written_out_are_the_rows_in_a_loop(monkeypatch):
    """Compiled, the walks' loops are written out when the kernel is
    lowered; interpreted (every other case of this file) they stay loops.
    The same body either way: operands and gradients equal to float32's last
    digits (XLA fuses a straight line otherwise than a loop's body)."""
    from byteps_tpu.ops import gdn_chunk

    q, k, v, g, beta, _ = _inputs(32, d_k=16, d_v=16)
    tokens = [chunked(x, 16) for x in (q, k, v, beta, g)]

    def run(written_out):
        # under the jit, whose cache knows nothing of the patch
        monkeypatch.setattr(gdn_chunk, "_written_out",
                            lambda interpret: written_out)
        ops = gdn_chunk._fwd_impl.__wrapped__(*tokens, jnp.dtype(F32), True)
        return (*ops, *gdn_chunk._bwd_impl.__wrapped__(
            *tokens, tuple(jnp.ones_like(x) for x in ops), True))

    for got, want in zip(run(True), run(False)):
        assert bool(jnp.isfinite(got).all())
        assert _rel(got, want) <= 1e-6
