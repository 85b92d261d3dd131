"""``kda_attention`` chooses how a chunk's operands are computed — the
Pallas kernel pair of ``ops/kda_chunk.py`` or the XLA form — from the
backend and the shapes. The rule as a pure function, the kernel pair
(interpret mode: its own code on the CPU) against ``jax.grad`` of the XLA
form, the closed-form gradient of a unit-triangular inverse that the
kernel's backward pass rests on, and the trace-time counters.
``tests/test_kimi_linear.py`` holds both forms to the token-by-token
recurrence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.parallel.linear_attention as la
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.linear_attention import (
    KERNEL_SITES, SCAN_SCOPE, SCAN_SITES, _chunk_operands,
    _unit_lower_inverse, chunk_log_decay, chunked, kda_attention, kda_form)

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("args, form", [
    # the Kimi-Linear cell: 32 heads of 128 x 128, chunks of 32
    (("tpu", 32, 128, 128, BF16, 32), "kernel"),
    (("tpu", 32, 128, 128, BF16, 64), "kernel"),
    (("tpu", 8, 128, 128, BF16, 128), "kernel"),
    (("tpu", 32, 128, 128, BF16, 256), "xla"),     # wider than a row of lanes
    (("tpu", 32, 128, 128, BF16, 20), "xla"),      # no whole sublane groups
    (("tpu", 12, 128, 128, BF16, 32), "xla"),
    (("tpu", 32, 128, 128, F32, 32), "xla"),
    (("tpu", 32, 64, 64, BF16, 32), "xla"),
    (("tpu", 32, 128, 64, BF16, 32), "xla"),
    (("tpu", 32, 192, 128, BF16, 32), "xla"),
    (("cpu", 32, 128, 128, BF16, 32), "xla"),
    (("gpu", 32, 128, 128, BF16, 32), "xla"),
    (("cpu", 3, 8, 6, F32, 16), "xla"),            # the CPU tests' shapes
])
def test_the_rule_is_a_pure_function_of_backend_and_shapes(args, form):
    # one decay a channel, as many key heads as value heads
    assert kda_form(*args, False, args[1]) == form
    assert kda_form(*args[:4], np.dtype(args[4]), args[5], False,
                    args[1]) == form


def _inputs(s, b=1, h=8, d_k=8, d_v=6, strength=1.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(jax.random.normal(ks[0], (b, s, h, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], (b, s, h, d_k))),
            jax.random.normal(ks[2], (b, s, h, d_v)),
            -strength * jax.nn.softplus(jax.random.normal(
                ks[3], (b, s, h, d_k))),
            jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h))),
            jax.random.normal(ks[5], (b, s, h, d_v)))


def _rel(got, want):
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


@pytest.mark.parametrize("s, chunk, sub, strength", [
    (80, 32, 8, 1.0),      # the cell's chunks; 16 zero tokens close the last
    (80, 32, 8, 8.0),      # ... under decays past float32's e^-88
    (40, 16, 4, 1.0),
])
def test_kernel_gradients_are_jax_grad_of_the_xla_form(monkeypatch, s, chunk,
                                                       sub, strength):
    """Value and the gradients with respect to q, k, v, g and beta, float32
    ``dtype`` on both sides. 2e-5: the kernel forms every pair one by one
    and solves by substitution where the XLA form multiplies sub-chunks
    and inverts by doubling; nothing discrete."""
    *args, weight = _inputs(s, strength=strength)

    def run():
        return jax.jit(jax.value_and_grad(
            lambda *a: (kda_attention(*a, chunk=chunk, sub=sub, dtype=F32)
                        * weight).sum(), argnums=(0, 1, 2, 3, 4)))(*args)

    want = run()
    monkeypatch.setattr(la, "kda_form", lambda *shapes: "kernel")
    got = run()
    assert abs(float(got[0]) - float(want[0])) <= 2e-5 * abs(float(want[0]))
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        assert _rel(g, w) <= 2e-5


def test_the_kernel_s_operands_are_the_xla_form_s_in_bf16():
    """The six operands one by one, ``dtype`` bf16 as on the chip: what is a
    matmul operand of the recurrence leaves in bf16, ``U_v``, ``e^{G_C}``
    and the pairs in float32. 1.6e-2: two roundings to bf16 of the same
    number's neighbours (the XLA form rounds ``T`` and ``beta b`` before
    their product, the kernel its result)."""
    from byteps_tpu.ops.kda_chunk import chunk_operands

    q, k, v, g, beta, _ = _inputs(64, d_k=16, d_v=16)
    tokens = [chunked(x, 32) for x in (q, k, v, beta)]
    got = chunk_operands(*tokens, chunked(g, 32), 8, BF16)   # cumulates g
    want = _chunk_operands(*(x.swapaxes(2, 3) for x in (
        *tokens, chunk_log_decay(g, 32))), 8, BF16)
    for name, a, b in zip(("w", "u_v", "q_g", "k_d", "gamma", "a_q"), got,
                          want):
        if name != "gamma":
            a = a.swapaxes(2, 3)
        assert a.dtype == (F32 if name in ("u_v", "gamma", "a_q") else BF16)
        assert _rel(a.astype(F32), b.astype(F32)) <= 1.6e-2, name


def test_closed_form_gradient_of_the_unit_lower_inverse():
    """``T = (I + A)^-1``: ``dA = -T^T dT T^T`` restricted to below the
    diagonal, against autodiff through the five doubling steps."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(np.tril(rng.standard_normal((3, 32, 32)), -1) * 0.3, F32)
    ct = jnp.asarray(rng.standard_normal((3, 32, 32)), F32)
    t, vjp = jax.vjp(_unit_lower_inverse, a)
    np.testing.assert_allclose(
        np.asarray(t @ (jnp.eye(32) + a)), np.broadcast_to(np.eye(32),
                                                           a.shape),
        atol=1e-5)
    t_t = t.swapaxes(-1, -2)
    closed = jnp.tril(-(t_t @ ct @ t_t), -1)
    assert _rel(closed, jnp.tril(vjp(ct)[0], -1)) <= 1e-5


def test_the_counters_and_the_names_in_the_lowered_program(monkeypatch):
    """Bumped while tracing, one a call site: on the CPU every site is
    counted as a scan and none as a kernel; told it may, every site takes
    the kernel, whose two calls are named under the scan's scope, forward
    and backward."""
    from byteps_tpu.ops.kda_chunk import BWD_NAME, FWD_NAME

    *args, weight = _inputs(32)

    def text():
        return jax.jit(jax.grad(
            lambda *a: (kda_attention(*a, chunk=16, sub=4, dtype=F32)
                        * weight).sum(), argnums=(0, 1, 2, 3, 4))).lower(
                            *args).as_text(debug_info=True)

    def sites():
        return metrics.counter(SCAN_SITES), metrics.counter(KERNEL_SITES)

    s0, k0 = sites()
    xla = text()
    assert sites() == (s0 + 1, k0)
    assert FWD_NAME not in xla and BWD_NAME not in xla
    monkeypatch.setattr(la, "kda_form", lambda *shapes: "kernel")
    kernel = text()
    assert sites() == (s0 + 2, k0 + 1)
    # both calls under the scan's scope, outside the groups' scan
    assert f"/jvp({SCAN_SCOPE})/jit(_fwd_impl)" in kernel
    assert f"/transpose(jvp({SCAN_SCOPE}))/jit(_bwd_impl)" in kernel
    for name in (FWD_NAME, BWD_NAME):
        assert f'"{name}/pallas_call"' in kernel, name
    # the pair tensor is the XLA form's alone
    assert "x4x4x8xf32" in xla and "x4x4x8xf32" not in kernel
