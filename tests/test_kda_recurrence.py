"""The scan over the chunks as the Pallas pair of ``ops/kda_recurrence.py``
(interpret mode: its own code on the CPU) against ``jax.grad`` of the XLA
body ``_recurrence`` — values and every gradient — the whole scan through
the pair where the operand kernels feed it, and the trace-time counter
(the rule is ``kda_form``'s ``"kernel"`` answer, whose table is
``tests/test_kda_kernel.py``'s). ``tests/test_kimi_linear.py`` and ``tests/test_qwen3_next.py`` hold
the whole scan to the token-by-token recurrence."""

import jax
import jax.numpy as jnp
import pytest

import byteps_tpu.parallel.linear_attention as la
from byteps_tpu.monitor import metrics
from byteps_tpu.ops.kda_recurrence import (BWD_NAME, FWD_NAME, _tiling,
                                           recurrence)
from byteps_tpu.parallel.linear_attention import (
    KERNEL_SITES, RECURRENCE_KERNEL_SITES, SCAN_SCOPE, SCAN_SITES,
    _recurrence, kda_attention, kda_form)

BF16, F32 = jnp.bfloat16, jnp.float32
NAMES = ("state", "w", "u_v", "q_g", "k_d", "gamma", "a_q")
# float32 round-off of a few dozen sums in another order | two roundings to
# bf16 of neighbouring numbers (the XLA body rounds a cotangent where the
# kernel keeps float32 until the product)
TOLERANCE = {F32: 5e-6, BF16: 2e-2}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _operands(g, h, dtype, *, b=2, chunk=16, d_k=8, d_v=6, rank_one=False,
              seed=0, decay=0.0):
    """(state, w, u_v, q_g, k_d, gamma, a_q) and the cotangents of (state',
    o). Keys and values of different widths, so that a transposed state
    read the wrong way round has the wrong shape."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = jax.random.normal
    args = (n(keys[0], (b, h, d_k, d_v)),
            (n(keys[1], (g, b, h, chunk, d_k)) * d_k ** -0.5).astype(dtype),
            n(keys[2], (g, b, h, chunk, d_v)),
            (n(keys[3], (g, b, h, chunk, d_k)) * d_k ** -0.5).astype(dtype),
            (n(keys[4], (g, b, h, chunk, d_k)) * d_k ** -0.5).astype(dtype),
            jax.nn.sigmoid(decay + n(keys[5], (g, b, h,
                                               1 if rank_one else d_k))),
            (n(keys[6], (g, b, h, chunk, chunk)) * chunk ** -0.5).astype(
                dtype))
    return args, (n(keys[7], (b, h, d_k, d_v)),
                  n(keys[8], (g, b, h, chunk, d_v)))


def _pair(dtype, **tiling):
    """The kernel pair under ``_recurrence``'s contract, the chunk leading
    and heads before tokens, [g, b, h, C, d]; its own operands lie [b, n, C,
    h, d] as the operand kernels leave them."""
    def turned(x):
        return x.transpose(1, 0, 3, 2, 4)

    def scan(state, w, u_v, q_g, k_d, gamma, a_q):
        state, o = recurrence(state, turned(w), turned(u_v), turned(q_g),
                              turned(k_d), jnp.moveaxis(gamma, 0, 1),
                              turned(a_q), dtype, **tiling)
        return state, turned(o)

    return scan


def _value_and_grads(scan, args, cts):
    """((state', o), the seven gradients) of ``scan(*args)`` under the
    cotangents ``cts``."""
    def scalar(*a):
        state, o = scan(*a)
        return (state * cts[0]).sum() + (o * cts[1]).sum(), (state, o)

    (_, out), grads = jax.jit(jax.value_and_grad(
        scalar, argnums=tuple(range(7)), has_aux=True))(*args)
    return out, grads


def _rel(got, want):
    got, want = (jnp.asarray(x, F32) for x in (got, want))
    return float(jnp.abs(got - want).max()) / max(
        float(jnp.abs(want).max()), 1e-30)


def _assert_close(got, want, tolerance, names):
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.isfinite(a.astype(F32)).all()), name
        assert _rel(a, b) <= tolerance, (name, _rel(a, b))


# g | heads | heads a grid step (None: ``_head_block``'s own) | chunks an
# inner group (None: the largest divisor of g up to 16) | one decay a head:
# a block that is every head, blocks that divide the heads, and one whose
# last grid step reaches past them; one inner group, and several
CASES = [(1, 3, None, None, False), (1, 16, 8, None, True),
         (4, 8, 8, 2, True), (4, 12, 8, None, False),
         (16, 3, None, None, True), (16, 16, 8, 4, False),
         (16, 20, 8, 8, False)]


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("g, heads, block, inner, rank_one", CASES)
def test_kernel_pair_is_jax_grad_of_the_xla_body(g, heads, block, inner,
                                                 rank_one, dtype):
    """(state', o) and the gradients of the state, w, u_v, q_g, k_d, gamma
    (of both ranks) and a_q."""
    args, cts = _operands(g, heads, dtype, rank_one=rank_one, seed=g)
    want = _value_and_grads(lambda *a: _recurrence(*a, dtype), args, cts)
    got = _value_and_grads(_pair(dtype, inner=inner, head_block=block),
                           args, cts)
    _assert_close(got[0], want[0], TOLERANCE[dtype], ("state'", "o"))
    _assert_close(got[1], want[1], TOLERANCE[dtype], NAMES)
    assert got[1][5].shape[-1] == (1 if rank_one else 8)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("g", [1, 4])
def test_two_groups_in_a_chain_are_one_group_of_twice_the_length(g, dtype):
    """State out -> state in: the kernel's own two calls against its one
    call over 2g chunks in two inner groups (the same arithmetic in the
    same order), and against the XLA body."""
    args, cts = _operands(2 * g, 8, dtype, seed=7)

    def chained(scan):
        def run(state, *xs):
            state, first = scan(state, *(x[:g] for x in xs))
            state, second = scan(state, *(x[g:] for x in xs))
            return state, jnp.concatenate([first, second])
        return run

    kernel = _pair(dtype, inner=g, head_block=8)
    whole = _value_and_grads(kernel, args, cts)
    chain = _value_and_grads(chained(kernel), args, cts)
    _assert_close(chain[0], whole[0], 1e-6, ("state'", "o"))
    _assert_close(chain[1], whole[1], 1e-6, NAMES)
    want = _value_and_grads(lambda *a: _recurrence(*a, dtype), args, cts)
    _assert_close(chain[1], want[1], TOLERANCE[dtype], NAMES)


def test_the_carried_state_is_never_rounded():
    """bf16 operands, 16 chunks of slow decay and small updates: the state
    the kernel carries is the XLA body's float32 one to float32 round-off
    (the products' operands are the same roundings of the same numbers),
    while a body that rounds the carried state to bf16 after every chunk —
    the same products otherwise — is three orders of magnitude off that."""
    args, _ = _operands(16, 4, BF16, seed=3, decay=6.0)
    args = (args[0], args[1], args[2] * 1e-2, *args[3:])

    def rounded(state, *xs):
        def body(state, chunk):
            state, o = _recurrence(state, *(x[None] for x in chunk), BF16)
            return state.astype(BF16).astype(F32), o[0]
        return jax.lax.scan(body, state, xs)

    want, _ = jax.jit(lambda *a: _recurrence(*a, BF16))(*args)
    got, _ = jax.jit(_pair(BF16))(*args)
    wrong, _ = jax.jit(rounded)(*args)
    assert got.dtype == F32
    assert _rel(got, want) <= 1e-6
    assert _rel(wrong, want) > 1e-3


@pytest.mark.parametrize("chunks, heads, tiling", [
    (256, 32, (16, 32)),    # a Kimi-Linear layer whole: 16 groups of 16
    (4, 32, (4, 32)),       # fewer chunks than an inner group holds
    (8, 32, (8, 32)), (32, 32, (16, 32)), (6, 32, (6, 32)), (34, 32, (2, 32)),
    (256, 64, (16, 32)),    # 16 states of 64 KB a head: 32 heads in 32 MB
    (256, 3, (16, 3)), (256, 40, (16, 32)),   # the last step past the heads
])
def test_the_tiling_is_sized_from_the_chunks_and_the_heads(chunks, heads,
                                                           tiling):
    w = jax.ShapeDtypeStruct((1, chunks, 32, heads, 128), BF16)
    assert _tiling(w, w, None, None) == tiling
    assert _tiling(w, w, 2, 8) == (2, 8)


@pytest.mark.parametrize("s", [
    80,     # 5 chunks, the last closed by zero tokens: one inner group of 5
    512,    # 32 chunks: two inner groups of 16
    272,    # 17 chunks, a prime: 17 inner groups of one
])
def test_the_scan_through_the_kernel_pair_is_the_scan_through_the_xla_body(
        monkeypatch, s):
    """``kda_attention`` whole, float32 ``dtype``, chunks of 16: ``o`` and
    the gradients of q, k, v, g and beta in the kernel form — the operand
    kernels and, over their operands as they lie, the recurrence pair in
    one call with the tiling it derives itself — against the XLA form's
    groups of ``_recurrence``."""
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    h, d_k, d_v = 8, 8, 6

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    args = (unit(jax.random.normal(keys[0], (1, s, h, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(keys[1], (1, s, h, d_k))),
            jax.random.normal(keys[2], (1, s, h, d_v)),
            -jax.nn.softplus(jax.random.normal(keys[3], (1, s, h, d_k))),
            jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, h))))
    weight = jax.random.normal(keys[5], (1, s, h, d_v))

    def run():
        return jax.jit(jax.value_and_grad(
            lambda *a: (kda_attention(*a, chunk=16, sub=4, dtype=F32)
                        * weight).sum(), argnums=(0, 1, 2, 3, 4)))(*args)

    want = run()
    k0 = metrics.counter(RECURRENCE_KERNEL_SITES)
    monkeypatch.setattr(la, "kda_form", lambda *shapes: "kernel")
    got = run()
    assert metrics.counter(RECURRENCE_KERNEL_SITES) == k0 + 1
    assert abs(float(got[0]) - float(want[0])) <= 2e-5 * abs(float(want[0]))
    _assert_close(got[1], want[1], 2e-5, ("q", "k", "v", "g", "beta"))


@pytest.mark.parametrize("per_head", [False, True],
                         ids=["a_channel", "a_head"])
def test_the_counter_and_the_names_in_the_lowered_program(monkeypatch,
                                                          per_head):
    """Bumped while tracing, one a call site. On the CPU ``kda_form``
    answers ``"xla"``: a scan site, no kernel site, neither name in the
    program. Told the backend is a ``tpu``, it takes the kernels at these
    shapes (bf16, 8 heads of 128 x 128, 64 chunks of 16) — one decay a
    channel — and both calls are named under the scan's scope, forward and
    backward, with no scan over groups around them; one decay a head, whose
    operands XLA builds a few chunks at a time, keeps ``_recurrence``."""
    h, d, s = 8, 128, 1024
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    args = (jax.random.normal(keys[0], (1, s, 1 if per_head else h, d)),
            jax.random.normal(keys[1], (1, s, 1 if per_head else h, d)),
            jax.random.normal(keys[2], (1, s, h, d)),
            -jax.nn.softplus(jax.random.normal(
                keys[3], (1, s, h) if per_head else (1, s, h, d))),
            jax.nn.sigmoid(jax.random.normal(keys[4], (1, s, h))))

    def text():
        return jax.jit(jax.grad(
            lambda *a: kda_attention(*a, chunk=16, sub=4).sum(),
            argnums=(0, 1, 2, 3, 4))).lower(*args).as_text(debug_info=True)

    def sites():
        return tuple(metrics.counter(name) for name in (
            SCAN_SITES, KERNEL_SITES, RECURRENCE_KERNEL_SITES))

    s0, o0, k0 = sites()
    xla = text()
    assert sites() == (s0 + 1, o0, k0)
    assert FWD_NAME not in xla and BWD_NAME not in xla
    monkeypatch.setattr(la, "kda_form",
                        lambda backend, *shapes: kda_form("tpu", *shapes))
    kernel = text()
    if per_head:
        assert sites() == (s0 + 2, o0, k0)
        assert FWD_NAME not in kernel and BWD_NAME not in kernel
        return
    assert sites() == (s0 + 2, o0 + 1, k0 + 1)
    assert f"/jvp({SCAN_SCOPE})/jit(_scan_fwd)" in kernel
    assert f"/transpose(jvp({SCAN_SCOPE}))/jit(_scan_bwd)" in kernel
    # the XLA form's scan over groups sits right under the scope
    assert f"jvp({SCAN_SCOPE})/while" in xla
    assert f"jvp({SCAN_SCOPE})/while" not in kernel
    for name in (FWD_NAME, BWD_NAME):
        assert f'"{name}/pallas_call"' in kernel, name
