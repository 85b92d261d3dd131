"""Pallas flash attention tests (interpret mode on CPU — the kernel code
path itself, not a shadow implementation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel.ring_attention import full_attention


def _qkv(rng, b=2, s=64, h=3, d=32, dtype=jnp.float32):
    def one():
        return jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    return one(), one(), one()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full(rng, causal):
    q, k, v = _qkv(rng)
    want = full_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, None, 32, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_flash_unaligned_seq(rng):
    """Sequence length not a multiple of the block: padding keys must not
    contaminate the softmax."""
    q, k, v = _qkv(rng, s=50)
    want = full_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, None, 32, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_flash_bf16(rng):
    q, k, v = _qkv(rng, dtype=jnp.bfloat16)
    want = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    got = flash_attention(q, k, v, True, None, 32, 32)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_flash_gradients(rng):
    q, k, v = _qkv(rng, b=1, s=32, h=2, d=16)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, True, None, 16, 16) ** 2).sum()

    def full_loss(q, k, v):
        return (full_attention(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_as_ulysses_inner(rng):
    """flash_attention plugs into ulysses_attention as the inner kernel."""
    from jax.sharding import Mesh

    from byteps_tpu.parallel.ulysses import ulysses_attention_sharded

    q, k, v = _qkv(rng, h=8, d=16)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("sp",))

    def inner(q, k, v, *, causal, scale):
        return flash_attention(q, k, v, causal, scale, 32, 32)

    got = ulysses_attention_sharded(q, k, v, mesh, causal=True,
                                    attn_fn=inner)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multiblock(rng, causal):
    """Backward across several bwd-kernel blocks and unaligned tails
    (seq 600 -> 3 dq blocks x 2 dkv blocks with padding)."""
    q, k, v = _qkv(rng, b=1, s=600, h=2, d=32)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal) ** 2).sum()

    def full_loss(q, k, v):
        return (full_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_gradients_cross_attention_shapes(rng):
    """seq_q != seq_k exercises independent q/k padding in the backward."""
    q, _, _ = _qkv(rng, b=1, s=100, h=2, d=16)
    _, k, v = _qkv(rng, b=1, s=260, h=2, d=16)

    g1 = jax.grad(lambda *a: (flash_attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (full_attention(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_gradients_causal_rectangular(rng):
    """causal + seq_q != seq_k: block-skip predicates combined with
    asymmetric q/k padding."""
    q, _, _ = _qkv(rng, b=1, s=100, h=2, d=16)
    _, k, v = _qkv(rng, b=1, s=260, h=2, d=16)

    g1 = jax.grad(lambda *a: (flash_attention(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (full_attention(*a, causal=True) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_sliding_window_matches_masked_reference(rng):
    """window=w equals full attention with an explicit band mask, forward
    and gradients."""
    b, s, h, d, w = 1, 300, 2, 16, 64
    q, k, v = _qkv(rng, b=b, s=s, h=h, d=d)

    def ref(q, k, v):
        scale = 1.0 / (d ** 0.5)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        pos_q = jnp.arange(s)[:, None]
        pos_k = jnp.arange(s)[None, :]
        mask = (pos_q >= pos_k) & (pos_q - pos_k < w)
        sc = jnp.where(mask[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    got = flash_attention(q, k, v, True, None, 64, 64, None, w)
    want = ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    g1 = jax.grad(lambda *a: (flash_attention(
        *a, True, None, 64, 64, None, w) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(q, k,
                                                                      v)
    for a_, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_flash_window_requires_causal(rng):
    q, k, v = _qkv(rng, b=1, s=32, h=1, d=16)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 16, 16, None, 8)


@pytest.mark.parametrize("backend,asked,want", [
    ("tpu", None, False),   # the chip path can never take the interpreter
    ("cpu", None, True),    # what lets these tests run the kernel code
    ("cpu", False, False),  # a measurement refuses interpretation outright
    ("tpu", True, True),
])
def test_interpret_resolution(monkeypatch, backend, asked, want):
    from byteps_tpu.ops.flash_attention import _resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _resolve_interpret(asked) is want


def test_equal_widths_lower_as_before_the_second_width():
    """PR 39 gave the kernels a value width of their own. With v as wide
    as q and k the three kernels lower to the text they lowered to at
    ``3f4a582`` (interpret mode: plain HLO, no source position in it):
    forward, dQ and dK/dV at 1 x 256 x 2 x 64, causal, blocks of 128."""
    import hashlib

    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    text = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128).sum(),
        argnums=(0, 1, 2))).lower(x, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "87ce996fa8e50d2dd795149e030aa8b9f4c1dd8b238dae133638605f1ee2b108")
