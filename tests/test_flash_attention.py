"""Pallas flash attention tests (interpret mode on CPU — the kernel code
path itself, not a shadow implementation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from byteps_tpu.ops import flash_attention
from byteps_tpu.parallel.ring_attention import full_attention

# the module: the package re-exports a function of the same name
fa = importlib.import_module("byteps_tpu.ops.flash_attention")


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


@pytest.fixture
def retraced():
    """The kernels' jitted callers forget what they traced, before and
    after: a test that patches what a kernel's body calls must not meet,
    or leave, a trace of the other body."""
    def forget():
        fa._flash_fwd_impl.clear_cache()
        fa._flash_bwd_impl.clear_cache()
    forget()
    yield forget
    forget()


@pytest.mark.parametrize("bodies, pin", [
    ("one_masked_body",
     "87ce996fa8e50d2dd795149e030aa8b9f4c1dd8b238dae133638605f1ee2b108"),
    ("two_bodies",
     "03a1ae93db44fdd83727f08c3f1facfbc6bfa82b94c0f7d6c8041635ba96d8b8"),
])
def test_equal_widths_lower_as_before_the_second_width(monkeypatch, retraced,
                                                       bodies, pin):
    """PR 39 gave the kernels a value width of their own. With v as wide
    as q and k the three kernels lower to the text they lowered to at
    ``3f4a582`` (interpret mode: plain HLO, no source position in it):
    forward, dQ and dK/dV at 1 x 256 x 2 x 64, causal, blocks of 128. The
    pair is what a shape over the fused backward's budget still runs.

    Since PR 62 a kernel holds its block's body twice, under a branch on
    the block's position (``_interior``), so the lowered text gained a
    branch and its pin moved: ``03a1ae93`` is the text of PR 62's own
    commit, the child of ``e72435c``. The pin of ``3f4a582`` stays beside
    it: with ``_interior`` answering ``False`` no second body is built,
    and what is left — the body every edge block runs — is that commit's
    text to the byte."""
    import hashlib

    monkeypatch.setattr(fa, "backward_form", lambda *shape: "pair")
    if bodies == "one_masked_body":
        monkeypatch.setattr(fa, "_interior", lambda *block: False)
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    text = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, True, None, 128, 128).sum(),
        argnums=(0, 1, 2))).lower(x, x, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == pin


# s_q, s_k, block_q, block_k, causal, window, interior blocks as the
# backward counts them (queries checked) | as the forward does
INTERIOR_CASES = {
    "causal": (128, 128, 16, 32, True, None, 12, 12),
    "causal_wide_query_blocks": (128, 128, 32, 16, True, None, 12, 12),
    "causal_square_blocks": (128, 128, 32, 32, True, None, 6, 6),
    "one_block": (32, 32, 32, 32, True, None, 0, 0),
    "not_causal": (64, 96, 16, 32, False, None, 12, 12),
    "not_causal_unaligned": (50, 70, 16, 32, False, None, 6, 8),
    "window_shorter_than_a_block": (128, 128, 16, 32, True, 10, 0, 0),
    "window_of_a_block": (128, 128, 32, 32, True, 32, 0, 0),
    # the nearest corners an interior block's farthest pair can have lie
    # bq + bk - 2 = 46 apart; on this grid they lie 47 apart
    "window_one_short_of_the_rule": (128, 128, 16, 32, True, 46, 0, 0),
    "window_one_short_of_an_interior_block": (128, 128, 16, 32, True, 47,
                                              0, 0),
    "window_of_the_first_interior_block": (128, 128, 16, 32, True, 48, 3, 3),
    "window_of_three_blocks": (256, 256, 32, 32, True, 96, 13, 13),
    "rectangular_causal_more_keys": (96, 160, 16, 32, True, None, 6, 6),
    "rectangular_causal_more_queries": (160, 96, 16, 32, True, None, 18, 18),
    "unaligned": (100, 100, 16, 32, True, None, 6, 9),
    "unaligned_window": (100, 100, 16, 32, True, 60, 2, 3),
}


@pytest.mark.parametrize("case", sorted(INTERIOR_CASES))
def test_a_block_is_interior_exactly_where_its_mask_is_all_true(case):
    """``_interior`` from a block's corners against ``_mask`` of the block,
    at every position of the grid, the way the forward asks (no query
    length) and the way the backward does: it never spares a block a mask
    could change, and it engages wherever it could."""
    s_q, s_k, bq, bk, causal, window, checked, unchecked = (
        INTERIOR_CASES[case])
    for seq_q, count in ((s_q, checked), (None, unchecked)):
        found = 0
        for q_start in range(0, s_q, bq):
            for k_start in range(0, s_k, bk):
                args = (q_start, k_start, bq, bk, seq_q, s_k, causal, window)
                interior = bool(fa._interior(*args))
                assert interior == bool(fa._mask(*args).all()), args
                found += interior
        assert found == count, seq_q


@pytest.mark.parametrize("window", [None, 160], ids=["causal", "window_160"])
def test_flash_four_by_four_blocks_match_full(rng, window):
    """Forward and gradients over 4 x 4 blocks of 64, where six blocks (one
    under the window, at the band's corner) run with no mask formed and
    the diagonal's and the band's edge run masked, against the XLA form."""
    q, k, v = _qkv(rng, b=1, s=256, h=2, d=32)
    assert fa._interior(192, 0, 64, 64, 256, 256, True, None)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, None, 64, 64, None, window)

    def full(q, k, v):
        return full_attention(q, k, v, causal=True, window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(full(q, k, v)),
                               rtol=2e-5, atol=2e-6)
    g1 = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: (full(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# batch, s_q, s_k, query heads, key heads, key width, value width, causal,
# window, dtype; blocks of 64 queries x 128 keys throughout
FUSED_CASES = {
    "width_64": (2, 256, 256, 2, 2, 64, 64, True, None, jnp.bfloat16),
    "width_128": (1, 256, 256, 2, 2, 128, 128, True, None, jnp.bfloat16),
    "keys_192_values_128": (1, 256, 256, 2, 2, 192, 128, True, None,
                            jnp.bfloat16),
    "8_heads_over_2": (1, 256, 256, 8, 2, 64, 64, True, None, jnp.bfloat16),
    "window_shorter_than_a_block": (1, 512, 512, 4, 2, 64, 64, True, 40,
                                    jnp.bfloat16),
    "window_longer_than_a_block": (1, 512, 512, 4, 1, 64, 64, True, 200,
                                   jnp.bfloat16),
    "unaligned": (1, 300, 300, 4, 2, 64, 64, True, None, jnp.bfloat16),
    "rectangular_causal": (2, 256, 384, 2, 2, 64, 64, True, None,
                           jnp.bfloat16),
    "rectangular_not_causal": (1, 256, 384, 4, 2, 64, 64, False, None,
                               jnp.bfloat16),
    "eight_by_four_blocks": (1, 512, 512, 2, 1, 64, 64, True, None,
                             jnp.bfloat16),
    "float32": (1, 300, 300, 2, 2, 32, 32, True, None, jnp.float32),
    # a window of 191 keys or more has interior blocks at 64 x 128: these
    # walk the unmasked body and the masked one in all three kernels
    "window_with_interior_blocks": (1, 512, 512, 4, 2, 64, 64, True, 300,
                                    jnp.bfloat16),
    "window_with_interior_blocks_unaligned": (2, 600, 600, 2, 1, 64, 64,
                                              True, 384, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_backward_is_the_pair_to_the_last_bit(rng, monkeypatch, case):
    """``bps_flash_bwd`` against ``bps_flash_dq`` + ``bps_flash_dkv``: the
    same ``p`` and ``dS`` of a block, the same products, and a block's
    contributions added in the order the pair adds them, so dQ, dK and dV
    are equal, not close. No sum's order had to change."""
    b, s_q, s_k, h, h_kv, d, d_v, causal, window, dtype = FUSED_CASES[case]
    q = jnp.asarray(rng.standard_normal((b, s_q, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s_k, h_kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s_k, h_kv, d_v)), dtype)
    w = jnp.asarray(rng.standard_normal((b, s_q, h, d_v)), jnp.float32)
    monkeypatch.setattr(fa, "_blocks", lambda *shape: (64, 128))
    assert fa.backward_form(s_q, s_k, d, d_v, h // h_kv, window) == "fused"

    def grads(form):
        monkeypatch.setattr(fa, "backward_form", lambda *shape: form)
        return jax.grad(
            lambda q, k, v: (flash_attention(q, k, v, causal, window=window)
                             .astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    pair, fused = grads("pair"), grads("fused")
    for name, want, got in zip(("dq", "dk", "dv"), pair, fused):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.abs(np.asarray(want, np.float32)).max() > 0.1, name
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32), name)


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_interior_blocks_change_no_bit(rng, monkeypatch, retraced, case):
    """With ``_interior`` answering ``False`` every live block runs the one
    masked body, the kernels as they were before PR 62. Out, logsumexp,
    dQ, dK and dV with the rule in place are those to the last bit: on an
    interior block the mask is all true and the select returns its
    operand. At a scale of 1 / 8: interpreted, a body is a computation
    XLA's CPU backend fuses by itself, and with no select between them it
    contracts ``s * scale - m`` into one fused multiply-add, which rounds
    once where the masked body rounds twice unless the product is exact
    (at 1 / sqrt(128) a third of the outputs differ by an ulp). Mosaic
    contracts nothing: on the chip the bits are equal at every width
    (PERF.md section 6, PR 62)."""
    b, s_q, s_k, h, h_kv, d, d_v, causal, window, dtype = FUSED_CASES[case]
    q = jnp.asarray(rng.standard_normal((b, s_q, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s_k, h_kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s_k, h_kv, d_v)), dtype)
    g = jnp.asarray(rng.standard_normal((b, s_q, h, d_v)), dtype)
    monkeypatch.setattr(fa, "_blocks", lambda *shape: (64, 128))

    def run():
        out, res = fa._flash_fwd(q, k, v, causal, 0.125, None, None, None,
                                 window)
        return (out, res[-1]) + fa._flash_bwd(causal, 0.125, None, None,
                                              None, window, res, g)

    got = run()
    retraced()
    monkeypatch.setattr(fa, "_interior", lambda *block: False)
    want = run()
    for name, a, b_ in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b_, np.float32), name)


# s_q, s_k, key width, value width, query heads a key head, window: what
# each cell's attention would ask (BERT's and Keye's never do: the XLA
# form at s 128, ``ops/sparse_flash.py``)
BACKWARD_FORMS = {
    "gpt2-124m.collective.1chip": ((1024, 1024, 64, 64, 1, None), "fused"),
    "gpt2-124m.ps.1chip": ((1024, 1024, 64, 64, 1, None), "fused"),
    "gpt2-124m.ps-bucketed.1chip": ((1024, 1024, 64, 64, 1, None), "fused"),
    "bert-large.collective.4chip": ((128, 128, 64, 64, 1, None), "fused"),
    "bert-large.collective.1chip": ((128, 128, 64, 64, 1, None), "fused"),
    "olmoe-1b-7b.collective-moe.1chip": (
        (4096, 4096, 128, 128, 1, None), "fused"),
    "keye-vl-2.0-30b-a3b.collective-dsa.1chip": (
        (8192, 8192, 128, 128, 8, None), "fused"),
    "ouro-2.6b.collective-loop.1chip": (
        (4096, 4096, 128, 128, 1, None), "fused"),
    "kimi-linear-48b-a3b.collective-kda.1chip": (
        (8192, 8192, 192, 128, 1, None), "fused"),
    "joyai-llm-flash.collective-mtp.1chip": (
        (8192, 8192, 192, 128, 1, None), "fused"),
    "laguna-xs.2.collective-swa.1chip, global": (
        (8192, 8192, 128, 128, 6, None), "fused"),
    "laguna-xs.2.collective-swa.1chip, windowed": (
        (8192, 8192, 128, 128, 8, 512), "fused"),
    "qwen3-next-80b-a3b.collective-gdn.1chip": (
        (16384, 16384, 256, 256, 8, None), "fused"),
    "zaya1-8b.collective-cca.1chip": (
        (16384, 16384, 128, 128, 4, None), "fused"),
    "mellum2-12b-a2.5b.collective-swa-moe.1chip, global": (
        (8192, 8192, 128, 128, 8, None), "fused"),
    "mellum2-12b-a2.5b.collective-swa-moe.1chip, windowed": (
        (8192, 8192, 128, 128, 8, 1024), "fused"),
    # 32,768 keys 256 wide: 64 MiB of float32 dK and dV, 64 more of their
    # output blocks' two buffers, against a limit of 96
    "too long for the limit": ((32768, 32768, 256, 256, 8, None), "pair"),
    "the keys alone decide": ((512, 32768, 256, 256, 8, None), "pair"),
    # the rule's edge at each width: the last length that reads "fused"
    # (tests/test_chip_compile.py compiles each) and the next block's
    "64, the last": ((37888, 37888, 64, 64, 1, None), "fused"),
    "64, one block on": ((38912, 38912, 64, 64, 1, None), "pair"),
    "64 holds the lanes of 128": ((37888, 37888, 128, 128, 2, None), "fused"),
    "128, one block on": ((38912, 38912, 128, 128, 2, None), "pair"),
    "192 / 128, the last": ((24576, 24576, 192, 128, 1, None), "fused"),
    "192 / 128, one block on": ((25600, 25600, 192, 128, 1, None), "pair"),
    "256, the last": ((18432, 18432, 256, 256, 8, None), "fused"),
    "256, one block on": ((19456, 19456, 256, 256, 8, None), "pair"),
    # a window's 512 x 512 blocks leave the sums more room
    "windowed, the last": ((45568, 45568, 128, 128, 8, 512), "fused"),
    "windowed, one block on": ((46080, 46080, 128, 128, 8, 512), "pair"),
    # float32 operands: output blocks of twice the bytes
    "float32 at 256": ((16384, 16384, 256, 256, 8, None, 4), "pair"),
    "float32 at 128": ((16384, 16384, 128, 128, 4, None, 4), "fused"),
}


@pytest.mark.parametrize("site", sorted(BACKWARD_FORMS))
def test_backward_form_is_a_pure_function_of_the_shapes(site):
    shape, form = BACKWARD_FORMS[site]
    assert fa.backward_form(*shape) == form
