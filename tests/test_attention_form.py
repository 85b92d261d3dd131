"""``full_attention`` chooses its form — the Pallas kernel or the two
einsums XLA fuses — from the backend and the operands' shapes. The rule as
a pure function, the kernel form against ``_single_device_attention`` at
the benchmark cells' head shapes (interpret mode: the kernel's own code on
the CPU), what the shapes that stay on the XLA form lower to, and the
trace-time counters."""

import contextlib
import functools
import hashlib
import importlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from byteps_tpu.monitor import metrics
from byteps_tpu.parallel.ring_attention import (
    FUSED_BACKWARD_SITES, INTERIOR_BLOCKS, KERNEL_SCOPE, KERNEL_SITES,
    LIVE_BLOCKS, XLA_SCOPE, XLA_SITES, _single_device_attention,
    attention_form, full_attention)
from tests.test_flash_attention import BACKWARD_FORMS

# the module: the package re-exports a function of the same name
ra = importlib.import_module("byteps_tpu.parallel.ring_attention")
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("args, form", [
    # the Ouro and OLMoE cells: 16 heads of 128, s 4096
    (("tpu", 4096, 4096, 128, True, BF16), "kernel"),
    # the GPT-2 cells: 12 heads of 64, s 1024
    (("tpu", 1024, 1024, 64, True, BF16), "kernel"),
    (("tpu", 512, 512, 128, True, BF16), "kernel"),
    # BERT-Large: s 128, not causal
    (("tpu", 128, 128, 64, False, BF16), "xla"),
    (("tpu", 4096, 4096, 128, False, BF16), "xla"),
    (("tpu", 256, 256, 128, True, BF16), "xla"),
    (("tpu", 4096, 256, 128, True, BF16), "xla"),
    (("tpu", 4096, 4096, 128, True, F32), "xla"),
    (("tpu", 4096, 4096, 80, True, BF16), "xla"),
    (("tpu", 4096, 4096, 256, True, BF16), "kernel"),     # PR 50
    (("tpu", 4096, 4096, 512, True, BF16), "xla"),
    (("cpu", 4096, 4096, 128, True, BF16), "xla"),
    (("gpu", 4096, 4096, 128, True, BF16), "xla"),
    (("cpu", 128, 128, 64, False, BF16), "xla"),
])
def test_the_rule_is_a_pure_function_of_backend_and_shapes(args, form):
    assert attention_form(*args) == form
    assert attention_form(*args[:5], np.dtype(args[5])) == form


@contextlib.contextmanager
def _kernel_form(monkeypatch):
    """Steer ``full_attention`` to the kernel off the chip: the rule reads
    a ``tpu`` backend, the kernel itself still sees the CPU and interprets.
    Blocks small enough that a cut sequence is a few of them."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    rule = attention_form
    with monkeypatch.context() as m:
        m.setattr(ra, "attention_form",
                  lambda backend, *rest: rule("tpu", *rest))
        m.setattr(ra, "KERNEL_MIN_SEQ", 128)
        m.setattr(fa, "_blocks", lambda s_q, s_k, d, window=None: (64, 128))
        yield


def _operands(rng, heads, head_dim, s=256, b=1):
    return tuple(jnp.asarray(rng.standard_normal((b, s, heads, head_dim)),
                             BF16) for _ in range(3))


def _assert_close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


class _Block(nn.Module):
    """Projections around the attention seam, as a model's block has."""
    heads: int
    head_dim: int

    @nn.compact
    def __call__(self, x, _=None):
        b, s, d = x.shape
        shape = (b, s, self.heads, self.head_dim)
        q, k, v = (nn.Dense(d, use_bias=False, dtype=BF16, name=n)(x)
                   .reshape(shape) for n in "qkv")
        out = full_attention(q, k, v, causal=True).reshape(b, s, d)
        return x + out.astype(x.dtype), None


class _Looped(nn.Module):
    """Ouro's form: one block under ``nn.remat``, scanned three times over
    the same parameters."""
    heads: int
    head_dim: int

    @nn.compact
    def __call__(self, x):
        block = nn.remat(_Block, prevent_cse=False)(self.heads,
                                                   self.head_dim)
        x, _ = nn.scan(lambda m, c, _: m(c), variable_broadcast="params",
                       split_rngs={"params": False}, length=3)(block, x, None)
        return x


@pytest.mark.parametrize("heads, head_dim", [(16, 128), (12, 64)],
                         ids=["16x128", "12x64"])
@pytest.mark.parametrize("wrap", ["plain", "checkpoint", "scan"])
def test_kernel_form_matches_the_xla_form(rng, monkeypatch, heads, head_dim,
                                          wrap):
    """Value and gradients at the cells' head shapes, bf16, causal."""
    if wrap == "scan":
        model = _Looped(heads, head_dim)
        x = jnp.asarray(rng.standard_normal((1, 256, heads * head_dim)), F32)
        params = model.init(jax.random.PRNGKey(0), x)

        def run():
            return jax.jit(jax.value_and_grad(
                lambda p: (model.apply(p, x) ** 2).mean()))(params)
    else:
        q, k, v = _operands(rng, heads, head_dim)
        w = jnp.asarray(rng.standard_normal(q.shape), F32)

        def run():
            # wrapped anew a run: jax.checkpoint keeps a function's trace
            attn = functools.partial(full_attention, causal=True)
            if wrap == "checkpoint":
                attn = jax.checkpoint(lambda *a: full_attention(
                    *a, causal=True))
            return jax.jit(jax.value_and_grad(
                lambda q, k, v: (attn(q, k, v).astype(F32) * w).sum(),
                argnums=(0, 1, 2)))(q, k, v)

    want = run()                      # the CPU backend: the XLA form
    before = metrics.counter(KERNEL_SITES)
    with _kernel_form(monkeypatch):
        got = run()
    assert metrics.counter(KERNEL_SITES) > before
    _assert_close(got[0], want[0], 2e-2)
    for g, r in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        _assert_close(g, r, 3e-2)


def _lowered(fn, *args, debug_info=False):
    return jax.jit(fn).lower(*args).as_text(debug_info=debug_info)


def test_a_bert_shaped_gradient_lowers_to_what_it_did(monkeypatch):
    """s 128, not causal: on a ``tpu`` backend too the rule keeps the XLA
    form, and what ``full_attention`` lowers to is, byte for byte, what
    ``_single_device_attention`` alone lowers to (the parent's text)."""
    x = jax.ShapeDtypeStruct((2, 128, 16, 64), BF16)

    def grad_of(attn):
        return jax.grad(lambda q, k, v: attn(q, k, v).astype(F32).sum(),
                        argnums=(0, 1, 2))

    before = grad_of(functools.partial(_single_device_attention,
                                       causal=False, scale=0.125))
    want = _lowered(before, x, x, x)
    rule = attention_form
    monkeypatch.setattr(ra, "attention_form",
                        lambda backend, *rest: rule("tpu", *rest))
    got = _lowered(grad_of(functools.partial(full_attention, causal=False)),
                   x, x, x)
    assert (hashlib.sha256(got.encode()).hexdigest()
            == hashlib.sha256(want.encode()).hexdigest())


def test_a_keye_tiny_gradient_never_reaches_full_attention():
    """The sparse-attention model has no call site of either form."""
    from byteps_tpu.models import KeyeTiny, keye_loss

    model = KeyeTiny()
    tokens = np.zeros((1, 32), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    sites = [metrics.counter(KERNEL_SITES), metrics.counter(XLA_SITES)]
    jax.jit(jax.grad(lambda p: keye_loss(model.apply(p, tokens), tokens))
            ).lower(params)
    assert [metrics.counter(KERNEL_SITES),
            metrics.counter(XLA_SITES)] == sites


def test_the_counters_count_one_site_per_attention_call(monkeypatch):
    """Bumped while tracing: a model of N unrolled layers counts N, a
    second call of the compiled step counts nothing."""
    from byteps_tpu.models import GPT2Small, lm_loss

    model = GPT2Small(vocab_size=64, num_layers=3, d_model=128, num_heads=2,
                      mlp_dim=128, max_len=256)
    tokens = np.zeros((1, 256), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)

    def sites():
        return metrics.counter(KERNEL_SITES), metrics.counter(XLA_SITES)

    def step():
        return jax.jit(jax.grad(
            lambda p: lm_loss(model.apply(p, tokens), tokens)))

    k0, x0 = sites()
    f = step()
    f(params)
    f(params)
    assert sites() == (k0, x0 + 3)
    with _kernel_form(monkeypatch):
        text = step().lower(params).as_text(debug_info=True)
    assert sites() == (k0 + 3, x0 + 3)
    assert KERNEL_SCOPE in text and XLA_SCOPE not in text


@pytest.mark.parametrize("limit, fused, kernels", [
    (None, 1, ("bps_flash_fwd", "bps_flash_bwd")),
    (0, 0, ("bps_flash_fwd", "bps_flash_dq", "bps_flash_dkv")),
], ids=["fits", "over_the_limit"])
def test_the_fused_backward_counter_counts_the_sites_that_take_it(
        rng, monkeypatch, limit, fused, kernels):
    """``bps_attention_fused_backward_sites_total`` beside the kernel
    sites' counter, at trace time: a site asks ``backward_form`` of its
    shapes, and what it lowers to is that form's kernels by name. On the
    CPU backend no site takes the kernel, and the counter stands."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    q, k, v = _operands(rng, 4, 64)
    k, v = k[:, :, :2], v[:, :, :2]

    def text():
        return _lowered(jax.grad(
            lambda q, k, v: full_attention(q, k, v, causal=True)
            .astype(F32).sum(), argnums=(0, 1, 2)), q, k, v, debug_info=True)

    def sites():
        return (metrics.counter(KERNEL_SITES),
                metrics.counter(FUSED_BACKWARD_SITES))

    if limit is not None:
        monkeypatch.setattr(fa, "_FUSED_VMEM_LIMIT", limit)
    k0, f0 = sites()
    text()
    assert sites() == (k0, f0)
    with _kernel_form(monkeypatch):
        lowered = text()
    assert sites() == (k0 + 1, f0 + fused)
    for name in ("bps_flash_fwd", "bps_flash_dq", "bps_flash_dkv",
                 "bps_flash_bwd"):
        assert (name in lowered) == (name in kernels), name


# (live, interior) blocks a head: what PERF.md section 6 (PR 62) prices
CENSUS = {
    "gpt2-124m.collective.1chip": (1, 0),
    "olmoe-1b-7b.collective-moe.1chip": (10, 6),
    "joyai-llm-flash.collective-mtp.1chip": (36, 28),
    "laguna-xs.2.collective-swa.1chip, windowed": (31, 0),
    "mellum2-12b-a2.5b.collective-swa-moe.1chip, windowed": (15, 0),
    "zaya1-8b.collective-cca.1chip": (136, 120),
}


@pytest.mark.parametrize(
    "site", sorted(site for site in BACKWARD_FORMS if "chip" in site))
def test_the_census_counts_the_blocks_no_mask_can_change(site):
    """``block_census`` of a cell's attention shape against the mask
    itself, block by block in numpy: a block is live where any pair of it
    survives, interior where every pair does."""
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    s_q, s_k, d, _, _, window = BACKWARD_FORMS[site][0]
    bq, bk = fa._clamped(s_q, s_k, *fa._blocks(s_q, s_k, d, window))
    live = interior = 0
    for q_start in range(0, s_q, bq):
        q_pos = q_start + np.arange(bq)[:, None]
        for k_start in range(0, s_k, bk):
            k_pos = k_start + np.arange(bk)[None, :]
            mask = (q_pos < s_q) & (k_pos < s_k) & (q_pos >= k_pos)
            if window is not None:
                mask &= q_pos - k_pos < window
            live += mask.any()
            interior += mask.all()
    assert fa.block_census(s_q, s_k, d, window) == (live, interior)
    assert CENSUS.get(site, (live, interior)) == (live, interior)


def test_the_block_counters_count_a_site_over_batch_and_heads(
        rng, monkeypatch):
    """``bps_attention_live_blocks_total`` and ``_interior_blocks_total``
    beside the kernel sites' counter, at trace time: ``block_census`` of
    the site's shapes times batch and query heads; an XLA site adds
    nothing."""
    q, k, v = _operands(rng, 4, 64, s=512, b=2)

    def blocks():
        return metrics.counter(LIVE_BLOCKS), metrics.counter(INTERIOR_BLOCKS)

    def trace():
        jax.eval_shape(lambda q, k, v: full_attention(q, k, v, causal=True),
                       q, k[:, :, :2], v[:, :, :2])

    before = blocks()
    trace()
    assert blocks() == before
    with _kernel_form(monkeypatch):
        trace()
    # 8 x 4 blocks of 64 x 128: 20 on or under the diagonal, 12 wholly under
    assert blocks() == (before[0] + 2 * 4 * 20, before[1] + 2 * 4 * 12)


def test_each_form_is_named_in_the_lowered_program(rng, monkeypatch):
    """``bps.attn.xla`` / ``bps.attn.kernel`` on the forward and on the
    backward pass's ops: what a device trace sums by."""
    q, k, v = _operands(rng, 2, 64)

    def text():
        return _lowered(jax.grad(
            lambda q, k, v: full_attention(q, k, v, causal=True)
            .astype(F32).sum(), argnums=(0, 1, 2)), q, k, v, debug_info=True)

    xla = text()
    assert f"jvp({XLA_SCOPE})" in xla and f"transpose(jvp({XLA_SCOPE}))" in xla
    with _kernel_form(monkeypatch):
        kernel = text()
    assert XLA_SCOPE not in kernel
    assert kernel.count(KERNEL_SCOPE) > 2


def test_the_kernel_module_imports_pallas_without_the_gpu_interpreter():
    """A process pays the kernel's import before its first step: the
    Mosaic GPU interpreter (three quarters of it) stays unloaded, nothing
    is left in ``sys.modules`` to stop a later import of it, and a Pallas
    that is loaded already is taken as it is."""
    import os
    import subprocess
    import sys

    child = (
        "import sys, importlib\n"
        "importlib.import_module('byteps_tpu.ops.flash_attention')\n"
        "gpu = 'jax._src.pallas.mosaic_gpu.interpret'\n"
        "assert 'jax.experimental.pallas.tpu' in sys.modules\n"
        "assert gpu not in sys.modules, 'a None left behind, or loaded'\n"
        "assert not [m for m in sys.modules\n"
        "            if m.startswith('jax.experimental.mosaic.gpu')]\n"
        "importlib.import_module(gpu + '.interpret_pallas_call')\n"
        "print('ok')\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", child], cwd=repo, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo})
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]
