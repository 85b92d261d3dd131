"""The state-space scan's kernels (``byteps_tpu/ops/ssd_scan.py``, PR 65)
interpreted on the CPU: against the XLA form of ``ssd_scan`` and against
the plain reference's token-by-token float32 recurrence, the rule that
picks the form, its counters, and the mixer that hands the scan ``[s,
channels]``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import plain_nemotron_h as plain
from byteps_tpu.monitor import metrics
from byteps_tpu.parallel import linear_attention as la
from byteps_tpu.parallel.linear_attention import (
    SSM_SCAN_KERNEL_SITES, SSM_SCAN_SCOPE, SSM_SCAN_SITES, ssd_form,
    ssd_scan, ssd_scan_channels)

F32, BF16 = jnp.float32, jnp.bfloat16
TENSORS = ("y", "dC", "dB", "dx", "dg", "ddt")


@pytest.fixture(autouse=True)
def _highest():
    """float32 matmuls at float32 on both sides of every comparison."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """``ssd_form`` told the backend is a ``tpu``: the kernels, which
    interpret themselves off one."""
    rule = la.ssd_form
    monkeypatch.setattr(la, "ssd_form",
                        lambda backend, *rest: rule("tpu", *rest))


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).ravel())
                 / jnp.linalg.norm(want.ravel()))


def _inputs(s, groups, strength, seed=0, b=1, per_group=8, n=128, p=64):
    """C, B at ``groups`` groups of ``n`` under ``per_group`` heads of ``p``
    each, x, the log-decay (``-strength dt``), the step and a cotangent: the
    kernels' widths."""
    h = per_group * groups
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    return (jax.random.normal(ks[0], (b, s, groups, n)),
            jax.random.normal(ks[1], (b, s, groups, n)),
            jax.random.normal(ks[2], (b, s, h, p)), -strength * dt, dt,
            jax.random.normal(ks[4], (b, s, h, p)))


def _recurrence(c, b, x, g, dt):
    return jax.vmap(lambda *row: plain.selective_scan(
        *row, scan_block=x.shape[1]))(c, b, x, g, dt)


def _all_six(fn, operands, w):
    y, vjp = jax.vjp(fn, *operands)
    return (y, *vjp(w))


# (s, groups, strength): two lengths, one not a multiple of the chunk (56
# zero tokens close its last chunk), two group counts; at strength 3 a
# chunk's cumulated log-decay goes far under -88
CASES = [(256, 1, 0.3), (256, 2, 3.0), (200, 1, 3.0), (200, 2, 0.3)]


@pytest.mark.parametrize("s,groups,strength", CASES)
def test_the_kernels_are_the_xla_form_and_the_token_recurrence(
        as_on_a_tpu, s, groups, strength):
    """``y`` and the five gradients of the form the rule picks on a TPU
    (bf16 operands) against the XLA form at the same precision and against
    the recurrence token by token in float32. ``y`` is the XLA form's to
    float32 rounding (the same products in the same precisions); the
    gradients differ by the cotangents' rounding (the kernel keeps them
    float32 where ``jax.grad`` of a bf16 product rounds them to bf16) and
    each is nearer the recurrence's than 1%."""
    *operands, w = _inputs(s, groups, strength)

    def picked(*a):
        return ssd_scan(*a, chunk=128, dtype=BF16)

    kernel_sites = metrics.counter(SSM_SCAN_KERNEL_SITES)
    got = _all_six(picked, operands, w)
    assert metrics.counter(SSM_SCAN_KERNEL_SITES) == kernel_sites + 1
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(la, "ssd_form", lambda *shapes: "xla")
        xla = _all_six(picked, operands, w)
    assert metrics.counter(SSM_SCAN_KERNEL_SITES) == kernel_sites + 1
    want = _all_six(_recurrence, operands, w)
    assert _rel(got[0], xla[0]) <= 1e-5
    for name, g, x, wanted in zip(TENSORS, got, xla, want):
        assert bool(jnp.isfinite(g).all()), name
        assert _rel(g, x) <= 6e-3, name
        assert _rel(g, wanted) <= 1e-2, name


@pytest.mark.parametrize("s,groups,strength", CASES[1:3])
def test_the_kernels_in_float32_are_the_recurrence_to_rounding(s, groups,
                                                               strength):
    """The kernels' algebra with nothing rounded to bf16 (a dtype the rule
    never picks them at): every tensor within 1e-5 of the recurrence, finite
    where a chunk's decay underflows."""
    from byteps_tpu.ops.ssd_scan import ssd_scan_kernel

    *operands, w = _inputs(s, groups, strength)
    assert float(jnp.cumsum(operands[3][:, :128], axis=1).min()) < -88

    def kernel(c, b, x, g, dt):
        mixed = jnp.concatenate(
            [t.reshape(*t.shape[:2], -1) for t in (x, b, c)], axis=-1)
        after = [(0, 0), (0, -s % 128), (0, 0)]
        G = la.chunk_log_decay(g, 128).reshape(1, -1, g.shape[2])
        return ssd_scan_kernel(
            jnp.pad(mixed, after), G, jnp.pad(dt, after), groups=groups,
            state=128, dtype=F32)[0][:, :s].reshape(x.shape)

    got = _all_six(kernel, operands, w)
    want = _all_six(_recurrence, operands, w)
    for name, g, wanted in zip(TENSORS, got, want):
        assert bool(jnp.isfinite(g).all()), name
        assert _rel(g, wanted) <= 1e-5, name


#  backend, dtype, state, channels, heads, groups, chunk
RULE = [
    (("tpu", BF16, 128, 64, 64, 8, 128), "kernel"),     # the Nemotron cell
    (("tpu", BF16, 128, 64, 8, 1, 128), "kernel"),
    (("tpu", BF16, 128, 64, 32, 2, 128), "kernel"),     # 16 heads a group
    (("cpu", BF16, 128, 64, 64, 8, 128), "xla"),        # the backend
    (("gpu", BF16, 128, 64, 64, 8, 128), "xla"),
    (("tpu", F32, 128, 64, 64, 8, 128), "xla"),         # the operands' dtype
    (("tpu", jnp.float16, 128, 64, 64, 8, 128), "xla"),
    (("tpu", BF16, 64, 64, 64, 8, 128), "xla"),         # the state
    (("tpu", BF16, 256, 64, 64, 8, 128), "xla"),
    (("tpu", BF16, 128, 128, 64, 8, 128), "xla"),       # a head's channels
    (("tpu", BF16, 128, 32, 64, 8, 128), "xla"),
    (("tpu", BF16, 128, 64, 64, 16, 128), "xla"),       # 4 heads a group
    (("tpu", BF16, 128, 64, 12, 1, 128), "xla"),        # 12: not in eights
    (("tpu", BF16, 128, 64, 64, 7, 128), "xla"),        # no divisor
    (("tpu", BF16, 128, 64, 64, 8, 64), "xla"),         # the chunk
    (("tpu", BF16, 128, 64, 64, 8, 256), "xla"),
    (("tpu", BF16, 16, 8, 4, 2, 8), "xla"),             # the tiny model's
]


@pytest.mark.parametrize("args,form", RULE)
def test_the_rule_is_a_pure_function_of_backend_and_shapes(args, form):
    assert ssd_form(*args) == form
    assert ssd_form(args[0], np.dtype(args[1]), *args[2:]) == form


def test_the_counters_and_the_names_in_the_lowered_program(monkeypatch):
    """Bumped while tracing, one a call site: on the CPU a site is counted
    as a scan and not as a kernel; told it is on a TPU, both; and the three
    calls are named under the scan's scope, the forward kernel in the
    forward pass, the states-only walk and the backward kernel in the
    backward pass. The float32 masks ``[b, group, heads, C, C]`` are the XLA
    form's alone."""
    from byteps_tpu.ops.ssd_scan import BWD_NAME, FWD_NAME, STATES_NAME

    c, b, x, g, dt, w = _inputs(512, 1, 0.3)
    mixed = jnp.concatenate(
        [t.reshape(*t.shape[:2], -1) for t in (x, b, c)], axis=-1)
    w = w.reshape(1, 512, -1)

    def text():
        return jax.jit(jax.value_and_grad(lambda *a: (ssd_scan_channels(
            *a, heads=8, groups=1, state=128, chunk=128, dtype=BF16)[0]
            * w).sum(), argnums=(0, 1, 2))).lower(
                mixed, g, dt).as_text(debug_info=True)

    def sites():
        return (metrics.counter(SSM_SCAN_SITES),
                metrics.counter(SSM_SCAN_KERNEL_SITES))

    s0, k0 = sites()
    xla = text()
    assert sites() == (s0 + 1, k0)
    assert "bps_ssd_scan" not in xla
    rule = la.ssd_form
    monkeypatch.setattr(la, "ssd_form",
                        lambda backend, *rest: rule("tpu", *rest))
    kernel = text()
    assert sites() == (s0 + 2, k0 + 1)
    assert f"/jvp({SSM_SCAN_SCOPE})/" in kernel
    assert f"/transpose(jvp({SSM_SCAN_SCOPE}))/" in kernel
    for name in (FWD_NAME, STATES_NAME, BWD_NAME):
        assert kernel.count(f'"{name}/pallas_call"') == 1, name
    assert "x8x128x128xf32" in xla and "x8x128x128xf32" not in kernel
    # ... and no [b, s, heads, p] tensor is left: x is read where it lies
    assert "x512x8x64x" in xla and "x512x8x64x" not in kernel


def test_the_two_entries_agree_and_refuse_wrong_shapes(as_on_a_tpu):
    c, b, x, g, dt, _ = _inputs(128, 1, 0.3)
    mixed = jnp.concatenate(
        [t.reshape(*t.shape[:2], -1) for t in (x, b, c)], axis=-1)
    flat, x_back = ssd_scan_channels(mixed, g, dt, heads=8, groups=1,
                                     state=128)
    assert flat.shape == (1, 128, 512) and flat.dtype == F32
    np.testing.assert_array_equal(x_back, mixed[..., :512])
    np.testing.assert_array_equal(
        flat.reshape(x.shape), ssd_scan(c, b, x, g, dt))
    # x's cotangent comes through the op, into ``mixed``'s where x stands

    def skip(form):
        with pytest.MonkeyPatch.context() as patch:
            if form == "xla":
                patch.setattr(la, "ssd_form", lambda *shapes: "xla")
            return jax.grad(lambda m: (ssd_scan_channels(
                m, g, dt, heads=8, groups=1, state=128)[1] ** 2).sum())(mixed)

    np.testing.assert_allclose(skip("kernel"), skip("xla"), atol=1e-6)
    assert float(jnp.abs(skip("kernel")[..., 512:]).max()) == 0.0
    with pytest.raises(ValueError, match="ssd_scan_channels"):
        ssd_scan_channels(mixed, g, dt, heads=8, groups=3, state=128)
    with pytest.raises(ValueError, match="ssd_scan_channels"):
        ssd_scan_channels(mixed, g[..., :4], dt, heads=8, groups=1,
                          state=128)


def test_the_mixer_is_the_same_in_both_forms(monkeypatch):
    """One Mamba-2 mixer at the kernels' widths, value and every parameter's
    gradient, the kernel form (told it is on a TPU) against the XLA form:
    the mixer hands either ``[s, channels]``."""
    from byteps_tpu.models.nemotron_h import Mamba2Mixer

    mixer = Mamba2Mixer(heads=8, head_dim=64, groups=1, state=128)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 32))
    params = mixer.init(jax.random.PRNGKey(0), x)
    w = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def both():
        return jax.value_and_grad(
            lambda p: (mixer.apply(p, x) * w).sum())(params)

    kernel_sites = metrics.counter(SSM_SCAN_KERNEL_SITES)
    want = both()
    assert metrics.counter(SSM_SCAN_KERNEL_SITES) == kernel_sites
    rule = la.ssd_form
    monkeypatch.setattr(la, "ssd_form",
                        lambda backend, *rest: rule("tpu", *rest))
    got = both()
    assert metrics.counter(SSM_SCAN_KERNEL_SITES) == kernel_sites + 1
    assert abs(float(got[0]) - float(want[0])) <= 1e-3 * abs(float(want[0]))
    flat = jax.tree_util.tree_leaves_with_path
    for (path, g), (_, wanted) in zip(flat(got[1]), flat(want[1])):
        assert _rel(g, wanted) <= 1e-2, jax.tree_util.keystr(path)
