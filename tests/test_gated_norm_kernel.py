"""``models/nemotron_h.py::gated_group_norm`` — a Mamba-2 mixer's skip, SiLU
gate and group norm — is XLA's (``gated_group_norm_xla``) or, where
``gate_form`` says so, the Pallas kernel pair of ``ops/gated_norm.py`` under
a rule that keeps its operands alone. The rule of the shapes as a pure
function, the forward kernel (interpret mode: its own code on the CPU)
against the XLA form over groups, head sizes, a skip that is not one and
several row blocks, the five gradients through the rule against ``jax.grad``
of the XLA form, ``x`` and ``z`` read where they lie in wider arrays, a
group's and a token's reach, and the two trace-time counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import byteps_tpu.models.nemotron_h as nh
from byteps_tpu.models.nemotron_h import (GATE_KERNEL_SITES,
                                          GATE_MAX_GROUP_WIDTH, GATE_ROWS,
                                          GATE_SITES, gate_form,
                                          gated_group_norm,
                                          gated_group_norm_xla)
from byteps_tpu.monitor import metrics
from byteps_tpu.ops import gated_norm as gn

BF16, F32 = jnp.bfloat16, jnp.float32
ROWS = 32           # the tests' row block: three of them in a sequence of 96
BF16_ULP = 2.0 ** -7    # a rounding apart, relative


@pytest.mark.parametrize("args, form", [
    (("tpu", 16384, 4096, 8, 64, BF16), "kernel"),     # the Nemotron cell
    (("tpu", 16384, 4096, 8, 128, BF16), "kernel"),
    (("tpu", 512, 128, 1, 64, BF16), "kernel"),        # the least of each
    (("tpu", 512, 512, 1, 64, BF16), "kernel"),
    (("tpu", 8192, 1024, 8, 128, "bfloat16"), "kernel"),
    # each refusal: the XLA form
    (("cpu", 16384, 4096, 8, 64, BF16), "xla"),
    (("gpu", 16384, 4096, 8, 64, BF16), "xla"),
    (("tpu", 16384, 4096, 8, 64, F32), "xla"),         # the result's dtype
    (("tpu", 16000, 4096, 8, 64, BF16), "xla"),        # no whole row blocks
    (("tpu", 256, 4096, 8, 64, BF16), "xla"),
    (("tpu", 16384, 4096, 3, 64, BF16), "xla"),        # no whole groups
    (("tpu", 16384, 4096, 0, 64, BF16), "xla"),
    (("tpu", 16384, 4096, 64, 64, BF16), "xla"),       # groups of 64 lanes
    (("tpu", 16384, 1536, 8, 64, BF16), "xla"),        # 192: no whole tiles
    (("tpu", 16384, 4096, 4, 64, BF16), "xla"),        # 1024: over a block
    (("tpu", 16384, 4096, 8, 96, BF16), "xla"),        # heads across groups
    (("cpu", 16, 32, 2, 8, BF16), "xla"),              # the CPU tests'
])
def test_the_rule_is_a_pure_function_of_backend_and_shapes(args, form):
    assert gate_form(*args) == form


def test_the_rule_and_the_kernel_agree_on_the_tiling():
    assert (GATE_ROWS, GATE_MAX_GROUP_WIDTH) == (gn.ROWS, gn.MAX_GROUP_WIDTH)
    assert gn.ROWS % gn._PIECE == 0 and gn._PIECE % gn._TILE == 0
    assert gn.MAX_GROUP_WIDTH % gn.LANES == 0
    # one block of each operand and result, twice (the pipeline's two
    # buffers), inside the 16 MiB of scoped VMEM a kernel gets unasked
    block = gn.ROWS * gn.MAX_GROUP_WIDTH
    assert 2 * block * (4 * 4 + 3 * 2) < 16 * 2 ** 20


def _inputs(groups, head_dim, width=128, s=96, b=2, z_extra=64, x_extra=0,
            seed=0):
    """y, x, z, skip, weight, ct: ``groups`` groups of ``width`` channels,
    ``z`` (bf16) ``z_extra`` columns wider than ``inner`` and ``x``
    ``x_extra`` wider, a skip about one and a weight about one."""
    inner = groups * width
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, s, inner), F32),
            jax.random.normal(ks[1], (b, s, inner + x_extra), F32),
            jax.random.normal(ks[2], (b, s, inner + z_extra), F32).astype(
                BF16),
            1.0 + 0.5 * jax.random.normal(ks[3], (inner // head_dim,), F32),
            1.0 + 0.1 * jax.random.normal(ks[4], (inner,), F32),
            jax.random.normal(ks[5], (b, s, inner), F32))


def _forward(y, x, z, skip, weight, groups, head_dim, dtype=BF16, rows=ROWS):
    return gn.gated_norm_forward(y, x, z, jnp.repeat(skip, head_dim), weight,
                                 groups=groups, dtype=dtype, rows=rows)


CASES = [(groups, head_dim, unit_skip)
         for groups in (1, 2, 8) for head_dim in (64, 128)
         for unit_skip in (False, True)]
IDS = [f"groups{g}-heads{h}-{'skip1' if u else 'skip'}" for g, h, u in CASES]


@pytest.mark.parametrize("groups, head_dim, unit_skip", CASES, ids=IDS)
def test_the_forward_kernel_is_the_xla_form(groups, head_dim, unit_skip):
    """Three row blocks a sequence and a grid step a group; in float32 to
    rounding, in bf16 (what the rule asks for) a rounding apart at most."""
    y, x, z, skip, weight, _ = _inputs(groups, head_dim)
    if unit_skip:
        skip = jnp.ones_like(skip)
    for dtype, rtol in ((F32, 1e-5), (BF16, BF16_ULP)):
        want = gated_group_norm_xla(y, x, z, skip, weight, groups=groups,
                                    head_dim=head_dim, dtype=dtype)
        got = _forward(y, x, z, skip, weight, groups, head_dim, dtype)
        assert got.dtype == dtype and got.shape == y.shape
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), rtol=rtol,
                                   atol=1e-6)


NAMES = ("dy", "dx", "dz", "dskip", "dweight")


@pytest.mark.parametrize("wide", [False, True], ids=["x", "x_lies_in"])
@pytest.mark.parametrize("groups, head_dim", [(1, 64), (2, 128), (8, 64)])
def test_the_gradients_through_the_rule_are_jax_grad_of_the_xla_form(
        monkeypatch, groups, head_dim, wide):
    """Told it is on a TPU, ``gated_group_norm`` runs the kernel pair (one
    block of ``GATE_ROWS`` rows here): all five gradients, in their
    operands' shapes and dtypes — ``dz`` as wide as ``z``, zero beside the
    gate's columns; with ``x_lies_in`` the kernels read ``x`` in the wider
    array and the cotangent still comes back through ``x``, none beside."""
    y, mixed, z, skip, weight, ct = _inputs(groups, head_dim, s=GATE_ROWS,
                                            b=1, x_extra=256 if wide else 0)
    inner = y.shape[-1]

    def loss(fn, wide):
        def scalar(y, mixed, z, skip, weight):
            out = fn(y, mixed[..., :inner], z, skip, weight, groups=groups,
                     head_dim=head_dim,
                     **({"x_lies_in": mixed} if wide else {}))
            return (out.astype(F32) * ct).sum()
        return jax.value_and_grad(scalar, (0, 1, 2, 3, 4))(
            y, mixed, z, skip, weight)

    want_loss, want = loss(gated_group_norm_xla, False)
    monkeypatch.setattr(nh, "gate_form", lambda *shapes: "kernel")
    got_loss, got = loss(gated_group_norm, wide)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-3)
    for name, g, ref in zip(NAMES, got, want):
        assert g.dtype == ref.dtype and g.shape == ref.shape, name
        g, ref = np.asarray(g, np.float32), np.asarray(ref, np.float32)
        if name in ("dx", "dz"):
            assert not g[..., inner:].any(), name
        # the cotangent of a bf16 result is one, so out's rounding moves no
        # gradient; dz is rounded to bf16 on both sides
        scale = np.abs(ref).max()
        np.testing.assert_allclose(
            g, ref, rtol=BF16_ULP if name == "dz" else 2e-5,
            atol=2e-6 * scale, err_msg=name)


@pytest.mark.parametrize("rows", [16, 32, 48, 96])
def test_the_row_block_changes_nothing(rows):
    y, x, z, skip, weight, ct = _inputs(2, 64, b=1)
    args = (y, x, z, jnp.repeat(skip, 64), weight)
    np.testing.assert_array_equal(
        gn.gated_norm_forward(*args, groups=2, rows=rows),
        gn.gated_norm_forward(*args, groups=2, rows=96))
    ct = ct.astype(BF16)
    got = gn.gated_norm_backward(*args, ct, groups=2, rows=rows)
    want = gn.gated_norm_backward(*args, ct, groups=2, rows=96)
    for name, g, ref in zip(NAMES, got, want):
        if name in ("dskip", "dweight"):    # a sum in another order
            np.testing.assert_allclose(g, ref, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(g, ref, err_msg=name)


@pytest.mark.parametrize("t, group", [(0, 0), (31, 1), (32, 2), (95, 3)])
def test_a_row_reaches_its_own_group_and_token_and_nothing_else(t, group):
    """A change to one channel of token ``t`` moves the result in that
    token's row of that channel's group alone — the whole group, through
    the mean of squares — whichever of ``y``, ``x`` and ``z`` it is made
    to, and the gradients ``dy``, ``dx``, ``dz`` of that row and group
    alone."""
    groups, width = 4, 128
    y, x, z, skip, weight, ct = _inputs(groups, 64, b=1)
    channel = group * width + 5
    # no chance zero there: a gate or a skipped sum of nothing moves nothing
    y, x, z = (a.at[0, t, channel].set(1.0) for a in (y, x, z))
    args = (y, x, z, jnp.repeat(skip, 64), weight)
    ct = ct.astype(BF16)
    base = gn.gated_norm_forward(*args, groups=groups, dtype=F32, rows=ROWS)
    base_grads = gn.gated_norm_backward(*args, ct, groups=groups, rows=ROWS)
    for i in range(3):
        moved = list(args)
        moved[i] = moved[i].at[0, t, channel].add(1.0)
        out = gn.gated_norm_forward(*moved, groups=groups, dtype=F32,
                                    rows=ROWS)
        changed = np.argwhere(np.asarray(out != base))
        assert set(changed[:, 1]) == {t}
        assert set(changed[:, 2] // width) == {group}
        assert len(changed) > width // 2            # the group's mean moved
        grads = gn.gated_norm_backward(*moved, ct, groups=groups, rows=ROWS)
        for name, g, ref in list(zip(NAMES, grads, base_grads))[:3]:
            changed = np.argwhere(np.asarray(g != ref))
            assert set(changed[:, 1]) == {t}, name
            assert set(changed[:, 2] // width) == {group}, name


@pytest.mark.parametrize("y, x, z, skip, weight, groups, rows", [
    ((1, 96, 200), (1, 96, 200), (1, 96, 200), 200, 200, 2, 32),   # lanes
    ((1, 96, 256), (1, 96, 256), (1, 96, 256), 256, 256, 3, 32),   # groups
    ((1, 96, 256), (1, 96, 256), (1, 96, 256), 256, 256, 0, 32),
    ((1, 96, 1024), (1, 96, 1024), (1, 96, 1024), 1024, 1024, 1, 32),
    ((1, 100, 256), (1, 100, 256), (1, 100, 256), 256, 256, 2, 32),  # rows
    ((1, 96, 256), (1, 96, 256), (1, 96, 256), 256, 256, 2, 24),   # a tile
    ((1, 96, 256), (1, 96, 128), (1, 96, 256), 256, 256, 2, 32),   # x narrow
    ((1, 96, 256), (1, 96, 256), (1, 96, 128), 256, 256, 2, 32),   # z narrow
    ((1, 96, 256), (1, 48, 256), (1, 96, 256), 256, 256, 2, 32),
    ((1, 96, 256), (1, 96, 256), (1, 96, 256), 4, 256, 2, 32),     # a head's
    ((1, 96, 256), (1, 96, 256), (1, 96, 256), 256, 128, 2, 32),
])
def test_the_kernels_refuse_what_their_tiling_does_not_hold(
        y, x, z, skip, weight, groups, rows):
    args = (jnp.zeros(y, F32), jnp.zeros(x, F32), jnp.zeros(z, BF16),
            jnp.zeros((skip,), F32), jnp.zeros((weight,), F32))
    with pytest.raises(ValueError, match="gated_norm kernel"):
        gn.gated_norm_forward(*args, groups=groups, rows=rows)
    with pytest.raises(ValueError, match="gated_norm kernel"):
        gn.gated_norm_backward(*args, jnp.zeros(y, BF16), groups=groups,
                               rows=rows)


def test_a_cotangent_of_another_shape_is_refused():
    y, x, z, skip, weight, _ = _inputs(2, 64, b=1)
    with pytest.raises(ValueError, match="cotangent"):
        gn.gated_norm_backward(y, x, z, jnp.repeat(skip, 64), weight,
                               jnp.zeros((1, 96, 128), BF16), groups=2,
                               rows=ROWS)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_counters_and_both_names_in_the_lowered_program(monkeypatch,
                                                            kernel):
    """Bumped while tracing, one a call site; a site that took the kernels
    counts on the second counter too and names both in its program, once
    each — which on the CPU holds no ``pallas_call``."""
    if kernel:     # told it is on a TPU: one whole block of ``GATE_ROWS``
        monkeypatch.setattr(nh, "gate_form", lambda *shapes: "kernel")
    y, x, z, skip, weight, ct = _inputs(2, 64, b=1,
                                        s=GATE_ROWS if kernel else 96)

    def sites():
        return [metrics.counter(n) for n in (GATE_SITES, GATE_KERNEL_SITES)]

    before = sites()
    text = jax.jit(jax.value_and_grad(
        lambda *a: (gated_group_norm(*a, groups=2, head_dim=64).astype(F32)
                    * ct).sum(), (0, 1, 2, 3, 4))).lower(
                        y, x, z, skip, weight).as_text(debug_info=True)
    assert list(np.subtract(sites(), before)) == [1, int(kernel)]
    for name in (gn.FWD_NAME, gn.BWD_NAME):
        assert text.count(f'"{name}/pallas_call"') == int(kernel), name
    assert ("pallas_call" in text) == kernel
