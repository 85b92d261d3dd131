"""Compile the main path's programs for the chip, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
*described* ``v5e:2x2`` topology (on-chip-measurement guide §2.3): what it
refuses here — an unaligned slice, too much VMEM, a program that does not
fit HBM — would have cost chip time. Nothing runs, so these say nothing
about results or speed; ``chip_smoke.py`` does that on the chip.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from byteps_tpu.ops.flash_attention import flash_attention  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent
    cache but cannot be read back without a chip; the next one then warns.
    Keep these silent and the cache clean."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


# (batch, seq, heads, head_dim), causal, window
FLASH_CASES = {
    "gpt2_124m_b8_s512": ((8, 512, 12, 64), True, None),
    "long_b1_s4096_d128": ((1, 4096, 8, 128), True, None),
    "window1024_b1_s4096": ((1, 4096, 8, 128), True, 1024),
    "bert_large_noncausal_b32_s128": ((32, 128, 16, 64), False, None),
    # what full_attention hands the kernel in the benchmark's cells
    "ouro_olmoe_b1_s4096_h16_d128": ((1, 4096, 16, 128), True, None),
    "gpt2_124m_b8_s1024": ((8, 1024, 12, 64), True, None),
    # a head 256 wide: blocks of 1024 again (PR 50), twice the operand bytes
    "wide_head_b1_s2048_d256": ((1, 2048, 4, 256), True, None),
}


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_kernel_compiles_for_v5e(topo, case, with_grads):
    shape, causal, window = FLASH_CASES[case]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if with_grads else fwd
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


# (query heads, window): the two kinds of layer of the Laguna cell, b 1 x s
# 8192 over 8 key heads of 128
GROUPED_CASES = {"laguna_window512_h64": (64, 512),
                 "laguna_global_h48": (48, None)}


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("case", sorted(GROUPED_CASES))
def test_grouped_flash_kernel_compiles_for_v5e(topo, case, with_grads):
    """Grouped keys (the K/V index maps read head i // groups, dK/dV sum
    their group inside the kernel) and, under the window, every grid
    walking the band alone, at the shape the cell runs and the blocks
    ``_blocks`` derives for it."""
    heads, window = GROUPED_CASES[case]
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                             sharding=one)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if with_grads else fwd
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= (
        2 if with_grads else 1)                        # forward, backward
    if with_grads:
        dq, dk, dv = jax.eval_shape(fn, q, kv, kv)
        assert (dq.shape, dk.shape, dv.shape) == (q.shape, kv.shape,
                                                  kv.shape)


FUSED = ("bps_flash_fwd", "bps_flash_bwd")
PAIR = ("bps_flash_fwd", "bps_flash_dq", "bps_flash_dkv")
# s, (query heads, key heads), (key width, value width), window, dtype
BACKWARD_FORM_CASES = {
    "qwen3_next_cell": (16384, (16, 2), (256, 256), None, jnp.bfloat16, FUSED),
    # the last length ``backward_form`` gives the fused kernel, by width
    "last_fused_64": (37888, (4, 2), (64, 64), None, jnp.bfloat16, FUSED),
    "last_fused_128": (37888, (4, 2), (128, 128), None, jnp.bfloat16, FUSED),
    "last_fused_192_128": (24576, (2, 2), (192, 128), None, jnp.bfloat16,
                           FUSED),
    "last_fused_256": (18432, (4, 2), (256, 256), None, jnp.bfloat16, FUSED),
    "last_fused_window512": (45568, (4, 2), (128, 128), 512, jnp.bfloat16,
                             FUSED),
    "last_fused_float32": (22528, (4, 2), (128, 128), None, jnp.float32,
                           FUSED),
    "pair_over_the_limit": (32768, (16, 2), (256, 256), None, jnp.bfloat16,
                            PAIR),
}


@pytest.mark.parametrize("case", sorted(BACKWARD_FORM_CASES))
def test_each_backward_form_compiles_for_v5e(topo, case):
    """What ``backward_form`` calls "fused" has to fit the VMEM its call
    asks for: at the Qwen3-Next cell's shape (32 MiB of float32 dK and dV),
    at the rule's last length of every width (more than one key head, so
    the long output blocks keep both their buffers), and beyond it the
    pair."""
    s, (heads, kv_heads), (d, d_v), window, dtype, kernels = (
        BACKWARD_FORM_CASES[case])
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, s, heads, d), dtype, sharding=one)
    k = jax.ShapeDtypeStruct((1, s, kv_heads, d), dtype, sharding=one)
    v = jax.ShapeDtypeStruct((1, s, kv_heads, d_v), dtype, sharding=one)
    text = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window,
                                        interpret=False)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    ).lower(q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") == len(kernels)
    for name in set(FUSED + PAIR):
        assert (name in text) == (name in kernels), name


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_at_two_widths_compiles_for_v5e(topo, with_grads):
    """The latent layer of the Kimi-Linear cell: 32 heads, keys 192 wide
    (128 + 64), values 128, s 16384 — Mosaic takes the 192 lanes as they
    are."""
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 16384, 32, 192), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one)

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, 192 ** -0.5, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if with_grads else fwd
    text = jax.jit(fn).lower(q, q, v).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
def test_kda_operand_kernels_compile_for_v5e(topo, with_grads):
    """The linear-attention layers of the Kimi-Linear cell: b 1 x s 8192 as
    256 chunks of 32 tokens (sub-chunks of 8), 32 heads of 128 x 128, bf16
    operands out — the forward kernel, and the forward that saves with the
    hand-written backward kernel through ``jax.grad``."""
    from byteps_tpu.ops.kda_chunk import chunk_operands

    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 256, 32, 32, 128), jnp.float32, sharding=one)
    beta = jax.ShapeDtypeStruct((1, 256, 32, 32), jnp.float32, sharding=one)

    def fwd(q, k, v, beta, G):
        return chunk_operands(q, k, v, beta, G, 8, jnp.bfloat16, False)

    def loss(*args):
        return sum(o.astype(jnp.float32).sum() for o in fwd(*args))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if with_grads else fwd
    text = jax.jit(fn).lower(x, x, x, beta, x).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if with_grads else 1)


# chunks, chunk, key heads, value heads
GDN_CASES = {
    # the Gated DeltaNet layers of the Qwen3-Next cell: b 1 x s 16384
    "qwen3_next_512x32_h16_32": (512, 32, 16, 32),
    # the edges of ``kda_form``'s rule for the per-head kernels, whose ask
    # of VMEM goes by the rows (tokens x value heads) a chunk holds:
    # ``HEAD_KERNEL_ROWS`` of them in shorter chunks of more heads, and the
    # least the tiling admits
    "rows_1024_as_16_h32_64": (4, 16, 32, 64),
    "rows_1024_as_8_h64_128": (4, 8, 64, 128),
    "least_8_h8_8": (4, 8, 8, 8),
}


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("case", sorted(GDN_CASES))
def test_gdn_operand_kernels_compile_for_v5e(topo, case, with_grads):
    """One decay a head, keys and values 128 wide, bf16 operands out as the
    recurrence kernels read them, one call over all chunks — the forward
    kernel, and through ``jax.grad`` the hand-written backward kernel, which
    keeps nothing of the forward. Every case is one the rule admits."""
    from byteps_tpu.ops.gdn_chunk import BWD_NAME, FWD_NAME, head_operands
    from byteps_tpu.parallel.linear_attention import kda_form

    chunks, chunk, key_heads, heads = GDN_CASES[case]
    assert kda_form("tpu", heads, 128, 128, jnp.bfloat16, chunk, True,
                    key_heads) == "head_kernel"
    one = SingleDeviceSharding(topo.devices[0])

    def described(*shape):
        return jax.ShapeDtypeStruct((1, chunks, chunk) + shape, jnp.float32,
                                    sharding=one)

    shapes = (described(key_heads, 128), described(key_heads, 128),
              described(heads, 128), described(heads), described(heads))

    def fwd(*args):
        return head_operands(*args, jnp.bfloat16, False)

    def loss(*args):
        return sum((o.astype(jnp.float32) ** 2).sum() for o in fwd(*args))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if with_grads else fwd
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if with_grads else 1)
    assert FWD_NAME in text and (BWD_NAME in text) == with_grads


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("chunks", [256, 512])
def test_kda_recurrence_kernels_compile_for_v5e(topo, chunks, with_grads):
    """The scan over every chunk of a Kimi-Linear layer in one call: 32
    heads of 128 x 128, chunks of 32, bf16 operands as the operand kernels
    leave them, a token a [heads, d] tile, at the cell's s 8,192 (256
    chunks) and at the s 16,384 its configuration was first measured at —
    the forward kernel, and through ``jax.grad`` the forward kernel that
    saves a state every 16 chunks and the backward kernel."""
    from byteps_tpu.ops.kda_recurrence import BWD_NAME, FWD_NAME, recurrence

    one = SingleDeviceSharding(topo.devices[0])

    def described(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    keys = described(1, chunks, 32, 32, 128)
    values = described(1, chunks, 32, 32, 128, dtype=jnp.float32)
    shapes = (described(1, 32, 128, 128, dtype=jnp.float32), keys, values,
              keys, keys, described(1, chunks, 32, 128, dtype=jnp.float32),
              described(1, chunks, 32, 32, 32))

    def fwd(*args):
        return recurrence(*args, jnp.bfloat16, False)

    def loss(*args):
        return sum(o.sum() for o in fwd(*args))

    fn = jax.grad(loss, argnums=tuple(range(7))) if with_grads else fwd
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if with_grads else 1)
    assert FWD_NAME in text and (BWD_NAME in text) == with_grads


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
def test_ssd_scan_kernels_compile_for_v5e(topo, with_grads):
    """The state-space scan of a Nemotron layer (PR 65) at the cell's shape,
    b 1 x s 16,384, 64 heads of 64 over 8 groups of state 128, reading ``[s,
    6144]`` as the convolution leaves it: the forward kernel, and through
    ``jax.grad`` the states-only walk and the backward kernel (the forward
    kernel's ``y`` is no residual, so a gradient alone runs no forward
    kernel), each within the VMEM it asks for."""
    from byteps_tpu.ops.ssd_scan import (BWD_NAME, FWD_NAME, STATES_NAME,
                                         ssd_scan_kernel)

    one = SingleDeviceSharding(topo.devices[0])
    shapes = tuple(jax.ShapeDtypeStruct((1, 16384, width), jnp.float32,
                                        sharding=one)
                   for width in (6144, 64, 64))

    def fwd(mixed, log_decay, dt):
        return ssd_scan_kernel(mixed, log_decay, dt, groups=8, state=128,
                               interpret=False)[0]

    def both(*args):
        y, vjp = jax.vjp(fwd, *args)
        return y, vjp(y)

    text = jax.jit(both if with_grads else fwd).lower(
        *shapes).compile().as_text()
    assert text.count("tpu_custom_call") == (3 if with_grads else 1)
    assert FWD_NAME in text
    assert (STATES_NAME in text) == (BWD_NAME in text) == with_grads
    # x, B, C are blocks of the one array: nothing is cut out of it
    assert "f32[1,16384,4096]{2,1,0:T(8,128)} slice(" not in text
    assert "[1,16384,64,64]" not in text


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
def test_gated_norm_kernels_compile_for_v5e(topo, as_on_a_tpu, with_grads):
    """The output chain of a Nemotron mixer (PR 69) as the model calls it at
    the cell's shape, b 1 x s 16,384, 8 groups of 512 channels, heads of 64,
    ``x`` in the convolution's ``[s, 6144]`` and ``z`` in the projection's
    ``[s, 12288]``: one kernel each way with the scoped VMEM a kernel gets
    unasked, and neither operand cut out of its array."""
    from byteps_tpu.models.nemotron_h import gated_group_norm
    from byteps_tpu.ops.gated_norm import BWD_NAME, FWD_NAME

    one = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def fwd(y, mixed, z, skip, weight):
        return gated_group_norm(y, mixed[..., :4096], z, skip, weight,
                                groups=8, head_dim=64, x_lies_in=mixed)

    def both(*args):
        out, vjp = jax.vjp(fwd, *args)
        return out, vjp(out)

    text = jax.jit(both if with_grads else fwd).lower(
        shape(1, 16384, 4096), shape(1, 16384, 6144),
        shape(1, 16384, 12288, dtype=jnp.bfloat16), shape(64),
        shape(4096)).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if with_grads else 1)
    assert FWD_NAME in text and (BWD_NAME in text) == with_grads
    assert "[1,16384,4096]{2,1,0:T(8,128)} slice(" not in text
    assert "[1,16384,4096]{2,1,0:T(8,128)(2,1)} slice(" not in text


# (s, channels, taps, x's dtype, a bias, SiLU): causal_conv's four call
# sites in the benchmark's cells, b 1
CONV_CASES = {
    "nemotron_s16384_c6144": (16384, 6144, 4, jnp.bfloat16, True, True),
    "qwen3_next_s16384_c8192": (16384, 8192, 4, jnp.bfloat16, False, True),
    "kimi_linear_s8192_c4096": (8192, 4096, 4, jnp.bfloat16, False, True),
    "zaya1_s16384_c1280": (16384, 1280, 2, jnp.float32, False, False),
}


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_causal_conv_kernel_compiles_for_v5e(topo, as_on_a_tpu, case):
    """The short convolution (PR 64) as the models call it, under a
    checkpoint, forward and backward: the forward kernel at the blocks
    ``conv_form`` admits, with the scoped VMEM a kernel gets unasked, and
    the XLA form's backward pass."""
    from byteps_tpu.models.kimi_linear import causal_conv
    from byteps_tpu.ops.causal_conv import FWD_NAME

    s, channels, taps, dtype, biased, silu = CONV_CASES[case]
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, s, channels), dtype, sharding=one)
    ct = jax.ShapeDtypeStruct((1, s, channels), jnp.float32, sharding=one)
    w = jax.ShapeDtypeStruct((taps, channels), jnp.float32, sharding=one)
    bias = (jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one)
            if biased else None)

    def both(x, w, bias, ct):
        y, vjp = jax.vjp(jax.checkpoint(
            lambda *a: causal_conv(*a, "silu" if silu else None)),
            x, w, bias)
        return y, vjp(ct)

    text = jax.jit(both).lower(x, w, bias, ct).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert FWD_NAME in text


@pytest.mark.parametrize("with_grads", [False, True], ids=["fwd", "fwd_bwd"])
def test_sparse_flash_kernels_compile_for_v5e(topo, with_grads):
    """A block of the Keye cell's sparse attention: 512 queries, 32 heads
    over 4 key-value heads of 128, all 8192 keys handed in and the mask of
    a span of 4096 — the forward and probabilities kernels, and through
    ``jax.grad`` the forward (for its logsumexp) and the backward kernel."""
    from byteps_tpu.ops.sparse_flash import masked_attention, renormalised

    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((512, 32 * 128), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((8192, 4, 128), jnp.bfloat16, sharding=one)
    keep = jax.ShapeDtypeStruct((512, 4096), jnp.bool_, sharding=one)
    first = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)

    def fwd(q, k, v, keep, first):
        out, lse, target = masked_attention(q, k, v, keep, first, 128 ** -0.5,
                                            False)
        return renormalised(out, lse), target

    def loss(*args):
        return fwd(*args)[0].astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if with_grads else fwd
    text = jax.jit(fn).lower(q, k, k, keep, first).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert ("bps_dsa_bwd" if with_grads else "bps_dsa_probs") in text


def _share_layer_text(topo, t, d, e, held, width, **gate):
    """The compiled text of a share's routed experts, forward and backward
    as a block recomputes them: ``t`` bf16 tokens ``d`` wide, a router over
    ``e`` experts, ``held`` of them here from expert 16 on, ``width`` wide."""
    from byteps_tpu.parallel.moe import dropless_moe_ffn

    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
              for shape, dtype in (((t, d), jnp.bfloat16),
                                   ((d, e), jnp.float32),
                                   ((held, d, width), jnp.float32),
                                   ((held, d, width), jnp.float32),
                                   ((held, width, d), jnp.float32))]

    @jax.checkpoint
    def layer(*args):
        return dropless_moe_ffn(*args, first_expert=16, norm_topk=True,
                                **gate)[0]

    return jax.jit(jax.value_and_grad(
        lambda *args: layer(*args).astype(jnp.float32).sum(),
        argnums=range(5))).lower(*shapes).compile().as_text()


def test_expert_share_compiles_its_passes_for_v5e(topo):
    """The routed experts of a Kimi-Linear layer, forward and backward as
    the block recomputes them: T 8192, k 8, experts 16..23 of 256, D 2304,
    width 1024, sigmoid gate. Passes of 4,096 of the 65,536 sorted rows:
    one choice between a pass and a loop of passes forward (the recomputed
    one is dead code: nothing of it is kept) and one backward, each side of
    it with its gather, its grouped matmuls and its scatter-add lowered for
    the chip, and no array of 65,536 rows by the model's or the experts'
    width anywhere."""
    from byteps_tpu.parallel.moe import held_row_bound, held_row_rungs

    assert held_row_bound(8192, 8, 8, 256) == 4096
    # a pass this small has no rung under it: the bound's beside the loop
    assert held_row_rungs(8192, 8, 8, 256) == (4096,)
    text = _share_layer_text(topo, 8192, 2304, 256, 8, 1024, top_k=8,
                             scoring="sigmoid", norm_eps=1e-20,
                             routed_scale=2.446)
    assert text.count(" conditional(") == text.count(" while(") == 2
    assert "bf16[4096,2304]" in text and "bf16[4096,1024]" in text
    assert "[65536,2304]" not in text and "[65536,1024]" not in text
    assert "bf16[2560,2304]" not in text and "bf16[3072,2304]" not in text


def test_expert_share_compiles_a_pass_a_rung_for_v5e(topo):
    """The routed experts of a Mellum2 layer, forward and backward as the
    block recomputes them: T 16384 (two sequences of 8,192), k 8, experts
    16..31 of 64, D 2304, width 896, softmax gate renormalised. The bound
    is half of the 131,072 sorted rows and the rung under it 40,960: one
    choice a direction between the rung's pass and the loop of bound-sized
    passes, so the compiled text holds the gather and the grouped matmuls
    at 40,960 rows and at 65,536, still two conditionals, and the loop once
    a direction and no second copy of it."""
    from byteps_tpu.parallel.moe import held_row_rungs

    rungs = held_row_rungs(16384, 8, 16, 64)
    assert rungs == (40960, 65536)
    text = _share_layer_text(topo, 16384, 2304, 64, 16, 896, top_k=8)
    assert text.count(" conditional(") == text.count(" while(") == 2
    for rung in rungs:
        assert f"bf16[{rung},2304]" in text and f"bf16[{rung},896]" in text
    assert "[49152,2304]" not in text
    assert "[131072,2304]" not in text and "[131072,896]" not in text


def _described(mesh, tree, spec):
    """``tree``'s shapes as arrays laid out by ``spec`` on ``mesh``."""
    from jax.sharding import NamedSharding

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)), tree)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """``full_attention``, ``kda_attention``, ``ssd_scan``, ``causal_conv``
    and ``gated_group_norm`` pick their forms by the backend they run on, and
    a kernel interprets itself off a TPU: here all are told the described
    chip's answer."""
    import importlib

    # the package exports functions under both modules' names
    fa = importlib.import_module("byteps_tpu.ops.flash_attention")
    ra = importlib.import_module("byteps_tpu.parallel.ring_attention")
    la = importlib.import_module("byteps_tpu.parallel.linear_attention")
    kl = importlib.import_module("byteps_tpu.models.kimi_linear")
    nh = importlib.import_module("byteps_tpu.models.nemotron_h")
    rule, scan_rule, conv_rule = ra.attention_form, la.kda_form, kl.conv_form
    ssd_rule, gate_rule = la.ssd_form, nh.gate_form
    monkeypatch.setattr(nh, "gate_form",
                        lambda backend, *rest: gate_rule("tpu", *rest))
    monkeypatch.setattr(la, "ssd_form",
                        lambda backend, *rest: ssd_rule("tpu", *rest))
    monkeypatch.setattr(ra, "attention_form",
                        lambda backend, *rest: rule("tpu", *rest))
    monkeypatch.setattr(la, "kda_form",
                        lambda backend, *rest: scan_rule("tpu", *rest))
    monkeypatch.setattr(kl, "conv_form",
                        lambda backend, *rest: conv_rule("tpu", *rest))
    # ... in every module that bound the name when it was imported
    for ops in (fa, *(importlib.import_module(f"byteps_tpu.ops.{name}")
                      for name in ("kda_chunk", "kda_recurrence",
                                   "gdn_chunk", "causal_conv", "ssd_scan",
                                   "gated_norm"))):
        monkeypatch.setattr(ops, "_resolve_interpret",
                            lambda interpret: False)


def test_rotary_latent_attention_compiles_for_v5e(topo, as_on_a_tpu):
    """A mixer of the JoyAI cell, forward and backward: b 1 x s 8192, 32
    heads behind latents of 1536 and 512, the interleaved rotation in
    float32 feeding the flash kernels at keys 192 / values 128."""
    from byteps_tpu.models.kimi_linear import KimiLatentAttention

    one = SingleDeviceSharding(topo.devices[0])
    layer = KimiLatentAttention(32, 128, 64, 128, 512, jnp.bfloat16, 1e-6,
                                1536, 32e6)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 2048))))
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply(p, x).astype(jnp.float32).sum(),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2      # forward, backward
    assert "bps_flash_bwd" in text
    assert "bps.mla.proj" in text and "bps.mla.attend" in text


@pytest.mark.parametrize("kind", ["gdn", "attn"])
def test_qwen3_next_mixers_compile_for_v5e(topo, as_on_a_tpu, kind):
    """The two mixers of the Qwen3-Next cell, forward and backward, b 1 x s
    16384 at the published widths: Gated DeltaNet (16 key heads under 32
    value heads of 128, chunks of 32: since PR 59 the per-head operand
    kernels and the recurrence kernels, one call over the 512 chunks each)
    and gated attention (16 query heads over 2 key heads of 256 feeding the
    flash kernels at their fourth width, with the blocks ``_blocks`` derives
    for it)."""
    from byteps_tpu.models.qwen3_next import GatedAttention, GatedDeltaNet

    one = SingleDeviceSharding(topo.devices[0])
    layer = (GatedDeltaNet(16, 32, 128, 128, chunk=32) if kind == "gdn"
             else GatedAttention(16, 2, 256, 1e7, 0.25))
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.float32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 2048))))
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply(p, x).astype(jnp.float32).sum(),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    if kind == "gdn":
        # operands and recurrence, forward (saving) and backward, and
        # since PR 64 the convolution's forward kernel (once more where the
        # backward pass forms the l2 norms' input again)
        assert 5 <= text.count("tpu_custom_call") <= 6
        for name in ("bps_gdn_operands_fwd", "bps_gdn_operands_bwd",
                     "bps_kda_recurrence_fwd", "bps_kda_recurrence_bwd",
                     "bps_causal_conv_fwd"):
            assert name in text
        assert "while(" not in text         # no scan over groups is left
        for scope in ("bps.gdn.prep", "bps.gdn.scan", "bps.gdn.out"):
            assert scope in text
    else:
        assert text.count("tpu_custom_call") >= 2      # forward, backward
        assert "bps_flash_bwd" in text
        assert "bps.gattn.proj" in text and "bps.gattn.attend" in text


def test_kimi_linear_mixer_lowers_for_v5e_to_what_it_did(topo, as_on_a_tpu,
                                                         monkeypatch):
    """PR 59 gave ``kda_form`` a fourth answer and ``kda_attention`` a
    second kernel branch. A KDA mixer of the Kimi-Linear cell (one decay a
    channel: 32 heads of 128, chunks of 32 in sub-chunks of 8, b 1 x s
    8192), forward and backward, lowers for the described chip to the text
    it lowered to at ``b4fce99``, the parent of PR 59 — with the source
    positions stripped from the kernels' serialized bodies, which carry the
    file and line of every frame under a ``pallas_call`` and change with
    any line added above one (``.claude/skills/verify/SKILL.md``). The
    three convolutions stay in their XLA form here, as they were at that
    commit: what PR 64 made of them is the case
    ``test_causal_conv_kernel_compiles_for_v5e``."""
    import hashlib
    import importlib
    import types

    from jax._src import tpu_custom_call
    from jaxlib.mlir.passmanager import PassManager

    from byteps_tpu.models.kimi_linear import KimiDeltaAttention

    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def stripped(module, **kwargs):
        with module.context:
            op = module.operation.clone()
            PassManager.parse("builtin.module(strip-debuginfo)").run(op)
        return lower(types.SimpleNamespace(context=module.context,
                                           operation=op), **kwargs)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm",
                        stripped)
    monkeypatch.setattr(importlib.import_module(
        "byteps_tpu.models.kimi_linear"), "conv_form", lambda *a: "xla")
    one = SingleDeviceSharding(topo.devices[0])
    layer = KimiDeltaAttention(32, 128, 128, 4, 32, 8)
    x = jax.ShapeDtypeStruct((1, 8192, 2304), jnp.float32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 2304))))
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply(p, x).astype(jnp.float32).sum(),
        argnums=(0, 1))).lower(params, x).as_text()
    assert text.count("tpu_custom_call") == 4
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "b577b795545004973efe5fbf632858acd30ca19bb923290636d5f2ee6356b1e3")


def test_zaya_mixer_compiles_for_v5e(topo, as_on_a_tpu):
    """The mixer of the ZAYA1 cell, forward and backward, b 1 x s 16384 at
    the published widths: 8 query heads over 2 key heads of 128 computed in
    the latent (the grouped flash kernels at 4 query heads a key head),
    behind the two convolutions, the mean, the value shift, the
    normalisation and the rotation, all XLA's to compile."""
    from byteps_tpu.models.zaya import CompressedConvAttention

    one = SingleDeviceSharding(topo.devices[0])
    layer = CompressedConvAttention(8, 2, 128, 5e6, 0.5)
    x = jax.ShapeDtypeStruct((1, 16384, 2048), jnp.float32, sharding=one)
    params = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 2048))))
    text = jax.jit(jax.grad(
        lambda p, x: layer.apply(p, x).astype(jnp.float32).sum(),
        argnums=(0, 1))).lower(params, x).compile().as_text()
    # the flash pair and, since PR 64, conv0's forward kernel
    assert text.count("tpu_custom_call") == 3
    for kernel in ("bps_flash_fwd", "bps_flash_bwd", "bps_causal_conv_fwd"):
        assert kernel in text
    for kernel in ("bps_flash_dq", "bps_flash_dkv"):
        assert kernel not in text
    for scope in ("bps.cca.proj", "bps.cca.mix", "bps.cca.attend"):
        assert scope in text


def test_expert_share_given_logits_compiles_one_pass_for_v5e(topo):
    """The experts of a ZAYA1 layer, forward and backward as the block
    recomputes them: T 16384, top-1 by the caller's logits, experts 0..7 of
    16, D 2048, width 2048. Half the experts held: the bound is every
    assignment, 16,384 rows, one pass whatever the routing, still behind
    the choice between a pass and a loop; a rung under it (10,240 rows: an
    even load is 8,192) would save too few rows to be written out
    (``HELD_RUNG_MIN_SAVED``: on the chip it read +2.0% of the cell's peak
    memory and no shorter step, PERF.md section 6, PR 67)."""
    from byteps_tpu.parallel.moe import (dropless_moe_ffn, held_row_bound,
                                         held_row_rungs)

    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one)
              for shape, dtype in (((16384, 2048), jnp.float32),
                                   ((16384, 16), jnp.float32),
                                   ((8, 2048, 2048), jnp.float32),
                                   ((8, 2048, 2048), jnp.float32),
                                   ((8, 2048, 2048), jnp.float32))]
    assert held_row_bound(16384, 1, 8, 16) == 16384
    assert held_row_rungs(16384, 1, 8, 16) == (16384,)

    @jax.checkpoint
    def layer(x, logits, *weights):
        return dropless_moe_ffn(x, None, *weights, top_k=1,
                                logits=logits)[0]

    text = jax.jit(jax.value_and_grad(
        lambda *args: layer(*args).astype(jnp.float32).sum(),
        argnums=range(5))).lower(*shapes).compile().as_text()
    assert text.count(" conditional(") == text.count(" while(") == 2
    assert "bf16[16384,2048]" in text and "bf16[10240,2048]" not in text


def test_joyai_collective_step_compiles_for_one_v5e(topo, as_on_a_tpu):
    """make_train_step over JoyAIFlashModel at the cell's widths, b 1 x s
    8192, adamw, for one described chip — cut to the dense layer and the
    MTP module (an expert layer, both passes through the head), which is
    every kind of program the cell's step holds, so that the case stays in
    tier-1's time (the whole cut compiles in 53 s: ``compiled_bytes`` in
    the configuration's file)."""
    import sys

    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.lib import cell as cell_lib

    path = os.path.join(repo, "benchmark", "configs", "joyai-llm-flash")
    cfg = {**cell_lib.load_json(path + ".json"), "num_hidden_layers": 1}
    init, loss_fn = cell_lib.load_module(path + ".py", "joyai_config").build(
        cfg)
    tx = optax.adamw(1e-4)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    bps.init(mesh=mesh)
    step = make_train_step(loss_fn, tx)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, cfg["seq_len"]), jnp.int32)}
    compiled = step.lower(
        _described(mesh, params, P()),
        _described(mesh, jax.eval_shape(tx.init, params), P()),
        _described(mesh, batch, P(("dcn", "ici")))).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 6       # two mixers' kernels
    assert "bps.mtp" in text and "ragged-dot" in text


def test_nemotron_collective_step_compiles_for_one_v5e(topo, as_on_a_tpu):
    """make_train_step over NemotronHModel at the cell's widths and size, b
    1 x s 16384, adamw, for one described chip — cut to one layer of each
    kind (``ME*``: a Mamba-2 layer with the state-space scan as its kernels
    (PR 65), an ungated expert layer of 8 held experts with its shared expert,
    the attention layer at 16 query heads a key head, where the fused
    backward kernel stays), which is every kind of program the cell's step
    holds, so that the case stays in tier-1's time (the whole nine layers
    compile in 44 s to 14.26 GB: ``compiled_bytes`` in the configuration's
    file)."""
    import sys

    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.lib import cell as cell_lib

    path = os.path.join(repo, "benchmark", "configs",
                        "nemotron-3-nano-30b-a3b")
    cfg = {**cell_lib.load_json(path + ".json"), "num_hidden_layers": 3,
           "hybrid_override_pattern": "ME*"}
    assert cfg["seq_len"] == 16384
    init, loss_fn = cell_lib.load_module(
        path + ".py", "nemotron_config").build(cfg)
    tx = optax.adamw(1e-4)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    bps.init(mesh=mesh)
    step = make_train_step(loss_fn, tx)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, cfg["seq_len"]), jnp.int32)}
    compiled = step.lower(
        _described(mesh, params, P()),
        _described(mesh, jax.eval_shape(tx.init, params), P()),
        _described(mesh, batch, P(("dcn", "ici")))).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9
    text = compiled.as_text()
    assert "bps_flash_fwd" in text and "bps_flash_bwd" in text
    assert "bps_flash_dq" not in text       # the fused backward, not the pair
    assert "ragged-dot" in text
    for scope in ("bps.ssm.proj", "bps.ssm.prep", "bps.ssm.scan",
                  "bps.ssm.out", "bps.nattn.attend", "bps.nattn.proj",
                  "bps.moe.route", "bps.moe.shared"):
        assert scope in text, scope
    # no kernel of the delta-rule scans: this scan has its own (PR 65),
    # forward, the states-only walk and backward, and hands them [s, 6144]
    # as the convolution leaves it: no [s, 64, 64] tensor is left
    assert "bps_kda_recurrence" not in text and "bps_gdn" not in text
    for name in ("bps_ssd_scan_fwd", "bps_ssd_scan_states",
                 "bps_ssd_scan_bwd"):
        assert name in text, name
    assert "[1,16384,64,64]" not in text
    # ... and the convolution before it is a kernel forward (PR 64)
    assert "bps_causal_conv_fwd" in text
    # ... and the skip, gate and group norm after it a kernel each way (PR
    # 69), the forward's once more where the layer is recomputed, reading
    # ``x`` and ``z`` where they lie: neither is cut out
    assert text.count("bps_gated_norm_fwd") >= 2 and "bps_gated_norm_bwd" in text
    assert "f32[1,16384,4096]{2,1,0:T(8,128)} slice(" not in text
    assert "bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)} slice(" not in text


@pytest.mark.slow
def test_gpt2_124m_collective_step_compiles_for_one_v5e(topo):
    """The whole chip_smoke phase-1 program — make_train_step, GPT-2 124M,
    adamw, b8 x s512 — for one described chip, and it fits its 16 GB."""
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step
    from byteps_tpu.models import GPT2Small, lm_loss

    model = GPT2Small()
    tx = optax.adamw(1e-4)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    bps.init(mesh=mesh)
    step = make_train_step(lambda p, b: lm_loss(model.apply(p, b), b), tx)

    tokens = jax.ShapeDtypeStruct((8, 512), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    compiled = step.lower(
        _described(mesh, params, P()),
        _described(mesh, jax.eval_shape(tx.init, params), P()),
        _described(mesh, tokens, P(("dcn", "ici")))).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9


@pytest.mark.slow
def test_gpt2_124m_ps_gradients_leave_the_chip_row_major(topo, as_on_a_tpu):
    """The PS step's gradient program — ``ps_grad_step``, GPT-2 124M at the
    benchmark's b8 x s1024 — for one described chip: every gradient leaf
    comes out in a row-major layout, so it lands on the host C-contiguous
    and the PS leg pushes it from where it landed. In its own shape a
    ``[768, 12, 64]`` q/k/v kernel's gradient gets ``major_to_minor``
    (1, 2, 0) from the compiler — 36 leaves, 85 MB a step of transposing
    copy on the host (PERF.md, PR 49) — so those leave flat."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from byteps_tpu.jax.training import ps_grad_step
    from byteps_tpu.models import GPT2Small, lm_loss

    model = GPT2Small()
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    grad = ps_grad_step(
        jax.value_and_grad(lambda p, b: lm_loss(model.apply(p, b), b)),
        mesh, ("dcn", "ici"), True, lambda g: g)
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    lowered = grad.lower(_described(mesh, params, P()),
                         _described(mesh, tokens, P(("dcn", "ici"))))
    _, shapes = lowered.out_info
    _, formats = lowered.compile().output_formats
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) == 196
    assert sum(l.shape == (768, 12, 64) for l in leaves) == 36
    for leaf, out, fmt in zip(leaves, jax.tree_util.tree_leaves(shapes),
                              jax.tree_util.tree_leaves(formats)):
        assert out.size == leaf.size
        assert out.ndim == (leaf.ndim if leaf.ndim <= 2 else 1)
        assert fmt.layout.major_to_minor == tuple(range(out.ndim)), (
            leaf.shape, fmt)


def test_phi4_flash_collective_step_compiles_for_one_v5e(topo, as_on_a_tpu):
    """make_train_step over Phi4FlashModel at the cell's widths and size,
    the whole cut (published layers 0, 1, 16, 17, 18, 19: every kind of
    layer the model has), adamw, for one described chip: under the 15.0 GB
    the sequence length's rule asks (``compiled_bytes`` in the
    configuration's file), the flash kernels at 40 / 20 x 64 with the fused
    backward, the convolution's kernel at 5,120 channels, the selective
    scan as XLA's loops (no kernel of another scan), and every scope the
    cell's readers read."""
    import sys

    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import make_train_step

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.lib import cell as cell_lib

    path = os.path.join(repo, "benchmark", "configs",
                        "phi-4-mini-flash-reasoning")
    cfg = cell_lib.load_json(path + ".json")
    init, loss_fn = cell_lib.load_module(
        path + ".py", "phi4_flash_config").build(cfg)
    tx = optax.adamw(1e-4)
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("dcn", "ici"))
    bps.init(mesh=mesh)
    step = make_train_step(loss_fn, tx)
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((1, cfg["seq_len"]), jnp.int32)}
    compiled = step.lower(
        _described(mesh, params, P()),
        _described(mesh, jax.eval_shape(tx.init, params), P()),
        _described(mesh, batch, P(("dcn", "ici")))).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 15.0e9
    text = compiled.as_text()
    assert "bps_flash_fwd" in text and "bps_flash_bwd" in text
    assert "bps_flash_dq" not in text       # the fused backward, not the pair
    assert "bps_causal_conv_fwd" in text
    for scope in ("bps.sel.proj", "bps.sel.prep", "bps.sel.scan",
                  "bps.sel.out", "bps.dattn.proj", "bps.dattn.window",
                  "bps.dattn.full", "bps.dattn.cross", "bps.dattn.diff",
                  "bps.gmu"):
        assert scope in text, scope
    for other in ("bps_ssd_scan", "bps_kda_recurrence", "bps_gdn"):
        assert other not in text, other
