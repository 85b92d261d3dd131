"""On the chip: ``full_attention`` at a cell's shapes against exact float32
attention — values and the three gradients, tensor by tensor — and the
wrong computations the tolerance must fail.

    chiprun --chips 1 -- python3 tools/attention_check.py [--seed N]

What a benchmark run cannot see (``benchmark/lib/reference.py`` compares
three losses to 2e-3, and at random weights a band one key off moves them by
3e-4: PERF.md, PR 47) is held here, on the device, for the compiled Mosaic
kernels the cell runs: the Laguna cell's two calls, b 1 x s 8,192 x 128 in
bf16 over 8 key heads — 64 query heads under a window of 512, 48 under the
causal triangle alone — and the latent call of the JoyAI and Kimi-Linear
cells, 32 heads over 32 key heads at s 8,192 with keys 192 wide and values
128 (PR 51), and, since PR 57, the GPT-2 cells' call, eight sequences of
1,024 x 12 heads of 64 (control: every head reading the key head after its
own). The reference is the benchmark's own plain one
(``benchmark/lib/plain_laguna.py::banded_attention``: the band as a mask
over all keys, the group as an axis, blocks of 256 queries) on the same
bf16 operands in float32 at the highest matmul precision, nothing of
``byteps_tpu`` in it.

The measure, for out, dQ, dK and dV each: ``|got - want|_2 / |want|_2``.
Operands are standard normal, so a query's logits spread over about a unit
and one key at the band's edge weighs 1/850 on average and a sixth now and
then: a key more or less moves every tensor by 3.8-4.0%, where the kernels
read 0.21-0.34% (bf16 outputs, bf16 probabilities) and the other layout of a
group 130%. ``TOLERANCE`` lies between (my chip run, PR 47: PERF.md section
6 has every reading). The precision shows in ``out`` alone, which carries
one rounding and not three: the kernels read 0.207-0.216%, the reference
with bf16 probabilities (the cell's own precision) 0.226-0.231%, with bf16
logits and statistics 0.40-0.44%; ``OUT_TOLERANCE`` lies between. The run
fails — exit 1, ``"ok": false`` — if a kernel's tensor reads above its
tolerance or a control below it. The controls are the reference computed
wrongly, not the kernel: a window one key short and one key long (the
windowed call), query head i reading key head ``i % key heads``, logits
scaled by the value width where it is not the key width (the latent call:
128^-1/2 for 192^-1/2), and bf16 logits and softmax statistics (on ``out``).

Since PR 55 one case more holds a whole mixer, not the kernels alone:
``check_cca`` compiles one layer of ``models/zaya.py::
CompressedConvAttention`` at the ZAYA1 cell's shapes — [1, 16384, 2048] in,
8 query heads over 2 key heads of 128 in the latent, bf16 operands — and
compares ``o W_o`` and the gradients of ``h``, ``W_q``, ``W_k``, ``W_v2``,
conv1's taps and the temperature with ``benchmark/lib/plain_zaya.py::cca``
in float32 at the highest matmul precision, on weights moved off their
initial zeros (temperature -0.3 | 0.3, biases 0.1 sigma). Its controls are
that reference computed wrongly: the value shift dropped, conv1's two taps
swapped (a convolution that reads token t + 1's tap at t), the q-k mean
left out, the temperature ignored, and the normalisation carried in bf16
(row, squares, running sum, root and quotient each rounded). Each must read
above ``CCA_TOLERANCE`` in some tensor and the mixer below it in all: the
mixer reads 0.66-0.96% (bf16 projections, bf16 q^ and k^ into the kernels),
the bf16 normalisation 2.6-4.0% in every tensor and the four others 31-298%
(my chip runs, PR 55: PERF.md section 6 has every reading).

Since PR 58 two cases more are the Mellum2 cell's calls (``windowed_1024``,
``global_32_over_4``): 32 query heads over 4 key heads of 128, the cell's
two sequences of 8,192 in one call, under a window of 1024 and under the
causal triangle; ``--cases a,b`` runs the named cases alone.

Since PR 71 two cases more are the Phi-4-mini-flash cell's calls
(``dattn_windowed_512``, ``dattn_causal``): differential attention's two
softmax maps as the model sends them, 40 query heads over 20 key heads of
64 — the 64-wide kernels GPT-2 runs, here grouped and, in one, under a
window of 512 — one sequence of 8,192; their controls are the band one key
off and head i reading key head ``i % 20``.

One JSON line a case, then ``{"ok": ..., "device": ...}``; off the chip the
kernels are interpreted at a small size (``tests/test_attention_check.py``).
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOLERANCE = 1.2e-2          # every tensor: the band, the grouping
OUT_TOLERANCE = 3.0e-3      # out alone: float32 logits and statistics
TENSORS = ("out", "dq", "dk", "dv")
CCA_TOLERANCE = 1.6e-2      # the mixer: every tensor under, every control over
CCA_TENSORS = ("out", "dh", "dW_q", "dW_k", "dW_v2", "dconv1", "dtheta")
CCA_CONTROLS = {"value_shift_dropped": {"value_shift": False},
                "conv1_taps_swapped": {"swap_taps": True},
                "qk_mean_left_out": {"qk_mean": False},
                "temperature_ignored": {"temperature": False},
                "bf16_normalisation": {"norm_dtype": "bfloat16"}}


# window None: the causal triangle; value_dim None: values as wide as keys;
# batch: sequences in the call, each checked against the reference
Case = collections.namedtuple(
    "Case", "name seq heads kv_heads head_dim window value_dim batch",
    defaults=(None, 1))


# the Laguna cell's two calls (benchmark/configs/laguna-xs.2.json) and the
# latent one of joyai-llm-flash.json and kimi-linear-48b-a3b.json
# the ZAYA1 cell's mixer (benchmark/configs/zaya1-8b.json)
Mixer = collections.namedtuple(
    "Mixer", "seq d_model heads kv_heads head_dim rope_theta rotary_factor")
CCA_CELL = Mixer(16384, 2048, 8, 2, 128, 5e6, 0.5)

CELL_CASES = (Case("windowed", 8192, 64, 8, 128, 512),
              Case("global", 8192, 48, 8, 128, None),
              Case("latent", 8192, 32, 32, 192, None, 128),
              # the GPT-2 cells' call (gpt2-124m.json): the width the most
              # cells share, eight sequences in one call
              Case("dense", 1024, 12, 12, 64, None, batch=8),
              # the Mellum2 cell's two calls (mellum2-12b-a2.5b.json): 32
              # query heads over 4 key heads, two sequences in one call
              Case("windowed_1024", 8192, 32, 4, 128, 1024, batch=2),
              Case("global_32_over_4", 8192, 32, 4, 128, None, batch=2),
              # the Phi-4-mini-flash cell's calls
              # (phi-4-mini-flash-reasoning.json): differential attention's
              # two maps, 40 query heads over 20 key heads of 64
              Case("dattn_windowed_512", 8192, 40, 20, 64, 512),
              Case("dattn_causal", 8192, 40, 20, 64, None))


def _relative(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(case: Case, seed: int, attend=None) -> dict:
    """One case: the program's attention (``attend(q, k, v, window)``, by
    default ``full_attention``) against the float32 reference, and the
    controls. ``attend`` is a test's handle on the kernel form off the chip."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.plain_laguna import banded_attention

    s, h, kv, d = case.seq, case.heads, case.kv_heads, case.head_dim
    d_v, b = case.value_dim or d, case.batch
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, s, kv, d_v), jnp.bfloat16)
    w = jax.random.normal(keys[3], (b, s, h, d_v), jnp.float32)  # cotangent

    if attend is None:
        from byteps_tpu.parallel import full_attention

        def attend(q, k, v, window):
            return full_attention(q, k, v, causal=True, window=window)

    def run(fn):
        """(out, dq, dk, dv) of ``fn(q, k, v) -> [b, s, h, d_v]``."""
        def scalar(q, k, v, w):
            out = fn(q, k, v)
            return (out.astype(jnp.float32) * w).sum(), out

        (_, out), grads = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1, 2), has_aux=True))(q, k, v, w)
        return jax.device_get((out, *grads))

    groups = h // kv

    def plain(window=case.window, dtype=jnp.float32,
              logits_dtype=jnp.float32, interleaved=False, scale_dim=d,
              next_key_head=False):
        """The plain reference as a function of the same operands, a
        sequence at a time; the keyword arguments are the ways to compute
        it wrongly. Its layout is [s, key heads, group, d]: query head i is
        member ``i % group`` of key head ``i // group``, or,
        ``interleaved``, head i reads key head ``i % key heads`` — the
        other way to lay a group out — or, ``next_key_head``, the key head
        after its own. The reference scales by the key width,
        ``scale_dim`` another width's; its values are as wide as its keys,
        so narrower ones go in under zeros and the output's first ``d_v``
        columns come out."""
        def one(q, k, v):
            grouped = (jnp.swapaxes(q.reshape(s, groups, kv, d), 1, 2)
                       if interleaved else q.reshape(s, kv, groups, d))
            padded_v = jnp.pad(v, ((0, 0), (0, 0), (0, d - d_v)))
            if next_key_head:
                k, padded_v = (jnp.roll(x, -1, axis=1)
                               for x in (k, padded_v))
            with jax.default_matmul_precision("highest"):
                out = banded_attention(
                    grouped.astype(dtype) * (d / scale_dim) ** 0.5,
                    k.astype(dtype), padded_v.astype(dtype),
                    window=window, dtype=dtype,
                    query_block=min(256, s), logits_dtype=logits_dtype)
            if interleaved:
                out = jnp.swapaxes(out, 1, 2)
            return out.reshape(s, h, d)[..., :d_v]

        return jax.vmap(one)

    lowered = jax.jit(lambda q, k, v: attend(q, k, v, case.window)).lower(
        q, k, v).as_text()
    got = run(lambda q, k, v: attend(q, k, v, case.window))
    want = run(plain())
    record = {
        "case": case._asdict(), "seed": seed,
        "kernel_in_program": "tpu_custom_call" in lowered,
        "kernel": dict(zip(TENSORS, map(_relative, got, want)))}

    controls = {}
    if case.window is not None:
        controls["window_minus_1"] = plain(window=case.window - 1)
        controls["window_plus_1"] = plain(window=case.window + 1)
    if kv > 1 and groups > 1:
        controls["heads_interleaved"] = plain(interleaved=True)
    if d_v != d:
        controls["scale_of_value_width"] = plain(scale_dim=d_v)
    if not controls:
        # neither a band, nor a group, nor a second width to get wrong
        controls["next_key_head"] = plain(next_key_head=True)
    def readings(fn):
        return dict(zip(TENSORS, map(_relative, run(fn), want)))

    record["controls"] = {name: readings(fn)
                          for name, fn in controls.items()}
    # the cell's own precision, reported; one precision below, a control
    record["bf16_probabilities"] = readings(plain(dtype=jnp.bfloat16))
    record["bf16_logits_and_statistics"] = readings(plain(
        dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16))
    record["tolerance"], record["out_tolerance"] = TOLERANCE, OUT_TOLERANCE
    record["ok"] = bool(
        max(record["kernel"].values()) <= TOLERANCE
        and record["kernel"]["out"] <= OUT_TOLERANCE
        and all(max(c.values()) > TOLERANCE
                for c in record["controls"].values())
        and record["bf16_logits_and_statistics"]["out"] > OUT_TOLERANCE)
    return record


def check_cca(seed: int, size: Mixer = CCA_CELL, dtype=None) -> dict:
    """One layer's compiled mixer against the plain one, and the controls.
    ``size`` and ``dtype`` (bf16 unless given) are a test's handle off the
    chip."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import plain_zaya
    from benchmark.lib.plain_qwen3_next import rotary_of
    from byteps_tpu.models.zaya import CompressedConvAttention

    layer = CompressedConvAttention(
        size.heads, size.kv_heads, size.head_dim, size.rope_theta,
        size.rotary_factor, dtype=dtype or jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(keys[0], (1, size.seq, size.d_model), jnp.float32)
    w = jax.random.normal(keys[1], h.shape, jnp.float32)      # cotangent
    p = dict(jax.jit(layer.init)(keys[2], h[:, :8])["params"])
    p["temperature"] = jnp.where(
        jnp.arange(size.kv_heads) % 2 == 0, -0.3, 0.3).astype(jnp.float32)
    for name, key in zip(("conv0_bias", "conv1_bias"), keys[3:]):
        p[name] = 0.1 * jax.random.normal(key, p[name].shape, jnp.float32)
    rotary = rotary_of(size.head_dim, size.rope_theta, size.rotary_factor)

    def plain(**control):
        control = {key: jnp.dtype(value) if key == "norm_dtype" else value
                   for key, value in control.items()}

        def fn(p, h):
            with jax.default_matmul_precision("highest"):
                return plain_zaya.cca(
                    h[0], p, head_dim=size.head_dim, rotary=rotary,
                    dtype=jnp.float32, query_block=min(128, size.seq),
                    **control)[None]

        return fn

    def run(fn):
        def scalar(p, h):
            out = fn(p, h)
            return (out.astype(jnp.float32) * w).sum(), out

        (_, out), (dp, dh) = jax.jit(jax.value_and_grad(
            scalar, argnums=(0, 1), has_aux=True))(p, h)
        return jax.device_get((
            out, dh, dp["q"]["kernel"], dp["k"]["kernel"],
            dp["v2"]["kernel"], dp["conv1"], dp["temperature"]))

    def program(p, h):
        return layer.apply({"params": p}, h)

    want = run(plain())

    def readings(fn):
        return dict(zip(CCA_TENSORS, map(_relative, run(fn), want)))

    record = {
        "case": {"name": "cca", **size._asdict()}, "seed": seed,
        "kernel_in_program": "tpu_custom_call" in jax.jit(program).lower(
            p, h).as_text(),
        "mixer": readings(program),
        "controls": {name: readings(plain(**control))
                     for name, control in CCA_CONTROLS.items()},
        "tolerance": CCA_TOLERANCE}
    record["ok"] = bool(
        max(record["mixer"].values()) <= CCA_TOLERANCE
        and all(max(c.values()) > CCA_TOLERANCE
                for c in record["controls"].values()))
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cases", default="", help="comma-separated names of "
                    "CELL_CASES (and 'cca'); empty: all of them")
    args = ap.parse_args()
    wanted = set(filter(None, args.cases.split(",")))

    import jax

    device = jax.devices()[0]
    ok = device.platform == "tpu"        # never a CPU's figures by mistake
    for case in CELL_CASES if ok else ():
        if wanted and case.name not in wanted:
            continue
        record = check(case, args.seed)
        ok = ok and record["ok"] and record["kernel_in_program"]
        print(json.dumps(record), flush=True)
    if ok and (not wanted or "cca" in wanted):
        record = check_cca(args.seed)
        ok = record["ok"] and record["kernel_in_program"]
        print(json.dumps(record), flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": device.platform, "kind": device.device_kind}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
