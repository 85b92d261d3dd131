"""Mixture-of-Experts FFN with expert parallelism (all-to-all dispatch).

Beyond-reference scope (SURVEY.md §2.7: EP absent from the reference);
opens the expert-parallel mesh axis the task brief asks for. GShard-shaped
design: top-1 gating with a capacity limit, one-hot dispatch/combine
einsums (MXU-friendly — no gathers/scatters in the hot path), and when an
``ep_axis`` is given the dispatched [experts, capacity, d] blocks ride two
``lax.all_to_all``s so each device runs only its local experts over the
full (global) token set.

Per-device code under ``shard_map`` when ``ep_axis`` is set; plain dense
computation otherwise.

``dropless_moe_ffn`` is the other design, for models that route every token
to its ``top_k`` experts whatever the load (OLMoE, Mixtral): no capacity and
no ``[T, E, C]`` one-hot (0.67 GB each way at 4096 tokens x 64 experts x 8),
but the assignments sorted by expert and the experts' two or three grouped
matmuls over the sorted rows. A device holds all experts or a contiguous
share of them (``first_expert``): it routes over all, computes its own
experts' part of the result and leaves the rest out; it has no ``ep_axis``
(no exchange) yet.
The shares that run today (``benchmark/configs``): 8 of 256 at top-8
(Kimi-Linear, JoyAI), 16 of 128 (Keye) and 16 of 256 (Laguna) at top-8, 32
of 512 at top-10 (Qwen3-Next), 8 of 16 at top-1 (ZAYA1), 16 of 64 at
top-8 (Mellum2: a quarter of the experts, two held assignments a token, a
pass of at most half of all T k rows) and 8 of 128 at top-6 (Nemotron-H, whose
experts are the layer's second body: ungated, ``down(relu(up(x))^2)``, two
grouped matmuls where SwiGLU has three).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.jax._compat import axis_size as _axis_size


def moe_dispatch(gate_logits: jax.Array, capacity: int,
                 _legacy_capacity: Optional[int] = None):
    """Top-1 dispatch/combine tensors.

    gate_logits: [T, E]. Returns (dispatch [T, E, C] one-hot,
    combine [T, E, C] gate-weighted, aux_loss scalar). Tokens beyond an
    expert's capacity are dropped (their combine weights are zero) — the
    standard capacity-factor contract.

    Accepts the pre-0.2 POSITIONAL 3-arg form ``moe_dispatch(x,
    gate_logits, capacity)`` (the token tensor was never used by the
    dispatch math) with a DeprecationWarning; remove the leading ``x``
    argument. Legacy calls that passed any of those args by keyword are
    not shimmed — they fail with Python's own "multiple values"
    TypeError at the call site.
    """
    if _legacy_capacity is not None:
        import warnings
        warnings.warn(
            "moe_dispatch(x, gate_logits, capacity) is deprecated; the "
            "leading token tensor was dropped — call "
            "moe_dispatch(gate_logits, capacity)",
            DeprecationWarning, stacklevel=2)
        gate_logits, capacity = capacity, _legacy_capacity
    import operator
    try:
        capacity = operator.index(capacity)  # any int-like, incl. 0-d jnp int
    except TypeError:
        # Catches any call where capacity ends up a tensor (e.g. a legacy
        # positional call that slipped the gate logits into this slot)
        # before it turns into a confusing deep-in-JAX error.
        raise TypeError(
            "moe_dispatch capacity must be a static int; got "
            f"{type(capacity).__name__}. Note the signature changed from "
            "moe_dispatch(x, gate_logits, capacity) to "
            "moe_dispatch(gate_logits, capacity) — drop the leading token "
            "tensor.") from None
    t, e = gate_logits.shape
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(gates, axis=-1)                    # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0        # [T, E]
    keep = (pos >= 0) & (pos < capacity)
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)               # [T, E, C]
    dispatch = slot * keep[..., None]
    gate_val = (gates * onehot).sum(-1, keepdims=True)     # [T, 1]
    combine = dispatch * gate_val[..., None]
    # load-balancing auxiliary loss (Shazeer et al.): mean_gate · frac
    density = onehot.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux = (density * density_proxy).sum() * (e ** 2) / e
    return dispatch, combine, aux


def moe_dispatch_top2(gate_logits: jax.Array, capacity: int):
    """Top-2 dispatch/combine tensors (GShard's original gating).

    gate_logits: [T, E]. Each token routes to its best TWO experts with
    combine weights renormalised over the CHOSEN pair (before capacity
    masking, as in GShard: a dropped second choice forfeits its share
    rather than re-inflating the first); second choices queue behind all
    first choices (GShard's position offset), so under capacity pressure
    first choices win slots. Returns
    (dispatch [T, E, C], combine [T, E, C], aux_loss).
    """
    t, e = gate_logits.shape
    gates = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_v, top_i = lax.top_k(gates, 2)                      # [T, 2]
    norm = top_v / jnp.maximum(top_v.sum(-1, keepdims=True), 1e-9)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    fill = jnp.zeros((e,), jnp.float32)  # slots taken by earlier choices
    for c in range(2):
        onehot = jax.nn.one_hot(top_i[:, c], e, dtype=jnp.float32)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + fill[None, :]) * onehot
        keep = onehot.astype(bool) & (pos < capacity)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32)
        d_c = slot * keep[..., None]
        dispatch = dispatch + d_c
        combine = combine + d_c * norm[:, c][:, None, None]
        fill = fill + onehot.sum(axis=0)

    # load balancing on FIRST choices (GShard): fraction routed x mean gate
    first = jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32)
    aux = (first.mean(0) * gates.mean(0)).sum() * (e ** 2) / e
    return dispatch, combine, aux


def moe_ffn(
    x: jax.Array,
    gate_w: jax.Array,
    w1: jax.Array,
    w2: jax.Array,
    *,
    capacity_factor: float = 1.25,
    ep_axis: Optional[str] = None,
    top_k: int = 1,
):
    """Top-1 (Switch) or top-2 (GShard) MoE feed-forward.

    x: [T, D] (local tokens); gate_w: [D, E]; w1: [E, D, H]; w2: [E, H, D].
    With ``ep_axis`` (size n, per-device code): E must be divisible by n;
    each device holds ALL expert weights but computes only its E/n local
    experts over the globally dispatched slots — pair with a sharded
    weight layout in real deployments. ``top_k=2`` routes each token to
    its two best experts (combine weights renormalised over the pair;
    size the capacity_factor ~2x accordingly). Returns ([T, D], aux_loss).
    """
    t, d = x.shape
    e = gate_w.shape[1]
    logits = x @ gate_w
    n = _axis_size(ep_axis) if ep_axis else 1
    # Per-DEVICE capacity (GShard): each device dispatches at most
    # cf·t_local/e slots per expert, keeping per-device slot volume at 1/n
    # of the dense problem (imbalance beyond cf is dropped, by design).
    capacity = max(1, int(capacity_factor * t / e))

    if top_k == 1:
        dispatch, combine, aux = moe_dispatch(logits, capacity)
    elif top_k == 2:
        dispatch, combine, aux = moe_dispatch_top2(logits, capacity)
    else:
        raise ValueError(f"top_k must be 1 or 2, got {top_k}")
    # [T, E, C] x [T, D] -> [E, C, D]
    slots = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))

    if ep_axis is None:
        h = jnp.einsum("ecd,edh->ech", slots, w1.astype(jnp.float32))
        h = jax.nn.gelu(h)
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(jnp.float32))
    else:
        if e % n != 0:
            raise ValueError(f"experts ({e}) must divide by '{ep_axis}' "
                             f"axis size ({n})")
        el = e // n
        me = lax.axis_index(ep_axis)
        # send each expert block to its owner; receive all devices' slots
        # for MY experts, stacked on the capacity-ish axis
        recv = lax.all_to_all(slots, ep_axis, split_axis=0, concat_axis=1,
                              tiled=True)                  # [El, n*C, D]
        w1_l = lax.dynamic_slice_in_dim(w1, me * el, el, 0)
        w2_l = lax.dynamic_slice_in_dim(w2, me * el, el, 0)
        h = jnp.einsum("ecd,edh->ech", recv, w1_l.astype(jnp.float32))
        h = jax.nn.gelu(h)
        out_l = jnp.einsum("ech,ehd->ecd", h, w2_l.astype(jnp.float32))
        # route results back to the tokens' home devices
        out = lax.all_to_all(out_l, ep_axis, split_axis=1, concat_axis=0,
                             tiled=True)                   # [E, C, D]

    y = jnp.einsum("tec,ecd->td", combine, out)
    if ep_axis is not None:
        aux = lax.pmean(aux, ep_axis)
    return y.astype(x.dtype), aux


# --------------------------------------------------------------------------
# Dropless top-k routing over grouped matmuls.

ROUTE_SCOPE = "bps.moe.route"      # router, top-k, sort, gather, combine
EXPERTS_SCOPE = "bps.moe.experts"  # the grouped matmuls and their casts
# the calls, at trace time, whose experts have no gate projection
UNGATED_SITES = "bps_moe_ungated_sites_total"


@jax.custom_vjp
def _permute(x: jax.Array, perm: jax.Array, inverse: jax.Array) -> jax.Array:
    """``x[perm]`` for a permutation and its inverse (``inverse[perm]`` =
    arange). Its gradient is the gather ``g[inverse]``; autodiff, which
    cannot know that ``perm`` has no repeats, would emit a scatter-add
    (3.7 against 1.1 ms for 32768 x 2048 bf16 on a v5e; PERF.md, PR 28)."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)

# A share's rows a pass over its even part T k H / E: the top rung of the
# ladder below and the stride of the loop beyond it. The cells that hold a
# share start at held loads of 0.87-1.36 of the even part (PERF.md section
# 5): 1 x would send JoyAI's 1.024 through two passes every step, 2 x leaves
# a balanced router (auxiliary loss or selection bias) room before the loop.
HELD_ROWS_OVER_EVEN = 2
HELD_ROWS_MULTIPLE = 512
# The rung under the bound, in eighths of it: 5/8 holds loads up to 1.25 of
# the even part where the bound is twice it. A pass gathers, masks,
# multiplies and scatter-adds its rows whatever the count, so a layer near
# its even part pays for 5/8 of the bound and not for all of it (Mellum2's
# step 591 -> 547 ms: PERF.md section 6, PR 67). One rung, and the bound
# left to the loop, by measurement: a pass written out costs a body a
# direction a layer to compile, load and hold. With the bound's pass written
# out as well the Mellum2 step compiled in 41.6 s where the parent's takes
# 31.6-40.8 and this form 35.9-37.1, loaded from the compile cache in 9.2
# for 8.2-8.5 and 8.4-8.8, and peaked at 12.023 GB for 12.039 and 11.831; a
# further rung at 3/4 (45.8-48.9 s, 10.0-10.4 s, 12.045 GB) was reached by
# one layer of one seed in five, at its window's end. The three forms' steps
# lie within 1.3 ms of each other (-45.5, -44.3 and -44.2 ms on the parent).
HELD_RUNG_EIGHTHS = 5
# The fewest rows the rung has to save (bound less rung) for a shape to get
# one, set between what was measured on either side of it. Above: Mellum2's
# rung saves 24,576 rows of 2304, Qwen3-Next's 7,680 of 2048 (its step 600.5
# -> 587.9 ms at the parent's memory and set-up). Below: ZAYA1's 6,144 of a
# 16,384-row pass (Keye's the same) read +2.0% of peak memory on the chip
# against a 1% bound under a ladder of three rungs and no shorter step, its
# load being at 2.0 by the window's end; Nemotron's 4,608, Laguna's 3,072
# and Kimi-Linear's and JoyAI's 1,536 are smaller still. Under the floor a
# shape keeps the bound's pass beside the loop, and its step lowers to the
# text it had before there was a rung.
HELD_RUNG_MIN_SAVED = 7168


def held_row_bound(t: int, top_k: int, held: int, e: int) -> int:
    """The most rows a share of ``held`` of ``e`` experts works on in one
    pass, the top rung of ``held_row_rungs`` and the stride of the loop
    that takes over beyond it: twice the even part of the assignments that
    reach the share, in 512s, and at most all T k. 4,096 of 65,536 for 8 of
    256 experts, 16,384 for 16 of 128; half of all rows for 16 of 64 at
    top-8, two held assignments a token (65,536 of 131,072 at 16,384
    tokens, 131,072 of 262,144 at 32,768); every row where half the experts
    or more are held."""
    rows = t * top_k
    room = -(-HELD_ROWS_OVER_EVEN * rows * held // e)
    return min(rows, -(-room // HELD_ROWS_MULTIPLE) * HELD_ROWS_MULTIPLE)


def held_row_rungs(t: int, top_k: int, held: int, e: int) -> tuple:
    """The sizes a share's pass comes in, ascending, the last of them
    ``held_row_bound``: a layer takes the first that holds the assignments
    that reached its held experts. Under the bound one rung,
    ``HELD_RUNG_EIGHTHS`` of it in 512s, where that lies at or over the
    even part T k H / E (no rung is sized for a routing that has fled the
    share) and saves ``HELD_RUNG_MIN_SAVED`` rows or more: (40,960, 65,536)
    for 16 of 64 experts at top-8 over 16,384 tokens; else the bound alone:
    (4,096,) for 8 of 256 at top-8 over 8,192."""
    bound = held_row_bound(t, top_k, held, e)
    even = -(-t * top_k * held // e)
    rung = -(-bound * HELD_RUNG_EIGHTHS
             // (8 * HELD_ROWS_MULTIPLE)) * HELD_ROWS_MULTIPLE
    if rung < even or bound - rung < HELD_RUNG_MIN_SAVED:
        return (bound,)
    return (rung, bound)


def _grouped_ffn(xs, weights, groups, rows, dtype):
    """An expert's body over rows sorted by expert, by the matrices it has:
    ``down(silu(gate(xs)) * up(xs))`` of ``weights`` (gate, up, down), three
    grouped matmuls, or the ungated ``down(relu(up(xs))^2)`` of (up, down),
    two. ``rows`` marks the rows inside ``groups`` where the groups do not
    cover them all."""
    with jax.named_scope(EXPERTS_SCOPE):
        # lax.ragged_dot: row i of the sorted rows times the matrix of its
        # group, float32 accumulation, result in `dtype`. The TPU compiler
        # makes one Mosaic kernel of each call (`ragged-dot` in the device
        # trace), forward, dgrad and the per-group wgrad alike; elsewhere
        # it is a masked dense product. Chosen over Pallas megablox by
        # measurement and for needing no import (PERF.md section 4).
        def grouped(lhs, w):
            out = lax.ragged_dot(lhs, w.astype(dtype), groups)
            # the TPU's kernel leaves rows beyond its groups undefined (x's
            # gradient came out 50 x too large unmasked; PERF.md, PR 33):
            # a share takes zeros there, forward and backward
            return out if rows is None else jnp.where(rows, out, 0)

        *first, w_down = weights
        if len(first) == 2:
            gate = grouped(xs, first[0])
            up = grouped(xs, first[1])
            return grouped(jax.nn.silu(gate) * up, w_down)
        return grouped(jnp.square(jax.nn.relu(grouped(xs, first[0]))),
                       w_down)


# jitted: the written-out pass and the loop's, forward and backward, in every
# expert layer of a model are this function at the same shapes, so one trace
# and one lowered function a rung serve them all (≈ 1 s less set-up on the
# chip's host; the compiled step is the same to the byte of its memory)
@partial(jax.jit, static_argnums=(0, 1))
def _held_pass(dtype, bound, start, y, x, top_w, weights, order, groups):
    """``y`` [T, D] float32 plus the held experts' part of the layer for
    the sorted rows ``start .. start + bound - 1``: those rows gathered
    from ``x`` (float32, so that its gradient adds in float32) by token,
    multiplied in the groups that fall inside the pass, and added to their
    tokens. Rows past the held experts' last are zero in and out."""
    top_k = top_w.shape[1]
    with jax.named_scope(ROUTE_SCOPE):
        # sorted row -> t*k + j
        first = lax.dynamic_slice_in_dim(order, start, bound)
        token = first // top_k
        ends = jnp.clip(jnp.cumsum(groups) - start, 0, bound)
        rows = (jnp.arange(bound) < ends[-1])[:, None]
        xs = jnp.where(rows, x[token].astype(dtype), 0)
        weight = top_w.reshape(-1)[first]
    ys = _grouped_ffn(xs, weights, jnp.diff(ends, prepend=0), rows, dtype)
    with jax.named_scope(ROUTE_SCOPE):
        return y.at[token].add(ys * weight[:, None])


def _pass_operands(bound, dtype, x, top_w, weights, order, groups):
    """What every pass reads, made once and under the scope that reads it:
    x in float32, the weights (the experts' two or three matrices, a tuple)
    in ``dtype``, the order padded to whole passes of the top rung."""
    with jax.named_scope(ROUTE_SCOPE):
        x = x.astype(jnp.float32)
    with jax.named_scope(EXPERTS_SCOPE):
        weights = tuple(w.astype(dtype) for w in weights)
    with jax.named_scope(ROUTE_SCOPE):
        order = jnp.pad(order, (0, -order.size % bound))
    return x, top_w, weights, order, groups


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_rows(rungs, dtype, *args):
    """A share's part of the layer, [T, D] float32: the sorted rows of the
    held experts, which come first, in one pass of the first of ``rungs``
    (``held_row_rungs``) where the assignments that reached them fit it,
    and else in passes of the last, the bound, as many as it takes — so
    whatever the routing no assignment is dropped and none beyond the held
    experts' is gathered or multiplied, and a layer near its even part does
    not move the bound's rows of zeros. Two bodies a direction behind one
    conditional on the count: the first rung's pass written out, and the
    loop, which would run it as well: around a ``while`` at the top level
    of a step the TPU compiler kept several blocks' recomputed residuals
    alive (15.2 against 12.0 GB compiled for the Kimi-Linear cell; PERF.md,
    PR 43), around a ``conditional`` it does not, so the loop stays inside
    a branch and the branches share their buffers. Where the bound has a
    rung under it the bound's own pass is the loop's first and has no body
    of its own (``HELD_RUNG_EIGHTHS`` has the measurement). Both sit outside
    the two scopes, which a pass opens itself: on a device trace neither
    carries one. Nothing but the arguments is kept for the backward pass,
    which makes the same choice from the same count, runs the same passes
    over ``jax.vjp`` of each and adds up the loop's gradients in float32."""
    rung, bound = rungs[0], rungs[-1]
    operands = _pass_operands(bound, dtype, *args)
    held_rows, y = args[-1].sum(), jnp.zeros(args[0].shape, jnp.float32)
    return lax.cond(
        held_rows <= rung,
        lambda: _held_pass(dtype, rung, jnp.int32(0), y, *operands),
        lambda: lax.while_loop(
            lambda at: at[0] < held_rows,
            lambda at: (at[0] + bound,
                        _held_pass(dtype, bound, at[0], at[1], *operands)),
            (jnp.int32(0), y))[1])


def _held_rows_fwd(rungs, dtype, *args):
    return _held_rows(rungs, dtype, *args), args


def _held_rows_bwd(rungs, dtype, args, g):
    rung, bound = rungs[0], rungs[-1]
    *floats, order, groups = _pass_operands(bound, dtype, *args)
    floats, held_rows = tuple(floats), groups.sum()

    def pulled(rows, start):
        """The float operands' gradients through the pass of ``rows`` rows
        at ``start``."""
        return jax.vjp(lambda *f: _held_pass(
            dtype, rows, start, jnp.zeros_like(g), *f, order, groups),
            *floats)[1](g)

    def summed():
        """Every pass's, added up in float32 and handed on as one pass's
        are: a weight's in ``dtype``, which is what a grouped matmul gives
        (float32 out of the choice would keep three float32 weights more a
        layer alive to the optimizer: + 0.9% of the Keye cell's memory)."""
        totals = lax.while_loop(
            lambda at: at[0] < held_rows,
            lambda at: (at[0] + bound, jax.tree_util.tree_map(
                lambda total, part: total + part.astype(jnp.float32),
                at[1], pulled(bound, at[0]))),
            (jnp.int32(0), jax.tree_util.tree_map(
                lambda f: jnp.zeros(f.shape, jnp.float32), floats)))[1]
        return jax.tree_util.tree_map(
            lambda total, f: total.astype(f.dtype), totals, floats)

    grads = lax.cond(held_rows <= rung,
                     lambda: pulled(rung, jnp.int32(0)), summed)
    return (*jax.tree_util.tree_map(
        lambda grad, arg: grad.astype(arg.dtype), grads, tuple(args[:3])),
        None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def dropless_moe_ffn(
    x: jax.Array,
    router_w: Optional[jax.Array],
    w_gate: Optional[jax.Array],
    w_up: jax.Array,
    w_down: jax.Array,
    *,
    top_k: int,
    dtype=jnp.bfloat16,
    first_expert: int = 0,
    norm_topk: bool = False,
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    norm_eps: float = 0.0,
    routed_scale: float = 1.0,
    logits: Optional[jax.Array] = None,
):
    """Dropless top-k expert layer: every token reaches its ``top_k``
    experts. An expert has one of two bodies, by the matrices it is given:
    SwiGLU, ``down(silu(gate(x)) * up(x))``, or with ``w_gate`` None the
    ungated ``down(relu(up(x))^2)`` (Nemotron-H's), two grouped matmuls
    where the other has three, through the same sort, passes and masks.

    x: [T, D]; router_w: [D, E]; w_gate, w_up: [H, D, M]; w_down:
    [H, M, D], the weights of the H <= E experts ``first_expert ..
    first_expert + H - 1`` held here; no biases. The router runs in float32
    at the highest matmul precision (which experts a token reaches must not
    turn on bf16 rounding): softmax over all E, ``lax.top_k``, and as
    combine weights the raw probabilities, or with ``norm_topk`` those
    renormalised over the chosen k. A caller whose router is more than one
    matrix (``models/zaya.py``'s reads a state handed from layer to layer)
    passes its own float32 ``logits`` [T, E] and None for ``router_w``:
    everything from the scores on is the same. ``scoring="sigmoid"`` is DeepSeek-V3's
    gate: the scores are ``sigmoid(logits)``, one expert's independent of
    the others'; ``select_bias`` [E] is added to the scores for the choice
    of the k and never to a weight (it balances the load without a loss);
    ``norm_eps`` joins the renormalisation's denominator (the source's
    1e-20) and ``routed_scale`` multiplies the weights. At their defaults
    the four change nothing. The assignments are sorted by expert
    (stable), the tokens gathered into that order, the experts' body
    computed as its grouped matmuls with ``dtype`` operands and float32
    accumulation, and the rows un-permuted and summed with their weights in
    float32.

    A share (H < E) routes over all E all the same and computes the
    assignments that fall to its own experts, all of them whatever the
    routing. The sort puts the held experts' rows first, and the share
    gathers, multiplies and adds to their tokens (float32) those rows
    alone, in one pass of the first size of ``held_row_rungs`` of the
    shapes that holds them — picked on the device from their count, so a
    layer near its even part moves 5/8 of ``held_row_bound``'s rows and not
    all of them — up to the bound, twice their even part, and in as many
    passes of the bound as the held rows take where the routing sends more
    (a loop on their count), never a row of an expert held elsewhere. Rows
    of a pass beyond the held experts' last are zero on the way in and out
    of every grouped matmul, so ``y`` is this share's part of the layer's
    output: the parts of shares that cover 0..E-1 add up to the whole
    layer's.

    Returns ``(y [T, D] in x's dtype, load_balance, z_loss, counts)``:
    ``load_balance`` = E / (T k) * sum_e counts_e * mean_t p[t, e] (1 when
    routing is uniform; gradient through p only; sigmoid scores are divided
    by their sum over E for it), ``z_loss`` =
    mean_t logsumexp(logits_t)^2, ``counts`` [E] int32 the assignments per
    expert over all E (they sum to T k).
    """
    t, d = x.shape
    if (logits is None) == (router_w is None):
        raise ValueError("give router_w or the caller's own logits, one of "
                         "the two")
    e = (router_w if logits is None else logits).shape[1]
    weights = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    if w_gate is None:
        from byteps_tpu.monitor import metrics

        metrics.inc_counter(UNGATED_SITES)
    held = w_up.shape[0]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k must be in 1..{e}, got {top_k}")
    if not 0 <= first_expert <= e - held:
        raise ValueError(f"experts {first_expert}..{first_expert + held - 1} "
                         f"are not among the router's {e}")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring must be softmax|sigmoid, got {scoring!r}")
    with jax.named_scope(ROUTE_SCOPE):
        if logits is None:
            logits = jnp.dot(x.astype(jnp.float32),
                             router_w.astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)   # [T, E]
        lse = jax.nn.logsumexp(logits, axis=-1)
        if scoring == "softmax":
            probs = scores = jnp.exp(logits - lse[:, None])
        else:
            scores = jax.nn.sigmoid(logits)
            probs = scores / scores.sum(axis=-1, keepdims=True)
        if select_bias is None:
            top_w, top_e = lax.top_k(scores, top_k)             # [T, k]
        else:
            _, top_e = lax.top_k(scores + select_bias, top_k)
            top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        if norm_topk:
            total = top_w.sum(axis=-1, keepdims=True)
            top_w = top_w / (total + norm_eps if norm_eps else total)
        if routed_scale != 1.0:
            top_w = top_w * routed_scale
        flat_e = sort_key = top_e.reshape(-1)                   # [T k]
        if held < e:
            # held experts 0..H-1 in their order, every other one as H: last
            local = flat_e - first_expert
            here = (local >= 0) & (local < held)
            top_w = jnp.where(here.reshape(t, top_k), top_w, 0.0)
            sort_key = jnp.where(here, local, held)
        order = jnp.argsort(sort_key, stable=True)  # sorted row -> t*k + j
        counts = (flat_e[:, None] == jnp.arange(e)[None, :]).sum(
            axis=0, dtype=jnp.int32)
        if held == e:
            back = jnp.argsort(order)               # t*k + j -> sorted row
            xs = _permute(jnp.repeat(x.astype(dtype), top_k, axis=0), order,
                          back)                                 # [T k, D]
        load_balance = (counts.astype(jnp.float32)
                        * probs.mean(axis=0)).sum() * (e / (t * top_k))
        z_loss = jnp.mean(lse * lse)
    if held == e:
        ys = _grouped_ffn(xs, weights, counts, None, dtype)
        with jax.named_scope(ROUTE_SCOPE):
            y = jnp.einsum("tkd,tk->td",
                           _permute(ys, back, order).reshape(t, top_k, d),
                           top_w, preferred_element_type=jnp.float32)
    else:
        y = _held_rows(
            held_row_rungs(t, top_k, held, e), dtype, x, top_w, weights,
            order,
            lax.slice_in_dim(counts, first_expert, first_expert + held))
    return y.astype(x.dtype), load_balance, z_loss, counts


def publish_moe_stats(moe_stats, held=None) -> dict:
    """Per-expert assignment counts (the ``"moe_stats"`` collection of a
    model applied with it mutable: every leaf an [E] count) to
    ``monitor/metrics.py``: gauge ``bps_moe_max_expert_load`` (the busiest
    expert's assignments over the mean, worst layer), counter
    ``bps_moe_assignments_total`` and, for a share ``held`` = (first expert,
    experts held), gauges ``bps_moe_held_load``: the assignments that reached
    the held experts over their even part T k H / E, all layers together,
    ``bps_moe_compact_share``: the share of layers whose held assignments
    fit ``held_row_bound``, the layers that take one pass over their rows
    and not several, and ``bps_moe_pass_rows_share``: the rows of the pass
    each layer's count picks (its rung of ``held_row_rungs``; beyond the
    bound the loop's passes together) over the bound, averaged over layers
    — 1.0 where every layer takes the bound, 0.625 where every layer takes
    the rung of 5/8. Returns what it published."""
    import numpy as np

    from byteps_tpu.monitor import metrics

    leaves = [np.asarray(c) for c in jax.tree_util.tree_leaves(moe_stats)]
    if not leaves:
        return {}
    out = {"bps_moe_max_expert_load":
           max(float(c.max() / c.mean()) for c in leaves),
           "bps_moe_assignments_total": float(sum(c.sum() for c in leaves))}
    if held is not None:
        first, n = held
        out["bps_moe_held_load"] = float(
            sum(c[first:first + n].sum() for c in leaves)
            / sum(c.sum() * n / c.size for c in leaves))
        # a layer's held rows and its rungs, which read t and top_k as their
        # product, the counts' sum
        layers = [(int(c[first:first + n].sum()),
                   held_row_rungs(int(c.sum()), 1, n, c.size))
                  for c in leaves]
        out["bps_moe_compact_share"] = float(np.mean([
            rows <= rungs[-1] for rows, rungs in layers]))
        out["bps_moe_pass_rows_share"] = float(np.mean([
            next((rung for rung in rungs if rows <= rung),
                 -(-rows // rungs[-1]) * rungs[-1]) / rungs[-1]
            for rows, rungs in layers]))
        for name in ("bps_moe_held_load", "bps_moe_compact_share",
                     "bps_moe_pass_rows_share"):
            metrics.set_gauge(name, out[name])
    metrics.set_gauge("bps_moe_max_expert_load",
                      out["bps_moe_max_expert_load"])
    metrics.inc_counter("bps_moe_assignments_total",
                        out["bps_moe_assignments_total"])
    return out
