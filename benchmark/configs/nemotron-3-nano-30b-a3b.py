"""NVIDIA-Nemotron-3-Nano-30B-A3B, one chip's share of a 16-chip deployment
(experts 0..7 of 128; rows 0..16,383 of the vocabulary, which 8 chips
divide), cut in depth to published layers 0-8, ``M E M E M * E M E``: four
Mamba-2 layers, four expert layers and one attention layer, each ONE mixer
under one norm. The program's model, its batches, its plain reference and
its operations per token. Sizes come from ``nemotron-3-nano-30b-a3b.json``
(``cfg``).

Parameters by hand (d 2688; the file's ``n_params``). A Mamba-2 layer: the
in-projection 2688 x (4096 + 6144 + 64) = 27,697,152 (here ``in`` 2688 x
10,240 and ``dt`` 2688 x 64); the convolution 4 x 6144 + 6144 (its bias) =
30,720; ``A_log``, ``D``, ``dt_bias`` 3 x 64; the gated norm 4096; the
out-projection 4096 x 2688 = 11,010,048; the layer's norm 2688:
**38,744,896**. An attention layer: ``W_q`` and ``W_o`` 2 x 2688 x 4096 =
22,020,096; ``W_k`` and ``W_v`` 2 x 2688 x 256 = 1,376,256; the norm 2688:
**23,399,040**. An expert 2 x 2688 x 1856 = **9,977,856** (two matrices: no
gate projection). An expert layer held here: router 2688 x 128 and its
selection bias 128 = 344,192; the shared expert 2 x 2688 x 3712 =
19,955,712; the norm 2688; 8 experts 79,822,848: **100,125,440**. Nine
layers 4 x 38,744,896 + 23,399,040 + 4 x 100,125,440 = 578,880,384;
embedding + head + final norm 2 x 16,384 x 2688 + 2688 = 88,083,072:
**666,963,456**. Published, 52 layers (23 + 23 + 6) with all 128 experts
(1,297,468,160 an expert layer: ISSUE 63's 1,297,468,288 is 128 over, its
total right) and 131,072 rows: 23 x 38,744,896 + 6 x 23,399,040 + 23 x
1,297,468,160 + 2 x 131,072 x 2688 + 2688 = **31,577,940,288** (the card's
31.6 B).
"""

from functools import partial

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``): the ``ssm`` and ``rmoe`` readers
# both ask, the first to ask pays. ``lib/cell.py`` hands a reader neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 16 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _model(cfg):
    from byteps_tpu.models import NemotronHModel

    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names one mixer a layer")
    if (cfg["n_group"], cfg["topk_group"]) != (1, 1) or not (
            cfg["norm_topk_prob"] and cfg["use_conv_bias"]):
        raise ValueError("NemotronHModel's router has no group limit and "
                         "renormalises; its convolution has a bias")
    if any(cfg[k] for k in ("use_bias", "mamba_proj_bias", "mlp_bias",
                            "attention_bias")):
        raise ValueError("NemotronHModel has no bias but the convolution's")
    if (cfg["mlp_hidden_act"], cfg["n_shared_experts"]) != ("relu2", 1):
        raise ValueError("NemotronHModel's experts are relu^2, one shared")
    return NemotronHModel(
        vocab_size=cfg["vocab_size"], pattern=pattern,
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        num_experts=cfg["n_routed_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"],
        shared_mlp_dim=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        first_expert=FIRST_EXPERT, conv_kernel=cfg["conv_kernel"],
        chunk=cfg["ssm_chunk"], loss_rows=cfg["loss_rows"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        eps=cfg["layer_norm_epsilon"])


def _init(cfg, model):
    """``init(key) -> params``: the model's own initialisation, then the
    selection biases balanced (``cfg["router_balance"]``, whose ``why`` has
    the reason): ``passes`` times, every expert layer's bias moves by
    ``rate`` x log(the expert's assignments over the even part), the log
    held to +-1, against one sequence of uniform tokens drawn from ``key``
    and not from the run's batches — the bias is state a deployment
    holds, as the weights are, and all of it comes from the seed."""
    import jax

    passes, rate = (cfg["router_balance"][k] for k in ("passes", "rate"))

    def balance(tokens, _, params):
        counted = model.apply(params, tokens, mutable=["moe_stats"])[1]
        layers = dict(params["params"])
        for name, stats in counted["moe_stats"].items():
            (counts,) = stats["moe"]["counts"]
            load = counts / counts.mean()
            moe = dict(layers[name]["moe"])
            # an expert no token chose reads log 0: -1 after the clip
            moe["select_bias"] -= rate * jnp.clip(jnp.log(load), -1.0, 1.0)
            layers[name] = {**layers[name], "moe": moe}
        return {"params": layers}

    def init(key):
        tokens = jax.random.randint(
            jax.random.fold_in(key, 1), (1, cfg["seq_len"]), 0,
            cfg["vocab_size"])
        return jax.lax.fori_loop(0, passes, partial(balance, tokens),
                                 model.init(key, EXAMPLE))

    return init


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import nemotron_h_loss

    model = _model(cfg)

    def loss_fn(params, batch):
        return nemotron_h_loss(model.apply(params, batch["tokens"]))

    return _init(cfg, model), loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats", "ssm_stats"}``, the model's two
    collections as numpy, under one jit that returns them alone (the
    compiler drops the head). Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)
        init = _init(cfg, model)

        @jax.jit
        def stats(key, tokens):
            return model.apply(init(key), tokens,
                               mutable=["moe_stats", "ssm_stats"])[1]

        STATS.update(jax.tree_util.tree_map(np.asarray, dict(stats(
            jax.random.PRNGKey(FIRST["seed"]), FIRST["tokens"][:rows]))))
    return STATS


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences, one document each: uniform
    tokens over this chip's slice of the vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """[rows, s-1], summing to 1: the loss is the mean over rows x (s-1)
    positions, so the shards need not be told apart."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    """The plain step's loss, in float32 at the highest matmul precision
    whatever ``compute_dtype`` says (ISSUE 63)."""
    from benchmark.lib.plain_nemotron_h import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"], state_size=cfg["ssm_state_size"],
            head_dim=cfg["head_dim"], top_k=cfg["num_experts_per_tok"],
            first_expert=FIRST_EXPERT,
            routed_scale=cfg["routed_scaling_factor"],
            eps=cfg["layer_norm_epsilon"], dtype=jnp.float32,
            **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the recurrence token by token, the causal triangle's pairs,
    the expected held experts, the sliced head at the rows with a target —
    so that a chunk's extra products, blocks above the diagonal, rows beyond
    the held groups and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). A Mamba-2 mixer's projections 27,697,152 + 11,010,048 =
    38,707,200; its recurrence, per head and token 5 x 128 x 64 (decay 1,
    the rank-one write 2, ``S^T C`` 2) = 40,960, x 64 heads x 3 (forward,
    and twice that backward) = 7,864,320. The attention mixer's projections
    22,020,096 + 1,376,256 = 23,396,352; a causal (query, key) pair of one
    head costs 2 x 128 (its score) + 2 x 128 (its value) forward and twice
    that backward, 1,536: 49,152 over 32 heads, and a sequence of 16,384
    has 134,225,920 pairs: 6.597 TFLOP. An expert layer: router 344,064,
    the shared expert 19,955,712, of a token's 6 experts the 6 x 8 / 128 =
    3/8 expected here, 3,741,696: 24,041,472. The head 2688 x 16,384 =
    44,040,192 at the s - 1 rows with a target (embedding look-ups are not
    matmuls). A row of the stack 6 x (4 x 38,707,200 + 23,396,352 + 4 x
    24,041,472) + 4 x 7,864,320 = 1,677,803,520; over s 16,384:
    1,677,803,520 + 402,677,760 (the pairs) + 264,225,024 (the head) =
    **2,344,706,304** a token, 38.42 TFLOP a step (17% the score and value
    products, 40% the four Mamba-2 layers' projections, 1.3% their
    recurrence, 25% the expert layers, 20 of it the shared expert, 11%
    the head); over s 8,192 the pairs are 201,351,168 and a token
    2,143,363,584, 17.56 TFLOP a step. ISSUE 63 estimated 2.35 GFLOP a
    token at 16,384 (1.91 of matmuls, 0.40 of the attention layer, 0.03 of
    recurrence): the same to its three digits."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    ssm_heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner, bc = ssm_heads * p, cfg["n_groups"] * cfg["ssm_state_size"]
    mamba = d * (2 * inner + 2 * bc + ssm_heads) + inner * d
    recurrence = 3 * 5 * ssm_heads * cfg["ssm_state_size"] * p
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    attention = 2 * d * (heads + cfg["num_key_value_heads"]) * head_dim
    pairs = 6 * 2 * head_dim * heads * s * (s + 1) // 2
    moe = (d * cfg["n_routed_experts"]
           + 2 * d * cfg["moe_shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * cfg["num_local_experts"]
           * 2 * d * cfg["moe_intermediate_size"] // cfg["n_routed_experts"])
    pattern = cfg["hybrid_override_pattern"]
    row = (pattern.count("M") * (6 * mamba + recurrence)
           + pattern.count("*") * 6 * attention
           + pattern.count("E") * 6 * moe)
    return (s * row + pattern.count("*") * pairs
            + (s - 1) * 6 * d * cfg["vocab_size"]) // s
