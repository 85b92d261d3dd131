"""Qwen3-Next-80B-A3B-Instruct, one chip's share of a 16-chip deployment
(experts 0..31 of 512; rows 0..18,991 of the vocabulary, which 8 chips
divide), cut in depth to one whole period — three Gated DeltaNet layers and
one gated attention layer, each with an expert layer: the program's model,
its batches, its plain reference and its operations per token. Sizes come
from ``qwen3-next-80b-a3b.json`` (``cfg``).

Parameters by hand (d 2048; the file's ``n_params``). A Gated DeltaNet
mixer: ``W_qkvz`` 2048 x (2048 + 2048 + 4096 + 4096) = 25,165,824; ``W_ba``
2048 x 64 = 131,072; the convolution 4 x 8192 = 32,768; ``A_log`` 32 and
``dt_bias`` 32; the head norm 128; ``W_o`` 4096 x 2048 = 8,388,608:
**33,718,464**. A gated attention mixer: ``W_q`` 2048 x 8192 = 16,777,216;
``W_k`` and ``W_v`` 2 x 2048 x 512 = 2,097,152; ``W_o`` 8,388,608; the q and
k norms 2 x 256: **27,263,488**. An expert layer held here: router 2048 x
512 = 1,048,576; the shared expert 3 x 2048 x 512 = 3,145,728 and its gate
2,048; 32 experts of 3,145,728 = 100,663,296: **104,859,648**. A layer's two
norms 4,096. A Gated DeltaNet layer 138,582,208, the attention layer
132,127,232, four layers 547,873,856; embedding + head + final norm 2 x
18,992 x 2048 + 2048 = 77,793,280: **625,667,136**. Published, 48 layers
(36 + 12) with all 512 experts (1,614,811,136 of experts, router, shared
expert and gate a layer) and 151,936 rows: 36 x 33,718,464 + 12 x
27,263,488 + 48 x 4,096 + 48 x 1,614,811,136 + 2 x 151,936 x 2048 + 2048 =
**79,674,391,296** (the card's 80 B; the MTP module is not in it).
"""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``): the ``gdn`` and ``eshare`` readers
# both ask, the first to ask pays. ``lib/cell.py`` hands a reader neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 16 that share a layer
AUX_WEIGHT = 0.001     # the load-balance loss's (assumed.aux_loss)
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _kinds(cfg):
    from byteps_tpu.models.qwen3_next import layer_kinds

    return layer_kinds(cfg["full_attention_interval"],
                       cfg["num_hidden_layers"])


def _model(cfg):
    from byteps_tpu.models import Qwen3NextModel

    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("Qwen3NextModel has an expert layer in every block")
    if cfg["rope_scaling"] is not None or not cfg["norm_topk_prob"]:
        raise ValueError("Qwen3NextModel scales no rotary frequency and "
                         "renormalises the chosen experts' weights")
    return Qwen3NextModel(
        vocab_size=cfg["vocab_size"], layer_kinds=_kinds(cfg),
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=float(cfg["rope_theta"]),
        rotary_factor=cfg["partial_rotary_factor"],
        key_heads=cfg["linear_num_key_heads"],
        value_heads=cfg["linear_num_value_heads"],
        key_dim=cfg["linear_key_head_dim"],
        value_dim=cfg["linear_value_head_dim"],
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"],
        shared_mlp_dim=cfg["shared_expert_intermediate_size"],
        first_expert=FIRST_EXPERT,
        conv_kernel=cfg["linear_conv_kernel_dim"], chunk=cfg["gdn_chunk"],
        loss_rows=cfg["loss_rows"], dtype=jnp.dtype(cfg["compute_dtype"]),
        eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import qwen3_next_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return qwen3_next_loss(model.apply(params, batch["tokens"]),
                               load_balance_weight=AUX_WEIGHT)

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats", "kda_stats"}``, the model's two
    collections as numpy, under one jit that returns them alone (the
    compiler drops the head). Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["moe_stats", "kda_stats"])[1]

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
    positions (plus the load-balance term, which is one number a batch), so
    the shards need not be told apart."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    """The plain step's loss, in float32 at the highest matmul precision
    whatever ``compute_dtype`` says (ISSUE 50): it fits the chip beside
    nothing else at the timed size, and the program's bf16 losses lie within
    2e-4 of it (PERF.md section 6, PR 50)."""
    from benchmark.lib.plain_qwen3_next import causal_lm_nll

    def weighted_loss(params, batch):
        nll, load_balance = causal_lm_nll(
            params, batch["tokens"], key_dim=cfg["linear_key_head_dim"],
            head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
            partial_rotary_factor=cfg["partial_rotary_factor"],
            top_k=cfg["num_experts_per_tok"], first_expert=FIRST_EXPERT,
            eps=cfg["rms_norm_eps"], dtype=jnp.float32,
            **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum() + AUX_WEIGHT * load_balance

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the recurrence token by token, the causal triangle's pairs,
    the expected held experts, the sliced head at the rows with a target —
    so that a chunk's extra products, blocks above the diagonal, rows beyond
    the held groups and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). A Gated DeltaNet mixer's projections (the parameters above
    less the convolution, ``A_log``, ``dt_bias`` and the norm): 25,165,824
    + 131,072 + 8,388,608 = 33,685,504; its recurrence, per value head and
    token 7 x 128 x 128 (decay 1, S^T k 2, the rank-one update 2, S^T q 2)
    = 114,688, x 32 heads x 3 (forward, and twice that backward) =
    11,010,048. A gated attention mixer's projections 16,777,216 +
    2,097,152 + 8,388,608 = 27,262,976; a causal (query, key) pair of one
    head costs 2 x 256 (its score) + 2 x 256 (its value) forward and twice
    that backward, 3,072: 49,152 over 16 heads, and a sequence of 16,384 has
    134,225,920 pairs: 6.597 TFLOP. An expert layer: router 1,048,576, the
    shared expert 3,145,728 and its gate 2,048, of a token's 10 experts the
    10 x 32 / 512 = 5/8 expected here, 1,966,080: 6,162,432. The head 2048 x
    18,992 = 38,895,616 at the s - 1 rows with a target (embedding look-ups
    are not matmuls). A row of the stack 6 x (3 x 33,685,504 + 27,262,976 +
    4 x 6,162,432) + 3 x 11,010,048 = 950,845,440; over s 16,384:
    950,845,440 + 402,677,760 (the pairs) + 233,359,452 (the head) =
    **1,586,882,652** a token, 26.00 TFLOP a step (25% the score and value
    products, 38% the three recurrent layers' projections, 2.1% their
    recurrence, 15% the head); over s 8,192 the pairs are 201,351,168 and a
    token 1,385,541,816, where ISSUE 50 counts 466 M forward a token, 1,398
    M with the backward pass, without the recurrence and with the head at
    every row: the same to its three digits."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gdn = (d * (2 * keys + 2 * values) + d * 2 * cfg["linear_num_value_heads"]
           + values * d)
    recurrence = (3 * 7 * cfg["linear_num_value_heads"]
                  * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])
    heads, head_dim = cfg["num_attention_heads"], cfg["head_dim"]
    attention = (d * heads * head_dim * 2
                 + 2 * d * cfg["num_key_value_heads"] * head_dim
                 + heads * head_dim * d)
    pairs = 6 * 2 * head_dim * heads * s * (s + 1) // 2
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["num_experts"]
           + 3 * d * cfg["shared_expert_intermediate_size"] + d
           + cfg["num_experts_per_tok"] * cfg["num_local_experts"] * expert
           // cfg["num_experts"])
    row = 0
    for kind in _kinds(cfg):
        linear = kind == "linear_attention"
        row += 6 * ((gdn if linear else attention) + moe)
        row += recurrence if linear else 0
    return (s * row + _kinds(cfg).count("full_attention") * pairs
            + (s - 1) * 6 * d * cfg["vocab_size"]) // s
