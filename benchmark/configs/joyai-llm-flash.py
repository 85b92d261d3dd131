"""JoyAI-LLM-Flash, one chip's share of a 32-chip deployment (experts 0..7
of 256; rows 0..16,159 of the vocabulary, which 8 chips divide), cut in
depth to the leading dense layer, the four expert layers after it and the
MTP module, whole: the program's model, its batches, its plain reference
and its operations per token. Sizes come from ``joyai-llm-flash.json``
(``cfg``).

Parameters by hand (d 2048; the file's ``n_params``). An MLA mixer: q_a
2048 x 1536 = 3,145,728; its norm 1536; q_b 1536 x 6144 (32 x 192) =
9,437,184; kv_a 2048 x 576 = 1,179,648; its norm 512; kv_b 512 x 8192 (32 x
256) = 4,194,304; o 4096 x 2048 = 8,388,608: **26,347,520**. One expert 3 x
2048 x 768 = 4,718,592 (so is the shared one); router and selection bias
2048 x 256 + 256 = 524,544; a layer's two norms 4,096; the dense SwiGLU 3 x
2048 x 7168 = 44,040,192. The dense layer 26,347,520 + 4,096 + 44,040,192 =
70,391,808; an expert layer held here 26,347,520 + 4,096 + 524,544 + 9 x
4,718,592 = 69,343,488; embedding + head + final norm 2 x 16,160 x 2048 +
2048 = 66,193,408; the MTP module an expert layer + eh_proj 2 x 2048 x
2048 = 8,388,608 + three norms 6,144 = 77,738,240. 70,391,808 + 4 x
69,343,488 + 66,193,408 + 77,738,240 = **491,697,408**. Published, 1 dense +
39 expert layers with all 256 experts (1,239,554,304 a layer) and 129,280
rows: 48.9 B without the MTP module, 50.2 B with it; a token meets 2.77 B of the
layers (3.30 B with embedding and head).
"""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``): the ``mtp`` and ``eshare`` readers
# both ask, the first to ask pays. ``lib/cell.py`` hands a reader neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 32 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _model(cfg):
    from byteps_tpu.models import JoyAIFlashModel

    if cfg["num_nextn_predict_layers"] != 1 or not cfg["rope_interleave"]:
        raise ValueError("JoyAIFlashModel has one MTP module and rotates "
                         "interleaved pairs")
    return JoyAIFlashModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], dense_mlp_dim=cfg["intermediate_size"],
        num_experts=cfg["n_routed_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        first_dense=cfg["first_k_dense_replace"],
        shared=cfg["n_shared_experts"], first_expert=FIRST_EXPERT,
        rope_theta=float(cfg["rope_theta"]), loss_rows=cfg["loss_rows"],
        dtype=jnp.dtype(cfg["compute_dtype"]), eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import joyai_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return joyai_loss(model.apply(params, batch["tokens"]),
                          cfg["mtp_loss_weight"])

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats", "mtp_stats"}``, the model's two
    collections as numpy, under one jit that returns them alone. Worked out
    once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["moe_stats", "mtp_stats"])[1]

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
    """[rows, s-1], summing to 1: the main term is the mean over rows x
    (s-1) positions, so the shards need not be told apart. The MTP term's
    rows x (s-2) weights follow from these in ``reference_loss``."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_joyai import causal_lm_nll

    def weighted_loss(params, batch):
        main, mtp = causal_lm_nll(
            params, batch["tokens"], heads=cfg["num_attention_heads"],
            kv_rank=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
            rope_dim=cfg["qk_rope_head_dim"], theta=float(cfg["rope_theta"]),
            top_k=cfg["num_experts_per_tok"], first_expert=FIRST_EXPERT,
            routed_scale=cfg["routed_scaling_factor"],
            eps=cfg["rms_norm_eps"], dtype=jnp.dtype(cfg["compute_dtype"]),
            **cfg["reference_blocks"])
        # a row's s-2 MTP positions share the weight its s-1 main ones had
        seq = batch["tokens"].shape[1]
        ahead = batch["weight"][:, :-1] * ((seq - 1) / (seq - 2))
        return ((main * batch["weight"]).sum()
                + cfg["mtp_loss_weight"] * (mtp * ahead).sum())

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per token of a step, forward and
    backward — causal pairs, the expected held experts, the sliced heads,
    each stream's rows that carry a target — so that the padded lanes of a
    192-wide head, rows beyond the held groups, the MTP stream's two padded
    rows and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). An MLA mixer's projections (the parameters above less its two
    norms) 26,345,472; its attention 6 x (192 + 128) x 32 = 61,440 a causal
    (query, key) pair. The dense SwiGLU 44,040,192. An expert layer: router
    524,288, of a token's 8 experts the 8 x 8 / 256 = 1/4 expected here,
    1,179,648, and the shared expert 4,718,592: 6,422,528. A head 2048 x
    16,160 = 33,095,680 (embedding look-ups are not matmuls); eh_proj
    8,388,608. A sequence of s tokens: the main stack at s rows, 6 x (5 x
    26,345,472 + 44,040,192 + 4 x 6,422,528) = 1,208,745,984 a row and 5 x
    61,440 x s (s + 1) / 2 for the pairs; its head at the s - 1 rows with a
    target, 198,574,080 a row; the MTP module at its s - 2 rows, 6 x
    (8,388,608 + 26,345,472 + 6,422,528 + 33,095,680) = 445,513,728 a row
    and 61,440 x (s - 2)(s - 1) / 2. At s 8,192, over s: **3,362,711,671**
    a token (45% the six layers' score and value products, 21% the MTP
    module). ISSUE 41 counts 3,362,967,552 with both heads and the module at
    all s rows: 255,881 more, 0.008%, the three rows without a target."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk
           + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                            + cfg["v_head_dim"])
           + heads * cfg["v_head_dim"] * d)
    pair = 6 * (qk + cfg["v_head_dim"]) * heads
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["n_routed_experts"] + cfg["n_shared_experts"] * expert
           + cfg["num_experts_per_tok"] * cfg["num_local_experts"] * expert
           // cfg["n_routed_experts"])
    dense = cfg["first_k_dense_replace"]
    head = d * cfg["vocab_size"]
    main = 6 * (layers * mla + dense * 3 * d * cfg["intermediate_size"]
                + (layers - dense) * moe)
    module = 6 * (2 * d * d + mla + moe + head)
    return (s * main + layers * pair * s * (s + 1) // 2 + (s - 1) * 6 * head
            + (s - 2) * module + pair * (s - 2) * (s - 1) // 2) // s
