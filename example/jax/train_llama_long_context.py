"""Long-context LLaMA training: Pallas flash attention + remat + DP/SP.

Demonstrates the long-context path (SURVEY.md §5 notes the reference has
none — this is byteps_tpu scope beyond parity): sliding-window flash
attention with O(seq) memory, per-block rematerialisation, and the
standard data-parallel framework step. With ``--sp`` the sequence is
sharded over the fast ``ici`` axis too (ring or Ulysses attention, the
SP-aware LM loss scoring chunk boundaries over the ring) while batch
rows stay data-parallel over ``dcn`` — a 2-D mesh from one jitted step.

    python example/jax/train_llama_long_context.py --seq-len 4096
    python example/jax/train_llama_long_context.py --seq-len 32768 --sp
    # multi-host: python -m byteps_tpu.launcher --local 2 --num-servers 1 -- \
    #   python example/jax/train_llama_long_context.py --seq-len 1024
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--batch-size", type=int, default=0,
                   help="global batch (default: 1 per chip; with --sp: "
                        "1 per dcn slice, since each row's sequence "
                        "spreads over the ici chips)")
    p.add_argument("--window", type=int, default=0,
                   help="sliding attention window (0 = full causal)")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--vocab", type=int, default=4096)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--d-model", type=int, default=512)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--fp32", action="store_true")
    p.add_argument("--sp", action="store_true",
                   help="shard the sequence over the ici axis (ring/"
                        "Ulysses attention + SP-aware loss); batch rows "
                        "stay data-parallel over dcn")
    p.add_argument("--sp-impl", choices=["ring", "ulysses"],
                   default="ring")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import byteps_tpu.jax as bps
    from byteps_tpu.jax.training import (make_train_step, replicate,
                                         shard_batch)
    from byteps_tpu.models import LlamaModel
    from byteps_tpu.models.transformer import lm_loss

    bps.init()
    n_dev = bps.device_count()
    mesh = bps.mesh()
    ici_n = mesh.shape.get("ici", 1)
    dcn_n = mesh.shape.get("dcn", 1)
    batch = args.batch_size or (dcn_n if args.sp else n_dev)
    dtype = jnp.float32 if args.fp32 else jnp.bfloat16
    # The Pallas kernel is compiled only on a TPU backend; elsewhere it
    # would run interpreted, so off-TPU this example uses XLA attention.
    attn_impl = "flash" if jax.default_backend() == "tpu" else "full"
    if args.sp:
        if args.window:
            raise SystemExit("--window (sliding flash) and --sp are "
                             "mutually exclusive: the SP backends are "
                             "ring/ulysses attention")
        if bps._st().config.use_ps:
            raise SystemExit(
                "--sp composes DP and SP inside one jitted step and needs "
                "collective mode; for multi-host run the processes under "
                "jax.distributed (one global mesh), not the PS launcher")
        attn_impl = args.sp_impl
    if bps.rank() == 0:
        print(f"attention backend: {attn_impl} "
              f"(jax backend {jax.default_backend()!r})", flush=True)

    # One source of truth for the architecture; the init-time variant only
    # flips the attention backend (init runs a short unsharded sequence).
    model_kw = dict(
        vocab_size=args.vocab, num_layers=args.layers,
        d_model=args.d_model, num_heads=args.heads,
        num_kv_heads=args.kv_heads, mlp_dim=args.d_model * 3,
        dtype=dtype, remat=True)
    model = LlamaModel(**model_kw, attn_impl=attn_impl,
                       **({"sp_axis": "ici"} if args.sp else {}))
    if args.window and attn_impl != "flash":
        raise SystemExit("--window needs the flash backend (run on TPU)")

    # SP mode trains one shared global batch (seeded identically on every
    # host); plain DP gives each worker its own rows.
    rng = np.random.default_rng(0 if args.sp else bps.rank())
    toks = jnp.asarray(rng.integers(0, args.vocab,
                                    (batch, args.seq_len)), jnp.int32)
    init_model = LlamaModel(**model_kw, attn_impl="full")
    params = init_model.init(jax.random.PRNGKey(0), toks[:1, :128])
    tx = optax.adamw(3e-4)

    if args.sp:
        # 2-D step: batch rows over dcn, sequence over ici; grads reduced
        # over BOTH axes by the ordinary hierarchical push_pull.
        from functools import partial

        from jax.sharding import NamedSharding, PartitionSpec as P

        from byteps_tpu.jax._compat import shard_map as _shard_map
        from byteps_tpu.models.transformer import sp_lm_loss

        @jax.jit
        @partial(_shard_map, mesh=mesh,
                 in_specs=(P(), P(), P("dcn", "ici")),
                 out_specs=(P(), P(), P()), check_vma=False)
        def step(p, o, t):
            loss, grads = jax.value_and_grad(
                lambda p_: sp_lm_loss(model.apply(p_, t), t, "ici"))(p)
            grads = bps.push_pull(grads, average=True)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            for ax in ("dcn", "ici"):
                loss = jax.lax.pmean(loss, ax)
            return p, o, loss

        p_r = replicate(params)
        o_r = replicate(tx.init(params))
        sharding = NamedSharding(mesh, P("dcn", "ici"))
        if jax.process_count() > 1:
            # multi-controller: every host seeded the same global batch;
            # each contributes its own dcn rows.
            rows = batch // jax.process_count()
            lo = bps.rank() * rows
            parts = jax.make_array_from_process_local_data(
                sharding, np.asarray(toks[lo:lo + rows]))
        else:
            parts = jax.device_put(toks, sharding)
    else:
        def loss_fn(p, batch_):
            return lm_loss(model.apply(p, batch_), batch_)

        step = make_train_step(loss_fn, tx, mesh)
        p_r = replicate(params)
        o_r = replicate(tx.init(params))
        parts = shard_batch(toks)

    p_r, o_r, loss = step(p_r, o_r, parts)   # compile
    float(np.asarray(loss))   # full sync
    t0 = time.perf_counter()
    for i in range(args.steps):
        p_r, o_r, loss = step(p_r, o_r, parts)
        if i == args.steps - 1:
            final = float(np.asarray(loss))  # forces completion
    dt = time.perf_counter() - t0
    if bps.rank() == 0:
        tok_s = batch * args.seq_len * args.steps / dt
        sp_note = f" sp={ici_n}x{args.sp_impl}" if args.sp else ""
        print(f"attn={attn_impl} seq={args.seq_len} window={args.window}"
              f"{sp_note}: {tok_s:,.0f} tokens/sec, final loss "
              f"{final:.4f}")
    bps.shutdown()


if __name__ == "__main__":
    main()
