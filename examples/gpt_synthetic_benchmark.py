"""GPT (decoder LM) synthetic benchmark — the long-context causal path.

Same harness shape as bert_synthetic_benchmark (reference
examples/tensorflow2_synthetic_benchmark.py CLI), on the decoder family:
causal flash attention by default, ring/Ulysses sequence parallelism via
``--seq-parallel``.

Run:  python examples/gpt_synthetic_benchmark.py --seq-len 2048
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models.gpt import GPT, gpt2_small, gpt_tiny, next_token_loss
from horovod_tpu.parallel.ring_attention import (
    ring_attention, ulysses_attention,
)
from horovod_tpu.training import init_train_state, make_train_step, shard_batch


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description="horovod_tpu GPT synthetic benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    parser.add_argument("--model", choices=["tiny", "gpt2"], default="gpt2")
    parser.add_argument("--batch-size", type=int, default=4,
                        help="per-rank sequences")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--attn", choices=["xla", "pallas"],
                        default="pallas")
    parser.add_argument("--seq-parallel", choices=["none", "ring", "ulysses"],
                        default="none")
    parser.add_argument("--num-warmup-batches", type=int, default=2)
    parser.add_argument("--num-batches-per-iter", type=int, default=5)
    parser.add_argument("--num-iters", type=int, default=3)
    parser.add_argument("--dtype", choices=["bfloat16", "float32"],
                        default="bfloat16")
    parser.add_argument("--num-in-graph-steps", type=int, default=1,
                        help="optimizer steps compiled into one program "
                             "(lax.scan); amortizes host dispatch")
    return parser.parse_args(argv)


def _attention_fn(args):
    if args.seq_parallel == "ring":
        return lambda q, k, v, m: ring_attention(
            q, k, v, causal=True, impl=args.attn)
    if args.seq_parallel == "ulysses":
        return lambda q, k, v, m: ulysses_attention(
            q, k, v, causal=True, impl=args.attn)
    if args.attn == "pallas":
        return None  # model default = causal flash
    from horovod_tpu.ops.flash_attention import softmax_attention

    return lambda q, k, v, m: softmax_attention(q, k, v, causal=True)


def run(args) -> dict:
    hvd.init()
    dtype = jnp.dtype(args.dtype)
    factory = gpt2_small if args.model == "gpt2" else gpt_tiny
    model = factory(dtype=dtype, attention_fn=_attention_fn(args),
                    max_len=max(args.seq_len, 1024))
    opt = optax.adam(1e-4)

    rng = np.random.default_rng(0)
    if args.seq_parallel == "none":
        step = make_train_step(
            apply_fn=lambda v, x, train=True: model.apply(v, x),
            loss_fn=next_token_loss, optimizer=opt,
            in_graph_steps=args.num_in_graph_steps,
        )
        # init with the hook-free twin (the attention_fn may need the mesh)
        init_twin = factory(dtype=dtype, max_len=max(args.seq_len, 1024))
        state = init_train_state(
            init_twin, opt, jnp.zeros((2, args.seq_len), jnp.int32),
        )
        ids = shard_batch(rng.integers(
            0, 1000, size=(args.batch_size * hvd.size(), args.seq_len)
        ).astype(np.int32))
        n_batches = args.batch_size * hvd.size()
    else:
        # sequence parallelism: the SEQUENCE dim is sharded across ranks
        # (batch replicated per step); positions are globalized via
        # seq_offset; the shifted LM loss is computed within each shard
        # (the n-1 shard-boundary predictions are dropped — negligible
        # at benchmark lengths) and averaged over ranks
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops import collectives
        from horovod_tpu.ops.fusion import allreduce_pytree
        from horovod_tpu.training import TrainState

        init_twin = factory(dtype=dtype, max_len=max(args.seq_len, 1024))
        state = init_train_state(
            init_twin, opt, jnp.zeros((2, args.seq_len), jnp.int32),
        )
        local_seq = args.seq_len // hvd.size()

        def per_rank(state, ids_shard):
            off = hvd.rank() * local_seq

            def loss_of(params):
                logits = model.apply(
                    {"params": params, **state.model_state},
                    ids_shard, seq_offset=off,
                )
                return next_token_loss(logits, ids_shard)

            loss, grads = jax.value_and_grad(loss_of)(state.params)
            grads = allreduce_pytree(grads, op=hvd.Average)
            loss = collectives.allreduce(loss, op=hvd.Average)
            updates, opt_state = opt.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return TrainState(params, opt_state, state.model_state,
                              state.step + 1), loss

        state_spec = TrainState(params=P(), opt_state=P(),
                                model_state=P(), step=P())
        step = hvd.spmd(
            per_rank, in_specs=(state_spec, P(None, hvd.AXIS)),
            out_specs=(state_spec, P()), donate_argnums=(0,),
        )
        from horovod_tpu import core
        from jax.sharding import NamedSharding

        ids = jax.device_put(
            rng.integers(0, 1000, size=(args.batch_size, args.seq_len)
                         ).astype(np.int32),
            NamedSharding(core.mesh(), P(None, hvd.AXIS)),
        )
        n_batches = args.batch_size

    def log(s):
        if hvd.rank() == 0:
            print(s, flush=True)

    log(f"Model: gpt-{args.model}  seq {args.seq_len}  attn {args.attn}  "
        f"sp {args.seq_parallel}")
    call = ((lambda st: step(st, ids, ids)) if args.seq_parallel == "none"
            else (lambda st: step(st, ids)))
    for _ in range(max(args.num_warmup_batches, 1)):
        state, loss = call(state)
    float(np.asarray(jax.device_get(loss)))

    rates = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, loss = call(state)
        float(np.asarray(jax.device_get(loss)))
        dt = time.perf_counter() - t0
        # sp mode runs its own single-step program; in-graph scan applies
        # to the data-parallel make_train_step path only
        k = (max(args.num_in_graph_steps, 1)
             if args.seq_parallel == "none" else 1)
        rate = n_batches * k * args.num_batches_per_iter / dt
        log(f"Iter: sequences/sec total: {rate:.1f}")
        rates.append(rate)

    mean = float(np.mean(rates))
    # in both modes the whole mesh jointly produced the counted sequences
    per_chip = mean / hvd.size()
    from horovod_tpu.utils.flops import param_count, transformer_mfu

    mfu = transformer_mfu(
        per_chip, param_count(state.params), model.num_layers,
        model.hidden_dim, args.seq_len, causal=True,
    )
    # None: the device kind is not in utils/flops.DEVICE_PEAKS
    mfu_txt = f"{mfu:.1%}" if mfu is not None else "n/a"
    log(f"sequences/sec per chip: {per_chip:.1f}  "
        f"(analytic MFU {mfu_txt} of the device's bf16 peak)")
    return {"seq_sec_per_chip": per_chip,
            "mfu": mfu,
            "final_loss": float(np.asarray(jax.device_get(loss)))}


if __name__ == "__main__":
    run(parse_args())
