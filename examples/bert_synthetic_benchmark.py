"""BERT synthetic benchmark: masked-LM pretraining throughput.

The analog of the reference's BERT profiling target (reference
examples/test_bert.sh drives gluon-nlp BERT with synthetic data and the
byteprofile tracer), built TPU-native on the in-repo flax BertEncoder:

* masked-LM objective over synthetic token streams,
* data parallelism over the mesh via the fused gradient allreduce,
* optional sequence parallelism (``--seq-parallel ring|ulysses``) on a
  (dp, sp) factorized world, and
* optional Pallas flash-attention kernels (``--attn pallas``).

Prints img-style "sentences/sec" iteration lines like the synthetic
ResNet benchmark.

Run:  python examples/bert_synthetic_benchmark.py --model tiny
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
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from examples.datasets import synthetic_tokens
from horovod_tpu.models.bert import BertEncoder, bert_base, bert_tiny
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.fusion import allreduce_pytree
from horovod_tpu.parallel.ring_attention import (
    ring_attention, ulysses_attention,
)


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="horovod_tpu BERT synthetic benchmark")
    p.add_argument("--model", choices=["tiny", "base"], default="base")
    p.add_argument("--batch-size", type=int, default=8,
                   help="per-rank sentences")
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--attn", choices=["xla", "pallas"], default="xla")
    p.add_argument("--seq-parallel", choices=["none", "ring", "ulysses"],
                   default="none")
    p.add_argument("--mask-prob", type=float, default=0.15)
    p.add_argument("--num-warmup-batches", type=int, default=3)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--dtype", choices=["bfloat16", "float32"],
                   default="bfloat16")
    p.add_argument("--adasum", action="store_true", default=False,
                   help="Adasum gradient reduction (BASELINE.json config "
                        "4: Adasum allreduce on BERT)")
    p.add_argument("--num-in-graph-steps", type=int, default=1,
                   help="optimizer steps compiled into one program "
                        "(lax.scan); amortizes host dispatch, as the "
                        "ResNet bench does")
    return p.parse_args(argv)


def _attention_fn(args):
    """Pick the attention implementation for the encoder hook."""
    if args.seq_parallel == "ring":
        return lambda q, k, v, mask: ring_attention(
            q, k, v, causal=False, impl=args.attn)
    if args.seq_parallel == "ulysses":
        return lambda q, k, v, mask: ulysses_attention(
            q, k, v, causal=False, impl=args.attn)
    if args.attn == "pallas":
        return lambda q, k, v, mask: flash_attention(q, k, v, causal=False)
    return None  # default dense path inside SelfAttention


def run(args) -> dict:
    hvd.init()
    dtype = jnp.dtype(args.dtype)
    factory = bert_tiny if args.model == "tiny" else bert_base
    model = factory(dtype=dtype, attention_fn=_attention_fn(args),
                    max_len=max(args.seq_len, 512))
    vocab = model.vocab_size

    tokens = synthetic_tokens(
        n=args.batch_size * hvd.size() * 4, seq_len=args.seq_len,
        vocab=vocab)
    rng = np.random.default_rng(5)
    mask = (rng.uniform(size=tokens.shape) < args.mask_prob)
    mask_id = vocab - 1
    inputs = np.where(mask, mask_id, tokens).astype(np.int32)

    opt = optax.adamw(1e-4)
    # init with a hook-free twin: the attention_fn (which may need the SPMD
    # mesh axis) doesn't change the parameter structure
    init_model = factory(dtype=dtype, max_len=max(args.seq_len, 512))
    variables = init_model.init(jax.random.PRNGKey(0), inputs[:1])
    params = variables["params"]
    opt_state = opt.init(params)

    # MLM head: tie to a fresh projection — predictions over the vocab
    head = jax.random.normal(jax.random.PRNGKey(1),
                             (model.hidden_dim, vocab), jnp.float32) * 0.02

    def loss_fn(params, head, ids_in, ids_tgt, mask):
        hidden = model.apply({"params": params}, ids_in)
        logits = hidden @ head
        raw = optax.softmax_cross_entropy_with_integer_labels(
            logits, ids_tgt)
        denom = jnp.maximum(mask.sum(), 1)
        return (raw * mask).sum() / denom

    # sequence dim sharded only under seq-parallel; batch dim under dp
    if args.seq_parallel == "none":
        data_spec = P(hvd.AXIS)       # batch sharded
    else:
        data_spec = P(None, hvd.AXIS)  # sequence sharded

    def one_step(params, opt_state, ids_in, ids_tgt, m):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, head, ids_in, ids_tgt, m)
        if args.adasum:
            from horovod_tpu.ops import collectives as _coll

            grads = jax.tree_util.tree_map(
                lambda g: _coll.allreduce(g, op=hvd.Adasum), grads)
        else:
            grads = allreduce_pytree(grads, op=hvd.Average)
        from horovod_tpu.ops import collectives
        loss = collectives.allreduce(loss, op=hvd.Average)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    k = max(args.num_in_graph_steps, 1)
    from horovod_tpu.training import scan_steps

    def step_of(carry, ids_in, ids_tgt, m):
        p, s = carry
        p, s, loss = one_step(p, s, ids_in, ids_tgt, m)
        return (p, s), loss

    scanned = scan_steps(step_of, k)

    @hvd.spmd(in_specs=(P(), P(), data_spec, data_spec, data_spec),
              out_specs=(P(), P(), P()),
              donate_argnums=(0, 1))
    def train_step(params, opt_state, ids_in, ids_tgt, m):
        (params, opt_state), loss = scanned(
            (params, opt_state), ids_in, ids_tgt, m)
        return params, opt_state, loss

    n = args.batch_size * hvd.size()
    ids_in = inputs[:n]
    ids_tgt = tokens[:n]
    m = mask[:n].astype(np.float32)

    if hvd.rank() == 0:
        print(f"Model: bert-{args.model}  seq {args.seq_len}  "
              f"attn {args.attn}  sp {args.seq_parallel}")

    for _ in range(args.num_warmup_batches):
        params, opt_state, loss = train_step(params, opt_state, ids_in,
                                             ids_tgt, m)
    float(np.asarray(jax.device_get(loss)))

    sent_secs = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            params, opt_state, loss = train_step(params, opt_state, ids_in,
                                                 ids_tgt, m)
        float(np.asarray(jax.device_get(loss)))
        dt = time.perf_counter() - t0
        sps = n * k * args.num_batches_per_iter / dt
        sent_secs.append(sps)
        if hvd.rank() == 0:
            print(f"Iter: sentences/sec total: {sps:.1f}")

    mean = float(np.mean(sent_secs))
    from horovod_tpu.utils.flops import param_count, transformer_mfu

    # the MLM head is a separate array outside `params` but its matmuls
    # (fwd + bwd) run every step — count it or MFU undercounts ~10%
    mfu = transformer_mfu(
        mean / hvd.size(), param_count(params) + int(np.prod(head.shape)),
        model.num_layers, model.hidden_dim, args.seq_len,
    )
    if hvd.rank() == 0:
        # None: the device kind is not in utils/flops.DEVICE_PEAKS
        mfu_txt = f"{mfu:.1%}" if mfu is not None else "n/a"
        print(f"sentences/sec per chip: {mean / hvd.size():.1f}  "
              f"(analytic MFU {mfu_txt} of the device's bf16 peak)")
    return {"sent_sec_total": mean,
            "sent_sec_per_chip": mean / hvd.size(),
            "mfu": mfu,
            "final_loss": float(np.asarray(jax.device_get(loss)))}


if __name__ == "__main__":
    run(parse_args())
