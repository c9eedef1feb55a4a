"""LLaMA pretraining through the high-level Trainer.

The "switch from the reference" demo: elastic launch + data-parallel
sharded training in ~50 lines, with flash checkpointing one flag away
(``--ckpt-dir``), a warmup-cosine schedule surfaced in the step logs,
interleaved evaluation (``--eval-every``), and the HF-style callback
hooks. For the master-fed elastic data path see
``train_tiny.py --use-dataloader``.

Run::

    python -m dlrover_tpu.cli --standalone --nproc_per_node=1 \
        examples/train_llama.py -- --steps 30 --eval-every 10
"""

import argparse
import itertools

import jax
import numpy as np
import optax

from dlrover_tpu import train as dtrain
from dlrover_tpu.accel import ParallelSpec
from dlrover_tpu.models.llama import Llama, LlamaConfig, loss_fn
from dlrover_tpu.train.trainer import LoggingCallback, Trainer


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch", type=int, default=8)
    parser.add_argument("--seq", type=int, default=128)
    parser.add_argument("--ckpt-dir", type=str, default="")
    parser.add_argument("--grad-accum", type=int, default=1)
    parser.add_argument("--eval-every", type=int, default=0)
    parser.add_argument("--spec", type=str, default="auto",
                        help='"auto" lets the strategy search pick the '
                        'mesh (and reconfigure the model); "data" pins '
                        "pure data parallelism")
    args = parser.parse_args()

    dtrain.init_training()
    # The batch shards over the data axis AND splits into grad-accum
    # microbatches: round it up so any slice size / accum combo works.
    n_dev = len(jax.devices())
    unit = n_dev * max(1, args.grad_accum)
    args.batch = -(-args.batch // unit) * unit
    cfg = LlamaConfig(
        vocab_size=2048, max_seq_len=args.seq, num_layers=4,
        num_heads=8, num_kv_heads=4, d_model=256,
        attn_impl="pallas",
    )

    def token_loss(module, params, batch):
        return loss_fn(module.apply({"params": params}, batch), batch)

    def batches(seed_offset: int = 0):
        # seed_offset=1 is the held-out eval stream: evaluation must
        # score data the model has not trained on.
        rng = np.random.default_rng(
            dtrain.global_rank() + 100_000 * seed_offset
        )
        while True:
            yield rng.integers(
                0, cfg.vocab_size, (args.batch, args.seq), dtype=np.int32
            )

    sample = next(batches())
    spec = "auto" if args.spec == "auto" else ParallelSpec(data=n_dev)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, 3e-4, warmup_steps=10,
        decay_steps=max(args.steps, 11),
    )
    trainer = Trainer(
        Llama(cfg), optax.adamw(schedule), token_loss, sample,
        spec=spec,
        checkpoint_dir=args.ckpt_dir, persist_every=10,
        grad_accum=args.grad_accum,
        callbacks=[LoggingCallback(every=10)],
        lr_schedule=schedule,
    )
    out = trainer.fit(
        batches(), steps=args.steps,
        eval_batches=(
            (lambda: itertools.islice(batches(seed_offset=1), 2))
            if args.eval_every else None
        ),
        eval_every=args.eval_every,
    )
    print(f"rank {dtrain.global_rank()}: done at step {out['step']}, "
          f"loss {out['loss']:.4f}"
          + (f", eval {out['eval_loss']:.4f}" if "eval_loss" in out
             else ""), flush=True)
    trainer.close()


if __name__ == "__main__":
    main()
