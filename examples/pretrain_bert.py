"""BASELINE config 2: BERT-base / ERNIE-style pretraining, end to end.

Runs MLM+NSP (BERT) or MLM+SOP (ERNIE, --model ernie) pretraining with
synthetic data (the input pipeline is interchangeable; the compute path is
the real one): {Bert,Ernie}ForPretraining + the matching criterion + AdamW
with warmup-decay LR and global-norm clip, batch sharded over the
'dp'(+'sharding') mesh axes when a mesh is up.

    python examples/pretrain_bert.py --steps 20 --hidden 256 --layers 4
    python examples/pretrain_bert.py --model ernie --steps 20
    python -m paddle_tpu.distributed.launch --nproc_per_node=2 \
        examples/pretrain_bert.py --steps 5       # DP over two processes
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=8192)
    p.add_argument("--masked", type=int, default=20, help="masked tokens/seq")
    p.add_argument("--model", choices=["bert", "ernie"], default="bert")
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    import paddle_tpu as paddle
    from paddle_tpu import nn, optimizer as opt
    from paddle_tpu.models import (
        BertConfig, BertForPretraining, BertPretrainingCriterion,
        ErnieConfig, ErnieForPretraining, ErniePretrainingCriterion,
    )
    from paddle_tpu.utils import compile_cache

    compile_cache.configure()

    paddle.seed(args.seed)
    if args.model == "ernie":
        cfg = ErnieConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                          num_layers=args.layers, num_heads=args.heads,
                          max_seq_len=args.seq, dropout=0.0)
        model = ErnieForPretraining(cfg)
        criterion = ErniePretrainingCriterion()
    else:
        cfg = BertConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                         num_layers=args.layers, num_heads=args.heads,
                         max_seq_len=args.seq, dropout=0.0)
        model = BertForPretraining(cfg)
        criterion = BertPretrainingCriterion()
    sched = opt.lr.LinearWarmup(
        opt.lr.PolynomialDecay(learning_rate=args.lr,
                               decay_steps=max(args.steps, 10)),
        warmup_steps=min(5, args.steps), start_lr=0.0, end_lr=args.lr)
    optimizer = opt.AdamW(learning_rate=sched,
                          parameters=model.parameters(), weight_decay=0.01,
                          grad_clip=nn.ClipGradByGlobalNorm(1.0))

    rng = np.random.RandomState(args.seed)
    b, s, m = args.batch, args.seq, args.masked
    ids = rng.randint(0, cfg.vocab_size, (b, s)).astype("int64")
    token_type = (rng.rand(b, s) > 0.5).astype("int64")
    # masked positions are flat indices into (b*s); labels are the originals
    pos = np.stack([rng.choice(s, m, replace=False) + i * s
                    for i in range(b)]).astype("int64")
    mlm_labels = ids.reshape(-1)[pos.reshape(-1)].astype("int64")
    nsp_labels = rng.randint(0, 2, (b,)).astype("int64")

    losses = []
    t0 = time.time()
    for step in range(args.steps):
        mlm_logits, nsp_logits = model(
            paddle.to_tensor(ids), paddle.to_tensor(token_type),
            masked_positions=paddle.to_tensor(pos))
        loss = criterion(mlm_logits, nsp_logits,
                         paddle.to_tensor(mlm_labels),
                         paddle.to_tensor(nsp_labels),
                         masked_lm_scale=float(pos.size))
        loss.backward()
        optimizer.step()
        optimizer.clear_grad()
        sched.step()
        losses.append(float(loss.numpy()))
        if step % 5 == 0 or step == args.steps - 1:
            tok_s = (b * s * (step + 1)) / (time.time() - t0)
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"lr {optimizer.get_lr():.2e}  tokens/s {tok_s:,.0f}",
                  flush=True)
    assert np.isfinite(losses).all(), "non-finite loss"
    assert losses[-1] < losses[0], (losses[0], losses[-1])
    print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return losses


if __name__ == "__main__":
    main()
