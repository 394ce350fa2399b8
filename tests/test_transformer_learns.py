"""Regression: the flagship transformer's learning probe actually falls.

The probe is a current-token copy task over a small pool of ids inside
the model's vocabulary. Targets drawn uniformly from a full 32000-id
vocabulary are unlearnable by design within a 32-step window (about 0.25
sightings a class a step): the loss flatlines at any learning rate while
the identical architecture learns the pool task. A flat loss here is
therefore the model's or the optimizer's fault, not the data's.
"""

import numpy as np

import paddle_tpu as pt

VOCAB, SEQ, BATCH, STEPS = 512, 48, 4, 32
#: ids are drawn from [0, POOL): every class is seen often enough to
#: separate within STEPS batches
POOL = 64


def copy_task_feeds(i, batch, seqlen, vocab):
    """Batch i of the copy task: seeded by the step index, so distinct
    steps draw distinct batches (a fixed batch would measure memorising,
    not learning)."""
    vrng = np.random.RandomState(7000 + i)
    src = vrng.randint(0, min(vocab, POOL), (batch, seqlen)).astype("int64")
    return {"src_ids": src, "tgt_ids": src[..., None]}


def test_tiny_transformer_copy_task_loss_falls():
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        avg, _ = tfm.transformer_lm_loss(
            vocab_size=VOCAB, seq_len=SEQ, n_layers=2, d_model=64,
            n_heads=2, d_ff=128, max_len=SEQ)
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(avg)

    stacked = {k: np.stack([copy_task_feeds(i, BATCH, SEQ, VOCAB)[k]
                            for i in range(STEPS)])
               for k in ("src_ids", "tgt_ids")}
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe = pt.Executor()
        exe.run(startup)
        (losses,) = exe.run_loop(main, feed=stacked, fetch_list=[avg],
                                 n_steps=STEPS, per_step_feeds=True,
                                 unroll=1)
    tr = np.asarray(losses, np.float32).reshape(-1)
    k = max(len(tr) // 8, 1)
    head, tail = float(tr[:k].mean()), float(tr[-k:].mean())
    assert tail < head - max(0.002 * abs(head), 1e-3), (
        f"tiny transformer copy-task loss did not fall: head {head:.4f} "
        f"-> tail {tail:.4f} (trajectory {tr[::max(STEPS // 8, 1)]})")
    # and not by a hair: the pool task is learnable by construction
    assert tail < head - 0.05, (
        f"loss fall is marginal (head {head:.4f} -> tail {tail:.4f}); "
        "the probe design has likely regressed toward one-shot classes")
