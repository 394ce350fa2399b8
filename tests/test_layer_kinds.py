"""The table of `models/transformer.py` tests itself: every
`layer_pattern` entry (`_ENTRIES`) from a smallest legal block that has
it, and every `BlockSpec` field past the base ones against its owner. A
kind that is added to the table and to nothing else is exercised here
before any architecture suite knows it; a field that is added without an
owner fails here, not in a bundle."""

import dataclasses

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import transformer as tfm

V, DM, NH, FF, SLOTS, BLOCK, POOL, MAXC = 61, 32, 4, 48, 2, 4, 9, 16
GQA = dict(norm="rms_norm", positions="rope", bias=False, attention="gqa",
           n_kv_heads=2, head_dim=8, ffn="gated")
SCAN = dict(conv_taps=4, ssm_inner=32, ssm_state=4, ssm_dt_rank=2)
SSD = dict(positions="none", conv_taps=4, ssm_inner=32, ssm_state=8,
           ssm_heads=4, ssm_groups=2)
LONG = dict(positions="none", qk_norm=True, decay_layers=2)
SPARSE = dict(sparse_kernel=4, sparse_stride=2, sparse_block=BLOCK,
              sparse_topk=3, sparse_window=BLOCK, sparse_init=1)
DIFF = dict(positions="none", differential=True)

#: a smallest legal block that has the kind: the period its two layers are
BLOCKS = {
    "window": dict(GQA, window=8, layer_pattern=("window", "full")),
    "full": dict(GQA, layer_pattern=("full", "full")),
    "conv": dict(GQA, conv_taps=3, layer_pattern=("conv", "full")),
    "mamba": dict(GQA, **SCAN, layer_pattern=("mamba", "full")),
    "memory": dict(GQA, **SCAN, layer_pattern=("memory", "gmu")),
    "gmu": dict(GQA, **SCAN, layer_pattern=("memory", "gmu")),
    "cross": dict(GQA, **DIFF, layer_pattern=("full", "cross")),
    "mamba2": dict(GQA, **SSD, layer_pattern=("mamba2", "ffn")),
    "attn": dict(GQA, **SSD, layer_pattern=("mamba2", "attn")),
    "ffn": dict(GQA, **SSD, layer_pattern=("mamba2", "ffn")),
    "linear": dict(GQA, **LONG, layer_pattern=("linear", "linear")),
    "blocksparse": dict(GQA, **LONG, **SPARSE,
                        layer_pattern=("blocksparse", "linear")),
    "mamba2_ffn": dict(GQA, **SSD, layer_pattern=("mamba2_ffn", "full")),
}


def test_the_cases_are_the_table():
    assert sorted(BLOCKS) == sorted(tfm._ENTRIES)
    assert set(tfm._MIXERS) == {e.mixer for e in tfm._ENTRIES.values()}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_an_entry_resolves_remembers_steps_and_trains_or_says_why(name):
    block = tfm.BlockSpec(**BLOCKS[name])
    entry = tfm._ENTRIES[name]
    at = block.layer_pattern.index(name)
    kind = block.layer(at, FF)
    # `layer()` resolves the entry as the table states it
    assert (kind.mixer, kind.cache, kind.memory) \
        == (entry.mixer, entry.cache, entry.memory)
    assert (kind.ffn != "none") == entry.ffn
    assert kind.window == (block.window if entry.cache == "window" else 0)
    assert kind.positions == ((getattr(block, entry.positions)
                               or block.positions)
                              if entry.positions else "none")
    assert (kind.kv_source >= 0) == (entry.cache == "shared")
    assert block.cache_kinds(2)[at] == entry.cache
    # what it remembers, as the step takes it
    said = block.cache_pools(NH, DM, at, MAXC)
    feeds = tfm.cache_feeds(block, at, NH, DM, SLOTS, BLOCK,
                            {"full": POOL, "window": POOL}, MAXC)
    assert len(feeds) == len(said["pools"]) + len(said.get("state", ()))
    if entry.cache in ("none", "shared"):
        assert feeds == [] and said["kind"] == entry.cache
    for (stem, shape), _ in zip(feeds, said["pools"]):
        assert shape[:2] == [POOL, BLOCK]
    for stem, shape in feeds[len(said["pools"]):]:
        assert shape[0] == SLOTS
    assert bool(said["pools"]) == (entry.cache in ("full", "window"))
    # a two-layer decode step builds, and takes and returns that memory
    main = pt.Program()
    with pt.program_guard(main, pt.Program()):
        logits, pool_outs, names = tfm.transformer_decode_step(
            V, n_layers=2, d_model=DM, n_heads=NH, d_ff=FF,
            max_context=MAXC, slots=SLOTS, block_size=BLOCK,
            pool_blocks=POOL, max_blocks_per_seq=MAXC // BLOCK,
            block=block, window_pool_blocks=POOL if block.window else 0)
    assert list(logits.shape) == [SLOTS, V]
    assert [n for n in names if n.endswith(f"_{at}")] \
        == [f"{stem}_{at}" for stem, _ in feeds]
    assert len(pool_outs) == 2 and len(pool_outs[at]) == len(feeds)
    for (_, shape), out in zip(feeds, pool_outs[at]):
        assert list(out.shape) == shape
    # the trainer takes it, or refuses it with the entry's own reason
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        if entry.untrained:
            with pytest.raises(NotImplementedError) as refusal:
                tfm.transformer_lm_loss(
                    vocab_size=V, seq_len=8, n_layers=2, d_model=DM,
                    n_heads=NH, d_ff=FF, max_len=8, block=block)
            assert str(refusal.value) == entry.untrained
            return
        loss, _ = tfm.transformer_lm_loss(
            vocab_size=V, seq_len=8, n_layers=2, d_model=DM, n_heads=NH,
            d_ff=FF, max_len=8, block=block)
        pt.optimizer.SGD(learning_rate=0.5).minimize(loss)
    rng = np.random.RandomState(3)
    ids = rng.randint(0, V, (2, 9))
    feed = {"src_ids": ids[:, :8], "tgt_ids": ids[:, 1:, None]}
    with pt.scope_guard(pt.Scope()):
        exe = pt.Executor()
        exe.run(startup)
        losses = [float(np.ravel(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0])[0])
                  for _ in range(3)]
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0], losses


def test_every_field_past_the_base_ones_has_one_owner():
    """The mixers that read it (a field shared by several, as `conv_taps`
    by the three that convolve, is theirs together), the experts or the
    model: one kind of owner a field, and every field one."""
    fields = [f.name for f in dataclasses.fields(tfm.BlockSpec)]
    assert fields[:len(tfm._ALWAYS_SAID)] == list(tfm._ALWAYS_SAID)
    assert len(tfm._ALWAYS_SAID) == 26      # what every bundle records
    later = set(fields) - set(tfm._ALWAYS_SAID)
    of_mixers = {f for m in tfm._MIXERS.values() for f in m.fields}
    owners = [of_mixers, set(tfm._EXPERT_FIELDS) & later,
              set(tfm._MODEL_FIELDS)]
    assert set().union(*owners) == later
    assert sum(len(o) for o in owners) == len(later)    # no field twice
    assert set(tfm._EXPERT_FIELDS) <= set(fields)
    # a field that is some mixer's is refused beside no such mixer, the
    # attention's apart: that is said model-wide
    for name, mixer in tfm._MIXERS.items():
        for field in mixer.fields if name != "attention" else ():
            wrong = 1 if isinstance(tfm._DEFAULTS[field], (int, bool)) \
                else "rope"
            with pytest.raises(ValueError, match="come with a"):
                tfm.BlockSpec(**dict(GQA, **{field: wrong}))
