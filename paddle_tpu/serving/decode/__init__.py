"""paddle_tpu.serving.decode — autoregressive generation over the
serving engine: paged KV cache + continuous batching.

PR 5's micro-batcher coalesces fixed-shape one-shot requests — right for
classifiers, wrong for LLM decode, where every sequence wants hundreds
of dependent single-token dispatches and sequences finish at different
times. This subsystem is the decode-shaped counterpart, layered on the
same artifact plane:

    DecodeEngine            facade: admission + scheduler + metrics
      ├── DecodeModel       the two-artifact bundle
      │                     (io.export_decode_model): length-bucketed
      │                     PREFILL artifacts, each under one jitted
      │                     call that keeps its K/V on the device and
      │                     one jitted, pool-donating scatter that
      │                     seeds them (PrefillKV is the handle between
      │                     the two), plus ONE fixed-shape DECODE-STEP
      │                     artifact whose KV pools thread
      │                     device-resident from fetch to feed, and
      │                     which chooses every slot's token on the
      │                     device (StepResult: the ids on the host,
      │                     the logits behind np.asarray)
      ├── DecodeScheduler   continuous batching: admit into free slots
      │                     of the in-flight batch (no drain barrier),
      │                     evict lowest-priority under pool pressure,
      │                     deadline-aware shedding by remaining-token
      │                     estimate (typed Overloaded /
      │                     DeadlineExceeded)
      ├── KVBlockPool       host accounting for the paged device pool:
      │                     fixed-size blocks, per-sequence block
      │                     tables, per-block refcounts,
      │                     alloc/share/free/defrag
      ├── PrefixIndex       KV economics half 1 (prefix.py): hash of
      │                     token prefixes at block granularity; prompts
      │                     sharing a resident prefix ALIAS its blocks
      │                     (one copy backs N sessions), copy-on-write
      │                     keeps shared blocks immutable
      └── drafters          KV economics half 2 (spec.py): speculative
                            decoding — a drafter proposes k tokens, the
                            SAME fixed-shape step verifies the chain
                            through idle slots, greedy acceptance stays
                            token-identical to plain decode

Correctness contract (tested): continuous-batched paged decode is
token-identical to a sequential per-sequence reference decode under
greedy sampling — including sequences admitted mid-flight, sequences
evicted then resumed, sequences aliasing a shared prefix, and
speculative steps under any drafter.

Env knobs (export-time geometry + runtime budget; declared in
paddle_tpu/flags.py):

    PT_DECODE_BLOCK_SIZE      tokens per KV block (export default 16)
    PT_DECODE_POOL_BLOCKS     pool blocks incl. the null block (64)
    PT_DECODE_MAX_SLOTS       decode-step slot count (8)
    PT_DECODE_MAX_NEW_TOKENS  default generation budget (64)
    PT_KV_SHARE               1 = copy-on-write prefix sharing (off)
    PT_SPEC_DRAFT             drafter: ngram | self | <bundle dir> (off)
    PT_SPEC_K                 drafted tokens per speculative step (4)
"""

from __future__ import annotations

from .engine import (DecodeEngine, DecodeModel, PrefillKV, PrefillRow,
                     StepResult)
from .kv_cache import KVBlockPool, PoolExhausted, blocks_for_tokens
from .prefix import PrefixIndex
from .scheduler import (DRAIN_REASONS, PREVIOUS_TOKEN, DecodeScheduler,
                        GenerationHandle, Sequence)
from .spec import NGramDrafter, PrefillDrafter, accept_greedy

__all__ = ["DecodeEngine", "DecodeModel", "PrefillKV", "PrefillRow",
           "StepResult", "DecodeScheduler", "PREVIOUS_TOKEN",
           "DRAIN_REASONS",
           "GenerationHandle", "Sequence", "KVBlockPool", "PoolExhausted",
           "blocks_for_tokens", "PrefixIndex", "NGramDrafter",
           "PrefillDrafter", "accept_greedy"]
