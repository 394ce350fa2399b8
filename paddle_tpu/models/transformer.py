"""Decoder-only transformer LM — the long-context flagship.

No 2018 reference equivalent (the reference's sequence models are LoD
LSTMs/seq2seq, SURVEY.md §5 "long context"); this model exists to exercise
the TPU-native extensions: fused/flash attention, ring & Ulysses sequence
parallelism over the `sp` mesh axis, and Megatron-style tensor parallelism
over `tp` — the capabilities the north star demands beyond reference parity.

Pre-norm blocks: x + MHA(N(x)), x + FFN(N(x)) (or, `parallel`, x +
MHA(N(x)) + FFN(N(x)) over ONE norm); an output head of its own (fc to
vocab) or the embedding's (`tied_head`). What N, the positions, the
projections and the FFN are is one `BlockSpec`, read by all three
builders here (the training program, the prefill buckets, the decode
step). Its default is the GPT-2 block this file began with: LayerNorm,
learned positions, biased projections, a dense GELU FFN. What differs
BETWEEN the layers of one model (how far back attention reads, whether
it carries positions, which FFN, which cache, and whether it mixes its
tokens by attention at all or by a gated short convolution, and whether
it HAS a mixer and a feed-forward part or is one of the two alone under
its one norm and residual) is a `LayerKind`, one a layer, resolved by
`BlockSpec.layer`: the builders ask it and never the model-wide fields.

What a layer kind IS, is written once: one `_ENTRIES` record a
`layer_pattern` entry and one `_MIXERS` record a mixer (its fields, its
refusals, what it remembers, how it is built for a prompt and a step).
`BlockSpec`, the three builders and `transformer_lm_loss` look a kind up
there; a new kind is one entry and its `layers.*` function
(docs/serving.md, "Adding a layer kind").
"""

from __future__ import annotations

import contextlib
import dataclasses
import math

from .. import layers
from ..core.program import remat_scope
from ..initializer import NormalInitializer
from ..param_attr import ParamAttr


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What ONE layer of a model is, where layers differ
    (`BlockSpec.layer`)."""

    window: int        #: rows its attention reads back, the token itself
    #: counted; 0: every earlier row
    positions: str     #: "learned" | "rope" | "none"
    ffn: str           #: "gelu" | "gated" | "moe_gated" | "none": the layer
    #: is its mixer alone, x + mixer(N(x))
    ffn_width: int
    cache: str         #: "full": blocks grow with the sequence |
    #: "window": only the blocks the window still reaches are kept |
    #: "state": no blocks at all, a fixed state a sequence | "shared":
    #: another layer's blocks (`kv_source`) | "none": no memory at all
    mixer: str = "attention"    #: | "short_conv" (layers.short_conv) |
    #: "mamba" (layers.selective_scan) | "gmu" (a gated memory unit) |
    #: "mamba2" (layers.mamba2_mixer) | "linear" (layers.linear_attention)
    #: | "blocksparse" (layers.block_sparse_attention) | "none": the layer
    #: is its feed-forward part alone, x + ffn(N(x))
    kv_source: int = -1    #: cache "shared": the layer whose pool this
    #: one reads (it has no K/V projection and no pool of its own)
    memory: str = ""       #: "gives": the mixer's scan output, before its
    #: gate, is handed to the later layers of the same step | "takes": the
    #: mixer gates by it
    published: int = -1    #: the layer's index in the published model,
    #: where a cut keeps it (a differential layer's `lambda_init`)
    rope_theta: float = 0.0     #: positions "rope": the layer's own base
    rope_scaling: tuple = ()    #: and its table's YaRN parameters
    #: (`ops.attention_ops.rope_table`); (): plain


@dataclasses.dataclass(frozen=True)
class _Entry:
    """What ONE `layer_pattern` entry is: all that `BlockSpec.layer`, the
    refusals and `transformer_lm_loss` know of it (`_ENTRIES`)."""

    mixer: str = "attention"    #: the `_MIXERS` record its layers resolve
    #: to (`LayerKind.mixer`)
    cache: str = "full"         #: `LayerKind.cache`
    ffn: bool = True            #: the block's FFN stands behind its mixer,
    #: under a second norm and residual; False: the mixer alone
    positions: str = ""         #: the block's field that says its
    #: positions, "positions" | "full_positions" | "linear_positions" (one
    #: left empty says `positions`); "": it carries none
    memory: str = ""            #: `LayerKind.memory`
    after: tuple = ()           #: (the entry that stands earlier in the
    #: period, what this one does with it, of what): a refusal's words
    numbered: bool = True       #: `LayerKind.published` is said
    dense_ffn: bool = False     #: its FFN is the block's DENSE one, built
    #: under a scope of its own so that a device trace tells it from the
    #: mixer (experts beside such a mixer are layers of their own)
    untrained: str = ""         #: why `transformer_lm_loss` refuses it;
    #: "": it trains


_NO_LONG_BACKWARD = (
    "'linear' and 'blocksparse' layers are served, not trained: "
    "the chunked recurrence's backward is not held to the "
    "reference's gradients and the selection has no training "
    "form; neither decode kernel has a backward")
_NO_PART_BACKWARD = (
    "layers that are a mixer or a feed-forward part alone "
    "('mamba2', 'attn', 'ffn') and a Mamba-2 mixer with an FFN "
    "('mamba2_ffn') are served, not trained: the chunked scan's "
    "backward is not held to the reference's gradients, and the "
    "decode kernel has none")

#: what a `layer_pattern` entry may be. A "conv" layer trains as it is:
#: every operation of `layers.short_conv` is differentiable
#: (tests/test_lfm2.py holds its gradients to jax.grad of the plain
#: reference).
_ENTRIES = {
    # attention over the block's window, and over every earlier row
    "window": _Entry(cache="window", positions="positions"),
    "full": _Entry(positions="full_positions"),
    # a gated short convolution in the attention's place
    "conv": _Entry("short_conv", "state", numbered=False),
    # a selective scan; "memory": one that also hands its scan output on,
    # "gmu": a gated memory unit reading that output
    "mamba": _Entry("mamba", "state"),
    "memory": _Entry("mamba", "state", memory="gives"),
    "gmu": _Entry("gmu", "none", memory="takes",
                  after=("memory", "gates by", "scan")),
    # attention with a query projection alone over the nearest earlier
    # "full" layer's pool
    "cross": _Entry(cache="shared", after=("full", "reads", "pool")),
    # the layers that are ONE part under their norm and residual: a
    # Mamba-2 mixer, full attention, the block's feed-forward part (no
    # mixer and no memory)
    "mamba2": _Entry("mamba2", "state", ffn=False,
                     untrained=_NO_PART_BACKWARD),
    "attn": _Entry(ffn=False, positions="full_positions",
                   untrained=_NO_PART_BACKWARD),
    "ffn": _Entry("none", "none", untrained=_NO_PART_BACKWARD),
    # linear attention with a constant decay a head (a state), and
    # attention over blocks chosen on pooled keys
    "linear": _Entry("linear", "state", positions="linear_positions",
                     untrained=_NO_LONG_BACKWARD),
    "blocksparse": _Entry("blocksparse", positions="positions",
                          untrained=_NO_LONG_BACKWARD),
    # a Mamba-2 mixer, then the block's FFN under a second norm and
    # residual: what "full" is to "attn"
    "mamba2_ffn": _Entry("mamba2", "state", dense_ffn=True,
                         untrained=_NO_PART_BACKWARD),
}


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """The parts of a decoder block that differ between architectures.
    Sizes (layers, widths, heads, vocabulary) stay arguments of the
    builders; `d_ff` is the FFN's width, of one expert when there are
    experts."""

    norm: str = "layer_norm"      #: "layer_norm" (scale + bias) |
    #: "rms_norm" | "layer_norm_gain" (LayerNorm with a gain and no bias)
    norm_eps: float = 1e-5
    positions: str = "learned"    #: "learned" table added to the
    #: embedding | "rope": q and k rotated, no table | "none" (a model
    #: whose state layers carry the order: attention="gqa", differential)
    rope_theta: float = 10000.0
    qk_norm: bool = False         #: RMS norm of the whole q and k
    #: projections before the head split ("mha": OLMoE's), or of each
    #: head over its own width, one gain for q and one for k ("gqa":
    #: Qwen3's)
    bias: bool = True             #: on the projections, the FFN, the head
    ffn: str = "gelu"             #: "gelu": dense, two matrices | "gated":
    #: dense gated SiLU, three | "moe_gated": dropless top-k of
    #: gated-SiLU experts
    num_experts: int = 0
    experts_per_tok: int = 0
    attention: str = "mha"        #: "mha": per-head K and V, one width |
    #: "latent": K and V up-projected from one low-rank row a token
    #: (layers.latent_attention); the cache holds that row | "gqa": K/V
    #: heads shared by groups of query heads, with or without a
    #: sparse-attention indexer (layers.grouped_attention)
    kv_lora_rank: int = 0         #: the four widths of a latent head
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  #: rotary pairs (2i, 2i+1): "latent"
    #: and "gqa" (which rotates halves (i, i + D/2) otherwise)
    router: str = "softmax"       #: "softmax" | "sigmoid_bias" | "sigmoid"
    #: (ops/moe_ops.py moe_gated_ffn)
    norm_topk: bool = False       #: gates renormalised over the chosen
    routed_scale: float = 1.0     #: and multiplied by this
    shared_width: int = 0         #: a gated expert every row takes
    dense_layers: int = 0         #: leading layers whose FFN is a dense
    dense_width: int = 0          #: gated SiLU of this width instead
    head_dim: int = 0             #: a head's width; 0: d_model // n_heads
    n_kv_heads: int = 0           #: "gqa": K/V heads, dividing n_heads
    index_heads: int = 0          #: "gqa": the indexer's query heads,
    index_head_dim: int = 0       #: its one width,
    index_topk: int = 0           #: and the rows a query keeps; 0: none
    # -- the base fields end here (`_ALWAYS_SAID`): `to_dict` leaves every
    # field from here on out while it is its default. What came with the
    # layer pattern: ------------------------------------------------------
    parallel: bool = False        #: x + attn(N(x)) + ffn(N(x)), one norm
    tied_head: bool = False       #: the head reads `tok_emb`, no weight
    #: of its own
    window: int = 0               #: "gqa": rows a window layer reads
    #: back, the token itself counted
    layer_pattern: tuple = ()     #: the period of layer kinds, "window"
    #: | "full" | "conv" (a gated short convolution in the attention's
    #: place), layer i takes entry i % len; (): every layer full
    full_positions: str = ""      #: positions of the FULL layers where
    #: they are not the block's: "none"
    shared_scale: float = 1.0     #: the shared expert's output times this
    #: (k shared experts averaged are one of k times the width at 1 / k)
    experts_first: int = 0        #: the range of experts THIS program
    experts_held: int = 0         #: holds of `num_experts` (0: all): the
    #: router keeps its whole width, only pairs on held experts are
    #: computed (one chip's share of an expert-parallel layer)
    conv_taps: int = 0            #: taps of a "conv" layer's causal
    #: depthwise convolution: its state is the `conv_taps - 1` rows
    #: before the token
    norm_topk_eps: float = 0.0    #: what `norm_topk` adds to the sum it
    #: divides by; 0: the op's own 1e-20
    # -- what came with the decoder-hybrid-decoder (state-space layers,
    # differential attention, one pool read by later layers) ------------
    differential: bool = False    #: "gqa": differential attention
    #: (ops/attention_ops.py, the text above `diff_attention`): two
    #: softmaxes over paired heads, subtracted, a sub-norm
    attn_bias: bool = False       #: a bias on the attention's projections
    #: where `bias` (the FFN's and the head's too) is False
    layer_ids: tuple = ()         #: each layer's index in the published
    #: model where a cut keeps some of its layers; (): layer i is i
    ssm_inner: int = 0            #: a "mamba" layer's inner width,
    ssm_state: int = 0            #: its state's columns a channel,
    ssm_dt_rank: int = 0          #: and its step projection's rank (its
    #: convolution's taps are `conv_taps`)
    dense_precision: str = ""     #: "high": the float32 products that
    #: `layers.fc` builds of the block (a dense FFN's, a gated memory
    #: unit's) and the head's at three bfloat16 passes on a TPU, where
    #: "" is the backend's default, ONE pass over operands rounded to
    #: bfloat16 (a block whose state layers multiply that rounding)
    # -- what came with layers that are ONE part alone (a Mamba-2 mixer,
    # an attention or the experts under one norm and one residual) -------
    ssm_heads: int = 0            #: a "mamba2" layer's heads, each with a
    #: state [ssm_inner / ssm_heads, ssm_state] (one decay a head),
    ssm_groups: int = 0           #: the groups its heads share B and C by,
    ssm_chunk: int = 0            #: and the rows of a prompt its chunked
    #: (SSD) form takes at a time; 0: 128 (a "linear" layer's too)
    expert_form: str = ""         #: "relu2": an expert (routed or shared)
    #: is TWO matrices, relu(x W_up)^2 W_down; "": gated SiLU of three
    # -- what came with linear attention beside block-sparse attention
    # over pooled keys, at prompts of tens of thousands of rows ----------
    attn_gate: bool = False       #: a "linear" or "blocksparse" mixer's
    #: output times sigmoid(x W_g) before its output projection
    sparse_kernel: int = 0        #: a "blocksparse" layer: rows a pooled
    sparse_stride: int = 0        #: key is the mean of, and apart;
    sparse_block: int = 0         #: rows of a block (the cache's page),
    sparse_topk: int = 0          #: blocks a query reads in all, of them
    sparse_window: int = 0        #: the ROWS' worth that end at its own
    sparse_init: int = 0          #: and the first few blocks;
    sparse_dense_len: int = 0     #: a call shorter than this is dense
    linear_positions: str = ""    #: positions of the "linear" layers
    #: where they are not the block's: "rope"
    decay_layers: int = 0         #: L of a "linear" layer's decay rule,
    #: the PUBLISHED depth (`layer_ids` gives a layer's index in it)
    embed_scale: float = 1.0      #: the embedding's rows times this,
    residual_scale: float = 1.0   #: each residual branch times this,
    logit_scale: float = 1.0      #: and the logits
    row_chunk: int = 0            #: rows a prefill's FFN takes at a time
    #: inside the one artifact (0: whole; a 32 k bucket's two [S, width]
    #: products would be gigabytes each)
    # -- what came with layers that are a Mamba-2 mixer AND a dense FFN,
    # each under a norm and a residual of its own ------------------------
    attn_scale: float = 0.0       #: "gqa": what the scores are multiplied
    #: by where it is a constant of the configuration and not
    #: 1 / sqrt(head_dim) (0)
    # -- what came with rotary parameters a layer KIND (window layers on
    # the plain table, full layers on a table stretched for long contexts)
    full_rope_theta: float = 0.0  #: the FULL layers' base where it is not
    #: the block's `rope_theta` (0), which the other layers keep
    full_rope_scaling: tuple = ()  #: the full layers' YaRN parameters:
    #: (factor, original context, beta_fast, beta_slow, the factor on cos
    #: and sin), `ops.attention_ops.rope_table`; (): the plain table
    # -- what came with a learned selection over a LATENT cache (an
    # indexer, `index_*`, under attention="latent") ----------------------
    q_lora_rank: int = 0          #: "latent": the width of the query's
    #: low-rank (a projection down, an RMS norm, a projection up); 0: one
    #: full-rank matrix. The latent block's indexer projects its query
    #: heads from it
    index_rope_dim: int = 0       #: "latent": the leading part of the
    #: indexer's width that rotates (0: all of it, rotate-half),
    index_rope_interleave: bool = False    #: in pairs (2i, 2i + 1)

    def __post_init__(self):
        for field, known in _KNOWN.items():
            if getattr(self, field) not in known:
                raise ValueError(f"unknown {field} "
                                 f"{getattr(self, field)!r}: one of {known}")
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "layer_ids",
                           tuple(int(i) for i in self.layer_ids))
        object.__setattr__(self, "full_rope_scaling", tuple(
            float(v) for v in self.full_rope_scaling))
        pattern = self.layer_pattern
        if any(k not in _ENTRIES for k in pattern) or self.window < 0 \
                or self._any(cache="window") != bool(self.window):
            raise ValueError(
                f"layer_pattern is a period of {tuple(_ENTRIES)}, "
                "and a window comes with a 'window' layer: "
                f"{pattern} and window {self.window}")
        # each mixer the period has checks what it takes (the block's
        # attention first: it is said model-wide); a field that means
        # something only with mixers the period has not is refused
        mixers = self.mixers
        for name in mixers:
            if _MIXERS[name].check:
                _MIXERS[name].check(self, mixers)
        taken = {f for name in mixers for f in _MIXERS[name].fields}
        for name, mixer in _MIXERS.items():
            strays = [f for f in mixer.fields if f not in taken
                      and getattr(self, f) != _DEFAULTS[f]]
            if strays:
                kinds = " | ".join(repr(k) for k, e in _ENTRIES.items()
                                   if e.mixer == name)
                raise ValueError(
                    f"{mixer.called or ', '.join(strays)} come with a "
                    f"{kinds} layer: {pattern} and "
                    f"{[getattr(self, f) for f in strays]}")
        for at, entry in enumerate(self._period):
            if entry.after and not any(
                    e is _ENTRIES[entry.after[0]]
                    for e in self._period[:at]):
                earlier, does, what = entry.after
                raise ValueError(
                    f"a {pattern[at]!r} layer {does} an earlier "
                    f"{earlier!r} layer's {what}: {pattern}")
        if min(self.embed_scale, self.residual_scale, self.logit_scale) <= 0 \
                or self.row_chunk < 0 or self.attn_scale < 0:
            raise ValueError("embed_scale, residual_scale and logit_scale "
                             "are positive, row_chunk and attn_scale are "
                             "not negative")
        if self.norm_topk_eps < 0 or (self.norm_topk_eps
                                      and not self.norm_topk):
            raise ValueError("norm_topk_eps belongs to norm_topk")
        held = (self.experts_first, self.experts_held)
        if self.ffn != "moe_gated":
            strays = [f for f in _EXPERT_FIELDS
                      if getattr(self, f) != _DEFAULTS[f]]
            if strays:
                raise ValueError(f"{', '.join(strays)} belong to "
                                 "ffn='moe_gated'")
        elif not 1 <= self.experts_per_tok <= self.num_experts:
            raise ValueError(
                f"moe_gated needs 1 <= experts_per_tok "
                f"({self.experts_per_tok}) <= num_experts "
                f"({self.num_experts})")
        if min(held) < 0 or sum(held) > self.num_experts \
                or (self.experts_first and not self.experts_held) \
                or self.experts_held == self.num_experts > 0:
            raise ValueError(f"held experts {held} outside the "
                             f"{self.num_experts} there are")
        if bool(self.dense_layers) != bool(self.dense_width) \
                or min(self.dense_layers, self.dense_width,
                       self.shared_width) < 0:
            raise ValueError("dense_layers and dense_width come together "
                             "and no width is negative")

    @classmethod
    def of(cls, value) -> "BlockSpec":
        """None (the GPT-2 block), a BlockSpec, or its dict form (what
        `export_decode_model` records in serving.json)."""
        if value is None:
            return GPT2_BLOCK
        if isinstance(value, cls):
            return value
        return cls(**dict(value))

    def to_dict(self) -> dict:
        """The flat dict a bundle's serving.json records. The base fields
        (`_ALWAYS_SAID`) are always said, but for the five of them that
        came with attention="gqa" (its last five: left out, at their
        defaults, of a block that is neither "gqa" nor gives a head_dim: a
        latent block's indexer is said); every later field is said
        where it is not its default, so a block that does not use a newer
        field records what it did before there was that field."""
        out = dataclasses.asdict(self)
        unsaid = () if self.attention == "gqa" or self.head_dim \
            else _ALWAYS_SAID[-5:]
        for key, value in list(out.items()):
            if (key in unsaid or key not in _ALWAYS_SAID) \
                    and value == _DEFAULTS[key]:
                del out[key]
            elif isinstance(value, tuple):
                out[key] = list(value)      # what JSON gives back
        return out

    def head_width(self, n_heads: int, d_model: int) -> int:
        return self.head_dim or d_model // n_heads

    @property
    def _period(self) -> tuple:
        """The `_ENTRIES` records of the period; no pattern: every layer
        "full"."""
        return tuple(_ENTRIES[k] for k in self.layer_pattern) \
            or (_ENTRIES["full"],)

    def _any(self, **what) -> bool:
        """Whether a layer of the period is an entry with these values."""
        return any(all(getattr(entry, k) == v for k, v in what.items())
                   for entry in self._period)

    @property
    def mixers(self) -> tuple:
        """The `_MIXERS` records the block's layers resolve to, in the
        table's order. The block's attention is said model-wide
        (`attention`) and checked whether or not a layer has it."""
        return tuple(name for name in _MIXERS
                     if name == "attention" or self._any(mixer=name))

    def layer(self, i: int, d_ff: int = 0) -> LayerKind:
        """What layer `i` is; `d_ff` the model's FFN width (of one
        expert where there are experts)."""
        period = self._period
        at = i % len(period)
        entry = period[at]
        ffn, width = (("gated", self.dense_width) if i < self.dense_layers
                      else (self.ffn, d_ff)) if entry.ffn else ("none", 0)
        source = -1
        if entry.cache == "shared":     # the nearest earlier layer whose
            source = i - at + max(      # pool it reads
                j for j in range(at)
                if period[j] is _ENTRIES[entry.after[0]])
        published = self.layer_ids[i] if self.layer_ids else i
        own = entry.positions == "full_positions"    # a full layer's
        theta = self.full_rope_theta if own and self.full_rope_theta \
            else self.rope_theta
        return LayerKind(
            self.window if entry.cache == "window" else 0,
            (getattr(self, entry.positions) or self.positions)
            if entry.positions else "none",
            ffn, width, entry.cache, entry.mixer, source, entry.memory,
            published if entry.numbered else -1,
            theta, self.full_rope_scaling if own else ())

    def cache_kinds(self, n_layers: int) -> list:
        """Every layer's kind of cache, "full" | "window" | "state" |
        "shared" | "none"."""
        return [self.layer(i).cache for i in range(n_layers)]

    def lambda_init(self, i: int) -> float:
        """A differential layer's `lambda_init`, from its PUBLISHED
        index: 0.8 - 0.6 exp(-0.3 index)."""
        return 0.8 - 0.6 * math.exp(-0.3 * self.layer(i).published)

    @property
    def held_experts(self) -> int:
        """Experts whose weights this program holds."""
        return self.experts_held or self.num_experts

    @property
    def sparse_sizes(self) -> dict:
        """A "blocksparse" layer's sizes as its op takes them (the window
        and the first blocks counted in blocks)."""
        return {"kernel": self.sparse_kernel, "stride": self.sparse_stride,
                "block": self.sparse_block, "topk": self.sparse_topk,
                "window": self.sparse_window // max(self.sparse_block, 1),
                "init": self.sparse_init,
                "dense_len": self.sparse_dense_len}

    def pooled_rows(self, max_context: int) -> int:
        """Pooled keys a sequence of `max_context` rows holds a
        "blocksparse" layer."""
        from ..ops.block_sparse_ops import pooled_rows
        return pooled_rows(max_context, self.sparse_kernel,
                           self.sparse_stride)

    @property
    def page_rows(self) -> int:
        """The rows a page of the pools must have (a layer that chooses
        whole blocks reads a page as the block it chose); 0: any."""
        return self.sparse_block

    def choosing_layers(self, n_layers: int) -> list:
        """The layers whose attention chooses what it reads: every layer
        of a block with an indexer, and the layers whose mixer chooses."""
        return [i for i in range(n_layers) if self.index_topk > 0
                or _MIXERS[self.layer(i).mixer].chooses]

    def cache_pools(self, n_heads: int, d_model: int,
                    layer: int = None, max_context: int = 0) -> dict:
        """What a paged cache holds of a token in ONE layer: the
        declaration `export_decode_model` records under `decode.cache`
        and the engine allocates from. `pools`: (feed stem, shape of a
        token's row) per pool of a layer; `row_floats`: the floats of
        them that carry the token (a latent row is stored in whole
        lane tiles of 128: the columns past `row_floats` are zeros).
        `layer`: the layer asked about (None: one that keeps pages). A
        layer without pages declares `state`, (feed stem, shape of a
        SEQUENCE's rows), which is all it remembers of a sequence
        however long; a layer whose mixer chooses blocks on pooled keys
        declares both, the `state` with `max_context`. What each mixer
        declares is its record's to say (`_MIXERS`: `remembers`)."""
        if layer is None:   # the period's first layer that keeps pages
            mixer = next((e.mixer for e in self._period
                          if e.cache in ("full", "window")), "attention")
            return _MIXERS[mixer].remembers(self, n_heads, d_model, 0)
        kind = self.layer(layer)
        if kind.cache in ("shared", "none"):
            return {"kind": kind.cache, "row_floats": 0, "pools": []}
        return _MIXERS[kind.mixer].remembers(self, n_heads, d_model,
                                             max_context)


def packed_kv_row(kv_heads: int, width: int) -> list:
    """The shape a pool stores one token's K (or V) heads in: [H_kv, D],
    or, where D is under a lane tile of 128 and the heads fill whole
    tiles, [H_kv D / 128, 128] with 128 / D heads side by side in a
    tile: a pool whose last dimension is under 128 is padded to it in
    the device's memory (8 heads of 64 would take twice their bytes),
    and the grouped decode kernel reads the packed form as it is
    (`kernels.paged_attention._paged_group_kernel`)."""
    if width < 128 and 128 % width == 0 and (kv_heads * width) % 128 == 0:
        return [kv_heads * width // 128, 128]
    return [kv_heads, width]


def _norm(x, name, block):
    scale = ParamAttr(name=f"{name}_scale")
    if block.norm == "rms_norm":
        return layers.rms_norm(x, begin_norm_axis=2, epsilon=block.norm_eps,
                               param_attr=scale, name=name)
    if block.norm == "layer_norm_gain":
        return layers.layer_norm(x, shift=False, begin_norm_axis=2,
                                 epsilon=block.norm_eps, name=name,
                                 param_attr=scale)
    return layers.layer_norm(x, begin_norm_axis=2, epsilon=block.norm_eps,
                             name=name, param_attr=scale,
                             bias_attr=ParamAttr(name=f"{name}_bias"))


def _bias(name, block):
    return ParamAttr(name=name) if block.bias else False


def _scaled(x, by):
    return x if by == 1.0 else layers.scale(x, scale=by)


def _embedding(ids, vocab_size, d_model, block=None):
    out = layers.embedding(ids, [vocab_size, d_model],
                           param_attr=ParamAttr(
                               name="tok_emb",
                               initializer=NormalInitializer(scale=0.02)))
    return out if block is None else _scaled(out, block.embed_scale)


def _head(x, vocab_size, block):
    x = _norm(x, "ln_f", block)
    if block.tied_head:     # logits = x E^T, E the embedding's own table
        from ..core.program import default_main_program
        table = default_main_program().global_block.var("tok_emb")
        return _scaled(layers.matmul(x, table, transpose_y=True,
                                     precision=block.dense_precision),
                       block.logit_scale)
    return _scaled(layers.fc(
        x, size=vocab_size, num_flatten_dims=2,
        param_attr=ParamAttr(name="lm_head_w"),
        bias_attr=_bias("lm_head_b", block), name="lm_head",
        precision=block.dense_precision), block.logit_scale)


def _ffn(x, d_model, d_ff, idx, tp_shard, block, active=None,
         stats_out=None, routes_out=None, load_out=None):
    """Layer `idx`'s FFN on [B, S, d_model]. With experts, `active` marks
    the live rows for the routing counters; each layer appends its
    counters var to `stats_out` (the decode step's business only), its
    chosen experts [B, S, top_k] to `routes_out` and what a training
    step counts of its experts to `load_out`."""
    layer = block.layer(idx, d_ff)
    kind, width = layer.ffn, layer.ffn_width
    if kind == "moe_gated":
        out, stats, experts = layers.moe_gated_ffn(
            x, block.num_experts, width, block.experts_per_tok,
            active=active, name=f"moe{idx}", router=block.router,
            norm_topk=block.norm_topk, routed_scale=block.routed_scale,
            shared_width=block.shared_width,
            shared_scale=block.shared_scale,
            held=(block.experts_first, block.held_experts),
            norm_topk_eps=block.norm_topk_eps or None,
            form=block.expert_form, load_out=load_out)
        if stats_out is not None:
            stats_out.append(stats)
        if routes_out is not None:
            routes_out.append(experts)
        return out
    mixed = block._any(dense_ffn=True)
    if kind == "gated" and (mixed or block.row_chunk
                            and int(x.shape[1]) > block.row_chunk):
        # ONE op, the same weights by the same names: a long bucket's
        # rows a chunk at a time; and in a model with layers whose FFN
        # is the DENSE one behind a mixer (`_Entry.dense_ffn`) under a
        # scope of its own, so that a device trace tells a layer's FFN
        # ("gated_ffn") from its mixer ("mamba2")
        return layers.gated_ffn_rows(x, width, stem=f"ffn{idx}",
                                     rows=block.row_chunk,
                                     precision=block.dense_precision,
                                     scope="gated_ffn" if mixed else "")
    from ..layer_helper import capture_new_params

    def fc(inp, size, tag, act=None):
        return capture_new_params(lambda: layers.fc(
            inp, size=size, num_flatten_dims=2, act=act,
            param_attr=ParamAttr(name=f"ffn{idx}_{tag}_w"),
            bias_attr=_bias(f"ffn{idx}_{tag}_b", block),
            name=f"ffn{idx}_{tag}", precision=block.dense_precision))

    if kind == "gated":     # (silu(x Wg) * (x Wu)) Wd; swish at beta 1
        gate, gate_params = fc(x, width, "gate", act="swish")
        up, up_params = fc(x, width, "up")
        up_params = gate_params + up_params
        out, down_params = fc(layers.elementwise_mul(gate, up), d_model,
                              "down")
    else:
        h, up_params = fc(x, width, "in", act="gelu")
        out, down_params = fc(h, d_model, "out")
    if tp_shard:
        from ..parallel.mesh import TP
        for v in up_params:
            if len(v.shape) == 2:
                v.sharding = (None, TP)      # column-parallel up-proj
        for v in down_params:
            if len(v.shape) == 2:
                v.sharding = (TP, None)      # row-parallel down-proj
    return out


def _gmu(x, memory, idx, d_model, block):
    """A gated memory unit in the attention's place: (silu(x W_in) * m)
    W_out, m the scan output an earlier "memory" layer handed on for
    the same rows ([B, S, ssm_inner]); no bias, no state."""
    def fc(inp, size, tag, act=None):
        return layers.fc(inp, size=size, num_flatten_dims=2, act=act,
                         param_attr=ParamAttr(name=f"gmu{idx}_{tag}_w"),
                         bias_attr=False, name=f"gmu{idx}_{tag}",
                         precision=block.dense_precision)

    gate = fc(x, block.ssm_inner, "in", act="swish")
    return fc(layers.elementwise_mul(gate, memory), d_model, "out")


def _residual(x, att, ln, ffn, idx, block):
    """The layer's output from its input x, its attention's output and
    its FFN, a function of a normed stream: sequential (the FFN reads a
    second norm of x + att) or parallel (it reads `ln`, the one norm the
    attention read); a layer that is one part alone is x + that part of
    `ln` (`att` None: the FFN; `LayerKind.ffn` "none": the mixer)."""
    by = block.residual_scale      # each branch times it (1: as it is)
    if att is None:
        return layers.elementwise_add(x, _scaled(ffn(ln), by))
    att = _scaled(att, by)
    if block.layer(idx).ffn == "none":
        return layers.elementwise_add(x, att)
    if block.parallel:
        return layers.elementwise_add(layers.elementwise_add(x, att),
                                      _scaled(ffn(ln), by))
    x = layers.elementwise_add(x, att)
    return layers.elementwise_add(
        x, _scaled(ffn(_norm(x, f"ln2_{idx}", block)), by))


# ---------------------------------------------------------------------------
# The mixers: one record each (`_MIXERS`). What a mixer takes of the block,
# what it refuses, what it remembers of a sequence and how a layer of it is
# built, for a prompt and for a step, are said there and nowhere else.
# ---------------------------------------------------------------------------

class _Stream:
    """What the layers of ONE program share: the block and its sizes, the
    residual stream `x`, its norm `ln1` that the layer's mixer reads, what
    a "memory" layer handed on (`memory`: its scan output, before its
    gate), and the program's mode: a prompt's keywords (`n_tokens`,
    `collect_kv`, `head_rows`, `max_len`; `trained`: what the training
    program alone passes its attention) or a step's (`pools`, a tuple a
    layer; `tables`, by kind of cache; `context_lens`; `positions`, and
    `own_positions`: a "linear" layer's where the block carries none),
    the other mode's left None. `selected` receives what a choosing layer
    chose, in either."""

    def __init__(self, block, n_heads, d_model, x, *, selected=None,
                 n_tokens=None, collect_kv=None, head_rows=None, max_len=0,
                 trained=None, pools=None, tables=None, context_lens=None,
                 positions=None, own_positions=None):
        self.block, self.n_heads, self.d_model = block, n_heads, d_model
        self.x, self.ln1, self.memory, self.selected = x, None, None, selected
        self.n_tokens, self.collect_kv = n_tokens, collect_kv
        self.head_rows, self.max_len = head_rows, max_len
        self.trained, self.pools, self.tables = trained, pools, tables
        self.context_lens = context_lens
        self.positions, self.own_positions = positions, own_positions
        self.step = pools is not None
        self.shared = {}        # a prompt: a differential layer's (K, V),
        self.narrowed = False   # for "cross" layers; only the head rows
        self.pool_outs = []     # go on. A step: the layers' pools after it

    def paged_kw(self, i, kind):
        """What a dual-mode function takes of a step's pages: the layer's
        pools and the table its kind of cache is reached through."""
        return dict(pools=self.pools[i], context_lens=self.context_lens,
                    block_tables=self.tables.get(kind.cache,
                                                 self.tables["full"])) \
            if self.step else {}

    def state_kw(self, i):
        """And of a state layer's memory, in either mode."""
        return dict(n_tokens=self.n_tokens, state_out=self.collect_kv,
                    state=self.pools[i] if self.step else None,
                    context_lens=self.context_lens)

    def pair(self, got):
        """(the mixer's output, the layer's memory after a step) of what
        a dual-mode function returns: that pair for a step, the output
        alone for a prompt."""
        return got if self.step else (got, ())


def _state(*rows):
    """The `cache_pools` answer of a layer that keeps no pages: (feed
    stem, shape of a SEQUENCE's rows) each."""
    return {"kind": "state", "row_floats": 0, "pools": [],
            "state": list(rows)}


def _kv(kind, row, used):
    return {"kind": kind, "row_floats": used,
            "pools": [("k_cache", row), ("v_cache", row)]}


def _takes_taps(block, mixers=None):
    if block.conv_taps < 2:
        raise ValueError(
            "conv_taps (>= 2) comes with a 'conv' or 'mamba' layer: "
            f"{block.layer_pattern} and conv_taps {block.conv_taps}")


def _takes_head_norms(block):
    if not block.qk_norm:
        raise ValueError("'linear' and 'blocksparse' layers are built "
                         "with per-head q/k-norm")


_PLAIN_SCALE = (
    "attn_scale is built for plain grouped-query attention (the flash "
    "forward, its query-row chunks, the grouped paged kernel): not "
    "differential, indexed or block-sparse")


# -- attention: its form is the block's (`attention`, `differential`), said
# model-wide, so its refusals run whether or not a layer of the period has it

def _check_attention(block, mixers):
    index = (block.index_heads, block.index_head_dim, block.index_topk)
    turned = block.index_rope_dim
    if any(index) and (min(index) < 1 or block.index_head_dim % 2):
        raise ValueError(
            "an indexer needs index_heads, index_topk >= 1 and "
            f"an even index_head_dim, got {index}")
    if block.q_lora_rank < 0 or turned < 0 or turned % 2 \
            or turned > block.index_head_dim \
            or (block.index_rope_interleave and not any(index)):
        raise ValueError(
            "q_lora_rank is a width, index_rope_dim an even part of "
            "index_head_dim and index_rope_interleave an indexer's: "
            f"{block.q_lora_rank}, {turned} of {block.index_head_dim}")
    if block.attention == "gqa":
        if block.n_kv_heads < 1 or block.head_dim < 2 \
                or block.head_dim % 2:
            raise ValueError("gqa needs n_kv_heads >= 1 and an even "
                             f"head_dim, got {block.n_kv_heads} and "
                             f"{block.head_dim}")
        if block.bias or block.positions == "learned" or (
                block.positions == "none" and not block.differential
                and not any(_MIXERS[m].orders for m in mixers)):
            raise ValueError("gqa is built with rotary positions and "
                             "no bias (`attn_bias` for its own "
                             "projections'); without positions where "
                             "it is differential or beside 'mamba2', "
                             "'mamba2_ffn' or 'linear' layers, which "
                             "carry the order")
        if block.differential and (
                block.positions != "none" or block.qk_norm or any(index)
                or block.n_kv_heads % 2 or block.head_dim % 2):
            raise ValueError(
                "differential attention is built without positions, "
                "q/k-norm or an indexer, over an even number of K/V "
                "heads")
    elif block.n_kv_heads or block.differential \
            or block.attn_bias or block.positions == "none" \
            or block.attn_scale:
        raise ValueError("n_kv_heads, differential, attn_bias, "
                         "attn_scale and positions='none' belong to "
                         "attention='gqa'")
    elif block.attention == "latent" and block.head_dim:
        raise ValueError("a latent head's widths are the four latent "
                         "ones, not head_dim")
    elif any(index) and (block.attention != "latent"
                         or not block.q_lora_rank):
        raise ValueError("the indexer's widths belong to attention='gqa' "
                         "or to attention='latent' with a q_lora_rank, "
                         "which its query heads are projected from")
    if block.attention != "latent" and (block.q_lora_rank or turned
                                        or block.index_rope_interleave):
        raise ValueError("q_lora_rank, index_rope_dim and "
                         "index_rope_interleave belong to "
                         "attention='latent'")
    if (block.full_positions
            or any(e is not _ENTRIES["full"] for e in block._period)) \
            and (block.attention != "gqa" or any(index)):
        raise ValueError("window layers, state layers, cross layers "
                         "and full_positions are built for "
                         "attention='gqa' without an indexer")
    if block.attn_scale and (block.differential or any(index)
                             or block._any(cache="shared")):
        raise ValueError(_PLAIN_SCALE)
    scaling = block.full_rope_scaling
    if block.full_rope_theta < 0 or (scaling and (
            len(scaling) != 5 or min(scaling) <= 0)):
        raise ValueError(
            "full_rope_theta is a base (0: the block's) and "
            "full_rope_scaling YaRN's five (factor, original context, "
            "beta_fast, beta_slow, attention factor), all positive: "
            f"{block.full_rope_theta} and {scaling}")
    if (block.full_rope_theta or scaling) and (
            block.attention != "gqa" or any(index) or block.differential
            or (block.full_positions or block.positions) != "rope"):
        raise ValueError(
            "full_rope_theta and full_rope_scaling are the rotary "
            "table of plain grouped-query FULL layers that rotate "
            "(attention='gqa', positions 'rope', no indexer)")
    latent = (block.kv_lora_rank, block.qk_nope_head_dim,
              block.qk_rope_head_dim, block.v_head_dim)
    if block.attention == "latent":
        if min(latent) < 1 or block.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention needs kv_lora_rank, "
                "qk_nope_head_dim, v_head_dim >= 1 and an even "
                f"qk_rope_head_dim, got {latent}")
        if block.positions != "rope" or block.qk_norm or block.bias:
            raise ValueError("latent attention is built with rotary "
                             "positions, no q/k-norm and no bias")
    elif any(latent):
        raise ValueError("the latent widths belong to "
                         "attention='latent'")
    elif block.rope_interleave and block.attention != "gqa":
        raise ValueError("rope_interleave belongs to attention="
                         "'latent' or 'gqa'")


def _attention_remembers(block, n_heads, d_model, max_context):
    width = block.head_width(n_heads, d_model)
    if block.attention == "latent":     # one row, in whole lane tiles
        used = block.kv_lora_rank + block.qk_rope_head_dim
        row = [-(-used // 128) * 128]
        if not block.index_topk:
            return {"kind": "latent", "row_floats": used,
                    "pools": [("latent_cache", row)]}
        # an index key beside it, and the latent row [1, W]: one a copy of
        # the kernel that reads the SELECTED rows (`kernels/
        # paged_attention.py`, the text above
        # `paged_sparse_latent_attention`)
        return {"kind": "latent_index",
                "row_floats": used + block.index_head_dim,
                "pools": [("latent_cache", [1] + row),
                          ("index_cache",
                           [-(-block.index_head_dim // 128) * 128])]}
    if block.attention == "mha":
        return _kv("kv", [n_heads, width], 2 * n_heads * width)
    used = 2 * block.n_kv_heads * width
    if block.differential:
        # K head g of the first set beside K head g of the second, and V
        # the same (what one differential head pair reads), the pairs
        # side by side in the row's lanes
        return _kv("kv_diff", [block.n_kv_heads * width], used)
    if not block.index_topk:
        return _kv("kv", packed_kv_row(block.n_kv_heads, width), used)
    out = _kv("kv_index", [block.n_kv_heads, width],
              used + block.index_head_dim)
    out["pools"].append(("index_cache",     # in whole lane tiles
                         [-(-block.index_head_dim // 128) * 128]))
    return out


def _mha(b, i, kind):
    block = b.block
    if b.step:      # a builder of its own: the weights by the same names
        att, k_out, v_out = _decode_attention(
            b.ln1, i, b.n_heads, block.head_width(b.n_heads, b.d_model),
            b.d_model, b.pools[i][0], b.pools[i][1], b.tables["full"],
            b.context_lens, block, b.positions)
        return att, (k_out, v_out)
    return layers.multi_head_attention(
        b.ln1, num_heads=b.n_heads, d_key=block.head_dim or None,
        kv_out=b.collect_kv, name=f"attn{i}",
        bias_attr=None if block.bias else False,
        qk_norm_eps=block.norm_eps if block.qk_norm else None,
        rope_theta=(block.rope_theta
                    if block.positions == "rope" else None),
        **b.trained), ()


def _latent(b, i, kind):
    block = b.block
    rows = [] if b.collect_kv is not None else None
    indexed = block.index_topk > 0
    got = layers.latent_attention(
        b.ln1, name=f"attn{i}", latent_out=rows,
        pool=b.pools[i][0] if b.step else None,
        index_pool=b.pools[i][1] if b.step and indexed else None,
        selected_out=b.selected,
        block_tables=b.tables["full"] if b.step else None,
        context_lens=b.context_lens, positions=b.positions,
        num_heads=b.n_heads, kv_lora_rank=block.kv_lora_rank,
        qk_nope_head_dim=block.qk_nope_head_dim,
        qk_rope_head_dim=block.qk_rope_head_dim,
        v_head_dim=block.v_head_dim, rope_theta=block.rope_theta,
        rope_interleave=block.rope_interleave, epsilon=block.norm_eps,
        q_lora_rank=block.q_lora_rank, index_heads=block.index_heads,
        index_head_dim=block.index_head_dim, index_topk=block.index_topk,
        index_rope_dim=block.index_rope_dim,
        index_rope_interleave=block.index_rope_interleave)
    if rows:
        b.collect_kv.append(tuple(rows))
    return b.pair(got)


def _gqa(b, i, kind):
    block = b.block
    rotary = ("none" if kind.positions == "none" else
              "interleave" if block.rope_interleave else "half")
    return b.pair(layers.grouped_attention(
        b.ln1, name=f"attn{i}", cache_out=b.collect_kv,
        selected_out=b.selected, positions=b.positions,
        **b.paged_kw(i, kind), num_heads=b.n_heads,
        num_kv_heads=block.n_kv_heads, head_dim=block.head_dim,
        rope_theta=kind.rope_theta, rope_scaling=kind.rope_scaling,
        qk_norm=block.qk_norm, index_heads=block.index_heads,
        index_head_dim=block.index_head_dim, index_topk=block.index_topk,
        epsilon=block.norm_eps, window=kind.window, rotary=rotary,
        scale=block.attn_scale))


def _differential(b, i, kind):
    """The two modes really differ: a prompt's layer keeps its (K, V) for
    the "cross" layers behind it and, asked for the head rows alone,
    narrows the stream to them; a step's reads pools."""
    block = b.block
    args = dict(num_heads=b.n_heads, num_kv_heads=block.n_kv_heads,
                head_dim=block.head_dim, lambda_init=block.lambda_init(i),
                epsilon=block.norm_eps, window=kind.window)
    cross = kind.cache == "shared"
    if b.step:
        # a cross layer reads its source's pools as this step left them,
        # through the full layers' table, and writes nothing
        return layers.diff_attention(
            b.ln1, name=f"attn{i}", kv=True if cross else None,
            **dict(b.paged_kw(i, kind), pools=b.pool_outs[kind.kv_source]
                   if cross else b.pools[i]), **args)
    if cross:
        return layers.diff_attention(
            b.ln1, name=f"attn{i}", kv=b.shared[kind.kv_source],
            q_rows=b.head_rows if b.narrowed else None, **args), ()
    rows = []
    whole = None
    if block._any(cache="shared") and b.head_rows is not None \
            and kind.cache == "full":
        # from this layer's query on, the head rows alone
        whole, b.narrowed = b.ln1, True
        b.x = layers.batch_gather(b.x, b.head_rows)
        b.ln1 = layers.batch_gather(b.ln1, b.head_rows)
        if b.memory is not None:
            b.memory = layers.batch_gather(b.memory, b.head_rows)
    att = layers.diff_attention(
        b.ln1, name=f"attn{i}", cache_out=rows, kv_from=whole,
        q_rows=b.head_rows if whole is not None else None, **args)
    b.shared[i] = rows[0]
    if b.collect_kv is not None:
        b.collect_kv.append(rows[0])
    return att, ()


# -- the mixers in the attention's place, and beside it

def _short_conv(b, i, kind):
    got = layers.short_conv(
        b.ln1, taps=b.block.conv_taps, name=f"conv{i}",
        n_tokens=b.n_tokens, state_out=b.collect_kv,
        state=b.pools[i][0] if b.step else None,
        context_lens=b.context_lens)
    return (got[0], (got[1],)) if b.step else (got, ())


def _check_mamba(block, mixers):
    _takes_taps(block)
    ssm = (block.ssm_inner, block.ssm_state, block.ssm_dt_rank)
    if min(ssm) < 1:
        raise ValueError(
            "ssm_inner, ssm_state and ssm_dt_rank (>= 1) come with a "
            "'mamba' layer, ssm_heads, ssm_groups and ssm_chunk with "
            "a 'mamba2' layer (ssm_chunk with a 'linear' one too): "
            f"{block.layer_pattern} and {ssm}")


def _mamba(b, i, kind):
    block = b.block
    handed = [] if kind.memory == "gives" else None
    got = layers.selective_scan(
        b.ln1, name=f"mamba{i}", memory_out=handed,
        d_inner=block.ssm_inner, d_state=block.ssm_state,
        dt_rank=block.ssm_dt_rank, taps=block.conv_taps, **b.state_kw(i))
    if handed:
        b.memory = handed[0]
    return b.pair(got)


def _check_mamba2(block, mixers):
    _takes_taps(block)
    ssd = (block.ssm_inner, block.ssm_state, block.ssm_heads,
           block.ssm_groups)
    if "mamba" in mixers or min(ssd) < 1 or block.ssm_dt_rank \
            or block.ssm_chunk < 0 \
            or block.ssm_inner % block.ssm_heads \
            or block.ssm_heads % block.ssm_groups:
        raise ValueError(
            "a 'mamba2' or 'mamba2_ffn' layer takes ssm_inner, "
            "ssm_state, ssm_heads (dividing ssm_inner) and "
            "ssm_groups (dividing ssm_heads), no ssm_dt_rank, and "
            f"stands beside no 'mamba' layer: {block.layer_pattern} "
            f"and {ssd}")
    if block._any(dense_ffn=True) and block.ffn == "moe_gated":
        raise ValueError(
            "a 'mamba2_ffn' layer's feed-forward part is the "
            "block's DENSE one ('gelu' | 'gated'); experts beside "
            "a Mamba-2 mixer are layers of their own ('mamba2', "
            "'ffn')")


def _mamba2(b, i, kind):
    block = b.block
    return b.pair(layers.mamba2_mixer(
        b.ln1, name=f"mamba{i}", d_inner=block.ssm_inner,
        d_state=block.ssm_state, heads=block.ssm_heads,
        groups=block.ssm_groups, taps=block.conv_taps,
        chunk=block.ssm_chunk or 128, epsilon=block.norm_eps,
        **b.state_kw(i)))


def _check_linear(block, mixers):
    if block.decay_layers < 2 or block.linear_positions not in ("", "rope"):
        raise ValueError(
            "a 'linear' layer takes decay_layers >= 2 (the "
            "published depth its decay is computed from) and "
            f"linear_positions '' or 'rope': {block.decay_layers} "
            f"and {block.linear_positions!r}")
    _takes_head_norms(block)


def _linear(b, i, kind):
    block = b.block
    return b.pair(layers.linear_attention(
        b.ln1, name=f"attn{i}", positions=b.own_positions,
        heads=b.n_heads, head_dim=block.head_width(b.n_heads, b.d_model),
        layer=kind.published, n_layers=block.decay_layers,
        rope_theta=block.rope_theta,
        rotary="half" if kind.positions == "rope" else "none",
        chunk=block.ssm_chunk or 128, epsilon=block.norm_eps,
        gate=block.attn_gate, **b.state_kw(i)))


_SPARSE_SIZES = ("sparse_kernel", "sparse_stride", "sparse_block",
                 "sparse_topk", "sparse_window", "sparse_init")


def _check_blocksparse(block, mixers):
    sparse = tuple(getattr(block, f) for f in _SPARSE_SIZES)
    if min(sparse) < 1 or block.sparse_dense_len < 0 \
            or block.sparse_kernel % block.sparse_stride \
            or block.sparse_block % block.sparse_stride \
            or block.sparse_window % block.sparse_block \
            or block.sparse_init + block.sparse_window \
            // block.sparse_block > block.sparse_topk:
        raise ValueError(
            "a 'blocksparse' layer takes sparse_kernel and "
            "sparse_block in whole sparse_strides, sparse_window "
            "in whole blocks, and sparse_topk blocks that hold "
            f"the first and the local ones: {sparse}")
    _takes_head_norms(block)
    if block.attn_scale:
        raise ValueError(_PLAIN_SCALE)


def _blocksparse_remembers(block, n_heads, d_model, max_context):
    # the K/V heads side by side in the lanes (a block of one head is
    # then one copy at whole lane tiles) and, asked with `max_context`,
    # the sequence's pooled keys
    row = [block.n_kv_heads * block.head_width(n_heads, d_model)]
    out = _kv("kv_blocks", row, 2 * row[0])
    if max_context:
        out["state"] = [("pooled_keys",
                         [block.pooled_rows(max_context)] + row)]
    return out


def _blocksparse(b, i, kind):
    block = b.block
    return b.pair(layers.block_sparse_attention(
        b.ln1, name=f"attn{i}", n_tokens=b.n_tokens,
        max_pooled=0 if b.collect_kv is None
        else block.pooled_rows(b.max_len),
        cache_out=b.collect_kv, selected_out=b.selected,
        **b.paged_kw(i, kind), num_heads=b.n_heads,
        num_kv_heads=block.n_kv_heads, head_dim=block.head_dim,
        sizes=block.sparse_sizes, epsilon=block.norm_eps,
        gate=block.attn_gate))


@dataclasses.dataclass(frozen=True)
class _Mixer:
    """What ONE mixer (a value of `LayerKind.mixer`) is."""

    build: object           #: (stream, layer, its LayerKind) -> (its output
    #: of `stream.ln1`, the layer's memory after a step: a tuple, () for a
    #: prompt). ONE function where a prompt's layer and a step's differ
    #: only in the keywords `_Stream` hands them
    remembers: object = None    #: its arm of `BlockSpec.cache_pools`:
    #: (block, n_heads, d_model, max_context) -> the declaration
    check: object = None    #: (block, the period's mixers): its refusals,
    #: run where the period has it
    fields: tuple = ()      #: the `BlockSpec` fields that mean something
    #: only with it (or with another that lists them too): set beside no
    #: such mixer, they are refused
    called: str = ""        #: what that refusal calls them; "": by name
    orders: bool = False    #: it carries the order of its rows: a block
    #: without positions may stand on it
    chooses: bool = False   #: it chooses the rows it reads, and says which


_ATTENTION = {"mha": _mha, "latent": _latent, "gqa": _gqa,
              "differential": _differential}


def _attention(b, i, kind):
    """Of the form the block's attention takes."""
    return _ATTENTION["differential" if b.block.differential
                      else b.block.attention](b, i, kind)


#: in the order the refusals run. "linear" stands before "blocksparse":
#: `attn_gate`, which both list, is then named by its name where neither
#: is there, and `called` speaks of the sparse sizes alone. What a state
#: layer remembers: (feed stem, shape of a SEQUENCE's rows) each.
_MIXERS = {
    "attention": _Mixer(
        _attention, _attention_remembers, _check_attention,
        fields=("window", "full_positions", "differential", "attn_bias",
                "attn_scale", "full_rope_theta", "full_rope_scaling",
                "q_lora_rank", "index_rope_dim", "index_rope_interleave")),
    "short_conv": _Mixer(
        _short_conv, lambda block, heads, d_model, context: _state(
            ("conv_state", [block.conv_taps - 1, d_model])),
        _takes_taps, fields=("conv_taps",)),
    # the scan's state with the channels on the lanes ([d_state, d_inner]:
    # a last dimension of 16 would be padded to 128 in the device's
    # memory, eight times its bytes) and the convolution's rows before
    # the token
    "mamba": _Mixer(
        _mamba, lambda block, heads, d_model, context: _state(
            ("ssm_state", [block.ssm_state, block.ssm_inner]),
            ("conv_state", [block.conv_taps - 1, block.ssm_inner])),
        _check_mamba,
        fields=("conv_taps", "ssm_inner", "ssm_state", "ssm_dt_rank")),
    # (silu(x W_in) * m) W_out, m what a "memory" layer handed on: no
    # state of its own
    "gmu": _Mixer(lambda b, i, kind: (
        _gmu(b.ln1, b.memory, i, b.d_model, b.block), ())),
    # a matrix a HEAD, the state's columns on the lanes, and the
    # convolution's rows of x, B and C before the token
    "mamba2": _Mixer(
        _mamba2, lambda block, heads, d_model, context: _state(
            ("ssm_state", [block.ssm_heads,
                           block.ssm_inner // block.ssm_heads,
                           block.ssm_state]),
            ("conv_state", [block.conv_taps - 1, block.ssm_inner
                            + 2 * block.ssm_groups * block.ssm_state])),
        _check_mamba2, orders=True,
        fields=("conv_taps", "ssm_inner", "ssm_state", "ssm_heads",
                "ssm_groups", "ssm_chunk")),
    # a matrix a head
    "linear": _Mixer(
        _linear, lambda block, heads, d_model, context: _state(
            ("ssm_state", [heads] + 2 * [block.head_width(heads,
                                                          d_model)])),
        _check_linear, orders=True,
        fields=("decay_layers", "linear_positions", "ssm_chunk",
                "attn_gate")),
    "blocksparse": _Mixer(
        _blocksparse, _blocksparse_remembers, _check_blocksparse,
        fields=_SPARSE_SIZES + ("sparse_dense_len", "attn_gate"),
        called="the sparse_* sizes", chooses=True),
    # the layer is its feed-forward part alone
    "none": _Mixer(lambda b, i, kind: (None, ())),
}

#: the fields past the base ones that are no mixer's: the experts' (an FFN
#: form's: set beside another `ffn`, they are refused, with the base ones
#: that are the experts' too) and the model's own
_EXPERT_FIELDS = ("router", "norm_topk", "routed_scale", "shared_width",
                  "dense_layers", "dense_width", "shared_scale",
                  "experts_first", "experts_held", "norm_topk_eps",
                  "expert_form")
_MODEL_FIELDS = ("parallel", "tied_head", "layer_pattern", "layer_ids",
                 "dense_precision", "embed_scale", "residual_scale",
                 "logit_scale", "row_chunk")
#: what each field that names a choice may say
_KNOWN = {
    "norm": ("layer_norm", "rms_norm", "layer_norm_gain"),
    "positions": ("learned", "rope", "none"),
    "ffn": ("gelu", "gated", "moe_gated"),
    "attention": ("mha", "latent", "gqa"),
    "router": ("softmax", "sigmoid_bias", "sigmoid"),
    "dense_precision": ("", "high"),
    "expert_form": ("", "relu2"),
    "full_positions": ("", "none"),
}
_DEFAULTS = {f.name: f.default for f in dataclasses.fields(BlockSpec)}
#: the base fields, `norm` .. `index_topk`: what `to_dict` always says
_ALWAYS_SAID = tuple(_DEFAULTS)[:tuple(_DEFAULTS).index("index_topk") + 1]
GPT2_BLOCK = BlockSpec()


def transformer_lm(src_ids, vocab_size, n_layers=2, d_model=128, n_heads=4,
                   d_ff=512, max_len=2048, dropout_rate=0.0,
                   causal=True, sp_mode="none", tp_shard=False,
                   remat=False, pos_table_len=None, collect_kv=None,
                   collect_routes=None, block=None, head_rows=None,
                   collect_selected=None, n_tokens=None,
                   collect_moe_load=None):
    """src_ids: [B, S] int64 var. Returns logits [B, S, vocab_size].

    block: a `BlockSpec` (or its dict form); None is the GPT-2 block.

    pos_table_len: size the `pos_emb` parameter to this many rows and
    slice the first S at use (default None keeps the historical
    shape-[S, d] parameter). A prefill program built per length bucket
    passes the trained sequence length here so every bucket shares the
    one trained table.

    collect_kv: optional list — each layer appends what a paged cache
    holds of its tokens, a tuple with one var per pool of
    `block.cache_pools`: per-head (k, v) ([B, S, H, d_key]), or the one
    latent row ([B, S, rank + rope]); the decode export fetches them to
    seed the cache (serving/decode).

    head_rows: an int var [B, K]: the head is computed for those K
    positions of each row only and the logits are [B, K, vocab_size] (a
    prefill wants its last position's row, not [S, vocab]).

    collect_routes: optional list; each layer with experts appends its
    chosen experts ([B, S, top_k] int32), for the decode export.

    collect_selected: optional list; each layer with an indexer appends
    the positions every row attended to, one bit a position ([B, S,
    ceil(S / 32)] int32), for the decode export.

    n_tokens: an int var [B], each row's true length: with `collect_kv`
    a state layer appends, in its K/V's place, the state a sequence of
    that length leaves (a "conv" layer's [B, conv_taps - 1, d_model]: the
    rows before position n_tokens, not before the padded bucket's end).

    A block with "cross" layers that is asked for `head_rows` alone runs
    its second decoder on THOSE rows: the layers up to its "full" layer
    and that layer's K and V run over the whole sequence, from that
    layer's query on only the head rows go through (a "gmu" layer and a
    "cross" layer keep nothing of a token, so nothing else is ever read
    of the other rows): the architecture's own saving, no approximation.
    """
    block = BlockSpec.of(block)
    seq_len = int(src_ids.shape[1])
    if seq_len > max_len:
        raise ValueError(f"sequence length {seq_len} exceeds max_len "
                         f"{max_len}; raise max_len")
    pos_rows = seq_len if pos_table_len is None else int(pos_table_len)
    if seq_len > pos_rows:
        raise ValueError(f"sequence length {seq_len} exceeds the "
                         f"pos_table_len {pos_rows} rows of pos_emb")
    x = _embedding(src_ids, vocab_size, d_model, block)
    if block.positions == "learned":
        pos = layers.create_parameter([pos_rows, d_model],
                                      dtype="float32", name="pos_emb",
                                      default_initializer=NormalInitializer(
                                          scale=0.02))
        if pos_rows != seq_len:
            pos = layers.slice(pos, axes=[0], starts=[0], ends=[seq_len])
        x = layers.elementwise_add(x, pos)
    if dropout_rate:
        x = layers.dropout(x, dropout_prob=dropout_rate)

    b = _Stream(block, n_heads, d_model, x, n_tokens=n_tokens,
                collect_kv=collect_kv, head_rows=head_rows,
                selected=collect_selected, max_len=max_len,
                trained=dict(causal=causal, sp_mode=sp_mode,
                             dropout_rate=dropout_rate, tp_shard=tp_shard))
    loads = [] if collect_moe_load is not None else None
    for i in range(n_layers):
        # remat: each transformer layer becomes one jax.checkpoint segment
        # (activation memory ~O(n_layers) -> O(1) per layer boundary).
        # remat may be a policy string ("save_attn" | "dots") — see
        # core.program.remat_scope: save_attn keeps the flash-attention
        # outputs so the backward skips the attention recompute.
        policy = remat if isinstance(remat, str) else None
        scope = remat_scope(f"tfm_layer_{i}", policy=policy) if remat \
            else contextlib.nullcontext()
        kind = block.layer(i)
        with scope:
            b.ln1 = _norm(b.x, f"ln1_{i}", block)
            att, _ = _MIXERS[kind.mixer].build(b, i, kind)
            b.x = _residual(b.x, att, b.ln1, lambda h: _ffn(
                h, d_model, d_ff, i, tp_shard, block,
                routes_out=collect_routes, load_out=loads), i, block)

    if loads:
        collect_moe_load.append(layers.sums(loads))
    x = b.x
    if head_rows is not None and not b.narrowed:
        x = layers.batch_gather(x, head_rows)
    return _head(x, vocab_size, block)


def transformer_lm_loss(vocab_size=1000, seq_len=128, **kw):
    """Build data vars + LM loss. Returns (avg_cost, logits)."""
    block = BlockSpec.of(kw.get("block"))
    for entry in _ENTRIES.values():     # a kind that is served, not trained
        if entry.untrained and entry in block._period:
            raise NotImplementedError(entry.untrained)
    if block.index_topk:
        raise NotImplementedError(
            "a sparse-attention indexer is served, not trained: its "
            "training loss (a KL of the indexer's scores against the "
            "dense attention's distribution) is not built; train the "
            "block with index_topk=0 (plain grouped-query attention)")
    src = layers.data("src_ids", [seq_len], dtype="int64")
    tgt = layers.data("tgt_ids", [seq_len, 1], dtype="int64")
    logits = transformer_lm(src, vocab_size, **kw)
    loss = layers.softmax_with_cross_entropy(logits, tgt)
    avg = layers.mean(loss)
    return avg, logits


# ---------------------------------------------------------------------------
# Autoregressive decode-step program (serving/decode)
# ---------------------------------------------------------------------------

def cache_feeds(block, i, n_heads, d_model, slots, block_size, blocks_of,
                max_context=0):
    """What the decode step takes, and returns, for layer `i`'s memory:
    [(feed stem, the whole array's shape)]. A pool is [blocks of the
    layer's kind of cache, block_size, *a token's row]; a state is
    [slots, *a sequence's rows] (a "blocksparse" layer has both: its
    pooled keys number by `max_context`)."""
    cache = block.cache_pools(n_heads, d_model, i, max_context)
    n_blocks = blocks_of.get(block.layer(i).cache, 0)
    return [(stem, [n_blocks, block_size] + list(row))
            for stem, row in cache["pools"]] \
        + [(stem, [slots] + list(rows)) for stem, rows in
           cache.get("state", ())]


def _decode_attention(x, idx, num_heads, d_key, d_model, k_pool, v_pool,
                      block_tables, context_lens, block, positions=None):
    """One layer's decode attention: project the single new token per
    slot, write its K/V row into the paged pool, attend through the block
    table. Parameter names match multi_head_attention(name=f"attn{idx}")
    so the decode program shares the trained weights by name. With
    rotary positions q and k are rotated by `positions` ([slots, 1],
    each slot's own) before the write: the pool holds rotated K, as the
    prefill's K/V seeded it."""
    from ..layers.attention import qk_normed
    name = f"attn{idx}"

    def proj(inp, width, tag):
        return layers.fc(inp, size=width, num_flatten_dims=2,
                         param_attr=ParamAttr(name=f"{name}_{tag}_w"),
                         bias_attr=_bias(f"{name}_{tag}_b", block),
                         name=f"{name}_{tag}")

    q = proj(x, num_heads * d_key, "q")
    k = proj(x, num_heads * d_key, "k")
    v = proj(x, num_heads * d_key, "v")
    q, k = qk_normed(q, k, block.norm_eps if block.qk_norm else None, name)
    qr = layers.reshape(q, [0, 0, num_heads, d_key])
    kr = layers.reshape(k, [0, 0, num_heads, d_key])
    vr = layers.reshape(v, [0, 0, num_heads, d_key])
    if block.positions == "rope":
        qr = layers.rotary_embedding(qr, positions, block.rope_theta)
        kr = layers.rotary_embedding(kr, positions, block.rope_theta)
    k_out, v_out = layers.paged_kv_write(k_pool, v_pool, kr, vr,
                                         block_tables, context_lens)
    ctx = layers.paged_attention(qr, k_out, v_out, block_tables,
                                 context_lens)
    merged = layers.reshape(ctx, [0, 0, num_heads * d_key])
    return proj(merged, d_model, "out"), k_out, v_out


def transformer_decode_step(vocab_size, *, n_layers, d_model, n_heads,
                            d_ff, max_context, slots, block_size,
                            pool_blocks, max_blocks_per_seq, block=None,
                            moe_stats_out=None, moe_routes_out=None,
                            selected_out=None, window_pool_blocks=0):
    """Build the fixed-shape continuous-batching decode step: ONE new
    token per active slot against the paged KV pool.

    block: a `BlockSpec` (or its dict form); None is the GPT-2 block.
    With experts the step also carries the routing counters: one more
    feed, `moe_stats` [3] int32 (pairs routed, experts touched,
    layer-steps, all over live slots only; [4] where the block holds a
    share of the experts: the pairs on held experts last), and the var holding that
    feed plus this step's counts over all layers is appended to
    `moe_stats_out` for the caller to fetch and feed back, as it does
    the pools. `moe_routes_out` receives one var, the step's chosen
    experts [n_layers, slots, top_k] int32 (inactive slots' rows too).
    With an indexer `selected_out` receives one var, the positions each
    slot attended to in each layer, [n_layers, slots, index_topk] int32
    (-1 behind a slot's count).

    Feeds (all static shape; no batch coalescing — the slot axis IS the
    batch): token_ids [slots] int64, context_lens [slots] int32 (span
    INCLUDING the new token; 0 = inactive slot), block_tables
    [slots, max_blocks_per_seq] int32 (entries into the pool; 0 is the
    reserved null block), and per layer the pools `block.cache_pools`
    declares, `{stem}_{i}` [pool_blocks, block_size, *row]:
    k_cache_{i}/v_cache_{i} with rows [H, d_key] (of the K/V heads
    where groups share them, and index_cache_{i} beside them with an
    indexer), or latent_cache_{i}. A block with window layers has two
    kinds of cache, each with block ids of its own: one more feed behind
    `block_tables`, `window_tables` (the same shape: entry p // block_size
    names the block of position p in either; a window layer's entries
    behind the window are the null block and are never read), and a
    window layer's pools hold `window_pool_blocks` blocks. A state
    layer has no pool: its feeds are `{stem}_{i}` [slots, *rows] for the
    rows its mixer declares (`cache_feeds`; a "conv" layer's one,
    `conv_state_{i}` [slots, conv_taps - 1, d_model], the rows before
    each slot's token), and its fetches the same arrays a row on (a slot
    of length 0 keeps its rows). A layer whose cache is "none" or
    "shared" has no feed: a "gmu" layer gates by the scan output the
    step's "memory" layer handed on, a "cross" layer reads the pools of
    its `kv_source` as that layer left them this step.

    Returns (logits [slots, vocab], [the layer's pools after the step,
    a tuple, per layer], feed_names) — the pool fetches are the next
    step's pool feeds.
    """
    block = BlockSpec.of(block)
    token_ids = layers.data("token_ids", [slots], dtype="int64",
                            append_batch_size=False)
    context_lens = layers.data("context_lens", [slots], dtype="int32",
                               append_batch_size=False)
    block_tables = layers.data("block_tables", [slots, max_blocks_per_seq],
                               dtype="int32", append_batch_size=False)
    feed_names = ["token_ids", "context_lens", "block_tables"]
    tables = {"full": block_tables}
    blocks_of = {"full": pool_blocks, "window": window_pool_blocks}
    if block.window:
        tables["window"] = layers.data(
            "window_tables", [slots, max_blocks_per_seq], dtype="int32",
            append_batch_size=False)
        feed_names.append("window_tables")
    pools = []
    for i in range(n_layers):
        feeds = cache_feeds(block, i, n_heads, d_model, slots, block_size,
                            blocks_of, max_context)
        pools.append(tuple(
            layers.data(f"{stem}_{i}", shape, dtype="float32",
                        append_batch_size=False) for stem, shape in feeds))
        feed_names += [f"{stem}_{i}" for stem, _ in feeds]

    # [slots] ids -> [slots, d] rows -> [slots, 1, d]: the decode "batch"
    # is the slot axis, the sequence axis is the single new token
    x = layers.unsqueeze(_embedding(token_ids, vocab_size, d_model, block),
                         [1])
    one = layers.fill_constant([slots], "int32", 1.0)
    zero = layers.fill_constant([slots], "int32", 0.0)
    # the new token sits at position context_len-1; inactive slots (len
    # 0) clamp to row 0 — their rows only ever land in the null block
    pos_ids = layers.elementwise_max(
        layers.elementwise_sub(context_lens, one), zero)
    if block.positions == "learned":
        pos_tab = layers.create_parameter(
            [max_context, d_model], dtype="float32", name="pos_emb",
            default_initializer=NormalInitializer(scale=0.02))
        x = layers.elementwise_add(
            x, layers.unsqueeze(layers.gather(pos_tab, pos_ids), [1]))
    positions = (layers.unsqueeze(pos_ids, [1])    # [slots, 1]
                 if block.positions == "rope" else None)
    # a "linear" layer's own, where the block's layers carry none
    own_positions = positions if positions is not None else (
        layers.unsqueeze(pos_ids, [1])
        if block.linear_positions == "rope" else None)

    stats, routes, selected = [], [], []
    if block.ffn == "moe_gated":
        stats.append(layers.data(
            "moe_stats", [4 if block.experts_held else 3], dtype="int32",
            append_batch_size=False))
        feed_names.append("moe_stats")
    b = _Stream(block, n_heads, d_model, x, pools=pools, tables=tables,
                context_lens=context_lens, positions=positions,
                own_positions=own_positions, selected=selected)
    pool_outs = b.pool_outs
    for i in range(n_layers):
        b.ln1 = _norm(b.x, f"ln1_{i}", block)
        kind = block.layer(i)
        att, outs = _MIXERS[kind.mixer].build(b, i, kind)
        pool_outs.append(outs)
        b.x = _residual(b.x, att, b.ln1, lambda h: _ffn(
            h, d_model, d_ff, i, tp_shard=False, block=block,
            active=context_lens, stats_out=stats, routes_out=routes),
            i, block)

    x = b.x
    logits = layers.reshape(_head(x, vocab_size, block),
                            [slots, vocab_size])
    if stats and moe_stats_out is not None:
        moe_stats_out.append(layers.sums(stats))
    if routes and moe_routes_out is not None:
        moe_routes_out.append(layers.stack(
            [layers.squeeze(r, [1]) for r in routes], axis=0))
    if selected and selected_out is not None:
        selected_out.append(layers.stack(selected, axis=0))
    return logits, pool_outs, feed_names
