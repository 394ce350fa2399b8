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
    # -- what came with the layer pattern; `to_dict` leaves each out
    # while it is the default -------------------------------------------
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

    #: the fields that belong to attention="gqa": `to_dict` leaves them
    #: out elsewhere, so what the bundles of the other kinds record is
    #: what it was before there was this kind
    _GQA_FIELDS = ("head_dim", "n_kv_heads", "index_heads",
                   "index_head_dim", "index_topk")
    _PATTERN_FIELDS = ("parallel", "tied_head", "window", "layer_pattern",
                       "full_positions", "shared_scale", "experts_first",
                       "experts_held")
    #: and what came with the conv layers, left out the same way
    _CONV_FIELDS = ("conv_taps", "norm_topk_eps")
    #: and with the state-space layers and differential attention
    _HYBRID_FIELDS = ("differential", "attn_bias", "layer_ids",
                      "ssm_inner", "ssm_state", "ssm_dt_rank",
                      "dense_precision")
    #: and with the layers that are one part alone
    _SPLIT_FIELDS = ("ssm_heads", "ssm_groups", "ssm_chunk", "expert_form")
    #: and with linear attention and block-sparse attention
    _LONG_FIELDS = ("attn_gate", "sparse_kernel", "sparse_stride",
                    "sparse_block", "sparse_topk", "sparse_window",
                    "sparse_init", "sparse_dense_len", "linear_positions",
                    "decay_layers", "embed_scale", "residual_scale",
                    "logit_scale", "row_chunk")
    #: and with the layers that are a Mamba-2 mixer and an FFN
    _MIXED_FIELDS = ("attn_scale",)
    #: what a `layer_pattern` entry may be: window and full attention
    #: layers, "conv" (a gated short convolution), "mamba" (a selective
    #: scan; "memory": one that also hands its scan output on), "gmu" (a
    #: gated memory unit reading that output) and "cross" (attention
    #: with a query projection alone over the nearest earlier "full"
    #: layer's pool); and the layers that are ONE part under their norm
    #: and residual: "mamba2" (a Mamba-2 mixer), "attn" (full attention)
    #: and "ffn" (the block's feed-forward part, no mixer and no memory);
    #: "linear" (linear attention with a constant decay a head: a state)
    #: and "blocksparse" (attention over blocks chosen on pooled keys);
    #: "mamba2_ffn" (a Mamba-2 mixer, then the block's FFN under a second
    #: norm and residual: what "full" is to "attn")
    _KINDS = ("window", "full", "conv", "mamba", "memory", "gmu", "cross",
              "mamba2", "attn", "ffn", "linear", "blocksparse",
              "mamba2_ffn")

    def __post_init__(self):
        if self.norm not in ("layer_norm", "rms_norm", "layer_norm_gain"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.positions not in ("learned", "rope", "none"):
            raise ValueError(f"unknown positions {self.positions!r}")
        if self.ffn not in ("gelu", "gated", "moe_gated"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        if self.attention not in ("mha", "latent", "gqa"):
            raise ValueError(f"unknown attention {self.attention!r}")
        index = (self.index_heads, self.index_head_dim, self.index_topk)
        if self.attention == "gqa":
            if self.n_kv_heads < 1 or self.head_dim < 2 \
                    or self.head_dim % 2:
                raise ValueError("gqa needs n_kv_heads >= 1 and an even "
                                 f"head_dim, got {self.n_kv_heads} and "
                                 f"{self.head_dim}")
            if any(index) and (min(index) < 1 or self.index_head_dim % 2):
                raise ValueError(
                    "an indexer needs index_heads, index_topk >= 1 and "
                    f"an even index_head_dim, got {index}")
            if self.bias or self.positions == "learned" or (
                    self.positions == "none" and not self.differential
                    and not any(k in ("mamba2", "mamba2_ffn", "linear")
                                for k in self.layer_pattern)):
                raise ValueError("gqa is built with rotary positions and "
                                 "no bias (`attn_bias` for its own "
                                 "projections'); without positions where "
                                 "it is differential or beside 'mamba2', "
                                 "'mamba2_ffn' or 'linear' layers, which "
                                 "carry the order")
            if self.differential and (
                    self.positions != "none" or self.qk_norm or any(index)
                    or self.n_kv_heads % 2 or self.head_dim % 2):
                raise ValueError(
                    "differential attention is built without positions, "
                    "q/k-norm or an indexer, over an even number of K/V "
                    "heads")
        elif self.n_kv_heads or any(index) or self.differential \
                or self.attn_bias or self.positions == "none" \
                or self.attn_scale:
            raise ValueError("n_kv_heads, the indexer's widths, "
                             "differential, attn_bias, attn_scale and "
                             "positions='none' belong to attention='gqa'")
        elif self.attention == "latent" and self.head_dim:
            raise ValueError("a latent head's widths are the four latent "
                             "ones, not head_dim")
        if self.router not in ("softmax", "sigmoid_bias", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}")
        if self.dense_precision not in ("", "high"):
            raise ValueError("unknown dense_precision "
                             f"{self.dense_precision!r}")
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "layer_ids",
                           tuple(int(i) for i in self.layer_ids))
        pattern = self.layer_pattern
        windowed = "window" in pattern
        scans = any(k in ("mamba", "memory") for k in pattern)
        heads_scan = "mamba2" in pattern or "mamba2_ffn" in pattern
        conv = "conv" in pattern or scans or heads_scan
        if any(k not in self._KINDS for k in pattern) \
                or windowed != bool(self.window) or self.window < 0:
            raise ValueError(
                f"layer_pattern is a period of {self._KINDS}, "
                "and a window comes with a 'window' layer: "
                f"{pattern} and window {self.window}")
        if conv != (self.conv_taps >= 2) or self.conv_taps < 0 \
                or self.conv_taps == 1:
            raise ValueError(
                "conv_taps (>= 2) comes with a 'conv' or 'mamba' layer: "
                f"{pattern} and conv_taps {self.conv_taps}")
        if (windowed or pattern and set(pattern) != {"full"}
                or self.full_positions) and (
                self.attention != "gqa" or any(index)):
            raise ValueError("window layers, state layers, cross layers "
                             "and full_positions are built for "
                             "attention='gqa' without an indexer")
        ssm = (self.ssm_inner, self.ssm_state, self.ssm_dt_rank)
        ssd = (self.ssm_inner, self.ssm_state, self.ssm_heads,
               self.ssm_groups)
        if heads_scan:
            if scans or min(ssd) < 1 or self.ssm_dt_rank \
                    or self.ssm_chunk < 0 \
                    or self.ssm_inner % self.ssm_heads \
                    or self.ssm_heads % self.ssm_groups:
                raise ValueError(
                    "a 'mamba2' or 'mamba2_ffn' layer takes ssm_inner, "
                    "ssm_state, ssm_heads (dividing ssm_inner) and "
                    "ssm_groups (dividing ssm_heads), no ssm_dt_rank, and "
                    f"stands beside no 'mamba' layer: {pattern} and {ssd}")
            if "mamba2_ffn" in pattern and self.ffn == "moe_gated":
                raise ValueError(
                    "a 'mamba2_ffn' layer's feed-forward part is the "
                    "block's DENSE one ('gelu' | 'gated'); experts beside "
                    "a Mamba-2 mixer are layers of their own ('mamba2', "
                    "'ffn')")
        elif scans != all(v >= 1 for v in ssm) or (not scans and any(ssm)) \
                or self.ssm_heads or self.ssm_groups \
                or (self.ssm_chunk and "linear" not in pattern):
            raise ValueError(
                "ssm_inner, ssm_state and ssm_dt_rank (>= 1) come with a "
                "'mamba' layer, ssm_heads, ssm_groups and ssm_chunk with "
                "a 'mamba2' layer (ssm_chunk with a 'linear' one too): "
                f"{pattern} and {ssm}")
        if self.expert_form not in ("", "relu2") or (
                self.expert_form and self.ffn != "moe_gated"):
            raise ValueError("expert_form is '' or 'relu2' and belongs to "
                             f"ffn='moe_gated': {self.expert_form!r}")
        for at, kind in enumerate(pattern):
            if kind == "gmu" and "memory" not in pattern[:at]:
                raise ValueError("a 'gmu' layer gates by an earlier "
                                 f"'memory' layer's scan: {pattern}")
            if kind == "cross" and "full" not in pattern[:at]:
                raise ValueError("a 'cross' layer reads an earlier 'full' "
                                 f"layer's pool: {pattern}")
        sparse = (self.sparse_kernel, self.sparse_stride, self.sparse_block,
                  self.sparse_topk, self.sparse_window, self.sparse_init)
        if "blocksparse" in pattern:
            if min(sparse) < 1 or self.sparse_dense_len < 0 \
                    or self.sparse_kernel % self.sparse_stride \
                    or self.sparse_block % self.sparse_stride \
                    or self.sparse_window % self.sparse_block \
                    or self.sparse_init + self.sparse_window \
                    // self.sparse_block > self.sparse_topk:
                raise ValueError(
                    "a 'blocksparse' layer takes sparse_kernel and "
                    "sparse_block in whole sparse_strides, sparse_window "
                    "in whole blocks, and sparse_topk blocks that hold "
                    f"the first and the local ones: {sparse}")
        elif any(sparse) or self.sparse_dense_len:
            raise ValueError("the sparse_* sizes come with a 'blocksparse' "
                             f"layer: {pattern} and {sparse}")
        if "linear" in pattern:
            if self.decay_layers < 2 or self.linear_positions not in (
                    "", "rope"):
                raise ValueError(
                    "a 'linear' layer takes decay_layers >= 2 (the "
                    "published depth its decay is computed from) and "
                    f"linear_positions '' or 'rope': {self.decay_layers} "
                    f"and {self.linear_positions!r}")
        elif self.decay_layers or self.linear_positions:
            raise ValueError("decay_layers and linear_positions come with "
                             f"a 'linear' layer: {pattern}")
        if ("linear" in pattern or "blocksparse" in pattern) \
                and not self.qk_norm:
            raise ValueError("'linear' and 'blocksparse' layers are built "
                             "with per-head q/k-norm")
        if self.attn_gate and not ("linear" in pattern
                                   or "blocksparse" in pattern):
            raise ValueError("attn_gate belongs to 'linear' and "
                             "'blocksparse' layers")
        if min(self.embed_scale, self.residual_scale, self.logit_scale) <= 0 \
                or self.row_chunk < 0 or self.attn_scale < 0:
            raise ValueError("embed_scale, residual_scale and logit_scale "
                             "are positive, row_chunk and attn_scale are "
                             "not negative")
        if self.attn_scale and (self.differential or any(index)
                                or "blocksparse" in pattern
                                or "cross" in pattern):
            raise ValueError("attn_scale is built for plain grouped-query "
                             "attention (the flash forward, its query-row "
                             "chunks, the grouped paged kernel): not "
                             "differential, indexed or block-sparse")
        if self.norm_topk_eps < 0 or (self.norm_topk_eps
                                      and not self.norm_topk):
            raise ValueError("norm_topk_eps belongs to norm_topk")
        if self.full_positions not in ("", "none"):
            raise ValueError(f"unknown full_positions "
                             f"{self.full_positions!r}")
        held = (self.experts_first, self.experts_held)
        if self.ffn != "moe_gated" and (any(held)
                                        or self.shared_scale != 1.0):
            raise ValueError("experts_first, experts_held and shared_scale "
                             "belong to ffn='moe_gated'")
        if min(held) < 0 or sum(held) > self.num_experts \
                or (self.experts_first and not self.experts_held) \
                or self.experts_held == self.num_experts > 0:
            raise ValueError(f"held experts {held} outside the "
                             f"{self.num_experts} there are")
        if self.ffn == "moe_gated" and not (
                1 <= self.experts_per_tok <= self.num_experts):
            raise ValueError(
                f"moe_gated needs 1 <= experts_per_tok "
                f"({self.experts_per_tok}) <= num_experts "
                f"({self.num_experts})")
        latent = (self.kv_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if self.attention == "latent":
            if min(latent) < 1 or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs kv_lora_rank, "
                    "qk_nope_head_dim, v_head_dim >= 1 and an even "
                    f"qk_rope_head_dim, got {latent}")
            if self.positions != "rope" or self.qk_norm or self.bias:
                raise ValueError("latent attention is built with rotary "
                                 "positions, no q/k-norm and no bias")
        elif any(latent):
            raise ValueError("the latent widths belong to "
                             "attention='latent'")
        elif self.rope_interleave and self.attention != "gqa":
            raise ValueError("rope_interleave belongs to attention="
                             "'latent' or 'gqa'")
        if self.ffn != "moe_gated" and (
                self.router != "softmax" or self.norm_topk
                or self.routed_scale != 1.0 or self.shared_width
                or self.dense_layers or self.dense_width):
            raise ValueError("router, norm_topk, routed_scale, "
                             "shared_width and leading dense layers "
                             "belong to ffn='moe_gated'")
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
        out = dataclasses.asdict(self)
        if self.attention != "gqa" and not self.head_dim:
            for key in self._GQA_FIELDS:
                del out[key]
        for key in (self._PATTERN_FIELDS + self._CONV_FIELDS
                    + self._HYBRID_FIELDS + self._SPLIT_FIELDS
                    + self._LONG_FIELDS + self._MIXED_FIELDS):
            if out[key] == getattr(GPT2_BLOCK, key):
                del out[key]
            elif key in ("layer_pattern", "layer_ids"):
                out[key] = list(out[key])    # what JSON gives back
        return out

    def head_width(self, n_heads: int, d_model: int) -> int:
        return self.head_dim or d_model // n_heads

    def layer(self, i: int, d_ff: int = 0) -> LayerKind:
        """What layer `i` is; `d_ff` the model's FFN width (of one
        expert where there are experts)."""
        pattern = self.layer_pattern
        at = i % len(pattern) if pattern else 0
        kind = pattern[at] if pattern else "full"
        ffn, width = (("gated", self.dense_width) if i < self.dense_layers
                      else (self.ffn, d_ff))
        published = self.layer_ids[i] if self.layer_ids else i
        if kind == "conv":      # no attention: no window, no positions
            return LayerKind(0, "none", ffn, width, "state", "short_conv")
        if kind in ("mamba", "memory"):
            return LayerKind(0, "none", ffn, width, "state", "mamba",
                             memory="gives" if kind == "memory" else "",
                             published=published)
        if kind == "gmu":
            return LayerKind(0, "none", ffn, width, "none", "gmu",
                             memory="takes", published=published)
        if kind == "mamba2":    # the mixer alone
            return LayerKind(0, "none", "none", 0, "state", "mamba2",
                             published=published)
        if kind == "mamba2_ffn":    # the mixer, then the block's FFN
            return LayerKind(0, "none", ffn, width, "state", "mamba2",
                             published=published)
        if kind == "linear":    # a state, and positions of its own
            return LayerKind(0, self.linear_positions or self.positions,
                             ffn, width, "state", "linear",
                             published=published)
        if kind == "blocksparse":   # blocks that grow with the sequence
            return LayerKind(0, self.positions, ffn, width, "full",
                             "blocksparse", published=published)
        if kind == "ffn":       # the feed-forward part alone: no memory
            return LayerKind(0, "none", ffn, width, "none", "none",
                             published=published)
        if kind == "attn":      # full attention alone
            return LayerKind(0, self.full_positions or self.positions,
                             "none", 0, "full", published=published)
        if kind == "cross":     # the nearest earlier full layer's pool
            source = i - at + max(j for j in range(at)
                                  if pattern[j] == "full")
            return LayerKind(0, "none", ffn, width, "shared",
                             kv_source=source, published=published)
        window = self.window if kind == "window" else 0
        positions = (self.full_positions or self.positions) \
            if kind == "full" else self.positions
        return LayerKind(window, positions, ffn, width, kind,
                         published=published)

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

    def cache_pools(self, n_heads: int, d_model: int,
                    layer: int = None, max_context: int = 0) -> dict:
        """What a paged cache holds of a token in ONE layer: the
        declaration `export_decode_model` records under `decode.cache`
        and the engine allocates from. `pools`: (feed stem, shape of a
        token's row) per pool of a layer; `row_floats`: the floats of
        them that carry the token (a latent row is stored in whole
        lane tiles of 128: the columns past `row_floats` are zeros).
        `layer`: the layer asked about (None: an attention layer). A
        "conv" layer has no pool: it declares `state`, (feed stem, shape
        of a SEQUENCE's rows), which is all it remembers of a sequence
        however long. K/V heads narrower than a lane tile are stored
        several to a tile (`packed_kv_row`). A "linear" layer declares
        `state`, a matrix a head; a "blocksparse" layer pools whose row
        is the K/V heads side by side in the lanes (a block of one head
        is then one copy at whole lane tiles) AND, with `max_context`, a
        `state`: the sequence's pooled keys."""
        kind = self.layer(layer) if layer is not None else None
        if kind is not None and kind.cache in ("shared", "none"):
            return {"kind": kind.cache, "row_floats": 0, "pools": []}
        if kind is not None and kind.mixer == "linear":
            width = self.head_width(n_heads, d_model)
            return {"kind": "state", "row_floats": 0, "pools": [],
                    "state": [("ssm_state", [n_heads, width, width])]}
        if "blocksparse" in self.layer_pattern and (
                kind is None or kind.mixer == "blocksparse"):
            row = [self.n_kv_heads * self.head_width(n_heads, d_model)]
            out = {"kind": "kv_blocks", "row_floats": 2 * row[0],
                   "pools": [("k_cache", row), ("v_cache", row)]}
            if kind is not None and max_context:
                out["state"] = [("pooled_keys",
                                 [self.pooled_rows(max_context)] + row)]
            return out
        if kind is not None and kind.mixer == "mamba2":
            # a matrix a HEAD, the state's columns on the lanes, and the
            # convolution's rows of x, B and C before the token
            return {"kind": "state", "row_floats": 0, "pools": [],
                    "state": [("ssm_state",
                               [self.ssm_heads,
                                self.ssm_inner // self.ssm_heads,
                                self.ssm_state]),
                              ("conv_state",
                               [self.conv_taps - 1, self.ssm_inner
                                + 2 * self.ssm_groups * self.ssm_state])]}
        if kind is not None and kind.mixer == "mamba":
            # the scan's state with the channels on the lanes ([d_state,
            # d_inner]: a last dimension of 16 would be padded to 128 in
            # the device's memory, eight times its bytes) and the
            # convolution's rows before the token
            return {"kind": "state", "row_floats": 0, "pools": [],
                    "state": [("ssm_state",
                               [self.ssm_state, self.ssm_inner]),
                              ("conv_state",
                               [self.conv_taps - 1, self.ssm_inner])]}
        if kind is not None and kind.cache == "state":
            return {"kind": "state", "row_floats": 0, "pools": [],
                    "state": [("conv_state",
                               [self.conv_taps - 1, d_model])]}
        if self.attention == "latent":
            used = self.kv_lora_rank + self.qk_rope_head_dim
            return {"kind": "latent", "row_floats": used,
                    "pools": [("latent_cache", [-(-used // 128) * 128])]}
        width = self.head_width(n_heads, d_model)
        if self.differential:
            # K head g of the first set beside K head g of the second,
            # and V the same (what one differential head pair reads),
            # the pairs side by side in the row's lanes
            row = [self.n_kv_heads * width]
            return {"kind": "kv_diff",
                    "row_floats": 2 * self.n_kv_heads * width,
                    "pools": [("k_cache", row), ("v_cache", row)]}
        if self.attention == "gqa":
            row = packed_kv_row(self.n_kv_heads, width) \
                if not self.index_topk else [self.n_kv_heads, width]
            pools = [("k_cache", row), ("v_cache", row)]
            used = 2 * self.n_kv_heads * width
            if self.index_topk:      # the index key, in whole lane tiles
                used += self.index_head_dim
                pools.append(("index_cache",
                              [-(-self.index_head_dim // 128) * 128]))
            return {"kind": "kv_index" if self.index_topk else "kv",
                    "row_floats": used, "pools": pools}
        row = [n_heads, width]
        return {"kind": "kv", "row_floats": 2 * n_heads * width,
                "pools": [("k_cache", row), ("v_cache", row)]}


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


GPT2_BLOCK = BlockSpec()


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


def _ffn(x, d_model, d_ff, idx, tp_shard, block=GPT2_BLOCK, active=None,
         stats_out=None, routes_out=None):
    """Layer `idx`'s FFN on [B, S, d_model]. With experts, `active` marks
    the live rows for the routing counters; each layer appends its
    counters var to `stats_out` (the decode step's business only) and
    its chosen experts [B, S, top_k] to `routes_out`."""
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
            form=block.expert_form)
        if stats_out is not None:
            stats_out.append(stats)
        if routes_out is not None:
            routes_out.append(experts)
        return out
    mixed = "mamba2_ffn" in block.layer_pattern
    if kind == "gated" and (mixed or block.row_chunk
                            and int(x.shape[1]) > block.row_chunk):
        # ONE op, the same weights by the same names: a long bucket's
        # rows a chunk at a time; and in a model with "mamba2_ffn" layers
        # under a scope of its own, so that a device trace tells a
        # layer's FFN ("gated_ffn") from its mixer ("mamba2")
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


def _grouped_args(block, n_heads, kind):
    rotary = ("none" if kind.positions == "none" else
              "interleave" if block.rope_interleave else "half")
    return dict(num_heads=n_heads, num_kv_heads=block.n_kv_heads,
                head_dim=block.head_dim, rope_theta=block.rope_theta,
                qk_norm=block.qk_norm, index_heads=block.index_heads,
                index_head_dim=block.index_head_dim,
                index_topk=block.index_topk, epsilon=block.norm_eps,
                window=kind.window, rotary=rotary, scale=block.attn_scale)


def _scan_args(block):
    return dict(d_inner=block.ssm_inner, d_state=block.ssm_state,
                dt_rank=block.ssm_dt_rank, taps=block.conv_taps)


def _ssd_args(block):
    return dict(d_inner=block.ssm_inner, d_state=block.ssm_state,
                heads=block.ssm_heads, groups=block.ssm_groups,
                taps=block.conv_taps, chunk=block.ssm_chunk or 128,
                epsilon=block.norm_eps)


def _linear_args(block, n_heads, d_model, kind):
    return dict(heads=n_heads, head_dim=block.head_width(n_heads, d_model),
                layer=kind.published, n_layers=block.decay_layers,
                rope_theta=block.rope_theta,
                rotary="half" if kind.positions == "rope" else "none",
                chunk=block.ssm_chunk or 128, epsilon=block.norm_eps,
                gate=block.attn_gate)


def _sparse_args(block, n_heads):
    return dict(num_heads=n_heads, num_kv_heads=block.n_kv_heads,
                head_dim=block.head_dim, sizes=block.sparse_sizes,
                epsilon=block.norm_eps, gate=block.attn_gate)


def _diff_args(block, n_heads, i):
    return dict(num_heads=n_heads, num_kv_heads=block.n_kv_heads,
                head_dim=block.head_dim, lambda_init=block.lambda_init(i),
                epsilon=block.norm_eps, window=block.layer(i).window)


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


def _latent_args(block, n_heads):
    return dict(num_heads=n_heads, kv_lora_rank=block.kv_lora_rank,
                qk_nope_head_dim=block.qk_nope_head_dim,
                qk_rope_head_dim=block.qk_rope_head_dim,
                v_head_dim=block.v_head_dim, rope_theta=block.rope_theta,
                rope_interleave=block.rope_interleave,
                epsilon=block.norm_eps)


def transformer_lm(src_ids, vocab_size, n_layers=2, d_model=128, n_heads=4,
                   d_ff=512, max_len=2048, dropout_rate=0.0,
                   causal=True, sp_mode="none", tp_shard=False,
                   remat=False, pos_table_len=None, collect_kv=None,
                   collect_routes=None, block=None, head_rows=None,
                   collect_selected=None, n_tokens=None):
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
    a "conv" layer appends the state a sequence of that length leaves
    ([B, conv_taps - 1, d_model]: the rows before position n_tokens, not
    before the padded bucket's end) in its K/V's place; a "mamba" layer
    its scan's state after row n_tokens - 1 and its convolution's rows,
    a "mamba2" layer the same two (a matrix a head).

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

    memory = None       # a "memory" layer's scan output, before its gate
    shared = {}         # a differential layer's (K, V), for "cross" layers
    narrowed = False    # only the head rows go on (the text above)
    crossed = "cross" in block.layer_pattern
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
            ln1 = _norm(x, f"ln1_{i}", block)
            if kind.mixer == "short_conv":
                att = layers.short_conv(
                    ln1, taps=block.conv_taps, name=f"conv{i}",
                    n_tokens=n_tokens, state_out=collect_kv)
            elif kind.mixer == "mamba":
                handed = [] if kind.memory == "gives" else None
                att = layers.selective_scan(
                    ln1, name=f"mamba{i}", n_tokens=n_tokens,
                    state_out=collect_kv, memory_out=handed,
                    **_scan_args(block))
                if handed:
                    memory = handed[0]
            elif kind.mixer == "gmu":
                att = _gmu(ln1, memory, i, d_model, block)
            elif kind.mixer == "mamba2":
                att = layers.mamba2_mixer(
                    ln1, name=f"mamba{i}", n_tokens=n_tokens,
                    state_out=collect_kv, **_ssd_args(block))
            elif kind.mixer == "linear":
                att = layers.linear_attention(
                    ln1, name=f"attn{i}", n_tokens=n_tokens,
                    state_out=collect_kv,
                    **_linear_args(block, n_heads, d_model, kind))
            elif kind.mixer == "blocksparse":
                att = layers.block_sparse_attention(
                    ln1, name=f"attn{i}", n_tokens=n_tokens,
                    max_pooled=0 if collect_kv is None
                    else block.pooled_rows(max_len),
                    cache_out=collect_kv, selected_out=collect_selected,
                    **_sparse_args(block, n_heads))
            elif kind.mixer == "none":
                att = None
            elif block.differential and kind.cache == "shared":
                att = layers.diff_attention(
                    ln1, name=f"attn{i}", kv=shared[kind.kv_source],
                    q_rows=head_rows if narrowed else None,
                    **_diff_args(block, n_heads, i))
            elif block.differential:
                rows = []
                whole = None
                if crossed and head_rows is not None \
                        and kind.cache == "full":
                    # from this layer's query on, the head rows alone
                    whole, narrowed = ln1, True
                    x = layers.batch_gather(x, head_rows)
                    ln1 = layers.batch_gather(ln1, head_rows)
                    if memory is not None:
                        memory = layers.batch_gather(memory, head_rows)
                att = layers.diff_attention(
                    ln1, name=f"attn{i}", cache_out=rows, kv_from=whole,
                    q_rows=head_rows if whole is not None else None,
                    **_diff_args(block, n_heads, i))
                shared[i] = rows[0]
                if collect_kv is not None:
                    collect_kv.append(rows[0])
            elif block.attention == "latent":
                rows = [] if collect_kv is not None else None
                att = layers.latent_attention(
                    ln1, name=f"attn{i}", latent_out=rows,
                    **_latent_args(block, n_heads))
                if rows:
                    collect_kv.append(tuple(rows))
            elif block.attention == "gqa":
                att = layers.grouped_attention(
                    ln1, name=f"attn{i}", cache_out=collect_kv,
                    selected_out=collect_selected,
                    **_grouped_args(block, n_heads, block.layer(i)))
            else:
                att = layers.multi_head_attention(
                    ln1, num_heads=n_heads, causal=causal, sp_mode=sp_mode,
                    d_key=block.head_dim or None,
                    dropout_rate=dropout_rate, tp_shard=tp_shard,
                    kv_out=collect_kv, name=f"attn{i}",
                    bias_attr=None if block.bias else False,
                    qk_norm_eps=block.norm_eps if block.qk_norm else None,
                    rope_theta=(block.rope_theta
                                if block.positions == "rope" else None))
            x = _residual(x, att, ln1, lambda h: _ffn(
                h, d_model, d_ff, i, tp_shard, block,
                routes_out=collect_routes), i, block)

    if head_rows is not None and not narrowed:
        x = layers.batch_gather(x, head_rows)
    return _head(x, vocab_size, block)


def transformer_lm_loss(vocab_size=1000, seq_len=128, **kw):
    """Build data vars + LM loss. Returns (avg_cost, logits)."""
    if BlockSpec.of(kw.get("block")).window:
        raise NotImplementedError(
            "a window layer is served, not trained: the flash kernels' "
            "backward (dq, dk/dv) has no window band in its block plan; "
            "train the block with layer_pattern=() (every layer full)")
    # a "conv" layer trains as it is: every operation of
    # `layers.short_conv` is differentiable (tests/test_lfm2.py holds its
    # gradients to jax.grad of the plain reference)
    if BlockSpec.of(kw.get("block")).index_topk:
        raise NotImplementedError(
            "a sparse-attention indexer is served, not trained: its "
            "training loss (a KL of the indexer's scores against the "
            "dense attention's distribution) is not built; train the "
            "block with index_topk=0 (plain grouped-query attention)")
    if any(k in ("linear", "blocksparse")
           for k in BlockSpec.of(kw.get("block")).layer_pattern):
        raise NotImplementedError(
            "'linear' and 'blocksparse' layers are served, not trained: "
            "the chunked recurrence's backward is not held to the "
            "reference's gradients and the selection has no training "
            "form; neither decode kernel has a backward")
    if any(k in ("mamba2", "attn", "ffn", "mamba2_ffn")
           for k in BlockSpec.of(kw.get("block")).layer_pattern):
        raise NotImplementedError(
            "layers that are a mixer or a feed-forward part alone "
            "('mamba2', 'attn', 'ffn') and a Mamba-2 mixer with an FFN "
            "('mamba2_ffn') are served, not trained: the chunked scan's "
            "backward is not held to the reference's gradients, and the "
            "decode kernel has none")
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
                      block_tables, context_lens, block=GPT2_BLOCK,
                      positions=None):
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
    window layer's pools hold `window_pool_blocks` blocks. A "conv"
    layer has no pool: its one feed is `conv_state_{i}` [slots,
    conv_taps - 1, d_model], the rows before each slot's token, and its
    fetch the same array a row on (a slot of length 0 keeps its rows);
    a "mamba" layer's two are `ssm_state_{i}` [slots, ssm_state,
    ssm_inner] and `conv_state_{i}` [slots, conv_taps - 1, ssm_inner];
    a "mamba2" layer's `ssm_state_{i}` [slots, ssm_heads, ssm_inner /
    ssm_heads, ssm_state] and `conv_state_{i}` [slots, conv_taps - 1,
    ssm_inner + 2 ssm_groups ssm_state]; an "ffn" layer has none.
    A "gmu" layer and a "cross" layer have no feed: the one gates by the
    scan output the step's "memory" layer handed on, the other reads the
    pools of its `kv_source` as that layer left them this step.

    Returns (logits [slots, vocab], [the layer's pools after the step,
    a tuple, per layer], feed_names) — the pool fetches are the next
    step's pool feeds.
    """
    block = BlockSpec.of(block)
    d_key = block.head_width(n_heads, d_model)
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
    pool_outs = []
    memory = None
    for i in range(n_layers):
        ln1 = _norm(x, f"ln1_{i}", block)
        kind = block.layer(i)
        if kind.mixer == "short_conv":
            att, state_out = layers.short_conv(
                ln1, taps=block.conv_taps, name=f"conv{i}",
                state=pools[i][0], context_lens=context_lens)
            pool_outs.append((state_out,))
        elif kind.mixer == "mamba":
            handed = [] if kind.memory == "gives" else None
            att, states = layers.selective_scan(
                ln1, name=f"mamba{i}", state=pools[i],
                context_lens=context_lens, memory_out=handed,
                **_scan_args(block))
            if handed:
                memory = handed[0]
            pool_outs.append(states)
        elif kind.mixer == "gmu":
            att = _gmu(ln1, memory, i, d_model, block)
            pool_outs.append(())
        elif kind.mixer == "mamba2":
            att, states = layers.mamba2_mixer(
                ln1, name=f"mamba{i}", state=pools[i],
                context_lens=context_lens, **_ssd_args(block))
            pool_outs.append(states)
        elif kind.mixer == "linear":
            att, states = layers.linear_attention(
                ln1, name=f"attn{i}", state=pools[i],
                context_lens=context_lens, positions=own_positions,
                **_linear_args(block, n_heads, d_model, kind))
            pool_outs.append(states)
        elif kind.mixer == "blocksparse":
            att, outs = layers.block_sparse_attention(
                ln1, name=f"attn{i}", pools=pools[i],
                block_tables=block_tables, context_lens=context_lens,
                selected_out=selected, **_sparse_args(block, n_heads))
            pool_outs.append(outs)
        elif kind.mixer == "none":
            att = None
            pool_outs.append(())
        elif block.differential:
            # a cross layer reads its source's pools as this step left
            # them, through the full layers' table, and writes nothing
            cross = kind.cache == "shared"
            att, outs = layers.diff_attention(
                ln1, name=f"attn{i}", kv=True if cross else None,
                pools=pool_outs[kind.kv_source] if cross else pools[i],
                block_tables=tables["window" if kind.cache == "window"
                                    else "full"],
                context_lens=context_lens, **_diff_args(block, n_heads, i))
            pool_outs.append(outs)
        elif block.attention == "latent":
            att, row_out = layers.latent_attention(
                ln1, name=f"attn{i}", pool=pools[i][0],
                block_tables=block_tables, context_lens=context_lens,
                positions=positions, **_latent_args(block, n_heads))
            pool_outs.append((row_out,))
        elif block.attention == "gqa":
            kind = block.layer(i)
            att, outs = layers.grouped_attention(
                ln1, name=f"attn{i}", pools=pools[i],
                block_tables=tables[kind.cache], context_lens=context_lens,
                positions=positions, selected_out=selected,
                **_grouped_args(block, n_heads, kind))
            pool_outs.append(outs)
        else:
            att, k_out, v_out = _decode_attention(
                ln1, i, n_heads, d_key, d_model, pools[i][0], pools[i][1],
                block_tables, context_lens, block, positions)
            pool_outs.append((k_out, v_out))
        x = _residual(x, att, ln1, lambda h: _ffn(
            h, d_model, d_ff, i, tp_shard=False, block=block,
            active=context_lens, stats_out=stats, routes_out=routes),
            i, block)

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
