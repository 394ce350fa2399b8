"""DecodeEngine: the generation facade over one exported decode bundle.

DecodeModel owns the device side: the deserialized prefill buckets, the
single decode-step executable, and the device-resident cache pools (what
they hold of a token is the bundle's to declare, `decode.cache` of
serving.json: per-head K and V, one latent row a layer, or K and V of
the heads that groups share with an index key beside them, or a
differential layer's paired heads side by side; a pool may have readers
that are not its writer, `decode.cache.shared`: layers with no pool of
their own that read it through the same table; and, for a layer that
mixes by a short convolution or a selective scan, no pool at all but a
STATE: a few rows a slot (a scan's: a matrix and a few rows), `decode.cache.kinds.state`, which rides with the pools
through every call, donated and updated in place like them, and which
an admission writes at the sequence's slot where it writes a pool at
the sequence's blocks). The
exported artifacts are the interchange format; the engine jits its own
calls over them, and every call that writes the pools takes them
donated, so each pool is one buffer that is updated in place and never
copied. An admission never leaves the device: per length
bucket, one jitted function wraps the bucket's artifact and returns the
last position's logits row (the artifact computes the head for that row
alone) and every layer's cache rows as device arrays, and
one jitted function with the pools donated scatters those rows into the
sequence's blocks in place. Only the padded ids, the block-id vector
and one logits row cross between host and device memory
(`DecodeMetrics.prefill_host_bytes` counts them). The step is one jitted
function over the step artifact (`jit_step`): the pools it returns are
the buffers it was given with one row a slot written, and it chooses
every slot's next token itself, so a step hands the host 4 bytes a slot
(`StepResult.tokens`) and its logits stay on the device until somebody
asks the result for them (`DecodeMetrics.step_host_bytes` counts what
moved, `logits_fetches` how often they were asked for). `decode_step`
returns when the step is DISPATCHED: the wait and the 4 bytes a slot
happen when the result's `.tokens` are first read, so a caller may
dispatch the next step first, naming `PREVIOUS_TOKEN` in a slot whose
input is the id this step chose (resolved on the device from the ids it
left there; the scheduler's dispatch ahead). A caller that reads the
result at once runs in lockstep, as every caller did. The model
also keeps account of the time it has nothing in flight on the device
(`_launched`, `_waited`: phase `device_idle`, docs/observability.md). The
prefill's bucket table (bounds, feed dtypes, request validation) is the
PR-5 ModelVersion's, loaded without its own warm-up. DecodeScheduler
owns the host side: slots, block accounting, admission, eviction.
DecodeEngine wires them and is what ServingEngine.load_decode_model
constructs.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from ..admission import AdmissionController, InvalidRequest, Overloaded
from ..batcher import env_float, env_int
from ..metrics import DecodeMetrics, DecodePhaseTimer
from ..registry import ModelVersion
from .kv_cache import KVBlockPool, blocks_for_tokens
from .prefix import PrefixIndex
from .scheduler import PREVIOUS_TOKEN, DecodeScheduler, GenerationHandle
from .spec import resolve_drafter

__all__ = ["DecodeModel", "DecodeEngine", "PrefillKV", "PrefillRow",
           "SequenceStateUnsupported", "StepResult",
           "WindowCacheUnsupported", "jit_step"]

#: how a pool is addressed (`DecodeModel._pool_table`): through the full
#: layers' table, the window layers', or, a state, by the slot
_FULL, _WINDOW, _STATE = 0, 1, 2


def jit_step(call, takes_weights: bool, n_pools: int):
    """The decode step as the engine runs it: one jitted function over
    the step artifact's `call`, with the pools donated and nothing else,
    so XLA writes a step's rows into the buffers it was given instead
    of into copies. The weights are an argument (the bundle's one device
    copy; {} where the artifact inlines them), never constants of the
    executable. `prev` is the ids the step before returned (int32
    [slots]): a slot whose token is `PREVIOUS_TOKEN` takes its input
    from there, so the host need not have seen it. Returns (every
    slot's greedy token, int32 [slots]: the
    first of equal maxima, as np.argmax takes it; the logits they were
    chosen from; the pools in the order they came; whatever the artifact
    returns behind them): donated buffers pair with outputs of their
    shape in that order."""
    import jax
    import jax.numpy as jnp

    def step(weights, tokens, lens, tables, pools, prev, *behind):
        tokens = jnp.where(tokens == PREVIOUS_TOKEN,
                           prev.astype(tokens.dtype), tokens)
        # one table, or one a kind of cache (full, window)
        tables = tables if isinstance(tables, tuple) else (tables,)
        feeds = (tokens, lens, *tables, *pools, *behind)
        outs = ModelVersion._normalize(
            call(weights, *feeds) if takes_weights else call(*feeds))
        ids = jnp.argmax(outs[0], axis=-1).astype(jnp.int32)
        return ids, outs[0], outs[1:1 + n_pools], outs[1 + n_pools:]

    step.__name__ = "decode_step"    # the executable's name in a profile
    return jax.jit(step, donate_argnums=4)


class PrefillKV(NamedTuple):
    """What `DecodeModel.prefill` hands to `seed_sequence`, opaque to
    everyone between them: the cache rows the bucket's artifact
    returned, still on the device at bucket length, and what is needed
    to place them."""

    arrays: tuple    #: one per pool, in the pools' order: (k_0, v_0,
    #: k_1, ...) each [batch, bound, H, D], or (latent_0, ...) each
    #: [batch, bound, rank + rope]
    n: int           #: true length: rows at or past it are padding
    bound: int       #: the bucket, which names the seeding executable


class PrefillRow:
    """The logits row `DecodeModel.prefill` returns, where it was
    computed (`.row`, device f32 [vocab]). Anything `np.asarray` takes:
    asking for it is the admission's one wait, and `on_wait` is told
    once, when that wait has returned, so that the model knows which of
    its dispatches the caller waited for."""

    __slots__ = ("row", "_on_wait", "_host")

    def __init__(self, row, on_wait: Callable[[], None]):
        self.row = row
        self._on_wait = on_wait
        self._host: Optional[np.ndarray] = None

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            self._host = np.asarray(self.row)
            self._on_wait()
        return self._host if dtype is None else self._host.astype(dtype)


class StepResult:
    """What `DecodeModel.decode_step` returns: a step that has been
    dispatched. `.tokens` are its greedy tokens on the host (int32
    [slots]); reading them first is the step's wait and its fetch (the
    phases `step_wait` and `step_fetch` are recorded there, `on_wait` is
    told when the wait has returned, `on_fetch` the bytes). Its logits
    stay where they were computed: anything `np.asarray` takes (and
    indexable like it), asking for the logits is what fetches them,
    once, behind the tokens, and `on_fetch` is told the bytes and that
    they were the logits."""

    __slots__ = ("ids", "logits", "_timer", "_on_wait", "_on_fetch",
                 "_tokens", "_host")

    def __init__(self, ids, logits, timer: DecodePhaseTimer,
                 on_wait: Callable[[], None],
                 on_fetch: Callable[[int, bool], None]):
        self.ids = ids           #: device int32 [slots]
        self.logits = logits     #: device f32 [slots, vocab]
        self._timer = timer
        self._on_wait = on_wait
        self._on_fetch = on_fetch
        self._tokens: Optional[np.ndarray] = None
        self._host: Optional[np.ndarray] = None

    @property
    def tokens(self) -> np.ndarray:
        if self._tokens is None:
            with self._timer.span("step_wait"):
                # the fetch below synchronises anyway; waiting here
                # first splits the device's time from the copy's
                self.ids.block_until_ready()
            self._on_wait()
            with self._timer.span("step_fetch"):
                self._tokens = np.asarray(self.ids)
            self._on_fetch(self._tokens.nbytes, False)
        return self._tokens

    def __array__(self, dtype=None, copy=None):
        if self._host is None:
            self.tokens          # the step's wait, recorded as one
            self._host = np.asarray(self.logits)
            self._on_fetch(self._host.nbytes, True)
        return self._host if dtype is None else self._host.astype(dtype)

    def __getitem__(self, index):
        return np.asarray(self)[index]


class _BucketCalls(NamedTuple):
    """One length bucket's admission path, built once at load."""

    prefill: Callable    #: jitted (weights, ids, n) -> (logits row, cache
    #: rows, chosen experts or None, selected positions or None)
    weights: Dict        #: the artifact's weights, passed as arguments
    seed: Callable       #: jitted, pools donated: (pools, rows, ids, n)
    ids_shape: tuple     #: the prefill feed, [batch, bound]
    ids_dtype: np.dtype


class WindowCacheUnsupported(ValueError):
    """What a bundle with window layers cannot do yet: a window layer's
    block is released once its rows fall behind the window, so it cannot
    back a shared prefix, and a draft chain's slots would each need a
    window table of their own."""


class SequenceStateUnsupported(ValueError):
    """What a bundle with state layers (gated short convolutions) cannot
    do yet: a state holds the rows before a sequence's NEWEST token
    alone, so a shared prefix has none at the point where it is shared,
    and a rejected draft would have to roll it back."""


class DecodeModel:
    """One loaded decode bundle (io.export_decode_model artifact dir)."""

    def __init__(self, model_dir: str, *, warmup: bool = True):
        import jax
        import jax.numpy as jnp
        from ...core.compat import jax_export

        with open(os.path.join(model_dir, "serving.json")) as f:
            meta = json.load(f)
        dec = meta.get("decode")
        if not dec:
            raise ValueError(
                f"{model_dir} has no decode section in serving.json — "
                "export with io.export_decode_model, not "
                "export_serving_model")
        self.model_dir = model_dir
        # the bucket table only: the engine compiles its own jitted
        # calls over the artifacts, so the bare ones are never warmed
        self.prefill_model = ModelVersion.load(model_dir, version=1,
                                               warmup=False)
        with open(os.path.join(model_dir, dec["file"]), "rb") as f:
            call = jax_export().deserialize(bytearray(f.read())).call
        # the step shares the prefill buckets' device weights
        names = dec.get("weights")
        self._step_weights = self._named_weights(names)
        #: bytes the bundle's weights hold on the device, and the dtype
        #: its matrices are stored and served in (`io.export_decode_model`
        #: `weight_dtype`; a bundle from before the record: float32)
        self.weight_bytes = sum(
            int(w.size) * w.dtype.itemsize for w in self.weights.values())
        self.weight_dtype = str(
            meta.get("weights", {}).get("dtype", "float32"))
        #: the pools the bundle declares: kind, a layer's row shapes,
        #: the floats of them that carry a token, bytes a token as stored
        self.cache = dec["cache"]
        #: a bundle with window layers: the rows such a layer reads back
        #: (0: none), every layer's kind of cache, and the window kind's
        #: pool (blocks, and the most a slot holds of them at once); the
        #: step then takes two tables, the full layers' and theirs
        self.window = int(self.cache.get("window", 0))
        kinds = self.cache.get("kinds", {}).get("window", {})
        self.window_pool_blocks = int(kinds.get("pool_blocks", 0))
        self.window_blocks_per_seq = int(kinds.get("blocks_per_seq", 0))
        self.window_layers = int(kinds.get("layers", 0))
        n_tables = 2 if self.window else 1
        #: a bundle with state layers: what such a layer keeps of a
        #: sequence ([rows, width] a slot), and how many there are
        state = self.cache.get("kinds", {}).get("state", {})
        self.state_layers = int(state.get("layers", 0))
        #: how each pool is addressed, in the step's feed order: a table
        #: (`_FULL`, `_WINDOW`) or, a state layer's array, the slot
        layer_kinds = self.cache.get("layer_kinds",
                                     ["full"] * int(dec["n_layers"]))
        #: a bundle whose full layers keep, beside their pools, rows a
        #: SEQUENCE (the pooled keys a block selection is scored on):
        #: arrays by the slot behind each such layer's pools
        pooled = [_STATE] * len(self.cache.get("pooled", {})
                                .get("rows", ()))
        self._pool_table = [
            tag
            for kind in layer_kinds
            for tag in ([_STATE] * len(state.get("rows", ()))
                        if kind == "state" else
                        # a layer that reads another's pool, or keeps
                        # nothing of a token, has none
                        [] if kind in ("shared", "none") else
                        [int(kind == "window")] * len(self.cache["rows"])
                        + (pooled if kind == "full" else []))]
        #: whether an admission writes anything at the sequence's slot
        self.slot_rows = _STATE in self._pool_table
        #: a bundle with layers that read a pool they do not own: how
        #: many such readers there are, and the layers that write a
        #: growing pool (the full layers)
        self.pool_readers = len(self.cache.get("shared", {})
                                .get("readers", ()))
        self.full_layers = layer_kinds.count("full")
        n_pools = len(self._pool_table)
        self._step_fn = jit_step(call, names is not None, n_pools)
        self._step = None    # its one executable: built at the first step
        #: the ids the newest dispatched step chose, on the device: what
        #: a `PREVIOUS_TOKEN` slot of the next step reads
        self._last_ids = jax.device_put(
            jnp.zeros((int(dec["slots"]),), jnp.int32),
            jax.local_devices()[0])
        #: bytes of the compiled step's arguments that it returns in
        #: place: the pools' bytes while the donation holds, 0 if XLA
        #: answered it with copies; None before the step is compiled
        self.step_aliased_bytes: Optional[int] = None
        self.slots = int(dec["slots"])
        self.block_size = int(dec["block_size"])
        self.pool_blocks = int(dec["pool_blocks"])
        self.max_blocks_per_seq = int(dec["max_blocks_per_seq"])
        self.max_context = int(dec["max_context"])
        self.n_layers = int(dec["n_layers"])
        self.vocab_size = int(dec["vocab_size"])
        self.eos_id = dec.get("eos_id")
        self.max_prompt_len = self.prefill_model.bounds[-1]
        #: the form each bucket's attention took when it was exported
        #: (`kernels.flash_attention.attention_form`); None: a bundle
        #: from before the record
        forms = dec.get("prefill_attention")
        self.prefill_attention = forms and {
            int(bound): form for bound, form in forms.items()}
        self._feed_meta = dec["feeds"]
        roles = dec["prefill_roles"]
        self._logits_role = roles["logits"]
        self._kv_roles = [tuple(p) for p in roles["kv"]]
        self._pool_dtype = jnp.float32
        #: every pool's shape, in the step's feed order
        self._pool_shapes = [
            tuple(m["shape"])
            for m in self._feed_meta[2 + n_tables:2 + n_tables + n_pools]]
        #: bytes the state layers' arrays hold, all slots (0: none)
        self.state_bytes = sum(
            4 * int(np.prod(shape)) for shape, tag in
            zip(self._pool_shapes, self._pool_table) if tag == _STATE)
        from ...kernels.paged_attention import PagedPlan, paged_decode_plan
        #: what the step's paged attention runs at this bundle's shapes,
        #: the plan its kernels' wrappers run by
        #: (`kernels.paged_attention.paged_decode_plan`; of a bundle whose
        #: attention reads chosen blocks, `kernels.block_sparse_attention
        #: .block_sparse_plan`, `block_sparse_kernel` below)
        sel = dec.get("selections")
        #: a bundle whose attention reads whole blocks chosen on pooled
        #: keys: the selection's sizes (None for any other)
        self.block_sparse = (sel or {}).get("blocks")
        self.block_sparse_kernel = None
        if self.block_sparse:
            from ...kernels.block_sparse_attention import block_sparse_plan
            from ...ops.block_sparse_ops import selection_width
            width = int(self.cache["rows"][0][0])
            self.block_sparse_kernel = block_sparse_plan(
                int(dec["n_heads"]), width // int(dec["head_dim"]),
                int(dec["head_dim"]), self.block_size, self._pool_dtype,
                selection_width(self.block_sparse["topk"],
                                self.block_sparse["block"],
                                self.block_sparse["dense_len"]))
            plan = PagedPlan(
                "block_sparse", self.block_sparse_kernel["pages_per_block"],
                self.block_sparse_kernel["heads_per_product"],
                self.block_sparse_kernel["score_columns_per_block"])
        else:
            plan = paged_decode_plan(
                self.cache["kind"], self.cache["rows"], int(dec["n_heads"]),
                self.block_size, self._pool_dtype, self.max_blocks_per_seq,
                self.window or None)
        #: P, the pages of one compute block of the paged decode kernel
        #: (the one that walks every live page of a slot: of an indexer
        #: bundle the index keys')
        self.paged_block_pages = plan.pages_per_block
        #: a block of the paged kernel where groups of query heads share
        #: K/V heads: the heads one product scores and its score columns
        #: (None: the per-head, latent and index kernels have no such block)
        self.paged_group_block = {
            "heads_per_product": plan.heads_per_product,
            "score_columns_per_block": plan.score_columns_per_block}
        #: a model with a sparse-attention indexer: the sparse attention
        #: kernel's two walks (kappa of the rule that chooses a slot's, P
        #: of the page walk, the row walk's chunk, the heads a product of
        #: a block scores and its score columns); None for any other
        self.sparse_kernel = plan.sparse
        #: a model with experts: what each grouped product of a decode
        #: step's expert layer runs (`kernels.expert_matmul
        #: .expert_matmul_plan` at the step's rows and the matrices as
        #: the bundle stores them: "ragged_dot", XLA's kernel, or
        #: "pallas", the repo's); None for a dense model
        self.expert_kernel = self._expert_plans(
            dec.get("model_cfg", {}).get("block") or {})
        self._device = jax.local_devices()[0]
        # A model with experts: the step takes and returns its routing
        # counters behind the pools (int32 [3], on the device). `_moe`
        # is (what was folded into host integers, the device's counters
        # since), replaced as one object so any thread reads a pair
        # that belongs together; None for a dense model.
        moe = dec.get("moe_stats")
        #: a model with experts: the chosen experts of the last prefill
        #: ([n_layers, bound, top_k] int32, rows past the prompt are
        #: padding) or decode step ([n_layers, slots, top_k]), left on
        #: the device. Nothing reads it while serving: it is what a
        #: check against a reference forces the reference's routes to.
        self.last_routes = None
        routes = dec.get("moe_routes")
        self._prefill_routes_role = routes["prefill"] if routes else None
        #: a model with a sparse-attention indexer: the positions
        #: attention was restricted to, left on the device as
        #: `last_routes` is and for the same reader. After a prefill:
        #: every row's, one bit a position (a tuple of [bound, bound /
        #: 32] int32, one a layer; `ops.attention_ops.unpack_mask`, or
        #: `np.stack` first); after a decode
        #: step: every slot's ([n_layers, slots, topk] int32, highest
        #: indexer score first, -1 behind the slot's count)
        self.last_selections = None
        self._prefill_selected_role = sel["prefill"] if sel else None
        #: rows a query keeps (0: the step reads every live row)
        self.index_topk = int(sel["topk"]) if sel else 0
        self._moe: Optional[tuple] = None
        self._moe_steps = 0
        if moe:
            self._moe_fields = len(moe["fields"])
            self._moe = (np.zeros(self._moe_fields, np.int64),
                         self._moe_zeros())
            self._moe_fold_every = max(
                1, (2 ** 30) // max(int(moe["max_per_step"]), 1))
        #: the engine's phase clocks; DecodeEngine points this at its
        #: DecodeMetrics' timer, a bare model keeps one of its own
        self.timer = DecodePhaseTimer()
        #: in-flight accounting (`_launched`, `_waited`): the number of
        #: the newest dispatch, and the `perf_counter` reading at which
        #: it was known finished, None while anything may be in flight
        self._dispatched = 0
        self._drained_at: Optional[float] = None
        #: told the bytes an admission moves between host and device
        #: memory, where they move; DecodeEngine points it at
        #: DecodeMetrics.on_prefill_host_bytes
        self.count_host_bytes: Callable[[int], None] = lambda nbytes: None
        #: told the bytes a step's results move to the host, where they
        #: move, and whether they are the logits somebody asked for;
        #: DecodeEngine points it at DecodeMetrics.on_step_host_bytes
        self.count_step_bytes: Callable[[int, bool], None] = \
            lambda nbytes, logits: None
        #: told, a step of a model with an indexer, the cache rows live
        #: in its slots, the rows of them its attention read, the slots
        #: whose rows the sparse kernel reached by walking their pages
        #: whole and the pages it read for them, a layer; DecodeEngine
        #: points it at DecodeMetrics.on_sparse_rows
        self.count_sparse_rows: Callable[[int, int, int, int], None] = \
            lambda live, selected, page_walk_slots, walked_pages: None
        #: told, a step of a model whose attention reads chosen blocks,
        #: the blocks its slots read, the pooled keys their choice was
        #: scored on and the slots that read densely, a layer and K/V
        #: head; DecodeEngine points it at DecodeMetrics.on_block_choices
        self.count_block_choices: Callable[[int, int, int], None] = \
            lambda blocks, pooled, dense_slots: None
        #: told, a step of a model with window layers, the rows those
        #: layers read and the rows the contexts hold, over slots and
        #: window layers; DecodeEngine points it at
        #: DecodeMetrics.on_window_rows
        self.count_window_rows: Callable[[int, int], None] = \
            lambda read, live: None
        #: told, a step of a model with state layers, its live slots
        #: times those layers (each moved its state a row on), and, an
        #: admission, the bytes of state it wrote into the slot (0 at a
        #: step); DecodeEngine points it at DecodeMetrics.on_state_rows
        self.count_state_rows: Callable[[int, int], None] = \
            lambda slot_steps, seeded_bytes: None
        #: told, a step of a model with layers that read another's pool,
        #: the rows the full pool's writers read of it and the rows its
        #: other readers did, over slots and layers; DecodeEngine points
        #: it at DecodeMetrics.on_pool_rows
        self.count_pool_rows: Callable[[int, int], None] = \
            lambda writer, readers: None
        self._admit_fns: Dict[int, _BucketCalls] = {
            bound: self._jit_bucket(self.prefill_model.bucket(bound))
            for bound in self.prefill_model.bounds}
        self.reset_pools()
        if warmup:
            self._warmup()

    @property
    def weights(self) -> Dict:
        """The bundle's weights on the device, by name: the one copy
        every artifact is called with."""
        return self.prefill_model.weights

    def _expert_plans(self, block: dict) -> Optional[dict]:
        """{product: its plan} of the first expert layer's grouped
        products at a decode step's rows (slots x top-k pairs; of a held
        share, at most one a held expert and slot)."""
        from ...kernels.expert_matmul import expert_matmul_plan
        stem = min((n[:-len("up_w")] for n in self.weights
                    if re.fullmatch(r"moe\d+_up_w", n)), default=None)
        if stem is None:
            return None
        mats = {tag: self.weights.get(f"{stem}{tag}_w")
                for tag in ("gate", "up", "down")}
        held = mats["up"].shape[0]
        rows = self.slots * min(int(block["experts_per_tok"]), held)
        return {tag: expert_matmul_plan(rows, w.shape[1], w.shape[2], held,
                                        w.dtype)._asdict()
                for tag, w in mats.items() if w is not None}

    def _named_weights(self, names: Optional[Sequence[str]]) -> Dict:
        """The weights one artifact takes as its first argument ({} for
        one that inlines them): what a jitted call over it is passed."""
        return {} if names is None else {n: self.weights[n] for n in names}

    # -- in-flight accounting -------------------------------------------------
    def _launched(self) -> int:
        """Right after a call that dispatched device work has returned.
        If the device was known drained, it had nothing to run from
        then until now: one `device_idle` phase, which is no scope (it
        opened in another call) and so no profiler annotation. Returns
        the dispatch's number, for the wait on it to give `_waited`."""
        now = time.perf_counter()
        if self._drained_at is not None:
            self.timer.add("device_idle", now - self._drained_at, now)
            self._drained_at = None
        self._dispatched += 1
        return self._dispatched

    def _waited(self, dispatch: int) -> None:
        """Right after a wait on `dispatch` has returned: the device is
        drained if that was the newest one. A wait on an older one (an
        admission's logits row, with the seeding dispatched behind it)
        says nothing of what came after: the device counts as busy, so
        `device_idle` is a lower bound on the device's idle time."""
        if dispatch == self._dispatched:
            self._drained_at = time.perf_counter()

    # -- device pools --------------------------------------------------------
    def reset_pools(self) -> None:
        """Zeroed pools, committed to the serving device: the same kind
        of argument as the pools a step or a seeding returns, so every
        executable is built once, in the warm-up."""
        import jax
        import jax.numpy as jnp
        # the old pools go before the new ones come: never two sets
        self._pools: List = []
        self._pools = [
            jax.device_put(jnp.zeros(shape, self._pool_dtype), self._device)
            for shape in self._pool_shapes]
        self._launched()

    def _moe_zeros(self):
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.zeros((self._moe_fields,), jnp.int32),
                              self._device)

    def moe_counters(self) -> Optional[tuple]:
        """(host totals, device counters since): their sum is the
        routed pairs, touched experts and layer-steps of every step
        dispatched so far. Fetches nothing; None for a dense model."""
        return self._moe

    def _warmup(self) -> None:
        """Every executable the engine runs, compiled (or pulled from
        the persistent cache) before the first real sequence: each
        bucket's prefill and seeding, writing the null block only, then
        one all-inactive step."""
        import jax
        for bound in self.prefill_model.bounds:
            last, kv = self.prefill([0] * bound)
            self.seed_sequence(
                [0] * blocks_for_tokens(bound, self.block_size), kv)
            jax.block_until_ready((last.row, self._pools))
            self._waited(self._dispatched)
        # like a real step's free slots, it writes the null block only;
        # reading its tokens is the wait: the load ends drained
        self.decode_step(np.zeros(self.slots, np.int64),
                         np.zeros(self.slots, np.int32),
                         np.zeros((self.slots, self.max_blocks_per_seq),
                                  np.int32)).tokens

    # -- admission: prefill, then seeding ------------------------------------
    def _jit_bucket(self, bucket):
        """The two jitted calls of one length bucket. The prefill wraps
        the artifact and keeps its outputs on the device; the seeding
        takes the pools donated and writes them in place."""
        import jax
        import jax.numpy as jnp

        call, names = bucket.unbound_call, bucket.weight_names
        weights = self._named_weights(names)
        order = self.prefill_model.fetch_names
        logits_at = order.index(self._logits_role)
        kv_at = [order.index(r) for pair in self._kv_roles for r in pair]
        routes_at = (order.index(self._prefill_routes_role)
                     if self._prefill_routes_role else None)
        selected_at = [order.index(r)
                       for r in self._prefill_selected_role or ()]
        bs = self.block_size
        n_blocks = blocks_for_tokens(bucket.length, bs)
        pad = n_blocks * bs - bucket.length

        def prefill(weights, ids, n):
            # the artifact computes the head for position n - 1 alone
            feeds = (ids, jnp.zeros((ids.shape[0],), jnp.int32).at[0].set(n))
            outs = ModelVersion._normalize(
                call(*feeds) if names is None else call(weights, *feeds))
            routes = None if routes_at is None else outs[routes_at][0]
            selected = (tuple(outs[i][0] for i in selected_at)
                        if selected_at else None)
            return (outs[logits_at][0, 0], tuple(outs[i] for i in kv_at),
                    routes, selected)

        pool_table = self._pool_table

        def seed(pools, kv, block_ids, n):
            # rows at or past n are the bucket's padding: a pool holds
            # zeros there, as if the true-length rows had been padded;
            # so do the columns of a pool's row past what the artifact
            # returned (a latent row stored in whole lane tiles).
            # `block_ids` is one vector, or one entry a way of
            # addressing (`_pool_table`): a window layer's names the
            # null block for the prompt's blocks behind the window,
            # which nothing reads; a state layer's is the slot, whose
            # rows the prefill returned whole
            per_kind = block_ids if isinstance(block_ids, tuple) \
                else (block_ids, block_ids)
            out = []
            for pool, rows, table in zip(pools, kv, pool_table):
                block_ids = per_kind[table]
                rows = rows[0]
                if table == _STATE:
                    out.append(pool.at[block_ids].set(
                        rows.astype(pool.dtype)))
                    continue
                by_block = rows.ndim + 1 < pool.ndim
                if by_block:
                    # a pool whose row has leading axes of one (a latent
                    # row a copy: [1, W])
                    rows = rows.reshape(rows.shape[:1] + (1,) * (
                        pool.ndim - rows.ndim - 1) + rows.shape[1:])
                if rows.shape[1:] != pool.shape[2:] and np.prod(
                        rows.shape[1:]) == np.prod(pool.shape[2:]):
                    # heads the pool packs into whole lane tiles
                    rows = rows.reshape(rows.shape[:1] + pool.shape[2:])
                wide = [(0, p - r) for p, r in zip(pool.shape[2:],
                                                   rows.shape[1:])]
                rows = jnp.pad(rows, [(0, pad)] + wide)
                live = (jnp.arange(n_blocks * bs) < n).reshape(
                    (-1,) + (1,) * (rows.ndim - 1))
                pages = jnp.where(live, rows, 0).astype(pool.dtype)
                pages = pages.reshape((n_blocks, bs) + pages.shape[1:])
                if not by_block:
                    out.append(pool.at[block_ids].set(pages))
                    continue
                # such a pool a block at a time, in place: XLA's scatter
                # would turn the WHOLE pool into the tiling of a
                # [NB, BS, W] array and back (`kernels.paged_attention
                # .paged_row_update`)

                def put(i, pool, pages=pages, block_ids=block_ids):
                    return jax.lax.dynamic_update_slice(
                        pool, jax.lax.dynamic_slice_in_dim(pages, i, 1),
                        (block_ids[i],) + (0,) * (pool.ndim - 1))

                out.append(jax.lax.fori_loop(0, n_blocks, put, pool))
            return out

        # the executables' names in a profile
        prefill.__name__ = f"prefill_{bucket.length}"
        seed.__name__ = f"seed_kv_{bucket.length}"
        feed = bucket.feeds[0]
        return _BucketCalls(jax.jit(prefill), weights,
                            jax.jit(seed, donate_argnums=0),
                            tuple(feed["shape"]), np.dtype(feed["dtype"]))

    def prefill(self, token_ids: Sequence[int]):
        """Run the prompt (or a resumed prompt+generated prefix) through
        its length bucket. Returns (last-position logits [vocab] as a
        `PrefillRow`, PrefillKV), both on the device: nothing has been
        waited for, the logits row's copy to the host has been
        requested."""
        n = len(token_ids)
        with self.timer.span("prefill_pad"):
            tokens = np.asarray(
                token_ids, dtype=self.prefill_model.feed_dtypes()["src_ids"])
            length = np.int32(n)
            bound = self.prefill_model.bucket_of({"src_ids": tokens,
                                                  "n_tokens": length})
            calls = self._admit_fns[bound]
            ids = np.zeros(calls.ids_shape, calls.ids_dtype)
            ids[0, :n] = tokens
        with self.timer.span("prefill_device"):
            last, arrays, self.last_routes, self.last_selections = \
                calls.prefill(calls.weights, ids, length)
            dispatch = self._launched()
            last.copy_to_host_async()
        self.count_host_bytes(ids.nbytes + length.nbytes)
        return (PrefillRow(last, lambda: self._waited(dispatch)),
                PrefillKV(arrays, n, bound))

    def window_span(self, length: int) -> tuple:
        """(first, count) of the table entries a window layer holds for
        a sequence of `length` cached tokens: from the block of the
        oldest row a query at position `length - 1` reads to the block
        of that row (`kv_cache.window_blocks`)."""
        from .kv_cache import window_blocks
        return window_blocks(length, self.window, self.block_size)

    def seed_sequence(self, block_ids: Sequence[int], kv: PrefillKV,
                      skip_rows: int = 0,
                      window_ids: Optional[Sequence[int]] = None,
                      slot: int = 0) -> None:
        """Write one sequence's prefill cache rows into its blocks: one
        dispatch, every pool updated in place. `skip_rows` rows at the
        front are already resident (aliased shared-prefix blocks,
        kv_cache.py refcounts) and MUST NOT be rewritten: their blocks'
        entries, like those past the prompt, name the null block, which
        nothing reads. A non-block-aligned skip means the whole prompt
        was matched (partial-tail alias), so nothing is written at
        all. A bundle with window layers: `window_ids` are the window
        pool's blocks for the table entries `window_span(kv.n)` names,
        the prompt's last window; without them the window layers are
        seeded whole through `block_ids` (ids that their pool has too:
        a caller that runs one sequence alone). A bundle with state
        layers: `slot` is the slot the sequence will decode in; the same
        dispatch overwrites that slot's state with what the prompt
        leaves behind, whatever the slot's former owner left there."""
        skip = int(skip_rows)
        bs = self.block_size
        with self.timer.span("seed_kv"):
            if kv.n <= skip:
                return   # fully aliased: every row already resident
            if skip % bs:
                raise ValueError(
                    f"skip_rows {skip} neither block-aligned nor "
                    f"the full prefill ({kv.n} rows)")
            used = blocks_for_tokens(kv.n, bs)
            if used > len(block_ids):
                raise ValueError(f"{kv.n} rows exceed {len(block_ids)} "
                                 f"blocks x {bs}")
            ids = np.zeros(blocks_for_tokens(kv.bound, bs), np.int32)
            ids[skip // bs:used] = block_ids[skip // bs:used]
            length = np.int32(kv.n)
            moved = ids.nbytes + length.nbytes
            if self.window:
                wids = ids
                if window_ids is not None:
                    first, count = self.window_span(kv.n)
                    if count != len(window_ids):
                        raise ValueError(
                            f"{len(window_ids)} window blocks for the "
                            f"{count} a prompt of {kv.n} rows holds")
                    wids = np.zeros_like(ids)
                    wids[first:first + count] = window_ids
                ids = (ids, wids)
                moved += wids.nbytes
            if self.slot_rows:
                if not 0 <= int(slot) < self.slots:
                    raise ValueError(f"slot {slot} outside the "
                                     f"{self.slots} there are")
                at = np.int32(slot)
                ids = (*ids, at) if self.window else (ids, ids, at)
                moved += at.nbytes
            self._pools = self._admit_fns[kv.bound].seed(
                self._pools, kv.arrays, ids, length)
            self._launched()
        self.count_host_bytes(moved)
        if self.state_layers:
            self.count_state_rows(0, self.state_bytes // self.slots)

    # -- the decode step -----------------------------------------------------
    def decode_step(self, token_ids: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray,
                    window_tables: Optional[np.ndarray] = None
                    ) -> StepResult:
        """One fixed-shape step over all slots, DISPATCHED: writes every
        slot's new cache row into the resident pools, in place (the
        pools given to the call are donated and deleted; `_pools` are
        its outputs, the same buffers), and chooses every slot's next
        token on the device. Returns a `StepResult`: its `.tokens`
        (host int32 [slots]: all of a step that crosses) wait for the
        step and fetch them when first read, its logits [slots, vocab]
        stay on the device behind `np.asarray`. A slot of `token_ids`
        that holds `PREVIOUS_TOKEN` is fed the id the step dispatched
        before this one chose for that slot, on the device: a caller
        that knows a sequence goes on need not read a step's tokens
        before it dispatches the next. Dispatches run in the order they
        were made and each takes the pools the one before left, so a
        block freed on the host and handed on is written only after
        every earlier reader. A bundle with window layers takes their
        table beside the full layers' (`window_tables`; without it both
        kinds read `block_tables`, as in `seed_sequence`)."""
        metas = self._feed_meta
        with self.timer.span("step_dispatch"):
            tables = np.asarray(block_tables,
                                dtype=np.dtype(metas[2]["dtype"]))
            if self.window:
                tables = (tables, tables if window_tables is None
                          else np.asarray(window_tables,
                                          dtype=tables.dtype))
            lens = np.asarray(context_lens,
                              dtype=np.dtype(metas[1]["dtype"]))
            args = [self._step_weights,
                    np.asarray(token_ids,
                               dtype=np.dtype(metas[0]["dtype"])),
                    lens, tables, self._pools, self._last_ids]
            if self._moe is not None:
                # not donated: DecodeMetrics holds a reference to them
                args.append(self._moe[1])
            if self._step is None:
                self._compile_step(args)
            ids, logits, self._pools, behind = self._step(*args)
            dispatch = self._launched()
            self._last_ids = ids
            if self._moe is not None:    # counters, then routes, behind
                self._carry_moe(behind[0])
                self.last_routes = behind[1]
            if self.index_topk or self.block_sparse:    # selections last
                self.last_selections = behind[-1]
            # the ids' copy to the host is requested now, behind the
            # step, as np.asarray alone would have requested it: the
            # wait, whenever it comes, must not put a host round trip
            # between the two
            ids.copy_to_host_async()
        if self.state_layers:
            self.count_state_rows(
                int(np.count_nonzero(lens)) * self.state_layers, 0)
        if self.pool_readers:
            rows = int(lens.astype(np.int64).sum())
            self.count_pool_rows(rows * self.full_layers,
                                 rows * self.pool_readers)
        if self.window:
            rows = lens.astype(np.int64)
            self.count_window_rows(
                int(np.minimum(rows, self.window).sum())
                * self.window_layers, int(rows.sum()) * self.window_layers)
        if self.index_topk:
            # which slots' pages were walked whole: the rule the op
            # itself applied to these lengths
            from ...kernels.paged_attention import sparse_walks_pages
            by_pages = sparse_walks_pages(
                lens, topk=self.index_topk, block_size=self.block_size)
            if (self.sparse_kernel or {}).get("walk") == "rows":
                by_pages[:] = False     # a kernel that walks rows alone
            self.count_sparse_rows(
                int(lens.sum()),
                int(np.minimum(lens, self.index_topk).sum()),
                int(by_pages.sum()),
                int((-(-lens[by_pages] // self.block_size)).sum()))
        if self.block_sparse:
            self._count_blocks(lens.astype(np.int64))
        return StepResult(ids, logits, self.timer,
                          lambda: self._waited(dispatch),
                          self.count_step_bytes)

    def _count_blocks(self, lens) -> None:
        """What the step's block-sparse layers read, from its lengths
        alone (`ops.block_sparse_ops.chosen_counts`: the rule the op
        applied to them)."""
        from ...ops.block_sparse_ops import chosen_counts
        read, pooled, chosen, dense = chosen_counts(
            lens, self.block_sparse, self.block_size)
        self.count_sparse_rows(int(lens.sum()), read, 0, 0)
        self.count_block_choices(chosen, pooled, dense)

    def _compile_step(self, args) -> None:
        """Build the step's one executable from the first step's own
        arguments, and read from it how many bytes it updates in place."""
        self._step = self._step_fn.lower(*args).compile()
        mem = self._step.memory_analysis()
        # a backend that reports nothing reads as no donation
        self.step_aliased_bytes = int(mem.alias_size_in_bytes) if mem else 0

    def _carry_moe(self, counters) -> None:
        """The step's routing counters become the next step's feed; once
        in `_moe_fold_every` steps, long before int32 could wrap, they
        are fetched into the host totals and restarted from zero. A
        carry and not a wrapping counter read as a difference: that
        would be right only while somebody reads it at least once a
        wrap, and a server nobody scraped for a day would then report
        too little without a sign of it; the fold costs 12 bytes once
        in 1.6 million steps of the 5-layer cell and depends on no
        reader."""
        base = self._moe[0]
        self._moe_steps += 1
        if self._moe_steps >= self._moe_fold_every:
            self._moe_steps = 0
            base = base + np.asarray(counters, np.int64)
            counters = self._moe_zeros()
        self._moe = (base, counters)

    def permute_blocks(self, mapping: Dict[int, int]) -> None:
        """Apply a kv_cache defrag mapping to the device pools: block
        old -> new for every moved block."""
        if not mapping:
            return
        import jax.numpy as jnp
        src = jnp.asarray(list(mapping.keys()), dtype=jnp.int32)
        dst = jnp.asarray(list(mapping.values()), dtype=jnp.int32)
        # the mapping is the full pools': a window layer's blocks have
        # ids of their own and are never compacted (they are released
        # and reused all through a sequence)
        # (nor has a state layer's array any block to move)
        self._pools = [p if table else p.at[dst].set(p[src])
                       for p, table in zip(self._pools, self._pool_table)]
        self._launched()

    def copy_block(self, src: int, dst: int) -> None:
        """Device-copy one pool block (every pool of every layer) — the
        copy-on-write primitive: a sequence about to write into a
        shared block gets its own copy first."""
        self._pools = [p if table == _STATE else p.at[dst].set(p[src])
                       for p, table in zip(self._pools, self._pool_table)]
        self._launched()

    def describe(self) -> dict:
        return {
            "model_dir": self.model_dir,
            "slots": self.slots, "block_size": self.block_size,
            "pool_blocks": self.pool_blocks,
            "max_context": self.max_context,
            "max_prompt_len": self.max_prompt_len,
            "prefill_buckets": self.prefill_model.bounds,
            # a bucket's attention as exported: "flash" (the Pallas
            # forward), "flash_selected" (the same over an indexer's
            # selection, a tile a block) or "masked_dense" (XLA's
            # products over whole score rows: off the chip)
            "prefill_attention": self.prefill_attention,
            "n_layers": self.n_layers, "vocab_size": self.vocab_size,
            "eos_id": self.eos_id,
            # the weights on the device: their bytes, and the dtype the
            # matrices are stored and served in
            "weight_bytes": self.weight_bytes,
            "weight_dtype": self.weight_dtype,
            "step_aliased_bytes": self.step_aliased_bytes,
            # where a step's next tokens are chosen: a step's ids cross
            # to the host when its result is asked for them, its logits
            # only on request
            "token_choice": "device",
            # what the pools hold of a token: the kind, a layer's row
            # shapes, the floats of them that carry the token, and the
            # bytes a token takes over all layers as stored
            "cache": dict(self.cache),
            # the paged kernel's walk, a layer call: P pages a compute
            # block, and the most blocks a call can walk (every slot at
            # the table's full width); it walks the live ones only.
            # Where groups of query heads share K/V heads, the query
            # heads one product of a block scores and the block's score
            # columns (its rows, once); None for the other kernels
            "paged_kernel": {
                "pages_per_block": self.paged_block_pages,
                "max_blocks_per_call": self.slots * -(
                    -self.max_blocks_per_seq // self.paged_block_pages),
                **self.paged_group_block},
            # a model with a sparse-attention indexer: how its attention
            # kernel reaches a slot's selected rows (None for any other)
            "sparse_kernel": self.sparse_kernel,
            # a model whose attention reads whole blocks chosen on
            # pooled keys: the kernel's walk over a K/V head's chosen
            # pages (None for any other)
            "block_sparse_kernel": self.block_sparse_kernel,
            # a model with experts: the plan of each grouped product of
            # a step's expert layer (None for a dense model)
            "expert_kernel": self.expert_kernel,
        }


class DecodeEngine:
    """Continuous-batching generation over one decode bundle.

    >>> eng = DecodeEngine("/models/lm_decode")
    >>> h = eng.generate([5, 17, 9], max_new_tokens=32)
    >>> for tok in h.stream(): ...
    >>> h.result()["tokens"]

    Knobs (constructor args win; env supplies deployment defaults):
    PT_DECODE_MAX_NEW_TOKENS (default generation budget),
    PT_SERVE_QUEUE_DEPTH / PT_SERVE_DEADLINE_MS (admission — shared with
    the one-shot engine on purpose: one admission policy per process),
    PT_KV_SHARE (copy-on-write prefix sharing, decode/prefix.py),
    PT_SPEC_DRAFT / PT_SPEC_K (speculative decoding, decode/spec.py).
    """

    def __init__(self, model_dir: Optional[str] = None, *,
                 model: Optional[DecodeModel] = None,
                 queue_depth: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 max_new_tokens: Optional[int] = None,
                 continuous: bool = True,
                 pool_blocks: Optional[int] = None,
                 metrics: Optional[DecodeMetrics] = None,
                 kv_share: Optional[bool] = None,
                 drafter: Optional[str] = None,
                 spec_k: Optional[int] = None,
                 name: str = "model", warmup: bool = True):
        if model is None:
            if model_dir is None:
                raise ValueError("DecodeEngine needs model_dir or model")
            model = DecodeModel(model_dir, warmup=warmup)
        self.model = model
        self.name = name
        self.max_new_tokens = (
            env_int("PT_DECODE_MAX_NEW_TOKENS", 64)
            if max_new_tokens is None else int(max_new_tokens))
        # pool_blocks may RESTRICT accounting below the artifact's pool
        # (partitioning one exported pool across tenants; forcing
        # eviction pressure in tests) — never exceed the device shape
        self.pool = KVBlockPool(min(pool_blocks or model.pool_blocks,
                                    model.pool_blocks), model.block_size)
        # a bundle with window layers: their blocks have a pool, and
        # ids, of their own; it holds every slot's window at once, so
        # it never runs dry and is never restricted
        window = getattr(model, "window", 0)
        self.window_pool = (KVBlockPool(model.window_pool_blocks,
                                        model.block_size)
                            if window else None)
        self.admission = AdmissionController(
            queue_depth=(env_int("PT_SERVE_QUEUE_DEPTH", 256)
                         if queue_depth is None else int(queue_depth)),
            max_batch_size=1,
            default_deadline_ms=(env_float("PT_SERVE_DEADLINE_MS", 0.0)
                                 if deadline_ms is None
                                 else float(deadline_ms)))
        self.metrics = metrics or DecodeMetrics(name)
        model.timer = self.metrics.timer
        model.count_host_bytes = self.metrics.on_prefill_host_bytes
        model.count_step_bytes = self.metrics.on_step_host_bytes
        model.count_sparse_rows = self.metrics.on_sparse_rows
        model.count_block_choices = self.metrics.on_block_choices
        model.count_window_rows = self.metrics.on_window_rows
        model.count_state_rows = self.metrics.on_state_rows
        model.count_pool_rows = self.metrics.on_pool_rows
        self.metrics.pool_readers = getattr(model, "pool_readers", 0)
        # a state layer's arrays, or a sparse layer's pooled keys: what
        # the bundle keeps of a SEQUENCE, by the slot
        state_layers = getattr(model, "state_layers", 0) \
            or int(getattr(model, "slot_rows", False))
        if state_layers:
            self.metrics.state_bytes = model.state_bytes
        self.metrics.weight_bytes = getattr(model, "weight_bytes", None)
        self.metrics.weight_dtype = getattr(model, "weight_dtype", None)
        self.metrics.index_topk = getattr(model, "index_topk", 0)
        self.metrics.block_sparse = bool(getattr(model, "block_sparse",
                                                 None))
        self.metrics.window = window
        self.metrics.step_aliased_probe = lambda: model.step_aliased_bytes
        cache = getattr(model, "cache", None)
        if cache:
            self.metrics.cache_bytes_per_token = cache["bytes_per_token"]
        probe = getattr(model, "moe_counters", None)
        if probe is not None and probe() is not None:
            self.metrics.moe_probe = probe
        # KV economics: both OFF unless asked for — the plain engine's
        # accounting (exact block ids, zero blocks at idle) is a tested
        # contract, and sharing retains blocks past sequence lifetime
        self.kv_share = (bool(env_int("PT_KV_SHARE", 0))
                         if kv_share is None else bool(kv_share))
        self.index = (PrefixIndex(self.pool) if self.kv_share else None)
        spec = (os.environ.get("PT_SPEC_DRAFT", "")
                if drafter is None else drafter)
        self.drafter = resolve_drafter(spec, model)
        self.spec_k = (env_int("PT_SPEC_K", 4)
                       if spec_k is None else int(spec_k))
        if window and (self.kv_share or self.drafter is not None):
            raise WindowCacheUnsupported(
                f"decode bundle {name!r} has window layers (window "
                f"{window}): a window block is not shareable yet "
                "(kv_share) and speculation's borrowed slots have no "
                "window table of their own; load it with both off")
        if state_layers and (self.kv_share or self.drafter is not None):
            raise SequenceStateUnsupported(
                f"decode bundle {name!r} has {state_layers} state layers "
                "(short convolutions, scans, linear attention, or the "
                "pooled keys of a block selection): a shared prefix has "
                "no state "
                "at the point it is shared (kv_share) and a rejected "
                "draft would have to roll the state back (speculation); "
                "load it with both off")
        self.scheduler = DecodeScheduler(model, self.pool, self.admission,
                                         self.metrics,
                                         continuous=continuous, name=name,
                                         prefix_index=self.index,
                                         drafter=self.drafter,
                                         spec_k=self.spec_k,
                                         window_pool=self.window_pool)

    # -- the request path ----------------------------------------------------
    def generate(self, prompt_ids: Sequence[int],
                 max_new_tokens: Optional[int] = None,
                 deadline_ms: Optional[float] = None, priority: int = 0,
                 eos_id: Optional[int] = None) -> GenerationHandle:
        """Admit one prompt; returns a GenerationHandle (stream() /
        result()). Raises typed admission errors reject-fast."""
        prompt = [int(t) for t in prompt_ids]
        max_new = (self.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if not prompt:
            raise InvalidRequest("prompt_ids must be non-empty")
        if max_new < 1:
            raise InvalidRequest(f"max_new_tokens {max_new} < 1")
        if any(t < 0 or t >= self.model.vocab_size for t in prompt):
            raise InvalidRequest(
                f"prompt ids outside [0, {self.model.vocab_size})")
        if len(prompt) > self.model.max_prompt_len:
            raise InvalidRequest(
                f"prompt length {len(prompt)} exceeds the largest "
                f"prefill bucket {self.model.max_prompt_len}")
        if len(prompt) + max_new > self.model.max_context:
            raise InvalidRequest(
                f"prompt {len(prompt)} + max_new {max_new} exceeds "
                f"max_context {self.model.max_context}")
        # a sequence the pool can NEVER hold is pool exhaustion by
        # construction: shed typed at submit instead of deadlocking the
        # admit loop (peak residency is prompt+max_new-1 cached tokens)
        peak = blocks_for_tokens(len(prompt) + max_new - 1,
                                 self.model.block_size)
        if peak > self.pool.capacity:
            self.metrics.on_shed("overload")
            raise Overloaded(
                f"sequence needs {peak} KV blocks at peak but the pool "
                f"holds {self.pool.capacity} — raise "
                f"PT_DECODE_POOL_BLOCKS or lower max_new_tokens")
        return self.scheduler.submit(prompt, max_new,
                                     deadline_ms=deadline_ms,
                                     priority=priority, eos_id=eos_id)

    # -- maintenance ---------------------------------------------------------
    def defrag(self) -> int:
        """Compact live blocks onto the lowest pool ids (host accounting
        + device permute). Returns blocks moved. Runs under the
        scheduler lock with zero live sequences — submission blocks on
        the same lock, so no sequence can be admitted (no decode step
        can touch the pools) mid-permute; raises RuntimeError when the
        engine is not idle."""

        def _do():
            mapping = self.pool.defrag()
            self.model.permute_blocks(mapping)
            if self.index is not None:
                # cached prefixes MOVE with their blocks — the index's
                # chains stay valid across compaction
                self.index.remap(mapping)
            return len(mapping)

        return self.scheduler.while_idle(_do)

    def kv_residency(self) -> dict:
        """Shared-block residency, the session-affinity health signal:
        a session's cached prefix lives HERE, so the fleet router's
        rendezvous hash should keep its follow-ups here too."""
        out = {"kv_blocks_shared": self.pool.blocks_shared,
               "kv_blocks_in_use": self.pool.blocks_in_use,
               "kv_blocks_indexed": (self.index.blocks_indexed
                                     if self.index is not None else 0)}
        if self.index is not None:
            out.update(prefix_hits=self.index.hits,
                       prefix_hit_tokens=self.index.hit_tokens)
        return out

    def metrics_snapshot(self) -> dict:
        return self.metrics.snapshot()

    def describe(self) -> dict:
        out = self.model.describe()
        out["continuous"] = self.scheduler.continuous
        out["max_new_tokens_default"] = self.max_new_tokens
        out["kv_share"] = self.kv_share
        out["drafter"] = (getattr(self.drafter, "name", "custom")
                          if self.drafter is not None else None)
        out["spec_k"] = self.spec_k if self.drafter is not None else 0
        # how far the loop dispatches ahead of the tokens it has read
        out["dispatch_ahead"] = self.scheduler.dispatch_ahead()
        # a slow step: how many phases overran, and what the newest
        # one's thread was doing (the stall sentinel's record)
        out.update(self.metrics.timer.overrun_snapshot())
        # what this bundle refuses at load (`WindowCacheUnsupported`,
        # `SequenceStateUnsupported`)
        out["refuses"] = (["kv_share", "speculation"]
                          if self.window_pool is not None
                          or getattr(self.model, "state_layers", 0)
                          or getattr(self.model, "slot_rows", False)
                          else [])
        return out

    def shutdown(self, drain: bool = True) -> None:
        self.scheduler.close(drain=drain)
