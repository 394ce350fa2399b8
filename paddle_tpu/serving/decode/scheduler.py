"""Continuous-batching scheduler: prefill/decode split, slot admission,
eviction under KV-pool pressure.

The shape insight (vLLM-style continuous batching, translated to AOT
artifacts): the decode step is ONE fixed-shape dispatch — one token per
slot — so sequences of wildly different lengths share a batch, and a
sequence that finishes frees its slot for a WAITING sequence at the very
next iteration. There is no drain-to-empty barrier: admission happens
into the in-flight batch. The alternative (static batching: admit N,
decode until ALL N finish) wastes every slot whose sequence finished
early — the `decode` bench config measures exactly that gap, and this
scheduler also implements the static mode (`continuous=False`) to BE the
honest baseline.

Split responsibilities:

    prefill   the prompt runs ONCE through the length-bucketed
              full-attention artifacts, emitting the first token AND
              every layer's K/V rows, which seed the sequence's pool
              blocks without leaving the device;
    decode    each iteration advances every RUNNING sequence one token
              through the paged decode-step artifact.

Eviction/preemption: when a sequence needs a KV block and the pool has
none, the lowest-priority (then youngest) victim is preempted — blocks
freed, sequence re-queued at the waiting front. A resumed sequence
re-prefills prompt+generated (greedy decode is a pure function of the
prefix, so the continuation is token-identical — tested). Shedding is
typed through PR-5's admission machinery: `Overloaded` (queue/pool
pressure, retryable) and `DeadlineExceeded` (the remaining-token
estimate — tokens left x EWMA step seconds — says the deadline is
unmeetable, or it already passed).

KV economics (PT_KV_SHARE / PT_SPEC_DRAFT, decode/prefix.py +
decode/spec.py): with a prefix index armed, admission aliases the
resident prefix of a new prompt into its block table (pool refcounts;
one copy backs N sessions) and copy-on-write keeps shared blocks
immutable — the first decode write into an aliased partial block
copies it out first (`_cow_for_write`). Under pool pressure the
scheduler releases cached-prefix references LRU-leaf-first BEFORE
preempting running sequences. With a drafter armed, idle slots verify
drafted tokens in the same fixed-shape step (decode/spec.py explains
the slot-packing), greedy acceptance keeps output token-identical to
plain decode, and block growth is provisioned for the FULL draft
window up front — speculation may be dropped for a step (never evicts
a peer) when the pool can't cover it.

Window layers (a bundle that declares two kinds of cache, kv_cache.py):
a sequence holds a second block list, in the window pool's ids, for the
table entries its window still reaches (`Sequence.wstart`, `.wblocks`).
Admission allocates the prompt's last window; each step releases the
blocks that fell wholly behind the window BEFORE it allocates the block
the new row needs, so a slot never holds more than
`window / block_size + 1` and the window pool (slots times that) never
runs dry: only the full pool knows pressure, eviction and preemption,
and those free a victim's window blocks with its others. The step takes
one table a kind.

State layers (a bundle that declares `state` among its kinds of cache,
kv_cache.py): what such a layer keeps of a sequence lives in the
sequence's SLOT of a device array and in no block, so the slot is
chosen BEFORE the admission's seeding, which writes it
(`model.seed_sequence(..., slot=)`), and stays the sequence's until it
ends or is preempted. Nothing else changes: a finish, an eviction and a
preemption free blocks and leave the slot's state to its next owner's
admission; a resume re-prefills `tokens_so_far` and so rebuilds it; the
over-run row of an EOS in flight moves the state of a slot nobody owns
any more; every dispatch takes the arrays the one before left, so an
admission's write comes after every step that moved the slot before it.

Dispatch ahead: `model.decode_step` returns when the step is dispatched,
and while the running set is steady the loop prepares and dispatches
step N+1 BEFORE it reads step N's tokens: a sequence that goes on is fed
`PREVIOUS_TOKEN`, the id step N chose for its slot, which the device
resolves from the ids it kept; step N's wait, fetch and emission then
run with N+1 queued behind it, so the device goes from one step into the
next and the host's work lies beside the device's. At most ONE step is
ever queued behind the one being waited for (`self._flight`). One thread
dispatches and every dispatch takes the pools the one before left, so
the order of dispatches is the order of writes: a block freed on the
host and handed on is written after every earlier reader. What the host
knows without the tokens: a finish by length (it ends with the step in
flight and takes no part in the next); not a finish by EOS (the
sequence rides in N+1, its one over-run row lands in a block it still
owns, its over-run token is dropped at emission and it is finished, and
its blocks freed, one step late: `_ended`). The loop DRAINS (collects
the step in flight before it dispatches anything else) for whatever
needs the host to hold every token or changes the running set, all read
from its own state (`DRAIN_REASONS`): an admission (a slot is free or
about to be and something waits; an admission itself is synchronous as
ever), block growth the pool cannot cover (an eviction, a
self-preemption, a copy-on-write's target hunt: a resume re-prefills
`tokens_so_far`), a running sequence's deadline shed, a drafter (its
proposals are made from host tokens: such an engine collects every step
at once, the same loop at depth 0). A sequence stays counted in `_load`
until the last step it rode in is emitted, so `while_idle` (defrag)
never runs with anything in flight.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence as Seq

import numpy as np

from ...obs import trace as obs_trace
from ...resilience import faults
from ..admission import (AdmissionController, DeadlineExceeded,
                         ModelUnavailable, Overloaded)
from ..metrics import DecodeMetrics
from .kv_cache import (KVBlockPool, PoolExhausted, block_table_row,
                       window_blocks)
from .spec import accept_greedy

__all__ = ["GenerationHandle", "Sequence", "DecodeScheduler",
           "PREVIOUS_TOKEN", "DRAIN_REASONS"]

_TOK, _DONE, _ERR = 0, 1, 2

#: in a slot of `decode_step`'s `token_ids`: "the id the step dispatched
#: before this one chose for this slot", resolved on the device
PREVIOUS_TOKEN = -1

#: why the loop collects the step in flight with nothing queued behind it
#: (`DecodeMetrics.on_drain`): the four that need the host to hold every
#: token or change the running set, and `tail`, the step every sequence
#: of which ends with it by length, so that nothing is left to dispatch
DRAIN_REASONS = ("admission", "eviction", "shed", "drafter", "tail")


class _Flight(NamedTuple):
    """A step that has been dispatched and not yet emitted."""

    active: List["Sequence"]
    drafts: Dict[int, List[int]]
    spec_slots: Dict[int, List[int]]
    feeds: tuple
    result: object     #: the model's, `.tokens` is the wait
    moe_ref: object    #: the routing counters as of this step's dispatch
    ahead: bool        #: dispatched with the step before it uncollected


class GenerationHandle:
    """The caller's view of one generation: a token stream plus a final
    result. Tokens arrive on an internal queue as the scheduler emits
    them; `stream()` yields them live, `result()` blocks to the end.
    Terminal failures (typed serving errors) raise from either."""

    def __init__(self, prompt_len: int):
        self.prompt_len = prompt_len
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self._result: Optional[dict] = None
        self._error: Optional[BaseException] = None
        #: the request's stamps, on `time.perf_counter()` (the trace
        #: ring's clock): submitted; first admitted (its prefill
        #: starts); first token emitted; finished or failed
        self.t_submit = time.perf_counter()
        self.t_admit: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None

    # -- scheduler side ------------------------------------------------------
    def _put_token(self, tok: int) -> None:
        if self.t_first_token is None:
            self.t_first_token = time.perf_counter()
        self._q.put((_TOK, tok))

    def _finish(self, result: dict) -> None:
        self.t_done = time.perf_counter()
        self._result = dict(result, t_submit=self.t_submit,
                            t_admit=self.t_admit,
                            t_first_token=self.t_first_token,
                            t_done=self.t_done)
        self._done.set()
        self._q.put((_DONE, self._result))

    def _fail(self, exc: BaseException) -> None:
        self.t_done = time.perf_counter()
        self._error = exc
        self._done.set()
        self._q.put((_ERR, exc))

    # -- caller side ---------------------------------------------------------
    def stream(self, timeout: Optional[float] = None):
        """Yield tokens as they are generated; returns when the sequence
        finishes, raises its typed error if it was shed/failed, raises
        TimeoutError (like result()) when no token arrives in time."""
        while True:
            try:
                kind, val = self._q.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    "generation still in progress") from None
            if kind == _TOK:
                yield val
            elif kind == _DONE:
                return
            else:
                raise val

    def result(self, timeout: Optional[float] = None) -> dict:
        """Block until the sequence finishes; returns {"tokens",
        "finish_reason", "evictions", "prompt_len"} and the request's
        stamps `t_submit`, `t_admit`, `t_first_token`, `t_done`
        (`time.perf_counter()` readings)."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in progress")
        if self._error is not None:
            raise self._error
        return dict(self._result)

    def done(self) -> bool:
        return self._done.is_set()


class Sequence:
    """Scheduler-internal state of one generation request."""

    __slots__ = ("sid", "prompt", "max_new", "deadline_t", "priority",
                 "eos_id", "handle", "t_submit", "generated", "blocks",
                 "slot", "cached_len", "evictions", "ctx", "wblocks",
                 "wstart")

    def __init__(self, sid: int, prompt: List[int], max_new: int,
                 deadline_t: Optional[float], priority: int,
                 eos_id: Optional[int], handle: GenerationHandle):
        self.sid = sid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline_t = deadline_t
        self.priority = priority
        self.eos_id = eos_id
        self.handle = handle
        self.t_submit = time.monotonic()
        self.generated: List[int] = []
        self.blocks: List[int] = []
        #: a model with window layers: the window pool's blocks for this
        #: sequence's table entries wstart .. wstart + len(wblocks) - 1,
        #: the ones its window still reaches
        self.wblocks: List[int] = []
        self.wstart = 0
        self.slot: Optional[int] = None
        #: pool positions holding this sequence's K/V; the LAST generated
        #: token is never cached (it is the next step's input)
        self.cached_len = 0
        self.evictions = 0
        #: submitter's trace context (the HTTP ingress span) — the
        #: scheduler thread parents this sequence's prefill/evict/resume
        #: events under it (obs/trace.py)
        self.ctx = obs_trace.current_context() if obs_trace.enabled() \
            else None

    @property
    def tokens_so_far(self) -> List[int]:
        return self.prompt + self.generated

    @property
    def remaining(self) -> int:
        return self.max_new - len(self.generated)


class DecodeScheduler:
    """One model's generation scheduler: a submission queue drained by
    one scheduler thread that interleaves prefill admission with
    fixed-shape decode steps over the in-flight slot batch.

    model: DecodeModel-like — max_prompt_len, max_context, slots,
    block_size, eos_id, prefill(tokens) -> (last_logits, kv),
    seed_sequence(blocks, kv, skip_rows=) (and `slot=` where the model
    says it has `state_layers`), decode_step(tokens, lens,
    tables) -> a result, returned once the step is dispatched, whose
    `.tokens` are every slot's greedy token (host int32 [slots];
    DecodeModel chooses them on the device, keeps the logits there, and
    waits for the step when `.tokens` are first read); a slot whose
    token is `PREVIOUS_TOKEN` is fed the id the step dispatched before
    chose for it. Called once a step, positionally, through the
    attribute (the benchmark wraps it on the instance), `lens` a host
    array. Free capacity given by the injected pool.
    `kv` is opaque here: whatever prefill returned goes to seed_sequence
    untouched (DecodeModel's is device-resident).
    `last_logits` is anything `np.asarray` takes; DecodeModel's is a
    `PrefillRow` over a device array, fetched only after the seeding
    was dispatched (the fetch tells the model which dispatch was waited
    for: its `device_idle` accounting).
    """

    def __init__(self, model, pool: KVBlockPool,
                 admission: AdmissionController,
                 metrics: Optional[DecodeMetrics] = None, *,
                 continuous: bool = True, name: str = "model",
                 prefix_index=None, drafter=None, spec_k: int = 0,
                 window_pool: Optional[KVBlockPool] = None):
        self.model = model
        self.pool = pool
        #: the window layers' pool, and the rows such a layer reads back
        #: (None and 0 for a model without them)
        self.window_pool = window_pool
        self.window = int(model.window) if window_pool is not None else 0
        self.admission = admission
        self.metrics = metrics or DecodeMetrics(name)
        self.continuous = continuous
        self.name = name
        #: scheduler-thread-owned, like _waiting/_running
        self.index = prefix_index
        self.drafter = drafter
        self.spec_k = max(0, int(spec_k)) if drafter is not None else 0
        self._cv = threading.Condition()
        self._incoming: List[Sequence] = []
        self._waiting: List[Sequence] = []   # scheduler-thread-owned
        self._running: List[Sequence] = []   # scheduler-thread-owned
        self._load = 0                       # live sequences, any state
        #: scheduler-thread-owned: the step dispatched and not yet
        #: emitted (at most one), and the sequences that ended by EOS
        #: with a step they ride in still on the device: finished, and
        #: their blocks freed, when that step is emitted
        self._flight: Optional[_Flight] = None
        self._ended: List[tuple] = []
        #: the step's clock: the last emission (or the wake from idle)
        #: and the admissions' seconds since, both on time.monotonic()
        self._mark = time.monotonic()
        self._admit_s = 0.0
        self._next_sid = 0
        self._closed = False
        self._drained = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"pt-decode[{name}]")
        self._thread.start()

    # -- client side ---------------------------------------------------------
    def queued(self) -> int:
        with self._cv:
            return self._load

    def submit(self, prompt: Seq[int], max_new: int,
               deadline_ms: Optional[float] = None, priority: int = 0,
               eos_id: Optional[int] = None) -> GenerationHandle:
        """Admit one generation request. Typed admission errors raise
        HERE (reject-fast); later shedding surfaces on the handle."""
        deadline_t = self.admission.deadline_for(deadline_ms)
        handle = GenerationHandle(len(prompt))
        with self._cv:
            if self._closed:
                raise ModelUnavailable(
                    f"decode engine {self.name!r} is shut down")
            try:
                self.admission.admit(self._load, deadline_t,
                                     model=self.name)
            except DeadlineExceeded:
                self.metrics.on_shed("deadline")
                raise
            except Exception:
                self.metrics.on_shed("overload")
                raise
            seq = Sequence(self._next_sid, list(prompt), int(max_new),
                           deadline_t, int(priority),
                           eos_id if eos_id is not None
                           else self.model.eos_id, handle)
            self._next_sid += 1
            self._incoming.append(seq)
            self._load += 1
            self.metrics.on_received()
            self._cv.notify()
        return handle

    def while_idle(self, fn):
        """Run fn() under the scheduler lock with ZERO live sequences —
        submit() blocks on the same lock, so nothing can be admitted (and
        no decode step can start) while fn mutates pool state. Raises if
        any sequence is live in any state (incoming/waiting/running)."""
        with self._cv:
            if self._load:
                raise RuntimeError(
                    f"engine {self.name!r} has {self._load} live "
                    "sequence(s); idle-only maintenance refused")
            return fn()

    def close(self, drain: bool = True,
              timeout: Optional[float] = None) -> None:
        """drain=True generates every admitted sequence to completion
        first; drain=False fails the backlog fast."""
        with self._cv:
            self._closed = True
            self._drain_on_close = drain
            self._cv.notify()
        self._drained.wait(timeout)
        self._thread.join(timeout)

    # -- scheduler thread ----------------------------------------------------
    def _loop(self) -> None:
        self._drain_on_close = True
        try:
            while True:
                with self._cv:
                    while True:
                        if self._incoming:
                            self._waiting.extend(self._incoming)
                            self._incoming.clear()
                        if self._closed:
                            break
                        if self._waiting or self._running \
                                or self._flight is not None:
                            break
                        with self.metrics.timer.span("sched_idle"):
                            self._cv.wait()
                        self._mark = time.monotonic()
                    if self._closed and not self._drain_on_close:
                        self._fail_backlog()
                    if self._closed and not (self._waiting
                                             or self._running
                                             or self._flight is not None):
                        return
                # heavy work outside the lock: only this thread touches
                # _waiting/_running. One pass: collect the step in
                # flight first if an admission needs it, admit, then
                # dispatch the next step and emit the one before it
                if self._flight is not None and self._slot_opens():
                    self._collect("admission")
                self._shed_unmeetable()
                self._admit()
                self._step()
                self._publish_gauges()
        finally:
            self._drained.set()

    def dispatch_ahead(self) -> dict:
        """How far the loop runs ahead of the tokens it has read: the
        steps it keeps queued behind the one it waits for (1; 0 with a
        drafter, which proposes from host tokens), and what it drains
        for. Decided pass by pass from the loop's own state, no knob."""
        return {"depth": 0 if self.spec_k else 1,
                "drains_for": list(DRAIN_REASONS)}

    def _fail_backlog(self) -> None:
        # the step in flight is abandoned, its tokens never read; what
        # had ended by EOS before it ended whole
        self._flight = None
        for seq, reason in self._ended:
            self._finish(seq, reason)
        self._ended = []
        for seq in self._waiting + self._running:
            self._terminate(seq, error=ModelUnavailable(
                f"decode engine {self.name!r} shut down before "
                "completion"))
        self._waiting.clear()
        self._running.clear()

    def _publish_gauges(self) -> None:
        self.metrics.set_gauges(
            active=len(self._running), waiting=len(self._waiting),
            blocks_in_use=self.pool.blocks_in_use,
            blocks_capacity=self.pool.capacity,
            high_water=self.pool.high_water,
            blocks_shared=self.pool.blocks_shared,
            blocks_indexed=(self.index.blocks_indexed
                            if self.index is not None else 0))

    # -- terminal transitions ------------------------------------------------
    def _terminate(self, seq: Sequence, *, result: Optional[dict] = None,
                   error: Optional[BaseException] = None) -> None:
        """Free-on-finish: every block goes back to the pool, whatever
        the outcome."""
        if seq.blocks:
            self.pool.free(seq.blocks)
            seq.blocks = []
        self._free_window(seq)
        seq.slot = None
        with self._cv:
            self._load -= 1
        if error is not None:
            self.metrics.on_finished(False)
            seq.handle._fail(error)
        else:
            self.metrics.on_finished(True)
            seq.handle._finish(result)

    def _finish(self, seq: Sequence, reason: str) -> None:
        self._terminate(seq, result={
            "tokens": list(seq.generated), "finish_reason": reason,
            "evictions": seq.evictions, "prompt_len": len(seq.prompt)})

    def _finish_reason(self, seq: Sequence, tok: int) -> Optional[str]:
        if seq.eos_id is not None and tok == seq.eos_id:
            return "eos"
        if len(seq.generated) >= seq.max_new:
            return "length"
        return None

    # -- deadline shedding ---------------------------------------------------
    def _shed_unmeetable(self) -> None:
        """Expired deadlines always shed; un-expired ones shed when the
        remaining-token estimate (tokens left x EWMA step seconds) says
        the deadline cannot be met — the cold engine (no estimate yet)
        never sheds on a guess."""
        now = time.monotonic()
        est = self.admission.estimated_batch_s()
        for lst in (self._waiting, self._running):
            for seq in list(lst):
                if seq.deadline_t is None:
                    continue
                expired = now >= seq.deadline_t
                unmeetable = (est is not None and
                              now + seq.remaining * est > seq.deadline_t)
                if expired or unmeetable:
                    if lst is self._running and self._flight is not None:
                        # its step is on the device: that token is
                        # emitted first, as in lockstep, and may end it
                        self._collect("shed")
                        if seq not in lst:
                            continue
                    lst.remove(seq)
                    self.metrics.on_shed("deadline")
                    why = ("deadline expired" if expired else
                           f"~{seq.remaining} tokens x {est * 1000:.1f} "
                           "ms/step exceed the deadline")
                    self._terminate(seq, error=DeadlineExceeded(
                        f"sequence shed: {why} (model {self.name!r})"))

    # -- eviction ------------------------------------------------------------
    def _evict(self, victim: Sequence) -> None:
        """Preempt: free blocks+slot, requeue at the waiting FRONT. If
        its grown context can no longer re-prefill (past the largest
        bucket), shed instead — resuming would be impossible."""
        self._running.remove(victim)
        self.pool.free(victim.blocks)
        victim.blocks = []
        self._free_window(victim)
        victim.slot = None
        victim.cached_len = 0
        victim.evictions += 1
        self.metrics.on_evicted()
        obs_trace.instant("evict", cat="decode", parent=victim.ctx,
                          model=self.name, sid=victim.sid,
                          generated=len(victim.generated))
        if len(victim.tokens_so_far) > self.model.max_prompt_len:
            self.metrics.on_shed("overload")
            self._terminate(victim, error=Overloaded(
                f"evicted under KV-pool pressure and its context "
                f"({len(victim.tokens_so_far)} tokens) exceeds the "
                f"largest prefill bucket {self.model.max_prompt_len} — "
                "cannot resume (model {0!r})".format(self.name)))
        else:
            self._waiting.insert(0, victim)

    # -- window layers' blocks ----------------------------------------------
    def _free_window(self, seq: Sequence) -> None:
        if seq.wblocks:
            self.window_pool.free(seq.wblocks)
            seq.wblocks = []
        seq.wstart = 0

    def _hold_window(self, seq: Sequence, length: int) -> int:
        """Make `seq`'s window blocks those a sequence of `length` cached
        rows holds (`window_blocks`): the ones wholly behind the window
        go back to the pool FIRST, then the entries up to the newest
        row's are allocated. Returns the blocks released. The pool holds
        every slot's window at once, so the allocation cannot fail while
        no more sequences hold blocks than there are slots."""
        first, count = window_blocks(length, self.window,
                                     self.pool.block_size)
        drop = min(max(first - seq.wstart, 0), len(seq.wblocks))
        if drop:
            self.window_pool.free(seq.wblocks[:drop])
            del seq.wblocks[:drop]
        if not seq.wblocks:
            seq.wstart = first
        else:
            seq.wstart += drop
        need = first + count - (seq.wstart + len(seq.wblocks))
        if need > 0:
            seq.wblocks.extend(self.window_pool.alloc(need))
        return drop

    def _evict_for(self, seq: Sequence, need: int,
                   allow_peers: bool) -> bool:
        """Evict running sequences until `need` blocks are free. Victims
        must rank strictly below `seq` — lower priority, or (only when
        allow_peers, the mid-decode growth case, which guarantees the
        oldest sequence always progresses) same priority but younger."""

        def rank(s: Sequence):
            return (s.priority, -s.t_submit)   # low priority, young first

        while not self.pool.can_alloc(need):
            # cached prefixes go first: dropping an index reference costs
            # a future alias, evicting a running sequence costs a full
            # re-prefill — cache beats nothing, live work beats cache
            if self.index is not None and self.index.release_lru(1):
                continue
            victims = [s for s in self._running if s is not seq
                       and (s.priority < seq.priority
                            or (allow_peers
                                and s.priority == seq.priority
                                and s.t_submit > seq.t_submit))]
            if not victims:
                return False
            self._evict(min(victims, key=rank))
        return True

    # -- admission (prefill) -------------------------------------------------
    def _admit(self) -> None:
        # with a step in flight no slot is free for what waits (the pass
        # would have collected it first: `_slot_opens`)
        if not self._waiting or self._flight is not None:
            return
        if not self.continuous and self._running:
            return   # the static baseline: drain-to-empty barrier
        # priority first, then arrival order (evictees keep their
        # original t_submit, so they resume before younger peers)
        order = sorted(self._waiting, key=lambda s: (-s.priority,
                                                     s.t_submit))
        for seq in order:
            if len(self._running) >= self.model.slots:
                break
            try:
                with self.metrics.timer.span(
                        "admit", parent=seq.ctx, model=self.name,
                        sid=seq.sid,
                        tokens=len(seq.prompt) + len(seq.generated)) as sp:
                    if not self._admit_one(seq):
                        sp.cancel()   # still waiting: no admission
            except Exception as e:  # noqa: BLE001 — one bad sequence
                # must never kill the scheduler thread: fail IT typed
                # (its blocks free in _terminate) and keep admitting
                if seq in self._waiting:
                    self._waiting.remove(seq)
                self._terminate(seq, error=e if isinstance(
                    e, (Overloaded, DeadlineExceeded)) else
                    _request_failed(self.name, e))

    def _admit_one(self, seq: Sequence) -> bool:
        """Returns False when the sequence stays waiting (no capacity
        yet), True when it left the waiting list: running, finished or
        failed."""
        tokens = seq.tokens_so_far
        shared: List[int] = []
        matched = 0
        if self.index is not None:
            shared, matched = self.index.match(tokens)
        if shared:
            # alias the resident prefix: take OUR reference per block AT
            # MATCH TIME — under pressure _evict_for drops index
            # references (release_lru), possibly on these very blocks,
            # and only this pin keeps them (and the `need` arithmetic
            # below) live until admission resolves
            self.pool.share(shared)
            seq.blocks = list(shared)
        need = self.pool.blocks_for_tokens(len(tokens)) - len(shared)
        if not self.pool.can_alloc(need) and \
                not self._evict_for(seq, need, allow_peers=False):
            if shared:
                self.pool.free(shared)   # unpin the aliased prefix
                seq.blocks = []
            return False   # stays waiting; capacity frees as others end
        self._waiting.remove(seq)
        if seq.handle.t_admit is None:
            seq.handle.t_admit = time.perf_counter()
            self.metrics.on_admitted(seq.handle.t_admit
                                     - seq.handle.t_submit)
        if seq.evictions:
            self.metrics.on_resumed()
            obs_trace.instant("resume", cat="decode", parent=seq.ctx,
                              model=self.name, sid=seq.sid)
        if shared:
            # write NOTHING below `matched` — those rows are, byte for
            # byte, what this prompt's prefill would write
            self.metrics.on_prefix_hit(matched, len(shared))
            obs_trace.instant("prefix_hit", cat="decode",
                              parent=seq.ctx, model=self.name,
                              sid=seq.sid, tokens=matched)
        if need:
            seq.blocks = seq.blocks + self.pool.alloc(need)
        t0 = time.monotonic()
        try:
            seeding = {}
            # the slot it will decode in, known before the seeding: a
            # model with state layers writes the sequence's state there
            slot = next(i for i in range(self.model.slots)
                        if all(r.slot != i for r in self._running))
            if getattr(self.model, "state_layers", 0) \
                    or getattr(self.model, "slot_rows", False):
                seeding["slot"] = slot
            if self.window:
                # the prompt's last window, and no block behind it
                self._hold_window(seq, len(tokens))
                seeding["window_ids"] = seq.wblocks
            # nothing waits on the device between the two dispatches;
            # the admission's one wait is the logits row, behind both
            last_logits, kv = self.model.prefill(tokens)
            self.model.seed_sequence(seq.blocks, kv, skip_rows=matched,
                                     **seeding)
            with self.metrics.timer.span("prefill_fetch"):
                last_logits = np.asarray(last_logits)
            self.metrics.on_prefill_host_bytes(last_logits.nbytes)
        except Exception as e:  # noqa: BLE001 — typed + delivered
            self._terminate(seq, error=e if isinstance(
                e, (Overloaded, DeadlineExceeded)) else
                _request_failed(self.name, e))
            return True
        dt = time.monotonic() - t0
        self._admit_s += dt     # not the next step's time (`_emit`)
        self.metrics.on_prefill(len(tokens), dt)
        seq.cached_len = len(tokens)
        if self.index is not None:
            # register this sequence's full prompt blocks (decode
            # writes land strictly past the prompt, so they stay
            # immutable while indexed)
            self.index.insert(tokens, seq.blocks)
        tok = int(np.argmax(last_logits))
        seq.generated.append(tok)
        seq.handle._put_token(tok)
        reason = self._finish_reason(seq, tok)
        if reason is not None:
            self._finish(seq, reason)
            return True
        seq.slot = slot
        self._running.append(seq)
        return True

    # -- copy-on-write -------------------------------------------------------
    def _cow_for_write(self, seq: Sequence, at: int) -> bool:
        """Make the block holding this step's first write position
        (`at`: the rows cached when it runs) exclusively `seq`'s. Only
        an aliased PARTIAL tail block can be hit — every block past the
        prompt was freshly allocated — so at most ONE copy per sequence
        lifetime. Returns
        False when the sequence had to be preempted for the copy target
        (pool exhausted with no lower-ranked victim): a shared block is
        NEVER written in place."""
        bi = at // self.pool.block_size
        if bi >= len(seq.blocks):
            return True   # the write lands in a to-be-allocated block
        old = seq.blocks[bi]
        if self.pool.refcount(old) <= 1:
            return True   # exclusively owned already
        if not self.pool.can_alloc(1) and \
                not self._evict_for(seq, 1, allow_peers=True):
            self._evict(seq)
            return False
        new = self.pool.alloc(1)[0]
        self.model.copy_block(old, new)
        self.pool.free([old])   # drop OUR reference; other owners keep it
        seq.blocks[bi] = new
        self.metrics.on_cow()
        obs_trace.instant("cow", cat="decode", parent=seq.ctx,
                          model=self.name, sid=seq.sid, block=old)
        return True

    # -- speculation ---------------------------------------------------------
    def _gather_drafts(self, budget: int) -> Dict[int, List[int]]:
        """Ask the drafter for up to spec_k tokens per running sequence,
        bounded by idle slots, the generation budget, and the context
        limit. A drafter crash (chaos site spec_verify) falls back to
        plain decode for that sequence's step — never kills it."""
        out: Dict[int, List[int]] = {}
        for seq in sorted(self._running,
                          key=lambda s: (-s.priority, s.t_submit)):
            if budget <= 0:
                break
            k = min(self.spec_k, budget, seq.remaining - 1,
                    self.model.max_context - seq.cached_len - 1,
                    (self.model.max_blocks_per_seq
                     * self.pool.block_size) - seq.cached_len - 1)
            if k < 1:
                continue
            try:
                faults.crash_point("spec_verify")
                proposed = self.drafter.propose(seq.tokens_so_far, k)
            except Exception:   # noqa: BLE001 — degrade, don't die
                self.metrics.on_spec_fallback()
                obs_trace.instant("spec_fallback", cat="decode",
                                  parent=seq.ctx, model=self.name,
                                  sid=seq.sid)
                continue
            drafts: List[int] = []
            for t in list(proposed)[:k]:
                t = int(t)
                if not 0 <= t < self.model.vocab_size:
                    break   # truncate, don't filter: a chain has no holes
                drafts.append(t)
            if drafts:
                out[seq.sid] = drafts
                budget -= len(drafts)
        return out

    # -- one decode step -----------------------------------------------------
    def _ends_in_flight(self, seq: Sequence) -> bool:
        """With a step in flight: does its token end `seq` by length?
        Known at its dispatch, without the token."""
        return len(seq.generated) + 1 >= seq.max_new

    def _goes_on(self, seq: Sequence, ahead) -> bool:
        """Does `seq` take part in the next step to dispatch (`ahead`:
        a step is in flight)?"""
        return not (ahead and self._ends_in_flight(seq))

    def _slot_opens(self) -> bool:
        """With a step in flight: could `_admit` place a waiting
        sequence once it is emitted? Then this pass collects it first:
        an admission waits on the device itself, and its first token is
        chosen on the host."""
        if not self._waiting:
            return False
        going = sum(not self._ends_in_flight(s) for s in self._running)
        if not self.continuous:
            return going == 0
        return going < self.model.slots

    def _growth_fits(self) -> bool:
        """With a step in flight: does the pool cover the blocks the
        step behind it takes (a row each, and the copy of a shared tail
        block)? If not, growth would evict, preempt or hunt a copy
        target, and a resume re-prefills `tokens_so_far`, which must
        hold the token in flight: the pass collects it first."""
        need = 0
        bs = self.pool.block_size
        for seq in self._running:
            if self._ends_in_flight(seq):
                continue
            at = seq.cached_len + 1
            need += max(self.pool.blocks_for_tokens(at + 1)
                        - len(seq.blocks), 0)
            if at // bs < len(seq.blocks) and \
                    self.pool.refcount(seq.blocks[at // bs]) > 1:
                need += 1
        return self.pool.can_alloc(need)

    def _step(self) -> None:
        """Dispatch the next step, then emit the one dispatched before
        it (its wait, its fetch, its tokens), which meanwhile had the
        new one queued behind it on the device."""
        timer = self.metrics.timer
        if self._flight is not None and not self._growth_fits():
            self._collect("eviction")
        ahead = self._flight is not None
        new = None
        if any(self._goes_on(s, ahead) for s in self._running):
            # one fixed-shape dispatch serving every running sequence:
            # with PT_TRACE on, step_prep records which sids share it (a
            # single-sequence step adopts that sequence's trace)
            with timer.span("step_prep",
                            parent=(self._running[0].ctx
                                    if len(self._running) == 1 else None),
                            model=self.name) as sp:
                plan = self._prepare_step(int(ahead))
                if plan is not None:
                    active, drafts, spec_slots, feeds = plan
                    if sp.kept():
                        sp.annotate(n=len(active),
                                    sids=[s.sid for s in active])
            if plan is not None:
                result = self.model.decode_step(*feeds)
                # the routing counters as of THIS step, before a later
                # dispatch replaces them: they travel with the step
                probe = self.metrics.moe_probe
                new = _Flight(active, drafts, spec_slots, feeds, result,
                              None if probe is None else probe(), ahead)
        before, self._flight = self._flight, new
        if before is not None:
            if new is None:
                self.metrics.on_drain("tail")
            self._emit(before)
        if new is not None and self.spec_k:
            self._collect("drafter")

    def _collect(self, reason: str) -> None:
        """Drain: emit the step in flight with nothing queued behind
        it. Costs one un-overlapped cycle, the lockstep loop's cost."""
        flight, self._flight = self._flight, None
        self.metrics.on_drain(reason)
        self._emit(flight)

    def _emit(self, flight: _Flight) -> None:
        chosen = flight.result.tokens    # the step's wait and its fetch
        with self.metrics.timer.span("step_emit"):
            # what the paged kernel had to read this step, a layer, and
            # what its compute blocks of P pages cover: from the feed's
            # own context lengths, on the host already
            pages = -(-flight.feeds[1] // self.model.block_size)
            per_block = self.model.paged_block_pages
            self.metrics.on_paged_pages(
                int(pages.sum()),
                int((-(-pages // per_block)).sum()) * per_block)
            # what this step added to the loop's time: emission to
            # emission, the admissions between taken out (they are
            # `prefill_s`), so that the two add to the busy wall time
            # whatever was queued behind what
            now = time.monotonic()
            dt = max(now - self._mark - self._admit_s, 0.0)
            self._mark, self._admit_s = now, 0.0
            self.admission.observe_batch(dt)
            self._emit_step(flight, chosen.tolist(), dt)

    def _prepare_step(self, ahead: int = 0):
        """Drafts, block growth, slot packing and the three feed arrays
        of one step. `ahead` is 1 when the step before it is still in
        flight: the host's lists then lack that step's row and token, so
        every length here is `cached_len + ahead` (the rows cached when
        THIS step runs; its new row is that position), a sequence the
        step in flight ends by length takes no part, and every other is
        fed `PREVIOUS_TOKEN`. Returns (active sequences, drafts, spec
        slots, (tokens, lens, tables), with the window layers' table
        behind for a model that has them), or None when every running
        sequence was preempted on the way."""
        slots = self.model.slots
        going = [s for s in self._running if self._goes_on(s, ahead)]
        drafts: Dict[int, List[int]] = {}
        if self.drafter is not None and self.spec_k > 0:
            drafts = self._gather_drafts(slots - len(self._running))
        # grow block capacity in priority order so the important
        # sequences claim blocks (and pick victims) first
        released = 0
        for seq in sorted(going, key=lambda s: (-s.priority, s.t_submit)):
            if seq.slot is None:
                drafts.pop(seq.sid, None)
                continue   # evicted by a higher-priority peer this pass
            at = seq.cached_len + ahead
            if not self._cow_for_write(seq, at):
                drafts.pop(seq.sid, None)
                continue   # preempted hunting a copy target
            if self.window:
                # the step's new row is position `at`: what falls behind
                # ITS window is released before its block is taken (with
                # a step in flight, that one's table still names the
                # released block: it was dispatched first and reads it
                # before any later dispatch writes it)
                released += self._hold_window(seq, at + 1)
            # provision the FULL draft window up front — acceptance is
            # variable but the pool must cover the maximum
            g = 1 + len(drafts.get(seq.sid, ()))
            need = (self.pool.blocks_for_tokens(at + g)
                    - len(seq.blocks))
            if need > 0 and g > 1 and not self.pool.can_alloc(need):
                # speculation never evicts a peer: drop the drafts and
                # retry as a plain one-token step
                drafts.pop(seq.sid, None)
                need = (self.pool.blocks_for_tokens(at + 1)
                        - len(seq.blocks))
            if need <= 0:
                continue
            if not self.pool.can_alloc(need) and \
                    not self._evict_for(seq, need, allow_peers=True):
                # no victims rank below it and the pool is dry: preempt
                # ITSELF — resume when capacity frees. Progress is
                # guaranteed: the oldest highest-priority sequence always
                # either allocates or finds victims, so the pool drains
                # toward completion rather than thrashing. (A sequence
                # that can never fit at all was already shed typed at
                # submit by the engine's peak-residency check.)
                drafts.pop(seq.sid, None)
                self._evict(seq)
                continue
            seq.blocks.extend(self.pool.alloc(need))
        if self.window:
            self.metrics.on_window_blocks(released,
                                          self.window_pool.blocks_in_use)
        active = [s for s in going if s.slot is not None]
        if not active:
            return None
        # slot packing: each drafted sequence borrows idle slots — slot
        # j of its chain feeds draft j with context_len L+1+j over the
        # SAME block table, so the step's kv-write phase lays down the
        # whole chain's rows before its attention phase reads them
        free_ids = [i for i in range(slots)
                    if all(r.slot != i for r in active)]
        spec_slots: Dict[int, List[int]] = {}
        for seq in active:
            d = drafts.get(seq.sid)
            if not d:
                continue
            take = free_ids[:len(d)]
            if len(take) < len(d):
                drafts[seq.sid] = d = d[:len(take)]
            if not d:
                drafts.pop(seq.sid, None)
                continue
            spec_slots[seq.sid] = take
            free_ids = free_ids[len(take):]
        tokens = np.zeros(slots, np.int64)
        lens = np.zeros(slots, np.int32)
        tables = np.zeros((slots, self.model.max_blocks_per_seq), np.int32)
        wtables = np.zeros_like(tables) if self.window else None
        for seq in active:
            row = block_table_row(seq.blocks,
                                  self.model.max_blocks_per_seq)
            tokens[seq.slot] = PREVIOUS_TOKEN if ahead \
                else seq.generated[-1]
            lens[seq.slot] = seq.cached_len + ahead + 1
            tables[seq.slot] = row
            if self.window:     # entries behind the window stay null
                wtables[seq.slot, seq.wstart:seq.wstart
                        + len(seq.wblocks)] = seq.wblocks
            for j, (sl, d) in enumerate(zip(spec_slots.get(seq.sid, ()),
                                            drafts.get(seq.sid, ())),
                                        start=1):
                tokens[sl] = d
                lens[sl] = seq.cached_len + 1 + j
                tables[sl] = row
        feeds = (tokens, lens, tables) + ((wtables,) if self.window else ())
        return active, drafts, spec_slots, feeds

    def _emit_step(self, flight: _Flight, chosen: List[int],
                   dt: float) -> None:
        """Greedy acceptance, token emission and finishes of one step
        whose chosen tokens, one a slot, are on the host. A sequence
        that ended by EOS with the step before (`_ended`: the host could
        not know when this one was dispatched) rode in this step too:
        its token here is dropped, and now that no step on the device
        names its blocks it is finished."""
        active, drafts, spec_slots = flight[:3]
        behind = self._flight.active if self._flight is not None else ()
        ended, self._ended = self._ended, []
        used = sum(len(v) for v in spec_slots.values())
        emitted_total = 0
        for seq in active:
            if seq.slot is None:
                continue     # one of `ended`: computed for nobody
            used += 1
            d = drafts.get(seq.sid, [])
            if d:
                chain = accept_greedy(
                    d, [chosen[seq.slot]]
                    + [chosen[sl] for sl in spec_slots[seq.sid]])
                self.metrics.on_spec(len(d), len(chain) - 1)
            else:
                chain = [chosen[seq.slot]]
            reason = None
            advanced = 0
            for tok in chain:
                seq.generated.append(tok)
                seq.handle._put_token(tok)
                advanced += 1
                reason = self._finish_reason(seq, tok)
                if reason is not None:
                    break
            # every emitted token's K/V row is now resident (the LAST
            # one stays the next step's input, exactly as in plain
            # decode); rejected draft rows sit past cached_len, masked,
            # and are rewritten before the mask ever reaches them
            seq.cached_len += advanced
            emitted_total += advanced
            if reason is not None:
                self._running.remove(seq)
                if seq in behind:
                    # it rides in the step queued behind this one
                    seq.slot = None
                    self._ended.append((seq, reason))
                else:
                    self._finish(seq, reason)
        for seq, reason in ended:
            self._finish(seq, reason)
        self.metrics.on_step(used, self.model.slots, dt, emitted_total,
                             flight.moe_ref, ahead=flight.ahead,
                             overrun=len(ended))


def _request_failed(name: str, cause: BaseException):
    from ..admission import RequestFailed
    return RequestFailed(
        f"decode engine {name!r} failed running prefill: {cause}",
        cause=cause)
