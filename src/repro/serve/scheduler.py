"""Continuous-batching serving engine with chunked prefill.

A fixed pool of B cache slots; requests are admitted into free slots as
they complete (vLLM-style iteration-level scheduling).  Every engine
iteration schedules a *mixed* batch of work:

* decode slots consume exactly one token (the previous output token);
* prefill slots consume up to ``chunk_size`` prompt tokens, written to
  the KV cache at the slot's absolute positions in a single
  ``prefill_chunk`` call — a 512-token prompt costs ~512/chunk_size
  engine steps to first token instead of 512.

Scheduling runs under a **per-step token budget** with a deadline-drop
policy, the serving analogue of DropCompute's Algorithm 1: the budget is
the compute threshold ``tau``, scheduled tokens are the micro-batches,
and prefill chunks past the threshold are *deferred to the next
iteration* rather than stalling every decode slot behind one long
prompt.  Two guarantees mirror the paper's semantics:

* decode slots are always scheduled (synchronous progress is preserved;
  only prefill becomes stochastic across iterations), and
* at least one prefill token is scheduled whenever prefill work is
  waiting (the analogue of ``min_microbatches=1`` — no starvation).

Shape stability: the dense mode compiles at most two programs per
session — a (B, chunk_size) mixed step and a (B, 1) decode-only step —
because the budget only changes the *contents* of the per-slot length
vector, never tensor shapes.  The packed mode compiles exactly one, at
the packed capacity (``packing.packed_capacity``).  Speculative decoding
(``spec=``) keeps the two-program story: decode steps widen to
(B, k + 1) verify grants and the mixed width becomes
``max(chunk_size, k + 1)`` — still fixed per engine configuration.

A consequence worth being precise about: per-step wall time is bounded
by the fixed cost of those two compiled programs, and the budget bounds
*scheduled tokens* (admission of new prefill work per iteration), which
is what spreads a long prompt across iterations so decode slots emit on
every one of them.  In the dense mode a mixed step computes the full
(B, chunk_size) shape regardless of how many tokens were granted;
``packed=True`` switches to the token-packed step program (vLLM-style
flattened batch, ``serve.packing`` + ``models.model.packed_prefill``)
whose compiled shape is the packed capacity — granted tokens alone
determine the compute, so the budget bounds actual per-step compute, not
just scheduled-token accounting.  Scheduling, deferral, and accounting
are shared between the two modes; the dense mode is the oracle the
packed parity suite (``tests/test_serve_packed.py``) compares against.

Decode is sampled per request (``Request.sampling`` — temperature /
top-k / top-p / seed; ``serve.sampling``): the step's logits feed a
jitted sampler instead of a bare argmax, with per-token PRNG keys
derived from (request seed, output index) so seeded streams replay
across restarts, step paths, and speculation.  The default params are
greedy and byte-identical to argmax decode.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ModelConfig
from ..models.model import (
    UnsupportedPatternError,
    init_decode_cache,
    packed_prefill,
    prefill_chunk,
    require_chunkable,
)
from . import packing
from .kv import KVCache, KVCacheSpec, reset_recurrent_state
from .sampling import SamplingParams, sample_tokens
from .spec import Proposer, SpecConfig, accept_sampled

PyTree = object


class UnsupportedDistError(NotImplementedError):
    """A serving mode was combined with a ``Distribution`` it cannot run
    under yet.  ``packed=True`` and ``cache="paged"`` both address KV by
    per-token indirection (slot gather / block tables) that would cross
    the sharded slot axis every step — making that gather mesh-aware is
    the ROADMAP "multi-host serving mesh" item.  Subclasses
    ``NotImplementedError`` so pre-existing handlers keep working."""


@functools.partial(jax.jit, static_argnames=("cfg", "moe_impl"))
def _engine_step(params, cfg: ModelConfig, cache, tokens, pos, lens,
                 moe_impl: str = "dense"):
    """Module-level jitted step: compilations are shared across engines
    with the same (cfg, shapes) — engine construction stays cheap.
    Returns ``(logits, cache, aux)``; ``aux["expert_overflow"]`` counts
    tokens the capacity-factor MoE router dropped this step (zero for
    dense dispatch and for MoE-free configs)."""
    return prefill_chunk(
        params, cfg, cache, tokens, pos, lens,
        moe_impl=moe_impl, return_aux=True,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "moe_impl"))
def _packed_engine_step(params, cfg: ModelConfig, cache, tokens, slot_ids, pos,
                        moe_impl: str = "dense"):
    """Token-packed step: one (capacity,) program per engine config."""
    return packed_prefill(
        params, cfg, cache, tokens, slot_ids, pos,
        moe_impl=moe_impl, return_aux=True,
    )


class AdmissionError(RuntimeError):
    """Raised by ``submit`` when the engine's wait queue is full."""


class InvalidRequestError(ValueError):
    """A request the engine can never serve correctly.

    Raised (never ``assert``-ed — asserts vanish under ``python -O``, and
    an admitted over-long request's out-of-range scatter writes are
    silently dropped, i.e. wrong tokens served) for: prompts longer than
    the slot can hold, empty prompts (decode would index
    ``prompt[-1]`` mid-step), and ``max_new_tokens < 1``.
    """


class EngineStateError(RuntimeError):
    """An engine lifecycle operation was called in the wrong state (e.g.
    ``reset_stats`` while requests are still in flight).  Raised, not
    ``assert``-ed, so the guard survives ``python -O``."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    #: per-request stochastic-decode knobs (``serve.sampling``); the
    #: default is greedy argmax — byte-identical to the pre-sampling
    #: engine.  Output token ``i`` is sampled with
    #: ``fold_in(PRNGKey(sampling.seed), i)`` regardless of step path
    #: (dense/packed/paged) or speculation, so seeded streams replay.
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    output: List[int] = dataclasses.field(default_factory=list)
    #: the engine finished this request short of ``max_new_tokens``
    #: (its slot ran out of cache positions) — surfaced instead of
    #: silently serving a truncated stream
    truncated: bool = False
    #: aborted via ``ContinuousBatcher.cancel`` before finishing
    cancelled: bool = False
    # --- latency accounting (filled in by the engine) ---
    #: ``submit`` stamps this only when unset, so a front-end that held
    #: the request in its own waiting room can pre-stamp the *original*
    #: arrival time and TTFT keeps measuring from the user-visible submit
    submitted_at: Optional[float] = None
    admitted_at: Optional[float] = None  # wall time the request got a slot
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    admitted_step: Optional[int] = None  # engine step the request got a slot
    first_token_step: Optional[int] = None

    @property
    def done(self) -> bool:
        return len(self.output) >= self.max_new_tokens

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (seconds), submit -> first output token.
        Includes queue wait: the clock starts when the request entered
        the system, not when a slot freed up."""
        if self.submitted_at is None or self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def queue_wait(self) -> Optional[float]:
        """Seconds spent waiting for a cache slot (submit -> admission)."""
        if self.submitted_at is None or self.admitted_at is None:
            return None
        return self.admitted_at - self.submitted_at

    @property
    def admitted_ttft(self) -> Optional[float]:
        """Seconds from slot admission to first output token — the
        prefill-side half of ``ttft`` (``ttft = queue_wait + this``)."""
        if self.admitted_at is None or self.first_token_at is None:
            return None
        return self.first_token_at - self.admitted_at

    @property
    def ttft_steps(self) -> Optional[int]:
        """Engine iterations from slot admission to first output token."""
        if self.admitted_step is None or self.first_token_step is None:
            return None
        return self.first_token_step - self.admitted_step + 1


@dataclasses.dataclass
class StepStats:
    """Per-iteration scheduling record (compute accounting for the budget)."""

    step: int
    decode_tokens: int  # decode slots fed (1 baseline token each)
    prefill_tokens: int  # prompt tokens consumed this step
    deferred_tokens: int  # prompt tokens pushed past the deadline
    wall_time: float  # host-measured step duration (seconds)
    shared_tokens: int = 0  # prompt tokens covered by prefix-cache pages
    used_pages: int = 0  # paged layout: pages referenced after this step
    draft_tokens: int = 0  # speculative draft tokens verified this step
    accepted_tokens: int = 0  # drafts the target model accepted
    queued_requests: int = 0  # requests waiting for a slot at step start
    #: scheduled tokens past ``token_budget`` this step.  The budget is a
    #: deferral threshold, not a hard cap: decode baselines are
    #: unconditional and the prefill starvation guard grants one token
    #: past an exhausted budget (see ``_schedule``), so a full decode
    #: batch under a tiny budget overshoots by design.  This field makes
    #: that overshoot explicit instead of letting BENCH records present
    #: tau as absolute.  Always 0 with no budget.
    budget_overshoot: int = 0
    #: routed (token, expert) assignments dropped to the residual path
    #: by the capacity-factor MoE dispatch this step — the per-expert
    #: mirror of ``budget_overshoot``: capacity is a static per-expert
    #: tau, and this is the work it deferred (here, *dropped*: MoE
    #: layers have a residual, so a dropped token still flows — it just
    #: skips the expert FFN).  Always 0 for dense dispatch and
    #: MoE-free configs.
    expert_overflow: int = 0

    @property
    def scheduled_tokens(self) -> int:
        return self.decode_tokens + self.draft_tokens + self.prefill_tokens


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0  # next absolute position to write

    @property
    def free(self) -> bool:
        return self.req is None

    @property
    def prefilling(self) -> bool:
        return self.req is not None and self.pos < len(self.req.prompt)


class ContinuousBatcher:
    """Engine: admit / step / drain.

    Args:
      params, cfg: model (attention-only patterns; see ``prefill_chunk``).
      batch_slots: cache slots B (max concurrent requests).
      max_len: per-slot cache length (prompt + generated tokens).
      chunk_size: max prompt tokens one slot consumes per step.
      token_budget: per-step compute cap in scheduled tokens — the serving
        ``tau``.  Decode slots always run; prefill fills the remainder and
        overflow chunks are deferred.  None = uncapped (schedule a full
        chunk for every prefilling slot).
      max_queue: admission control — ``submit`` raises ``AdmissionError``
        once this many requests are waiting for a slot.  None = unbounded.
      packed: run the token-packed step program instead of the dense
        (B, chunk_size) one.  The compiled shape is the packed capacity
        (``packing.packed_capacity``), so granted tokens alone determine
        per-step compute and the budget becomes a real compute bound.
        Scheduling and outputs are identical to the dense mode.
      cache: KV-cache layout — "dense" (one worst-case ``(max_len,)`` row
        per slot; the parity oracle), "paged" (page pool + block tables +
        prefix sharing; see ``repro.serve.kv``), or a ``KVCacheSpec``.
        Paged engines admit a request only when the page pool can cover
        its worst case (prompt + max_new, minus shareable prefix pages),
        map prefix-cache pages instead of re-prefilling shared prompt
        prefixes, and free pages on completion (retaining them for
        prefix reuse until the pool needs them back).
      page_size / num_pages: paged-layout knobs (tokens per page; pool
        size, default worst-case ``batch_slots * blocks_per_slot``).
      kv_dtype: paged pool element dtype (``KVCacheSpec.kv_dtype``).
        None = the compute dtype (bit-identical to dense); "int8" =
        quantized pages with per-row scales, ~half the bytes per page so
        the same HBM admits ~2x the pages (outputs are allclose to the
        oracle, not bit-identical).  Ignored when ``cache`` is already a
        ``KVCacheSpec``.
      spec: speculative decoding — a ``repro.serve.spec.SpecConfig`` (or a
        bare ``Proposer``, wrapped with the default ``k``).  Decode slots
        then verify up to ``k`` proposed tokens per step in one chunked
        verify grant (chunked prefill at the slot's absolute positions —
        the contract ``models.model.verify_step`` documents; the engine's
        one jitted step program serves prefill, decode, and verify
        grants alike), keep the draft prefix matching the target's
        per-column *sampled* tokens plus a bonus token
        (rejection-sampling acceptance — ``spec.accept_sampled``; the
        argmax prefix match when the request is greedy), and roll
        rejected KV back (position-mask trim for dense,
        ``KVCache.trim_slot`` for paged).
        Draft tokens are scheduled under ``token_budget`` with lower
        priority than decode baselines and higher than prefill chunks.
        Output streams are token-identical to the non-speculative
        engine — greedy or seeded-sampled alike — by construction.
      dist: optional ``repro.dist.Distribution`` — shards the decode cache
        (slots over the data axes, KV heads over "model") and the params
        by the path-based rules; the jitted engine step then partitions
        from the committed input shardings.  None = local placement.
      capacity_factor: MoE serving dispatch — when set (requires
        ``cfg.n_experts > 0``), expert FFNs run over fixed per-expert
        buffers of ``ceil(cf * tokens * top_k / n_experts)`` slots
        (``models.moe.apply_moe_capacity``) instead of the dense
        every-token-through-every-expert matmul.  Tokens past an
        expert's capacity are *dropped to the residual path* — the
        per-expert analogue of the token-budget ``tau``: a static
        compute bound enforced by deferrable-work dropping, reported
        per step as ``StepStats.expert_overflow`` (the per-expert
        mirror of ``budget_overshoot``).  ``float('inf')`` never drops
        and is byte-identical to dense dispatch; ``None`` (default)
        keeps the dense path.

    Recurrent patterns ('R'/'M' layers) serve through the same engine
    with two carve-outs, both rooted in the carried state being an
    in-place value rather than an append-only log: speculative decoding
    is refused at construction (rejected drafts cannot roll back state
    the scan already consumed), and paged prefix sharing is disabled
    (skipping shared prompt tokens would skip their recurrent-state
    updates — attention pages can be mapped, recurrent state cannot).
    """

    def __init__(
        self,
        params: PyTree,
        cfg: ModelConfig,
        batch_slots: int,
        max_len: int,
        chunk_size: int = 16,
        token_budget: Optional[int] = None,
        max_queue: Optional[int] = None,
        packed: bool = False,
        cache: "str | KVCacheSpec" = "dense",
        page_size: int = 16,
        num_pages: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        spec: "Optional[SpecConfig | Proposer]" = None,
        dist=None,
        capacity_factor: Optional[float] = None,
    ):
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        if isinstance(spec, Proposer):
            spec = SpecConfig(proposer=spec)
        self.spec = spec
        if spec is not None:
            spec.proposer.bind_engine(batch_slots, max_len)
        # fail at construction, not on the first step mid-trace
        require_chunkable(cfg, "ContinuousBatcher")
        self.recurrent = bool(set(cfg.pattern) & {"R", "M"})
        if self.recurrent and spec is not None:
            # raised here, not on the first rejected draft: trim_slot
            # would refuse mid-serve, stranding every in-flight request
            raise UnsupportedPatternError(
                "speculative decoding needs KV rollback of rejected "
                "drafts; recurrent state ('R'/'M' layers) has already "
                "consumed them and cannot roll back (see "
                "KVCache.trim_slot)"
            )
        if capacity_factor is not None:
            if cfg.n_experts <= 0:
                raise ValueError(
                    "capacity_factor is an MoE dispatch knob but the "
                    f"config has n_experts={cfg.n_experts}"
                )
            if capacity_factor <= 0:
                raise ValueError(
                    f"capacity_factor must be > 0, got {capacity_factor}"
                )
            # cfg is the jitted step's static arg: bake the factor in so
            # the compiled program's expert buffers are sized once
            cfg = dataclasses.replace(
                cfg, capacity_factor=float(capacity_factor)
            )
        self.moe_impl = "capacity" if capacity_factor is not None else "dense"
        if isinstance(cache, KVCacheSpec):
            kv_spec = cache
            # raised, not assert-ed: under python -O a mismatched spec
            # would serve silently-wrong tokens (too-few block tables /
            # scatter-dropped writes past the logical buffer)
            if kv_spec.num_slots != batch_slots or kv_spec.max_len != max_len:
                raise ValueError(
                    f"KVCacheSpec(num_slots={kv_spec.num_slots}, "
                    f"max_len={kv_spec.max_len}) disagrees with the engine's "
                    f"batch_slots={batch_slots}, max_len={max_len}"
                )
        else:
            kv_spec = KVCacheSpec(
                num_slots=batch_slots, max_len=max_len, layout=cache,
                page_size=page_size, num_pages=num_pages, kv_dtype=kv_dtype,
            )
        if packed and dist is not None:
            raise UnsupportedDistError(
                "packed=True with a Distribution is not supported yet: the "
                "per-token slot gather would cross the sharded slot axis "
                "every step (the ROADMAP multi-host serving-mesh item)"
            )
        if kv_spec.layout == "paged" and dist is not None:
            raise UnsupportedDistError(
                "cache='paged' with a Distribution is not supported yet: "
                "the block-table page gather would cross the sharded page "
                "pool every step (the ROADMAP multi-host serving-mesh item)"
            )
        self.packed = packed
        self.packed_capacity = (
            packing.packed_capacity(
                batch_slots, chunk_size, token_budget,
                draft_k=self.spec.k if self.spec is not None else 0,
            )
            if packed else None
        )
        # Second, smaller packed program for pure-decode steps (every
        # grant a single token, no drafts): capacity = batch_slots, so a
        # decode step's FFN/unembed run over B rows instead of the mixed
        # program's budget-sized capacity — the same two-program design
        # as the dense engine's (B, chunk) + (B, 1) pair.
        self.packed_decode_capacity = batch_slots if packed else None
        self.dist = dist
        if dist is not None:
            params = dist.shard(params)
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.chunk_size = chunk_size
        self.token_budget = token_budget
        self.max_queue = max_queue
        self.slots = [_Slot() for _ in range(batch_slots)]
        self.kv: Optional[KVCache] = None
        if kv_spec.layout == "paged":
            self.kv = kv_spec.build(params, cfg)
            self.cache = self.kv.state
        else:
            build = functools.partial(
                init_decode_cache, params, cfg, batch_slots, max_len, linear=True
            )
            if dist is None:
                self.cache = build()
            else:
                # materialize directly into the sharded layout — building the
                # full cache on one device first would peak at the unsharded
                # size, the very thing sharding is for
                c_sh = dist.cache_shardings(jax.eval_shape(build))
                self.cache = jax.jit(build, out_shardings=c_sh)()
        self.queue: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self.cancelled: Dict[int, Request] = {}
        self.steps = 0
        self.step_stats: List[StepStats] = []
        self._shared_step = 0
        self._overflow_step = 0
        self._step_callbacks: List = []

    # ------------------------------------------------------------------
    def add_step_callback(self, fn) -> None:
        """Register ``fn(stats: StepStats)`` to run at the end of every
        engine iteration, after the step's outputs and accounting have
        been committed.  The async front-end uses this to observe the
        step timeline; callbacks run on whatever thread drives ``step``
        and must not mutate engine state."""
        self._step_callbacks.append(fn)

    def validate_request(self, req: Request) -> None:
        """Reject a request the engine can never serve — without
        queueing it.  Raises ``InvalidRequestError`` for malformed
        requests and ``AdmissionError`` for ones the paged pool can
        never hold; the front-end calls this at its own submit time so
        a doomed request fails at the caller instead of timing out in
        the waiting room."""
        # raised, never assert-ed: under python -O an over-long request
        # would be admitted and its out-of-range scatter writes silently
        # dropped — wrong tokens served, no error anywhere
        if not req.prompt:
            raise InvalidRequestError(
                f"request {req.uid}: empty prompt (decode needs at least "
                f"one prompt token to condition on)"
            )
        if req.max_new_tokens < 1:
            raise InvalidRequestError(
                f"request {req.uid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}"
            )
        if not isinstance(req.sampling, SamplingParams):
            # a duck-typed stand-in would fail inside the jitted sampler
            # mid-step (or worse, coerce silently); reject at submit
            raise InvalidRequestError(
                f"request {req.uid}: sampling must be a SamplingParams, "
                f"got {type(req.sampling).__name__}"
            )
        if len(req.prompt) + req.max_new_tokens > self.max_len:
            raise InvalidRequestError(
                f"request {req.uid} too long: {len(req.prompt)} prompt + "
                f"{req.max_new_tokens} new tokens > max_len {self.max_len}"
            )
        if self.kv is not None and self.kv.tables is not None:
            need = self.kv.tables.pages_required(
                len(req.prompt), req.max_new_tokens
            )
            if need > self.kv.num_pages:
                # admission is FIFO, so queueing an impossible request
                # would livelock it and everything behind it
                raise AdmissionError(
                    f"request references {need} pages at worst case but "
                    f"the pool has {self.kv.num_pages}; raise num_pages "
                    f"or split the request"
                )

    def submit(self, req: Request):
        self.validate_request(req)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            raise AdmissionError(
                f"queue full ({len(self.queue)}/{self.max_queue}); retry later"
            )
        if req.submitted_at is None:
            # pre-stamped by front-ends that queued the request upstream:
            # TTFT always measures from the user-visible submit
            req.submitted_at = time.perf_counter()
        self.queue.append(req)

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it is — waiting in the queue, mid-
        prefill, or mid-decode.  Frees the slot (and, for the paged
        layout, decrefs every page the slot held: shared prefix pages
        survive with their other owners, fully-registered prompt pages
        move to the reclaimable prefix-cache tier, and the partially
        written tail page returns to the free list).  Returns True when
        the request was found live; a finished/unknown uid is False.

        Must not be called while ``step`` is executing (the async
        front-end serializes cancels between steps).
        """
        now = time.perf_counter()
        for k, r in enumerate(self.queue):
            if r.uid == uid:
                self.queue.pop(k)
                r.cancelled = True
                r.finished_at = now
                self.cancelled[uid] = r
                return True
        for i, s in enumerate(self.slots):
            if s.req is not None and s.req.uid == uid:
                r = s.req
                s.req = None  # dense rows are position-masked; no scrub
                r.cancelled = True
                r.finished_at = now
                self.cancelled[uid] = r
                if self.kv is not None:
                    self.kv.free_slot(i)
                if self.spec is not None:
                    self.spec.proposer.free_slot(i)
                return True
        return False

    def _dedup_inflight_prefix(self, head: Request) -> bool:
        """In-flight prefix dedup: should ``head`` stay queued because an
        active slot is still prefilling a prompt whose shareable prefix
        pages ``head`` will be able to map once they land?

        Prefix sharing only maps *fully-written* pages, so two identical
        prompts prefilling in lockstep would each write their own copy —
        duplicating the entire prefill.  Parking the duplicate until the
        leader's pages are published turns that into one prefill plus a
        page mapping.  Parking is bounded: the leader always progresses
        (the starvation guard grants it >= 1 token per step) and parking
        stops the moment the prefix cache can supply everything the
        leader will ever publish for this prompt — or the leader stops
        prefilling.
        """
        if self.recurrent:
            # prefix sharing is disabled for 'R'/'M' patterns (shared
            # tokens would skip recurrent-state updates), so no pages
            # will ever be published — parking would wait on nothing
            return False
        ps = self.kv.page_size
        limit = (len(head.prompt) - 1) // ps  # head's shareable-block cap
        if limit == 0:
            return False
        best = 0
        for s in self.slots:
            if s.free or not s.prefilling:
                continue
            p = s.req.prompt
            m = 0
            n_common = min(len(head.prompt), len(p))
            while m < n_common and head.prompt[m] == p[m]:
                m += 1
            best = max(best, min(m // ps, limit))
        if best == 0:
            return False
        return best * ps > self.kv.probe_shared(head.prompt)

    def _admit(self):
        for i, s in enumerate(self.slots):
            if s.free and self.queue:
                if self.kv is not None:
                    head = self.queue[0]
                    if self._dedup_inflight_prefix(head):
                        # park: the leader's prefix pages will cover this
                        # prompt; admission stays FIFO (no skip-ahead)
                        break
                    shared = self.kv.admit_slot(
                        i, head.prompt, head.max_new_tokens
                    )
                    if shared is None:
                        # the pool cannot guarantee the head request yet;
                        # admission stays FIFO (no skip-ahead starvation)
                        break
                else:
                    shared = 0
                    if self.recurrent:
                        # dense layout bypasses KVCache.admit_slot: zero
                        # the recycled slot's recurrent rows here.  KV
                        # rows are position-masked and need no scrub,
                        # but carried state is read unmasked every step
                        # — a previous tenant's h/conv/state would seed
                        # the new request.
                        self.cache = reset_recurrent_state(self.cache, [i])
                s.req = self.queue.pop(0)
                # prompt tokens covered by shared prefix pages are already
                # in the cache — skip straight past them
                s.pos = shared
                self._shared_step += shared
                s.req.admitted_step = self.steps
                s.req.admitted_at = time.perf_counter()

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(not s.free for s in self.slots)

    # ------------------------------------------------------------------
    def _propose(self) -> Dict[int, List[int]]:
        """Ask the speculative proposer for draft tokens per decode slot.

        The ask is clamped so the verify grant can never write past the
        slot's cache (``max_len``) or emit past the request's
        ``max_new_tokens`` — acceptance emits up to ``drafts + 1`` tokens.
        """
        if self.spec is None:
            return {}
        decode_slots = [
            i for i, s in enumerate(self.slots) if not s.free and not s.prefilling
        ]
        # drafts are granted from the budget left after the unconditional
        # decode baselines; don't pay proposer compute (a draft model is
        # real work) for tokens the scheduler can never grant
        headroom = (
            self.spec.k if self.token_budget is None
            else self.token_budget - len(decode_slots)
        )
        if headroom <= 0:
            return {}
        asks = []
        for i in decode_slots:
            s = self.slots[i]
            r = s.req
            k = min(
                self.spec.k,
                headroom,
                r.max_new_tokens - len(r.output) - 1,
                self.max_len - s.pos - 1,
            )
            if k > 0:
                asks.append((i, r.prompt + r.output, k))
        if not asks:
            return {}
        drafts = self.spec.proposer.propose_batch(asks)
        # never trust a proposer to honor the clamp it was given
        return {i: list(drafts.get(i, ()))[:k] for i, _, k in asks}

    def _schedule(self, drafts: Dict[int, List[int]]) -> List[int]:
        """Per-slot token counts for this step under the budget.

        Decode baselines first (1 token each, unconditional), then
        speculative draft tokens, then prefill chunks — both in admission
        order (oldest request first, NOT slot order — slots are recycled,
        so slot index says nothing about age) until ``token_budget`` is
        exhausted.  Draft tokens rank above prefill (they extend decode
        work, which the engine always prioritizes) but below baselines:
        with a tight budget spec degrades gracefully to plain decode.
        The oldest prefilling request is always granted >= 1 token, so
        under sustained load every prompt reaches the head of the line
        and makes progress: no starvation.

        The budget may therefore be exceeded, in exactly two intentional
        ways (both are liveness guarantees, mirroring the paper's
        semantics — only *deferrable* work is stochastic across steps):

        1. decode baselines are unconditional — up to ``batch_slots``
           tokens are scheduled even when ``token_budget`` is smaller,
           so every in-flight request emits on every step;
        2. the starvation guard grants the oldest prefilling slot one
           token past an exhausted budget (the ``min_microbatches=1``
           analogue), so a prompt behind a full decode batch still
           reaches its first token.

        ``packing.packed_capacity`` sizes the packed program for both
        exceptions, and each step reports the realized excess as
        ``StepStats.budget_overshoot``.
        """
        n = [0] * len(self.slots)
        spent = 0
        prefill, decode = [], []
        for i, s in enumerate(self.slots):
            if s.free:
                continue
            if not s.prefilling:
                n[i] = 1  # decode baseline: always scheduled
                spent += 1
                decode.append(i)
            else:
                prefill.append(i)
        by_age = lambda i: (self.slots[i].req.admitted_step, self.slots[i].req.uid)
        decode.sort(key=by_age)
        for i in decode:
            want = len(drafts.get(i, ()))
            left = want if self.token_budget is None else self.token_budget - spent
            grant = min(want, max(left, 0))
            n[i] += grant
            spent += grant
        prefill.sort(key=by_age)
        for rank, i in enumerate(prefill):
            s = self.slots[i]
            want = min(self.chunk_size, len(s.req.prompt) - s.pos)
            left = want if self.token_budget is None else self.token_budget - spent
            grant = min(want, max(left, 0))
            if grant == 0 and rank == 0:
                grant = 1  # starvation guard (min_microbatches analogue)
            n[i] = grant
            spent += grant
        return n

    def _run_dense(self, grants, out_base) -> Dict[int, np.ndarray]:
        """Dense (B, C) step.  Returns {slot: per-granted-column sampled
        tokens} — the last column is the emitted/bonus token, the earlier
        columns are what the speculative verifier checks drafts against.
        Greedy slots (``temperature == 0``, the default) sample by
        raw-logits argmax: byte-identical to the pre-sampling engine.

        ``out_base`` maps slot -> output index of the grant's first
        column's prediction (negative mid-prefill; those columns' samples
        are discarded, so their key indices are clamped at 0).
        """
        b = len(self.slots)
        mixed = any(self.slots[i].prefilling for i, _, _ in grants)
        c = self.chunk_size if mixed else 1
        if self.spec is not None:
            # verify grants are up to 1 + k wide; keep the two-programs
            # shape story by folding them into fixed widths
            c = max(c, self.spec.k + 1) if mixed else self.spec.k + 1
        tokens = np.zeros((b, c), np.int32)
        pos = np.zeros((b,), np.int32)
        lens = np.zeros((b,), np.int32)
        seeds = np.zeros((b, c), np.uint32)
        oidx = np.zeros((b, c), np.int32)
        temps = np.zeros((b, c), np.float32)  # unused rows: argmax, discarded
        topk = np.zeros((b, c), np.int32)
        topp = np.ones((b, c), np.float32)
        for i, pos0, toks in grants:
            n = len(toks)
            tokens[i, :n] = toks
            pos[i] = pos0
            lens[i] = n
            sp = self.slots[i].req.sampling
            seeds[i] = sp.seed & 0xFFFFFFFF
            temps[i] = sp.temperature
            topk[i] = sp.top_k
            topp[i] = sp.top_p
            oidx[i, :n] = np.maximum(out_base[i] + np.arange(n), 0)
        logits, self.cache, aux = _engine_step(
            self.params, self.cfg, self.cache, jnp.asarray(tokens),
            jnp.asarray(pos), jnp.asarray(lens), moe_impl=self.moe_impl,
        )
        # Synchronize every step (np.asarray blocks on the result; the
        # jitted sampler dispatches asynchronously in the same chain, so
        # sampling adds no extra sync).  The host needs the sampled tokens
        # to schedule the next step, and the sync keeps the host token/pos
        # buffers alive until the step that reads them has run: async
        # dispatch once read them after they were rebound (garbage tokens
        # on a CPU backend).  Whether a TPU step needs the wait before the
        # next layout is built is open (ROADMAP 1.2).
        next_tok = np.asarray(sample_tokens(
            logits, seeds, oidx, temps, topk, topp
        ))  # (B, C)
        self._overflow_step = int(np.asarray(aux["expert_overflow"]))
        return {i: next_tok[i, : len(toks)] for i, _, toks in grants}

    def _run_packed(self, grants, out_base) -> Dict[int, np.ndarray]:
        """Token-packed (capacity,) step: compute scales with grants.

        Pure-decode steps (every grant one token) take the decode-sized
        program; any prefill or draft widens a grant past one token and
        routes to the mixed-capacity program.  Sampling params and
        per-position key indices are slot-gathered per packed entry
        (``PackedLayout.out_idx``), so a packed row samples exactly what
        the dense row for the same (request, output index) samples.
        """
        capacity = self.packed_capacity
        if all(len(toks) == 1 for _, _, toks in grants):
            capacity = self.packed_decode_capacity
        layout = packing.pack_step(grants, capacity, out_base=out_base)
        seeds = np.zeros((capacity,), np.uint32)
        temps = np.zeros((capacity,), np.float32)  # padding: argmax, discarded
        topk = np.zeros((capacity,), np.int32)
        topp = np.ones((capacity,), np.float32)
        for i, (j, m) in layout.spans.items():
            sp = self.slots[i].req.sampling
            seeds[j : j + m] = sp.seed & 0xFFFFFFFF
            temps[j : j + m] = sp.temperature
            topk[j : j + m] = sp.top_k
            topp[j : j + m] = sp.top_p
        logits, self.cache, aux = _packed_engine_step(
            self.params, self.cfg, self.cache, jnp.asarray(layout.tokens),
            jnp.asarray(layout.slot_ids), jnp.asarray(layout.positions),
            moe_impl=self.moe_impl,
        )
        next_tok = np.asarray(sample_tokens(
            logits, seeds, layout.out_idx, temps, topk, topp
        ))  # (P,) — syncs
        self._overflow_step = int(np.asarray(aux["expert_overflow"]))
        return {i: next_tok[j : j + m] for i, (j, m) in layout.spans.items()}

    def step(self):
        """One engine iteration: mixed chunked-prefill + decode/verify."""
        t0 = time.perf_counter()
        queued0 = len(self.queue)  # queue depth before this step's admission
        self._shared_step = 0
        self._overflow_step = 0  # set by the step runner from the jit aux
        self._admit()
        if self.kv is not None:
            # lazy prefix sharing: an older request may have finished
            # writing pages this prompt can map since the last step
            for i, s in enumerate(self.slots):
                if not s.free and s.prefilling:
                    n_sh = self.kv.share(i, s.req.prompt, s.pos)
                    if n_sh:
                        s.pos += n_sh
                        self._shared_step += n_sh
        drafts = self._propose()
        n = self._schedule(drafts)
        decode_toks = prefill_toks = deferred = draft_toks = accepted_toks = 0
        grants: List[packing.Grant] = []  # (slot, start pos, tokens)
        granted_draft: Dict[int, List[int]] = {}
        # slot -> output index of the grant's first column's prediction:
        # column c at absolute position pos + c predicts position
        # pos + c + 1, i.e. output index pos + c + 1 - len(prompt)
        # (negative mid-prefill — those columns' samples are discarded).
        # This feeds the sampler's per-position PRNG keys, which must
        # depend only on (request seed, output index) for seeded streams
        # to replay across step paths and speculation.
        out_base: Dict[int, int] = {}
        for i, s in enumerate(self.slots):
            if s.free or n[i] == 0:
                if not s.free and s.prefilling:
                    deferred += min(self.chunk_size, len(s.req.prompt) - s.pos)
                continue
            r = s.req
            if s.prefilling:
                toks = r.prompt[s.pos : s.pos + n[i]]
                prefill_toks += n[i]
                deferred += max(
                    min(self.chunk_size, len(r.prompt) - s.pos) - n[i], 0
                )
            else:
                # the budget may have truncated the proposer's draft
                draft = drafts.get(i, [])[: n[i] - 1]
                granted_draft[i] = draft
                toks = [r.output[-1] if r.output else r.prompt[-1]] + draft
                decode_toks += 1
                draft_toks += len(draft)
            out_base[i] = s.pos + 1 - len(r.prompt)
            grants.append((i, s.pos, toks))

        if self.kv is not None:
            # allocate (and copy-on-write, if any page is shared) every
            # page this step's grants will scatter into, then hand the
            # refreshed block tables to the jitted step
            self.kv.prepare_step(grants)
            self.cache = self.kv.state
        used_pages = self.kv.used_pages if self.kv is not None else 0

        sampled = (
            self._run_packed(grants, out_base)
            if self.packed
            else self._run_dense(grants, out_base)
        )
        if self.kv is not None:
            self.kv.state = self.cache

        now = time.perf_counter()
        for i, s in enumerate(self.slots):
            if s.free or n[i] == 0:
                continue
            r = s.req
            was_prefilling = s.prefilling
            if was_prefilling:
                s.pos += n[i]
                if self.kv is not None:
                    # publish fully-written prompt pages for prefix sharing
                    self.kv.register_prompt_pages(i, r.prompt, s.pos)
                if s.pos < len(r.prompt):
                    continue  # still mid-prompt; no token emitted this step
                emitted = [int(sampled[i][n[i] - 1])]
            else:
                # verify: rejection-sampling acceptance — keep the draft
                # prefix matching the target's per-column samples (+ the
                # bonus/resampled token), roll back the rejected tail's
                # KV.  Greedy params make this the argmax prefix match.
                accepted, emitted = accept_sampled(granted_draft[i], sampled[i])
                remaining = r.max_new_tokens - len(r.output)
                if len(emitted) > remaining:
                    # Clamp: a request asking for N tokens must never
                    # stream N+k (the proposer ask is clamped too, but
                    # this is the structural guarantee — spec streams are
                    # length-identical to greedy even against a proposer
                    # that ignores its ask).  The clamped tail's KV is
                    # left untrimmed: the request finishes this step and
                    # free_slot reclaims every page.
                    emitted = emitted[:remaining]
                    accepted = len(emitted) - 1
                    s.pos += 1 + accepted
                else:
                    s.pos += 1 + accepted
                    if self.kv is not None and accepted < len(granted_draft[i]):
                        self.kv.trim_slot(i, s.pos)
                accepted_toks += accepted
            r.output.extend(emitted)
            if r.first_token_at is None:
                r.first_token_at = now
                r.first_token_step = self.steps
            if r.done or s.pos >= self.max_len:
                # a slot out of cache positions ends the request early;
                # flag it rather than silently serving a short stream
                r.truncated = not r.done
                r.finished_at = now
                self.finished[r.uid] = r
                s.req = None  # slot becomes available next step
                if self.kv is not None:
                    self.kv.free_slot(i)
                if self.spec is not None:
                    self.spec.proposer.free_slot(i)

        scheduled = decode_toks + draft_toks + prefill_toks
        stats = StepStats(
            self.steps, decode_toks, prefill_toks, deferred, now - t0,
            shared_tokens=self._shared_step,
            used_pages=used_pages,
            draft_tokens=draft_toks,
            accepted_tokens=accepted_toks,
            queued_requests=queued0,
            budget_overshoot=(
                max(scheduled - self.token_budget, 0)
                if self.token_budget is not None else 0
            ),
            expert_overflow=self._overflow_step,
        )
        self.step_stats.append(stats)
        self.steps += 1
        for fn in self._step_callbacks:
            fn(stats)

    def run(self, max_steps: int = 10_000) -> Dict[int, Request]:
        steps = 0
        while self.busy and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # ------------------------------------------------------------------
    def reset_stats(self):
        """Clear per-step and per-request accounting (e.g. after warmup).

        The KV cache contents are left as-is: slots are position-masked,
        so stale rows from earlier requests are never attended.  Paged
        page-usage counters rebaseline (``KVCache.reset_accounting``) so
        ``touched_pages`` counts only post-reset page traffic — live and
        prefix-cached pages survive.
        """
        if self.busy:
            # raised, not assert-ed: under python -O a mid-flight reset
            # would silently corrupt every in-flight request's accounting
            raise EngineStateError("reset_stats while requests are in flight")
        self.steps = 0
        self.step_stats = []
        self.finished = {}
        self.cancelled = {}
        self._shared_step = 0  # stale counter from the last step otherwise
        self._overflow_step = 0
        if self.kv is not None:
            self.kv.reset_accounting()

    def stats_summary(self) -> Dict[str, float]:
        """Aggregate engine + latency statistics.

        TTFT is split into its two phases so queue pressure is visible:
        ``queue_wait`` (submit -> slot admission — invisible compute-side,
        dominated by slot contention) and ``admitted_ttft`` (admission ->
        first token — the prefill-side latency the chunk/budget knobs
        control).  ``ttft = queue_wait + admitted_ttft`` per request; all
        three report mean/p50/p99.
        """
        st = self.step_stats
        done = list(self.finished.values())
        ttfts = [r.ttft for r in done if r.ttft is not None]

        def pct(values, q):
            return float(np.quantile(values, q)) if values else float("nan")

        def dist(prefix, values):
            return {
                f"mean_{prefix}": float(np.mean(values)) if values else float("nan"),
                f"p50_{prefix}": pct(values, 0.50),
                f"p99_{prefix}": pct(values, 0.99),
            }
        paged = (
            {
                "shared_tokens": float(sum(s.shared_tokens for s in st)),
                "peak_used_pages": float(max((s.used_pages for s in st), default=0)),
                "touched_pages": float(self.kv.tables.touched_pages),
                "num_pages": float(self.kv.num_pages),
            }
            if self.kv is not None
            else {}
        )
        n_draft = sum(s.draft_tokens for s in st)
        n_accept = sum(s.accepted_tokens for s in st)
        spec = (
            {
                "draft_tokens": float(n_draft),
                "accepted_tokens": float(n_accept),
                "acceptance_rate": (
                    n_accept / n_draft if n_draft else float("nan")
                ),
            }
            if self.spec is not None
            else {}
        )
        generated = sum(len(r.output) for r in done)
        waits = [r.queue_wait for r in done if r.queue_wait is not None]
        admitted = [r.admitted_ttft for r in done if r.admitted_ttft is not None]
        return {
            **paged,
            **spec,
            "generated_tokens": float(generated),
            "steps_per_token": (
                self.steps / generated if generated else float("nan")
            ),
            "truncated": float(sum(r.truncated for r in done)),
            "cancelled": float(len(self.cancelled)),
            "steps": float(self.steps),
            "max_step_tokens": float(max((s.scheduled_tokens for s in st), default=0)),
            "mean_step_tokens": float(
                np.mean([s.scheduled_tokens for s in st]) if st else 0.0
            ),
            # tau is a deferral threshold, not a hard cap (decode
            # baselines + the starvation guard; see _schedule) — report
            # the realized excess so BENCH consumers see it
            "budget_overshoot_tokens": float(
                sum(s.budget_overshoot for s in st)
            ),
            "max_budget_overshoot": float(
                max((s.budget_overshoot for s in st), default=0)
            ),
            # capacity-factor MoE dispatch: (token, expert) routes the
            # per-expert capacity dropped to the residual path — the
            # per-expert analogue of the deferral accounting above
            "expert_overflow_tokens": float(
                sum(s.expert_overflow for s in st)
            ),
            "max_expert_overflow": float(
                max((s.expert_overflow for s in st), default=0)
            ),
            "mean_queued_requests": float(
                np.mean([s.queued_requests for s in st]) if st else 0.0
            ),
            "deferred_tokens": float(sum(s.deferred_tokens for s in st)),
            "max_step_wall": float(max((s.wall_time for s in st), default=0.0)),
            "finished": float(len(done)),
            "mean_ttft": float(np.mean(ttfts)) if ttfts else float("nan"),
            "p50_ttft": pct(ttfts, 0.50),
            "p99_ttft": pct(ttfts, 0.99),
            **dist("queue_wait", waits),
            **dist("admitted_ttft", admitted),
        }
