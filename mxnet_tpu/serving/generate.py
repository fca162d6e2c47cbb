"""GenerationEngine — slot-based continuous batching for
autoregressive decoding.

The InferenceEngine (engine.py) multiplies throughput for FIXED
forwards by coalescing requests; generation breaks its model: one
request is not one forward but a prefill plus an unknown number of
decode steps. Whole-batch ("static") generation — collect B prompts,
decode until ALL finish — leaves slots idle behind the longest
sequence and stalls arrivals behind batch formation. Iteration-level
scheduling (Orca, OSDI'22; vLLM's continuous batching) instead admits
and evicts requests at DECODE-STEP boundaries. The TPU-native twist
here is fixed-shape slot batches: the KV cache is a preallocated
``max_slots``-row pytree (gluon/model_zoo/gpt.py ``init_cache``) and
every step of every mix of requests runs ONE AOT-warmed decode
program — occupancy changes rebind slot rows, never shapes, so the
steady state compiles exactly nothing.

Architecture::

    caller threads ── submit(prompt) ──► bounded request queue
                                              │ (admission control:
                                              │  queue_limit, timeout,
                                              ▼  closed-engine reject)
                                        generator thread
                     ┌──────────────────────────────────────────────┐
                     │ per step: admit queued prompts into FREE     │
                     │ slots (prefill bucketed on the seq axis via  │
                     │ BucketingPolicy, K/V scattered into the      │
                     │ cache at the slot row) ── one fixed-shape    │
                     │ decode_step over ALL slots ── emit one token │
                     │ per live slot into its stream ── evict       │
                     │ EOS / max-tokens / deadline slots (freed     │
                     │ rows admit the next prompts mid-sequence)    │
                     └──────────────────────────────────────────────┘

``submit`` returns a :class:`GenerationStream` — a token-stream
future: iterate it to consume tokens as they are generated, or call
``result(timeout)`` for the completed :class:`GenerationResult`.
Admission control and shutdown follow the InferenceEngine contract
exactly (``QueueFullError`` / ``RequestTimeoutError`` /
``EngineClosedError``; ``close()`` drains-then-rejects via the shared
``BoundedQueueWorker``; no stream is ever left hanging), and
``MXTPU_SERVING=0`` degrades to synchronous inline generation.

Decoding is GREEDY (argmax) — which is what makes engine output
token-identical to a single-request ``prefill`` + ``decode_step`` loop
at the same slot width: rows of one XLA program are bit-independent,
so a request's tokens do not depend on its co-tenants.

``paged=True`` swaps the dense per-slot cache for the PAGED KV cache
(vLLM-style block tables; docs/SERVING.md "Paged KV cache"): a global
page pool + static-shape page tables, host-side refcounted page
allocation (serving/paging.py), prefix reuse (a shared system prompt
is prefilled ONCE and its immutable pages are shared across slots,
copy-on-write at the divergence page), and Sarathi/Orca-style chunked
prefill (at most ONE fixed-width chunk per engine iteration,
interleaved with the decode step, so a long prompt bounds TPOT instead
of stalling every in-flight request for a whole monolithic prefill).
Same fixed-shape/zero-steady-state-compile discipline; greedy output
stays token-identical to the dense engine.

``draft_model=`` turns on SPECULATIVE DECODING (docs/SERVING.md
"Speculative decoding & sampling"): a second, smaller decoder
proposes ``spec_k`` tokens per slot per iteration and the target
verifies all ``spec_k + 1`` positions in one fixed-shape program,
committing 1..``spec_k + 1`` tokens — the per-SLOT throughput
multiplier that composes with continuous batching's cross-slot one.
Greedy output stays TOKEN-IDENTICAL to the non-speculative engine;
stochastic requests use the residual-distribution accept rule, which
preserves the target distribution exactly. ``submit(temperature=,
top_k=, top_p=, seed=)`` is a first-class per-request feature on
every engine: knobs ride per-slot runtime vectors through one
fixed-shape sampling program (ops/sampling.py), keys are explicit
and split per slot per step inside the trace, and a seeded stream
is bitwise-reproducible whenever the admission schedule is replayed
— across engine restarts included.

Telemetry (docs/OBSERVABILITY.md): counters
``serving.generate.{requests,tokens,prefills,evictions,rejected_full,
rejected_closed,timeouts,errors}``, gauges ``serving.generate.slots``
(occupancy + peak) / ``serving.generate.queue.depth``, histograms
``serving.generate.{prefill,decode,ttft}``; paged mode adds
``serving.generate.pages.{allocated,shared,cow_copies,freed}`` /
``pages.free`` / ``prefix_hits`` / ``prefill_chunks`` and the
``prefill_chunks_per_iter`` gauge whose peak proves the one-chunk
decode-stall bound; speculation adds
``serving.generate.spec.{proposed,accepted,rejected}`` counters and
the ``spec.accept_rate`` / ``spec.tokens_per_step`` gauges; sampling
adds ``serving.generate.sampling.requests``; multi-tenant LoRA adds
the ``serving.generate.lora.{adapters_loaded,adapters_evicted,
requests}`` counters, the ``lora.active_adapters`` gauge, the
``lora.load`` histogram, and the ``ops.lora.trace`` compile counter
(the bank analog of ``model.gpt.trace`` for the zero-retrace gates).
"""
from __future__ import annotations

import collections
import contextlib
import os
import queue
import threading
import time
import weakref

import numpy as onp

from .. import telemetry, tracing
from ..random_state import request_key
from .._bounded_worker import BoundedQueueWorker
from ..bucketing import BucketingPolicy, as_policy
from . import paging
from .engine import (
    EngineClosedError, QueueFullError, ReplicaFailedError,
    RequestTimeoutError, _live_engines, _serving_enabled,
)

__all__ = ["GenerationEngine", "GenerationStream", "GenerationResult"]


class GenerationResult:
    """Completed generation: ``tokens`` (generated ids, prompt
    excluded), ``finish_reason`` in {"eos", "length", "timeout",
    "closed"}, and the ``prompt_len`` it continued from."""

    __slots__ = ("tokens", "finish_reason", "prompt_len")

    def __init__(self, tokens, finish_reason, prompt_len):
        self.tokens = tokens
        self.finish_reason = finish_reason
        self.prompt_len = prompt_len

    def __len__(self):
        return len(self.tokens)

    def __repr__(self):
        return (f"GenerationResult({len(self.tokens)} tokens, "
                f"finish_reason={self.finish_reason!r})")


class GenerationStream:
    """Per-request token-stream future.

    Iterating yields token ids as the engine produces them (multiple
    iterators each see the full stream); ``result(timeout)`` blocks for
    the final :class:`GenerationResult`. A rejected/failed request
    raises the failure from both paths — never a hung consumer."""

    def __init__(self, prompt_len):
        self.prompt_len = prompt_len
        self._cv = threading.Condition()
        self._tokens: list = []
        self._reason = None
        self._exc = None
        self._watchers: list = []
        #: ``time.perf_counter()`` stamps of the first token and of
        #: completion — producer-side, so latency measurement needs no
        #: consumer thread racing the stream.
        self.first_token_at = None
        self.done_at = None
        #: the request's tracing.Trace, or None (tracing off for this
        #: request — the near-zero disabled path)
        self._trace = None

    # -- producer side (generator thread) ------------------------------
    def _emit(self, token: int):
        # one protocol, one implementation: the finished-stream guard
        # (a stale step racing an injected crash must not append),
        # first-token stamp, wakeup and watcher fan-out all live in
        # _emit_many
        self._emit_many((token,))

    def _emit_many(self, tokens):
        """Append a SEQUENCE of tokens under one lock acquisition and
        one wakeup — the speculative-commit fast path: a verify step
        commits up to k+1 tokens at once, and per-token notify_all
        with a live ``result()`` waiter costs a GIL bounce each (the
        dominant per-iteration cost at interactive concurrency)."""
        if not tokens:
            return
        with self._cv:
            if self._reason is not None or self._exc is not None:
                return  # finished streams take no more tokens
            if not self._tokens:
                self.first_token_at = time.perf_counter()
            toks = [int(t) for t in tokens]
            self._tokens.extend(toks)
            if self._trace is not None:
                self._trace.event("emit", n=len(toks),
                                  total=len(self._tokens))
            self._cv.notify_all()
            for on_token, _fin in self._watchers:
                for tok in toks:
                    on_token(tok)

    def _finish(self, reason=None, exc=None):
        with self._cv:
            if self._reason is not None or self._exc is not None:
                return  # first outcome stands (close racing a finish)
            self._reason = reason
            self._exc = exc
            self.done_at = time.perf_counter()
            if self._trace is not None:
                self._trace.finish(reason=reason, error=exc)
            self._cv.notify_all()
            watchers, self._watchers = self._watchers, []
            for _tok, on_finish in watchers:
                on_finish(reason, exc)

    def _watch(self, on_token, on_finish):
        """Producer-side event subscription (the Router's retry hook):
        ``on_token(tok)`` fires for every token — including, first, a
        replay of tokens already emitted — and ``on_finish(reason,
        exc)`` exactly once at completion. Callbacks run under the
        stream lock on the producer thread; they must be quick and must
        not raise (a raise propagates into the producing engine)."""
        with self._cv:
            for tok in self._tokens:
                on_token(tok)
            if self._reason is not None or self._exc is not None:
                on_finish(self._reason, self._exc)
            else:
                self._watchers.append((on_token, on_finish))

    # -- consumer side --------------------------------------------------
    def done(self) -> bool:
        with self._cv:
            return self._reason is not None or self._exc is not None

    @property
    def trace_id(self):
        """The request's trace id, or None when untraced."""
        return None if self._trace is None else self._trace.trace_id

    def trace(self):
        """The request's recorded spans (list of dicts — see
        ``tracing.Span``), or None when the request was not traced
        (tracing disabled and no ``submit(trace=True)``). Available
        live (spans so far) and after completion (the full
        queue→admission→prefill→decode→emit→finish lifecycle)."""
        return None if self._trace is None else self._trace.spans()

    @property
    def tokens(self):
        """Snapshot of the tokens generated so far."""
        with self._cv:
            return list(self._tokens)

    def __iter__(self):
        i = 0
        while True:
            with self._cv:
                while i >= len(self._tokens) and self._reason is None \
                        and self._exc is None:
                    self._cv.wait()  # every producer path notifies
                if i < len(self._tokens):
                    tok = self._tokens[i]
                    i += 1
                elif self._exc is not None:
                    raise self._exc
                else:
                    return
            yield tok

    def result(self, timeout=None) -> GenerationResult:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._reason is None and self._exc is None:
                rem = None if deadline is None \
                    else deadline - time.monotonic()
                if rem is not None and rem <= 0:
                    raise TimeoutError(
                        "generation still running after result() timeout")
                self._cv.wait(rem)
            if self._exc is not None:
                raise self._exc
            return GenerationResult(list(self._tokens), self._reason,
                                    self.prompt_len)


class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "stream", "t_submit",
                 "t_enq", "deadline", "temperature", "top_k", "top_p",
                 "key", "adapter_idx")

    def __init__(self, prompt, max_new, eos_id, stream, t_submit,
                 t_enq, deadline, temperature=0.0, top_k=0, top_p=1.0,
                 key=None, adapter_idx=0):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.stream = stream
        self.t_submit = t_submit
        self.t_enq = t_enq     # monotonic enqueue stamp (queue wait)
        self.deadline = deadline
        self.temperature = temperature   # 0.0 = greedy
        self.top_k = top_k               # 0 = off
        self.top_p = top_p               # 1.0 = off
        self.key = key                   # (2,) uint32 PRNG key data
        self.adapter_idx = adapter_idx   # LoRA bank slot (0 = base)


class _Adapter:
    """Host-side registry record of one loaded LoRA adapter: its bank
    slot, the number of requests pinning it (submitted and not yet
    finished), and whether an unload is deferred behind those pins."""

    __slots__ = ("name", "idx", "refs", "unloading")

    def __init__(self, name, idx):
        self.name = name
        self.idx = idx
        self.refs = 0
        self.unloading = False


class _Slot:
    __slots__ = ("stream", "last", "left", "eos_id", "deadline", "n_ctx")

    def __init__(self, stream, last, left, eos_id, deadline, n_ctx):
        self.stream = stream
        self.last = last       # last emitted token (next step's input)
        self.left = left       # generated-token budget remaining
        self.eos_id = eos_id
        self.deadline = deadline
        self.n_ctx = n_ctx     # cache rows filled (prompt + decoded)


class _PagedSlot:
    """Slot state in paged mode. ``state`` is "prefill" (chunks still
    pending — the slot sits out decode steps) or "decode". ``row`` is
    the host mirror of the slot's page-table row (physical page per
    logical page index; scrap 0 past its reservation); ``page_refs``
    are the pool references the slot holds (released at eviction);
    ``cow_pending`` is ``(src, dst, logical_idx)`` when the slot's next
    decode write would land in a SHARED page — the divergence page is
    copied to ``dst`` right before that first write (copy-on-write)."""

    __slots__ = ("stream", "last", "left", "eos_id", "deadline", "n_ctx",
                 "state", "chunks", "row", "page_refs", "cow_pending",
                 "prompt", "seq", "t_submit", "draft_prompt", "key",
                 "adapter_idx", "blk")

    def __init__(self, stream, left, eos_id, deadline, n_ctx, row,
                 page_refs, prompt, seq, t_submit):
        self.stream = stream
        self.adapter_idx = 0   # LoRA bank slot (0 = base model)
        self.draft_prompt = None   # kept in speculative mode for the
        # draft's dense prefill when the slot enters decode
        self.key = None   # stochastic requests: the PRNG key, parked
        # here until decode entry (see _arm_sampling)
        self.last = None
        self.left = left
        self.eos_id = eos_id
        self.deadline = deadline
        self.n_ctx = n_ctx
        self.state = "prefill"
        self.chunks = collections.deque()
        self.row = row
        self.page_refs = page_refs
        self.cow_pending = None
        self.prompt = prompt   # kept until registered in the index
        self.seq = seq         # admission order (oldest prefills first)
        self.t_submit = t_submit
        self.blk = None        # block-diffusion engines: the _Block


def block_schedule(masked, steps):
    """How many positions each denoising step of a block unmasks: LLaDA's
    ``get_num_transfer_tokens``, ``masked`` positions split over
    ``steps``, the remainder to the first steps. A step that would unmask
    nothing is not run: the block is done when no mask is left."""
    base, extra = divmod(int(masked), int(steps))
    return [k for k in (base + (i < extra) for i in range(int(steps))) if k]


class _Block:
    """The host's mirror of a slot's block in a block-diffusion engine
    (docs/SERVING.md "Block-diffusion steps"): the block's first position
    ``start``, the prompt tokens it opens with (``tail``), the steps it
    takes (``steps``, ``block_schedule``) and how many are dispatched
    (``step``), the request's end ``end`` (budget or capacity), whether
    the block is still to be handed to the device (``fresh``) and whether
    the request's last step is dispatched (``last``). It follows what is
    DISPATCHED: the device holds the block itself."""

    __slots__ = ("start", "tail", "steps", "step", "end", "fresh", "last")

    def __init__(self, start, tail, steps, end):
        self.start, self.tail, self.steps, self.end = start, tail, steps, end
        self.step = 0
        self.fresh, self.last = True, False


class _Tick:
    """A plain decode tick between its dispatch and its commit. ``rows``
    are ``(slot, slot state)`` pairs, the state object being the identity
    of the request the row served: a slot that is empty or holds another
    request by commit time was served a stale row-tick, and its output is
    dropped. A tick dispatched ahead holds its greedy ``pick``, a ``(B,)``
    array still on the device; a synchronous tick its ``logits``, from
    which it picks at its sync."""

    __slots__ = ("rows", "logits", "pick", "t0", "tt0")

    def __init__(self, rows, t0, tt0):
        self.rows = rows
        self.logits = self.pick = None
        self.t0 = t0       # telemetry clock at dispatch
        self.tt0 = tt0     # perf_counter at dispatch, if any row traces


class _GenWorker(BoundedQueueWorker):
    """Consumer side of the request queue: the admit/step loop.

    Same shutdown contract as the InferenceEngine batcher: a graceful
    ``_draining`` phase finishes admitted work, ``stop()`` is the hard
    deadline whose drain rejects queued leftovers through
    ``_drained``."""

    def __init__(self, engine: "GenerationEngine", queue_limit: int):
        super().__init__(queue_limit, name="GenerationEngine.worker")
        self._engine = weakref.ref(engine)
        self._draining = False
        self.start()

    def run(self):
        try:
            self._run()
        except Exception as e:  # noqa: BLE001 — a failed step must not
            # strand waiters: fail every live stream and queued request
            telemetry.counter("serving.generate.errors")
            eng = self._engine()
            if eng is not None:
                eng._fail_all(e)
            return
        # hard-stopped mid-generation: the worker owns the slots, so it
        # (not close(), racing is_alive) finishes leftover streams —
        # truncated output with finish_reason="closed", never a hang
        eng = self._engine()
        if eng is not None and self._stopped:
            eng._close_active("closed")

    def _run(self):
        while not self._stopped:
            eng = self._engine()
            if eng is None:
                return  # abandoned engine: streams die with their refs
            # every model-touching path holds _gen_lock — warmup() may
            # be tracing the jitted closures concurrently, and tracing
            # (parameter rebinding in the _bind wrapper) is not
            # thread-safe against itself
            with eng._gen_lock, tracing.phase("serve.iter"):
                with tracing.phase("serve.admit"):
                    eng._admit(self._queue)
                active = eng._n_active
                if active:
                    eng._step()
            if eng._gen_waiters:
                # fairness: this loop re-acquires _gen_lock back to
                # back, and lock handoff is unfair under the GIL — a
                # rollover/warmup/fault-injection caller could starve
                # for an entire generation. Cede one scheduler slice
                # between steps when someone is waiting (rare).
                time.sleep(0.0005)
            if active:
                continue
            del eng  # don't pin the engine while blocking on the queue
            try:
                with tracing.phase("serve.idle"):
                    r = self._queue.get(timeout=0.05)
            except queue.Empty:
                if self._draining:
                    return
                continue
            eng = self._engine()
            if eng is None:
                r.stream._finish(exc=EngineClosedError(
                    "engine was garbage-collected"))
                return
            with eng._gen_lock, tracing.phase("serve.iter"), \
                    tracing.phase("serve.admit"):
                eng._admit_one(r)

    def _drained(self, item):
        if isinstance(item, _GenRequest):
            telemetry.counter("serving.generate.rejected_closed")
            item.stream._finish(exc=EngineClosedError(
                "engine closed before the request was scheduled"))

    def close(self, timeout: float):
        self._draining = True
        self.join(timeout=max(0.0, timeout))
        self.stop(timeout=min(timeout, 2.0) if timeout > 0 else 0.1)


def _refuse_unsupported(model, asked):
    """The model-engine contract's first half (docs/SERVING.md): a model
    that states what it supports (``generation_support``, a dict) is
    held to it, and every other option is refused here, by the option's
    name, before anything is built. ``asked`` maps an option's name to
    ``(the capability it needs, whether it was asked for, its value)``.
    A capability is ``True``/``False``, or for a dtype option the tuple
    of values taken. A model that states nothing (``GPTModel``, test
    doubles) is probed attribute by attribute as before."""
    support = getattr(model, "generation_support", None)
    if support is None:
        return None
    for option, (capability, wanted, value) in asked.items():
        if not wanted:
            continue
        allowed = support.get(capability, False)
        if allowed is True or (isinstance(allowed, tuple)
                               and str(value) in allowed):
            continue
        only = f" (it takes {', '.join(allowed)})" \
            if isinstance(allowed, tuple) and allowed else ""
        raise ValueError(
            f"{option}={value!r} is not supported by "
            f"{type(model).__name__}{only}: the model's "
            f"generation_support states what the engine may be asked "
            f"for with it")
    return support


class GenerationEngine:
    """Continuously-batched greedy generation over a decoder model.

    Parameters
    ----------
    model
        A decoder exposing the explicit-cache generation API —
        ``init_cache(batch_size, max_length, dtype)`` /
        ``prefill(tokens, valid_length, cache, slots)`` /
        ``decode_step(tokens, cache)`` (gluon/model_zoo/gpt.py
        ``GPTModel`` is the in-tree implementation).
    max_slots : int
        Concurrent sequences per decode step — the fixed batch width
        of the decode program and the KV-cache row count.
    max_length : int, optional
        Cache sequence capacity (default: the model's position table).
        A prompt must leave room for at least one generated token.
    max_new_tokens : int
        Default generated-token budget per request (``submit``
        overrides per call).
    eos_id : int, optional
        Default stop token (``submit`` overrides per call).
    queue_limit : int
        Bound on queued requests; beyond it ``submit`` raises
        :class:`QueueFullError` immediately (load shedding).
    timeout_ms : float, optional
        Default deadline: a request still QUEUED past it is rejected
        with :class:`RequestTimeoutError`; one already generating is
        finished early with ``finish_reason="timeout"`` (partial
        output delivered — tokens already streamed can't be unsent).
    prefill_bucketing : BucketingPolicy | str | None
        Sequence-axis policy for prefill (default pow2, min 8, clamped
        to the cache capacity; paged mode raises the floor to the page
        size). Each bucket is one compiled prefill width — ``warmup()``
        AOT-compiles them all.
    paged : bool
        Replace the dense per-slot cache with the PAGED KV cache: a
        global pool of fixed-size pages plus a static-shape page table
        per slot (docs/SERVING.md "Paged KV cache"). Enables prefix
        reuse (shared prompts prefilled once, refcounted, copy-on-write
        at the divergence page) and chunked prefill (at most one chunk
        per engine iteration, so long prompts can't stall in-flight
        decode). Greedy output stays token-identical to dense mode.
    page_size : int
        Tokens per KV page (power of two dividing ``max_length``).
        Also the prefix-sharing granularity: only whole pages are
        shared.
    n_pages : int, optional
        Physical pages in the pool (default: the dense cache's exact
        HBM budget, ``max_slots * max_length / page_size``, plus the
        reserved scrap page). Fewer pages overcommit HBM against
        short/shared traffic: admission defers (FIFO) while the pool
        is exhausted, after evicting cold cached prefixes.
    prefill_chunk : int
        Chunked-prefill width (multiple of ``page_size``; default
        ``max(32, 2 * page_size)`` capped at the cache capacity). A
        prompt longer than one bucketed chunk is admitted as
        fixed-width chunks, one per engine iteration.
    prefix_cache : bool
        Keep finished prompts' pages in a refcounted LRU index so
        later requests sharing their prefix skip that prefill (an
        exact repeat skips prefill entirely — its first token is
        computed straight off the cached K/V).
    quantize : str, optional
        ``"int8_weights"`` arms weight-only int8 decode: the model's
        attention/MLP projection weights are quantized per-output-
        channel symmetric int8 at engine load (re-quantized under the
        swap lock on every ``load_weights`` rollover) and the decode
        path runs the fused dequant-matmul kernel — the fp32 weights
        never re-stream from HBM. Greedy output is held to the
        bounded-divergence gate documented in docs/SERVING.md
        ("Low-precision decode"), not token-identity.
    kv_dtype : str, optional
        ``"int8"`` stores the KV cache quantized (a quarter the K/V
        bytes of fp32; per-head-per-slot scales dense, per-head-per-
        page scales paged — so a paged pool holds ~4x the pages in
        the same HBM). Alias for ``cache_dtype`` with the quantized
        layout; attention dequantizes inside the decode kernels.
    draft_model : optional
        A second, SMALLER decoder from the same model family (same
        vocabulary) that turns on draft-model SPECULATIVE DECODING:
        each engine iteration the draft proposes ``spec_k`` tokens per
        decoding slot and the target model verifies all ``spec_k + 1``
        positions in one fixed-shape program, committing the accepted
        prefix plus one target token — between 1 and ``spec_k + 1``
        tokens per slot per iteration instead of exactly one. Greedy
        output stays TOKEN-IDENTICAL to the non-speculative engine
        (the accept rule only ever commits the target's own greedy
        tokens); stochastic requests use the speculative-sampling
        residual rule, which preserves the target distribution
        exactly. The draft keeps its own dense fp32 cache and is
        rolled back to the accept point every iteration.
    spec_k : int
        Draft tokens proposed per slot per iteration (default 4).
        Each cache row reserves a ``spec_k`` scratch margin at the
        top (usable capacity is ``max_length - spec_k``) so a verify
        write never clamps; rejected entries die above the ``len``
        waterline.
    speculative : bool, optional
        Defaults to ``draft_model is not None``. Passing
        ``speculative=True`` without a draft raises — self-speculation
        is not implemented.
    mesh_layout : str, optional
        ``"tp"`` runs ONE model sharded across the device mesh
        (tensor parallel — parallel/partition.py's ``"tp"`` layout):
        the attention/MLP weights are placed over the mesh's ``tp``
        axis by their logical axes, the KV cache is sharded over the
        HEADS axis, and every generation program compiles SPMD — so a
        model (plus cache) larger than one device's HBM serves from
        the whole mesh. Greedy output is token-identical to the
        unsharded engine (the only numeric difference is the
        reduction order of the ``tp`` partial sums). Currently the
        dense fp32 engine only; ``num_heads`` must be divisible by
        the ``tp`` axis size.
    mesh : jax.sharding.Mesh, optional
        The mesh for ``mesh_layout`` (default: the process-global
        ``parallel.get_mesh()``). Must carry a ``tp`` axis.
    lora_rank : int, optional
        Arm batched multi-tenant LoRA (docs/SERVING.md "Multi-tenant
        LoRA"): the model grows a stacked adapter bank (ops/lora.py)
        over its attention projections and every generation program
        gathers each slot's adapter by a per-slot index vector —
        thousands of fine-tunes share ONE engine, one compiled
        program, one KV pool. ``load_adapter(name, params)`` /
        ``unload_adapter(name)`` manage tenants at runtime with zero
        retraces (the banks are runtime arguments, the quant-table
        discipline); ``submit(adapter=name)`` binds a request.
        Per-tenant greedy output is token-identical to a dedicated
        single-adapter engine. Composes with ``paged=True`` (prefix
        reuse stays base-model-only), int8 (the LoRA delta stays fp32
        over the dequant base path) and speculative decoding (the
        draft proposes with the BASE model; verify/commit runs
        adapted — greedy commits stay the adapted model's own,
        acceptance degrades gracefully and is reported).
    max_adapters : int, optional
        Loadable adapter slots in the bank (default 8; bank slot 0 is
        the reserved all-zeros base adapter on top of these). Only
        meaningful with ``lora_rank``.
    decode_ticks : int, optional
        Fuse ``k`` decode iterations into one jitted scan per engine
        tick (docs/SERVING.md "Multi-tick decode"): one host sync and
        one dispatch amortize over up to k tokens per slot, with
        per-slot eos/budget stop handling moved IN-PROGRAM. Default 1
        is bitwise today's single-step path. Greedy output is
        token-identical across tick sizes; seeded sampling is
        bitwise-reproducible on a replayed admission schedule.
        Composes with ``paged``/int8 KV/LoRA/per-request sampling;
        rejected alongside ``speculative`` (that path already
        amortizes its sync over ``spec_k + 1`` tokens). Trades tail
        latency granularity for throughput: deadlines and eviction
        run at block (k-token) granularity.
    compute_dtype : str, optional
        ``"bfloat16"`` runs the generation programs with bf16
        parameters and activations (fp32 master weights stay the
        source of truth; rollovers re-cast with zero retraces) —
        softmax/LayerNorm statistics and the returned logits stay
        fp32, and the KV cache defaults to bf16 (int8 KV still
        composes via ``kv_dtype``). Held to the same teacher-forced
        bounded-divergence contract as int8. Default/``"float32"``
        is bitwise today's fp32 path.
    """

    def __init__(self, model, max_slots: int = 8, max_length=None,
                 max_new_tokens: int = 64, eos_id=None,
                 queue_limit: int = 256, timeout_ms=None,
                 prefill_bucketing=None, cache_dtype=None,
                 paged: bool = False, page_size: int = 16,
                 n_pages=None, prefill_chunk=None,
                 prefix_cache: bool = True, quantize=None,
                 kv_dtype=None, draft_model=None, spec_k: int = 4,
                 speculative=None, mesh_layout=None, mesh=None,
                 lora_rank=None, max_adapters=None,
                 decode_ticks: int = 1, compute_dtype=None):
        self.paged = bool(paged)
        support = _refuse_unsupported(model, {
            "paged": ("paged" if paged else "dense_cache", True,
                      bool(paged)),
            "prefix_cache": ("prefix_cache", paged and prefix_cache,
                             prefix_cache),
            "quantize": ("quantize", quantize is not None, quantize),
            "kv_dtype": ("kv_dtype", kv_dtype is not None, kv_dtype),
            "cache_dtype": ("cache_dtype", cache_dtype is not None,
                            cache_dtype),
            "draft_model": ("speculative", draft_model is not None,
                            draft_model),
            "speculative": ("speculative", bool(speculative),
                            speculative),
            "decode_ticks": ("decode_ticks", int(decode_ticks) != 1,
                             decode_ticks),
            "mesh_layout": ("mesh_layout", mesh_layout is not None,
                            mesh_layout),
            "mesh": ("mesh_layout", mesh is not None, mesh),
            "lora_rank": ("lora", lora_rank is not None, lora_rank),
            "max_adapters": ("lora", max_adapters is not None,
                             max_adapters),
            "compute_dtype": ("compute_dtype", True,
                              compute_dtype or "float32"),
        })
        #: a model that generates by diffusion over blocks states it
        #: (``block_diffusion``); the engine then runs the block-diffusion
        #: step path, chosen here once
        bd = (support or {}).get("block_diffusion")
        self._blockdiff = dict(bd) if bd else None
        if self._blockdiff is not None and not paged:
            raise ValueError("a block-diffusion model is served paged")
        self._finish_ahead = self._finish_tick if self._blockdiff is None \
            else self._finish_denoise
        if speculative is None:
            speculative = draft_model is not None
        self.speculative = bool(speculative)
        if self.speculative and draft_model is None:
            raise ValueError(
                "speculative=True needs a draft_model (a second, "
                "smaller decoder from the same model_zoo family)")
        if draft_model is not None and not self.speculative:
            raise ValueError(
                "draft_model without speculative decoding is inert; "
                "drop it or pass speculative=True")
        self.draft = draft_model
        self.spec_k = int(spec_k)
        self.decode_ticks = int(decode_ticks)
        if self.decode_ticks < 1:
            raise ValueError(f"decode_ticks must be >= 1, got "
                             f"{decode_ticks}")
        if self.decode_ticks > 1 and self.speculative:
            raise ValueError(
                "decode_ticks > 1 does not compose with speculative "
                "decoding: the speculative iteration already amortizes "
                "one host sync over up to spec_k+1 tokens — pick one "
                "amortization scheme")
        if quantize not in (None, "int8_weights"):
            raise ValueError(
                f"unsupported quantize={quantize!r} (only "
                f"'int8_weights')")
        if kv_dtype is not None:
            if cache_dtype is not None \
                    and str(cache_dtype) != str(kv_dtype):
                raise ValueError(
                    f"kv_dtype={kv_dtype!r} conflicts with "
                    f"cache_dtype={cache_dtype!r}")
            if str(kv_dtype) != "int8":
                raise ValueError(
                    f"unsupported kv_dtype={kv_dtype!r} (only 'int8'; "
                    f"use cache_dtype for plain float layouts)")
            cache_dtype = kv_dtype
        self.quantize = quantize
        if quantize is not None:
            if not callable(getattr(model, "quantize_params", None)):
                raise TypeError(
                    "quantize='int8_weights' needs a model exposing "
                    "quantize_params() (gluon.model_zoo.gpt.GPTModel)")
            t0 = telemetry.clock()
            model.quantize_params()
            telemetry.hist_since("serving.generate.quant.quantize", t0)
            n, saved = model.quantized_param_stats() \
                if callable(getattr(model, "quantized_param_stats",
                                    None)) else (0, 0)
            telemetry.counter("serving.generate.quant.params", n)
            telemetry.counter("serving.generate.quant.bytes_saved",
                              saved)
        if compute_dtype not in (None, "float32", "bfloat16"):
            raise ValueError(
                f"unsupported compute_dtype={compute_dtype!r} (only "
                f"'float32' or 'bfloat16')")
        self.compute_dtype = "float32" if compute_dtype is None \
            else str(compute_dtype)
        self._cast_shadow = False
        if self.compute_dtype == "bfloat16":
            if mesh_layout is not None:
                raise ValueError(
                    "compute_dtype='bfloat16' does not compose with "
                    "mesh_layout yet: the cast shadow buffers are not "
                    "re-placed over the mesh")
            # a model with ``cast_compute_params`` keeps fp32 masters:
            # the closures consume a bf16 shadow list installed as
            # runtime arguments (the int8 quant-table discipline —
            # load_weights re-casts with zero retraces). The draft
            # model, if any, stays fp32: its logits only steer
            # proposals. A model without it that STATES bfloat16
            # compute holds bfloat16 leaves itself: no shadow is kept.
            cast = getattr(model, "cast_compute_params", None)
            self._cast_shadow = callable(cast)
            if self._cast_shadow:
                t0 = telemetry.clock()
                cast("bfloat16")
                telemetry.hist_since("serving.generate.cast.cast", t0)
            elif support is None:
                raise TypeError(
                    "compute_dtype='bfloat16' needs a model exposing "
                    "cast_compute_params() "
                    "(gluon.model_zoo.gpt.GPTModel)")
        self.lora_enabled = lora_rank is not None
        if max_adapters is not None and not self.lora_enabled:
            raise ValueError(
                "max_adapters without lora_rank is inert; pass "
                "lora_rank= to arm the batched adapter bank")
        if self.lora_enabled:
            self.lora_rank = int(lora_rank)
            if self.lora_rank < 1:
                raise ValueError(f"lora_rank must be >= 1, got "
                                 f"{lora_rank}")
            self.max_adapters = 8 if max_adapters is None \
                else int(max_adapters)
            if self.max_adapters < 1:
                raise ValueError(f"max_adapters must be >= 1, got "
                                 f"{max_adapters}")
            for attr in ("arm_lora", "set_adapter", "clear_adapter"):
                if not callable(getattr(model, attr, None)):
                    raise TypeError(
                        f"lora_rank= needs a model exposing the "
                        f"batched-LoRA API (missing {attr!r}); see "
                        f"gluon.model_zoo.gpt.GPTModel")
            # bank slot 0 is the reserved all-zeros base adapter, so
            # the bank holds max_adapters + 1 slots; arming BEFORE
            # warmup() means the one structural retrace happens there
            model.arm_lora(self.max_adapters + 1, self.lora_rank)
        else:
            self.lora_rank = None
            self.max_adapters = 0
        api = ("init_paged_cache", "prefill_paged", "decode_step_paged") \
            if self.paged else ("init_cache", "prefill", "decode_step")
        if self._blockdiff is not None:
            api = ("init_paged_cache", "prefill_paged", "denoise_step_paged")
        if self.paged and prefix_cache:
            # a prefix hit peeks, binds a table row and copies on write
            api += ("peek_logits_paged", "bind_slot_paged",
                    "copy_page_paged")
        if self.speculative:
            api += (("verify_commit_paged",)
                    if self.paged else ("verify_commit",))
        if self.decode_ticks > 1:
            api += (("decode_multi_paged",)
                    if self.paged else ("decode_multi",))
        for attr in api:
            if not callable(getattr(model, attr, None)):
                raise TypeError(
                    f"GenerationEngine needs a decoder with the "
                    f"explicit-cache generation API (missing "
                    f"{attr!r}); see gluon.model_zoo.gpt.GPTModel")
        if self.speculative:
            if self.spec_k < 1:
                raise ValueError("spec_k must be >= 1")
            for attr in ("init_cache", "prefill", "propose_tokens",
                         "advance_len"):
                if not callable(getattr(draft_model, attr, None)):
                    raise TypeError(
                        f"draft_model needs the dense explicit-cache "
                        f"generation API (missing {attr!r}); see "
                        f"gluon.model_zoo.gpt.GPTModel")
            tv = getattr(model, "_vocab_size", None)
            dv = getattr(draft_model, "_vocab_size", None)
            if tv is not None and dv is not None and tv != dv:
                raise TypeError(
                    f"draft vocab {dv} != target vocab {tv}: "
                    f"speculative decoding needs one tokenizer — the "
                    f"draft proposes TARGET token ids")
        if int(max_slots) < 1:
            raise ValueError("max_slots must be >= 1")
        if int(max_new_tokens) < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.mesh_layout = mesh_layout
        self._part = None
        self._tp_heads = 0
        self._cache_sh = None  # canonical TP cache shardings (lazy)
        self._rep_sh = None    # replicated-over-mesh target (draft)
        self._step_collectives = None  # per-decode collective counts
        if mesh_layout is not None:
            if mesh_layout != "tp":
                raise ValueError(
                    f"unsupported mesh_layout={mesh_layout!r} (only "
                    f"'tp')")
            from .. import parallel as _parallel
            from ..parallel import partition as _partition
            m = mesh if mesh is not None else _parallel.get_mesh()
            if m is None:
                raise RuntimeError(
                    "mesh_layout='tp' needs a mesh: pass mesh= or "
                    "call parallel.set_mesh first")
            tp = int(m.shape.get("tp", 1))
            if tp <= 1:
                raise ValueError(
                    "mesh_layout='tp' needs a mesh with a 'tp' axis "
                    "of size > 1 (parallel.make_mesh((1, n), "
                    "('dp', 'tp')))")
            n_heads = int(getattr(model, "_num_heads", 0) or 0)
            if n_heads <= 0:
                raise TypeError(
                    "mesh_layout='tp' needs a model exposing "
                    "_num_heads (the KV cache shards by heads; "
                    "gluon.model_zoo.gpt.GPTModel does)")
            if n_heads % tp:
                raise ValueError(
                    f"num_heads {n_heads} is not divisible by the tp "
                    f"axis size {tp}: the KV cache shards by heads")
            self._tp_heads = n_heads
            self._part = _partition.Partitioner("tp", mesh=m)
            from jax.sharding import NamedSharding as _NS, \
                PartitionSpec as _P
            self._rep_sh = _NS(m, _P())
            # place the parameters over the mesh BEFORE any closure
            # traces: the jitted generation programs read the params'
            # committed shardings and compile SPMD. The attention ops
            # trace on their jnp paths (ops.attention.jnp_only — a
            # pallas_call cannot ride inside an SPMD program), which
            # requires rebuilding any closures a prior single-device
            # user of this model left behind.
            if callable(getattr(model, "_gen_params", None)):
                model._gen_params()   # materialize deferred shapes
            self._part.place(model.collect_params())
            if callable(getattr(model, "set_force_jnp_attention",
                                None)):
                model.set_force_jnp_attention(True)
            # derived generation state (int8 quant tables computed
            # above from the then-unplaced weights; LoRA banks armed
            # above) re-places onto shardings riding the weights' axes
            if callable(getattr(model, "shard_generation_state",
                                None)):
                model.shard_generation_state(self._part)
            if self.speculative:
                # the DRAFT runs REPLICATED over the mesh while the
                # target is tp: its params/cache are small (a draft is
                # a truncation of the target), and replication keeps
                # propose/verify_commit at their 3-dispatch shape —
                # no cross-placement transfers inside the iteration
                _partition.Partitioner("dp", mesh=m).place(
                    draft_model.collect_params())
                if callable(getattr(draft_model,
                                    "set_force_jnp_attention", None)):
                    draft_model.set_force_jnp_attention(True)
            for axis, size in m.shape.items():
                telemetry.gauge(f"parallel.mesh.axis_sizes.{axis}",
                                int(size))
        else:
            # a single-device engine must UNDO a prior tp engine's
            # jnp-only tracing mark on a reused model (and draft) —
            # leaving it set would silently trace the slow jnp
            # attention paths instead of Pallas on a TPU box, with no
            # error or telemetry signal
            for mdl in (model, draft_model):
                if mdl is not None and callable(
                        getattr(mdl, "set_force_jnp_attention", None)):
                    mdl.set_force_jnp_attention(False)
        self.model = model
        self.max_slots = int(max_slots)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.queue_limit = max(1, int(queue_limit))
        self.timeout_ms = timeout_ms
        self._s_max = int(max_length) if max_length is not None \
            else int(model.max_length)
        #: usable sequence capacity. A speculative engine reserves a
        #: ``spec_k`` scratch margin at the top of every cache row: a
        #: verify step writes up to ``len + spec_k`` K/V entries before
        #: knowing how many will commit, and that write must never
        #: clamp/wrap — rejected entries sit above the ``len``
        #: waterline (never attended, overwritten next step) instead
        self._s_cap = self._s_max - self.spec_k if self.speculative \
            else self._s_max
        if self._s_cap < 2:
            raise ValueError(
                f"max_length {self._s_max} leaves no usable capacity "
                f"after the spec_k={self.spec_k} verify margin")
        policy = as_policy(prefill_bucketing)
        if cache_dtype is None and self.compute_dtype == "bfloat16":
            # bf16 compute writes bf16 K/V — default the cache to
            # match (half the HBM and bandwidth); int8 KV still
            # composes by passing cache_dtype/kv_dtype="int8"
            cache_dtype = "bfloat16"
        self._cache_dtype = cache_dtype
        if self.paged:
            ps = int(page_size)
            if ps < 1 or (ps & (ps - 1)):
                raise ValueError("page_size must be a power of two")
            if self._s_max % ps:
                raise ValueError(
                    f"page_size {ps} must divide max_length "
                    f"{self._s_max}")
            self._ps = ps
            self._p_max = self._s_max // ps
            chunk = int(prefill_chunk) if prefill_chunk is not None \
                else min(self._s_max, max(32, 2 * ps))
            if chunk % ps or not 0 < chunk <= self._s_max:
                raise ValueError(
                    f"prefill_chunk {chunk} must be a positive "
                    f"multiple of page_size {ps} within the cache "
                    f"capacity {self._s_max}")
            widest = (support or {}).get("prefill_chunk_max")
            if widest is not None and chunk > int(widest):
                raise ValueError(
                    f"prefill_chunk={chunk} is wider than "
                    f"{type(model).__name__} takes ({widest}): a chunk "
                    f"is written into its window layers' ring before it "
                    f"is attended")
            self._chunk = chunk
            #: a model that states ``prefill_last`` is told, with each
            #: chunk, whether it is the prompt's last (it may then run
            #: less than its whole depth on the others); any other is
            #: called with the arguments it always was
            self._prefill_last = bool(
                (support or {}).get("prefill_last"))
            if policy is None:
                policy = BucketingPolicy(mode="pow2",
                                         min_size=max(8, ps))
            self.policy = policy.clamped(self._s_max)
            for w in self.policy.sizes(self._chunk):
                if w <= self._chunk and w % ps:
                    raise ValueError(
                        f"prefill bucket {w} is not a multiple of "
                        f"page_size {ps} (page-granular scatter needs "
                        f"aligned widths)")
            #: default pool = the dense cache's HBM budget exactly
            #: (max_slots full-length rows) + the scrap page; prefix
            #: sharing turns the saving into extra effective slots
            np_total = int(n_pages) if n_pages is not None \
                else self.max_slots * self._p_max + 1
            self._pool = paging.PagePool(np_total)
            self._prefix = paging.PrefixIndex(self._pool, ps) \
                if prefix_cache else None
            self._blocked: collections.deque = collections.deque()
            self._seq = 0
            self._chunks_this_iter = 0
            self._cache = model.init_paged_cache(
                self.max_slots, np_total, ps, self._s_max,
                dtype=cache_dtype)
        else:
            if policy is None:
                policy = BucketingPolicy(mode="pow2", min_size=8)
            self.policy = policy.clamped(self._s_max)
            self._cache = model.init_cache(self.max_slots, self._s_max,
                                           dtype=cache_dtype)
        # COMMIT the cache to its device up front: a fresh
        # ``init_cache`` holds uncommitted arrays, a jitted step's
        # outputs are committed — and the pjit C++ fast path caches
        # executables PER INPUT-SHARDING SIGNATURE, so the first
        # admission after the first step would silently recompile
        # every prefill bucket a second time (~1s stalls that no
        # trace counter sees; found by driving the speculative engine
        # under JAX_LOG_COMPILES)
        self._cache = self._commit(self._cache)
        #: the draft model's OWN cache: dense even under a paged
        #: target (the draft is small — its whole cache costs a
        #: fraction of one target layer's pool) and fp32 (its logits
        #: only steer proposals; the target's verify is what commits)
        self._draft_cache = None if not self.speculative \
            else self._commit_draft(
                draft_model.init_cache(self.max_slots, self._s_max))
        #: per-slot sampling state, threaded as runtime (B,) vectors
        #: through the fixed-shape sampling/verify programs — a mixed
        #: greedy/stochastic batch runs ONE compiled program
        self._temps = onp.zeros((self.max_slots,), "f4")
        self._topks = onp.zeros((self.max_slots,), "i4")
        self._topps = onp.ones((self.max_slots,), "f4")
        self._keys = onp.zeros((self.max_slots, 2), "u4")
        self._n_sampling = 0   # active slots with temperature > 0
        self._samplers = None  # jitted ops/sampling.py programs (lazy)
        #: the decode tick in flight (docs/SERVING.md "The tick in
        #: flight"): dispatched, its pick not yet on the host. Touched
        #: under ``_gen_lock`` only
        self._ahead: _Tick | None = None
        #: per-slot LoRA bank indices, threaded as a runtime (B,)
        #: vector through every fixed-shape generation program — a
        #: batch mixing any tenants (base rows included) runs ONE
        #: compiled program; the vector is data, never shape
        self._adapter_idx = onp.zeros((self.max_slots,), "i4")
        #: host-side adapter registry: name -> _Adapter (bank slot,
        #: pin count, deferred-unload flag). _lora_lock is a LEAF
        #: lock — taken from submit, stream-finish callbacks and the
        #: swap-locked load/unload paths, never around a model call
        self._lora_lock = threading.Lock()
        self._lora_reg: dict = {}
        self._lora_free = list(range(1, self.max_adapters + 1))
        #: freed-but-not-yet-zeroed bank slots: eviction paths run in
        #: stream-finish callbacks that may hold the worker's
        #: ``_gen_lock``, where ``model.clear_adapter`` (a
        #: read-modify-write of the banks) cannot be serialized
        #: against a concurrent ``set_adapter`` — so the factors are
        #: zeroed lazily inside the NEXT ``load_adapter``'s
        #: ``_gen_exclusive`` window (a freed slot is unreachable —
        #: no registry name maps to it — so this is hygiene, never
        #: correctness, and bank bytes are preallocated either way)
        self._lora_stale: set = set()
        self._kv_int8 = "k_scale" in self._cache
        if self._kv_int8:   # quant.* telemetry only for quantized
            # engines — an fp32 fleet must not populate the namespace
            kv_bytes = sum(
                int(a.size) * a.dtype.itemsize
                for key in ("k", "v", "k_scale", "v_scale")
                for a in self._cache.get(key, ()))
            telemetry.gauge("serving.generate.quant.kv_bytes_per_slot",
                            kv_bytes // self.max_slots)
        self._slots: list = [None] * self.max_slots
        self._n_active = 0
        #: serializes every model call (worker admit/step, sync-mode
        #: generation, warmup) — jit TRACING mutates shared parameter
        #: bindings, so two threads may never trace concurrently
        self._gen_lock = threading.Lock()
        #: count of threads waiting on _gen_lock via _gen_exclusive —
        #: the worker's step loop yields between steps when non-zero
        #: (unfair lock handoff would otherwise starve them)
        self._gen_waiters = 0
        self._lock = threading.Lock()
        self._closed = False
        #: set (to a ReplicaFailedError) when the generator thread died
        #: from an unexpected error — a broken replica, not a close()
        self._failure: ReplicaFailedError | None = None
        self._sync = not _serving_enabled()
        self._worker = None if self._sync \
            else _GenWorker(self, self.queue_limit)
        _live_engines.add(self)

    @property
    def precision(self) -> str:
        """The replica's numeric configuration — ``"fp32"``,
        ``"int8_weights"``, ``"int8_kv"``, ``"bf16"`` or a ``+``-join
        of the armed reductions. Router fleets must be
        precision-homogeneous: retries re-run a request on another
        replica and the bounded-divergence contract only holds within
        ONE reduced-precision configuration."""
        parts = []
        if self.compute_dtype == "bfloat16":
            parts.append("bf16")
        if self.quantize is not None:
            parts.append(self.quantize)
        if self._kv_int8:
            parts.append("int8_kv")
        return "+".join(parts) if parts else "fp32"

    @property
    def speculation(self) -> str:
        """The replica's speculative-decoding configuration — ``"off"``
        or ``"k=<spec_k>:draft=<type>:<layers>L-<units>u"``. Router
        fleets must be speculation-homogeneous (the precision-
        homogeneity rule's sibling): a retried STOCHASTIC request
        replays its seed, and its committed stream depends on the
        draft/spec_k key-consumption schedule — mixing configurations
        would make the retry's tokens depend on which replica caught
        it."""
        if not self.speculative:
            return "off"
        d = self.draft
        return (f"k={self.spec_k}:draft={type(d).__name__}:"
                f"{getattr(d, '_num_layers', '?')}L-"
                f"{getattr(d, '_units', '?')}u")

    @property
    def lora(self) -> str:
        """The replica's batched-LoRA configuration — ``"off"`` or
        ``"rank=<r>:max=<n>"``. Router fleets must be LoRA-config-
        homogeneous (the precision/speculation rule's sibling): a
        retried request re-runs ``adapter=`` on another replica, and
        the binding only means the same thing when every replica's
        bank has the same shape."""
        if not self.lora_enabled:
            return "off"
        return f"rank={self.lora_rank}:max={self.max_adapters}"

    @property
    def mesh_config(self) -> str:
        """The replica's mesh-parallel configuration — ``"off"`` or
        ``"tp:<axis>=<size>x..."``. Router fleets must be
        mesh-homogeneous (the precision/speculation/LoRA rule's
        sibling): a cross-replica retry must replay the IDENTICAL
        numeric config, and a tp engine's logits differ from an
        unsharded replica's in the partial-sum reduction order — a
        mixed fleet would make a retried stream's tokens depend on
        which replica caught it."""
        if self._part is None:
            return "off"
        mesh = self._part.mesh
        axes = "x".join(f"{a}={int(n)}" for a, n in mesh.shape.items())
        return f"{self.mesh_layout}:{axes}"

    def capabilities(self) -> str:
        """One-line summary of the engine's configured capabilities —
        quoted by every ``submit`` kwarg-validation error so a caller
        holding the wrong engine sees what this one actually does."""
        return (f"precision={self.precision}, "
                f"speculation={self.speculation}, lora={self.lora}, "
                f"paged={self.paged}, mesh={self.mesh_config}")

    def _submit_error(self, arg, value, why):
        """The shared ``submit`` kwarg-validation error: names the
        offending argument AND the engine's configured capabilities
        (a bare TypeError told the caller neither)."""
        return TypeError(
            f"submit() {arg}={value!r} not supported: {why} "
            f"(engine capabilities: {self.capabilities()})")

    # -- multi-tenant LoRA (docs/SERVING.md "Multi-tenant LoRA") --------
    @property
    def adapters(self):
        """Sorted names of the loaded adapters (unload-pending ones —
        pinned by in-flight requests — excluded: they reject new
        submits already)."""
        with self._lora_lock:
            return sorted(name for name, ad in self._lora_reg.items()
                          if not ad.unloading)

    def has_adapter(self, name) -> bool:
        """Membership check for ONE adapter name (loaded and not
        unload-pending) — a single dict lookup under the leaf lock.
        The Router's per-submit validation hot path: it must not
        materialize and sort the whole registry per replica per
        request just to answer a membership question."""
        with self._lora_lock:
            ad = self._lora_reg.get(name)
            return ad is not None and not ad.unloading

    def _lora_active_locked(self):
        """Loaded-adapter count for the ``lora.active_adapters``
        gauge — unload-pending names excluded, matching the
        :attr:`adapters` property and the OBSERVABILITY.md row (they
        already reject new submits). Call under ``_lora_lock``."""
        return sum(1 for ad in self._lora_reg.values()
                   if not ad.unloading)

    def load_adapter(self, name, params, alpha=1.0):
        """Load (or refresh) one tenant's LoRA adapter under the swap
        lock, with ZERO retraces: the stacked banks are runtime
        arguments of the jitted closures, so installing the factors is
        a step-boundary array swap — the ``load_weights`` discipline
        applied to the tenant axis. ``params`` is the flat
        ``{"layers.<li>.<proj>.A"/".B": array}`` mapping of
        ``GPTModel.set_adapter``. Refreshing an existing name keeps
        its bank slot; in-flight requests bound to it simply continue
        on the new factors (the documented rollover semantics)."""
        if not self.lora_enabled:
            raise TypeError(
                f"load_adapter({name!r}): this engine has no LoRA "
                f"bank (constructed without lora_rank=) (engine "
                f"capabilities: {self.capabilities()})")
        if self._closed:
            raise EngineClosedError("load_adapter on a closed engine")
        t0 = telemetry.clock()
        with self._gen_exclusive():
            with self._lora_lock:
                ad = self._lora_reg.get(name)
                if ad is not None and ad.unloading:
                    raise ValueError(
                        f"adapter {name!r} is unloading (pinned by "
                        f"in-flight requests); retry once they finish")
                if ad is None and not self._lora_free:
                    raise ValueError(
                        f"adapter capacity exhausted: {self.max_adapters} "
                        f"slots all hold live adapters "
                        f"({sorted(self._lora_reg)!r}, unload-pending "
                        f"included)")
                idx = ad.idx if ad is not None \
                    else self._lora_free[0]
                stale = self._lora_stale
                self._lora_stale = set()
            # the model calls happen under _gen_exclusive only (never
            # the leaf lock): a worker step is between iterations
            # here. First zero any slots freed since the last swap
            # window (evicted tenants' factors must not linger in the
            # bank), then install the new factors.
            for s in stale:
                # idx included even though set_adapter overwrites it:
                # if the install's validation raises, the slot must
                # not keep the evicted tenant's factors
                self.model.clear_adapter(s)
            self.model.set_adapter(idx, params, alpha=alpha)
            with self._lora_lock:
                if self._lora_reg.get(name) is None:
                    # fresh load — or a refresh whose name vanished
                    # between the two lock sections (a concurrent
                    # unload completing via a pin drop takes only the
                    # leaf lock): the factors ARE installed in `idx`,
                    # so re-register instead of returning success for
                    # an adapter that is no longer loaded
                    self._lora_free.remove(idx)
                    self._lora_reg[name] = _Adapter(name, idx)
                # the slot holds a live install now: a concurrent
                # eviction in the window above must not leave it
                # marked for the next swap's lazy zeroing
                self._lora_stale.discard(idx)
                n_active = self._lora_active_locked()
        telemetry.hist_since("serving.generate.lora.load", t0)
        telemetry.counter("serving.generate.lora.adapters_loaded")
        telemetry.gauge("serving.generate.lora.active_adapters",
                        n_active)
        return self

    def unload_adapter(self, name) -> bool:
        """Unload an adapter. Returns True when the bank slot was
        freed immediately; False when in-flight requests still pin it
        — the unload is DEFERRED: the name stops accepting new
        submits now, and the slot is freed when the last pinned
        request finishes (``lora.adapters_evicted`` counts the actual
        eviction either way)."""
        if not self.lora_enabled:
            raise TypeError(
                f"unload_adapter({name!r}): this engine has no LoRA "
                f"bank (constructed without lora_rank=) (engine "
                f"capabilities: {self.capabilities()})")
        with self._lora_lock:
            ad = self._lora_reg.get(name)
            if ad is None:
                raise ValueError(
                    f"unknown adapter {name!r} (loaded: "
                    f"{sorted(self._lora_reg)!r})")
            if ad.refs > 0:
                ad.unloading = True
                n_active = self._lora_active_locked()
                deferred = True
            else:
                del self._lora_reg[name]
                self._lora_free.append(ad.idx)
                self._lora_free.sort()
                self._lora_stale.add(ad.idx)
                n_active = self._lora_active_locked()
                deferred = False
        telemetry.gauge("serving.generate.lora.active_adapters",
                        n_active)
        if deferred:
            return False
        telemetry.counter("serving.generate.lora.adapters_evicted")
        return True

    def _pin_adapter(self, name):
        """Resolve an ``adapter=`` submit binding to its bank slot and
        pin it (in-flight requests keep their adapter loaded: an
        unload while they run is deferred, never a mid-stream tenant
        swap to base)."""
        with self._lora_lock:
            ad = self._lora_reg.get(name)
            if ad is None or ad.unloading:
                loaded = sorted(n for n, a in self._lora_reg.items()
                                if not a.unloading)
                raise ValueError(
                    f"submit() adapter={name!r} is not loaded on this "
                    f"engine (loaded adapters: {loaded!r}; engine "
                    f"capabilities: {self.capabilities()})")
            ad.refs += 1
            return ad.idx

    def _unpin_adapter(self, name):
        """Drop one request's pin; completes a deferred unload when
        the last pin goes (stream-finish callback — leaf lock only,
        safe under the worker's ``_gen_lock``)."""
        evicted = False
        with self._lora_lock:
            ad = self._lora_reg.get(name)
            if ad is None:
                return
            ad.refs -= 1
            if ad.refs <= 0 and ad.unloading:
                del self._lora_reg[name]
                self._lora_free.append(ad.idx)
                self._lora_free.sort()
                self._lora_stale.add(ad.idx)
                evicted = True
                n_active = self._lora_active_locked()
        if evicted:
            telemetry.counter("serving.generate.lora.adapters_evicted")
            telemetry.gauge("serving.generate.lora.active_adapters",
                            n_active)

    def _ensure_samplers(self):
        """The jitted ops/sampling.py programs (lazy — importing jax
        at engine construction is fine, but tracing belongs under
        ``_gen_lock`` at warmup/first use). Each actual trace counts
        ``ops.sampling.trace`` — the sampling analog of
        ``model.gpt.trace`` for the zero-steady-state-compile gates."""
        if self._samplers is None:
            import jax

            from ..ops import sampling as _smp

            def counted(fn, name):
                def wrapper(*args):
                    telemetry.counter("ops.sampling.trace")
                    tracing.flight.record("compile",
                                          what="ops.sampling")
                    return fn(*args)
                # the program's name in the profiler's trace
                # (jit_<name>; docs/OBSERVABILITY.md)
                wrapper.__name__ = wrapper.__qualname__ = name
                return wrapper

            self._samplers = {
                "sample": jax.jit(counted(_smp.sample_tokens,
                                          "sampling_sample")),
                "greedy": jax.jit(counted(_smp.greedy_tokens,
                                          "sampling_greedy")),
                "carry": jax.jit(counted(_smp.carry_tokens,
                                         "sampling_carry")),
            }
        return self._samplers

    def _warm_samplers(self, logits):
        """Compile every engine-level sampler shape the steady state
        can hit: the (1, V) first-token pick, the (B, V) decode-step
        pick, an all-greedy tick's (B, V) argmax and the merge of that
        pick with the host's tokens for the next tick, the last two on
        the warm-up tick's own ``logits`` (as the live tick's are
        placed: a committed and an uncommitted input compile apart).
        The speculative draft/accept math lives inside the model's
        fused closures (``_warmup_spec``)."""
        smp = self._ensure_samplers()
        b, vocab = self.max_slots, int(logits.shape[-1])
        if self._part is None:
            smp["carry"](smp["greedy"](logits), onp.zeros((b,), "i4"),
                         onp.zeros((b,), "?"))
        smp["sample"](onp.zeros((1, 2), "u4"),
                      onp.zeros((1, vocab), "f4"),
                      onp.zeros((1,), "f4"),
                      onp.zeros((1,), "i4"), onp.ones((1,), "f4"))
        smp["sample"](onp.zeros((b, 2), "u4"),
                      onp.zeros((b, vocab), "f4"),
                      onp.zeros((b,), "f4"),
                      onp.zeros((b,), "i4"), onp.ones((b,), "f4"))

    def _commit(self, cache):
        """Pin a cache pytree to its device(s) (see the constructor
        note: committed and uncommitted inputs compile SEPARATE pjit
        executables, and caches cross that line after their first
        donated step). The target must be EXPLICIT — a bare
        ``device_put`` preserves the uncommitted state. Under
        ``mesh_layout="tp"`` the target is the partitioner's cache
        sharding (K/V over the heads axis) instead of one device."""
        import jax
        if self._part is not None:
            return self._part.place_cache(cache, self._tp_heads)
        return jax.device_put(cache, jax.devices()[0])

    def _recommit(self, cache):
        """TP mode: pin a jitted step's returned cache back onto the
        canonical heads-sharded placement, so every program always
        sees ONE input-sharding signature (GSPMD is free to pick a
        different output sharding, and the pjit executable cache keys
        on input shardings — a drifting cache would silently compile
        a second executable per program). The shardings pytree is
        computed ONCE (the cache's shapes are fixed for the engine's
        lifetime) so the per-step cost is one device_put that is a
        no-op copy-wise when the shardings already match. Entirely
        outside TP mode."""
        if self._part is None:
            return cache
        import jax
        if self._cache_sh is None:
            self._cache_sh = self._part.cache_shardings(cache,
                                                        self._tp_heads)
        return jax.device_put(cache, self._cache_sh)

    def _commit_draft(self, cache):
        """Commit the DRAFT model's dense cache: replicated over the
        whole mesh under ``mesh_layout="tp"`` (the replicated-draft
        rule — every device holds the full draft state, so the fused
        propose program runs SPMD with zero cross-device traffic),
        one device otherwise."""
        import jax
        if self._part is not None:
            return jax.device_put(cache, self._rep_sh)
        return jax.device_put(cache, jax.devices()[0])

    def _recommit_draft(self, cache):
        """TP mode: pin a draft step's returned cache back onto the
        replicated placement (the draft analog of :meth:`_recommit` —
        one input-sharding signature per program)."""
        if self._part is None:
            return cache
        import jax
        return jax.device_put(cache, self._rep_sh)

    def _emit_collectives(self):
        """Bump the ``parallel.collectives.*`` counters by the decode
        program's per-step collective counts (measured once from the
        compiled HLO at warmup — ``GPTModel.decode_hlo``)."""
        if self._step_collectives:
            for kind, n in self._step_collectives.items():
                telemetry.counter(f"parallel.collectives.{kind}", n)

    # -- lifecycle -----------------------------------------------------
    @contextlib.contextmanager
    def _gen_exclusive(self):
        """Acquire ``_gen_lock`` as a registered waiter. The worker's
        step loop re-acquires the lock back to back and Python lock
        handoff is unfair — without the waiter signal a rollover,
        warmup, or fault-injection caller can starve for as long as a
        whole generation under continuous decode traffic."""
        with self._lock:
            self._gen_waiters += 1
        try:
            with self._gen_lock:
                # a step boundary has nothing in flight: whoever holds
                # the lock swaps weights, traces or kills on a quiet
                # device queue
                self._drain_ahead()
                yield
        finally:
            with self._lock:
                self._gen_waiters -= 1

    def warmup(self):
        """Compile the steady state ahead of traffic: one prefill per
        sequence bucket the policy can produce, plus the decode step.
        After this, serving any traffic mix triggers zero new traces
        (``model.gpt.trace`` telemetry stays flat)."""
        # compile against a THROWAWAY cache of the live cache's shapes
        # (the jit cache keys on shapes/dtypes, so the programs carry
        # over): the worker thread may already be serving self._cache,
        # and prefill/decode_step DONATE their cache argument — touching
        # the live one here would race the step loop into a
        # donated-buffer error. _gen_lock additionally keeps our traces
        # mutually exclusive with any in-flight worker step.
        with self._gen_exclusive():
            if self._closed:
                # close() won the lock first: compiling against a
                # closing engine is wasted work at best and a
                # donated-buffer race at worst — bail cleanly
                return self
            if self.paged:
                self._warmup_paged()
                self._warmup_telemetry()
                return self
            cache = self._commit(self.model.init_cache(
                self.max_slots, self._s_max, dtype=self._cache_dtype))
            for sb in self.policy.sizes(self._s_cap - 1):
                toks = onp.zeros((1, sb), "i4")
                _, cache = self.model.prefill(toks, [sb], cache,
                                              slots=[0])
                if self._part is not None:
                    # pin back to the canonical heads-sharded layout
                    # so every program warms against the ONE input
                    # sharding signature the live path will feed it
                    cache = self._recommit(cache)
            lg, cache = self.model.decode_step(
                self._tick_tokens(onp.zeros((self.max_slots,), "i4")),
                cache)
            cache = self._recommit(cache)
            if self.decode_ticks > 1:
                cache = self._warmup_multi(cache)
            self._warm_samplers(lg)
            if self.speculative:
                self._warmup_spec(cache)
            self._warmup_telemetry()
        return self

    def _warmup_multi(self, cache):
        """Compile the fused multi-tick decode scan against the
        throwaway cache. ONE program serves every traffic mix — the
        budget/eos/sampling vectors are runtime data — so this single
        warm call is the whole multi-tick steady state."""
        b, k = self.max_slots, self.decode_ticks
        fn = self.model.decode_multi_paged if self.paged \
            else self.model.decode_multi
        _, _, _, cache = fn(
            onp.zeros((b,), "i4"), onp.full((b,), k, "i4"), cache, k,
            onp.zeros((b, 2), "u4"), onp.zeros((b,), "f4"),
            onp.zeros((b,), "i4"), onp.ones((b,), "f4"),
            onp.full((b,), -1, "i4"))
        return self._recommit(cache)

    def _warmup_telemetry(self):
        """Post-warmup measurements (outside any serving window):
        the MEASURED per-device bytes of params + live cache
        (``serving.generate.per_device_bytes`` — under
        ``mesh_layout="tp"`` this is each device's SHARE; single-
        device engines report the full footprint), and, for a
        mesh-sharded engine, the decode program's per-step collective
        counts (compiled-HLO evidence feeding the
        ``parallel.collectives.*`` counters each tick)."""
        from ..parallel import partition as _partition
        if callable(getattr(self.model, "collect_params", None)):
            leaves = [p.data()._data
                      for p in self.model.collect_params().values()]
            telemetry.gauge(
                "serving.generate.per_device_bytes",
                _partition.per_device_bytes(leaves + [self._cache]))
        if self._part is not None \
                and callable(getattr(self.model, "decode_hlo", None)):
            if self.speculative and callable(
                    getattr(self.model, "verify_commit_hlo", None)):
                # a speculative engine's steady state runs the fused
                # verify_commit per iteration, never the single-token
                # decode — measure the program the counters describe
                text = self.model.verify_commit_hlo(
                    self.spec_k, self._cache, paged=self.paged)
            else:
                toks = onp.zeros((self.max_slots,), "i4")
                kw = {}
                if self.paged:
                    kw["active"] = onp.ones((self.max_slots,), "i4")
                text = self.model.decode_hlo(toks, self._cache, **kw)
            colls = _partition.hlo_collectives(text)
            self._step_collectives = {
                kind.replace("-", "_"): int(v["count"])
                for kind, v in colls.items()}

    def _warmup_spec(self, cache):
        """Compile the speculative steady state against throwaway
        caches: the draft's prefill buckets, the fused k-step propose
        (greedy AND sampled variants — traffic can flip between them
        as stochastic requests come and go), the fused
        verify+accept+advance (both variants), and the draft-rollback
        advance_len."""
        b, k = self.max_slots, self.spec_k
        zb = onp.zeros((b,), "i4")
        ones = onp.ones((b,), "i4")
        keys = onp.zeros((b, 2), "u4")
        tf = onp.zeros((b,), "f4")
        pf = onp.ones((b,), "f4")
        dcache = self._commit_draft(self.draft.init_cache(b,
                                                          self._s_max))
        for sb in self.policy.sizes(self._s_cap - 1):
            _, dcache = self.draft.prefill(
                onp.zeros((1, sb), "i4"), [sb], dcache, slots=[0])
            dcache = self._recommit_draft(dcache)
        dt, dcache = self.draft.propose_tokens(zb, dcache, k)
        dcache = self._recommit_draft(dcache)
        dt, q, _, dcache = self.draft.propose_tokens(
            zb, dcache, k, keys=keys, temps=tf, top_ks=zb, top_ps=pf)
        dcache = self._recommit_draft(dcache)
        dcache = self._recommit_draft(self.draft.advance_len(zb,
                                                             dcache))
        vc = self.model.verify_commit_paged if self.paged \
            else self.model.verify_commit
        _, _, cache = vc(zb, dt, ones, cache)
        cache = self._recommit(cache)
        _, _, _, cache = vc(zb, dt, ones, cache, q=q, keys=keys,
                            temps=tf, top_ks=zb, top_ps=pf)

    def _chunk_widths(self):
        """Every width ``_admit`` can give a chunk. A prefix hit starts
        a prompt's chunks at any page, so near the cache's end a chunk
        may shrink to any page multiple; without a prefix index chunks
        start at multiples of the chunk width, and only the one that
        reaches the cache's end is narrower."""
        if self._prefix is not None:
            return list(range(self._ps, self._chunk + 1, self._ps))
        tail = self._s_max % self._chunk
        return [tail, self._chunk] if tail else [self._chunk]

    def _last_kw(self, last: bool) -> dict:
        """``last=`` for a model that asked to be told (the
        ``prefill_last`` key of its ``generation_support``), nothing for
        any other."""
        return {"last": last} if self._prefill_last else {}

    def _warmup_paged(self):
        """Compile the paged steady state against a throwaway cache:
        one fresh-prefill program per bucket <= the chunk width, one
        chunk program per width a chunk can take (``_chunk_widths``:
        tail chunks shrink near the cache end), the decode step, the peek
        (prefix-hit) path, and the table-bind / page-copy (COW)
        helpers. Physical page ids are DATA, not shape — id choice
        here is arbitrary."""
        cache = self._commit(self.model.init_paged_cache(
            self.max_slots, self._pool.n_pages, self._ps, self._s_max,
            dtype=self._cache_dtype))
        row = onp.ones((self._p_max,), "i4")
        for sb in self.policy.sizes(self._chunk):
            if sb > self._chunk:
                continue
            _, cache = self.model.prefill_paged(
                onp.zeros((1, sb), "i4"), sb, 0, row, cache,
                fresh=True, **self._last_kw(True))
            cache = self._recommit(cache)
        for w in self._chunk_widths():
            # a model told which chunk is last has a program for each
            for kw in ([{"last": False}, {"last": True}]
                       if self._prefill_last else [{}]):
                _, cache = self.model.prefill_paged(
                    onp.zeros((1, w), "i4"), w, 0, row, cache, start=0,
                    **kw)
                cache = self._recommit(cache)
        if self._blockdiff is not None:
            b, blk = self.max_slots, self._blockdiff["block_length"]
            self.model.denoise_step_paged(
                onp.ones((b,), "i4"), onp.ones((b,), "i4"),
                onp.zeros((b, blk), "i4"), onp.zeros((b, blk), "?"),
                onp.zeros((b,), "i4"), onp.ones((b, self._p_max), "i4"),
                cache)
            return
        lg, cache = self.model.decode_step_paged(
            self._tick_tokens(onp.zeros((self.max_slots,), "i4")),
            onp.ones((self.max_slots,), "i4"), cache)
        cache = self._recommit(cache)
        if self.decode_ticks > 1:
            cache = self._warmup_multi(cache)
        if self._prefix is not None:
            self.model.peek_logits_paged(0, 0, cache)
            cache = self._recommit(self.model.bind_slot_paged(0, row, 1,
                                                              cache))
            cache = self._recommit(self.model.copy_page_paged(1, 1,
                                                              cache))
        self._warm_samplers(lg)
        if self.speculative:
            self._warmup_spec(cache)

    def load_weights(self, source, strict: bool = True):
        """Zero-downtime weight rollover: swap the model's parameter
        buffers from a committed checkpoint while traffic is live.

        ``source`` is a checkpoint path (a ``CheckpointManager`` root —
        latest committed step wins — or one step directory) or an
        in-memory ``{name: array}`` mapping. The swap happens at a
        decode-STEP boundary under ``_gen_lock``: in-flight slots keep
        their KV cache and continue decoding (their next token simply
        comes from the new weights), queued requests are untouched, and
        nothing recompiles — the jitted prefill/decode closures take
        parameter buffers as runtime arguments, so installing
        same-shape/dtype buffers into the live parameter NDArrays
        changes no trace (``model.gpt.trace`` stays flat; asserted in
        tests). Sharded parameters keep their placement via
        ``device_put`` onto the old buffer's sharding.

        ``strict=True`` (default) requires the checkpoint names to
        cover the model's parameters exactly; ``strict=False`` swaps
        the intersection. Shape mismatches always raise — before any
        buffer is touched, so a bad checkpoint can never leave the
        model half-swapped."""
        from .. import checkpoint as _ckpt
        if self._closed:
            raise EngineClosedError("load_weights on a closed engine")
        if isinstance(source, dict):
            new_params = source
        else:
            new_params, _meta = _ckpt.read_params(source)
        t0 = telemetry.clock()
        with self._gen_exclusive():  # step boundary: the worker is
            # between decode steps (and yields to us promptly — the
            # waiter signal), warmup is not tracing
            _ckpt.swap_param_buffers(self.model.collect_params(),
                                     new_params, strict=strict)
            if self.quantize is not None:
                # re-quantize from the fresh fp32 buffers INSIDE the
                # swap window: the quant tables are runtime args of
                # the jitted closures, so this installs new int8
                # weights with zero retraces — and a decode step may
                # never see new fp32 params with stale int8 tables
                tq = telemetry.clock()
                self.model.quantize_params()
                if self._part is not None:
                    # fresh tables follow the (still-placed) weights'
                    # axes — re-pin explicitly so the closures keep
                    # seeing the one canonical table sharding
                    self.model.shard_generation_state(self._part)
                telemetry.hist_since(
                    "serving.generate.quant.requantize", tq)
            if self.compute_dtype == "bfloat16" and self._cast_shadow:
                # re-cast the bf16 shadow buffers from the fresh fp32
                # masters INSIDE the swap window — same avals, so zero
                # retraces (the quant-table discipline); a decode step
                # may never see stale bf16 params after the swap
                tc = telemetry.clock()
                self.model.cast_compute_params("bfloat16")
                telemetry.hist_since(
                    "serving.generate.cast.recast", tc)
            if self.paged and self._prefix is not None:
                # the prefix cache holds K/V computed with the OLD
                # weights: a post-swap prefix hit would silently serve
                # stale attention context forever. Flush it (pages
                # pinned by in-flight slots stay alive via their own
                # refs — those slots finish on mixed weights, the same
                # documented in-flight tradeoff as the dense rollover)
                # and suppress registration of any prompt prefilled
                # before/across the swap — publishing mixed-weight K/V
                # would poison future requests.
                self._prefix.release_all()
                for s in self._slots:
                    if s is not None:
                        s.prompt = None
        telemetry.hist_since("serving.generate.swap", t0)
        telemetry.counter("serving.generate.weight_swaps")
        return self

    def close(self, timeout: float = 5.0):
        """Stop admission, finish ACTIVE generations and drain the
        queue under ``timeout``; past the deadline queued requests are
        rejected and still-active streams are finished early with
        ``finish_reason="closed"`` — nothing ever hangs. Idempotent;
        also invoked via ``atexit``."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        if self._worker is not None:
            self._worker.close(timeout)
            if not self._worker.is_alive():
                # thread provably dead: it can no longer touch slots
                self._close_active("closed")
        else:
            self._close_active("closed")  # sync mode: nothing active
        _live_engines.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close(timeout=0.5)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    @property
    def closed(self) -> bool:
        return self._closed

    # -- admission -----------------------------------------------------
    def _validate(self, prompt, max_new_tokens, eos_id):
        prompt = onp.asarray(prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError(
                f"prompt must be a non-empty 1-D token sequence, got "
                f"shape {prompt.shape}")
        if not onp.issubdtype(prompt.dtype, onp.integer):
            raise ValueError(f"prompt must hold token ids, got dtype "
                             f"{prompt.dtype}")
        if prompt.size > self._s_cap - 1:
            margin = "" if not self.speculative else \
                f" minus the spec_k={self.spec_k} verify margin"
            raise ValueError(
                f"prompt length {prompt.size} leaves no room to "
                f"generate (cache capacity {self._s_max}{margin})")
        max_new = self.max_new_tokens if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.paged:
            cap = min(int(prompt.size) + max_new, self._s_cap)
            need = -(-cap // self._ps)
            if need > self._pool.n_pages - 1:
                raise ValueError(
                    f"request needs up to {need} KV pages but the pool "
                    f"holds {self._pool.n_pages - 1} allocatable pages")
        eos = self.eos_id if eos_id is None else eos_id
        return prompt.astype("i4"), max_new, eos

    @staticmethod
    def _validate_sampling(temperature, top_k, top_p, seed):
        """Normalize/validate the per-request sampling knobs. Returns
        ``(temperature, top_k, top_p, seed)`` with the greedy/off
        defaults filled in (``0.0``, ``0``, ``1.0``, ``None``). Shared
        with the Router's pre-admission validation."""
        t = 0.0 if temperature is None else float(temperature)
        if not t >= 0.0:   # also rejects NaN
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy), got "
                f"{temperature!r}")
        k = 0 if top_k is None else int(top_k)
        if k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got "
                             f"{top_k!r}")
        p = 1.0 if top_p is None else float(top_p)
        if not 0.0 < p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1] (1 = off), got {top_p!r}")
        if seed is not None:
            seed = int(seed)
        return t, k, p, seed

    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               timeout_ms=None, temperature=None, top_k=None,
               top_p=None, seed=None, adapter=None,
               trace=None) -> GenerationStream:
        """Queue one prompt; returns a :class:`GenerationStream`.
        Raises :class:`EngineClosedError` / :class:`QueueFullError` /
        ``ValueError`` immediately instead of returning a stream that
        can never complete.

        ``temperature``/``top_k``/``top_p`` select per-request
        stochastic sampling (default greedy: ``temperature`` absent or
        0 — ``top_k``/``top_p`` are then ignored). ``seed`` pins the
        request's explicit PRNG key: the same seed yields a bitwise-
        identical token stream on every rerun of the same engine
        configuration, across engine restarts (docs/SERVING.md
        "Speculative decoding & sampling"). Without a seed, a fresh
        one is drawn per request.

        ``adapter`` names a loaded LoRA adapter (``load_adapter``) the
        request decodes under — per-slot runtime data, so any tenant
        mix shares the one compiled program; the adapter stays PINNED
        (unload defers) until the request finishes. Default: the base
        model.

        ``trace`` arms per-request tracing: ``True`` records the
        request's full lifecycle as spans readable via the stream's
        ``trace()``; ``False`` disables it even under
        ``MXTPU_TRACING=1``; ``None`` (default) follows the module
        flag; a ``tracing.Trace`` instance threads an existing trace
        through (the Router's cross-replica retries)."""
        if self._failure is not None:
            telemetry.counter("serving.generate.rejected_closed")
            raise ReplicaFailedError(str(self._failure),
                                     cause=self._failure.cause)
        if self._closed:
            telemetry.counter("serving.generate.rejected_closed")
            raise EngineClosedError("submit on a closed engine")
        prompt, max_new, eos = self._validate(prompt, max_new_tokens,
                                              eos_id)
        temp, tk, tp, seed = self._validate_sampling(
            temperature, top_k, top_p, seed)
        if temp > 0 and self._blockdiff is not None:
            raise self._submit_error(
                "temperature", temperature, "a block-diffusion step "
                "unmasks each block greedily")
        if adapter is not None and not self.lora_enabled:
            raise self._submit_error(
                "adapter", adapter, "this engine has no LoRA bank "
                "(constructed without lora_rank=)")
        key = None
        if temp > 0:
            telemetry.counter("serving.generate.sampling.requests")
            if seed is None:
                seed = int.from_bytes(os.urandom(4), "little")
            key = request_key(seed)
        aidx = 0
        if adapter is not None:
            aidx = self._pin_adapter(adapter)  # raises on unknown name
            telemetry.counter("serving.generate.lora.requests")
        telemetry.counter("serving.generate.requests")
        stream = GenerationStream(int(prompt.size))
        tr = tracing.start_trace(trace)
        if tr is not None:
            stream._trace = tr
            tr.event("submit", prompt_len=int(prompt.size),
                     max_new=max_new)
        if adapter is not None:
            # every stream finishes exactly once on every engine path
            # (the no-hung-stream contract) — the finish callback is
            # therefore the one place the pin reliably drops
            stream._watch(lambda _tok: None,
                          lambda _r, _e: self._unpin_adapter(adapter))
        tmo = self.timeout_ms if timeout_ms is None else timeout_ms
        now = time.monotonic()
        req = _GenRequest(
            prompt, max_new, eos, stream, telemetry.clock(), now,
            now + tmo / 1e3 if tmo is not None else None,
            temperature=temp, top_k=tk, top_p=tp, key=key,
            adapter_idx=aidx)
        if self._sync:  # MXTPU_SERVING=0: inline generation
            with self._gen_lock:
                self._admit_one(req)
                while self._n_active:
                    self._step()
                if self.paged and self._blocked:
                    # an idle sync engine can never unblock a stashed
                    # request (validated capacity makes this a pool-
                    # accounting bug, not a load condition) — reject
                    # rather than hang
                    self._blocked.popleft().stream._finish(
                        exc=QueueFullError(
                            "page pool exhausted for a synchronous "
                            "request"))
            return stream
        try:
            self._worker._queue.put_nowait(req)
        except queue.Full:
            telemetry.counter("serving.generate.rejected_full")
            if adapter is not None:
                # the stream never reaches the engine, so its finish
                # callback never fires — drop the pin here
                self._unpin_adapter(adapter)
            raise QueueFullError(
                f"request queue at queue_limit={self.queue_limit}") \
                from None
        telemetry.gauge("serving.generate.queue.depth",
                        self._worker._queue.qsize())
        if self._failure is not None:
            # the worker died while the request was being queued: its
            # drain may have missed this request — fail it ourselves
            stream._finish(exc=ReplicaFailedError(
                str(self._failure), cause=self._failure.cause))
        elif self._closed:
            # close() raced the put: its drain may have missed this
            # request — reject it ourselves (no-op if already handled)
            stream._finish(exc=EngineClosedError(
                "engine closed while the request was being queued"))
        return stream

    def generate(self, prompt, timeout=None, **kwargs) -> GenerationResult:
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, **kwargs).result(timeout)

    # -- scheduling (generator thread / sync mode) ---------------------
    def _admit(self, q):
        if self.paged:
            # page-starved requests wait in _blocked (FIFO — younger
            # queue entries must not starve an older blocked one).
            # queue_wait is recorded at the ACTUAL admission (or the
            # rejection), so time spent blocked on KV pages shows up
            # in the histogram an operator reads next to pages.free
            while self._blocked and self._n_active < self.max_slots:
                r = self._blocked[0]
                waited_ms = (time.monotonic() - r.t_enq) * 1e3
                if r.deadline is not None \
                        and time.monotonic() > r.deadline:
                    telemetry.hist("serving.generate.queue_wait",
                                   waited_ms)
                    telemetry.counter("serving.generate.timeouts")
                    r.stream._finish(exc=RequestTimeoutError(
                        f"request deadline expired while awaiting KV "
                        f"pages (waited {waited_ms:.1f} ms)"))
                    self._blocked.popleft()
                    continue
                if not self._try_admit_paged(r):
                    break
                telemetry.hist("serving.generate.queue_wait", waited_ms)
                if r.stream._trace is not None:
                    r.stream._trace.add_ms("queue", waited_ms,
                                           blocked=True)
                self._blocked.popleft()
        while self._n_active < self.max_slots \
                and not (self.paged and self._blocked):
            try:
                r = q.get_nowait()
            except queue.Empty:
                break
            self._admit_one(r)
        telemetry.gauge(
            "serving.generate.queue.depth",
            q.qsize() + (len(self._blocked) if self.paged else 0))

    def _admit_one(self, r: _GenRequest):
        """Admit ``r`` into a free slot and (dense mode) prefill it and
        emit its first token; paged mode allocates its pages and either
        peeks the first token off a fully-cached prefix or queues its
        prefill chunks. Called only at step boundaries."""
        waited_ms = (time.monotonic() - r.t_enq) * 1e3
        if r.deadline is not None and time.monotonic() > r.deadline:
            telemetry.hist("serving.generate.queue_wait", waited_ms)
            telemetry.counter("serving.generate.timeouts")
            r.stream._finish(exc=RequestTimeoutError(
                f"request expired in queue before prefill (waited "
                f"{waited_ms:.1f} ms)"))
            return
        try:
            self._admit_one_inner(r, waited_ms)
        except Exception as e:  # noqa: BLE001 — the worker is about to
            # die (_fail_all); without this the IN-HAND request —
            # already popped from the queue, not yet in a slot — would
            # be invisible to the cleanup and hang its caller forever
            r.stream._finish(exc=ReplicaFailedError(
                f"admission failed: {type(e).__name__}: {e}", cause=e))
            raise

    def _admit_one_inner(self, r: _GenRequest, waited_ms):
        if self.paged:
            # a page-starved request goes to _blocked: its queue_wait
            # is recorded when it actually admits (or rejects), not
            # here — the blocked time is the interesting part
            if self._try_admit_paged(r):
                telemetry.hist("serving.generate.queue_wait",
                               waited_ms)
                if r.stream._trace is not None:
                    r.stream._trace.add_ms("queue", waited_ms)
            else:
                if r.stream._trace is not None:
                    r.stream._trace.event("deferred", why="kv_pages")
                self._blocked.append(r)
            return
        telemetry.hist("serving.generate.queue_wait", waited_ms)
        tr = r.stream._trace
        if tr is not None:
            tr.add_ms("queue", waited_ms)
        slot = self._slots.index(None)
        n = int(r.prompt.size)
        if tr is not None:
            tr.event("admission", slot=slot, mode="dense")
        tracing.flight.record("gen.admit", slot=slot, mode="dense",
                              trace_id=r.stream.trace_id)
        sb = self.policy.bucket(n)
        padded = onp.zeros((1, sb), "i4")
        padded[0, :n] = r.prompt
        self._arm_sampling(slot, r)
        pt0 = time.perf_counter() if tr is not None else 0.0
        t0 = telemetry.clock()
        with tracing.phase("serve.prefill.dispatch", slot=slot, start=0,
                           tokens=n, fresh=True):
            with tracing.phase("serve.call.prefill_dense"):
                logits, self._cache = self.model.prefill(
                    padded, onp.asarray([n], "i4"), self._cache,
                    slots=onp.asarray([slot], "i4"),
                    **self._akw(self._adapter_idx[slot:slot + 1]))
            if self._part is not None:
                self._cache = self._recommit(self._cache)
            if self.speculative:
                # the draft mirrors the target's committed prefix from
                # the moment the slot exists — its own (dense) prefill
                # of the same padded prompt into the same slot row
                with tracing.phase("serve.call.draft_prefill"):
                    _, self._draft_cache = self.draft.prefill(
                        padded, onp.asarray([n], "i4"), self._draft_cache,
                        slots=onp.asarray([slot], "i4"))
                self._draft_cache = self._recommit_draft(
                    self._draft_cache)
        telemetry.hist_since("serving.generate.prefill", t0)
        telemetry.counter("serving.generate.prefills")
        if tr is not None:
            tr.add("prefill", pt0, slot=slot, tokens=n)
        with tracing.phase("serve.prefill.sync"):
            tok = self._pick_first(slot, onp.asarray(logits)[0])
        s = _Slot(r.stream, tok, r.max_new - 1, r.eos_id, r.deadline,
                  n_ctx=n)
        self._slots[slot] = s
        self._n_active += 1
        r.stream._emit(tok)
        telemetry.counter("serving.generate.tokens")
        telemetry.hist_since("serving.generate.ttft", r.t_submit)
        if s.eos_id is not None and tok == s.eos_id:
            self._evict(slot, "eos")
        elif s.left <= 0 or s.n_ctx >= self._s_cap:
            self._evict(slot, "length")
        else:
            telemetry.gauge("serving.generate.slots", self._n_active)

    def _arm_sampling(self, slot: int, r: _GenRequest):
        """Install a request's sampling knobs into the per-slot
        vectors the fixed-shape programs read (greedy requests write
        the defaults — the vectors must never carry a previous
        tenant's state). The PRNG key is installed here only in DENSE
        mode, where admission prefills synchronously and the first
        pick follows immediately; a PAGED slot can sit in its prefill
        phase for several iterations whose decode ticks split EVERY
        row's key — installing at admission would make the
        pre-first-token split count depend on co-tenant activity and
        break seeded reproducibility, so the key waits on the slot
        (``_PagedSlot.key``) until ``_first_token`` installs it."""
        self._temps[slot] = r.temperature
        self._topks[slot] = r.top_k
        self._topps[slot] = r.top_p
        self._adapter_idx[slot] = r.adapter_idx
        if r.temperature > 0:
            self._n_sampling += 1
            if not self.paged:
                self._keys[slot] = r.key

    def _akw(self, idx):
        """``adapters=`` kwarg for a model call — present only on a
        LoRA-enabled engine, so other decoder families never need to
        grow the keyword."""
        return {"adapters": idx} if self.lora_enabled else {}

    def _pick_first(self, slot: int, logits_row):
        """First token of a fresh admission, from its prefill/peek
        logits row: host argmax for greedy slots (bit-identical to the
        pre-sampling engine), the jitted (1, V) sampler for stochastic
        ones — the same key chain the decode steps continue."""
        logits_row = logits_row.reshape(-1)
        if self._temps[slot] <= 0:
            return int(logits_row.argmax())
        smp = self._ensure_samplers()
        with tracing.phase("serve.call.pick"):
            tok, nk = smp["sample"](
                self._keys[slot:slot + 1],
                onp.asarray(logits_row, "f4")[None],
                self._temps[slot:slot + 1], self._topks[slot:slot + 1],
                self._topps[slot:slot + 1])
        self._keys[slot] = onp.asarray(nk)[0]
        return int(onp.asarray(tok)[0])

    # -- paged scheduling ----------------------------------------------
    def _alloc_pages(self, n):
        """Allocate ``n`` pool pages, evicting LRU cached prefixes to
        make room; None when even an empty prefix cache can't cover
        them (the pages are pinned by active slots)."""
        out = self._pool.alloc(n)
        while out is None and self._prefix is not None \
                and self._prefix.evict_lru():
            out = self._pool.alloc(n)
        return out

    def _release_pages(self, pids):
        for pid in pids:
            self._pool.release(pid)

    def _try_admit_paged(self, r: _GenRequest) -> bool:
        """Place ``r`` into a free slot: match the longest cached
        prefix, reserve its worst-case private pages (so decode can
        never run out mid-sequence), and either peek its first token
        straight off a fully-cached prompt or queue its prefill
        chunks. False when the pool (after prefix-cache eviction)
        cannot cover the reservation — the request stays blocked."""
        length = int(r.prompt.size)
        ps = self._ps
        cap_pages = -(-min(length + r.max_new, self._s_cap) // ps)
        shared_pages, shared_tokens = [], 0
        if self._prefix is not None and r.adapter_idx == 0:
            # prefix reuse is BASE-MODEL-only: cached pages hold K/V
            # computed under the projections that prefilled them, and
            # an adapter changes q/k/v — serving one tenant's pages to
            # another (or adapted pages to base traffic) would
            # silently swap attention context. Adapter requests always
            # prefill fresh and never publish to the index.
            shared_pages, shared_tokens = self._prefix.match(r.prompt)
        peek = shared_tokens == length
        first_write = (length if peek else shared_tokens) // ps
        # retain the matched pages BEFORE allocating: _alloc_pages may
        # LRU-evict the very record backing them, and unretained pages
        # would return to the free list and come straight back as this
        # request's PRIVATE pages (LIFO) — the row would alias shared
        # and private, and chunk prefill would overwrite the shared
        # prefix K/V (found by review with a live tight-pool repro)
        refs = []
        n_shared = len(shared_pages) if peek else first_write
        for i in range(n_shared):
            self._pool.retain(shared_pages[i])
            refs.append(shared_pages[i])
        private = self._alloc_pages(cap_pages - first_write)
        if private is None and refs:
            # our retained prefix refs pinned exactly the pages the
            # allocator's eviction sweep tried to reclaim: drop the
            # match and retry UNSHARED — a transiently page-heavy
            # prefix hit must degrade to a plain prefill, not fail an
            # admission a retry would satisfy
            self._release_pages(refs)
            refs = []
            shared_pages, shared_tokens = [], 0
            peek = False
            first_write = n_shared = 0
            private = self._alloc_pages(cap_pages)
        if private is None:
            self._release_pages(refs)
            return False
        slot = self._slots.index(None)
        tr = r.stream._trace
        if tr is not None:
            tr.event("admission", slot=slot, mode="paged", peek=peek,
                     prefix_tokens=shared_tokens)
        tracing.flight.record("gen.admit", slot=slot, mode="paged",
                              peek=peek, prefix_tokens=shared_tokens,
                              trace_id=r.stream.trace_id)
        row = onp.zeros((self._p_max,), "i4")   # scrap past the cap
        for i in range(n_shared):
            row[i] = shared_pages[i]
        refs.extend(private)
        s = _PagedSlot(r.stream, r.max_new, r.eos_id, r.deadline,
                       n_ctx=length, row=row, page_refs=refs,
                       prompt=r.prompt, seq=self._seq,
                       t_submit=r.t_submit)
        s.adapter_idx = r.adapter_idx
        if self.speculative:
            # survives prefix registration (which clears s.prompt):
            # the draft's dense prefill runs when the slot enters
            # decode, prefix hit or not — the draft has no prefix cache
            s.draft_prompt = r.prompt
        s.key = r.key   # installed at decode entry (_first_token)
        self._arm_sampling(slot, r)
        self._seq += 1
        if peek:
            if length % ps:
                # the shared partial tail is this slot's divergence
                # page: COW it right before the first decode write
                s.cow_pending = (int(row[first_write]), private[0],
                                 first_write)
                row[first_write + 1:cap_pages] = private[1:]
            else:
                row[first_write:cap_pages] = private
            telemetry.counter("serving.generate.prefix_hits")
            self._slots[slot] = s
            self._n_active += 1
            pt0 = time.perf_counter() if tr is not None else 0.0
            t0 = telemetry.clock()
            with tracing.phase("serve.call.bind"):
                self._cache = self.model.bind_slot_paged(
                    slot, row, length, self._cache)
            self._cache = self._recommit(self._cache)
            with tracing.phase("serve.call.peek"):
                logits = self.model.peek_logits_paged(
                    int(r.prompt[-1]), slot, self._cache,
                    **self._akw(self._adapter_idx[slot:slot + 1]))
            telemetry.hist_since("serving.generate.prefill", t0)
            telemetry.counter("serving.generate.prefills")
            if tr is not None:
                tr.add("prefill", pt0, slot=slot, tokens=length,
                       peek=True)
            self._register_prefix(s)
            with tracing.phase("serve.prefill.sync"):
                self._first_token(slot, s, onp.asarray(logits))
            return True
        row[first_write:cap_pages] = private
        start0 = first_write * ps
        # a block-diffusion prompt prefills its whole blocks only: the
        # rest opens its first block
        n_pre = length if self._blockdiff is None \
            else length - length % self._blockdiff["block_length"]
        fresh = (n_pre > 0 and start0 == 0
                 and self.policy.bucket(n_pre) <= self._chunk)
        if fresh:
            w = self.policy.bucket(n_pre)
            toks = onp.zeros((1, w), "i4")
            toks[0, :n_pre] = r.prompt[:n_pre]
            s.chunks.append((toks, 0, n_pre, True))
        else:
            pos = start0
            while pos < n_pre:
                w = min(self._chunk, self._s_max - pos)
                nv = min(w, n_pre - pos)
                toks = onp.zeros((1, w), "i4")
                toks[0, :nv] = r.prompt[pos:pos + nv]
                s.chunks.append((toks, pos, nv, False))
                pos += nv
        self._slots[slot] = s
        self._n_active += 1
        if not s.chunks:
            # a block-diffusion prompt shorter than a block
            telemetry.counter("serving.generate.prefills")
            self._enter_denoise(s)
        return True

    def _register_prefix(self, s: _PagedSlot):
        """Publish a completed prompt's pages to the prefix index so
        later identical/shared-prefix requests reuse them. When the
        prompt ends mid-page and this slot will keep decoding, the now
        index-retained tail page becomes shared — arm a COW so the
        slot's first decode write copies it instead of corrupting the
        cached prefix."""
        if self._prefix is None or s.prompt is None \
                or s.adapter_idx != 0:  # adapted K/V never publishes
            return
        length = int(s.prompt.size)
        needs_cow = (length % self._ps != 0 and s.cow_pending is None
                     and s.left > 1 and s.n_ctx < self._s_cap)
        dst = None
        if needs_cow:
            dst = self._alloc_pages(1)
            if dst is None:
                return  # can't afford to freeze the tail: skip caching
        if not self._prefix.register(s.prompt, s.row):
            if dst:
                self._release_pages(dst)
        elif dst:
            s.cow_pending = (int(s.row[length // self._ps]), dst[0],
                             length // self._ps)
            s.page_refs.append(dst[0])
        s.prompt = None

    def _first_token(self, slot: int, s: _PagedSlot, logits_row):
        """Emit a freshly-admitted request's first token (from its last
        prefill chunk's logits or the prefix-hit peek) — the paged
        analog of dense ``_admit_one``'s tail. In speculative mode the
        slot's entry into decode is also where the DRAFT catches up:
        one dense draft prefill of the full prompt (the draft has no
        paged pool and no prefix cache — it is small enough that a
        monolithic prefill is cheaper than teaching it chunking)."""
        if self.speculative and s.draft_prompt is not None:
            n = int(s.draft_prompt.size)
            sb = self.policy.bucket(n)
            padded = onp.zeros((1, sb), "i4")
            padded[0, :n] = s.draft_prompt
            with tracing.phase("serve.call.draft_prefill"):
                _, self._draft_cache = self.draft.prefill(
                    padded, onp.asarray([n], "i4"), self._draft_cache,
                    slots=onp.asarray([slot], "i4"))
            self._draft_cache = self._recommit_draft(self._draft_cache)
            s.draft_prompt = None
        if s.key is not None:
            # decode entry is where the request's PRNG key goes live:
            # installing it at admission would let every co-tenant
            # tick during the chunked prefill split it (the
            # fixed-shape programs advance ALL rows), making the
            # stream depend on co-tenant activity
            self._keys[slot] = s.key
            s.key = None
        tok = self._pick_first(
            slot, logits_row.reshape(-1, logits_row.shape[-1])[0])
        s.last = tok
        s.left -= 1
        s.state = "decode"
        s.stream._emit(tok)
        telemetry.counter("serving.generate.tokens")
        telemetry.hist_since("serving.generate.ttft", s.t_submit)
        if s.eos_id is not None and tok == s.eos_id:
            self._evict(slot, "eos")
        elif s.left <= 0 or s.n_ctx >= self._s_cap:
            self._evict(slot, "length")
        else:
            telemetry.gauge("serving.generate.slots", self._n_active)

    def _prefill_tick(self) -> int:
        """Run AT MOST ONE prefill chunk (oldest admitted slot first):
        the decode-stall bound — a 192-token prompt spends several
        iterations prefilling, each interleaved with a decode step over
        the in-flight slots, so TPOT p99 is bounded by one chunk, not
        one monolithic prefill."""
        best = None
        for i, s in enumerate(self._slots):
            if s is not None and s.state == "prefill" \
                    and (best is None or s.seq < self._slots[best].seq):
                best = i
        if best is None:
            return 0
        s = self._slots[best]
        if s.deadline is not None and time.monotonic() > s.deadline:
            telemetry.counter("serving.generate.timeouts")
            self._evict_exc(best, RequestTimeoutError(
                "request deadline expired during chunked prefill"))
            return 0
        toks, start, n_valid, fresh = s.chunks.popleft()
        tr = s.stream._trace
        pt0 = time.perf_counter() if tr is not None else 0.0
        t0 = telemetry.clock()
        with tracing.phase("serve.prefill.dispatch", slot=best,
                           start=start, tokens=n_valid, fresh=fresh):
            with tracing.phase("serve.call.prefill"):
                logits, self._cache = self.model.prefill_paged(
                    toks, n_valid, best, s.row, self._cache, start=start,
                    fresh=fresh, **self._last_kw(not s.chunks),
                    **self._akw(self._adapter_idx[best:best + 1]))
            self._cache = self._recommit(self._cache)
        telemetry.hist_since("serving.generate.prefill", t0)
        telemetry.counter("serving.generate.prefill_chunks")
        if tr is not None:
            tr.add("prefill_chunk", pt0, slot=best, start=start,
                   tokens=n_valid)
        self._chunks_this_iter += 1
        if not s.chunks:
            telemetry.counter("serving.generate.prefills")
            if self._blockdiff is not None:
                self._enter_denoise(s)
                return 1
            self._register_prefix(s)
            with tracing.phase("serve.prefill.sync"):
                self._first_token(best, s, onp.asarray(logits))
        return 1

    def _cow_sweep(self):
        """Copy-on-write: a decoding slot whose next cache write would
        land in a SHARED page copies the divergence page first and
        rebinds its table row. Runs before every paged decode/verify
        step (a speculative verify writes through the same table)."""
        for i, s in enumerate(self._slots):
            if s is not None and s.state == "decode" \
                    and s.cow_pending is not None:
                src, dst, logical = s.cow_pending
                tr = s.stream._trace
                pt0 = time.perf_counter() if tr is not None else 0.0
                with tracing.phase("serve.cow", slot=i):
                    with tracing.phase("serve.call.copy"):
                        self._cache = self.model.copy_page_paged(
                            src, dst, self._cache)
                    self._cache = self._recommit(self._cache)
                    s.row[logical] = dst
                    with tracing.phase("serve.call.bind"):
                        self._cache = self.model.bind_slot_paged(
                            i, s.row, s.n_ctx, self._cache)
                    self._cache = self._recommit(self._cache)
                self._pool.release(src)
                s.page_refs.remove(src)
                s.cow_pending = None
                telemetry.counter("serving.generate.pages.cow_copies")
                if tr is not None:
                    tr.add("cow_copy", pt0, slot=i, src=src, dst=dst)

    def _pick_step_tokens(self, logits):
        """Per-slot next tokens from a decode step's raw (B, V)
        logits. Every active slot greedy: their argmax, taken where the
        logits lie (``ops.sampling.greedy_tokens``), so that ``B`` ints
        come to the host and not ``B x V`` floats (32 slots over a
        vocabulary of 200,064 are 25.6 MB a tick). Otherwise one
        fixed-shape sampler call whose greedy rows are in-program argmax
        (the same ints) and whose stochastic rows consume their slot's
        key."""
        if self._n_sampling:
            if self._part is not None:
                # TP mode: hand the sampler HOST logits — the device
                # logits carry a GSPMD-chosen (vocab-sharded) layout,
                # and the sampler's pjit executable cache keys on
                # input shardings; warmup fed host arrays, so the
                # live path must too (one signature per program)
                logits = onp.asarray(logits)
            with tracing.phase("serve.call.pick"):
                tok, nk = self._ensure_samplers()["sample"](
                    self._keys, logits, self._temps, self._topks,
                    self._topps)
            # onp.array, not asarray: a jax array converts to a
            # READ-ONLY numpy view, and _arm_sampling assigns into
            # this buffer per admission
            self._keys = onp.array(nk, dtype="u4")
            return onp.asarray(tok)
        if self._part is not None:
            # TP mode: the logits are vocab-sharded (see above)
            return onp.asarray(logits).argmax(axis=-1)
        with tracing.phase("serve.call.pick"):
            pick = self._ensure_samplers()["greedy"](logits)
        return onp.asarray(pick)

    def _decode_idxs(self):
        """The slots a decode/spec tick serves this iteration: every
        occupied slot (dense mode — dense slots are always decoding)
        or every slot in its decode phase (paged mode — prefilling
        slots ride the fixed-shape program masked out)."""
        return [i for i, s in enumerate(self._slots)
                if s is not None
                and (not self.paged or s.state == "decode")]

    def _tick_counters(self, dispatches, fused):
        """Amortization telemetry, bumped once per decode/spec tick:
        the tick materialized its outputs in ONE host sync
        (``host_syncs``), dispatched ``dispatches`` jitted programs
        to produce them, and fused ``fused`` decode iterations behind
        that sync (``ticks_per_sync`` — the ``decode_ticks`` knob's
        live readout; 1 on a plain tick). tests/test_multitick.py
        holds host syncs a token and dispatch counts from these
        counters, so the amortization is counted, never asserted."""
        telemetry.counter("serving.generate.host_syncs")
        telemetry.counter("serving.generate.dispatches",
                          int(dispatches))
        telemetry.gauge("serving.generate.ticks_per_sync", int(fused))

    def _commit_outputs(self, idxs, outs, span_cb, clipped=None):
        """The ONE host-commit bookkeeping loop every tick flavor
        (plain, multi-tick, speculative) funnels through: record the
        slot's tracing span (``span_cb(slot, s, out)``), emit its
        token block, advance its budget/length counters, and apply
        the eviction ladder — eos first, then budget/capacity
        (``clipped`` marks speculative slots whose emission was
        clipped short of the in-program commit: exhausted even when
        the counters alone would not say so), then deadline (checked
        once per BLOCK — a multi-token tick times out at block
        granularity). Returns the number of tokens emitted."""
        with tracing.phase("serve.commit"):
            now = time.monotonic()
            n_emitted = 0
            for i in idxs:
                s = self._slots[i]
                out = outs[i]
                span_cb(i, s, out)
                s.stream._emit_many(out)
                n_emitted += len(out)
                if not out:
                    # can only mean an exhausted slot the evict checks
                    # below would have caught last tick
                    self._evict(i, "length")
                    continue
                s.last = out[-1]
                s.left -= len(out)
                s.n_ctx += len(out)
                if s.eos_id is not None and out[-1] == s.eos_id:
                    self._evict(i, "eos")
                elif s.left <= 0 or s.n_ctx >= self._s_cap \
                        or (clipped is not None and clipped.get(i)):
                    self._evict(i, "length")
                elif s.deadline is not None and now > s.deadline:
                    telemetry.counter("serving.generate.timeouts")
                    self._evict(i, "timeout")
            if n_emitted:  # one delta per tick, not one call per token
                telemetry.counter("serving.generate.tokens", n_emitted)
            telemetry.gauge("serving.generate.slots", self._n_active)
        return n_emitted

    def _tick_tokens(self, toks):
        """A plain tick's host tokens as the decode program takes them
        on every path: committed to the device, as the pick of a tick in
        flight is (a committed and an uncommitted input compile apart,
        so warm-up, the synchronous tick and the tick dispatched ahead
        all feed the one placement). A tp-mesh engine hands over host
        arrays as before."""
        if self._part is not None:
            return toks
        import jax
        return jax.device_put(toks, jax.devices()[0])

    def _ahead_ok(self) -> bool:
        """Whether this tick may be left in flight (docs/SERVING.md "The
        tick in flight"): the plain single-step tick on one device, every
        live row greedy (a sampled row's key lives on the host and comes
        back from the sampler each tick), nobody waiting for a step
        boundary, the engine open and run by its worker."""
        return (self.decode_ticks == 1 and self._part is None
                and not self._n_sampling and not self._gen_waiters
                and not self._closed and not self._sync)

    def _decode_tick(self):
        """One decode tick over all DECODING slots — dense and paged
        (prefilling paged slots ride along masked out: their writes
        are redirected to the scrap page and their ``len`` stands
        still). With ``decode_ticks > 1`` the tick runs the fused
        multi-tick scan instead of the single-step program
        (docs/SERVING.md "Multi-tick decode"): one host sync commits
        up to k tokens per slot.

        The plain tick is dispatched AHEAD where ``_ahead_ok``: this
        tick is queued on the device before the tick before it is
        synced and committed, and takes each continuing row's token from
        that tick's pick where it lies, so the host's commit, admission
        and preparation run while the device works. A row is served if
        it will still have budget and capacity once the tick in flight
        commits (length evictions are predicted here); an end the host
        cannot predict (eos, a deadline) finds its row already in the
        next tick, whose output for it ``_finish_tick`` drops. Where
        ``_ahead_ok`` stops holding the tick in flight is drained first
        and the tick runs synchronously."""
        ahead = self._ahead_ok()
        if not ahead:
            self._drain_ahead()
        if self.paged:
            # a row with a tick in flight has no copy pending: the sweep
            # before its first tick took it
            self._cow_sweep()
        if self.decode_ticks > 1:
            idxs = self._decode_idxs()
            if idxs:
                self._decode_tick_multi(idxs)
            return
        prev, self._ahead = self._ahead, None
        pending = dict(prev.rows) if prev is not None else {}
        toks = onp.zeros((self.max_slots,), "i4")
        active = onp.zeros((self.max_slots,), "i4")
        fresh = onp.zeros((self.max_slots,), "?")
        rows = []
        any_trace = False
        for i in self._decode_idxs():
            s = self._slots[i]
            if pending.get(i) is s:
                # its token is row i of the pick in flight, whose commit
                # takes one of ``left`` and adds one to ``n_ctx``
                if s.left <= 1 or s.n_ctx + 1 >= self._s_cap:
                    continue
            else:
                toks[i] = s.last
                fresh[i] = True
            active[i] = 1
            rows.append((i, s))
            if s.stream._trace is not None:
                any_trace = True
        tick = self._dispatch_tick(prev, rows, toks, active, fresh,
                                   any_trace, ahead) if rows else None
        if prev is not None:
            self._finish_tick(prev)
            if tick is not None and any_trace:
                # a traced row's "decode" span is its wait for this
                # token: from the commit of the one before it
                tick.tt0 = time.perf_counter()
        if tick is None:
            return
        if not ahead:
            self._finish_tick(tick)
        elif not any(self._slots[i] is s for i, s in rows):
            # every row it serves ended at the commit above: nothing
            # waits for this tick
            self._drain_ahead()

    def _dispatch_tick(self, prev, rows, toks, active, fresh, any_trace,
                       ahead) -> _Tick:
        """Queue one plain tick on the device. Its tokens: the host's
        ``toks`` where no tick is in flight, else ``prev``'s pick where
        it lies, with the ``fresh`` rows (in decode since ``prev`` was
        dispatched) merged in from ``toks`` on the device."""
        with tracing.phase("serve.decode.dispatch"):
            if prev is None:
                tokens = self._tick_tokens(toks)
            elif fresh.any():
                with tracing.phase("serve.call.carry"):
                    tokens = self._ensure_samplers()["carry"](
                        prev.pick, toks, fresh)
            else:
                tokens = prev.pick
            tt0 = time.perf_counter() if any_trace else 0.0
            t0 = telemetry.clock()
            if self.paged:
                with tracing.phase("serve.call.decode"):
                    logits, self._cache = self.model.decode_step_paged(
                        tokens, active, self._cache,
                        **self._akw(self._adapter_idx))
                self._cache = self._recommit(self._cache)
            else:
                with tracing.phase("serve.call.decode_dense"):
                    logits, self._cache = self.model.decode_step(
                        tokens, self._cache,
                        **self._akw(self._adapter_idx))
                if self._part is not None:
                    self._cache = self._recommit(self._cache)
            self._emit_collectives()
            tick = _Tick(rows, t0, tt0)
            if ahead:
                with tracing.phase("serve.call.pick"):
                    tick.pick = self._ensure_samplers()["greedy"](logits)
                self._ahead = tick
                if prev is not None:
                    telemetry.counter("serving.generate.ticks_ahead")
            else:
                tick.logits = logits
        return tick

    def _finish_tick(self, tick: _Tick):
        """Sync a plain tick's pick and commit it for the rows whose
        request is still the one the tick served."""
        if tick.pick is not None and self._ahead is None:
            # dispatched ahead, and nothing was queued behind it
            telemetry.counter("serving.generate.ahead_drains")
        with tracing.phase("serve.decode.sync"):
            step_toks = self._pick_step_tokens(tick.logits) \
                if tick.pick is None else onp.asarray(tick.pick)
        # closed AFTER the tick's host sync, as the multi-tick and
        # speculative ticks close it: the tick as a caller feels it
        # (before it, on an asynchronous device, it timed the enqueue)
        telemetry.hist_since("serving.generate.decode", tick.t0)
        self._tick_counters(1, 1)
        idxs = [i for i, s in tick.rows if self._slots[i] is s]
        if len(idxs) < len(tick.rows):
            telemetry.counter("serving.generate.stale_row_ticks",
                              len(tick.rows) - len(idxs))
        outs = {i: [int(step_toks[i])] for i in idxs}

        def span(i, s, out):
            if s.stream._trace is not None:
                s.stream._trace.add("decode", tick.tt0, slot=i,
                                    token=out[-1])
        self._commit_outputs(idxs, outs, span)

    def _drain_ahead(self):
        """Sync and commit the tick in flight, if any: after it the
        engine is where the synchronous loop is between two ticks."""
        tick, self._ahead = self._ahead, None
        if tick is not None:
            self._finish_ahead(tick)

    # -- block-diffusion steps (docs/SERVING.md "Block-diffusion steps") --
    def _enter_denoise(self, s: _PagedSlot):
        """A block-diffusion slot whose whole prompt blocks are cached
        opens its first block: the prompt's last ``p mod B`` tokens, the
        mask at the block's other positions. Its first denoising step
        hands the block to the device (``_Block.fresh``)."""
        bd = self._blockdiff
        blk, p = bd["block_length"], int(s.prompt.size)
        tail = p % blk
        s.blk = _Block(p - tail, tail,
                       block_schedule(blk - tail, bd["denoise_steps"]),
                       min(p + s.left, self._s_cap))
        s.state = "decode"

    def _denoise_tick(self):
        """One denoising step of every decoding slot's block in ONE
        fixed-shape program (the model's ``denoise_step_paged``); a slot
        whose last step this was yields its block at the tick's commit,
        and the block's K/V are committed in the slot's next step, on the
        device. The blocks live on the device: the host follows each
        slot's static schedule (``_Block``), so it knows which slots a
        step completes and reads tokens only then.

        Dispatched ahead where ``_ahead_ok`` holds, as the plain tick is
        (docs/SERVING.md "The tick in flight"): a step needs nothing of
        the step before it on the host, so this one is queued before the
        one in flight is synced and committed. A slot whose last block is
        dispatched is served no further step; an end the host cannot
        predict (eos, a deadline) finds its slot in the next step, whose
        output for it ``_finish_denoise`` drops."""
        ahead = self._ahead_ok()
        if not ahead:
            self._drain_ahead()
        prev, self._ahead = self._ahead, None
        b, blk = self.max_slots, self._blockdiff["block_length"]
        full = block_schedule(blk, self._blockdiff["denoise_steps"])
        take = onp.zeros((b,), "i4")
        fresh = onp.zeros((b,), "i4")
        f_blk = onp.zeros((b, blk), "i4")
        f_fill = onp.zeros((b, blk), "?")
        f_start = onp.zeros((b,), "i4")
        f_table = onp.zeros((b, self._p_max), "i4")
        rows = []
        any_trace = False
        for i in self._decode_idxs():
            s = self._slots[i]
            g = s.blk
            if g.last:
                continue
            take[i] = g.steps[g.step]
            if g.fresh:
                fresh[i], g.fresh = 1, False
                f_blk[i, :g.tail] = s.prompt[g.start:g.start + g.tail]
                f_fill[i, :g.tail] = True
                f_start[i] = g.start
                f_table[i] = s.row
            g.step += 1
            # ``(slot, request, where its new tokens start in the block;
            # -1 where the block is not done at this step)``
            if g.step == len(g.steps):
                rows.append((i, s, g.tail))
                g.last = g.start + blk >= g.end
                g.start, g.tail, g.step, g.steps = g.start + blk, 0, 0, full
            else:
                rows.append((i, s, -1))
            if s.stream._trace is not None:
                any_trace = True
        tick = None
        if rows:
            with tracing.phase("serve.decode.dispatch"):
                tt0 = time.perf_counter() if any_trace else 0.0
                t0 = telemetry.clock()
                with tracing.phase("serve.call.denoise"):
                    out, self._cache = self.model.denoise_step_paged(
                        take, fresh, f_blk, f_fill, f_start, f_table,
                        self._cache)
                telemetry.counter("serving.generate.dispatches")
                telemetry.counter("serving.generate.denoise_steps",
                                  len(rows))
                tick = _Tick(rows, t0, tt0)
                tick.pick = out
                if ahead:
                    self._ahead = tick
                    if prev is not None:
                        telemetry.counter("serving.generate.ticks_ahead")
        if prev is not None:
            self._finish_denoise(prev)
        if tick is None:
            return
        if not ahead:
            self._finish_denoise(tick)
        elif not any(self._slots[i] is s for i, s, _ in rows):
            self._drain_ahead()

    def _finish_denoise(self, tick: _Tick):
        """Commit a denoising step: for each slot whose block it completed
        (and whose request is still the one it served), sync the blocks
        and emit the block's new tokens, cut at the request's budget and
        after the first eos: one host sync at most."""
        live = [(i, s, at) for i, s, at in tick.rows
                if self._slots[i] is s]
        if len(live) < len(tick.rows):
            telemetry.counter("serving.generate.stale_row_ticks",
                              len(tick.rows) - len(live))
        done = [(i, s, at) for i, s, at in live if at >= 0]
        outs = {}
        if done:
            with tracing.phase("serve.decode.sync"):
                toks = onp.asarray(tick.pick)
            telemetry.counter("serving.generate.host_syncs")
            for i, s, at in done:
                out = [int(t) for t in toks[i, at:at + s.left]]
                if s.eos_id is not None and s.eos_id in out:
                    out = out[:out.index(s.eos_id) + 1]
                if s.n_ctx == s.prompt.size:
                    telemetry.hist_since("serving.generate.ttft",
                                         s.t_submit)
                outs[i] = out
            telemetry.counter("serving.generate.blocks_committed", len(outs))
        telemetry.hist_since("serving.generate.decode", tick.t0)
        if not outs:
            return

        def span(i, s, out):
            if s.stream._trace is not None:
                s.stream._trace.add("block", tick.tt0, slot=i,
                                    tokens=len(out))
        self._commit_outputs(list(outs), outs, span)

    def _decode_tick_multi(self, idxs):
        """One MULTI-TICK decode tick: ``decode_ticks`` fused decode
        iterations in ONE jitted scan, committed through one host
        sync. Per-slot eos/budget stop handling runs IN-PROGRAM — a
        finished slot keeps scanning against its frozen/scrap
        position with its emissions masked — so the host receives a
        finished (B, k) token block plus its emission mask and
        commits each slot's prefix in one ``_emit_many``. Budgets
        are clamped host-side to each slot's remaining token budget
        and capacity headroom, so the scan can never over-emit; mixed
        greedy/stochastic batches and every per-request knob are
        runtime vectors (keys split per scan step in-trace), so
        steady-state traffic compiles nothing."""
        k = self.decode_ticks
        b = self.max_slots
        with tracing.phase("serve.decode.dispatch"):
            toks = onp.zeros((b,), "i4")
            budgets = onp.zeros((b,), "i4")
            eos_ids = onp.full((b,), -1, "i4")
            any_trace = False
            for i in idxs:
                s = self._slots[i]
                toks[i] = s.last
                budgets[i] = min(k, s.left, self._s_cap - s.n_ctx)
                if s.eos_id is not None:
                    eos_ids[i] = s.eos_id
                if s.stream._trace is not None:
                    any_trace = True
            tt0 = time.perf_counter() if any_trace else 0.0
            t0 = telemetry.clock()
            fn = self.model.decode_multi_paged if self.paged \
                else self.model.decode_multi
            with tracing.phase("serve.call.multi"):
                tok_blk, emit_blk, keys, self._cache = fn(
                    toks, budgets, self._cache, k, self._keys,
                    self._temps, self._topks, self._topps, eos_ids,
                    **self._akw(self._adapter_idx))
            if self.paged or self._part is not None:
                self._cache = self._recommit(self._cache)
            self._emit_collectives()
        with tracing.phase("serve.decode.sync"):
            tok_h = onp.asarray(tok_blk)  # the (B, k) block's ONE sync
            emit_h = onp.asarray(emit_blk)
            # onp.array, not asarray: a jax array converts to a
            # READ-ONLY numpy view, and _arm_sampling assigns into
            # this buffer
            self._keys = onp.array(keys, dtype="u4")
        telemetry.hist_since("serving.generate.decode", t0)
        self._tick_counters(1, k)
        outs = {i: [int(t) for t in tok_h[i, :int(emit_h[i].sum())]]
                for i in idxs}

        def span(i, s, out):
            # ONE span covering the whole k-token block (never k
            # spans, never zero) — the flight/trace contract
            if s.stream._trace is not None:
                s.stream._trace.add("decode", tt0, slot=i,
                                    tokens=len(out))
        self._commit_outputs(idxs, outs, span)

    def _evict_exc(self, slot: int, exc):
        """Reject a slot whose stream has delivered nothing yet (a
        prefill-phase deadline): an exception, not a truncated
        result."""
        s = self._slots[slot]
        if s.stream._trace is not None:
            s.stream._trace.event("evict", slot=slot,
                                  error=f"{type(exc).__name__}: {exc}")
        tracing.flight.record("gen.evict", slot=slot,
                              error=type(exc).__name__,
                              trace_id=s.stream.trace_id)
        s.stream._finish(exc=exc)
        self._free_slot(slot)

    def _release_slot_refs(self, s):
        if self.paged and s.page_refs:
            self._release_pages(s.page_refs)
            s.page_refs = []

    def _free_slot(self, slot: int):
        s = self._slots[slot]
        self._release_slot_refs(s)
        self._slots[slot] = None
        self._n_active -= 1
        if self._temps[slot] > 0:
            self._n_sampling -= 1
        self._temps[slot] = 0.0    # the next tenant must never read a
        self._topks[slot] = 0      # previous request's knobs
        self._topps[slot] = 1.0
        self._adapter_idx[slot] = 0  # freed rows decode as base
        telemetry.counter("serving.generate.evictions")
        telemetry.gauge("serving.generate.slots", self._n_active)

    def _step(self):
        """One engine iteration. Paged mode: at most one prefill chunk
        (``_prefill_tick``) then one fixed-shape decode step over the
        decoding slots. Dense mode: one decode step over ALL slots;
        emit one token per live slot, evict finished slots (their rows
        are free for the next admission — mid-sequence, zero
        recompiles)."""
        if self.paged:
            # the gauge counts EVERY chunk run inside this iteration
            # (accumulated by _prefill_tick itself, not inferred from
            # its call count) so the one-chunk decode-stall bound is
            # falsifiable: a future second tick call would push the
            # peak past 1 and fail the tests/bench gate
            self._chunks_this_iter = 0
            self._prefill_tick()
            telemetry.gauge("serving.generate.prefill_chunks_per_iter",
                            self._chunks_this_iter)
            if any(s is not None and s.state == "decode"
                   for s in self._slots):
                if self.speculative:
                    self._spec_tick()
                elif self._blockdiff is not None:
                    self._denoise_tick()
                else:
                    self._decode_tick()
            return
        if self.speculative:
            self._spec_tick()
            return
        self._decode_tick()

    # -- speculative decoding (docs/SERVING.md) -------------------------
    def _spec_tick(self):
        """One speculative iteration over every decoding slot: the
        draft proposes ``spec_k`` tokens per slot (k dense draft
        steps, tokens and keys chained on-device — no host sync), the
        target verifies all ``k + 1`` positions in ONE fixed-shape
        program, the accept rule (ops/sampling.py) commits the
        accepted prefix plus one target-derived token, and both caches
        advance to the accept point (``advance_len`` — the rejected
        tail sits above the ``len`` waterline and the next verify
        overwrites it; the draft, which ran k steps, ROLLS BACK by the
        same counter). Greedy slots commit exactly the tokens
        non-speculative decode would; stochastic slots commit a
        sample from exactly the warped target distribution."""
        if self.paged:
            self._cow_sweep()
        idxs = self._decode_idxs()
        if not idxs:
            return
        k = self.spec_k
        b = self.max_slots
        with tracing.phase("serve.decode.dispatch"):
            toks = onp.zeros((b,), "i4")
            active = onp.zeros((b,), "i4")
            any_trace = False
            for i in idxs:
                toks[i] = self._slots[i].last
                active[i] = 1
                if self._slots[i].stream._trace is not None:
                    any_trace = True
            tt0 = time.perf_counter() if any_trace else 0.0
            sampled = bool(self._n_sampling)
            t0 = telemetry.clock()
            # three dispatches + one host sync per iteration: the fused
            # k-step draft propose, the fused verify+accept+advance, and
            # the draft rollback — at serving model sizes the per-call
            # dispatch overhead dominates, so the k draft steps, the k+1
            # verify, the accept rule and the len bump each run INSIDE
            # one program instead of as ~3k separate calls
            verify = self.model.verify_commit_paged if self.paged \
                else self.model.verify_commit
            if sampled:
                with tracing.phase("serve.call.propose"):
                    dt, q, keys, self._draft_cache = \
                        self.draft.propose_tokens(
                            toks, self._draft_cache, k, keys=self._keys,
                            temps=self._temps, top_ks=self._topks,
                            top_ps=self._topps)
                self._draft_cache = self._recommit_draft(self._draft_cache)
                with tracing.phase("serve.call.verify"):
                    commit, n_commit, keys, self._cache = verify(
                        toks, dt, active, self._cache, q=q, keys=keys,
                        temps=self._temps, top_ks=self._topks,
                        top_ps=self._topps,
                        **self._akw(self._adapter_idx))
            else:
                with tracing.phase("serve.call.propose"):
                    dt, self._draft_cache = self.draft.propose_tokens(
                        toks, self._draft_cache, k)
                self._draft_cache = self._recommit_draft(self._draft_cache)
                with tracing.phase("serve.call.verify"):
                    commit, n_commit, self._cache = verify(
                        toks, dt, active, self._cache,
                        **self._akw(self._adapter_idx))
            self._cache = self._recommit(self._cache)
            self._emit_collectives()
        with tracing.phase("serve.decode.sync"):
            commit_h = onp.asarray(commit)  # the tick's one host sync
            n_h = onp.asarray(n_commit)
            if sampled:
                self._keys = onp.array(keys, dtype="u4")  # writable
        telemetry.hist_since("serving.generate.decode", t0)
        # commit bookkeeping: eos cuts the emission at the stop token,
        # budget/capacity clip it. A clipped slot is EVICTED, so the
        # cache's full-commit len (advanced in-program) is a dead
        # row's counter; the draft rolls back by the same arithmetic
        # (it ran k steps on every row — fixed shape).
        ddelta = onp.full((b,), -k, "i4")
        outs = {}
        clipped = {}
        proposed = len(idxs) * k
        accepted = 0
        for i in idxs:
            s = self._slots[i]
            m = int(n_h[i])
            accepted += m - 1
            out = [int(t) for t in commit_h[i, :m]]
            if s.eos_id is not None and s.eos_id in out:
                out = out[:out.index(s.eos_id) + 1]
            out = out[:min(len(out), s.left, self._s_cap - s.n_ctx)]
            outs[i] = out
            clipped[i] = len(out) < m
            ddelta[i] += m
        with tracing.phase("serve.call.advance"):
            self._draft_cache = self.draft.advance_len(ddelta,
                                                       self._draft_cache)
        self._draft_cache = self._recommit_draft(self._draft_cache)
        telemetry.counter("serving.generate.spec.proposed", proposed)
        telemetry.counter("serving.generate.spec.accepted", accepted)
        telemetry.counter("serving.generate.spec.rejected",
                          proposed - accepted)
        if proposed:
            telemetry.gauge("serving.generate.spec.accept_rate",
                            accepted / proposed)
        # propose + verify_commit + draft advance = 3 dispatches; the
        # one host sync amortizes over up to k+1 tokens per slot
        self._tick_counters(3, k + 1)

        def span(i, s, out):
            if s.stream._trace is not None:
                s.stream._trace.add("verify", tt0, slot=i, proposed=k,
                                    committed=len(out))
        n_emitted = self._commit_outputs(idxs, outs, span,
                                         clipped=clipped)
        telemetry.gauge("serving.generate.spec.tokens_per_step",
                        n_emitted)

    def _evict(self, slot: int, reason: str):
        s = self._slots[slot]
        if s.stream._trace is not None:
            s.stream._trace.event("evict", slot=slot, reason=reason)
        tracing.flight.record("gen.evict", slot=slot, reason=reason,
                              trace_id=s.stream.trace_id)
        s.stream._finish(reason=reason)
        self._free_slot(slot)

    def _close_active(self, reason: str):
        """Finish every still-active stream with ``reason`` (idempotent
        per stream: a first outcome stands) and free the slots. A paged
        slot still in its PREFILL phase has delivered nothing — it is
        rejected with :class:`EngineClosedError` like a queued request,
        never handed an empty 'successful' result. Paged mode also
        rejects page-starved blocked requests."""
        self._ahead = None  # its rows end here: nothing is emitted
        # after a finish, so the pick is left where it lies
        for i, s in enumerate(self._slots):
            if s is not None:
                if self.paged and s.state == "prefill":
                    s.stream._finish(exc=EngineClosedError(
                        "engine closed during chunked prefill (no "
                        "tokens were generated)"))
                else:
                    s.stream._finish(reason=reason)
                self._release_slot_refs(s)
                self._slots[i] = None
        self._n_active = 0
        self._n_sampling = 0
        self._teardown_paged(EngineClosedError(
            "engine closed while the request awaited KV pages"))

    def _teardown_paged(self, exc):
        """Terminal paged cleanup shared by close and worker-crash:
        reject every page-starved blocked request with ``exc`` and
        drain the prefix index — a dead engine serves nothing, and the
        pool/gauge must read fully free afterwards (post-close
        accounting, dashboards, leak checks)."""
        if not self.paged:
            return
        while self._blocked:
            self._blocked.popleft().stream._finish(exc=exc)
        if self._prefix is not None:
            self._prefix.release_all()

    def _fail_all(self, exc):
        """Worker crashed mid-step (the cache may hold donated/invalid
        buffers): fail every live stream and queued request with a
        :class:`ReplicaFailedError` — retryable replica death, NOT a
        deliberate close — and close the engine; a broken engine must
        reject, not wedge."""
        failure = exc if isinstance(exc, ReplicaFailedError) \
            else ReplicaFailedError(
                f"generation worker died: {type(exc).__name__}: {exc}",
                cause=exc)
        if not isinstance(exc, ReplicaFailedError):
            failure.__cause__ = exc
        self._failure = failure
        self._closed = True
        tracing.flight.dump("engine.fail_all",
                            error=f"{type(exc).__name__}: {exc}")
        self._ahead = None
        for i, s in enumerate(self._slots):
            if s is not None:
                s.stream._finish(exc=failure)
                self._release_slot_refs(s)
                self._slots[i] = None
        self._n_active = 0
        self._n_sampling = 0
        self._teardown_paged(failure)
        if self._worker is not None:
            self._worker._stopped = True  # a still-looping worker (an
            # injected failure, not a real crash) exits at its next poll
            try:
                while True:
                    r = self._worker._queue.get_nowait()
                    r.stream._finish(exc=failure)
            except queue.Empty:
                pass
        _live_engines.discard(self)
