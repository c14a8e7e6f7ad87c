"""Multi-tenant batched decode: ``ServePool`` packs independent generation
requests into a fixed ``(slots, max_len)`` decode batch.

The serving substrate (``make_serve_steps`` + the per-slot-position KV cache
from ``transformer.init_cache``) decodes a whole batch in one jitted step,
each row at its OWN offset.  ``ServePool`` is the scheduler on top:

* ``submit()`` enqueues a request (prompt + token budget + optional EOS);
* admission prefills the prompt on a dedicated batch-1 cache and SCATTERS
  the resulting KV rows (and per-slot position) into a free slot of the
  pool cache — live tenants' rows are untouched, so admitting tenant B
  never re-prefills tenant A;
* every ``step()`` runs ONE batched decode over all slots; finished rows
  (budget exhausted or EOS emitted) free their slot, which the next
  admission recycles;
* ``stats()`` reports slot occupancy, token and request counts, and the
  host time of each phase of ``step()`` (``"phases"``) —
  ``Session.report()`` surfaces it for every pool the session created.

The pool cache is DONATED to every program that returns its successor
(decode, adoption, slot release, parking), so each writes the KV stack in
place: the pool rebinds ``_cache`` to the result and never touches the old
one.  ``stats()["kv_in_place"]`` says whether the K stack still lives in
the device buffer the pool started with.  The batch-1 template that every
admission starts from is never donated.

Each phase of ``step()`` is a profiler span ``pool.<phase>`` on the host
timeline, nested in ``pool.step`` and on the clock the device trace uses,
and an always-on counter (count, total and longest seconds) under
``stats()["phases"]`` (``pipeline/spans.py``).  ``docs/serving.md`` lists
the phases.

The aggregate win is the usual continuous-batching one: a decode step over
``k`` live slots costs roughly the same wall time as over one, so serving
``k`` tenants concurrently multiplies tokens/s until the step becomes
compute-bound (``benchmarks/serve_pool.py`` tracks the curve).

Continuous admission (``prefill_chunk=`` / ``bucket_prompts=``) streams the
admission prefill instead of running it whole:

* ``bucket_prompts=True`` right-pads each prompt to a power-of-two length
  bucket before prefill (causal masking makes real positions independent of
  the padding), collapsing the per-prompt-length jit retraces of the legacy
  path to at most ~``log2(max_len)`` distinct prefill shapes;
* ``prefill_chunk=N`` feeds the (padded) prompt through the incremental
  chunk prefill N tokens at a time, ONE chunk per ``step()`` while tenants
  are live — a long prompt's admission interleaves with decode instead of
  stalling every live tenant for its full prefill.

Both are token-identical to the legacy whole-prompt path (asserted in
tests/test_traffic.py) and compose with paged KV: bucket-padding pages
never reach the pool (adoption copies only the real context), and an
admission abandoned mid-stream (deadline, chaos) drops its private batch-1
cache without touching the pool page table.  ``pipeline/traffic.py`` +
``benchmarks/traffic_replay.py`` measure the latency win under open-loop
Poisson load.

Works transparently over a mesh-sharded serving state (``mesh=`` — see
``docs/serving.md``): the pool cache lives in the flash-decoding layout and
admission scatters into the sharded rows.

Graceful degradation (see docs/resilience.md "Degradation policy"): a bad
request fails ALONE; healthy tenants keep their slots and their tokens.

* page-reservation admission — each request reserves its worst-case page
  count up front, so an oversubscribed pool (``pool_pages=``) backpressures
  at admission (bounded FIFO retry, then a per-request failure) instead of
  underflowing the free list mid-decode;
* a NaN/inf logit guard quarantines only the offending slot (fail + free
  the pages, no token appended) — the other slots' tokens are
  bit-identical to a fault-free run;
* per-request deadlines (``submit(deadline_s=)``) and a pool wall-clock
  budget (``run(budget_s=)``) expire stragglers as failures;
* flash decode-attention degrades to the XLA gather path (the same maths,
  equal up to f32 reduction order) when the Pallas call raises
  (``models.nn._paged_attention``).

Failures are reported per-request: ``request(rid).status == "failed"`` with
a stable ``.error`` code (``FailReason`` — the router's retry/trip policy
keys on it) and the human-readable ``.error_detail``, and aggregated in
``stats()["failures"]`` (a bounded ring of recent entries; the per-reason
counters in ``stats()["fail_reasons"]`` stay exact forever).

Time comes from an injectable clock (``pipeline.clock``): deadlines,
budgets and the request stamps ``submitted_at``, ``admitted_at`` and
``first_token_at`` all read ``clock.now()``, so tests pin expiry behavior
on a ``VirtualClock`` instead of sleeping.

Example::

    pool = session.serve_pool(slots=4, max_len=64)
    for p in prompts:                       # independent tenants
        pool.submit(p, max_new_tokens=16)
    outputs = pool.run()                    # {rid: np.ndarray of token ids}
    print(pool.stats()["phases"]["decode_wait"])
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import paged_cache
from repro.pipeline.clock import WallClock
from repro.pipeline.spans import Phases
from repro.resilience import faults
from repro.train.steps import make_serve_steps

# families whose decode step tolerates per-slot state: transformers carry
# per-slot positions in the KV cache; SSM states are position-free.
# hybrid/encdec caches still hold one shared position per segment, and the
# vlm/encdec frontends need more than a token prompt at admission.
SUPPORTED_FAMILIES = ("dense", "moe", "ssm")

# recent-failure ring size (aggregate counters stay exact past the cap)
FAILURE_LOG_CAP = 512


class FailReason(str, enum.Enum):
    """Stable failure-reason codes carried in ``Request.error`` and
    ``stats()["failures"]``.  The free-text explanation lives in
    ``Request.error_detail`` / the failure entry's ``detail`` — policy
    code (router retries, breaker trips, alerting) keys on THESE values,
    never on message text.  A ``str`` mixin so existing substring checks
    and JSON serialization keep working."""

    DEADLINE = "deadline"        # per-request deadline_s expired
    QUARANTINE = "quarantine"    # NaN/inf logits; slot quarantined
    ADMISSION = "admission"      # page backpressure retries exhausted
    BUDGET = "budget"            # pool run(budget_s=) exhausted
    SHED = "shed"                # load-shed at the router front door
    REPLICA = "replica"          # serving replica died/tripped under it

    def __str__(self) -> str:    # "deadline", not "FailReason.DEADLINE"
        return self.value


@dataclasses.dataclass
class Request:
    """One tenant's generation request, tracked by the pool.

    ``tokens`` accumulates the generated ids (the first comes from the
    admission prefill, the rest from batched decode steps).  ``status``
    walks ``queued -> live -> done`` — or ``-> failed`` (NaN quarantine,
    deadline/budget expiry, admission retry exhaustion), with the stable
    reason code in ``error`` (a ``FailReason``) and the human-readable
    explanation in ``error_detail``.  ``done`` stays the boolean
    "completed successfully" flag (failed requests are terminal but NOT
    done)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: int | None = None
    deadline_s: float | None = None
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "queued"         # queued | live | done | failed
    error: FailReason | None = None
    error_detail: str | None = None
    slot: int | None = None
    # pool clock.now() at submit, when admission began (it left the
    # queue), and when its first token was appended
    submitted_at: float = 0.0
    admitted_at: float | None = None
    first_token_at: float | None = None
    admit_denials: int = 0         # backpressure retries so far
    pages_reserved: int = 0        # worst-case pages held while admitted

    @property
    def output(self) -> np.ndarray:
        return np.asarray(self.tokens, np.int32)


class ServePool:
    """Fixed-slot multi-tenant decode scheduler over one weight snapshot.

    Built once per serving session (``Session.serve_pool``): runs
    ``init_serve`` for the pool batch (weight-cache contraction + pool KV
    cache); the admission prefill path reuses that same weight snapshot
    over a batch-1 cache template (serve params are batch-independent — no
    second contraction, no second mesh placement).  The snapshot is taken
    at construction — like ``ServeHandle``, a pool built before a
    ``finetune``/``squeeze`` keeps serving the OLD weights; build a new
    pool after mutating the session.
    """

    def __init__(self, model, params, slots: int, max_len: int, *,
                 weight_cache: bool = True, mesh=None, rules=None,
                 axes=None, version: int = 0, paged: bool = False,
                 page_size: int = 16, pool_pages: int | None = None,
                 admission_retry_limit: int = 1000,
                 guard_logits: bool = True,
                 prefill_chunk: int | None = None,
                 bucket_prompts: bool = False, bucket_min: int = 8,
                 clock=None):
        if model.cfg.family not in SUPPORTED_FAMILIES:
            raise NotImplementedError(
                f"ServePool supports families {SUPPORTED_FAMILIES}; "
                f"{model.cfg.family!r} decode still tracks one shared "
                "position per cache segment (or needs a non-token frontend "
                "at admission), so slots cannot sit at independent offsets")
        if paged and model.cfg.family == "ssm":
            raise ValueError("paged KV cache requires an attention KV "
                             "cache; family 'ssm' has none")
        if slots < 1:
            raise ValueError(f"slots={slots} must be >= 1")
        if pool_pages is not None and not paged:
            raise ValueError("pool_pages= requires paged=True")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be >= 1 (or None to "
                "disable chunked admission)")
        if bucket_min < 1:
            raise ValueError(f"bucket_min={bucket_min} must be >= 1")
        if ((prefill_chunk is not None or bucket_prompts)
                and model.prefill_chunk is None):
            raise ValueError(
                "chunked/bucketed admission needs an incremental KV prefill "
                f"(model.prefill_chunk); family {model.cfg.family!r} has "
                "none — use the default whole-prompt admission")
        self.slots, self.max_len = slots, max_len
        self.mesh = mesh
        self.version = version
        self.paged, self.page_size = paged, page_size
        self.admission_retry_limit = admission_retry_limit
        self.guard_logits = guard_logits
        self.prefill_chunk = prefill_chunk
        self.bucket_prompts, self.bucket_min = bucket_prompts, bucket_min
        # all deadline/budget arithmetic reads this clock (tests pass a
        # VirtualClock; share ONE instance with the router/replay loop)
        self.clock = WallClock() if clock is None else clock
        # continuous admission: prompts stream through the chunked-prefill
        # step (one chunk per decode step while tenants are live)
        self._continuous = prefill_chunk is not None or bucket_prompts
        t0 = time.perf_counter()
        # pool-batch steps: one jitted decode over all slots
        prefill, self._decode, init_pool, chunk_step = make_serve_steps(
            model, weight_cache=weight_cache, mesh=mesh, rules=rules,
            axes=axes, paged=paged, page_size=page_size,
            pool_pages=pool_pages)
        self._sparams, self._cache = init_pool(params, slots, max_len)
        if paged:
            # park every slot at the capacity sentinel: idle rows neither
            # write pages nor allocate from the shared pool until a tenant
            # is adopted into them
            self._cache = jax.jit(paged_cache.park, donate_argnums=0)(
                self._cache)
        # Admission path: batch-1 prefill over the SAME weight snapshot —
        # serve params are batch-independent, so the pool never contracts
        # (or, under a mesh, places) a second copy of the weights.  Only a
        # batch-1 cache template is extra.  The pool's mesh-jitted prefill
        # is pinned to the pool cache's shardings, so admission gets its
        # own jit; the committed placement of ``_sparams`` carries through
        # it without explicit in_shardings.
        cache_kw = {"paged": True, "page_size": page_size} if paged else {}
        if mesh is None:
            self._decode = jax.jit(self._decode, donate_argnums=2)
            self._prefill1 = jax.jit(prefill)
            self._chunk1 = (jax.jit(chunk_step)
                            if chunk_step is not None else None)
            self._cache1_template = model.init_cache(1, max_len, **cache_kw)
        else:
            from repro.parallel import sharding as S
            from repro.parallel.ctx import maybe_mesh
            rules1 = S.make_rules(mesh) if rules is None else rules
            cache1 = model.init_cache(1, max_len, **cache_kw)
            cshard1 = S.cache_sharding(
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             cache1), mesh, rules1)
            self._cache1_template = jax.device_put(cache1, cshard1)
            jit1 = jax.jit(
                lambda p, b, c: model.prefill(p, b, c, phase="prefill"))

            def prefill1(p, b, c):
                with maybe_mesh(mesh):  # activation constraints at trace
                    return jit1(p, b, c)

            self._prefill1 = prefill1
            self._chunk1 = chunk_step  # already jit-backed + mesh-wrapped
        # after a bucketed prefill the batch-1 cache position sits at the
        # PADDED length; pin it back to the real prompt length so adoption
        # copies (and decode continues from) exactly the real context
        self._fix_len = jax.jit(
            lambda c, n: dict(c, pos=jnp.full_like(c["pos"], n)))
        self.init_seconds = time.perf_counter() - t0

        self._adopt = jax.jit(paged_cache.adopt if paged
                              else self._adopt_fn, donate_argnums=0)
        self._free = (jax.jit(paged_cache.free, donate_argnums=0)
                      if paged else None)
        self._kv_start = self._kv_buffers()
        # per-slot finiteness of the decode logits (device-side reduce: a
        # (slots,) bool vector crosses to host, never the logits)
        self._finite = jax.jit(
            lambda l: jnp.isfinite(l).all(axis=tuple(range(1, l.ndim))))
        self._requests: dict[int, Request] = {}
        self._queue: collections.deque[int] = collections.deque()
        self._slot_rid: list[int | None] = [None] * slots
        self._last_tok = np.zeros((slots, 1), np.int32)
        self._next_rid = 0
        # in-flight chunked admission (continuous mode): at most one prompt
        # streams through the batch-1 chunk prefill at a time, one chunk per
        # step while tenants are live.  The target slot is NOT in
        # ``_slot_rid`` until the last chunk lands (decode skips it).
        self._admit_state: dict | None = None
        # page-reservation admission state (paged pools only)
        self._total_pages = (int(self._cache["k_pages"].shape[1])
                             if paged else 0)
        self._reserved_pages = 0
        # ---- stats ----
        self._decode_steps = 0
        self._live_slot_steps = 0       # sum of live slots over decode steps
        self._kv_share_sum = 0.0        # sum of decode_kv_share over steps
        self._tokens_generated = 0
        self._prefill_tokens = 0        # prompt tokens prefilled (real, unpadded)
        self._decode_tokens = 0         # tokens produced by batched decode
        self._prefill_shapes: set[int] = set()  # distinct prefill seq lengths
        self._completed = 0
        self._failed = 0
        # recent failures only (long replays must not grow without bound);
        # _fail_reasons keeps the exact per-reason totals forever
        self._failure_cap = int(os.environ.get("REPRO_FAILURE_LOG_CAP",
                                               FAILURE_LOG_CAP))
        self._failures: collections.deque[dict] = collections.deque(
            maxlen=self._failure_cap)
        self._fail_reasons: collections.Counter = collections.Counter()
        # host time of each phase of step(), and its profiler spans
        self._phases = Phases("pool")

    # ---- admission ----

    @staticmethod
    def _adopt_fn(pool_cache, one_cache, slot):
        """Scatter a batch-1 dense cache into pool slot ``slot``: row
        ``slot`` of every K/V stack or SSM state (layers, batch, ...) and
        of ``pos`` (batch,) takes the admitted tenant's, while all other
        rows pass through untouched.  (A paged pool adopts through
        ``paged_cache.adopt``.)"""
        def one(pc, oc):
            if pc.ndim == 1:                        # per-slot positions
                return pc.at[slot].set(oc[0])
            return pc.at[:, slot].set(oc[:, 0].astype(pc.dtype))
        return jax.tree.map(one, pool_cache, one_cache)

    def _need_pages(self, prompt_len: int, max_new: int) -> int:
        """Worst-case page count a request can ever occupy: the prefill
        appends ``prompt_len`` keys, each decode step one more, and the
        LAST generated token never appends (its key is never attended)."""
        if not self.paged:
            return 0
        return -(-(prompt_len + max_new - 1) // self.page_size)

    def validate_request(self, prompt, max_new_tokens: int,
                         deadline_s: float | None = None) -> np.ndarray:
        """Reject requests that can NEVER be served — prompt + budget over
        ``max_len`` or over the whole physical page pool — up front, with an
        actionable error; returns the normalized (1-D int32) prompt.  (This
        is also what makes head-of-line admission safe: a queued request
        always fits EVENTUALLY.)  Shared by ``submit`` and the fleet
        router, which validates against pool geometry before enqueueing."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size} tokens) + max_new_tokens "
                f"({max_new_tokens}) exceeds the pool max_len "
                f"({self.max_len}); raise max_len or shorten the request")
        need = self._need_pages(prompt.size, max_new_tokens)
        if need > self._total_pages:
            raise ValueError(
                f"request needs {need} KV pages (prompt {prompt.size} + "
                f"max_new_tokens {max_new_tokens} at page_size "
                f"{self.page_size}) but the physical pool only holds "
                f"{self._total_pages}; raise pool_pages or shorten the "
                f"request")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s={deadline_s} must be positive")
        return prompt

    def submit(self, prompt, max_new_tokens: int, eos_id: int | None = None,
               deadline_s: float | None = None) -> int:
        """Enqueue one generation request; returns its request id.  The
        prompt is a 1-D sequence of token ids; admission happens at the next
        ``step()``/``run()`` when a slot is free.  ``deadline_s`` bounds the
        request's total wall-clock lifetime (queue wait included): past it,
        the request fails with whatever tokens it has.  Impossible requests
        are rejected here (``validate_request``)."""
        prompt = self.validate_request(prompt, max_new_tokens, deadline_s)
        rid = self._next_rid
        self._next_rid += 1
        self._requests[rid] = Request(rid, prompt, max_new_tokens, eos_id,
                                      deadline_s=deadline_s,
                                      submitted_at=self.clock.now())
        self._queue.append(rid)
        return rid

    def request(self, rid: int) -> Request:
        """The tracked request (status/error/tokens) for ``rid``."""
        return self._requests[rid]

    def _finish(self, req: Request):
        req.done = True
        req.status = "done"
        self._release_reservation(req)
        self._completed += 1

    def _fail(self, req: Request, reason: FailReason, detail: str):
        """Terminal per-request failure: the pool keeps serving everyone
        else; the partial output stays on the request.  ``reason`` is the
        stable policy code, ``detail`` the human-readable explanation."""
        req.status = "failed"
        req.error = reason
        req.error_detail = detail
        self._release_reservation(req)
        self._failed += 1
        self._fail_reasons[reason.value] += 1
        self._failures.append({"rid": req.rid, "slot": req.slot,
                               "reason": reason.value, "detail": detail})

    def _release_reservation(self, req: Request):
        self._reserved_pages -= req.pages_reserved
        req.pages_reserved = 0

    def _release_slot(self, slot: int):
        """Free pool slot ``slot`` (pages back to the pool for paged
        caches); the next admission recycles it."""
        self._slot_rid[slot] = None
        if self.paged:
            self._cache = self._free(self._cache, jnp.int32(slot))

    def _expired(self, req: Request) -> bool:
        return (req.deadline_s is not None
                and self.clock.now() - req.submitted_at > req.deadline_s)

    def _expire(self):
        """Fail queued and live requests past their deadline."""
        if any(self._requests[r].deadline_s is not None
               for r in self._queue) or any(
                   r is not None and self._requests[r].deadline_s is not None
                   for r in self._slot_rid):
            keep = collections.deque()
            for rid in self._queue:
                req = self._requests[rid]
                if self._expired(req):
                    self._fail(req, FailReason.DEADLINE,
                               f"deadline ({req.deadline_s}s) expired "
                               "before admission")
                else:
                    keep.append(rid)
            self._queue = keep
            for slot, rid in enumerate(self._slot_rid):
                if rid is None:
                    continue
                req = self._requests[rid]
                if self._expired(req):
                    self._fail(req, FailReason.DEADLINE,
                               f"deadline ({req.deadline_s}s) expired "
                               f"after {len(req.tokens)} tokens")
                    self._release_slot(slot)
        st = self._admit_state
        if st is not None and self._expired(st["req"]):
            # in-flight chunked admission: drop the half-built batch-1
            # cache; nothing was adopted, so the pool is untouched
            self._admit_state = None
            self._fail(st["req"], FailReason.DEADLINE,
                       f"deadline ({st['req'].deadline_s}s) "
                       "expired between prefill chunks "
                       f"({st['next']}/{len(st['pieces'])})")

    def _admit_one(self, slot: int, req: Request):
        """Prefill the prompt at batch 1 and scatter its cache rows into
        ``slot``.  The prefill's last-position logits yield the tenant's
        FIRST generated token (mirror of ``ServeHandle.generate``)."""
        req.slot = slot
        req.admitted_at = self.clock.now()
        batch = {"tokens": jnp.asarray(req.prompt)[None, :]}
        self._prefill_shapes.add(int(req.prompt.size))
        with self._phases("prefill_chunk", rid=req.rid, chunk=0,
                          tokens=int(req.prompt.size)):
            logits, cache1 = self._prefill1(self._sparams, batch,
                                            self._cache1_template)
        with self._phases("first_token", rid=req.rid):
            first = int(np.asarray(jnp.argmax(logits[:, -1], -1))[0])
        req.tokens.append(first)
        req.first_token_at = self.clock.now()
        self._tokens_generated += 1
        self._prefill_tokens += int(req.prompt.size)
        if req.max_new_tokens == 1 or first == req.eos_id:
            self._finish(req)       # never occupies the slot
        else:
            req.status = "live"
            self._slot_rid[slot] = req.rid
            self._last_tok[slot, 0] = first
            with self._phases("adopt", rid=req.rid, slot=slot):
                self._cache = self._adopt(self._cache, cache1,
                                          jnp.int32(slot))

    # ---- continuous admission (chunked / length-bucketed prefill) ----
    #
    # The legacy path above prefills the WHOLE prompt in one jitted call:
    # every distinct prompt length is a fresh trace, and a long prompt
    # stalls all live tenants for its full prefill.  Continuous mode fixes
    # both: prompts are right-padded to a power-of-two length bucket (the
    # causal mask makes real positions independent of the padding, so
    # distinct traces collapse to ~log2(max_len)) and fed through the
    # incremental chunk prefill ONE chunk per step while tenants are live —
    # decode interleaves between chunks, so a long admission never stalls
    # the pool.  Token-identical to the legacy path (asserted in
    # tests/test_traffic.py).

    def _bucket_len(self, n: int) -> int:
        """Padded prefill length for an ``n``-token prompt: next power of
        two, floored at ``bucket_min``, capped at ``max_len``."""
        if not self.bucket_prompts:
            return n
        return min(max(self.bucket_min, 1 << (n - 1).bit_length()),
                   self.max_len)

    def _pieces(self, prompt: np.ndarray) -> list[np.ndarray]:
        """Split the (bucket-padded) prompt into prefill chunks.  Padding
        token ids are irrelevant (never attended by real positions, and
        their KV is overwritten before decode attends it): zeros."""
        padded_len = self._bucket_len(prompt.size)
        if padded_len != prompt.size:
            prompt = np.concatenate(
                [prompt, np.zeros(padded_len - prompt.size, np.int32)])
        c = self.prefill_chunk
        if c is None or c >= padded_len:
            return [prompt]
        return [prompt[i:i + c] for i in range(0, padded_len, c)]

    def _admit_start(self, slot: int, req: Request):
        """Begin a (possibly multi-step) chunked admission into ``slot``."""
        req.slot = slot
        req.status = "admitting"
        req.admitted_at = self.clock.now()
        self._admit_state = {"req": req, "slot": slot,
                             "cache": self._cache1_template,
                             "pieces": self._pieces(req.prompt),
                             "next": 0, "off": 0, "first": None}

    def _admit_piece(self):
        """Run ONE prefill chunk of the in-flight admission; complete the
        admission (first token + pool adoption) after the last chunk."""
        st = self._admit_state
        req = st["req"]
        if st["next"] > 0 and (faults.admit_chunk_expired(st["next"])
                               or self._expired(req)):
            # deadline blew between chunks: the half-built batch-1 cache is
            # simply dropped — nothing was adopted, the pool page table and
            # the slot are untouched
            self._admit_state = None
            self._fail(req, FailReason.DEADLINE,
                       f"deadline ({req.deadline_s}s) expired between "
                       f"prefill chunks ({st['next']}/{len(st['pieces'])})")
            return
        piece = st["pieces"][st["next"]]
        self._prefill_shapes.add(int(piece.size))
        with self._phases("prefill_chunk", rid=req.rid, chunk=st["next"],
                          tokens=int(piece.size)):
            logits, st["cache"] = self._chunk1(
                self._sparams, {"tokens": jnp.asarray(piece)[None, :]},
                st["cache"])
        # the REAL last prompt token's logits row picks the first generated
        # token — under bucket padding that row is inside some chunk, not
        # necessarily the last position of the last chunk
        last = int(req.prompt.size) - 1
        if st["off"] <= last < st["off"] + piece.size:
            with self._phases("first_token", rid=req.rid):
                st["first"] = int(np.asarray(
                    jnp.argmax(logits[0, last - st["off"]], -1)))
        st["off"] += int(piece.size)
        st["next"] += 1
        if st["next"] >= len(st["pieces"]):
            self._admit_state = None
            self._admit_complete(req, st)

    def _admit_complete(self, req: Request, st: dict):
        """All chunks prefilled: emit the first token; adopt into the pool
        slot unless the request finished instantly (mirrors _admit_one)."""
        first = st["first"]
        req.tokens.append(first)
        req.first_token_at = self.clock.now()
        self._tokens_generated += 1
        self._prefill_tokens += int(req.prompt.size)
        if req.max_new_tokens == 1 or first == req.eos_id:
            self._finish(req)       # never occupies the slot
        else:
            # pin the batch-1 position from the padded length back to the
            # real prompt length: adoption then copies only the real
            # context (paged: only ceil(real/ps) pages — padding pages
            # never reach the pool), and decode overwrites the padded KV
            # at position ``real_len`` before anything attends it
            slot = st["slot"]
            with self._phases("adopt", rid=req.rid, slot=slot):
                cache1 = self._fix_len(st["cache"],
                                       jnp.int32(req.prompt.size))
                self._cache = self._adopt(self._cache, cache1,
                                          jnp.int32(slot))
            req.status = "live"
            self._slot_rid[slot] = req.rid
            self._last_tok[slot, 0] = first

    def _admission_blocked(self, req: Request) -> bool:
        """Page backpressure: deny admission while the head request's
        worst-case reservation does not fit the unreserved remainder of the
        pool.  Head-of-line blocking is deliberate (FIFO fairness) and safe:
        ``submit`` already rejected anything that can never fit, so the head
        clears as live tenants finish and release their reservations."""
        if not self.paged:
            return False
        need = self._need_pages(req.prompt.size, req.max_new_tokens)
        denied = (self._reserved_pages + need > self._total_pages
                  or faults.page_admission_denied())
        if denied:
            req.admit_denials += 1
        else:
            req.pages_reserved = need
            self._reserved_pages += need
        return denied

    def _free_slot_for_admission(self) -> int | None:
        """A slot no live tenant (and no in-flight admission) holds."""
        held = (self._admit_state["slot"]
                if self._admit_state is not None else None)
        for slot in range(self.slots):
            if self._slot_rid[slot] is None and slot != held:
                return slot
        return None

    def _admit(self):
        if self._continuous:
            self._admit_continuous()
            return
        # keep scanning: an admission that finishes instantly (one-token
        # budget / first-token EOS) leaves its slot free for the next
        # pending request in the SAME pass
        progressed = True
        while self._queue and progressed:
            progressed = False
            for slot in range(self.slots):
                if not self._queue:
                    return
                if self._slot_rid[slot] is not None:
                    continue
                req = self._requests[self._queue[0]]
                if self._admission_blocked(req):
                    if req.admit_denials > self.admission_retry_limit:
                        self._queue.popleft()
                        self._fail(req, FailReason.ADMISSION,
                                   "page-pool admission denied "
                                   f"{req.admit_denials} times "
                                   "(admission_retry_limit="
                                   f"{self.admission_retry_limit})")
                        progressed = True
                    # else: leave the head queued; a later step retries
                    break
                self._queue.popleft()
                self._admit_one(slot, req)
                progressed = True

    def _admit_continuous(self):
        """Continuous-mode admission: while tenants are live, run at most
        ONE prefill chunk per step (decode interleaves between chunks, so a
        long prompt never stalls the pool); with nobody live there is
        nothing to stall, so drain chunks back-to-back."""
        while True:
            if self._admit_state is not None:
                self._admit_piece()
            elif self._queue:
                slot = self._free_slot_for_admission()
                if slot is None:
                    return
                req = self._requests[self._queue[0]]
                if self._admission_blocked(req):
                    if req.admit_denials > self.admission_retry_limit:
                        self._queue.popleft()
                        self._fail(req, FailReason.ADMISSION,
                                   "page-pool admission denied "
                                   f"{req.admit_denials} times "
                                   "(admission_retry_limit="
                                   f"{self.admission_retry_limit})")
                        continue    # head failed: try the next request
                    return          # head stays queued; a later step retries
                self._queue.popleft()
                self._admit_start(slot, req)
                self._admit_piece()
            else:
                return
            if self.live > 0:
                return              # decode is waiting: one chunk per step

    # ---- decode ----

    @property
    def live(self) -> int:
        """Currently occupied slots."""
        return sum(r is not None for r in self._slot_rid)

    @property
    def pending(self) -> int:
        """Submitted but not yet admitted requests."""
        return len(self._queue)

    @property
    def admitting(self) -> bool:
        """A chunked admission is in flight (continuous mode only)."""
        return self._admit_state is not None

    @property
    def free_pages(self) -> int | None:
        """Unreserved KV pages (host-side reservation accounting — no
        device sync), ``None`` for dense pools.  The router's least-loaded
        policy reads this."""
        if not self.paged:
            return None
        return self._total_pages - self._reserved_pages

    def step(self) -> int:
        """Expire deadline-blown requests, admit whatever fits, then run ONE
        batched decode step over all slots.  Returns the number of live
        slots that advanced (0 means the pool is drained).

        NaN/inf quarantine (``guard_logits``): a live slot whose logits row
        went non-finite fails ALONE — no token is appended for it, its slot
        and pages are freed, and every healthy slot's argmax is taken from
        the same logit values it would see in a fault-free run (token
        parity is asserted in tests/test_resilience.py).

        Every phase below is a span ``pool.<phase>`` nested in
        ``pool.step`` and a counter in ``stats()["phases"]``;
        ``first_token`` and ``decode_wait`` are the host's waits on the
        device."""
        phase = self._phases
        with phase("step", live=self.live, pending=self.pending):
            with phase("expire"):
                self._expire()
            # the request admission serves: the in-flight one, else the head
            st = self._admit_state
            head = (st["req"].rid if st is not None
                    else self._queue[0] if self._queue else None)
            with phase("admit", **({} if head is None else {"rid": head})):
                self._admit()
            if self.live == 0:
                return 0
            with phase("decode"):
                tok, logits, self._cache = self._decode(
                    self._sparams, jnp.asarray(self._last_tok), self._cache)
                if self.paged:              # while the device decodes
                    self._kv_share_sum += self._decode_kv_share()
            with phase("decode_wait"):
                # chaos: NaN-poison one slot's logits at the chosen decode
                # step (host-side copy — device values and healthy slots
                # are untouched)
                corrupted = faults.corrupt_decode_logits(logits,
                                                         self._decode_steps)
                if corrupted is not None:
                    finite = np.isfinite(corrupted).all(
                        axis=tuple(range(1, corrupted.ndim)))
                    tok_host = np.argmax(corrupted[:, -1], axis=-1
                                         ).astype(np.int32)[:, None]
                else:
                    finite = (np.asarray(self._finite(logits))
                              if self.guard_logits else None)
                    tok_host = np.asarray(tok)
            self._decode_steps += 1
            advanced = finished = 0
            with phase("emit") as span:
                for slot, rid in enumerate(self._slot_rid):
                    if rid is None:
                        continue
                    advanced += 1
                    req = self._requests[rid]
                    if finite is not None and not finite[slot]:
                        self._fail(req, FailReason.QUARANTINE,
                                   "non-finite logits at decode step "
                                   f"{self._decode_steps - 1} (slot {slot} "
                                   "quarantined)")
                        self._release_slot(slot)
                        continue            # no token appended for it
                    t = int(tok_host[slot, 0])
                    req.tokens.append(t)
                    self._tokens_generated += 1
                    self._decode_tokens += 1
                    self._last_tok[slot, 0] = t
                    if (len(req.tokens) >= req.max_new_tokens
                            or t == req.eos_id):
                        self._finish(req)
                        self._release_slot(slot)  # recycled at next admission
                        finished += 1
                span.set_metadata(finished=finished)
            self._live_slot_steps += advanced
            return advanced

    def _decode_kv_share(self) -> float:
        """Share of the pool's key span this decode step gathers
        (``kernels.decode_attention.decode_kv_share``): each live slot
        attends its context plus the token it appends; every other slot
        is parked at the capacity sentinel, length 0."""
        from repro.kernels import decode_attention as DA
        lengths = np.zeros(self.slots, np.int64)
        for slot, rid in enumerate(self._slot_rid):
            if rid is not None:
                req = self._requests[rid]
                lengths[slot] = req.prompt.size + len(req.tokens)
        model = (1 if self.mesh is None else dict(
            zip(self.mesh.axis_names, self.mesh.devices.shape)).get("model", 1))
        pages = self.max_len // self.page_size
        return DA.decode_kv_share(np.minimum(lengths, self.max_len),
                                  self.page_size, pages, model)

    def run(self, budget_s: float | None = None) -> dict[int, np.ndarray]:
        """Drain the pool: step until every submitted request completed (or
        failed).  Returns {rid: generated token ids} for ALL successfully
        finished requests; failures are on ``request(rid)`` / ``stats()``.

        ``budget_s`` bounds the WHOLE drain's clock time (the injected
        ``clock``: wall seconds by default, deterministic steps on a
        ``VirtualClock``): past it, every still-queued/live request fails
        with its partial output and the call returns what completed in
        time."""
        t0 = self.clock.now()
        while (self._queue or self.live > 0
               or self._admit_state is not None):
            if budget_s is not None and self.clock.now() - t0 > budget_s:
                for rid in list(self._queue):
                    self._fail(self._requests[rid], FailReason.BUDGET,
                               f"pool wall-clock budget ({budget_s}s) "
                               "exhausted before admission")
                self._queue.clear()
                if self._admit_state is not None:
                    st, self._admit_state = self._admit_state, None
                    self._fail(st["req"], FailReason.BUDGET,
                               "pool wall-clock budget "
                               f"({budget_s}s) exhausted between prefill "
                               f"chunks ({st['next']}/{len(st['pieces'])})")
                for slot, rid in enumerate(self._slot_rid):
                    if rid is not None:
                        req = self._requests[rid]
                        self._fail(req, FailReason.BUDGET,
                                   "pool wall-clock budget "
                                   f"({budget_s}s) exhausted after "
                                   f"{len(req.tokens)} tokens")
                        self._release_slot(slot)
                break
            advanced = self.step()
            self.clock.on_step(advanced)   # no-op on WallClock
            if (advanced == 0 and not self._queue
                    and self._admit_state is None):
                break
        return {rid: r.output for rid, r in self._requests.items()
                if r.done}

    # ---- reporting ----

    def _kv_buffers(self) -> tuple | None:
        """Device buffers of the pool's K stack, one per addressable shard
        (``None`` for a cache without K/V)."""
        if not isinstance(self._cache, dict):
            return None
        k = self._cache.get("k_pages", self._cache.get("k"))
        return tuple(sh.data.unsafe_buffer_pointer()
                     for sh in k.addressable_shards)

    def stats(self) -> dict:
        """Scheduler counters: slot occupancy (mean live fraction per decode
        step), token and admission/completion totals, and ``phases``: for
        each phase of ``step()``, ``{"n", "s", "max_s"}``: entries and host
        seconds (``time.perf_counter()``) since the pool was built, and the
        longest single entry since the previous ``stats()`` call.
        ``kv_in_place``: the K stack still sits in the device buffer it
        was allocated in (every program that rebinds the cache wrote it in
        place); ``None`` without a KV cache.  ``decode_kv_share``: the mean
        over decode steps of the share of the ``(slots, max_len)`` key span
        the XLA decode gather reads (``_decode_kv_share``); ``None`` for a
        dense pool or before the first decode step."""
        page_pool = None
        if self.paged:
            pages = int(self._cache["k_pages"].shape[1])
            used = int(jax.device_get(paged_cache.used_pages(self._cache)))
            page_pool = {"pages": pages, "used": used,
                         "reserved": self._reserved_pages,
                         "page_size": self.page_size,
                         "occupancy": used / pages}
        from repro.kernels import decode_attention as DA
        return {
            "page_pool": page_pool,
            "failed": self._failed,
            # bounded ring of RECENT failures; fail_reasons stays exact
            "failures": list(self._failures),
            "fail_reasons": dict(self._fail_reasons),
            "failure_log_cap": self._failure_cap,
            "flash_fallbacks": DA.FALLBACKS,
            "slots": self.slots,
            "max_len": self.max_len,
            "mesh": None if self.mesh is None else
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            "submitted": self._next_rid,
            "completed": self._completed,
            "pending": self.pending,
            "live": self.live,
            "decode_steps": self._decode_steps,
            "tokens_generated": self._tokens_generated,
            "occupancy": (self._live_slot_steps
                          / max(self._decode_steps * self.slots, 1)),
            "init_seconds": round(self.init_seconds, 4),
            # REAL prompt tokens prefilled (bucket padding excluded), and
            # tokens produced by batched decode
            "prefill_tokens": self._prefill_tokens,
            "decode_tokens": self._decode_tokens,
            "phases": self._phases.snapshot(),
            "kv_in_place": (None if self._kv_start is None
                            else self._kv_buffers() == self._kv_start),
            "decode_kv_share": (self._kv_share_sum / self._decode_steps
                                if self.paged and self._decode_steps
                                else None),
            # admission retrace accounting: distinct prefill/chunk sequence
            # lengths fed to the batch-1 jit (each is one trace); bucketing
            # bounds this at ~log2(max_len)
            "prefill_traces": len(self._prefill_shapes),
            "prefill_chunk": self.prefill_chunk,
            "bucket_prompts": self.bucket_prompts,
            "weights_version": self.version,
        }
