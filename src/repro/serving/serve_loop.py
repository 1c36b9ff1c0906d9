"""Serving driver: prefill/decode step factories + continuous batching.

``make_serve_fns`` returns jit-able pure step functions (the things the
dry-run lowers); ``ServeLoop`` is the host-side driver implementing
*correct* continuous batching over fixed decode slots:

  * **Per-slot prefill** — admission runs the new request's prompt as a
    batch-1 prefill and scatters the resulting cache into ONLY its own
    batch row (``LM.insert_slot_caches``); other in-flight slots' KV is
    never touched.
  * **Per-slot positions** — every decode step carries a (B,) position
    vector, so requests with different prompt lengths each attend at
    their own position (``models.attention.decode_step`` masks per row).
  * **On-device sampling** — batched greedy / max-subtracted temperature
    sampling under ``jax.random``; per-(request, token) keys make a
    request's sampled continuation independent of what else is
    co-scheduled in the batch.
  * **Bounded admission queue with counted load-shed** — ``enqueue``
    parks requests up to ``ServeConfig.queue_capacity``; at capacity the
    newest request is rejected with a structured
    :class:`~repro.resilience.RequestResult` (``status=SHED``, counted
    in ``stats``), never an exception.  ``step`` admits into free slots
    and retires sequences on EOS or ``max_new``, so the loop drains a
    request stream without manual slot management.

Request lifecycle hardening (PR 10, ROADMAP §Resilience invariants):
every request the loop ever sees terminates with exactly one
``RequestResult`` in ``results`` carrying a definite status —
DONE / FAILED / TIMEOUT / SHED / CANCELLED.  Per-request deadlines
(decode-step and wall budgets) retire cleanly as TIMEOUT; ``cancel``
retires as CANCELLED; and ``step`` contains faults at three levels:
an admission fault retires only that request FAILED, a batched-decode
fault leaves ALL state untouched (the identical step is retried next
call — decode is a pure function of (caches, toks, pos), so the retry
is bitwise; a consecutive-failure budget retires the active set FAILED
instead of spinning), and a per-slot retirement fault retires only that
slot's request.  The chaos gate (``benchmarks/chaos_bench.py``) holds
the PR 4 slot-isolation contract under fire: surviving requests' token
streams are bitwise equal to a fault-free run.

Per-request outputs are bit-identical to a solo run of the same request
(locked by tests/test_serving.py): decode compute is row-independent and
admission writes are slot-local.

Every ``step`` is a ``serve.step`` span (:class:`~repro.core.spans.Span`)
holding ``serve.admit`` per admission (``serve.prefill``, ``serve.insert``,
``serve.first_token``), ``serve.decode`` (inputs, decode and sampler
dispatch), ``serve.wait`` (the host blocked on the sampled tokens) and
``serve.retire`` (per-slot bookkeeping); each span's seconds add up in
``stats`` under its name plus ``_s`` (``serve.step_s``, ...).  The prefill
program runs under the ``prefill`` named scope, so its device operations
carry it in their op names.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import Span
from repro.models.model_zoo import LM
from repro.resilience import faults
from repro.resilience.fallback import fallback_counters
from repro.resilience.lifecycle import RequestResult, RequestStatus

from .gust_serve import GustServeConfig, decode_step_gust, gustify

__all__ = [
    "ServeConfig",
    "make_serve_fns",
    "make_sampler",
    "ServeLoop",
    "RequestResult",
    "RequestStatus",
]


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch: int
    seq_len: int  # cache capacity
    dtype: str = "bfloat16"
    temperature: float = 0.0  # 0 = greedy
    eos_id: Optional[int] = None  # retire a slot when it samples this token
    queue_capacity: int = 64  # bounded admission queue (full -> counted SHED)
    gust: Optional[GustServeConfig] = None  # None = dense decode
    # default per-request deadlines (enqueue/submit may override per
    # request); None = unbounded.  max_steps_per_request counts decode
    # steps while admitted; max_seconds_per_request is a wall budget.
    max_steps_per_request: Optional[int] = None
    max_seconds_per_request: Optional[float] = None
    # consecutive contained decode-step failures tolerated before the
    # active set is retired FAILED instead of retrying forever
    max_step_failures: int = 8

    @property
    def jnp_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32


def make_serve_fns(lm: LM, cfg: ServeConfig, gust_tree=None):
    """Returns (prefill_fn, decode_fn, init_caches_fn), all pure.

    ``init_caches_fn`` takes an optional batch override (the serve loop
    prefills new requests at batch=1).  ``decode_fn(params, caches,
    tokens, pos, counts, active[, leaves]) -> (logits, caches, counts)``
    takes ``pos`` as a (B,) int32 vector of per-slot positions.  For a
    model with expert layers ``counts`` are the routing counters
    (:func:`_moe_counters`), advanced on the device by this step's routes
    over the rows ``active`` (B,) marks; otherwise both are None.

    With GUST, ``decode_fn`` takes the stacked plan leaves
    (``{key: tree["mats"][key]["leaves"]}``) as its last argument: closed
    over, they would be embedded in the compiled program as constants —
    hundreds of MB at published widths — and every compile would copy
    them.  ``gust_tree`` supplies only the plan meta.
    """
    dtype = cfg.jnp_dtype

    def init_caches(batch: Optional[int] = None):
        return lm.init_caches(batch or cfg.batch, cfg.seq_len, dtype)

    def prefill_fn(params, batch, caches):
        with jax.named_scope("prefill"):
            return lm.prefill(params, batch, caches, dtype=dtype)

    def count(counts, active, routes=None):
        if counts is None:
            return None
        r = routes & active[None, :, None]  # (L, B, held)
        return {
            "routed_pairs": counts["routed_pairs"] + r.sum(dtype=jnp.int32),
            "computed_cols": counts["computed_cols"] + routes.size,
            "expert_tokens": counts["expert_tokens"] + r.sum(1, dtype=jnp.int32),
            "expert_steps": counts["expert_steps"] + r.any(1).astype(jnp.int32),
        }

    if cfg.gust is not None and cfg.gust.enable:
        if gust_tree is None:
            raise ValueError("gust serving requires a gustify()/dryrun tree")

        metas = {k: v["meta"] for k, v in gust_tree["mats"].items()}

        def decode_fn(params, caches, tokens, pos, counts, active, leaves):
            tree = {"mats": {k: {"leaves": leaves[k], "meta": metas[k]}
                             for k in metas}}
            logits, caches, *routes = decode_step_gust(
                lm, params, tree, caches, tokens, pos, cfg=cfg.gust,
                dtype=dtype, routes=counts is not None)
            return logits, caches, count(counts, active, *routes)
    else:

        def decode_fn(params, caches, tokens, pos, counts, active):
            logits, caches, routes = lm.decode(params, caches, tokens, pos,
                                               dtype=dtype)
            return logits, caches, count(counts, active, routes)

    return prefill_fn, decode_fn, init_caches


def _moe_counters(lm: LM):
    """Zeroed device-side routing counters of ``lm``'s expert layers, or
    None without them: ``routed_pairs`` ((token, held expert) pairs
    routed, over active rows), ``computed_cols`` (expert columns the
    decode computes: held experts x batch, every expert layer),
    ``expert_tokens`` and ``expert_steps`` ((L, held): tokens routed to
    each held expert of each expert layer, and the decode steps in which
    it got at least one)."""
    sc = lm.stack
    moe = [bc for bc in sc.pattern if bc.kind == "attn_moe"]
    if not moe:
        return None
    layers = sc.reps * len(moe) + sum(
        sc.pattern[j].kind == "attn_moe" for j in range(sc.n_tail))
    per_expert = jnp.zeros((layers, moe[0].moe.held), jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    return {"routed_pairs": zero, "computed_cols": zero,
            "expert_tokens": per_expert, "expert_steps": per_expert}


def make_sampler(temperature: float) -> Callable:
    """Jitted batched sampler:
    (logits (B, V), base_key, rid_step (B, 2) int32) -> (B,) int32.

    Greedy at ``temperature <= 0``.  The temperature path subtracts the
    per-row max before scaling, so logits of magnitude ~1e3+ stay finite
    (the host-side ``np.exp(logits / T)`` it replaces overflowed to
    inf/NaN); sampling itself is ``jax.random.categorical``'s Gumbel
    trick, which never exponentiates the logits.  Row r's key is
    ``fold_in(fold_in(base_key, rid_step[r, 0]), rid_step[r, 1])`` —
    per-(request id, token index), derived INSIDE the jit so a decode
    step costs one fused call, not 2B host-side fold_in dispatches.
    """

    def sample(logits, base_key, rid_step):
        logits = logits.astype(jnp.float32)
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        z = (logits - logits.max(axis=-1, keepdims=True)) / temperature

        def one(row, rs):
            key = jax.random.fold_in(jax.random.fold_in(base_key, rs[0]), rs[1])
            return jax.random.categorical(key, row)

        return jax.vmap(one)(z, rid_step).astype(jnp.int32)

    return jax.jit(sample)


@dataclasses.dataclass
class _Slot:
    active: bool = False
    request_id: int = -1
    pos: int = 0
    generated: Optional[List[int]] = None
    max_new: int = 0
    steps: int = 0  # decode steps taken while this request held the slot
    deadline_steps: Optional[int] = None
    deadline_s: Optional[float] = None
    admitted_t: float = 0.0


class ServeLoop:
    """Host-side continuous-batching driver over fixed decode slots.

    Requests are (prompt_tokens, max_new_tokens).  ``submit`` admits
    immediately into a free slot (raising when none is free);
    ``enqueue`` parks the request in the bounded admission queue and
    ``step``/``run_to_completion`` admit as slots free up.  Each
    admission prefills ONLY its own slot (batch-1 prefill + slot-local
    cache insert) and each decode step advances every active slot one
    token at that slot's own position.
    """

    def __init__(self, lm: LM, params, cfg: ServeConfig, seed: int = 0):
        self.lm, self.params, self.cfg = lm, params, cfg
        gust_tree = None
        if cfg.gust is not None and cfg.gust.enable:
            gust_tree = gustify(lm, params, cfg.gust)
        self.gust_tree = gust_tree
        # the plan leaves ride as a decode argument, never as constants
        self._decode_extra = () if gust_tree is None else (
            {k: v["leaves"] for k, v in gust_tree["mats"].items()},
        )
        pre, dec, init = make_serve_fns(lm, cfg, gust_tree)
        self._prefill = jax.jit(pre)
        self._decode = jax.jit(dec)
        # donate the full cache: insertion scatters one batch row and the
        # caller rebinds self.caches, so XLA can update in place instead
        # of copying every layer's KV per admission (no-op on CPU)
        self._insert = jax.jit(lm.insert_slot_caches, donate_argnums=0)
        self._sampler = make_sampler(cfg.temperature)
        self.caches = init()
        # immutable batch-1 cache template reused by every admission
        # (prefill is pure, so the template is never mutated)
        self._cache_template_b1 = init(1)
        self.slots = [_Slot() for _ in range(cfg.batch)]
        # routing counters of the expert layers, advanced on the device
        # by every decode step; read_counters() brings them to the host
        self._counts = _moe_counters(lm)
        self._base_key = jax.random.PRNGKey(seed)
        self._next_id = 0
        self.pending: Deque[Tuple] = collections.deque()
        self.completed: Dict[int, List[int]] = {}
        self.results: Dict[int, RequestResult] = {}
        self._decode_failures = 0  # consecutive contained step failures
        self._step_num = 0  # the serve.step span's step number
        self.stats = {
            "decode_steps": 0, "active_slot_steps": 0, "prefills": 0,
            "done": 0, "failed": 0, "timeouts": 0, "shed": 0,
            "cancelled": 0, "decode_retries": 0,
        }

    # -- lifecycle bookkeeping ---------------------------------------------
    def _retire(
        self,
        rid: int,
        status: RequestStatus,
        tokens: Optional[List[int]] = None,
        *,
        reason: str = "",
        steps: int = 0,
    ) -> RequestResult:
        """Record the one terminal result for ``rid`` (first status
        wins) and bump its status counter; DONE additionally lands in
        ``completed`` for back-compat."""
        if rid in self.results:
            return self.results[rid]
        res = RequestResult(rid, status, list(tokens or []), reason, steps)
        self.results[rid] = res
        key = {
            RequestStatus.DONE: "done",
            RequestStatus.FAILED: "failed",
            RequestStatus.TIMEOUT: "timeouts",
            RequestStatus.SHED: "shed",
            RequestStatus.CANCELLED: "cancelled",
        }[status]
        self.stats[key] = self.stats.get(key, 0) + 1
        if status is RequestStatus.DONE:
            self.completed[rid] = res.tokens
        return res

    # -- admission ---------------------------------------------------------
    def enqueue(
        self,
        prompt: np.ndarray,
        max_new: int,
        *,
        deadline_steps: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Park one request in the bounded admission queue.  Returns id.

        At ``queue_capacity`` the request is load-shed (reject-newest
        backpressure): it still gets an id, but terminates immediately
        with a counted ``status=SHED`` result instead of ever being
        admitted — structured rejection, not an exception, so a bursty
        client can't crash the serving path."""
        rid = self._next_id
        self._next_id += 1
        if len(self.pending) >= self.cfg.queue_capacity:
            self._retire(
                rid, RequestStatus.SHED,
                reason=f"admission queue full (capacity {self.cfg.queue_capacity})",
            )
            return rid
        self.pending.append((
            rid, np.asarray(prompt, np.int32), int(max_new),
            deadline_steps, deadline_s,
        ))
        return rid

    def submit(
        self,
        prompt: np.ndarray,
        max_new: int,
        *,
        deadline_steps: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ) -> int:
        """Admit one request into a free slot NOW; runs its prefill.
        Still raises when no slot is free (an immediate-admission caller
        wants the error); an admission *fault* retires the request
        FAILED instead of propagating."""
        free = [i for i, s in enumerate(self.slots) if not s.active]
        if not free:
            raise RuntimeError("no free slots")
        rid = self._next_id
        self._next_id += 1
        try:
            self._admit(
                free[0], rid, np.asarray(prompt, np.int32), int(max_new),
                deadline_steps, deadline_s,
            )
        except Exception as err:  # contained: only this request fails
            self._retire(
                rid, RequestStatus.FAILED, reason=f"admission failed: {err!r}"
            )
        return rid

    def cancel(self, rid: int) -> bool:
        """Explicitly cancel a pending or active request.  Retires it
        with ``status=CANCELLED`` (keeping any tokens generated so far)
        and frees its slot; returns False when ``rid`` is unknown or
        already terminal."""
        if rid in self.results:
            return False
        for entry in self.pending:
            if entry[0] == rid:
                self.pending.remove(entry)
                self._retire(rid, RequestStatus.CANCELLED, reason="cancelled while queued")
                return True
        for i, s in enumerate(self.slots):
            if s.active and s.request_id == rid:
                self._retire(
                    rid, RequestStatus.CANCELLED, s.generated,
                    reason="cancelled while active", steps=s.steps,
                )
                self.slots[i] = _Slot()
                return True
        return False

    def _admit(
        self,
        i: int,
        rid: int,
        prompt: np.ndarray,
        max_new: int,
        deadline_steps: Optional[int] = None,
        deadline_s: Optional[float] = None,
    ):
        """Per-slot prefill: batch-1 prompt pass + slot-local cache insert.

        The prefill jit keys on the exact prompt length, so each distinct
        length in the stream compiles once (exact-length prefill is what
        keeps admission bit-identical to a solo run; length bucketing
        needs masked prefill — see ROADMAP open items)."""
        st = self.stats
        with Span("serve.admit", st, rid=rid, prompt_len=int(prompt.shape[0])):
            faults.trip("serve.admit", tag=str(rid))
            with Span("serve.prefill", st):
                logits, one = self._prefill(
                    self.params,
                    {"tokens": jnp.asarray(prompt)[None]},
                    self._cache_template_b1,
                )
            with Span("serve.insert", st):
                self.caches = self._insert(self.caches, one, i)
            with Span("serve.first_token", st):
                first = int(np.asarray(
                    self._sample_rows(logits[:, -1], [(rid, 0)]))[0])
        st["prefills"] += 1
        slot = _Slot(
            True, rid, int(prompt.shape[0]), [first], max_new,
            deadline_steps=(
                deadline_steps if deadline_steps is not None
                else self.cfg.max_steps_per_request
            ),
            deadline_s=(
                deadline_s if deadline_s is not None
                else self.cfg.max_seconds_per_request
            ),
            admitted_t=time.monotonic(),
        )
        if self._finished(slot, first):
            self._retire(rid, RequestStatus.DONE, slot.generated)
        else:
            self.slots[i] = slot

    def _admit_from_queue(self):
        free = [i for i, s in enumerate(self.slots) if not s.active]
        while free and self.pending:
            rid, prompt, max_new, dl_steps, dl_s = self.pending.popleft()
            try:
                self._admit(free.pop(0), rid, prompt, max_new, dl_steps, dl_s)
            except Exception as err:
                # Contained: a faulted admission retires ONLY this
                # request (the slot was never activated, and a partial
                # batch-1 cache insert into an inactive row cannot
                # influence other rows' decode — attention is per-row).
                self._retire(
                    rid, RequestStatus.FAILED,
                    reason=f"admission failed: {err!r}",
                )
            # _admit may complete the request instantly (EOS/max_new=1),
            # leaving the slot free — recompute instead of assuming
            free = [i for i, s in enumerate(self.slots) if not s.active]

    # -- sampling ----------------------------------------------------------
    def _sample_rows(self, logits_rows, rid_step: List[Tuple[int, int]]):
        """Sample one token per row (dispatched; the result stays on the
        device).  ``rid_step[r] = (request_id, token index)`` seeds row
        r's key, making each request's sampled continuation independent
        of which other requests share the batch."""
        return self._sampler(
            logits_rows, self._base_key, jnp.asarray(rid_step, jnp.int32)
        )

    def _finished(self, slot: _Slot, token: int) -> bool:
        if self.cfg.eos_id is not None and token == self.cfg.eos_id:
            return True
        return len(slot.generated) >= slot.max_new + 1

    # -- decode ------------------------------------------------------------
    def _expire_deadlines(self):
        """Retire every active slot whose decode-step or wall budget has
        expired: clean TIMEOUT with the tokens generated so far."""
        now = time.monotonic()
        for i, s in enumerate(self.slots):
            if not s.active:
                continue
            over_steps = s.deadline_steps is not None and s.steps >= s.deadline_steps
            over_wall = s.deadline_s is not None and now - s.admitted_t >= s.deadline_s
            if over_steps or over_wall:
                why = (
                    f"step budget {s.deadline_steps} exhausted" if over_steps
                    else f"wall budget {s.deadline_s}s exhausted"
                )
                self._retire(
                    s.request_id, RequestStatus.TIMEOUT, s.generated,
                    reason=why, steps=s.steps,
                )
                self.slots[i] = _Slot()

    def step(self) -> int:
        """Admit from the queue, then one decode step for all active
        slots (each at its own position); returns #active after retirement.

        No exception escapes: admission faults retire one request
        (``_admit_from_queue``), and a batched decode/sample fault is
        contained HERE with all state untouched — ``self.caches`` is
        only rebound after both succeed, and decode is a pure function
        of (caches, toks, pos), so the retried step next call is bitwise
        identical to the one that faulted.  After
        ``cfg.max_step_failures`` consecutive contained failures the
        active set retires FAILED (definite status) instead of spinning.
        """
        self._step_num += 1
        with Span("serve.step", self.stats, step=self._step_num):
            return self._step()

    def _step(self) -> int:
        st = self.stats
        self._admit_from_queue()
        self._expire_deadlines()
        active = [i for i, s in enumerate(self.slots) if s.active]
        if not active:
            return 0
        try:
            with Span("serve.decode", st):
                toks = np.zeros((self.cfg.batch, 1), np.int32)
                pos = np.zeros((self.cfg.batch,), np.int32)
                for i in active:
                    toks[i, 0] = self.slots[i].generated[-1]
                    pos[i] = self.slots[i].pos
                active_rows = None
                if self._counts is not None:
                    active_rows = np.zeros((self.cfg.batch,), bool)
                    active_rows[active] = True
                    active_rows = jnp.asarray(active_rows)
                faults.trip("serve.decode")
                logits, new_caches, new_counts = self._decode(
                    self.params, self.caches, jnp.asarray(toks),
                    jnp.asarray(pos), self._counts, active_rows,
                    *self._decode_extra,
                )
                sampled = self._sample_rows(
                    logits[:, 0],
                    [
                        # inactive rows sample garbage that is discarded;
                        # any non-negative key seed works (fold_in is uint32)
                        (s.request_id, len(s.generated)) if s.active
                        else (0, 0)
                        for s in self.slots
                    ],
                )
            with Span("serve.wait", st):
                sampled = np.asarray(sampled)
        except Exception as err:  # sanctioned containment (GUST-L07 site)
            st["decode_retries"] = st.get("decode_retries", 0) + 1
            self._decode_failures += 1
            if self._decode_failures >= self.cfg.max_step_failures:
                for i in active:
                    s = self.slots[i]
                    self._retire(
                        s.request_id, RequestStatus.FAILED, s.generated,
                        reason=(
                            f"decode failed {self._decode_failures} "
                            f"consecutive steps: {err!r}"
                        ),
                        steps=s.steps,
                    )
                    self.slots[i] = _Slot()
                self._decode_failures = 0
            return len([s for s in self.slots if s.active])
        self._decode_failures = 0
        self.caches = new_caches
        self._counts = new_counts
        st["decode_steps"] += 1
        st["active_slot_steps"] += len(active)
        with Span("serve.retire", st):
            for i in active:
                s = self.slots[i]
                try:
                    faults.trip("serve.slot", tag=str(s.request_id))
                    tok = int(sampled[i])
                    s.generated.append(tok)
                    s.pos += 1
                    s.steps += 1
                    if self._finished(s, tok):
                        self._retire(
                            s.request_id, RequestStatus.DONE, s.generated,
                            steps=s.steps,
                        )
                        self.slots[i] = _Slot()
                except Exception as err:  # contained: one slot, one request
                    self._retire(
                        s.request_id, RequestStatus.FAILED, s.generated,
                        reason=f"slot fault: {err!r}", steps=s.steps,
                    )
                    self.slots[i] = _Slot()
        return len([s for s in self.slots if s.active])

    @property
    def occupancy(self) -> float:
        """Mean fraction of decode-slot work spent on live requests."""
        steps = self.stats["decode_steps"]
        if steps == 0:
            return 0.0
        return self.stats["active_slot_steps"] / (steps * self.cfg.batch)

    def read_counters(self) -> Dict:
        """Bring the device-side routing counters into ``stats`` (one
        transfer; the decode steps never wait for them): ``moe.routed_pairs``,
        ``moe.computed_cols``, and per expert layer and held expert
        ``moe.expert_tokens`` and ``moe.expert_steps`` (lists of lists).
        Returns ``stats``; a model without expert layers adds nothing."""
        if self._counts is not None:
            host = jax.device_get(self._counts)
            for k, v in host.items():
                self.stats["moe." + k] = v.tolist()
        return self.stats

    def resilience_stats(self) -> Dict[str, int]:
        """Lifecycle + degradation counters in one snapshot: terminal
        statuses, contained decode retries, and the process-wide
        fallback counters (``repro.resilience.fallback_counters``) —
        what ``launch/serve.py`` and the chaos benchmark report."""
        out = {
            k: self.stats.get(k, 0)
            for k in (
                "done", "failed", "timeouts", "shed", "cancelled",
                "decode_retries",
            )
        }
        out.update({f"fallback_{k}": v for k, v in fallback_counters.items()})
        return out

    def run_to_completion(self, max_steps: int = 10_000):
        """Drain the admission queue and every active slot.  Bounded:
        with per-request deadlines and the consecutive-failure budget,
        every admitted request reaches a terminal status in finitely
        many steps even under persistent faults."""
        for _ in range(max_steps):
            if self.step() == 0 and not self.pending:
                break
        self.read_counters()
