"""Continuous-batching serving engine: host scheduler over the slot pool.

The host side of the serving plane: an admission queue in front of the
slotted decode programs (:mod:`dlrover_tpu.serving.decode`).  Each live
request owns one KV-cache *slot*; a single jitted ``decode_step`` advances
every occupied slot one token per call, and a request that finishes frees
its slot for the next queued request **on the very next step** — no
lockstep batch holding stragglers hostage (continuous batching).  Compare
``static_batching=True``, the baseline ``tools/serve_bench.py`` measures
against: admission waits until the whole pool drains, so every batch runs
as long as its longest member.

Three optional planes compose on top of the base loop:

* **Tensor parallelism** (``tp=N``): params and the KV pool shard over
  the mesh's ``tensor`` axis (:mod:`dlrover_tpu.serving.tp`); the
  scheduler is unchanged — shardings live entirely inside the programs.
  :meth:`fold_tp` re-folds a live engine onto a different device count
  (fleet resize) without touching queued or live requests.
* **Disaggregated prefill** (``role=``): a ``"prefill"`` engine turns
  prompts into :class:`PrefilledPage` s — host-resident KV cache rows —
  on its ``outbox``; a ``"decode"`` engine accepts pages via
  :meth:`insert_page` and only ever runs the cheap per-token program, so
  its decode-step latency never absorbs a multi-hundred-token prefill
  bubble.  ``"mixed"`` (the default) is the classic colocated engine.
* **Speculative decoding** (``draft_config``/``draft_params``): a small
  draft model proposes γ greedy tokens per slot in one program and the
  target verifies the whole chunk in one program — ``n+1`` tokens per
  two dispatches instead of one per dispatch, bitwise-lossless for
  greedy requests (``decode.SpecPrograms``).

Integration points:

* **Faultline** — every admission fires the ``serve.admit`` seam under the
  PR-6 retry/deadline policy, so chaos plans cover the serving front door.
* **Telemetry** — a ``serve`` event (QPS, latency p50/p95 with sample
  count, slot occupancy, speculation acceptance) is recorded on a step
  cadence; the master's servicer routes it into
  ``SpeedMonitor.record_serve`` → ``dlrover_serve_*`` gauges → the
  auto-scaler's latency/occupancy replica policy.
* **AOT warm-start** — :meth:`aot_compile` compiles prefill-per-bucket +
  insert + decode (+ draft/verify when speculating) before the first
  request and books the wall time as a compile-goodput event (``cached``
  when the process-wide program memo already holds the executables).
"""

from __future__ import annotations

import dataclasses
import json
import math
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common import faults, telemetry
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.retry import RetryPolicy
from dlrover_tpu.models.transformer import TransformerConfig
from dlrover_tpu.rl.generation import SamplingParams
from dlrover_tpu.runtime import compile_cache
from dlrover_tpu.serving.bucketing import make_buckets, pad_to_bucket, \
    pick_bucket
from dlrover_tpu.serving.decode import get_programs, get_spec_programs
from dlrover_tpu.serving.tp import ServeTPMesh, build_tp_mesh
from dlrover_tpu.serving import hotswap

ROLES = ("mixed", "prefill", "decode")


@dataclasses.dataclass
class Request:
    """One generation request.  ``prompt`` is a 1-D int32 token array;
    ``eos_id < 0`` disables early stop."""

    uid: str
    prompt: np.ndarray
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams
    )
    eos_id: int = -1


@dataclasses.dataclass
class RequestResult:
    """A finished request: generated tokens (prompt excluded) and their
    logprobs under the raw next-token distribution."""

    uid: str
    prompt: np.ndarray
    tokens: np.ndarray
    logprobs: np.ndarray
    submit_t: float
    admitted_t: float
    done_t: float

    @property
    def latency_s(self) -> float:
        return self.done_t - self.submit_t

    @property
    def queue_s(self) -> float:
        return self.admitted_t - self.submit_t


@dataclasses.dataclass
class PrefilledPage:
    """One prefilled request in wire form: the batch-1 KV cache row as a
    HOST numpy pytree (plus the draft model's row when the decode pool
    speculates), the first sampled token, and the bookkeeping a decode
    engine needs to resume the request exactly where prefill left it.
    Host numpy is deliberate — it is what a real fleet would put on the
    wire between a prefill host and a decode host, and ``place_row``
    re-lands it under the receiving pool's sharding."""

    request: Request
    submit_t: float
    admitted_t: float
    true_len: int
    first_token: int
    first_logp: float
    row: Any
    draft_row: Any = None
    nbytes: int = 0


class _SlotState:
    __slots__ = (
        "request", "generated", "logps", "submit_t", "admitted_t", "target"
    )

    def __init__(self, request: Request, submit_t: float,
                 admitted_t: float):
        self.request = request
        self.generated: List[int] = []
        self.logps: List[float] = []
        self.submit_t = submit_t
        self.admitted_t = admitted_t
        self.target = request.sampling.max_new_tokens


def _nearest_rank(sorted_values: Sequence[float], p: float) -> float:
    """The nearest-rank quantile (ceil(p*n)-th order statistic): an
    ACTUAL observed sample, never an off-by-one index into thin air —
    p95 of 3 samples is the max, not the median."""
    n = len(sorted_values)
    if n == 0:
        return 0.0
    return sorted_values[min(n - 1, max(0, math.ceil(p * n) - 1))]


class ServingEngine:
    """Slot-pool scheduler bound to one (config, params) pair."""

    def __init__(
        self,
        config: TransformerConfig,
        params,
        *,
        slots: int = 4,
        buckets: Optional[Sequence[int]] = None,
        max_top_k: int = 64,
        seed: int = 0,
        static_batching: bool = False,
        telemetry_every: int = 32,
        client=None,
        admit_policy: Optional[RetryPolicy] = None,
        tp: int = 0,
        tp_devices: Optional[int] = None,
        role: str = "mixed",
        draft_config: Optional[TransformerConfig] = None,
        draft_params=None,
        spec_tokens: int = 4,
    ):
        if role not in ROLES:
            raise ValueError(f"role must be one of {ROLES}, got {role!r}")
        if buckets is None:
            buckets = make_buckets(max(1, config.max_seq_len // 2))
        # A restarted replica re-traces its programs but reads their
        # executables back from the persistent cache.
        compile_cache.maybe_enable()
        self._base_config = config
        self.role = role
        self.tp: Optional[ServeTPMesh] = (
            build_tp_mesh(tp, tp_devices) if tp and tp > 1 else None
        )
        self.programs = get_programs(
            config, slots, tuple(buckets), max_top_k, tp=self.tp
        )
        self.params = self.programs.place_params(params)
        self.slots = slots
        self.buckets = self.programs.buckets
        self.static_batching = static_batching
        self.telemetry_every = max(1, telemetry_every)
        self.client = client
        self.cache = self.programs.init_cache(self.params)
        # Speculative plane: the draft shares slots/buckets/TP with the
        # target so its pool rows line up slot-for-slot.  A prefill-role
        # engine keeps draft PROGRAMS (to ship draft rows in its pages)
        # but no draft pool and no SpecPrograms — it never decodes.
        self._draft_base_config = draft_config
        self.spec = None
        self.draft_programs = None
        self.draft_params = None
        self.draft_cache = None
        self.spec_tokens = spec_tokens
        if draft_config is not None:
            if draft_params is None:
                raise ValueError("draft_config requires draft_params")
            self.draft_programs = get_programs(
                draft_config, slots, tuple(buckets), max_top_k,
                tp=self.tp,
            )
            self.draft_params = self.draft_programs.place_params(
                draft_params
            )
            if role != "prefill":
                self.spec = get_spec_programs(
                    self.programs, self.draft_programs, spec_tokens
                )
                self.draft_cache = self.draft_programs.init_cache(
                    self.draft_params
                )
        self._rng = jax.random.PRNGKey(seed)
        self._slot_state: List[Optional[_SlotState]] = [None] * slots
        self._tokens = np.zeros((slots,), np.int32)
        self._positions = np.zeros((slots,), np.int32)
        self._temps = np.zeros((slots,), np.float32)
        self._topks = np.zeros((slots,), np.int32)
        self._queue: Deque[Tuple[Request, float]] = deque()
        # Disaggregation mailboxes: a prefill engine fills ``outbox``;
        # a decode-capable engine drains ``_page_queue`` into slots.
        self.outbox: Deque[PrefilledPage] = deque()
        self._page_queue: Deque[PrefilledPage] = deque()
        self._pages_in = 0
        self._pages_out = 0
        self._page_bytes_out = 0
        self.results: Dict[str, RequestResult] = {}
        # The PR-6 front door: injected admission faults (serve.admit) are
        # retried with backoff under a deadline instead of dropping the
        # request on the floor.
        self.admit_policy = admit_policy or RetryPolicy(
            max_attempts=4, base_delay_s=0.05, max_delay_s=0.5,
            deadline_s=5.0, retryable=(faults.FaultInjected,),
            name="serve.admit", quiet=True,
        )
        self._step_i = 0
        self._completed: Deque[Tuple[float, float, int]] = deque(maxlen=512)
        self._occupancy: Deque[float] = deque(maxlen=256)
        # Wall seconds of each step that decoded at least one live slot —
        # the decode-interference signal the disaggregation gate compares
        # (a colocated engine's decode steps absorb prefill bubbles).
        self._step_lat: Deque[float] = deque(maxlen=512)
        self._requests_done = 0
        self._tokens_out = 0
        self._submitted = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # Weight provenance: bumped by every verified hot-swap; the
        # version rides the serve.swap telemetry event so the master can
        # tell which weights each replica is answering with.
        self.weights_version = 0
        self._digest_fn = None
        # Classified HBM accounting: serving params + the paged KV pool
        # (target and draft) register as bound methods, which the
        # registry holds via WeakMethod — a torn-down engine (fleet
        # replica kill, bench teardown) unregisters itself on collection.
        from dlrover_tpu.utils import memory_profile

        memory_profile.registry().register(
            "params", f"serve.{id(self)}.params", self.memory_params
        )
        memory_profile.registry().register(
            "kv_pool", f"serve.{id(self)}.kv", self.memory_kv_pool
        )

    def memory_params(self):
        """Registry provider: device params (target + draft)."""
        out = [self.params]
        if self.draft_params is not None:
            out.append(self.draft_params)
        return out

    def memory_kv_pool(self):
        """Registry provider: the paged KV pool (target + draft)."""
        out = [self.cache]
        if self.draft_cache is not None:
            out.append(self.draft_cache)
        return out

    # -- admission ------------------------------------------------------------

    def submit(self, request: Request) -> str:
        """Queue a request (validated + fault-seam guarded).  Raises
        ``ValueError`` for never-admissible requests and ``RetryError``
        when the admission seam stays down past the policy deadline."""
        if self.role == "decode":
            raise ValueError(
                f"request {request.uid}: a decode-role engine admits "
                "prefilled pages (insert_page), not prompts"
            )
        prompt = np.asarray(request.prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError(f"request {request.uid}: empty prompt")
        n_new = request.sampling.max_new_tokens
        if n_new < 1:
            raise ValueError(
                f"request {request.uid}: max_new_tokens must be >= 1"
            )
        bucket = pick_bucket(prompt.size, self.buckets)
        # Speculating engines reserve γ extra positions: a verify step
        # writes K/V up to γ past the committed position.
        headroom = self.spec_tokens if self.draft_programs is not None \
            else 0
        if bucket + n_new + headroom > self.programs.config.max_seq_len:
            raise ValueError(
                f"request {request.uid}: bucket {bucket} + max_new_tokens "
                f"{n_new}"
                + (f" + spec headroom {headroom}" if headroom else "")
                + f" exceeds max_seq_len {self.programs.config.max_seq_len}"
            )
        if request.sampling.top_k > max(1, self.programs.max_top_k):
            raise ValueError(
                f"request {request.uid}: top_k {request.sampling.top_k} "
                f"exceeds the engine's max_top_k {self.programs.max_top_k}"
            )
        request = dataclasses.replace(request, prompt=prompt)
        submit_t = time.perf_counter()

        def admit():
            faults.fire("serve.admit", uid=request.uid)
            self._queue.append((request, submit_t))

        self.admit_policy.call(admit)
        self._submitted += 1
        return request.uid

    def insert_page(self, page: PrefilledPage) -> None:
        """Accept a prefilled KV page from a prefill replica (the decode
        half of the disaggregated path); it lands in a slot on the next
        :meth:`step`."""
        if self.role == "prefill":
            raise ValueError("a prefill-role engine cannot accept pages")
        self._page_queue.append(page)
        self._pages_in += 1

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slot_state) if s is None]

    def _live_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slot_state) if s is not None]

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _maybe_finish(self, slot: int, last_token: int) -> bool:
        state = self._slot_state[slot]
        if len(state.generated) >= state.target or (
            state.request.eos_id >= 0
            and last_token == state.request.eos_id
        ):
            self._finish(slot)
            return True
        return False

    def _admit_draft_row(self, slot: int, padded: np.ndarray,
                         true_len: int, draft_row=None):
        """Seed the draft pool's slot row: land a streamed row, or run
        the draft's own prefill (greedy — proposals are always argmax)."""
        if draft_row is not None:
            row = self.draft_programs.place_row(draft_row)
        else:
            row, _, _ = self.draft_programs.prefill(
                self.draft_params,
                jnp.asarray(padded[None, :]),
                jnp.int32(true_len),
                self._next_rng(),
                jnp.zeros((1,), jnp.float32),
                jnp.zeros((1,), jnp.int32),
            )
        self.draft_cache = self.draft_programs.insert(
            self.draft_cache, row, jnp.int32(slot)
        )

    def _admit_one(self, slot: int, request: Request, submit_t: float):
        padded, true_len = pad_to_bucket(request.prompt, self.buckets)
        state = _SlotState(
            request, submit_t=submit_t, admitted_t=time.perf_counter()
        )
        s = request.sampling
        row, first, logp = self.programs.prefill(
            self.params,
            jnp.asarray(padded[None, :]),
            jnp.int32(true_len),
            self._next_rng(),
            jnp.full((1,), s.temperature, jnp.float32),
            jnp.full((1,), s.top_k, jnp.int32),
        )
        self.cache = self.programs.insert(
            self.cache, row, jnp.int32(slot)
        )
        first_tok = int(np.asarray(first)[0])
        state.generated.append(first_tok)
        state.logps.append(float(np.asarray(logp)[0]))
        self._slot_state[slot] = state
        self._tokens[slot] = first_tok
        self._positions[slot] = true_len
        self._temps[slot] = s.temperature
        self._topks[slot] = s.top_k
        if self.spec is not None:
            self._admit_draft_row(slot, padded, true_len)
        self._maybe_finish(slot, first_tok)

    def _admit_page(self, slot: int, page: PrefilledPage):
        """Resume a remotely-prefilled request: land its KV row into the
        slot and pick up decoding after the (already sampled) first
        token — no prefill program runs here."""
        request = page.request
        state = _SlotState(
            request, submit_t=page.submit_t, admitted_t=page.admitted_t
        )
        row = self.programs.place_row(page.row)
        self.cache = self.programs.insert(
            self.cache, row, jnp.int32(slot)
        )
        state.generated.append(page.first_token)
        state.logps.append(page.first_logp)
        self._slot_state[slot] = state
        self._tokens[slot] = page.first_token
        self._positions[slot] = page.true_len
        self._temps[slot] = request.sampling.temperature
        self._topks[slot] = request.sampling.top_k
        if self.spec is not None:
            padded, _ = pad_to_bucket(request.prompt, self.buckets)
            self._admit_draft_row(
                slot, padded, page.true_len, draft_row=page.draft_row
            )
        self._maybe_finish(slot, page.first_token)

    def _prefill_page(self, request: Request,
                      submit_t: float) -> PrefilledPage:
        """The prefill half of the disaggregated path: one prompt → one
        host-resident page (KV row pulled off-device — the stream a real
        fleet would put on the wire)."""
        padded, true_len = pad_to_bucket(request.prompt, self.buckets)
        s = request.sampling
        row, first, logp = self.programs.prefill(
            self.params,
            jnp.asarray(padded[None, :]),
            jnp.int32(true_len),
            self._next_rng(),
            jnp.full((1,), s.temperature, jnp.float32),
            jnp.full((1,), s.top_k, jnp.int32),
        )
        host_row = jax.tree.map(np.asarray, row)
        draft_row = None
        if self.draft_programs is not None:
            drow, _, _ = self.draft_programs.prefill(
                self.draft_params,
                jnp.asarray(padded[None, :]),
                jnp.int32(true_len),
                self._next_rng(),
                jnp.zeros((1,), jnp.float32),
                jnp.zeros((1,), jnp.int32),
            )
            draft_row = jax.tree.map(np.asarray, drow)
        nbytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(host_row)
        ) + sum(
            leaf.nbytes for leaf in jax.tree.leaves(draft_row or [])
        )
        self._pages_out += 1
        self._page_bytes_out += nbytes
        return PrefilledPage(
            request=request,
            submit_t=submit_t,
            admitted_t=time.perf_counter(),
            true_len=int(true_len),
            first_token=int(np.asarray(first)[0]),
            first_logp=float(np.asarray(logp)[0]),
            row=host_row,
            draft_row=draft_row,
            nbytes=nbytes,
        )

    def _finish(self, slot: int):
        state = self._slot_state[slot]
        assert state is not None
        done_t = time.perf_counter()
        result = RequestResult(
            uid=state.request.uid,
            prompt=state.request.prompt,
            tokens=np.asarray(state.generated, np.int32),
            logprobs=np.asarray(state.logps, np.float32),
            submit_t=state.submit_t,
            admitted_t=state.admitted_t,
            done_t=done_t,
        )
        self.results[state.request.uid] = result
        self._completed.append(
            (done_t, result.latency_s, len(state.generated))
        )
        self._requests_done += 1
        self._tokens_out += len(state.generated)
        self._slot_state[slot] = None
        self._tokens[slot] = 0
        self._positions[slot] = 0
        self._temps[slot] = 0.0
        self._topks[slot] = 0

    # -- the step loop --------------------------------------------------------

    def step(self) -> int:
        """One scheduler tick.  Mixed/decode roles: admit pages then
        prompts into free slots, advance every live slot (one token
        plain, up to γ+1 speculating).  Prefill role: turn up to
        ``slots`` queued prompts into outbox pages.  Returns the number
        of live slots decoded."""
        self._step_i += 1
        t0 = time.perf_counter()
        if self.role == "prefill":
            lanes = 0
            while self._queue and lanes < self.slots:
                request, submit_t = self._queue.popleft()
                self.outbox.append(self._prefill_page(request, submit_t))
                lanes += 1
            self._occupancy.append(0.0)
            if self._step_i % self.telemetry_every == 0:
                self._emit_telemetry()
            return 0
        can_admit = (
            not self.static_batching or not self._live_slots()
        )
        if can_admit:
            for slot in self._free_slots():
                if self._page_queue:
                    self._admit_page(slot, self._page_queue.popleft())
                elif self._queue:
                    request, submit_t = self._queue.popleft()
                    self._admit_one(slot, request, submit_t)
                else:
                    break
        live = self._live_slots()
        if live:
            if self.spec is not None:
                self._spec_step(live)
            else:
                self.cache, next_tokens, logps = self.programs.decode_step(
                    self.params,
                    self.cache,
                    jnp.asarray(self._tokens),
                    jnp.asarray(self._positions),
                    self._next_rng(),
                    jnp.asarray(self._temps),
                    jnp.asarray(self._topks),
                )
                next_np = np.asarray(next_tokens)
                logp_np = np.asarray(logps)
                for slot in live:
                    state = self._slot_state[slot]
                    tok = int(next_np[slot])
                    state.generated.append(tok)
                    state.logps.append(float(logp_np[slot]))
                    self._tokens[slot] = tok
                    self._positions[slot] += 1
                    self._maybe_finish(slot, tok)
            self._step_lat.append(time.perf_counter() - t0)
        self._occupancy.append(len(live) / self.slots)
        if self._step_i % self.telemetry_every == 0:
            self._emit_telemetry()
        return len(live)

    def _spec_step(self, live: List[int]):
        """One speculative round for every live slot: draft proposes γ,
        target verifies the γ+1 chunk, n+1 tokens commit per slot.  Free
        slots compute (and write) garbage the next insert overwrites —
        the same contract as the plain decode step."""
        gamma = self.spec.spec_tokens
        self.draft_cache, proposals = self.spec.propose(
            self.draft_params,
            self.draft_cache,
            jnp.asarray(self._tokens),
            jnp.asarray(self._positions),
        )
        chunk = np.concatenate(
            [self._tokens[:, None], np.asarray(proposals)], axis=1
        ).astype(np.int32)
        (self.cache, emitted, emit_len, logps,
         accepted) = self.spec.verify(
            self.params,
            self.cache,
            jnp.asarray(chunk),
            jnp.asarray(self._positions),
            self._next_rng(),
            jnp.asarray(self._temps),
            jnp.asarray(self._topks),
        )
        em = np.asarray(emitted)
        lens = np.asarray(emit_len)
        lp = np.asarray(logps)
        acc = np.asarray(accepted)
        for slot in live:
            state = self._slot_state[slot]
            if self._temps[slot] <= 0.0:
                # Acceptance only counts greedy rows: sampled rows
                # force n=0 by construction, not by draft quality.
                self._spec_proposed += gamma
                self._spec_accepted += int(acc[slot])
            n_emit = int(lens[slot])
            last_tok = int(em[slot, 0])
            finished = False
            for j in range(n_emit):
                tok = int(em[slot, j])
                state.generated.append(tok)
                state.logps.append(float(lp[slot, j]))
                last_tok = tok
                if len(state.generated) >= state.target or (
                    state.request.eos_id >= 0
                    and tok == state.request.eos_id
                ):
                    finished = True
                    break
            self._tokens[slot] = last_tok
            self._positions[slot] += n_emit
            if finished:
                self._finish(slot)

    def run(
        self,
        requests: Sequence[Request],
        max_steps: Optional[int] = None,
    ) -> Dict[str, RequestResult]:
        """Submit ``requests`` and step until all complete."""
        for request in requests:
            self.submit(request)
        return self.drain(max_steps=max_steps)

    def drain(
        self, max_steps: Optional[int] = None
    ) -> Dict[str, RequestResult]:
        if max_steps is None:
            pending = len(self._queue) + len(self._live_slots()) \
                + len(self._page_queue)
            max_steps = 64 + 2 * sum(
                s.request.sampling.max_new_tokens
                for s in self._slot_state if s is not None
            ) + 2 * sum(
                r.sampling.max_new_tokens for r, _ in self._queue
            ) + 2 * sum(
                p.request.sampling.max_new_tokens
                for p in self._page_queue
            ) + 4 * pending
        for _ in range(max_steps):
            if not self._queue and not self._live_slots() \
                    and not self._page_queue:
                break
            self.step()
        else:
            raise RuntimeError(
                f"drain did not converge within {max_steps} steps "
                f"(queue={len(self._queue)}, live={self._live_slots()})"
            )
        self._emit_telemetry()
        return self.results

    # -- TP re-fold -----------------------------------------------------------

    def fold_tp(self, physical_tp: int) -> None:
        """Re-fold a live TP engine onto ``physical_tp`` devices (a fleet
        resize): swap in the programs for the new fold and relay params +
        both KV pools under the new shardings.  Queued and live requests
        are untouched — the host scheduler state is fold-invariant, and a
        fold back to a previously-seen width retraces nothing (the
        program memo keys on ``(logical, physical)``)."""
        if self.tp is None:
            raise ValueError(
                "fold_tp requires an engine built with tp > 1"
            )
        if physical_tp == self.tp.physical_tp:
            return
        new_tp = self.tp.fold_to(physical_tp)
        programs = get_programs(
            self._base_config, self.slots, self.buckets,
            self.programs.max_top_k, tp=new_tp,
        )
        self.params = programs.place_params(self.params)
        self.cache = new_tp.place(self.cache, programs._pool_sh)
        if self.draft_programs is not None:
            draft_programs = get_programs(
                self._draft_base_config, self.slots, self.buckets,
                self.programs.max_top_k, tp=new_tp,
            )
            self.draft_params = draft_programs.place_params(
                self.draft_params
            )
            if self.draft_cache is not None:
                self.draft_cache = new_tp.place(
                    self.draft_cache, draft_programs._pool_sh
                )
            self.draft_programs = draft_programs
            if self.spec is not None:
                self.spec = get_spec_programs(
                    programs, draft_programs, self.spec_tokens
                )
        self.programs = programs
        self.tp = new_tp
        logger.info(
            "serve TP re-folded: logical=%d physical=%d",
            new_tp.logical_tp, new_tp.physical_tp,
        )

    def kv_device_bytes(self) -> int:
        """Max per-device bytes of the target KV pool — the capacity
        number the ``--tp-drill`` certifies falls as 1/tp."""
        return self.programs.pool_device_bytes(self.cache)

    # -- stats / telemetry ----------------------------------------------------

    def stats(self) -> Dict[str, float]:
        latencies = sorted(lat for _, lat, _ in self._completed)
        if len(self._completed) >= 2:
            t_first = self._completed[0][0]
            t_last = self._completed[-1][0]
            qps = (
                (len(self._completed) - 1) / (t_last - t_first)
                if t_last > t_first else 0.0
            )
        else:
            qps = 0.0
        occupancy = (
            sum(self._occupancy) / len(self._occupancy)
            if self._occupancy else 0.0
        )
        steps = sorted(self._step_lat)
        spec_rate = (
            self._spec_accepted / self._spec_proposed
            if self._spec_proposed else 0.0
        )
        return {
            "qps": qps,
            "p50_s": _nearest_rank(latencies, 0.50),
            "p95_s": _nearest_rank(latencies, 0.95),
            # Sample count behind the latency quantiles: a p95 over two
            # requests is noise, and the scale policy can say so.
            "p95_n": float(len(latencies)),
            "decode_step_p50_s": _nearest_rank(steps, 0.50),
            "decode_step_p95_s": _nearest_rank(steps, 0.95),
            "decode_step_n": float(len(steps)),
            "occupancy": occupancy,
            "slots": float(self.slots),
            "requests": float(self._requests_done),
            "tokens": float(self._tokens_out),
            "steps": float(self._step_i),
            "spec_accept_rate": spec_rate,
            "spec_proposed": float(self._spec_proposed),
            "spec_accepted": float(self._spec_accepted),
            "pages_in": float(self._pages_in),
            "pages_out": float(self._pages_out),
            "page_bytes_out": float(self._page_bytes_out),
        }

    def _emit_telemetry(self):
        stats = self.stats()
        telemetry.event(
            "serve",
            qps=stats["qps"], p50_s=stats["p50_s"], p95_s=stats["p95_s"],
            p95_n=int(stats["p95_n"]),
            occupancy=stats["occupancy"], slots=int(stats["slots"]),
            requests=int(stats["requests"]), tokens=int(stats["tokens"]),
            spec_accept_rate=stats["spec_accept_rate"],
            spec_proposed=int(stats["spec_proposed"]),
            spec_accepted=int(stats["spec_accepted"]),
            decode_step_p95_s=stats["decode_step_p95_s"],
        )

    # -- live weight hot-swap -------------------------------------------------

    def swap_weights(
        self,
        checkpoint_dir: str,
        *,
        step: Optional[int] = None,
        storage=None,
    ) -> Dict[str, object]:
        """Replace the decode params with a committed checkpoint, live.

        No drain, no recompile: the serving programs take params as
        *arguments*, so a tree with identical leaf shapes/dtypes swaps in
        as an assignment between two decode steps — queued requests keep
        their slots, live slots keep their KV rows, and the trace
        counters stay flat (asserted by the tier-1 swap test).  Under TP
        the landing ``device_put`` targets each leaf's existing sharding,
        so swapped weights come up sharded exactly like their
        predecessors.

        The integrity chain, end to end: the
        :class:`~dlrover_tpu.checkpoint.engine.StorageStepReader` only
        yields bytes whose digest sidecar + per-shard crcs verify; the
        assembled arrays are folded into a host-side reference digest
        (``hotswap.host_digest``, bitwise the ``state_digest`` fold);
        after landing, the on-device swapped tree is digested with the
        PR-9 jitted program and must reproduce the reference.  A mismatch
        — the ``serve.swap`` Faultline seam injects exactly that by
        flipping one landed mantissa bit — rolls back to the prior tree,
        which is retained until the verify passes.  Every outcome books a
        versioned ``serve.swap`` telemetry event.

        Returns a report dict (``ok``, ``rolled_back``, ``version``,
        ``step``, ``digest``, ``seconds``); raises ``ValueError`` when
        the checkpoint cannot map onto the decode params at all (drifted
        shapes/dtypes — that needs new programs, not a swap) and
        ``RuntimeError`` when no verifiable step exists.
        """
        t0 = time.perf_counter()
        from dlrover_tpu.checkpoint.engine import StorageStepReader
        from dlrover_tpu.trainer.state_digest import (
            _digest_tree, format_digest,
        )

        reader = StorageStepReader(
            checkpoint_dir, storage=storage, num_hosts=1
        )
        loaded_step, arrays = reader.load_from_storage(step=step)
        if arrays is None:
            raise RuntimeError(
                f"no verifiable committed step in {checkpoint_dir}"
                + (f" (wanted step {step})" if step is not None else "")
            )
        sources = hotswap.map_checkpoint_to_params(arrays, self.params)
        reference = hotswap.host_digest(sources)
        _, leaves = hotswap.leaf_paths(self.params)
        treedef = jax.tree_util.tree_structure(self.params)
        landed = jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(src, leaf.sharding)
            for src, leaf in zip(sources, leaves)
        ])
        try:
            faults.fire("serve.swap", step=loaded_step)
        except faults.FaultInjected:
            # The scripted corruption: one flipped bit in the landed tree
            # (programs untouched) — the digest compare below must catch
            # it and roll back.
            landed = hotswap.flip_param_bit(landed)
        if self._digest_fn is None:
            self._digest_fn = jax.jit(_digest_tree)
        prior = self.params
        self.params = landed
        device_digest = int(np.asarray(self._digest_fn(self.params)))
        ok = device_digest == reference
        rolled_back = False
        if not ok:
            # The prior tree was retained exactly for this: corrupted
            # weights never answer a request.
            self.params = prior
            rolled_back = True
            logger.error(
                "hot-swap REJECTED: swapped-tree digest %s != checkpoint "
                "reference %s; rolled back to version %d",
                format_digest(device_digest), format_digest(reference),
                self.weights_version,
            )
        else:
            self.weights_version += 1
            logger.info(
                "hot-swap: step %d live as weights version %d (digest %s)",
                loaded_step, self.weights_version,
                format_digest(device_digest),
            )
        seconds = time.perf_counter() - t0
        telemetry.event(
            "serve.swap", duration_s=seconds, ok=ok,
            rolled_back=rolled_back, version=self.weights_version,
            step=loaded_step, digest=format_digest(device_digest),
        )
        if self.client is not None:
            self.client.report_event("serve.swap", json.dumps({
                "ok": ok, "rolled_back": rolled_back,
                "version": self.weights_version, "step": loaded_step,
            }))
        return {
            "ok": ok, "rolled_back": rolled_back,
            "version": self.weights_version, "step": loaded_step,
            "digest": format_digest(device_digest),
            "seconds": seconds,
        }

    # -- AOT warm-start -------------------------------------------------------

    def aot_compile(self) -> float:
        """Compile every serving program ahead of the first request and
        book the wall time as a compile-goodput event (``cached=True``
        when the program memo already held the executables — the warm
        start an elastic serving replica restart should hit)."""
        seconds = self.programs.aot_compile(self.params)
        if self.draft_programs is not None:
            seconds += self.draft_programs.aot_compile(self.draft_params)
        if self.spec is not None:
            seconds += self.spec.aot_compile(
                self.params, self.draft_params
            )
        detail = {
            "seconds": round(seconds, 6),
            "restart": False,
            "cached": seconds == 0.0,
            "phase": "serve_aot",
        }
        logger.info("serve AOT warmup: %s", detail)
        telemetry.event("compile", duration_s=seconds,
                        cached=detail["cached"], phase="serve_aot")
        if self.client is not None:
            self.client.report_event("compile", json.dumps(detail))
        return seconds
