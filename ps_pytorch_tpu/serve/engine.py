"""Continuous-batching serving engine with hot checkpoint rollover.

The third role in the reference deployment — the evaluator that polls a
shared checkpoint directory and runs inference out-of-band — grown into
a serving loop (ROADMAP item 3): a fixed pool of KV-cache slots stepped
by ONE compiled decode program, requests admitted and evicted per step
by the host-side scheduler (serve/scheduler.py), weights hot-swapped
mid-serve when the trainer lands a new checkpoint.

Static shapes everywhere, exactly two compiled programs:

- ``prefill``: one slot's padded prompt ([max_prompt_len] int32; the
  pad tail's K/V is causally downstream of the real prompt only, never
  attended — decode overwrites each position before its first read)
  through the batched causal forward, K/V captured per block and written
  into the slot with ``lax.dynamic_update_slice``;
- ``decode``: every slot advances one token — per-slot positions,
  per-slot length masks (models/decode._attend_cached generalized to a
  length VECTOR), scatter writes at each slot's own position, greedy
  argmax. Finished/empty slots ride along masked (their writes land in
  regions the next occupant overwrites before attending), so admit/
  evict never recompiles.

Weights: the checkpoint's param tree lives on device as ONE padded flat
f32 vector in the flat-state engine's own layout
(parallel/buckets.FlatVector, the same geometry the trainer trains in),
so a checkpoint rollover is a single flat-buffer swap — the compiled
steps see an identical aval and never retrace. Rollover semantics are
PINNED as drain-then-swap: when a newer valid checkpoint appears
(checkpoint.load_latest_valid — the read-only single-read fast path),
admission pauses, in-flight sequences FINISH ON THE WEIGHTS THAT
STARTED THEM, then the buffer swaps and admission resumes. A completion
therefore always carries exactly one ``weights_step``, never a mix.

Rollover is HARDENED against a staged checkpoint going bad during the
drain (ARCHITECTURE §7i): staging records only the step number (the
poll validated the bytes it read, then discards them), and the swap
re-reads the file from disk. A corrupt or unreadable re-read ABORTS
the swap — one ``rollover_abort`` event, admissions resume on the OLD
weights token-exact (the flat buffer was never touched), nothing is
quarantined (the serving process never writes the training
directory), and the next poll retries whatever is then newest. A
``drain_timeout_s`` watchdog bounds how long a drain may pause
admissions before the engine gives up on the staged step entirely.

Request lifecycle contract (§7i): every submitted request terminates
in EXACTLY one of completed | shed | expired, each with a structured
JSONL event through ``event_sink`` — ``request_done``,
``request_shed`` (the AdmissionController refused the arrival), or
``deadline_expired`` (at submit, in queue, or evicted mid-decode).
``outcomes`` is the ledger the chaos drill audits for silent drops.

On a mesh the pool shards over the slot axis (parallel/mesh.
pool_sharding) with weights replicated: the decode step is
embarrassingly slot-parallel — ZERO collectives, a property the
``serve_decode`` pscheck contract (PSC107) pins at the jaxpr level.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import (
    CheckpointCorruptError,
    checkpoint_path,
    listify_raw,
    load_checkpoint_raw,
    load_latest_valid,
)
from ..models.transformer import (
    TransformerConfig,
    _rms_norm,
    select_attention,
    transformer_block,
)
from ..parallel.buckets import (
    FlatVector,
    _np_tree_to_flat,
    plan_buckets,
    tree_layout,
    tree_view,
)
from ..obs import NULL_TRACER
from ..parallel.mesh import pool_sharding, replicated_sharding
from ..utils import get_logger
from .kv import attend_pool, init_kv_pool, write_slot, write_token
from .scheduler import Completion, Expired, Request, SlotScheduler

logger = get_logger()


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Pool geometry + storage policy for one serving engine."""

    slots: int = 8
    max_len: int = 256           # cache positions per slot
    max_prompt_len: int = 64     # static prefill width (pad target)
    kv_int8: bool = False        # int8 K/V payload + block scales
    donate: bool = True          # donate the pool through both steps


def make_prefill_step(cfg: TransformerConfig, serve: ServeConfig):
    """(params, pool, prompt [max_prompt_len], slot) -> pool.

    The same block math as models/decode.prefill (transformer_block +
    the config's within-chip attention), targeted at one pool slot."""

    def prefill(params_any, pool, prompt, slot):
        params = tree_view(params_any)
        cd = cfg.effective_compute_dtype
        t = prompt.shape[0]
        pos = jnp.arange(t)
        x = (params["embed"][prompt] + params["pos_embed"][pos]).astype(cd)
        x = x[None]  # [1, T, D]
        base_attend = select_attention(cfg, None)

        for i, blk in enumerate(params["blocks"]):

            def attend(q, k, v, _i=i):
                nonlocal pool
                pool = write_slot(pool, _i, slot, k[0], v[0])
                return base_attend(q, k, v)

            x = transformer_block(cfg, x, blk, attend)
        return pool

    return prefill


def make_decode_step(cfg: TransformerConfig, serve: ServeConfig):
    """(params, pool, tok [S], pos [S], active [S])
    -> (pool, next [S], next_pos [S]).

    One greedy token for every slot at once. Inactive slots hold their
    token and position (the argmax is masked away) and their cache write
    is benign: the position they scribble is re-written by the slot's
    next occupant before it is ever attended. next/next_pos are returned
    so steady-state ticks can thread them straight back in as the next
    step's device inputs — zero host->device transfers between
    admissions/evictions (see ServingEngine.tick)."""

    def step(params_any, pool, tok, pos, active):
        params = tree_view(params_any)
        cd = cfg.effective_compute_dtype
        x = (params["embed"][tok] + params["pos_embed"][pos]).astype(cd)
        x = x[:, None]  # [S, 1, D]
        scale = 1.0 / (cfg.head_dim ** 0.5)
        lengths = pos + 1

        for i, blk in enumerate(params["blocks"]):

            def attend(q, k, v, _i=i):
                nonlocal pool
                pool = write_token(pool, _i, pos, k[:, 0], v[:, 0])
                return attend_pool(pool, _i, q, lengths, scale)

            x = transformer_block(cfg, x, blk, attend)

        xf = _rms_norm(x[:, 0].astype(cd), params["out_norm"].astype(cd))
        logits = (xf @ params["embed"].T.astype(cd)).astype(jnp.float32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tok)
        return pool, nxt, pos + active.astype(jnp.int32)

    return step


def _flat_params(layout, plan, tree) -> np.ndarray:
    """Host-side pack of a param tree into the engine's flat geometry."""
    return _np_tree_to_flat(layout, plan, tree)


class ServingEngine:
    """One model, one slot pool, one request loop.

    Greedy decode only (the serving contract is determinism: the same
    request set replays to the same tokens regardless of batching —
    pinned by tests/test_serve.py against per-sequence models/decode)."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Dict,
        serve: ServeConfig,
        mesh=None,
        model_dir: Optional[str] = None,
        step: Optional[int] = None,
        clock=None,
        tracer=None,
        admission=None,
        faults=None,
        event_sink=None,
        drain_timeout_s: Optional[float] = None,
        sleep=None,
    ):
        from ..models.lm import require_dense

        require_dense(cfg, "the serving engine (no latent KV cache and no recurrent-state cache yet)")
        if not cfg.causal:
            raise ValueError("serving decode is autoregressive: cfg.causal")
        if serve.max_len > cfg.max_seq_len:
            raise ValueError(
                f"serve.max_len {serve.max_len} exceeds the model's "
                f"positional range {cfg.max_seq_len}"
            )
        if mesh is not None and serve.slots % mesh.devices.size:
            raise ValueError(
                f"slots ({serve.slots}) must divide over the mesh "
                f"({mesh.devices.size} devices) for slot sharding"
            )
        self.cfg = cfg
        self.serve = serve
        self.mesh = mesh
        self.model_dir = model_dir
        self.step = step
        # the latency clock: read at admission and again after each
        # token fetch. The open-loop driver (serve/traffic.py) rebases it
        # so arrival times and emission times share one timeline; tests
        # inject a virtual clock for determinism.
        self.clock = clock or time.perf_counter
        # span tracer (obs/trace.py): serve-tick phases + per-request
        # lifecycle spans. NULL_TRACER (the default) is inert — tick()
        # stays at exactly one host sync either way (PSL004 pins it).
        # Spans run on the tracer's REAL clock, independent of the
        # latency clock above (which tests inject/virtualize).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # SLO-aware admission (serve/admission.AdmissionController): when
        # set, every submit is offered to the controller first; sheds are
        # evented refusals, never silent drops
        self.admission = admission
        # serve-side FaultPlan (resilience/faults.py): slow_decode ticks
        # and rollover_corrupt staging hooks
        self.faults = faults
        # structured lifecycle events (request_done / request_shed /
        # deadline_expired / rollover_abort) — obs/schema.py kinds
        self._event_sink = event_sink
        # drain watchdog: how long a staged rollover may pause admissions
        # before the engine gives up on the staged step (None = forever)
        self.drain_timeout_s = drain_timeout_s
        # injectable stall primitive for fault hooks: virtual-clock tests
        # advance their clock here instead of real-sleeping
        self._sleep = sleep if sleep is not None else time.sleep
        self.scheduler = SlotScheduler(
            serve.slots, serve.max_len, serve.max_prompt_len
        )

        # weights: ONE padded flat f32 vector in the flat-state layout
        # (single bucket — the rollover swap is one buffer either way)
        self._layout = tree_layout(params)
        self._plan = plan_buckets(self._layout.total, 0, align=1)
        flat = _flat_params(self._layout, self._plan, params)
        self._params = FlatVector(
            flat=self._place(flat), layout=self._layout, plan=self._plan
        )

        pool = init_kv_pool(cfg, serve.slots, serve.max_len, int8=serve.kv_int8)
        if mesh is not None:
            sh = pool_sharding(mesh, dim=1)
            pool = {k: jax.device_put(v, sh) for k, v in pool.items()}
        self._pool = pool

        donate = (1,) if serve.donate else ()
        self._prefill = jax.jit(
            make_prefill_step(cfg, serve), donate_argnums=donate
        )
        # the [slots] token/position outputs are pinned to the placement
        # _place gives the host-built triple: a tick that threads them
        # straight back in then hits the SAME compiled program as a tick
        # that rebuilt the triple from host arrays. Left to the compiler,
        # on a mesh the two differ in sharding and the second variant
        # compiles mid-traffic, after warmup
        small = replicated_sharding(mesh) if mesh is not None else None
        self._decode = jax.jit(
            make_decode_step(cfg, serve), donate_argnums=donate,
            out_shardings=(None, small, small),
        )

        s = serve.slots
        self._tok = np.zeros((s,), np.int32)
        self._pos = np.zeros((s,), np.int32)
        self._active = np.zeros((s,), bool)
        # device-side (tok, pos, active) triple: rebuilt from the host
        # arrays only on ticks AFTER an admission/eviction (dirty);
        # otherwise the previous step's own outputs thread straight back
        # in — steady-state ticks pay zero host->device transfers
        self._dev: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None
        self._dirty = True
        # a staged rollover is the STEP NUMBER only: the swap re-reads
        # the file from disk so damage landing between stage and swap is
        # discovered (and aborted) instead of served
        self._pending: Optional[int] = None
        self.rollovers: List[Dict[str, Any]] = []
        self.rollover_aborts: List[Dict[str, Any]] = []
        # the lifecycle ledger: rid -> "completed" | "shed" | "expired".
        # Every submit lands exactly one entry; the chaos drill audits it
        # against the event stream for silent drops. The per-request
        # records are BOUNDED (a long-lived server must not grow its
        # audit without limit — same stance as the tracer ring); the
        # totals live in outcome_counts and never saturate.
        self._ledger_cap = 65536
        self.outcomes: Dict[int, str] = {}
        self.outcome_counts: Dict[str, int] = {
            "completed": 0, "shed": 0, "expired": 0,
        }
        self.shed: Deque[Dict[str, Any]] = deque(maxlen=self._ledger_cap)
        self.expired: Deque[Expired] = deque(maxlen=self._ledger_cap)
        # a step the drain watchdog gave up on: never re-staged (only a
        # strictly newer checkpoint supersedes it)
        self._abandoned_step: Optional[int] = None
        self._tick_no = 0
        # per-slot admission instant on the TRACER clock (request
        # lifecycle spans) and the open drain's start, if any
        self._admit_tr_t: Dict[int, float] = {}
        self._drain_tr_t0: Optional[float] = None
        # the drain's start on the LATENCY clock (tests virtualize it) —
        # the watchdog's timebase, distinct from the tracer clock above
        self._drain_clk_t0: Optional[float] = None

    # ------------------------------------------------------- construction
    @classmethod
    def from_checkpoint(
        cls,
        model_dir: str,
        serve: ServeConfig,
        step: Optional[int] = None,
        mesh=None,
        compute_dtype=None,
        tracer=None,
        **engine_kw,
    ) -> "ServingEngine":
        """Load a cli/train_lm checkpoint (dense LMs; the evaluator's
        scheme-agnostic raw layout) into a serving engine.
        ``engine_kw`` passes through to the constructor (admission,
        faults, event_sink, drain_timeout_s, clock, sleep)."""
        if step is None:
            found = load_latest_valid(model_dir)
            if found is None:
                raise FileNotFoundError(f"no valid checkpoints in {model_dir}")
            step, raw = found
        else:
            raw = load_checkpoint_raw(model_dir, step)
        cfg, params = checkpoint_model(raw, compute_dtype)
        return cls(cfg, params, serve, mesh=mesh, model_dir=model_dir,
                   step=step, tracer=tracer, **engine_kw)

    def _place(self, host: np.ndarray) -> jax.Array:
        """Host array -> device, replicated over the mesh when there is
        one (the flat weights and the per-tick [slots] vectors)."""
        if self.mesh is not None:
            return jax.device_put(host, replicated_sharding(self.mesh))
        return jnp.asarray(host)

    # ---------------------------------------------------------- rollover
    def poll_rollover(self) -> Optional[int]:
        """Stage the newest valid checkpoint newer than the serving step
        (single-read validate). Returns the staged step, or None. Only
        the STEP is staged — the swap re-reads the file after the drain,
        so corruption landing in between is discovered, not served. The
        swap itself waits for the drain — see tick()."""
        if self.model_dir is None:
            return None
        # while a rollover is already staged, only a STRICTLY newer step
        # re-stages — repeated polls during a drain stay one cheap
        # listdir; a step the drain watchdog abandoned is never retried
        after = max(
            x for x in (self._pending, self._abandoned_step, self.step)
            if x is not None
        )
        found = load_latest_valid(self.model_dir, after_step=after)
        if found is None:
            return None
        new_step, raw = found
        cfg, params = checkpoint_model(raw, self.cfg.compute_dtype)
        layout = tree_layout(params)
        if layout.shapes != self._layout.shapes:
            raise ValueError(
                f"checkpoint step {new_step} has a different param "
                f"geometry than the serving model — rollover would "
                f"require a recompile, refusing"
            )
        if self._drain_tr_t0 is None:
            self._drain_tr_t0 = self.tracer.now()
        if self._drain_clk_t0 is None:
            self._drain_clk_t0 = self.clock()
        self._pending = new_step
        if self.faults is not None:
            # chaos hook: damage the staged file AFTER validation — the
            # swap-time re-read must catch it (rollover_abort)
            self.faults.maybe_corrupt_staged(
                checkpoint_path(self.model_dir, new_step), new_step
            )
        logger.info(
            "rollover staged: step %s -> %d (draining %d in-flight)",
            self.step, new_step, self.scheduler.n_inflight,
        )
        return new_step

    def _close_drain_span(self, to_step: int, outcome: str) -> None:
        if self._drain_tr_t0 is not None:
            # the drain interval spans ticks: staged in one poll, ended
            # (swap or abort) ticks later — record it as one explicit
            # span so the timeline shows WHY admission paused
            self.tracer.add(
                "rollover_drain", self._drain_tr_t0,
                self.tracer.now() - self._drain_tr_t0, cat="serve",
                from_step=self.step, to_step=to_step, outcome=outcome,
            )
            self._drain_tr_t0 = None
        self._drain_clk_t0 = None

    def _try_swap(self, now_s: float) -> None:
        """Drain complete: re-read the staged checkpoint and swap the
        flat buffer — or abort onto the old weights if the bytes on disk
        went bad since staging."""
        new_step = self._pending
        try:
            # read_attempts=1: an unreadable staged file is an abort
            # verdict, not something to retry-backoff INSIDE the request
            # loop — the next poll is the retry
            raw = load_checkpoint_raw(self.model_dir, new_step,
                                      read_attempts=1)
            _, params = checkpoint_model(raw, self.cfg.compute_dtype)
            if tree_layout(params).shapes != self._layout.shapes:
                raise ValueError(
                    f"staged checkpoint step {new_step} changed param "
                    f"geometry between stage and swap"
                )
            flat = _flat_params(self._layout, self._plan, params)
        except (CheckpointCorruptError, OSError, ValueError) as e:
            # the staged bytes are gone/bad: abort the swap, keep serving
            # the OLD weights (the flat buffer was never touched — token-
            # exact by construction), retry whatever the next poll finds.
            # Nothing is quarantined: the serving process never writes
            # the training directory.
            self._abort_rollover(now_s, reason="corrupt_staged",
                                 error=str(e))
            return
        self._pending = None
        self._close_drain_span(new_step, outcome="swap")
        with self.tracer.span(
            "rollover_swap", cat="serve",
            from_step=self.step, to_step=new_step,
        ):
            self._params = FlatVector(
                flat=self._place(flat),
                layout=self._layout,
                plan=self._plan,
            )
        self.rollovers.append(
            {"from_step": self.step, "to_step": new_step, "at_s": now_s}
        )
        logger.info("rollover complete: now serving step %d", new_step)
        self.step = new_step

    def _abort_rollover(self, now_s: float, reason: str,
                        error: str = "") -> None:
        staged = self._pending
        self._pending = None
        self._close_drain_span(staged, outcome="abort")
        if reason == "drain_timeout":
            # the watchdog gave up on this step: only a strictly newer
            # checkpoint may stage again (a corrupt abort retries — the
            # next poll re-validates the directory from scratch)
            self._abandoned_step = staged
        rec = {
            "kind": "rollover_abort",
            "from_step": self.step,
            "staged_step": staged,
            "reason": reason,
            "error": error,
            "at_s": round(now_s, 6),
        }
        self.rollover_aborts.append(dict(rec))
        self._emit(rec)
        self.tracer.instant(
            "rollover_abort", cat="serve", from_step=self.step,
            staged_step=staged, reason=reason,
        )
        logger.warning(
            "rollover abort (%s): staying on step %s, staged step %s "
            "dropped%s",
            reason, self.step, staged, f" ({error})" if error else "",
        )

    @property
    def draining(self) -> bool:
        return self._pending is not None

    # ------------------------------------------------------------ intake
    def _emit(self, record: Dict[str, Any]) -> None:
        if self._event_sink is not None:
            self._event_sink(record)

    def _record_outcome(self, rid: int, outcome: str) -> None:
        if rid >= 0:  # warmup probes (negative rids) are not traffic
            self.outcome_counts[outcome] += 1
        self.outcomes[rid] = outcome
        while len(self.outcomes) > self._ledger_cap:
            self.outcomes.pop(next(iter(self.outcomes)))

    def _record_expired(self, exp: Expired) -> None:
        self._record_outcome(exp.rid, "expired")
        self.expired.append(exp)
        self._emit({
            "kind": "deadline_expired",
            "rid": exp.rid,
            "where": exp.where,
            "deadline_s": round(exp.deadline_s, 6),
            "expired_s": round(exp.expired_s, 6),
            "tokens_done": len(exp.tokens),
        })

    def submit(self, request: Request) -> None:
        """Front door: a request terminates right here when its deadline
        already passed (expired) or the admission controller refuses it
        (shed) — both evented, neither ever queued. Everything else goes
        to the scheduler's FIFO."""
        now_s = self.clock()
        if request.deadline_s is not None and request.deadline_s <= now_s:
            self._record_expired(Expired(
                rid=request.rid, where="submit",
                deadline_s=float(request.deadline_s), expired_s=now_s,
            ))
            return
        if self.admission is not None:
            shed, projected = self.admission.offered(
                now_s, self.scheduler.n_queued
            )
            if shed:
                rec = {
                    "kind": "request_shed",
                    "rid": request.rid,
                    "projected_wait_s": round(projected, 6),
                    "queue_depth": self.scheduler.n_queued,
                    "slo_budget_s": self.admission.slo_budget_s,
                    "at_s": round(now_s, 6),
                }
                self._record_outcome(request.rid, "shed")
                self.shed.append(dict(rec))
                self._emit(rec)
                return
        self.scheduler.submit(request)

    # -------------------------------------------------------------- loop
    def _expire_deadlines(self, now_s: float) -> None:
        """Terminate queued and in-flight requests whose deadline passed:
        queued ones never admit; in-flight ones are evicted mid-decode
        (their slot is freed and masked out — the next occupant stays
        token-exact, same argument as a normal evict)."""
        for req in self.scheduler.expire_queued(now_s):
            self._record_expired(Expired(
                rid=req.rid, where="queue",
                deadline_s=float(req.deadline_s), expired_s=now_s,
            ))
        for slot in list(self.scheduler.active_slots):
            req = self.scheduler.request_in(slot)
            if req.deadline_s is not None and req.deadline_s <= now_s:
                exp = self.scheduler.expire_slot(slot, now_s)
                self._active[slot] = False
                self._dirty = True
                t0 = self._admit_tr_t.pop(slot, None)
                if t0 is not None:
                    self.tracer.add(
                        "request", t0, self.tracer.now() - t0,
                        cat="request", slot=slot, rid=exp.rid,
                        outcome="expired", new_tokens=len(exp.tokens),
                    )
                self._record_expired(exp)

    def tick(self) -> List[Completion]:
        """One scheduler round: expire deadlines, swap-if-drained (or
        abort), admit, one decode step, record/evict. Returns the
        completions that finished this tick."""
        self._tick_no += 1
        tr = self.tracer
        if self.faults is not None:
            # injected per-tick stall (chaos: drives queue growth and
            # with it the admission controller) — host-side, pre-decode
            self.faults.maybe_slow_decode(self._tick_no, sleep=self._sleep)
        now_s = self.clock()
        self._expire_deadlines(now_s)
        if self._pending is not None:
            if self.scheduler.n_inflight == 0:
                self._try_swap(now_s)
            elif (
                self.drain_timeout_s is not None
                and self._drain_clk_t0 is not None
                and now_s - self._drain_clk_t0 > self.drain_timeout_s
            ):
                # drain watchdog: a drain may not pause admissions
                # forever — give up on the staged step, resume service
                self._abort_rollover(now_s, reason="drain_timeout")
        if self.admission is not None:
            self.admission.observe_tick(now_s, self.scheduler.n_queued)
        if self._pending is None:
            for slot, req in self.scheduler.admit(now_s):
                self._admit_slot(slot, req)
                if self.admission is not None:
                    self.admission.record_admit(now_s)
        if self.scheduler.n_inflight == 0:
            return []

        with tr.span("decode_dispatch", cat="serve", tick=self._tick_no):
            if self._dirty or self._dev is None:
                self._dev = (
                    self._place(self._tok), self._place(self._pos),
                    self._place(self._active),
                )
                self._dirty = False
            tok_d, pos_d, act_d = self._dev
            self._pool, nxt, new_pos = self._decode(
                self._params, self._pool, tok_d, pos_d, act_d
            )
            self._dev = (nxt, new_pos, act_d)
        # THE per-tick host sync: the scheduler cannot admit/evict
        # without this step's tokens — one fused [slots] fetch, not a
        # per-request read
        with tr.span("token_fetch", cat="serve", tick=self._tick_no):
            tokens = np.asarray(jax.device_get(nxt))  # psl: sync-ok
        # latency is measured at emission (after the fetch retires), not
        # at tick entry — the fetch IS the serving latency's device half
        emit_s = self.clock()

        done: List[Completion] = []
        with tr.span("evict", cat="serve", tick=self._tick_no):
            for slot in list(self.scheduler.active_slots):
                token = int(tokens[slot])
                self._tok[slot] = token
                self._pos[slot] += 1
                if self.scheduler.record_token(slot, token, emit_s):
                    self._active[slot] = False
                    self._dirty = True  # next tick rebuilds the triple
                    c = self.scheduler.evict(
                        slot, emit_s, weights_step=self.step
                    )
                    self._record_outcome(c.rid, "completed")
                    self._emit({
                        "kind": "request_done",
                        "rid": c.rid,
                        "new_tokens": len(c.tokens),
                        "weights_step": c.weights_step,
                        "met_deadline": c.met_deadline,
                        "ttft_s": round(c.latencies_s[0], 6)
                        if c.latencies_s else None,
                    })
                    t0 = self._admit_tr_t.pop(slot, None)
                    if t0 is not None:
                        # request lifecycle (admission -> finish on the
                        # tracer clock); the queue component — arrival ->
                        # admission, measured on the latency clock —
                        # rides as an attribute
                        tr.add(
                            "request", t0, tr.now() - t0, cat="request",
                            slot=slot,
                            rid=c.rid, queue_s=round(c.queue_s, 6),
                            prefill_s=round(c.prefill_s, 6),
                            decode_s=round(c.decode_s, 6),
                            new_tokens=len(c.tokens),
                            weights_step=c.weights_step,
                        )
                    done.append(c)
        if tr.enabled and self._tick_no % 256 == 0:
            # the serve loop's "log window": bounded-latency flushes off
            # the ring so a long-lived server never loses old spans
            tr.flush()
        return done

    def _admit_slot(self, slot: int, req: Request) -> None:
        with self.tracer.span(
            "admit_prefill", cat="serve", slot=slot, rid=req.rid
        ):
            self._admit_tr_t[slot] = self.tracer.now()
            plen = int(req.prompt.shape[0])
            if plen > 1:
                padded = np.zeros((self.serve.max_prompt_len,), np.int32)
                padded[:plen] = req.prompt
                self._pool = self._prefill(
                    self._params, self._pool, jnp.asarray(padded),
                    np.int32(slot),
                )
            self._tok[slot] = int(req.prompt[plen - 1])
            self._pos[slot] = plen - 1
            self._active[slot] = True
            self._dirty = True  # next tick rebuilds the device triple

    # ------------------------------------------------------- conveniences
    def compiled_decode_text(self) -> str:
        """Optimized-HLO text of the decode step (bench op-count probe).
        Lowered over the live avals — tracing only, nothing executes and
        no pool buffer is donated by a .lower()."""
        s = self.serve.slots
        return self._decode.lower(
            self._params, self._pool,
            jax.ShapeDtypeStruct((s,), jnp.int32),
            jax.ShapeDtypeStruct((s,), jnp.int32),
            jax.ShapeDtypeStruct((s,), jnp.bool_),
        ).compile().as_text()

    def warmup(self) -> None:
        """Compile both steps (one throwaway request through prefill +
        decode) so served latency measures the engine, not XLA. The pool
        slot it dirties is freed and overwritten on first real use.
        Bypasses the front door (admission control and fault ticks must
        target served traffic, not the compile probe): the scheduler is
        fed directly, the warmup's rid -1 outcome is dropped, and tick
        numbering restarts at 0 so ``slow_decode`` plans are warmup-
        invariant."""
        plen = min(2, self.serve.max_prompt_len)
        self.scheduler.submit(Request(
            rid=-1, prompt=np.zeros((plen,), np.int32), max_new_tokens=1
        ))
        faults, sink, adm = self.faults, self._event_sink, self.admission
        self.faults = None
        self._event_sink = None
        self.admission = None  # compile walltime is not drain evidence
        try:
            while not self.scheduler.idle:
                self.tick()
        finally:
            self.faults = faults
            self._event_sink = sink
            self.admission = adm
        self.outcomes.pop(-1, None)
        self._tick_no = 0

    def decode_requests(self, requests: Sequence[Request],
                        poll_every: int = 0) -> List[Completion]:
        """Closed-loop drive: submit everything, tick to idle. With
        ``poll_every`` > 0, poll for a checkpoint rollover every that
        many ticks (tests use this to pin the drain semantics)."""
        for r in requests:
            self.submit(r)
        out: List[Completion] = []
        ticks = 0
        while not self.scheduler.idle or self._pending is not None:
            out.extend(self.tick())
            ticks += 1
            if poll_every and ticks % poll_every == 0:
                self.poll_rollover()
        return sorted(out, key=lambda c: c.rid)


def checkpoint_model(raw: dict, compute_dtype) -> Tuple[TransformerConfig, Dict]:
    """Rebuild (TransformerConfig, params tree) from a train_lm raw
    checkpoint dict. Dense models only — MoE decode needs the roomy-
    capacity expert mixture and is not in the serving engine yet."""
    m = raw["model"]
    if m.get("kind", "dense") != "dense":
        raise ValueError(
            "the serving engine decodes dense LM checkpoints only "
            f"(checkpoint kind: {m.get('kind')!r})"
        )
    cfg = TransformerConfig(
        vocab_size=int(m["vocab_size"]),
        dim=int(m["dim"]),
        depth=int(m["depth"]),
        heads=int(m["heads"]),
        mlp_ratio=int(m["mlp_ratio"]),
        max_seq_len=int(m["max_seq_len"]),
        compute_dtype=compute_dtype,
    )
    params = jax.tree.map(np.asarray, listify_raw(raw["params"]))
    return cfg, params
