"""Training drivers: the host-side loop around the jitted PS train step.

This is the TPU-native collapse of the reference's three role runtimes
(SURVEY.md sections 1-3). `SyncReplicasMaster_NN.start()` (sync_replicas_
master_nn.py:133-197), `DistributedWorker.train()` (distributed_worker.py:
104-180) and the single-machine `NN_Trainer.train_and_validate` (nn_ops.py:
48-88) all become ONE driver: under SPMD there is no master process, no
worker processes, no step handshake — a single host loop dispatches one
fused XLA program per global step over the whole mesh. `num_workers=1` on
one chip is exactly the reference's single_machine.py baseline.

The driver owns everything the reference's role runtimes owned that is not
the step itself: epoch iteration, per-iteration reference-format log lines
(utils/logging.py), eval cadence, single-writer checkpoints, and resume
(which the reference lacks — sync_replicas_master_nn.py:102 always restarts
at step 1).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Optional

import jax
import numpy as np

from . import checkpoint as ckpt
from .data import (
    BatchIterator,
    Dataset,
    make_preprocessor,
    prefetch_to_device,
    prepare_data,
    shard_for_worker,
)
from .models import build_model, input_shape_for
from .optim import build_optimizer
from .parallel import (
    PSConfig,
    batch_sharding,
    init_ps_state,
    make_mesh,
    make_ps_eval_step,
    make_ps_train_step,
    shard_state,
)
from .obs import (
    NULL_TRACER,
    ProfileWindow,
    Tracer,
    new_run_id,
    run_header,
    setup_line_once,
    setup_tracer,
    validate_event,
)
from .resilience import AdaptiveMaskController, resolve_fault_plan
from .resilience import elastic
from .utils import PhaseTimer, format_eval_line, format_iter_line, get_logger

logger = get_logger()


def append_metrics_line(path: Optional[str], record: dict) -> None:
    """Structured metrics sink (one JSON object per line). The reference
    has only parseable log text (SURVEY.md section 5 'no TensorBoard/CSV');
    this is the machine-readable channel next to it.

    THE write choke point for every event emitter: each record is
    validated/normalized against the observability event registry
    (obs/schema.py — unknown kinds and missing required fields raise,
    declared counter fields are coerced to int) and stamped with a
    ``t_wall`` wall-clock second, so the JSONL stream merges onto the
    span-trace timeline (tools/trace_report.py overlays)."""
    if not path:
        return
    record = validate_event(record)
    record.setdefault("t_wall", round(time.time(), 6))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def _shared_run_id() -> str:
    """One run id for ALL processes of a multihost run.

    ``new_run_id()`` is per-process RNG, so each host would stamp its
    metrics run header and span-trace file with a DIFFERENT id, breaking
    the cross-process correlation tools/trace_report.py merges on
    (PSL007). Process 0's draw is broadcast as bytes so every host
    carries the same id."""
    rid = np.frombuffer(new_run_id().encode("ascii"), dtype=np.uint8)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        rid = multihost_utils.broadcast_one_to_all(rid)
    return np.asarray(rid).tobytes().decode("ascii")


def average_metrics(step_fn, batches) -> dict:
    """Uniform average of per-batch metric dicts (batches are equal-sized:
    BatchIterator drops partial tails). Shared by Trainer.validate and the
    out-of-band Evaluator."""
    sums, count = {}, 0
    for batch in batches:
        # eval is off the hot path; fetching every batch is the point here
        m = jax.device_get(step_fn(batch))  # psl: sync-ok
        for k, v in m.items():
            sums[k] = sums.get(k, 0.0) + float(v)
        count += 1
    return {k: v / max(count, 1) for k, v in sums.items()}


@dataclasses.dataclass
class TrainConfig:
    """Host-loop configuration, mirroring the reference CLI surface
    (/root/reference/src/distributed_nn.py:24-68). Engine-level knobs
    (num_aggregate, compression, placement, BN mode) live in PSConfig."""

    network: str = "LeNet"
    dataset: str = "MNIST"
    batch_size: int = 128  # per-worker batch, reference --batch-size
    test_batch_size: int = 500
    epochs: int = 100
    max_steps: int = 10000
    lr: float = 0.01
    momentum: float = 0.5
    weight_decay: float = 0.0
    optimizer: str = "sgd"  # sgd | adam (reference optim/)
    seed: int = 1
    log_interval: int = 10
    eval_freq: int = 50
    train_dir: str = "output/models/"
    save_checkpoints: bool = True
    compress_checkpoints: bool = False  # native C++ codec (ops/codec.py)
    resume: bool = False
    data_root: Optional[str] = None
    allow_synthetic: bool = True
    shard_mode: str = "reshuffle"  # reference parity; "disjoint" improvement
    dtype: str = "float32"  # compute dtype: float32 | bfloat16 (MXU-native)
    remat: bool = False  # per-block activation rematerialization (ResNets)
    metrics_file: Optional[str] = None  # append one JSON line per logged step
    # span tracing (obs/trace.py, --trace): write this process's host-
    # phase span stream (trace_train_p<i>.jsonl) into this directory.
    # None = the NULL tracer: zero overhead, zero host syncs (pslint
    # PSL004 patrols the instrumented paths). tools/trace_report.py
    # merges per-process files and summarizes p50/p99 per phase.
    trace_dir: Optional[str] = None
    profile_dir: Optional[str] = None  # jax.profiler trace output
    # bounded profiler capture window [profile_start, profile_start +
    # profile_steps): None = auto (one warmup step after the run's first
    # step, so compilation stays out of the capture)
    profile_start: Optional[int] = None
    profile_steps: int = 10
    # straggler watchdog (reference --kill-threshold, distributed_nn.py:52:
    # there it was meant to kill slow workers; under SPMD there is nothing
    # to kill, so the live semantics are detection + structured warning)
    straggler_threshold_s: Optional[float] = None
    # watchdog escalation: this many CONSECUTIVE straggler steps collapse
    # into one structured `straggler_storm` event (per-step warnings are
    # suppressed until the storm breaks — N slow steps is a condition,
    # not N incidents)
    straggler_storm_n: int = 3
    # non-finite guard abort: raise after this many consecutive skipped
    # steps (0 = never abort — count and log only). The guard itself is
    # PSConfig.nonfinite_guard; this is the host-side tripwire.
    max_consecutive_skips: int = 8
    # adaptive partial aggregation window (steps): with PSConfig.
    # num_aggregate_min/max set, the controller re-picks the aggregation
    # count every this-many steps from the straggler watchdog's timings
    # (resilience/elastic.AdaptiveMaskController; needs the watchdog
    # armed — straggler_threshold_s is the slow-step criterion)
    adapt_window: int = 20
    # deterministic fault injection: a JSON FaultPlan ('@path' to read a
    # file), resilience/faults.py; PS_TPU_FAULTS env var when unset here
    fault_plan: Optional[str] = None


class Trainer:
    """Drives PS data-parallel training of one model on one mesh."""

    def __init__(self, tcfg: TrainConfig, pcfg: PSConfig, dataset: Optional[Dataset] = None):
        self.tcfg, self.pcfg = tcfg, pcfg
        if tcfg.straggler_storm_n < 1:
            # 0 would silently swallow BOTH the per-step straggler events
            # (streak < n never true) and the storm event (streak == n
            # never true) — reject it instead of losing observability
            raise ValueError(
                f"straggler_storm_n must be >= 1, got "
                f"{tcfg.straggler_storm_n} (1 = escalate immediately; "
                f"use a large value to effectively disable storms)"
            )
        self._stop_requested = False
        # one run id ties this run's streams together (metrics JSONL run
        # header + the per-process span trace file) — broadcast from
        # process 0 so every host agrees on it. The tracer comes first
        # so that set-up itself is timed (the `build` spans below); its
        # header's geometry is filled in at the end of __init__, before
        # anything can flush.
        self.run_id = _shared_run_id()
        self.tracer = NULL_TRACER
        if tcfg.trace_dir:
            self.tracer = Tracer(
                "train",
                path=os.path.join(
                    tcfg.trace_dir,
                    f"trace_train_p{jax.process_index()}.jsonl",
                ),
                run_id=self.run_id,
                pid=jax.process_index(),
                # host spans double as jax.profiler.TraceAnnotation
                # scopes, so a --profile-dir capture shows the named
                # phases on the profiler timeline too
                annotate=True,
                # its first flush also writes the set-up record of this
                # trainer's life: `build*`, jax's intervals, the first call
                with_setup=True,
            )
        # straggler watchdog event counter (observable --mode action)
        self.straggler_steps = 0
        # storm escalation state (straggler_storm_n consecutive slow steps)
        self.straggler_storms = 0
        self._straggler_streak = 0
        # non-finite guard: skip count already reported to the host (the
        # device-side truth rides the metrics dict, fetched per window)
        self._skipped_seen = 0
        # adaptive partial aggregation: the host half that picks each
        # window's traced count (the train step takes it as an argument);
        # the controller itself rejects a missing watchdog threshold —
        # its policy consumes the watchdog's per-step walltimes
        self._adaptive = None
        if pcfg.adaptive_aggregate:
            self._adaptive = AdaptiveMaskController(
                pcfg,
                tcfg.straggler_threshold_s,
                tcfg.adapt_window,
                event_sink=lambda rec: append_metrics_line(
                    tcfg.metrics_file, rec
                ),
                # multi-host: hosts see different local walltimes but
                # must trace the SAME count into the global psum; the
                # controller applies this min-over-hosts at each window
                # close (boundaries are step-counted, so every host
                # reaches the collective together). One int32 DCN
                # allgather per window — noise next to the per-step
                # stop consensus.
                consensus=(
                    self._count_consensus
                    if jax.process_count() > 1
                    else None
                ),
            )
        self.faults = resolve_fault_plan(tcfg.fault_plan)
        if self.faults is not None:
            logger.warning("fault injection ACTIVE: %s", self.faults)
        # set-up the program owns, by part: data, model (mesh, network,
        # optimizer), state (initialised and sharded: the many one-op
        # programs) and step (the jitted train and eval steps); into the
        # process's set-up record, so the spans exist without --trace
        tr = setup_tracer()
        with tr.span("build"):
            with tr.span("build.data"):
                self.dataset = dataset or prepare_data(
                    tcfg.dataset, root=tcfg.data_root,
                    allow_synthetic=tcfg.allow_synthetic,
                )
            with tr.span("build.model"):
                self._build_model(tcfg, pcfg)
            with tr.span("build.state"):
                n_params = self._build_state(tcfg, pcfg)
            with tr.span("build.step"):
                self._build_step(tcfg, pcfg)
        if self.tracer.enabled:
            self.tracer.header["geometry"] = self._geometry()
        logger.info(
            "model %s (%d params), dataset %s%s, %d workers",
            tcfg.network,
            n_params,
            self.dataset.name,
            " [synthetic]" if self.dataset.synthetic else "",
            pcfg.num_workers,
        )

    def _build_model(self, tcfg: TrainConfig, pcfg: PSConfig) -> None:
        if pcfg.dcn_hosts > 1:
            from .parallel import make_hybrid_mesh

            self.mesh = make_hybrid_mesh(
                num_hosts=pcfg.dcn_hosts,
                per_host=pcfg.num_workers // pcfg.dcn_hosts,
                axis_names=pcfg.axis_name,
            )
        else:
            self.mesh = make_mesh(num_workers=pcfg.num_workers)
        import jax.numpy as jnp

        compute_dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[tcfg.dtype]
        # compute in bf16 on the MXU when asked; params/optimizer state and
        # the loss stay f32 (flax dtype= is the compute dtype only)
        self.model = build_model(
            tcfg.network,
            num_classes=self.dataset.num_classes,
            dtype=compute_dtype,
            bn_axis_name=pcfg.axis_name if pcfg.bn_mode == "synced" else None,
            remat=tcfg.remat,
        )
        self.tx = build_optimizer(
            tcfg.optimizer,
            tcfg.lr,
            momentum=tcfg.momentum,
            weight_decay=tcfg.weight_decay,
            # the state is flat: the whole-vector update variants —
            # same math, no per-leaf tree_map
            flat=True,
        )

    def _build_state(self, tcfg: TrainConfig, pcfg: PSConfig) -> int:
        """Initialise and shard the state; returns the parameter count."""
        shape = input_shape_for(tcfg.network)
        state = init_ps_state(
            self.model, self.tx, pcfg, jax.random.key(tcfg.seed), shape
        )
        self.state = shard_state(state, self.mesh, pcfg)
        # the true count is static metadata (the padded buffer would
        # over-count by the alignment tail, and materializing the tree
        # view just to count would waste a params-sized device
        # allocation)
        return state.params.layout.total

    def _build_step(self, tcfg: TrainConfig, pcfg: PSConfig) -> None:
        pre_train = make_preprocessor(tcfg.dataset, train=True)
        pre_eval = make_preprocessor(tcfg.dataset, train=False)
        self._train_step = make_ps_train_step(
            self.model, self.tx, pcfg, self.mesh, preprocess=pre_train,
            faults=self.faults,
        )
        self._eval_step = make_ps_eval_step(
            self.model, pcfg, self.mesh, preprocess=pre_eval
        )
        self._key = jax.random.key(tcfg.seed + 1)
        self._ckpt = ckpt.AsyncCheckpointer(
            event_sink=lambda rec: append_metrics_line(tcfg.metrics_file, rec),
            faults=self.faults,
        )

    def _geometry(self) -> dict:
        """The run-header geometry block: enough to interpret a stream
        without the CLI line that produced it."""
        return {
            "num_workers": self.pcfg.num_workers,
            "network": self.tcfg.network,
            "dataset": self.tcfg.dataset,
            "opt_placement": self.pcfg.opt_placement,
            "processes": jax.process_count(),
        }

    # ------------------------------------------------------------------ resume
    def try_resume(self) -> Optional[int]:
        """Restore the newest VALID checkpoint from train_dir, if any.

        A corrupt/truncated file (CRC trailer mismatch, torn bytes) is
        quarantined — renamed `*.corrupt`, out of the model_step_N
        namespace — and the next older checkpoint is tried: a damaged
        latest checkpoint costs one eval_freq window of progress, not the
        run. Transient read errors (already retried with backoff inside
        the read) skip the file WITHOUT quarantining it. Structure
        mismatches (e.g. comm_state for a disabled feature) still raise:
        they are configuration errors, not damage.

        Multi-host: the step is chosen ONCE (process 0 walks the list)
        and broadcast, because a file torn on only some replicas of a
        shared dir would otherwise send hosts down different fallbacks —
        and JAX never cross-checks replicated values, so the run would
        continue silently divergent.

        Elastic resume (resilience/elastic.py): when the dir's
        ``elastic.json`` manifest says the checkpoint was written under a
        DIFFERENT mesh geometry (worker count, optimizer placement, or a
        ZeRO-1 bucket/quant carving change), the raw state is reshaped
        into this run's geometry before restore — params and optimizer
        moments bit-exact, per-worker EF residuals and local BN stats
        re-distributed — and a ``resume_reshape`` event lands in the
        metrics JSONL."""
        steps = ckpt.available_steps(self.tcfg.train_dir)
        if jax.process_count() > 1:
            return self._try_resume_multihost(steps)
        if not steps:
            return None
        target = jax.device_get(self.state)
        for step in reversed(steps):
            try:
                restored = self._restore_step(target, step)
            except ckpt.CheckpointCorruptError as e:
                self._quarantine(step, e)
                continue
            except OSError as e:
                logger.warning(
                    "resume: checkpoint step %d unreadable (%s); trying "
                    "older (file left in place)", step, e,
                )
                continue
            self.state = shard_state(restored, self.mesh, self.pcfg)
            self._sync_guard_baseline()
            logger.info(
                "resumed from %s",
                ckpt.checkpoint_path(self.tcfg.train_dir, step),
            )
            return step
        return None

    def _restore_step(self, target, step: int):
        """Load checkpoint `step` into `target`'s structure, routing
        through the elastic reshape when the dir's geometry manifest says
        the file was written on a different mesh. Raises exactly what
        load_checkpoint raises (CheckpointCorruptError/OSError for
        damage, ValueError for config mismatches), so the resume loops'
        fallback handling is unchanged."""
        raw = ckpt.load_checkpoint_raw(self.tcfg.train_dir, step)
        src = elastic.load_geometry(self.tcfg.train_dir, step=step)
        dst = elastic.geometry_of(self.pcfg)
        if src is not None and elastic.needs_reshape(src, dst):
            logger.warning(
                "resume-reshape: checkpoint step %d was written on "
                "%d workers (%s placement); reshaping onto %d workers "
                "(%s placement)",
                step, src.num_workers, src.opt_placement,
                dst.num_workers, dst.opt_placement,
            )
            raw = elastic.reshape_raw_state(raw, src, self.pcfg, target)
            append_metrics_line(
                self.tcfg.metrics_file,
                {
                    "kind": "resume_reshape",
                    "step": step,
                    "from": src.to_json(),
                    "to": dst.to_json(),
                },
            )
            return ckpt.restore_from_raw(target, raw, step)
        try:
            restored = ckpt.restore_from_raw(target, raw, step)
        except ValueError as e:
            if src is None:
                # structure mismatch with no manifest to reshape by: a
                # pre-elastic checkpoint resumed on a changed mesh
                raise ValueError(
                    f"cannot restore checkpoint step {step}: {e}. No "
                    f"elastic.json manifest (or per-step entry) in "
                    f"{self.tcfg.train_dir!r} — if the mesh geometry "
                    f"changed since this checkpoint was written, resume "
                    f"once on the ORIGINAL geometry (which now writes "
                    f"the manifest) and then reshape."
                ) from e
            raise
        if src is None and self.pcfg.opt_placement == "sharded":
            # the one geometry change shapes canNOT catch: a ZeRO-1
            # bucket/quant re-carving keeps the stacked [n, shard]
            # moment shapes and only permutes the worker->region
            # mapping. Without a manifest we cannot verify it, so say
            # so instead of staying silent.
            logger.warning(
                "resumed checkpoint step %d without an elastic manifest "
                "entry: cannot verify its ZeRO-1 carving matches "
                "--bucket-bytes/--quant-block-size — if those changed "
                "since it was written, optimizer moments are silently "
                "mis-mapped; resume on the original settings if unsure",
                step,
            )
        return restored

    def _sync_guard_baseline(self) -> None:
        """A restored GuardState carries the LIFETIME skip count — seed
        the host's already-reported watermark from it, or the first
        metrics fetch of a healthy resumed run re-reports the old skips
        as a fresh grad_skip event."""
        if self.state.guard_state is not None:
            self._skipped_seen = int(
                jax.device_get(self.state.guard_state.skipped)
            )

    def _quarantine(self, step: int, err: BaseException) -> None:
        logger.warning(
            "resume: checkpoint step %d is corrupt (%s); quarantining "
            "and falling back", step, err,
        )
        quarantined = ckpt.quarantine_checkpoint(self.tcfg.train_dir, step)
        append_metrics_line(
            self.tcfg.metrics_file,
            {"kind": "ckpt_quarantined", "step": step,
             "path": quarantined, "error": str(err)},
        )

    def _try_resume_multihost(self, steps) -> Optional[int]:
        """Mesh-consensus resume: process 0 picks the newest step that
        passes an integrity check (quarantining corrupt ones — one
        renamer, so no os.replace race), the choice is broadcast, and
        every process restores that SAME step. A host whose own replica
        then fails the agreed load raises loudly — a crashed process
        beats silently divergent replicated state."""
        from jax.experimental import multihost_utils

        chosen = -1
        if jax.process_index() == 0:
            for step in reversed(steps):
                try:
                    ckpt.verify_checkpoint(self.tcfg.train_dir, step)
                    chosen = step
                    break
                except ckpt.CheckpointCorruptError as e:
                    self._quarantine(step, e)
                except OSError as e:
                    logger.warning(
                        "resume: checkpoint step %d unreadable (%s); "
                        "trying older (file left in place)", step, e,
                    )
        chosen = int(multihost_utils.broadcast_one_to_all(np.int32(chosen)))
        if chosen < 0:
            return None
        target = jax.device_get(self.state)
        restored = self._restore_step(target, chosen)
        self.state = shard_state(restored, self.mesh, self.pcfg)
        self._sync_guard_baseline()
        logger.info(
            "resumed from %s (mesh-consensus choice)",
            ckpt.checkpoint_path(self.tcfg.train_dir, chosen),
        )
        return chosen

    # ----------------------------------------------------------- guard (host)
    def _guard_check(self, m: dict, step_no: int, abort: bool = True) -> None:
        """Host half of the non-finite gradient guard. Runs wherever the
        metrics dict is already on host (log window / backpressure sync —
        the guard itself never forces a transfer): emits one structured
        `grad_skip` event per window that saw new skips, and aborts once
        the device-side skip streak crosses max_consecutive_skips — at
        that point the optimizer is the identity and "training" is a very
        expensive sleep; the operator should resume from the last good
        checkpoint with a smaller lr / different data shard."""
        if "skipped_steps" not in m:
            return
        skipped, streak = int(m["skipped_steps"]), int(m["skip_streak"])
        if skipped > self._skipped_seen:
            logger.warning(
                "non-finite gradients: %d step(s) skipped so far "
                "(current streak %d) — params were NOT updated on those",
                skipped, streak,
            )
            rec = {
                "kind": "grad_skip",
                "step": step_no,
                "skipped_steps": skipped,
                "skip_streak": streak,
            }
            if "loss_scale" in m:
                rec["loss_scale"] = float(m["loss_scale"])
            append_metrics_line(self.tcfg.metrics_file, rec)
            self._skipped_seen = skipped
        if not abort:
            return
        k = self.tcfg.max_consecutive_skips
        if k > 0 and streak >= k:
            raise RuntimeError(
                f"aborting at step {step_no}: {streak} consecutive steps "
                f"had non-finite gradients (threshold {k}) — every one "
                f"was skipped, so params are stuck at step "
                f"{step_no - streak}. Training has diverged or the input "
                f"shard is corrupt; resume from the last valid checkpoint "
                f"with --resume after fixing the cause."
            )

    def _maybe_end_storm(self, last_slow_step: int) -> None:
        """Close an open straggler storm with ONE structured event
        carrying the storm's true length. The storm-start event is
        emitted at streak == storm_n (so its `consecutive` is always
        exactly storm_n) and per-step records are suppressed while it
        lasts — without a closing record the storm's extent would be
        unrecoverable from the JSONL."""
        t = self.tcfg
        if self._straggler_streak < t.straggler_storm_n:
            return
        logger.warning(
            "straggler storm cleared: %d consecutive slow steps "
            "(steps %d-%d)",
            self._straggler_streak,
            last_slow_step - self._straggler_streak + 1,
            last_slow_step,
        )
        append_metrics_line(
            t.metrics_file,
            {
                "kind": "straggler_storm_end",
                "step": last_slow_step,
                "start_step": last_slow_step - self._straggler_streak + 1,
                "consecutive": self._straggler_streak,
            },
        )

    @staticmethod
    def _count_consensus(proposed: int) -> int:
        """Mesh-wide agreement on the next window's aggregation count:
        min over hosts of the local proposals — a straggler seen by ANY
        host shrinks the mask for everyone; recovery needs every host
        clean. Collective (host allgather): every host reaches the same
        window boundary on the same step, like _stop_consensus."""
        from jax.experimental import multihost_utils

        return int(np.min(multihost_utils.process_allgather(
            np.asarray([proposed], np.int32)
        )))

    def _record_geometry(self, step_no: int) -> None:
        """Record this run's mesh geometry in the elastic.json manifest
        (single writer), keyed by checkpoint step — an elastically
        resumed dir holds mixed-geometry checkpoints, and a fallback
        resume must reshape each file by the geometry that WROTE it."""
        if jax.process_index() == 0:
            elastic.save_geometry(
                self.tcfg.train_dir, elastic.geometry_of(self.pcfg),
                step=step_no,
            )

    # ------------------------------------------------------------ graceful stop
    def request_stop(self) -> None:
        """Ask the training loop to stop after the current step (and write
        a final checkpoint). Safe from signal handlers/threads."""
        self._stop_requested = True

    @property
    def stop_requested(self) -> bool:
        return self._stop_requested

    def _stop_consensus(self) -> bool:
        """Mesh-wide agreement on the stop flag, checked once per step.

        Single-process: just the local flag. Multi-host: OR of every
        process's flag via a host allgather — a collective, so EVERY
        process must reach this same point each step (they do: the train
        loops run the same schedule). A SIGTERM delivered to any one host
        therefore stops all of them at the same step boundary, after
        which the (also collective) checkpoint save is safe. Cost is one
        scalar DCN allgather per step — noise next to the gradient psum.
        Promotes a remotely-raised stop into the local flag so the
        preemption exit path (skip validation, log) behaves identically
        on every host."""
        if jax.process_count() == 1:
            return self._stop_requested
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([1 if self._stop_requested else 0], np.int32)
        )
        if bool(np.any(flags)):
            self._stop_requested = True
            return True
        return False

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT -> graceful stop: finish the step, checkpoint,
        return — so a preempted run resumes exactly with --resume. (The
        reference's only recovery is killall + restart from step 1.)
        Call from the main thread; second signal falls back to the
        default handler (hard kill).

        Multi-host safe: the handler only sets the LOCAL flag; the train
        loop reaches mesh consensus on it every step (_stop_consensus), so
        a signal on one host stops every host at the same step boundary —
        a unilateral local stop would desert the other hosts' collectives
        mid-step and deadlock until the scheduler hard-killed everyone."""
        import signal

        def handler(signum, frame):
            logger.warning(
                "signal %d: stopping after current step (next one kills)",
                signum,
            )
            self.request_stop()
            signal.signal(signum, signal.SIG_DFL)

        self._prev_handlers = {
            signal.SIGTERM: signal.signal(signal.SIGTERM, handler),
            signal.SIGINT: signal.signal(signal.SIGINT, handler),
        }

    def restore_signal_handlers(self) -> None:
        """Put back whatever handlers were installed before
        install_signal_handlers (embedding applications keep theirs)."""
        import signal

        for signum, prev in getattr(self, "_prev_handlers", {}).items():
            if prev is None:
                # prior handler was installed from C (signal.signal
                # returned None) — we cannot re-install it; leave ours
                # replaced by the safe default instead of raising
                signal.signal(signum, signal.SIG_DFL)
            else:
                signal.signal(signum, prev)
        self._prev_handlers = {}

    # ------------------------------------------------------------------- train
    def train(self) -> dict:
        """Run up to epochs/max_steps. Returns final metrics. A stop
        requested BEFORE the loop starts (signal during setup) is honored
        at the first step — never silently cleared."""
        t = self.tcfg
        # the stream-opening run header: FIRST record, before resume can
        # emit resume_reshape/ckpt_quarantined events into the file
        append_metrics_line(
            t.metrics_file,
            run_header(
                "train", run_id=self.run_id, geometry=self._geometry(),
                pid=jax.process_index(),
            ),
        )
        if t.resume:
            self.try_resume()
        global_batch = t.batch_size * self.pcfg.num_workers
        # reference parity: each worker shuffles the full set independently
        # (loader.py docstring); the global batch stacks per-worker slices.
        iters = []
        for w in range(self.pcfg.num_workers):
            imgs, labels, seed = shard_for_worker(
                self.dataset.train_images,
                self.dataset.train_labels,
                w,
                self.pcfg.num_workers,
                mode=t.shard_mode,
                seed=t.seed,
            )
            iters.append(BatchIterator(imgs, labels, t.batch_size, seed=seed))
        total = iters[0].num_samples
        steps_per_epoch = len(iters[0])
        metrics = {}
        step_no = int(jax.device_get(self.state.step))
        first_step = step_no + 1  # pays XLA compilation (also after resume)
        timer = PhaseTimer()
        # metrics stay on device between log windows (the host loop never
        # blocks dispatch), so per-step timer.total measures dispatch, not
        # compute. The logged/recorded time_cost is therefore the window
        # average: (walltime since last log, measured AFTER the window's
        # device_get drained all in-flight steps) / steps in the window —
        # the honest steady-state per-step time analysis/ scripts expect.
        window_t0, window_steps = time.perf_counter(), 0
        # dispatch backpressure: without any per-step sync the host could
        # enqueue an unbounded run-ahead (every in-flight step pins its
        # sharded batch on device). Bound it independently of log_interval.
        unsynced, max_unsynced = 0, 32
        done = False
        # profiler window: profile_steps post-compile steps (obs/
        # profiler.py), parity role of the reference's per-phase wall
        # spans but with real device timelines (SURVEY.md section 5
        # "tracing"; view with tensorboard/xprof)
        pw = ProfileWindow(
            t.profile_dir,
            start_step=(
                t.profile_start if t.profile_start is not None
                else first_step + 1
            ),
            num_steps=t.profile_steps,
            step=self._train_step,
        )
        if t.profile_dir and (pw.start > t.max_steps or pw.stop <= first_step):
            # the window misses this run's steps entirely — starts past
            # max_steps, or (an explicit --profile-start on a resumed
            # run) ended before the resume point. Say so rather than
            # silently writing nothing.
            logger.info(
                "profile-dir set but the capture window [%d, %d) misses "
                "this run's steps [%d, %d] — no trace will be written",
                pw.start, pw.stop, first_step, t.max_steps,
            )
        tr = self.tracer
        flush_due = False  # a log window closed; flush after next dispatch
        last_saved = None
        try:
            for epoch in range(1, t.epochs + 1):
                if done:
                    break
                epochs_iters = [it.epoch() for it in iters]

                def _host_batches(eis=epochs_iters):
                    for _ in range(steps_per_epoch):
                        # the synchronous host gather, apart from h2d
                        with tr.span("gather"):
                            parts = [next(ei) for ei in eis]
                            batch = {
                                k: np.concatenate([p[k] for p in parts])
                                for k in parts[0]
                            }
                        yield batch

                # batches land on the mesh PRE-SHARDED (leading dim split
                # across workers), so the step consumes them directly
                # instead of re-laying-out a replicated batch. The
                # prefetch queue dispatches each device_put one batch
                # early — the TRANSFER overlaps compute, but the host
                # gather itself is synchronous and stays in the fetch
                # phase (prefetch_to_device is a plain generator, no
                # worker thread)
                prefetched = prefetch_to_device(
                    _host_batches(), size=2,
                    device=batch_sharding(self.mesh, self.pcfg),
                    tracer=tr,  # gather and h2d spans nest under fetch
                )
                for batch_idx in range(steps_per_epoch):
                    if step_no >= t.max_steps:
                        # check BEFORE stepping so a --resume of a finished run
                        # is a no-op instead of overshooting max_steps
                        done = True
                        break
                    with tr.span("step", step=step_no + 1):
                        pw.before_step(step_no + 1, sync=self.state.params)
                        timer.reset()
                        with timer.phase("fetch"), tr.span(
                            "fetch", step=step_no + 1
                        ):
                            sharded = next(prefetched)
                        with timer.phase("step"):
                            with tr.span("dispatch", step=step_no + 1):
                                # the traced per-window controller
                                # output: same compiled program for
                                # every value
                                extras = (
                                    ()
                                    if self._adaptive is None
                                    else (np.int32(self._adaptive.count),)
                                )
                                self.state, metrics = self._train_step(
                                    self.state, sharded, self._key, *extras
                                )
                            if flush_due:
                                # the per-window flush, once the device
                                # is busy again: between the window's
                                # sync and this dispatch it stood idle,
                                # and span I/O there would lengthen the
                                # gap a traced run is there to measure
                                with tr.span("trace_flush"):
                                    tr.flush()
                                flush_due = False
                            if self.faults is not None:
                                # injected host stall, inside the timed phase
                                # so the watchdog sees it as a real slow step
                                self.faults.maybe_sleep(step_no + 1)
                            if t.straggler_threshold_s is not None:
                                # the watchdog times real step walltime, not
                                # dispatch — an intentional per-step barrier,
                                # only when the watchdog is armed (the span
                                # observes the EXISTING barrier; tracing off
                                # or on, the sync set is identical)
                                with tr.span("sync", step=step_no + 1):
                                    jax.block_until_ready(metrics)
                        step_no += 1
                        if self.faults is not None:
                            # injected preemption: SIGTERM ourselves at the
                            # planned step boundary; the installed handler
                            # raises the stop flag and _stop_consensus below
                            # turns it into a graceful checkpointed stop
                            self.faults.maybe_sigterm(step_no)
                        window_steps += 1
                        if self._adaptive is not None and step_no != first_step:
                            # the controller eats the same walltime the
                            # watchdog reads (real: its barrier is armed);
                            # the compile step is exempt like the watchdog's
                            self._adaptive.record(step_no, timer.total)
                        # counts even with the watchdog's per-step barrier:
                        # block_until_ready syncs but never FETCHES, and the
                        # guard's host half (skip events + the abort) needs
                        # values — the backpressure block below is what keeps
                        # it live when log windows don't fetch
                        unsynced += 1
                        if (
                            t.straggler_threshold_s is not None
                            and timer.total > t.straggler_threshold_s
                            and step_no != first_step  # compilation step exempt
                        ):
                            # watchdog ACTION (not just a log line): count the
                            # event and emit a machine-readable record, so
                            # --mode's semantics are observable — dashboards /
                            # the analysis layer aggregate straggler_steps the
                            # way the reference's notebooks scraped worker
                            # time-cost distributions. (Killing is meaningless
                            # under SPMD: there is no per-worker process to
                            # kill; slow steps indicate input stalls or host
                            # interference instead.)
                            self.straggler_steps += 1
                            self._straggler_streak += 1
                            if self._straggler_streak < t.straggler_storm_n:
                                logger.warning(
                                    "straggler step: Step: %d took %.4fs (threshold %.4fs)",
                                    step_no,
                                    timer.total,
                                    t.straggler_threshold_s,
                                )
                                append_metrics_line(
                                    t.metrics_file,
                                    {
                                        "kind": "straggler",
                                        "step": step_no,
                                        "time_cost": round(timer.total, 6),
                                        "threshold": t.straggler_threshold_s,
                                    },
                                )
                            elif self._straggler_streak == t.straggler_storm_n:
                                # escalation: N consecutive slow steps is one
                                # CONDITION, not N incidents — emit a single
                                # storm event and go quiet until it breaks
                                # (straggler_steps keeps counting throughout)
                                self.straggler_storms += 1
                                logger.warning(
                                    "straggler storm: %d consecutive slow steps "
                                    "(through step %d, threshold %.4fs) — "
                                    "suppressing per-step warnings until it "
                                    "clears",
                                    self._straggler_streak,
                                    step_no,
                                    t.straggler_threshold_s,
                                )
                                append_metrics_line(
                                    t.metrics_file,
                                    {
                                        "kind": "straggler_storm",
                                        "step": step_no,
                                        "start_step": (
                                            step_no - t.straggler_storm_n + 1
                                        ),
                                        "consecutive": self._straggler_streak,
                                        "threshold": t.straggler_threshold_s,
                                    },
                                )
                        elif t.straggler_threshold_s is not None:
                            # a fast step breaks the streak: if a storm was
                            # open, close its window (last slow step was the
                            # previous one)
                            self._maybe_end_storm(step_no - 1)
                            self._straggler_streak = 0
                        if t.log_interval > 0 and (
                            step_no % t.log_interval == 0 or step_no == 1
                        ):
                            # the once-per-window transfer: draining here makes
                            # the window walltime below include every in-flight
                            # step, so the per-step average stays honest.
                            # (time_cost is the authoritative per-step number;
                            # the Fetch/Forward fields remain raw host phase
                            # durations — with the watchdog disarmed, Forward
                            # is dispatch time, not compute.)
                            # One block boundary: from the end of `sync`
                            # (the device has drained and now waits) to
                            # the end of the next step's dispatch, the
                            # host holds the chip — every piece below is
                            # its own span so that wait has names.
                            with tr.span(
                                "window_close", step=step_no,
                                block=window_steps,
                            ):
                                # the wait and the read apart: `sync` ends
                                # when the window's last step has finished
                                # (the device goes idle), `metrics_fetch` is
                                # the values' way to the host after that
                                # (1.2 ms for five scalars on the v5e), which
                                # one `device_get` span hid inside the wait
                                with tr.span("sync", step=step_no):
                                    jax.block_until_ready(metrics)
                                with tr.span("metrics_fetch"):
                                    metrics = jax.device_get(metrics)  # psl: sync-ok
                                unsynced = 0
                                step_time = (
                                    time.perf_counter() - window_t0
                                ) / max(window_steps, 1)
                                window_t0, window_steps = time.perf_counter(), 0
                                with tr.span("log"):
                                    logger.info(
                                        format_iter_line(
                                            rank="mesh",
                                            step=step_no,
                                            epoch=epoch,
                                            seen=batch_idx * global_batch,
                                            total=total * self.pcfg.num_workers,
                                            loss=float(metrics["loss"]),
                                            time_cost=step_time,
                                            fetch=timer.durations.get("fetch", 0.0),
                                            forward=timer.durations.get("step", 0.0),
                                        )
                                    )
                                with tr.span("metrics_write"):
                                    append_metrics_line(
                                        t.metrics_file,
                                        {
                                            "kind": "train",
                                            "step": step_no,
                                            "epoch": epoch,
                                            "time_cost": round(step_time, 6),
                                            **{k: float(v) for k, v in metrics.items()},
                                        },
                                    )
                                # guard host half piggybacks on the window
                                # fetch: skip events + the consecutive-skip
                                # abort. Runs AFTER the window's train record
                                # lands (unlike the backpressure block below)
                                # so an aborting window is still in the JSONL
                                with tr.span("guard", step=step_no):
                                    self._guard_check(metrics, step_no)
                            # set-up is over: the process's time to its
                            # first step, by phase, once
                            line = setup_line_once()
                            if line:
                                logger.info(line)
                            # span I/O waits for the next dispatch (above):
                            # here the device is idle
                            flush_due = True
                        if unsynced >= max_unsynced:
                            # backpressure barrier + periodic fetch (reached
                            # when no log window fetched recently, e.g.
                            # log_interval=0 or very large): bounds dispatch
                            # run-ahead and keeps the guard abort live when
                            # logging is off — with the watchdog armed the
                            # buffers are already ready, so this is fetch-only
                            with tr.span("sync", step=step_no):
                                metrics = jax.device_get(metrics)  # psl: sync-ok
                            with tr.span("guard", step=step_no):
                                self._guard_check(metrics, step_no)
                            unsynced = 0
                        if (
                            t.save_checkpoints
                            # 0 = no periodic saves (the final checkpoint after
                            # the loop still writes; use save_checkpoints=False
                            # to suppress every write)
                            and t.eval_freq > 0
                            and step_no % t.eval_freq == 0
                        ):
                            # the span covers the host half (state gather +
                            # submit); the write itself is async
                            with tr.span("ckpt_save", step=step_no):
                                self._record_geometry(step_no)
                                self._ckpt.save(
                                    self.state,
                                    t.train_dir,
                                    step_no,
                                    compress=t.compress_checkpoints,
                                )
                            last_saved = step_no
                        if step_no >= t.max_steps:
                            done = True
                            break
                        with tr.span("stop_check"):
                            stop = self._stop_consensus()
                        if stop:
                            logger.warning(
                                "graceful stop at step %d (resume with --resume)",
                                step_no,
                            )
                            done = True
                            break
            if t.save_checkpoints and metrics and last_saved != step_no:
                with tr.span("ckpt_save", step=step_no):
                    self._record_geometry(step_no)
                    self._ckpt.save(
                        self.state,
                        t.train_dir,
                        step_no,
                        compress=t.compress_checkpoints,
                    )
        finally:
            pw.close(self.state.params)  # run ended (or raised) mid-window
            # drain the async writer even on error, so a submitted
            # checkpoint is durable (or its failure raised) before the
            # caller observes the outcome
            self._ckpt.wait()
            tr.flush()  # trailing partial window's spans
        out = {k: float(v) for k, v in metrics.items()}
        if out:
            # final drain of the guard's host half: a skip in a trailing
            # partial window (or a whole run shorter than log_interval)
            # still lands its grad_skip event in the JSONL. No abort —
            # the run is already over, the counter just needs reporting.
            self._guard_check(out, step_no, abort=False)
            # a storm still open at run end gets its closing event too
            self._maybe_end_storm(step_no)
        if self.straggler_steps:
            out["straggler_steps"] = float(self.straggler_steps)
            out["straggler_storms"] = float(self.straggler_storms)
        if self._adaptive is not None:
            out["agg_count"] = float(self._adaptive.count)
            out["mask_adaptations"] = float(self._adaptive.adaptations)
        return out

    # ---------------------------------------------------------------- validate
    def validate(self) -> dict:
        """Full pass over the test split (parity: nn_ops.py:90-106).

        Eval batches ride the same prefetch path as training: one batch
        in flight, landing on the mesh PRE-SPLIT across workers
        (batch_sharding) instead of single-device-then-redistribute —
        the transfer of batch k+1 overlaps the eval step on batch k."""
        t = self.tcfg
        n = self.pcfg.num_workers
        bs = max(t.test_batch_size // n, 1) * n
        it = BatchIterator(
            self.dataset.test_images,
            self.dataset.test_labels,
            bs,
            shuffle=False,
        )
        prefetched = prefetch_to_device(
            iter(it), size=2, device=batch_sharding(self.mesh, self.pcfg)
        )
        out = average_metrics(
            lambda b: self._eval_step(self.state, b), prefetched
        )
        if out:
            step_no = int(jax.device_get(self.state.step))
            logger.info(
                format_eval_line(step_no, out["loss"], out["prec1"], out["prec5"])
            )
            append_metrics_line(
                t.metrics_file, {"kind": "eval", "step": step_no, **out}
            )
        return out
