"""Step-tagged single-writer checkpoints with real resume.

Capability parity with the reference's checkpoint story — `torch.save` of a
state_dict to `train_dir/model_step_{N}` every eval_freq steps
(/root/reference/src/sync_replicas_master_nn.py:264-270,194-196;
distributed_worker.py:301-307) consumed by a polling evaluator
(distributed_evaluator.py:79-88) — minus its two defects, deliberately:

- The reference has EVERY worker write the same NFS path for ResNet/VGG (an
  write race, distributed_worker.py:175-177). Here exactly one host process
  writes, atomically (tmp file + os.replace), so a polling reader can never
  observe a torn file.
- The reference cannot resume (training always restarts at step 1,
  sync_replicas_master_nn.py:102). `latest_step` + `load_checkpoint` make
  resume a first-class operation (see trainer.PSTrainer.resume).

Format: flax.serialization msgpack bytes of the full PSTrainState (params,
optimizer state, BN stats, step) — accelerator-agnostic host arrays —
optionally wrapped in the native C++ codec (ops/codec.py, the Blosc-role
equivalent: reference compression.py w_compress wraps checkpointed weights
too). Compressed files carry a 'PSCK' magic; load auto-detects either form.

Layout neutrality: checkpoints are TREE-SHAPED at this boundary though
the live PS state is flat (params/moments as padded flat vectors).
parallel.buckets.FlatVector registers serialization handlers that
convert at the edge, so a file is byte-compatible with the tree-state
ones earlier versions wrote, those load unchanged, and nothing in THIS
module knows the live layout.

Integrity (resilience layer): every file ends with an 8-byte CRC32
trailer — b'PSC1' + crc32(everything before it) — written inside the same
atomic write, so on-disk corruption (bit rot, torn NFS replication, a
fault-injected truncation) is detected at read time instead of surfacing
as a cryptic msgpack error mid-resume. Trailer-less files written before
this layer existed still load (the trailer is recognized, never
required), so existing runs/ artifacts and in-flight --resume dirs stay
valid. `latest_valid_step` + `quarantine_checkpoint` turn a corrupt
newest checkpoint into a fall-back instead of a crash, and all file I/O
retries transient OSErrors with bounded exponential backoff
(resilience/retry.py — the shared-NFS evaluator is where transient EIO
lives).
"""

from __future__ import annotations

import logging
import os
import re
import struct
import time
import zlib
from typing import Iterator, Optional

import jax
from flax import serialization

from .resilience import retry_io
from .resilience.guard import reconcile_guard_state

logger = logging.getLogger("ps_pytorch_tpu")

CKPT_RE = re.compile(r"^model_step_(\d+)$")
COMPRESSED_MAGIC = b"PSCK"
# integrity trailer: magic + little-endian crc32 of all preceding bytes
TRAILER_MAGIC = b"PSC1"
TRAILER_LEN = len(TRAILER_MAGIC) + 4
# suffix a quarantined (corrupt) checkpoint is renamed to; CKPT_RE no
# longer matches it, so available_steps/resume stop seeing it
QUARANTINE_SUFFIX = ".corrupt"
# top-level PSTrainState fields that are observability, not math: when a
# checkpoint predates the field, loading resets it to the target's fresh
# value instead of erroring (unlike comm_state, whose silent loss would
# change the training trajectory — see load_checkpoint). Each maps to the
# owning module's reconcile hook — (stored_dict, fresh_dict) -> merged —
# so this layer never learns the field's internals
RESETTABLE_FIELDS = {"guard_state": reconcile_guard_state}


class CheckpointError(Exception):
    """Base for checkpoint integrity/IO failures."""


class CheckpointCorruptError(CheckpointError):
    """The on-disk bytes are damaged (CRC mismatch, truncation, codec or
    msgpack failure) — retrying will not help; quarantine + fall back."""


class CheckpointWriteError(CheckpointError):
    """A (possibly background) checkpoint write failed; carries the step
    and path so the failure is actionable when it surfaces at wait()."""

    def __init__(self, step: int, path: str, cause: BaseException):
        super().__init__(
            f"checkpoint write failed for step {step} at {path}: {cause}"
        )
        self.step = step
        self.path = path


def checkpoint_path(model_dir: str, step: int) -> str:
    # name parity with the reference's _generate_model_path
    return os.path.join(model_dir, f"model_step_{step}")


def _gather_host_state(state):
    """Bring `state` to full host arrays on every process.

    Single-process: plain device_get. Multi-host (process_count > 1):
    ONLY leaves that are jax.Arrays with non-addressable shards get the
    multihost_utils gather (a collective — every process must call this,
    and every process holds the same pytree structure, so the per-leaf
    collectives line up). Host-local leaves (numpy arrays, scalars,
    metadata strings) pass through untouched — handing them to
    process_allgather would stack/concat them per-process. The writer
    side then keeps exactly one process writing (see save_checkpoint)."""
    if jax.process_count() <= 1:
        return jax.device_get(state)
    from jax.experimental import multihost_utils

    def leaf(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return multihost_utils.process_allgather(x, tiled=True)
        if isinstance(x, jax.Array):
            return jax.device_get(x)
        return x

    return jax.tree.map(leaf, state)


def save_checkpoint(state, model_dir: str, step: int, compress: bool = False,
                    faults=None) -> str:
    """Atomically write `state` (any flax-serializable pytree) for `step`.

    Multi-host: collective (all processes must call it — the gather is a
    collective op); only process 0 writes the file, preserving the
    single-writer guarantee, and a barrier after the write means the
    write has COMPLETED before any process returns. A write FAILURE on
    process 0 must reach that barrier too — raising before it would
    strand processes 1..N-1 in the collective forever — so the error is
    held across an ok/fail broadcast and then raised on every process
    (a failed checkpoint is a collective outcome, not a process-0
    secret). The path is on process 0's filesystem: reading it from
    other processes (e.g. --resume after preemption) requires
    `model_dir` to be on storage all hosts share — a gcsfuse bucket
    (tools/tpu_cluster.py mount) or NFS, exactly like the reference's
    NFS train_dir (README.md:23).

    This hold-then-broadcast shape is the sanctioned error idiom psdiverge
    (PSL006, ARCHITECTURE §7b) checks against: raising inside the
    ``process_index() == 0`` branch BEFORE the barrier is exactly the
    stranded-collective bug this function once shipped, and is now a
    regression fixture in tests/test_lint.py."""
    host_state = _gather_host_state(state)
    path = checkpoint_path(model_dir, step)
    err = None
    if jax.process_index() == 0:
        try:
            _write_host_state(host_state, model_dir, step, compress,
                              faults=faults)
        except BaseException as e:
            err = e
    if jax.process_count() > 1:
        import numpy as np
        from jax.experimental import multihost_utils

        ok = multihost_utils.broadcast_one_to_all(
            np.int32(0 if err is not None else 1)
        )
        multihost_utils.sync_global_devices(f"ckpt_save_{step}")
        if err is None and not int(ok):
            raise CheckpointWriteError(
                step, path,
                RuntimeError("checkpoint write failed on process 0"),
            )
    if err is not None:
        raise err
    return path


def _write_host_state(state, model_dir: str, step: int, compress: bool,
                      faults=None) -> str:
    """Host-side half of a save (state already device_get). Runs on the
    async writer thread; everything here is pure host CPU + disk.

    The CRC trailer is computed over the final on-disk bytes (after the
    codec, if any) and written inside the same atomic tmp+replace, so a
    reader can never observe a trailer that does not match its payload.
    Disk I/O retries transient OSErrors; an injected write fault
    (resilience.FaultPlan.ckpt_write_fail) fails every attempt so the
    failure genuinely surfaces."""
    os.makedirs(model_dir, exist_ok=True)
    path = checkpoint_path(model_dir, step)
    data = serialization.to_bytes(state)
    if compress:
        from .ops import codec

        # itemsize 4: the payload is dominated by f32 leaves, so a 4-byte
        # shuffle feeds the LZ stage well; correctness is itemsize-agnostic
        data = COMPRESSED_MAGIC + codec.compress_bytes(data, itemsize=4)
    data += TRAILER_MAGIC + struct.pack("<I", zlib.crc32(data))

    def write():
        if faults is not None:
            faults.maybe_fail_ckpt_write(step)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    retry_io(write, desc=f"checkpoint write step {step}")
    if faults is not None:
        faults.maybe_corrupt_ckpt(path, step)
    return path


class AsyncCheckpointer:
    """Overlap checkpoint serialization + disk IO with training.

    The device->host transfer happens on the caller's thread (it must
    observe a consistent step boundary); msgpack serialization, codec
    compression, and the atomic write run on one background thread, so the
    train loop never blocks on disk. `wait()` drains pending writes —
    Trainer.train calls it before returning, keeping the reference's
    synchronous visible behavior (a checkpoint exists when training is
    done) without its per-step stall. Single writer by construction
    (one thread), preserving the no-torn-reads guarantee.

    Failure handling: a background write that fails is logged — and
    reported through `event_sink` as a structured ``ckpt_write_failed``
    record — AT FAILURE TIME on the writer thread, then re-raised from
    the next `save()`/`wait()` wrapped in CheckpointWriteError carrying
    the step and path it was writing (the bare future exception said
    neither)."""

    def __init__(self, event_sink=None, faults=None):
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="ckpt")
        self._pending = None
        self._event_sink = event_sink
        self._faults = faults

    def save(self, state, model_dir: str, step: int, compress: bool = False):
        if jax.process_count() > 1:
            # multi-host: degrade to the synchronous collective save — its
            # barrier gives every process a durable-write guarantee, which
            # an async submit on process 0 alone cannot (the other
            # processes' wait() would be a no-op on an unwritten file).
            # Same failure wrapper as the async path: the event + the
            # step/path context are promised unconditionally.
            self._logged(
                lambda: save_checkpoint(state, model_dir, step, compress,
                                        faults=self._faults),
                model_dir, step,
            )
            return
        host_state = _gather_host_state(state)
        self.wait()  # keep at most one write in flight
        self._pending = self._pool.submit(
            self._write_logged, host_state, model_dir, step, compress
        )

    def _write_logged(self, host_state, model_dir: str, step: int,
                      compress: bool):
        return self._logged(
            lambda: _write_host_state(
                host_state, model_dir, step, compress, faults=self._faults
            ),
            model_dir, step,
        )

    def _logged(self, write, model_dir: str, step: int):
        path = checkpoint_path(model_dir, step)
        try:
            return write()
        except CheckpointWriteError:
            # already wrapped: save_checkpoint's collective-outcome raise
            # on processes 1..N-1. Process 0 owns the log line and the
            # structured event — re-wrapping would nest the message and
            # duplicate the JSONL record once per process.
            raise
        except Exception as e:
            # report NOW (on the async path: the writer thread), not at
            # the next wait() — by then the loop is steps ahead and the
            # context is gone. Exception, not BaseException: a
            # KeyboardInterrupt landing in the synchronous multi-host
            # save must not masquerade as a ckpt_write_failed event.
            logger.error(
                "checkpoint write failed (step %d, %s): %s",
                step, path, e,
            )
            if self._event_sink is not None:
                try:
                    self._event_sink({
                        "kind": "ckpt_write_failed",
                        "step": step,
                        "path": path,
                        "error": str(e),
                    })
                except Exception:
                    logger.exception("ckpt_write_failed event sink raised")
            raise CheckpointWriteError(step, path, e) from e

    def wait(self):
        if self._pending is not None:
            try:
                self._pending.result()
            finally:
                self._pending = None


def _read_payload(model_dir: str, step: int, read_attempts: int = 3):
    """Read one checkpoint file and verify+strip its CRC trailer.

    Returns (payload, had_trailer); the payload is still codec-compressed
    if it was written that way. A file without the trailer is a
    pre-resilience checkpoint and is accepted as-is — the trailer is
    detected, never demanded, so seed-era runs/ artifacts keep loading."""
    path = checkpoint_path(model_dir, step)

    def read():
        with open(path, "rb") as f:
            return f.read()

    data = retry_io(
        read, desc=f"checkpoint read step {step}", attempts=read_attempts
    )
    if len(data) >= TRAILER_LEN and data[-TRAILER_LEN:-4] == TRAILER_MAGIC:
        payload, (crc,) = data[:-TRAILER_LEN], struct.unpack(
            "<I", data[-4:]
        )
        if zlib.crc32(payload) != crc:
            raise CheckpointCorruptError(
                f"CRC mismatch in {path}: stored {crc:#010x}, computed "
                f"{zlib.crc32(payload):#010x} — the file is damaged"
            )
        return payload, True
    return data, False


def _decode_payload(data: bytes, path: str):
    """Trailer-stripped bytes -> raw nested dicts (codec, then msgpack),
    with decode failures (the signature of a damaged trailer-less file)
    classified as corruption. Structure mismatches AFTER a clean restore
    are config errors, not corruption, and propagate unchanged from the
    from_state_dict side."""
    if data[:4] == COMPRESSED_MAGIC:
        from .ops import codec

        try:
            data = codec.decompress_bytes(data[4:])
        except Exception as e:
            raise CheckpointCorruptError(
                f"codec decompression failed for {path}: {e}"
            ) from e
    try:
        return serialization.msgpack_restore(data)
    except (ValueError, EOFError, struct.error) as e:
        raise CheckpointCorruptError(
            f"cannot deserialize {path}: {e}"
        ) from e
    except Exception as e:
        # msgpack's unpack exceptions don't all subclass ValueError;
        # anything raised from its module is a damaged-bytes signature
        if type(e).__module__.partition(".")[0] == "msgpack":
            raise CheckpointCorruptError(
                f"cannot deserialize {path}: {e}"
            ) from e
        raise


def _restore_raw(model_dir: str, step: int, read_attempts: int = 3):
    data, _ = _read_payload(model_dir, step, read_attempts)
    return _decode_payload(data, checkpoint_path(model_dir, step))


def verify_checkpoint(
    model_dir: str, step: int, read_attempts: int = 3
) -> None:
    """Raise CheckpointCorruptError (damaged) or OSError (unreadable) if
    checkpoint `step` cannot be restored; return None when valid.

    A CRC trailer, when present, certifies every byte of the file, so
    the (possibly large) codec + msgpack decode is skipped — validation
    on the evaluator's poll path must not double each checkpoint's load
    cost. Legacy trailer-less files get the full decode: a restore is
    the only way to detect their truncation. Callers that wrap this in
    their own retry loop (_await_readable) pass read_attempts=1 so the
    two backoff schedules don't multiply."""
    data, had_trailer = _read_payload(model_dir, step, read_attempts)
    if not had_trailer:
        _decode_payload(data, checkpoint_path(model_dir, step))


def quarantine_checkpoint(model_dir: str, step: int) -> str:
    """Rename a damaged checkpoint out of the `model_step_N` namespace so
    resume/eval stop considering it, but the bytes stay for forensics."""
    path = checkpoint_path(model_dir, step)
    target = path + QUARANTINE_SUFFIX
    os.replace(path, target)
    logger.warning("quarantined corrupt checkpoint %s -> %s", path, target)
    return target


def latest_valid_step(model_dir: str) -> Optional[int]:
    """Newest step whose file passes a full integrity check, skipping
    (but not touching) corrupt/truncated ones — read-only, so a polling
    evaluator can call it while racing the trainer's writer."""
    for step in reversed(available_steps(model_dir)):
        try:
            verify_checkpoint(model_dir, step)
            return step
        except (CheckpointCorruptError, OSError) as e:
            logger.warning(
                "checkpoint step %d is not loadable (%s); trying older",
                step, e,
            )
    return None


def listify_raw(tree):
    """msgpack restores list-typed pytree nodes as dicts {'0': ...}; undo.

    Shared by every raw-dict consumer (cli/evaluate_lm, serve/engine):
    the LM checkpoint's params carry a ``blocks`` LIST, and a consumer
    rebuilding model structure from the raw dict needs it back."""
    if isinstance(tree, dict):
        if tree and all(k.isdigit() for k in tree):
            return [listify_raw(tree[str(i)]) for i in range(len(tree))]
        return {k: listify_raw(v) for k, v in tree.items()}
    return tree


def load_latest_valid(model_dir: str, after_step: Optional[int] = None):
    """Read-only fast path for a polling consumer (the serving engine's
    hot-rollover poll): newest checkpoint strictly newer than
    ``after_step`` (None = any), loaded as raw nested dicts in ONE read.

    ``latest_valid_step`` + ``load_checkpoint_raw`` pay two reads per
    file (verify, then load — inherent to checking before yielding a
    step to an arbitrary consumer). Here the consumer is this function's
    own caller, so the CRC check and the decode run on the SAME in-memory
    bytes: one read per candidate, corrupt/unreadable files are skipped
    (never touched — the writer may still be racing us), and the result
    is ``(step, raw_dict)`` or None when nothing newer loads."""
    for step in reversed(available_steps(model_dir)):
        if after_step is not None and step <= after_step:
            return None
        try:
            data, _ = _read_payload(model_dir, step, read_attempts=1)
            return step, _decode_payload(data, checkpoint_path(model_dir, step))
        except (CheckpointCorruptError, OSError) as e:
            logger.warning(
                "checkpoint step %d is not loadable (%s); trying older",
                step, e,
            )
    return None


def load_checkpoint(target, model_dir: str, step: int):
    """Load step N into the structure of `target` (an initialized state).
    Auto-detects codec-compressed checkpoints.

    Forward-compat: a top-level field that exists in `target` with value
    None but is absent from the stored dict (a field added to the state
    AFTER the checkpoint was written, e.g. PSTrainState.comm_state) is
    filled with None instead of hard-erroring — old checkpoints stay
    resumable as long as the new feature is off. A non-None target field
    still errors loudly (its state genuinely cannot be reconstructed).
    The converse mismatch — the checkpoint CARRIES state for a feature
    the target has off (stored comm_state, target None) — also errors
    loudly: flax would otherwise pass the raw arrays through a None
    target silently, and dropping accumulated EF residuals would quietly
    change the training math.

    RESETTABLE_FIELDS (guard_state) get softer treatment in BOTH
    directions: absent from the checkpoint -> restored as the target's
    fresh value (counters re-zeroed); present but disabled in the target
    -> dropped. They are observability, and must never strand a
    checkpoint the way lost EF residuals would."""
    return restore_from_raw(target, _restore_raw(model_dir, step), step)


def restore_from_raw(target, raw, step: int):
    """The merge half of ``load_checkpoint``: raw nested dicts (already
    read and decoded — or transformed, e.g. by the elastic resume-reshape
    in resilience/elastic.py) into the structure of ``target``, with the
    same forward-compat and RESETTABLE_FIELDS rules documented there."""
    tgt_dict = serialization.to_state_dict(target)
    if isinstance(raw, dict) and isinstance(tgt_dict, dict):
        for k, v in tgt_dict.items():
            if k in RESETTABLE_FIELDS:
                if v is None:
                    # guard off now: drop whatever was stored — and fill
                    # the key in when a pre-guard checkpoint lacks it, or
                    # from_state_dict errors on the missing field
                    raw[k] = None
                elif raw.get(k) is None:
                    # pre-guard checkpoint (key absent) or guard-off run
                    # (stored None): restore the target's fresh counters
                    raw[k] = v
                elif isinstance(raw[k], dict) and isinstance(v, dict):
                    # both sides carry state: the owning module decides
                    # what survives the config change (e.g. the guard's
                    # dyn-flag/loss-scale rules live in resilience/guard)
                    raw[k] = RESETTABLE_FIELDS[k](raw[k], v)
                continue
            if k not in raw and v is None:
                raw[k] = None
            elif v is None and raw.get(k) is not None:
                raise ValueError(
                    f"checkpoint step {step} carries state for field {k!r} "
                    f"but the target state has it disabled (None). Enable "
                    f"the matching feature (e.g. --error-feedback for "
                    f"comm_state) to resume this checkpoint, or rebuild it "
                    f"without that state."
                )
    return serialization.from_state_dict(target, raw)


def restore_sharded(target, model_dir: str, step: int, mesh, specs):
    """Load step N and place every leaf on `mesh` with its PartitionSpec
    from `specs` (a pytree of PartitionSpecs, e.g. parallel.tp_param_specs
    output or an opt_state_specs tree).

    save_checkpoint gathers sharded arrays to full host arrays
    (device_get single-process; multihost_utils.process_allgather when
    process_count > 1), so a checkpoint written from a tp/pp/moe-sharded
    state restores onto ANY mesh shape whose specs divide the shapes —
    resharding across different device counts (and host counts) is free.
    """
    from .parallel.mesh import place_on_mesh

    return place_on_mesh(load_checkpoint(target, model_dir, step), mesh, specs)


def load_checkpoint_raw(model_dir: str, step: int,
                        read_attempts: int = 3) -> dict:
    """Load step N as raw nested dicts, no target structure required.

    This is what lets the evaluator stay ignorant of the trainer's optimizer
    and placement config: it only consumes params/batch_stats/step and never
    needs to reconstruct the opt_state pytree (whose structure varies by
    --optimizer/--opt-placement).

    ``read_attempts=1`` disables the I/O retry backoff — the serving
    engine's swap-time re-read (serve/engine._try_swap) wants a fast
    verdict on the staged file, not a multi-attempt stall inside the
    request loop; its caller treats OSError as an abort, not a retry."""
    return _restore_raw(model_dir, step, read_attempts)


def available_steps(model_dir: str):
    if not os.path.isdir(model_dir):
        return []
    steps = []
    for name in os.listdir(model_dir):
        m = CKPT_RE.match(name)
        if m:
            steps.append(int(m.group(1)))
    return sorted(steps)


def latest_step(model_dir: str) -> Optional[int]:
    steps = available_steps(model_dir)
    return steps[-1] if steps else None


def _await_readable(model_dir: str, step: int, attempts: int,
                    base_delay_s: float) -> bool:
    """True once checkpoint `step` fully restores; retries both OSError
    (NFS close-to-open visibility: listed but not yet openable) and
    corruption (a replica still propagating) with backoff. False = gave
    up — the caller skips the step instead of dying downstream."""
    try:
        retry_io(
            # read_attempts=1: this outer loop IS the retry schedule;
            # _read_payload's internal retry would multiply it (5 outer
            # x 3 inner = 15 reads with compounded backoff)
            lambda: verify_checkpoint(model_dir, step, read_attempts=1),
            desc=f"checkpoint step {step} readability",
            attempts=attempts,
            base_delay_s=base_delay_s,
            retry_on=(OSError, CheckpointCorruptError),
        )
        return True
    except (OSError, CheckpointCorruptError) as e:
        logger.warning(
            "checkpoint step %d never became readable (%s): skipping it",
            step, e,
        )
        return False


def poll_checkpoints(
    model_dir: str,
    start_after: int = 0,
    interval_s: float = 10.0,
    timeout_s: Optional[float] = None,
    validate: bool = True,
    validate_attempts: int = 5,
    validate_delay_s: float = 0.2,
) -> Iterator[int]:
    """Yield new checkpoint steps as they appear (evaluator's consume loop;
    parity: distributed_evaluator.py:79-88 polls every 10s). Stops when
    `timeout_s` elapses with no new checkpoint (None = poll forever).

    With `validate` (default), a step visible in the directory listing
    but not yet fully readable — slow NFS visibility, or a corrupt file —
    is retried with backoff and then SKIPPED rather than yielded once and
    left to crash the consumer (the reference evaluator's torch.load
    simply died there)."""
    seen = start_after
    waited = 0.0
    while True:
        fresh = [s for s in available_steps(model_dir) if s > seen]
        if fresh:
            waited = 0.0
            for s in fresh:
                seen = s
                if validate and not _await_readable(
                    model_dir, s, validate_attempts, validate_delay_s
                ):
                    continue
                yield s
            continue
        if timeout_s is not None and waited >= timeout_s:
            return
        time.sleep(interval_s)
        waited += interval_s
