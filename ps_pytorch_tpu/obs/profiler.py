"""Bounded jax.profiler capture windows for the train/serve drivers.

``--profile-dir`` captures a device+host profiler trace for the step
window ``[start, start + n)`` — on TPU an op-level device timeline, on
CPU host events (both render in xprof/tensorboard, and the host spans
from obs/trace.py appear as named TraceAnnotation regions when the
driver's tracer runs with ``annotate=True``).

The ONE deliberate host sync lives here: stopping a trace must wait for
the in-flight window to retire or the file ends mid-step. It runs
exactly once per capture (never per step) and carries the ``psl:
sync-ok`` pragma — pslint's strict PSL004 sweep over ``obs/`` flags any
other sync in this tree.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

from ..utils import get_logger
from .scopes import write_step_scopes

logger = get_logger()


class ProfileWindow:
    """Start/stop ``jax.profiler`` around steps ``[start, start+n)``.

    Drive it with ``before_step(step, sync=...)`` immediately before
    dispatching ``step``; ``close(sync)`` (idempotent) from a finally
    block so a run that ends or raises inside the window still writes a
    valid trace. ``sync`` is any pytree to block on before stopping —
    the trainer passes its params so the captured window contains
    retired device work, not just dispatch. With `step` (a step that
    keeps its own census: obs/scopes.ScopedStep) the capture gets
    `step_scopes.json` beside it when it stops."""

    def __init__(self, profile_dir: Optional[str], start_step: int,
                 num_steps: int = 10, step=None):
        # validate only when profiling is actually requested: the trainer
        # constructs this unconditionally, and a stray --profile-steps 0
        # without --profile-dir must not abort the run it doesn't affect
        if profile_dir is not None and num_steps < 1:
            raise ValueError(f"profile window needs >= 1 step, got {num_steps}")
        self.dir = profile_dir
        self.start = int(start_step)
        self.stop = int(start_step) + int(num_steps)
        self.active = False
        # the step whose census goes beside the capture (obs/scopes.
        # ScopedStep), read when the capture stops
        self.step = step

    def before_step(self, step: int, sync=None) -> None:
        if self.dir is None:
            return
        if not self.active and self.start <= step < self.stop:
            import jax

            # device ops and the program's own annotations, nothing
            # more: Python call tracing and host tracer level 2 (every
            # layout chunk of an upload, 300,000 events a block on the
            # v5e) slow the loader tenfold — the capture would distort
            # the loop it watches (PERF.md section 3)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True
            logger.info(
                "profiler capture started: steps [%d, %d) -> %s",
                self.start, self.stop, self.dir,
            )
        elif self.active and step >= self.stop:
            self._finish(sync)

    def close(self, sync=None) -> None:
        """Stop an open capture (run ended or raised inside the window)."""
        if self.active:
            self._finish(sync)

    def _finish(self, sync) -> None:
        import jax

        if sync is not None:
            # once per CAPTURE, not per step: the trace must contain the
            # window's retired device work, so this barrier is the point
            jax.block_until_ready(sync)  # psl: sync-ok
        jax.profiler.stop_trace()
        self.active = False
        logger.info("profiler trace written to %s", self.dir)
        # what `tools/trace_report.py device` joins the capture with
        write_step_scopes(self.dir, self.step)


def device_planes(profile_dir: str):
    """(the newest capture `plugins/profile/*/*.xplane.pb` under a
    `--profile-dir`, its `/device:` planes as jax.profiler.ProfileData
    gives them); (None, []) where there is no capture."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None, []
    newest = max(files, key=os.path.getmtime)
    return newest, [plane for plane in ProfileData.from_file(newest).planes
                    if plane.name.startswith("/device:")]
