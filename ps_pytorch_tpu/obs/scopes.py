"""Names for the parts of a step program, written INSIDE the program.

A train step is one XLA program of thousands of ops; which of them belong
to a mixer, an FFN, the head, the update is known only where the model is
written down. `scope(NAME)` is `jax.named_scope`: it adds NAME to the
`op_name` metadata of every op traced under it and changes nothing the
compiler emits (tests/test_scopes.py holds a step's compiled text, metadata
stripped, to the same step with `scope` made a no-op). `obs/hlo.py` reads
the names back out of a compiled program's text, and with them the phase
jax itself writes around them (`jvp(...)`, `transpose(jvp(...))`,
`rematted_computation`).

ONE vocabulary, here: a model imports the constants, no scope's string is
spelt anywhere else, and a path outside `SCOPES` is not read as a scope. A
path is made by nesting: `with scope(MIXER_KDA): ... with scope(DELTA_RULE)`
gives `mixer/kda/delta_rule`; `FLASH` sits under whichever mixer calls the
flash attention (`mixer/*/flash`).

This module also keeps what a reader needs after the loop has let go of its
step: `ScopedStep`, the jitted step with its own census (`scopes()`), and a
small registry of the last step built under each program name, which holds
the step weakly and, of a step a profiler capture saw, the census it read
as it went (`last_step`). jax is imported where `scope` and `ScopedStep`
are called, not with the module.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import json
import os
import time
import weakref
from typing import Dict, Optional, Tuple

from .trace import setup_tracer

# what `scope()` is given (the relative names that models import) ...
EMBED = "embed"
MIXER_ATTENTION = "mixer/attention"
MIXER_MLA = "mixer/mla"
MIXER_SSD = "mixer/ssd"
MIXER_KDA = "mixer/kda"
MIXER_EVA = "mixer/eva"
MIXER_SWA = "mixer/swa"
EVA_POOL = "pool"               # the four under MIXER_EVA (ops/eva.py)
EVA_LOCAL = "local"
EVA_REMOTE = "remote"
EVA_MERGE = "merge"
DELTA_RULE = "delta_rule"       # under MIXER_KDA
SCAN = "scan"                   # under MIXER_SSD
FLASH = "flash"                 # under any mixer
ROPE = "rope"                   # the three under MIXER_SWA / MIXER_ATTENTION
ATTN_GATE = "gate"              # (models/swa_moe.py)
KV_REPEAT = "kv_repeat"
FFN = "ffn"
MLP = "mlp"                     # under FFN
MOE = "moe"                     # under FFN
MOE_ROUTE = "route"             # the four under FFN / MOE
MOE_DISPATCH = "dispatch"
MOE_EXPERTS = "experts"
MOE_COMBINE = "combine"
HEAD_LOSS = "head_loss"
GRAD_REDUCE = "grad_reduce"
UPDATE = "update"
AUGMENT = "augment"
MODEL = "model"

# ... and the paths they make, one line each: what `obs/hlo.py` accepts as a
# scope, what PERF.md section 3 lists, what a metric's `scope` expression is
# written against. A `*` stands for any characters of one segment: a mixer
# kind, a bucket's offset (those two are written by the PS step's own
# `jax.named_scope(f"bucket_reduce_o{start}")`, under the scopes of this file).
SCOPES: Tuple[Tuple[str, str], ...] = (
    ("embed", "token (and position) embedding"),
    ("mixer/attention", "the attention half of a block: norm, q/k/v, heads, W_o (dense and grouped-query)"),
    ("mixer/mla", "the latent-attention half of a block: norm, q, the latent pair, rotation, W_o"),
    ("mixer/ssd", "the Mamba-2 half of a block: norm, in_proj, conv, dt, gated norm, out_proj"),
    ("mixer/ssd/scan", "ops/ssd.ssd_chunked: the chunked scan with the relayouts into and out of its chunks"),
    ("mixer/kda", "the delta-rule half of a block: norm, the three short convs, L2 norms, decay, beta, gated norm, W_o"),
    ("mixer/kda/delta_rule", "ops/kda.kda_chunked: chunk layout, cumulative sums, the ps_kda_* kernels, the scan across chunks"),
    ("mixer/eva", "the EVA half of a block: norm, q/k/v, rotation, the folds into and out of ops/eva.eva_attention, W_o"),
    ("mixer/eva/pool", "ops/eva.eva_pool: a chunk of keys and values into one learned summary of each"),
    ("mixer/eva/local", "the causal ps_flash_* passes over the windows folded into the leading axis"),
    ("mixer/eva/remote", "the ps_flash_* passes of every query over the pooled keys of the windows before its own"),
    ("mixer/eva/merge", "the two passes' triples joined by (m, l) and normalized; delta; the two dq summed"),
    ("mixer/swa", "the sliding-window half of a block: norm, q/k/v over grouped heads, W_o (models/swa_moe.py; its global layers are mixer/attention)"),
    ("mixer/*/flash", "ops/flash_attention.flash_attention: fold, pad, the ps_flash_* kernels, unfold"),
    ("mixer/*/rope", "the rotation of q and k: plain, or YaRN frequencies on the leading part of a head"),
    ("mixer/*/kv_repeat", "keys and values repeated to the query heads' width before the attention call"),
    ("mixer/*/gate", "the sigmoid gate a head and token on the attention output, before W_o"),
    ("ffn", "the FFN half's own norm and residual"),
    ("ffn/mlp", "a dense (gated or GELU) MLP: a dense layer's, or the shared experts'"),
    ("ffn/moe", "parallel/moe.moe_dropless_local outside its four parts (the counters)"),
    ("ffn/moe/route", "the float32 router: scores, top-k, weights"),
    ("ffn/moe/dispatch", "rows by expert: sorts, masks, a pass's gather into its buffer"),
    ("ffn/moe/experts", "the grouped products ps_moe_gmm / ps_moe_tgmm and the gate between them"),
    ("ffn/moe/combine", "the rows' weights and the gather back to tokens"),
    ("head_loss", "final norm, head, log_softmax, the loss"),
    ("grad_reduce", "the gradients' (and the loss's and counters') psum / pmean; the PS wire with its quantize and error feedback"),
    ("grad_reduce/bucket_reduce_o*", "PS step, pipelined wire: one bucket's reduce chain, by its offset (parallel/collectives.py, ps.py)"),
    ("update", "tx.update and apply_updates; in the PS step also what follows them: metric means, the guard's select"),
    ("update/bucket_update_o*", "PS step, pipelined wire: one bucket's update, by its offset (parallel/ps.py)"),
    ("augment", "PS step: crop, flip, normalise"),
    ("model", "PS step: the network's apply inside value_and_grad"),
)
PATHS = frozenset(path for path, _ in SCOPES)

# phases ("forward" | "backward" | "remat" | "update" | "input" | "other") are
# read from what jax writes, except these scopes', which are a phase by
# themselves (nothing of them is differentiated)
PHASE_OF_SCOPE = {"grad_reduce": "update", "update": "update", "augment": "input"}


def _named_scope(name: str):
    import jax

    return jax.named_scope(name)


@contextlib.contextmanager
def scope(name: str):
    """`jax.named_scope(name)` for a name of this module, as a context or a
    decorator. (Through `_named_scope`, looked up at each entry: the tests
    put a null context there to show that a scope is metadata only.)"""
    with _named_scope(name):
        yield


# ----------------------------------------------------------- the step's own


@functools.cache
def source_stamp() -> str:
    """Eight hex digits of this package's sources.

    jax leaves metadata out of the compile cache's key, so an executable
    found in the cache carries the `op_name`s of whoever compiled it first:
    a step compiled before a scope was written (or moved) would give a
    census without it, silently. The step programs therefore carry this
    stamp in their NAME (`stamped`), which is in the key: a cache entry made
    from other sources is never taken for this program's. The price is one
    compilation of the step after any edit under ps_pytorch_tpu/."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for folder, subfolders, files in os.walk(root):
        subfolders.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(folder, name)
            digest.update(os.path.relpath(path, root).encode())
            with open(path, "rb") as f:
                digest.update(f.read())
    return digest.hexdigest()[:8]


def stamped_name(name: str) -> str:
    return f"{name}_src{source_stamp()}"


def stamped(fun):
    """`fun` under its name plus the source stamp: what a step builder
    hands to `jax.jit`, which names the program after it."""
    fun.__name__ = fun.__qualname__ = stamped_name(fun.__name__)
    return fun


class _Ran:
    """What a step's calls leave behind, shared by the step and the
    registry so that it outlives the step: the abstract shapes of its first
    call, whether a profiler capture was running during any call, and the
    census once read."""

    __slots__ = ("program", "avals", "profiled", "census")

    def __init__(self, program: str):
        self.program = program
        self.avals = None
        self.profiled = False
        self.census = None

    def scopes(self) -> dict:
        """The census a released step left (`last_step` hands this object
        out once the step is gone)."""
        if self.census is None:
            raise RuntimeError(
                f"{self.program}: the step was released and no capture ran during its calls, "
                "so its census was not kept")
        return self.census


# {program: (weak reference to the last step built under it, its _Ran)}
_LAST: Dict[str, Tuple["weakref.ref", _Ran]] = {}


def last_step(program: str):
    """The step most recently built under `program` in this process
    ("lm_train_step": parallel/dp_sp.py; "ps_train_step": parallel/ps.py),
    or None. The registry holds the step WEAKLY: a loaded executable keeps
    its code and its reserved scratch on the device (0.2-0.4 GB and 2-9 GB
    for the benchmark's LM steps), so it must go when the loop lets go of
    the step. A step that ran under a profiler capture reads its census as
    it is released; `last_step` then hands out what is left of it, which
    still answers `scopes()`."""
    kept = _LAST.get(program)
    if kept is None:
        return None
    step = kept[0]()
    return kept[1] if step is None else step


def _abstract(x):
    """What `jit.lower` needs of one argument to find the executable the
    call made: shape, dtype, weak type, and the sharding if committed."""
    import jax

    if not isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
        return x
    # a typed key wraps the array that was or was not committed
    data = jax.random.key_data(x) if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key) else x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=getattr(x, "weak_type", False),
        sharding=x.sharding if data.committed else None)


class _NoProfilerState:
    profile_session = None


def _profiler_state():
    """(jax's own record of a running capture, whether it could be found).
    jax.profiler.start_trace keeps its session there until stop_trace; it
    is private, so where it is missing every step counts as profiled."""
    try:
        from jax._src.profiler import _profile_state

        _profile_state.profile_session
        return _profile_state, True
    except (ImportError, AttributeError):
        return _NoProfilerState, False


def _read_census(jitted, ran: _Ran) -> dict:
    """obs/hlo.census of the executable `jitted` ran at `ran.avals`: lowering
    and compiling at those shapes finds it in jit's own caches (no second
    trace or compilation: tests/test_scopes.py counts). `read_s` says where
    the reading's time went."""
    from .hlo import census

    t0 = time.perf_counter()
    compiled = jitted.lower(*ran.avals).compile()
    t1 = time.perf_counter()
    text = compiled.as_text()
    t2 = time.perf_counter()
    out = {"program": ran.program, **census(text)}
    out["read_s"] = {"executable": t1 - t0, "text": t2 - t1, "parse": time.perf_counter() - t2}
    return out


def _released(jitted, ran: _Ran) -> None:
    """The step is gone (`weakref.finalize`): keep its census if a capture
    saw it run and nobody has read it yet, then let go of the executable."""
    if ran.profiled and ran.census is None and ran.avals is not None:
        try:
            ran.census = _read_census(jitted, ran)
        except Exception:  # a finalizer has nobody to raise to
            pass


class ScopedStep:
    """A jitted step that can give the census of its own executable.

    Calling it calls the jitted function. The first call also notes the
    arguments' abstract shapes and is the `setup.first_call` span of the
    process's set-up record (obs/trace.setup_tracer: trace, lowering,
    compile or cache load and the first dispatch, with jax's own spans
    inside it), and a call made while a profiler capture runs marks the
    step as profiled: two tests a call (`is None`, `is not None`) on the
    way to the jitted function, and no record after the first. `scopes()`
    reads the census of the executable the calls ran. Everything else
    (`lower`, `trace`, `eval_shape`, ...) is the jitted function's."""

    def __init__(self, program: str, jitted):
        self.program = program
        self._jitted = jitted
        self._avals = None
        self._ran = ran = _Ran(program)
        self._capture, found = _profiler_state()
        ran.profiled = not found
        _LAST[program] = (weakref.ref(self), ran)
        weakref.finalize(self, _released, jitted, ran).atexit = False

    def __call__(self, *args):
        if self._avals is None:
            return self._first_call(args)
        if self._capture.profile_session is not None:
            self._ran.profiled = True
        return self._jitted(*args)

    def _first_call(self, args):
        import jax

        self._avals = self._ran.avals = jax.tree_util.tree_map(_abstract, args)
        if self._capture.profile_session is not None:
            self._ran.profiled = True
        with setup_tracer().span("setup.first_call", program=self.program):
            return self._jitted(*args)

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def compiled_text(self) -> str:
        if self._avals is None:
            raise RuntimeError(f"{self.program}: compiled_text() before the step's first call")
        return self._jitted.lower(*self._avals).compile().as_text()

    def scopes(self) -> dict:
        """obs/hlo.census of the executable that ran, read once and kept."""
        if self._ran.census is None:
            if self._avals is None:
                raise RuntimeError(f"{self.program}: scopes() before the step's first call")
            self._ran.census = _read_census(self._jitted, self._ran)
        return self._ran.census


def step_scopes_instant(step: ScopedStep) -> dict:
    """The `step_scopes` instant of cli/train_lm.py: the census in one
    record (`instructions`, those under more than one scope, the Mosaic
    calls; `phases` and `scopes` comma-joined), and what reading it cost."""
    t0 = time.perf_counter()
    census = step.scopes()
    rows = census["by_place"]
    return {
        "program": step.program,
        "instructions": sum(r["instructions"] for r in rows),
        "mixed_instructions": sum(r["mixed_instructions"] for r in rows),
        "mosaic_calls": sum(sum(r["kernels"].values()) for r in rows),
        "placed_bytes_pct": round(census["placed_bytes_pct"], 3),
        "phases": ",".join(census["phases"]),
        "scopes": ",".join(sorted({r["scope"] for r in rows if r["scope"]})),
        "census_s": round(time.perf_counter() - t0, 3),
    }


def write_step_scopes(directory: str, step) -> Optional[str]:
    """`<directory>/step_scopes.json` of a step that has run, for
    `tools/trace_report.py device`; None where the step keeps no census."""
    if not hasattr(step, "scopes"):
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "step_scopes.json")
    with open(path, "w") as f:
        json.dump(step.scopes(), f)
    return path
