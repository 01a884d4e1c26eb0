"""The pslint rules (PSL001-PSL008). Pure-AST: no jax import, no code
execution. PSL006-PSL008 (SPMD-divergence taint analysis) live in
diverge.py and are registered here.

Each rule is a class with `rule_id` and `check(tree, path, axes, donors)`
yielding (lineno, col, message) tuples. Shared helpers keep name
resolution (attribute-chain tails) consistent across rules.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

Finding3 = Tuple[int, int, str]


def _tail(func: ast.expr) -> Optional[str]:
    """`jax.lax.psum` -> 'psum', `psum` -> 'psum', else None."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dotted(node: ast.expr) -> str:
    """Best-effort dotted name: `np.random.uniform` -> 'np.random.uniform'."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _str_constants(node: ast.expr) -> Iterator[ast.Constant]:
    """String constants in `node`, descending through tuples/lists."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        yield node
    elif isinstance(node, (ast.Tuple, ast.List)):
        for elt in node.elts:
            yield from _str_constants(elt)


def _donate_argnums(call: ast.Call) -> Tuple[int, ...]:
    """Donated positions of a ``jit(..., donate_argnums=...)`` call. The
    repo idiom is ``donate_argnums=(0, 1) if donate else ()`` — the
    enabled (IfExp body) branch is what callers get unless they opt out."""
    for k in call.keywords:
        if k.arg == "donate_argnums":
            v = k.value
            if isinstance(v, ast.IfExp):
                v = v.body
            return _const_int_tuple(v)
    return ()


def _compound_bodies(stmt: ast.stmt) -> List[List[ast.stmt]]:
    """All statement blocks of a compound statement: body, orelse,
    finalbody, and every except-handler body."""
    bodies: List[List[ast.stmt]] = [stmt.body]
    for attr in ("orelse", "finalbody"):
        extra = getattr(stmt, attr, None)
        if extra:
            bodies.append(extra)
    for h in getattr(stmt, "handlers", []) or []:
        bodies.append(h.body)
    return bodies


def _arg(call: ast.Call, pos: int, kw: str) -> Optional[ast.expr]:
    if len(call.args) > pos and not any(
        isinstance(a, ast.Starred) for a in call.args[: pos + 1]
    ):
        return call.args[pos]
    for k in call.keywords:
        if k.arg == kw:
            return k.value
    return None


# ------------------------------------------------------------------- PSL001

class MeshAxisRule:
    """String-literal axis names must match a declared ``*_AXIS`` constant
    — and should *be* the constant, so a rename in parallel/mesh.py can't
    silently orphan a collective (psum over a nonexistent axis fails at
    trace time at best, at run time on a different mesh at worst)."""

    rule_id = "PSL001"

    # func tail -> (positional index, keyword) of the axis-name argument
    AXIS_CALLS: Dict[str, Tuple[int, str]] = {
        "psum": (1, "axis_name"),
        "pmean": (1, "axis_name"),
        "pmax": (1, "axis_name"),
        "pmin": (1, "axis_name"),
        "ppermute": (1, "axis_name"),
        "pshuffle": (1, "axis_name"),
        "all_gather": (1, "axis_name"),
        "all_to_all": (1, "axis_name"),
        "psum_scatter": (1, "axis_name"),
        "axis_index": (0, "axis_name"),
        "axis_size": (0, "axis_name"),
    }
    SPEC_CALLS = {"PartitionSpec", "P"}

    def check(self, tree: ast.AST, path: str, axes: Dict[str, str],
              donors: Dict[str, Tuple[int, ...]]) -> Iterable[Finding3]:
        known = ", ".join(sorted(axes))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _tail(node.func)
            literals: List[ast.Constant] = []
            if tail in self.AXIS_CALLS:
                pos, kw = self.AXIS_CALLS[tail]
                target = _arg(node, pos, kw)
                if target is not None:
                    literals.extend(_str_constants(target))
            elif tail in self.SPEC_CALLS:
                for a in list(node.args) + [k.value for k in node.keywords]:
                    literals.extend(_str_constants(a))
            elif tail == "Mesh":
                target = _arg(node, 1, "axis_names")
                if target is not None:
                    literals.extend(_str_constants(target))
            for lit in literals:
                name = lit.value
                if name in axes:
                    yield (
                        lit.lineno,
                        lit.col_offset,
                        f"axis literal '{name}' — use the {axes[name]} "
                        f"constant from ps_pytorch_tpu.parallel",
                    )
                else:
                    yield (
                        lit.lineno,
                        lit.col_offset,
                        f"unknown mesh axis '{name}' (declared axes: {known})",
                    )


# ------------------------------------------------------------------- PSL002

class RecompilationRule:
    """jit must be built once, outside the hot path. Flags jax.jit/pjit
    (a) called inside a per-iteration context — for/while loop bodies,
    while-tests, comprehension/generator element expressions (a for's
    iterable and else-bodies run once and are exempt), (b) applied to a
    lambda (a fresh callable per call never hits the jit cache), (c)
    compiled and immediately invoked inside such a context
    (``jax.jit(f)(x)`` per iteration discards the executable and
    recompiles; outside one, a single build + call is not a hazard)."""

    rule_id = "PSL002"

    JIT_TAILS = {"jit", "pjit"}

    _COMPREHENSIONS = (ast.ListComp, ast.SetComp, ast.DictComp,
                       ast.GeneratorExp)

    def check(self, tree: ast.AST, path: str, axes: Dict[str, str],
              donors: Dict[str, Tuple[int, ...]]) -> Iterable[Finding3]:
        yield from self._visit(tree, depth=0)

    def _is_jit_call(self, node: ast.AST) -> bool:
        return (
            isinstance(node, ast.Call)
            and _tail(node.func) in self.JIT_TAILS
            # plain `partial(...)`/`functools.partial(jax.jit, ...)` etc.
            # never reach here: tail must literally be jit/pjit
        )

    def _check_node(self, node: ast.AST, depth: int) -> Iterator[Finding3]:
        if self._is_jit_call(node):
            assert isinstance(node, ast.Call)
            if depth > 0:
                yield (
                    node.lineno,
                    node.col_offset,
                    "jax.jit called inside a loop — build the jitted "
                    "function once outside the hot path (each call "
                    "compiles into a fresh, unshared cache)",
                )
            if node.args and isinstance(node.args[0], ast.Lambda):
                yield (
                    node.lineno,
                    node.col_offset,
                    "jax.jit on a lambda — a fresh callable never hits "
                    "the jit cache across builders; hoist the body to a "
                    "module-level def (cache the jitted result if built "
                    "per-config)",
                )
        if (
            isinstance(node, ast.Call)
            and self._is_jit_call(node.func)
            # only certain-recompile sites: outside a loop, compiling
            # once and calling once is not a hazard (and binding the
            # callable first would change nothing)
            and depth > 0
        ):
            yield (
                node.lineno,
                node.col_offset,
                "jit(...)(...) inside a loop compiles a fresh "
                "executable every iteration and discards it — build "
                "the jitted function once, outside the loop",
            )

    def _visit(self, node: ast.AST, depth: int) -> Iterator[Finding3]:
        """Depth tracks how many per-iteration contexts enclose `node`.
        Loop HEADERS that run once (a for's iterable, else-bodies) stay at
        the enclosing depth; while-tests, loop bodies, and comprehension
        element/condition expressions are per-iteration."""
        yield from self._check_node(node, depth)
        if isinstance(node, (ast.For, ast.AsyncFor)):
            yield from self._visit(node.target, depth)
            yield from self._visit(node.iter, depth)  # evaluated once
            for n in node.body:
                yield from self._visit(n, depth + 1)
            for n in node.orelse:
                yield from self._visit(n, depth)  # else: runs once
        elif isinstance(node, ast.While):
            yield from self._visit(node.test, depth + 1)  # per iteration
            for n in node.body:
                yield from self._visit(n, depth + 1)
            for n in node.orelse:
                yield from self._visit(n, depth)
        elif isinstance(node, self._COMPREHENSIONS):
            first = node.generators[0]
            yield from self._visit(first.iter, depth)  # evaluated once
            for gen in node.generators:
                yield from self._visit(gen.target, depth + 1)
                for cond in gen.ifs:
                    yield from self._visit(cond, depth + 1)
            for gen in node.generators[1:]:
                yield from self._visit(gen.iter, depth + 1)
            if isinstance(node, ast.DictComp):
                yield from self._visit(node.key, depth + 1)
                yield from self._visit(node.value, depth + 1)
            else:
                yield from self._visit(node.elt, depth + 1)
        else:
            for child in ast.iter_child_nodes(node):
                yield from self._visit(child, depth)


# ------------------------------------------------------------------- PSL003

class TracedPurityRule:
    """Side effects inside traced functions run once at trace time (or
    never again after a cache hit): prints vanish, wall-clock reads freeze,
    np.random draws become compile-time constants, and mutation of closure
    or global state desyncs across retraces. Traced = decorated with
    jit/pjit, or passed (by name or as a lambda) to jit / shard_map /
    vmap / pmap / grad / value_and_grad / checkpoint / remat / scan /
    while_loop / fori_loop — including every nested def inside one."""

    rule_id = "PSL003"

    TRACERS = {
        "jit", "pjit", "shard_map", "vmap", "pmap", "grad",
        "value_and_grad", "checkpoint", "remat", "scan", "while_loop",
        "fori_loop", "custom_vjp", "custom_jvp", "pallas_call",
    }
    CLOCK_CALLS = {
        "time.time", "time.perf_counter", "time.monotonic",
        "time.process_time", "datetime.datetime.now", "datetime.now",
    }
    MUTATORS = {"append", "extend", "insert", "pop", "remove", "clear",
                "update", "setdefault"}

    def check(self, tree: ast.AST, path: str, axes: Dict[str, str],
              donors: Dict[str, Tuple[int, ...]]) -> Iterable[Finding3]:
        defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)

        traced: List[ast.AST] = []
        seen: Set[int] = set()

        def mark(fn: ast.AST) -> None:
            if id(fn) not in seen:
                seen.add(id(fn))
                traced.append(fn)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    if _tail(d) in ("jit", "pjit"):
                        mark(node)
                    elif (
                        isinstance(dec, ast.Call)
                        and _tail(dec.func) == "partial"
                        and dec.args
                        and _tail(dec.args[0]) in ("jit", "pjit")
                    ):
                        mark(node)
            elif isinstance(node, ast.Call) and _tail(node.func) in self.TRACERS:
                for a in node.args:
                    # unwrap functools.partial(fn, ...): fn is what traces
                    if isinstance(a, ast.Call) and _tail(a.func) == "partial" and a.args:
                        a = a.args[0]
                    if isinstance(a, ast.Lambda):
                        mark(a)
                    elif isinstance(a, ast.Name):
                        for fn in defs.get(a.id, ()):
                            mark(fn)

        for fn in traced:
            yield from self._check_traced(fn)

    def _check_traced(self, fn: ast.AST) -> Iterator[Finding3]:
        local: Set[str] = set()
        args = fn.args if hasattr(fn, "args") else None
        if args is not None:
            for a in (
                list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
                + ([args.vararg] if args.vararg else [])
                + ([args.kwarg] if args.kwarg else [])
            ):
                local.add(a.arg)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
                local.add(node.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local.add(node.name)
                for a in (
                    list(node.args.posonlyargs) + list(node.args.args)
                    + list(node.args.kwonlyargs)
                    + ([node.args.vararg] if node.args.vararg else [])
                    + ([node.args.kwarg] if node.args.kwarg else [])
                ):
                    local.add(a.arg)

        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                yield (
                    node.lineno, node.col_offset,
                    "`global` inside a traced function — mutation happens "
                    "at trace time only and is frozen into the compiled "
                    "program",
                )
            elif isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                tail = _tail(node.func)
                if tail == "print" and isinstance(node.func, ast.Name):
                    yield (
                        node.lineno, node.col_offset,
                        "print() inside a traced function runs once at "
                        "trace time and shows tracers, not values — use "
                        "jax.debug.print",
                    )
                elif dotted in self.CLOCK_CALLS:
                    yield (
                        node.lineno, node.col_offset,
                        f"{dotted}() inside a traced function freezes to "
                        "the trace-time value — time on the host, around "
                        "the jitted call",
                    )
                elif re.match(r"^(np|numpy)\.random\.", dotted):
                    yield (
                        node.lineno, node.col_offset,
                        f"{dotted}() inside a traced function is drawn "
                        "once at trace time and baked into the program — "
                        "use jax.random with an explicit key",
                    )
            elif isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
                # bare mutator statement (`lst.append(x)`): result unused,
                # so the call exists only for its side effect — which under
                # trace happens once. A captured result (`a, b = tx.update(
                # ...)`) is a pure functional call and is NOT flagged.
                call = node.value
                if (
                    isinstance(call.func, ast.Attribute)
                    and call.func.attr in self.MUTATORS
                    and isinstance(call.func.value, ast.Name)
                    and call.func.value.id not in local
                ):
                    yield (
                        call.lineno, call.col_offset,
                        f"mutation of closure/global '{call.func.value.id}."
                        f"{call.func.attr}()' inside a traced function — "
                        "runs at trace time only; return the value instead",
                    )
            elif (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)
                and isinstance(node.targets[0].value, ast.Name)
                and node.targets[0].value.id not in local
            ):
                yield (
                    node.lineno, node.col_offset,
                    f"subscript-assignment to closure/global "
                    f"'{node.targets[0].value.id}' inside a traced function "
                    "— runs at trace time only",
                )


# ------------------------------------------------------------------- PSL004

class HostSyncRule:
    """Hot-path loop bodies in the training driver must not synchronously
    pull device values to the host every step: `.item()`, `float(metrics)`,
    `np.asarray(device)`, and `jax.device_get` all block dispatch and
    serialize the pipeline. Periodic, intentional transfers carry a
    ``# psl: sync-ok`` pragma. Scope: modules named in HOT_MODULES —
    the training driver AND the serving request loop (serve/engine.py),
    where a stray per-token fetch beyond the scheduler's one fused
    [slots] read would serialize every decode tick."""

    rule_id = "PSL004"

    # entries with a "/" match as path suffixes (pinning the rule to THE
    # serve engine, not any future module that happens to be named
    # engine.py); bare names match by basename
    HOT_MODULES = {"trainer.py", "serve/engine.py"}
    # directory trees where EVERY function is a hot-path loop body by
    # contract, scanned in STRICT mode: the observability layer runs
    # inside the training/serving tick, so any host sync it introduces
    # perturbs the run it measures. Strict mode additionally flags
    # jax.block_until_ready — elsewhere the blessed barrier primitive,
    # here a new sync the traced run would not otherwise have (the one
    # deliberate profiler-stop barrier carries `# psl: sync-ok`).
    HOT_TREES = ("ps_pytorch_tpu/obs/",)
    STEP_CALL_RE = re.compile(r"(^|[._])(train_|eval_)?step(_fn)?$")
    # a per-step entry point (the serving engine's tick()) IS a loop
    # body by contract — its caller invokes it once per decode step —
    # so its top level is scanned at loop depth 1 even though the
    # enclosing `while` lives in another function
    HOT_FN_RE = re.compile(r"^_?tick\w*$")

    _COMPOUND = (ast.For, ast.AsyncFor, ast.While, ast.If, ast.With,
                 ast.AsyncWith, ast.Try)

    def _is_hot(self, path: str) -> bool:
        norm = path.replace(os.sep, "/")
        for mod in self.HOT_MODULES:
            if "/" in mod:
                if norm == mod or norm.endswith("/" + mod):
                    return True
            elif os.path.basename(path) == mod:
                return True
        return False

    def _in_hot_tree(self, path: str) -> bool:
        norm = "/" + path.replace(os.sep, "/")
        return any("/" + tree in norm for tree in self.HOT_TREES)

    def check(self, tree: ast.AST, path: str, axes: Dict[str, str],
              donors: Dict[str, Tuple[int, ...]]) -> Iterable[Finding3]:
        strict = self._in_hot_tree(path)
        if not strict and not self._is_hot(path):
            return
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # flow-sensitive: taint follows statement order, so a
                # periodic `metrics = jax.device_get(metrics)` inside a
                # log window untaints only from that point on — per-step
                # syncs on the same name BEFORE the fetch still flag
                depth0 = (
                    1 if strict or self.HOT_FN_RE.match(node.name) else 0
                )
                yield from self._scan_block(
                    node.body, tainted=set(), loop_depth=depth0,
                    flagged=set(), strict=strict,
                )

    def _flag_stmt(
        self, stmt: ast.stmt, tainted: Set[str], loop_depth: int,
        flagged: Set[int], strict: bool = False,
    ) -> Iterator[Finding3]:
        if loop_depth == 0:
            return

        def refs_tainted(expr: ast.expr) -> bool:
            return any(
                isinstance(n, ast.Name) and n.id in tainted
                for n in ast.walk(expr)
            )

        for n in ast.walk(stmt):
            if not isinstance(n, ast.Call) or id(n) in flagged:
                continue
            tail = _tail(n.func)
            msg = None
            if tail == "device_get":
                msg = (
                    "jax.device_get in a hot-path loop blocks dispatch "
                    "every step — batch transfers behind a periodic "
                    "window or mark intentional ones `# psl: sync-ok`"
                )
            elif tail == "item" and isinstance(n.func, ast.Attribute):
                msg = (
                    ".item() in a hot-path loop forces a device->host "
                    "sync every step"
                )
            elif (
                tail in ("float", "int")
                and isinstance(n.func, ast.Name)
                and n.args
                and refs_tainted(n.args[0])
            ):
                msg = (
                    f"{tail}() on a device value in a hot-path loop "
                    "forces a sync every step — fetch metrics "
                    "periodically instead"
                )
            elif (
                tail == "asarray"
                and _dotted(n.func) in ("np.asarray", "numpy.asarray")
                and n.args
                and refs_tainted(n.args[0])
            ):
                msg = (
                    "np.asarray on a device value in a hot-path loop "
                    "copies to host synchronously every step"
                )
            elif strict and tail == "block_until_ready":
                msg = (
                    "block_until_ready in observability code adds a host "
                    "sync the traced run would not otherwise pay — the "
                    "tracer must reuse the driver's existing per-window "
                    "sync points (a deliberate once-per-capture profiler "
                    "barrier may carry `# psl: sync-ok`)"
                )
            if msg is not None:
                flagged.add(id(n))
                yield (n.lineno, n.col_offset, msg)

    def _apply_taint(self, stmt: ast.stmt, tainted: Set[str]) -> None:
        if not isinstance(stmt, ast.Assign):
            return
        calls = [n for n in ast.walk(stmt.value) if isinstance(n, ast.Call)]
        from_step = any(
            self.STEP_CALL_RE.search(_dotted(c.func) or "") for c in calls
        )
        fetched = any(
            _tail(c.func) in ("device_get", "block_until_ready")
            for c in calls
        )
        names = {
            t.id
            for tgt in stmt.targets
            for t in ast.walk(tgt)
            if isinstance(t, ast.Name) and isinstance(t.ctx, ast.Store)
        }
        if from_step and not fetched:
            tainted |= names
        else:
            # any other rebinding (a host fetch, a fresh dict, ...) kills
            # the taint from this point in the flow on
            tainted -= names

    def _scan_block(
        self, stmts: List[ast.stmt], tainted: Set[str], loop_depth: int,
        flagged: Set[int], strict: bool = False,
    ) -> Iterator[Finding3]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, self._COMPOUND):
                for attr in ("test", "iter"):
                    header = getattr(stmt, attr, None)
                    if header is not None:
                        # a while-test re-runs every iteration, so it sits
                        # INSIDE its own loop; a for's iterable (and an
                        # if-test) evaluates at the enclosing depth
                        header_depth = (
                            loop_depth + 1
                            if isinstance(stmt, ast.While) and attr == "test"
                            else loop_depth
                        )
                        yield from self._flag_stmt(
                            ast.Expr(value=header), tainted, header_depth,
                            flagged, strict,
                        )
                bodies = _compound_bodies(stmt)
                if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                    # two passes: a step call at the top of the loop body
                    # re-taints names a window fetch untainted at the
                    # bottom, matching the next iteration's flow
                    for _ in range(2):
                        for b in bodies:
                            yield from self._scan_block(
                                b, tainted, loop_depth + 1, flagged, strict
                            )
                    if isinstance(stmt, ast.While):
                        # back-edge: the test re-runs with the body's taint
                        yield from self._flag_stmt(
                            ast.Expr(value=stmt.test), tainted,
                            loop_depth + 1, flagged, strict,
                        )
                else:
                    for b in bodies:
                        yield from self._scan_block(
                            b, tainted, loop_depth, flagged, strict
                        )
            else:
                yield from self._flag_stmt(
                    stmt, tainted, loop_depth, flagged, strict
                )
                self._apply_taint(stmt, tainted)


# ------------------------------------------------------------------- PSL005

def collect_donor_factories(tree: ast.AST) -> Dict[str, Tuple[int, ...]]:
    """Functions that return ``jax.jit(..., donate_argnums=...)``, bare or
    as an argument of a wrapper (obs/scopes.ScopedStep): their
    name -> the donated positions. The repo idiom is
    ``return jax.jit(mapped, donate_argnums=(0, 1) if donate else ())`` —
    the enabled branch of the conditional is what callers get unless they
    pass ``donate=False``."""
    out: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for ret in ast.walk(node):
            if not (isinstance(ret, ast.Return) and isinstance(ret.value, ast.Call)):
                continue
            # the jit call itself, or one handed to a wrapper in the
            # return (`return ScopedStep(name, jax.jit(...))`)
            for call in [ret.value] + [a for a in ret.value.args if isinstance(a, ast.Call)]:
                if _tail(call.func) not in ("jit", "pjit"):
                    continue
                nums = _donate_argnums(call)
                if nums:
                    out[node.name] = nums
    return out


def _const_int_tuple(node: ast.expr) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals: List[int] = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                vals.append(e.value)
            else:
                return ()
        return tuple(vals)
    return ()


class DonationReuseRule:
    """`donate_argnums` hands the input buffer to XLA: on TPU, reading the
    python variable afterwards touches deallocated memory (CPU only warns
    — which is why this passes in tests and dies on the pod). Flags reads
    of a variable after it was passed in a donated position without being
    rebound. Donor step functions are discovered from the linted sources
    (any factory returning jit(..., donate_argnums=...)); call sites that
    pass ``donate=False`` to the factory are exempt."""

    rule_id = "PSL005"

    _COMPOUND = (ast.For, ast.AsyncFor, ast.While, ast.If, ast.With,
                 ast.AsyncWith, ast.Try)

    def check(self, tree: ast.AST, path: str, axes: Dict[str, str],
              donors: Dict[str, Tuple[int, ...]]) -> Iterable[Finding3]:
        # step vars assigned anywhere in the module (module level or any
        # function) are visible to nested scopes via closures — collect a
        # module-wide seed so `def run(...): es_step(...)` is still checked
        seed: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                self._register(node, node.value, donors, seed)
        scopes: List[List[ast.stmt]] = [tree.body] + [
            n.body for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for body in scopes:
            yield from self._scan_block(body, donors, dict(seed), {})

    def _register(
        self,
        stmt: ast.Assign,
        call: ast.Call,
        donors: Dict[str, Tuple[int, ...]],
        step_vars: Dict[str, Tuple[int, ...]],
    ) -> None:
        """`x = <factory>(...)` or `x = jax.jit(..., donate_argnums=...)`."""
        tail = _tail(call.func)
        targets = [t for t in stmt.targets if isinstance(t, ast.Name)]
        if not targets:
            return
        if tail in donors:
            opted_out = any(
                k.arg == "donate"
                and isinstance(k.value, ast.Constant)
                and k.value.value is False
                for k in call.keywords
            )
            for tgt in targets:
                if opted_out:
                    step_vars.pop(tgt.id, None)
                else:
                    step_vars[tgt.id] = donors[tail]
        elif tail in ("jit", "pjit"):
            nums = _donate_argnums(call)
            if nums:
                for tgt in targets:
                    step_vars[tgt.id] = nums

    def _process_exprs(
        self,
        nodes: List[ast.AST],
        step_vars: Dict[str, Tuple[int, ...]],
        consumed: Dict[str, str],
    ) -> Iterator[Finding3]:
        """Steps for one simple statement (or a compound header): report
        loads of consumed names, then apply this statement's donations.

        Lambda bodies are excluded: their execution is deferred and their
        parameters shadow enclosing names, so neither their loads nor the
        step calls inside them happen at this statement."""

        def walk_no_lambda(node: ast.AST) -> Iterator[ast.AST]:
            if isinstance(node, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                return
            yield node
            for child in ast.iter_child_nodes(node):
                yield from walk_no_lambda(child)

        walked = [n for node in nodes for n in walk_no_lambda(node)]
        for n in walked:
            if (
                isinstance(n, ast.Name)
                and isinstance(n.ctx, ast.Load)
                and n.id in consumed
            ):
                yield (
                    n.lineno, n.col_offset,
                    f"'{n.id}' read after being donated to "
                    f"{consumed[n.id]} — the buffer is invalid on TPU "
                    "(CPU only warns); rebind the result or build the "
                    "step with donate=False",
                )
                consumed.pop(n.id, None)  # one report per donation
        for n in walked:
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id in step_vars
            ):
                for pos in step_vars[n.func.id]:
                    if pos < len(n.args) and isinstance(n.args[pos], ast.Name):
                        consumed[n.args[pos].id] = f"'{n.func.id}'"
        for n in walked:
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                consumed.pop(n.id, None)

    def _scan_block(
        self,
        stmts: List[ast.stmt],
        donors: Dict[str, Tuple[int, ...]],
        step_vars: Dict[str, Tuple[int, ...]],
        consumed: Dict[str, str],
    ) -> Iterator[Finding3]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested scopes are scanned separately
            if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
                self._register(stmt, stmt.value, donors, step_vars)
            if isinstance(stmt, self._COMPOUND):
                headers: List[ast.AST] = []
                for attr in ("test", "iter", "target"):
                    v = getattr(stmt, attr, None)
                    if v is not None:
                        headers.append(v)
                for item in getattr(stmt, "items", []) or []:
                    headers.append(item.context_expr)
                    if item.optional_vars is not None:
                        headers.append(item.optional_vars)
                yield from self._process_exprs(headers, step_vars, consumed)
                bodies = _compound_bodies(stmt)
                if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                    # two passes: a donation at the bottom of the loop body
                    # reaches a load at the top on the next iteration
                    shadow = dict(consumed)
                    for b in bodies:
                        for _ in self._scan_block(b, donors, step_vars, shadow):
                            pass
                    consumed.update(shadow)
                for b in bodies:
                    yield from self._scan_block(b, donors, step_vars, consumed)
            else:
                yield from self._process_exprs([stmt], step_vars, consumed)


# Imported at the bottom so diverge.py can reuse this module's helpers
# (STEP_CALL_RE, _dotted, _tail) without a circular import at load time.
from .diverge import (  # noqa: E402
    DivergentGuardRule,
    DivergentOrderRule,
    DivergentTracedRule,
)

RULES = [
    MeshAxisRule(),
    RecompilationRule(),
    TracedPurityRule(),
    HostSyncRule(),
    DonationReuseRule(),
    DivergentGuardRule(),
    DivergentTracedRule(),
    DivergentOrderRule(),
]
RULE_IDS = tuple(r.rule_id for r in RULES)
