"""The ONE reader of a compiled program's text: which scope and phase each
of its instructions belongs to.

`instruction_scopes(text)` takes `jitted.lower(...).compile().as_text()` and
returns, for every instruction that runs as an op of its own (the entry
computation's and those of `while` / `call` / `conditional` bodies, at any
depth; not the insides of a fusion or of an `async-start`), its place:
phase, scope path, the kind of work it does, whether it is `mixed`. A device
trace names its events by these instruction names, so the table joins a
capture to the program (`time_by_place`).

**Where a name comes from.** jax writes `op_name="jit(step)/jvp(mixer/kda)/
delta_rule/while/body/dot_general"` on every op. The scope path is what
remains of it in obs/scopes.SCOPES when the transforms' wrappers and every
segment outside the vocabulary are dropped (a segment extends the path only
where the longer path is in the vocabulary, so `while`, `checkpoint`,
`closed_call` or a primitive named like a scope cannot bend it). The phase:
`rematted_computation` anywhere in the name -> "remat"; else `transpose(` ->
"backward"; else `jvp(` -> "forward"; else the scope's own phase
(obs/scopes.PHASE_OF_SCOPE: the update, the gradients' reduction, the input
augmentation); else "other".

**A fusion holds instructions of several names: the rule.** A fusion goes to
the place of the instruction inside it that does most work: a product (`dot`,
`convolution`) or a custom call before a reduction (`reduce`, `reduce-window`,
`scatter`, `sort`, `select-and-scatter`) before anything else that computes
or moves data; among instructions of that rank, the place with most result
bytes; instructions that only rename data (parameter, constant, tuple, bitcast,
broadcast, iota, reshape) and those without a scope of the vocabulary do not
vote. If no instruction inside votes, the fusion's own `op_name` decides; an
instruction with no name at all (the compiler's own layout `copy`) takes a
neighbour's place (`_inherit`). The fusion is `mixed` when a voting instruction has another place, and lists
those places. XLA fuses Adam's elementwise update into the weight-gradient
product: such a fusion is the product's (backward, the layer's scope), mixed
with `update:update`.
"""

from __future__ import annotations

import io
import re
from fnmatch import fnmatchcase
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from .scopes import PATHS, PHASE_OF_SCOPE

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KERNEL = re.compile(r"ps_[a-z0-9_]+")
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"[A-Za-z_][\w.\-]*\(|\)")
# `  [ROOT ]%name = <type> opcode(` ; the type is one array or a tuple of them
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_ARRAY = re.compile(r"([a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn|fnuz)?)?)\[([0-9,]*)\]")
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")

_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
             "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
             "c64": 8, "c128": 16}
_PRODUCTS = ("dot", "convolution")
_REDUCTIONS = ("reduce", "reduce-window", "scatter", "sort", "select-and-scatter")
_RENAMES = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast", "broadcast",
            "iota", "reshape", "after-all", "partition-id", "replica-id")
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
                "collective-permute", "collective-broadcast")
# instructions that only hold other instructions' ops
CONTAINERS = ("while", "call", "conditional")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


class Place(NamedTuple):
    phase: str              # forward | backward | remat | update | input | other
    scope: str              # a path of obs/scopes.SCOPES with `*` filled in, or ""
    work: str               # "dot" | "kernel" | "collective" | "reduce" | "other" | "container"
    mixed: Tuple[str, ...]  # the other "phase:scope" a fusion holds; () for a pure one
    via: str = ""           # "operand" | "user": whose place a nameless instruction took


_PATTERNS = tuple(tuple(p.split("/")) for p in PATHS)
_paths: Dict[str, str] = {}


def _fits(segs: List[str], pattern: Tuple[str, ...]) -> bool:
    """`segs` is the start of `pattern` (a `*` stands for any characters)."""
    return len(segs) <= len(pattern) and all(fnmatchcase(s, p) for s, p in zip(segs, pattern))


def in_vocabulary(path: str) -> bool:
    segs = path.split("/")
    return any(len(p) == len(segs) and _fits(segs, p) for p in _PATTERNS)


def scope_path(op_name: str) -> str:
    """The vocabulary path inside an `op_name` ("" where none)."""
    if op_name in _paths:
        return _paths[op_name]
    flat = _WRAPPER.sub("", _JIT.sub("", op_name))
    segs: List[str] = []
    for seg in flat.split("/")[:-1]:           # the last segment is the primitive
        if seg and any(_fits(segs + [seg], p) for p in _PATTERNS):
            segs.append(seg)
    path = "/".join(segs)
    _paths[op_name] = out = path if in_vocabulary(path) else ""
    return out


def phase_of(op_name: str, scope: str) -> str:
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return PHASE_OF_SCOPE.get(scope.split("/", 1)[0], "other")


def _result_bytes(type_text: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(type_text):
        n = _ITEMSIZE.get(dtype, 1 if dtype.startswith("f8") else 0)
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n
    return total


def _split_type(rest: str) -> Tuple[str, str]:
    """`<type> opcode(...` -> (type, opcode): the type ends at the first
    space outside parentheses and braces."""
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch in "({"
        depth -= ch in ")}"
        if ch == " " and depth == 0:
            return rest[:i], rest[i + 1:].split("(", 1)[0].strip()
    return rest, ""


def _operands(rest: str, after: int) -> Tuple[str, ...]:
    """The `%names` between the opcode's parentheses."""
    start = rest.find("(", after)
    depth = 0
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            return tuple(_OPERAND.findall(rest[start:i]))
    return ()


class _Instr(NamedTuple):
    name: str
    opcode: str
    bytes: int
    op_name: str
    called: Tuple[str, ...]     # computations it runs as ops of their own
    fused: Optional[str]        # the computation a fusion (or an `async-*`) holds
    mosaic: bool
    operands: Tuple[str, ...]


def _computations(text: Iterable[str]):
    """{computation: [_Instr]}, the entry computation's name. An
    instruction's line outside any computation (a fragment of a program's
    text) is kept under the name ""."""
    comps: Dict[str, List[_Instr]] = {}
    entry, cur = None, None
    for line in text:
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
                continue
        elif line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        type_text, opcode = _split_type(m.group(2))
        op = _OP_NAME.search(line)
        called, fused = [], None
        if opcode == "fusion" or opcode.startswith("async-"):
            # `async-start` / `-update` / `-done` is how a program read back
            # from the compile cache spells `slice-start` / `slice-done`: it
            # holds the one op it wraps as a fusion holds its ops, and that
            # op runs as no op of its own
            c = _CALLS.search(line)
            fused = c.group(1) if c else None
        elif opcode in CONTAINERS:
            called = [name for key, name in _CALLED.findall(line) if key != "to_apply"
                      or opcode == "call"]
            b = _BRANCHES.search(line)
            if b:
                called += [s.strip().lstrip("%") for s in b.group(1).split(",")]
        (comps.setdefault("", []) if cur is None else cur).append(_Instr(
            m.group(1), opcode, _result_bytes(type_text),
            op.group(1) if op else "", tuple(called), fused,
            MOSAIC_TARGET in line, _operands(m.group(2), len(type_text))))
    return comps, entry


def _rank(opcode: str) -> int:
    if opcode in _PRODUCTS or opcode == "custom-call":
        return 3
    if opcode in _REDUCTIONS:
        return 2
    return 0 if opcode in _RENAMES else 1


def _work(opcode: str) -> str:
    if opcode in _PRODUCTS:
        return "dot"
    if opcode == "custom-call":
        return "kernel"
    if opcode.startswith(_COLLECTIVES):
        return "collective"
    if opcode in _REDUCTIONS:
        return "reduce"
    return "container" if opcode in CONTAINERS else "other"


def _own_place(ins: _Instr) -> Place:
    scope = scope_path(ins.op_name)
    return Place(phase_of(ins.op_name, scope), scope, _work(ins.opcode), ())


def _inside(comps, name: str) -> List[_Instr]:
    """Every instruction a fusion holds, through nested fusions."""
    out: List[_Instr] = []
    for ins in comps.get(name, ()):
        if ins.fused and ins.fused != name:
            out += _inside(comps, ins.fused)
        else:
            out.append(ins)
    return out


def _fusion_place(comps, ins: _Instr) -> Place:
    votes: Dict[Tuple[int, str, str], int] = {}
    top_work = "other"
    top = 0
    for inner in _inside(comps, ins.fused):
        rank = _rank(inner.opcode)
        if rank > top:
            top, top_work = rank, _work(inner.opcode)
        scope = scope_path(inner.op_name)
        if not rank or not scope:
            continue
        key = (rank, phase_of(inner.op_name, scope), scope)
        votes[key] = votes.get(key, 0) + inner.bytes
    if not votes:
        own = _own_place(ins)
        return own._replace(work=top_work)
    best = max(r for r, _, _ in votes)
    (_, phase, scope), _ = max(((k, b) for k, b in votes.items() if k[0] == best),
                               key=lambda kb: (kb[1], kb[0]))
    others = sorted({f"{p}:{s}" for _, p, s in votes} - {f"{phase}:{scope}"})
    return Place(phase, scope, top_work, tuple(others))


def _table(comps, entry) -> Dict[str, Place]:
    table: Dict[str, Place] = {}
    todo, done = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in done or comp not in comps:
            continue
        done.add(comp)
        for ins in comps[comp]:
            if ins.opcode in ("parameter", "constant", "tuple", "get-tuple-element", "bitcast"):
                continue
            table[ins.name] = _fusion_place(comps, ins) if ins.fused else _own_place(ins)
            todo += ins.called
        _inherit(comps[comp], table)
    return table


def _inherit(body: List[_Instr], table: Dict[str, Place]) -> None:
    """An instruction the compiler made carries no name (a layout `copy`, a
    broadcast of zeros, the tuple a loop is handed). It takes the place of
    the nearest named instruction that reads it (a relayout is made for its
    reader), else of the nearest it reads from, each found through other
    nameless ones."""
    users: Dict[str, List[str]] = {}
    for ins in body:
        for op in ins.operands:
            users.setdefault(op, []).append(ins.name)
    # a bitcast or a tuple element is no op of its own, but its name counts
    near = {ins.name: (place, "") for ins in body
            for place in [table.get(ins.name) or _own_place(ins)] if place.scope}

    def spread(order, neighbours, via):
        for ins in order:
            if ins.name not in near:
                found = next((near[n][0] for n in neighbours(ins) if n in near), None)
                if found is not None:
                    near[ins.name] = (found, via)

    spread(body[::-1], lambda ins: users.get(ins.name, ()), "user")
    spread(body, lambda ins: ins.operands, "operand")
    for name, (found, via) in near.items():
        if via and name in table and table[name].work != "container":
            table[name] = Place(found.phase, found.scope, table[name].work, (), via)


def instruction_scopes(compiled_text: str) -> Dict[str, Place]:
    """{instruction name: Place} for every instruction that runs as an op
    of its own; see the module docstring for the rules."""
    return _table(*_computations(io.StringIO(compiled_text)))


def _kernels(comps) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {"mosaic": {}, "jnp": {}}
    for body in comps.values():
        for ins in body:
            names = _KERNEL.findall(ins.op_name)
            if not names:
                continue
            name = names[-1]
            if ins.mosaic:
                out["mosaic"][name] = out["mosaic"].get(name, 0) + 1
            elif name.endswith("_jnp"):
                name = name[: -len("_jnp")]
                out["jnp"][name] = out["jnp"].get(name, 0) + 1
    return out


def kernel_census(hlo_text: str) -> Dict[str, Dict[str, int]]:
    """{"mosaic": {kernel: calls}, "jnp": {kernel: ops}}: which path each
    Pallas entry took in a compiled program (ops/pallas_mode.kernel_census
    is this function). Every `pl.pallas_call` in ops/ carries a
    `name="ps_<kernel>"` and every jnp twin runs under
    `jax.named_scope("ps_<kernel>_jnp")`; both survive into the optimized
    HLO's `op_name`. A kernel that ran in interpret mode is in neither: it
    lowered to plain HLO with no scope of its own."""
    return _kernels(_computations(io.StringIO(hlo_text))[0])


def census(compiled_text: str) -> dict:
    """What `ScopedStep.scopes()` returns: `instructions` {name: [phase,
    scope, work, [mixed...], via]}; `by_place`, one row a (phase, scope) with its
    instructions, result bytes and Mosaic kernels by name; `kernels` (the
    kernel census); `placed_bytes_pct`, of the result bytes of
    instructions that take device time (`_takes_time`), the share whose
    instruction has a scope and a phase other than "other"; `phases`."""
    comps, entry = _computations(io.StringIO(compiled_text))
    table = _table(comps, entry)
    by_name = {ins.name: ins for body in comps.values() for ins in body}
    rows: Dict[Tuple[str, str], dict] = {}
    total = placed = 0
    for name, place in table.items():
        ins = by_name[name]
        if place.work == "container":
            continue
        row = rows.setdefault((place.phase, place.scope), {
            "phase": place.phase, "scope": place.scope, "instructions": 0,
            "result_bytes": 0, "mixed_instructions": 0, "kernels": {}})
        row["instructions"] += 1
        row["result_bytes"] += ins.bytes
        row["mixed_instructions"] += bool(place.mixed)
        if ins.mosaic:
            kernel = (_KERNEL.findall(ins.op_name) or [ins.name])[-1]
            row["kernels"][kernel] = row["kernels"].get(kernel, 0) + 1
        if _takes_time(ins):
            total += ins.bytes
            placed += ins.bytes if is_placed(place) else 0
    return {
        "instructions": {n: [p.phase, p.scope, p.work, list(p.mixed), p.via]
                         for n, p in table.items()},
        "by_place": sorted(rows.values(), key=lambda r: -r["result_bytes"]),
        "kernels": _kernels(comps),
        "phases": sorted({p.phase for p in table.values() if p.work != "container"}),
        "placed_bytes_pct": 100.0 * placed / total if total else 0.0,
    }


def _takes_time(ins: _Instr) -> bool:
    """Not the halves of an asynchronous copy, a custom call that only
    renames (`ConcatBitcast`), or an instruction that moves nothing."""
    if ins.opcode.endswith(("-start", "-done")) and not ins.opcode.startswith(_COLLECTIVES):
        return False
    if ins.opcode == "custom-call" and not ins.mosaic:
        return False
    return _rank(ins.opcode) > 0


def is_placed(place) -> bool:
    """A scope of the vocabulary and a phase: what is not `unplaced`."""
    return bool(place[1]) and place[0] != "other"


# ------------------------------------------------------ joining a capture

_HLO_LINE = re.compile(r"^%?([\w.\-]+) = ")


def hlo_line_name(event_name: str) -> Optional[str]:
    """The instruction's name where a device event is named by its whole
    HLO line (`%fusion.5 = f32[...] fusion(...)`: the v5e's profiler), else
    None."""
    m = _HLO_LINE.match(event_name)
    return m.group(1) if m else None


def instruction_of(event_name: str, table) -> Optional[str]:
    """The instruction a device event ran. The v5e's profiler names an
    event by its whole HLO line (`%fusion.5 = f32[...] fusion(...)`); the
    benchmark's traces keep `<instruction>_<dtype>_<dims>`
    (reducers/trace.short_name), so the longest prefix that ends before a
    `_` and names an instruction of the table is taken."""
    whole = hlo_line_name(event_name)
    if whole is not None:
        return whole if whole in table else None
    name = event_name
    while name:
        if name in table:
            return name
        name, sep, _ = name.rpartition("_")
        if not sep:
            return None
    return None


def time_by_place(table, events) -> dict:
    """Device time by place. `table`: {instruction: (phase, scope, work,
    mixed, via)}; `events`: (name, seconds) of one device's ops, containers
    already left out. Returns seconds: `by_place` {(phase, scope, work):
    s}, `mixed` {("phase:scope", other "phase:scope"): s} (a mixed fusion's
    whole time under each pair it holds), `unfound` s and the names behind
    it, `inherited` s (nameless instructions placed by a neighbour),
    `mixed_total` s (in mixed fusions, each once), `total` s."""
    by_place: Dict[Tuple[str, str, str], float] = {}
    mixed: Dict[Tuple[str, str], float] = {}
    unfound_names: Dict[str, float] = {}
    found_as: Dict[str, Optional[str]] = {}
    total = unfound = inherited = mixed_total = 0.0
    for name, dur in events:
        if name not in found_as:
            found_as[name] = instruction_of(name, table)
        ins = found_as[name]
        total += dur
        if ins is None:
            unfound += dur
            unfound_names[name] = unfound_names.get(name, 0.0) + dur
            continue
        phase, scope, work, others, via = table[ins]
        key = (phase, scope, work)
        by_place[key] = by_place.get(key, 0.0) + dur
        if via:
            inherited += dur
        if others:
            mixed_total += dur
        for other in others:
            mixed[(f"{phase}:{scope}", other)] = mixed.get((f"{phase}:{scope}", other), 0.0) + dur
    return {"by_place": by_place, "mixed": mixed, "unfound": unfound,
            "unfound_names": unfound_names, "inherited": inherited,
            "mixed_total": mixed_total, "total": total}
