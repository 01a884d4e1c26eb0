"""The program's own host spans, as the reducers that read them see them:
`ev["spans"]`, each record with `t_abs`, its start on the host clock that
`ev["window_t0"]` was read on. A span opened inside another carries its
`parent`'s name and the `step` of the loop iteration it belongs to.

Which steps the host-clock metrics read: the last two whole blocks that end
at or before `window_t0`, the settled warm-up blocks. They ran untraced and
compiled nothing, and the `[bench] traced` line already uses them as its
untraced yardstick. The capture starts at `window_t0` and is stopped inside
the `log` span of the last traced block, so no block of the window is safe
in every cell.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def end(span: dict) -> float:
    return span["t_abs"] + span["dur"]


def named(spans, name: str, parent: Optional[str] = None) -> List[dict]:
    return sorted((s for s in spans or [] if s["name"] == name
                   and (parent is None or s.get("parent") == parent)),
                  key=lambda s: s["t_abs"])


def by_step(spans, name: str, parent: Optional[str] = None) -> Dict[int, dict]:
    """The span of that name per step; of several in one step, the first."""
    out: Dict[int, dict] = {}
    for s in named(spans, name, parent):
        if "step" in s:
            out.setdefault(s["step"], s)
    return out


def settled_blocks(ev: dict) -> List[Tuple[dict, dict]]:
    """(opening, closing) `window_close` spans of the last two whole blocks
    that end at or before `window_t0`: a block runs from the end of one
    `window_close` to the end of the next, and is whole when the closing one
    counts `block_steps` steps. A stream without `window_close` spans (a
    program that does not record them) gives none."""
    k = int(ev["cell"].traffic["block_steps"])
    closes = [w for w in named(ev.get("spans"), "window_close")
              if w["t_abs"] <= ev["window_t0"]][-3:]
    return [(a, b) for a, b in zip(closes, closes[1:]) if b.get("block") == k]
