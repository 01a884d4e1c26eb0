"""The host's part of one block boundary, median over the settled blocks'
boundaries, in milliseconds. A boundary runs from the end of `sync` inside a
`window_close` (the device has drained and now waits) to the end of the next
step's `dispatch` (it has work again). `part`: `whole`; `input`, what of it
lies under the next step's `fetch` (with its `gather` and `h2d`); `report`,
the rest (`log`, `metrics_write`, `guard`, `stop_check`, the `dispatch` and
whatever no span covers), so that the two parts sum to the whole."""

import statistics

from benchmark.reducers import host_spans as hs


def boundaries(ev: dict):
    """(whole, input) seconds of the boundary that opens each settled block."""
    spans = ev.get("spans")
    blocks = hs.settled_blocks(ev)
    if not blocks:
        return []
    syncs = hs.by_step(spans, "sync", parent="window_close")
    fetches, dispatches = hs.by_step(spans, "fetch"), hs.by_step(spans, "dispatch")
    out = []
    for opening, _ in blocks:
        step = opening["step"]
        sync, fetch, dispatch = syncs.get(step), fetches.get(step + 1), dispatches.get(step + 1)
        if sync is None or dispatch is None:
            continue
        t0, t1 = hs.end(sync), hs.end(dispatch)
        under = 0.0 if fetch is None else max(
            0.0, min(hs.end(fetch), t1) - max(fetch["t_abs"], t0))
        out.append((t1 - t0, under))
    return out


def reduce(args: dict, ev: dict):
    got = boundaries(ev)
    if not got:
        return None
    pick = {"whole": lambda w, i: w, "input": lambda w, i: i,
            "report": lambda w, i: w - i}[args["part"]]
    return 1e3 * statistics.median(pick(w, i) for w, i in got)
