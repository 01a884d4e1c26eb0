"""Time inside the spans named `span` (opened under `parent`) that close the
settled blocks, over those blocks' time (one `window_close` end to the
next). Percent. With `sync` under `window_close`: the share of a block the
host spends waiting for the device."""

from benchmark.reducers import host_spans as hs


def reduce(args: dict, ev: dict):
    blocks = hs.settled_blocks(ev)
    inside = hs.by_step(ev.get("spans"), args["span"], parent=args.get("parent"))
    took = [(inside[b["step"]]["dur"], hs.end(b) - hs.end(a))
            for a, b in blocks if b["step"] in inside]
    if not took or sum(t for _, t in took) <= 0:
        return None
    return 100.0 * sum(d for d, _ in took) / sum(t for _, t in took)
