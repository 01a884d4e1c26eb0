"""Median duration of the program's spans named `span`, times `scale`
(1000 for milliseconds). With `blocks` = "settled", only the iterations of
the settled blocks (host_spans.settled_blocks); with `without`, only the
iterations that hold no span of that name (a `step` that closes no window)."""

import statistics

from benchmark.reducers import host_spans as hs


def reduce(args: dict, ev: dict):
    spans = hs.named(ev.get("spans"), args["span"])
    if args.get("blocks") == "settled":
        blocks = hs.settled_blocks(ev)
        if not blocks:
            return None
        lo, hi = blocks[0][0]["step"], blocks[-1][1]["step"]
        spans = [s for s in spans if lo < s.get("step", lo) <= hi]
    if args.get("without"):
        skip = set(hs.by_step(ev.get("spans"), args["without"]))
        spans = [s for s in spans if s.get("step") not in skip]
    if not spans:
        return None
    return float(args.get("scale", 1.0)) * statistics.median(s["dur"] for s in spans)
