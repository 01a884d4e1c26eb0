"""A count the harness or the program made: `counters[args["counter"]]`."""


def reduce(args: dict, ev: dict):
    return ev.get("counters", {}).get(args["counter"])
