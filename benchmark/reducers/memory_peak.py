"""Peak bytes in use on the fullest chip after the window, in GiB."""


def reduce(args: dict, ev: dict):
    peak = ev.get("memory_peak_bytes")
    return None if not peak else peak / 2 ** 30
