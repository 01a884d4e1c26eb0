"""One number from the program's own set-up record, up to the measured window.

The program keeps one record a process of what it did between the process's
birth and its first steady step (`ps_pytorch_tpu.obs.trace.setup_tracer`:
the process's age when the record opened, jax's own trace / lower / compile
/ cache-load intervals by program name, the spans of its set-up functions,
each step program's first call). The reducers run in the run's own process,
so the record is read where it lies (`snapshot()` leaves it whole). Which
spans a metric reads, and in what order they take a moment that two of them
cover, is in the metric's own layer_metrics/<name>.json: the yardstick is
here and not in the program.

Only records that had ended by `ev["window_t0"]` count (the reference
compiles after it). `args["form"]`:

- `instant_attr`: attribute `attr` of the first record named `span`;
- `count`: how many records are named in `spans`;
- `union`: the seconds the `spans` cover together, less what the spans
  named in `without` cover of them;
- `since_last`: from the end of the last of `spans` to `window_t0`, less
  what `without` covers there (None where there is none of `spans`);
- `rest`: from the record's first moment (`process_start`, else its first
  record) to the end of the last of `until_last` (to `window_t0` where there
  is none), less what `without` covers there.

The eight `setup_*` metrics are a partition: `setup_before_program_s` (the
process's age at the record's first moment), four unions that each leave
out the ones before it (`setup_cache_load_s`, `setup_trace_lower_s`,
`setup_first_call_s`, `setup_build_s`), `setup_warm_s` after the last step
program's first call and `setup_unplaced_s` before it, so the seven in
seconds sum to `window_t0` less the process's birth. A form that finds no
record of its kind among others reads 0.0 (a cold cache loads nothing); on
a program that keeps no set-up record every metric is None and left out.
"""


def _covered_s(intervals) -> float:
    """Seconds the (start, end) intervals cover together."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def _within(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def reduce(args: dict, ev: dict):
    try:
        from ps_pytorch_tpu.obs.trace import setup_tracer
    except ImportError:  # a program that keeps no set-up record
        return None
    tracer = setup_tracer()
    end = ev["window_t0"] - tracer.header["t_mono"]
    records = [r for r in tracer.snapshot() if r["t"] + r["dur"] <= end + 1e-6]
    if not records:
        return None
    form = args["form"]
    if form not in ("instant_attr", "count", "union", "since_last", "rest"):
        raise ValueError(f"setup_spans: no form {form!r}")
    if form == "instant_attr":
        return next((r.get(args["attr"]) for r in records if r["name"] == args["span"]), None)
    if form == "count":
        return sum(r["name"] in args["spans"] for r in records)
    first = next((r["t"] for r in records if r["name"] == "process_start"),
                 min(r["t"] for r in records))
    spans = lambda names: [(max(r["t"], first), r["t"] + r["dur"])
                           for r in records if r["name"] in names]
    without = spans(args.get("without", ()))
    if form == "union":
        return _covered_s(spans(args["spans"]) + without) - _covered_s(without)
    if form == "since_last":
        last = max((b for _, b in spans(args["spans"])), default=None)
        return None if last is None else (end - last) - _covered_s(_within(without, last, end))
    last = max((b for _, b in spans(args["until_last"])), default=end)
    return (last - first) - _covered_s(_within(without, first, last))
