#!/usr/bin/env bash
# One-shot smoke of the full product surface on a virtual 8-device CPU mesh
# (no TPU needed). Exercises: both static-analysis gates (pslint source
# gate, pscheck jaxpr contract gate), the multi-chip dryrun (all
# parallelism axes), the PS CNN trainer + evaluator, the elasticity
# drill (SIGTERM on 8 workers -> resume-reshape on 4 with an adaptive
# mask under a straggler storm), the flat state under the int8 wire
# (EF + guard NaN-inject), the homomorphic compressed-domain wire
# (2round int8 + EF + 64 KiB buckets + pipelined overlap + NaN-inject),
# the LM trainer on tp with
# vocab-parallel embedding + the LM evaluator with KV-cache sampling,
# the serving engine under open-loop traffic with one hot checkpoint
# rollover, the observability leg (traced train + serve merged into one
# Chrome timeline by tools/trace_report.py), the serve-chaos leg
# (traffic spike + decode stalls + corrupt staged rollover -> shed
# events, full lifecycle accounting, rollover abort onto old weights).
# Budget ~8 minutes of CPU (compiles dominate). The chip has its own
# smoke: python chip_smoke.py.
#
#   bash tools/smoke.sh
set -euo pipefail
cd "$(dirname "$0")/.."

run() {
  echo "== $*"
  env -i PATH="$PATH" HOME="$HOME" \
      JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      "$@"
}

TMP=$(mktemp -d)
trap 'rm -rf "$TMP"' EXIT

# static analysis first: cheapest signal, fails fastest. The psdiverge
# pass (PSL006-008, multihost deadlock/torn-replica hazards) runs as its
# own leg so a divergence regression is named before the general gate;
# lint.sh reads only source text; check.sh traces the real step
# functions on the same 8-device CPU environment the rest of the smoke
# uses.
run bash tools/lint.sh --select PSL006,PSL007,PSL008
run bash tools/lint.sh

# psnumerics precision-flow gate (PSC111-114) runs as the check phase's
# first step: the full registry must PROVE its quantized-wire numerics
# clean, and each broken fixture must still trip its rule — an analyzer
# that stopped seeing anything would otherwise pass vacuously.
run bash tools/check.sh --select PSC111,PSC112,PSC113,PSC114
for pair in numerics_fresh_scale:PSC111 numerics_dropped_residual:PSC112 \
            numerics_widened_accum:PSC113 numerics_silent_downcast:PSC114; do
  fixture="${pair%%:*}"; rule="${pair##*:}"
  if run bash tools/check.sh --registry tests.check_fixtures \
         --only "$fixture" --select "$rule"; then
    echo "numerics smoke: fixture $fixture did not trip $rule"; exit 1
  fi
done
run bash tools/check.sh --registry tests.check_fixtures \
    --only numerics_ef_closed --select PSC111,PSC112,PSC113,PSC114
run bash tools/check.sh

run python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --grad-accum-steps 2 --max-steps 6 --eval-freq 3 --log-interval 3 \
    --train-dir "$TMP/cnn"
run python -m ps_pytorch_tpu.cli.evaluate \
    --network LeNet --dataset MNIST --model-dir "$TMP/cnn" --once

# resilience chaos smoke (ARCHITECTURE §7d): a NaN gradient at step 4 is
# skipped by the device-side guard, the step-6 checkpoint is corrupted on
# disk as it lands, and the --resume run must quarantine it and restart
# from the valid step-3 checkpoint
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --max-steps 6 --eval-freq 3 --log-interval 1 \
    --fault-plan '{"nan_grads":[4],"ckpt_corrupt":[6]}' \
    --train-dir "$TMP/chaos"
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --max-steps 8 --eval-freq 3 --log-interval 1 --resume \
    --train-dir "$TMP/chaos"
test -f "$TMP/chaos/model_step_6.corrupt" \
    || { echo "chaos smoke: corrupt checkpoint was not quarantined"; exit 1; }

# elasticity leg (ARCHITECTURE §7f): a ZeRO-1 run SIGTERMs itself at
# step 3 on the 8-worker mesh (graceful stop + checkpoint + elastic.json
# manifest); the --resume run SHRINKS to a 4-worker mesh — the elastic
# reshape re-carves params/moments bit-exactly — and rides the adaptive
# aggregation mask through an injected straggler storm, which must drop
# the mask count within one window (a mask_adapt event) while the step
# numbering continues from the checkpoint (loss continuity, no restart)
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 8 \
    --opt-placement sharded --max-steps 30 --eval-freq 100 \
    --log-interval 1 --fault-plan '{"sigterm": 3}' \
    --train-dir "$TMP/elastic"
test -f "$TMP/elastic/elastic.json" \
    || { echo "elastic smoke: geometry manifest was not written"; exit 1; }
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 4 --batch-size 8 \
    --opt-placement sharded --max-steps 6 --eval-freq 100 \
    --log-interval 1 --resume --train-dir "$TMP/elastic" \
    --num-aggregate-min 2 --num-aggregate-max 4 --adapt-window 2 \
    --mode kill --kill-threshold 0.75 \
    --fault-plan '{"slow_steps": [5], "slow_s": 1.5}' \
    --metrics-file "$TMP/elastic_resume.jsonl"
run python - "$TMP/elastic_resume.jsonl" <<'PYEOF'
import json, math, sys
events = [json.loads(l) for l in open(sys.argv[1])]
kinds = [e["kind"] for e in events]
assert "resume_reshape" in kinds, kinds
trains = [e for e in events if e["kind"] == "train"]
assert trains and trains[0]["step"] == 4, trains[:1]   # continued, not restarted
assert all(math.isfinite(e["loss"]) for e in trains), trains
adapt = [e for e in events if e["kind"] == "mask_adapt"]
assert adapt and adapt[0]["from"] == 4 and adapt[0]["to"] == 3, adapt
print("elastic smoke: 8->4 reshape ok, mask %d->%d under storm, loss %.3f"
      % (adapt[0]["from"], adapt[0]["to"], trains[-1]["loss"]))
PYEOF

# flat-state leg (ARCHITECTURE §6f): int8 wire + error feedback + a NaN
# gradient at step 3 — the guard must skip-step by rolling back the FLAT
# params/moment vectors, and training must continue to a clean finish
# on the 8-device CPU mesh
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --max-steps 6 --eval-freq 3 --log-interval 1 \
    --compress-grad compress --quant-block-size 32 \
    --error-feedback --bucket-bytes 65536 \
    --fault-plan '{"nan_grads":[3]}' \
    --train-dir "$TMP/flat"

# homomorphic-wire leg (ARCHITECTURE §6h, --wire-domain homomorphic):
# the bandwidth-honest 2-round int8 wire summed in the COMPRESSED
# domain (shared scales, integer accumulation, one deferred
# scale-multiply per bucket), stacked with error feedback, 64 KiB
# buckets, and the pipelined schedule — and a NaN gradient at step 3
# proving the non-finite guard still fires on the homomorphic wire
# (the guard reduces the RAW gradients, upstream of the lattice)
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --max-steps 6 --eval-freq 3 --log-interval 1 \
    --compress-grad 2round --quant-block-size 32 --error-feedback \
    --bucket-bytes 65536 --overlap on --wire-domain homomorphic \
    --fault-plan '{"nan_grads":[3]}' \
    --metrics-file "$TMP/homomorphic/metrics.jsonl" \
    --train-dir "$TMP/homomorphic"
run python - "$TMP/homomorphic/metrics.jsonl" <<'PYEOF'
import json, math, sys
events = [json.loads(l) for l in open(sys.argv[1])]
skips = [e for e in events if e.get("kind") == "grad_skip"]
assert skips and skips[0]["skipped_steps"] >= 1, skips
trains = [e for e in events if e.get("kind") == "train"]
assert trains and math.isfinite(trains[-1]["loss"]), trains
print("homomorphic smoke: guard skipped %d step(s) on the int8 "
      "compressed-domain wire, final loss %.3f"
      % (skips[-1]["skipped_steps"], trains[-1]["loss"]))
PYEOF

run python -m ps_pytorch_tpu.cli.train_lm \
    --parallelism tp --heads 8 --dim 64 --vocab-size 64 --shard-vocab \
    --seq-len 64 --max-steps 20 --log-interval 10 --lr 0.3 \
    --train-dir "$TMP/lm" --eval-freq 10
run python -m ps_pytorch_tpu.cli.evaluate_lm \
    --model-dir "$TMP/lm" --once --generate 16

# serving leg (ARCHITECTURE §7e): serve the freshly-trained LM from its
# step-10 checkpoint under the open-loop traffic generator on the same
# 8-device mesh; the poll must hot-roll onto step 20 mid-serve
# (drain-then-swap), every request must complete, and the latency tail
# must be finite
run python -m ps_pytorch_tpu.cli.serve \
    --model-dir "$TMP/lm" --step 10 --slots 8 --max-len 64 \
    --requests 24 --rate 40 --prompt-min 4 --prompt-max 12 \
    --new-min 8 --new-max 16 --poll-interval 0.1 --num-workers 8 \
    --summary-file "$TMP/serve.json" --trace "$TMP/trace"
run python - "$TMP/serve.json" <<'PYEOF'
import json, math, sys
s = json.load(open(sys.argv[1]))
assert s["requests_completed"] == 24 and s["new_tokens"] > 0, s
assert math.isfinite(s["p99_token_latency_s"]), s
assert s["weights_step"] == 20 and len(s["rollovers"]) == 1, s
assert math.isfinite(s["p99_queue_s"]) and math.isfinite(s["p99_prefill_s"]), s
print("serve smoke: %d tokens at %.1f tok/s, p99 %.4fs (queue p99 %.4fs), "
      "rollover 10->20"
      % (s["new_tokens"], s["tokens_per_sec"], s["p99_token_latency_s"],
         s["p99_queue_s"]))
PYEOF

# observability leg (ARCHITECTURE §7g): train 10 traced steps on the
# 8-dev mesh (span stream + metrics run header; the injected NaN grad at
# step 3 lands a grad_skip event for the overlay), merge with the
# serving leg's trace (written above into the same dir — it includes the
# rollover drain), and assert the merged Chrome timeline loads, spans
# nest, and every required phase is present with sane percentiles
run python -m ps_pytorch_tpu.cli.train \
    --network LeNet --dataset MNIST --num-workers 8 --batch-size 64 \
    --max-steps 10 --eval-freq 5 --log-interval 5 \
    --fault-plan '{"nan_grads":[3]}' \
    --trace "$TMP/trace" --metrics-file "$TMP/obs_train.jsonl" \
    --train-dir "$TMP/obs"
run python tools/trace_report.py "$TMP/trace" \
    --metrics "$TMP/obs_train.jsonl" \
    --out "$TMP/trace_merged.json" --summary-out "$TMP/trace_summary.json" \
    --require-phases fetch,h2d,dispatch,sync,guard,ckpt_save,admit_prefill,decode_dispatch,token_fetch,evict,rollover_drain,rollover_swap,request \
    > /dev/null
run python - "$TMP/trace_merged.json" "$TMP/trace_summary.json" <<'PYEOF'
import json, sys
merged = json.load(open(sys.argv[1]))
spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
assert spans and all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans), "bad events"
s = json.load(open(sys.argv[2]))
assert s["nesting_ok"], s
assert s["n_overlay_events"] >= 1, s  # the injected grad_skip marker
assert {c["component"] for c in s["streams"]} == {"train", "serve"}, s["streams"]
for name, st in s["phases"].items():
    assert st["count"] >= 1 and 0 <= st["p50_s"] <= st["p99_s"], (name, st)
frac = s["fraction_of_loop_walltime"]["train"]
assert abs(sum(frac.values()) - 1.0) < 0.01, frac
print("obs smoke: %d phases merged (train+serve), %d span events, "
      "dispatch fraction %.2f"
      % (len(s["phases"]), len(spans), frac.get("dispatch", 0.0)))
PYEOF

# serve-chaos leg (ARCHITECTURE §7i): the same LM under fire on the
# 8-dev mesh — a 5x seeded traffic spike, injected slow_decode stalls,
# per-request deadlines, SLO-aware admission, and a rollover_corrupt
# fault that truncates the staged step-20 checkpoint the moment it is
# staged. Every request must terminate with exactly one lifecycle event
# (zero silent drops), sheds must fire, the rollover must ABORT onto
# the step-10 weights (service continues), and the chaos trace must
# merge under --require-phases. Runs after the obs leg: it damages the
# step-20 checkpoint file for good.
run python -m ps_pytorch_tpu.cli.serve \
    --model-dir "$TMP/lm" --step 10 --slots 8 --max-len 64 \
    --requests 64 --rate 40 --prompt-min 4 --prompt-max 12 \
    --new-min 8 --new-max 16 --poll-interval 0.05 --num-workers 8 \
    --deadline 2.0 --slo-budget 0.25 --admit-window 0.1 \
    --traffic-spike 5,0,2 --drain-timeout 5 \
    --fault-plan '{"slow_decode":[2,3,4,5,6,7,8,9,10,11,12,13,14,15],"slow_decode_s":0.05,"rollover_corrupt":[20]}' \
    --events "$TMP/chaos_events.jsonl" --summary-file "$TMP/chaos.json" \
    --trace "$TMP/chaos_trace"
run python - "$TMP/chaos.json" "$TMP/chaos_events.jsonl" <<'PYEOF'
import json, sys
from ps_pytorch_tpu.obs.schema import validate_event
s = json.load(open(sys.argv[1]))
assert s["requests_submitted"] == 64, s
assert (s["requests_completed"] + s["requests_shed"]
        + s["requests_expired"]) == 64, s
assert s["requests_shed"] >= 1, s           # the controller said no
assert s["weights_step"] == 10 and s["rollovers"] == [], s
assert len(s["rollover_aborts"]) == 1, s
assert s["rollover_aborts"][0]["reason"] == "corrupt_staged", s
events = [json.loads(l) for l in open(sys.argv[2])]
for e in events:
    validate_event(dict(e))
terminal = {"request_done", "request_shed", "deadline_expired"}
rids = sorted(e["rid"] for e in events if e["kind"] in terminal)
assert rids == list(range(64)), rids        # every request, exactly once
assert any(e["kind"] == "rollover_abort" for e in events), "no abort event"
assert any(e["kind"] == "admission_adapt" for e in events), "no adapt event"
print("serve-chaos smoke: %d completed / %d shed / %d expired, rollover "
      "10->20 aborted (corrupt_staged), goodput %.1f tok/s"
      % (s["requests_completed"], s["requests_shed"], s["requests_expired"],
         s["goodput_tokens_per_sec"] or 0.0))
PYEOF
run python tools/trace_report.py "$TMP/chaos_trace" \
    --out "$TMP/chaos_trace_merged.json" \
    --summary-out "$TMP/chaos_trace_summary.json" \
    --require-phases admit_prefill,decode_dispatch,token_fetch,evict,rollover_drain,request \
    > /dev/null

# autotune leg (ARCHITECTURE §7h): trace-only knob search over the
# trimmed LeNet grid on the 8-dev CPU mesh — candidates are pruned by
# the PSC contract rules before costing (the grid deliberately contains
# a config-invalid point AND a PSC103-pruned one), survivors ranked by
# the trace-only cost model, and the evidence record must land with a
# schema-valid run_header. Nothing executes; compiles are trace-only.
run python tools/autotune.py --model lenet --grid smoke --trace-only \
    --out "$TMP/autotune_lenet.json"
run python - "$TMP/autotune_lenet.json" <<'PYEOF'
import json, sys
from ps_pytorch_tpu.obs.schema import validate_event
rec = json.load(open(sys.argv[1]))
validate_event(rec)                      # kind "autotune" round-trips
validate_event(dict(rec["run"]))         # nested run_header is valid
assert rec["run"]["component"] == "autotune", rec["run"]
assert rec["trace_only"] and rec["n_candidates"] >= 8, rec["n_candidates"]
costs = [c["cost"]["modeled_step_s"] for c in rec["candidates"]]
assert costs == sorted(costs) and all(c > 0 for c in costs), costs[:3]
stages = {p["stage"] for p in rec["pruned"]}
assert "config" in stages, stages        # engine-refused combination
contract = [p for p in rec["pruned"] if p["stage"] == "contract"]
assert contract and any("PSC103" in p["rules"] for p in contract), contract
assert rec["best"]["flag_line"].startswith("--network LeNet"), rec["best"]
print("autotune smoke: %d ranked, %d pruned (%s), best %s"
      % (rec["n_candidates"], rec["n_pruned"], sorted(stages),
         rec["best"]["name"]))
PYEOF

echo "SMOKE OK"
