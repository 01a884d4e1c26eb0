"""Two-process DCN smoke (VERDICT round-1 item 7).

Spawns TWO real `jax.distributed` processes on localhost (4 virtual CPU
devices each -> one 8-device job) and drives the actual product CLI:

- hybrid dcn x workers mesh training end to end (cli.train --dcn-hosts 2),
  both processes running the same command — exactly the tools/
  run_multihost.sh contract;
- the multi-host checkpoint path (collective gather, process-0 single
  writer, durability barrier) producing a file the single-process
  evaluator can read;
- mesh-consensus graceful stop: SIGTERM delivered to ONE process stops
  BOTH at the same step boundary with a checkpoint written (trainer.
  _stop_consensus) — the capability the reference's tag-77 kill never
  actually wired (SURVEY.md section 2 straggler row).

These need cross-process CPU collectives, which ride jaxlib's gloo TCP
backend. Gloo pairs match ops by FIFO
order, not tags, and XLA's CPU executor can issue independent
collectives of one computation in thread-pool order — so under load a
run occasionally dies with `gloo::EnforceNotMet` (op-size mismatch) or
a peer-reset/hang as a process aborts mid-collective. That is a known
transport flake of the gloo backend, independent of the product code
under test, so each test retries its whole 2-process attempt ONCE when
the failure signature is gloo's; a second strike fails the test.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tpu_env import clean_cpu_env  # noqa: E402
from tools.mp_util import free_port as _free_port  # noqa: E402

# the failure signatures of the gloo TCP transport (see module
# docstring) — the ONLY errors a retry may absorb
_GLOO_FLAKE_SIGNS = (
    "gloo::EnforceNotMet",
    "Gloo all-reduce failed",
    "Connection reset by peer",
    "Connection refused",
    "Broken pipe",
)


def _spawn(pid: int, port: int, tmp, extra, env_extra=None):
    env = clean_cpu_env(n_devices=4)
    if env_extra:
        env.update(env_extra)
    argv = [
        sys.executable, "-m", "ps_pytorch_tpu.cli.train",
        "--coordinator-address", f"localhost:{port}",
        "--num-processes", "2", "--process-id", str(pid),
        "--network", "LeNet", "--dataset", "MNIST",
        "--batch-size", "8", "--lr", "0.05",
        "--train-dir", str(tmp / "ckpt"),
        "--metrics-file", str(tmp / f"metrics_{pid}.jsonl"),
        "--log-interval", "1",
        *extra,
    ]
    return subprocess.Popen(
        argv, env=env, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _finish(procs, timeout=420, hang_ok=False):
    """Collect both processes. ``hang_ok``: a hung pair is killed and
    reported in the outputs instead of failing the test — the caller's
    gloo-flake retry decides (a process aborting mid-collective leaves
    its peer blocked forever, so a hang IS one of gloo's signatures)."""
    outs = []
    hung = False
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(5, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            hung = True
            if not hang_ok:
                pytest.fail(
                    f"2-process run hung; partial output:\n{out[-3000:]}"
                )
        outs.append(out)
    return (outs, hung) if hang_ok else outs


def _gloo_flaked(procs, outs, hung) -> bool:
    if any(s in out for out in outs for s in _GLOO_FLAKE_SIGNS):
        return hung or any(p.returncode != 0 for p in procs)
    return False


def _run_pair_with_gloo_retry(tmp_path, attempt_fn):
    """Run one 2-process attempt; retry up to THREE more times iff the
    failure signature is the gloo transport's (a loaded container can
    flake several attempts in a row — observed on full-suite runs; the
    signature gate means a real failure still surfaces on its first
    shot). ``attempt_fn()`` must spawn a fresh pair and return (procs,
    outs, hung); stale metrics files are cleared between attempts so
    assertions never read the flaked run."""
    for attempt in range(4):
        for i in (0, 1):
            mf = tmp_path / f"metrics_{i}.jsonl"
            if mf.exists():
                mf.unlink()
        procs, outs, hung = attempt_fn()
        if not (attempt < 3 and _gloo_flaked(procs, outs, hung)):
            break
    if hung:
        pytest.fail(
            f"2-process run did not complete (hung or died before "
            f"stepping); partial output:\n{outs[0][-2000:]}"
            f"\n---\n{outs[1][-2000:]}"
        )
    return procs, outs


@pytest.mark.multihost
def test_two_process_hybrid_mesh_train_and_checkpoint(tmp_path):
    extra = ["--max-steps", "4", "--eval-freq", "2", "--dcn-hosts", "2",
             "--num-workers", "8"]

    def attempt():
        port = _free_port()
        procs = [_spawn(i, port, tmp_path, extra) for i in (0, 1)]
        outs, hung = _finish(procs, hang_ok=True)
        return procs, outs, hung

    procs, outs = _run_pair_with_gloo_retry(tmp_path, attempt)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"rc={p.returncode}\n{out[-3000:]}"
        assert "Step: 4" in out, out[-2000:]
    # single writer, durable on both processes by the time either returns
    assert (tmp_path / "ckpt" / "model_step_4").exists()
    # both processes trained the SAME model: identical loss trajectories
    rows = []
    for i in (0, 1):
        with open(tmp_path / f"metrics_{i}.jsonl") as f:
            rows.append([
                e["loss"] for e in map(json.loads, f)
                # by kind, not substring: the run_header record also
                # contains the text "train" ("component": "train")
                if e.get("kind") == "train"
            ])
    assert rows[0] == pytest.approx(rows[1]), "processes diverged"

    # the ordinary single-process evaluator consumes the multi-host file
    ev = subprocess.run(
        [
            sys.executable, "-m", "ps_pytorch_tpu.cli.evaluate",
            "--model-dir", str(tmp_path / "ckpt"),
            "--network", "LeNet", "--dataset", "MNIST", "--once",
        ],
        env=clean_cpu_env(n_devices=1), cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert ev.returncode == 0, ev.stderr[-2000:]
    assert "Prec@1" in (ev.stdout + ev.stderr)


@pytest.mark.multihost
@pytest.mark.slow
def test_adaptive_mask_reaches_host_consensus(tmp_path):
    """Only process 0 is stalled (PS_TPU_FAULTS is per-process env), but
    the adaptive controller must ADOPT identical per-window counts on
    both hosts — each window's proposal is min-reduced across hosts
    (trainer._count_consensus), so the host that saw no local slowness
    still shrinks its traced count. Divergent counts entering one
    global psum would silently diverge the replicated params."""
    extra = [
        "--max-steps", "8", "--eval-freq", "0", "--dcn-hosts", "2",
        "--num-workers", "8",
        "--num-aggregate-min", "2", "--num-aggregate-max", "8",
        "--adapt-window", "2", "--mode", "kill", "--kill-threshold", "2.5",
    ]

    def attempt():
        port = _free_port()
        procs = [
            _spawn(
                i, port, tmp_path, extra,
                env_extra=(
                    {"PS_TPU_FAULTS": '{"slow_steps": [3], "slow_s": 6.0}'}
                    if i == 0 else None
                ),
            )
            for i in (0, 1)
        ]
        outs, hung = _finish(procs, hang_ok=True)
        return procs, outs, hung

    procs, outs = _run_pair_with_gloo_retry(tmp_path, attempt)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"rc={p.returncode}\n{out[-3000:]}"
    streams = []
    for i in (0, 1):
        with open(tmp_path / f"metrics_{i}.jsonl") as f:
            events = [json.loads(l) for l in f]
        streams.append([
            (e["step"], e["from"], e["to"])
            for e in events if e["kind"] == "mask_adapt"
        ])
    # the un-stalled process followed the consensus: same adaptations at
    # the same steps (gloo CPU steps carry real jitter, so the exact
    # trajectory varies — EQUALITY across hosts is the property under
    # test; the deterministic drop/recover policy is pinned by the
    # single-process suite), and the injected stall at step 3 dropped
    # the count at its window boundary
    assert streams[0] == streams[1], streams
    assert streams[0], "no mask_adapt event despite the injected stall"
    step0, frm0, to0 = streams[0][0]
    assert step0 == 3 and frm0 == 8 and to0 < 8, streams


@pytest.mark.multihost
def test_sigterm_on_one_process_stops_both(tmp_path):
    extra = ["--max-steps", "100000", "--eval-freq", "0", "--dcn-hosts", "2",
             "--num-workers", "8"]

    def attempt():
        port = _free_port()
        procs = [_spawn(i, port, tmp_path, extra) for i in (0, 1)]
        # wait until BOTH processes are stepping (metrics lines appear),
        # then signal ONLY process 0 — consensus must stop process 1 too
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if all(
                (tmp_path / f"metrics_{i}.jsonl").exists() for i in (0, 1)
            ):
                break
            if any(p.poll() is not None for p in procs):
                # a process died before stepping: let the gloo-retry
                # classifier see the output instead of failing here
                outs, hung = _finish(procs, timeout=10, hang_ok=True)
                return procs, outs, True
            time.sleep(0.5)
        else:
            for p in procs:
                p.kill()
            outs, _ = _finish(procs, timeout=10, hang_ok=True)
            return procs, outs, True
        procs[0].send_signal(signal.SIGTERM)
        outs, hung = _finish(procs, hang_ok=True)
        return procs, outs, hung

    procs, outs = _run_pair_with_gloo_retry(tmp_path, attempt)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"rc={p.returncode}\n{out[-3000:]}"
        assert "graceful stop at step" in out, out[-2000:]
        assert "skipping validation" in out
    # the post-stop checkpoint was written (resume point)
    steps = [
        f for f in os.listdir(tmp_path / "ckpt") if f.startswith("model_step_")
    ]
    assert steps, "no checkpoint written on graceful stop"
