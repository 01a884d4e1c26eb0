"""Headline-length (T=8192) ring+flash exactness on CPU.

A CORRECTNESS artifact at the headline sequence length (its speed on a
chip: not measured): ring attention with
the Pallas flash kernel (interpret mode on CPU), 8-way sequence parallel,
against the naive full-attention oracle — value and gradient.

Shapes are the smallest that still exercise the headline length (B=1, H=1,
D=64): the ring/flash code paths are shape-generic, and T is the quantity
under test. The oracle materializes the full [8192, 8192] score matrix
(256 MB f32) — exactly what the flash ring exists to avoid.

  PS_TPU_PALLAS_INTERPRET=1 JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python tools/longcontext_cpu_check.py --out runs/longcontext_t8192_cpu.json

The committed artifact is read by PARITY.md's long-context section (A7).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--heads", type=int, default=1)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--grad", action="store_true", default=True)
    p.add_argument("--no-grad", dest="grad", action="store_false")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    os.environ.setdefault("PS_TPU_PALLAS_INTERPRET", "1")
    # this tool is a CPU correctness check by definition, and jax reads
    # the platform and the virtual device count once at backend creation:
    # re-exec under the one canonical CPU-mesh environment unless already
    # in it (same pattern as __graft_entry__.py).
    from tpu_env import clean_cpu_env, env_is_clean

    if not env_is_clean(args.devices):
        import subprocess

        # inherit the caller's cwd so a relative --out lands where asked;
        # imports resolve through the absolute REPO sys.path entry
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)]
            + (sys.argv[1:] if argv is None else list(argv)),
            env=clean_cpu_env(n_devices=args.devices),
            capture_output=True, text=True,
        )
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            raise SystemExit(proc.returncode)
        return json.loads(proc.stdout)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ps_pytorch_tpu.parallel.ring_attention import (
        full_attention,
        make_ring_attention,
        make_seq_mesh,
        shard_sequence,
    )

    B, T, H, D = 1, args.seq, args.heads, args.dim
    mesh = make_seq_mesh(args.devices)
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()

    report = {
        "seq": T, "dim": D, "heads": H, "devices": args.devices,
        "backend": jax.default_backend(),
        "pallas_interpret": os.environ.get("PS_TPU_PALLAS_INTERPRET") == "1",
        "checks": [],
    }

    ring = make_ring_attention(mesh, causal=True, impl="flash")
    qs, ks, vs = (shard_sequence(x, mesh) for x in (q, k, v))

    t0 = time.time()
    got = jax.device_get(ring(qs, ks, vs))
    t_ring = time.time() - t0
    t0 = time.time()
    want = jax.device_get(full_attention(q, k, v, causal=True))
    t_oracle = time.time() - t0
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    report["checks"].append({
        "what": "value: ring_flash(causal, 8-way SP) vs full_attention",
        "max_abs_err": err, "oracle_max_abs": scale,
        "ring_seconds": round(t_ring, 1),
        "oracle_seconds": round(t_oracle, 1),
        "pass": bool(err < 2e-4),
    })

    if args.grad:
        # gradient through the ring (custom VJP path) vs oracle gradient,
        # on a scalar loss that weights every position
        w = jnp.asarray(rng.randn(*got.shape).astype(np.float32))

        def loss_ring(q_, k_, v_):
            return jnp.sum(ring(q_, k_, v_) * shard_sequence(w, mesh))

        def loss_full(q_, k_, v_):
            return jnp.sum(full_attention(q_, k_, v_, causal=True) * w)

        t0 = time.time()
        gr = jax.device_get(jax.grad(loss_ring, argnums=(0, 1, 2))(qs, ks, vs))
        t_g = time.time() - t0
        gf = jax.device_get(jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v))
        for name, a, b in zip("qkv", gr, gf):
            e = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            s = float(np.max(np.abs(np.asarray(b))))
            report["checks"].append({
                "what": f"grad d{name}: ring_flash custom-VJP vs oracle",
                "max_abs_err": e, "oracle_max_abs": s,
                # grads accumulate T-long reductions; tolerance scales
                # with the oracle's own magnitude
                "pass": bool(e < 2e-4 * max(1.0, s)),
            })
        report["grad_seconds"] = round(t_g, 1)

    report["all_pass"] = all(c["pass"] for c in report["checks"])
    print(json.dumps(report, indent=2))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"report -> {args.out}", file=sys.stderr)
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["all_pass"] else 1)
