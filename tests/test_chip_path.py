"""Nothing on the path may make a CPU or interpreter run look like a chip run.

Three pins, all checkable without a chip:

- on a TPU backend every Pallas entry in ops/ reaches ``pl.pallas_call``
  WITHOUT ``interpret`` (the ``{} or {"interpret": True}`` regression: the
  compiled mode is the empty dict, which is falsy);
- the compile cache has one rule: ``JAX_COMPILATION_CACHE_DIR`` wins and
  then no directory is set in code; unset, the fixed in-checkout path;
- ``chip_smoke.py`` refuses a CPU: non-zero exit, the reason on stderr, no
  result line; and the result line it would print holds exactly the keys
  its caller parses.
"""

import inspect
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from ps_pytorch_tpu.ops import flash_attention as fa
from ps_pytorch_tpu.ops import pallas_mode as pm
from ps_pytorch_tpu.ops import quantize as qz
from ps_pytorch_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def on_tpu(monkeypatch):
    """What the code can observe on a chip: a 'tpu' default backend and
    neither Pallas environment variable."""
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_mode_is_compiled_on_tpu_and_interprets_only_when_asked(
    on_tpu, monkeypatch
):
    assert pm.pallas_mode() == pm.COMPILED == {}
    assert pm.kernel_mode("x") == pm.COMPILED
    assert pm.describe(pm.pallas_mode()) == "compiled"
    monkeypatch.setenv("PS_TPU_PALLAS_INTERPRET", "1")
    assert pm.pallas_mode() == pm.kernel_mode("x") == pm.INTERPRET
    assert pm.describe(pm.pallas_mode()) == "interpret"
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET")
    monkeypatch.setenv("PS_TPU_DISABLE_PALLAS", "1")
    assert pm.pallas_mode() is None and pm.describe(None) == "jnp"
    # an entry with no jnp twin must not quietly interpret on a chip
    with pytest.raises(RuntimeError, match="no jnp twin"):
        pm.kernel_mode("flash_partial")


def test_mode_off_tpu(monkeypatch):
    monkeypatch.delenv("PS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("PS_TPU_DISABLE_PALLAS", raising=False)
    assert jax.default_backend() == "cpu"
    assert pm.pallas_mode() is None
    assert pm.kernel_mode("flash_partial") == pm.INTERPRET


def test_every_pallas_entry_is_compiled_on_tpu(on_tpu, monkeypatch):
    """Drive every public entry of the two kernel modules as a TPU backend
    would, with pl.pallas_call replaced by a recorder (that then interprets,
    so the call still computes on this CPU)."""
    calls = []
    real = pl.pallas_call

    def recorder(kernel, *args, **kw):
        calls.append((kw.get("name"), kw.get("interpret", False)))
        kw["interpret"] = True
        return real(kernel, *args, **kw)

    monkeypatch.setattr(pl, "pallas_call", recorder)

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
               for _ in range(3))
    # flash_attention forward, then its custom VJP (fwd + dqkv, and past
    # the plan's cap fwd + dq + dkv)
    fa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    loss = lambda q: jnp.sum(fa.flash_attention(q, k, v, causal=True))
    jax.grad(loss)(q)
    with monkeypatch.context() as past_the_cap:
        past_the_cap.setattr(fa, "FUSED_BWD_CAP", 0)
        jax.grad(loss)(q)
    # the ring-hop partials, with the default mode argument
    q3, k3, v3 = (x.transpose(0, 2, 1, 3).reshape(2, 64, 32)
                  for x in (q, k, v))
    pv, m, l = fa.flash_partial(q3, k3, v3, 0.2, True, 0, 0)
    fa.flash_grads_partial(
        q3, k3, v3, q3, m + jnp.log(l), jnp.zeros_like(m), 0.2, True, 0, 0
    )
    # quantizers: per-tensor, per-row, and the homomorphic gather hop
    x = jnp.asarray(rng.randn(8 * 1024).astype(np.float32))
    qz.quantize_int8(x)
    qz.quantize_int8(x, block_size=128)
    qz.accumulate_rescale_int8(
        jnp.asarray(rng.randint(-127, 128, (4, 1024)).astype(np.int8)), 4.0
    )

    interpreted = sorted({name for name, interp in calls if interp})
    assert not interpreted, f"interpret=True on a TPU backend: {interpreted}"
    # every pallas_call site in the two modules was reached: a new kernel
    # must be driven here too
    in_source = set()
    for mod in (fa, qz):
        in_source |= set(
            re.findall(r'name="(ps_[a-z0-9_]+)"', inspect.getsource(mod))
        )
    assert {name for name, _ in calls} == in_source
    assert len(in_source) == 7


def _quantize_rows_128(a):
    return qz.quantize_int8(a, block_size=128)


def test_a_shape_that_goes_to_jnp_says_so(on_tpu):
    """Stochastic rounding and an unaligned row count legitimately take the
    jnp twin; the choice is a named scope in the traced program."""
    x = jnp.ones((3 * 128,), jnp.float32)  # 3 rows: not a sublane multiple
    text = jax.jit(_quantize_rows_128).lower(x).as_text(debug_info=True)
    assert "ps_quantize_rows_jnp" in text
    assert pm.kernel_census(
        'x = f32[] multiply(), metadata={op_name="jit(f)/ps_quantize_rows_jnp/mul"}\n'
        'y = s8[] custom-call(), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(f)/transpose(jvp(ps_flash_dqkv))/pallas_call"}'
    ) == {"mosaic": {"ps_flash_dqkv": 1}, "jnp": {"ps_quantize_rows": 1}}


# ------------------------------------------------------------ cache rule


def _record_config_updates(monkeypatch):
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    return updates


def test_cache_dir_env_wins_and_nothing_is_set_in_code(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    updates = _record_config_updates(monkeypatch)
    assert compile_cache.enable_persistent_compile_cache() == "/somewhere/else"
    assert not [n for n, _ in updates if n.endswith("cache_dir")]


def test_cache_dir_unset_is_the_fixed_in_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = _record_config_updates(monkeypatch)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_persistent_compile_cache() == want
    assert [v for n, v in updates if n.endswith("cache_dir")] == [want]
    # no argument to override the rule with
    assert not inspect.signature(
        compile_cache.enable_persistent_compile_cache
    ).parameters


# ------------------------------------------------------------- chip_smoke


def test_chip_smoke_refuses_a_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_chip_smoke_result_line_has_exactly_the_parsed_keys():
    """Whoever runs chip_smoke.py parses its LAST stdout line and refuses
    any key but ok / device{platform, kind, count}: per-leg detail belongs
    on the summary line before it (a PR was refused for merging the two)."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(chip_smoke.describe_devices(jax.devices()))
    assert "\n" not in line
    got = json.loads(line)
    assert got == {
        "ok": True,
        "device": {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }
    assert isinstance(got["device"]["kind"], str)
    assert type(got["device"]["count"]) is int
    # and main() ends on it: the result line is the last thing printed
    tail = inspect.getsource(chip_smoke.main).rstrip().splitlines()[-2:]
    assert "print(result_line(device)" in tail[0] and "return 0" in tail[1]
