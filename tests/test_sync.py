"""host_sync (utils/sync.py): the honest timing barrier.

It must return only after the probed computation retired; a CPU run
cannot show a step still in flight on a device, but it can pin the
contract: it touches every leaf, tolerates Nones/empty trees/python
scalars, and returns a finite float.
"""

import jax
import jax.numpy as jnp
import numpy as np

from ps_pytorch_tpu.utils import host_sync


def test_host_sync_touches_all_leaves():
    tree = {"a": jnp.ones((4, 4)), "b": [jnp.zeros((2,)), jnp.full((3,), 2.0)]}
    out = host_sync(tree)
    assert np.isfinite(out)
    # probe = sum of first elements: 1 + 0 + 2
    assert out == 3.0


def test_host_sync_handles_none_scalars_and_empty():
    assert host_sync({}) == 0.0
    assert host_sync(None) == 0.0
    tree = {"x": None, "y": jnp.asarray(5.0), "z": 7}  # python int: no dtype
    assert host_sync(tree) == 5.0


def test_host_sync_multiple_trees():
    a = {"p": jnp.asarray([1.0, 9.0])}
    b = (jnp.asarray([[2.0]]), None)
    assert host_sync(a, b) == 3.0


def test_host_sync_serializes_pending_work():
    # after host_sync returns, the computation's result must be readable
    # with no further device work (smoke: value is correct)
    x = jnp.ones((64, 64))
    square = jax.jit(jnp.matmul)
    y = square(x, x)
    host_sync(y)
    assert float(y[0, 0]) == 64.0
