"""Pin the timing-fence contract.

A wall is only as good as its fence: these tests pin the two halves of
``value_fence``'s contract:

* ``value_fence`` fetches the VALUE of the last pytree leaf by direct
  buffer copy, and for a solver step's ``(variables, slots, loss)``
  output that leaf IS the loss — so the fetched scalar has data
  dependence on the whole step (ref integrity model:
  caffe/src/caffe/util/benchmark.cpp:18-82 — the Timer exists so walls
  are real).
* Large last leaves raise instead of silently timing a multi-MB
  device-to-host copy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.common import value_fence
from sparknet_tpu.proto import parse
from sparknet_tpu.solvers import Solver, SolverConfig

TINY_NET = """
name: "fence_net"
layer { name: "data" type: "MemoryData" top: "data" top: "target"
        memory_data_param { batch_size: 4 channels: 3 height: 1 width: 1 } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "pred"
        inner_product_param { num_output: 1 weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "pred" bottom: "target" top: "loss" }
"""


def _feeds():
    rs = np.random.RandomState(0)
    return {
        "data": jnp.asarray(rs.randn(4, 3, 1, 1), jnp.float32),
        "target": jnp.asarray(rs.randn(4, 1), jnp.float32),
    }


@pytest.mark.smoke
def test_fence_leaf_is_the_loss():
    """The fenced scalar of a train-step output equals the step's loss —
    i.e. the fence has data dependence on the full computation, not on
    an incidental leaf."""
    solver = Solver(SolverConfig(base_lr=0.1, solver_type="SGD"),
                    parse(TINY_NET), feed_shapes={"target": (4, 1)})
    step, v, s, key = solver.jitted_train_step(donate=False)
    out = step(v, s, 0, _feeds(), key)
    _, _, loss = out
    fenced = value_fence(out)
    assert fenced == float(np.asarray(loss))
    # and the last leaf of the full output pytree is exactly that loss
    last = jax.tree_util.tree_leaves(out)[-1]
    assert np.asarray(last) == np.asarray(loss)


def test_fence_rejects_large_leaf():
    """A big trailing leaf (e.g. fencing raw logits) is an error, not a
    silent multi-MB copy inside a timed region."""
    big = jnp.zeros((512, 1024), jnp.float32)
    with pytest.raises(ValueError, match="last leaf"):
        value_fence((1.0, big))


def test_fence_fetches_value_not_readiness():
    """The fence returns the numeric value of the scalar — a caller can
    (and bench.py does) assert finiteness on it."""
    assert value_fence(jnp.float32(2.5)) == 2.5
    assert value_fence((jnp.zeros((3,)), jnp.float32(7.0))) == 7.0
