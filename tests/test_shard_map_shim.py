"""The shard_map wrapper (distribution/context.py) must execute on the
installed JAX. This runs in-process over a 1-device mesh — no
subprocess, no ``slow`` marker — so the fast CI subset exercises the
``check_vma`` call at call time, not just at import. The multi-device
semantics are covered by tests/test_distribution.py (slow)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distribution import context as dctx
from repro.launch.mesh import auto_mesh


def test_shim_executes_on_current_jax():
    """Calling through the wrapper must construct ``jax.shard_map`` with
    the kwarg the installed JAX takes — a wrong kwarg raises here."""
    mesh = auto_mesh((1,), ("model",))
    x = jnp.arange(8.0, dtype=jnp.float32).reshape(2, 4)

    def body(xx):
        return jax.lax.psum(xx, "model")

    fn = dctx.shard_map(body, mesh=mesh, in_specs=P(None, None),
                        out_specs=P(None, None))
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(x)),
                                  np.asarray(x))
