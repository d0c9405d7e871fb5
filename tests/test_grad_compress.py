import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import grad_compress as gc


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=8192), jnp.float32)
    y = gc.compress_roundtrip(x)
    err = float(jnp.max(jnp.abs(x - y)))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert err <= scale * 1.01


def test_error_feedback_unbiased_over_time():
    """Sum of compressed grads with EF converges to sum of true grads."""
    rng = np.random.default_rng(1)
    params = {"w": jnp.zeros((256,), jnp.float32)}
    err = gc.ErrorFeedback.init(params)
    true_sum = np.zeros(256)
    comp_sum = np.zeros(256)
    for i in range(50):
        g = {"w": jnp.asarray(rng.normal(size=256), jnp.float32)}
        cg, err = gc.ErrorFeedback.apply(g, err)
        true_sum += np.asarray(g["w"])
        comp_sum += np.asarray(cg["w"])
    resid = np.abs(true_sum - comp_sum).max()
    # residual stays bounded by one quantization step, not O(n_steps)
    assert resid < 0.2, resid


@pytest.mark.slow
def test_compressed_allreduce_multidevice():
    """int8 all-to-all reduce-scatter + all-gather == plain sum (8 devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.train import grad_compress as gc

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 4096)), jnp.float32)
        reduce_fn = gc.make_compressed_allreduce(mesh, "data")
        out = np.asarray(reduce_fn(g))
        ref = np.asarray(g).sum(axis=0)
        rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.05, rel
        print("ALLREDUCE_OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "ALLREDUCE_OK" in r.stdout, r.stdout + r.stderr
