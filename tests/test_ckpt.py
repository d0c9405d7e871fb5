import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import (CheckpointManager, latest_step,
                                   load_checkpoint, load_checkpoint_arrays,
                                   save_checkpoint)


def _tree(seed=0):
    r = np.random.default_rng(seed)
    return {
        "params": {
            "w": jnp.asarray(r.normal(size=(16, 8)), jnp.float32),
            "b": jnp.asarray(r.normal(size=(8,)), jnp.bfloat16),
        },
        "opt": {"m": jnp.asarray(r.normal(size=(16, 8)), jnp.float32),
                "step": jnp.asarray(3, jnp.int32)},
    }


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la, np.float32),
                                      np.asarray(lb, np.float32))


def test_save_load_roundtrip():
    tree = _tree()
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, tree, step=7, n_shards=3)
        out, step = load_checkpoint(d, tree)
        assert step == 7
        _assert_tree_equal(tree, out)


def test_latest_step_and_retention():
    tree = _tree()
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save_async(tree, s)
            mgr.wait()
        assert latest_step(d) == 4
        steps = sorted(int(x.split("_")[1]) for x in os.listdir(d))
        assert steps == [3, 4]


def test_load_checkpoint_arrays_template_free():
    """Template-free restore: flat host-numpy dicts (the streamed HSS
    build's level state) round-trip bit-exactly WITH their extra metadata,
    without the caller supplying a pytree template or touching a device."""
    state = {
        "d_leaf": np.arange(24, dtype=np.float32).reshape(4, 6),
        "skel": np.arange(8, dtype=np.int32),
        "ranks": np.asarray([3, 2, 3, 1], np.int32),
    }
    fp = dict(kind="hss_streamed_build", n=128, h=1.5)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, state, step=2, n_shards=3, extra=fp)
        arrays, step, extra = load_checkpoint_arrays(d)
        assert step == 2
        assert extra == fp                      # JSON round-trip preserved
        assert set(arrays) == set(state)
        for k in state:
            assert isinstance(arrays[k], np.ndarray)
            assert arrays[k].dtype == state[k].dtype
            np.testing.assert_array_equal(arrays[k], state[k])


def test_shard_count_independence():
    """A checkpoint written with N shards restores from any reader."""
    tree = _tree(1)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, tree, step=1, n_shards=7)
        out, _ = load_checkpoint(d, tree)
        _assert_tree_equal(tree, out)


def test_training_resume_bit_exact():
    """Interrupted-and-resumed training == uninterrupted training."""
    from repro.configs import get_config
    from repro.models.transformer import Model
    from repro.train import optim
    from repro.train.step import make_train_step
    from repro.data.tokens import batch_for_config

    cfg = get_config("deepseek-coder-33b").reduced()
    model = Model(cfg)
    step_fn = jax.jit(make_train_step(model))

    def run(n_steps, state):
        for s in range(state.get("_step", 0), n_steps):
            batch = jax.tree.map(
                jnp.asarray, batch_for_config(cfg, 2, 32, s))
            p, o, _ = step_fn(state["params"], state["opt"], batch)
            state = {"params": p, "opt": o, "_step": s + 1}
        return state

    params = model.init(jax.random.PRNGKey(0))
    s0 = {"params": params, "opt": optim.adamw_init(params), "_step": 0}

    # uninterrupted 6 steps
    ref = run(6, dict(s0))

    # interrupted at 3 + checkpoint + restore + continue
    with tempfile.TemporaryDirectory() as d:
        mid = run(3, dict(s0))
        save_checkpoint(d, {"params": mid["params"], "opt": mid["opt"]},
                        step=3)
        restored, step = load_checkpoint(
            d, {"params": mid["params"], "opt": mid["opt"]})
        resumed = run(6, {"params": restored["params"],
                          "opt": restored["opt"], "_step": step})
    _assert_tree_equal(ref["params"], resumed["params"])


@pytest.mark.slow
def test_elastic_reshard_subprocess():
    """Save under an 8-device mesh, restore under a 4-device mesh."""
    import subprocess, sys, textwrap

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, tempfile
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.checkpoint import save_checkpoint, load_checkpoint

        mesh8 = make_mesh((4, 2), ("data", "model"))
        arr = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        sharded = jax.device_put(arr, NamedSharding(mesh8, P("data", "model")))
        tree = {"w": sharded}
        d = tempfile.mkdtemp()
        save_checkpoint(d, tree, step=1)

        mesh4 = make_mesh((2, 2), ("data", "model"))
        sh4 = {"w": NamedSharding(mesh4, P("model", "data"))}
        out, step = load_checkpoint(d, tree, shardings=sh4)
        np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(arr))
        assert out["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr
