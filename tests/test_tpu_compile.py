"""Real-size compiles for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology lets Mosaic and XLA compile for one of its chips.  That
catches what interpret-mode tests cannot — block shapes off the (8, 128)
tiling, primitives Mosaic cannot lower, programs that do not fit the
device's memory — at no chip time.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and each test worker imports every
test file.  The persistent compilation cache is off around these compiles:
an entry written for a described device cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import admm as admm_mod
from repro.core import compression
from repro.core import factorization
from repro.core.hss import HSSMatrix
from repro.kernels.compress.kernel import fused_assemble_id_pallas
from repro.kernels.compress.laplacian import laplacian_block_pallas
from repro.kernels.gaussian.kernel import gaussian_block_pallas

HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


# The accurate preset's leaf stage (CompressionParams.accurate: rank 64,
# 64 NEAR + 128 FAR proxies) at leaf size 256, features padded to 128 lanes.
ACC_K, ACC_M, ACC_S, F_PAD = 64, 256, 192, 128


@pytest.mark.parametrize("kernel_name", ["gaussian", "laplacian"])
def test_fused_assemble_id_compiles(one_chip, kernel_name):
    b = 32
    compiled, hlo = _compile(
        lambda xc, xp, cm: fused_assemble_id_pallas(
            xc, xp, cm, kernel_name=kernel_name, h=1.0, k=ACC_K,
            m_real=ACC_M, s_real=ACC_S, f_real=18),
        _sds(one_chip, (b, ACC_M, F_PAD)), _sds(one_chip, (b, ACC_S, F_PAD)),
        _sds(one_chip, (b, 1, ACC_M)))
    assert "tpu_custom_call" in hlo
    # Mosaic refuses a kernel whose blocks overrun VMEM at compile time;
    # what memory_analysis reports is the HBM side: R (k, m) f32 and the
    # pivots per node, tile-padded.
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= b * ACC_K * (ACC_M * 4 + 4)


@pytest.mark.parametrize("block_fn", [
    pytest.param(lambda a, b: gaussian_block_pallas(a, b, 1.0),
                 id="gaussian"),
    pytest.param(lambda a, b: laplacian_block_pallas(a, b, 1.0, f_real=18),
                 id="laplacian"),
])
def test_block_kernel_compiles(one_chip, block_fn):
    x = _sds(one_chip, (1024, F_PAD))
    _, hlo = _compile(block_fn, x, x)
    assert "tpu_custom_call" in hlo


def _hss_struct(sharding, n, m, r, f):
    """HSSMatrix of shape structs for a fixed-rank build of n points."""
    levels = int(np.log2(n // m))
    i32 = jnp.int32
    return HSSMatrix(
        x=_sds(sharding, (n, f)),
        d_leaf=_sds(sharding, (n // m, m, m)),
        u_leaf=_sds(sharding, (n // m, m, r)),
        skel_leaf=_sds(sharding, (n // m, r), i32),
        transfers=tuple(_sds(sharding, (2 ** (levels - k), 2 * r, r))
                        for k in range(1, levels)),
        skels=tuple(_sds(sharding, (2 ** (levels - k), r), i32)
                    for k in range(1, levels)),
        b_mats=tuple(_sds(sharding, (2 ** (levels - k), r, r))
                     for k in range(1, levels + 1)),
        levels=levels, leaf_size=m)


def _admm_fits_one_chip(one_chip, n, f, columns):
    """The engine's ADMM program (10 iterations of the HSS solve on the
    warm-started C grid's state) for ``columns`` dual columns, leaf 256,
    rank 32."""
    m, r, beta, max_it = 256, 32, 100.0, 10
    hss = _hss_struct(one_chip, n, m, r, f)
    fac = jax.eval_shape(lambda h: factorization.factorize(h, beta), hss)
    fac = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), fac)

    def run(fac_, ys, pmask, knob, z0, mu0):
        task = admm_mod.svm_task(ys, knob * pmask)
        state, trace = admm_mod.admm_boxqp(
            fac_.solve_mat, task, fac_.beta, max_it, z0=z0, mu0=mu0)
        return state.z, state.mu, trace.iters_run

    compiled, _ = _compile(
        run, fac, _sds(one_chip, (columns, n)), _sds(one_chip, (columns, n)),
        _sds(one_chip, ()), _sds(one_chip, (n, columns)),
        _sds(one_chip, (n, columns)))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    # the leaf factors alone: G (n, m) and E (n, r) in f32
    assert mem.argument_size_in_bytes >= n * (m + r) * 4
    assert total < HBM_BYTES, total


def test_admm_run_at_2_20_rows_fits_one_chip(one_chip):
    _admm_fits_one_chip(one_chip, 2 ** 20, 18, 1)


def test_admm_run_with_seven_ovr_columns_fits_one_chip(one_chip):
    """The ``covtype.train`` size: 2^16 rows of 54 features, 7 columns."""
    _admm_fits_one_chip(one_chip, 2 ** 16, 54, 7)


@pytest.mark.parametrize("n", [2 ** 16, 2 ** 20])
def test_device_knn_fits_one_chip(one_chip, n):
    """The NEAR search's exact k-NN program for 18-feature rows: at the
    ``susy.train`` size and at 2^20 rows, with the block it chooses."""
    k, block = 4, compression._knn_block(n)
    assert block * n * 4 <= compression._KNN_TILE_BYTES
    compiled, _ = _compile(
        lambda x: compression._device_knn(x, k=k, block=block),
        _sds(one_chip, (n, 18)))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.output_size_in_bytes >= n * k * 4
    assert total < HBM_BYTES, total


def test_device_knn_fits_one_chip_on_covtype_rows(one_chip):
    """The k-NN program for the ``covtype.train`` rows: 2^16 x 54."""
    n, k = 2 ** 16, 4
    compiled, _ = _compile(
        lambda x: compression._device_knn(
            x, k=k, block=compression._knn_block(n)),
        _sds(one_chip, (n, 54)))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
