import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines
from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine, grid_search
from repro.core.kernelfn import KernelSpec
from tests.conftest import make_blobs


def _train_test(n_train=1000, n_test=400, seed=0, sep=1.6, n_features=4):
    x, y = make_blobs(n_train + n_test, n_features=n_features, seed=seed, sep=sep)
    return x[:n_train], y[:n_train], x[n_train:], y[n_train:]


def test_hss_svm_end_to_end_accuracy():
    xtr, ytr, xte, yte = _train_test()
    engine = HSSSVMEngine(
        spec=KernelSpec(h=1.0),
        comp=CompressionParams(rank=32, n_near=64, n_far=96),
        leaf_size=128, max_it=10,
    )
    model = engine.fit(xtr, ytr, c_value=1.0)
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    assert acc > 0.9, acc


def test_hss_matches_dense_exact_kernel_accuracy():
    """Paper's central claim (Tables 2 vs 4/5): approx kernel ≈ exact accuracy."""
    xtr, ytr, xte, yte = _train_test(n_train=512, n_test=256)
    spec = KernelSpec(h=1.0)
    # dense exact-kernel ADMM reference
    z, bias = baselines.dense_admm_fit(
        jnp.asarray(xtr), jnp.asarray(ytr), spec, c_value=1.0, beta=100.0,
        max_it=10)
    pred_dense = baselines.dense_predict(
        jnp.asarray(xtr), jnp.asarray(ytr), z, bias, spec, jnp.asarray(xte))
    acc_dense = float(jnp.mean(pred_dense == yte))
    # HSS
    engine = HSSSVMEngine(
        spec=spec, comp=CompressionParams(rank=32, n_near=64, n_far=96),
        leaf_size=64, max_it=10)
    model = engine.fit(xtr, ytr, c_value=1.0)
    acc_hss = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    assert acc_hss > acc_dense - 0.03, (acc_hss, acc_dense)


def test_padding_is_inert():
    """Non-power-of-two dataset: pads must not change predictions materially."""
    xtr, ytr, xte, yte = _train_test(n_train=600, n_test=200)  # pads to 1024
    engine = HSSSVMEngine(
        spec=KernelSpec(h=1.0), comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=64, max_it=10)
    model = engine.fit(xtr, ytr, c_value=1.0)
    # padded coordinates must carry exactly zero dual weight
    n_pad = model.z_y.shape[0] - 600
    assert n_pad > 0
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    assert acc > 0.88, acc


def test_grid_search_reuses_factorization():
    xtr, ytr, xte, yte = _train_test(n_train=512, n_test=128)
    model, info = grid_search(
        xtr, ytr, xte, yte, hs=[1.0], knobs=[0.1, 1.0, 10.0],
        trainer_kwargs=dict(
            comp=CompressionParams(rank=24, n_near=48, n_far=64),
            leaf_size=64, max_it=10),
    )
    assert info["best_accuracy"] > 0.85
    assert len(info["results"]) == 3
    # compression ran once: all C share the same compression time
    comp_times = {v["compression_s"] for v in info["results"].values()}
    assert len(comp_times) == 1


def test_admm_time_much_smaller_than_compression():
    """Paper Tables 4/5: ADMM Time << Compression time (amortization claim)."""
    xtr, ytr, _, _ = _train_test(n_train=2048, n_test=10)
    engine = HSSSVMEngine(
        spec=KernelSpec(h=1.0), comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=128, max_it=10)
    rep = engine.prepare(xtr, ytr)
    engine.train(1.0)
    # ADMM per-C cost must be below compression+factorization cost
    assert engine.report.admm_s < rep.compression_s + rep.factorization_s


def test_laplacian_kernel_end_to_end():
    """KernelSpec(name='laplacian'): compression -> factorization -> ADMM ->
    predict must work and classify (previously zero coverage)."""
    xtr, ytr, xte, yte = _train_test(n_train=640, n_test=128, seed=3, sep=1.8)
    engine = HSSSVMEngine(
        spec=KernelSpec(name="laplacian", h=2.0),
        comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=64, max_it=10)
    model = engine.fit(xtr, ytr, c_value=1.0)
    assert model.spec.name == "laplacian"
    acc = float(jnp.mean(model.predict(jnp.asarray(xte)) == yte))
    assert acc > 0.85, acc


def test_report_fields():
    xtr, ytr, _, _ = _train_test(n_train=256, n_test=10)
    engine = HSSSVMEngine(
        spec=KernelSpec(h=1.0), comp=CompressionParams(rank=16, n_near=32, n_far=32),
        leaf_size=64, max_it=5)
    rep = engine.prepare(xtr, ytr)
    assert rep.memory_mb > 0
    assert rep.beta == 100.0
