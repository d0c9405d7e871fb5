"""Serving-tier tests: parity, the shared-factorization cache, batching.

The synthetic ``EngineModel``s here skip training on purpose — scoring is
a pure function of (x_perm, z_y, biases, spec), so random coefficients
exercise every decode path at zero build cost.  The one trained model
(``trained_binary``) is reserved for the tests that need real dual
structure (the warm C-sweep shared-cache proof)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineModel
from repro.core.kernelfn import DEFAULT_SCORE_BLOCK, KernelSpec
from repro.serve import BatchPolicy, ServingEngine, batched_scores

TASKS = ("binary", "ovr", "ovo", "svr", "oneclass", "krr", "gp")


def mk_model(task="binary", d=96, f=4, h=1.3, beta=64.0, seed=0):
    """A synthetic EngineModel of the given task shape (no training)."""
    r = np.random.default_rng(seed)
    x = r.normal(size=(d, f)).astype(np.float32)
    n_prob = 3 if task in ("ovr", "ovo") else 1
    zy = (0.3 * r.normal(size=(d, n_prob))).astype(np.float32)
    biases = (0.1 * r.normal(size=n_prob)).astype(np.float32)
    classes = (np.arange(3.0, dtype=np.float32) if n_prob == 3
               else np.array([-1.0, 1.0], np.float32))
    pairs = (np.array([[0, 1], [0, 2], [1, 2]], np.int32)
             if task == "ovo" else None)
    return EngineModel(
        x_perm=jnp.asarray(x), z_y=jnp.asarray(zy),
        biases=jnp.asarray(biases), classes=classes,
        spec=KernelSpec(h=h), c_value=1.0,
        binary=task == "binary",
        strategy="ovo" if task == "ovo" else "ovr",
        task=task if task in ("svr", "oneclass", "krr", "gp") else "svm",
        pairs=pairs, beta=beta)


def _queries(model, n=37, seed=1):
    r = np.random.default_rng(seed)
    return r.normal(size=(n, model.x_perm.shape[1])).astype(np.float32)


# --------------------------------------------------------------------- #
# scoring parity                                                         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("task", TASKS)
def test_f32_parity_bit_identical(task):
    """The engine's f32 tick path IS kernel_matvec_streamed: scores and
    predictions must equal the model's own predict bit for bit."""
    model = mk_model(task, seed=3)
    engine = ServingEngine()
    mid = engine.add_model(model)
    xq = _queries(model)
    scores, preds = engine.score(mid, xq)
    ref_s = np.asarray(model.decision_function(jnp.asarray(xq)))
    ref_p = np.asarray(model.predict(jnp.asarray(xq)))
    assert scores.shape == ref_s.shape
    assert np.array_equal(scores, ref_s)
    assert np.array_equal(preds, ref_p)


# pinned bf16 tolerance: block kernel evaluated from bf16 operands with f32
# accumulation — relative score error is bounded by a few bf16 ulps (~0.4%)
# times the kernel-sum conditioning; 2e-2 absolute on O(1) scores holds with
# ~4x margin on these problems (see measured maxima in test body asserts).
BF16_ATOL = 2e-2


@pytest.mark.parametrize("task", TASKS)
def test_bf16_parity_tolerance(task):
    model = mk_model(task, seed=5)
    f32 = ServingEngine()
    b16 = ServingEngine(policy=BatchPolicy(compute_dtype="bfloat16"))
    i32, i16 = f32.add_model(model), b16.add_model(model)
    xq = _queries(model, n=64)
    s32, p32 = f32.score(i32, xq)
    s16, p16 = b16.score(i16, xq)
    np.testing.assert_allclose(s16, s32, atol=BF16_ATOL)
    # decisions may legitimately flip only within the tolerance band of a
    # decision boundary; away from it they must agree
    if task in ("svr", "krr", "gp"):
        np.testing.assert_allclose(p16, p32, atol=BF16_ATOL)
    else:
        margin = (np.min(np.abs(s32), axis=-1) if s32.ndim > 1
                  else np.abs(s32))
        clear = margin > BF16_ATOL
        assert np.array_equal(np.asarray(p16)[clear],
                              np.asarray(p32)[clear])


def test_bf16_path_has_no_downcast_accumulators():
    """Dogfood repro.analysis on the batched score function itself: the
    bf16 path must accumulate every contraction in f32 and stay
    callback-free (satellite of the PR 3 precision convention)."""
    from repro.analysis import jaxpr_check

    model = mk_model("ovr")
    xq = jnp.asarray(_queries(model, n=16))
    for dt in ("float32", "bfloat16"):
        jaxpr = jax.make_jaxpr(
            lambda q, s, z, b: batched_scores(
                q, s, z, b, spec=model.spec, block=8, compute_dtype=dt)
        )(xq, model.x_perm, model.z_y, model.biases)
        assert jaxpr_check.dtype_downcasts(jaxpr) == []
        assert jaxpr_check.host_callbacks(jaxpr) == []


def test_laplacian_kernel_serves_too():
    model = dataclasses.replace(
        mk_model("binary"), spec=KernelSpec(name="laplacian", h=1.5))
    engine = ServingEngine()
    mid = engine.add_model(model)
    xq = _queries(model)
    scores, _ = engine.score(mid, xq)
    ref = np.asarray(model.decision_function(jnp.asarray(xq)))
    assert np.array_equal(scores, ref)


# --------------------------------------------------------------------- #
# the shared-factorization cache                                         #
# --------------------------------------------------------------------- #
def test_same_factorization_models_share_one_cache_entry(trained_binary):
    """k models off one warm C-sweep (same compression+factorization ⇒
    same (h, β, support set)) must occupy exactly ONE device-resident
    cache entry: one support upload, one launch scoring all of them."""
    eng, _, xq, _ = trained_binary
    models = eng.train_grid([0.5, 1.0, 2.0])
    serve = ServingEngine()
    ids = [serve.add_model(m) for m in models]
    assert serve.stats()["groups"] == 1

    tickets = [serve.submit(i, xq) for i in ids]
    assert serve.flush() == len(ids)
    st = serve.stats()
    assert st["cache_entries"] == 1
    assert st["support_uploads"] == 1          # k models, ONE upload
    assert st["launches"] == 1                 # k models, ONE kernel pass
    # the memory proof: resident bytes = one support copy, not k
    xs = np.asarray(jax.device_get(models[0].x_perm))
    assert st["resident_support_bytes"] == xs.nbytes
    group = serve.model_group(ids[0])
    assert all(serve.model_group(i) is group for i in ids)

    for t, m in zip(tickets, models):
        scores, preds = t.result(timeout=0)
        assert np.array_equal(scores,
                              np.asarray(m.decision_function(jnp.asarray(xq))))
        assert np.array_equal(preds, np.asarray(m.predict(jnp.asarray(xq))))


def test_distinct_bandwidths_do_not_share():
    a = mk_model("binary", seed=1, h=1.0)
    b = dataclasses.replace(a, spec=KernelSpec(h=2.0))
    serve = ServingEngine()
    serve.add_model(a), serve.add_model(b)
    assert serve.stats()["groups"] == 2


def test_lru_eviction_drops_device_state_only():
    serve = ServingEngine(max_resident=1)
    ia = serve.add_model(mk_model("binary", seed=1, h=1.0))
    ib = serve.add_model(mk_model("binary", seed=2, h=2.0))
    xq = _queries(mk_model("binary"))
    ra1 = serve.score(ia, xq)
    rb = serve.score(ib, xq)            # evicts a's device arrays
    st = serve.stats()
    assert st["cache_entries"] == 1 and st["evictions"] == 1
    ra2 = serve.score(ia, xq)           # transparent re-upload, b evicted
    st = serve.stats()
    assert st["support_uploads"] == 3 and st["evictions"] == 2
    assert np.array_equal(ra1[0], ra2[0])
    assert rb[0].shape == ra1[0].shape


# --------------------------------------------------------------------- #
# dynamic batching                                                       #
# --------------------------------------------------------------------- #
def test_tick_deinterleaves_mixed_requests():
    """Requests of different sizes and different same-group models in one
    tick come back correctly sliced per request and per model."""
    base = mk_model("ovr", seed=7)
    other = dataclasses.replace(           # same group: same spec/beta/xs
        base, z_y=base.z_y * 0.5, biases=base.biases + 1.0)
    serve = ServingEngine()
    i1, i2 = serve.add_model(base), serve.add_model(other)
    reqs = [(i1, _queries(base, n=5, seed=21)),
            (i2, _queries(base, n=17, seed=22)),
            (i1, _queries(base, n=1, seed=23)),
            (i2, _queries(base, n=30, seed=24))]
    tickets = [serve.submit(i, q) for i, q in reqs]
    assert serve.flush() == 4
    assert serve.stats()["launches"] == 1      # one pass for the whole tick
    for (mid, q), t in zip(reqs, tickets):
        m = base if mid == i1 else other
        scores, preds = t.result(timeout=0)
        assert np.array_equal(
            scores, np.asarray(m.decision_function(jnp.asarray(q))))
        assert np.array_equal(preds, np.asarray(m.predict(jnp.asarray(q))))


def test_occupancy_pads_to_buckets_one_compile_each():
    model = mk_model("binary", d=64)
    serve = ServingEngine(policy=BatchPolicy(buckets=(16, 64), block=32))
    mid = serve.add_model(model)
    for occ in (1, 3, 7, 11, 16, 20, 40, 64):
        serve.score(mid, _queries(model, n=occ, seed=occ))
    compiles = serve.scorer_compiles()
    assert compiles is None or compiles == 2, (
        f"8 occupancies over 2 buckets compiled {compiles}x")


def test_oversize_tick_chunks_at_top_bucket():
    model = mk_model("binary", d=64)
    serve = ServingEngine(policy=BatchPolicy(buckets=(16, 32), block=32))
    mid = serve.add_model(model)
    xq = _queries(model, n=70)              # 3 chunks: 32 + 32 + pad(6->16)
    scores, _ = serve.score(mid, xq)
    ref = np.asarray(model.decision_function(jnp.asarray(xq)))
    assert np.array_equal(scores, ref)
    assert serve.stats()["launches"] == 3


def test_max_batch_triggers_tick_without_flush():
    model = mk_model("binary", d=64)
    serve = ServingEngine(policy=BatchPolicy(max_batch=8, buckets=(16,)))
    mid = serve.add_model(model)
    t1 = serve.submit(mid, _queries(model, n=4, seed=1))
    assert not t1.done
    t2 = serve.submit(mid, _queries(model, n=4, seed=2))  # hits max_batch
    assert t1.done and t2.done


def test_threaded_driver_resolves_without_manual_flush():
    model = mk_model("binary", d=64)
    serve = ServingEngine(policy=BatchPolicy(max_wait_ms=1.0))
    mid = serve.add_model(model)
    serve.start()
    try:
        tickets = [serve.submit(mid, _queries(model, n=3, seed=s))
                   for s in range(5)]
        for t in tickets:
            scores, preds = t.result(timeout=10.0)
            assert scores.shape == (3,)
    finally:
        serve.stop()
    assert not serve.running


# --------------------------------------------------------------------- #
# decode details                                                         #
# --------------------------------------------------------------------- #
def test_ovo_host_decode_matches_device_vote():
    """The tick's numpy OVO decode must replicate tasks.ovo_vote's
    tie-break (votes + 1e-3·tanh(margin)) exactly."""
    from repro.core.tasks import ovo_vote
    from repro.serve.engine import _ovo_vote_np

    r = np.random.default_rng(9)
    pairs = np.array([[a, b] for a in range(4) for b in range(a + 1, 4)],
                     np.int32)
    scores = r.normal(size=(50, pairs.shape[0])).astype(np.float32)
    # include exact-tie rows (all-zero scores) and near-tie rows
    scores[0] = 0.0
    scores[1, :] = 1e-6
    dev = np.asarray(ovo_vote(jnp.asarray(scores), pairs, 4))
    host = _ovo_vote_np(scores, pairs, 4)
    assert np.array_equal(dev, host)


def test_block_kwarg_is_one_shared_constant():
    """Satellite: every predict/score path defaults to the ONE streaming
    block constant."""
    import inspect

    from repro.core.kernelfn import kernel_matvec_streamed

    for fn in (EngineModel.predict, EngineModel.decision_function):
        assert inspect.signature(fn).parameters["block"].default \
            == DEFAULT_SCORE_BLOCK, fn
    assert inspect.signature(kernel_matvec_streamed).parameters[
        "block"].default == DEFAULT_SCORE_BLOCK
    assert BatchPolicy().block == DEFAULT_SCORE_BLOCK
