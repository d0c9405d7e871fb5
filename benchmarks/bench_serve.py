"""Closed-loop serving bench: batched ticks vs the per-request demo loop.

One trained model per task (binary / k-class OVR / k-class OVO / ε-SVR /
ν one-class), then the same closed-loop request stream — R requests of q
query points each — driven through the serving tier two ways:

  * **loop** — the per-request demo loop the launch CLI used to hand-roll:
    every request is its own tick (bucket = request size), so each pays a
    full dispatch + kernel launch + host decode;
  * **ticks** — request-level dynamic batching: ``max_batch`` queued query
    rows trigger a tick, so 64 requests share ONE multi-column
    ``kernel_matvec_streamed`` launch and one host decode.

Both paths run the SAME jitted scorer (``repro.serve.batched_scores``), so
f32 predictions are bit-identical between them and to the trained model's
own ``predict`` — the recorded ``accuracy`` field is the served-vs-trained
prediction agreement of the batched path, which ci/check_bench.py
hard-gates against the committed reference (accuracy drift in the serving
tier fails CI; p50/p99 latency regressions warn).

Per task the JSON record carries: sustained QPS (query points/s) and
p50/p99 request latency for both paths, the batched-over-loop throughput
gain (the acceptance floor is >= 3x at tick batches of >= 64 requests),
and the shared-cache counters of the batched engine.

Usage: python benchmarks/bench_serve.py --json BENCH_serve.json [--smoke]
The committed BENCH_serve.json is generated with --smoke (the scale the
ci/run_tests.sh --bench tier reruns, so the guard compares like to like).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import CompressionParams
from repro.core.engine import HSSSVMEngine
from repro.core.kernelfn import KernelSpec
from repro.data import synthetic
from repro.serve import BatchPolicy, ServingEngine
from repro.launch.cache import use_compile_cache

COMP = CompressionParams(rank=32, n_near=48, n_far=64)

# (case, engine task, strategy, h, knob): the four box-QP task families,
# with k-class served both ways (OVR argmax and OVO vote decode).
TASK_CASES = [
    ("binary", "svm", "ovr", 1.2, 1.0),
    ("ovr", "svm", "ovr", 1.5, 1.0),
    ("ovo", "svm", "ovo", 1.5, 1.0),
    ("svr", "svr", "ovr", 1.0, 0.1),
    ("oneclass", "oneclass", "ovr", 2.0, 0.1),
]

N_REQUESTS = 256          # closed-loop request count per path
QUERIES_PER_REQUEST = 2   # small per-request payload — the batching regime
TICK_REQUESTS = 64        # requests per batched tick (>= the acceptance 64)

JSON_RECORDS: list[dict] = []


def _record(case: str, **kw) -> dict:
    rec = dict(case=case, **kw)
    JSON_RECORDS.append(rec)
    return rec


def _train(case, task, strategy, h, knob, n_train, n_test):
    if case == "binary":
        xtr, ytr, xte, _ = synthetic.train_test(
            "blobs", n_train, n_test, seed=0, n_features=6, sep=2.0)
    elif case in ("ovr", "ovo"):
        xtr, ytr, xte, _ = synthetic.train_test(
            "multiclass_blobs", n_train, n_test, seed=0, n_classes=4,
            sep=3.0)
    elif case == "svr":
        xtr, ytr, xte, _ = synthetic.train_test(
            "noisy_sine", n_train, n_test, seed=0, noise=0.1)
    else:
        xtr, _ = synthetic.blobs_with_outliers(
            n_train, n_features=4, outlier_frac=0.1, seed=0)
        xte, _ = synthetic.blobs_with_outliers(
            n_test, n_features=4, outlier_frac=0.1, seed=1)
        ytr = None
    eng = HSSSVMEngine(
        spec=KernelSpec(h=h), comp=COMP, leaf_size=128,
        max_it=30 if task == "oneclass" else 10, task=task,
        strategy=strategy, svr_c=2.0 if task == "svr" else 1.0)
    model = eng.fit(xtr, ytr, c_value=knob)
    return model, np.asarray(xte, np.float32)


def _percentiles_ms(latencies: list[float]) -> tuple[float, float]:
    lat = np.sort(np.asarray(latencies)) * 1e3
    p50 = float(lat[len(lat) // 2])
    p99 = float(lat[min(int(np.ceil(len(lat) * 0.99)) - 1, len(lat) - 1)])
    return p50, p99


def _requests(xte: np.ndarray, n_requests: int, q: int, seed: int = 1):
    r = np.random.default_rng(seed)
    idx = r.integers(0, xte.shape[0], size=(n_requests, q))
    return [xte[i] for i in idx]


def _agreement(preds: list[np.ndarray], ref: np.ndarray) -> float:
    got = np.concatenate([np.asarray(p).reshape(-1) for p in preds])
    if np.issubdtype(ref.dtype, np.floating) and not np.issubdtype(
            got.dtype, np.integer):
        # svr: regression values — agreement is exact f32 match
        return float(np.mean(got == ref))
    return float(np.mean(got == ref))


def bench_task(case, task, strategy, h, knob, scale: float) -> dict:
    n_train = max(int(4096 * scale), 512)
    n_test = 1024
    model, xte = _train(case, task, strategy, h, knob, n_train, n_test)
    q = QUERIES_PER_REQUEST
    reqs = _requests(xte, N_REQUESTS, q)
    all_rows = np.concatenate(reqs, axis=0)
    ref_preds = np.asarray(model.predict(jnp.asarray(all_rows))).reshape(-1)

    # --- per-request demo loop: one tick (and one launch) per request ----
    loop = ServingEngine(policy=BatchPolicy(buckets=(q,)))
    mid = loop.add_model(model)
    loop.score(mid, reqs[0])                    # compile outside timing
    loop.drain_latencies()
    preds_loop = []
    t0 = time.perf_counter()
    for xq in reqs:
        _, p = loop.score(mid, xq)
        preds_loop.append(p)
    loop_s = time.perf_counter() - t0
    loop_p50, loop_p99 = _percentiles_ms(loop.drain_latencies())
    loop_qps = N_REQUESTS * q / loop_s

    # --- batched ticks: max_batch rows of queued requests per launch -----
    tick_rows = TICK_REQUESTS * q
    ticks = ServingEngine(policy=BatchPolicy(
        max_batch=tick_rows, buckets=(tick_rows,)))
    mid = ticks.add_model(model)
    ticks.score(mid, np.concatenate(reqs[:TICK_REQUESTS]))  # compile
    ticks.drain_latencies()
    t0 = time.perf_counter()
    tickets = [ticks.submit(mid, xq) for xq in reqs]  # max_batch auto-ticks
    ticks.flush()                                     # drain the remainder
    ticks_s = time.perf_counter() - t0
    preds_ticks = [t.result(timeout=0)[1] for t in tickets]
    tick_p50, tick_p99 = _percentiles_ms(ticks.drain_latencies())
    tick_qps = N_REQUESTS * q / ticks_s
    stats = ticks.stats()

    agree_ticks = _agreement(preds_ticks, ref_preds)
    agree_loop = _agreement(preds_loop, ref_preds)
    speedup = tick_qps / max(loop_qps, 1e-9)
    rec = _record(
        f"serve/{case}",
        n_train=n_train, task=task, strategy=strategy,
        requests=N_REQUESTS, queries_per_request=q,
        tick_requests=TICK_REQUESTS,
        accuracy=agree_ticks,             # served-vs-trained, hard-gated
        agreement_loop=agree_loop,
        qps=tick_qps, loop_qps=loop_qps, speedup=speedup,
        p50_ms=tick_p50, p99_ms=tick_p99,
        loop_p50_ms=loop_p50, loop_p99_ms=loop_p99,
        launches=stats["launches"], support_uploads=stats["support_uploads"],
    )
    print(f"serve/{case}: loop {loop_qps:.0f} q/s "
          f"(p50 {loop_p50:.2f}ms p99 {loop_p99:.2f}ms) -> ticks "
          f"{tick_qps:.0f} q/s (p50 {tick_p50:.2f}ms p99 {tick_p99:.2f}ms) "
          f"= {speedup:.1f}x, agreement {agree_ticks:.4f}")
    return rec


def bench_shared_cache(scale: float) -> None:
    """The factorization-sharing economy at serve time: k same-(h, β)
    models behind one engine = ONE support upload and one launch per tick,
    vs one per model without sharing."""
    n_train = max(int(4096 * scale), 512)
    xtr, ytr, xte, _ = synthetic.train_test(
        "blobs", n_train, 512, seed=0, n_features=6, sep=2.0)
    eng = HSSSVMEngine(spec=KernelSpec(h=1.2), comp=COMP, leaf_size=128,
                       max_it=10)
    eng.prepare(xtr, ytr)
    models = eng.train_grid([0.25, 0.5, 1.0, 2.0])

    serve = ServingEngine()
    ids = [serve.add_model(m) for m in models]
    xq = np.asarray(xte[:64], np.float32)
    for i in ids:
        serve.submit(i, xq)
    serve.flush()
    st = serve.stats()
    xs_bytes = int(np.asarray(jax.device_get(models[0].x_perm)).nbytes)
    _record(
        "serve/shared_cache",
        n_train=n_train, n_models=len(models),
        cache_entries=st["cache_entries"],
        support_uploads=st["support_uploads"],
        launches=st["launches"],
        resident_support_bytes=st["resident_support_bytes"],
        unshared_support_bytes=xs_bytes * len(models),
    )
    print(f"serve/shared_cache: {len(models)} models -> "
          f"{st['cache_entries']} cache entry, {st['support_uploads']} "
          f"upload, {st['launches']} launch/tick, "
          f"{st['resident_support_bytes']}B resident "
          f"(vs {xs_bytes * len(models)}B unshared)")


def write_json(path: str) -> None:
    payload = dict(
        n_devices=jax.device_count(),
        backend=jax.default_backend(),
        results=JSON_RECORDS,
    )
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {len(JSON_RECORDS)} records to {path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable output path")
    ap.add_argument("--smoke", action="store_true",
                    help="toy training sizes — the ci/run_tests.sh --bench "
                         "tier (the committed reference scale)")
    args = ap.parse_args()
    use_compile_cache()

    scale = 0.125 if args.smoke else 1.0
    for case, task, strategy, h, knob in TASK_CASES:
        bench_task(case, task, strategy, h, knob, scale)
    bench_shared_cache(scale)
    write_json(args.json)
