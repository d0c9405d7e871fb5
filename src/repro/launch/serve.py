"""Serving driver: batched LM prefill+decode, or kernel box-QP scoring.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --preset tiny \
      --batch 4 --prompt-len 32 --gen 16

  PYTHONPATH=src python -m repro.launch.serve --task svm \
      --svm-classes 4 --svm-train 8192 --batch 256 --requests 50

  PYTHONPATH=src python -m repro.launch.serve --task svr --batch 256
  PYTHONPATH=src python -m repro.launch.serve --task oneclass --batch 256
  PYTHONPATH=src python -m repro.launch.serve --task krr --batch 256

The kernel paths train their model on ONE shared HSS factorization via the
unified engine (repro.core.engine.HSSSVMEngine; pass --svm-mesh to build
and serve sharded over all local devices), then serve score/predict
requests through the serving tier (``repro.serve``): ``ServingEngine.score``
is the one scoring entry point for every task decode, ``--registry DIR``
round-trips the trained model through the persistent versioned registry
(``--prune-tol`` applies the SV-pruning load transform), and
``--serve-dtype bfloat16`` switches the score path to bf16 block evaluation
with f32 accumulation.  ``--task svm`` is k-class classification; ``--task
svr`` serves ε-SVR regression values on the noisy-sine generator; ``--task
oneclass`` serves ν one-class novelty scores on blobs-with-outliers (the
knobs are --svm-eps / --svm-nu); ``--task krr`` / ``--task gp`` serve kernel
ridge / GP posterior-mean regression values trained by ONE multi-RHS solve
with zero ADMM iterations (the knob is --svm-lam, the ridge/noise λ).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def serve_lm(args) -> None:
    from repro.configs.registry import get_config
    from repro.models.transformer import Model

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = cfg.reduced()
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_len = args.prompt_len + args.gen

    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)),
        jnp.int32)}
    if cfg.frontend == "vision_stub":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(args.batch, cfg.n_prefix_tokens,
                             cfg.frontend_dim)), jnp.float32)
        max_len += cfg.n_prefix_tokens

    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    generated = []
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for _ in range(args.gen):
        generated.append(np.asarray(nxt)[:, 0])
        logits, cache = decode(params, cache, nxt)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    toks = np.stack(generated, axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill*1e3:.1f}ms")
    print(f"decode: {args.gen} steps x batch {args.batch} in "
          f"{t_decode*1e3:.1f}ms "
          f"({args.gen*args.batch/max(t_decode,1e-9):.1f} tok/s)")
    print("sample token ids:", toks[0][:12].tolist())


def serve_svm(args) -> None:
    from repro.core.compression import CompressionParams
    from repro.core.engine import HSSSVMEngine
    from repro.core.kernelfn import KernelSpec
    from repro.data import synthetic

    task = args.task
    n_test = max(args.batch, 512)
    # --svm-h default is task-appropriate for the built-in demo dataset;
    # an explicit value always wins.
    if task == "svr":
        xtr, ytr, xte, yte = synthetic.train_test(
            "noisy_sine", n_train=args.svm_train, n_test=n_test, seed=0,
            noise=0.1)
        knob, h = args.svm_eps, 1.0 if args.svm_h is None else args.svm_h
    elif task in ("krr", "gp"):
        xtr, ytr, xte, yte = synthetic.train_test(
            "noisy_sine", n_train=args.svm_train, n_test=n_test, seed=0,
            noise=0.1)
        knob, h = args.svm_lam, 1.0 if args.svm_h is None else args.svm_h
    elif task == "oneclass":
        xtr, ytr = synthetic.blobs_with_outliers(
            args.svm_train, n_features=4, outlier_frac=0.1, seed=0)
        xte, yte = synthetic.blobs_with_outliers(
            n_test, n_features=4, outlier_frac=0.1, seed=1)
        knob, h = args.svm_nu, 2.0 if args.svm_h is None else args.svm_h
    else:
        xtr, ytr, xte, yte = synthetic.train_test(
            "multiclass_blobs", n_train=args.svm_train, n_test=n_test,
            seed=0, n_classes=args.svm_classes, sep=3.0)
        knob, h = args.svm_c, 1.5 if args.svm_h is None else args.svm_h

    mesh = None
    if args.svm_mesh and jax.device_count() > 1:
        from repro.launch.mesh import make_data_mesh

        mesh = make_data_mesh()
        print(f"mesh-parallel build over {jax.device_count()} devices")

    t0 = time.time()
    engine = HSSSVMEngine(
        spec=KernelSpec(h=h),
        comp=CompressionParams(rank=32, n_near=48, n_far=64),
        leaf_size=256, max_it=30 if task == "oneclass" else 10,
        mesh=mesh, task=task, svr_c=args.svm_c)
    model = engine.fit(xtr, None if task == "oneclass" else ytr,
                       c_value=knob)
    t_train = time.time() - t0
    pred = model.predict(jnp.asarray(xte))
    if task == "svr":
        quality = (f"holdout rmse "
                   f"{float(jnp.sqrt(jnp.mean((pred - yte) ** 2))):.4f}")
        head = f"ε-SVR (ε={knob})"
    elif task in ("krr", "gp"):
        quality = (f"holdout rmse "
                   f"{float(jnp.sqrt(jnp.mean((pred - yte) ** 2))):.4f}, "
                   f"admm iters {engine.report.iters_run}")
        name = "KRR" if task == "krr" else "GP mean"
        head = f"{name} (λ={knob})"
    elif task == "oneclass":
        from repro.core.tasks import oneclass_metrics

        m = oneclass_metrics(pred, yte)
        quality = (f"outlier precision {m['precision']:.3f} / recall "
                   f"{m['recall']:.3f}")
        head = f"one-class SVM (ν={knob})"
    else:
        acc = float(jnp.mean(pred == jnp.asarray(yte)))
        quality = f"holdout acc {acc:.4f}"
        head = f"{args.svm_classes}-class SVM (C={knob})"
    rep = engine.report
    print(f"trained {head} on {args.svm_train} pts "
          f"in {t_train:.1f}s (compress {rep.compression_s:.1f}s / factor "
          f"{rep.factorization_s:.2f}s / batched ADMM {rep.admm_s:.2f}s), "
          f"{quality}")

    registry = None
    if args.registry:
        from repro.serve import ModelRegistry

        registry = ModelRegistry(args.registry)
    _, lat_ms, qps = serve_requests(
        model, xte, args.requests, args.batch, registry=registry,
        prune_tol=args.prune_tol, serve_dtype=args.serve_dtype)
    per_pass = (f"{args.svm_classes} classes" if task == "svm"
                else {"svr": "regression values",
                      "krr": "regression values",
                      "gp": "posterior means",
                      "oneclass": "novelty scores"}[task])
    print(f"served {args.requests} requests x batch {args.batch}: "
          f"{qps:.0f} points/s, latency p50 {lat_ms[len(lat_ms)//2]:.2f}ms "
          f"p95 {lat_ms[int(len(lat_ms)*0.95)-1]:.2f}ms "
          f"({per_pass} per pass)")


def serve_requests(model, xq: np.ndarray, n_requests: int, batch: int, *,
                   registry=None, prune_tol: float | None = None,
                   serve_dtype: str = "float32"):
    """The request loop through the serving tier.

    ONE scoring entry point (``ServingEngine.score``) covers all task
    decodes.  With a ``ModelRegistry`` the model is first round-tripped
    through it (optionally SV-pruned on load).  One warm-up request at the
    request batch shape compiles the scorer outside the timed loop; then
    ``n_requests`` requests of ``batch`` rows drawn from ``xq``.

    Returns ([(row indices, predictions)] per request, sorted latencies in
    ms, points/s over the loop).
    """
    from repro.serve import BatchPolicy, ServingEngine

    serve = ServingEngine(
        policy=BatchPolicy(compute_dtype=serve_dtype), registry=registry)
    if registry is not None:
        version = registry.save(model.task, model)
        print(f"registered model {model.task!r} v{version} under "
              f"{registry.root}")
        mid = serve.load(model.task, prune_tol=prune_tol)
    else:
        mid = serve.add_model(model)

    rng = np.random.default_rng(1)
    serve.score(mid, xq[:batch])                      # compile outside timing

    served = []
    t_serve = time.time()
    for _ in range(n_requests):
        idx = rng.integers(0, xq.shape[0], size=batch)
        _scores, pred = serve.score(mid, xq[idx])
        served.append((idx, pred))
    t_serve = time.time() - t_serve
    lat_ms = np.sort(np.array(serve.drain_latencies())[-n_requests:]) * 1e3
    qps = n_requests * batch / max(t_serve, 1e-9)
    return served, lat_ms, qps


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="lm",
                    choices=["lm", "svm", "svr", "oneclass", "krr", "gp"])
    ap.add_argument("--arch", default=None, help="LM arch (required for lm)")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--svm-classes", type=int, default=4)
    ap.add_argument("--svm-train", type=int, default=8192)
    ap.add_argument("--svm-h", type=float, default=None,
                    help="kernel bandwidth (default: per-task demo value "
                         "1.5 svm / 1.0 svr / 2.0 oneclass)")
    ap.add_argument("--svm-c", type=float, default=1.0,
                    help="C (svm); the SVR box bound (svr)")
    ap.add_argument("--svm-eps", type=float, default=0.1,
                    help="ε tube half-width (task svr)")
    ap.add_argument("--svm-nu", type=float, default=0.1,
                    help="ν outlier-fraction bound (task oneclass)")
    ap.add_argument("--svm-lam", type=float, default=1.0,
                    help="ridge / GP noise λ (tasks krr and gp)")
    ap.add_argument("--svm-mesh", action="store_true",
                    help="mesh-parallel HSS build/serve over all local "
                         "devices (core.engine.HSSSVMEngine)")
    ap.add_argument("--registry", default=None,
                    help="model-registry root: save the trained model there "
                         "and serve it back through the registry")
    ap.add_argument("--prune-tol", type=float, default=None,
                    help="SV-pruning tolerance applied on registry load")
    ap.add_argument("--serve-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="serving-tier kernel block compute dtype")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    if args.task in ("svm", "svr", "oneclass", "krr", "gp"):
        serve_svm(args)
    else:
        if args.arch is None:
            ap.error("--arch is required for --task lm")
        serve_lm(args)


if __name__ == "__main__":
    main()
