"""Training driver: LM substrate runs and mesh-parallel SVM training.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-9b --preset tiny \
      --steps 50 --ckpt-dir /tmp/run1

  PYTHONPATH=src python -m repro.launch.train --task svm \
      --svm-train 16384 --svm-c-grid 0.1,1,10

  PYTHONPATH=src python -m repro.launch.train --task krr \
      --svm-train 16384 --svm-c-grid 0.5,2,8

LM presets: tiny (CPU-runnable reduced config), full (the assigned config —
requires the production mesh).  Fault tolerance: checkpoints every
--ckpt-every steps (async), resumes from the latest checkpoint, runs under a
StepGuard deadline, and supports failure-injection drills (--fail-at).

The SVM task drives repro.core.engine.HSSSVMEngine: when more than one
device is visible the whole pipeline (compression, factorization, ADMM
C-grid, bias, holdout scoring) runs node/sample-sharded over a mesh of all
local devices.  --task krr / --task gp run the ADMM-free kernel-ridge / GP
posterior-mean path on the same engine: --svm-c-grid then sweeps the ridge
λ (one cached refactorization + one multi-RHS solve each) and the holdout
metric is RMSE.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


def build_svm_engine(task: str, h: float, rank: int, leaf: int, mesh=None):
    """The launch driver's engine: ``HSSSVMEngine`` with the driver's
    compression (rank cap, 48 NEAR + 64 FAR proxies) and 10 ADMM
    iterations per knob value."""
    from repro.core.compression import CompressionParams
    from repro.core.engine import HSSSVMEngine
    from repro.core.kernelfn import KernelSpec

    return HSSSVMEngine(
        spec=KernelSpec(h=h),
        comp=CompressionParams(rank=rank, n_near=48, n_far=64),
        leaf_size=leaf, max_it=10, mesh=mesh, task=task)


def fit_svm_grid(engine, xtr, ytr, xte, yte, c_grid, log=print) -> list:
    """prepare once, then the warm-started knob sweep; returns
    [(knob, model, holdout metric)] — accuracy for svm, RMSE for krr/gp.

    The whole fit is one ``hss.fit`` span (``repro.obs``); each knob's
    holdout scoring, up to the metric's host read, is an ``hss.predict``."""
    task = engine.task
    with obs.span("hss.fit", rows=int(xtr.shape[0]),
                  features=int(xtr.shape[1]), knobs=len(c_grid)):
        rep = engine.prepare(xtr, ytr)
        log(f"prepare: compress {rep.compression_s:.1f}s, factorize "
            f"{rep.factorization_s:.2f}s, HSS {rep.memory_mb:.1f} MB, "
            f"beta {rep.beta:g}")
        yte_j = jnp.asarray(yte)
        knob_name = "λ" if task in ("krr", "gp") else "C"
        out = []
        for c, model in zip(c_grid, engine.train_grid(c_grid)):
            with obs.span("hss.predict", knob=float(c)):
                pred = model.predict(jnp.asarray(xte))
                if task in ("krr", "gp"):
                    metric = float(jnp.sqrt(jnp.mean((pred - yte_j) ** 2)))
                    log(f"{knob_name}={c:g}: holdout rmse {metric:.4f} "
                        f"(admm iters {engine.report.iters_run})")
                else:
                    metric = float(jnp.mean(pred == yte_j))
                    log(f"{knob_name}={c:g}: holdout acc {metric:.4f}")
            out.append((c, model, metric))
    return out


def train_svm(args) -> None:
    from repro.data import synthetic
    from repro.launch.mesh import make_data_mesh

    task = args.task
    dataset = args.svm_dataset
    if task in ("krr", "gp") and dataset == "blobs":
        dataset = "noisy_sine"        # regression demo default
    xtr, ytr, xte, yte = synthetic.train_test(
        dataset, args.svm_train, args.svm_test, seed=0)
    mesh = None
    if jax.device_count() > 1 and not args.svm_local:
        mesh = make_data_mesh()
        print(f"mesh-parallel build over {jax.device_count()} devices")
    engine = build_svm_engine(task, args.svm_h, args.svm_rank, args.svm_leaf,
                              mesh=mesh)
    t0 = time.time()
    c_grid = [float(c) for c in args.svm_c_grid.split(",")]
    fit_svm_grid(engine, xtr, ytr, xte, yte, c_grid)
    stage = "solve" if task in ("krr", "gp") else "ADMM"
    knob_name = "λ" if task in ("krr", "gp") else "C"
    print(f"done in {time.time() - t0:.1f}s "
          f"({stage} total {engine.report.admm_s:.2f}s across the "
          f"{knob_name} grid)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="lm",
                    choices=["lm", "svm", "krr", "gp"])
    ap.add_argument("--arch", default=None, help="LM arch (required for lm)")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "small",
                                                         "full"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, action="append", default=[])
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--svm-dataset", default="blobs")
    ap.add_argument("--svm-train", type=int, default=16384)
    ap.add_argument("--svm-test", type=int, default=2048)
    ap.add_argument("--svm-h", type=float, default=1.0)
    ap.add_argument("--svm-c-grid", default="0.1,1,10")
    ap.add_argument("--svm-rank", type=int, default=32)
    ap.add_argument("--svm-leaf", type=int, default=256)
    ap.add_argument("--svm-local", action="store_true",
                    help="force the single-device engine path")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    if args.task in ("svm", "krr", "gp"):
        train_svm(args)
        return
    if args.arch is None:
        ap.error("--arch is required for --task lm")

    from repro.ckpt.checkpoint import CheckpointManager
    from repro.configs.registry import get_config
    from repro.data.tokens import batch_for_config
    from repro.dist import fault
    from repro.models.transformer import Model
    from repro.train import optim
    from repro.train.step import make_train_step

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = cfg.reduced()
    elif args.preset == "small":
        cfg = cfg.reduced(n_layers=4, d_model=256, n_heads=8, head_dim=32,
                          d_ff=1024, vocab=2048)
    model = Model(cfg)
    opt_cfg = optim.AdamWConfig(lr=args.lr)
    step_fn = jax.jit(make_train_step(model, opt_cfg,
                                      num_microbatches=args.microbatches))
    injector = fault.FailureInjector(tuple(args.fail_at))
    manager = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    def build_state():
        params = model.init(jax.random.PRNGKey(0))
        return {"params": params, "opt": optim.adamw_init(params, opt_cfg)}

    template = build_state()

    def one_step(state, step):
        injector.check(step)
        batch = jax.tree.map(
            jnp.asarray,
            batch_for_config(cfg, args.batch, args.seq, step))
        params, opt, metrics = step_fn(state["params"], state["opt"], batch)
        if step % args.log_every == 0:
            print(f"step {step}: loss={float(metrics['loss']):.4f} "
                  f"grad_norm={float(metrics['grad_norm']):.3f}", flush=True)
        return {"params": params, "opt": opt}

    def save(state, step):
        if manager:
            manager.save_async(state, step)

    def restore():
        if not manager:
            return None
        try:
            state, step = manager.restore(template)
            print(f"resumed from step {step}", flush=True)
            return state, step
        except FileNotFoundError:
            return None

    t0 = time.time()
    state, report = fault.run_resilient(
        args.steps, build_state, one_step, save, restore,
        ckpt_every=args.ckpt_every,
        guard=fault.StepGuard(deadline_s=3600.0),
    )
    if manager:
        manager.wait()
    print(f"done: {args.steps} steps in {time.time() - t0:.1f}s, "
          f"restarts={report['restarts']}, "
          f"stragglers={len(report['stragglers'])}")


if __name__ == "__main__":
    main()
