"""Trained models back to back, each on rows no other model has seen.

One model is the launch driver's path: ``fit_svm_grid(build_svm_engine(...),
x, y, x_holdout, y_holdout, [C])`` -- a fresh engine, ``prepare`` on the host
rows (pad, cluster tree, NEAR search, compression, factorization), ``train``
at C, ``predict`` on the holdout.  Model ``i`` of a run draws its rows from
the stream ``(seed, i)`` when it starts; set-up trains model 0 to warm every
shape, and the window trains models 1, 2, ... until ``--seconds`` have
passed, ending when the last one finishes.  ``train_s`` is the window over
the models finished; drawing a model's rows is part of it (about 1% of a
model, printed).  ``prepare`` is timed by a wrapper around the engine's own
method, so the host-prep reading does not hang on any log line.

Traffic parameters: ``holdout_rows``, ``c``, ``check_models`` (how many
finished models the check compares), ``ref_tile`` (the reference's Cholesky
tile).
"""
from __future__ import annotations

import time

import numpy as np

from bench import program
from bench.reference import svm as ref


def _rows(run, i):
    n, nh = run.config["rows"], run.traffic["holdout_rows"]
    x, y = run.data.generate(n + nh, (run.seed, i))
    return x[:n], y[:n], x[n:], y[n:]


def fit_one(run, rows):
    """One model through the launch driver's path; returns (model, record)."""
    from repro.launch.train import fit_svm_grid

    x, y, xh, yh = rows
    eng = program.build_engine(run.config)
    spans = {}
    inner = eng.prepare

    def prepare(*args, **kwargs):
        t = time.perf_counter()
        rep = inner(*args, **kwargs)
        spans["prepare_s"] = time.perf_counter() - t
        return rep

    eng.prepare = prepare
    t0 = time.perf_counter()
    (c, model, acc), = fit_svm_grid(eng, x, y, xh, yh, [run.traffic["c"]],
                                    log=lambda _m: None)
    t1 = time.perf_counter()
    rep = eng.report
    return model, dict(model_s=t1 - t0, prepare_s=spans["prepare_s"],
                       compression_s=rep.compression_s,
                       factorization_s=rep.factorization_s,
                       admm_s=rep.admm_s, acc=acc)


def setup(run) -> None:
    fit_one(run, _rows(run, 0))


def window(run, seconds: float) -> dict:
    done = []
    draw_s = 0.0
    t0 = time.perf_counter()
    i = 0
    while not done or time.perf_counter() - t0 < seconds:
        i += 1
        t = time.perf_counter()
        rows = _rows(run, i)
        draw_s += time.perf_counter() - t
        model, rec = fit_one(run, rows)
        done.append((i, model, rec))
    t1 = time.perf_counter()
    run.state["done"] = done
    recs = [r for _, _, r in done]
    print(f"train: {len(done)} models, drawing their rows took "
          f"{draw_s:.3f} s of {t1 - t0:.3f} s", flush=True)
    print("train: models " + "; ".join(
        f"{r['model_s']:.3f} s (prepare {r['prepare_s']:.3f}, compress "
        f"{r['compression_s']:.3f}, factorize {r['factorization_s']:.3f}, "
        f"admm {r['admm_s']:.3f}, acc {r['acc']:.4f})" for r in recs),
        flush=True)
    return {"metrics": {"train_s": (t1 - t0) / len(done)},
            "attempted": len(done), "failed": 0,
            "record": {"models": recs}}


def _sample(run, n_done: int) -> list[int]:
    k = min(run.traffic["check_models"], n_done)
    rng = np.random.default_rng([run.seed, 7])
    return sorted(rng.choice(n_done, size=k, replace=False).tolist())


def answers(run) -> list[dict]:
    """What the window's sampled models say: support counts, holdout
    accuracy, and whether the support set is the training set."""
    done = run.state["done"]
    out = []
    for j in _sample(run, len(done)):
        i, model, rec = done[j]
        x = _rows(run, i)[0]
        out.append(dict(index=i, nsv=program.support_counts(model.z_y),
                        acc=rec["acc"],
                        support=program.support_mismatch(model.x_perm, x)))
    return out


def free(run) -> None:
    run.state.pop("done", None)


def reference(run, prog: list[dict], max_it: int | None = None,
              rows_frac: float = 1.0) -> list[dict]:
    """The plain reference on each compared model's rows.  ``max_it`` and
    ``rows_frac`` break a guarantee of the configuration, for the control."""
    cfg, tr = run.config, run.traffic
    out = []
    for p in prog:
        x, y, xh, yh = _rows(run, p["index"])
        if rows_frac < 1.0:
            keep = np.sort(np.random.default_rng([run.seed, 9]).permutation(
                x.shape[0])[:int(x.shape[0] * rows_frac)])
            x, y = x[keep], y[keep]
        chol = ref.TiledCholesky.build(x, cfg["h"], ref.paper_beta(x.shape[0]),
                                       min(tr["ref_tile"], x.shape[0]))
        classes, ys = ref.one_vs_rest(y)
        fit, = ref.admm_grid(chol, ys, [tr["c"]], max_it or cfg["max_it"])
        del chol
        pred = ref.labels(ref.decision(x, fit, xh, cfg["h"]), classes)
        out.append(dict(index=p["index"], nsv=program.support_counts(fit.zy),
                        acc=float(np.mean(pred == yh)), support=0.0))
    return out


def compare(prog: list[dict], refs: list[dict], run) -> list:
    from bench.run import Check

    gap = max(program.count_gap(p["nsv"], r["nsv"])
              for p, r in zip(prog, refs))
    drop = max(r["acc"] - p["acc"] for p, r in zip(prog, refs))
    sup = max(p["support"] for p in prog)
    return [Check("sv_count_gap", gap, run.limit("sv_count_gap")),
            Check("acc_drop", drop, run.limit("acc_drop")),
            Check("support_mismatch", sup, run.limit("support_mismatch"))]


def controls(run, prog: list[dict], refs: list[dict]) -> dict:
    """Readings of the reference put in the program's place with one
    guarantee broken, and of the program's own bfloat16 factor storage."""
    out = {f"ref_max_it_{k}": compare(reference(run, prog, max_it=k), refs,
                                      run)
           for k in (5, 1)}
    out["ref_rows_half"] = compare(reference(run, prog, rows_frac=0.5), refs,
                                   run)
    look = []
    for p in prog:
        from repro.launch.train import fit_svm_grid

        eng = program.build_engine(run.config)
        eng.store_dtype = "bfloat16"
        x, y, xh, yh = _rows(run, p["index"])
        (_, model, acc), = fit_svm_grid(eng, x, y, xh, yh, [run.traffic["c"]],
                                        log=lambda _m: None)
        look.append(dict(index=p["index"], acc=acc, support=0.0,
                         nsv=program.support_counts(model.z_y)))
    out["program_bf16_factors"] = compare(look, refs, run)
    return out
