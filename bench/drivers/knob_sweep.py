"""C sweeps on one prepared engine: compress once, train many.

Set-up draws the training rows from ``(seed, 0)`` and the validation rows
from ``(seed, 1)``, builds the launch driver's engine, runs ``prepare``
(pad, tree, NEAR search, compression, factorization) and one C value with
its ``predict`` to warm every shape.  The window then repeats what
``launch.train.fit_svm_grid`` does after its ``prepare``: ``train_grid`` over
the C grid, warm-started from the previous C, then ``predict`` on the
validation rows and the accuracy of each model, cycle after cycle, until
``--seconds`` have passed; it ends with the last whole cycle.
``sweep_point_s`` is the window over the C values finished.

Traffic parameters: ``validation_rows``, ``c_grid``, ``check_cycles`` (how
many cycles, drawn from the seed, keep their models for the check),
``ref_tile``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import program
from bench.reference import svm as ref


def setup(run) -> None:
    import jax.numpy as jnp

    cfg, tr = run.config, run.traffic
    x, y = run.data.generate(cfg["rows"], (run.seed, 0))
    xv, yv = run.data.generate(tr["validation_rows"], (run.seed, 1))
    eng = program.build_engine(cfg)
    rep = eng.prepare(x, y)
    print(f"sweep: prepare compress {rep.compression_s:.3f} s, factorize "
          f"{rep.factorization_s:.3f} s, beta {rep.beta:g}", flush=True)
    model, _ = eng.train(tr["c_grid"][0])
    float(jnp.mean(model.predict(jnp.asarray(xv)) == jnp.asarray(yv)))
    run.state.update(x=x, y=y, xv=xv, yv=yv, engine=eng)
    rng = np.random.default_rng([run.seed, 7])
    run.state["keep"] = {0} | set(
        rng.choice(np.arange(1, 64), size=tr["check_cycles"] - 1,
                   replace=False).tolist())


def window(run, seconds: float) -> dict:
    import jax.numpy as jnp

    eng, xv, yv = run.state["engine"], run.state["xv"], run.state["yv"]
    grid = [float(c) for c in run.traffic["c_grid"]]
    keep = run.state["keep"]
    accs, kept = [], {}
    admm0 = eng.report.admm_s
    t0 = time.perf_counter()
    cycle = 0
    while cycle == 0 or time.perf_counter() - t0 < seconds:
        yv_j = jnp.asarray(yv)
        models = eng.train_grid(grid)
        row = []
        for model in models:
            pred = model.predict(jnp.asarray(xv))
            row.append(float(jnp.mean(pred == yv_j)))
        accs.append(row)
        if cycle in keep:
            kept[cycle] = models
        cycle += 1
    t1 = time.perf_counter()
    points = cycle * len(grid)
    run.state.update(accs=np.asarray(accs), kept=kept)
    print(f"sweep: {cycle} cycles of {len(grid)} C values, accuracy "
          f"{accs[0]}", flush=True)
    return {"metrics": {"sweep_point_s": (t1 - t0) / points},
            "attempted": points, "failed": 0,
            "record": {"points": points,
                       "admm_s": eng.report.admm_s - admm0}}


def answers(run) -> dict:
    """Every finished C value's accuracy, and the support counts and
    support set of the kept cycles' models."""
    x = run.state["x"]
    kept = run.state["kept"]
    nsv = {j: [program.support_counts(m.z_y) for m in ms]
           for j, ms in kept.items()}
    support = max(program.support_mismatch(ms[0].x_perm, x)
                  for ms in kept.values())
    return dict(accs=run.state["accs"], nsv=nsv, support=support)


def free(run) -> None:
    for k in ("engine", "kept"):
        run.state.pop(k, None)


def reference(run, prog: dict, max_it: int | None = None,
              rows_frac: float = 1.0) -> dict:
    """The plain reference's C sweep on the same rows; ``max_it`` and
    ``rows_frac`` break a guarantee of the configuration, for the control."""
    cfg, tr = run.config, run.traffic
    x, y = run.state["x"], run.state["y"]
    xv, yv = run.state["xv"], run.state["yv"]
    if rows_frac < 1.0:
        keep = np.sort(np.random.default_rng([run.seed, 9]).permutation(
            x.shape[0])[:int(x.shape[0] * rows_frac)])
        x, y = x[keep], y[keep]
    chol = ref.TiledCholesky.build(x, cfg["h"], ref.paper_beta(x.shape[0]),
                                   min(tr["ref_tile"], x.shape[0]))
    classes, ys = ref.one_vs_rest(y)
    fits = ref.admm_grid(chol, ys, tr["c_grid"], max_it or cfg["max_it"])
    del chol
    accs, nsv = [], []
    for fit in fits:
        pred = ref.labels(ref.decision(x, fit, xv, cfg["h"]), classes)
        accs.append(float(np.mean(pred == yv)))
        nsv.append(program.support_counts(fit.zy))
    return dict(accs=np.asarray(accs), nsv=nsv)


def compare(prog: dict, refs: dict, run) -> list:
    from bench.run import Check

    gap = max(program.count_gap(p, r) for ms in prog["nsv"].values()
              for p, r in zip(ms, refs["nsv"]))
    drop = float(np.max(refs["accs"][None, :] - prog["accs"]))
    return [Check("sv_count_gap", gap, run.limit("sv_count_gap")),
            Check("acc_drop", drop, run.limit("acc_drop")),
            Check("support_mismatch", prog["support"],
                  run.limit("support_mismatch"))]


def _as_program(r: dict) -> dict:
    return dict(accs=r["accs"][None, :], nsv={0: r["nsv"]}, support=0.0)


def controls(run, prog: dict, refs: dict) -> dict:
    """Readings of the reference put in the program's place with one
    guarantee broken, and of the program's own bfloat16 factor storage."""
    import jax.numpy as jnp

    out = {f"ref_max_it_{k}": compare(
        _as_program(reference(run, prog, max_it=k)), refs, run)
        for k in (5, 1)}
    out["ref_rows_half"] = compare(
        _as_program(reference(run, prog, rows_frac=0.5)), refs, run)
    eng = program.build_engine(run.config)
    eng.store_dtype = "bfloat16"
    eng.prepare(run.state["x"], run.state["y"])
    models = eng.train_grid(run.traffic["c_grid"])
    yv = jnp.asarray(run.state["yv"])
    accs = [float(jnp.mean(m.predict(jnp.asarray(run.state["xv"])) == yv))
            for m in models]
    out["program_bf16_factors"] = compare(
        dict(accs=np.asarray(accs)[None, :], support=0.0,
             nsv={0: [program.support_counts(m.z_y) for m in models]}),
        refs, run)
    return out
