"""Open-loop scoring requests against one resident model.

Set-up draws the model from the seed, with no training: ``support_rows``
rows of the configuration's generator as the support set, duals ``y_i z_i``
with ``z_i`` uniform in [0, C], and a bias.  It loads the model into a
``ServingEngine`` with the default ``BatchPolicy``, starts its threaded tick
driver, and runs one launch per bucket of the policy to warm every shape.

Requests arrive as a Poisson process at ``requests_per_s``; each asks for
a lognormal number of rows (median ``rows_median``, shape ``rows_sigma``,
clipped to [``rows_min``, ``rows_max``]).  The exponential gaps and the
sizes are one fixed multiset, drawn from ``shape_seed``, that the run's
seed only reorders, so every seed offers the same work in the window.
Rows come from a pool drawn from the seed.  Each request is timed from its
due time to its result; one that is not answered within ``wait_s`` of the
window's close is missing.

Traffic parameters: those above, ``pool_rows``, ``check_requests`` (how
many requests, drawn from the seed, the check compares).
"""
from __future__ import annotations

import time

import numpy as np

from bench.reference import scores as ref_scores


def _schedule(tr: dict, seed: int, seconds: float):
    """(due times, rows) of the window's requests: ``requests_per_s *
    seconds`` gaps and sizes from the fixed stream, reordered by the seed,
    the gaps scaled so that the last request falls due before the close."""
    rng = np.random.default_rng(tr["shape_seed"])
    n = max(int(tr["requests_per_s"] * seconds), 1)
    gaps = rng.exponential(1.0, size=n + 1)
    sizes = np.clip(np.rint(tr["rows_median"] * np.exp(
        tr["rows_sigma"] * rng.normal(size=n))), tr["rows_min"],
        tr["rows_max"]).astype(np.int64)
    order = np.random.default_rng([seed, 2])
    gaps = gaps[order.permutation(n + 1)]
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    return due, sizes[order.permutation(n)]


def make_model(run):
    """(support rows, duals y z, bias) drawn from the seed."""
    cfg = run.config
    xs, ys = run.data.generate(cfg["served_support_rows"], (run.seed, 0))
    rng = np.random.default_rng([run.seed, 1])
    z = rng.uniform(0.0, run.traffic["c"], size=xs.shape[0])
    zy = (ys * z).astype(np.float32)[:, None]
    bias = np.array([rng.uniform(-1.0, 1.0)], np.float32)
    return xs, zy, bias


def start_engine(run, xs, zy, bias, compute_dtype: str = "float32"):
    """A ServingEngine holding the model, one launch per bucket done."""
    import jax.numpy as jnp
    from repro.core.engine import EngineModel
    from repro.core.kernelfn import KernelSpec
    from repro.serve.engine import BatchPolicy, ServingEngine

    cfg = run.config
    model = EngineModel(
        x_perm=jnp.asarray(xs), z_y=jnp.asarray(zy), biases=jnp.asarray(bias),
        classes=np.array([-1.0, 1.0], np.float32),
        spec=KernelSpec(name=cfg["kernel"], h=cfg["h"]),
        c_value=run.traffic["c"], binary=True)
    engine = ServingEngine(BatchPolicy(compute_dtype=compute_dtype))
    mid = engine.add_model(model)
    pool = run.state["pool"]
    for b in engine.policy.buckets:
        engine.score(mid, pool[:b])
    return engine, mid


def setup(run) -> None:
    tr = run.traffic
    xs, zy, bias = make_model(run)
    pool, _ = run.data.generate(tr["pool_rows"], (run.seed, 3))
    run.state.update(xs=xs, zy=zy, bias=bias, pool=pool)
    engine, mid = start_engine(run, xs, zy, bias)
    run.state.update(engine=engine, mid=mid)


def window(run, seconds: float) -> dict:
    tr = run.traffic
    engine, mid, pool = run.state["engine"], run.state["mid"], \
        run.state["pool"]
    due, sizes = _schedule(tr, run.seed, seconds)
    starts = np.random.default_rng([run.seed, 4]).integers(
        0, pool.shape[0] - tr["rows_max"], size=due.shape[0])
    n = due.shape[0]
    tickets = [None] * n
    late = np.zeros(n)
    before = engine.stats()
    engine.start()
    t0 = time.perf_counter()
    due_abs = t0 + due
    for k in range(n):
        wait = due_abs[k] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        tickets[k] = engine.submit(mid, pool[starts[k]:starts[k] + sizes[k]])
        late[k] = time.perf_counter() - due_abs[k]
    t_close = t0 + seconds
    deadline = t_close + tr["wait_s"]
    lat = np.empty(n)
    for k, t in enumerate(tickets):
        t._event.wait(max(deadline - time.perf_counter(), 0.0))
        lat[k] = (t.t_done - due_abs[k]) if t.done else np.inf
    engine.stop()
    after = engine.stats()
    missing = int(np.sum(~np.isfinite(lat)))
    run.state.update(tickets=tickets, starts=starts, sizes=sizes,
                     missing=missing, due=due, lat=lat, late=late)
    rows = int(sizes.sum())
    print(f"serve: {n} requests, {rows} rows in {seconds} s "
          f"({rows / seconds:.0f} rows/s offered); generator late p50 "
          f"{np.median(late) * 1e3:.3f} ms, p99 "
          f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
          f"{late.max() * 1e3:.3f} ms; missing {missing}", flush=True)
    delta = {k: after[k] - before[k]
             for k in ("ticks", "launches", "queries", "requests")}
    print(f"serve: engine {delta}", flush=True)
    p99 = float(np.percentile(np.where(np.isfinite(lat), lat, deadline - t0),
                              99)) * 1e3
    return {"metrics": {"serve_p99_ms": p99},
            "attempted": n, "failed": missing,
            "record": dict(delta, support_rows=run.state["xs"].shape[0],
                           features=run.state["xs"].shape[1],
                           columns=run.state["zy"].shape[1],
                           late_p99_s=float(np.percentile(late, 99)))}


def sample(run) -> np.ndarray:
    n = len(run.state["tickets"])
    rng = np.random.default_rng([run.seed, 5])
    return np.sort(rng.choice(n, size=min(run.traffic["check_requests"], n),
                              replace=False))


def sampled_rows(run, idx) -> np.ndarray:
    pool, starts, sizes = (run.state[k] for k in ("pool", "starts", "sizes"))
    return np.concatenate([pool[starts[k]:starts[k] + sizes[k]]
                           for k in idx])


def answers(run) -> dict:
    """Served (scores, labels) of the sampled requests, rows concatenated
    (NaN where a request was not answered), and the missing count."""
    idx = sample(run)
    s, p = [], []
    for k in idx:
        t = run.state["tickets"][k]
        m = int(run.state["sizes"][k])
        if t.done:
            s.append(np.asarray(t.scores, np.float64).reshape(m))
            p.append(np.asarray(t.predictions).reshape(m))
        else:
            s.append(np.full(m, np.nan))
            p.append(np.zeros(m))
    return dict(idx=idx, scores=np.concatenate(s), labels=np.concatenate(p),
                missing=run.state["missing"])


def free(run) -> None:
    for k in ("engine", "tickets"):
        run.state.pop(k, None)


def reference(run, prog: dict, support_frac: float = 1.0) -> np.ndarray:
    """Reference scores of the sampled rows; ``support_frac`` < 1 scores
    against part of the support set only, for the control."""
    xs, zy = run.state["xs"], run.state["zy"]
    if support_frac < 1.0:
        keep = np.sort(np.random.default_rng([run.seed, 9]).permutation(
            xs.shape[0])[:int(xs.shape[0] * support_frac)])
        xs, zy = xs[keep], zy[keep]
    return ref_scores.scores(sampled_rows(run, prog["idx"]), xs, zy,
                             run.state["bias"], run.config["h"])[:, 0]


def compare(prog: dict, r: np.ndarray, run) -> list:
    from bench.run import Check

    s, labels = prog["scores"], prog["labels"]
    rms = float(np.sqrt(np.mean(r * r)))
    gap_lim = run.limit("score_gap")
    gap = float(np.max(np.abs(s - r)) / rms)
    if not np.isfinite(gap):
        gap = float("inf")
    sure = np.abs(r) > 2.0 * (gap_lim if gap_lim is not None else 0.0) * rms
    flips = float(np.sum(sure & (labels != np.where(r >= 0, 1, -1))))
    return [Check("score_gap", gap, gap_lim),
            Check("label_flips", flips, run.limit("label_flips")),
            Check("unanswered", float(prog["missing"]),
                  run.limit("unanswered"))]


def controls(run, prog: dict, r: np.ndarray) -> dict:
    """Readings of the reference put in the program's place against half
    of the support set, and of the program's own bfloat16 score path."""
    half = reference(run, prog, support_frac=0.5)
    ctrl = dict(prog, scores=half, labels=np.where(half >= 0, 1, -1))
    out = {"ref_support_half": compare(ctrl, r, run)}
    engine, mid = start_engine(run, run.state["xs"], run.state["zy"],
                               run.state["bias"], compute_dtype="bfloat16")
    pool, starts, sizes = (run.state[k] for k in ("pool", "starts", "sizes"))
    tickets = [engine.submit(mid, pool[starts[k]:starts[k] + sizes[k]])
               for k in prog["idx"]]
    engine.flush()
    s = np.concatenate([np.asarray(t.scores, np.float64).reshape(-1)
                        for t in tickets])
    lab = np.concatenate([np.asarray(t.predictions).reshape(-1)
                          for t in tickets])
    out["program_bf16_scores"] = compare(dict(prog, scores=s, labels=lab), r,
                                         run)
    return out
