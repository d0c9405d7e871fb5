"""Layer 2: trace-level checks over the pipeline's hot paths.

The AST rules (repro.analysis.rules) see syntax; this layer sees the
program jax actually builds.  It traces the hot paths with
``jax.make_jaxpr`` / ``jax.eval_shape`` on a small synthetic problem and
asserts whole-program facts no syntactic rule can prove:

  * **no-downcast** — no ``dot_general``/conv in the traced graph
    accumulates in bf16/f16 (covers every spelling: ``einsum``, ``@``,
    ``jnp.dot``, ``lax.dot_general``) — the f32-accumulation convention
    the ``precision-accumulate`` AST rule enforces at the source level;
  * **no-host-callback** — no callback primitive inside a traced hot
    path (a ``pure_callback``/``io_callback`` smuggled into a jitted
    body serializes every step on the host);
  * **one-compile-per-sweep** — a warm-started 4-point C-grid on the
    engine triggers exactly ONE compilation of the ADMM run (the traced
    scalar-knob convention: knobs enter as ``jnp.asarray(c, f32)``);
  * **streamed-stage purity** — the per-batch stage functions of the
    out-of-core ``compress_streamed`` walk are callback-free and
    f32-accumulating in both fixed-rank and adaptive modes (the host
    orchestrates BETWEEN batches; nothing may call back DURING one);
  * **mesh-placement** — under a multi-device mesh, the compressed /
    factorized artifacts land exactly where ``dist.api
    .node_partition_spec`` says, and the matmat/solve jaxprs pin their
    per-level intermediates with sharding constraints (the PR 3 route
    around the XLA SPMD reshape miscompile);
  * **serve-path** — the serving tier's batch scorer
    (``repro.serve.batched_scores``) is callback-free and f32-accumulating
    in BOTH compute dtypes (the bf16 block path is exactly where a missing
    ``preferred_element_type`` would silently bite), and a tick stream
    with varying queue occupancy compiles once per configured bucket —
    never once per occupancy (the pad-to-bucket rule, end to end);
  * **kernel-linalg** — the RAW streamed scoring matvec probed with bf16
    inputs (the path the serve check's f32 probes never reached), plus
    the KRR solve and Lanczos sweeps of the kernel linear-algebra task
    family, all callback-free and f32-accumulating.

Scope note: ``compression.compress`` is deliberately NOT traced here —
it is host-orchestrated by design (proxy-index selection runs in numpy
via ``jax.device_get``), so ``make_jaxpr`` cannot see through it.  Its
output PLACEMENT is still checked (mesh check), and its inner jitted
stages are covered by the AST layer.

Checks report ``Finding``s with line 0 and a pseudo-path naming the
traced entry point, so the CLI renders them uniformly with lint hits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.findings import Finding

# primitives that contract-and-accumulate: their output dtype IS the
# accumulator dtype, so a bf16/f16 output means a low-precision accumulator
_ACCUM_PRIMS = {"dot_general", "conv_general_dilated"}
_LOW_PRECISION = {jnp.bfloat16.dtype, jnp.float16.dtype}

# callback primitives across jax versions
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "python_callback",
                   "debug_callback", "outside_call", "host_callback_call"}


# --------------------------------------------------------------------- #
# jaxpr walkers                                                          #
# --------------------------------------------------------------------- #
def iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs
    (pjit bodies, scan/while/cond branches, custom_jvp calls, ...)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from iter_eqns(sub)


def dtype_downcasts(jaxpr) -> list[str]:
    """dot_general/conv eqns whose ACCUMULATOR is bf16/f16.

    With ``preferred_element_type=float32`` a bf16×bf16 contraction gets
    an f32 out-aval; without it the output (= accumulator) stays bf16.
    """
    bad = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in _ACCUM_PRIMS:
            continue
        in_dts = [v.aval.dtype for v in eqn.invars
                  if hasattr(v.aval, "dtype")]
        if not in_dts or not all(jnp.issubdtype(d, jnp.floating)
                                 for d in in_dts):
            continue
        out_dts = [v.aval.dtype for v in eqn.outvars
                   if hasattr(v.aval, "dtype")]
        for d in out_dts:
            if d in _LOW_PRECISION:
                bad.append(f"{eqn.primitive.name}: "
                           f"{[str(x) for x in in_dts]} -> {d}")
    return bad


def host_callbacks(jaxpr) -> list[str]:
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in _CALLBACK_PRIMS
            or "callback" in eqn.primitive.name]


def sharding_constraint_count(jaxpr) -> int:
    return sum(1 for eqn in iter_eqns(jaxpr)
               if "sharding_constraint" in eqn.primitive.name)


def abstract_signature(*args):
    """Mirror of jit's cache key for array/scalar args — two calls with
    equal signatures hit the same executable.  Python scalars map to
    their weak result dtype: a C-grid of plain floats shares one entry,
    but a grid mixing int and float (or a grid of 0-d np arrays with
    drifting dtypes) does NOT — which is why the repo's convention is
    ``jnp.asarray(c, jnp.float32)`` at every jit boundary."""
    sig = []
    for a in jax.tree.leaves(args):
        if isinstance(a, (jax.Array, np.ndarray)):
            weak = bool(getattr(a, "weak_type", False))
            sig.append((tuple(a.shape), str(a.dtype), weak))
        else:
            sig.append(("scalar", str(jnp.result_type(type(a))), True))
    return tuple(sig)


# --------------------------------------------------------------------- #
# probe problem                                                          #
# --------------------------------------------------------------------- #
def _blobs(n: int, seed: int = 0):
    r = np.random.default_rng(seed)
    half = n // 2
    mu = np.zeros(4, np.float32)
    mu[0] = 2.5
    x = np.concatenate([r.normal(size=(half, 4)) + mu,
                        r.normal(size=(n - half, 4)) - mu]).astype(np.float32)
    y = np.concatenate([np.ones(half), -np.ones(n - half)]).astype(np.float32)
    return x, y


def build_probe(n: int = 256, leaf: int = 32, store_dtype: str | None = None,
                mesh=None):
    """A small compress+factorize instance for tracing the hot paths."""
    from repro.core import compression, factorization, tree as tree_mod
    from repro.core.kernelfn import KernelSpec

    x, y = _blobs(n)
    t = tree_mod.build_tree(x, leaf_size=leaf)
    xp = x[t.perm]
    spec = KernelSpec(h=1.0)
    params = compression.CompressionParams(rank=16, n_near=16, n_far=24)
    if mesh is None:
        hss = compression.compress(jnp.asarray(xp), t, spec, params)
        fac = factorization.factorize(hss, 8.0, store_dtype=store_dtype)
    else:
        hss = compression.compress_sharded(xp, t, spec, params, mesh)
        fac = factorization.factorize_sharded(hss, 8.0, mesh,
                                              store_dtype=store_dtype)
    yp = jnp.asarray(y[t.perm])
    return hss, fac, yp


def _finding(entry: str, message: str) -> Finding:
    return Finding(rule="trace-check", path=f"<trace:{entry}>", line=0,
                   message=message, line_content="")


def _check_traced(entry: str, jaxpr, want_constraints: bool = False
                  ) -> list[Finding]:
    out = []
    for bad in dtype_downcasts(jaxpr):
        out.append(_finding(entry, f"low-precision accumulation: {bad} — "
                            "pass preferred_element_type=jnp.float32"))
    for cb in host_callbacks(jaxpr):
        out.append(_finding(entry, f"host callback {cb!r} inside a traced "
                            "hot path"))
    if want_constraints and sharding_constraint_count(jaxpr) == 0:
        out.append(_finding(entry, "no sharding constraints in the traced "
                            "graph under an active mesh — per-level "
                            "intermediates must be pinned via "
                            "dist.api.constrain_nodes"))
    return out


# --------------------------------------------------------------------- #
# the checks                                                             #
# --------------------------------------------------------------------- #
def check_hot_paths(store_dtype: str | None = "bfloat16") -> list[Finding]:
    """Trace matmat / solve_mat / factorize / the ADMM scan and assert
    no low-precision accumulation and no host callbacks.  Runs with bf16
    factor storage by default — the configuration where a missing
    ``preferred_element_type`` actually bites."""
    from repro.core import admm as admm_mod
    from repro.core import factorization
    from repro.core.tasks import compute_bias_batched

    hss, fac, yp = build_probe(store_dtype=store_dtype)
    n = hss.n
    v = jnp.zeros((n, 2), jnp.float32)
    findings = []

    findings += _check_traced(
        "HSSMatrix.matmat", jax.make_jaxpr(lambda b: hss.matmat(b))(v))
    findings += _check_traced(
        "hss_solve_mat", jax.make_jaxpr(lambda b: fac.solve_mat(b))(v))
    findings += _check_traced(
        "factorize",
        jax.make_jaxpr(lambda h: factorization.factorize(
            h, 8.0, store_dtype=store_dtype))(hss))

    ys = yp[None, :]
    pmask = jnp.ones_like(ys)

    def admm_run(knob, z0, mu0):
        task = admm_mod.svm_task(ys, knob * pmask)
        state, trace = admm_mod.admm_boxqp(fac.solve_mat, task, fac.beta,
                                           4, z0=z0, mu0=mu0)
        return state.z, state.mu, trace.iters_run

    z0 = jnp.zeros((n, 1), jnp.float32)
    knob = jnp.asarray(1.0, jnp.float32)
    findings += _check_traced(
        "admm_boxqp", jax.make_jaxpr(admm_run)(knob, z0, z0))
    findings += _check_traced(
        "compute_bias_batched",
        jax.make_jaxpr(lambda z, c: compute_bias_batched(
            hss, ys.T, z, c * pmask.T, pmask.T))(z0, knob))
    return findings


def check_compress_kernels() -> list[Finding]:
    """Trace the fused Pallas compression stages (repro.kernels.compress)
    and assert no sub-f32 accumulation and no host callbacks.

    Probed on bf16 inputs — the configuration where a missing
    ``preferred_element_type`` inside the fused assemble+ID deflation loop
    (or the laplacian block kernel's epilogue) would actually produce a
    bf16 accumulator.  ``iter_eqns`` recurses through the ``pallas_call``
    body jaxpr, so the on-chip contractions are covered, not just the
    padding wrapper.  The plain ``compress`` orchestration stays
    deliberately untraced (host-orchestrated by design — see module
    docstring); this check covers the device stages it dispatches to.
    """
    from repro.kernels.compress import ops as cops
    from repro.kernels.compress.laplacian import laplacian_block

    b, m, s, f, k = 2, 32, 16, 4, 8
    xc = jnp.zeros((b, m, f), jnp.bfloat16)
    xp = jnp.zeros((b, s, f), jnp.bfloat16)
    findings = []
    for name in ("gaussian", "laplacian"):
        jaxpr = jax.make_jaxpr(lambda c, p: cops.batched_assemble_id(
            c, p, k, kernel_name=name, h=1.0, rtol=1e-4, adaptive=True,
            interpret=True))(xc, xp)
        findings += _check_traced(f"fused_assemble_id[{name}]", jaxpr)
    xa = jnp.zeros((33, f), jnp.bfloat16)
    xb = jnp.zeros((65, f), jnp.bfloat16)
    findings += _check_traced(
        "laplacian_block",
        jax.make_jaxpr(lambda a, c: laplacian_block(
            a, c, 1.0, interpret=True))(xa, xb))
    return findings


def check_streamed_stage() -> list[Finding]:
    """Trace the streamed out-of-core compression stages and assert no host
    callbacks and no sub-f32 accumulation.

    ``compress_streamed`` is host-orchestrated on purpose (batch slicing,
    checkpointing and skeleton bookkeeping run in numpy), but each batch
    dispatches to the three pure stage functions traced here — a callback
    smuggled into one of them would serialize every batch of a paper-scale
    build on the host.  Probed in f32 (the streamed path computes in the
    input dtype; bf16 storage is a factorization-layer concern), in both
    fixed-rank and adaptive modes, so the rank-masked candidate branch is
    covered too.
    """
    from repro.core import compression as comp
    from repro.core.kernelfn import KernelSpec

    spec = KernelSpec(h=1.0)
    b, m, f, r0, nf = 2, 32, 4, 8, 12
    xl = jnp.zeros((b, m, f), jnp.float32)
    xp_leaf = jnp.zeros((b, m + nf, f), jnp.float32)
    cp = jnp.zeros((b, 2 * r0, f), jnp.float32)
    xp_lvl = jnp.zeros((b, 2 * r0 + nf, f), jnp.float32)
    cm = jnp.ones((b, 2 * r0), jnp.float32)
    findings = []
    for adaptive in (False, True):
        tag = "adaptive" if adaptive else "fixed"
        rtol = 1e-4 if adaptive else None
        findings += _check_traced(
            f"stream_leaf_batch[{tag}]",
            jax.make_jaxpr(lambda a, p: comp._stream_leaf_batch(
                spec, a, p, r0, rtol, adaptive))(xl, xp_leaf))
        findings += _check_traced(
            f"stream_level_batch[{tag}]",
            jax.make_jaxpr(lambda c, p, k: comp._stream_level_batch(
                spec, c, p, k if adaptive else None, r0, rtol,
                adaptive))(cp, xp_lvl, cm))
        findings += _check_traced(
            f"stream_root_batch[{tag}]",
            jax.make_jaxpr(lambda c, k: comp._stream_root_batch(
                spec, c, k if adaptive else None, adaptive))(cp, cm))
    return findings


def check_recompile_engine(c_grid=(0.5, 1.0, 2.0, 4.0), engines: int = 1,
                           rows: int = 256) -> list[Finding]:
    """``engines`` fresh engines of one shape, each running a warm-started
    C-sweep one after the other, must trace the shared ADMM program at most
    once in all (the traced-scalar knob convention, end to end).

    Reads the ``hss.admm.traces`` counter, which the program bumps each
    time jax traces it.  One trace serves the whole sweep and every later
    engine of the same shapes; none at all if an earlier engine of this
    process traced them.  More than one means a knob reaches jit as a fresh
    Python value instead of a traced ``jnp.asarray`` scalar, or something
    differs from engine to engine."""
    from repro import obs
    from repro.core import compression
    from repro.core.engine import HSSSVMEngine
    from repro.core.kernelfn import KernelSpec

    x, y = _blobs(rows)
    before = obs.total("hss.admm.traces")
    for _ in range(engines):
        engine = HSSSVMEngine(
            spec=KernelSpec(h=1.0),
            comp=compression.CompressionParams(rank=16, n_near=16, n_far=24),
            leaf_size=32, max_it=4)
        engine.prepare(x, y)
        engine.train_grid(list(c_grid))
    traces = obs.total("hss.admm.traces") - before
    if traces <= 1:
        return []
    sigs = abstract_signature(jnp.asarray(c_grid[0], jnp.float32))
    return [_finding(
        "engine.train_grid",
        f"{engines} engine(s) x {len(c_grid)}-point C-sweep traced the ADMM "
        f"program {traces}x (expected at most 1): a knob is reaching jit as "
        f"a fresh Python value instead of a traced jnp.asarray scalar "
        f"(expected signature per call: {sigs}), or the engines differ")]


def check_serve_path() -> list[Finding]:
    """The serving tier's hot path, both halves of its contract:

    1. ``batched_scores`` traced in f32 AND bf16 must show no sub-f32
       dot_general accumulator and no host callback — the bf16 block
       path is all einsums, so one missing ``preferred_element_type``
       flips every score accumulation to bf16;
    2. a ``ServingEngine`` fed ticks at many different queue occupancies
       must compile its scorer exactly once per configured bucket (the
       pad-to-bucket rule): a compile count tracking occupancy means the
       padding broke and every distinct queue length pays an XLA compile.
    """
    from repro.core.engine import EngineModel
    from repro.core.kernelfn import KernelSpec
    from repro.serve import BatchPolicy, ServingEngine, batched_scores

    d, f, p = 64, 4, 3
    spec = KernelSpec(h=1.0)
    xs = jnp.zeros((d, f), jnp.float32)
    zy = jnp.zeros((d, p), jnp.float32)
    biases = jnp.zeros((p,), jnp.float32)
    xq = jnp.zeros((32, f), jnp.float32)
    findings = []
    for dt in ("float32", "bfloat16"):
        jaxpr = jax.make_jaxpr(
            lambda q, s, z, b: batched_scores(
                q, s, z, b, spec=spec, block=16, compute_dtype=dt)
        )(xq, xs, zy, biases)
        findings += _check_traced(f"serve.batched_scores[{dt}]", jaxpr)

    model = EngineModel(
        x_perm=xs, z_y=zy, biases=biases,
        classes=np.array([0.0, 1.0, 2.0], np.float32), spec=spec,
        c_value=1.0, binary=False, strategy="ovr", task="svm", beta=8.0)
    engine = ServingEngine(policy=BatchPolicy(buckets=(16, 64), block=16))
    mid = engine.add_model(model)
    occupancies = (1, 3, 7, 11, 16, 20, 40, 64)   # 2 buckets, 8 shapes
    for occ in occupancies:
        engine.score(mid, np.zeros((occ, f), np.float32))
    compiles = engine.scorer_compiles()
    if compiles is None:
        findings.append(_finding(
            "serve.tick", "cannot read the jit cache size on this jax "
            "version — occupancy recompile guard inconclusive"))
    elif compiles != 2:
        findings.append(_finding(
            "serve.tick",
            f"{len(occupancies)} tick occupancies over 2 buckets compiled "
            f"{compiles}x (expected 2): queue shapes are reaching the "
            "scorer unpadded — the bucket padding rule broke"))
    return findings


def check_kernel_linalg() -> list[Finding]:
    """The kernel linear-algebra family's traced paths.

    1. the RAW streamed scoring matvec (``kernel_matvec_streamed``) probed
       with bf16 rows/support/coefficients — exactly the path the layer-2
       sweep never saw before this check (``batched_scores`` routes bf16
       through its own einsum twin, so the raw path's bare ``@``
       accumulations sat outside every earlier probe);
    2. the KRR/GP train step (``krr.krr_solve``) on a bf16-stored
       factorization — ONE multi-RHS solve, callback-free, f32-accumulating;
    3. the Lanczos sweep (``lanczos.top_eigenpairs``) on the HSS matvec —
       the scan body's reorthogonalization and Ritz recombination are all
       contractions and must hold the f32 convention too.
    """
    from repro.core import krr as krr_mod
    from repro.core import lanczos as lanczos_mod
    from repro.core.kernelfn import KernelSpec, kernel_matvec_streamed

    findings = []
    spec = KernelSpec(h=1.0)
    for dt in (jnp.float32, jnp.bfloat16):
        xr = jnp.zeros((40, 4), dt)
        xc = jnp.zeros((64, 4), dt)
        v = jnp.zeros((64, 3), dt)
        jaxpr = jax.make_jaxpr(
            lambda a, c, w: kernel_matvec_streamed(spec, a, c, w, block=16)
        )(xr, xc, v)
        findings += _check_traced(
            f"kernel_matvec_streamed[{jnp.dtype(dt).name}]", jaxpr)

    hss, fac, _ = build_probe(store_dtype="bfloat16")
    targets = jnp.zeros((hss.n, 2), jnp.float32)
    findings += _check_traced(
        "krr.krr_solve",
        jax.make_jaxpr(lambda b: krr_mod.krr_solve(fac, b))(targets))
    findings += _check_traced(
        "lanczos.top_eigenpairs",
        jax.make_jaxpr(lambda: lanczos_mod.top_eigenpairs(hss, 4, seed=0))())
    return findings


def _constraint_spec_violations(entry: str, jaxpr, mesh) -> list[Finding]:
    """Each sharding_constraint pin on a node-stacked (ndim>=3)
    intermediate must carry EXACTLY the node_partition_spec placement —
    a drifted pin is worse than none (it forces the wrong layout)."""
    from jax.sharding import NamedSharding

    from repro.dist import api as dist_api

    out = []
    for eqn in iter_eqns(jaxpr):
        if "sharding_constraint" not in eqn.primitive.name:
            continue
        aval = eqn.outvars[0].aval
        if not hasattr(aval, "shape") or len(aval.shape) < 3:
            continue                       # vectors/matrices: other rules
        got = eqn.params.get("sharding")
        if got is None or not hasattr(got, "is_equivalent_to"):
            continue
        want = NamedSharding(mesh, dist_api.node_partition_spec(
            mesh, len(aval.shape), aval.shape[0]))
        if not got.is_equivalent_to(want, len(aval.shape)):
            out.append(_finding(
                entry,
                f"sharding pin on {tuple(aval.shape)} intermediate is "
                f"{got}, but node_partition_spec says {want.spec} — the "
                "placement rule drifted between dist.api and this sweep"))
    return out


def check_mesh_placement() -> list[Finding]:
    """Under a multi-device mesh: the factorization sits exactly where
    ``fac_shardings`` (= node_partition_spec per leaf) puts it, no
    O(N·m) compression artifact is fully replicated, and the matmat /
    solve graphs pin their node-stacked per-level intermediates with
    sharding constraints that MATCH node_partition_spec."""
    from jax.sharding import NamedSharding

    from repro.core.distributed import fac_shardings
    from repro.dist import api as dist_api
    from repro.launch.mesh import make_data_mesh

    ndev = len(jax.devices())
    if ndev < 2 or ndev & (ndev - 1):
        return [_finding(
            "mesh", f"skipped: needs a power-of-two multi-device setup, "
            f"have {ndev} device(s) — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")]
    mesh = make_data_mesh()
    hss, fac, _ = build_probe(n=32 * ndev * 2, leaf=32, mesh=mesh)
    findings = []

    # factorization placement: fac_shardings is the contract
    want_tree = fac_shardings(jax.eval_shape(lambda: fac), mesh)
    for i, (leaf, want) in enumerate(zip(jax.tree.leaves(fac),
                                         jax.tree.leaves(want_tree))):
        if not isinstance(leaf, jax.Array):
            continue
        if not leaf.sharding.is_equivalent_to(want, leaf.ndim):
            findings.append(_finding(
                "mesh:fac",
                f"factor leaf {i} shape {tuple(leaf.shape)} placed as "
                f"{leaf.sharding}, but fac_shardings says {want.spec}"))

    # compression placement: the O(N·m)/O(N·r) arrays must be sharded
    for name in ("d_leaf", "u_leaf", "x"):
        a = getattr(hss, name)
        if a.sharding.is_fully_replicated:
            findings.append(_finding(
                "mesh:hss",
                f"hss.{name} shape {tuple(a.shape)} is fully replicated "
                "under the mesh — an O(N·m) artifact landed whole on "
                "every device"))

    n = hss.n
    v = jnp.zeros((n, 2), jnp.float32)
    with dist_api.use_mesh(mesh), mesh:
        mm = jax.make_jaxpr(lambda b: hss.matmat(b))(v)
        sv = jax.make_jaxpr(lambda b: fac.solve_mat(b))(v)
    findings += _check_traced("mesh:matmat", mm, want_constraints=True)
    findings += _check_traced("mesh:solve_mat", sv, want_constraints=True)
    findings += _constraint_spec_violations("mesh:matmat", mm, mesh)
    findings += _constraint_spec_violations("mesh:solve_mat", sv, mesh)
    return findings


def run_all() -> list[Finding]:
    """Every trace-level check; empty result = hot paths are clean."""
    findings = []
    findings += check_hot_paths()
    findings += check_compress_kernels()
    findings += check_streamed_stage()
    findings += check_recompile_engine()
    findings += check_serve_path()
    findings += check_kernel_linalg()
    findings += check_mesh_placement()
    # informational skips are not failures
    return [f for f in findings if not f.message.startswith("skipped:")]
