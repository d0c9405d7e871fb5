"""Distributed HSS-ADMM SVM training: shardings and the mesh cell.

Sample dimension d is sharded across ALL mesh devices (node-major): the
leaf-level factorization arrays (E, G — O(N r) and O(N m)) live device-local;
reduced-level arrays shard along the node axis until n_k stops dividing the
device count, where they auto-degrade to replicated (they are O(r^2 * n_k) —
tiny).  The ADMM vector iterates are fully data-parallel; the only
cross-device traffic is

  * the level-transition pairings in the solve (collective-permute /
    all-gather of skeleton vectors, O(r * n_k) per level), and
  * the scalar reductions (w2, norms) — psums.

exactly matching the communication pattern of distributed-memory HSS solvers
(STRUMPACK's design, adapted to SPMD/pjit).

Training on a mesh is ``core.engine.HSSSVMEngine(mesh=...)``: its sharded
build (``compression.compress_sharded`` / ``factorization.factorize_sharded``)
places the factorization per ``fac_shardings``, so no stage of
prepare→train ever materializes an unsharded O(N·m) array.
``build_svm_cell`` exposes the same ADMM step both ways: as a
ShapeDtypeStruct dry-run cell (launch/dryrun.py) and, given ``data=(x, y)``,
as a real executable cell over a live sharded factorization.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.admm import admm_svm
from repro.core.factorization import HSSFactorization, hss_solve


def factorization_shapes(n: int, leaf: int, rank: int, dtype=jnp.float32
                         ) -> HSSFactorization:
    """ShapeDtypeStruct skeleton of a factorization for an n-point problem.

    ``dtype`` sets the E/G factor storage (bf16 = §Perf change D1); the
    root LU stays f32.
    """
    levels = int(math.log2(n // leaf))
    n_leaf = n // leaf

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    e_lvls, g_lvls = [], []
    for k in range(1, levels):
        n_k = n_leaf // 2 ** k
        e_lvls.append(sds(n_k, 2 * rank, rank))
        g_lvls.append(sds(n_k, 2 * rank, 2 * rank))
    return HSSFactorization(
        e_leaf=sds(n_leaf, leaf, rank),
        g_leaf=sds(n_leaf, leaf, leaf),
        e_lvls=tuple(e_lvls),
        g_lvls=tuple(g_lvls),
        root_lu=jax.ShapeDtypeStruct((2 * rank, 2 * rank), jnp.float32),
        root_piv=jax.ShapeDtypeStruct((2 * rank,), jnp.int32),
        levels=levels, leaf_size=leaf, beta=1e4,
    )


def _node_axis(mesh: Mesh):
    """All mesh axes combined — the node/sample axis uses every device."""
    return tuple(mesh.axis_names)


def fac_shardings(fac_shapes: HSSFactorization, mesh: Mesh) -> Any:
    """Node-axis sharding with replication fallback for small upper levels.

    Only the node-stacked (n_k, ·, ·) factor arrays shard; a level whose
    node count does not divide the device count degrades to replicated (it
    is O(r² n_k) — tiny).  The dense root LU/pivots are replicated outright:
    every device needs them whole for the root solve.
    """
    from repro.dist.api import node_partition_spec

    def shard_nodes(leaf):
        return NamedSharding(
            mesh, node_partition_spec(mesh, leaf.ndim, leaf.shape[0]))

    return jax.tree.map(shard_nodes, fac_shapes)


def vec_sharding(mesh: Mesh) -> NamedSharding:
    """(n,) ADMM iterate vectors: the sample axis over all mesh devices."""
    return NamedSharding(mesh, PartitionSpec(_node_axis(mesh)))


def make_distributed_admm_step(beta: float, max_it: int = 10,
                               solve_dtype=None):
    """The lowered unit: full ADMM training for one C (paper Alg. 3 7-14).

    Includes the w = K_beta^{-1} e precomputation and MaxIt closed-form
    iterations; the HSS solve inside is the level-batched telescoping solve,
    whose reshapes across the node axis generate the collective schedule.
    """

    def step(fac: HSSFactorization, y: jax.Array, c_value: jax.Array):
        if solve_dtype is not None:
            solver = lambda b: hss_solve(
                fac, b.astype(solve_dtype)).astype(b.dtype)
        else:
            solver = lambda b: hss_solve(fac, b)
        state, trace = admm_svm(solver, y, c_value, beta, max_it)
        return state.z, trace.primal_res

    return step


def build_svm_cell(mesh: Mesh, n: int = 1 << 22, leaf: int = 256,
                   rank: int = 64, beta: float = 1e4, max_it: int = 10,
                   dtype=jnp.float32, solve_dtype=None, data=None,
                   spec=None, comp=None, c_value: float = 1.0):
    """(fn, args, in_shardings) for the SVM distributed training cell.

    Without ``data`` this is the dry-run cell: ``args`` are
    ShapeDtypeStructs for an n-point problem (default n = 4.2M samples — the
    susy-scale regime; paper Table 1's largest dataset is 3.5M) and the cell
    is lower/compile-only (launch/dryrun.py); the third arg is the scalar C.

    With ``data=(x, y)`` the cell runs FOR REAL: a thin wrapper over
    ``core.engine.HSSSVMEngine`` builds the sharded compression +
    factorization under ``mesh`` and ``args`` are live mesh-resident arrays
    — (factorization, permuted labels, per-coordinate C upper bound) with
    the bound equal to ``c_value`` on real points and 0 on pads — so
    ``jax.jit(fn, in_shardings=in_sh)(*args)`` trains that C end-to-end
    with every stage node-sharded.  To sweep C, rescale:
    ``fn(fac, y, new_c / c_value * args[2])``.  ``spec``/``comp`` override
    the kernel and compression accuracy knobs (engine defaults otherwise).
    """
    fn = make_distributed_admm_step(beta, max_it, solve_dtype=solve_dtype)
    if data is None:
        fac_shapes = factorization_shapes(n, leaf, rank, dtype=dtype)
        fac_sh = fac_shardings(fac_shapes, mesh)
        y_shape = jax.ShapeDtypeStruct((n,), jnp.float32)
        c_shape = jax.ShapeDtypeStruct((), jnp.float32)
        in_sh = (fac_sh, vec_sharding(mesh),
                 NamedSharding(mesh, PartitionSpec()))
        return fn, (fac_shapes, y_shape, c_shape), in_sh

    from repro.core.compression import CompressionParams
    from repro.core.engine import HSSSVMEngine
    from repro.core.kernelfn import KernelSpec

    x, y = data
    eng = HSSSVMEngine(
        spec=spec if spec is not None else KernelSpec(h=1.0),
        comp=comp if comp is not None else CompressionParams(rank=rank),
        leaf_size=leaf, beta=beta, max_it=max_it, mesh=mesh,
        store_dtype=(None if jnp.dtype(dtype) == jnp.float32
                     else jnp.dtype(dtype).name),
    )
    eng.prepare(x, y)
    v_sh = vec_sharding(mesh)
    y_d = jax.device_put(eng.problem_labels[0], v_sh)
    c_vec = jax.device_put(c_value * eng.problem_masks[0], v_sh)   # pads -> 0
    fac = eng.fac
    in_sh = (fac_shardings(jax.eval_shape(lambda: fac), mesh), v_sh, v_sh)
    return fn, (fac, y_d, c_vec), in_sh
