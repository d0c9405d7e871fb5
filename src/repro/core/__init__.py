"""The paper's contribution: HSS kernel approximation + ADMM SVM training."""

from repro.core.admm import (
    ADMMState, BoxQPTask, admm_boxqp, admm_svm, admm_svm_batched, paper_beta,
    svm_task,
)
from repro.core.compression import (
    CompressionParams, compress, compress_sharded, compression_error,
    kernel_eval_count,
)
from repro.core.engine import EngineModel, HSSSVMEngine, grid_search
from repro.core.factorization import (
    HSSFactorization, factorize, factorize_sharded, hss_solve, hss_solve_mat,
)
from repro.core.hss import HSSMatrix, shrink_to_fit
from repro.core.kernelfn import KernelSpec, kernel_block
from repro.core.krr import krr_solve
# NOTE: the raw ``lanczos`` sweep is deliberately NOT re-exported — binding
# that name here would shadow the ``repro.core.lanczos`` submodule attribute.
from repro.core.lanczos import spectral_embed, top_eigenpairs
from repro.core.tasks import one_class_task, svr_task
from repro.core.tree import ClusterTree, build_tree, pad_dataset

__all__ = [
    "ADMMState", "BoxQPTask", "admm_boxqp", "admm_svm", "admm_svm_batched",
    "paper_beta", "svm_task",
    "one_class_task", "svr_task",
    "CompressionParams", "compress", "compress_sharded", "compression_error",
    "kernel_eval_count",
    "EngineModel", "HSSSVMEngine", "grid_search",
    "HSSFactorization", "factorize", "factorize_sharded",
    "hss_solve", "hss_solve_mat",
    "HSSMatrix", "shrink_to_fit", "KernelSpec", "kernel_block",
    "krr_solve",
    "spectral_embed", "top_eigenpairs",
    "ClusterTree", "build_tree", "pad_dataset",
]
