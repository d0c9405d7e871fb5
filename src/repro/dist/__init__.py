"""repro.dist — the distribution layer: mesh context, sharding rules,
pipeline parallelism, and fault handling.

Modules:
  api       — ``use_mesh`` context, logical-axis resolution (``resolve_spec``),
              the ``constrain`` activation-sharding hint used throughout
              repro.models, and the repo's ``shard_map`` wrapper.
  sharding  — pytree -> NamedSharding rules for params / optimizer state /
              batches / decode caches (consumed by launch.specs and
              launch.dryrun).
  pipeline  — GPipe-style pipeline parallelism over a "stage" mesh axis.
  fault     — StepGuard deadlines + straggler detection, failure injection
              drills, and checkpoint-resuming ``run_resilient``.

Everything degrades gracefully outside a mesh context: ``constrain`` is a
no-op, so the same model code serves single-device smoke tests and the
512-chip dry-run.
"""
from repro.dist import api, fault, pipeline, sharding  # noqa: F401
