"""TPU-native parallelism: device meshes, SPMD training, sequence parallelism.

This package is the TPU-first replacement for the reference's entire
distribution stack (SURVEY.md §2.3, §5.8): where MXNet composes a dependency
engine + KVStore comm strategies (``src/kvstore/comm.h``) + ps-lite servers
(``src/kvstore/kvstore_dist.h``) + NCCL (``kvstore_nccl.h``), this package
composes a ``jax.sharding.Mesh`` + ``jax.jit`` over sharded arrays: XLA
inserts the collectives (psum/all-gather/reduce-scatter) and routes them over
ICI.  Axes:

- ``dp``  — data parallel (batch dimension; the KVStore allreduce role)
- ``tp``  — tensor/model parallel (Megatron-style weight sharding; the
  reference only has manual ``ctx_group`` placement, §2.3)
- ``sp``  — sequence/context parallel (ring attention, §5.7 — absent in the
  reference and designed fresh here)
"""
from .mesh import make_mesh, device_mesh, current_mesh  # noqa: F401
from .sharding import (  # noqa: F401
    PartitionRule, infer_param_specs, named_sharding, data_shard_info,
)
from .optim import FunctionalOptimizer  # noqa: F401
from .trainer import SPMDTrainer, make_train_step  # noqa: F401
from .ulysses import ulysses_attention, ulysses_self_attention  # noqa: F401
from .ring_attention import (  # noqa: F401
    ring_attention, ring_self_attention, blockwise_attention_reference,
)
from .checkpoint import (  # noqa: F401
    save_spmd_checkpoint, load_spmd_checkpoint, SPMDCheckpointManager,
    CheckpointCorrupted, CommitBarrierTimeout,
)
from .pipeline import (gpipe, gpipe_interleaved,  # noqa: F401
                       pipeline_stage_loop, pipeline_train_1f1b)
from .moe import moe_layer, switch_moe_local  # noqa: F401
from .sp_context import (  # noqa: F401
    sequence_parallel_scope, current_sequence_parallel,
    traced_mesh_scope, traced_mesh,
)
