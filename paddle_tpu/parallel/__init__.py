"""paddle_tpu.parallel: the distributed stack (reference:
python/paddle/distributed). Aliased as `paddle_tpu.distributed`.

Layer map (SURVEY.md §2.3/§2.4 -> TPU):
- topology/HCG            -> mesh.py (one jax Mesh, axes dp/pp/sharding/sep/mp)
- communication/*         -> collective.py (XLA collectives facade)
- auto_parallel semi-auto -> api.py + placement.py (shard_tensor/reshard)
- fleet.layers.mpu        -> mpu.py (TP layers)
- meta_parallel sharding  -> sharding.py (ZeRO 1/2/3 as sharding specs)
- pipeline_parallel       -> pipeline.py (shard_map+ppermute scan)
- sequence_parallel/sep   -> sequence_parallel.py (SP utils + Ulysses)
- moe                     -> moe.py
- fleet facade            -> fleet.py
- env/launch              -> env.py
"""
from .env import ParallelEnv, get_rank, get_world_size  # noqa: F401
from .mesh import (  # noqa: F401
    HYBRID_AXES, HybridCommunicateGroup, auto_mesh, build_mesh,
    get_global_mesh, set_global_mesh,
)
from .placement import (  # noqa: F401
    Partial, Placement, ProcessMesh, Replicate, Shard,
)
from .api import (  # noqa: F401
    dtensor_from_fn, get_placements, reshard, shard_constraint, shard_layer,
    shard_optimizer, shard_tensor, unshard_dtensor,
)
from .spmd_rules import (  # noqa: F401
    get_spmd_rule, register_spmd_rule, shard_parameters,
    with_spmd_constraint,
)
from .collective import (  # noqa: F401
    Group, ReduceOp, all_gather, all_gather_object, all_reduce, all_to_all,
    all_to_all_single, barrier, broadcast, gather, get_group, irecv, isend,
    new_group, recv, reduce, reduce_scatter, scatter, send, stream,
)
from .data_parallel import DataParallel, scale_batch  # noqa: F401
from .sharding import (  # noqa: F401
    group_sharded_parallel, save_group_sharded_model, shard_accumulators,
    shard_params_stage3,
)
from .pipeline import (  # noqa: F401
    LayerDesc, PipelineLayer, PipelineParallel, SharedLayerDesc,
    pipeline_apply,
)
from .sequence_parallel import (  # noqa: F401
    AllGatherOp, GatherOp, ReduceScatterOp, ScatterOp, SegmentParallel,
    gather_seq, mark_as_sequence_parallel_parameter, sep_attention_context,
    split_seq, ulysses_alltoall,
)
from .moe import GShardGate, MoELayer, NaiveGate, SwitchGate, moe_dispatch  # noqa: F401
from .moe import BiasBalancedSigmoidGate, DroplessMoELayer  # noqa: F401
from .fleet import DistributedStrategy  # noqa: F401
from . import fleet  # noqa: F401  (module; its own `fleet` instance plus
#                      init/distributed_model are module-level, matching the
#                      reference where paddle.distributed.fleet is a module)
from . import auto_tuner  # noqa: F401
from . import checkpoint  # noqa: F401
from . import cost_model  # noqa: F401
from . import elastic  # noqa: F401
from . import pipeline_spmd  # noqa: F401
from .pipeline_spmd import pipeline_forward, stack_stage_params  # noqa: F401
from . import pipeline_viz  # noqa: F401
from .pipeline_viz import (  # noqa: F401
    pipeline_timeline, render_timeline, save_chrome_trace, timeline_stats,
)
from . import ring_attention as ring_attention_mod  # noqa: F401
from .ring_attention import ring_attention  # noqa: F401
from . import watchdog  # noqa: F401
from .watchdog import StepWatchdog, barrier  # noqa: F401
from .elastic import ElasticManager  # noqa: F401
from .checkpoint import load_state_dict, save_state_dict  # noqa: F401
from .trainer import (  # noqa: F401
    AdamWState, adamw_update, init_adamw_state, make_eval_step,
    make_train_step, read_report,
)
from . import mpu  # noqa: F401
from . import collective as communication  # noqa: F401
from . import collectives  # noqa: F401
from .collectives import (  # noqa: F401
    quantized_all_gather, quantized_psum, quantized_psum_tree,
    quantized_reduce_scatter, resolve_quantized_collectives,
)


def init_parallel_env():
    """reference: python/paddle/distributed/parallel.py:957 — NCCL/TCPStore
    bootstrap. Single-controller JAX needs no per-rank rendezvous on one
    host; multi-host uses jax.distributed.initialize (env.init_distributed)."""
    from .env import init_distributed

    init_distributed()
    return ParallelEnv()


def spawn(func, args=(), nprocs=-1, **kwargs):
    """reference: python/paddle/distributed/spawn.py. Single-controller JAX
    owns all local devices in one process — run inline (nprocs>1 has no
    per-process meaning here)."""
    if nprocs not in (-1, 1):
        import warnings

        warnings.warn(
            f"paddle_tpu.distributed.spawn: nprocs={nprocs} ignored — "
            "single-controller JAX drives all devices from one process; "
            "running func inline once.")
    func(*args)
from . import compat  # noqa: E402,F401
from .compat import (  # noqa: E402,F401
    CountFilterEntry, DistAttr, DistModel, InMemoryDataset, ParallelMode,
    ProbabilityEntry, QueueDataset, ReduceType, ShardingStage1,
    ShardingStage2, ShardingStage3, ShowClickEntry, Strategy, alltoall,
    alltoall_single, broadcast_object_list, destroy_process_group,
    get_backend, gloo_barrier, gloo_init_parallel_env, gloo_release,
    is_available, is_initialized, scatter_object_list, shard_dataloader,
    shard_scaler, split, to_static, wait)
from . import launch  # noqa: E402,F401
from . import checkpoint as io  # noqa: E402,F401
from . import passes  # noqa: E402,F401
