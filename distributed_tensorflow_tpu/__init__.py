"""distributed_tensorflow_tpu — a TPU-native distributed training framework.

A ground-up re-design of the capabilities of the reference
``BaiYuYuan/distributed-tensorflow`` (a TensorFlow fork whose core surface is
the NCCL-backed ``tf.distribute`` stack — see SURVEY.md) built idiomatically
on JAX/XLA for TPU:

- NCCL / ring allreduce            -> XLA collectives over ICI (psum et al.)
- grpc worker data plane           -> single-program SPMD execution (pjit)
- TF_CONFIG cluster resolution     -> kept, plus TPU-VM metadata discovery
- DistributedVariable              -> sharded ``jax.Array`` with NamedSharding
- MirroredStrategy / MWMS / PS     -> Strategy API over a ``jax.sharding.Mesh``
- coordination service             -> ``jax.distributed`` (TSL coord service)

Conventional import:

    import distributed_tensorflow_tpu as dtx
"""

from distributed_tensorflow_tpu.cluster.topology import (
    Topology,
    DeviceAssignment,
    make_mesh,
)
from distributed_tensorflow_tpu.cluster.resolver import (
    ClusterSpec,
    ClusterResolver,
    SimpleClusterResolver,
    TFConfigClusterResolver,
    TPUClusterResolver,
)
from distributed_tensorflow_tpu.cluster import bootstrap
from distributed_tensorflow_tpu.cluster.bootstrap import initialize

from distributed_tensorflow_tpu.parallel.collectives import (
    CollectiveType,
    ReduceOp,
    CommunicationImplementation,
    CommunicationOptions,
)
from distributed_tensorflow_tpu.parallel import collectives
from distributed_tensorflow_tpu.parallel.values import (
    DistributedValues,
    PerReplica,
    Mirrored,
    DistributedVariable,
    MirroredVariable,
    SyncOnReadVariable,
    VariableSynchronization,
    VariableAggregation,
)
from distributed_tensorflow_tpu.parallel.sharded_variable import (
    Partitioner,
    FixedShardsPartitioner,
    MinSizePartitioner,
    MaxSizePartitioner,
    ShardedVariable,
)
from distributed_tensorflow_tpu.parallel.cross_device_ops import (
    CrossDeviceOps,
    ReductionToOneDevice,
    IciAllReduce,
    HierarchicalAllReduce,
    select_cross_device_ops,
)
from distributed_tensorflow_tpu.parallel.strategy import (
    Strategy,
    ReplicaContext,
    get_replica_context,
    get_strategy,
    has_strategy,
    in_cross_replica_context,
)
from distributed_tensorflow_tpu.parallel.one_device import OneDeviceStrategy
from distributed_tensorflow_tpu.parallel.mirrored import MirroredStrategy
from distributed_tensorflow_tpu.parallel.multi_worker import (
    MultiWorkerMirroredStrategy,
    CollectiveAllReduceStrategy,
)
from distributed_tensorflow_tpu.parallel.tpu_strategy import TPUStrategy
from distributed_tensorflow_tpu.parallel.parameter_server import (
    ParameterServerStrategy,
    ParameterServerStrategyV1,
    ParameterServerStrategyV2,
)
from distributed_tensorflow_tpu.parallel.central_storage import (
    CentralStorageStrategy,
)
from distributed_tensorflow_tpu.parallel.ps_values import (
    AggregatingVariable,
    CachingVariable,
)
from distributed_tensorflow_tpu.cluster.platform_resolvers import (
    GCEClusterResolver,
    KubernetesClusterResolver,
    SageMakerClusterResolver,
    SlurmClusterResolver,
)

from distributed_tensorflow_tpu.input.dataset import (
    AutoShardPolicy,
    InputOptions,
    Dataset,
    DistributedDataset,
)

from distributed_tensorflow_tpu import models
from distributed_tensorflow_tpu import ops
from distributed_tensorflow_tpu import training
from distributed_tensorflow_tpu import keras
from distributed_tensorflow_tpu import embedding
from distributed_tensorflow_tpu.cluster.coordination import (
    coordination_service,
)
from distributed_tensorflow_tpu import resilience
from distributed_tensorflow_tpu.resilience import RetryPolicy
from distributed_tensorflow_tpu import serving
from distributed_tensorflow_tpu.utils import bfloat16
from distributed_tensorflow_tpu.utils import summary
from distributed_tensorflow_tpu.utils import tensor_tracer

__version__ = "0.1.0"
