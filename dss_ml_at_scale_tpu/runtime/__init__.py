"""Runtime substrate: device meshes, process topology, multi-host init."""

from .mesh import (  # noqa: F401
    MeshSpec,
    batch_sharding,
    make_mesh,
    replicated_sharding,
    shard_batch_to_mesh,
)
from .compile_cache import enable_compile_cache  # noqa: F401
from .topology import Topology, local_topology  # noqa: F401
from .distributed import initialize_distributed  # noqa: F401
from .rpc import (  # noqa: F401
    RpcAuthError,
    RpcConnectTimeout,
    RpcHandshakeTimeout,
    RpcRemoteError,
    RpcServer,
    rpc_call,
)
