from pytorchdistributed_tpu.runtime.mesh import (  # noqa: F401
    Axis,
    MeshConfig,
    create_mesh,
    local_mesh,
)
from pytorchdistributed_tpu.runtime.dist import (  # noqa: F401
    init_process_group,
    destroy_process_group,
    get_rank,
    get_world_size,
    is_initialized,
)
