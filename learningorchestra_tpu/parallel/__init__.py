from learningorchestra_tpu.parallel.mesh import (  # noqa: F401
    MeshRuntime, get_runtime, local_mesh, pad_rows, replicate, shard_rows)
