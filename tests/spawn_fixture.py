"""Child entry for test_distributed spawn tests (module-level so the
'spawn' start method can pickle it)."""

import json
import os


def write_env_info(out_dir):
    # JAX_PLATFORMS=cpu is inherited from the suite (tests/conftest.py)
    import paddle_tpu.distributed as dist

    env = dist.ParallelEnv()
    initialized = dist.init_parallel_env()
    import jax

    info = {"rank": env.rank, "world_size": env.world_size,
            "initialized": initialized,
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "endpoints": env.trainer_endpoints,
            "current_endpoint": env.current_endpoint}
    with open(os.path.join(out_dir, f"rank{env.rank}.json"), "w") as f:
        json.dump(info, f)
    # barrier before exit: rank 0 hosts the coordination service — if it
    # returns first the service dies under the still-joining peers
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("spawn_fixture_done")


def crash_on_rank1(out_dir):
    import paddle_tpu.distributed as dist

    if dist.ParallelEnv().rank == 1:
        raise RuntimeError("boom")  # peers are left blocked in rendezvous
    dist.init_parallel_env()  # blocks waiting for the crashed peer
