from repro.io import IOConfig, IOEngine, IOPriority  # noqa: F401
from repro.offload.checkpoint import (CheckpointError,  # noqa: F401
                                      load_manifest, restore_checkpoint,
                                      save_checkpoint)
from repro.offload.autotune import (AutotuneConfig,  # noqa: F401
                                    AutotuneController,
                                    route_seconds_error)
from repro.core.plan import shard_bounds  # noqa: F401
from repro.offload.engine import OffloadConfig, OffloadEngine  # noqa: F401
from repro.offload.stores import (HostStore, SSDStore, TieredVector,  # noqa: F401
                                  TrafficMeter)
from repro.offload.buffers import naive_padded, pack, waste_ratio  # noqa: F401


def make_engine(cfg, ocfg, key, workdir, *, io_cfg=None, num_ranks=1):
    """The one documented construction path for offload engines.

    Builds an :class:`OffloadEngine` on ``num_ranks`` data-parallel
    ranks (1: the single-rank engine) from the model config,
    :class:`OffloadConfig`, PRNG key, and the SSD workdir. ``io_cfg``
    (an :class:`IOConfig`) overrides ``ocfg.io`` when given — handy when
    the storage topology (paths, pacing, placement policy) is decided
    separately from the schedule. Config validation is eager: a typo'd
    ``schedule`` / ``activation_policy`` / ``path_policy``, or a rank
    count the engine refuses, raises ``ValueError`` here, before any
    file or thread exists. ``repro.serve.ServeEngine`` builds its I/O
    stack through the same configs.
    """
    import dataclasses as _dc

    if io_cfg is not None:
        ocfg = _dc.replace(ocfg, io=io_cfg)
    if num_ranks < 1:
        raise ValueError(f"num_ranks={num_ranks} must be >= 1")
    return OffloadEngine(cfg, ocfg, key, workdir, ranks=num_ranks)
