"""Runtime services: fault tolerance (heartbeats, stragglers, elastic
rescale plans), the persistent-compilation-cache layer, and the
one-process-per-chip guard for code that starts child processes."""
import sys

from . import compile_cache
from .fault_tolerance import (ElasticPlanner, HeartbeatMonitor, RescalePlan,
                              SpikeGuard, StragglerDetector)

__all__ = ["ElasticPlanner", "HeartbeatMonitor", "RescalePlan", "SpikeGuard",
           "StragglerDetector", "compile_cache", "refuse_child_if_tpu_held"]


def refuse_child_if_tpu_held(child: str) -> None:
    """Raise before starting ``child`` when this process holds a TPU.

    A chip belongs to one process at a time: once this process has started
    JAX's TPU backend, a child that needs the chip fails or hangs.  Checks
    without initializing any backend, so a parent that never touched JAX
    stays free to start chip-holding children."""
    if "jax" not in sys.modules:
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized() \
            and xla_bridge.default_backend() == "tpu":
        raise RuntimeError(
            f"refusing to start {child}: this process already holds the "
            f"TPU, and a chip belongs to one process at a time (run it from "
            f"a parent that has not touched JAX, or in this process)")
