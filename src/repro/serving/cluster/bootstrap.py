"""Process bootstrap for the multi-host AIDW serving fleet.

One serving fleet is N host *processes* (plus, degenerately, N in-process
hosts for tests and single-machine runs).  Each process calls
:func:`bootstrap` once at startup to learn

* its identity — ``host_id`` in ``[0, n_hosts)`` (host 0 is the
  coordinator: it owns the :class:`~repro.serving.cluster.epochs
  .EpochCoordinator` and the query :class:`~repro.serving.cluster.router
  .Router`),
* its **local** device mesh — the data plane is deliberately per-host
  (every host serves queries against its own dataset replica on its own
  devices; consistency comes from the epoch protocol, not from cross-host
  collectives), so the mesh is built over ``jax.local_devices()`` only,
* whether ``jax.distributed`` is active — when a coordinator address is
  given the runtime is initialized multi-controller style
  (``jax.distributed.initialize``), which pins ``process_index`` /
  ``process_count`` to the fleet identity and lets future cross-host
  collectives (ring-sharded datasets over the fleet) reuse the same
  bootstrap.  CPU test fleets run this for real: 2 processes x 4 forced
  host devices (``--xla_force_host_platform_device_count=4``) is the CI
  cluster-suite configuration.

``jax.distributed`` is OPTIONAL: transport-only fleets (the load
generator's ``--cluster-procs`` mode) skip it and take identity from the
explicit config, falling back to ``AIDW_CLUSTER_*`` environment variables —
the control plane (``repro.serving.cluster.rpc``) is plain sockets either
way.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

__all__ = ["ClusterConfig", "ClusterContext", "bootstrap", "local_mesh"]


@dataclass(frozen=True)
class ClusterConfig:
    """Fleet identity + bootstrap knobs for ONE host process.

    ``jax_coordinator`` (``host:port``) turns on ``jax.distributed``;
    ``control_port`` is the base TCP port for the serving control plane
    (host ``i`` listens on ``control_port + i``; see ``cluster.rpc``).
    """

    n_hosts: int = 1
    host_id: int = 0
    jax_coordinator: str | None = None
    control_host: str = "127.0.0.1"
    control_port: int = 29900
    mesh_axis: str = "q"
    use_local_mesh: bool = True       # serve across all local devices
    # persistent XLA compilation cache directory (None = the default of
    # compile_cache.enable).  Fleet host processes bootstrapped with the
    # same directory SHARE one cache: the first host compiles, every later
    # join deserializes.
    cache_dir: str | None = None

    @classmethod
    def from_env(cls, **overrides) -> "ClusterConfig":
        """Identity from ``AIDW_CLUSTER_{N_HOSTS,HOST_ID,JAX_COORDINATOR,
        CONTROL_HOST,CONTROL_PORT}`` env vars (plus ``AIDW_CACHE_DIR`` for
        the shared compilation cache), overridable by kwargs."""
        env = {
            "n_hosts": int(os.environ.get("AIDW_CLUSTER_N_HOSTS", "1")),
            "host_id": int(os.environ.get("AIDW_CLUSTER_HOST_ID", "0")),
            "jax_coordinator":
                os.environ.get("AIDW_CLUSTER_JAX_COORDINATOR") or None,
            "control_host":
                os.environ.get("AIDW_CLUSTER_CONTROL_HOST", "127.0.0.1"),
            "control_port":
                int(os.environ.get("AIDW_CLUSTER_CONTROL_PORT", "29900")),
            "cache_dir": os.environ.get("AIDW_CACHE_DIR") or None,
        }
        env.update(overrides)
        return cls(**env)

    def control_address(self, host_id: int) -> tuple[str, int]:
        return self.control_host, self.control_port + int(host_id)


@dataclass
class ClusterContext:
    """What :func:`bootstrap` hands the rest of the cluster stack."""

    cfg: ClusterConfig
    host_id: int
    n_hosts: int
    mesh: object | None               # LOCAL mesh (None = single device)
    jax_distributed: bool             # jax.distributed.initialize succeeded

    @property
    def is_coordinator(self) -> bool:
        return self.host_id == 0

    def shutdown(self) -> None:
        """Deregister from ``jax.distributed`` (no-op otherwise).

        The coordination service runs a fleet-wide SHUTDOWN BARRIER: every
        process must call this (the worker after its serve loop drains, the
        coordinator once it has closed its remote-host proxies) or the
        stragglers' processes are killed by the service's heartbeat-timeout
        error propagation.  Local jax stays usable afterwards.
        """
        if not self.jax_distributed:
            return
        import jax

        jax.distributed.shutdown()
        self.jax_distributed = False


def local_mesh(axis: str = "q"):
    """1-D mesh over this process's LOCAL devices (None if just one).

    Built from ``jax.local_devices()`` explicitly — ``jax.make_mesh``
    defaults to the GLOBAL device list, which under ``jax.distributed``
    would silently build a cross-process mesh the per-host data plane must
    not use.
    """
    import jax
    import numpy as np

    devs = jax.local_devices()
    if len(devs) <= 1:
        return None
    return jax.sharding.Mesh(np.asarray(devs), (axis,))


def bootstrap(cfg: ClusterConfig | None = None, **overrides) -> ClusterContext:
    """Initialize this process's fleet identity (idempotent per process).

    With ``cfg.jax_coordinator`` set and ``n_hosts > 1``, runs
    ``jax.distributed.initialize`` (all fleet processes must do so — it
    barriers on the coordinator) and cross-checks the fleet identity
    against ``jax.process_index``/``process_count``.  Without it, identity
    is taken from the config/env alone: the serving data plane never needs
    cross-process collectives, so a transport-only fleet is fully
    functional.
    """
    if cfg is None:
        cfg = ClusterConfig.from_env(**overrides)
    elif overrides:
        raise ValueError("pass either a ClusterConfig or overrides, not both")
    if not (0 <= cfg.host_id < cfg.n_hosts):
        raise ValueError(
            f"host_id {cfg.host_id} out of range for n_hosts={cfg.n_hosts}")

    # persistent compilation cache BEFORE any compile: subprocess fleet
    # hosts bootstrapped with the same directory (flag or AIDW_CACHE_DIR)
    # share one cache, so a joining host deserializes the ladder the first
    # host compiled.  Also installs the compile-event listeners that feed
    # the per-host compile_cache_hits/misses counters.
    from ...runtime import compile_cache
    compile_cache.enable(cfg.cache_dir)

    import jax

    distributed = False
    if cfg.n_hosts > 1 and cfg.jax_coordinator:
        try:
            jax.distributed.initialize(
                coordinator_address=cfg.jax_coordinator,
                num_processes=cfg.n_hosts, process_id=cfg.host_id)
            distributed = True
        except RuntimeError:
            # already initialized (bootstrap called twice in-process): keep
            # going with the existing runtime rather than failing the host
            distributed = jax.process_count() == cfg.n_hosts
        if distributed and (jax.process_index() != cfg.host_id
                            or jax.process_count() != cfg.n_hosts):
            raise RuntimeError(
                f"fleet identity mismatch: config says host "
                f"{cfg.host_id}/{cfg.n_hosts}, jax.distributed says "
                f"{jax.process_index()}/{jax.process_count()}")

    mesh = local_mesh(cfg.mesh_axis) if cfg.use_local_mesh else None
    return ClusterContext(cfg=cfg, host_id=cfg.host_id, n_hosts=cfg.n_hosts,
                          mesh=mesh, jax_distributed=distributed)
