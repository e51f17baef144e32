"""Multi-host rendezvous — the NetworkManager replacement.

The reference rendezvouses workers through a driver ServerSocket handshake
(status:host:port:partition:executor messages, machine-list broadcast —
reference: NetworkManager.scala:55-80,123-169,294-440).  On TPU the
rendezvous is ``jax.distributed.initialize`` against a coordinator address;
after it, every process sees the global device set and collectives need no
further setup.  Retry semantics mirror the reference's exponential backoff
around ``LGBM_NetworkInit`` (NetworkManager.scala:182-205).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Optional

import jax

logger = logging.getLogger("synapseml_tpu")


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Rendezvous parameters (the machine-list analogue)."""
    coordinator_address: Optional[str] = None   # "host:port"
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    init_timeout_s: float = 300.0
    #: force a backend ("cpu" for the simulated multi-host slice; None keeps
    #: the ambient platform — on a TPU pod the runtime picks the TPU backend)
    platform: Optional[str] = None
    #: virtual devices per process (CPU backend only; a TPU host's chip
    #: count is fixed by hardware)
    local_device_count: Optional[int] = None


_initialized = False


def _configure_backend(cfg: ClusterConfig) -> None:
    """Apply platform/device-count config BEFORE the JAX backend exists.

    The CPU backend only joins cross-process collectives when its gloo
    implementation is selected at client-creation time, so this must run
    before anything touches ``jax.devices()``.
    """
    if cfg.platform is not None:
        jax.config.update("jax_platforms", cfg.platform)
    if cfg.platform == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if cfg.local_device_count:
            jax.config.update("jax_num_cpu_devices", cfg.local_device_count)


def initialize_cluster(config: Optional[ClusterConfig] = None,
                       max_retries: int = 5,
                       base_delay_s: float = 1.0) -> None:
    """Join the cluster; idempotent; no-op when single-process (the local[*]
    analogue) or when running under a managed TPU runtime that already
    initialized. Retries with exponential backoff like the reference's
    NetworkInit (NetworkManager.scala:182-205)."""
    global _initialized
    if _initialized:
        return
    cfg = config or ClusterConfig()
    if cfg.coordinator_address is None and cfg.num_processes in (None, 1):
        _initialized = True   # single host: nothing to rendezvous
        return
    _configure_backend(cfg)
    delay = base_delay_s
    last: Optional[BaseException] = None
    for attempt in range(max_retries):
        try:
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id,
                initialization_timeout=int(cfg.init_timeout_s),
            )
            _initialized = True
            logger.info("joined cluster: process %d/%d",
                        jax.process_index(), jax.process_count())
            return
        except Exception as e:
            last = e
            logger.warning("rendezvous attempt %d failed: %s", attempt, e)
            # jax.distributed.initialize sets global state before connecting;
            # clear it or every retry raises "should only be called once"
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            time.sleep(delay)
            delay *= 2
    raise RuntimeError(f"cluster rendezvous failed after {max_retries} attempts") from last


def shutdown_cluster() -> None:
    global _initialized
    if _initialized and jax.process_count() > 1:
        jax.distributed.shutdown()
    _initialized = False
