"""Worker entry point for the multi-process launcher.

One OS process per cluster rank: configure the backend BEFORE it
initializes, rendezvous through ``initialize_cluster``, run the task, print
the JSON result behind a marker the driver greps for.  This is the worker
half of the reference's handshake (NetworkManager.scala:123-169 — there the
worker phones the driver's ServerSocket and blocks on the machine-list
reply; here ``jax.distributed.initialize`` is both legs).

Gang supervision hooks (all driver-controlled via env):

- ``SMLTPU_HB_INTERVAL_S`` > 0 starts the heartbeat emitter thread FIRST,
  so the driver distinguishes "still importing jax" (beats flowing, no
  step) from "process wedged" (beats stopped) from "boot failure" (no
  beat at all).
- ``SMLTPU_RENDEZVOUS_TIMEOUT_S`` arms a host-side watchdog around the
  blocking ``initialize_cluster`` call: a coordinator that never answers
  becomes a structured :class:`~synapseml_tpu.parallel.collectives.
  CollectiveTimeout` (op ``rendezvous``) and a fast non-zero exit, not an
  indefinitely-hung rank.
- ``SMLTPU_CKPT_DIR`` names the gang's checkpoint directory; tasks read
  it to resume elastically after a relaunch.
- the persistent compilation cache needs no hook: the worker inherits
  ``JAX_COMPILATION_CACHE_DIR`` from the driver's environment (the
  package import exports the resolved directory), so a relaunched or
  resized gang loads compiled executables from disk instead of
  re-running XLA.

Gang observability hooks (see :mod:`synapseml_tpu.telemetry.gangplane`):

- ``SMLTPU_TM_INTERVAL_S`` > 0 starts the telemetry wire emitter beside
  the heartbeat thread: one ``SMLMP_TM:`` line per interval carrying the
  cumulative metric snapshot plus incremental completed spans and flight
  events.  A FINAL batch flushes synchronously before the result marker,
  so a clean exit drops no spans or metrics (satisfying the contract
  that ``shutdown_cluster`` loses nothing a crash wouldn't).
- ``SMLTPU_OBS_DIR`` names the observability directory: the flight
  recorder's ring dumps there SIGKILL-atomically (``flight-rank<r>.json``)
  on SIGTERM — the teardown signal a failing gang's healthy peers
  receive — and again on clean exit, giving the driver's post-mortem
  gather the full ring instead of the bounded wire tail.

Run as ``python -m synapseml_tpu.parallel.worker`` with the SMLTPU_* env
set by ``launcher.run_on_local_cluster``.
"""

from __future__ import annotations

import importlib
import json
import os
import signal
import sys


def _flight_dump_path(obs_dir: str, rank: int) -> str:
    return os.path.join(obs_dir, f"flight-rank{rank}.json")


def _install_flight_dump(rank: int):
    """SIGTERM → dump the flight ring, then exit 143 without unwinding
    (the rank may be parked in a dead collective no ``finally`` block
    would ever reach).  Returns ``(dump, install)`` — the dump callable
    for the clean path and the installer for re-arming — or None when no
    obs dir is configured.  Re-arming matters: ``jax.distributed``'s
    rendezvous registers XLA's own SIGTERM preemption notifier, which
    would silently replace this handler, so the worker installs once
    early (covers a teardown DURING rendezvous) and again right after
    the cluster forms."""
    from synapseml_tpu.telemetry.gangplane import OBS_DIR_ENV
    obs_dir = os.environ.get(OBS_DIR_ENV)
    if not obs_dir:
        return None
    from synapseml_tpu.telemetry.flight import get_flight

    def dump() -> None:
        try:
            get_flight().dump(_flight_dump_path(obs_dir, rank), rank=rank)
        except BaseException:
            pass                # a failed dump must not mask the teardown

    def on_term(signum, frame):  # pragma: no cover - signal path
        dump()
        os._exit(143)

    def install() -> None:
        try:
            signal.signal(signal.SIGTERM, on_term)
        except (ValueError, OSError):   # non-main thread / exotic platform
            pass

    install()
    return dump, install


def main() -> int:
    coordinator = os.environ["SMLTPU_COORDINATOR"]
    n_procs = int(os.environ["SMLTPU_NUM_PROCESSES"])
    rank = int(os.environ["SMLTPU_PROCESS_ID"])
    platform = os.environ.get("SMLTPU_PLATFORM") or None
    local_devices = int(os.environ.get("SMLTPU_LOCAL_DEVICES", "0")) or None
    task = os.environ["SMLTPU_TASK"]
    task_args = json.loads(os.environ.get("SMLTPU_TASK_ARGS", "null"))

    # heartbeats first: the gang supervisor must see this rank alive
    # before (and during) the slow rendezvous below
    from synapseml_tpu.parallel import heartbeat
    emitter = heartbeat.start_emitter(rank)
    # telemetry wire export + the crash flight dump ride the same early
    # start: the driver holds a near-current tail even for a rank that
    # dies during the rendezvous
    from synapseml_tpu.telemetry import gangplane
    tm_emitter = gangplane.start_emitter(rank)
    flight_hooks = _install_flight_dump(rank)
    flight_dump = flight_hooks[0] if flight_hooks else None

    # compile/cache-hit attribution, before anything compiles (the cache
    # directory itself came in through the environment)
    from synapseml_tpu.parallel.compilecache import \
        install_compile_listeners
    install_compile_listeners()

    from synapseml_tpu.parallel.distributed import (ClusterConfig,
                                                    initialize_cluster,
                                                    shutdown_cluster)
    cfg = ClusterConfig(
        coordinator_address=coordinator,
        num_processes=n_procs,
        process_id=rank,
        platform=platform,
        local_device_count=local_devices,
    )
    rdv_timeout = float(
        os.environ.get("SMLTPU_RENDEZVOUS_TIMEOUT_S", "0") or 0)
    if rdv_timeout > 0:
        from synapseml_tpu.parallel.collectives import dispatch_watchdog
        dispatch_watchdog(initialize_cluster, cfg,
                          op="rendezvous", axis="-",
                          timeout_s=rdv_timeout)
    else:
        initialize_cluster(cfg)
    heartbeat.beat(step=0)        # rendezvoused: step 0 is reachable
    if flight_hooks is not None:
        flight_hooks[1]()         # re-arm: the rendezvous installed XLA's
        #                           SIGTERM notifier over our dump handler

    mod_name, fn_name = task.split(":", 1)
    fn = getattr(importlib.import_module(mod_name), fn_name)
    result = fn(task_args)
    # the final telemetry batch flushes BEFORE the result marker: clean
    # exits must drop no spans or metrics (the periodic loop stops first
    # so the flush cannot interleave with a concurrent emission)
    if tm_emitter is not None:
        tm_emitter.stop()
        tm_emitter.emit_now(final=True)
    # marker line is the contract with launcher.run_on_local_cluster —
    # a single write call so the heartbeat thread's lines cannot land
    # between the result text and its newline
    sys.stdout.write("SMLMP_RESULT:" + json.dumps(result) + "\n")
    sys.stdout.flush()
    # keep beating THROUGH the distributed shutdown: it can take longer
    # than the hang threshold, and a rank finishing cleanly must not be
    # declared hung in its last second
    shutdown_cluster()
    if emitter is not None:
        emitter.stop()
    if flight_dump is not None:
        flight_dump()             # clean-path dump: the full on-disk ring
    return 0


if __name__ == "__main__":
    sys.exit(main())
