"""Task functions executed on real multi-process clusters by the launcher.

Imported by ``synapseml_tpu.parallel.worker`` subprocesses (the tests dir
rides the propagated sys.path).  Every function takes one JSON-decoded arg
and returns something JSON-serializable.
"""

import hashlib

import numpy as np


def _binary_data(n=2000, f=12, seed=7):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logits = X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


def distributed_serving_roundtrip(args):
    """Each rank: DistributedServingServer + echo pipeline; rank 0 routes
    one request to EVERY rank via the gathered routing table."""
    import json
    import threading
    import urllib.request

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from synapseml_tpu.parallel.collectives import psum, shard_map_over
    from synapseml_tpu.parallel.mesh import DATA_AXIS
    from synapseml_tpu.serving import DistributedServingServer, ServingReply

    devs = jax.devices()
    mesh = Mesh(np.array(devs), (DATA_AXIS,))

    def barrier():
        one = jnp.ones((len(devs),), jnp.float32)
        out = jax.jit(shard_map_over(mesh, P(DATA_AXIS), P(DATA_AXIS))(
            psum))(one)
        assert float(np.asarray(out.addressable_shards[0].data)[0]) == len(devs)

    rank = jax.process_index()
    srv = DistributedServingServer()
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            for req in srv.get_batch(max_rows=8, timeout_s=0.05):
                srv.reply(req.id, ServingReply(200, json.dumps(
                    {"rank": rank, "echo": req.json()["x"]}).encode()))

    t = threading.Thread(target=loop, daemon=True)
    t.start()
    barrier()                      # every rank's listener is up
    results = []
    if rank == 0:
        for r in range(len(srv.routing_table)):
            body = json.dumps({"x": r * 10}).encode()
            rep = urllib.request.urlopen(urllib.request.Request(
                srv.url_for_rank(r), data=body), timeout=10).read()
            results.append(json.loads(rep))
    barrier()                      # replies done before any rank closes
    stop.set()
    t.join(timeout=5)
    srv.close()
    return {"rank": rank,
            "table": [[h, p] for h, p in srv.routing_table],
            "results": results}


def compile_cache_probe(args):
    """Compile a jitted program and report the persistent compilation
    cache's verdict counters — the worker inherited
    ``JAX_COMPILATION_CACHE_DIR`` from the driver, so a FIRST gang
    launch reports misses (compiled + stored) and a RELAUNCH over the
    same dir reports hits (loaded from disk, no XLA)."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel.compilecache import (cache_stats,
                                                     compilation_cache_dir)

    f = jax.jit(lambda x: (x @ x.T).sum())
    float(f(jnp.ones((96, 96))))
    stats = cache_stats()
    return {"rank": jax.process_index(),
            "dir": compilation_cache_dir(), **stats}


def sleep_task(args):
    """Sleep then echo — gang-supervision scaffolding: with a
    ``heartbeat.emit=hang:rank=k`` fault armed via env, rank k's emitter
    wedges and the driver must declare the hang long before this sleep
    (or the global timeout) finishes."""
    import time

    import jax

    args = args or {}
    time.sleep(float(args.get("seconds", 30.0)))
    return {"rank": jax.process_index(), "ok": True}


def chatty_task(args):
    """Print a flood of lines (then optionally fail) — pins the driver's
    ring-buffered log tails: the WorkerFailure must carry only the tail,
    and the driver must not have grown with the flood."""
    import sys

    import jax

    args = args or {}
    n = int(args.get("lines", 5000))
    for i in range(n):
        print(f"chatty line {i:07d}", flush=(i % 500 == 0))
    sys.stdout.flush()
    if args.get("fail"):
        raise RuntimeError("chatty task failing as requested")
    return {"rank": jax.process_index(), "lines": n}


def elastic_counter(args):
    """Deterministic synthetic trainer with step checkpoints — the
    cheap elastic-relaunch pin (no XLA compile in the loop).

    Each step evolves an integer state through a fixed recurrence, saves
    a checkpoint, reports the step on the heartbeat channel, and passes
    the ``mp.step`` kill point (arm ``kill_rank``/``preempt`` there to
    die mid-train).  On relaunch the task restores the latest complete
    checkpoint from the gang's ``SMLTPU_CKPT_DIR`` and continues, so the
    final state must be bit-identical to a fault-free run.
    """
    import os
    import time

    import jax

    from synapseml_tpu.core.checkpoint import CheckpointManager
    from synapseml_tpu.parallel.heartbeat import beat
    from synapseml_tpu.resilience import get_faults

    args = args or {}
    steps = int(args.get("steps", 8))
    step_sleep_s = float(args.get("step_sleep_s", 0.0))
    ckpt_dir = os.environ.get("SMLTPU_CKPT_DIR") or args.get("ckpt_dir")
    # per-rank subdir: every rank checkpoints the (identical) state
    # without racing the others' atomic publishes
    if ckpt_dir:
        ckpt_dir = os.path.join(ckpt_dir, f"rank{jax.process_index()}")
    mgr = CheckpointManager(ckpt_dir, max_to_keep=3) if ckpt_dir else None
    state = np.int64(int(args.get("seed", 1)))
    start = 0
    if mgr is not None:
        latest = mgr.latest_step()
        if latest is not None:
            state = np.int64(np.asarray(mgr.restore(latest)["state"]))
            start = latest + 1
            # announce the restored durable position: the supervisor's
            # recovery clock closes on the first beat re-reaching the
            # dead attempt's best step, which on resume we already HOLD
            beat(step=latest)
    t_loop = time.perf_counter()
    for step in range(start, steps):
        state = np.int64((int(state) * 6364136223846793005 + 1442695040888963407)
                         % (1 << 63))
        if mgr is not None:
            mgr.save(step, {"state": np.asarray(state)})
        beat(step=step)
        get_faults().kill_point("mp.step", step=step,
                                rank=jax.process_index())
        if step_sleep_s > 0:
            time.sleep(step_sleep_s)
    loop_s = time.perf_counter() - t_loop
    # world_size makes the task RESIZE-capable scaffolding: the state
    # recurrence is world-size-free (f^steps(seed) whatever the gang
    # shape), so a shrunken/grown relaunch must still produce the
    # bit-exact fault-free state — and the result reports what size
    # actually ran (plus loop timing for the degraded-throughput
    # bench), so resize pins assert the topology too
    return {"rank": jax.process_index(), "state": int(state),
            "resumed_from": start, "steps_run": steps - start,
            "loop_s": round(loop_s, 4),
            "world_size": jax.process_count()}


def gbdt_elastic_digest(args):
    """GBDT training that checkpoints every iteration into the gang's
    ``SMLTPU_CKPT_DIR`` — the elastic-resume bit-exactness pin: SIGKILL
    one rank mid-train, let the supervisor relaunch, and the final model
    digest must equal the fault-free run's."""
    import hashlib
    import os

    import jax

    from synapseml_tpu.models.gbdt.booster import BoostingConfig, train
    from synapseml_tpu.parallel import data_parallel_mesh

    args = args or {}
    X, y = _binary_data(n=int(args.get("n", 400)), f=int(args.get("f", 8)))
    mesh = data_parallel_mesh(len(jax.devices()))
    cfg = BoostingConfig(objective="binary",
                         num_iterations=int(args.get("iters", 4)),
                         num_leaves=7, min_data_in_leaf=5, max_bin=31,
                         collective_compression=args.get("compression",
                                                         "none"))
    ckpt_dir = os.environ.get("SMLTPU_CKPT_DIR") or args.get("ckpt_dir")
    booster, _ = train(X, y, cfg, mesh=mesh,
                       checkpoint_dir=ckpt_dir, checkpoint_interval=1)
    text = booster.to_string()
    margins = booster.predict_margin(X[:8])
    # holdout AUC on a fixed fresh draw: the RESIZE acceptance metric —
    # a shrunken resume is documented tolerance-close (row repartition
    # reassociates the histogram psum), where same-size resume pins md5
    from synapseml_tpu.models.gbdt.metrics import auc as _auc
    Xh, yh = _binary_data(n=300, f=int(args.get("f", 8)), seed=99)
    ph = np.asarray(booster.predict_margin(Xh)).ravel()
    return {
        "rank": jax.process_index(),
        "world_size": jax.process_count(),
        "model_md5": hashlib.md5(text.encode()).hexdigest(),
        "margins": [round(float(m), 6) for m in np.asarray(margins).ravel()],
        "holdout_auc": round(float(_auc(yh, ph)), 6),
    }


def obs_probe(args):
    """Observability-plane scaffolding: registers worker-side metrics,
    opens spans, checkpoints each step and beats — everything the
    ``SMLMP_TM:`` wire should deliver to the driver, plus flight events
    (checkpoint/heartbeat/fault) for the post-mortem gather.  Passes the
    ``mp.step`` kill point so ``kill_rank`` schedules work unchanged."""
    import os
    import time

    import jax

    from synapseml_tpu.core.checkpoint import CheckpointManager
    from synapseml_tpu.parallel.heartbeat import beat
    from synapseml_tpu.resilience import get_faults
    from synapseml_tpu.telemetry import get_registry, span

    args = args or {}
    steps = int(args.get("steps", 6))
    step_sleep_s = float(args.get("step_sleep_s", 0.1))
    rank = jax.process_index()
    ckpt_dir = os.environ.get("SMLTPU_CKPT_DIR")
    if ckpt_dir:
        ckpt_dir = os.path.join(ckpt_dir, f"rank{rank}")
    mgr = CheckpointManager(ckpt_dir, max_to_keep=2) if ckpt_dir else None
    steps_c = get_registry().counter(
        "obs_probe_steps_total", "steps the obs-probe task ran", ("phase",))
    for step in range(steps):
        with span("obs_probe.step", step=step):
            steps_c.inc(1, phase="train")
            if mgr is not None:
                mgr.save(step, {"state": np.asarray(step)})
            beat(step=step)
            get_faults().kill_point("mp.step", step=step, rank=rank)
            if step_sleep_s > 0:
                time.sleep(step_sleep_s)
    return {"rank": rank, "steps": steps}


def gbdt_fit_digest(args):
    """Fit a GBDT over ALL global devices; return a bit-exact model digest.

    Run on a 1-process x 4-device cluster and a 2-process x 2-device cluster,
    the digests must be identical: the SPMD program is the same, only the
    process boundary moves (the reference's useSingleDatasetMode=false
    multi-worker parity, LightGBMBase.scala).
    """
    import jax
    from synapseml_tpu.models.gbdt.booster import BoostingConfig, train
    from synapseml_tpu.parallel import data_parallel_mesh

    args = args or {}
    X, y = _binary_data(n=int(args.get("n", 2000)))
    mesh = data_parallel_mesh(len(jax.devices()))
    cfg = BoostingConfig(objective="binary", num_iterations=6,
                         num_leaves=15, min_data_in_leaf=5)
    booster, _ = train(X, y, cfg, mesh=mesh)
    text = booster.to_string()
    margins = booster.predict_margin(X[:16])
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
        "model_md5": hashlib.md5(text.encode()).hexdigest(),
        "model_len": len(text),
        "margins": [round(float(m), 6) for m in np.asarray(margins).ravel()],
    }
