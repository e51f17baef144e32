"""Runner of kind ``dl_train``: fine-tuning steps of ``DLTrainer`` on a data
mesh over the cell's chips.

Set-up builds ONE object, the trainer's compiled step with its state (the
parameters made from the seed by the configuration's reference and placed in
the program's own tree and shardings), drives it through its first three
steps on the seed's first three batches, keeping what the comparison needs
(each loss; Adam's first moment after step 1, which is a tenth of the first
gradient as the optimizer got it; the parameters after step 3), and hands the
same step and state to the window.  The window: a fixed number of further
steps (``traffic.job_units``) on a cycle of host-prepared batches, each put on
the device through ``trainer.shard_batch`` as a user's loop does, ended by
``block_until_ready`` on the last step's loss.  Then the state is dropped and
the reference follows the same three steps in float32.
"""

from __future__ import annotations

import gc
import re
import time
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from benchmark import harness


def ref_name(path: Tuple[Any, ...], renames: List[List[str]]) -> str:
    """The reference's name of a program leaf: the inverse of ``renames``
    is not needed, so the program's path is renamed toward the reference."""
    # dict keys name the leaf; attribute keys are flax's partitioning boxes
    name = "/".join(str(k.key) for k in path if hasattr(k, "key"))
    for pattern, repl in renames:
        name = re.sub(pattern, repl, name)
    return name


def place_params(state, ref_params: Dict[str, Any], renames):
    """The reference's parameters in the program's tree, each leaf with the
    sharding the program gave it."""
    import jax
    flat, treedef = jax.tree_util.tree_flatten_with_path(state.params)
    used, leaves = set(), []
    for path, old in flat:
        name = ref_name(path, renames)
        if name not in ref_params:
            raise SystemExit(f"the reference has no parameter {name!r} for the "
                             f"program's leaf {jax.tree_util.keystr(path)}")
        new = ref_params[name]
        if new.shape != old.shape:
            raise SystemExit(f"{name}: reference {new.shape}, program {old.shape}")
        used.add(name)
        leaves.append(jax.device_put(new.astype(old.dtype), old.sharding))
    if used != set(ref_params):
        raise SystemExit(f"the program has no leaf for {sorted(set(ref_params) - used)}")
    names = [ref_name(p, renames) for p, _ in flat]
    return state.replace(params=jax.tree_util.tree_unflatten(treedef, leaves)), names


def host_leaves(tree, names: List[str]) -> Dict[str, np.ndarray]:
    import jax
    return {n: np.asarray(x) for n, x in zip(names, jax.tree.leaves(tree))}


def norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(v.astype(np.float64)))))
            for k, v in tree.items()}


def build(cell, seed: int, devs):
    """(trainer, state, step, feed, batches, p0, names): the one object the
    first steps and the window both drive."""
    import jax

    from synapseml_tpu.models.dl.precision import resolve_precision
    from synapseml_tpu.models.dl.training import DLTrainer, OptimizerConfig
    from synapseml_tpu.parallel.mesh import make_mesh

    cfg, ref = cell.config, cell.reference()
    mesh = make_mesh({"data": len(devs)}, devs)
    model_cfg = harness.import_object(cfg["model"]["config_class"])(
        **{arg: cfg[key] for arg, key in cfg["model"]["config_args"].items()})
    model = harness.import_object(cfg["model"]["class"])(model_cfg)
    trainer = DLTrainer(model, OptimizerConfig(
        name="adamw", learning_rate=cfg["learning_rate"],
        weight_decay=cfg["weight_decay"]), mesh,
        precision=resolve_precision(cfg["trainer_precision"]))
    batches = ref.make_batches(cfg, seed, int(cell.traffic["distinct_batches"]))
    mask = np.ones(batches[0][0].shape, bool)
    state = trainer.init_state(0, batches[0][0], mask)
    p0 = ref.init_params(cfg, seed)
    state, names = place_params(state, p0, cfg["model"]["renames"])
    p0 = {k: np.asarray(v) for k, v in p0.items()}
    step = trainer.train_step()
    key = jax.random.PRNGKey(0)

    def feed(state, ids, labels):
        with harness.annotate("train.input"):
            bi, bm, bl = trainer.shard_batch((ids, mask[:len(ids)], labels))
        with harness.annotate("train.step"):
            return step(state, (bi, bm), bl, key)
    return trainer, state, feed, batches, p0, names


def first_steps(state, feed: Callable, batches, p0, names) -> Tuple[Any, Dict]:
    """Steps 1 to 3 through the window's own call and feed; what the
    comparison needs is copied to the host before the next step donates it."""
    import jax
    losses, mu1 = [], None
    for t in range(3):
        state, m = feed(state, *batches[t])
        losses.append(float(np.asarray(m["loss"])))
        if t == 0:
            adam = [s for s in jax.tree.leaves(
                state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu")]
            if len(adam) != 1:
                raise SystemExit("no single Adam state in the optimizer state")
            mu1 = host_leaves(adam[0].mu, names)
    p3 = host_leaves(state.params, names)
    first = norms({k: v / (1.0 - 0.9) for k, v in mu1.items()})
    change = norms({k: p3[k].astype(np.float64) - p0[k] for k in p3})
    return state, {"losses": losses, "first_grad_norm": first,
                   "change_norm": change}


def compare(cell, prog: Dict[str, Any], refd: Dict[str, Any]) -> Dict[str, Any]:
    g = cell.reference().gaps(prog, refd)
    lim = cell.config["limits"]
    return {"compared": {k: {"value": g[k], "limit": lim[k]} for k in lim},
            "detail": {k: v for k, v in g.items() if k not in lim}}


def run(cell, seed: int, seconds: float, trace: bool, devs, compiles
        ) -> Dict[str, Any]:
    import jax

    from benchmark import traffic as traffic_mod

    cfg, ref = cell.config, cell.reference()
    trainer, state, feed, batches, p0, names = build(cell, seed, devs)
    state, prog = first_steps(state, feed, batches, p0, names)
    harness.say(f"first steps' losses {prog['losses']}; "
                f"{compiles.requests} compile requests so far")
    n_steps = traffic_mod.job_units(cell.traffic, seconds)
    tracer = harness.Tracer() if trace else None
    t_from = n_steps // 3
    t_to = t_from + int(cell.traffic.get("trace_steps", 20))
    facts: Dict[str, Any] = {"steps": n_steps, "global_batch": cfg["global_batch"],
                             "sequence_length": cfg["sequence_length"]}
    c0 = compiles.requests
    t0 = time.monotonic()
    m = None
    for i in range(n_steps):
        if trace and i == t_from:
            jax.block_until_ready(m)
            tracer.start()
        state, m = feed(state, *batches[(3 + i) % len(batches)])
        if trace and i + 1 == t_to:
            jax.block_until_ready(m["loss"])
            tracer.stop()
            facts["traced_steps"] = t_to - t_from
            facts["trace_host"] = (tracer.host_t0, tracer.host_t1)
    last_loss = float(jax.block_until_ready(m["loss"]))
    t1 = time.monotonic()
    facts["compiles_in_window"] = compiles.requests - c0
    peak = harness.device_record(devs)["memory_peak_bytes"]
    harness.say(f"window {t1 - t0:.3f} s: {n_steps} steps, last loss "
                f"{last_loss:.4f}, compiles in window {compiles.requests - c0}")
    del state, trainer, feed, m
    gc.collect()
    jax.clear_caches()
    t_ref = time.monotonic()
    refd = ref.train(cfg, seed, batches)
    verdict = compare(cell, prog, refd)
    ref_s = time.monotonic() - t_ref
    harness.say(f"reference followed three steps in {ref_s:.1f} s: losses "
                f"{refd['losses']}; {verdict['detail']}")
    return {"end_to_end": {"train_samples_per_s":
                           n_steps * cfg["global_batch"] / (t1 - t0)},
            "window_start": t0, "attempted": n_steps + 3,
            "failed": 0 if np.isfinite(last_loss) else 1,
            "compared": verdict["compared"], "facts": facts,
            "memory_peak_bytes": peak,
            "info": {"reference_s": ref_s, "window_s": t1 - t0,
                     **verdict["detail"]},
            "trace": tracer.reduce() if trace else None}


def control(cell, seed: int, seconds: float, devs, compiles,
            with_control: bool = True) -> Dict[str, Any]:
    """One seed's readings for the limits: the program's first three steps
    against the reference, and with ``with_control`` the reference put in
    the program's place in float8 (the control) and with each fault a
    training cell can have (half of the batch left out; the exchange between
    chips left out; a state returned unchanged reads 1 by this measure and
    needs no run; the loss altered by 5% where it is reported)."""
    import jax

    cfg, ref = cell.config, cell.reference()
    trainer, state, feed, batches, p0, names = build(cell, seed, devs)
    state, prog = first_steps(state, feed, batches, p0, names)
    del state, trainer, feed
    gc.collect()
    jax.clear_caches()
    refd = ref.train(cfg, seed, batches)
    lim = cfg["limits"]

    def read(p):
        g = ref.gaps(p, refd)
        return {k: g[k] for k in lim}
    readings = {"program": read(prog), "losses": prog["losses"],
                "worst": {k: v for k, v in ref.gaps(prog, refd).items()
                          if k.startswith("worst") or k == "leaves_left_out"}}
    if not with_control:
        return readings
    readings["control"] = {low: read(ref.train(cfg, seed, batches, quant=low))
                           for low in cfg["check"]["controls"]}
    readings["faults"] = {
        "half_batch": read(ref.train(cfg, seed, batches, variant="half_batch")),
        "no_exchange": read(ref.train(cfg, seed, batches, variant="no_exchange",
                                      chips=len(devs))),
        "loss_altered_5pct": read(dict(prog, losses=[x * 1.05
                                                     for x in prog["losses"]]))}
    return readings
