"""Runner of kind ``gbdt_fit``: one ``fit`` of the configuration's estimator
on the configuration's table, made from the seed.

Set-up is everything a fit does before its boosting loop: the data, binning,
upload, compilation (the program warms its scanned loop on a thread while it
bins).  The window is the boosting loop on the path a plain ``fit`` takes:
it opens at the main thread's first dispatch of the scanned program (once the
operands it waits for are on the device) and closes when ``fit`` returns with
the trees on the host.  To see that first dispatch the runner wraps the
program's ``booster._make_scan`` for the length of the fit; nothing else of
the program is touched, and if the hook never fires the run fails.  The work
is fixed: ``traffic.job_units`` iterations from the window's seconds.

Then the model's predictions of a seeded sample of rows are taken through the
user's ``transform``, the program's state is dropped, and the reference
checks the first trees and a seeded sample of the later ones, its gradients
rounded as the configuration's ``check.reference_ingest`` states (the
precision in which the configuration says gradients enter a histogram).
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness


def tree_dicts(booster) -> List[Dict[str, np.ndarray]]:
    return [{k: np.asarray(getattr(t, k)) for k in t._fields}
            for t in booster.trees]


def timed_fit(cell, X, y, iterations: int, trace: bool, compiles,
              sample_rows: np.ndarray) -> Dict[str, Any]:
    """The fit, its window by the host clock, the trees and the program's
    predictions of ``sample_rows``."""
    import jax

    from synapseml_tpu import Dataset
    import synapseml_tpu.models.gbdt.booster as booster_mod

    cfg = cell.config
    params = {k: (iterations if v == "$iterations" else v)
              for k, v in cfg["estimator"]["params"].items()}
    est = harness.import_object(cfg["estimator"]["class"])(**params)
    tracer = harness.Tracer() if trace else None
    mark: Dict[str, Any] = {}
    orig = booster_mod._make_scan

    def make_scan(*a, **k):
        fn = orig(*a, **k)

        def scan(*args, **kw):
            if "t0" not in mark and \
                    threading.current_thread() is threading.main_thread():
                jax.block_until_ready(args)       # set-up's uploads end here
                if tracer is not None:
                    tracer.start()
                    mark["span"] = harness.annotate("fit.window")
                    mark["span"].__enter__()
                mark["c0"] = compiles.requests
                mark["t0"] = time.monotonic()
            return fn(*args, **kw)
        return scan

    booster_mod._make_scan = make_scan
    try:
        model = est.fit(Dataset({"features": X, "label": y}))
        t1 = time.monotonic()
    finally:
        booster_mod._make_scan = orig
        if "span" in mark:
            mark["span"].__exit__(None, None, None)
            tracer.stop()
    if "t0" not in mark:
        raise SystemExit("the fit never dispatched the scanned boosting "
                         "program on the main thread: the window's start was "
                         "not seen (too few iterations, or the program's loop "
                         "changed)")
    measures = model.training_measures
    want = cfg.get("expect_hist_path")
    if want and measures.hist_path != want:
        raise SystemExit(f"histogram path {measures.hist_path!r}, the "
                         f"configuration states {want!r}")
    if measures.iterations != iterations:
        raise SystemExit(f"the fit ran {measures.iterations} iterations, "
                         f"{iterations} were asked")
    peak = harness.device_record(jax.devices())["memory_peak_bytes"]
    scored = model.transform(Dataset({"features": X[sample_rows],
                                      "label": y[sample_rows]}))
    raw = np.asarray([np.asarray(r, np.float64)
                      for r in scored["rawPrediction"]])
    predicted = raw[:, 1] if raw.ndim == 2 else raw
    out = {"t0": mark["t0"], "t1": t1, "iterations": iterations,
           "trees": tree_dicts(model.booster), "predicted": predicted,
           "compiles_in_window": compiles.requests - mark["c0"],
           "memory_peak_bytes": peak, "tracer": tracer,
           "program_measures": measures.as_dict()}
    del model, scored, est
    return out


def judge(cell, ref, X, y, fit: Dict[str, Any], seed: int,
          sample_rows: np.ndarray, which=None, walk: bool = True
          ) -> Dict[str, Any]:
    """The numbers compared, each with its limit.  ``which`` and ``walk``
    narrow the check to some trees, or leave the predictions out, for the
    readings of planted faults."""
    cfg, chk = cell.config, cell.config["check"]
    n_trees = len(fit["trees"])
    if which is None:
        first = list(range(min(chk["first_trees"], n_trees)))
        later = [t for t in range(n_trees) if t not in first]
        pick = np.random.default_rng([int(seed), 5]).permutation(len(later))
        which = first + sorted(later[i] for i in pick[:chk["sampled_trees"]])
    t_a = time.monotonic()
    got = ref.check_fit(cfg, X, y, fit["trees"], which,
                        chk.get("reference_ingest"))
    t_b = time.monotonic()
    values = {"split_gain_gap": got["split_gain_gap"],
              "leaf_value_gap": got["leaf_value_gap"],
              "node_count_gap": got["node_count_gap"],
              "leaves_short": float(cfg["num_leaves"] - got["min_leaves"])}
    if walk:
        base = ref.initial_margin(y)
        walked = ref.walk_margin(X[sample_rows], base, fit["trees"])
        rms = float(np.sqrt(np.mean((walked - base) ** 2)))
        values["predict_gap"] = float(np.abs(fit["predicted"] - walked).max()
                                      / max(rms, 1e-30))
    lim = cfg["limits"]
    return {"compared": {k: {"value": float(v), "limit": lim[k]}
                         for k, v in values.items()},
            "trees_checked": which,
            "timings": {"check_fit_s": round(t_b - t_a, 1),
                        "walk_s": round(time.monotonic() - t_b, 1)}}


def run(cell, seed: int, seconds: float, trace: bool, devs, compiles
        ) -> Dict[str, Any]:
    import jax

    from benchmark import traffic as traffic_mod

    cfg = cell.config
    ref = cell.reference()
    X, y = ref.make_data(cfg, seed)
    harness.say(f"data made: {X.shape}")
    iterations = traffic_mod.job_units(cell.traffic, seconds)
    sample_rows = np.sort(np.random.default_rng([int(seed), 4]).choice(
        len(X), min(cfg["check"]["predict_rows"], len(X)), replace=False))
    fit = timed_fit(cell, X, y, iterations, trace, compiles, sample_rows)
    gc.collect()
    jax.clear_caches()
    t0, t1 = fit["t0"], fit["t1"]
    harness.say(f"window {t1 - t0:.3f} s: {iterations} iterations, compiles in "
                f"window {fit['compiles_in_window']}, program's own measures "
                f"{fit['program_measures']}")
    t_ref = time.monotonic()
    verdict = judge(cell, ref, X, y, fit, seed, sample_rows)
    ref_s = time.monotonic() - t_ref
    harness.say(f"reference checked trees {verdict['trees_checked']} in "
                f"{ref_s:.1f} s ({verdict['timings']})")
    facts = {"iterations": iterations,
             "compiles_in_window": fit["compiles_in_window"]}
    return {"end_to_end": {"boost_iters_per_s": iterations / (t1 - t0)},
            "window_start": t0, "attempted": iterations, "failed": 0,
            "compared": verdict["compared"], "facts": facts,
            "memory_peak_bytes": fit["memory_peak_bytes"],
            "info": {"trees_checked": verdict["trees_checked"],
                     "reference_s": ref_s, "window_s": t1 - t0},
            "trace": fit["tracer"].reduce() if trace else None}


def control_numbers(cfg, ref, X, y, fit: Dict[str, Any], numbers,
                    low: str) -> Dict[str, float]:
    """The control's numbers: the leaves the reference gives the first three
    trees with its gradients rounded to ``low``, put in the program's place,
    one tree at a time (so the margins before it stay the program's), and
    judged as a run is.  The widest of each number over the three."""
    import copy
    first = [0, 1, 2]
    lowp = ref.check_fit(cfg, X, y, fit["trees"], first, low)["per_tree"]
    worst: Dict[str, float] = {}
    for t in first:
        planted = dict(fit, trees=copy.deepcopy(fit["trees"]))
        tree = planted["trees"][t]
        n = int(tree["num_nodes"])
        leaves = np.flatnonzero(tree["left_child"][:n] < 0)
        tree["leaf_value"][leaves] = lowp[t]["leaf_ref"]
        for k, v in numbers(planted, which=[t], walk=False).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def control(cell, seed: int, seconds: float, devs, compiles,
            with_control: bool = True) -> Dict[str, Any]:
    """One seed's readings for the limits: the program's numbers and, with
    ``with_control``, the control's (the reference with rounded gradients
    in the program's place, :func:`control_numbers`) and each fault the cell can have,
    planted in what the fit returned: the state left unchanged (every tree a
    copy of the first, as when the margins never move), half of the rows
    left out (a fit of the first half, checked against the whole table), an
    answer altered where it is produced (one leaf value by 5%; one split
    moved by 32 bins; one prediction by 0.01).  A fault is read on the tree
    it was planted in."""
    import copy

    from benchmark import traffic as traffic_mod

    cfg = cell.config
    ref = cell.reference()
    X, y = ref.make_data(cfg, seed)
    iterations = traffic_mod.job_units(cell.traffic, seconds)
    rows = np.sort(np.random.default_rng([int(seed), 4]).choice(
        len(X), min(cfg["check"]["predict_rows"], len(X)), replace=False))
    fit = timed_fit(cell, X, y, iterations, False, compiles, rows)
    fit.pop("tracer")

    def numbers(f, **kw):
        j = judge(cell, ref, X, y, f, seed, rows, **kw)
        harness.say(f"judged {j['trees_checked']} {j['timings']}")
        return {k: v["value"] for k, v in j["compared"].items()}

    readings = {"program": numbers(fit),
                "rate": iterations / (fit["t1"] - fit["t0"])}
    if not with_control:
        return readings
    readings["control"] = {low: control_numbers(cfg, ref, X, y, fit, numbers,
                                                low)
                           for low in cfg["check"]["controls"]}
    faults = readings["faults"] = {}
    same = dict(fit, trees=[fit["trees"][0]] * len(fit["trees"]))
    faults["state_unchanged"] = numbers(same, which=[1], walk=False)
    leaf = copy.deepcopy(fit)
    t = leaf["trees"][1]
    j = int(np.flatnonzero(t["left_child"][:int(t["num_nodes"])] < 0)[0])
    t["leaf_value"][j] *= 1.05
    faults["leaf_altered_5pct"] = numbers(leaf, which=[1], walk=False)
    split = copy.deepcopy(fit)
    t = split["trees"][2]
    b = int(t["split_bin"][0])
    t["split_bin"][0] = b + 32 if b < 128 else b - 32
    faults["split_moved_32_bins"] = numbers(split, which=[2], walk=False)
    pred = dict(fit, predicted=fit["predicted"].copy())
    pred["predicted"][0] += 0.01
    faults["prediction_altered"] = numbers(pred, which=[0])
    half = len(X) // 2
    hfit = timed_fit(cell, X[:half], y[:half], iterations, False, compiles,
                     rows[rows < half])
    hfit.pop("tracer")
    faults["half_of_the_rows"] = numbers(hfit, which=[0], walk=False)
    return readings
