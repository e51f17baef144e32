"""Each runner driven through its Python function on the tiny files beside
this test (the look for a chip skipped, the rest of a run as it is), and
``correct`` seen to come out false: for the control of each configuration,
and for each fault its cell can have, planted under the timed path."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, run as run_mod

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "tiny", "BENCHMARK.json")) as f:
    TINY = json.load(f)
LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def drive(name, seed, seconds=2.0):
    import jax
    import synapseml_tpu  # noqa: F401
    cell = harness.Cell(TINY, name, harness.ROOT)
    out = cell.runner().run(cell=cell, seed=seed, seconds=seconds, trace=False,
                            devs=jax.devices(), compiles=harness.CompileCounter())
    out["correct"] = harness.decide(out)
    return cell, out


def line(cell, out):
    import jax
    return json.loads(json.dumps(run_mod.build_result(cell, out, False,
                                                      jax.devices())))


# -- llm_serve -------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    return drive("tiny-decoder.tiny-closed4", 2 ** 31 + 11)


def test_serving_last_line(served):
    cell, out = served
    res = line(cell, out)
    assert list(res)[:5] == LINE_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 8
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    c = res["compared"]["served_logit_gap"]
    assert c["value"] <= c["limit"]


def test_serving_window_is_all_the_work_of_the_window(served):
    _, out = served
    f = out["facts"]
    t0, t1 = f["t0"], f["t1"]
    recs = [r for r in f["records"] if not r["error"]]
    by_hand = sum(sum(t0 <= t < t1 for t in r["times"])
                  + (r["prompt_len"] if t0 <= r["times"][0] < t1 else 0)
                  for r in recs)
    assert out["end_to_end"]["tokens_per_s"] == pytest.approx(by_hand / (t1 - t0))
    # the ramp: every client had completed a request before the window opened
    first_done = {}
    for r in recs:
        first_done[r["client"]] = min(first_done.get(r["client"], 1e30),
                                      r["times"][-1])
    assert len(first_done) == 4 and max(first_done.values()) <= t0
    assert f["compiles_in_window"] == 0


def test_serving_the_check_takes_the_longest_request(served):
    cell, out = served
    prompts, tokens = out["sample"]
    f = out["facts"]
    done = [r for r in f["records"] if not r["error"]
            and f["t0"] <= r["times"][-1] < f["t1"]]
    longest = max(r["prompt_len"] + len(r["tokens"]) for r in done)
    assert len(prompts[0]) + len(tokens[0]) == longest
    assert len(prompts) == cell.config["check"]["sample_requests"]


def test_serving_control_fp8_is_not_correct(served):
    cell, out = served
    prompts, tokens = out["sample"]
    ref, cfg = cell.reference(), cell.config
    got = ref.served_gaps(cfg, 2 ** 31 + 11, prompts, tokens,
                          cfg["engine"]["max_len"], control="fp8")
    assert got["widest_gap"] > cfg["limits"]["served_logit_gap"]
    assert got["widest_gap"] >= 3 * max(
        out["compared"]["served_logit_gap"]["value"], 1e-4)


def test_serving_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from synapseml_tpu.models.llm import slots
    real = slots.SlotEngine._plain_step

    def altered(self):
        events = real(self)
        for ev in events:          # every slot: the sample may hold any
            ev.token = (ev.token + 1) % self.cfg.vocab_size
        return events
    monkeypatch.setattr(slots.SlotEngine, "_plain_step", altered)
    _, out = drive("tiny-decoder.tiny-closed4", 12)
    assert out["correct"] is False
    c = out["compared"]["served_logit_gap"]
    assert out["failed"] > 0 or c["value"] > c["limit"]


# -- gbdt_fit ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    return drive("tiny-table.tiny-fit", 2 ** 31 + 3)


def test_fitting_last_line(fitted):
    cell, out = fitted
    res = line(cell, out)
    assert list(res)[:5] == LINE_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == 50
    assert set(res["metrics"]) == {"boost_iters_per_s", "setup_s"}
    assert set(res["compared"]) == {"split_gain_gap", "leaf_value_gap",
                                    "node_count_gap", "predict_gap",
                                    "leaves_short"}
    assert out["info"]["trees_checked"][:3] == [0, 1, 2]
    assert len(out["info"]["trees_checked"]) == 4


@pytest.fixture(scope="module")
def readings():
    import jax
    import synapseml_tpu  # noqa: F401
    cell = harness.Cell(TINY, "tiny-table.tiny-fit", harness.ROOT)
    return cell, cell.runner().control(cell, 7, 1.0, jax.devices(),
                                       harness.CompileCounter())


@pytest.mark.parametrize("fault", ["state_unchanged", "half_of_the_rows",
                                   "leaf_altered_5pct", "split_moved_32_bins",
                                   "prediction_altered"])
def test_fitting_a_fault_is_not_correct(readings, fault):
    cell, r = readings
    lim = cell.config["limits"]
    assert all(r["program"][k] <= lim[k] for k in lim), r["program"]
    got = r["faults"][fault]
    assert any(got[k] > lim[k] for k in got), got


def as_result(cell, numbers, failed=0):
    """A reading in the shape ``harness.decide`` judges a run by."""
    lim = cell.config["limits"]
    return {"compared": {k: {"value": v, "limit": lim[k]}
                         for k, v in numbers.items() if k in lim},
            "failed": failed}


def test_fitting_control_fp8_is_not_correct(readings):
    """The control (the reference's leaves from float8 gradients, put in
    the program's place) goes through the run's own ``judge`` and
    ``decide``: not correct, by ``leaf_value_gap``, which reads over the
    limit the real cell is held to.  The same leaves from bfloat16
    gradients, the ingest the configuration states and the check is made
    in, are correct, and read a tenth of the control's or less."""
    cell, r = readings
    assert cell.config["check"]["reference_ingest"] == "bfloat16"
    assert harness.decide(as_result(cell, r["program"])) is True
    fp8, bf16 = r["control"]["fp8"], r["control"]["bfloat16"]
    assert set(fp8) == set(r["program"]) - {"predict_gap"}
    assert harness.decide(as_result(cell, fp8)) is False
    assert harness.decide(as_result(cell, bf16)) is True
    assert fp8["leaf_value_gap"] > cell.config["limits"]["leaf_value_gap"]
    with open(os.path.join(harness.HERE, "configs", "gbdt-higgs-shape.json")) as f:
        real = json.load(f)
    assert fp8["leaf_value_gap"] > real["limits"]["leaf_value_gap"]
    assert fp8["leaf_value_gap"] > 10 * max(bf16["leaf_value_gap"],
                                            r["program"]["leaf_value_gap"])


def test_fitting_data_and_bins_come_from_the_seed():
    cell = harness.Cell(TINY, "tiny-table.tiny-fit", harness.ROOT)
    ref = cell.reference()
    cfg = dict(cell.config, rows=3000)
    (xa, ya), (xb, yb), (xc, _) = (ref.make_data(cfg, s) for s in (5, 5, 6))
    assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
    assert not np.array_equal(xa, xc)
    assert xa.shape == (3000, 28) and xa.dtype == np.float32
    assert set(np.unique(ya)) == {0.0, 1.0}
    ub = ref.bin_bounds(cfg, xa)
    assert ub.shape == (28, 255) and np.all(np.diff(ub[:, :254], axis=1) > 0)


# -- dl_train (four of conftest's virtual CPU devices) ---------------------------

def drive_training(seed, wrap_feed=None):
    import jax
    import synapseml_tpu  # noqa: F401
    cell = harness.Cell(TINY, "tiny-encoder.tiny-ft", harness.ROOT)
    runner = cell.runner()
    real_build = runner.build
    if wrap_feed is not None:
        def build(*a, **k):
            trainer, state, feed, batches, p0, names = real_build(*a, **k)
            return trainer, state, wrap_feed(feed), batches, p0, names
        runner.build = build
    try:
        out = runner.run(cell=cell, seed=seed, seconds=1.0, trace=False,
                         devs=jax.devices()[:4],
                         compiles=harness.CompileCounter())
    finally:
        runner.build = real_build
    out["correct"] = harness.decide(out)
    return cell, out


@pytest.fixture(scope="module")
def trained():
    return drive_training(2 ** 31 + 9)


def test_training_last_line(trained):
    import jax
    cell, out = trained
    res = json.loads(json.dumps(run_mod.build_result(cell, out, False,
                                                     jax.devices()[:4])))
    assert list(res)[:5] == LINE_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert res["device"]["count"] == 4
    assert set(res["compared"]) == {"first_loss_gap", "loss_gap",
                                    "first_grad_norm_gap", "param_change_gap"}
    assert out["facts"]["compiles_in_window"] == 0
    # key biases have no gradient under softmax: left out by the rule
    assert all(n.endswith("key/bias") or n.endswith("query/bias")
               for n in out["info"]["leaves_left_out"])
    assert any(n.endswith("key/bias") for n in out["info"]["leaves_left_out"])


def _unchanged(feed):
    return lambda state, ids, labels: (state, feed(state, ids, labels)[1])


def _half(feed):
    return lambda state, ids, labels: feed(state, ids[:len(ids) // 2],
                                           labels[:len(labels) // 2])


def _loss_altered(feed):
    def f(state, ids, labels):
        state, m = feed(state, ids, labels)
        return state, dict(m, loss=m["loss"] * 1.01)
    return f


@pytest.mark.parametrize("fault", [_unchanged, _half, _loss_altered],
                         ids=["state_unchanged", "half_of_the_batch",
                              "loss_altered"])
def test_training_a_fault_under_the_timed_path_is_not_correct(fault):
    _, out = drive_training(31, fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


@pytest.fixture(scope="module")
def training_readings():
    import jax
    import synapseml_tpu  # noqa: F401
    cell = harness.Cell(TINY, "tiny-encoder.tiny-ft", harness.ROOT)
    return cell, cell.runner().control(cell, 7, 1.0, jax.devices()[:4],
                                       harness.CompileCounter())


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange",
                                   "loss_altered_5pct"])
def test_training_a_fault_in_the_references_place_is_not_correct(
        training_readings, fault):
    cell, r = training_readings
    lim = cell.config["limits"]
    assert all(r["program"][k] <= lim[k] for k in lim), r["program"]
    assert any(r["faults"][fault][k] > lim[k] for k in lim), r["faults"][fault]


def test_training_control_fp8_is_not_correct(training_readings):
    """The control (the reference's three steps with both operands of every
    product in float8, put in the program's place) goes through the run's
    own ``gaps`` and ``decide``: not correct."""
    cell, r = training_readings
    assert harness.decide(as_result(cell, r["program"])) is True
    fp8 = r["control"]["fp8"]
    assert set(fp8) == set(cell.config["limits"])
    assert harness.decide(as_result(cell, fp8)) is False


def test_training_data_and_parameters_come_from_the_seed():
    cell = harness.Cell(TINY, "tiny-encoder.tiny-ft", harness.ROOT)
    ref, cfg = cell.reference(), cell.config
    a, b, c = (ref.make_batches(cfg, s, 2) for s in (3, 3, 4))
    assert all(np.array_equal(x[0], y[0]) and np.array_equal(x[1], y[1])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0][0], c[0][0])
    ids = a[0][0]
    assert ids.shape == (16, 32) and len({tuple(r) for r in ids}) == 16
    pa, pb = ref.init_params(cfg, 2 ** 31 + 1), ref.init_params(cfg, 2 ** 31 + 1)
    assert set(pa) == set(ref.param_shapes(cfg))
    assert all(np.array_equal(pa[k], pb[k]) for k in pa)
    assert not np.array_equal(pa["pooler/kernel"],
                              ref.init_params(cfg, 1)["pooler/kernel"])
