"""Runner of kind ``llm_serve``: a model served by ``LLMServer`` over HTTP
under a closed loop of streaming clients.

Set-up: the weights are made on the device from the seed by the
configuration's reference (bfloat16, one compiled program for every layer)
and handed to the program in its own parameter tree; ``LLMServer`` warms its
programs; the load generator's child runs its ramp.  The window: what the
child stamps.  Then the server is closed, its state freed, and the reference
runs once over a seeded sample of the requests the window finished, the
longest among them: ``correct`` compares how far a served token's logit lies
below the reference's best.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.parse
from typing import Any, Dict, List

import numpy as np

from benchmark import harness

HERE = os.path.dirname(os.path.abspath(__file__))


def _set_path(tree: Dict[str, Any], path: str, value: Any) -> None:
    parts = path.split("/")
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = value


def build_model(config: Dict[str, Any]):
    spec, eng = config["model"], config["engine"]
    lookup = {**config, **{f"engine.{k}": v for k, v in eng.items()}}
    args = {arg: lookup[key] for arg, key in spec["config_args"].items()}
    model_cfg = harness.import_object(spec["config_class"])(**args)
    return harness.import_object(spec["class"])(model_cfg)


def build_variables(config: Dict[str, Any], ref, seed: int) -> Dict[str, Any]:
    """The reference's weights in the program's parameter tree."""
    names = config["model"]["params"]
    params: Dict[str, Any] = {}
    for key, arr in ref.outer_weights(config, seed).items():
        _set_path(params, names["outer"][key], arr)
    for i in range(config["num_hidden_layers"]):
        prefix = names["layer_prefix"].format(i=i)
        for key, arr in ref.layer_weights(config, seed, i).items():
            _set_path(params, prefix + "/" + names["layer"][key], arr)
    return {"params": params}


class Load:
    """The child process and the thread that reads its lines."""

    def __init__(self, args: Dict[str, Any]):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(os.path.dirname(HERE), "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, text=True)
        self.events: Dict[str, Dict[str, Any]] = {}
        self.cv = threading.Condition()
        self.proc.stdin.write(json.dumps(args) + "\n")
        self.proc.stdin.close()
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            msg = json.loads(line)
            with self.cv:
                self.events[msg["event"]] = msg
                self.cv.notify_all()
        with self.cv:
            self.events.setdefault("eof", {})
            self.cv.notify_all()

    def wait(self, event: str, timeout: float) -> Dict[str, Any]:
        end = time.monotonic() + timeout
        with self.cv:
            while event not in self.events:
                if "error" in self.events or "eof" in self.events:
                    raise RuntimeError(f"load generator ended before {event!r}: "
                                       f"{self.events.get('error')}")
                left = end - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"no {event!r} from the load generator")
                self.cv.wait(left)
            return self.events[event]

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.reader.join(10)


def window_metrics(records: List[Dict[str, Any]], t0: float, t1: float
                   ) -> Dict[str, Any]:
    """All the work of the window over its seconds, and the tails over all
    its requests.  A request's prompt tokens count when its first token
    arrives, each output token at its own arrival."""
    tokens = 0
    tpot, ttft, done = [], [], []
    for r in records:
        if r["error"] or not r["times"]:
            continue
        times = np.asarray(r["times"])
        if t0 <= times[0] < t1:
            tokens += r["prompt_len"]
        tokens += int(((times >= t0) & (times < t1)).sum())
        complete = len(r["tokens"]) == r["max_new_tokens"]
        if complete and t0 <= times[-1] < t1:
            done.append(r)
            if len(times) > 1:
                tpot.append((times[-1] - times[0]) / (len(times) - 1))
        if t0 <= r["t_send"] < t1:
            ttft.append(times[0] - r["t_send"])
    return {"tokens": tokens, "tokens_per_s": tokens / (t1 - t0),
            "tpot_s": tpot, "ttft_s": ttft, "completed": done}


def sample_for_check(done: List[Dict[str, Any]], seed: int, n: int
                     ) -> List[Dict[str, Any]]:
    """``n`` of the window's finished requests drawn from the seed, the
    longest (prompt plus output) always among them."""
    if not done:
        return []
    done = sorted(done, key=lambda r: r["index"])
    longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["index"]))
    rest = [r for r in done if r is not longest]
    pick = np.random.default_rng([int(seed), 3]).permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def run(cell, seed: int, seconds: float, trace: bool, devs, compiles
        ) -> Dict[str, Any]:
    import jax

    from benchmark import traffic as traffic_mod
    from synapseml_tpu.serving import LLMServer

    config, traffic = cell.config, cell.traffic
    eng = config["engine"]
    ref = cell.reference()
    model = build_model(config)
    variables = build_variables(config, ref, seed)
    jax.block_until_ready(variables)
    harness.say("weights on the device")
    server = LLMServer(model, variables, n_slots=eng["n_slots"],
                       max_len=eng["max_len"], warmup=eng["warmup"],
                       attention_backend=eng["attention_backend"],
                       reply_timeout_s=eng["reply_timeout_s"],
                       max_new_tokens_default=32)
    load = None
    facts: Dict[str, Any] = {"steps": [], "admits": 0}
    try:
        engine = server.engine
        if engine.attention_backend != eng["expect_attention_backend"]:
            raise SystemExit(
                f"attention backend resolved {engine.attention_backend!r}, the "
                f"configuration states {eng['expect_attention_backend']!r}")
        plane = engine.compile_plane
        if plane is not None and plane.status != "warm":
            raise SystemExit(f"compile plane is {plane.status!r}")
        harness.say(f"server warm at {server.url} "
                    f"({compiles.requests} compile requests so far)")
        tracer = harness.Tracer() if trace else None
        if trace:
            # the calls into the scheduler, named on the profiler's clock;
            # per step: slots in use and their live spans (what the paged
            # kernel has to read), kept only while the trace is on
            def sample():
                if tracer.on:
                    act = engine.active
                    facts["steps"].append(
                        (int(act.sum()), int(engine.lengths[act].sum())))

            def count_admit():
                if tracer.on:
                    facts["admits"] += 1
            harness.wrap_annotated(engine, "step", "engine.step", sample)
            harness.wrap_annotated(engine, "admit", "engine.admit", count_admit)
        url = urllib.parse.urlsplit(server.url)
        load = Load({"host": url.hostname, "port": url.port, "path": url.path,
                     "traffic": cell.traffic_path, "seed": seed,
                     "vocab": config["vocab_size"], "seconds": seconds,
                     "timeout_s": eng["reply_timeout_s"]})
        load.wait("ramp_start", 60)
        t0 = load.wait("window_start", 300)["t"]
        c0 = compiles.requests
        if trace:
            lead = min(float(traffic.get("trace_lead_s", 1.0)), seconds / 4)
            span = min(float(traffic.get("trace_seconds", 3.0)), seconds / 2)
            time.sleep(max(0.0, t0 + lead - time.monotonic()))
            tracer.start()
            time.sleep(span)
            tracer.stop()
        t1 = load.wait("window_end", seconds + 120)["t"]
        c1 = compiles.requests
        result = load.wait("result", 180)
        peak = harness.device_record(devs)["memory_peak_bytes"]
        facts.update(
            occupancy_now=engine.active_count, n_slots=engine.n_slots,
            steps_run=engine.steps_run,
            prefix_tokens_reused=engine.prefix_tokens_reused,
            compiles_in_window=c1 - c0)
    finally:
        if load is not None:
            load.close()
        server.close()
    # free the program's state before the reference touches the device: the
    # buffers themselves, since threads and registries of the program may
    # still refer to the engine
    for leaf in jax.tree.leaves((engine.cache, variables)):
        leaf.delete()
    del server, engine, variables, model
    gc.collect()
    jax.clear_caches()

    records = result["records"]
    wm = window_metrics(records, t0, t1)
    failed = sum(1 for r in records if r["error"]
                 or len(r["tokens"]) != r["max_new_tokens"]) \
        + int(result["never_answered"])
    harness.say(f"window {t1 - t0:.3f} s: {len(records)} requests sent, "
                f"{len(wm['completed'])} completed in it, {wm['tokens']} tokens, "
                f"client_think_p90_ms {result['client_think_p90_ms']:.3f}, "
                f"compiles in window {c1 - c0}")
    end_to_end = {"tokens_per_s": wm["tokens_per_s"]}
    if wm["tpot_s"]:
        end_to_end["tpot_p95_ms"] = 1e3 * harness.percentile(wm["tpot_s"], 95)
    facts.update(
        tpot_s=wm["tpot_s"], ttft_s=wm["ttft_s"],
        client_think_p90_ms=result["client_think_p90_ms"],
        records=records, t0=t0, t1=t1,
        trace_host=(tracer.host_t0, tracer.host_t1) if trace else None)

    sample = sample_for_check(wm["completed"], seed,
                              int(config["check"]["sample_requests"]))
    order = traffic_mod.request_order(traffic, seed)
    prompts = [traffic_mod.request(traffic, seed, r["index"],
                                   config["vocab_size"], order)["ids"]
               for r in sample]
    t_ref = time.monotonic()
    if sample:
        gaps = ref.served_gaps(config, seed, prompts,
                               [r["tokens"] for r in sample], eng["max_len"])
    else:
        gaps = {"widest_gap": float("nan"), "tokens": 0, "mismatches": 0}
    harness.say(f"reference over {len(sample)} requests, {gaps['tokens']} "
                f"served tokens, in {time.monotonic() - t_ref:.1f} s: {gaps}")
    compared = {"served_logit_gap": {"value": gaps["widest_gap"],
                                     "limit": config["limits"]["served_logit_gap"]}}
    out = {"end_to_end": end_to_end, "window_start": t0,
           "attempted": len(records) + int(result["never_answered"]),
           "failed": failed, "compared": compared, "facts": facts,
           "memory_peak_bytes": peak,
           "sound": len(sample) > 0,
           "info": {"requests_completed_in_window": len(wm["completed"]),
                    "tokens_compared": gaps["tokens"],
                    "client_think_p90_ms": result["client_think_p90_ms"],
                    "reference_s": time.monotonic() - t_ref}}
    out["trace"] = tracer.reduce() if trace else None
    out["sample"] = (prompts, [r["tokens"] for r in sample])
    return out


def control(cell, seed: int, seconds: float, devs, compiles,
            with_control: bool = True) -> Dict[str, Any]:
    """One seed's readings for the limit: the program's widest gap (a short
    window at the cell's own load), and on the same prompts and served
    tokens the gap of the token each lower precision puts first."""
    out = run(cell, seed, seconds, False, devs, compiles)
    prompts, served = out["sample"]
    ref, cfg = cell.reference(), cell.config
    readings = {"program": {"served_logit_gap":
                            out["compared"]["served_logit_gap"]["value"]},
                "tokens": out["info"]["tokens_compared"],
                "failed": out["failed"], "control": {}}
    for low in cfg["check"]["controls"] if with_control else []:
        g = ref.served_gaps(cfg, seed, prompts, served, cfg["engine"]["max_len"],
                            control=low)
        readings["control"][low] = {"served_logit_gap": g["widest_gap"],
                                    "mismatches": g["mismatches"]}
    return readings
