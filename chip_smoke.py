#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no child that touches JAX.  It drives the three hot paths once
through the entry points a user calls, at the full width of models the repo
supports (random weights from a seed), and checks what comes out by the
repo's own means:

1. **serving** — a direct ``paged_decode_attention`` parity check against the
   dense masked-softmax equations in float32, then ``LLMServer`` over
   Llama-3-1B geometry (``warmup="sync"``, default ``attention_backend``)
   answering eight real HTTP requests with zero in-loop compiles;
2. **dl** — ``DLTrainer`` on BERT-base, five ``train_step()`` calls over a
   ``data=len(jax.devices())`` mesh;
3. **gbdt** — ``GBDTClassifier.fit`` on 1M x 28 seeded rows at 255 bins, on
   the Pallas histogram path, holdout AUC above a floor.

It exits non-zero, before doing any work, unless ``jax.default_backend()``
is ``tpu``; any failed check, exception, non-200 reply or ``failed`` compile
plane is a non-zero exit.  A run that passed ends with two lines on standard
output: ``[chip_smoke] report {...}`` (identity, wall seconds, compile
requests and persistent-cache hits/stores per phase, the resolved attention
backend and histogram path), then — the last line, and nothing else in it —
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reports it.  It is not a benchmark: it reports no rate.

``--rehearse-cpu`` runs the same code at toy sizes on whatever backend JAX
has (Pallas through the interpreter), to debug the script itself without a
chip.  A rehearsal prints its report line and never the ``"ok"`` line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


def check(ok: bool, what: str) -> None:
    """A failed check ends the run: non-zero exit, no result line."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal."""
    # serving
    llama: Callable[[], Any]              # -> LlamaConfig
    n_slots: int
    prompt_lens: Tuple[int, ...]          # eight requests
    shared_prefix: int                    # request 2 repeats this much of 1
    max_new: Tuple[int, int]              # inclusive range of max_new_tokens
    #: (B, max_len, H, KV, D) geometries for the direct kernel check
    kernel_geometries: Tuple[Tuple[int, int, int, int, int], ...]
    kernel_interpret: bool
    attention_backend: str                # what 'auto' must resolve to
    # dl trainer
    bert: Callable[[], Any]               # -> TransformerConfig
    bert_batch_per_chip: int
    bert_seq: int
    # gbdt
    gbdt_rows: int
    gbdt_iters: int
    gbdt_auc_floor: float
    hist_path: str                        # what the histogram gate must pick


def full_sizes() -> Sizes:
    from synapseml_tpu.models.dl.transformer import TransformerConfig
    from synapseml_tpu.models.llm import LlamaConfig
    return Sizes(
        llama=lambda: LlamaConfig.llama3_1b(max_len=1024),
        n_slots=16,
        # prefill buckets 32..1024, spans of one tile to most of the cache
        prompt_lens=(150, 160, 20, 45, 90, 260, 400, 700),
        shared_prefix=100, max_new=(16, 32),
        # Llama-3-1B at the served cache shape, and the 8B head geometry
        kernel_geometries=((16, 1024, 32, 8, 64), (4, 1024, 32, 8, 128)),
        kernel_interpret=False, attention_backend="paged",
        bert=lambda: TransformerConfig.bert_base(num_classes=2, max_len=128),
        bert_batch_per_chip=128, bert_seq=128,
        # 0.954 is XLA:CPU's holdout AUC for the same 20 iterations at
        # full-resolution splits (PR 22); the floor leaves room for the
        # two-level histograms and bf16 ingest the chip path turns on
        gbdt_rows=1_000_000, gbdt_iters=20, gbdt_auc_floor=0.93,
        hist_path="pallas")


def rehearsal_sizes() -> Sizes:
    from synapseml_tpu.models.dl.transformer import TransformerConfig
    from synapseml_tpu.models.llm import LlamaConfig
    return Sizes(
        llama=lambda: LlamaConfig.tiny(num_layers=2, max_len=128),
        n_slots=4, prompt_lens=(40, 44, 5, 9, 14, 33, 70, 90),
        shared_prefix=24, max_new=(3, 6),
        kernel_geometries=((4, 64, 8, 4, 16),), kernel_interpret=True,
        attention_backend="dense",
        bert=lambda: TransformerConfig.tiny(num_classes=2),
        bert_batch_per_chip=4, bert_seq=32,
        gbdt_rows=20_000, gbdt_iters=5, gbdt_auc_floor=0.85,
        hist_path="xla_scatter")


class Phase:
    """Wall seconds, compile requests and persistent-cache traffic of one
    phase (``compiles`` counts requests, cache hits included;
    ``cache_misses`` counts programs compiled and then stored)."""

    def __init__(self, name: str, report: Dict[str, Any]):
        self.name, self.report = name, report

    def __enter__(self):
        from synapseml_tpu.parallel.compilecache import cache_stats
        say(f"phase {self.name}: start")
        self.t0, self.s0 = time.monotonic(), cache_stats()
        self.extra: Dict[str, Any] = {}
        return self

    def __exit__(self, exc_type, exc, tb):
        from synapseml_tpu.parallel.compilecache import cache_stats
        if exc_type is None:
            s1 = cache_stats()
            self.report[self.name] = {
                "wall_s": round(time.monotonic() - self.t0, 2),
                **{k: s1[k] - self.s0[k] for k in s1}, **self.extra}
            say(f"phase {self.name}: ok {json.dumps(self.report[self.name])}")
        return False


# -- serving ---------------------------------------------------------------

def dense_reference(q, k, v, spans):
    """The dense path's masked-softmax equations
    (``models/llm/model.py`` ``CausalAttention``) in float32 numpy.
    q (B, S, H, D); k, v (B, L, KV, D); query j of slot b sits at
    position ``spans[b]-S+j`` and attends keys ``<=`` itself."""
    B, S, H, D = q.shape
    L, KV = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, S, KV, g, D)
    logits = np.einsum("bskgd,btkd->bkgst", qg, k,
                       optimize=True) / np.sqrt(np.float32(D))
    qpos = (spans[:, None] - S + np.arange(S)[None, :])          # (B, S)
    mask = np.arange(L)[None, None, :] <= qpos[:, :, None]       # (B, S, L)
    logits = np.where(mask[:, None, None], logits,
                      np.finfo(np.float32).min)
    logits = logits - logits.max(-1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bkgst,btkd->bskgd", p, v,
                     optimize=True).reshape(B, S, H, D)


def kernel_parity(sz: Sizes) -> None:
    """``paged_decode_attention`` against :func:`dense_reference`.

    Every position at or past a slot's live span holds POISON — a key
    aligned with the queries (it would take nearly all the probability)
    and a value of 100 — because that is what the engine's junk-write
    invariant allows there; one masked key let through moves the output
    by tens.  Stated tolerance: ``|kernel - ref| <= 0.03 + 0.03*|ref|``,
    about four bf16 ulp at |ref| ~ 1 — the kernel rounds its output (and
    the MXU its probabilities) to bf16."""
    import jax.numpy as jnp

    from synapseml_tpu.models.llm.pallas_attn import (
        paged_decode_attention, paged_geometry)

    for (B, L, H, KV, D) in sz.kernel_geometries:
        ragged = np.array([1, 2, 3, 17, 100, L // 4 - 1, L // 4, L // 4 + 1,
                           L // 2 - 1, L // 2, L // 2 + 1, 700 * L // 1024,
                           3 * L // 4, L - 24, L - 1, L])
        rng = np.random.default_rng(B * 1000 + D)
        for S in (1, 4):
            geo = paged_geometry(L, H, KV, D, jnp.bfloat16, max_query_span=S)
            check(geo is not None, f"no paged geometry for L={L} D={D} S={S}")
            # spans to the end of the cache, plus (S == 1) batches whose
            # longest span ends at each smaller power-of-two tile count
            longest = [geo.total_tiles]
            if S == 1:
                nt = geo.total_tiles // 2
                while nt >= 1:
                    longest.append(nt)
                    nt //= 2
            for nt in longest:
                spans = np.clip(np.resize(ragged, B), S, nt * geo.tile)
                spans[0], spans[-1] = S, nt * geo.tile     # both extremes
                q = rng.normal(size=(B, S, H, D)).astype(np.float32)
                k = rng.normal(size=(B, L, KV, D)).astype(np.float32)
                v = rng.normal(size=(B, L, KV, D)).astype(np.float32)
                dead = np.arange(L)[None, :] >= spans[:, None]    # (B, L)
                qbar = q.reshape(B, S, KV, H // KV, D).mean((1, 3))
                k = np.where(dead[:, :, None, None],
                             8.0 * qbar[:, None], k)
                v = np.where(dead[:, :, None, None], 100.0, v)
                qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))
                out = paged_decode_attention(
                    qb[:, 0] if S == 1 else qb, kb, vb,
                    jnp.asarray(spans, jnp.int32), tile=geo.tile,
                    interpret=sz.kernel_interpret)
                out = np.asarray(out.astype(jnp.float32)).reshape(B, S, H, D)
                ref = dense_reference(*(np.asarray(a.astype(jnp.float32))
                                        for a in (qb, kb, vb)), spans)
                check(np.isfinite(out).all(), "kernel output not finite")
                err = np.abs(out - ref) - 0.03 * np.abs(ref)
                check(float(err.max()) <= 0.03,
                      f"paged kernel vs dense reference: B={B} L={L} H={H} "
                      f"KV={KV} D={D} S={S} tiles={nt}: excess error "
                      f"{float(err.max()):.4f} at slot "
                      f"{int(np.unravel_index(err.argmax(), err.shape)[0])} "
                      f"(spans {spans.tolist()})")
                say(f"kernel parity ok: B={B} L={L} H={H} KV={KV} D={D} "
                    f"S={S} tile={geo.tile} tiles={nt} max|err|="
                    f"{float(np.abs(out - ref).max()):.4f}")


def _counter_total(name: str) -> float:
    from synapseml_tpu.telemetry import get_registry
    c = get_registry().get(name)
    return 0.0 if c is None else float(sum(c.series().values()))


def _post(url: str, body: Dict[str, Any]) -> Tuple[int, bytes]:
    req = urllib.request.Request(url, method="POST",
                                 data=json.dumps(body).encode())
    with urllib.request.urlopen(req, timeout=300) as r:    # raises on non-2xx
        return r.status, r.read()


def serving_phase(sz: Sizes, ph: Phase) -> None:
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.models.llm import (LlamaModel, cast_params,
                                          engine_jit_cache_size,
                                          program_lattice)
    from synapseml_tpu.parallel.compilecache import cache_stats
    from synapseml_tpu.serving import LLMServer

    kernel_parity(sz)

    cfg = sz.llama()
    model = LlamaModel(cfg)
    variables = cast_params(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    server = LLMServer(model, variables, n_slots=sz.n_slots, warmup="sync")
    try:
        engine = server.engine
        plane = engine.compile_plane
        snap = plane.snapshot()
        say(f"compile plane: {json.dumps(snap)}")
        check(engine.attention_backend == sz.attention_backend,
              f"attention backend resolved {engine.attention_backend!r}, "
              f"expected {sz.attention_backend!r}")
        check(plane.status == "warm" and "error" not in snap,
              f"compile plane is {plane.status!r}: {snap}")
        check(snap["programs_warm"] == snap["programs_total"] > 0,
              f"lattice not fully warm: {snap}")

        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                   for n in sz.prompt_lens]
        prompts[1][:sz.shared_prefix] = prompts[0][:sz.shared_prefix]
        max_new = [int(m) for m in np.linspace(sz.max_new[0], sz.max_new[1],
                                               len(prompts))]
        buckets = {engine._bucket(len(p)) for p in prompts}
        check(len(buckets) >= 3, f"prompts cover prefill buckets {buckets}")

        stalls0 = _counter_total("llm_compile_stalls_total")
        jit0, compiles0 = engine_jit_cache_size(), cache_stats()["compiles"]
        replies: Dict[int, List[int]] = {}

        def plain(i: int) -> None:
            status, body = _post(server.url, {"ids": prompts[i],
                                              "max_new_tokens": max_new[i]})
            check(status == 200, f"request {i}: HTTP {status}")
            replies[i] = json.loads(body)["ids"]

        def streamed(i: int) -> None:
            status, body = _post(server.url, {
                "ids": prompts[i], "max_new_tokens": max_new[i],
                "stream": True})
            check(status == 200, f"stream request {i}: HTTP {status}")
            lines = [json.loads(ln) for ln in body.splitlines() if ln.strip()]
            check(all("error" not in ln for ln in lines),
                  f"stream request {i} carried an error line: {lines[-1]}")
            check(lines[-1].get("done") is True, f"stream {i} never finished")
            toks = [ln["token"] for ln in lines[:-1]]
            check(toks == lines[-1]["ids"], f"stream {i}: tokens != final ids")
            replies[i] = toks

        # request 0 alone, so that its prefix is indexed when request 1
        # (same first tokens) is admitted; then the other seven at once —
        # slots at different spans decode side by side — one of them streamed
        plain(0)
        with ThreadPoolExecutor(len(prompts) - 1) as pool:
            futures = [pool.submit(streamed if i == 4 else plain, i)
                       for i in range(1, len(prompts))]
            for f in futures:
                f.result(600)       # re-raises what the request raised

        for i, ids in sorted(replies.items()):
            check(len(ids) == max_new[i],
                  f"request {i}: {len(ids)} ids for max_new_tokens="
                  f"{max_new[i]}")
            check(all(isinstance(t, int) and 0 <= t < cfg.vocab_size
                      for t in ids), f"request {i}: id outside the vocabulary")
        check(len(replies) == len(prompts), "a request got no reply")
        check(engine.prefix_hits >= 1
              and engine.prefix_tokens_reused >= min(64, sz.shared_prefix),
              f"shared prefix was not reused: hits={engine.prefix_hits} "
              f"tokens={engine.prefix_tokens_reused}")
        check(_counter_total("llm_compile_stalls_total") == stalls0,
              "llm_compile_stalls_total moved while serving")
        check(engine_jit_cache_size() == jit0
              and cache_stats()["compiles"] == compiles0,
              f"a program compiled while serving: jit cache {jit0} -> "
              f"{engine_jit_cache_size()}, compile requests {compiles0} -> "
              f"{cache_stats()['compiles']}")
        check(_counter_total("serving_errors_total") == 0,
              "serving_errors_total is not zero")
        for path in ("/metrics", "/readyz"):
            with urllib.request.urlopen(server.server.url_for(path),
                                        timeout=30) as r:
                body = r.read()
                check(r.status == 200 and body, f"GET {path}: {r.status}")
            if path == "/readyz":
                ready = json.loads(body)
                check(ready["warmup"]["state"] == "warm", f"/readyz: {ready}")
        ph.extra.update(attention_backend=engine.attention_backend,
                        programs_warm=snap["programs_warm"],
                        warmup_s=snap.get("warmup_seconds"),
                        decode_programs=[s.key for s in program_lattice(engine)
                                         if s.kind == "decode"],
                        steps=engine.steps_run,
                        prefix_tokens_reused=engine.prefix_tokens_reused)
    finally:
        server.close()


# -- dl trainer ------------------------------------------------------------

def dl_phase(sz: Sizes, ph: Phase) -> None:
    import jax

    from synapseml_tpu.models.dl.precision import resolve_precision
    from synapseml_tpu.models.dl.training import DLTrainer, OptimizerConfig
    from synapseml_tpu.models.dl.transformer import TextEncoder
    from synapseml_tpu.parallel.compilecache import cache_stats
    from synapseml_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    mesh = make_mesh({"data": len(devs)}, devs)
    cfg = sz.bert()
    trainer = DLTrainer(TextEncoder(cfg), OptimizerConfig(learning_rate=2e-5),
                        mesh, precision=resolve_precision("bf16"))
    rng = np.random.default_rng(0)
    bs = sz.bert_batch_per_chip * len(devs)
    ids = rng.integers(0, cfg.vocab_size, (bs, sz.bert_seq))
    mask = np.ones((bs, sz.bert_seq), bool)
    labels = rng.integers(0, 2, bs)
    state = trainer.init_state(0, ids, mask)
    step = trainer.train_step()
    bi, bm, bl = trainer.shard_batch((ids, mask, labels))
    key = jax.random.PRNGKey(0)

    check(len({s.device for s in bi.addressable_shards}) == len(devs),
          "the batch does not have one addressable shard per device")
    for leaf in jax.tree.leaves(state.params):
        check(len(leaf.sharding.device_set) == len(devs),
              "a parameter does not live on every device")

    losses = []
    for i in range(5):
        # the step donates its input state on the chip: thread it through
        state, m = step(state, (bi, bm), bl, key)
        losses.append(float(np.asarray(m["loss"])))
        check(np.isfinite(losses[-1]), f"step {i + 1}: loss {losses[-1]}")
        if i == 0:
            after_first = cache_stats()["compiles"]
    check(0.1 < losses[0] < 3.0,
          f"first loss {losses[0]:.4f} is not a 2-class cross-entropy")
    check(cache_stats()["compiles"] == after_first,
          f"a program compiled after the first step: {after_first} -> "
          f"{cache_stats()['compiles']} compile requests")
    for leaf in jax.tree.leaves(state.params):
        check(len(leaf.sharding.device_set) == len(devs),
              "a trained parameter does not live on every device")
    ph.extra.update(mesh={"data": len(devs)}, batch=bs,
                    losses=[round(x, 4) for x in losses])


# -- gbdt ------------------------------------------------------------------

def _gbdt_labels(rng, X):
    """One label concept for train AND holdout."""
    return (X[:, 0] * 2 - X[:, 1] + X[:, 2] * X[:, 3]
            + rng.normal(scale=0.5, size=len(X)) > 0).astype(np.float64)


def gbdt_phase(sz: Sizes, ph: Phase) -> None:
    import jax

    from synapseml_tpu import Dataset
    from synapseml_tpu.models.gbdt import (BoostingConfig, GBDTClassifier,
                                           train)
    from synapseml_tpu.models.gbdt.metrics import auc
    from synapseml_tpu.parallel import data_parallel_mesh

    n_dev = len(jax.devices())
    rng = np.random.default_rng(0)
    X = rng.normal(size=(sz.gbdt_rows, 28)).astype(np.float32)
    y = _gbdt_labels(rng, X)
    # numShards=0: every local device, through data_parallel_mesh
    model = GBDTClassifier(numIterations=sz.gbdt_iters, numLeaves=31,
                           maxBin=255).fit(Dataset({"features": X,
                                                    "label": y}))
    measures = model.training_measures
    check(measures.iterations == sz.gbdt_iters,
          f"trained {measures.iterations} iterations")
    check(measures.hist_path == sz.hist_path,
          f"histogram path {measures.hist_path!r}, expected "
          f"{sz.hist_path!r}")
    rng_h = np.random.default_rng(7)
    Xh = rng_h.normal(size=(100_000, 28)).astype(np.float32)
    yh = _gbdt_labels(rng_h, Xh)
    margin = model.booster.predict_margin(Xh)
    check(np.isfinite(margin).all(), "non-finite margins")
    auc_h = float(auc(yh, margin))
    check(auc_h > sz.gbdt_auc_floor,
          f"holdout AUC {auc_h:.4f} <= floor {sz.gbdt_auc_floor}")
    scored = model.transform(Dataset({"features": Xh[:1000],
                                      "label": yh[:1000]}))
    pred = np.asarray(scored["prediction"], np.float64)
    check(float((pred == yh[:1000]).mean()) > 0.8,
          "transform() predictions disagree with the labels")

    # a bin count the kernels do not take (B % 8 != 0): the XLA scatter
    # builder on THIS backend, with its own ingest dtype branch
    Xs, ys = X[:20_000], y[:20_000]
    b_xla, _ = train(Xs, ys, BoostingConfig(
        objective="binary", num_iterations=3, num_leaves=15, max_bin=100))
    check(b_xla.measures.hist_path == "xla_scatter",
          f"max_bin=100 took {b_xla.measures.hist_path!r}")
    auc_x = float(auc(ys, b_xla.predict_margin(Xs)))
    check(auc_x > 0.85, f"XLA-scatter fit AUC {auc_x:.4f}")

    if n_dev > 1:
        # __graft_entry__'s dp parity, on the real mesh: sharded training
        # equals single-device training
        pcfg = BoostingConfig(objective="binary", num_iterations=2,
                              num_leaves=7, min_data_in_leaf=5)
        Xp, yp = X[:4096], y[:4096]
        b1, _ = train(Xp, yp, pcfg)
        bn, _ = train(Xp, yp, pcfg, mesh=data_parallel_mesh(n_dev))
        gap = float(np.abs(b1.predict_margin(Xp)
                           - bn.predict_margin(Xp)).max())
        check(gap <= 1e-4, f"dp parity: 1 vs {n_dev} devices differ by {gap}")
    ph.extra.update(hist_path=measures.hist_path, shards=n_dev,
                    holdout_auc=round(auc_h, 4),
                    xla_scatter_auc=round(auc_x, 4))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on any backend, to debug this script; "
                         "never prints \"ok\"")
    args = ap.parse_args()

    import jax

    # importing the package resolves the compile cache directory
    from synapseml_tpu.native import native_available
    from synapseml_tpu.parallel.compilecache import (
        compilation_cache_dir, install_compile_listeners)

    backend = jax.default_backend()
    devs = jax.devices()
    identity = {
        "jax": jax.__version__,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "compile_cache_dir": compilation_cache_dir(),
        "native": native_available(),
    }
    say(f"jax {jax.__version__} backend={backend} "
        f"device_kind={devs[0].device_kind!r} devices={len(devs)} "
        f"compile_cache={identity['compile_cache_dir']} "
        f"native={identity['native']}")
    if args.rehearse_cpu:
        say("REHEARSAL at toy sizes: this run proves nothing about the chip")
        sz = rehearsal_sizes()
    else:
        if backend != "tpu":
            say(f"backend is {backend!r}, not 'tpu': nothing was run")
            return 2
        sz = full_sizes()
    check(identity["native"], "the native loader did not build (g++): "
          "1M-row binning would take the numpy path")
    install_compile_listeners()

    phases: Dict[str, Any] = {}
    t0 = time.monotonic()
    with Phase("serving", phases) as ph:
        serving_phase(sz, ph)
    with Phase("dl", phases) as ph:
        dl_phase(sz, ph)
    with Phase("gbdt", phases) as ph:
        gbdt_phase(sz, ph)
    result = {**identity, "wall_s": round(time.monotonic() - t0, 2),
              "attention_backend": phases["serving"]["attention_backend"],
              "hist_path": phases["gbdt"]["hist_path"], "phases": phases}
    say("report " + json.dumps({"rehearsal": args.rehearse_cpu, **result}))
    if not args.rehearse_cpu:
        # the last line: exactly these keys, the device as JAX reports it
        print(json.dumps({"ok": True, "device": identity["device"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
