"""Deep-learning pipeline estimators: text + vision classifiers.

API parity with the reference's Horovod estimators
(reference: DeepVisionClassifier.py:31-269, DeepTextClassifier.py:27-290,
DeepVisionModel.py, DeepTextModel.py), re-designed so ``fit`` runs a pjit
train loop over the device mesh (grad psum over ICI) instead of spawning
Horovod processes per Spark executor.

Param name parity: batchSize/maxEpochs/learningRate/optimizer/backbone/
maxTokenLen mirror the reference's TorchEstimator kwargs (captured there by
``utils.keywords_catch``, dl/utils.py:11).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ...core.dataset import Dataset
from ...core.params import (BoolParam, FloatParam, IntParam, ListParam,
                            Params, PyObjectParam, StringParam)
from ...core.pipeline import Estimator, Model
from .resnet import make_backbone
from .tokenizer import (WordPieceTokenizer, WordTokenizer,
                        tokenizer_from_dict)
from .training import (DLTrainer, OptimizerConfig, TrainState,
                       iterate_minibatches, make_dl_mesh, num_minibatches)
from .transformer import TextEncoder, TransformerConfig

from flax import linen as nn
from flax.core import freeze


def _bert_checkpoint_assets(path, dropout_rate):
    """Tokenizer + TransformerConfig for an HF-format BERT checkpoint dir
    (config.json + vocab.txt); a bare weights file needs neither — the
    caller keeps its configured dims and corpus tokenizer."""
    import json
    import os

    d = path if os.path.isdir(path) else os.path.dirname(path)
    cfg_path = os.path.join(d, "config.json")
    vocab_path = os.path.join(d, "vocab.txt")
    if not os.path.exists(cfg_path) or not os.path.exists(vocab_path):
        raise ValueError(
            f"checkpoint {path!r} needs config.json and vocab.txt beside the "
            "weights (an HF model directory) so dims and tokenization match "
            "the pretrained weights")
    with open(cfg_path) as f:
        hc = json.load(f)
    tokenizer = WordPieceTokenizer.from_vocab_file(
        vocab_path, lowercase=hc.get("do_lower_case", True))
    # max_len must equal the pretrained position table for weight import;
    # callers truncate sequences separately via maxTokenLen
    cfg = TransformerConfig(
        vocab_size=hc["vocab_size"],
        max_len=int(hc.get("max_position_embeddings", 512)),
        num_layers=hc["num_hidden_layers"],
        num_heads=hc["num_attention_heads"],
        d_model=hc["hidden_size"],
        d_ff=hc["intermediate_size"],
        dropout_rate=dropout_rate)
    return tokenizer, cfg


def _host_params(state: TrainState):
    """Unbox + pull params/extra vars to host numpy for storage."""
    unboxed = nn.meta.unbox({"params": state.params, **state.extra_vars})
    return jax.tree.map(np.asarray, unboxed)


def _batched_infer(key: str, n: int, batch_size: int,
                   infer_chunk) -> np.ndarray:
    """OOM-adaptive inference loop shared by the DL model transforms:
    runs ``infer_chunk(start, size, bs)`` over ``[0, n)`` windows of
    ``batch_size`` rows, and on XLA ``RESOURCE_EXHAUSTED`` halves the
    batch size and reruns instead of dying (safe size remembered per
    stage in the ``rowguard_safe_batch_size`` gauge)."""
    from ...resilience.rowguard import oom_fault_point, run_adaptive

    def run(bs: int) -> np.ndarray:
        outs = []
        for start in range(0, n, bs):
            size = min(bs, n - start)
            oom_fault_point(key, size)
            outs.append(infer_chunk(start, size, bs))
        return np.concatenate(outs)

    return run_adaptive(key, batch_size, run)


class _DLParamsBase(Params):
    #: the DL stages name their inputs textCol/imageCol — declare them to
    #: the row guard so contract checks + None screens cover them
    _guard_input_params = ("inputCol", "inputCols", "textCol", "imageCol")

    labelCol = StringParam(doc="label column", default="label")
    predictionCol = StringParam(doc="prediction column", default="prediction")
    probabilityCol = StringParam(doc="probability column", default="probability")
    batchSize = IntParam(doc="global batch size", default=32)
    maxEpochs = IntParam(doc="training epochs", default=3)
    learningRate = FloatParam(doc="peak learning rate", default=1e-4)
    optimizer = StringParam(doc="adamw|adam|sgd", default="adamw",
                            allowed=("adamw", "adam", "sgd"))
    weightDecay = FloatParam(doc="adamw weight decay", default=0.01)
    lrSchedule = StringParam(doc="constant|cosine|linear", default="cosine",
                             allowed=("constant", "cosine", "linear"))
    warmupRatio = FloatParam(doc="warmup fraction of steps", default=0.06)
    gradClipNorm = FloatParam(doc="gradient clip norm (0=off)", default=1.0)
    seed = IntParam(doc="rng seed", default=0)
    numDevices = IntParam(doc="devices to use (0=all)", default=0)
    modelParallelism = IntParam(doc="tensor-parallel size over mesh 'model' "
                                    "axis", default=1)
    zero1 = BoolParam(doc="shard optimizer moments over the data axis "
                          "(ZeRO-1 weight-update sharding)", default=False)
    validationFraction = FloatParam(doc="fraction held out for eval logging",
                                    default=0.0)
    checkpointDir = StringParam(doc="step-checkpoint directory (resume "
                                "automatically if it holds checkpoints)")
    checkpointInterval = IntParam(doc="save every N optimizer steps "
                                  "(0 = off)", default=0)
    checkpointManager = PyObjectParam(
        doc="core.checkpoint.CheckpointManager to save/resume through "
            "(overrides checkpointDir) — the preemption-tolerant fit "
            "surface: re-fit with the same manager resumes from "
            "latest_step")
    stepProfiler = PyObjectParam(
        doc="telemetry.gangplane.StepProfiler decomposing each train "
            "step into data/compute/collective/other wall time "
            "(train_step_seconds{model,segment}); with capture_xla=True "
            "it also records the compiled step's XLA cost analysis for "
            "the roofline summary")
    rematPolicy = StringParam(
        doc="rematerialize model blocks in the backward pass: 'none' | "
            "'dots_saveable' (keep matmul outputs, recompute the cheap "
            "chains) | 'full'/'blocks' (save only block inputs — O(1)-"
            "block activation memory for ~1/3 more FLOPs).  Bit-exact vs "
            "'none' by construction (the recompute re-runs the identical "
            "ops); the byte-diet lever for bandwidth-bound fine-tunes",
        default="none",
        allowed=("none", "dots_saveable", "full", "blocks"))
    precision = StringParam(
        doc="mixed-precision policy (models/dl/precision.py): 'bf16' "
            "(default — bf16 activations, f32 grads/params, the "
            "historical step byte-for-byte) | 'f32' (full-precision "
            "compute) | 'bf16_grad' (bf16 activations AND gradient "
            "leaves across the sync boundary; f32 master params/"
            "optimizer/batch-stats — holdout-parity pinned, composes "
            "with collectiveCompression, EF residuals stay f32)",
        default="bf16", allowed=("bf16", "f32", "bf16_grad"))
    collectiveCompression = PyObjectParam(
        doc="wire codec + sharding for the gradient sync: 'none' "
            "(default, the unchanged pjit path) | 'bf16' | 'int8' "
            "(both with error feedback) | a parallel.compression."
            "CollectiveConfig (compression / sharded_update / "
            "error_feedback / min_size knobs) — runs the step as manual "
            "data-parallel shard_map with a quantized allreduce and/or "
            "reduce-scatter sharded weight update; requires a pure "
            "data mesh (modelParallelism/expertParallelism == 1)")

    def _collective_config(self):
        from ...parallel.compression import resolve_collective_config
        return resolve_collective_config(self.get("collectiveCompression"))

    def _precision_policy(self):
        from .precision import resolve_precision
        return resolve_precision(self.precision)

    def _model_dtype(self):
        """Model compute dtype under the precision policy (the models'
        own default is bf16; 'f32' lifts the whole forward/backward)."""
        return self._precision_policy().compute_dtype

    def _checkpoint_loop(self, trainer: "DLTrainer", state: "TrainState",
                         step=None) -> "_CheckpointLoop":
        return _CheckpointLoop(self, trainer, state, step)

    def _opt_config(self, total_steps: int) -> OptimizerConfig:
        return OptimizerConfig(
            name=self.optimizer, learning_rate=self.learningRate,
            weight_decay=self.weightDecay, schedule=self.lrSchedule,
            warmup_steps=int(total_steps * self.warmupRatio),
            total_steps=total_steps, grad_clip_norm=self.gradClipNorm)


class _CheckpointLoop:
    """Shared resume scaffolding for the DL fit loops (SURVEY §5.4 — the
    reference cannot resume mid-training; this build can).

    Responsibilities: restore the latest step into the initialized state's
    structure, RE-SHARD the restored host arrays onto the trainer's mesh
    (restore_state_dict hands back uncommitted numpy — without device_put
    the tensor-parallel layout would silently degrade to replication),
    validate that the data-order-determining config matches the run that
    wrote the checkpoint, and save every ``checkpointInterval`` steps.
    """

    # keys that determine the deterministic data order being replayed —
    # maxEpochs is deliberately absent (resuming with MORE epochs is the
    # normal continue-training pattern)
    _CONFIG_KEYS = ("batchSize", "seed", "validationFraction")
    #: collectiveCompression codec → config-guard float (the guard
    #: compares floats; a codec switch mid-run would silently change
    #: both the numerics and the checkpoint structure)
    _CODEC_CODE = {"none": 0.0, "bf16": 1.0, "int8": 2.0}

    def __init__(self, est: "_DLParamsBase", trainer, state, step=None):
        from ...core.checkpoint import CheckpointManager
        self.manager = None
        self.start_step = 0
        self.interval = int(est.checkpointInterval)
        self.state = state
        self._step = step
        self._config = {k: float(est.get_or_default(k))
                        for k in self._CONFIG_KEYS}
        self._config["shards"] = float(trainer.mesh.shape["data"])
        # ALWAYS written (0.0 = off), so toggling any knob that changes
        # the step's numerics against an existing checkpoint mismatches
        # instead of slipping through the saved∩current key intersection
        # below: codec, sharding, EF, the big/small partition
        # (min_size), the int8 chunk, and whether the manual shard_map
        # step (per-rank dropout stream ≠ pjit's) is in use at all
        cc = getattr(trainer, "collective", None)
        self._config["compression"] = self._CODEC_CODE[
            cc.compression if cc is not None else "none"]
        self._config["sharded_update"] = float(
            cc.sharded_update if cc is not None else False)
        self._config["error_feedback"] = float(
            cc.error_feedback if cc is not None else False)
        self._config["manual_step"] = float(cc is not None)
        self._config["codec_min_size"] = float(
            cc.min_size if cc is not None else 0.0)
        self._config["codec_chunk"] = float(
            cc.chunk if cc is not None and cc.compression == "int8"
            else 0.0)
        # the RESOLVED planner routing (ISSUE 14): 0.0 when every plan
        # under this config is the flat dispatch (strategy='flat', or
        # 'auto' with no trusted topology — every pre-planner
        # checkpoint), else 1 + the strategy's index.  A routing switch
        # changes the gradient-sync numerics (hierarchical quantizes
        # intra-host sums; ring/tree reassociate), so it refuses like a
        # codec toggle — the satellite's "loud refusal" contract.
        from ...parallel.planner import STRATEGIES, get_planner
        # the stamp must name a route the gradient sync can actually
        # run: a config that neither compresses nor explicitly routes
        # leaves compressed_tree_sync's big-leaf set empty (bare 'auto'
        # syncs flat even on a trusted topology), and the ZeRO-1
        # sharded_update step reduce-scatters directly without ever
        # consulting the planner — both stamp flat, else the guard
        # would refuse resumes against numerically identical syncs
        unroutable = (cc is None or cc.sharded_update
                      or (not cc.compresses and not cc.routes))
        routing = ("flat" if unroutable
                   else get_planner().resolved_routing(
                       cc, world=int(trainer.mesh.shape["data"])))
        self._config["routing"] = (
            0.0 if routing == "flat"
            else float(1 + STRATEGIES.index(routing)))
        # precision changes the numerics the resumed batches train under
        # ('bf16_grad' rounds the gradient stream); rematPolicy is
        # deliberately ABSENT — remat is bit-exact by construction, so a
        # remat toggle may resume any checkpoint
        from .precision import PRECISION_CODE
        self._config["precision"] = PRECISION_CODE[
            str(est.get_or_default("precision"))]
        manager = est.get("checkpointManager")
        ckpt_dir = est.get("checkpointDir")
        if manager is None and not ckpt_dir:
            return
        self.manager = (manager if manager is not None
                        else CheckpointManager(ckpt_dir))
        ckpt_dir = self.manager.directory
        latest = self.manager.latest_step()
        if latest is None:
            return
        saved_cfg = {k: v for k, v in self.manager.metrics(latest).items()
                     if k in self._config}
        # checkpoints that predate the compression keys never wrote them:
        # absence means the pjit step at compression-off wrote it, so the
        # missing keys compare as 0.0 — enabling any codec/manual/sharding
        # knob against such a checkpoint mismatches instead of slipping
        # the saved∩current intersection
        for k in ("compression", "sharded_update", "error_feedback",
                  "manual_step", "codec_min_size", "codec_chunk",
                  "precision",        # pre-precision checkpoints = 'bf16'
                  "routing"):         # pre-planner checkpoints = flat
            saved_cfg.setdefault(k, 0.0)
        # "shards" is the one WORLD-SIZE key: a mismatch there is an
        # elastic gang resize, not a config error — the checkpoint is
        # world-size-independent by contract (gather-to-canonical-then-
        # reshard below), so it re-shards instead of refusing.  Every
        # other key still refuses: those change the numerics/data order
        # in ways no re-shard can reconcile.
        mismatch = {k: (saved_cfg[k], self._config[k]) for k in saved_cfg
                    if saved_cfg[k] != self._config[k] and k != "shards"}
        if mismatch:
            raise ValueError(
                f"checkpoint at {ckpt_dir} step {latest} was written with a "
                f"different data-order config {mismatch}; resuming would "
                f"silently train on wrong batches — use a fresh "
                f"checkpointDir or restore manually")
        saved_shards = int(saved_cfg.get("shards",
                                         self._config["shards"]))
        cur_shards = int(self._config["shards"])
        resized = saved_shards != cur_shards
        residuals = self._residuals()
        if residuals is not None:
            # error-feedback residuals are live training state: they
            # ride the same checkpoint pytree so kill→resume replays the
            # exact compressed gradient stream (bit-exactness pinned in
            # tests/test_collectives_compression.py).  Restoring across
            # a resize, the saved (N, *shape) stacking lands in the
            # M-shaped template positionally and reshard_restored
            # re-lays it before anything touches a device.
            restored, res = self.manager.restore_state_dict(
                (state, residuals))
            if resized:
                restored, res = trainer.reshard_restored(
                    restored, res, saved_shards)
            res = jax.device_put(res, jax.tree_util.tree_map(
                lambda _: trainer.residual_sharding(), res))
            self._step.set_residuals(res)
        else:
            restored = self.manager.restore_state_dict(state)
            if resized:
                restored, _ = trainer.reshard_restored(
                    restored, None, saved_shards)
        if resized:
            from ...resilience.faults import get_faults
            from ...telemetry.flight import record as flight_record
            get_faults().note("dl.resize_resume", saved=saved_shards,
                              current=cur_shards)
            flight_record("resize_resume", trainer="dl",
                          saved_shards=saved_shards,
                          current_shards=cur_shards)
        if trainer.state_shardings is not None:
            restored = jax.device_put(restored, trainer.state_shardings)
        self.state = restored
        self.start_step = int(np.asarray(restored.step))

    def _residuals(self):
        return getattr(self._step, "residuals", None)

    def skips(self, gstep: int) -> bool:
        """True while replaying already-trained steps (data order is
        re-derived deterministically; no compute runs)."""
        return gstep <= self.start_step

    def after_step(self, gstep: int, state) -> None:
        if self.manager and self.interval and gstep % self.interval == 0:
            residuals = self._residuals()
            payload = ((state, residuals) if residuals is not None
                       else state)
            self.manager.save(gstep, jax.device_get(payload),
                              metrics=self._config)
            # preemption point: a kill/preempt fault lands exactly where
            # a real TPU eviction would — after a durable step, before
            # the next one
            from ...resilience.faults import get_faults
            get_faults().kill_point("dl.checkpoint", step=gstep)


class DeepTextClassifier(_DLParamsBase, Estimator):
    """BERT-style text classifier (reference: DeepTextClassifier.py:27)."""
    textCol = StringParam(doc="input text column", default="text")
    maxTokenLen = IntParam(doc="max sequence length "
                               "(DeepTextClassifier.py:55)", default=128)
    vocabSize = IntParam(doc="tokenizer vocab size", default=8192)
    modelSize = StringParam(doc="tiny|small|base", default="small",
                            allowed=("tiny", "small", "base"))
    checkpoint = StringParam(
        doc="HF-format BERT checkpoint to fine-tune from: a model dir "
            "(config.json + vocab.txt + weights) or a weights file; "
            "overrides modelSize/vocabSize with the checkpoint's dims "
            "(from_pretrained analogue, LitDeepTextModel.py:86)")
    dropoutRate = FloatParam(doc="dropout rate", default=0.1)
    numExperts = IntParam(doc="0 = dense FFN; >0 = MoE FFN with this many "
                              "experts, sharded over the mesh expert axis",
                          default=0)
    gradientCheckpointing = BoolParam(
        doc="rematerialize encoder blocks in the backward pass "
            "(jax.checkpoint): O(1)-block activation memory for ~1/3 more "
            "FLOPs — fits longer sequences / larger per-chip batches",
        default=False)
    moeTopK = IntParam(doc="MoE router top-k", default=2)
    expertParallelism = IntParam(doc="expert-axis mesh size (>1 shards "
                                     "experts over chips; requires "
                                     "numExperts > 0)", default=1)

    def _model_config(self, num_classes: int) -> TransformerConfig:
        sizes = {
            "tiny": dict(num_layers=2, num_heads=4, d_model=128, d_ff=512),
            "small": dict(num_layers=4, num_heads=8, d_model=256, d_ff=1024),
            "base": dict(num_layers=12, num_heads=12, d_model=768, d_ff=3072),
        }[self.modelSize]
        return TransformerConfig(
            vocab_size=self.vocabSize, max_len=self.maxTokenLen,
            num_classes=num_classes, dropout_rate=self.dropoutRate,
            num_experts=self.numExperts, moe_top_k=self.moeTopK,
            remat=bool(self.gradientCheckpointing), **sizes)

    def _fit(self, ds: Dataset) -> "DeepTextModel":
        texts = list(ds[self.textCol])
        y_raw = np.asarray(ds[self.labelCol], np.float64)
        classes = np.unique(y_raw)
        labels = np.searchsorted(classes, y_raw).astype(np.int32)
        num_classes = len(classes)

        ckpt_path = self.get("checkpoint")
        ckpt_cfg = None
        if ckpt_path:
            tokenizer, ckpt_cfg = _bert_checkpoint_assets(
                ckpt_path, self.dropoutRate)
        else:
            tokenizer = WordTokenizer.fit(texts, self.vocabSize)
        ids, mask = tokenizer.encode(texts, self.maxTokenLen)

        ep = int(self.expertParallelism)
        if ep > 1:
            if self.numExperts <= 0:
                raise ValueError("expertParallelism > 1 requires "
                                 "numExperts > 0 (MoE FFN)")
            if self.numExperts % ep:
                raise ValueError(
                    f"numExperts={self.numExperts} must be divisible by "
                    f"expertParallelism={ep} to shard experts evenly")
            from ...parallel.mesh import dp_ep_mesh
            devs = jax.devices()[:self.numDevices or None]
            if len(devs) % ep:
                raise ValueError(
                    f"expertParallelism={ep} does not divide the "
                    f"{len(devs)} available devices")
            mesh = dp_ep_mesh(ep, devs)
        else:
            mesh = make_dl_mesh(self.modelParallelism,
                                self.numDevices or None)
        shards = mesh.shape["data"]

        # validationFraction: hold out rows for per-epoch eval logging
        n_all = len(texts)
        n_val = int(n_all * self.validationFraction)
        if n_val:
            val_slice = slice(n_all - n_val, n_all)
            ids, mask, labels, val_ids, val_mask, val_labels = (
                ids[:n_all - n_val], mask[:n_all - n_val],
                labels[:n_all - n_val], ids[val_slice], mask[val_slice],
                labels[val_slice])
        n = len(labels)
        total_steps = num_minibatches(n, self.batchSize, shards) * self.maxEpochs

        base_cfg = (ckpt_cfg if ckpt_cfg is not None
                    else self._model_config(num_classes))
        # estimator-level overrides applied once, whichever branch built
        # the config (the checkpoint path carries the pretrained dims);
        # rematPolicy supersedes the legacy gradientCheckpointing bool
        remat = (self.rematPolicy if self.rematPolicy != "none"
                 else bool(self.gradientCheckpointing))
        cfg = dataclasses.replace(base_cfg, num_classes=num_classes,
                                  remat=remat, dtype=self._model_dtype())
        model = TextEncoder(cfg)
        trainer = DLTrainer(model, self._opt_config(total_steps), mesh,
                            zero1=bool(self.zero1),
                            collective=self._collective_config(),
                            precision=self._precision_policy())
        sample_n = max(self.batchSize, shards)
        state = trainer.init_state(self.seed, ids[:sample_n], mask[:sample_n])
        if ckpt_path:
            from .checkpoints import import_bert
            state = state.replace(params=import_bert(
                state.params, ckpt_path, num_layers=cfg.num_layers))
        step = trainer.train_step()
        eval_step = trainer.eval_step()
        rng = np.random.default_rng(self.seed)
        key = jax.random.PRNGKey(self.seed)

        ckpt = self._checkpoint_loop(trainer, state, step)
        state = ckpt.state
        gstep = 0
        history = []
        metrics = {}
        prof = self.get("stepProfiler")
        try:
            for epoch in range(self.maxEpochs):
                for idx in iterate_minibatches(n, self.batchSize, shards, rng):
                    gstep += 1
                    if ckpt.skips(gstep):
                        continue
                    if prof is not None:
                        prof.step_begin(gstep)
                    bi, bm, bl = trainer.shard_batch(
                        (ids[idx], mask[idx], labels[idx]))
                    if prof is not None:
                        prof.mark("data")
                        if prof.capture_xla:
                            # items = per-DEVICE samples: the captured
                            # cost is the SPMD per-device program's
                            prof.capture_cost("dl_text_step", step,
                                              state, (bi, bm), bl, key,
                                              items=len(idx) // shards)
                    state, metrics = step(state, (bi, bm), bl, key)
                    if prof is not None:
                        # async dispatch returns immediately; sync so
                        # "compute" times execution, not the enqueue
                        jax.block_until_ready(metrics)
                        prof.mark("compute")
                    ckpt.after_step(gstep, state)
                    if prof is not None:
                        prof.step_end()       # checkpoint write → "other"
                if ckpt.skips(gstep):
                    continue  # whole epoch already covered by the checkpoint
                record = {k: float(v) for k, v in metrics.items()}
                if n_val:
                    vlogits = np.asarray(eval_step(state, (val_ids, val_mask)))
                    record["val_accuracy"] = float(
                        (vlogits.argmax(-1) == val_labels).mean())
                history.append(record)
        finally:
            if prof is not None:
                prof.finish()   # exception path: close the open
                #                 step, restore the thread-local

        return DeepTextModel(
            modelPayload={
                "variables": _host_params(state),
                "config": cfg,
                "tokenizer": tokenizer.to_dict(),
                "classes": [float(c) for c in classes],
                "history": history,
            },
            textCol=self.textCol,
            predictionCol=self.predictionCol,
            probabilityCol=self.probabilityCol,
            maxTokenLen=self.maxTokenLen,
            batchSize=self.batchSize,
        )


class DeepTextModel(Model):
    """Inference transformer (reference: DeepTextModel.py:1-119)."""
    textCol = StringParam(doc="input text column", default="text")
    predictionCol = StringParam(doc="prediction column", default="prediction")
    probabilityCol = StringParam(doc="probability column", default="probability")
    maxTokenLen = IntParam(doc="max sequence length", default=128)
    batchSize = IntParam(doc="inference batch size", default=64)
    modelPayload = PyObjectParam(doc="trained weights + tokenizer + config")

    def _transform(self, ds: Dataset) -> Dataset:
        payload = self.modelPayload
        cfg: TransformerConfig = payload["config"]
        model = TextEncoder(cfg)
        tokenizer = tokenizer_from_dict(payload["tokenizer"])
        variables = payload["variables"]
        classes = np.asarray(payload["classes"])

        texts = list(ds[self.textCol])
        ids, mask = tokenizer.encode(texts, self.maxTokenLen)

        @jax.jit
        def infer(ids, mask):
            return model.apply(variables, ids, mask, deterministic=True)

        n = len(texts)

        def infer_chunk(start, size, bs):
            chunk_ids = ids[start:start + size]
            chunk_mask = mask[start:start + size]
            if size < bs and n > bs:               # pad tail: static shapes
                padn = bs - size
                chunk_ids = np.concatenate([chunk_ids, np.zeros((padn, ids.shape[1]), ids.dtype)])
                chunk_mask = np.concatenate([chunk_mask, np.zeros((padn, mask.shape[1]), mask.dtype)])
                return np.asarray(infer(chunk_ids, chunk_mask))[:size]
            return np.asarray(infer(chunk_ids, chunk_mask))

        # structural OOM key (not uid): a reloaded model keeps its
        # discovered safe batch size, and the gauge stays bounded by the
        # number of distinct architectures
        key = (f"dl:text:{cfg.num_layers}l{cfg.d_model}d"
               f"{cfg.vocab_size}v:{self.maxTokenLen}t")
        logits = _batched_infer(key, n, int(self.batchSize), infer_chunk)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        proba = e / e.sum(-1, keepdims=True)
        pred = classes[np.argmax(proba, axis=1)]
        return (ds.with_column(self.predictionCol, pred.astype(np.float64))
                  .with_column(self.probabilityCol, list(proba.astype(np.float64))))


class DeepVisionClassifier(_DLParamsBase, Estimator):
    """CNN image classifier (reference: DeepVisionClassifier.py:31)."""
    imageCol = StringParam(doc="image column (HWC arrays)", default="image")
    backbone = StringParam(doc="resnet18|resnet34|resnet50|resnet101|resnet152",
                           default="resnet50")
    checkpoint = StringParam(
        doc="torchvision-format resnet checkpoint (state-dict file) to "
            "fine-tune from; the classifier head reloads only when its "
            "shape matches (pretrained-backbone analogue, "
            "DeepVisionClassifier.py:31)")

    def _fit(self, ds: Dataset) -> "DeepVisionModel":
        imgs = np.stack([np.asarray(im, np.float32) for im in ds[self.imageCol]])
        # decide normalization once at fit; the model stores the decision so
        # transform always scales consistently
        scale255 = bool(imgs.max() > 2.0)
        if scale255:
            imgs = imgs / 255.0
        y_raw = np.asarray(ds[self.labelCol], np.float64)
        classes = np.unique(y_raw)
        labels = np.searchsorted(classes, y_raw).astype(np.int32)

        mesh = make_dl_mesh(1, self.numDevices or None)
        shards = mesh.shape["data"]
        n = len(imgs)
        total_steps = num_minibatches(n, self.batchSize, shards) * self.maxEpochs

        model = make_backbone(self.backbone, num_classes=len(classes),
                              remat=self.rematPolicy,
                              dtype=self._model_dtype())
        trainer = DLTrainer(model, self._opt_config(total_steps), mesh,
                            has_batch_stats=True, train_kwarg="train",
                            zero1=bool(self.zero1),
                            collective=self._collective_config(),
                            precision=self._precision_policy())
        sample_n = max(self.batchSize, shards)
        state = trainer.init_state(self.seed, imgs[:sample_n])
        if self.get("checkpoint"):
            from .checkpoints import import_resnet
            from .resnet import BACKBONES, BottleneckResNetBlock
            bb = BACKBONES[self.backbone]
            new_vars = import_resnet(
                {"params": state.params, **state.extra_vars},
                self.get("checkpoint"),
                stage_sizes=bb.keywords["stage_sizes"],
                bottleneck=bb.keywords["block_cls"] is BottleneckResNetBlock)
            state = state.replace(
                params=new_vars["params"],
                extra_vars={k: v for k, v in new_vars.items()
                            if k != "params"})
        step = trainer.train_step()
        rng = np.random.default_rng(self.seed)
        key = jax.random.PRNGKey(self.seed)

        ckpt = self._checkpoint_loop(trainer, state, step)
        state = ckpt.state
        gstep = 0
        history = []
        metrics = {}
        prof = self.get("stepProfiler")
        try:
            for epoch in range(self.maxEpochs):
                for idx in iterate_minibatches(n, self.batchSize, shards, rng):
                    gstep += 1
                    if ckpt.skips(gstep):
                        continue
                    if prof is not None:
                        prof.step_begin(gstep)
                    bi, bl = trainer.shard_batch((imgs[idx], labels[idx]))
                    if prof is not None:
                        prof.mark("data")
                        if prof.capture_xla:
                            # items = per-DEVICE samples (see text path)
                            prof.capture_cost("dl_vision_step", step,
                                              state, (bi,), bl, key,
                                              items=len(idx) // shards)
                    state, metrics = step(state, (bi,), bl, key)
                    if prof is not None:
                        # async dispatch returns immediately; sync so
                        # "compute" times execution, not the enqueue
                        jax.block_until_ready(metrics)
                        prof.mark("compute")
                    ckpt.after_step(gstep, state)
                    if prof is not None:
                        prof.step_end()
                if ckpt.skips(gstep):
                    continue
                history.append({k: float(v) for k, v in metrics.items()})
        finally:
            if prof is not None:
                prof.finish()   # exception path: close the open
                #                 step, restore the thread-local

        return DeepVisionModel(
            modelPayload={
                "variables": _host_params(state),
                "backbone": self.backbone,
                "classes": [float(c) for c in classes],
                "scale255": scale255,
                "history": history,
            },
            imageCol=self.imageCol,
            predictionCol=self.predictionCol,
            probabilityCol=self.probabilityCol,
            batchSize=self.batchSize,
        )


class DeepVisionModel(Model):
    """Inference transformer (reference: DeepVisionModel.py:1-122)."""
    imageCol = StringParam(doc="image column", default="image")
    predictionCol = StringParam(doc="prediction column", default="prediction")
    probabilityCol = StringParam(doc="probability column", default="probability")
    batchSize = IntParam(doc="inference batch size", default=64)
    modelPayload = PyObjectParam(doc="trained weights + config")

    def _transform(self, ds: Dataset) -> Dataset:
        payload = self.modelPayload
        classes = np.asarray(payload["classes"])
        model = make_backbone(payload["backbone"], num_classes=len(classes))
        variables = payload["variables"]

        imgs = np.stack([np.asarray(im, np.float32) for im in ds[self.imageCol]])
        if payload.get("scale255"):
            imgs = imgs / 255.0

        @jax.jit
        def infer(x):
            return model.apply(variables, x, train=False)

        n = len(imgs)

        def infer_chunk(start, size, bs):
            chunk = imgs[start:start + size]
            if size < bs and n > bs:
                padn = bs - size
                chunk = np.concatenate([chunk, np.zeros((padn,) + chunk.shape[1:],
                                                        chunk.dtype)])
                return np.asarray(infer(chunk))[:size]
            return np.asarray(infer(chunk))

        key = (f"dl:vision:{payload['backbone']}:{len(classes)}c:"
               f"{'x'.join(str(d) for d in imgs.shape[1:])}")
        logits = _batched_infer(key, n, int(self.batchSize), infer_chunk)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        proba = e / e.sum(-1, keepdims=True)
        pred = classes[np.argmax(proba, axis=1)]
        return (ds.with_column(self.predictionCol, pred.astype(np.float64))
                  .with_column(self.probabilityCol, list(proba.astype(np.float64))))
