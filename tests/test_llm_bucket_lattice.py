"""The prefill bucket lattice (``slots.prefill_buckets``): powers of two from
the floor, ``max_len``, and one bucket of ``3p/2`` between the last doubling
``p`` and ``max_len`` where ``p`` is 1,024 or more and ``3p/2`` is under
``max_len``.

- one parametrised case a lattice: each benchmark configuration's
  ``max_len``, engines of 1,536 positions or fewer (the parent's lattice
  exactly) and an explicit ``min_bucket`` as the floor, through the function
  and through an engine;
- greedy decode through an engine of 2,048 positions, with a prompt in the
  1,536 bucket beside one in the 2,048 bucket, token for token the dense
  ``generate`` path; the rows counter and ``engine.admit``'s
  ``padding_rows``;
- the new buckets of the Mistral and MiMo configurations described at their
  published widths (no weights, no chip): the prefill kernel on every kind,
  with the tiles of their power-of-two neighbours, and the projection sites
  cut or kept as theirs.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.runners.llm_serve import build_model  # noqa: E402
from synapseml_tpu.models.llm import (LlamaConfig, LlamaModel,  # noqa: E402
                                      SlotEngine, generate, init_cache)
from synapseml_tpu.models.llm import model as M  # noqa: E402
from synapseml_tpu.models.llm import slots as S  # noqa: E402
from synapseml_tpu.telemetry import get_registry, get_tracer  # noqa: E402

pytestmark = pytest.mark.llmserve


def doublings(floor, max_len):
    """The lattice of powers of two alone: the floor doubled, then
    ``max_len``."""
    out, b = [], floor
    while b < max_len:
        out.append(b)
        b *= 2
    return tuple(out) + (max_len,)


#: id -> (max_len, floor, the lattice)
LATTICES = {
    "mistral-2048": (2048, 8, doublings(8, 1024) + (1536, 2048)),
    "olmo-1536": (1536, 8, doublings(8, 1536)),
    "command-a-plus-5632": (5632, 8, doublings(8, 5632)),
    "mimo-16384": (16384, 8, doublings(8, 8192) + (12288, 16384)),
    "ax-k1-17920": (17920, 8, doublings(8, 17920)),
    "tiny-64": (64, 8, doublings(8, 64)),
    "tiny-1024": (1024, 8, doublings(8, 1024)),
    "tiny-1025": (1025, 8, doublings(8, 1025)),
    "floor-64-at-2048": (2048, 64, doublings(64, 1024) + (1536, 2048)),
    "floor-1024-at-4096": (4096, 1024, (1024, 2048, 3072, 4096)),
    "floor-at-max-len": (2048, 2048, (2048,)),
    "floor-4-at-1536": (1536, 4, doublings(4, 1536)),
}


@pytest.mark.parametrize("case", list(LATTICES))
def test_the_lattice_follows_max_len_and_the_floor(case):
    max_len, floor, want = LATTICES[case]
    assert S.prefill_buckets(max_len, floor) == want
    if max_len <= 1536:
        # every engine of 1,536 positions or fewer keeps the powers of two
        assert want == doublings(floor, max_len)
    cfg = LlamaConfig.tiny(num_layers=1, max_len=max_len, dtype=jnp.float32)
    eng = SlotEngine(LlamaModel(cfg), {}, n_slots=1, max_len=max_len,
                     min_bucket=floor, name=f"t-lattice-{case}")
    assert eng._buckets == want
    # each prompt length lands in the smallest bucket that holds it
    for n in (1, max_len // 2 + 1, 3 * max_len // 4, max_len):
        assert eng._bucket(n) == min(b for b in want if b >= n)


def test_greedy_decode_through_the_mid_bucket_is_the_dense_paths(tmp_path):
    cfg = LlamaConfig.tiny(num_layers=2, max_len=2048, dtype=jnp.float32)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(42)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in (1100, 1600)]
    name = "t-lattice-exact"
    eng = SlotEngine(model, variables, n_slots=2, max_len=2048,
                     attention_backend="dense", min_bucket=8, name=name)
    assert eng._buckets[-3:] == (1024, 1536, 2048)
    jax.profiler.start_trace(str(tmp_path))         # step spans are live
    try:
        admitted = [eng.admit(ids, 8) for ids in prompts]
    finally:
        jax.profiler.stop_trace()
    assert [r.bucket for r in admitted] == [1536, 2048]
    spans = [s.attrs for s in get_tracer().spans("engine.admit")
             if s.attrs.get("prompt_tokens") in (1100, 1600)][-2:]
    assert [(s["bucket"], s["padding_rows"]) for s in spans] == \
        [(1536, 436), (2048, 448)]
    rows = get_registry().counter("llm_prefill_rows_total", "",
                                  ("engine", "rows"))
    assert rows.value(engine=name, rows="real") == 2700
    assert rows.value(engine=name, rows="padding") == 436 + 448
    out = eng.run_to_completion()
    for ids, res in zip(prompts, admitted):
        want = generate(model, variables, ids[None], max_new_tokens=8)[0]
        np.testing.assert_array_equal(out[res.slot], want)


@pytest.fixture
def described(monkeypatch):
    """``config name -> SlotEngine`` at the configuration's published widths
    and ``max_len`` with a cache of shapes alone and no weights: what the
    engine decides from shapes, without a byte of the model."""
    monkeypatch.setattr(S, "init_cache", lambda cfg, b, n: jax.eval_shape(
        lambda: init_cache(cfg, b, n)))

    def build(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        model = build_model(config)
        return model, SlotEngine(
            model, {}, n_slots=1, max_len=config["engine"]["max_len"],
            attention_backend="interpret", min_bucket=8,
            name=f"t-lattice-{name}")
    return build


@pytest.mark.parametrize("config,new,neighbours", [
    ("mistral-7b-v0.3-l16", 1536, (1024, 2048)),
    ("mimo-v2.5-l7-e16", 12288, (8192, 16384))])
def test_the_new_bucket_runs_as_its_power_of_two_neighbours(
        described, config, new, neighbours):
    model, eng = described(config)
    assert new in eng._buckets

    def tiles(pb):
        path, kinds = eng._prefill_plan(pb)
        return path, [(kc.kind, geo.bq, geo.bk, geo.key_steps)
                      for kc, geo in kinds]
    path, got = tiles(new)
    assert path == "tiled"
    # the bucket is whole query blocks of each kind
    assert all(new % bq == 0 for _, bq, _, _ in got)
    for pb in neighbours:
        assert tiles(pb) == (path, got), pb
        # the projections are cut or kept as the neighbours' are
        assert M.projection_layout(model.cfg, new) == \
            M.projection_layout(model.cfg, pb)
