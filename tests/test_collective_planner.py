"""Topology-aware collective planner (parallel/planner.py, ISSUE 14).

Pins the full routing contract: topology-snapshot honesty (coords/slice
``None`` fallback, no fabricated structure), the ring/tree/hierarchical
decision table over payload bytes × world size × link class, the
size-bucketed plan cache, numerical parity of every route against the
flat dispatch (hierarchical ≡ flat within 2e-5 at f32), the jaxpr-level
``strategy='flat'`` byte-identity pin, the per-leaf error-feedback
invariant under hierarchical routing, strategy-labeled wire accounting +
StepProfiler segment split, checkpoint refusal across a routing switch
(the codec-toggle guard's sibling), placement strategies, and the
GangSupervisor resize → re-plan pin via call-log/flight events.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from synapseml_tpu.parallel import (CollectiveConfig, CollectivePlanner,
                                    DATA_AXIS, TopologySpec,
                                    data_parallel_mesh, get_planner,
                                    get_topology, partition_assignment,
                                    place_partitions, planned_psum,
                                    set_planner)
from synapseml_tpu.parallel.compression import compressed_psum
from synapseml_tpu.parallel.planner import (PLANNER_METRICS,
                                            TREE_CUTOFF_BYTES, _decide)
from synapseml_tpu.telemetry import get_registry

pytestmark = pytest.mark.topo

#: the synthetic 2-host topology the CPU-container legs route on —
#: injected, never discovered (the container has no coords to discover)
SPEC_2X4 = TopologySpec(n_hosts=2, devices_per_host=4)


@pytest.fixture
def planner():
    """A fresh planner with the synthetic 2×4 spec injected, installed
    as the process planner for the test and ALWAYS restored after — a
    leaked injected spec would silently re-route every other suite's
    collectives."""
    fresh = CollectivePlanner(spec=SPEC_2X4)
    prev = set_planner(fresh)
    try:
        yield fresh
    finally:
        set_planner(prev)


@pytest.fixture
def bare_planner():
    """A fresh planner with NO injected spec (discovery on this CPU
    container yields an untrusted snapshot — the unknown-topology
    honesty leg)."""
    fresh = CollectivePlanner()
    prev = set_planner(fresh)
    try:
        yield fresh
    finally:
        set_planner(prev)


# ---------------------------------------------------------------------------
# topology snapshot honesty (satellite: coords/slice_index None fallback)
# ---------------------------------------------------------------------------

class TestTopologySnapshot:
    def test_cpu_snapshot_has_none_coords_not_fabricated(self):
        """The CPU container's devices expose no mesh coords or slice
        index: the snapshot must carry explicit Nones (per device, in
        device order), never a made-up grid — the PR 9/11 spec-table
        honesty pattern."""
        topo = get_topology()
        assert len(topo.coords) == topo.num_devices
        assert len(topo.slice_indices) == topo.num_devices
        assert all(c is None for c in topo.coords)
        assert topo.coords_known is False
        assert topo.num_slices() is None

    def test_discovered_spec_is_untrusted_on_cpu(self, bare_planner):
        spec = bare_planner.spec()
        assert spec is not None and spec.source == "discovered"
        assert spec.trusted is False          # no coords → never routes
        # and the ICI table has no CPU entry: link class stays unknown
        assert spec.ici_bytes_per_s is None

    def test_injected_spec_is_trusted_and_validated(self):
        assert SPEC_2X4.trusted and SPEC_2X4.multi_host
        assert SPEC_2X4.world == 8
        with pytest.raises(ValueError, match="n_hosts"):
            TopologySpec(n_hosts=0)


# ---------------------------------------------------------------------------
# the decision table
# ---------------------------------------------------------------------------

SMALL = 8 << 10            # 8 KiB — latency-bound class
LARGE = 8 << 20            # 8 MiB — bandwidth-bound class


class TestDecisionTable:
    def test_small_payload_routes_tree(self, planner):
        cfg = CollectiveConfig(strategy="auto", manual=True)
        plan = planner.plan(SMALL, 8, cfg)
        assert (plan.strategy, plan.reason) == ("tree", "latency_bound")

    def test_large_payload_single_host_routes_ring(self):
        single = CollectivePlanner(
            spec=TopologySpec(n_hosts=1, devices_per_host=8))
        cfg = CollectiveConfig(strategy="auto", manual=True)
        plan = single.plan(LARGE, 8, cfg)
        assert (plan.strategy, plan.reason) == ("ring", "bandwidth_bound")

    def test_multi_host_codec_routes_hierarchical(self, planner):
        cfg = CollectiveConfig(compression="int8", strategy="auto")
        plan = planner.plan(LARGE, 8, cfg)
        assert (plan.strategy, plan.reason) == ("hierarchical",
                                                "multi_host_codec")
        assert plan.inner == 4 and plan.outer == 2

    def test_multi_host_uncompressed_still_goes_two_level(self, planner):
        cfg = CollectiveConfig(strategy="auto", manual=True)
        plan = planner.plan(LARGE, 8, cfg)
        assert (plan.strategy, plan.reason) == ("hierarchical",
                                                "multi_host")

    def test_unknown_topology_plans_flat(self, bare_planner):
        """The honesty rule: 'auto' with no trusted topology must trace
        exactly the pre-planner dispatch."""
        cfg = CollectiveConfig(compression="int8", strategy="auto")
        plan = bare_planner.plan(LARGE, 8, cfg)
        assert (plan.strategy, plan.reason) == ("flat", "unknown_topology")

    def test_single_rank_and_forced_flat(self, planner):
        cfg = CollectiveConfig(compression="int8", strategy="auto")
        assert planner.plan(LARGE, 1, cfg).strategy == "flat"
        flat = CollectiveConfig(compression="int8", strategy="flat")
        assert planner.plan(LARGE, 8, flat).reason == "forced"

    def test_structural_fallbacks(self, planner):
        tree = CollectiveConfig(strategy="tree", manual=True)
        assert planner.plan(SMALL, 6, tree).strategy == "flat"   # non-pow2
        assert planner.plan(SMALL, 6, tree).reason == "non_pow2_world"
        hier = CollectiveConfig(strategy="hierarchical", manual=True)
        # a 4-rank axis under the 2x4 spec never leaves host 0
        assert planner.plan(LARGE, 4, hier).reason == "indivisible_world"

    def test_bad_strategy_fails_fast_at_config(self):
        with pytest.raises(ValueError, match="strategy"):
            CollectiveConfig(strategy="spanning_tree")

    def test_plan_cache_bucketed_and_counted(self, planner):
        cfg = CollectiveConfig(compression="int8", strategy="auto")
        c = get_registry().get("collective_plans_total")
        before = c.value(strategy="hierarchical", reason="multi_host_codec",
                         model="spec")
        p1 = planner.plan(LARGE - 100, 8, cfg)
        p2 = planner.plan(LARGE, 8, cfg)            # same pow2 bucket
        assert p1 is p2
        assert planner.cache_size() >= 1
        after = c.value(strategy="hierarchical", reason="multi_host_codec",
                        model="spec")
        assert after == before + 1                  # one synthesis, one count
        # a different payload class is a different plan
        p3 = planner.plan(SMALL, 8, cfg)
        assert p3 is not p1 and p3.strategy == "tree"

    def test_decision_fn_rejects_unknown_strategy(self):
        class Fake:
            strategy = "gossip"
            compresses = False
        with pytest.raises(ValueError, match="gossip"):
            _decide(LARGE, 8, SPEC_2X4, Fake())

    def test_tree_cutoff_is_the_documented_boundary(self, planner):
        cfg = CollectiveConfig(strategy="auto", manual=True)
        at = planner.plan(TREE_CUTOFF_BYTES, 8, cfg)
        above = planner.plan(2 * TREE_CUTOFF_BYTES + 1, 8, cfg)
        assert at.strategy == "tree" and above.strategy != "tree"


# ---------------------------------------------------------------------------
# execution: parity vs flat, jaxpr pin, wire accounting
# ---------------------------------------------------------------------------

def _routed_psum(mesh, cfg, x, op="topo_test"):
    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(DATA_AXIS),
                       out_specs=P(), check_vma=False)
    def f(v):
        return planned_psum(v.sum(0), DATA_AXIS, cfg, op=op)
    return np.asarray(f(x))


class TestExecutionParity:
    @pytest.mark.parametrize("strategy", ["ring", "tree", "hierarchical"])
    def test_f32_route_matches_flat_within_2e5(self, planner, strategy):
        """The acceptance bound: every route is the same sum, within
        reassociation (2e-5 relative) of the flat psum."""
        mesh = data_parallel_mesh(8)
        rng = np.random.default_rng(3)
        x = rng.normal(size=(8, 100_000)).astype(np.float32)
        cfg = CollectiveConfig(strategy=strategy, manual=True)
        out = _routed_psum(mesh, cfg, x)
        ref = _routed_psum(mesh, None, x)
        scale = np.abs(ref).max()
        assert np.abs(out - ref).max() <= 2e-5 * scale, strategy

    def test_hierarchical_int8_parity_with_flat_int8(self, planner):
        """Same codec both sides — only the route differs.  Hierarchical
        quantizes intra-host SUMS (2 quantization events per value
        instead of 8), so its error is bounded by the flat leg's."""
        mesh = data_parallel_mesh(8)
        rng = np.random.default_rng(4)
        x = rng.normal(size=(8, 65536)).astype(np.float32)
        exact = x.sum(0)
        step = np.abs(x).max() / 127.0
        flat = _routed_psum(mesh, CollectiveConfig(
            compression="int8", strategy="flat", min_size=64), x)
        hier = _routed_psum(mesh, CollectiveConfig(
            compression="int8", strategy="hierarchical", min_size=64), x)
        # both are the quantized sum within the codec's error budget
        assert np.abs(flat - exact).max() <= 8 * step
        assert np.abs(hier - exact).max() <= 8 * step
        # routing changed the error pattern, not the quantity
        assert np.abs(hier - flat).max() <= 16 * step

    def test_hierarchical_channel_major_protects_small_channels(
            self, planner):
        """The GBDT histogram shape (…, grad/hess/count): counts ~1e4×
        the gradients must not flatten the gradient channel's scale on
        the hierarchical inter-host leg either."""
        mesh = data_parallel_mesh(8)
        rng = np.random.default_rng(5)
        n = 1931                                   # non-chunk-multiple
        hist = np.stack([rng.normal(size=(8, n)) * 1e-2,
                         np.abs(rng.normal(size=(8, n))) * 1e-2,
                         rng.integers(100, 20000, (8, n)).astype(float)],
                        axis=-1).astype(np.float32)
        cfg = CollectiveConfig(compression="int8",
                               strategy="hierarchical", min_size=64)

        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P(DATA_AXIS), out_specs=P(),
                           check_vma=False)
        def f(v):
            return planned_psum(v[0], DATA_AXIS, cfg, op="topo_hist")
        out = np.asarray(f(hist))
        ref = hist.sum(0)
        for ch in (0, 1):
            err = np.abs(out[..., ch] - ref[..., ch]).max()
            assert err < np.abs(ref[..., ch]).max() * 0.02, (ch, err)

    def test_flat_strategy_jaxpr_byte_identical(self, planner):
        """The acceptance pin: strategy='flat' (and config=None) trace
        EXACTLY the pre-planner dispatch — compared at the jaxpr level
        against a direct compressed_psum of the same config."""
        mesh = data_parallel_mesh(8)
        x = np.zeros((8, 4096), np.float32)

        def jaxpr(fn):
            return str(jax.make_jaxpr(jax.shard_map(
                fn, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
                check_vma=False))(x))

        for cfg in (None,
                    CollectiveConfig(compression="none", strategy="flat"),
                    CollectiveConfig(compression="int8", strategy="flat",
                                     min_size=64),
                    CollectiveConfig(compression="bf16", strategy="flat",
                                     min_size=64)):
            planned = jaxpr(lambda v: planned_psum(v.sum(0), DATA_AXIS,
                                                   cfg, op="t"))
            legacy = jaxpr(lambda v: compressed_psum(v.sum(0), DATA_AXIS,
                                                     cfg, op="t"))
            assert planned == legacy, cfg

    def test_auto_on_unknown_topology_jaxpr_identical(self, bare_planner):
        """'auto' with no trusted topology is the flat jaxpr too — the
        default path's byte-identity does not depend on the strategy
        field staying 'flat'."""
        mesh = data_parallel_mesh(8)
        x = np.zeros((8, 4096), np.float32)
        auto = CollectiveConfig(compression="int8", strategy="auto",
                                min_size=64)
        flat = CollectiveConfig(compression="int8", strategy="flat",
                                min_size=64)

        def jaxpr(cfg):
            return str(jax.make_jaxpr(jax.shard_map(
                lambda v: planned_psum(v.sum(0), DATA_AXIS, cfg, op="t"),
                mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(),
                check_vma=False))(x))
        assert jaxpr(auto) == jaxpr(flat)

    def test_wire_bytes_labeled_by_strategy(self, planner):
        """Every routed dispatch lands a strategy-labeled wire series —
        including uncompressed routes (wire == logical, codec='none')."""
        mesh = data_parallel_mesh(8)
        rng = np.random.default_rng(6)
        x = rng.normal(size=(8, 65536)).astype(np.float32)
        reg = get_registry()
        _routed_psum(mesh, CollectiveConfig(
            compression="int8", strategy="hierarchical", min_size=64), x,
            op="topo_wire")
        _routed_psum(mesh, CollectiveConfig(strategy="ring", manual=True),
                     x, op="topo_wire")
        m = reg.get("collective_wire_bytes_total")
        hier = m.value(op="topo_wire", axis=DATA_AXIS, codec="int8",
                       strategy="hierarchical")
        ring = m.value(op="topo_wire", axis=DATA_AXIS, codec="none",
                       strategy="ring")
        assert hier > 0
        assert ring == 65536 * 4              # f32 route: wire == logical

    def test_plan_decision_lands_in_flight_ring(self, planner):
        from synapseml_tpu.telemetry.flight import get_flight
        mesh = data_parallel_mesh(8)
        x = np.zeros((8, 300_000), np.float32)    # 1.2 MB: codec class
        cfg = CollectiveConfig(compression="int8", strategy="auto",
                               min_size=64)
        _routed_psum(mesh, cfg, x, op="topo_flight")
        evs = [e for e in get_flight().events()
               if e.get("kind") == "plan_decide"
               and e.get("op") == "topo_flight"]
        assert evs, "plan decision not flight-recorded"
        assert evs[-1]["strategy"] == "hierarchical"
        assert evs[-1]["world"] == 8 and evs[-1]["inner"] == 4

    def test_profiler_collective_segment_split_by_strategy(self, planner):
        """The StepProfiler satellite: the host-dispatched allreduce
        attributes its collective-segment seconds to the planned
        strategy, so flat-vs-planned bench pairs isolate routing."""
        from synapseml_tpu.parallel import allreduce_fn
        from synapseml_tpu.telemetry.gangplane import StepProfiler
        mesh = data_parallel_mesh(8)
        x = jnp.asarray(np.random.default_rng(7).normal(
            size=(8, 300_000)).astype(np.float32))   # past the tree cutoff
        fn_flat = allreduce_fn(mesh, config=CollectiveConfig(
            compression="int8", strategy="flat", min_size=64))
        fn_auto = allreduce_fn(mesh, config=CollectiveConfig(
            compression="int8", strategy="auto", min_size=64))
        prof = StepProfiler("topo_prof")
        with prof.step(0):
            np.asarray(fn_flat(x))
            np.asarray(fn_auto(x))
        s = prof.summary()["collective_seconds_by_strategy"]
        assert s.get("flat", 0) > 0 and s.get("hierarchical", 0) > 0

    def test_timeout_payload_names_route_phases(self, planner):
        """The allreduce_fn satellite: a watchdogged planned dispatch
        that times out names the strategy and its wire phases instead
        of one opaque op name."""
        from synapseml_tpu.parallel.collectives import (CollectiveTimeout,
                                                        dispatch_watchdog)
        plan = planner.plan(LARGE, 8, CollectiveConfig(
            compression="int8", strategy="hierarchical"))
        phases = plan.phases("int8")
        assert phases == ("intra_reduce_scatter@f32",
                          "inter_allreduce@int8", "intra_all_gather@f32")
        import threading
        hang = threading.Event()
        with pytest.raises(CollectiveTimeout) as ei:
            dispatch_watchdog(hang.wait, op="allreduce_fn",
                              axis=DATA_AXIS, timeout_s=0.05,
                              payload_bytes=123, codec="int8",
                              logical_bytes=456,
                              strategy="hierarchical", phases=phases)
        hang.set()
        err = ei.value
        assert err.strategy == "hierarchical"
        assert err.phases == phases
        assert "inter_allreduce@int8" in str(err)


# ---------------------------------------------------------------------------
# error feedback under hierarchical routing
# ---------------------------------------------------------------------------

class TestHierarchicalErrorFeedback:
    def test_ef_invariant_sum_of_residuals_is_total_error(self, planner):
        """The EF contract under routing: each rank keeps the error of
        the intra-host shard it owned on the quantized inter-host leg,
        so sum_r(residual_r) == sum_r(g_r) - reduced_total exactly (to
        f32 epsilon) — the same invariant the flat codec carries and
        the elastic resize re-sharding relies on."""
        from synapseml_tpu.parallel.compression import compressed_tree_sync
        mesh = data_parallel_mesh(8)
        cfg = CollectiveConfig(compression="int8",
                               strategy="hierarchical",
                               error_feedback=True, min_size=64)
        rng = np.random.default_rng(8)
        g = rng.normal(size=(8, 4096)).astype(np.float32)

        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                           out_specs=(P(), P(DATA_AXIS)), check_vma=False)
        def sync(gv, res):
            red, nres = compressed_tree_sync({"w": gv[0]}, DATA_AXIS, cfg,
                                             residuals={"w": res},
                                             mean=True)
            return red["w"], nres["w"]

        red, nres = sync(g, np.zeros((8, 1, 4096), np.float32))
        red, nres = np.asarray(red), np.asarray(nres)
        lhs = g.sum(0)
        rhs = red * 8 + nres.reshape(8, 4096).sum(0)
        step = np.abs(g).max() / 127.0
        assert np.abs(lhs - rhs).max() < 1e-5
        # each rank owns exactly its 1/inner shard of the error
        nonzero = [(np.abs(nres[r, 0]) > 0).sum() for r in range(8)]
        assert all(nz <= 4096 // 4 for nz in nonzero)
        # and the error really is quantization-sized, not structural
        assert np.abs(nres).max() <= step + 1e-6

    def test_routed_sync_tracks_flat_sync_descent(self, planner):
        """Six manual-DP steps, hierarchical-int8 vs flat-int8 vs f32:
        the routed sync is the same training trajectory within
        quantization tolerance (the DL/GBDT holdout-parity class)."""
        import tests.test_collectives_compression as tc
        flat = CollectiveConfig(compression="int8", error_feedback=True,
                                min_size=64, strategy="flat")
        hier = CollectiveConfig(compression="int8", error_feedback=True,
                                min_size=64, strategy="hierarchical")
        _, s_f, _, m_f = tc._run_trainer(flat, steps=6, devices=8)
        _, s_h, _, m_h = tc._run_trainer(hier, steps=6, devices=8)
        _, s_b, _, m_b = tc._run_trainer(None, steps=6, devices=8)
        assert abs(m_h["loss"] - m_b["loss"]) < 0.05
        assert abs(m_h["loss"] - m_f["loss"]) < 0.02
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(
                            s_h.params)),
                        jax.tree_util.tree_leaves(jax.device_get(
                            s_f.params))):
            assert np.abs(np.asarray(a, np.float32)
                          - np.asarray(b, np.float32)).max() < 0.1


class TestGBDTHierarchicalParity:
    def test_gbdt_hierarchical_int8_holds_holdout_auc(self, planner):
        """The PR 6 GBDT parity pin re-run with the route changed:
        hierarchical-int8 histogram psums grow trees whose holdout AUC
        matches the flat-int8 AND the f32 fits within the codec
        tolerance."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        from synapseml_tpu.models.gbdt.metrics import auc
        rng = np.random.default_rng(11)
        X = rng.normal(size=(4000, 8)).astype(np.float32)
        y = (X[:, 0] * 2 - X[:, 1] + X[:, 2] * X[:, 3]
             + rng.normal(scale=0.5, size=4000) > 0).astype(np.float64)
        mesh = data_parallel_mesh(8)

        def fit(cc):
            b, _ = train(X, y, BoostingConfig(
                objective="binary", num_iterations=5, num_leaves=15,
                max_bin=63, collective_compression=cc), mesh=mesh)
            return auc(y, b.predict_margin(X))

        a_f32 = fit("none")
        a_flat = fit(CollectiveConfig(compression="int8", min_size=512,
                                      strategy="flat"))
        a_hier = fit(CollectiveConfig(compression="int8", min_size=512,
                                      strategy="hierarchical"))
        assert abs(a_hier - a_flat) <= 0.01, (a_hier, a_flat)
        assert abs(a_hier - a_f32) <= 0.01, (a_hier, a_f32)


# ---------------------------------------------------------------------------
# checkpoint guard: a routing switch refuses loudly
# ---------------------------------------------------------------------------

class TestRoutingCheckpointGuard:
    def test_gbdt_routing_switch_refuses_resume(self, planner, tmp_path):
        """The codec-toggle guard's sibling: remaining trees must not
        grow on a differently-routed histogram wire than the carried
        ones — hierarchical quantizes intra-host sums, flat per-rank
        payloads."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        rng = np.random.default_rng(9)
        X = rng.normal(size=(2000, 8)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
        mesh = data_parallel_mesh(8)
        ck = str(tmp_path / "ck")

        def cfg(strategy, iters):
            return BoostingConfig(
                objective="binary", num_iterations=iters, num_leaves=15,
                max_bin=63, collective_compression=CollectiveConfig(
                    compression="int8", min_size=512, strategy=strategy))

        train(X, y, cfg("hierarchical", 3), mesh=mesh,
              checkpoint_dir=ck, checkpoint_interval=1)
        with pytest.raises(ValueError, match="collective_compression"):
            train(X, y, cfg("flat", 6), mesh=mesh,
                  checkpoint_dir=ck, checkpoint_interval=1)
        # the same routing resumes freely (and bit-exactly, per the
        # PR 6 resume pins this guard composes with)
        resumed, _ = train(X, y, cfg("hierarchical", 6), mesh=mesh,
                           checkpoint_dir=ck, checkpoint_interval=1)
        assert resumed.num_trees == 6

    def test_gbdt_pre_planner_checkpoint_resumes_under_auto(
            self, bare_planner, tmp_path):
        """A checkpoint written with no strategy key (or strategy
        'flat') must resume under the DEFAULT 'auto' config wherever
        topology is unknown — 'auto' resolves flat there, so the
        effective wire key is unchanged."""
        from synapseml_tpu.models.gbdt import BoostingConfig, train
        rng = np.random.default_rng(10)
        X = rng.normal(size=(2000, 8)).astype(np.float32)
        y = (X[:, 0] - X[:, 1] > 0).astype(np.float64)
        mesh = data_parallel_mesh(8)
        ck = str(tmp_path / "ck")

        def cfg(strategy, iters):
            return BoostingConfig(
                objective="binary", num_iterations=iters, num_leaves=15,
                max_bin=63, collective_compression=CollectiveConfig(
                    compression="int8", min_size=512, strategy=strategy))
        train(X, y, cfg("flat", 3), mesh=mesh,
              checkpoint_dir=ck, checkpoint_interval=1)
        resumed, _ = train(X, y, cfg("auto", 6), mesh=mesh,
                           checkpoint_dir=ck, checkpoint_interval=1)
        assert resumed.num_trees == 6

    def test_dl_guard_encodes_resolved_routing(self, planner):
        """The DL checkpoint guard's 'routing' key is the RESOLVED
        route class: 0.0 (flat) for strategy='flat' AND for 'auto' on
        unknown topology — so pre-planner checkpoints resume under
        default configs — and a distinct code per explicit strategy."""
        from synapseml_tpu.parallel.planner import STRATEGIES
        pl = get_planner()
        flat = CollectiveConfig(compression="int8", strategy="flat")
        hier = CollectiveConfig(compression="int8",
                                strategy="hierarchical")
        auto = CollectiveConfig(compression="int8", strategy="auto")
        assert pl.resolved_routing(None) == "flat"
        assert pl.resolved_routing(flat) == "flat"
        assert pl.resolved_routing(hier) == "hierarchical"
        # trusted injected spec: auto is a live routing policy
        assert pl.resolved_routing(auto) == "auto"
        bare = CollectivePlanner()
        assert bare.resolved_routing(auto) == "flat"
        assert "auto" in STRATEGIES and STRATEGIES.index("auto") == 0

    def test_resolved_routing_tracks_structural_fallback(self, planner):
        """The guard key must stamp the route the sync ACTUALLY ran,
        not the one requested: an explicit 'hierarchical' with no
        trusted topology, or 'tree' on a non-pow2 world, synced flat
        (`_decide` fallback) — stamping the requested name would let a
        later resume on a coords-exposing cluster (or a pow2 resize)
        silently switch numerics past the refusal guard."""
        hier = CollectiveConfig(compression="int8",
                                strategy="hierarchical")
        tree = CollectiveConfig(strategy="tree")
        bare = CollectivePlanner()
        # unknown topology: a hierarchical request really syncs flat
        assert bare.resolved_routing(hier) == "flat"
        pl = get_planner()
        # trusted 2x4 spec but an indivisible/undersized world
        assert pl.resolved_routing(hier, world=6) == "flat"
        assert pl.resolved_routing(hier, world=8) == "hierarchical"
        # tree structurally requires a pow2 world
        assert pl.resolved_routing(tree, world=6) == "flat"
        assert pl.resolved_routing(tree, world=8) == "tree"
        # world 1 is always the flat dispatch, whatever was requested
        assert pl.resolved_routing(hier, world=1) == "flat"


# ---------------------------------------------------------------------------
# supervisor: resize → re-plan (the PR 7 hook)
# ---------------------------------------------------------------------------

class TestSupervisorReplan:
    def test_resize_invalidates_and_rebuilds_plan_cache(
            self, planner, fault_registry):
        """The acceptance pin: a GangSupervisor resize drops every
        cached plan, notes 'plan.refresh' with the NEW world size in
        the fault call log, flight-records 'plan_invalidate', and the
        next plan rebuilds at the new world size."""
        from synapseml_tpu.parallel import GangSupervisor
        from synapseml_tpu.telemetry.flight import get_flight
        fault_registry.record_calls = True
        cfg = CollectiveConfig(compression="int8", strategy="auto")
        seeded = planner.plan(LARGE, 8, cfg)
        assert seeded.strategy == "hierarchical"
        assert planner.cache_size() >= 1
        epoch0 = planner.epoch()

        sup = GangSupervisor("mp_tasks:noop", n_processes=2,
                             devices_per_process=1,
                             heartbeat_interval_s=0.0)
        sup.resize(1)
        sup._plan_before_launch(0)          # the attempt-boundary hook
        assert sup.world_size == 1

        assert planner.cache_size() == 0, "resize left stale plans"
        assert planner.epoch() > epoch0
        notes = [ctx for site, ctx in fault_registry.call_log
                 if site == "plan.refresh"]
        assert notes and notes[-1]["world_size"] == 1
        assert notes[-1]["reason"] == "resize_shrink"
        evs = [e for e in get_flight().events()
               if e.get("kind") == "plan_invalidate"]
        assert evs and evs[-1]["world_size"] == 1
        # rebuild at the new world: one rank → flat, freshly synthesized
        rebuilt = planner.plan(LARGE, 1, cfg)
        assert rebuilt.strategy == "flat" and rebuilt is not seeded

    def test_refresh_keeps_injected_spec_drops_discovered(self, planner):
        planner.refresh("unit", world_size=4)
        assert planner.spec() is SPEC_2X4       # injected spec survives
        bare = CollectivePlanner()
        s1 = bare.spec()
        bare.refresh("unit")
        s2 = bare.spec()
        assert s1 is not None and s2 is not None and s2 is not s1


# ---------------------------------------------------------------------------
# placement satellite
# ---------------------------------------------------------------------------

class TestPlacementStrategies:
    def test_block_matches_historical_behavior(self):
        mesh = data_parallel_mesh(4)
        pm = place_partitions(10, mesh)
        assert pm.rank_to_partitions[0] == [0, 1, 2]    # remainder first
        assert pm.rank_to_partitions[3] == [8, 9]
        # contiguity: the rows_for_rank contract
        for r in range(4):
            parts = pm.rank_to_partitions[r]
            assert parts == list(range(parts[0], parts[-1] + 1))

    def test_round_robin_interleaves(self):
        mesh = data_parallel_mesh(4)
        pm = place_partitions(10, mesh, strategy="round_robin")
        assert pm.rank_to_partitions[0] == [0, 4, 8]
        assert pm.rank_to_partitions[1] == [1, 5, 9]
        assert sorted(pm.partition_to_rank) == list(range(10))
        with pytest.raises(ValueError, match="strategy"):
            place_partitions(10, mesh, strategy="shuffled")

    def test_planner_groups_ride_partition_assignment(self, planner):
        """The hierarchical intra-host grouping is the block placement
        of ranks onto hosts — one assignment core for both."""
        plan = planner.plan(LARGE, 8, CollectiveConfig(
            compression="int8", strategy="hierarchical"))
        intra, inter = plan._groups()
        pm = partition_assignment(8, 2, strategy="block")
        assert intra == [pm.rank_to_partitions[0], pm.rank_to_partitions[1]]
        assert inter == [[0, 4], [1, 5], [2, 6], [3, 7]]


# ---------------------------------------------------------------------------
# metric hygiene: planner names documented
# ---------------------------------------------------------------------------

class TestPlannerMetricsDocumented:
    def test_planner_metrics_in_docs(self):
        """PLANNER_METRICS held to the GANG_METRICS docs bar, plus the
        strategy label on the wire series."""
        import pathlib
        repo = pathlib.Path(__file__).resolve().parent.parent
        docs = "\n".join(p.read_text(encoding="utf-8")
                         for p in (repo / "docs" / "api").glob("*.md"))
        missing = sorted(n for n in PLANNER_METRICS if n not in docs)
        assert not missing, f"planner metrics absent from docs: {missing}"
        assert "collective_wire_bytes_total{op,axis,codec,strategy}" in docs
