"""Sharded serving plane (engine/sharded/) + per-decision router
(sched/router.py).

Spec/geometry/router tests are pure host logic (fast tier). The engine
tests run on a micro real model over the virtual 8-device CPU mesh
(conftest forces XLA_FLAGS=--xla_force_host_platform_device_count=8):

- param_specs / serving_param_specs / validate_specs_divisibility at the
  FLAGSHIP 70B geometry for tp=2/4/8 — the spec family the north star
  serves under — plus the non-divisible failure path;
- the ragged/tp seam: decode_matmul='ragged' on a tp>1 mesh must refuse
  LOUDLY at build time (the pallas kernel cannot be partitioned by
  GSPMD; silently serving dense under a 'ragged' label poisoned a bench
  round once already);
- THE acceptance pin: greedy decisions on a tp=2 mesh are token-identical
  to tp=1, through packed admission and fused decode (slow tier — two
  engines compile).
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from k8s_llm_scheduler_tpu.engine.sharded import (
    FleetGeometry,
    ServingPlane,
    build_plane,
    member_tp,
    serving_param_specs,
)
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig, get_config
from k8s_llm_scheduler_tpu.parallel.mesh import make_mesh
from k8s_llm_scheduler_tpu.parallel.sharding import (
    param_specs,
    validate_specs_divisibility,
)
from k8s_llm_scheduler_tpu.types import NodeMetrics, PodSpec

CFG_70B = get_config("llama-3.3-70b-instruct")


def make_node(name="node-1", labels=None, taints=()):
    return NodeMetrics(
        name=name,
        cpu_usage_percent=30.0,
        memory_usage_percent=40.0,
        available_cpu_cores=8.0,
        available_memory_gb=32.0,
        pod_count=10,
        max_pods=110,
        labels=labels or {},
        taints=taints,
        conditions={"Ready": "True"},
    )


def make_pod(name="pod-1", node_selector=None, tolerations=(), priority=0,
             affinity_rules=None):
    return PodSpec(
        name=name,
        namespace="default",
        cpu_request=0.1,
        memory_request=0.125,
        node_selector=node_selector or {},
        tolerations=tolerations,
        affinity_rules=affinity_rules or {},
        priority=priority,
    )


# ------------------------------------------------------- 70B spec geometry
class TestSpecs70B:
    @pytest.mark.parametrize("tp", [2, 4, 8])
    def test_divisibility_and_specs_at_70b(self, tp):
        """The flagship geometry divides cleanly at every serving tp and
        the spec tree matches the init_params structure leaf for leaf."""
        mesh = make_mesh({"tp": tp})
        validate_specs_divisibility(CFG_70B, mesh)
        specs = param_specs(CFG_70B, tp="tp")
        assert specs["embed"] == P("tp", None)
        layers = specs["layers"]
        for col in ("wq", "wk", "wv", "w_gate", "w_up"):
            assert layers[col] == P(None, None, "tp"), col
        for row in ("wo", "w_down"):
            assert layers[row] == P(None, "tp", None), row
        for norm in ("attn_norm", "mlp_norm"):
            assert layers[norm] == P(None, None)
        # per-device kv heads stay whole (the paged cache shards axis 3)
        assert CFG_70B.n_kv_heads % tp == 0

    @pytest.mark.parametrize("tp", [2, 4, 8])
    def test_serving_specs_cover_quantized_leaves(self, tp):
        """int8 serving trees carry {"q","scale"} per projection: q keeps
        the weight spec, scale drops the contracted dim (it broadcasts
        over it) but keeps the output-dim sharding."""
        specs = serving_param_specs(CFG_70B, quantized=True)
        layers = specs["layers"]
        for name in ("wq", "wk", "wv", "w_gate", "w_up"):
            assert layers[name]["q"] == P(None, None, "tp"), name
            assert layers[name]["scale"] == P(None, None, "tp"), name
        for name in ("wo", "w_down"):
            assert layers[name]["q"] == P(None, "tp", None), name
            # row-parallel: output dim is unsharded, so scale replicates
            assert layers[name]["scale"] == P(None, None, None), name
        # norms/embed are not quantized — plain specs pass through
        assert layers["attn_norm"] == P(None, None)
        assert specs["embed"] == P("tp", None)

    def test_non_divisible_heads_refused(self):
        """kv heads not divisible by tp must fail loudly up front, not
        pad silently inside GSPMD."""
        bad = LlamaConfig(
            name="bad-kv", vocab_size=512, d_model=96, n_layers=2,
            n_heads=6, n_kv_heads=3, d_ff=128, max_seq_len=512,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        mesh = make_mesh({"tp": 2})
        with pytest.raises(ValueError, match="n_kv_heads=3"):
            validate_specs_divisibility(bad, mesh)


# ----------------------------------------------------------- serving plane
class TestServingPlane:
    def test_build_plane_off_mesh_and_tp1(self):
        assert build_plane(None) is None
        assert build_plane(make_mesh({"tp": 1})) is None

    def test_plane_specs(self):
        mesh = make_mesh({"tp": 2})
        plane = build_plane(mesh)
        assert isinstance(plane, ServingPlane)
        assert plane.kv_pages.spec == P(None, None, None, "tp", None)
        assert plane.prefix_kv.spec == P(None, None, "tp", None)
        assert plane.logits.spec == P(None, "tp")
        assert plane.replicated.spec == P()

    def test_place_kv_lands_sharded(self):
        mesh = make_mesh({"tp": 2})
        plane = build_plane(mesh)
        pages = jnp.zeros((2, 8, 4, 2, 16), jnp.float32)
        placed = plane.place_kv(pages)
        assert placed.sharding.spec == P(None, None, None, "tp", None)

    def test_engine_shardings_hashable(self):
        """The shardings bundle rides through functools.partial into
        jitted impls — it must hash (jit treats partial kwargs as part
        of the callable identity)."""
        plane = build_plane(make_mesh({"tp": 2}))
        sh = plane.engine_shardings()
        assert hash(sh) == hash(plane.engine_shardings())


# ---------------------------------------------------------- fleet geometry
class _Member:
    def __init__(self, tp=None):
        if tp is not None:
            self.slice_tp = tp


class TestFleetGeometry:
    def test_member_tp_resolution(self):
        assert member_tp(_Member(8)) == 8
        assert member_tp(_Member()) == 1  # no attr, no engine -> 1

    def test_prefill_order_largest_first_stable(self):
        geo = FleetGeometry.of([_Member(2), _Member(8), _Member(2), _Member(4)])
        assert geo.tp_sizes == (2, 8, 2, 4)
        assert geo.total_devices == 16
        assert not geo.uniform
        assert geo.prefill_order() == [1, 3, 0, 2]  # 8, 4, then 2s in order

    def test_split_snaps_to_group_boundaries(self):
        geo = FleetGeometry.of([_Member(2), _Member(8), _Member(2), _Member(4)])
        # half the devices = the tp=8 member alone (8 of 16)
        assert geo.split_for_device_share(0.5) == 1
        # 80% -> 8+4=12 of 16 is the closest boundary
        assert geo.split_for_device_share(0.8) == 2
        # degenerate shares still leave >=1 member per side
        assert geo.split_for_device_share(0.0) == 1
        assert geo.split_for_device_share(1.0) == 3

    def test_uniform_fleet_keeps_roster_order(self):
        geo = FleetGeometry.of([_Member(2), _Member(2), _Member(2)])
        assert geo.uniform
        assert geo.prefill_order() == [0, 1, 2]
        assert geo.split_for_device_share(2 / 3) == 2


# ----------------------------------------------------------------- router
class _Arm:
    """Scripted DecisionBackend arm: returns its tag, or raises."""

    def __init__(self, tag, fail=None):
        self.tag = tag
        self.fail = fail
        self.calls = 0
        self.prewarms = 0

    def get_scheduling_decision(self, pod, nodes):
        self.calls += 1
        if self.fail is not None:
            raise self.fail
        from k8s_llm_scheduler_tpu.types import SchedulingDecision

        return SchedulingDecision(
            selected_node=self.tag, confidence=1.0, reasoning=pod.name,
        )

    def prewarm_prefix(self, nodes):
        self.prewarms += 1

    def close(self):
        pass


class TestRouter:
    def _router(self, big=None, fast=None, **policy_kw):
        from k8s_llm_scheduler_tpu.sched.router import (
            RoutedBackend,
            RouterPolicy,
        )

        return RoutedBackend(
            big or _Arm("big-node"), fast or _Arm("fast-node"),
            RouterPolicy(**policy_kw),
        )

    def test_simple_pod_goes_fast_complex_goes_big(self):
        r = self._router()
        nodes = [make_node()]
        # warm the snapshot so the cold-start rule doesn't mask the
        # complexity rule
        r.prewarm_prefix(nodes)
        assert r.get_scheduling_decision(
            make_pod(), nodes
        ).selected_node == "fast-node"
        complex_pod = make_pod(
            node_selector={"zone": "a"}, priority=10,
        )
        assert r.get_scheduling_decision(
            complex_pod, nodes
        ).selected_node == "big-node"
        stats = r.get_stats()
        assert stats["router"]["routed_fast"] == 1
        assert stats["router"]["routed_big"] == 1
        assert stats["router"]["route_reasons"] == {
            "simple_pod": 1, "constraint_complexity": 1,
        }

    def test_deadline_pressure_routes_fast(self):
        from k8s_llm_scheduler_tpu.sched.deadline import (
            DeadlineBudget,
            running,
        )
        from k8s_llm_scheduler_tpu.sched.router import classify_decision

        r = self._router()
        nodes = [make_node()]
        r.prewarm_prefix(nodes)
        complex_pod = make_pod(node_selector={"zone": "a"}, priority=10)
        # 5ms: under big_min_budget_ms
        with running(DeadlineBudget.start(5.0)):
            arm, reason = classify_decision(
                complex_pod, nodes, policy=r.policy, warm=r._warm
            )
        assert (arm, reason) == ("fast", "deadline_budget")

    def test_cold_snapshot_routes_fast_and_prewarms_big(self):
        big = _Arm("big-node")
        r = self._router(big=big, big_cold_extra_ms=1e9)
        complex_pod = make_pod(node_selector={"zone": "a"}, priority=10)
        nodes = [make_node()]
        # cold snapshot + unmeetable cold-start budget -> fast, with the
        # big arm prewarmed in the background for next time
        d = r.get_scheduling_decision(complex_pod, nodes)
        assert d.selected_node == "fast-node"
        assert big.prewarms == 1
        assert r.get_stats()["router"]["route_reasons"] == {
            "cold_snapshot": 1,
        }
        # snapshot is now warm: the same pod routes big
        d2 = r.get_scheduling_decision(complex_pod, nodes)
        assert d2.selected_node == "big-node"

    def test_failover_on_arm_error_not_on_verdicts(self):
        from k8s_llm_scheduler_tpu.engine.backend import NoFeasibleNodeError

        nodes = [make_node()]
        # big arm down -> complex pod fails over to fast
        r = self._router(big=_Arm("big-node", fail=RuntimeError("down")))
        r.prewarm_prefix(nodes)
        complex_pod = make_pod(node_selector={"zone": "a"}, priority=10)
        assert r.get_scheduling_decision(
            complex_pod, nodes
        ).selected_node == "fast-node"
        assert r.get_stats()["router"]["failovers"] == 1
        # a no-feasible-node VERDICT propagates — the other arm would
        # just re-answer an answered question
        r2 = self._router(
            fast=_Arm("fast-node", fail=NoFeasibleNodeError("none fit"))
        )
        r2.prewarm_prefix(nodes)
        with pytest.raises(NoFeasibleNodeError):
            r2.get_scheduling_decision(make_pod(), nodes)

    def test_batch_splits_by_class_and_reassembles_in_order(self):
        r = self._router()
        nodes = [make_node()]
        r.prewarm_prefix(nodes)
        pods = [
            make_pod("p0"),
            make_pod("p1", node_selector={"zone": "a"}, priority=10),
            make_pod("p2"),
        ]
        out = r.get_scheduling_decisions_batch(pods, nodes)
        assert [d.selected_node for d in out] == [
            "fast-node", "big-node", "fast-node",
        ]
        assert [d.reasoning for d in out] == ["p0", "p1", "p2"]

    def test_async_path_routes_and_fails_over(self):
        r = self._router(big=_Arm("big-node", fail=RuntimeError("down")))
        nodes = [make_node()]
        r.prewarm_prefix(nodes)
        complex_pod = make_pod(node_selector={"zone": "a"}, priority=10)
        d = asyncio.run(r.get_scheduling_decision_async(complex_pod, nodes))
        assert d.selected_node == "fast-node"
        assert r.get_stats()["router"]["failovers"] == 1


# --------------------------------------------------------- ragged/tp seam
MICRO_TP = LlamaConfig(
    name="sharded-micro", vocab_size=512, d_model=64, n_layers=2,
    n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
    rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
)


def _micro_engine(mesh=None, **kw):
    from k8s_llm_scheduler_tpu.engine.engine import InferenceEngine
    from k8s_llm_scheduler_tpu.engine.sharded import serving_param_specs
    from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer
    from k8s_llm_scheduler_tpu.models.llama import init_params
    from k8s_llm_scheduler_tpu.parallel.sharding import shard_params

    params = init_params(jax.random.PRNGKey(0), MICRO_TP)
    if mesh is not None:
        params = shard_params(params, mesh, serving_param_specs(MICRO_TP))
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_pages_per_seq", 16)
    kw.setdefault("prefill_buckets", (32, 64, 128))
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("prefix_chunk", 32)
    return InferenceEngine(params, MICRO_TP, ByteTokenizer(), mesh=mesh, **kw)


class TestRaggedTpSeam:
    def test_ragged_refused_on_tp_mesh(self):
        """Regression: 'ragged' on tp>1 used to silently serve dense
        while bench labels said ragged. Now it refuses at build time."""
        with pytest.raises(ValueError, match="single-device-only"):
            _micro_engine(mesh=make_mesh({"tp": 2}), decode_matmul="ragged")

    def test_dense_builds_on_tp_mesh(self):
        engine = _micro_engine(mesh=make_mesh({"tp": 2}))
        assert engine.kv.sharding is not None
        assert engine.kv.k.sharding.spec == P(None, None, None, "tp", None)


class TestRandomInit:
    def test_one_init_gives_every_layout_the_same_weights(self):
        """build_local_backend random-inits through ONE jitted program:
        born sharded on a mesh (every leaf a NamedSharding over it, never
        whole on device 0) and bit-identical to the single-device tree —
        the premise of comparing greedy token digests across tp."""
        import numpy as np

        from k8s_llm_scheduler_tpu.engine.local import _init_params

        cfg = get_config("tiny")
        mesh = make_mesh({"tp": 2})
        single, sharded = _init_params(0, cfg), _init_params(0, cfg, mesh)
        wq = sharded["layers"]["wq"]
        assert wq.sharding.spec == P(None, None, "tp")
        assert len(wq.sharding.device_set) == 2
        for a, b in zip(jax.tree_util.tree_leaves(single),
                        jax.tree_util.tree_leaves(sharded), strict=True):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            )


# ----------------------------------------------------- tp identity (slow)
@pytest.mark.slow
class TestTpIdentity:
    def test_tp2_greedy_token_identical_to_tp1(self):
        """THE acceptance pin: the same weights serve byte-identical
        greedy decisions on a tp=2 mesh and off-mesh — through packed
        admission and the fused decode runtime."""
        from k8s_llm_scheduler_tpu.engine.tokenizer import ByteTokenizer

        tok = ByteTokenizer()
        e1 = _micro_engine(mesh=None, admission_chunk_tokens=16)
        e2 = _micro_engine(mesh=make_mesh({"tp": 2}), admission_chunk_tokens=16)
        prefix = tok.encode("CLUSTER STATE: " + " ".join(
            f"node-{i} cpu={10 + i}" for i in range(4)
        ))
        prompts = [
            tok.encode("pod-a needs a node"),
            tok.encode("p" * 45),  # spans 3 admission chunks of 16
            tok.encode("pod-c"),
        ]
        outs = []
        for engine in (e1, e2):
            engine.set_prefix(prefix)
            serial = [
                engine.generate(p, max_new_tokens=8).token_ids
                for p in prompts
            ]
            req_ids = engine.admit_packed(prompts, max_new_tokens=8)
            fused = {}
            while len(fused) < len(prompts):
                for fin in engine.step_fused():
                    fused[fin.req_id] = fin.token_ids
            assert [fused[r] for r in req_ids] == serial
            outs.append(serial)
        assert outs[0] == outs[1]
