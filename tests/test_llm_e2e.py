"""Full-stack hermetic E2E: watch -> prompt -> TPU-style LLM decode -> bind.

The reference can only test this path against live Minikube + the live HF
API with a human in the loop (test_e2e.py:59-66). Here the whole thing runs
in-process: FakeCluster + LocalLLMBackend (tiny random-weight Llama,
grammar-constrained decoding) + DecisionClient + Scheduler. Zero network,
zero external API calls — the north-star property, demonstrated end to end.
"""

import asyncio

import jax.numpy as jnp
import pytest

from k8s_llm_scheduler_tpu.core.breaker import CircuitBreaker
from k8s_llm_scheduler_tpu.core.cache import DecisionCache
from k8s_llm_scheduler_tpu.engine.local import build_local_backend
from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.sched.client import DecisionClient
from k8s_llm_scheduler_tpu.sched.loop import Scheduler
from k8s_llm_scheduler_tpu.testing import (
    SCHEDULER_NAME,
    async_deadline,
    fixture_pods,
    pod_burst,
    synthetic_cluster,
)
from k8s_llm_scheduler_tpu.types import DecisionSource

# Everything here jit-compiles models/kernels (seconds per test):
# full-suite only, excluded from the fast tier (TESTING.md).
pytestmark = pytest.mark.slow

E2E_CFG = LlamaConfig(
    name="e2e-test", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
    n_kv_heads=2, d_ff=128, max_seq_len=4096, rope_theta=10000.0,
    dtype=jnp.float32, tie_embeddings=True,
)


@pytest.fixture(scope="module")
def backend():
    b = build_local_backend(
        cfg=E2E_CFG,
        max_slots=4, num_pages=256, page_size=64,
        prefill_buckets=(512, 1024, 2048, 4096),
        chunk_steps=16, temperature=0.0, max_new_tokens=160,
    )
    yield b
    b.close()


def make_stack(cluster, backend):
    client = DecisionClient(
        backend=backend,
        cache=DecisionCache(),
        breaker=CircuitBreaker(),
        retry_delay=0.0,
    )
    return Scheduler(
        cluster, cluster, client,
        scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=60.0,
    )


class TestLLMEndToEnd:
    @pytest.mark.asyncio
    async def test_fixture_pods_scheduled_by_llm(self, backend):
        cluster = synthetic_cluster(3)
        for pod in fixture_pods():
            cluster.add_pod(pod)
        scheduler = make_stack(cluster, backend)
        task = asyncio.create_task(scheduler.run())
        try:
            async with async_deadline(120):
                while cluster.bind_count < 3:
                    await asyncio.sleep(0.05)
        finally:
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=10)

        node_names = {n.name for n in cluster.get_node_metrics()}
        for pod in fixture_pods():
            bound = cluster.get_pod("default", pod.name)
            assert bound.node_name in node_names
            assert bound.phase == "Running"
        stats = scheduler.get_stats()
        # At least one real LLM decision; the rest may be cache hits.
        assert stats["llm_decisions"] >= 1
        assert stats["fallback_decisions"] == 0

    @pytest.mark.asyncio
    async def test_burst_batches_through_engine(self, backend):
        """A 12-pod burst with 3 shapes: decisions batch through the engine,
        cache collapses repeats, every pod lands."""
        cluster = synthetic_cluster(5)
        for pod in pod_burst(12, distinct_shapes=3):
            cluster.add_pod(pod)
        scheduler = make_stack(cluster, backend)
        task = asyncio.create_task(scheduler.run())
        try:
            async with async_deadline(120):
                while cluster.bind_count < 12:
                    await asyncio.sleep(0.05)
        finally:
            scheduler.stop()
            cluster.close()
            await asyncio.wait_for(task, timeout=10)

        stats = scheduler.get_stats()
        assert stats["total_scheduled"] == 12
        assert stats["client"]["cached_requests"] >= 6
        assert stats["fallback_decisions"] == 0

    @pytest.mark.asyncio
    async def test_llm_decision_metadata(self, backend):
        """Direct client call: decision carries LLM provenance and a node
        from the live list (grammar-guaranteed)."""
        cluster = synthetic_cluster(4)
        client = DecisionClient(backend=backend, cache=None, breaker=None,
                                retry_delay=0.0)
        from conftest import make_pod

        nodes = cluster.get_node_metrics()
        decision = await client.get_scheduling_decision(make_pod(), nodes)
        assert decision.source is DecisionSource.LLM
        assert decision.selected_node in {n.name for n in nodes}
        assert 0.0 <= decision.confidence <= 1.0
        assert decision.latency_ms > 0


class TestPrefixPrewarm:
    def test_prewarm_installs_the_real_group_key(self):
        """prewarm_prefix's dummy-suffix construction must land on the
        EXACT group key a real pod produces — otherwise the install is
        useless (the burst would switch groups anyway) and silently so."""
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec

        backend = build_local_backend(
            cfg=E2E_CFG, max_slots=2, num_pages=64, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            temperature=0.0, compile_cache_dir=None,
        )
        try:
            cluster = synthetic_cluster(3)
            nodes = cluster.get_node_metrics()
            cluster.close()
            assert backend.prewarm_prefix(nodes).result(timeout=120) is True
            pod = raw_pod_to_spec(next(iter(pod_burst(1))))
            item = backend._prepare_item(pod, nodes)
            assert backend._current_group == item.group_key
            # a decision on the warm group serves without switching
            d = backend.get_scheduling_decision(pod, nodes)
            assert d.selected_node in {n.name for n in nodes}
            assert backend._current_group == item.group_key
            # idempotent: same snapshot re-prewarms as a no-op True
            assert backend.prewarm_prefix(nodes).result(timeout=30) is True
        finally:
            backend.close()


class TestCotAnswerStyle:
    def test_cot_decision_through_serving_stack(self):
        """answer_style='cot' (reasoning before the constrained choice):
        the full serving path still yields a valid decision whose parsed
        object matches the reference schema — field order is wire-level
        only."""
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec

        backend = build_local_backend(
            cfg=E2E_CFG, max_slots=2, num_pages=64, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            temperature=0.0, answer_style="cot", tokenizer_name="numeric",
            compile_cache_dir=None,
        )
        try:
            cluster = synthetic_cluster(3)
            nodes = cluster.get_node_metrics()
            cluster.close()
            pod = raw_pod_to_spec(next(iter(pod_burst(1))))
            d = backend.get_scheduling_decision(pod, nodes)
            assert d.selected_node in {n.name for n in nodes}
            assert 0.0 <= d.confidence <= 1.0
            assert d.source is DecisionSource.LLM
        finally:
            backend.close()


class TestShardedBackend:
    """Full decision flow with the model tensor-parallel over the virtual
    8-device CPU mesh — the hermetic stand-in for the v5p TP path."""

    async def test_tp_sharded_decisions(self):
        import jax

        cfg = LlamaConfig(
            name="tp-e2e", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, max_seq_len=4096, rope_theta=10000.0,
            dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg, mesh_axes={"tp": 2},
            max_slots=2, num_pages=64, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            chunk_steps=8, temperature=0.0, max_new_tokens=160,
            # Kernels ON under tp sharding: the engine must wrap them in
            # shard_map (interpret mode on the CPU mesh), not fall back.
            prefix_attn_impl="pallas",
        )
        try:
            from k8s_llm_scheduler_tpu.ops.attention import AttnImpl

            impl = backend.engine.prefix_attn_impl
            assert isinstance(impl, AttnImpl) and impl.kind == "pallas"
            assert impl.mesh is not None
            # params actually sharded over the mesh
            leaves = jax.tree_util.tree_leaves(backend.engine.params)
            assert any(
                len(leaf.sharding.device_set) == 2 for leaf in leaves
            ), "no parameter is sharded over the tp axis"
            cluster = synthetic_cluster(3)
            client = DecisionClient(
                backend, cache=DecisionCache(), breaker=CircuitBreaker(),
                retry_delay=0.0,
            )
            sched = Scheduler(
                cluster, cluster, client,
                scheduler_name=SCHEDULER_NAME, snapshot_ttl_s=60.0,
            )
            task = asyncio.create_task(sched.run())
            for pod in pod_burst(4, distinct_shapes=2):
                cluster.add_pod(pod)
            async with async_deadline(120):
                while cluster.bind_count < 4:
                    await asyncio.sleep(0.02)
            sched.stop()
            await asyncio.wait_for(task, timeout=30)
            stats = sched.get_stats()
            assert stats["total_scheduled"] == 4
            assert stats["llm_decisions"] >= 2
            # phase tracing wired through the loop
            assert stats["phases"]["decide"]["count"] == 4
            assert stats["phases"]["bind"]["count"] == 4
        finally:
            backend.close()
            cluster.close()

    def test_sharded_pallas_matches_xla_decisions(self):
        """Same pods, same sharded mesh: shard-mapped Pallas kernels and the
        XLA cascade produce identical greedy decisions."""
        from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec

        cfg = LlamaConfig(
            name="tp-parity", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        cluster = synthetic_cluster(3)
        nodes = cluster.get_node_metrics()
        pods = [raw_pod_to_spec(p) for p in pod_burst(2, distinct_shapes=2)]
        decisions = {}
        for impl in ("pallas", "xla"):
            backend = build_local_backend(
                cfg=cfg, mesh_axes={"tp": 2},
                max_slots=2, num_pages=64, page_size=64,
                prefill_buckets=(512, 1024, 2048, 4096),
                chunk_steps=8, temperature=0.0, max_new_tokens=160,
                prefix_attn_impl=impl,
            )
            try:
                decisions[impl] = [
                    backend.get_scheduling_decision(p, nodes).selected_node
                    for p in pods
                ]
            finally:
                backend.close()
        assert decisions["pallas"] == decisions["xla"]

    def test_serving_rejects_non_tp_axes(self):
        """dp>1 serving meshes replicate weights without sharding the batch
        — build_local_backend must reject them loudly."""
        cfg = LlamaConfig(
            name="tp-reject", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        with pytest.raises(ValueError, match="only a tp axis"):
            build_local_backend(cfg=cfg, mesh_axes={"tp": 2, "dp": 2})
        with pytest.raises(ValueError, match="only a tp axis"):
            build_local_backend(cfg=cfg, mesh_axes={"dp": 2})


class TestGroupSwitching:
    """Interleaved cluster snapshots force (prefix, grammar) group switches
    in the wave worker — including with held partial batches in flight."""

    async def test_interleaved_clusters_all_decide(self):
        cfg = LlamaConfig(
            name="group-e2e", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg, max_slots=2, num_pages=128, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            chunk_steps=8, temperature=0.0, max_new_tokens=160,
        )
        try:
            from conftest import make_node, make_pod

            # three DISTINCT snapshots (different node sets -> different
            # prefixes and grammars)
            snapshots = [
                [make_node(f"grp{g}-node-{i}") for i in range(3)]
                for g in range(3)
            ]
            # interleave decisions across groups from concurrent tasks
            async def decide(g, i):
                pod = make_pod(name=f"pod-g{g}-{i}", cpu=0.1 * (i + 1))
                d = await backend.get_scheduling_decision_async(
                    pod, snapshots[g]
                )
                assert d.selected_node.startswith(f"grp{g}-"), (
                    g, d.selected_node,
                )
                return d

            results = await asyncio.gather(
                *(decide(g, i) for i in range(4) for g in range(3))
            )
            assert len(results) == 12
            stats = backend.get_stats()
            assert stats["completed"] >= 12
        finally:
            backend.close()

    async def test_sustained_hot_group_cannot_starve_other_group(self):
        """ADVICE r1: a sustained stream of current-group requests used to
        defer other-group items until the 60s request timeout. The fairness
        bound (group_switch_after_s) must get the cold group decided while
        the hot stream keeps the pipeline non-empty throughout."""
        cfg = LlamaConfig(
            name="fair-e2e", vocab_size=512, d_model=64, n_layers=2,
            n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=4096,
            rope_theta=10000.0, dtype=jnp.float32, tie_embeddings=True,
        )
        backend = build_local_backend(
            cfg=cfg, max_slots=2, num_pages=128, page_size=64,
            prefill_buckets=(512, 1024, 2048, 4096),
            chunk_steps=8, temperature=0.0, max_new_tokens=160,
        )
        backend.group_switch_after_s = 0.2
        try:
            from conftest import make_node, make_pod

            hot = [make_node(f"hot-node-{i}") for i in range(3)]
            cold = [make_node(f"cold-node-{i}") for i in range(3)]

            stop_feeding = asyncio.Event()

            async def hot_stream():
                """Keep >= max_slots hot decisions in flight continuously."""
                n = 0
                done = 0
                inflight: set[asyncio.Task] = set()
                while not stop_feeding.is_set():
                    while len(inflight) < 4:
                        pod = make_pod(name=f"hot-{n}", cpu=0.01 * (n % 7 + 1))
                        inflight.add(asyncio.create_task(
                            backend.get_scheduling_decision_async(pod, hot)
                        ))
                        n += 1
                    finished, inflight = await asyncio.wait(
                        inflight, return_when=asyncio.FIRST_COMPLETED
                    )
                    done += len(finished)
                await asyncio.gather(*inflight, return_exceptions=True)
                return done

            feeder = asyncio.create_task(hot_stream())
            # let the hot pipeline get going
            await asyncio.sleep(0.3)
            pod = make_pod(name="cold-pod")
            t0 = asyncio.get_running_loop().time()
            async with async_deadline(55):
                d = await backend.get_scheduling_decision_async(pod, cold)
            waited = asyncio.get_running_loop().time() - t0
            stop_feeding.set()
            hot_done = await feeder
            assert d.selected_node.startswith("cold-"), d.selected_node
            # the hot stream really was saturating the engine the whole time
            assert hot_done >= 4, hot_done
            # bounded by the fairness window + a few wave lengths — nowhere
            # near the 60s starvation timeout this guards against. The bound
            # is deliberately loose: CPU waves run seconds each on a
            # contended CI host, and the OLD behavior failed by hitting the
            # full 60s timeout, not by being slow.
            assert waited < 40.0, waited
        finally:
            backend.close()
