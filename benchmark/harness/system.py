"""Everything that touches the program under test, and nothing else does.

The system is built exactly as `cli run --fake-cluster` and `chip_smoke.py`
build it: `cli._build_stack(cfg, cluster)` then `Scheduler.run()`. From the
program the benchmark takes the stack, its counters, and the calls into
each layer, which it wraps from this side (host spans, wave records): no
file of the program is edited.
"""

from __future__ import annotations

import contextlib
import copy
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from harness import seam
from harness import traffic as T

HOST_SPANS = ("snapshot", "decide", "submit_wave", "harvest_wave", "prefix_prefill", "bind")


# ------------------------------------------------------------- configuration
def program_config(conf: dict, mix: dict):
    """config.py DEFAULTS (not the CWD's config.yaml, not the environment)
    with the model, which the configuration's architecture registers with
    the program by code of its own (arch/<architecture>.py `register`), the
    committed tokenizer fixture, and the departures the configuration and
    traffic files state under `serve`."""
    from k8s_llm_scheduler_tpu.config import DEFAULTS, Config
    from k8s_llm_scheduler_tpu.testing import BPE_FIXTURE

    cfg = Config(copy.deepcopy(DEFAULTS))
    cfg.data["llm"]["model"] = seam.program(conf).register(conf)
    cfg.data["llm"]["tokenizer_path"] = BPE_FIXTURE
    for dotted, value in {**conf.get("serve", {}), **mix.get("serve", {})}.items():
        node = cfg.data
        *parents, leaf = dotted.split(".")
        for key in parents:
            node = node[key]
        if leaf not in node:
            raise KeyError(f"serve departure {dotted!r} is not a config.py key")
        node[leaf] = copy.deepcopy(value)
    return cfg


# ------------------------------------------------------------------ cluster
def make_cluster(mix: dict, seed: int):
    """The seeded FakeCluster, with the moment of every acknowledged bind
    kept and a hook that the closed loop uses to release the next pod."""
    from k8s_llm_scheduler_tpu.cluster.fake import FakeCluster, FakeNode
    from k8s_llm_scheduler_tpu.cluster.interface import RawPod

    class BenchCluster(FakeCluster):
        def __init__(self) -> None:
            super().__init__()
            self.acks: dict[str, float] = {}   # pod name -> perf_counter at ack
            self.on_ack = None
            self.bind_hook = contextlib.nullcontext

        def bind_pod_to_node(self, pod_name, namespace, node_name):
            with self.bind_hook():
                ok = super().bind_pod_to_node(pod_name, namespace, node_name)
            if ok:
                self.acks[pod_name] = time.perf_counter()
                if self.on_ack is not None:
                    self.on_ack(pod_name)
            return ok

        def set_usage(self, updates) -> None:
            with self._lock:
                for name, cpu, mem in updates:
                    node = self._nodes[name]
                    node.cpu_usage_percent, node.memory_usage_percent = cpu, mem

    cluster = BenchCluster()
    spec = mix["cluster_spec"]
    for node in T.make_nodes(spec, seed):
        cluster.add_node(FakeNode(
            name=node.name, cpu_capacity_cores=node.cpu_cores,
            memory_capacity_gb=node.memory_gb, max_pods=node.max_pods,
            labels=dict(node.labels), taints=node.taints,
            cpu_usage_percent=node.cpu_usage_percent,
            memory_usage_percent=node.memory_usage_percent,
        ))
        for j in range(node.preloaded_pods):
            cluster.add_pod(RawPod(
                name=f"pre-{node.name}-{j}", namespace="kube-system", phase="Running",
                node_name=node.name, uid=f"pre-{node.name}-{j}",
            ))
    return cluster


def raw_pod(plan: T.PodPlan):
    from k8s_llm_scheduler_tpu.cluster.interface import RawPod

    return RawPod(
        name=plan.name, namespace="default", scheduler_name=T.SCHEDULER_NAME,
        container_requests=({"cpu": f"{plan.cpu_m}m", "memory": f"{plan.mem_mi}Mi"},),
        node_selector=dict(plan.node_selector), tolerations=plan.tolerations,
        priority=plan.priority, uid=f"uid-{plan.name}",
    )


# ------------------------------------------------------------------- probes
class Probes:
    """Wrappers around the calls into each layer, put on the live objects
    from this side. They keep (a) one record per wave: host clock at submit
    and harvest, the rows, the prompt and prefix token ids by reference,
    and the served tokens, which the comparison that decides `correct`
    samples; (b) host spans under the names in HOST_SPANS, written into
    the profiler's trace (TraceAnnotation) while a trace is being taken."""

    def __init__(self, scheduler, backend, cluster) -> None:
        self.waves: list[dict] = []
        self.prefix_prefills: list[tuple[float, float, int]] = []
        self.tracing = False
        self._lock = threading.Lock()
        self.engine = engine = backend.engine
        self._wrap(scheduler, "_node_snapshot", "snapshot", is_async=True)
        self._wrap(scheduler.client, "get_scheduling_decision", "decide", is_async=True)
        cluster.bind_hook = lambda: self._span("bind")
        submit, harvest = engine.submit_wave, engine.harvest_wave
        inner = engine._set_prefix_inner
        pending: dict[int, dict] = {}

        def submit_wave(prompts, max_new_tokens=200):
            t0 = time.perf_counter()
            with self._span("submit_wave"):
                handle = submit(prompts, max_new_tokens)
            prefix = engine._prefix
            pending[id(handle)] = {
                "t_submit": t0, "rows": len(prompts), "prompts": prompts,
                "prefix_ids": prefix.token_ids if prefix is not None else (),
                "prefix_cap": int(prefix.k.shape[1]) if prefix is not None else 0,
            }
            return handle

        def harvest_wave(handle):
            with self._span("harvest_wave"):
                fins = harvest(handle)
            rec = pending.pop(id(handle), None)
            if rec is not None:
                rec["t_harvest"] = time.perf_counter()
                rec["served"] = [f.token_ids for f in fins]
                with self._lock:
                    self.waves.append(rec)
            return fins

        def set_prefix_inner(prompt_ids, _sp, activate=True):
            before = engine.stats["prefix_prefills"]
            t0 = time.perf_counter()
            with self._span("prefix_prefill"):
                out = inner(prompt_ids, _sp, activate)
            if engine.stats["prefix_prefills"] != before:
                self.prefix_prefills.append((t0, time.perf_counter(), len(prompt_ids)))
            return out

        engine.submit_wave, engine.harvest_wave = submit_wave, harvest_wave
        engine._set_prefix_inner = set_prefix_inner

    def _span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, obj, attr: str, name: str, is_async: bool) -> None:
        fn = getattr(obj, attr)

        async def wrapped(*args, **kwargs):
            with self._span(name):
                return await fn(*args, **kwargs)

        setattr(obj, attr, wrapped)

    def waves_between(self, t0: float, t1: float) -> list[dict]:
        with self._lock:
            return [w for w in self.waves if t0 <= w["t_harvest"] < t1]


# ------------------------------------------------------------------- build
def build(conf: dict, mix: dict, seed: int):
    """(scheduler, backend, cluster, probes, cfg): the stack of
    `cli run --fake-cluster` over the seeded cluster."""
    from k8s_llm_scheduler_tpu.cli import _build_stack

    if conf["weights_seed"] != 0:
        raise ValueError("cli._backend_kwargs passes no init seed: the program always inits from 0")
    cfg = program_config(conf, mix)
    cluster = make_cluster(mix, seed)
    scheduler, backend = _build_stack(cfg, cluster)
    return scheduler, backend, cluster, Probes(scheduler, backend, cluster), cfg


def _decide_all(backend, pods, nodes, deadline: float) -> None:
    with ThreadPoolExecutor(max_workers=len(pods)) as pool:
        futs = [pool.submit(backend.get_scheduling_decision, p, nodes) for p in pods]
        for f in futs:
            f.result(timeout=max(deadline - time.monotonic(), 1.0))


def _drain_prewarm(backend, deadline: float) -> None:
    while backend.engine.wave_prewarm_backlog() > 0:
        if time.monotonic() > deadline:
            raise TimeoutError("sibling wave prewarm did not drain")
        time.sleep(0.02)


def warm_up(backend, cluster, mix: dict, seed: int, deadline: float) -> dict:
    """Compile or load every program the cell's traffic can reach, through
    the backend seam the decision client calls (chip_smoke.py's order):
    the pinned prefix and the grammar, a full-width wave, the idle worker's
    half-width sibling; then the same at the widest delta the encoder
    allows before it re-pins (a longer prefix buffer is another program).
    Nothing is retried: an error here ends the run with its message."""
    from k8s_llm_scheduler_tpu.cluster.interface import raw_pod_to_spec

    engine = backend.engine
    zones = mix["cluster_spec"]["zones"]
    pods = [raw_pod_to_spec(raw_pod(p)) for p in T._stratified_shapes(
        mix["shape_table"], 2 * engine.max_slots, seed, zones, what="setup", idx_offset=T.SETUP_IDX)]
    stages = {}
    for stage, drifted in (("pinned", 0), ("widest_delta", None)):
        if drifted is None:
            spec = mix["cluster_spec"]
            n_delta = int(backend._delta.repin_fraction * spec["nodes"]) if backend._delta else 0
            if n_delta == 0:
                break
            rng = T.rng_for(T.state_seed(spec, seed), "setup-drift")
            lo, hi = spec["usage_percent_range"]
            names = [spec["name_format"].format(i=i) for i in range(spec["nodes"])]
            # the LAST nodes in render order: the longest delta text sits
            # behind the whole pin, as a late drift would put it
            cluster.set_usage([(n, T._usage(rng, lo, hi), T._usage(rng, lo, hi))
                               for n in names[-n_delta:]])
        nodes = cluster.get_node_metrics()
        t0 = time.perf_counter()
        if not backend.prewarm_prefix(nodes).result(timeout=deadline - time.monotonic()):
            raise RuntimeError(f"warm-up: prefix install was dropped at stage {stage}")
        t1 = time.perf_counter()
        batch = pods[: engine.max_slots] if stage == "pinned" else pods[engine.max_slots:]
        _decide_all(backend, batch, nodes, deadline)
        t2 = time.perf_counter()
        _drain_prewarm(backend, deadline)
        stages[stage] = {
            "prefix_tokens": engine.prefix_len,
            "prefix_buffer": int(engine._prefix.k.shape[1]),
            "prefix_s": t1 - t0, "full_wave_s": t2 - t1,
            "half_wave_s": time.perf_counter() - t2,
        }
    return stages
