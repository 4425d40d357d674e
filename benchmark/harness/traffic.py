"""The one general traffic generator: cluster and pods from a traffic file and a seed.

Everything a metric depends on is the same for every seed: the count of
nodes and pods in every class, the arrival count, the burst sizes and the
pool of (name, request) texts, hence the suffix-length histogram. The seed
permutes order, assigns classes to nodes, and draws loads inside a class's
range. No JAX here: the rehearsal and the tests import this on a bare CPU.
"""

from __future__ import annotations

import dataclasses
import json
import random
from pathlib import Path

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"
SCHEDULER_NAME = "ai-llama-scheduler"  # config.py DEFAULTS scheduler.name
BLOCK = 64  # pods to a stratified block (the shapes file's per_64 counts)
# A pod's index in its class is added to its requests (millicores, MiB), so
# that no two shapes share a decision-cache key. A run's own pods stay under
# 1,000 in every class (40 blocks x 20 a block at most); set-up and warm-up
# shapes start here, apart from them and from each other, and still small
# enough that a GPU pod (4 cores + 1.1) fits more than half of the nodes.
SETUP_IDX, WARM_IDX = 1000, 1100


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    """The traffic file `traffic/<name>.json` with its cluster and shape
    tables read in beside it."""
    mix = load_json(TRAFFIC_DIR / f"{name}.json")
    if mix.get("kind") not in ("closed_depth", "timetable"):
        raise ValueError(f"traffic {name!r}: unknown kind {mix.get('kind')!r}")
    mix["name"] = name
    mix["cluster_spec"] = load_json(TRAFFIC_DIR / mix["cluster"])
    mix["shape_table"] = load_json(TRAFFIC_DIR / mix["shapes"])["classes"]
    if sum(c["per_64"] for c in mix["shape_table"]) != BLOCK:
        raise ValueError(f"traffic {name!r}: per_64 counts must add up to {BLOCK}")
    return mix


def rng_for(seed: int, what: str) -> random.Random:
    """An independent stream per purpose, so adding a draw to one never
    shifts another. Seeds are any whole number (the driver's pass 2**31)."""
    return random.Random(f"{int(seed)}:{what}")


# ------------------------------------------------------------------ cluster
@dataclasses.dataclass(frozen=True)
class NodeSpec:
    name: str
    cpu_cores: float
    memory_gb: float
    max_pods: int
    labels: dict
    taints: tuple
    cpu_usage_percent: float
    memory_usage_percent: float
    preloaded_pods: int


def _expand(classes: list[dict], total: int, what: str) -> list[dict]:
    out = [c for c in classes for _ in range(c["count"])]
    if len(out) != total:
        raise ValueError(f"{what}: counts add up to {len(out)}, not {total}")
    return out


def _usage(rng: random.Random, lo: float, hi: float) -> float:
    return round(rng.uniform(lo, hi), 1)


def state_seed(spec: dict, seed: int):
    """What the cluster's state is drawn from: the cluster file's constant
    `state_seed` where it has one (then --seed only permutes orders), else
    the run's seed."""
    return spec.get("state_seed", seed)


def make_nodes(spec: dict, seed: int) -> list[NodeSpec]:
    n = spec["nodes"]
    rng = rng_for(state_seed(spec, seed), "cluster")
    caps = _expand(spec["capacity_classes"], n, "capacity_classes")
    pools = _expand(spec["pools"], n, "pools")
    rng.shuffle(caps)
    rng.shuffle(pools)
    zones = [i % spec["zones"] for i in range(n)]
    rng.shuffle(zones)
    lo, hi = spec["usage_percent_range"]
    plo, phi = spec["preloaded_pods_range"]
    nodes = []
    for i in range(n):
        labels = {"zone": f"z{zones[i]}", **pools[i]["label"]}
        nodes.append(NodeSpec(
            name=spec["name_format"].format(i=i),
            cpu_cores=caps[i]["cpu_cores"], memory_gb=caps[i]["memory_gb"],
            max_pods=spec["max_pods"], labels=labels,
            taints=tuple(dict(t) for t in pools[i]["taints"]),
            cpu_usage_percent=_usage(rng, lo, hi),
            memory_usage_percent=_usage(rng, lo, hi),
            preloaded_pods=rng.randint(plo, phi),
        ))
    return nodes


def drift_schedule(spec: dict, seed: int, ticks: int) -> list[list[tuple[str, float, float]]]:
    """For each tick, the (node, cpu%, mem%) updates it applies: a
    seed-permuted cycle over the nodes, nodes_per_tick at a time. The
    values a node reports on its k-th visit come from the state seed, so
    that seeds differ in the order of the same updates."""
    n, per = spec["nodes"], spec["drift"]["nodes_per_tick"]
    lo, hi = spec["usage_percent_range"]
    order = list(range(n))
    rng_for(seed, "drift").shuffle(order)
    values = rng_for(state_seed(spec, seed), "drift-values")
    rounds = -(-ticks * per // n)
    table = [[(_usage(values, lo, hi), _usage(values, lo, hi)) for _ in range(n)] for _ in range(rounds)]
    out = []
    for t in range(ticks):
        picks = [((t * per + j) // n, order[(t * per + j) % n]) for j in range(per)]
        out.append([(spec["name_format"].format(i=i), *table[visit][i]) for visit, i in picks])
    return out


# --------------------------------------------------------------------- pods
@dataclasses.dataclass(frozen=True)
class PodPlan:
    """One pod as the generator emits it. `shape` identifies the
    decision-cache equivalence class inside the run."""
    name: str
    cls: str
    shape: str
    cpu_m: int
    mem_mi: int
    priority: int
    node_selector: dict
    tolerations: tuple


def _class_pod(cls: dict, idx: int, replica: int, zones: int) -> PodPlan:
    """Pod `idx` of class `cls`: the text depends on (class, idx, replica)
    alone, so the pool of texts is the same for every seed."""
    selector = dict(cls.get("node_selector", {}))
    if cls.get("node_selector_zone"):
        selector["zone"] = f"z{idx % zones}"
    return PodPlan(
        name=f"{cls['name']}-{idx:04d}-{replica}",
        cls=cls["name"], shape=f"{cls['name']}-{idx:04d}",
        cpu_m=cls["cpu_m"] + idx, mem_mi=cls["mem_mi"] + idx,
        priority=cls["priority"], node_selector=selector,
        tolerations=tuple(dict(t) for t in cls.get("tolerations", ())),
    )


def _apportion(table: list[dict], n: int) -> dict[str, int]:
    """How many of n pods each class gets: the per_64 shares, whole blocks
    exactly, a remainder by largest fraction (ties in table order). The
    same for every seed."""
    whole, rem = divmod(n, BLOCK)
    counts = {c["name"]: c["per_64"] * whole for c in table}
    quota = [(c["per_64"] * rem / BLOCK, c) for c in table]
    given = 0
    for q, c in quota:
        counts[c["name"]] += int(q)
        given += int(q)
    for q, c in sorted(quota, key=lambda qc: -(qc[0] - int(qc[0])))[: rem - given]:
        counts[c["name"]] += 1
    return counts


def _stratified_shapes(table: list[dict], n: int, seed: int, zones: int,
                       what: str = "pods", idx_offset: int = 0) -> list[PodPlan]:
    """n distinct shapes with the same class counts for every seed
    (_apportion), and the same pool of texts: class k uses indices
    idx_offset .. idx_offset + count_k. Whole blocks of 64 each hold the
    table's per_64 counts, so any prefix of whole blocks is stratified
    too; the seed permutes the order inside a block and which index lands
    in which block."""
    rng = rng_for(seed, what)
    counts = _apportion(table, n)
    idx_order = {}
    for cls in table:
        order = list(range(idx_offset, idx_offset + counts[cls["name"]]))
        rng.shuffle(order)
        idx_order[cls["name"]] = order
    out = []
    whole, rem = divmod(n, BLOCK)
    rem_counts = _apportion(table, rem)
    for b in range(whole + (1 if rem else 0)):
        block = []
        for cls in table:
            k = cls["per_64"] if b < whole else rem_counts[cls["name"]]
            lo = cls["per_64"] * b
            for idx in idx_order[cls["name"]][lo:lo + k]:
                block.append(_class_pod(cls, idx, 0, zones))
        rng.shuffle(block)
        out.extend(block)
    return out


def closed_depth_pods(mix: dict, seed: int) -> list[PodPlan]:
    """The closed loop's pod sequence, longer than any run consumes
    (pool_blocks x 64). With `distinct_shapes` set, every pod is a replica
    of one of that many Deployment shapes (the first of a stratified
    block), round-robin in a seed-permuted order."""
    zones = mix["cluster_spec"]["zones"]
    n_blocks = mix["pool_blocks"]
    distinct = mix.get("distinct_shapes")
    if not distinct:
        return _stratified_shapes(mix["shape_table"], n_blocks * BLOCK, seed, zones)
    base = _stratified_shapes(mix["shape_table"], distinct, seed, zones)
    rng = rng_for(seed, "replicas")
    out = []
    for r in range(n_blocks * BLOCK // distinct):
        row = [dataclasses.replace(p, name=f"{p.shape}-{r}") for p in base]
        rng.shuffle(row)
        out.extend(row)
    return out


@dataclasses.dataclass(frozen=True)
class Burst:
    due_s: float  # relative to the window's start; negative = warm-up
    pods: tuple


def timetable(mix: dict, seed: int, seconds: float) -> tuple[list[Burst], list[Burst]]:
    """(warm bursts, window bursts). The window holds a whole number of
    cycles of burst_sizes, each cycle in a seed-permuted order, one burst
    every period_s, the last due at least one period before the end."""
    period, sizes = mix["period_s"], list(mix["burst_sizes"])
    reps = mix["replicas_per_shape"]
    cycles = max(int(seconds // (period * len(sizes))), 1)
    rng = rng_for(seed, "bursts")
    order = []
    for _ in range(cycles):
        cyc = list(sizes)
        rng.shuffle(cyc)
        order.extend(cyc)
    warm_sizes = list(mix.get("warm_bursts", ()))
    table, zones = mix["shape_table"], mix["cluster_spec"]["zones"]

    def bursts(sizes_: list[int], what: str, offset: int) -> list[tuple]:
        shapes = _stratified_shapes(
            table, sum(sizes_) // reps, seed, zones, what=what, idx_offset=offset)
        out, cursor = [], 0
        for size in sizes_:
            lead = shapes[cursor:cursor + size // reps]
            cursor += size // reps
            # leaders first, then their replicas: one leads, one follows
            out.append(tuple(dataclasses.replace(p, name=f"{p.shape}-{r}")
                             for r in range(reps) for p in lead))
        return out

    window = [Burst(due_s=i * period, pods=pods)
              for i, pods in enumerate(bursts(order, "pods", 0))]
    # warm-up shapes come from indices no window pod uses
    warm = [Burst(due_s=-1.0, pods=pods)
            for pods in bursts(warm_sizes, "warm", WARM_IDX)] if warm_sizes else []
    return warm, window


# ------------------------------------------------------- stratification facts
def class_counts(pods) -> dict[str, int]:
    out: dict[str, int] = {}
    for p in pods:
        out[p.cls] = out.get(p.cls, 0) + 1
    return dict(sorted(out.items()))
