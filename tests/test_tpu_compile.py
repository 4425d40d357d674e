"""The main path's Pallas kernels at real widths, COMPILED for a described
TPU v5e with no chip attached (jax.experimental.topologies): what interpret
mode cannot show. `ops/grouped_matmul.py` at LongCat-Flash's [6144, 2048]
experts passed every interpret-mode test and was refused here for 28 MB of
double-buffered blocks against Mosaic's 16 MiB default (PERF.md §6 PR 34).

One file, and the topology described inside a fixture: only the worker that
runs this file loads the TPU's library, and a host where it cannot be
described skips these tests and no others. Nothing runs, so nothing here is
a time.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from k8s_llm_scheduler_tpu.ops import grouped_matmul as gm


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the host lacks
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described device can be written to the persistent
    cache and never read back: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# (rows, experts held, K, N): a decode call and a suffix call of the third
# family's share, the second family's suffix call, and the short path of the
# fourth family's share in a decode and a suffix call (half of T x 10 rows)
SHAPES = [(2304, 16, 6144, 2048), (12288, 16, 6144, 2048), (4096, 64, 2048, 1536),
          (1024, 128, 2048, 512), (5120, 128, 2048, 512)]


@pytest.mark.parametrize("rows, experts, k, n", SHAPES)
def test_the_grouped_kernels_compile_for_the_chip(one_chip, no_compile_cache, rows, experts, k, n):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    sizes, layer = shape((experts,), jnp.int32), shape((), jnp.int32)
    up = jax.jit(lambda x, w0, w1, s, l: gm.grouped_matmul(x, (w0, w1), s, l, swiglu=True, interpret=False))
    down = jax.jit(lambda x, w, s, l: gm.grouped_matmul(x, (w,), s, l, out_dtype=jnp.float32, interpret=False))
    w_up, w_down = shape((2, experts, k, n), jnp.bfloat16), shape((2, experts, n, k), jnp.bfloat16)
    for fn, args in ((up, (shape((rows, k), jnp.bfloat16), w_up, w_up, sizes, layer)),
                     (down, (shape((rows, n), jnp.bfloat16), w_down, sizes, layer))):
        compiled = fn.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()


def test_the_limit_is_asked_for_only_where_the_blocks_outgrow_the_default():
    # the second family's experts compile as they always did: no limit named
    assert gm._vmem_limit(128, 2048, 512, 2, 2, 2) is None
    assert gm._vmem_limit(128, 1536, 512, 1, 2, 4) is None
    # [6144, 512] gate and up, double-buffered: 25 MB of weights alone
    limit = gm._vmem_limit(128, 6144, 512, 2, 2, 2)
    assert limit is not None and 28 << 20 < limit < 64 << 20
    assert gm._vmem_limit(128, 2048, 512, 1, 2, 4) is None  # its down projection fits


@pytest.mark.parametrize("rows, positions, chunk", [(8, 24, 24), (8, 128, 32), (1, 2048, 32)])
def test_the_chunked_delta_rule_compiles_for_the_chip(one_chip, no_compile_cache, monkeypatch, rows, positions, chunk):
    """models/gdn_moe.py `gated_delta_chunks` at the published head sizes (32
    value heads of 128 x 128, float32; 16 key heads serve them) on a member
    of three periods: a decode block as one chunk, a suffix call as four, a
    prefix prefill as 64 for one row. The whole chunk is ops/gdn_scan.py's
    kernel, compiled by Mosaic."""
    from k8s_llm_scheduler_tpu.models import gdn_moe
    from k8s_llm_scheduler_tpu.ops import gdn_scan

    # the default backend here is the CPU, where a kernel is interpreted: compile it as the chip does
    monkeypatch.setattr(gdn_scan, "pallas_interpret", lambda interpret=None: False)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qk, v, gb = shape(rows, 16, positions, 128), shape(rows, 32, positions, 128), shape(rows, 32, positions)
    # the member is DONATED, as the layer scan's carry hands it over: the kernel's state is held to HBM, and
    # the copy XLA makes of an argument it may not overwrite, straight into such an operand, ABORTS this
    # compiler's memory-space assignment (no program of the family has that shape: the members always ride a
    # loop's carry; PERF.md §6 PR 38)
    compiled = jax.jit(
        lambda q, k, v, g, b, lens, s, p: gdn_moe.gated_delta_chunks(q, k, v, g, b, lens, s, p, chunk),
        donate_argnums=(6,),
    ).lower(qk, qk, v, gb, gb, shape(rows, dtype=jnp.int32), shape(3, rows, 32, 128, 128),
            shape(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert "tpu_custom_call" in text and "gdn_chunk_scan" in text
    # the state goes into the kernel and comes out of it where it lies: no copy of a member, none of an entry
    assert not re.search(r"f32\[(3,)?%d,32,128,128\]\S* copy" % rows, text)


@pytest.mark.parametrize("rows, positions, chunk", [(8, 24, 24), (8, 128, 64), (1, 2048, 64)])
def test_the_chunked_mamba2_scan_compiles_for_the_chip(one_chip, no_compile_cache, monkeypatch, rows, positions,
                                                      chunk):
    """models/mamba2_hybrid.py `ssd_chunks` at the published sizes (64 heads
    of 64 x 128, float32; B and C of 128 shared by the heads) on a member of
    four periods: a decode block as one chunk, a suffix call as two, a
    prefix prefill as 32 for one row. The whole chunk is ops/ssd_scan.py's
    kernel, compiled by Mosaic; the member is donated, as the layer scan's
    carry hands it over."""
    from k8s_llm_scheduler_tpu.models import mamba2_hybrid
    from k8s_llm_scheduler_tpu.ops import ssd_scan

    monkeypatch.setattr(ssd_scan, "pallas_interpret", lambda interpret=None: False)

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, dt, a, b, c, lens, s, p: mamba2_hybrid.ssd_chunks(x, dt, a, b, c, lens, s, p, chunk),
        donate_argnums=(6,),
    ).lower(shape(rows, 64, positions, 64), shape(rows, 64, positions), shape(64), shape(rows, positions, 128),
            shape(rows, positions, 128), shape(rows, dtype=jnp.int32), shape(4, rows, 64, 64, 128),
            shape(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert "tpu_custom_call" in text and "ssd_chunk_scan" in text
    # the state goes into the kernel and comes out of it where it lies: no copy of a member, none of an entry
    assert not re.search(r"f32\[(4,)?%d,64,64,128\]\S* copy" % rows, text)


# The routers the sparse cells run: (router outputs, picks, a selection bias)
# of glm-4_7-flash, longcat-flash-chat (512 experts and 256 identity ones) and
# qwen3-next-80b-a3b
ROUTERS = [(64, 4, True), (768, 12, True), (512, 10, False)]


@pytest.mark.parametrize("rows", [192, 1024, 2048])  # a decode call, a suffix call, a prefix prefill
@pytest.mark.parametrize("outputs, k, biased", ROUTERS)
def test_the_router_top_k_compiles_for_the_chip(one_chip, no_compile_cache, rows, outputs, k, biased):
    from k8s_llm_scheduler_tpu.ops.router_top_k import router_top_k

    scores = jax.ShapeDtypeStruct((rows, outputs), jnp.float32, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((outputs,), jnp.float32, sharding=one_chip) if biased else None
    compiled = jax.jit(lambda s, b: router_top_k(s, b, k, interpret=False)).lower(scores, bias).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "router_top_k" in text and not re.search(r"\bsort\(", text)


# (router outputs, picks, bias, score, experts held, identity experts, model width, expert width)
LAYERS = [(64, 4, True, "sigmoid", 64, None, 2048, 1536), (768, 12, True, "softmax", 16, 256, 6144, 2048),
          (512, 10, False, "softmax", 128, None, 2048, 512)]


@pytest.mark.parametrize("outputs, k, biased, score, held, zero, d, fe", LAYERS)
def test_the_routed_layer_sorts_nothing_on_the_chip(one_chip, no_compile_cache, monkeypatch, outputs, k, biased,
                                                    score, held, zero, d, fe):
    """models/mla_moe.py `routed_experts` at a decode call's 192 tokens and
    each sparse cell's widths, compiled as the chip does: the top k is the
    kernel, and neither it nor the order by expert lowers to a sort."""
    import types

    from k8s_llm_scheduler_tpu.models import mla_moe
    from k8s_llm_scheduler_tpu.ops import router_top_k as rtk

    monkeypatch.setattr(gm, "pallas_interpret", lambda interpret=None: False)
    monkeypatch.setattr(rtk, "pallas_interpret", lambda interpret=None: False)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = types.SimpleNamespace(n_experts_per_tok=k, experts_held=held, expert_first=0, router_score=score,
                                norm_topk_prob=True, routed_scaling_factor=1.0, n_zero_experts=zero,
                                n_routed_experts=outputs - (zero or 0))
    lp = {"router": shape(d, outputs), "we_gate": shape(1, held, d, fe), "we_up": shape(1, held, d, fe),
          "we_down": shape(1, held, fe, d), "layer": shape(dtype=jnp.int32)}
    if biased:
        lp["router_bias"] = shape(outputs, dtype=jnp.float32)
    compiled = jax.jit(lambda lp_, h, v: mla_moe.routed_experts(lp_, cfg, h, v)).lower(
        lp, shape(192, d, dtype=jnp.float32), shape(192, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bsort\(", text)
    assert "router_top_k" in text


# (rows, positions): the command-a-plus cell's decode call, suffix call and a
# chunk of its prefix prefill, against a 12,288-token prefix buffer
@pytest.mark.parametrize("rows, positions", [(8, 24), (8, 128), (1, 2048)])
def test_the_window_prefix_kernel_compiles_for_the_chip(one_chip, no_compile_cache, rows, positions):
    """ops/pallas_prefix_attention.py `window_prefix_attention` at the
    published heads (128 query heads of 128, 8 KV heads) and window (4,096):
    each row's lowest key a (q_block, 1) block beside the scalar-prefetched
    first block of each query block, compiled by Mosaic under its own name."""
    from k8s_llm_scheduler_tpu.ops.pallas_prefix_attention import window_prefix_attention

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda q, k, v, n, lo: window_prefix_attention(q, k, v, n, lo, window=4096, interpret=False)
    ).lower(shape(rows, positions, 128, 128), shape(12288, 8, 128), shape(12288, 8, 128),
            shape(dtype=jnp.int32), shape(rows, positions, dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "window_prefix_attention" in text
    assert "flash_prefix_attention_parts" not in text


def test_block_decode_copies_no_weight_out_of_its_stack(one_chip, no_compile_cache, monkeypatch):
    """models/cohere2_moe.py `forward_block_decode` at the command-a-plus
    cell's share (4 layers, 128 query heads, 16 held experts, a 12,288-token
    prefix buffer), compiled as the chip does: no weight of a layer is
    copied out of its stack into HBM on a model call. With W_q laid out [D,
    H hd] or [H hd, D], its four layers were copied out whole, 512 MB a call
    and a tenth of the cell's device time (PERF.md §5). A copy into VMEM
    (memory space S(1)) is the matmul's own read of a weight and is allowed;
    a copy in HBM reads and writes the weight once more."""
    import json
    import math
    from pathlib import Path

    from k8s_llm_scheduler_tpu.models import cohere2_moe
    from k8s_llm_scheduler_tpu.models.configs import Cohere2MoeConfig
    from k8s_llm_scheduler_tpu.ops import pallas_prefix_attention as ppa
    from k8s_llm_scheduler_tpu.ops import router_top_k as rtk

    for mod in (gm, ppa, rtk):
        monkeypatch.setattr(mod, "pallas_interpret", lambda interpret=None: False)
    path = Path(__file__).resolve().parents[1] / "benchmark" / "configs" / "command-a-plus-05-2026.json"
    conf = json.loads(path.read_text())
    cfg = Cohere2MoeConfig.from_hf(conf["name"], conf, expert_first=0, expert_count=16)

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: shape(*a.shape, dtype=a.dtype),
                                    jax.eval_shape(lambda k: cohere2_moe.init_params(k, cfg), jax.random.PRNGKey(0)))
    R, F, L, kv = 8, 24, cfg.n_layers, (cfg.n_kv_heads, cfg.head_dim)
    compiled = jax.jit(
        lambda *a: cohere2_moe.forward_block_decode(a[0], cfg, *a[1:], prefix_impl="pallas")
    ).lower(params, shape(R, F), shape(R, F, dtype=jnp.bool_), shape(R), shape(R, F),
            *(shape(L, R, 128, *kv, dtype=cfg.dtype) for _ in range(2)), shape(R),
            *(shape(L, R, 102, *kv, dtype=cfg.dtype) for _ in range(2)), shape(R),
            *(shape(L, 12288, *kv, dtype=cfg.dtype) for _ in range(2)), shape()).compile()
    # a layer's slice of a weight, whatever its layout or shape: no array of block decode's has that size
    sizes = {math.prod(leaf.shape[1:]) for leaf in jax.tree_util.tree_leaves(params["layers"])}
    copied, fused = [], False
    for line in compiled.as_text().splitlines():
        if line and not line.startswith(" "):   # a computation's header: fused ones run inside their consumer
            fused = "fused" in line.split(" ")[0] or "wrapped" in line.split(" ")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) (copy|fusion|transpose)\(", line)
        if fused or not m:
            continue
        for dims, layout in re.findall(r"bf16\[([0-9,]+)\]\{([^}]*)\}", m.group(1)):
            if math.prod(map(int, dims.split(","))) in sizes and "S(1)" not in layout:
                copied.append(line.strip()[:160])
    assert not copied, copied
