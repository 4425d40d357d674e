"""Checkpoint loading: HF safetensors -> sharded JAX params, orbax native.

The reference never loads weights — its 70B lives behind the HuggingFace
API (reference scheduler.py:425, config.yaml:8). Self-hosting the decision
LLM makes weight loading a real subsystem (SURVEY §5 checkpoint/resume;
§7 hard part #1: 70B into a TP mesh without host-RAM blowups):

- **Streaming HF import**: `load_hf_checkpoint` walks the model's
  safetensors shard files tensor by tensor. Each per-layer tensor is
  transposed to this framework's [in, out] einsum layout and written
  straight into its stacked parameter's DEVICE buffer — preallocated
  sharded on the mesh, updated in place via a donated
  dynamic_update_index_in_dim. Peak host memory is ONE LAYER tensor
  (~0.5 GB for the 70B MLP matrix in bf16), never a stacked parameter
  and never the model: HF shards interleave parameter kinds, so
  accumulating stacked host buffers would approach the full 140 GB.
- **Direct-to-shard placement**: with a mesh + PartitionSpecs
  (parallel/sharding.py), layer slices and top-level tensors are placed
  via `jax.device_put(x, NamedSharding(mesh, spec))` — XLA slices the
  host array straight onto the devices; nothing is replicated on host.
- **Native checkpoints**: orbax save/restore of the params pytree for
  fast resume (resharding happens at restore via the same specs).

HF -> framework tensor map (Llama 3.x family):
  model.embed_tokens.weight            -> embed                [V, D]
  model.layers.{i}.input_layernorm     -> layers.attn_norm[i]  [D]
  model.layers.{i}.self_attn.q_proj    -> layers.wq[i]         [D, H*hd] (T)
  model.layers.{i}.self_attn.k_proj    -> layers.wk[i]         [D, KV*hd] (T)
  model.layers.{i}.self_attn.v_proj    -> layers.wv[i]         [D, KV*hd] (T)
  model.layers.{i}.self_attn.o_proj    -> layers.wo[i]         [H*hd, D] (T)
  model.layers.{i}.post_attention_layernorm -> layers.mlp_norm[i]
  model.layers.{i}.mlp.gate_proj       -> layers.w_gate[i]     [D, F] (T)
  model.layers.{i}.mlp.up_proj         -> layers.w_up[i]       [D, F] (T)
  model.layers.{i}.mlp.down_proj       -> layers.w_down[i]     [F, D] (T)
  model.norm.weight                    -> final_norm           [D]
  lm_head.weight                       -> lm_head              [D, V] (T)
  (lm_head absent => tie_embeddings; HF rotary is half-split, matching
   models/llama.apply_rope — no permutation needed.)
"""

from __future__ import annotations

import json
import logging
import os
import re
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import Params
from k8s_llm_scheduler_tpu.parallel.sharding import param_specs

logger = logging.getLogger(__name__)

from k8s_llm_scheduler_tpu.models.quant import (  # noqa: E402
    QUANT_KEYS as _QUANT_KEYS,
    _quantize_weight_donated as _quantize_donated,
)

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)\.weight$")

# HF suffix -> (param key under "layers", transpose?)
_LAYER_MAP = {
    "input_layernorm": ("attn_norm", False),
    "self_attn.q_proj": ("wq", True),
    "self_attn.k_proj": ("wk", True),
    "self_attn.v_proj": ("wv", True),
    "self_attn.o_proj": ("wo", True),
    "post_attention_layernorm": ("mlp_norm", False),
    "mlp.gate_proj": ("w_gate", True),
    "mlp.up_proj": ("w_up", True),
    "mlp.down_proj": ("w_down", True),
}

_TOP_MAP = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}


def _expected_shapes(cfg: LlamaConfig) -> dict[str, tuple[int, ...]]:
    hd = cfg.head_dim
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers.attn_norm": (L, D),
        "layers.wq": (L, D, cfg.n_heads * hd),
        "layers.wk": (L, D, cfg.n_kv_heads * hd),
        "layers.wv": (L, D, cfg.n_kv_heads * hd),
        "layers.wo": (L, cfg.n_heads * hd, D),
        "layers.mlp_norm": (L, D),
        "layers.w_gate": (L, D, F),
        "layers.w_up": (L, D, F),
        "layers.w_down": (L, F, D),
    }
    if not cfg.tie_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def _flat_specs(cfg: LlamaConfig, tp: str | None, fsdp: str | None):
    specs = param_specs(cfg, tp=tp, fsdp=fsdp)
    flat = {"embed": specs["embed"], "final_norm": specs["final_norm"]}
    for k, v in specs["layers"].items():
        flat[f"layers.{k}"] = v
    if "lm_head" in specs:
        flat["lm_head"] = specs["lm_head"]
    return flat


def checkpoint_files(path: str | Path) -> list[Path]:
    """The safetensors shards of an HF checkpoint dir, index-ordered."""
    path = Path(path)
    index = path / "model.safetensors.index.json"
    if index.exists():
        with open(index) as f:
            weight_map = json.load(f)["weight_map"]
        return [path / name for name in sorted(set(weight_map.values()))]
    single = path / "model.safetensors"
    if single.exists():
        return [single]
    files = sorted(path.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no safetensors files under {path}")
    return files


def load_hf_checkpoint(
    path: str | Path,
    cfg: LlamaConfig,
    mesh: Mesh | None = None,
    *,
    tp: str | None = "tp",
    fsdp: str | None = None,
    dtype: Any | None = None,
    quantize: str | None = None,
) -> Params:
    """Stream an HF Llama safetensors checkpoint into (sharded) JAX params.

    Walks shard files tensor by tensor. Each per-layer tensor is written
    STRAIGHT into its stacked parameter's device buffer (allocated sharded
    on the mesh up front; the write is a donated
    dynamic_update_index_in_dim, so it is in-place) — host peak is ONE
    LAYER tensor, not a stacked parameter. HF shard files interleave the
    parameter kinds, so accumulating stacked host buffers per kind would
    hold nearly the whole model in host RAM at 70B scale (~140 GB).
    """
    from safetensors import safe_open

    dtype = dtype or cfg.dtype
    shapes = _expected_shapes(cfg)
    flat_specs = _flat_specs(cfg, tp, fsdp)

    def place(name: str, host: np.ndarray | jax.Array) -> jax.Array:
        if mesh is not None:
            return jax.device_put(host, NamedSharding(mesh, flat_specs[name]))
        return jnp.asarray(host)

    def alloc(name: str) -> jax.Array:
        if mesh is not None:
            return jax.jit(
                lambda: jnp.zeros(shapes[name], dtype),
                out_shardings=NamedSharding(mesh, flat_specs[name]),
            )()
        return jnp.zeros(shapes[name], dtype)

    set_layer = jax.jit(  # graftlint: ok[donated-buffer-escape] — pure index update: in/out shardings are identical by construction, so XLA aliases the donation without a bundle
        lambda buf, x, i: jax.lax.dynamic_update_index_in_dim(buf, x, i, 0),
        donate_argnums=(0,),
    )

    filled: dict[str, int] = {}
    out_flat: dict[str, jax.Array] = {}

    for file in checkpoint_files(path):
        with safe_open(str(file), framework="np") as f:
            for hf_name in f.keys():
                m = _LAYER_RE.match(hf_name)
                if m:
                    layer, suffix = int(m.group(1)), m.group(2)
                    if suffix not in _LAYER_MAP:
                        logger.warning("skipping unknown tensor %s", hf_name)
                        continue
                    key, transpose = _LAYER_MAP[suffix]
                    name = f"layers.{key}"
                    if layer >= cfg.n_layers:
                        raise ValueError(
                            f"{hf_name}: layer {layer} >= n_layers={cfg.n_layers}"
                        )
                    tensor = f.get_tensor(hf_name)
                    if transpose:
                        tensor = np.ascontiguousarray(tensor.T)
                    if tensor.shape != shapes[name][1:]:
                        raise ValueError(
                            f"{hf_name}: shape {tensor.shape} != expected "
                            f"{shapes[name][1:]}"
                        )
                    if name not in out_flat:
                        out_flat[name] = alloc(name)
                        filled[name] = 0
                    host = _cast(tensor, dtype)
                    if mesh is not None:
                        spec = flat_specs[name]
                        slice_spec = P(*spec[1:]) if len(spec) > 1 else P()
                        dev = jax.device_put(
                            host, NamedSharding(mesh, slice_spec)
                        )
                    else:
                        dev = jnp.asarray(host)
                    out_flat[name] = set_layer(
                        out_flat[name], dev, jnp.int32(layer)
                    )
                    filled[name] += 1
                    if (
                        quantize == "int8"
                        and filled[name] == cfg.n_layers
                        and name.split(".", 1)[1] in _QUANT_KEYS
                    ):
                        out_flat[name] = _quantize_donated(out_flat[name])
                elif hf_name in _TOP_MAP:
                    name, transpose = _TOP_MAP[hf_name]
                    if name == "lm_head" and cfg.tie_embeddings:
                        logger.info("ignoring lm_head (tied embeddings)")
                        continue
                    tensor = f.get_tensor(hf_name)
                    if transpose:
                        tensor = np.ascontiguousarray(tensor.T)
                    if tensor.shape != shapes[name]:
                        raise ValueError(
                            f"{hf_name}: shape {tensor.shape} != expected {shapes[name]}"
                        )
                    out_flat[name] = place(name, _cast(tensor, dtype))
                else:
                    logger.warning("skipping unknown tensor %s", hf_name)

    missing = set(shapes) - set(out_flat)
    partial = {
        n: f"{filled[n]}/{cfg.n_layers}"
        for n in filled
        if filled[n] < cfg.n_layers
    }
    if missing or partial:
        raise ValueError(
            f"checkpoint incomplete: missing {sorted(missing)}"
            + (f"; partial layer stacks {partial}" if partial else "")
        )

    params: Params = {
        "embed": out_flat["embed"],
        "final_norm": out_flat["final_norm"],
        "layers": {
            k.split(".", 1)[1]: v
            for k, v in out_flat.items()
            if k.startswith("layers.")
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = out_flat["lm_head"]
    return params


def _cast(host: np.ndarray, dtype) -> np.ndarray:
    """Cast a host buffer to the target dtype HOST-SIDE (ml_dtypes handles
    bf16 in numpy). Staying on host matters: the only device transfer must
    be place()'s sharded device_put — routing through jnp.asarray here would
    commit the full stacked parameter to one device and OOM it at 70B."""
    import ml_dtypes

    if dtype == jnp.bfloat16:
        target = np.dtype(ml_dtypes.bfloat16)
    else:
        target = np.dtype(jnp.dtype(dtype).name)
    return host.astype(target, copy=False)


class CheckpointError(RuntimeError):
    """A native checkpoint is missing, torn, or shaped for another config.

    Raised by restore_checkpoint's pre-validation with the offending path
    and the FIRST mismatched param — instead of the deep orbax/tensorstore
    stack trace the raw restore produces for the same faults."""


# ------------------------------------------------------------------ orbax
def _fsync_tree(root: Path) -> None:
    """fsync every file and directory under `root` (and `root` itself):
    a rename is only crash-safe once the renamed tree's CONTENT is on
    disk — rename-then-crash with dirty pages can leave a torn tree
    under the final name, which is exactly the window save_checkpoint's
    bare renames used to carry (the registry's write-aside discipline,
    rollout/registry.py, fsyncs before every publish rename)."""
    for dirpath, _dirnames, filenames in os.walk(root):
        for fname in filenames:
            fd = os.open(os.path.join(dirpath, fname), os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
        fd = os.open(dirpath, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)


def save_checkpoint(path: str | Path, params: Params) -> None:
    """Write a native orbax checkpoint of the params pytree (overwrites —
    orbax's default refuses an existing dir AFTER a full training run has
    already been spent).

    ATOMIC against crashes: orbax's force=True DELETES the existing dir
    before writing, so a save that wedges mid-transfer would destroy the
    only snapshot a --resume run depends on. Write aside, fsync the staged tree, then swap — the
    fsync matters as much as the rename order: a crash between a bare
    rename and writeback would leave a TORN tree under the active name
    (the durability round's journal/registry discipline, now here
    too). The previous checkpoint survives as `.old` until the new one
    is durably in place."""
    import shutil

    import orbax.checkpoint as ocp

    path = Path(path).resolve()
    tmp = path.with_name(path.name + ".saving")
    if tmp.exists():
        shutil.rmtree(tmp)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(tmp, params, force=True)
        ckptr.wait_until_finished()
    _fsync_tree(tmp)
    old = path.with_name(path.name + ".old")
    if old.exists():
        shutil.rmtree(old)
    if path.exists():
        os.rename(path, old)
    os.rename(tmp, path)
    # make both renames durable before dropping the only fallback copy
    fd = os.open(path.parent, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
    if old.exists():
        shutil.rmtree(old)


def restore_checkpoint(
    path: str | Path,
    cfg: LlamaConfig,
    mesh: Mesh | None = None,
    *,
    tp: str | None = "tp",
    fsdp: str | None = None,
) -> Params:
    """Restore a native orbax checkpoint, resharded onto `mesh` (or one
    host device). Restoration is direct-to-shard: orbax reads only each
    device's slice of every parameter.

    Pre-validates before touching orbax's restore path: a missing dir, a
    partial/torn checkpoint (no orbax metadata), or a stored tree whose
    shapes don't match `cfg` raises CheckpointError naming the path and
    the first mismatched param — not a tensorstore traceback."""
    import orbax.checkpoint as ocp

    path = Path(path).resolve()
    if not path.exists():
        raise CheckpointError(f"checkpoint dir {path} does not exist")
    if not path.is_dir():
        raise CheckpointError(f"checkpoint path {path} is not a directory")
    if not any((path / marker).exists() for marker in ("_METADATA", "_CHECKPOINT_METADATA")):
        raise CheckpointError(
            f"{path} is not an orbax checkpoint (no _METADATA — partial or "
            f"torn save, or an HF safetensors dir passed to the native "
            f"restore path)"
        )
    shapes = _expected_shapes(cfg)
    flat_specs = _flat_specs(cfg, tp, fsdp)

    def abstract(name: str):
        if mesh is not None:
            sharding = NamedSharding(mesh, flat_specs[name])
        else:
            sharding = None
        return jax.ShapeDtypeStruct(shapes[name], cfg.dtype, sharding=sharding)

    target: Params = {
        "embed": abstract("embed"),
        "final_norm": abstract("final_norm"),
        "layers": {
            name.split(".", 1)[1]: abstract(name)
            for name in shapes
            if name.startswith("layers.")
        },
    }
    if not cfg.tie_embeddings:
        target["lm_head"] = abstract("lm_head")
    with ocp.StandardCheckpointer() as ckptr:
        _validate_stored_shapes(ckptr, path, cfg, shapes)
        try:
            return ckptr.restore(path, target)
        except Exception as exc:
            raise CheckpointError(
                f"restore of {path} failed for config {cfg.name!r}: {exc}"
            ) from exc


def _validate_stored_shapes(ckptr, path: Path, cfg: LlamaConfig, shapes) -> None:
    """Compare the stored tree's metadata against the config's expected
    shapes; raise CheckpointError on the first mismatch or missing param."""
    try:
        meta = ckptr.metadata(path)
    except Exception:
        # metadata unreadable on this orbax version/layout: fall through to
        # restore, whose failures are wrapped in CheckpointError anyway
        return
    # orbax >= 0.11 returns a StepMetadata with the tree under
    # .item_metadata (a TreeMetadata: subscriptable, not a dict); older
    # versions returned the tree itself. None = no tree was stored.
    tree = getattr(meta, "item_metadata", meta)
    if tree is None:
        return

    def lookup(name: str):
        node = tree
        for part in name.split("."):
            try:
                node = node[part]
            except (KeyError, IndexError, TypeError):
                return None
        return node

    for name in sorted(shapes):
        leaf = lookup(name)
        if leaf is None:
            raise CheckpointError(
                f"{path}: checkpoint is missing param {name!r} expected by "
                f"config {cfg.name!r}"
            )
        stored = tuple(getattr(leaf, "shape", ()) or ())
        if stored and stored != tuple(shapes[name]):
            raise CheckpointError(
                f"{path}: param {name!r} has shape {stored}, but config "
                f"{cfg.name!r} expects {tuple(shapes[name])} — the "
                f"checkpoint was trained for a different config"
            )
