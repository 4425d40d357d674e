"""Pipeline-parallel (GPipe-style) training for the decision model.

Completes the parallelism vocabulary next to dp/fsdp/tp/sp
(train/train_step.py): the transformer trunk is split into `pp` STAGES —
each device on the pp mesh axis holds a contiguous block of layers — and a
batch is fed through as microbatches on the classic GPipe schedule: at tick
t, stage s runs microbatch (t - s) and hands its activations to stage s+1
over the ICI ring (`lax.ppermute` inside `shard_map`). The backward
pipeline is DERIVED by autodiff: ppermute's transpose is the reverse
permute, so `jax.grad` through the scheduled forward yields the mirrored
activation/gradient flow with no hand-written backward.

TPU-first notes:
- Stage-sharded weights: the stacked layer pytree [L, ...] reshapes to
  [pp, L/pp, ...] and shards its leading axis over the pp ring — each
  device materializes only its own layers (what makes 70B-scale trunks fit
  per-host HBM without fsdp).
- Activations move stage-to-stage by neighbor ppermute — point-to-point ICI
  traffic, never an all-gather of the trunk.
- The schedule is a lax.scan over pp + n_micro - 1 ticks with masked
  injection/collection — static shapes, no Python control flow in jit.
- Composes with dp (batch axis): mesh {dp, pp}. tp/sp inside a stage would
  need manual collectives under shard_map and is out of scope here — use
  the GSPMD train step (train_step.py) for those axes.

The reference has no training surface at all (SURVEY §2.3): all of its
model parallelism happened server-side behind the HF API.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from k8s_llm_scheduler_tpu.models.configs import LlamaConfig
from k8s_llm_scheduler_tpu.models.llama import (
    Params,
    _logits,
    init_params,
    prefill_layer,
    rope_inv_freq,
)
from k8s_llm_scheduler_tpu.train.train_step import TrainState, causal_lm_loss


def stage_params(params: Params, n_stages: int) -> Params:
    """Reshape the stacked layer pytree [L, ...] -> [pp, L/pp, ...]."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    if L % n_stages:
        raise ValueError(f"n_layers={L} not divisible by pp={n_stages}")
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, L // n_stages, *a.shape[1:]),
        params["layers"],
    )
    return out


def make_pp_train_step(
    cfg: LlamaConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation | None = None,
    n_micro: int | None = None,
) -> tuple[Callable, Callable]:
    """Build (init_fn, step_fn) with the trunk pipelined over the pp axis.

    Mesh axes: pp (required, size >= 2) and optionally dp. Batch must be
    divisible by dp * n_micro. Returns the same (init_fn, step_fn) surface
    as make_train_step, with step_fn.place_batch for input placement.
    """
    optimizer = optimizer or optax.adamw(1e-5)
    axes = dict(mesh.shape)
    n_stages = axes.get("pp", 1)
    if n_stages < 2:
        raise ValueError("make_pp_train_step needs a pp mesh axis of size >= 2")
    unsupported = [a for a in ("tp", "sp", "fsdp") if axes.get(a, 1) > 1]
    if unsupported:
        raise ValueError(
            f"pp composes with dp only; use train_step.make_train_step for {unsupported}"
        )
    dp = "dp" if axes.get("dp", 1) > 1 else None
    n_micro_ = n_micro or 2 * n_stages
    inv_freq = rope_inv_freq(cfg)

    def trunk(x, seq_lens, stage_layers):
        """Pipelined trunk under shard_map: x [Bl, S, D] (dp-local,
        pp-replicated) -> same shape, after all L layers."""
        s = jax.lax.axis_index("pp")
        # local view keeps the split pp axis as a size-1 leading dim
        stage_layers = jax.tree_util.tree_map(lambda a: a[0], stage_layers)
        Bl, S, D = x.shape
        if Bl % n_micro_:
            raise ValueError(
                f"local batch {Bl} not divisible by n_micro={n_micro_}"
            )
        Bm = Bl // n_micro_
        micro_x = x.reshape(n_micro_, Bm, S, D)
        micro_lens = seq_lens.reshape(n_micro_, Bm)
        positions = jnp.broadcast_to(jnp.arange(S), (Bm, S))

        def apply_stage(h, lens):
            def body(h, lp):
                h, _ = prefill_layer(lp, cfg, h, positions, lens, inv_freq)
                return h, None

            h, _ = jax.lax.scan(body, h, stage_layers)
            return h

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # stage s works on microbatch t - s (clipped; masked when out of range)
            mb = jnp.clip(t - s, 0, n_micro_ - 1)
            x_in = jnp.where(s == 0, micro_x[jnp.clip(t, 0, n_micro_ - 1)], buf)
            lens = micro_lens[mb]
            h = apply_stage(x_in, lens)
            # last stage collects its finished microbatch BEFORE the shift
            out_idx = t - (n_stages - 1)
            collect = (out_idx >= 0) & (out_idx < n_micro_) & (s == n_stages - 1)
            upd = outs.at[jnp.clip(out_idx, 0, n_micro_ - 1)].set(h)
            outs = jnp.where(collect, upd, outs)
            buf = jax.lax.ppermute(h, "pp", perm)
            return (buf, outs), None

        buf0 = jnp.zeros((Bm, S, D), x.dtype)
        outs0 = jnp.zeros((n_micro_, Bm, S, D), x.dtype)
        if hasattr(jax.lax, "pvary"):
            # newer jax: scan carries must carry the same varying-manual-axes
            # type as the tick outputs (which vary over the mesh axes)
            buf0 = jax.lax.pvary(buf0, tuple(mesh.axis_names))
            outs0 = jax.lax.pvary(outs0, tuple(mesh.axis_names))
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(n_stages + n_micro_ - 1)
        )
        # replicate the result across the pp ring (only the last stage holds it)
        outs = jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pp")
        return outs.reshape(Bl, S, D)

    trunk_sharded = shard_map(
        trunk,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(dp), P("pp")),
        out_specs=P(dp, None, None),
    )

    data_sharding = NamedSharding(mesh, P(dp, None))
    lens_sharding = NamedSharding(mesh, P(dp))

    def loss_fn(params, tokens, seq_lens):
        x = params["embed"][tokens]
        x = trunk_sharded(x, seq_lens, params["layers"])
        logits = _logits(params, cfg, x)
        return causal_lm_loss(logits, tokens, seq_lens)

    @jax.jit
    def step_fn(state: TrainState, tokens, seq_lens):
        tokens = jax.lax.with_sharding_constraint(tokens, data_sharding)
        loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens, seq_lens)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    def init_fn(rng: jax.Array) -> TrainState:
        params = stage_params(init_params(rng, cfg), n_stages)
        specs: Params = {
            "embed": P(),
            "final_norm": P(),
            "layers": jax.tree_util.tree_map(lambda _: P("pp"), params["layers"]),
        }
        if "lm_head" in params:
            specs["lm_head"] = P()
        params = jax.tree_util.tree_map(
            lambda a, sp: jax.device_put(a, NamedSharding(mesh, sp)), params, specs
        )
        opt_state = jax.jit(optimizer.init)(params)  # moments inherit shardings
        return TrainState(params, opt_state, jnp.zeros((), jnp.int32))

    def place_batch(tokens, seq_lens):
        return (
            jax.device_put(tokens, data_sharding),
            jax.device_put(seq_lens, lens_sharding),
        )

    step_fn.place_batch = place_batch  # type: ignore[attr-defined]
    return init_fn, step_fn
