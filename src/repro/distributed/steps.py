"""pjit-ready step functions (train / prefill / decode) with sharding trees.

``build_*`` returns ``(fn, in_shardings, out_shardings, abstract_args)`` so
callers can either execute (``jax.jit(fn, ...)(...)``) or dry-run
(``.lower(*abstract).compile()``) against any mesh.  Donation is enabled for
params/optimizer/decode-state so ``memory_analysis`` reflects steady-state
HBM, not doubled buffers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import shapes as SH
from repro.distributed import constraints as C
from repro.distributed import sharding as S
from repro.models import params as MP
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.train import optimizer as O

Pytree = Any


@dataclasses.dataclass(frozen=True)
class StepBundle:
    fn: Callable
    in_shardings: Tuple
    out_shardings: Tuple
    abstract_args: Tuple
    donate_argnums: Tuple[int, ...] = ()

    def jit(self):
        return jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )

    def lower(self):
        return self.jit().lower(*self.abstract_args)


# ---------------------------------------------------------------------------
# Optimizer-state shardings (mirrors optimizer.init structure)
# ---------------------------------------------------------------------------

def opt_state_shardings(
    cfg: ModelConfig, mesh: Mesh, strategy: S.Strategy, opt_cfg: O.OptConfig
) -> Pytree:
    specs = MP.param_specs(cfg)

    def moment_m(spec: MP.ParamSpec):
        base = S.opt_state_sharding_for(spec, mesh, strategy)
        if opt_cfg.state_dtype == "q8":
            _, sshape = O._q8_shapes(spec.shape)
            scale = NamedSharding(
                mesh,
                S.spec_for(
                    sshape, spec.logical_axes,
                    {**strategy.param_rules, **strategy.opt_rules}, mesh,
                ),
            )
            return {"q": base, "scale": scale}
        return base

    def moment_v(spec: MP.ParamSpec):
        return S.opt_state_sharding_for(spec, mesh, strategy)

    is_spec = lambda x: isinstance(x, MP.ParamSpec)
    return {
        "m": jax.tree.map(moment_m, specs, is_leaf=is_spec),
        "v": jax.tree.map(moment_v, specs, is_leaf=is_spec),
        "count": S.replicated(mesh),
    }


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(
    cfg: ModelConfig,
    opt_cfg: O.OptConfig,
    *,
    remat: str = "dots",
    microbatches: int = 1,
) -> Callable:
    def loss_fn(p, b):
        loss, metrics = T.train_loss(p, cfg, b, remat=remat)
        return loss, metrics

    def train_step(params, opt_state, batch, seed):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            mb = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]),
                batch,
            )

            def acc(carry, b):
                g_acc, l_acc = carry
                b = jax.tree.map(
                    lambda x: C.constrain(
                        x, ("batch",) + (None,) * (x.ndim - 1)
                    ),
                    b,
                )
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, b)
                g_acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), g_acc, g
                )
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(acc, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss_sum / microbatches
            metrics = {"loss": loss}
        rng = jax.random.PRNGKey(seed)
        params, opt_state, om = O.apply(grads, params, opt_state, opt_cfg, rng)
        out_metrics = {"loss": metrics.get("loss", loss), **om}
        return params, opt_state, out_metrics

    return train_step


def build_train_step(
    cfg: ModelConfig,
    shape: SH.ShapeSpec,
    mesh: Mesh,
    strategy: S.Strategy,
    opt_cfg: Optional[O.OptConfig] = None,
    *,
    remat: str = "dots",
    microbatches: int = 1,
) -> StepBundle:
    opt_cfg = opt_cfg or O.OptConfig()
    fn = make_train_step(cfg, opt_cfg, remat=remat, microbatches=microbatches)

    abstract_params = MP.abstract_params(cfg)
    abstract_opt = O.abstract_state(abstract_params, opt_cfg)
    bspecs = SH.batch_specs(cfg, shape)
    seed_spec = jax.ShapeDtypeStruct((), jnp.int32)

    p_shard = S.param_shardings(cfg, mesh, strategy)
    o_shard = opt_state_shardings(cfg, mesh, strategy, opt_cfg)
    b_shard = S.batch_shardings(cfg, bspecs, mesh, strategy)
    rep = S.replicated(mesh)

    # Metrics tree: loss/lr/grad_norm scalars -> replicated.
    return StepBundle(
        fn=C.wrap(fn, mesh, strategy.act_rules),
        in_shardings=(p_shard, o_shard, b_shard, rep),
        out_shardings=(p_shard, o_shard, rep),
        abstract_args=(abstract_params, abstract_opt, bspecs, seed_spec),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------------
# Prefill / decode (serving)
# ---------------------------------------------------------------------------

def build_prefill_step(
    cfg: ModelConfig,
    shape: SH.ShapeSpec,
    mesh: Mesh,
    strategy: S.Strategy,
    *,
    remat: str = "dots",
    max_len: int = 0,
) -> StepBundle:
    """``max_len``: decode-cache length, when decoding is to follow the
    ``shape.seq_len``-token prompt (default: the prompt fills the cache)."""
    max_len = max_len or shape.seq_len

    def prefill_step(params, batch):
        return T.prefill(params, cfg, batch, max_len=max_len, remat=remat)

    abstract_params = MP.abstract_params(cfg)
    bspecs = SH.batch_specs(cfg, shape)
    p_shard = S.param_shardings(cfg, mesh, strategy)
    b_shard = S.batch_shardings(cfg, bspecs, mesh, strategy)
    state_specs = jax.eval_shape(
        lambda: T.init_decode_state(cfg, shape.global_batch, max_len)
    )
    st_shard = S.decode_state_shardings(cfg, state_specs, mesh, strategy)
    logits_shard = NamedSharding(mesh, P(("pod", "data") if "pod" in mesh.axis_names else "data"))

    return StepBundle(
        fn=C.wrap(prefill_step, mesh, strategy.act_rules),
        in_shardings=(p_shard, b_shard),
        out_shardings=(logits_shard, st_shard),
        abstract_args=(abstract_params, bspecs),
    )


def build_decode_step(
    cfg: ModelConfig,
    shape: SH.ShapeSpec,
    mesh: Mesh,
    strategy: S.Strategy,
) -> StepBundle:
    def decode_fn(params, state, batch, idx):
        return T.decode_step(params, cfg, state, batch, idx)

    abstract_params = MP.abstract_params(cfg)
    bspecs = SH.batch_specs(cfg, shape)
    state_specs = SH.decode_state_specs(cfg, shape)
    idx_spec = jax.ShapeDtypeStruct((), jnp.int32)

    p_shard = S.param_shardings(cfg, mesh, strategy)
    b_shard = S.batch_shardings(cfg, bspecs, mesh, strategy)
    st_shard = S.decode_state_shardings(cfg, state_specs, mesh, strategy)
    rep = S.replicated(mesh)
    batch_axes = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    bsz = shape.global_batch
    import math

    n_dp = math.prod(
        dict(zip(mesh.axis_names, mesh.devices.shape)).get(a, 1) for a in batch_axes
    )
    logits_shard = (
        NamedSharding(mesh, P(batch_axes)) if bsz % n_dp == 0 else rep
    )

    return StepBundle(
        fn=C.wrap(decode_fn, mesh, strategy.act_rules),
        in_shardings=(p_shard, st_shard, b_shard, rep),
        out_shardings=(logits_shard, st_shard),
        abstract_args=(abstract_params, state_specs, bspecs, idx_spec),
        donate_argnums=(1,),
    )


def build_step(
    cfg: ModelConfig,
    shape: SH.ShapeSpec,
    mesh: Mesh,
    strategy: S.Strategy,
    **kw,
) -> StepBundle:
    if shape.kind == SH.TRAIN:
        return build_train_step(cfg, shape, mesh, strategy, **kw)
    if shape.kind == SH.PREFILL:
        kw.pop("opt_cfg", None), kw.pop("microbatches", None)
        return build_prefill_step(cfg, shape, mesh, strategy, **{k: v for k, v in kw.items() if k == "remat"})
    if shape.kind == SH.DECODE:
        return build_decode_step(cfg, shape, mesh, strategy)
    raise ValueError(shape.kind)
