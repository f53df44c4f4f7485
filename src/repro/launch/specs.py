"""input_specs + sharding resolution for every (arch x shape x mesh) cell.

``input_specs(cfg, shape)`` returns weak-type-correct ShapeDtypeStruct
stand-ins for every step input (no allocation), plus the PartitionSpec
trees the launcher turns into NamedShardings.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.config import SHAPES, ModelConfig, RunPlan, ShapeSpec
from repro.models import encdec, lm, nn
from repro.fl import steps as steps_mod
from . import mesh as mesh_mod


def fit_spec(spec: P, shape: tuple[int, ...], sizes: dict[str, int]) -> P:
    """Drop mesh axes from dims they don't divide (e.g. batch=1 decode)."""
    out = []
    for i, part in enumerate(spec):
        if part is None:
            out.append(None)
            continue
        axes = part if isinstance(part, tuple) else (part,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        if i < len(shape) and shape[i] % total == 0:
            out.append(part)
        else:
            out.append(None)
    return P(*out)


def fit_specs_tree(specs, shapes, sizes):
    return jax.tree.map(
        lambda s, x: fit_spec(s, x.shape, sizes),
        specs,
        shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


class Cell(NamedTuple):
    """Everything needed to lower one (arch x shape x mesh) cell."""

    cfg: ModelConfig
    shape: ShapeSpec
    plan: RunPlan
    step_fn: Any
    args: tuple  # ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _batch_specs(cfg: ModelConfig, spec: ShapeSpec, *, with_labels: bool) -> tuple[dict, dict]:
    B, S = spec.global_batch, spec.seq_len
    batch, bspecs = {}, {}
    dp = nn.DP
    if cfg.is_encoder_decoder:
        batch["embeds"] = _sds((B, S, cfg.d_model), cfg.jdtype)  # frontend stub
        bspecs["embeds"] = P(dp, None, None)
        batch["tokens"] = _sds((B, S), jnp.int32)
        bspecs["tokens"] = P(dp, None)
    elif cfg.embed_inputs and spec.kind in ("train", "prefill"):
        batch["embeds"] = _sds((B, S, cfg.d_model), cfg.jdtype)  # patch embeds stub
        bspecs["embeds"] = P(dp, None, None)
    else:
        batch["tokens"] = _sds((B, S), jnp.int32)
        bspecs["tokens"] = P(dp, None)
    if with_labels:
        batch["labels"] = _sds((B, S), jnp.int32)
        bspecs["labels"] = P(dp, None)
    return batch, bspecs


def resolve(tree, *, multi_pod: bool, pod_replicated: bool):
    """Resolve logical placeholders; pod_replicated forces fsdp=('data',)."""
    return nn.resolve_specs(tree, multi_pod=multi_pod and not pod_replicated)


def build_cell(arch: str, shape_name: str, mesh, *, aggregation: str | None = None,
               cfg_overrides: dict | None = None, grad_accum: int | None = None) -> Cell:
    cfg = configs.get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    spec = SHAPES[shape_name]
    plan = configs.get_plan(arch, shape_name)
    import dataclasses

    if aggregation is not None:
        plan = dataclasses.replace(plan, aggregation=aggregation)
    if grad_accum is not None:
        plan = dataclasses.replace(plan, grad_accum=grad_accum)
    sizes = mesh_mod.mesh_axis_sizes(mesh)
    multi_pod = "pod" in sizes
    npods = sizes.get("pod", 1)
    # Totoro tree mode: params replicated across pods (zone replicas)
    pod_replicated = plan.aggregation.startswith("totoro_tree") and multi_pod and spec.kind == "train"

    def rs(tree):
        """Param/state resolution (pod-replicated in totoro_tree mode)."""
        return resolve(tree, multi_pod=multi_pod, pod_replicated=pod_replicated)

    def rs_batch(tree):
        """Batch/cache resolution — always sharded across pods when present."""
        return resolve(tree, multi_pod=multi_pod, pod_replicated=False)

    # activation sharding axes for with_sharding_constraint inside the graph.
    # Batch dims are sharded over ('pod','data') even when params are
    # pod-replicated (zones process disjoint clients); in the podded-vmap
    # (q8) mode the pod dim is outside the vmapped view, so 'data' only.
    podded_mode = plan.aggregation == "totoro_tree_q8" and multi_pod and spec.kind == "train"
    if multi_pod and not podded_mode:
        nn.set_activation_axes(dp=("pod", "data"), tp="model", sp="model", sizes=sizes)
    else:
        nn.set_activation_axes(dp="data", tp="model", sp="model", sizes=sizes)

    model = encdec if cfg.is_encoder_decoder else lm
    key = jax.random.key(0)
    params_shapes = jax.eval_shape(lambda k: model.init_params(k, cfg), key)
    pspecs = rs(model.param_specs(cfg))
    pspecs = fit_specs_tree(pspecs, params_shapes, sizes)

    if spec.kind == "train":
        podded = plan.aggregation == "totoro_tree_q8" and multi_pod
        state_shapes = jax.eval_shape(
            lambda k: steps_mod.init_train_state(
                cfg, model.init_params(k, cfg), num_pods=npods, podded=podded
            ),
            key,
        )
        sspecs = steps_mod.train_state_specs(cfg, pspecs, params_shapes, podded=podded)
        sspecs = fit_specs_tree(sspecs, state_shapes, sizes)
        batch, bspecs = _batch_specs(cfg, spec, with_labels=True)
        bspecs = rs_batch(bspecs)
        bspecs = fit_specs_tree(bspecs, batch, sizes)
        step = steps_mod.build_train_step(cfg, plan, num_pods=npods)
        in_sh = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs, is_leaf=lambda x: isinstance(x, P)),
        )
        out_sh = (in_sh[0], NamedSharding(mesh, P()))
        return Cell(cfg, spec, plan, step, (state_shapes, batch), in_sh, out_sh, (0,))

    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
    B, S = spec.global_batch, spec.seq_len

    if spec.kind == "prefill":
        batch, bspecs = _batch_specs(cfg, spec, with_labels=False)
        bspecs = fit_specs_tree(rs_batch(bspecs), batch, sizes)
        if cfg.is_encoder_decoder:
            cache_shp, cache_specs = encdec.cache_shapes(cfg, B, S, S)
        else:
            cache_shp, cache_specs = lm.cache_shapes(cfg, B, S)
        cache_specs = fit_specs_tree(rs_batch(cache_specs), cache_shp, sizes)
        step = steps_mod.build_prefill_step(cfg)
        in_sh = (
            pshard,
            jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs, is_leaf=lambda x: isinstance(x, P)),
        )
        out_sh = (
            jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs, is_leaf=lambda x: isinstance(x, P)),
            NamedSharding(mesh, P()),
        )
        return Cell(cfg, spec, plan, step, (params_shapes, batch), in_sh, out_sh, ())

    assert spec.kind == "decode"
    if cfg.is_encoder_decoder:
        cache_shp, cache_specs = encdec.cache_shapes(cfg, B, S, S)
    else:
        cache_shp, cache_specs = lm.cache_shapes(cfg, B, S)
    cache_specs = fit_specs_tree(rs_batch(cache_specs), cache_shp, sizes)
    cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cache_specs, is_leaf=lambda x: isinstance(x, P))
    token = _sds((B, 1), jnp.int32)
    dp_part = ("pod", "data") if multi_pod else ("data",)
    tok_spec = fit_spec(P(dp_part, None), token.shape, sizes)
    tok_sh = NamedSharding(mesh, tok_spec)
    idx = _sds((), jnp.int32)
    idx_sh = NamedSharding(mesh, P())
    step = steps_mod.build_decode_step(cfg)
    in_sh = (pshard, cache_sh, tok_sh, idx_sh)
    out_sh = (cache_sh, NamedSharding(mesh, fit_spec(P(("pod", "data") if multi_pod else ("data",)), (B,), sizes)))
    return Cell(cfg, spec, plan, step, (params_shapes, cache_shp, token, idx), in_sh, out_sh, (1,))


def lower_cell(cell: Cell, mesh):
    jitted = jax.jit(
        cell.step_fn,
        in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings,
        donate_argnums=cell.donate_argnums,
    )
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*cell.args)
        return lowered
