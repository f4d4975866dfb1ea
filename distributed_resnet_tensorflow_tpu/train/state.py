"""Train state: params + BN batch stats + optimizer state + step.

Successor of the reference's implicit graph-collection state — TF global
variables, BN moving averages updated via UPDATE_OPS control deps (reference
resnet_model.py:118-121), optimizer slots on the parameter servers. Here it
is one explicit pytree, shardable leaf-by-leaf via NamedSharding.

Precision contract (parallel/precision.py; docs/precision.md): every
float leaf of this state — params, BN stats, optimizer moments — is an
f32 MASTER regardless of the ``train.precision`` policy. The bf16 policy
lives entirely in the APPLY (the model's compute dtype casts masters
per-op; the cast's transpose re-accumulates gradients into f32), so
checkpoints, restores and the serving hot swap never see a cast leaf —
``Trainer.init_state`` guards this with ``check_master_dtypes``.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.sharding import tree_param_shardings


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    batch_stats: Any
    opt_state: Any
    # static (not traced):
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    @jax.named_scope("optimizer")  # names the update's ops in a trace
    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        return self.replace(step=self.step + 1, params=new_params,
                            opt_state=new_opt_state)


def _make_init_fn(model, tx, input_shape):
    # a shape of float32, or a ShapeDtypeStruct where the model's input is
    # no float image (token ids)
    dummy = jnp.zeros(getattr(input_shape, "shape", input_shape),
                      getattr(input_shape, "dtype", jnp.float32))

    def init_fn(rng):
        variables = model.init(rng, dummy, train=False)
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        opt_state = tx.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats=batch_stats, opt_state=opt_state,
                          apply_fn=model.apply, tx=tx)

    return init_fn


def init_input(model, cfg, rows: int):
    """What ``rows`` examples of a model's ``init`` input look like: the
    family's own (token ids: models/transformer.CausalDecoder.init_input),
    else the float32 vector or image the config sizes. The ONE place the
    trainer and the static elaborators (analysis/) size an init batch."""
    if hasattr(model, "init_input"):
        return model.init_input(rows, cfg.data)
    if cfg.model.name == "logistic":
        return (rows, cfg.model.input_size)
    return (rows, cfg.data.image_size, cfg.data.image_size, 3)


def abstract_train_state(model, tx, input_shape) -> TrainState:
    """Shape/dtype-only TrainState — zero data, zero compute. The static
    elaborator (analysis/elaborate.py) builds model states for every
    preset × mesh layout this way; create_train_state uses the same init
    function, so the abstract state and the real one cannot drift."""
    return jax.eval_shape(_make_init_fn(model, tx, input_shape),
                          jax.random.PRNGKey(0))


def create_train_state(rng: jax.Array, model, tx, input_shape,
                       mesh: Mesh = None, zero1: bool = False,
                       zero1_min_size: int = 0) -> TrainState:
    """Initialize model + optimizer state.

    When a mesh is given, init runs under jit with output shardings so large
    params materialize directly sharded (never gathered on one host) — the
    replacement for both replica_device_setter placement (reference
    resnet_cifar_main.py:392-396) and Horovod's rank-0 variable broadcast
    (reference resnet_cifar_main_horovod.py:316): replicated init is identical
    on every process by seeded construction.

    ``zero1=True`` lays the optimizer state out in the ZeRO-1 rule-table
    sharding (``parallel/sharding.zero1_state_shardings``): each data
    replica materializes only its 1/N optimizer shard from step 0.
    """
    init_fn = _make_init_fn(model, tx, input_shape)
    if mesh is None:
        return init_fn(rng)

    # Evaluate shapes, derive shardings, then jit-init with those outputs.
    abstract = jax.eval_shape(init_fn, rng)
    shardings = state_shardings(abstract, mesh, zero1=zero1,
                                zero1_min_size=zero1_min_size)
    jit_init = jax.jit(init_fn, out_shardings=shardings)
    return jit_init(rng)


def state_shardings(state_shapes, mesh: Mesh, zero1: bool = False,
                    zero1_min_size: int = 0):
    """NamedShardings for every leaf of a TrainState (params/opt_state follow
    the fsdp rule; step/batch_stats replicated).

    ``zero1=True`` additionally shards the optimizer state over the
    ``data`` axis via the regex→PartitionSpec rule table
    (``parallel/sharding.zero1_state_shardings``, arXiv:2004.13336); each
    resolution records its counted partition report into the process-global
    ``parallel.sharding.zero1_stats``. Params stay replicated-per-fsdp —
    ZeRO-1 shards the UPDATE and its state, not the forward weights."""
    param_sh = tree_param_shardings(state_shapes.params, mesh)
    rep = NamedSharding(mesh, P())
    if zero1:
        from ..parallel.sharding import (ZERO1_MIN_SIZE, Zero1Report,
                                         zero1_state_shardings, zero1_stats)
        report = Zero1Report(mesh.shape.get("data", 1))
        opt_sh = zero1_state_shardings(
            state_shapes.opt_state, mesh,
            min_size=zero1_min_size or ZERO1_MIN_SIZE, report=report)
        zero1_stats.record_report(report)
    else:
        # optimizer moments mirror the param tree INCLUDING names (optax
        # states embed the param pytree), so the name-aware rule (fsdp +
        # tensor) applies to them identically; scalar counters fall
        # through to replicated
        opt_sh = tree_param_shardings(state_shapes.opt_state, mesh)
    bs_sh = jax.tree_util.tree_map(lambda _: rep, state_shapes.batch_stats)
    return TrainState(step=rep, params=param_sh, batch_stats=bs_sh,
                      opt_state=opt_sh, apply_fn=state_shapes.apply_fn,
                      tx=state_shapes.tx)
