"""Follow a training run's first steps with a plain reference.

``follow`` takes the configuration's stated sizes, the seed and the host
batches the program was fed, makes the same initial weights from the seed,
and walks the steps in float32 at ``highest`` matmul precision: loss,
gradients, the optimizer's update. It returns what the comparison reads:
the loss at each boundary asked for, and per leaf the norm (and the inner
product with a seeded probe vector) of the optimizer's first moment after
the first boundary and of the parameters' change after the last.

The same walk in a lower precision is the control (``precision="fp8"``:
both operands of every product rounded to float8 e4m3 with a per-tensor
scale, straight-through in the backward pass; ``"bf16"`` likewise), and the
same walk with a fault planted stands in for a broken program
(``fault="half_batch"``: the second half of every batch left out of the
loss and the mean taken over the rest; ``"quarter_batch"``: what one of four chips
computes when the exchange between them is left out).
"""
from __future__ import annotations

import importlib
import json
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import optim


def family_module(family: str):
    return importlib.import_module(f"benchmark.reference.{family}")


def init_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 7)


def flip_draws(augment_seed: int, n: int, rows: int) -> np.ndarray:
    """The flip decisions of the n-th staged batch: the configuration states
    them as bernoulli(fold_in(PRNGKey(seed), n), 1/2) over the rows."""
    key = jax.random.fold_in(jax.random.PRNGKey(augment_seed), np.uint32(n))
    return np.asarray(jax.random.bernoulli(key, 0.5, (rows,)))


def _quantizer(precision: Optional[str]):
    """Rounds a product's operand to the control's precision, and the
    cotangent that comes back through it likewise (float8 with a per-tensor
    scale, so that small gradients do not flush to zero)."""
    if precision is None:
        return lambda a: a
    dtype, top = {"fp8": (jnp.float8_e4m3fn, 448.0),
                  "bf16": (jnp.bfloat16, None)}[precision]

    def rounded(a):
        if top is None:
            return a.astype(dtype).astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return (a / s).astype(dtype).astype(jnp.float32) * s

    @jax.custom_vjp
    def quant(a):
        return rounded(a)
    quant.defvjp(lambda a: (rounded(a), None), lambda _, g: (rounded(g),))
    return quant


def probes(shapes: Dict[str, tuple], seed: int) -> Dict[str, jnp.ndarray]:
    """One seeded standard-normal vector per leaf. The inner product of a
    leaf's gradient with its probe is a scalar that moves in first order
    with any error in the gradient, where a norm moves in second order."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 991)
    return {n: jax.random.normal(jax.random.fold_in(key, i), shapes[n], jnp.float32)
            for i, n in enumerate(sorted(shapes))}


def norms_and_probes(tree: Dict[str, jnp.ndarray], seed: int) -> Dict[str, Dict]:
    """Per leaf: the L2 norm, and the inner product with the leaf's probe."""
    r = probes({n: v.shape for n, v in tree.items()}, seed)
    return {"norm": {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                     for n, v in tree.items()},
            "probe": {n: jnp.sum(v.astype(jnp.float32) * r[n]) for n, v in tree.items()}}


def _xent_sum(logits, labels, weights):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * (logz - picked))


_COMPILED: dict = {}


def _functions(config: dict, precision: Optional[str]):
    """The jitted pieces of the walk, made once for a configuration and a
    precision (a tool that reads many seeds in one process reuses them)."""
    key = (json.dumps(config["model"], sort_keys=True),
           json.dumps(config["optimizer"], sort_keys=True), config["family"], precision)
    if key in _COMPILED:
        return _COMPILED[key]
    fam = family_module(config["family"])
    model, opt = config["model"], config["optimizer"]
    quant = _quantizer(precision)
    rep_sh = NamedSharding(Mesh(np.asarray(jax.devices()), ("rows",)), P())

    def block_grad(params, x, y, w):
        def ce_sum(p):
            return _xent_sum(fam.logits(p, x, model, quant), y, w)
        return jax.value_and_grad(ce_sum)(params)

    def finish(params, ce_sum, grads, rows):
        ce = ce_sum / rows
        grads = {n: g / rows for n, g in grads.items()}
        loss = ce
        if opt["name"] == "momentum":  # decay rides the loss, as the source has it
            loss = ce + optim.l2_term(params, opt["weight_decay"])
            grads = {n: g + (opt["weight_decay"] * params[n]
                             if optim.decayed(n, params[n]) else 0.0)
                     for n, g in grads.items()}
        return loss, grads

    def norms(tree):
        return {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in tree.items()}

    fns = {
        "block_grad": jax.jit(block_grad), "finish": jax.jit(finish),
        "apply": jax.jit(lambda p, g, s, lr, n: optim.update(opt, p, g, s, lr, n)),
        "norms": jax.jit(norms),
        "moment": jax.jit(lambda s, seed: norms_and_probes(optim.first_moment(opt, s), seed)),
        "change": jax.jit(lambda p, p0, seed: norms_and_probes(
            {n: p[n] - p0[n] for n in p}, seed)),
        "init": jax.jit(lambda k: fam.init_params(k, model), out_shardings=rep_sh),
        "opt_init": jax.jit(lambda p: optim.init(opt, p), out_shardings=rep_sh),
    }
    _COMPILED[key] = fns
    return fns


def follow(config: dict, seed: int, batches: Sequence[Dict[str, np.ndarray]],
           boundaries: Sequence[int], augment_seed: int = 0,
           precision: Optional[str] = None, fault: Optional[str] = None
           ) -> Dict[str, object]:
    """Walk ``boundaries[-1]`` steps over ``batches`` (one per step)."""
    fam = family_module(config["family"])
    fns = _functions(config, precision)
    lr_at = optim.schedule(config["optimizer"])
    devices = jax.devices()
    rows_sh = NamedSharding(Mesh(np.asarray(devices), ("rows",)), P("rows"))
    with jax.default_matmul_precision("highest"):
        params = p0 = fns["init"](init_key(seed))
        state = fns["opt_init"](params)
        out = {"loss": {}, "moment": None, "change": None, "grad1": None}
        for step in range(boundaries[-1]):
            batch = batches[step]
            x_all = fam.prepare(batch["images"],
                                flip_draws(augment_seed, step, len(batch["labels"])))
            y_all = jnp.asarray(batch["labels"], jnp.int32)
            rows = x_all.shape[0]
            # a fault keeps the shapes (and so the compiled program): the
            # rows left out get weight 0 in the loss and the mean is taken
            # over the rest; under batch normalisation they still count in
            # the batch's statistics
            kept = rows // {"half_batch": 2, "quarter_batch": 4}.get(fault, 1)
            w_all = jnp.asarray(np.arange(rows) < kept, jnp.float32)
            blk = rows if fam.ROW_BLOCK is None else min(rows, fam.ROW_BLOCK * len(devices))
            ce_sum, grads = 0.0, None
            for r0 in range(0, rows if fam.ROW_BLOCK is None else kept, blk):
                x, y, w = (a[r0:r0 + blk] for a in (x_all, y_all, w_all))
                if fam.ROW_BLOCK is not None and x.shape[0] % len(devices) == 0:
                    x, y, w = (jax.device_put(a, rows_sh) for a in (x, y, w))
                c, g = fns["block_grad"](params, x, y, w)
                ce_sum = ce_sum + c
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
            loss, grads = fns["finish"](params, ce_sum, grads, float(kept))
            if step == 0:
                out["grad1"] = fns["norms"](grads)
            params, state = fns["apply"](params, grads, state,
                                         float(lr_at(config["start_step"] + step)),
                                         float(step + 1))
            if step + 1 in boundaries:
                out["loss"][step + 1] = loss
            if step + 1 == boundaries[0]:
                out["moment"] = fns["moment"](state, seed % (2 ** 31 - 1))
        out["change"] = fns["change"](params, p0, seed % (2 ** 31 - 1))
    return jax.tree_util.tree_map(lambda a: float(np.asarray(a)), out)
