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

What a family states (``benchmark/reference/<family>.py``). Every family
gives ``init_params(key, model)`` (leaves by name, from the seed) and
``program_paths(model)`` (where the program keeps each leaf). An *example*
is what ``examples_per_s`` counts and what a fault leaves out: an image, or
a whole sequence. Beyond that a family may state, each on its own:

* ``examples(batch, step, augment_seed) -> {name: array}``: which keys of
  the host batch it reads and how they become per-example arrays (leading
  axis: the examples), with whatever the configuration states is drawn per
  step from ``augment_seed``;
* ``loss_sum(params, block, weights, model, quant) -> (sum, aux)``: the
  loss of a block of whole examples as the sum over them of ``weights[e]``
  times the example's loss, with as many terms as the family has (a mean
  over positions, a weighted second head); the walk adds the blocks' sums
  and divides by the examples kept, so a fault's weights of 0 leave rows
  out. ``aux`` is a tree of sums that add over blocks (routing counts), or
  None;
* ``EXAMPLE_BLOCK``: how many examples a block holds per device (None: the
  whole batch at once, for a family whose examples are coupled);
* ``decayed(name, leaf) -> bool``: which leaves the weight decay touches;
* ``after_update(params, aux, model) -> params``: a rule that moves state
  after the optimizer's update from what the loss returned beside its
  value, with ``RULED``, the names of the leaves that only the rule moves.
  Such a leaf lives among the parameters, has no gradient (the family's
  loss stops it) and is not decayed, so the optimizer leaves it where it
  is; the comparison keeps it in the parameters' change although its
  gradient is nought (``"ruled"`` in what ``follow`` returns).

A family that states none of these gets the walk of an image classifier:
``prepare(images, flips)`` with the flips the configuration states drawn
from ``augment_seed``, ``logits(params, x, model, quant)``, one softmax
cross-entropy a row against ``labels``, blocks of ``ROW_BLOCK`` rows, and
``optim.decayed``.
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


# -- the default: what a family without a batch and a loss of its own gets --

def flip_draws(augment_seed: int, n: int, rows: int) -> np.ndarray:
    """The flip decisions of the n-th staged batch: the configuration states
    them as bernoulli(fold_in(PRNGKey(seed), n), 1/2) over the rows."""
    key = jax.random.fold_in(jax.random.PRNGKey(augment_seed), np.uint32(n))
    return np.asarray(jax.random.bernoulli(key, 0.5, (rows,)))


def _xent_sum(logits, labels, weights):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * (logz - picked))


def _classifier(fam):
    """``examples`` and ``loss_sum`` of an image classifier, from the
    family's ``prepare`` and ``logits``."""
    def examples(batch, step, augment_seed):
        flips = flip_draws(augment_seed, step, len(batch["labels"]))
        return {"x": fam.prepare(batch["images"], flips),
                "y": jnp.asarray(batch["labels"], jnp.int32)}

    def loss_sum(params, block, weights, model, quant):
        return _xent_sum(fam.logits(params, block["x"], model, quant),
                         block["y"], weights), None
    return examples, loss_sum


class Stated:
    """What a family states of the contract above, each with its default."""

    def __init__(self, fam):
        examples, loss_sum = _classifier(fam)
        self.examples = getattr(fam, "examples", examples)
        self.loss_sum = getattr(fam, "loss_sum", loss_sum)
        self.block = fam.EXAMPLE_BLOCK if hasattr(fam, "EXAMPLE_BLOCK") else fam.ROW_BLOCK
        self.decayed = getattr(fam, "decayed", optim.decayed)
        self.after_update = getattr(fam, "after_update", None)
        self.ruled = tuple(getattr(fam, "RULED", ()))


# -- the walk ---------------------------------------------------------------

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


_COMPILED: dict = {}


def _functions(config: dict, precision: Optional[str]):
    """The jitted pieces of the walk, made once for a configuration and a
    precision (a tool that reads many seeds in one process reuses them)."""
    key = (json.dumps(config["model"], sort_keys=True),
           json.dumps(config["optimizer"], sort_keys=True), config["family"], precision)
    if key in _COMPILED:
        return _COMPILED[key]
    fam = family_module(config["family"])
    stated = Stated(fam)
    model, opt = config["model"], config["optimizer"]
    quant = _quantizer(precision)
    rep_sh = NamedSharding(Mesh(np.asarray(jax.devices()), ("rows",)), P())

    def block_grad(params, block, w):
        def total(p):
            return stated.loss_sum(p, block, w, model, quant)
        (value, aux), grads = jax.value_and_grad(total, has_aux=True)(params)
        return value, aux, grads

    def finish(params, ce_sum, grads, rows):
        ce = ce_sum / rows
        grads = {n: g / rows for n, g in grads.items()}
        loss = ce
        if opt["name"] == "momentum":  # decay rides the loss, as the source has it
            loss = ce + optim.l2_term(params, opt["weight_decay"], stated.decayed)
            grads = {n: g + (opt["weight_decay"] * params[n]
                             if stated.decayed(n, params[n]) else 0.0)
                     for n, g in grads.items()}
        return loss, grads

    def norms(tree):
        return {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in tree.items()}

    fns = {
        "block_grad": jax.jit(block_grad), "finish": jax.jit(finish),
        "apply": jax.jit(lambda p, g, s, lr, n: optim.update(opt, p, g, s, lr, n,
                                                             stated.decayed)),
        "rule": stated.after_update and jax.jit(
            lambda p, aux: stated.after_update(p, aux, model)),
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
    stated = Stated(family_module(config["family"]))
    fns = _functions(config, precision)
    lr_at = optim.schedule(config["optimizer"])
    devices = jax.devices()
    rows_sh = NamedSharding(Mesh(np.asarray(devices), ("rows",)), P("rows"))
    with jax.default_matmul_precision("highest"):
        params = p0 = fns["init"](init_key(seed))
        state = fns["opt_init"](params)
        out = {"loss": {}, "moment": None, "change": None, "grad1": None}
        for step in range(boundaries[-1]):
            every = stated.examples(batches[step], step, augment_seed)
            rows = len(next(iter(every.values())))
            # a fault keeps the shapes (and so the compiled program): the
            # rows left out get weight 0 in the loss and the mean is taken
            # over the rest; under batch normalisation they still count in
            # the batch's statistics
            kept = rows // {"half_batch": 2, "quarter_batch": 4}.get(fault, 1)
            w_all = jnp.asarray(np.arange(rows) < kept, jnp.float32)
            blk = rows if stated.block is None else min(rows, stated.block * len(devices))
            ce_sum, aux, grads = 0.0, None, None
            for r0 in range(0, rows if stated.block is None else kept, blk):
                block = {n: a[r0:r0 + blk] for n, a in every.items()}
                w = w_all[r0:r0 + blk]
                if stated.block is not None and w.shape[0] % len(devices) == 0:
                    block, w = jax.device_put((block, w), rows_sh)
                c, a, g = fns["block_grad"](params, block, w)
                ce_sum = ce_sum + c
                aux = a if grads is None else jax.tree_util.tree_map(jnp.add, aux, a)
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
            loss, grads = fns["finish"](params, ce_sum, grads, float(kept))
            if step == 0:
                out["grad1"] = fns["norms"](grads)
            params, state = fns["apply"](params, grads, state,
                                         float(lr_at(config["start_step"] + step)),
                                         float(step + 1))
            if fns["rule"]:
                params = fns["rule"](params, aux)
            if step + 1 in boundaries:
                out["loss"][step + 1] = loss
            if step + 1 == boundaries[0]:
                out["moment"] = fns["moment"](state, seed % (2 ** 31 - 1))
        out["change"] = fns["change"](params, p0, seed % (2 ** 31 - 1))
        if stated.ruled:
            out["ruled"] = {n: 1.0 for n in stated.ruled}
    return jax.tree_util.tree_map(lambda a: float(np.asarray(a)), out)
