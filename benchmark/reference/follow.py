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

What the walk costs in memory. A family of P parameters costs the walk
**12 P bytes on each device**, whatever the family: the float32 weights, the
running sum of the gradient, and one block's gradient, beside one block's
activations. Every program that replaces a buffer is given that buffer
(``donate_argnums``): the sum over blocks, the division by the rows kept,
the update, the rule. The initial weights are not kept: the initialiser's
program runs again from the seed when the change is read, group by group
where the update ran so (inside ``change``'s own program the compiler sums
the norms in another order). The optimizer's moments (8 P
for AdamW, 4 P for momentum) are needed only at the update, and ``layout``
says where they wait meanwhile, from the device's ``bytes_limit`` as the
runtime states it and the family's count, and from nothing else:

* where weights, gradient sum, block gradient and moments together (20 P
  bytes under AdamW) are at most half of ``bytes_limit``, or the runtime
  states no limit (the CPU), the moments stay on the device and the update
  is one program over all leaves: 304 M parameters ask 6.1 GB of a v5e
  chip's 16.9 and stay;
* otherwise they wait in host memory (8 P bytes there), and the update runs
  over groups of leaves of at most a sixteenth of ``bytes_limit`` each (a
  larger leaf alone): a group's moments go up, the group is updated in
  place, the moment is read where a boundary asks for it, and they come
  down again, so the device never holds old and new state whole; and the
  host waits for each block's sum before it asks for the next block.

A block's activations are the family's own to keep small: ``EXAMPLE_BLOCK``,
recomputation inside ``loss_sum``, attention in blocks of queries. The
program, beside it, keeps 16 P bytes on the device (float32 weights,
gradients, Adam's two moments) and its own activations; a configuration is
sized from these two counts. ``follow`` returns, under ``walk``, the
device's peak before and after the walk and where the moments waited.
"""
from __future__ import annotations

import importlib
import json
import math
from typing import Dict, List, Optional, Sequence, Tuple

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


def probes(shapes: Dict[str, tuple], seed: int,
           order: Optional[Sequence[str]] = None) -> Dict[str, jnp.ndarray]:
    """One seeded standard-normal vector per leaf. The inner product of a
    leaf's gradient with its probe is a scalar that moves in first order
    with any error in the gradient, where a norm moves in second order.
    A leaf's vector is drawn from its place among all the leaves' sorted
    names: ``order``, where ``shapes`` holds only some of them."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)), 991)
    return {n: jax.random.normal(jax.random.fold_in(key, i), shapes[n], jnp.float32)
            for i, n in enumerate(order or sorted(shapes)) if n in shapes}


def norms_and_probes(tree: Dict[str, jnp.ndarray], seed: int,
                     order: Optional[Sequence[str]] = None) -> Dict[str, Dict]:
    """Per leaf: the L2 norm, and the inner product with the leaf's probe."""
    r = probes({n: v.shape for n, v in tree.items()}, seed, order)
    return {"norm": {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                     for n, v in tree.items()},
            "probe": {n: jnp.sum(v.astype(jnp.float32) * r[n]) for n, v in tree.items()}}


def device_bytes_limit(devices) -> Optional[int]:
    """The least ``bytes_limit`` the runtime states for these devices; None
    where it states none (the CPU)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    return min(limits) if all(limits) else None


def layout(params, state, limit: Optional[int]) -> Tuple[bool, List[Tuple[str, ...]]]:
    """Whether the optimizer's moments wait on the host, and the groups of
    leaves the update runs over (the rule is the module docstring's).
    ``params`` and ``state`` are arrays or their shapes."""
    def nbytes(tree):
        return sum(math.prod(a.shape) * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))
    if limit is None or 3 * nbytes(params) + nbytes(state) <= limit / 2:
        return False, [tuple(params)]
    groups: List[List[str]] = [[]]
    held = 0
    for name, leaf in params.items():
        if groups[-1] and held + nbytes(leaf) > limit / 16:
            groups.append([])
            held = 0
        groups[-1].append(name)
        held += nbytes(leaf)
    return True, [tuple(g) for g in groups]


def _peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


_COMPILED: dict = {}


def _functions(config: dict, precision: Optional[str]):
    """The jitted pieces of the walk, made once for a configuration and a
    precision (a tool that reads many seeds in one process reuses them).
    Those that take leaves take any group of them: a group's names are its
    tree's structure, so each group compiles once."""
    key = (json.dumps(config["model"], sort_keys=True),
           json.dumps(config["optimizer"], sort_keys=True), config["family"], precision)
    if key in _COMPILED:
        return _COMPILED[key]
    fam = family_module(config["family"])
    stated = Stated(fam)
    model, opt = config["model"], config["optimizer"]
    quant = _quantizer(precision)
    mesh = Mesh(np.asarray(jax.devices()), ("rows",))
    rep_sh = NamedSharding(mesh, P())

    def block_grad(params, block, w):
        def total(p):
            return stated.loss_sum(p, block, w, model, quant)
        (value, aux), grads = jax.value_and_grad(total, has_aux=True)(params)
        return value, aux, grads

    def add(ce_sum, aux, grads, c, a, g):
        add_trees = lambda x, y: jax.tree_util.tree_map(jnp.add, x, y)  # noqa: E731
        return ce_sum + c, add_trees(aux, a), add_trees(grads, g)

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

    def init(key, names=None):
        """The weights from the seed: all of them, or the leaves named."""
        return {n: v for n, v in fam.init_params(key, model).items()
                if names is None or n in names}

    fns = {
        "block_grad": jax.jit(block_grad),
        "add": jax.jit(add, donate_argnums=(2,)),
        "finish": jax.jit(finish, donate_argnums=(2,)),
        "apply": jax.jit(lambda p, g, s, lr, n: optim.update(opt, p, g, s, lr, n,
                                                             stated.decayed),
                         donate_argnums=(0, 2)),
        "rule": stated.after_update and jax.jit(
            lambda p, aux: stated.after_update(p, aux, model), donate_argnums=(0,)),
        "norms": jax.jit(norms),
        "moment": jax.jit(lambda s, seed, order: norms_and_probes(
            optim.first_moment(opt, s), seed, order), static_argnames=("order",)),
        "change": jax.jit(lambda p, p0, seed, order: norms_and_probes(
            {n: p[n] - p0[n] for n in p}, seed, order), static_argnames=("order",)),
        "init": jax.jit(init, static_argnames=("names",), out_shardings=rep_sh),
        "opt_init": jax.jit(lambda p: optim.init(opt, p), out_shardings=rep_sh),
        "replicated": rep_sh, "by_rows": NamedSharding(mesh, P("rows")),
    }
    _COMPILED[key] = fns
    return fns


def _gradient(fns, stated, params, batch, step: int, augment_seed: int,
              fault: Optional[str], devices, tight: bool):
    """The sum over a batch's blocks of loss, ``aux`` and gradient, and the
    rows kept. Beside ``params`` the device holds the running sum and one
    block's gradient, which is let go as soon as it is added. Where memory
    is ``tight`` (the moments wait on the host) the host asks for no
    further block, and no update, until this one is in the sum: the runtime
    allocates a program's outputs when the program is enqueued, and a host
    that runs ahead holds two blocks' gradients and the first groups'
    moments at once (11.9 GB in place of 9.1 at 707 M parameters)."""
    every = stated.examples(batch, step, augment_seed)
    rows = len(next(iter(every.values())))
    # a fault keeps the shapes (and so the compiled program): the rows left
    # out get weight 0 in the loss and the mean is taken over the rest;
    # under batch normalisation they still count in the batch's statistics
    kept = rows // {"half_batch": 2, "quarter_batch": 4}.get(fault, 1)
    w_all = jnp.asarray(np.arange(rows) < kept, jnp.float32)
    blk = rows if stated.block is None else min(rows, stated.block * len(devices))
    total = None
    for r0 in range(0, rows if stated.block is None else kept, blk):
        block = {n: a[r0:r0 + blk] for n, a in every.items()}
        w = w_all[r0:r0 + blk]
        if stated.block is not None and w.shape[0] % len(devices) == 0:
            block, w = jax.device_put((block, w), fns["by_rows"])
        one = fns["block_grad"](params, block, w)
        total = one if total is None else fns["add"](*total, *one)
        del one
        if tight:
            jax.block_until_ready(total[0])
    return (*total, kept)


def _update(fns, params, grads, state, groups, on_host: bool, lr: float, n: float,
            read: Optional[Tuple[int, Optional[tuple]]]):
    """The optimizer's update over each group of leaves in turn, in place.
    With the moments on the host a group's go up, are updated and come down
    while the next group's are on their way. ``read`` (seed, order) asks for
    the first moment's norms and probes, taken before it leaves the device."""
    moment = {"norm": {}, "probe": {}}
    coming_down = None

    def land(group_state):
        for kind, leaves in group_state.items():
            state[kind].update({k: np.asarray(v) for k, v in leaves.items()})

    for names in groups:
        mine = {kind: {k: leaves[k] for k in names} for kind, leaves in state.items()}
        if on_host:
            mine = jax.device_put(mine, fns["replicated"])
        moved, mine = fns["apply"]({k: params[k] for k in names},
                                   {k: grads.pop(k) for k in names}, mine, lr, n)
        params.update(moved)
        if read is not None:
            got = fns["moment"](mine, *read)
            moment = {k: {**moment[k], **got[k]} for k in moment}
        if on_host:
            for leaf in jax.tree_util.tree_leaves(mine):
                leaf.copy_to_host_async()
            if coming_down is not None:
                land(coming_down)
            coming_down = mine
        else:  # one group: the whole state, updated in place
            state = mine
    if coming_down is not None:
        land(coming_down)
    return state, moment


def follow(config: dict, seed: int, batches: Sequence[Dict[str, np.ndarray]],
           boundaries: Sequence[int], augment_seed: int = 0,
           precision: Optional[str] = None, fault: Optional[str] = None
           ) -> Dict[str, object]:
    """Walk ``boundaries[-1]`` steps over ``batches`` (one per step)."""
    stated = Stated(family_module(config["family"]))
    fns = _functions(config, precision)
    lr_at = optim.schedule(config["optimizer"])
    devices = jax.devices()
    seed31 = seed % (2 ** 31 - 1)
    peak_before = _peak(devices)
    with jax.default_matmul_precision("highest"):
        params = fns["init"](init_key(seed), None)
        shapes = jax.eval_shape(fns["opt_init"], params)
        on_host, groups = layout(params, shapes, device_bytes_limit(devices))
        if on_host:
            state = jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), shapes)
        else:
            state = fns["opt_init"](params)
        # a leaf's probe is drawn from its place among all the leaves
        order = tuple(sorted(params)) if len(groups) > 1 else None
        out = {"loss": {}, "moment": None, "change": None, "grad1": None}
        for step in range(boundaries[-1]):
            ce_sum, aux, grads, kept = _gradient(fns, stated, params, batches[step], step,
                                                 augment_seed, fault, devices, on_host)
            loss, grads = fns["finish"](params, ce_sum, grads, float(kept))
            if step == 0:
                out["grad1"] = fns["norms"](grads)
            state, moment = _update(
                fns, params, grads, state, groups, on_host,
                float(lr_at(config["start_step"] + step)), float(step + 1),
                (seed31, order) if step + 1 == boundaries[0] else None)
            if fns["rule"]:
                params = fns["rule"](params, aux)
            if step + 1 in boundaries:
                out["loss"][step + 1] = loss
            if step + 1 == boundaries[0]:
                out["moment"] = moment
        del state, grads
        out["change"] = {"norm": {}, "probe": {}}
        for names in groups:  # against the initial weights, made again from the seed
            p0 = fns["init"](init_key(seed), names if len(groups) > 1 else None)
            got = fns["change"]({k: params.pop(k) for k in names}, p0, seed31, order)
            out["change"] = {k: {**out["change"][k], **got[k]} for k in got}
        if stated.ruled:
            out["ruled"] = {n: 1.0 for n in stated.ruled}
    out = jax.tree_util.tree_map(lambda a: float(np.asarray(a)), out)
    out["walk"] = {"moments": "host" if on_host else "device", "groups": len(groups),
                   "device_peak_bytes_before": peak_before,
                   "device_peak_bytes": _peak(devices)}
    return out
