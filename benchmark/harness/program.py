"""The system under test, and the only file of the benchmark that imports it.

``build`` makes the program's own ``Trainer`` from the cell's configuration
and traffic files, gives it weights made by the benchmark from the seed (one
jitted call on the device, through the reference's initialiser, so that the
reference can make the same ones without taking anything from the program),
and hands back what the window and the comparison need: the trainer, the
stream of host batches, the hooks ``main.py train`` runs with, and readers
of the optimizer state.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import jax
import jax.numpy as jnp
from distributed_resnet_tensorflow_tpu.parallel.mesh import create_mesh
from distributed_resnet_tensorflow_tpu.train.hooks import LoggingHook, NanGuardHook
from distributed_resnet_tensorflow_tpu.train.loop import Trainer
from distributed_resnet_tensorflow_tpu.utils.compile_cache import configure_compile_cache
from distributed_resnet_tensorflow_tpu.utils.config import get_preset
from distributed_resnet_tensorflow_tpu.utils.metrics import input_stages

from ..reference import follow
from . import spec


def seed31(seed: int) -> int:
    """The seed as the program's config takes it (a non-negative int32)."""
    return seed % (2 ** 31 - 1)


def place_compile_cache():
    """JAX's persistent cache where the program keeps it: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``<checkout>/.jax_cache``, a
    fixed path inside the checkout (none in a rehearsal on the CPU). Every
    program is kept, whatever it cost to compile, so that a second run
    compiles nothing."""
    placed = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


#: keys of a configuration's ``model`` / ``optimizer`` block that the
#: program fixes in its code and not in its config, and keys it holds in
#: another group: the default, for a family that states neither
#: (``FIXED_IN_CODE`` / ``HELD_ELSEWHERE`` in its reference module); a
#: configuration file adds keys of its own under ``fixed_in_code``
FIXED_IN_CODE = {"model": ["mlp_ratio", "layer_norm_epsilon", "gelu", "pooling",
                           "compute_dtype_control"],
                 "optimizer": ["b1", "b2", "eps"]}
HELD_ELSEWHERE = {"image_size": ["data", "image_size"]}


def _stated(cfg, config: dict, family) -> None:
    """The configuration file says what runs: every number it states for the
    model and the optimizer has to be what the program's config holds. What
    the program's config does not hold (a published count beside the count
    held here) the family's module or the configuration file names."""
    where = {"model": cfg.model, "optimizer": cfg.optimizer}
    aliases = getattr(family, "HELD_ELSEWHERE", HELD_ELSEWHERE)
    by_family = getattr(family, "FIXED_IN_CODE", FIXED_IN_CODE)
    by_file = config.get("fixed_in_code", {})
    free = {group: set(by_family.get(group, ())) | set(by_file.get(group, ()))
            for group in where}
    for group, obj in where.items():
        for key, want in config[group].items():
            if key in free[group]:
                continue  # fixed in the program's code, not in its config
            if key in aliases:
                have = getattr(getattr(cfg, aliases[key][0]), aliases[key][1])
            else:
                have = getattr(obj, key)
            if isinstance(have, tuple):
                have = list(have)
            if have != want:
                raise spec.SpecError(
                    f"configuration states {group}.{key}={want!r}, the program "
                    f"runs {have!r}")


class Program:
    NanLossError = NanGuardHook.NanLossError

    def __init__(self, cell: spec.Cell, seed: int, devices):
        config, traffic = cell.config, cell.traffic
        cfg = get_preset(config["preset"])
        overrides = dict(config.get("overrides", {}))
        overrides.update(traffic.get("overrides", {}))
        overrides["train.batch_size"] = traffic["per_chip_batch"] * traffic["chips"]
        overrides["train.seed"] = seed31(seed)
        for axis, n in traffic["mesh"].items():
            overrides[f"mesh.{axis}"] = n
        for key, value in overrides.items():
            cfg.override(key, value)
        self.family = spec.module("reference", config["family"])
        _stated(cfg, config, self.family)
        self.cell, self.cfg, self.seed = cell, cfg, seed
        self.k = max(1, cfg.train.steps_per_loop)
        self.global_batch = cfg.train.batch_size
        self.trainer = Trainer(cfg, mesh=create_mesh(cfg.mesh, devices=list(devices)))
        self.trainer.init_state(seed31(seed))
        self._paths = self.family.program_paths(config["model"])
        self._install_weights()
        self.stream = spec.module("generators", traffic["generator"]).make(
            traffic, config, seed)
        self.data_iter = iter(self.stream)

    # -- weights ----------------------------------------------------------
    def _to_tree(self, flat: Dict[str, jax.Array]):
        tree: dict = {}
        for name, path in self._paths.items():
            node = tree
            *dirs, leaf = path.split("/")
            for d in dirs:
                node = node.setdefault(d, {})
            node[leaf] = flat[name]
        return tree

    def _flat(self, tree) -> Dict[str, jax.Array]:
        out = {}
        for name, path in self._paths.items():
            node = tree
            for d in path.split("/"):
                node = node[d]
            out[name] = node
        return out

    def _install_weights(self) -> None:
        state = self.trainer.state
        theirs = {"/".join(str(getattr(k, "key", k)) for k in path)
                  for path, _ in jax.tree_util.tree_flatten_with_path(state.params)[0]}
        if theirs != set(self._paths.values()):
            odd = sorted(theirs ^ set(self._paths.values()))[:6]
            raise spec.SpecError(f"the reference's leaves and the program's differ: {odd}")
        model = self.cell.config["model"]
        shardings = jax.tree_util.tree_map(lambda a: a.sharding, state.params)
        make = jax.jit(lambda key: self._to_tree(self.family.init_params(key, model)),
                       out_shardings=shardings)
        params = make(follow.init_key(self.seed))
        start = self.cell.config["start_step"]

        def restart(node):  # the schedule's own counter, not Adam's
            if type(node).__name__ == "ScaleByScheduleState":
                return node._replace(count=jnp.full_like(node.count, start))
            if isinstance(node, tuple) and not hasattr(node, "_fields"):
                return tuple(restart(n) for n in node)
            if hasattr(node, "inner_state"):
                return node._replace(inner_state=restart(node.inner_state))
            return node
        self.trainer.state = state.replace(
            params=params, step=jnp.full_like(state.step, start),
            opt_state=restart(state.opt_state))

    # -- what the comparison reads ---------------------------------------
    @staticmethod
    def first_moment(opt_state):
        """The first ``trace`` or ``mu`` in the optimizer's state, through
        chains (tuples), wrappers (``inner_state``) and the dict of states a
        partitioned optimizer keeps, one per label (``inner_states``)."""
        def find(node):
            if isinstance(node, jax.Array):  # an array has a method called trace
                return None
            for attr in ("trace", "mu"):
                if hasattr(node, attr):
                    return getattr(node, attr)
            if isinstance(node, dict):
                node = tuple(node.values())
            if isinstance(node, tuple):
                for n in node:
                    got = find(n)
                    if got is not None:
                        return got
            for attr in ("inner_state", "inner_states"):
                if hasattr(node, attr):
                    return find(getattr(node, attr))
            return None
        got = find(opt_state)
        if got is None:
            raise spec.SpecError("no first moment in the optimizer's state")
        return got

    def read_moment(self, opt_state):
        """Per leaf, under the reference's names: the norm of the first
        moment and its inner product with the leaf's probe. One small jitted
        call, dispatched before the next step donates the buffers."""
        if not hasattr(self, "_moment"):
            self._moment = jax.jit(lambda t, seed: follow.norms_and_probes(
                self._flat(t), seed))
        return self._moment(self.first_moment(opt_state), seed31(self.seed))

    def read_change(self, params):
        """The same for params minus the initial weights, which are made
        again from the seed inside the call (no second copy is kept)."""
        if not hasattr(self, "_change"):
            model = self.cell.config["model"]

            def change(p, key, seed):
                p0 = self.family.init_params(key, model)
                return follow.norms_and_probes(
                    {n: v - p0[n] for n, v in self._flat(p).items()}, seed)
            self._change = jax.jit(change)
        return self._change(params, follow.init_key(self.seed), seed31(self.seed))

    # -- the loop main.py train runs --------------------------------------
    def hooks(self) -> List:
        cfg = self.cfg
        guard = cfg.resilience.nan_check_every_steps or max(cfg.train.log_every_steps, 1)
        return [NanGuardHook(every_steps=guard),
                LoggingHook(cfg.train.log_every_steps, batch_size=cfg.train.batch_size,
                            print_fn=lambda s: print(s, file=sys.stderr))]

    def stage_counters(self) -> dict:
        return input_stages.snapshot()

    def resolutions(self) -> dict:
        return self.trainer.resolutions()

    def close(self) -> None:
        """Stop the input threads and let go of the state."""
        for attr in ("_dev_prefetch", "_multi_prefetch"):
            entry = getattr(self.trainer, attr, None)
            if entry is not None:
                entry[1].close()
        self.trainer.state = None
        self.trainer = None
