"""Vision Transformer — the attention-based model family.

The reference is conv-only; this framework treats attention and long context
as first-class (ops/attention.py, ops/pallas/flash_attention.py). This module
provides the trainable model that exercises those ops end-to-end through the
same Trainer/config path as the ResNets:

  * ``VisionTransformer`` — patchify → encoder stack → mean-pool → head,
    drop-in for the classification pipeline (same (B, H, W, C) → logits
    contract as the ResNets).
  * ``attention_impl`` selects the kernel: "dense" (reference semantics),
    "blockwise" (O(T) memory lax), or "flash" (Pallas TPU kernel).

All linear algebra is MXU-shaped (model dims multiples of 128 recommended);
bf16 compute / f32 params as elsewhere.
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import present_batch_axes


def _constrain(x: jax.Array, mesh, spec: "P") -> jax.Array:
    """with_sharding_constraint when a mesh is attached (no-op otherwise) —
    pins GSPMD's layout choice at the block boundaries."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    if not any(s is not None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def flash_or_dense(t: int) -> str:
    """The ONE auto rule for flash-vs-dense (no seq axis involved): the
    Pallas kernel on TPU past its measured crossover vs dense —
    docs/flash_tune_r3.json: parity at 1k tokens, 1.1× at 2k, 1.4× at 4k,
    10× at 8k. Shared by the per-block path (_apply_attention) and the
    pipelined path (stage blocks see the full t per microbatch)."""
    return "flash" if (jax.default_backend() == "tpu"
                       and t >= 2048) else "dense"


def _per_shard(fn, mesh):
    """``fn(q, k, v)`` run on each device's local (B, T, H, D) shard.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), and it only lowers where EVERY mesh axis is manual: so
    under a multi-device mesh the kernel runs inside a full-manual
    shard_map, batch dim split over the batch axes and heads over
    ``tensor`` (attention is independent per example and per head)."""
    from ..parallel.mesh import shard_map_unchecked
    if mesh is None or mesh.size == 1:
        return fn
    tensor = "tensor" if mesh.shape.get("tensor", 1) > 1 else None
    spec = P(present_batch_axes(mesh) or None, None, tensor, None)
    return shard_map_unchecked(fn, mesh, in_specs=(spec, spec, spec),
                               out_specs=spec)


def _apply_attention(q, k, v, impl: str, mesh=None):
    if impl == "auto":
        # resolved HERE, where the true sequence length is known at trace
        # time: ring when a seq mesh axis exists; otherwise the shared
        # flash_or_dense crossover rule
        if mesh is not None and mesh.shape.get("seq", 1) > 1:
            impl = "ring"
        else:
            impl = flash_or_dense(q.shape[1])
    if impl == "dense":
        from ..ops.attention import attention
        return attention(q, k, v)
    if impl == "blockwise":
        from ..ops.attention import blockwise_attention
        return blockwise_attention(q, k, v)
    if impl in ("flash", "flash_interpret"):
        from ..ops.pallas import flash_attention
        interpret = impl == "flash_interpret"
        return _per_shard(
            lambda q, k, v: flash_attention(q, k, v, False, interpret),
            mesh)(q, k, v)
    if impl == "ring":
        from ..ops.attention import ring_attention_sharded
        if mesh is None or mesh.shape.get("seq", 1) <= 1:
            raise ValueError(
                "attention_impl='ring' needs a mesh with a seq axis > 1 "
                "(set mesh.sequence and pass the mesh to the model)")
        return ring_attention_sharded(q, k, v, mesh,
                                      batch_axes=present_batch_axes(mesh))
    raise ValueError(f"unknown attention_impl {impl!r}")


class MultiHeadAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        if d % self.num_heads:
            raise ValueError(f"dim {d} not divisible by heads {self.num_heads}")
        hd = d // self.num_heads
        # kernels carry an explicit head axis — (D, 3, H, hd) / (H, hd, D) —
        # so tensor parallelism shards WHOLE heads (see
        # parallel/sharding.py); a fused (D, 3D) kernel column-sharded over
        # `tensor` would misalign with the q|k|v split boundaries and force
        # resharding around the split in every block
        qkv = nn.DenseGeneral((3, self.num_heads, hd), use_bias=False,
                              dtype=self.dtype, name="qkv")(x)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        out = _apply_attention(q, k, v, self.attention_impl, self.mesh)
        return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                               dtype=self.dtype, name="proj")(out)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"
    mesh: Any = None
    num_experts: int = 0             # >0 → Switch MoE MLP (models/moe.py)
    expert_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_dispatch: str = "auto"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        mesh = self.mesh
        tensor = mesh.shape.get("tensor", 1) if mesh is not None else 1
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MultiHeadAttention(self.num_heads, self.dtype,
                                   self.attention_impl, mesh)(h)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.num_experts > 0:
            from .moe import SwitchMlp
            return x + SwitchMlp(
                num_experts=self.num_experts, mlp_ratio=self.mlp_ratio,
                capacity_factor=self.expert_capacity_factor,
                dtype=self.dtype, mesh=mesh, top_k=self.moe_top_k,
                dispatch=self.moe_dispatch)(h)
        h = nn.Dense(self.mlp_ratio * d, dtype=self.dtype)(h)
        h = nn.gelu(h)
        if tensor > 1:
            # column-parallel up-projection: hidden dim lives on `tensor`;
            # the row-parallel down-projection contracts it (XLA all-reduce).
            # Keep the token dim on `seq` when both parallelisms are active —
            # replicating it here would all-gather the 4x-dim hidden, the
            # largest activation, defeating sequence parallelism
            seq_spec = "seq" if mesh.shape.get("seq", 1) > 1 else None
            h = _constrain(h, mesh, P(present_batch_axes(mesh) or None,
                                      seq_spec, "tensor"))
        h = nn.Dense(d, dtype=self.dtype)(h)
        return x + h


class VisionTransformer(nn.Module):
    num_classes: int = 10
    patch_size: int = 4
    dim: int = 128
    depth: int = 6
    num_heads: int = 4
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    attention_impl: str = "dense"
    remat: bool = False
    # device mesh for sequence (`seq` axis: ring attention + token sharding),
    # tensor (`tensor` axis: Megatron-style block sharding, see
    # parallel/sharding.py param_sharding_rule), and pipeline (`pipeline`
    # axis: GPipe microbatching, models/pipeline.py) parallelism. None =
    # single-device semantics; arrays may still be batch-sharded by jit.
    mesh: Any = None
    pipeline_microbatches: int = 0  # 0 → 2 × pipeline stages
    pipeline_interleave: int = 1    # v>1 → circular schedule (v chunks/stage)
    num_experts: int = 0            # >0 → Switch MoE MLPs over `expert`
    expert_capacity_factor: float = 1.25
    moe_top_k: int = 1
    moe_dispatch: str = "auto"

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = True) -> jax.Array:
        del train  # no BN; deterministic (dropout-free baseline config)
        b, h, w, c = x.shape
        p = self.patch_size
        if h % p or w % p:
            raise ValueError(f"image {h}x{w} not divisible by patch {p}")
        x = x.astype(self.dtype)
        # patchify: conv with stride p == linear patch embed
        x = nn.Conv(self.dim, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, name="patch_embed")(x)
        x = x.reshape(b, -1, self.dim)
        t = x.shape[1]
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, t, self.dim), jnp.float32)
        x = x + pos.astype(self.dtype)
        mesh = self.mesh
        seq = mesh.shape.get("seq", 1) if mesh is not None else 1
        pipeline = mesh.shape.get("pipeline", 1) if mesh is not None else 1
        if seq > 1:
            if t % seq:
                raise ValueError(f"{t} tokens not divisible by seq axis {seq}")
            # tokens sharded over `seq`: LayerNorm/MLP are token-pointwise and
            # partition cleanly; attention runs the ppermute ring
            x = _constrain(x, mesh, P(present_batch_axes(mesh) or None,
                                      "seq", None))
        if pipeline > 1:
            # GPipe microbatch pipeline over stacked-parameter stages
            # (models/pipeline.py); parameterization differs from the
            # per-block modules (pack_encoder_params converts).
            # Attention inside a stage: dense, the fused Pallas flash
            # kernel (round 4), or — with a seq axis — ring attention over
            # the token sharding (round 5, pp×seq). 'auto' applies the
            # same trace-time rules as the unpipelined path: ring when a
            # seq axis exists, else flash on TPU past the measured
            # crossover (docs/flash_tune_r3.json; the pipeline's
            # per-microbatch token count is the full t).
            impl = self.attention_impl
            if impl == "auto":
                impl = "ring" if seq > 1 else flash_or_dense(t)
            allowed = ("ring", "ring_interpret") if seq > 1 else \
                ("dense", "flash", "flash_interpret")
            if impl not in allowed:
                raise ValueError(
                    f"pipeline parallelism with seq axis {seq} supports "
                    f"attention_impl in {allowed} "
                    f"(got {self.attention_impl!r})")
            from .pipeline import PipelinedEncoder
            x = PipelinedEncoder(depth=self.depth, num_heads=self.num_heads,
                                 mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                                 mesh=mesh,
                                 microbatches=self.pipeline_microbatches,
                                 interleave=self.pipeline_interleave,
                                 remat=self.remat,
                                 attention_impl=impl,
                                 num_experts=self.num_experts,
                                 expert_capacity_factor=self.expert_capacity_factor,
                                 moe_top_k=self.moe_top_k,
                                 name="encoder")(x)
        else:
            block = EncoderBlock
            if self.remat:
                block = nn.remat(block)
            for _ in range(self.depth):
                x = block(self.num_heads, self.mlp_ratio, self.dtype,
                          self.attention_impl, mesh,
                          num_experts=self.num_experts,
                          expert_capacity_factor=self.expert_capacity_factor,
                          moe_top_k=self.moe_top_k,
                          moe_dispatch=self.moe_dispatch,
                          )(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        x = x.mean(axis=1).astype(jnp.float32)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
