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

import math
from functools import partial
from typing import Any, NamedTuple, Optional

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


# ---------------------------------------------------------------------------
# Causal decoder: the token-model families. Built from a list of layer kinds
# (``sliding_attention`` / ``full_attention``): RMS norms before each part,
# grouped query heads with per-head RMS norms on queries and keys, SwiGLU
# feed-forward in the leading dense layers and models/moe.DroplessMoe in the
# rest, an untied head over the vocabulary rows held here. The residual
# stream and every norm are float32; products run in ``dtype``. What a
# family fixes beyond its sizes is in ``FAMILIES`` and nowhere else, keyed
# on ``model.name`` (the config holds sizes; whether a name is a causal
# decoder at all is ``name in FAMILIES``):
#
# * ``afmoe``: RMS norms AFTER each part too, a sigmoid gate on the
#   attention's output, rotary positions on the window layers only, sigmoid
#   routing with a rule-moved bias, the next-token loss;
# * ``sdar_moe``: neither, rotary positions on every layer, softmax
#   routing, and the block-diffusion loss of arXiv:2503.09573 over a noisy
#   and a clean copy of each sequence under ``ops.attention.Mask``'s third
#   kind;
# * ``nemotron_h``: ONE mixer a block (``MixerBlock``: x + mixer(norm(x))),
#   the layer's kind choosing it: the Mamba-2 mixer (models/mamba.py), the
#   expert layer with relu² experts, or attention with no head norms, no
#   gate and no positional term; sigmoid routing with a rule-moved bias,
#   the next-token loss.
# ---------------------------------------------------------------------------

#: the attention kinds of both blocks, then the one-mixer block's other two
LAYER_KINDS = ("sliding_attention", "full_attention", "mamba", "moe")


class Family(NamedTuple):
    post_norms: bool         # an RMS norm on each branch's output
    gated_attention: bool    # o ⊙ sigmoid(a W_gate) before the projection
    rope_all_layers: bool    # rotary on the full_attention layers too
    router: str              # models/moe.ROUTERS
    objective: str           # next_token | block_diffusion
    head_norms: bool = True  # RMS norms on each query and key head
    expert: str = "swiglu"   # models/moe.EXPERTS, routed and shared alike
    one_mixer: bool = False  # MixerBlock in DecoderBlock's place


FAMILIES = {
    "afmoe": Family(True, True, False, "sigmoid_bias", "next_token"),
    "sdar_moe": Family(False, False, True, "softmax", "block_diffusion"),
    "nemotron_h": Family(False, False, False, "sigmoid_bias", "next_token",
                         head_norms=False, expert="relu2", one_mixer=True),
}


def routes(cfg, i: int) -> bool:
    """Whether layer i of a decoder holds an expert layer."""
    if FAMILIES[cfg.name].one_mixer:
        return cfg.layer_types[i] == "moe"
    return i >= cfg.num_dense_layers


def causal_flash_or_dense(impl: str) -> str:
    """The decoder's auto rule: the Pallas kernels on a TPU (nothing of
    size [T, T] exists there at any length), their jax.numpy twin elsewhere."""
    if impl != "auto":
        return impl
    return "flash" if jax.default_backend() == "tpu" else "dense"


def causal_attention(q, k, v, mask, impl: str, mesh=None):
    """softmax(q kᵀ/√d) v over the pairs ``mask`` counts (an
    ``ops.attention.Mask``); k and v may carry fewer heads than q."""
    impl = causal_flash_or_dense(impl)
    if impl == "dense":
        from ..ops.attention import attention
        return attention(q, k, v, mask)
    if impl in ("flash", "flash_interpret"):
        from ..ops.pallas import flash_attention
        interpret = impl == "flash_interpret"
        return _per_shard(
            lambda q, k, v: flash_attention(q, k, v, mask, interpret),
            mesh)(q, k, v)
    raise ValueError(f"the decoder's attention_impl is auto | dense | flash "
                     f"| flash_interpret, not {impl!r}")


class RMSNorm(nn.Module):
    """x / sqrt(mean(x²) + eps) · scale over the last axis, in float32."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def rotary(x: jax.Array, theta: float, positions=None) -> jax.Array:
    """Rotary positions over the whole last axis of x (B, T, H, hd), the
    rotate-half convention, in float32: ``positions`` (T,), one id a token
    whatever its place in the row, or 0..T-1."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if positions is None:
        positions = jnp.arange(t)
    angles = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


class GroupedAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    kind: str                        # one of LAYER_KINDS
    window: int
    rope_theta: float
    eps: float
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    mesh: Any = None
    gated: bool = True               # o ⊙ sigmoid(a W_gate)
    rope_all_layers: bool = False    # rotary on the full layers too
    mask: Any = None                 # an ops.attention.Mask in the causal one's place
    head_norms: bool = True          # RMS norms on each query and key head

    @nn.compact
    def __call__(self, a: jax.Array, positions=None) -> jax.Array:
        b, t, d = a.shape
        h, kv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        a = a.astype(self.dtype)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        q = dense(h * hd, name="q_proj")(a).reshape(b, t, h, hd)
        k = dense(kv * hd, name="k_proj")(a).reshape(b, t, kv, hd)
        v = dense(kv * hd, name="v_proj")(a).reshape(b, t, kv, hd)
        if self.head_norms:
            q = RMSNorm(self.eps, name="q_norm")(q)
            k = RMSNorm(self.eps, name="k_norm")(k)
        sliding = self.kind == "sliding_attention"
        # afmoe's full layers carry no positional term at all
        if sliding or self.rope_all_layers:
            with jax.named_scope("rotary"):
                q = rotary(q, self.rope_theta, positions)
                k = rotary(k, self.rope_theta, positions)
        from ..ops.attention import Mask
        mask = self.mask or Mask("causal", self.window if sliding else None)
        # the kernels (or their twin) with their casts, reshapes and block
        # tables: `attention` without `core` is what the kernels do not do
        with jax.named_scope("core"):
            o = causal_attention(q.astype(self.dtype), k.astype(self.dtype),
                                 v, mask, self.attention_impl, self.mesh)
        o = o.reshape(b, t, h * hd)
        if self.gated:
            o = o * nn.sigmoid(dense(h * hd, name="gate_proj")(a))
        return dense(d, name="o_proj")(o)


class DecoderBlock(nn.Module):
    cfg: Any                         # the ModelConfig (utils/config.py)
    index: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    mesh: Any = None
    mask: Any = None                 # GroupedAttention's

    @nn.compact
    def __call__(self, x: jax.Array, positions=None):
        """x (B, T, d) float32 -> (x, counts, windows): counts (E,) of this
        layer's assignments and the windows its held experts walked
        (models/moe.held_experts_sum) where it routes, scalars 0 where it
        is dense."""
        c = self.cfg
        family = FAMILIES[c.name]
        kind = c.layer_types[self.index]
        if kind not in LAYER_KINDS[:2]:
            raise ValueError(f"layer kind {kind!r} is none of {LAYER_KINDS[:2]}")
        norm = partial(RMSNorm, c.rms_norm_eps)

        def joins(branch, name):  # as it is, or through a norm of its own
            return norm(name=name)(branch) if family.post_norms else branch
        with jax.named_scope("attention"):
            a = GroupedAttention(
                c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                kind, c.sliding_window, c.rope_theta, c.rms_norm_eps,
                self.dtype, self.attention_impl, self.mesh,
                family.gated_attention, family.rope_all_layers, self.mask,
                family.head_norms, name="attn")(norm(name="input_norm")(x),
                                                positions)
            x = x + joins(a, "post_attn_norm")
        m = norm(name="pre_mlp_norm")(x)
        if self.index < c.num_dense_layers:
            from .moe import SwiGLU
            f = SwiGLU(c.intermediate_size, self.dtype,
                       name="mlp")(m.astype(self.dtype))
            counts = windows = jnp.zeros((), jnp.float32)
        else:
            f, counts, windows = _expert_layer(c, family, self.dtype, m)
        return x + joins(f, "post_mlp_norm"), counts, windows


def _expert_layer(c, family, dtype, m):
    """The routed experts held here and the shared one over m (B, T, d),
    inside the current module: (out, counts, windows)."""
    from .moe import DroplessMoe
    b, t, d = m.shape
    shared = c.moe_shared_expert_intermediate_size \
        or c.moe_intermediate_size * c.num_shared_experts
    # a method other than __call__ is profiled as "moe.walked": the scope
    # the traces are read by is named here
    with jax.named_scope("moe"):
        f, counts, windows = DroplessMoe(
            c.num_experts, tuple(c.experts_held), c.num_experts_per_tok,
            c.moe_intermediate_size, shared, c.route_scale, dtype,
            family.router, family.expert,
            name="moe").walked(m.reshape(b * t, d))
    return f.reshape(b, t, d), counts, windows


class MixerBlock(nn.Module):
    """x + mixer(RMSNorm(x)), ONE mixer a block, the layer's kind choosing
    it: ``mamba`` (models/mamba.Mamba2), ``moe`` (the expert layer) or
    ``full_attention`` (the family's GroupedAttention, causal). Returns
    (x, counts, windows) as ``DecoderBlock`` does."""
    cfg: Any
    index: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    mesh: Any = None
    mask: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, positions=None):
        c = self.cfg
        family = FAMILIES[c.name]
        kind = c.layer_types[self.index]
        norm = partial(RMSNorm, c.rms_norm_eps, name="input_norm")
        counts = windows = jnp.zeros((), jnp.float32)
        if kind == "mamba":
            from .mamba import Mamba2
            with jax.named_scope("mamba"):
                x = x + Mamba2(c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                               c.ssm_state_size, c.conv_kernel, c.chunk_size,
                               c.rms_norm_eps, (c.time_step_floor, c.time_step_min,
                                                c.time_step_max), self.dtype,
                               name="mamba")(norm()(x))
        elif kind == "moe":
            f, counts, windows = _expert_layer(c, family, self.dtype, norm()(x))
            x = x + f
        elif kind == "full_attention":
            with jax.named_scope("attention"):
                x = x + GroupedAttention(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    kind, c.sliding_window, c.rope_theta, c.rms_norm_eps,
                    self.dtype, self.attention_impl, self.mesh,
                    family.gated_attention, family.rope_all_layers, self.mask,
                    family.head_norms, name="attn")(norm()(x), positions)
        else:
            raise ValueError(f"a one-mixer block is mamba, moe or "
                             f"full_attention, not {kind!r}")
        return x, counts, windows


class CausalDecoder(nn.Module):
    """tokens (B, T) int32 -> logits (B, T, V) float32; with ``targets``
    (B, T') the training outputs instead: ``{"loss", "correct", "counts",
    "windows"}``,
    the mean cross-entropy of the first T' positions against them and the
    share of positions whose largest logit is the target (``weights``
    (B, T'): Σ w · nll over B·T', and the share over the positions of
    weight > 0), computed a chunk of positions at a time (the logits of a
    whole batch never exist, and positions past T' never meet the head),
    and per routing layer the counts of assignments (``layer<i>`` -> (E,))
    and the windows its held experts walked a chunk (a list of scalars).
    ``positions`` (T,) are the tokens' rotary ids where they are not 0..T-1,
    ``mask`` an ``ops.attention.Mask`` in the place of the layers' causal
    ones."""
    cfg: Any
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    remat: bool = True
    mesh: Any = None

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = True, targets=None,
                 positions=None, mask=None, weights=None):
        del train  # no dropout, no batch statistics
        c = self.cfg
        d, v = c.hidden_size, c.vocab_held
        embedding = _Embedding(v, d, name="embed")()
        x = embedding[tokens].astype(jnp.float32)
        if c.mup_enabled:
            x = x * math.sqrt(d)
        block = MixerBlock if FAMILIES[c.name].one_mixer else DecoderBlock
        if self.remat:
            # a block's activations are recomputed in the backward pass,
            # but for the flash kernel's output and logsumexp (134 MB and
            # 2 MB a layer at 16,384 tokens): the forward kernel then runs
            # once a step, not twice
            from ..ops.pallas.flash_attention import SAVEABLE
            block = nn.remat(block, policy=jax.checkpoint_policies
                             .save_only_these_names(*SAVEABLE))
        counts, windows = {}, []
        for i in range(len(c.layer_types)):
            x, got, walked = block(c, i, self.dtype, self.attention_impl,
                                   self.mesh, mask,
                                   name=f"layer{i}")(x, positions)
            if routes(c, i):
                counts[f"layer{i}"] = got
                windows.append(walked)
        x = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
        from .moe import Kernel
        head = Kernel(v, name="lm_head")(d)
        with jax.named_scope("lm_head"):
            if targets is None:
                return jnp.dot(x.astype(self.dtype), head.astype(self.dtype),
                               preferred_element_type=jnp.float32)
            loss, correct = chunked_next_token_loss(
                x[:, :targets.shape[1]], head, targets, self.dtype, weights)
        return {"loss": loss, "correct": correct, "counts": counts,
                "windows": windows}

    def objective(self):
        """The family's loss over its own batch (train/loop.py)."""
        return {"next_token": NextTokenObjective,
                "block_diffusion": BlockDiffusionObjective}[
                    FAMILIES[self.cfg.name].objective](self.cfg)

    def init_input(self, rows: int, data_cfg) -> jax.ShapeDtypeStruct:
        """What ``init`` is traced on: parameters do not depend on the
        sequence's length, so a short one."""
        return jax.ShapeDtypeStruct((rows, min(data_cfg.seq_len, 128)),
                                    jnp.int32)


class NextTokenObjective:
    """``{"tokens": int32 (B, T + 1)}``: inputs are all but the last id of
    a row, targets all but the first; the loss is the mean next-token
    cross-entropy over every position of the batch, with no auxiliary term.
    ``after_update`` is the router-bias rule (arXiv:2408.15664): after the
    optimizer's update, ``b += load_balance_coeff · sign(mean(c) − c)`` from
    the step's counts ``c`` of assignments to each published expert; the
    bias has no gradient (models/moe.biased_topk_route stops it) and no
    decay (train/optimizers._non_bn_mask)."""
    batch_keys = ("tokens",)

    def __init__(self, cfg):
        self.cfg = cfg

    def prepare(self, batch, step, midx=None):
        del step, midx
        return batch

    def forward(self, apply_fn, variables, batch):
        tokens = batch["tokens"]
        out = apply_fn({"params": variables["params"]}, tokens[:, :-1],
                       train=True, targets=tokens[:, 1:])
        metrics = {"precision": out["correct"],
                   **_held_load(self.cfg, out)}
        return (out["loss"], metrics, variables["batch_stats"], [],
                out["counts"])

    def after_update(self, params, counts):
        params = dict(params)
        for layer, c in counts.items():
            moe = dict(params[layer]["moe"])
            moe["router_bias"] = moe["router_bias"] \
                + self.cfg.load_balance_coeff * jnp.sign(jnp.mean(c) - c)
            params[layer] = dict(params[layer], moe=moe)
        return params


def _held_load(cfg, out) -> dict:
    """The step's assignments to the experts held here, a mean over the
    routing layers, the fullest held expert against its layer's mean, and
    the windows of sorted assignments a layer walked, a mean over the
    layers (1 at up to twice the expected load, models/moe.walk_rows: the
    expert layer's time rises with it a window at a time)."""
    counts = out["counts"]
    if not counts:
        return {}
    lo, hi = cfg.experts_held
    held = jnp.stack([c[lo:hi] for c in counts.values()])
    per_layer = jnp.sum(held, axis=-1)
    # expectation: tokens × top-k × held / published, each layer
    return {"moe_assignments_held": jnp.mean(per_layer),
            "moe_load_max_over_mean": jnp.max(
                jnp.max(held, axis=-1) * (hi - lo)
                / jnp.maximum(per_layer, 1.0)),
            "moe_windows": jnp.mean(jnp.stack(out["windows"]))}


class BlockDiffusionObjective:
    """``{"tokens": int32 (N, L), "masked": uint8 (N, L), "t": float32
    (N, L/B)}`` (data/tokens.block_diffusion_iterator): the block-diffusion
    loss of arXiv:2503.09573, vectorised. The decoder runs once over 2L
    positions a sequence, the noisy copy (a masked id replaced by
    ``mask_token_held``) then the clean copy, a token of either copy at the
    position id of its place in the sequence, under the block-diffusion
    ``Mask``. The head reads the noisy copy alone, each position predicting
    the id at its own place (no shift); an example's loss is
    (1/L) Σ_i masked_i / t_block(i) · nll_i, the step's the mean over the
    examples; ``precision`` is the share of masked positions whose largest
    logit is the id. No auxiliary term and no rule after the update."""
    batch_keys = ("tokens", "masked", "t")
    after_update = None

    def __init__(self, cfg):
        self.cfg = cfg

    def prepare(self, batch, step, midx=None):
        del step, midx
        return batch

    def forward(self, apply_fn, variables, batch):
        from ..ops.attention import block_diffusion_mask
        c = self.cfg
        tokens, masked = batch["tokens"], batch["masked"] != 0
        length = tokens.shape[1]
        with jax.named_scope("blockdiff_input"):
            noisy = jnp.where(masked, c.mask_token_held, tokens)
            both = jnp.concatenate([noisy, tokens], axis=1)
            positions = jnp.tile(jnp.arange(length), 2)
            weights = masked / jnp.repeat(batch["t"], c.block_length, axis=1)
        out = apply_fn({"params": variables["params"]}, both, train=True,
                       targets=tokens, positions=positions,
                       mask=block_diffusion_mask(length, c.block_length),
                       weights=weights)
        metrics = {"precision": out["correct"],
                   "masked_share": jnp.mean(masked.astype(jnp.float32)),
                   "loss_weight_mean": jnp.mean(weights),
                   **_held_load(c, out)}
        return out["loss"], metrics, variables["batch_stats"], [], None


class _Embedding(nn.Module):
    rows: int
    features: int

    @nn.compact
    def __call__(self):
        return self.param("embedding", nn.initializers.normal(0.02),
                          (self.rows, self.features))


#: positions to a block of logits (chunked_next_token_loss)
LOSS_CHUNK = 2048


def chunked_next_token_loss(x, head, targets, dtype, weights=None):
    """Mean over all positions of logsumexp(x W) − (x W)[target], and the
    share of positions whose arg-max is the target; ``LOSS_CHUNK`` positions
    at a time, each chunk's logits recomputed in the backward pass. With
    ``weights`` (as ``targets``): Σ weight · nll over the number of
    positions, and the share among the positions of weight > 0."""
    b, t, d = x.shape
    n = b * t
    chunk = math.gcd(n, LOSS_CHUNK)
    w = head.astype(dtype)
    if weights is None:
        weights = jnp.ones(targets.shape, jnp.float32)
        counted = n
    else:
        counted = jnp.maximum(jnp.sum(weights > 0), 1)

    @jax.checkpoint
    def one(args):
        xc, yc, wc = args
        logits = jnp.dot(xc.astype(dtype), w,
                         preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - picked
        hit = (jnp.argmax(logits, axis=-1) == yc).astype(jnp.float32)
        return jnp.sum(wc * nll), jnp.sum((wc > 0) * hit)
    split = lambda a: a.reshape((n // chunk, chunk) + a.shape[2:])  # noqa: E731
    nll, hit = jax.lax.map(one, (split(x), split(targets), split(weights)))
    return jnp.sum(nll) / n, jnp.sum(hit) / counted
