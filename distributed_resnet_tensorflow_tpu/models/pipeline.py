"""Pipeline parallelism — GPipe-style microbatched encoder over the
``pipeline`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.10: pure data
parallel); this module completes the mesh: every axis of
(data, fsdp, seq, tensor, pipeline) now has a consumer. Design:

  * The encoder's per-layer parameters are STACKED on a leading depth axis
    and sharded over ``pipeline`` (each stage holds depth/P layers) — the
    pipeline analog of the fsdp/tensor rules in parallel/sharding.py.
  * Execution is a ``shard_map`` over the pipeline axis running the GPipe
    schedule as one ``lax.scan`` over M + P - 1 ticks: at tick t, stage s
    processes microbatch t - s; activations hop stages via
    ``lax.ppermute`` (ICI neighbor traffic), stage 0 injects microbatches,
    the last stage collects outputs, and a final masked ``psum`` broadcasts
    them to every stage. Reverse-mode AD is the transposed schedule (scan
    reversed, ppermute inverted) — the backward pipeline for free.
  * Bubble ticks compute on zero-activations and are masked out of the
    result; the bubble fraction is (P-1)/(M+P-1), so M defaults to 2P.
  * ``interleave`` (v) > 1 runs the CIRCULAR schedule (Megatron interleaved /
    praxis circular): each stage holds v non-adjacent chunks of depth/(P*v)
    layers and every microbatch rides the ring v times, shrinking the bubble
    to (P-1)/(v*M+P-1) at the cost of v× more ICI hops per microbatch. At
    tick t, stage s works on u = t - s decomposed as (chunk, microbatch) =
    (u // M, u mod M); wrapped activations re-enter stage 0 through a
    per-microbatch queue because the wrap takes M-P+1 ticks (requires
    M >= P). v=1 reduces to plain GPipe.

On 1F1B: the schedule that cuts *activation memory* (not the bubble) to
O(P) microbatches per stage requires launching each microbatch's backward
eagerly, interleaved with later forwards — a per-microbatch autograd runtime,
which fights XLA's whole-program compilation model. The TPU-native
equivalents are (a) this circular schedule, which attacks the bubble
directly, and (b) ``remat=True``, which bounds the per-tick residual to the
stage inputs that reverse-mode scan transposition must keep — the same
stage-boundary stash 1F1B keeps, held for the whole step rather than P
ticks. Both compose. Measured at fixed global batch (compiled temp bytes per
device, ``tools/pipeline_memory.py`` → ``docs/pipeline_memory_r3.json``):
remat bounds the stash ~10× (738→65 MB at P=4, M=4); at EQUAL bubble the
circular schedule matches GPipe's activation memory (555 MB at v=2, M=4 vs
552 MB at v=1, M=8, both bubble 0.273) while running v× larger microbatches
— the bubble knob that does not shrink the per-tick MXU work — and extends
the reachable bubble floor past where GPipe's microbatches hit size 1.

The block math mirrors ``transformer.EncoderBlock`` op-for-op (pre-LN MHA +
pre-LN MLP with residuals) but is written against explicit stacked params so
one program serves every stage. ``pack_encoder_params`` converts a standard
per-block ViT param tree into the stacked layout (checkpoint migration and
the exact-parity tests).
"""
from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

_LN_EPS = 1e-6  # nn.LayerNorm default


def resolve_microbatches(microbatches: int, pstages: int) -> int:
    """The ONE home of the microbatch default (0 → 2 × stages, the GPipe
    sweet spot at bubble (P-1)/(M+P-1)). Shared by the encoder itself,
    Trainer.eval_pad_multiple (eval batches must pad to shards × M) and
    the static elaborator's layout filter — three callers that must agree
    or eval crashes with 'local batch must be a multiple of microbatches'
    at step 1."""
    return microbatches or 2 * pstages


def _layer_norm(x, scale, bias):
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + _LN_EPS)
    return (y * scale + bias).astype(x.dtype)


def _block_apply(p, x, num_heads, dtype, tp_axis=None, attn_impl="dense",
                 moe=None, seq=None):
    """One encoder block from a stacked-param slice ``p`` — the explicit-math
    twin of transformer.EncoderBlock (kept in lockstep; exact-parity test:
    tests/test_pipeline.py).

    ``tp_axis``: Megatron tensor parallelism inside the pipeline stage. The
    caller hands this function TENSOR-LOCAL param shards (whole heads of the
    qkv/proj kernels, columns of mlp_w1/b1, rows of mlp_w2 — the same layout
    parallel/sharding.py assigns the per-block modules); the two row-parallel
    contractions (attention out-proj, MLP down-proj) then produce partial
    sums that one ``lax.psum`` each completes — 2 collectives per block,
    exactly the Megatron count. Replicated tensors (x, LN params, mlp_b2)
    stay replicated across ``tp_axis``.

    ``attn_impl``: "dense" (XLA reference), the fused Pallas flash kernel
    ("flash" / "flash_interpret" for CPU tests) — long-context attention
    inside pipeline stages (round 4; the pallas_call runs fine under the
    pipeline shard_map, and the kernel's custom vjp rides the transposed
    scan schedule like any other block op) — or ring attention
    ("ring" / "ring_interpret", round 5, pp×seq): tokens arrive sharded
    over the ``seq`` mesh axis (``seq`` = static (axis_name, n_shards)),
    kv chunks rotate the ICI ring via ppermute INSIDE the pipeline tick,
    and the ring's custom backward rides the transposed scan exactly like
    flash did. "ring" runs the Pallas flash inner block on TPU and the
    pure-lax online recurrence elsewhere (the ring_attention_sharded auto
    rule); "ring_interpret" forces the interpreter kernels (CPU parity
    tests). LayerNorm/MLP are token-pointwise and partition cleanly over
    the extra token sharding.

    When ``p`` carries MoE leaves (moe_w1/...), the MLP is a Switch
    mixture (pp×ep, see _moe_mlp); ``moe`` is the static
    (top_k, capacity_factor, ep_axis) triple. Returns (x, aux) — aux is
    the Switch load-balancing loss for this block (0.0 for the dense
    MLP)."""
    b, t, d = x.shape
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = jnp.einsum("btd,dchk->btchk", h, p["qkv_kernel"].astype(dtype))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if attn_impl in ("flash", "flash_interpret"):
        from ..ops.pallas import flash_attention
        o = flash_attention(q, k, v, False, attn_impl == "flash_interpret")
    elif attn_impl == "dense":
        from ..ops.attention import attention
        o = attention(q, k, v)  # local heads only under tp
    elif attn_impl in ("ring", "ring_interpret"):
        from ..ops.attention import resolve_ring_kernel
        seq_axis, n_seq = seq
        kern = resolve_ring_kernel(
            "flash_interpret" if attn_impl == "ring_interpret" else "auto")
        if kern == "lax":
            from ..ops.attention import ring_attention
            o = ring_attention(q, k, v, seq_axis)
        else:
            from ..ops.pallas.flash_attention import ring_flash_attention
            o = ring_flash_attention(q, k, v, seq_axis, n_seq, False,
                                     kern == "flash_interpret")
    else:
        raise ValueError(
            f"pipelined blocks support dense/flash/ring attention, "
            f"got {attn_impl!r}")
    o = jnp.einsum("bthk,hkd->btd", o, p["proj_kernel"].astype(dtype))
    if tp_axis is not None:
        o = lax.psum(o, tp_axis)
    x = x + o
    h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    if "moe_w1" in p:
        top_k, cap_factor, ep_axis = moe or (1, 1.25, None)
        h, aux = _moe_mlp(p, h, dtype, top_k, cap_factor, ep_axis, tp_axis)
        return x + h, aux
    h = jnp.einsum("btd,df->btf", h, p["mlp_w1"].astype(dtype)) \
        + p["mlp_b1"].astype(dtype)
    h = nn.gelu(h)
    h = jnp.einsum("btf,fd->btd", h, p["mlp_w2"].astype(dtype))
    if tp_axis is not None:
        h = lax.psum(h, tp_axis)
    h = h + p["mlp_b2"].astype(dtype)
    return x + h, jnp.float32(0.0)


def _moe_mlp(p, h, dtype, top_k=1, capacity_factor=1.25, ep_axis=None,
             tp_axis=None):
    """Switch MoE MLP from stacked-slice params, expert-sharded over
    ``ep_axis`` inside the pipeline shard_map (pp×ep, round 4).

    Tokens arrive REPLICATED across the expert axis (the pipeline body's
    x spec mentions batch axes only), so each device can gather its LOCAL
    experts' token slots directly — the O(N + E_loc·C) slot-table dispatch
    of models/moe.py, offset into the device's expert range — compute its
    expert block, and contribute a partial combine; ONE ``lax.psum`` over
    the expert axis completes the output. No one-hot tensors, no token
    all-to-all (the replication the pipeline already maintains makes the
    exchange free). Routing runs identically on every expert-peer
    (replicated router params) so drop decisions are globally consistent;
    the capacity group is the (data-shard, microbatch) token block.
    Returns (out, aux) with the Switch load-balancing loss.

    Routing/dispatch/combine/FFN math is the SHARED models/moe.py
    machinery (_route_assign, gather_slot_table, combine_from_slots,
    expert_ffn, switch_aux_loss) — the only pipeline-specific parts are
    the per-device expert offset and the completing psum.

    ``tp_axis`` (pp×ep×tp, round 5): each local expert's FFN is
    additionally Megatron-split over the tensor axis — the caller's
    stacked params arrive column-/row-sharded (stacked_encoder_spec) and
    expert_ffn's internal psum completes the down-projection before the
    expert-axis combine psum."""
    import math
    from .moe import (_route_assign, combine_from_slots, expert_ffn,
                      gather_slot_table, switch_aux_loss)
    b, t, d = h.shape
    n = b * t
    e_glob = p["router_kernel"].shape[-1]
    e_loc = p["moe_w1"].shape[0]
    my = lax.axis_index(ep_axis) if ep_axis is not None else 0
    flat = h.reshape(n, d)
    logits = flat.astype(jnp.float32) @ p["router_kernel"].astype(jnp.float32) \
        + p["router_bias"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    cap = max(1, math.ceil(top_k * (n / e_glob) * capacity_factor))
    assigned = _route_assign(probs, e_glob, cap, top_k)

    sel = gather_slot_table(assigned, n, cap, e_loc, e_lo=my * e_loc)
    padded = jnp.concatenate(
        [flat.astype(dtype), jnp.zeros((1, d), dtype)], axis=0)
    ein = jnp.take(padded, sel, axis=0).reshape(e_loc, cap, d)
    eout = expert_ffn(ein, p["moe_w1"], p["moe_bias1"], p["moe_w2"],
                      p["moe_bias2"], dtype,
                      tp_axis=tp_axis).reshape(e_loc * cap, d)
    out = combine_from_slots(assigned, eout, n, cap, dtype, e_loc,
                             e_lo=my * e_loc)
    if ep_axis is not None:
        out = lax.psum(out, ep_axis)
    return out.reshape(b, t, d), switch_aux_loss(probs)


class PipelinedEncoder(nn.Module):
    """Stacked-parameter transformer encoder, pipelined when
    ``mesh.shape['pipeline'] > 1`` (plain scan over layers otherwise)."""

    depth: int
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    mesh: Any = None
    microbatches: int = 0  # 0 → 2 × pipeline stages
    remat: bool = False    # jax.checkpoint each block (GPipe's usual pairing)
    interleave: int = 1    # v>1 → circular schedule, v chunks per stage
    attention_impl: str = "dense"  # dense | flash | flash_interpret
    num_experts: int = 0           # >0 → Switch MoE MLPs (pp×ep)
    expert_capacity_factor: float = 1.25
    moe_top_k: int = 1

    def _params(self, d):
        hd = d // self.num_heads
        f = self.mlp_ratio * d
        vs = jax.nn.initializers.variance_scaling
        def stacked(name, shape, init):
            return self.param(name, init, (self.depth,) + shape,
                              jnp.float32)
        ones = lambda key, shape, dtype: jnp.ones(shape, dtype)   # noqa: E731
        zeros = nn.initializers.zeros
        p = {
            "ln1_scale": stacked("ln1_scale", (d,), ones),
            "ln1_bias": stacked("ln1_bias", (d,), zeros),
            "qkv_kernel": stacked(
                "qkv_kernel", (d, 3, self.num_heads, hd),
                vs(1.0, "fan_in", "truncated_normal", in_axis=1,
                   out_axis=(2, 3, 4), batch_axis=0)),
            "proj_kernel": stacked(
                "proj_kernel", (self.num_heads, hd, d),
                vs(1.0, "fan_in", "truncated_normal", in_axis=(1, 2),
                   out_axis=3, batch_axis=0)),
            "ln2_scale": stacked("ln2_scale", (d,), ones),
            "ln2_bias": stacked("ln2_bias", (d,), zeros),
        }
        if self.num_experts > 0:
            e = self.num_experts
            # SwitchMlp's stacked-expert layout with a leading depth axis;
            # "bias"-named like models/moe.py so optimizer masks skip them
            p.update({
                "router_kernel": stacked(
                    "router_kernel", (d, e),
                    vs(1.0, "fan_in", "truncated_normal",
                       in_axis=1, out_axis=2, batch_axis=0)),
                "router_bias": stacked("router_bias", (e,), zeros),
                "moe_w1": stacked(
                    "moe_w1", (e, d, f),
                    vs(1.0, "fan_in", "truncated_normal", in_axis=2,
                       out_axis=3, batch_axis=(0, 1))),
                "moe_bias1": stacked("moe_bias1", (e, f), zeros),
                "moe_w2": stacked(
                    "moe_w2", (e, f, d),
                    vs(1.0, "fan_in", "truncated_normal", in_axis=2,
                       out_axis=3, batch_axis=(0, 1))),
                "moe_bias2": stacked("moe_bias2", (e, d), zeros),
            })
        else:
            p.update({
                "mlp_w1": stacked(
                    "mlp_w1", (d, f),
                    vs(1.0, "fan_in", "truncated_normal", in_axis=1,
                       out_axis=2, batch_axis=0)),
                "mlp_b1": stacked("mlp_b1", (f,), zeros),
                "mlp_w2": stacked(
                    "mlp_w2", (f, d),
                    vs(1.0, "fan_in", "truncated_normal", in_axis=1,
                       out_axis=2, batch_axis=0)),
                "mlp_b2": stacked("mlp_b2", (d,), zeros),
            })
        return p

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        params = self._params(d)
        nblocks = self.depth
        pstages = self.mesh.shape.get("pipeline", 1) \
            if self.mesh is not None else 1

        tp = self.mesh.shape.get("tensor", 1) if self.mesh is not None else 1
        tp_axis = "tensor" if (tp > 1 and pstages > 1) else None
        sp = self.mesh.shape.get("seq", 1) if self.mesh is not None else 1
        ring = self.attention_impl in ("ring", "ring_interpret")
        if sp > 1 and not ring:
            raise ValueError(
                "pipeline x seq runs ring attention inside the stage "
                "blocks; set attention_impl='ring' "
                f"(got {self.attention_impl!r})")
        if ring and sp <= 1:
            raise ValueError(
                "attention_impl='ring' in the pipelined encoder requires "
                "mesh.seq > 1")
        if ring and t % sp:
            raise ValueError(f"{t} tokens not divisible by seq axis {sp}")
        seq_static = ("seq", sp) if ring else None

        block_fn = _block_apply
        if self.remat:
            block_fn = jax.checkpoint(
                _block_apply, static_argnums=(2, 3, 4, 5, 6, 7))
        moe_static = None
        if self.num_experts > 0:
            ep = self.mesh.shape.get("expert", 1) \
                if self.mesh is not None else 1
            moe_static = (self.moe_top_k, self.expert_capacity_factor,
                          "expert" if ep > 1 else None)
            if self.num_experts % max(1, ep):
                raise ValueError(
                    f"num_experts {self.num_experts} not divisible by "
                    f"expert axis {ep}")

        def run_layers(p, h, tp_ax=None, mapped=True):
            """(h, aux_sum) over this param stack's layers. ``mapped=False``
            is for callers OUTSIDE the shard_map (sequential path, init
            fallback): the expert/seq axis names are only bound inside the
            mapped body, so the moe triple drops its axis and ring
            attention falls back to dense — mathematically identical over
            the then-unsharded token dim, and parameter-free either way."""
            mo = moe_static if mapped else moe_unmapped()
            ai, sq = self.attention_impl, seq_static
            if not mapped and ring:
                ai, sq = "dense", None
            def step(hh, pp):
                hh, aux = block_fn(pp, hh, self.num_heads, self.dtype,
                                   tp_ax, ai, mo, sq)
                return hh, aux
            h, auxs = lax.scan(step, h, p)
            return h, jnp.sum(auxs)

        def moe_unmapped():
            return (moe_static[0], moe_static[1], None) \
                if moe_static is not None else None

        v = max(1, self.interleave)
        if pstages > 1 and nblocks % (pstages * v):
            raise ValueError(
                f"depth {nblocks} not divisible by pipeline stages "
                f"{pstages} x interleave {v}")
        if tp_axis is not None:
            if self.num_heads % tp:
                raise ValueError(
                    f"heads {self.num_heads} not divisible by tensor axis {tp}")
            if (self.mlp_ratio * d) % tp:
                raise ValueError(
                    f"mlp hidden {self.mlp_ratio * d} not divisible by "
                    f"tensor axis {tp}")
        m = resolve_microbatches(self.microbatches, pstages)
        if v > 1 and pstages > 1 and m < pstages:
            # the circular wrap takes M-P+1 ticks; M >= P keeps the stage-0
            # re-injection queue causally ahead of its consumption
            raise ValueError(
                f"interleave {v} requires microbatches ({m}) >= pipeline "
                f"stages ({pstages})")
        # microbatching applies to the LOCAL batch: each data-parallel shard
        # runs its own pipeline over its slice of the batch
        if self.mesh is not None:
            from ..parallel.mesh import batch_shard_count
            n_batch_shards = batch_shard_count(self.mesh)
        else:
            n_batch_shards = 1
        local_b = b // max(1, n_batch_shards)

        def finish(y, aux):
            if self.num_experts > 0 and not self.is_initializing():
                self.sow("losses", "moe_aux", aux)
            return y

        if pstages <= 1:
            # sequential path (mesh-less, or pipeline axis collapsed):
            # plain layer scan. The product only reaches PipelinedEncoder
            # with pipeline > 1 (VisionTransformer routes unpipelined MoE
            # through SwitchMlp), so no expert axis handling lives here.
            y, aux = run_layers(params, x, mapped=False)
            return finish(y, aux)
        if local_b < m or local_b % m:
            # the shape-only init dummy may be too small to microbatch —
            # parameters are created identically on both paths, so it runs
            # sequentially; a REAL batch in this state must fail loudly
            # (a silent sequential fallback would idle P-1 stages)
            if self.is_initializing():
                return run_layers(params, x, mapped=False)[0]
            raise ValueError(
                f"local batch {local_b} (global {b} over {n_batch_shards} "
                f"batch shards) must be a multiple of microbatches {m}")

        mesh = self.mesh
        from ..parallel.mesh import present_batch_axes
        batch_axes = present_batch_axes(mesh)
        x_spec = P(batch_axes or None, "seq" if ring else None, None)
        # per-leaf specs MATCH param_sharding_rule's placement (pipeline on
        # the stacked depth axis, tensor on heads/hidden when tp is active)
        # so the shard_map consumes the training state's own shards with no
        # per-step resharding
        from ..parallel.sharding import stacked_encoder_spec
        p_spec = {name: stacked_encoder_spec(name, leaf.ndim, tp)
                  for name, leaf in params.items()}
        perm = [(i, (i + 1) % pstages) for i in range(pstages)]

        def _aux_reduce(aux_acc):
            """Stage-local aux sums → one replicated (1,)-vector: sum stages,
            mean over microbatches (matching the unpipelined batch-level
            scale) and over the batch (and token, under seq sharding)
            shards. Shape (1,) rather than scalar end-to-end: a rank-0
            value at the shard_map boundary becomes a rank-0 residual
            under AD, and the shard_map transpose of jax 0.4.37 (which
            this was written against; not re-checked on 0.9) assigned
            residual cotangents axis names on dim 0 — a _SpecError for
            scalars (the pp×ep MoE failure this comment documents; see
            analysis/elaborate.py which now catches the class)."""
            aux = lax.psum(aux_acc, "pipeline") / m
            for ax in batch_axes:
                aux = lax.pmean(aux, ax)
            if ring:
                aux = lax.pmean(aux, "seq")
            return aux

        def pipelined(p_local, xg):
            stage = lax.axis_index("pipeline")
            mb = xg.shape[0] // m
            xs = xg.reshape((m, mb) + xg.shape[1:])

            def tick(carry, tt):
                prev, out, aux_acc = carry
                recv = lax.ppermute(prev, "pipeline", perm)
                inject = lax.dynamic_index_in_dim(
                    xs, jnp.clip(tt, 0, m - 1), axis=0, keepdims=False)
                h = jnp.where(stage == 0, inject, recv)
                y, aux = run_layers(p_local, h, tp_axis)
                u = tt - stage  # bubble ticks route zero activations:
                valid = jnp.logical_and(u >= 0, u < m)  # mask their aux
                aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
                idx = tt - (pstages - 1)
                upd = lax.dynamic_update_index_in_dim(
                    out, y.astype(out.dtype), jnp.clip(idx, 0, m - 1), axis=0)
                write = jnp.logical_and(stage == pstages - 1,
                                        jnp.logical_and(idx >= 0, idx < m))
                out = jnp.where(write, upd, out)
                return (y, out, aux_acc), None

            zero = jnp.zeros((mb,) + xg.shape[1:], xg.dtype)
            out0 = jnp.zeros_like(xs)
            (last, out, aux_acc), _ = lax.scan(
                tick, (zero, out0, jnp.zeros((1,), jnp.float32)),
                jnp.arange(m + pstages - 1))
            # outputs live on the last stage only; masked psum broadcasts
            out = lax.psum(
                jnp.where(stage == pstages - 1, out, jnp.zeros_like(out)),
                "pipeline")
            return out.reshape(xg.shape), _aux_reduce(aux_acc)

        def pipelined_circular(p_local, xg):
            """Circular schedule: v chunks of k layers per stage, vM+P-1
            ticks; stage s at tick t works item u = t - s as
            (chunk, microbatch) = (u // M, u mod M). Stage P-1's output for
            chunk c < v-1 rides the same ppermute ring back to stage 0,
            which parks it in a per-microbatch queue until its chunk-(c+1)
            slot comes up M-P+1 ticks later."""
            k = nblocks // (pstages * v)
            stage = lax.axis_index("pipeline")
            mb = xg.shape[0] // m
            xs = xg.reshape((m, mb) + xg.shape[1:])

            def chunk_params(p, c):
                return jax.tree_util.tree_map(
                    lambda a: lax.dynamic_slice_in_dim(a, c * k, k, axis=0),
                    p)

            def tick(carry, tt):
                prev, wrapq, out, aux_acc = carry
                recv = lax.ppermute(prev, "pipeline", perm)
                u = tt - stage
                mi = jnp.mod(u, m)
                ci = jnp.floor_divide(u, m)
                # stage 0: park the wrapped activation that stage P-1
                # produced at tick tt-1 (its work item was u' = tt - P)
                up = tt - pstages
                store = jnp.logical_and(
                    stage == 0,
                    jnp.logical_and(up >= 0,
                                    jnp.floor_divide(up, m) < v - 1))
                wrapq = jnp.where(
                    store,
                    lax.dynamic_update_index_in_dim(
                        wrapq, recv.astype(wrapq.dtype), jnp.mod(up, m),
                        axis=0),
                    wrapq)
                mi_c = jnp.clip(mi, 0, m - 1)
                inject = lax.dynamic_index_in_dim(xs, mi_c, axis=0,
                                                  keepdims=False)
                parked = lax.dynamic_index_in_dim(wrapq, mi_c, axis=0,
                                                  keepdims=False)
                h = jnp.where(stage == 0,
                              jnp.where(ci == 0, inject, parked), recv)
                y, aux = run_layers(
                    chunk_params(p_local, jnp.clip(ci, 0, v - 1)),
                    h, tp_axis)
                valid = jnp.logical_and(u >= 0, u < v * m)
                aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
                write = jnp.logical_and(stage == pstages - 1,
                                        jnp.logical_and(ci == v - 1, u >= 0))
                upd = lax.dynamic_update_index_in_dim(
                    out, y.astype(out.dtype), mi_c, axis=0)
                out = jnp.where(write, upd, out)
                return (y, wrapq, out, aux_acc), None

            zero = jnp.zeros((mb,) + xg.shape[1:], xg.dtype)
            (last, _wq, out, aux_acc), _ = lax.scan(
                tick,
                (zero, jnp.zeros_like(xs), jnp.zeros_like(xs),
                 jnp.zeros((1,), jnp.float32)),
                jnp.arange(v * m + pstages - 1))
            out = lax.psum(
                jnp.where(stage == pstages - 1, out, jnp.zeros_like(out)),
                "pipeline")
            return out.reshape(xg.shape), _aux_reduce(aux_acc)

        from ..parallel.mesh import shard_map_unchecked
        body = pipelined if v == 1 else pipelined_circular
        fn = shard_map_unchecked(body, mesh, in_specs=(p_spec, x_spec),
                                 out_specs=(x_spec, P(None)))
        y, aux = fn(params, x)
        return finish(y, aux[0])


def circular_layer_order(depth: int, pstages: int, interleave: int):
    """stored-row -> network-layer index map for the stacked layout.

    GPipe (interleave=1) stacks layers in network order; the circular
    schedule stores stage-major order (stage s's rows are its v chunks
    back-to-back, keeping the ``pipeline`` sharding of axis 0 contiguous):
    stored[s*(v*k) + c*k + i] = network[(c*pstages + s)*k + i].
    """
    import numpy as np
    v = max(1, interleave)
    if depth % (pstages * v):
        raise ValueError(f"depth {depth} not divisible by {pstages}x{v}")
    k = depth // (pstages * v)
    net = np.arange(depth).reshape(v, pstages, k)
    return net.transpose(1, 0, 2).reshape(depth)


def repack_stacked_params(stacked, depth: int, src=(1, 1), dst=(1, 1)):
    """Re-permute every depth-stacked leaf of an encoder param tree between
    storage layouts — checkpoint migration when (mesh.pipeline, interleave)
    changes between save and restore (the checkpoint manager refuses such
    restores; this is the deliberate-migration path). ``src``/``dst`` are
    (pstages, interleave) pairs; (P, 1) and (1, v) are both network order."""
    import numpy as np
    src_order = circular_layer_order(depth, *src)
    dst_order = circular_layer_order(depth, *dst)
    idx = jnp.asarray(np.argsort(src_order)[dst_order])
    return jax.tree_util.tree_map(lambda a: jnp.take(a, idx, axis=0), stacked)


def pack_encoder_params(vit_params: dict, depth: int, pstages: int = 1,
                        interleave: int = 1) -> dict:
    """Stack a standard per-block ViT param tree (EncoderBlock_i modules)
    into the PipelinedEncoder layout — checkpoint migration between the
    unpipelined and pipelined parameterizations. ``pstages``/``interleave``
    select the circular stacking order (no-ops at their defaults).
    Handles both MLP kinds: dense (Dense_0/Dense_1) and Switch MoE
    (SwitchMlp_0 → router/moe leaves)."""
    order = circular_layer_order(depth, max(1, pstages), interleave)

    def block(i):
        return vit_params[f"EncoderBlock_{i}"]

    def stack(fn):
        return jnp.stack([jnp.asarray(fn(block(int(i)))) for i in order])

    out = {
        "ln1_scale": stack(lambda b: b["LayerNorm_0"]["scale"]),
        "ln1_bias": stack(lambda b: b["LayerNorm_0"]["bias"]),
        "qkv_kernel": stack(
            lambda b: b["MultiHeadAttention_0"]["qkv"]["kernel"]),
        "proj_kernel": stack(
            lambda b: b["MultiHeadAttention_0"]["proj"]["kernel"]),
        "ln2_scale": stack(lambda b: b["LayerNorm_1"]["scale"]),
        "ln2_bias": stack(lambda b: b["LayerNorm_1"]["bias"]),
    }
    if "SwitchMlp_0" in block(0):
        out.update({
            "router_kernel": stack(
                lambda b: b["SwitchMlp_0"]["router"]["kernel"]),
            "router_bias": stack(
                lambda b: b["SwitchMlp_0"]["router"]["bias"]),
            "moe_w1": stack(lambda b: b["SwitchMlp_0"]["w1"]),
            "moe_bias1": stack(lambda b: b["SwitchMlp_0"]["bias1"]),
            "moe_w2": stack(lambda b: b["SwitchMlp_0"]["w2"]),
            "moe_bias2": stack(lambda b: b["SwitchMlp_0"]["bias2"]),
        })
    else:
        out.update({
            "mlp_w1": stack(lambda b: b["Dense_0"]["kernel"]),
            "mlp_b1": stack(lambda b: b["Dense_0"]["bias"]),
            "mlp_w2": stack(lambda b: b["Dense_1"]["kernel"]),
            "mlp_b2": stack(lambda b: b["Dense_1"]["bias"]),
        })
    return out
