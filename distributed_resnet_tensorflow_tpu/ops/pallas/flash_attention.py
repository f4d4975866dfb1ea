"""Flash attention — Pallas TPU kernels, fused forward AND backward.

Canonical TPU tiling: grid (batch·heads, q_blocks, k_blocks) with the k-block
dimension innermost and sequential ("arbitrary" semantics); online-softmax
accumulators (m, l, acc) live in VMEM scratch and persist across the k-block
iterations, so VMEM holds only one (block_q, d) query tile and one
(block_k, d) key/value tile at a time — O(block) VMEM, any sequence length.
Output (+ the logsumexp residual) is written on the last k iteration.

The backward is the flash-attention-2 formulation in two Pallas passes that
recompute P per tile from (q, k, lse) — no O(T²) residuals and no extra full
forward: a dQ kernel marching k-blocks innermost, and a dK/dV kernel
marching q-blocks innermost, with Δ = rowsum(dO ∘ O) precomputed as one
fused elementwise pass.

Layout: (B, T, H, D). The wrapper pads T up to lcm(block_q, block_k) and D to
the 128-lane width; padded keys are masked via ``valid_len``, padded queries
are sliced off. Causal masking uses the dense-attention convention: with
tq == tk the diagonal, i.e. query i attends keys ≤ i. ``window`` (with
``causal``) narrows that to the band i − window < j ≤ i: blocks outside the
band are skipped like blocks above the diagonal, in the compute (``pl.when``)
and in the copy (their block index is clamped to the nearest live block, and
a block index that repeats is not fetched again).

Grouped heads: ``k`` and ``v`` may carry fewer heads than ``q`` (H a multiple
of KV; query head h reads key/value head h // (H/KV)). Keys and values are
never copied per query head: the index maps send a group's query heads to
one (B·KV, T, D) block, and the dK/dV pass sums over the group's heads in
its innermost grid dimension.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_NEG_INF = -1e30
BLOCK_Q = 256
BLOCK_K = 256

# block tables from tools/tune_flash_attention.py on TPU v5e (bf16, causal,
# fwd+bwd grad time over the full {128,256,512}² grid at T ∈ 1k..8k for
# head dims 64 AND 128 — docs/flash_tune_r3.json): each bucket carries its
# measured winner (e.g. T=4096 d=64: 512×512 at 11.9 ms vs 14.9 for the
# old 256×256 guess; T=8192: 12.5 ms vs dense 126.7 → 10.1×). The winners
# shift with head dim (wider heads → smaller tiles; the VMEM working set
# per tile scales with d). Entries must come from the tuner, never
# intuition — an early guessed 256×512 row measured 1.8× slower than what
# it replaced.
_BLOCK_TABLES = {
    64: ((1024, (512, 512)), (2048, (128, 512)),
         (4096, (512, 512)), (8192, (512, 512))),
    128: ((1024, (128, 128)), (2048, (256, 256)),
          (4096, (256, 256)), (8192, (256, 512))),
}


def _pick_blocks(t: int, d: int) -> tuple:
    table = _BLOCK_TABLES[64 if d <= 96 else 128]
    for upper, blocks in table:
        if t <= upper:
            return blocks
    return table[-1][1]


def _live(qi, kj, causal, window, block_q, block_k):
    """Does the (q-block, k-block) tile hold any unmasked pair?"""
    if not causal:
        return True
    live = kj * block_k <= qi * block_q + block_q - 1
    if window is not None:  # its last key is inside the first query's band
        live = jnp.logical_and(
            live, kj * block_k + block_k - 1 > qi * block_q - window)
    return live


def _tile_mask(qi, kj, causal, window, valid_len, block_q, block_k):
    """(block_q, block_k) bool of the pairs that count, or None for all."""
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    mask = None
    if valid_len is not None:
        mask = k_pos < valid_len
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        cm = q_pos >= k_pos
        if window is not None:
            cm = jnp.logical_and(cm, k_pos > q_pos - window)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return mask


def _live_k(qi, kj, causal, window, block_q, block_k):
    """The k-block to hold while q-block ``qi`` meets k-block ``kj``: ``kj``
    itself where the tile is live, else the nearest live one."""
    if not causal:
        return kj
    hi = (qi * block_q + block_q - 1) // block_k
    lo = 0 if window is None else \
        jnp.maximum(qi * block_q - window + 1, 0) // block_k
    return jnp.clip(kj, lo, hi)


def _live_q(kj, qi, causal, window, block_q, block_k, nq):
    """The q-block to hold while k-block ``kj`` meets q-block ``qi``."""
    if not causal:
        return qi
    lo = jnp.minimum((kj * block_k) // block_q, nq - 1)
    hi = nq - 1 if window is None else jnp.minimum(
        (kj * block_k + block_k - 2 + window) // block_q, nq - 1)
    return jnp.clip(qi, lo, hi)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale, causal, window, valid_len, block_q, block_k, nk):
    """One (q-block, k-block) tile. Scratch m/l/acc persist across the
    innermost (k-block) grid dimension."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # blocks strictly above the causal diagonal, or wholly outside the
    # window's band, contribute nothing
    @pl.when(_live(qi, kj, causal, window, block_q, block_k))
    def _accumulate():
        # keep matmul OPERANDS in the input dtype (bf16 on the MXU's native
        # rate — an f32 cast would halve/quarter throughput); accumulate f32
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qi, kj, causal, window, valid_len, block_q,
                          block_k)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev, l_prev, acc_prev = m_ref[:], l_ref[:], acc_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_prev * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # logsumexp residual for the fused backward: lse = m + log(l);
        # guard fully-masked rows (m = -inf) to keep exp(s - lse) finite
        m = m_ref[:]
        lse_ref[0] = jnp.where(m <= _NEG_INF / 2, 0.0, m + jnp.log(l))


def _geometry(t, d, block_q, block_k):
    """Common fwd/bwd tiling: clamp blocks to the (padded) sequence, keeping
    them a multiple of the TPU sublane tile (16 covers bf16's (16,128) and
    f32's (8,128)) so Mosaic accepts shapes like t=196 (ViT-224/16)."""
    t16 = -(-t // 16) * 16
    block_q = min(block_q, t16)
    block_k = min(block_k, t16)
    step = math.lcm(block_q, block_k)
    tpad = (-t) % step
    dpad = (-d) % 128
    return block_q, block_k, tpad, dpad


def _fold(x, b, h, d):  # (B,T,H,D) → (B·H, T, D)
    return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)


def _unfold(x, b, h, t, d):  # (B·H, T, D) → (B,T,H,D)
    return x.reshape(b, h, x.shape[1], x.shape[2])[:, :, :t, :d] \
        .transpose(0, 2, 1, 3)


def _group(q, k, window, causal):
    """Query heads to a key/value head; refuses what the kernels cannot do."""
    h, kvh = q.shape[2], k.shape[2]
    if h % kvh:
        raise ValueError(f"{h} query heads are no multiple of {kvh} "
                         "key/value heads")
    if window is not None and not causal:
        raise ValueError("a window needs causal=True")
    if window is not None and q.shape[1] != k.shape[1]:
        raise ValueError("a window needs as many queries as keys")
    return h // kvh


def _flash_forward(q, k, v, causal=False, interpret=False,
                   block_q=BLOCK_Q, block_k=BLOCK_K, return_residuals=False,
                   window=None):
    b, t, h, d = q.shape
    g = _group(q, k, window, causal)
    scale = 1.0 / math.sqrt(d)
    block_q, block_k, tpad, dpad = _geometry(t, d, block_q, block_k)

    qf = _fold(q, b, h, d)
    kf, vf = (_fold(x, b, h // g, d) for x in (k, v))
    if tpad or dpad:
        pad = ((0, 0), (0, tpad), (0, dpad))
        qf, kf, vf = (jnp.pad(x, pad) for x in (qf, kf, vf))
    tp, dp = qf.shape[1], qf.shape[2]
    nq, nk = tp // block_q, tp // block_k
    grid = (b * h, nq, nk)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        valid_len=(t if tpad else None), block_q=block_q, block_k=block_k,
        nk=nk)

    def k_at(bh, i, j):  # a group's query heads read one key/value head
        return (bh // g, _live_k(i, j, causal, window, block_q, block_k), 0)

    scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, 1), jnp.float32),
               pltpu.VMEM((block_q, dp), jnp.float32)]
    extra = {}
    if not interpret:
        extra = dict(compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, i, j: (bh, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, block_k, dp), k_at, memory_space=_VMEM),
            pl.BlockSpec((1, block_k, dp), k_at, memory_space=_VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, i, j: (bh, i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tp, dp), q.dtype),
            jax.ShapeDtypeStruct((b * h, tp, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",  # what a trace's events are found by
        **extra,
    )(qf, kf, vf)
    # the lse output is computed even when discarded (no-grad path): a
    # second kernel variant isn't worth the (B·H, Tp, 1) f32 write it saves
    out_bthd = _unfold(out, b, h, t, d)
    if return_residuals:
        return out_bthd, lse
    return out_bthd


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, window, valid_len, block_q,
                   block_k, nk):
    """dQ pass: grid (B·H, nq, nk), k-blocks innermost/sequential.
    dS = P ∘ (dO·Vᵀ − Δ); dQ = scale · dS·K   (flash-attention-2 backward)."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_live(qi, kj, causal, window, block_q, block_k))
    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]                                   # (bq, 1)
        delta = delta_ref[0]                               # (bq, 1)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qi, kj, causal, window, valid_len, block_q,
                          block_k)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale, causal, window, valid_len, block_q, block_k, nq,
                    group):
    """dK/dV pass: grid (B·KV, nk, group·nq): the q-blocks of every query
    head of the group innermost/sequential, so one key/value head's
    gradient sums over its group without leaving VMEM.
    dV = Pᵀ·dO;  dK = scale · dSᵀ·Q."""
    kj = pl.program_id(1)
    r = pl.program_id(2)
    qi = r % nq

    @pl.when(r == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_live(qi, kj, causal, window, block_q, block_k))
    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        mask = _tile_mask(qi, kj, causal, window, valid_len, block_q,
                          block_k)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_acc[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[:] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32) * scale

    @pl.when(r == group * nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal=False, interpret=False,
                    block_q=BLOCK_Q, block_k=BLOCK_K, window=None):
    """Fused Pallas backward: recomputes P per tile from (q, k, lse) — no
    O(T²) residuals, two passes over the kv/q grids."""
    b, t, h, d = q.shape
    group = _group(q, k, window, causal)
    kvh = h // group
    scale = 1.0 / math.sqrt(d)
    block_q, block_k, tpad, dpad = _geometry(t, d, block_q, block_k)

    qf, dof, of = (_fold(x, b, h, d) for x in (q, g, out))
    kf, vf = (_fold(x, b, kvh, d) for x in (k, v))
    if tpad or dpad:
        pad = ((0, 0), (0, tpad), (0, dpad))
        qf, kf, vf, dof, of = (jnp.pad(x, pad)
                               for x in (qf, kf, vf, dof, of))
    tp, dp = qf.shape[1], qf.shape[2]
    nq, nk = tp // block_q, tp // block_k
    # Δ = rowsum(dO ∘ O): tiny elementwise pass, let XLA fuse it
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B·H, tp, 1)

    common = dict(scale=scale, causal=causal, window=window,
                  valid_len=(t if tpad else None),
                  block_q=block_q, block_k=block_k)

    # one BlockSpec builder per operand kind; the q/k index maps swap between
    # the (bh, qi, kj) grid of the dQ pass and the (bh, kj, qi) grid of dK/dV
    def qb(im):
        return pl.BlockSpec((1, block_q, dp), im, memory_space=_VMEM)

    def kb(im):
        return pl.BlockSpec((1, block_k, dp), im, memory_space=_VMEM)

    def rb(im):
        return pl.BlockSpec((1, block_q, 1), im, memory_space=_VMEM)

    live = (causal, window, block_q, block_k)
    q_at = lambda bh, i, j: (bh, i, 0)    # noqa: E731
    k_at = lambda bh, i, j: (bh // group, _live_k(i, j, *live), 0)  # noqa: E731
    # the dK/dV grid runs over key/value heads; r walks the group's query
    # heads and, within each, its q-blocks
    q_at2 = lambda bkv, j, r: (bkv * group + r // nq,   # noqa: E731
                               _live_q(j, r % nq, *live, nq), 0)
    k_at2 = lambda bkv, j, r: (bkv, j, 0)   # noqa: E731

    extra = {}
    if not interpret:
        extra = dict(compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **common),
        grid=(b * h, nq, nk),
        in_specs=[qb(q_at), kb(k_at), kb(k_at), qb(q_at), rb(q_at), rb(q_at)],
        out_specs=qb(q_at),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **extra,
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, group=group, **common),
        grid=(b * kvh, nk, group * nq),
        in_specs=[qb(q_at2), kb(k_at2), kb(k_at2), qb(q_at2), rb(q_at2),
                  rb(q_at2)],
        out_specs=[kb(k_at2), kb(k_at2)],
        out_shape=[
            jax.ShapeDtypeStruct((b * kvh, tp, dp), k.dtype),
            jax.ShapeDtypeStruct((b * kvh, tp, dp), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
        **extra,
    )(qf, kf, vf, dof, lse, delta)

    return (_unfold(dq, b, h, t, d), _unfold(dk, b, kvh, t, d),
            _unfold(dv, b, kvh, t, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, interpret: bool = False,
                    block_q: int = 0, block_k: int = 0,
                    window=None) -> jax.Array:
    """Pallas flash attention, q (B, T, H, D), k and v (B, T, KV, D) with H
    a multiple of KV. Differentiable with a FUSED Pallas backward (dq +
    dk/dv kernels recomputing P from the lse residual — O(T) memory, no
    extra full forward). ``window`` (with ``causal``): query i sees keys
    i − window < j ≤ i. ``block_q``/``block_k`` of 0 pick the
    measured-optimal tile for the sequence length and head dim
    (_BLOCK_TABLES; tools/tune_flash_attention.py re-derives them).
    ``ops.attention.attention`` takes the same arguments and is the
    kernels' ``jax.numpy`` twin."""
    bq, bk = _resolve_blocks(q, block_q, block_k)
    return _flash_forward(q, k, v, causal, interpret,
                          block_q=bq, block_k=bk, window=window)


def _resolve_blocks(q, block_q, block_k):
    auto_q, auto_k = _pick_blocks(q.shape[1], q.shape[3])
    return block_q or auto_q, block_k or auto_k


#: names a recomputation policy can keep (``jax.checkpoint_policies.
#: save_only_these_names``): with the kernel's output and its logsumexp
#: saved, a recomputed block does not run the forward kernel a second time
SAVEABLE = ("flash_out", "flash_lse")


def _fa_fwd(q, k, v, causal, interpret, block_q, block_k, window):
    from jax.ad_checkpoint import checkpoint_name
    bq, bk = _resolve_blocks(q, block_q, block_k)
    out, lse = _flash_forward(q, k, v, causal, interpret,
                              block_q=bq, block_k=bk, return_residuals=True,
                              window=window)
    out = checkpoint_name(out, SAVEABLE[0])
    # kept without its trailing axis of 1, which a tiled layout pads to a
    # lane's 128
    lse = checkpoint_name(lse[..., 0], SAVEABLE[1])
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, interpret, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    bq, bk = _resolve_blocks(q, block_q, block_k)
    return _flash_backward(q, k, v, out, lse[..., None], g, causal,
                           interpret, block_q=bq, block_k=bk, window=window)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# Ring attention with the Pallas kernel as the inner block (round 4,
# VERDICT r3 #4). The pure-lax ring (ops/attention.ring_attention) is bound
# by its O(T²) f32 softmax elementwise traffic — measured 1.5×-3.6× slower
# than the fused kernel at 8k-32k tokens (docs/ring_attention_r4.json),
# and re-expressing its matmuls in bf16 measured a wash, so the kernel is
# the only way to make the sequence-parallel path perf-grade.
#
# Forward: per ring step, one _flash_forward call against the resident kv
# chunk (causal only on the diagonal step); per-chunk (out, lse) pairs are
# merged with the standard logsumexp combine. Backward: a custom ring —
# _flash_backward per chunk with the GLOBAL lse (p = exp(s - lse_global)
# recovers the true softmax slice, the flash-2 decomposition), dq
# accumulating locally while dk/dv accumulators ride the ring WITH their
# kv chunks (n hops = home). Causal skips: device `my` executes only ring
# steps i <= my (lax.cond), the same work skipping the lax ring does.
# ---------------------------------------------------------------------------


def _ring_combine(M, S, A, o_i, lse_i):
    """Merge one chunk's normalized output into the running combine.

    M/S (B,H,T) running max / rescaled sumexp; A (B,T,H,D) f32 running
    numerator; o_i chunk output (softmax-normalized within the chunk);
    lse_i (B,H,T) the chunk's logsumexp."""
    M_new = jnp.maximum(M, lse_i)
    w_old = jnp.exp(M - M_new)          # first step: exp(-inf - x) = 0
    w_new = jnp.exp(lse_i - M_new)
    A_new = A * w_old.transpose(0, 2, 1)[..., None] \
        + o_i.astype(jnp.float32) * w_new.transpose(0, 2, 1)[..., None]
    return M_new, S * w_old + w_new, A_new


def _ring_impl(q, k, v, axis_name, n, causal, interpret):
    """Returns (out, global lse (B,H,T) f32). Call under shard_map."""
    b, t, h, d = q.shape
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    bq, bk = _pick_blocks(t, d)
    M = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    S = jnp.zeros((b, h, t), jnp.float32)
    A = jnp.zeros((b, t, h, d), jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        # ring step i: this device holds kv chunk (my - i) mod n; with
        # causal masking that chunk is visible iff (my - i) mod n <= my,
        # i.e. iff i <= my — and i == 0 is always the causal diagonal
        is_diag = causal and i == 0

        def compute(args, _diag=is_diag):
            M_, S_, A_, k_c, v_c = args
            o_i, lse_f = _flash_forward(
                q, k_c, v_c, causal=_diag, interpret=interpret,
                block_q=bq, block_k=bk, return_residuals=True)
            lse_i = lse_f[:, :t, 0].reshape(b, h, t)
            return _ring_combine(M_, S_, A_, o_i, lse_i)

        args = (M, S, A, k_cur, v_cur)
        if causal and i > 0:
            M, S, A = jax.lax.cond(
                my >= i, compute, lambda a: (a[0], a[1], a[2]), args)
        else:
            M, S, A = compute(args)
        if i < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    S_safe = jnp.where(S == 0.0, 1.0, S)
    out = (A / S_safe.transpose(0, 2, 1)[..., None]).astype(v.dtype)
    return out, M + jnp.log(S_safe)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, axis_size: int,
                         causal: bool = False,
                         interpret: bool = False) -> jax.Array:
    """Sequence-parallel flash attention over mesh axis ``axis_name``
    (size ``axis_size``) — call under shard_map with q/k/v time-sharded
    (B, T/n, H, D per device). Differentiable; the backward rides the same
    ring (see module comment above)."""
    out, _ = _ring_impl(q, k, v, axis_name, axis_size, causal, interpret)
    return out


def _ring_fa_fwd(q, k, v, axis_name, n, causal, interpret):
    out, lse = _ring_impl(q, k, v, axis_name, n, causal, interpret)
    return out, (q, k, v, out, lse)


def _ring_fa_bwd(axis_name, n, causal, interpret, res, g):
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    bq, bk = _pick_blocks(t, d)
    _, _, tpad, _ = _geometry(t, d, bq, bk)
    lse_f = lse.reshape(b * h, t, 1)
    if tpad:
        # pad rows only meet zero-padded dO rows, so any finite value works
        lse_f = jnp.pad(lse_f, ((0, 0), (0, tpad), (0, 0)))

    dq = jnp.zeros(q.shape, jnp.float32)
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        is_diag = causal and i == 0

        def compute(args, _diag=is_diag):
            dq_a, dk_c, dv_c, k_c, v_c = args
            dqi, dki, dvi = _flash_backward(
                q, k_c, v_c, out, lse_f, g, causal=_diag,
                interpret=interpret, block_q=bq, block_k=bk)
            return (dq_a + dqi.astype(jnp.float32),
                    dk_c + dki.astype(jnp.float32),
                    dv_c + dvi.astype(jnp.float32))

        args = (dq, dk_cur, dv_cur, k_cur, v_cur)
        if causal and i > 0:
            dq, dk_cur, dv_cur = jax.lax.cond(
                my >= i, compute, lambda a: (a[0], a[1], a[2]), args)
        else:
            dq, dk_cur, dv_cur = compute(args)
        # rotate kv AND the kv-grad accumulators together on every step —
        # after n hops each chunk's accumulated (dk, dv) is back home
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_cur = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = jax.lax.ppermute(dv_cur, axis_name, perm)
    return (dq.astype(q.dtype), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


ring_flash_attention.defvjp(_ring_fa_fwd, _ring_fa_bwd)
