"""Flash attention — Pallas TPU kernels, fused forward AND backward.

Tiling: ``flash_fwd`` runs on the grid (batch·heads, q_blocks, k-walk): the
innermost dimension, sequential ("arbitrary" semantics), is a WALK over the
live k-blocks of the q-block, not over all of them. A tile is live when it
holds a pair that counts. Which pairs count is one value, an
``ops.attention.Mask``: every pair, the causal ones (with a window the band),
or block diffusion's three parts over a noisy and a clean copy of a
sequence. The live k-blocks of a q-block are a short list of unbroken runs:
one under the first two kinds (all of them, up to the diagonal, the band's
width), two under the third (a noisy q-block its own noisy blocks and the
clean blocks before its last; ``flash_bwd_dkv``'s clean k-block the noisy
q-blocks after it and the clean ones from it on). The walk is as long as the
longest row's runs together and goes through a row's runs one after the
other, the step → block map affine on each (a shorter row ends on steps that
compute nothing and fetch nothing: their block index repeats the last live
one). Online-softmax accumulators (m, l, acc) live in VMEM scratch across
the walk, so VMEM holds one (block_q, d) query tile and one (block_k, d)
key/value tile at a time — O(block) VMEM, any sequence length. Output and
the logsumexp residual are written on the walk's last step.

``_Tiles`` holds the arithmetic (the live runs, a tile's mask) that the index
maps, the kernels' predicates and ``tile_census`` all read. Every live tile
of a call that masks at all builds its mask: leaving it off the tiles whose
every pair counts was measured and gained nothing (PERF.md §6, PR 34).

Row statistics are lane-dense. The logsumexp and Δ = rowsum(dO ∘ O) cross
HBM as (B·H, 1, T) rows, the queries in the lanes (a trailing axis of 1
pads every number to a 128-lane register row, in HBM and in VMEM). Inside
``flash_fwd`` and ``flash_bwd_dq`` a q-block's statistics are (block_q, 128)
with every lane of a row the same number, so they meet the score tile as
whole registers; they are turned from and into rows once a q-block.

The backward is the flash-attention-2 formulation in two Pallas passes that
recompute P per tile from (q, k, lse) — no O(T²) residuals and no extra full
forward: ``flash_bwd_dq`` walking k-blocks innermost, and ``flash_bwd_dkv``
walking, for each k-block, the live q-blocks of every query head of its
group. ``flash_bwd_dkv`` computes its tile keys first (Sᵀ = K·Qᵀ): Pᵀ and
dSᵀ come out as the operands dV = Pᵀ·dO and dK = dSᵀ·Q take (no tile is
transposed), and the statistics subtract as the rows they arrive as.

Layout: (B, T, H, D). The wrapper pads T up to lcm(block_q, block_k) and D to
the 128-lane width; padded keys are masked via
``valid_len``, padded queries are sliced off. Causal masking uses the
dense-attention convention: with tq == tk the diagonal, i.e. query i attends
keys ≤ i. ``window`` (with ``causal``) narrows that to the band
i − window < j ≤ i. Under block diffusion T is two copies of a sequence,
[noisy; clean], and each copy is padded at its own end, so no tile holds
positions of both (the other order, the copies interleaved block by block
under the causal walk, computes 2L² pairs for the L² + LB that count and
measured 1.43× slower at the cell's call: PERF.md §6, PR 35).

Grouped heads: ``k`` and ``v`` may carry fewer heads than ``q`` (H a multiple
of KV; query head h reads key/value head h // (H/KV)). Keys and values are
never copied per query head: the index maps send a group's query heads to
one (B·KV, T, D) block, and the dK/dV pass sums over the group's heads in
its innermost grid dimension.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import CAUSAL, FULL, Mask, as_mask

_VMEM = pltpu.VMEM
_NEG_INF = -1e30
_LANES = 128

# Block tables: every entry is a winner of tools/tune_flash_attention.py on a
# TPU v5e (bf16, causal), never intuition — an early guessed 256×512 row
# measured 1.8× slower than what it replaced. Keyed by what decides the
# winner: the head size (≤ 96 reads the 64 table), whether the call has a
# window, and T (a row serves every T up to its own). An entry is the
# (block_q, block_k) with the least summed device time of the three kernels
# in docs/flash_tune_v5e_gqa_window.json (PR 34, the kernels as they are
# since: {128,256,512,1024}² with each point's tile_census). Head size 128:
# B=2 with 32 query heads on 4, window 2048 and none, T 1k..8k, and equal
# heads without a window, which agree on every row; head size 64: 16 equal
# heads, no window (a windowed call there reads the row without). With the
# band walked alone and the statistics lane-dense, what is left to save is
# a tile's fixed cost (the statistics' update, the accumulator's rescale,
# the grid step), so the winners are 2 to 4 times the tiles of the table
# before (docs/flash_tune_r3.json: {128,256,512}² on the kernels of that
# day; where its rows differ from these they now lose by 1.1× to 3.7×).
# The block-diffusion rows (T the call's positions, two copies of T/2 ids in
# diffusion blocks of 4; docs/flash_tune_v5e_blockdiff.json, PR 35) are the
# winners over {256,512,1024}² ({128,...}² at T = 8,192) of the same call
# shape: by 6.3% at 4,096, 7.6% at 8,192, 9.8% at 16,384.
# A second run of every call's four best pairs
# (docs/flash_tune_v5e_gqa_window_repeat.json) read each kernel within 0.02%
# of the first and the same winners; the narrowest win by 0.29% (T = 1,024,
# (512, 512) over (1024, 1024)) and by 0.8% and 1.2% (T = 4,096).
# The sweeps ran powers of two alone. T is padded up to lcm(block_q,
# block_k), so a T just over a multiple of its blocks pays dead work that
# grows with them (2,100 tokens at head size 128 run as 3,072). No caller on
# the chip sends one today (the encoder's 197 fit one tile): sweep such a T
# before trusting its row.
# Head size 256 (latent attention's 192 + 64, 20 heads of their own keys
# and values, causal; docs/flash_tune_v5e_mla256.json) is the winner over
# {256,512,1024}² at T = 8,192: (1024, 512) by 1.1% over (512, 1024) and
# 2.6% over (512, 512) in the three kernels' summed time. The dK/dV kernel
# cannot hold (1024, 1024) in its 16 MB of scoped VMEM (RESOURCE_EXHAUSTED).
_BLOCK_TABLES = {
    (64, False): ((4096, (512, 512)), (8192, (1024, 1024))),
    (128, False): ((2048, (512, 512)), (8192, (1024, 1024))),
    (128, True): ((8192, (512, 512)),),
    (128, "block_diffusion"): ((4096, (512, 512)), (16384, (1024, 1024))),
    (256, False): ((8192, (1024, 512)),),
}


#: (largest head size, the table's head size) in order: the row a call's
#: head size reads
_HEAD_ROWS = ((96, 64), (128, 128), (256, 256))


def _pick_blocks(t: int, d: int, mask: Mask = FULL) -> tuple:
    """The table's (block_q, block_k) for the call: the row of the head
    size ``_HEAD_ROWS`` gives and of the mask's kind, else of that head
    size's plain causal kind. A head size past the last row raises."""
    head = next((row for upper, row in _HEAD_ROWS if d <= upper), None)
    if head is None:
        raise ValueError(f"no row of the flash kernels' block tables serves "
                         f"head size {d} (at most {_HEAD_ROWS[-1][0]})")
    kind = mask.kind if mask.copies == 2 else mask.window is not None
    table = _BLOCK_TABLES.get((head, kind), _BLOCK_TABLES[head, False])
    for upper, blocks in table:
        if t <= upper:
            return blocks
    return table[-1][1]


class _Plan(NamedTuple):
    """The blocks of a call's three kernels and the padding of T (of each
    copy where the mask lays the positions out in several) and of D."""
    block_q: int
    block_k: int
    tpad: int
    dpad: int


def _plan(t: int, d: int, mask: Mask = FULL, block_q: int = 0,
          block_k: int = 0) -> _Plan:
    """Blocks as given, the table's where 0, clamped to the (padded)
    sequence, keeping them a multiple of the TPU sublane tile (16 covers
    bf16's (16,128) and f32's (8,128)) so Mosaic accepts shapes like t=196
    (ViT-224/16). T is padded to their least common multiple, D to 128.
    Under a mask of several copies each copy is a sequence of its own: the
    blocks are clamped to it and it is padded at its own end, so that no
    tile holds positions of two copies."""
    table_q, table_k = _pick_blocks(t, d, mask)
    t = t // mask.copies
    t16 = -(-t // 16) * 16
    block_q = min(block_q or table_q, t16)
    block_k = min(block_k or table_k, t16)
    return _Plan(block_q, block_k, tpad=(-t) % math.lcm(block_q, block_k),
                 dpad=(-d) % 128)


def _ints(*xs) -> bool:
    return all(isinstance(x, (int, bool)) for x in xs)


def _max(a, b):
    return max(a, b) if _ints(a, b) else jnp.maximum(a, b)


def _min(a, b):
    return min(a, b) if _ints(a, b) else jnp.minimum(a, b)


def _where(c, a, b):
    return (a if c else b) if _ints(c) else jnp.where(c, a, b)


def _steps(runs):
    """Live blocks in a row's runs: an empty run's last lies before its
    first."""
    return sum(_max(hi - lo + 1, 0) for lo, hi in runs)


def _walk(runs, step):
    """The block held at ``step`` of a walk over a row's runs, one after
    the other: a map that is affine on each run. Past the walk's end it is
    the last live block, and a block index that repeats is not fetched
    again."""
    s = _min(step, _steps(runs) - 1)
    block, start = None, 0
    for lo, hi in runs:
        here = lo + s - start
        block = here if block is None else _where(s >= start, here, block)
        start = start + _max(hi - lo + 1, 0)
    return block


class _Tiles(NamedTuple):
    """The (q-block, k-block) tiles of one call: which hold a pair that
    counts (live), the order in which a kernel walks them, and a tile's
    mask. The runs are integer arithmetic on block indices, so the index
    maps and predicates of the kernels (on traced indices) and
    ``tile_census`` (on Python ints) read one source.

    ``valid_len`` is the number of real keys (of a copy) where the sequence
    was padded, else None. The live k-blocks of a q-block, and the live
    q-blocks of a k-block, are a short list of unbroken runs: one (a band)
    under ``full`` and ``causal``, two under ``block_diffusion``, where a
    row's live tiles lie in both copies. A kernel's innermost grid
    dimension is the longest row's walk over its runs."""
    mask: Mask
    valid_len: Optional[int]
    block_q: int
    block_k: int
    nq: int
    nk: int

    @property
    def stride(self) -> int:
        """Where the second copy starts, padding included."""
        return self.nq * self.block_q // self.mask.copies

    def k_runs(self, qi):
        """(first, last) of each run of live k-blocks of q-block ``qi``."""
        m, bq, bk = self.mask, self.block_q, self.block_k
        if m.kind == "full":
            return ((0, self.nk - 1),)
        if m.kind == "causal":
            lo = 0 if m.window is None else \
                _max(qi * bq - m.window + 1, 0) // bk
            return ((lo, (qi * bq + bq - 1) // bk),)
        # [noisy; clean]: ids b0·B .. b1·B + B − 1 are the diffusion blocks
        # the q-block's own ids touch
        nqh, nkh, B = self.nq // 2, self.nk // 2, m.block
        clean = qi >= nqh
        i0 = (qi - _where(clean, nqh, 0)) * bq
        b0, b1 = i0 // B, (i0 + bq - 1) // B
        # a noisy q-block: the noisy keys of its own blocks, then the clean
        # keys before its last block; a clean one: the clean keys up to the
        # end of its last block
        own = (_where(clean, 0, b0 * B // bk),
               _where(clean, -1, _min((b1 * B + B - 1) // bk, nkh - 1)))
        last = (b1 + _where(clean, 1, 0)) * B - 1
        return (own, (nkh, nkh + _min(last // bk, nkh - 1)))

    def q_runs(self, kj):
        """(first, last) of each run of live q-blocks of k-block ``kj``."""
        m, bq, bk = self.mask, self.block_q, self.block_k
        if m.kind == "full":
            return ((0, self.nq - 1),)
        if m.kind == "causal":
            hi = self.nq - 1 if m.window is None else _min(
                (kj * bk + bk - 2 + m.window) // bq, self.nq - 1)
            return ((kj * bk // bq, hi),)
        nqh, nkh, B = self.nq // 2, self.nk // 2, m.block
        clean = kj >= nkh
        j0 = (kj - _where(clean, nkh, 0)) * bk
        b0, b1 = j0 // B, (j0 + bk - 1) // B
        # a noisy k-block: the noisy queries of its own blocks; a clean
        # one: the noisy queries of later blocks, then the clean queries
        # from its first block on
        noisy = (_where(clean, (b0 + 1) * B // bq, b0 * B // bq),
                 _where(clean, nqh - 1,
                        _min((b1 * B + B - 1) // bq, nqh - 1)))
        return (noisy, (nqh + b0 * B // bq, _where(clean, 2 * nqh - 1, -1)))

    @property
    def k_steps(self) -> int:
        """Length of the k-walk: the most live k-blocks of a q-block."""
        return max(_steps(self.k_runs(qi)) for qi in range(self.nq))

    @property
    def q_steps(self) -> int:
        return max(_steps(self.q_runs(kj)) for kj in range(self.nk))

    @property
    def masks(self) -> bool:
        """Does the call mask any pair at all?"""
        return self.mask.kind != "full" or self.valid_len is not None

    def mask_of(self, qi, kj, keys_first=False):
        """(block_q, block_k) bool of the tile's pairs that count;
        (block_k, block_q) with ``keys_first``."""
        q_axis, k_axis = (1, 0) if keys_first else (0, 1)

        def across(n, axis):  # positions 0..n-1 laid along ``axis``
            shape = (n, 1) if axis == 0 else (1, n)
            return jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        k_pos = kj * self.block_k + across(self.block_k, k_axis)
        q_pos = qi * self.block_q + across(self.block_q, q_axis)
        seen = self.mask.counts(q_pos, k_pos, self.stride)
        if self.valid_len is None:
            return seen
        if self.mask.copies == 2:  # the key's id within its own copy
            k_pos = k_pos - jnp.where(k_pos >= self.stride, self.stride, 0)
        real = k_pos < self.valid_len
        return real if seen is None else jnp.logical_and(real, seen)

    def census(self) -> dict:
        live = sum(_steps(self.k_runs(qi)) for qi in range(self.nq))
        return {"grid_steps": self.nq * self.k_steps,
                "grid_steps_dkv": self.nk * self.q_steps,
                "live": live, "masks": self.masks}


def _tiles(t, mask: Mask, plan: _Plan) -> _Tiles:
    tp = t + mask.copies * plan.tpad
    return _Tiles(mask, t // mask.copies if plan.tpad else None,
                  plan.block_q, plan.block_k, tp // plan.block_q,
                  tp // plan.block_k)


def tile_census(t: int, d: int, causal, window, block_q: int,
                block_k: int) -> dict:
    """What one head of one kernel call walks at these sizes: ``grid_steps``
    (the grid of ``flash_fwd`` and ``flash_bwd_dq``: q-blocks × the k-walk;
    ``grid_steps_dkv`` is ``flash_bwd_dkv``'s, k-blocks × the q-walk of one
    query head), ``live`` (tiles computed: they hold a pair that counts)
    and ``masks`` (does every live tile build and apply a mask: all do or
    none does; masking the edge tiles alone was measured and gained
    nothing, PERF.md §6, PR 34). Built from the ``_Tiles`` the kernels'
    index maps and predicates use; ``causal`` and ``window`` as
    ``flash_attention`` takes them (a ``Mask`` among them); blocks of 0
    are the table's."""
    mask = as_mask(causal, window)
    return _tiles(t, mask, _plan(t, d, mask, block_q, block_k)).census()


def _lanes(x, n):
    """A per-row statistic kept as (rows, 128), every lane of a row the same
    number, against a tile ``n`` lanes wide: whole registers side by side,
    where a (rows, 1) column would be spread over the lanes each time."""
    if n % _LANES:
        return jnp.broadcast_to(x[:, :1], (x.shape[0], n))
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _to_row(x):
    """(rows, 128) lane-replicated → (1, rows): the form that crosses HBM."""
    return x.T[:1]


def _to_lanes(row):
    """(1, rows) → (rows, 128) lane-replicated."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale, tiles):
    """One (q-block, k-block) tile. Scratch m/l/acc persist across the
    innermost grid dimension, the walk over the q-block's live k-blocks."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    runs = tiles.k_runs(qi)
    kj = _walk(runs, j)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a walk shorter than the longest ends on steps past the last live block
    @pl.when(j < _steps(runs))
    def _accumulate():
        # keep matmul OPERANDS in the input dtype (bf16 on the MXU's native
        # rate — an f32 cast would halve/quarter throughput); accumulate f32
        q = q_ref[0]                                      # (bq, d)
        k = k_ref[0]                                      # (bk, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if tiles.masks:
            s = jnp.where(tiles.mask_of(qi, kj), s, _NEG_INF)
        m_prev, l_prev, acc_prev = m_ref[:], l_ref[:], acc_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[1]))
        corr = jnp.exp(m_prev - m_new)
        m_ref[:] = m_new
        l_ref[:] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_prev * _lanes(corr, acc_prev.shape[1]) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == tiles.k_steps - 1)
    def _finalize():
        l = l_ref[:]
        l = jnp.where(l == 0.0, 1.0, l)
        acc = acc_ref[:]
        o_ref[0] = (acc / _lanes(l, acc.shape[1])).astype(o_ref.dtype)
        # logsumexp residual for the fused backward: lse = m + log(l);
        # guard fully-masked rows (m = -inf) to keep exp(s - lse) finite
        m = m_ref[:]
        lse_ref[0] = _to_row(
            jnp.where(m <= _NEG_INF / 2, 0.0, m + jnp.log(l)))


def _fold(x, b, h, d):  # (B,T,H,D) → (B·H, T, D)
    return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)


def _pad(x, plan: _Plan, copies: int):
    """(N, T, D) padded to the plan: each copy at its own end, D at its."""
    if not (plan.tpad or plan.dpad):
        return x
    n, t, d = x.shape
    x = jnp.pad(x.reshape(n, copies, t // copies, d),
                ((0, 0), (0, 0), (0, plan.tpad), (0, plan.dpad)))
    return x.reshape(n, -1, d + plan.dpad)


def _unfold(x, b, h, t, d, copies=1):  # padded (B·H, T, D) → (B,T,H,D)
    x = x.reshape(b, h, copies, x.shape[1] // copies, x.shape[2])
    return x[:, :, :, :t // copies, :d].reshape(b, h, t, d) \
        .transpose(0, 2, 1, 3)


def _group(q, k, mask: Mask):
    """Query heads to a key/value head; refuses what the kernels cannot do."""
    h, kvh = q.shape[2], k.shape[2]
    if h % kvh:
        raise ValueError(f"{h} query heads are no multiple of {kvh} "
                         "key/value heads")
    if mask.window is not None and q.shape[1] != k.shape[1]:
        raise ValueError("a window needs as many queries as keys")
    if mask.copies == 2 and not q.shape[1] == k.shape[1] == 2 * mask.length:
        raise ValueError(f"{mask} over {q.shape[1]} queries and "
                         f"{k.shape[1]} keys")
    return h // kvh


def _flash_forward(q, k, v, plan: _Plan, mask: Mask = FULL, interpret=False):
    """(out (B, T, H, D), lse (B·H, 1, Tp) float32: the queries in the
    lanes, the padded ones included)."""
    b, t, h, d = q.shape
    g = _group(q, k, mask)
    scale = 1.0 / math.sqrt(d)
    block_q, block_k = plan.block_q, plan.block_k

    qf = _pad(_fold(q, b, h, d), plan, mask.copies)
    kf, vf = (_pad(_fold(x, b, h // g, d), plan, mask.copies)
              for x in (k, v))
    tp, dp = qf.shape[1], qf.shape[2]
    tiles = _tiles(t, mask, plan)
    grid = (b * h, tiles.nq, tiles.k_steps)
    kernel = functools.partial(_flash_kernel, scale=scale, tiles=tiles)

    def k_at(bh, i, j):  # a group's query heads read one key/value head
        return (bh // g, _walk(tiles.k_runs(i), j), 0)

    scratch = [pltpu.VMEM((block_q, _LANES), jnp.float32),
               pltpu.VMEM((block_q, _LANES), jnp.float32),
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
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i),
                         memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tp, dp), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tp), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_fwd",  # what a trace's events are found by
        **extra,
    )(qf, kf, vf)
    # the lse output is computed even when discarded (no-grad path): a
    # second kernel variant isn't worth the (B·H, 1, Tp) f32 write it saves
    return _unfold(out, b, h, t, d, mask.copies), lse


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, lse_s, delta_s, *, scale, tiles):
    """dQ pass: grid (B·H, nq, k-walk), k-blocks innermost/sequential.
    dS = P ∘ (dO·Vᵀ − Δ); dQ = scale · dS·K   (flash-attention-2 backward)."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    runs = tiles.k_runs(qi)
    kj = _walk(runs, j)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        # the q-block's statistics arrive as rows and stay for the whole
        # walk: turned once into the form the tiles subtract
        lse_s[:] = _to_lanes(lse_ref[0])
        delta_s[:] = _to_lanes(delta_ref[0])

    @pl.when(j < _steps(runs))
    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - _lanes(lse_s[:], s.shape[1]))
        if tiles.masks:
            p = jnp.where(tiles.mask_of(qi, kj), p, 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - _lanes(delta_s[:], s.shape[1]))).astype(k.dtype)
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(j == tiles.k_steps - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, tiles, group):
    """dK/dV pass: grid (B·KV, nk, group·q-walk): the live q-blocks of every
    query head of the group innermost/sequential, so one key/value head's
    gradient sums over its group without leaving VMEM.
    dV = Pᵀ·dO;  dK = scale · dSᵀ·Q, on the tile computed keys first
    (Sᵀ = K·Qᵀ): Pᵀ and dSᵀ come out in the orientation the two products
    take, and the q-block's statistics subtract as the rows they arrive as."""
    kj = pl.program_id(1)
    r = pl.program_id(2)
    runs = tiles.q_runs(kj)
    qi = _walk(runs, r % tiles.q_steps)

    @pl.when(r == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(r % tiles.q_steps < _steps(runs))
    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = jnp.dot(k, q.T, preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[0])                      # (bk, bq) − (1, bq)
        if tiles.masks:
            pt = jnp.where(tiles.mask_of(qi, kj, keys_first=True), pt, 0.0)
        dv_acc[:] += jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = jnp.dot(v, do.T, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dk_acc[:] += jnp.dot(dst, q, preferred_element_type=jnp.float32) * scale

    @pl.when(r == group * tiles.q_steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, plan: _Plan, mask: Mask = FULL,
                    interpret=False):
    """Fused Pallas backward: recomputes P per tile from (q, k, lse) — no
    O(T²) residuals, two passes over the kv/q grids. ``lse`` as
    ``_flash_forward`` returns it."""
    b, t, h, d = q.shape
    group = _group(q, k, mask)
    kvh = h // group
    scale = 1.0 / math.sqrt(d)
    block_q, block_k = plan.block_q, plan.block_k

    qf, dof, of = (_pad(_fold(x, b, h, d), plan, mask.copies)
                   for x in (q, g, out))
    kf, vf = (_pad(_fold(x, b, kvh, d), plan, mask.copies) for x in (k, v))
    tp, dp = qf.shape[1], qf.shape[2]
    tiles = _tiles(t, mask, plan)
    # Δ = rowsum(dO ∘ O): tiny elementwise pass, let XLA fuse it
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None]                      # (B·H, 1, tp)

    common = dict(scale=scale, tiles=tiles)

    # one BlockSpec builder per operand kind; the q/k index maps swap between
    # the (bh, qi, k-walk) grid of the dQ pass and the (bkv, kj, q-walk)
    # grid of dK/dV
    def qb(im):
        return pl.BlockSpec((1, block_q, dp), im, memory_space=_VMEM)

    def kb(im):
        return pl.BlockSpec((1, block_k, dp), im, memory_space=_VMEM)

    def rb(im):  # a q-block's statistics: one row, the queries in the lanes
        return pl.BlockSpec((1, 1, block_q), im, memory_space=_VMEM)

    q_at = lambda bh, i, j: (bh, i, 0)    # noqa: E731
    r_at = lambda bh, i, j: (bh, 0, i)    # noqa: E731
    k_at = lambda bh, i, j: (   # noqa: E731
        bh // group, _walk(tiles.k_runs(i), j), 0)
    # the dK/dV grid runs over key/value heads; r walks the group's query
    # heads and, within each, the k-block's live q-blocks
    steps = tiles.q_steps
    q_at2 = lambda bkv, j, r: (   # noqa: E731
        bkv * group + r // steps, _walk(tiles.q_runs(j), r % steps), 0)
    r_at2 = lambda bkv, j, r: (   # noqa: E731
        bkv * group + r // steps, 0, _walk(tiles.q_runs(j), r % steps))
    k_at2 = lambda bkv, j, r: (bkv, j, 0)   # noqa: E731

    extra = {}
    if not interpret:
        extra = dict(compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(b * h, tiles.nq, tiles.k_steps),
        in_specs=[qb(q_at), kb(k_at), kb(k_at), qb(q_at), rb(r_at), rb(r_at)],
        out_specs=qb(q_at),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
        **extra,
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, group=group, **common),
        grid=(b * kvh, tiles.nk, group * steps),
        in_specs=[qb(q_at2), kb(k_at2), kb(k_at2), qb(q_at2), rb(r_at2),
                  rb(r_at2)],
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

    return tuple(_unfold(x, b, n, t, d, mask.copies)
                 for x, n in ((dq, h), (dk, kvh), (dv, kvh)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal=False, interpret: bool = False,
                    block_q: int = 0, block_k: int = 0,
                    window=None) -> jax.Array:
    """Pallas flash attention, q (B, T, H, D), k and v (B, T, KV, D) with H
    a multiple of KV. Differentiable with a FUSED Pallas backward (dq +
    dk/dv kernels recomputing P from the lse residual — O(T) memory, no
    extra full forward). ``causal`` is False (every pair), True, or a
    ``Mask`` (ops/attention.py: the one description of which pairs count);
    ``window`` (with ``causal=True``): query i sees keys i − window < j ≤ i.
    ``block_q``/``block_k`` of 0 pick the tile the tuner measured fastest
    for the head size, the sequence length and the kind of mask
    (``_BLOCK_TABLES``; ``tools/tune_flash_attention.py`` re-derives its
    rows and writes the evidence, docs/flash_tune_v5e_gqa_window.json and
    docs/flash_tune_v5e_blockdiff.json). Which tiles the call walks
    follows from these static sizes alone (``tile_census`` counts them).
    ``ops.attention.attention`` takes the same arguments and is the
    kernels' ``jax.numpy`` twin."""
    mask = as_mask(causal, window)
    plan = _plan(q.shape[1], q.shape[3], mask, block_q, block_k)
    return _flash_forward(q, k, v, plan, mask, interpret)[0]


#: names a recomputation policy can keep (``jax.checkpoint_policies.
#: save_only_these_names``): with the kernel's output and its logsumexp
#: saved, a recomputed block does not run the forward kernel a second time
SAVEABLE = ("flash_out", "flash_lse")


def _fa_fwd(q, k, v, causal, interpret, block_q, block_k, window):
    from jax.ad_checkpoint import checkpoint_name
    mask = as_mask(causal, window)
    plan = _plan(q.shape[1], q.shape[3], mask, block_q, block_k)
    out, lse = _flash_forward(q, k, v, plan, mask, interpret)
    out = checkpoint_name(out, SAVEABLE[0])
    lse = checkpoint_name(lse[:, 0], SAVEABLE[1])  # (B·H, Tp), dense
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, interpret, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    mask = as_mask(causal, window)
    plan = _plan(q.shape[1], q.shape[3], mask, block_q, block_k)
    return _flash_backward(q, k, v, out, lse[:, None], g, plan, mask,
                           interpret)


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
    plan = _plan(t, d)
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
            o_i, lse_f = _flash_forward(q, k_c, v_c, plan,
                                        CAUSAL if _diag else FULL, interpret)
            lse_i = lse_f[:, 0, :t].reshape(b, h, t)
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
    plan = _plan(t, d)
    # pad rows only meet zero-padded dO rows, so any finite value works
    lse_f = jnp.pad(lse.reshape(b * h, 1, t),
                    ((0, 0), (0, 0), (0, plan.tpad)))

    dq = jnp.zeros(q.shape, jnp.float32)
    dk_cur = jnp.zeros(k.shape, jnp.float32)
    dv_cur = jnp.zeros(v.shape, jnp.float32)
    k_cur, v_cur = k, v
    for i in range(n):
        is_diag = causal and i == 0

        def compute(args, _diag=is_diag):
            dq_a, dk_c, dv_c, k_c, v_c = args
            dqi, dki, dvi = _flash_backward(
                q, k_c, v_c, out, lse_f, g, plan,
                CAUSAL if _diag else FULL, interpret)
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
