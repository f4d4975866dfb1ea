"""Attention ops: dense reference, blockwise (flash-style) computation, and
ring attention for sequence/context parallelism.

The reference is a vision-only trainer with NO attention or sequence axis
(SURVEY.md §5 'long-context': absent) — but its successor must treat long
context as first-class. This module provides the sequence-parallel substrate:

  * ``attention``          — dense softmax attention (numerical reference).
  * ``blockwise_attention``— online-softmax accumulation over key/value
    blocks (flash-attention recurrence) in pure lax; O(T) memory in the
    sequence dimension instead of O(T²).
  * ``ring_attention``     — the same recurrence where the key/value blocks
    live on DIFFERENT devices along a ``seq`` mesh axis and rotate around the
    ICI ring via ``lax.ppermute``; each device computes attention for its
    query chunk against every kv chunk while only ever holding 1/N of the
    sequence. Use under ``shard_map`` over a mesh with a ``seq`` axis (helper:
    ``ring_attention_sharded``). Supports causal masking via global block
    offsets.

Design notes (jax-ml.github.io/scaling-book model): the ring pattern
overlaps compute of block i with the ppermute of block i+1 — XLA schedules
the collective-permute asynchronously; the loop is a ``lax.fori_loop`` so the
whole ring is one compiled program.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


class Mask(NamedTuple):
    """Which (query, key) pairs of an attention call count: the one value
    that ``attention`` below (a dense [T, T] mask), the Pallas kernels'
    ``_Tiles`` (a tile's mask on traced positions, the live tiles) and
    ``tile_census`` (Python ints) all read.

    * ``full``: every pair;
    * ``causal``: keys j <= i, with ``window`` the band i - window < j <= i;
    * ``block_diffusion`` (arXiv:2503.09573, vectorised training): the
      call's 2·``length`` positions are a noisy copy of a sequence followed
      by its clean copy, position id i of a copy in diffusion block
      i // ``block``. A noisy query sees the noisy keys of its own block and
      the clean keys of earlier blocks; a clean query the clean keys up to
      the end of its own block; no clean query sees a noisy key. Inside a
      block attention runs both ways."""
    kind: str = "full"
    window: Optional[int] = None
    length: int = 0
    block: int = 0

    @property
    def copies(self) -> int:
        """Equal segments the call's positions are laid out in."""
        return 2 if self.kind == "block_diffusion" else 1

    def counts(self, q_pos, k_pos, stride: Optional[int] = None):
        """Bool, broadcast over int32 positions of queries and keys: does
        the pair count? None where every pair does. ``stride`` is where the
        clean copy starts when each copy was padded (``length`` else)."""
        if self.kind == "full":
            return None
        if self.kind == "causal":
            seen = q_pos >= k_pos
            if self.window is not None:
                seen = jnp.logical_and(seen, k_pos > q_pos - self.window)
            return seen
        stride = self.length if stride is None else stride
        q_clean, k_clean = q_pos >= stride, k_pos >= stride
        qb = _floordiv(q_pos - jnp.where(q_clean, stride, 0), self.block)
        kb = _floordiv(k_pos - jnp.where(k_clean, stride, 0), self.block)
        # a clean key counts for the blocks before the query's, and for its
        # own where the query is clean; a noisy key for a noisy query of its
        # own block. The copies are folded into the block numbers on each
        # side (vectors along one axis), so that the pairs cost two
        # comparisons and an OR: a kernel cannot select between two vectors
        # of booleans
        never = jnp.iinfo(jnp.int32).max
        clean_keys = jnp.where(k_clean, kb, never) \
            < qb + q_clean.astype(jnp.int32)
        noisy_keys = jnp.where(k_clean, -2, kb) == jnp.where(q_clean, -1, qb)
        return jnp.logical_or(clean_keys, noisy_keys)


def _floordiv(x, n: int):
    """x // n for positions (none negative): a shift where n is a power of
    two, which a kernel's vector unit has and a division it may not."""
    return x >> (n.bit_length() - 1) if n & (n - 1) == 0 else x // n


FULL = Mask()
CAUSAL = Mask("causal")


def block_diffusion_mask(length: int, block: int) -> Mask:
    if length <= 0 or block <= 0 or length % block:
        raise ValueError(f"a sequence of {length} ids is no whole number of "
                         f"diffusion blocks of {block}")
    return Mask("block_diffusion", None, length, block)


def as_mask(mask=False, window=None) -> Mask:
    """A ``Mask`` as given, or the one the shorthand names: False full, True
    causal, with ``window`` the causal band."""
    if isinstance(mask, Mask):
        if window is not None:
            raise ValueError("a window beside a Mask: give Mask a window")
        return mask
    if window is not None and not mask:
        raise ValueError("a window needs causal=True")
    return Mask("causal", window) if mask else FULL


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal=False, window=None) -> jax.Array:
    """Dense reference attention, and the ``jax.numpy`` twin of the Pallas
    kernels (ops/pallas/flash_attention.py takes the same arguments).
    Shapes: q (B, T, H, D) — batch, time, heads, head_dim; k and v
    (B, T, KV, D) with H a multiple of KV (query head h reads key/value head
    h // (H/KV)). ``causal`` is False, True or a ``Mask``; ``window`` (with
    ``causal=True``): query i sees keys i − window < j ≤ i. fp32 softmax
    regardless of input dtype."""
    b, tq, h, d = q.shape
    tk, kvh = k.shape[1], k.shape[2]
    mask = as_mask(causal, window)
    if h % kvh:
        raise ValueError(f"{h} query heads are no multiple of {kvh} "
                         "key/value heads")
    if mask.copies == 2 and not tq == tk == 2 * mask.length:
        raise ValueError(f"{mask} over {tq} queries and {tk} keys")
    scale = 1.0 / math.sqrt(d)
    if h != kvh:  # grouped heads: one key/value head to each group
        q = q.reshape(b, tq, kvh, h // kvh, d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    s = s * scale
    # queries are the LAST tq positions of the key timeline
    seen = mask.counts(jnp.arange(tq)[:, None] + (tk - tq),
                       jnp.arange(tk)[None, :])
    if seen is not None:
        s = jnp.where(seen, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if h != kvh:
        return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype),
                          v).reshape(b, tq, h, d)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _online_block(q, k, v, m, l, acc, scale, mask=None):
    """One flash-attention accumulation step.

    q: (B,Tq,H,D); k,v: (B,Tk,H,D); m,l: (B,H,Tq); acc: (B,Tq,H,D);
    mask: (Tq,Tk) bool or None.

    Matmuls run in the INPUT dtype with fp32 accumulation
    (``preferred_element_type``): bf16 inputs ride the MXU at full rate
    (the r3 inner block upcast V to fp32, turning the PV matmul into a
    multi-pass fp32 MXU op — the main reason the ring underperformed the
    Pallas kernel, docs/ring_attention_r4.md); fp32 inputs (CPU tests)
    keep exact-parity semantics. Softmax statistics stay fp32 always.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows: exp(-inf - (-inf)) → use finite m
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, acc_new


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        block_size: int = 512,
                        causal: bool = False) -> jax.Array:
    """Single-device flash-style attention via lax.fori_loop over kv blocks."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if tk % block_size != 0:
        block_size = math.gcd(tk, block_size) or tk
    nblocks = tk // block_size
    scale = 1.0 / math.sqrt(d)

    m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    acc0 = jnp.zeros((b, tq, h, d), jnp.float32)
    # dense-reference convention: queries are the LAST tq positions of the
    # key timeline (tril offset tk - tq), so suffix-query decode works
    q_pos = jnp.arange(tq) + (tk - tq)

    def body(i, carry):
        m, l, acc = carry
        kb = lax.dynamic_slice_in_dim(k, i * block_size, block_size, axis=1)
        vb = lax.dynamic_slice_in_dim(v, i * block_size, block_size, axis=1)
        mask = None
        if causal:
            k_pos = i * block_size + jnp.arange(block_size)
            mask = q_pos[:, None] >= k_pos[None, :]
        return _online_block(q, kb, vb, m, l, acc, scale, mask)

    m, l, acc = lax.fori_loop(0, nblocks, body, (m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(v.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = False) -> jax.Array:
    """Sequence-parallel attention over a named mesh axis (call under
    shard_map with q/k/v sharded on the time dimension).

    Local shapes: (B, T_local, H, D). Device j initially holds kv chunk j;
    at ring step i it processes kv chunk (j - i) mod N and forwards its
    current chunk to device (j + 1) mod N.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    m0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    acc0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    q_pos = my * t_local + jnp.arange(t_local)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def accumulate(i, m, l, acc, k_cur, v_cur):
        src = (my - i) % n  # global chunk index of the kv we currently hold
        k_pos = src * t_local + jnp.arange(t_local)
        mask = q_pos[:, None] >= k_pos[None, :] if causal else None
        return _online_block(q, k_cur, v_cur, m, l, acc, scale, mask)

    def body(i, carry):
        m, l, acc, k_cur, v_cur = carry
        m, l, acc = accumulate(i, m, l, acc, k_cur, v_cur)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    # ring for n-1 steps, then the final chunk without a wasted ppermute
    m, l, acc, k_last, v_last = lax.fori_loop(
        0, n - 1, body, (m0, l0, acc0, k, v))
    m, l, acc = accumulate(n - 1, m, l, acc, k_last, v_last)
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(v.dtype)


def resolve_ring_kernel(kernel: str) -> str:
    """The ONE auto rule for the ring inner block: the fused Pallas flash
    kernels on TPU (measured 1.5×-3.6× the lax ring at 8k-32k tokens,
    docs/ring_attention_r4.json), the pure-lax online recurrence elsewhere.
    Shared by ring_attention_sharded and the pipelined stage blocks
    (models/pipeline.py) so the two paths cannot drift."""
    if kernel not in ("auto", "lax", "flash", "flash_interpret"):
        raise ValueError(f"unknown ring attention kernel {kernel!r}")
    if kernel == "auto":
        return "flash" if jax.default_backend() == "tpu" else "lax"
    return kernel


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, causal: bool = False,
                           seq_axis: str = "seq",
                           batch_axes: tuple = (),
                           kernel: str = "auto") -> jax.Array:
    """Convenience wrapper: shard_map ring attention over ``mesh[seq_axis]``
    with time-dim sharding (B, T/seq, H, D per device).

    ``batch_axes`` names mesh axes the batch dim is already split over (e.g.
    ("data",)) so composition with data parallelism keeps the batch sharded
    instead of all-gathering it at the shard_map boundary.

    ``kernel`` picks the per-step inner block: "lax" = the pure-lax online
    recurrence (any backend); "flash" = the fused Pallas kernel
    (ops/pallas/flash_attention.ring_flash_attention — measured 1.5×-3.6×
    faster at 8k-32k tokens, docs/ring_attention_r4.json);
    "flash_interpret" = the same kernels in the Pallas interpreter (CPU
    parity tests); "auto" = flash on TPU, lax elsewhere."""
    from ..parallel.mesh import shard_map_unchecked

    n = mesh.shape[seq_axis]
    mode = resolve_ring_kernel(kernel)

    spec = P(batch_axes or None, seq_axis, None, None)
    if mode == "lax":
        body = functools.partial(ring_attention, axis_name=seq_axis,
                                 causal=causal)
    else:
        from .pallas.flash_attention import ring_flash_attention
        interp = mode == "flash_interpret"

        def body(q, k, v):
            return ring_flash_attention(q, k, v, seq_axis, n, causal,
                                        interp)
    fn = shard_map_unchecked(
        body, mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
