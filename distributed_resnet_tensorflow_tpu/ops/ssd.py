"""The chunked scan of a selective state space: Mamba-2's SSD
(arXiv:2405.21060, section 6), as ``jax.numpy`` that XLA compiles.

Per head h, with the state S (P × N) starting at 0:

    S_t = exp(Δ_t A_h) · S_{t−1} + Δ_t · x_t ⊗ B_t,    y_t = S_t · C_t

x (B, T, H, P), Δ (B, T, H), A (H,) < 0, B and C (B, T, G, N), head h reading
group ⌊h·G/H⌋. The sequence is cut into chunks of Q positions. Inside a chunk
the recurrence is four products over Q × Q and Q × N tiles (the decay between
two positions of a chunk is exp of a difference of cumulative sums, the
matrix L):

* ``C·Bᵀ`` masked by L: which earlier position of the chunk each one reads;
* that times ``Δ·x``: the chunk's own part of y;
* ``Bᵀ·(decay to the chunk's end · Δ·x)``: the state the chunk leaves;
* ``C·S_prev`` scaled by the decay from the chunk's start: what the chunks
  before it give.

The state is carried from chunk to chunk by a ``lax.scan``; its body is
checkpointed, so the backward pass keeps one state a chunk (B·H·P·N float32)
and makes a chunk's Q × Q tiles again. Decay exponents, their sums, the
carried state and the elementwise work are float32; the four products take
operands in ``dtype`` and accumulate in float32.

Positions past T (T not a multiple of Q) are padded with Δ = 0 and x, B, C
= 0: they neither decay the state nor add to it, and their outputs are cut
off.

This is the scan on every backend. Pallas kernels of the same scan (a grid
of sequence × group of heads × chunk, the state in VMEM) took 16.2 ms
against this form's 18.5 for a layer's forward and backward alone on a TPU
v5e, and gained nothing the cell's step could tell from its noise (PERF.md
section 6): they were not kept.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def chunks(t: int, chunk: int) -> int:
    """Chunks a sequence of t positions is cut into."""
    return -(-t // chunk)


def _state_shape(batch: int, heads: int, groups: int, head_dim: int, state: int):
    """The carried state: (B, G, E, P, N), E heads reading each group."""
    return batch, groups, heads // groups, head_dim, state


def scan_census(batch: int, t: int, heads: int, groups: int, head_dim: int,
                state: int, chunk: int) -> dict:
    """What one layer's ``chunked_scan`` walks at these sizes: ``chunks`` a
    sequence (the ``lax.scan``'s steps, every head at once), ``tiles`` (the
    Q × Q decay tiles a step makes, one a sequence and head) and
    ``state_bytes`` (the float32 state the checkpointed scan keeps a chunk
    for its backward pass, over all chunks)."""
    n = chunks(t, chunk)
    carried = 4
    for size in _state_shape(batch, heads, groups, head_dim, state):
        carried *= size
    return {"chunks": n, "tiles": batch * heads, "state_bytes": n * carried}


def _chunk(a_heads, dtype):
    """The scan's body over one chunk: (state, inputs) -> (state, y)."""
    def body(s, inputs):
        # x (B, Q, G, E, P), dt (B, Q, G, E), b and c (B, Q, G, N); the
        # state s (B, G, E, P, N) float32; E heads read each group
        x, dt, b, c = inputs
        q = dt.shape[1]
        cum = jnp.cumsum(dt * a_heads, axis=1)                 # (B, Q, G, E)
        rows = jnp.moveaxis(cum, 1, -1)                        # (B, G, E, Q)
        seen = jnp.tril(jnp.ones((q, q), bool))
        decay = jnp.exp(jnp.where(seen, rows[..., :, None] - rows[..., None, :],
                                  -jnp.inf))                   # L: (B, G, E, Q, Q)
        dot = lambda spec, u, v: jnp.einsum(  # noqa: E731
            spec, u.astype(dtype), v.astype(dtype),
            preferred_element_type=jnp.float32)
        u = x.astype(jnp.float32) * dt[..., None]              # Δ·x
        scores = dot("bign,bjgn->bgij", c, b)[:, :, None] * decay
        y = dot("bgeij,bjgep->bigep", scores, u)
        y = y + dot("bign,bgepn->bigep", c, s) * jnp.exp(cum)[..., None]
        to_end = jnp.exp(cum[:, -1:] - cum)[..., None]         # (B, Q, G, E, 1)
        s = s * jnp.exp(cum[:, -1])[..., None, None] \
            + dot("bjgn,bjgep->bgepn", b, u * to_end)
        return s, y
    return body


def chunked_scan(x, dt, a, b, c, chunk: int, dtype=jnp.bfloat16):
    """y (B, T, H, P) float32 of the recurrence in the module docstring,
    chunk by chunk; ``dt`` is Δ (after its softplus), ``a`` is A."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{h} heads do not share {g} groups evenly")
    e = h // g
    pad = chunks(t, chunk) * chunk - t

    def split(v, *tail):  # (B, T, ...) -> (chunks, B, Q, ...)
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((bsz, -1, chunk) + tail), 1, 0)
    inputs = (split(x, g, e, p), split(dt.astype(jnp.float32), g, e),
              split(b, g, n), split(c, g, n))
    s0 = jnp.zeros(_state_shape(bsz, h, g, p, n), jnp.float32)
    body = jax.checkpoint(_chunk(a.astype(jnp.float32).reshape(g, e), dtype))
    _, y = jax.lax.scan(body, s0, inputs)
    return jnp.moveaxis(y, 0, 1).reshape(bsz, -1, h, p)[:, :t]
