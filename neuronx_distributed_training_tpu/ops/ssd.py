"""The state-space scan of a Mamba-2 mixer (``models/nemotron_h.py``): every
head carries a state ``S [head_dim, state]`` across the whole sequence.

Per token ``t`` and head ``h`` of group ``g = h // (heads / groups)``, with
``dt_t = softplus(dt_t + dt_bias_h)`` and ``a_h = -exp(A_log_h)`` (one scalar
a head), ``S`` zero before the sequence's start:

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t B_t^T      (x_t [head_dim], B_t [state])
    y_t = S_t C_t + D_h x_t

``softplus``, ``exp``, the decay products and ``S`` in float32
(``STATE_DTYPE``); ``x``, ``B`` and ``C`` enter the matmuls in the dtype they
arrive in, accumulated in float32.

**How it is computed** is one way, whatever the call (``WAY``): the chunked
algorithm of the Mamba-2 paper (arXiv:2405.21060, listing 1) in plain
``jax.numpy`` einsums, left to XLA, backward by autodiff.  The sequence is cut
into chunks of ``chunk`` tokens (the source's ``chunk_size``: 128).  Inside a
chunk the recurrence is a masked, decay-weighted product: ``y_i += sum_{j <=
i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j``, ``cum`` the running sum of ``dt
a`` from the chunk's start; ``C B^T`` is made once a group, the decays once a
head.  A chunk leaves one state (what its tokens add to ``S`` by its end), a
serial pass over the chunks (``lax.scan``: 64 steps at 8192 tokens, never one
over the tokens) carries ``S`` from chunk to chunk in float32, and a chunk's
tokens read the state that entered it: ``y_i += exp(cum_i) S_in C_i``.  The
chunks' entering states reach that product in the operands' dtype (a matmul
operand, rounded once; the carried state is not).  ``BLOCK_CHUNKS`` chunks are
multiplied at once and the blocks follow one another in a ``lax.scan`` whose
body keeps its inputs only: all chunks at once keep 3 GB a layer for the
backward pass at the benchmark's shape (two sequences of 8192, 64 heads),
which one v5e beside 8 GB of state does not hold; blocks of 4 measured
fastest of 2, 4, 8 and 16.  PERF.md section 6 (PR 46)
has what this way costs on one v5e at the benchmark's shape beside its bytes'
least, and what a kernel that keeps the decays and ``C B^T`` in VMEM would.

``attention_mask`` (left padding: 1 = a real token) zeroes ``dt`` and ``x`` at
padded positions: a padded token decays nothing and adds nothing, so the
first real token meets the zero state an unpadded sequence starts from.
``segment_ids`` (packed documents) reset ``S`` at a document's start: a decay
of 0 there, so a token reads only what its own document wrote.  A length that
is no multiple of ``chunk`` is padded to one the same way and cut off again.

The sequence dependency is total: a split of the sequence over chips (context
or sequence parallelism) or over time (cached decode) has to carry ``S``, and
this function does not: the family refuses those (models/nemotron_h.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: the source's ``chunk_size``
CHUNK = 128
#: ``run_summary.json``'s ``ssd.way``: how the scan is computed
WAY = "xla_chunked_einsum"
#: chunks multiplied at once (``_block``); the blocks follow one another
BLOCK_CHUNKS = 4
#: the carried state's dtype (a test moves it to show that it is held)
STATE_DTYPE = jnp.float32


def _f32(x):
    """Float32 for the step sizes, the decays and the state, as a norm's
    internals."""
    return x.astype(jnp.float32)  # jaxlint: disable=JL106


def bytes_per_token(heads: int, head_dim: int, state: int, groups: int,
                    itemsize: int = 2) -> int:
    """The least the forward scan moves a token: ``x`` read and ``y`` written,
    ``B`` and ``C`` read, ``dt`` read."""
    return (2 * heads * head_dim + 2 * groups * state + heads) * itemsize


def _chunk_states(decay, local, state, dtype):
    """The state that ENTERS every chunk of a block: ``decay [b, k, g, r]`` a
    whole chunk's decay, ``local [b, k, g, r, p, n]`` what a chunk's own tokens
    leave, ``state`` what enters the first -> ``([b, k, g, r, p, n]`` in
    ``dtype``, as the product that reads it takes it``, the state that leaves
    the last)``; the carry in ``STATE_DTYPE``."""
    def step(carry, chunk):
        d, s = chunk
        return (d[..., None, None] * carry + s).astype(STATE_DTYPE), carry.astype(dtype)

    state, entering = jax.lax.scan(
        step, state, (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(entering, 0, 1), state


def _block(state, block):
    """``BLOCK_CHUNKS`` chunks at once: ``state [b, g, r, p, n]`` enters the
    first, ``block`` holds ``x [b, k, q, g, r, p]``, ``B`` / ``C [b, k, q, g,
    n]``, ``step`` / ``cum [b, k, g, r, q]`` (heads ahead of a chunk's tokens)
    ``skip [b, k, g, r]`` (``D``) and ``counts`` / ``to_end`` / ``reads`` /
    ``carried`` (which pairs, inputs, readers and chunks count: masks, or None)
    -> ``(the state that leaves the last, y [b, k, q, g, r, p])``."""
    xc, Bc, Cc, stepc, cum = (block[name] for name in ("x", "B", "C", "step", "cum"))
    dtype = xc.dtype
    q = xc.shape[2]
    counts = jnp.tril(jnp.ones((q, q), bool))
    if block.get("counts") is not None:
        counts = counts & block["counts"]

    # inside a chunk: (C B^T * decays * dt) x
    scores = jnp.einsum("bkign,bkjgn->bkgij", Cc, Bc, preferred_element_type=jnp.float32)
    decays = jnp.exp(jnp.where(counts, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    weights = scores[:, :, :, None] * decays * stepc[..., None, :]    # [b, k, g, r, i, j]
    y = jnp.einsum("bkgrij,bkjgrp->bkigrp", weights.astype(dtype), xc,
                   preferred_element_type=jnp.float32)

    # what a chunk's tokens leave by its end, and the pass over the chunks
    left = jnp.exp(cum[..., -1:] - cum) * stepc                       # [b, k, g, r, q]
    chunk_decay = jnp.exp(cum[..., -1])                               # [b, k, g, r]
    from_state = jnp.exp(cum)
    if block.get("to_end") is not None:
        left = jnp.where(block["to_end"], left, 0.0)
        chunk_decay = jnp.where(block["carried"], chunk_decay, 0.0)
        from_state = jnp.where(block["reads"], from_state, 0.0)
    weighted = (_f32(xc) * left.transpose(0, 1, 4, 2, 3)[..., None]).astype(dtype)
    local = jnp.einsum("bkjgrp,bkjgn->bkgrpn", weighted, Bc,
                       preferred_element_type=jnp.float32)
    entering, state = _chunk_states(chunk_decay, local, state, dtype)

    # what the entering state gives a chunk's tokens
    y = y + (jnp.einsum("bkign,bkgrpn->bkigrp", Cc, entering,
                        preferred_element_type=jnp.float32)
             * from_state.transpose(0, 1, 4, 2, 3)[..., None])
    y = y + block["skip"][:, :, None, :, :, None] * _f32(xc)
    return state, y.astype(dtype)


def ssd_scan(x: jax.Array, B: jax.Array, C: jax.Array, dt: jax.Array,
             A_log: jax.Array, D: jax.Array, dt_bias: Optional[jax.Array] = None, *,
             chunk: int = CHUNK, attention_mask: Optional[jax.Array] = None,
             segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """``x [b, s, heads, head_dim]``, ``B`` / ``C [b, s, groups, state]``, ``dt
    [b, s, heads]`` (before its bias and softplus), ``A_log`` / ``D`` /
    ``dt_bias [heads]`` -> ``y`` like ``x``.  ``attention_mask``,
    ``segment_ids``: ``[b, s]`` or None."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"ssd_scan: {h} heads are no multiple of {g} groups")
    r = h // g
    dtype = x.dtype
    step = _f32(dt) if dt_bias is None else _f32(dt) + _f32(dt_bias)
    step = jax.nn.softplus(step)                                  # [b, s, h]
    if attention_mask is not None:
        real = attention_mask.astype(bool)
        step = jnp.where(real[..., None], step, 0.0)
        x = jnp.where(real[..., None, None], x, jnp.zeros((), dtype))
    q = min(chunk, s)
    pad = -s % q
    if pad:   # whole chunks: the rows past the end add nothing and are cut off again
        x, B, C, step = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, B, C, step))
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge")
    c = (s + pad) // q
    xc = x.reshape(b, c, q, g, r, p)
    Bc, Cc = B.reshape(b, c, q, g, n), C.reshape(b, c, q, g, n)
    # heads ahead of the chunk's tokens: ``[b, c, g, r, q]``
    stepc = step.reshape(b, c, q, g, r).transpose(0, 1, 3, 4, 2)
    # the running sum of dt a inside a chunk, as a product with a triangle of
    # ones (XLA's TPU cumsum is a reduce-window: 2.7 ms of a layer's 10)
    cum = jnp.einsum("bcgrj,ij->bcgri", stepc * (-jnp.exp(_f32(A_log))).reshape(g, r, 1),
                     jnp.tril(jnp.ones((q, q), jnp.float32)),
                     precision=jax.lax.Precision.HIGHEST)
    skip = jnp.broadcast_to(_f32(D).reshape(1, 1, g, r), (b, c, g, r))
    block = {"x": xc, "B": Bc, "C": Cc, "step": stepc, "cum": cum, "skip": skip}
    if segment_ids is not None:
        seg = segment_ids.reshape(b, c, q)
        last = seg[..., -1]                                           # [b, c]
        before = jnp.pad(last, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
        # pairs of a chunk's tokens in one document; and where no document
        # starts between: a token's input reaches its chunk's end, a token
        # reads the entering state, a chunk passes it on
        block["counts"] = (seg[..., :, None] == seg[..., None, :])[:, :, None, None]
        block["to_end"] = (seg == last[..., None])[:, :, None, None]  # [b, c, 1, 1, q]
        block["reads"] = (seg == before[..., None])[:, :, None, None]
        block["carried"] = (last == before)[:, :, None, None]         # [b, c, 1, 1]

    # blocks of chunks one after another, each kept as its inputs and run again
    # in the backward pass: what autodiff keeps of a block (the decays, the
    # weights, their products: ``[b, k, heads, q, q]`` float32 each) is live
    # for one block at a time
    k = next(n for n in range(min(BLOCK_CHUNKS, c), 0, -1) if c % n == 0)
    blocks = jax.tree_util.tree_map(
        lambda t: jnp.moveaxis(t.reshape((b, c // k, k) + t.shape[2:]), 1, 0), block)
    _, y = jax.lax.scan(jax.checkpoint(_block),
                        jnp.zeros((b, g, r, p, n), STATE_DTYPE), blocks)
    return jnp.moveaxis(y, 0, 1).reshape(b, c * q, h, p)[:, :s]
