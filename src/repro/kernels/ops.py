"""Jitted public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode -- the
kernel body runs in Python for correctness validation; on a TPU backend
they compile to Mosaic.  Any other backend is an error, not a silent
interpreter run.  The wrappers also own layout adaptation (BSHD <->
BHSD transposes, chunking/padding) so model code calls a clean surface.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import pack as _pack
from . import ssd_scan as _ssd

__all__ = ["flash_attention", "ssd_chunked_pallas", "pack_blocks", "pack_cols"]


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels compile for TPU or run interpreted on CPU; "
        f"JAX's default backend is {backend!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, block_q, block_k):
    qt = jnp.swapaxes(q, 1, 2)   # (B,H,S,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret())
    return jnp.swapaxes(out, 1, 2)


def _flash_fwd(q, k, v, causal, window, block_q, block_k):
    return _flash_attention(q, k, v, causal, window, block_q, block_k), (q, k, v)


def _flash_bwd(causal, window, block_q, block_k, res, g):
    # Backward recomputes attention blockwise (flash-style: no S^2
    # materialization) via the oracle's VJP -- the standard structure of the
    # flash backward pass, here expressed through XLA instead of a second
    # hand-written kernel.
    from repro.models.layers import blockwise_attention

    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(
            q_, k_, v_, causal=causal, window=window,
            q_chunk=block_q, k_chunk=block_k), q, k, v)
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 512):
    """q (B,S,H,D); k/v (B,S,KV,D) -> (B,S,H,D). Differentiable (custom VJP)."""
    return _flash_attention(q, k, v, causal, window, block_q, block_k)


def _ssd_oracle(x, dA, Bm, Cm, chunk, initial_state):
    from repro.models.ssm import ssd_chunked

    return ssd_chunked(x, dA, Bm, Cm, chunk=chunk, initial_state=initial_state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _ssd_pallas(x, dA, Bm, Cm, chunk, initial_state):
    return _ssd_impl(x, dA, Bm, Cm, chunk, initial_state)


def _ssd_fwd(x, dA, Bm, Cm, chunk, initial_state):
    return (_ssd_impl(x, dA, Bm, Cm, chunk, initial_state),
            (x, dA, Bm, Cm, initial_state))


def _ssd_bwd(chunk, res, g):
    x, dA, Bm, Cm, initial_state = res
    if initial_state is None:
        _, vjp = jax.vjp(
            lambda *a: _ssd_oracle(*a, chunk, None), x, dA, Bm, Cm)
        return (*vjp(g), None)
    _, vjp = jax.vjp(
        lambda x_, dA_, B_, C_, s0: _ssd_oracle(x_, dA_, B_, C_, chunk, s0),
        x, dA, Bm, Cm, initial_state)
    return vjp(g)


_ssd_pallas.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_chunked_pallas(x, dA, Bm, Cm, chunk: int = 256, initial_state=None):
    """Drop-in for models.ssm.ssd_chunked with the intra-chunk part in Pallas.

    x (B,S,H,P) pre-multiplied by dt; dA (B,S,H); Bm/Cm (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,N,P)). Differentiable (custom VJP;
    backward runs the oracle's VJP -- the recurrence grads stay in XLA).
    """
    return _ssd_pallas(x, dA, Bm, Cm, chunk, initial_state)


def _ssd_impl(x, dA, Bm, Cm, chunk: int = 256, initial_state=None):
    b, s, h, p = x.shape
    g, n = Bm.shape[2], Bm.shape[3]
    r = h // g
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s

    def pad3(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    xp = pad3(x).reshape(b, nc, q, h, p)
    dAp = pad3(dA).reshape(b, nc, q, h)
    Bp = pad3(Bm).reshape(b, nc, q, g, n)
    Cp = pad3(Cm).reshape(b, nc, q, g, n)

    y_diag, states = _ssd.ssd_intra_chunk(xp, dAp, Bp, Cp, interpret=_interpret())

    # inter-chunk recurrence + off-diagonal correction (cheap, stays in XLA)
    dA_cs = jnp.cumsum(dAp, axis=2)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                   # (b,nc,h)
    s0 = (jnp.zeros((b, h, n, p), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def scan_fn(prev, inp):
        st, dec = inp
        new = prev * dec[:, :, None, None] + st
        return new, prev

    final, prevs = jax.lax.scan(
        scan_fn, s0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prevs = jnp.moveaxis(prevs, 0, 1)                           # (b,nc,h,n,p)

    in_decay = jnp.exp(dA_cs)                                   # (b,nc,q,h)
    Ch = jnp.repeat(Cp, r, axis=3) if g != h else Cp            # (b,nc,q,h,n)
    y_off = jnp.einsum("bcqhn,bchnp->bcqhp", Ch, prevs)
    y_off = y_off * in_decay[..., None]

    y = (y_diag + y_off).reshape(b, nc * q, h, p)[:, :s]
    return y.astype(x.dtype), final


# ``interpret`` is resolved outside the jit so it is part of the cache key.
_pack_blocks = jax.jit(_pack.pack_blocks,
                       static_argnames=("tile_rows", "block_cols", "interpret"))
_pack_cols = jax.jit(_pack.pack_cols,
                     static_argnames=("tile_cols", "block_rows", "interpret"))


def pack_blocks(src, tile_offsets, tile_rows: int, block_cols: int):
    return _pack_blocks(src, tile_offsets, tile_rows=tile_rows,
                        block_cols=block_cols, interpret=_interpret())


def pack_cols(src, tile_offsets, tile_cols: int, block_rows: int):
    return _pack_cols(src, tile_offsets, tile_cols=tile_cols,
                      block_rows=block_rows, interpret=_interpret())
