"""Mamba-2 SSD (state-space duality) chunked scan, TPU Pallas.

Grid: (batch, n_chunks) — chunks iterate minor-most so the inter-chunk
recurrent state (H, P, N) persists in VMEM scratch across the sequential
grid steps (TPU cores execute the grid in order; this is the TPU-native
replacement for the CUDA kernel's cross-block state passing).

Per chunk the kernel computes, entirely in VMEM:
  * cumulative log-decays (cumsum over the chunk),
  * the intra-chunk quadratic term  C_l (sum_m exp(A_l..m) B_m dt_m x_m)
    via two MXU matmuls (L x L scores, masked lower-triangular),
  * the inter-chunk term  C_l exp(A_l..0) . state,
  * the state update      state <- exp(A_L..0) state + B^T (decay dt x).

Head dim and state dim (P=64/128, N=64/128) are MXU-friendly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, state_ref, *,
                chunk: int, n_heads: int, head_dim: int, d_state: int,
                n_groups: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (L, H, P)
    dt = dt_ref[0].astype(jnp.float32)        # (L, H)
    B_ = b_ref[0].astype(jnp.float32)         # (L, G, N)
    C_ = c_ref[0].astype(jnp.float32)         # (L, G, N)
    a = a_ref[...].astype(jnp.float32)        # (H,)

    L, H, P = chunk, n_heads, head_dim
    G, N = n_groups, d_state
    rep = H // G

    da = dt * a[None, :]                      # (L, H) negative
    css = jnp.cumsum(da, axis=0)              # inclusive
    seg_end = css[-1]                         # (H,)

    Bh = jnp.repeat(B_, rep, axis=1)          # (L, H, N)
    Ch = jnp.repeat(C_, rep, axis=1)

    # inter-chunk: y_inter[l] = (C_l * exp(css_l)) . state
    Cd = Ch * jnp.exp(css)[..., None]         # (L, H, N)
    state = state_ref[...]                    # (H, P, N)
    y_inter = jnp.einsum("lhn,hpn->lhp", Cd, state,
                         preferred_element_type=jnp.float32)

    # intra-chunk quadratic form
    scores = jnp.einsum("lhn,mhn->lmh", Ch, Bh,
                        preferred_element_type=jnp.float32)   # (L, L, H)
    decay = jnp.exp(css[:, None, :] - css[None, :, :])        # (L, L, H)
    mask = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    att = jnp.where(mask[..., None], scores * decay, 0.0)
    att = att * dt[None, :, :]                                # dt_m
    y_intra = jnp.einsum("lmh,mhp->lhp", att, x,
                         preferred_element_type=jnp.float32)

    # state update
    sdecay = jnp.exp(seg_end[None, :] - css)                  # (L, H)
    xw = x * (dt * sdecay)[..., None]                         # (L, H, P)
    chunk_state = jnp.einsum("lhn,lhp->hpn", Bh, xw,
                             preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(seg_end)[:, None, None] + chunk_state

    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, B_, C_, *, chunk: int = 128, interpret: bool):
    """x: (B, T, H, P); dt: (B, T, H) (post-softplus); a: (H,) negative;
    B_, C_: (B, T, G, N).  Returns y: (B, T, H, P) fp32.

    T is padded to a chunk multiple with dt=0 (identity decay, no input).
    """
    Bb, T, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    L = min(chunk, T)
    pad = -T % L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = x.shape[1] // L

    kernel = functools.partial(_ssd_kernel, chunk=L, n_heads=H, head_dim=P,
                               d_state=N, n_groups=G)
    y = pl.pallas_call(
        kernel,
        grid=(Bb, n_chunks),
        in_specs=[
            pl.BlockSpec((1, L, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, L, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, L, G, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, L, G, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
        ],
        out_specs=pl.BlockSpec((1, L, H, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, n_chunks * L, H, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, B_, C_, a)
    if pad:
        y = y[:, :T]
    return y
