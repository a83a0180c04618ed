"""Pallas TPU kernel: chunked RWKV-6 WKV recurrence.

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)

Grid: (batch*heads) parallel x sequence-chunks sequential; the (dh x dh)
state is carried transposed (Sᵀ) in VMEM scratch across chunks. Within a
chunk the recurrence is stepped with a fori_loop over token rows read
from the refs — per step the work is one (dh x dh) VPU decay, one rank-1
kᵀv matmul and one (1 x dh)(dh x dh) matvec, all resident in VMEM
(dh = 64 for every RWKV-6 size). The data-dependent decay w_t (the
"Finch" feature) rules out the pure-matmul chunk form without log-space
renormalization; the in-VMEM stepped form sidesteps that stability
issue (see ref.wkv6_ref for the oracle).

VMEM per grid step (f32): 4*T*dh (r,k,v,w) + dh^2 (S) + T*dh (o)
  = T=256, dh=64: ~350 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, t: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[...].astype(jnp.float32)   # (1, dh)

    def step(i, st):
        # st is Sᵀ, so the per-key decay w scales its COLUMNS: a (1, dh)
        # row broadcast. One token's rows are read from and written to
        # the refs (Mosaic has no dynamic slice of an in-register value).
        r = r_ref[0, pl.ds(i, 1), :].astype(jnp.float32)    # (1, dh)
        k = k_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        w = w_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        # o = r (S + diag(u) kᵀv) = r S + (r·(u∘k)) v
        o_i = jax.lax.dot_general(r, st, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) \
            + jnp.sum(r * u * k, axis=1, keepdims=True) * v
        o_ref[0, pl.ds(i, 1), :] = o_i.astype(o_ref.dtype)
        vk = jax.lax.dot_general(v, k, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return st * w + vk                                  # (S')ᵀ

    s_ref[...] = jax.lax.fori_loop(0, t, step, s_ref[...])


def wkv6_fwd(r: Array, k: Array, v: Array, w: Array, u: Array, *,
             chunk: int = 256, interpret: bool = False) -> Array:
    """r,k,v,w: (N, L, dh); u: (dh,) -> o: (N, L, dh).

    N = batch*heads flattened; L padded to a chunk multiple (w=1, k=0 in
    the pad keeps the state frozen, so padding is exact).
    """
    n, l, dh = r.shape
    t = min(chunk, l)
    pad = (-l) % t
    if pad:
        r = jnp.pad(r, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)),
                    constant_values=1.0)
    lp = l + pad
    grid = (n, lp // t)
    out = pl.pallas_call(
        functools.partial(_kernel, t=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, dh), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dh), lambda b, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, dh), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((n, lp, dh), v.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(r, k, v, w, u.reshape(1, dh))
    return out[:, :l]
