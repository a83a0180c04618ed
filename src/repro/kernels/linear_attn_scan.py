"""Pallas TPU kernel: chunked causal linear attention (prefix-state scan).

The compute hot spot of random-feature attention (paper Fig. 1): given
feature-mapped queries/keys Q', K' (L x m) and values V (L x dv), compute

    out_i = ( Q'_i . sum_{j<=i} K'_j V_j^T ) / ( Q'_i . sum_{j<=i} K'_j )

in O(L m dv) by carrying the running state S (m x dv) and normalizer z (m)
across sequence chunks.

TPU adaptation (vs the CUDA shared-memory loop): the (batch*heads) axis maps
to the PARALLEL grid dimension; the chunk axis maps to the LAST (sequential)
grid dimension, so S and z live in VMEM scratch and persist across grid
steps. Within a chunk the causal part is tril(Q'K'^T) V — an MXU-friendly
(T x m)(m x T)(T x dv) matmul chain. T, m, dv should be multiples of the
128-lane register tile for full MXU utilization; the wrapper pads.

VMEM working set per grid step (f32):
    q,k: 2*T*m    v,o: 2*T*dv    S: m*dv    z: m    local: T*T
For T = m = 256, dv = 128: ~1.0 MB « 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(q_ref, k_ref, v_ref, o_ref, s_ref, z_ref, *, eps: float,
            nc: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    q = q_ref[0].astype(jnp.float32)        # (T, m)
    k = k_ref[0].astype(jnp.float32)        # (T, m)
    v = v_ref[0].astype(jnp.float32)        # (T, dv)
    t = q.shape[0]

    s_in = s_ref[...]                        # (m, dv)
    z_in = z_ref[0]                          # (m,)

    local = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (T, T)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    local = jnp.where(row >= col, local, 0.0)

    num = (jnp.dot(q, s_in, preferred_element_type=jnp.float32)
           + jnp.dot(local, v, preferred_element_type=jnp.float32))
    den = (jnp.dot(q, z_in[:, None],
                   preferred_element_type=jnp.float32)[:, 0]
           + jnp.sum(local, axis=1))
    o_ref[0] = (num / (den[:, None] + eps)).astype(o_ref.dtype)

    s_ref[...] = s_in + jax.lax.dot_general(
        k, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # K^T V: (m, dv)
    z_ref[0] = z_in + jnp.sum(k, axis=0)


def linear_attention_causal_fwd(qf: Array, kf: Array, v: Array, *,
                                chunk: int = 256, eps: float = 1e-6,
                                interpret: bool = False) -> Array:
    """qf, kf: (N, L, m); v: (N, L, dv) -> (N, L, dv).

    N is flattened batch*heads. L is padded to a multiple of ``chunk``.
    """
    n, l, m = qf.shape
    dv = v.shape[-1]
    t = min(chunk, l)
    pad = (-l) % t
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // t

    grid = (n, nc)
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, dv), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, dv), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((n, lp, dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((m, dv), jnp.float32),
            pltpu.VMEM((1, m), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qf, kf, v)
    return out[:, :l]


def _kernel_carry(q_ref, k_ref, v_ref, s0_ref, z0_ref,
                  o_ref, so_ref, zo_ref, s_ref, z_ref, *, eps: float):
    """Same scan as ``_kernel`` but seeded from (and emitting) the prefix
    state — the chunked-prefill resume point of docs/serving.md."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(jnp.float32)
        z_ref[...] = z0_ref[0].astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)        # (T, m)
    k = k_ref[0].astype(jnp.float32)        # (T, m)
    v = v_ref[0].astype(jnp.float32)        # (T, dv)
    t = q.shape[0]

    s_in = s_ref[...]                        # (m, dv)
    z_in = z_ref[0]                          # (m,)

    local = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (T, T)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    local = jnp.where(row >= col, local, 0.0)

    num = (jnp.dot(q, s_in, preferred_element_type=jnp.float32)
           + jnp.dot(local, v, preferred_element_type=jnp.float32))
    den = (jnp.dot(q, z_in[:, None],
                   preferred_element_type=jnp.float32)[:, 0]
           + jnp.sum(local, axis=1))
    o_ref[0] = (num / (den[:, None] + eps)).astype(o_ref.dtype)

    s_new = s_in + jax.lax.dot_general(
        k, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # K^T V: (m, dv)
    z_new = z_in + jnp.sum(k, axis=0)
    s_ref[...] = s_new
    z_ref[0] = z_new
    # the state output block is revisited every sequential step; the last
    # chunk's write is what lands in HBM
    so_ref[0] = s_new
    zo_ref[0] = z_new[None]


def linear_attention_causal_carry_fwd(qf: Array, kf: Array, v: Array,
                                      s0: Array, z0: Array, *,
                                      chunk: int = 256, eps: float = 1e-6,
                                      interpret: bool = False
                                      ) -> tuple[Array, Array, Array]:
    """Chunked causal linear attention resumed from a carried prefix state.

    qf, kf: (N, L, m); v: (N, L, dv); s0: (N, m, dv); z0: (N, m).
    Returns (out (N, L, dv) in v.dtype, s (N, m, dv) f32, z (N, m) f32).
    L is padded to a multiple of ``chunk``; padded key rows must be (and
    are, per the wrapper contract) zero features so the final state is
    unaffected.
    """
    n, l, m = qf.shape
    dv = v.shape[-1]
    t = min(chunk, l)
    pad = (-l) % t
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // t

    # z rides as (N, 1, m): Mosaic tiles the last two block dims by
    # (8, 128) unless they equal the array's, so the blocked N axis
    # must lead
    grid = (n, nc)
    out, s_f, z_f = pl.pallas_call(
        functools.partial(_kernel_carry, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, t, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, m), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, t, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, m, dv), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b, c: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, t, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, m, dv), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, m), lambda b, c: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, lp, dv), v.dtype),
            jax.ShapeDtypeStruct((n, m, dv), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, m), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((m, dv), jnp.float32),
            pltpu.VMEM((1, m), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(qf, kf, v, s0, z0.reshape(n, 1, m))
    return out[:, :l], s_f, z_f.reshape(n, m)
