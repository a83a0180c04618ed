"""Pallas TPU megakernel: fused data-aligned PRF prefill chunk.

The prefill twin of ``prf_fused_decode``: ONE kernel per layer per
packed (P, L) chunk that takes RAW scaled q/k/v (d-dim, the 1/sqrt(d)
temperature pre-absorbed), the precomposed data-aligned projection
``A = (W M)^T`` (plain ``W^T`` for the isotropic Performer/LFK kinds),
the per-row ragged ``valid_len`` of the token-budget packer, and the
carried ``AttnServeState`` (S, z, c), and fuses the whole resumable
prefill pass in VMEM — per (row-block, KV-group, chunk) grid step:

    qraw = q A − ‖Mq‖²/2          kraw = k A − ‖Mk‖²/2
    c'   = max(c, max_{valid,m} kraw)    ρ = exp(c − c')
    qf   = [pos < valid_len] · exp(qraw − max_{valid,m} qraw)/√m
    kf   = [pos < valid_len] · exp(kraw − c')/√m
    out  = (qf·(ρS) + tril(qf kfᵀ)·v) / (qf·(ρz) + Σ tril(qf kfᵀ) + ε)
    S'   = ρS + kfᵀv              z' = ρz + Σ_T kf

replacing the two-stage prefill path (jnp ``_resume_qk_features`` +
``linear_attn_scan`` carry kernel): the (N, L, m) feature tensors never
exist in HBM, the running-max k-stabilizer rescale happens while S is
already resident for the rank-1 chunk update, and
``input_output_aliases`` writes the incoming state pool IN PLACE so a
resumed chunk never reallocates pool-sized (S, z, c) buffers.

Ragged masking lives IN-KERNEL: a row's positions at or past its
``valid_len`` contribute nothing to the chunk's k-stabilizer max and
get zero k-features, so they leave no trace in (S, z, c) — the contract
that lets the serving engine pad several staged admissions into one
batched call. Padded positions also get zero q-features, so their
outputs are 0 (callers gather per-row at ``valid_len − 1``) and stay
finite even in a chunk that holds none of the row's positions.

Grid: (row blocks, G, L/T chunks) — rows and KV groups parallel, the
chunk axis sequential ("arbitrary") so the (S, z, c) output blocks act
as the VMEM-resident carry: initialized from the aliased state inputs
at chunk 0, revisited every sequential step, flushed to HBM once when
the row/group block retires. Row blocks never pad (``_block_divisor``,
same reason as decode: a padded copy would be the pool-sized
allocation the aliasing removes).

GQA: k-features are computed ONCE per KV group per chunk and shared by
the Hg query heads; the per-head work (tril local attention + state
update) is a static unroll over (row, head) of plain 2-D MXU matmuls.

VMEM per grid step (f32) is dominated by the S carry block
``block_b·Hg·m·dv`` plus the chunk features ``block_b·(Hg+1)·T·m``:
for block_b = 1, Hg = 8, m = 256, dv = 128, T = 256 that is
~1 MB + ~2.4 MB of the ~16 MB/core — grow ``block_b`` only for small
(Hg, m, T) geometries.

On non-TPU backends the wrapper in ``repro.kernels.ops`` runs this with
interpret=True (same numerics, no Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from repro.kernels.prf_fused_decode import _block_divisor, _featurize

Array = jax.Array

_NEG = float(jnp.finfo(jnp.float32).min)


def _kernel(q_ref, k_ref, v_ref, a_ref, m_ref, vl_ref, c_ref, s_ref,
            z_ref, o_ref, so_ref, zo_ref, co_ref, *, stabilize: bool,
            eps: float):
    ci = pl.program_id(2)
    tb, _, hg, t, _ = q_ref.shape
    m = a_ref.shape[-1]
    inv_sqrt_m = m ** -0.5
    f32 = jnp.float32

    # chunk 0 seeds the carry: the (S, z, c) OUTPUT blocks are revisited
    # by every sequential chunk step (their index maps ignore ci), so
    # they live in VMEM for the whole row/group visit and double as the
    # carried state; the aliased inputs are only ever read here.
    @pl.when(ci == 0)
    def _init():
        so_ref[...] = s_ref[...].astype(f32)
        zo_ref[...] = z_ref[...].astype(f32)
        co_ref[...] = c_ref[...].astype(f32)

    a = a_ref[0].astype(f32)                             # (d, m)
    m_mat = None if m_ref is None else m_ref[0].astype(f32)
    # the block's features in one matmul each, from its (row, head) tiles
    q = jnp.concatenate([q_ref[b, 0, h] for b in range(tb)
                         for h in range(hg)], axis=0)
    k = jnp.concatenate([k_ref[b, 0] for b in range(tb)], axis=0)
    qraw = _featurize(q.astype(f32), a, m_mat)           # (Tb*Hg*T, m)
    kraw = _featurize(k.astype(f32), a, m_mat)           # (Tb*T, m) ONCE
    #                                                      per group

    # absolute chunk position of every (T, m) feature entry's row
    pos = ci * t + jax.lax.broadcasted_iota(jnp.int32, (t, m), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    tril = row >= col

    # static unroll over (row, head): every operand is a 2-D tile and
    # every matmul 2-D (MXU-shaped); the kfᵀv chunk update and Σkf are
    # shared across the Hg heads.
    for b in range(tb):
        # ragged valid_len mask: absolute chunk positions vs the row's
        # length. Wrapper L-padding lands past every valid_len, so one
        # mask covers both the packer's ragged rows and the pow-2 tail
        # padding.
        valid = pos < vl_ref[b]                          # (T, m)
        kraw_b = kraw[b * t:(b + 1) * t]                 # (T, m)
        c_old = co_ref[b, 0]                             # (1, 1) carry
        if stabilize:
            # running max over the carried c and this chunk's VALID key
            # logits; masked rows advance c by nothing (max of _NEG
            # sentinels never beats a real carry) and rho stays 1.
            mk = jnp.max(jnp.where(valid, kraw_b, _NEG), keepdims=True)
            c_new = jnp.maximum(c_old, mk)
            rho = jnp.exp(c_old - c_new)                 # (1, 1), <= 1
            kf_b = jnp.exp(kraw_b - c_new) * inv_sqrt_m
        else:
            # unstabilized features carry c == 0 (the init state's -1e30
            # sentinel only ever zeroes an all-zero fresh state)
            c_new = jnp.zeros_like(c_old)
            rho = jnp.exp(c_old)
            kf_b = jnp.exp(kraw_b) * inv_sqrt_m
        kf_b = jnp.where(valid, kf_b, 0.0)               # masked -> 0
        v_b = v_ref[b, 0].astype(f32)                    # (T, dv)
        ds = jax.lax.dot_general(kf_b, v_b, (((0,), (0,)), ((), ())),
                                 preferred_element_type=f32)  # (m, dv)
        dz = jnp.sum(kf_b, axis=0, keepdims=True)        # (1, m)
        for h in range(hg):
            i = b * hg + h
            qraw_bh = qraw[i * t:(i + 1) * t]            # (T, m)
            if stabilize:
                qmax = jnp.max(jnp.where(valid, qraw_bh, _NEG),
                               keepdims=True)
                qf_bh = jnp.exp(qraw_bh - qmax) * inv_sqrt_m
            else:
                qf_bh = jnp.exp(qraw_bh) * inv_sqrt_m
            # padded positions read nothing: in a chunk that holds none
            # of the row's positions qmax is _NEG and qf overflows, and
            # the NaN outputs (inf·0) would reach the next layer's v,
            # then its state through 0·NaN in kfᵀv
            qf_bh = jnp.where(valid, qf_bh, 0.0)
            s_old = so_ref[b, 0, h] * rho                # (m, dv)
            z_old = zo_ref[b, 0, h:h + 1] * rho          # (1, m)
            local = jax.lax.dot_general(
                qf_bh, kf_b, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)              # (T, T)
            if t > 1:
                local = jnp.where(tril, local, 0.0)
                local_sum = jnp.sum(local, axis=1, keepdims=True)
            else:
                # one position: the causal block is its own diagonal
                # (and Mosaic cannot mask or reduce a one-lane tile)
                local_sum = local
            num = (jnp.dot(qf_bh, s_old, preferred_element_type=f32)
                   + jnp.dot(local, v_b, preferred_element_type=f32))
            den = jax.lax.dot_general(
                qf_bh, z_old, (((1,), (1,)), ((), ())),
                preferred_element_type=f32) + local_sum      # (T, 1)
            o_ref[b, 0, h] = (num / (den + eps)).astype(o_ref.dtype)
            so_ref[b, 0, h] = s_old + ds
            zo_ref[b, 0, h:h + 1] = z_old + dz
        co_ref[b, 0] = c_new


def _no_mmat_kernel(kernel, q_ref, k_ref, v_ref, a_ref, vl_ref, c_ref,
                    s_ref, z_ref, *out_refs, **kw):
    """Isotropic variant: no m_mat operand; the norm uses x itself."""
    kernel(q_ref, k_ref, v_ref, a_ref, None, vl_ref, c_ref, s_ref,
           z_ref, *out_refs, **kw)


def prf_fused_prefill_fwd(q: Array, k: Array, v: Array, a: Array,
                          m_mat: Array | None, s: Array, z: Array,
                          c: Array, valid_len: Array | None = None, *,
                          stabilize: bool = True, eps: float = 1e-6,
                          chunk: int = 256, block_b: int = 1,
                          interpret: bool = False):
    """Advance a (B, G)-state pool over a packed L-token chunk, fused.

    q: (B, G, Hg, L, d); k, v: (B, G, L, d|dv); a: (G, d, m);
    m_mat: (G, r, d) or None (isotropic); s: (B, G, Hg, m, dv) f32;
    z: (B, G, Hg, m) f32; c: (B, G) f32 running k-stabilizer;
    valid_len: (B,) int32 ragged row lengths (None = all rows full).

    Returns (out (B, G, Hg, L, dv) in v.dtype, s_new, z_new, c_new)
    with the state outputs ALIASED to the input buffers (in-place pool
    update under jit when the caller donates the pool). L is padded to
    a multiple of ``chunk`` internally; the pad is masked like ragged
    padding and sliced off the output.
    """
    b, g, hg, l, d = q.shape
    m = a.shape[-1]
    dv = v.shape[-1]
    t = min(chunk, l)
    pad = (-l) % t
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    lp = l + pad
    nc = lp // t
    vl = (jnp.full((b,), l, jnp.int32) if valid_len is None
          else valid_len.astype(jnp.int32)).reshape(b, 1, 1)
    tb = _block_divisor(b, block_b)
    grid = (b // tb, g, nc)

    in_specs = [
        pl.BlockSpec((tb, 1, hg, t, d), lambda i, gi, ci: (i, gi, 0, ci,
                                                           0)),
        pl.BlockSpec((tb, 1, t, d), lambda i, gi, ci: (i, gi, ci, 0)),
        pl.BlockSpec((tb, 1, t, dv), lambda i, gi, ci: (i, gi, ci, 0)),
        pl.BlockSpec((1, d, m), lambda i, gi, ci: (gi, 0, 0)),
    ]
    inputs = [q, k, v, a]
    if m_mat is not None:
        r = m_mat.shape[-2]
        in_specs.append(pl.BlockSpec((1, r, d),
                                     lambda i, gi, ci: (gi, 0, 0)))
        inputs.append(m_mat)
        kernel = _kernel
    else:
        kernel = functools.partial(_no_mmat_kernel, _kernel)
    # Mosaic tiles the last two block dims by (8, 128) unless they equal
    # the array's: the per-row valid_len and per-(row, group) c get
    # trailing unit axes (free reshapes) so the blocked axes are leading
    in_specs.append(pl.BlockSpec((tb, 1, 1), lambda i, gi, ci: (i, 0, 0)))
    inputs.append(vl)
    n_state = len(inputs)
    in_specs += [
        pl.BlockSpec((tb, 1, 1, 1), lambda i, gi, ci: (i, gi, 0, 0)),
        pl.BlockSpec((tb, 1, hg, m, dv),
                     lambda i, gi, ci: (i, gi, 0, 0, 0)),
        pl.BlockSpec((tb, 1, hg, m), lambda i, gi, ci: (i, gi, 0, 0)),
    ]
    inputs += [c.astype(jnp.float32).reshape(b, g, 1, 1), s, z]

    out, s_new, z_new, c_new = pl.pallas_call(
        functools.partial(kernel, stabilize=stabilize, eps=eps),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((tb, 1, hg, t, dv),
                         lambda i, gi, ci: (i, gi, 0, ci, 0)),
            pl.BlockSpec((tb, 1, hg, m, dv),
                         lambda i, gi, ci: (i, gi, 0, 0, 0)),
            pl.BlockSpec((tb, 1, hg, m), lambda i, gi, ci: (i, gi, 0, 0)),
            pl.BlockSpec((tb, 1, 1, 1), lambda i, gi, ci: (i, gi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, g, hg, lp, dv), v.dtype),
            jax.ShapeDtypeStruct((b, g, hg, m, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, g, hg, m), jnp.float32),
            jax.ShapeDtypeStruct((b, g, 1, 1), jnp.float32),
        ),
        # the state pool (c, s, z) is updated IN PLACE: input n_state is
        # c -> output 3, n_state+1 is s -> output 1, n_state+2 is z -> 2
        input_output_aliases={n_state: 3, n_state + 1: 1, n_state + 2: 2},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(*inputs)
    return out[:, :, :, :l], s_new, z_new, c_new.reshape(b, g)
