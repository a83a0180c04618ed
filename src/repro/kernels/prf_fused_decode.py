"""Pallas TPU megakernel: fused data-aligned PRF decode step.

One kernel per (slot-block, KV-group) grid step that takes RAW scaled
q/k/v (d-dim, the 1/sqrt(d) temperature pre-absorbed), the precomposed
data-aligned projection ``A = (W M)^T`` (plain ``W^T`` for the isotropic
Performer/LFK kinds), the carried running k-stabilizer ``c`` and the
(S, z) slot-pool block, and fuses the whole decode hot path in VMEM:

    qraw = q A − ‖Mq‖²/2          kraw = k A − ‖Mk‖²/2
    c'   = max(c, max_m kraw)     ρ = exp(c − c')        (in-kernel
    qf   = exp(qraw − max_m qraw)/√m                      online-max
    kf   = exp(kraw − c')/√m                              stabilizer)
    S'   = ρ S + kf vᵀ            z' = ρ z + kf
    out  = (qf · S') / (qf · z' + ε)

replacing the jnp ``_resume_qk_features`` + two-dispatch
(``prf_featmap`` → ``prf_decode_step``) decode path: the (N, m) feature
tensors never exist in HBM, and ``input_output_aliases`` updates the
S/z/c slot pool IN PLACE instead of allocating a fresh pool-sized
buffer every token — the two HBM round trips that dominate the
memory-bound decode regime (docs/kernels.md §Fused decode).

GQA: k/v are per KV group ((B, G, d)); k-features are computed ONCE per
group inside the kernel and broadcast to the Hg query heads at the
update, instead of materializing (B, G, Hg, m) broadcast features like
the two-kernel path.

Grid: (slot blocks, G); both axes embarrassingly parallel. Slot blocks
never pad: the wrapper shrinks ``block_b`` to a divisor of B so the
aliased pool blocks tile exactly (padding would allocate the pool copy
the aliasing exists to avoid). VMEM per step (f32) is dominated by the
S block: ``block_b·Hg·m·dv`` — for block_b = 8, Hg = 8, m = 256,
dv = 128: ~8 MB of 16 MB; shrink ``block_b`` for bigger geometries.

On non-TPU backends the wrapper in ``repro.kernels.ops`` runs this with
interpret=True (same numerics, no Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _featurize(x2, a, m_mat):
    """Raw PRF logits for flattened rows x2 (R, d): x2 A − ‖M x2‖²/2.

    The projection runs through the precomposed A (ONE matmul); the
    norm term needs the low-rank re-embedding M x2 (darkformer) or x2
    itself (isotropic, m_mat None).
    """
    logits = jax.lax.dot_general(x2, a, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    xt = x2 if m_mat is None else jax.lax.dot_general(
        x2, m_mat, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return logits - 0.5 * jnp.sum(xt * xt, axis=-1, keepdims=True)


def _kernel(q_ref, k_ref, v_ref, a_ref, m_ref, c_ref, s_ref, z_ref,
            o_ref, so_ref, zo_ref, co_ref, *, stabilize: bool,
            eps: float):
    tb, _, hg, _ = q_ref.shape
    m = a_ref.shape[-1]
    inv_sqrt_m = m ** -0.5
    f32 = jnp.float32
    a = a_ref[0].astype(f32)                             # (d, m)
    m_mat = None if m_ref is None else m_ref[0].astype(f32)

    # the block's features in one matmul each, from its slots' rows
    q = jnp.concatenate([q_ref[b, 0] for b in range(tb)], axis=0)
    k = jnp.concatenate([k_ref[b, 0] for b in range(tb)], axis=0)
    qraw = _featurize(q.astype(f32), a, m_mat)           # (Tb*Hg, m)
    kraw = _featurize(k.astype(f32), a, m_mat)           # (Tb, m) — ONCE
    #                                                      per KV group
    # static unroll over the block's slots: every operand is a 2-D tile
    # and every contraction a plain 2-D matmul (Mosaic-shaped)
    for b in range(tb):
        qraw_b = qraw[b * hg:(b + 1) * hg]               # (Hg, m)
        kraw_b = kraw[b:b + 1]                           # (1, m)
        v = v_ref[b, 0].astype(f32)                      # (1, dv)
        c_old = c_ref[b, 0]                              # (1, 1)
        if stabilize:
            # online running-max: fold the new key's max into the
            # carried stabilizer and rescale the accumulated state ONCE
            # (§3 of docs/kernels.md); the q shift cancels pointwise so
            # the current token's own max is enough.
            qf = jnp.exp(qraw_b - jnp.max(qraw_b, axis=-1, keepdims=True)) \
                * inv_sqrt_m
            c_new = jnp.maximum(c_old,
                                jnp.max(kraw_b, axis=-1, keepdims=True))
            rho = jnp.exp(c_old - c_new)                 # <= 1
            kf = jnp.exp(kraw_b - c_new) * inv_sqrt_m
        else:
            # unstabilized features carry c == 0 (the init state's -1e30
            # sentinel only ever zeroes an all-zero fresh state)
            qf = jnp.exp(qraw_b) * inv_sqrt_m
            c_new = jnp.zeros_like(c_old)
            rho = jnp.exp(c_old)
            kf = jnp.exp(kraw_b) * inv_sqrt_m

        # rank-1 update kf vᵀ, shared by the Hg query heads of the group
        kv = jax.lax.dot_general(kf, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=f32)  # (m, dv)
        z_new = z_ref[b, 0].astype(f32) * rho + kf       # (Hg, m)
        den = jnp.sum(qf * z_new, axis=1, keepdims=True)  # (Hg, 1)
        for h in range(hg):
            s_new = s_ref[b, 0, h].astype(f32) * rho + kv  # (m, dv)
            num = jnp.dot(qf[h:h + 1], s_new,
                          preferred_element_type=f32)    # (1, dv)
            o_ref[b, 0, h:h + 1] = (num / (den[h:h + 1] + eps)) \
                .astype(o_ref.dtype)
            so_ref[b, 0, h] = s_new.astype(so_ref.dtype)
        zo_ref[b, 0] = z_new.astype(zo_ref.dtype)
        co_ref[b, 0] = c_new.astype(co_ref.dtype)


def _block_divisor(b: int, block_b: int) -> int:
    """Largest tile <= block_b that divides b exactly — the aliased pool
    blocks must tile the slot axis with NO padding (a padded copy would
    be exactly the pool-sized allocation the aliasing removes)."""
    tb = max(1, min(block_b, b))
    while b % tb:
        tb -= 1
    return tb


def prf_fused_decode_fwd(q: Array, k: Array, v: Array, a: Array,
                         m_mat: Array | None, s: Array, z: Array,
                         c: Array, *, stabilize: bool = True,
                         eps: float = 1e-6, block_b: int = 8,
                         interpret: bool = False):
    """Advance a (B, G)-slot pool by one token, fully fused.

    q: (B, G, Hg, d); k, v: (B, G, d|dv); a: (G, d, m);
    m_mat: (G, r, d) or None (isotropic); s: (B, G, Hg, m, dv) f32;
    z: (B, G, Hg, m) f32; c: (B, G) f32 running k-stabilizer.

    Returns (out (B, G, Hg, dv) f32, s_new, z_new, c_new) with the
    state outputs ALIASED to the input buffers (in-place pool update
    under jit when the caller donates the pool).
    """
    b, g, hg, d = q.shape
    m = a.shape[-1]
    dv = v.shape[-1]
    tb = _block_divisor(b, block_b)
    grid = (b // tb, g)

    # Mosaic tiles the last two block dims by (8, 128) unless they equal
    # the array's: the per-(slot, group) operands k, v, c get trailing
    # unit axes (free reshapes) so the blocked B and G axes are leading
    in_specs = [
        pl.BlockSpec((tb, 1, hg, d), lambda i, gi: (i, gi, 0, 0)),
        pl.BlockSpec((tb, 1, 1, d), lambda i, gi: (i, gi, 0, 0)),
        pl.BlockSpec((tb, 1, 1, dv), lambda i, gi: (i, gi, 0, 0)),
        pl.BlockSpec((1, d, m), lambda i, gi: (gi, 0, 0)),
    ]
    inputs = [q, k.reshape(b, g, 1, d), v.reshape(b, g, 1, dv), a]
    if m_mat is not None:
        r = m_mat.shape[-2]
        in_specs.append(pl.BlockSpec((1, r, d), lambda i, gi: (gi, 0, 0)))
        inputs.append(m_mat)
        kernel = _kernel
    else:
        kernel = functools.partial(_no_mmat_kernel, _kernel)
    n_lead = len(inputs)
    in_specs += [
        pl.BlockSpec((tb, 1, 1, 1), lambda i, gi: (i, gi, 0, 0)),
        pl.BlockSpec((tb, 1, hg, m, dv), lambda i, gi: (i, gi, 0, 0, 0)),
        pl.BlockSpec((tb, 1, hg, m), lambda i, gi: (i, gi, 0, 0)),
    ]
    inputs += [c.astype(jnp.float32).reshape(b, g, 1, 1), s, z]

    out, s_new, z_new, c_new = pl.pallas_call(
        functools.partial(kernel, stabilize=stabilize, eps=eps),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((tb, 1, hg, dv), lambda i, gi: (i, gi, 0, 0)),
            pl.BlockSpec((tb, 1, hg, m, dv),
                         lambda i, gi: (i, gi, 0, 0, 0)),
            pl.BlockSpec((tb, 1, hg, m), lambda i, gi: (i, gi, 0, 0)),
            pl.BlockSpec((tb, 1, 1, 1), lambda i, gi: (i, gi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, g, hg, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, g, hg, m, dv), jnp.float32),
            jax.ShapeDtypeStruct((b, g, hg, m), jnp.float32),
            jax.ShapeDtypeStruct((b, g, 1, 1), jnp.float32),
        ),
        # the slot pool (s, z, c) is updated IN PLACE: input n_lead is
        # c -> output 3, n_lead+1 is s -> output 1, n_lead+2 is z -> 2
        input_output_aliases={n_lead: 3, n_lead + 1: 1, n_lead + 2: 2},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*inputs)
    return out, s_new, z_new, c_new.reshape(b, g)


def _no_mmat_kernel(kernel, q_ref, k_ref, v_ref, a_ref, c_ref, s_ref,
                    z_ref, *out_refs, **kw):
    """Isotropic variant: no m_mat operand; the norm uses x itself."""
    kernel(q_ref, k_ref, v_ref, a_ref, None, c_ref, s_ref, z_ref,
           *out_refs, **kw)
