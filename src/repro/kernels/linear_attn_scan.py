"""Pallas TPU kernels: causal linear attention over prefix states.

The compute hot spot of random-feature attention (paper Fig. 1): given
feature-mapped queries/keys Q', K' (L x m) and values V (L x dv), compute

    out_i = ( Q'_i . sum_{j<=i} K'_j V_j^T ) / ( Q'_i . sum_{j<=i} K'_j + eps )

in O(L m dv) by carrying the running state S (m x dv) and normalizer z (m)
across sequence chunks of T positions. The row axes map to PARALLEL grid
dimensions and the chunk axis to the LAST (sequential) one, so the
carried state lives in VMEM scratch across grid steps; within a chunk
the causal part is tril(Q'K'^T) V, an MXU-friendly matmul chain.

Training pair (``prf_mix_fwd`` / ``prf_mix_bwd``). A grid step takes
one batch row's chunk of ``gb`` KV groups (all of them unless fewer
fill whole 128-lane tiles of the values). For each group the Hg query
heads are stacked into (Hg T, m) rows against the group's one K' and V,
so K'^T V is computed once per group and dK', dV come out summed over
its heads. Values, outputs and their cotangents stay token-major,
(B, L, heads x dv) as the projections around the mix hold them. The
denominator rides the MXU as extra value columns: the values are
extended in VMEM to V' = [V | 0 | 1] of width 2 dh (dh = 64 for
dv <= 64, else dv rounded up to 128 lanes), the state to
S' = [S | z z ... z], and one product gives [num | den den ... den];
a lane roll by dh lines den up with num. Per grid step (f32 unless
noted):

  forward   out = num / (den + eps), den kept per row (f32) for the
            backward; S' += K'^T V'.
  backward  G' = [g / (den + eps) | 0 | -(g . out) / ((den + eps) dh)],
            the cotangent of [num | den] with the den part spread over
            its dh lanes; P = tril(G' V'^T). A forward sweep gives
            dQ' = G' S'^T + P K'; a reverse sweep carries
            dS' = sum over later chunks of Q'^T G' and gives
            dK' = P^T Q' + V' dS'^T,  dV = tril(Q'K'^T)^T G' + K' dS'.

Nothing of size L x m reaches HBM but the inputs and dQ', dK'. Matmul
operands take the features' dtype (values and intermediates are cast
to it) and accumulate in f32.

The carry kernel (``linear_attention_causal_carry_fwd``) is the serving
form: one row per (batch, group, head), seeded from and emitting the
prefix state, the resume point of chunked prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
F32 = jnp.float32


def _value_width(dv: int) -> int:
    """dh: the lanes V takes in V' = [V | 0 | 1] (2 dh wide)."""
    return 64 if dv <= 64 else -(-dv // 128) * 128


def _group_block(g: int, h: int, dv: int) -> int:
    """KV groups a grid step takes: the fewest whose values and outputs
    fill whole 128-lane tiles, else all of them."""
    return next((gb for gb in range(1, g) if g % gb == 0
                 and (gb * dv) % 128 == 0 and (gb * h * dv) % 128 == 0), g)


def _tile(l: int, block: int) -> tuple[int, int]:
    """(T, padded L): chunks of ``block`` positions, or one chunk of
    the whole sequence rounded up to 16 rows when it is shorter."""
    t = min(block, -(-l // 16) * 16)
    return t, -(-l // t) * t


def _pad_len(x: Array, lp: int, axis: int) -> Array:
    pad = lp - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _dot(a, b, ca: int, cb: int):
    """a . b contracting a's dim ``ca`` with b's ``cb``, f32 result."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=F32)


def _causal(x, h: int, t: int):
    """Zero the entries of (h t, t) above each head's diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    x = jnp.where((row >= col)[None], x.reshape(h, t, t), 0.0)
    return x.reshape(h * t, t)


def _lanes(x, w: int):
    """(r, 128) with equal lanes -> (r, w)."""
    reps = -(-w // 128)
    if reps > 1:
        x = jnp.concatenate([x] * reps, axis=1)
    return x[:, :w]


def _extended(v_ref, j: int, dv: int, dt):
    """V' = [V | 0 | 1] (T, 2 dh) of the block's group ``j``."""
    v = v_ref[0, :, j * dv:(j + 1) * dv].astype(F32)
    t, dh = v.shape[0], _value_width(dv)
    parts = [v, jnp.zeros((t, dh - dv), F32)] if dh > dv else [v]
    return jnp.concatenate(parts + [jnp.ones((t, dh), F32)],
                           axis=1).astype(dt)


def _head_rows(ref, j: int, h: int, dv: int):
    """Group ``j``'s heads of a token-major (1, T, gb h dv) block,
    stacked as (h T, dv) rows, f32."""
    x = ref[0]
    return jnp.concatenate(
        [x[:, (j * h + i) * dv:(j * h + i + 1) * dv] for i in range(h)],
        axis=0).astype(F32)


def _den_cols(den_ref, j: int, h: int, t: int):
    """Group ``j``'s per-row denominators, a (1, gb, h, T) block, as
    (h T, 128) with each row's value in every lane."""
    return jnp.concatenate(
        [jnp.broadcast_to(den_ref[0, j, i:i + 1, :], (128, t)).T
         for i in range(h)], axis=0)


def _cotangent(g_ref, o_ref, den_ref, j: int, h: int, t: int, dv: int,
               eps: float):
    """G' (h T, 2 dh) f32 of group ``j``: the cotangent of [num | den]
    (module doc)."""
    dh = _value_width(dv)
    g = _head_rows(g_ref, j, h, dv)
    o = _head_rows(o_ref, j, h, dv)
    rden = 1.0 / (_den_cols(den_ref, j, h, t) + eps)         # (h T, 128)
    dden = -jnp.sum(g * o, axis=1, keepdims=True) * rden * (1.0 / dh)
    parts = [g * _lanes(rden, dv)]
    if dh > dv:
        parts.append(jnp.zeros((h * t, dh - dv), F32))
    parts.append(_lanes(dden, dh))
    return jnp.concatenate(parts, axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, den_ref, sx_ref, *,
                eps: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        sx_ref[...] = jnp.zeros_like(sx_ref)

    _, gb, h, t, m = q_ref.shape
    dv = v_ref.shape[-1] // gb
    dh = _value_width(dv)
    outs = []
    for j in range(gb):
        q = q_ref[0, j].reshape(h * t, m)
        k, sx = k_ref[0, j], sx_ref[j]
        vx = _extended(v_ref, j, dv, q.dtype)
        a = _causal(_dot(q, k, 1, 1), h, t).astype(q.dtype)
        nx = _dot(q, sx.astype(q.dtype), 1, 0) + _dot(a, vx, 1, 0)
        den = pltpu.roll(nx, dh, 1)          # [den | num]: den in lane 0
        out = nx[:, :dv] / (den[:, :dv] + eps)
        outs += [out[i * t:(i + 1) * t] for i in range(h)]
        for i in range(h):
            den_ref[0, j, i:i + 1, :] = den[i * t:(i + 1) * t, :128].T[:1]
        sx_ref[j] = sx + _dot(k, vx, 0, 0)
    o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _dq_kernel(k_ref, v_ref, g_ref, o_ref, den_ref, dq_ref, sx_ref, *,
               eps: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        sx_ref[...] = jnp.zeros_like(sx_ref)

    _, gb, h, t, m = dq_ref.shape
    dv = v_ref.shape[-1] // gb
    for j in range(gb):
        k, sx = k_ref[0, j], sx_ref[j]
        vx = _extended(v_ref, j, dv, k.dtype)
        gx = _cotangent(g_ref, o_ref, den_ref, j, h, t, dv,
                        eps).astype(k.dtype)
        p = _causal(_dot(gx, vx, 1, 1), h, t).astype(k.dtype)
        dq = _dot(gx, sx.astype(k.dtype), 1, 1) + _dot(p, k, 1, 0)
        dq_ref[0, j] = dq.reshape(h, t, m).astype(dq_ref.dtype)
        sx_ref[j] = sx + _dot(k, vx, 0, 0)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, den_ref, dk_ref,
                dv_ref, dsx_ref, *, eps: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dsx_ref[...] = jnp.zeros_like(dsx_ref)

    _, gb, h, t, m = q_ref.shape
    dv = v_ref.shape[-1] // gb
    dvs = []
    for j in range(gb):
        q = q_ref[0, j].reshape(h * t, m)
        k, dsx = k_ref[0, j], dsx_ref[j]
        vx = _extended(v_ref, j, dv, q.dtype)
        gx = _cotangent(g_ref, o_ref, den_ref, j, h, t, dv,
                        eps).astype(q.dtype)
        a = _causal(_dot(q, k, 1, 1), h, t).astype(q.dtype)
        p = _causal(_dot(gx, vx, 1, 1), h, t).astype(q.dtype)
        dsx_c = dsx.astype(q.dtype)
        dk_ref[0, j] = (_dot(p, q, 0, 0) + _dot(vx, dsx_c, 1, 1)).astype(
            dk_ref.dtype)
        dvx = _dot(a, gx, 0, 0) + _dot(k, dsx_c, 1, 0)
        dvs.append(dvx[:, :dv])
        dsx_ref[j] = dsx + _dot(q, gx, 0, 0)
    dv_ref[0] = jnp.concatenate(dvs, axis=1).astype(dv_ref.dtype)


def _grid(q: Array, dv: int, block: int, reverse: bool = False):
    """(grid, specs by operand, T, padded L) of the pair for features
    ``q`` (B, G, H, L, m) and values dv wide; ``reverse`` walks the
    chunks from the last."""
    b, g, h, l, m = q.shape
    gb = _group_block(g, h, dv)
    t, lp = _tile(l, block)
    nc = lp // t

    def at(c):
        return nc - 1 - c if reverse else c
    specs = {
        "q": pl.BlockSpec((1, gb, h, t, m),
                          lambda i, j, c: (i, j, 0, at(c), 0)),
        "k": pl.BlockSpec((1, gb, t, m), lambda i, j, c: (i, j, at(c), 0)),
        "v": pl.BlockSpec((1, t, gb * dv), lambda i, j, c: (i, at(c), j)),
        "o": pl.BlockSpec((1, t, gb * h * dv),
                          lambda i, j, c: (i, at(c), j)),
        "den": pl.BlockSpec((1, gb, h, t),
                            lambda i, j, c: (i, j, 0, at(c))),
    }
    return (b, g // gb, nc), specs, t, lp


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def prf_mix_fwd(q: Array, k: Array, v: Array, *, eps: float = 1e-6,
                block: int = 128,
                interpret: bool = False) -> tuple[Array, Array]:
    """Causal mix of the KV groups of each batch row.

    q: (B, G, H, L, m) features of the H query heads of each group;
    k: (B, G, L, m) the group's key features; v: (B, L, G dv) values,
    token-major. Returns (out (B, L, G H dv) in v.dtype, token-major,
    den (B, G, H, L) f32), the denominators without ``eps``.
    """
    b, g, h, l, m = q.shape
    dv = v.shape[-1] // g
    grid, s, t, lp = _grid(q, dv, block)
    q, k = _pad_len(q, lp, 3), _pad_len(k, lp, 2)
    v = _pad_len(v, lp, 1)
    out, den = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[s["q"], s["k"], s["v"]],
        out_specs=(s["o"], s["den"]),
        out_shape=(jax.ShapeDtypeStruct((b, lp, g * h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, g, h, lp), F32)),
        scratch_shapes=[pltpu.VMEM((g // grid[1], m, 2 * _value_width(dv)),
                                   F32)],
        compiler_params=_params(), interpret=interpret,
        name="prf_mix_fwd",
    )(q, k, v)
    return out[:, :l], den[..., :l]


def prf_mix_bwd(q: Array, k: Array, v: Array, g: Array, out: Array,
                den: Array, *, eps: float = 1e-6, block: int = 128,
                interpret: bool = False
                ) -> tuple[Array, Array, Array]:
    """Gradients of ``prf_mix_fwd``'s ``out`` with respect to q, k and
    v, given its cotangent ``g`` (token-major, like ``out``) and the
    forward's ``out`` and ``den``. Returns f32 (dq, dk, dv) in the
    inputs' shapes; dk and dv are summed over each group's heads."""
    b, ng, h, l, m = q.shape
    dv = v.shape[-1] // ng
    grid, fwd, t, lp = _grid(q, dv, block)
    _, rev, _, _ = _grid(q, dv, block, reverse=True)
    q, den = _pad_len(q, lp, 3), _pad_len(den, lp, 3)
    k = _pad_len(k, lp, 2)
    v, g, out = (_pad_len(x, lp, 1) for x in (v, g, out))
    e, gb = 2 * _value_width(dv), ng // grid[1]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, eps=eps),
        grid=grid,
        in_specs=[fwd["k"], fwd["v"], fwd["o"], fwd["o"], fwd["den"]],
        out_specs=fwd["q"],
        out_shape=jax.ShapeDtypeStruct((b, ng, h, lp, m), F32),
        scratch_shapes=[pltpu.VMEM((gb, m, e), F32)],
        compiler_params=_params(), interpret=interpret,
        name="prf_mix_bwd_dq",
    )(k, v, g, out, den)
    dk, dvv = pl.pallas_call(
        functools.partial(_dkv_kernel, eps=eps),
        grid=grid,
        in_specs=[rev["q"], rev["k"], rev["v"], rev["o"], rev["o"],
                  rev["den"]],
        out_specs=(rev["k"], rev["v"]),
        out_shape=(jax.ShapeDtypeStruct((b, ng, lp, m), F32),
                   jax.ShapeDtypeStruct((b, lp, ng * dv), F32)),
        scratch_shapes=[pltpu.VMEM((gb, m, e), F32)],
        compiler_params=_params(), interpret=interpret,
        name="prf_mix_bwd_dkv",
    )(q, k, v, g, out, den)
    return dq[:, :, :, :l], dk[:, :, :l], dvv[:, :l]


def _kernel_carry(q_ref, k_ref, v_ref, s0_ref, z0_ref,
                  o_ref, so_ref, zo_ref, s_ref, z_ref, *, eps: float):
    """One (batch, group, head) row of the scan per grid row, seeded
    from (and emitting) the prefix state — the chunked-prefill resume
    point of docs/serving.md."""
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
