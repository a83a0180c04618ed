"""The paper's attention mechanism as one composable entry point.

``rf_attention`` dispatches on FeatureConfig.kind:

  exact       -> softmax attention (optionally sliding-window)
  performer   -> isotropic PRF linear attention (Choromanski 2021)
  darkformer  -> data-aware PRF linear attention (this paper)
  lfk         -> learned-feature-kernel linear attention (paper baseline)
  random      -> fixed random attention weights (paper baseline)
  constant    -> uniform attention (paper baseline)

plus the serving variants (prefill / decode).

Layout: q is (B, G, Hg, L, d) — G KV groups (GQA), Hg query heads per
group; k, v are (B, G, 1, L, d). Feature params are per group:
{"w": (G, m, r), "m_mat": (G, r, d)}.

Numerical-stability contract for PRFs (exp of raw logits):
  * q features: any per-(b,g,h,position) scale cancels in num/den — we use a
    per-(b,g,h) max.
  * k features: the scale must be CONSTANT ACROSS POSITIONS to preserve the
    relative weights. Training/prefill uses one max over (L, m); decode
    carries a running max ``c`` in the state and rescales (S, z) by
    exp(c_old - c_new) when a new key exceeds it — the linear-attention
    analogue of online-softmax rescaling.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import feature_maps as fm
from repro.core import linear_attention as la
from repro.core import scopes
# module-level: the wrappers resolve interpret-vs-TPU once; importing
# inside the hot functions re-ran the import machinery on every trace
from repro.kernels import ops as kops

Array = jax.Array

# feature kinds with a decode-time PRF state (and hence a fused path)
PRF_KINDS = fm.PRF_KINDS


def _scale_qk(q: Array, k: Array, multiplier: Optional[float] = None
              ) -> tuple[Array, Array]:
    """Absorb the 1/sqrt(d) softmax temperature symmetrically (paper fn. 2),
    or a model's own logit scale ``multiplier`` in its place."""
    d = q.shape[-1]
    s = d ** -0.25 if multiplier is None else multiplier ** 0.5
    return q * s, k * s


def _raw_logits(x: Array, fparams: dict, kind: str) -> Array:
    """PRF pre-exp logits: w.x - ||x||^2/2 (iso/lfk) or w.(Mx) - ||Mx||^2/2.

    x: (B, G, H, L, d) -> (B, G, H, L, m), f32.

    Trainability contract (paper §6): the projection W is a FIXED random
    draw for performer and darkformer (stop-gradient); only the LFK
    baseline trains W directly, and only darkformer trains M (= the
    learned covariance Sigma = M^T M).
    """
    w = fparams["w"].astype(jnp.float32)              # (G, m, r)
    if kind != "lfk":
        w = jax.lax.stop_gradient(w)
    x = x.astype(jnp.float32)
    if kind == "darkformer":
        m_mat = fparams["m_mat"].astype(jnp.float32)  # (G, r, d)
        x = jnp.einsum("bghld,grd->bghlr", x, m_mat)
    elif kind not in ("performer", "lfk"):
        raise ValueError(f"unsupported feature kind {kind!r}")
    return (jnp.einsum("bghlr,gmr->bghlm", x, w)
            - 0.5 * jnp.sum(jnp.square(x), axis=-1, keepdims=True))


def _stab_max(raw: Array, enabled: bool) -> Array:
    if not enabled:
        return jnp.zeros(raw.shape[:-2] + (1, 1), raw.dtype)
    return jax.lax.stop_gradient(
        jnp.max(raw, axis=(-2, -1), keepdims=True))


def _qk_feature_pair(q, k, fparams, cfg: fm.FeatureConfig):
    """q:(B,G,Hg,L,d), k:(B,G,1,L,d) -> qf:(B,G,Hg,L,m), kf:(B,G,1,L,m)."""
    with jax.named_scope(scopes.PRF_FEATURES):
        inv_sqrt_m = cfg.num_features ** -0.5
        qraw = _raw_logits(q, fparams, cfg.kind)
        kraw = _raw_logits(k, fparams, cfg.kind)
        qf = jnp.exp(qraw - _stab_max(qraw, cfg.stabilize)) * inv_sqrt_m
        kc = _stab_max(kraw, cfg.stabilize)
        kf = jnp.exp(kraw - kc) * inv_sqrt_m
    return qf, kf, kc


def _resume_qk_features(qs, ks, fparams, cfg: fm.FeatureConfig, c_in,
                        valid_mask: Optional[Array] = None):
    """Feature pair against the RUNNING k-stabilizer carried in ``c_in``
    (see module docstring): the new max folds the incoming one, and the
    carried (S, z) must be scaled by ``rescale = exp(c_in - c_new)``.
    The shared core of one-token decode and resumed chunk prefill.

    ``valid_mask`` ((B, 1, 1, L, 1) bool, or None for all-valid) marks
    ragged-row padding: masked positions contribute nothing to the
    stabilizer maxes and get zero q- and k-features, so a padded row's
    state advances exactly as its unpadded (B=1) counterpart would, and
    a row with no valid position (its q max is -inf) stays finite.
    Returns (qf, kf, c_new, rescale)."""
    with jax.named_scope(scopes.PRF_FEATURES):
        inv_sqrt_m = cfg.num_features ** -0.5
        qraw = _raw_logits(qs, fparams, cfg.kind)
        kraw = _raw_logits(ks, fparams, cfg.kind)
        if valid_mask is not None:
            neg = jnp.finfo(jnp.float32).min
            qraw_m = jnp.where(valid_mask, qraw, neg)
            kraw_m = jnp.where(valid_mask, kraw, neg)
        else:
            qraw_m, kraw_m = qraw, kraw
        qf = jnp.exp(qraw - _stab_max(qraw_m, cfg.stabilize)) * inv_sqrt_m
        if cfg.stabilize:
            c_new = jnp.maximum(c_in, _stab_max(kraw_m, True))
        else:
            # unstabilized features carry c == 0 (the init state's -inf
            # sentinel only ever zeroes an all-zero fresh state)
            c_new = jnp.zeros_like(c_in)
        rescale = jnp.exp(c_in - c_new)                    # <= 1
        kf = jnp.exp(kraw - c_new) * inv_sqrt_m
        if valid_mask is not None:
            qf = jnp.where(valid_mask, qf, 0.0)
            kf = jnp.where(valid_mask, kf, 0.0)
    return qf, kf, c_new, rescale


def rf_attention(q: Array, k: Array, v: Array, fparams: Optional[dict],
                 cfg: fm.FeatureConfig, *, causal: bool = True,
                 window: Optional[int] = None, chunk: int = 256,
                 use_kernel: bool = False,
                 baseline_key: Optional[Array] = None,
                 attention_multiplier: Optional[float] = None) -> Array:
    """Training-time attention. Returns (B, G, Hg, L, dv).

    The causal PRF mix takes the Pallas pair (KV groups unbroadcast, a
    backward of its own) wherever ``kops.train_mix_kernel`` says: on
    one TPU device always, off the TPU when ``use_kernel`` asks;
    elsewhere ``linear_attention_causal_blockwise``, its oracle.
    """
    b, g, hg, l, _ = q.shape
    dv = v.shape[-1]
    if cfg.kind == "exact":
        qs, ks = _scale_qk(q, k, attention_multiplier)
        return la.exact_attention(qs, ks, v, causal=causal, window=window)
    if cfg.kind == "constant":
        out = la.constant_attention(v, causal=causal)
        return jnp.broadcast_to(out, (b, g, hg, l, dv))
    if cfg.kind == "random":
        assert baseline_key is not None, "random baseline needs a key"
        out = la.random_attention(baseline_key, v, causal=causal)
        return jnp.broadcast_to(out, (b, g, hg, l, dv))

    with jax.named_scope(scopes.PRF_FEATURES):
        qs, ks = _scale_qk(q, k, attention_multiplier)
    qf, kf, _ = _qk_feature_pair(qs, ks, fparams, cfg)
    with jax.named_scope(scopes.PRF_MIX):
        if causal and kops.train_mix_kernel(use_kernel):
            return kops.linear_attention_causal(qf, kf, v, eps=cfg.eps)
        kf = jnp.broadcast_to(kf, (b, g, hg, l, cfg.num_features))
        vv = jnp.broadcast_to(v, (b, g, hg, l, dv))
        if not causal:
            return la.linear_attention_noncausal(qf, kf, vv, eps=cfg.eps)
        return la.linear_attention_causal_blockwise(qf, kf, vv,
                                                    chunk=chunk,
                                                    eps=cfg.eps)


class AttnServeState(NamedTuple):
    """Serving state.

    exact  — KV cache (B, G, Lmax, d) + write index. ``length`` is ()
             int32 when the whole batch decodes in lock-step, or (B,)
             int32 for per-slot lengths (continuous batching: each slot
             owns one page of the cache and writes at its own index).
    paged  — ``table`` set selects block-granular paging: ``kv_k`` /
             ``kv_v`` become SHARED page pools (n_pages, page_size, G,
             d) and ``table`` (B, max_pages) maps each row's logical
             page j to a physical pool page (page 0 is the reserved
             garbage page that masked/inactive writes land on). Rows
             can then share physical prefix pages copy-on-write — the
             prefix-cache fork path (repro/serving/prefix_cache.py).
    linear — running (S, z) plus the running k-stabilizer ``c``. All
             leaves carry a leading batch axis, so the state doubles as
             a slot pool: slot i lives at batch row i of every leaf.
    """
    kv_k: Optional[Array] = None
    kv_v: Optional[Array] = None
    length: Optional[Array] = None          # () or (B,) int32
    s: Optional[Array] = None               # (B, G, Hg, m, dv) f32
    z: Optional[Array] = None               # (B, G, Hg, m)     f32
    c: Optional[Array] = None               # (B, G, 1, 1, 1)   f32
    table: Optional[Array] = None           # (B, max_pages)    int32


def _exact_prefill_resume(qs, ks, v, state: AttnServeState,
                          window: Optional[int], out_dtype,
                          valid_len: Optional[Array] = None):
    """Append an l-token chunk to the exact KV cache and attend the chunk
    queries over the whole valid prefix. ``state.length`` is () or (B,)
    — the multi-token generalization of ``_exact_decode``.

    ``valid_len`` ((B,) int32, requires a (B,) ``length``) marks ragged
    rows: row b appends only its first ``valid_len[b]`` keys/values and
    advances its write index by ``valid_len[b]`` — the padded positions
    of a batched multi-admission prefill chunk leave no trace. The
    ragged write is a masked gather-scatter, NOT a dynamic slice: a
    padded chunk near the end of a page can have ``idx + l > lmax``,
    and dynamic_update_slice would clamp the start and shift every
    valid write."""
    l = qs.shape[-2]
    idx = state.length
    if valid_len is not None:
        # per-cache-position source index into the chunk; positions in
        # [idx, idx + valid_len) take chunk token (pos - idx), the rest
        # keep the old page contents
        lmax = state.kv_k.shape[2]
        kpos = jnp.arange(lmax)
        rel = kpos[None] - idx[:, None]                  # (B, lmax)
        keep = (rel >= 0) & (rel < valid_len[:, None])
        relc = jnp.clip(rel, 0, l - 1)[:, None, :, None]
        knew = jnp.take_along_axis(
            ks[:, :, 0], jnp.broadcast_to(relc, ks[:, :, 0].shape[:2]
                                          + (lmax, ks.shape[-1])), axis=2)
        vnew = jnp.take_along_axis(
            v[:, :, 0], jnp.broadcast_to(relc, v[:, :, 0].shape[:2]
                                         + (lmax, v.shape[-1])), axis=2)
        km = keep[:, None, :, None]
        kc = jnp.where(km, knew, state.kv_k)
        vc = jnp.where(km, vnew, state.kv_v)
        qpos_b = idx[:, None] + jnp.arange(l)[None]      # (B, l)
    elif idx.ndim == 0:
        kc = jax.lax.dynamic_update_slice_in_dim(
            state.kv_k, ks[:, :, 0], idx, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(
            state.kv_v, v[:, :, 0], idx, axis=2)
        qpos = idx + jnp.arange(l)                       # (l,) absolute
        qpos_b = qpos[None]                              # (1, l)
    else:
        write = jax.vmap(
            lambda cache, new, i: jax.lax.dynamic_update_slice_in_dim(
                cache, new, i, axis=1))
        kc = write(state.kv_k, ks[:, :, 0], idx)
        vc = write(state.kv_v, v[:, :, 0], idx)
        qpos_b = idx[:, None] + jnp.arange(l)[None]      # (B, l)
    lmax = kc.shape[2]
    kpos = jnp.arange(lmax)
    valid = kpos[None, None, :] <= qpos_b[:, :, None]    # (B|1, l, lmax)
    if window is not None:
        valid &= kpos[None, None, :] > qpos_b[:, :, None] - window
    vmask = valid[:, None, None]                         # (B|1,1,1,l,lmax)
    logits = jnp.einsum("bghqd,bgkd->bghqk", qs, kc).astype(jnp.float32)
    logits = jnp.where(vmask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bghqk,bgkd->bghqd", probs, vc).astype(out_dtype)
    adv = l if valid_len is None else valid_len
    return out, state._replace(kv_k=kc, kv_v=vc, length=idx + adv)


def _exact_paged_append_attend(qs, ks, v, state: AttnServeState,
                               window: Optional[int], out_dtype,
                               valid_len: Optional[Array] = None):
    """Paged-KV generalization of :func:`_exact_prefill_resume`.

    Token t of row b lands at flat pool position
    ``table[b, (length[b]+t) // ps] * ps + (length[b]+t) % ps``; masked
    (padded) positions are routed to the reserved garbage page 0, so a
    ragged batched chunk leaves no trace outside each row's own pages.
    Reads gather the row's whole table (max_pages * ps logical
    positions, unused ones masked to -inf) and apply the same
    prefix-masked softmax as the contiguous path — paged and contiguous
    streams agree to f32 rounding under identical chunk schedules, and
    paged-vs-paged is bitwise (only the physical page ids differ, which
    the gather erases). Decode is the l=1 case.

    Because rows only ever append at their own length, a physical page
    that is FULLY covered by some row's committed prefix is append-only
    immutable — which is what lets the prefix cache share prefix pages
    across forked rows and copy only the partial tail page
    (copy-on-write at fork, repro/serving/prefix_cache.py).
    """
    b, g, hg, l, dh = qs.shape
    npg, ps, gk, dhk = state.kv_k.shape
    mp = state.table.shape[1]
    idx = state.length                                   # (B,)
    pos = idx[:, None] + jnp.arange(l)[None]             # (B, l) absolute
    logical = jnp.minimum(pos // ps, mp - 1)
    phys = jnp.take_along_axis(state.table, logical, axis=1)
    flat = phys * ps + pos % ps                          # (B, l) pool pos
    if valid_len is not None:
        keep = jnp.arange(l)[None] < valid_len[:, None]
        flat = jnp.where(keep, flat, 0)                  # garbage page 0
    kf = state.kv_k.reshape(npg * ps, gk, dhk)
    vf = state.kv_v.reshape(npg * ps, gk, dhk)
    knew = jnp.moveaxis(ks[:, :, 0], 1, 2).reshape(b * l, gk, dhk)
    vnew = jnp.moveaxis(v[:, :, 0], 1, 2).reshape(b * l, gk, -1)
    kf = kf.at[flat.reshape(-1)].set(knew.astype(kf.dtype))
    vf = vf.at[flat.reshape(-1)].set(vnew.astype(vf.dtype))
    # gather each row's paged prefix back as a logically-contiguous view
    gidx = (state.table[:, :, None] * ps
            + jnp.arange(ps)[None, None]).reshape(b, mp * ps)
    kc = jnp.moveaxis(kf[gidx], 1, 2)                    # (B, G, Lc, dh)
    vc = jnp.moveaxis(vf[gidx], 1, 2)
    kpos = jnp.arange(mp * ps)
    valid = kpos[None, None, :] <= pos[:, :, None]       # (B, l, Lc)
    if window is not None:
        valid &= kpos[None, None, :] > pos[:, :, None] - window
    vmask = valid[:, None, None]                         # (B,1,1,l,Lc)
    logits = jnp.einsum("bghqd,bgkd->bghqk", qs, kc).astype(jnp.float32)
    logits = jnp.where(vmask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bghqk,bgkd->bghqd", probs, vc).astype(out_dtype)
    adv = l if valid_len is None else valid_len
    return out, state._replace(kv_k=kf.reshape(npg, ps, gk, dhk),
                               kv_v=vf.reshape(npg, ps, gk, dhk),
                               length=idx + adv)


def rf_attention_prefill(q, k, v, fparams, cfg: fm.FeatureConfig, *,
                         window: Optional[int] = None, chunk: int = 256,
                         max_len: Optional[int] = None,
                         use_kernel: bool = False,
                         state: Optional[AttnServeState] = None,
                         valid_len: Optional[Array] = None,
                         proj: Optional[dict] = None,
                         attention_multiplier: Optional[float] = None):
    """Causal pass over a prompt (chunk) + advanced serving state.

    ``state=None`` is the legacy whole-prompt entry point: the serving
    state is built from scratch and the k-stabilizer is one max over the
    whole prompt. With an incoming ``state`` the pass *resumes*: the
    chunk attends to the carried prefix, and the stabilizer becomes a
    running max with an online exp(c_old - c_new) rescale of (S, z) —
    the multi-token generalization of ``rf_attention_decode``, so a
    prompt split into chunks reproduces the whole-prompt pass to f32
    rounding (bit-exact only when the whole prompt is one chunk from a
    fresh state, which fixes the stabilizer trajectory).

    ``valid_len`` ((B,) int32, resume-only) makes the chunk ragged: row b
    advances over its first ``valid_len[b]`` positions only; padded
    positions contribute nothing to the state (masked k-features / masked
    cache writes). Outputs at padded positions are garbage by contract —
    callers gather per-row at ``valid_len - 1``.

    With ``use_kernel`` a resumed PRF chunk runs through Pallas — fully
    fused when ``proj`` carries the precomposed projection
    (``fm.precompose_projection``): ONE ``prf_fused_prefill`` megakernel
    per layer per packed chunk does projection, feature map, in-kernel
    running-max rescale, ragged ``valid_len`` masking, the causal
    carried-state scan and the (S, z, c) advance, aliasing the state in
    place. Without ``proj`` the two-stage path (jnp
    ``_resume_qk_features`` + the ``linear_attn_scan`` carry kernel) is
    kept as the oracle.
    """
    b, g, hg, l, _ = q.shape
    dv = v.shape[-1]
    if valid_len is not None and state is None:
        raise ValueError("valid_len requires an incoming serve state "
                         "(ragged rows only arise in resumed chunks)")
    if cfg.kind == "exact":
        qs, ks = _scale_qk(q, k, attention_multiplier)
        if state is not None:
            if state.table is not None:
                return _exact_paged_append_attend(qs, ks, v, state, window,
                                                  v.dtype,
                                                  valid_len=valid_len)
            return _exact_prefill_resume(qs, ks, v, state, window, v.dtype,
                                         valid_len=valid_len)
        out = la.exact_attention(qs, ks, v, causal=True, window=window)
        lmax = max_len or l
        kc = jnp.pad(ks[:, :, 0], ((0, 0), (0, 0), (0, lmax - l), (0, 0)))
        vc = jnp.pad(v[:, :, 0], ((0, 0), (0, 0), (0, lmax - l), (0, 0)))
        state = AttnServeState(kv_k=kc, kv_v=vc,
                               length=jnp.full((), l, jnp.int32))
        return out, state

    qs, ks = _scale_qk(q, k, attention_multiplier)
    if state is None:
        qf, kf, kc = _qk_feature_pair(qs, ks, fparams, cfg)
        kfb = jnp.broadcast_to(kf, (b, g, hg, l, cfg.num_features))
        vv = jnp.broadcast_to(v, (b, g, hg, l, dv))
        if use_kernel:
            out = kops.linear_attention_causal(qf, kfb, vv, eps=cfg.eps)
        else:
            out = la.linear_attention_causal_chunked(qf, kfb, vv,
                                                     chunk=chunk,
                                                     eps=cfg.eps)
        s = jnp.einsum("bghlm,bghld->bghmd", kfb.astype(jnp.float32),
                       vv.astype(jnp.float32))
        z = jnp.sum(kfb.astype(jnp.float32), axis=-2)
        return out, AttnServeState(s=s, z=z, c=kc)

    # resume: fused megakernel when the precomposed projection is in
    # hand — raw q/k go straight in, valid_len masked in-kernel, state
    # aliased in place (docs/kernels.md §Fused prefill).
    if use_kernel and proj is not None and cfg.kind in PRF_KINDS:
        out, s, z, c = kops.fused_prf_prefill(
            qs, ks[:, :, 0], v[:, :, 0], proj["a"], proj.get("m_mat"),
            state.s, state.z, state.c[:, :, 0, 0, 0], valid_len,
            stabilize=cfg.stabilize, eps=cfg.eps, chunk=chunk)
        return (out.astype(v.dtype),
                state._replace(s=s, z=z, c=c[:, :, None, None, None]))
    # resume: online rescale of the k stabilizer, then the carried-state
    # chunked scan.
    vmask = (None if valid_len is None else
             (jnp.arange(l)[None] < valid_len[:, None])
             .reshape(b, 1, 1, l, 1))
    qf, kf, c_new, rescale = _resume_qk_features(qs, ks, fparams, cfg,
                                                 state.c, valid_mask=vmask)
    kfb = jnp.broadcast_to(kf, (b, g, hg, l, cfg.num_features))
    vv = jnp.broadcast_to(v, (b, g, hg, l, dv))
    s0 = state.s * rescale
    z0 = state.z * rescale[..., 0]
    if use_kernel:
        out, s, z = kops.linear_attention_prefill_chunk(
            qf, kfb, vv, s0, z0, chunk=chunk, eps=cfg.eps)
    else:
        out, s, z = la.linear_attention_causal_carry(
            qf, kfb, vv, s0, z0, chunk=chunk, eps=cfg.eps)
    return out, AttnServeState(s=s, z=z, c=c_new)


def init_linear_serve_state(b, g, hg, m, dv) -> AttnServeState:
    return AttnServeState(
        s=jnp.zeros((b, g, hg, m, dv), jnp.float32),
        z=jnp.zeros((b, g, hg, m), jnp.float32),
        c=jnp.full((b, g, 1, 1, 1), -1e30, jnp.float32))


def _exact_decode(qs, ks, v, state: AttnServeState,
                  window: Optional[int], out_dtype):
    """Exact-attention decode step with a () or (B,) write index.

    With a (B,) ``length`` every batch row (= serving slot) appends its
    key/value at its own position and masks its own valid prefix — the
    per-slot page write of the continuous-batching engine. Exactly the
    l=1 case of the resumable prefill chunk, so there is one copy of the
    cache-write + prefix-mask + masked-softmax contract.
    """
    return _exact_prefill_resume(qs, ks, v, state, window, out_dtype)


def rf_attention_decode(q, k, v, state: AttnServeState, fparams,
                        cfg: fm.FeatureConfig, *,
                        window: Optional[int] = None,
                        use_kernel: bool = False,
                        proj: Optional[dict] = None,
                        attention_multiplier: Optional[float] = None):
    """One-token decode. q: (B,G,Hg,1,d); k,v: (B,G,1,1,d).

    ``state.length`` (exact) may be () for lock-step batches or (B,) for
    per-slot decode; the linear state is per-slot by construction. With
    ``use_kernel`` the linear path runs through Pallas — fully fused
    when ``proj`` carries the precomposed projection
    (``fm.precompose_projection``): ONE ``prf_fused_decode`` megakernel
    does projection, feature map, in-kernel stabilizer rescale, (S, z)
    update and readout with the state aliased in place. Without
    ``proj`` the legacy two-stage path (jnp ``_resume_qk_features`` +
    ``prf_decode_step``) is kept as the oracle.
    """
    b, g, hg, _, _ = q.shape
    dv = v.shape[-1]
    if cfg.kind == "exact":
        qs, ks = _scale_qk(q, k, attention_multiplier)
        if state.table is not None:
            return _exact_paged_append_attend(qs, ks, v, state, window,
                                              v.dtype)
        return _exact_decode(qs, ks, v, state, window, v.dtype)

    qs, ks = _scale_qk(q, k, attention_multiplier)
    if use_kernel and proj is not None and cfg.kind in PRF_KINDS:
        out, s, z, c = kops.fused_prf_decode(
            qs[..., 0, :], ks[:, :, 0, 0, :], v[:, :, 0, 0, :],
            proj["a"], proj.get("m_mat"), state.s, state.z,
            state.c[:, :, 0, 0, 0], stabilize=cfg.stabilize,
            eps=cfg.eps)
        return (out.astype(v.dtype)[..., None, :],
                state._replace(s=s, z=z, c=c[:, :, None, None, None]))
    # Online rescale of the k stabilizer — shared with the resumed
    # prefill chunk (decode is its one-token case).
    qf, kf, c_new, rescale = _resume_qk_features(qs, ks, fparams, cfg,
                                                 state.c)
    kfb = jnp.broadcast_to(kf[:, :, :, 0], (b, g, hg, cfg.num_features))
    vv = jnp.broadcast_to(v[:, :, :, 0], (b, g, hg, dv))
    qf1 = qf[..., 0, :]                            # (B,G,Hg,m)
    if use_kernel:
        out, s, z = kops.linear_attention_decode_step(
            qf1, kfb, vv.astype(jnp.float32), state.s, state.z,
            rescale[..., 0, 0], eps=cfg.eps)
        return (out.astype(v.dtype)[..., None, :],
                state._replace(s=s, z=z, c=c_new))
    s = state.s * rescale + (
        kfb[..., :, None] * vv[..., None, :].astype(jnp.float32))
    z = state.z * rescale[..., 0] + kfb
    num = jnp.einsum("bghm,bghmd->bghd", qf1, s)
    den = jnp.einsum("bghm,bghm->bgh", qf1, z)
    out = (num / (den[..., None] + cfg.eps)).astype(v.dtype)
    return out[..., None, :], state._replace(s=s, z=z, c=c_new)
