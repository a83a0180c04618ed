"""The composable LM stack: config, init, train/prefill/decode entrypoints.

Supports heterogeneous block patterns (dense attention, sliding-window
attention, RG-LRU, RWKV-6), GQA, MoE FFNs, qk-norm, RoPE, tied heads,
text/audio/VLM modalities — enough to express all 10 assigned architectures
plus the paper's Gemma-style model, with the paper's RF attention selectable
per config (FeatureConfig.kind).

Layer stacking: the block pattern repeats over the depth; full repetitions
are stacked and executed with jax.lax.scan (keeps HLO size and compile time
independent of depth — essential for the 512-device dry-run), any remainder
layers run unscanned. Each scanned unit is wrapped in jax.checkpoint with a
configurable remat policy.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import feature_maps as fm
from repro.core import scopes
from repro.models import layers as ll
from repro.models import attention_block as ab
from repro.models import recurrent as rec

Array = jax.Array

_CP = jax.checkpoint_policies
REMAT_POLICIES = {
    "none": None,
    "full": _CP.nothing_saveable,
    # matmuls without batch dimensions, and what the expert layer names
    # (its grouped matmuls are not dot_generals)
    "dots": _CP.save_from_both_policies(
        _CP.dots_with_no_batch_dims_saveable,
        _CP.save_only_these_names(ll.MOE_SAVED)),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    d_head: int = 0                       # 0 -> d_model // n_heads
    block_pattern: tuple = ("attn",)      # cycled: attn|local|rec|rwkv
    attn: fm.FeatureConfig = fm.FeatureConfig(kind="darkformer")
    window: Optional[int] = None          # for "local" blocks
    rope_theta: float = 10000.0           # <=0 disables RoPE
    qk_norm: bool = False
    mlp_kind: str = "swiglu"              # swiglu|geglu|gelu
    moe: Optional[ll.MoEConfig] = None
    tie_embeddings: bool = True
    causal: bool = True
    modality: str = "text"                # text|audio|vlm
    norm_kind: str = "rmsnorm"
    d_rnn: int = 0                        # rec blocks; 0 -> d_model
    logit_softcap: float = 0.0
    num_patches: int = 256                # vlm prefix length
    dtype: str = "float32"                # param/activation dtype
    remat: str = "dots"                   # key of REMAT_POLICIES
    scan_layers: bool = True
    use_kernel: bool = False              # pallas linear-attention path
    z_loss: float = 1e-4
    # Scalar multipliers; None emits no op. The embedding is scaled by
    # the first (Gemma's sqrt(d_model), Granite's 12), each residual
    # branch by the second; under PRF attention q and k are each scaled
    # by the square root of the third (the logits' scale, in place of
    # 1/sqrt(d)); the logits are divided by the last.
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None
    # Per-arch sharding-rule overrides: ((path-regex, partition-spec-tuple),
    # ...) applied before the global rules in repro.parallel.sharding.
    # Sharding is geometry-dependent; archs whose dims interact badly with
    # the global rules pin their layout here.
    sharding_overrides: tuple = ()

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def rnn_width(self) -> int:
        return self.d_rnn or self.d_model

    @property
    def param_dtype(self):
        return jnp.dtype(self.dtype)

    def layer_kinds(self) -> list[str]:
        p = self.block_pattern
        return [p[i % len(p)] for i in range(self.n_layers)]

    @property
    def n_units(self) -> int:
        return self.n_layers // len(self.block_pattern)

    @property
    def n_rem(self) -> int:
        return self.n_layers % len(self.block_pattern)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelConfig, kind: str) -> dict:
    dt = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": ll.norm_init(cfg.norm_kind, cfg.d_model, dt),
                         "ln2": ll.norm_init(cfg.norm_kind, cfg.d_model, dt)}
    if kind in ("attn", "local"):
        p["attn"] = ab.attn_init(k1, cfg.d_model, cfg.n_heads, cfg.n_kv,
                                 cfg.head_dim, cfg.attn, cfg.qk_norm, dt)
        p["ffn"] = (ll.moe_init(k2, cfg.d_model, cfg.moe, dt)
                    if cfg.moe else
                    ll.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dt))
    elif kind == "rec":
        p["rec"] = rec.rglru_init(k1, cfg.d_model, cfg.rnn_width, dt)
        p["ffn"] = (ll.moe_init(k2, cfg.d_model, cfg.moe, dt)
                    if cfg.moe else
                    ll.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_kind, dt))
    elif kind == "rwkv":
        p["tmix"] = rec.rwkv6_init(k1, cfg.d_model, cfg.n_heads, dtype=dt)
        p["cmix"] = rec.rwkv6_channel_mix_init(k2, cfg.d_model, cfg.d_ff, dt)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return p


def _unit_init(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(cfg.block_pattern))
    return {f"b{i}": _block_init(keys[i], cfg, kind)
            for i, kind in enumerate(cfg.block_pattern)}


def init_params(key, cfg: ModelConfig) -> dict:
    dt = cfg.param_dtype
    ke, ku, kr, kh, kp = jax.random.split(key, 5)
    params: dict[str, Any] = {
        "embed": ll.trunc_normal(ke, (cfg.vocab, cfg.d_model), 1.0, dt),
        "final_norm": ll.norm_init(cfg.norm_kind, cfg.d_model, dt),
    }
    if cfg.n_units > 0:
        unit_keys = jax.random.split(ku, cfg.n_units)
        params["units"] = jax.vmap(
            lambda k: _unit_init(k, cfg))(unit_keys)
    if cfg.n_rem:
        rem_keys = jax.random.split(kr, cfg.n_rem)
        params["rem"] = [
            _block_init(rem_keys[i], cfg,
                        cfg.block_pattern[i % len(cfg.block_pattern)])
            for i in range(cfg.n_rem)]
    if not cfg.tie_embeddings:
        params["lm_head"] = ll.trunc_normal(kh, (cfg.d_model, cfg.vocab),
                                            1.0, dt)
    if cfg.modality == "audio":
        params["mask_embed"] = ll.trunc_normal(kp, (cfg.d_model,), 1.0, dt)
    return params


# ---------------------------------------------------------------------------
# Block application (train / prefill: full-sequence)
# ---------------------------------------------------------------------------

def _apply_block(params, x, cfg: ModelConfig, kind: str, *,
                 layer_key: Optional[Array], state=None, mode="train",
                 position=None, valid_len=None, proj=None):
    """Returns (x, aux_loss, new_state).

    ``valid_len`` ((B,) int32, prefill mode only) marks ragged rows of a
    padded multi-admission chunk; every stateful mixer masks its carry so
    padded positions leave no trace (see the per-mixer docstrings).
    ``proj`` (prefill / decode modes) is the block's precomposed serve
    projection selecting the fused megakernel path under
    ``cfg.use_kernel`` (prefill: ``prf_fused_prefill``; decode:
    ``prf_fused_decode``).
    """
    aux = zero_aux(cfg)
    # the attention's pre-norm is counted with its projections
    with (jax.named_scope(scopes.ATTN_IN) if kind in ("attn", "local")
          else contextlib.nullcontext()):
        h = ll.apply_norm(cfg.norm_kind, params["ln1"], x)
    new_state = state
    common = dict(n_heads=cfg.n_heads, n_kv=cfg.n_kv, d_head=cfg.head_dim,
                  qk_norm=cfg.qk_norm, rope_theta=cfg.rope_theta,
                  attention_multiplier=cfg.attention_multiplier)
    window = cfg.window if kind == "local" else None
    if kind in ("attn", "local"):
        if mode == "train":
            mix = ab.attn_apply(params["attn"], h, cfg.attn, causal=cfg.causal,
                                window=window, use_kernel=cfg.use_kernel,
                                baseline_key=layer_key, **common)
        elif mode == "prefill":
            # state is the block's incoming serve state; position the
            # chunk's start offset — prefill is a resumable multi-token
            # step, exactly parallel to decode.
            mix, new_state = ab.attn_prefill(
                params["attn"], h, cfg.attn, window=window,
                state=state, position=position, valid_len=valid_len,
                use_kernel=cfg.use_kernel, proj=proj, **common)
        else:  # decode
            mix, new_state = ab.attn_decode(
                params["attn"], h, state, cfg.attn, position=position,
                window=window, use_kernel=cfg.use_kernel, proj=proj,
                **common)
        with jax.named_scope(scopes.ATTN_OUT):
            x = _residual(x, mix, cfg)
        x, aux = _ffn(params["ln2"], params["ffn"], x, aux, cfg)
    elif kind == "rec":
        if mode == "train":
            mix, _ = rec.rglru_apply(params["rec"], h, None)
        else:                       # prefill chunk / decode: carry state
            mix, new_state = rec.rglru_apply(params["rec"], h, state,
                                             valid_len=valid_len)
        x = _residual(x, mix, cfg)
        x, aux = _ffn(params["ln2"], params["ffn"], x, aux, cfg)
    elif kind == "rwkv":
        if mode == "train":
            mix, _ = rec.rwkv6_apply(params["tmix"], h, cfg.n_heads, None)
            x = _residual(x, mix, cfg)
            h2 = ll.apply_norm(cfg.norm_kind, params["ln2"], x)
            f, _ = rec.rwkv6_channel_mix(params["cmix"], h2, None)
            x = _residual(x, f, cfg)
        else:                       # prefill chunk / decode: carry state
            tstate, cshift = state
            mix, tstate = rec.rwkv6_apply(params["tmix"], h, cfg.n_heads,
                                          tstate, valid_len=valid_len)
            x = _residual(x, mix, cfg)
            h2 = ll.apply_norm(cfg.norm_kind, params["ln2"], x)
            f, cshift = rec.rwkv6_channel_mix(params["cmix"], h2, cshift,
                                              valid_len=valid_len)
            x = _residual(x, f, cfg)
            new_state = (tstate, cshift)
    return x, aux, new_state


def zero_aux(cfg: ModelConfig) -> dict:
    """A block's auxiliaries, zero: the loss it adds (``loss``) and, with
    experts, the routing counters summed over layers (``moe_load``, the
    (token, choice) pairs each held expert took; ``moe_away``, the pairs
    routed to experts held elsewhere)."""
    aux = {"loss": jnp.zeros((), jnp.float32)}
    if cfg.moe:
        aux["moe_load"] = jnp.zeros((cfg.moe.n_held,), jnp.int32)
        aux["moe_away"] = jnp.zeros((), jnp.int32)
    return aux


def _add_aux(a: dict, b: dict) -> dict:
    return jax.tree_util.tree_map(jnp.add, a, b)


def _residual(x, branch, cfg: ModelConfig):
    if cfg.residual_multiplier is not None:
        # in float32: the multiplier itself is not a bfloat16 number
        branch = (branch.astype(jnp.float32) * cfg.residual_multiplier
                  ).astype(branch.dtype)
    return x + branch


def _ffn(norm, ffn, x, aux, cfg: ModelConfig):
    """Pre-norm FFN (dense or experts) plus its residual. Returns (x,
    aux), with the experts' loss and counters in place of ``aux``'s."""
    with jax.named_scope(scopes.MLP):
        h = ll.apply_norm(cfg.norm_kind, norm, x)
        if cfg.moe:
            f, loss, counters = ll.moe_apply(ffn, h, cfg.moe)
            aux = {"loss": loss, "moe_load": counters["load"],
                   "moe_away": counters["away"]}
        else:
            f = ll.mlp_apply(ffn, h, cfg.mlp_kind)
        return _residual(x, f, cfg), aux


def _embed_inputs(params, cfg: ModelConfig, batch: dict) -> Array:
    with jax.named_scope(scopes.EMBED):
        dt = cfg.param_dtype
        if cfg.modality == "audio":
            x = batch["frames"].astype(dt)
            if "mask" in batch:
                me = params["mask_embed"].astype(dt)
                x = jnp.where(batch["mask"][..., None], me[None, None], x)
            return x
        tok = _scale_embedding(params["embed"][batch["tokens"]], cfg)
        if cfg.modality == "vlm":
            patches = batch["patch_embeds"].astype(dt)
            return jnp.concatenate([patches, tok.astype(dt)], axis=1)
        return tok.astype(dt)


def _scale_embedding(tok: Array, cfg: ModelConfig) -> Array:
    if cfg.embedding_multiplier is not None:
        tok = tok * jnp.asarray(cfg.embedding_multiplier, tok.dtype)
    return tok


def _logits(params, cfg: ModelConfig, x: Array) -> Array:
    with jax.named_scope(scopes.LM_HEAD):
        x = ll.apply_norm(cfg.norm_kind, params["final_norm"], x)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        if cfg.logits_scaling is not None:
            logits = logits / cfg.logits_scaling
        if cfg.logit_softcap > 0:
            c = cfg.logit_softcap
            logits = c * jnp.tanh(logits / c)
        return logits


def forward_train(params, cfg: ModelConfig, batch: dict,
                  rng: Optional[Array] = None) -> tuple[Array, dict]:
    """Full forward. Returns (logits (B, L, V), aux): ``zero_aux``'s
    tree summed over the layers."""
    x = _embed_inputs(params, cfg, batch)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    aux_total = zero_aux(cfg)

    def unit_body(x, xs):
        unit_params, uidx = xs
        aux_u = zero_aux(cfg)
        for i, kind in enumerate(cfg.block_pattern):
            lk = jax.random.fold_in(rng, uidx * 16 + i)
            x, aux, _ = _apply_block(unit_params[f"b{i}"], x, cfg, kind,
                                     layer_key=lk, mode="train")
            aux_u = _add_aux(aux_u, aux)
        return x, aux_u

    if cfg.n_units > 0:
        body = unit_body
        policy = REMAT_POLICIES[cfg.remat]
        if policy is not None:
            body = jax.checkpoint(unit_body, policy=policy,
                                  prevent_cse=not cfg.scan_layers)
        if cfg.scan_layers:
            x, auxs = jax.lax.scan(
                body, x, (params["units"], jnp.arange(cfg.n_units)))
            aux_total = _add_aux(aux_total, jax.tree_util.tree_map(
                lambda a: jnp.sum(a, axis=0), auxs))
        else:
            units = params["units"]
            for u in range(cfg.n_units):
                up = jax.tree_util.tree_map(lambda a: a[u], units)
                x, aux_u = body(x, (up, jnp.asarray(u)))
                aux_total = _add_aux(aux_total, aux_u)
    for i in range(cfg.n_rem):
        kind = cfg.block_pattern[i % len(cfg.block_pattern)]
        lk = jax.random.fold_in(rng, 10_000 + i)
        x, aux, _ = _apply_block(params["rem"][i], x, cfg, kind,
                                 layer_key=lk, mode="train")
        aux_total = _add_aux(aux_total, aux)
    return _logits(params, cfg, x), aux_total


def collect_qk(params, cfg: ModelConfig, batch: dict) -> dict:
    """Run the stack and capture post-RoPE q/k of every attention block.

    Calibration tap for the whitening init (App. C): returns
    {"unit<u>/b<i>": (q, k)} with q: (B, G, Hg, L, dh), k: (B, G, 1, L, dh).
    Runs the layer loop in Python (no scan) — intended for the reduced /
    bench-scale models used in calibration passes.
    """
    x = _embed_inputs(params, cfg, batch)
    taps: dict = {}
    kinds = cfg.layer_kinds()
    plen = len(cfg.block_pattern)

    def get_block_params(li: int):
        u, i = divmod(li, plen)
        if u < cfg.n_units:
            return jax.tree_util.tree_map(lambda a: a[u],
                                          params["units"])[f"b{i}"], u, i
        return params["rem"][li - cfg.n_units * plen], u, i

    for li, kind in enumerate(kinds):
        bp, u, i = get_block_params(li)
        if kind in ("attn", "local"):
            h = ll.apply_norm(cfg.norm_kind, bp["ln1"], x)
            q, k, _ = ab._project(bp["attn"], h, cfg.n_heads, cfg.n_kv,
                                  cfg.head_dim, cfg.qk_norm,
                                  jnp.arange(h.shape[1]), cfg.rope_theta)
            taps[f"unit{u}/b{i}"] = (q, k)
        x, _, _ = _apply_block(bp, x, cfg, kind,
                               layer_key=jax.random.PRNGKey(li),
                               mode="train")
    return taps


def whitening_calibrate(params, cfg: ModelConfig, batch: dict,
                        shrink: float = 0.05):
    """Set every darkformer m_mat to Lambda^{-1/2} from a calibration batch
    (scaled q/k statistics; the d^{-1/4} temperature is absorbed so the
    covariance matches what the feature map actually sees)."""
    from repro.core import calibration as cal
    if cfg.attn.kind != "darkformer":
        return params
    taps = collect_qk(params, cfg, batch)
    scale = (cfg.head_dim ** -0.25 if cfg.attention_multiplier is None
             else cfg.attention_multiplier ** 0.5)
    new = jax.tree_util.tree_map(lambda a: a, params)
    plen = len(cfg.block_pattern)
    for name, (q, k) in taps.items():
        u = int(name.split("/")[0][4:])
        bi = name.split("/")[1]
        if u < cfg.n_units:
            fp = new["units"][bi]["attn"]["feat"]
        else:
            fp = new["rem"][u * plen + int(bi[1:])
                            - cfg.n_units * plen]["attn"]["feat"]
        g = fp["m_mat"].shape[-3] if fp["m_mat"].ndim > 2 else \
            fp["m_mat"].shape[0]
        r = fp["m_mat"].shape[-2]
        mats = []
        for gi in range(q.shape[1]):
            mats.append(cal.whiten_m_from_qk(
                q[:, gi] * scale, k[:, gi] * scale, r, shrink))
        m_new = jnp.stack(mats)
        if fp["m_mat"].ndim > 2 and u < cfg.n_units:
            fp["m_mat"] = fp["m_mat"].at[u].set(
                m_new.astype(fp["m_mat"].dtype))
        else:
            fp["m_mat"] = m_new.astype(fp["m_mat"].dtype)
    return new


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch: dict,
            rng: Optional[Array] = None) -> tuple[Array, dict]:
    logits, aux_tree = forward_train(params, cfg, batch, rng)
    aux = aux_tree["loss"]
    with jax.named_scope(scopes.LOSS):
        labels = batch["labels"]
        if cfg.modality == "vlm":
            logits = logits[:, -labels.shape[1]:]    # loss on text positions
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll_tok = jnp.take_along_axis(logits, labels[..., None],
                                     axis=-1)[..., 0] - logz
        if cfg.modality == "audio" and "mask" in batch:
            wmask = batch["mask"].astype(jnp.float32)
        else:
            wmask = (labels >= 0).astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(wmask), 1.0)
        ce = -jnp.sum(ll_tok * wmask) / denom
        zl = cfg.z_loss * jnp.sum(jnp.square(logz) * wmask) / denom
        loss = ce + zl + aux
        acc = jnp.sum((jnp.argmax(logits, -1) == labels) * wmask) / denom
    metrics = {"loss": loss, "ce": ce, "z_loss": zl, "aux": aux,
               "accuracy": acc}
    if cfg.moe:
        metrics["moe_load"] = aux_tree["moe_load"]
        metrics["moe_away"] = aux_tree["moe_away"]
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
#
# Layer-stacked serving layout: a HOMOGENEOUS block pattern (every layer
# the same kind — the darkformer/performer/exact/rwkv configs) collapses
# the per-unit {"b0", "b1", ...} trees into ONE tree whose leaves carry a
# leading (n_layers,) axis, and the jitted serving steps lax.scan a
# single compiled layer body over it. One executable regardless of
# depth: compile time and per-token dispatch overhead stop scaling with
# L. Heterogeneous patterns (recurrentgemma's ("rec","rec","local"))
# keep the per-unit scan with the pattern unrolled inside the body.
# A stacked serve state holds the layer tree under state["layers"]
# instead of state["units"]/state["rem"] (the slot axis moves to 1 for
# every layer leaf — repro/serving/slots.py and
# repro.parallel.serve_state_specs understand both layouts).


def can_stack_layers(cfg: ModelConfig) -> bool:
    """True when every layer is the same block kind (and scanned), so
    serving states and params can stack along one leading layer axis."""
    return (cfg.scan_layers and cfg.n_units > 0 and cfg.n_rem == 0
            and len(set(cfg.block_pattern)) == 1)


def stack_layer_params(params: dict, cfg: ModelConfig) -> dict:
    """One block tree with leaves (n_layers, ...): layer u*k + i is
    pattern position i of unit u. For the common k = 1 patterns this is
    just ``params["units"]["b0"]`` — no copy. For k > 1 the interleave
    materializes a stacked copy, so engines stack ONCE at build and
    pass it back through ``params["layers"]`` (the serving steps prefer
    that key over re-stacking per call)."""
    units = params["units"]
    k = len(cfg.block_pattern)
    if k == 1:
        return units["b0"]

    def interleave(*leaves):
        st = jnp.stack(leaves, axis=1)             # (U, k, ...)
        return st.reshape((-1,) + st.shape[2:])
    return jax.tree_util.tree_map(
        interleave, *[units[f"b{i}"] for i in range(k)])


def build_decode_proj(params: dict, cfg: ModelConfig,
                      stacked: bool = False) -> Optional[dict]:
    """Precompose every attention layer's serve projection A = (W M)^T
    (``fm.precompose_projection``) — ONCE, at engine build, so the fused
    decode AND prefill megakernels never re-derive it per step. Returns
    a pytree mirroring the serve-state layout ({"layers": ...} when
    ``stacked``, else {"units": {"b<i>": ...}, "rem": [...]} with None
    at non-PRF blocks), or None when the config has no fused path.

    ``decode_step`` / ``prefill_chunk`` build this on the fly when not
    given one (inside the caller's jit — same composition, bit-identical
    A), so engines that precompute and engines that don't agree exactly.
    """
    if not (cfg.use_kernel and cfg.attn.kind in fm.PRF_KINDS):
        return None
    if not any(k in ("attn", "local") for k in cfg.layer_kinds()):
        return None
    if stacked:
        sp = (params["layers"] if "layers" in params
              else stack_layer_params(params, cfg))
        return {"layers": fm.precompose_projection(sp["attn"]["feat"],
                                                   cfg.attn.kind)}
    proj: dict[str, Any] = {}
    if cfg.n_units > 0:
        proj["units"] = {
            f"b{i}": (fm.precompose_projection(
                params["units"][f"b{i}"]["attn"]["feat"], cfg.attn.kind)
                if kind in ("attn", "local") else None)
            for i, kind in enumerate(cfg.block_pattern)}
    if cfg.n_rem:
        proj["rem"] = [
            (fm.precompose_projection(params["rem"][i]["attn"]["feat"],
                                      cfg.attn.kind)
             if cfg.block_pattern[i % len(cfg.block_pattern)]
             in ("attn", "local") else None)
            for i in range(cfg.n_rem)]
    return proj


def _init_block_state(cfg: ModelConfig, kind: str, b: int, max_len: int,
                      per_slot: bool = False):
    if kind in ("attn", "local"):
        return ab.init_attn_serve_state(
            cfg.attn, b, cfg.n_heads, cfg.n_kv, cfg.head_dim, max_len,
            cfg.window if kind == "local" else None, per_slot=per_slot)
    if kind == "rec":
        return rec.init_rglru_state(b, cfg.rnn_width)
    if kind == "rwkv":
        return (rec.init_rwkv_state(b, cfg.d_model, cfg.n_heads),
                jnp.zeros((b, cfg.d_model), jnp.float32))
    raise ValueError(kind)


def init_serve_state(cfg: ModelConfig, b: int, max_len: int,
                     per_slot: bool = False,
                     stacked: bool = False) -> dict:
    """Initial serving state for a batch of b sequences.

    ``per_slot`` turns the state into a continuous-batching slot pool:
    ``pos`` (and the exact-attention cache lengths) become (b,) vectors so
    every batch row advances independently (see repro.serving).

    ``stacked`` (requires :func:`can_stack_layers`) lays the per-layer
    states along ONE leading (n_layers,) axis under ``state["layers"]``
    so the serving steps scan a single layer body — the engine's layout
    for homogeneous configs.
    """
    state: dict[str, Any] = {}
    if stacked:
        if not can_stack_layers(cfg):
            raise ValueError(
                f"{cfg.name}: stacked serve states need a homogeneous "
                f"scanned block pattern (got {cfg.block_pattern}, "
                f"n_rem={cfg.n_rem}, scan_layers={cfg.scan_layers})")
        kind0 = cfg.block_pattern[0]
        state["layers"] = jax.vmap(
            lambda _: _init_block_state(cfg, kind0, b, max_len,
                                        per_slot))(
            jnp.arange(cfg.n_layers))
        state["pos"] = jnp.zeros((b,) if per_slot else (), jnp.int32)
        return state
    if cfg.n_units > 0:
        def one_unit(_):
            return {f"b{i}": _init_block_state(cfg, kind, b, max_len,
                                               per_slot)
                    for i, kind in enumerate(cfg.block_pattern)}
        state["units"] = jax.vmap(one_unit)(jnp.arange(cfg.n_units))
    if cfg.n_rem:
        state["rem"] = [
            _init_block_state(
                cfg, cfg.block_pattern[i % len(cfg.block_pattern)], b,
                max_len, per_slot)
            for i in range(cfg.n_rem)]
    state["pos"] = jnp.zeros((b,) if per_slot else (), jnp.int32)
    return state


def init_paged_serve_state(cfg: ModelConfig, b: int, max_len: int,
                           page_size: int) -> dict:
    """Slot pool for the block-granular paged exact-KV layout.

    Exact + layer-stacked only: each row carries a (max_pages,) page
    table and a write index per layer; the shared page pools come from
    :func:`init_kv_pages` and are attached around each jitted step
    (:func:`attach_kv_pages`). Slot ops see only the detached tree (the
    None kv leaves are skipped), so admission/commit/freeze scatter
    tables and lengths — never pages: forking a cached prefix into N
    rows copies page IDS, not keys/values
    (repro/serving/prefix_cache.py)."""
    if cfg.attn.kind != "exact" or not can_stack_layers(cfg):
        raise ValueError(
            f"{cfg.name}: paged KV serve states need an exact-attention "
            f"layer-stacked config (kind={cfg.attn.kind}, "
            f"stackable={can_stack_layers(cfg)})")
    max_pages = -(-max_len // page_size)
    state = {"layers": jax.vmap(
        lambda _: ab.init_paged_attn_state(b, max_pages))(
        jnp.arange(cfg.n_layers)),
        "pos": jnp.zeros((b,), jnp.int32)}
    return state


def init_kv_pages(cfg: ModelConfig, n_pages: int, page_size: int) -> dict:
    """Shared per-layer exact-KV page pools: {"k", "v"} each
    (n_layers, n_pages, page_size, G, d_head). Page 0 is the reserved
    garbage page masked/inactive writes are routed to."""
    shape = (cfg.n_layers, n_pages, page_size, cfg.n_kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32)}


def attach_kv_pages(state: dict, pages: dict) -> dict:
    """Graft the shared page pools into a detached paged serve state so
    ``decode_step`` / ``prefill_chunk`` can run it: the per-layer scan
    slices pages along the leading layer axis exactly like every other
    state leaf."""
    return {**state,
            "layers": state["layers"]._replace(kv_k=pages["k"],
                                               kv_v=pages["v"])}


def detach_kv_pages(state: dict) -> tuple[dict, dict]:
    """Inverse of :func:`attach_kv_pages`: split an advanced state back
    into (detached slot-pool tree, updated page pools)."""
    la = state["layers"]
    pages = {"k": la.kv_k, "v": la.kv_v}
    return ({**state, "layers": la._replace(kv_k=None, kv_v=None)},
            pages)


def prefill_chunk(params, cfg: ModelConfig, batch: dict, state: dict,
                  valid_len: Optional[Array] = None,
                  proj: Optional[dict] = None,
                  fused: bool = True) -> tuple[Array, dict]:
    """Advance a serve state over one prompt chunk.

    ``state`` is a serve state from :func:`init_serve_state` (fresh) or a
    previous ``prefill_chunk`` call — its ``pos`` (() or (B,) int32) is
    the chunk's start offset, threaded to every layer (RoPE rotations,
    exact-cache write indices, recurrent carries). Returns
    (last-position logits (B, V), advanced state). This is the resume
    point the chunked-prefill scheduler interleaves with decode steps
    (repro/serving/engine.py); whole-prompt :func:`prefill` is the
    degenerate one-chunk schedule.

    ``valid_len`` ((B,) int32) makes the chunk *ragged*: row b consumes
    only its first ``valid_len[b]`` tokens — the rest are padding that
    leaves no trace in the advanced state (masked PRF (S, z) updates,
    per-row exact-cache append lengths, masked RG-LRU/RWKV carries), and
    the returned logits are gathered at each row's last valid position.
    This is what lets the serving engine pad several staged admissions'
    chunks into ONE batched (B, L) call. A chunk whose rows are ALL full
    should pass ``valid_len=None``: the masked path is mathematically the
    identity then, but XLA may fuse it differently (f32-close, not
    bitwise) — the engine does exactly this for its exactness contract.

    With ``cfg.use_kernel`` and a PRF kind the chunk runs the fused
    ``prf_fused_prefill`` megakernel — ONE pallas_call per layer per
    packed chunk, valid_len masked in-kernel, (S, z, c) aliased in
    place. ``proj`` is the precomposed per-layer projection pytree
    (:func:`build_decode_proj`) — pass the engine-built one to keep the
    M·Wᵀ composition off the per-chunk path, or leave None to compose
    inside the call (bit-identical output). ``fused=False`` forces the
    legacy two-stage path (jnp featmap + carry-scan kernel — the oracle
    the megakernel is tested against).
    """
    x = _embed_inputs(params, cfg, batch)
    pos = state["pos"]
    adv = x.shape[1] if valid_len is None else valid_len
    new_state: dict[str, Any] = {"pos": pos + adv}
    if proj is None and fused:
        proj = build_decode_proj(params, cfg, stacked="layers" in state)
    elif not fused:
        proj = None

    if "layers" in state:                  # layer-stacked homogeneous
        kind0 = cfg.block_pattern[0]
        sp = (params["layers"] if "layers" in params
              else stack_layer_params(params, cfg))
        proj_l = None if proj is None else proj["layers"]

        def layer_body(x, xs):
            layer_params, layer_state, layer_proj = xs
            x, _, st = _apply_block(layer_params, x, cfg, kind0,
                                    layer_key=None, state=layer_state,
                                    mode="prefill", position=pos,
                                    valid_len=valid_len, proj=layer_proj)
            return x, st

        x, layer_states = jax.lax.scan(layer_body, x,
                                       (sp, state["layers"], proj_l))
        new_state["layers"] = layer_states
        if valid_len is None:
            x_last = x[:, -1:]
        else:
            x_last = jnp.take_along_axis(
                x, jnp.maximum(valid_len - 1, 0)[:, None, None], axis=1)
        return _logits(params, cfg, x_last)[:, 0], new_state

    proj_units = (proj or {}).get("units") or \
        {f"b{i}": None for i in range(len(cfg.block_pattern))}

    def unit_body(x, xs):
        unit_params, unit_state, unit_proj = xs
        new_states = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, _, st = _apply_block(unit_params[f"b{i}"], x, cfg, kind,
                                    layer_key=None,
                                    state=unit_state[f"b{i}"],
                                    mode="prefill", position=pos,
                                    valid_len=valid_len,
                                    proj=unit_proj[f"b{i}"])
            new_states[f"b{i}"] = st
        return x, new_states

    if cfg.n_units > 0:
        if cfg.scan_layers:
            x, unit_states = jax.lax.scan(
                unit_body, x, (params["units"], state["units"],
                               proj_units))
            new_state["units"] = unit_states
        else:
            per_unit = []
            for u in range(cfg.n_units):
                sl = jax.tree_util.tree_map(lambda a: a[u],
                                            (params["units"],
                                             state["units"],
                                             proj_units))
                x, st_u = unit_body(x, sl)
                per_unit.append(st_u)
            new_state["units"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_unit)
    if cfg.n_rem:
        rem_proj = (proj or {}).get("rem") or [None] * cfg.n_rem
        new_state["rem"] = []
        for i in range(cfg.n_rem):
            kind = cfg.block_pattern[i % len(cfg.block_pattern)]
            x, _, st = _apply_block(params["rem"][i], x, cfg, kind,
                                    layer_key=None, state=state["rem"][i],
                                    mode="prefill", position=pos,
                                    valid_len=valid_len,
                                    proj=rem_proj[i])
            new_state["rem"].append(st)
    if valid_len is None:
        x_last = x[:, -1:]
    else:                          # per-row last-valid-token gather
        x_last = jnp.take_along_axis(
            x, jnp.maximum(valid_len - 1, 0)[:, None, None], axis=1)
    return _logits(params, cfg, x_last)[:, 0], new_state


def prefill(params, cfg: ModelConfig, batch: dict, max_len: int
            ) -> tuple[Array, dict]:
    """Full-prompt pass; returns ((B, 1, V) last logits, serve state).

    One whole-prompt ``prefill_chunk`` from a fresh serve state — the
    degenerate chunking schedule, so chunked and blocking admission share
    a single mechanism.
    """
    b = (batch["frames"] if cfg.modality == "audio"
         else batch["tokens"]).shape[0]
    state = init_serve_state(cfg, b=b, max_len=max_len)
    logits, state = prefill_chunk(params, cfg, batch, state)
    return logits[:, None], state


def decode_step(params, cfg: ModelConfig, token: Array, state: dict,
                proj: Optional[dict] = None, fused: bool = True
                ) -> tuple[Array, dict]:
    """One serving step. token: (B,) int32 -> (logits (B, V), new state).

    With ``cfg.use_kernel`` and a PRF kind, decode runs the fused
    megakernel; ``proj`` is the precomposed per-layer projection pytree
    (:func:`build_decode_proj`) — pass the engine-built one to keep the
    M·Wᵀ composition off the per-token path, or leave None to compose
    inside the step (bit-identical output). ``fused=False`` forces the
    legacy two-stage kernel path (the oracle the megakernel is tested
    against). A ``state`` from ``init_serve_state(stacked=True)`` runs
    one scanned layer body over the stacked layer axis.
    """
    pos = state["pos"]
    x = _scale_embedding(params["embed"][token][:, None], cfg)
    x = x.astype(cfg.param_dtype)
    new_state: dict[str, Any] = {"pos": pos + 1}
    if proj is None and fused:
        proj = build_decode_proj(params, cfg, stacked="layers" in state)
    elif not fused:
        proj = None

    if "layers" in state:                  # layer-stacked homogeneous
        kind0 = cfg.block_pattern[0]
        sp = (params["layers"] if "layers" in params
              else stack_layer_params(params, cfg))
        proj_l = None if proj is None else proj["layers"]

        def layer_body(x, xs):
            layer_params, layer_state, layer_proj = xs
            x, _, st = _apply_block(layer_params, x, cfg, kind0,
                                    layer_key=None, state=layer_state,
                                    mode="decode", position=pos,
                                    proj=layer_proj)
            return x, st

        x, layer_states = jax.lax.scan(
            layer_body, x, (sp, state["layers"], proj_l))
        new_state["layers"] = layer_states
        return _logits(params, cfg, x)[:, 0], new_state

    proj_units = (proj or {}).get("units") or \
        {f"b{i}": None for i in range(len(cfg.block_pattern))}

    def unit_body(x, xs):
        unit_params, unit_state, unit_proj = xs
        new_states = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, _, st = _apply_block(unit_params[f"b{i}"], x, cfg, kind,
                                    layer_key=None,
                                    state=unit_state[f"b{i}"],
                                    mode="decode", position=pos,
                                    proj=unit_proj[f"b{i}"])
            new_states[f"b{i}"] = st
        return x, new_states

    if cfg.n_units > 0:
        if cfg.scan_layers:
            x, unit_states = jax.lax.scan(
                unit_body, x, (params["units"], state["units"],
                               proj_units))
            new_state["units"] = unit_states
        else:
            per_unit = []
            for u in range(cfg.n_units):
                sl = jax.tree_util.tree_map(lambda a: a[u],
                                            (params["units"],
                                             state["units"],
                                             proj_units))
                x, st_u = unit_body(x, sl)
                per_unit.append(st_u)
            new_state["units"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_unit)
    if cfg.n_rem:
        rem_proj = (proj or {}).get("rem") or [None] * cfg.n_rem
        new_state["rem"] = []
        for i in range(cfg.n_rem):
            kind = cfg.block_pattern[i % len(cfg.block_pattern)]
            x, _, st = _apply_block(params["rem"][i], x, cfg, kind,
                                    layer_key=None, state=state["rem"][i],
                                    mode="decode", position=pos,
                                    proj=rem_proj[i])
            new_state["rem"].append(st)
    return _logits(params, cfg, x)[:, 0], new_state
