"""Plain float32 reference of Granite 3.0's mixture-of-experts decoder
(``granitemoe``) under DARKFormer attention, on one chip's share of the
experts.

Written from the model's equations and nothing of the program. Each
layer: RMSNorm with a (1 + s) scale; GQA attention whose q and k are
rotated and each scaled by the square root of ``attention_multiplier``
(the logits' scale in place of 1/sqrt(d)), through positive random
features per KV group and causal linear attention (``reference.py``'s
``_causal_prf``); the residual branch times ``residual_multiplier``; a
router over all ``num_experts`` experts whose top k logits are
softmaxed into gates; each held expert's SwiGLU computed densely over
every token and weighted by the token's gate for it (0 where the token
was not routed there); the residual branch times ``residual_multiplier``
again. The embedding is scaled by ``embedding_multiplier``, and the tied
head's logits are divided by ``logits_scaling``. Every matmul runs in
float32 at the highest precision; ``quant="fp8"`` is the control, every
matmul in float8 e4m3 as in ``reference.py``.

The experts held elsewhere are left out, as in the program: with
``held`` (start, stop) the layer adds only those experts' part.

Departure from the published recipe, followed by the program too: the
router's auxiliary loss is the Switch formula, per layer
``coef * E * sum_e f_e * p_e`` with ``f_e`` the share of the batch's
tokens whose first choice is expert e and ``p_e`` the batch's mean
router probability of e, summed over layers. ``f`` is a count over the
whole batch, so a first forward pass over every row finds it; the
gradient then goes row by row with ``f`` fixed (it has none of its own).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import (F32, _adamw, _causal_prf, _mm, _rms,
                                 _rope, leaf_norms, lr_at)

# the update in place: the float32 state of a 466M-parameter model is
# 1.9 GB a copy, and the chip holds few
_adamw_inplace = jax.jit(_adamw.__wrapped__, donate_argnums=(0, 1, 2, 3))


def _route(h, router, dims, mm):
    """Router logits over all experts -> (gates (L, held), top-1 (L,),
    probabilities (L, E))."""
    logits = mm("ld,de->le", h, router)
    top, idx = jax.lax.top_k(logits, dims["top_k"])
    gates = jax.nn.softmax(top, axis=-1)
    lo, hi = dims["held"]
    e = jnp.arange(lo, hi)
    w = jnp.sum(jnp.where(idx[:, :, None] == e, gates[:, :, None], 0.0),
                axis=1)                                   # (L, held)
    return w, idx[:, 0], jax.nn.softmax(logits, axis=-1)


def _layer(x, lp, pos, dims, quant, top1_share):
    """One block. Returns (x, top-1 counts (E,), the aux loss's part
    from this row given the batch's ``top1_share`` (E,))."""
    mm = _mm(quant)
    H, G, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    Hg = H // G
    L = x.shape[0]
    eps = dims["rms_norm_eps"]
    res = dims["residual_multiplier"]
    qk = dims["attention_multiplier"] ** 0.5
    h = _rms(x, lp["ln1"]["scale"], eps)
    at = lp["attn"]
    q = mm("ld,de->le", h, at["wq"]).reshape(L, G * Hg, dh)
    k = mm("ld,de->le", h, at["wk"]).reshape(L, G, dh)
    v = mm("ld,de->le", h, at["wv"]).reshape(L, G, dh)
    q = (_rope(q, pos, dims["rope_theta"]) * qk).reshape(L, G, Hg, dh)
    k = _rope(k, pos, dims["rope_theta"]) * qk
    # W is a fixed draw (no gradient); M is learned
    w = jax.lax.stop_gradient(at["feat"]["w"].astype(F32))  # (G, m, r)
    M = at["feat"]["m_mat"].astype(F32)                  # (G, r, d)
    qt = mm("lghd,grd->lghr", q, M)
    kt = mm("lgd,grd->lgr", k, M)
    qraw = mm("lghr,gmr->lghm", qt, w) - 0.5 * jnp.sum(qt * qt, -1,
                                                        keepdims=True)
    kraw = mm("lgr,gmr->lgm", kt, w) - 0.5 * jnp.sum(kt * kt, -1,
                                                      keepdims=True)
    att = _causal_prf(qraw, kraw, v).reshape(L, H * dh)
    x = x + res * mm("le,ed->ld", att, at["wo"])
    h2 = _rms(x, lp["ln2"]["scale"], eps)
    f = lp["ffn"]
    gate_w, top1, probs = _route(h2, f["router"], dims, mm)
    g = mm("ld,edf->elf", h2, f["w_gate"])
    u = mm("ld,edf->elf", h2, f["w_up"])
    y = mm("elf,efd->eld", jax.nn.silu(g) * u, f["w_out"])
    x = x + res * mm("eld,le->ld", y, gate_w)
    counts = jnp.sum(jax.nn.one_hot(top1, dims["num_experts"]), axis=0)
    aux = dims["router_aux_loss_coef"] * dims["num_experts"] * jnp.sum(
        top1_share * jnp.sum(probs, axis=0))
    return x, counts, aux


def logits(weights, dims, tokens, top1_share, quant=None):
    """For one row of token ids (L,): the logits (L, V), per-layer top-1
    counts (layers, E), and the row's part of the aux loss (summed over
    layers, before the division by the batch's tokens)."""
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = weights["embed"][tokens].astype(F32) * dims["embedding_multiplier"]

    def body(x, xs):
        lp, share = xs
        x, counts, aux = _layer(x, lp, pos, dims, quant, share)
        return x, (counts, aux)

    x, (counts, aux) = jax.lax.scan(jax.checkpoint(body), x,
                                    (weights["units"]["b0"], top1_share))
    x = _rms(x, weights["final_norm"]["scale"], dims["rms_norm_eps"])
    lg = _mm(quant)("ld,vd->lv", x, weights["embed"])
    return lg / dims["logits_scaling"], counts, jnp.sum(aux)


def row_terms(weights, dims, row, top1_share, quant=None):
    """For one row of token ids (L + 1): the sums of cross-entropy and
    of logz^2 over its positions, with ``logits``' counts and aux."""
    tokens, labels = row[:-1], row[1:]
    lg, counts, aux = logits(weights, dims, tokens, top1_share, quant)
    logz = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0] - logz
    return -jnp.sum(ll), jnp.sum(logz * logz), counts, aux


@functools.partial(jax.jit, static_argnames=("dims_key", "quant"))
def _row_counts(w32, row, dims_key, quant):
    dims = dict(dims_key)
    share = jnp.zeros((dims["layers"], dims["num_experts"]), F32)
    return row_terms(w32, dims, row, share, quant)[2]


@functools.partial(jax.jit, static_argnames=("dims_key", "quant"))
def _row_grad(w32, row, share, scale, dims_key, quant):
    dims = dict(dims_key)

    def f(w):
        ce, lz, _, aux = row_terms(w, dims, row, share, quant)
        return (ce + dims["z_loss"] * lz + aux) * scale, (ce, lz, aux)

    (_, (ce, lz, aux)), g = jax.value_and_grad(f, has_aux=True)(w32)
    return ce, lz, aux, g


def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


_add_inplace = jax.jit(_add, donate_argnums=(0,))


def train_steps(weights, dims: dict, tcfg: dict, batches, quant=None
                ) -> dict:
    """Follow the trainer's first steps, as ``reference.train_steps``
    does (AdamW with global-norm clipping and decoupled weight decay,
    parameters stored back in their own dtypes), with the loss = mean
    cross-entropy + z_loss * mean(logz^2) + the router's aux loss. The
    first gradient and the starting weights are kept on the host.
    ``batches``: (steps, rows, seq + 1)."""
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, weights)
    # a copy: the update consumes it, and ``weights`` stays the caller's
    w32 = jax.tree_util.tree_map(lambda x: jnp.array(x, F32, copy=True),
                                 weights)
    w0 = jax.device_get(w32)
    mu = jax.tree_util.tree_map(jnp.zeros_like, w32)
    nu = jax.tree_util.tree_map(jnp.zeros_like, w32)
    count = jnp.zeros((), F32)
    hp = tuple(float(tcfg[k]) for k in ("b1", "b2", "eps",
                                         "weight_decay", "grad_clip"))
    dk = tuple(sorted(dims.items()))
    losses, first, first_vec = [], None, None
    for step in range(batches.shape[0]):
        rows = batches[step]
        n_tok = rows.shape[0] * (rows.shape[1] - 1)
        with jax.default_matmul_precision("highest"):
            share = sum(_row_counts(w32, jnp.asarray(r), dk, quant)
                        for r in rows) / n_tok
            grads = jax.tree_util.tree_map(jnp.zeros_like, w32)
            total = 0.0
            for r in rows:
                ce, lz, aux, g = _row_grad(w32, jnp.asarray(r), share,
                                           1.0 / n_tok, dk, quant)
                grads = _add_inplace(grads, g)
                del g
                total += (float(ce) + dims["z_loss"] * float(lz)
                          + float(aux))
            w32, mu, nu, count, clipped = _adamw_inplace(
                w32, grads, mu, nu, count, lr_at(step, tcfg), hp)
        del grads
        # stored back in the parameters' own dtypes, as the trainer does
        w32 = jax.tree_util.tree_map(
            lambda p, dt: p.astype(dt).astype(F32), w32, dtypes)
        losses.append(total / n_tok)
        if first is None:
            first, first_vec = leaf_norms(clipped), jax.device_get(clipped)
        del clipped
    del mu, nu
    change = leaf_norms(jax.tree_util.tree_map(
        lambda p, p0: p - jnp.asarray(p0), w32, w0))
    return {"loss": losses, "grad": first, "grad_vec": first_vec,
            "change": change}


def dims_of(cj: dict, base: dict) -> dict:
    """The reference's sizes: ``model.ref_dims`` of the configuration
    file (``base``) and its experts, share and multipliers."""
    return dict(base, layers=cj["num_hidden_layers"],
                num_experts=cj["router_experts"],
                top_k=cj["num_experts_per_tok"],
                held=tuple(cj["experts_held"]),
                router_aux_loss_coef=cj["router_aux_loss_coef"],
                embedding_multiplier=cj["embedding_multiplier"],
                residual_multiplier=cj["residual_multiplier"],
                attention_multiplier=cj["attention_multiplier"],
                logits_scaling=cj["logits_scaling"])

