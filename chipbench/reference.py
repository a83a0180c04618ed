"""Plain float32 reference of the trained model.

A llama-style decoder with DARKFormer attention, written from the
model's equations and nothing of the program: RMSNorm with a (1 + s)
scale, rotary embeddings on split halves, q and k scaled by d^-1/4,
positive random features phi(x) = exp(W M x - |M x|^2 / 2) / sqrt(m)
per KV group, causal linear attention, a SwiGLU MLP, and tied or untied
output heads. Every matmul runs in float32 at the highest precision.

The feature shifts cancel in the attention ratio, so the reference takes
them where they are safest: a per-position shift for queries and a
running (causal) maximum for keys, carried chunk by chunk with the exact
exp(c_old - c_new) rescale. No denominator floor is needed.

``quant="fp8"`` is the control: every matmul runs in float8 e4m3, the
step below the bfloat16 that the configuration states. Its operands
and, in the backward pass, the cotangent it takes are rounded with a
per-tensor scale; products accumulate in float32.

It reads the weights the benchmark made, by the names of the layout
they are stored in, and a dict of the model's sizes (``dims``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
CHUNK = 256


def _q8(x):
    """Round to float8 e4m3 with a per-tensor scale."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    """A matmul in float8, forward and backward: both operands, and the
    cotangent that the backward matmuls take, are rounded to e4m3 with
    a scale of their own, and the products accumulate in float32."""
    return _einsum(spec, _q8(a), _q8(b))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _q8(a), _q8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, ct):
    _, vjp = jax.vjp(functools.partial(_einsum, spec), *res)
    return vjp(_q8(ct))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def _mm(quant):
    def mm(spec, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if quant == "fp8":
            return _einsum_fp8(spec, a, b)
        return _einsum(spec, a, b)
    return mm


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, pos, theta):
    """x: (L, H, d); rotate split halves by position."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos[:, None, None].astype(F32) * freqs            # (L, 1, d/2)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _causal_prf(qraw, kraw, v):
    """qraw: (L, G, Hg, m), kraw: (L, G, m), v: (L, G, dv) -> (L, G, Hg, dv).

    Chunked causal linear attention; queries shifted by their own max,
    keys by the running max up to the end of each chunk."""
    L, G, Hg, m = qraw.shape
    dv = v.shape[-1]
    T = min(CHUNK, L)
    nc = L // T
    qf = jnp.exp(qraw - jnp.max(qraw, axis=-1, keepdims=True))
    qc = qf.reshape(nc, T, G, Hg, m)
    kc = kraw.reshape(nc, T, G, m)
    vc = v.reshape(nc, T, G, dv)
    tri = jnp.tril(jnp.ones((T, T), F32))

    def body(carry, xs):
        S, z, c = carry                     # (G, m, dv), (G, m), (G,)
        q, k, vv = xs
        c_new = jnp.maximum(c, jnp.max(k, axis=(0, 2)))
        rho = jnp.exp(c - c_new)
        kf = jnp.exp(k - c_new[None, :, None])           # (T, G, m)
        S = S * rho[:, None, None]
        z = z * rho[:, None]
        a = jnp.einsum("tghm,sgm->ghts", q, kf,
                       precision=HI) * tri               # (G, Hg, T, T)
        num = (jnp.einsum("tghm,gmd->tghd", q, S, precision=HI)
               + jnp.einsum("ghts,sgd->tghd", a, vv, precision=HI))
        den = (jnp.einsum("tghm,gm->tgh", q, z, precision=HI)
               + jnp.sum(a, axis=-1).transpose(2, 0, 1))
        out = num / jnp.maximum(den, 1e-37)[..., None]
        S = S + jnp.einsum("tgm,tgd->gmd", kf, vv, precision=HI)
        z = z + jnp.sum(kf, axis=0)
        return (S, z, c_new), out

    init = (jnp.zeros((G, m, dv), F32), jnp.zeros((G, m), F32),
            jnp.full((G,), -1e30, F32))
    _, out = jax.lax.scan(body, init, (qc, kc, vc))
    return out.reshape(L, G, Hg, dv)


def _layer(x, lp, pos, dims, quant):
    mm = _mm(quant)
    H, G, dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    Hg = H // G
    L = x.shape[0]
    eps = dims["rms_norm_eps"]
    h = _rms(x, lp["ln1"]["scale"], eps)
    at = lp["attn"]
    q = mm("ld,de->le", h, at["wq"]).reshape(L, G * Hg, dh)
    k = mm("ld,de->le", h, at["wk"]).reshape(L, G, dh)
    v = mm("ld,de->le", h, at["wv"]).reshape(L, G, dh)
    q = _rope(q, pos, dims["rope_theta"]) * dh ** -0.25
    k = _rope(k, pos, dims["rope_theta"]) * dh ** -0.25
    q = q.reshape(L, G, Hg, dh)
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
    x = x + mm("le,ed->ld", att, at["wo"])
    h2 = _rms(x, lp["ln2"]["scale"], eps)
    f = lp["ffn"]
    gate = mm("ld,df->lf", h2, f["w_gate"])
    up = mm("ld,df->lf", h2, f["w_up"])
    return x + mm("lf,fd->ld", jax.nn.silu(gate) * up, f["w_out"])


def hidden(weights, dims, tokens, quant=None):
    """Final-norm hidden states (L, d) for one row of token ids (L a
    multiple of the chunk, or shorter than it)."""
    L = tokens.shape[0]
    pos = jnp.arange(L)
    x = weights["embed"][tokens].astype(F32)
    layers = weights["units"]["b0"]

    def body(x, lp):
        return _layer(x, lp, pos, dims, quant), None

    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, layers)
    return _rms(x, weights["final_norm"]["scale"], dims["rms_norm_eps"])


def head(weights, dims):
    if dims["tie_word_embeddings"]:
        return weights["embed"].T
    return weights["lm_head"]


def logits(weights, dims, tokens, quant=None):
    return _mm(quant)("ld,dv->lv", hidden(weights, dims, tokens, quant),
                      head(weights, dims))


# -- training ----------------------------------------------------------

def row_loss_sums(weights, dims, row, quant=None):
    """(sum of cross-entropy, sum of logz^2) over one row's positions."""
    tokens, labels = row[:-1], row[1:]
    lg = logits(weights, dims, tokens, quant)
    logz = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0] - logz
    return -jnp.sum(ll), jnp.sum(logz * logz)


def lr_at(step, tcfg):
    """Linear warm-up then cosine decay to a tenth (the trainer's)."""
    s = jnp.asarray(step, F32)
    warm = jnp.minimum(1.0, (s + 1.0) / max(tcfg["warmup"], 1))
    total = tcfg["schedule_steps"]
    prog = jnp.clip((s - tcfg["warmup"]) / max(total - tcfg["warmup"], 1),
                    0.0, 1.0)
    cos = 0.1 + 0.9 * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return tcfg["lr"] * warm * cos


@functools.partial(jax.jit, static_argnames=("dims_key", "quant"))
def _row_grad(w32, row, scale, z_loss, dims_key, quant):
    dims = dict(dims_key)

    def f(w):
        ce, lz = row_loss_sums(w, dims, row, quant)
        return (ce + z_loss * lz) * scale, (ce, lz)

    (_, (ce, lz)), g = jax.value_and_grad(f, has_aux=True)(w32)
    return ce, lz, g


@jax.jit
def _adamw(w32, grads, mu, nu, count, lr, hp):
    b1, b2, eps, wd, clip = hp
    leaves = jax.tree_util.tree_leaves(grads)
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    sc = jnp.minimum(1.0, clip / (gn + 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * sc, grads)
    count = count + 1
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu,
                                grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                  + wd * p), w32, mu, nu)
    return new, mu, nu, count, grads


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(F32))))) for p, x in flat}


def train_steps(weights, dims: dict, tcfg: dict, batches, quant=None
                ) -> dict:
    """Follow the trainer's first steps: AdamW with global-norm clipping
    and decoupled weight decay, the loss = mean cross-entropy + z_loss *
    mean(logz^2), parameters stored back in their own dtypes after each
    update. ``batches``: (steps, rows, seq + 1). Returns per-step loss,
    the first (clipped) gradient and its per-leaf norms, and the
    per-leaf norms of the parameters' change over all the steps."""
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, weights)
    w32 = jax.tree_util.tree_map(lambda x: x.astype(F32), weights)
    w0 = w32
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
        grads = jax.tree_util.tree_map(jnp.zeros_like, w32)
        ce_t = lz_t = 0.0
        with jax.default_matmul_precision("highest"):
            for r in range(rows.shape[0]):
                ce, lz, g = _row_grad(w32, jnp.asarray(rows[r]),
                                      1.0 / n_tok, dims["z_loss"], dk,
                                      quant)
                grads = jax.tree_util.tree_map(jnp.add, grads, g)
                ce_t += float(ce)
                lz_t += float(lz)
            w32, mu, nu, count, clipped = _adamw(
                w32, grads, mu, nu, count, lr_at(step, tcfg), hp)
        # stored back in the parameters' own dtypes, as the trainer does
        w32 = jax.tree_util.tree_map(
            lambda p, dt: p.astype(dt).astype(F32), w32, dtypes)
        losses.append((ce_t + dims["z_loss"] * lz_t) / n_tok)
        if first is None:
            first, first_vec = leaf_norms(clipped), clipped
    change = leaf_norms(jax.tree_util.tree_map(jnp.subtract, w32, w0))
    return {"loss": losses, "grad": first, "grad_vec": first_vec,
            "change": change}
