"""Fused data-aligned PRF prefill megakernel (ISSUE 5 tentpole).

Five layers of guarantee, all in interpret mode on CPU:

  * kernel vs oracle: ``prf_fused_prefill_fwd`` == ``ref.prf_fused_
    prefill_ref`` across kinds, GQA geometries, ragged valid_len rows
    (incl. a pure-padding valid_len=0 row and a row ending mid-chunk),
    stabilize=False, and multi-chunk internal scans (where the oracle
    is chained per-sub-chunk — the kernel's stabilizer trajectory);
  * kernel vs the jnp prefill path: the fused one-call chunk equals
    ``rf_attention_prefill(use_kernel=False)`` to f32 rounding over a
    SEQUENCE of resumed ragged chunks — the running-stabilizer
    contract — and a fused CHUNKED stream reproduces the one-shot jnp
    ``lm.prefill`` greedy stream;
  * aliasing: the pallas_call carries ``input_output_aliases`` mapping
    the (c, s, z) state inputs onto the state outputs, so a donated
    pool is updated in place;
  * one pallas_call per layer per packed chunk: the jaxpr of a fused
    ``lm.prefill_chunk`` contains exactly ONE pallas primitive (inside
    the scanned layer body);
  * engine: ragged batched admission under ``use_kernel`` streams
    identically to the jnp engine, and ``stats`` reports which path
    compiled.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs as cfgs
from repro.core import attention as rfa
from repro.core import feature_maps as fm
from repro.kernels import ops, ref
from repro.kernels.prf_fused_prefill import prf_fused_prefill_fwd
from repro.models import lm


def _fused_inputs(b, g, hg, d, r, m, dv, l, dark, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (b, g, hg, l, d))
    k = jax.random.normal(ks[1], (b, g, l, d))
    v = jax.random.normal(ks[2], (b, g, l, dv))
    m_mat = 0.4 * jax.random.normal(ks[3], (g, r, d)) if dark else None
    w = jax.random.normal(ks[4], (g, m, r if dark else d))
    a = (jnp.einsum("gmr,grd->gdm", w, m_mat) if dark
         else jnp.swapaxes(w, -1, -2))
    s = jax.random.normal(ks[5], (b, g, hg, m, dv))
    z = jax.random.uniform(ks[6], (b, g, hg, m)) + 0.5
    c = jax.random.normal(ks[7], (b, g)) + 1.0
    return q, k, v, a, m_mat, s, z, c


def _chained_oracle(q, k, v, a, m_mat, s, z, c, valid_len, t, stabilize):
    """Per-sub-chunk oracle chain: the kernel advances its running-max
    stabilizer once per internal T-chunk, so the ground truth for a
    multi-chunk call is the jnp oracle resumed T tokens at a time."""
    l = q.shape[3]
    outs = []
    for st_ in range(0, l, t):
        en = min(st_ + t, l)
        vls = (None if valid_len is None
               else jnp.clip(valid_len - st_, 0, en - st_))
        o, s, z, c = ref.prf_fused_prefill_ref(
            q[:, :, :, st_:en], k[:, :, st_:en], v[:, :, st_:en],
            a, m_mat, s, z, c, vls, stabilize=stabilize)
        outs.append(o)
    return jnp.concatenate(outs, axis=3), s, z, c


def _assert_close(out, exp, l, valid_len, msg):
    for o, e, name in zip(out, exp, ("out", "s", "z", "c")):
        o = np.asarray(o, np.float32)
        e = np.asarray(e, np.float32)
        if name == "out" and valid_len is not None:
            # outputs at masked positions are garbage by contract
            mask = (np.arange(l)[None] < np.asarray(valid_len)[:, None]
                    )[:, None, None, :, None]
            o = np.where(mask, o, 0.0)
            e = np.where(mask, e, 0.0)
        np.testing.assert_allclose(o, e, atol=2e-5, rtol=2e-4,
                                   err_msg=(name, msg))


@pytest.mark.parametrize(
    "b,g,hg,d,r,m,dv,l,dark,stab,chunk,block_b,valid_len", [
        (1, 1, 1, 4, 2, 8, 4, 5, True, True, 8, 1, None),
        (3, 2, 2, 8, 4, 16, 8, 12, True, True, 16, 2, None),   # GQA
        (4, 1, 3, 8, 8, 16, 8, 7, False, True, 4, 8, None),    # iso, 2-chunk
        (2, 2, 2, 8, 4, 16, 8, 9, True, False, 4, 1, None),    # no stab
        (4, 2, 2, 8, 4, 16, 8, 10, True, True, 4, 4, (0, 3, 10, 7)),
        (3, 1, 2, 8, 4, 16, 8, 11, True, True, 16, 3, (11, 5, 0)),
        (5, 2, 1, 4, 4, 8, 4, 6, True, False, 8, 3, (6, 0, 2, 5, 1)),
        (6, 3, 4, 8, 4, 16, 8, 8, False, True, 8, 4, (8, 1, 7, 0, 4, 8)),
    ])
def test_fused_prefill_kernel_vs_oracle(b, g, hg, d, r, m, dv, l, dark,
                                        stab, chunk, block_b, valid_len):
    args = _fused_inputs(b, g, hg, d, r, m, dv, l, dark, seed=b * 7 + l)
    vl = (None if valid_len is None
          else jnp.asarray(valid_len, jnp.int32))
    out = prf_fused_prefill_fwd(*args, vl, stabilize=stab, chunk=chunk,
                                block_b=block_b, interpret=True)
    exp = _chained_oracle(*args, vl, min(chunk, l), stab)
    _assert_close(out, exp, l, valid_len, (b, g, hg, l, chunk))


def test_fused_prefill_padded_positions_stay_finite():
    """Rows with no valid position in a whole internal chunk (a 0-length
    row, rows ending before the last chunk) still give finite outputs
    everywhere: NaN at padded positions would reach the next layer's v
    and, through 0·NaN in kfᵀv, its state."""
    args = _fused_inputs(4, 2, 2, 8, 4, 16, 8, 10, True, seed=3)
    vl = jnp.asarray((0, 3, 10, 7), jnp.int32)
    out = prf_fused_prefill_fwd(*args, vl, chunk=4, block_b=2,
                                interpret=True)
    for o in out:
        assert np.isfinite(np.asarray(o)).all()


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 3),
       st.integers(1, 3), st.integers(1, 10), st.booleans(),
       st.booleans())
def test_fused_prefill_kernel_vs_oracle_hypothesis(seed, b, g, hg, l,
                                                   dark, ragged):
    d, r, m, dv = 8, 4, 16, 8
    args = _fused_inputs(b, g, hg, d, r, m, dv, l, dark, seed=seed)
    vl = None
    if ragged:
        vl = jax.random.randint(jax.random.PRNGKey(seed + 1), (b,), 0,
                                l + 1)
    out = prf_fused_prefill_fwd(*args, vl, chunk=4, block_b=2,
                                interpret=True)
    exp = _chained_oracle(*args, vl, min(4, l), True)
    _assert_close(out, exp, l, vl, (seed, b, g, hg, l))


# ---------------------------------------------------------------------------
# fused path vs the jnp prefill path (rf_attention_prefill)
# ---------------------------------------------------------------------------

def _attn_setup(kind, b, g, hg, d, m, seed=0):
    cfg = fm.FeatureConfig(kind=kind, num_features=m, feature_rank=0)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    fparams = fm.init_feature_params(ks[0], cfg, d, n_groups=g)
    if kind == "darkformer":
        fparams["m_mat"] = fparams["m_mat"] + 0.1 * jax.random.normal(
            ks[1], fparams["m_mat"].shape)
    state = rfa.init_linear_serve_state(b, g, hg, m, d)
    proj = fm.precompose_projection(fparams, kind)
    return cfg, fparams, state, proj


@pytest.mark.parametrize("kind", ["darkformer", "performer", "lfk"])
@pytest.mark.parametrize("stabilize", [True, False])
def test_fused_prefill_chunk_sequence_matches_jnp_path(kind, stabilize):
    """Chunk-by-chunk resumed prefill through the megakernel tracks the
    jnp path (f32 tolerance) over a multi-chunk SEQUENCE with ragged
    rows: same running-max stabilizer trajectory, same masked state
    advance, even though the fused path composes the projection as one
    x @ (W M)^T matmul."""
    b, g, hg, d, m, l = 3, 2, 2, 8, 16, 6
    cfg, fparams, state, proj = _attn_setup(kind, b, g, hg, d, m)
    cfg = dataclasses.replace(cfg, stabilize=stabilize)
    state_f = state
    key = jax.random.PRNGKey(7)
    vls = [None, jnp.asarray([6, 3, 0]), jnp.asarray([2, 6, 5]), None]
    for t, vl in enumerate(vls):
        kq, kk, kv, key = jax.random.split(key, 4)
        # large scale so new keys keep beating the running max and the
        # in-kernel rho-rescale actually fires
        q = 2.0 * jax.random.normal(kq, (b, g, hg, l, d))
        k = 2.0 * jax.random.normal(kk, (b, g, 1, l, d))
        v = jax.random.normal(kv, (b, g, 1, l, d))
        out_j, state = rfa.rf_attention_prefill(q, k, v, fparams, cfg,
                                                state=state, valid_len=vl)
        out_f, state_f = rfa.rf_attention_prefill(q, k, v, fparams, cfg,
                                                  state=state_f,
                                                  valid_len=vl,
                                                  use_kernel=True,
                                                  proj=proj)
        of, oj = np.asarray(out_f), np.asarray(out_j)
        if vl is not None:
            mask = (np.arange(l)[None] < np.asarray(vl)[:, None]
                    )[:, None, None, :, None]
            of = np.where(mask, of, 0.0)
            oj = np.where(mask, oj, 0.0)
        np.testing.assert_allclose(of, oj, atol=1e-4, err_msg=(kind, t))
        for name in ("s", "z", "c"):
            np.testing.assert_allclose(
                np.asarray(getattr(state_f, name)),
                np.asarray(getattr(state, name)), atol=1e-4,
                err_msg=(kind, t, name))


def test_fused_prefill_row_ending_mid_chunk_leaves_no_trace():
    """A ragged row whose valid length ends inside the kernel's internal
    T-chunk advances its state exactly as the same row prefixed alone
    (B=1, unpadded) — the padding contract at sub-chunk granularity."""
    b, g, hg, d, m, l = 3, 1, 2, 8, 16, 10
    cfg, fparams, state, proj = _attn_setup("darkformer", b, g, hg, d, m,
                                            seed=3)
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, g, hg, l, d))
    k = jax.random.normal(kk, (b, g, 1, l, d))
    v = jax.random.normal(kv, (b, g, 1, l, d))
    vl = jnp.asarray([10, 6, 0], jnp.int32)   # row 1 ends mid-chunk (T=4)
    _, st_batch = rfa.rf_attention_prefill(
        q, k, v, fparams, cfg, state=state, valid_len=vl,
        use_kernel=True, proj=proj, chunk=4)
    for row in range(b):
        lr = int(vl[row])
        st1 = rfa.init_linear_serve_state(1, g, hg, m, d)
        if lr > 0:
            _, st1 = rfa.rf_attention_prefill(
                q[row:row + 1, :, :, :lr], k[row:row + 1, :, :, :lr],
                v[row:row + 1, :, :, :lr], fparams, cfg, state=st1,
                use_kernel=True, proj=proj, chunk=4)
        for name in ("s", "z", "c"):
            np.testing.assert_allclose(
                np.asarray(getattr(st_batch, name)[row:row + 1]),
                np.asarray(getattr(st1, name)), atol=1e-5,
                err_msg=(row, name))


def test_fused_chunked_stream_matches_one_shot_jnp_prefill():
    """Multi-chunk resume parity at the lm level: feeding a prompt
    through the fused kernel in resumed chunks reproduces the one-shot
    jnp ``lm.prefill`` — greedy next token identical, every state leaf
    f32-close (the stabilizer trajectory differs, so bitwise equality
    is out of scope by the docs/kernels.md §3 contract)."""
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (13,), 0,
                                cfg.vocab)
    lg_ref, st_ref = lm.prefill(params, cfg,
                                {"tokens": prompt[None]}, max_len=32)
    st = lm.init_serve_state(cfg, b=1, max_len=32, per_slot=True,
                             stacked=True)
    lg = None
    for start in (0, 5, 10):
        end = min(start + 5, 13)
        lg, st = lm.prefill_chunk(params, cfg_k,
                                  {"tokens": prompt[None, start:end]}, st)
    assert int(jnp.argmax(lg[0])) == int(jnp.argmax(lg_ref[0, -1]))
    np.testing.assert_allclose(np.asarray(lg[0]),
                               np.asarray(lg_ref[0, -1]), atol=1e-3)
    # the assembled state must CONTINUE the sequence like the reference:
    # greedy decode streams from both states agree
    toks_f = [int(jnp.argmax(lg[0]))]
    toks_r = [int(jnp.argmax(lg_ref[0, -1]))]
    st_r = st_ref
    for _ in range(4):
        lg, st = lm.decode_step(params, cfg_k,
                                jnp.asarray(toks_f[-1:]), st)
        toks_f.append(int(jnp.argmax(lg[0])))
        lg_r, st_r = lm.decode_step(params, cfg,
                                    jnp.asarray(toks_r[-1:]), st_r)
        toks_r.append(int(jnp.argmax(lg_r[0])))
    assert toks_f == toks_r


# ---------------------------------------------------------------------------
# in-place aliasing + one-call-per-layer
# ---------------------------------------------------------------------------

def test_fused_prefill_aliases_state_in_place():
    """The lowered pallas_call maps the (c, s, z) state INPUTS onto the
    state OUTPUTS (input_output_aliases), so under jit with a donated
    staging pool no second pool-sized buffer is allocated."""
    q, k, v, a, m_mat, s, z, c = _fused_inputs(4, 2, 2, 8, 4, 16, 8, 6,
                                               dark=True)
    vl = jnp.asarray([6, 3, 6, 0], jnp.int32)

    def run(q, k, v, s, z, c):
        return ops.fused_prf_prefill(q, k, v, a, m_mat, s, z, c, vl)

    jaxpr = jax.make_jaxpr(run)(q, k, v, s, z, c)
    eqns = [e for e in jaxpr.jaxpr.eqns if "pallas" in str(e.primitive)]
    assert len(eqns) == 1, "prefill must be ONE fused pallas_call"
    aliases = dict(eqns[0].params["input_output_aliases"])
    # inputs: q k v a m_mat vl c s z -> outputs: out s_new z_new c_new
    assert aliases == {6: 3, 7: 1, 8: 2}
    # the iso variant drops m_mat, shifting the map by one
    jaxpr_iso = jax.make_jaxpr(
        lambda q, k, v, s, z, c: ops.fused_prf_prefill(
            q, k, v, a, None, s, z, c, vl))(q, k, v, s, z, c)
    eqns_iso = [e for e in jaxpr_iso.jaxpr.eqns
                if "pallas" in str(e.primitive)]
    assert dict(eqns_iso[0].params["input_output_aliases"]) == \
        {5: 3, 6: 1, 7: 2}


def _count_pallas(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if "pallas" in str(eqn.primitive):
            n += 1
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", None)
            if sub is not None:
                n += _count_pallas(sub)
            elif isinstance(val, (list, tuple)):
                for v_ in val:
                    sub = getattr(v_, "jaxpr", None)
                    if sub is not None:
                        n += _count_pallas(sub)
    return n


def test_fused_prefill_is_one_pallas_call_per_layer_per_chunk():
    """The fused lm-level chunk lowers to exactly ONE pallas primitive —
    sitting inside the scanned layer body, i.e. one kernel dispatch per
    layer per packed chunk (the ISSUE 5 acceptance bar). The two-stage
    path also carries one (the carry scan), so the fused path must not
    regress the count while absorbing the whole featmap stage."""
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    st = lm.init_serve_state(cfg, b=2, max_len=32, per_slot=True,
                             stacked=True)
    toks = jnp.zeros((2, 8), jnp.int32)
    vl = jnp.asarray([8, 5], jnp.int32)
    proj = lm.build_decode_proj(params, cfg_k, stacked=True)
    jaxpr = jax.make_jaxpr(
        lambda p, s, t, v: lm.prefill_chunk(p, cfg_k, {"tokens": t}, s,
                                            valid_len=v, proj=proj))(
        params, st, toks, vl)
    assert _count_pallas(jaxpr.jaxpr) == 1
    # and the jnp reference path has none
    jaxpr_j = jax.make_jaxpr(
        lambda p, s, t, v: lm.prefill_chunk(p, cfg, {"tokens": t}, s,
                                            valid_len=v))(
        params, st, toks, vl)
    assert _count_pallas(jaxpr_j.jaxpr) == 0


# ---------------------------------------------------------------------------
# engine: ragged batched admission through the fused path
# ---------------------------------------------------------------------------

def test_engine_ragged_admission_runs_fused_path_and_matches_jnp():
    """A burst of ragged admissions under chunked prefill, decoded
    through the fused kernels, streams identically to the jnp engine —
    and the engine reports the path it compiled."""
    from repro.serving import Request, ServingEngine
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [jax.random.randint(jax.random.PRNGKey(10 + i),
                                  (n,), 0, cfg.vocab).tolist()
               for i, n in enumerate((11, 5, 9, 2))]
    streams = {}
    paths = {}
    for use_kernel in (False, True):
        c = dataclasses.replace(cfg, use_kernel=use_kernel)
        eng = ServingEngine(params, c, max_slots=3, max_len=48,
                            chunk_tokens=8)
        uids = [eng.submit(Request(prompt=p, max_new_tokens=n))
                for p, n in zip(prompts, (5, 4, 6, 3))]
        got = {r.uid: r.tokens for r in eng.run()}
        streams[use_kernel] = [got[u] for u in uids]
        paths[use_kernel] = (eng.stats["prefill_path"],
                             eng.stats["decode_path"])
    assert streams[False] == streams[True]
    assert paths[False] == ("jnp", "jnp")
    assert paths[True] == ("fused_kernel", "fused_kernel")


def test_engine_stats_report_exact_path():
    from repro.serving import ServingEngine
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    cfg_ex = dataclasses.replace(cfgs.darkify(cfg, "exact"),
                                 use_kernel=True)
    eng = ServingEngine(params, cfg_ex, max_slots=2, max_len=32)
    assert eng.stats["prefill_path"] == "exact"
    assert eng.stats["decode_path"] == "exact"


def test_engine_blocking_admission_longer_than_kernel_chunk():
    """Blocking admission packs whole prompts into one call, so a short
    row shares a call longer than the kernel's 256-token chunk with a
    long one and holds no position in its last chunk: the fused engine
    still streams identically to the jnp engine."""
    from repro.serving import Request, ServingEngine
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [jax.random.randint(jax.random.PRNGKey(20 + i), (n,), 0,
                                  cfg.vocab).tolist()
               for i, n in enumerate((300, 50))]
    streams = {}
    for use_kernel in (False, True):
        c = dataclasses.replace(cfg, use_kernel=use_kernel)
        eng = ServingEngine(params, c, max_slots=2, max_len=320)
        uids = [eng.submit(Request(prompt=p, max_new_tokens=4))
                for p in prompts]
        got = {r.uid: r.tokens for r in eng.run()}
        streams[use_kernel] = [got[u] for u in uids]
    assert streams[False] == streams[True]
