"""Continuous-batching engine semantics (repro/serving/).

The load-bearing claim (ISSUE acceptance + docs/serving.md): a sequence
decoded inside a busy heterogeneous batch — admitted into a reused slot,
surrounded by other sequences being admitted/evicted mid-decode — yields
bit-identical f32 greedy tokens to the same sequence decoded alone with
``lm.prefill`` + ``lm.decode_step``. Slot rows are computed elementwise
over the batch axis, so co-batching must not perturb numerics at all.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.models import lm
from repro.serving import Request, ServingEngine, slots as slot_ops


def _cfg(kind: str, **kw):
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    cfg = cfgs.darkify(cfg, kind, cfg.attn.num_features)
    return dataclasses.replace(cfg, **kw) if kw else cfg


def _params(cfg):
    return lm.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(vocab, lengths, seed=1):
    key = jax.random.PRNGKey(seed)
    return [jax.random.randint(jax.random.fold_in(key, i), (l,), 0,
                               vocab).tolist()
            for i, l in enumerate(lengths)]


def _reference_greedy(params, cfg, prompt, n, max_len):
    """Single-sequence greedy decode: the ground truth the engine must hit."""
    lg, st = lm.prefill(params, cfg, {"tokens": jnp.asarray([prompt])},
                        max_len=max_len)
    toks = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(n - 1):
        lg, st = lm.decode_step(params, cfg, jnp.asarray(toks[-1:]), st)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


@pytest.mark.parametrize("kind", ["darkformer", "performer", "exact"])
def test_engine_matches_reference_bit_for_bit(kind):
    """3 requests of different lengths over 2 slots: the third is only
    admitted once a slot frees mid-decode, so slots are reused and the
    batch is heterogeneous throughout — outputs must still be exact."""
    cfg = _cfg(kind)
    params = _params(cfg)
    lengths, gens = (5, 9, 7), (6, 3, 8)
    prompts = _prompts(cfg.vocab, lengths)
    refs = [_reference_greedy(params, cfg, p, n, max_len=48)
            for p, n in zip(prompts, gens)]

    eng = ServingEngine(params, cfg, max_slots=2, max_len=48)
    uids = [eng.submit(Request(prompt=p, max_new_tokens=n))
            for p, n in zip(prompts, gens)]
    got = {r.uid: r.tokens for r in eng.run()}
    for uid, ref in zip(uids, refs):
        assert got[uid] == ref, kind
    st = eng.stats
    assert st["admitted"] == st["finished"] == 3
    assert st["decode_slot_steps"] > st["decode_steps"]  # real co-batching


def test_engine_pallas_matches_reference_path():
    """Engine-level kernel parity: the same traffic decoded through the
    Pallas prf_decode_step / linear_attn_scan kernels must reproduce the
    pure-jnp engine's greedy streams (f32 kernels agree to ~1e-6 on
    logits, far below greedy argmax gaps)."""
    streams = {}
    for use_kernel in (False, True):
        cfg = _cfg("darkformer", use_kernel=use_kernel)
        params = _params(cfg)
        prompts = _prompts(cfg.vocab, (6, 11, 8))
        eng = ServingEngine(params, cfg, max_slots=2, max_len=48)
        uids = [eng.submit(Request(prompt=p, max_new_tokens=n))
                for p, n in zip(prompts, (5, 4, 6))]
        got = {r.uid: r.tokens for r in eng.run()}
        streams[use_kernel] = [got[u] for u in uids]
    assert streams[False] == streams[True]


def test_mid_decode_admission_and_eviction():
    """A request submitted while others are mid-decode joins a freed slot;
    cancelling an active request evicts it without disturbing the rest."""
    cfg = _cfg("darkformer")
    params = _params(cfg)
    prompts = _prompts(cfg.vocab, (6, 6, 6))
    ref2 = _reference_greedy(params, cfg, prompts[2], 5, max_len=32)

    eng = ServingEngine(params, cfg, max_slots=2, max_len=32)
    uid0 = eng.submit(Request(prompt=prompts[0], max_new_tokens=30))
    uid1 = eng.submit(Request(prompt=prompts[1], max_new_tokens=30))
    for _ in range(3):
        eng.step()
    assert eng.num_active == 2
    # submit a third mid-decode; both slots busy -> it must wait
    uid2 = eng.submit(Request(prompt=prompts[2], max_new_tokens=5))
    eng.step()
    assert eng.num_active == 2
    # evict request 0 mid-decode -> request 2 takes over its slot
    res0 = eng.cancel(uid0)
    assert res0.cancelled and len(res0.tokens) >= 4
    finished = eng.run()
    got = {r.uid: r for r in finished}
    assert uid2 in got and uid1 in got
    # the late-admitted sequence still decodes exactly
    assert got[uid2].tokens == ref2


def test_slot_write_read_roundtrip():
    """write_slot/read_slot are inverse over the heterogeneous state tree
    (scanned-unit leaves slot-axis 1, pos/length slot-axis 0)."""
    cfg = _cfg("exact")  # exact has the richest state (caches + lengths)
    params = _params(cfg)
    pool = lm.init_serve_state(cfg, b=3, max_len=16, per_slot=True)
    _, st = lm.prefill(params, cfg,
                       {"tokens": jnp.asarray([_prompts(cfg.vocab, (7,))[0]])},
                       max_len=16)
    pool2 = slot_ops.write_slot(pool, st, jnp.int32(1))
    back = slot_ops.read_slot(pool2, jnp.int32(1))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(st)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        assert jax.tree_util.keystr(pa) == jax.tree_util.keystr(pb)
        np.testing.assert_array_equal(
            np.asarray(a).reshape(np.asarray(b).shape), np.asarray(b),
            err_msg=jax.tree_util.keystr(pa))
    # untouched slots stayed zero/frozen
    other = slot_ops.read_slot(pool2, jnp.int32(0))
    for leaf in jax.tree_util.tree_leaves(other):
        if leaf.dtype == jnp.int32:
            assert int(np.max(np.asarray(leaf))) == 0


def test_chunked_prefill_admission_matches_blocking_admission():
    """chunk_tokens splits admission into resumed prompt chunks; the
    k-stabilizer trajectory changes, so logits only agree to f32
    rounding — greedy streams must still match on this model."""
    cfg = _cfg("darkformer")
    params = _params(cfg)
    prompts = _prompts(cfg.vocab, (13, 9))
    streams = {}
    for chunk in (None, 4):
        eng = ServingEngine(params, cfg, max_slots=2, max_len=48,
                            chunk_tokens=chunk)
        uids = [eng.submit(Request(prompt=p, max_new_tokens=6))
                for p in prompts]
        got = {r.uid: r.tokens for r in eng.run()}
        streams[chunk] = [got[u] for u in uids]
    assert streams[None] == streams[4]


def test_poisson_arrivals_respected():
    """Requests are not admitted before their arrival_time; the fast
    (realtime=False) runner skips idle gaps but keeps ordering."""
    cfg = _cfg("darkformer")
    params = _params(cfg)
    prompts = _prompts(cfg.vocab, (5, 5))
    eng = ServingEngine(params, cfg, max_slots=4, max_len=32)
    eng.submit(Request(prompt=prompts[0], max_new_tokens=3,
                       arrival_time=0.0))
    eng.submit(Request(prompt=prompts[1], max_new_tokens=3,
                       arrival_time=10.0))  # far future
    eng.step()
    assert eng.num_active == 1              # second not arrived yet
    results = eng.run(realtime=False)       # clock-jumps over the gap
    assert len(results) + len([s for s in eng._slots if s]) >= 1
    all_res = results
    assert sum(1 for r in all_res if r.tokens) >= 1
    assert not eng.has_work


@pytest.mark.parametrize("kind", ["darkformer", "exact"])
def test_compiled_text_covers_decode_and_prefill(kind):
    """The engine hands back both compiled step programs. Off the TPU the
    Pallas kernels run interpreted, so no Mosaic custom call is in them
    (on a TPU chip_smoke.py asserts the opposite)."""
    cfg = _cfg(kind, use_kernel=kind != "exact")
    eng = ServingEngine(_params(cfg), cfg, max_slots=2, max_len=32,
                        chunk_tokens=8)
    text = eng.compiled_text(rows=2, length=8)
    assert set(text) == {"decode", "prefill"}
    for t in text.values():
        assert t.startswith("HloModule")
        assert "tpu_custom_call" not in t
