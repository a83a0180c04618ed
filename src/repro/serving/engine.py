"""Continuous-batching serving engine over the O(1)-state PRF decode.

The paper's serving claim (docs/serving.md) is that PRF attention decodes
from a fixed-size running state — an (m x d_v) sum S, an (m,) normalizer
z and the running stabilizer max c per head — so a server can multiplex
many users over one batched decode step regardless of how long each
context is. The same state is what makes prefill *chunkable*: the state
after k prompt tokens is a valid resume point (``lm.prefill_chunk``), so
prompt work can be cut into budgeted slices instead of monopolizing the
device. This engine is that multiplexer:

  * a FIFO **request queue** with arrival times (Poisson traffic plugs in
    here — see benchmarks/serve_latency.py);
  * a device-resident **slot pool**: one serve-state pytree with
    ``max_slots`` batch rows, per-slot positions and (for the exact
    fallback) per-slot KV write indices — plus a same-shape **staging
    pool** holding every mid-prefill admission's partial state
    (repro/serving/slots.py);
  * a **token-budget packer**: each ``step()`` splits at most
    ``chunk_tokens`` prompt tokens across ALL staged admissions and
    advances them together in ONE padded (P, L) ``prefill_chunk`` call
    — under bucketing the grants are COALESCED to one shared pow-2
    size (prev_pow2(budget/P)) so non-tail rows pack with zero padding
    waste (occupancy 1.0 under ragged bursts); ragged rows are masked
    per-row (``valid_len``) and chunk lengths are bucketed to powers
    of two so compiles stay bounded by (rows <= max_slots) x (log2
    length buckets). With ``cfg.use_kernel`` the packed call runs the
    ``prf_fused_prefill`` megakernel against the same engine-built
    projections as decode (one pallas_call per layer per chunk,
    valid_len masked in-kernel, staging rows aliased in place).
    ``chunk_tokens=None`` is the blocking baseline: all staged
    admissions prefill their whole prompts in one padded call;
  * one jitted **batched decode step** that advances all slots in
    lock-step; inactive slots are masked so their state stays bit-frozen
    (skipped entirely — a static fast path — when every slot is live).
    A mid-prefill slot's state lives in the staging pool until its last
    chunk lands, so partial prefills never perturb pool rows. For
    homogeneous configs both pools are LAYER-STACKED
    (``lm.can_stack_layers``): the step scans one compiled layer body
    over a leading (n_layers,) axis, and with ``cfg.use_kernel`` that
    body runs the ``prf_fused_decode`` megakernel against per-layer
    projections precomposed once at engine build
    (``lm.build_decode_proj``).

Two step schedulers share those pieces:

**Sequential** (``overlap=False``): one packed prefill chunk, then one
batched decode, back-to-back with a blocking token readback — the
reference scheduler every numerical-contract test pins down.

**Overlapped** (``overlap=True``, the serve-CLI default): the step loop
is restructured around JAX async dispatch so decode never waits on
prefill and the host never idles on readback:

  1. *retire* — block on the ONE-STEP-DELAYED sample buffer from the
     previous step's decode (``jax.device_get`` on tokens that have had
     a whole prefill chunk's worth of device time to finish), append
     the now-ready tokens, fire ``Request.on_token`` hooks, evict
     finished rows. This is the step's only synchronization point; the
     blocked time is recorded per step as ``decode_stall_ms``;
  2. *admit* — reserve slots + batched staging-row reset, as before;
  3. *merge* — admissions whose final prefill chunk landed during the
     PREVIOUS step are committed into the slot pool now (one deferred
     ``merge_slots`` scatter), their first tokens sampled from the
     saved final-chunk logits and scattered into the device-resident
     token feed — so the merge rides ahead of this step's decode
     instead of serializing after a prefill;
  4. *decode dispatch* — the batched decode + sample step is enqueued
     immediately, reading last step's sampled tokens straight from the
     device feed buffer (no host round-trip on the token feedback
     path); its sampled tokens become the NEXT step's retire target;
  5. *prefill dispatch* — the chunk PACKED during the previous step is
     enqueued behind the decode (rows whose request was cancelled since
     packing are dropped); admissions finishing their prompt this chunk
     queue a pending merge for step +1;
  6. *pack* — the NEXT chunk's token block is packed on the host into a
     double-buffered staging array (``slots.PackBuffer``) while this
     step's chunk is still in flight.

The pipeline trades one step of latency on each edge (admission to
first chunk, prefill completion to decode participation, sample to host
visibility) for a decode dispatch that never blocks on prefill or
readback: all host-side packing, bookkeeping and sampling-parameter
work overlaps device execution, and the decode stall observed at retire
collapses to whatever dispatch could not hide. ``flush()`` drains the
in-flight tail (stream end / step-driven callers); cancellation drops a
request's in-flight tokens without a callback.

``prefix_cache=`` adds admission-time prefix reuse
(repro/serving/prefix_cache.py): chunked prefill captures state
snapshots at block-aligned cursor boundaries, and a later request whose
prompt starts with a cached prefix is admitted by FORKING the snapshot
— one broadcast scatter seeds its staging row (``slots.fork_slots``)
and its cursor starts at the cached length, so only the un-cached
suffix is prefilled. For the PRF kinds the fork is O(1) in prefix
length (the state is the fixed-size (S, z, c) tuple); exact configs
switch the pools to a block-granular PAGED KV layout — rows hold page
tables over shared page pools, a fork shares the prefix's full pages
(refcounted) and copies only the partial tail page (copy-on-write).
Both schedulers go through the same admission path, so fork-on-admit
composes with overlap, cancel and flush; ``stats`` gains ``prefix_*``
hit/capture/eviction counters and ``forked_tokens``.

Pass ``mesh=`` to place BOTH pools under a device mesh: every pool leaf
is sharded per ``repro.parallel.serve_state_specs`` (slots over the data
axes, head groups of the KV-cache / linear state over 'model'),
``device_put`` at construction, donated through every step, and pinned
with ``with_sharding_constraint`` inside the jitted step functions so
XLA never silently migrates the pool. Decode under a mesh is
token-identical to the unsharded engine (tests/test_distributed.py,
tests/test_overlapped_serving.py).

Numerical contract: slot rows are computed elementwise over the batch
axis, so a sequence decoded inside a busy heterogeneous batch produces
bit-identical f32 logits to the same sequence decoded alone with
``lm.prefill`` + ``lm.decode_step`` (tests/test_serving_engine.py
asserts this for darkformer, performer and exact kernels). Chunking a
prompt changes the k-stabilizer trajectory (a running max instead of one
whole-prompt max), so chunked admission matches blocking admission to
f32 rounding — and bit-exactly when ``chunk_tokens >= prompt_len``
(tests/test_chunked_prefill.py). Batching staged admissions into one
padded call masks every padded position out of the advanced states, so
batched prefill matches the serial (``prefill_rows=1``) schedule to f32
rounding; with one staged row and ``bucket_prefill=False`` the packed
call IS the legacy unpadded chunk, bit-for-bit. The overlapped loop
runs the SAME jitted step functions in a different dispatch order, so
overlap-vs-sequential token streams are identical per request
(tests/test_overlapped_serving.py asserts bitwise stream equality under
Poisson admission storms, including mid-stream cancel and eviction).

Sampling: per-request ``temperature`` / ``top_k`` / ``top_p`` are
applied inside one jitted batched sample step; the defaults (0 / 0 /
1.0) leave the greedy path bit-identical to plain argmax. Every row
draws with its own key ``fold_in(fold_in(base, uid), token_index)`` —
a schedule-invariant derivation (independent of step count, batch
composition, and chunk boundaries), which is what lets sampled streams
match bitwise across the sequential and overlapped schedulers.

Timing contract: every recorded token time is a *readiness* time — the
engine blocks on the device value before reading the clock, never
timing a dispatch return (under async dispatch a ``perf_counter`` delta
around an unblocked call measures enqueue latency and silently
under-reports TPOT). ``stats`` surfaces the per-step blocked time
(``decode_stall_ms_*``) and how many dispatches the device queue ran
ahead of the fetched buffer (``dispatch_depth_*``).
"""
from __future__ import annotations

import bisect
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.serving import slots as slot_ops
from repro.serving.prefix_cache import (NoFreePages, PageAllocator,
                                        PrefixCache, PrefixCacheConfig)
from repro.serving.request import Request, RequestResult

Array = jax.Array


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


class _Slot:
    """Host-side record of the sequence occupying one pool row.

    A slot is *prefilling* while ``cursor < len(req.prompt)`` — its
    attention state lives in staging-pool row i and it takes no part in
    decode. Once the last chunk lands the staged row is committed into
    the pool and the slot decodes. ``emitted`` counts tokens *enqueued*
    for the row (under the overlapped loop this runs one step ahead of
    ``result.tokens``, which only holds host-retired tokens); it is the
    per-row token index folded into the sampling key.
    """

    __slots__ = ("req", "result", "budget", "cursor", "emitted")

    def __init__(self, req: Request, result: RequestResult, budget: int):
        self.req = req
        self.result = result
        self.budget = budget
        self.cursor = 0
        self.emitted = 0


class ServingEngine:
    """Continuous-batching generation over a fixed slot pool.

    Typical use::

        eng = ServingEngine(params, cfg, max_slots=8, max_len=512,
                            chunk_tokens=64, overlap=True)
        eng.submit(Request(prompt=[...], max_new_tokens=64))
        results = eng.run()

    or drive it step-by-step and ``submit`` more requests while others
    are mid-decode. ``overlap=True`` selects the pipelined step loop
    (concurrent prefill/decode dispatch, double-buffered chunk packing,
    one-step-delayed non-blocking token readback — module docstring);
    the default ``overlap=False`` is the sequential reference scheduler
    (one packed prefill chunk then one blocking batched decode per
    ``step()``). Token streams are identical between the two; the
    serve CLI defaults to overlap.

    ``prefill_rows`` caps how many staged admissions share the packed
    prefill call (None = all staged, i.e. up to ``max_slots``; 1 =
    the serial one-admission-per-step schedule of the pre-batching
    engine). ``bucket_prefill`` pads packed chunk lengths up to powers
    of two to bound recompiles; disable it for bit-exact parity with
    the serial unpadded schedule at P=1. ``mesh`` shards the slot and
    staging pools per ``serve_state_specs`` (see module docstring).
    ``prefix_cache`` (True for defaults, or a ``PrefixCacheConfig``)
    enables snapshot capture + fork-on-admit prefix reuse, switching
    exact configs to the paged-KV layout (module docstring).
    """

    def __init__(self, params, cfg: lm.ModelConfig, *, max_slots: int = 4,
                 max_len: int = 256, chunk_tokens: Optional[int] = None,
                 seed: int = 0, mesh=None,
                 prefill_rows: Optional[int] = None,
                 bucket_prefill: bool = True,
                 overlap: bool = False,
                 prefix_cache: Union[bool, PrefixCacheConfig,
                                     None] = None):
        if cfg.modality != "text":
            raise ValueError("serving engine drives text decode only")
        if chunk_tokens is not None and chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        if prefill_rows is not None and prefill_rows < 1:
            raise ValueError("prefill_rows must be >= 1 (None = no cap)")
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.chunk_tokens = chunk_tokens
        self.prefill_rows = prefill_rows
        self.bucket_prefill = bucket_prefill
        self.overlap = overlap
        self.mesh = mesh
        # homogeneous configs stack all L layer states along one leading
        # axis so the jitted steps scan ONE compiled layer body
        # (lm.can_stack_layers); heterogeneous patterns keep the
        # per-unit layout
        self._stacked = lm.can_stack_layers(cfg)
        if prefix_cache is True:
            prefix_cache = PrefixCacheConfig()
        self._pc_cfg: Optional[PrefixCacheConfig] = prefix_cache or None
        # snapshots are captured only when a prefill cursor lands
        # EXACTLY on a block_tokens multiple — with a block_tokens that
        # does not divide chunk_tokens the chunk grants can step over
        # every boundary, silently capturing nothing (no hits, and the
        # documented fork-parity guarantee assumes the alignment), so
        # reject the combination instead of relying on the docstring
        # convention
        if (self._pc_cfg is not None and chunk_tokens is not None
                and chunk_tokens % self._pc_cfg.block_tokens):
            raise ValueError(
                f"prefix-cache block_tokens={self._pc_cfg.block_tokens}"
                f" must divide chunk_tokens={chunk_tokens}: capture "
                "points fire only when a prefill cursor lands on a "
                "block boundary (docs/serving.md §prefix cache)")
        # with a prefix cache, exact configs switch the pools to the
        # block-granular paged-KV layout: rows hold page TABLES over a
        # shared page pool, so a cached prefix's pages can be shared
        # across forks (copy-on-write on the partial tail page only).
        # Every other kind's state is fixed-size, so snapshots fork
        # through the plain broadcast scatter and need no paging.
        self._paged = (self._pc_cfg is not None and self._stacked
                       and cfg.attn.kind == "exact"
                       and any(k in ("attn", "local")
                               for k in cfg.layer_kinds()))
        if self._paged:
            ps = self._pc_cfg.page_size
            self._page_size = ps
            self._max_pages = -(-max_len // ps)
            # page 0 is the reserved garbage page; beyond every slot's
            # worst case, ``cache_pages`` extra pages let cached
            # prefixes stay resident while all slots are busy
            cache_pages = self._pc_cfg.cache_pages or 2 * self._max_pages
            n_pages = 1 + max_slots * self._max_pages + cache_pages
            self.pool = lm.init_paged_serve_state(cfg, b=max_slots,
                                                  max_len=max_len,
                                                  page_size=ps)
            self.staging = lm.init_paged_serve_state(cfg, b=max_slots,
                                                     max_len=max_len,
                                                     page_size=ps)
            self._fresh_row = lm.init_paged_serve_state(cfg, b=1,
                                                        max_len=max_len,
                                                        page_size=ps)
            self._pages = lm.init_kv_pages(cfg, n_pages, ps)
            self._alloc = PageAllocator(n_pages)
            self._page_bytes_each = (2 * cfg.n_layers * ps * cfg.n_kv
                                     * cfg.head_dim * 4)
        else:
            self.pool = lm.init_serve_state(cfg, b=max_slots,
                                            max_len=max_len,
                                            per_slot=True,
                                            stacked=self._stacked)
            # fixed-size staging pool: row i holds the partial prefill
            # state of the admission reserved on slot i (same pytree as
            # the pool)
            self.staging = lm.init_serve_state(cfg, b=max_slots,
                                               max_len=max_len,
                                               per_slot=True,
                                               stacked=self._stacked)
            # immutable one-row template scattered at admission; every
            # prefill chain starts from this fresh per-slot row
            self._fresh_row = lm.init_serve_state(cfg, b=1,
                                                  max_len=max_len,
                                                  per_slot=True,
                                                  stacked=self._stacked)
            self._pages = None
            self._alloc = None
        # physical page ids owned by each slot (refcounts in _alloc);
        # freed slots park here until _flush_freed zeroes their tables
        # and releases the pages (zombie-write safety, see _free)
        self._slot_pages: list[Optional[list[int]]] = [None] * max_slots
        self._pending_clear: list[int] = []
        # precomposed per-layer serve projections (A = (W M)^T): the
        # M·Wᵀ composition happens HERE, once at engine build — the
        # fused decode megakernel then does a single x @ A per token,
        # and the SAME pytree feeds the packed-prefill step so batched
        # ragged admission runs the fused prefill megakernel too
        self._decode_proj = lm.build_decode_proj(params, cfg,
                                                 stacked=self._stacked)
        # which implementation the jitted steps compiled — surfaced in
        # ``stats`` so bench runs can assert they measured the path
        # they claim (fused_kernel / jnp / exact / none)
        self._serve_paths = self._resolve_serve_paths()
        # likewise the layer-stacked param tree: interleaved once here
        # (a no-copy alias for the k=1 patterns) so the jitted steps
        # never re-stack weights per token
        self._step_params = params
        if self._stacked:
            self._step_params = dict(params)
            self._step_params["layers"] = lm.stack_layer_params(params,
                                                                cfg)

        pool_shardings = None
        if mesh is not None:
            from repro.parallel import serve_state_specs, make_shardings
            pool_shardings = make_shardings(
                serve_state_specs(self.pool, mesh), mesh)
            self.pool = jax.device_put(self.pool, pool_shardings)
            self.staging = jax.device_put(self.staging, pool_shardings)
            if self._paged:
                # the shared page pools carry no slot axis; replicate
                # them (page gathers/scatters are id-indexed)
                from jax.sharding import NamedSharding, PartitionSpec
                rep = NamedSharding(mesh, PartitionSpec())
                self._pages = jax.device_put(self._pages,
                                             {"k": rep, "v": rep})

        # prefix-hash -> state-snapshot store; snapshots are promoted
        # back to device with the pools' mesh sharding on a host-tier
        # hit, and evicted paged entries hand their pages back to the
        # allocator (repro/serving/prefix_cache.py)
        self.prefix_cache: Optional[PrefixCache] = None
        if self._pc_cfg is not None:
            self.prefix_cache = PrefixCache(
                self._pc_cfg, to_device=self._snapshot_to_device,
                release_pages=(self._alloc.release if self._paged
                               else None))

        self._slots: list[Optional[_Slot]] = [None] * max_slots
        self._active = np.zeros(max_slots, bool)
        self._temps = np.zeros(max_slots, np.float32)
        self._top_ks = np.zeros(max_slots, np.int32)
        self._top_ps = np.ones(max_slots, np.float32)
        self._toks = np.zeros(max_slots, np.int32)
        self._uids = np.zeros(max_slots, np.int32)
        self._prefill_order: list[int] = []    # slot idx, admission FIFO
        self._queue: list[Request] = []        # sorted by arrival_time
        self._key = jax.random.PRNGKey(seed)
        self._t0: Optional[float] = None
        self._ttfts: list[float] = []
        # -- overlap pipeline state (all None/empty when overlap=False) -
        # device-resident token feed: decode reads last step's sampled
        # tokens from here without a host round-trip
        self._feed = jnp.zeros((max_slots,), jnp.int32)
        # double-buffered host staging for packed chunk tokens
        self._pack = slot_ops.PackBuffer(max_slots, _next_pow2(max_len))
        self._next_chunk: Optional[dict] = None     # packed, undispatched
        self._pending_merge: Optional[dict] = None  # landed, unmerged
        self._inflight: Optional[dict] = None       # sampled, unfetched
        self._dispatch_seq = 0          # jitted dispatches issued so far
        self._stall_ms: list[float] = []        # per-retire blocked time
        self._depths: list[int] = []            # per-retire queue depth
        self._stats = {"decode_steps": 0, "decode_slot_steps": 0,
                       "prefill_tokens": 0, "prefill_chunks": 0,
                       "prefill_calls": 0, "prefill_padded_tokens": 0,
                       "prefill_rows_max": 0,
                       "max_prefill_tokens_per_step": 0,
                       "emitted_tokens": 0, "admitted": 0, "finished": 0,
                       "forked_requests": 0, "forked_tokens": 0}

        cfg_ = cfg  # closed over by the jitted steps

        def _constrain(tree):
            if pool_shardings is None:
                return tree
            return jax.lax.with_sharding_constraint(tree, pool_shardings)

        def _decode(params, proj, pool, toks, active, all_active):
            logits, new = lm.decode_step(params, cfg_, toks, pool,
                                         proj=proj)
            new = slot_ops.freeze_inactive(pool, new, active,
                                           all_active=all_active)
            return logits, _constrain(new)

        def _prefill(params, proj, staging, toks, idx, valid_len):
            # gather the P staged rows, advance them over one padded
            # (P, L) chunk, scatter them back — ONE device program per
            # step regardless of how many admissions are in flight;
            # with the precomposed proj the chunk runs the fused
            # prf_fused_prefill megakernel (one pallas_call per layer)
            sub = slot_ops.read_slots(staging, idx)
            logits, new = lm.prefill_chunk(params, cfg_, {"tokens": toks},
                                           sub, valid_len=valid_len,
                                           proj=proj)
            return logits, _constrain(slot_ops.write_slots(staging, new,
                                                           idx))

        def _commit(pool, staging, idx):
            # finished admissions: one fused gather+scatter promotes the
            # staged rows into the slot pool (the deferred merge of the
            # overlapped loop rides this same scatter)
            return _constrain(slot_ops.merge_slots(pool, staging, idx))

        def _reset(staging, fresh, idx):
            # one broadcast scatter seeds every slot admitted this step
            # — from the fresh one-row template, or from a cached prefix
            # snapshot (fork-on-admit: the prefix cache's O(1) fork IS
            # this scatter, repro/serving/prefix_cache.py)
            return _constrain(slot_ops.fork_slots(staging, fresh, idx))

        def _snap(staging, idx):
            # one-row snapshot gather for prefix capture; read_slots
            # keeps the slot axis, so the row round-trips through the
            # seed scatters above
            return slot_ops.read_slots(staging, idx)

        def _decode_paged(params, proj, pool, pages, toks, active,
                          all_active):
            # paged exact layout: graft the shared page pools into the
            # detached slot tree around the step, split them back out
            # after (pages are donated through, like the pool)
            st = lm.attach_kv_pages(pool, pages)
            logits, new = lm.decode_step(params, cfg_, toks, st,
                                         proj=proj)
            new, pages = lm.detach_kv_pages(new)
            new = slot_ops.freeze_inactive(pool, new, active,
                                           all_active=all_active)
            return logits, _constrain(new), pages

        def _prefill_paged(params, proj, staging, pages, toks, idx,
                           valid_len):
            sub = slot_ops.read_slots(staging, idx)
            logits, new = lm.prefill_chunk(
                params, cfg_, {"tokens": toks},
                lm.attach_kv_pages(sub, pages), valid_len=valid_len,
                proj=proj)
            new, pages = lm.detach_kv_pages(new)
            return (logits,
                    _constrain(slot_ops.write_slots(staging, new, idx)),
                    pages)

        def _seed_paged(staging, row, idx, tables):
            # paged admission/fork seed: broadcast the snapshot (or
            # fresh) row, but give every seeded slot its OWN page table
            # — shared prefix pages + freshly allocated growth pages
            k = idx.shape[0]
            rows = slot_ops.tree_slot_map(
                lambda p, axis: jnp.repeat(p, k, axis=axis), row)
            la = rows["layers"]
            rows["layers"] = la._replace(table=jnp.broadcast_to(
                tables[None], (la.table.shape[0],) + tables.shape))
            return _constrain(slot_ops.write_slots(staging, rows, idx))

        def _copy_pages(pages, src, dst):
            # copy-on-write at fork: duplicate the partial tail pages
            # ``src`` into ``dst`` across the k/v pools of every layer
            return {n: p.at[:, dst].set(jnp.take(p, src, axis=1))
                    for n, p in pages.items()}

        def _scatter_toks(feed, idx, vals):
            # merge first tokens into the device token feed
            return feed.at[idx].set(vals)

        def _row_keys(uids, counts):
            # schedule-invariant per-row sampling keys: (uid, token
            # index) — independent of step count and batch composition,
            # so a row's draws are identical under every scheduler
            base = self._key
            return jax.vmap(lambda u, n: jax.random.fold_in(
                jax.random.fold_in(base, u), n))(uids, counts)

        def _sample_plain(logits, uids, counts, temps):
            # greedy / plain-temperature rows only: skips the two
            # full-vocab sorts of the top-k/p masks on the hot loop
            greedy = jnp.argmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            keys = _row_keys(uids, counts)
            drawn = jax.vmap(jax.random.categorical)(keys, scaled)
            return jnp.where(temps > 0, drawn, greedy).astype(jnp.int32)

        def _sample(logits, uids, counts, temps, top_ks, top_ps):
            v = logits.shape[-1]
            greedy = jnp.argmax(logits, axis=-1)
            scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
            # per-row top-k: drop logits below the k-th largest
            # (top_k <= 0 disables; the mask is then all-True)
            desc = jnp.sort(scaled, axis=-1)[:, ::-1]
            kidx = jnp.clip(jnp.where(top_ks > 0, top_ks, v) - 1, 0, v - 1)
            kth = jnp.take_along_axis(desc, kidx[:, None], axis=-1)
            masked = jnp.where(scaled >= kth, scaled, -jnp.inf)
            # per-row nucleus: keep the smallest prefix of probability
            # mass >= top_p (top_p >= 1 disables)
            probs = jax.nn.softmax(masked, axis=-1)
            sp = jnp.sort(probs, axis=-1)[:, ::-1]
            cum = jnp.cumsum(sp, axis=-1)
            keep = ((cum - sp) < top_ps[:, None]) | (top_ps[:, None] >= 1.0)
            cutoff = jnp.min(jnp.where(keep, sp, jnp.inf), axis=-1,
                             keepdims=True)
            masked = jnp.where(probs >= cutoff, masked, -jnp.inf)
            keys = _row_keys(uids, counts)
            drawn = jax.vmap(jax.random.categorical)(keys, masked)
            return jnp.where(temps > 0, drawn, greedy).astype(jnp.int32)

        def _first_plain(logits, ridx, uids, counts, temps):
            return _sample_plain(jnp.take(logits, ridx, axis=0),
                                 uids, counts, temps)

        def _first(logits, ridx, uids, counts, temps, top_ks, top_ps):
            return _sample(jnp.take(logits, ridx, axis=0),
                           uids, counts, temps, top_ks, top_ps)

        if self._paged:
            self._decode_fn = jax.jit(_decode_paged,
                                      donate_argnums=(2, 3),
                                      static_argnums=(6,))
            self._prefill_fn = jax.jit(_prefill_paged,
                                       donate_argnums=(2, 3))
            self._seed_fn = jax.jit(_seed_paged, donate_argnums=(0,))
            self._copy_pages_fn = jax.jit(_copy_pages,
                                          donate_argnums=(0,))
        else:
            self._decode_fn = jax.jit(_decode, donate_argnums=(2,),
                                      static_argnums=(5,))
            self._prefill_fn = jax.jit(_prefill, donate_argnums=(2,))
        self._snap_fn = jax.jit(_snap)
        self._commit_fn = jax.jit(_commit, donate_argnums=(0,))
        self._reset_fn = jax.jit(_reset, donate_argnums=(0,))
        self._scatter_fn = jax.jit(_scatter_toks, donate_argnums=(0,))
        self._sample_fn = jax.jit(_sample)
        self._sample_plain_fn = jax.jit(_sample_plain)
        self._first_fn = jax.jit(_first)
        self._first_plain_fn = jax.jit(_first_plain)

    # -- introspection ----------------------------------------------------

    def _resolve_serve_paths(self) -> dict:
        """Name the attention implementation each jitted step compiled:
        ``fused_kernel`` (the prf_fused_prefill / prf_fused_decode
        megakernels against the engine-precomposed projections — what
        ``cfg.use_kernel`` always selects here, since the engine builds
        the projections at construction; the two-stage kernel path is
        reachable only through the lm-level ``fused=False`` oracle
        entry points, never through the engine), ``jnp`` (pure-XLA
        reference), ``exact`` (softmax over per-slot KV pages — no
        Pallas path), or ``none`` (no attention blocks, e.g. pure-RWKV
        stacks)."""
        cfg = self.cfg
        if not any(k in ("attn", "local") for k in cfg.layer_kinds()):
            path = "none"
        elif cfg.attn.kind == "exact":
            # "exact_paged": softmax over a block-granular page table
            # into the shared page pools (prefix-cache engines)
            path = "exact_paged" if self._paged else "exact"
        elif self._decode_proj is not None:
            path = "fused_kernel"
        else:
            path = "jnp"
        return {"prefill_path": path, "decode_path": path}

    def compiled_text(self, rows: int, length: int) -> dict[str, str]:
        """HLO text of the decode step and of a packed (rows, length)
        prefill chunk, as XLA compiles them for this engine's pools —
        e.g. to confirm that the Pallas kernels are in the program
        (``tpu_custom_call``) and were not interpreted."""
        toks = jnp.zeros((rows, length), jnp.int32)
        idx = jnp.arange(rows, dtype=jnp.int32)
        active = jnp.asarray(self._active)
        head = (self._step_params, self._decode_proj)
        pages = (self._pages,) if self._paged else ()
        dec = self._decode_fn.lower(*head, self.pool, *pages, self._feed,
                                    active, False)
        pre = self._prefill_fn.lower(*head, self.staging, *pages, toks,
                                     idx, None)
        return {"decode": dec.compile().as_text(),
                "prefill": pre.compile().as_text()}

    def _snapshot_to_device(self, tree):
        """Promote a host-tier prefix snapshot back to device, with the
        pools' mesh sharding when the engine runs sharded (the b=1 slot
        dims replicate under ``serve_state_specs``)."""
        if self.mesh is None:
            return jax.device_put(tree)
        from repro.parallel import serve_state_specs, make_shardings
        return jax.device_put(
            tree, make_shardings(serve_state_specs(tree, self.mesh),
                                 self.mesh))

    # -- clock ------------------------------------------------------------

    def _now(self) -> float:
        if self._t0 is None:
            self._t0 = time.monotonic()
        return time.monotonic() - self._t0

    # -- client API -------------------------------------------------------

    def submit(self, req: Union[Request, Sequence[int]], **kw) -> int:
        """Queue a request (or a bare token prompt). Returns its uid.

        Validates everything that would otherwise fail opaquely (or
        silently clamp) inside the jitted step functions: empty prompts,
        prompts that don't fit the per-slot ``max_len`` context budget
        alongside at least one generated token, out-of-vocab token ids,
        and degenerate sampling parameters.
        """
        if not isinstance(req, Request):
            req = Request(prompt=list(req), **kw)
        if len(req.prompt) == 0:
            raise ValueError("empty prompt: a request must carry at least "
                             "one prompt token")
        if len(req.prompt) + 1 > self.max_len:
            raise ValueError(
                f"prompt length {len(req.prompt)} does not fit max_len "
                f"{self.max_len}: a slot's context page must hold the "
                f"prompt plus at least one generated token "
                f"(prompt <= max_len - 1 = {self.max_len - 1})")
        lo, hi = min(req.prompt), max(req.prompt)
        if lo < 0 or hi >= self.cfg.vocab:
            raise ValueError(
                f"prompt token ids must lie in the vocab range "
                f"[0, {self.cfg.vocab}) (got min={lo}, max={hi}); "
                f"out-of-range ids would be silently clamped by the "
                f"embedding gather inside jit")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (admission "
                             "always samples the first token)")
        if req.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if req.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables)")
        if req.top_p <= 0:
            # top_p <= 0 would mask EVERY token to -inf and the row
            # would silently stream token 0
            raise ValueError("top_p must be > 0 (>= 1.0 disables)")
        bisect.insort(self._queue, req, key=lambda r: r.arrival_time)
        return req.uid

    def cancel(self, uid: int) -> Optional[RequestResult]:
        """Evict a queued, mid-prefill or mid-decode request. Returns its
        partial result (None if the uid is unknown).

        Under the overlapped loop a cancelled request's in-flight work
        is dropped, not flushed: tokens already sampled on device but
        not yet retired are discarded (no ``on_token`` callback), a
        packed-but-undispatched prefill chunk row is skipped at
        dispatch, and a landed-but-unmerged staging row is never
        committed — so the partial result holds exactly the tokens the
        host had observed, the same cut as the sequential scheduler.
        """
        for i, req in enumerate(self._queue):
            if req.uid == uid:
                self._queue.pop(i)
                return RequestResult(uid=uid, prompt=list(req.prompt),
                                     arrival_time=req.arrival_time,
                                     cancelled=True)
        for i, slot in enumerate(self._slots):
            if slot is not None and slot.req.uid == uid:
                res = slot.result
                res.cancelled = True
                res.finish_time = self._now()
                self._free(i)
                return res
        return None

    @property
    def num_active(self) -> int:
        return int(self._active.sum())

    @property
    def num_prefilling(self) -> int:
        return len(self._prefill_order)

    @property
    def has_work(self) -> bool:
        return (bool(self._queue)
                or any(s is not None for s in self._slots)
                or self._inflight is not None)

    def next_arrival(self) -> Optional[float]:
        return self._queue[0].arrival_time if self._queue else None

    @property
    def _pipeline_idle(self) -> bool:
        """No in-flight or staged work anywhere in the pipeline — safe
        to jump the clock to the next arrival."""
        return (self.num_active == 0 and not self._prefill_order
                and self._next_chunk is None
                and self._pending_merge is None
                and self._inflight is None)

    # -- scheduler --------------------------------------------------------

    def _free(self, i: int) -> None:
        self._slots[i] = None
        self._active[i] = False
        self._temps[i] = 0.0
        self._top_ks[i] = 0
        self._top_ps[i] = 1.0
        self._uids[i] = 0
        if i in self._prefill_order:
            self._prefill_order.remove(i)
        if self._paged and self._slot_pages[i] is not None:
            # don't release the pages yet: dispatches already enqueued
            # against this row (a lock-step decode, an in-flight chunk)
            # may still write through its table. _flush_freed zeroes the
            # table first — routing any zombie write to the garbage
            # page — then hands the pages back.
            self._pending_clear.append(i)

    def _flush_freed(self) -> None:
        """Zero the pool/staging page tables of slots freed since the
        last step, then release their pages. Runs at the head of every
        step, BEFORE admissions can reallocate the pages: the table
        resets are enqueued behind any straggling writes (single-stream
        dispatch order), so a reallocated page can never be clobbered by
        a freed row's in-flight tail."""
        if not self._paged or not self._pending_clear:
            return
        idx = jnp.asarray(sorted(set(self._pending_clear)), jnp.int32)
        self.pool = self._reset_fn(self.pool, self._fresh_row, idx)
        self.staging = self._reset_fn(self.staging, self._fresh_row, idx)
        self._dispatch_seq += 2
        for i in set(self._pending_clear):
            pages = self._slot_pages[i]
            self._slot_pages[i] = None
            if pages:
                self._alloc.release(pages)
        self._pending_clear.clear()

    def _activate(self, i: int) -> None:
        """Load slot i's sampling params into the batched host arrays."""
        slot = self._slots[i]
        self._active[i] = True
        self._temps[i] = slot.req.temperature
        self._top_ks[i] = slot.req.top_k
        self._top_ps[i] = slot.req.top_p
        self._uids[i] = slot.req.uid

    def _sample_one(self, req: Request, logits_row: Array,
                    count: int) -> int:
        """Sample one row with its schedule-invariant (uid, count) key.
        ``count`` is the row's token index (0 = the first token sampled
        at admission)."""
        uids = jnp.full((1,), req.uid, jnp.int32)
        counts = jnp.full((1,), count, jnp.int32)
        temps = jnp.full((1,), req.temperature, jnp.float32)
        if req.top_k <= 0 and req.top_p >= 1.0:
            return int(self._sample_plain_fn(logits_row, uids, counts,
                                             temps)[0])
        return int(self._sample_fn(
            logits_row, uids, counts, temps,
            jnp.full((1,), req.top_k, jnp.int32),
            jnp.full((1,), req.top_p, jnp.float32))[0])

    def _paged_admit_pages(self, req: Request, ent) -> tuple:
        """Build an admission's page table: the cached prefix's fully
        covered pages are SHARED (refcount retained), its partial tail
        page is queued for a copy-on-write duplication, and fresh pages
        cover the rest of prompt + generation budget. Returns (table
        (max_pages,) int32, owned page ids, [(src, dst)] tail copies).
        Raises NoFreePages (after trying a cache reclaim, with the
        match's own refcounts unwound) to defer the admission."""
        ps = self._page_size
        budget = min(req.max_new_tokens, self.max_len - len(req.prompt))
        n_total = -(-(len(req.prompt) + budget) // ps)
        n_shared = 0 if ent is None else len(ent.tokens) // ps
        shared = [] if ent is None else list(ent.pages[:n_shared])
        tail_src = (ent.pages[n_shared]
                    if ent is not None and len(ent.tokens) % ps else None)
        n_new = n_total - n_shared
        # Pin the match BEFORE any reclaim/alloc: without exclude=ent a
        # reclaim could evict the very entry being forked, dropping its
        # pages into the LIFO free list where alloc() re-issues them as
        # this request's writable growth pages (double-booked prefix
        # pages, silent KV corruption). The retains double as the
        # slot's own refs on the fully shared pages; the extra tail-src
        # ref keeps the CoW source alive even if a later admission in
        # the same batch evicts the entry — _admissions releases it
        # once the batched copy is dispatched.
        pinned = shared + ([] if tail_src is None else [tail_src])
        self._alloc.retain(pinned)
        try:
            if n_new > self._alloc.n_free:
                self.prefix_cache.reclaim_pages(self._alloc, n_new,
                                                exclude=ent)
            fresh = self._alloc.alloc(n_new)      # raises NoFreePages
        except NoFreePages:
            self._alloc.release(pinned)
            raise
        copies = [] if tail_src is None else [(tail_src, fresh[0])]
        own = shared + fresh
        table = np.zeros(self._max_pages, np.int32)
        table[:len(own)] = own
        return table, own, copies

    def _seed(self, row: dict, idxs: list, tables: list) -> None:
        """Seed staging rows ``idxs`` from the one-row state ``row`` in
        one broadcast scatter (paged rows also get their own tables)."""
        idx = jnp.asarray(idxs, jnp.int32)
        if self._paged:
            self.staging = self._seed_fn(self.staging, row, idx,
                                         jnp.asarray(np.stack(tables)))
        else:
            self.staging = self._reset_fn(self.staging, row, idx)
        self._dispatch_seq += 1

    def _admissions(self, now: float) -> None:
        """Reserve a free slot (freshly seeded staging row) for every
        arrived request, FIFO. With a prefix cache, admission first
        matches the longest cached prefix and seeds the staging row from
        its snapshot instead of the fresh template (fork-on-admit): the
        slot's cursor starts at the cached length and chunked prefill
        resumes from there, so only the un-cached suffix is computed.
        Same-entry admissions share one broadcast seed scatter; paged
        admissions allocate their page tables here and defer (stay
        queued) when the page pool is exhausted even after evicting
        cached prefixes."""
        fresh_adm: list[int] = []
        fresh_tables: list = []
        forks: dict[str, list] = {}    # entry key -> [ent, idxs, tables]
        copies: list[tuple[int, int]] = []
        while self._queue and self._queue[0].arrival_time <= now:
            free = [i for i in range(self.max_slots)
                    if self._slots[i] is None]
            if not free:
                break
            req = self._queue[0]
            ent = (self.prefix_cache.match(req.prompt)
                   if self.prefix_cache is not None else None)
            table = own = None
            if self._paged:
                try:
                    table, own, cps = self._paged_admit_pages(req, ent)
                except NoFreePages:
                    # backpressure: requeue (it never left the queue)
                    # and undo the match stat so the retry next step
                    # doesn't double-count
                    if ent is not None:
                        self.prefix_cache.hits -= 1
                    else:
                        self.prefix_cache.misses -= 1
                    break
                copies.extend(cps)
            self._queue.pop(0)
            i = free[0]
            result = RequestResult(uid=req.uid,
                                   prompt=list(map(int, req.prompt)),
                                   arrival_time=req.arrival_time)
            # exact-cache pages hold max_len keys: prompt + decoded tokens
            budget = min(req.max_new_tokens,
                         self.max_len - len(req.prompt))
            self._slots[i] = _Slot(req, result, budget)
            self._slot_pages[i] = own
            self._prefill_order.append(i)
            if ent is not None:
                self._slots[i].cursor = len(ent.tokens)
                self._stats["forked_requests"] += 1
                self._stats["forked_tokens"] += len(ent.tokens)
                grp = forks.setdefault(ent.key, [ent, [], []])
                grp[1].append(i)
                grp[2].append(table)
            else:
                fresh_adm.append(i)
                fresh_tables.append(table)
        if copies:
            # one batched CoW duplication for every forked tail page
            self._pages = self._copy_pages_fn(
                self._pages, jnp.asarray([s for s, _ in copies],
                                         jnp.int32),
                jnp.asarray([d for _, d in copies], jnp.int32))
            self._dispatch_seq += 1
            # drop the tail-src pins taken in _paged_admit_pages: the
            # copies are enqueued, and dispatch order protects their
            # source contents from any later page reuse
            self._alloc.release([s for s, _ in copies])
        if fresh_adm:
            self._seed(self._fresh_row, fresh_adm, fresh_tables)
        for ent, idxs, tables in forks.values():
            self._seed(self.prefix_cache.device_state(ent), idxs, tables)

    def _plan_prefill(self) -> list[tuple[int, int]]:
        """Token-budget packer: split this step's prompt-token budget
        across the staged admissions, FIFO. Returns [(slot, tokens)].

        Blocking mode (``chunk_tokens=None``) grants every staged
        admission its full remaining prompt. Chunked + bucketed mode
        COALESCES: every staged row gets the same pow-2 grant
        ``g = prev_pow2(chunk_tokens // rows)``, so all non-tail rows
        land in one shared length bucket with ZERO padding waste —
        ``prefill_batch_occupancy`` is 1.0 under ragged admission
        bursts until the rows' last partial chunks. Unbucketed chunked
        mode keeps the legacy FIFO ceil-shares (the serial bit-exact
        contract at ``prefill_rows=1``). Either way at most
        ``chunk_tokens`` prompt tokens total run between two decode
        steps (the invariant the latency benchmark measures).
        """
        staged = self._prefill_order
        if self.prefill_rows is not None:
            staged = staged[:self.prefill_rows]
        grants: list[tuple[int, int]] = []
        if self.chunk_tokens is None:
            for i in staged:
                slot = self._slots[i]
                grants.append((i, len(slot.req.prompt) - slot.cursor))
            return grants
        budget = self.chunk_tokens
        if self.bucket_prefill and staged and budget >= len(staged):
            # coalesced equal-length grants: one bucket, no padding
            g = 1 << ((budget // len(staged)).bit_length() - 1)
            for i in staged:
                slot = self._slots[i]
                grants.append((i, min(len(slot.req.prompt) - slot.cursor,
                                      g)))
            return grants
        for j, i in enumerate(staged):
            if budget <= 0:
                break
            slot = self._slots[i]
            rem = len(slot.req.prompt) - slot.cursor
            share = -(-budget // (len(staged) - j))      # ceil division
            t = min(rem, share)
            grants.append((i, t))
            budget -= t
        return grants

    def _record_prefill_stats(self, n_rows: int, spent: int,
                              l_pad: int) -> None:
        self._stats["prefill_tokens"] += spent
        self._stats["prefill_chunks"] += n_rows
        self._stats["prefill_calls"] += 1
        self._stats["prefill_padded_tokens"] += n_rows * l_pad
        self._stats["prefill_rows_max"] = max(
            self._stats["prefill_rows_max"], n_rows)
        self._stats["max_prefill_tokens_per_step"] = max(
            self._stats["max_prefill_tokens_per_step"], spent)

    def _maybe_capture(self, i: int) -> None:
        """Capture a prefix snapshot of slot i's staging row when its
        prefill cursor just crossed a ``block_tokens`` boundary (or, with
        ``capture_final``, completed the prompt — the multi-turn reuse
        point). The snapshot is a one-row gather of the staging pool;
        paged rows additionally retain their covering prefix pages so
        the entry keeps them alive after the donor slot is freed."""
        pc = self.prefix_cache
        if pc is None:
            return
        slot = self._slots[i]
        cur = slot.cursor
        bt = pc.cfg.block_tokens
        final = cur == len(slot.req.prompt)
        if not ((cur > 0 and cur % bt == 0)
                or (final and pc.cfg.capture_final)):
            return
        tokens = slot.req.prompt[:cur]
        if pc.has(tokens):
            return
        snap = self._snap_fn(self.staging, jnp.asarray([i], jnp.int32))
        self._dispatch_seq += 1
        if self._paged:
            n_cov = -(-cur // self._page_size)
            pages = list(self._slot_pages[i][:n_cov])
            self._alloc.retain(pages)
            pc.put(tokens, snap, pages=pages,
                   page_bytes=n_cov * self._page_bytes_each)
        else:
            pc.put(tokens, snap)

    # -- sequential scheduler ---------------------------------------------

    def _prefill_work(self) -> None:
        """Advance every scheduled admission by its granted chunk in ONE
        padded batched ``prefill_chunk`` call, then commit + activate the
        admissions whose prompts finished (also batched)."""
        grants = self._plan_prefill()
        if not grants:
            return
        ts = np.asarray([t for _, t in grants], np.int32)
        l_pad = int(ts.max())
        if self.bucket_prefill:
            l_pad = _next_pow2(l_pad)
        toks = self._pack.pack(
            [self._slots[i].req.prompt[self._slots[i].cursor:
                                       self._slots[i].cursor + t]
             for i, t in grants], l_pad)
        # all-full rows take the legacy unpadded path (bit-exact with the
        # serial schedule); ragged rows carry per-row valid lengths
        vl = None if (ts == l_pad).all() else jnp.asarray(ts)
        idx = jnp.asarray([i for i, _ in grants], jnp.int32)
        if self._paged:
            logits, self.staging, self._pages = self._prefill_fn(
                self._step_params, self._decode_proj, self.staging,
                self._pages, jnp.asarray(toks), idx, vl)
        else:
            logits, self.staging = self._prefill_fn(
                self._step_params, self._decode_proj, self.staging,
                jnp.asarray(toks), idx, vl)
        self._dispatch_seq += 1
        self._record_prefill_stats(len(grants), int(ts.sum()), l_pad)

        done: list[tuple[int, int]] = []
        for r, (i, t) in enumerate(grants):
            slot = self._slots[i]
            slot.cursor += t
            self._maybe_capture(i)
            if slot.cursor == len(slot.req.prompt):
                done.append((r, i))
        if not done:
            return
        self.pool = self._commit_fn(
            self.pool, self.staging,
            jnp.asarray([i for _, i in done], jnp.int32))
        self._dispatch_seq += 1
        for r, i in done:
            self._prefill_order.remove(i)
            self._finish_admission(i, logits[r:r + 1])

    def _finish_admission(self, i: int, logits: Array) -> None:
        """Activate pool row i (already committed from staging). Blocks
        on the sampled first token — readiness, not dispatch — before
        stamping its time."""
        slot = self._slots[i]
        first = self._sample_one(slot.req, logits, count=0)
        now = self._now()
        if slot.req.on_token is not None:
            slot.req.on_token(first, now)
        slot.result.admit_time = now
        slot.result.tokens = [first]
        slot.result.token_times = [now]
        slot.emitted = 1
        self._ttfts.append(now - slot.req.arrival_time)
        self._activate(i)
        self._toks[i] = first
        self._stats["emitted_tokens"] += 1
        self._stats["admitted"] += 1

    # -- overlapped scheduler ---------------------------------------------

    def _retire(self, finished: list[RequestResult]) -> None:
        """Fetch the one-step-delayed token buffers, append the now-ready
        tokens, evict finished rows. The ONLY blocking point of the
        overlapped loop; the blocked time is the step's decode stall."""
        rec = self._inflight
        if rec is None:
            return
        self._inflight = None
        t0 = time.perf_counter()
        first = rec["first"]
        dec = rec["decode"]
        first_np = np.asarray(first[2]) if first is not None else None
        dec_np = np.asarray(dec[2]) if dec is not None else None
        self._stall_ms.append((time.perf_counter() - t0) * 1e3)
        self._depths.append(self._dispatch_seq - rec["seq"])
        now = self._now()
        done_now: set[int] = set()
        if first is not None:
            for i, uid, tok in zip(first[0], first[1], first_np):
                slot = self._slots[i]
                if slot is None or slot.req.uid != uid:
                    continue               # cancelled while in flight
                tok = int(tok)
                if slot.req.on_token is not None:
                    slot.req.on_token(tok, now)
                slot.result.admit_time = now
                slot.result.tokens = [tok]
                slot.result.token_times = [now]
                self._ttfts.append(now - slot.req.arrival_time)
                self._toks[i] = tok
                self._stats["emitted_tokens"] += 1
                self._stats["admitted"] += 1
                if self._done(slot):
                    # finished on its first token: the decode that ran
                    # concurrently was speculative — drop its token
                    done_now.add(i)
                    finished.append(self._finish(i))
        if dec is not None:
            self._stats["decode_steps"] += 1
            self._stats["decode_slot_steps"] += len(dec[0])
            for i, uid in zip(dec[0], dec[1]):
                if i in done_now:
                    continue
                slot = self._slots[i]
                if slot is None or slot.req.uid != uid:
                    continue               # cancelled while in flight
                tok = int(dec_np[i])
                if slot.req.on_token is not None:
                    slot.req.on_token(tok, now)
                slot.result.tokens.append(tok)
                slot.result.token_times.append(now)
                self._toks[i] = tok
                self._stats["emitted_tokens"] += 1
                if self._done(slot):
                    finished.append(self._finish(i))

    def _merge_pending(self) -> Optional[tuple]:
        """Commit admissions whose final chunk landed last step into the
        slot pool (one deferred merge scatter), sample their first
        tokens from the saved final-chunk logits, and scatter them into
        the device token feed — all dispatched AHEAD of this step's
        decode. Returns the retire record (slots, uids, tokens_dev)."""
        pm = self._pending_merge
        if pm is None:
            return None
        self._pending_merge = None
        keep = [(i, uid, r) for i, uid, r in pm["rows"]
                if self._slots[i] is not None
                and self._slots[i].req.uid == uid]
        if not keep:
            return None
        idx_np = np.asarray([i for i, _, _ in keep], np.int32)
        idx = jnp.asarray(idx_np)
        self.pool = self._commit_fn(self.pool, self.staging, idx)
        self._dispatch_seq += 1
        ridx = jnp.asarray([r for _, _, r in keep], jnp.int32)
        uids = np.asarray([uid for _, uid, _ in keep], np.int32)
        counts = np.zeros(len(keep), np.int32)       # first token: index 0
        reqs = [self._slots[i].req for i, _, _ in keep]
        temps = np.asarray([q.temperature for q in reqs], np.float32)
        tks = np.asarray([q.top_k for q in reqs], np.int32)
        tps = np.asarray([q.top_p for q in reqs], np.float32)
        if (tks > 0).any() or (tps < 1.0).any():
            toks = self._first_fn(pm["logits"], ridx, jnp.asarray(uids),
                                  jnp.asarray(counts), jnp.asarray(temps),
                                  jnp.asarray(tks), jnp.asarray(tps))
        else:
            toks = self._first_plain_fn(pm["logits"], ridx,
                                        jnp.asarray(uids),
                                        jnp.asarray(counts),
                                        jnp.asarray(temps))
        self._dispatch_seq += 1
        seq = self._dispatch_seq        # producing dispatch, for depth
        self._feed = self._scatter_fn(self._feed, idx, toks)
        self._dispatch_seq += 1
        for i, _, _ in keep:
            self._activate(i)
            self._slots[i].emitted = 1
        return (list(idx_np), list(uids), toks, seq)

    def _dispatch_decode(self) -> Optional[tuple]:
        """Enqueue one batched decode + sample over the active rows,
        reading the token feed straight from device. Returns the retire
        record (rows, uids, tokens_dev) fetched NEXT step."""
        rows = np.nonzero(self._active)[0]
        if rows.size == 0:
            return None
        counts = np.zeros(self.max_slots, np.int32)
        for i in rows:
            counts[i] = self._slots[i].emitted
        if self._paged:
            logits, self.pool, self._pages = self._decode_fn(
                self._step_params, self._decode_proj, self.pool,
                self._pages, self._feed, jnp.asarray(self._active),
                bool(self._active.all()))
        else:
            logits, self.pool = self._decode_fn(
                self._step_params, self._decode_proj, self.pool,
                self._feed, jnp.asarray(self._active),
                bool(self._active.all()))
        self._dispatch_seq += 1
        uids = jnp.asarray(self._uids)
        counts_j = jnp.asarray(counts)
        if (self._top_ks > 0).any() or (self._top_ps < 1.0).any():
            toks = self._sample_fn(logits, uids, counts_j,
                                   jnp.asarray(self._temps),
                                   jnp.asarray(self._top_ks),
                                   jnp.asarray(self._top_ps))
        else:
            toks = self._sample_plain_fn(logits, uids, counts_j,
                                         jnp.asarray(self._temps))
        self._dispatch_seq += 1
        # the sampled buffer IS the next feed: merged rows' first tokens
        # are scattered on top next step, inactive rows are don't-care
        self._feed = toks
        for i in rows:
            self._slots[i].emitted += 1
        return (list(rows), [int(self._uids[i]) for i in rows], toks,
                self._dispatch_seq)

    def _dispatch_prefill(self) -> None:
        """Enqueue the chunk packed last step (behind this step's
        decode). Rows cancelled since packing are dropped; rows whose
        prompt completes queue the deferred merge for next step."""
        ch = self._next_chunk
        if ch is None:
            return
        self._next_chunk = None
        live = [j for j, (i, uid, _) in enumerate(ch["grants"])
                if self._slots[i] is not None
                and self._slots[i].req.uid == uid]
        if not live:
            return
        grants = [ch["grants"][j] for j in live]
        toks = ch["toks"]
        if len(live) != len(ch["grants"]):
            toks = toks[live]
        ts = np.asarray([t for _, _, t in grants], np.int32)
        l_pad = ch["l_pad"]
        vl = None if (ts == l_pad).all() else jnp.asarray(ts)
        idx = jnp.asarray([i for i, _, _ in grants], jnp.int32)
        if self._paged:
            logits, self.staging, self._pages = self._prefill_fn(
                self._step_params, self._decode_proj, self.staging,
                self._pages, jnp.asarray(toks), idx, vl)
        else:
            logits, self.staging = self._prefill_fn(
                self._step_params, self._decode_proj, self.staging,
                jnp.asarray(toks), idx, vl)
        self._dispatch_seq += 1
        self._record_prefill_stats(len(grants), int(ts.sum()), l_pad)
        done: list[tuple[int, int, int]] = []
        for r, (i, uid, t) in enumerate(grants):
            slot = self._slots[i]
            slot.cursor += t
            self._maybe_capture(i)
            if slot.cursor == len(slot.req.prompt):
                self._prefill_order.remove(i)
                done.append((i, uid, r))
        if done:
            self._pending_merge = {"rows": done, "logits": logits}

    def _pack_next_chunk(self) -> None:
        """Plan + pack the NEXT prefill chunk into the idle half of the
        double buffer while this step's chunk is still in flight."""
        grants = self._plan_prefill()
        if not grants:
            return
        ts = np.asarray([t for _, t in grants], np.int32)
        l_pad = int(ts.max())
        if self.bucket_prefill:
            l_pad = _next_pow2(l_pad)
        toks = self._pack.pack(
            [self._slots[i].req.prompt[self._slots[i].cursor:
                                       self._slots[i].cursor + t]
             for i, t in grants], l_pad)
        self._next_chunk = {
            "grants": [(i, self._slots[i].req.uid, t) for i, t in grants],
            "toks": toks, "l_pad": l_pad}

    def _step_overlap(self) -> list[RequestResult]:
        """One turn of the pipelined loop — see the module docstring's
        retire/admit/merge/decode/prefill/pack timeline."""
        finished: list[RequestResult] = []
        self._retire(finished)
        self._flush_freed()
        self._admissions(self._now())
        first_rec = self._merge_pending()
        decode_rec = self._dispatch_decode()
        self._dispatch_prefill()
        self._pack_next_chunk()
        if first_rec is not None or decode_rec is not None:
            # depth baseline: the EARLIEST producing sample dispatch —
            # everything enqueued after it (token-feed scatter, prefill
            # chunk) is work the device queue runs ahead with
            seq = min(r[3] for r in (first_rec, decode_rec)
                      if r is not None)
            self._inflight = {"first": first_rec, "decode": decode_rec,
                              "seq": seq}
        return finished

    def flush(self) -> list[RequestResult]:
        """Drain the overlap pipeline's in-flight tail without
        dispatching new work: retire the delayed token buffer, apply any
        pending merge (whose first tokens are then retired too). After
        ``flush()`` every token produced so far is host-visible. No-op
        on the sequential scheduler. Returns newly finished results."""
        finished: list[RequestResult] = []
        while self._inflight is not None or self._pending_merge is not None:
            self._retire(finished)
            rec = self._merge_pending()
            if rec is not None:
                self._inflight = {"first": rec, "decode": None,
                                  "seq": rec[3]}
        return finished

    # -- decode -----------------------------------------------------------

    def step(self) -> list[RequestResult]:
        """Admit what has arrived, advance prefill and decode, evict
        finished sequences. Returns newly finished results (possibly
        empty). Sequential mode runs one packed prefill chunk then one
        blocking batched decode; overlap mode runs the pipelined
        retire/merge/dispatch turn (module docstring)."""
        if self.overlap:
            return self._step_overlap()
        finished: list[RequestResult] = []
        self._flush_freed()
        self._admissions(self._now())
        self._prefill_work()
        # admission may already exhaust a request (budget/eos on token 1)
        for i, slot in enumerate(self._slots):
            if slot is not None and self._active[i] and self._done(slot):
                finished.append(self._finish(i))
        if not self._active.any():
            return finished

        # static all-active flag: a fully occupied pool skips the
        # pool-wide freeze select (bit-identical either way)
        counts = np.zeros(self.max_slots, np.int32)
        for i in np.nonzero(self._active)[0]:
            counts[i] = self._slots[i].emitted
        if self._paged:
            logits, self.pool, self._pages = self._decode_fn(
                self._step_params, self._decode_proj, self.pool,
                self._pages, jnp.asarray(self._toks),
                jnp.asarray(self._active), bool(self._active.all()))
        else:
            logits, self.pool = self._decode_fn(
                self._step_params, self._decode_proj, self.pool,
                jnp.asarray(self._toks), jnp.asarray(self._active),
                bool(self._active.all()))
        self._dispatch_seq += 1
        # host-side check: only pay the full-vocab sort/cumsum masks when
        # some active row actually uses top-k/p (the masks are identity
        # at the defaults, so both paths sample identically)
        if (self._top_ks > 0).any() or (self._top_ps < 1.0).any():
            toks_dev = self._sample_fn(logits, jnp.asarray(self._uids),
                                       jnp.asarray(counts),
                                       jnp.asarray(self._temps),
                                       jnp.asarray(self._top_ks),
                                       jnp.asarray(self._top_ps))
        else:
            toks_dev = self._sample_plain_fn(logits,
                                             jnp.asarray(self._uids),
                                             jnp.asarray(counts),
                                             jnp.asarray(self._temps))
        self._dispatch_seq += 1
        seq_at_sample = self._dispatch_seq
        # block on token READINESS before stamping times (under async
        # dispatch an unblocked perf_counter delta would time the
        # enqueue, not the token)
        t0 = time.perf_counter()
        toks = np.asarray(toks_dev)
        self._stall_ms.append((time.perf_counter() - t0) * 1e3)
        self._depths.append(self._dispatch_seq - seq_at_sample)
        now = self._now()
        n_act = int(self._active.sum())
        self._stats["decode_steps"] += 1
        self._stats["decode_slot_steps"] += n_act
        for i in np.nonzero(self._active)[0]:
            slot = self._slots[i]
            tok = int(toks[i])
            if slot.req.on_token is not None:
                slot.req.on_token(tok, now)
            slot.result.tokens.append(tok)
            slot.result.token_times.append(now)
            slot.emitted += 1
            self._toks[i] = tok
            self._stats["emitted_tokens"] += 1
            if self._done(slot):
                finished.append(self._finish(i))
        return finished

    def _done(self, slot: _Slot) -> bool:
        toks = slot.result.tokens
        if len(toks) >= slot.budget:
            return True
        return slot.req.eos_id is not None and toks[-1] == slot.req.eos_id

    def _finish(self, i: int) -> RequestResult:
        res = self._slots[i].result
        res.finish_time = self._now()
        self._free(i)
        self._stats["finished"] += 1
        return res

    # -- batch runner -----------------------------------------------------

    def run(self, realtime: bool = False) -> list[RequestResult]:
        """Drive ``step()`` until queue, slots and the overlap pipeline
        drain.

        ``realtime=True`` honors future ``arrival_time``s by sleeping
        while the pool is empty (Poisson-traffic benchmarking); otherwise
        arrival order is respected but waits are skipped.
        """
        results: list[RequestResult] = []
        while self.has_work:
            if self._pipeline_idle and self._queue:
                wait = self._queue[0].arrival_time - self._now()
                if wait > 0:
                    if realtime:
                        time.sleep(wait)
                    else:
                        self._t0 -= wait       # jump the clock forward
            results.extend(self.step())
        return results

    # -- metrics ----------------------------------------------------------

    @property
    def stats(self) -> dict:
        s = dict(self._stats)
        s.update(self._serve_paths)
        s["overlap"] = self.overlap
        s["paged_kv"] = self._paged
        if self.prefix_cache is not None:
            s.update(self.prefix_cache.stats)
        if self._paged:
            s["kv_page_size"] = self._page_size
            s["kv_pages_total"] = self._alloc.n_pages
            s["kv_pages_free"] = self._alloc.n_free
        steps = max(s["decode_steps"], 1)
        # fraction of slot-steps that carried a live sequence
        s["mean_occupancy"] = (s["decode_slot_steps"]
                               / (steps * self.max_slots))
        # fraction of the padded (P x L) prefill compute spent on real
        # prompt tokens, and how many admissions each call advanced
        s["prefill_batch_occupancy"] = (
            s["prefill_tokens"] / s["prefill_padded_tokens"]
            if s["prefill_padded_tokens"] else 1.0)
        s["prefill_rows_per_call"] = (
            s["prefill_chunks"] / s["prefill_calls"]
            if s["prefill_calls"] else 0.0)
        if self._ttfts:
            s["ttft_p50"] = float(np.percentile(self._ttfts, 50))
            s["ttft_p99"] = float(np.percentile(self._ttfts, 99))
        # per-step pipeline counters: how long the host blocked for the
        # token buffer (readiness stall) and how many dispatches the
        # device queue ran ahead of the fetched buffer
        if self._stall_ms:
            s["decode_stall_ms_p50"] = float(np.percentile(
                self._stall_ms, 50))
            s["decode_stall_ms_p99"] = float(np.percentile(
                self._stall_ms, 99))
            s["decode_stall_ms_max"] = float(np.max(self._stall_ms))
        if self._depths:
            s["dispatch_depth_mean"] = float(np.mean(self._depths))
            s["dispatch_depth_max"] = int(np.max(self._depths))
        return s
