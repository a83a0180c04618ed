"""Serving launcher: thin CLI over the continuous-batching engine.

Demonstrates the paper's O(1)-state decoding at the system level: with a
PRF kernel the per-sequence serving state is (m x d_v) per head
regardless of context length, and ``repro.serving.ServingEngine``
multiplexes many sequences of different lengths over one batched decode
step — admitting and evicting mid-decode. Compare ``--kernel exact``
(per-slot KV-cache pages) vs ``--kernel darkformer`` (O(1) PRF state).
Design doc: docs/serving.md.

Examples:
  # 8 heterogeneous requests over 4 slots, greedy
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 8 --slots 4 --prompt-len 16-64 --gen 32

  # Poisson open-loop traffic at 2 req/s
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 16 --slots 4 --rate 2.0

  # prefix-heavy traffic: fork the shared 96-token prompt from the cache
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
      --requests 16 --chunk-tokens 32 --prefix-cache --shared-prefix 96
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import numpy as np

from repro import configs as cfgs
from repro.models import lm
from repro.parallel import param_specs, make_shardings
from repro.serving import PrefixCacheConfig, ServingEngine
from repro.serving.request import shared_prefix_requests, \
    synthetic_requests
from repro import checkpoint as ckpt_lib
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import setup_compile_cache


def _parse_range(spec: str) -> tuple[int, int]:
    """'64' -> (64, 64); '16-64' -> (16, 64)."""
    if "-" in spec:
        lo, hi = spec.split("-", 1)
        return int(lo), int(hi)
    return int(spec), int(spec)


def main(argv: list[str] | None = None):
    """Serve synthetic traffic as the CLI arguments say and print the
    report. Returns (engine, results) for callers that check them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--kernel", default=None,
                    help="exact|performer|darkformer|lfk (default: config)")
    ap.add_argument("--dtype", default=None,
                    help="param/activation dtype, e.g. float32 "
                         "(default: config)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (max concurrent sequences)")
    ap.add_argument("--max-len", type=int, default=256,
                    help="per-slot context budget (prompt + generated)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", default="16-64",
                    help="prompt length or lo-hi range")
    ap.add_argument("--gen", default="32", help="new tokens or lo-hi range")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = all at t=0")
    ap.add_argument("--realtime", action="store_true",
                    help="sleep through arrival gaps instead of skipping")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill: spend at most N prompt tokens "
                         "per engine step so admissions interleave with "
                         "decode (default: blocking whole-prompt prefill)")
    ap.add_argument("--prefill-rows", type=int, default=None,
                    help="cap on staged admissions sharing one batched "
                         "prefill call (default: all staged; 1 = serial "
                         "one-admission-per-step schedule)")
    ap.add_argument("--no-bucket-prefill", action="store_true",
                    help="disable pow-2 bucketing of packed prefill chunk "
                         "lengths (more recompiles, zero padding waste)")
    ap.add_argument("--overlap", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="pipelined step loop: concurrent prefill/decode "
                         "dispatch, double-buffered chunk packing, "
                         "one-step-delayed non-blocking token readback "
                         "(--no-overlap = sequential reference scheduler; "
                         "token streams are identical either way)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route prefill/decode through the Pallas kernels "
                         "(decode = the fused prf_fused_decode megakernel "
                         "with engine-precomposed projections); PRF kinds "
                         "only — warns and is ignored for --kernel exact, "
                         "whose softmax decode has no Pallas path")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="capture prefix snapshots at block boundaries "
                         "and admit later requests sharing a cached "
                         "prefix by forking its state (O(1) for PRF "
                         "kinds; exact switches to paged KV with "
                         "copy-on-write page sharing)")
    ap.add_argument("--prefix-block", type=int, default=16,
                    help="prefix-cache capture/match granularity in "
                         "tokens (align with --chunk-tokens grants)")
    ap.add_argument("--prefix-device-mb", type=int, default=64,
                    help="device-tier snapshot budget (MiB) before LRU "
                         "demotion to host")
    ap.add_argument("--prefix-host-mb", type=int, default=256,
                    help="host-tier snapshot budget (MiB) before LRU "
                         "eviction")
    ap.add_argument("--page-size", type=int, default=16,
                    help="exact paged-KV page size in tokens "
                         "(prefix-cache engines only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="generate prefix-heavy traffic: N-token shared "
                         "prompt prefix on ~80%% of requests (0 = fully "
                         "random prompts)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k sampling (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus sampling (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--load", default=None, help="checkpoint dir")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = cfgs.get_config(args.arch, reduced=args.reduced)
    if args.kernel:
        # of FEATURE_KINDS, only these have a prefill/decode state path
        # (trig/random/constant are training-time baselines)
        servable = ("exact", "performer", "darkformer", "lfk")
        if args.kernel not in servable:
            raise SystemExit(f"unservable --kernel {args.kernel!r} "
                             f"(choose from {', '.join(servable)})")
        cfg = cfgs.darkify(cfg, args.kernel, cfg.attn.num_features)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    if args.use_kernel:
        if cfg.attn.kind == "exact":
            # previously accepted silently while doing nothing — the
            # exact softmax decode has no Pallas path to select
            print("warning: --use-kernel has no effect with the 'exact' "
                  "kernel (Pallas paths exist for the PRF kinds only); "
                  "ignoring the flag", file=sys.stderr)
        else:
            cfg = dataclasses.replace(cfg, use_kernel=True)
    if cfg.modality != "text":
        raise SystemExit("serving engine drives text decode only")
    mesh = mesh_lib.make_local_mesh(args.mesh_data, args.mesh_model)

    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.load:
        params, _ = ckpt_lib.restore_checkpoint(args.load, params)
    pshard = make_shardings(
        param_specs(params, mesh, moe=cfg.moe is not None), mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, pshard)

    # a non-trivial mesh shards the slot + staging pools too (pools are
    # device_put per serve_state_specs and constrained inside the jitted
    # steps); a 1x1 mesh keeps the single-device fast path
    pool_mesh = mesh if args.mesh_data * args.mesh_model > 1 else None
    pc = None
    if args.prefix_cache:
        pc = PrefixCacheConfig(block_tokens=args.prefix_block,
                               device_bytes=args.prefix_device_mb << 20,
                               host_bytes=args.prefix_host_mb << 20,
                               page_size=args.page_size)
    engine = ServingEngine(params, cfg, max_slots=args.slots,
                           max_len=args.max_len,
                           chunk_tokens=args.chunk_tokens,
                           seed=args.seed, mesh=pool_mesh,
                           prefill_rows=args.prefill_rows,
                           bucket_prefill=not args.no_bucket_prefill,
                           overlap=args.overlap, prefix_cache=pc)
    if args.shared_prefix > 0:
        reqs = shared_prefix_requests(
            args.requests, cfg.vocab, seed=args.seed, rate=args.rate,
            prefix_len=args.shared_prefix,
            suffix_range=_parse_range(args.prompt_len),
            gen_range=_parse_range(args.gen),
            temperature=args.temperature)
    else:
        reqs = synthetic_requests(
            args.requests, cfg.vocab, seed=args.seed, rate=args.rate,
            prompt_range=_parse_range(args.prompt_len),
            gen_range=_parse_range(args.gen),
            temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p)
    try:
        for r in reqs:
            engine.submit(r)
    except ValueError as e:                    # e.g. prompt >= max_len
        raise SystemExit(f"bad request: {e}")

    print(f"serving {args.requests} requests over {args.slots} slots "
          f"(kernel={cfg.attn.kind}, dtype={cfg.dtype}, "
          f"max_len={args.max_len}, "
          f"rate={args.rate or 'batch'}"
          + (f", mesh={args.mesh_data}x{args.mesh_model}" if pool_mesh
             is not None else "") + ")")
    results = engine.run(realtime=args.realtime)

    for res in sorted(results, key=lambda r: r.uid):
        span = res.finish_time - res.arrival_time
        print(f"  req {res.uid}: prompt={len(res.prompt)} "
              f"gen={len(res.tokens)} ttft={res.ttft * 1e3:.0f}ms "
              f"span={span:.2f}s tokens[:8]={res.tokens[:8]}")

    st = engine.stats
    print(f"attention paths: prefill={st['prefill_path']} "
          f"decode={st['decode_path']} "
          f"scheduler={'overlap' if st['overlap'] else 'sequential'}")
    if "decode_stall_ms_p50" in st:
        print(f"decode stall (host blocked on token readiness): "
              f"p50={st['decode_stall_ms_p50']:.2f}ms "
              f"p99={st['decode_stall_ms_p99']:.2f}ms "
              f"max={st['decode_stall_ms_max']:.2f}ms; "
              f"dispatch depth mean={st['dispatch_depth_mean']:.1f} "
              f"max={st['dispatch_depth_max']}")
    tpots = np.array([t for r in results for t in r.tpots])
    span = max(r.finish_time for r in results) - min(
        r.arrival_time for r in results)
    print(f"throughput: {st['emitted_tokens'] / max(span, 1e-9):.1f} tok/s "
          f"({st['emitted_tokens']} tokens in {span:.2f}s)")
    if tpots.size:
        print(f"per-token latency: p50={np.percentile(tpots, 50) * 1e3:.1f}ms "
              f"p99={np.percentile(tpots, 99) * 1e3:.1f}ms")
    if "ttft_p50" in st:
        print(f"ttft: p50={st['ttft_p50'] * 1e3:.0f}ms "
              f"p99={st['ttft_p99'] * 1e3:.0f}ms")
    if "prefix_hits" in st:
        line = (f"prefix cache: hit rate "
                f"{st['prefix_hit_rate'] * 100:.0f}% "
                f"({st['prefix_hits']}/{st['prefix_hits'] + st['prefix_misses']}), "
                f"{st['forked_tokens']} prompt tokens forked over "
                f"{st['forked_requests']} requests; "
                f"{st['prefix_entries']} entries "
                f"({st['prefix_device_bytes'] >> 10}KiB dev / "
                f"{st['prefix_host_bytes'] >> 10}KiB host), "
                f"{st['prefix_evictions']} evictions")
        if st.get("paged_kv"):
            line += (f"; paged KV: {st['kv_pages_total']} pages x "
                     f"{st['kv_page_size']} tok, "
                     f"{st['kv_pages_free']} free")
        print(line)
    print(f"slot occupancy: {st['mean_occupancy'] * 100:.0f}% over "
          f"{st['decode_steps']} decode steps")
    print(f"prefill: {st['prefill_tokens']} tokens in "
          f"{st['prefill_chunks']} chunks over {st['prefill_calls']} "
          f"batched calls ({st['prefill_rows_per_call']:.1f} rows/call, "
          f"batch occupancy {st['prefill_batch_occupancy'] * 100:.0f}%, "
          f"max {st['max_prefill_tokens_per_step']} tokens per step)")
    return engine, results


if __name__ == "__main__":
    main()
