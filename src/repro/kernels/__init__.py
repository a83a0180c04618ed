"""Pallas TPU kernels for the PRF-attention hot spots (+ jnp oracles).

Kernels (each: <name>.py pallas_call + BlockSpec, oracle in ref.py, jit'd
differentiable wrapper in ops.py):

  * linear_attn_scan  — chunked causal linear attention (the O(Lmd) scan
    that replaces the softmax O(L^2 d) matmuls; paper Fig. 1): the
    training pair (forward and a backward of its own, one KV group per
    grid row) and the carried-state form that serving prefill resumes
  * prf_featmap       — fused phi(x) = exp(W Mx - ||Mx||^2/2 - c)/sqrt(m)
  * prf_decode_step   — fused one-token serving update of the (S, z)
    prefix state with online-stabilizer rescale (forward-only)
  * prf_fused_decode  — the decode MEGAKERNEL: projection -> exp feature
    map with in-kernel running-max stabilizer -> rank-1 (S, z) update ->
    readout, pool aliased in place (forward-only; subsumes the
    prf_featmap + prf_decode_step pair on the serving hot path)
"""
from repro.kernels import ops, ref
from repro.kernels.ops import (fused_prf_decode, linear_attention_causal,
                               linear_attention_decode_step, prf_featmap)
