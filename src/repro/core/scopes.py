"""Names of the training step's layers in the compiled program.

Each layer of the step runs under a ``jax.named_scope`` of one of these
names. A scope adds only metadata: XLA keeps it in every op's
``op_name`` (under transform wrappers such as ``jvp(mlp)`` or
``transpose(jvp(prf_mix))`` for the backward pass), and the profiler's
device trace carries that as the op's ``tf_op``, so device time can be
read per layer. The step's scopes are flat: none opens inside another,
so the innermost of them on an op's path names its layer.

An expert layer names its parts inside ``mlp`` (``MOE_SCOPES``); the
step's layers still end at ``mlp``, which keeps counting all of it.
"""

EMBED = "embed"                  # token gather (scatter-add backward)
ATTN_IN = "attn_in"              # pre-norm, q/k/v projections, RoPE
PRF_FEATURES = "prf_features"    # M and W projections, stabilizer, exp
PRF_MIX = "prf_mix"              # causal mix: chunk scores, prefix state
ATTN_OUT = "attn_out"            # head merge, wo, residual
MLP = "mlp"                      # pre-norm, FFN (or experts), residual
LM_HEAD = "lm_head"              # final norm, head matmul, f32 logits
LOSS = "loss"                    # logsumexp, cross-entropy, z-loss
OPTIMIZER = "optimizer"          # clipping, schedule, AdamW update

STEP_SCOPES = (EMBED, ATTN_IN, PRF_FEATURES, PRF_MIX, ATTN_OUT, MLP,
               LM_HEAD, LOSS, OPTIMIZER)

MOE_ROUTER = "moe_router"        # router logits, top-k, gates, aux loss
MOE_DISPATCH = "moe_dispatch"    # sort by expert, group sizes, gather
MOE_EXPERTS = "moe_experts"      # the grouped matmuls
MOE_COMBINE = "moe_combine"      # unsort, gated sum over the k choices

MOE_SCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE)
