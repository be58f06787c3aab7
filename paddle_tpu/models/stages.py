"""The stages of the compiled train step, by name.

`models/trainer.py` and the model families open one `jax.named_scope` per
stage, so every instruction of the compiled program says in its `op_name`
which stage it belongs to, and a device trace can be read by stage. The
strings are written here and nowhere else: the models, the trainer and the
benchmark's readers (`benchmarks/layer_metrics/_stages.py`) import them.

Flat, never nested, with one exception (MTP, below). A dense block is
ATTN_QKV + ATTN_CORE + ATTN_OUT + MLP and nothing else; a sparse family
(models/mla_moe.py) adds ROUTER and EXPERTS beside MLP, which is then its
dense MLP and its shared expert, and RESIDUAL_MIX around every sub-layer;
a linear-attention layer has LINEAR_ATTN where a softmax layer has ATTN_CORE.
The same name opened twice is one stage. MTP is the exception: one scope
around the whole multi-token-prediction module, opened OUTSIDE the stages
its layer and its head pass open themselves, so a path reads
`.../jvp(mtp)/.../attn_core/...`. A reader files an instruction under the
FIRST stage name of its path (`layer_metrics/_stages.place`), so the module
goes whole under MTP and every other stage keeps meaning the trunk.
Direction (forward,
backward, what remat repeats) is not a scope: JAX writes `jvp(...)`,
`transpose(jvp(...))` and `rematted_computation` into the path itself.
A scope exists only while tracing; the compiled program differs by metadata
strings alone. No JAX import here: the benchmark reads the names before it
decides which platform JAX may see.
"""
EMBED = "embed"             # token / position / type lookups, their sum,
#                             the embedding LN (bert), the cast
ATTN_QKV = "attn_qkv"       # entry sharding constraint, the norm that
#                             feeds attention, q/k/v projection and bias
ATTN_CORE = "attn_core"     # split into heads, every layout change, rope,
#                             scores / mask / softmax / context or the
#                             kernel call with its all-to-alls, merge
ATTN_OUT = "attn_out"       # output projection, bias, residual add (bert:
#                             the LN after it)
MLP = "mlp"                 # its norm, both projections, activation,
#                             residual add (bert: the LN after it), exit
#                             sharding constraint
LOSS_HEAD = "loss_head"     # final norm, MLM transform (bert), logits,
#                             float32 log-softmax or vocabulary-parallel
#                             cross-entropy, the pick and the mean
OPTIMIZER = "optimizer"     # all of the step after value_and_grad: AdamW
#                             on master weights, a family's own
#                             `state_update` (the routers' selection
#                             biases moved by load), the cast to params
ROUTER = "moe_router"       # expert scores, the top-k, the routing weights
EXPERTS = "moe_experts"     # the routed experts held here: sort, gather,
#                             grouped products, activation, weighted combine
RESIDUAL_MIX = "residual_mix"   # several residual streams: the norm of the
#                             flattened streams, the three projections,
#                             Sinkhorn, read-in and write-back
MTP = "mtp"                 # the multi-token-prediction module, whole: its
#                             two norms, the projection of [hidden ; next
#                             embedding], its layer (which opens the block's
#                             stages inside this one), its final norm, its
#                             pass through the shared head and its loss

LINEAR_ATTN = "linear_attn"     # a linear-attention layer between its
#                             projections and its output projection: the
#                             short convolutions, SiLU, the head norms of q
#                             and k, softplus and the log-decays, the
#                             chunked delta rule, the gated head norm. A
#                             softmax layer of the same model keeps
#                             ATTN_CORE, so a trace tells the two apart

BLOCK = (ATTN_QKV, ATTN_CORE, ATTN_OUT, MLP)
ALL = (EMBED,) + BLOCK + (LOSS_HEAD, OPTIMIZER) \
    + (ROUTER, EXPERTS, RESIDUAL_MIX) + (MTP,) + (LINEAR_ATTN,)
