"""Logical-axis sharding rules: the strategy layer of the parallelism library.

Where the reference applies parallelism by *module surgery* (wrapping modules
in FSDP/DDP, swapping ``nn.Linear`` for ``RowParallelLinear`` — ref
``atorch/atorch/auto/opt_lib/*`` and
``atorch/atorch/modules/distributed_modules/layers.py:239-763``), the
TPU-native design applies it by *naming*: model code annotates every parameter
and activation with logical axis names, and a strategy is just a rule table
mapping logical names to mesh axes.  Changing strategy = changing the table;
XLA inserts the collectives (all-gather for FSDP params, psum for TP partials,
all-to-all for Ulysses SP and MoE dispatch) automatically.

Strategy equivalences with the reference (SURVEY.md §2.5):

  ===============  =====================================================
  reference        rule here
  ===============  =====================================================
  DDP              ``batch -> ('data',)`` only (params replicated)
  ZeRO/FSDP        ``embed -> 'fsdp'`` etc. (params sharded over fsdp)
  TP (Megatron)    ``mlp/heads/vocab -> 'tensor'`` (row/col/vocab split)
  Ulysses SP       ``act_seq -> 'seq'`` outside attention,
                   ``act_heads -> ('seq','tensor')`` inside (a2a resharding)
  MoE / EP         ``expert -> 'expert'`` (a2a token dispatch)
  ===============  =====================================================
"""

from __future__ import annotations

from typing import List, Sequence, Tuple, Union

from dlrover_tpu.runtime.mesh import (
    DATA_AXIS,
    EXPERT_AXIS,
    FSDP_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    TENSOR_AXIS,
)

MeshAxes = Union[None, str, Tuple[str, ...]]
Rules = Sequence[Tuple[str, MeshAxes]]

# Logical axis names used by all models in dlrover_tpu.models.
BATCH = "batch"            # activation batch dim
ACT_SEQ = "act_seq"        # activation sequence dim (sharded under SP)
ACT_HEADS = "act_heads"    # activation heads dim inside attention
ACT_EMBED = "act_embed"    # activation embedding dim
EMBED = "embed"            # param embedding dim (FSDP shard dim)
MLP = "mlp"                # param MLP hidden dim (TP col split)
HEADS = "heads"            # param attention heads dim (TP split)
KV = "kv"                  # param per-head dim (a KDA head's dk decay
                           # channels too: whole on every device)
LATENT = "latent"          # latent attention's low-rank dim (q 1536, kv 512+64)
SSM_INNER = "ssm_inner"    # a state-space mixer's fused columns [z|x|B|C|dt]
SSM_HEADS = "ssm_heads"    # its per-head scalars (A_log, D, dt_bias)
CONV_INNER = "conv_inner"  # a gated short convolution's columns [B|C|z], its
                           # taps' channels and its output projection's rows
VOCAB = "vocab"            # param vocab dim (TP vocab split)
EXPERT = "expert"          # param expert dim (EP shard dim)
LAYERS = "layers"          # scanned layer dim (within one pipeline stage)
STAGES = "stages"          # pipeline stage dim (params + rolling state buffer)
NORM = "norm"              # 1-D norm scales/biases
GATHERED = "gathered"      # force-unsharded dim (explicit FSDP all-gather)


def make_rules(
    *,
    fsdp: bool = True,
    tensor: bool = True,
    sequence: bool = True,
    expert: bool = True,
    pipeline: bool = True,
    context: str = "ulysses",
) -> List[Tuple[str, MeshAxes]]:
    """Build the rule table for a strategy combination.

    All rules are safe to leave on even when the corresponding mesh axis has
    size 1 (the sharding becomes a no-op), so the default is "everything on"
    and the mesh shape alone decides the real strategy — mirroring how
    ``auto_accelerate`` composes optimizations without code changes.

    ``context`` picks the sequence-parallel style inside attention:
    ``"ulysses"`` reshards seq->heads at attention boundaries (a2a);
    ``"ring"`` keeps the sequence sharded and the ring_attention impl
    streams K/V over the seq axis (pair with ``attention_impl="ring"``).
    """
    rules: List[Tuple[str, MeshAxes]] = [
        (BATCH, (DATA_AXIS, FSDP_AXIS)),
        (ACT_EMBED, TENSOR_AXIS),
        (KV, None),
        # A latent is whole on every device: its down-projection contracts
        # the (fsdp-sharded) embed dim, its up-projection splits by heads.
        (LATENT, None),
        # A state-space mixer's heads read the B and C of their GROUP, and
        # its one input projection lays z, x, B, C and dt side by side: no
        # even split of those columns keeps a head with its group, so the
        # columns, the convolution's channels and the per-head scalars are
        # whole on every device (its projections still split on embed), and
        # the scan runs on each device's own batch rows.
        (SSM_INNER, None),
        (SSM_HEADS, None),
        # A gated short convolution multiplies channel c of B, of C and of
        # z, which lie d columns apart in ONE projection: no even split of
        # its 3d columns keeps the three together, so they are whole on
        # every device (the projections still split on embed).
        (CONV_INNER, None),
        (NORM, None),
        (GATHERED, None),
    ]
    rules.append((ACT_SEQ, SEQ_AXIS if sequence else None))
    if context == "ring":
        # Ring CP: heads stay tensor-sharded; sequence stays seq-sharded.
        rules.append((ACT_HEADS, TENSOR_AXIS if tensor else None))
    else:
        # Ulysses: heads sharded over the seq (and tensor) axes inside
        # attention, letting XLA introduce the seq<->heads all-to-all at
        # attention boundaries.
        rules.append(
            (ACT_HEADS, ((SEQ_AXIS, TENSOR_AXIS) if sequence else TENSOR_AXIS)
             if tensor or sequence else None)
        )
    rules.append((EMBED, FSDP_AXIS if fsdp else None))
    if tensor:
        rules += [(MLP, TENSOR_AXIS), (HEADS, TENSOR_AXIS), (VOCAB, TENSOR_AXIS)]
    else:
        rules += [(MLP, None), (HEADS, None), (VOCAB, None)]
    rules.append((EXPERT, EXPERT_AXIS if expert else None))
    # Pipelining shards the *stage* dim (see parallel/pipeline.py); the
    # per-stage layer dim stays unsharded.
    rules.append((STAGES, PIPE_AXIS if pipeline else None))
    rules.append((LAYERS, None))
    return rules


# The default "everything composable" rule table.
DEFAULT_RULES: List[Tuple[str, MeshAxes]] = make_rules()

# Pure data-parallel (DDP-equivalent): replicate params, shard batch.
DDP_RULES: List[Tuple[str, MeshAxes]] = make_rules(
    fsdp=False, tensor=False, sequence=False, expert=False
)

# Ring context-parallelism: pair with TransformerConfig.attention_impl="ring".
RING_RULES: List[Tuple[str, MeshAxes]] = make_rules(context="ring")
