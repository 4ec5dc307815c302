"""The flagship decoder-only Transformer LM (GPT-2 / Llama family, opt. MoE).

One model covers the reference's example/benchmark families (nanoGPT GPT-2,
Llama2 — ref ``examples/pytorch/nanogpt/train.py``,
``atorch/examples/llama2/``): config flags pick learned-position+LayerNorm+GELU
(GPT-2) or RoPE+RMSNorm+SwiGLU+GQA (Llama), and ``num_experts > 0`` switches
the MLP to expert-parallel MoE.  The DeepSeek-V3 family's parts are flags
too: latent attention (``kv_lora_rank``), a sigmoid router with a
bias-corrected choice, a shared expert, a chip's share of the experts
(``experts_held``), leading dense layers before the scanned expert layers
(``first_k_dense``) and a multi-token-prediction module (``mtp_depth``)
whose output leaves the model only where the caller hands it the next
tokens (the train step does).  Nemotron-H's layers are kinds of a layer pattern:
a layer that is ONE residual branch (a Mamba-2 state-space mixer, an
attention without rotation, an expert layer of ungated ``relu2`` experts
with a shared expert of its own width, or a dense MLP alone).  Granite-4.0-H's
layer, a mixer AND an expert layer on two pre-norm branches, runs as two
such layers (``ssm`` | ``attention``, then ``experts``), and its four scalar
multipliers are fields with neutral defaults: ``embed_scale`` on the
embedding's output, ``attention_scale`` on the scores, ``residual_scale`` on
a branch's output before its residual add (every block class), and
``logit_scale`` on the logits.  Ling-3.0-flash's are flags again: the
linear layers' rule (``linear_rule="kda"``: a decay per channel under a
bounded gate), latent attention without a q latent (``q_lora_rank`` 0) and
with a head-wise output gate (``attention_gate``), a group-limited router
(``router_groups`` / ``router_topk_groups``), and ``first_k_dense`` leading
layers ahead of a PATTERNED trunk whose two-branch blocks carry the expert
layer.  LFM2's are a third two-branch kind, ``conv`` (a gated short
convolution: two gates round a 3-tap causal depthwise convolution, no
state, no softmax, no position; ``models/gated_conv.py``), a QK-norm PER
HEAD (``qk_norm="per_head"``) and ``router_norm_eps`` beside the
renormalising sum of a sigmoid router's gates.  Mellum2's are a fourth,
``sliding_attention``: the config's attention under a band of the causal
mask (``sliding_window`` keys, ``ops/flash_attention.py``'s ``window``),
each kind with its own rotation (plain RoPE under the window; the full
layers' ``rope_scaling="yarn"`` and its five numbers, ``layers.Rotation``).
Command A+'s are the PARALLEL form of the two-branch block
(``parallel_block``: one norm feeds the mixer and the MLP, ``x + Mixer(n) +
MLP(n)``), a LayerNorm without a bias (``norm_use_bias``), full layers
WITHOUT positions beside windowed layers that rotate (``full_rope``), and
shared experts that are AVERAGED over their published count, of which a chip
may hold a share (``shared_expert_combine``, ``shared_experts_held``).

TPU-first structure:
  * layers are ``nn.scan``-stacked: one trace regardless of depth (fast
    compiles), weights carry a leading ``layers`` dim that the pipeline
    strategy shards over the ``pipe`` mesh axis;
  * remat (activation checkpointing — the analogue of the reference's
    ``checkpoint_optimization``) is a config knob with XLA-friendly policies;
  * every param/activation is logically annotated so any strategy from
    ``dlrover_tpu.parallel.rules`` applies without touching model code.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from dlrover_tpu.models import attention as attention_lib
from dlrover_tpu.models import gated_conv
from dlrover_tpu.models import layers
from dlrover_tpu.models import linear_attention
from dlrover_tpu.models import mamba2
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.attention import (
    FULL_ATTENTION,
    INDEX_ATTENTION,
    REUSE_ATTENTION,
    SLIDING_ATTENTION,
)
from dlrover_tpu.models.family import Family
from dlrover_tpu.models.moe import check_share, ungated
from dlrover_tpu.ops import remat_policy as remat_policies
from dlrover_tpu.ops import ssd
from dlrover_tpu.parallel import rules as lr


LINEAR_ATTENTION = "linear_attention"
CONV = "conv"
# Layers of TWO residual branches: a mixer (softmax attention over the
# causal triangle, over a window of it or over a set of keys that the layer
# chooses or is handed, a delta rule or a gated short convolution), then an
# MLP (``Block``).
TWO_BRANCH_KINDS = (
    FULL_ATTENTION, LINEAR_ATTENTION, CONV, SLIDING_ATTENTION,
    INDEX_ATTENTION, REUSE_ATTENTION,
)
# The sparse attention layers (models/sparse_attention.py): the choice of
# keys rides beside the residual stream through every layer of such a model.
INDEX_KINDS = (INDEX_ATTENTION, REUSE_ATTENTION)
# Layers that are ONE residual branch, ``x + f(Norm(x))`` (``BranchBlock``):
# a state-space mixer, an attention, an expert layer, a dense MLP.
SSM = "ssm"
ATTENTION = "attention"
EXPERTS = "experts"
MLP = "mlp"
BRANCH_KINDS = (SSM, ATTENTION, EXPERTS, MLP)
LAYER_KINDS = TWO_BRANCH_KINDS + BRANCH_KINDS


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    num_kv_heads: int = 0          # 0 -> same as num_heads (no GQA)
    head_dim: int = 0              # 0 -> d_model // num_heads
    d_ff: int = 0                  # 0 -> 4*d_model (gelu) or 8/3*d_model (swiglu)
    max_seq_len: int = 1024
    position: str = "learned"      # "learned" (GPT-2) | "rope" (Llama) |
                                   # "none" (Nemotron-H: the state-space
                                   # layers give the order)
    norm: str = "layernorm"        # "layernorm" | "rmsnorm"
    activation: str = "gelu"       # "gelu" | "swiglu" | "relu2" (ungated,
                                   # the rectifier squared)
    rope_theta: float = 10000.0
    use_bias: bool = True          # GPT-2 uses biases, Llama does not
    tie_embeddings: bool = True
    # The embedding table's initial std (0 -> the program's 0.02).  Every
    # branch reads the residual stream through a norm, so what this sets
    # is how far the token's own row outweighs the branches' outputs at
    # init: see ``benchmark/configs/nemotron-3-nano-30b-a3b.json``.
    embed_init_std: float = 0.0
    # Granite's scalar multipliers (with ``logit_scale`` below, which is
    # 1 / ``logits_scaling``): on the embedding's output, on the attention
    # scores before the softmax (0 -> ``head_dim ** -0.5``) and on every
    # branch's output before its residual add.  The defaults change nothing.
    embed_scale: float = 1.0
    attention_scale: float = 0.0
    residual_scale: float = 1.0
    # MoE
    num_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_dispatch: str = "einsum"   # "einsum" | "a2a" | "a2a_int8"
                                   # (EP-shardable) | "grouped" (EP=1 only)
    # Renormalise the chosen gates to sum to one (Mixtral) or use the
    # softmax probabilities of the chosen experts as they are (OLMoE).
    norm_topk_prob: bool = True
    # Load-balancing loss: "top1" (Switch: first choices only, E^2/k) or
    # "topk" (E * sum_e f_e * P_e with f_e counted over all k choices, the
    # form the Mixtral/OLMoE reference implementations train with).
    moe_aux_form: str = "top1"
    # The DeepSeek-V3 family's router and expert layer (models/moe.py):
    # "sigmoid" scores with the k experts chosen on score + bias
    # (``router_bias``; the bias is a parameter no gradient reaches, moved
    # by ``router_bias_rate`` x sign(mean load - load) after each step) and
    # the gates scaled by ``routed_scaling_factor``; ``num_shared_experts``
    # shared experts of the routed experts' width every token passes
    # through; ``experts_held`` (0 = all) of the ``num_experts`` the router
    # chooses among live on this chip, from ``first_expert`` on, and the
    # rows set aside are ``moe_row_budget`` x the expected share;
    # ``moe_d_ff`` is an expert's width where dense layers have ``d_ff``.
    router_scoring: str = "softmax"
    router_bias: bool = False
    router_bias_rate: float = 0.001
    routed_scaling_factor: float = 1.0
    num_shared_experts: int = 0
    experts_held: int = 0
    first_expert: int = 0
    moe_row_budget: float = 1.25
    moe_d_ff: int = 0
    # A group-limited choice (DeepSeek-V3 §2.1.2, models/moe.py
    # ``group_limited``): the experts are ``router_groups`` runs of
    # consecutive ones and a token's ``top_k`` come from its
    # ``router_topk_groups`` best groups.  1 and 1: no limit.
    router_groups: int = 1
    router_topk_groups: int = 1
    # The shared expert's own width (0 -> ``num_shared_experts`` x the
    # routed experts' width, the DeepSeek-V3 family's).
    shared_expert_d_ff: int = 0
    # How the ``num_shared_experts`` shared experts join: "sum" (the
    # DeepSeek-V3 family's: one MLP as wide as all of them) or "average"
    # (Command A+'s: their sum over the PUBLISHED count).  A chip may hold
    # ``shared_experts_held`` (0 = all) of them whole, as it holds a share
    # of the routed experts: what the others would add is left out, and the
    # divisor stays the published count.
    shared_expert_combine: str = "sum"
    shared_experts_held: int = 0
    # What a sigmoid router's renormalising sum is added to (the
    # DeepSeek-V3 family's code: 1e-20; LFM2's: 1e-6).
    router_norm_eps: float = 1e-20
    # Layers before the scanned trunk whose MLP is dense (``d_ff`` wide)
    # though the trunk's is sparse: ``dense_0`` .. of ``num_layers``.  Ahead
    # of a patterned trunk their mixers are the pattern's continued
    # backwards (``layer_kind``), and the pattern is of two-branch kinds.
    first_k_dense: int = 0
    # Multi-token prediction (DeepSeek-V3 §2.2): one module (depth 1) that
    # predicts token i+2 from the trunk's hidden state i and the embedding
    # of token i+1 through a layer of its own and the SHARED head; the
    # train step adds ``mtp_weight`` x its cross-entropy to the loss.
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # The kind of the MTP module's layer beside a ``layer_pattern`` (a
    # two-branch kind; without a pattern every layer is full attention and
    # so is the module's).
    mtp_layer_kind: str = ""
    # RMSNorm over the whole q and k projections (all heads jointly, own
    # scale each) before the head split and RoPE (OLMoE, OLMo-2);
    # "per_head": each head's columns alone under ONE [head_dim] scale for
    # q and one for k (the LFM2 family).
    qk_norm: Any = False
    # Latent attention (models/attention.py ``LatentAttention``), on where
    # ``kv_lora_rank`` is set: q through a ``q_lora_rank`` latent, k and v
    # rebuilt from a ``kv_lora_rank`` latent, ``qk_rope_head_dim`` rotary
    # columns shared by all heads; keys ``qk_nope_head_dim +
    # qk_rope_head_dim`` wide, values ``v_head_dim``.
    # ``q_lora_rank`` 0 beside the other four: q straight from the stream
    # (no latent, no q norm).  ``attention_gate`` "head_wise": each head's
    # output times ``sigmoid(n W_gate)_h`` before the output projection;
    # "elementwise": every channel of every head under its own gate
    # (``W_gate`` [d, H, hd]; grouped-query attention only).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    attention_gate: str = ""
    # Sparse attention (models/sparse_attention.py; DeepSeek-V3.2's DSA):
    # an ``index_attention`` layer's indexer (``index_n_heads`` heads of
    # ``index_head_dim`` over the q latent) picks ``index_topk`` keys a
    # query for latent attention, a ``reuse_attention`` layer takes the
    # nearest earlier choice; the choosing layers' KL terms join the loss
    # and train the indexers alone (the terms share no parameter with the
    # rest of the loss, so a weight would only scale the indexers' step);
    # ``index_init_score_std`` seeds the indexer's scores' spread (0: the
    # default initialiser).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_init_score_std: float = 0.0
    # One period of layer kinds (``LAYER_KINDS``: "full_attention",
    # "linear_attention" and "conv" are a mixer AND an MLP; "ssm", "attention",
    # "experts" and "mlp" are that part alone on one residual branch),
    # repeated num_layers / len(layer_pattern) times; empty = every layer
    # full attention.  The trunk scans over PERIODS: a period applies its
    # blocks in order, each under its own name (``linear_0`` .. ``full_3``,
    # ``experts_0`` .. ``attention_8``) with its own parameters stacked
    # over the periods.
    layer_pattern: Tuple[str, ...] = ()
    # The Mamba-2 mixer of the "ssm" layers (models/mamba2.py): heads, a
    # head's width and state size, the groups that share B and C, taps of
    # the short convolution, the scan's chunk, the range ``dt`` is drawn
    # from at init, and how the scan runs (ops/ssd.py: "xla" | "kernel").
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # ``out_proj``'s initial scale over lecun normal's: the mixer's output
    # at init is largely one vector for every late token of a sequence (a
    # running sum of SiLU'd, so positive-mean, inputs), and Mamba's
    # reference code rescales it by ``(residual branches) ** -0.5``; see
    # ``benchmark/configs/granite-4.0-h-small.json``.
    ssm_out_init_scale: float = 1.0
    ssm_impl: str = "xla"
    # The gated-delta-rule mixer of the "linear_attention" layers
    # (models/linear_attention.py): heads (0 -> num_heads), key and value
    # head sizes, taps of the short convolution, and whether beta is
    # doubled so that the state's transition may have negative eigenvalues.
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = False
    # The linear layers' rule: "delta" (Gated DeltaNet: one decay a head,
    # ``GatedDeltaNet``) or "kda" (Kimi Delta Attention: a decay a channel,
    # ``KimiDeltaAttention``): under the safe gate ``g = linear_decay_bound
    # x sigmoid(..)`` (Ling's ``kda_lower_bound``), or with
    # ``linear_decay_bound`` 0 under the published gate ``-exp(A_log) x
    # softplus(..)``, which has no bound and takes the rule's exact form
    # (ops/kda.py).  ``linear_gate_rank``: the columns the decay's and the
    # output gate's projections pass through (Kimi Linear's
    # ``kda_use_full_proj`` false: the head width); 0: full rank.  Under
    # "kda" too ``linear_allow_neg_eigval`` doubles beta.
    linear_rule: str = "delta"
    linear_decay_bound: float = -5.0
    linear_gate_rank: int = 0
    # The spread BETWEEN CHANNELS the KDA decay's pre-activation is SEEDED
    # with (``dt_bias`` normal about the default's middle; 0: the default
    # initialiser, under which no token decays a channel by more than
    # e^-2): a trained gate has channels that keep a write for thousands of
    # tokens and channels that close, and only such a gate shows whether
    # the rule holds without a bound and whether its float32 state matters.
    # See ``benchmark/configs/solar-open2-250b.json``.
    linear_decay_init_std: float = 0.0
    # Taps of the "conv" layers' gated short convolution
    # (models/gated_conv.py; LFM2's ``conv_L_cache``).
    conv_kernel: int = 3
    # The "sliding_attention" layers (Mellum2's): the config's attention
    # under a band of the causal mask, a query seeing itself and the
    # ``sliding_window - 1`` tokens before it, rotated by plain RoPE at
    # ``rope_theta``.
    sliding_window: int = 0
    # The spread the scaled scores ``q k^T / sqrt(head_dim)`` are SEEDED
    # with (0: the default initialisers): the query and key kernels are
    # drawn at ``sqrt(attn_init_score_std / d_model)``.  The default
    # initialiser counts the heads into its fan-in, so seeded scores spread
    # by under 0.1, every softmax is flat, a layer adds the running mean of
    # its values (one vector for all late tokens) and no comparison of
    # losses can see a window or a rotation; a trained model's scores are
    # peaked.  See ``benchmark/configs/mellum2-12b-a2.5b.json``.
    attn_init_score_std: float = 0.0
    # The rotation of the FULL attention layers where it is more than
    # ``rope_theta``: ``"yarn"`` and its five numbers (``layers.Rotation``;
    # the published ``rope_parameters.full_attention``).
    rope_scaling: str = ""
    rope_scaling_factor: float = 0.0
    rope_original_max_position: int = 0
    rope_beta_fast: float = 0.0
    rope_beta_slow: float = 0.0
    rope_attention_factor: float = 0.0
    # Whether the FULL attention layers rotate q and k.  False beside
    # windowed layers that do (Command A+'s "global NoPE"): a full layer
    # sees the order only through what the windowed layers wrote.
    full_rope: bool = True
    # "pre": x + f(Norm(x)) (GPT-2, Llama, Mixtral, OLMoE); "post":
    # x + Norm(f(x)), each branch's OUTPUT normalised before the residual
    # add (OLMo 2 and later).
    norm_placement: str = "pre"
    # The two-branch layers' PARALLEL form (Command A+'s
    # ``use_parallel_block``): ``n = Norm(x); x' = x + Mixer(n) + MLP(n)``,
    # ONE norm a layer (``ln``), neither branch sees the other's output.
    parallel_block: bool = False
    norm_eps: float = 1e-5         # every RMSNorm / LayerNorm / QK-norm
    # A LayerNorm's bias (GPT-2 has one; the ``cohere2`` family's subtracts
    # the mean, scales, and has none).  An RMSNorm never has one.
    norm_use_bias: bool = True
    # numerics / execution
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "xla"    # "xla" | "flash"
    flash_block_q: int = 1024      # measured fastest on v5e at seq 1024
    flash_block_kv: int = 1024
    # One-pass Pallas LayerNorm backward (ops/fused_norm.py): attacks the
    # 6.4 ms/layer LN-bwd sink.  Numerics-tested; on-chip speedup not
    # measured — off until a trace prices it.
    fused_ln: bool = False
    remat: str = "none"            # a name registered in
                                   # ops/remat_policy.py (the flash_* ones
                                   # need attention_impl="flash")
    scan_layers: bool = True
    logits_dtype: Any = jnp.float32
    logit_scale: float = 1.0       # µP output multiplier (optimizers/mup.py);
                                   # Granite's 1 / logits_scaling
    # Pipeline parallelism (see parallel/pipeline.py): stages must divide
    # num_layers; microbatches default to the stage count.
    pipeline_stages: int = 1
    num_microbatches: int = 0
    # Autoregressive decode mode (rl/generation.py): attention maintains a
    # KV cache ("cache" collection, [B, max_seq_len, H_kv, hd] per layer)
    # and attends single-token queries against it.  Param tree is
    # UNCHANGED vs decode=False — the same weights serve training and
    # generation.  attention_impl may be "xla" or "flash" (flash serves
    # wide position-0 prefill chunks through the Pallas kernel and falls
    # back to the cached einsum path for single-token/narrow queries;
    # "ring" has no decode path).  No pipelining.
    decode: bool = False
    # Circular (interleaved-1F1B-equivalent) schedule: each device holds
    # `interleave` layer chunks and every microbatch makes that many laps
    # around the stage ring, cutting the bubble fraction from
    # (S-1)/(M+S-1) to (S-1)/(vM+S-1) at v x the stage-handoff traffic
    # (ref ``StageInterleaver.py``; measured +13.6% critical path at
    # S=4/M=8, tools/pipeline_account.py).  Requires num_layers divisible
    # by stages*interleave and microbatches >= stages.
    pipeline_interleave: int = 1

    @property
    def resolved_kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def num_scan_units(self) -> int:
        """What the trunk scans (and the pipeline stacks) over: periods of
        the layer pattern, or single layers without one."""
        return (self.num_layers - self.first_k_dense) // max(
            1, len(self.layer_pattern)
        )

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def resolved_moe_d_ff(self) -> int:
        return self.moe_d_ff or self.resolved_d_ff

    @property
    def resolved_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def resolved_shared_held(self) -> int:
        return self.shared_experts_held or self.num_shared_experts

    @property
    def resolved_shared_d_ff(self) -> int:
        """The width of the ONE MLP that is the shared experts held here."""
        return self.shared_expert_d_ff or (
            self.resolved_shared_held * self.resolved_moe_d_ff
        )

    @property
    def shared_expert_scale(self) -> float:
        """What the shared MLP's output is multiplied by: 1 over the
        PUBLISHED count where the shared experts are averaged."""
        if self.shared_expert_combine == "average":
            return 1.0 / self.num_shared_experts
        return 1.0

    @property
    def norms_per_layer(self) -> int:
        """Norms of a two-branch layer: one feeds both branches of the
        parallel form."""
        return 1 if self.parallel_block else 2

    def num_layers_of(self, kind: str) -> int:
        """Layers of ``kind`` in the scanned trunk."""
        return self.num_scan_units * self.layer_pattern.count(kind)

    @property
    def num_linear_layers(self) -> int:
        """The trunk's, and those of a dense prefix ahead of it."""
        return self.num_layers_of(LINEAR_ATTENTION) + sum(
            self.layer_kind(i) == LINEAR_ATTENTION
            for i in range(self.first_k_dense)
        )

    @property
    def num_conv_layers(self) -> int:
        """As ``num_linear_layers``: the dense prefix's count too."""
        return self.num_layers_of(CONV) + sum(
            self.layer_kind(i) == CONV for i in range(self.first_k_dense)
        )

    @property
    def num_ssm_layers(self) -> int:
        return self.num_layers_of(SSM)

    @property
    def num_sliding_layers(self) -> int:
        """As ``num_linear_layers``: the dense prefix's count too."""
        return self.num_layers_of(SLIDING_ATTENTION) + sum(
            self.layer_kind(i) == SLIDING_ATTENTION
            for i in range(self.first_k_dense)
        )

    def _layers_of(self, kind: str) -> int:
        """Layers of ``kind``: the trunk's, the dense prefix's and the MTP
        module's."""
        return self.num_layers_of(kind) + sum(
            self.layer_kind(i) == kind for i in range(self.first_k_dense)
        ) + self.mtp_depth * (self.mtp_layer_kind == kind)

    @property
    def num_index_layers(self) -> int:
        """Layers that CHOOSE a query's keys (each holds an indexer)."""
        return self._layers_of(INDEX_ATTENTION)

    @property
    def num_reuse_layers(self) -> int:
        """Layers that attend over an earlier layer's choice."""
        return self._layers_of(REUSE_ATTENTION)

    @property
    def num_full_layers(self) -> int:
        """Layers of ``full_attention`` (every layer without a pattern)."""
        if not self.layer_pattern:
            return self.num_layers
        return self.num_layers_of(FULL_ATTENTION) + sum(
            self.layer_kind(i) == FULL_ATTENTION
            for i in range(self.first_k_dense)
        )

    def rotation(
        self, kind: str = FULL_ATTENTION
    ) -> Optional[layers.Rotation]:
        """``kind``'s rotary embedding: plain RoPE under the window, the
        scaled one (where the config names one) on the full layers, ``None``
        for full layers without positions (``full_rope`` False)."""
        if kind == SLIDING_ATTENTION:
            return layers.Rotation(self.rope_theta)
        if not self.full_rope:
            return None
        return layers.Rotation(
            self.rope_theta, self.rope_scaling, self.rope_scaling_factor,
            self.rope_original_max_position, self.rope_beta_fast,
            self.rope_beta_slow, self.rope_attention_factor,
        )

    @property
    def ssm_heads_per_step(self) -> int:
        """Heads one grid step of the scan kernels holds at these sizes
        (``ops/ssd.py``); 0 where the kernels do not hold them."""
        return ssd.heads_per_step(
            self.ssm_num_heads, self.ssm_head_dim, self.ssm_groups,
            self.ssm_state_size, self.ssm_chunk, self.dtype,
        )

    def layer_kind(self, layer: int) -> str:
        """Layer ``layer``'s kind: the trunk starts a period after the
        dense prefix, whose layers continue the pattern backwards."""
        if not self.layer_pattern:
            return FULL_ATTENTION
        return self.layer_pattern[
            (layer - self.first_k_dense) % len(self.layer_pattern)
        ]

    def __post_init__(self):
        # a JSON list arrives as a list; the config must stay hashable
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        self._check_pattern()
        self._check_family()
        self._check_block()
        self.rotation()             # yarn without its five numbers raises
        if self.attention_impl not in ("xla", "flash", "ring"):
            raise ValueError(
                f"attention_impl must be 'xla', 'flash' or 'ring', got "
                f"{self.attention_impl!r}"
            )
        # Registry-backed validation (ops/remat_policy.py): unknown names
        # and flash-name policies under a non-flash impl both raise here —
        # the flash_out/flash_lse names only exist inside the flash
        # kernel's custom_vjp, so elsewhere those policies would silently
        # save nothing (= remat "full") and the HFU accounting keyed on
        # the remat string would be wrong.
        remat_policies.validate(self.remat, self.attention_impl)
        if self.decode:
            if self.attention_impl == "ring":
                raise ValueError(
                    "decode=True requires attention_impl='xla' or 'flash' "
                    "(got 'ring'); ring streams K/V over a sharded "
                    "sequence axis a decode cache does not have"
                )
            if self.pipeline_stages > 1:
                raise ValueError("decode=True requires pipeline_stages=1")
        if self.pipeline_interleave < 1:
            raise ValueError("pipeline_interleave must be >= 1")
        if self.pipeline_interleave > 1:
            if self.pipeline_stages <= 1:
                raise ValueError(
                    "pipeline_interleave > 1 requires pipeline_stages > 1"
                )
            chunks = self.pipeline_stages * self.pipeline_interleave
            if self.num_scan_units % chunks:
                raise ValueError(
                    f"num_layers {self.num_layers} ({self.num_scan_units} "
                    f"scanned units) not divisible by stages*interleave "
                    f"{chunks}"
                )
            micro = self.num_microbatches or self.pipeline_stages
            if micro < self.pipeline_stages:
                raise ValueError(
                    f"circular schedule needs microbatches >= stages "
                    f"(got {micro} < {self.pipeline_stages}): lap L of a "
                    "microbatch re-enters stage 0 only after lap L-1 "
                    "cleared the ring"
                )

    def _check_pattern(self):
        pattern = self.layer_pattern
        unknown = sorted(set(pattern) - set(LAYER_KINDS))
        if unknown:
            raise ValueError(
                f"layer_pattern kinds must be among {list(LAYER_KINDS)}, "
                f"got {unknown}"
            )
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(
                f"norm_placement must be 'pre' or 'post', got "
                f"{self.norm_placement!r}"
            )
        if not pattern:
            return
        if (self.num_layers - self.first_k_dense) % len(pattern):
            raise ValueError(
                f"num_layers {self.num_layers} is no whole number of "
                f"periods of the {len(pattern)}-layer pattern {pattern}"
                + (f" after the first_k_dense = {self.first_k_dense} "
                   "layer(s) of the dense prefix" if self.first_k_dense
                   else "")
            )
        if self.num_scan_units % self.pipeline_stages:
            raise ValueError(
                f"pipeline_stages {self.pipeline_stages} does not divide "
                f"the {self.num_scan_units} periods of the "
                f"{len(pattern)}-layer pattern ({self.num_layers} layers): "
                "a stage holds whole periods"
            )
        if SSM in pattern:
            self._check_ssm()
        if CONV in pattern:
            if self.conv_kernel < 2:
                raise ValueError(
                    f"a conv layer needs conv_kernel >= 2 taps, got "
                    f"{self.conv_kernel}"
                )
            if self.decode:
                raise ValueError(
                    "decode=True with a conv layer: its convolution's last "
                    f"{self.conv_kernel - 1} rows of B * z a sequence have "
                    "no place beside the KV cache yet (serving/decode.py, "
                    "serving/engine.py); this model trains only"
                )
        if SLIDING_ATTENTION in pattern:
            if self.sliding_window < 1:
                raise ValueError(
                    "a sliding_attention layer needs sliding_window (the "
                    "keys a query sees, itself among them), got "
                    f"{self.sliding_window}"
                )
            if self.latent_attention or self.attention_impl == "ring":
                raise ValueError(
                    "a sliding_attention layer is the plain grouped-query "
                    "attention under attention_impl 'flash' or 'xla': "
                    "latent attention and ring attention know no window"
                )
            if self.decode:
                raise ValueError(
                    "decode=True with a sliding_attention layer: its ring "
                    f"of {self.sliding_window} cached rows has no place "
                    "beside the whole cache of the full layers yet "
                    "(serving/decode.py, models/attention.cached_attention);"
                    " this model trains only"
                )
        if set(pattern) & set(INDEX_KINDS) or self.mtp_layer_kind in (
            INDEX_KINDS
        ):
            self._check_index()
        if EXPERTS in pattern and not self.num_experts:
            raise ValueError(
                "an 'experts' layer needs num_experts (and moe_d_ff, top_k)"
            )
        if LINEAR_ATTENTION in pattern:
            if not (self.linear_key_head_dim and self.linear_value_head_dim):
                raise ValueError(
                    "a linear_attention layer needs linear_key_head_dim and "
                    f"linear_value_head_dim, got {self.linear_key_head_dim} "
                    f"and {self.linear_value_head_dim}"
                )
            if self.linear_rule not in ("delta", "kda"):
                raise ValueError(
                    "linear_rule must be 'delta' or 'kda', got "
                    f"{self.linear_rule!r}"
                )
            if self.linear_rule == "kda" and not (
                -88.0 / 16 < self.linear_decay_bound <= 0
            ):
                raise ValueError(
                    "linear_decay_bound bounds a token's log decay from "
                    "below so that a 16-token sub-chunk's stays inside "
                    "float32 (ops/kda.py), or is 0 for the gate without a "
                    "bound: it must lie in (-5.5, 0), got "
                    f"{self.linear_decay_bound}"
                )
            if self.linear_gate_rank < 0 or (
                self.linear_gate_rank and self.linear_rule != "kda"
            ):
                raise ValueError(
                    "linear_gate_rank is the per-channel rule's "
                    "(linear_rule 'kda'), 0 or a number of columns, got "
                    f"{self.linear_gate_rank} under {self.linear_rule!r}"
                )
            if self.decode:
                raise ValueError(
                    "decode=True with a linear_attention layer: the layer's "
                    "recurrent state [H, dv, dk] and its convolution's last "
                    "taps have no place beside the KV cache yet "
                    "(serving/decode.py, serving/engine.py); this model "
                    "trains only"
                )

    def _check_index(self):
        """Sparse attention: latent attention's layers, an indexer's three
        sizes, a first layer that chooses."""
        if not (
            self.latent_attention and self.q_lora_rank and self.index_n_heads
            and self.index_head_dim and self.index_topk > 0
        ):
            raise ValueError(
                "an index_attention / reuse_attention layer is latent "
                "attention with a q latent (the indexer reads it) over "
                "index_topk keys chosen by index_n_heads heads of "
                f"index_head_dim, got q_lora_rank={self.q_lora_rank}, "
                f"kv_lora_rank={self.kv_lora_rank}, {self.index_n_heads} x "
                f"{self.index_head_dim}, index_topk={self.index_topk}"
            )
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError(
                f"the indexer rotates qk_rope_head_dim {self.qk_rope_head_dim}"
                f" columns of its index_head_dim {self.index_head_dim}"
            )
        others = sorted(set(self.layer_pattern) - set(INDEX_KINDS))
        if others or self.layer_kind(0) != INDEX_ATTENTION:
            raise ValueError(
                "a model with sparse attention layers has no other kind "
                "(every layer carries the choice) and its first layer "
                f"chooses: layer 0 is {self.layer_kind(0)!r}, other kinds "
                f"{others}"
            )
        if self.pipeline_stages > 1:
            raise ValueError(
                "pipeline_stages > 1 with sparse attention layers: the "
                "choice a layer hands on would have to cross a stage's "
                "boundary beside the residual stream, and "
                "parallel/pipeline.py's carry holds the stream alone"
            )

    def _check_block(self):
        """The parallel block, the bias-free LayerNorm, full layers without
        positions and the shared experts' mean and share."""
        if self.parallel_block:
            one_branch = sorted(set(self.layer_pattern) & set(BRANCH_KINDS))
            if self.norm_placement != "pre" or one_branch or self.mtp_depth:
                raise ValueError(
                    "parallel_block is the pre-norm two-branch layer with "
                    "ONE norm, x + Mixer(Norm(x)) + MLP(Norm(x)): "
                    "norm_placement='post' norms each branch's output by "
                    "itself, a one-branch kind has no second branch, and the "
                    "MTP module's layer is the serial one; got norm_placement"
                    f"={self.norm_placement!r}, one-branch kinds {one_branch}"
                    f", mtp_depth={self.mtp_depth}"
                )
        if not self.norm_use_bias and self.norm != "layernorm":
            raise ValueError(
                "norm_use_bias=False leaves a LayerNorm's bias out; "
                f"norm={self.norm!r} has none"
            )
        if not self.full_rope and (
            self.position != "rope" or self.rope_scaling
            or not self.num_sliding_layers
        ):
            raise ValueError(
                "full_rope=False takes the rotation off the full attention "
                "layers BESIDE sliding_attention layers that keep it "
                "(position='rope'; rope_scaling is the full layers' rotation "
                "and has nothing to scale); a model with no positions at all "
                f"is position='none'; got position={self.position!r}, "
                f"rope_scaling={self.rope_scaling!r}, "
                f"{self.num_sliding_layers} sliding layer(s)"
            )
        if self.shared_expert_combine not in ("sum", "average") or (
            self.shared_expert_combine == "average"
            and not self.num_shared_experts
        ):
            raise ValueError(
                "shared_expert_combine must be 'sum' or 'average' (of "
                "num_shared_experts), got "
                f"{self.shared_expert_combine!r} with "
                f"{self.num_shared_experts} shared expert(s)"
            )
        held, shared = self.shared_experts_held, self.num_shared_experts
        if held and (
            held < 0 or not shared or shared % held or self.shared_expert_d_ff
        ):
            raise ValueError(
                f"shared_experts_held {held} must divide num_shared_experts "
                f"{shared}: a chip holds whole shared experts of the routed "
                "experts' width (no shared_expert_d_ff), one of "
                "num_shared_experts / held equal shares"
            )

    def _check_ssm(self):
        h, p, n, g = (
            self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_size,
            self.ssm_groups,
        )
        if not (h and p and n) or g < 1 or h % g:
            raise ValueError(
                "an ssm layer needs ssm_num_heads, ssm_head_dim and "
                "ssm_state_size, and ssm_groups dividing the heads, got "
                f"{h}, {p}, {n}, {g}"
            )
        if self.ssm_impl not in ssd.IMPLS:
            raise ValueError(
                f"ssm_impl must be one of {ssd.IMPLS}, got {self.ssm_impl!r}"
            )
        if self.ssm_impl == "kernel" and not self.ssm_heads_per_step:
            raise ValueError(
                f"ssm_impl='kernel' lays heads side by side in {ssd.LANES}"
                f"-lane tiles: ssm_head_dim {p} must divide {ssd.LANES}, "
                f"a group's {h}/{g} heads be whole tiles wide and one tile "
                f"with its chunk of {self.ssm_chunk} fit VMEM "
                "(ops/ssd.py heads_per_step); ssm_impl='xla' takes any sizes"
            )
        if self.decode:
            raise ValueError(
                "decode=True with an ssm layer: the layer's recurrent "
                f"state [H, P, N] ({h} x {p} x {n}) and its convolution's "
                f"last {self.ssm_conv_kernel - 1} rows have no place beside "
                "the KV cache yet (serving/decode.py, serving/engine.py); "
                "this model trains only"
            )

    def _check_family(self):
        """The DeepSeek-V3 family's fields: whole shares, a trunk left
        after the dense prefix, latent attention's five sizes together."""
        if self.num_experts:
            check_share(
                self.num_experts, self.experts_held, self.first_expert,
                self.moe_dispatch,
            )
        elif self.experts_held or self.num_shared_experts or self.router_bias:
            raise ValueError(
                "experts_held, num_shared_experts and router_bias describe "
                "an expert layer: set num_experts"
            )
        if self.position not in ("learned", "rope", "none"):
            raise ValueError(
                "position must be 'learned', 'rope' or 'none', got "
                f"{self.position!r}"
            )
        if self.activation not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                "activation must be 'gelu', 'swiglu' or 'relu2', got "
                f"{self.activation!r}"
            )
        if self.qk_norm not in (False, True, "per_head"):
            raise ValueError(
                "qk_norm must be False, True (all heads jointly) or "
                f"'per_head', got {self.qk_norm!r}"
            )
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                "router_scoring must be 'softmax' or 'sigmoid', got "
                f"{self.router_scoring!r}"
            )
        if self.router_bias and self.router_scoring != "sigmoid":
            raise ValueError(
                "router_bias corrects a sigmoid router's choice; got "
                f"router_scoring={self.router_scoring!r}"
            )
        if self.router_scoring == "sigmoid" and self.moe_dispatch != "grouped":
            raise ValueError(
                "router_scoring='sigmoid' is routed by moe_dispatch="
                f"'grouped' only, got {self.moe_dispatch!r}"
            )
        if not 0 <= self.first_k_dense < max(1, self.num_layers):
            raise ValueError(
                f"first_k_dense {self.first_k_dense} must leave a trunk of "
                f"the {self.num_layers} layers"
            )
        if self.first_k_dense and set(self.layer_pattern) - set(
            TWO_BRANCH_KINDS
        ):
            raise ValueError(
                "first_k_dense puts layers with a dense MLP before the "
                "trunk: beside a layer_pattern every kind must be a mixer "
                f"AND an MLP ({list(TWO_BRANCH_KINDS)}), got "
                f"{list(self.layer_pattern)}"
            )
        if self.router_groups < 1 or self.router_topk_groups < 1 or (
            self.router_groups > 1 and (
                self.router_scoring != "sigmoid"
                or self.num_experts % self.router_groups
                or self.router_topk_groups > self.router_groups
                or self.top_k > self.router_topk_groups
                * (self.num_experts // self.router_groups)
                or self.num_experts // self.router_groups < 2
            )
        ):
            raise ValueError(
                "a group-limited choice is a sigmoid router's: "
                f"router_groups {self.router_groups} must divide num_experts "
                f"{self.num_experts} into groups of two or more, and "
                f"router_topk_groups {self.router_topk_groups} of them hold "
                f"at least top_k {self.top_k} experts"
            )
        if self.first_k_dense and (
            self.num_layers - self.first_k_dense
        ) % self.pipeline_stages:
            raise ValueError(
                f"pipeline_stages {self.pipeline_stages} does not divide "
                f"the {self.num_layers - self.first_k_dense} layers after "
                f"the {self.first_k_dense} dense one(s), which run ahead "
                "of the first stage"
            )
        if self.mtp_depth not in (0, 1):
            raise ValueError(
                f"mtp_depth must be 0 or 1 (one module), got {self.mtp_depth}"
            )
        if self.mtp_layer_kind and (
            self.mtp_layer_kind not in TWO_BRANCH_KINDS or not self.mtp_depth
            or self.mtp_layer_kind in (LINEAR_ATTENTION, CONV)
        ):
            raise ValueError(
                "mtp_layer_kind is the attention kind of the MTP module's "
                f"layer (mtp_depth 1), got {self.mtp_layer_kind!r} with "
                f"mtp_depth={self.mtp_depth}"
            )
        if self.mtp_depth and self.layer_pattern and not self.mtp_layer_kind:
            raise ValueError(
                "mtp_depth with a layer_pattern: the module's layer has no "
                "kind to take; state it (mtp_layer_kind)"
            )
        latent = (
            self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim,
        )
        if any(latent + (self.q_lora_rank,)) and not all(latent):
            raise ValueError(
                "latent attention needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim together (q_lora_rank 0: q "
                f"straight from the stream), got {latent}"
            )
        if self.attention_gate not in ("", "head_wise", "elementwise") or (
            self.attention_gate == "elementwise" and self.latent_attention
        ):
            raise ValueError(
                "attention_gate is '' or 'head_wise' (latent attention's "
                "gate and grouped-query attention's) or 'elementwise' "
                "(grouped-query attention's alone), got "
                f"{self.attention_gate!r}"
            )
        if not self.latent_attention:
            return
        if self.position != "rope":
            raise ValueError(
                "latent attention rotates qk_rope_head_dim columns: it "
                f"needs position='rope', got {self.position!r}"
            )
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} must be even"
            )
        if self.attention_impl == "ring" or self.qk_norm:
            raise ValueError(
                "latent attention runs under attention_impl 'flash' or "
                "'xla' and has its own latent norms (no qk_norm)"
            )
        if self.decode:
            raise ValueError(
                "decode=True with latent attention: its cache would hold "
                "the normed kv latent and the one rotated key row "
                f"({self.kv_lora_rank} + {self.qk_rope_head_dim} numbers a "
                "token) with W_kvb absorbed into the query and output "
                "sides, and serving/decode.py's cache pool holds [H_kv, hd] "
                "keys and values only; this model trains only"
            )

    @property
    def resolved_linear_heads(self) -> int:
        return self.linear_num_heads or self.num_heads

    @property
    def resolved_d_ff(self) -> int:
        if self.d_ff:
            return self.d_ff
        if self.activation == "swiglu":
            # Llama convention: ~8/3 * d_model, rounded up to an MXU-friendly
            # multiple of 128 lanes.
            return ((8 * self.d_model // 3) + 127) // 128 * 128
        return 4 * self.d_model

    def num_params(self) -> int:
        """Approximate parameter count (for MFU/HFU accounting): the
        parameters HELD, so a chip's share of the experts counts
        ``experts_held`` of them; latent attention by its five projections
        and two latent norms; the shared experts, the dense prefix and the
        MTP module (a layer, ``eh_proj`` and its three norms) where set; a
        one-branch layer by its one part; a layer's own norms and the
        final one are left out, as ever."""
        d, v, l = self.d_model, self.vocab_size, self.num_layers
        swiglu = 3 if self.activation == "swiglu" else 2
        if self.latent_attention:
            h, qk = self.num_heads, self.qk_nope_head_dim + self.qk_rope_head_dim
            attn = (
                (
                    d * self.q_lora_rank + self.q_lora_rank * h * qk
                    + self.q_lora_rank
                ) if self.q_lora_rank else d * h * qk
            ) + (
                d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * h
                * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d + self.kv_lora_rank
                + (d * h if self.attention_gate else 0)
            )
        else:
            h = self.resolved_head_dim * self.num_heads
            hkv = self.resolved_head_dim * self.resolved_kv_heads
            attn = d * h + 2 * d * hkv + h * d + {
                "": 0, "head_wise": d * self.num_heads, "elementwise": d * h,
            }[self.attention_gate]
        dense_ff = swiglu * d * self.resolved_d_ff
        if self.num_experts:
            one = swiglu * d * self.resolved_moe_d_ff
            ff = (
                self.resolved_experts_held * one
                + swiglu * d * self.resolved_shared_d_ff
                + d * self.num_experts
                + (self.num_experts if self.router_bias else 0)
            )
        else:
            ff = dense_ff
        embed = v * d + (0 if self.position != "learned" else self.max_seq_len * d)
        head = 0 if self.tie_embeddings else v * d
        if self.qk_norm == "per_head":
            attn += 2 * self.resolved_head_dim
        linear, conv = self.num_linear_layers, self.num_conv_layers
        dense = self.first_k_dense
        mtp = self.mtp_depth * (attn + ff + 2 * d * d + 3 * d)
        # an indexer in the layers that choose only: wq_b, wk, its key's
        # LayerNorm, weights_proj
        indexers = self.num_index_layers * (
            self.q_lora_rank * self.index_n_heads * self.index_head_dim
            + d * self.index_head_dim + 2 * self.index_head_dim
            + d * self.index_n_heads
        )
        # layers that are one branch: none of them is a mixer AND an MLP
        ssm, alone, experts, mlp = (
            self.num_layers_of(kind) for kind in BRANCH_KINDS
        )
        two = l - ssm - alone - experts - mlp
        return (
            (two - linear - conv + alone) * attn
            + linear * self._linear_mixer_params()
            + conv * self._conv_mixer_params()
            + ssm * self._ssm_mixer_params()
            + (two - dense + experts) * ff + (dense + mlp) * dense_ff
            + embed + head + mtp + indexers
        )

    def _ssm_mixer_params(self) -> int:
        """The input and output projections, the convolution's taps and
        bias, A_log, D, dt_bias and the gated norm's scale
        (models/mamba2.py)."""
        h, inner = self.ssm_num_heads, self.ssm_num_heads * self.ssm_head_dim
        bc = self.ssm_groups * self.ssm_state_size
        return (
            self.d_model * (2 * inner + 2 * bc + h) + inner * self.d_model
            + (self.ssm_conv_kernel + 1) * (inner + 2 * bc) + 3 * h + inner
        )

    def _conv_mixer_params(self) -> int:
        """The ``[d, 3d]`` and ``[d, d]`` projections and the taps
        (models/gated_conv.py)."""
        d = self.d_model
        return 3 * d * d + d * d + self.conv_kernel * d

    def _linear_mixer_params(self) -> int:
        """q, k, v, gate and output projections, the two gate
        projections, the convolution's taps, A_log, dt_bias and the output
        norm's scale (models/linear_attention.py); under ``kda`` the decay
        projection is a channel's ([d, H dk], or through
        ``linear_gate_rank`` columns as the output gate's then is) and
        dt_bias a channel's."""
        d, h = self.d_model, self.resolved_linear_heads
        dk, dv = self.linear_key_head_dim, self.linear_value_head_dim
        if self.linear_rule == "kda":
            r = self.linear_gate_rank
            gates = (d + h * dk) * r + (d + h * dv) * r if r else (
                d * h * dk + d * h * dv
            )
            return (
                2 * d * h * dk + 2 * d * h * dv + gates + d * h
                + self.linear_conv_kernel * h * (2 * dk + dv)
                + h + h * dk + dv
            )
        return (
            2 * d * h * dk + 3 * d * h * dv + 2 * d * h
            + self.linear_conv_kernel * h * (2 * dk + dv) + 2 * h + dv
        )


class Mlp(nn.Module):
    d_ff: int
    activation: str
    use_bias: bool
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        h = layers.DenseGeneral(
            self.d_ff,
            kernel_axes=(lr.EMBED, lr.MLP),
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="wi",
        )(x)
        if self.activation == "swiglu":
            g = layers.DenseGeneral(
                self.d_ff,
                kernel_axes=(lr.EMBED, lr.MLP),
                use_bias=self.use_bias,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name="wg",
            )(x)
            h = nn.silu(g) * h
        else:
            h = ungated(h, self.activation)
        return layers.DenseGeneral(
            d,
            kernel_axes=(lr.MLP, lr.EMBED),
            use_bias=self.use_bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="wo",
        )(h)


def _norm(cfg: TransformerConfig, name: str, fused: bool = True):
    """The config's norm under ``name``; ``fused``: whether ``fused_ln``'s
    one-pass backward applies (the layers' norms; not the final norm's or
    the MTP module's)."""
    return layers.make_norm(
        cfg.norm, cfg.dtype, cfg.param_dtype, name,
        fused_backward=fused and cfg.fused_ln,
        epsilon=cfg.norm_eps, use_bias=cfg.norm_use_bias,
    )


def _dense_mlp(cfg: TransformerConfig):
    return Mlp(
        d_ff=cfg.resolved_d_ff,
        activation=cfg.activation,
        use_bias=cfg.use_bias,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        name="mlp",
    )


def _add_branch(cfg: TransformerConfig, x: jax.Array, y: jax.Array):
    """``x + residual_scale * y``: a branch joins the residual stream."""
    with jax.named_scope("residual"):
        return x + (y if cfg.residual_scale == 1.0 else y * cfg.residual_scale)


class Block(nn.Module):
    """One layer: a token mixer of ``kind`` (softmax attention, plain or
    latent, a delta rule or the gated short convolution) and an MLP, each
    on its residual
    branch.  ``dense_mlp`` makes the MLP the dense one (``d_ff`` wide)
    though the model's trunk is sparse: a leading dense layer.  Under
    ``parallel_block`` both branches read ONE norm of the layer's input
    (``ln``) and join the stream in one add: ``x + Mixer(n) + MLP(n)``.
    In a model with sparse attention layers the carry has a third entry,
    the choice of keys (``sparse_attention.Index``), which an
    ``index_attention`` layer replaces and a ``reuse_attention`` layer
    reads."""

    config: TransformerConfig
    kind: str = FULL_ATTENTION
    dense_mlp: bool = False

    @nn.compact
    def __call__(
        self,
        carry: Tuple[jax.Array, jax.Array],
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> Tuple[Tuple[jax.Array, jax.Array], None]:
        cfg = self.config
        x, aux, *index = carry
        x = nn.with_logical_constraint(x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED))
        post = cfg.norm_placement == "post"

        def norm(name, y):
            return _norm(cfg, name)(y)

        def mixer(y):
            if self.kind in INDEX_KINDS:
                from dlrover_tpu.models import sparse_attention

                y, index[0] = sparse_attention.from_config(
                    cfg, self.kind, name="attn"
                )(y, positions, segment_ids, index[0])
                return y
            if self.kind == LINEAR_ATTENTION:
                return linear_attention.from_config(cfg, name="linear_attn")(y)
            if self.kind == CONV:
                return gated_conv.from_config(cfg, name="conv")(y)
            return attention_lib.from_config(cfg, self.kind, name="attn")(
                y, positions, segment_ids
            )

        def mlp(y):
            if cfg.num_experts and not self.dense_mlp:
                return moe_lib.from_config(cfg, name="moe")(y)
            return _dense_mlp(cfg)(y), None

        if cfg.parallel_block:
            n = norm("ln", x)
            # the names the serial form's branches carry, so that a remat
            # policy keeps here what it keeps there
            y = jax.ad_checkpoint.checkpoint_name(mixer(n), "attn_out")
            z, layer_aux = mlp(n)
            z = jax.ad_checkpoint.checkpoint_name(z, "mlp_out")
            if layer_aux is not None:
                aux = aux + layer_aux
            x = _add_branch(cfg, x, y + z)
            x = nn.with_logical_constraint(
                x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED)
            )
            return (x, aux, *index), None
        y = mixer(x if post else norm("ln_attn", x))
        if post:
            y = norm("ln_attn", y)
        # Named checkpoint: under the "attn_out" remat policy the backward
        # skips re-running the whole attention forward (the priciest part of
        # recompute) at b*s*d bf16 per layer of extra HBM.
        y = jax.ad_checkpoint.checkpoint_name(y, "attn_out")
        x = _add_branch(cfg, x, y)
        y, layer_aux = mlp(x if post else norm("ln_mlp", x))
        if layer_aux is not None:
            aux = aux + layer_aux
        if post:
            y = norm("ln_mlp", y)
        # Under the "branch_out" policy the backward rebuilds the residual
        # stream from saved branch outputs instead of re-running the wo
        # matmul (b*s*d bf16 per layer of extra HBM each).
        y = jax.ad_checkpoint.checkpoint_name(y, "mlp_out")
        x = _add_branch(cfg, x, y)
        x = nn.with_logical_constraint(x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED))
        return (x, aux, *index), None


class BranchBlock(nn.Module):
    """One layer that is ONE residual branch, ``x + f(Norm(x))`` (under
    ``norm_placement="post"``: ``x + Norm(f(x))``), with one norm ``ln``:
    ``f`` is ``kind``'s part alone, a Mamba-2 mixer (``ssm``), the config's
    attention (``attn``), its expert layer (``moe``) or its dense MLP
    (``mlp``).  ``Block``'s signature, so a period mixes both."""

    config: TransformerConfig
    kind: str = SSM

    @nn.compact
    def __call__(
        self,
        carry: Tuple[jax.Array, jax.Array],
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ) -> Tuple[Tuple[jax.Array, jax.Array], None]:
        cfg = self.config
        x, aux = carry
        x = nn.with_logical_constraint(x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED))
        post = cfg.norm_placement == "post"
        norm = _norm(cfg, "ln")
        y = x if post else norm(x)
        if self.kind == SSM:
            y = mamba2.from_config(cfg, name="ssm")(y)
        elif self.kind == ATTENTION:
            y = attention_lib.from_config(cfg, name="attn")(
                y, positions, segment_ids
            )
        elif self.kind == EXPERTS:
            y, layer_aux = moe_lib.from_config(cfg, name="moe")(y)
            aux = aux + layer_aux
        else:
            y = _dense_mlp(cfg)(y)
        if post:
            y = norm(y)
        # the names Block's two branches carry, so that a policy which
        # keeps a mixer's or an MLP's output keeps this layer's
        y = jax.ad_checkpoint.checkpoint_name(
            y, "attn_out" if self.kind in (SSM, ATTENTION) else "mlp_out"
        )
        x = _add_branch(cfg, x, y)
        x = nn.with_logical_constraint(x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED))
        return (x, aux), None


def block_class(
    cfg: TransformerConfig, prevent_cse: bool, kind: str = FULL_ATTENTION
):
    """``kind``'s block class (``Block``, or ``BranchBlock`` for a layer
    that is one branch), under the config's remat policy (registry lookup,
    ops/remat_policy.py: named save sets and builtins resolve there).
    A block that IS a scan's body needs no barrier against CSE (the loop
    is one); a block among others does, or the compiler may rebuild every
    block of the body before the first cotangent arrives."""
    return _block_class(cfg, prevent_cse, kind in BRANCH_KINDS)


@functools.lru_cache(maxsize=64)
def _block_class(cfg: TransformerConfig, prevent_cse: bool, branch: bool):
    cls = BranchBlock if branch else Block
    if cfg.remat == "none":
        return cls
    return nn.remat(
        cls,
        policy=remat_policies.jax_policy(cfg.remat),
        prevent_cse=prevent_cse,
        static_argnums=(),
    )


def slot_name(position: int, kind: str) -> str:
    """A block's name inside a period: ``linear_0`` .. ``full_3``,
    ``experts_0`` .. ``attention_8``."""
    return f"{kind.split('_')[0]}_{position}"


class Period(nn.Module):
    """One period of ``config.layer_pattern``: its blocks in order, each
    (under the remat policy) with its kind and its own name.  The scanned
    unit of a patterned trunk, with ``Block``'s signature."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, carry, positions=None, segment_ids=None):
        cfg = self.config
        for i, kind in enumerate(cfg.layer_pattern):
            carry, _ = block_class(cfg, prevent_cse=True, kind=kind)(
                cfg, kind, name=slot_name(i, kind)
            )(carry, positions, segment_ids)
        return carry, None


class MTPModule(nn.Module):
    """The multi-token-prediction module, depth 1 (DeepSeek-V3 §2.2)::

        h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_eh      # 2d -> d
        one more layer of the trunk's kind (its own attention, router,
        shared and held experts), then a norm of its own

    ``h`` is the trunk's output BEFORE its final norm, the embedding the
    model's own; the caller puts the model's shared head on the result,
    which predicts token ``i + 2``.  Returns ``(hidden, aux)``; in a model
    with sparse attention layers the module's layer is handed the trunk's
    last choice (``index``), and the choice it leaves (its own ``L^I``
    added, where it chooses) is returned third."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, hidden, next_embed, positions, segment_ids, *index):
        cfg = self.config

        def norm(name, y):
            return _norm(cfg, name, fused=False)(y)

        x = layers.DenseGeneral(
            cfg.d_model,
            kernel_axes=(None, lr.EMBED),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            name="proj",
        )(jnp.concatenate(
            [norm("hnorm", hidden), norm("enorm", next_embed)], axis=-1
        ))
        (x, aux, *index), _ = block_class(cfg, prevent_cse=True)(
            cfg, cfg.mtp_layer_kind or FULL_ATTENTION, name="block"
        )((x, jnp.zeros((), jnp.float32), *index), positions, segment_ids)
        return (norm("norm", x), aux, *index)


# The multi-token-prediction module's own cross-entropy (token i + 2 from
# position i), beside the main loss it trains with: a scalar the step
# computes itself (``trainer/train_lib.py``), no sown vector and no kernel.
MTP_FAMILY = Family(
    event="mtp",
    stats={"mtp_loss": None},
    has=lambda cfg: cfg.mtp_depth,
    read=lambda cfg, loss: dict(
        mtp_loss=float(loss), weight=float(cfg.mtp_weight)
    ),
    kernel_facts=lambda cfg, seq_len: {},
)



def _of_sparse_attention(name: str):
    """``models/sparse_attention.py``'s ``name``, imported when it is first
    called: a model without such a layer imports none of it."""
    def call(*args):
        from dlrover_tpu.models import sparse_attention

        return getattr(sparse_attention, name)(*args)
    return call


def _index_kernel_facts(cfg: TransformerConfig, seq_len: int):
    if not cfg.num_index_layers:
        return {
            "sparse_attention": "none", "sparse_block": None,
            "sparse_backward": None, "index_select": "none",
            "index_mask_bytes": None,
        }
    return _of_sparse_attention("kernel_facts")(cfg, seq_len)


# The sparse attention layers' indexers (models/sparse_attention.py): per
# choosing layer the pairs chosen and seen, the largest index score and the
# layer's KL term; ``index_stats`` is that module's ``STATS_NAME``.
INDEX_FAMILY = Family(
    event="index",
    stats={"index_stats": _of_sparse_attention("fold_stats")},
    has=lambda cfg: cfg.num_index_layers,
    read=_of_sparse_attention("read"),
    kernel_facts=_index_kernel_facts,
    absmax="score_absmax",
)

# Every family of layers that sows statistics or chooses kernels, in the
# order the step folds their vectors and a report books their events.
FAMILIES = (
    moe_lib.FAMILY, MTP_FAMILY, linear_attention.FAMILY, mamba2.FAMILY,
    gated_conv.FAMILY, attention_lib.FAMILY, INDEX_FAMILY,
)


def families(cfg: TransformerConfig) -> Tuple[Family, ...]:
    """The families ``cfg`` has layers of, in ``FAMILIES``' order."""
    return tuple(family for family in FAMILIES if family.has(cfg))


def kernel_facts(cfg: TransformerConfig, seq_len: int) -> Dict[str, Any]:
    """How each kernel family runs in ``cfg``'s step program on sequences
    of ``seq_len`` tokens, for the ``compile`` event: every family's keys,
    ``none`` where ``cfg`` has no such layer.  ``short_conv`` is said by
    both families that run the short convolution: the paths of both,
    joined.  Ahead of them what no family says, the two-branch layers'
    form: ``block_form`` (``parallel``: one norm feeds both branches, which
    do not depend on each other inside a layer; ``serial``) and
    ``block_norms``, the norms a layer."""
    facts: Dict[str, Any] = {
        "block_form": "parallel" if cfg.parallel_block else "serial",
        "block_norms": cfg.norms_per_layer,
    }
    for family in FAMILIES:
        for key, said in family.kernel_facts(cfg, seq_len).items():
            if key in facts:
                paths = {facts[key], said} - {"none"}
                said = "+".join(sorted(paths)) or "none"
            facts[key] = said
    return facts


class TransformerLM(nn.Module):
    """Decoder-only LM.  ``__call__(tokens) -> (logits, aux_loss)``.

    The trunk is ``first_k_dense`` leading dense layers (``dense_<i>``,
    applied one by one; under pipelining they run ahead of the stage ring,
    on the whole batch, as the first stage's input) and then the scanned
    units (``blocks``).  With ``mtp_depth`` and ``next_tokens`` (the token
    after each of ``tokens``: the train step's targets) a third value is
    returned, the MTP module's logits (its normed hidden state under
    ``return_hidden``), which predict the token after ``next_tokens``;
    without ``next_tokens`` the call is the two values it always was."""

    config: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        return_hidden: bool = False,
        next_tokens: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, ...]:
        cfg = self.config
        if cfg.position == "learned" and tokens.shape[1] > cfg.max_seq_len:
            # XLA gather would silently clamp overflow positions to the last
            # table row — make it loud (RoPE has no such limit).
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds max_seq_len "
                f"{cfg.max_seq_len} of the learned position table"
            )
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        embed = layers.Embed(
            num_embeddings=cfg.vocab_size,
            features=cfg.d_model,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            embedding_init=(
                nn.initializers.normal(stddev=cfg.embed_init_std)
                if cfg.embed_init_std else layers.default_embed_init
            ),
            name="embed",
        )
        def embedded(ids):
            rows = embed(ids)
            return rows if cfg.embed_scale == 1.0 else rows * cfg.embed_scale

        x = embedded(tokens)
        if cfg.position == "learned":
            pos_table = self.param(
                "pos_embedding",
                nn.with_logical_partitioning(
                    layers.default_embed_init, (lr.ACT_SEQ, lr.EMBED)
                ),
                (cfg.max_seq_len, cfg.d_model),
                cfg.param_dtype,
            )
            with jax.named_scope("pos_embed"):
                x = x + pos_table.astype(cfg.dtype)[positions]
        x = nn.with_logical_constraint(x, (lr.BATCH, lr.ACT_SEQ, lr.ACT_EMBED))

        block_cls = block_class(cfg, prevent_cse=not cfg.scan_layers)
        # What is stacked and scanned: single layers, or whole periods of
        # the layer pattern (each slot of a period then has its own
        # parameters, stacked over the periods).
        unit_cls = Period if cfg.layer_pattern else block_cls
        aux0 = jnp.zeros((), jnp.float32)
        # the choice of keys beside the stream, where layers choose
        index = ()
        if cfg.num_index_layers:
            from dlrover_tpu.models import sparse_attention

            index = (sparse_attention.empty_index(*tokens.shape[:2]),)
        if cfg.first_k_dense:
            prefix_cls = block_class(cfg, prevent_cse=True)
            carry = (x, aux0, *index)
            for i in range(cfg.first_k_dense):
                carry, _ = prefix_cls(
                    cfg, cfg.layer_kind(i), True, name=f"dense_{i}"
                )(carry, positions, segment_ids)
            x, aux0, *index = carry
        if cfg.pipeline_stages > 1:
            from dlrover_tpu.parallel.pipeline import PipelinedBlocks

            x, aux = PipelinedBlocks(cfg, unit_cls, name="blocks")(
                x, aux0, positions, segment_ids
            )
        elif cfg.scan_layers:
            stack = nn.scan(
                unit_cls,
                # "intermediates" carries the stats the layers sow (MoE
                # router, linear attention) — stacked on a leading axis
                # where the caller applies with mutable=["intermediates"]
                # (the train step does), absent otherwise.
                variable_axes={"params": 0, "cache": 0, "intermediates": 0},
                split_rngs={"params": True},
                in_axes=nn.broadcast,
                length=cfg.num_scan_units,
                metadata_params={nn.PARTITION_NAME: lr.LAYERS},
            )(cfg, name="blocks")
            (x, aux, *index), _ = stack(
                (x, aux0, *index), positions, segment_ids
            )
        else:
            carry = (x, aux0, *index)
            for i in range(cfg.first_k_dense, cfg.num_layers):
                kind = cfg.layer_kind(i)
                carry, _ = block_class(cfg, prevent_cse=True, kind=kind)(
                    cfg, kind, name=f"block_{i}"
                )(carry, positions, segment_ids)
            x, aux, *index = carry

        mtp_hidden = None
        if cfg.mtp_depth and (
            next_tokens is not None or self.is_initializing()
        ):
            # the module's parameters are made at init whoever calls
            mtp_hidden, mtp_aux, *mtp_index = MTPModule(cfg, name="mtp")(
                x, embedded(tokens if next_tokens is None else next_tokens),
                positions, segment_ids, *index,
            )
            if next_tokens is None:
                mtp_hidden = None
            else:
                # the module trains (its expert layer's term, its
                # indexer's) only where it is asked for
                aux, index = aux + mtp_aux, mtp_index

        x = _norm(cfg, "ln_final", fused=False)(x)
        if return_hidden:
            # Caller computes the loss head itself (chunked CE path) — the
            # [B, S, V] logits tensor is never materialized.  The µP logit
            # multiplier folds into the hidden states so chunked CE sees
            # the same scaled logits as the materialized path.
            if cfg.logit_scale != 1.0:
                x = x * cfg.logit_scale
                if mtp_hidden is not None:
                    mtp_hidden = mtp_hidden * cfg.logit_scale
            if mtp_hidden is not None:
                return x, self._aux_term(aux, index), mtp_hidden
            return x, self._aux_term(aux, index)
        if cfg.tie_embeddings:
            head = embed.attend
        else:
            head = layers.DenseGeneral(
                cfg.vocab_size,
                kernel_axes=(lr.EMBED, lr.VOCAB),
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                name="lm_head",
            )

        def logits_of(hidden):
            logits = nn.with_logical_constraint(
                head(hidden), (lr.BATCH, lr.ACT_SEQ, lr.VOCAB)
            )
            if cfg.logit_scale != 1.0:
                logits = logits * cfg.logit_scale
            return logits.astype(cfg.logits_dtype)

        if mtp_hidden is not None:
            with jax.named_scope("mtp/head"):
                mtp_logits = logits_of(mtp_hidden)
            return logits_of(x), self._aux_term(aux, index), mtp_logits
        return logits_of(x), self._aux_term(aux, index)

    def _aux_term(self, aux, index):
        """What joins the loss beside the cross-entropies: the expert
        layers' balance term, weighed, and the indexers' KL terms."""
        aux = aux * self.config.moe_aux_weight
        if index:
            aux = aux + index[0].kl
        return aux
