"""Latent attention over a CHOSEN set of keys: DeepSeek-V3.2's sparse
attention (DSA, arXiv:2512.02556 §2.1) as GLM-5.2 (``glm_moe_dsa``) runs it,
training form.  ``n`` is the normed residual stream, ``c_q`` the main
attention's normed query latent (``models/attention.py``)::

    indexer (a layer that CHOOSES, kind ``index_attention``):
      q^I_{t,j} = (c_q W^I_qb)_j              J heads of D
      k^I_s     = LayerNorm(n_s W^I_k)        ONE key of D for all heads
      RoPE on the first ``rope`` columns of each, the main attention's theta
      w_t       = (n_t W^I_w) J^-1/2 D^-1/2   float32
      I_{t,s}   = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s),  s <= t
    choice:  S_t = the ``topk`` keys of {s <= t} with the largest I_{t,s}
             (ties to the lower key, every key where t < topk; inside a
             document where segment ids are given)
    attention: LatentAttention's, the softmax over S_t only
    a layer that REUSES (``reuse_attention``) has no indexer and takes S
    from the nearest ``index_attention`` layer before it (IndexShare)

What trains the indexer (V3.2 §2.1.1, the sparse stage): its inputs are
detached, and each choosing layer adds ``L^I = mean_t KL(p_t ||
softmax_{S_t}(I_t))`` where ``p_t = (1 / H) sum_h A_h[t, S_t]`` is that
layer's own attention, summed over the heads held here and detached.  The
language-model loss reaches no indexer weight (the choice has no gradient)
and ``L^I`` nothing but indexer weights.

The choice rides BESIDE the residual stream as :class:`Index`: the int8
mask ``[B, T, T]`` (1: chosen) and the sum of the ``L^I`` so far.  Scopes
under a layer's ``attn/``: ``indexer/`` (three projections, norm, rotation),
``select/`` (scores and choice, ``ops/index_select.py``), ``sparse/`` (the
attention over the choice: ``ops/sparse_flash_attention.py``'s kernels
under ``attention_impl="flash"`` where the sequence is whole blocks, a
forward call and, by the shapes, ONE backward call where dq over the
sequence fits in VMEM or the split pair where it does not
(``sparse_flash_attention.backward_path``; ``kernel_facts`` says which); the
masked ``jax.numpy`` form otherwise), ``index_kl/`` (the term AND its
gradient: both are computed in the forward, row block by row block, and
the backward only scales them, so nothing ``[T, T]`` is kept).

Left out of the published inference code, each changing no choice it could
make in exact arithmetic or being a precision choice: the Hadamard rotation
of ``q^I`` and ``k^I`` (orthogonal), their FP8 quantisation.  There is no
decode path (an indexer key cache beside the latent cache).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from dlrover_tpu.models import layers
from dlrover_tpu.models.attention import (
    INDEX_ATTENTION,
    LatentAttention,
    latent_output,
    latent_qkv,
)
from dlrover_tpu.ops import index_select
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime.mesh import shard_local

F32 = jnp.float32
STATS_NAME = "index_stats"
# Rows a step of the blocked passes (choice, KL term) holds: at 16,384 keys
# and 32 indexer heads the rectified scores of 128 rows are 256 MiB.
BLOCK_ROWS = 128
# The sparse kernels' block.
FLASH_BLOCK = 512


class Index(NamedTuple):
    """What an ``index_attention`` layer hands the layers after it."""

    mask: jax.Array      # int8 [B, T, T], 1: query t attends to key s
    kl: jax.Array        # float32 scalar: the sum of L^I so far


def empty_index(batch: int, seq_len: int) -> Index:
    """Before the first choosing layer: nothing chosen, no term."""
    return Index(
        jnp.zeros((batch, seq_len, seq_len), jnp.int8), jnp.zeros((), F32)
    )


class Indexer(nn.Module):
    """``(q^I [B, T, J, D], k^I [B, T, D], w [B, T, J] float32)``."""

    num_heads: int
    head_dim: int
    rope_dim: int
    rope_theta: float
    norm_eps: float
    dtype: Any
    param_dtype: Any
    init_score_std: float = 0.0

    @nn.compact
    def __call__(self, n, c_q, positions):
        def dense(width, axes, name, **kwargs):
            return layers.DenseGeneral(
                width, kernel_axes=axes, use_bias=False, dtype=self.dtype,
                param_dtype=self.param_dtype, name=name, **kwargs,
            )

        seeded = {}
        if self.init_score_std:
            # a head's query entries at this variance: I spreads with it
            seeded["kernel_init"] = nn.initializers.variance_scaling(
                self.init_score_std ** 2, "fan_in", "normal", in_axis=0,
                out_axis=(1, 2),
            )
        # replicated under every rule table: the choice is one for all heads
        q = dense(
            (self.num_heads, self.head_dim), (lr.LATENT, None, None), "wq_b",
            **seeded,
        )(c_q)
        k = layers.make_norm(
            "layernorm", self.dtype, self.param_dtype, "k_norm",
            epsilon=self.norm_eps,
        )(dense(self.head_dim, (lr.EMBED, None), "wk")(n))
        w = dense(self.num_heads, (lr.EMBED, None), "weights_proj")(n)
        with jax.named_scope("rope"):
            rope = self.rope_dim
            q_pe, k_pe = layers.rotary_embedding(
                q[..., :rope], k[..., None, :rope], positions,
                layers.rope_frequencies(rope // 2, self.rope_theta),
            )
            q = jnp.concatenate([q_pe, q[..., rope:]], axis=-1)
            k = jnp.concatenate([k_pe[..., 0, :], k[..., rope:]], axis=-1)
        w = w.astype(F32) * (self.num_heads ** -0.5 * self.head_dim ** -0.5)
        return q, k, w


def masked_attention(q, k, v, mask, scale) -> Tuple[jax.Array, jax.Array]:
    """The ``jax.numpy`` form of attention over a choice: ``(o [B, T, H,
    Dv], lse [B, T, H])``, float32 softmax over the keys ``mask`` names."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=F32
    ) * scale
    s = jnp.where(mask[:, None] != 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return out, jax.lax.stop_gradient(lse.transpose(0, 2, 1))


def sparse_path(attention_impl: str, seq_len: int) -> Tuple[str, int]:
    """``("kernel", block)`` or ``("xla", 0)``: how attention over a choice
    runs at ``seq_len`` tokens, from the shapes alone."""
    from dlrover_tpu.ops import sparse_flash_attention as sfa

    block = sfa.block_size(seq_len, FLASH_BLOCK)
    if attention_impl == "flash" and block:
        return "kernel", block
    return "xla", 0


def _kl_block(q_i, w, k_i, q, k, lse, mask, scale):
    """``sum_t KL(p_t || softmax_{S_t}(I_t))`` over a block of rows and its
    gradient for the indexer's three outputs, by hand (one pass over the
    block; the products of the backward take bfloat16 operands where the
    forward's did): ``q_i [B, R, J, D]``, ``w [B, R, J]``, ``q [B, R, H,
    d]``, ``lse [B, R, H]``, ``mask [B, R, T']`` against the keys ``k_i [B,
    T', D]``, ``k [B, T', H, d]``.  Returns ``(term, dq_i, dw, dk_i)``."""
    chosen = mask != 0
    # the layer's own attention over the choice, averaged over the heads
    s = jnp.einsum("brhd,bthd->brht", q, k, preferred_element_type=F32)
    p = jnp.where(
        chosen[:, :, None], jnp.exp(s * scale - lse[..., None]), 0.0
    ).mean(axis=2)
    z = jnp.einsum("brjd,btd->brjt", q_i, k_i, preferred_element_type=F32)
    w = w.astype(F32)
    score = jnp.where(
        chosen, (jax.nn.relu(z) * w[..., None]).sum(axis=2), -jnp.inf
    )
    log_q = score - jax.nn.logsumexp(score, axis=-1, keepdims=True)
    live = chosen & (p > 0.0)
    safe_p = jnp.where(live, p, 1.0)
    term = jnp.where(
        live, p * (jnp.log(safe_p) - jnp.where(live, log_q, 0.0)), 0.0
    ).sum()
    # d term / d I[t, s] = softmax_S(I)[s] x sum_live p - p[s] on live pairs
    p_live = jnp.where(live, p, 0.0)
    d_score = jnp.where(chosen, jnp.exp(log_q), 0.0) * p_live.sum(
        axis=-1, keepdims=True
    ) - p_live
    dw = (jax.nn.relu(z) * d_score[:, :, None]).sum(axis=-1)
    dz = jnp.where(
        z > 0.0, d_score[:, :, None] * w[..., None], 0.0
    ).astype(q_i.dtype)
    dq = jnp.einsum("brjt,btd->brjd", dz, k_i, preferred_element_type=F32)
    dk = jnp.einsum("brjt,brjd->btd", dz, q_i, preferred_element_type=F32)
    return term, dq, dw, dk


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def index_kl(q_i, k_i, w, q, k, lse, mask, scale, block_rows):
    """``L^I`` of one choosing layer: the mean over queries of ``KL(p_t ||
    softmax_{S_t}(I_t))``, a gradient for ``q_i``, ``k_i`` and ``w`` only
    (``q``, ``k`` and ``lse``, the main attention's, are read detached)."""
    return _index_kl_fwd(q_i, k_i, w, q, k, lse, mask, scale, block_rows)[0]


def _index_kl_fwd(q_i, k_i, w, q, k, lse, mask, scale, block_rows):
    b, t = q_i.shape[:2]
    rows = index_select.row_block(t, block_rows)
    total, dk_sum = jnp.zeros((), F32), jnp.zeros(k_i.shape, F32)
    dqs, dws = [], []
    for first, count, keys in index_select.key_runs(t, rows):
        def rows_of(x, i):
            return jax.lax.dynamic_slice_in_dim(x, i * rows, rows, axis=1)

        def step(carry, i, keys=keys):
            total, dk_sum = carry
            term, dq, dw, dk = _kl_block(
                rows_of(q_i, i), rows_of(w, i), k_i[:, :keys],
                rows_of(q, i), k[:, :keys], rows_of(lse, i),
                rows_of(mask, i)[:, :, :keys], scale,
            )
            return (total + term, dk_sum.at[:, :keys].add(dk)), (
                dq.astype(q_i.dtype), dw.astype(w.dtype)
            )

        (total, dk_sum), (dq, dw) = jax.lax.scan(
            step, (total, dk_sum), first + jnp.arange(count)
        )
        dqs.append(dq)
        dws.append(dw)
    count = F32(b * t)

    def whole(blocks):  # runs of [blocks, B, rows, ...] -> [B, T, ...]
        x = jnp.concatenate(blocks)
        return jnp.moveaxis(x, 0, 1).reshape(b, t, *x.shape[3:]) / count

    grads = (
        whole(dqs).astype(q_i.dtype), (dk_sum / count).astype(k_i.dtype),
        whole(dws).astype(w.dtype),
    )
    # kept by a remat policy that keeps the kernels' outputs: the backward
    # scales these and does not walk the rows a second time
    grads = jax.ad_checkpoint.checkpoint_name(grads, "index_kl_grads")
    return total / count, grads


def _index_kl_bwd(scale, block_rows, grads, g):
    dq, dk, dw = grads
    return (
        (g * dq).astype(dq.dtype), (g * dk).astype(dk.dtype),
        (g * dw).astype(dw.dtype), None, None, None, None,
    )


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


class SparseLatentAttention(LatentAttention):
    """``LatentAttention`` over a chosen set.  ``chooses``: the layer has
    an indexer, makes the choice and adds its ``L^I``; otherwise it takes
    the choice it is handed.  ``__call__(n, positions, segment_ids, index)
    -> (y, index)``."""

    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    chooses: bool = True
    index_init_score_std: float = 0.0

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
        index: Optional[Index] = None,
    ) -> Tuple[jax.Array, Index]:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q, k, v, c_q = latent_qkv(self, x, positions)
        if index is None:
            index = empty_index(*x.shape[:2])
        mask = index.mask
        if self.chooses:
            # the indexer learns from its own term alone
            q_i, k_i, w = Indexer(
                self.index_n_heads, self.index_head_dim,
                self.qk_rope_head_dim, self.rope_theta, self.norm_eps,
                self.dtype, self.param_dtype,
                init_score_std=self.index_init_score_std, name="indexer",
            )(
                jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q),
                positions,
            )
            with jax.named_scope("select"):
                mask, picked = index_select.choose_blocked(
                    *jax.lax.stop_gradient((q_i, k_i, w)), segment_ids,
                    self.index_topk, BLOCK_ROWS,
                )
                # kept for the backward (ops/remat_policy.py): neither this
                # layer's nor the reusing layers' runs the choice again
                mask = jax.ad_checkpoint.checkpoint_name(
                    jax.lax.stop_gradient(mask), "index_choice"
                )
        scale = self.scale or (
            self.qk_nope_head_dim + self.qk_rope_head_dim
        ) ** -0.5
        with jax.named_scope("sparse"):
            out, lse = attend(self.attention_impl, q, k, v, mask, scale)
        kl = index.kl
        if self.chooses:
            with jax.named_scope("index_kl"):
                term = index_kl(
                    q_i, k_i, w,
                    *jax.lax.stop_gradient((q, k, lse)), mask, scale,
                    BLOCK_ROWS,
                )
            kl = kl + term
            self.sow(
                "intermediates", STATS_NAME,
                jax.lax.stop_gradient(jnp.concatenate([picked, term[None]])),
            )
        return latent_output(self, out, x), Index(mask, kl)


def attend(attention_impl, q, k, v, mask, scale):
    """Attention over the choice ``mask``: ``(o, lse)`` by the kernels on
    each device's own batch rows and heads, or by the ``jax.numpy`` form
    (:func:`sparse_path`)."""
    path, block = sparse_path(attention_impl, q.shape[1])
    if path == "xla":
        return masked_attention(q, k, v, mask, scale)
    from dlrover_tpu.ops import sparse_flash_attention as sfa

    heads = nn.logical_to_mesh_axes((lr.BATCH, None, lr.ACT_HEADS, lr.KV))
    rows = nn.logical_to_mesh_axes((lr.BATCH, None, None))
    return shard_local(
        functools.partial(sfa.mha, scale=scale, block=block),
        in_specs=(heads, heads, heads, rows),
        out_specs=(
            heads, nn.logical_to_mesh_axes((lr.BATCH, None, lr.ACT_HEADS))
        ),
    )(q, k, v, mask)


def from_config(cfg, kind: str, **kwargs) -> SparseLatentAttention:
    """The config's sparse attention layer of ``kind``: the one place that
    reads the config's fields into the layer's."""
    return SparseLatentAttention(
        num_heads=cfg.num_heads,
        q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        attention_impl=cfg.attention_impl,
        scale=cfg.attention_scale,
        init_score_std=cfg.attn_init_score_std,
        index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim,
        index_topk=cfg.index_topk,
        chooses=kind == INDEX_ATTENTION,
        index_init_score_std=cfg.index_init_score_std,
        **kwargs,
    )


def fold_stats(stacked: jax.Array) -> jax.Array:
    """The choosing layers' ``[chosen pairs, pairs a query may see, largest
    |I|, L^I]`` as one vector: the counts and the terms summed, the largest
    score the largest."""
    return jnp.stack([
        stacked[:, 0].sum(), stacked[:, 1].sum(), stacked[:, 2].max(),
        stacked[:, 3].sum(),
    ])


def read(cfg, vec) -> Dict[str, Any]:
    """The ``index`` event of the step's folded vector."""
    chosen, seen, absmax, kl = (float(v) for v in vec)
    choosing = cfg.num_index_layers
    return dict(
        index_layers=choosing,
        shared_layers=cfg.num_reuse_layers,
        topk=cfg.index_topk,
        selected_share=chosen / seen if seen else 0.0,
        score_absmax=absmax,
        kl=kl / max(1, choosing),
    )


def kernel_facts(cfg, seq_len: int) -> Dict[str, Any]:
    """``sparse_attention``: the form attention over a choice runs in
    (``masked_kernel``: the Pallas kernels over the causal triangle under
    the mask; ``masked_xla``), its block, ``sparse_backward`` (the kernels'
    backward at these shapes, ``one_pass`` or ``split``: what their own
    ``backward_path`` answers; ``None`` without kernels), ``index_select``
    (how the choice is found) and ``index_mask_bytes`` (the choice a layer
    hands on, for a sequence), for a model with such layers
    (``models/transformer.py`` says ``none`` for the others without
    importing this module)."""
    path, block = sparse_path(cfg.attention_impl, seq_len)
    backward = None
    if path == "kernel":
        from dlrover_tpu.ops import sparse_flash_attention as sfa

        backward = sfa.backward_path(
            seq_len, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            cfg.v_head_dim, block, cfg.dtype,
        )
    return {
        "sparse_attention": "masked_kernel" if path == "kernel"
        else "masked_xla",
        "sparse_block": block or None,
        "sparse_backward": backward,
        "index_select": f"count32_rows{index_select.row_block(seq_len, BLOCK_ROWS)}",
        "index_mask_bytes": seq_len * seq_len,
    }

