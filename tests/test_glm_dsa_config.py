"""What GLM-5.2's configuration refuses and counts, on the CPU: the new
fields' bad values, the defaults that leave every other model as it was,
the published order of choosing and reusing layers, the published widths'
parameter count against the issue's arithmetic (~750B, the cut's
2,595,851,264 with the MTP module and 2,116,302,848 without), the benchmark
file against the catalog's config, the cache key, the kernels' facts."""

import dataclasses
import json
import os

import pytest

from dlrover_tpu.models import glm_dsa
from dlrover_tpu.models.glm_dsa import glm_dsa_config
from dlrover_tpu.models.joyai_llm_flash import joyai_llm_flash_config
from dlrover_tpu.models.transformer import (
    INDEX_ATTENTION,
    LAYER_KINDS,
    REUSE_ATTENTION,
    TransformerConfig,
    families,
    kernel_facts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "glm-5.2"
SMALL = dict(
    vocab_size=128, num_layers=5, first_k_dense=1, d_model=32, num_heads=2,
    d_ff=48, max_seq_len=32, q_lora_rank=16, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, num_experts=8,
    top_k=2, moe_d_ff=16, index_n_heads=2, index_head_dim=16, index_topk=8,
)
REDUCED = ["first_k_dense_replace", "indexer_types", "mlp_layer_types",
           "n_routed_experts", "num_attention_heads", "num_hidden_layers",
           "num_key_value_heads", "vocab_size"]


def config(**overrides):
    return glm_dsa_config(**{**SMALL, **overrides})


def cell_file():
    with open(os.path.join(REPO, "benchmark", "configs", f"{NAME}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("overrides,message", [
    (dict(index_topk=0), "index_topk keys chosen by index_n_heads"),
    (dict(index_n_heads=0), "index_topk keys chosen by index_n_heads"),
    (dict(q_lora_rank=0), "latent attention with a q latent"),
    (dict(index_head_dim=4), "the indexer rotates qk_rope_head_dim 8"),
    (dict(first_k_dense=0, num_layers=4), "its first layer chooses"),
    (dict(layer_pattern=(INDEX_ATTENTION, "full_attention"), num_layers=5),
     "has no other kind"),
    (dict(pipeline_stages=2, num_layers=9), "cross a stage's boundary"),
    (dict(mtp_layer_kind=""), "state it \\(mtp_layer_kind\\)"),
    (dict(mtp_layer_kind="conv"), "mtp_layer_kind is the attention kind"),
    (dict(mtp_depth=0), "mtp_layer_kind is the attention kind"),
    (dict(decode=True), "decode=True with latent attention"),
    (dict(num_layers=6), "no whole number of periods of the 4-layer pattern"),
    (dict(layer_pattern=("index", "reuse_attention")),
     "layer_pattern kinds must be among .*'index_attention', "
     "'reuse_attention'"),
])
def test_bad_values_of_the_new_fields_raise(overrides, message):
    with pytest.raises(ValueError, match=message):
        config(**overrides)


def test_the_defaults_leave_every_other_model_as_it_was():
    plain = TransformerConfig()
    assert (plain.index_n_heads, plain.index_head_dim, plain.index_topk) == (
        0, 0, 0
    )
    assert plain.mtp_layer_kind == "" and plain.num_index_layers == 0
    assert {INDEX_ATTENTION, REUSE_ATTENTION} <= set(LAYER_KINDS)
    # the sibling family's model: an MTP module without a pattern needs no
    # kind, has no sparse layer and no ``index`` family
    joyai = joyai_llm_flash_config()
    assert (joyai.num_index_layers, joyai.num_reuse_layers) == (0, 0)
    assert "index" not in [f.event for f in families(joyai)]
    facts = kernel_facts(joyai, 8192)
    assert facts["sparse_attention"] == "none"
    assert facts["sparse_backward"] is None
    assert facts["index_select"] == "none"
    assert "index" in [f.event for f in families(config())]
    # an MTP module beside a pattern is legal once its kind is stated
    assert config(mtp_layer_kind=REUSE_ATTENTION).num_reuse_layers == 4


def test_the_published_order_is_indexer_types():
    published = [glm_dsa.KIND_OF[t] for t in glm_dsa.INDEXER_TYPES]
    assert glm_dsa.INDEXER_TYPES.count("full") == 21
    assert glm_dsa.INDEXER_TYPES.count("shared") == 57
    # the builder's 77 layers are published layers 2 to 78: the leading
    # dense layers counted once, whole periods after them
    cfg = glm_dsa_config()
    assert (cfg.num_layers, cfg.first_k_dense) == (77, 1)
    assert [cfg.layer_kind(i) for i in range(76)] == published[2:]
    # three dense layers that all choose are more than a prefix that
    # continues the pattern backwards can say: refused, not mis-typed
    with pytest.raises(ValueError, match="its first layer chooses"):
        glm_dsa_config(num_layers=79, first_k_dense=3)
    # the benchmark's cut: published layers 2 to 6
    cut = glm_dsa_config(num_layers=5, first_k_dense=1)
    assert [cut.layer_kind(i) for i in range(5)] == published[2:7]
    assert (cut.num_index_layers, cut.num_reuse_layers) == (3, 3)


def test_the_published_widths_count_what_the_issue_counts():
    whole = glm_dsa_config()
    # 77 layers (19 whole periods) and the module: ~750B
    assert 7.4e11 < whole.num_params() < 7.7e11
    cut = glm_dsa_config(
        num_layers=5, first_k_dense=1, num_heads=16, experts_held=8,
        vocab_size=19456,
    )
    # the issue's arithmetic, plus what it leaves out: two latent norms a
    # layer (2,560), an indexer's key norm (256), the module's three norms
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    assert indexer == 9_371_648
    attention = 53_346_304 + 2048 + 512
    expert = 3 * 6144 * 2048
    router = 6144 * 256 + 256
    sparse = attention + 9 * expert + router
    dense = attention + 3 * 6144 * 12288
    module = sparse + indexer + 256 + 2 * 6144 * 6144 + 3 * 6144
    trunk = (
        dense + 4 * sparse + 2 * (indexer + 256) + 2 * 19456 * 6144
    )
    assert cut.num_params() == trunk + module == 2_595_851_264
    without = dataclasses.replace(cut, mtp_depth=0, mtp_layer_kind="")
    assert without.num_params() == trunk == 2_116_302_848
    file = cell_file()
    assert file["num_params"] in (trunk + module, trunk)


def test_the_file_holds_every_key_of_the_catalog_s_config():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "GLM-5.2"]
    file = cell_file()
    assert file["source"] == row["source_url"]
    reduced = file["reduced"]
    fallback = ["num_nextn_predict_layers"] * (
        file["num_nextn_predict_layers"] == 0
    )
    assert sorted(reduced) == sorted(REDUCED + fallback)
    for key, published in row["config"].items():
        assert key in file, key
        if key in ("indexer_types", "mlp_layer_types"):
            assert file[key] == published[2:7], key
            assert reduced[key]["run"] == file[key], key
        elif key in reduced:
            assert reduced[key]["published"] == published, key
            assert reduced[key]["run"] == file[key], key
            assert reduced[key]["why"]
        else:
            assert file[key] == published, key      # the nested group too
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "index_head_dim",
                "index_n_heads", "index_topk", "num_experts_per_tok",
                "head_dim", "qk_head_dim"):
        assert key not in reduced
    assert file["rope_theta"] == row["config"]["rope_parameters"]["rope_theta"]
    assert (file["router_experts"], file["token_vocab"]) == (256, 19360)
    manifest = {
        c["name"]: c for c in json.load(
            open(os.path.join(REPO, "BENCHMARK.json"))
        )["configs"]
    }[NAME]
    assert sorted(manifest["reduced"]) == sorted(reduced)
    assert manifest["source"] == row["source_url"]


def test_to_program_maps_to_fields_that_exist():
    from benchmark import build

    file = cell_file()
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    for field, key in file["to_program"].items():
        assert field in fields, field
        assert key in file, key
    assert set(file["program"]) <= fields
    for name in ("optimizer", "precision", "index_loss", "mtp_layer_kind",
                 "indexer_norms", "rope_interleave", "replicated_indexer",
                 "router_bias_rate", "seeded_scales"):
        assert name in file["assumed"], name
    deployment = file["deployment"]
    for said in ("32 chips share each layer", "experts over 32",
                 "64 heads over 4", "the vocabulary over 8", "held WHOLE",
                 "No width is cut"):
        assert said in deployment, said
    for name in ("serving", "dense_warm_up", "hadamard_and_fp8"):
        assert name in file["left_out"], name
    cfg = build.transformer_config(build.model_group(file), 16384)
    want = glm_dsa_config(
        num_layers=5, first_k_dense=1, num_heads=16, experts_held=8,
        vocab_size=19456, mtp_depth=cfg.mtp_depth,
        mtp_layer_kind=cfg.mtp_layer_kind,
    )
    for field in ("d_model", "num_heads", "d_ff", "moe_d_ff", "num_experts",
                  "experts_held", "first_expert", "top_k", "norm_topk_prob",
                  "routed_scaling_factor", "norm_eps", "norm", "rope_theta",
                  "tie_embeddings", "use_bias", "layer_pattern",
                  "num_shared_experts", "router_scoring", "router_bias",
                  "router_bias_rate", "position", "activation",
                  "moe_dispatch", "first_k_dense", "q_lora_rank",
                  "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim", "index_n_heads", "index_head_dim",
                  "index_topk", "mtp_weight"):
        assert getattr(cfg, field) == getattr(want, field), field
    assert (cfg.remat, cfg.attention_impl) == ("flash_only", "flash")
    facts = kernel_facts(cfg, 16384)
    assert facts["sparse_attention"] == "masked_kernel"
    assert facts["sparse_block"] == 512
    # dq over the 16,384 tokens fits in VMEM: one backward call, not two
    assert facts["sparse_backward"] == "one_pass"
    assert kernel_facts(cfg, 32768)["sparse_backward"] == "split"
    assert facts["index_select"] == "count32_rows128"
    assert facts["index_mask_bytes"] == 16384 * 16384
    # the grouped GEMMs at K = 6,144 keep the whole-K strip resident
    assert facts["gmm_strips"] == "resident"


def test_cache_key_covers_the_new_fields():
    from dlrover_tpu.runtime.compile_cache import train_cache_key

    def key(**kw):
        return train_cache_key(
            dataclasses.replace(config(), **kw), (1, 1, 1, 1, 1, 1),
            global_batch_size=8, seq_len=32,
        )

    keys = {
        key(), key(index_topk=4), key(index_n_heads=4),
        key(index_head_dim=8), key(index_init_score_std=2.0),
        key(mtp_layer_kind=REUSE_ATTENTION),
    }
    assert len(keys) == 6
