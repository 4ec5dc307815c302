"""auto_tune strategy search on the virtual 8-device CPU mesh.

Mirrors the reference's auto_accelerate tests
(ref ``atorch/atorch/tests/common_tests/auto_accelerate_test.py``): the
search must produce a feasible, runnable strategy without hand-picking.
"""

import dataclasses

import jax
import pytest

from dlrover_tpu.auto import auto_tune
from dlrover_tpu.auto.tune import enumerate_candidates
from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.models.llama import moe_llama_config


def tiny_cfg(**kw):
    return gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4,
        vocab_size=512, max_seq_len=64, **kw,
    )


def test_enumeration_respects_divisibility():
    cands = enumerate_candidates(tiny_cfg(), 8)
    assert cands
    for c in cands:
        sizes = c.parallel.sizes(8)
        assert sizes["tensor"] in (1, 2, 4)  # must divide 4 heads
        if c.parallel.seq > 1:
            assert 4 % (c.parallel.seq * c.parallel.tensor) == 0
        assert c.parallel.expert == 1  # dense model: no ep
        if c.parallel.pipe > 1:
            assert 2 % c.parallel.pipe == 0


def test_enumeration_moe_gets_expert_axis():
    cfg = moe_llama_config(
        "tiny", num_experts=2, num_layers=2, vocab_size=512, max_seq_len=64
    )
    cands = enumerate_candidates(cfg, 8)
    assert any(c.parallel.expert == 2 for c in cands)
    # MoE pipeline is unsupported (pipeline.py guard): never enumerated.
    assert all(c.parallel.pipe == 1 for c in cands)


@pytest.mark.slow  # compiles every candidate strategy, ~13s on 1 core
def test_auto_tune_picks_runnable_strategy():
    n = min(8, len(jax.devices()))
    result = auto_tune(
        tiny_cfg(),
        global_batch_size=16,
        n_devices=n,
        optimizer="adamw",
        max_measure=2,
    )
    assert result.parallel.sizes(n)  # multiplies to n
    assert result.best.measured_step_time is not None
    assert result.model_config.remat == result.remat
    # Ranked record doubles as the strategy report (dryrun evidence).
    assert result.candidates[0].est_step_time > 0


def test_auto_tune_memory_pruning_rejects_oversized():
    """A model far beyond HBM at dp=1 must push the search toward sharded
    strategies or fail loudly — never silently pick an OOM config."""
    big = gpt2_config("1.5b", max_seq_len=1024)
    cands = enumerate_candidates(big, 8, remat_policies=("none",))
    from dlrover_tpu.auto.tune import _estimate

    dp_only = [
        c for c in cands
        if c.parallel.data == 8 and c.parallel.fsdp == 1
    ]
    assert dp_only
    # On CPU specs (8 GB budget in the model table) a 1.5B adamw state
    # with remat=none cannot fit a single device's share.
    _estimate(dp_only[0], big, 64, 1024, "adamw", 8)
    assert dp_only[0].rejected


@pytest.mark.slow  # compiles one program per batch multiple, ~22s on 1 core
def test_auto_tune_batch_search_opt_in():
    """search_batch explores batch multiples, ranks by throughput, and
    reports the winner's batch; default search leaves batch untouched."""
    n = min(8, len(jax.devices()))
    result = auto_tune(
        tiny_cfg(),
        global_batch_size=16,
        n_devices=n,
        optimizer="adamw",
        max_measure=2,
        search_batch=True,
    )
    assert result.global_batch_size in (16, 32, 64)
    assert result.best.measured_tokens_per_sec is not None
    # Default path keeps the sentinel (caller's batch stands).
    plain = auto_tune(
        tiny_cfg(), global_batch_size=16, n_devices=n, measure=False,
    )
    assert plain.global_batch_size == 0


def test_search_kernels_widens_space_and_estimates():
    """VERDICT r3 #9: flash blocks / CE chunking / microbatches /
    quantized-DCN knobs enter the search (estimate-ranked, no measure)."""
    from dlrover_tpu.auto import tune

    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=512,
        max_seq_len=512, attention_impl="flash",
    )
    narrow = tune.enumerate_candidates(cfg, 8, seq_len=512)
    wide = tune.enumerate_candidates(
        cfg, 8, search_kernels=True, seq_len=512, multihost=True,
    )
    assert len(wide) > 4 * len(narrow)
    # every knob dimension is represented
    assert any(c.flash_block != (0, 0) for c in wide)
    assert any(c.ce_chunks == 16 for c in wide)
    assert any(c.quantized_dcn for c in wide)
    pipes = [c for c in wide if c.parallel.pipe > 1]
    if pipes:
        assert any(c.microbatches > c.parallel.pipe for c in pipes)

    result = tune.auto_tune(
        cfg, global_batch_size=16, seq_len=512, n_devices=8,
        measure=False, search_kernels=True,
    )
    assert result.best.est_step_time != float("inf")
    # the winner's knobs surface on the result
    assert result.ce_chunks == result.best.ce_chunks
    if result.best.flash_block != (0, 0):
        assert result.model_config.flash_block_q == result.best.flash_block[0]


def test_sampled_search_with_refinement_is_deterministic():
    from dlrover_tpu.auto import tune

    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=512,
        max_seq_len=512, attention_impl="flash",
    )
    kwargs = dict(
        global_batch_size=16, seq_len=512, n_devices=8, measure=False,
        search_kernels=True, max_enumerate=64,
    )
    a = tune.auto_tune(cfg, **kwargs)
    b = tune.auto_tune(cfg, **kwargs)
    assert tune._cand_key(a.best) == tune._cand_key(b.best)
    assert len([c for c in a.candidates if not c.rejected]) > 0


def test_unchunked_ce_memory_includes_logits():
    """CE chunking's real effect is the logits working set: the estimator
    must see it (it is what OOMs the 1.5B bench without chunking)."""
    from dlrover_tpu.auto import tune
    from dlrover_tpu.runtime.mesh import ParallelConfig

    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=50304,
        max_seq_len=512,
    )
    plain = tune.Candidate(ParallelConfig(data=8), "attn_out")
    chunked = tune.Candidate(
        ParallelConfig(data=8), "attn_out", ce_chunks=16
    )
    for cand in (plain, chunked):
        tune._estimate(cand, cfg, 64, 512, "adamw", 8)
    assert plain.est_hbm_gb > chunked.est_hbm_gb


def test_interleave_knob_enumerated_and_materialized():
    """pipeline_interleave joins the searched knobs (r5: the circular
    schedule is a real capability, so auto_tune must be able to pick
    it); the winner's v lands on the tuned model config."""
    config = gpt2_config(
        "124m", num_layers=4, d_model=64, num_heads=4, vocab_size=256,
        max_seq_len=64,
    )
    cands = enumerate_candidates(
        config, 4, search_kernels=True, seq_len=64,
    )
    piped = [c for c in cands if c.parallel.pipe == 2]
    assert any(c.interleave == 2 for c in piped)
    assert any(c.interleave == 0 for c in piped)
    # layers=6 cannot split into 2*2 chunks with pipe=2? 6 % 4 != 0 ->
    # no v=2 candidates for that pipe depth.
    config6 = gpt2_config(
        "124m", num_layers=6, d_model=64, num_heads=4, vocab_size=256,
        max_seq_len=64,
    )
    cands6 = enumerate_candidates(config6, 4, search_kernels=True,
                                  seq_len=64)
    assert not any(
        c.interleave == 2 for c in cands6 if c.parallel.pipe == 2
    )

    from dlrover_tpu.auto.tune import _estimate

    a = next(c for c in piped if c.interleave == 0 and c.microbatches == 2
             and c.remat == "full" and c.ce_chunks == 0
             and c.flash_block == (0, 0))
    b = dataclasses.replace(a, interleave=2)
    for c in (a, b):
        _estimate(c, config, 8, 64, "adamw", 4)
    assert b.est_step_time != a.est_step_time  # the knob changes the model


# ---- remat policies in the search (ops/remat_policy.py) -----------------


def test_search_kernels_widens_with_flash_policies_only():
    from dlrover_tpu.auto import tune

    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=512,
        max_seq_len=512, attention_impl="flash",
    )
    flash_names = {"flash_only", "flash_res"}
    narrow = tune.enumerate_candidates(cfg, 8, seq_len=512)
    assert not flash_names & {c.remat for c in narrow}
    wide = tune.enumerate_candidates(cfg, 8, search_kernels=True,
                                     seq_len=512)
    assert {c.remat for c in wide} == {c.remat for c in narrow} | flash_names
    # Under xla the flash names exist nowhere: nothing is added.
    xla = dataclasses.replace(cfg, attention_impl="xla")
    assert {
        c.remat for c in tune.enumerate_candidates(
            xla, 8, search_kernels=True, seq_len=512)
    } == {c.remat for c in tune.enumerate_candidates(xla, 8, seq_len=512)}
    with pytest.raises(ValueError, match="no broadcast encoding"):
        tune.enumerate_candidates(cfg, 8, remat_policies=("offlaod",))


def test_remat_broadcast_codes_roundtrip():
    """Multihost agreement broadcasts the remat choice as an int — every
    registered policy must roundtrip, and a code no policy has is the
    loud version-skew error."""
    from dlrover_tpu.auto import tune
    from dlrover_tpu.ops import remat_policy as rp

    for name in rp.available():
        assert tune._decode_remat(tune._encode_remat(name)) == name
    with pytest.raises(ValueError):
        tune._encode_remat("no_such_policy")
    for code in (-1, len(rp.available())):
        with pytest.raises(ValueError, match="version skew"):
            tune._decode_remat(code)


def test_newly_registered_policy_broadcasts_with_no_edit(monkeypatch):
    """The registry is the one home of the remat decision: a policy added
    to it has a broadcast code and is enumerable with no edit to
    auto/tune.py."""
    from dlrover_tpu.auto import tune
    from dlrover_tpu.ops import remat_policy as rp

    monkeypatch.setattr(rp, "_REGISTRY", dict(rp._REGISTRY))
    rp.register(rp.RematPolicy("mlp_only", saved_names=("mlp_out",)))
    assert "mlp_only" in rp.available()
    assert tune._decode_remat(tune._encode_remat("mlp_only")) == "mlp_only"
    cfg = gpt2_config(
        "124m", num_layers=2, d_model=64, num_heads=4, vocab_size=512,
        max_seq_len=512,
    )
    cands = tune.enumerate_candidates(
        cfg, 8, remat_policies=("full", "mlp_only"), seq_len=512)
    assert any(c.remat == "mlp_only" for c in cands)


def test_pick_grad_accum_prefers_smallest_fitting():
    """The tuner picks the smallest feasible N that fits HBM: plentiful
    memory -> N=1; shrinking budgets force more microbatches; a bf16
    accumulator never needs MORE microbatches than fp32 at equal HBM."""
    from dlrover_tpu.auto import pick_grad_accum
    from dlrover_tpu.runtime.mesh import ParallelConfig

    cfg = gpt2_config("1.5b", max_seq_len=2048)
    par = ParallelConfig(data=8)
    roomy = pick_grad_accum(
        cfg, par, 64, 2048, remat="full", hbm_bytes=10_000e9
    )
    assert roomy == 1
    tight = pick_grad_accum(
        cfg, par, 64, 2048, remat="full", hbm_bytes=16e9
    )
    assert tight > 1
    assert 64 % (8 * tight) == 0  # feasible: microbatch divides dp
    bf16 = pick_grad_accum(
        cfg, par, 64, 2048, remat="full", hbm_bytes=16e9,
        accum_dtype="bf16",
    )
    assert bf16 <= tight


def test_est_comm_time_prices_int8_cheaper():
    """est_comm_time: zero without a data axis; int8 beats fp32 on the
    wire for a wire-bound reduce."""
    from dlrover_tpu.auto import est_comm_time
    from dlrover_tpu.runtime.mesh import ParallelConfig

    cfg = gpt2_config("1.5b", max_seq_len=2048)
    assert est_comm_time(cfg, ParallelConfig(data=1, fsdp=8)) == 0.0
    full = est_comm_time(cfg, ParallelConfig(data=8), "none")
    int8 = est_comm_time(cfg, ParallelConfig(data=8), "int8")
    assert full > 0 and int8 > 0
    assert int8 < full
