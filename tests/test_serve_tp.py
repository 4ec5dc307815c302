"""Tensor-parallel serving: sharded decode programs, disaggregated
prefill, speculative decoding.

Tier-1 coverage for the PR-17 serving plane:

1. the TP mesh — fold rule (largest divisor that fits), config
   divisibility validation, serve-key sensitivity to the new knobs;
2. TP decode — greedy token parity vs tp=1 (fp32 activations make the
   argmax decisive, so parity is bitwise), per-device KV-pool bytes
   shrinking with the fold, mid-serve re-fold to a seen width hitting
   the program memo (zero retrace);
3. disaggregated prefill — a prefill-role replica streams KV page rows
   to a decode-role replica through the fleet with token parity against
   a colocated engine, and role guards reject the wrong traffic;
4. speculative decoding — a draft sharing the target's weights accepts
   nearly everything, a random draft accepts little but NEVER changes
   the emitted greedy stream, sampled rows complete, and the γ bounds /
   verify-write headroom are enforced at submit time;
5. scale policy — low-confidence p95 (few completed requests) neither
   triggers a breach scale-out nor licenses a scale-in; the prefill pool
   scales on its own backlog signal.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.master.auto_scaler import ServeScalePolicy
from dlrover_tpu.models.transformer import TransformerConfig, TransformerLM
from dlrover_tpu.rl.generation import SamplingParams
from dlrover_tpu.runtime.compile_cache import serve_cache_key
from dlrover_tpu.serving import ReplicaFleet, Request, ServingEngine
from dlrover_tpu.serving.engine import _nearest_rank
from dlrover_tpu.serving.tp import (
    ServeTPMesh,
    build_tp_mesh,
    fold_width,
    validate_tp_config,
)
from dlrover_tpu.trainer import train_lib

VOCAB, SEQ = 64, 32
BUCKETS = (8,)
SLOTS = 2


@pytest.fixture(scope="module")
def setup():
    # fp32 activations: greedy parity across TP widths is only bitwise
    # when the top-2 logit gap exceeds the reduction reassociation
    # error, which bf16 does not guarantee (tools/serve_bench.py has the
    # same note for the drill).
    config = TransformerConfig(
        vocab_size=VOCAB, d_model=32, num_heads=4, num_layers=2,
        d_ff=64, max_seq_len=SEQ, dtype=jnp.float32,
    )
    params = TransformerLM(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return config, params


def _engine(setup, **kw):
    config, params = setup
    kw.setdefault("slots", SLOTS)
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("seed", 0)
    return ServingEngine(config, params, **kw)


def _reqs(n=4, new=6, temp=0.0):
    out = []
    for i in range(n):
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(100 + i),
                               (5 + i % 4,), 1, VOCAB),
            np.int32,
        )
        out.append(Request(
            uid=f"r{i}", prompt=prompt,
            sampling=SamplingParams(max_new_tokens=new, temperature=temp),
        ))
    return out


def _tokens(results):
    return {u: r.tokens.tolist() for u, r in results.items()}


# -- TP mesh units ------------------------------------------------------------


def test_fold_width_largest_fitting_divisor():
    assert fold_width(4, 8) == 4
    assert fold_width(4, 3) == 2
    assert fold_width(4, 1) == 1
    assert fold_width(6, 4) == 3
    with pytest.raises(ValueError):
        fold_width(0, 4)


def test_validate_tp_config_names_failing_dim(setup):
    config, _ = setup
    validate_tp_config(config, 2)
    validate_tp_config(config, 4)
    with pytest.raises(ValueError, match="num_heads"):
        validate_tp_config(config, 8)


def test_tp_mesh_fold_preserves_logical_shape():
    tp = build_tp_mesh(4)
    assert isinstance(tp, ServeTPMesh)
    assert tp.logical_tp == 4 and tp.physical_tp == 4
    folded = tp.fold_to(2)
    assert folded.logical_tp == 4 and folded.physical_tp == 2


def test_serve_cache_key_covers_tp_and_spec_knobs(setup):
    # Single witness; knob-by-knob coverage of serve_cache_key is
    # enforced statically by tracelint CKY001 (tests/test_lint_gate.py).
    config, _ = setup

    def key(**kw):
        return serve_cache_key(config, slots=SLOTS, buckets=BUCKETS,
                               max_top_k=64, **kw)

    base = key()
    assert key() == base
    assert key(tp=(2, 2)) != key(tp=(2, 1))  # re-fold = new programs


# -- TP decode parity + sharding ----------------------------------------------


def test_tp2_greedy_parity_and_kv_bytes_shrink(setup):
    plain = _engine(setup)
    baseline = _tokens(plain.run(_reqs()))
    assert all(len(t) == 6 for t in baseline.values())
    tp2 = _engine(setup, tp=2, tp_devices=2)
    assert _tokens(tp2.run(_reqs())) == baseline
    # The KV pool is sharded on the heads axis: per-device bytes halve
    # (up to the replicated scalar rows).
    assert tp2.kv_device_bytes() < plain.kv_device_bytes()
    assert tp2.kv_device_bytes() <= plain.kv_device_bytes() / 2 * 1.15


@pytest.mark.slow  # one more TP fold to compile (~10s on 1 core)
def test_tp4_greedy_parity(setup):
    plain = _engine(setup)
    baseline = _tokens(plain.run(_reqs()))
    tp4 = _engine(setup, tp=4, tp_devices=4)
    assert _tokens(tp4.run(_reqs())) == baseline
    assert tp4.kv_device_bytes() < plain.kv_device_bytes() / 2


@pytest.mark.slow  # compiles the tp=4 and tp=2 folds (~20s on 1 core)
def test_fold_tp_mid_serve_then_back_zero_retrace(setup):
    engine = _engine(setup, tp=4, tp_devices=4)
    reqs = _reqs(n=6, new=8)
    for r in reqs[:3]:
        engine.submit(r)
    engine.step()
    # Cold fold 4→2 mid-serve: live KV rows re-place onto the new fold
    # and decoding continues — requests land complete.
    engine.fold_tp(2)
    engine.drain()
    for r in reqs[3:]:
        engine.submit(r)
    engine.step()
    # Warm fold back to a seen width must hit the program memo: zero
    # traces of any serve program while requests are still in flight.
    keys = ("serve_prefill", "serve_insert", "serve_decode")
    before = {k: train_lib.TRACE_COUNTS[k] for k in keys}
    engine.fold_tp(4)
    results = engine.drain()
    assert sorted(results) == sorted(r.uid for r in reqs)
    assert all(train_lib.TRACE_COUNTS[k] == before[k] for k in keys)
    # And the folded streams match the unfolded greedy baseline.
    baseline = _tokens(_engine(setup).run(_reqs(n=6, new=8)))
    assert _tokens(results) == baseline


# -- disaggregated prefill ----------------------------------------------------


def test_page_streaming_parity_vs_colocated(setup):
    colocated = _tokens(_engine(setup).run(_reqs()))
    fleet = ReplicaFleet(min_replicas=1)
    pre = _engine(setup, role="prefill")
    dec = _engine(setup, role="decode", seed=0)
    fleet.add_replica(pre)
    fleet.add_replica(dec)
    for r in _reqs():
        fleet.submit(r)
    for _ in range(200):
        if fleet.pending() == 0:
            break
        fleet.step()
    assert fleet.pending() == 0
    assert _tokens(fleet.results) == colocated
    stats = fleet.stats()
    assert stats["pages_streamed"] == len(colocated)
    assert stats["page_bytes_streamed"] > 0
    assert dec.stats()["pages_in"] == len(colocated)
    assert pre.stats()["pages_out"] == len(colocated)


def test_role_guards_reject_wrong_traffic(setup):
    dec = _engine(setup, role="decode")
    with pytest.raises(ValueError, match="decode"):
        dec.submit(_reqs(n=1)[0])
    pre = _engine(setup, role="prefill")
    pre.submit(_reqs(n=1)[0])
    assert pre.step() >= 0
    assert len(pre.outbox) == 1
    with pytest.raises(ValueError, match="prefill"):
        pre.insert_page(pre.outbox[0])


# -- speculative decoding -----------------------------------------------------


def test_spec_self_draft_accepts_nearly_everything(setup):
    config, params = setup
    plain = _tokens(_engine(setup).run(_reqs(new=8)))
    spec = _engine(setup, draft_config=config, draft_params=params,
                   spec_tokens=3)
    assert _tokens(spec.run(_reqs(new=8))) == plain
    stats = spec.stats()
    # The draft IS the target: fp32 keeps the γ+1-wide verify pass and
    # the incremental draft pass argmax-identical, so every proposal is
    # accepted.  The max_new_tokens boundary cuts what is committed, not
    # what ``verify`` counts: a request's prefill emits 1 token, its first
    # round 4 more (5 of 8), its second is the last, so each of the 4
    # requests proposes 2 x γ = 6 and accepts 6.  (The draft is fed pγ
    # too, so a fully accepted round leaves its cache whole: ROADMAP D15.)
    assert stats["spec_proposed"] == 4 * 2 * 3
    assert stats["spec_accepted"] == stats["spec_proposed"]


def test_spec_random_draft_never_changes_the_stream(setup):
    config, params = setup
    draft_config = dataclasses.replace(config, num_layers=1)
    draft_params = TransformerLM(draft_config).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    plain = _tokens(_engine(setup).run(_reqs(new=8)))
    spec = _engine(setup, draft_config=draft_config,
                   draft_params=draft_params, spec_tokens=3)
    # Rejection sampling's whole contract: a useless draft costs speed,
    # never correctness.
    assert _tokens(spec.run(_reqs(new=8))) == plain
    stats = spec.stats()
    assert stats["spec_proposed"] > 0
    assert stats["spec_accepted"] <= stats["spec_proposed"]
    assert stats["spec_accept_rate"] < 0.8


def test_spec_sampled_rows_complete(setup):
    config, params = setup
    spec = _engine(setup, draft_config=config, draft_params=params,
                   spec_tokens=3, seed=11)
    results = spec.run(_reqs(new=7, temp=0.8))
    assert len(results) == 4
    assert all(len(r.tokens) == 7 for r in results.values())
    assert all(np.all(r.tokens < VOCAB) for r in results.values())


def test_spec_headroom_enforced_at_submit(setup):
    config, params = setup
    plain = _engine(setup)
    spec = _engine(setup, draft_config=config, draft_params=params,
                   spec_tokens=3)
    prompt = np.arange(1, 6, dtype=np.int32)
    # bucket 8 + 22 new fits max_seq_len 32 plain, but not with the
    # γ=3 verify-write headroom on top.
    fits_plain = Request(
        uid="edge", prompt=prompt,
        sampling=SamplingParams(max_new_tokens=22),
    )
    plain.submit(fits_plain)
    with pytest.raises(ValueError, match="spec headroom"):
        spec.submit(fits_plain)


def test_spec_tokens_bounds(setup):
    config, params = setup
    for bad in (0, 15):
        with pytest.raises(ValueError, match="spec_tokens"):
            _engine(setup, draft_config=config, draft_params=params,
                    spec_tokens=bad)


# -- quantile confidence + scale policy ---------------------------------------


def test_nearest_rank_quantile():
    values = sorted(float(v) for v in range(1, 11))
    assert _nearest_rank(values, 0.50) == 5.0
    assert _nearest_rank(values, 0.95) == 10.0
    assert _nearest_rank([3.0], 0.95) == 3.0
    assert _nearest_rank([2.0, 4.0], 0.95) == 4.0


def test_maybe_scale_ignores_low_confidence_p95(setup):
    fleet = ReplicaFleet(spawn=lambda: _engine(setup, seed=9))
    fleet.add_replica(_engine(setup))
    policy = ServeScalePolicy(slo_p95_s=1.0, min_qps=0.0, min_samples=8)
    # p95 breach backed by 2 completions: noise, not a signal.
    shaky = dict(replicas=1.0, qps=5.0, p95_s=2.0, occupancy=0.2,
                 p95_n=2.0)
    fleet.stats = lambda: shaky  # type: ignore[method-assign]
    assert fleet.maybe_scale(policy) is None
    # Occupancy is always well-sampled: it still scales out.
    hot = dict(shaky, occupancy=0.95)
    fleet.stats = lambda: hot  # type: ignore[method-assign]
    assert fleet.maybe_scale(policy) == "out"
    # An unconfident LOW p95 cannot license a scale-in either.
    idle = dict(replicas=2.0, qps=5.0, p95_s=0.1, occupancy=0.05,
                p95_n=2.0)
    fleet.stats = lambda: idle  # type: ignore[method-assign]
    assert fleet.maybe_scale(policy) is None
    confident = dict(idle, p95_n=50.0)
    fleet.stats = lambda: confident  # type: ignore[method-assign]
    assert fleet.maybe_scale(policy) == "in"


def test_maybe_scale_prefill_pool_on_backlog(setup):
    fleet = ReplicaFleet(
        spawn=lambda: _engine(setup, seed=9),
        spawn_prefill=lambda: _engine(setup, role="prefill", seed=10),
    )
    fleet.add_replica(_engine(setup, role="prefill"))
    fleet.add_replica(_engine(setup, role="decode"))
    policy = ServeScalePolicy(min_qps=0.0, prefill_backlog_high=4.0)
    backed_up = dict(replicas=2.0, qps=5.0, p95_s=0.1, occupancy=0.2,
                     p95_n=50.0, prefill_replicas=1.0,
                     prefill_backlog=9.0)
    fleet.stats = lambda: backed_up  # type: ignore[method-assign]
    assert fleet.maybe_scale(policy) == "out"
    assert sum(
        1 for r in fleet._replicas.values()
        if getattr(r.engine, "role", "mixed") == "prefill"
    ) == 2
