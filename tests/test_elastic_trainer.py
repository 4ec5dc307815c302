"""ElasticTrainer façade: direct API tests (no agent/master)."""

import os

import numpy as np
import pytest

from dlrover_tpu.models.gpt2 import gpt2_config
from dlrover_tpu.models.transformer import TransformerConfig
from dlrover_tpu.trainer.elastic_trainer import ElasticTrainer, TrainerConfig


@pytest.fixture(autouse=True)
def _isolated_shm(monkeypatch, tmp_path):
    """The flash-ckpt shm arena outlives processes and is named by the job
    tag: without a unique tag, a previous run's arena (holding a newer
    step) would satisfy this test's restore."""
    monkeypatch.setenv(
        "DLROVER_TPU_JOB", f"et{os.getpid()}_{tmp_path.name}"
    )
    monkeypatch.setenv(
        "DLROVER_TPU_SOCKET_DIR", str(tmp_path / "socks")
    )


def _tiny_model():
    return gpt2_config(
        "124m", num_layers=1, d_model=64, num_heads=2,
        vocab_size=256, max_seq_len=32,
    )


def _loader(batches, batch, seq, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(batches):
        toks = rng.integers(0, vocab, size=(batch, seq + 1), dtype=np.int32)
        yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


@pytest.mark.parametrize(
    "config_cls,field,value",
    [
        pytest.param(TransformerConfig, "fused_qkv", True, id="fused_qkv"),
        pytest.param(TransformerConfig, "pin_attn_layouts", False,
                     id="pin_attn_layouts"),
        pytest.param(TransformerConfig, "wo_transposed", False,
                     id="wo_transposed"),
        pytest.param(TransformerConfig, "scan_unroll", 1, id="scan_unroll"),
        pytest.param(TrainerConfig, "reuse_compiled", True,
                     id="reuse_compiled"),
    ],
)
def test_config_naming_a_removed_field_fails_at_construction(
    config_cls, field, value
):
    """A job spec or a benchmark file that still names a field PR 29
    removed meets the error where ``Config(**spec)`` is built, even with
    the value that used to be the default — not a silently ignored key."""
    with pytest.raises(TypeError, match=field):
        config_cls(**{field: value})


def test_fit_trains_and_reports(tmp_path):
    seen = []
    trainer = ElasticTrainer(
        _tiny_model(),
        TrainerConfig(
            global_batch_size=8, seq_len=32, learning_rate=1e-2,
            checkpoint_dir=str(tmp_path / "ckpt"), ckpt_every=4,
            report_every=2,
        ),
        client=None,
    )
    final = trainer.fit(
        _loader(20, 8, 32), max_steps=10,
        on_step=lambda step, m: seen.append(step),
    )
    trainer.close()
    assert final == 10
    assert seen == list(range(1, 11))


def test_resume_continues_from_committed_step(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    cfg = TrainerConfig(
        global_batch_size=8, seq_len=32, learning_rate=1e-2,
        checkpoint_dir=ckpt, ckpt_every=5,
    )
    first = ElasticTrainer(_tiny_model(), cfg, client=None)
    first.fit(_loader(20, 8, 32), max_steps=10)
    first.close()

    second = ElasticTrainer(_tiny_model(), cfg, client=None)
    assert second.step == 10  # restored
    final = second.fit(_loader(20, 8, 32, seed=1), max_steps=14)
    second.close()
    assert final == 14

    # A third trainer resuming AT max_steps must still re-commit its state
    # under its own world (the chaos-test regression).
    third = ElasticTrainer(_tiny_model(), cfg, client=None)
    assert third.step == 14
    assert third.fit(_loader(2, 8, 32), max_steps=14) == 14
    third.close()
    from dlrover_tpu.common.storage import (
        CheckpointDirLayout,
        PosixDiskStorage,
    )

    assert CheckpointDirLayout(ckpt).latest_step(PosixDiskStorage()) == 14
