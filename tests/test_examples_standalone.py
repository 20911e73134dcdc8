"""Example-suite smoke tests: each example script run standalone."""

import numpy as np
import pytest

from conftest import _run_example
from edl_tpu.distill.teacher_server import TeacherServer


@pytest.mark.integration
def test_resnet_example_standalone():
    out = _run_example("examples/resnet/train.py", [
        "--depth", "18", "--epochs", "1", "--steps_per_epoch", "4",
        "--total_batch_size", "8", "--image_size", "32",
        "--num_classes", "4"])
    assert out["model"] == "ResNet18_vd"
    assert out["steps"] == 4
    assert out["imgs_per_sec"] > 0


@pytest.mark.integration
def test_bert_pipeline_example_learns():
    out = _run_example("examples/bert_pipeline/train.py", [
        "--pp", "4", "--steps", "60", "--d_model", "32",
        "--num_heads", "2", "--mlp_dim", "64", "--seq_len", "16",
        "--vocab_size", "50", "--lr", "5e-3"],
        timeout=300, device_count=8)
    assert out["model"] == "bert_pipeline_pp4_dp2"
    # the parity task is learnable: loss must drop toward 0 from ~ln(2)
    assert out["final_loss"] < out["first_loss"] - 0.2, out


@pytest.mark.integration
def test_bert_pipeline_example_interleaved_learns():
    """--chunks 2: the interleaved (circular) engine behind the same
    example CLI, on a config where the Megatron-exact schedule wins."""
    out = _run_example("examples/bert_pipeline/train.py", [
        "--pp", "2", "--chunks", "2", "--num_layers", "4",
        "--num_micro", "8", "--steps", "60", "--d_model", "32",
        "--num_heads", "2", "--mlp_dim", "64", "--seq_len", "16",
        "--vocab_size", "50", "--lr", "5e-3"],
        timeout=300, device_count=8)
    assert out["model"] == "bert_pipeline_pp2_dp4_v2"
    assert out["final_loss"] < out["first_loss"] - 0.2, out


@pytest.mark.integration
def test_long_context_example_runs_with_remat():
    out = _run_example("examples/long_context/train.py", [
        "--sp", "4", "--seq_len", "256", "--steps", "6", "--d_model",
        "32", "--num_heads", "2", "--mlp_dim", "64", "--remat"],
        timeout=300, device_count=8)
    assert out["model"] == "bert_ring_sp4_dp2"
    assert out["seq_len"] == 256 and out["remat"]
    assert np.isfinite(out["final_loss"])
    assert out["tokens_per_sec"] > 0


@pytest.mark.integration
def test_gpt_example_learns_and_generates():
    out = _run_example("examples/gpt/train.py",
                       ["--steps", "150"], timeout=300)
    assert out["final_loss"] < 0.3 * out["first_loss"]
    assert out["gen_accuracy"] >= 0.75


@pytest.mark.integration
def test_ctr_example_learns():
    out = _run_example("examples/ctr/train.py", [
        "--epochs", "2", "--steps_per_epoch", "30",
        "--total_batch_size", "128", "--num_fields", "6",
        "--vocab_per_field", "50"])
    assert out["final_loss"] < 0.67  # below chance-level BCE (~0.69)


@pytest.mark.integration
def test_resnet_distill_example_with_teacher():
    def teacher_fn(feed):
        # a deterministic "teacher": logits derived from channel means
        img = feed["image"]
        base = img.mean(axis=(1, 2, 3), keepdims=False)
        return {"logits": np.stack([base * (i + 1) for i in range(10)],
                                   axis=1).astype(np.float32)}

    teacher = TeacherServer(
        teacher_fn, {"image": ([32, 32, 3], "<f4")},
        {"logits": ([10], "<f4")}, max_batch=16, host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/resnet_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4",
            "--total_batch_size", "8", "--teachers", teacher.endpoint])
        assert out["steps"] == 4
    finally:
        teacher.stop()


@pytest.mark.integration
def test_nlp_distill_example_with_bert_teacher():
    import jax
    import jax.numpy as jnp

    from edl_tpu.models import bert

    model = bert.bert_tiny(dtype=jnp.float32)
    dummy = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), dummy)

    @jax.jit
    def infer(ids):
        return model.apply(variables, ids)

    def teacher_fn(feed):
        return {"logits": np.asarray(infer(jnp.asarray(
            feed["input_ids"].astype(np.int32))))}

    teacher = TeacherServer(
        teacher_fn, {"input_ids": ([32], "<i4")}, {"logits": ([2], "<f4")},
        max_batch=16, host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/nlp_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4", "--batch_size", "8",
            "--teachers", teacher.endpoint])
        assert "final_loss" in out
    finally:
        teacher.stop()


@pytest.mark.integration
def test_gpt_distill_example_with_lm_teacher():
    """Sequence-level KD end-to-end: gpt teacher backend -> DistillReader
    -> student GPT trained on per-position soft targets."""
    from edl_tpu.distill.teacher_server import gpt_teacher

    teacher = gpt_teacher(vocab_size=64, seq_len=16, max_batch=8,
                          host="127.0.0.1").start()
    try:
        out = _run_example("examples/distill/gpt_distill.py", [
            "--epochs", "1", "--steps_per_epoch", "4",
            "--total_batch_size", "8", "--seq_len", "16",
            "--vocab_size", "64", "--teachers", teacher.endpoint])
        assert out["steps"] == 4
        assert np.isfinite(out["final_loss"])
    finally:
        teacher.stop()
