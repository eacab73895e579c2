"""The runnable examples must actually run (the reference's de-facto test
strategy was examples-as-integration-tests — SURVEY.md §4)."""

import os

import pytest
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_example(script: str, *args):
    """Run an example in a child on the forced virtual 8-CPU mesh
    (JAX_PLATFORMS=cpu: a child never wants the chip); shared by every
    example test."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO)
    return subprocess.run(
        [sys.executable, str(REPO / "examples" / script), *args],
        env=env, capture_output=True, text=True, timeout=900,
    )


@pytest.mark.slow
def test_parallelism_example_runs_all_strategies():
    proc = run_example("parallelism.py", "--quick")
    assert proc.returncode == 0, proc.stderr[-2000:]
    for tag in ("[dp]", "[tp]", "[fsdp]", "[pp]", "[sp]", "[ep]"):
        assert tag in proc.stdout, (tag, proc.stdout)


@pytest.mark.slow
def test_mnist_example_runs_end_to_end():
    """The reference's canonical example: transformers → trainer →
    predictor → evaluator, via the CLI."""
    proc = run_example("mnist.py", "--model", "mlp", "--rows", "2048",
                       "--epochs", "2", "--batch-size", "32")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "test accuracy:" in proc.stdout, proc.stdout
    acc = float(proc.stdout.rsplit("test accuracy:", 1)[1].strip())
    assert acc > 0.8, proc.stdout  # synthetic mnist is easy — it must learn


@pytest.mark.slow
def test_longcontext_example_runs_quick():
    proc = run_example("longcontext.py", "--quick")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[flash+remat]" in proc.stdout
    assert "[sp]" in proc.stdout


@pytest.mark.slow
def test_lm_example_runs_and_generates():
    """Causal-LM example: trains on the cyclic language and the KV-cached
    generations continue it (the script self-checks accuracy > 0.9)."""
    proc = run_example("lm.py", "--quick")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout, proc.stdout


@pytest.mark.slow
def test_lm_example_modern_decoder_combo():
    """RoPE + GQA + sliding window through the example CLI."""
    proc = run_example("lm.py", "--quick", "--pos", "rope",
                       "--kv-heads", "2", "--window", "16")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout, proc.stdout
