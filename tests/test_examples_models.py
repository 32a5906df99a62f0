"""End-to-end CI of the Llama and ResNet examples and the benchmark scripts,
each driven in a subprocess exactly as a user would run it (``_run_example``
of ``tests/test_examples.py``, which holds the MNIST examples of every
distribution mode; a file of their own because the driver hands a worker a
file at a time, and the two together were a long unit of work)."""

import re

import pytest

from test_examples import _run_example

# Full example trainings in subprocesses: minutes of wall time.  The fast
# core-path loop deselects these (pytest -m "not heavy").
pytestmark = pytest.mark.heavy


class TestLlamaExamples:
    def test_llama_dp_tp(self):
        """BASELINE config 5: Llama data+model parallel (dp x tp mesh) with
        the 8B-scale memory controls on (remat + chunked loss).  The example
        itself asserts loss decrease; rc 0 == converged."""
        out = _run_example("train_llama.py", "--dp", "2", "--tp", "4",
                           "--steps", "40", "--loss-chunk", "16",
                           subdir="llama")
        assert "tok/s" in out and "loss" in out

    def test_llama_train_then_generate(self):
        """Train -> generate -> score against the Markov oracle: after
        training, generated transitions must be legal well above the 0.8%
        chance level (a true end-to-end generation-quality check).  The
        config measures ~15% over 192 scored transitions, so the 5%
        threshold has a wide margin against numeric drift."""
        out = _run_example("train_llama.py", "--dp", "2", "--tp", "4",
                           "--steps", "550", "--batch", "16", "--lr", "2e-2",
                           "--generate", "48", subdir="llama")
        m = re.search(r"generation legality: ([0-9.]+)%", out)
        assert m, out
        assert float(m.group(1)) > 5.0, out   # ~6x chance, ~1/3 of measured

    def test_llama_dp_sp_tp_ring(self):
        """Long-context variant: dp x sp x tp with ring attention."""
        out = _run_example("train_llama.py", "--dp", "2", "--sp", "2",
                           "--tp", "2", "--attn", "ring", "--steps", "25",
                           subdir="llama")
        assert "tok/s" in out

    def test_llama_pipeline(self):
        """Pipeline variant: decoder layers as GPipe stages over pp."""
        out = _run_example("train_llama.py", "--pp", "2", "--microbatches",
                           "4", "--batch", "8", "--steps", "25",
                           subdir="llama")
        assert "pipeline: 2 stages" in out and "tok/s" in out

    def test_llama_moe_expert_parallel(self):
        """MoE variant: routed-expert FFN sharded over an ep axis (the
        example itself asserts loss decrease; rc 0 == converged)."""
        out = _run_example("train_llama.py", "--dp", "2", "--ep", "4",
                           "--tp", "1", "--moe-experts", "4", "--steps",
                           "30", subdir="llama")
        assert "'ep': 4" in out and "tok/s" in out


class TestResNetExample:
    def test_train_eval_checkpoint_resume(self, tmp_path):
        """BASELINE config 2 end to end: train, EMA BN stats, inference-mode
        eval, async checkpointing, then resume (params AND stats restored)
        continuing to a better model."""
        d = str(tmp_path / "ck")
        out1 = _run_example("train_resnet.py", "--epochs", "2",
                            "--ckpt-dir", d, "--ckpt-every", "15",
                            subdir="resnet")
        m1 = re.search(r"inference-mode accuracy ([0-9.]+)%", out1)
        assert m1, out1
        out2 = _run_example("train_resnet.py", "--epochs", "1",
                            "--ckpt-dir", d, subdir="resnet")
        assert "resumed from step" in out2, out2
        m2 = re.search(r"inference-mode accuracy ([0-9.]+)%", out2)
        assert m2, out2
        assert float(m2.group(1)) >= float(m1.group(1)), (out1, out2)
        assert float(m2.group(1)) > 70.0, out2


class TestBenchmarks:
    def test_llama_bench_smoke(self):
        """benchmarks/llama_bench.py runs end to end and emits parseable
        JSON for both the train and decode metrics."""
        import json

        out = _run_example("llama_bench.py", "--preset", "tiny",
                           "--steps", "4", subdir=None, top="benchmarks",
                           timeout=300)
        lines = [json.loads(l) for l in out.splitlines() if l.strip()]
        # Headline metric rows carry value/unit; the autotune section
        # (PR 9) rides as its own line without them.
        metrics = [l for l in lines if "value" in l]
        assert len(metrics) == 2, out
        assert all(l["value"] > 0 and l["unit"] == "tokens/sec"
                   for l in metrics), metrics
        assert any("autotune" in l for l in lines), out

    def test_moe_volume_smoke(self):
        """benchmarks/moe_volume.py --quick compiles dense + one MoE config
        and reports collective volumes (the ep communication analysis)."""
        import json

        out = _run_example("moe_volume.py", "--quick", subdir=None,
                           top="benchmarks", timeout=300)
        lines = [json.loads(l) for l in out.splitlines() if l.strip()]
        assert len(lines) == 3, out
        dense, moe, a2a = lines
        assert dense["config"] == "dense" and moe["ep"] == 4
        assert moe["collective_total_mb"] > dense["collective_total_mb"] > 0
        # The token-shuffle layer's exchange is a REAL all-to-all.
        assert a2a["config"].startswith("a2a-layer")
        assert a2a["all_to_all_mb"] > 0

    def test_vit_bench_smoke(self):
        """benchmarks/vit_bench.py runs end to end with remat and emits
        parseable JSON."""
        import json

        out = _run_example("vit_bench.py", "--preset", "tiny", "--steps",
                           "4", "--remat", "dots", subdir=None,
                           top="benchmarks", timeout=300)
        lines = [json.loads(l) for l in out.splitlines() if l.strip()]
        metrics = [l for l in lines if "value" in l]
        assert len(metrics) == 1, out
        assert metrics[0]["value"] > 0 and metrics[0]["unit"] == "images/sec"
        assert any("autotune" in l for l in lines), out
