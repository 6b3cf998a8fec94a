"""End-to-end behaviour tests for the whole system."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ref
from repro.core.alphabet import DNA
from repro.core.api import BuildReport, EraConfig, EraIndexer
from repro.core.prepare import PrepareStats
from repro.core.vertical import VerticalStats
from repro.data.strings import BlockStream, dataset, synthetic_string


class TestEraSystem:
    def test_full_dataset_pipeline(self):
        """dataset -> index -> query, the quickstart path."""
        s, alpha = dataset("dna", 3000, seed=1)
        idx = EraIndexer(alpha, EraConfig(memory_bytes=16384, r_bytes=512)).build(s)
        assert idx.n_leaves == len(s)
        pat = s[100:106]
        assert np.array_equal(idx.find(pat), ref.occurrences(s, pat))

    def test_repeat_heavy_string(self):
        """Planted repeats force deep elastic-range iterations."""
        s = synthetic_string(DNA, 2000, seed=2, repeat_fraction=0.8, repeat_len=128)
        stats = PrepareStats()
        rep = BuildReport(VerticalStats(), stats)
        idx = EraIndexer(DNA, EraConfig(memory_bytes=8192, r_bytes=256,
                                        build_impl="none")).build(s, rep)
        assert idx.n_leaves == len(s)
        assert stats.iterations >= 2  # repeats -> multiple range rounds

    def test_block_stream_skip_reads_less(self):
        s, _ = dataset("dna", 1 << 16, seed=3)
        full = BlockStream(s, block_bytes=1024)
        for _ in full.read_all():
            pass
        sparse = BlockStream(s, block_bytes=1024)
        offs = np.arange(0, len(s), 8192)
        for _ in sparse.read_for_offsets(offs, 64):
            pass
        assert sparse.stats.bytes_read < full.stats.bytes_read


class TestTrainSystem:
    def test_loss_decreases_small_model(self):
        from repro.launch.train import train
        params, losses = train("qwen3-1.7b", smoke=True, steps=30, batch=4,
                               seq=32, lr=2e-3, log_every=5)
        assert len(losses) >= 3
        assert losses[-1] < losses[0], losses

    def test_checkpoint_resume_exact(self, tmp_path):
        from repro.launch.train import train
        ck = str(tmp_path / "ck")
        train("qwen3-1.7b", smoke=True, steps=10, batch=2, seq=16,
              ckpt_dir=ck, ckpt_every=5, log_every=100)
        # resume from step 10 and run to 12: must not error, must load step 10
        params, _ = train("qwen3-1.7b", smoke=True, steps=12, batch=2, seq=16,
                          ckpt_dir=ck, ckpt_every=50, resume=True, log_every=100)
        assert params is not None


class TestServeSystem:
    def test_batched_generation(self):
        from repro.launch.serve import serve
        tokens, stats = serve("qwen3-1.7b", smoke=True, batch=3, prompt_len=8, gen=6)
        assert tokens.shape == (3, 6)
        assert stats["decode_tok_s"] > 0

    def test_ssm_generation(self):
        from repro.launch.serve import serve
        tokens, _ = serve("falcon-mamba-7b", smoke=True, batch=2, prompt_len=8, gen=4)
        assert tokens.shape == (2, 4)


class TestDedupPipeline:
    def test_dedup_flags_duplicates(self):
        from repro.data.tokens import dedup_mask
        rng = np.random.default_rng(0)
        seqs = rng.integers(0, 1000, size=(6, 64), dtype=np.int32)
        seqs[3] = seqs[1]  # exact duplicate content
        keep = dedup_mask(seqs, min_repeat=32)
        assert keep.sum() < 6  # at least one of the duplicates flagged


class TestBenchmarkRunner:
    @pytest.mark.parametrize("mode", [[], ["--smoke"]])
    def test_a_failed_suite_fails_the_run(self, mode, monkeypatch):
        """Every mode exits non-zero when a suite raised."""
        import sys

        from benchmarks import bench_fabric, run

        def boom(quick=True):
            raise RuntimeError("suite failed")

        monkeypatch.setattr(bench_fabric, "run", boom)
        monkeypatch.setattr(sys, "argv", ["run", "--only", "fabric", *mode])
        with pytest.raises(SystemExit) as e:
            run.main()
        assert e.value.code == 1
