"""Runtime substrate: checkpointing, fault-tolerant distributed ERA build,
optimizer behaviour, gradient compression, data pipeline determinism."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import ref
from repro.core.alphabet import DNA
from repro.core.api import EraConfig, EraIndexer
from repro.data.tokens import TokenPipelineConfig, batch_at_step
from repro.launch.era_run import build_distributed
from repro.optim import adamw, compress
from repro.runtime import checkpoint
from repro.runtime.scheduler import WorkQueue


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
                "b": {"c": jnp.ones((4,), jnp.int32)}}
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, tree, step=7, meta={"tag": "x"})
        got, meta = checkpoint.restore(p, tree)
        assert meta["step"] == 7 and meta["tag"] == "x"
        np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))

    def test_restore_validates_shapes(self, tmp_path):
        p = str(tmp_path / "ck.npz")
        checkpoint.save(p, {"a": jnp.zeros((2, 2))})
        with pytest.raises(ValueError):
            checkpoint.restore(p, {"a": jnp.zeros((3, 3))})

    def test_latest_step(self, tmp_path):
        for s in (10, 30, 20):
            checkpoint.save(str(tmp_path / f"step_{s}.npz"), {"a": jnp.zeros(1)}, step=s)
        assert checkpoint.latest_step_path(str(tmp_path)).endswith("step_30.npz")

    def test_train_state_roundtrip(self, tmp_path):
        from repro.models import transformer as T
        from repro.models.config import smoke_config
        from repro.models.registry import get_config
        cfg = smoke_config(get_config("qwen3-1.7b"))
        params = T.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        opt = adamw.init(params)
        p = str(tmp_path / "train.npz")
        checkpoint.save(p, (params, opt), step=3)
        (p2, o2), meta = checkpoint.restore(p, (params, opt))
        assert meta["step"] == 3
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDistributedEra:
    def test_matches_serial(self):
        s = DNA.random_string(600, seed=31)
        cfg = EraConfig(memory_bytes=2048, r_bytes=128, build_impl="none")
        serial = EraIndexer(DNA, cfg).build(s)
        dist, qstats, _ = build_distributed(s, DNA, cfg, n_workers=3)
        assert set(dist.subtrees) == set(serial.subtrees)
        for p in serial.subtrees:
            np.testing.assert_array_equal(dist.subtrees[p].ell, serial.subtrees[p].ell)
        assert qstats["done"] == qstats["total"]

    def test_survives_node_failure(self):
        s = DNA.random_string(500, seed=32)
        cfg = EraConfig(memory_bytes=1024, r_bytes=128, build_impl="none")
        idx, qstats, _ = build_distributed(
            s, DNA, cfg, n_workers=3, fail_worker="w1", fail_after=1)
        assert qstats["done"] == qstats["total"]
        assert idx.n_leaves == len(s)
        # queries still correct after recovery
        pat = s[5:9]
        np.testing.assert_array_equal(idx.find(pat), ref.occurrences(s, pat))

    def test_checkpoint_recovery_skips_done_groups(self, tmp_path):
        s = DNA.random_string(400, seed=33)
        cfg = EraConfig(memory_bytes=1024, r_bytes=128, build_impl="none")
        ck = str(tmp_path / "groups.jsonl")
        build_distributed(s, DNA, cfg, n_workers=2, checkpoint_path=ck)
        # second run replays from the log: queue reports all done, no pulls
        q = WorkQueue(checkpoint_path=ck)
        q.add_tasks([1.0] * sum(1 for _ in open(ck)))
        assert q.drained


class TestOptim:
    def test_adamw_converges_quadratic(self):
        cfg = adamw.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                                total_steps=200, schedule="constant")
        params = {"x": jnp.array([5.0, -3.0])}
        opt = adamw.init(params)
        loss = lambda p: jnp.sum(jnp.square(p["x"] - jnp.array([1.0, 2.0])))
        for _ in range(150):
            g = jax.grad(loss)(params)
            params, opt, _ = adamw.update(cfg, g, opt, params)
        assert float(loss(params)) < 1e-2

    def test_clipping(self):
        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = adamw.clip_by_global_norm(g, 1.0)
        assert float(adamw.global_norm(clipped)) <= 1.0 + 1e-5
        assert float(norm) > 100.0

    def test_schedule_warmup_and_decay(self):
        cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
        lr0 = float(adamw.schedule_lr(cfg, jnp.asarray(0)))
        lr10 = float(adamw.schedule_lr(cfg, jnp.asarray(10)))
        lr99 = float(adamw.schedule_lr(cfg, jnp.asarray(99)))
        assert lr0 < lr10 and lr99 < lr10
        assert abs(lr10 - 1.0) < 0.1


class TestCompression:
    def test_quant_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
        q, s = compress.quantize_int8(x)
        err = np.abs(np.asarray(compress.dequantize_int8(q, s) - x))
        assert err.max() <= float(s) / 2 + 1e-6

    def test_error_feedback_reduces_bias(self):
        """With EF, the accumulated compressed sum tracks the true sum."""
        rng = np.random.default_rng(1)
        g_true = jnp.asarray(rng.normal(size=(64,)), jnp.float32) * 0.01
        g_tree = {"g": g_true}
        err_tree = compress.init_error_state(g_tree)
        acc_c = np.zeros(64)
        for step in range(50):
            (q, s), err_tree = compress.compress_with_feedback(g_tree, err_tree)
            acc_c += np.asarray(compress.dequantize_int8(q["g"], s["g"]))
        acc_t = np.asarray(g_true) * 50
        np.testing.assert_allclose(acc_c, acc_t, atol=float(s["g"]) * 2 + 1e-5)


class TestDataPipeline:
    def test_deterministic_restart(self):
        cfg = TokenPipelineConfig(vocab=100, batch=4, seq_len=16, seed=5)
        a = batch_at_step(cfg, 42)
        b = batch_at_step(cfg, 42)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        c = batch_at_step(cfg, 43)
        assert not np.array_equal(a["tokens"], c["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = TokenPipelineConfig(vocab=50, batch=2, seq_len=8, seed=0)
        b = batch_at_step(cfg, 0)
        assert b["tokens"].shape == b["labels"].shape == (2, 8)


class TestCompileCache:
    """One placeable persistent-compilation-cache directory."""

    @pytest.fixture
    def restore_config(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_environment_names_the_cache(self, monkeypatch, tmp_path,
                                         restore_config):
        from repro.launch.compile_cache import use_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)

    def test_default_is_fixed_and_ignored_in_checkout(self, monkeypatch,
                                                      restore_config):
        from pathlib import Path

        from repro.launch.compile_cache import use_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = Path(__file__).resolve().parents[1]
        path = use_compile_cache()
        assert path == str(root / ".jax_cache") == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
