"""End-to-end pipeline tests: data loader, train driver (with resume),
serving engine, and a real (subprocess, 512-device) dry-run cell."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.data.lm_data import MarkovCorpus, TokenLoader


class TestData:
    def test_corpus_learnable_structure(self):
        c = MarkovCorpus(vocab=128, seed=0)
        rng = np.random.default_rng(0)
        toks = c.sample(rng, 4, 256)
        assert toks.shape == (4, 256)
        assert toks.min() >= 0 and toks.max() < 128
        # successor entropy is bounded: next token comes from 8 choices
        pairs = set()
        for row in toks:
            pairs.update(zip(row[:-1], row[1:]))
        succ = {}
        for a, b in pairs:
            succ.setdefault(a, set()).add(b)
        assert max(len(v) for v in succ.values()) <= 8

    def test_loader_prefetch_and_shapes(self):
        c = MarkovCorpus(vocab=64, seed=1)
        loader = TokenLoader(c, batch=2, seq=32, prefetch=2, seed=2)
        b1 = next(loader)
        b2 = next(loader)
        assert b1["tokens"].shape == (2, 32)
        assert not np.array_equal(b1["tokens"], b2["tokens"])
        loader.close()


class TestTrainDriver:
    def test_loss_descends_and_resumes(self, tmp_path):
        from repro.launch import train as train_cli

        r1 = train_cli.main([
            "--arch", "qwen3-4b", "--smoke", "--steps", "12", "--batch", "4",
            "--seq", "64", "--ckpt", str(tmp_path), "--ckpt-every", "6",
            "--log-every", "50"])
        assert len(r1["losses"]) == 12
        r2 = train_cli.main([
            "--arch", "qwen3-4b", "--smoke", "--steps", "16", "--batch", "4",
            "--seq", "64", "--ckpt", str(tmp_path), "--ckpt-every", "6",
            "--log-every", "50"])
        assert len(r2["losses"]) == 4  # resumed at step 12
        assert np.isfinite(r2["losses"]).all()


class TestServeEngine:
    def test_batched_requests_complete(self):
        from repro.configs import get_smoke
        from repro.nn import init_params
        from repro.serving import Request, ServeEngine

        cfg = get_smoke("qwen3-4b")
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        engine = ServeEngine(params, cfg, batch=2, max_seq=48)
        rng = np.random.default_rng(0)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                        max_new=6) for i in range(5)]
        engine.run(reqs)
        assert all(r.done for r in reqs)
        assert all(len(r.out) >= 6 for r in reqs)
        assert all(0 <= t < cfg.vocab for r in reqs for t in r.out)

    def test_pending_drains_into_freed_slot_same_step(self):
        """A queued request must start decoding the step a slot frees
        (admission staleness fix): submit 2 into a 1-slot table, drive
        the first to completion — the pending one is prefil led by the
        same step() that freed the slot, not a step later."""
        from repro.configs import get_smoke
        from repro.nn import init_params
        from repro.serving import Request, ServeEngine

        cfg = get_smoke("qwen3-4b")
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        engine = ServeEngine(params, cfg, batch=1, max_seq=48)
        rng = np.random.default_rng(1)
        first = Request(rid=0, prompt=rng.integers(
            0, cfg.vocab, 8).astype(np.int32), max_new=2)
        second = Request(rid=1, prompt=rng.integers(
            0, cfg.vocab, 8).astype(np.int32), max_new=2)
        assert engine.submit(first) is True
        assert engine.submit(second) is False  # table full -> queued
        assert engine.pending == [second]
        engine.step()  # first reaches max_new=2 and frees its slot
        assert first.done
        assert engine.pending == []  # drained by the SAME step
        assert engine.slots[0] is second  # already prefil led
        assert len(second.out) == 1
        engine.step()
        assert second.done

    def test_greedy_deterministic(self):
        from repro.configs import get_smoke
        from repro.nn import init_params
        from repro.serving import Request, ServeEngine

        cfg = get_smoke("qwen3-4b")
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        outs = []
        for _ in range(2):
            engine = ServeEngine(params, cfg, batch=1, max_seq=32)
            req = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                          max_new=5)
            engine.run([req])
            outs.append(tuple(req.out))
        assert outs[0] == outs[1]


@pytest.mark.slow
class TestDryRunIntegration:
    """One real 512-device dry-run cell in a subprocess (the deliverable-e
    path end to end, cheapest cell)."""

    def test_dryrun_cell_artifact(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun",
             "--arch", "rwkv6-3b", "--shape", "decode_32k",
             "--out", str(tmp_path)],
            capture_output=True, text=True, timeout=900,
            env={**os.environ, "PYTHONPATH": "src"},
            cwd=Path(__file__).resolve().parents[1])
        assert proc.returncode == 0, proc.stderr[-2000:]
        arts = list(tmp_path.glob("*.json"))
        assert len(arts) == 1
        rec = json.loads(arts[0].read_text())
        assert rec["chips"] == 256
        r = rec["roofline"]
        assert r["flops_per_chip"] > 0
        assert r["bottleneck"] in ("compute", "memory", "collective")
        assert rec["memory"]["total_bytes_per_device"] > 0
