"""End-to-end behaviour tests: the paper's pipeline on a tiny scale, the
serving engine, backtrack training, checkpointing, data pipeline.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import load_checkpoint, save_checkpoint
from repro.configs import get_config, reduced
from repro.core.resnet_trainer import (collect_outputs, evaluate_tradeoff,
                                       train_backtrack)
from repro.core.training import backtrack_training_plan
from repro.data.synth_images import make_image_splits
from repro.data.lm_pipeline import SyntheticLMStream
from repro.models.model import build_model
from repro.models.resnet import CIResNet
from repro.serving import CascadeServingEngine, Request


@pytest.fixture(scope="module")
def tiny_trained():
    train, val, test = make_image_splits(n_classes=4, n_train=512, n_val=256,
                                         n_test=256, seed=5)
    model = CIResNet(n_blocks=1, n_classes=4, enhance_dim=32)
    report = train_backtrack(model, train, n_epochs=2, batch_size=64,
                             augment=False, test=test)
    return model, report, (train, val, test)


def test_backtrack_training_learns(tiny_trained):
    model, report, (train, val, test) = tiny_trained
    # final component must beat chance (0.25) clearly
    assert report.component_acc[2] > 0.5
    # phase-1 loss decreased
    pl = report.phase_losses["backbone+last"]
    assert np.mean(pl[-5:]) < np.mean(pl[:5])


def test_backtrack_phases_freeze_backbone(tiny_trained):
    """Head phases must not change the backbone (Algorithm 2)."""
    plan = backtrack_training_plan(3)
    assert plan[0].train_backbone and plan[0].epochs == 1.25
    assert all(not p.train_backbone for p in plan[1:])
    assert [p.loss_head for p in plan] == [2, 0, 1]


def test_tradeoff_sweep_monotone(tiny_trained):
    model, report, (train, val, test) = tiny_trained
    sweep = evaluate_tradeoff(model, report.params, report.state, val, test,
                              [0.0, 0.05, 0.2], 4)
    speedups = [r.speedup for _, r in sweep]
    assert speedups == sorted(speedups)          # larger eps -> faster
    assert all(r.speedup >= 1.0 for _, r in sweep)
    fracs = sweep[-1][1].exit_fractions
    assert abs(fracs.sum() - 1.0) < 1e-9


def test_confidence_accuracy_correlation(tiny_trained):
    """Fig-4 claim: higher-confidence samples are more accurate."""
    model, report, (_, _, test) = tiny_trained
    confs, preds, corrects = collect_outputs(model, report.params,
                                             report.state, test)
    m = 2
    order = np.argsort(confs[m])
    lo = corrects[m][order[:len(order) // 3]].mean()
    hi = corrects[m][order[-len(order) // 3:]].mean()
    assert hi >= lo


# ---------------------------------------------------------------------------

def test_serving_engine_thresholds_trade_speed(tmp_path):
    cfg = reduced(get_config("qwen2.5-3b")).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def run(th):
        c = cfg.with_cascade(thresholds=(th, 0.0))
        eng = CascadeServingEngine(c, model, params, lane_batch=2,
                                   n_lanes=1, cache_len=32)
        for i in range(4):
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, c.vocab_size, 6).astype(np.int32), max_new_tokens=4))
        eng.run(100)
        return eng

    easy = run(0.0)     # everything exits at component 0
    hard = run(1.1)     # nothing exits early
    assert easy.stats()["requests_finished"] == 4
    assert hard.stats()["requests_finished"] == 4
    assert easy.speedup() > hard.speedup()
    assert hard.speedup() == pytest.approx(1.0)
    assert easy.stats()["mean_exit_depth"] == 0.0


def test_checkpoint_roundtrip(tmp_path):
    cfg = reduced(get_config("yi-9b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    path = save_checkpoint(str(tmp_path), 7, params)
    assert os.path.exists(path)
    restored = load_checkpoint(str(tmp_path), params)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    cfg = reduced(get_config("yi-9b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), 1, params)
    other = build_model(reduced(get_config("yi-9b"), d_model=128)).init(
        jax.random.PRNGKey(0))
    with pytest.raises((ValueError, KeyError)):
        load_checkpoint(str(tmp_path), other)


def test_lm_stream_is_learnable_markov():
    s = SyntheticLMStream(vocab_size=64, seq_len=32, batch_size=4,
                          easy_frac=1.0, seed=0)
    x, y = next(s)
    assert x.shape == (4, 32) and y.shape == (4, 32)
    # with easy_frac=1 every next token is one of the 4 Markov successors
    nxt = s.next_tok[x.reshape(-1)]
    assert (y.reshape(-1)[:, None] == nxt).any(axis=1).all()


def test_synth_images_difficulty_controls_noise():
    train, _, _ = make_image_splits(n_classes=4, n_train=256, n_val=8,
                                    n_test=8, seed=1)
    assert train.images.shape == (256, 32, 32, 3)
    # standardized per-sample
    assert np.allclose(train.images.mean(axis=(1, 2, 3)), 0, atol=1e-4)


def test_trainability_mask_llm_layout():
    """Algorithm-2 phase masks over the CascadeModel pytree: head phases
    freeze the backbone and other heads."""
    from repro.core.training import backtrack_training_plan, trainability_mask
    cfg = reduced(get_config("qwen2.5-3b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    plan = backtrack_training_plan(cfg.cascade.n_components)
    m0 = trainability_mask(params, plan[0])       # backbone+last
    assert bool(m0["embed"]) and bool(m0["lm_head"])
    assert not bool(m0["exits"][0]["norm"]["w"])
    m1 = trainability_mask(params, plan[1])       # head 0 only
    assert bool(m1["exits"][0]["norm"]["w"])
    assert not bool(m1["embed"]) and not bool(m1["lm_head"])


def test_compile_cache_dir_env_wins_else_repo_path(monkeypatch):
    """The persistent compilation cache lives where
    JAX_COMPILATION_CACHE_DIR says (the helper sets nothing then), and
    otherwise at the fixed <repo>/.jax_cache."""
    from pathlib import Path

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV)
        got = compile_cache.enable_compile_cache()
        repo = Path(__file__).resolve().parents[1]
        assert got == str(repo / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
