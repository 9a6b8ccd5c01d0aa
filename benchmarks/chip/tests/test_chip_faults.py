"""A whole run (the chip check skipped) with the timed path broken
underneath must come out ``correct: false``: a token altered where it is
produced, a decode step that returns its cache unchanged, and half of
each lane left out (its rows answered with the other half's tokens).  A
one-chip cell has no exchange between chips to leave out.

The check replays a sample of the finished requests; a fault that hits
half the rows escapes a sample of k with odds 2^-k, so these runs sample
twelve (the cells sample four: 1/16 a run, and every check makes many
runs)."""
import numpy as np
import pytest

import chip_tiny

SEED = 2**31 + 29


def _alter_tokens(monkeypatch):
    from repro.serving import runtime
    orig = runtime.DeviceDecodeLoop.run_chunk

    def run_chunk(self, *a, **kw):
        chunk, cache, state = orig(self, *a, **kw)
        chunk.tokens = (chunk.tokens + 1) % 256
        return chunk, cache, state

    monkeypatch.setattr(runtime.DeviceDecodeLoop, "run_chunk", run_chunk)


def _cache_unchanged(monkeypatch):
    from repro.core import exec as ex
    orig = ex.StagedExecutor.decode_step

    def decode_step(self, params, token, cache, state, extra=None):
        d, _, st = orig(self, params, token, cache, state, extra)
        return d, cache, st

    monkeypatch.setattr(ex.StagedExecutor, "decode_step", decode_step)


def _half_lane(monkeypatch):
    from repro.serving import runtime
    orig = runtime.DeviceDecodeLoop.run_chunk

    def run_chunk(self, *a, **kw):
        chunk, cache, state = orig(self, *a, **kw)
        half = chunk.tokens.shape[1] // 2
        chunk.tokens = np.concatenate(
            [chunk.tokens[:, :half], chunk.tokens[:, :half]], axis=1)
        return chunk, cache, state

    monkeypatch.setattr(runtime.DeviceDecodeLoop, "run_chunk", run_chunk)


def test_unbroken_run_is_correct():
    out = chip_tiny.run(chip_tiny.cell("noexit"), SEED)
    assert out["correct"] and out["failed"] == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _cache_unchanged,
                                   _half_lane])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    lim = dict(chip_tiny.LIMITS, sample_requests=12)
    out = chip_tiny.run(chip_tiny.cell("noexit", limits=lim), SEED)
    assert not out["correct"]
    assert any(v["value"] > v["limit"] for v in out["checks"].values())
