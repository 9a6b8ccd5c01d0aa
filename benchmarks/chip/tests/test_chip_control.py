"""The comparison's control at a CPU-sized cell: the bfloat16 program
passes every limit on three seeds, and the reference computed with
float8 weights in the program's place fails at least one on each.  On
the chip the same reading is made at each cell's own size by
``limits_probe.py``; the limits it set are in ``limits/``."""
import pytest

import chip_tiny

SEEDS = (11, 12, 2**31 + 13)
# A tied unembedding is the 0.02-scale embedding, so at this width its
# logits are a sixth of an untied head's and so is every reading: over
# seven seeds the program read token_gap <= 0.007 and conf_err <= 0.0071,
# the float8 control token_gap >= 0.025 and conf_err >= 0.024.
TIED_LIMITS = dict(chip_tiny.LIMITS, token_gap=0.015, conf_err=0.014)


@pytest.mark.parametrize("mix,tied", [
    pytest.param("allexit", False, id="allexit"),
    pytest.param("noexit", False, id="noexit"),
    pytest.param("mixed", False, id="mixed"),
    pytest.param("noexit", True, id="noexit-tied")])
def test_program_passes_and_float8_control_fails(mix, tied):
    lim = TIED_LIMITS if tied else chip_tiny.LIMITS
    cell = chip_tiny.cell(mix, limits=lim, tie_word_embeddings=tied)
    for seed in SEEDS:
        out = chip_tiny.run(cell, seed, control=True)
        got = {k: v["value"] for k, v in out["checks"].items()}
        bad = out["control"]
        assert out["correct"], (seed, got)
        assert all(got[k] <= lim[k] for k in got), (seed, got)
        assert any(bad[k] > lim[k] for k in bad), (seed, bad)
        assert list(out)[-1] == "checks"
