"""Traffic generation and the harness's client loop on a CPU-sized cell:
seeded requests, closed-loop refills, the mixed point's median thresholds,
and the end-to-end arithmetic on a hand-built timeline."""
import numpy as np
import pytest

import chip_tiny
import harness

SEED = 2**31 + 17


def _gen(mix="allexit", seed=SEED, stream="window"):
    c = chip_tiny.cell(mix)
    return harness.generator_module(c.traffic).Generator(
        c.traffic, seed, c.config["vocab_size"], stream=stream)


def test_same_seed_same_requests():
    a, b = _gen(), _gen()
    ra, rb = a.start(), b.start()
    assert [r["rid"] for r in ra] == [r["rid"] for r in rb]
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(x["prompt"], y["prompt"])
    # the next request depends on the client and its count, not on order
    nxa = [a.on_finish(r)[0] for r in ra[::-1]]
    nxb = [b.on_finish(r)[0] for r in rb]
    by_rid = {r["rid"]: r["prompt"] for r in nxb}
    for r in nxa:
        np.testing.assert_array_equal(r["prompt"], by_rid[r["rid"]])
    other = _gen(seed=SEED + 1).start()
    assert not np.array_equal(other[0]["prompt"], ra[0]["prompt"])
    warm = _gen(stream="warmup").start()
    assert not np.array_equal(warm[0]["prompt"], ra[0]["prompt"])
    assert all(r["prompt"].shape == (16,) and r["max_new_tokens"] == 8
               for r in ra)
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 256
               for r in ra)


def _engine(mix):
    import jax
    c = chip_tiny.cell(mix)
    gen_mod = harness.generator_module(c.traffic)
    eng, ths = harness.build_engine(c, SEED, lambda *a: None)
    gen = gen_mod.Generator(c.traffic, SEED, c.config["vocab_size"])
    return c, eng, ths, harness.Clients(eng, gen,
                                        jax.profiler.TraceAnnotation)


def test_closed_loop_keeps_every_client_in_flight_and_refills_lanes_whole():
    c, eng, _, cl = _engine("allexit")
    clients, lane_batch = c.traffic["clients"], eng.lane_batch
    cl.submit(cl.gen.start())
    for _ in range(12):
        done = cl.step()
        for rec in done:
            cl.submit(cl.gen.on_finish(rec.spec))
        assert len(cl.open) == clients
    prefills = [e for e in eng.flight.events.snapshot()
                if e["name"] == "lane_prefill"]
    assert len(prefills) >= 2 * eng.n_lanes
    for e in prefills:
        assert e["attrs"]["rids"] == lane_batch
        assert e["attrs"]["positions"] == c.traffic["prompt_len"]
    waves = {}
    for r in cl.recs.values():
        if r.lane is not None:
            waves.setdefault((r.lane, r.first_step), []).append(r)
    assert all(len(v) == lane_batch for v in waves.values())
    for r in cl.recs.values():
        if r.result is not None:
            assert r.n == c.traffic["max_new_tokens"]


def test_mixed_quantile_rule_splits_exits_near_half():
    c, eng, ths, cl = _engine("mixed")
    assert len(ths) == 2 and all(0.0 < t < 1.0 for t in ths)
    cl.submit(cl.gen.start())
    for _ in range(60):
        for rec in cl.step():
            cl.submit(cl.gen.on_finish(rec.spec))
    exits = np.array([e for r in cl.recs.values() if r.result is not None
                      for e in r.result["exit_depths"]])
    assert exits.size >= 200
    share0 = float(np.mean(exits == 0))
    assert 0.3 <= share0 <= 0.7, share0
    assert np.all(np.bincount(exits, minlength=3) > 0)


@pytest.mark.parametrize("seed", [SEED, 11, 2**31 + 29])
def test_mixed_thresholds_are_the_median_prefill_confidence(seed):
    import jax.numpy as jnp
    c = chip_tiny.cell("mixed")
    eng, ths = harness.build_engine(c, seed, lambda *a: None)
    assert ths == c.traffic["thresholds"]
    assert tuple(eng.cfg.cascade.thresholds) == tuple(ths) + (0.0,)
    gen = harness.generator_module(c.traffic).Generator(c.traffic, seed, 256)
    prompts = np.stack([r["prompt"] for r in gen.start()])
    logits, _ = eng.model.forward_train(eng.params, jnp.asarray(prompts))
    for m in range(2):
        z = np.asarray(logits[m], np.float64)
        conf = 1.0 / np.exp(z - z.max(-1, keepdims=True)).sum(-1)
        assert 0.4 <= np.mean(conf >= ths[m]) <= 0.6


def _rec(rid, t_sub, marks, done=None, n_new=None, lane=0, slot=0, step=1,
         exits=None):
    r = harness.Rec(spec={"rid": rid, "client": 0,
                          "prompt": np.zeros(10, np.int32),
                          "max_new_tokens": n_new or marks[-1][1]},
                    t_submit=t_sub, lane=lane, slot=slot, first_step=step)
    for t, n in marks:
        if r.n == 0:
            r.t_first = t
        r.n, r.t_last = n, t
        r.marks.append((t, n))
    r.t_done = done
    r.exits = exits or [0] * r.n
    return r


def test_end_to_end_arithmetic_on_a_hand_built_timeline():
    # window [10, 20]
    recs = [
        # whole request inside: ttft 1.0, tpot (15 - 12) / (4 - 1) = 1.0
        _rec(1, 11.0, [(12.0, 1), (13.0, 3), (15.0, 4)], done=15.0),
        # first token inside, finished after the close: 2 of 5 tokens count
        _rec(2, 17.0, [(19.5, 2), (21.0, 5)], done=21.0),
        # finished inside: ttft 0.5, tpot (18 - 10.5) / 3 = 2.5
        _rec(3, 10.0, [(10.5, 1), (16.0, 2), (18.0, 4)], done=18.0),
        # sent in the window, no token by the close
        _rec(4, 19.9, [(20.5, 1)]),
    ]
    e2e, finished, n_tok, n_first = harness.end_to_end(recs, 10.0, 20.0,
                                                       10.0)
    assert n_tok == 4 + 2 + 4
    assert e2e["tokens_per_s"] == pytest.approx(1.0)
    assert n_first == 3
    ttft = [1000.0, 2500.0, 500.0]
    assert e2e["ttft_p90_ms"] == pytest.approx(np.percentile(ttft, 90))
    assert sorted(r.spec["rid"] for r in finished) == [1, 3]
    assert e2e["tpot_p90_ms"] == pytest.approx(
        np.percentile([1000.0, 2500.0], 90))


def test_lane_steps_and_cohort_depth_from_hand_built_waves():
    # one lane wave of four rows in two cohorts of two, three tokens each
    ex = [[2, 0, 1], [2, 1, 0], [2, 0, 0], [2, 2, 0]]
    recs = [_rec(i, 0.0, [(1.0, 1), (2.0, 3)], done=2.0, slot=i,
                 exits=ex[i]) for i in range(4)]
    for r in recs:
        r.result = {"tokens": [5, 6, 7], "exit_depths": r.exits,
                    "confs": [0.1, 0.1, 0.1]}
    steps = harness._lane_steps(recs, 0.0, 3.0)
    assert steps == [([0, 1, 0, 2], [11] * 4), ([1, 0, 0, 0], [12] * 4)]
    c = chip_tiny.cell("mixed", limits=dict(chip_tiny.LIMITS,
                                            sample_requests=4))
    rows = harness.check_rows(c, recs, np.random.default_rng(0),
                              cohorts=2, lane_batch=4)
    by_first_exit = {tuple(e): d for e, d in zip(rows["exits"].tolist(),
                                                  rows["depth"].tolist())}
    # cohort {0, 1}: step 1 max(0, 1) = 1, step 2 max(1, 0) = 1
    assert by_first_exit[(2, 0, 1)][1:] == [1, 1]
    # cohort {2, 3}: step 1 max(0, 2) = 2, step 2 max(0, 0) = 0
    assert by_first_exit[(2, 2, 0)][1:] == [2, 0]
