"""The comparison that decides ``correct``: what the timed path served,
held to the float32 reference at the same prompts and tokens.

Three numbers, each the worst case over every sampled token:

* ``token_gap`` — how far the served token's reference logit lies below
  the reference's best logit, at the component the token answered at;
* ``conf_err`` — |log of the confidence the engine reported − log of the
  reference's confidence| at that component;
* ``exit_margin`` — how far, in log confidence, the reference puts an
  exit decision on the wrong side of its threshold: a component the token
  passed whose confidence reached the threshold, or the answering
  intermediate component whose confidence fell short (0 when every
  decision agrees; infinite where a threshold of 0 or above 1 makes the
  decision certain and the engine broke it).

The control (``quant="fp8"``: the reference with float8 weights in the
program's place) is read at the same positions: the reference's gap of
the token the control puts first, the control's confidence, and the
decisions the control's confidences make.
"""
from __future__ import annotations

import numpy as np

NAMES = ("token_gap", "conf_err", "exit_margin")


def _log_th(thresholds):
    with np.errstate(divide="ignore"):
        return np.log(np.asarray(thresholds, np.float64))


def exit_violation(logconf, exits, thresholds) -> float:
    """``logconf`` (n, R, G) reference log confidences; ``exits`` (R, G)
    the component each token answered at; ``thresholds`` the n - 1
    intermediate thresholds."""
    n = logconf.shape[0]
    lt = _log_th(thresholds)
    worst = 0.0
    for m in range(n - 1):
        passed = exits > m
        answered = exits == m
        with np.errstate(invalid="ignore"):
            v_pass = np.where(passed, logconf[m] - lt[m], -np.inf)
            v_ans = np.where(answered, lt[m] - logconf[m], -np.inf)
        worst = max(worst, float(np.max(v_pass)), float(np.max(v_ans)))
    return worst


def first_exit(logconf, thresholds) -> np.ndarray:
    """The component each position answers at under ``thresholds``."""
    n = logconf.shape[0]
    lt = _log_th(thresholds)
    e = np.full(logconf.shape[1:], n - 1, np.int64)
    for m in reversed(range(n - 1)):
        e = np.where(logconf[m] >= lt[m], m, e)
    return e


def _at(x, exits):
    """x (n, R, G) -> x[exits[r, g], r, g]."""
    return np.take_along_axis(x, exits[None], axis=0)[0]


def numbers(reference, sizes, seed, rows: dict, thresholds,
            control: bool = False):
    """``reference``: the configuration's reference module
    (``references/<name>.py``).  ``rows``: prompts (R, P), tokens (R, G),
    exits (R, G), confs (R, G), depth (R, G).  Returns ({name: value},
    {name: control value} or None)."""
    exits = np.asarray(rows["exits"], np.int64)
    tokens = np.asarray(rows["tokens"], np.int64)
    ctrl = None
    queries = [tokens]
    if control:
        ctrl = reference.head_stats(sizes, seed, rows["prompts"],
                                    rows["tokens"], rows["depth"],
                                    tokens[..., None], quant="fp8")
        queries.append(_at(ctrl["argmax"], exits))
    ref = reference.head_stats(sizes, seed, rows["prompts"], rows["tokens"],
                               rows["depth"], np.stack(queries, -1))
    zmax = ref["zmax"].astype(np.float64)
    logconf = zmax - ref["lse"]
    zq = ref["zq"].astype(np.float64)
    served = np.take_along_axis(zq[..., 0], exits[None], axis=0)[0]
    got = {
        "token_gap": float(np.max(_at(zmax, exits) - served)),
        "conf_err": float(np.max(np.abs(
            np.log(np.asarray(rows["confs"], np.float64))
            - _at(logconf, exits)))),
        "exit_margin": exit_violation(logconf, exits, thresholds),
    }
    if not control:
        return got, None
    c_log = ctrl["zmax"].astype(np.float64) - ctrl["lse"]
    c_tok = np.take_along_axis(zq[..., 1], exits[None], axis=0)[0]
    bad = {
        "token_gap": float(np.max(_at(zmax, exits) - c_tok)),
        "conf_err": float(np.max(np.abs(_at(c_log, exits)
                                        - _at(logconf, exits)))),
        "exit_margin": exit_violation(
            logconf, first_exit(c_log, thresholds), thresholds),
    }
    return got, bad


def judge(got: dict, limits: dict) -> bool:
    return all(got[k] <= limits[k] for k in NAMES)
