"""Device-resident decode runtime: multi-token serving without per-token
host round-trips.

The host-runtime engine (`serving/engine.py`) dispatches ONE jitted decode
step per generated token and immediately syncs the result to host
(``np.asarray(tok)``), so at small lane batches the per-call dispatch +
sync overhead swamps exactly the compute that ``cond_batch`` segment
skipping saves.  :class:`DeviceDecodeLoop` closes that gap: it jits a
``lax.while_loop`` over ``(DecodeState, cache, token, output buffers)``
(built by :func:`repro.launch.steps.make_decode_loop_step`) and decodes up
to K tokens entirely on device — tokens, exit indices, confidences and the
per-step live mask land in preallocated ``(K, B)`` device buffers, and the
host syncs once per chunk instead of once per token.

Because each loop iteration is one :class:`~repro.core.exec.StagedExecutor`
step, everything the staged executor does carries over unchanged inside the
loop: cond_batch segment skipping, cohort-split skip predicates
(``cascade.n_cohorts``) in either cohort layout (the cohort-major hot path
or the legacy copy ablation — ``cascade.cohort_layout``), stateful measures
(patience streaks ride in the carried ``DecodeState.policy``), and the
per-segment execution counters.  With ``cfg.use_kernels`` the kernel fast
path also runs *inside* the while_loop carry: the per-slot
``DecodeState.active`` mask reaches the exit-masked decode-attention kernel
every iteration (drained slots stop paying attention FLOPs mid-chunk), and
each component's exit decision + DecodeState update (patience streaks,
confidence EMA) is one fused exit-update kernel over the exit logits.
The loop ends early once every slot has either spent its token budget or
hit the cache limit, mirroring the host engine's per-token finish rule —
which is what keeps host- and device-runtime token streams bit-identical
(pinned by ``tests/test_runtime.py``).  The one sanctioned divergence is
admission timing: requests still QUEUED when a chunk starts join only at
the next chunk boundary (the engine admits between dispatches), so under
over-capacity load a lane's re-prefill point — and with it the affected
sequences — can differ from the host runtime's per-token admission.

Multi-device lanes: pass a ``mesh`` and the whole loop carry is sharded by
the existing rules in :mod:`repro.launch.shard_rules`
(:func:`~repro.launch.shard_rules.decode_loop_in_specs` — weights serve1d,
cache via ``cache_spec``, DecodeState via ``decode_state_spec``, token /
budget vectors batch-sharded).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.shard_rules import decode_loop_in_specs, to_shardings
from repro.launch.steps import make_decode_loop_step
from repro.obs.spans import span


def kernel_provenance(cfg) -> dict:
    """The kernel execution backend this config actually runs — recorded
    in every serving bench row so a speedup number can never be read
    without knowing whether it was measured through the Pallas interpreter
    (CPU CI: advisory) or the compiled Mosaic path (gated strictly)."""
    from repro.kernels.backend import resolve_interpret
    interpret = resolve_interpret(cfg.kernel_interpret)
    return {
        "kernel_backend": "interpret" if interpret else "compiled",
        "kernel_platform": jax.default_backend(),
    }


@dataclasses.dataclass
class DecodeChunk:
    """Host view of one device-loop dispatch, trimmed to the steps that ran.

    ``tokens`` / ``exits`` / ``confs`` / ``live`` are (n_steps, B); row i of
    ``live`` marks the slots that were still generating when step i's token
    was produced (a slot's valid outputs are exactly its True rows).
    ``seconds`` is the host-measured wall-clock of the dispatch including
    the single per-chunk sync: ``launch_s`` (the ``engine.decode.launch``
    span, the jitted call until it returns) plus ``sync_s`` (the
    ``engine.decode.sync`` span, the batched ``device_get`` and the
    trim); ``t_host`` is the ``perf_counter`` stamp at dispatch start (so
    the flight recorder can place the chunk's slice on a wall-clock
    timeline without adding any sync of its own); ``compiled`` marks the
    warm-up call that paid jit compilation (callers should report its time
    as compile cost, not decode cost).
    """

    tokens: np.ndarray
    exits: np.ndarray
    confs: np.ndarray
    live: np.ndarray
    n_steps: int
    remaining: np.ndarray
    seconds: float
    compiled: bool
    t_host: float = 0.0
    launch_s: float = 0.0
    sync_s: float = 0.0


class DeviceDecodeLoop:
    """Jitted K-token ``lax.while_loop`` decode over the staged executor.

    One instance per (config, lane shape): the loop program is compiled
    once and reused by every lane, since all lanes share
    ``(lane_batch, cache_len)``.  ``run_chunk`` is the whole public
    surface — feed it the lane's continuation token, cache, carried
    DecodeState and per-slot remaining-token budget; get back a
    :class:`DecodeChunk` plus the new (device-resident, donated-in)
    cache and state.

    With ``mesh`` set, inputs are constrained to the shard_rules layout so
    lanes run multi-device; the loop carry never leaves the mesh.
    """

    def __init__(self, model, cfg, chunk: int = 8, cache_len: int = 256,
                 mesh=None):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.cfg = cfg
        self.chunk = int(chunk)
        self.cache_len = int(cache_len)
        self.mesh = mesh
        # install tuned tiles BEFORE the loop program traces: tiles are
        # static kernel params, so installing later would force a retrace;
        # installing here keeps _cache_size() == 1 for the lane lifetime
        kt = getattr(cfg, "kernel_tune", None)
        if kt is not None and kt.enabled:
            from repro.kernels.autotune import ensure_tuned
            ensure_tuned(cfg)
        self._fn = make_decode_loop_step(model, cfg, self.chunk,
                                         self.cache_len)
        self._jitted = None
        # where token, state and remaining go before a chunk's clock starts
        self._host_in = None
        self._warm = False

    # ------------------------------------------------------------------
    def _build(self, params, cache, state, batch: int):
        # cache + state are donated: the loop is the only consumer and the
        # caller always adopts the returned buffers (in-place carry keeps
        # the chunk wall-clock honest, exactly like the host engine's step)
        if self.mesh is None:
            return jax.jit(self._fn, donate_argnums=(2, 3))
        specs = decode_loop_in_specs(params, cache, state, self.cfg,
                                     self.mesh, batch)
        shardings = tuple(
            None if s is None else to_shardings(self.mesh, s)
            for s in specs)
        self._host_in = (shardings[1], shardings[3], shardings[4])
        return jax.jit(self._fn, in_shardings=shardings,
                       donate_argnums=(2, 3))

    # ------------------------------------------------------------------
    def run_chunk(self, params, token, cache, state, remaining, extra=None):
        """Decode up to ``chunk`` tokens for one lane on device.

        token: (B, 1) int32 continuation token per slot; remaining: (B,)
        int32 tokens each slot may still generate (0 = finished slot).
        ``state.active`` must already mask finished slots.  Returns
        ``(DecodeChunk, new_cache, new_state)``; the passed cache/state are
        donated and must not be reused.
        """
        with span("engine.decode.prep"):
            token = np.asarray(token, np.int32)
            remaining = np.asarray(remaining, np.int32)
            if self._jitted is None:
                self._jitted = self._build(params, cache, state,
                                           token.shape[0])
            # the small host vectors (token, remaining, the active mask in
            # state) go straight to the loop's input layout, outside the
            # timed spans: a plain device copy would sit on one device of a
            # mesh
            token, state, remaining = jax.device_put(
                (token, state, remaining), self._host_in)
        with span("engine.decode.launch") as launch:
            (toks, exits, confs, live, n_steps, cache, state,
             rem) = self._jitted(params, token, cache, state, remaining,
                                 extra)
        with span("engine.decode.sync") as sync:
            # the ONE host sync per chunk: a single batched device_get of
            # the small (K, B) buffers + counters (cache/state stay on
            # device)
            n, toks, exits, confs, live, rem = jax.device_get(
                (n_steps, toks, exits, confs, live, rem))
            n = int(n)
            toks, exits, confs, live = (toks[:n], exits[:n], confs[:n],
                                        live[:n])
        compiled = not self._warm
        self._warm = True
        return (DecodeChunk(tokens=toks, exits=exits, confs=confs,
                            live=live, n_steps=n, remaining=rem,
                            seconds=launch.seconds + sync.seconds,
                            compiled=compiled, t_host=launch.t0,
                            launch_s=launch.seconds, sync_s=sync.seconds),
                cache, state)
