"""Per-arch / per-backend Pallas kernel tile autotuner.

The kernels ship with hand-picked default tiles (``tk=512`` KV tiles for
decode attention, ``(8, 1024)`` logits tiles for the exit-update family,
...).  Whether those win depends on the execution backend: the Pallas
*interpreter* (CPU CI) pays per-grid-cell Python dispatch, so it wants
few large tiles, while compiled Mosaic on a TPU wants tiles sized to VMEM
and the VPU/MXU shapes.  This module measures instead of guessing:

* :func:`sweep` times every candidate tile shape for each kernel on
  representative shapes — the default tiles are always in the candidate
  set, so the winner is never slower than the default *on the measured
  shapes by construction* (``tuned_us = min over candidates``).
* Winners install into a process-wide **tile registry** that every
  ``kernels/ops.py`` wrapper consults at call time.  Tile shapes are
  static kernel parameters (they are BlockSpec shapes), so an install
  that changes a tile costs exactly one recompile of that kernel's inner
  jit; re-installing identical tiles is a jit cache hit.  Installation
  happens *before* a serving loop traces (``DeviceDecodeLoop`` calls
  :func:`ensure_tuned` in its constructor), so the loop's
  ``_cache_size() == 1`` zero-retrace contract is preserved.
* :func:`ensure_tuned` persists the sweep in a config-hash-keyed JSON
  artifact (the ``repro.autotune.artifacts`` idiom: atomic write, key
  check on load, refuse-don't-guess on mismatch) so a fleet of engines
  sweeps once per (platform, backend, preset) and warm-starts afterwards.

``paged_gather`` has no free tile parameter (its block shape IS the cache
block), so its tunable axis is *implementation selection*: the Pallas
scalar-prefetch gather vs the plain XLA ``store[table]`` take — whichever
measures faster on this backend.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import resolve_interpret
from repro.utils import get_logger

log = get_logger("kernels.autotune")

TILE_ARTIFACT_VERSION = 1

# hand-picked seeds — every kernel's no-registry fallback, and always a
# member of its candidate set (the >= 1.0 tuned-speedup invariant)
DEFAULT_TILES: Dict[str, Dict[str, Any]] = {
    "decode_attention": {"tk": 512},
    "flash_attention": {"tq": 128, "tk": 128},
    "rmsnorm": {"rt": 8},
    "confidence": {"bt": 8, "vt": 2048},
    "exit_update": {"bt": 8, "vt": 1024},
    # matches exit_update: same (bt, vt) ⇒ same streaming accumulation
    # order ⇒ bit-identical confidences between the fused and mega paths.
    # vt = 1024 because a (2048, 2048) bf16 head tile, double-buffered,
    # overflows the megakernel's VMEM at d = 2048 on a TPU v5e
    "megakernel": {"bt": 8, "vt": 1024},
    "paged_gather": {"impl": "pallas"},
}

CANDIDATE_TILES: Dict[str, List[Dict[str, Any]]] = {
    "decode_attention": [{"tk": t} for t in (128, 256, 512, 1024)],
    "flash_attention": [{"tq": tq, "tk": tk}
                        for tq in (64, 128) for tk in (64, 128, 256)],
    "rmsnorm": [{"rt": r} for r in (4, 8, 16, 32, 64)],
    "confidence": [{"bt": b, "vt": v}
                   for b in (8, 16, 32) for v in (512, 1024, 2048)],
    "exit_update": [{"bt": b, "vt": v}
                    for b in (8, 16, 32) for v in (512, 1024, 2048)],
    "megakernel": [{"bt": b, "vt": v}
                   for b in (8, 16) for v in (512, 1024, 2048)],
    "paged_gather": [{"impl": "pallas"}, {"impl": "take"}],
}

# sweep presets: (name, shape dict) per kernel.  "tiny" = CI-sized (the
# interpreter makes big sweeps expensive); "serving" = the serving-bench
# shapes (lane_batch 4 x cohorts, cache_len 256, reduced vocab).
SWEEP_SHAPES: Dict[str, Dict[str, List[Dict[str, int]]]] = {
    "tiny": {
        "decode_attention": [{"B": 4, "KV": 2, "qpk": 2, "hd": 64,
                              "W": 128}],
        "flash_attention": [{"B": 2, "H": 4, "KV": 2, "hd": 64, "S": 128}],
        "rmsnorm": [{"R": 32, "d": 256}],
        "confidence": [{"B": 8, "V": 2048}],
        "exit_update": [{"B": 8, "V": 2048}],
        "megakernel": [{"B": 8, "d": 256, "V": 2048}],
        "paged_gather": [{"NB": 32, "bs": 16, "kv": 2, "hd": 64, "B": 4,
                          "nblk": 8}],
    },
    "serving": {
        "decode_attention": [{"B": 8, "KV": 2, "qpk": 2, "hd": 64,
                              "W": 256}],
        "flash_attention": [{"B": 2, "H": 4, "KV": 2, "hd": 64, "S": 256}],
        "rmsnorm": [{"R": 64, "d": 512}, {"R": 256, "d": 4096}],
        "confidence": [{"B": 8, "V": 8192}],
        "exit_update": [{"B": 8, "V": 8192}],
        "megakernel": [{"B": 8, "d": 512, "V": 8192}],
        "paged_gather": [{"NB": 64, "bs": 16, "kv": 2, "hd": 64, "B": 8,
                          "nblk": 16}],
    },
}

# ---------------------------------------------------------------------------
# the tile registry ops.py consults
# ---------------------------------------------------------------------------

_TUNED: Dict[str, Dict[str, Any]] = {}


def tile(kernel: str, param: str):
    """The resolved value of one tile parameter: tuned if installed,
    else the hand-picked default.  Read at wrapper-call (= trace) time,
    NOT baked into any one trace — swapping a tile invalidates exactly
    the affected kernel's inner-jit cache entry."""
    tuned = _TUNED.get(kernel)
    if tuned is not None and param in tuned:
        return tuned[param]
    return DEFAULT_TILES[kernel][param]


def install_tiles(tiles: Dict[str, Dict[str, Any]]) -> None:
    """Install sweep winners into the registry (merge per kernel)."""
    for kernel, params in tiles.items():
        if kernel not in DEFAULT_TILES:
            raise ValueError(f"unknown kernel {kernel!r}")
        _TUNED.setdefault(kernel, {}).update(params)


def reset_tiles() -> None:
    """Drop every installed tile (tests; defaults apply again)."""
    _TUNED.clear()


def current_tiles() -> Dict[str, Dict[str, Any]]:
    """The effective tile table: defaults overlaid with installs."""
    out = {k: dict(v) for k, v in DEFAULT_TILES.items()}
    for k, v in _TUNED.items():
        out[k].update(v)
    return out


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------

def _time_us(fn, reps: int = 3) -> float:
    """Median wall time of ``fn()`` in µs (after one warm-up/compile call).

    Median over reps: a single scheduler hiccup must not crown the wrong
    tile (the winner feeds a >= 1.0 speedup gate)."""
    out = fn()
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
            else x, out)
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def _shape_tag(shape: Dict[str, int]) -> str:
    return ";".join(f"{k}={v}" for k, v in sorted(shape.items()))


def _make_call(kernel: str, shape: Dict[str, int], params: Dict[str, Any],
               interpret: bool):
    """A zero-arg timed callable for (kernel, shape, candidate tiles)."""
    rng = np.random.default_rng(0)

    def arr(*s, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(s), dtype)

    if kernel == "decode_attention":
        from repro.kernels.decode_attention import decode_attention
        q = arr(shape["B"], shape["KV"], shape["qpk"], shape["hd"])
        k = arr(shape["B"], shape["KV"], shape["W"], shape["hd"])
        v = arr(shape["B"], shape["KV"], shape["W"], shape["hd"])
        kpos = jnp.arange(shape["W"], dtype=jnp.int32)
        t = jnp.asarray(shape["W"] - 1, jnp.int32)
        return lambda: decode_attention(q, k, v, t, kpos, None,
                                        tk=params["tk"], interpret=interpret)
    if kernel == "flash_attention":
        from repro.kernels.flash_attention import flash_attention
        q = arr(shape["B"], shape["H"], shape["S"], shape["hd"])
        k = arr(shape["B"], shape["KV"], shape["S"], shape["hd"])
        v = arr(shape["B"], shape["KV"], shape["S"], shape["hd"])
        return lambda: flash_attention(q, k, v, tq=params["tq"],
                                       tk=params["tk"], interpret=interpret)
    if kernel == "rmsnorm":
        from repro.kernels.rmsnorm import rmsnorm
        x = arr(shape["R"], shape["d"])
        w = jnp.ones((shape["d"],), jnp.float32)
        return lambda: rmsnorm(x, w, rt=params["rt"], interpret=interpret)
    if kernel == "confidence":
        from repro.kernels.confidence import confidence
        x = arr(shape["B"], shape["V"])
        return lambda: confidence(x, bt=params["bt"], vt=params["vt"],
                                  interpret=interpret)
    if kernel == "exit_update":
        from repro.kernels.exit_update import exit_update
        B = shape["B"]
        x = arr(B, shape["V"])
        zi = jnp.zeros((B,), jnp.int32)
        zf = jnp.zeros((B,), jnp.float32)
        ones = jnp.ones((B,), jnp.int32)
        return lambda: exit_update(
            x, zi, zi, zi, zf, zi, zf, ones, threshold=0.5, m=0,
            n_components=2, bt=params["bt"], vt=params["vt"],
            interpret=interpret)
    if kernel == "megakernel":
        from repro.kernels.megakernel import exit_head_update
        B = shape["B"]
        h = arr(B, shape["d"])
        w = jnp.ones((shape["d"],), jnp.float32)
        head = arr(shape["d"], shape["V"])
        zi = jnp.zeros((B,), jnp.int32)
        zf = jnp.zeros((B,), jnp.float32)
        ones = jnp.ones((B,), jnp.int32)
        return lambda: exit_head_update(
            h, w, head, zi, zi, zi, zf, zi, zf, ones, threshold=0.5, m=0,
            n_components=2, bt=params["bt"], vt=params["vt"],
            interpret=interpret)
    if kernel == "paged_gather":
        table = jnp.asarray(
            rng.integers(0, shape["NB"], (shape["B"], shape["nblk"])),
            jnp.int32)
        store = arr(shape["NB"], shape["bs"], shape["kv"], shape["hd"])
        if params["impl"] == "take":
            fn = jax.jit(lambda s, t: jnp.take(s, t, axis=0).reshape(
                (t.shape[0], t.shape[1] * s.shape[1]) + s.shape[2:]))
            return lambda: fn(store, table)
        from repro.kernels.paged_gather import paged_gather
        return lambda: paged_gather(store, table, interpret=interpret)
    raise ValueError(f"unknown kernel {kernel!r}")


def sweep(kernels: Optional[List[str]] = None, shapes: str = "tiny",
          reps: int = 3, interpret: Optional[bool] = None,
          ) -> Tuple[Dict[str, Dict[str, Any]], List[Dict[str, Any]]]:
    """Time every candidate tile for every kernel; return
    ``(winners, rows)``.

    ``winners[kernel]`` is the candidate minimizing total time across the
    preset's shapes.  ``rows`` carries one bench record per (kernel,
    shape): default vs tuned µs from the SAME sweep (so
    ``tuned_speedup >= 1.0`` holds by construction) plus the backend
    provenance (interpret/compiled, platform) the gate requires.
    """
    interpret = resolve_interpret(interpret)
    backend = "interpret" if interpret else "compiled"
    platform = jax.default_backend()
    kernels = list(kernels or DEFAULT_TILES)
    preset = SWEEP_SHAPES[shapes]
    winners: Dict[str, Dict[str, Any]] = {}
    rows: List[Dict[str, Any]] = []
    for kernel in kernels:
        cands = CANDIDATE_TILES[kernel]
        default = DEFAULT_TILES[kernel]
        if default not in cands:
            cands = cands + [default]
        shape_list = preset[kernel]
        # times[c][s] = µs of candidate c on shape s
        times = [[_time_us(_make_call(kernel, s, c, interpret), reps)
                  for s in shape_list] for c in cands]
        totals = [sum(ts) for ts in times]
        best = int(np.argmin(totals))
        di = cands.index(default)
        winners[kernel] = dict(cands[best])
        for si, s in enumerate(shape_list):
            rows.append({
                "kernel": kernel,
                "shape": _shape_tag(s),
                "tiles": dict(cands[best]),
                "default_tiles": dict(default),
                "default_us": round(times[di][si], 2),
                "tuned_us": round(times[best][si], 2),
                # the PER-SHAPE winner can differ from the per-kernel
                # winner; the gate checks the installed (per-kernel) one,
                # so report exactly what installs
                "tuned_speedup": round(
                    times[di][si] / max(times[best][si], 1e-9), 4),
                "backend": backend,
                "platform": platform,
            })
        log.info("kernel %s: tuned %s (default %s)", kernel, winners[kernel],
                 default)
    return winners, rows


# ---------------------------------------------------------------------------
# config-hash-keyed tile artifact (the autotune/artifacts.py idiom)
# ---------------------------------------------------------------------------

def tune_key(shapes: str = "tiny", interpret: Optional[bool] = None) -> str:
    """Stable identity of a tile sweep: tiles transfer only between
    processes with the same execution backend, platform, candidate grids
    and sweep preset."""
    interpret = resolve_interpret(interpret)
    ident = {
        "version": TILE_ARTIFACT_VERSION,
        "platform": jax.default_backend(),
        "interpret": bool(interpret),
        "shapes": shapes,
        "candidates": CANDIDATE_TILES,
        "defaults": DEFAULT_TILES,
    }
    blob = json.dumps(ident, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()


@dataclasses.dataclass
class TileArtifact:
    """One persisted tile sweep: the winners plus the timing evidence."""

    config_key: str
    platform: str
    interpret: bool
    shapes: str
    tiles: Dict[str, Dict[str, Any]]
    rows: List[Dict[str, Any]]

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["version"] = TILE_ARTIFACT_VERSION
        return d

    @classmethod
    def from_json(cls, d: dict) -> "TileArtifact":
        d = dict(d)
        ver = d.pop("version", TILE_ARTIFACT_VERSION)
        if ver != TILE_ARTIFACT_VERSION:
            raise ValueError(
                f"tile artifact version {ver} != {TILE_ARTIFACT_VERSION}")
        return cls(**d)


def tile_artifact_path(artifact_dir: str, key: str) -> str:
    return os.path.join(artifact_dir, f"kernel_tiles_{key[:16]}.json")


def save_tile_artifact(artifact_dir: str, artifact: TileArtifact) -> str:
    """Atomically persist; returns the written path."""
    os.makedirs(artifact_dir, exist_ok=True)
    path = tile_artifact_path(artifact_dir, artifact.config_key)
    fd, tmp = tempfile.mkstemp(dir=artifact_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(artifact.to_json(), f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def load_tile_artifact(artifact_dir: str, shapes: str = "tiny",
                       interpret: Optional[bool] = None
                       ) -> Optional[TileArtifact]:
    """The artifact matching this process's tune key, or None.

    A key mismatch inside the file (hand-copied artifact, different
    platform/backend/candidate grid) WARNS and returns None — the caller
    falls back to the default tiles and may re-sweep; stale tiles are
    never silently installed."""
    key = tune_key(shapes, interpret)
    path = tile_artifact_path(artifact_dir, key)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        art = TileArtifact.from_json(json.load(f))
    if art.config_key != key:
        log.warning(
            "tile artifact %s was swept under key %s..., not this "
            "backend/platform's %s... — falling back to default tiles",
            path, art.config_key[:16], key[:16])
        return None
    return art


def ensure_tuned(cfg=None, artifact_dir: Optional[str] = None,
                 shapes: Optional[str] = None, reps: int = 3,
                 force: bool = False) -> TileArtifact:
    """Sweep-or-load, then install: the one entry point engine builds use.

    Resolution order: a matching artifact in ``artifact_dir`` (skip the
    sweep) > a fresh :func:`sweep` (persisted when ``artifact_dir`` is
    set).  ``cfg`` supplies ``kernel_tune.artifact_dir`` /
    ``kernel_tune.shapes`` defaults and its ``kernel_interpret``
    override.  Returns the installed artifact.
    """
    interpret = None
    if cfg is not None:
        interpret = cfg.kernel_interpret
        if artifact_dir is None:
            artifact_dir = cfg.kernel_tune.artifact_dir
        if shapes is None:
            shapes = cfg.kernel_tune.shapes
    shapes = shapes or "tiny"
    art = None
    if artifact_dir and not force:
        art = load_tile_artifact(artifact_dir, shapes, interpret)
    if art is None:
        tiles, rows = sweep(shapes=shapes, reps=reps, interpret=interpret)
        art = TileArtifact(
            config_key=tune_key(shapes, interpret),
            platform=jax.default_backend(),
            interpret=resolve_interpret(interpret),
            shapes=shapes, tiles=tiles, rows=rows)
        if artifact_dir:
            save_tile_artifact(artifact_dir, art)
    install_tiles(art.tiles)
    return art
