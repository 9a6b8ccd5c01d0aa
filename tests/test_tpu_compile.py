"""Mosaic / XLA compiles for a described TPU v5e (no chip attached).

The Pallas kernels run interpreted in every other test, which cannot see
what the TPU compiler refuses: blocks that break the (8, 128) tiling rule,
more VMEM than a kernel may use, a program that does not fit the chip's
16 GB.  These tests compile each kernel of the main serving path at the
published widths of qwen2.5-3b (d 2048, 16/2 heads of 128, ff 11008,
vocab 151,936, bf16) for one chip of a v5e:2x2 topology, plus the whole
full-width device decode loop with ``use_kernels=True``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so describing it while
pytest-xdist workers import this file would make the workers collect
different tests.  Compiles for a described chip cannot be read back from
the persistent compilation cache, so the cache is off around them.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.autotune import tile
from repro.kernels.cohort_cache import cohort_scatter
from repro.kernels.confidence import confidence
from repro.kernels.decode_attention import decode_attention
from repro.kernels.exit_update import exit_update
from repro.kernels.flash_attention import flash_attention
from repro.kernels.megakernel import exit_head_update
from repro.kernels.paged_gather import paged_gather
from repro.kernels.rmsnorm import rmsnorm

CHIP_HBM_BYTES = 16 * 1024 ** 3          # one TPU v5e chip

# qwen2.5-3b published widths
D, H, KV, HD, V = 2048, 16, 2, 128, 151_936
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "can't describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _carry(B):
    # answered pred exit conf streak ema active
    return [((B,), jnp.bool_), ((B,), I32), ((B,), I32), ((B,), F32),
            ((B,), I32), ((B,), F32), ((B,), jnp.bool_)]


def _decode_attention():
    B, W = 8, 4096
    fn = lambda q, k, v, t, kpos, live: decode_attention(      # noqa: E731
        q, k, v, t, kpos, live, tk=tile("decode_attention", "tk"),
        interpret=False)
    return fn, [((B, KV, H // KV, HD), BF16), ((B, KV, W, HD), BF16),
                ((B, KV, W, HD), BF16), ((), I32), ((W,), I32),
                ((B,), jnp.bool_)]


def _exit_update():
    B = 16
    fn = lambda x, *c: exit_update(                            # noqa: E731
        x, *c, threshold=0.5, m=0, n_components=3, patience_k=2,
        ema_decay=0.8, bt=tile("exit_update", "bt"),
        vt=tile("exit_update", "vt"), interpret=False)
    return fn, [((B, V), BF16)] + _carry(B)


def _confidence():
    B = 16
    fn = lambda x: confidence(x, bt=tile("confidence", "bt"),  # noqa: E731
                              vt=tile("confidence", "vt"), interpret=False)
    return fn, [((B, V), BF16)]


def _megakernel():
    B = 16
    fn = lambda h, w, head, live, *c: exit_head_update(        # noqa: E731
        h, w, head, *c, threshold=0.5, m=1, n_components=3, patience_k=0,
        live=live, bt=tile("megakernel", "bt"), vt=tile("megakernel", "vt"),
        interpret=False)
    return fn, ([((B, D), BF16), ((D,), BF16), ((D, V), BF16),
                 ((B,), jnp.bool_)] + _carry(B))


def _cohort_scatter():
    # one stacked K leaf of a 12-layer segment: (L, B, W, kv, hd), B=8 in
    # two cohorts of 4 rows (not a multiple of 8)
    L, B, C, W = 12, 8, 2, 1024
    fn = lambda dst, src: cohort_scatter(dst, src, 1, C,       # noqa: E731
                                         interpret=False)
    return fn, [((L, B, W, KV, HD), BF16), ((L, B // C, W, KV, HD), BF16)]


def _rmsnorm():
    fn = lambda x, w: rmsnorm(x, w, rt=tile("rmsnorm", "rt"),  # noqa: E731
                              interpret=False)
    return fn, [((8 * 64, D), BF16), ((D,), BF16)]


def _flash_attention():
    S = 2048
    fn = lambda q, k, v: flash_attention(                      # noqa: E731
        q, k, v, tq=tile("flash_attention", "tq"),
        tk=tile("flash_attention", "tk"), interpret=False)
    return fn, [((1, H, S, HD), BF16), ((1, KV, S, HD), BF16),
                ((1, KV, S, HD), BF16)]


def _paged_gather():
    B, bs, W = 8, 16, 1024
    nblk = W // bs
    fn = lambda store, table: paged_gather(store, table,       # noqa: E731
                                           interpret=False)
    return fn, [((B * nblk + 1, bs, KV, HD), BF16), ((B, nblk), I32)]


KERNELS = {
    "decode_attention": _decode_attention,
    "exit_update": _exit_update,
    "confidence": _confidence,
    "megakernel": _megakernel,
    "cohort_scatter": _cohort_scatter,
    "rmsnorm": _rmsnorm,
    "flash_attention": _flash_attention,
    "paged_gather": _paged_gather,
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, shapes = KERNELS[kernel]()
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{kernel}: no Mosaic kernel in the compiled program"


def test_full_width_decode_loop_with_kernels_fits_one_chip(one_chip):
    """The whole qwen2.5-3b device decode loop (lane batch 8, 1024-slot
    ring, cond_batch, every kernel on) compiles for one v5e chip, and its
    arguments plus temporaries fit the chip's HBM."""
    from repro.launch.steps import make_decode_loop_step, make_decode_state
    from repro.models.model import build_model

    B, W, chunk = 8, 1024, 8
    cfg = get_config("qwen2.5-3b").replace(
        use_kernels=True, kernel_interpret=False).with_cascade(
        thresholds=(0.5, 0.5, 0.0), exit_mode="cond_batch",
        n_cohorts=2).with_kernel_tune(megakernel=True, cohort_scatter=True)
    model = build_model(cfg)
    step = make_decode_loop_step(model, cfg, chunk, W)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(B, W)))
    state = on_chip(jax.eval_shape(lambda: make_decode_state(cfg, B)))
    token = jax.ShapeDtypeStruct((B, 1), I32, sharding=one_chip)
    remaining = jax.ShapeDtypeStruct((B,), I32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        params, token, cache, state, remaining, None).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert used < CHIP_HBM_BYTES, mem
