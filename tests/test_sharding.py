"""Sharding-rule unit tests over an AbstractMesh (no devices needed).

These pin the layout contracts that the dry-run proves end-to-end:
divisibility-gated placement, FSDP placement, serve1d/serve2d semantics,
expert-parallel fallbacks, and the batch-1 sequence-parallel cache rule.
"""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.shard_rules import batch_spec, cache_spec, param_spec
from repro.models.model import build_model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _leaf(spec_tree, *path):
    node = spec_tree
    for p in path:
        node = node[p]
    return node


@pytest.fixture(scope="module")
def qwen_params():
    cfg = get_config("qwen2.5-3b")
    model = build_model(cfg)
    return cfg, jax.eval_shape(model.init, jax.random.PRNGKey(0))


def test_default_layout_tp_plus_fsdp(qwen_params):
    cfg, params = qwen_params
    spec = param_spec(params, cfg, MESH)
    # embed (V, d): vocab over model (151936 % 16 == 0), fsdp on d
    assert _leaf(spec, "embed") == P("model", "data")
    # column-parallel wq (L, d, H*hd): model on last, data on first free
    wq = _leaf(spec, "segments")[0][0]["attn"]["wq"]
    assert wq[-1] == "model" and "data" in wq
    # row-parallel wo (L, H*hd, d): model on -2
    wo = _leaf(spec, "segments")[0][0]["attn"]["wo"]
    assert wo[-2] == "model"
    # norms replicated
    assert _leaf(spec, "final_norm")["w"] == P()


def test_serve1d_no_fsdp(qwen_params):
    cfg, params = qwen_params
    spec = param_spec(params, cfg, MESH, mode="serve1d")
    wq = _leaf(spec, "segments")[0][0]["attn"]["wq"]
    assert wq[-1] == "model"
    assert "data" not in tuple(a for a in wq if a)


def test_serve2d_combined_axes(qwen_params):
    cfg, params = qwen_params
    spec = param_spec(params, cfg, MESH, mode="serve2d")
    wq = _leaf(spec, "segments")[0][0]["attn"]["wq"]
    # 16 heads x 128 = 2048 divisible by 256 -> combined axes on output dim
    assert wq[-1] == ("model", "data")


def test_moe_expert_parallel_and_fallback():
    # qwen3: 128 experts % 16 == 0 -> expert parallel (+ff over data in 2d)
    cfg = get_config("qwen3-moe-235b-a22b")
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    spec = param_spec(params, cfg, MESH, mode="serve2d")

    def find_moe(spec_tree):
        for seg in spec_tree["segments"]:
            for stage in seg:
                if "moe" in stage:
                    return stage["moe"]
        raise AssertionError("no moe stage")
    moe = find_moe(spec)
    assert moe["w_up"][-3] == "model" and moe["w_up"][-1] == "data"
    # mixtral: 8 experts not divisible by 16 -> tensor-parallel inside experts
    cfg2 = get_config("mixtral-8x7b")
    params2 = jax.eval_shape(build_model(cfg2).init, jax.random.PRNGKey(0))
    spec2 = param_spec(params2, cfg2, MESH)
    moe2 = find_moe(spec2)
    assert moe2["w_up"][-3] is None and moe2["w_up"][-1] == "model"


def test_cache_batch_vs_sequence_parallel():
    cfg = get_config("yi-9b")
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 1024))
    spec = cache_spec(cache, cfg, MESH, batch=128)
    k = spec["segments"][0][0]["k"]
    assert k[1] == "data"                 # batch over data
    # batch=1 long-context: shard the KV slot dim instead
    cache1 = jax.eval_shape(lambda: model.init_cache(1, 1024))
    spec1 = cache_spec(cache1, cfg, MESH, batch=1)
    k1 = spec1["segments"][0][0]["k"]
    assert k1[1] is None and k1[2] == "data"


def test_batch_spec_divisibility():
    cfg = get_config("yi-9b")
    assert batch_spec(cfg, MESH, 128, 2)[0] == "data"
    assert batch_spec(cfg, MESH, 1, 2) == P()
    assert batch_spec(cfg, MESH_MP, 128, 2)[0] == ("pod", "data")


def test_whisper_vocab_not_sharded():
    # 51865 does not divide 16 -> unembedding replicated on the vocab dim
    cfg = get_config("whisper-tiny")
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    spec = param_spec(params, cfg, MESH, fsdp=False)
    assert spec["lm_head"][-1] is None
    assert spec["embed"][0] is None


def test_every_arch_spec_structurally_valid():
    """Every placed axis must divide its dim (the invariant the dry-run
    relies on); specs must match param tree structure."""
    from repro.configs import list_configs
    for arch in list_configs():
        if arch == "ci-resnet18":
            continue
        cfg = get_config(arch)
        params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        for mode in ("default", "serve1d", "serve2d"):
            spec = param_spec(params, cfg, MESH, mode=mode)
            flat_p = jax.tree_util.tree_leaves_with_path(params)
            flat_s = jax.tree_util.tree_leaves_with_path(
                spec, is_leaf=lambda x: isinstance(x, P))
            assert len(flat_p) == len(flat_s)
            sizes = dict(MESH.shape)
            for (path, leaf), (_, sp) in zip(flat_p, flat_s):
                for dim, ax in zip(np.shape(leaf), tuple(sp)):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    total = int(np.prod([sizes[a] for a in axes]))
                    assert dim % total == 0, (arch, mode, path, dim, ax)
