"""The program's configuration of a dense GQA decoder (Qwen2 / Llama
layout) from a configuration file's published keys: the default builder
where a configuration file names no ``program``."""
from __future__ import annotations


def model_config(config: dict, thresholds, n_cohorts: int):
    """The ``ModelConfig`` that serves ``config`` with the intermediate
    ``thresholds`` and ``n_cohorts`` cohorts per lane."""
    from repro.configs.base import (CascadeConfig, ModelConfig, ObsConfig,
                                    PagedCacheConfig)
    c, srv = config, config["serving"]
    cas = c["cascade"]
    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qkv_bias=bool(c["attention_bias"]), rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["torch_dtype"], use_kernels=bool(srv["use_kernels"]),
        source=c["source"],
        cascade=CascadeConfig(
            n_components=cas["n_components"],
            exit_boundaries=tuple(cas["exit_boundaries"]),
            thresholds=tuple(float(t) for t in thresholds) + (0.0,),
            confidence=cas["confidence"], exit_mode=cas["exit_mode"],
            n_cohorts=int(n_cohorts)),
        paged_cache=PagedCacheConfig(layout=srv["cache_layout"]),
        obs=ObsConfig(enabled=True, max_events=int(srv["max_events"])))
