"""Work arithmetic: the operations and bytes that prefill and decode need,
from the configuration's shapes and the exit indices the engine returned.

"Required" work does not depend on how the program implements a step:

* a decode lane step reads, once, the weights of every segment that at
  least one live row reached, the shared unembedding and the norm gain of
  every exit head evaluated, and the K/V projection weights (with biases
  and the attention norm gain) of the layers no row reached, whose cache
  entries are backfilled; per row it reads the K/V of the layers up to its
  own exit at its own context length, writes one K/V slot in every layer
  and reads its embedding row;
* a row's decode operations are its layers up to its exit, every exit
  head up to and including the one it answered at, and the K/V
  projections of the layers past its exit (backfill);
* a prefill computes every layer at every position (causal attention
  counts each query's visible keys only) and every exit head at the last
  position, reads every weight once and writes the prompt's K/V.

A multiply-add counts two operations.  Weights and cache are bf16 (2
bytes), the configuration's dtype.
"""
from __future__ import annotations

BYTES = 2


def sizes(config: dict) -> dict:
    """The shapes this module, the weights and the reference use, from a
    configuration file's keys."""
    cas = config["cascade"]
    return {
        "d": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "bias": bool(config["attention_bias"]),
        "tied": bool(config["tie_word_embeddings"]),
        "rope_theta": config["rope_theta"],
        "eps": config["rms_norm_eps"],
        "n_components": cas["n_components"],
        "boundaries": list(cas["exit_boundaries"]),
    }


def segment_layers(s: dict) -> list:
    b = [0] + list(s["boundaries"]) + [s["n_layers"]]
    return [b[i + 1] - b[i] for i in range(len(b) - 1)]


def kv_width(s: dict) -> int:
    return s["n_kv"] * s["head_dim"]


def layer_params(s: dict) -> int:
    d, hd = s["d"], s["head_dim"]
    H, kv = s["n_heads"], kv_width(s)
    attn = d * H * hd + 2 * d * kv + H * hd * d
    bias = (H * hd + 2 * kv) if s["bias"] else 0
    return attn + bias + 3 * d * s["d_ff"] + 2 * d


def param_count(s: dict) -> int:
    """Every parameter: layers, embedding, unembedding (none of its own
    where the two are tied), exit and final norm gains."""
    tables = 1 if s["tied"] else 2
    return (s["n_layers"] * layer_params(s) + tables * s["vocab"] * s["d"]
            + s["n_components"] * s["d"])


def layer_macs(s: dict, kv_len: int) -> int:
    """Decode multiply-adds of one layer for one token over ``kv_len``
    keys (projections, scores and values, MLP)."""
    d, hd, H = s["d"], s["head_dim"], s["n_heads"]
    proj = d * H * hd + 2 * d * kv_width(s) + H * hd * d
    return proj + H * hd * kv_len * 2 + 3 * d * s["d_ff"]


def head_macs(s: dict) -> int:
    return s["d"] * s["vocab"]


def backfill_macs(s: dict) -> int:
    return 2 * s["d"] * kv_width(s)


def token_macs(s: dict, exit_: int, kv_len: int) -> int:
    """Multiply-adds of the layers up to component ``exit_`` plus that
    component's head: the per-token cost the cascade's MAC accounting
    counts."""
    n = sum(segment_layers(s)[:exit_ + 1])
    return n * layer_macs(s, kv_len) + head_macs(s)


def decode_row_flops(s: dict, exit_: int, kv_len: int) -> int:
    segs = segment_layers(s)
    reached = sum(segs[:exit_ + 1])
    skipped = s["n_layers"] - reached
    macs = (reached * layer_macs(s, kv_len) + (exit_ + 1) * head_macs(s)
            + skipped * backfill_macs(s))
    return 2 * macs


def decode_step_flops(s: dict, exits, kv_lens) -> int:
    return sum(decode_row_flops(s, e, c) for e, c in zip(exits, kv_lens))


def decode_step_bytes(s: dict, exits, kv_lens) -> int:
    d, kv = s["d"], kv_width(s)
    segs = segment_layers(s)
    top = max(exits)
    reached = sum(segs[:top + 1])
    b = reached * layer_params(s)
    b += d * s["vocab"] + (top + 1) * d
    backfill = 2 * d * kv + (2 * kv if s["bias"] else 0) + d
    b += (s["n_layers"] - reached) * backfill
    for e, c in zip(exits, kv_lens):
        b += sum(segs[:e + 1]) * c * 2 * kv
        b += s["n_layers"] * 2 * kv + d
    return BYTES * b


def prefill_flops(s: dict, rows: int, seq: int) -> int:
    d, hd, H = s["d"], s["head_dim"], s["n_heads"]
    proj = d * H * hd + 2 * d * kv_width(s) + H * hd * d + 3 * d * s["d_ff"]
    pairs = seq * (seq + 1) // 2
    per_row = (s["n_layers"] * (seq * proj + 2 * H * hd * pairs)
               + s["n_components"] * head_macs(s))
    return 2 * rows * per_row


def prefill_bytes(s: dict, rows: int, seq: int) -> int:
    # every layer, the unembedding and the norm gains once; of an untied
    # embedding only the prompt's rows
    weights = (s["n_layers"] * layer_params(s) + s["vocab"] * s["d"]
               + s["n_components"] * s["d"])
    kv_write = rows * seq * s["n_layers"] * 2 * kv_width(s)
    return BYTES * (weights + kv_write + rows * seq * s["d"])


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline bound: the larger of operations over peak rate and
    bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
