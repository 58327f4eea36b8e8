"""Kimi-K2-Instruct's decode step as plain layer nests, derived from the
published config keys that ``bench/configs/kimi-k2-decode32k.json`` holds
(moonshotai/Kimi-K2-Instruct config.json) and its deployment, independently
of the program's ``kimi_k2_decode``.

One device of a 48-way expert-parallel deployment holds 8 of the 384
routed experts of each MoE layer and decodes its own sequences, each over
its own latent cache.  Per layer, on the absorbed MLA decode path
(DeepSeek-V2 Sec 2.1): the query's low-rank down and up projections, the
KV down projection to the 512-wide latent plus the 64-wide rope key, the
per-head absorption of W_UK into the query, the scores of each sequence's
heads against its own cache (576 = latent + rope), the context over the
cache's latent part, the per-head absorption of W_UV, and the output
projection.  Layer 0 has the dense FFN; the others the router over all
routed experts, the shared expert and the held routed experts, each with
its own token count.  Embedding, head, softmax, norms, RoPE, SiLU and
top-k carry no MACs here and are left out.

Each layer is ``(name, (K, C, Y, X, R, S), stride, kind, group_rows)``: a
GEMM (M, N, Kg) is ``(M, Kg, N, 1, 1, 1)``, ``grouped`` puts G GEMMs with
their own weights on X, ``ragged`` gives each group its rows, Y being the
largest.
"""
from __future__ import annotations


def _gemm(name, m, n, kg):
    return (name, (m, kg, n, 1, 1, 1), 1, "plain", ())


def _grouped(name, g, m, n, kg):
    return (name, (m, kg, n, g, 1, 1), 1, "grouped", ())


def _ragged(name, m, rows, kg):
    rows = tuple(int(n) for n in rows)
    return (name, (m, kg, max(rows), len(rows), 1, 1), 1, "ragged", rows)


def layers(config: dict):
    """The decode step's layers under ``config`` (the cell's file)."""
    c = config
    dep = c["deployment"]
    batch, cache = dep["sequences"], dep["cache_tokens"]
    d, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    lat = c["kv_lora_rank"]
    routed = dep["expert_parallel_devices"] * c["n_routed_experts"]
    moe = c["moe_intermediate_size"]
    shared = moe * c["n_shared_experts"]
    loads = c["expert_loads"]
    if len(loads) != c["num_hidden_layers"] - c["first_k_dense_replace"]:
        raise ValueError("one list of expert loads per MoE layer kept")
    out = []
    for i in range(c["num_hidden_layers"]):
        p = f"L{i}."
        out += [_gemm(p + "q_a", c["q_lora_rank"], batch, d),
                _gemm(p + "q_b", heads * (nope + rope), batch,
                      c["q_lora_rank"]),
                _gemm(p + "kv_a", lat + rope, batch, d),
                _grouped(p + "q_absorb", heads, lat, batch, nope),
                _grouped(p + "scores", batch, cache, heads, lat + rope),
                _grouped(p + "context", batch, lat, heads, cache),
                _grouped(p + "v_absorb", heads, c["v_head_dim"], batch, lat),
                _gemm(p + "o", d, batch, heads * c["v_head_dim"])]
        if i < c["first_k_dense_replace"]:
            out += [_gemm(p + "gate_up", 2 * c["intermediate_size"], batch,
                          d),
                    _gemm(p + "down", d, batch, c["intermediate_size"])]
            continue
        held = loads[i - c["first_k_dense_replace"]]
        if len(held) != c["n_routed_experts"]:
            raise ValueError("one load per routed expert held")
        out += [_gemm(p + "router", routed, batch, d),
                _gemm(p + "shared.gate_up", 2 * shared, batch, d),
                _gemm(p + "shared.down", d, batch, shared),
                _ragged(p + "experts.gate_up", 2 * moe, held, d),
                _ragged(p + "experts.down", d, held, moe)]
    return out
