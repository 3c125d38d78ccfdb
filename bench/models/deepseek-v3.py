"""DeepSeek-V3's layer equations, for the plain reference.

Found by name: a configuration whose ``"workload"`` is ``deepseek-v3``
is checked against this file. It imports nothing of the program. Every
such module defines the same three functions:

``weight_bytes(config, traffic) -> int``
    read bytes of one decode step's scaled weight slice;
``request_bytes(config, traffic, req) -> (read, write)``
    the KV bytes one decoding request moves in one step; ``req`` has
    ``rid``, ``context`` (prompt plus tokens already decoded) and the
    run's ``seed``;
``pool_geometry(config) -> dict``
    the keyword arguments bound onto the program's KV page-pool factory.

DeepSeek-V3 (arXiv:2412.19437): MLA attention in every layer, a dense
FFN in the first ``n_dense_layers``, then top-k routed experts plus
shared ones; its KV cache is the MLA latent, read as whole pages of the
whole context each step.
"""

#: The recorder's row: every weight extent reads at least one.
ROW = 4096


def weight_bytes(config: dict, traffic: dict) -> int:
    """Read bytes of the first ``n_ops`` decode ops' weights on one
    device (batch 1), each extent scaled by ``step_scale`` and floored
    at one row."""
    m = config["model"]
    n_ops, scale = int(traffic["n_ops"]), float(traffic["step_scale"])
    d, hd, bpp = m["d_model"], m["head_dim"], m["bytes_per_param"]
    attn = (d * m["mla_q_lora"]
            + m["mla_q_lora"] * m["n_heads"] * (hd + m["mla_rope_dim"])
            + d * (m["mla_kv_lora"] + m["mla_rope_dim"])
            + m["mla_kv_lora"] * m["n_heads"] * 2 * hd
            + m["n_heads"] * hd * d) * bpp
    extents = []
    for layer in range(m["n_layers"]):
        extents.append([attn])
        if layer >= m["n_dense_layers"]:
            expert = 3 * d * m["d_ff"] * bpp
            e_dev = m["n_experts"] // m["moe_ep"]
            active = e_dev * (1.0 - (1.0 - m["top_k"] / m["n_experts"]))
            ffn = [expert] * max(1, round(active))
            if m["n_shared_experts"]:
                ffn.append(m["n_shared_experts"] * expert)
            extents.append(ffn)
        else:
            extents.append([3 * d * m["dense_d_ff"] // m["n_devices"] * bpp])
        if len(extents) >= n_ops:
            break
    return sum(max(ROW, int(n * scale))
               for op in extents[:n_ops] for n in op if n > 0)


def _page(kv: dict) -> tuple[int, int, int]:
    """``(pools, bytes per token per pool, tokens per page)``: the
    latent split evenly over the pools, a page of whole rows."""
    per_tok = ((kv["kv_lora_rank"] + kv["qk_rope_head_dim"]) // kv["pools"]
               * kv["itemsize"])
    return (kv["pools"], per_tok,
            kv["rows_per_page"] * kv["row_bytes"] // per_tok)


def request_bytes(config: dict, traffic: dict, req) -> tuple[int, int]:
    """Whole pages of the request's context read from every pool, one
    token appended to every pool."""
    pools, per_tok, page_tokens = _page(config["kv"])
    pages = -(-int(req.context) // page_tokens)
    return pools * pages * page_tokens * per_tok, pools * per_tok


def pool_geometry(config: dict) -> dict:
    """The per-token latent split evenly over the recorder's pools, one
    head per pool."""
    kv = config["kv"]
    dims = kv["kv_lora_rank"] + kv["qk_rope_head_dim"]
    if dims % kv["pools"]:
        raise ValueError(f"{dims} latent values do not split over "
                         f"{kv['pools']} pools")
    return {"n_kv_heads": 1, "head_dim": dims // kv["pools"],
            "rows_per_page": int(kv["rows_per_page"])}
