"""Kimi-Linear's layer equations, for the plain reference.

Found by name: a configuration whose ``"workload"`` is ``kimi-linear``
is checked against this file. It imports nothing of the program and
defines the three functions every such module defines (see
``deepseek-v3.py``).

Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-Instruct
config.json; arXiv:2510.26692): 27 layers, KDA (gated delta-rule linear
attention) at the 1-indexed ``kda_layers`` and NoPE MLA without q-LoRA
at the others, a dense FFN in the first layer, then top-k routed experts
plus a shared one. A decode step moves one period of the layer pattern:
the layers up to and including the first MLA layer (KDA, KDA, KDA, MLA).
Its cache is the MLA latent, read as whole pages of the whole context,
beside one recurrent state per KDA layer, read whole and rewritten in
place.
"""

#: The recorder's row: every weight extent reads at least one.
ROW = 4096


def _period(m: dict) -> list[str]:
    """Attention kinds of one period: up to the first MLA layer."""
    kda = set(m["kda_layers"])
    first_mla = min(layer for layer in range(1, m["n_layers"] + 1)
                    if layer not in kda)
    return ["kda" if layer in kda else "mla"
            for layer in range(1, first_mla + 1)]


def kda_weight_params(m: dict) -> int:
    """One KDA layer: q, k, v and o projections, the low-rank forget and
    output gates, beta and the depthwise short convolution."""
    d, width = m["d_model"], m["kda_heads"] * m["kda_head_dim"]
    rank = m["kda_gate_rank"]
    return (4 * d * width + 2 * (d * rank + rank * width)
            + d * m["kda_heads"] + 3 * width * m["kda_conv"])


def mla_weight_params(m: dict) -> int:
    """One MLA layer without q-LoRA: q straight from the hidden state,
    the kv down and up projections and o."""
    d, h, hd, rope = (m["d_model"], m["n_heads"], m["head_dim"],
                      m["mla_rope_dim"])
    return (d * h * (hd + rope) + d * (m["mla_kv_lora"] + rope)
            + m["mla_kv_lora"] * h * 2 * hd + h * hd * d)


def weight_bytes(config: dict, traffic: dict) -> int:
    """Read bytes of the first ``n_ops`` decode ops' weights on one
    device (batch 1), each extent scaled by ``step_scale`` and floored
    at one row: per layer its attention, then its FFN (the dense FFN
    split over the devices, or one routed expert of this device's
    share, as batch 1 picks, plus the shared expert)."""
    m = config["model"]
    n_ops, scale = int(traffic["n_ops"]), float(traffic["step_scale"])
    bpp = m["bytes_per_param"]
    kda = set(m["kda_layers"])
    expert = 3 * m["d_model"] * m["d_ff"] * bpp
    e_dev = m["n_experts"] // m["moe_ep"]
    routed = max(1, round(e_dev * m["top_k"] / m["n_experts"]))
    extents = []
    for layer in range(1, m["n_layers"] + 1):
        extents.append([(kda_weight_params(m) if layer in kda
                         else mla_weight_params(m)) * bpp])
        if layer > m["n_dense_layers"]:
            extents.append([expert] * routed
                           + [m["n_shared_experts"] * expert])
        else:
            extents.append([3 * m["d_model"] * m["dense_d_ff"]
                            // m["n_devices"] * bpp])
        if len(extents) >= n_ops:
            break
    return sum(max(ROW, int(n * scale))
               for op in extents[:n_ops] for n in op if n > 0)


def state_bytes(config: dict) -> int:
    """One KDA layer's state per sequence: each head's head_dim x
    head_dim state, and the conv's last ``kda_conv - 1`` inputs of q, k
    and v."""
    m, s = config["model"], config["state"]
    width = m["kda_heads"] * m["kda_head_dim"]
    return (m["kda_heads"] * m["kda_head_dim"] ** 2 * s["itemsize"]
            + (m["kda_conv"] - 1) * 3 * width * s["conv_itemsize"])


def _page(kv: dict) -> tuple[int, int, int]:
    """``(pools, bytes per token per pool, tokens per page)``: the
    latent split evenly over the pools, a page of whole rows."""
    per_tok = ((kv["kv_lora_rank"] + kv["qk_rope_head_dim"]) // kv["pools"]
               * kv["itemsize"])
    return (kv["pools"], per_tok,
            kv["rows_per_page"] * kv["row_bytes"] // per_tok)


def request_bytes(config: dict, traffic: dict, req) -> tuple[int, int]:
    """Per period: whole pages of the request's context read from every
    MLA pool and one token appended to each, and every KDA state of the
    period read whole and rewritten."""
    pools, per_tok, page_tokens = _page(config["kv"])
    pages = -(-int(req.context) // page_tokens)
    states = _period(config["model"]).count("kda") * state_bytes(config)
    return (pools * pages * page_tokens * per_tok + states,
            pools * per_tok + states)


def pool_geometry(config: dict) -> dict:
    """The MLA latent split evenly over the recorder's pools, one head
    per pool (the state pool is the program's own, sized from the
    workload)."""
    kv = config["kv"]
    dims = kv["kv_lora_rank"] + kv["qk_rope_head_dim"]
    if dims % kv["pools"]:
        raise ValueError(f"{dims} latent values do not split over "
                         f"{kv['pools']} pools")
    return {"n_kv_heads": 1, "head_dim": dims // kv["pools"],
            "rows_per_page": int(kv["rows_per_page"])}
