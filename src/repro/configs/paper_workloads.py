"""The paper's three evaluation LLMs (§VI-A) for the TPOT / LBR / energy
reproduction: DeepSeek-V3 (MLA + MoE), Grok-1 (GQA + MoE), Llama-3-405B
(GQA + dense FFN). Weights in BF16; parallelism per §VI-A: prefill TP=8;
decode attention TP = 1 / 8 / 8 (MLA's compressed KV favors data
parallelism); MoE uses expert parallelism across the 8 accelerators.

The serving replay (:mod:`repro.serve.replay`, :mod:`repro.serve.cluster`)
also runs Kimi-Linear (:data:`KIMI_LINEAR`), a hybrid of KDA linear
attention and MLA layers, through :data:`SERVING_WORKLOADS`.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

#: Attention kinds a layer can have: multi-head latent attention,
#: grouped-query attention, and Kimi Delta Attention (gated delta-rule
#: linear attention with a fixed-size recurrent state per sequence).
ATTN_KINDS = ("mla", "gqa", "kda")


@dataclass(frozen=True)
class PaperWorkload:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int          # GQA kv heads (MLA: latent dim handled below)
    head_dim: int
    d_ff: int                # dense FFN or per-expert intermediate
    vocab: int
    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    n_dense_layers: int = 0  # leading dense-FFN layers (DeepSeek: 3)
    dense_d_ff: int = 0
    # MLA
    mla_kv_lora: int = 0     # compressed KV dim (c_kv); 0 => plain GQA
    mla_q_lora: int = 0
    mla_rope_dim: int = 0
    # parallelism (§VI-A, decode)
    attn_tp: int = 8
    moe_ep: int = 8
    bytes_per_param: int = 2
    # per-layer attention kinds; () = every layer MLA (mla_kv_lora set)
    # or GQA
    layer_kinds: tuple[str, ...] = ()
    # KDA (Kimi Delta Attention)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0        # short-convolution kernel over q, k and v
    kda_gate_rank: int = 0   # low-rank forget- and output-gate projections
    state_itemsize: int = 4  # recurrent state (float32)

    def __post_init__(self) -> None:
        if self.layer_kinds and len(self.layer_kinds) != self.n_layers:
            raise ValueError(f"{self.name}: {len(self.layer_kinds)} layer "
                             f"kinds for {self.n_layers} layers")
        bad = set(self.layer_kinds) - set(ATTN_KINDS)
        if bad:
            raise ValueError(f"{self.name}: unknown attention kinds {bad}")
        if "kda" in self.layer_kinds and not (self.kda_heads
                                              and self.kda_head_dim):
            raise ValueError(f"{self.name}: KDA layers need kda_heads and "
                             f"kda_head_dim")

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def attn_kind(self, layer: int) -> str:
        """The attention kind of 0-indexed ``layer``."""
        if self.layer_kinds:
            return self.layer_kinds[layer]
        return "mla" if self.mla_kv_lora else "gqa"

    @property
    def period_kinds(self) -> tuple[str, ...]:
        """One period of the layer pattern: the leading layers up to and
        including the first whose attention keeps a per-token cache
        (MLA or GQA). A model without linear attention has a period of
        one layer; Kimi-Linear's is KDA, KDA, KDA, MLA."""
        kinds = tuple(self.attn_kind(i) for i in range(self.n_layers))
        cached = [i for i, k in enumerate(kinds) if k != "kda"]
        return kinds[:cached[0] + 1] if cached else kinds

    @property
    def kda_state_bytes(self) -> int:
        """One KDA layer's recurrent state per sequence, read whole and
        rewritten in place every decode step: the (head_dim x head_dim)
        state of each head at ``state_itemsize``, plus the short
        convolution's last ``kda_conv - 1`` inputs of q, k and v."""
        width = self.kda_heads * self.kda_head_dim
        return (self.kda_heads * self.kda_head_dim ** 2 * self.state_itemsize
                + (self.kda_conv - 1) * 3 * width * self.bytes_per_param)

    @property
    def kv_bytes_per_token_per_layer(self) -> int:
        """KV-cache bytes appended per token per layer (BF16)."""
        if self.mla_kv_lora:
            return (self.mla_kv_lora + self.mla_rope_dim) * self.bytes_per_param
        return 2 * self.n_kv_heads * self.head_dim * self.bytes_per_param


DEEPSEEK_V3 = PaperWorkload(
    name="deepseek-v3",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, head_dim=128,
    d_ff=2048, vocab=129280,
    n_experts=256, top_k=8, n_shared_experts=1,
    n_dense_layers=3, dense_d_ff=18432,
    mla_kv_lora=512, mla_q_lora=1536, mla_rope_dim=64,
    attn_tp=1,            # MLA favors DP for attention (§VI-A)
    moe_ep=8,
)

GROK_1 = PaperWorkload(
    name="grok-1",
    n_layers=64, d_model=6144, n_heads=48, n_kv_heads=8, head_dim=128,
    d_ff=32768, vocab=131072,
    n_experts=8, top_k=2,
    attn_tp=8, moe_ep=8,
)

LLAMA_3_405B = PaperWorkload(
    name="llama-3-405b",
    n_layers=126, d_model=16384, n_heads=128, n_kv_heads=8, head_dim=128,
    d_ff=53248, vocab=128256,
    attn_tp=8, moe_ep=1,
)

PAPER_WORKLOADS = {w.name: w for w in (DEEPSEEK_V3, GROK_1, LLAMA_3_405B)}

#: Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-Instruct
#: config.json; arXiv:2510.26692): 27 layers in a 3:1 pattern of KDA and
#: NoPE MLA (MLA at 1-indexed layers 4, 8, ..., 24 and 27), no q-LoRA,
#: a dense FFN in the first layer, then 256 routed experts (top-8) plus
#: one shared. Mapped like DeepSeek-V3 (§VI-A): attention data-parallel,
#: experts 8-way.
KIMI_LINEAR = PaperWorkload(
    name="kimi-linear",
    n_layers=27, d_model=2304, n_heads=32, n_kv_heads=32, head_dim=128,
    d_ff=1024, vocab=163840,
    n_experts=256, top_k=8, n_shared_experts=1,
    n_dense_layers=1, dense_d_ff=9216,
    mla_kv_lora=512, mla_q_lora=0, mla_rope_dim=64,
    attn_tp=1, moe_ep=8,
    layer_kinds=("kda", "kda", "kda", "mla") * 6 + ("kda", "kda", "mla"),
    kda_heads=32, kda_head_dim=128, kda_conv=4, kda_gate_rank=128,
)

#: Every workload the serving replay can run: the paper's three plus
#: Kimi-Linear.
SERVING_WORKLOADS = {**PAPER_WORKLOADS, KIMI_LINEAR.name: KIMI_LINEAR}


# ---------------------------------------------------------------------------
# Serving-trace length mixes (repro.serve.replay)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ServingMix:
    """Prompt/output token-length distribution for a serving trace.

    Prompt lengths are lognormal (median ``prompt_median``, coefficient of
    variation ``prompt_cv``); output lengths are geometric with mean
    ``out_mean``. Both are clamped to ``[1, *_max]``. The replay subsystem
    samples these through a seeded RNG
    (:class:`repro.serve.replay.ArrivalProcess`) and may scale them down
    uniformly (``length_scale``) to keep cycle-level simulation tractable
    — the *shape* of the mix, not its absolute token count, is what
    stresses the memory system.
    """

    prompt_median: int
    prompt_cv: float
    out_mean: int
    prompt_max: int = 8192
    out_max: int = 2048


# Chat-style mixes per evaluation model: MoE chat traffic (DeepSeek,
# Grok) skews to short-median / heavy-tail prompts; the dense Llama row
# mirrors the paper's long-context 8K-seq evaluation point.
SERVING_MIXES = {
    "deepseek-v3": ServingMix(prompt_median=512, prompt_cv=1.0, out_mean=256),
    "grok-1": ServingMix(prompt_median=512, prompt_cv=1.0, out_mean=256),
    "llama-3-405b": ServingMix(prompt_median=2048, prompt_cv=0.5,
                               out_mean=256),
    "kimi-linear": ServingMix(prompt_median=512, prompt_cv=1.0, out_mean=256),
}

#: The serve-replay sweep mix (benchmarks/serve_trace.py and
#: examples/serve_replay.py must agree on it, or the example's headline
#: stops reproducing the gated conditions): the chat mix with outputs
#: shortened so a cycle-level full load sweep stays tractable at 1/16
#: length scale.
REPLAY_SWEEP_MIX = ServingMix(prompt_median=512, prompt_cv=1.0, out_mean=128,
                              prompt_max=4096, out_max=512)
