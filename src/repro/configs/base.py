"""Configuration system for the repro framework.

Every selectable architecture (``--arch <id>``) is described by a
:class:`ModelConfig`.  Configs are plain frozen dataclasses so they can be
hashed into jit static arguments and printed into EXPERIMENTS.md verbatim.

The federated-protocol knobs live in :class:`FederatedConfig` and the mesh /
launch knobs in :class:`RunConfig`.  ``reduced()`` derives the CPU smoke-test
variant of any architecture (2 layers, d_model<=512, <=4 experts) required by
the per-arch smoke tests.

These dataclasses are the ENGINE-LEVEL configuration.  The serializable,
validating front-door over them is :class:`repro.api.FederationSpec`
(docs/api.md): a spec's ``to_federated_config()`` / ``to_round_config()``
compile into the classes below, and new scenario-level code should build
specs rather than hand-wiring these.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Architecture kinds
# ---------------------------------------------------------------------------
DENSE = "dense"
MOE = "moe"
SSM = "ssm"
HYBRID = "hybrid"
VLM = "vlm"
AUDIO = "audio"
NTM = "ntm"  # the paper's own models (ProdLDA / CTM)

ARCH_KINDS = (DENSE, MOE, SSM, HYBRID, VLM, AUDIO, NTM)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    num_experts: int = 0
    top_k: int = 1
    # capacity factor used to bound per-expert token count in the dense
    # einsum-dispatch implementation (tokens routed beyond capacity are
    # dropped, matching standard TPU MoE practice).
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # llama4-style: interleave dense and MoE layers (1 = every layer MoE)
    moe_every: int = 1
    # shared expert (qwen3 uses none, llama4 uses one shared expert)
    num_shared_experts: int = 0
    # GShard routing groups — aligned with the data-axis sharding so the
    # position-in-expert assignment is shard-local (16 = the production
    # data axis; automatically reduced to divide small test batches)
    num_groups: int = 16
    # "capacity": softmax router, Switch aux loss, per-expert capacity
    # (tokens past it dropped).  "noaux_tc": DeepSeek-V3's router —
    # sigmoid scores over all experts, top-k chosen on scores plus a
    # selection bias, gates the chosen scores normalised to sum 1 times
    # ``routed_scaling_factor``, no aux loss, no dropped token
    routing: str = "capacity"
    routed_scaling_factor: float = 1.0
    # expert parallelism: the experts are split into ``ep_size`` equal
    # contiguous shares and this chip holds share ``ep_rank`` (noaux_tc
    # routing only; the router still scores all ``num_experts``)
    ep_size: int = 1
    ep_rank: int = 0

    @property
    def num_held(self) -> int:
        return self.num_experts // max(self.ep_size, 1)


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD) configuration."""

    state_dim: int = 128          # N — SSM state size per head
    head_dim: int = 64            # P — channels per SSD head
    expand: int = 2               # d_inner = expand * d_model
    chunk_size: int = 256         # SSD block length
    conv_width: int = 4           # depthwise causal conv width


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture from the assigned pool (or the paper's NTM)."""

    name: str = "unnamed"
    kind: str = DENSE
    citation: str = ""

    # transformer backbone
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0             # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    qkv_bias: bool = False        # qwen1.5 style
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    activation: str = "swiglu"    # "swiglu" | "gelu"

    # MLA (minicpm3 / deepseek-style multi-head latent attention)
    use_mla: bool = False
    mla_kv_lora_rank: int = 256
    mla_q_lora_rank: int = 768   # 0 = a direct query projection (no w_dq)
    mla_rope_head_dim: int = 32
    # decode-time weight absorption (DeepSeek-V2 serving optimization):
    # attention scores/combine run directly in the latent space, the
    # per-step K/V expansion disappears (EXPERIMENTS.md §Perf pair C)
    mla_absorb: bool = False

    # sliding-window attention (enables long_500k for dense archs)
    sliding_window: int = 0       # 0 = full causal attention

    # M-RoPE (qwen2-vl): rotary split across (temporal, h, w) sections
    use_mrope: bool = False
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)

    # encoder-only (audio): bidirectional attention, masked-prediction head
    encoder_only: bool = False
    # frontend stub width: precomputed frame/patch embedding dim (0 = vocab)
    frontend_embed_dim: int = 0

    moe: MoEConfig = field(default_factory=MoEConfig)
    # DeepSeek-style leading dense layers (``first_k_dense_replace``): the
    # first ``first_k_dense`` layers are dense SwiGLU of width
    # ``dense_d_ff`` (0 = ``d_ff``), outside the scanned MoE units
    first_k_dense: int = 0
    dense_d_ff: int = 0
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # hymba: fraction of "heads" that are SSD heads in the parallel hybrid
    # block; attention and mamba run in parallel and are mean-fused.
    hybrid_attn: bool = False

    # NTM-specific (ProdLDA / CTM)
    num_topics: int = 50
    ntm_hidden: Tuple[int, ...] = (100, 100)
    ntm_dropout: float = 0.2
    contextual_dim: int = 0       # CombinedTM: SBERT embedding size (0 = ProdLDA)
    learn_priors: bool = True

    dtype: str = "bfloat16"       # activation dtype on the target hardware
    param_dtype: str = "float32"

    # lowering knobs (not architecture): scan_layers=False unrolls the
    # layer loop and unroll_chunks=True unrolls the attention/SSD chunk
    # scans — used by the roofline analysis lowering, where XLA's
    # cost_analysis counts while-loop bodies only once.
    scan_layers: bool = True
    unroll_chunks: bool = False
    # remat each scanned layer (the "remat scan" pattern): backward
    # recomputes the layer body from its input, so saved activations are
    # one (B,S,D) residual per layer instead of every intermediate
    remat_layers: bool = False

    # ----------------------------------------------------------------- utils
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def num_params(self) -> int:
        """Analytic parameter count (used for roofline MODEL_FLOPS=6ND)."""
        if self.kind == NTM:
            v, k = self.vocab_size, self.num_topics
            h = list(self.ntm_hidden)
            in_dim = v + self.contextual_dim
            n = 0
            dims = [in_dim] + h
            for a, b in zip(dims[:-1], dims[1:]):
                n += a * b + b
            n += 2 * (h[-1] * k + k)        # mu and logvar heads
            n += k * v                      # beta decoder
            return n
        d, hd = self.d_model, self.resolved_head_dim
        nq, nkv = self.num_heads, self.num_kv_heads
        n = self.vocab_size * d                      # embed
        if not self.tie_embeddings and not self.encoder_only:
            n += self.vocab_size * d                 # lm head
        per_layer = 0
        if self.kind == SSM:
            s = self.ssm
            d_in = s.expand * d
            nheads = d_in // s.head_dim
            per_layer = d * (2 * d_in + 2 * nheads * s.state_dim) \
                + d_in * s.conv_width + d_in * d + nheads + nheads
        else:
            if self.use_mla:
                qr, kr, rr = self.mla_q_lora_rank, self.mla_kv_lora_rank, \
                    self.mla_rope_head_dim
                per_layer += d * qr + qr * nq * (hd + rr) + qr if qr \
                    else d * nq * (hd + rr)
                per_layer += d * (kr + rr) + kr * nq * (hd + hd) + kr
                per_layer += nq * hd * d
            else:
                per_layer += d * (nq * hd) + 2 * d * (nkv * hd) + (nq * hd) * d
                if self.qkv_bias:
                    per_layer += nq * hd + 2 * nkv * hd
            if self.kind == HYBRID:
                s = self.ssm
                d_in = s.expand * d
                nheads = d_in // s.head_dim
                per_layer += d * (2 * d_in + 2 * nheads * s.state_dim) \
                    + d_in * s.conv_width + d_in * d + 2 * nheads
            # FFN
            if self.kind == MOE and self.moe.num_experts:
                e = self.moe.num_held + self.moe.num_shared_experts
                ffn = e * 3 * d * self.d_ff \
                    + d * self.moe.num_experts  # + router
                if self.moe.routing == "noaux_tc":
                    ffn += self.moe.num_experts  # selection bias
            else:
                mult = 3 if self.activation == "swiglu" else 2
                ffn = mult * d * self.d_ff
            per_layer += ffn + 2 * d  # + norms
            # the leading dense layers hold a SwiGLU in place of the FFN
            n += self.first_k_dense * (
                3 * d * (self.dense_d_ff or self.d_ff) - ffn)
        n += self.num_layers * per_layer + d
        return n

    def num_active_params(self) -> int:
        """Active params per token (MoE discounts inactive experts)."""
        if self.kind != MOE or not self.moe.num_experts:
            return self.num_params()
        total = self.num_params()
        e, k = self.moe.num_experts, self.moe.top_k
        sh = self.moe.num_shared_experts
        ffn = 3 * self.d_model * self.d_ff
        inactive = self.num_layers * (e - k) * ffn
        return total - inactive

    def reduced(self) -> "ModelConfig":
        """CPU smoke-test variant: same family, tiny dimensions."""
        d = min(self.d_model, 256)
        nh = min(self.num_heads, 4)
        nkv = max(1, min(self.num_kv_heads, nh))
        # preserve GQA ratio flavor: kv=1 stays 1, kv==heads stays equal
        if self.num_kv_heads == self.num_heads:
            nkv = nh
        elif self.num_kv_heads == 1:
            nkv = 1
        else:
            nkv = max(1, nh // 2)
        kw = dict(
            num_layers=2,
            d_model=d,
            num_heads=nh,
            num_kv_heads=nkv,
            head_dim=d // nh if nh else 0,
            d_ff=min(self.d_ff, 512) or 0,
            vocab_size=min(self.vocab_size, 512),
            max_seq_len=min(self.max_seq_len, 512),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
        )
        if self.kind == MOE:
            kw["moe"] = replace(self.moe, num_experts=4,
                                top_k=min(self.moe.top_k, 2))
        if self.kind in (SSM, HYBRID):
            kw["ssm"] = replace(self.ssm, state_dim=min(self.ssm.state_dim, 16),
                                head_dim=32, chunk_size=64)
        if self.use_mla:
            kw["mla_kv_lora_rank"] = 32
            kw["mla_q_lora_rank"] = 48 if self.mla_q_lora_rank else 0
            kw["mla_rope_head_dim"] = 16
        if self.dense_d_ff:
            kw["dense_d_ff"] = min(self.dense_d_ff, 512)
        if self.use_mrope:
            hd = d // nh
            kw["mrope_sections"] = (hd // 2 - 2 * (hd // 8), hd // 8, hd // 8)
        if self.frontend_embed_dim:
            kw["frontend_embed_dim"] = d
        if self.kind == NTM:
            kw = dict(vocab_size=min(self.vocab_size, 512),
                      num_topics=min(self.num_topics, 10),
                      ntm_hidden=(32, 32),
                      contextual_dim=32 if self.contextual_dim else 0)
        return replace(self, **kw)


@dataclass(frozen=True)
class FederatedConfig:
    """gFedNTM protocol knobs (paper Alg. 1 + beyond-paper extensions)."""

    num_clients: int = 5
    learning_rate: float = 2e-3     # lambda in Eq. (3)
    max_rounds: int = 100           # I in Alg. 1
    # Sync-Opt syncs every minibatch (paper). local_steps>1 = FedAvg-style
    # beyond-paper optimization (divides collective volume).
    local_steps: int = 1
    aggregation: str = "weighted_mean"  # Eq. (2)
    # beyond-paper:
    secure_aggregation: bool = False    # pairwise-mask secure agg simulation
    compression_topk: float = 0.0       # 0 = dense; else fraction of grads kept
    dp_noise_multiplier: float = 0.0    # local DP Gaussian noise
    dp_clip_norm: float = 1.0
    # wire format for client round messages, consumed by the "precision"
    # transform: "" = fp32 (dense, exact), "bf16" = messages rounded to
    # bfloat16 before transmission, accumulated in fp32 server-side.
    # Incompatible with secure aggregation (bitwise mask cancellation).
    message_precision: str = ""
    rel_tol: float = 1e-5               # stopping criterion on weight change


@dataclass(frozen=True)
class RoundConfig:
    """Scenario knobs for the unified engine (``core/engine.py``).

    The defaults reproduce paper Algorithm 1 exactly: full participation
    (K = L), one local step (E = 1), no stragglers, and a FedAvg server
    update with ``server_lr = 1`` — which IS the Eq. (3) SGD step.  Every
    other setting is a beyond-paper regime; ``docs/rounds.md`` and
    ``docs/scenarios.md`` map each knob to the paper / related-work
    setting it reproduces.

    Scenario-level code should not build this directly: the declarative
    ``repro.api.FederationSpec`` (``schedule``/``server_opt``/
    ``execution`` sections) validates and serializes the same surface
    and compiles here via ``to_round_config()`` (docs/api.md).
    """

    # execution path: "loop" steps the cohort client-by-client on the
    # host (the literal Alg. 1 composition); "vmap" stacks the cohort's
    # minibatches on a leading client axis and runs all K local updates,
    # the Eq. (2) combine and the server optimizer in ONE jitted graph
    # (DESIGN.md §4).  Both retrace the same trajectory (tested).
    exec_mode: str = "loop"
    # participation: K clients sampled out of L per round (0 = all L)
    clients_per_round: int = 0
    # "uniform" | "weighted" (by corpus size) | "deterministic" (seeded
    # round-robin over a fixed permutation — full coverage, no variance)
    sampling: str = "uniform"
    sampling_seed: int = 0
    # E local SGD steps per selected client before the delta is sent
    local_epochs: int = 1
    # server optimizer applied to the weighted delta (core/aggregation.py
    # SERVER_OPTIMIZERS registry): "fedavg" | "fedavgm" | "fedadam"
    server_optimizer: str = "fedavg"
    server_lr: float = 1.0
    server_momentum: float = 0.9    # FedAvgM beta / FedAdam b1
    server_beta2: float = 0.999     # FedAdam b2
    server_eps: float = 1e-3        # FedAdam tau
    # staleness model: each selected client independently straggles with
    # probability ``straggler_prob``; its update arrives 1..max_staleness
    # rounds late, down-weighted by staleness_decay ** age.  max_staleness
    # = 0 disables the buffer entirely (synchronous, paper regime).
    # Under exec_mode="vmap" the straggler path runs as an in-graph
    # fixed-capacity ring buffer (DESIGN.md §4); exec_mode="loop" keeps
    # the host-side pending list + ``combine_arrivals`` reference.
    straggler_prob: float = 0.0
    max_staleness: int = 0
    staleness_decay: float = 0.5
    # message transforms applied to each client's round message (delta or
    # grad) before the Eq. (2) combine — names from
    # ``core.transforms.TRANSFORMS``: "dp" (clip + Gaussian local DP,
    # driven by FederatedConfig.dp_*), "topk" (top-k sparsification +
    # error feedback, FederatedConfig.compression_topk), "secure"
    # (pairwise cancelling masks, bitwise-exact sum-to-zero; requires
    # synchronous full participation).  Both exec modes apply them: the
    # loop path per client on the host, the vmap path as vectorized ops
    # INSIDE the fused jitted graph (loop/vmap parity <1e-5, tested).
    transforms: Tuple[str, ...] = ()
    # fixed-K cohort stacking (vmap mode): pad cohorts shrunken by
    # mid-training dropout/join with zero-weight rows up to
    # clients_per_round, so every round — including empty ones under the
    # straggler buffer — reuses ONE compiled graph instead of retracing
    # per distinct cohort size.  Zero-weight rows are absent from the
    # combine, the ring buffer and all transform state.  Disable only to
    # reproduce the pre-PR-4 retrace-per-size behavior.
    pad_cohorts: bool = True
    # device heterogeneity: per-client local-epoch counts (client l runs
    # local_epochs_by_client[l % len] epochs).  () = homogeneous
    # ``local_epochs``.  Under vmap the cohort is stacked to the max and
    # shorter clients' extra epochs are gated off inside the scan.
    local_epochs_by_client: Tuple[int, ...] = ()
    # mid-training availability: client l joins the federation at round
    # client_join_round[l % len] (default 0 = present from the start) and
    # leaves at client_leave_round[l % len] (0 = never leaves).  The
    # scheduler only samples among active clients; a round with no active
    # clients is a no-op (due stragglers still deliver).
    client_join_round: Tuple[int, ...] = ()
    client_leave_round: Tuple[int, ...] = ()
    # data partitioner spec for scenario drivers (launch/simulate.py,
    # benchmarks/bench_scenarios.py): "topic" (the paper's §4.2 per-node
    # topic split), "iid", "dirichlet(alpha)", "quantity_skew(alpha)" —
    # registry in data/federated_split.py.  The engine itself never reads
    # this; it describes how the driver builds the client corpora.
    partition: str = "topic"
    # aggregation kernel backend for the fused vmap graphs: "xla" (the
    # parity reference — the plain-XLA combine/transform expressions the
    # engine always ran) or "pallas" (the fused kernels in
    # kernels/fed_aggregate.py via kernels/ops.py).  Like pad_cohorts,
    # this is a vmap-path knob: loop mode always runs host XLA and IS
    # the reference both vmap backends are held to (<=1e-5, tested).
    kernel_backend: str = "xla"
    # device-mesh width for the fused vmap graphs (FederationSpec's
    # execution.mesh.data): 0 = unsharded single-device execution; N >= 1
    # builds a ("data",)-axis mesh over the first N local devices
    # (parallel/sharding.py fed_mesh) and shards the stacked (K, ...)
    # cohort, the (L, ...) per-client state trees and the straggler ring
    # over it.  K and L must be divisible by N (refused, never silently
    # repartitioned).  Another vmap-path knob: loop mode stays the
    # unsharded host reference the sharded graphs are held to.
    mesh_data: int = 0


@dataclass(frozen=True)
class ShapeConfig:
    """One of the four assigned input shapes."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

INPUT_SHAPES = {
    s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
}


@dataclass(frozen=True)
class RunConfig:
    """Launcher-level configuration."""

    arch: str = "phi3-mini-3.8b"
    shape: str = "train_4k"
    multi_pod: bool = False
    optimizer: str = "sgd"          # paper Eq. (3); "adam" available
    learning_rate: float = 2e-3
    remat: str = "none"             # "none" | "full" | "dots"
    seed: int = 0
    steps: int = 100
    log_every: int = 10
    checkpoint_dir: str = ""
    federated: FederatedConfig = field(default_factory=FederatedConfig)
    rounds: RoundConfig = field(default_factory=RoundConfig)


def asdict(cfg) -> dict:
    return dataclasses.asdict(cfg)
