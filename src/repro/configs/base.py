"""Model / training / distribution configuration system.

Every assigned architecture gets a ``configs/<id>.py`` exporting ``CONFIG``
(the exact full-size config) and ``SMOKE_CONFIG`` (reduced same-family
variant: <=2 layers, d_model<=512, <=4 experts) used by CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                  # hidden size of each expert FFN
    capacity_factor: float = 1.25
    group_size: int = 512          # dispatch group (tokens) for the einsum path
    moe_every: int = 1             # 1 = every layer is MoE; 2 = alternate dense/MoE
    n_shared_experts: int = 0      # always-on shared expert(s) (llama4)
    router_aux_weight: float = 0.01
    combine_seq_shard: bool = False  # constrain combine output group-sharded
                                     # over `model` (RS instead of AR)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    kind: str = "mamba2"           # mamba2 | rwkv6
    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64             # SSM head dim (mamba2 P / rwkv head size)
    chunk: int = 128               # SSD chunk length (mamba2)


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """zamba2-style: mamba backbone + one weight-shared attention block
    applied every `shared_every` positions."""
    shared_every: int = 6


@dataclasses.dataclass(frozen=True)
class FrontendConfig:
    """Stubbed modality frontend (assignment carve-out): input_specs()
    provides precomputed embeddings of this shape."""
    kind: str                       # "vision" | "audio"
    n_tokens: int                   # patches / frames per example
    embed_dim: int                  # frontend output dim
    text_tokens: int = 0            # VLM: text positions appended after patches


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    # attention behaviour
    causal: bool = True
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None    # window for local layers
    local_global_pattern: int = 0           # k>0: alternate k local : 1 global
    rope_theta: float = 10_000.0
    # mlp
    mlp_type: str = "swiglu"                # swiglu | geglu | gelu
    # subsystem configs
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    frontend: Optional[FrontendConfig] = None
    # numerics / training
    tie_embeddings: bool = False
    scale_embed: bool = False               # gemma: token embedding * sqrt(D)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: str = "none"                     # none | full | dots
    loss_chunk: int = 0                     # 0 = unchunked cross-entropy
    scan_unroll: bool = False               # unroll the layer scan (dry-run
                                            # analysis: exact HLO flops/collectives)
    attn_impl: str = "naive"                # naive | chunked (flash-style online
                                            # softmax, never materialises SxS)
    attn_chunk: int = 1024                  # KV block size for chunked attention
    # paper-technique defaults for this arch
    source: str = ""                        # citation

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    def n_params(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        D, F, V, Hd = self.d_model, self.d_ff, self.vocab_size, self.resolved_head_dim
        H, KV, L = self.n_heads, self.n_kv_heads, self.n_layers
        emb = V * D * (1 if self.tie_embeddings else 2)
        attn = D * H * Hd + 2 * D * KV * Hd + H * Hd * D
        if self.mlp_type in ("swiglu", "geglu"):
            mlp = 3 * D * F
        else:
            mlp = 2 * D * F
        per_layer = attn + mlp + 2 * D
        if self.family == "moe":
            m = self.moe
            expert = 3 * D * m.d_expert
            moe_layer = attn + m.n_experts * expert + D * m.n_experts + 2 * D \
                + m.n_shared_experts * 3 * D * self.d_ff
            n_moe = L // m.moe_every
            total_blocks = (L - n_moe) * per_layer + n_moe * moe_layer
        elif self.family == "ssm" and self.ssm.kind == "rwkv6":
            # rwkv: timemix (r,k,v,g,o ~ 5 D^2 + decay lora) + channelmix ~ 2*D*F
            total_blocks = L * (5 * D * D + 2 * D * F + 2 * D)
        elif self.family in ("ssm", "hybrid"):
            di = self.ssm.expand * D
            mamba = D * (2 * di + 2 * self.ssm.d_state * (di // self.ssm.head_dim)) \
                + di * D + di * self.ssm.d_conv
            if self.family == "hybrid":
                n_shared = L // (self.hybrid.shared_every + 1) if self.hybrid else 0
                total_blocks = (L - n_shared) * (mamba + 2 * D) + (attn + mlp + 2 * D)
            else:
                total_blocks = L * (mamba + 2 * D)
        else:
            total_blocks = L * per_layer
        proj = 0
        if self.frontend is not None and self.frontend.kind == "vision":
            proj = self.frontend.embed_dim * D + D * D
        if self.family == "audio":
            emb = self.frontend.embed_dim * D + V * D   # in-proj + class head
        return emb + total_blocks + proj + D

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.n_params()
        m = self.moe
        D = self.d_model
        expert = 3 * D * m.d_expert
        inactive = (m.n_experts - m.top_k) * expert * (self.n_layers // m.moe_every)
        return self.n_params() - inactive


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


ARCH_IDS = [
    "yi-9b", "hubert-xlarge", "qwen3-1.7b", "zamba2-1.2b", "qwen3-moe-30b-a3b",
    "llama4-maverick-400b-a17b", "gemma2-9b", "rwkv6-3b",
    "llava-next-mistral-7b", "gemma-7b",
]


def get_config(arch: str, smoke: bool = False) -> ModelConfig:
    """Load the named arch's ModelConfig (or its tiny SMOKE_CONFIG) from
    its ``repro.configs.<arch>`` module, lazily imported."""
    mod = importlib.import_module(f"repro.configs.{arch.replace('-', '_').replace('.', '_')}")
    return mod.SMOKE_CONFIG if smoke else mod.CONFIG


def parse_topology(spec: str) -> Tuple[str, ...]:
    """Comma-separated topology spec -> name tuple ("ring,hypercube" ->
    ("ring", "hypercube")).  The single parser for both the CLI validation
    (launch/train.py, pre-jax) and the trainer — this module is jax-free, so
    the two can never drift."""
    return tuple(t.strip() for t in spec.split(",") if t.strip())


def parse_straggler_edges(spec: str) -> Tuple[Tuple[int, int], ...]:
    """Comma-separated edge spec -> node-pair tuple ("0-1,2-3" ->
    ((0, 1), (2, 3))).  Syntax-level validation only (integers, 'a-b'
    shape, no self-edges, nonnegative ids) so the CLI can fail fast
    pre-jax; membership in the compiled schedule's edge support is
    checked by ``StalenessProcess`` once the schedule exists.  This
    module is jax-free, so launcher and trainer share one parser."""
    out = []
    for part in (p.strip() for p in spec.split(",") if p.strip()):
        halves = part.split("-")
        if len(halves) != 2:
            raise ValueError(f"straggler edge {part!r} is not of the "
                             f"form 'a-b'")
        try:
            a, b = int(halves[0]), int(halves[1])
        except ValueError:
            raise ValueError(f"straggler edge {part!r} has non-integer "
                             f"node ids") from None
        if a < 0 or b < 0:
            raise ValueError(f"straggler edge {part!r} has negative "
                             f"node ids")
        if a == b:
            raise ValueError(f"straggler edge {part!r} is a self-edge")
        out.append((min(a, b), max(a, b)))
    if not out:
        raise ValueError(f"empty straggler edge spec {spec!r}")
    return tuple(out)


def parse_delay_probs(spec: str) -> Tuple[float, ...]:
    """Comma-separated probability list ("0.1,0.2,0.7" -> floats).
    Syntax + sign/mass validation only; the arity-vs-max_staleness check
    lives with the consumer (CLI pre-jax, ``StalenessProcess`` at build
    time).  Jax-free, shared by launcher and trainer."""
    try:
        probs = tuple(float(p.strip())
                      for p in spec.split(",") if p.strip())
    except ValueError:
        raise ValueError(f"delay probs {spec!r} must be a comma-"
                         f"separated float list") from None
    if not probs:
        raise ValueError(f"empty delay-probs spec {spec!r}")
    if min(probs) < 0 or sum(probs) <= 0:
        raise ValueError(f"delay probs must be nonnegative with positive "
                         f"mass, got {probs}")
    return probs


@dataclasses.dataclass(frozen=True)
class ChocoConfig:
    """Paper-technique settings for decentralized training."""
    compressor: str = "top_k"       # compression.make_compressor name
    comp_kwargs: tuple = (("fraction", 0.01),)
    gossip_axis: str = "data"       # mesh axis carrying the gossip graph
    # gossip graph name (core.topology registry: ring | torus | hypercube |
    # star | chain | fully_connected), or a comma-separated sequence
    # ("ring,hypercube") for time-varying mixing — the schedule compiler
    # (comm/schedule.py) compiles one schedule per name and the engine
    # cycles through them across the gossip_steps rounds of each SGD step
    topology: str = "ring"
    # CHOCO gossip rounds per SGD step (Hashemi et al. 2020: multiple gossip
    # steps per update dramatically improve communication-constrained
    # convergence); the packed engine builds the bucket spec once per step,
    # so k rounds amortize k compressions into one pack
    gossip_steps: int = 1
    consensus_gamma: Optional[float] = None   # None = Theorem-2 stepsize
    # which leaves gossip exactly (uncompressed): tiny leaves where compression
    # overhead > saving (beyond-paper optimisation, off for paper-faithful runs)
    exact_small_leaves: bool = False
    small_leaf_threshold: int = 8_192
    # dtype of the error-feedback states x_hat and s (beyond-paper memory
    # optimisation: bf16 halves the 2N-state overhead and the wire payload)
    state_dtype: str = "float32"
    # bucketed flat-buffer gossip engine (comm/packing.py): pack the pytree
    # into a few dtype-homogeneous buckets, compress once per bucket, ship
    # one payload per neighbour.  False = legacy per-leaf exchange.
    packed_gossip: bool = True
    # segment alignment inside compressed buckets; None = the compressor's
    # block width (block_top_k) or the 128-lane unit
    pack_align: Optional[int] = None
    # stochastic topology process (comm/stochastic.py): None = static
    # schedule replay; "matching" samples one compiled round per gossip
    # round (one permute launch/step, replica-based engine); "linkfail"
    # drops each edge i.i.d. with edge_drop_prob per round (weights
    # renormalized into the diagonal).  Theorem-2 gamma is re-derived from
    # the EXPECTED mixing matrix's eigengap.
    # "staleness" runs the bounded-staleness async engine
    # (comm/async_gossip.py): every edge's payload may arrive up to
    # max_staleness rounds late (per-edge delay sampled from the shared
    # exchange key) and nodes proceed on the freshest copy they hold.
    topology_process: Optional[str] = None
    edge_drop_prob: float = 0.1          # linkfail Bernoulli drop probability
    matching_sampler: str = "uniform"    # matching round sampler: uniform|weighted
    # staleness bound tau for topology_process="staleness": per-edge delays
    # are sampled uniformly from {0..tau} (tau=0 degenerates to the always-
    # fresh replica engine).  Theorem-2 gamma folds tau into omega and uses
    # the delay-averaged mixing matrix phi*W + (1-phi)*I, phi = E[1/(1+d)].
    max_staleness: int = 1
    # pipelined engine (comm/pipelined.py): compress the PRE-gradient
    # iterate and integrate the received payload at the NEXT step's update
    # so the collective overlaps the backward pass (tau=1 deterministic
    # staleness; gamma re-derived from (W+I)/2 with omega/2).  Requires
    # mode='choco', a single static topology, and no topology_process.
    pipeline_gossip: bool = False
    # kernel backend for the gossip hot path (kernels/dispatch.py):
    # 'auto' picks the fused Pallas kernels on a TPU and jnp elsewhere,
    # 'pallas'/'jnp' force.  Never part of the
    # checkpoint fingerprint: flipping it changes neither the state layout
    # nor the wire bytes, so resumes are backend-portable.
    kernel_backend: str = "auto"
    # non-IID data skew (data/partition.py): Dirichlet(alpha) per-node
    # vocab/label shards — alpha -> inf is IID ("shuffled"), alpha -> 0 is
    # disjoint shards ("sorted").  None = the legacy heterogeneity knob.
    data_skew_alpha: Optional[float] = None
    # per-edge straggler links for topology_process="staleness": canonical
    # "a-b,c-d" edge list whose delays come from straggler_delay_probs
    # (comma-separated P(d=0..tau); None = point mass at tau, a maximally
    # slow link) instead of the global uniform/delay_probs distribution.
    straggler_edges: Optional[str] = None
    straggler_delay_probs: Optional[str] = None

    def comp_dict(self):
        return dict(self.comp_kwargs)
