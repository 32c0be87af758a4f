"""Unified architecture configuration.

Every assigned architecture (dense / MoE / SSM / hybrid / enc-dec, with optional
modality-frontend stubs) is an instance of :class:`ModelConfig`.  The config is
consumed by three independent subsystems:

* ``models/``      — builds parameters and the forward/serve functions,
* ``core/graphgen``— builds the costed dataflow graph the paper's partitioner runs on,
* ``launch/``      — builds ShapeDtypeStruct input specs for the dry-run.

Layer structure is expressed as a per-layer ``LayerSpec(mixer, ffn)`` sequence,
compressed into scan-friendly ``Segment`` runs (cycle of layer classes × repeats)
so that XLA compiles one body per layer class instead of one per layer.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import NamedTuple, Optional

# Mixer kinds. "global"/"local" are softmax attention (local = sliding window),
# "mla" is DeepSeek multi-head latent attention, "ssd" is Mamba-2 state space
# duality, "rglru" is the RecurrentGemma gated linear recurrence.
MIXERS = ("global", "local", "mla", "ssd", "rglru")
FFNS = ("dense", "moe", "none")


@dataclass(frozen=True)
class LayerSpec:
    mixer: str
    ffn: str

    def __post_init__(self):
        assert self.mixer in MIXERS, self.mixer
        assert self.ffn in FFNS, self.ffn

    @property
    def key(self) -> str:
        return f"{self.mixer}+{self.ffn}"


class Yarn(NamedTuple):
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it:
    the rotary frequencies are interpolated by ``factor`` below the
    correction range set by ``beta_fast``/``beta_slow`` rotations over
    ``original_max_position``, and attention logits grow by
    ``mscale_all_dim``'s YaRN mscale squared."""

    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class Segment:
    """A run of ``repeats`` consecutive super-layers, each made of ``cycle``."""

    cycle: tuple[LayerSpec, ...]
    repeats: int


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # -- attention flavour ---------------------------------------------------
    # layer_cycle: repeating cycle of (mixer, ffn) layer classes; padded /
    # truncated to n_layers. Overridden per-layer by dense_first (DeepSeek).
    layer_cycle: tuple[tuple[str, str], ...] = (("global", "dense"),)
    window_size: int = 0                 # sliding/local attention window
    attn_logit_softcap: float = 0.0      # gemma2-style softcap on attn logits
    final_logit_softcap: float = 0.0     # gemma2-style softcap on lm logits
    rope_theta: float = 10_000.0
    rope_yarn: Optional[Yarn] = None     # YaRN scaling of the MLA rope
    qk_norm: bool = False

    # -- FFN -----------------------------------------------------------------
    ffn_act: str = "silu"                # silu => SwiGLU, gelu => GeGLU

    # -- MoE -----------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    first_k_dense: int = 0               # DeepSeek: first k layers use dense FFN
    router_aux_coef: float = 0.0
    norm_topk_prob: bool = True          # renormalise the top-k gates (Mixtral)
    # (index, count): this device holds routed experts index * n / count
    # to (index + 1) * n / count - 1 of every MoE layer; the router still
    # scores all n_experts (expert parallelism across ``count`` devices)
    expert_shard: tuple[int, int] = (0, 1)

    # -- MLA (DeepSeek-V2) ----------------------------------------------------
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    # -- SSD (Mamba-2) ---------------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 256
    d_conv: int = 4

    # -- RG-LRU (RecurrentGemma) -----------------------------------------------
    lru_width: int = 0
    lru_block_width: int = 0             # conv1d width inside recurrent block

    # -- encoder-decoder --------------------------------------------------------
    n_enc_layers: int = 0                # >0 => enc-dec; decoder = n_layers

    # -- modality frontend (STUB: precomputed embeddings are model inputs) -----
    frontend: Optional[str] = None       # None | "vision" | "audio"
    frontend_tokens: int = 0             # patches / frames per sample
    frontend_dim: int = 0                # embedding dim delivered by the stub

    # -- misc -------------------------------------------------------------------
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    emb_scale: bool = False              # gemma-style sqrt(d) embedding scale

    def __post_init__(self):
        # plain sequences (a config read back from JSON) take their types
        if self.rope_yarn is not None and not isinstance(self.rope_yarn,
                                                         Yarn):
            object.__setattr__(self, "rope_yarn", Yarn(*self.rope_yarn))
        object.__setattr__(self, "expert_shard", tuple(self.expert_shard))
        index, count = self.expert_shard
        if self.n_experts % count or not 0 <= index < count:
            raise ValueError(f"expert_shard {self.expert_shard} does not "
                             f"split {self.n_experts} experts")

    # ---------------------------------------------------------------------------
    def layers(self) -> tuple[LayerSpec, ...]:
        """Expand layer_cycle (+ first_k_dense override) to n_layers specs."""
        out = []
        cyc = self.layer_cycle
        for i in range(self.n_layers):
            mixer, ffn = cyc[i % len(cyc)]
            if ffn == "moe" and i < self.first_k_dense:
                ffn = "dense"
            out.append(LayerSpec(mixer, ffn))
        return tuple(out)

    def enc_layers(self) -> tuple[LayerSpec, ...]:
        return tuple(LayerSpec("global", "dense") for _ in range(self.n_enc_layers))

    def segments(self) -> tuple[Segment, ...]:
        """Compress layers() into (cycle, repeats) scan segments.

        Greedy: take the longest prefix that is an integer number of repeats of
        the leading cycle (cycle length = len(layer_cycle), or shorter uniform
        runs for remainders / overrides).
        """
        specs = list(self.layers())
        segs: list[Segment] = []
        i = 0
        clen = len(self.layer_cycle)
        while i < len(specs):
            # try full-cycle run
            if clen > 1 and i + clen <= len(specs):
                cyc = tuple(specs[i : i + clen])
                reps = 1
                j = i + clen
                while j + clen <= len(specs) and tuple(specs[j : j + clen]) == cyc:
                    reps += 1
                    j += clen
                if reps >= 1 and (clen > 1):
                    segs.append(Segment(cyc, reps))
                    i = j
                    continue
            # uniform run of a single class
            cyc = (specs[i],)
            reps = 1
            j = i + 1
            while j < len(specs) and specs[j] == specs[i]:
                reps += 1
                j += 1
            segs.append(Segment(cyc, reps))
            i = j
        return tuple(segs)

    # -- derived sizes ----------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a TP-friendly multiple (Megatron-style): the
        embed/unembed tables use this; CE masks the pad ids. <=2% waste."""
        mult = 2048
        if self.vocab_size % 16 == 0:
            return self.vocab_size
        return ((self.vocab_size + mult - 1) // mult) * mult

    @property
    def held_experts(self) -> range:
        """The routed experts this device holds (``expert_shard``)."""
        index, count = self.expert_shard
        n = self.n_experts // count
        return range(index * n, (index + 1) * n)

    @property
    def d_inner(self) -> int:  # SSD inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def param_count(self) -> int:
        """Total parameter count (exact, matches init_params)."""
        total = self.vocab_size * self.d_model  # embed
        if not self.tie_embeddings:
            total += self.vocab_size * self.d_model
        total += self.d_model  # final norm
        for spec in list(self.layers()) + list(self.enc_layers()):
            total += self._mixer_params(spec.mixer) + self._ffn_params(spec.ffn)
            total += 2 * self.d_model  # two pre-norms (approx; ssd/rglru have one)
        if self.n_enc_layers:  # cross attention in every decoder layer
            total += self.n_layers * self._cross_attn_params()
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed-active experts)."""
        total = self.vocab_size * self.d_model
        if not self.tie_embeddings:
            total += self.vocab_size * self.d_model
        for spec in list(self.layers()) + list(self.enc_layers()):
            total += self._mixer_params(spec.mixer)
            if spec.ffn == "moe":
                per_exp = 3 * self.d_model * self.d_ff_expert
                total += per_exp * (self.experts_per_token + self.n_shared_experts)
                total += self.d_model * self.n_experts  # router
            elif spec.ffn == "dense":
                total += 3 * self.d_model * self.d_ff
        if self.n_enc_layers:
            total += self.n_layers * self._cross_attn_params()
        return total

    def _mixer_params(self, mixer: str) -> int:
        d = self.d_model
        if mixer in ("global", "local"):
            return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if mixer == "mla":
            p = d * self.kv_lora_rank + d * (self.n_heads * self.qk_rope_dim)
            if self.q_lora_rank:
                p += d * self.q_lora_rank + self.q_lora_rank * self.n_heads * (
                    self.qk_rope_dim + self.qk_nope_dim)
            else:
                p += d * self.n_heads * (self.qk_rope_dim + self.qk_nope_dim)
            p += self.kv_lora_rank * self.n_heads * (self.qk_nope_dim + self.v_head_dim)
            p += self.n_heads * self.v_head_dim * d
            return p
        if mixer == "ssd":
            di, ns, nh = self.d_inner, self.ssm_state, self.ssm_heads
            p = d * (2 * di + 2 * ns + nh)   # in_proj -> x, z, B, C, dt
            p += self.d_conv * (di + 2 * ns)  # causal conv over x,B,C
            p += 2 * nh                       # A_log, D
            p += di * d                       # out_proj
            return p
        if mixer == "rglru":
            w = self.lru_width
            p = 2 * d * w                     # linear x and gate branches
            p += self.lru_block_width * w     # temporal conv1d
            p += 2 * w * w // 1 if False else 2 * w  # (diagonal recurrence gates)
            p += 2 * w * w                    # input gate + recurrence gate projections
            p += w * d                        # out proj
            return p
        raise ValueError(mixer)

    def _ffn_params(self, ffn: str) -> int:
        if ffn == "dense":
            return 3 * self.d_model * self.d_ff
        if ffn == "moe":
            per_exp = 3 * self.d_model * self.d_ff_expert
            held = len(self.held_experts)
            return (held + self.n_shared_experts) * per_exp + \
                self.d_model * self.n_experts
        return 0

    def _cross_attn_params(self) -> int:
        d = self.d_model
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        n_layers = min(self.n_layers, 4 if len(self.layer_cycle) <= 2 else 2 * len(self.layer_cycle))
        # keep cycle structure intact
        clen = len(self.layer_cycle)
        if clen > 1:
            n_layers = max(clen, (n_layers // clen) * clen) + (1 if self.first_k_dense else 0)
        d_model = 64
        head_dim = 16
        n_heads = 4
        n_kv = max(1, min(self.n_kv_heads, 2)) if self.n_kv_heads != self.n_heads else 4
        return self.replace(
            n_layers=max(2, n_layers),
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=128,
            d_ff_expert=64 if self.d_ff_expert else 0,
            vocab_size=512,
            n_experts=min(self.n_experts, 4),
            experts_per_token=min(self.experts_per_token, 2),
            n_shared_experts=min(self.n_shared_experts, 1),
            q_lora_rank=32 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_rope_dim=8 if self.qk_rope_dim else 0,
            qk_nope_dim=8 if self.qk_nope_dim else 0,
            v_head_dim=16 if self.v_head_dim else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            ssm_chunk=16,
            lru_width=64 if self.lru_width else 0,
            lru_block_width=4 if self.lru_width else 0,
            window_size=min(self.window_size, 32) if self.window_size else 0,
            n_enc_layers=min(self.n_enc_layers, 2),
            frontend_tokens=8 if self.frontend else 0,
            frontend_dim=d_model if self.frontend else 0,
        )


@dataclass(frozen=True)
class ShapeConfig:
    """One dry-run cell's input shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}
