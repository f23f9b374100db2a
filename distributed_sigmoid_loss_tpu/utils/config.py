"""Config dataclasses — the framework's flag system.

The reference's entire config surface is constructor args and test kwargs
(``gpu_batch_size``, ``rank/world_size/bidir``, ``emb_dim/world_size/batch_size`` —
SURVEY.md §5). We mirror those knob names 1:1 in :class:`LossConfig` and add the model /
train configs the BASELINE.json end-to-end targets need (ViT-B/16 + text transformer,
global batch 4096-32768).
"""

from __future__ import annotations

import dataclasses
from typing import Literal


@dataclasses.dataclass(frozen=True)
class LossConfig:
    """Distributed sigmoid loss knobs (reference constructor args)."""

    variant: Literal["all_gather", "ring"] = "ring"
    # "sigmoid" = SigLIP (the reference's loss); "softmax" = CLIP/InfoNCE (the
    # open_clip loss the reference's ring variant was a PR against) — same two
    # comm variants; the model's `bias` param is unused (zero grad) under it.
    family: Literal["sigmoid", "softmax"] = "sigmoid"
    bidir: bool = True  # rwightman_sigmoid_loss.py:30
    axis_name: str = "dp"
    # HIGHEST = fp32 accumulation for parity gates; DEFAULT = bf16 for throughput.
    precision: str = "highest"
    # Streaming 2-D Pallas loss kernel: every logits block (fused gather,
    # chunked scan body, ring hop) computes tile-by-tile in VMEM with a
    # fused-backward recompute VJP; with quant_train="int8" towers the block
    # products run the int8 MXU path. Composes with loss_impl="chunked" and
    # ring_overlap; falls back to XLA per block for non-tileable shapes
    # (recorded at trace time, never silent).
    use_pallas: bool = False
    # "chunked" (all_gather sigmoid only): stream the gathered negatives
    # through a lax.scan over W chunk-blocks instead of one fused
    # (local_b, W*local_b) matmul — the full logits matrix is never
    # materialized, cutting peak loss HBM ~W* (ops/sigmoid_loss.py
    # sigmoid_loss_chunk_scan). Parity-oracled against "fused".
    loss_impl: Literal["fused", "chunked"] = "fused"
    # Ring sigmoid only: double-buffer the hop loop (hop k+1's ppermute issued
    # before hop k's block matmuls) so XLA hides ICI latency behind the MXU.
    # Bitwise-comparable to the serial ring (same accumulation order).
    ring_overlap: bool = False


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """Image tower. Defaults = ViT-B/16 (BASELINE.json config #4)."""

    image_size: int = 224
    patch_size: int = 16
    width: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_ratio: int | float = 4
    embed_dim: int = 512  # shared image-text embedding space
    pool: Literal["gap", "map"] = "map"  # SigLIP uses MAP (attention-pool) heads
    # HF-format SigLIP has no vision projection (the MAP head output IS the
    # embedding, so embed_dim must equal width); ours defaults to a projection
    # into the shared space like open_clip.
    use_proj: bool = True
    dtype: str = "bfloat16"  # activation dtype on TPU; params stay fp32
    remat: bool = True  # jax.checkpoint each block: trade FLOPs for HBM
    scan_layers: bool = True  # lax.scan over blocks: O(1) compile in depth
    # "auto" = fused Pallas kernel for bf16 self-attention on TPU (f32 keeps the
    # dense path: the fused backward is bf16-grade), XLA dense softmax elsewhere.
    attn_impl: Literal["auto", "dense", "flash"] = "auto"
    # "nothing" = full remat; "save_hot" = save attention-core + MLP-hidden
    # activations across backward (recompute only projections/elementwise).
    remat_policy: Literal["nothing", "save_hot", "save_all_hot", "save_mlp"] = "nothing"
    # Long-context vision (high-res ViTs: 384px/14 = 729 tokens, 512px/16 =
    # 1024): shard the patch sequence over this mesh axis and run
    # sequence-parallel attention in the blocks — same contract as the text
    # tower's fields (the MAP pooling head stays sequence-global; GSPMD
    # gathers for it). The axis size must divide the patch count.
    sequence_parallel_axis: str | None = None
    sequence_parallel_impl: Literal["ring", "ulysses"] = "ring"
    # Mixture-of-experts: >0 swaps each block's dense MLP for that many experts
    # (expert weights shard over the "ep" mesh axis; see models/moe.py). Train
    # with moe_aux_weight on make_train_step so routing stays balanced.
    moe_experts: int = 0
    moe_num_selected: int = 1  # 1 = Switch top-1, 2 = top-2 with renormalized gates
    moe_capacity_factor: float = 1.25
    # Routing group size (GShard groups): capacity is per-group, keeping the
    # dispatch tensors O(tokens*E*C_group); tune down for tight HBM budgets.
    moe_group_size: int = 512
    # "int8": run the block projection matmuls (q/k/v/out/wi/wo) in dynamic
    # symmetric int8 — v5e int8 MXU peak is 2x bf16. INFERENCE ONLY (round()
    # kills gradients); make_train_step rejects quantized configs.
    quant: Literal["", "int8"] = ""
    # "int8": TRAINABLE int8 — same block projection matmuls and the same
    # dynamic symmetric recipe in the forward, but through the
    # straight-through estimator (ops/quant.py int8_dot_general_ste): backward
    # is the exact unquantized bf16/f32 VJP, so gradients flow. Embeddings,
    # layernorms, pooling heads, and the loss head stay full-precision.
    # Mutually exclusive with `quant` (see tower_quant_mode).
    quant_train: Literal["", "int8"] = ""

    @classmethod
    def vit_b16(cls, **kw) -> "ViTConfig":
        return cls(**kw)

    @classmethod
    def vit_l14(cls, **kw) -> "ViTConfig":
        return cls(patch_size=14, width=1024, depth=24, num_heads=16, **kw)

    @classmethod
    def tiny_test(cls) -> "ViTConfig":
        return cls(
            image_size=16, patch_size=8, width=32, depth=2, num_heads=2,
            embed_dim=16, dtype="float32", remat=False, scan_layers=False,
        )


@dataclasses.dataclass(frozen=True)
class TextConfig:
    """Text tower: non-causal transformer over tokenized captions (SigLIP-style)."""

    vocab_size: int = 32000
    context_length: int = 64
    width: int = 768
    depth: int = 12
    num_heads: int = 12
    mlp_ratio: int | float = 4
    embed_dim: int = 512
    # "map" = attention pooling (open_clip SigLIP); "last" = last-token hidden
    # state (HF-format SigLIP, modeling_siglip.SiglipTextTransformer).
    pool: Literal["map", "last"] = "map"
    dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    attn_impl: Literal["auto", "dense", "flash"] = "auto"
    remat_policy: Literal["nothing", "save_hot", "save_all_hot", "save_mlp"] = "nothing"
    # Long-context: shard the sequence over this mesh axis and run sequence-parallel
    # attention inside the blocks (requires an ambient mesh via jax.set_mesh).
    sequence_parallel_axis: str | None = None
    # "ring" (ppermute, O(s_local²) memory) or "ulysses" (all-to-all head scatter,
    # 2 collective hops; needs num_heads % axis_size == 0).
    sequence_parallel_impl: Literal["ring", "ulysses"] = "ring"
    causal: bool = False
    # Mixture-of-experts (see ViTConfig): >0 enables MoE MLPs in the blocks.
    moe_experts: int = 0
    moe_num_selected: int = 1
    moe_capacity_factor: float = 1.25
    moe_group_size: int = 512
    # "int8": run the block projection matmuls (q/k/v/out/wi/wo) in dynamic
    # symmetric int8 — v5e int8 MXU peak is 2x bf16. INFERENCE ONLY (round()
    # kills gradients); make_train_step rejects quantized configs.
    quant: Literal["", "int8"] = ""
    # "int8": trainable int8 via the straight-through estimator — see
    # ViTConfig.quant_train (same contract, text tower).
    quant_train: Literal["", "int8"] = ""
    # The block's make-up (BLOCK_OPTIONS below). The defaults are the SigLIP
    # block — pre-LN LayerNorm, biased projections, a tanh-GELU MLP, a learned
    # position table, every layer's weights used once — and give today's
    # parameter tree and numbers. A language-model-class text tower changes them:
    # "rmsnorm" scales by rsqrt(mean(x^2) + 1e-6), no mean and no bias.
    norm: Literal["layernorm", "rmsnorm"] = "layernorm"
    # A second norm on each sub-layer's OUTPUT, before the residual add:
    # x + norm(f(norm(x))), four norms a layer.
    sandwich_norm: bool = False
    # "swiglu" = gated MLP, three matmuls: wo(silu(wg x) * (wi x)); "relu2" =
    # ungated, two: wo(relu(wi x)^2). With ``moe_router="sigmoid"`` it is the
    # routed and shared experts' kind too ("swiglu" or "relu2").
    mlp: Literal["gelu", "swiglu", "relu2"] = "gelu"
    # False drops the bias of the blocks' attention and MLP projections.
    use_bias: bool = True
    # "rope" = rotary positions on q and k (rotate-half convention, positions
    # 0..s-1, base rope_theta) in place of the learned ``pos_embed`` table: on
    # the whole head of an "attn" layer, on the ``mla_qk_shared_dim`` wide parts
    # of an "mla" layer; "none" = neither (causal mixers carry the order).
    pos: Literal["learned", "rope", "none"] = "learned"
    rope_theta: float = 10000.0
    # > 1 runs the whole stack (its ``depth`` layers, then the final norm) this
    # many times on ONE set of weights, each pass feeding the next; the
    # embedding is pooled from the last pass. Each weight's gradient is the sum
    # over its uses.
    loops: int = 1
    # The normalisations' epsilon (LayerNorm and RMSNorm alike).
    norm_eps: float = 1e-6
    # "rmsnorm" with its leaf stored as an offset from 1: x rsqrt(mean(x^2) +
    # eps) (1 + offset), the leaf ``offset`` initially 0. Weight decay then pulls
    # the scale to 1 and not to 0: another training step, not another name.
    norm_unit_offset: bool = False
    # A stack of several layer kinds in one order: the token mixer of each of
    # the ``depth`` layers, "attn" (the block's softmax attention), "kda" (a
    # chunked gated delta rule behind a short causal convolution,
    # ops/gated_delta_rule.py) or "mla" (latent attention: keys and values
    # expanded from one low-rank latent, a key part shared by all heads, value
    # heads of their own width; models/mixers.py) or "eva" (softmax attention
    # that is exact inside ``eva_window`` tokens and reads every earlier window
    # as one pooled key and value per ``eva_chunk`` tokens, under one softmax) or
    # "ssm" (a Mamba-2 state-space layer: a scalar-decay recurrence behind a short
    # causal convolution, under a gated group norm; ops/ssm.py).
    # Empty = "attn" in every layer, today's stack. Such a stack is causal and
    # takes ``pos="none"`` (or "rope" where no layer is a recurrence; "eva"
    # takes "rope" alone). Unlike layers run unrolled with remat per layer (no
    # two neighbours share a parameter tree to scan over, so ``scan_layers``
    # does not apply to them); layers that are all alike are one stack, scanned
    # where ``scan_layers`` says, whatever their mixer.
    mixers: tuple[str, ...] = ()
    # The first layers keep the dense MLP where ``moe_experts > 0``.
    leading_dense_layers: int = 0
    # "kda": key and value head size (``num_heads`` heads; the low-rank gates
    # pass through this many channels too) and the convolution's kernel size.
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    # "mla": per head a key part expanded from the latent and a key part shared
    # by all heads (queries are their sum wide), the value head, the latent.
    mla_qk_nope_dim: int = 128
    mla_qk_shared_dim: int = 64
    mla_v_dim: int = 128
    mla_kv_rank: int = 512
    # > 0: the queries come through a latent too, x Wqa -> RMSNorm -> Wqb, this
    # many channels wide; 0 = one projection.
    mla_q_rank: int = 0
    # "eva": the tokens of a window (exact, causal attention inside it; the
    # context is whole windows) and of a chunk (one summary each; a window is
    # whole chunks).
    eva_window: int = 2048
    eva_chunk: int = 16
    # "sigmoid" = the router of the latent-attention language models: scores
    # sigmoid(x Wr) in float32, the ``moe_num_selected`` largest of scores + a
    # selection bias (a leaf that takes no gradient, decay or optimizer state),
    # weights = the chosen scores renormalised x ``moe_route_scale``; bias-free
    # SwiGLU experts ``moe_hidden`` wide, ``moe_shared_experts`` more that every
    # token runs; dispatched by a sort over the held experts' assignments with
    # no capacity: no token is dropped whatever the imbalance. "softmax" is the
    # capacity-dropping GShard layer (k in {1, 2}, biased GELU experts).
    moe_router: Literal["softmax", "sigmoid"] = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_experts: int = 0
    moe_hidden: int = 0  # 0 = round(width * mlp_ratio), the dense MLP's
    # The chip's share of the routed experts, experts 0..held-1 (0 = all): the
    # router keeps its ``moe_experts`` outputs and its top-k, this chip computes
    # its own experts' part and leaves out what the absent ones would add.
    moe_experts_held: int = 0
    # The shared expert's own hidden width (one expert that wide, of the routed
    # experts' kind); 0 = ``moe_shared_experts`` x ``moe_hidden``.
    moe_shared_hidden: int = 0
    # True: initialisation ends by setting every sigmoid router's selection bias
    # to where the recipe's balancing update holds a trained router: on the sample
    # batch's token ids (uniform ids where ``create_train_state`` is given shapes
    # alone) every expert is chosen by tokens x ``moe_num_selected`` /
    # ``moe_experts`` tokens (train/train_step.py balance_routers). False leaves
    # the bias zero: routers drawn at random route as unevenly as they fall. The
    # step is the same either way.
    moe_balanced_init: bool = False
    # An "attn" layer's key / value heads (0 = ``num_heads``: every query head its
    # own; else query head h reads key / value head h // (num_heads /
    # num_kv_heads)) and its head size (0 = ``width // num_heads``; else the
    # projections are width -> heads x head_dim and back).
    num_kv_heads: int = 0
    head_dim: int = 0
    # Each layer's window, one number a layer (empty = none anywhere): 0 = a full
    # causal layer; w > 0 = a window layer, whose query t reads keys t - w + 1 .. t
    # (w keys, the token itself among them). "attn" layers of a causal tower only.
    attn_windows: tuple[int, ...] = ()
    # Which "attn" layers ``pos="rope"`` rotates: "all", or "window": the layers
    # with a window alone, a full layer then taking no position at all.
    rope_layers: Literal["all", "window"] = "all"
    # An "attn" layer's two options: an RMSNorm (``norm_eps``) over the ``head_dim``
    # lanes of every head of q and of k, one scale each shared by the heads, before
    # the rotation; and a gate, a fifth projection width -> heads x head_dim whose
    # sigmoid multiplies the heads' outputs before the output projection.
    attn_qk_norm: bool = False
    attn_gate: bool = False
    # The token embedding's output times this, before the first layer (muP:
    # sqrt(width)).
    embed_scale: float = 1.0
    # "pair" = every layer is a token mixer AND a feed-forward part, each under
    # its own norm (every block so far). "single" = every layer is ONE sub-layer,
    # x + f(norm(x)) under one norm: ``mixers`` then names each layer's f, a
    # mixer alone ("ssm", "attn": no feed-forward part) or the routed
    # feed-forward part alone ("moe": no mixer). "ssm" is a layer of such a
    # stack only.
    sublayers: Literal["pair", "single"] = "pair"
    # "ssm" (a Mamba-2 state-space layer, models/mixers.py SsmMixer): heads of
    # ``ssm_head_dim`` channels in ``ssm_groups`` groups that share B and C of
    # ``ssm_state`` channels, behind a causal depthwise convolution of
    # ``ssm_conv_size`` taps; ``ssm_chunk`` tokens a chunk of the scan (how the
    # recurrence is computed, not what it computes: ops/ssm.py).
    ssm_num_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_conv_size: int = 4
    ssm_chunk: int = 128

    def __post_init__(self):
        # A configuration file gives a list; modules hash their configuration.
        object.__setattr__(self, "mixers", tuple(self.mixers))
        object.__setattr__(self, "attn_windows", tuple(self.attn_windows))

    @classmethod
    def base(cls, **kw) -> "TextConfig":
        return cls(**kw)

    @classmethod
    def tiny_test(cls) -> "TextConfig":
        return cls(
            vocab_size=64, context_length=8, width=32, depth=2, num_heads=2,
            embed_dim=16, dtype="float32", remat=False, scan_layers=False,
        )


# TextConfig's block options with the value each has in the SigLIP block. Code
# that re-implements or maps that block (parallel/pp_towers.py,
# models/hf_import.py) refuses any other value by the option's name.
BLOCK_OPTIONS = {
    "norm": "layernorm", "sandwich_norm": False, "mlp": "gelu", "use_bias": True,
    "pos": "learned", "loops": 1, "norm_eps": 1e-6, "mixers": (),
    "leading_dense_layers": 0, "moe_router": "softmax", "moe_route_scale": 1.0,
    "moe_shared_experts": 0, "moe_hidden": 0, "moe_experts_held": 0, "mla_q_rank": 0,
    "norm_unit_offset": False, "moe_shared_hidden": 0, "num_kv_heads": 0, "head_dim": 0,
    "sublayers": "pair", "attn_windows": (), "rope_layers": "all", "attn_qk_norm": False, "attn_gate": False,
    "embed_scale": 1.0,
}


def changed_block_options(cfg: "ViTConfig | TextConfig") -> list[str]:
    """The block options ``cfg`` sets away from the SigLIP block, as
    ``name=value`` for a refusal's message (a ViTConfig has none)."""
    return [
        f"{k}={getattr(cfg, k)!r}" for k, v in BLOCK_OPTIONS.items() if getattr(cfg, k, v) != v
    ]


def tower_quant_mode(cfg: "ViTConfig | TextConfig") -> str:
    """THE quant-mode resolution for a tower config, shared by the live towers
    (models/vit.py, models/text.py) and the pipelined forward
    (parallel/pp_towers.py) so the three can never disagree on which dot a
    config injects. Returns ``""`` (full precision), ``"int8"``
    (inference-only dynamic int8), or ``"int8_ste"`` (trainable
    straight-through int8); raises when both flags are set — one tower cannot
    run two quantization recipes at once.
    """
    if cfg.quant and cfg.quant_train:
        raise ValueError(
            f"quant={cfg.quant!r} and quant_train={cfg.quant_train!r} are "
            "mutually exclusive: pick the inference recipe (quant) or the "
            "trainable STE recipe (quant_train)"
        )
    if cfg.quant_train:
        return "int8_ste"
    if cfg.quant:
        return "int8"
    return ""


@dataclasses.dataclass(frozen=True)
class SigLIPConfig:
    vision: ViTConfig = dataclasses.field(default_factory=ViTConfig)
    text: TextConfig = dataclasses.field(default_factory=TextConfig)
    loss: LossConfig = dataclasses.field(default_factory=LossConfig)

    @classmethod
    def b16(cls) -> "SigLIPConfig":
        return cls()

    @classmethod
    def l14(cls, **vision_kw) -> "SigLIPConfig":
        """ViT-L/14 + width-1024 text tower (BASELINE.json config #5). The single
        source of truth for the L/14 pairing: the CLI builds from here."""
        return cls(
            vision=ViTConfig.vit_l14(**vision_kw),
            text=TextConfig(width=1024, num_heads=16),
        )

    @classmethod
    def so400m(cls) -> "SigLIPConfig":
        """SoViT-400m/14 — the shape-optimized flagship of the SigLIP release
        (google/siglip-so400m-patch14-224), HF-shaped so `models.hf_import` weights
        drop in: no vision projection, last-token text pooling, fractional MLP."""
        return cls(
            vision=ViTConfig(
                patch_size=14, width=1152, depth=27, num_heads=16,
                mlp_ratio=4304 / 1152, embed_dim=1152, use_proj=False,
            ),
            text=TextConfig(
                width=1152, depth=27, num_heads=16, mlp_ratio=4304 / 1152,
                embed_dim=1152, pool="last",
            ),
        )

    @classmethod
    def tiny_test(cls) -> "SigLIPConfig":
        return cls(vision=ViTConfig.tiny_test(), text=TextConfig.tiny_test())


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-3
    weight_decay: float = 1e-4
    warmup_steps: int = 2000
    total_steps: int = 100_000
    b1: float = 0.9
    b2: float = 0.95
    global_batch: int = 4096
    # "warmup_cosine" (open_clip default), "rsqrt" (the SigLIP paper's inverse
    # sqrt with linear warmup — total_steps-free, for open-ended pretraining),
    # or "constant" (after warmup).
    schedule: Literal["warmup_cosine", "rsqrt", "constant"] = "warmup_cosine"
    # Dtype of Adam's first moment (None = param dtype, f32). "bfloat16" halves
    # the larger moment buffer — ~1.75 GB on so400m — the cheap end of the
    # optimizer-memory ladder before ZeRO-1; the second moment stays f32 (its
    # wide dynamic range is what bf16's 8 mantissa bits lose first).
    adam_mu_dtype: str | None = None
    # Optimizer family. "adamw" is the contrastive-pretraining default;
    # "lion" stores ONE momentum slot (half adam's state — pairs well with
    # mu_dtype bf16 for a 4x optimizer-memory cut; prefers ~3-10x smaller lr
    # and ~3x larger weight_decay than adamw); "adafactor" stores factored
    # second moments (rows+cols instead of a full matrix per kernel — the
    # biggest-model memory option; b1/b2/adam_mu_dtype are ignored).
    optimizer: Literal["adamw", "lion", "adafactor"] = "adamw"
