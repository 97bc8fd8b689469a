"""Transformer LM — the framework's flagship sharded model family.

The reference's largest model is an MLP; this module is where the TPU
framework goes beyond it: a decoder-only transformer expressed as a pure
function over an explicit parameter pytree with a *sharding-spec pytree*
alongside, so the same code runs

- single-chip (all specs replicated),
- tensor-parallel (Megatron-style: attention heads and MLP hidden sharded
  over the ``model`` axis; XLA inserts the psum where activations re-enter
  the replicated residual stream),
- data-parallel (batch over ``data``), and
- sequence-parallel for long context (``seq`` axis +
  :func:`~elephas_tpu.ops.ring_attention.ring_attention_sharded`).

bfloat16 activations/matmuls by default: MXU-native, half the HBM traffic
of f32; parameters and the softmax/loss stay f32 for stability.
"""
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import grouped_experts as _gx
from . import mamba2 as _mamba
from . import mla as _mla
from .mamba2 import Mamba2Mixer  # noqa: F401  (part of the config's surface)
from .mla import YarnScaling  # noqa: F401  (part of the config's surface)
from ..ops.attention import NEG_INF, attention
from ..ops.pallas_attention import flash_attention, flash_attention_sharded
from ..ops.ring_attention import ring_attention_sharded


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """Fixed scalar multipliers a model was trained with (maximal-update
    parametrisation, Falcon-H1's ``*_multiplier`` keys), each applied
    where its name says: to the embedding's output, the logits,
    attention's input, output and keys, the mixer's input, its five
    projection segments ``[z | x | B | C | dt]`` and its output, and the
    gated MLP's gate (before the activation) and output."""
    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    ssm_out: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0

    def __post_init__(self):
        if len(self.ssm) != 5:
            raise ValueError("ssm takes five multipliers, one a segment "
                             "of the mixer's projection: z, x, B, C, dt")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: single-device attention implementation: ``auto`` picks the Pallas
    #: flash kernel on TPU and the XLA-fused path elsewhere; ``flash`` /
    #: ``xla`` force one. Ring attention (mesh + seq_axis) overrides this.
    attention_impl: str = "auto"
    #: mixture-of-experts MLP: >1 replaces every dense MLP block with
    #: ``num_experts`` gated experts sharded over the ``model`` mesh axis
    #: (expert parallelism — each device owns E/tp experts and XLA
    #: all-reduces the combined output back into the residual stream)
    num_experts: int = 0
    #: tokens route to the top-k experts. k=1 is Switch-style (output
    #: scaled by the raw softmax probability, keeping router gradient
    #: alive); k>1 renormalizes the selected probabilities (Mixtral-style)
    expert_top_k: int = 2
    #: weight of the load-balancing auxiliary loss (Switch eq. 4) added to
    #: the LM loss — 0 disables it
    moe_aux_weight: float = 0.01
    #: expert dispatch: ``dense`` computes every expert for every token and
    #: lets the gate zero the rest (static shapes, exact, FLOPs scale with
    #: ``num_experts``); ``routed`` scatters tokens into per-expert
    #: capacity buffers so FLOPs scale with ``expert_top_k`` (tokens over
    #: capacity are dropped, Switch-style); ``auto`` picks routed for
    #: large expert counts and dense for tiny ones / expert-sharded meshes
    moe_dispatch: str = "auto"
    #: routed-dispatch expert capacity = ``ceil(capacity_factor * top_k *
    #: tokens / num_experts)`` — 1.0 is exact-balance, >1 gives headroom
    moe_capacity_factor: float = 1.25
    #: DeepSeek-MoE style shared expert: one always-on dense MLP whose
    #: output adds to the routed combine — captures common knowledge so
    #: the routed experts can specialize; replicated like a dense MLP
    moe_shared_expert: bool = False
    #: rematerialize each block's activations in the backward pass
    #: (``jax.checkpoint`` per layer): trades ~1/3 more FLOPs for
    #: activation memory that stays O(1) in depth — the standard TPU
    #: HBM trade for long sequences / deep stacks
    remat: bool = False
    #: remat granularity: ``full`` recomputes everything in the block;
    #: ``dots`` saves matmul outputs and recomputes only the cheap
    #: elementwise work (jax ``dots_saveable`` policy — much less
    #: recompute for a fraction of full remat's memory win)
    remat_policy: str = "full"
    #: position encoding: ``learned`` adds a trained (max_seq_len, d)
    #: table at the embedding; ``rope`` rotates q/k per layer (RoFormer)
    #: — relative positions, no length-bound table, the standard choice
    #: for long-context models; ``sinusoidal`` is the original
    #: parameter-free sin/cos table (Vaswani et al.); ``alibi`` adds the
    #: per-head linear distance penalty (Press et al.) — parameter-free,
    #: strong length extrapolation, forces the xla attention path
    positional: str = "learned"
    #: weight of the z-loss term ``mean(logsumexp(logits)^2)`` (PaLM §5):
    #: keeps logits from drifting large, which stabilizes bf16 training
    #: at scale — 0 disables it (1e-4 is the usual setting)
    z_loss_weight: float = 0.0
    #: RoPE base frequency (10000 is the RoFormer default; larger bases
    #: extend usable context)
    rope_theta: float = 10000.0
    #: sliding-window attention (Mistral style): each position attends
    #: to at most the last ``attention_window`` keys (itself included).
    #: None = full causal context. Decode keeps an O(window) effective
    #: read set; the xla path applies the band mask, the flash kernel
    #: skips out-of-band tiles in-kernel, and the ring path skips whole
    #: out-of-band hops statically (windowed sequence parallelism
    #: composes)
    attention_window: Optional[int] = None
    #: int8 KV cache for decoding: cache entries store int8 with a
    #: per-(position, head) absmax scale — long-context decode re-reads
    #: the whole cache every step, so int8 halves that HBM traffic
    #: (composes with GQA and weight-only int8 serving)
    kv_cache_quant: bool = False
    #: MLP variant: ``gelu`` (GPT-2 style, w1/w2) or ``swiglu`` (Llama
    #: style: SiLU(x@w1) * (x@w3) @ w2 — the gated unit that wins at
    #: equal parameter count, Shazeer 2020). Dense blocks only; MoE
    #: experts keep gelu
    mlp_variant: str = "gelu"
    #: normalization: ``layernorm`` (mean+variance, learned beta) or
    #: ``rmsnorm`` (scale-only, no centering — cheaper and the modern
    #: default, Zhang & Sennrich 2019)
    norm: str = "layernorm"
    #: flash-attention tile sizes (None = the kernel defaults, 256/512).
    #: The best tiles move with sequence length — the seq-scaling bench
    #: measured block_q=512, block_k=1024 fastest for seq >= 2k — so the
    #: MFU ablation row sweeps these on-chip
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    #: tie the LM head to the token embedding (GPT-2 style, the
    #: default); False gives the head its own (d_model, vocab) matrix —
    #: common at larger scales where input/output roles diverge
    tied_embedding: bool = True
    #: label smoothing for the LM cross-entropy: eps mass spreads
    #: uniformly over the vocab (Szegedy et al.; standard for seq2seq /
    #: large-LM training) — 0 disables
    label_smoothing: float = 0.0
    #: residual dropout (GPT-2 scheme): applied to each attention and
    #: MLP sublayer output before it re-enters the residual stream —
    #: active only when a ``dropout_key`` reaches the forward pass
    #: (training); inference/generate paths never drop
    dropout_rate: float = 0.0
    #: chunked-vocab LM loss: when set, the training loss streams the
    #: logsumexp over vocab chunks of this size inside a rematerialized
    #: ``lax.scan`` instead of materializing the full ``(batch, seq,
    #: vocab)`` f32 logits (1 GB at vocab 32k, batch 8, seq 1024) — the
    #: standard large-vocab HBM trade. Applies when the embedding is not
    #: vocab-sharded (single device / pure dp); tensor-parallel meshes
    #: already spread the logits over the model axis and keep the dense
    #: path. Inference/generate paths are unaffected.
    loss_vocab_chunk: Optional[int] = None
    #: grouped-query attention: number of key/value heads. ``None`` means
    #: ``num_heads`` (standard multi-head); ``1`` is multi-query (MQA).
    #: Each group of ``num_heads / num_kv_heads`` query heads shares one
    #: k/v head — kv-projection FLOPs and (decisively) the decode KV
    #: cache shrink by that factor while attention quality stays close to
    #: full MHA (GQA, Ainslie et al. 2023)
    num_kv_heads: Optional[int] = None
    #: RMSNorm's epsilon (``norm='rmsnorm'``; LayerNorm keeps 1e-5)
    rms_norm_eps: float = 1e-5
    #: attention kind of every layer: ``mha`` (multi-head / grouped-query
    #: with per-head k/v in the cache) or ``mla`` (multi-head latent
    #: attention, :mod:`~elephas_tpu.models.mla`: low-rank query and
    #: key/value projections, and a cache of ONE latent vector of
    #: ``kv_lora_rank + qk_rope_head_dim`` values a position). ``mla``
    #: takes its sizes from the five fields below, rotates only the rope
    #: dims (``rope_theta``, ``rope_scaling``) and ignores ``positional``
    #: (set it to ``rope``), ``num_kv_heads`` and ``attention_window``
    attention_kind: str = "mha"
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    #: YaRN scaling of the ``mla`` rope dims (``YarnScaling``); None is
    #: plain RoPE
    rope_scaling: Optional[_mla.YarnScaling] = None
    #: MLP kind of each layer, ``"dense"`` or ``"experts"``, one entry a
    #: layer (leading dense layers, then expert layers). None: every
    #: layer has experts when ``num_experts`` > 1, else is dense
    mlp_kinds: Optional[Tuple[str, ...]] = None
    #: expert kind: ``gelu`` (ungated, biased, dense or capacity-routed
    #: dispatch per ``moe_dispatch``, optional gelu shared expert) or
    #: ``swiglu`` (:mod:`~elephas_tpu.models.grouped_experts`: gated, no
    #: bias, width ``expert_d_ff``, grouped matmuls over sorted tokens,
    #: no token ever dropped, shared SwiGLU of width ``shared_d_ff``)
    expert_variant: str = "gelu"
    expert_d_ff: Optional[int] = None
    shared_d_ff: int = 0
    #: ``swiglu`` experts route ``group_limited_greedy``: the
    #: ``expert_top_k`` best among the ``moe_topk_groups`` best of
    #: ``moe_n_groups`` groups, scaled by ``routed_scaling_factor``, not
    #: renormalised (the defaults, one group, are plain top-k)
    moe_n_groups: int = 1
    moe_topk_groups: int = 1
    routed_scaling_factor: float = 1.0
    #: the experts this rank holds, ``(first, count)`` out of the
    #: router's ``num_experts`` (``swiglu`` experts only; None: all). The
    #: layer routes over every expert and computes its own experts' part
    held_experts: Optional[Tuple[int, int]] = None
    #: width of an attention head where it is not ``d_model / num_heads``
    #: (a model whose attention is narrower than its residual stream)
    attention_head_dim: Optional[int] = None
    #: a state-space mixer (:mod:`~elephas_tpu.models.mamba2`) beside the
    #: attention of EVERY layer, on the same normed input, their outputs
    #: added (a hybrid block); it keeps per-slot state
    #: (:meth:`state_leaves`) beside the per-position cache. ``mha``
    #: attention and dense MLPs only. None: attention alone
    ssm: Optional[Mamba2Mixer] = None
    #: ``Multipliers`` the model applies at fixed places; None: none, and
    #: every program is what it is without this field
    multipliers: Optional[Multipliers] = None

    def __post_init__(self):
        if self.attention_impl not in ("auto", "flash", "xla"):
            raise ValueError("attention_impl must be 'auto', 'flash' or "
                             f"'xla', got {self.attention_impl!r}")
        if self.num_experts > 1 and not (
                1 <= self.expert_top_k <= self.num_experts):
            raise ValueError("expert_top_k must be in [1, num_experts]")
        if self.moe_dispatch not in ("auto", "dense", "routed"):
            raise ValueError("moe_dispatch must be 'auto', 'dense' or "
                             f"'routed', got {self.moe_dispatch!r}")
        if self.moe_capacity_factor <= 0:
            raise ValueError("moe_capacity_factor must be positive")
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError("dropout_rate must be in [0, 1)")
        if not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError("label_smoothing must be in [0, 1)")
        if self.attention_window is not None and self.attention_window < 1:
            raise ValueError("attention_window must be >= 1")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError("remat_policy must be 'full' or 'dots', "
                             f"got {self.remat_policy!r}")
        if self.mlp_variant not in ("gelu", "swiglu"):
            raise ValueError("mlp_variant must be 'gelu' or 'swiglu', "
                             f"got {self.mlp_variant!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError("norm must be 'layernorm' or 'rmsnorm', "
                             f"got {self.norm!r}")
        if self.positional not in ("learned", "rope", "sinusoidal",
                                   "alibi"):
            raise ValueError(
                "positional must be 'learned', 'rope', 'sinusoidal' or "
                f"'alibi', got {self.positional!r}")
        if self.positional == "rope" and self.head_dim % 2:
            raise ValueError("rope requires an even head_dim")
        if self.ssm is not None and (
                self.attention_kind != "mha" or self.kv_cache_quant
                or self.num_experts > 1):
            raise ValueError("a state-space mixer (ssm) runs beside mha "
                             "attention and a dense MLP, without "
                             "kv_cache_quant")
        if self.multipliers is not None and self.loss_vocab_chunk:
            raise ValueError("loss_vocab_chunk streams the head without "
                             "multipliers.lm_head: not with multipliers")
        if self.num_kv_heads is not None and (
                self.num_kv_heads < 1
                or self.num_heads % self.num_kv_heads):
            raise ValueError(
                f"num_kv_heads ({self.num_kv_heads}) must divide "
                f"num_heads ({self.num_heads})")
        if self.attention_kind not in ("mha", "mla"):
            raise ValueError("attention_kind must be 'mha' or 'mla', got "
                             f"{self.attention_kind!r}")
        if self.attention_kind == "mla":
            sizes = (self.q_lora_rank, self.kv_lora_rank,
                     self.qk_nope_head_dim, self.qk_rope_head_dim,
                     self.v_head_dim)
            if any(v is None or v < 1 for v in sizes):
                raise ValueError(
                    "attention_kind='mla' needs q_lora_rank, kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even")
            if self.kv_cache_quant or self.attention_window is not None:
                raise ValueError("attention_kind='mla' composes with "
                                 "neither kv_cache_quant nor "
                                 "attention_window")
        if self.mlp_kinds is not None and (
                len(self.mlp_kinds) != self.num_layers
                or any(k not in ("dense", "experts")
                       for k in self.mlp_kinds)
                or ("experts" in self.mlp_kinds
                    and self.num_experts < 2)):
            raise ValueError(
                "mlp_kinds needs one of 'dense' / 'experts' per layer "
                f"(and num_experts > 1 for 'experts'), got "
                f"{self.mlp_kinds!r}")
        if self.expert_variant not in ("gelu", "swiglu"):
            raise ValueError("expert_variant must be 'gelu' or 'swiglu', "
                             f"got {self.expert_variant!r}")
        swiglu = self.expert_variant == "swiglu"
        if swiglu and self.moe_shared_expert:
            raise ValueError("moe_shared_expert is the gelu experts' "
                             "shared expert; swiglu experts take "
                             "shared_d_ff")
        if not swiglu and (self.held_experts is not None
                           or self.shared_d_ff
                           or (self.moe_n_groups, self.moe_topk_groups,
                               self.routed_scaling_factor) != (1, 1, 1.0)):
            raise ValueError("held_experts, shared_d_ff, moe_n_groups, "
                             "moe_topk_groups and routed_scaling_factor "
                             "belong to expert_variant='swiglu'")
        if swiglu and self.num_experts > 1:
            if not self.expert_d_ff or self.expert_d_ff < 1:
                raise ValueError("swiglu experts need expert_d_ff")
            if (self.moe_n_groups < 1
                    or self.num_experts % self.moe_n_groups
                    or not 1 <= self.moe_topk_groups <= self.moe_n_groups
                    or self.moe_topk_groups * self.num_experts
                    // self.moe_n_groups < self.expert_top_k):
                raise ValueError(
                    "group-limited routing needs moe_n_groups dividing "
                    "num_experts and moe_topk_groups groups that hold at "
                    "least expert_top_k experts")
            if self.held_experts is not None:
                first, count = self.held_experts
                if not (0 <= first and count >= 1
                        and first + count <= self.num_experts):
                    raise ValueError(
                        f"held_experts {self.held_experts!r} is no range "
                        f"of the {self.num_experts} experts")

    @property
    def head_dim(self) -> int:
        if self.attention_head_dim is not None:
            return self.attention_head_dim
        return self.d_model // self.num_heads

    def has_experts(self, layer: int) -> bool:
        """Whether layer ``layer``'s MLP is an expert layer."""
        if self.mlp_kinds is not None:
            return self.mlp_kinds[layer] == "experts"
        return self.num_experts > 1

    def cache_leaves(self) -> Dict[str, Tuple[int, int]]:
        """What one layer caches per position, ``{leaf: (heads,
        width)}``: the one place that says how the decode cache is laid
        out. Every cache leaf is ``(batch | blocks, heads, positions,
        width)``; :func:`init_kv_cache` and the paged pool are built
        from this and everything that moves cache blocks maps over the
        leaves."""
        if self.attention_kind == "mla":
            return {"latent": (1, _mla.latent_width(self))}
        return {"k": (self.kv_heads, self.head_dim),
                "v": (self.kv_heads, self.head_dim)}

    def state_leaves(self) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
        """What one layer keeps per SLOT and not per position, ``{leaf:
        (shape, dtype)}``: the mixer's ``conv`` inputs and ``ssm`` state
        for a config with ``ssm``, nothing otherwise. A decode cache
        holds them under ``cache["state"]["layer_i"]`` with a leading
        rows (or slots) axis, beside the per-position leaves."""
        if self.ssm is None:
            return {}
        return _mamba.state_leaves(self.ssm, self.dtype)

    @property
    def kv_heads(self) -> int:
        """Effective number of key/value heads (GQA group count)."""
        return (self.num_kv_heads if self.num_kv_heads is not None
                else self.num_heads)


def init_params(config: TransformerConfig, key) -> Dict:
    """Initialize the parameter pytree."""
    c = config
    keys = jax.random.split(key, 2 + c.num_layers)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, c.param_dtype)
                / math.sqrt(fan_in))

    embed: Dict[str, Any] = {
        "tokens": 0.02 * jax.random.normal(
            keys[0], (c.vocab_size, c.d_model), c.param_dtype),
    }
    if c.positional == "learned":
        embed["pos"] = 0.02 * jax.random.normal(
            keys[1], (c.max_seq_len, c.d_model), c.param_dtype)
    params: Dict[str, Any] = {
        "embed": embed,
        "final_ln": {"gamma": jnp.ones((c.d_model,), c.param_dtype),
                     "beta": jnp.zeros((c.d_model,), c.param_dtype)},
    }
    if not c.tied_embedding:
        params["head"] = dense(jax.random.fold_in(keys[0], 1),
                               (c.d_model, c.vocab_size), c.d_model)
    for i in range(c.num_layers):
        lk = jax.random.split(keys[2 + i], 7)
        if c.attention_kind == "mla":
            attn = _mla.init_attn(c, jax.random.split(lk[0], 5), dense)
        else:
            attn = {
                "wq": dense(lk[0], (c.d_model, c.num_heads, c.head_dim), c.d_model),
                "wk": dense(lk[1], (c.d_model, c.kv_heads, c.head_dim), c.d_model),
                "wv": dense(lk[2], (c.d_model, c.kv_heads, c.head_dim), c.d_model),
                "wo": dense(lk[3], (c.num_heads, c.head_dim, c.d_model),
                            c.num_heads * c.head_dim),
            }
        layer = {
            "ln1": {"gamma": jnp.ones((c.d_model,), c.param_dtype),
                    "beta": jnp.zeros((c.d_model,), c.param_dtype)},
            "attn": attn,
            "ln2": {"gamma": jnp.ones((c.d_model,), c.param_dtype),
                    "beta": jnp.zeros((c.d_model,), c.param_dtype)},
        }
        if c.ssm is not None:
            layer["ssm"] = _mamba.init_mixer(
                c, jax.random.fold_in(lk[0], 1), dense)
        if c.has_experts(i) and c.expert_variant == "swiglu":
            layer["moe"] = _gx.init_experts(
                c, jax.random.split(lk[4], 7), dense)
        elif c.has_experts(i):
            layer["moe"] = {
                "gate": dense(lk[6], (c.d_model, c.num_experts), c.d_model),
                "w1": dense(lk[4], (c.num_experts, c.d_model, c.d_ff),
                            c.d_model),
                "b1": jnp.zeros((c.num_experts, c.d_ff), c.param_dtype),
                "w2": dense(lk[5], (c.num_experts, c.d_ff, c.d_model), c.d_ff),
                "b2": jnp.zeros((c.num_experts, c.d_model), c.param_dtype),
            }
            if c.moe_shared_expert:
                sk = jax.random.split(lk[6], 3)
                layer["moe"]["shared"] = {
                    "w1": dense(sk[1], (c.d_model, c.d_ff), c.d_model),
                    "b1": jnp.zeros((c.d_ff,), c.param_dtype),
                    "w2": dense(sk[2], (c.d_ff, c.d_model), c.d_ff),
                    "b2": jnp.zeros((c.d_model,), c.param_dtype),
                }
        else:
            layer["mlp"] = {
                "w1": dense(lk[4], (c.d_model, c.d_ff), c.d_model),
                "b1": jnp.zeros((c.d_ff,), c.param_dtype),
                "w2": dense(lk[5], (c.d_ff, c.d_model), c.d_ff),
                "b2": jnp.zeros((c.d_model,), c.param_dtype),
            }
            if c.mlp_variant == "swiglu":
                layer["mlp"]["w3"] = dense(jax.random.fold_in(lk[4], 1),
                                           (c.d_model, c.d_ff), c.d_model)
        params[f"layer_{i}"] = layer
    return params


def param_specs(config: TransformerConfig, model_axis: str = "model",
                mesh: Optional[Mesh] = None) -> Dict:
    """Megatron-style tensor-parallel PartitionSpecs mirroring init_params.

    qkv projections shard the head axis; the output projection and MLP
    down-projection shard their contracting dimension, so each block needs
    exactly one all-reduce (inserted by XLA) where it re-enters the
    residual stream.

    GQA configs shard the (smaller) k/v head axis the same way when it
    divides the tensor-parallel degree; otherwise (e.g. MQA's single kv
    head on tp=2) wk/wv replicate — pass ``mesh`` so the divisibility is
    known (the mesh-blind default assumes divisible).
    """
    def div(dim):
        return mesh is None or _mesh_divides(mesh, model_axis, dim)

    kv_spec = (P(None, model_axis, None) if div(config.kv_heads)
               else P(None, None, None))
    # every sharded dim falls back to replicated when it does not divide
    # the model axis (same rule across the model families)
    h_ax = model_axis if div(config.num_heads) else None
    ff_ax = model_axis if div(config.d_ff) else None
    v_ax = model_axis if div(config.vocab_size) else None
    e_ax = (model_axis
            if div(config.num_experts if config.num_experts > 1 else 1)
            else None)
    embed_specs: Dict[str, Any] = {"tokens": P(v_ax, None)}
    if config.positional == "learned":
        embed_specs["pos"] = P(None, None)
    specs: Dict[str, Any] = {
        "embed": embed_specs,
        "final_ln": {"gamma": P(None), "beta": P(None)},
    }
    if not config.tied_embedding:
        specs["head"] = P(None, v_ax)
    for i in range(config.num_layers):
        layer_specs = {
            "ln1": {"gamma": P(None), "beta": P(None)},
            "attn": (_mla.attn_specs(P, h_ax)
                     if config.attention_kind == "mla" else {
                         "wq": P(None, h_ax, None),
                         "wk": kv_spec,
                         "wv": kv_spec,
                         "wo": P(h_ax, None, None),
                     }),
            "ln2": {"gamma": P(None), "beta": P(None)},
        }
        if config.ssm is not None:
            layer_specs["ssm"] = _mamba.mixer_specs(P)
        if config.has_experts(i) and config.expert_variant == "swiglu":
            held = _gx.held_range(config)[1]
            layer_specs["moe"] = _gx.expert_specs(
                config, P, model_axis if div(held) else None, ff_ax)
        elif config.has_experts(i):
            # expert parallelism: the expert dimension shards over the
            # model axis, so each device holds and computes E/tp experts;
            # the gate is replicated and XLA all-reduces the weighted
            # combine back into the (replicated) residual stream
            layer_specs["moe"] = {
                "gate": P(None, None),
                "w1": P(e_ax, None, None),
                "b1": P(e_ax, None),
                "w2": P(e_ax, None, None),
                "b2": P(e_ax, None),
            }
            if config.moe_shared_expert:
                # the shared expert shards like a dense Megatron MLP
                layer_specs["moe"]["shared"] = {
                    "w1": P(None, ff_ax), "b1": P(ff_ax),
                    "w2": P(ff_ax, None), "b2": P(None)}
        else:
            layer_specs["mlp"] = {"w1": P(None, ff_ax),
                                  "b1": P(ff_ax),
                                  "w2": P(ff_ax, None), "b2": P(None)}
            if config.mlp_variant == "swiglu":
                # the gate shards its output dim like w1 (elementwise
                # product stays local to the model shard)
                layer_specs["mlp"]["w3"] = P(None, ff_ax)
        specs[f"layer_{i}"] = layer_specs
    return specs


def _mesh_divides(mesh: Mesh, axis: Optional[str], dim: int) -> bool:
    """True when ``dim`` splits evenly over mesh axis ``axis`` (vacuously
    true for axis=None) — the shard_map divisibility precondition."""
    if axis is None:
        return True
    size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis)
    return size is not None and dim % size == 0


def select_attention_impl(config: TransformerConfig, mesh: Optional[Mesh],
                          seq_axis: Optional[str], batch_axis: Optional[str],
                          model_axis: Optional[str], batch: int,
                          backend: Optional[str] = None,
                          n_devices: Optional[int] = None) -> str:
    """Decide the attention execution path: ``'ring_flash'`` /
    ``'ring'`` (sequence-parallel, flash-kernel or einsum hops),
    ``'flash_sharded'`` (Pallas kernel per device under shard_map),
    ``'flash'`` (bare Pallas kernel, single device) or ``'xla'``.

    Pure given ``backend``/``n_devices`` (injected in tests; defaulted from
    the live JAX runtime otherwise). Encodes the safety rules: the bare
    Mosaic call has no SPMD partitioning rule, so ``'auto'`` only picks it
    when exactly one device is visible, and under a mesh the kernel is
    reached exclusively through shard_map with divisible batch/head dims.
    """
    c = config
    backend = backend if backend is not None else jax.default_backend()
    if mesh is not None and seq_axis is not None:
        # windowed configs compose: the ring applies the band over
        # global positions and statically skips out-of-band hops; each
        # hop's local block runs the Pallas flash kernel on TPU
        if (c.attention_impl == "flash"
                or (c.attention_impl == "auto" and backend == "tpu")):
            return "ring_flash"
        return "ring"
    if mesh is not None:
        if (c.attention_impl != "xla"
                and (c.attention_impl == "flash" or backend == "tpu")
                and _mesh_divides(mesh, batch_axis, batch)
                and _mesh_divides(mesh, model_axis, c.num_heads)
                and _mesh_divides(mesh, model_axis, c.kv_heads)):
            return "flash_sharded"
        return "xla"
    n_devices = (n_devices if n_devices is not None
                 else len(jax.devices()))
    if c.attention_impl == "flash" or (c.attention_impl == "auto"
                                       and backend == "tpu"
                                       and n_devices == 1):
        return "flash"
    return "xla"


def _alibi_slopes(num_heads: int) -> jnp.ndarray:
    """Per-head geometric slopes (Press et al.): for 2^n heads,
    2^(-8i/n); other counts interpolate the same way HF/ALiBi do."""
    def pow2_slopes(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]

    n = 2 ** math.floor(math.log2(num_heads))
    slopes = pow2_slopes(n)
    if n < num_heads:
        slopes += pow2_slopes(2 * n)[0::2][:num_heads - n]
    return jnp.asarray(slopes, jnp.float32)


def _apply_rope(x, positions, config: "TransformerConfig"):
    """Rotate the head dimension of ``x`` (..., seq, head_dim) by the
    position-dependent RoPE angles (RoFormer, half-split convention).
    Angles are computed in f32; the rotation runs in x's dtype."""
    c = config
    half = c.head_dim // 2
    freqs = c.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                             / c.head_dim)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (seq, half)
    cos = jnp.cos(angles).astype(x.dtype)
    sin = jnp.sin(angles).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _dropout(x, rate: float, key):
    """Inverted dropout; identity when key is None (inference)."""
    if key is None or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def _layer_norm(x, gamma, beta, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps)) * gamma + beta


def _rms_norm(x, gamma, eps=1e-5):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * gamma


def _norm(x, sub: Dict, c) -> jnp.ndarray:
    """Config-selected normalization (rmsnorm ignores beta)."""
    if getattr(c, "norm", "layernorm") == "rmsnorm":
        return _rms_norm(x, sub["gamma"], getattr(c, "rms_norm_eps", 1e-5))
    return _layer_norm(x, sub["gamma"], sub["beta"])


def _times(x, c, name: str):
    """``x`` times the config's multiplier ``name``
    (:class:`Multipliers`); ``x`` itself, and so the program it had, for
    a config without multipliers (the encoder families' configs have no
    such field)."""
    multipliers = getattr(c, "multipliers", None)
    return x if multipliers is None else x * getattr(multipliers, name)


def _qkv(layer: Dict, h: jnp.ndarray, c: TransformerConfig):
    """Per-head ``q, k, v`` ``(B, heads, T, head_dim)`` of the normed
    input ``h`` ``(B, T, D)``, before any rotation."""
    h = _times(h, c, "attention_in")
    q = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wq"].astype(c.dtype))
    k = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wk"].astype(c.dtype))
    v = jnp.einsum("btd,dhk->bhtk", h, layer["attn"]["wv"].astype(c.dtype))
    return q, _times(k, c, "key"), v


def _attn_out(layer: Dict, o: jnp.ndarray, c: TransformerConfig):
    """Heads ``o`` ``(B, heads, T, head_dim)`` through the output
    projection: attention's contribution to the residual stream."""
    return _times(jnp.einsum("bhtk,hkd->btd", o,
                             layer["attn"]["wo"].astype(c.dtype)),
                  c, "attention_out")


def _flash_blocks(c: TransformerConfig) -> Dict[str, int]:
    """Configured flash tile overrides as kwargs (empty = kernel defaults)."""
    blocks = {}
    if getattr(c, "flash_block_q", None):
        blocks["block_q"] = int(c.flash_block_q)
    if getattr(c, "flash_block_k", None):
        blocks["block_k"] = int(c.flash_block_k)
    return blocks


def _attn_apply(layer: Dict, x: jnp.ndarray, c: TransformerConfig,
                attn_fn, dropout_key=None) -> jnp.ndarray:
    """Pre-LN attention sublayer with residual; ``attn_fn(q, k, v) -> o``
    supplies the attention implementation. ``dropout_key`` enables
    residual dropout on the sublayer output (training only)."""
    h = _norm(x, layer["ln1"], c)
    h = h.astype(c.dtype)
    q, k, v = _qkv(layer, h, c)
    if c.positional == "rope":
        # rotation happens on the logically-global sequence (GSPMD keeps
        # the iota global under sharding), before any ring/flash shard_map
        pos = jnp.arange(x.shape[1])
        q = _apply_rope(q, pos, c)
        k = _apply_rope(k, pos, c)
    if (c.kv_heads != c.num_heads
            and not getattr(attn_fn, "handles_gqa", False)):
        # GQA: broadcast each k/v head over its query group so the
        # xla/flash paths see full-width heads (XLA fuses the repeat
        # into the downstream matmul). GQA-aware paths (the ring, which
        # circulates narrow k/v buffers over ICI) take kv-width inputs.
        groups = c.num_heads // c.kv_heads
        k = jnp.repeat(k, groups, axis=1)
        v = jnp.repeat(v, groups, axis=1)
    out = _attn_out(layer, attn_fn(q, k, v), c)
    if getattr(c, "ssm", None) is not None:
        # the hybrid block: the mixer reads the same normed input, from
        # a zero state, and its output is added to attention's
        out = out + _mamba.mixer_apply(
            layer["ssm"], h, _mamba.zero_layer_state(c, x.shape[0]), c)[0]
    return x + _dropout(out, c.dropout_rate, dropout_key)


def _mlp_apply(layer: Dict, x: jnp.ndarray, c: TransformerConfig,
               dropout_key=None) -> jnp.ndarray:
    """Pre-LN dense MLP sublayer with residual (gelu or SwiGLU)."""
    h = _norm(x, layer["ln2"], c)
    h = h.astype(c.dtype)
    if getattr(c, "mlp_variant", "gelu") == "swiglu":
        gate = jax.nn.silu(_times(h @ layer["mlp"]["w1"].astype(c.dtype)
                                  + layer["mlp"]["b1"].astype(c.dtype),
                                  c, "mlp_gate"))
        h = gate * (h @ layer["mlp"]["w3"].astype(c.dtype))
    else:
        h = jax.nn.gelu(h @ layer["mlp"]["w1"].astype(c.dtype)
                        + layer["mlp"]["b1"].astype(c.dtype))
    h = _times(h @ layer["mlp"]["w2"].astype(c.dtype)
               + layer["mlp"]["b2"].astype(c.dtype), c, "mlp_down")
    return x + _dropout(h, c.dropout_rate, dropout_key)


def block_apply(layer: Dict, x: jnp.ndarray, config: TransformerConfig,
                attn_fn=None) -> jnp.ndarray:
    """One full dense transformer block ``(batch, seq, d_model) ->
    same shape`` — the shape-preserving unit the GPipe pipeline stages
    (:mod:`~elephas_tpu.parallel.pipeline`) are built from. Defaults to
    causal XLA attention (each pipeline stage sees full local sequence)."""
    if attn_fn is None:
        attn_fn = partial(attention, causal=True)
    x = _attn_apply(layer, x, config, attn_fn)
    return _mlp_apply(layer, x, config)


def _sinusoidal_table(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Parameter-free sin/cos position encoding (Vaswani et al. §3.5):
    ``(..., d_model)`` for integer ``positions``."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    table = jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)
    if d_model % 2:
        table = jnp.pad(table, [(0, 0)] * (table.ndim - 1) + [(0, 1)])
    return table


def embed_apply(embed: Dict, tokens: jnp.ndarray,
                config: TransformerConfig) -> jnp.ndarray:
    """Token (+ positional) embedding -> activations in the compute
    dtype. Shared by the monolithic forward and the pipelined LM entry.
    RoPE configs carry position in the per-layer q/k rotation instead of
    an additive table; sinusoidal adds the parameter-free table."""
    x = _times(embed["tokens"][tokens], config, "embedding")
    if config.positional == "learned":
        x = x + embed["pos"][:tokens.shape[1]]
    elif config.positional == "sinusoidal":
        x = x + _sinusoidal_table(jnp.arange(tokens.shape[1]),
                                  config.d_model)
    return x.astype(config.dtype)


def head_logits(embed: Dict, final_ln: Dict, x: jnp.ndarray,
                head: Optional[jnp.ndarray] = None,
                norm: str = "layernorm",
                rms_norm_eps: float = 1e-5,
                multipliers: Optional[Multipliers] = None) -> jnp.ndarray:
    """Final norm + LM head (tied to the embedding unless an untied
    ``head`` matrix is given); f32 logits for a stable softmax, times
    ``multipliers.lm_head`` where the config has multipliers. Shared by
    the monolithic forward and the pipelined LM exit."""
    x = x.astype(jnp.float32)
    x = (_rms_norm(x, final_ln["gamma"], rms_norm_eps) if norm == "rmsnorm"
         else _layer_norm(x, final_ln["gamma"], final_ln["beta"]))
    logits = (x @ head.astype(jnp.float32) if head is not None
              else x @ embed["tokens"].T.astype(jnp.float32))
    return logits if multipliers is None else logits * multipliers.lm_head


def next_token_loss(logits: jnp.ndarray, tokens: jnp.ndarray,
                    label_smoothing: float = 0.0,
                    weights: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token cross-entropy, mean over all positions (or a
    ``weights``-weighted mean — packed training zeroes cross-document
    and padding targets); with label smoothing, eps probability mass
    spreads uniformly over the vocab."""
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce_pos = -picked
    if label_smoothing:
        eps = label_smoothing
        ce_pos = (1.0 - eps) * ce_pos - eps * jnp.mean(logp, axis=-1)
    if weights is None:
        return jnp.mean(ce_pos)
    w = weights.astype(ce_pos.dtype)
    return jnp.sum(ce_pos * w) / jnp.maximum(jnp.sum(w), 1.0)


def segment_target_weights(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """Per-target weights for packed rows ``(B, T) -> (B, T-1)``: target
    t+1 counts only when positions t and t+1 belong to the same non-pad
    (id > 0) segment."""
    a, b = segment_ids[:, :-1], segment_ids[:, 1:]
    return ((a == b) & (b > 0)).astype(jnp.float32)


def chunked_next_token_losses(x: jnp.ndarray, embed: Dict, final_ln: Dict,
                              tokens: jnp.ndarray, chunk: int,
                              head: Optional[jnp.ndarray] = None,
                              norm: str = "layernorm",
                              weights: Optional[jnp.ndarray] = None,
                              rms_norm_eps: float = 1e-5
                              ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                         jnp.ndarray]:
    """Streamed LM loss pieces from the final hidden states: returns
    ``(cross_entropy, lse, mean_logits)`` where ``lse[b, t] =
    logsumexp_v(logits)`` (so the z-loss comes free) and ``mean_logits``
    is the per-position vocab mean (the label-smoothing term), WITHOUT
    materializing ``(B, T, V)`` logits. The vocab axis is processed in
    ``chunk``-sized slices inside a rematerialized scan — each chunk's
    logits live only transiently in both passes, bounding peak HBM at
    ``(B, T, chunk)``.
    """
    h = x.astype(jnp.float32)
    h = (_rms_norm(h, final_ln["gamma"], rms_norm_eps) if norm == "rmsnorm"
         else _layer_norm(h, final_ln["gamma"], final_ln["beta"]))[:, :-1]
    targets = tokens[:, 1:]                                  # (B, T')
    emb = (head.T if head is not None
           else embed["tokens"]).astype(jnp.float32)         # (V, D)
    v, d = emb.shape
    nc = -(-v // chunk)
    pad = nc * chunk - v
    emb_p = jnp.pad(emb, ((0, pad), (0, 0)))
    # padded rows must not contribute to the logsumexp
    valid = (jnp.arange(nc * chunk) < v).reshape(nc, chunk)
    emb_c = emb_p.reshape(nc, chunk, d)

    @jax.checkpoint
    def body(carry, ec):
        m, s, tot = carry
        e_chunk, mask = ec
        logits_c = jnp.einsum("btd,cd->btc", h, e_chunk)
        logits_c = jnp.where(mask, logits_c, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits_c, axis=-1))
        s = (s * jnp.exp(m - m_new)
             + jnp.sum(jnp.exp(logits_c - m_new[..., None]), axis=-1))
        tot = tot + jnp.sum(jnp.where(mask, logits_c, 0.0), axis=-1)
        return (m_new, s, tot), None

    m0 = jnp.full(h.shape[:2], NEG_INF, jnp.float32)
    s0 = jnp.zeros(h.shape[:2], jnp.float32)
    (m, s, tot), _ = jax.lax.scan(body, (m0, s0, s0), (emb_c, valid))
    lse = m + jnp.log(s)                                     # (B, T')
    # target logit via a row gather — (B, T', D) transient, not (B,T',V)
    picked = jnp.sum(h * emb[targets], axis=-1)
    ce_pos = lse - picked
    if weights is not None:
        w = weights.astype(ce_pos.dtype)
        ce = jnp.sum(ce_pos * w) / jnp.maximum(jnp.sum(w), 1.0)
    else:
        ce = jnp.mean(ce_pos)
    return ce, lse, tot / v


def select_moe_dispatch(config: "TransformerConfig",
                        mesh: Optional[Mesh] = None,
                        model_axis: Optional[str] = None) -> str:
    """Resolve ``config.moe_dispatch`` to ``'dense'`` or ``'routed'``.

    ``auto`` picks routed dispatch (FLOPs ∝ top_k) once the expert count
    is big enough for the savings to matter. Under an expert-sharded mesh
    the routed path runs as an explicit shard_map program
    (:func:`_moe_block_routed_ep` — each device dispatches to its local
    expert slice, one psum combines), so routing stays available with
    expert parallelism as long as the experts divide the axis."""
    if config.moe_dispatch != "auto":
        return config.moe_dispatch
    if config.num_experts <= 4:
        return "dense"
    if mesh is not None and not _mesh_divides(mesh, model_axis,
                                              config.num_experts):
        return "dense"  # experts don't divide the axis: keep the einsum
    return "routed"


def _moe_gates(h, moe, config: "TransformerConfig"):
    """Shared router: f32 softmax probabilities, exact top-k selection and
    the Switch load-balancing aux loss.

    The router runs in f32 (bf16 logits would tie-break wrongly and the
    module's contract keeps softmaxes f32). Gating: full softmax first,
    then top-k selection — for k=1 the output is scaled by the raw
    probability (Switch style: renormalizing a single entry to 1.0 would
    starve the router of gradient), for k>1 the selected probabilities
    are renormalized (Mixtral style).

    Returns ``(probs, gate_vals, topi, aux)`` with ``gate_vals``/``topi``
    of shape ``(..., top_k)``.
    """
    c = config
    gate_logits = (h.astype(jnp.float32)
                   @ moe["gate"].astype(jnp.float32))  # (..., E)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    # exact top-k via lax.top_k indices: a >=kth-value threshold would
    # select MORE than k experts when probabilities tie (common for
    # duplicated token contexts), silently changing the gate mass
    gate_vals, topi = jax.lax.top_k(probs, c.expert_top_k)
    if c.expert_top_k > 1:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    # Switch aux loss (eq. 4): num_experts * sum_e f_e * P_e, where f_e is
    # the fraction of tokens whose top choice is e and P_e the mean router
    # probability of e — minimized by a uniform routing distribution
    lead_axes = tuple(range(probs.ndim - 1))
    top1 = jax.nn.one_hot(jnp.argmax(probs, axis=-1), c.num_experts,
                          dtype=jnp.float32)
    aux = c.num_experts * jnp.sum(jnp.mean(top1, axis=lead_axes)
                                  * jnp.mean(probs, axis=lead_axes))
    return probs, gate_vals, topi, aux


def _moe_block(h, moe, config: "TransformerConfig",
               dispatch: Optional[str] = None):
    """Gated mixture-of-experts MLP.

    Dense dispatch runs every expert on all tokens and lets the top-k
    gate zero the rest — trades routed-FLOP savings for perfectly static
    shapes while still *distributing* expert compute over the mesh via
    the expert-sharded parameters. Routed dispatch
    (:func:`_moe_block_routed`) scatters tokens into per-expert capacity
    buffers so FLOPs scale with ``top_k`` instead of ``num_experts``.

    Returns ``(out, aux)`` where ``aux`` is the Switch load-balancing
    loss term for this block (f32 scalar).
    """
    c = config
    if dispatch is None:
        dispatch = select_moe_dispatch(c)
    if dispatch == "routed":
        return _moe_block_routed(h, moe, c)
    probs, gate_vals, topi, aux = _moe_gates(h, moe, c)
    # scatter the (renormalized) top-k gate values back onto the E axis
    gates = jnp.sum(jax.nn.one_hot(topi, c.num_experts,
                                   dtype=gate_vals.dtype)
                    * gate_vals[..., None], axis=-2)
    gates = gates.astype(c.dtype)
    he = jax.nn.gelu(
        jnp.einsum("btd,edf->betf", h, moe["w1"].astype(c.dtype))
        + moe["b1"].astype(c.dtype)[None, :, None, :])
    out = (jnp.einsum("betf,efd->betd", he, moe["w2"].astype(c.dtype))
           + moe["b2"].astype(c.dtype)[None, :, None, :])
    return jnp.einsum("betd,bte->btd", out, gates), aux


def _shared_expert(h: jnp.ndarray, shared: Dict,
                   c: "TransformerConfig") -> jnp.ndarray:
    """Always-on dense MLP added to the MoE combine (gelu, like the
    experts)."""
    g = jax.nn.gelu(h @ shared["w1"].astype(c.dtype)
                    + shared["b1"].astype(c.dtype))
    return (g @ shared["w2"].astype(c.dtype)
            + shared["b2"].astype(c.dtype))


def _routed_capacity(config: "TransformerConfig", n_tokens: int) -> int:
    c = int(np.ceil(config.moe_capacity_factor * config.expert_top_k
                    * n_tokens / config.num_experts))
    return min(max(c, 1), n_tokens)


def _routed_dispatch(hf, gate_vals, topi, w1, b1, w2, b2,
                     config: "TransformerConfig", capacity: int,
                     expert_offset: int = 0):
    """Scatter → expert MLP → gather for the expert slice
    ``[expert_offset, expert_offset + w1.shape[0])``.

    Tokens scatter into per-expert capacity buffers; each expert runs its
    MLP once over its ``(capacity, d_model)`` buffer, and outputs gather
    back to token order weighted by the gate. Assignments beyond an
    expert's capacity are dropped (their gate contribution is zero — the
    token passes through on the residual stream only), with earlier
    tokens and higher-ranked choices winning: the static-shape price of
    routing, bounded by the aux loss keeping the router balanced. All
    shapes are static: XLA-friendly scatter-add/gather, no host sync.
    Assignments outside the expert slice also drop — under expert
    parallelism every device runs this on its local slice and a psum
    sums the slices' contributions.
    """
    c = config
    N, D = hf.shape
    k = c.expert_top_k
    e_local = w1.shape[0]

    # flatten assignments token-major so earlier tokens (and, within a
    # token, higher-ranked choices) win the capacity race
    experts = topi.reshape(N * k)                         # (N*k,)
    assign = jax.nn.one_hot(experts, c.num_experts, dtype=jnp.int32)
    # position of each assignment within its expert's buffer — computed
    # over the FULL expert range so every slice agrees on positions
    pos_in_expert = jnp.cumsum(assign, axis=0) - assign
    pos = jnp.sum(pos_in_expert * assign, axis=-1)        # (N*k,)
    keep = pos < capacity
    local = experts - expert_offset
    in_slice = (local >= 0) & (local < e_local)

    token_idx = jnp.arange(N * k) // k
    xs = hf[token_idx].astype(c.dtype)                    # (N*k, D)
    # out-of-capacity / out-of-slice scatters are pushed out of bounds
    # and land on mode='drop'; their gathers below are masked through the
    # zeroed gate
    safe_e = jnp.where(in_slice, local, 0)
    pos_eff = jnp.where(in_slice & keep, pos, capacity)
    buf = jnp.zeros((e_local, capacity, D), c.dtype)
    buf = buf.at[safe_e, pos_eff].add(xs, mode="drop")

    he = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", buf, w1.astype(c.dtype))
        + b1.astype(c.dtype)[:, None, :])
    out_buf = (jnp.einsum("ecf,efd->ecd", he, w2.astype(c.dtype))
               + b2.astype(c.dtype)[:, None, :])

    gate_flat = (gate_vals.reshape(N * k)
                 * (keep & in_slice).astype(gate_vals.dtype)).astype(c.dtype)
    picked = out_buf[safe_e, jnp.minimum(pos, capacity - 1)]  # (N*k, D)
    return jnp.sum((picked * gate_flat[:, None]).reshape(N, k, D), axis=1)


def _moe_block_routed(h, moe, config: "TransformerConfig"):
    """Capacity-factor routed MoE dispatch (Switch Transformer §2.2).

    Per-token expert FLOPs are ``capacity_factor * top_k * 2 * d_model *
    d_ff`` — independent of ``num_experts`` (dense dispatch pays
    ``num_experts``×). See :func:`_routed_dispatch` for the scatter/
    gather mechanics and drop semantics.
    """
    c = config
    B, T, D = h.shape
    hf = h.reshape(B * T, D)
    _, gate_vals, topi, aux = _moe_gates(hf, moe, c)
    out = _routed_dispatch(hf, gate_vals, topi, moe["w1"], moe["b1"],
                           moe["w2"], moe["b2"], c,
                           _routed_capacity(c, B * T))
    return out.reshape(B, T, D), aux


def _moe_block_routed_ep(h, moe, config: "TransformerConfig", mesh: Mesh,
                         data_axis: Optional[str], model_axis: str):
    """Routed dispatch under expert parallelism, as an explicit shard_map
    program: every device routes its local token shard to its local
    expert slice (out-of-slice assignments drop at the scatter), and one
    psum over the ``model`` axis sums the slices' contributions back into
    the replicated residual stream — the same single-collective shape as
    the dense einsum path, with routed FLOP economics per device.

    Capacity is per data shard (``ceil(cf * k * local_tokens / E)``), the
    standard per-group capacity of sharded MoE — identical to the global
    computation when nothing drops.
    """
    c = config
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = axis_sizes[model_axis]

    def local_fn(h_l, gate, w1_l, b1_l, w2_l, b2_l):
        bl, tl, dl = h_l.shape
        hf = h_l.reshape(bl * tl, dl)
        _, gate_vals, topi, aux = _moe_gates(hf, {"gate": gate}, c)
        offset = jax.lax.axis_index(model_axis) * (c.num_experts // ep)
        out = _routed_dispatch(hf, gate_vals, topi, w1_l, b1_l, w2_l,
                               b2_l, c, _routed_capacity(c, bl * tl),
                               expert_offset=offset)
        out = jax.lax.psum(out.reshape(bl, tl, dl), model_axis)
        if data_axis is not None:
            aux = jax.lax.pmean(aux, data_axis)
        return out, aux

    batch_spec = P(data_axis, None, None)
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(batch_spec, P(None, None), P(model_axis, None, None),
                  P(model_axis, None), P(model_axis, None, None),
                  P(model_axis, None)),
        out_specs=(batch_spec, P()),
        check_vma=False)(h, moe["gate"], moe["w1"], moe["b1"], moe["w2"],
                     moe["b2"])
    return out, aux


def forward(params: Dict, tokens: jnp.ndarray, config: TransformerConfig,
            mesh: Optional[Mesh] = None, seq_axis: Optional[str] = None,
            batch_axis: Optional[str] = None,
            model_axis: Optional[str] = None,
            dropout_key=None,
            segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Token ids ``(batch, seq)`` -> logits ``(batch, seq, vocab)``.

    When ``mesh`` and ``seq_axis`` are given, attention runs as ring
    attention with k/v shards streaming over the ``seq_axis`` ring.
    ``dropout_key`` activates residual dropout (training); omit it for
    deterministic inference.
    """
    logits, _ = forward_with_aux(params, tokens, config, mesh=mesh,
                                 seq_axis=seq_axis, batch_axis=batch_axis,
                                 model_axis=model_axis,
                                 dropout_key=dropout_key,
                                 segment_ids=segment_ids)
    return logits


def forward_with_aux(params: Dict, tokens: jnp.ndarray,
                     config: TransformerConfig,
                     mesh: Optional[Mesh] = None,
                     seq_axis: Optional[str] = None,
                     batch_axis: Optional[str] = None,
                     model_axis: Optional[str] = None,
                     dropout_key=None,
                     segment_ids: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Like :func:`forward` but also returns the summed MoE auxiliary
    (load-balancing) loss — 0.0 for dense configs."""
    x, aux_total = _hidden_with_aux(params, tokens, config, mesh=mesh,
                                    seq_axis=seq_axis, batch_axis=batch_axis,
                                    model_axis=model_axis,
                                    dropout_key=dropout_key,
                                    segment_ids=segment_ids)
    return head_logits(params["embed"], params["final_ln"], x,
                       head=params.get("head"), norm=config.norm,
                       rms_norm_eps=config.rms_norm_eps,
                       multipliers=config.multipliers), aux_total


def _hidden_with_aux(params: Dict, tokens: jnp.ndarray,
                     config: TransformerConfig,
                     mesh: Optional[Mesh] = None,
                     seq_axis: Optional[str] = None,
                     batch_axis: Optional[str] = None,
                     model_axis: Optional[str] = None,
                     dropout_key=None,
                     segment_ids: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The block stack up to (but excluding) the LM head: final hidden
    states ``(B, T, D)`` + summed MoE aux loss. ``segment_ids`` (packed
    rows, ids > 0, 0 = padding) isolate documents: attention stays
    within a segment (causal AND same-segment; forces the xla path)."""
    c = config
    x = embed_apply(params["embed"], tokens, c)
    aux_total = jnp.zeros((), jnp.float32)
    attn_impl = select_attention_impl(c, mesh, seq_axis, batch_axis,
                                      model_axis, tokens.shape[0])
    if segment_ids is not None or c.positional == "alibi":
        attn_impl = "xla"  # segment masks / alibi bias live here only
    if attn_impl in ("ring", "ring_flash"):
        attn_fn = partial(ring_attention_sharded, mesh=mesh,
                          seq_axis=seq_axis, causal=True,
                          batch_axis=batch_axis,
                          window=c.attention_window,
                          impl=("flash" if attn_impl == "ring_flash"
                                else "einsum"))
        # the ring folds GQA groups internally and keeps k/v narrow on
        # the wire — don't pre-broadcast them
        attn_fn.handles_gqa = True
    elif attn_impl == "flash_sharded":
        # dp/tp meshes hit the Pallas kernel through shard_map (batch
        # pinned to the data axis, heads to the Megatron model axis —
        # attention needs no cross-device communication)
        attn_fn = partial(flash_attention_sharded, mesh=mesh, causal=True,
                          batch_axis=batch_axis, head_axis=model_axis,
                          window=c.attention_window, **_flash_blocks(c))
        # the kernel resolves GQA via its kv-row index maps — narrow k/v
        # all the way into VMEM, no head-broadcast materialization; a
        # sliding window skips out-of-band blocks in-kernel
        attn_fn.handles_gqa = True
    elif attn_impl == "flash":
        attn_fn = partial(flash_attention, causal=True,
                          window=c.attention_window, **_flash_blocks(c))
        attn_fn.handles_gqa = True
    elif (segment_ids is not None or c.attention_window is not None
          or c.positional == "alibi"):
        t = tokens.shape[1]
        q_pos = jnp.arange(t)[:, None]
        k_pos = jnp.arange(t)[None, :]
        mask = (k_pos <= q_pos)[None, None, :, :]      # (1, 1, T, T)
        if c.attention_window is not None:
            mask = mask & (k_pos > q_pos - c.attention_window)[None, None]
        if segment_ids is not None:
            same = (segment_ids[:, None, :, None]
                    == segment_ids[:, None, None, :])  # (B, 1, T, T)
            mask = mask & same & (segment_ids > 0)[:, None, None, :]
        bias = None
        if c.positional == "alibi":
            slopes = _alibi_slopes(c.num_heads)        # (H,)
            dist = (q_pos - k_pos).astype(jnp.float32)  # (T, T)
            bias = (-slopes[:, None, None] * dist)[None]  # (1, H, T, T)
        attn_fn = partial(attention, causal=False, mask=mask, bias=bias)
    else:
        attn_fn = partial(attention, causal=True)

    moe_dispatch = (select_moe_dispatch(c, mesh, model_axis)
                    if c.num_experts > 1 else None)
    # routed + expert-sharded mesh -> the explicit shard_map EP program,
    # when the experts divide the axis (shard_map precondition) and no
    # sequence axis is in play (the shard_map would force a seq
    # re-gather). Every other routed case keeps the GSPMD routed path —
    # an explicit moe_dispatch='routed' is always honored as routed.
    ep = (dict(zip(mesh.axis_names, mesh.devices.shape)).get(model_axis, 1)
          if mesh is not None and model_axis is not None else 1)
    moe_ep = (moe_dispatch == "routed" and ep > 1 and seq_axis is None
              and _mesh_divides(mesh, model_axis, c.num_experts))

    mla = c.attention_kind == "mla"
    if mla and (mesh is not None and seq_axis is not None
                or segment_ids is not None):
        raise ValueError("attention_kind='mla' runs the plain causal "
                         "path: no sequence axis, no segment_ids")

    def layer_apply(layer, x, layer_key, experts):
        if layer_key is not None:
            attn_key, mlp_key = jax.random.split(layer_key)
        else:
            attn_key = mlp_key = None
        if mla:
            h = _norm(x, layer["ln1"], c).astype(c.dtype)
            x = x + _dropout(_mla.attn_full(layer["attn"], h, c),
                             c.dropout_rate, attn_key)
        else:
            x = _attn_apply(layer, x, c, attn_fn, dropout_key=attn_key)
        if experts:
            h = _norm(x, layer["ln2"], c)
            h = h.astype(c.dtype)
            if c.expert_variant == "swiglu":
                # (the shared expert is part of the layer; no auxiliary
                # loss: the router's balance is not trained here yet)
                out, _ = _gx.experts_apply(h, layer["moe"], c)
                aux = jnp.zeros((), jnp.float32)
            elif moe_ep:
                out, aux = _moe_block_routed_ep(h, layer["moe"], c, mesh,
                                                batch_axis, model_axis)
            else:
                out, aux = _moe_block(h, layer["moe"], c,
                                      dispatch=moe_dispatch)
            if c.moe_shared_expert:
                out = out + _shared_expert(h, layer["moe"]["shared"], c)
            return x + _dropout(out, c.dropout_rate, mlp_key), aux
        return (_mlp_apply(layer, x, c, dropout_key=mlp_key),
                jnp.zeros((), jnp.float32))

    if c.remat:
        # recompute each block's activations in the backward pass instead
        # of keeping them live: activation memory stays O(1) in depth
        policy = (jax.checkpoint_policies.dots_saveable
                  if c.remat_policy == "dots" else None)
        layer_apply = jax.checkpoint(layer_apply, policy=policy,
                                     static_argnums=(3,))

    for i in range(c.num_layers):
        layer_key = (jax.random.fold_in(dropout_key, i)
                     if dropout_key is not None else None)
        x, aux = layer_apply(params[f"layer_{i}"], x, layer_key,
                             c.has_experts(i))
        aux_total = aux_total + aux

    return x, aux_total


def lm_loss(params: Dict, tokens: jnp.ndarray, config: TransformerConfig,
            mesh: Optional[Mesh] = None, seq_axis: Optional[str] = None,
            batch_axis: Optional[str] = None,
            model_axis: Optional[str] = None,
            dropout_key=None,
            segment_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Next-token cross-entropy (mean over all positions), plus the
    weighted MoE load-balancing auxiliary loss for MoE configs."""
    # the chunked (streamed-logsumexp) loss applies when the embedding is
    # not vocab-sharded: a tp mesh already spreads the logits over the
    # model axis, and chunk-slicing a sharded vocab would fight GSPMD
    weights = (segment_target_weights(segment_ids)
               if segment_ids is not None else None)
    chunk = config.loss_vocab_chunk
    vocab_sharded = (mesh is not None and model_axis is not None
                     and mesh.shape.get(model_axis, 1) > 1)
    if chunk and not vocab_sharded:
        x, aux = _hidden_with_aux(params, tokens, config, mesh=mesh,
                                  seq_axis=seq_axis, batch_axis=batch_axis,
                                  model_axis=model_axis,
                                  dropout_key=dropout_key,
                                  segment_ids=segment_ids)
        loss, lse, mean_logits = chunked_next_token_losses(
            x, params["embed"], params["final_ln"], tokens, int(chunk),
            head=params.get("head"), norm=config.norm, weights=weights,
            rms_norm_eps=config.rms_norm_eps)
        if config.label_smoothing:
            # mean_v logp_v = mean_v logits_v - lse
            eps = config.label_smoothing
            smooth = lse - mean_logits
            if weights is not None:
                smooth_mean = (jnp.sum(smooth * weights)
                               / jnp.maximum(jnp.sum(weights), 1.0))
            else:
                smooth_mean = jnp.mean(smooth)
            loss = (1.0 - eps) * loss + eps * smooth_mean
        if config.num_experts > 1 and config.moe_aux_weight:
            loss = loss + config.moe_aux_weight * aux
        if config.z_loss_weight:
            z2 = lse * lse
            if weights is not None:
                z_mean = (jnp.sum(z2 * weights)
                          / jnp.maximum(jnp.sum(weights), 1.0))
            else:
                z_mean = jnp.mean(z2)
            loss = loss + config.z_loss_weight * z_mean
        return loss
    logits, aux = forward_with_aux(params, tokens, config, mesh=mesh,
                                   seq_axis=seq_axis, batch_axis=batch_axis,
                                   model_axis=model_axis,
                                   dropout_key=dropout_key,
                                   segment_ids=segment_ids)
    loss = next_token_loss(logits, tokens,
                           label_smoothing=config.label_smoothing,
                           weights=weights)
    if config.num_experts > 1 and config.moe_aux_weight:
        loss = loss + config.moe_aux_weight * aux
    if config.z_loss_weight:
        # PaLM-style z-loss: penalize the log-partition so logits don't
        # drift large (bf16 stability); only predicting positions count
        z = jax.scipy.special.logsumexp(logits[:, :-1], axis=-1)
        z2 = z * z
        if weights is not None:
            z_mean = (jnp.sum(z2 * weights)
                      / jnp.maximum(jnp.sum(weights), 1.0))
        else:
            z_mean = jnp.mean(z2)
        loss = loss + config.z_loss_weight * z_mean
    return loss


def _extend_spec(spec: P, shape, axis: str, size: int) -> P:
    """Add ``axis`` to ``spec`` on the first still-unsharded dimension of
    ``shape`` divisible by ``size``; unchanged if none qualifies."""
    if size <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (s, dim) in enumerate(zip(entries, shape)):
        if s is None and dim % size == 0 and dim >= size:
            entries[i] = axis
            return P(*entries)
    return spec


def fsdp_param_specs(config: TransformerConfig, mesh: Mesh,
                     data_axis: str = "data",
                     model_axis: Optional[str] = "model",
                     param_shapes: Optional[Dict] = None) -> Dict:
    """Fully-sharded (ZeRO-3 style) PartitionSpecs: every parameter keeps
    its tensor-parallel sharding (when ``model_axis`` is on the mesh) and
    additionally shards its first still-unsharded divisible dimension over
    the ``data`` axis. Parameter, gradient, and (via ``jit(tx.init)`` on
    the sharded params) optimizer memory all scale down with the
    data-parallel degree; XLA/GSPMD inserts the all-gather at each use and
    the reduce-scatter on the gradients — the standard JAX FSDP recipe
    (sharding annotation, not hand-written collectives).

    TPU-native counterpart of reference weight replication per worker
    (``/root/reference/elephas/spark_model.py:207`` broadcasts full
    weights to every executor); here each device holds 1/dp of them.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dsize = sizes.get(data_axis, 1)
    base = (param_specs(config, model_axis=model_axis, mesh=mesh)
            if model_axis is not None and sizes.get(model_axis, 1) > 1
            else jax.tree_util.tree_map(
                lambda _: P(), param_specs(config),
                is_leaf=lambda x: isinstance(x, P)))
    shapes = (param_shapes if param_shapes is not None
              else jax.eval_shape(lambda k: init_params(config, k),
                                  jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda s, leaf: _extend_spec(s, leaf.shape, data_axis, dsize),
        base, shapes, is_leaf=lambda x: isinstance(x, P))


def zero_opt_specs(tx, params: Dict, config: TransformerConfig, mesh: Mesh,
                   data_axis: str = "data", model_axis: str = "model"):
    """ZeRO-1 style PartitionSpecs for the optimizer state: param-shaped
    state leaves (Adam moments etc.) keep their tensor-parallel sharding
    and additionally shard their first still-unsharded, divisible
    dimension over the ``data`` axis — optimizer memory scales down with
    the data-parallel degree instead of being replicated across it (the
    gradients are already replicated post-psum, so XLA turns the update
    into a per-shard computation plus the collectives it needs). Scalar
    leaves (step counts) replicate.

    Works structurally: optax states are (nested) tuples/NamedTuples
    whose fields are either pytrees with the params' treedef or scalars.
    """
    dsize = dict(zip(mesh.axis_names, mesh.devices.shape)).get(data_axis, 1)
    specs = param_specs(config, model_axis=model_axis, mesh=mesh)
    shapes = jax.tree_util.tree_map(lambda p: jax.ShapeDtypeStruct(
        p.shape, p.dtype), params)
    ext = jax.tree_util.tree_map(
        lambda s, leaf: _extend_spec(s, leaf.shape, data_axis, dsize),
        specs, shapes, is_leaf=lambda x: isinstance(x, P))
    return _opt_state_specs(tx, shapes, ext)


def _opt_state_specs(tx, param_shapes: Dict, leaf_specs: Dict):
    """PartitionSpecs for ``tx.init``'s state: param-shaped subtrees take
    ``leaf_specs`` (one spec per param), everything else replicates.
    Works structurally — optax states are (nested) tuples/NamedTuples
    whose fields are either pytrees with the params' treedef or scalars."""
    params_treedef = jax.tree_util.tree_structure(param_shapes)
    state_shapes = jax.eval_shape(tx.init, param_shapes)

    def walk(node):
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[walk(getattr(node, f))
                                for f in node._fields])
        if isinstance(node, (tuple, list)):
            return type(node)(walk(s) for s in node)
        if jax.tree_util.tree_structure(node) == params_treedef:
            return jax.tree_util.tree_map(lambda s, _: s, leaf_specs, node,
                                          is_leaf=lambda x: isinstance(x, P))
        return P()  # scalar / non-param-shaped leaf: replicate

    return walk(state_shapes)


def make_train_step(config: TransformerConfig, tx,
                    mesh: Optional[Mesh] = None,
                    data_axis: Optional[str] = "data",
                    model_axis: Optional[str] = "model",
                    seq_axis: Optional[str] = None,
                    zero_optimizer: bool = False,
                    accum_steps: int = 1,
                    fsdp: bool = False,
                    packed: bool = False):
    """Build a jitted (params, opt_state, tokens) -> (params, opt_state, loss)
    step with dp/tp(/sp) shardings. With ``mesh=None`` it is the plain
    single-device step. ``zero_optimizer=True`` pins the optimizer state
    to :func:`zero_opt_specs` shardings (ZeRO-1: moments sharded over the
    data axis instead of replicated). ``accum_steps > 1`` splits the
    token batch into that many microbatches and accumulates gradients in
    one ``lax.scan`` before the single optimizer update — the effective
    batch no longer has to fit in memory at once (equal-size microbatches
    make the result identical to the unaccumulated step).

    ``packed=True`` adds a trailing ``segment_ids`` argument to the
    step (packed-row training: segment-isolated attention + boundary-
    masked loss). Note: with ``accum_steps > 1`` the accumulated loss
    averages per-microbatch weighted means — identical to the one-shot
    step only when every microbatch carries the same valid-target count
    (rows from the same packing run are statistically so).

    ``fsdp=True`` (mesh required) pins params — and, through
    ``jit(tx.init)`` on params already placed by
    ``shard_params(..., fsdp_axis=data_axis)``, the optimizer moments —
    to :func:`fsdp_param_specs`: every large tensor lives 1/dp-sharded
    over the data axis and GSPMD all-gathers it at use / reduce-scatters
    its gradient (ZeRO-3)."""
    accum_steps = max(1, int(accum_steps))
    fsdp_shardings = fsdp_opt_shardings = None
    if fsdp:
        if mesh is None or data_axis is None:
            raise ValueError("fsdp=True requires a mesh and a data_axis")
        if zero_optimizer:
            raise ValueError(
                "fsdp already shards the optimizer state (ZeRO-3 strictly "
                "contains ZeRO-1) — drop zero_optimizer")
        param_shapes = jax.eval_shape(lambda k: init_params(config, k),
                                      jax.random.PRNGKey(0))
        specs = fsdp_param_specs(config, mesh, data_axis=data_axis,
                                 model_axis=model_axis,
                                 param_shapes=param_shapes)
        as_sharding = partial(jax.tree_util.tree_map,
                              lambda s: NamedSharding(mesh, s),
                              is_leaf=lambda x: isinstance(x, P))
        fsdp_shardings = as_sharding(specs)
        fsdp_opt_shardings = as_sharding(
            _opt_state_specs(tx, param_shapes, specs))

    use_dropout = config.dropout_rate > 0

    def loss_and_grads(params, tokens, dropout_key, segment_ids=None):
        return jax.value_and_grad(lm_loss)(
            params, tokens, config, mesh=mesh, seq_axis=seq_axis,
            batch_axis=data_axis if mesh is not None else None,
            model_axis=model_axis if mesh is not None else None,
            dropout_key=dropout_key, segment_ids=segment_ids)

    def step(params, opt_state, tokens, dropout_key=None,
             segment_ids=None):
        if accum_steps > 1:
            if tokens.shape[0] % accum_steps:
                raise ValueError(
                    f"batch {tokens.shape[0]} does not split into "
                    f"{accum_steps} microbatches")
            micro = tokens.reshape((accum_steps,
                                    tokens.shape[0] // accum_steps)
                                   + tokens.shape[1:])
            if mesh is not None and data_axis is not None:
                # keep each microbatch sharded over the data axis (the
                # reshape otherwise leaves XLA free to pick a layout it
                # then repartitions with a full rematerialization)
                micro = jax.lax.with_sharding_constraint(
                    micro, NamedSharding(mesh, P(None, data_axis,
                                                 *([None] * (micro.ndim - 2)))))
            mkeys = (jax.random.split(dropout_key, accum_steps)
                     if use_dropout else jnp.zeros((accum_steps, 2),
                                                   jnp.uint32))

            if segment_ids is not None:
                seg_micro = segment_ids.reshape(micro.shape)
            else:
                seg_micro = jnp.zeros_like(micro)  # unused placeholder

            def body(carry, xs):
                tk, mk, sg = xs
                gsum, lsum = carry
                loss, grads = loss_and_grads(
                    params, tk, mk if use_dropout else None,
                    sg if segment_ids is not None else None)
                gsum = jax.tree_util.tree_map(jnp.add, gsum, grads)
                return (gsum, lsum + loss), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (gsum, lsum), _ = jax.lax.scan(body, (zeros, 0.0),
                                           (micro, mkeys, seg_micro))
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
            loss = lsum / accum_steps
        else:
            loss, grads = loss_and_grads(
                params, tokens, dropout_key if use_dropout else None,
                segment_ids)
        if fsdp_shardings is not None:
            # keep the gradient fully sharded before the optimizer math:
            # GSPMD then reduce-scatters it and runs the update per-shard
            grads = jax.lax.with_sharding_constraint(grads, fsdp_shardings)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        if fsdp_shardings is not None:
            params = jax.lax.with_sharding_constraint(params, fsdp_shardings)
        return params, opt_state, loss

    if not (zero_optimizer and mesh is not None):
        # positional signature: (params, opt, tokens[, key][, segments])
        # — historical arities preserved when dropout/packing are off
        if not use_dropout and not packed:
            def wrapped(params, opt_state, tokens):
                return step(params, opt_state, tokens, None, None)
            n_extra = 0
        elif use_dropout and not packed:
            def wrapped(params, opt_state, tokens, dropout_key):
                return step(params, opt_state, tokens, dropout_key, None)
            n_extra = 1
        elif packed and not use_dropout:
            def wrapped(params, opt_state, tokens, segment_ids):
                return step(params, opt_state, tokens, None, segment_ids)
            n_extra = 1
        else:
            def wrapped(params, opt_state, tokens, dropout_key,
                        segment_ids):
                return step(params, opt_state, tokens, dropout_key,
                            segment_ids)
            n_extra = 2
        if fsdp_shardings is not None:
            return jax.jit(
                wrapped, donate_argnums=(0, 1),
                in_shardings=(fsdp_shardings, fsdp_opt_shardings, None)
                + (None,) * n_extra,
                out_shardings=(fsdp_shardings, fsdp_opt_shardings, None))
        return jax.jit(wrapped, donate_argnums=(0, 1))

    jitted = {}

    def stepper(params, opt_state, tokens, *extra):
        # the opt-state shardings depend on the params treedef, so the
        # jit wrapper is built on first call and cached
        if "fn" not in jitted:
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                zero_opt_specs(tx, params, config, mesh, data_axis,
                               model_axis),
                is_leaf=lambda x: isinstance(x, P))
            # in_shardings too: a replicated opt state passed on the
            # first call is resharded on entry, so the donated input and
            # the sharded output alias cleanly
            n_extra = (1 if use_dropout else 0) + (1 if packed else 0)
            if use_dropout and packed:
                fn = step
            elif use_dropout:
                fn = lambda p, o, t, dk: step(p, o, t, dk, None)
            elif packed:
                fn = lambda p, o, t, sg: step(p, o, t, None, sg)
            else:
                fn = lambda p, o, t: step(p, o, t, None, None)
            jitted["fn"] = jax.jit(
                fn, donate_argnums=(0, 1),
                in_shardings=(None, shardings, None) + (None,) * n_extra,
                out_shardings=(None, shardings, None))
        return jitted["fn"](params, opt_state, tokens, *extra)

    return stepper


def abstract_params(config: TransformerConfig, mesh: Optional[Mesh] = None,
                    model_axis: str = "model",
                    fsdp_axis: Optional[str] = None) -> Dict:
    """The parameter pytree as ``jax.ShapeDtypeStruct`` leaves — with the
    mesh's NamedShardings attached when ``mesh`` is given (tensor-parallel
    specs; fully-sharded when ``fsdp_axis`` is set).

    This is the restore template for sharded checkpointing: passing it as
    ``CheckpointManager.restore(..., template=...)`` makes orbax read each
    parameter directly into its device shards (no host-side full-tensor
    materialization), including restoring onto a *different* mesh topology
    than the one that saved — the TPU-native upgrade over the reference's
    whole-model h5 reload (``/root/reference/elephas/spark_model.py:355``).
    """
    shapes = jax.eval_shape(lambda k: init_params(config, k),
                            jax.random.PRNGKey(0))
    if mesh is None:
        return shapes
    specs = (fsdp_param_specs(config, mesh, data_axis=fsdp_axis,
                              model_axis=model_axis, param_shapes=shapes)
             if fsdp_axis is not None
             else param_specs(config, model_axis=model_axis, mesh=mesh))
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, s)),
        shapes, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def shard_params(params: Dict, config: TransformerConfig, mesh: Mesh,
                 model_axis: str = "model",
                 fsdp_axis: Optional[str] = None) -> Dict:
    """Place the parameter pytree onto the mesh per :func:`param_specs`
    (tensor-parallel), or — with ``fsdp_axis`` — per
    :func:`fsdp_param_specs` (fully sharded over the data axis on top of
    any tensor parallelism)."""
    specs = (fsdp_param_specs(config, mesh, data_axis=fsdp_axis,
                              model_axis=model_axis)
             if fsdp_axis is not None
             else param_specs(config, model_axis=model_axis, mesh=mesh))
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


# ---------------------------------------------------------------- decoding
def init_kv_cache(config: TransformerConfig, batch: int,
                  max_len: Optional[int] = None) -> Dict:
    """Per-layer key/value cache for autoregressive decoding:
    ``(batch, kv_heads, max_len, head_dim)`` zeros in the compute dtype —
    GQA configs carry ``num_kv_heads`` cache heads, a
    ``num_heads/num_kv_heads``-fold HBM saving at decode time.

    With ``config.kv_cache_quant`` the cache stores int8 entries plus a
    per-(position, head) f32 absmax scale — decode at long contexts is
    bound by re-reading the cache every step, so int8 halves that
    traffic on top of the GQA saving."""
    c = config
    length = max_len or c.max_seq_len
    shape = (batch, c.kv_heads, length, c.head_dim)
    if c.kv_cache_quant:
        sshape = shape[:-1] + (1,)
        return {f"layer_{i}": {"k": jnp.zeros(shape, jnp.int8),
                               "k_scale": jnp.zeros(sshape, jnp.float32),
                               "v": jnp.zeros(shape, jnp.int8),
                               "v_scale": jnp.zeros(sshape, jnp.float32)}
                for i in range(c.num_layers)}
    cache = {f"layer_{i}": {
        leaf: jnp.zeros((batch, heads, length, width), c.dtype)
        for leaf, (heads, width) in c.cache_leaves().items()}
        for i in range(c.num_layers)}
    if c.ssm is not None:
        # what a row keeps per SLOT beside its positions: zero is the
        # state of a row that has seen no token
        cache["state"] = _mamba.zero_state(c, batch)
    return cache


def _mlp_sublayer(layer: Dict, x: jnp.ndarray, c: TransformerConfig,
                  i: int, live=None):
    """Layer ``i``'s MLP sublayer (with its residual) on the inference
    paths, by the layer's kind. Returns ``(x, stats)``; ``stats`` is the
    routing counts of :func:`grouped_experts.experts_apply` for a swiglu
    expert layer, else None."""
    if not c.has_experts(i):
        return _mlp_apply(layer, x, c), None
    h2 = _norm(x, layer["ln2"], c).astype(c.dtype)
    if c.expert_variant == "swiglu":
        out, stats = _gx.experts_apply(h2, layer["moe"], c, live=live)
        return x + out, stats
    # dense gating, matching decode_step's decode-time semantics
    out, _ = _moe_block(h2, layer["moe"], c, dispatch="dense")
    if c.moe_shared_expert:
        out = out + _shared_expert(h2, layer["moe"]["shared"], c)
    return x + out, None


def _with_mixer(out: jnp.ndarray, layer: Dict, h: jnp.ndarray, state: Dict,
                new_state: Dict, i: int, c: TransformerConfig):
    """The hybrid block on the cache paths: attention's output ``out``
    plus layer ``i``'s state-space mixer over the same normed input
    ``h``, run from the rows' ``state`` (``{"layer_i": ...}``), whose
    successor goes into ``new_state``. ``out`` itself for a config
    without a mixer."""
    if c.ssm is None:
        return out
    mixed, new_state[f"layer_{i}"] = _mamba.mixer_apply(
        layer["ssm"], h, state[f"layer_{i}"], c)
    return out + mixed


def _kv_quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(B, H, D) bf16/f32 -> int8 data + (B, H, 1) absmax scale (the one
    int8 recipe lives in :mod:`.quantization`)."""
    from .quantization import quantize_weight

    q = quantize_weight(x, (-1,))
    return q.data, q.scale


def prefill_cache(params: Dict, tokens: jnp.ndarray,
                  config: TransformerConfig,
                  max_len: int) -> Tuple[jnp.ndarray, Dict]:
    """Batched prompt prefill: one forward pass over ``(batch, T)``
    prompt tokens that writes every position's k/v into a fresh decode
    cache and returns the last position's logits ``(batch, vocab)``.

    The sequential alternative — teacher-forcing the prompt through
    ``decode_step`` — re-reads all weights once PER PROMPT TOKEN; this
    pass reads them once total, turning prefill from dispatch/bandwidth-
    bound into a single MXU-bound forward. Math mirrors
    :func:`decode_step` exactly (same norms, RoPE convention, GQA
    grouping, window/alibi masks, dense MoE gating), so decode picks up
    from the cache bit-consistently with the step-by-step path.

    Uniform-length prompts only: ragged batches interleave per-row
    generation with other rows' prefill (a row past its own prompt end
    feeds back its sampled token), which a batched pass cannot express —
    ``generate`` keeps the scan path for those.
    """
    c = config
    b, t = tokens.shape
    x = embed_apply(params["embed"], tokens, c)              # (B, T, D)
    cache = init_kv_cache(c, b, max_len)
    positions = jnp.arange(t)
    q_pos = positions[:, None]
    k_pos = positions[None, :]
    mask = k_pos <= q_pos
    if c.attention_window is not None:
        mask = mask & (k_pos > q_pos - c.attention_window)
    mask = mask[None, None]                                  # (1, 1, T, T)
    scale = 1.0 / math.sqrt(c.head_dim)
    new_cache: Dict = {}
    new_state: Dict = {}
    for i in range(c.num_layers):
        layer = params[f"layer_{i}"]
        h = _norm(x, layer["ln1"], c)
        h = h.astype(c.dtype)
        if c.attention_kind == "mla":
            q_nope, q_rope = _mla.project_query(layer["attn"], h,
                                                positions, c)
            latent = _mla.project_latent(layer["attn"], h, positions, c)
            new_cache[f"layer_{i}"] = {
                "latent": cache[f"layer_{i}"]["latent"].at[:, 0, :t].set(
                    latent)}
            x = x + _mla.attend_expanded(layer["attn"], q_nope, q_rope,
                                         latent, mask[0], c)
            x, _ = _mlp_sublayer(layer, x, c, i)
            continue
        q, k, v = _qkv(layer, h, c)
        if c.positional == "rope":
            q = _apply_rope(q, positions, c)
            k = _apply_rope(k, positions, c)
        # write the whole prompt's k/v into the cache in one shot
        # ((B, H, T, D) -> cache rows [0, T))
        if c.kv_cache_quant:
            kq8, ks = _kv_quantize(k)
            vq8, vs = _kv_quantize(v)
            lc = cache[f"layer_{i}"]
            new_cache[f"layer_{i}"] = {
                "k": lc["k"].at[:, :, :t].set(kq8),
                "k_scale": lc["k_scale"].at[:, :, :t].set(ks),
                "v": lc["v"].at[:, :, :t].set(vq8),
                "v_scale": lc["v_scale"].at[:, :, :t].set(vs)}
            # attention inside prefill consumes the QUANTIZED k/v, so the
            # step-by-step path (which attends over dequantized cache
            # entries) is reproduced exactly
            k = (kq8 * ks).astype(c.dtype)
            v = (vq8 * vs).astype(c.dtype)
        else:
            lc = cache[f"layer_{i}"]
            new_cache[f"layer_{i}"] = {
                "k": lc["k"].at[:, :, :t].set(k),
                "v": lc["v"].at[:, :, :t].set(v)}
        groups = c.num_heads // c.kv_heads
        qg = q.reshape(b, c.kv_heads, groups, t, c.head_dim)
        scores = jnp.einsum("bngqk,bntk->bngqt", qg, k) * scale
        if c.positional == "alibi":
            dist = (q_pos - k_pos).astype(jnp.float32)       # (T, T)
            ab = (-_alibi_slopes(c.num_heads)[:, None, None]
                  * dist[None]).reshape(c.kv_heads, groups, t, t)
            scores = scores + ab[None]
        scores = jnp.where(mask[:, :, None], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bngqt,bntk->bngqk", weights, v)
        o = o.reshape(b, c.num_heads, t, c.head_dim)
        x = x + _with_mixer(_attn_out(layer, o, c), layer, h,
                            cache.get("state"), new_state, i, c)
        x, _ = _mlp_sublayer(layer, x, c, i)
    if new_state:
        new_cache["state"] = new_state
    logits = head_logits(params["embed"], params["final_ln"], x[:, -1],
                         head=params.get("head"), norm=c.norm,
                         rms_norm_eps=c.rms_norm_eps,
                         multipliers=c.multipliers)
    return logits, new_cache


#: widths in :func:`prefill_ladder`: the row's length, halved three times
PREFILL_RUNGS = 4


def prefill_ladder(chunk: int, length: int) -> Tuple[int, ...]:
    """Widths for :func:`decode_block`'s ``attend_widths`` when a row of
    ``length`` cached positions is filled ``chunk`` tokens at a time,
    derived from the shapes: the row's length halved
    ``PREFILL_RUNGS - 1`` times, none narrower than a chunk (512 /
    1,024 / 2,048 for 512-token chunks of a 2,048-long row)."""
    return tuple(sorted({max(length >> k, min(chunk, length))
                         for k in range(PREFILL_RUNGS)}))


def attend_width(widths: Sequence[int], need: int) -> int:
    """The width :func:`decode_block` picks on the device for a block
    whose last position is ``need - 1``: the host's copy of its
    arithmetic, for counters."""
    return next((w for w in widths if w >= need), widths[-1])


def decode_block(params: Dict, cache: Dict, tokens: jnp.ndarray, pos0,
                 config: TransformerConfig,
                 attend_widths: Sequence[int] = (),
                 last_only: bool = False
                 ) -> Tuple[jnp.ndarray, Dict]:
    """Multi-token cached decode: process ``(batch, S)`` tokens sitting
    at positions ``pos0 .. pos0+S-1`` of an ongoing sequence, reading and
    writing the rolling k/v cache, and return (logits ``(batch, S,
    vocab)`` for every block position, updated cache).

    The block generalization of :func:`decode_step` (S=1) and
    :func:`prefill_cache` (``pos0=0`` on a fresh cache): one weight read
    covers S positions, so the verify pass of speculative decoding and
    chunked continuation of long prompts run MXU-bound instead of
    weight-bandwidth-bound. Math matches ``decode_step`` exactly (norms,
    RoPE convention, GQA grouping, window/alibi masks, dense MoE gating,
    int8 cache quantization), pinned by parity tests.

    ``pos0`` may be a scalar or a ``(batch,)`` vector — per-row offsets
    are what batched speculative decoding needs, because rows accept
    different numbers of draft tokens per round. Within the block each
    query attends causally: cache positions ``<= pos0+j`` for block slot
    ``j`` (all S slots' k/v are written before attention, so intra-block
    attention sees the new keys).

    What the block WRITES goes into the whole row. What its attention
    READS is the first ``W`` cached positions: by default the row's
    whole length, which is what the S=1 step and the speculative verify
    block (a vector ``pos0``, each row at its own offset) run at. With
    ``attend_widths`` (ascending, the last one the row's length: e.g.
    :func:`prefill_ladder`) the program holds per layer a branch for
    each width and picks, on the device, the narrowest ``W >= pos0 + S``
    -- for a vector ``pos0`` from the largest offset. Everything at and
    beyond ``pos0 + S`` has zero weight under the causal mask, so every
    width that covers it gives the same result: the mask, the K/V (for
    ``attention_kind="mla"`` the latent's expansion to per-head keys and
    values), both score products, the softmax and the value product run
    ``W`` wide instead of ``max_len`` wide. A chunk of a prompt that
    fills a quarter of its row does a quarter of the attention.

    A config with a state-space mixer (``ssm``) carries the rows' state
    under ``cache["state"]``: every layer's mixer runs over the block
    from it (one token: the state's update; more: the chunk scan) and
    the returned cache holds the state after the block's last token.

    With ``last_only`` the head runs for the block's last position
    alone and the logits are ``(batch, 1, vocab)``: all a prompt's chunk
    needs, and at a vocabulary of 261,120 the difference between 1 and
    512 rows of the largest matrix in the model.
    """
    c = config
    b, s = tokens.shape
    pos0 = jnp.asarray(pos0)
    vec = pos0.ndim == 1
    length = jax.tree_util.tree_leaves(cache["layer_0"])[0].shape[2]
    widths = tuple(int(w) for w in attend_widths) or (length,)
    if list(widths) != sorted(set(widths)) or widths[-1] != length:
        raise ValueError(f"attend_widths {widths} must ascend to the "
                         f"row's length, {length}")
    # the narrowest width that covers every position a query may see,
    # picked on the device (a single width is no branch)
    pick = jnp.searchsorted(jnp.asarray(widths), jnp.max(pos0) + s,
                            side="left")
    blockpos = (pos0[:, None] + jnp.arange(s)[None, :] if vec
                else pos0 + jnp.arange(s))             # (B, S) or (S,)
    x = _times(params["embed"]["tokens"][tokens], c, "embedding")
    if c.positional == "learned":
        x = x + params["embed"]["pos"][blockpos]
    elif c.positional == "sinusoidal":
        x = x + _sinusoidal_table(blockpos, c.d_model)
    x = x.astype(c.dtype)                              # (B, S, D)
    qp = blockpos if vec else blockpos[None, :]        # (B|1, S)
    scale = 1.0 / math.sqrt(c.head_dim)
    groups = c.num_heads // c.kv_heads

    def mask_over(kpos):
        mask = kpos[None, None, :] <= qp[:, :, None]   # (B|1, S, W)
        if c.attention_window is not None:
            mask = mask & (kpos[None, None, :]
                           > qp[:, :, None] - c.attention_window)
        return mask

    def latent_attend_over(width):
        # the first `width` positions are all a query may see when they
        # cover pos0 + S
        return lambda attn, q_nope, q_rope, buf: _mla.attend_expanded(
            attn, q_nope, q_rope, buf[:, 0, :width],
            mask_over(jnp.arange(width)), c)

    def attend_over(width):
        def attend(qg, lc):
            ck, cv = lc["k"][:, :, :width], lc["v"][:, :, :width]
            if c.kv_cache_quant:
                ck = (ck * lc["k_scale"][:, :, :width]).astype(c.dtype)
                cv = (cv * lc["v_scale"][:, :, :width]).astype(c.dtype)
            kpos = jnp.arange(width)
            scores = jnp.einsum("bngsk,bntk->bngst", qg, ck) * scale
            if c.positional == "alibi":
                dist = (qp[:, :, None] - kpos[None, None, :]).astype(
                    jnp.float32)                       # (B|1, S, W)
                ab = (-_alibi_slopes(c.num_heads)[None, :, None, None]
                      * dist[:, None]).reshape(
                          dist.shape[0], c.kv_heads, groups, s, width)
                scores = scores + ab
            scores = jnp.where(mask_over(kpos)[:, None, None, :, :],
                               scores, NEG_INF)
            weights = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bngst,bntk->bngsk", weights, cv)
        return attend

    branches = [(latent_attend_over if c.attention_kind == "mla"
                 else attend_over)(w) for w in widths]
    # rope angle positions: (B, 1, S) broadcasts per-row angles over the
    # head axis of (B, H, S, K); a (S,) vector broadcasts over B and H
    rp = blockpos[:, None, :] if vec else blockpos
    if vec:
        bidx = jnp.arange(b)[:, None, None]
        hidx = jnp.arange(c.kv_heads)[None, :, None]
        widx = (bidx, hidx, blockpos[:, None, :])      # -> (B, H, S)
    new_cache: Dict = {}
    new_state: Dict = {}
    for i in range(c.num_layers):
        layer = params[f"layer_{i}"]
        h = _norm(x, layer["ln1"], c)
        h = h.astype(c.dtype)
        if c.attention_kind == "mla":
            q_nope, q_rope = _mla.project_query(layer["attn"], h,
                                                blockpos, c)
            new = _mla.project_latent(layer["attn"], h, blockpos, c)
            buf = cache[f"layer_{i}"]["latent"]
            if vec:
                buf = buf.at[jnp.arange(b)[:, None], 0, blockpos].set(new)
            else:
                buf = jax.lax.dynamic_update_slice(
                    buf, new[:, None].astype(buf.dtype), (0, 0, pos0, 0))
            new_cache[f"layer_{i}"] = {"latent": buf}
            x = x + jax.lax.switch(pick, branches, layer["attn"], q_nope,
                                   q_rope, buf)
            x, _ = _mlp_sublayer(layer, x, c, i)
            continue
        q, k_new, v_new = _qkv(layer, h, c)
        if c.positional == "rope":
            q = _apply_rope(q, rp, c)
            k_new = _apply_rope(k_new, rp, c)

        def write(buf, val):
            if vec:
                return buf.at[widx].set(val)
            return jax.lax.dynamic_update_slice(
                buf, val.astype(buf.dtype), (0, 0, pos0, 0))

        lc = cache[f"layer_{i}"]
        if c.kv_cache_quant:
            kq8, ks = _kv_quantize(k_new)
            vq8, vs = _kv_quantize(v_new)
            written = {"k": write(lc["k"], kq8),
                       "k_scale": write(lc["k_scale"], ks),
                       "v": write(lc["v"], vq8),
                       "v_scale": write(lc["v_scale"], vs)}
        else:
            written = {"k": write(lc["k"], k_new),
                       "v": write(lc["v"], v_new)}
        new_cache[f"layer_{i}"] = written
        qg = q.reshape(b, c.kv_heads, groups, s, c.head_dim)
        o = jax.lax.switch(pick, branches, qg, written)
        o = o.reshape(b, c.num_heads, s, c.head_dim)
        x = x + _with_mixer(_attn_out(layer, o, c), layer, h,
                            cache.get("state"), new_state, i, c)
        x, _ = _mlp_sublayer(layer, x, c, i)
    if new_state:
        new_cache["state"] = new_state
    logits = head_logits(params["embed"], params["final_ln"],
                         x[:, -1:] if last_only else x,
                         head=params.get("head"), norm=c.norm,
                         rms_norm_eps=c.rms_norm_eps,
                         multipliers=c.multipliers)
    return logits, new_cache


def chunked_blocks(block_fn, cache, tokens, pos0: int, chunk: int):
    """Thread ``(logits, cache)`` through ``block_fn`` over
    ``chunk``-sized column slices of ``tokens`` ``(B, T)`` starting at
    position ``pos0``. ``block_fn(cache, block, start_pos, is_first) ->
    (logits, cache)``; returns the LAST block's logits and the final
    cache. THE chunk loop — :func:`prefill_cache_chunked` and the
    serving engine's chunked admission both ride it, so chunk-boundary
    semantics live in one place."""
    logits = None
    for start in range(0, tokens.shape[1], chunk):
        logits, cache = block_fn(cache, tokens[:, start:start + chunk],
                                 pos0 + start, start == 0)
    return logits, cache


def prefill_cache_chunked(params: Dict, tokens: jnp.ndarray,
                          config: TransformerConfig, max_len: int,
                          chunk: int = 512) -> Tuple[jnp.ndarray, Dict]:
    """Chunked prompt prefill: like :func:`prefill_cache` but processing
    the prompt in ``chunk``-sized :func:`decode_block` passes, so peak
    attention memory is O(chunk * T) instead of O(T^2) — the long-prompt
    serving path (a 32k-token prompt at chunk=512 materializes 1/64th of
    the score matrix at a time). Returns the last position's logits and
    the filled cache, matching ``prefill_cache`` numerically.

    The prompt length need not divide ``chunk``: the tail block is its
    natural (smaller) size, costing at most one extra compile.
    """
    c = config
    b, _ = tokens.shape
    logits, cache = chunked_blocks(
        lambda cache, blk, pos, _first: decode_block(params, cache, blk,
                                                     pos, c),
        init_kv_cache(c, b, max_len), tokens, 0, chunk)
    return logits[:, -1], cache


def decode_step(params: Dict, cache: Dict, tokens: jnp.ndarray, pos,
                config: TransformerConfig) -> Tuple[jnp.ndarray, Dict]:
    """One autoregressive step: token ids ``(batch,)`` at position ``pos``
    -> (next-token logits ``(batch, vocab)``, updated cache).

    The incremental mirror of :func:`forward` — O(seq) per step instead
    of the O(seq^2) full recompute. ``pos`` may be a scalar (all rows at
    the same position — the plain decode loop) or a ``(batch,)`` vector
    of per-row positions, which speculative decoding and continuous
    batching need because rows advance their caches independently.

    Implemented as the S=1 case of :func:`decode_block`, so every
    config variant (GQA, window, ALiBi, int8 cache, MoE) has exactly one
    cached-attention implementation to keep bit-consistent.
    """
    logits, new_cache = decode_block(params, cache, tokens[:, None], pos,
                                     config)
    return logits[:, 0], new_cache


def _filter_logits(logits: jnp.ndarray, top_k: Optional[int],
                   top_p: Optional[float]) -> jnp.ndarray:
    """Sampling filters: keep the top-k logits and/or the nucleus (the
    smallest set of tokens whose probability mass reaches top_p); the
    rest drop to -inf. Static-shape formulations (sort + threshold), so
    the whole thing stays inside the decode scan."""
    if top_k is not None and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits, NEG_INF)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until the cumulative mass passes top_p (always
        # keeping the most probable one)
        keep_sorted = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool),
             cum[..., :-1] < top_p], axis=-1)
        # threshold = smallest kept logit
        threshold = jnp.min(jnp.where(keep_sorted, sorted_logits,
                                      jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits >= threshold, logits, NEG_INF)
    return logits


@partial(jax.jit, static_argnames=("prompt_len", "max_new_tokens",
                                   "config", "sample", "top_k", "top_p",
                                   "use_rep_penalty", "logits_processor"))
def _generate_scan(params, prompt, temperature, key, prompt_len: int,
                   max_new_tokens: int, config: TransformerConfig,
                   sample: bool, top_k: Optional[int] = None,
                   top_p: Optional[float] = None,
                   repetition_penalty=1.0, use_rep_penalty: bool = False,
                   prompt_lengths: Optional[jnp.ndarray] = None,
                   logits_processor=None):
    c = config
    batch = prompt.shape[0]
    total = prompt_len + max_new_tokens
    if max_new_tokens == 0:
        return jnp.zeros((batch, 0), jnp.int32)
    lens = (prompt_lengths if prompt_lengths is not None
            else jnp.full((batch,), prompt_len, jnp.int32))
    seen0 = jnp.zeros((batch, c.vocab_size), bool)
    if use_rep_penalty:
        # only real prompt positions mark the presence buffer (padded
        # tails scatter out of range and drop)
        valid = jnp.arange(prompt.shape[1])[None, :] < lens[:, None]
        marked = jnp.where(valid, prompt, c.vocab_size)
        seen0 = seen0.at[jnp.arange(batch)[:, None], marked].set(
            True, mode="drop")

    def next_token(logits, seen, key):
        if logits_processor is not None:
            # user constraint hook (jax-traceable): grammar masks, token
            # bans, logit biases — applied before penalties and filters,
            # so constraints bound what sampling can ever pick
            logits = logits_processor(logits)
        if use_rep_penalty:
            # CTRL-style: shrink already-emitted tokens' logits toward
            # "less likely" on whichever side of zero they sit
            p = repetition_penalty
            penalized = jnp.where(logits > 0, logits / p, logits * p)
            logits = jnp.where(seen, penalized, logits)
        if sample:
            key, sub = jax.random.split(key)
            # temperature first, then top-k/top-p: the nucleus is chosen
            # on the tempered distribution (conventional HF/CTRL order)
            filtered = _filter_logits(logits / temperature, top_k, top_p)
            nxt = jax.random.categorical(sub, filtered, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt, key

    def mark_seen(seen, nxt, t):
        if use_rep_penalty:
            # only tokens actually fed back (emitted) mark the presence
            # buffer; samples discarded for prompt positions scatter out
            # of range and drop — 'prompt or emitted so far' semantics
            mark = jnp.where(t + 1 >= lens, nxt, c.vocab_size)
            seen = seen.at[jnp.arange(batch), mark].set(True, mode="drop")
        return seen

    if prompt_lengths is None:
        # uniform prompts: batched prefill — ONE forward writes the
        # whole prompt's k/v (weights read once, not once per token),
        # then the scan covers only the generated positions
        logits0, cache = prefill_cache(params, prompt, c, total)
        nxt0, key = next_token(logits0, seen0, key)
        seen = mark_seen(seen0, nxt0, prompt_len - 1)

        def gen_step(carry, t):
            cache, prev, key, seen = carry
            logits, cache = decode_step(params, cache, prev, t, c)
            nxt, key = next_token(logits, seen, key)
            seen = mark_seen(seen, nxt, t)
            return (cache, nxt, key, seen), nxt

        if max_new_tokens == 1:
            return nxt0[:, None]
        _, rest = jax.lax.scan(gen_step, (cache, nxt0, key, seen),
                               jnp.arange(prompt_len, total - 1))
        return jnp.concatenate([nxt0[:, None], rest.T], axis=1)

    # ragged prompts: rows finish their prompts at different steps and
    # start generating while others still teacher-force, so the cache
    # fills token-by-token in one unified scan
    cache = init_kv_cache(c, batch, total)

    def step_fn(carry, t):
        cache, prev, key, seen = carry
        tok = jnp.where(t < lens,
                        prompt[:, jnp.minimum(t, prompt_len - 1)], prev)
        logits, cache = decode_step(params, cache, tok, t, c)
        nxt, key = next_token(logits, seen, key)
        seen = mark_seen(seen, nxt, t)
        return (cache, nxt, key, seen), nxt

    (_, _, _, _), sampled = jax.lax.scan(
        step_fn, (cache, prompt[:, 0], key, seen0), jnp.arange(total - 1))
    # sampled[t] is the model's token for position t+1: row b's
    # generation starts at its own prompt end, i.e. steps
    # lens[b]-1 .. lens[b]+max_new-2 (a per-row gather)
    idx = (lens[:, None] - 1) + jnp.arange(max_new_tokens)[None, :]
    return jnp.take_along_axis(sampled.T, idx, axis=1)


def generate(params: Dict, prompt: jnp.ndarray, max_new_tokens: int,
             config: TransformerConfig, temperature: float = 0.0,
             key=None, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             repetition_penalty: float = 1.0,
             prompt_lengths=None, logits_processor=None) -> jnp.ndarray:
    """Autoregressive generation: ``(batch, prompt_len)`` prompt ids ->
    ``(batch, max_new_tokens)`` sampled continuations.

    One jitted ``lax.scan`` over positions, compiled once per
    (config, shape, greedy/sampled, filters) combination — the config
    and lengths are static jit arguments, so repeated calls reuse the
    executable. Prompt positions teacher-force the cache, generation
    positions feed the previous sample back. ``temperature=0`` is greedy
    argmax; otherwise categorical sampling at the given temperature
    (``key`` required), optionally filtered to the ``top_k`` most
    probable tokens and/or the ``top_p`` nucleus.
    ``repetition_penalty > 1`` (CTRL) down-weights tokens already in the
    prompt or emitted so far.

    Ragged batches: pass right-padded prompts plus ``prompt_lengths``
    ``(batch,)`` — each row teacher-forces its own prefix and its
    continuation aligns at index 0 of the output (per-row gather).

    ``logits_processor`` is an optional jax-traceable
    ``(batch, vocab) -> (batch, vocab)`` hook applied to every step's
    logits before penalties and filters — the constraint point for
    grammar masks, token bans, or logit biases (set banned entries to
    ``-inf``; greedy and sampling both then never pick them). One
    recompile per distinct function object.
    """
    c = config
    prompt = jnp.asarray(prompt)
    _, prompt_len = prompt.shape
    total = prompt_len + max_new_tokens
    if total > c.max_seq_len:
        raise ValueError(f"prompt_len + max_new_tokens = {total} exceeds "
                         f"max_seq_len = {c.max_seq_len}")
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    if top_k is not None and top_k < 1:
        raise ValueError("top_k must be >= 1")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError("top_p must be in (0, 1]")
    if repetition_penalty < 1.0:
        raise ValueError("repetition_penalty must be >= 1")
    if key is None:
        key = jax.random.PRNGKey(0)
    if prompt_lengths is not None:
        prompt_lengths = jnp.asarray(prompt_lengths, jnp.int32)
        if prompt_lengths.shape != (prompt.shape[0],):
            raise ValueError("prompt_lengths must be (batch,)")
    return _generate_scan(params, prompt, jnp.float32(temperature), key,
                          prompt_len, int(max_new_tokens), c,
                          temperature > 0,
                          int(top_k) if top_k is not None else None,
                          float(top_p) if top_p is not None else None,
                          jnp.float32(repetition_penalty),
                          repetition_penalty != 1.0,
                          prompt_lengths,
                          logits_processor=logits_processor)


@partial(jax.jit, static_argnames=("prompt_len", "max_new_tokens",
                                   "config", "num_beams", "eos_id"))
def _beam_search_scan(params, prompt, prompt_len: int, max_new_tokens: int,
                      config: TransformerConfig, num_beams: int,
                      length_penalty, eos_id: Optional[int]):
    c = config
    batch = prompt.shape[0]
    total = prompt_len + max_new_tokens
    bb = batch * num_beams

    # beams ride the batch axis of one shared decode program; identical
    # prefixes mean the prompt prefills ONCE per row (not per beam) and
    # the resulting cache/logits repeat across the beam axis
    logits_row, cache_row = prefill_cache(params, prompt, c, total)
    logits = jnp.repeat(logits_row, num_beams, axis=0)        # (B*K, V)
    cache = jax.tree_util.tree_map(
        lambda a: jnp.repeat(a, num_beams, axis=0), cache_row)

    # only beam 0 is live initially (identical beams would tie)
    scores0 = jnp.tile(jnp.asarray([0.0] + [NEG_INF] * (num_beams - 1),
                                   jnp.float32), (batch, 1))   # (B, K)
    tokens0 = jnp.zeros((batch, num_beams, max_new_tokens), jnp.int32)
    finished0 = jnp.zeros((batch, num_beams), bool)

    def step(carry, t):
        cache, logits, scores, tokens, finished = carry
        logp = jax.nn.log_softmax(logits, axis=-1)            # (B*K, V)
        logp = logp.reshape(batch, num_beams, c.vocab_size)
        if eos_id is not None:
            # finished beams may only emit eos, at no additional cost
            frozen = jnp.full_like(logp[0, 0], NEG_INF).at[eos_id].set(0.0)
            logp = jnp.where(finished[..., None], frozen, logp)
        flat = (scores[..., None] + logp).reshape(batch, -1)  # (B, K*V)
        top_scores, top_flat = jax.lax.top_k(flat, num_beams)  # (B, K)
        beam_idx = top_flat // c.vocab_size
        token = top_flat % c.vocab_size

        # reorder everything along the beam axis
        tokens = jnp.take_along_axis(tokens, beam_idx[..., None], axis=1)
        tokens = tokens.at[:, :, t].set(token)
        finished = jnp.take_along_axis(finished, beam_idx, axis=1)
        if eos_id is not None:
            finished = finished | (token == eos_id)
        gather = (beam_idx
                  + jnp.arange(batch)[:, None] * num_beams).reshape(-1)
        cache = jax.tree_util.tree_map(lambda a: a[gather], cache)

        logits, cache = decode_step(params, cache, token.reshape(-1),
                                    prompt_len + t, c)
        return (cache, logits, top_scores, tokens, finished), None

    (cache, _, scores, tokens, finished), _ = jax.lax.scan(
        step, (cache, logits, scores0, tokens0, finished0),
        jnp.arange(max_new_tokens))

    # Google-NMT length penalty ((5 + L) / 6) ** alpha
    if eos_id is not None:
        lengths = jnp.where(
            finished,
            1.0 + jnp.argmax(tokens == eos_id, axis=-1).astype(jnp.float32),
            float(max_new_tokens))
    else:
        lengths = jnp.full(scores.shape, float(max_new_tokens))
    norm = ((5.0 + lengths) / 6.0) ** length_penalty
    ranked = scores / norm
    order = jnp.argsort(-ranked, axis=1)
    return (jnp.take_along_axis(tokens, order[..., None], axis=1),
            jnp.take_along_axis(ranked, order, axis=1))


def beam_search(params: Dict, prompt: jnp.ndarray, max_new_tokens: int,
                config: TransformerConfig, num_beams: int = 4,
                length_penalty: float = 0.0,
                eos_id: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decoding: ``(batch, prompt_len)`` prompts ->
    ``(sequences, scores)`` with sequences ``(batch, num_beams,
    max_new_tokens)`` sorted best-first.

    Beams ride the batch axis of the same jitted KV-cache decode program
    ``generate`` uses (one compiled scan; cache reordered by a beam
    gather each step — static shapes throughout). ``eos_id`` freezes
    finished beams; ``length_penalty`` applies the GNMT normalization
    ``((5+L)/6)**alpha`` at ranking time.
    """
    c = config
    prompt = jnp.asarray(prompt)
    _, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > c.max_seq_len:
        raise ValueError("prompt_len + max_new_tokens exceeds max_seq_len")
    if num_beams < 1:
        raise ValueError("num_beams must be >= 1")
    return _beam_search_scan(params, prompt, prompt_len,
                             int(max_new_tokens), c, int(num_beams),
                             jnp.float32(length_penalty), eos_id)
