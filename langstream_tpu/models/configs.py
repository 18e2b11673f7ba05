"""Model architecture configs + presets for the supported families.

Families cover the build's target configs: Gemma-2B (single chip), Llama-3-8B
(TP over v5e-8), Mixtral-8x7B (MoE, expert-parallel), plus tiny test configs.
Field semantics follow the HF config.json conventions so `models.loader` can
map checkpoints mechanically.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Optional


# every leaf a page pool may hold a token's state in, by page: K, V, an
# indexer's key and, for a model that keeps a LATENT in place of K and V, that
# latent (`ModelConfig.page_leaves` says which a model has; the engine's page
# copies, zeroes and snapshots go over exactly these)
PAGE_LEAVES = ("k", "v", "ik", "lat")

# what a kind of attention layer may have of its own (`ModelConfig.window_attention`)
KIND_FIELDS = (
    "n_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "attn_gate",
)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: Optional[int] = None  # defaults to d_model // n_heads
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 8192
    activation: str = "silu"  # silu (llama/mixtral) | gelu (gemma)
    tie_embeddings: bool = False
    # gemma-style stabilisers
    embedding_scale: bool = False  # multiply embeddings by sqrt(d_model)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # MoE (mixtral-style); n_experts=0 → dense FFN
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # expert capacity = ceil(T*k*factor/E) (≤0 → lossless C=T, quadratic in T)
    moe_capacity_factor: float = 2.0
    # post-norm variants (gemma2) — not needed for the supported presets yet
    dtype: str = "bfloat16"
    # when set, full-sequence attention runs as RING attention over this
    # shard_map axis (sequence/context parallelism for long inputs); set via
    # parallel.sp.sequence_parallel_forward, never directly in presets
    ring_axis: Optional[str] = None
    # when set, the Pallas attention kernels run under a shard_map over this
    # jax Mesh's "model" axis (ops/attention._per_kv_head); set by
    # ServingEngine from its own mesh, never directly in presets
    kernel_mesh: Optional[Any] = None
    # attention kernel choice: "auto" (pallas on TPU when shapes fit),
    # "pallas" (force, interpret-mode off-TPU), "jnp" (reference path)
    attention_impl: str = "auto"
    # KV cache storage: "model" (activation dtype) | "int8" (per-token
    # per-head symmetric quant — halves decode's cache read stream; the
    # dequant fuses into the attention einsum's operand load)
    kv_cache_dtype: str = "model"
    # llama-3.1-style NTK rope scaling (HF rope_scaling type "llama3"):
    # frequencies below the low-freq wavelength threshold are divided by
    # ``factor``; a smooth ramp interpolates through the transition band
    rope_scaling_factor: Optional[float] = None
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_seq_len: int = 8192
    # ``rope_scaling_type`` "yarn" (HF rope_scaling type "yarn", in DeepSeek-V3's
    # form; the factor and the original length are the two fields above):
    # frequency i of a rotary of width d is blended from f_i towards f_i /
    # factor along a ramp between the dimensions that turn ``beta_fast`` and
    # ``beta_slow`` times over the original length (`yarn_blend`), the tables
    # are multiplied by mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    # and the softmax scale by mscale(factor, mscale_all_dim)^2 where
    # ``mscale_all_dim`` is set (``attn_scale``), mscale(f, m) = 0.1 m ln f + 1.
    # Read by a model that keeps a latent alone: ``__post_init__`` refuses it
    # elsewhere, whose kernels scale by 1 / sqrt(head_dim)
    rope_scaling_type: str = "llama3"
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 0.0
    # layer pattern (hybrid models): the kinds of one PERIOD of layers,
    # each "linear_attention" (gated delta rule, a recurrent state per
    # sequence: ops/gated_delta.py), "conv" (a gated short convolution, whose
    # state is its tail alone: ``conv_kernel``) or "full_attention"; n_layers is a whole
    # number of periods and every kind is a stack of its own under
    # params["layers"][kind]. Empty: every layer is the one block above, in
    # one stack. The full layers of a pattern are the OLMo block: the norm
    # on each sublayer's OUTPUT, RMSNorm over the whole width of q and of k
    # before the heads are split (``qk_norm``), rotary or none (``rope``).
    layer_pattern: tuple = ()
    linear_n_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    # beta in (0, 2) instead of (0, 1): the state's transition may flip sign
    linear_allow_neg_eigval: bool = False
    # a "conv" layer (LFM2's): [B | C | u] = in_proj(norm(x)), a causal
    # depthwise convolution of ``conv_kernel`` taps over B * u with no bias and
    # no activation, out_proj(C * that); a sequence keeps the last
    # ``conv_kernel - 1`` inputs of the convolution, d_model wide, a layer
    conv_kernel: int = 0
    output_norm: bool = False  # full layers: x + norm(f(x)), not x + f(norm(x))
    qk_norm: bool = False
    rope: bool = True  # False: attention turns nothing
    # window layers (a "sliding_attention" kind in ``layer_pattern``): query
    # i sees keys i - sliding_window + 1 .. i, turned by rotary; beside them
    # the "full_attention" layers of such a model are causal over everything
    # and turn NOTHING (no positional turn at all). Each kind keeps pages of
    # its own (serving/pagepool.py): a window row holds a ring of the last
    # ``sliding_window`` tokens plus the dispatch in flight
    sliding_window: int = 0
    rope_interleaved: bool = False  # pairs (2i, 2i+1), not (i, i + D/2)
    # "rms" | "layer" (subtract the mean, divide by sqrt(var + eps), scale,
    # no bias, in float32); the eps is ``rms_norm_eps`` either way
    norm: str = "rms"
    logit_scale: float = 1.0  # logits = logit_scale * h @ E^T
    # The block of a model with window layers is ONE block (transformer
    # `_parallel_layer`): parallel, x + Attn(u) + MoE(u) with u =
    # norm(x), one norm a layer, and an expert layer that holds a share
    # (`moe_ffn_held`). ``sliding_window``, ``rope_interleaved``, ``norm``,
    # ``moe_scoring`` and ``n_shared_experts`` are read by that block alone,
    # so ``__post_init__`` refuses them without window layers, and window
    # layers without experts. The expert layer's: the router's scoring
    # ("softmax" over the chosen logits | "sigmoid" of every logit, the
    # chosen weights divided by their sum) and shared experts whose MEAN is
    # added once.
    moe_scoring: str = "softmax"
    n_shared_experts: int = 0
    # The no-drop expert layer (`moe_ffn_held`), read by the parallel block
    # always and by the sequential block (`_ffn_half`) where ``experts_held``
    # is set: the router is ``n_experts`` wide, this program holds experts
    # ``experts_held`` = (first, count) of them and computes their part of
    # the result for the tokens routed to them, dropping nothing, each an
    # expert of width ``moe_d_ff`` (0: ``d_ff``). ``experts_held`` (): the
    # parallel block holds all of them; the sequential block keeps `moe_ffn`
    # and its capacity rule (``moe_capacity_factor``), which reads no
    # ``moe_d_ff``: an expert width apart from ``d_ff`` is refused there
    moe_d_ff: int = 0
    experts_held: tuple = ()
    # RMSNorm of q and of k over each HEAD's ``head_dim`` (one weight vector
    # shared by the heads), after the heads are split and before rotary;
    # ``qk_norm`` is the norm over the whole width before the split
    qk_norm_heads: bool = False
    # A model that fills a BLOCK of tokens by denoising (docs/SERVING.md
    # "A model that fills blocks"): attention is causal across blocks of
    # ``block_length`` positions and two-way inside one, the logits at a
    # position score the token AT it, and a row advances by a block:
    # denoise passes fix the open positions whose confidence exceeds
    # ``confidence_threshold``, and at least ``block_schedule[step]`` of the
    # most confident, over ``denoise_steps`` steps; ``mask_token_id`` stands
    # at an open position and is never an answer. 0: the model is
    # autoregressive and none of the four is read
    block_length: int = 0
    denoise_steps: int = 0
    confidence_threshold: float = 1.0
    mask_token_id: Optional[int] = None
    # A model whose attention reads a LEARNED SELECTION (docs/SERVING.md "A
    # model whose attention reads a learned selection"): an indexer of
    # ``index_n_heads`` heads of ``index_head_dim`` and ONE key head scores
    # every visible token for a query, `sum_j w_j relu(qI_j . kI)` in
    # float32, and the query attends to the ``index_topk`` tokens of largest
    # score (a tie to the lower position), one selection a token and layer
    # for all heads. The indexer's key is a third leaf a token of the cache
    # and the page pool, ``"ik"``. ``index_topk`` 0: none, and neither other
    # field is read
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # Rotary in three position streams (m-rope): of the head's
    # ``head_dim / 2`` frequencies the first ``mrope_section[0]`` turn by a
    # token's temporal position, the next by its height, the rest by its
    # width. (): one stream. Text has the three equal, which is the plain
    # rotary: a [B, S] position is that
    mrope_section: tuple = ()
    # The indexer's rotary and input, where they are not the above: its heads'
    # FIRST ``index_rope_dim`` lanes are turned, in the attention's own pairs
    # and frequencies (0: the whole head, pairs (i, i + Di/2), frequencies
    # theta^(-2i/Di)), and its queries read the attention's normed input
    # ("hidden") or the model's query latent ("query_latent": a model with
    # ``q_lora_rank``)
    index_rope_dim: int = 0
    index_query_input: str = "hidden"
    # LATENT attention (docs/SERVING.md "A model that keeps a latent, not keys
    # and values"): the query through a normed latent of ``q_lora_rank``, keys
    # and values through ONE normed latent of ``kv_lora_rank`` a token and one
    # rotary key of ``qk_rope_head_dim`` shared by all heads; a head's q.k is
    # over ``qk_nope_head_dim + qk_rope_head_dim``, its value ``v_head_dim``
    # wide, which need not be the key's width. A token's cache is (latent |
    # rotary key), ONE leaf ``"lat"`` of the cache and the page pool in place
    # of ``"k"`` and ``"v"``; a decode step attends in the latent space (the
    # up-projection absorbed into the query and the output), a segment over
    # the latents re-expanded. Two reads: under a learned selection where the
    # model has an indexer (``index_topk`` > 0, its key a second leaf
    # ``"ik"``), else DENSE, every cached latent of the row read a step and
    # nothing of an indexer traced. ``kv_lora_rank`` 0: none, and none of the
    # five is read
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the first ``n_leading_dense`` layers carry a dense FFN of ``d_ff`` in
    # place of the expert layer: a stack of their own, ``params["dense_layers"]``,
    # run before the expert layers' scan (pool layers 0 .. n_leading_dense - 1).
    # In a model with a ``layer_pattern`` they are layers of their KIND
    # (``params["dense_layers"][kind]``, a stack a kind that has one): the
    # periods that hold them run ahead of the period loop, each layer at its
    # own place in its kind's pages and state (`dense_of`)
    n_leading_dense: int = 0
    # the no-drop expert layer's router (`_route_all`): ``router_bias`` adds a
    # float32 vector a layer, ``lp["router_bias"]``, to the scores that CHOOSE
    # the experts and not to the weights; the chosen weights, normalised, are
    # multiplied by ``routed_scaling``; ``router_norm_eps`` is added to the sum
    # the sigmoid scores are divided by (0: nothing is added, and traced)
    router_bias: bool = False
    routed_scaling: float = 1.0
    router_norm_eps: float = 0.0
    # An attention geometry A KIND (docs/SERVING.md "A model whose kinds of
    # layer keep different latents"): in a model that keeps a latent, a
    # ``layer_pattern`` of "full_attention" and "sliding_attention" layers is
    # the SEQUENTIAL pre-norm block for both, and the window kind may have its
    # own heads, latent ranks, head widths and rotary base: ``window_attention``
    # holds them as (field, value) pairs over `KIND_FIELDS`, what it leaves out
    # is the model's. The fields above are the FULL kind's, and the indexer is
    # the full kind's alone; `of_kind` gives a kind's geometry as a config of
    # its own, which every function below the layer reads (``attn_scale``,
    # ``latent_width``, ``latent_key_width``, ``rope_dim``, ``page_leaves``,
    # ``kv_bytes_per_token`` answer for the kind they are asked of). The
    # window kind's tokens live in the pool's window group, ONE leaf ``"lat"``
    # of its own width.
    window_attention: tuple = ()
    # the normed latents are multiplied by sqrt(d_model / rank) (the query's by
    # ``q_lora_rank``, the key-value latent's by ``kv_lora_rank``; the rotary
    # key is not): a latent's up-projection sees the variance a full-width
    # input would give. A token's cached latent is the rescaled one
    latent_rescale: bool = False
    # "" | "headwise": o_h <- sigmoid(u W_g)_h o_h, one scalar a head and token
    # from the layer's normed input, on the head's output before ``wo``
    attn_gate: str = ""
    # set by `of_kind` alone: this config IS the named kind's view of a model
    kind_view: str = ""

    def of_kind(self, kind: str) -> "ModelConfig":
        """The attention geometry of the model's layers of ``kind``: the model
        itself but for the window kind of a model that keeps a latent, whose
        view carries ``window_attention``'s fields in the model's and no
        indexer."""
        if kind != "sliding_attention" or self.kind_view or not self.latent_kinds:
            return self
        return _kind_view(self, kind)

    @property
    def attn_window(self) -> int:
        """What a query of THIS kind's layers sees behind it (a kind's view,
        `of_kind`): ``sliding_window`` positions with itself for the window
        kind of a model whose kinds keep latents, else 0, everything."""
        return self.sliding_window if self.kind_view == "sliding_attention" else 0

    @property
    def latent_kinds(self) -> bool:
        """Window layers beside full ones in a model that keeps a LATENT:
        both are the sequential block, each kind at its own geometry."""
        return self.has_window and self.has_latent

    @property
    def parallel_block(self) -> bool:
        """Window layers over K and V: the parallel block (command-a-plus's)."""
        return self.has_window and not self.has_latent

    @property
    def has_indexer(self) -> bool:
        return self.index_topk > 0

    @property
    def has_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def yarn(self) -> bool:
        return bool(self.rope_scaling_factor) and self.rope_scaling_type == "yarn"

    def yarn_mscale(self, mscale: float) -> float:
        """YaRN's ``0.1 x mscale x ln(factor) + 1`` (1 up to a factor of 1)."""
        factor = float(self.rope_scaling_factor or 1.0)
        return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0

    @property
    def yarn_blend(self) -> tuple:
        """(low, high) of YaRN's ramp over a rotary's ``rope_dim / 2``
        frequencies: up to ``low`` a frequency stays f_i, from ``high`` on it
        is f_i / factor, between them the two are blended linearly. The
        dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
        original length, rounded outwards and kept inside the rotary."""
        d, base = self.rope_dim, float(self.rope_theta)
        original = float(self.rope_scaling_original_max_seq_len)

        def turns(rotations: float) -> float:
            return d * math.log(original / (rotations * 2.0 * math.pi)) / (2.0 * math.log(base))

        low = max(math.floor(turns(self.rope_scaling_beta_fast)), 0)
        high = min(math.ceil(turns(self.rope_scaling_beta_slow)), d - 1)
        return low, high

    @property
    def attn_scale(self) -> float:
        """What a latent model's scores are multiplied by before the softmax:
        1 / sqrt(the expanded head's q.k width), times YaRN's
        mscale(factor, ``mscale_all_dim``)^2 where that is set."""
        scale = self.resolved_head_dim**-0.5
        if self.yarn and self.rope_scaling_mscale_all_dim:
            scale *= self.yarn_mscale(self.rope_scaling_mscale_all_dim) ** 2
        return scale

    @property
    def latent_width(self) -> int:
        """What a token's latent holds: the key-value latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_key_width(self) -> int:
        """The width the latent is KEPT at in the cache and the page pool:
        ``latent_width`` rounded up to whole 128-lane rows, the tail zeros (576
        is kept at 640). The chip's tiled layouts pad a minor dimension to 128
        lanes in HBM as in VMEM, so 576 lanes occupy 640 either way; kept
        explicitly, the padding is the program's, the memory plan counts it
        and a page is one aligned DMA (``index_key_width`` has the case where
        it was measured)."""
        return -(-self.latent_width // 128) * 128

    @property
    def rope_dim(self) -> int:
        """The width the rotary turns: the head's, or a latent model's one
        rotary key's."""
        return self.qk_rope_head_dim if self.has_latent else self.resolved_head_dim

    @property
    def kv_head_pack(self) -> int:
        """KV heads that share a 128-lane row of the cache and the page pool:
        2 for heads of 64 (an even number of them; K and V alone, in the
        activation dtype, one page group), else 1. A leaf is then
        [.., Hkv / 2, T, 128], heads 2j and 2j + 1 side by side: the chip's
        tiled layouts keep a minor dimension of 64 at 128 lanes (a pool twice
        its bytes, and at a width under a lane row the compiler may lay a leaf
        out pages-minor: ``index_key_width``), and every paged and prefill
        kernel takes the packed leaf as a head of 128 (`ops/attention.
        pair_queries`: a query reads its own half)."""
        narrow = self.resolved_head_dim == 64 and self.n_kv_heads % 2 == 0
        plain = not (
            self.has_latent or self.has_indexer or self.has_window or self.fills_blocks
            or self.ring_axis is not None or self.kv_cache_dtype == "int8"
        )
        return 2 if narrow and plain else 1

    def kv_bytes_per_token(self, itemsize: int = 2, kind: str = "full_attention") -> int:
        """Bytes a token holds in the page pool's group of ``kind`` (the
        full-attention group, or the window group), over that kind's layers,
        at ``itemsize`` a value (an int8 pool's float32 scale a head beside
        its values): what `make_page_pool`'s leaves come to a token."""
        layers, of = self.n_layers_of(kind), self.of_kind(kind)
        if of.has_latent:
            token = of.latent_key_width * itemsize
        elif of.kv_cache_dtype == "int8":
            token = 2 * of.n_kv_heads * (of.resolved_head_dim + 4)
        else:
            token = 2 * of.n_kv_heads * of.resolved_head_dim * itemsize
        if of.has_indexer:
            token += of.index_key_width * itemsize
        return layers * token

    @property
    def index_key_width(self) -> int:
        """The width the indexer's key is KEPT at in the cache and the page
        pool: ``index_head_dim`` rounded up to whole 128-lane rows, the tail
        zeros. At a width under a lane row (64) the chip's compiler lays the
        leaf out pages-minor for the gather by page and relays the WHOLE leaf
        every layer and step (PERF.md section 6, PR 43)."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def page_leaves(self) -> tuple:
        """The leaves a token has in the page pool's full-attention group:
        ``"k"`` and ``"v"``, or for a model that keeps a latent ``"lat"`` alone
        in their place; after them the indexer's key, ``"ik"``, where the
        model has an indexer."""
        kept = ("lat",) if self.has_latent else ("k", "v")
        return kept + (("ik",) if self.has_indexer else ())

    @property
    def has_window(self) -> bool:
        """Window layers, and with them a second page group. Over K and V
        (``parallel_block``) they imply the parallel block whose expert layer
        holds a share, and the expert counts its decode chunks AND prefill
        segments return (MOE_HELD_COUNTS); over a latent (``latent_kinds``)
        the block stays the sequential one."""
        return "sliding_attention" in self.layer_pattern

    @property
    def holds_experts(self) -> bool:
        """The expert layer is `moe_ffn_held` (and the programs count
        MOE_HELD_COUNTS): the parallel block's always, the sequential
        block's where ``experts_held`` says so."""
        return self.parallel_block or bool(self.experts_held)

    @property
    def fills_blocks(self) -> bool:
        return self.block_length > 0

    @property
    def block_schedule(self) -> tuple:
        """How many open positions each denoise step of a block fixes at
        least: ``block_length // denoise_steps`` each, the remainder on the
        first steps."""
        b, t = self.block_length, self.denoise_steps
        return tuple(b // t + (step < b % t) for step in range(t)) if t else ()

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def held_experts(self) -> tuple:
        """(first, count) of the routed experts this program holds."""
        return tuple(self.experts_held) or (0, self.n_experts)

    @property
    def is_recurrent(self) -> bool:
        """A sequence keeps a row of state beside its pages: the delta rule's
        state and its convolution's tail, or a "conv" layer's tail alone."""
        return "linear_attention" in self.layer_pattern or "conv" in self.layer_pattern

    def dense_of(self, kind: str) -> int:
        """How many of the leading dense layers of a pattern model are of
        ``kind``: where the kind's expert stack starts among its layers.
        Leading dense layer i is of kind ``layer_pattern[i % period]``, whether
        it lies inside the first periods or stands before them (``dense_ahead``)."""
        pattern, n = self.layer_pattern, self.n_leading_dense
        return sum(pattern[i % len(pattern)] == kind for i in range(n)) if pattern else 0

    @property
    def dense_ahead(self) -> int:
        """Leading dense layers that stand BEFORE the first period: all of
        them where ``n_layers`` is ``n_leading_dense`` + whole periods and not
        whole periods itself (LFM2's lie INSIDE its first period: 0)."""
        pattern = self.layer_pattern
        return self.n_leading_dense if pattern and self.n_layers % len(pattern) else 0

    @property
    def n_periods(self) -> int:
        if not self.layer_pattern:
            return 0
        return (self.n_layers - self.dense_ahead) // len(self.layer_pattern)

    def n_layers_of(self, kind: str) -> int:
        """Layers of ``kind`` in the model; the page pool's layer axis is
        ``n_layers_of("full_attention")``, the window group's
        ``n_layers_of("sliding_attention")``."""
        if not self.layer_pattern:
            return self.n_layers if kind == "full_attention" else 0
        ahead = self.dense_of(kind) if self.dense_ahead else 0
        return ahead + self.n_periods * self.layer_pattern.count(kind)

    @property
    def linear_key_dim(self) -> int:
        return self.linear_n_heads * self.linear_key_head_dim

    @property
    def linear_value_dim(self) -> int:
        return self.linear_n_heads * self.linear_value_head_dim

    @property
    def linear_conv_dim(self) -> int:
        return 2 * self.linear_key_dim + self.linear_value_dim

    def __post_init__(self) -> None:
        if self.kind_view:  # a kind's view of a model that passed what follows
            return
        if self.layer_pattern:
            unknown = set(self.layer_pattern) - {
                "linear_attention", "conv", "full_attention", "sliding_attention"
            }
            # whole periods, the leading dense layers inside the first of them
            # or standing before it (``dense_ahead``)
            period = len(self.layer_pattern)
            if unknown or (
                self.n_layers % period and (self.n_layers - self.n_leading_dense) % period
            ):
                raise ValueError(
                    f"{self.name}: layer_pattern {self.layer_pattern} over "
                    f"{self.n_layers} layers"
                    + (f" ({self.n_leading_dense} leading dense)" if self.n_leading_dense else "")
                )
            if self.n_layers % period and not (self.has_window and self.kv_lora_rank > 0):
                raise ValueError(
                    f"{self.name}: layer_pattern {self.layer_pattern} over {self.n_layers} "
                    f"layers: {self.n_leading_dense} leading dense layers BEFORE the first "
                    "period belong to a model whose attention kinds keep latents (a "
                    "sequential local cache is cut by whole periods)"
                )
            if "conv" in self.layer_pattern and (
                self.conv_kernel < 2 or "linear_attention" in self.layer_pattern
            ):
                raise ValueError(
                    f"{self.name}: conv layers need conv_kernel >= 2 and no "
                    "linear_attention layer beside them (one tail a state row)"
                )
            if self.parallel_block and (
                self.sliding_window < 1 or self.is_recurrent or not self.is_moe
            ):
                raise ValueError(
                    f"{self.name}: window layers need sliding_window >= 1, no "
                    "recurrent layer beside them and an expert layer "
                    "(n_experts > 0): their block is the parallel one"
                )
            if self.latent_kinds and (self.sliding_window < 1 or self.is_recurrent):
                raise ValueError(
                    f"{self.name}: window layers over a latent need sliding_window >= 1 "
                    "and no recurrent layer beside them"
                )
        if self.conv_kernel and "conv" not in self.layer_pattern:
            raise ValueError(
                f"{self.name}: conv_kernel belongs to a layer_pattern with conv layers"
            )
        window_only = {
            "sliding_window": self.sliding_window > 0,
            "rope_interleaved": self.rope_interleaved,
            "norm": self.norm != "rms",
            "moe_scoring": self.moe_scoring != "softmax",
            "n_shared_experts": self.n_shared_experts > 0,
        }
        if self.has_latent:  # its sequential block reads these three too
            for key in ("rope_interleaved", "moe_scoring", "n_shared_experts"):
                window_only[key] = False
        if "conv" in self.layer_pattern:  # the router of its sequential block
            window_only["moe_scoring"] = False
        if not self.has_window and any(window_only.values()):
            raise ValueError(
                f"{self.name}: {', '.join(k for k, on in window_only.items() if on)} "
                "belong to the block of a model with window layers "
                "(layer_pattern with sliding_attention); no other block reads them"
            )
        if self.experts_held:
            first, count = self.experts_held
            if first < 0 or count < 1 or first + count > self.n_experts:
                raise ValueError(
                    f"{self.name}: experts_held {self.experts_held} of {self.n_experts}"
                )
            if "linear_attention" in self.layer_pattern or self.output_norm:
                raise ValueError(
                    f"{self.name}: experts_held belongs to a pre-norm block "
                    "(the sequential or the parallel one), not to a layer "
                    "pattern with delta-rule layers or an output norm"
                )
        if self.moe_d_ff > 0 and not self.holds_experts:
            raise ValueError(
                f"{self.name}: moe_d_ff is read by the no-drop expert layer "
                "(window layers, or experts_held); moe_ffn's experts are d_ff wide"
            )
        if self.qk_norm and self.qk_norm_heads:
            raise ValueError(f"{self.name}: qk_norm and qk_norm_heads are two norms, not one")
        if self.fills_blocks:
            contradicts = {
                f"denoise_steps {self.denoise_steps} outside 1..{self.block_length}":
                    not 1 <= self.denoise_steps <= self.block_length,
                f"mask_token_id {self.mask_token_id} outside the vocabulary":
                    self.mask_token_id is None
                    or not 0 <= self.mask_token_id < self.vocab_size,
                f"confidence_threshold {self.confidence_threshold} outside (0, 1]":
                    not 0.0 < self.confidence_threshold <= 1.0,
                "a layer pattern (a block-filling model has one kind of layer)":
                    bool(self.layer_pattern),
                "ring_axis": self.ring_axis is not None,
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: block_length {self.block_length} with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        elif self.denoise_steps or self.mask_token_id is not None:
            raise ValueError(
                f"{self.name}: denoise_steps and mask_token_id belong to a model "
                "that fills blocks (block_length > 0)"
            )

        # a pattern of attention kinds alone over a latent (``latent_kinds``, or
        # full layers alone) is the one pattern a latent and an indexer take
        other_kinds = bool(set(self.layer_pattern) - {"full_attention", "sliding_attention"})
        if self.has_indexer:
            contradicts = {
                # (but the pattern of latent kinds: window layers over a latent)
                "a layer pattern, a window or a recurrent layer":
                    bool(self.layer_pattern) and (other_kinds or not self.has_latent),
                "fills_blocks (block_length > 0)": self.fills_blocks,
                "an output norm": self.output_norm,
                "an int8 KV cache": self.kv_cache_dtype == "int8",
                "ring_axis": self.ring_axis is not None,
                f"index_n_heads {self.index_n_heads} or index_head_dim "
                f"{self.index_head_dim} under 1, or an odd index_head_dim":
                    self.index_n_heads < 1 or self.index_head_dim < 2
                    or self.index_head_dim % 2 == 1,
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: an indexer (index_topk {self.index_topk}) with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        elif self.index_n_heads or self.index_head_dim:
            raise ValueError(
                f"{self.name}: index_n_heads and index_head_dim belong to a model "
                "with an indexer (index_topk > 0)"
            )
        if self.index_rope_dim or self.index_query_input != "hidden":
            contradicts = {
                "no indexer (index_topk 0)": not self.has_indexer,
                f"index_rope_dim {self.index_rope_dim} odd or over index_head_dim":
                    self.index_rope_dim % 2 == 1 or self.index_rope_dim > self.index_head_dim,
                f"index_rope_dim {self.index_rope_dim} apart from the rotary's width "
                f"{self.rope_dim} (the indexer turns by the attention's own angles)":
                    bool(self.index_rope_dim) and self.index_rope_dim != self.rope_dim,
                f"index_query_input {self.index_query_input!r} (hidden | query_latent)":
                    self.index_query_input not in ("hidden", "query_latent"),
                "index_query_input query_latent without a query latent (q_lora_rank 0)":
                    self.index_query_input == "query_latent" and self.q_lora_rank < 1,
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: the indexer's rotary and input with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        if self.has_latent:
            contradicts = {
                # (but window layers beside full ones, each kind a latent of its own)
                "a layer pattern, a window or a recurrent layer":
                    bool(self.layer_pattern) and (other_kinds or not self.has_window),
                "a norm other than rms (the parallel block's)": self.norm != "rms",
                "fills_blocks (block_length > 0)": self.fills_blocks,
                "m-rope (mrope_section)": bool(self.mrope_section),
                "an int8 KV cache": self.kv_cache_dtype == "int8",
                "an output norm": self.output_norm,
                "qk_norm or qk_norm_heads (the latents are normed, not the heads)":
                    self.qk_norm or self.qk_norm_heads,
                "ring_axis": self.ring_axis is not None,
                "an attention soft cap": self.attn_logit_softcap is not None,
                f"q_lora_rank {self.q_lora_rank}, qk_nope_head_dim "
                f"{self.qk_nope_head_dim}, qk_rope_head_dim {self.qk_rope_head_dim} or "
                f"v_head_dim {self.v_head_dim} under 1, or an odd qk_rope_head_dim":
                    min(self.q_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim,
                        self.v_head_dim) < 1
                    or self.qk_rope_head_dim % 2 == 1,
                f"v_head_dim {self.v_head_dim} apart from qk_nope_head_dim + "
                "qk_rope_head_dim and no multiple of 8 under an indexer (the selected read's "
                "kernels take a value of its own width in whole sublane rows, as the dense "
                "read's do)":
                    self.has_indexer and self.v_head_dim % 8 != 0
                    and self.v_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim,
                f"head_dim {self.head_dim} (a latent model's head is qk_nope_head_dim "
                "+ qk_rope_head_dim: leave it unset)": self.head_dim is not None,
                f"n_kv_heads {self.n_kv_heads} apart from n_heads (the expanded "
                "form has a key and a value a head)": self.n_kv_heads != self.n_heads,
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: a latent (kv_lora_rank {self.kv_lora_rank}) with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        elif self.q_lora_rank or self.qk_nope_head_dim or self.qk_rope_head_dim or self.v_head_dim:
            raise ValueError(
                f"{self.name}: q_lora_rank, qk_nope_head_dim, qk_rope_head_dim and "
                "v_head_dim belong to a model with a latent (kv_lora_rank > 0)"
            )
        if self.latent_rescale or self.attn_gate:
            contradicts = {
                "no latent (kv_lora_rank 0: the latent's block alone reads them)":
                    not self.has_latent,
                f"attn_gate {self.attn_gate!r} ('' | headwise)":
                    self.attn_gate not in ("", "headwise"),
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: latent_rescale / attn_gate with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        if self.window_attention:
            own = dict(self.window_attention)
            view = self.of_kind("sliding_attention")
            contradicts = {
                "no window layers over a latent (layer_pattern with sliding_attention "
                "and kv_lora_rank > 0)": not self.latent_kinds,
                f"fields outside {KIND_FIELDS}": bool(set(own) - set(KIND_FIELDS))
                    or len(own) != len(self.window_attention),
                "a rank, a head width or n_heads under 1, or an odd qk_rope_head_dim":
                    min(view.n_heads, view.q_lora_rank, view.kv_lora_rank,
                        view.qk_nope_head_dim, view.qk_rope_head_dim, view.v_head_dim) < 1
                    or view.qk_rope_head_dim % 2 == 1,
            }
            if any(contradicts.values()):
                raise ValueError(
                    f"{self.name}: window_attention {self.window_attention} with "
                    + "; ".join(k for k, on in contradicts.items() if on)
                )
        if self.rope_scaling_type not in ("llama3", "yarn"):
            raise ValueError(
                f"{self.name}: rope_scaling_type {self.rope_scaling_type!r} (llama3 | yarn)"
            )
        if self.rope_scaling_type == "yarn" and not (
            self.has_latent and self.rope_scaling_factor
            and self.rope_scaling_beta_fast > self.rope_scaling_beta_slow > 0
        ):
            raise ValueError(
                f"{self.name}: rope_scaling_type yarn belongs to a model with a latent "
                "(kv_lora_rank > 0: its block alone reads the softmax factor, attn_scale), "
                "with a rope_scaling_factor and beta_fast > beta_slow > 0"
            )
        if self.n_leading_dense and not (
            (self.has_latent or "conv" in self.layer_pattern)
            and self.experts_held and 0 < self.n_leading_dense < self.n_layers
        ):
            raise ValueError(
                f"{self.name}: n_leading_dense {self.n_leading_dense} belongs to a model "
                "with a latent, or with a pattern of conv layers, whose later layers "
                "hold experts (experts_held), and leaves an expert layer"
            )
        if (
            self.router_bias or self.routed_scaling != 1.0 or self.router_norm_eps
        ) and not self.holds_experts:
            raise ValueError(
                f"{self.name}: router_bias and routed_scaling (router_norm_eps with them) "
                "are read by the no-drop expert layer's router (window layers, or experts_held)"
            )
        if self.mrope_section and (
            len(self.mrope_section) != 3
            or sum(self.mrope_section) != self.resolved_head_dim // 2
        ):
            raise ValueError(
                f"{self.name}: mrope_section {self.mrope_section} is three sections "
                f"that sum to half a head ({self.resolved_head_dim // 2})"
            )

    @property
    def resolved_head_dim(self) -> int:
        if self.has_latent:  # the expanded form's head: q.k over this, v over v_head_dim
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def _attn_params(self) -> int:
        d, hd = self.d_model, self.resolved_head_dim
        if not self.has_latent:
            return d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        return (
            d * self.q_lora_rank + self.q_lora_rank * self.n_heads * hd
            + d * self.latent_width
            + self.kv_lora_rank * self.n_heads * (self.qk_nope_head_dim + self.v_head_dim)
            + self.n_heads * self.v_head_dim * d
        )

    @property
    def approx_params(self) -> int:
        """Rough parameter count (placement decisions, not accounting)."""
        d = self.d_model
        attn = self._attn_params
        if self.latent_kinds:  # each attention kind at its own geometry
            window = self.n_layers_of("sliding_attention")
            embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
            ffn = 3 * d * self.expert_d_ff * (
                self.held_experts[1] + self.n_shared_experts
            ) + d * self.n_experts if self.is_moe else 3 * d * self.d_ff
            return (
                (self.n_layers - window) * attn
                + window * self.of_kind("sliding_attention")._attn_params
                + self.n_layers * ffn + self.n_leading_dense * (3 * d * self.d_ff - ffn) + embed
            )
        if self.layer_pattern:
            linear = 2 * d * (self.linear_key_dim + self.linear_value_dim) + (
                self.linear_value_dim * d
            )
            n_lin = self.n_layers_of("linear_attention")
            n_conv = self.n_layers_of("conv")  # in_proj d x 3d, out_proj d x d
            mixers = (
                n_lin * linear + n_conv * 4 * d * d + (self.n_layers - n_lin - n_conv) * attn
            )
            embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
            ffn = dense = 3 * d * self.d_ff
            if self.is_moe:  # what is HELD here, not the published count
                ffn = 3 * d * self.expert_d_ff * (
                    self.held_experts[1] + self.n_shared_experts
                ) + d * self.n_experts
            return mixers + self.n_layers * ffn + self.n_leading_dense * (dense - ffn) + embed
        if self.is_moe:  # what is HELD here, where the layer holds a share
            held = self.held_experts[1] if self.experts_held else self.n_experts
            ffn = (held + self.n_shared_experts) * 3 * d * self.expert_d_ff + d * self.n_experts
        else:
            ffn = 3 * d * self.d_ff
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        dense = self.n_leading_dense * (3 * d * self.d_ff - ffn)
        return self.n_layers * (attn + ffn) + dense + embed

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


@functools.lru_cache(maxsize=None)
def _kind_view(config: ModelConfig, kind: str) -> ModelConfig:
    """`ModelConfig.of_kind`'s view, built once a (model, kind)."""
    own = {k: v for k, v in config.window_attention if k in KIND_FIELDS}
    return dataclasses.replace(
        config, **own, n_kv_heads=own.get("n_heads", config.n_heads), index_topk=0,
        index_n_heads=0, index_head_dim=0, index_rope_dim=0, index_query_input="hidden",
        window_attention=(), kind_view=kind,
    )


def _preset(**kw) -> ModelConfig:
    return ModelConfig(**kw)


MODEL_PRESETS: dict[str, ModelConfig] = {
    # test-size configs (CI / CPU mesh) — dims divisible by 8 for TP tests
    "tiny-test": _preset(
        name="tiny-test",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=8,
        n_kv_heads=4,
        d_ff=128,
        # wide enough for the RAG examples' stuffed prompts (context + history)
        max_seq_len=1024,
    ),
    "tiny-moe-test": _preset(
        name="tiny-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=8,
        n_kv_heads=4,
        d_ff=128,
        max_seq_len=256,
        n_experts=8,
        n_experts_per_tok=2,
    ),
    "gemma-2b": _preset(
        name="gemma-2b",
        vocab_size=256000,
        d_model=2048,
        n_layers=18,
        n_heads=8,
        n_kv_heads=1,
        d_ff=16384,
        head_dim=256,
        rope_theta=10000.0,
        activation="gelu",
        tie_embeddings=True,
        embedding_scale=True,
        max_seq_len=8192,
    ),
    "llama-3-8b": _preset(
        name="llama-3-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        rms_norm_eps=1e-5,
        max_seq_len=8192,
    ),
    "llama-3-8b-shallow": _preset(
        # 8B widths with 4 layers: single-chip perf probing without 16G of HBM
        name="llama-3-8b-shallow",
        vocab_size=128256,
        d_model=4096,
        n_layers=4,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        rms_norm_eps=1e-5,
        max_seq_len=8192,
    ),
    "llama-3.1-8b": _preset(
        # llama-3-8b widths + NTK rope scaling → 128k context
        name="llama-3.1-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=500000.0,
        rms_norm_eps=1e-5,
        max_seq_len=131072,
        rope_scaling_factor=8.0,
        rope_scaling_low_freq_factor=1.0,
        rope_scaling_high_freq_factor=4.0,
        rope_scaling_original_max_seq_len=8192,
    ),
    "mixtral-8x1b": _preset(
        # mixtral-8x7b architecture (8 experts, top-2, 3.5x ffn ratio,
        # GQA kv=8, rope 1e6) scaled to what ONE 16GiB v5e chip serves in
        # int8 (~8.9B total / ~1.06B per expert): the single-chip bench row
        # for the Mixtral MoE serving path — the full-size preset above
        # shards over dp×ep×tp instead (see
        # __graft_entry__._mixtral_sharding_lower_check)
        name="mixtral-8x1b",
        vocab_size=32000,
        d_model=2048,
        n_layers=24,
        n_heads=16,
        n_kv_heads=8,
        d_ff=7168,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_seq_len=32768,
        n_experts=8,
        n_experts_per_tok=2,
    ),
    "mixtral-8x7b": _preset(
        name="mixtral-8x7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_seq_len=32768,
        n_experts=8,
        n_experts_per_tok=2,
    ),
    "tiny-hybrid-test": _preset(
        # the olmo-hybrid layer pattern at test size: d 64, 8 layers,
        # linear heads 4 x 8/16, conv 4 (tests/test_olmo_hybrid.py)
        name="tiny-hybrid-test",
        vocab_size=512,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        max_seq_len=1024,
        layer_pattern=("linear_attention",) * 3 + ("full_attention",),
        linear_n_heads=4,
        linear_key_head_dim=8,
        linear_value_head_dim=16,
        linear_conv_kernel=4,
        linear_allow_neg_eigval=True,
        output_norm=True,
        qk_norm=True,
        rope=False,
    ),
    "tiny-window-moe-test": _preset(
        # the command-a-plus block at test size (tests/test_cohere2_moe.py):
        # (window x3, full) x2, a window of 2 pages of 8, 16 sigmoid-routed
        # experts top-4 of which this share holds 4, two averaged shared
        # experts, LayerNorm, the parallel block, a tied head
        name="tiny-window-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=8,
        n_heads=8,
        n_kv_heads=2,
        d_ff=32,
        head_dim=16,
        rope_theta=50000.0,
        rms_norm_eps=1e-5,
        max_seq_len=256,
        tie_embeddings=True,
        layer_pattern=("sliding_attention",) * 3 + ("full_attention",),
        sliding_window=16,
        rope_interleaved=True,
        norm="layer",
        n_experts=16,
        n_experts_per_tok=4,
        moe_scoring="sigmoid",
        n_shared_experts=2,
        experts_held=(0, 4),
    ),
    "tiny-blockfill-moe-test": _preset(
        # a model that fills blocks of 4 tokens by denoising, at test size
        # (tests/test_block_diffusion.py, tests/test_sdar_moe.py): the
        # sequential block under a block-causal mask, per-head q/k norm, 16
        # softmax-routed experts top-4 of width 32 apart from d_ff, none
        # dropped, an untied head
        name="tiny-blockfill-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=8,
        n_kv_heads=2,
        d_ff=128,
        head_dim=16,
        rope_theta=1000000.0,
        max_seq_len=256,
        n_experts=16,
        n_experts_per_tok=4,
        moe_d_ff=32,
        experts_held=(0, 16),
        qk_norm_heads=True,
        block_length=4,
        denoise_steps=4,
        confidence_threshold=0.9,
        mask_token_id=511,
    ),
    "tiny-sparse-moe-test": _preset(
        # a model whose attention reads a learned selection, at test size
        # (tests/test_sparse_attention.py): the sequential block, per-head
        # q/k norm, m-rope, an indexer of 2 heads of 16 that keeps 8 tokens
        # (a 40-token sequence selects), 16 softmax-routed experts top-4 of
        # width 32, none dropped, an untied head
        name="tiny-sparse-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=4,
        n_heads=8,
        n_kv_heads=2,
        d_ff=128,
        head_dim=16,
        rope_theta=10000000.0,
        rms_norm_eps=1e-6,
        max_seq_len=256,
        n_experts=16,
        n_experts_per_tok=4,
        moe_d_ff=32,
        experts_held=(0, 16),
        qk_norm_heads=True,
        index_n_heads=2,
        index_head_dim=16,
        index_topk=8,
        mrope_section=(2, 3, 3),
    ),
    "tiny-latent-moe-test": _preset(
        # a model that keeps a latent in place of K and V, at test size
        # (tests/test_latent_attention.py): a leading dense layer and three
        # expert layers, a query latent of 32, a key-value latent of 16 and a
        # rotary key of 8 for 4 heads of 8 + 8 (values 16), interleaved
        # rotary, an indexer of 2 heads of 16 (8 turned) that reads the query
        # latent and keeps 8 tokens (a 40-token sequence selects), 8
        # sigmoid-routed experts top-2 chosen under a bias, scaled by 2.5, of
        # which this share holds 4, one shared expert, an untied head
        name="tiny-latent-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_seq_len=256,
        rope_interleaved=True,
        n_experts=8,
        n_experts_per_tok=2,
        moe_d_ff=32,
        experts_held=(0, 4),
        moe_scoring="sigmoid",
        n_shared_experts=1,
        router_bias=True,
        routed_scaling=2.5,
        n_leading_dense=1,
        q_lora_rank=32,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=16,
        index_n_heads=2,
        index_head_dim=16,
        index_topk=8,
        index_rope_dim=8,
        index_query_input="query_latent",
    ),
    "tiny-latent-dense-moe-test": _preset(
        # a model that keeps a latent and has NO indexer, at test size
        # (tests/test_latent_dense_attention.py): every cached latent is read
        # a step. A leading dense layer and three expert layers, a query
        # latent of 32, a key-value latent of 16 and a rotary key of 8 for 4
        # heads whose q.k is 16 + 8 = 24 wide and whose value 16 (Dv != Dk),
        # interleaved rotary under YaRN: of the rotary's 4 frequencies the
        # ramp (low 1, high 3) keeps 0 and 1, blends 2 and divides 3 by the
        # factor 8, and the softmax scale carries (0.1 ln 8 + 1)^2 = 1.459; 8
        # sigmoid-routed experts top-2 chosen under a non-zero bias, scaled
        # by 2.5, of which this share holds 4, one shared expert, an untied
        # head
        name="tiny-latent-dense-moe-test",
        vocab_size=512,
        d_model=64,
        n_layers=4,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        rope_theta=100.0,
        rms_norm_eps=1e-5,
        max_seq_len=256,
        rope_interleaved=True,
        rope_scaling_type="yarn",
        rope_scaling_factor=8.0,
        rope_scaling_original_max_seq_len=128,
        rope_scaling_beta_fast=4.0,
        rope_scaling_beta_slow=1.0,
        rope_scaling_mscale=1.0,
        rope_scaling_mscale_all_dim=1.0,
        n_experts=8,
        n_experts_per_tok=2,
        moe_d_ff=32,
        experts_held=(0, 4),
        moe_scoring="sigmoid",
        n_shared_experts=1,
        router_bias=True,
        routed_scaling=2.5,
        n_leading_dense=1,
        q_lora_rank=32,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
    ),
    "tiny-dots3-test": _preset(
        # two KINDS of latent layer in one model, at test size
        # (tests/test_dots3_note.py): a leading dense layer of the full kind
        # BEFORE the first period, then (full, window x 3) x 2. The full kind:
        # 4 heads of 8 + 8 (values 8: narrower than the key), a key-value
        # latent of 16, base 1e6, an indexer of 2 heads of 16 (8 turned) on the
        # query latent that keeps 16 tokens. The window kind: 2 heads of 24 + 8
        # (values 8), a key-value latent of 32, base 1e3, the last 9 tokens.
        # Both rescale their normed latents and gate each head's output. 8
        # sigmoid-routed experts top-2 under a non-zero bias, of which this
        # share holds 4, one shared expert, an untied head
        name="tiny-dots3-test",
        vocab_size=512,
        d_model=64,
        n_layers=9,
        n_heads=4,
        n_kv_heads=4,
        d_ff=128,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_seq_len=256,
        rope_interleaved=True,
        layer_pattern=(
            "full_attention", "sliding_attention", "sliding_attention", "sliding_attention",
        ),
        sliding_window=9,
        n_experts=8,
        n_experts_per_tok=2,
        moe_d_ff=32,
        experts_held=(0, 4),
        moe_scoring="sigmoid",
        n_shared_experts=1,
        router_bias=True,
        n_leading_dense=1,
        q_lora_rank=32,
        kv_lora_rank=16,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=8,
        index_n_heads=2,
        index_head_dim=16,
        index_topk=16,
        index_rope_dim=8,
        index_query_input="query_latent",
        window_attention=(
            ("n_heads", 2), ("kv_lora_rank", 32), ("qk_nope_head_dim", 24),
            ("rope_theta", 1000.0),
        ),
        latent_rescale=True,
        attn_gate="headwise",
    ),
    "tiny-lfm2-test": _preset(
        # the LFM2-MoE block at test size (tests/test_lfm2_moe.py): (conv,
        # conv, full, conv) x2, the first two layers dense; a convolution of 3
        # taps; 4 heads of 64 over 2 KV heads (two to a lane row in cache and
        # pool), per-head q/k norm; 8 sigmoid-routed experts top-2 of width
        # 32 chosen under a bias, all held, the sum under + 1e-6; a tied head
        name="tiny-lfm2-test",
        vocab_size=512,
        d_model=256,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        head_dim=64,
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_seq_len=1024,
        tie_embeddings=True,
        layer_pattern=("conv", "conv", "full_attention", "conv"),
        conv_kernel=3,
        qk_norm_heads=True,
        n_experts=8,
        n_experts_per_tok=2,
        moe_d_ff=32,
        experts_held=(0, 8),
        moe_scoring="sigmoid",
        router_bias=True,
        router_norm_eps=1e-6,
        n_leading_dense=2,
    ),
    "olmo-hybrid-7b": _preset(
        # allenai/Olmo-Hybrid-7B config.json: (gated delta-rule x3, full
        # attention) x8; full layers MHA 30 x 128 without rotary
        name="olmo-hybrid-7b",
        vocab_size=100352,
        d_model=3840,
        n_layers=32,
        n_heads=30,
        n_kv_heads=30,
        d_ff=11008,
        head_dim=128,
        rms_norm_eps=1e-6,
        max_seq_len=65536,
        layer_pattern=("linear_attention",) * 3 + ("full_attention",),
        linear_n_heads=30,
        linear_key_head_dim=96,
        linear_value_head_dim=192,
        linear_conv_kernel=4,
        linear_allow_neg_eigval=True,
        output_norm=True,
        qk_norm=True,
        rope=False,
    ),
}


@dataclass
class GenerationOptions:
    """Per-request sampling options (the knobs the reference forwards to the
    OpenAI API: max-tokens/temperature/top-p, AIChatCompletionsConfiguration)."""

    max_new_tokens: int = 256
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0  # 0 → disabled
    top_p: float = 1.0
    stop_tokens: tuple[int, ...] = ()
    seed: Optional[int] = None
    # request lifecycle (serving/engine.py): wall-clock budget in seconds
    # from submit. A request past its deadline finishes with
    # finish_reason="deadline" at the next chunk boundary (partial tokens
    # kept); one that expires while still QUEUED fails with
    # DeadlineExceededError instead of burning a slot it can no longer use.
    deadline_s: Optional[float] = None
    # cap on time spent waiting for a slot; exceeded → fails in queue
    max_queue_wait_s: Optional[float] = None
    # multi-LoRA multiplexing (serving/adapters.py): name of a registered
    # adapter to serve this request with — the per-request POLICY input of
    # the agentic tier. None/"" = the base model (device pool row 0).
    adapter: Optional[str] = None
    # constrained decoding (serving/constrain.py): OpenAI-style
    # response_format — {"type": "json_schema", "json_schema": {...}} or
    # {"type": "regex", "regex": "..."}. The engine compiles it to a
    # token DFA at submit and guarantees the completion stays inside it.
    response_format: Optional[dict] = None
    # mid-derivation grammar resume (docs/SERVING.md §18): the DFA state
    # the constrained stream had already reached when its replica died /
    # its KV migrated. The prompt then carries the partial derivation and
    # generation continues FROM this state instead of restarting the
    # grammar at state 0 — what makes a constrained stream survivable on
    # the fleet wire. Only meaningful alongside the SAME response_format
    # (the state indexes that grammar's DFA); validated against the
    # compiled DFA at submit.
    grammar_resume_state: Optional[int] = None
    # multi-tenant overload control (serving/tenancy.py, docs/SERVING.md
    # §19): the tenant this request is billed and scheduled under. The
    # gateway stamps it from the langstream tenant id (a client-supplied
    # `langstream-tenant` header wins); None lands in the shared
    # "default" tenant.
    tenant: Optional[str] = None
    # scheduling priority WITHIN the tenant (low | normal | high): breaks
    # ties among one tenant's own queued requests and is the admission
    # class the brownout ladder sheds first (level 3 rejects "low").
    # Never a cross-tenant queue jump — fair share is weight-only.
    priority: str = "normal"
    # per-request cost budget in TOKENS (prompt + generated): generation
    # finishes with finish_reason="length" once the budget is spent, and
    # a prompt that cannot afford a single generated token is rejected at
    # submit. Feeds the tenant's token-rate quota accounting.
    max_cost_tokens: Optional[int] = None

    @staticmethod
    def from_dict(d: dict) -> "GenerationOptions":
        stops = d.get("stop-tokens", d.get("stop_tokens", ()))
        deadline = d.get("deadline", d.get("deadline-s", d.get("deadline_s")))
        queue_wait = d.get(
            "max-queue-wait", d.get("max-queue-wait-s", d.get("max_queue_wait_s"))
        )
        response_format = d.get("response-format", d.get("response_format"))
        resume = d.get(
            "grammar-resume-state", d.get("grammar_resume_state")
        )
        priority = str(d.get("priority") or "normal").lower()
        if priority not in ("low", "normal", "high"):
            raise ValueError(
                f"unknown priority {priority!r}; supported: low, normal, high"
            )
        cost = d.get("max-cost-tokens", d.get("max_cost_tokens"))
        return GenerationOptions(
            # `max-new-tokens` is the spelling every example pipeline uses;
            # the remote providers read both, so this one must too
            max_new_tokens=int(
                d.get("max-tokens")
                or d.get("max-new-tokens")
                or d.get("max_new_tokens")
                or 256
            ),
            temperature=float(d.get("temperature", 0.0)),
            top_k=int(d.get("top-k", d.get("top_k", 0))),
            top_p=float(d.get("top-p", d.get("top_p", 1.0))),
            stop_tokens=tuple(int(t) for t in stops),
            seed=d.get("seed"),
            deadline_s=float(deadline) if deadline is not None else None,
            max_queue_wait_s=float(queue_wait) if queue_wait is not None else None,
            adapter=(str(d["adapter"]) if d.get("adapter") else None),
            response_format=(
                dict(response_format) if response_format else None
            ),
            grammar_resume_state=(
                int(resume) if resume is not None else None
            ),
            tenant=(str(d["tenant"]) if d.get("tenant") else None),
            priority=priority,
            max_cost_tokens=(int(cost) if cost is not None else None),
        )
