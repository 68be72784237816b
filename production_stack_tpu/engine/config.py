"""Engine configuration objects.

These play the role of the ``vllm serve`` flags the reference's Helm chart
renders (reference helm/templates/deployment-vllm-multi.yaml:57-103:
--max-model-len, --dtype, --tensor-parallel-size, --enable-chunked-prefill,
--enable-prefix-caching), re-expressed for a JAX engine.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax.numpy as jnp

from production_stack_tpu.qos import parse_priority

_DTYPE_MAP = {
    "bfloat16": jnp.bfloat16,
    "float32": jnp.float32,
    "float16": jnp.float16,
}


# The architectures read as a Llama shape (from_hf_config's last
# branch): as ``architectures`` gives them, or as ``model_type``.
_LLAMA_SHAPES = frozenset(
    f"{family}{suffix}" for family in ("llama", "mistral", "qwen2")
    for suffix in ("", "forcausallm"))


def _expert_parallel_share(hf: dict):
    """(expert_parallel_size, expert_parallel_rank) of a config.json
    that says which block of the routed experts this engine holds."""
    ep = int(hf.get("expert_parallel_size", 1))
    rank = int(hf.get("expert_parallel_rank", 0))
    if not 0 <= rank < ep:
        raise ValueError(
            f"expert_parallel_rank {rank} is not one of "
            f"expert_parallel_size {ep} blocks")
    return ep, rank


@dataclasses.dataclass
class ModelConfig:
    """Architecture hyperparameters (HF-config compatible field names)."""

    name: str = "tiny-llama"
    # llama | opt | gpt2 | mistral | qwen2 | mixtral | qwen3_next | jamba
    # | lfm2_moe | longcat_flash | glm4_moe_lite | granitemoehybrid
    # | exaone_moe | sdar_moe (models/registry.py FAMILIES)
    architecture: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 22
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # OPT/GPT-2 specifics
    do_layer_norm_before: bool = True
    activation: str = "silu"  # silu (llama) | relu (opt) | gelu (gpt2)
    # Qwen2-style q/k/v projection biases on the llama-family body.
    attention_bias: bool = False
    # Mixtral-style sparse MoE (architecture == "mixtral").
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # Qwen3-Next hybrid decoders (architecture == "qwen3_next",
    # models/qwen3_next.py). Layer i is gated full attention when
    # (i + 1) % full_attention_interval == 0 and a Gated DeltaNet
    # (linear attention) layer otherwise; 0 = every layer is full
    # attention (every other architecture). The linear layers keep a
    # recurrent state per sequence instead of pages
    # (engine/kv_cache.py state slots). Which layers those are, and
    # what one keeps, is its family's to say (models/registry.py).
    full_attention_interval: int = 0
    # Share of each head's dimensions the rotary embedding turns.
    partial_rotary_factor: float = 1.0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # Sparse block: ``num_experts`` routed experts are HELD by this
    # engine, block ``expert_parallel_rank`` of ``expert_parallel_size``
    # equal blocks; the router is as wide as all of them
    # (num_experts * expert_parallel_size, the published count) and a
    # token keeps its num_experts_per_tok choices, of which this
    # engine computes the ones it holds (ops/moe.py).
    num_experts: int = 0
    expert_parallel_size: int = 1
    expert_parallel_rank: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    # Jamba hybrid decoders (architecture == "jamba", models/jamba.py).
    # Layer i is attention (no positional encoding) when
    # i % attn_layer_period == attn_layer_offset and a Mamba-1 mixer
    # otherwise, which keeps per sequence the selective scan's state
    # h [mamba_d_inner, mamba_d_state] and the last mamba_d_conv - 1
    # inputs of its convolution; mamba_d_inner = mamba_expand *
    # hidden_size.
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # LFM2-MoE hybrid decoders (architecture == "lfm2_moe",
    # models/lfm2_moe.py). ``layer_types`` lists every layer as "conv"
    # (a gated short convolution, which keeps per sequence the last
    # conv_L_cache - 1 inputs of its convolution and nothing else) or
    # "full_attention" (QK-normed GQA over pages); the published order
    # follows no period. The first num_dense_layers feed-forwards are
    # SwiGLU MLPs of intermediate_size, the rest routed experts
    # (num_experts held, as above) chosen by sigmoid score plus a
    # learned bias and weighted by the scores alone over their sum.
    layer_types: tuple = ()
    conv_L_cache: int = 3
    num_dense_layers: int = 0
    # LongCat-Flash decoders (architecture == "longcat_flash",
    # models/longcat_flash.py). A layer is two latent-attention (MLA)
    # sublayers and two dense SwiGLU feed-forwards of
    # intermediate_size around one routed-expert branch. A sublayer
    # caches per token one latent of kv_lora_rank values and one
    # rotary key of qk_rope_head_dim shared by every head, and nothing
    # else; a query head is qk_nope_head_dim + qk_rope_head_dim wide, a
    # value head v_head_dim. The two scales are the published
    # mla_scale_q_lora / mla_scale_kv_lora (1.0: off). The router is a
    # softmax over the routed experts (num_experts held, as above) and
    # zero_expert_num identity experts after them, chosen with a
    # learned bias, weighted by routed_scaling_factor times the
    # scores alone.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_q_scale: float = 1.0
    mla_kv_scale: float = 1.0
    zero_expert_num: int = 0
    routed_scaling_factor: float = 1.0
    # GLM-4 MoE lite decoders (architecture == "glm4_moe_lite",
    # models/glm4_moe_lite.py): one MLA sublayer a layer (the fields
    # above, both scales 1), the first num_dense_layers feed-forwards
    # dense SwiGLUs of intermediate_size, the rest num_experts routed
    # experts chosen by sigmoid score plus a learned bias and weighted
    # routed_scaling_factor times the scores over their sum, beside a
    # shared expert of shared_expert_intermediate_size added whole.
    # num_nextn_predict_layers: multi-token-prediction layers the
    # engine keeps (0 or 1): one more decoder layer with a cache entry
    # of its own, which drafts inside the deferred burst. The engine's
    # configuration sets it to 0 where scheduler.draft_module is off:
    # the module is then neither made nor given pages.
    num_nextn_predict_layers: int = 0
    # Granite-MoE-hybrid decoders (architecture == "granitemoehybrid",
    # models/granitemoehybrid.py). ``layer_types`` lists every layer
    # as "mamba" (a Mamba-2 mixer: mamba_n_heads heads of mamba_d_head
    # channels, each with a state of mamba_d_state numbers a channel
    # and one scalar decay a head; B and C shared by all heads, one
    # group; mamba_d_inner = mamba_n_heads * mamba_d_head; prefill in
    # the matrix form mamba_chunk_size tokens at a time) or "attention" (grouped
    # queries over pages, no position term, scores scaled by
    # attention_multiplier). Every layer's feed-forward is num_experts
    # held experts of moe_intermediate_size under a softmax router
    # beside a shared expert of shared_expert_intermediate_size added
    # whole. The embedding is scaled by embedding_multiplier, each
    # sublayer's output by residual_multiplier, and the logits are
    # divided by logits_scaling.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # EXAONE-MoE decoders (architecture == "exaone_moe",
    # models/exaone_moe.py). ``layer_types`` lists every layer as
    # "sliding_attention" (a query sees the sliding_window keys that
    # end with its own; rotary; its K/V is a ring of sliding_window
    # places a sequence in the state pool, never pages) or
    # "full_attention" (the whole row over pages, no position term).
    # Norms on each sublayer's output; the first num_dense_layers
    # feed-forwards dense, the rest the expert block of glm4_moe_lite.
    sliding_window: int = 0
    # SDAR-MoE block-diffusion decoders (architecture == "sdar_moe",
    # models/sdar_moe.py): the Qwen3-MoE layer (per-head q/k norms,
    # softmax top-k of num_experts held experts) under sight by block:
    # positions come in blocks of diffusion_block_length (a power of
    # two), a place not known yet enters as mask_token_id's embedding,
    # and a block is generated by up to diffusion_steps denoising
    # passes that commit its places by diffusion_remasking
    # (ops/sampling.py REMASKING_STRATEGIES; the dynamic rule's
    # diffusion_confidence_threshold) and one store pass. The three
    # last are a request's defaults (denoising_steps,
    # remasking_strategy, confidence_threshold).
    diffusion_block_length: int = 0
    mask_token_id: int = 0
    diffusion_steps: int = 0
    diffusion_remasking: str = "low_confidence_dynamic"
    diffusion_confidence_threshold: float = 0.9
    # Weight-only quantization: none | int8 (engine/quantization.py).
    quantization: str = "none"
    # Decode attention implementation:
    #   auto            -> pallas on TPU, xla elsewhere (resolved by the
    #                      model runner at init)
    #   xla             -> gather-based reference (ops/attention.py)
    #   pallas          -> Pallas kernel (ops/paged_attention_pallas.py)
    #   pallas-interpret-> Pallas interpreter mode (CPU testing)
    attention_impl: str = "auto"
    # Per-shape overrides resolved by the model runner's compile probe:
    # decode and prefill kernels degrade to XLA *independently* (a
    # Mosaic failure in one must not discard the other). None =
    # follow attention_impl.
    attention_impl_decode: Optional[str] = None
    attention_impl_prefill: Optional[str] = None
    # Unified-step ([R, W] mixed batch) kernel, resolved separately: the
    # fused ragged kernel (pallas_ragged) is served under an explicit
    # 'pallas' where it compiles, never under auto
    # (model_runner.PALLAS_RAGGED_IN_AUTO); None = compose the family
    # prefill impl (model_runner._resolve_unified_impl).
    attention_impl_unified: Optional[str] = None

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads

    @property
    def jax_dtype(self):
        return _DTYPE_MAP[self.dtype]

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def family(self):
        """What the architecture's family declares
        (models/registry.py)."""
        from production_stack_tpu.models.registry import family
        return family(self.architecture)

    @property
    def layer_is_linear(self) -> tuple:
        """Per layer: True where the layer keeps a recurrent state
        (Gated DeltaNet, a Mamba mixer) and no pages, False where it
        attends over the paged cache. The pattern is static and the
        family's to give; a family that declares none has pages in
        every layer."""
        layers = self.family.recurrent_layers
        if layers is None:
            return (False,) * self.num_hidden_layers
        return layers(self)

    @property
    def num_kv_layers(self) -> int:
        """Layers whose K and V live in pages."""
        return self.layer_is_linear.count(False)

    @property
    def has_recurrent_state(self) -> bool:
        return any(self.layer_is_linear)

    @property
    def page_cache(self):
        """What the paged cache holds a token, as the family declares
        it (models/registry.py ``PageCache``): the paged entries, the
        heads and rows of one plane, and the planes an entry has (2: K
        and V; 1: a latent stored once). A family that declares
        nothing keeps K and V of ``num_key_value_heads`` heads of
        ``head_dim`` in every layer that is not recurrent."""
        from production_stack_tpu.models.registry import page_cache
        return page_cache(self)

    @property
    def cache_entry_is_state(self) -> tuple:
        """Per cache entry: True where it is a recurrent layer's state
        pool, False where it is pages. One entry a layer, unless the
        family declares its paged entries itself."""
        if self.family.page_cache is None:
            return self.layer_is_linear
        return (False,) * self.page_cache.entries

    @property
    def has_latent_cache(self) -> bool:
        """The paged entries are one plane each: a latent in the place
        of a (K, V) pair."""
        return self.page_cache.planes == 1

    @property
    def has_draft_module(self) -> bool:
        """The family declares a draft module and the configuration
        keeps it: the deferred burst drafts with it."""
        return (self.family.draft_module
                and self.num_nextn_predict_layers >= 1)

    @property
    def block_length(self) -> int:
        """Positions a block of a family that generates by diffusion
        over blocks (models/registry.py ``Family.block``); 0 for a
        family that generates left to right."""
        block = self.family.block
        return block(self) if block is not None else 0

    @property
    def router_width(self) -> int:
        """Outputs the router chooses among: the published count of
        routed experts and, after them, the zero-compute ones."""
        return (self.num_experts * self.expert_parallel_size
                + self.zero_expert_num)

    def recurrent_state_shapes(self):
        """One sequence's state in one recurrent layer, as its family
        declares it: the shapes of the recurrence's own state
        (float32; None for a layer that keeps none) and of the causal
        convolution's tail of inputs (model dtype)."""
        from production_stack_tpu.models.registry import state_pools
        return tuple(entry and tuple(entry[0])
                     for entry in state_pools(self))

    def recurrent_state_bytes(self) -> int:
        """Bytes of one sequence's recurrent state over all recurrent
        layers (0 for a model with none)."""
        if not self.has_recurrent_state:
            return 0
        per_layer = sum(
            math.prod(shape) * jnp.dtype(
                self.jax_dtype if dtype == "model" else dtype).itemsize
            for shape, dtype in self.family.state(self))
        return per_layer * self.layer_is_linear.count(True)

    @classmethod
    def _from_sdar_moe(cls, hf: dict, name: str) -> "ModelConfig":
        """``from_hf_config`` for ``SDARMoeForCausalLM`` / ``sdar_moe``:
        the published Qwen3-MoE keys, and this engine's own for what
        the family's ``generate.py`` takes as arguments (defaults
        those of that script)."""
        from production_stack_tpu.ops.sampling import REMASKING_STRATEGIES
        ep, rank = _expert_parallel_share(hf)
        block = int(hf.get("diffusion_block_length", 4))
        steps = int(hf.get("diffusion_steps", 4))
        remasking = hf.get("diffusion_remasking", "low_confidence_dynamic")
        mask_id = int(hf.get("mask_token_id", 151669))
        refused = [why for bad, why in (
            (bool(hf.get("mlp_only_layers")),
             f"mlp_only_layers {hf.get('mlp_only_layers')}: every layer "
             "is served as an expert layer"),
            (hf.get("decoder_sparse_step", 1) != 1,
             f"decoder_sparse_step {hf.get('decoder_sparse_step')}: "
             "every layer is served as an expert layer"),
            (hf.get("rope_scaling") is not None,
             f"rope_scaling {hf.get('rope_scaling')!r}: the rotary "
             "embedding is served unscaled"),
            (bool(hf.get("use_sliding_window", False)),
             "use_sliding_window true: a query sees the whole row up "
             "to the end of its block"),
            (bool(hf.get("attention_bias", False)),
             "attention_bias true: the attention projections are "
             "served without a bias"),
            (hf.get("hidden_act", "silu") != "silu",
             f"hidden_act {hf.get('hidden_act')!r}: the experts are "
             "SwiGLU"),
            (block < 1 or block & (block - 1) != 0,
             f"diffusion_block_length {block}: a block's end is found "
             "as position | (length - 1), so its length is a power of "
             "two"),
            (not 1 <= steps <= max(block, 1),
             f"diffusion_steps {steps} for blocks of {block}: a "
             "denoising pass commits at least one place, so a block "
             "takes 1 to its length of them"),
            (remasking not in REMASKING_STRATEGIES,
             f"diffusion_remasking {remasking!r}: the rules served are "
             f"{', '.join(REMASKING_STRATEGIES)}"),
            (not 0 <= mask_id < hf["vocab_size"],
             f"mask_token_id {mask_id} is no row of an embedding of "
             f"{hf['vocab_size']} rows"),
        ) if bad]
        if refused:
            raise ValueError(
                "SDAR-MoE config this engine does not serve: "
                + "; ".join(refused))
        return cls(
            name=name or hf.get("_name_or_path", "sdar-moe"),
            architecture="sdar_moe",
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("intermediate_size", 0),
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get(
                "max_position_embeddings", 32768),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            rope_theta=hf.get("rope_theta", 1e6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            # The count this engine holds; the router's width is this
            # times expert_parallel_size.
            num_experts=hf["num_experts"],
            expert_parallel_size=ep,
            expert_parallel_rank=rank,
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=hf.get("norm_topk_prob", True),
            diffusion_block_length=block,
            mask_token_id=mask_id,
            diffusion_steps=steps,
            diffusion_remasking=remasking,
            diffusion_confidence_threshold=float(hf.get(
                "diffusion_confidence_threshold", 0.9)),
            activation="silu",
            dtype="bfloat16",
        )

    @classmethod
    def from_hf_config(cls, hf: dict, name: str = "") -> "ModelConfig":
        """Build from a HuggingFace config.json dict. The
        architecture is the first of ``architectures``, else
        ``model_type``, else a Llama; one that no branch below knows
        is refused and not read as a Llama shape."""
        arch = (hf.get("architectures")
                or [hf.get("model_type") or "LlamaForCausalLM"])[0].lower()
        if "gpt2" in arch:
            return cls(
                name=name or hf.get("_name_or_path", "gpt2"),
                architecture="gpt2",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["n_embd"],
                intermediate_size=hf.get("n_inner") or 4 * hf["n_embd"],
                num_hidden_layers=hf["n_layer"],
                num_attention_heads=hf["n_head"],
                num_key_value_heads=hf["n_head"],
                max_position_embeddings=hf["n_positions"],
                tie_word_embeddings=True,
                activation="gelu",
                dtype="bfloat16",
            )
        if "qwen3next" in arch:
            ep, rank = _expert_parallel_share(hf)
            unsupported = [k for k, bad in (
                ("mlp_only_layers", bool(hf.get("mlp_only_layers"))),
                ("decoder_sparse_step",
                 hf.get("decoder_sparse_step", 1) != 1),
                ("rope_scaling", hf.get("rope_scaling") is not None),
                ("use_sliding_window",
                 bool(hf.get("use_sliding_window", False))),
                ("attention_bias", bool(hf.get("attention_bias", False))),
            ) if bad]
            if unsupported:
                raise ValueError(
                    "Qwen3-Next config keys this engine does not "
                    f"serve at a non-default value: {unsupported}")
            return cls(
                name=name or hf.get("_name_or_path", "qwen3-next"),
                architecture="qwen3_next",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf.get("intermediate_size", 0),
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 262144),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
                rope_theta=hf.get("rope_theta", 1e7),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                full_attention_interval=hf.get(
                    "full_attention_interval", 4),
                partial_rotary_factor=hf.get(
                    "partial_rotary_factor", 0.25),
                linear_num_key_heads=hf["linear_num_key_heads"],
                linear_num_value_heads=hf["linear_num_value_heads"],
                linear_key_head_dim=hf["linear_key_head_dim"],
                linear_value_head_dim=hf["linear_value_head_dim"],
                linear_conv_kernel_dim=hf.get(
                    "linear_conv_kernel_dim", 4),
                # The count this engine holds; the router's width is
                # this times expert_parallel_size.
                num_experts=hf["num_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                shared_expert_intermediate_size=hf[
                    "shared_expert_intermediate_size"],
                norm_topk_prob=hf.get("norm_topk_prob", True),
                activation="silu",
                dtype="bfloat16",
            )
        if "sdarmoe" in arch.replace("_", ""):
            return cls._from_sdar_moe(hf, name)
        if "exaonemoe" in arch.replace("_", ""):
            ep, rank = _expert_parallel_share(hf)
            layers = hf["num_hidden_layers"]
            layer_types = tuple(hf["layer_types"])
            other = sorted(set(layer_types)
                           - {"sliding_attention", "full_attention"})
            window = hf.get("sliding_window")
            dense = int(hf.get("first_k_dense_replace", 0))
            mlp_types = hf.get("mlp_layer_types")
            windows = hf.get("sliding_windows")
            rope = hf.get("rope_parameters") or {}
            refused = [why for bad, why in (
                (bool(other),
                 f"layer_types entries {other}: a layer is served as "
                 "'sliding_attention' (a window's ring in the state "
                 "pool) or 'full_attention' (causal attention over the "
                 "paged cache), and no other kind has a path"),
                (len(layer_types) != layers,
                 f"layer_types lists {len(layer_types)} layers and "
                 f"num_hidden_layers says {layers}"),
                ("sliding_attention" in layer_types
                 and not (isinstance(window, int) and window > 0),
                 f"sliding_window {window!r} with sliding_attention "
                 "layers: the ring holds a fixed number of places"),
                (windows is not None and list(windows) != [
                    window if kind == "sliding_attention" else 0
                    for kind in layer_types],
                 "sliding_windows does not say sliding_window where "
                 "layer_types says sliding_attention and 0 elsewhere: "
                 "one window serves every windowed layer"),
                (not 0 <= dense <= layers,
                 f"first_k_dense_replace {dense} of {layers} layers"),
                (mlp_types is not None and list(mlp_types) != (
                    ["dense"] * dense + ["sparse"] * (layers - dense)),
                 "mlp_layer_types is not first_k_dense_replace "
                 f"({dense}) 'dense' entries and 'sparse' after them: "
                 "the dense feed-forwards are served first"),
                (rope.get("rope_type", "default") != "default"
                 or hf.get("rope_scaling") is not None,
                 f"rope_parameters rope_type "
                 f"{rope.get('rope_type')!r} / rope_scaling "
                 f"{hf.get('rope_scaling')!r}: the windowed layers' "
                 "rotary embedding is served unscaled ('default')"),
                (int(hf.get("n_group", 1)) != 1
                 or int(hf.get("topk_group", 1)) != 1,
                 f"n_group {hf.get('n_group')} / topk_group "
                 f"{hf.get('topk_group')}: the experts are chosen "
                 "among all of them, with no group limit"),
                (hf.get("scoring_func", "sigmoid") != "sigmoid",
                 f"scoring_func {hf.get('scoring_func')!r}: the router "
                 "scores each expert by a sigmoid"),
                (not hf.get("norm_topk_prob", True),
                 "norm_topk_prob false: the chosen experts' scores are "
                 "divided by their sum"),
                (int(hf.get("num_shared_experts", 1)) != 1,
                 f"num_shared_experts {hf.get('num_shared_experts')}: "
                 "one shared expert is added whole"),
                (int(hf.get("num_nextn_predict_layers", 0)) > 1,
                 f"num_nextn_predict_layers "
                 f"{hf.get('num_nextn_predict_layers')}: at most one "
                 "prediction layer is read, and it is not served (a "
                 "draft over K/V pages and a ring has no burst)"),
                (bool(hf.get("attention_bias", False)),
                 "attention_bias true: the attention projections are "
                 "served without a bias"),
                (hf.get("hidden_act", "silu") != "silu",
                 f"hidden_act {hf.get('hidden_act')!r}: the "
                 "feed-forwards and the experts are SwiGLU"),
            ) if bad]
            if refused:
                raise ValueError(
                    "EXAONE-MoE config this engine does not serve: "
                    + "; ".join(refused))
            return cls(
                name=name or hf.get("_name_or_path", "exaone-moe"),
                architecture="exaone_moe",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=layers,
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 262144),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                rope_theta=float(rope.get(
                    "rope_theta", hf.get("rope_theta", 1e6))),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                layer_types=layer_types,
                sliding_window=int(window or 0),
                num_dense_layers=dense,
                # The count this engine holds; the router's width is
                # this times expert_parallel_size.
                num_experts=hf["num_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                shared_expert_intermediate_size=(
                    hf["moe_intermediate_size"]
                    * int(hf.get("num_shared_experts", 1))),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                # The checkpoint's prediction layer is not served: the
                # main model's logits do not depend on it.
                num_nextn_predict_layers=0,
                activation="silu",
                dtype="bfloat16",
            )
        if "granitemoehybrid" in arch.replace("_", ""):
            ep, rank = _expert_parallel_share(hf)
            layer_types = tuple(hf["layer_types"])
            other = sorted(set(layer_types) - {"mamba", "attention"})
            heads = hf["mamba_n_heads"]
            d_head = hf.get("mamba_d_head") or (
                hf.get("mamba_expand", 2) * hf["hidden_size"] // heads)
            groups = hf.get("mamba_n_groups", 1)
            refused = [why for bad, why in (
                (bool(other),
                 f"layer_types entries {other}: a layer is served as "
                 "'mamba' (the Mamba-2 mixer) or 'attention' (causal "
                 "attention over the paged cache), and no other kind "
                 "has a path"),
                (len(layer_types) != hf["num_hidden_layers"],
                 f"layer_types lists {len(layer_types)} layers and "
                 f"num_hidden_layers says {hf['num_hidden_layers']}"),
                (bool(hf.get("mamba_proj_bias", False)),
                 "mamba_proj_bias true: the Mamba mixer's in and out "
                 "projections are served without a bias"),
                (not hf.get("mamba_conv_bias", True),
                 "mamba_conv_bias false: the convolution is served "
                 "with its bias"),
                (hf.get("position_embedding_type", "nope") != "nope",
                 f"position_embedding_type "
                 f"{hf.get('position_embedding_type')!r}: the "
                 "attention layers are served with no position term "
                 "('nope'); the Mamba layers carry the order"),
                (groups != 1,
                 f"mamba_n_groups {groups}"
                 + ("" if heads % groups == 0 else
                    f", which does not divide mamba_n_heads {heads}")
                 + ": B and C are served shared by all heads (one "
                 "group)"),
                (heads * d_head
                 != hf.get("mamba_expand", 2) * hf["hidden_size"],
                 f"mamba_n_heads {heads} x mamba_d_head {d_head} is "
                 f"not mamba_expand {hf.get('mamba_expand', 2)} x "
                 f"hidden_size {hf['hidden_size']}"),
                (bool(hf.get("attention_bias", False)),
                 "attention_bias true: the attention projections are "
                 "served without a bias"),
                (hf.get("hidden_act", "silu") != "silu",
                 f"hidden_act {hf.get('hidden_act')!r}: the experts "
                 "and the shared expert are SwiGLU"),
                (hf.get("normalization_function",
                        "rmsnorm") != "rmsnorm",
                 f"normalization_function "
                 f"{hf.get('normalization_function')!r}: every norm "
                 "is served as an RMS norm"),
                (not hf.get("shared_intermediate_size"),
                 "shared_intermediate_size 0: every layer is served "
                 "with its shared expert"),
            ) if bad]
            if refused:
                raise ValueError(
                    "Granite-MoE-hybrid config this engine does not "
                    "serve: " + "; ".join(refused))
            return cls(
                name=name or hf.get("_name_or_path", "granitemoehybrid"),
                architecture="granitemoehybrid",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 131072),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           True),
                layer_types=layer_types,
                mamba_n_heads=heads,
                mamba_d_head=d_head,
                mamba_d_state=hf.get("mamba_d_state", 128),
                mamba_d_conv=hf.get("mamba_d_conv", 4),
                mamba_expand=hf.get("mamba_expand", 2),
                mamba_chunk_size=hf.get("mamba_chunk_size", 256),
                # The count this engine holds (the key counts what is
                # held); the router's width is this times
                # expert_parallel_size. The source has no key of its
                # own for an expert's width: intermediate_size is it.
                num_experts=hf["num_local_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["intermediate_size"],
                shared_expert_intermediate_size=hf[
                    "shared_intermediate_size"],
                embedding_multiplier=float(
                    hf.get("embedding_multiplier", 1.0)),
                attention_multiplier=float(
                    hf.get("attention_multiplier", 1.0)),
                residual_multiplier=float(
                    hf.get("residual_multiplier", 1.0)),
                logits_scaling=float(hf.get("logits_scaling", 1.0)),
                activation="silu",
                dtype="bfloat16",
            )
        if "jamba" in arch:
            refused = [why for bad, why in (
                (hf.get("num_experts", 1) > 1,
                 f"num_experts {hf.get('num_experts')}: the feed-forward "
                 "of every layer is served as one dense SwiGLU MLP, "
                 "and a Jamba with routed experts has no expert layer "
                 "here"),
                (hf.get("sliding_window") is not None,
                 f"sliding_window {hf.get('sliding_window')}: the "
                 "attention layers are served as full causal attention "
                 "over the paged cache (the family that serves a "
                 "window is exaone_moe, whose windowed layers keep a "
                 "ring in the state pool: models/exaone_moe.py)"),
                (bool(hf.get("mamba_proj_bias", False)),
                 "mamba_proj_bias: the Mamba mixer's in and out "
                 "projections are served without a bias"),
                (not hf.get("mamba_conv_bias", True),
                 "mamba_conv_bias false: the convolution is served "
                 "with its bias"),
            ) if bad]
            if refused:
                raise ValueError(
                    "Jamba config this engine does not serve: "
                    + "; ".join(refused))
            return cls(
                name=name or hf.get("_name_or_path", "jamba"),
                architecture="jamba",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 262144),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                attn_layer_period=hf["attn_layer_period"],
                attn_layer_offset=hf["attn_layer_offset"],
                mamba_d_state=hf.get("mamba_d_state", 16),
                mamba_d_conv=hf.get("mamba_d_conv", 4),
                mamba_expand=hf.get("mamba_expand", 2),
                # The published default: ceil(hidden_size / 16).
                mamba_dt_rank=(hf.get("mamba_dt_rank")
                               or -(-hf["hidden_size"] // 16)),
                activation="silu",
                dtype="bfloat16",
            )
        if "lfm2moe" in arch.replace("_", ""):
            ep, rank = _expert_parallel_share(hf)
            layer_types = tuple(hf["layer_types"])
            other = sorted(set(layer_types) - {"conv", "full_attention"})
            refused = [why for bad, why in (
                (bool(other),
                 f"layer_types entries {other}: a layer is served as "
                 "'conv' (the gated short convolution) or "
                 "'full_attention' (causal attention over the paged "
                 "cache), and no other kind has a path"),
                (len(layer_types) != hf["num_hidden_layers"],
                 f"layer_types lists {len(layer_types)} layers and "
                 f"num_hidden_layers says {hf['num_hidden_layers']}"),
                (bool(hf.get("conv_bias", False)),
                 "conv_bias true: the convolution and its two "
                 "projections are served without a bias"),
                (hf.get("rope_scaling") is not None,
                 "rope_scaling: the rotary embedding is served "
                 "unscaled"),
                (not 0 <= hf.get("num_dense_layers", 0)
                 <= hf["num_hidden_layers"],
                 f"num_dense_layers {hf.get('num_dense_layers')} of "
                 f"{hf['num_hidden_layers']} layers"),
                (not hf.get("use_expert_bias", True),
                 "use_expert_bias false: the experts are chosen by "
                 "score plus the learned bias"),
                (not hf.get("norm_topk_prob", True),
                 "norm_topk_prob false: the chosen experts' scores are "
                 "divided by their sum"),
                (float(hf.get("routed_scaling_factor", 1.0)) != 1.0,
                 f"routed_scaling_factor "
                 f"{hf.get('routed_scaling_factor')}: the routed sum "
                 "is served unscaled"),
            ) if bad]
            if refused:
                raise ValueError(
                    "LFM2-MoE config this engine does not serve: "
                    + "; ".join(refused))
            return cls(
                name=name or hf.get("_name_or_path", "lfm2-moe"),
                architecture="lfm2_moe",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_key_value_heads"],
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 128000),
                rms_norm_eps=hf.get("norm_eps", 1e-5),
                rope_theta=hf.get("rope_theta", 1e6),
                # The family's convention: the head is the embedding.
                tie_word_embeddings=hf.get("tie_word_embeddings", True),
                layer_types=layer_types,
                conv_L_cache=hf.get("conv_L_cache", 3),
                num_dense_layers=hf.get("num_dense_layers", 2),
                # The count this engine holds; the router's width is
                # this times expert_parallel_size.
                num_experts=hf["num_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                activation="silu",
                dtype="bfloat16",
            )
        if "longcatflash" in arch.replace("_", ""):
            ep, rank = _expert_parallel_share(hf)
            refused = [why for bad, why in (
                (hf.get("attention_method", "MLA") != "MLA",
                 f"attention_method {hf.get('attention_method')!r}: "
                 "every attention sublayer is served as latent "
                 "attention (MLA) over the latent pages"),
                (not hf.get("q_lora_rank"),
                 "q_lora_rank unset: the query is served through its "
                 "low-rank pair and its norm"),
                (hf.get("zero_expert_type", "identity") != "identity",
                 f"zero_expert_type {hf.get('zero_expert_type')!r}: a "
                 "zero-compute expert is served as the identity"),
                (bool(hf.get("attention_bias", False)),
                 "attention_bias true: the attention projections are "
                 "served without a bias"),
                (hf.get("rope_scaling") is not None,
                 "rope_scaling: the rotary embedding is served "
                 "unscaled"),
                (bool(hf.get("norm_topk_prob", False)),
                 "norm_topk_prob true: the chosen scores are served "
                 "scaled by routed_scaling_factor and not divided by "
                 "their sum"),
                (hf.get("hidden_act", "silu") != "silu",
                 f"hidden_act {hf.get('hidden_act')!r}: the "
                 "feed-forwards and the experts are SwiGLU"),
            ) if bad]
            if refused:
                raise ValueError(
                    "LongCat-Flash config this engine does not serve: "
                    + "; ".join(refused))
            h = hf["hidden_size"]
            return cls(
                name=name or hf.get("_name_or_path", "longcat-flash"),
                architecture="longcat_flash",
                vocab_size=hf["vocab_size"],
                hidden_size=h,
                intermediate_size=hf["ffn_hidden_size"],
                num_hidden_layers=hf["num_layers"],
                num_attention_heads=hf["num_attention_heads"],
                # One latent a token serves every head.
                num_key_value_heads=1,
                head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 131072),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                rope_theta=hf.get("rope_theta", 1e7),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                kv_lora_rank=hf["kv_lora_rank"],
                q_lora_rank=hf["q_lora_rank"],
                qk_nope_head_dim=hf["qk_nope_head_dim"],
                qk_rope_head_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
                mla_q_scale=((h / hf["q_lora_rank"]) ** 0.5
                             if hf.get("mla_scale_q_lora") else 1.0),
                mla_kv_scale=((h / hf["kv_lora_rank"]) ** 0.5
                              if hf.get("mla_scale_kv_lora") else 1.0),
                # The count this engine holds; the router's width is
                # this times expert_parallel_size plus the
                # zero-compute experts, which no engine holds.
                num_experts=hf["n_routed_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["moe_topk"],
                moe_intermediate_size=hf["expert_ffn_hidden_size"],
                zero_expert_num=hf.get("zero_expert_num", 0),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                activation="silu",
                dtype="bfloat16",
            )
        if "glm4moelite" in arch.replace("_", ""):
            ep, rank = _expert_parallel_share(hf)
            refused = [why for bad, why in (
                (not hf.get("q_lora_rank"),
                 "q_lora_rank unset: the query is served through its "
                 "low-rank pair and its norm"),
                (int(hf.get("n_group", 1)) > 1
                 or int(hf.get("topk_group", 1)) > 1,
                 f"n_group {hf.get('n_group')} / topk_group "
                 f"{hf.get('topk_group')}: the experts are chosen "
                 "among all of them, with no group limit"),
                (hf.get("topk_method", "noaux_tc") != "noaux_tc",
                 f"topk_method {hf.get('topk_method')!r}: the experts "
                 "are chosen by sigmoid score plus the learned bias "
                 "(noaux_tc)"),
                (hf.get("rope_scaling") is not None,
                 "rope_scaling: the rotary embedding is served "
                 "unscaled"),
                (int(hf.get("num_nextn_predict_layers", 0)) > 1,
                 f"num_nextn_predict_layers "
                 f"{hf.get('num_nextn_predict_layers')}: the burst "
                 "verifies one draft a row an iteration, from one "
                 "prediction layer"),
                (bool(hf.get("attention_bias", False)),
                 "attention_bias true: the attention projections are "
                 "served without a bias"),
                (not hf.get("norm_topk_prob", True),
                 "norm_topk_prob false: the chosen experts' scores are "
                 "divided by their sum"),
                (float(hf.get("partial_rotary_factor", 1)) != 1.0,
                 f"partial_rotary_factor "
                 f"{hf.get('partial_rotary_factor')}: all of "
                 "qk_rope_head_dim is turned"),
                (int(hf.get("n_shared_experts", 1)) != 1,
                 f"n_shared_experts {hf.get('n_shared_experts')}: one "
                 "shared expert is added whole"),
                (not 0 <= int(hf.get("first_k_dense_replace", 0))
                 <= hf["num_hidden_layers"],
                 f"first_k_dense_replace "
                 f"{hf.get('first_k_dense_replace')} of "
                 f"{hf['num_hidden_layers']} layers"),
                (hf.get("hidden_act", "silu") != "silu",
                 f"hidden_act {hf.get('hidden_act')!r}: the "
                 "feed-forwards and the experts are SwiGLU"),
            ) if bad]
            if refused:
                raise ValueError(
                    "GLM-4 MoE lite config this engine does not serve: "
                    + "; ".join(refused))
            return cls(
                name=name or hf.get("_name_or_path", "glm4-moe-lite"),
                architecture="glm4_moe_lite",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                # One latent a token serves every head.
                num_key_value_heads=1,
                head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 202752),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                rope_theta=hf.get("rope_theta", 1e6),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                kv_lora_rank=hf["kv_lora_rank"],
                q_lora_rank=hf["q_lora_rank"],
                qk_nope_head_dim=hf["qk_nope_head_dim"],
                qk_rope_head_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
                num_dense_layers=int(hf.get("first_k_dense_replace", 0)),
                # The count this engine holds; the router's width is
                # this times expert_parallel_size.
                num_experts=hf["n_routed_experts"],
                expert_parallel_size=ep,
                expert_parallel_rank=rank,
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                shared_expert_intermediate_size=(
                    hf["moe_intermediate_size"]
                    * int(hf.get("n_shared_experts", 1))),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                num_nextn_predict_layers=int(
                    hf.get("num_nextn_predict_layers", 0)),
                activation="silu",
                dtype="bfloat16",
            )
        if "mixtral" in arch:
            return cls(
                name=name or hf.get("_name_or_path", "mixtral"),
                architecture="mixtral",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf["intermediate_size"],
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf.get(
                    "num_key_value_heads", hf["num_attention_heads"]),
                head_dim=hf.get("head_dim"),
                max_position_embeddings=hf.get(
                    "max_position_embeddings", 4096),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
                rope_theta=hf.get("rope_theta", 1e6),
                tie_word_embeddings=hf.get("tie_word_embeddings",
                                           False),
                num_local_experts=hf.get("num_local_experts", 8),
                num_experts_per_tok=hf.get("num_experts_per_tok", 2),
                activation="silu",
                dtype="bfloat16",
            )
        if "opt" in arch:
            return cls(
                name=name or hf.get("_name_or_path", "opt"),
                architecture="opt",
                vocab_size=hf["vocab_size"],
                hidden_size=hf["hidden_size"],
                intermediate_size=hf.get("ffn_dim", 4 * hf["hidden_size"]),
                num_hidden_layers=hf["num_hidden_layers"],
                num_attention_heads=hf["num_attention_heads"],
                num_key_value_heads=hf["num_attention_heads"],
                max_position_embeddings=hf["max_position_embeddings"],
                tie_word_embeddings=hf.get("tie_word_embeddings", True),
                do_layer_norm_before=hf.get("do_layer_norm_before", True),
                activation="relu",
                dtype="bfloat16",
            )
        if arch not in _LLAMA_SHAPES:
            raise ValueError(
                f"architecture {arch!r} is none this engine serves "
                "(config.json 'architectures', else 'model_type'): it "
                "knows GPT-2, OPT, Mixtral, Qwen3-Next, Jamba, LFM2-MoE, "
                "LongCat-Flash, GLM-4 MoE lite, Granite-MoE-hybrid, "
                "EXAONE-MoE and the "
                f"Llama shapes {sorted(_LLAMA_SHAPES)}, and reads no "
                "other as one of them")
        qwen = "qwen2" in arch
        return cls(
            name=name or hf.get("_name_or_path", "llama"),
            architecture="qwen2" if qwen else "llama",
            # Qwen2 puts biases on q/k/v (HF Qwen2Attention); plain
            # Llama exposes the same switch via attention_bias.
            attention_bias=(True if qwen
                            else hf.get("attention_bias", False)),
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            rope_theta=hf.get("rope_theta", 10000.0),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            activation="silu",
            dtype="bfloat16",
        )


@dataclasses.dataclass
class CacheConfig:
    """Paged KV cache geometry."""

    page_size: int = 16  # tokens per page
    num_pages: int = 1024  # total pages in HBM (per shard)
    enable_prefix_caching: bool = True
    # HBM buffer layout (models/llama.py cached_attention):
    #   auto      -> per_layer, except pipeline/context-parallel
    #                configs (which shard or walk the stacked L axis)
    #                resolve to stacked (model_runner; a builder's
    #                capture, no driver's number: ROADMAP D4).
    #   stacked   -> one [L, kv, pages, d, page_size] array per k/v;
    #                layer writes are in-place scatters at a static
    #                layer index.
    #   per_layer -> a tuple of L [kv, pages, d, page_size] buffers;
    #                every scatter/kernel touches exactly one layer's
    #                buffer (67 MB vs 2.1 GB operands at the 1B bench
    #                config) and donation aliases buffers 1:1.
    cache_layout: str = "auto"
    # KV page storage dtype (docs/kv_quantization.md):
    #   auto / bf16 -> pages in the model compute dtype (bf16 in
    #                  serving; an f32 model keeps f32 pages) — the
    #                  two spellings are synonyms so --kv-cache-dtype
    #                  bf16 states the default explicitly.
    #   int8        -> pages quantized on write (symmetric per-slot
    #                  scales, ops/quant_kv.py) and dequantized
    #                  in-kernel; the page budget is expanded to spend
    #                  the SAME HBM bytes (~2x pages at bf16 widths).
    kv_cache_dtype: str = "auto"
    # Recurrent-state slots for a model with linear-attention layers
    # (engine/kv_cache.py), beside the trash slot 0. Derived by
    # EngineConfig, not set by hand: 0 for a model whose state is all
    # pages, else max_num_seqs + prefill_batch_size (a sequence holds
    # its slot from its first prefill chunk, before it counts as
    # running).
    num_state_slots: int = 0

    def max_tokens(self) -> int:
        return self.page_size * self.num_pages

    def resolved_kv_dtype(self) -> str:
        """'int8' or 'bf16' (the full-precision family; the actual
        page dtype is the model compute dtype)."""
        return "int8" if self.kv_cache_dtype == "int8" else "bf16"

    def kv_slot_bytes(self, model: "ModelConfig") -> int:
        """HBM bytes one cached token costs per head of one plane of
        one paged entry: the plane's rows plus, for int8, one f32
        scale."""
        width = model.page_cache.width
        if self.resolved_kv_dtype() == "int8":
            return width + 4
        return width * jnp.dtype(model.jax_dtype).itemsize

    def kv_bytes_per_token(self, model: "ModelConfig") -> int:
        """Total cache bytes appended per committed token: every paged
        entry, every plane it has (K and V, or one latent), every
        head."""
        pc = model.page_cache
        return (pc.planes * pc.entries * pc.heads
                * self.kv_slot_bytes(model))


@dataclasses.dataclass
class SchedulerConfig:
    """Continuous-batching shape budget (all static under jit)."""

    max_num_seqs: int = 8  # decode batch width (padded)
    max_model_len: int = 2048
    prefill_chunk_size: int = 512  # chunked prefill unit
    # Distinct sequences whose next chunks batch into one prefill
    # program (fixed row count; rows pad with the trash page).
    prefill_batch_size: int = 4
    # Decode iterations fused into one compiled program (tokens feed
    # back on device; 1 host round-trip per K tokens). 1 = off.
    decode_steps: int = 1
    # Deferred KV writes inside a decode burst: append each step's K/V
    # to a dense [B, S, kv, d] tail (one-hot select, no scatter) and
    # flush the tail to the pages ONCE per burst per layer. Motivated
    # by a decode ablation (builder-captured 2026-07-31, not measured
    # by the driver): the per-step paged scatters cost ~5.1 of 11.1 ms
    # for ~1 MB written.
    # Single-runner path of the families whose forward takes kv_tail
    # (model_runner.DEFERRED_KV_FAMILIES, guarded there); requires
    # decode_steps > 1.
    deferred_kv_writes: bool = False
    # Draft-free speculative decoding (prompt lookup, docs/
    # speculative.md): propose up to K continuation tokens per row
    # from each sequence's own n-gram history and verify all K+1
    # positions in ONE fixed-shape forward pass. 0 = off. Composes
    # with decode_steps > 1 as a hybrid — steps where the proposer
    # drafted run the verify program, draft-less steps fall back to
    # the multi-step decode burst. Incompatible with
    # deferred_kv_writes (the verify step must write draft KV
    # eagerly so later draft positions attend to earlier ones).
    speculative_k: int = 0
    # Minimum n-gram length the proposer must match in the sequence's
    # history before drafting its continuation.
    speculative_min_match: int = 2
    # Drafting by the model's own multi-token-prediction module inside
    # the deferred burst (docs/speculative.md, "The module as
    # proposer"): an iteration verifies one draft a row and commits one
    # or two tokens. The server's --draft-module auto resolves this on
    # where the family declares a draft module and the checkpoint's
    # configuration keeps one (num_nextn_predict_layers >= 1); off, the
    # module is not made and has no pages. Needs deferred_kv_writes.
    draft_module: bool = False
    # Generation by diffusion over blocks (docs/block_diffusion.md):
    # the positions a block (0: the model generates left to right) and
    # the denoising passes a block is planned at, both the model's
    # (EngineConfig takes them from it: no flag sets them). A prefill
    # covers a prompt's whole blocks and yields no token; a burst of
    # decode_steps forward passes is decode_steps // (block_steps + 1)
    # blocks a row.
    block_length: int = 0
    block_steps: int = 0
    # Overlapped async execution pipeline (docs/async_pipeline.md):
    # plan and dispatch decode step N+1 — feeding step N's sampled
    # tokens forward as a device array — before step N's results are
    # read back to the host, so completion work (detokenize, stop
    # checks, stream fan-out) overlaps device execution. Composes
    # with speculative_k (the ahead plan assumes one committed token
    # and reconciles extra accepted tokens through the stale-token
    # drop path) and with decode_steps > 1 (burst windows execute
    # synchronously between pipelined single-step stretches).
    # Greedy output is byte-identical to the synchronous loop.
    async_scheduling: bool = False
    # Unified ragged step (docs/unified_step.md): plan prefill chunks
    # INTO decode/spec steps under a token budget instead of
    # alternating phases, executing genuinely mixed batches through
    # one fixed-shape [rows, W] ragged program (span-gather +
    # spec_verify emit 1..k+1 tokens per row through one shape).
    # Pure-decode and pure-prefill steps keep the bimodal dispatch
    # paths, so greedy streams stay byte-identical when no mixing
    # happens. The server's --unified-step auto resolves this on for
    # eligible single-runner configs (unified_step_eligible).
    unified_step: bool = False
    max_queue_len: int = 1024

    def max_pages_per_seq(self, page_size: int) -> int:
        return math.ceil(self.max_model_len / page_size)


@dataclasses.dataclass
class ParallelConfig:
    """Device-mesh shape; tensor parallel maps to the 'tp' mesh axis over
    ICI (reference passes --tensor-parallel-size to vLLM + /dev/shm for
    NCCL, deployment-vllm-multi.yaml:84-87,226-233 — XLA needs neither)."""

    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    # Layer stages over the 'pp' mesh axis — a SERVING feature here
    # (parallel/pipeline_serving.py), unlike the reference which has no
    # pipeline parallelism at all (SURVEY.md §2.6).
    pipeline_parallel_size: int = 1
    # Sequence/context parallelism over the 'sp' mesh axis: prompts at
    # least ``long_prefill_threshold`` tokens prefill in ONE dispatch
    # with the sequence sharded T/sp per device and ring attention
    # doing the O(T^2) mixing (parallel/context_serving.py) — the
    # long-context strategy the reference lacks entirely.
    context_parallel_size: int = 1
    # Prompts this long (tokens) take the sp prefill path; defaults to
    # 2 x prefill_chunk_size when context_parallel_size > 1.
    long_prefill_threshold: Optional[int] = None
    # Forced ICI-slice count for topology discovery
    # (parallel/topology.py): 0 = auto-discover (TPU slice coords,
    # process grouping). >0 splits the visible devices into that many
    # equal contiguous slices — how the XLA_FLAGS-forced CPU harness
    # rehearses multi-slice layouts in CI.
    num_slices: int = 0
    # Per-axis placement overrides for the MeshPlan, as
    # "axis=ici|any" pairs ("tp=ici,pp=any"). 'auto' keeps the
    # defaults: tp/sp confined to one ICI domain (a replica is a
    # slice), dp/pp free to cross slices over DCN.
    mesh_placement: str = "auto"

    def __post_init__(self):
        if self.num_slices < 0:
            raise ValueError("parallel.num_slices must be >= 0")
        # Reject placement typos at config time, not first dispatch.
        from production_stack_tpu.parallel.topology import (
            parse_placement,
        )
        parse_placement(self.mesh_placement)


@dataclasses.dataclass
class LoRAConfig:
    """Multi-LoRA serving (the reference's --enable-lora pass-through,
    helm/templates/deployment-vllm-multi.yaml:66-68; see engine/lora.py)."""

    enable: bool = False
    max_loras: int = 8  # adapter slots (slot 0 is always the base model)
    max_lora_rank: int = 16


@dataclasses.dataclass
class OffloadConfig:
    """KV offload tiers (the LMCache analogue; see engine/offload.py)."""

    enable: bool = False
    host_pool_bytes: int = 2 * 1024 ** 3
    remote_url: Optional[str] = None


@dataclasses.dataclass
class KVEconConfig:
    """Cluster KV economy knobs (docs/kv_economy.md).

    Engine-side semantics: the summary tracker behind GET /kv/summary
    and the host pool's eviction hysteresis. The cluster cache server
    reuses the same flag spellings for its authoritative server-side
    policy (admission by distinct-requester demand, TTL + watermark
    chain eviction) with its own defaults — see
    engine/cache_server.py.
    """

    # Hot chains advertised in the /kv/summary snapshot (and tracker
    # sizing: up to 8x this many chains are tracked pre-admission).
    summary_top_k: int = 64
    # Decayed hit count a chain needs before it is advertised as hot.
    admit_hits: int = 2
    # Seconds an idle chain stays in the summary tracker (0 = no TTL).
    ttl_s: float = 900.0
    # Host offload pool fill fractions: above high, evict down to low
    # (oldest-first, same order as the pool's LRU). 1.0/1.0 keeps the
    # legacy evict-exactly-at-capacity behavior.
    watermark_high: float = 1.0
    watermark_low: float = 1.0

    def __post_init__(self):
        if self.summary_top_k < 1:
            raise ValueError("kvecon.summary_top_k must be >= 1")
        if self.admit_hits < 1:
            raise ValueError("kvecon.admit_hits must be >= 1")
        if self.ttl_s < 0:
            raise ValueError("kvecon.ttl_s must be >= 0")
        if not 0.0 < self.watermark_low <= self.watermark_high <= 1.0:
            raise ValueError(
                "kvecon watermarks must satisfy 0 < low <= high <= 1 "
                f"(got low={self.watermark_low!r} "
                f"high={self.watermark_high!r})")


@dataclasses.dataclass
class QoSConfig:
    """Overload quality-of-service (docs/qos.md): priority classes,
    preempt-to-offload, and engine-side shedding."""

    # Priority class assumed for requests without an x-priority
    # header: interactive | batch | background. Defaults to the
    # middle class so unlabeled traffic stays sheddable.
    default_priority: str = "batch"
    # Under page pressure, ship the preemption victim's committed KV
    # pages to the offload tier (when one is configured) instead of
    # discarding them, so re-admission restores pages instead of
    # recomputing the whole prompt. Inert without --enable-kv-offload.
    preempt_to_offload: bool = True
    # Waiting-queue fill fraction (of max_queue_len) past which the
    # server sheds non-interactive submissions with 429 + Retry-After
    # instead of letting them age out in the queue.
    shed_threshold: float = 0.95

    def __post_init__(self):
        # Raises ValueError on anything outside the priority
        # vocabulary — the config-contract's tested rejection for
        # invalid priority strings.
        parse_priority(self.default_priority)
        if not 0.0 < self.shed_threshold <= 1.0:
            raise ValueError(
                "qos.shed_threshold must be in (0, 1] "
                f"(got {self.shed_threshold!r})")


@dataclasses.dataclass
class AutotuneConfig:
    """Self-tuning controller policy (docs/autotuning.md).

    Shared cadence/guardrail knobs plus the per-controller clamp
    bands the autotuner enforces. The mode gate is the contract:
    ``off`` never even constructs controllers' tick path, ``shadow``
    computes and span-logs decisions without applying them (the A/B
    story), ``on`` closes the loop.
    """

    # off | shadow | on (autotune.MODES).
    mode: str = "off"
    # Seconds between controller ticks (the bounded cadence).
    interval_s: float = 2.0
    # Relative dead-band: proposals within this fraction of the
    # current knob value are dropped (hysteresis against jitter).
    dead_band: float = 0.05
    # Comma-separated controller-name allowlist, or "all".
    controllers: str = "all"
    # Guardrail blame window: a perf-drift flip / 5m-burn rise
    # freezes every controller that applied a decision this recently.
    freeze_window_s: float = 30.0
    # 5m SLO burn rate at/above which a rise trips the guardrail.
    burn_threshold: float = 1.0
    # Decode ITL p99 target the prefill-budget controller steers
    # toward (grow mixed-step admission while under, shrink over).
    target_itl_ms: float = 50.0
    # Clamp floors/caps for individual controllers. Spec-k cap is
    # --speculative-k itself; checkpoint interval floors/caps bound
    # the halving/doubling walk; shed floor keeps QoS from shedding
    # more than operators signed up for.
    min_spec_k: int = 1
    min_checkpoint_interval_tokens: int = 64
    max_checkpoint_interval_tokens: int = 4096
    min_shed_threshold: float = 0.5

    def __post_init__(self):
        if self.mode not in ("off", "shadow", "on"):
            raise ValueError(
                "autotune.mode must be 'off', 'shadow' or 'on' "
                f"(got {self.mode!r})")
        if self.interval_s <= 0:
            raise ValueError("autotune.interval_s must be > 0")
        if not 0.0 <= self.dead_band < 1.0:
            raise ValueError(
                "autotune.dead_band must be in [0, 1) "
                f"(got {self.dead_band!r})")
        if self.freeze_window_s <= 0:
            raise ValueError("autotune.freeze_window_s must be > 0")
        if self.min_spec_k < 1:
            raise ValueError("autotune.min_spec_k must be >= 1")
        if not 0.0 < self.min_shed_threshold <= 1.0:
            raise ValueError(
                "autotune.min_shed_threshold must be in (0, 1] "
                f"(got {self.min_shed_threshold!r})")
        if (self.min_checkpoint_interval_tokens < 1
                or self.max_checkpoint_interval_tokens
                < self.min_checkpoint_interval_tokens):
            raise ValueError(
                "autotune checkpoint interval bounds must satisfy "
                "1 <= min <= max (got "
                f"min={self.min_checkpoint_interval_tokens!r} "
                f"max={self.max_checkpoint_interval_tokens!r})")


@dataclasses.dataclass
class EngineConfig:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)
    scheduler: SchedulerConfig = dataclasses.field(
        default_factory=SchedulerConfig)
    parallel: ParallelConfig = dataclasses.field(
        default_factory=ParallelConfig)
    offload: OffloadConfig = dataclasses.field(
        default_factory=OffloadConfig)
    lora: LoRAConfig = dataclasses.field(default_factory=LoRAConfig)
    qos: QoSConfig = dataclasses.field(default_factory=QoSConfig)
    kvecon: KVEconConfig = dataclasses.field(
        default_factory=KVEconConfig)
    autotune: AutotuneConfig = dataclasses.field(
        default_factory=AutotuneConfig)
    seed: int = 0
    # Disaggregated serving role (docs/disaggregation.md):
    #   both    -> monolithic engine (default; fully backward
    #              compatible): serves prefill + decode.
    #   prefill -> computes prompt KV, ships committed pages over the
    #              offload wire and answers with a handoff descriptor
    #              instead of a token stream (POST /v1/disagg/prefill).
    #   decode  -> accepts handoff submissions (POST
    #              /v1/disagg/handoff), restores the shipped pages and
    #              streams decode from the first sampled token.
    engine_role: str = "both"
    # Seconds a decode-role engine holds a handoff in AWAITING_KV while
    # its pages are unreachable (remote tier down) before degrading to
    # a full prompt recompute. 0 = recompute immediately on a miss.
    handoff_timeout_s: float = 30.0
    # Per-chip peak FLOP/s for the observatory's MFU gauge
    # (engine/perf_observatory.py). 0 = resolve from the device-kind
    # table; unknown devices (including CPU) then report MFU 0 rather
    # than a guessed utilization.
    device_peak_flops: float = 0.0
    # Mid-stream crash safety (docs/crash_recovery.md): every N
    # generated tokens, ship a streaming sequence's committed KV pages
    # to the offload tier and publish a resume descriptor so the
    # router can re-submit the stream to another engine after this
    # process dies. 0 = no checkpointing (streams die with the
    # engine). Inert without an offload tier for the page ship, but
    # the descriptor (token journal) is still published so a resume
    # can recompute.
    checkpoint_interval_tokens: int = 0
    # Seconds a single engine step may run before /health flips to
    # 503 so the router's prober rotates the replica out (a hung
    # device program blocks the step thread; the asyncio health
    # handler keeps serving). 0 = watchdog disabled.
    step_watchdog_s: float = 0.0

    def __post_init__(self):
        if self.engine_role not in ("prefill", "decode", "both"):
            raise ValueError(
                "engine_role must be 'prefill', 'decode' or 'both' "
                f"(got {self.engine_role!r})")
        if self.handoff_timeout_s < 0:
            raise ValueError("handoff_timeout_s must be >= 0")
        if self.device_peak_flops < 0:
            raise ValueError("device_peak_flops must be >= 0")
        if self.checkpoint_interval_tokens < 0:
            raise ValueError("checkpoint_interval_tokens must be >= 0")
        if self.step_watchdog_s < 0:
            raise ValueError("step_watchdog_s must be >= 0")
        if self.engine_role == "prefill":
            # A prefill-role engine never decodes past the first
            # sampled token, so decode-side machinery is dead weight
            # at best and a config lie at worst — reject it loudly.
            if self.scheduler.speculative_k > 0:
                raise ValueError(
                    "engine_role='prefill' is incompatible with "
                    "speculative_k > 0 (speculation accelerates "
                    "decode; a prefill-role engine hands off after "
                    "the first token; docs/disaggregation.md "
                    "§interactions)")
            # async_scheduling on a prefill-role engine is legal but
            # inert: prefill dispatches run synchronously, so the
            # pipeline simply never goes ahead. The server's
            # --async-scheduling auto still resolves it off for the
            # role (no decode steps to overlap).
        if self.cache.kv_cache_dtype not in ("auto", "bf16", "int8"):
            raise ValueError(
                "cache.kv_cache_dtype must be 'auto', 'bf16' or "
                f"'int8' (got {self.cache.kv_cache_dtype!r})")
        if self.model.has_recurrent_state:
            refused = _recurrent_state_refusals(self)
            if refused:
                raise ValueError(
                    f"{self.model.architecture} keeps a recurrent "
                    "state beside its pages; refused: " + "; ".join(
                        f"{feature} ({why})" for feature, why in refused))
            window = self.model.sliding_window
            if window and window % self.cache.page_size:
                raise ValueError(
                    f"{self.model.architecture}: sliding_window "
                    f"{window} is not a whole number of pages of "
                    f"{self.cache.page_size} tokens (--page-size): a "
                    "sequence's ring is laid out as pages are and read "
                    "by the paged kernels, which take whole pages")
            if self.cache.cache_layout == "auto":
                self.cache = dataclasses.replace(
                    self.cache, cache_layout="per_layer")
            self.cache = dataclasses.replace(
                self.cache,
                num_state_slots=(self.scheduler.max_num_seqs
                                 + self.scheduler.prefill_batch_size))
        if self.scheduler.draft_module:
            if not self.model.has_draft_module:
                raise ValueError(
                    "draft_module needs a family that declares a draft "
                    "module and a checkpoint whose configuration keeps "
                    "one (num_nextn_predict_layers >= 1); "
                    f"{self.model.architecture} with "
                    "num_nextn_predict_layers "
                    f"{self.model.num_nextn_predict_layers} has none")
            if not self.scheduler.deferred_kv_writes:
                raise ValueError(
                    "draft_module needs deferred_kv_writes (and "
                    "decode_steps > 1): the module drafts inside the "
                    "deferred burst, whose tails are what a rejected "
                    "draft is rolled back in (docs/speculative.md)")
            if self.scheduler.speculative_k > 0:
                raise ValueError(
                    "draft_module is incompatible with speculative_k "
                    "> 0: one proposer a row (docs/speculative.md "
                    "§interactions)")
        elif self.model.num_nextn_predict_layers:
            # The module is not loaded: no weights, no cache entry.
            self.model = dataclasses.replace(
                self.model, num_nextn_predict_layers=0)
        if self.model.block_length:
            refused = _block_diffusion_refusals(self)
            if refused:
                raise ValueError(
                    f"{self.model.architecture} generates by diffusion "
                    "over blocks of "
                    f"{self.model.block_length} positions; refused: "
                    + "; ".join(f"{feature} ({why})"
                                for feature, why in refused))
            if self.cache.cache_layout == "auto":
                self.cache = dataclasses.replace(
                    self.cache, cache_layout="per_layer")
            self.scheduler = dataclasses.replace(
                self.scheduler, block_length=self.model.block_length,
                block_steps=self.model.diffusion_steps)
        if self.model.has_latent_cache:
            refused = _latent_cache_refusals(self)
            if refused:
                raise ValueError(
                    f"{self.model.architecture} caches one latent a "
                    "token a sublayer in the place of a (K, V) pair; "
                    "refused: " + "; ".join(
                        f"{feature} ({why})" for feature, why in refused))
            if self.cache.cache_layout == "auto":
                self.cache = dataclasses.replace(
                    self.cache, cache_layout="per_layer")
        if self.cache.resolved_kv_dtype() == "int8":
            # int8 now composes with pipeline/context parallelism:
            # the pp/sp shard_map boundaries carry QuantKV pytree
            # specs (congruent data+scale sharding, mirroring
            # shard_cache) — the former exclusivity raises dissolved
            # with the topology-aware mesh (docs/parallelism.md).
            # Spend the SAME HBM byte budget on more (narrower)
            # pages: a full-precision slot is head_dim * itemsize
            # bytes, an int8 slot head_dim + 4 (f32 scale) — ~1.9x
            # more pages at bf16 widths. Guarded by a sentinel on the
            # CacheConfig object because dataclasses.replace(self)
            # re-runs __post_init__ on the SAME CacheConfig instance.
            if not getattr(self.cache, "_kv_pages_expanded", False):
                full_slot = (self.model.head_dim
                             * jnp.dtype(self.model.jax_dtype).itemsize)
                expanded = (self.cache.num_pages * full_slot
                            // (self.model.head_dim + 4))
                self.cache = dataclasses.replace(
                    self.cache, num_pages=max(expanded,
                                              self.cache.num_pages))
                self.cache._kv_pages_expanded = True
        if self.scheduler.speculative_k > 0:
            if self.scheduler.deferred_kv_writes:
                raise ValueError(
                    "speculative_k is incompatible with "
                    "deferred_kv_writes (the verify step writes draft "
                    "KV eagerly so accepted tokens can attend to it; "
                    "docs/speculative.md §interactions)")
            if self.scheduler.speculative_min_match < 1:
                raise ValueError("speculative_min_match must be >= 1")
        # async_scheduling now composes with decode_steps > 1 (burst
        # windows run synchronously between pipelined single-step
        # stretches) and speculative_k > 0 (the ahead plan assumes
        # one committed token per row and reconciles multi-accept
        # steps through the stale-token drop path) — the former
        # exclusivity raises died with the unified ragged step
        # (docs/unified_step.md §dissolved-rules).
        # Learned-position-embedding models (gpt2/opt) index a fixed
        # [max_positions, h] table; JAX clamps out-of-range gathers
        # silently, so positions past the table would all reuse the
        # last row and quietly degrade long generations. Cap the
        # serving length at the model's limit instead.
        if (self.model.architecture in ("gpt2", "opt")
                and self.scheduler.max_model_len
                > self.model.max_position_embeddings):
            from production_stack_tpu.utils.log import init_logger
            init_logger(__name__).warning(
                "max_model_len %d exceeds %s's position table (%d); "
                "clamping to %d",
                self.scheduler.max_model_len, self.model.architecture,
                self.model.max_position_embeddings,
                self.model.max_position_embeddings,
            )
            self.scheduler = dataclasses.replace(
                self.scheduler,
                max_model_len=self.model.max_position_embeddings,
            )


def _recurrent_state_refusals(config: "EngineConfig"):
    """(feature, why) for every configured feature that moves, skips
    or rolls back K/V pages without the recurrent state of a hybrid
    model, or has no path for that state. What is true of any such
    model is worded here; where the reason is the family's own
    (what it has no sharding rule or quantized form for) the family
    words it (models/registry.py ``refusals``)."""
    s, p = config.scheduler, config.parallel
    own = config.model.family.refusals
    checks = (
        (config.offload.enable, "KV offload",
         "it moves pages to another tier and back without the state"),
        (config.engine_role != "both", "disaggregated prefill/decode",
         "the handoff ships pages without the state"),
        (config.checkpoint_interval_tokens > 0,
         "mid-stream checkpoint descriptors",
         "a resume restores pages without the state"),
        (s.speculative_k > 0, "speculative decoding",
         "a rejected draft's K/V is overwritten in place by the next "
         "step, and the recurrent state it advanced cannot be rolled "
         "back"),
        (p.pipeline_parallel_size > 1, "pipeline-parallel serving",
         "the staged forward has no state pools"),
        (p.context_parallel_size > 1, "context-parallel prefill",
         "the ring prefill has no state pools"),
        (p.tensor_parallel_size > 1, "tensor parallelism",
         own["tensor parallelism"]),
        (s.unified_step, "the unified ragged step",
         "its rows mix decode tokens and prompt chunks in one block, "
         "which the recurrent layers do not take"),
        (config.lora.enable, "LoRA", "the model has no LoRA targets"),
        (config.cache.resolved_kv_dtype() == "int8", "int8 KV pages",
         "the hybrid cache is not quantized"),
        (config.model.quantization != "none", "weight quantization",
         own["weight quantization"]),
        (config.cache.cache_layout == "stacked",
         "cache_layout='stacked'",
         "pages and state pools are per-layer buffers"),
    )
    return [(feature, why) for on, feature, why in checks if on]


def _block_diffusion_refusals(config: "EngineConfig"):
    """(feature, why) for every configured feature that takes a step
    to commit one token a row left to right, or has no path for a
    block's passes."""
    s, p, m = config.scheduler, config.parallel, config.model
    own = m.family.refusals
    block, passes = m.block_length, m.diffusion_steps + 1
    checks = (
        (s.speculative_k > 0, "speculative decoding by prompt lookup",
         "a draft continues a row left to right and is verified "
         "causally; a block's places are committed in any order"),
        (s.draft_module, "a draft module",
         "its proposal is the next token after the last committed one, "
         "which a block has none of"),
        (s.unified_step, "the unified ragged step",
         "its decode rows are one token under a causal mask, and a "
         "block's rows see their block in both directions"),
        (s.async_scheduling, "async scheduling",
         "its plan-ahead step assumes one committed token a row a "
         "dispatch"),
        (not s.deferred_kv_writes or s.decode_steps < passes,
         f"decode_steps {s.decode_steps} with deferred_kv_writes "
         f"{s.deferred_kv_writes}",
         f"a block is {m.diffusion_steps} denoising passes and a store "
         "pass over the burst's tails, so a burst is at least "
         f"{passes} forward passes (--decode-steps) with deferred K/V "
         "writes"),
        (p.tensor_parallel_size > 1, "tensor parallelism",
         own["tensor parallelism"]),
        (p.pipeline_parallel_size > 1, "pipeline-parallel serving",
         "the staged forward has no pass over a block"),
        (p.context_parallel_size > 1, "context-parallel prefill",
         "the ring prefill's mask is causal"),
        (config.engine_role != "both", "disaggregated prefill/decode",
         "the handoff follows the prefill's first token, and this "
         "prefill yields none"),
        (config.offload.enable, "KV offload",
         "a restored row resumes after its last token, not at a "
         "block's edge"),
        (config.checkpoint_interval_tokens > 0,
         "mid-stream checkpoint descriptors",
         "a resume restores pages up to a token, not to a block's "
         "edge"),
        (config.lora.enable, "LoRA", "the model has no LoRA targets"),
        (config.cache.resolved_kv_dtype() == "int8", "int8 KV pages",
         "the burst's tails flush to plain planes in place"),
        (m.quantization != "none", "weight quantization",
         own["weight quantization"]),
        (config.cache.cache_layout == "stacked",
         "cache_layout='stacked'",
         "the family's counters ride the per-layer cache tuples"),
        (any(n % block for n in (config.cache.page_size,
                                 s.prefill_chunk_size, s.max_model_len)),
         f"page_size {config.cache.page_size}, prefill_chunk_size "
         f"{s.prefill_chunk_size} or max_model_len {s.max_model_len}",
         f"pages, chunks and the longest row end at a block's edge: "
         f"each is a whole number of blocks of {block}"),
    )
    return [(feature, why) for on, feature, why in checks if on]


def _latent_cache_refusals(config: "EngineConfig"):
    """(feature, why) for every configured feature that reads, writes
    or ships the cache as a (K, V) pair of one head size, and has not
    been given a form for one latent plane an entry."""
    s, p = config.scheduler, config.parallel
    own = config.model.family.refusals
    checks = (
        (config.cache.resolved_kv_dtype() == "int8", "int8 KV pages",
         "QuantKV quantizes a K and a V plane a head; the latent plane "
         "has no quantized form"),
        (config.engine_role != "both", "disaggregated prefill/decode",
         "the handoff's KV_WIRE_VERSION frames carry K and V planes"),
        (config.offload.enable, "KV offload",
         "a page's payload is its K and V planes"),
        (config.checkpoint_interval_tokens > 0,
         "mid-stream checkpoint descriptors",
         "a descriptor restores K and V pages"),
        (s.speculative_k > 0, "speculative decoding by prompt lookup",
         "its verify step is a single-step program that writes K and V "
         "pages eagerly; over a latent a draft is verified inside the "
         "deferred burst, by a family that declares a draft module "
         "(--draft-module)"),
        (p.tensor_parallel_size > 1, "tensor parallelism",
         own["tensor parallelism"]),
        (p.pipeline_parallel_size > 1, "pipeline-parallel serving",
         "the staged forward builds K and V planes"),
        (p.context_parallel_size > 1, "context-parallel prefill",
         "the ring prefill walks K and V planes"),
        (config.model.quantization != "none", "weight quantization",
         own["weight quantization"]),
        (s.unified_step, "the unified ragged step",
         "its kernels read K and V pages of one head size"),
        (config.lora.enable, "LoRA", "the model has no LoRA targets"),
        (config.cache.cache_layout == "stacked",
         "cache_layout='stacked'",
         "the latent planes are per-entry buffers, two a layer"),
    )
    return [(feature, why) for on, feature, why in checks if on]


# ---- staticcheck config-contract markers -------------------------------
# Read statically by staticcheck/analyzers/config_contract.py (keep
# them literals). Every field reachable from EngineConfig must map to
# a tpu-engine CLI flag by naming convention, appear in
# CLI_FLAG_ALIASES, or be declared INTERNAL here — so "operators
# can't reach this knob" is always a decision, never an accident.

CLI_FLAG_ALIASES = {
    # field path                    flag that sets it
    "model.name": "--model",
    "cache.enable_prefix_caching": "--disable-prefix-caching",
    "lora.enable": "--enable-lora",
    "offload.enable": "--enable-kv-offload",
    "offload.host_pool_bytes": "--kv-host-pool-bytes",
    "offload.remote_url": "--kv-remote-url",
    "kvecon.summary_top_k": "--kv-summary-top-k",
    "kvecon.admit_hits": "--kv-admit-hits",
    "kvecon.ttl_s": "--kv-ttl-s",
    "kvecon.watermark_high": "--kv-watermark-high",
    "kvecon.watermark_low": "--kv-watermark-low",
    "autotune.mode": "--autotune",
    "autotune.interval_s": "--autotune-interval-s",
    "autotune.dead_band": "--autotune-dead-band",
    "autotune.controllers": "--autotune-controllers",
    "autotune.freeze_window_s": "--autotune-freeze-window-s",
    "autotune.burn_threshold": "--autotune-burn-threshold",
    "autotune.target_itl_ms": "--autotune-target-itl-ms",
    "autotune.min_spec_k": "--autotune-min-spec-k",
    "autotune.min_checkpoint_interval_tokens":
        "--autotune-min-checkpoint-interval-tokens",
    "autotune.max_checkpoint_interval_tokens":
        "--autotune-max-checkpoint-interval-tokens",
    "autotune.min_shed_threshold": "--autotune-min-shed-threshold",
}

INTERNAL_FIELDS = {
    # ModelConfig architecture hyperparameters are owned by the
    # checkpoint's HF config.json (from_hf_config) — a CLI override
    # would desync weights from geometry.
    "model.architecture",
    "model.vocab_size",
    "model.hidden_size",
    "model.intermediate_size",
    "model.num_hidden_layers",
    "model.num_attention_heads",
    "model.num_key_value_heads",
    "model.head_dim",
    "model.max_position_embeddings",
    "model.rms_norm_eps",
    "model.rope_theta",
    "model.tie_word_embeddings",
    "model.do_layer_norm_before",
    "model.activation",
    "model.attention_bias",
    "model.num_local_experts",
    "model.num_experts_per_tok",
    "model.full_attention_interval",
    "model.partial_rotary_factor",
    "model.linear_num_key_heads",
    "model.linear_num_value_heads",
    "model.linear_key_head_dim",
    "model.linear_value_head_dim",
    "model.linear_conv_kernel_dim",
    "model.num_experts",
    "model.expert_parallel_size",
    "model.expert_parallel_rank",
    "model.moe_intermediate_size",
    "model.shared_expert_intermediate_size",
    "model.norm_topk_prob",
    "model.attn_layer_period",
    "model.attn_layer_offset",
    "model.mamba_d_state",
    "model.mamba_d_conv",
    "model.mamba_expand",
    "model.mamba_dt_rank",
    "model.layer_types",
    "model.sliding_window",
    "model.conv_L_cache",
    "model.num_dense_layers",
    "model.kv_lora_rank",
    "model.q_lora_rank",
    "model.qk_nope_head_dim",
    "model.qk_rope_head_dim",
    "model.v_head_dim",
    "model.mla_q_scale",
    "model.mla_kv_scale",
    "model.zero_expert_num",
    "model.num_nextn_predict_layers",
    "model.routed_scaling_factor",
    "model.mamba_n_heads",
    "model.mamba_d_head",
    "model.mamba_chunk_size",
    "model.embedding_multiplier",
    "model.attention_multiplier",
    "model.residual_multiplier",
    "model.logits_scaling",
    "model.diffusion_block_length",
    "model.mask_token_id",
    "model.diffusion_steps",
    "model.diffusion_remasking",
    "model.diffusion_confidence_threshold",
    # Per-shape kernel overrides resolved by the model runner's
    # compile probe, not operator-set (--attention-impl is the knob).
    "model.attention_impl_decode",
    "model.attention_impl_prefill",
    "model.attention_impl_unified",
    # Data parallelism is derived mesh residue (devices not consumed
    # by tp/pp/sp), never requested directly.
    "parallel.data_parallel_size",
    # Derived from the model and the scheduler's widths.
    "cache.num_state_slots",
    # The model's own (EngineConfig.__post_init__ copies them).
    "scheduler.block_length",
    "scheduler.block_steps",
}

# Mutually-exclusive feature combos: (field_a, field_b, token). The
# analyzer requires a config-time `raise ValueError` in this module
# whose message contains `token`, AND a pytest.raises test under
# tests/ referencing both `token` and field_b's name — deleting
# either the rejection or its test is a staticcheck failure.
EXCLUSIVITY_RULES = (
    ("scheduler.speculative_k", "scheduler.deferred_kv_writes",
     "deferred_kv"),
    ("engine_role", "scheduler.speculative_k", "engine_role"),
)
# Dissolved by the unified ragged step (docs/unified_step.md):
#   async_scheduling x decode_steps, async_scheduling x
#   speculative_k, engine_role x async_scheduling. Those combos are
#   now legal compositions, not rejected pairs.
# Dissolved by the topology-aware mesh + pp/cp ragged step
#   (docs/parallelism.md): kv_cache_dtype x pipeline_parallel_size,
#   kv_cache_dtype x context_parallel_size — QuantKV pytree specs
#   flow through the pp/sp shard_map boundaries with congruent
#   data+scale sharding (parallel/mesh.py shard_cache).


def bench_1b_model_config() -> ModelConfig:
    """The 1B-class llama geometry the ``--model bench-1b`` server
    builds (chip_smoke.py, tests/conftest.py)."""
    return ModelConfig(
        name="llama-1b-class",
        architecture="llama",
        vocab_size=32128,
        hidden_size=2048,
        intermediate_size=5632,
        num_hidden_layers=16,
        num_attention_heads=32,
        num_key_value_heads=8,
        head_dim=64,
        max_position_embeddings=2048,
        dtype="bfloat16",
    )


def tiny_model_config(architecture: str = "llama") -> ModelConfig:
    """A tiny model for tests/benchmarks that runs anywhere."""
    return ModelConfig(
        name=f"tiny-{architecture}",
        architecture=architecture,
        vocab_size=512,
        hidden_size=128,
        intermediate_size=256,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2 if architecture == "llama" else 4,
        max_position_embeddings=512,
        activation={"llama": "silu", "opt": "relu",
                    "gpt2": "gelu"}[architecture],
        dtype="float32",
    )


def tiny_jamba_config() -> ModelConfig:
    """A tiny Jamba (both layer kinds, the attention layer neither
    first nor last, one KV head under four query heads) for tests that
    run anywhere."""
    return ModelConfig(
        name="tiny-jamba",
        architecture="jamba",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=1,
        max_position_embeddings=512,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        attn_layer_period=3,
        attn_layer_offset=1,
        mamba_d_state=8,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_dt_rank=4,
        dtype="float32",
    )


def tiny_lfm2_moe_config(expert_parallel_size: int = 1,
                         expert_parallel_rank: int = 0) -> ModelConfig:
    """A tiny LFM2-MoE (both layer kinds in an order no period gives,
    one dense feed-forward before the expert layers, held experts of a
    wider router, two query heads a KV head) for tests that run
    anywhere."""
    return ModelConfig(
        name="tiny-lfm2-moe",
        architecture="lfm2_moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=5,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        rope_theta=1e6,
        tie_word_embeddings=True,
        layer_types=("conv", "full_attention", "conv", "conv",
                     "full_attention"),
        conv_L_cache=3,
        num_dense_layers=1,
        num_experts=8 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        dtype="float32",
    )


def tiny_granitemoehybrid_config(expert_parallel_size: int = 1,
                                 expert_parallel_rank: int = 0
                                 ) -> ModelConfig:
    """A tiny Granite-MoE-hybrid (Mamba-2 mixers around one attention
    layer that is neither first nor last, two query heads a KV head,
    held experts of a wider softmax router beside an ungated shared
    expert in every layer, the four multipliers at values that are not
    1 and an attention multiplier that is not ``head_dim ** -0.5``) for
    tests that run anywhere."""
    return ModelConfig(
        name="tiny-granitemoehybrid",
        architecture="granitemoehybrid",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=32,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        tie_word_embeddings=True,
        layer_types=("mamba", "mamba", "attention", "mamba"),
        mamba_n_heads=4,
        mamba_d_head=32,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_expand=2,
        mamba_chunk_size=8,
        num_experts=8 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=48,
        embedding_multiplier=12.0,
        attention_multiplier=0.125,
        residual_multiplier=0.22,
        logits_scaling=4.0,
        dtype="float32",
    )


def tiny_exaone_moe_config(expert_parallel_size: int = 1,
                           expert_parallel_rank: int = 0,
                           sliding_window: int = 16) -> ModelConfig:
    """A tiny EXAONE-MoE (windowed layers around one full layer that
    is neither first nor last, a window of one tiny page, two query
    heads a KV head, a leading dense layer, held experts of a wider
    sigmoid router beside a shared expert, a scaling factor that is
    not 1) for tests that run anywhere."""
    return ModelConfig(
        name="tiny-exaone-moe",
        architecture="exaone_moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=96,
        num_hidden_layers=4,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention", "sliding_attention"),
        sliding_window=sliding_window,
        num_dense_layers=1,
        num_experts=8 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=32,
        routed_scaling_factor=2.5,
        dtype="float32",
    )


def tiny_sdar_moe_config(expert_parallel_size: int = 1,
                         expert_parallel_rank: int = 0,
                         **overrides) -> ModelConfig:
    """A small SDAR-MoE for tests: blocks of four, every layer an
    expert layer, float32."""
    return ModelConfig(**{**dict(
        name="tiny-sdar-moe",
        architecture="sdar_moe",
        vocab_size=512,
        hidden_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=512,
        rms_norm_eps=1e-6,
        rope_theta=1e6,
        num_experts=8 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        diffusion_block_length=4,
        mask_token_id=511,
        diffusion_steps=2,
        diffusion_remasking="sequential",
        dtype="float32",
    ), **overrides})


def tiny_longcat_flash_config(expert_parallel_size: int = 1,
                              expert_parallel_rank: int = 0
                              ) -> ModelConfig:
    """A tiny LongCat-Flash (two layers of two latent-attention
    sublayers around a shortcut-connected expert branch, 8 routed + 4
    zero-compute experts chosen 3 at a time, both low-rank scales on)
    for tests that run anywhere."""
    return ModelConfig(
        name="tiny-longcat-flash",
        architecture="longcat_flash",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=1,
        head_dim=24,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        rope_theta=1e7,
        tie_word_embeddings=False,
        kv_lora_rank=24,
        q_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        mla_q_scale=(64 / 32) ** 0.5,
        mla_kv_scale=(64 / 24) ** 0.5,
        num_experts=8 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        zero_expert_num=4,
        routed_scaling_factor=6.0,
        dtype="float32",
    )


def tiny_glm4_moe_lite_config(vocab_size: int = 512,
                              num_nextn_predict_layers: int = 1
                              ) -> ModelConfig:
    """A tiny GLM-4 MoE lite (a dense layer, two expert layers of 8
    sigmoid-routed experts chosen 3 at a time beside a shared expert,
    latent attention, and the prediction module) for tests that run
    anywhere."""
    return ModelConfig(
        name="tiny-glm4-moe-lite",
        architecture="glm4_moe_lite",
        vocab_size=vocab_size,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=1,
        head_dim=24,
        max_position_embeddings=512,
        rms_norm_eps=1e-5,
        rope_theta=1e6,
        tie_word_embeddings=False,
        kv_lora_rank=24,
        q_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        num_dense_layers=1,
        num_experts=8,
        num_experts_per_tok=3,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=32,
        routed_scaling_factor=1.8,
        num_nextn_predict_layers=num_nextn_predict_layers,
        dtype="float32",
    )


def tiny_qwen3_next_config(expert_parallel_size: int = 1,
                           expert_parallel_rank: int = 0) -> ModelConfig:
    """A tiny hybrid model (one period and a half of the layer
    pattern, both layer kinds, held experts of a wider router) for
    tests that run anywhere."""
    return ModelConfig(
        name="tiny-qwen3-next",
        architecture="qwen3_next",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=0,
        num_hidden_layers=6,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=32,
        max_position_embeddings=512,
        rms_norm_eps=1e-6,
        rope_theta=1e7,
        full_attention_interval=3,
        partial_rotary_factor=0.25,
        linear_num_key_heads=2,
        linear_num_value_heads=4,
        linear_key_head_dim=16,
        linear_value_head_dim=16,
        linear_conv_kernel_dim=4,
        num_experts=16 // expert_parallel_size,
        expert_parallel_size=expert_parallel_size,
        expert_parallel_rank=expert_parallel_rank,
        num_experts_per_tok=4,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=32,
        norm_topk_prob=True,
        dtype="float32",
    )
