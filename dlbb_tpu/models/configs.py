"""Model size configurations (reference ``models.py:252-271`` MODEL_CONFIGS).

``attention="simplified"`` replicates the reference's benchmarking shortcut
(take the query third of the QKV projection as the attention output,
``models.py:162-167``); ``attention="full"`` is real causal multi-head
attention — an option the reference lacks but a real framework needs.
"""

from __future__ import annotations

from dataclasses import dataclass, fields as dataclass_fields, replace
from typing import Any, Optional

# the kinds of layer a ``layer_types`` pattern may name (the published
# config's own words)
LINEAR_ATTENTION = "linear_attention"
FULL_ATTENTION = "full_attention"
# multi-head latent attention (MLA): one low-rank latent and one rotary
# key a token, shared by every head (``model_type: deepseek_v3``)
LATENT_ATTENTION = "latent_attention"
# a Mamba-2 state-space (SSD) layer (``model_type: granitemoehybrid``):
# a scalar decay a head over a ``[d_head, d_state]`` state, ``B`` and
# ``C`` shared by the heads of a group (``ops/ssd.py``)
MAMBA = "mamba"
LAYER_KINDS = (LINEAR_ATTENTION, FULL_ATTENTION, LATENT_ATTENTION, MAMBA)
# lanes of one cached latent row: ``kv_lora_rank + qk_rope_head_dim``
# rounded up to whole lanes of 128 (the TPU tiles the plane's last dim
# by 128, so the memory is spent either way, and whole lanes are what
# the decode kernel's copies and products take)
LATENT_LANES = 128


@dataclass(frozen=True)
class ModelConfig:
    hidden_size: int
    num_layers: int
    num_heads: int
    ffn_intermediate: int
    # "full" — exact causal/bidirectional MHA, auto-routed to the pallas
    #   flash kernel on real TPUs at S >= transformer.FLASH_ROUTE_MIN_SEQ
    #   (same math, faster kernel; dense einsum elsewhere);
    # "dense" — exact MHA, einsum kernel always (opt-out of the routing);
    # "simplified" (reference parity shortcut) | "flash" (force the pallas
    # kernel, dlbb_tpu.ops) | "ring" | "ulysses" (sequence/context-parallel
    # attention — dlbb_tpu.parallel)
    attention: str = "full"
    dtype: str = "bfloat16"
    # Grouped-query attention: number of K/V heads (None = num_heads, i.e.
    # full MHA; 1 = MQA).  Query heads share K/V heads in groups of
    # num_heads // num_kv_heads.  The projection/params shrink in every
    # mode, and K/V activations stay at kv_heads width end-to-end through
    # every kernel (dense einsum broadcasting, grouped flash blocks,
    # grouped ring/Ulysses) — the only broadcasts left are sharding
    # fallbacks when a mesh axis cannot divide kv_heads (see
    # transformer._attention).
    num_kv_heads: int | None = None
    # Causal (decoder) masking; False = bidirectional attention.  The
    # "simplified" reference shortcut has no attention at all and ignores
    # this; every real kernel (full/flash/ring/ulysses) supports both.
    causal: bool = True
    # Mixture-of-experts FFN (0 = dense FFN).  num_experts > 0 replaces each
    # block's FFN with moe_top_k-gated experts; experts shard over an
    # ``ep`` mesh axis (capability extension — the reference has no EP,
    # SURVEY §2.2).
    num_experts: int = 0
    moe_top_k: int = 2
    # "dense": every expert runs on every token, gates select (exact, no
    # drops; per-device FLOPs scale with num_experts/ep).
    # "capacity": GShard-style einsum dispatch into per-expert capacity
    # buffers of moe_capacity_factor * S * k / E slots per sequence;
    # over-capacity (token, expert) routing slots are dropped individually
    # (a fully-dropped token passes through the residual only) and
    # per-device FLOPs are capacity-bounded.
    moe_dispatch: str = "dense"
    moe_capacity_factor: float = 1.25
    # Activation rematerialisation: recompute each block's activations in
    # the backward pass instead of storing them (jax.checkpoint around the
    # scanned block) — trades ~1/3 more FLOPs for O(layers) less activation
    # HBM, the standard TPU memory/compute trade.
    remat: bool = False
    # How the tensor-parallel projections meet their collectives
    # (dlbb_tpu/parallel/collective_matmul.py, docs/overlap.md):
    # - "auto" (the default): the shapes decide.  A dense-FFN forward or
    #   train step on a mesh with tp > 1 and pp == 1 takes the overlapped
    #   route when its hops are large enough not to be latency and enough
    #   of them hide behind the partial matmuls beside them
    #   (collective_matmul.auto_schedule: the thresholds and the chip runs
    #   that set them); every other program is the fused one, and nothing
    #   raises.  models/transformer.py::tp_overlap_route gives the route a
    #   program took;
    # - "off": forced fused.  GSPMD Megatron layout, XLA inserts the two
    #   exposed all-reduces a layer;
    # - "ring": forced.  Every TP projection is a ring-decomposed
    #   all-gather-matmul / matmul-reduce-scatter, a chain of neighbour
    #   ppermutes hidden behind per-shard partial matmuls, and activations
    #   between blocks live sequence-sharded over tp;
    # - "bidir": forced, the same on a bidirectional ring (both ICI
    #   directions per step; half the hops for the all-gather side).
    # A forced ring requires tp > 1, pp == 1, a dense (non-MoE) FFN, and a
    # sequence length divisible by the sequence-shard count, validated by
    # validate_tp_overlap below.
    tp_overlap: str = "auto"
    # Rematerialisation policy (effective only with remat=True):
    # - "full": save nothing per block, recompute the whole block forward
    #   in the backward pass (max memory saving, ~+1 forward of recompute);
    # - "dots": jax.checkpoint_policies.dots_saveable — save matmul/einsum
    #   outputs, recompute only the cheap elementwise ops (layernorm, gelu,
    #   softmax): most of the memory saving at near-zero matmul recompute,
    #   usually the best MFU point on TPU (the score tensors of dense
    #   attention are dot outputs, so "dots" keeps them resident — at long
    #   S prefer "full" or flash attention).
    remat_policy: str = "full"
    # -- the block family.  The defaults are the reference's GPT block
    # (LayerNorm with bias, GELU, biased projections, no vocabulary:
    # hidden states in, hidden states out).  The second family is the
    # hybrid of ``models/hybrid.py`` (Olmo-Hybrid): RMSNorm applied to
    # each sub-layer's OUTPUT, bias-free projections, a SwiGLU MLP,
    # QK-norm, a token embedding and an untied output head, and a stack
    # whose layers follow the repeating pattern ``layer_types``.  The
    # field names are the ones the model's published ``config.json``
    # uses, so that a benchmark configuration's ``published`` section
    # compares with ``program.model`` key by key.  Only these two
    # combinations are implemented; any other is an error, not a guess.
    norm: str = "layernorm"             # "layernorm" | "rmsnorm"
    mlp: str = "gelu"                   # "gelu" (up, down) | "swiglu"
    bias: bool = True
    qk_norm: bool = False
    rms_norm_eps: float = 1e-6
    vocab_size: int = 0                 # 0 = no embedding, no head
    # one PERIOD of the layer pattern, e.g. ("linear_attention",) * 3 +
    # ("full_attention",); num_layers is a whole number of periods.
    # None = one kind of layer (the GPT block).
    layer_types: Optional[tuple[str, ...]] = None
    # the linear-attention (gated delta rule) layers' sizes
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    linear_allow_neg_eigval: bool = False
    # where a sub-layer's RMSNorm sits: "post" (OLMo 2/3: on the
    # sub-layer's OUTPUT, ``h = x + norm(f(x))``), "pre" (on its INPUT,
    # ``h = x + f(norm(x))``) or "sandwich" (one on each, four scales a
    # layer: ``h = x + norm(f(norm(x)))``)
    norm_placement: str = "post"
    # the latent-attention (MLA) layers' sizes and rotary base, under
    # the published config's own keys.  ``q_lora_rank`` is not here: the
    # low-rank query path is not implemented (ROADMAP.md, Queue 2)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotary base of the latent-attention layers (adjacent pairs of the
    # rotary key) and, where > 0, of the full-attention layers (the
    # whole head dim, half-split pairs ``(i, i + d/2)``); 0 = the
    # full-attention layers take no rotary positions (Olmo-Hybrid)
    rope_theta: float = 0.0
    # the looped stack: the SAME ``num_layers`` layers and final norm run
    # ``total_ut_steps`` times a token, each pass over K/V planes of its
    # own, with a one-output exit gate after every pass; a token leaves
    # at the first pass whose cumulative exit probability reaches
    # ``early_exit_threshold`` (1 = every token runs every pass).  Both
    # under the published config's own keys.  1 = a plain stack, no gate
    total_ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # routed experts of the ``layer_types`` family (``ops/routed_experts
    # .py``): the first ``first_k_dense_replace`` layers keep the dense
    # SwiGLU of ``ffn_intermediate``; every later layer has
    # ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``,
    # ``num_experts_per_tok`` a token chosen by ``sigmoid`` scores plus a
    # selection bias (``noaux_tc`` with one group), weighted by the
    # chosen scores normalised to ``routed_scaling_factor``, beside
    # ``n_shared_experts`` shared experts every token takes.  0 routed
    # experts = a dense MLP in every layer.  (``num_experts`` above is
    # the GPT block's softmax-gated MoE, which serving refuses.)
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    # the state-space (``mamba``) layers' sizes, under the published
    # config's own keys: ``mamba_n_heads`` heads of ``mamba_d_head``
    # (together ``mamba_expand x hidden_size``), a state of
    # ``mamba_d_state`` a head and value, ``B`` and ``C`` of
    # ``mamba_n_groups`` groups (one is what is implemented), a causal
    # convolution of ``mamba_d_conv`` positions over x, B and C together
    # (with a bias where ``mamba_conv_bias``), and the tokens of one
    # chunk of the chunked scan
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_d_conv: int = 0
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    # Granite's four scalars of the ``layer_types`` family, each neutral
    # by default: the embedding times ``embedding_multiplier``, each
    # sub-layer's output times ``residual_multiplier`` before it joins
    # the residual stream, the logits over ``logits_scaling``, and the
    # attention scores times ``attention_multiplier`` in place of
    # ``head_dim ** -0.5`` (None).  ``tie_word_embeddings``: the output
    # head IS the embedding table (one tensor, counted once)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    tie_word_embeddings: bool = False

    def __post_init__(self) -> None:
        if self.layer_types is not None:
            # a YAML/JSON list arrives as a list; the dataclass is hashed
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        self._validate_family()
        if self.hidden_size % self.num_heads != 0:
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_heads {self.num_heads}"
            )
        if self.attention not in ("full", "dense", "simplified", "flash",
                                  "ring", "ulysses"):
            raise ValueError(f"unknown attention mode {self.attention!r}")
        if self.num_experts < 0:
            raise ValueError(f"num_experts must be >= 0, got {self.num_experts}")
        if self.num_experts > 0 and not (
                1 <= self.moe_top_k <= self.num_experts):
            raise ValueError(
                f"moe_top_k={self.moe_top_k} must be in [1, "
                f"num_experts={self.num_experts}]"
            )
        if self.moe_dispatch not in ("dense", "capacity"):
            raise ValueError(
                f"unknown moe_dispatch {self.moe_dispatch!r} "
                "(expected 'dense' or 'capacity')"
            )
        if self.moe_capacity_factor <= 0:
            raise ValueError(
                f"moe_capacity_factor must be > 0, got "
                f"{self.moe_capacity_factor}"
            )
        if self.tp_overlap not in ("auto", "off", "ring", "bidir"):
            raise ValueError(
                f"unknown tp_overlap {self.tp_overlap!r} "
                "(expected 'auto', 'off', 'ring', or 'bidir')"
            )
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} "
                "(expected 'full' or 'dots'; remat=False is the no-remat "
                "point of the ladder)"
            )
        if self.num_kv_heads is not None:
            if not 1 <= self.num_kv_heads <= self.num_heads:
                raise ValueError(
                    f"num_kv_heads={self.num_kv_heads} must be in "
                    f"[1, num_heads={self.num_heads}]"
                )
            if self.num_heads % self.num_kv_heads != 0:
                raise ValueError(
                    f"num_heads={self.num_heads} not divisible by "
                    f"num_kv_heads={self.num_kv_heads}"
                )

    def _validate_family(self) -> None:
        gpt = (self.norm == "layernorm" and self.mlp == "gelu" and self.bias
               and not self.qk_norm and self.vocab_size == 0
               and self.layer_types is None and self.total_ut_steps == 1)
        if gpt:
            return
        kinds = set(self.layer_types or ())
        unknown = kinds - set(LAYER_KINDS)
        if self.layer_types is not None and (not self.layer_types
                                             or unknown):
            raise ValueError(
                f"layer_types must be a non-empty pattern of {LAYER_KINDS}, "
                f"got {self.layer_types}")
        # QK-norm belongs to the full-attention layers (OLMo 2/3), which
        # take it, rotary positions, both or neither (``nope``: the
        # recurrent layers beside them carry position); a stack of
        # latent-attention layers norms its latent instead
        hybrid = (self.norm == "rmsnorm" and self.mlp == "swiglu"
                  and not self.bias and self.vocab_size > 0
                  and self.layer_types is not None
                  and (FULL_ATTENTION in kinds or not self.qk_norm))
        if not hybrid:
            raise ValueError(
                "model family not implemented: the program runs the GPT "
                "block (norm='layernorm', mlp='gelu', bias=true, "
                "qk_norm=false, no vocab_size, no layer_types, "
                "total_ut_steps=1) or the layer_types block "
                "(norm='rmsnorm', mlp='swiglu', bias=false, vocab_size > "
                "0, layer_types given, norm_placement 'post', 'pre' or "
                "'sandwich', total_ut_steps >= 1; its full_attention "
                "layers take qk_norm=true, rotary positions by rope_theta "
                "> 0, both or neither; qk_norm=false for a stack without "
                "full_attention layers); got "
                f"norm={self.norm!r}, mlp={self.mlp!r}, bias={self.bias}, "
                f"qk_norm={self.qk_norm}, rope_theta={self.rope_theta}, "
                f"vocab_size={self.vocab_size}, "
                f"layer_types={self.layer_types}, "
                f"total_ut_steps={self.total_ut_steps}")
        period = len(self.layer_types)
        lead = self.first_k_dense_replace
        if lead < 0 or lead % period or (self.num_layers - lead) % period \
                or lead > self.num_layers:
            raise ValueError(
                f"num_layers={self.num_layers} is not a whole number of "
                f"periods of {period} layers (layer_types="
                f"{self.layer_types}) after first_k_dense_replace={lead} "
                "leading layers (themselves whole periods)")
        if self.norm_placement not in ("post", "pre", "sandwich"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r} "
                "(expected 'post', 'pre' or 'sandwich')")
        if self.rope_theta > 0 and FULL_ATTENTION in kinds \
                and self.head_dim % 2:
            raise ValueError(
                f"rotary positions need an even head_dim, got "
                f"{self.head_dim}")
        if self.total_ut_steps < 1 or not 0 < self.early_exit_threshold <= 1:
            raise ValueError(
                "the looped stack needs total_ut_steps >= 1 and 0 < "
                f"early_exit_threshold <= 1, got {self.total_ut_steps} and "
                f"{self.early_exit_threshold}")
        if self.total_ut_steps > 1 and (kinds != {FULL_ATTENTION}
                                        or self.n_routed_experts):
            raise ValueError(
                f"total_ut_steps={self.total_ut_steps} is implemented for "
                "a stack of full_attention layers with a dense MLP: only "
                "the K/V planes are laid out per (pass, layer); a "
                "recurrent state or a latent plane per pass, and the "
                "routing of every pass among what the probes return, are "
                f"not (layer_types={self.layer_types}, n_routed_experts="
                f"{self.n_routed_experts})")
        if LATENT_ATTENTION in kinds:
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim,
                     self.qk_rope_head_dim, self.v_head_dim)
            if min(sizes) < 1 or self.rope_theta <= 0 \
                    or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent_attention layers need kv_lora_rank, "
                    "qk_nope_head_dim, an even qk_rope_head_dim and "
                    f"v_head_dim >= 1 and rope_theta > 0, got {sizes}, "
                    f"rope_theta={self.rope_theta}")
        if self.n_routed_experts:
            sizes = (self.num_experts_per_tok, self.moe_intermediate_size)
            if min(sizes) < 1 or self.num_experts_per_tok \
                    > self.n_routed_experts or self.n_shared_experts < 0:
                raise ValueError(
                    "routed experts need 1 <= num_experts_per_tok <= "
                    "n_routed_experts, moe_intermediate_size >= 1 and "
                    f"n_shared_experts >= 0, got {sizes}, n_shared_experts="
                    f"{self.n_shared_experts}")
        elif lead:
            raise ValueError(
                f"first_k_dense_replace={lead} without n_routed_experts: "
                "every layer's MLP is dense already")
        if LINEAR_ATTENTION in self.layer_types:
            sizes = (self.linear_num_key_heads, self.linear_num_value_heads,
                     self.linear_key_head_dim, self.linear_value_head_dim,
                     self.linear_conv_kernel_dim)
            if min(sizes) < 1:
                raise ValueError(
                    "linear_attention layers need linear_num_key_heads, "
                    "linear_num_value_heads, linear_key_head_dim, "
                    "linear_value_head_dim and linear_conv_kernel_dim >= 1, "
                    f"got {sizes}")
            if self.linear_num_key_heads != self.linear_num_value_heads:
                raise ValueError(
                    "linear_num_key_heads != linear_num_value_heads "
                    f"({self.linear_num_key_heads} != "
                    f"{self.linear_num_value_heads}): grouped value heads "
                    "in the gated delta rule are not implemented")
        if MAMBA in kinds:
            sizes = (self.mamba_n_heads, self.mamba_d_head,
                     self.mamba_d_state, self.mamba_d_conv,
                     self.mamba_chunk_size)
            if min(sizes) < 1:
                raise ValueError(
                    "mamba layers need mamba_n_heads, mamba_d_head, "
                    "mamba_d_state, mamba_d_conv and mamba_chunk_size >= 1, "
                    f"got {sizes}")
            if self.mamba_n_heads * self.mamba_d_head \
                    != self.mamba_expand * self.hidden_size:
                raise ValueError(
                    f"mamba_n_heads x mamba_d_head = {self.mamba_n_heads} x "
                    f"{self.mamba_d_head} is not mamba_expand x hidden_size "
                    f"= {self.mamba_expand} x {self.hidden_size}")
            if self.mamba_n_groups != 1:
                raise ValueError(
                    f"mamba_n_groups={self.mamba_n_groups} is not "
                    "implemented: B and C are one group's, shared by every "
                    "head (ops/ssd.py)")
            if LINEAR_ATTENTION in kinds:
                raise ValueError(
                    "linear_attention and mamba layers in one stack are not "
                    "implemented: the cache keeps ONE recurrent-state plane "
                    "and one plane of convolution inputs, of one kind's "
                    "shape (serve/kvcache.py::HybridCache)")
        if (self.is_moe or self.forces_tp_ring or self.remat
                or self.attention not in ("full", "dense")
                or not self.causal):
            raise ValueError(
                "the hybrid family runs causal exact attention with a dense "
                "MLP: no experts, tp_overlap, remat, or attention modes "
                "other than 'full'/'dense'")

    @property
    def forces_tp_ring(self) -> bool:
        """``tp_overlap`` names a ring schedule: that route is taken or
        the plan is refused, where "auto" would quietly stay fused."""
        return self.tp_overlap in ("ring", "bidir")

    @property
    def is_hybrid(self) -> bool:
        """The heterogeneous stack of ``models/hybrid.py``."""
        return self.layer_types is not None

    def layers_of(self, kind: str) -> int:
        """How many of the ``num_layers`` layers are of ``kind``."""
        if self.layer_types is None:
            return self.num_layers if kind == FULL_ATTENTION else 0
        periods = self.num_layers // len(self.layer_types)
        return periods * self.layer_types.count(kind)

    @property
    def kv_planes(self) -> int:
        """K (and V) planes a cache holds: one for every (pass,
        full-attention layer), pass-major (``pass x L_full + l``)."""
        return self.total_ut_steps * self.layers_of(FULL_ATTENTION)

    @property
    def has_routed_experts(self) -> bool:
        """Routed and shared experts after the leading dense layers
        (``ops/routed_experts.py``), in the ``layer_types`` family."""
        return self.n_routed_experts > 0

    @property
    def expert_layers(self) -> int:
        """Layers whose MLP is the expert layer."""
        return (self.num_layers - self.first_k_dense_replace
                if self.has_routed_experts else 0)

    @property
    def latent_width(self) -> int:
        """Values of one cached latent row as counted: the normed latent
        and the rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values of one cached latent row as HELD: ``latent_width`` in
        whole lanes (``LATENT_LANES``), the rest zeros."""
        return -(-self.latent_width // LATENT_LANES) * LATENT_LANES

    @property
    def mamba_inner(self) -> int:
        """The state-space layers' inner width: every head's values."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_channels(self) -> int:
        """Channels of the state-space layers' convolution: x of every
        head, then B and C of every group."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def linear_conv_channels(self) -> int:
        """Channels of the linear layers' short convolution: q, k and v
        of every head."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        """Effective K/V head count (GQA; == num_heads for full MHA)."""
        return self.num_kv_heads or self.num_heads

    @property
    def qkv_width(self) -> int:
        """Fused QKV projection output width:
        H (queries) + 2 * kv_heads * head_dim (keys + values)."""
        return self.hidden_size + 2 * self.kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ModelConfig":
        """Build from the YAML ``model:`` section
        (``configs/baseline_config.yaml``, schema parity with reference
        ``config/baseline_config.yaml:7-13``).  A ``size:`` key selects a
        named config; explicit fields override it."""
        d = dict(d)
        size = d.pop("size", None)
        base = MODEL_CONFIGS[size] if size else None
        known = {f.name for f in dataclass_fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            # a misspelt key must not fall back to a default: a typo in
            # ``layer_types`` would run the GPT block under another
            # model's name
            raise ValueError(
                f"unknown model key(s) {unknown}; ModelConfig has "
                f"{sorted(known)}")
        fields = {}
        for k in known:
            if k in d:
                fields[k] = d[k]
            elif base is not None:
                fields[k] = getattr(base, k)
        return cls(**fields)


# Attention modes that actually partition the sequence dimension over an
# sp mesh axis.  Single source of truth for config validation (harnesses)
# and the mesh-level guard in transformer._attention.
SP_CAPABLE_ATTENTION = ("ring", "ulysses")


def validate_attention_parallelism(config: ModelConfig, sp: int) -> None:
    """Reject attention-mode / sequence-parallel combinations that would
    silently compute the wrong thing or replicate work per sp shard."""
    if config.is_hybrid and sp > 1:
        raise ValueError(
            f"parallelism.sequence_parallel={sp} is not implemented for "
            "layer_types models: the gated delta rule's state would have "
            "to be handed from one sequence shard to the next")
    if config.attention in SP_CAPABLE_ATTENTION and sp <= 1:
        raise ValueError(
            f"attention={config.attention!r} requires "
            "parallelism.sequence_parallel > 1"
        )
    if sp > 1 and config.attention not in SP_CAPABLE_ATTENTION:
        raise ValueError(
            f"parallelism.sequence_parallel={sp} requires attention in "
            f"{SP_CAPABLE_ATTENTION} (attention={config.attention!r} does "
            "not partition the sequence; it would run replicated per sp "
            "shard)"
        )


def validate_tp_overlap(config: ModelConfig, tp: int, pp: int = 1,
                        seq_len: int = 0, sp: int = 1) -> None:
    """Reject a FORCED tp_overlap schedule the decomposition cannot run
    ("auto" and "off" pass anywhere: where a ring cannot run, "auto"
    stays fused).

    The ring kernels gather/scatter the *sequence* dim over tp, so the
    knob needs a real tp axis, an even sequence split, a dense FFN (the
    MoE expert dispatch keeps its GSPMD lowering), and no pipeline (the
    pipeline engine owns its own shard_map and activation layout)."""
    if not config.forces_tp_ring:
        return
    if tp <= 1:
        raise ValueError(
            f"model.tp_overlap={config.tp_overlap!r} requires "
            "parallelism.world_size (tp) > 1 — without a tp axis there is "
            "no collective to overlap"
        )
    if pp > 1:
        raise ValueError(
            f"model.tp_overlap={config.tp_overlap!r} is incompatible with "
            "pipeline_parallel > 1 (the pipeline engine owns the "
            "activation layout)"
        )
    if config.is_moe:
        raise ValueError(
            f"model.tp_overlap={config.tp_overlap!r} requires a dense FFN "
            "(the MoE expert dispatch is not ring-decomposed; run MoE "
            "models with tp_overlap='off')"
        )
    if seq_len and seq_len % (tp * max(1, sp)) != 0:
        raise ValueError(
            f"input.sequence_length={seq_len} not divisible by the "
            f"sequence-shard count {tp * max(1, sp)} (tp={tp}"
            f"{f' x sp={sp}' if sp > 1 else ''}) required by "
            f"tp_overlap={config.tp_overlap!r}"
        )


def validate_expert_parallelism(config: ModelConfig, ep: int) -> None:
    """Reject expert-parallel degrees that cannot shard the expert dim."""
    if ep <= 1:
        return
    if config.has_routed_experts:
        raise ValueError(
            f"parallelism.expert_parallel={ep} is not implemented for the "
            "layer_types family's routed experts: every expert of a layer "
            "is held by the chip that holds the layer (no expert-parallel "
            "share and no all-to-all; ROADMAP.md, Queue 2)")
    if not config.is_moe:
        raise ValueError(
            f"parallelism.expert_parallel={ep} requires a MoE model "
            "(model.num_experts > 0)"
        )
    if config.num_experts % ep != 0:
        raise ValueError(
            f"num_experts={config.num_experts} not divisible by "
            f"expert_parallel={ep}"
        )


# Attention modes the serving engine's paged-cache path supports: the
# cache stores K/V at kv_heads width and decode attends over it with the
# exact dense kernel, so only the exact-MHA modes qualify ("simplified"
# has no K/V at all; ring/ulysses partition the sequence the cache owns).
SERVABLE_ATTENTION = ("full", "dense")

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}

# serving.kv_quantization values the paged cache supports: "int8"
# stores K/V blocks as int8 with one fp32 scale per (layer, slot,
# block, kv-head) as a side-channel plane (serve/kvcache.QuantKVCache).
KV_QUANTIZATION_MODES = ("none", "int8")


def kv_cache_bytes_raw(num_layers: int, max_batch: int, max_seq: int,
                       kv_heads: int, head_dim: int,
                       dtype: str = "bfloat16",
                       kv_quantization: str = "none",
                       block_size: Optional[int] = None) -> int:
    """The one KV-cache footprint formula, on raw geometry (for callers
    holding a serialized model record instead of a ModelConfig — e.g.
    ``obs/attribution.py`` pricing a run's report): K + V, every layer,
    every slot, ``max_seq`` tokens at GQA ``kv_heads`` width.

    ``kv_quantization="int8"`` prices the quantized layout instead:
    1 byte per K/V element plus the fp32 scale side-channel (one scale
    per block per kv-head, needing ``block_size``)."""
    if kv_quantization not in KV_QUANTIZATION_MODES:
        raise ValueError(
            f"kv_quantization={kv_quantization!r} not in "
            f"{KV_QUANTIZATION_MODES}"
        )
    elems = 2 * num_layers * max_batch * max_seq * kv_heads
    if kv_quantization == "int8":
        if block_size is None or block_size < 1 or max_seq % block_size:
            raise ValueError(
                "kv_quantization='int8' needs a positive block_size "
                f"dividing max_seq={max_seq} to price the per-block "
                f"scale plane (got block_size={block_size})"
            )
        # int8 data + fp32 scales [L, B, num_blocks, kvh] for K and V
        return elems * head_dim + (elems // block_size) * 4
    return elems * head_dim * _DTYPE_BYTES.get(dtype, 2)


def kv_rows(config: ModelConfig, tp: int = 1) -> bool:
    """Whether a ``layer_types`` model's K/V planes hold a token's K (or
    V) of a layer as ONE row of ``kv_heads x head_dim`` values, ``[planes,
    slots, blocks, block, kv_heads x head_dim]``, and not head by head:
    where a head is no whole number of 128 lanes but the row is (8 heads
    of 64 are four lanes-rows).  Held head by head, the TPU would tile
    ``(heads, head_dim)`` by (8, 128) and a head of 64 would take the
    room of 128: twice the K/V.  The row is what the decode kernel then
    copies and multiplies (``ops/decode_attention.py``).  Not under tp,
    where the planes keep a head dim to shard, and not for a model
    without full-attention layers, whose planes hold nothing."""
    row = config.kv_heads * config.head_dim
    return (config.is_hybrid and tp <= 1 and config.kv_planes > 0
            and config.head_dim % 128 != 0 and row % 128 == 0)


def cache_kv_heads(config: ModelConfig, tp: int = 1) -> int:
    """K/V heads a cache plane holds.  The GPT block's planes hold
    ``kv_heads``.  A ``layer_types`` model's hold them rounded up to a
    whole number of 8 when the head dim is not sharded (the added heads
    stay zero and are attended by zero queries): the TPU tiles a plane
    by (8, 128) over (heads, head_dim), so the memory is spent either
    way, and with 30 heads the v5e compiler re-laid both whole planes
    out inside every decode step to get whole tiles (two 2 GB copies a
    step, compiled for the chip without it, PR 27).  Under tp the planes
    keep ``kv_heads``, so that query and key heads split alike.  Planes
    that hold whole rows (:func:`kv_rows`) hold ``kv_heads`` too: no
    head is added and none is widened."""
    if config.is_hybrid and tp <= 1 and not kv_rows(config, tp):
        return -(-config.kv_heads // 8) * 8
    return config.kv_heads


def kv_cache_bytes(config: ModelConfig, max_batch: int,
                   max_seq: int, kv_quantization: str = "none",
                   block_size: Optional[int] = None, tp: int = 1) -> int:
    """Total (unsharded) KV-cache footprint of a serving config: K + V,
    every layer (of a looped stack: in every pass,
    ``ModelConfig.kv_planes``), every slot, ``max_seq`` tokens at GQA
    ``kv_heads`` width, in the model dtype (or the int8 + fp32-scale
    layout when quantized)."""
    return kv_cache_bytes_raw(config.kv_planes, max_batch,
                              max_seq, cache_kv_heads(config, tp),
                              config.head_dim,
                              config.dtype,
                              kv_quantization=kv_quantization,
                              block_size=block_size)


def state_cache_bytes(config: ModelConfig, max_batch: int) -> int:
    """Total (unsharded) footprint of the recurrent layers' slot-indexed
    state (``serve/kvcache.py::HybridCache``; linear-attention or
    state-space layers, of which a model has one kind): per layer and
    slot one float32 ``[heads, d_v, d_k]`` recurrent state and the last
    ``conv_kernel - 1`` inputs of the short convolution in the model
    dtype.  It does not grow with a slot's length, so the block ledger
    never counts it; 0 for a model without such layers."""
    itemsize = _DTYPE_BYTES.get(config.dtype, 2)
    n_ssm = config.layers_of(MAMBA)
    if n_ssm:
        # a state-space layer's: ``[heads, d_head, d_state]`` float32 and
        # the last ``d_conv - 1`` inputs of x, B and C together
        state = config.mamba_inner * config.mamba_d_state * 4
        conv = (config.mamba_d_conv - 1) * config.mamba_conv_channels
        return n_ssm * max_batch * (state + conv * itemsize)
    n_lin = config.layers_of(LINEAR_ATTENTION)
    if not n_lin:
        return 0
    state = (config.linear_num_value_heads * config.linear_value_head_dim
             * config.linear_key_head_dim * 4)
    conv = ((config.linear_conv_kernel_dim - 1) * config.linear_conv_channels
            * itemsize)
    return n_lin * max_batch * (state + conv)


def latent_cache_bytes(config: ModelConfig, max_batch: int, max_seq: int,
                       held: bool = True) -> int:
    """Total (unsharded) footprint of the latent-attention layers' paged
    plane (``serve/kvcache.py::HybridCache.latent``): per layer, slot and
    token ONE row of the normed latent and the rotated shared key, in the
    model dtype; as ``held`` (whole lanes, ``ModelConfig.latent_row``) or
    as counted (``latent_width``).  0 for a model without such layers."""
    n_lat = config.layers_of(LATENT_ATTENTION)
    if not n_lat:
        return 0
    row = config.latent_row if held else config.latent_width
    return (n_lat * max_batch * max_seq * row
            * _DTYPE_BYTES.get(config.dtype, 2))


def kv_cache_bytes_per_device(config: ModelConfig, max_batch: int,
                              max_seq: int, dp: int = 1,
                              tp: int = 1,
                              kv_quantization: str = "none",
                              block_size: Optional[int] = None) -> int:
    """Per-device KV-cache footprint under the serving sharding contract
    (slot dim over dp, kv-head dim over tp) — the ONE number both the
    build-time HBM budget gate (``validate_serving``) and the static
    memory audit's decode-step cross-check
    (``analysis/memory_audit.py``, rule ``serving-cache-drift``) price,
    so the two can never drift apart: the audit pins this formula
    against the donated cache-carry bytes of the compiled decode
    program.  The scale side-channel of the int8 layout shards over the
    same dp × tp axes as the data it scales, so one divisor covers
    both."""
    shards = max(1, dp) * (tp if tp > 1 else 1)
    # the state planes shard like the K/V planes (slots over dp, heads
    # over tp), so the one divisor covers them too
    return (kv_cache_bytes(config, max_batch, max_seq,
                           kv_quantization=kv_quantization,
                           block_size=block_size, tp=tp)
            + state_cache_bytes(config, max_batch)
            + latent_cache_bytes(config, max_batch, max_seq)) // shards


def validate_serving(config: ModelConfig, max_batch: int, max_seq: int,
                     block_size: int, dp: int = 1, tp: int = 1,
                     hbm_budget_bytes: Optional[int] = None,
                     draft_config: Optional[ModelConfig] = None,
                     kv_quantization: str = "none") -> None:
    """Reject serving configurations the engine cannot run — at build
    time, with a clear error, never as an OOM (or a wrong answer) in the
    middle of a trace.

    Covers the model envelope (exact-MHA attention, dense FFN, no
    tp_overlap), the cache divisibility contract (blocks tile max_seq;
    dp tiles the slot dim; tp tiles kv_heads), and — when
    ``hbm_budget_bytes`` is set — the per-device KV-cache HBM footprint:
    ``max_batch x max_seq`` K/V at kv_heads width, divided by the dp x tp
    shards that actually partition it.

    ``draft_config`` is the speculative-decoding draft model
    (``serving.speculation="draft-model"``): it is validated against the
    SAME mesh and cache geometry (the draft plane is sharded by the same
    ``ParallelismPlan``, so e.g. its ``kv_heads % tp`` contract is
    identical), and its resident weights + second KV-cache plane are
    priced INTO the HBM budget alongside the target cache — an
    infeasible ``(spec, max_batch, gamma)`` combination fails here at
    build time, not as an OOM mid-trace.

    ``kv_quantization="int8"`` prices the quantized cache layout (int8
    data + fp32 per-block scales) against the budget — the capacity
    lever that admits more resident requests per HBM byte."""
    if kv_quantization not in KV_QUANTIZATION_MODES:
        raise ValueError(
            f"serving.kv_quantization={kv_quantization!r} not in "
            f"{KV_QUANTIZATION_MODES}"
        )
    if config.is_hybrid:
        # what the hybrid family's serving path does not have yet, each
        # refused by its mechanism (ROADMAP.md, Queue 2)
        if kv_quantization != "none":
            raise ValueError(
                f"serving.kv_quantization={kv_quantization!r} is not "
                "implemented for layer_types models: the hybrid programs "
                "read and write the fp K/V layout only")
        if draft_config is not None:
            raise ValueError(
                "speculation='draft-model' is not implemented for "
                "layer_types models: a rejected draft needs the recurrent "
                "state rolled back, and the state cache keeps no snapshots")
        lin_heads = config.linear_num_value_heads
        if (tp > 1 and LINEAR_ATTENTION in config.layer_types
                and lin_heads % tp != 0):
            raise ValueError(
                f"linear_num_value_heads={lin_heads} not divisible by "
                f"tp={tp}: the recurrent state shards its head dim over tp")
        if tp > 1 and MAMBA in config.layer_types:
            raise ValueError(
                f"tp={tp} is not implemented for mamba layers: B and C are "
                "shared by every head, so they would be computed whole on "
                "every shard beside a head-sharded x, and the fused "
                "in-projection and its convolution are not split that way "
                "(ROADMAP.md, Queue 2)")
        if tp > 1 and (LATENT_ATTENTION in config.layer_types
                       or config.has_routed_experts):
            raise ValueError(
                f"tp={tp} is not implemented for latent_attention layers "
                "or routed experts: the one latent a token is shared by "
                "every head, so the plane has no head dim to shard, and "
                "the grouped expert products are not partitioned "
                "(ROADMAP.md, Queue 2)")
    if config.attention not in SERVABLE_ATTENTION:
        raise ValueError(
            f"serving requires attention in {SERVABLE_ATTENTION} "
            f"(attention={config.attention!r}: the paged KV-cache stores "
            "exact per-position K/V; simplified has none and ring/ulysses "
            "partition the sequence the cache owns)"
        )
    if config.is_moe:
        raise ValueError(
            "serving requires a dense FFN in the GPT block "
            "(model.num_experts == 0: models/transformer.py::_moe_ffn_dense "
            "runs every expert on every token and _moe_ffn_capacity drops "
            "tokens; neither is wired into the decode step).  The path "
            "that serves experts is the layer_types family's "
            "(model.n_routed_experts with layer_types: "
            "ops/routed_experts.py, no capacity and no dropped token)"
        )
    if config.forces_tp_ring:
        raise ValueError(
            f"serving requires model.tp_overlap 'auto' or 'off' (got "
            f"{config.tp_overlap!r}): the ring schedules gather the "
            "sequence dim, which decode steps of length 1 cannot shard"
        )
    if max_batch < 1:
        raise ValueError(f"serving.max_batch must be >= 1, got {max_batch}")
    if block_size < 1 or max_seq % block_size != 0:
        raise ValueError(
            f"serving.max_seq={max_seq} must be a positive multiple of "
            f"serving.block_size={block_size} (the cache is paged in "
            "whole blocks)"
        )
    if dp > 1 and max_batch % dp != 0:
        raise ValueError(
            f"serving.max_batch={max_batch} not divisible by dp={dp} "
            "(decode slots shard over the dp axis)"
        )
    if tp > 1 and config.kv_heads % tp != 0:
        raise ValueError(
            f"kv_heads={config.kv_heads} not divisible by tp={tp}: the "
            "KV-cache shards its head dim over tp, so GQA configs need "
            "kv_heads % tp == 0 (pick a smaller tp or more kv heads)"
        )
    if draft_config is not None:
        try:
            validate_serving(draft_config, max_batch, max_seq, block_size,
                             dp=dp, tp=tp)
        except ValueError as e:
            raise ValueError(
                f"speculative draft model is not servable on the same "
                f"ParallelismPlan (dp={dp}, tp={tp}): {e}"
            ) from e
    if hbm_budget_bytes is not None:
        per_device = kv_cache_bytes_per_device(
            config, max_batch, max_seq, dp=dp, tp=tp,
            kv_quantization=kv_quantization, block_size=block_size)
        draft_bytes = 0
        if draft_config is not None:
            # the draft plane is resident for the whole trace: weights
            # (sharded over tp like the target's) + its own paged
            # KV-cache plane, priced against the SAME budget
            from dlbb_tpu.models.transformer import num_parameters

            draft_bytes = (
                num_parameters(draft_config)
                * _DTYPE_BYTES.get(draft_config.dtype, 2)
                // (tp if tp > 1 else 1)
                + kv_cache_bytes_per_device(
                    draft_config, max_batch, max_seq, dp=dp, tp=tp)
            )
        if per_device + draft_bytes > hbm_budget_bytes:
            draft_note = (
                f" + speculative draft plane {draft_bytes / 2**30:.2f} "
                "GiB (weights + second KV-cache)" if draft_bytes else "")
            raise ValueError(
                f"serving KV-cache footprint {per_device / 2**30:.2f} GiB "
                f"per device (max_batch={max_batch} x max_seq={max_seq} "
                f"x {config.layers_of(FULL_ATTENTION)} layers"
                + (f" x {config.total_ut_steps} passes"
                   if config.total_ut_steps > 1 else "")
                + f" x kv_heads="
                f"{config.kv_heads} x head_dim={config.head_dim} x 2 "
                "(K+V), "
                + (f"int8 + fp32 scales per {block_size}-token block"
                   if kv_quantization == "int8"
                   else f"{_DTYPE_BYTES[config.dtype]} B [{config.dtype}]")
                + (f", + {state_cache_bytes(config, max_batch) / 2**30:.2f}"
                   " GiB of recurrent state and convolution inputs, + "
                   f"{latent_cache_bytes(config, max_batch, max_seq) / 2**30:.2f}"
                   " GiB of latents"
                   if config.is_hybrid else "")
                + f", sharded over dp={dp} x tp={tp})"
                f"{draft_note} "
                f"exceeds the HBM budget of "
                f"{hbm_budget_bytes / 2**30:.2f} GiB — shrink max_batch/"
                "max_seq or raise serving.hbm_budget_gb if the device "
                "really has the headroom"
            )


# Reference sizes (``models.py:252-271``).
MODEL_CONFIGS: dict[str, ModelConfig] = {
    "1B": ModelConfig(hidden_size=2048, num_layers=24, num_heads=16,
                      ffn_intermediate=8192),
    "7B": ModelConfig(hidden_size=4096, num_layers=32, num_heads=32,
                      ffn_intermediate=16384),
    "13B": ModelConfig(hidden_size=5120, num_layers=40, num_heads=40,
                       ffn_intermediate=20480),
}
