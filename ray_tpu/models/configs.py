"""Transformer configurations.

One `TransformerConfig` describes every decoder `models/transformer.py`
runs: softmax attention as MHA / GQA (optionally with QK-norm, over the
whole projection or a head at a time) or latent
(compressed key/value) attention with its own head widths; a dense SwiGLU
FFN, or routed experts (a softmax router with the load-balancing loss, or
a sigmoid router with a choice bias that is a buffer and a scaling
factor), optionally behind leading dense layers of their own width, with
shared experts every token passes, and with only a contiguous share of
the experts held here (one chip of an expert-parallel layer). Trained on
next-token cross-entropy over one causal stream, or (`block_length`) as a
block-diffusion model: a masked-token loss over a doubled stream under
the block-diffusion mask (models/diffusion.py). A hybrid
(`layer_pattern`) is a published sequence of single sublayers, each a
Mamba-2 mixer (`M`), an attention block (`*`) or an expert layer (`E`)
alone; its experts may live in a latent (`moe_latent`) and be plain
`relu(x)^2` MLPs with no gate. A decoder-hybrid-decoder (SambaY; the
lower-case kinds of `layer_pattern`) is a sequence of layers each a
Mamba-1 mixer, differential attention (windowed, full or cross) or a gated
memory unit FOLLOWED by a dense MLP, whose later layers read one earlier
mixer's scan output and one earlier attention layer's keys and values. A
linear-attention hybrid (the kinds `k`, `K`, `l`, `L`) is a sequence of
layers each Kimi Delta Attention (ops/kda.py) or softmax attention
(latent where `kv_lora_rank` says so, its queries projected directly
where `q_lora_rank` is 0), FOLLOWED by a dense MLP (lower case) or by
experts (upper case), whose router may limit a token's choice to some
groups of experts (`moe_groups`, `moe_topk_groups`). `W` is attention
under a causal window of `attn_window` keys FOLLOWED by experts, beside
`L`, full attention then experts: a model that mixes the two
(`layer_types`), with its RoPE scaled by YaRN (`rope_yarn_factor`) on the
full layers only. `d` is a Gated DeltaNet mixer (ops/kda.py: the delta
rule behind one decay a head) and `a` softmax attention, each FOLLOWED by
a dense MLP and each sublayer under OLMo-2/3's reordered norm,
`x + norm(f(x))` with no norm before the sublayer. A looped stack
(`loops` > 1; Ouro's `total_ut_steps`) passes the stream that many times
through the SAME layers, the final norm closing every pass, with one head
and (`exit_gate`) one exit gate after every pass and the loss an
expectation over the pass a token leaves at (`Transformer.loss`); its
layers stand under a sandwich norm, `x + norm(f(norm(x)))`
(`norm_placement` "both": where the homogeneous stack's norms sit). A
residual path of several streams (`residual_streams` = n > 1;
manifold-constrained hyper-connections, arXiv 2512.24880 after 2409.19606)
carries n copies of the stream from the embedding to the final norm: every
sublayer reads one mix of them and writes back through two more, the three
maps computed from the stream itself, the n x n one made doubly stochastic
by `hc_sinkhorn_iters` Sinkhorn rounds (ops/mhc.py). YaRN on latent
attention is DeepSeek-V3's reading (`rope_yarn_mscale_all_dim`): a factor
on the WHOLE softmax scale, all of a head's columns, and none on the
rotary tables, which touch 64 of 192 of them. `n` is a Mamba-2 mixer
FOLLOWED by a dense MLP (Granite 4.0-H's layer beside `l` without RoPE),
under four muP scalars (`embed_scale`, `residual_scale`, `attn_scale`,
`logit_divisor`).

Packed documents (`segment_ids` [B, T] beside the tokens; no field here)
are an input of the step: attention sees a query's own document, a
Mamba-2 mixer's convolution and scan start anew at a document's first
position, the loss drops the labels that cross a boundary; every other
kind that mixes positions refuses them by name (the comment above
`__post_init__`, `Transformer.untaught_by_packing`).

Named scales: GPT-2 125M (BASELINE.json's data-parallel config),
Llama-2 7B (its FSDP config) and OLMoE-1B-7B (the sparse-expert decoder of
BENCHMARK.json's `train_olmoe_d1`), each at its published depth. The
benchmark's own configurations are the published `config.json` files
under benchmark/configs/, mapped onto TransformerConfig by its jobs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None      # None -> = n_heads (MHA)
    d_ff: Optional[int] = None            # None -> 4*d_model (8/3 for swiglu
                                          # users should set explicitly)
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # RMSNorm with a learned gain over the whole q and the whole k
    # projection (all heads together), before the split into heads and
    # before RoPE: OLMoE's / OLMo-2's QK-norm. With qk_norm_per_head each
    # query and each key head is normed over its own head_dim with one
    # gain a side that its heads share (Qwen3's `q_norm` / `k_norm`);
    # latent attention's QK-norm is always that one.
    qk_norm: bool = False
    qk_norm_per_head: bool = False
    # "auto" | "dense" | "flash" | "ring" | "ulysses". auto = pallas
    # flash kernel on TPU when the seq axis is unsharded (ring when it
    # is), dense elsewhere; dense = materialized-scores attention with
    # GSPMD-managed layout; ring/ulysses = explicit shard_map SP.
    attention_impl: str = "dense"
    # dtypes: params kept in param_dtype, compute runs in dtype (bf16 on
    # TPU keeps the MXU fed; accumulation is f32 via preferred_element_type)
    dtype: Any = "bfloat16"
    param_dtype: Any = "float32"
    remat: bool = False                   # jax.checkpoint each layer
    # what a checkpointed layer keeps for its backward pass besides the
    # scan's carry (Transformer._remat). "attention": the flash forward
    # kernel's output and logsumexp, so the kernel is not run again, and
    # an expert layer's routing (the router's f32 logits, the chosen
    # experts, the sort's permutations and counts), so neither are the
    # router's product, the top-k and the sorts (a layer without the
    # flash kernel and without a router names nothing and is "full"'s
    # program); "full": nothing, the whole layer is recomputed (min
    # memory, +1 fwd pass); "dots": "attention" and every matmul's output
    # (near-zero recompute FLOPs, fastest when activations fit). Any
    # other value raises.
    remat_policy: str = "attention"
    # chunk the lm-head + cross-entropy over the sequence axis so the
    # [B,T,vocab] f32 logits (+grad) never materialize at once; 0 = off.
    loss_chunk: int = 256
    # unroll factor for the layer scan. True unrolls fully: XLA sees
    # static weight slices (no dynamic-slice bookkeeping per layer) and
    # can fuse across layer boundaries; costs compile time, wins step
    # time for shallow stacks. Keep 1 (rolled) for deep models and for
    # the pipeline axis.
    scan_unroll: int = 1
    # Mixture-of-Experts FFN (ops/moe.py): 0 = dense FFN; >0 replaces
    # every layer's FFN with a softmax top-k router over moe_experts
    # gated experts of width d_ff, dropless (sorted dispatch) unless the
    # mesh has an "expert" axis above 1, over which the expert weights
    # shard. The router aux (load-balancing) loss is added to the LM loss
    # with moe_aux_coeff (`router_aux_loss_coef`).
    moe_experts: int = 0
    moe_top_k: int = 2
    # renormalise a token's top-k router weights to sum to 1
    # (`norm_topk_prob`; OLMoE publishes false)
    moe_norm_topk: bool = True
    moe_aux_coeff: float = 0.01
    # "softmax": probabilities over the experts, top-k of them, the aux
    # loss above. "sigmoid": independent scores `sigmoid(W_r x)`; the
    # choice is the top-k of score + `router_bias` (a per-expert buffer
    # that no gradient and no optimizer update reaches:
    # `Transformer.frozen`), the weights are the chosen scores themselves;
    # no aux loss (`topk_method: noaux_tc`).
    moe_scoring: str = "softmax"
    # the weighted sum of a token's experts times this
    # (`routed_scaling_factor`)
    moe_routed_scale: float = 1.0
    # shared experts: one gated FFN of width moe_shared_experts * d_ff
    # that every token passes, added to the routed sum (`n_shared_experts`)
    moe_shared_experts: int = 0
    # the first moe_dense_layers layers keep a dense FFN of width
    # moe_dense_ff (`first_k_dense_replace`, `intermediate_size`); the
    # n_layers - moe_dense_layers after them are expert layers
    moe_dense_layers: int = 0
    moe_dense_ff: Optional[int] = None
    # this program's share of an expert-parallel layer: experts
    # moe_expert_offset .. + moe_experts_held are resident (0 held = all
    # moe_experts). The router keeps its moe_experts outputs and its k a
    # token; token-slots routed to an expert held elsewhere run through
    # nothing here and are counted (ops/moe.py).
    moe_experts_held: int = 0
    moe_expert_offset: int = 0
    # Latent attention (kv_lora_rank > 0; DeepSeek-V2's MLA): queries and
    # keys/values are up-projected from RMS-normed low-rank latents; a
    # head is qk_nope_head_dim columns without position plus
    # qk_rope_head_dim rotary columns, the rotary key head shared by all
    # heads; values are v_head_dim wide. n_kv_heads is n_heads.
    # q_lora_rank 0: no query latent, the queries are one projection of
    # the stream. With qk_norm each query head and each key head
    # ([k_nope | k_rope]) is RMS-normed over its own head_dim with one
    # gain a side, before RoPE.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # width of an attention head where it is not d_model / n_heads (a
    # chip's share of the heads, or a model that publishes `head_dim`)
    attn_head_dim: int = 0
    # rotary embedding on q and k; off where other layers carry position
    rope: bool = True
    # A hybrid's layers, one character a layer (`hybrid_override_pattern`):
    # `M` a Mamba-2 mixer, `*` attention, `E` an expert layer, each alone
    # as `x + f(norm(x))`. Empty: every layer is attention then an FFN.
    # len(layer_pattern) == n_layers; `pattern_runs` factors it into the
    # runs `Transformer._stack` scans.
    layer_pattern: str = ""
    # the Mamba-2 mixer (ops/ssm.py): ssm_heads heads of ssm_head_dim,
    # ssm_groups groups of B/C of state ssm_state, a depthwise causal
    # convolution of ssm_conv_kernel taps, the scan in chunks of ssm_chunk
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # experts that live in a latent of this width: one down-projection of
    # the stream before the dispatch, one up-projection after the combine;
    # the router and the shared expert read the stream itself (0: none)
    moe_latent: int = 0
    # the experts' and the shared expert's activation, and whether a gate
    # multiplies it: "silu" gated is `silu(x Wg) * (x Wu)`, "relu2"
    # ungated is `relu(x W1)^2`
    moe_act: str = "silu"
    moe_gated: bool = True
    # the shared expert's own width (0: moe_shared_experts * d_ff)
    moe_shared_ff: int = 0
    # The lower-case kinds of `layer_pattern`, each sublayer FOLLOWED by a
    # dense gated MLP of width d_ff in the same layer (SambaY, arXiv
    # 2507.06607): `m` a Mamba-1 mixer, `s` the Mamba-1 mixer whose scan
    # output (before its gate) is the memory of every `g` after it, `w`
    # attention under a causal window of attn_window keys, `f` full causal
    # attention whose keys and values are those of every `c` after it, `g`
    # a gated memory unit `(m * silu(h W1)) W2`, `c` cross-attention with a
    # query projection only. The Mamba-1 mixer (ops/ssm.py): ssm_d_inner
    # channels each with its own state of ssm_state and its own decay,
    # the step from a low-rank projection of ssm_dt_rank, ssm_conv_kernel
    # taps, the scan in chunks of ssm_chunk with the state carried.
    ssm_d_inner: int = 0
    ssm_dt_rank: int = 0
    attn_window: int = 0
    # differential attention (arXiv 2410.05258): heads in pairs, two
    # softmax maps a pair and `(A1 - lambda A2) V` with V 2 x head_dim
    # wide and shared by two key pairs, lambda learned (four vectors of
    # head_dim a layer around lambda_init = 0.8 - 0.6 exp(-0.3 l), l the
    # PUBLISHED index of the layer: layer_index_offset + its place here),
    # an RMS norm over the pair's output, times (1 - lambda_init)
    diff_attention: bool = False
    layer_index_offset: int = 0
    # a bias on attention's projections (q, k, v and the output)
    attn_bias: bool = False
    # "rms": RMSNorm with a gain; "layernorm": LayerNorm with gain and
    # bias (`<name>_bias` beside every norm's gain, the final one too)
    norm: str = "rms"
    # Four more kinds of `layer_pattern`, each a sublayer FOLLOWED by a
    # dense gated MLP of width moe_dense_ff or d_ff (lower case) or by the
    # experts (upper case: the `moe_*` sizes, experts of width d_ff): `k`
    # and `K` Kimi Delta Attention (ops/kda.py: kda_heads heads of
    # kda_head_dim, keys and values alike, three depthwise causal
    # convolutions of kda_conv_kernel taps, a decay per channel bounded
    # below by kda_gate_lower a step, the delta rule in chunks of
    # kda_chunk); `l` and `L` softmax attention as the other sizes say
    # (latent with kv_lora_rank). A leading dense layer is a lower-case
    # character, not `moe_dense_layers`.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4
    kda_chunk: int = 64
    kda_gate_lower: float = -5.0
    # group-limited routing (`n_group`, `topk_group`; the sigmoid router):
    # the experts in moe_groups equal groups, a group ranked by the sum
    # of its two largest scores (with the choice bias), a token's top-k
    # taken among the experts of its moe_topk_groups best groups. 1: none
    moe_groups: int = 1
    moe_topk_groups: int = 1
    # Block diffusion (arXiv 2503.09573, 2510.06303; models/diffusion.py).
    # 0: next-token training under a causal mask. Above 0 the sequence is
    # blocks of block_length tokens, attention is causal by block (a block
    # sees itself in both directions, `ops/attention.block_visible`), and
    # `Transformer.loss` is the masked-token loss: each block noised at
    # its own time t ~ U(diffusion_t_min, 1) (a token replaced by
    # mask_token_id with probability t), the stream the noised copy and
    # the clean one behind it, the cross-entropy over the masked
    # positions weighted by 1/t, no shift by one. mask_token_id -1: the
    # vocabulary's last row.
    block_length: int = 0
    mask_token_id: int = -1
    diffusion_t_min: float = 1e-3
    # One more kind of `layer_pattern`: `W`, attention under a causal
    # window of attn_window keys (the query's own among them) FOLLOWED by
    # the experts, as `L` is full attention followed by them.
    # YaRN (arXiv 2309.00071; `rope_parameters.rope_type: yarn`), 0: none.
    # The per-pair frequencies theta^(-p/half) are kept where a pair turns
    # more than rope_yarn_beta_fast times over rope_yarn_original_len
    # positions, divided by rope_yarn_factor where it turns fewer than
    # rope_yarn_beta_slow times, blended linearly between (the bounds
    # floored and ceiled: `truncate`), and cos and sin are multiplied by
    # rope_yarn_attention_factor (0: 0.1 ln(factor) + 1), on q and on k.
    # The window kinds (`w`, `W`) keep the plain table: a model scales the
    # layers that see the whole context and not those that see a window.
    rope_yarn_factor: float = 0.0
    rope_yarn_original_len: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_yarn_attention_factor: float = 0.0
    # Two more kinds of `layer_pattern`, each a sublayer FOLLOWED by a
    # dense gated MLP (width moe_dense_ff or d_ff) and each sublayer under
    # the reordered norm of OLMo-2/3, `x + norm(f(x))`: no norm before a
    # sublayer, one on its output (the leaves `<name>_post_norm` where the
    # other kinds have `<name>_norm`; the kind says where the norm sits,
    # no other field does). `d` a Gated DeltaNet mixer (arXiv 2412.06464;
    # ops/kda.py: gdn_heads heads with keys of gdn_key_dim and values of
    # gdn_value_dim, one depthwise causal convolution of gdn_conv_kernel
    # taps over q, k and v, ONE unbounded decay a head and step, beta in
    # (0, 2) where gdn_neg_eigval (`allow_neg_eigval`) else (0, 1), a gate
    # a channel on the normed output, the delta rule in chunks of
    # gdn_chunk); `a` softmax attention as the other sizes say.
    gdn_heads: int = 0
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv_kernel: int = 4
    gdn_neg_eigval: bool = False
    gdn_chunk: int = 64
    # Where the homogeneous stack's norms sit (a `layer_pattern`'s kinds
    # say it themselves): "pre" `x + f(norm(x))`, the leaves
    # `<name>_norm`; "post" `x + norm(f(x))`, the leaves
    # `<name>_post_norm`; "both" the sandwich norm `x + norm(f(norm(x)))`,
    # both leaves, four gains a layer.
    norm_placement: str = "pre"
    # A looped stack (arXiv 2510.25741; `total_ut_steps`): the stream
    # passes `loops` times through the same layers, the final norm closes
    # every pass and the next pass reads the normed stream; the position
    # ids are the same in every pass. 1: the layers run once. With
    # `exit_gate` a linear map of each pass's normed hidden state to one
    # scalar (a gain of d_model and a bias: 2,049 parameters at 2,048)
    # says with what probability a token leaves after that pass, and
    # `Transformer.loss` is the expected next-token loss under that exit
    # distribution less `exit_entropy_coeff` times its entropy.
    loops: int = 1
    exit_gate: bool = False
    exit_entropy_coeff: float = 0.05
    # A residual path of several streams (`hc_mult`; ops/mhc.py). 1: the
    # one stream `x + f(norm(x))` of every other model, and nothing of
    # this is traced. n > 1: the embedding is repeated into n streams
    # X [n, d] a token, and a sublayer with its own phi [n*d, n*n + 2n],
    # b [n*n + 2n] and alpha [3] forms, in float32, u = vec(X) /
    # sqrt(mean(vec(X)^2) + norm_eps) (one statistic over all n*d values,
    # no gain), m = u phi, H_pre = sigmoid(alpha_0 m[:n] + b[:n]),
    # H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n]) and H_res from
    # A = clamp(alpha_2 mat(m[2n:]) + b[2n:], -hc_res_clamp, hc_res_clamp)
    # (row-major) by M = exp(A) and hc_sinkhorn_iters times M = M /
    # (rowsum + hc_eps), M = M / (colsum + hc_eps); it reads
    # h = sum_i H_pre[i] X[i] under its own norm and leaves
    # X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f(norm(h)). The streams
    # are summed before the final norm. The homogeneous stack's (with its
    # leading dense run) under dense or flash attention; a layer_pattern,
    # `loops` above 1, `block_length`, ring, ulysses and `pipeline_loss`
    # refuse it by name until someone needs them.
    residual_streams: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # YaRN as DeepSeek-V3's family reads `mscale_all_dim`: the softmax
    # scale head_dim^-1/2 times (0.1 * this * ln(rope_yarn_factor) + 1)^2,
    # over ALL of a head's columns (0: the scale as it is). With `mscale`
    # = `mscale_all_dim` the rotary tables stay unscaled:
    # rope_yarn_attention_factor 1.0.
    rope_yarn_mscale_all_dim: float = 0.0
    # One more lower-case kind of `layer_pattern`: `n`, a Mamba-2 mixer
    # (the `ssm_*` sizes of `M`) FOLLOWED by a dense gated MLP of width
    # moe_dense_ff or d_ff, each under its own pre-norm (Granite 4.0-H's
    # layer; its attention layer is `l` with `rope` off). And the four muP
    # scalars such a model publishes, each 1 (0 for the softmax's) where a
    # model has none and then not traced: the embedding's output times
    # embed_scale (`embedding_multiplier`), every sublayer's output times
    # residual_scale before it joins the stream (`residual_multiplier`),
    # attn_scale as the softmax scale in place of head_dim^-1/2
    # (`attention_multiplier`), the logits divided by logit_divisor
    # (`logits_scaling`).
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: float = 0.0
    logit_divisor: float = 1.0
    # Packed documents are no field of this configuration: `segment_ids`
    # [B, T] int32 is an input of `Transformer.apply` / `hidden` / `loss`
    # (a batch's "segment_ids" beside its "tokens"), one id a position,
    # each document one run of equal ids. A boundary does this in each
    # sublayer that mixes positions: softmax attention (dense and flash;
    # causal or under a window) sees the keys of the query's document
    # only; a Mamba-2 mixer's (`M`, `n`) convolution reads zero for a tap
    # in another document and its scan starts every document from a zero
    # state; the loss leaves out a label that lies in another document
    # than its position. A document's outputs are those of the document
    # run alone. `Transformer.untaught_by_packing` names what refuses
    # `segment_ids`: Mamba-1 (`m`, `s`), Kimi Delta Attention (`k`, `K`),
    # Gated DeltaNet (`d`), differential and cross attention, the
    # block-diffusion mask, ring and ulysses attention, a looped stack, a
    # residual path of several streams, `pipeline_loss`.

    def __post_init__(self):
        if self.norm not in ("rms", "layernorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.norm == "layernorm" and not self.layer_pattern:
            raise ValueError("LayerNorm's biases are a layer_pattern's "
                             "sublayers': the homogeneous layer has none")
        if self.layer_pattern:
            unknown = set(self.layer_pattern) - set("M*" + EXPERT_KINDS
                                                    + FFN_KINDS)
            if unknown or len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: {self.n_layers} "
                    f"characters of M, *, {', '.join(EXPERT_KINDS)}, "
                    f"{', '.join(FFN_KINDS)} (got {sorted(unknown)})")
            self._check_shared_tensors()
            if set("Mn") & set(self.layer_pattern) and (
                    not self.ssm_heads or self.ssm_heads % self.ssm_groups):
                raise ValueError("a mixer needs ssm_heads, a multiple of "
                                 "ssm_groups")
            if bool(set(EXPERT_KINDS) & set(self.layer_pattern)) \
                    != bool(self.moe_experts) or self.moe_dense_layers \
                    or (self.kv_lora_rank
                        and set("*wfcWa") & set(self.layer_pattern)):
                raise ValueError("a layer_pattern has expert layers where "
                                 "it says E, K, L or W, no leading dense "
                                 "run (moe_dense_layers) and latent "
                                 "attention in the kinds l and L only")
            if set("kK") & set(self.layer_pattern) and not self.kda_heads:
                raise ValueError("Kimi Delta Attention (k, K) needs "
                                 "kda_heads")
            if "d" in self.layer_pattern and not self.gdn_heads:
                raise ValueError("a Gated DeltaNet mixer (d) needs "
                                 "gdn_heads")
        if self.moe_act not in ("silu", "relu2"):
            raise ValueError(f"unknown moe_act {self.moe_act!r}")
        if self.kv_lora_rank:
            if not (self.qk_rope_head_dim and self.v_head_dim) \
                    or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs an even qk_rope_head_dim and "
                    "v_head_dim")
            if self.kv_heads != self.n_heads:
                raise ValueError("latent attention has one key/value head "
                                 "per query head")
        elif not self.attn_head_dim and self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} % n_heads "
                             f"{self.n_heads} != 0")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_scoring {self.moe_scoring!r}")
        if self.moe_groups > 1 and (
                self.moe_scoring != "sigmoid"
                or self.moe_experts % self.moe_groups
                or not 1 <= self.moe_topk_groups <= self.moe_groups
                or self.moe_experts // self.moe_groups < 2
                or self.moe_topk_groups * self.moe_experts
                // self.moe_groups < self.moe_top_k):
            raise ValueError(
                "group-limited routing is the sigmoid router's: moe_experts "
                "in moe_groups equal groups of two experts or more, "
                "moe_topk_groups of them holding moe_top_k experts")
        if self.moe_experts:
            first, held = self.moe_expert_offset, self.held_experts
            if first < 0 or first + held > self.moe_experts:
                raise ValueError(
                    f"experts {first}..{first + held} of {self.moe_experts}")
            if held < self.moe_experts and self.moe_scoring == "softmax" \
                    and self.moe_aux_coeff:
                raise ValueError("the softmax router's aux loss needs every "
                                 "expert's count, the other chips' too: a "
                                 "held share runs it with moe_aux_coeff 0, "
                                 "or the sigmoid router")
            if not 0 <= self.moe_dense_layers < self.n_layers:
                raise ValueError("moe_dense_layers must leave an expert "
                                 "layer")
        elif self.moe_dense_layers or self.moe_shared_experts \
                or self.moe_experts_held:
            raise ValueError("moe_* sizes without moe_experts")
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head says how qk_norm norms")
        if self.block_length < 0 or not 0 < self.diffusion_t_min < 1 \
                or not -1 <= self.mask_token_id < self.vocab_size:
            raise ValueError(
                "block diffusion: block_length 0 or above, diffusion_t_min "
                "inside (0, 1), mask_token_id a row of the vocabulary")
        if self.rope_yarn_factor and (
                self.rope_yarn_factor < 1 or self.rope_yarn_original_len < 1
                or not self.rope):
            raise ValueError(
                "YaRN: rope_yarn_factor 1 or above (0: none), with "
                "rope_yarn_original_len, on a model with RoPE")
        if self.norm_placement not in ("pre", "post", "both") or (
                self.norm_placement != "pre" and self.layer_pattern):
            raise ValueError(
                f"norm_placement {self.norm_placement!r}: pre, post or "
                f"both, on the homogeneous stack (a layer_pattern's kinds "
                f"say where their norms sit)")
        if self.loops < 1 or (self.exit_gate and self.loops == 1):
            raise ValueError("loops is 1 or above, and an exit gate is a "
                             "looped stack's (loops above 1)")
        if self.loops > 1 and (
                self.layer_pattern or self.moe_experts or self.block_length
                or self.attention_impl in ("ring", "ulysses")):
            raise ValueError(
                "a looped stack (loops above 1) is the homogeneous dense "
                "layer under dense or flash attention: no layer_pattern, "
                "no experts, no block diffusion, no ring or ulysses")
        if self.residual_streams < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError("residual_streams is 1 or above, "
                             "hc_sinkhorn_iters 0 or above")
        if self.residual_streams > 1:
            for what, has in (
                    ("a layer_pattern", self.layer_pattern),
                    ("a looped stack (loops above 1)", self.loops > 1),
                    ("block diffusion (block_length)", self.block_length),
                    ("ring or ulysses attention",
                     self.attention_impl in ("ring", "ulysses"))):
                if has:
                    raise ValueError(
                        f"a residual path of several streams "
                        f"(residual_streams above 1) does not run under "
                        f"{what} yet")
        if self.rope_yarn_mscale_all_dim and not self.rope_yarn_factor:
            raise ValueError("rope_yarn_mscale_all_dim scales the softmax "
                             "under YaRN: it needs rope_yarn_factor")
        if self.block_length and (
                self.attention_impl in ("ring", "ulysses")
                or self.layer_pattern or self.attn_window):
            raise ValueError(
                "the block-diffusion mask runs on dense and flash "
                "attention in the homogeneous layer: not under ring or "
                "ulysses, a window or a layer_pattern")

    def _check_shared_tensors(self):
        """The lower-case kinds' sizes, and every reader of a tensor that
        crosses layers after the one layer that makes it."""
        pattern = self.layer_pattern
        if set("ms") & set(pattern) and not (
                self.ssm_d_inner and self.ssm_dt_rank):
            raise ValueError("a Mamba-1 mixer (m, s) needs ssm_d_inner and "
                             "ssm_dt_rank")
        if set("wW") & set(pattern) and not self.attn_window:
            raise ValueError("window attention (w, W) needs attn_window")
        if self.diff_attention and (
                self.n_heads % 4 or self.kv_heads * 2 != self.n_heads
                or self.kv_lora_rank or self.qk_norm):
            raise ValueError(
                "differential attention pairs the heads: n_heads a "
                "multiple of 4, half as many key heads, no latent and no "
                "QK-norm")
        for reader, maker, what in (
                ("g", "s", "a gated memory unit (g) reads the scan output "
                           "of the mixer s"),
                ("c", "f", "cross-attention (c) reads the keys and values "
                           "of the full attention layer f")):
            if pattern.count(maker) > 1:
                raise ValueError(f"layer_pattern {pattern!r}: {what}, and "
                                 f"one layer makes it")
            if reader in pattern and not 0 <= pattern.find(maker) \
                    < pattern.find(reader):
                raise ValueError(
                    f"layer_pattern {pattern!r}: {what}, and there is no "
                    f"{maker} before the first {reader}")
            if reader in pattern and not self.ssm_d_inner:
                raise ValueError(f"{what}: ssm_d_inner is its width")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        """Width of a query / key head (what the attention kernel tiles);
        a value head is `v_dim` wide."""
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def v_dim(self) -> int:
        return self.v_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def rope_dim(self) -> int:
        """Columns of a head that carry position."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def yarn_attention_factor(self) -> float:
        """What YaRN's cos and sin are multiplied by."""
        return self.rope_yarn_attention_factor or (
            0.1 * math.log(self.rope_yarn_factor) + 1.0)

    @property
    def softmax_scale(self) -> float:
        """What attention's scores are multiplied by: head_dim^-1/2 (or
        `attn_scale` where the model publishes one), under
        `rope_yarn_mscale_all_dim` times YaRN's factor squared."""
        scale = self.attn_scale or self.head_dim ** -0.5
        if self.rope_yarn_mscale_all_dim:
            scale *= (0.1 * self.rope_yarn_mscale_all_dim
                      * math.log(self.rope_yarn_factor) + 1.0) ** 2
        return scale

    @property
    def hc_maps(self) -> int:
        """Numbers a sublayer's three maps hold a token: n, n and n x n."""
        n = self.residual_streams
        return n * n + 2 * n

    @property
    def _hc_params(self) -> int:
        """A sublayer's phi, b and its three alphas (0 on one stream)."""
        if self.residual_streams == 1:
            return 0
        return (self.residual_streams * self.d_model + 1) * self.hc_maps + 3

    @property
    def mask_token(self) -> int:
        """The id a noised token is replaced by."""
        return self.mask_token_id % self.vocab_size

    @property
    def held_experts(self) -> int:
        return self.moe_experts_held or self.moe_experts

    @property
    def ssm_inner(self) -> int:
        """Width of the mixer's x, z and output: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    def lambda_init(self, layer: int) -> float:
        """Differential attention's lambda_init of this program's layer
        `layer`, by its published index."""
        return 0.8 - 0.6 * math.exp(
            -0.3 * (self.layer_index_offset + layer))

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: [x | B | C]."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def shared_ff(self) -> int:
        return self.moe_shared_ff or self.moe_shared_experts * self.ff_dim

    @property
    def pattern_runs(self):
        return pattern_runs(self.layer_pattern)

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)

    @property
    def num_params(self) -> int:
        d, l, f, v = self.d_model, self.n_layers, self.ff_dim, self.vocab_size
        hd, nh, nkv = self.head_dim, self.n_heads, self.kv_heads
        if self.kv_lora_rank:   # down, latent norm, up for q and for kv
            qr, kvr = self.q_lora_rank, self.kv_lora_rank
            attn = ((d * qr + qr + qr * nh * hd if qr else d * nh * hd)
                    + d * (kvr + self.rope_dim) + kvr
                    + kvr * nh * (self.qk_nope_head_dim + self.v_dim)
                    + nh * self.v_dim * d)
        else:
            attn = d * nh * hd + 2 * d * nkv * hd + nh * hd * d
        if self.qk_norm:   # a head at a time: one gain a side
            attn += 2 * hd if self.kv_lora_rank or self.qk_norm_per_head \
                else nh * hd + nkv * hd
        if self.layer_pattern:
            return self._pattern_params(attn)
        # a sublayer's norms and, on several streams, its maps' leaves
        norms = (4 if self.norm_placement == "both" else 2) * d \
            + 2 * self._hc_params
        mlp = 3 * d * f
        dense = 0
        if self.moe_experts:
            # the experts held here, the shared ones and the router (its
            # choice bias is a buffer, not a parameter)
            mlp = (self.held_experts + self.moe_shared_experts) * mlp \
                + d * self.moe_experts
            dense = self.moe_dense_layers * (
                attn + norms + 3 * d * (self.moe_dense_ff or f))
            l -= self.moe_dense_layers
        head = 0 if self.tie_embeddings else d * v
        gate = d + 1 if self.exit_gate else 0
        return v * d + dense + l * (attn + mlp + norms) + d + gate + head

    def _pattern_params(self, attn: int) -> int:
        """num_params of a hybrid: each sublayer with its one norm."""
        d, v = self.d_model, self.vocab_size
        mixer = self._mixer_params
        mats = 3 if self.moe_gated else 2
        width = self.moe_latent or d
        expert = (d * self.moe_experts                     # router
                  + (2 * d * self.moe_latent if self.moe_latent else 0)
                  + self.held_experts * mats * width * self.ff_dim
                  + (mats * d * self.shared_ff
                     if self.moe_shared_experts else 0))
        each = {"M": mixer, "*": attn, "E": expert}
        if set(FFN_KINDS) & set(self.layer_pattern):
            each.update(self._ffn_kind_params(attn))
        norm = d * self._norm_leaves
        # a sublayer, its second norm and the experts
        each.update(K=self._kda_params + norm + expert,
                    L=attn + norm + expert, W=attn + norm + expert)
        layers = sum(each[c] + norm for c in self.layer_pattern)
        head = 0 if self.tie_embeddings else d * v
        return v * d + layers + norm + head

    @property
    def _mixer_params(self) -> int:
        """A Mamba-2 mixer without its norm."""
        d, inner, conv = self.d_model, self.ssm_inner, self.ssm_conv_dim
        return (d * (inner + conv + self.ssm_heads)        # W_in
                + conv * self.ssm_conv_kernel + conv       # conv, its bias
                + 3 * self.ssm_heads                       # dt_bias, A_log, D
                + inner + inner * d)                       # gated norm, W_out

    @property
    def _kda_params(self) -> int:
        """A Kimi Delta Attention sublayer without its norm: q, k, v and
        their convolutions, the decay's projection with A and its bias,
        beta's and the output gate's, the head norm's gain, W_o."""
        d, h, hd = self.d_model, self.kda_heads, self.kda_head_dim
        return (3 * d * h * hd + 3 * h * hd * self.kda_conv_kernel
                + d * h * hd + h + h * hd + 2 * d * h + hd + h * hd * d)

    @property
    def _gdn_params(self) -> int:
        """A Gated DeltaNet mixer without its norm: q, k, v and their
        convolution, the decay's and beta's projections with A and dt, the
        output gate's, the head norm's gain, W_o."""
        d, h = self.d_model, self.gdn_heads
        dk, dv = self.gdn_key_dim, self.gdn_value_dim
        return (d * h * (2 * dk + dv) + h * (2 * dk + dv)
                * self.gdn_conv_kernel + 2 * d * h + 2 * h + d * h * dv
                + dv + h * dv * d)

    @property
    def _norm_leaves(self) -> int:
        """Leaves of width d_model a norm has: a gain, and a bias."""
        return 2 if self.norm == "layernorm" else 1

    def _ffn_kind_params(self, attn: int):
        """Parameters of each lower-case kind without its first norm
        (`_pattern_params` adds one a layer): the sublayer, the second
        norm and the MLP."""
        d, inner, n = self.d_model, self.ssm_d_inner, self.ssm_state
        hd = self.head_dim
        mixer = (d * 2 * inner                               # W_in
                 + inner * self.ssm_conv_kernel + inner      # conv, bias
                 + inner * (self.ssm_dt_rank + 2 * n)        # W_x
                 + self.ssm_dt_rank * inner + inner          # W_dt, b_dt
                 + inner * n + inner + inner * d)            # A_log, D, W_out
        bias = (self.n_heads + 2 * self.kv_heads) * hd + d \
            if self.attn_bias else 0
        # four lambda vectors and the pair norm's gain (lambda_init is a
        # buffer)
        diff = 4 * hd + 2 * hd if self.diff_attention else 0
        kv = 2 * d * self.kv_heads * hd + (
            2 * self.kv_heads * hd if self.attn_bias else 0)
        rest = self._norm_leaves * d \
            + 3 * d * (self.moe_dense_ff or self.ff_dim)
        each = {"m": mixer, "s": mixer, "w": attn + bias + diff,
                "f": attn + bias + diff, "g": 2 * d * inner,
                "c": attn + bias + diff - kv, "k": self._kda_params,
                "l": attn, "d": self._gdn_params, "a": attn,
                "n": self._mixer_params}
        return {kind: count + rest for kind, count in each.items()}


# the kinds of `layer_pattern` that are followed by a dense MLP in the same
# layer (TransformerConfig: `m`, `s`, `w`, `f`, `g`, `c`, `k`, `l`, `d`,
# `a`, `n`), those that are or end in an expert layer (`E` alone, `K`,
# `L`, `W`), and those whose sublayers stand under the reordered norm
FFN_KINDS = "msfwgckldan"
EXPERT_KINDS = "EKLW"
REORDERED_KINDS = "da"


def pattern_runs(pattern: str):
    """A layer pattern as the runs a scan takes: [(block, repeats), ...]
    with the blocks' repeats laid end to end giving the pattern back. At
    each position the block whose repeats cover the most layers (the
    shorter block on a tie); layers that repeat nothing join the block
    before them if that one runs once. `MEMEMEMEM*E` is 4 x `ME` and one
    `M*E`; the published 88 layers are eight runs."""
    runs = []
    i = 0
    while i < len(pattern):
        best = (1, 1)
        for p in range(1, (len(pattern) - i) // 2 + 1):
            block, r = pattern[i:i + p], 1
            while pattern[i + r * p:i + (r + 1) * p] == block:
                r += 1
            if r > 1 and p * r > best[0] * best[1]:
                best = (p, r)
        p, r = best
        if r == 1 and runs and runs[-1][1] == 1:
            runs[-1] = (runs[-1][0] + pattern[i], 1)
        else:
            runs.append((pattern[i:i + p], r))
        i += p * r
    return runs


TINY = TransformerConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128)

# GPT-2 small scale (125M): 12L/768d, 50k vocab, learned-pos in the
# original — here RoPE (TPU-first redesign, not a port). Head shape is
# 6 heads x 128 head_dim rather than the original 12 x 64: identical
# parameter count and FLOPs (d_total = 768 either way), but head_dim
# 128 fills the MXU's 128-lane contraction on the QK^T/PV matmuls where
# 64 leaves half the array idle, and 6 heads halve the softmax VPU work
# — measured +30% train-step throughput on v5e-class chips.
GPT2_125M = TransformerConfig(
    vocab_size=50304,  # 50257 padded to a multiple of 128 for the MXU
    d_model=768, n_layers=12, n_heads=6, d_ff=3072, max_seq_len=1024,
    tie_embeddings=True)

LLAMA2_7B = TransformerConfig(
    vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
    n_kv_heads=32, d_ff=11008, max_seq_len=4096, norm_eps=1e-5,
    remat=True)

# OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct config.json; arXiv
# 2409.02060): 16 layers of MHA with QK-norm and 64 SiLU-gated experts of
# width 1024, top-8 routing with unnormalised weights, no shared expert.
# 6.92B parameters, 1.3B of them active for a token.
OLMOE_1B_7B = TransformerConfig(
    vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
    n_kv_heads=16, d_ff=1024, max_seq_len=4096, rope_theta=10000.0,
    norm_eps=1e-5, qk_norm=True, moe_experts=64, moe_top_k=8,
    moe_norm_topk=False, moe_aux_coeff=0.01, remat=True)
