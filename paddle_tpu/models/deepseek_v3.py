"""DeepSeek-V3 family (``model_type: deepseek_v3``): a decoder whose
attention keeps ONE low-rank latent a token and layer for all its heads
(multi-head latent attention) and reads, at every position, EVERY earlier
token's; rotary frequencies blended as YaRN blends them, with its scale on
the softmax; the first ``first_k_dense_replace`` layers dense SwiGLU, the
others routed experts chosen inside the ``topk_group`` best of
``n_group`` groups of experts (sigmoid scores with a selection bias, a
group's score the sum of its two largest, top-k among the kept groups'
experts, weights renormalised over the chosen and scaled by
``routed_scaling_factor``) beside one shared expert. Serving only,
through the paged ``DecodeEngine``.

With ``n = RMSNorm(h)`` at a layer's entry (no biases anywhere):

    query    c_q = RMSNorm(n W_dq) [q_lora_rank]; q = c_q W_uq -> H heads
             of q_nope [qk_nope_head_dim] | q_rope [qk_rope_head_dim]
    latent   [c_kv | k_r] = n W_dkv [kv_lora_rank | qk_rope_head_dim];
             c_kv = RMSNorm(c_kv); k_r is one rotary key for all heads;
             [k_nope | v] = c_kv W_ukv -> H heads of qk_nope_head_dim |
             v_head_dim
    rope     interleaved pairs (x0,x1),(x2,x3),.. on q_rope and k_r, by
             YaRN's frequencies (``glm_moe_dsa.rope_inv_freq``); cos and
             sin times m(mscale) / m(mscale_all_dim), m(a) = 0.1 a
             ln(factor) + 1
    scores   s[t,u] = (q_nope[t].k_nope[u] + q_rope[t].k_r[u]) x
             (qk_nope_head_dim + qk_rope_head_dim)^-0.5 x
             m(mscale_all_dim)^2 over EVERY u <= t; softmax in float32;
             o = sum p v; out = concat(o) W_o
    experts  as above; y = shared(n') + routed_scaling_factor x sum over
             the chosen of g_e expert_e(n')

The multi-token-prediction layer adds no term to the next token's logits
and is not held.

The family is ``models/glm_moe_dsa.py``'s without its indexer, and what
is the same is that file's code: the latent projection in the ABSORBED
form (``_project_latent``: a query meets the cached latent as it lies,
H heads against one ``kv_lora_rank + qk_rope_head_dim`` wide key and one
``kv_lora_rank`` wide value a token), the output projection, the
feed-forward layers with the held share of the experts (``_ffn``), the
block loop of the cold prefill (``_prefill``) with its causal pass over
the row's carried latents (``_causal_latent_pass``), the decode step's
layer loop, the leaves and the model object. This file's own: the
configuration (YaRN, groups), and the two attends, both DENSE over the
row's whole context. A slot holds ONE kind of page, the latent's
(``PagedPrograms.value_pool`` false: the engine builds no second pool),
and both programs read it by ``kernels/latent_attention.py``: a decode
step the live rows' own pages, each once, as key and as value
(``mla_latent_decode``); a block of the cold prefill the row's carried
latents up to its own rows in ONE launch a layer (``mla_latent_prefill``:
scores, probabilities and the running sum of 4 heads x 512 queries stay
in fast memory, tiles of keys no query of the block may see are not
fetched), the plain pass being the CPU's path and the kernel's oracle.

The cold prefill takes the absorbed form as well (278.5 kFLOP a causal
pair and layer at these widths where keys and values expanded from the
latents take 81.9 k): expanded keys and values of a 32 k row are 2.7 GB a
layer, which one chip cannot carry beside the weights, and expanded anew
for every block of 256 queries they cost 33.5 MFLOP a key where the
block's own attention costs 21.0: 0.76 of the absorbed pass's operations
(0.44 at the 512 queries a block of a share of the experts holds),
in head-sized products of contraction 192 and 128 against the absorbed
pass's 640 and 2048 (PERF.md, Findings PR 44, has the chip's reading).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..kernels.latent_attention import latent_decode_attention
from . import glm_moe_dsa as G
from .paged_stack import PagedPrograms, _token_insert, greedy_chunk

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "DEEPSEEK_V3_PRESETS"]


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # a dense layer's SwiGLU
    moe_intermediate_size: int = 2048       # one expert's, routed or shared
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    num_key_value_heads: int = 128          # the expanded form's; unused
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256             # the router's outputs
    held_experts: tuple = None              # (first, count) held here
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    n_group: int = 8
    topk_group: int = 4
    rope_theta: float = 10000.0
    # rope_scaling, ``type: yarn``
    rope_type: str = "yarn"
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: str = "float32"

    def __post_init__(self):
        G.check_stack(self)
        for name, value in (("scoring_func", "sigmoid"),
                            ("rope_type", "yarn"), ("norm_topk_prob", True)):
            if getattr(self, name) != value:
                raise ValueError(
                    f"DeepseekV3 supports {name}={value!r} alone (the "
                    f"published deepseek_v3 configuration), got "
                    f"{getattr(self, name)!r}")
        per = self.n_routed_experts // max(self.n_group, 1)
        if not (1 <= self.topk_group <= self.n_group
                and per * self.n_group == self.n_routed_experts
                and (self.n_group == 1 or per >= 2)
                and self.topk_group * per >= self.num_experts_per_tok):
            raise ValueError(
                f"{self.n_routed_experts} experts in n_group={self.n_group} "
                f"groups of which topk_group={self.topk_group} stay cannot "
                f"give {self.num_experts_per_tok} experts a token")
        if self.mscale != self.mscale_all_dim:
            raise ValueError(
                f"mscale={self.mscale} != mscale_all_dim="
                f"{self.mscale_all_dim}: cos and sin would be scaled, "
                f"which the program does not do")

    @property
    def softmax_scale(self):
        """``(nope + rope)^-0.5`` times YaRN's ``m(mscale_all_dim)^2``."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * G.yarn_mscale(self.factor, self.mscale_all_dim) ** 2

    @property
    def logit_divisor(self):
        return 1.0 / self.softmax_scale

    def inv_freq(self):
        return G.rope_inv_freq(
            self.qk_rope_head_dim, self.rope_theta,
            (self.factor, self.original_max_position_embeddings,
             self.beta_fast, self.beta_slow))

    latent_dim = G.GlmMoeDsaConfig.latent_dim
    latent_lanes = G.GlmMoeDsaConfig.latent_lanes
    n_moe = G.GlmMoeDsaConfig.n_moe
    runs = G.GlmMoeDsaConfig.runs


DEEPSEEK_V3_PRESETS = {
    # one dense layer and four expert layers at debug widths: 32 experts
    # in 4 groups of which 2 stay, 8 of them held (the first group)
    "debug": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                  moe_intermediate_size=32, num_hidden_layers=5,
                  first_k_dense_replace=1, num_attention_heads=4,
                  q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=32,
                  held_experts=(0, 8), num_experts_per_tok=4, n_group=4,
                  topk_group=2, n_shared_experts=1,
                  original_max_position_embeddings=64),
}

# keys a piece of the plain causal pass takes at most: the scores of
# 128 heads x 256 queries x 1024 keys are what 64 x 256 x 2048 are
ATTEND_KEYS_HEADS = G.ATTEND_KEYS * 64


def _prefill_attend(cfg, lp, x, positions, caches, l, start, pad, first):
    """One layer's attention for one block of the cold prefill
    (``glm_moe_dsa._prefill``'s ``attend``): the block's latents go into
    the row's carried cache and every query reads every earlier one."""
    lat_c, = caches
    qc, lat, _, _ = G._project_latent(cfg, lp, x, positions)
    lat_c = jax.lax.dynamic_update_slice(lat_c, lat[None], (l, start, 0))
    o_lat = G._causal_latent_pass(
        cfg, qc, lat_c, l, start, start + jnp.arange(x.shape[0]), pad, first,
        at_most=ATTEND_KEYS_HEADS // cfg.num_attention_heads)
    return o_lat, (lat_c,)


def _decode_attention(cfg, lp, x, l, kp, tables, lens, live):
    """One layer's attention for one token per slot at position ``lens``
    [b]: the token's latent goes into the row's page at ``[layer,
    page]``, then the live rows' absorbed queries read their own pages,
    the new token's among them (a slot without a row reads nothing)."""
    bs = kp.shape[-2]
    qc, lat, _, _ = G._project_latent(cfg, lp, x, lens)
    page = jnp.take_along_axis(tables, (lens // bs)[:, None], axis=1)[:, 0]
    kp = _token_insert(kp, l, page, lens % bs, lat[:, None])
    with jax.named_scope("mla_dense_decode"):
        o_lat = latent_decode_attention(
            qc, kp, tables, jnp.where(live, lens + 1, 0), l,
            rank=cfg.kv_lora_rank, scale=cfg.softmax_scale)
    return G._out_proj(cfg, lp, o_lat), kp


def _decode_step(cfg, w, embed, final_norm, lm_head, tok, tables, lens,
                 pool, live):
    """One token per slot through the whole stack: tok [b] -> (float32
    logits [b, V], pool); pool = (latent pages, counters)."""
    x = jnp.take(embed, tok, axis=0)

    def attend(lp, x, l, pages):
        o, kp = _decode_attention(cfg, lp, x, l, *pages, tables, lens, live)
        return o, (kp,)

    x, pool = G._decode_layers(cfg, w, x, pool, live, attend)
    return G._logits(cfg, x, final_norm, lm_head), pool


def ctx_tokens(layers):
    """What the engine counts on the host at every decode launch
    (``PagedPrograms.host_counters``) from the contexts of the live rows
    at each of the launch's steps (int64 [steps, rows]): the cached
    latents the steps' attention reads, a row, layer and step."""
    return {"mla_ctx_tokens": lambda ctx: layers * int(ctx.sum())}


class DeepseekV3ForCausalLM(G.GlmMoeDsaForCausalLM):
    """Stacked-parameter DeepSeek-V3: ``GlmMoeDsaForCausalLM``'s leaves
    without the indexer's."""

    config_class, presets, indexer = (DeepseekV3Config, DEEPSEEK_V3_PRESETS,
                                      False)

    def paged_programs(self, chunk, prefill_block, mp_axis=None,
                       seq_axis=None, n_seq=1):
        """What ``DecodeEngine`` binds for this family."""
        cfg = self.config

        def prefill_paged(stacked, embed, fnorm, lm, scales, ids, pad_len,
                          table_row, slot, *pool):
            """ids [1, s_max] right-aligned; the row's latents go into
            its pages inside the program (``slot`` names no state: the
            family keeps none)."""
            logits, pool = G._prefill(cfg, stacked, embed, fnorm, lm, ids,
                                      pad_len, table_row, pool,
                                      prefill_block, attend=_prefill_attend)
            return (jnp.argmax(logits, axis=-1), *pool)

        def decode_chunk_paged(stacked, embed, fnorm, lm, scales, tok,
                               tables, lens, *pool):
            """One chunk; a slot with ``lens == 0`` holds no row: its
            token reads nothing, is routed to no expert and counted
            nowhere."""
            return greedy_chunk(_decode_step, (cfg, stacked, embed, fnorm, lm),
                                chunk, tok, tables, lens, pool)

        family = "a program of this family's own (latent pages)"
        return PagedPrograms(
            prefill_paged=prefill_paged,
            decode_chunk_paged=decode_chunk_paged,
            kv_layers=cfg.num_hidden_layers, kv_heads=1,
            head_dim=cfg.latent_lanes, value_pool=False,
            slot_state=lambda slots: (
                jax.ShapeDtypeStruct((5,), jnp.int32),),
            device_counters=("moe_pairs", "moe_expert_visits",
                             "moe_full_stream", "moe_groups_visited",
                             "moe_stream_rows"),
            host_counters=ctx_tokens(cfg.num_hidden_layers),
            trace_scopes=("mla_prefill_attn", "mla_dense_decode",
                          "moe_group_route", "moe_shared_ffn",
                          "moe_expert_ffn"),
            unsupported={
                "prefix_cache": f"a prefix hit needs {family} that starts "
                                "behind the cached pages",
                "paged=False": "the latent is a page of the pool",
                "chunked_prefill": f"a prompt's chunks need {family}",
                "spec_decode": f"verifying a draft needs {family}; the "
                               "multi-token-prediction layer is not held",
                "kv_dtype='int8'": "the pool holds a latent, not per-head "
                                   "keys and values with a scale a page",
                "mesh": "the latent is shared by all heads and the held "
                        "experts have no sharding rule"})
