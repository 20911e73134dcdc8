"""Sparse decoder family: a causal LM whose layers are a token MIXER, a
top-k mixture of experts, or — every model but one — the first followed by
the second, with RMSNorm, no biases in the matrices and an output head that
is a matrix of its own or, under ``tie_embeddings``, the embedding itself:
ONE [rows held, d_model] matrix read by the gather at the bottom and by the
product at the top, its gradient the sum of the two uses, so that a
vocabulary slice cuts both at once. What differs between the models of the
family is said by attributes.
Per layer, what it holds (``part_layout``: a mixer AND a feed-forward part,
each behind a norm of its own — the default —, or ONE of the two behind the
layer's one norm, x + f(norm(x))) and the mixer's kind (``mixer_layout``):
grouped-query softmax attention, the GATED DELTA RULE
(``ops/gated_delta.py``: a linear-attention layer whose memory is a
[key width, value width] float32 matrix a value head, behind a causal
depthwise convolution and in front of a gated RMSNorm), KIMI DELTA
ATTENTION (the same rule with a decay a KEY CHANNEL, from a low-rank gate;
a head-wise RMSNorm under a low-rank sigmoid gate on the way out), or a
MAMBA-2 state-space mixer (``ops/ssd.py``: a [head width, state size]
float32 matrix a head, decayed by a scalar, B and C shared by the heads of
a group, behind a causal depthwise convolution WITH a bias and in front of
a gated RMSNorm over each group), or a GATED SHORT CONVOLUTION (LFM2's:
one in-projection to three streams B, C and x, C * conv(B * x) with a causal
depthwise convolution of ``conv_width`` taps and no bias, an out-projection;
no recurrent state, no norm and no activation of its own, so its result is
CUBIC in the layer's normed input). For an attention
layer: rotary positions or none, over the whole head or its leading
``rotary_dim``; which keys a query reads — the full causal prefix, a causal
window, or the ``select_topk`` keys a learned indexer chose
(DeepSeek-Sparse-Attention: ``ops/sparse_attention.py``; the indexer
learns from a loss term of its own and from nothing else); RMSNorm over the
head width on queries and keys (``qk_norm``); a sigmoid gate on the
attention's result from a second half of the query projection
(``attn_gate``). For the expert part: the router's input (``router_input``:
the mixer's normed input, i.e. tapped BEFORE the mixer, or the expert
layer's own normed input); the experts' gate (``expert_activation``: ReLU
or SiLU) or, for UNGATED experts of two matrices (``expert_gated`` False),
their hidden layer's activation (``relu2``: squared ReLU); a SHARED expert
every token passes through beside the routed ones
(``shared_expert_width``). And whether an RMSNorm's gain is zero-centred,
``1 + g`` (``zero_centered_norm``). A layer that holds NO experts
(``experts_held`` 0) has a dense gated feed-forward part of ``dense_width``
in their place, and ``sandwich_norm`` puts an RMSNorm on each sublayer's
result before it joins the residual. ``dense_layout`` says so a layer: the
leading layer(s) of a stack dense, the rest with experts, in one parameter
tree and one set of counters. An attention layer with a ``latent_dim`` is
MULTI-HEAD LATENT ATTENTION (DeepSeek-V2's): keys and values are rebuilt, a
head at a time, from ONE latent a token (a down-projection and an RMSNorm,
then an up-projection to this chip's heads), the rotary part of the key
(``rope_head_dim`` of the ``head_dim`` q/k width) is one head that all
query heads read — turned by the positions, or, in a layer without
positions, as it comes —, and values and the result are ``v_head_dim``
wide. The
router may score with a sigmoid and choose by score + a bias
(``router_scoring``, ``routed_scaling``:
``parallel/moe.py:route_sigmoid_top_k``; ``router_norm_eps`` is what the
model adds to the chosen scores' sum), and the shared expert may go
without its gate (``shared_expert_gate``). The defaults are SmallThinker's.

A model with ``loop_steps`` U > 1 is a LOOPED decoder (arXiv:2510.25741):
its ``num_layers`` layers are run U times over with the SAME parameters —
one scan over passes, so the compiled step holds the stack once —, the
final norm closes every pass and its result enters the next, the head and
a learned exit gate read every pass's result, and the training loss is the
expectation of the passes' losses under the exit distribution the gates
give, less ``exit_entropy_weight`` times its entropy. A weight's gradient
is the sum over its U uses, added up in float32: the cast to the compute
dtype sits inside the pass.

A model with a ``block_length`` is trained by DIFFUSION OVER BLOCKS
(BD3-LM, arXiv:2503.09573) instead of next-token prediction: every sequence
runs through the layers as a stream [x_t ; x_0] — a noised copy, some tokens
replaced by the mask token block by block (:func:`block_diffusion_noise`, on
the input side), before the clean copy — with both copies of token i at
position i and attention under the two-stream block mask
(``ops/block_diffusion_attention.py``); the head reads the noised half only
and a masked position's target is its own clean token.

One chip's share of an expert-parallel, head-parallel, vocabulary-parallel
deployment: a layer is told how many query and key-value heads, which
experts (``first_expert``, ``experts_held``) and how many rows of the
vocabulary it holds. The router scores ALL ``num_experts``; rows routed to
experts held elsewhere are left out of the result, which goes on to the
next layer as it is (``parallel/moe.py:held_experts_ffn``). Nothing stands
in for the absent chips.

bf16 activations and products; float32 parameters, router scores,
attention softmax and logits. Attention goes through the one dispatch
(``ops/attention.py:attention_context``: dense or the Pallas flash
kernels).
"""

import functools
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from edl_tpu.ops import block_diffusion_attention as bd_attention
from edl_tpu.ops import gated_delta, sparse_attention, ssd
from edl_tpu.ops.attention import (attention_context,
                                   block_diffusion_attention,
                                   selected_attention)
from edl_tpu.ops.cross_entropy import next_ids, token_cross_entropy
from edl_tpu.parallel import moe

#: what a layer counts about its routing each step (float32 scalars);
#: `load_max` is kept as a running maximum, the others as running sums
COUNTERS = ("rows_held", "load_max", "load_mean", "tokens_unserved",
            "rows_dropped", "rows_moved")
#: and, in a model with a selecting layer, about its selection: the keys
#: kept summed over the rows, the rows that kept another number than
#: min(position + 1, select_topk) (exact ties at the threshold only), and
#: the layer's index loss (mean over its tokens); running sums
SELECT_COUNTERS = ("pairs_kept", "rows_off_count", "index_loss")
#: and, in a model whose router scores with a sigmoid and chooses by score
#: + bias, per expert layer (0 for a dense one): the (token, slot) choices
#: the bias changed, and the sum over tokens of the chosen experts' weights
#: (``routed_scaling`` a token); running sums
ROUTE_COUNTERS = ("route_bias_flips", "route_weight_sum")
#: and, in a model trained by diffusion over blocks: per layer, the (query,
#: key) pairs of one head that the attention forward counted under the mask
#: it applied; and ``loss_tokens``, one scalar for the model: the masked
#: positions that carried loss; running sums
BLOCK_DIFFUSION_COUNTERS = ("pairs_attended",)
#: and, in a model with a gated-delta-rule layer, per such layer (0 for an
#: attention layer): the most negative cumulative log decay a chunk of any
#: step reached (what a form that takes ``exp(-gamma)`` would overflow on;
#: a running minimum) and the largest |S| at a chunk's end (a running
#: maximum)
GATED_DELTA_COUNTERS = ("gdn_chunk_log_decay_min", "gdn_state_absmax")
#: and, in a model with a Mamba-2 layer, per such layer (0 for any other):
#: the same two of the SSD scan (``ops/ssd.py``)
SSD_COUNTERS = ("ssd_chunk_log_decay_min", "ssd_state_absmax")
#: and, in a model with a Kimi-Delta-Attention layer, per such layer (0 for
#: any other): the same two of the rule at a vector decay, the minimum over
#: the key channels too
KDA_COUNTERS = ("kda_chunk_log_decay_min", "kda_state_absmax")
#: and, in a model with a gated-short-convolution layer, per such layer (0
#: for any other): the largest |C * z| any step formed, float32, before it is
#: rounded for the out-projection (a running maximum: what says the cubic
#: product stays inside bfloat16's range)
SHORTCONV_COUNTERS = ("conv_gate_absmax",)
#: and, in a looped model, per PASS (``[loop_steps]`` each, not per layer):
#: the mean over the predicted tokens of the exit distribution p(u) (sums to
#: 1 over the passes, a running sum over the steps) and of pass u's own
#: next-token loss, as the loss applied them; and the root mean square of
#: the residual stream at the end of pass u, BEFORE the final norm (a
#: running maximum: what says the recurrence stays bounded)
LOOP_COUNTERS = ("loop_exit_mass", "loop_pass_loss", "loop_stream_rms_max")
#: how a counter is kept over the steps (and, in a looped model, a layer's
#: over the passes of a step), where not as a running sum
_RUNNING = {"load_max": jnp.maximum, "gdn_state_absmax": jnp.maximum,
            "gdn_chunk_log_decay_min": jnp.minimum,
            "ssd_state_absmax": jnp.maximum,
            "ssd_chunk_log_decay_min": jnp.minimum,
            "kda_state_absmax": jnp.maximum,
            "kda_chunk_log_decay_min": jnp.minimum,
            "conv_gate_absmax": jnp.maximum,
            "loop_stream_rms_max": jnp.maximum}
#: what a layer under remat keeps for its backward, the one policy of every
#: model of the family (a name that no layer of a model emits saves
#: nothing): the chosen experts with the two grouped products' results (the
#: part whose cost follows the routing); a selecting layer's indexer
#: operands and thresholds (the choice WITH what it was made from); and the
#: masked attention kernels' own residuals, result and lse — 34 MB a layer
#: at a 16384-row stream of 8 heads of 128, against a second ``dsa_fwd``
#: (10 ms) or ``bdiff_fwd`` (2.7 ms) in every layer's backward that would
#: only rebuild them; and the gated delta rule's result and chunk-end
#: states (335 MB a layer at 16384 tokens of 16 value heads, against a
#: second ``gdn_fwd``; a vector decay's likewise, under names of their own,
#: against a second ``kda_fwd``); and the SSD scan's (101 MB a layer at 8192
#: tokens of 32 heads of 64 x 128, against a second ``ssd_fwd``). The band
#: kernels name no residual and run twice.
#: A latent-attention layer names none either — neither the latent with its
#: rotary key (576 values a token: 9.4 MB a layer at 8192 tokens) nor this
#: chip's k and v (8 x 320 values a token: 42 MB): its backward starts from
#: the layer's input and rebuilds both, 19 + 34 GFLOP a layer at 8192
#: tokens of 8 heads, under 0.3 ms at the chip's peak beside the band
#: kernels' second forward.
SAVED_UNDER_REMAT = (moe.SAVED_UNDER_REMAT
                     + sparse_attention.SAVED_UNDER_REMAT
                     + bd_attention.SAVED_UNDER_REMAT
                     + gated_delta.SAVED_UNDER_REMAT
                     + gated_delta.KDA_SAVED_UNDER_REMAT
                     + ssd.SAVED_UNDER_REMAT)


def _init(std=0.02):
    return nn.initializers.normal(stddev=std)


class RMSNorm(nn.Module):
    """``zero_centered``: the gain is 1 + scale, scale seeded at zero."""
    eps: float = 1e-6
    zero_centered: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros if
                           self.zero_centered else nn.initializers.ones,
                           (x.shape[-1],), jnp.float32)
        if self.zero_centered:
            scale = 1.0 + scale
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + self.eps)
        return (y * scale).astype(x.dtype)


class GroupRMSNorm(nn.Module):
    """RMSNorm over each of ``groups`` equal parts of the last axis, one
    plain gain an entry: a part's statistics are its own, so a chip that
    holds whole groups norms what it holds as the uncut layer would."""
    groups: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32).reshape(
            x.shape[:-1] + (self.groups, x.shape[-1] // self.groups))
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                                + self.eps)
        return (y.reshape(x.shape) * scale).astype(x.dtype)


def rope(x, theta, positions=None, rotary_dim=None):
    """Rotary positions, half-split convention: x [b, s, h, d]; pair i is
    (x[i], x[i + d/2]), turned by position * theta ** (-2 i / d).
    ``positions`` [s] are an argument: each row's position, for a stream in
    which a row's index is not its position (two copies of one sequence);
    None counts them 0 .. s - 1. ``rotary_dim``: only the LEADING
    ``rotary_dim`` of the head are turned (half-split inside them), the
    rest passes untouched."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate([
            rope(x[..., :rotary_dim], theta, positions),
            x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    if positions is None:
        positions = jnp.arange(x.shape[1])
    ang = positions.astype(jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _log_uniform(low, high, transform=jnp.log):
    """An initializer: ``transform`` of a value drawn log-uniformly from
    [low, high]."""
    def init(key, shape, dtype=jnp.float32):
        x = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       jnp.log(low), jnp.log(high)))
        return transform(x).astype(dtype)
    return init


class SparseDecoderLayer(nn.Module):
    """h = norm(x); x' = x + mixer(h), attention, the gated delta rule,
    Kimi Delta Attention, a Mamba-2 state-space mixer or a gated short
    convolution (``mixer``);
    u = norm(x'); out = x' + held experts(u) [+ shared expert(u)], routed on
    h or on u (``router_input``);
    with no expert held, out = x' + dense feed-forward(u), no router and no
    routing counters; under ``sandwich_norm`` each sublayer's result is
    normed before it is added. A layer of ``parts`` "mixer" ends at x', one
    of "ffn" starts there (x' = x): one sublayer behind one norm. Returns
    (out, counters); a selecting layer's counters hold its index loss,
    which is differentiable (towards the indexer alone)."""
    heads: int                 # query heads held here
    kv_heads: int              # key-value heads held here
    head_dim: int
    num_experts: int           # the router's width: all the layer's experts
    experts_held: int
    first_expert: int
    experts_per_token: int
    expert_width: int
    use_rope: bool
    rope_theta: float
    window: Optional[int]      # None: the whole causal prefix
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    use_flash: Optional[bool] = None
    select_topk: Optional[int] = None   # keys a query keeps; None: no indexer
    index_heads: int = 0
    index_dim: int = 0
    router_input: str = "attn_norm"     # or "moe_norm"
    expert_activation: str = "relu"     # or "silu"
    qk_norm: bool = False
    streams: Optional[Tuple[int, int]] = None   # the two-stream block mask
    #: or "gated_delta", "mamba2", "kda", "shortconv"
    mixer: str = "attention"
    gdn_key_heads: int = 0              # key heads of a gated-delta layer
    gdn_value_heads: int = 0            # its value heads, held here
    gdn_head_dim: int = 0               # the width of both
    conv_width: int = 4
    attn_gate: bool = False             # sigmoid gate on attention's result
    rotary_dim: Optional[int] = None    # None: the whole head
    zero_centered_norm: bool = False
    shared_expert_width: int = 0        # 0: no shared expert
    dense_width: int = 0                # the feed-forward part's, no experts
    sandwich_norm: bool = False         # a norm on each sublayer's result
    latent_dim: int = 0                 # > 0: latent attention, this wide
    rope_head_dim: int = 0              # its rotary part of head_dim
    v_head_dim: int = 0                 # its values' width; 0: head_dim
    router_scoring: str = "softmax"     # or "sigmoid" (score + bias)
    routed_scaling: float = 1.0         # on a sigmoid router's weights
    router_norm_eps: float = 1e-20      # beside its chosen scores' sum
    shared_expert_gate: bool = True     # a sigmoid gate on the shared expert
    parts: str = "both"                 # or "mixer", "ffn": that one alone
    expert_gated: bool = True           # False: experts of two matrices
    ssm_heads: int = 0                  # Mamba-2 heads held here
    ssm_head_dim: int = 0
    ssm_groups: int = 0                 # groups held here: B, C a group
    ssm_state: int = 0
    ssm_chunk: int = ssd.CHUNK
    ssm_first_head: int = 0             # of the whole model's (A's seeding)
    kda_heads: int = 0                  # Kimi-Delta-Attention heads held here
    kda_head_dim: int = 0               # the width of q, k and v alike
    kda_gate_rank: int = 0              # of the two low-rank gates

    def _route(self, x):
        """(idx, weights, what a sigmoid router counts: {} for a softmax)"""
        b, s, d = x.shape
        with jax.named_scope("moe.route"):
            router = self.param("router", _init(), (d, self.num_experts),
                                jnp.float32)
            if self.router_scoring == "sigmoid":
                return moe.route_sigmoid_top_k(
                    x.reshape(b * s, d), router,
                    self.param("router_bias", nn.initializers.zeros,
                               (self.num_experts,), jnp.float32),
                    self.experts_per_token, self.routed_scaling,
                    self.router_norm_eps)
            return moe.route_top_k(x.reshape(b * s, d), router,
                                   self.experts_per_token) + ({},)

    def _index(self, h, proj):
        """The indexer on stop_gradient(h): (qi, ki, wi, tau, the packed
        kept set, lse_i) — `selected_attention`'s ``select``. Its four
        tensors learn from the index loss alone, and nothing upstream of
        them learns from it."""
        from jax.ad_checkpoint import checkpoint_name
        d = h.shape[-1]
        h = jax.lax.stop_gradient(h)
        with jax.named_scope("attn.index"):
            qi = jnp.einsum("bsd,dhk->bshk", h, proj(
                "index_query", (d, self.index_heads, self.index_dim)))
            ki = jnp.einsum("bsd,dk->bsk", h, proj("index_key",
                                                   (d, self.index_dim)))
            wi = jnp.einsum("bsd,dh->bsh", h, proj(
                "index_weight", (d, self.index_heads)),
                preferred_element_type=jnp.float32)
            if self.use_rope:
                qi = rope(qi, self.rope_theta)
                ki = rope(ki[:, :, None], self.rope_theta)[:, :, 0]
            qi, ki, wi = (checkpoint_name(x, n) for x, n in zip(
                (qi, ki, wi), sparse_attention.SAVED_UNDER_REMAT))
            # the kernels' dispatch as `selected_attention` makes it
            tau, kept, lse_i = sparse_attention.index_selection(
                qi, ki, wi, self.select_topk, use_kernel=self.use_flash,
                interpret=jax.default_backend() == "cpu")
        # for tools that compare the choice (a no-op unless a caller makes
        # the collection mutable)
        self.sow("intermediates", "select", (qi, ki, wi, tau))
        return qi, ki, wi, tau, kept, lse_i

    def _norm(self, name):
        return RMSNorm(self.eps, self.zero_centered_norm, name=name)

    def _own_norm(self, name, x):
        """A norm of the layer's own, outside any mixer's scope."""
        with jax.named_scope("norm"):
            return self._norm(name)(x)

    def _gated_delta(self, h, proj):
        """The linear-attention mixer on the normed input h: (its part of
        the residual, the rule's two statistics). The projection's columns
        are grouped by KEY head — q, k, then that head's value heads' v and
        their z; b and a likewise — so a contiguous split of the heads over
        chips is a split over key heads."""
        b, s, d = h.shape
        dt, f32 = self.dtype, jnp.float32
        hk, hv, dh = self.gdn_key_heads, self.gdn_value_heads, \
            self.gdn_head_dim
        r = hv // hk
        with jax.named_scope("mixer.gdn.proj"):
            qkvz = jnp.einsum("bsd,dhk->bshk", h, proj(
                "in_proj_qkvz", (d, hk, (2 + 2 * r) * dh)))
            ba = jnp.einsum("bsd,dhk->bshk", h,
                            proj("in_proj_ba", (d, hk, 2 * r)),
                            preferred_element_type=f32)
            flat = lambda x: x.reshape(b, s, -1)
            q, k, v = (flat(qkvz[..., lo * dh:hi * dh]) for lo, hi in (
                (0, 1), (1, 2), (2, 2 + r)))
            z = qkvz[..., (2 + r) * dh:].reshape(b, s, hv, dh)
            beta, a = flat(ba[..., :r]), flat(ba[..., r:])
        with jax.named_scope("mixer.gdn.conv"):
            conv = self.param("conv", _init(), ((2 * hk + hv) * dh,
                                                self.conv_width), f32)
            mixed = jax.nn.silu(gated_delta.causal_conv(
                jnp.concatenate([q, k, v], axis=-1), conv))
            q, k = (mixed[..., i * hk * dh:(i + 1) * hk * dh].reshape(
                b, s, hk, dh) for i in (0, 1))
            v = mixed[..., 2 * hk * dh:].reshape(b, s, hv, dh).astype(dt)
        with jax.named_scope("mixer.gdn.scan"):
            a_log = self.param("A_log", _log_uniform(1.0, 16.0), (hv,), f32)
            dt_bias = self.param(
                "dt_bias", _log_uniform(1e-3, 1e-1,
                                        lambda x: jnp.log(jnp.expm1(x))),
                (hv,), f32)
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            unit = lambda x: x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)
            o, stats = gated_delta.gated_delta_rule(
                (unit(q) * dh ** -0.5).astype(dt), unit(k).astype(dt), v, g,
                jax.nn.sigmoid(beta), use_kernel=self.use_flash)
        with jax.named_scope("mixer.gdn.out"):
            y = (RMSNorm(self.eps, name="norm_gdn")(o.astype(f32))
                 * jax.nn.silu(z.astype(f32))).astype(dt)
            out = jnp.einsum("bshk,hkd->bsd", y, proj("out", (hv, dh, d)))
        return out, stats

    def _kda(self, h, proj):
        """Kimi Delta Attention on the normed input h: (its part of the
        residual, the rule's two statistics). q, k and v, the convolution's
        channels, the gates' up-projections, beta, A_log and dt_bias all
        come a HEAD at a time, so a contiguous split of the heads over
        chips is a split of every tensor but the two gates'
        down-projections, which are whole on each chip as a latent's is."""
        b, s, d = h.shape
        dt, f32 = self.dtype, jnp.float32
        hh, dh, rank = self.kda_heads, self.kda_head_dim, self.kda_gate_rank
        with jax.named_scope("mixer.kda.proj"):
            parts = [jnp.einsum("bsd,dhk->bshk", h, proj(name, (d, hh, dh)))
                     for name in ("query", "key", "value")]
            low = jnp.einsum("bsd,dgr->bsgr", h,
                             proj("gates_down", (d, 2, rank)))
            beta = jnp.einsum("bsd,dh->bsh", h, proj("in_proj_b", (d, hh)),
                              preferred_element_type=f32)
        with jax.named_scope("mixer.kda.conv"):
            # q, k and v each through its own channels, one after another,
            # and rounded once here: the float32 sums, the SiLU and their
            # cotangents of all three side by side were 1.6 GB of a layer's
            # backward at 8192 tokens
            conv = self.param("conv", _init(), (3, hh * dh, self.conv_width),
                              f32)
            q, k, v = (jax.nn.silu(gated_delta.causal_conv(
                x.reshape(b, s, hh * dh), conv[i])).astype(dt).reshape(
                    b, s, hh, dh) for i, x in enumerate(parts))
        with jax.named_scope("mixer.kda.gate"):
            a_log = self.param("A_log", _log_uniform(1.0, 16.0), (hh,), f32)
            dt_bias = self.param(
                "dt_bias", _log_uniform(1e-3, 1e-1,
                                        lambda x: jnp.log(jnp.expm1(x))),
                (hh, dh), f32)
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(jnp.einsum(
                "bsr,rhk->bshk", low[:, :, 0], proj("decay_up",
                                                    (rank, hh, dh)),
                preferred_element_type=f32) + dt_bias)
        with jax.named_scope("mixer.kda.scan"):
            unit = lambda x: x.astype(f32) * jax.lax.rsqrt(jnp.sum(
                jnp.square(x.astype(f32)), -1, keepdims=True) + 1e-6)
            o, stats = gated_delta.gated_delta_rule(
                (unit(q) * dh ** -0.5).astype(dt), unit(k).astype(dt), v, g,
                jax.nn.sigmoid(beta), use_kernel=self.use_flash)
        with jax.named_scope("mixer.kda.out"):
            gate = jnp.einsum("bsr,rhk->bshk", low[:, :, 1],
                              proj("gate_up", (rank, hh, dh)),
                              preferred_element_type=f32)
            y = (RMSNorm(self.eps, name="norm_kda")(o.astype(f32))
                 * jax.nn.sigmoid(gate)).astype(dt)
            out = jnp.einsum("bshk,hkd->bsd", y, proj("out", (hh, dh, d)))
        return out, stats

    def _short_conv(self, h, proj):
        """The gated short convolution on the normed input h: (its part of
        the residual, the largest |C * z|). B, C and x are bfloat16 products
        of h; B * x, the taps' sums and C * z are float32 (`causal_conv`'s),
        and the CUBIC product is rounded ONCE, where it enters the
        out-projection. The channels are the model's width, whole on every
        chip of a deployment: nothing here is a head's."""
        d = h.shape[-1]
        f32 = jnp.float32
        with jax.named_scope("mixer.conv.in_proj"):
            bcx = jnp.einsum("bsd,dgc->bsgc", h,
                             proj("in_proj_bcx", (d, 3, d)))
        with jax.named_scope("mixer.conv.gate"):
            taps = self.param(
                "conv", lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -shape[1] ** -0.5, shape[1] ** -0.5),
                (d, self.conv_width), f32)
            gated = bcx[:, :, 1].astype(f32) * gated_delta.causal_conv(
                bcx[:, :, 0].astype(f32) * bcx[:, :, 2].astype(f32), taps)
            top = jnp.max(jnp.abs(jax.lax.stop_gradient(gated)))
        with jax.named_scope("mixer.conv.out"):
            out = jnp.einsum("bsc,cd->bsd", gated.astype(self.dtype),
                             proj("out", (d, d)))
        return out, {"conv_gate_absmax": top}

    def _mamba2(self, h, proj):
        """The Mamba-2 mixer on the normed input h: (its part of the
        residual, the scan's two statistics). The projection's columns are
        laid a GROUP at a time — the group's heads' z, their x, the group's
        B and C; the heads' steps likewise —, and the convolution's channels
        with them, so a contiguous split over chips is a split over whole
        groups, and the gated norm's statistics are a group's own."""
        b, s, d = h.shape
        dt, f32 = self.dtype, jnp.float32
        hh, p, g, n = (self.ssm_heads, self.ssm_head_dim, self.ssm_groups,
                       self.ssm_state)
        wide = hh // g * p              # a group's heads, side by side
        with jax.named_scope("ssm.in_proj"):
            zxbc = jnp.einsum("bsd,dgk->bsgk", h, proj(
                "in_proj_zxbc", (d, g, 2 * wide + 2 * n)))
            step = jnp.einsum("bsd,dgk->bsgk", h,
                              proj("in_proj_dt", (d, g, hh // g)),
                              preferred_element_type=f32)
            z = zxbc[..., :wide].reshape(b, s, hh, p)
        with jax.named_scope("ssm.conv"):
            channels = g * (wide + 2 * n)
            mixed = jax.nn.silu(gated_delta.causal_conv(
                zxbc[..., wide:].reshape(b, s, channels),
                self.param("conv", _init(), (channels, self.conv_width),
                           f32),
                self.param("conv_bias", _init(), (channels,), f32))).reshape(
                b, s, g, wide + 2 * n).astype(dt)
            x = mixed[..., :wide].reshape(b, s, hh, p)
            bm, cm = mixed[..., wide:wide + n], mixed[..., wide + n:]
        with jax.named_scope("ssm.scan"):
            first = self.ssm_first_head
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(
                    1.0 + first + jnp.arange(shape[0], dtype=dtype)),
                (hh,), f32)
            dt_bias = self.param(
                "dt_bias", _log_uniform(1e-3, 1e-1, lambda x: jnp.log(
                    jnp.expm1(jnp.maximum(x, 1e-4)))), (hh,), f32)
            skip = self.param("D", nn.initializers.ones, (hh,), f32)
            y, stats = ssd.ssd_scan(
                x, jax.nn.softplus(step.reshape(b, s, hh) + dt_bias), a_log,
                bm, cm, skip, chunk=self.ssm_chunk,
                use_kernel=self.use_flash)
        with jax.named_scope("ssm.norm"):
            y = GroupRMSNorm(g, self.eps, name="norm_ssm")(
                (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(
                    b, s, hh * p)).astype(dt).reshape(b, s, hh, p)
        with jax.named_scope("ssm.out"):
            out = jnp.einsum("bshk,hkd->bsd", y, proj("out", (hh, p, d)))
        return out, stats

    def _latent_attention(self, h, proj, positions):
        """Multi-head latent attention on the normed input h: its part of
        the residual. W_kv_down [d, latent + rope] is WHOLE on every chip of
        a head-parallel group (the latent is not sharded); kv_up, query and
        out are cut by heads. The 192-wide key is built by laying the one
        rotary key beside every head's own part: the band kernels then read
        it as any key. In a layer without positions (``use_rope`` False)
        that key part, and q's last ``rope_head_dim``, go UNTURNED."""
        b, s, d = h.shape
        dt = self.dtype
        hd, dr, dc = self.head_dim, self.rope_head_dim, self.latent_dim
        dn, dv = hd - dr, self.v_head_dim or self.head_dim
        with jax.named_scope("attn.latent.down"):
            down = jnp.einsum("bsd,dk->bsk", h, proj("kv_down", (d, dc + dr)))
            c = self._norm("norm_latent")(down[..., :dc])
        with jax.named_scope("attn.latent.up"):
            kv = jnp.einsum("bsc,chk->bshk", c,
                            proj("kv_up", (dc, self.heads, dn + dv)))
        with jax.named_scope("attn.latent"):
            q = jnp.einsum("bsd,dhk->bshk", h,
                           proj("query", (d, self.heads, hd)))
            turn = (lambda x: rope(x, self.rope_theta, positions)) \
                if self.use_rope else (lambda x: x)
            q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
            k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
                turn(down[:, :, None, dc:]), (b, s, self.heads, dr))],
                axis=-1)
            a = attention_context(q, k, kv[..., dn:], causal=True, mask=None,
                                  dtype=dt, use_flash=self.use_flash,
                                  window=self.window)
            return jnp.einsum("bshk,hkd->bsd", a,
                              proj("out", (self.heads, dv, d)))

    def _attention(self, h, proj, positions, select):
        """Grouped-query attention on the normed input h: (its part of the
        residual, what the masked paths counted: (kl, kept) of a selection,
        the pairs of the two-stream block mask, else None)."""
        d = h.shape[-1]
        dt = self.dtype
        hd = self.head_dim
        counted = None
        with jax.named_scope("attn.select" if select else
                             "attn.block_diffusion" if self.streams else
                             "attn.window" if self.window else "attn.full"):
            q = jnp.einsum("bsd,dhk->bshk", h, proj(
                "query", (d, self.heads, 2 * hd if self.attn_gate else hd)))
            if self.attn_gate:      # a head at a time: its query, its gate
                q, gate = q[..., :hd], q[..., hd:]
            k = jnp.einsum("bsd,dhk->bshk", h,
                           proj("key", (d, self.kv_heads, hd)))
            v = jnp.einsum("bsd,dhk->bshk", h,
                           proj("value", (d, self.kv_heads, hd)))
            if self.qk_norm:
                q = self._norm("norm_query")(q)
                k = self._norm("norm_key")(k)
            if self.use_rope:
                q = rope(q, self.rope_theta, positions, self.rotary_dim)
                k = rope(k, self.rope_theta, positions, self.rotary_dim)
            if select:
                a, *counted = selected_attention(
                    q, k, v, select, dtype=dt, use_flash=self.use_flash)
            elif self.streams:
                a, counted = block_diffusion_attention(
                    q, k, v, self.streams, dtype=dt,
                    use_flash=self.use_flash)
            else:
                a = attention_context(q, k, v, causal=True, mask=None,
                                      dtype=dt, use_flash=self.use_flash,
                                      window=self.window)
            if self.attn_gate:
                with jax.named_scope("attn.gate"):
                    a = (a * jax.nn.sigmoid(gate.astype(jnp.float32))
                         ).astype(dt)
            return jnp.einsum("bshk,hkd->bsd", a,
                              proj("out", (self.heads, hd, d))), counted

    def _feed_forward(self, x, dense, routing):
        """The feed-forward part on x, behind its norm: (its part of the
        residual [b * s, d], the routing's counters; {} for a dense one).
        ``routing``: (idx, p, counted) where the router read the mixer's
        normed input, else None and it reads this part's own."""
        b, s, d = x.shape
        u = self._own_norm("norm_moe", x)
        gated = self.expert_gated
        # a gated linear unit's first matrix is gate then up, side by side
        first = ("gate_up", 2) if gated else ("up", 1)
        weight = lambda name, shape: self.param(name, _init(), shape,
                                                jnp.float32)
        if dense:
            m, counters = moe.dense_ffn(
                u.reshape(b * s, d),
                weight("ffn_" + first[0], (d, first[1] * self.dense_width)),
                weight("ffn_down", (self.dense_width, d)),
                activation=self.expert_activation, gated=gated), {}
        else:
            idx, p, routed = routing or self._route(u)
            f = self.expert_width
            up = weight("experts_" + first[0],
                        (self.experts_held, d, first[1] * f))
            down = weight("experts_down", (self.experts_held, f, d))
            m, counters = moe.held_experts_ffn(
                u.reshape(b * s, d), idx, p, up, down,
                self.first_expert, activation=self.expert_activation,
                gated=gated)
            counters = dict(counters, **routed)
        if self.shared_expert_width:
            fs = self.shared_expert_width
            m = m + moe.shared_expert_ffn(
                u.reshape(b * s, d),
                weight("shared_" + first[0], (d, first[1] * fs)),
                weight("shared_down", (fs, d)),
                weight("shared_gate", (d,))
                if self.shared_expert_gate else None,
                activation=self.expert_activation, gated=gated)
        return m, counters

    @nn.compact
    def __call__(self, x, positions=None):
        b, s, d = x.shape
        dt = self.dtype
        proj = lambda name, shape: self.param(name, _init(), shape,
                                              jnp.float32).astype(dt)
        if self.router_input not in ("attn_norm", "moe_norm"):
            raise ValueError("router_input %r" % (self.router_input,))
        if self.mixer not in ("attention", "gated_delta", "mamba2", "kda",
                              "shortconv"):
            raise ValueError("mixer %r" % (self.mixer,))
        if self.parts not in ("both", "mixer", "ffn"):
            raise ValueError("parts %r" % (self.parts,))
        if self.streams and (self.select_topk or self.window):
            raise ValueError("the two-stream block mask takes no selection "
                             "and no window")
        has_mixer, has_ffn = self.parts != "ffn", self.parts != "mixer"
        linear = has_mixer and self.mixer == "gated_delta"
        state_space = has_mixer and self.mixer == "mamba2"
        delta = has_mixer and self.mixer == "kda"
        conv = has_mixer and self.mixer == "shortconv"
        if (linear or state_space or delta or conv) and (
                self.streams or self.select_topk or self.window):
            raise ValueError("a %s layer takes no mask" % (
                "gated-delta-rule" if linear else
                "Mamba-2" if state_space else
                "Kimi-Delta-Attention" if delta else "short-convolution"))
        if conv and (self.latent_dim or self.qk_norm or self.attn_gate):
            raise ValueError("a short-convolution layer has no query, key "
                             "or value: no latent, q/k norm or gate")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError("router_scoring %r" % (self.router_scoring,))
        if self.latent_dim and not delta and (
                linear or state_space or self.streams or self.select_topk
                or self.qk_norm or self.attn_gate
                or self.kv_heads != self.heads):
            raise ValueError(
                "a latent-attention layer is causal softmax attention with "
                "as many key-value heads as query heads, and no selection, "
                "block mask, q/k norm or gate")
        dense = self.experts_held == 0
        if self.parts != "both" and (self.sandwich_norm or (
                self.router_input == "attn_norm" and not dense)):
            raise ValueError("a layer of one sublayer has one norm: no "
                             "sandwich norm, and the router reads its own "
                             "layer's normed input")
        select = counted = routing = None
        if has_mixer:
            h = self._own_norm("norm_attn", x)
            if self.router_input == "attn_norm" and not dense:
                routing = self._route(h)
            select = self._index(h, proj) if self.select_topk else None
            if linear:
                mixed, counted = self._gated_delta(h, proj)
            elif state_space:
                mixed, counted = self._mamba2(h, proj)
            elif delta:
                mixed, counted = self._kda(h, proj)
            elif conv:
                mixed, counted = self._short_conv(h, proj)
            elif self.latent_dim:
                mixed, counted = self._latent_attention(h, proj,
                                                        positions), None
            else:
                mixed, counted = self._attention(h, proj, positions, select)
            if self.sandwich_norm:
                mixed = self._own_norm("norm_attn_out", mixed)
            x = x + mixed
        m, counters = (self._feed_forward(x, dense, routing) if has_ffn
                       else (None, {}))
        if select:
            kl, kept = counted
            with jax.named_scope("attn.index_loss"):
                want = jnp.minimum(jnp.arange(s) + 1, self.select_topk)
                counters = dict(
                    counters, pairs_kept=kept.sum(),
                    rows_off_count=jnp.sum(kept != want[None]).astype(
                        jnp.float32),
                    index_loss=kl.mean())
        if self.streams:
            counters = dict(counters, pairs_attended=counted.sum())
        if linear or state_space or delta:
            prefix = "gdn_" if linear else "ssd_" if state_space else "kda_"
            counters = dict(counters, **{prefix + n: v
                                         for n, v in counted.items()})
        if conv:
            counters = dict(counters, **counted)
        if m is None:
            return x, counters
        m = m.reshape(b, s, d)
        if self.sandwich_norm:
            m = self._own_norm("norm_ffn_out", m)
        return x + m, counters


class SparseDecoder(nn.Module):
    """ids [b, s] -> (float32 logits [b, s, vocab], counters {name: [L]}).
    ``positions`` [s] come with the ids (default: 0 .. s - 1) and
    ``streams`` = (block_length, clean_from) is the two-stream block mask
    of a stream [x_t ; x_0]: the logits are then of the noised half alone,
    [b, clean_from, vocab]. A looped model (``loop_steps`` U > 1) returns
    (logits [U, b, s, vocab] of every pass, exit-gate scores [U - 1, b, s]
    float32 before their sigmoid — the last pass takes what mass is left —,
    counters {a layer's: [L], the loop's: [U]})."""
    vocab_size: int            # rows of the vocabulary held here
    d_model: int
    num_layers: int
    heads: int
    kv_heads: int
    head_dim: int
    num_experts: int
    experts_held: int
    first_expert: int
    experts_per_token: int
    expert_width: int
    rope_layout: Sequence[int]      # per layer: 1 = rotary, 0 = none
    window_layout: Sequence[int]    # per layer: 1 = window, 0 = full
    window: int
    rope_theta: float
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False
    use_flash: Optional[bool] = None
    select_layout: Sequence[int] = ()   # per layer: 1 = learned selection
    select_topk: int = 0
    index_heads: int = 0
    index_dim: int = 0
    router_input: str = "attn_norm"
    expert_activation: str = "relu"
    qk_norm: bool = False
    index_loss_weight: float = 1.0
    block_length: int = 0           # > 0: trained by diffusion over blocks
    #: per layer: 0 = attention, 1 = gated delta rule, 2 = Mamba-2, 3 = Kimi
    #: Delta Attention, 4 = gated short convolution
    mixer_layout: Sequence[int] = ()
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_head_dim: int = 0
    conv_width: int = 4
    attn_gate: bool = False
    rotary_dim: Optional[int] = None
    zero_centered_norm: bool = False
    shared_expert_width: int = 0
    dense_width: int = 0            # with experts_held 0: a dense layer's
    sandwich_norm: bool = False
    loop_steps: int = 1             # > 1: the stack run that often, looped
    exit_entropy_weight: float = 0.05   # beta of the looped model's loss
    dense_layout: Sequence[int] = ()    # per layer: 1 = dense, no experts
    latent_dim: int = 0             # > 0: multi-head latent attention
    rope_head_dim: int = 0
    v_head_dim: int = 0
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0
    router_norm_eps: float = 1e-20
    shared_expert_gate: bool = True
    #: per layer: 0 = a mixer and a feed-forward part, 1 = the mixer alone,
    #: 2 = the feed-forward part alone
    part_layout: Sequence[int] = ()
    expert_gated: bool = True       # False: ungated experts, two matrices
    ssm_heads: int = 0              # a Mamba-2 layer's heads held here
    ssm_head_dim: int = 0
    ssm_groups: int = 0
    ssm_state: int = 0
    ssm_chunk: int = ssd.CHUNK
    ssm_first_head: int = 0
    kda_heads: int = 0              # a Kimi-Delta-Attention layer's, held here
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    tie_embeddings: bool = False    # the head reads the embedding's rows

    def parts(self):
        """Per layer: "both", "mixer" or "ffn" (``part_layout``)."""
        layout = tuple(self.part_layout) + (0,) * self.num_layers
        return tuple(("both", "mixer", "ffn")[flag]
                     for flag in layout[:self.num_layers])

    def mixers(self):
        """Per layer: its mixer's kind (``mixer_layout``); of a layer that
        is a feed-forward part alone, the kind it does not have."""
        layout = tuple(self.mixer_layout) + (0,) * self.num_layers
        return tuple(("attention", "gated_delta", "mamba2", "kda",
                      "shortconv")[flag]
                     for flag in layout[:self.num_layers])

    def dense_layers(self):
        """Per layer: whether its feed-forward part is dense (no experts,
        no shared expert, no router)."""
        layout = tuple(self.dense_layout) + (0,) * self.num_layers
        return tuple(bool(flag) or self.experts_held == 0
                     for flag in layout[:self.num_layers])

    def counted(self):
        """What `counter_names` and `init_counters` ask of a model."""
        return dict(selects=self.selects(),
                    gated_delta=any(self.gated_delta_layers()),
                    ssd=any(self.mamba_layers()),
                    kda=any(self.kda_layers()),
                    shortconv=any(self.short_conv_layers()),
                    routed=self.experts_held > 0,
                    scored=self.experts_held > 0
                    and self.router_scoring == "sigmoid")

    def gated_delta_layers(self):
        """Per layer: whether its mixer is the gated delta rule."""
        return tuple(part != "ffn" and mixer == "gated_delta"
                     for part, mixer in zip(self.parts(), self.mixers()))

    def mamba_layers(self):
        """Per layer: whether its mixer is a Mamba-2 state-space mixer."""
        return tuple(part != "ffn" and mixer == "mamba2"
                     for part, mixer in zip(self.parts(), self.mixers()))

    def kda_layers(self):
        """Per layer: whether its mixer is Kimi Delta Attention."""
        return tuple(part != "ffn" and mixer == "kda"
                     for part, mixer in zip(self.parts(), self.mixers()))

    def short_conv_layers(self):
        """Per layer: whether its mixer is a gated short convolution."""
        return tuple(part != "ffn" and mixer == "shortconv"
                     for part, mixer in zip(self.parts(), self.mixers()))

    def select_layers(self):
        """Per layer: whether it reads a learned selection."""
        layout = tuple(self.select_layout) + (0,) * self.num_layers
        return tuple(bool(flag) for flag in layout[:self.num_layers])

    def selects(self):
        return any(self.select_layers())

    def _stack(self, x, positions, streams):
        """The ``num_layers`` layers, each once: (x, its layers' counters)."""
        layer_cls = (nn.remat(
            SparseDecoderLayer,
            policy=jax.checkpoint_policies.save_only_these_names(
                *SAVED_UNDER_REMAT))
            if self.remat else SparseDecoderLayer)
        # a model that counts its positions calls its layers as it did
        positions_arg = () if positions is None else (positions,)
        per_layer = []
        linear = self.gated_delta_layers()
        state_space = self.mamba_layers()
        delta = self.kda_layers()
        conv = self.short_conv_layers()
        parts, mixers = self.parts(), self.mixers()
        dense = self.dense_layers()
        counted = self.counted()
        routing = counter_names(routed=counted["routed"],
                                scored=counted["scored"])
        for i, select in enumerate(self.select_layers()):
            x, counters = layer_cls(
                heads=self.heads, kv_heads=self.kv_heads,
                head_dim=self.head_dim, num_experts=self.num_experts,
                experts_held=0 if dense[i] else self.experts_held,
                first_expert=self.first_expert,
                experts_per_token=self.experts_per_token,
                expert_width=self.expert_width,
                use_rope=bool(self.rope_layout[i]),
                rope_theta=self.rope_theta,
                window=self.window if self.window_layout[i] else None,
                eps=self.eps, dtype=self.dtype, use_flash=self.use_flash,
                select_topk=self.select_topk if select else None,
                index_heads=self.index_heads, index_dim=self.index_dim,
                router_input=self.router_input,
                expert_activation=self.expert_activation,
                qk_norm=self.qk_norm and not conv[i], streams=streams,
                mixer=mixers[i],
                gdn_key_heads=self.gdn_key_heads,
                gdn_value_heads=self.gdn_value_heads,
                gdn_head_dim=self.gdn_head_dim, conv_width=self.conv_width,
                attn_gate=self.attn_gate and not conv[i],
                rotary_dim=self.rotary_dim,
                zero_centered_norm=self.zero_centered_norm,
                shared_expert_width=0 if dense[i]
                else self.shared_expert_width,
                dense_width=self.dense_width,
                sandwich_norm=self.sandwich_norm,
                latent_dim=0 if conv[i] else self.latent_dim,
                rope_head_dim=self.rope_head_dim,
                v_head_dim=self.v_head_dim,
                router_scoring=self.router_scoring,
                routed_scaling=self.routed_scaling,
                router_norm_eps=self.router_norm_eps,
                shared_expert_gate=self.shared_expert_gate,
                parts=parts[i], expert_gated=self.expert_gated,
                ssm_heads=self.ssm_heads, ssm_head_dim=self.ssm_head_dim,
                ssm_groups=self.ssm_groups, ssm_state=self.ssm_state,
                ssm_chunk=self.ssm_chunk,
                ssm_first_head=self.ssm_first_head,
                kda_heads=self.kda_heads, kda_head_dim=self.kda_head_dim,
                kda_gate_rank=self.kda_gate_rank,
                name="layer_%d" % i)(x, *positions_arg)
            # a layer without the part counts zeros beside those with it
            if dense[i] or parts[i] == "mixer":
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in routing})
            if self.selects() and not select:
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in SELECT_COUNTERS})
            if any(linear) and not linear[i]:
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in GATED_DELTA_COUNTERS})
            if any(state_space) and not state_space[i]:
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in SSD_COUNTERS})
            if any(delta) and not delta[i]:
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in KDA_COUNTERS})
            if any(conv) and not conv[i]:
                counters = dict(counters, **{n: jnp.zeros((), jnp.float32)
                                             for n in SHORTCONV_COUNTERS})
            per_layer.append(counters)
        return x, per_layer

    def _head(self, x, streams=None, embed=None):
        """The final norm and the head — a matrix of its own, or ``embed``
        [rows held, d_model] where the model ties them: float32 logits."""
        with jax.named_scope("norm"):
            x = RMSNorm(self.eps, self.zero_centered_norm,
                        name="norm_final")(x)
        with jax.named_scope("loss.block_diffusion" if streams
                             else "lm_head"):
            if embed is not None:
                return x, jnp.einsum("bsd,vd->bsv", x,
                                     embed.astype(self.dtype),
                                     preferred_element_type=jnp.float32)
            head = self.param("lm_head", _init(),
                              (self.d_model, self.vocab_size), jnp.float32)
            return x, jnp.einsum("bsd,dv->bsv", x, head.astype(self.dtype),
                                 preferred_element_type=jnp.float32)

    def _looped(self, x, positions):
        """The stack ``loop_steps`` times over with the same parameters, as
        ONE scan over passes: the parameters are closed over (float32; a
        layer casts them inside the pass, so the passes' contributions to
        a weight's gradient are added in float32), a pass carries its
        normed result into the next and yields its logits, its exit-gate
        score and the root mean square of its stream before that norm."""
        def one_pass(mdl, x, _):
            z, per_layer = mdl._stack(x, positions, None)
            rms = jnp.sqrt(jnp.mean(jnp.square(z.astype(jnp.float32))))
            x, logits = mdl._head(z)
            with jax.named_scope("loop.exit_gate"):
                score = jnp.einsum(
                    "bsd,d->bs", x.astype(jnp.float32),
                    mdl.param("exit_gate", _init(), (mdl.d_model,),
                              jnp.float32)) + mdl.param(
                    "exit_gate_bias", nn.initializers.zeros, (1,),
                    jnp.float32)
            return x, (logits, score, rms, per_layer)

        # round the scan, not inside its body: the loop's own operations
        # (the passes' saved values sliced and laid down) carry it too
        with jax.named_scope("loop.pass"):
            _, (logits, scores, rms, per_layer) = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=self.loop_steps)(
                    self, x, None)
        # a layer's counters come out [passes] each: one number a step
        per_layer = [{n: functools.reduce(_RUNNING.get(n, jnp.add), c[n])
                      for n in c} for c in per_layer]
        return logits, scores[:-1], per_layer, {"loop_stream_rms_max": rms}

    @nn.compact
    def __call__(self, ids, positions=None, streams=None):
        embed = self.param("embed", _init(), (self.vocab_size, self.d_model),
                           jnp.float32)
        with jax.named_scope("embed"):
            x = jnp.take(embed, ids, axis=0).astype(self.dtype)
        names = counter_names(block_diffusion=bool(streams),
                              **self.counted())
        stacked = lambda per_layer: {
            n: jnp.stack([c[n] for c in per_layer]) for n in names}
        if self.loop_steps > 1:
            if streams or self.selects() or self.tie_embeddings:
                raise ValueError("a looped model takes no block mask, no "
                                 "learned selection and no tied head")
            logits, scores, per_layer, loop = self._looped(x, positions)
            return logits, scores, dict(stacked(per_layer), **loop)
        x, per_layer = self._stack(x, positions, streams)
        if streams:                 # the clean half fed keys and values
            x = x[:, :streams[1]]
        return self._head(x, streams, embed if self.tie_embeddings
                          else None)[1], stacked(per_layer)


def counter_names(selects=False, block_diffusion=False, gated_delta=False,
                  routed=True, scored=False, ssd=False, kda=False,
                  shortconv=False):
    """The per-layer counters of a model: the routing's (none where no
    layer holds an expert; a sigmoid router's two with them) and, by what
    the model does, the selection's, the two-stream attention's, the
    gated delta rule's, the SSD scan's, Kimi Delta Attention's or the
    gated short convolution's."""
    return ((COUNTERS if routed else ())
            + (ROUTE_COUNTERS if scored else ())
            + (SELECT_COUNTERS if selects else ())
            + (BLOCK_DIFFUSION_COUNTERS if block_diffusion else ())
            + (GATED_DELTA_COUNTERS if gated_delta else ())
            + (SSD_COUNTERS if ssd else ())
            + (KDA_COUNTERS if kda else ())
            + (SHORTCONV_COUNTERS if shortconv else ()))


def init_counters(num_layers, selects=False, block_diffusion=False,
                  gated_delta=False, routed=True, loop_steps=1,
                  scored=False, ssd=False, kda=False, shortconv=False):
    """The counters a trainer carries in its extra state: ``{"counters":
    {name: [L] float32, "steps": scalar}}`` — the routing's and, for a
    model with a selecting layer, the selection's; for one trained by
    diffusion over blocks, the attention's pairs and the scalar
    ``loss_tokens``; for one with gated-delta-rule, Mamba-2 or
    Kimi-Delta-Attention layers, the scan's two (from zero: a log decay is
    never positive, a size never
    negative); for one with gated-short-convolution layers, the gate's
    maximum; for a looped one, ``LOOP_COUNTERS``, ``[loop_steps]`` each."""
    # one buffer each: the trainer donates its state to the step
    scalars = ("steps",) + (("loss_tokens",) if block_diffusion else ())
    per_pass = LOOP_COUNTERS if loop_steps > 1 else ()
    return {"counters": dict(
        {n: jnp.zeros((num_layers,), jnp.float32)
         for n in counter_names(selects, block_diffusion, gated_delta,
                                routed, scored, ssd, kda, shortconv)},
        **{n: jnp.zeros((loop_steps,), jnp.float32) for n in per_pass},
        **{n: jnp.zeros((), jnp.float32) for n in scalars})}


def accumulate_counters(extra, step_counters):
    old = extra["counters"]
    new = {n: _RUNNING.get(n, jnp.add)(old[n], step_counters[n])
           for n in step_counters}
    new["steps"] = old["steps"] + 1.0
    return dict(extra, counters=new)


def block_diffusion_noise(ids, key, block_length, mask_token_id, t_min=1e-3):
    """The input side of training by diffusion over blocks, for a pipeline
    to call under jit: ids [rows, T] -> (noisy_ids [rows, T], loss_weight
    [rows, T] float32). One t ~ U[t_min, 1] a (row, block); each token of
    the block becomes ``mask_token_id`` independently with probability t
    (linear schedule, absorbing state); the weight of a masked position is
    1 / t of its block, of any other 0."""
    rows, t_len = ids.shape
    key_t, key_m = jax.random.split(key)
    t = jax.random.uniform(key_t, (rows, t_len // block_length), jnp.float32,
                           t_min, 1.0)
    t = jnp.repeat(t, block_length, axis=1)
    masked = jax.random.uniform(key_m, (rows, t_len), jnp.float32) < t
    return (jnp.where(masked, jnp.int32(mask_token_id), ids),
            jnp.where(masked, 1.0 / t, 0.0))


def _block_diffusion_loss(model, params, batch):
    """(loss, step counters): the stream [x_t ; x_0] with both copies of
    token i at position i, the head on the noised half, and the mean over
    rows x T of weight * cross-entropy against the position's OWN clean
    token (no shift: every row of the logits against its own id)."""
    ids, noisy = batch["input_ids"], batch["noisy_ids"]
    t_len = ids.shape[1]
    logits, counters = model.apply(
        {"params": params}, jnp.concatenate([noisy, ids], axis=1),
        jnp.tile(jnp.arange(t_len), 2), (model.block_length, t_len))
    with jax.named_scope("loss.block_diffusion"):
        weight = batch["loss_weight"].astype(jnp.float32)
        loss = jnp.mean(weight * token_cross_entropy(logits, ids))
        counters = dict(counters,
                        loss_tokens=jnp.sum(weight > 0).astype(jnp.float32))
    return loss, counters


def _exit_expectation_loss(model, params, batch):
    """(loss, step counters) of a looped model: with l_i(u) the next-token
    cross-entropy of pass u at token i and lambda_i(u) the sigmoid of its
    exit-gate score, the exit distribution is p_i(u) = lambda_i(u) x the
    product over j < u of (1 - lambda_i(j)), the LAST pass taking what mass
    is left; the loss is the mean over the predicted tokens of
    sum_u p_i(u) l_i(u) - beta H(p_i) (arXiv:2510.25741's first-stage
    objective under a uniform prior over exits), worked out from log p so
    that a gate near 0 or 1 gives no log of zero. The shift is in the
    TARGETS: every row of the four passes' logits is read where it lies,
    and the row with nothing to predict leaves the [passes, B, T] result."""
    ids = batch["input_ids"]
    logits, scores, counters = model.apply({"params": params}, ids)
    with jax.named_scope("loss.exit_expectation"):
        ce = token_cross_entropy(logits, jnp.broadcast_to(
            next_ids(ids), logits.shape[:-1]))[..., :-1]
        scores = scores[:, :, :-1]      # of the positions that predict
        stay = jnp.cumsum(jax.nn.log_sigmoid(-scores), axis=0)
        log_p = jnp.concatenate([
            jax.nn.log_sigmoid(scores[:1]),
            jax.nn.log_sigmoid(scores[1:]) + stay[:-1], stay[-1:]], axis=0)
        p = jnp.exp(log_p)
        loss = jnp.mean(jnp.sum(
            p * (ce + model.exit_entropy_weight * log_p), axis=0))
        counters = dict(counters, loop_exit_mass=p.mean(axis=(1, 2)),
                        loop_pass_loss=ce.mean(axis=(1, 2)))
    return loss, counters


def create_model_and_loss(model, dummy_batch=1, dummy_seq=16):
    """(model, params, extra_state, loss_fn) for ElasticTrainer with
    ``has_aux=True``: next-token cross-entropy over batch["input_ids"]
    (shift inside, of the TARGETS: the logits are read whole and the last
    row's loss is dropped) and, where a layer selects its keys,
    ``index_loss_weight`` times the mean over those layers of their index
    loss; for a model with a
    ``block_length``, the block-diffusion loss over batch["input_ids"]
    (clean), batch["noisy_ids"] and batch["loss_weight"] (m / t, float32:
    :func:`block_diffusion_noise`); for a looped model, the expectation of
    the passes' losses under its exit distribution
    (:func:`_exit_expectation_loss`). The extra state carries the model's
    counters on the device (``trainer.extra_state["counters"]``), which the
    trainer mirrors into obs.metrics where it synchronises anyway."""
    dummy = jnp.zeros((dummy_batch, dummy_seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), dummy)["params"]
    block_diffusion = model.block_length > 0
    if block_diffusion and model.selects():
        raise ValueError("diffusion over blocks takes no learned selection")
    own_loss = (_block_diffusion_loss if block_diffusion else
                _exit_expectation_loss if model.loop_steps > 1 else None)

    def loss_fn(params, extra, batch, rng):
        if own_loss:
            loss, counters = own_loss(model, params, batch)
            return loss, accumulate_counters(
                extra, jax.lax.stop_gradient(counters))
        ids = batch["input_ids"]
        logits, counters = model.apply({"params": params}, ids)
        with jax.named_scope("loss.next_token"):
            loss = token_cross_entropy(
                logits, next_ids(ids))[:, :-1].mean()
            if model.selects():
                loss = loss + model.index_loss_weight * (
                    counters["index_loss"].sum()
                    / sum(model.select_layers()))
        return loss, accumulate_counters(
            extra, jax.lax.stop_gradient(counters))

    return (model, params, init_counters(
        model.num_layers, block_diffusion=block_diffusion,
        loop_steps=model.loop_steps, **model.counted()), loss_fn)
