"""GPT decoder family: causal LM with KV-cache generation, TP/SP-ready.

Net-new vs the reference (its NLP scope stopped at classification
distillation — SURVEY.md §5.7 marks long-context/causal LM absent): a
decoder-only transformer for the model zoo, built on the same attention
substrate as BERT — dense causal attention by default, the Pallas flash
kernel (`edl_tpu/ops/flash_attention.py`) or ring attention over the sp
axis (`edl_tpu/parallel/ring_attention.py`) for long sequences — plus an
incremental-decode path (flax "cache" collection) so teacher-style
serving and sampling don't re-run the prefix per token.
"""

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from edl_tpu.ops.cross_entropy import next_ids, token_cross_entropy


class CausalSelfAttention(nn.Module):
    """Causal MHA with an optional single-token decode mode.

    decode=False: full-sequence causal attention via the shared
    edl_tpu.ops.attention.attention_context dispatch (dense / flash /
    ring).
    decode=True: x is [b, 1, d]; K/V are written into "cache" variables
    sized [b, max_len, h, hd] at ``decode_index`` — the ONE source of
    truth for the decode position (the same value drives the position
    embedding in Gpt), so a retried step overwrites its own slot instead
    of silently drifting — and attention runs against the prefix.
    ``decode_index`` may be a scalar (all rows at the same position, the
    ``generate`` path) or a [b] vector (each row at its OWN position —
    the slot-batched continuous-decode path in serve.decode_engine).

    prefill=True with ``prefill_offset`` set: x is a CHUNK of the prompt
    [b, C, d] whose first token sits at sequence position ``offset``;
    K/V are written into the cache at ``[offset, offset+C)`` and each
    chunk row attends the ALREADY-WRITTEN prefix ``[0, offset+i]`` —
    the Sarathi-style chunked-prefill primitive (and the suffix-prefill
    step of shared-prefix KV reuse, where ``[0, offset)`` was copied
    from a cached row). The mask runs against the full cache like the
    decode path, so junk beyond ``offset+C`` is never attended.

    ``use_flash=None`` (default) auto-dispatches dense→flash by kernel
    legality (see ops/attention.flash_dispatch_reason); True/False still
    force a path. The pre-auto default was ``False`` — pass it
    explicitly to pin the dense path."""
    num_heads: int
    max_len: int
    dtype: Any = jnp.bfloat16
    use_ring: bool = False
    use_flash: Optional[bool] = None
    mesh: Any = None
    ring_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, decode=False, decode_index=None,
                 prefill=False, prefill_offset=None):
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        dense = lambda feats, name: nn.DenseGeneral(
            feats, dtype=self.dtype, param_dtype=jnp.float32, name=name)
        q = dense((self.num_heads, head_dim), "query")(x)
        k = dense((self.num_heads, head_dim), "key")(x)
        v = dense((self.num_heads, head_dim), "value")(x)

        if prefill:
            # ONE batched causal forward over the whole prompt that also
            # fills cache slots [0:s] — generation then decodes only the
            # new tokens instead of re-feeding the prefix one at a time
            if self.ring_axis or self.use_ring:
                # the cache layout holds the FULL sequence per device;
                # a seq-sharded prefill would fill it with local slices
                raise ValueError("prefill does not support ring "
                                 "attention (seq-sharded K/V); build the "
                                 "serving model without use_ring")
            b, s = x.shape[:2]
            ck = self.variable(
                "cache", "k", jnp.zeros,
                (b, self.max_len, self.num_heads, head_dim), self.dtype)
            cv = self.variable(
                "cache", "v", jnp.zeros,
                (b, self.max_len, self.num_heads, head_dim), self.dtype)
            if prefill_offset is not None:
                # chunked / suffix prefill: write this chunk's K/V at the
                # offset and attend the full cache under the shifted
                # causal mask — chunk row i sees keys [0, off+i], i.e.
                # the already-written prefix plus its own chunk prefix.
                # Same dense-masked numeric class as the decode path
                # (f32 scores, -1e30 mask), so junk beyond off+s — rows
                # are reused without zeroing — is never attended.
                off = jnp.asarray(prefill_offset, jnp.int32)
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(self.dtype), (0, off, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(self.dtype), (0, off, 0, 0))
                key_pos = jnp.arange(self.max_len)[None, None, None, :]
                q_pos = (off + jnp.arange(s))[None, None, :, None]
                mask = key_pos <= q_pos
                scale = head_dim ** -0.5
                scores = jnp.einsum(
                    "bqhd,bkhd->bhqk", (q * scale).astype(jnp.float32),
                    ck.value.astype(jnp.float32))
                scores = jnp.where(mask, scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", probs,
                                 cv.value.astype(jnp.float32))
                ctx = ctx.astype(self.dtype)
            else:
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(self.dtype), (0, 0, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(self.dtype), (0, 0, 0, 0))
                from edl_tpu.ops.attention import attention_context
                ctx = attention_context(q, k, v, causal=True, mask=None,
                                        dtype=self.dtype,
                                        use_flash=self.use_flash)
        elif decode:
            if x.shape[1] != 1:
                raise ValueError("decode mode feeds one token at a time")
            if decode_index is None:
                raise ValueError("decode mode needs decode_index")
            b = x.shape[0]
            ck = self.variable(
                "cache", "k", jnp.zeros,
                (b, self.max_len, self.num_heads, head_dim), self.dtype)
            cv = self.variable(
                "cache", "v", jnp.zeros,
                (b, self.max_len, self.num_heads, head_dim), self.dtype)
            idx = jnp.asarray(decode_index, jnp.int32)
            if idx.ndim == 0:
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(self.dtype), (0, idx, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(self.dtype), (0, idx, 0, 0))
                mask = (jnp.arange(self.max_len)[None, None, None, :]
                        <= idx)
            else:
                # vector decode_index: one position PER ROW, the slot
                # layout of the continuous-batching engine — every slot
                # advances through its own sequence independently inside
                # ONE fixed-shape step (scatter write + per-row prefix
                # mask; no recompile as slot membership churns)
                if idx.shape != (b,):
                    raise ValueError(
                        "vector decode_index must be [batch]=%d, got %s"
                        % (b, idx.shape))
                rows = jnp.arange(b)
                ck.value = ck.value.at[rows, idx].set(
                    k[:, 0].astype(self.dtype))
                cv.value = cv.value.at[rows, idx].set(
                    v[:, 0].astype(self.dtype))
                mask = (jnp.arange(self.max_len)[None, None, None, :]
                        <= idx[:, None, None, None])
            scale = head_dim ** -0.5
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", (q * scale).astype(jnp.float32),
                ck.value.astype(jnp.float32))
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs,
                             cv.value.astype(jnp.float32))
            ctx = ctx.astype(self.dtype)
        else:
            from edl_tpu.ops.attention import attention_context
            ctx = attention_context(
                q, k, v, causal=True, mask=None, dtype=self.dtype,
                ring_axis=self.ring_axis, use_ring=self.use_ring,
                use_flash=self.use_flash, mesh=self.mesh)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               param_dtype=jnp.float32, name="out")(ctx)


class GptBlock(nn.Module):
    """Pre-LN decoder block: x + attn(ln(x)); x + mlp(ln(x)).

    ``use_flash``: None = auto (flash where legal on TPU), True/False
    force; was ``False`` before the auto default."""
    num_heads: int
    mlp_dim: int
    max_len: int
    dtype: Any = jnp.bfloat16
    use_ring: bool = False
    use_flash: Optional[bool] = None
    mesh: Any = None
    ring_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, decode=False, decode_index=None,
                 prefill=False, prefill_offset=None):
        # device-side scopes: obs/devtime.py reads them from a profile
        with jax.named_scope("norm"):
            h = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="ln_attn")(x)
        with jax.named_scope("attn.full"):
            x = x + CausalSelfAttention(
                self.num_heads, self.max_len, self.dtype, self.use_ring,
                self.use_flash, self.mesh, ring_axis=self.ring_axis,
                name="attention")(h, decode=decode,
                                  decode_index=decode_index,
                                  prefill=prefill,
                                  prefill_offset=prefill_offset)
        with jax.named_scope("norm"):
            h = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="ln_mlp")(x)
        with jax.named_scope("ffn.dense"):
            h = nn.Dense(self.mlp_dim, dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp_up")(h)
            h = nn.gelu(h)
            h = nn.Dense(x.shape[-1], dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp_down")(h)
            return x + h


class Gpt(nn.Module):
    """Decoder-only causal LM; logits via the tied word embedding.

    ``use_flash``: None = auto-dispatch (Pallas flash on TPU when the
    shape is kernel-legal, dense otherwise — numerics-gated vs dense in
    tier-1), True = force flash, False = force dense. The default was
    ``False`` until the roofline-gap PR; explicit callers are
    unaffected."""
    vocab_size: int = 32000
    num_layers: int = 12
    d_model: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: Any = jnp.bfloat16
    use_ring: bool = False
    use_flash: Optional[bool] = None
    mesh: Any = None
    ring_axis: Optional[str] = None
    remat: bool = False

    @nn.compact
    def __call__(self, input_ids, decode=False, decode_index=None,
                 prefill=False, prefill_offset=None):
        # Embed with dtype=f32 so the tied-head attend() computes fp32
        # logits (Embed.attend promotes to its OWN dtype — a bf16 embed
        # would silently demote the logits); the activation stream is
        # cast down explicitly instead.
        embed = nn.Embed(self.vocab_size, self.d_model,
                         param_dtype=jnp.float32, dtype=jnp.float32,
                         name="word_embed")
        with jax.named_scope("embed"):
            x = embed(input_ids).astype(self.dtype)
        s = input_ids.shape[1]
        if decode:
            if decode_index is None:
                raise ValueError("decode mode needs decode_index")
            idx = jnp.asarray(decode_index, jnp.int32)
            if idx.ndim == 0:
                pos_ids = jnp.full((1, s), idx, jnp.int32)
            else:
                # per-row positions (slot-batched decode): row i sits at
                # its own sequence offset
                pos_ids = idx[:, None]
        else:
            pos_ids = jnp.arange(s)[None, :]
            if prefill and prefill_offset is not None:
                # chunk rows sit at absolute positions off..off+s-1
                pos_ids = pos_ids + jnp.asarray(prefill_offset, jnp.int32)
            if self.ring_axis:
                pos_ids = pos_ids + jax.lax.axis_index(self.ring_axis) * s
        with jax.named_scope("embed"):
            x = x + nn.Embed(self.max_len, self.d_model,
                             param_dtype=jnp.float32, dtype=self.dtype,
                             name="pos_embed")(pos_ids)
        # remat is a TRAINING lever; on the decode/prefill paths it is
        # useless AND nn.remat would trace the boolean kwargs into
        # abstract values (TracerBoolConversionError — caught by the
        # static accounting, which compiled remat=True for the first
        # time)
        use_remat = self.remat and not decode and not prefill
        block_cls = nn.remat(GptBlock) if use_remat else GptBlock
        for i in range(self.num_layers):
            block = block_cls(self.num_heads, self.mlp_dim,
                              self.max_len, self.dtype, self.use_ring,
                              self.use_flash, self.mesh,
                              ring_axis=self.ring_axis,
                              name="block_%d" % i)
            if use_remat:
                x = block(x)  # training defaults; no traced bools
            else:
                x = block(x, decode=decode, decode_index=decode_index,
                          prefill=prefill, prefill_offset=prefill_offset)
        with jax.named_scope("norm"):
            x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                             name="ln_final")(x)
        # weight-tied LM head (embed.attend = x @ embedding.T)
        with jax.named_scope("lm_head"):
            return embed.attend(x.astype(jnp.float32))


class GptEmbed(nn.Module):
    """Pipeline ``encode`` end: token ids → activations. With seq_axis
    set (in-shard sequence parallelism) each shard embeds its seq SLICE
    with shard-offset positions."""
    vocab_size: int
    d_model: int
    max_len: int
    dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, input_ids):
        s = input_ids.shape[1]
        x = nn.Embed(self.vocab_size, self.d_model,
                     param_dtype=jnp.float32, dtype=self.dtype,
                     name="word_embed")(input_ids)
        pos_ids = jnp.arange(s)[None, :]
        if self.seq_axis:
            pos_ids = pos_ids + jax.lax.axis_index(self.seq_axis) * s
        return x + nn.Embed(self.max_len, self.d_model,
                            param_dtype=jnp.float32, dtype=self.dtype,
                            name="pos_embed")(pos_ids)


class GptStage(nn.Module):
    """One pipeline stage: ``layers_per_stage`` causal blocks. ring_axis
    composes sequence parallelism INTO the stage (causal in-shard ring —
    cross-shard causality is the ring algorithm's job)."""
    layers_per_stage: int
    num_heads: int
    mlp_dim: int
    max_len: int
    dtype: Any = jnp.bfloat16
    remat: bool = False
    ring_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        block_cls = nn.remat(GptBlock) if self.remat else GptBlock
        for i in range(self.layers_per_stage):
            x = block_cls(self.num_heads, self.mlp_dim, self.max_len,
                          self.dtype, ring_axis=self.ring_axis,
                          name="block_%d" % i)(x)
        return x


class GptHead(nn.Module):
    """Pipeline ``decode`` end: final LN + (untied) LM head. The tied
    head of ``Gpt`` would couple decode params to the encode stage across
    the pipeline, so the factored form unties it."""
    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="ln_final")(x)
        return nn.Dense(self.vocab_size, dtype=jnp.float32,
                        param_dtype=jnp.float32, name="lm_head")(x)


def create_gpt_pipeline(pp, num_layers=4, d_model=64, num_heads=4,
                        mlp_dim=128, vocab_size=256, max_len=128,
                        seq_len=32, dtype=jnp.bfloat16, seed=0,
                        seq_parallel_axis=None):
    """A causal LM factored for pipeline parallelism.

    Returns (params, encode_fn, stage_fn, decode_fn, sequential_loss)
    for ``pipeline_value_and_grad`` (same contract as
    bert.create_bert_pipeline). ``y`` passed to the engine is the FULL
    [batch, seq] id tensor (replicated along seq shards); the decode end
    computes the next-token loss, and under ``seq_parallel_axis`` each
    shard slices its own global-offset targets from it and returns its
    loss CONTRIBUTION (the engine sums over seq shards). The boundary
    token between neighboring shards is handled by the global slicing —
    the last local position of shard i targets the first token of shard
    i+1."""
    if num_layers % pp != 0:
        raise ValueError("num_layers %d not divisible by pp %d"
                         % (num_layers, pp))
    spa = seq_parallel_axis
    embed = GptEmbed(vocab_size, d_model, max_len, dtype)
    stage = GptStage(num_layers // pp, num_heads, mlp_dim, max_len, dtype)
    head = GptHead(vocab_size, dtype)
    embed_sp = GptEmbed(vocab_size, d_model, max_len, dtype, seq_axis=spa)
    stage_sp = GptStage(num_layers // pp, num_heads, mlp_dim, max_len,
                        dtype, ring_axis=spa)

    root = jax.random.PRNGKey(seed)
    k_embed, k_head, *k_stages = jax.random.split(root, 2 + pp)
    ids = jnp.zeros((1, seq_len), jnp.int32)
    p_enc = embed.init(k_embed, ids)["params"]
    act = embed.apply({"params": p_enc}, ids)
    per_stage = [stage.init(k, act)["params"] for k in k_stages]
    p_stages = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage)
    p_dec = head.init(k_head, act)["params"]
    params = {"encode": p_enc, "stages": p_stages, "decode": p_dec}

    def encode_fn(p, batch_x):
        return embed_sp.apply({"params": p}, batch_x)

    def stage_fn(p, x):
        return stage_sp.apply({"params": p}, x)

    def _lm_loss(logits, y, shard_idx):
        """Loss contribution of this shard's logits [b, s_loc, V] given
        the FULL targets y [b, s_glob]: local position j predicts global
        token shard_idx*s_loc + j + 1; the final global position has no
        target and is masked. Normalized by the GLOBAL token count so
        contributions sum to the sequential mean."""
        b, s_loc = logits.shape[:2]
        s_glob = y.shape[1]
        # pad y so the last shard's slice never overruns
        y_pad = jnp.concatenate(
            [y, jnp.zeros((b, 1), y.dtype)], axis=1)
        tgt = jax.lax.dynamic_slice(
            y_pad, (0, shard_idx * s_loc + 1), (b, s_loc))
        ce = token_cross_entropy(logits.astype(jnp.float32), tgt)
        glob_pos = shard_idx * s_loc + jnp.arange(s_loc)
        valid = (glob_pos < s_glob - 1).astype(jnp.float32)
        return (ce * valid[None]).sum() / (b * (s_glob - 1))

    def decode_fn(p, x, y):
        logits = head.apply({"params": p}, x)
        if spa:
            return _lm_loss(logits, y, jax.lax.axis_index(spa))
        return _lm_loss(logits, y, 0)

    def sequential_loss(params, batch_x, y):
        x = embed.apply({"params": params["encode"]}, batch_x)
        for s_i in range(pp):
            p_s = jax.tree_util.tree_map(lambda a: a[s_i],
                                         params["stages"])
            x = stage.apply({"params": p_s}, x)
        logits = head.apply({"params": params["decode"]}, x)
        return _lm_loss(logits, y, 0)

    return params, encode_fn, stage_fn, decode_fn, sequential_loss


def gpt_partition_rules():
    """Megatron-style TP rules, same scheme as bert_partition_rules."""
    return [
        (r"attention/(query|key|value)/kernel", P(None, "tp", None)),
        (r"attention/out/kernel", P("tp", None, None)),
        (r"mlp_up/kernel", P(None, "tp")),
        (r"mlp_down/kernel", P("tp", None)),
        (r"word_embed/embedding", P("tp", None)),
    ]


def gpt_tiny(**kw):
    kw.setdefault("num_layers", 4)
    kw.setdefault("d_model", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("mlp_dim", 128)
    kw.setdefault("vocab_size", 256)
    kw.setdefault("max_len", 128)
    return Gpt(**kw)


def create_model_and_loss(model=None, dummy_batch=1, dummy_seq=16, **kw):
    """(model, params, loss_fn) for ElasticTrainer — next-token
    cross-entropy over batch["input_ids"] (shift inside, of the TARGETS:
    the logits are read whole and the last row's loss is dropped)."""
    model = model or gpt_tiny(**kw)
    dummy = jnp.zeros((dummy_batch, dummy_seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), dummy)["params"]

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        logits = model.apply({"params": params}, ids)
        # predict token t+1 from prefix <= t: no slice and no gather of the
        # [b, s, vocab] logits, and no one-hot of that size in memory
        with jax.named_scope("loss.next_token"):
            return token_cross_entropy(
                logits, next_ids(ids))[:, :-1].mean()

    return model, params, loss_fn


def init_cache(model, params, batch_size):
    """Zeroed KV caches for incremental decode. Shapes come from
    eval_shape over init — no parameter tensor is materialized, and the
    cache contents (which init would have polluted with the dummy
    token's K/V) are created as real zeros."""
    dummy = jnp.zeros((batch_size, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dummy, decode=True,
                           decode_index=jnp.zeros((), jnp.int32)))
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


def _filter_logits(logits, top_k=0, top_p=0.0):
    """Mask logits outside the sampling nucleus: keep the top_k largest
    (0 = all) and/or the smallest prefix of the sorted distribution whose
    probability mass reaches top_p (0 = all). Static shapes throughout
    (sort + mask, no dynamic gather sizes) so it scans under jit."""
    if top_k and top_k > 0:
        k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and 0.0 < top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep ranks whose PRECEDING mass is < top_p (always >= 1 token)
        keep = jnp.concatenate(
            [jnp.zeros_like(cum[..., :1]), cum[..., :-1]], axis=-1) < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def generate(model, params, prompt_ids, max_new_tokens, rng=None,
             temperature=0.0, top_k=0, top_p=0.0):
    """Autoregressive sampling with the KV cache: ONE batched prefill
    forward fills the cache over the whole prompt (no per-token prefix
    re-feeding), then a lax.scan decodes ``max_new_tokens`` (greedy at
    temperature 0; temperature > 0 samples, optionally truncated to the
    ``top_k`` largest logits and/or the ``top_p`` nucleus). Returns
    [b, prompt+new] ids."""
    b, prompt_len = prompt_ids.shape
    total = prompt_len + max_new_tokens
    if total > model.max_len:
        raise ValueError("prompt+new %d exceeds max_len %d"
                         % (total, model.max_len))
    if max_new_tokens < 1:
        return prompt_ids
    cache = init_cache(model, params, b)
    rng = rng if rng is not None else jax.random.PRNGKey(0)

    def sample(logits, feed_pos):
        if temperature > 0:
            # temperature FIRST, then the nucleus: top_p must be a mass
            # of the actual sampling distribution (the HF processor order)
            scaled = _filter_logits(logits / temperature, top_k=top_k,
                                    top_p=top_p)
            nxt = jax.random.categorical(
                jax.random.fold_in(rng, feed_pos), scaled, axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32)

    logits, muts = model.apply(
        {"params": params, "cache": cache}, prompt_ids, prefill=True,
        mutable=["cache"])
    cache = muts["cache"]
    first = sample(logits[:, -1], prompt_len - 1)
    seq0 = jnp.concatenate(
        [prompt_ids, first[:, None],
         jnp.zeros((b, max_new_tokens - 1), jnp.int32)], axis=1)

    def step(carry, t):
        cache, seq, tok = carry
        logits, muts = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            decode=True, decode_index=t, mutable=["cache"])
        nxt = sample(logits[:, 0], t)
        seq = jax.lax.dynamic_update_slice(seq, nxt[:, None], (0, t + 1))
        return (muts["cache"], seq, nxt), None

    # feed positions prompt_len..total-2; position t produces token t+1
    (_, seq, _), _ = jax.lax.scan(
        step, (cache, seq0, first),
        jnp.arange(prompt_len, total - 1))
    return seq


def synthetic_lm_batch(batch_size, seq_len=32, vocab_size=256, seed=0):
    """Learnable synthetic stream: arithmetic sequences mod vocab (each
    next token is prev + step, a pattern a causal LM can learn)."""
    rng = np.random.RandomState(seed)
    start = rng.randint(0, vocab_size, (batch_size, 1))
    step = rng.randint(1, 7, (batch_size, 1))
    pos = np.arange(seq_len)[None, :]
    ids = (start + step * pos) % vocab_size
    return {"input_ids": ids.astype(np.int32)}
