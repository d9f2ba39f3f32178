"""Transformer language models (flagship models for the TPU build).

Reference analog: BERT-style encoders are built from paddle.nn.Transformer
(nn/layer/transformer.py:437 TransformerEncoderLayer) — BASELINE config 4
(BERT-base SQuAD fine-tune) uses exactly this stack.  This module provides the
assembled model the reference leaves to downstream libraries, because the
benchmark needs it.

TPU-native: parameters carry partition_spec metadata ('mp' axis on the big
matmuls — column-parallel QKV/FFN-in, row-parallel proj/FFN-out) so pjit
shards them over the mesh; attention runs through ops.attention (flash kernel
on TPU).
"""
from __future__ import annotations

import numpy as np

from .. import nn
from ..nn import functional as F
from ..tensor import Tensor


class BertEmbeddings(nn.Layer):
    def __init__(self, vocab_size, hidden_size, max_position_embeddings=512,
                 type_vocab_size=2, dropout=0.1):
        super().__init__()
        self.word_embeddings = nn.Embedding(vocab_size, hidden_size)
        self.position_embeddings = nn.Embedding(max_position_embeddings, hidden_size)
        self.token_type_embeddings = nn.Embedding(type_vocab_size, hidden_size)
        self.layer_norm = nn.LayerNorm(hidden_size)
        self.dropout = nn.Dropout(dropout)
        # shard the vocab table rows over mp
        self.word_embeddings.weight.partition_spec = ("mp", None)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from ..ops.creation import arange, zeros_like
        from ..ops.manipulation import expand

        seq = input_ids.shape[1]
        if position_ids is None:
            position_ids = arange(seq, dtype="int64")
        if token_type_ids is None:
            token_type_ids = zeros_like(input_ids, dtype="int64")
        emb = (self.word_embeddings(input_ids)
               + self.position_embeddings(position_ids)
               + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(emb))


class BertModel(nn.Layer):
    """BERT encoder (bert-base defaults)."""

    def __init__(self, vocab_size=30522, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=3072,
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 max_position_embeddings=512, type_vocab_size=2):
        super().__init__()
        self.embeddings = BertEmbeddings(vocab_size, hidden_size,
                                         max_position_embeddings,
                                         type_vocab_size, hidden_dropout_prob)
        enc_layer = nn.TransformerEncoderLayer(
            hidden_size, num_attention_heads, intermediate_size,
            dropout=hidden_dropout_prob, activation="gelu",
            attn_dropout=attention_probs_dropout_prob)
        self.encoder = nn.TransformerEncoder(enc_layer, num_hidden_layers)
        self.pooler = nn.Linear(hidden_size, hidden_size)
        self._annotate_tp()

    def _annotate_tp(self):
        """Megatron-style partition specs: QKV + FFN-in column parallel, attn
        proj + FFN-out row parallel (XLA inserts the psums under pjit)."""
        for layer in self.encoder.layers:
            attn = layer.self_attn
            for proj in (attn.q_proj, attn.k_proj, attn.v_proj):
                proj.weight.partition_spec = (None, "mp")
                proj.bias.partition_spec = ("mp",)
            attn.out_proj.weight.partition_spec = ("mp", None)
            layer.linear1.weight.partition_spec = (None, "mp")
            layer.linear1.bias.partition_spec = ("mp",)
            layer.linear2.weight.partition_spec = ("mp", None)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        # a 2D [B, S] validity mask is passed through unchanged: the
        # attention op understands it natively and can route it to the flash
        # kernel (converting to a [B,1,1,S] additive float here would force
        # the O(S²) XLA path)
        x = self.embeddings(input_ids, token_type_ids)
        x = self.encoder(x, src_mask=attention_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForSequenceClassification(nn.Layer):
    def __init__(self, bert: BertModel = None, num_classes=2, dropout=0.1,
                 **bert_kwargs):
        super().__init__()
        self.bert = bert or BertModel(**bert_kwargs)
        hidden = self.bert.pooler.weight.shape[0]
        self.dropout = nn.Dropout(dropout)
        self.classifier = nn.Linear(hidden, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        return self.classifier(self.dropout(pooled))


class BertForQuestionAnswering(nn.Layer):
    """SQuAD head (BASELINE config 4)."""

    def __init__(self, bert: BertModel = None, **bert_kwargs):
        super().__init__()
        self.bert = bert or BertModel(**bert_kwargs)
        hidden = self.bert.pooler.weight.shape[0]
        self.classifier = nn.Linear(hidden, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(seq)
        from ..ops.manipulation import split as _split

        start, end = _split(logits, 2, axis=-1)
        return start.squeeze(-1), end.squeeze(-1)


class GPTDecoderLayer(nn.Layer):
    def __init__(self, hidden, heads, ffn, dropout=0.0):
        super().__init__()
        self.ln1 = nn.LayerNorm(hidden)
        self.attn = nn.MultiHeadAttention(hidden, heads, dropout=dropout)
        self.ln2 = nn.LayerNorm(hidden)
        self.fc1 = nn.Linear(hidden, ffn)
        self.fc2 = nn.Linear(ffn, hidden)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None, causal=False):
        h = self.ln1(x)
        x = x + self.attn(h, h, h, attn_mask=mask,
                          is_causal=causal and mask is None)
        h = self.ln2(x)
        x = x + self.dropout(self.fc2(F.gelu(self.fc1(h))))
        return x


class GPTModel(nn.Layer):
    """Decoder-only causal LM — the long-context flagship (pairs with ring
    attention / context parallelism; new capability per SURVEY §5.7)."""

    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_size=3072, max_seq_len=1024, dropout=0.0):
        super().__init__()
        self.wte = nn.Embedding(vocab_size, hidden_size)
        self.wpe = nn.Embedding(max_seq_len, hidden_size)
        self.layers = nn.LayerList([
            GPTDecoderLayer(hidden_size, num_heads, ffn_size, dropout)
            for _ in range(num_layers)
        ])
        self.ln_f = nn.LayerNorm(hidden_size)
        self.wte.weight.partition_spec = ("mp", None)
        for layer in self.layers:
            attn = layer.attn
            for proj in (attn.q_proj, attn.k_proj, attn.v_proj):
                proj.weight.partition_spec = (None, "mp")
                proj.bias.partition_spec = ("mp",)
            attn.out_proj.weight.partition_spec = ("mp", None)
            layer.fc1.weight.partition_spec = (None, "mp")
            layer.fc1.bias.partition_spec = ("mp",)
            layer.fc2.weight.partition_spec = ("mp", None)

    def forward(self, input_ids):
        from ..ops.creation import arange

        B, S = input_ids.shape
        pos = arange(S, dtype="int64")
        x = self.wte(input_ids) + self.wpe(pos)
        # causal masking rides the attention op (is_causal -> the Pallas
        # flash route at S>=128), never a materialized S×S tril
        for layer in self.layers:
            x = layer(x, causal=True)
        x = self.ln_f(x)
        # weight-tied LM head
        return F.linear(x, self.wte.weight.t())

    def generate(self, input_ids, max_new_tokens=32, end_id=0,
                 decode_strategy="greedy", num_beams=4,
                 length_penalty=0.0):
        """KV-cache incremental decoding (text/generation.py — the
        fixed-shape TPU redesign of the reference's Cache +
        dynamic_decode serving path)."""
        from .generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         end_id=end_id, decode_strategy=decode_strategy,
                         num_beams=num_beams,
                         length_penalty=length_penalty)


class HybridDecoderLayer(nn.Layer):
    """x += mixer(RMSNorm(x)); x += ffn(RMSNorm(x)).  A sparse-expert ffn
    also hands back its routing counts: forward then returns (x, counts)."""

    def __init__(self, hidden_size, mixer, ffn, epsilon=1e-5):
        super().__init__()
        self.input_norm = nn.RMSNorm(hidden_size, epsilon)
        self.mixer = mixer
        self.post_norm = nn.RMSNorm(hidden_size, epsilon)
        self.ffn = ffn

    def forward(self, x):
        x = x + self.mixer(self.input_norm(x))
        h = self.ffn(self.post_norm(x))
        if isinstance(h, tuple):
            return x + h[0], h[1]
        return x + h


class _HybridDecoderLM(nn.Layer):
    """What the hybrid decoder LMs share: token embedding, a stack of
    HybridDecoderLayers — a mixer by the per-layer table `layer_kinds`, a
    dense SwiGLU FFN in the first `first_dense` layers and one chip's share
    of a sparse-expert layer in the rest — each rematerialised in the
    backward pass while training where `recompute` (fleet.recompute), a
    final RMSNorm and a head: its own Linear, or the embedding matrix
    transposed.  `vocab_size` is the rows held here: a slice of the
    vocabulary is a smaller vocabulary.

    The buffer `moe_routed_tokens` [sparse layers, count + 1] adds up, step
    by step inside the step's own buffers, the assignments routed to each
    held expert and (last column) to absent ones; hapi publishes it as the
    counter `moe.routed_tokens` (framework.monitor) without a host sync.
    It is float32 and never reset: exact to 2**24 a column and in
    proportion beyond, where an int32 would wrap within a long run."""

    step_counters = {"moe.routed_tokens": "moe_routed_tokens"}
    _family = ""        # the spans are text/<_family>/build and /forward
    _kinds = {}         # layer kind -> its count's name in the build span

    def _build(self, vocab_size, hidden_size, layer_kinds, make_mixer,
               intermediate_size, first_dense, experts, epsilon, recompute,
               tie_head):
        """`make_mixer(kind)` builds one layer's mixer; `experts` are
        nn.SparseExpertShare's arguments after the hidden size."""
        from ..utils.profiler import RecordEvent

        kinds = list(layer_kinds)
        for kind in kinds:
            if kind not in self._kinds:
                raise ValueError(f"layer kind {kind!r}: "
                                 f"{' or '.join(self._kinds)}")
        held = int(experts["experts_held"][1])

        def layer(i):
            mixer = make_mixer(kinds[i])
            ffn = nn.SwiGLU(hidden_size, intermediate_size) \
                if i < first_dense \
                else nn.SparseExpertShare(hidden_size, **experts)
            return HybridDecoderLayer(hidden_size, mixer, ffn, epsilon)

        with RecordEvent(
                f"text/{self._family}/build", layers=len(kinds),
                **{name: kinds.count(kind)
                   for kind, name in self._kinds.items()},
                experts_held=held,
                experts_published=int(experts["num_experts_published"])):
            self.recompute = recompute
            self.embed_tokens = nn.Embedding(vocab_size, hidden_size)
            self.layers = nn.LayerList([layer(i) for i in range(len(kinds))])
            self.norm = nn.RMSNorm(hidden_size, epsilon)
            self.lm_head = None if tie_head else nn.Linear(
                hidden_size, vocab_size, bias_attr=False)
            self.register_buffer("moe_routed_tokens", Tensor(np.zeros(
                (max(0, len(kinds) - first_dense), held + 1), np.float32)),
                persistable=False)

    def forward(self, input_ids):
        from ..distributed.fleet.recompute import recompute
        from ..ops.manipulation import stack
        from ..utils.profiler import RecordEvent

        B, T = input_ids.shape
        # under jit this runs once, where the step is traced
        with RecordEvent(f"text/{self._family}/forward",
                         tokens=int(B) * int(T), layers=len(self.layers)):
            x = self.embed_tokens(input_ids)
            counts = []
            for layer in self.layers:
                out = recompute(layer, x) \
                    if self.recompute and self.training else layer(x)
                if isinstance(out, (tuple, list)):
                    x = out[0]
                    counts.append(out[1])
                else:
                    x = out
            if counts:
                # in place: functional_call reads the buffer object back
                self.moe_routed_tokens._value = (
                    self.moe_routed_tokens._value
                    + stack(counts)._value)
            x = self.norm(x)
            if self.lm_head is not None:
                return self.lm_head(x)
            return F.linear(x, self.embed_tokens.weight.t())


class KimiLinearModel(_HybridDecoderLM):
    """Decoder-only LM of the Kimi-Linear family: layers of different
    kinds by a per-layer table — `layer_kinds[i]` is "kda" (gated
    delta-rule linear attention) or "mla" (NoPE latent attention) — a
    dense SwiGLU FFN in the first `first_dense` layers and one chip's share
    of a sparse-expert layer with its shared expert in the rest
    (`experts_held` = (start, count) of `num_experts_published`;
    nn.SparseExpertShare), RMSNorm, an untied head; the loop, recomputation
    and the routing counter `moe_routed_tokens` are the base class's (the
    counter's last column takes ~63,500 a step of 8,192 tokens: exact for
    33,800 steps)."""

    _family = "kimi_linear"
    _kinds = {"kda": "kda_layers", "mla": "mla_layers"}

    def __init__(self, vocab_size, hidden_size, layer_kinds, num_heads,
                 kda_head_dim, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, num_experts_published, experts_held,
                 experts_per_token, routed_scale=1.0, renormalize=True,
                 first_dense=1, conv_size=4, gate_rank=None, epsilon=1e-5,
                 recompute=False):
        super().__init__()

        def make_mixer(kind):
            if kind == "kda":
                return nn.KimiDeltaAttention(
                    hidden_size, num_heads, kda_head_dim, conv_size,
                    gate_rank, epsilon)
            return nn.LatentAttention(
                hidden_size, num_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, epsilon)

        self._build(
            vocab_size, hidden_size, layer_kinds, make_mixer,
            intermediate_size, first_dense, dict(
                expert_size=moe_intermediate_size,
                num_experts_published=num_experts_published,
                experts_held=experts_held,
                experts_per_token=experts_per_token,
                routed_scale=routed_scale, renormalize=renormalize),
            epsilon, recompute, tie_head=False)


class Lfm2MoeModel(_HybridDecoderLM):
    """Decoder-only LM of the LFM2 sparse-expert family: `layer_kinds[i]`
    is "conv" (the gated short-convolution mixer, nn.GatedShortConv) or
    "full_attention" (grouped-query attention with per-head QK-norm and
    rotary positions, nn.GroupedQueryAttention); a dense SwiGLU FFN in the
    first `first_dense` layers and one chip's share of a sparse-expert
    layer WITHOUT a shared expert in the rest (`experts_held` = (start,
    count) of `num_experts_published`); RMSNorm; the head is the embedding
    matrix transposed (one leaf, its gradient the sum of both uses).  The
    loop, recomputation and the routing counter `moe_routed_tokens` are the
    base class's."""

    _family = "lfm2_moe"
    _kinds = {"conv": "conv_layers", "full_attention": "attn_layers"}

    def __init__(self, vocab_size, hidden_size, layer_kinds, num_heads,
                 num_kv_heads, intermediate_size, moe_intermediate_size,
                 num_experts_published, experts_held, experts_per_token,
                 routed_scale=1.0, renormalize=True, first_dense=2,
                 conv_size=3, rope_theta=1000000.0, epsilon=1e-5,
                 recompute=False):
        super().__init__()

        def make_mixer(kind):
            if kind == "conv":
                return nn.GatedShortConv(hidden_size, conv_size)
            return nn.GroupedQueryAttention(
                hidden_size, num_heads, num_kv_heads, rope_theta, epsilon)

        self._build(
            vocab_size, hidden_size, layer_kinds, make_mixer,
            intermediate_size, first_dense, dict(
                expert_size=moe_intermediate_size,
                num_experts_published=num_experts_published,
                experts_held=experts_held,
                experts_per_token=experts_per_token,
                routed_scale=routed_scale, renormalize=renormalize,
                shared_expert=False),
            epsilon, recompute, tie_head=True)
