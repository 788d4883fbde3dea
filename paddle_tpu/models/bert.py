"""BERT family (BASELINE.json config 2: BERT-base MLM pretrain; the reference
hosts this in PaddleNLP). Encoder built from paddle_tpu.nn.TransformerEncoder
so attention rides the same flash path."""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding, Dropout
from ..nn.layer.norm import LayerNorm
from ..nn.layer.transformer import TransformerEncoder, TransformerEncoderLayer
from ..nn.layer.activation import GELU, Tanh
from ..nn import functional as F
from ..nn.initializer import Normal
from ..core.tensor import Tensor
from ..tensor import manipulation as M


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0


BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(hidden_size=1024, num_hidden_layers=24,
                        num_attention_heads=16, intermediate_size=4096)


class BertEmbeddings(Layer):
    def __init__(self, c: BertConfig):
        super().__init__()
        init = Normal(0.0, c.initializer_range)
        self.word_embeddings = Embedding(c.vocab_size, c.hidden_size, weight_attr=init)
        self.position_embeddings = Embedding(c.max_position_embeddings, c.hidden_size, weight_attr=init)
        self.token_type_embeddings = Embedding(c.type_vocab_size, c.hidden_size, weight_attr=init)
        self.layer_norm = LayerNorm(c.hidden_size, c.layer_norm_eps)
        self.dropout = Dropout(c.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        b, s = input_ids.shape
        pos = Tensor(jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is not None:
            x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertPooler(Layer):
    def __init__(self, c: BertConfig):
        super().__init__()
        self.dense = Linear(c.hidden_size, c.hidden_size)
        self.activation = Tanh()

    def forward(self, hidden_states):
        return self.activation(self.dense(hidden_states[:, 0]))


class BertModel(Layer):
    def __init__(self, config: BertConfig = None, with_pool=True):
        super().__init__()
        c = config or BertConfig()
        self.config = c
        self.embeddings = BertEmbeddings(c)
        enc_layer = TransformerEncoderLayer(
            c.hidden_size, c.num_attention_heads, c.intermediate_size,
            dropout=c.hidden_dropout_prob, activation=c.hidden_act,
            attn_dropout=c.attention_probs_dropout_prob,
            layer_norm_eps=c.layer_norm_eps,
        )
        self.encoder = TransformerEncoder(enc_layer, c.num_hidden_layers)
        self.pooler = BertPooler(c) if with_pool else None

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            # [B,S] 1/0 -> additive [B,1,1,S]
            m = attention_mask._data if isinstance(attention_mask, Tensor) else attention_mask
            mask = Tensor(((1.0 - m[:, None, None, :]) * -1e30).astype(jnp.float32))
        seq = self.encoder(x, mask)
        pooled = self.pooler(seq) if self.pooler is not None else None
        return seq, pooled


class BertLMPredictionHead(Layer):
    def __init__(self, c: BertConfig, embedding_weights=None):
        super().__init__()
        self.transform = Linear(c.hidden_size, c.hidden_size)
        self.activation = GELU()
        self.layer_norm = LayerNorm(c.hidden_size, c.layer_norm_eps)
        self.decoder_weight = embedding_weights  # tied
        self.decoder_bias = self.create_parameter([c.vocab_size], is_bias=True)

    def forward(self, x):
        x = self.layer_norm(self.activation(self.transform(x)))
        from ..tensor.math import matmul

        return matmul(x, M.t(self.decoder_weight)) + self.decoder_bias


class BertForMaskedLM(Layer):
    def __init__(self, config: BertConfig = None):
        super().__init__()
        c = config or BertConfig()
        self.config = c
        self.bert = BertModel(c, with_pool=False)
        self.cls = BertLMPredictionHead(c, self.bert.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None, labels=None):
        seq, _ = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.cls(seq)
        if labels is not None:
            loss = F.cross_entropy(
                M.reshape(logits, [-1, self.config.vocab_size]),
                M.reshape(labels, [-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits


class BertForSequenceClassification(Layer):
    def __init__(self, config: BertConfig = None, num_classes=2):
        super().__init__()
        c = config or BertConfig()
        self.bert = BertModel(c, with_pool=True)
        self.dropout = Dropout(c.hidden_dropout_prob)
        self.classifier = Linear(c.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None, labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            loss = F.cross_entropy(logits, labels)
            return loss, logits
        return logits
