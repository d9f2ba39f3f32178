"""Operations and bytes of paged attention over a ragged batch — what the
ALGORITHM needs for one layer's call, from the counts the engine attaches
to its `serving/ragged_step` span, never what a kernel chooses to move
(whole pools, padded heads, junk rows): the share reads the same whatever
implements attention, and cannot pass 100%."""
from __future__ import annotations

KV_ITEM_BYTES = {"int8": 1, "bfloat16": 2, "float16": 2, "float32": 4}


def kv_item_bytes(config):
    """Bytes of one cached K or V element as the configuration serves it:
    `kv_cache_dtype` where it states one, else the model's float32."""
    serving = config.get("serving", {}).get("enable_serving", {})
    return KV_ITEM_BYTES[serving.get("kv_cache_dtype") or "float32"]


def paged_attention(attn_pairs, ctx_tokens, rows, heads, head_dim,
                    item_bytes):
    """One layer, one step.  `attn_pairs`: (query row, key position)
    pairs attended — QK^T and PV are 2 * head_dim operations each per
    pair and head.  `ctx_tokens`: KV positions the lanes read — K and V
    once each.  `rows`: query rows that carry a token — q read, o
    written."""
    flops = 4 * heads * head_dim * attn_pairs
    nbytes = (2 * ctx_tokens + 2 * rows) * heads * head_dim * item_bytes
    return flops, nbytes
