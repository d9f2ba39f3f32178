"""model_type `lfm2_moe`: the program's Lfm2MoeModel
(paddle_tpu.text.models) through its public constructor, at the sizes of a
configuration written in the keys of the Hugging Face config.json of
LiquidAI/LFM2-8B-A1B (`layer_types` 0-based, one entry a layer).  The
configuration's `num_experts` are the experts held here, out of
`num_experts_published`, from `experts_held_start` on."""
from __future__ import annotations


def construct(config):
    from paddle_tpu.text.models import Lfm2MoeModel

    kinds = list(config["layer_types"])
    assert len(kinds) == config["num_hidden_layers"]
    assert not config["conv_bias"] and config["use_expert_bias"]
    assert config.get("tie_word_embeddings", True)
    assert config["hidden_size"] % config["num_attention_heads"] == 0
    return Lfm2MoeModel(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_kinds=kinds, num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts_published=config["num_experts_published"],
        experts_held=(config.get("experts_held_start", 0),
                      config["num_experts"]),
        experts_per_token=config["num_experts_per_tok"],
        routed_scale=config["routed_scaling_factor"],
        renormalize=config["norm_topk_prob"],
        first_dense=config["num_dense_layers"],
        conv_size=config["conv_L_cache"], rope_theta=config["rope_theta"],
        epsilon=config["norm_eps"],
        recompute=bool(config.get("training", {}).get("recompute")))
