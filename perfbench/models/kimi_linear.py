"""model_type `kimi_linear`: the program's KimiLinearModel
(paddle_tpu.text.models) through its public constructor, at the sizes of a
configuration written in the keys of the Hugging Face config.json of
moonshotai/Kimi-Linear-48B-A3B-Instruct (layer tables 1-based).  The
configuration's `num_experts` are the experts held here, out of
`num_experts_published`, from `experts_held_start` on."""
from __future__ import annotations


def construct(config):
    from paddle_tpu.text.models import KimiLinearModel

    la = config["linear_attn_config"]
    layers = range(1, config["num_hidden_layers"] + 1)
    assert all((i in la["kda_layers"]) != (i in la["full_attn_layers"])
               for i in layers)
    kinds = ["kda" if i in la["kda_layers"] else "mla" for i in layers]
    assert la["num_heads"] == config["num_attention_heads"]
    assert config["num_expert_group"] == 1 and config["mla_use_nope"]
    assert config["num_shared_experts"] == 1 and not config["q_lora_rank"]
    return KimiLinearModel(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_kinds=kinds, num_heads=config["num_attention_heads"],
        kda_head_dim=la["head_dim"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts_published=config["num_experts_published"],
        experts_held=(config.get("experts_held_start", 0),
                      config["num_experts"]),
        experts_per_token=config["num_experts_per_token"],
        routed_scale=config["routed_scaling_factor"],
        renormalize=config["moe_renormalize"],
        first_dense=config["first_k_dense_replace"],
        conv_size=la["short_conv_kernel_size"],
        gate_rank=config.get("gate_low_rank_dim"),
        epsilon=config["rms_norm_eps"],
        recompute=bool(config.get("training", {}).get("recompute")))
