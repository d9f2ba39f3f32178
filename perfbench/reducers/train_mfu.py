"""Model FLOP/s utilisation, %: operations the forward and backward passes
need per token (harness/flops.py) times the run's tokens per second, over
chips times the bf16 peak."""
from perfbench.harness import flops as F
from perfbench.harness.model import reference


def reduce(ctx):
    cfg, values = ctx["job"].config, ctx["values"]
    if "train_tok_s" not in values:
        return None
    per_token = F.train_flops_per_token(
        reference().n_params(cfg), cfg["n_positions"] * cfg["n_embd"],
        cfg["n_layer"], cfg["n_embd"], values["seq_len"])
    return 100.0 * per_token * values["train_tok_s"] / (
        values["chips"] * ctx["peaks"]()["bf16_flops_per_s"])
