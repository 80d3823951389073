"""A sparse-expert decoder on the program's ``MoETransformer``: Qwen3
attention on every layer, a router and ``num_experts`` SwiGLU experts of
width ``moe_intermediate_size`` in place of the MLP, ``num_experts_per_tok``
of them active for a token. The capacity factor equals the number of
experts, so that no token is ever dropped."""
from harness import model

qwen = model.load_module("arch/qwen.py")


def program_config(cfg: dict):
    return qwen.program_config(cfg).replace(
        family="moe", d_ff=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_capacity_factor=float(cfg["num_experts"]))


def param_layout(cfg: dict) -> dict:
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    blocks = qwen.attention_blocks(cfg)
    blocks.update(router=((L, 1, D, E), D ** -0.5),
                  w_gate=((L, 1, E, D, F), D ** -0.5),
                  w_up=((L, 1, E, D, F), D ** -0.5),
                  w_down=((L, 1, E, F, D), F ** -0.5))
    return qwen.with_head(cfg, blocks)


def matmul_params_per_token(cfg: dict) -> int:
    """Attention, the router, and the active experts only."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    per_layer = (qwen.attention_matmul_params(cfg) + D * cfg["num_experts"]
                 + cfg["num_experts_per_tok"] * 3 * D * F)
    return cfg["num_hidden_layers"] * per_layer


def attention_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]
