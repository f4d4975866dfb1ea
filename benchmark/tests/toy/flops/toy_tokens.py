"""FLOPs one training step of the toy family needs, from the configuration
and the traffic (the sequence length is the traffic's)."""


def ffn_macs_per_token(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff"]


def train_flops_per_step(config: dict, traffic: dict) -> int:
    model = config["model"]
    tokens = traffic["per_chip_batch"] * traffic["chips"] * traffic["seq_len"]
    heads = 2 * model["d_model"] * model["vocab_held"]
    return 3 * 2 * tokens * (ffn_macs_per_token(model) + heads)
