"""Share of the chip's peak the toy family's feed-forward kernel reaches:
its operations from the cell's shapes (``run`` carries the configuration,
the traffic and the global batch) over the summed time of the kernel's
events, found by the name its ``name=`` gave the instruction whatever its
rank among the device's operations. Nothing to read: nothing returned."""
from benchmark.flops import toy_tokens


def read(run: dict):
    t = run["trace"]
    if not t or not t["op_events"].get("toy_gated_ffn"):
        return None
    tokens = run["global_batch"] * run["traffic"]["seq_len"]
    flops = 2 * toy_tokens.ffn_macs_per_token(run["config"]["model"]) * tokens * t["steps"]
    return 100.0 * flops / (t["op_s"]["toy_gated_ffn"] * run["peaks"]["bf16_flops_per_s"])
