"""The expert layer's grouped products against their roofline, over the
traced steps. What the products must do follows from the rows the router
sent here, whatever implements them: every expert that received a row in a
layer call has its three matrices read once (gate, up, down), every routed
row is read and written at the model's width, and a routed row costs
``6 x hidden_size x moe_intermediate_size`` FLOPs. The least time is the
larger of bytes over the chip's memory bandwidth and FLOPs over its matmul
peak; the share is that over the device time of the launches ``pattern``
names. Rows and experts come from the program's device-side counters
(``DecoderCore.moe_metrics``), scraped by the job when the profile starts
and when it stops."""
from benchmark import xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def expert_bytes(config) -> int:
    """One expert's gate, up and down matrices as stored."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * ITEMSIZE[config["weight_dtype"]])


def least_seconds(config, peaks, rows: int, experts_hit: int) -> float:
    """``rows`` routed rows over ``experts_hit`` (expert, layer call)
    pairs that received one."""
    size = ITEMSIZE[config["weight_dtype"]]
    moved = experts_hit * expert_bytes(config) \
        + rows * 2 * config["hidden_size"] * size
    flops = rows * 6 * config["hidden_size"] * config["moe_intermediate_size"]
    return max(moved / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])


def read(run, pattern):
    trace, moe = run.get("trace"), run["counters"].get("moe_traced")
    if not trace or not moe:
        return None
    seconds = xplane.op_seconds(trace, pattern)
    rows = sum(kind["rows_routed_here"] for kind in moe.values())
    hit = sum(kind["experts_hit"] for kind in moe.values())
    if not seconds or not rows:
        return None
    return 100.0 * least_seconds(run["config"], run["peaks"], rows, hit) \
        / seconds
