"""The paged-attention decode launch over a LATENT cache against its
roofline. A decode row at context ``len`` must read the one cached row of
every position once (the STORED bytes of the pages of ``len`` positions:
the row's padding to whole lane tiles is stored and read, ``latent_row``)
and score it against every query head: ``len x heads x (row columns +
value columns) x 2`` FLOPs, the key product over the row's 576 columns and
the value product over its leading 512. The least time of a launch is the larger of the
bytes over the chip's memory bandwidth and the FLOPs over its matmul peak,
summed over the layers run here; the share is that over the device time of
the launches ``pattern`` names (the decode-shaped ones: one query a tile).
Rows come from the context lengths of the rows in flight in the traced
decode-only steps (the job's loop keeps them, ``decode_lens``); widths from
the configuration, whatever implements the launch."""
from benchmark import xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def row_width(config) -> int:
    """Columns of a cached row that carry numbers."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def page_bytes(config) -> int:
    """One page of one layer, as the pool stores it."""
    return (config["engine"]["block_size"] * config["latent_row"]["stored"]
            * ITEMSIZE[config["engine"]["kv_dtype"]])


def row_flops(config, length: int) -> int:
    """One decode row at context ``length``, one layer."""
    return (length * config["num_attention_heads"]
            * (row_width(config) + config["kv_lora_rank"]) * 2)


def least_seconds(config, peaks, lens) -> float:
    """The launches of one decode step a layer, over the layers run here,
    for rows at contexts ``lens``."""
    block, layers = config["engine"]["block_size"], len(config["layers_run"])
    moved = sum(-(-n // block) for n in lens) * page_bytes(config)
    flops = sum(row_flops(config, n) for n in lens)
    return layers * max(moved / peaks["hbm_bytes_per_s"],
                        flops / peaks["bf16_flops"])


def read(run, pattern):
    trace, config = run.get("trace"), run["config"]
    if not trace or "kv_lora_rank" not in config:
        return None
    seconds = xplane.op_seconds(trace, pattern)
    steps = [s.decode_lens for s in run["steps"][:trace["steps"]]
             if not s.prefill_tokens and getattr(s, "decode_lens", None)]
    if not seconds or not steps:
        return None
    least = sum(least_seconds(config, run["peaks"], lens) for lens in steps)
    return 100.0 * least / seconds
