"""The paged-attention decode launch against its roofline where layers have
sliding windows. The launch is HBM-bound: a decode row reads K and V of every
KV head over the pages of its context once, and on a sliding layer only over
the pages of the last ``sliding_window`` positions; the least time is those
bytes over the chip's memory bandwidth. Bytes come from the context lengths
of the rows in flight in the traced decode-only steps (the job's loop keeps
them, ``decode_lens``), layer by layer through the configuration's layer
types; time is the device time of the launches that ``pattern`` names (the
decode-shaped ones: one query a tile). The kernel may also read the page
the window's first position shares with older ones: uncredited."""
from benchmark import xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4, "int8": 1}


def page_bytes(config) -> int:
    """K and V of one page of one layer, as the pool stores them."""
    return (2 * config["num_key_value_heads"] * config["engine"]["block_size"]
            * config["head_dim"] * ITEMSIZE[config["engine"]["kv_dtype"]])


def row_pages(config, length: int) -> int:
    """Pages a decode row at context ``length`` must read, over the layers
    run here."""
    block = config["engine"]["block_size"]
    pages = 0
    for i in config["layers_run"]:
        seen = min(length, config["sliding_window"]) \
            if config["layer_types"][i] == "sliding_attention" else length
        pages += -(-seen // block)
    return pages


def read(run, pattern):
    trace = run.get("trace")
    if not trace or "layers_run" not in run["config"]:
        return None
    seconds = xplane.op_seconds(trace, pattern)
    lens = [n for s in run["steps"][:trace["steps"]]
            if not s.prefill_tokens
            for n in (getattr(s, "decode_lens", None) or ())]
    if not seconds or not lens:
        return None
    config = run["config"]
    least = sum(row_pages(config, n) for n in lens) * page_bytes(config) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
