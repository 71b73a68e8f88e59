"""The paged-attention decode launch against its roofline. The launch is
HBM-bound: a decode row reads every real page of its context once (K and V,
all heads) and computes two FLOPs a byte, so the least time is bytes over the
chip's memory bandwidth. Bytes come from the lengths of the rows in flight in
the traced decode-only steps; time is the device time of the launches that
``pattern`` names (the decode-shaped ones: one query row a tile)."""
from benchmark import xplane


def kv_bytes_per_page(config) -> int:
    """K and V of one page, all heads and layers, as the pool stores them."""
    width = {"bfloat16": 2, "float32": 4, "int8": 1}[
        config["engine"]["kv_dtype"]]
    return (2 * config["n_heads"] * config["engine"]["block_size"]
            * config["d_head"] * width * config["n_layers"])


def read(run, pattern):
    trace = run.get("trace")
    if not trace:
        return None
    seconds = xplane.op_seconds(trace, pattern)
    pages = sum(s.decode_pages for s in run["steps"][:trace["steps"]]
                if not s.prefill_tokens)
    if not seconds or not pages:
        return None
    least = pages * kv_bytes_per_page(run["config"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
