"""The paged-attention decode launch against its roofline where only SOME
layers hold K/V (the others keep a few rows a slot in the cache's state
store and launch nothing). The launch is HBM-bound: a decode row reads K and
V of every stored KV row over the pages of its context once, on the layers
of ``layers_run`` whose type is ``full_attention`` and on no other; the least
time is those bytes over the chip's memory bandwidth. Bytes are the STORED
ones (``kv_row``: the pool keeps ``heads_a_row`` heads side by side in a row
of ``stored_width`` columns, ``stored_heads`` rows a position and plane).
They come from the context lengths of the rows in flight in the traced
decode-only steps (the job's loop keeps them, ``decode_lens``); time is the
device time of the launches that ``pattern`` names (the decode-shaped ones:
one query a tile)."""
from benchmark import xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4, "int8": 1}


def kv_layers(config) -> int:
    """Layers run here that hold K/V."""
    return sum(config["layer_types"][i] == "full_attention"
               for i in config["layers_run"])


def page_bytes(config) -> int:
    """K and V of one page of one K/V layer, as the pool stores them."""
    row = config["kv_row"]
    return (2 * row["stored_heads"] * config["engine"]["block_size"]
            * row["stored_width"] * ITEMSIZE[config["engine"]["kv_dtype"]])


def row_bytes(config, length: int) -> int:
    """What a decode row at context ``length`` must read, over the K/V
    layers run here."""
    block = config["engine"]["block_size"]
    return kv_layers(config) * -(-length // block) * page_bytes(config)


def read(run, pattern):
    trace, config = run.get("trace"), run["config"]
    if not trace or "kv_row" not in config:
        return None
    seconds = xplane.op_seconds(trace, pattern)
    lens = [n for s in run["steps"][:trace["steps"]]
            if not s.prefill_tokens
            for n in (getattr(s, "decode_lens", None) or ())]
    if not seconds or not lens:
        return None
    least = sum(row_bytes(config, n) for n in lens) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
