"""Model FLOP/s utilisation: the forward and backward FLOPs a token needs
(no recompute counted) times the tokens per second of the window's steps
(their own durations, so that what writing the trace costs between two steps
of a traced run is left out), over the chips' bf16 peak. An end-to-end
utilisation, not a kernel's roofline share."""


def read(run):
    c, ms = run["counters"], run["series"].get("train_step_ms")
    if not ms:
        return None
    rate = c["tokens_per_step"] * len(ms) / (sum(ms) / 1e3)
    return 100.0 * c["flops_per_token"] * rate \
        / (c["chips"] * run["peaks"]["bf16_flops"])
