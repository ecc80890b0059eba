"""Model FLOPs that the window's prefills need (the configuration's count:
causal attention, the LM head at the last position only), over the window's
length times the chip's peak bf16 FLOP/s."""


def read(run):
    u = run.record["units"]
    if not u["count"]:
        return None
    flops = u["count"] * run.model.prefill_flops(u["batch"], u["prompt_len"],
                                                run.spec)
    return 100.0 * flops / (u["window_s"] * run.peaks["flops_per_s"]
                            * run.chips)
