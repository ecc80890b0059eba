"""The least time the chip could take for the window's work, over the
window's length. Each call (a batch's prefill, each decode step) takes at
least the larger of its needed FLOPs over peak FLOP/s and its needed bytes
(params at their dtype, the cache read up to the position, one token's K/V
written) over peak HBM bytes/s; decode steps are bound by bytes."""


def read(run):
    u = run.record["units"]
    if not u["count"]:
        return None
    m, b, s = run.model, u["batch"], u["prompt_len"]
    fl, bw = run.peaks["flops_per_s"] * run.chips, \
        run.peaks["hbm_bytes_per_s"] * run.chips
    least = max(m.prefill_flops(b, s, run.spec) / fl, m.prefill_bytes(b, s, run.spec) / bw)
    least += sum(max(m.decode_flops(b, p, run.spec) / fl, m.decode_bytes(b, p, run.spec) / bw)
                 for p in range(s, s + u["new_tokens"] - 1))
    return 100.0 * u["count"] * least / u["window_s"]
