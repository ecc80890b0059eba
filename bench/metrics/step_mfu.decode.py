"""Model FLOPs that the window needs (each batch's prefill and every decode
step, attention over the filled positions only), over the window's length
times the chip's peak bf16 FLOP/s. Decode is bound by bytes, so this share
stays small; decode_step_roofline reads the bound that binds."""


def read(run):
    u = run.record["units"]
    if not u["count"]:
        return None
    m, b, s = run.model, u["batch"], u["prompt_len"]
    per_unit = m.prefill_flops(b, s, run.spec) + sum(
        m.decode_flops(b, p, run.spec) for p in range(s, s + u["new_tokens"] - 1))
    return 100.0 * u["count"] * per_unit / (
        u["window_s"] * run.peaks["flops_per_s"] * run.chips)
