"""Model FLOP/s utilization: tokens/s x FLOPs a token REQUIRES (benchlib.
flops, recomputation not counted) over chips x the published bf16 peak.
An end-to-end utilization, not a kernel's roofline share."""


def read(record):
    static = record.get("static", {})
    rate = record.get("end_to_end", {}).get("train_tokens_per_s")
    peaks = static.get("peaks")
    if not (rate and peaks and static.get("flops_per_token")):
        return None
    return 100.0 * rate * static["flops_per_token"] / (
        static["chips"] * peaks["bf16_flops_per_s"])
