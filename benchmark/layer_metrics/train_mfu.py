"""Train step. Model FLOP/s utilisation: trained tokens per second of the
window times the model's FLOPs a token (`costs.train_flops_per_token`,
6 N + 12 L H T, no recomputation counted) over the chip's published bf16
peak."""


def read(rec):
    if not rec.get("tokens") or not rec.get("peaks"):
        return None
    rate = rec["tokens"] / rec["window_s"]
    return 100.0 * rate * rec["flops_per_token"] / rec["peaks"]["bf16_flops_per_s"]
