"""Model FLOP/s utilisation: the benchmark's own count of the forward
multiply-accumulates of one sample (reference/cnn_ref.macs_per_sample),
times 6 (2 FLOPs a MAC; backward costs twice the forward), times the
samples per second of the traced run's window outside the profiled slice,
over chips times the chip's bf16 peak (peaks.json). The f32 cells multiply
at XLA's default precision, which on a TPU is one bf16 pass with f32
accumulation, so the bf16 peak is their ceiling too. Layer: Net_layers.
Moves train_samples_per_s."""


def compute(run: dict, trace: dict | None):
    rate, peaks = run.get("untraced_samples_per_s"), run.get("peaks")
    if not rate or not peaks:
        return None
    flops = 6 * run["macs_per_sample"] * rate
    share = flops / (run["chips"] * peaks["bf16_flops_per_s"])
    if not 0.0 < share < 1.0:
        raise ValueError(f"MFU {share} is outside (0, 1): the MAC count, "
                         f"the rate or the peak is wrong")
    return 100.0 * share
