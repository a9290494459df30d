"""The whole training step's share of the chip's bf16 peak: the traced
run's samples per second times the forward and backward operations of
one sample (the program module's ``train_flops_per_sample``), over the
chips' peak."""


def read(run):
    if run.trace is None or run.samples <= 0:
        return None
    rate = run.samples / run.window_s
    peak = run.peak("bf16_flops_per_s") * run.cell.chips
    per_sample = run.cell.program.train_flops_per_sample(run.cell.config)
    return 100.0 * rate * per_sample / peak
