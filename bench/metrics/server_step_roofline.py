"""The server step's share of its roofline.  A step has to move
``flops.server_step_bytes`` through HBM at the least (M gradients, the
parameters and the accumulator in, both out) and to do
``flops.server_step_flops``; the least time the chip could take is the
larger of bytes over HBM bandwidth and operations over the peak, and the
bytes bound it.  The step's time is the summed device time of the
programs that run within the benchmark's ``server_step`` spans in the
traced window: the coefficients and the fused step, whose Pallas kernel
finds its operands in on-chip memory, so that the kernel alone has no
HBM roofline."""
import flops


def read(run):
    if run.trace is None or not run.round_walls:
        return None
    execs = run.trace.programs("server_step")
    if not execs:
        return None
    m = run.cell.traffic["shards_per_round"]
    least = max(
        flops.server_step_bytes(run.cell.program, run.cell.config, m)
        / run.peak("hbm_bytes_per_s"),
        flops.server_step_flops(run.cell.program, run.cell.config, m)
        / run.peak("bf16_flops_per_s"))
    step_s = sum(e.seconds for e in execs)
    return 100.0 * len(run.round_walls) * least / step_s
