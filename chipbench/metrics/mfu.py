"""mfu: model FLOP utilization of the traced window, in percent: training
FLOPs per token from the configuration's shapes (6 N + 12 L H Q T) times
the tokens trained per second, over chips times the bf16 peak of the
``device_kind``.  Moves ``tokens_per_s``."""
from chipbench import peaks


def read(run):
    t0, t1 = run.window
    if not run.walls or t1 <= t0:
        return None
    tokens = len(run.walls) * run.tokens_per_round
    flops = peaks.flops_per_token(run.cell.config, run.cell.mix["seq"])
    return 100.0 * flops * tokens / (t1 - t0) / (
        run.chips * peaks.peak(run.device_kind))
