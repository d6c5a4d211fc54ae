"""mfu.batch: operations the window's engine work needs (real prompt
tokens and the tokens live slots decoded, as the configuration's
architecture counts them: chipbench/archs/<model_type>.py), over the
window's seconds times the chip's bf16 peak (chipbench/peaks.py), in per
cent. The whole model step's share of the peak."""
from chipbench.measures import mfu


def read(run):
    return mfu(run)
