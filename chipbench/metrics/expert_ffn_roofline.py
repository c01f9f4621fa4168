"""Percent of the expert_ffn kernel's device time that the real tokens'
routed assignments need at the chip's peak: their FLOPs, or their rows in
and out and the weights of the experts they hit, whichever bounds."""


def read(run):
    return run.roofline("expert_ffn")
