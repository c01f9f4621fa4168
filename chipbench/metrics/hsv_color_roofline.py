"""Percent of the hsv_color kernel device time that the histograms of the
real crops it classified need at the chip peak (HBM bound)."""


def read(run):
    return run.roofline("hsv_color")
