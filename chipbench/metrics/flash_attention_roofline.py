"""Percent of the flash_attention kernel device time that the attention
of the scored rows real tokens needs at the chip peak."""


def read(run):
    return run.roofline("flash_attention")
