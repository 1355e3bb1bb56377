"""Decode MFU, %: model FLOPs of the tokens decode steps delivered in the
window over the device time of the decode step programs (device trace)
times the chip's bf16 peak."""
from chipbench.measure import decode_mfu


def read(run):
    return decode_mfu(run)
