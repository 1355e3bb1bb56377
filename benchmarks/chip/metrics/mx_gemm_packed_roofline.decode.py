"""``mx_gemm_packed`` roofline share in decode steps, %: each call's least
time (its operations over the peak or its bytes over HBM bandwidth,
whichever is larger, from the operand shapes in the trace) over its
device time, summed over the calls in the decode step programs."""
from chipbench.measure import gemm_roofline_share


def read(run):
    return gemm_roofline_share(run, "decode")
