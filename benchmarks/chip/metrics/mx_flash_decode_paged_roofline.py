"""``mx_flash_decode_paged`` roofline share, %: the least time of the
decode attention the window's decoded tokens needed (every layer reads
its MXFP8 K and V over the token's context once) over the kernel's device
time in decode steps."""
from chipbench.measure import decode_attention_share


def read(run):
    return decode_attention_share(run)
