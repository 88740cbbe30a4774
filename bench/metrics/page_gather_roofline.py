"""``page_gather`` and ``page_scatter`` (Pallas) against the memory
roofline: the least time for the rows they gather and scatter
(``lib/roofline``, from each op's own shapes) at the chip's peak
bandwidth, over their measured device time, in percent.  Counts the
kernels inside ``fused_step`` programs (B rows each; a read-only step
has no scatter)."""
from metrics.lib.roofline import kernel_of, op_bytes, share_pct


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    nbytes, secs = 0, 0.0
    for (mod, op), (n, t) in tr.op_in_module.items():
        if "fused_step" in mod and \
                kernel_of(op) in ("page_gather", "page_scatter"):
            nbytes += n * op_bytes(op)
            secs += t
    if secs <= 0:
        return None
    return share_pct(nbytes, secs, run["peak"]["hbm_bytes_per_s"])
