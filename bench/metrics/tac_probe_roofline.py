"""``tac_probe`` (Pallas) against the memory roofline: the least time
its bytes take at the chip's peak bandwidth (the directory row and the
lanes, ``lib/roofline.probe_bytes``, from each op's own shapes), over its
measured device time, in percent.  Counts the probe inside
``fused_step`` programs."""
from metrics.lib.roofline import kernel_of, op_bytes, share_pct


def read(run):
    tr = run.get("trace")
    if tr is None:
        return None
    nbytes, secs = 0, 0.0
    for (mod, op), (n, t) in tr.op_in_module.items():
        if "fused_step" in mod and kernel_of(op) == "tac_probe":
            nbytes += n * op_bytes(op)
            secs += t
    if secs <= 0:
        return None
    return share_pct(nbytes, secs, run["peak"]["hbm_bytes_per_s"])
